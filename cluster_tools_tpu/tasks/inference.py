"""Blockwise CNN inference: boundary/affinity prediction over the mesh.

Re-design of the reference's ``cluster_tools/inference/`` (SURVEY.md §2a,
§3.4): there, each slurm job loaded a PyTorch model onto its GPU and looped
blocks (read block+halo -> normalize -> model -> crop halo -> write C
channels).  Here one driver process runs the flax model batched over the
device mesh through the :class:`BlockwiseExecutor` — the whole forward is a
single jitted SPMD program, blocks sharded across devices, with the same
double-buffered host IO.

Params: ``input_path/input_key`` (raw), ``output_path/output_key``
(multi-channel float32, shape ``(C,) + volume``), ``checkpoint_path``
(flax msgpack or flat npz of params; None -> randomly initialized weights,
recorded as ``<tmp_folder>/<task uid>.weights.npz``), ``model`` config dict
(``name`` + kwargs for :func:`..models.get_model`), ``halo``,
``normalize_percentile`` or fixed ``normalize_range``, ``activation``
('sigmoid'/'softmax'/None).

The outer block (block + 2 halo) is rounded up, per axis, to what the
model's input extents must be multiples of (``input_multiple()``, else
``2**depth``).  Spans (docs/OBSERVABILITY.md "Inference"):
``inference.weights`` (made or read, ``params``, ``nbytes``) and
``inference.pass`` around the sweep (``lanes``, ``dispatches``,
``outer_shape``, ``outer_voxels``, ``padded_voxels``, ``inner_voxels``,
``flops_per_lane``, ``flops``); the same counts go into the manifest and ``io_metrics.json``
(there the FLOPs as the total only).

The weights are the sweep's replicated operand
(``BlockwiseExecutor.map_blocks(..., replicated_operand=...)``): the kernel
receives them as its first argument and captures only plain values (the
model's name and config, the activation), so its sharded program is kept
across jobs by ``parallel/step_cache.py`` and serves every checkpoint of
the same architecture; the manifest's ``step_cache`` says where the job's
program came from (process, store or built).  A ``model: "auto"`` kernel
captures its imported module, which does not freeze, and keeps its
executor's own cache.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict

import numpy as np
import jax
import jax.numpy as jnp

from ..runtime import trace as trace_mod
from ..runtime.executor import (
    BlockwiseExecutor,
    is_sub_block,
    region_verifier,
)
from ..runtime.task import BaseTask, WorkflowBase, get_task_cls
from ..utils.volume_utils import Blocking, blocks_in_volume, file_reader, pad_block_to


def load_checkpoint(path: str, model, sample_shape):
    """Load params: ``.msgpack`` (flax.serialization), ``.npz`` (flat
    '/'-joined keys), or ``.pt``/``.pth`` (torch state_dict, converted —
    see :mod:`cluster_tools_tpu.models.torch_import`)."""
    import flax

    if path.endswith((".pt", ".pth")):
        from ..models.torch_import import load_torch_checkpoint

        return load_torch_checkpoint(path, model, sample_shape)
    if path.endswith(".npz"):
        import flax.traverse_util as tu

        with np.load(path) as f:
            flat = {tuple(k.split("/")): f[k] for k in f.files}
        if next(iter(flat))[0] != "params":
            flat = {("params",) + k: v for k, v in flat.items()}
        return tu.unflatten_dict(flat)
    with open(path, "rb") as f:
        return flax.serialization.from_bytes(
            init_params(model, sample_shape), f.read()
        )


def init_params(model, sample_shape):
    """Random weights from ``PRNGKey(0)``, made under ``jit`` from an input
    of ``sample_shape`` (the params' shapes do not depend on its extent, so
    callers pass the smallest the model accepts)."""
    return jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros(sample_shape, jnp.float32)
    )


def save_checkpoint(path: str, params) -> None:
    """Save flax params as flat npz (portable, no pickle)."""
    import flax.traverse_util as tu

    flat = tu.flatten_dict(params)
    np.savez(path, **{"/".join(map(str, k)): np.asarray(v) for k, v in flat.items()})


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def input_multiple(model, ndim: int) -> tuple:
    """What the model's input extents must be multiples of, per axis."""
    if hasattr(model, "input_multiple"):
        return tuple(int(m) for m in model.input_multiple())
    return (2 ** int(getattr(model, "depth", 0)),) * ndim


class InferenceBase(BaseTask):
    """Blockwise model prediction (reference: ``InferenceBase``)."""

    task_name = "inference"
    #: the sweep's counts, in io_metrics.json too (they add up over a
    #: resumed run's passes, so the FLOPs go there as a total)
    io_metrics_keys = (
        "n_blocks", "lanes", "dispatches", "outer_voxels", "padded_voxels",
        "inner_voxels", "flops",
    )

    @staticmethod
    def default_task_config():
        return {
            "threads_per_job": 1,
            "device_batch": 1,
            "halo": [8, 8, 8],
            "model": {"name": "unet3d", "out_channels": 1},
            "checkpoint_path": None,
            "activation": "sigmoid",
            "normalize_percentile": None,
            "normalize_range": None,
        }

    def run_impl(self):
        from ..runtime import handoff

        cfg = self.get_config()
        inp = handoff.resolve_dataset(cfg["input_path"], cfg["input_key"])
        shape = inp.shape
        block_shape = tuple(cfg["block_shape"])
        halo = tuple(cfg.get("halo") or [0] * len(shape))
        from ..models import get_model  # lazy: flax only needed here

        model_cfg: Dict[str, Any] = dict(cfg.get("model") or {})
        model_name = model_cfg.pop("name", "unet3d")
        ckpt = cfg.get("checkpoint_path")
        variables = None
        if model_name == "auto":
            # "bring your own torch U-Net": architecture inferred from the
            # checkpoint's tensor census, no hand-written model config
            if not ckpt:
                raise ValueError(
                    "model name 'auto' infers the architecture from a "
                    "torch checkpoint — set checkpoint_path to a .pt/.pth"
                )
            from ..models.torch_import import import_torch_unet

            # remaining model-config keys override the inferred
            # architecture (e.g. dtype, norm)
            model, variables = import_torch_unet(ckpt, **model_cfg)
        else:
            model = get_model(model_name, **model_cfg)
        out_channels = getattr(model, "out_channels", 1)
        mult = input_multiple(model, len(shape))

        # static kernel shape: outer block rounded up to the model's multiple
        outer = tuple(
            _round_up(b + 2 * h, m) for b, h, m in zip(block_shape, halo, mult)
        )
        smallest = (1,) + mult + (1,)
        weights_path = None
        with trace_mod.span("inference.weights", task=self.uid) as weights_span:
            if variables is not None:
                source = "torch"  # imported together with the model above
            elif ckpt:
                source = "checkpoint"
                variables = load_checkpoint(ckpt, model, smallest)
            else:
                source = "init"
                self.logger.info("no checkpoint_path: random weights, recorded")
                variables = init_params(model, smallest)
                # recorded as upstream leaves its problem, for inspection
                weights_path = os.path.join(self.tmp_folder, f"{self.uid}.weights.npz")
                save_checkpoint(weights_path, variables)
            leaves = jax.tree_util.tree_leaves(variables)
            n_params = sum(int(np.prod(a.shape)) for a in leaves)
            nbytes = sum(int(a.nbytes) for a in leaves)
            weights_span.note(source=source, params=n_params, nbytes=nbytes,
                              path=weights_path)

        # MemoryTarget output (docs/PERFORMANCE.md "Task-graph fusion"):
        # the probability map stays in RAM for a downstream watershed /
        # thresholding consumer, spilling to this path under the ladder
        out = self.handoff_dataset(
            cfg["output_path"], cfg["output_key"],
            shape=(out_channels,) + shape,
            chunks=(1,) + block_shape,
            dtype="float32",
        )
        blocking = Blocking(shape, block_shape)
        block_ids = blocks_in_volume(
            shape, block_shape, cfg.get("roi_begin"), cfg.get("roi_end")
        )
        done = set(self.blocks_done())
        blocks_all = [blocking.get_block(b, halo) for b in block_ids]
        todo = [b for b in blocks_all if b.block_id not in done]

        pct = cfg.get("normalize_percentile")
        rng_norm = cfg.get("normalize_range")
        activation = cfg.get("activation", "sigmoid")

        allow_split = bool(cfg.get("allow_block_split", False))
        if allow_split and rng_norm is None:
            # per-block normalization statistics change with the read
            # region, so a split sub-block would be normalized differently
            # from its unsplit parent — only a fixed range is split-safe
            raise ValueError(
                "allow_block_split=True requires normalize_range "
                "(per-block percentile/min-max normalization is not "
                "split-safe)"
            )

        def load(block):
            data = np.asarray(inp[block.outer_bb]).astype(np.float32)
            if rng_norm is not None:
                lo, hi = float(rng_norm[0]), float(rng_norm[1])
            elif pct is not None:
                lo, hi = np.percentile(data, [100 - pct, pct])
            else:
                lo, hi = float(data.min()), float(data.max())
            data = (data - lo) / max(hi - lo, 1e-6)
            if is_sub_block(block):
                # degrade-split fragment: pad to its OWN U-Net multiple —
                # the smaller allocation is the point of the split (it
                # never enters a stacked batch, so the static shape does
                # not apply)
                target = tuple(_round_up(s, m) for s, m in zip(data.shape, mult))
            else:
                target = outer
            return (pad_block_to(data, target)[..., None],)

        if model_name == "auto":
            # the module inferred from the checkpoint is captured: it does
            # not freeze, so the program stays in the executor's own cache
            def make_model():
                return model
        else:
            # plain values only: the kernel's identity freezes, so its
            # sharded program is kept across jobs; building the module
            # under trace costs nothing on the device
            make_model = functools.partial(get_model, model_name, **model_cfg)

        def predict_block(params, x):
            logits = make_model().apply(params, x[None])[0]
            if activation == "sigmoid":
                y = jax.nn.sigmoid(logits)
            elif activation == "softmax":
                y = jax.nn.softmax(logits, axis=-1)
            else:
                y = logits
            return jnp.moveaxis(y, -1, 0)  # -> (C, z, y, x)

        def store(block, raw):
            rel = block.inner_in_outer_bb
            out[(slice(None),) + block.bb] = np.asarray(raw)[(slice(None),) + rel]

        executor = BlockwiseExecutor(
            target=self.target,
            device_batch=int(cfg.get("device_batch", 1)),
            io_threads=int(cfg.get("io_threads") or max(1, self.max_jobs)),
            max_retries=int(cfg.get("io_retries", 2)),
            backoff_base=float(cfg.get("io_backoff_s", 0.05)),
        )
        # float probability outputs: the executor's built-in NaN/inf check
        # quarantines any block a bad kernel or checkpoint corrupts
        with trace_mod.span(
            "inference.pass", task=self.uid, n_blocks=len(todo),
            outer_shape=list(outer),
        ) as pass_span:
            summary = executor.map_blocks(
                predict_block,
                blocks_all,
                load,
                store,
                on_block_done=lambda b: self.log_block_success(b.block_id),
                done_block_ids=done,
                failures_path=self.failures_path,
                task_name=self.uid,
                block_deadline_s=cfg.get("block_deadline_s"),
                watchdog_period_s=cfg.get("watchdog_period_s"),
                store_verify_fn=region_verifier(
                    out, bb_of=lambda b: (slice(None),) + b.bb
                ),
                schedule=str(cfg.get("block_schedule") or "morton"),
                sweep_mode=str(cfg.get("sweep_mode") or "auto"),
                sharded_batch=cfg.get("sharded_batch"),
                device_pool=str(cfg.get("device_pool") or "auto"),
                device_pool_bytes=cfg.get("device_pool_bytes"),
                # opt-in OOM split (config allow_block_split): the conv kernel
                # is shape-local, so sub-block outputs tile the parent's region
                # exactly when halo covers the receptive field and the
                # normalization range is fixed (enforced above)
                splittable=allow_split,
                split_halo=halo,
                min_block_shape=cfg.get("min_block_shape"),
                degrade_wait_s=float(cfg.get("degrade_wait_s", 5.0)),
                inflight_byte_budget=cfg.get("inflight_byte_budget"),
                replicated_operand=variables,
            )
            # every lane computes the whole padded outer block, padding
            # lanes included (padded_voxels, as the watershed's ws.pass)
            lanes = len(todo) + int(summary.get("n_lanes_padded", 0))
            counts = {
                "lanes": lanes,
                "dispatches": int(summary.get("n_dispatches", 0)),
                "outer_voxels": len(todo) * int(np.prod(outer)),
                "padded_voxels": lanes * int(np.prod(outer)),
                "inner_voxels": sum(int(np.prod(b.shape)) for b in todo),
            }
            if hasattr(model, "forward_flops"):
                counts["flops_per_lane"] = int(model.forward_flops(outer))
                counts["flops"] = counts["lanes"] * counts["flops_per_lane"]
            pass_span.note(**counts)
        return {
            "n_blocks": len(todo),
            "out_channels": int(out_channels),
            "model": model_name,
            "outer_shape": list(outer),
            "weights": {"source": source, "params": n_params, "path": weights_path},
            # where the sweep's program came from (process, store or built;
            # parallel/step_cache.py), None where the executor kept it in
            # its own cache
            "step_cache": summary.get("program"),
            **counts,
        }


class InferenceLocal(InferenceBase):
    target = "local"


class InferenceTPU(InferenceBase):
    target = "tpu"


class InferenceWorkflow(WorkflowBase):
    task_name = "inference_workflow"

    def requires(self):
        from . import inference as inf_mod

        return [
            get_task_cls(inf_mod, "Inference", self.target)(
                tmp_folder=self.tmp_folder,
                config_dir=self.config_dir,
                max_jobs=self.max_jobs,
                dependencies=self.dependencies,
                **self.params,
            )
        ]
