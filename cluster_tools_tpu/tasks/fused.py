"""The fused mesh-resident segmentation step as a task-library citizen.

The blockwise watershed/CC task chains (SURVEY.md §3.2/§3.5) exist for
volumes larger than device memory; when the working ROI *fits* in HBM, five
tasks and thousands of chunk round-trips collapse into ONE compiled SPMD
program — the same fused step the benchmark measures
(:func:`cluster_tools_tpu.parallel.pipeline.make_ws_ccl_step`: halo exchange
over ICI, per-shard DT watershed, cross-shard fragment stitch and
union-find CC merge as collectives).  This task is the workflow-API bridge
to that fast path: read the ROI, run the step over the device mesh, write
``ws``/``cc`` labels back blockwise.

The reference has no analogue — its runtime cannot express "one program
over many nodes" at all; this is where the TPU-first redesign pays off
directly through the same task/config machinery users already drive.
"""

from __future__ import annotations

import numpy as np

from ..ops import work
from ..runtime.task import BaseTask, WorkflowBase, get_task_cls
from ..utils.volume_utils import file_reader


def collective_bytes(roi_shape, grid, halo):
    """What one job's collectives move, reckoned from the shapes the step
    is built for (nothing is read back from a device): ``cuts``, the faces
    between neighbouring shards; ``halo_bytes``, the float32 planes that
    cross them in the halo exchange, both ways, all cuts together;
    ``gathered_pair_bytes``, the label-pair list of the cross-shard merge
    as each device holds it after the ``all_gather`` (the deduped branch of
    ``merge_labels_by_pairs``).  ``grid`` holds the mesh sizes over the
    leading volume axes."""
    from ..parallel.distributed_ccl import default_pair_cap

    n_shards = int(np.prod(grid))
    local = [s // g for s, g in zip(roi_shape, grid)] + list(roi_shape[len(grid):])
    cuts = sum((g - 1) * n_shards // g for g in grid)
    # as exchange_all: a later axis forwards the halos an earlier one received
    padded, halo_voxels = list(local), 0
    for a, g in enumerate(grid):
        slab = halo * int(np.prod(padded)) // padded[a]
        halo_voxels += 2 * (g - 1) * (n_shards // g) * slab
        padded[a] += 2 * halo
    pair_rows = 0
    if n_shards > 1:
        faces = sum(int(np.prod(local)) // local[a] for a in range(len(grid)))
        pair_rows = n_shards * min(faces, default_pair_cap(faces))
    return {"cuts": int(cuts), "halo_bytes": 4 * int(halo_voxels),
            "gathered_pair_bytes": 8 * int(pair_rows)}


class FusedSegmentationBase(BaseTask):
    """Whole-ROI fused watershed + merged CC on the device mesh.

    Params: ``input_path/input_key`` (boundary map), ``output_path`` +
    ``ws_key``/``cc_key`` (either may be omitted to skip that output).
    Config: ``threshold``, ``halo``, ``dt_max_distance``,
    ``min_seed_distance``, ``stitch_ws_threshold``, ``exact_edt``,
    ``max_labels_per_shard``, ``impl``, ``decomposition`` — the
    fused-pipeline knobs; ``decomposition="grid"`` shards the ROI over z
    AND y instead of z-slabs.  ``execution="split"`` runs the step as the
    four-program staged chain (``parallel.split_pipeline``) instead of the
    fused monolith — bit-identical outputs, per-program compile cost in
    the tiled-CCL class; the mode for backends where the monolith's
    compile time, not runtime, is the binding constraint.

    The ROI must fit in device memory (sharded over the mesh); this task
    refuses inputs whose sharded extents (z; plus y for "grid") do not
    divide over the spatial mesh axes.
    """

    task_name = "fused_segmentation"
    #: the shards' work records (docs/OBSERVABILITY.md "The work record")
    io_metrics_keys = ("work",)

    @staticmethod
    def default_task_config():
        return {
            "threads_per_job": 1,
            "threshold": 0.25,
            "halo": 4,
            "dt_max_distance": None,
            "min_seed_distance": 0.0,
            "stitch_ws_threshold": None,
            "exact_edt": False,
            "max_labels_per_shard": None,
            "impl": "auto",
            # "slab" shards z only; "grid" factors the devices over z AND y
            # (the 2-axis spatial decomposition) — both extents must divide
            "decomposition": "slab",
            # "fused" = one compiled program; "split" = the staged
            # four-program chain (same outputs, compile-cap friendly)
            "execution": "fused",
        }

    def run_impl(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from ..ops.tile_ws import resolved_modes
        from ..parallel import step_cache
        from ..parallel.mesh import describe_devices, device_peak_bytes
        from ..runtime import handoff
        from ..runtime import trace as trace_mod

        # the job's host phases as spans (docs/OBSERVABILITY.md "The fused
        # job"): setup, read, dispatch, wait, then d2h / widen / write per
        # output; all the shared null span with the tracer off
        with trace_mod.span("fused.setup") as sp:
            cfg = self.get_config()
            # fusable input edge: a live in-memory boundary-map handle is
            # consumed without a storage read
            inp = handoff.resolve_dataset(cfg["input_path"], cfg["input_key"])
            shape = inp.shape
            roi_begin = tuple(cfg.get("roi_begin") or (0,) * len(shape))
            roi_end = tuple(cfg.get("roi_end") or shape)
            roi = tuple(slice(b, e) for b, e in zip(roi_begin, roi_end))
            roi_shape = tuple(e - b for b, e in zip(roi_begin, roi_end))
            builder, build_args, mesh, sp_desc, execution = self._build_step(
                cfg, roi_shape)
            sp.note(execution=execution, mesh=sp_desc)
        halo = int(np.max(cfg.get("halo") or 0))
        self.logger.info(
            f"{execution} step on mesh {sp_desc}, roi {roi_shape}, "
            f"halo={halo}; "
            f"mesh.devices={describe_devices(mesh.devices)}; "
            f"kernels={resolved_modes(build_args['impl'])}"
        )
        with trace_mod.span("fused.read") as sp:
            vol = np.asarray(inp[roi]).astype(np.float32)
            sp.note(nbytes=int(vol.nbytes))
        # the input onto the step's own input sharding (batch over dp, the
        # leading volume axes over the spatial mesh axes), one shard a
        # device, so that the copy has a span of its own
        in_sharding = NamedSharding(mesh, PartitionSpec(*mesh.axis_names))
        with trace_mod.span("fused.h2d", nbytes=int(vol.nbytes)) as sp:
            x = jax.block_until_ready(jax.device_put(vol[None], in_sharding))
            sp.note(shards=len(x.addressable_shards))
        del vol
        # the call up to its return: the step looked up under the key of
        # what it is built from (process, then store), loaded or built,
        # then enqueued; only the one-program step goes to the store
        fun_name = "ws_ccl_step" if execution == "fused" else "ws_ccl_split"
        with trace_mod.span("fused.dispatch", fun_name=fun_name):
            step, step_info = step_cache.step_for(
                mesh, x, execution, builder, build_args)
            out = step(x)
        del x
        self.logger.info(f"step_cache={step_info}")
        # the shards' work records come back with the labels, on every job:
        # a few hundred bytes, one dict a shard (docs/OBSERVABILITY.md "The
        # work record")
        with trace_mod.span("fused.wait") as sp:
            ws, cc, n_fg, overflow, records = jax.block_until_ready(out)
            records = work.unpack(records)
            sp.note(work=records)
        if bool(np.asarray(overflow)):
            tripped = [
                f"shard {i}: {line}"
                for i, rec in enumerate(records) for line in work.tripped(rec)
            ]
            raise RuntimeError(
                "fused step overflowed a capacity (" + "; ".join(tripped)
                + "). Of these only max_labels_per_shard is an option of "
                "this task: the others follow the shard's shape, so shard "
                "the ROI over more devices, or use the blockwise task chain, "
                "where each is an option"
            )

        out_f = file_reader(cfg["output_path"])
        block_shape = tuple(cfg["block_shape"])
        written = {}
        for key_cfg, data in (("ws_key", ws), ("cc_key", cc)):
            key = cfg.get(key_cfg)
            if not key:
                continue
            with trace_mod.span("fused.d2h", output=key,
                                shards=len(data.addressable_shards)) as sp:
                arr = np.asarray(data[0])
                sp.note(nbytes=int(arr.nbytes))
            with trace_mod.span("fused.widen", output=key) as sp:
                arr = arr.astype(np.uint64)
                sp.note(nbytes=int(arr.nbytes))
            with trace_mod.span("fused.write", output=key,
                                nbytes=int(arr.nbytes)):
                ds = out_f.require_dataset(
                    key, shape=shape, chunks=block_shape, dtype="uint64"
                )
                # the whole ROI is already host-resident: one sliced write
                ds[roi] = arr
            written[key] = int(arr.max())
        return {
            # float32 psum: exact below 2**24 per shard; round-to-nearest
            # (not truncate) so a 1-ulp-low representation can't report
            # off-by-one.  Counts past 2**24 are approximate by design.
            "n_foreground": int(round(float(np.asarray(n_fg)))),
            "mesh": sp_desc,
            "written": written,
            "collectives": collective_bytes(
                roi_shape, mesh.devices.shape[1:], halo),
            "device_memory": device_peak_bytes(mesh.devices),
            "step_cache": dict(step_info, totals=step_cache.totals()),
            "work": records,
        }

    def _build_step(self, cfg, roi_shape):
        """The mesh over the task's devices and what the step for it is
        built from: ``(builder, build_args, mesh, mesh description,
        execution)``; ``builder(mesh, **build_args)`` makes the step, and
        the step's key holds every one of ``build_args``."""
        from ..parallel.mesh import backend_devices, make_mesh
        from ..parallel.pipeline import make_ws_ccl_step
        from ..parallel.split_pipeline import make_ws_ccl_split

        if len(roi_shape) != 3:
            raise ValueError(f"fused segmentation is 3-D only, got {roi_shape}")

        # one ROI = batch of 1: every device of the task's target goes to
        # the spatial axes (the same rule as the blockwise executor, so
        # target="tpu" with no TPU raises here too)
        devices = backend_devices(self.target)
        n_dev = len(devices)
        decomposition = str(cfg.get("decomposition", "slab"))
        if decomposition == "grid" and n_dev > 1:
            # factor devices over z and y, z getting the larger share
            sy = next(
                d for d in range(int(n_dev**0.5), 0, -1) if n_dev % d == 0
            )
            sz = n_dev // sy
            mesh = make_mesh(
                axis_names=("dp", "spz", "spy"), grid=(1, sz, sy),
                devices=devices,
            )
            sp_axis = ("spz", "spy")
            divides = (roi_shape[0] % sz == 0) and (roi_shape[1] % sy == 0)
            sp_desc = f"spz={sz} spy={sy}"
        elif decomposition in ("slab", "grid"):
            mesh = make_mesh(
                axis_names=("dp", "sp"), grid=(1, n_dev), devices=devices
            )
            sp_axis = "sp"
            divides = roi_shape[0] % n_dev == 0
            sp_desc = f"sp={n_dev}"
        else:
            raise ValueError(
                f"decomposition must be 'slab' or 'grid', got {decomposition!r}"
            )
        if not divides:
            raise ValueError(
                f"ROI extents {roi_shape} do not divide over the spatial "
                f"mesh axes ({sp_desc})"
            )

        halo = int(np.max(cfg.get("halo") or 0))
        dt_max = cfg.get("dt_max_distance")
        if dt_max is None and halo and not cfg.get("exact_edt"):
            # per-shard EDT is halo-capped by default (blockwise reference
            # semantics); with exact_edt, None means truly global radii —
            # the saturation exact_edt exists to remove must stay removable
            dt_max = float(halo)
        execution = str(cfg.get("execution", "fused"))
        if execution not in ("fused", "split"):
            raise ValueError(
                f"execution must be 'fused' or 'split', got {execution!r}"
            )
        builder = make_ws_ccl_step if execution == "fused" else make_ws_ccl_split
        build_args = dict(
            halo=halo,
            threshold=float(cfg["threshold"]),
            sp_axis=sp_axis,
            dt_max_distance=dt_max,
            min_seed_distance=float(cfg.get("min_seed_distance") or 0.0),
            max_labels_per_shard=cfg.get("max_labels_per_shard"),
            impl=str(cfg.get("impl", "auto")),
            exact_edt=bool(cfg.get("exact_edt", False)),
            stitch_ws_threshold=cfg.get("stitch_ws_threshold"),
        )
        return builder, build_args, mesh, sp_desc, execution


class FusedSegmentationLocal(FusedSegmentationBase):
    target = "local"


class FusedSegmentationTPU(FusedSegmentationBase):
    target = "tpu"


class FusedSegmentationWorkflow(WorkflowBase):
    """One-task workflow wrapper so the CLI/registry can launch it."""

    task_name = "fused_segmentation_workflow"

    def requires(self):
        from . import fused as fused_mod

        return [
            get_task_cls(fused_mod, "FusedSegmentation", self.target)(
                tmp_folder=self.tmp_folder,
                config_dir=self.config_dir,
                max_jobs=self.max_jobs,
                dependencies=self.dependencies,
                **self.params,
            )
        ]

    def run_impl(self):
        # surface the inner task's output stats in the workflow's own
        # success manifest — failures_report and operators read the
        # workflow manifest, and a bare {} hid what the fused path wrote
        try:
            doc = self.requires()[0].output().read()
        except OSError:
            return {}
        return {
            k: doc[k]
            for k in ("n_foreground", "written", "mesh", "collectives",
                      "step_cache")
            if k in doc
        }
