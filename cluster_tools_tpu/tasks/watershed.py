"""Blockwise distance-transform watershed tasks (single- and two-pass).

Re-design of the reference's ``cluster_tools/watershed/`` (SURVEY.md §2a
"watershed", §3.5): per-block DT watershed with halo, labels offset for
global uniqueness, and the two-pass checkerboard variant where pass-two
blocks seed from already-labeled pass-one neighbors — cross-block-consistent
labels without a separate stitching task.

TPU shape: the fused kernel (threshold -> EDT -> seeds -> watershed, one
compiled program) is vmapped over a block batch and sharded over the mesh by
the :class:`~cluster_tools_tpu.runtime.executor.BlockwiseExecutor`; the halo
comes from overlapping host reads at ingress (the mesh-resident sharded
variant lives in ``parallel/pipeline.py``).

Label encoding: ``global = block_id * (n_outer + 1) + local`` (uint64), where
``local`` is the kernel's flat-index label within the static outer block —
globally unique by construction, made dense by the relabel workflow.
"""

from __future__ import annotations

import os
from typing import List, Optional

import numpy as np

from ..ops import work
from ..ops.watershed import (
    distance_transform_watershed,
    dt_watershed_seeded,
    filter_small_segments,
)
from ..runtime.executor import (
    BlockwiseExecutor,
    region_verifier,
    validate_labels,
)
from ..parallel.mesh import device_peak_bytes
from ..runtime import trace as trace_mod
from ..runtime.task import BaseTask, WorkflowBase, get_task_cls
from ..utils import function_utils as fu
from ..utils.volume_utils import (
    Blocking,
    blocks_in_volume,
    file_reader,
    pad_block_to,
)

import jax
import jax.numpy as jnp


def _tiled_cap_knobs(cfg):
    """Config-set capacity knobs for the tiled kernels (None = omit, the
    ops-level defaults apply).  Only meaningful for ``impl != 'legacy'``;
    raise the reported-overflow one, rerun the failed blocks."""
    return {
        k: int(cfg[k])
        for k in ("exit_cap", "fill_cap", "adj_cap", "fill_rounds",
                  "table_cap", "pair_cap", "edge_cap")
        if cfg.get(k) is not None
    }


def _tiled_kwargs(kp, cfg):
    """The tiled kernels' keyword arguments: the kernel parameters but
    ``connectivity``, and the capacity knobs.  Made outside the kernels, so
    that they capture plain values and not the task's config (whose output
    key differs every job): a kernel's identity is then equal from job to
    job wherever what it reads is, and the executor keeps its sweep
    program across jobs (``runtime/executor.py::kernel_identity``)."""
    tk = {k: v for k, v in kp.items() if k != "connectivity"}
    tk.update(_tiled_cap_knobs(cfg))
    return tk


def _outer_shape(block_shape, halo):
    return tuple(b + 2 * h for b, h in zip(block_shape, halo))


def _refuse_checkerboard_hybrids(cfg):
    """What the two-pass checkerboard cannot combine, refused in one place:
    the workflow asks before pass one burns hours on even blocks, the two
    tasks ask again because each can be run on its own."""
    if cfg.get("two_d"):
        # pass one would be segmented per slice and pass two in 3-D
        raise NotImplementedError(
            "two_d=True is not supported with the two-pass watershed; use "
            "the single-pass watershed for per-slice segmentation"
        )
    if cfg.get("agglomerate_threshold") is not None:
        # pass-two labels carry immutable external seed ids from pass one;
        # merging either pass blockwise would desynchronize the shared
        # label space: agglomerate on the single-pass task instead
        raise NotImplementedError(
            "agglomerate_threshold is not supported with the two-pass "
            "watershed (pass_parity / two_pass=True)"
        )


def _validate_block_labels(block, out):
    """:func:`validate_labels` on a kernel's labels and flag; its work
    record holds -1 for what the program did not count."""
    return validate_labels(block, out[:2])


def _warn_overflow(logger, block, rec):
    """Capacity-truncated labels are under-merged: never silent, and named
    (``ops/work.py::tripped``) so that the right knob is raised."""
    logger.warning(
        f"block {block.block_id} overflowed a tiled-watershed capacity ("
        + ("; ".join(work.tripped(rec)) or "none recorded")
        + "); labels may be under-merged (raise that cap in the task's "
        "config, or use impl=legacy)"
    )


def _pass_counters(summary, blocks, outer, use_tiled, overflow_blocks,
                   records, two_d=False):
    """What one sweep of the watershed computed, for the task's manifest and
    its ``ws.pass`` span (docs/OBSERVABILITY.md "Two-pass watershed"): the
    blocks it ran, its dispatches, the lanes that carried no block, and the
    voxels computed (every lane at the kernel's tile-padded outer shape)
    beside those of the outer blocks and of the inner blocks it stored;
    ``two_d`` and ``planes``, the planes flooded one by one (every lane's
    tile-padded planes, 0 for a 3-D flood); and
    ``work``, the real lanes' work records as one (``ops/work.py::total``;
    ``records``: block id -> the lane's record, which the span keeps); and
    ``step_cache``, where the sweep's program came from (process, store or
    built; ``parallel/step_cache.py``), None where the executor kept it in
    its own cache."""
    padded = outer
    if use_tiled:
        from ..ops.tile_ws import _ws_static_plan

        padded = _ws_static_plan(outer, None, None, None)[1]
    n_blocks = len(blocks)
    lanes_padded = int(summary.get("n_lanes_padded", 0))
    return {
        "n_blocks": n_blocks,
        "dispatches": int(summary.get("n_dispatches", 0)),
        "lanes_padded": lanes_padded,
        "two_d": bool(two_d),
        "planes": (n_blocks + lanes_padded) * int(padded[0]) if two_d else 0,
        "padded_voxels": (n_blocks + lanes_padded) * int(np.prod(padded)),
        "outer_voxels": n_blocks * int(np.prod(outer)),
        "inner_voxels": sum(int(np.prod(b.shape)) for b in blocks),
        "overflow_blocks": sorted(overflow_blocks),
        "work": work.total(records.values()),
        "step_cache": summary.get("program"),
    }


class _WsTaskBase(BaseTask):
    """Shared machinery for the watershed task family."""

    @staticmethod
    def default_task_config():
        return {
            "threads_per_job": 1,
            "device_batch": 1,
            "threshold": 0.25,
            "sigma_seeds": 0.0,
            "min_seed_distance": 0.0,
            "sampling": None,
            "size_filter": 0,
            # mean-boundary threshold for in-block fragment agglomeration
            # after the flood (reference: watershed/agglomerate.py); None
            # disables.  Fragments whose contact's size-weighted mean
            # boundary value is below the threshold merge (average linkage).
            "agglomerate_threshold": None,
            "two_d": False,
            "connectivity": 1,
            "halo": [4, 4, 4],
            # EDT cap in physical (sampling) units; None derives it from the
            # halo.  Uncapped, a >160-extent block selects the O(n^2)
            # broadcast min-plus and allocates an (.., n, n) intermediate —
            # the cap keeps the erosion cascade O(cap) per axis, and
            # distances beyond the halo scale are meaningless blockwise
            # anyway (SURVEY.md §7 hard part 5).
            "dt_max_distance": None,
            # watershed kernel: "auto" (two-level tile machinery — saddle-
            # union fill respects ridge heights; the synthetic-EM validation
            # measured 6.5% fragment impurity vs 35% for the legacy ring
            # fill, which can adopt labels THROUGH membranes), "legacy"
            # (round-2 dense fixpoint), or explicit "pallas"/"xla".  2-D
            # mode floods each z-plane on the tiled kernels too (their
            # planar form); connectivity != 1 always uses legacy.  Honored
            # by both the single-pass and the two-pass (externally seeded)
            # tasks.
            "impl": "auto",
            # tiled-kernel capacity knobs (None = the ops-level defaults;
            # ignored by the legacy kernel).  Raise on overflow reports:
            # exit/fill/adj govern the cross-tile exit and saddle-fill
            # buffers, fill_rounds the Boruvka round count, table_cap the
            # VMEM remap tables, pair/edge_cap the seed CCL's face merge.
            "exit_cap": None,
            "fill_cap": None,
            "adj_cap": None,
            "fill_rounds": None,
            "table_cap": None,
            "pair_cap": None,
            "edge_cap": None,
        }

    def _setup(self):
        from ..runtime import handoff

        cfg = self.get_config()
        # fusable input edge (inference -> watershed): a live in-memory
        # handoff from the producing task is consumed directly; otherwise
        # this is the plain storage dataset
        inp = handoff.resolve_dataset(cfg["input_path"], cfg["input_key"])
        shape = inp.shape
        block_shape = tuple(cfg["block_shape"])
        halo = tuple(cfg.get("halo") or [0] * len(shape))
        blocking = Blocking(shape, block_shape)
        block_ids = blocks_in_volume(
            shape, block_shape, cfg.get("roi_begin"), cfg.get("roi_end")
        )
        # MemoryTarget output (docs/PERFORMANCE.md "Task-graph fusion"):
        # with memory_handoffs on, the label volume stays in host RAM for
        # the graph/features/write consumers, spilling to this storage
        # path under the degrade ladder; off, this IS the storage dataset
        out = self.handoff_dataset(
            cfg["output_path"], cfg["output_key"],
            shape=shape, chunks=block_shape, dtype="uint64",
        )
        mask_ds = None
        if cfg.get("mask_path"):
            mask_ds = file_reader(cfg["mask_path"])[cfg["mask_key"]]
        return cfg, inp, out, mask_ds, shape, block_shape, halo, blocking, block_ids

    def _kernel_params(self, cfg):
        sampling = cfg.get("sampling")
        dt_max = cfg.get("dt_max_distance")
        if dt_max is None:
            # halo-derived default with a floor of 8.  Trade-off: the capped
            # EDT saturates object interiors thicker than 2x the cap into
            # one constant plateau, so two thick bodies joined by an equally
            # thick neck collapse to a single seed (uncapped they could
            # separate).  Uncapped, a >160-extent block instead selects the
            # O(n^2) broadcast min-plus and allocates tens of GB.  Workloads
            # with very thick objects should set dt_max_distance explicitly
            # above the object radius.
            halo = cfg.get("halo") or [0]
            samp = sampling or [1.0] * len(halo)
            dt_max = max(8.0, max(float(h) * float(s) for h, s in zip(halo, samp)))
        return dict(
            threshold=float(cfg["threshold"]),
            sigma_seeds=float(cfg.get("sigma_seeds") or 0.0),
            min_seed_distance=float(cfg.get("min_seed_distance") or 0.0),
            sampling=None if sampling is None else tuple(sampling),
            connectivity=int(cfg.get("connectivity", 1)),
            dt_max_distance=float(dt_max),
        )

    @staticmethod
    def _agglomerate_block(lab: np.ndarray, bnd: np.ndarray, threshold: float):
        """In-block average-linkage merge of WS fragments (reference:
        ``watershed/agglomerate.py``): fragments whose contact's
        size-weighted mean boundary value is below ``threshold`` fuse.

        Runs on the padded-outer labels so halo context participates, like
        the reference's in-block agglomeration.  Isolated fragments (no RAG
        edge) keep distinct ids.  Single-pass blocks only: two-pass labels
        carry immutable external seed ids that must not merge blockwise.
        """
        from ..ops.agglomeration import average_agglomeration
        from ..ops.rag import block_rag

        lab = np.ascontiguousarray(lab)
        uv, sizes, feats = block_rag(lab.astype(np.uint64), bnd)
        if len(uv) == 0:
            return lab
        nodes = np.unique(uv).astype(np.int64)
        remap = np.zeros(int(nodes.max()) + 1, np.int64)
        remap[nodes] = np.arange(len(nodes))
        merged = average_agglomeration(
            len(nodes), remap[uv.astype(np.int64)], feats[:, 0], sizes, threshold
        )
        all_labels = np.unique(lab[lab > 0]).astype(np.int64)
        table = np.zeros(int(all_labels.max()) + 1, lab.dtype)
        table[nodes] = (merged + 1).astype(lab.dtype)
        iso = np.setdiff1d(all_labels, nodes, assume_unique=True)
        k = int(merged.max()) + 1 if len(merged) else 0
        table[iso] = (np.arange(len(iso)) + k + 1).astype(lab.dtype)
        return table[lab]

    def _log_execution(self, executor, impl, use_tiled):
        """Which devices and which kernels this sweep runs — ``auto``
        resolves per backend, and a run's log must say what it resolved to."""
        from ..ops.tile_ws import resolved_modes
        from ..parallel.mesh import describe_devices

        self.logger.info(
            f"executor.devices={describe_devices(executor.devices)}; "
            f"kernels={resolved_modes(impl if use_tiled else 'legacy')}"
        )

    def _sweep(self, executor, cfg, kernel, blocks, load, store, block_done,
               done, out, parity, outer, use_tiled, overflow_blocks, records,
               two_d=False):
        """One sweep of the block grid through the executor, as both passes
        run it; returns the pass's counters (:func:`_pass_counters`).  The
        ``ws.pass`` span is the pass on the timeline: which parity, how many
        blocks, whether each plane is flooded alone (``two_d``), and (once
        the sweep is through) the lanes its dispatches carried, the planes
        and voxels they computed and the real lanes' work records (``work``:
        one dict a block, by block id; a padding lane's row never reaches
        ``store``)."""
        todo = [b for b in blocks if b.block_id not in done]
        with trace_mod.span(
            "ws.pass", task=self.uid, parity=parity, n_blocks=len(todo),
            two_d=bool(two_d),
        ) as pass_span:
            summary = executor.map_blocks(
                kernel,
                blocks,
                load,
                store,
                on_block_done=block_done,
                done_block_ids=done,
                validate_fn=_validate_block_labels,
                failures_path=self.failures_path,
                task_name=self.uid,
                block_deadline_s=cfg.get("block_deadline_s"),
                watchdog_period_s=cfg.get("watchdog_period_s"),
                store_verify_fn=region_verifier(out),
                schedule=str(cfg.get("block_schedule") or "morton"),
                # one sharded program per Morton batch when the mesh/sweep
                # is big enough (docs/PERFORMANCE.md "Sharded sweeps");
                # bit-identical to per-block dispatch, which stays the
                # degrade fallback
                sweep_mode=str(cfg.get("sweep_mode") or "auto"),
                sharded_batch=cfg.get("sharded_batch"),
                # HBM-resident page pool for ragged sweeps: pages upload
                # once, re-address per batch (docs/PERFORMANCE.md
                # "Device-resident data plane")
                device_pool=str(cfg.get("device_pool") or "auto"),
                device_pool_bytes=cfg.get("device_pool_bytes"),
                # degrade policy: OOM/ENOSPC blocks wait for headroom and
                # re-execute instead of burning same-size retries.  NEVER
                # splittable: the label encoding (block_id * (n_outer+1) +
                # flat index in the STATIC outer block) depends on the outer
                # shape, so sub-block re-execution could not reproduce the
                # unsplit labels bit-identically.
                splittable=False,
                degrade_wait_s=float(cfg.get("degrade_wait_s", 5.0)),
                inflight_byte_budget=cfg.get("inflight_byte_budget"),
            )
            counters = _pass_counters(
                summary, todo, outer, use_tiled, overflow_blocks, records,
                two_d,
            )
            pass_span.note(
                lanes=len(todo) + counters["lanes_padded"],
                work=[dict(records[b], block=b) for b in sorted(records)],
                **{k: counters[k] for k in (
                    "dispatches", "planes", "padded_voxels", "outer_voxels",
                    "inner_voxels",
                )},
            )
        return counters

    def _store_labels(self, out, block, raw, n_outer, size_dtype=np.uint64):
        """Crop inner region from the padded-outer labels and globalize."""
        inner = raw[block.inner_in_outer_bb]
        glob = np.where(
            inner > 0,
            np.uint64(block.block_id) * np.uint64(n_outer + 1)
            + inner.astype(np.uint64),
            np.uint64(0),
        )
        out[block.bb] = glob
        return glob


class WatershedBase(_WsTaskBase):
    """Single-pass blockwise DT watershed (independent blocks).

    Params: ``input_path/input_key`` (boundary/height map), ``output_path/
    output_key``; kernel params per ``default_task_config``.  Optional
    ``pass_parity`` (0/1) restricts to checkerboard-even/odd blocks — used by
    the two-pass workflow for pass one.
    """

    task_name = "watershed"

    def run_impl(self):
        (
            cfg,
            inp,
            out,
            mask_ds,
            shape,
            block_shape,
            halo,
            blocking,
            block_ids,
        ) = self._setup()
        parity = cfg.get("pass_parity")
        if parity is not None:
            block_ids = [
                b
                for b in block_ids
                if sum(blocking.block_grid_position(b)) % 2 == int(parity)
            ]
        done = set(self.blocks_done())
        blocks_all = [blocking.get_block(b, halo) for b in block_ids]
        todo = [b for b in blocks_all if b.block_id not in done]
        outer = _outer_shape(block_shape, halo)
        n_outer = int(np.prod(outer))
        kp = self._kernel_params(cfg)
        two_d = bool(cfg.get("two_d", False))
        size_filter = int(cfg.get("size_filter") or 0)
        agg_thr = cfg.get("agglomerate_threshold")
        if parity is not None:
            # pass one of the checkerboard: its labels seed pass two
            _refuse_checkerboard_hybrids(cfg)
        # boundary blocks stashed between load and store for the host-side
        # agglomeration (unique keys; dict ops are GIL-atomic across the IO
        # threads)
        bnd_stash = {}

        def load(block):
            data = inp[block.outer_bb].astype(np.float32)
            # pad with 1.0 (pure boundary) so basins don't leak off-volume
            data = pad_block_to(data, outer, constant_values=1.0)
            if agg_thr is not None:
                bnd_stash[block.block_id] = data
            if mask_ds is not None:
                m = mask_ds[block.outer_bb] > 0
                m = pad_block_to(m, outer)
            else:
                m = np.ones(outer, bool)
            return data, m

        impl = str(cfg.get("impl", "auto"))
        use_tiled = (
            impl != "legacy"
            and int(kp.get("connectivity", 1)) == 1
            and len(outer) == 3
        )

        tk = _tiled_kwargs(kp, cfg)

        def ws_block(b, m):
            if use_tiled:
                from ..ops.tile_ws import dt_watershed_tiled

                lab, ovf, rec = dt_watershed_tiled(b, mask=m, impl=impl, **tk)
            else:
                lab = distance_transform_watershed(b, mask=m, two_d=two_d, **kp)
                ovf = jnp.zeros((), bool)
                rec = work.pack({})  # the dense fixpoint counts nothing
            if size_filter > 0:
                lab = filter_small_segments(
                    lab, b, jnp.int32(size_filter), connectivity=kp["connectivity"]
                )
            return lab, ovf, rec

        def ws_block_planar(b, m):
            """``two_d`` on the tiled kernels: one flood per z-plane
            (upstream's ``apply_dt_2d`` + ``apply_ws_2d``).  A function of
            its own, so that its sweep program carries its name
            (``jit_sharded_ws_block_planar``)."""
            from ..ops.tile_ws import PLANES, dt_watershed_tiled

            lab, ovf, rec = dt_watershed_tiled(
                b, mask=m, impl=impl, axes=PLANES, **tk
            )
            if size_filter > 0:
                # a freed voxel regrows from its own plane
                lab = filter_small_segments(
                    lab, b, jnp.int32(size_filter), planes=True
                )
            return lab, ovf, rec

        kernel = ws_block_planar if use_tiled and two_d else ws_block

        overflow_blocks = set()
        records = {}  # block id -> the lane's work record

        def store(block, raw):
            lab, ovf, rec = raw
            records[block.block_id] = rec = work.unpack(rec)[0]
            if bool(np.asarray(ovf)):
                overflow_blocks.add(block.block_id)
                _warn_overflow(self.logger, block, rec)
            lab = np.asarray(lab)
            if agg_thr is not None:
                # peek, don't pop: a store retry (including a post-store
                # integrity-verify retry) must find the stash intact — the
                # stash is released in block_done below
                lab = self._agglomerate_block(
                    lab, bnd_stash[block.block_id], float(agg_thr)
                )
            self._store_labels(out, block, lab, n_outer)

        def block_done(block):
            bnd_stash.pop(block.block_id, None)
            self.log_block_success(block.block_id)

        device_memory = {}  # the host path holds no device
        if impl == "host":
            # reference-style per-job scipy compute (ops/host.py): no
            # device, no jit — the executor's vmap+jit contract does not
            # apply, so run the blocks on a thread pool (scipy EDT /
            # watershed_ift release the GIL, so max_jobs threads really
            # overlap compute as well as IO)
            if two_d:
                raise NotImplementedError("impl='host' is 3-D only")
            if size_filter > 0 or agg_thr is not None:
                raise NotImplementedError(
                    "impl='host' does not support size_filter / "
                    "agglomerate_threshold — use the device impls"
                )
            # params the host kernel has no twin for must fail, not drift
            if float(kp.get("sigma_seeds") or 0.0) > 0:
                raise NotImplementedError(
                    "impl='host' does not support sigma_seeds"
                )
            if int(kp.get("connectivity", 1)) != 1:
                raise NotImplementedError(
                    "impl='host' supports connectivity=1 only"
                )
            from concurrent.futures import ThreadPoolExecutor

            from ..ops.host import host_dt_watershed

            nothing_counted = np.full(len(work.NAMES), work.UNSET, np.int32)

            def _host_block(block):
                b, m = load(block)
                lab = host_dt_watershed(
                    b,
                    threshold=float(kp["threshold"]),
                    dt_max_distance=kp.get("dt_max_distance"),
                    min_seed_distance=float(kp.get("min_seed_distance", 0.0)),
                    mask=m,
                    sampling=kp.get("sampling"),
                )
                store(block, (lab, False, nothing_counted))
                self.log_block_success(block.block_id)

            with ThreadPoolExecutor(max(1, self.max_jobs)) as pool:
                # list() propagates the first worker exception
                list(pool.map(_host_block, todo))
            counters = _pass_counters(
                {}, todo, outer, False, overflow_blocks, records
            )
        else:
            executor = BlockwiseExecutor(
                target=self.target,
                device_batch=int(cfg.get("device_batch", 1)),
                io_threads=int(cfg.get("io_threads") or max(1, self.max_jobs)),
                max_retries=int(cfg.get("io_retries", 2)),
                backoff_base=float(cfg.get("io_backoff_s", 0.05)),
            )
            self._log_execution(executor, impl, use_tiled)
            counters = self._sweep(
                executor, cfg, kernel, blocks_all, load, store, block_done,
                done, out, parity, outer, use_tiled, overflow_blocks, records,
                two_d,
            )
            device_memory = device_peak_bytes(executor.devices)
        return {
            **counters,
            "n_blocks": len(block_ids),
            "n_outer": n_outer,
            "device_memory": device_memory,
        }


class WatershedLocal(WatershedBase):
    target = "local"


class WatershedTPU(WatershedBase):
    target = "tpu"


class TwoPassWatershedBase(_WsTaskBase):
    """Pass two of the checkerboard: odd blocks seed from even neighbors.

    Reads the boundary map *and* the pass-one labels in the halo region; the
    visible neighbor labels become external seeds (compressed to dense ids on
    host), so basins continue across block faces with identical global ids
    (SURVEY.md §3.5).
    """

    task_name = "two_pass_watershed"

    def run_impl(self):
        (
            cfg,
            inp,
            out,
            mask_ds,
            shape,
            block_shape,
            halo,
            blocking,
            block_ids,
        ) = self._setup()
        if all(h == 0 for h in halo):
            raise ValueError("two-pass watershed requires a nonzero halo")
        _refuse_checkerboard_hybrids(cfg)
        block_ids = [
            b
            for b in block_ids
            if sum(blocking.block_grid_position(b)) % 2 == 1
        ]
        done = set(self.blocks_done())
        blocks_all = [blocking.get_block(b, halo) for b in block_ids]
        outer = _outer_shape(block_shape, halo)
        n_outer = int(np.prod(outer))
        kp = self._kernel_params(cfg)
        size_filter = int(cfg.get("size_filter") or 0)

        # per-block external-seed tables, keyed by block id (host side),
        # and how many external labels each block saw (kept past block_done)
        tables = {}
        n_ext = {}

        def load(block):
            data = pad_block_to(
                inp[block.outer_bb].astype(np.float32), outer, constant_values=1.0
            )
            prev = pad_block_to(out[block.outer_bb], outer)
            with trace_mod.span(
                "ws2.ext_seeds", task=self.uid, block_id=int(block.block_id),
                nbytes=int(prev.nbytes),
            ) as ext_span:
                # keep only voxels owned by even-parity (pass-one) blocks:
                # pass one is a completed barrier, so those chunks are
                # immutable here; reading odd-parity neighbors' chunks would
                # race with concurrent pass-two stores, and diagonal odd
                # blocks must not seed us anyway
                grids = np.ix_(
                    *(
                        np.arange(b, b + o) // bs
                        for b, o, bs in zip(
                            block.outer_begin, prev.shape, block_shape
                        )
                    )
                )
                prev = np.where(sum(grids) % 2 == 0, prev, np.uint64(0))
                ext_labels = np.unique(prev[prev > 0])
                dense = np.zeros(outer, np.int32)
                if len(ext_labels):
                    dense = np.searchsorted(ext_labels, prev).astype(np.int32) + 1
                    dense[prev == 0] = 0
                ext_span.note(n_ext=len(ext_labels))
            tables[block.block_id] = ext_labels
            n_ext[block.block_id] = len(ext_labels)
            if mask_ds is not None:
                m = pad_block_to(mask_ds[block.outer_bb] > 0, outer)
            else:
                m = np.ones(outer, bool)
            return data, dense, m

        impl = str(cfg.get("impl", "auto"))
        if impl == "host":
            # pass one would run scipy while this pass runs the seeded
            # device kernel: two flood semantics stitched into one label
            # space.  Refuse the hybrid (same policy as two_d).
            raise NotImplementedError(
                "impl='host' is not supported for two-pass watershed: the "
                "seeded continuation only exists as a device kernel"
            )
        use_tiled = impl != "legacy" and int(kp.get("connectivity", 1)) == 1

        tk = _tiled_kwargs(kp, cfg)

        def ws_block_seeded(b, ext, m):
            if use_tiled:
                from ..ops.tile_ws import dt_watershed_seeded_tiled

                lab, ovf, rec = dt_watershed_seeded_tiled(
                    b, ext, mask=m, impl=impl, **tk
                )
            else:
                lab = dt_watershed_seeded(b, ext, mask=m, **kp)
                ovf = jnp.zeros((), bool)
                rec = work.pack({})  # the dense fixpoint counts nothing
            if size_filter > 0:
                # external ids live in (N, 2N]; widen the size-count domain
                lab = filter_small_segments(
                    lab,
                    b,
                    jnp.int32(size_filter),
                    connectivity=kp["connectivity"],
                    max_label=2 * n_outer,
                )
            return lab, ovf, rec

        overflow_blocks = set()
        records = {}  # block id -> the lane's work record

        def store(block, raw):
            raw, ovf, rec = raw
            records[block.block_id] = rec = work.unpack(rec)[0]
            if bool(np.asarray(ovf)):
                # same contract as the single-pass store: recorded so the
                # blocks can be rerun programmatically
                overflow_blocks.add(block.block_id)
                _warn_overflow(self.logger, block, rec)
            raw = np.asarray(raw)[block.inner_in_outer_bb]
            # peek, don't pop: a store retry must find the table intact
            ext_labels = tables[block.block_id]
            with trace_mod.span(
                "ws2.relabel", task=self.uid, block_id=int(block.block_id),
                nbytes=int(raw.nbytes),
            ):
                is_ext = raw > n_outer
                glob = np.zeros(raw.shape, np.uint64)
                if is_ext.any():
                    glob[is_ext] = ext_labels[
                        np.clip(raw[is_ext] - n_outer - 1, 0, len(ext_labels) - 1)
                    ]
                new = (raw > 0) & ~is_ext
                glob[new] = np.uint64(block.block_id) * np.uint64(
                    n_outer + 1
                ) + raw[new].astype(np.uint64)
            out[block.bb] = glob

        def block_done(block):
            # release the seed table only once the block is fully stored
            # (a verify-triggered re-store must still find it)
            tables.pop(block.block_id, None)
            self.log_block_success(block.block_id)

        executor = BlockwiseExecutor(
            target=self.target,
            device_batch=int(cfg.get("device_batch", 1)),
            io_threads=int(cfg.get("io_threads") or max(1, self.max_jobs)),
            max_retries=int(cfg.get("io_retries", 2)),
            backoff_base=float(cfg.get("io_backoff_s", 0.05)),
        )
        self._log_execution(executor, impl, use_tiled)
        counters = self._sweep(
            executor, cfg, ws_block_seeded, blocks_all, load, store,
            block_done, done, out, 1, outer, use_tiled, overflow_blocks,
            records,
        )
        return {
            **counters,
            "n_blocks": len(block_ids),
            "n_outer": n_outer,
            "n_ext_labels": sum(n_ext.values()),
            "device_memory": device_peak_bytes(executor.devices),
        }


class TwoPassWatershedLocal(TwoPassWatershedBase):
    target = "local"


class TwoPassWatershedTPU(TwoPassWatershedBase):
    target = "tpu"


class WatershedWorkflow(WorkflowBase):
    """Watershed workflow: single-pass, or two-pass checkerboard when
    ``two_pass=True`` (reference: ``WatershedWorkflow`` /
    ``TwoPassWatershed``)."""

    task_name = "watershed_workflow"

    def requires(self):
        from . import watershed as ws_mod

        p = dict(self.params)
        two_pass = bool(p.pop("two_pass", False))
        if two_pass:
            # before pass one burns hours on even blocks
            _refuse_checkerboard_hybrids(p)
        common = dict(
            tmp_folder=self.tmp_folder,
            config_dir=self.config_dir,
            max_jobs=self.max_jobs,
        )
        if not two_pass:
            return [
                get_task_cls(ws_mod, "Watershed", self.target)(
                    **common, dependencies=self.dependencies, **p
                )
            ]
        t1 = get_task_cls(ws_mod, "Watershed", self.target)(
            **common, dependencies=self.dependencies, pass_parity=0, **p
        )
        t2 = get_task_cls(ws_mod, "TwoPassWatershed", self.target)(
            **common, dependencies=[t1], **p
        )
        return [t2]

    #: what a pass's manifest says of its sweep (:func:`_pass_counters`)
    _PASS_KEYS = (
        "n_blocks", "dispatches", "lanes_padded", "two_d", "planes",
        "padded_voxels", "outer_voxels", "inner_voxels", "n_ext_labels",
        "overflow_blocks", "work", "step_cache",
    )

    def run_impl(self):
        """The passes' counters, gathered from their manifests into the
        workflow's own and into ``io_metrics.json`` (docs/OBSERVABILITY.md
        "Two-pass watershed"), keyed by task, first pass first."""
        task, chain = self.requires()[0], []
        while task is not None:
            chain.insert(0, task)
            task = next(
                (d for d in task.dependencies if isinstance(d, _WsTaskBase)),
                None,
            )
        passes = {}
        for task in chain:
            try:
                doc = task.output().read()
            except OSError:
                doc = {}
            passes[task.task_name] = {
                k: doc[k] for k in self._PASS_KEYS if k in doc
            }
        fu.record_io_metrics(
            fu.io_metrics_path(self.tmp_folder), self.uid, {"passes": passes}
        )
        return {"passes": passes}
