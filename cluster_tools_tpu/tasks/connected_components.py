"""Blockwise connected components with global label stitching.

Re-design of the reference's ``cluster_tools/connected_components/``
(SURVEY.md §3.2).  The reference ran five luigi tasks: per-block vigra CCL ->
prefix-sum label offsets -> per-face equivalence scan -> serial
``nifty.ufd`` union-find -> blockwise write.  Two structural changes here:

1. **No offset pass.**  Per-block labels are the *global flat index of the
   component's minimum voxel + 1* — globally unique by construction (the
   device CCL kernel already produces block-local min-voxel indices, which
   the host shifts into volume coordinates).  The reference needed the
   prefix-sum because vigra labels were 1..k per block.
2. **The union-find merge is a device kernel** (pointer jumping over the
   dense label table), not a serial C++ loop — the reference's named
   scalability cliff (SURVEY.md §3.2 "serial on one node").

Task chain (same barrier structure as the reference, so resume behaves the
same):

    BlockComponents   (mesh-batched)  per-block CCL -> global labels + uniques
    MergeLabels       (driver)        merge per-block uniques -> dense table
    BlockFaces        (host IO pool)  boundary scan (faces, plus edges and
                                      corners at connectivity>1) -> pairs
    MergeAssignments  (device)        union-find -> assignment table
    Write             (host IO pool)  apply assignment blockwise
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

from ..ops.ccl import label_components, label_components_keyed
from ..ops.unionfind import union_find, union_find_host
from ..parallel.mesh import describe_devices
from ..runtime import handoff
from ..runtime.executor import (
    BlockwiseExecutor,
    region_verifier,
    validate_labels,
)
from ..runtime.task import BaseTask, WorkflowBase, build
from ..utils.volume_utils import Blocking, blocks_in_volume, file_reader, pad_block_to

import jax.numpy as jnp


def _uniques_path(tmp_folder: str, block_id: int) -> str:
    d = os.path.join(tmp_folder, "cc_uniques")
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"block_{block_id}.npy")


def _faces_path(tmp_folder: str, block_id: int) -> str:
    d = os.path.join(tmp_folder, "cc_faces")
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"block_{block_id}.npy")


class BlockComponentsBase(BaseTask):
    """Pass 1: per-block CCL on the thresholded/binary input.

    Params: ``input_path/input_key`` (binary or real-valued volume),
    ``output_path/output_key`` (uint64 labels), optional ``threshold`` +
    ``threshold_mode`` ('greater'/'less'), optional ``mask_path/mask_key``.
    """

    task_name = "block_components"

    @staticmethod
    def default_task_config():
        return {
            "threads_per_job": 1,
            "device_batch": 1,
            "threshold": None,
            "threshold_mode": "greater",
            "connectivity": 1,
            # keyed=True: components of equal-valued regions (CC on a
            # segmentation, each segment split into its connected parts)
            "keyed": False,
        }

    def run_impl(self):
        cfg = self.get_config()
        # fusable input edge: a producer's live in-memory handoff (e.g. an
        # inference probability map) is consumed without a storage read
        inp = handoff.resolve_dataset(cfg["input_path"], cfg["input_key"])
        shape = inp.shape
        block_shape = tuple(cfg["block_shape"])
        blocking = Blocking(shape, block_shape)
        block_ids = blocks_in_volume(
            shape, block_shape, cfg.get("roi_begin"), cfg.get("roi_end")
        )
        # MemoryTarget output: label volume stays in RAM for the faces /
        # write consumers, spill-to-storage under the degrade ladder
        out = self.handoff_dataset(
            cfg["output_path"], cfg["output_key"],
            shape=shape, chunks=block_shape, dtype="uint64",
        )
        # the per-block uniques below are block-grain ARTIFACT handoffs:
        # stamp the marker epoch even when the dataset itself spilled at
        # birth, or a resumed process would trust markers whose uniques
        # died in this process's RAM
        self.declare_handoff_producer()
        done = set(self.blocks_done())
        blocks_all = [blocking.get_block(b) for b in block_ids]

        threshold = cfg.get("threshold")
        mode = cfg.get("threshold_mode", "greater")
        connectivity = int(cfg.get("connectivity", 1))
        if not 1 <= connectivity <= len(shape):
            # fail in pass 1, before any blocks burn time with an empty or
            # nonsense neighborhood
            raise ValueError(f"connectivity must be in [1, {len(shape)}]")
        keyed = bool(cfg.get("keyed", False))
        mask_ds = None
        if cfg.get("mask_path"):
            mask_ds = file_reader(cfg["mask_path"])[cfg["mask_key"]]

        def load(block):
            data = inp[block.bb]
            if keyed:
                # dense per-block int32 keys (device kernels can't take
                # uint64 labels); key identity only matters within a block
                _, keys = np.unique(np.asarray(data), return_inverse=True)
                keys = keys.reshape(np.asarray(data).shape).astype(np.int32)
                keys[np.asarray(data) == 0] = 0
                if mask_ds is not None:
                    keys[~(np.asarray(mask_ds[block.bb]) > 0)] = 0
                return (pad_block_to(keys, block_shape),)
            if threshold is None:
                m = data > 0
            elif mode == "greater":
                m = data > threshold
            else:
                m = data < threshold
            if mask_ds is not None:
                m &= mask_ds[block.bb] > 0
            return (pad_block_to(m, block_shape).astype(bool),)

        n_pad = int(np.prod(block_shape))

        def kernel(m):
            if keyed:
                return label_components_keyed(m, connectivity=connectivity)
            return label_components(m, connectivity=connectivity)

        def store(block, raw):
            # raw: padded-block flat index of component min voxel, sentinel=n
            bs = block.shape
            raw = raw[tuple(slice(0, s) for s in bs)]
            fg = raw < n_pad
            local = np.unravel_index(raw[fg].astype(np.int64), block_shape)
            coords = tuple(
                l + b for l, b in zip(local, block.begin)
            )
            glob = np.ravel_multi_index(coords, shape).astype(np.uint64) + 1
            labels = np.zeros(bs, np.uint64)
            labels[fg] = glob
            out[block.bb] = labels
            self.save_handoff_array(
                _uniques_path(self.tmp_folder, block.block_id), np.unique(glob)
            )

        executor = BlockwiseExecutor(
            target=self.target,
            device_batch=int(cfg.get("device_batch", 1)),
            io_threads=int(cfg.get("io_threads") or max(1, self.max_jobs)),
            max_retries=int(cfg.get("io_retries", 2)),
            backoff_base=float(cfg.get("io_backoff_s", 0.05)),
        )
        self.logger.info(
            f"executor.devices={describe_devices(executor.devices)}"
        )
        executor.map_blocks(
            kernel,
            blocks_all,
            load,
            store,
            on_block_done=lambda b: self.log_block_success(b.block_id),
            done_block_ids=done,
            validate_fn=validate_labels,
            failures_path=self.failures_path,
            task_name=self.uid,
            block_deadline_s=cfg.get("block_deadline_s"),
            watchdog_period_s=cfg.get("watchdog_period_s"),
            store_verify_fn=region_verifier(out),
            schedule=str(cfg.get("block_schedule") or "morton"),
            sweep_mode=str(cfg.get("sweep_mode") or "auto"),
            sharded_batch=cfg.get("sharded_batch"),
            device_pool=str(cfg.get("device_pool") or "auto"),
            device_pool_bytes=cfg.get("device_pool_bytes"),
            # degrade on OOM/ENOSPC; never splittable: the per-block CC
            # decomposition (and the min-voxel label of a component crossing
            # a would-be split plane) changes under sub-block re-execution
            splittable=False,
            degrade_wait_s=float(cfg.get("degrade_wait_s", 5.0)),
            inflight_byte_budget=cfg.get("inflight_byte_budget"),
        )
        return {"n_blocks": len(block_ids), "shape": list(shape)}


class BlockComponentsLocal(BlockComponentsBase):
    target = "local"


class BlockComponentsTPU(BlockComponentsBase):
    target = "tpu"


class MergeLabelsBase(BaseTask):
    """Merge per-block unique labels into the dense global label table.

    Replaces the reference's ``merge_offsets`` prefix-sum (our labels are
    globally unique already); the table maps sorted uint64 labels -> dense
    int32 ids for the device union-find.
    """

    task_name = "merge_labels"

    def run_impl(self):
        cfg = self.get_config()
        shape = handoff.resolve_dataset(
            cfg["input_path"], cfg["input_key"]
        ).shape
        block_ids = blocks_in_volume(
            shape, tuple(cfg["block_shape"]), cfg.get("roi_begin"), cfg.get("roi_end")
        )
        uniques = [
            handoff.load_array(_uniques_path(self.tmp_folder, b))
            for b in block_ids
            if handoff.array_exists(_uniques_path(self.tmp_folder, b))
        ]
        table = (
            np.unique(np.concatenate(uniques))
            if uniques
            else np.zeros(0, np.uint64)
        )
        self.save_handoff_array(
            os.path.join(self.tmp_folder, "cc_label_table.npy"), table
        )
        return {"n_labels": len(table)}


class MergeLabelsLocal(MergeLabelsBase):
    target = "local"


class MergeLabelsTPU(MergeLabelsBase):
    target = "tpu"


def _shifted_views(a: np.ndarray, b: np.ndarray, shifts) -> tuple:
    """Views pairing ``a[p]`` with ``b[p + shifts]`` (per free axis)."""
    sl_a, sl_b = [], []
    for sh, n in zip(shifts, a.shape):
        if sh == 1:
            sl_a.append(slice(0, n - 1))
            sl_b.append(slice(1, n))
        elif sh == -1:
            sl_a.append(slice(1, n))
            sl_b.append(slice(0, n - 1))
        else:
            sl_a.append(slice(None))
            sl_b.append(slice(None))
    return a[tuple(sl_a)], b[tuple(sl_b)]


class BlockFacesBase(BaseTask):
    """Pass 2: scan adjacent block boundaries for label equivalences.

    For every block and every unordered neighbor direction (faces at
    connectivity 1; faces, edges, and corners at higher connectivity), reads
    the 1-voxel slabs on either side of the shared boundary and emits
    (label_a, label_b) pairs for every in-range voxel offset with at most
    ``connectivity`` differing coordinates — the blockwise completion of the
    per-block CCL's neighborhood (scipy semantics).  Host-side: thin-slab IO
    is bandwidth-bound, not compute.
    """

    task_name = "block_faces"

    def run_impl(self):
        from itertools import product

        from ..ops.ccl import _neighbor_offsets

        cfg = self.get_config()
        connectivity = int(cfg.get("connectivity", 1))
        keyed = bool(cfg.get("keyed", False))
        inp_ds = (
            handoff.resolve_dataset(cfg["input_path"], cfg["input_key"])
            if keyed else None
        )
        # fusable edge (block_components -> block_faces): slab reads come
        # from the live in-memory label volume when one exists
        ds = handoff.resolve_dataset(cfg["output_path"], cfg["output_key"])
        shape = ds.shape
        ndim = len(shape)
        block_shape = tuple(cfg["block_shape"])
        blocking = Blocking(shape, block_shape)
        block_ids = blocks_in_volume(
            shape, block_shape, cfg.get("roi_begin"), cfg.get("roi_end")
        )
        roi_set = set(block_ids)
        if not 1 <= connectivity <= ndim:
            raise ValueError(f"connectivity must be in [1, {ndim}]")
        # the kernel's half-neighborhood doubles as the unordered
        # block-direction list (each adjacent block pair scanned once);
        # {-1,0,1} offsets make sum(|o|) == nnz, so the budgets coincide
        directions = _neighbor_offsets(ndim, connectivity)
        self.declare_handoff_producer()

        def slab_bbs(block, d):
            """(our-side bb, neighbor-side bb) of the shared boundary."""
            bb_a, bb_b = [], []
            for a, o in enumerate(d):
                b, e = block.begin[a], block.end[a]
                if o == 1:
                    bb_a.append(slice(e - 1, e))
                    bb_b.append(slice(e, e + 1))
                elif o == -1:
                    bb_a.append(slice(b, b + 1))
                    bb_b.append(slice(b - 1, b))
                else:
                    bb_a.append(slice(b, e))
                    bb_b.append(slice(b, e))
            return tuple(bb_a), tuple(bb_b)

        def process(block_id: int):
            block = blocking.get_block(block_id)
            pairs = []
            for d in directions:
                nbr = blocking.neighbor_id_offset(block_id, d)
                if nbr is None or nbr not in roi_set:
                    continue
                bb_a, bb_b = slab_bbs(block, d)
                crossing = tuple(a for a in range(ndim) if d[a] != 0)
                A = np.asarray(ds[bb_a]).squeeze(axis=crossing)
                B = np.asarray(ds[bb_b]).squeeze(axis=crossing)
                if keyed:
                    ka = np.asarray(inp_ds[bb_a]).squeeze(axis=crossing)
                    kb = np.asarray(inp_ds[bb_b]).squeeze(axis=crossing)
                free_budget = connectivity - len(crossing)
                for s in product((-1, 0, 1), repeat=ndim - len(crossing)):
                    if sum(1 for o in s if o) > free_budget:
                        continue
                    av, bv = _shifted_views(A, B, s)
                    both = (av > 0) & (bv > 0)
                    if keyed:
                        # CC-on-segmentation: only merge across the boundary
                        # where the ORIGINAL segment label matches
                        kav, kbv = _shifted_views(ka, kb, s)
                        both &= kav == kbv
                    if both.any():
                        p = np.stack([av[both], bv[both]], axis=1)
                        pairs.append(np.unique(p, axis=0))
            result = (
                np.concatenate(pairs)
                if pairs
                else np.zeros((0, 2), np.uint64)
            )
            self.save_handoff_array(_faces_path(self.tmp_folder, block_id), result)

        n = self.host_block_map(block_ids, process)
        return {"n_blocks": n}


class BlockFacesLocal(BlockFacesBase):
    target = "local"


class BlockFacesTPU(BlockFacesBase):
    target = "tpu"


class MergeAssignmentsBase(BaseTask):
    """Union-find over all face equivalences -> global assignment table.

    The reference ran serial ``nifty.ufd`` here; we map labels to dense ids
    and run the pointer-jumping union-find on device (host scipy fallback for
    tiny problems).  The final assignment renumbers roots consecutively.
    """

    task_name = "merge_assignments"

    @staticmethod
    def default_task_config():
        return {"threads_per_job": 1, "device_batch": 1, "use_device": True}

    def run_impl(self):
        cfg = self.get_config()
        shape = handoff.resolve_dataset(
            cfg["input_path"], cfg["input_key"]
        ).shape
        table = handoff.load_array(
            os.path.join(self.tmp_folder, "cc_label_table.npy")
        )
        block_ids = blocks_in_volume(
            shape, tuple(cfg["block_shape"]), cfg.get("roi_begin"), cfg.get("roi_end")
        )
        pair_files = [
            _faces_path(self.tmp_folder, b)
            for b in block_ids
            if handoff.array_exists(_faces_path(self.tmp_folder, b))
        ]
        pairs = (
            np.concatenate([handoff.load_array(f) for f in pair_files])
            if pair_files
            else np.zeros((0, 2), np.uint64)
        )
        if len(pairs):
            pairs = np.unique(pairs, axis=0)
        n = len(table)
        # dense ids: position in the sorted label table
        dense_pairs = np.searchsorted(table, pairs).astype(np.int64)
        if n and cfg.get("use_device", True) and len(dense_pairs):
            roots = np.asarray(
                union_find(jnp.asarray(dense_pairs.astype(np.int32)), n)
            ).astype(np.int64)
        else:
            roots = union_find_host(dense_pairs, n)
        # renumber roots consecutively 1..K
        uniq_roots, assignment = np.unique(roots, return_inverse=True)
        assignment = (assignment + 1).astype(np.uint64)
        self.save_handoff_arrays(
            os.path.join(self.tmp_folder, "cc_assignments.npz"),
            keys=table,
            values=assignment,
        )
        return {"n_labels": n, "n_components": len(uniq_roots)}


class MergeAssignmentsLocal(MergeAssignmentsBase):
    target = "local"


class MergeAssignmentsTPU(MergeAssignmentsBase):
    target = "tpu"


class ConnectedComponentsWorkflow(WorkflowBase):
    """End-to-end blockwise CCL (reference: ``ConnectedComponentsWorkflow``)."""

    task_name = "connected_components_workflow"

    def requires(self):
        from . import connected_components as cc_mod
        from . import write as write_mod
        from ..runtime.task import get_task_cls

        cfg_common = dict(
            tmp_folder=self.tmp_folder,
            config_dir=self.config_dir,
            max_jobs=self.max_jobs,
        )
        p = self.params
        # provisional per-block labels live in a tmp dataset, so the final
        # Write never mutates its own input (crash-safe block resume)
        tmp_path = os.path.join(self.tmp_folder, "cc_blocks.zarr")
        tmp_key = "labels"
        t1 = get_task_cls(cc_mod, "BlockComponents", self.target)(
            **cfg_common,
            dependencies=self.dependencies,
            input_path=p["input_path"],
            input_key=p["input_key"],
            output_path=tmp_path,
            output_key=tmp_key,
            **{
                k: p[k]
                for k in ("threshold", "threshold_mode", "mask_path", "mask_key", "block_shape", "connectivity", "keyed")
                if k in p
            },
        )
        t2 = get_task_cls(cc_mod, "MergeLabels", self.target)(
            **cfg_common,
            dependencies=[t1],
            input_path=p["input_path"],
            input_key=p["input_key"],
            **{k: p[k] for k in ("block_shape",) if k in p},
        )
        t3 = get_task_cls(cc_mod, "BlockFaces", self.target)(
            **cfg_common,
            dependencies=[t2],
            output_path=tmp_path,
            output_key=tmp_key,
            input_path=p["input_path"],
            input_key=p["input_key"],
            **{k: p[k] for k in ("block_shape", "connectivity", "keyed") if k in p},
        )
        t4 = get_task_cls(cc_mod, "MergeAssignments", self.target)(
            **cfg_common,
            dependencies=[t3],
            input_path=p["input_path"],
            input_key=p["input_key"],
            **{k: p[k] for k in ("block_shape",) if k in p},
        )
        t5 = get_task_cls(write_mod, "Write", self.target)(
            **cfg_common,
            dependencies=[t4],
            input_path=tmp_path,
            input_key=tmp_key,
            output_path=p["output_path"],
            output_key=p["output_key"],
            assignment_path=os.path.join(self.tmp_folder, "cc_assignments.npz"),
            **{k: p[k] for k in ("block_shape",) if k in p},
        )
        return [t5]

    def run_impl(self):
        return {}
