"""Generic blockwise assignment writer.

Reference: ``cluster_tools/write/`` — "apply node-assignment table to
segmentation, blockwise", the final step of nearly every labeling workflow
(SURVEY.md §2a).  The assignment is an ``npz`` with sorted ``keys`` (uint64
labels) and ``values`` (new labels); unmatched labels map to 0.  Pure host
work (a searchsorted per block is memory-bound), parallelized over an IO
thread pool.
"""

from __future__ import annotations

import os
import numpy as np

from ..runtime import handoff
from ..runtime import trace as trace_mod
from ..runtime.executor import region_verifier
from ..runtime.task import BaseTask
from ..utils.volume_utils import Blocking, blocks_in_volume, file_reader


def apply_assignment_np(
    labels: np.ndarray, keys: np.ndarray, values: np.ndarray
) -> np.ndarray:
    """Map ``labels`` through the (sorted keys -> values) table; 0 stays 0,
    labels missing from the table map to 0."""
    idx = np.searchsorted(keys, labels)
    idx = np.clip(idx, 0, max(len(keys) - 1, 0))
    if len(keys) == 0:
        return np.zeros_like(labels)
    matched = keys[idx] == labels
    out = np.where(matched & (labels != 0), values[idx], 0)
    return out.astype(values.dtype if len(values) else labels.dtype)


class WriteBase(BaseTask):
    """Params: ``input_path/input_key`` (labels to relabel),
    ``output_path/output_key`` (may equal input for in-place),
    ``assignment_path`` (npz with keys/values)."""

    task_name = "write"

    def run_impl(self):
        cfg = self.get_config()
        # fusable edges (watershed -> write, multicut -> write): labels and
        # the assignment table come from live in-memory handoffs when the
        # producers published them; the OUTPUT always goes to storage —
        # it is the workflow's product, not an intermediate
        inp = handoff.resolve_dataset(cfg["input_path"], cfg["input_key"])
        shape = inp.shape
        block_shape = tuple(cfg["block_shape"])
        f = handoff.load_arrays(cfg["assignment_path"])
        keys, values = f["keys"], f["values"]

        out_f = file_reader(cfg["output_path"])
        out = out_f.require_dataset(
            cfg["output_key"], shape=shape, chunks=block_shape, dtype="uint64"
        )
        blocking = Blocking(shape, block_shape)
        block_ids = blocks_in_volume(
            shape, block_shape, cfg.get("roi_begin"), cfg.get("roi_end")
        )

        def process(block_id):
            block = blocking.get_block(block_id)
            with trace_mod.span("write.block", block_id=int(block_id)) as sp:
                labels = inp[block.bb]
                sp.note(nbytes=int(labels.nbytes))
                out[block.bb] = apply_assignment_np(labels, keys, values)

        n = self.host_block_map(
            block_ids, process,
            store_verify_fn=region_verifier(out), blocking=blocking,
        )
        return {"n_blocks": n}


class WriteLocal(WriteBase):
    target = "local"


class WriteTPU(WriteBase):
    target = "tpu"
