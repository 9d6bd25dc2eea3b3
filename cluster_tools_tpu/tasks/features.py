"""Edge features: per-block accumulation + global weighted merge.

Re-design of the reference's ``cluster_tools/features/`` (SURVEY.md §2a
"features"): ``block_edge_features.py`` accumulated boundary-map/affinity
statistics per RAG edge through ``nifty.distributed``; ``merge_edge_features``
did the count-weighted merge.  Here the per-block scan+accumulate reuses the
jitted RAG kernel (:func:`..ops.rag.block_rag` with values), and the merge is
:func:`..ops.rag.merge_feature_lists` on the driver.

Artifacts (in ``tmp_folder/graph``, next to the graph):

    features_block_<id>.npz  {uv, feats}     per-block edge features
    features.npy             float32 [m, 5]  (mean, min, max, count, variance) per
                                             global edge, aligned with
                                             graph.npz's edge list
"""

from __future__ import annotations

import os
import numpy as np

from ..ops.rag import block_rag, merge_feature_lists
from ..runtime import handoff
from ..runtime import trace as trace_mod
from ..runtime.task import BaseTask, WorkflowBase
from ..utils.volume_utils import Blocking, blocks_in_volume, file_reader
from .graph import _upper_halo_bb, graph_dir, load_global_graph


def block_features_path(tmp_folder: str, block_id: int) -> str:
    return os.path.join(graph_dir(tmp_folder), f"features_block_{block_id}.npz")


def features_path(tmp_folder: str) -> str:
    return os.path.join(graph_dir(tmp_folder), "features.npy")


def _read_boundary_map(ds, bb, channel):
    """Read a boundary/affinity map block; reduce a channel axis if present.

    ``channel``: None (no channel axis), int, or list of ints (averaged) —
    matching the reference's affinity-channel handling.
    """
    if channel is None:
        return np.asarray(ds[bb])
    if isinstance(channel, int):
        return np.asarray(ds[(slice(channel, channel + 1),) + bb][0])
    sel = np.asarray(ds[(slice(min(channel), max(channel) + 1),) + bb])
    sel = sel[[c - min(channel) for c in channel]]
    return sel.mean(axis=0)


class BlockEdgeFeaturesBase(BaseTask):
    """Per-block edge-feature accumulation (reference:
    ``block_edge_features.py``).

    Params: ``input_path/input_key`` (boundary or affinity map, optionally
    with a leading channel axis + ``channel`` selector), ``labels_path/
    labels_key`` (the supervoxels the graph was built from).
    """

    task_name = "block_edge_features"

    @staticmethod
    def default_task_config():
        return {"threads_per_job": 1, "device_batch": 1, "channel": None}

    def run_impl(self):
        cfg = self.get_config()
        # fusable edges: the boundary map may itself be a live in-memory
        # handoff (inference/ilastik output), and the supervoxels come
        # from the watershed producer's handle when one exists
        ds_in = handoff.resolve_dataset(cfg["input_path"], cfg["input_key"])
        ds_labels = handoff.resolve_dataset(cfg["labels_path"], cfg["labels_key"])
        shape = ds_labels.shape
        block_shape = tuple(cfg["block_shape"])
        blocking = Blocking(shape, block_shape)
        block_ids = blocks_in_volume(
            shape, block_shape, cfg.get("roi_begin"), cfg.get("roi_end")
        )
        channel = cfg.get("channel")
        self.declare_handoff_producer()

        def process(block_id: int):
            block = blocking.get_block(block_id)
            bb = _upper_halo_bb(block, shape)
            with trace_mod.span("features.block", block_id=int(block_id)) as sp:
                seg = np.asarray(ds_labels[bb])
                val = _read_boundary_map(ds_in, bb, channel)
                sp.note(nbytes=int(seg.nbytes + val.nbytes),
                        shape=list(seg.shape), inner=[int(s) for s in block.shape])
                uv, _, feats = block_rag(
                    seg, values=val, inner_shape=block.shape
                )
                self.save_handoff_arrays(
                    block_features_path(self.tmp_folder, block_id),
                    uv=uv, feats=feats,
                )

        n = self.host_block_map(block_ids, process)
        return {"n_blocks": n}


class BlockEdgeFeaturesLocal(BlockEdgeFeaturesBase):
    target = "local"


class BlockEdgeFeaturesTPU(BlockEdgeFeaturesBase):
    target = "tpu"


class MergeEdgeFeaturesBase(BaseTask):
    """Count-weighted merge of block features onto the global edge list
    (reference: ``merge_edge_features.py``)."""

    task_name = "merge_edge_features"

    def run_impl(self):
        cfg = self.get_config()
        shape = handoff.resolve_dataset(
            cfg["labels_path"], cfg["labels_key"]
        ).shape
        block_ids = blocks_in_volume(
            shape, tuple(cfg["block_shape"]), cfg.get("roi_begin"), cfg.get("roi_end")
        )
        _, uv_global, _, _ = load_global_graph(self.tmp_folder)

        def parts():
            for b in block_ids:
                f = handoff.load_arrays(
                    block_features_path(self.tmp_folder, b)
                )
                yield f["uv"], f["feats"]

        with trace_mod.span("features.merge", n_blocks=len(block_ids)):
            feats = merge_feature_lists(uv_global, parts())
        self.save_handoff_array(features_path(self.tmp_folder), feats)
        return {"n_edges": len(feats)}


class MergeEdgeFeaturesLocal(MergeEdgeFeaturesBase):
    target = "local"


class MergeEdgeFeaturesTPU(MergeEdgeFeaturesBase):
    target = "tpu"


class EdgeFeaturesWorkflow(WorkflowBase):
    """BlockEdgeFeatures -> MergeEdgeFeatures."""

    task_name = "edge_features_workflow"

    def requires(self):
        from . import features as feat_mod
        from ..runtime.task import get_task_cls

        common = dict(
            tmp_folder=self.tmp_folder,
            config_dir=self.config_dir,
            max_jobs=self.max_jobs,
        )
        p = self.params
        keys = {
            k: p[k]
            for k in (
                "input_path",
                "input_key",
                "labels_path",
                "labels_key",
                "channel",
                "block_shape",
                "roi_begin",
                "roi_end",
            )
            if k in p
        }
        t1 = get_task_cls(feat_mod, "BlockEdgeFeatures", self.target)(
            **common, dependencies=self.dependencies, **keys
        )
        t2 = get_task_cls(feat_mod, "MergeEdgeFeatures", self.target)(
            **common, dependencies=[t1], **keys
        )
        return [t2]
