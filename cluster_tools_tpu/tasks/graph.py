"""Region-adjacency-graph extraction as a blockwise task chain.

Re-design of the reference's ``cluster_tools/graph/`` (SURVEY.md §2a
"graph", §3.3): there, ``initial_sub_graphs.py`` ran the ``nifty.distributed``
C++ per-block RAG extractor against N5, ``merge_sub_graphs.py`` merged block
graphs up a scale hierarchy, and ``map_edge_ids.py`` produced
block-edge→global-edge ID maps for features/multicut.  Here the per-block
scan is a jitted device kernel (:mod:`..ops.rag`) and the graph artifacts are
small npz files in ``tmp_folder/graph``:

    InitialSubGraphs  (host IO pool + device scans)  block_<id>.npz {nodes, uv, sizes}
    MergeSubGraphs    (driver)                        graph.npz {nodes, uv, edges, sizes}
    MapEdgeIds        (host IO pool)                  edge_ids_<id>.npy

``nodes``/``uv`` carry the original (uint64) segment labels; ``edges`` is the
same edge list in dense node indices (row into ``nodes``) for solver use.
Label 0 is background/ignore and never becomes a node.
"""

from __future__ import annotations

import os
import numpy as np

from ..ops.rag import block_rag, find_edge_ids, merge_edge_lists
from ..runtime import handoff
from ..runtime import trace as trace_mod
from ..runtime.task import BaseTask, WorkflowBase
from ..utils.volume_utils import Blocking, blocks_in_volume


def graph_dir(tmp_folder: str) -> str:
    d = os.path.join(tmp_folder, "graph")
    os.makedirs(d, exist_ok=True)
    return d


def block_graph_path(tmp_folder: str, block_id: int) -> str:
    return os.path.join(graph_dir(tmp_folder), f"block_{block_id}.npz")


def global_graph_path(tmp_folder: str) -> str:
    return os.path.join(graph_dir(tmp_folder), "graph.npz")


def edge_ids_path(tmp_folder: str, block_id: int) -> str:
    return os.path.join(graph_dir(tmp_folder), f"edge_ids_{block_id}.npy")


def load_global_graph(tmp_folder: str):
    """Load the merged graph: (nodes, uv, edges, sizes).  Served from the
    in-memory handoff when the producing task published one (task-graph
    fusion), else from the npz artifact."""
    f = handoff.load_arrays(global_graph_path(tmp_folder))
    return f["nodes"], f["uv"], f["edges"], f["sizes"]


def _upper_halo_bb(block, shape):
    """Inner bb extended by +1 voxel on upper faces (clipped): the RAG halo
    convention of :mod:`..ops.rag` — each voxel-face pair owned by one block."""
    return tuple(
        slice(b, min(e + 1, s)) for b, e, s in zip(block.begin, block.end, shape)
    )


class InitialSubGraphsBase(BaseTask):
    """Per-block RAG extraction (reference: ``initial_sub_graphs.py``).

    Params: ``input_path/input_key`` (the label/supervoxel volume).
    """

    task_name = "initial_sub_graphs"

    def run_impl(self):
        cfg = self.get_config()
        # fusable edge (watershed -> graph): consume the supervoxel volume
        # from the producer's in-memory handoff when one is live
        ds = handoff.resolve_dataset(cfg["input_path"], cfg["input_key"])
        shape = ds.shape
        block_shape = tuple(cfg["block_shape"])
        blocking = Blocking(shape, block_shape)
        block_ids = blocks_in_volume(
            shape, block_shape, cfg.get("roi_begin"), cfg.get("roi_end")
        )
        # block graphs are published in memory for MergeSubGraphs; stale
        # markers from a previous process are invalidated here
        self.declare_handoff_producer()

        def process(block_id: int):
            block = blocking.get_block(block_id)
            with trace_mod.span("graph.block", block_id=int(block_id)) as sp:
                seg = np.asarray(ds[_upper_halo_bb(block, shape)])
                sp.note(nbytes=int(seg.nbytes), shape=list(seg.shape),
                        inner=[int(s) for s in block.shape])
                # return_nodes: the inner node set comes out of the
                # extraction's own dense-label pass instead of a second host
                # np.unique scan over the block's voxels (ISSUE 1 fused-path
                # satellite)
                uv, sizes, _, nodes = block_rag(
                    seg, inner_shape=block.shape, return_nodes=True
                )
                nodes = nodes.astype(np.uint64)
                self.save_handoff_arrays(
                    block_graph_path(self.tmp_folder, block_id),
                    nodes=nodes,
                    uv=uv,
                    sizes=sizes,
                )

        n = self.host_block_map(block_ids, process)
        return {"n_blocks": n}


class InitialSubGraphsLocal(InitialSubGraphsBase):
    target = "local"


class InitialSubGraphsTPU(InitialSubGraphsBase):
    target = "tpu"


class MergeSubGraphsBase(BaseTask):
    """Merge per-block graphs into the global graph (reference:
    ``merge_sub_graphs.py``; the scale hierarchy collapses to one tree-merge
    on the driver since block graphs are tiny host artifacts here)."""

    task_name = "merge_sub_graphs"

    def run_impl(self):
        cfg = self.get_config()
        shape = handoff.resolve_dataset(
            cfg["input_path"], cfg["input_key"]
        ).shape
        block_ids = blocks_in_volume(
            shape, tuple(cfg["block_shape"]), cfg.get("roi_begin"), cfg.get("roi_end")
        )
        edge_lists, node_lists = [], []
        with trace_mod.span("graph.merge", n_blocks=len(block_ids)):
            for b in block_ids:
                f = handoff.load_arrays(block_graph_path(self.tmp_folder, b))
                edge_lists.append((f["uv"], f["sizes"]))
                node_lists.append(f["nodes"])
            uv, sizes = merge_edge_lists(edge_lists)
            nodes = (
                np.unique(np.concatenate(node_lists))
                if node_lists
                else np.zeros(0, np.uint64)
            )
            # dense edge representation for solvers: rows index into `nodes`
            edges = np.searchsorted(nodes, uv).astype(np.int64)
        self.save_handoff_arrays(
            global_graph_path(self.tmp_folder),
            nodes=nodes,
            uv=uv,
            edges=edges,
            sizes=sizes,
        )
        return {"n_nodes": len(nodes), "n_edges": len(uv)}


class MergeSubGraphsLocal(MergeSubGraphsBase):
    target = "local"


class MergeSubGraphsTPU(MergeSubGraphsBase):
    target = "tpu"


class MapEdgeIdsBase(BaseTask):
    """Map each block's edges to global edge ids (reference:
    ``map_edge_ids.py``) — consumed by features merge and multicut
    subproblem extraction."""

    task_name = "map_edge_ids"

    def run_impl(self):
        cfg = self.get_config()
        shape = handoff.resolve_dataset(
            cfg["input_path"], cfg["input_key"]
        ).shape
        block_ids = blocks_in_volume(
            shape, tuple(cfg["block_shape"]), cfg.get("roi_begin"), cfg.get("roi_end")
        )
        _, uv_global, _, _ = load_global_graph(self.tmp_folder)
        self.declare_handoff_producer()

        def process(block_id: int):
            uv = handoff.load_arrays(
                block_graph_path(self.tmp_folder, block_id)
            )["uv"]
            ids = find_edge_ids(uv_global, uv)
            self.save_handoff_array(
                edge_ids_path(self.tmp_folder, block_id), ids
            )

        n = self.host_block_map(block_ids, process)
        return {"n_blocks": n}


class MapEdgeIdsLocal(MapEdgeIdsBase):
    target = "local"


class MapEdgeIdsTPU(MapEdgeIdsBase):
    target = "tpu"


class GraphWorkflow(WorkflowBase):
    """InitialSubGraphs -> MergeSubGraphs -> MapEdgeIds."""

    task_name = "graph_workflow"

    def requires(self):
        from . import graph as graph_mod
        from ..runtime.task import get_task_cls

        common = dict(
            tmp_folder=self.tmp_folder,
            config_dir=self.config_dir,
            max_jobs=self.max_jobs,
        )
        p = self.params
        keys = {
            k: p[k]
            for k in ("input_path", "input_key", "block_shape", "roi_begin", "roi_end")
            if k in p
        }
        t1 = get_task_cls(graph_mod, "InitialSubGraphs", self.target)(
            **common, dependencies=self.dependencies, **keys
        )
        t2 = get_task_cls(graph_mod, "MergeSubGraphs", self.target)(
            **common, dependencies=[t1], **keys
        )
        t3 = get_task_cls(graph_mod, "MapEdgeIds", self.target)(
            **common, dependencies=[t2], **keys
        )
        return [t3]
