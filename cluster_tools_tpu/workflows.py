"""End-to-end segmentation workflows.

Re-design of the reference's ``cluster_tools/workflows.py`` (SURVEY.md §2a
"Workflows", §3.3): the flagship ``MulticutSegmentationWorkflow`` chains

    watershed (supervoxels) -> graph -> edge features -> costs
    -> hierarchical multicut -> write

with each stage the task family from :mod:`.tasks`.  Workflow classes follow
the reference's pattern: one class per pipeline, ``target=`` selecting the
backend trio member, parameters forwarded to the stage tasks, and
``get_config()`` aggregating every stage's defaults for the config_dir.
"""

from __future__ import annotations

import os
from typing import Any, Dict

from .runtime.task import WorkflowBase, get_task_cls
from .utils import function_utils as fu
from .tasks import costs as costs_mod
from .tasks import features as feat_mod
from .tasks import fused as fused_mod
from .tasks import graph as graph_mod
from .tasks import multicut as mc_mod
from .tasks import watershed as ws_mod
from .tasks import write as write_mod
from .tasks.multicut import assignments_path


def _pick(p: Dict[str, Any], *names: str) -> Dict[str, Any]:
    return {k: p[k] for k in names if k in p}


def _tasks_below(task, seen=None):
    """Every task of ``task``'s DAG but itself, each uid once."""
    seen = set() if seen is None else seen
    for dep in task.requires():
        if dep.uid not in seen:
            seen.add(dep.uid)
            yield dep
            yield from _tasks_below(dep, seen)


class MulticutSegmentationWorkflow(WorkflowBase):
    """boundary map -> supervoxels -> RAG -> features -> costs -> multicut
    -> segmentation.

    Params:
      ``input_path/input_key``    boundary/affinity map (float),
      ``ws_path/ws_key``          supervoxel dataset (created unless
                                  ``skip_ws``); ``ws_path`` defaults to
                                  ``output_path``,
      ``output_path/output_key``  final segmentation,
      ``skip_ws``                 use an existing supervoxel dataset,
      ``two_pass_ws``             checkerboard two-pass watershed,
      ``execution``               left out: the blockwise watershed chain
                                  makes the supervoxels.  ``"fused"`` /
                                  ``"split"``: the ROI fits the device(s),
                                  and the mesh-resident step
                                  (:mod:`.tasks.fused`, which this is a
                                  parameter of) makes them in one program,
      watershed params (``threshold``, ``sigma_seeds``, ``halo``, ...; with
      ``execution`` the fused task's: ``dt_max_distance``, ``impl``,
      ``decomposition``, ...),
      ``channel``                 boundary-map channel selector for features,
      ``beta``/``weighting_scheme`` cost transform,
      ``n_scales``                subproblem levels,
      ``agglomerator``            solver key for subproblems + global solve.
    """

    task_name = "multicut_segmentation_workflow"

    def requires(self):
        p = self.params
        common = dict(
            tmp_folder=self.tmp_folder,
            config_dir=self.config_dir,
            max_jobs=self.max_jobs,
        )
        ws_path, ws_key = p.get("ws_path") or p["output_path"], p["ws_key"]
        deps = list(self.dependencies)
        grid = _pick(p, "block_shape", "roi_begin", "roi_end")

        if not p.get("skip_ws", False):
            deps = [self._supervoxels(common, deps, ws_path, ws_key, grid)]

        g = graph_mod.GraphWorkflow(
            **common,
            target=self.target,
            dependencies=deps,
            input_path=ws_path,
            input_key=ws_key,
            **grid,
        )
        feats = feat_mod.EdgeFeaturesWorkflow(
            **common,
            target=self.target,
            dependencies=[g],
            input_path=p["input_path"],
            input_key=p["input_key"],
            labels_path=ws_path,
            labels_key=ws_key,
            **_pick(p, "channel"),
            **grid,
        )
        costs = get_task_cls(costs_mod, "ProbsToCosts", self.target)(
            **common,
            dependencies=[feats],
            **_pick(p, "beta", "weighting_scheme", "weighting_exponent"),
        )
        mc = mc_mod.MulticutWorkflow(
            **common,
            target=self.target,
            dependencies=[costs],
            input_path=ws_path,
            input_key=ws_key,
            **_pick(
                p, "n_scales", "agglomerator",
                "solver_shards", "reduce_fanout", "solver_workers",
            ),
            **grid,
        )
        write = get_task_cls(write_mod, "Write", self.target)(
            **common,
            dependencies=[mc],
            input_path=ws_path,
            input_key=ws_key,
            output_path=p["output_path"],
            output_key=p["output_key"],
            assignment_path=assignments_path(self.tmp_folder),
            **_pick(p, "block_shape"),
        )
        return [write]

    def _supervoxels(self, common, deps, ws_path, ws_key, grid):
        """The task that makes the supervoxels: the mesh-resident step where
        ``execution`` is given (its watershed output only, no ``cc_key``;
        every other parameter the fused task's own), else the blockwise
        watershed chain."""
        p = self.params
        if p.get("execution") is not None:
            return get_task_cls(fused_mod, "FusedSegmentation", self.target)(
                **common,
                dependencies=deps,
                input_path=p["input_path"],
                input_key=p["input_key"],
                output_path=ws_path,
                ws_key=ws_key,
                **_pick(p, *fused_mod.FusedSegmentationBase.default_task_config()),
                **grid,
            )
        return ws_mod.WatershedWorkflow(
            **common,
            target=self.target,
            dependencies=deps,
            input_path=p["input_path"],
            input_key=p["input_key"],
            output_path=ws_path,
            output_key=ws_key,
            two_pass=p.get("two_pass_ws", False),
            **_pick(
                p,
                "threshold",
                "sigma_seeds",
                "min_seed_distance",
                "sampling",
                "size_filter",
                "two_d",
                "halo",
                "block_shape",
                "mask_path",
                "mask_key",
            ),
        )

    def run_impl(self):
        """What the chain did, gathered from its tasks' manifests into the
        workflow's own and into ``io_metrics.json`` (docs/OBSERVABILITY.md
        "Multicut"): the graph's size, the device dispatches of the two
        voxel-bound stages, how often a consumer was served from memory or
        went to the store, the energy of the partition, and where the
        fused step came from."""
        docs: Dict[str, Dict[str, Any]] = {}
        for task in _tasks_below(self):
            try:
                docs[task.task_name] = task.output().read()
            except OSError:
                continue

        def of(task_name, *keys):
            doc = docs.get(task_name, {})
            for k in keys:
                doc = (doc or {}).get(k)
            return doc

        io = [d.get("io_metrics") or {} for d in docs.values()]
        summary = {
            "n_blocks": of("initial_sub_graphs", "n_blocks"),
            "n_nodes": of("merge_sub_graphs", "n_nodes"),
            "n_edges": of("merge_sub_graphs", "n_edges"),
            "n_segments": of("solve_global", "n_segments"),
            "energy": of("solve_global", "energy"),
            "rag_dispatches": {
                "graph": of("initial_sub_graphs", "io_metrics", "rag_dispatches"),
                "features": of("block_edge_features", "io_metrics", "rag_dispatches"),
            },
            "handoff_hits": sum(m.get("handoffs_served", 0) for m in io),
            "store_reads": sum(
                m.get("misses", 0) + m.get("direct_reads", 0) for m in io),
            "store_read_bytes": sum(m.get("bytes_from_storage", 0) for m in io),
        }
        step = of("fused_segmentation", "step_cache")
        if step:
            summary["step_cache"] = _pick(step, "from", "fallback")
        fu.record_io_metrics(
            fu.io_metrics_path(self.tmp_folder), self.uid, {"multicut": summary}
        )
        return {"multicut": summary}

    @staticmethod
    def get_config() -> Dict[str, Dict[str, Any]]:
        """Aggregated per-task default configs (reference pattern: workflows
        expose ``get_config()`` so users can materialize + edit the JSONs)."""
        return {
            "global": WorkflowBase.default_global_config(),
            "fused_segmentation": fused_mod.FusedSegmentationBase.default_task_config(),
            "watershed": ws_mod.WatershedBase.default_task_config(),
            "two_pass_watershed": ws_mod.TwoPassWatershedBase.default_task_config(),
            "initial_sub_graphs": graph_mod.InitialSubGraphsBase.default_task_config(),
            "block_edge_features": feat_mod.BlockEdgeFeaturesBase.default_task_config(),
            "probs_to_costs": costs_mod.ProbsToCostsBase.default_task_config(),
            "solve_subproblems": mc_mod.SolveSubproblemsBase.default_task_config(),
            "solve_global": mc_mod.SolveGlobalBase.default_task_config(),
        }


class AgglomerativeClusteringWorkflow(WorkflowBase):
    """boundary map -> supervoxels -> RAG -> features -> average-linkage
    agglomeration -> segmentation (reference:
    ``AgglomerativeClusteringWorkflow``).

    Same parameters as :class:`MulticutSegmentationWorkflow` minus the
    multicut ones, plus ``agglomeration_threshold`` (merge edges while the
    mean boundary probability is below it)."""

    task_name = "agglomerative_clustering_workflow"

    def requires(self):
        from .tasks import agglomerative_clustering as ac_mod
        from .tasks.agglomerative_clustering import agglomerative_assignments_path

        p = self.params
        common = dict(
            tmp_folder=self.tmp_folder,
            config_dir=self.config_dir,
            max_jobs=self.max_jobs,
        )
        ws_path, ws_key = p["ws_path"], p["ws_key"]
        deps = list(self.dependencies)
        if not p.get("skip_ws", False):
            ws = ws_mod.WatershedWorkflow(
                **common,
                target=self.target,
                dependencies=deps,
                input_path=p["input_path"],
                input_key=p["input_key"],
                output_path=ws_path,
                output_key=ws_key,
                two_pass=p.get("two_pass_ws", False),
                **_pick(
                    p,
                    "threshold",
                    "sigma_seeds",
                    "min_seed_distance",
                    "sampling",
                    "size_filter",
                    "two_d",
                    "halo",
                    "block_shape",
                    "mask_path",
                    "mask_key",
                ),
            )
            deps = [ws]
        grid = _pick(p, "block_shape", "roi_begin", "roi_end")
        g = graph_mod.GraphWorkflow(
            **common,
            target=self.target,
            dependencies=deps,
            input_path=ws_path,
            input_key=ws_key,
            **grid,
        )
        feats = feat_mod.EdgeFeaturesWorkflow(
            **common,
            target=self.target,
            dependencies=[g],
            input_path=p["input_path"],
            input_key=p["input_key"],
            labels_path=ws_path,
            labels_key=ws_key,
            **_pick(p, "channel"),
            **grid,
        )
        ac = get_task_cls(ac_mod, "AgglomerativeClustering", self.target)(
            **common,
            dependencies=[feats],
            threshold=p.get("agglomeration_threshold", 0.5),
        )
        write = get_task_cls(write_mod, "Write", self.target)(
            **common,
            dependencies=[ac],
            input_path=ws_path,
            input_key=ws_key,
            output_path=p["output_path"],
            output_key=p["output_key"],
            assignment_path=agglomerative_assignments_path(self.tmp_folder),
            **_pick(p, "block_shape"),
        )
        return [write]


    @staticmethod
    def get_config() -> Dict[str, Dict[str, Any]]:
        """Aggregated per-task default configs (reference pattern)."""
        from .tasks import agglomerative_clustering as ac_mod

        return {
            "global": WorkflowBase.default_global_config(),
            "watershed": ws_mod.WatershedBase.default_task_config(),
            "initial_sub_graphs": graph_mod.InitialSubGraphsBase.default_task_config(),
            "block_edge_features": feat_mod.BlockEdgeFeaturesBase.default_task_config(),
            "agglomerative_clustering":
                ac_mod.AgglomerativeClusteringBase.default_task_config(),
        }


class LiftedMulticutSegmentationWorkflow(WorkflowBase):
    """Lifted multicut segmentation (reference:
    ``LiftedMulticutSegmentationWorkflow``): the multicut chain plus a
    node-label attribution that induces sparse lifted edges —

        ws -> graph -> features -> costs
           -> node_labels (overlap with ``labels_path/labels_key``, e.g. a
              nucleus or semantic segmentation)
           -> sparse lifted neighborhood -> lifted costs
           -> hierarchical lifted multicut -> write

    Extra params over :class:`MulticutSegmentationWorkflow`:
    ``labels_path/labels_key`` (the attribution volume),
    ``max_graph_distance``, ``w_attractive``/``w_repulsive``."""

    task_name = "lifted_multicut_segmentation_workflow"

    def requires(self):
        from .tasks import lifted_features as lf_mod
        from .tasks import lifted_multicut as lmc_mod
        from .tasks import node_labels as nl_mod
        from .tasks.lifted_multicut import lmc_assignments_path

        p = self.params
        common = dict(
            tmp_folder=self.tmp_folder,
            config_dir=self.config_dir,
            max_jobs=self.max_jobs,
        )
        ws_path, ws_key = p["ws_path"], p["ws_key"]
        deps = list(self.dependencies)
        if not p.get("skip_ws", False):
            ws = ws_mod.WatershedWorkflow(
                **common,
                target=self.target,
                dependencies=deps,
                input_path=p["input_path"],
                input_key=p["input_key"],
                output_path=ws_path,
                output_key=ws_key,
                two_pass=p.get("two_pass_ws", False),
                **_pick(
                    p,
                    "threshold",
                    "sigma_seeds",
                    "min_seed_distance",
                    "sampling",
                    "size_filter",
                    "two_d",
                    "halo",
                    "block_shape",
                    "mask_path",
                    "mask_key",
                ),
            )
            deps = [ws]
        grid = _pick(p, "block_shape", "roi_begin", "roi_end")
        g = graph_mod.GraphWorkflow(
            **common,
            target=self.target,
            dependencies=deps,
            input_path=ws_path,
            input_key=ws_key,
            **grid,
        )
        feats = feat_mod.EdgeFeaturesWorkflow(
            **common,
            target=self.target,
            dependencies=[g],
            input_path=p["input_path"],
            input_key=p["input_key"],
            labels_path=ws_path,
            labels_key=ws_key,
            **_pick(p, "channel"),
            **grid,
        )
        costs = get_task_cls(costs_mod, "ProbsToCosts", self.target)(
            **common,
            dependencies=[feats],
            **_pick(p, "beta", "weighting_scheme", "weighting_exponent"),
        )
        nl = nl_mod.NodeLabelWorkflow(
            **common,
            target=self.target,
            dependencies=[g],
            input_path=ws_path,
            input_key=ws_key,
            labels_path=p["labels_path"],
            labels_key=p["labels_key"],
            **grid,
        )
        lifted_nh = get_task_cls(
            lf_mod, "SparseLiftedNeighborhood", self.target
        )(
            **common,
            dependencies=[g],
            **_pick(p, "max_graph_distance"),
        )
        lifted_costs = get_task_cls(lf_mod, "CostsFromNodeLabels", self.target)(
            **common,
            dependencies=[nl, lifted_nh],
            **_pick(p, "w_attractive", "w_repulsive"),
        )
        lmc = lmc_mod.LiftedMulticutWorkflow(
            **common,
            target=self.target,
            dependencies=[costs, lifted_costs],
            input_path=ws_path,
            input_key=ws_key,
            **_pick(
                p, "n_scales",
                "solver_shards", "reduce_fanout", "solver_workers",
            ),
            **grid,
        )
        write = get_task_cls(write_mod, "Write", self.target)(
            **common,
            dependencies=[lmc],
            input_path=ws_path,
            input_key=ws_key,
            output_path=p["output_path"],
            output_key=p["output_key"],
            assignment_path=lmc_assignments_path(self.tmp_folder),
            **_pick(p, "block_shape"),
        )
        return [write]

    @staticmethod
    def get_config() -> Dict[str, Dict[str, Any]]:
        """Aggregated per-task default configs (reference pattern)."""
        from .tasks import lifted_features as lf_mod
        from .tasks import lifted_multicut as lmc_mod
        from .tasks import node_labels as nl_mod

        return {
            "global": WorkflowBase.default_global_config(),
            "watershed": ws_mod.WatershedBase.default_task_config(),
            "initial_sub_graphs": graph_mod.InitialSubGraphsBase.default_task_config(),
            "block_edge_features": feat_mod.BlockEdgeFeaturesBase.default_task_config(),
            "probs_to_costs": costs_mod.ProbsToCostsBase.default_task_config(),
            "block_node_labels": nl_mod.BlockNodeLabelsBase.default_task_config(),
            "sparse_lifted_neighborhood":
                lf_mod.SparseLiftedNeighborhoodBase.default_task_config(),
            "costs_from_node_labels":
                lf_mod.CostsFromNodeLabelsBase.default_task_config(),
            "solve_lifted_subproblems":
                lmc_mod.SolveLiftedSubproblemsBase.default_task_config(),
            "solve_lifted_global":
                lmc_mod.SolveLiftedGlobalBase.default_task_config(),
        }
