"""Distributed agglomeration over the octant reduce tree
(parallel/reduce_tree.py, docs/PERFORMANCE.md "Distributed agglomeration"):
topology, Morton partitions, frontier-aware contraction quality vs the
single-host engine, determinism, the degraded:unsharded_solve fallback,
task-level wiring (SolveGlobal / agglomerative clustering / stitching),
solver observability in manifests + io_metrics.json, and the <10 s
bench-solve smoke twin (tier-1; cpu)."""

import json
import os

import numpy as np
import pytest

from cluster_tools_tpu.ops.contraction import gaec_parallel
from cluster_tools_tpu.ops.multicut import multicut_energy
from cluster_tools_tpu.parallel import reduce_tree as rt
from cluster_tools_tpu.runtime import faults
from cluster_tools_tpu.utils import function_utils as fu
from cluster_tools_tpu.utils.synthetic import grid_rag


@pytest.fixture(autouse=True)
def _clean_injector():
    yield
    faults.reset()


def _grid_problem(g=10, seed=0, shards=4):
    n, edges, costs = grid_rag(g=g, seed=seed)
    pos = np.stack(np.unravel_index(np.arange(n), (g, g, g)), axis=1)
    return n, edges, costs, rt.morton_node_shards(pos, shards)


# -- topology -----------------------------------------------------------------


def test_tree_levels_fanout2():
    levels = rt.reduce_tree_levels(8, 2)
    # leaves (one singleton group per shard), then fanout-2 merges to root
    assert [len(l) for l in levels] == [8, 4, 2, 1]
    assert levels[0][0] == (0,) and levels[-1] == [(0, 1)]


def test_tree_levels_ragged_fanout():
    levels = rt.reduce_tree_levels(5, 3)
    assert [len(l) for l in levels] == [5, 2, 1]
    assert levels[1] == [(0, 1, 2), (3, 4)]


def test_tree_levels_single_shard_has_one_root_level():
    assert rt.reduce_tree_levels(1, 2) == [[(0,)]]


def test_tree_levels_rejects_bad_args():
    with pytest.raises(ValueError):
        rt.reduce_tree_levels(0, 2)
    with pytest.raises(ValueError):
        rt.reduce_tree_levels(4, 1)


# -- partitions ---------------------------------------------------------------


def test_morton_shards_are_octant_contiguous():
    g = 8
    pos = np.stack(
        np.unravel_index(np.arange(g ** 3), (g, g, g)), axis=1
    )
    shards = rt.morton_node_shards(pos, 8)
    assert shards.min() == 0 and shards.max() == 7
    # balanced: each shard holds exactly one octant's worth of nodes
    counts = np.bincount(shards)
    assert (counts == g ** 3 // 8).all()
    # octant purity: an aligned half-grid corner block maps to ONE shard
    corner = (pos < 4).all(axis=1)
    assert len(set(shards[corner].tolist())) == 1


def test_contiguous_shards_balanced_and_monotone():
    s = rt.contiguous_node_shards(10, 3)
    assert (np.diff(s) >= 0).all()
    assert s.min() == 0 and s.max() == 2
    assert rt.contiguous_node_shards(2, 8).max() == 1  # capped at n_nodes


# -- the sharded solve --------------------------------------------------------


def test_sharded_solve_matches_single_host_energy_within_0p1pct():
    n, edges, costs, node_shard = _grid_problem(g=12, shards=4)
    lab_single = gaec_parallel(n, edges, costs, impl="numpy")
    lab_tree, info = rt.sharded_solve(n, edges, costs, node_shard, fanout=2)
    e_single = multicut_energy(edges, costs, lab_single)
    e_tree = multicut_energy(edges, costs, lab_tree)
    gap = abs(e_tree - e_single) / abs(e_single)
    assert gap <= 1e-3, f"energy gap {100 * gap:.3f}% > 0.1%"
    assert info["sharded"] and info["shards"] == 4
    assert len(info["levels"]) == 3
    # per-level observability: edge counts + timings recorded
    for lvl in info["levels"]:
        assert lvl["edges_in"] >= lvl["edges_out"] >= 0
        assert lvl["solve_s"] >= 0 and lvl["merge_s"] >= 0


def test_sharded_solve_deterministic_across_reruns_and_pool_widths():
    n, edges, costs, node_shard = _grid_problem(g=10, shards=4)
    lab1, _ = rt.sharded_solve(n, edges, costs, node_shard, max_workers=4)
    lab2, _ = rt.sharded_solve(n, edges, costs, node_shard, max_workers=1)
    lab3, _ = rt.sharded_solve(n, edges, costs, node_shard, max_workers=4)
    assert np.array_equal(lab1, lab2)
    assert np.array_equal(lab1, lab3)


def test_sharded_solve_average_linkage_mode():
    """mode='min' with (weight*size, size) payload — the agglomerative
    clustering contract — produces a sane clustering close to the
    single-host average linkage."""
    from cluster_tools_tpu.ops.contraction import average_parallel

    rng = np.random.default_rng(3)
    n, edges, _ = grid_rag(g=8, seed=3)
    probs = rng.random(len(edges))
    sizes = np.ones(len(edges))
    payload = np.stack([probs * sizes, sizes], axis=1)
    node_shard = rt.contiguous_node_shards(n, 4)
    lab_tree, _ = rt.sharded_solve(
        n, edges, payload, node_shard, mode="min", threshold=0.3
    )
    lab_single = average_parallel(n, edges, probs, sizes, 0.3, impl="numpy")
    # not necessarily identical (hierarchical order), but same regime
    k_tree = lab_tree.max() + 1
    k_single = lab_single.max() + 1
    assert 0 < k_tree <= n
    assert abs(k_tree - k_single) / k_single < 0.15


def test_sharded_solve_carries_lifted_edges():
    """Lifted edges relabel through every level, internal ones join the
    node solves, and a strongly repulsive lifted pair stays separated."""
    n, edges, costs, node_shard = _grid_problem(g=6, shards=2)
    # a long-range strongly repulsive constraint between two grid corners
    lifted_edges = np.array([[0, n - 1]], np.int64)
    lifted_costs = np.array([-1e4])
    lab, info = rt.sharded_solve(
        n, edges, costs, node_shard,
        lifted_edges=lifted_edges, lifted_payload=lifted_costs,
    )
    assert info["sharded"]
    assert lab[0] != lab[n - 1]


def test_tree_rounds_counted_for_frontier_solves():
    """The reduce tree's contraction rounds land in the process counters
    (the observability satellite): interior leaf merges on a grid RAG must
    tick tree_rounds."""
    n, edges, costs, node_shard = _grid_problem(g=10, shards=4)
    snap = rt.solve_snapshot()
    rt.sharded_solve(n, edges, costs, node_shard)
    delta = rt.solve_delta(snap)
    assert delta["tree_rounds"] > 0
    assert delta["sharded_solves"] == 1 and delta["solve_shards"] == 4
    assert delta["boundary_edges_in"] == len(edges)
    assert 0 < delta["boundary_edges_out"] < len(edges)


def test_frontier_contraction_defers_boundary_best_nodes():
    """A node whose best edge is external abstains: the 2-chain a-b with a
    stronger frontier edge at b contracts nothing; without the frontier
    edge it contracts."""
    edges = np.array([[0, 1]], np.int64)
    payload = np.array([[1.0]])
    # no frontier: the pair merges
    lab = rt.frontier_contraction(
        2, edges, payload,
        np.zeros(0, np.int64), np.zeros(0, np.int64), np.zeros((0, 1)),
    )
    assert lab[0] == lab[1]
    # frontier edge at node 1 with higher priority: node 1 abstains
    lab = rt.frontier_contraction(
        2, edges, payload,
        np.array([1]), np.array([7]), np.array([[5.0]]),
    )
    assert lab[0] != lab[1]


# -- the attributed entry point ----------------------------------------------


def _entry_kwargs(tmp_path, **over):
    kw = dict(
        solver_shards=4,
        fanout=2,
        failures_path=str(tmp_path / "failures.json"),
        task_name="unit_solve",
        unsharded=None,
    )
    kw.update(over)
    return kw


def test_solve_entry_degenerate_single_shard_is_exact():
    n, edges, costs, node_shard = _grid_problem(g=6, shards=2)
    expect = gaec_parallel(n, edges, costs, impl="numpy")
    labels, info = rt.solve_with_reduce_tree(
        n, edges, costs,
        node_shard=node_shard,
        solver_shards=1,
        fanout=2,
        failures_path="/nonexistent/failures.json",
        task_name="unit",
        unsharded=lambda: expect,
    )
    assert info == {"sharded": False, "shards": 1}
    assert labels is expect


def test_solve_entry_degrades_to_unsharded_on_injected_fault(tmp_path):
    """A `solve` fault forces the fallback: the result is the single-host
    labels bit-for-bit and failures.json attributes
    degraded:unsharded_solve."""
    n, edges, costs, node_shard = _grid_problem(g=6, shards=2)
    expect = gaec_parallel(n, edges, costs, impl="numpy")
    faults.configure(
        {"faults": [{"site": "solve", "kind": "error", "fail_attempts": 9}]}
    )
    snap = rt.solve_snapshot()
    labels, info = rt.solve_with_reduce_tree(
        n, edges, costs,
        node_shard=node_shard,
        **_entry_kwargs(tmp_path, unsharded=lambda: expect),
    )
    faults.reset()
    assert np.array_equal(labels, expect)
    assert info["degraded"] == "unsharded_solve"
    assert rt.solve_delta(snap)["unsharded_fallbacks"] == 1
    doc = json.loads((tmp_path / "failures.json").read_text())
    recs = [r for r in doc["records"] if r["task"] == "unit_solve"]
    assert len(recs) == 1
    assert recs[0]["resolution"] == "degraded:unsharded_solve"
    assert recs[0]["resolved"] and recs[0]["sites"] == {"solve": 1}


def test_solve_entry_resolves_partition_thunk_inside_ladder(tmp_path):
    """Partition construction (a thunk re-opening block geometry) runs
    inside the fallback ladder: a raising thunk degrades with attribution,
    a None-returning thunk (no geometry) goes single-host silently."""
    n, edges, costs, node_shard = _grid_problem(g=6, shards=2)
    expect = gaec_parallel(n, edges, costs, impl="numpy")

    def boom():
        raise OSError("ws store unreachable at solve time")

    labels, info = rt.solve_with_reduce_tree(
        n, edges, costs,
        node_shard=boom,
        **_entry_kwargs(tmp_path, unsharded=lambda: expect),
    )
    assert np.array_equal(labels, expect)
    assert info["degraded"] == "unsharded_solve"
    doc = json.loads((tmp_path / "failures.json").read_text())
    assert any(
        r["resolution"] == "degraded:unsharded_solve" for r in doc["records"]
    )
    # a thunk resolving to None is NOT a failure: no record, no fallback
    snap = rt.solve_snapshot()
    labels, info = rt.solve_with_reduce_tree(
        n, edges, costs,
        node_shard=lambda: None,
        **_entry_kwargs(
            tmp_path / "none", unsharded=lambda: expect,
            failures_path=str(tmp_path / "none_failures.json"),
        ),
    )
    assert np.array_equal(labels, expect)
    assert info == {"sharded": False, "shards": 1}
    assert rt.solve_delta(snap)["unsharded_fallbacks"] == 0
    assert not (tmp_path / "none_failures.json").exists()
    # and a working thunk runs the sharded path
    labels, info = rt.solve_with_reduce_tree(
        n, edges, costs,
        node_shard=lambda: node_shard,
        **_entry_kwargs(tmp_path, unsharded=lambda: expect, solver_shards=2),
    )
    assert info["sharded"] is True and info["shards"] == 2


def test_solve_entry_degrades_when_worker_group_cannot_form(tmp_path):
    """workers > 1 without a scratch_dir (or any worker failure) must fall
    back, not crash."""
    n, edges, costs, node_shard = _grid_problem(g=6, shards=2)
    expect = gaec_parallel(n, edges, costs, impl="numpy")
    labels, info = rt.solve_with_reduce_tree(
        n, edges, costs,
        node_shard=node_shard,
        **_entry_kwargs(
            tmp_path, unsharded=lambda: expect, workers=2, scratch_dir=None
        ),
    )
    assert np.array_equal(labels, expect)
    assert info["degraded"] == "unsharded_solve"


# -- task-level wiring --------------------------------------------------------


def _run_multicut(tmp_path, name, **extra):
    from cluster_tools_tpu.runtime.task import build
    from cluster_tools_tpu.workflows import MulticutSegmentationWorkflow

    from .test_multicut_workflow import _write_ds, make_case

    root = tmp_path / name
    tmp_folder = str(root / "tmp")
    config_dir = str(root / "config")
    os.makedirs(config_dir, exist_ok=True)
    with open(os.path.join(config_dir, "global.config"), "w") as f:
        json.dump({"block_shape": [8, 8, 8]}, f)
    gt, sv, bmap = make_case()
    path = os.path.join(str(root), "data.zarr")
    _write_ds(path, "bmap", bmap)
    _write_ds(path, "sv", sv)
    kw = dict(
        tmp_folder=tmp_folder,
        config_dir=config_dir,
        max_jobs=4,
        target="local",
        input_path=path,
        input_key="bmap",
        ws_path=path,
        ws_key="sv",
        output_path=path,
        output_key="seg",
        skip_ws=True,
        n_scales=1,
        beta=0.5,
    )
    kw.update(extra)
    wf = MulticutSegmentationWorkflow(**kw)
    assert build([wf])
    from cluster_tools_tpu.utils.volume_utils import file_reader

    return tmp_folder, np.asarray(file_reader(path)["seg"][:])


def test_solve_global_sharded_task_wiring(tmp_path):
    """SolveGlobal with solver_shards=2: the workflow completes, the
    manifest carries the solver observability block (sharded tree shape),
    io_metrics.json carries the counters, and the segmentation matches
    the unsharded run's (the oracle case is unambiguous)."""
    tmp1, seg1 = _run_multicut(tmp_path, "unsharded")
    # n_scales=0: SolveGlobal sees the full (attractive) RAG, so the
    # sharded tree actually contracts (rounds > 0) instead of inheriting
    # an already-reduced all-repulsive residual
    tmp2, seg2 = _run_multicut(
        tmp_path, "sharded",
        solver_shards=2, reduce_fanout=2, agglomerator="gaec_parallel",
        n_scales=0,
    )
    from .helpers import assert_labels_equivalent

    assert_labels_equivalent(seg1, seg2)
    # manifest observability
    solve_manifest = None
    for fn in os.listdir(tmp2):
        if fn.startswith("solve_global") and fn.endswith(".success.json"):
            solve_manifest = json.load(open(os.path.join(tmp2, fn)))
    assert solve_manifest is not None
    solver = solve_manifest["solver"]
    assert solver["sharded"] is True and solver["shards"] == 2
    assert solver["edges_in"] > 0 and solver["energy"] is not None
    # rounds are reported by the numpy/frontier rungs; the native root
    # rung is bit-parity but does not count its loop (docstring) — here
    # the leaves correctly abstain (every attractive edge crosses the
    # z-plane between the two octants), so only assert presence
    assert solver["rounds"] >= 0 and "rounds" in solver
    assert [l["groups"] for l in solver["levels"]] == [2, 1]
    assert solver["levels"][-1]["internal_edges"] > 0  # root solved them
    # io_metrics attribution
    metrics = json.load(open(fu.io_metrics_path(tmp2)))
    solve_tasks = {
        uid: m for uid, m in metrics["tasks"].items()
        if uid.startswith("solve_global")
    }
    assert solve_tasks
    m = next(iter(solve_tasks.values()))
    assert m["sharded_solves"] == 1 and m["solve_shards"] == 2
    assert m["boundary_edges_in"] > 0
    # the unsharded twin's solve manifests carry the observability block
    # too (every solve, not just sharded ones)
    for prefix in ("solve_global", "solve_subproblems"):
        docs = [
            json.load(open(os.path.join(tmp1, fn)))
            for fn in os.listdir(tmp1)
            if fn.startswith(prefix) and fn.endswith(".success.json")
        ]
        assert docs and all("solver" in d for d in docs)
    assert docs[0]["solver"]["edges_in"] >= 0


def test_agglomerative_clustering_sharded(tmp_path):
    """The agglomerative task completes sharded and emits the solver
    block; the clustering stays in the unsharded run's regime."""
    from cluster_tools_tpu.runtime.task import build
    from cluster_tools_tpu.tasks.agglomerative_clustering import (
        AgglomerativeClusteringLocal,
        agglomerative_assignments_path,
    )
    from cluster_tools_tpu.tasks.features import EdgeFeaturesWorkflow
    from cluster_tools_tpu.tasks.graph import GraphWorkflow

    from .test_multicut_workflow import _write_ds, make_case

    _, sv, bmap = make_case()
    root = str(tmp_path)
    config_dir = os.path.join(root, "config")
    os.makedirs(config_dir, exist_ok=True)
    with open(os.path.join(config_dir, "global.config"), "w") as f:
        json.dump({"block_shape": [8, 8, 8]}, f)
    path = os.path.join(root, "data.zarr")
    _write_ds(path, "bmap", bmap)
    _write_ds(path, "sv", sv)

    results = {}
    for name, shards in (("unsharded", 1), ("sharded", 2)):
        tmp_folder = os.path.join(root, name)
        common = dict(
            tmp_folder=tmp_folder, config_dir=config_dir, max_jobs=2
        )
        g = GraphWorkflow(
            **common, target="local", input_path=path, input_key="sv"
        )
        feats = EdgeFeaturesWorkflow(
            **common, target="local", dependencies=[g],
            input_path=path, input_key="bmap",
            labels_path=path, labels_key="sv",
        )
        task = AgglomerativeClusteringLocal(
            **common, dependencies=[feats], threshold=0.7,
            solver_shards=shards, impl="numpy",
        )
        assert build([task])
        with np.load(agglomerative_assignments_path(tmp_folder)) as f:
            results[name] = f["values"].copy()
        manifest = task.output().read()
        assert "solver" in manifest
        assert manifest["solver"]["sharded"] is (shards > 1)
    k1 = len(np.unique(results["unsharded"]))
    k2 = len(np.unique(results["sharded"]))
    assert abs(k1 - k2) <= max(2, 0.2 * k1)


# -- report rendering ---------------------------------------------------------


def test_failures_report_renders_solver_metrics(tmp_path, capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "failures_report",
        os.path.join(
            os.path.dirname(os.path.dirname(__file__)),
            "scripts", "failures_report.py",
        ),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    lines = mod.format_io_metrics({
        "solve_global.abc": {
            "solver_calls": 3, "solver_rounds": 17,
            "solver_edges_in": 1000, "solver_edges_out": 120,
            "sharded_solves": 1, "solve_shards": 4, "solve_levels": 2,
            "tree_rounds": 9, "boundary_edges_in": 1000,
            "boundary_edges_out": 80, "tree_solve_s": 0.5,
            "tree_merge_s": 0.1, "unsharded_fallbacks": 1,
        },
    })
    text = "\n".join(lines)
    assert "3 solve(s), 26 contraction round(s)" in text
    assert "edges 1000 -> 120 surviving" in text
    assert "4 shard(s) over 2 level(s)" in text
    assert "1 unsharded fallback(s)" in text


def test_bench_trajectory_script(tmp_path):
    """The aggregator reads every BENCH_r*.json shape and emits one row
    per round."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_trajectory",
        os.path.join(
            os.path.dirname(os.path.dirname(__file__)),
            "scripts", "bench_trajectory.py",
        ),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rows = mod.collect_rows()
    assert len(rows) >= 10
    table = mod.render_table(rows)
    assert table.count("| r") >= 10
    # every known shape produced a real headline
    by_round = {r["round"]: r for r in rows}
    assert not any("no extractor" in r["headline"] for r in rows)
    assert "dispatches" in by_round[7]["headline"]
    assert "intermediate storage" in by_round[8]["headline"]
    assert "energy gap" in by_round[9]["headline"]
    # marker-delimited doc rewrite is idempotent and non-destructive
    doc = tmp_path / "PERF.md"
    doc.write_text(
        f"# head\n\n{mod.MARK_BEGIN}\nstale\n{mod.MARK_END}\n\n# tail\n"
    )
    assert mod.write_doc(table, str(doc))
    text = doc.read_text()
    assert "stale" not in text and "# head" in text and "# tail" in text
    assert table in text


# -- the bench smoke twin -----------------------------------------------------


def test_bench_solve_smoke():
    """<10 s twin of `make bench-solve`: gap within 0.1%, deterministic,
    and the 2-worker group bit-identical to the in-process tree."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(
            os.path.dirname(os.path.dirname(__file__)), "bench.py"
        )
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    rec = bench.solve_bench(smoke=True)
    assert rec["smoke"] is True
    assert rec["gap_within_0p1pct"] is True
    assert rec["reduce_tree"]["deterministic_across_reruns"] is True
    assert rec["worker_group"]["bit_identical_to_in_process"] is True
