"""Collective reduce plane (parallel/reduce_tree.py, docs/PERFORMANCE.md
"Collective reduce plane"): bit-identity of the collective level engine
vs the host/packet path across fan-ins and ragged boundary widths, the
degrade ladder (init failure, hop deadline, env kill-switch — each rung
attributed ``degraded:packet_plane`` and bit-identical by construction),
the counter plane (collective_hops / packet_fallbacks /
bytes_over_interconnect / contraction_dispatches), the auto-eligibility
floor, and the ``_wait_npz`` fast-fail guards (level deadline + dead
publisher pid probe).  The multi-process worker-group rungs live in the
slow-marked tests at the bottom (tier-2); everything else is tier-1 on
the in-process 8-device CPU mesh."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from cluster_tools_tpu.parallel import reduce_tree as rt
from cluster_tools_tpu.runtime import faults
from cluster_tools_tpu.utils.synthetic import grid_rag


@pytest.fixture(autouse=True)
def _clean_injector():
    yield
    faults.reset()


def _grid_problem(g=8, seed=0, shards=4):
    n, edges, costs = grid_rag(g=g, seed=seed)
    pos = np.stack(np.unravel_index(np.arange(n), (g, g, g)), axis=1)
    return n, edges, costs, rt.morton_node_shards(pos, shards)


def _solve(plane, n, edges, payload, node_shard, tmp_path=None, **over):
    kw = dict(fanout=2, reduce_plane=plane)
    if tmp_path is not None:
        kw.update(
            failures_path=str(tmp_path / "failures.json"),
            task_name="plane_solve",
        )
    kw.update(over)
    return rt.sharded_solve(n, edges, payload, node_shard, **kw)


# -- bit-identity -------------------------------------------------------------


@pytest.mark.parametrize(
    "shards,mode,threshold",
    [(2, "max", 0.0), (4, "max", 0.0), (8, "max", 0.0), (4, "min", 0.5)],
)
def test_collective_bit_identical_to_packet(shards, mode, threshold):
    """Fan-in 2/4/8 and both aggregation modes: the collective plane's
    labels are bit-for-bit the host path's — the plane choice is pure
    performance, never semantics."""
    n, edges, costs, node_shard = _grid_problem(g=8, seed=shards, shards=shards)
    lab_p, info_p = _solve("packet", n, edges, costs, node_shard,
                           mode=mode, threshold=threshold)
    snap = rt.solve_snapshot()
    lab_c, info_c = _solve("collective", n, edges, costs, node_shard,
                           mode=mode, threshold=threshold)
    assert np.array_equal(lab_p, lab_c)
    assert info_p["reduce_plane"] == "host"
    assert info_c["reduce_plane"] == "collective"
    assert all(l["plane"] == "collective" for l in info_c["levels"])
    d = rt.solve_delta(snap)
    assert d["collective_hops"] == len(info_c["levels"])
    assert d["packet_fallbacks"] == 0
    assert d["bytes_over_interconnect"] > 0


def test_collective_bit_identical_average_linkage_payload():
    """k=2 payload (weighted-mean columns, the agglomerative task's
    contract): merge-summed payload ratios survive the padded lanes."""
    n, edges, costs, node_shard = _grid_problem(g=8, seed=3, shards=4)
    sizes = np.ones_like(costs)
    payload = np.stack([np.asarray(costs, np.float64), sizes], axis=1)
    lab_p, _ = _solve("packet", n, edges, payload, node_shard,
                      mode="min", threshold=0.5)
    lab_c, info_c = _solve("collective", n, edges, payload, node_shard,
                           mode="min", threshold=0.5)
    assert np.array_equal(lab_p, lab_c)
    assert info_c["reduce_plane"] == "collective"


def test_collective_bit_identical_ragged_and_zero_edge_shards():
    """Ragged boundary widths: one fat shard, skinny siblings, and a
    shard with NO edges at all — the fixed-lane marshalling (fill pages +
    valid extents) must not invent or drop edges."""
    # 4 contiguous shards of 10 nodes; shard 3 fully isolated (zero edges)
    n = 40
    rs = np.random.default_rng(7)
    u = np.arange(0, 29)
    v = u + 1                      # chain across shards 0-2 (boundary hops)
    extra_u = rs.integers(0, 10, size=25)        # shard 0 is fat
    extra_v = rs.integers(10, 20, size=25)
    edges = np.stack(
        [np.concatenate([u, extra_u]), np.concatenate([v, extra_v])], axis=1
    ).astype(np.int64)
    keep = edges[:, 0] != edges[:, 1]
    edges = edges[keep]
    costs = rs.random(len(edges))
    node_shard = rt.contiguous_node_shards(n, 4)
    lab_p, _ = _solve("packet", n, edges, costs, node_shard)
    lab_c, info_c = _solve("collective", n, edges, costs, node_shard)
    assert np.array_equal(lab_p, lab_c)
    assert info_c["reduce_plane"] == "collective"
    # the isolated shard keeps its nodes singleton across both planes
    assert len(set(lab_c[30:40].tolist())) == 10


# -- counter plane ------------------------------------------------------------


def test_collective_counters_one_dispatch_per_level():
    """The acceptance metric: the collective plane pays ONE device
    dispatch per tree level; the host path pays one per contraction
    round (>= 2x more on any multi-round level)."""
    n, edges, costs, node_shard = _grid_problem(g=8, seed=0, shards=8)
    snap = rt.solve_snapshot()
    _, info_h = _solve("packet", n, edges, costs, node_shard)
    host = rt.solve_delta(snap)
    snap = rt.solve_snapshot()
    _, info_c = _solve("collective", n, edges, costs, node_shard)
    coll = rt.solve_delta(snap)
    levels = len(info_c["levels"])
    assert coll["contraction_dispatches"] == levels
    assert coll["collective_hops"] == levels
    assert host["collective_hops"] == 0
    # host dispatches = contraction rounds across all groups/levels
    assert host["contraction_dispatches"] >= 2 * levels


# -- degrade ladder -----------------------------------------------------------


def test_demanded_collective_init_fault_degrades_attributed(tmp_path):
    """Init-failure rung: an injected `hop` error while the plane boots
    degrades to the packet plane — bit-identical labels, a
    degraded:packet_plane failures record, and the fallback counter."""
    n, edges, costs, node_shard = _grid_problem(g=6, shards=4)
    expect, _ = _solve("packet", n, edges, costs, node_shard)
    faults.configure(
        {"faults": [{"site": "hop", "kind": "error", "fail_attempts": 9}]}
    )
    snap = rt.solve_snapshot()
    labels, info = _solve(
        "collective", n, edges, costs, node_shard, tmp_path=tmp_path
    )
    faults.reset()
    assert np.array_equal(labels, expect)
    assert info["reduce_plane"] == "host"
    d = rt.solve_delta(snap)
    assert d["packet_fallbacks"] == 1 and d["collective_hops"] == 0
    doc = json.loads((tmp_path / "failures.json").read_text())
    recs = [r for r in doc["records"] if r["task"] == "plane_solve"]
    assert len(recs) == 1
    assert recs[0]["resolution"] == "degraded:packet_plane"
    assert recs[0]["resolved"] and recs[0]["sites"] == {"hop": 1}


def test_hop_deadline_degrades_mid_solve(tmp_path):
    """Runtime rung: a hung level-0 dispatch trips the hop deadline; the
    plane was live, so the degradation is attributed, and the level (plus
    every later one) re-solves on the host path bit-identically."""
    n, edges, costs, node_shard = _grid_problem(g=6, shards=4)
    expect, _ = _solve("packet", n, edges, costs, node_shard)
    faults.configure(
        {"faults": [{"site": "hop", "kind": "hang", "blocks": [0],
                     "seconds": 2.0}]}
    )
    snap = rt.solve_snapshot()
    labels, info = _solve(
        "collective", n, edges, costs, node_shard, tmp_path=tmp_path,
        hop_deadline_s=0.3,
    )
    faults.reset()
    assert np.array_equal(labels, expect)
    assert info["reduce_plane"] == "host"
    assert "hop deadline" in info["degraded_plane"]
    assert all(l["plane"] == "host" for l in info["levels"])
    assert rt.solve_delta(snap)["packet_fallbacks"] == 1
    doc = json.loads((tmp_path / "failures.json").read_text())
    assert any(
        r["resolution"] == "degraded:packet_plane" for r in doc["records"]
    )


def test_collectives_disabled_env_is_the_fallback_arm(tmp_path):
    """The bench's fallback arm: CT_COLLECTIVES_DISABLED force-fails the
    plane init, and a demanded collective degrades with attribution."""
    n, edges, costs, node_shard = _grid_problem(g=6, shards=2)
    expect, _ = _solve("packet", n, edges, costs, node_shard)
    os.environ["CT_COLLECTIVES_DISABLED"] = "1"
    try:
        snap = rt.solve_snapshot()
        labels, info = _solve(
            "collective", n, edges, costs, node_shard, tmp_path=tmp_path
        )
    finally:
        del os.environ["CT_COLLECTIVES_DISABLED"]
    assert np.array_equal(labels, expect)
    assert info["reduce_plane"] == "host"
    assert rt.solve_delta(snap)["packet_fallbacks"] == 1
    doc = json.loads((tmp_path / "failures.json").read_text())
    assert any(
        r["resolution"] == "degraded:packet_plane" for r in doc["records"]
    )


def test_auto_plane_floor_and_override(tmp_path, monkeypatch):
    """`auto` stays on the host path below the edge floor — silently: no
    failures record, no fallback counter (probing is not a failure).
    Dropping the floor flips the same solve onto the collective plane."""
    n, edges, costs, node_shard = _grid_problem(g=6, shards=4)
    snap = rt.solve_snapshot()
    labels_h, info = _solve(
        "auto", n, edges, costs, node_shard, tmp_path=tmp_path
    )
    assert info["reduce_plane"] == "host"
    d = rt.solve_delta(snap)
    assert d["packet_fallbacks"] == 0 and d["collective_hops"] == 0
    assert not (tmp_path / "failures.json").exists()
    monkeypatch.setenv("CT_REDUCE_PLANE_MIN_EDGES", "1")
    snap = rt.solve_snapshot()
    labels_c, info = _solve(
        "auto", n, edges, costs, node_shard, tmp_path=tmp_path
    )
    assert info["reduce_plane"] == "collective"
    assert rt.solve_delta(snap)["collective_hops"] == len(info["levels"])
    assert np.array_equal(labels_h, labels_c)


@pytest.mark.parametrize("exc", [ImportError, AttributeError, TypeError])
def test_auto_plane_does_not_absorb_program_defects(tmp_path, monkeypatch, exc):
    """A moved API or a bad call inside the plane is a defect, not a fault:
    `auto` must raise it (through the unsharded-solve ladder too) instead
    of landing on the host rung with every test still green."""
    n, edges, costs, node_shard = _grid_problem(g=6, shards=4)
    monkeypatch.setenv("CT_REDUCE_PLANE_MIN_EDGES", "1")

    def broken(self, probs, level, deadline):
        raise exc("defect in the level program")

    monkeypatch.setattr(rt.CollectiveReducePlane, "_dispatch", broken)
    with pytest.raises(exc):
        _solve("auto", n, edges, costs, node_shard, tmp_path=tmp_path)
    with pytest.raises(exc):
        rt.solve_with_reduce_tree(
            n, edges, costs, node_shard=node_shard, solver_shards=4,
            fanout=2, failures_path=str(tmp_path / "failures.json"),
            task_name="plane_solve",
            unsharded=lambda: np.zeros(n, np.int64),
        )
    assert not (tmp_path / "failures.json").exists()


def test_env_plane_override_wins(monkeypatch):
    """CT_REDUCE_PLANE is the operator kill-switch: it overrides the
    call-site knob in both directions."""
    n, edges, costs, node_shard = _grid_problem(g=6, shards=2)
    monkeypatch.setenv("CT_REDUCE_PLANE", "packet")
    snap = rt.solve_snapshot()
    _, info = _solve("collective", n, edges, costs, node_shard)
    assert info["reduce_plane"] == "host"
    # packet demanded by env: not even an attempt, so no fallback counted
    assert rt.solve_delta(snap)["packet_fallbacks"] == 0
    monkeypatch.setenv("CT_REDUCE_PLANE", "bogus")
    with pytest.raises(ValueError):
        _solve("auto", n, edges, costs, node_shard)


# -- packet-plane fast-fail guards (_wait_npz) --------------------------------


def test_wait_npz_dead_publisher_fails_in_a_quarter_second(tmp_path):
    """A dead publishing worker surfaces via the pid probe in ~0.25 s —
    naming the os pid — instead of burning the full patience window."""
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()  # reaped: os.kill(pid, 0) now raises ProcessLookupError
    pid_path = tmp_path / "worker_0.json"
    pid_path.write_text(json.dumps({"os_pid": proc.pid}))
    t0 = time.monotonic()
    with pytest.raises(rt.ShardedSolveError, match=f"{proc.pid}.*is dead"):
        rt._wait_npz(
            str(tmp_path / "packet_l0_g0.npz"), 30.0,
            owner_pid_path=str(pid_path),
        )
    assert time.monotonic() - t0 < 5.0


def test_wait_npz_level_deadline_caps_total_wait(tmp_path):
    """The absolute level deadline bounds the whole level to ONE window
    (a worker dying between levels used to cost levels x patience)."""
    t0 = time.monotonic()
    with pytest.raises(rt.ShardedSolveError, match="level deadline"):
        rt._wait_npz(
            str(tmp_path / "packet_l0_g0.npz"), 30.0,
            deadline=time.monotonic() + 0.3,
        )
    assert time.monotonic() - t0 < 5.0


def test_wait_npz_live_unprobeable_pid_keeps_waiting(tmp_path):
    """PermissionError from the probe (alive but unowned pid) must NOT
    fail the hop — only ProcessLookupError means the publisher is gone."""
    pid_path = tmp_path / "worker_0.json"
    pid_path.write_text(json.dumps({"os_pid": 1}))  # init: alive, EPERM
    with pytest.raises(rt.ShardedSolveError, match="did not arrive"):
        rt._wait_npz(
            str(tmp_path / "packet_l0_g0.npz"), 0.6,
            owner_pid_path=str(pid_path),
        )


# -- the bench smoke twin -----------------------------------------------------


def test_bench_reduce_plane_smoke():
    """<10 s twin of `make bench-reduce`: the collective arm pays one
    dispatch per level (>=2x fewer than the host arm), stays off the
    filesystem, and the force-disabled fallback arm degrades attributed
    and bit-identical."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(
            os.path.dirname(os.path.dirname(__file__)), "bench.py"
        )
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    rec = bench.reduce_plane_bench(smoke=True)
    assert rec["smoke"] is True
    assert rec["accepted"] is True
    assert rec["dispatch_ratio_host_over_collective"] >= 2.0
    assert rec["collective_arm"]["packet_fallbacks"] == 0
    assert rec["collective_arm"]["collective_hops"] == rec["tree_levels"]
    assert rec["fallback_arm"]["bit_identical_to_host"] is True
    assert "degraded:packet_plane" in rec["fallback_arm"]["resolutions"]


# -- worker-group rungs (multi-process; tier-2) -------------------------------


@pytest.mark.slow
def test_worker_group_auto_plane_bit_identical(tmp_path):
    """2-process worker group under `auto`: each worker probes collective
    support once (deterministically — siblings must agree or the packet
    exchange deadlocks) and the group lands on the best supported rung.
    Labels are bit-identical to the in-process solve either way."""
    g, shards = 10, 4
    n, edges, costs = grid_rag(g=g, seed=1)
    pos = np.stack(np.unravel_index(np.arange(n), (g, g, g)), axis=1)
    node_shard = rt.morton_node_shards(pos, shards)
    lab_in, _ = _solve("packet", n, edges, costs, node_shard)
    try:
        lab_w, info = rt.solve_over_workers(
            n, edges, costs, node_shard, fanout=2, n_workers=2,
            scratch_dir=str(tmp_path / "hops"), timeout=240,
            reduce_plane="auto",
        )
    except rt.ShardedSolveError as e:
        if "aren't implemented on the CPU backend" in str(e):
            pytest.skip("jaxlib CPU backend has no multiprocess collectives")
        raise
    assert np.array_equal(lab_in, lab_w)
    assert info["reduce_plane"] in ("packet", "collective")
    if info["reduce_plane"] == "packet":
        # auto degraded: the probe's verdict must be on the record
        assert info["plane_reason"]


@pytest.mark.slow
def test_worker_group_demanded_collective_rides_the_ladder(tmp_path):
    """Demanded collective through the task entry point with a worker
    group: on a backend without multi-process collectives the group
    degrades to the packet plane ONCE, driver-side, with a
    degraded:packet_plane record — and the labels still match."""
    g, shards = 10, 4
    n, edges, costs = grid_rag(g=g, seed=2)
    pos = np.stack(np.unravel_index(np.arange(n), (g, g, g)), axis=1)
    node_shard = rt.morton_node_shards(pos, shards)
    lab_in, _ = _solve("packet", n, edges, costs, node_shard)
    labels, info = rt.solve_with_reduce_tree(
        n, edges, costs,
        node_shard=node_shard,
        solver_shards=shards,
        fanout=2,
        reduce_plane="collective",
        failures_path=str(tmp_path / "failures.json"),
        task_name="worker_ladder",
        unsharded=lambda: lab_in,
        workers=2,
        scratch_dir=str(tmp_path / "hops"),
        worker_timeout=240,
    )
    assert np.array_equal(labels, lab_in)
    if info.get("reduce_plane") != "collective":
        doc = json.loads((tmp_path / "failures.json").read_text())
        recs = [r for r in doc["records"] if r["task"] == "worker_ladder"]
        assert any(
            r["resolution"] == "degraded:packet_plane" for r in recs
        )
