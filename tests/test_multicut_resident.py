"""The resident multicut deployment (``mc_fused_384``) at a size the CPU holds.

``cli run multicut`` with ``execution="fused"``: boundary map -> fragments by
the fused mesh step -> graph -> features -> costs -> multicut -> write, one
job, held to the plain reference (``benchmark/reference_multicut.py``)
through the comparison that decides the cell's ``correct``
(``benchmark/comparisons/mc_labels.py``).  A sound run reads every count 0
or under its limit; each planted fault is caught by its own count.  The
chip's twin is ``python3 -m benchmark.run --workload multicut384.volumes``.
"""

import json
import os
import shutil

import jax
import numpy as np
import pytest

from benchmark import control, data, run
from benchmark import reference as ref
from benchmark import reference_multicut as mc
from benchmark.traffic import Job
from cluster_tools_tpu import cli
from cluster_tools_tpu.ops import contraction, rag
from cluster_tools_tpu.runtime import trace as trace_mod
from cluster_tools_tpu.runtime.task import build, get_task_cls
from cluster_tools_tpu.tasks import costs as costs_mod
from cluster_tools_tpu.tasks import features as feat_mod
from cluster_tools_tpu.utils.volume_utils import file_reader
from cluster_tools_tpu.workflows import MulticutSegmentationWorkflow, _tasks_below

SHAPE, BLOCK, CELLS = (64, 64, 64), [32, 32, 32], 8
PARAMS = dict(threshold=0.5, halo=16, dt_max_distance=16.0, block_shape=BLOCK,
              execution="fused", impl="auto", decomposition="slab", beta=0.5,
              n_scales=1)
CFG = {"params": PARAMS}
mc_labels = run.load_by_file("comparisons", "mc_labels")


def _workflow(root, seed, **extra):
    tmp = os.path.join(root, f"job{seed}")
    os.makedirs(tmp, exist_ok=True)
    with open(os.path.join(tmp, "global.config"), "w") as f:
        json.dump({"block_shape": BLOCK}, f)
    # no ``ws_path``: the harness passes none
    return cli._resolve("multicut")(
        tmp_folder=tmp, config_dir=tmp, max_jobs=4, target="local",
        input_path=os.path.join(root, "in.zarr"), input_key=f"vol{seed}",
        output_path=os.path.join(root, "out.zarr"), ws_key=f"ws{seed}",
        output_key=f"seg{seed}", **PARAMS, **extra)


@pytest.fixture(scope="module")
def jobs(tmp_path_factory):
    """Two jobs of the workflow in one process, on two seeds' volumes, the
    exact fill on and the tracer on: ``{seed: dict(vol, ws, seg, tmp, rec,
    manifest)}``, the second job's compile counters, and the ring's spans."""
    from cluster_tools_tpu.parallel import mesh

    root = str(tmp_path_factory.mktemp("mc_resident"))
    one_device = mesh.backend_devices
    out = {}
    with pytest.MonkeyPatch.context() as patch:
        # the deployment's: one device under the step, the exact fill
        patch.setattr(mesh, "backend_devices", lambda target="local", n=None: one_device(target, 1))
        patch.setenv("CT_FILL_MODE", "dense")
        trace_mod.configure(enabled=True, trace_dir=os.path.join(root, "ctt_trace"))
        try:
            store = file_reader(os.path.join(root, "in.zarr"))
            for seed in (11, 12):
                vol = data.membrane_volume(seed, 0, SHAPE, CELLS)
                store.create_dataset(f"vol{seed}", shape=SHAPE, chunks=tuple(BLOCK),
                                     dtype="float32")[...] = vol
                snap = trace_mod.compile_snapshot()
                wf = _workflow(root, seed)
                assert build([wf]), f"workflow failed, logs in {wf.tmp_folder}"
                path = os.path.join(root, "out.zarr")
                out[seed] = dict(
                    vol=vol, root=root, tmp=wf.tmp_folder,
                    ws=ref.read_zarr(path, f"ws{seed}"), seg=ref.read_zarr(path, f"seg{seed}"),
                    compiles=trace_mod.compile_delta(snap), manifest=wf.output().read(),
                    rec={"job": Job(0, 0, None, None, SHAPE), "tmp": wf.tmp_folder,
                         "outputs": {"ws": (path, f"ws{seed}"), "seg": (path, f"seg{seed}")}})
            out["spans"] = trace_mod._get().snapshot_events()
        finally:
            trace_mod.reset()
    return out


def counts(job, seg=None, tmp=None):
    return mc_labels.check_multicut(job["vol"], job["ws"], job["seg"] if seg is None else seg,
                                    tmp or job["tmp"], PARAMS["beta"])


def altered_artefacts(job, tmp_path, alter):
    """A copy of the job's ``graph/`` with ``alter(uv, sizes, costs)``'s
    result in place of the graph and the costs."""
    tmp = str(tmp_path / "tmp")
    shutil.copytree(os.path.join(job["tmp"], "graph"), os.path.join(tmp, "graph"))
    with np.load(os.path.join(tmp, "graph", "graph.npz")) as f:
        doc = dict(f)
    costs = np.load(os.path.join(tmp, "graph", "costs.npy"))
    doc["uv"], doc["sizes"], costs = alter(doc["uv"], doc["sizes"], costs)
    np.savez(os.path.join(tmp, "graph", "graph.npz"), **doc)
    np.save(os.path.join(tmp, "graph", "costs.npy"), costs)
    return tmp


# -- a sound run ----------------------------------------------------------------


@pytest.mark.parametrize("seed", [11, 12])
def test_sound_job_reads_every_count_under_its_limit(jobs, seed):
    job = jobs[seed]
    got = mc_labels.check_jobs({"check_units": 8}, CFG, [job["rec"]], {0: job["vol"]}, seed)
    assert set(got) == set(mc_labels.LIMITS)
    assert {k: v for k, v in got.items() if v > mc_labels.LIMITS[k]} == {}
    assert len(np.unique(job["seg"])) < len(np.unique(job["ws"]))


def test_ws_path_defaults_to_output_path(jobs):
    wf = _workflow(jobs[11]["root"], 11)
    below = {t.task_name: t for t in _tasks_below(wf)}
    assert "ws_path" not in wf.params
    assert below["fused_segmentation"].params["output_path"] == wf.params["output_path"]
    assert below["initial_sub_graphs"].params["input_path"] == wf.params["output_path"]
    assert below["write"].params["input_path"] == wf.params["output_path"]
    # the fragment stage takes the step's watershed output only
    assert "cc_key" not in below["fused_segmentation"].params
    assert below["fused_segmentation"].params["dt_max_distance"] == 16.0


def test_without_execution_the_blockwise_watershed_makes_the_fragments(tmp_path):
    wf = MulticutSegmentationWorkflow(
        tmp_folder=str(tmp_path), config_dir=str(tmp_path), target="local",
        input_path="a", input_key="b", ws_path="c", ws_key="d", output_path="e",
        output_key="f", threshold=0.5, halo=[2, 2, 2])
    names = {t.task_name for t in _tasks_below(wf)}
    assert "watershed" in names and "fused_segmentation" not in names


def test_second_job_on_other_data_compiles_nothing(jobs):
    assert jobs[11]["compiles"]["requests"] > 0
    assert jobs[12]["compiles"].get("requests", 0) == 0, jobs[12]["compiles"]


def test_manifest_and_io_metrics_carry_the_chain_counters(jobs):
    job = jobs[11]
    m = job["manifest"]["multicut"]
    nodes = np.unique(job["ws"])
    assert m["n_blocks"] == 8 and m["n_nodes"] == len(nodes[nodes != 0])
    assert m["n_edges"] == len(mc.rag(job["ws"])[0])
    assert m["rag_dispatches"] == {"graph": 8, "features": 8}
    assert m["store_reads"] > 0 and m["handoff_hits"] == 0
    assert m["step_cache"]["from"] in ("built", "store", "process")
    assert m["step_cache"]["fallback"] is None and m["energy"] < 0
    assert jobs[12]["manifest"]["multicut"]["step_cache"]["from"] == "process"
    with open(os.path.join(job["tmp"], "io_metrics.json")) as f:
        tasks = json.load(f)["tasks"]
    assert [v["multicut"] for k, v in tasks.items()
            if k.startswith("multicut_segmentation_workflow")] == [m]


# -- planted faults, each caught by its own count ------------------------------------


def test_fault_fragment_split_across_two_segments(jobs):
    job = jobs[11]
    seg = job["seg"].copy()
    label = np.unique(job["ws"])[-1]
    z = np.nonzero(job["ws"] == label)
    half = tuple(a[: len(a) // 2] for a in z)
    seg[half] = seg.max() + 1
    got = counts(job, seg=seg)
    assert got["mc_fragments_split"] == 1


def test_fault_two_unconnected_fragments_merged(jobs):
    job = jobs[11]
    uv, _ = mc.rag(job["ws"])
    nodes = np.unique(job["ws"])
    nodes = nodes[nodes != 0]
    node_seg, _ = mc_labels.fragment_segments(job["ws"], job["seg"], nodes)
    touching = {tuple(sorted(p)) for p in node_seg[np.searchsorted(nodes, uv)].tolist()}
    segs = np.unique(node_seg).tolist()
    a, b = next((a, b) for a in segs for b in segs if a < b and (a, b) not in touching)
    seg = job["seg"].copy()
    seg[seg == b] = a
    got = counts(job, seg=seg)
    assert got["mc_segments_disconnected"] == 1 and got["mc_fragments_split"] == 0


def test_fault_edge_dropped_from_the_graph(jobs, tmp_path):
    job = jobs[11]
    tmp = altered_artefacts(job, tmp_path, lambda uv, sizes, c: (uv[1:], sizes[1:], c[1:]))
    got = counts(job, tmp=tmp)
    assert got["mc_rag_edge_mismatch"] == 1 and got["mc_cost_mismatch"] == 0


def test_fault_face_count_off_by_one(jobs, tmp_path):
    def alter(uv, sizes, c):
        sizes = sizes.copy()
        sizes[0] += 1
        return uv, sizes, c

    got = counts(jobs[11], tmp=altered_artefacts(jobs[11], tmp_path, alter))
    assert got["mc_rag_edge_mismatch"] == 1


def test_fault_costs_from_a_bfloat16_rounded_map(jobs, tmp_path):
    """The feature and cost tasks run again over the stored fragments with
    the boundary map read rounded to bfloat16 (the benchmark's control)."""
    job = jobs[11]
    tmp = altered_artefacts(job, tmp_path, lambda uv, sizes, c: (uv, sizes, c))
    os.remove(os.path.join(tmp, "graph", "costs.npy"))
    os.remove(os.path.join(tmp, "graph", "features.npy"))
    common = dict(tmp_folder=tmp, config_dir=job["tmp"], max_jobs=4)
    store = dict(input_path=os.path.join(job["root"], "in.zarr"), input_key="vol11",
                 labels_path=os.path.join(job["root"], "out.zarr"), labels_key="ws11")
    feats = feat_mod.EdgeFeaturesWorkflow(**common, target="local", block_shape=BLOCK, **store)
    task = get_task_cls(costs_mod, "ProbsToCosts", "local")(**common, dependencies=[feats], beta=0.5)
    with control.bfloat16_reads():
        assert build([task])
    got = counts(job, tmp=tmp)
    assert got["mc_cost_mismatch"] > 0.5 * len(mc.rag(job["ws"])[0])
    assert got["mc_rag_edge_mismatch"] == 0


def test_fault_a_worse_partition(jobs):
    job = jobs[11]
    got = counts(job, seg=job["ws"])  # nothing merged: every attractive edge cut
    assert got["mc_energy_gap_ppm"] > mc_labels.LIMITS["mc_energy_gap_ppm"]
    assert got["mc_fragments_split"] == 0 and got["mc_segments_disconnected"] == 0


def test_missing_artefacts_count_as_missing(jobs, tmp_path):
    assert counts(jobs[11], tmp=str(tmp_path))["labels_missing"] == 1


# -- the plain reference ---------------------------------------------------------


def test_reference_rag_means_and_energy_on_a_hand_made_volume():
    labels = np.zeros((1, 2, 3), np.uint64)
    labels[0, 0] = [1, 1, 2]
    labels[0, 1] = [3, 0, 2]
    b = np.arange(6, dtype=np.float32).reshape(1, 2, 3) / 10
    uv, mean, faces = mc.edge_means(labels, b)
    assert uv.tolist() == [[1, 2], [1, 3]] and faces.tolist() == [1, 1]
    np.testing.assert_allclose(mean, [0.2, 0.3], rtol=1e-6)  # max of each face's two voxels
    costs = mc.probs_to_costs(mean)
    np.testing.assert_allclose(costs, np.log((1 - mean) / mean))
    edges = np.array([[0, 1], [0, 2]])
    assert mc.energy(edges, costs, np.array([0, 0, 1])) == pytest.approx(costs[1])
    assert mc.connected_in(edges, np.array([0, 1, 1])) == 1  # 1 and 2 share no edge


def test_reference_solve_finds_the_optimum_of_a_small_graph():
    # two triangles joined by a repulsive bridge; one weakly repulsive edge
    # inside a triangle must stay joined (cutting it cuts two attractive ones)
    edges = np.array([[0, 1], [1, 2], [0, 2], [3, 4], [4, 5], [3, 5], [2, 3]])
    costs = np.array([2.0, 2.0, -0.5, 1.0, 1.0, 1.0, -3.0])
    labels = mc.solve(6, edges, costs)
    assert len(set(labels[:3])) == 1 and len(set(labels[3:])) == 1 and labels[0] != labels[3]
    best = min(mc.energy(edges, costs, np.array(p)) for p in np.ndindex(*(3,) * 6))
    assert mc.energy(edges, costs, labels) == pytest.approx(best)


# -- spans, scopes, buckets ------------------------------------------------------


def test_spans_of_every_stage_with_the_tracer_on(jobs):
    spans = [e for e in jobs["spans"] if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    assert {"graph.block", "graph.merge", "features.block", "features.merge",
            "costs.transform", "mc.block_nodes", "mc.subproblem", "mc.reduce",
            "mc.solve", "write.block", "fused.wait"} <= names
    for name in ("graph.block", "features.block", "write.block"):
        rows = [e["args"] for e in spans if e["name"] == name]
        assert len(rows) == 16 and all(r["nbytes"] > 0 and "block_id" in r for r in rows)
    block = next(e["args"] for e in spans if e["name"] == "features.block")
    assert block["inner"] == BLOCK and all(s in (32, 33) for s in block["shape"])
    ran = {e["args"]["task_name"] for e in spans if e["name"] == "task.run"}
    for meta in ("mc_ws_stage_s", "mc_graph_stage_s", "mc_features_stage_s",
                 "mc_solve_stage_s", "mc_write_stage_s"):
        assert set(run.load_json(run.HERE, "metrics", meta + ".json")["tasks"]) <= ran


def test_no_span_with_the_tracer_off():
    assert not trace_mod.enabled()
    for name in ("graph.block", "features.block", "write.block", "mc.solve", "mc.subproblem"):
        assert trace_mod.span(name, block_id=0) is trace_mod._NULL
    before = len(trace_mod._get().snapshot_events())
    from cluster_tools_tpu.ops.multicut import kernighan_lin

    kernighan_lin(3, np.array([[0, 1], [1, 2]]), np.array([1.0, -1.0]))
    assert len(trace_mod._get().snapshot_events()) == before


def test_stage_scopes_are_in_the_lowered_programs():
    seg = jax.ShapeDtypeStruct((9, 9, 9), np.int32)
    val = jax.ShapeDtypeStruct((9, 9, 9), np.float32)
    text = rag.device_edge_aggregate.lower(
        seg, val, edge_cap=64, with_values=True, inner_shape=(8, 8, 8)).as_text(debug_info=True)
    assert "rag.scan" in text and "rag.aggregate" in text
    text = rag.device_rag_costs.lower(seg, val, 64, 0.5, inner_shape=(8, 8, 8)).as_text(debug_info=True)
    assert "rag.costs" in text
    vec = jax.ShapeDtypeStruct((16,), np.float32)
    text = costs_mod._costs_program.lower(vec, vec, 3.0, 0.0, 1.0, 1e-5, weighted=True).as_text(debug_info=True)
    assert "rag.costs" in text
    ids = jax.ShapeDtypeStruct((256,), np.int32)
    pay = jax.ShapeDtypeStruct((256, 1), np.float32)
    text = contraction._device_contract.lower(
        ids, ids, pay, np.float32(0), n_nodes=64, mode="max", k=1).as_text(debug_info=True)
    assert "mc.contract" in text


def test_small_solves_stay_on_the_host_and_cost_lists_share_one_program(monkeypatch):
    rng = np.random.default_rng(0)
    # on an accelerator `auto` takes the device program from 65,536 edges on
    monkeypatch.setattr(contraction.jax, "default_backend", lambda: "tpu")
    assert contraction._resolve_impl("auto", 1300) in ("native", "numpy")
    assert contraction._resolve_impl("auto", 1 << 16) == "jax"
    assert contraction._resolve_impl("jax", 3) == "jax"
    monkeypatch.undo()
    assert contraction._resolve_impl("auto", 1 << 20) in ("native", "numpy")
    n = 60
    edges = np.array([(i, j) for i in range(n) for j in range(i + 1, min(i + 4, n))])
    costs = rng.normal(size=len(edges))
    np.testing.assert_array_equal(contraction.gaec_parallel(n, edges, costs, impl="jax"),
                                  contraction.gaec_parallel(n, edges, costs, impl="numpy"))
    c0 = costs_mod._costs_program._cache_size()
    for m in (5, 700, 70000):
        p = rng.uniform(0.02, 0.98, m).astype(np.float32)
        np.testing.assert_allclose(costs_mod.compute_costs(p, beta=0.4),
                                   mc.probs_to_costs(p, 0.4), rtol=2e-5, atol=2e-5)
    assert costs_mod._costs_program._cache_size() - c0 <= 1


# -- the metric readers ----------------------------------------------------------


def test_readers_read_the_program_spans_and_nothing_without_them(jobs):
    t0 = min(e["ts"] for e in jobs["spans"])
    traced = {"job": {"t0": t0 - 1, "t1": max(e["ts"] + e.get("dur", 0) for e in jobs["spans"]) + 1},
              "runtime_spans": jobs["spans"], "trace": None, "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in ("mc_ws_stage_s", "mc_graph_stage_s", "mc_features_stage_s",
                 "mc_solve_stage_s", "mc_write_stage_s"):
        meta = run.load_json(run.HERE, "metrics", name + ".json")
        assert run.load_reader(name).read(traced, meta) > 0
        assert run.load_reader(name).read(dict(traced, runtime_spans=[]), meta) is None
    # no profile beside the job: the device readers find nothing and say so
    for name in ("rag_device_s", "rag_scan_roofline"):
        meta = run.load_json(run.HERE, "metrics", name + ".json")
        assert run.load_reader(name).read(traced, meta) is None
    scan_bytes = run.load_reader("rag_scan_roofline").scan_bytes
    # 65^3 block with its halo: labels + values once, 3 * 64 * 64 * 64 owned pairs
    assert scan_bytes([65, 65, 65], [64, 64, 64], True) == 8 * 65 ** 3 + 12 * 3 * 64 ** 3
    assert scan_bytes([64, 65, 65], [64, 64, 64], False) == 4 * 64 * 65 * 65 + 8 * (63 + 64 + 64) * 64 * 64
