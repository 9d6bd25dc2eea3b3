"""The work record (``cluster_tools_tpu/ops/work.py``, docs/OBSERVABILITY.md
"The work record"): what the watershed / CCL programs count and return beside
their labels, where it lands on the host, and the benchmark's three readers.

The chase's counts are held against its numpy oracle in
``tests/test_tile_ws.py``; the two-pass sweep's rows in
``tests/test_two_pass_aniso.py``.
"""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from cluster_tools_tpu.ops import tile_ccl, tile_ws, work
from cluster_tools_tpu.runtime import trace
from cluster_tools_tpu.utils.volume_utils import file_reader

from .helpers import programs_built_here
from .test_dense_fill import (
    _EQUALITY_CASES, _axis_faces, _fill_n_table_reference, _masked_case,
    _two_cycle_case,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _record(counts):
    """A part's counts as the host sees them after pack / unpack."""
    return work.unpack(work.pack(counts))[0]


def _raised(rec):
    return {name for name in work.OVER if rec[name] > 0}


# --------------------------------------------------------------------------
# the table
# --------------------------------------------------------------------------


def test_pack_unpack_total_and_join():
    a = _record({work.FLOW_EXITS: 7, work.FLOW_CHASE_LIVE: 3, work.CAP_EXIT: 64,
                 work.OVER_EXIT: False})
    assert a[work.FLOW_EXITS] == 7 and a[work.FILL_BASINS] == work.UNSET
    # a sum carried in units comes back in slots
    assert a[work.FLOW_CHASE_LIVE] == 3 * work.UNITS[work.FLOW_CHASE_LIVE]
    with pytest.raises(KeyError):
        work.pack({"flow.exit": 1})
    b = _record({work.FLOW_EXITS: 5, work.CAP_EXIT: 64, work.OVER_EXIT: True,
                 work.FLOW_REMAP_TILE_MAX: 9})
    both = work.total([a, b])
    assert both[work.FLOW_EXITS] == 12 and both[work.CAP_EXIT] == 64
    assert both[work.OVER_EXIT] == 1 and both[work.FLOW_REMAP_TILE_MAX] == 9
    assert both[work.FILL_BASINS] == work.UNSET
    # parts of one program: a bit two parts raise is their OR
    joined = work.join({work.OVER_ROUNDS: jnp.asarray(False), work.FILL_ROUNDS: 3},
                       {work.OVER_ROUNDS: jnp.asarray(True)})
    assert bool(joined[work.OVER_ROUNDS]) and joined[work.FILL_ROUNDS] == 3
    assert bool(work.any_over(joined))
    # programs run one after another: a name is counted by one of them
    ccl = work.pack({work.CCL_PAIRS: 4, work.CAP_PAIR: 9, work.OVER_EDGE: True})
    merged = work.unpack(work.merge(
        work.as_seed_ccl(ccl), work.pack({work.CCL_PAIRS: 6, work.FILL_ROUNDS: 2})))[0]
    # a watershed's seed CCL counts under seeds.*, the foreground's under ccl.*
    assert merged[work.SEEDS_PAIRS] == 4 and merged[work.CAP_SEED_PAIR] == 9
    assert merged[work.CCL_PAIRS] == 6 and merged[work.CAP_PAIR] == work.UNSET
    assert merged[work.FILL_ROUNDS] == 2 and merged[work.OVER_EDGE] == 1


def test_no_module_but_the_table_spells_a_counters_name():
    """``ops/work.py`` is the one place: every other module of the package
    says ``work.FLOW_EXITS``, so a misspelt name fails where it is written."""
    spelled = []
    for path in glob.glob(os.path.join(ROOT, "cluster_tools_tpu", "**", "*.py"),
                          recursive=True):
        if path.endswith(os.path.join("ops", "work.py")):
            continue
        with open(path) as f:
            text = f.read()
        spelled += [(path, n) for n in work.NAMES
                    if f'"{n}"' in text or f"'{n}'" in text]
    assert spelled == []
    # and what the benchmark's readers are told to read is in the table
    for metric in ("capacity_fallbacks", "capacity_peak_fill", "live_slot_share",
                   "fallback_branches_run"):
        with open(os.path.join(ROOT, "benchmark", "metrics", metric + ".json")) as f:
            meta = json.load(f)
        named = set(meta.get("fallbacks", []))
        named |= {n for pair in meta.get("capacities", []) for n in pair}
        for walk in meta.get("walks", []):
            named |= {walk["capacity"], walk["trips"], *walk["live"],
                      *walk["live_up_to_capacity"]}
        named |= set(meta.get("tails", []))
        named |= {meta[key] for key in ("ran", "both_sides") if key in meta}
        assert named and named <= set(work.NAMES), metric


def _mesh(n):
    from cluster_tools_tpu.parallel.mesh import make_mesh

    return make_mesh(axis_names=("dp", "sp"), grid=(1, n), devices=jax.devices()[:n])


def _program_records():
    from cluster_tools_tpu.parallel.pipeline import make_ws_ccl_step
    from cluster_tools_tpu.parallel.split_pipeline import make_ws_ccl_split

    b = jax.ShapeDtypeStruct((16, 16, 128), jnp.float32)
    i = jax.ShapeDtypeStruct((16, 16, 128), jnp.int32)
    x = jax.ShapeDtypeStruct((1, 16, 16, 16), jnp.float32)
    kw = dict(halo=2, threshold=0.5, dt_max_distance=2.0)
    return {
        "dt_watershed": lambda: jax.eval_shape(
            lambda v: tile_ws.dt_watershed_tiled(v, impl="xla")[2], b),
        "dt_watershed_seeded": lambda: jax.eval_shape(
            lambda v, e: tile_ws.dt_watershed_seeded_tiled(v, e, impl="xla")[2],
            b, i),
        "seeded_watershed": lambda: jax.eval_shape(
            lambda v, e: tile_ws.seeded_watershed_tiled(
                v, e, impl="xla", fill_mode="capacity")[2], b, i),
        "label_components": lambda: jax.eval_shape(
            lambda m: tile_ccl.label_components_tiled(m, impl="xla")[2],
            jax.ShapeDtypeStruct((16, 16, 128), jnp.bool_)),
        "mesh_step": lambda: jax.eval_shape(make_ws_ccl_step(_mesh(2), **kw), x)[4],
        "split_step": lambda: jax.eval_shape(
            lambda v: make_ws_ccl_split(_mesh(2), **kw)(v), x)[4],
    }


@pytest.mark.parametrize("program", ["dt_watershed", "dt_watershed_seeded",
                                     "seeded_watershed", "label_components",
                                     "mesh_step", "split_step"])
def test_every_program_returns_one_vector_of_the_tables_length(program):
    rec = _program_records()[program]()
    assert rec.dtype == jnp.int32 and rec.shape[-1] == len(work.NAMES)
    # a per-shard output of the mesh programs: (B, shards, K), no reduction
    assert rec.shape[:-1] == ((1, 2) if program.endswith("_step") else ())
    assert len(set(work.NAMES)) == len(work.NAMES)


# --------------------------------------------------------------------------
# the dense fill counts what numpy counts
# --------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["chunks_7_1", "chunks_9_6_4_3"])
def test_dense_fill_counts_against_numpy(case):
    make, face_cap, _, chunks = _EQUALITY_CASES[case]
    vals, height = make()
    got, flag, counts = tile_ws.fill_unseeded_basins_dense(
        jnp.asarray(vals), jnp.asarray(height), face_cap=face_cap)
    rec = _record(counts)
    assert int(flag) == 0 and _raised(rec) == set()
    _, _, live = _fill_n_table_reference(
        jnp.asarray(vals), jnp.asarray(height), face_cap=face_cap)
    live = [int(x) for x in np.asarray(live) if x > 0]
    chunk = -(-face_cap // 16)
    faces = _axis_faces(vals)
    assert [rec[n] for n in work.FILL_FACES] == faces
    assert rec[work.FILL_HARVEST_TRIPS] == sum(-(-f // chunk) for f in faces)
    flat = np.arange(vals.size).reshape(vals.shape)
    assert rec[work.FILL_BASINS] == int(np.sum(vals == -flat - 2))
    # every round with a live face changes the table; one more finds none
    assert rec[work.FILL_ROUNDS] == len(live) + 1 == len(chunks)
    assert rec[work.FILL_ROUND_TRIPS] == sum(-(-n // chunk) for n in live)
    unit = work.UNITS[work.FILL_LIVE_FACES]
    assert rec[work.FILL_LIVE_FACES] == unit * sum(-(-n // unit) for n in live)
    assert rec[work.FILL_CLOSURE_TRIPS] >= rec[work.FILL_ROUNDS]
    assert rec[work.CAP_FACE] == face_cap and rec[work.CAP_BASIN] == vals.size


# --------------------------------------------------------------------------
# the soft capacity: 64 entries a tile fit the table, 65 take the gather
# --------------------------------------------------------------------------

TILE = (16, 16, 128)


@pytest.mark.parametrize("n_codes,fallback", [(64, 0), (65, 1)])
def test_exit_remap_reports_the_fullest_tile_and_its_branch(n_codes, fallback):
    shape = (32, 16, 128)   # two tiles; every changed code lies in the second
    n = int(np.prod(shape))
    values = np.full(shape, 7, np.int32)
    cells = n // 2 + 2 * np.arange(n_codes)
    codes = np.full(1024, tile_ws.BIG, np.int32)
    codes[:n_codes] = -(cells + 1) - 2          # each names the voxel beside
    values.reshape(-1)[cells] = codes[:n_codes]
    finals = np.where(codes < tile_ws.BIG, 100 + np.arange(1024), codes).astype(np.int32)
    code_tiles = np.where(codes < tile_ws.BIG, 1, tile_ws.BIG).astype(np.int32)
    # a code that resolves to itself changes nothing and takes no slot
    codes[n_codes], finals[n_codes], code_tiles[n_codes] = -5 - 2, -5 - 2, 0
    out, counts = tile_ws._remap_exits(
        jnp.asarray(values), jnp.asarray(codes), jnp.asarray(code_tiles),
        jnp.asarray(finals), "pallas", TILE, 64, True)
    assert int(counts[work.FLOW_REMAP_TILE_MAX]) == n_codes
    assert int(counts[work.FLOW_REMAP_FALLBACK]) == fallback
    want = values.copy()
    want.reshape(-1)[cells] = finals[:n_codes]
    np.testing.assert_array_equal(np.asarray(out), want)
    # the portable kernels have no such branch: nothing to report
    _, none = tile_ws._remap_exits(
        jnp.asarray(values), jnp.asarray(codes), jnp.asarray(code_tiles),
        jnp.asarray(finals), "xla", TILE, 64, False)
    assert _record(none)[work.FLOW_REMAP_FALLBACK] == work.UNSET


@pytest.mark.parametrize("n_pairs,fallback", [(64, 0), (65, 1)])
def test_ccl_remap_reports_the_fullest_tile_and_its_branch(n_pairs, fallback):
    """``n_pairs`` two-voxel components cross the face between two tiles:
    each takes its label from the first tile, so the second tile's table
    needs ``n_pairs`` entries and the first tile's none."""
    mask = np.zeros((32, 16, 128), bool)
    spots = 2 * np.arange(n_pairs)              # (y, x) apart, not neighbours
    mask[15:17, spots // 128 * 2, spots % 128] = True
    got = {}
    for impl, interpret in (("pallas", True), ("xla", False)):
        labels, overflow, record = tile_ccl.label_components_tiled(
            jnp.asarray(mask), impl=impl, tile=TILE, interpret=interpret)
        assert not bool(overflow)
        got[impl] = (np.asarray(labels), work.unpack(record)[0])
    np.testing.assert_array_equal(got["pallas"][0], got["xla"][0])
    rec = got["pallas"][1]
    assert rec[work.CCL_REMAP_TILE_MAX] == n_pairs
    assert rec[work.CCL_REMAP_FALLBACK] == fallback
    assert rec[work.CCL_PAIRS] == rec[work.CCL_EDGES] == n_pairs
    assert rec[work.CAP_TABLE] == 64 and _raised(rec) == set()
    assert got["xla"][1][work.CCL_REMAP_FALLBACK] == work.UNSET
    assert got["xla"][1][work.CCL_EDGES] == n_pairs


# --------------------------------------------------------------------------
# the overflow flag, split by what tripped
# --------------------------------------------------------------------------


def _noise(shape=(16, 16, 128), seed=5):
    import scipy.ndimage as ndi

    b = ndi.gaussian_filter(np.random.default_rng(seed).random(shape), 1.5)
    return jnp.asarray(((b - b.min()) / (b.max() - b.min())).astype(np.float32))


def _flow(monkeypatch=None, **kw):
    b = _noise((32, 32, 128))
    seeds, valid, _, _ = tile_ws._dt_seeds_core(
        b, None, None, threshold=0.5, sigma_seeds=0.0, min_seed_distance=0.0,
        sampling=None, dt_max_distance=None, impl="xla", tile=None,
        pair_cap=None, edge_cap=None, table_cap=64, interpret=False)
    args = dict(impl="xla", tile=None, exit_cap=None, table_cap=64, interpret=False)
    args.update(kw)
    _, _, flag, counts = tile_ws._ws_flow_core(b, seeds, valid, **args)
    return flag, counts


def _over_exit(monkeypatch):
    return _flow(exit_cap=64)


def _over_hops(monkeypatch):
    from functools import partial

    monkeypatch.setattr(tile_ws, "chase_exits",
                        partial(tile_ws.chase_exits, max_hops=1))
    return _flow()


def _over_face(monkeypatch):
    vals, height = _masked_case(12, (14, 15, 16), 0.15)
    _, flag, counts = tile_ws.fill_unseeded_basins_dense(
        jnp.asarray(vals), jnp.asarray(height), face_cap=200)
    return flag, counts


def _over_basin(monkeypatch):
    # every voxel a seedless basin of its own: twice what the table holds
    shape = (32, 32, 128)
    vals = -np.arange(np.prod(shape), dtype=np.int32).reshape(shape) - 2
    height = np.random.default_rng(3).random(shape).astype(np.float32)
    _, flag, counts = tile_ws.fill_unseeded_basins_dense(
        jnp.asarray(vals), jnp.asarray(height), face_cap=1 << 19)
    return flag, counts


def _over_basin_no_id(monkeypatch):
    vals, height = _two_cycle_case()
    vals[1, 1, 3] = -1   # a code whose terminal does not carry it
    _, flag, counts = tile_ws.fill_unseeded_basins_dense(
        jnp.asarray(vals), jnp.asarray(height))
    return flag, counts


def _over_rounds(monkeypatch):
    vals, height = _masked_case(22, (10, 11, 12), 0.02)
    _, flag, counts = tile_ws.fill_unseeded_basins_dense(
        jnp.asarray(vals), jnp.asarray(height), max_rounds=1)
    return flag, counts


def _over_adj(monkeypatch):
    vals, height = _masked_case(12, (14, 15, 16), 0.15)
    vals = np.where(vals == -1, 0, vals)
    _, _, flag, counts = tile_ws.fill_unseeded_basins(
        jnp.asarray(vals), jnp.asarray(height), adj_cap=8)
    return flag, counts


def _over_edge(monkeypatch):
    mask = np.random.default_rng(2).random((32, 32, 128)) < 0.6
    _, flag, record = tile_ccl.label_components_tiled(
        jnp.asarray(mask), impl="xla", pair_cap=16)
    return flag, work.unpack(record)


def _over_labels(monkeypatch):
    from cluster_tools_tpu.parallel.pipeline import make_ws_ccl_step

    vol = np.random.default_rng(0).random((1, 16, 16, 16)).astype(np.float32)
    step = make_ws_ccl_step(_mesh(2), halo=2, threshold=0.5, max_labels_per_shard=4)
    *_, flag, records = jax.block_until_ready(step(vol))
    return flag, work.unpack(records)


@pytest.mark.parametrize("bit,make,names", [
    (work.OVER_EXIT, _over_exit, "raise exit_cap"),
    (work.OVER_HOPS, _over_hops, "raise max_hops"),
    (work.OVER_FACE, _over_face, "raise face_cap"),
    (work.OVER_BASIN, _over_basin, "raise basin_cap"),
    # the count fits: the line names the cause, which no capacity mends
    (work.OVER_BASIN, _over_basin_no_id, "use fill_mode=capacity"),
    (work.OVER_ROUNDS, _over_rounds, "raise fill_rounds"),
    (work.OVER_ADJ, _over_adj, "raise adj_cap"),
    (work.OVER_EDGE, _over_edge, "raise pair_cap"),
    (work.OVER_LABELS, _over_labels, "raise max_labels_per_shard"),
])
def test_each_capacity_alone_trips_the_flag_and_is_named(monkeypatch, bit, make, names):
    flag, counts = make(monkeypatch)
    assert bool(np.asarray(flag))
    records = counts if isinstance(counts, list) else [_record(counts)]
    for rec in records:
        assert _raised(rec) == {bit}
        (line,) = work.tripped(rec)
        assert line.startswith(bit) and names in line
        assert ("raise" in line) == names.startswith("raise")
        _, counted = work.OVERFLOWS[bit]
        # the count that passed it, and the capacity's size where it has one
        assert any(f"{n} = {rec[n]}" in line for n in counted if rec[n] >= 0)
        for size in {work.CAPACITY[n] for n in counted if n in work.CAPACITY}:
            assert rec[size] < 0 or f"{size} = {rec[size]}" in line


def test_the_flag_is_false_where_no_bit_is_raised():
    lab, flag, rec = tile_ws.dt_watershed_tiled(
        _noise(), threshold=0.5, impl="xla", fill_mode="dense")
    rec = work.unpack(rec)[0]
    assert not bool(flag) and _raised(rec) == set() and work.tripped(rec) == []
    # every count the program made fits the capacity it is read against
    read = [(n, c) for n, c in work.CAPACITY.items()
            if rec[n] >= 0 and c != work.CAP_TABLE]
    assert len(read) >= 8 and all(0 <= rec[n] <= rec[c] for n, c in read)


# --------------------------------------------------------------------------
# the fused task: on every job, traced or not
# --------------------------------------------------------------------------

SHAPE = (32, 32, 32)


def _fused_task(root, tag, **params):
    from cluster_tools_tpu.tasks.fused import FusedSegmentationLocal

    tmp = os.path.join(root, f"tmp_{tag}")
    os.makedirs(tmp)
    with open(os.path.join(tmp, "global.config"), "w") as f:
        json.dump({}, f)
    path = os.path.join(root, f"{tag}.zarr")
    vol = np.random.default_rng(7).random(SHAPE).astype(np.float32)
    file_reader(path).create_dataset(
        "b", shape=SHAPE, chunks=(16, 16, 16), dtype="float32")[...] = vol
    return FusedSegmentationLocal(
        tmp_folder=tmp, config_dir=tmp, max_jobs=1, input_path=path,
        input_key="b", output_path=path, ws_key="ws", cc_key="cc",
        threshold=0.5, halo=4, block_shape=[16, 16, 16], **params)


@pytest.fixture
def two_devices(monkeypatch):
    """The fused task takes every device of its target: give it two."""
    from cluster_tools_tpu.parallel import mesh

    devices = mesh.backend_devices("local")[:2]
    monkeypatch.setattr(mesh, "backend_devices", lambda *a, **k: devices)


@pytest.mark.parametrize("traced", [False, True])
def test_fused_job_carries_one_record_a_shard(tmp_path, two_devices, traced):
    from cluster_tools_tpu.runtime.task import build

    task = _fused_task(str(tmp_path), "on" if traced else "off")
    trace.configure(enabled=traced)
    try:
        with programs_built_here():
            assert build([task]), "fused task failed (see logs)"
        events = trace._get().snapshot_events()
    finally:
        trace.reset()
    doc = task.output().read()
    records = doc["work"]
    assert doc["mesh"] == "sp=2" and len(records) == 2
    for rec in records:
        assert set(rec) == set(work.NAMES) and _raised(rec) == set()
        assert rec[work.SEEDS_EDGES] >= 0 and rec[work.FLOW_CHASE_HOPS] > 0
        # the CPU backend compiles the portable kernels: no such branch
        assert rec[work.FLOW_REMAP_FALLBACK] == work.UNSET
        assert rec[work.CAP_LABELS] == work.UNSET
    assert records[0] != records[1]     # each shard's own, nothing reduced
    with open(os.path.join(task.tmp_folder, "io_metrics.json")) as f:
        assert json.load(f)["tasks"][task.uid]["work"] == records
    waits = [e for e in events if e["ph"] == "X" and e["name"] == "fused.wait"]
    if not traced:
        assert events == []
        return
    (wait,) = waits
    assert wait["args"]["work"] == records
    # and the benchmark's readers read them off the span as the task wrote it
    got = {
        metric: bench_run.load_reader(metric).read(
            dict(job={}, runtime_spans=events),
            bench_run.load_json(ROOT, "benchmark", "metrics", metric + ".json"))
        for metric in ("capacity_fallbacks", "capacity_peak_fill", "live_slot_share")
    }
    assert got["capacity_fallbacks"] == 0       # no such branch on this backend
    assert got["capacity_peak_fill"] == max(
        100.0 * r[n] / r[c] for r in records for n, c in work.CAPACITY.items()
        if r[n] >= 0 and c != work.CAP_TABLE)
    assert 0 < got["live_slot_share"] <= 100


def test_a_tripped_capacity_is_named_in_the_fused_tasks_error(tmp_path, two_devices):
    task = _fused_task(str(tmp_path), "over", max_labels_per_shard=4)
    with programs_built_here(), pytest.raises(RuntimeError) as err:
        task.run()
    text = str(err.value)
    assert "shard 0: over.labels" in text and "shard 1: over.labels" in text
    assert "cap.labels = 4" in text and "raise max_labels_per_shard" in text
    assert "step.fragments = " in text


# --------------------------------------------------------------------------
# the benchmark's readers, on hand-made records
# --------------------------------------------------------------------------


def _rec(**counts):
    return dict({n: work.UNSET for n in work.NAMES},
                **{k.replace("__", "."): v for k, v in counts.items()})


#: exits 1000 of 1600 (chunk 100): 3 hops of 10 + 2 + 1 trips over 1000 +
#: 150 + 10 chains; faces 300 + 0 + 500 of 3200 (chunk 200): harvest 2 + 0 + 3
#: trips, rounds of 4 + 1 trips over 800 + 96 faces
ONE = _rec(flow__exits=1000, flow__exit_family_max=400, flow__chase_hops=3,
           flow__chase_trips=13, flow__chase_live=1168, flow__chase_tail_hops=1,
           flow__remap_tile_max=130, flow__remap_fallback=1,
           fill__faces_z=300, fill__faces_y=0, fill__faces_x=500,
           fill__harvest_trips=5, fill__basins=40, fill__round_trips=5,
           fill__live_faces=896, seeds__remap_tile_max=12, seeds__remap_fallback=0,
           ccl__remap_fallback=0, cap__exit=1600, cap__face=3200, cap__basin=4096,
           cap__table=64, branch__fallbacks_run=1, branch__both_sides=0)
#: a second shard: emptier lists, but its seed CCL's table overflows too
TWO = dict(ONE, **{work.FLOW_EXITS: 160, work.SEEDS_REMAP_FALLBACK: 1,
                   work.BRANCH_FALLBACKS_RUN: 2})
#: the batch of ONE's and TWO's lanes runs both fallbacks, and the seed
#: CCL's cond on both sides: every lane's record says so
BATCH = {work.BRANCH_FALLBACKS_RUN: 2, work.BRANCH_BOTH_SIDES: 1}
ONE_LIVE = 100.0 * (1168 + 896 + 800) / (13 * 100 + (5 + 5) * 200)

READINGS = {
    # one program execution, one shard
    "one": ([("fused.wait", [ONE])],
            dict(capacity_fallbacks=1, capacity_peak_fill=62.5,
                 live_slot_share=ONE_LIVE, fallback_branches_run=1)),
    # a branch counts once an execution, however many lanes took it (the
    # exits' in both lanes of the first pass, the seed CCL's in one); two
    # executions (the two passes of a sweep) count apart.  TWO walks what
    # ONE walks, so three equal walks give ONE's share
    "two": ([("ws.pass", [dict(ONE, **BATCH), dict(TWO, **BATCH)]),
             ("ws.pass", [TWO])],
            dict(capacity_fallbacks=4, capacity_peak_fill=62.5,
                 live_slot_share=ONE_LIVE, fallback_branches_run=4)),
    # a program from before the record: spans without the argument
    "none": ([("fused.wait", None), ("task.run", None)],
             dict(capacity_fallbacks=None, capacity_peak_fill=None,
                  live_slot_share=None, fallback_branches_run=None)),
    # a record from before the branch counts: the new reader has nothing
    "before": ([("ws.pass", [dict(ONE, **{n: work.UNSET for n in work.BRANCH})])],
               dict(capacity_fallbacks=1, capacity_peak_fill=62.5,
                    live_slot_share=ONE_LIVE, fallback_branches_run=None)),
}


@pytest.mark.parametrize("metric", ["capacity_fallbacks", "capacity_peak_fill",
                                    "live_slot_share", "fallback_branches_run"])
@pytest.mark.parametrize("case", sorted(READINGS))
def test_readers_on_hand_made_records(case, metric):
    spans, want = READINGS[case]
    traced = dict(job={"t0": 0.0, "t1": 100.0}, runtime_spans=[
        {"ph": "X", "name": name, "ts": 1.0 + i, "dur": 0.5,
         "args": {"work": records} if records else {}}
        for i, (name, records) in enumerate(spans)])
    meta = bench_run.load_json(ROOT, "benchmark", "metrics", metric + ".json")
    got = bench_run.load_reader(metric).read(traced, meta)
    if want[metric] is None:
        assert got is None
    else:
        assert got == pytest.approx(want[metric])


def test_fallback_branches_run_says_each_execution(capfd):
    traced = dict(job={"t0": 0.0, "t1": 100.0}, runtime_spans=[
        {"ph": "X", "name": "ws.pass", "ts": 1.0 + i, "dur": 0.5,
         "args": {"work": records}}
        for i, records in enumerate([[dict(ONE, **BATCH)] * 2, [TWO]])])
    meta = bench_run.load_json(
        ROOT, "benchmark", "metrics", "fallback_branches_run.json")
    assert bench_run.load_reader("fallback_branches_run").read(traced, meta) == 4
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("[fallback_branches_run]")]
    assert lines == [
        "[fallback_branches_run] execution 0 ws.pass: "
        "branch.fallbacks_run=2 branch.both_sides=1",
        "[fallback_branches_run] execution 1 ws.pass: "
        "branch.fallbacks_run=2 branch.both_sides=0"]


def test_live_slot_share_says_each_walk_and_the_tails(capfd):
    """The counts no metric's value holds are on the reader's own line."""
    traced = dict(job={"t0": 0.0, "t1": 100.0}, runtime_spans=[
        {"ph": "X", "name": "ws.pass", "ts": 1.0, "dur": 0.5,
         "args": {"work": [dict(ONE, block=7, **{work.FILL_ROUNDS: 2,
                                                 work.FILL_CLOSURE_TRIPS: 6})]}}])
    meta = bench_run.load_json(ROOT, "benchmark", "metrics", "live_slot_share.json")
    assert bench_run.load_reader("live_slot_share").read(traced, meta) == \
        pytest.approx(ONE_LIVE)
    (line,) = [ln for ln in capfd.readouterr().err.splitlines()
               if ln.startswith("[live_slot_share]")]
    assert "execution 0 ws.pass block 7: flow.chase_trips 1168 of 1300 slots" in line
    assert "fill.round_trips 896 of 1000 slots" in line
    assert "fill.harvest_trips 800 of 1000 slots" in line
    assert line.endswith("flow.chase_hops=3; flow.chase_tail_hops=1; "
                         "fill.rounds=2; fill.closure_trips=6")
