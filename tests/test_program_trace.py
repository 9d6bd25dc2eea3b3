"""benchmark/program_trace.py and the ten per-layer readers that use it:
the recorded TPU trace in ``benchmark/testdata`` for the wire reader, a
small synthetic ``traced`` (a hand-written ``.xplane.pb`` beside a
hand-made ``reduce_trace.Reduced``) for the readers, and ``selfcheck``'s
spanless ``traced``, on which every reader has to return nothing."""

import json
import os

import pytest

from benchmark import program_trace, reduce_trace, run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = os.path.join(ROOT, "benchmark", "testdata", "small.xplane.pb")

NEW_METRICS = ("ws_flow_device_s", "ws_fill_device_s", "ws_seeds_device_s",
               "ccl_device_s", "device_unscoped_share", "trace_lower_s",
               "executable_load_s", "labels_d2h_s", "io_span_share",
               "idle_unattributed_share")


# -- a protobuf writer for the few XSpace fields the reader knows ------------


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(field, n):
    return _varint(field << 3) + _varint(n)


def _msg(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, event_metadata=(), stat_names=(), lines=()):
    """event_metadata: (id, name, {stat name: str}); lines: (t0 ns, [(metadata
    id, offset ps, duration ps)])."""
    stat_id = {n: i + 1 for i, n in enumerate(stat_names)}
    body = _msg(2, name)
    for t0_ns, events in lines:
        body += _msg(3, _msg(2, "line") + _int(3, t0_ns) + b"".join(
            _msg(4, _int(1, mid) + _int(2, off) + _int(3, dur))
            for mid, off, dur in events))
    for mid, text, stats in event_metadata:
        md = _int(1, mid) + _msg(2, text) + b"".join(
            _msg(5, _int(1, stat_id[k]) + _msg(5, v)) for k, v in stats.items())
        body += _msg(4, _int(1, mid) + _msg(2, md))
    for n, i in stat_id.items():
        body += _msg(5, _int(1, i) + _msg(2, _int(1, i) + _msg(2, n)))
    return _msg(1, body)


STEP = "jit(ws_ccl_step)/jit(_dt_watershed_tiled_jit)/"
#: (instruction, opcode, start s, duration s, tf_op) of a made-up main program
DEVICE_OPS = (
    ("fusion.1", "fusion", 10.0, 1.0, STEP + "ws.seeds/jit(_dt_squared_impl)/edt/min:"),
    ("fusion.2", "fusion", 11.0, 0.5, STEP + "ws.seeds/jit(f)/ccl.tile/add:"),
    ("while.3", "while", 12.0, 4.0, STEP + "ws.flow/ws.flow.propagate/while:"),
    ("fusion.4", "fusion", 12.0, 3.0, "while/body/min:"),          # path lost
    ("sort.5", "sort", 15.0, 1.0, STEP + "ws.flow/ws.flow.exits/sort:"),
    ("fusion.6", "fusion", 16.0, 6.0, STEP + "ws.fill/ws.fill.dense/while/body/scatter:"),
    ("fusion.7", "fusion", 22.0, 1.5, "jit(ws_ccl_step)/jit(f)/ccl.merge/gather:"),
    ("copy.8", "copy", 23.5, 0.5, "jit(ws_ccl_step)/convert_element_type:"),
)


def _op_text(name, opcode):
    return f"%{name} = s32[8]{{0}} {opcode}(s32[8]{{0}} %p)"


@pytest.fixture
def traced(tmp_path):
    """The job ran on the ring's clock from 100.0 to 120.0; the trace's
    clock is 95 s behind it (the job is the window 5.0-25.0); the harness's
    t0 was taken 0.25 s before task.run opened."""
    work = tmp_path / "work"
    (work / "jobs" / "j0").mkdir(parents=True)
    prof = work / "profile" / "plugins" / "profile" / "x"
    prof.mkdir(parents=True)
    device = _plane(
        "/device:TPU:0", stat_names=("tf_op", "source"),
        event_metadata=[(i + 1, _op_text(n, oc), {"tf_op": tf, "source": "tile_ws.py:1"})
                        for i, (n, oc, _, _, tf) in enumerate(DEVICE_OPS)])
    host = _plane(
        "/host:CPU", event_metadata=[(1, "task.run", {}), (2, "bench.job", {})],
        lines=[(5_000_000_000, [(2, 0, int(20e12)), (1, int(0.25e12), int(19e12))])])
    (prof / "h.xplane.pb").write_bytes(device + host)

    ops = [reduce_trace.Op(n, oc, _op_text(n, oc), s, d) for n, oc, s, d, _ in DEVICE_OPS]
    red = reduce_trace.Reduced(
        window=(5.0, 25.0), busy_s=14.0, n_chips=1, n_device_events=len(ops), ops=ops,
        modules=[("jit_ws_ccl_step(1)", 10.0, 14.0), ("jit_gather(2)", 24.2, 0.1)])

    def span(name, ts, dur, tid=1, **args):
        return {"ph": "X", "name": name, "ts": ts, "dur": dur, "tid": tid, "args": args}

    spans = [
        span("task.build", 100.2, 19.5), span("task.run", 100.25, 19.0),
        span("fused.setup", 100.3, 0.2), span("fused.read", 100.5, 1.0),
        span("io.read", 100.6, 0.8), span("fused.dispatch", 101.5, 3.5),
        span("jax.trace", 101.5, 1.5, fun_name="ws_ccl_step"),
        span("jax.trace", 101.7, 0.5, fun_name="inner"),
        span("jax.lower", 103.0, 0.5), span("jax.backend_compile", 103.5, 1.2),
        span("jax.cache_load", 103.6, 1.0), span("fused.wait", 105.0, 14.0),
        span("fused.d2h", 119.0, 0.1, output="ws"), span("fused.widen", 119.1, 0.1),
        span("fused.write", 119.2, 0.3), span("io.write", 119.25, 0.2),
        span("io.write", 119.3, 0.1, tid=2),      # another thread, overlapping
        span("task.finalize", 119.5, 0.2),
        span("fused.read", 130.0, 1.0),           # a later job's: outside
    ]
    return dict(trace=red, peaks={"hbm_bytes_per_s": 819e9}, runtime_spans=spans,
                job={"tmp": str(work / "jobs" / "j0"), "t0": 100.0, "t1": 120.0},
                io_spans=[], io_seconds=0.0)


def _selfcheck_traced():
    red = reduce_trace.reduce_file(SMALL)
    return dict(trace=red, peaks={"hbm_bytes_per_s": 819e9},
                job={"t0": 10.0, "t1": 10.0 + red.window_s}, io_spans=[(10.0, 10.01)],
                io_seconds=0.01, runtime_spans=[])


def _read_metric(name, traced):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        meta = json.load(f)
    return run.load_reader(name).read(traced, meta)


# -- the wire reader on the recorded trace -----------------------------------


def test_op_scopes_finds_the_sorts_path_in_the_recorded_trace():
    scopes = program_trace.op_scopes(SMALL)
    (sort,) = [tf for text, tf in scopes.items() if text.startswith("%sort.6 = ")]
    assert sort == "jit(f)/jit(sort)/sort"
    # an expander dropped this one's path
    assert "reduce_window_sum" in scopes.values()
    # keyed by what reduce_trace.Op.text holds
    red = reduce_trace.reduce_file(SMALL)
    assert any(op.text in scopes for op in red.ops if op.opcode == "sort")


def test_host_events_are_on_reduce_traces_clock():
    red = reduce_trace.reduce_file(SMALL)
    (job,) = [(s, d) for name, s, d in program_trace._read(SMALL)["host"]
              if name == "bench.job"]
    assert job[0] == pytest.approx(red.window[0], abs=1e-9)
    assert job[1] == pytest.approx(red.window_s, abs=1e-9)


def test_stage_of_takes_the_outermost_stage():
    assert program_trace.stage_of(STEP + "ws.seeds/jit(f)/ccl.tile/add") == "ws.seeds"
    assert program_trace.stage_of("jit(s)/step.stitch/ccl.merge/sort") == "step.stitch"
    assert program_trace.stage_of("jit(s)/ws.flow/ws.flow.chase/while/body/min") == "ws.flow"
    assert program_trace.stage_of("jit(s)/ws.flow.chase/min") is None   # no stage of its own
    assert program_trace.stage_of("reduce_window_sum") is None
    assert program_trace.stage_of(None) is None


def test_trace_file_follows_the_harness_layout(traced):
    assert program_trace.trace_file(traced).endswith("h.xplane.pb")
    assert program_trace.trace_file({"job": {"t0": 1.0}}) is None
    assert program_trace.trace_file({}) is None


def test_a_pathless_leaf_inherits_the_loop_around_it(traced):
    stages = {op.name: stage for op, stage in program_trace.main_ops(traced)}
    assert stages["fusion.4"] == "ws.flow"          # inside while.3
    assert stages["fusion.2"] == "ws.seeds"         # seed CCL counts as seeds
    assert stages["copy.8"] is None


def test_host_spans_and_the_clock_tie(traced):
    spans = program_trace.host_spans(traced)
    # placed by the task.run annotation (5.25 on the trace) and the ring's
    # (100.25): 95 s, where the harness's tie (bench.job = t0) says 95 s too
    by_name = {name: (a, b) for name, a, b, _, _ in spans}
    assert by_name["task.run"] == pytest.approx((5.25, 24.25))
    assert "fused.read" in by_name and len(spans) == 18    # the later job's is out
    assert program_trace.clock_shift(traced) == pytest.approx(-95.0)
    # no annotation in the file: the harness's tie
    traced["job"]["tmp"] = None
    assert program_trace.clock_shift(traced) == pytest.approx(5.0 - 100.0)


# -- the ten readers ---------------------------------------------------------

EXPECTED = {
    "ws_flow_device_s": 4.0,             # fusion.4 (inherited) + sort.5
    "ws_fill_device_s": 6.0,
    "ws_seeds_device_s": 1.5,            # edt + the seed CCL
    "ccl_device_s": 1.5,
    "device_unscoped_share": 100 * 0.5 / 13.5,
    "trace_lower_s": 2.0,                # union: the inner trace lies in the outer
    "executable_load_s": 1.2,
    "labels_d2h_s": 0.2,
    "io_span_share": 100 * (0.8 + 0.2) / 20.0,     # the two writes overlap
    # idle 5-10, 11.5-12 and 24-25 on the trace = ring 100-105, 106.5-107 and
    # 119-120; phase spans cover 100.3-105 of the first, all of the second
    # (fused.wait) and 119-119.7 of the third
    "idle_unattributed_share": 100 * (6.5 - 4.7 - 0.5 - 0.7) / 6.5,
}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_reads_the_synthetic_job(traced, name):
    assert _read_metric(name, traced) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_on_selfchecks_traced(name):
    assert _read_metric(name, _selfcheck_traced()) is None


def test_stage_readers_return_nothing_for_a_program_without_names(traced, tmp_path):
    """The parent commit's program: operations carry tf_op, no stage name."""
    path = program_trace.trace_file(traced)
    plain = _plane("/device:TPU:0", stat_names=("tf_op",), event_metadata=[
        (i + 1, _op_text(n, oc), {"tf_op": "jit(step)/jit(f)/add:"})
        for i, (n, oc, *_rest) in enumerate(DEVICE_OPS)])
    with open(path, "wb") as f:
        f.write(plain)
    program_trace._read.cache_clear()
    for name in NEW_METRICS[:5]:
        assert _read_metric(name, traced) is None
    # the spans are read without the annotation: the harness's tie places them
    assert _read_metric("idle_unattributed_share", traced) == pytest.approx(
        100 * (6.5 - 4.7 - 0.5 - 0.7) / 6.5)
    # a program from before the phase spans has its envelopes only
    traced["runtime_spans"] = [e for e in traced["runtime_spans"]
                               if e["name"] in ("task.build", "task.run")]
    assert _read_metric("idle_unattributed_share", traced) is None


def test_every_new_metric_is_declared_for_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    # PR 28's ten follow PR 26's six; later cells append themselves to the lists
    assert [m["name"] for m in bench["per_layer"]][6:16] == list(NEW_METRICS)
    for name in NEW_METRICS:
        assert declared[name]["workloads"][0] == "fused384.volumes"
        assert declared[name]["moves"] == "voxels_per_s"


# -- PR 32's reader: the fill's round loop ------------------------------------

FILL = STEP + "ws.fill/ws.fill.dense/"
ROUNDS = FILL + "ws.fill.rounds/while/body/"
#: two rounds of the loop: a pass of 3 trips then 1, the closure loop, and a
#: once-a-job gather outside the scope
ROUND_OPS = (
    ("fusion.20", "fusion", 16.0, 1.0, FILL + "gather:"),
    ("while.21", "while", 17.0, 4.5, ""),          # a TPU trace gives a while no path
    ("while.22", "while", 17.0, 3.0, ""),
    ("fusion.23", "fusion", 17.0, 1.0, ROUNDS + "while/body/scatter-min:"),
    ("fusion.23", "fusion", 18.0, 1.0, ROUNDS + "while/body/scatter-min:"),
    ("fusion.23", "fusion", 19.0, 1.0, ROUNDS + "while/body/scatter-min:"),
    ("while.24", "while", 20.0, 0.5, ""),
    ("fusion.25", "fusion", 20.0, 0.25, ROUNDS + "while/body/gather:"),
    ("fusion.25", "fusion", 20.25, 0.25, ROUNDS + "while/body/gather:"),
    ("while.22", "while", 20.5, 0.5, ""),
    ("fusion.23", "fusion", 20.5, 0.5, ROUNDS + "while/body/scatter-min:"),
    ("while.24", "while", 21.0, 0.25, ""),
    ("fusion.25", "fusion", 21.0, 0.25, ROUNDS + "while/body/gather:"),
    ("fusion.26", "fusion", 21.25, 0.125, ROUNDS + "any:"),
    # a last round that finds no face: its passes run no trip and leave no event
    ("while.24", "while", 21.375, 0.125, ""),
    ("fusion.25", "fusion", 21.375, 0.125, ROUNDS + "while/body/gather:"),
)


def _with_fill_ops(traced, fill_ops):
    """``traced`` with the fill's single fusion replaced by ``fill_ops``."""
    ops = [op for op in DEVICE_OPS if op[0] != "fusion.6"] + list(fill_ops)
    texts = {}
    for n, oc, _, _, tf in ops:
        texts.setdefault(_op_text(n, oc), tf)
    device = _plane(
        "/device:TPU:0", stat_names=("tf_op",),
        event_metadata=[(i + 1, text, {"tf_op": tf}) for i, (text, tf) in enumerate(texts.items())])
    with open(program_trace.trace_file(traced), "wb") as f:
        f.write(device)
    program_trace._read.cache_clear()
    traced["trace"].ops = [reduce_trace.Op(n, oc, _op_text(n, oc), s, d) for n, oc, s, d, _ in ops]
    return traced


@pytest.fixture
def traced_rounds(traced):
    """``traced`` with the fill's single fusion replaced by a round loop."""
    return _with_fill_ops(traced, ROUND_OPS)


def test_rounds_reader_sums_the_loop_and_lists_its_rounds(traced_rounds, capfd):
    assert _read_metric("ws_fill_rounds_device_s", traced_rounds) == pytest.approx(4.5)
    listed = [line for line in capfd.readouterr().err.splitlines()
              if line.startswith("[ws_fill_rounds]")]
    assert len(listed) == 3
    assert "round 1:   3.500s  while.22 x3 3.000s  while.24 x2 0.500s" in listed[0]
    assert "round 2:   0.875s  while.22 x1 0.500s  while.24 x1 0.250s" in listed[1]
    assert "round 3:   0.125s  while.24 x1 0.125s" in listed[2]
    # the stage metric keeps the whole of ws.fill: the loop and the gather outside it
    assert _read_metric("ws_fill_device_s", traced_rounds) == pytest.approx(5.5)


def test_rounds_reader_returns_nothing_without_the_scope(traced):
    """The parent commit's program has ``ws.fill.dense`` and no
    ``ws.fill.rounds``; ``selfcheck``'s trace has no stage at all."""
    assert _read_metric("ws_fill_rounds_device_s", traced) is None
    assert _read_metric("ws_fill_rounds_device_s", _selfcheck_traced()) is None


def test_rounds_metric_is_declared_for_both_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (mine,) = [m for m in bench["per_layer"] if m["name"] == "ws_fill_rounds_device_s"]
    assert mine["workloads"][:2] == ["fused384.volumes", "fused4x384.volumes.sp4"]
    assert (mine["layer"], mine["moves"]) == ("kernels", "voxels_per_s")


# -- PR 36's reader: the fill's harvest, walked to each axis's count -----------

HARVEST = FILL + "ws.fill.harvest/"
#: ids and a compaction outside the loops, then one loop an axis: 2 trips, 3
#: trips, and an axis without a face (its loop runs no trip: no leaf inside);
#: the rounds and the final resolve lie outside the scope
HARVEST_OPS = (
    ("fusion.30", "fusion", 16.0, 0.5, HARVEST + "cumsum:"),
    ("fusion.31", "fusion", 16.5, 0.25, HARVEST + "scatter:"),
    ("while.32", "while", 16.75, 1.0, ""),
    ("fusion.33", "fusion", 16.75, 0.25, HARVEST + "while/body/gather:"),
    ("fusion.34", "fusion", 17.0, 0.25, HARVEST + "while/body/dynamic_update_slice:"),
    ("fusion.33", "fusion", 17.25, 0.25, HARVEST + "while/body/gather:"),
    ("fusion.34", "fusion", 17.5, 0.25, HARVEST + "while/body/dynamic_update_slice:"),
    ("fusion.31", "fusion", 17.75, 0.25, HARVEST + "scatter:"),
    ("while.35", "while", 18.0, 0.75, ""),
    ("fusion.36", "fusion", 18.0, 0.25, HARVEST + "while/body/gather:"),
    ("fusion.36", "fusion", 18.25, 0.25, HARVEST + "while/body/gather:"),
    ("fusion.36", "fusion", 18.5, 0.25, HARVEST + "while/body/gather:"),
    ("while.37", "while", 18.75, 0.0, ""),
    ("while.38", "while", 19.0, 1.0, ""),
    ("fusion.39", "fusion", 19.0, 1.0, ROUNDS + "while/body/scatter-min:"),
    ("fusion.40", "fusion", 20.0, 1.5, FILL + "ws.fill.resolve/gather:"),
)


@pytest.fixture
def traced_harvest(traced):
    """``traced`` with the fill's single fusion replaced by harvest, one
    round and the final resolve."""
    return _with_fill_ops(traced, HARVEST_OPS)


def test_harvest_reader_sums_the_scope_and_lists_each_axis(traced_harvest, capfd):
    assert _read_metric("ws_fill_harvest_device_s", traced_harvest) == pytest.approx(2.75)
    listed = [line for line in capfd.readouterr().err.splitlines()
              if line.startswith("[ws_fill_harvest]")]
    assert len(listed) == 3
    assert "axis loop 1: while.32 x2 of 16  1.000s" in listed[0]
    assert "axis loop 2: while.35 x3 of 16  0.750s" in listed[1]
    assert "loops 1.750s, outside them 1.000s" in listed[2]
    # its neighbours keep their own: the rounds 1.0, the whole fill with the resolve
    assert _read_metric("ws_fill_rounds_device_s", traced_harvest) == pytest.approx(1.0)
    assert _read_metric("ws_fill_device_s", traced_harvest) == pytest.approx(5.25)


@pytest.mark.parametrize("which", ["the_parents_program", "selfcheck"])
def test_harvest_reader_returns_nothing_without_the_scope(traced_rounds, which):
    """The parent commit's program has ``ws.fill.rounds`` and no
    ``ws.fill.harvest``; ``selfcheck``'s trace has no stage at all."""
    assert _read_metric("ws_fill_harvest_device_s", _selfcheck_traced()
                        if which == "selfcheck" else traced_rounds) is None


def test_harvest_metric_is_declared_for_every_cell_with_the_fused_step():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (mine,) = [m for m in bench["per_layer"] if m["name"] == "ws_fill_harvest_device_s"]
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "ws_fill_harvest_device_s.json")) as f:
        meta = json.load(f)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert mine[key] == meta[key]
    # the three cells of the fused step, then (PR 37) the two-pass cell,
    # whose blockwise programs run the same harvest under a vmap, and (PR 41)
    # the per-section cell, whose planar program harvests two axes
    assert mine["workloads"] == ["fused384.volumes", "fused4x384.volumes.sp4",
                                 "multicut384.volumes", "twopass125.volumes",
                                 "ws2d125.volumes"]
    assert (mine["unit"], mine["better"], mine["source"]) == ("s", "lower", "device_trace")
    assert (mine["layer"], mine["moves"]) == ("kernels", "voxels_per_s")
    assert meta["stages"] == ["ws.fill.harvest"]


# -- PR 38's reader: the exit chase, hop by hop ---------------------------------

CHASE = STEP + "ws.flow/ws.flow.chase/"
#: the parent's program: a first gather of every slot, then one loop whose
#: every hop gathers every slot again (inside the tier's conditional)
CHASE_FULL_WIDTH_OPS = (
    ("conditional.50", "conditional", 16.0, 3.0, ""),
    ("fusion.51", "fusion", 16.0, 0.5, CHASE + "cond/branch_1_fun/gather:"),
    ("while.52", "while", 16.5, 2.5, ""),
) + tuple(("fusion.53", "fusion", 16.5 + 0.5 * i, 0.5,
           CHASE + "cond/branch_1_fun/while/body/gather:") for i in range(5))
#: the change's: the live codes compacted once, then three hops of 3, 2 and 1
#: trips; the last trip holds a loop over lanes, as a program under vmap does
CHASE_HOP_OPS = (
    ("fusion.60", "fusion", 16.0, 0.25, CHASE + "scatter:"),
    ("while.61", "while", 16.25, 3.0, ""),
    ("while.62", "while", 16.25, 1.5, ""),
) + tuple(x for i in range(3) for x in (
    ("fusion.63", "fusion", 16.25 + 0.5 * i, 0.25, CHASE + "while/body/while/body/gather:"),
    ("fusion.64", "fusion", 16.5 + 0.5 * i, 0.25, CHASE + "while/body/while/body/scatter:"),
)) + (
    ("while.62", "while", 17.75, 1.0, ""),
) + tuple(x for i in range(2) for x in (
    ("fusion.63", "fusion", 17.75 + 0.5 * i, 0.25, CHASE + "while/body/while/body/gather:"),
    ("fusion.64", "fusion", 18.0 + 0.5 * i, 0.25, CHASE + "while/body/while/body/scatter:"),
)) + (
    ("while.62", "while", 18.75, 0.5, ""),
    ("fusion.63", "fusion", 18.75, 0.125, CHASE + "while/body/while/body/gather:"),
    ("while.65", "while", 18.875, 0.25, ""),
) + tuple(("fusion.66", "fusion", 18.875 + 0.0625 * i, 0.0625,
           CHASE + "while/body/while/body/dynamic_slice:") for i in range(4)) + (
    ("fusion.64", "fusion", 19.125, 0.125, CHASE + "while/body/while/body/scatter:"),
)


def _chase_lines(capfd):
    return [line for line in capfd.readouterr().err.splitlines()
            if line.startswith("[ws_flow_chase]")]


def test_chase_reader_lists_the_parents_loop_at_the_full_width(traced, capfd):
    traced = _with_fill_ops(traced, CHASE_FULL_WIDTH_OPS)
    assert _read_metric("ws_flow_chase_device_s", traced) == pytest.approx(3.0)
    listed = _chase_lines(capfd)
    assert len(listed) == 2
    assert "loop 1: while.52 5 hops at the full width  2.500s  (0.5000s a hop)" in listed[0]
    assert "loops 2.500s, outside them 0.500s" in listed[1]
    # the stage keeps the whole flow: propagate 3.0, the exits' sort 1.0, the chase
    assert _read_metric("ws_flow_device_s", traced) == pytest.approx(7.0)


def test_chase_reader_lists_each_hop_with_its_trips(traced, capfd):
    traced = _with_fill_ops(traced, CHASE_HOP_OPS)
    assert _read_metric("ws_flow_chase_device_s", traced) == pytest.approx(3.25)
    listed = _chase_lines(capfd)
    assert len(listed) == 5
    assert "loop 1: while.61 3 hops  3.000s  6 trips" in listed[0]
    assert "hop 1: while.62 x3 of 16  1.500s" in listed[1]
    assert "hop 2: while.62 x2 of 16  1.000s" in listed[2]
    assert "hop 3: while.62 x1 of 16  0.500s" in listed[3]
    assert "loops 3.000s, outside them 0.250s" in listed[4]
    assert _read_metric("ws_flow_device_s", traced) == pytest.approx(7.25)


@pytest.mark.parametrize("which", ["a_program_without_the_scope", "selfcheck"])
def test_chase_reader_returns_nothing_without_the_scope(traced, which):
    """A program from before the stage names' inner scopes has ``ws.flow``
    and no ``ws.flow.chase``; ``selfcheck``'s trace has no stage at all."""
    assert _read_metric("ws_flow_chase_device_s", _selfcheck_traced()
                        if which == "selfcheck" else traced) is None


def test_chase_metric_is_declared_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    mine = by_name["ws_flow_chase_device_s"]
    # the work record's three readers (PR 39) read the same four cells
    for name in ("capacity_fallbacks", "capacity_peak_fill", "live_slot_share"):
        assert by_name[name]["workloads"] == mine["workloads"]
        assert by_name[name]["source"] == "program_span"
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "ws_flow_chase_device_s.json")) as f:
        meta = json.load(f)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert mine[key] == meta[key]
    # every watershed runs the chase: the fused step's three cells and the
    # two-pass and per-section cells' blockwise programs, there under a vmap
    # over lanes; the inference cell runs no watershed
    watersheds = [w["name"] for w in bench["workloads"] if w["config"] != "unet3d_cremi_125"]
    assert mine["workloads"] == watersheds == [
        "fused384.volumes", "fused4x384.volumes.sp4", "multicut384.volumes",
        "twopass125.volumes", "ws2d125.volumes"]
    assert (mine["unit"], mine["better"], mine["source"]) == ("s", "lower", "device_trace")
    assert (mine["layer"], mine["moves"]) == ("kernels", "voxels_per_s")
    assert meta["stages"] == ["ws.flow.chase"]


# -- the compiled step (PR 34) and the executor's sweep programs (PR 40) read
# back from the step store -----------------------------------------------------

#: metric, its span, its layer, the cells that report it
LOADS = [
    ("step_load_s", "fused.step_load", "entry and workflow",
     ["fused384.volumes", "fused4x384.volumes.sp4", "multicut384.volumes"]),
    ("executor_program_load_s", "executor.program_load", "executor",
     ["twopass125.volumes", "ws2d125.volumes"]),
]


@pytest.fixture(params=LOADS, ids=[m for m, *_ in LOADS])
def load_metric(request):
    return request.param


def _store_hit(traced, name):
    """``traced`` as a job whose dispatch read its program from the store:
    look-up, load, enqueue; nothing traced, lowered or compiled.  A second
    thread's load overlaps the first's (their union counts once), and a
    later job's lies outside."""
    keep = [e for e in traced["runtime_spans"] if not e["name"].startswith("jax.")]

    def span(ts, dur, tid=1, **args):
        return {"ph": "X", "name": name, "ts": ts, "dur": dur, "tid": tid, "args": args}

    traced["runtime_spans"] = keep + [
        span(101.5, 2.5, key="k", nbytes=1 << 20),
        span(103.5, 1.0, tid=2, key="k2"),
        span(131.0, 2.0),
    ]
    return traced


def test_load_reader_sums_the_store_reads_of_the_job(traced, load_metric):
    metric, name, _, _ = load_metric
    hit = _store_hit(traced, name)
    assert _read_metric(metric, hit) == pytest.approx(3.0)
    # the load has left executable_load_s, which reads jax.backend_compile
    assert _read_metric("executable_load_s", hit) is None
    assert _read_metric("trace_lower_s", hit) is None
    # and the other program's reader finds nothing of it
    (other,) = [m for m, *_ in LOADS if m != metric]
    assert _read_metric(other, hit) is None


@pytest.mark.parametrize("which", ["a_built_job", "a_process_hit", "selfcheck"])
def test_load_reader_returns_nothing_without_the_span(traced, which, load_metric):
    """The parent commit's program (and a job that built its program with
    no store): phase spans and no load span; a job that found the program
    in its process; ``selfcheck``'s trace: no span at all."""
    if which == "a_process_hit":
        traced["runtime_spans"] = [e for e in traced["runtime_spans"]
                                   if not e["name"].startswith("jax.")]
    assert _read_metric(load_metric[0], _selfcheck_traced() if which == "selfcheck"
                        else traced) is None


def test_load_metric_is_declared_for_every_cell_with_its_program(load_metric):
    metric, name, layer, cells = load_metric
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (mine,) = [m for m in bench["per_layer"] if m["name"] == metric]
    with open(os.path.join(ROOT, "benchmark", "metrics", metric + ".json")) as f:
        meta = json.load(f)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert mine[key] == meta[key]
    assert mine["workloads"] == cells
    assert (mine["unit"], mine["better"], mine["source"]) == ("s", "lower", "program_span")
    assert (mine["layer"], mine["moves"]) == (layer, "voxels_per_s")
    assert meta["spans"] == [name]
