"""The fused job measured from inside (docs/OBSERVABILITY.md "The fused
job"): host phase spans, compile counters, stage names on the device.

One traced run of the fused task at 32^3 on the virtual CPU mesh feeds most
tests (module-scoped: ``--dist loadfile`` keeps a file in one process).
"""

import glob
import json
import os
import re

import jax
import numpy as np
import pytest

from cluster_tools_tpu.runtime import trace
from cluster_tools_tpu.runtime.task import build
from cluster_tools_tpu.utils.volume_utils import file_reader

from .helpers import programs_built_here

SHAPE = (32, 32, 32)

#: every span of the fused job's table; task.finalize follows task.run
FUSED_SPANS = ("fused.setup", "fused.read", "io.read", "fused.h2d",
               "fused.dispatch", "fused.wait", "fused.d2h", "fused.widen",
               "fused.write", "io.write", "jax.trace", "jax.lower",
               "jax.backend_compile")

#: every stage scope the compiled step carries, per fill mode
STAGE_SCOPES = ("step.halo", "step.globalize", "step.stitch", "step.count",
                "edt", "ws.seeds", "ws.flow", "ws.flow.descent",
                "ws.flow.propagate", "ws.flow.exits", "ws.flow.chase",
                "ws.fill", "ccl.tile", "ccl.merge")


def _run_fused(root, tag, traced):
    """One fused job on a fixed volume; returns (tmp_folder, ws, cc, events)."""
    from cluster_tools_tpu.tasks.fused import FusedSegmentationLocal

    tmp = os.path.join(root, f"tmp_{tag}")
    os.makedirs(tmp)
    with open(os.path.join(tmp, "global.config"), "w") as f:
        json.dump({}, f)
    path = os.path.join(root, f"{tag}.zarr")
    vol = np.random.default_rng(7).random(SHAPE).astype(np.float32)
    file_reader(path).create_dataset(
        "b", shape=SHAPE, chunks=(16, 16, 16), dtype="float32")[...] = vol
    trace.configure(enabled=traced)
    try:
        task = FusedSegmentationLocal(
            tmp_folder=tmp, config_dir=tmp, max_jobs=1, input_path=path,
            input_key="b", output_path=path, ws_key="ws", cc_key="cc",
            threshold=0.5, halo=4, block_shape=[16, 16, 16])
        assert build([task]), "fused task failed (see logs)"
        events = trace._get().snapshot_events()
        stats = trace.stats()
    finally:
        trace.reset()
    r = file_reader(path, "r")
    return dict(tmp=tmp, ws=r["ws"][...], cc=r["cc"][...], events=events,
                stats=stats)


@pytest.fixture(scope="module")
def traced_job(tmp_path_factory):
    # the job that builds its step: an earlier test file of this process may
    # have left the same step ready
    with programs_built_here():
        return _run_fused(str(tmp_path_factory.mktemp("fused_traced")), "on", True)


def _spans(job, name):
    return [e for e in job["events"] if e["ph"] == "X" and e["name"] == name]


def _inside(child, parent, eps=1e-6):
    return (child["tid"] == parent["tid"]
            and child["ts"] >= parent["ts"] - eps
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + eps)


@pytest.mark.parametrize("name", FUSED_SPANS)
def test_span_recorded_and_nested_under_task_run(traced_job, name):
    run = _spans(traced_job, "task.run")
    assert len(run) == 1
    found = _spans(traced_job, name)
    assert found, f"no {name} span"
    for sp in found:
        assert _inside(sp, run[0]), f"{name} not inside task.run"
        assert sp["args"]["task"] == run[0]["args"]["task"]


def test_finalize_follows_task_run(traced_job):
    (run,), (fin,) = _spans(traced_job, "task.run"), _spans(traced_job, "task.finalize")
    assert fin["ts"] >= run["ts"] + run["dur"] - 1e-6
    assert fin["args"]["task"] == run["args"]["task"]


def test_nbytes_are_the_arrays_sizes(traced_job):
    n = int(np.prod(SHAPE))
    (read,), (io_read,) = _spans(traced_job, "fused.read"), _spans(traced_job, "io.read")
    assert read["args"]["nbytes"] == io_read["args"]["nbytes"] == 4 * n
    assert io_read["args"]["key"].endswith(":b")
    for name, width in (("fused.d2h", 4), ("fused.widen", 8), ("fused.write", 8),
                        ("io.write", 8)):
        found = _spans(traced_job, name)
        assert len(found) == 2                      # once per output
        assert [s["args"]["nbytes"] for s in found] == [width * n] * 2
    assert [s["args"]["output"] for s in _spans(traced_job, "fused.d2h")] == ["ws", "cc"]
    # the copy to the devices is a span of its own, before the dispatch;
    # one shard a device, there and back
    (h2d,), (dispatch,) = _spans(traced_job, "fused.h2d"), _spans(traced_job, "fused.dispatch")
    assert h2d["args"]["nbytes"] == 4 * n and h2d["args"]["shards"] == 8
    assert read["ts"] + read["dur"] <= h2d["ts"] and h2d["ts"] + h2d["dur"] <= dispatch["ts"]
    assert [s["args"]["shards"] for s in _spans(traced_job, "fused.d2h")] == [8, 8]
    (setup,) = _spans(traced_job, "fused.setup")
    assert setup["args"]["execution"] == "fused" and setup["args"]["mesh"] == "sp=8"


def test_children_take_no_longer_than_their_parent(traced_job):
    # fused.step_load / step_build / step_store lie inside fused.dispatch
    phases = [e for e in traced_job["events"] if e["ph"] == "X"
              and e["name"].startswith("fused.")
              and not e["name"].startswith("fused.step_")]
    (run,) = _spans(traced_job, "task.run")
    assert sum(p["dur"] for p in phases) <= run["dur"] + 1e-6
    for write in _spans(traced_job, "fused.write"):
        inner = [s for s in _spans(traced_job, "io.write") if _inside(s, write)]
        assert len(inner) == 1 and inner[0]["dur"] <= write["dur"]
    # self time on the same rule, from the merged timeline's summary
    with open(os.path.join(traced_job["tmp"], "trace_summary.json")) as f:
        sites = json.load(f)["sites"]
    assert sites["fused.write"]["self_s"] <= sites["fused.write"]["total_s"]
    assert sites["task.run"]["self_s"] < 0.5 * sites["task.run"]["total_s"]


def test_listener_spans_carry_fun_name_inside_dispatch(traced_job):
    (dispatch,) = _spans(traced_job, "fused.dispatch")
    assert dispatch["args"]["fun_name"] == "ws_ccl_step"
    for name in ("jax.trace", "jax.lower", "jax.backend_compile"):
        step = [s for s in _spans(traced_job, name)
                if "ws_ccl_step" in s["args"].get("fun_name", "")]
        assert step, f"no {name} span of the step"
        assert all(_inside(s, dispatch) for s in step)


def test_compile_counters_in_io_metrics(traced_job):
    with open(os.path.join(traced_job["tmp"], "io_metrics.json")) as f:
        tasks = json.load(f)["tasks"]
    (comp,) = [m["compile"] for uid, m in tasks.items()
               if uid.startswith("fused_segmentation.")]
    assert set(comp) == {"requests", "cache_hits", "cache_misses", "uncached",
                         "trace_s", "lower_s", "backend_s", "cache_load_s",
                         "programs_missed"}
    assert comp["cache_misses"] == comp["requests"] - comp["cache_hits"]
    assert comp["trace_s"] > 0 and comp["lower_s"] > 0 and comp["backend_s"] > 0
    # the step was built anew for this job, so it reached the compiler
    assert any("ws_ccl_step" in p for p in comp["programs_missed"])
    # self times add up to no more than the dispatch they happened in
    (run,) = _spans(traced_job, "task.run")
    total = sum(comp[k] for k in ("trace_s", "lower_s", "backend_s", "cache_load_s"))
    assert total <= run["dur"]


def test_tracer_off_records_nothing_and_labels_are_bit_identical(traced_job, tmp_path):
    off = _run_fused(str(tmp_path), "off", False)
    assert off["stats"] == {"spans": 0, "instants": 0, "dropped": 0, "flushes": 0}
    assert off["events"] == []
    assert not os.path.exists(os.path.join(off["tmp"], "trace.json"))
    np.testing.assert_array_equal(off["ws"], traced_job["ws"])
    np.testing.assert_array_equal(off["cc"], traced_job["cc"])
    # the counters are always on: which level gave the step (a build counts
    # its compile too, tests/test_fused_step_cache.py)
    with open(os.path.join(off["tmp"], "io_metrics.json")) as f:
        tasks = json.load(f)["tasks"]
    assert any(sum(m.get("step_cache", {}).values()) == 1 for m in tasks.values())


def test_manifest_carries_device_memory(traced_job):
    (mf,) = glob.glob(os.path.join(traced_job["tmp"], "fused_segmentation.*.success.json"))
    with open(mf) as f:
        doc = json.load(f)
    assert doc["device_memory"] == {}        # the CPU backend reports none
    logs = "".join(open(p).read() for p in glob.glob(os.path.join(traced_job["tmp"], "*.log")))
    assert "device.peak_bytes=" not in logs


def test_device_peak_bytes_reports_both_peaks():
    from cluster_tools_tpu.parallel.mesh import device_peak_bytes

    class Dev:
        platform, id = "tpu", 0

        def memory_stats(self):
            return {"peak_bytes_in_use": 10, "peak_bytes_reserved": 30,
                    "bytes_in_use": 1}

    class Silent(Dev):
        id = 1

        def memory_stats(self):
            return None

    assert device_peak_bytes([Dev(), Silent()]) == {
        "tpu:0": {"peak_bytes_in_use": 10, "peak_bytes_reserved": 30}}


def test_spans_land_on_host_cpu_under_a_profiler_session(tmp_path):
    """With the tracer on, a context-managed span opens a TraceAnnotation:
    the profiler's own trace shows the program's spans on /host:CPU, and the
    task.run annotation ties the ring's clock to the trace's."""
    from benchmark import program_trace

    trace.configure(enabled=True)
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with trace.task_context("demo"):
                with trace.span("fused.read", nbytes=8):
                    np.zeros(8).sum()
        finally:
            jax.profiler.stop_trace()
        ring = {e["name"]: e for e in trace._get().snapshot_events()}
    finally:
        trace.reset()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    host = {name: (start, dur) for name, start, dur in program_trace._read(path)["host"]}
    assert "task.run" in host and "fused.read" in host
    # one shift places both ring spans on the trace's clock
    shift = host["task.run"][0] - ring["task.run"]["ts"]
    assert abs(host["fused.read"][0] - (ring["fused.read"]["ts"] + shift)) < 2e-3
    assert abs(host["fused.read"][1] - ring["fused.read"]["dur"]) < 2e-3


def test_tracer_off_opens_no_annotation(monkeypatch):
    monkeypatch.delenv("CTT_TRACE", raising=False)
    trace.reset()
    opened = []
    monkeypatch.setattr(trace, "_annotate", lambda name: opened.append(name))
    with trace.span("fused.read"):
        pass
    with trace.begin("task.run"):
        pass
    assert opened == []


@pytest.mark.parametrize("execution", ["fused", "split"])
@pytest.mark.parametrize("fill_mode", ["dense", "capacity"])
def test_step_hlo_holds_every_stage_scope(monkeypatch, fill_mode, execution):
    """The names reach the program the compiler gets: the lowered step's
    text (with debug info: the op_name of every operation) holds every
    stage scope, for both fills and both executions."""
    from cluster_tools_tpu.parallel.mesh import make_mesh
    from cluster_tools_tpu.parallel.pipeline import make_ws_ccl_step
    from cluster_tools_tpu.parallel.split_pipeline import make_ws_ccl_split

    monkeypatch.setenv("CT_FILL_MODE", fill_mode)
    mesh = make_mesh(axis_names=("dp", "sp"), grid=(1, 2), devices=jax.devices()[:2])
    kw = dict(halo=2, threshold=0.5, dt_max_distance=2.0, stitch_ws_threshold=0.5)
    x = jax.ShapeDtypeStruct((1, 16, 16, 16), np.float32)
    if execution == "fused":
        step = make_ws_ccl_step(mesh, **kw)
        assert step.__name__ == "ws_ccl_step"
        text = step.lower(x).as_text(debug_info=True)
    else:
        split = make_ws_ccl_split(mesh, **kw)
        assert [f.__name__ for f in split.stages.values()] == [
            "ws_ccl_seeds", "ws_ccl_flow", "ws_ccl_fill", "ws_ccl_cc"]
        padded, seeds, ovf, rec = jax.eval_shape(split.stages["seeds"], x)
        values, h, _, _ = jax.eval_shape(
            split.stages["flow"], padded, seeds, ovf, rec)
        text = "".join(
            split.stages[name].lower(*args).as_text(debug_info=True)
            for name, args in (("seeds", (x,)), ("flow", (padded, seeds, ovf, rec)),
                               ("fill", (values, h, x, ovf, rec)),
                               ("cc", (x, ovf, rec))))
    for scope in STAGE_SCOPES + (f"ws.fill.{fill_mode}",):
        # a location reads loc("ws.flow/ws.flow.chase/reshape"(...))
        assert re.search(r'["/]' + re.escape(scope) + r'["/]', text), scope


def test_summarize_self_s():
    """Self time: a span's duration less what its children (the innermost
    containing span of the same process and thread is the parent) cover."""
    def ev(name, ts, dur, tid=0, pid=1):
        return {"ph": "X", "name": name, "pid": pid, "tid": tid, "ts": ts * 1e6,
                "dur": dur * 1e6, "args": {}}

    sites = trace.summarize({"traceEvents": [
        ev("task.run", 0.0, 10.0),
        ev("fused.dispatch", 1.0, 6.0),
        ev("jax.trace", 1.5, 2.0),
        ev("jax.trace", 2.0, 0.5),          # an inner jit, inside the outer trace
        ev("jax.lower", 3.5, 1.0),
        ev("fused.wait", 7.0, 2.0),
        ev("io.read", 3.0, 5.0, tid=1),     # another thread: nobody's child
    ]})["sites"]
    assert sites["task.run"]["self_s"] == pytest.approx(2.0)
    assert sites["fused.dispatch"]["self_s"] == pytest.approx(3.0)
    assert sites["jax.trace"]["total_s"] == pytest.approx(2.5)
    assert sites["jax.trace"]["self_s"] == pytest.approx(2.0)
    assert sites["jax.lower"]["self_s"] == pytest.approx(1.0)
    assert sites["io.read"]["self_s"] == pytest.approx(5.0)


def test_compile_delta_counts_a_fresh_program_once():
    """Always on: a program that is new to the process moves the counters,
    a cached call does not; nested phases are counted as self time."""
    trace.reset()
    snap = trace.compile_snapshot()

    def fresh_program_for_compile_delta(x):
        return jax.numpy.sort(x * 3.0 + 1.0)

    f = jax.jit(fresh_program_for_compile_delta)
    f(np.arange(8.0)).block_until_ready()
    first = trace.compile_delta(snap)
    assert any("fresh_program_for_compile_delta" in p for p in first["programs_missed"])
    assert first["trace_s"] > 0 and first["lower_s"] > 0 and first["backend_s"] > 0
    assert first["requests"] - first["cache_hits"] == first["cache_misses"]
    snap = trace.compile_snapshot()
    f(np.arange(8.0)).block_until_ready()
    again = trace.compile_delta(snap)
    assert again["programs_missed"] == [] and again["trace_s"] == 0
    assert trace.stats()["spans"] == 0      # counters, not spans, when off


def test_compile_phases_are_self_time(monkeypatch):
    t0 = trace.time.monotonic() + 1000.0     # later than anything heard so far
    clock = [t0]
    monkeypatch.setattr(trace.time, "monotonic", lambda: clock[0])
    snap = trace.compile_snapshot()
    trace_ev = "/jax/core/compile/jaxpr_trace_duration"
    clock[0] = t0 + 1.0
    trace._on_compile_duration(trace_ev, 0.5, fun_name="inner")     # [0.5, 1]
    clock[0] = t0 + 2.0
    trace._on_compile_duration(trace_ev, 2.0, fun_name="outer")     # [0, 2]
    clock[0] = t0 + 4.0
    trace._on_compile_event("/jax/compilation_cache/compile_requests_use_cache")
    trace._on_compile_event("/jax/compilation_cache/cache_hits")
    trace._on_compile_duration("/jax/compilation_cache/cache_retrieval_time_sec", 1.0)
    clock[0] = t0 + 4.5
    trace._on_compile_duration("/jax/core/compile/backend_compile_duration", 1.5,
                               fun_name="outer")
    delta = trace.compile_delta(snap)
    assert delta["trace_s"] == pytest.approx(2.0)        # not 2.5
    assert delta["cache_load_s"] == pytest.approx(1.0)
    assert delta["backend_s"] == pytest.approx(0.5)      # the rest of 1.5
    assert (delta["requests"], delta["cache_hits"], delta["cache_misses"],
            delta["uncached"]) == (1, 1, 0, 0)
    assert delta["programs_missed"] == []
