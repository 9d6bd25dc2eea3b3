"""Unified tracing plane (docs/OBSERVABILITY.md, ``runtime/trace.py``).

Covers the ISSUE-10 satellite matrix: trace shards from a 2-process
worker group merge into one ordered timeline (including the clock-offset
case — each process's monotonic timestamps are placed through its own
``(wall0, mono0)`` anchor), tracer-off is a TRUE no-op (no files, no
counters), the aggregator's percentiles / critical path / overlap
figures, the executor's span emission through a real sweep, the CT008
timing discipline helpers, io_metrics provenance (schema v2), and the
text/JSON report surfaces (``failures_report.py --trace/--json``,
``scripts/progress.py``).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from cluster_tools_tpu.runtime import trace

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO_ROOT, "scripts")


@pytest.fixture(autouse=True)
def _fresh_tracer():
    trace.reset()
    yield
    trace.reset()


def _shard(tmp, hostname, pid, wall0, mono0, events):
    """Hand-write one process shard (the schema flush() produces)."""
    os.makedirs(tmp, exist_ok=True)
    path = os.path.join(tmp, f"shard_{hostname}_{pid}.json")
    with open(path, "w") as f:
        json.dump({
            "version": 1, "pid": pid, "hostname": hostname,
            "wall0": wall0, "mono0": mono0, "dropped": 0,
            "events": events,
        }, f)
    return path


def _span(name, ts, dur, tid=1, **args):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "tid": tid,
            "args": args}


# -- merger: clock-offset correction across processes -------------------------


def test_merge_two_process_clock_offset(tmp_path):
    """Two shards whose monotonic clocks are offset by HOURS still
    interleave correctly: event order on the merged timeline follows the
    wall anchors, not the raw monotonic values."""
    d = str(tmp_path / "trace")
    # process A: booted long ago (mono runs high), events at wall 1000.0+
    _shard(d, "hosta", 100, wall0=1000.0, mono0=50_000.0, events=[
        _span("executor.load", 50_000.5, 0.2, block=1),
        _span("executor.store", 50_002.0, 0.1, block=1),
    ])
    # process B: fresh boot (mono near zero), events at wall 1001.0+
    # -> its first event falls BETWEEN A's two events on the wall clock
    _shard(d, "hostb", 200, wall0=1001.0, mono0=3.0, events=[
        _span("solve.worker", 3.1, 0.5, worker=1),
    ])
    doc = trace.merge(d)
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert [e["name"] for e in spans] == [
        "executor.load", "solve.worker", "executor.store",
    ]
    # rebased at the earliest event, microseconds
    assert spans[0]["ts"] == 0.0
    assert spans[1]["ts"] == pytest.approx(0.6e6)
    assert spans[2]["ts"] == pytest.approx(1.5e6)
    # two distinct process tracks, named host:pid
    names = {
        e["args"]["name"] for e in doc["traceEvents"]
        if e.get("ph") == "M" and e["name"] == "process_name"
    }
    assert names == {"hosta:100", "hostb:200"}
    assert doc["otherData"]["processes"] == 2


def test_merge_skips_torn_shard(tmp_path):
    d = str(tmp_path / "trace")
    _shard(d, "h", 1, 10.0, 0.0, [_span("task.run", 0.0, 1.0, task="t")])
    with open(os.path.join(d, "shard_h_2.json"), "w") as f:
        f.write('{"version": 1, "events": [')  # torn mid-write
    doc = trace.merge(d)
    assert [e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"] \
        == ["task.run"]


def test_two_real_processes_flush_and_merge(tmp_path):
    """Two actual subprocesses (distinct pids, independent monotonic
    anchors) flush shards into one directory via CTT_TRACE=<dir>; the
    merged timeline holds both processes' spans in wall order."""
    d = str(tmp_path / "trace")
    # worker 1 opens its span only after worker 0 has closed its own (a
    # file handed over, not a fixed stagger: importing the package takes
    # seconds and varies by more than any short sleep on a loaded host)
    prog = (
        "import os, time\n"
        "from cluster_tools_tpu.runtime import trace\n"
        "idx = int(os.environ['IDX'])\n"
        "baton = os.environ['BATON']\n"
        "while idx and not os.path.exists(baton):\n"
        "    time.sleep(0.01)\n"
        "with trace.span('worker.main', worker=idx):\n"
        "    time.sleep(0.05)\n"
        "if not idx:\n"
        "    open(baton, 'w').close()\n"
        "assert trace.flush() is not None\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["CTT_TRACE"] = d
    env["BATON"] = str(tmp_path / "baton")
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen([sys.executable, "-c", prog],
                         env={**env, "IDX": str(i)})
        for i in range(2)
    ]
    for p in procs:
        assert p.wait(timeout=60) == 0
    assert len(os.listdir(d)) == 2
    doc = trace.merge(d)
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert [e["args"]["worker"] for e in spans] == [0, 1]  # wall order
    assert len({e["pid"] for e in spans}) == 2
    summary = trace.summarize(doc)
    assert summary["n_processes"] == 2
    assert summary["sites"]["worker.main"]["count"] == 2


# -- tracer-off: a true no-op -------------------------------------------------


def test_tracer_off_is_noop(tmp_path, monkeypatch):
    monkeypatch.delenv("CTT_TRACE", raising=False)
    trace.reset()
    assert not trace.enabled()
    # pure-timeline spans return the shared null context: no clock reads,
    # no allocation, no counters
    s1 = trace.span("executor.load", block=1)
    s2 = trace.span("executor.store", block=2)
    assert s1 is s2
    with s1:
        pass
    trace.instant("fault:load", block=1)
    # begin() still measures (counters need the elapsed seconds) but must
    # not record
    sp = trace.begin("executor.sweep")
    assert sp.end() >= 0.0
    assert trace.flush() is None
    assert trace.write_timeline(str(tmp_path)) is None
    assert trace.stats() == {
        "spans": 0, "instants": 0, "dropped": 0, "flushes": 0,
    }
    assert not os.path.exists(str(tmp_path / "trace.json"))


def test_operator_env_pin_wins(monkeypatch, tmp_path):
    pin = str(tmp_path / "pinned")
    monkeypatch.setenv("CTT_TRACE", pin)
    trace.reset()
    assert trace.enabled()
    assert trace.trace_dir() == pin
    trace.set_trace_dir(str(tmp_path / "elsewhere"))  # first writer wins
    assert trace.trace_dir() == pin


def test_new_run_repoints_task_derived_dir(tmp_path, monkeypatch):
    """A long-lived process running run A then run B (different tmp_folder):
    B's task-derived set_trace_dir seals A's shard in A's dir, clears the
    ring, and re-points — the two runs' timelines never cross-contaminate.
    Explicit configure()/env dirs stay pinned (previous test)."""
    monkeypatch.setenv("CTT_TRACE", "1")
    trace.reset()
    dir_a = str(tmp_path / "a" / "trace")
    dir_b = str(tmp_path / "b" / "trace")
    trace.set_trace_dir(dir_a)
    with trace.span("task.run", task="run_a"):
        pass
    trace.set_trace_dir(dir_a)  # same run: no-op
    assert trace.trace_dir() == dir_a
    trace.set_trace_dir(dir_b)  # NEW run: seal A, fresh ring
    assert trace.trace_dir() == dir_b
    with trace.span("task.run", task="run_b"):
        pass
    trace.flush()
    ev_a = trace.merge(dir_a)["traceEvents"]
    ev_b = trace.merge(dir_b)["traceEvents"]
    tasks_a = {e["args"]["task"] for e in ev_a if e.get("ph") == "X"}
    tasks_b = {e["args"]["task"] for e in ev_b if e.get("ph") == "X"}
    assert tasks_a == {"run_a"} and tasks_b == {"run_b"}
    trace.reset()


def test_ring_buffer_drops_oldest(tmp_path):
    trace.configure(enabled=True, trace_dir=str(tmp_path / "t"), buffer=10)
    for i in range(25):
        with trace.span("s", i=i):
            pass
    st = trace.stats()
    assert st["spans"] == 10 and st["dropped"] == 15
    trace.flush()
    doc = trace.merge(str(tmp_path / "t"))
    assert doc["otherData"]["dropped"] == 15


# -- aggregator ----------------------------------------------------------------


def test_summarize_percentiles_and_critical_path(tmp_path):
    d = str(tmp_path / "trace")
    events = [
        _span("executor.load", float(i), 0.010 + 0.001 * i, block=i)
        for i in range(100)
    ]
    # a 3-task chain + an off-path sibling: the critical path must follow
    # the dependency edges, not just the biggest durations
    events += [
        _span("task.run", 200.0, 10.0, task="a.1", deps=[]),
        _span("task.run", 211.0, 5.0, task="b.1", deps=["a.1"]),
        _span("task.run", 211.0, 20.0, task="side.1", deps=[]),
        _span("task.run", 232.0, 2.0, task="c.1", deps=["b.1", "side.1"]),
    ]
    _shard(d, "h", 1, 1000.0, 0.0, events)
    summary = trace.summarize(trace.merge(d))
    site = summary["sites"]["executor.load"]
    assert site["count"] == 100
    assert site["p50_ms"] == pytest.approx(60.0, abs=2.0)
    assert site["p99_ms"] == pytest.approx(109.0, abs=2.0)
    assert site["max_ms"] == pytest.approx(109.0, abs=1.0)
    cp = summary["critical_path"]
    assert cp["tasks"] == ["side.1", "c.1"]
    assert cp["total_s"] == pytest.approx(22.0)


def test_summarize_overlap_and_utilization(tmp_path):
    d = str(tmp_path / "trace")
    _shard(d, "h", 1, 0.0, 0.0, [
        _span("executor.sweep", 0.0, 10.0),
        _span("executor.batch_wait", 1.0, 2.0),
        {"ph": "i", "name": "degraded:unsharded", "ts": 5.0, "dur": 0.0,
         "tid": 1, "args": {"block": 3}},
    ])
    summary = trace.summarize(trace.merge(d))
    assert summary["overlap"]["overlap_efficiency"] == pytest.approx(0.8)
    assert summary["instants"] == {"degraded:unsharded": 1}
    (proc,) = summary["processes"]
    assert proc["busy_s_by_cat"]["executor"] == pytest.approx(12.0)


# -- the executor emits the span set through a real sweep ----------------------


def test_executor_sweep_emits_spans(tmp_path):
    from cluster_tools_tpu.runtime.executor import BlockwiseExecutor
    from cluster_tools_tpu.utils.volume_utils import Blocking

    trace.configure(enabled=True, trace_dir=str(tmp_path / "trace"))
    blocking = Blocking([16, 16, 16], [8, 8, 8])
    blocks = [blocking.get_block(i) for i in range(blocking.n_blocks)]
    store = {}
    ex = BlockwiseExecutor(io_threads=2, max_retries=1)
    with trace.task_context("trace_sweep"):
        ex.map_blocks(
            lambda x: x + 1, blocks,
            load_fn=lambda b: (np.zeros((8, 8, 8), np.float32),),
            store_fn=lambda b, out: store.__setitem__(int(b.block_id), out),
            failures_path=None, task_name="trace_sweep",
            block_deadline_s=None, watchdog_period_s=None,
            store_verify_fn=None, schedule="morton", sweep_mode="auto",
        )
    trace.flush()
    summary = trace.write_timeline(str(tmp_path))
    sites = summary["sites"]
    assert sites["executor.load"]["count"] == 8
    assert sites["executor.store"]["count"] == 8
    assert sites["executor.dispatch"]["count"] >= 1
    assert sites["executor.sweep"]["count"] == 1
    assert sites["task.run"]["count"] == 1
    # every per-block span is task-attributed (CT008's point)
    doc = json.load(open(str(tmp_path / "trace.json")))
    for e in doc["traceEvents"]:
        if e.get("name") in ("executor.load", "executor.store"):
            assert e["args"]["task"] == "trace_sweep"


def test_walltime_matches_time_time():
    import time

    assert abs(trace.walltime() - time.time()) < 1.0


# -- io_metrics provenance (schema v2) ----------------------------------------


def test_record_io_metrics_provenance(tmp_path):
    import socket

    from cluster_tools_tpu.utils import function_utils as fu

    path = str(tmp_path / "io_metrics.json")
    fu.record_io_metrics(path, "ws.1", {"hits": 5, "misses": 2})
    fu.record_io_metrics(path, "ws.1", {"hits": 3, "sweep_s": 0.5})
    doc = json.load(open(path))
    assert doc["version"] == 2
    assert doc["tasks"]["ws.1"]["hits"] == 8  # additive merge unchanged
    key = f"{socket.gethostname()}:{os.getpid()}"
    prov = doc["provenance"]["ws.1"][key]
    assert prov["merges"] == 2
    assert set(prov["counters"]) == {"hits", "misses", "sweep_s"}
    assert prov["last_updated"]
    # a second (simulated) process stays separately attributable
    doc["provenance"]["ws.1"]["otherhost:999"] = {
        "host": "otherhost", "pid": 999, "merges": 1,
        "last_updated": "x", "counters": ["hits"],
    }
    with open(path, "w") as f:
        json.dump(doc, f)
    fu.record_io_metrics(path, "ws.1", {"hits": 1})
    doc = json.load(open(path))
    assert len(doc["provenance"]["ws.1"]) == 2


# -- report surfaces ----------------------------------------------------------


def _report_main():
    sys.path.insert(0, SCRIPTS)
    import failures_report

    return failures_report


def test_failures_report_trace_section(tmp_path, capsys):
    fr = _report_main()
    d = str(tmp_path)
    _shard(os.path.join(d, "trace"), "h", 1, 0.0, 0.0, [
        _span("task.run", 0.0, 1.0, task="t.1", deps=[]),
        _span("executor.load", 0.1, 0.2, block=0),
    ])
    trace.write_timeline(d, os.path.join(d, "trace"))
    assert fr.main(["failures_report.py", "--trace", d]) == 0
    out = capsys.readouterr().out
    assert "executor.load" in out and "critical path" in out


def test_failures_report_json_combined(tmp_path, capsys):
    fr = _report_main()
    d = str(tmp_path)
    from cluster_tools_tpu.utils import function_utils as fu

    fu.record_failures(
        os.path.join(d, "failures.json"), "ws.1",
        [{"block_id": 3, "sites": {"load": 2}, "error": "boom",
          "quarantined": True, "resolved": True}],
    )
    fu.record_io_metrics(
        os.path.join(d, "io_metrics.json"), "ws.1", {"hits": 1}
    )
    _shard(os.path.join(d, "trace"), "h", 1, 0.0, 0.0,
           [_span("task.run", 0.0, 1.0, task="ws.1", deps=[])])
    trace.write_timeline(d, os.path.join(d, "trace"))
    rc = fr.main(["failures_report.py", "--json", d, "--no-lint"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0  # resolved failures + no lint pass = clean
    assert doc["failures"]["n_records"] == 1
    assert doc["failures"]["tasks"][0]["task"] == "ws.1"
    assert doc["io_metrics"]["tasks"]["ws.1"]["hits"] == 1
    assert doc["io_metrics"]["provenance"]["ws.1"]
    assert doc["trace"]["sites"]["task.run"]["count"] == 1
    assert doc["lint"] is None


def test_progress_script(tmp_path, capsys):
    sys.path.insert(0, SCRIPTS)
    import progress

    from cluster_tools_tpu.runtime.supervision import write_heartbeat
    from cluster_tools_tpu.utils import function_utils as fu

    d = str(tmp_path)
    # task A: done (manifest + markers)
    fu.log_block_success(d, "a.1", 0)
    fu.log_block_success(d, "a.1", 1)
    fu.atomic_write_json(
        os.path.join(d, "a.1.success.json"), {"runtime_s": 1.5}
    )
    # task B: in-flight (fresh heartbeat, some markers, no manifest)
    fu.log_block_success(d, "b.1", 0)
    write_heartbeat(d, "b.1")
    # task C: failed (unresolved record)
    fu.record_failures(
        os.path.join(d, "failures.json"), "c.1",
        [{"block_id": 7, "sites": {"store": 3}, "error": "x",
          "quarantined": True, "resolved": False}],
    )
    doc = progress.collect_progress(d, stale_after_s=60.0)
    states = {t["task"]: t["state"] for t in doc["tasks"]}
    assert states["a.1"] == "done"
    assert states["b.1"] == "in-flight"
    assert states["c.1"] == "failed"
    by = {t["task"]: t for t in doc["tasks"]}
    assert by["a.1"]["blocks_done"] == 2
    assert by["c.1"]["unresolved"] == 1
    rc = progress.main(["progress.py", d])
    out = capsys.readouterr().out
    assert rc == 1  # a failed task = operator attention
    assert "UNRESOLVED" in out and "done" in out
    # stale heartbeat -> stalled? warning
    doc = progress.collect_progress(d, stale_after_s=0.0)
    states = {t["task"]: t["state"] for t in doc["tasks"]}
    assert states["b.1"] == "stalled?"


# -- CT008 guards against regression ------------------------------------------


def test_no_wall_clock_timing_in_runtime():
    """The CT008 invariant, asserted directly (belt + braces with the
    lint rule): runtime/ reads time.time/perf_counter only in trace.py."""
    runtime_dir = os.path.join(REPO_ROOT, "cluster_tools_tpu", "runtime")
    offenders = []
    for fname in sorted(os.listdir(runtime_dir)):
        if not fname.endswith(".py") or fname == "trace.py":
            continue
        src = open(os.path.join(runtime_dir, fname)).read()
        if "time.time()" in src or "perf_counter()" in src:
            offenders.append(fname)
    assert offenders == []
