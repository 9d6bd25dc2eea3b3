"""Run one cell of the benchmark once.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Makes sure the cell's programs are in the persistent compile cache (where
they are not: a warm-up job in a child process, before this one opens the
chip, so that the window starts from the same state in a run that compiles
as in every later one), opens the chip, makes the cell's volumes from
``--seed``, then runs whole jobs back to back through the entry that
``python -m cluster_tools_tpu.cli run <workflow>`` uses, for ``--seconds``;
a job that has started runs to its end and counts.  After the
window it reads the device's peak, compares what the jobs stored with the
plain reference (the comparison that the configuration names), and prints
one JSON object as the last line of standard output.  There is no CPU
fallback: without a TPU it exits 2.

The harness is driven by data.  It finds the cell in
``benchmark/workloads/<cell>.json``, its configuration in
``benchmark/configs/<config>.json``, the configuration's comparison in
``benchmark/comparisons/<comparison>.py`` and every per-layer metric in
``benchmark/metrics/<metric>.json`` (with ``<metric>.py`` as its reader), by
the names in ``BENCHMARK.json`` and in those files; see README.md.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse
import contextlib
import glob
import hashlib
import importlib.util
import json
import logging
import os
import shutil
import subprocess
import sys
import threading
import traceback
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(HERE, "_work")
#: the bound the harness gives JAX's persistent cache in its own process: the
#: machine's 192 MiB would evict a cell's programs between two runs
CACHE_MAX_BYTES = 8 << 30


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_PROCESS:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# --------------------------------------------------------------------------
# the cell, from the files named in BENCHMARK.json
# --------------------------------------------------------------------------


def load_cell(name: str) -> dict:
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in bench['workloads']]}")
    cell = load_json(HERE, "workloads", name + ".json")
    for key in ("config", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(f"{name}: {key} differs between BENCHMARK.json "
                             f"({entry[key]!r}) and its file ({cell[key]!r})")
    conf_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, conf_entry["file"])

    def reports(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    return dict(
        name=name, cell=cell, config=config, bench=bench,
        end_to_end=[m for m in bench["end_to_end"] if reports(m)],
        per_layer=[m for m in bench["per_layer"] if reports(m)],
    )


def source_digest(spec: dict) -> str:
    """What the cell's compiled programs depend on: the program's sources,
    the cell's two files, JAX, and where the checkout lies (a program's
    cache key holds the paths of its sources: the same tree at another path
    misses the entries a first one wrote).  A marker made for another digest
    is not this cell's."""
    import jax

    h = hashlib.sha256()
    paths = sorted(glob.glob(os.path.join(ROOT, "cluster_tools_tpu", "**", "*.py"),
                             recursive=True))
    for p in paths:
        with open(p, "rb") as f:
            h.update(p[len(ROOT):].encode() + b"\0" + f.read())
    h.update(json.dumps([spec["cell"], spec["config"]], sort_keys=True).encode())
    h.update((jax.__version__ + "\0" + ROOT).encode())
    return h.hexdigest()[:20]


# --------------------------------------------------------------------------
# counting compiles: JAX's own monitoring events
# --------------------------------------------------------------------------


class CompileCounter(logging.Handler):
    """Counts compile requests, persistent-cache hits and the seconds spent
    reading programs back, from JAX's monitoring events; keeps the names of
    the programs that missed the cache, from JAX's own debug log."""

    def __init__(self):
        import jax.monitoring as mon

        super().__init__(logging.DEBUG)
        self.requests = self.hits = self.uncached = 0
        self.load_s = self.compile_s = 0.0
        self.missed: List[str] = []
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)
        log = logging.getLogger("jax._src.compiler")
        log.addHandler(self)
        if log.getEffectiveLevel() > logging.DEBUG:
            log.setLevel(logging.DEBUG)
            log.propagate = False  # its debug lines are for this handler only

    def emit(self, record):
        if "CACHE MISS" in str(record.msg) and record.args:
            self.missed.append(str(record.args[0]))

    def _event(self, name: str, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/task_disabled_cache":
            self.uncached += 1

    def _duration(self, name: str, secs: float, **_):
        if name == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.load_s += secs
        elif name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def snapshot(self) -> dict:
        return dict(requests=self.requests, hits=self.hits,
                    misses=self.requests - self.hits, uncached=self.uncached,
                    load_s=self.load_s, compile_or_load_s=self.compile_s)

    def since(self, snap: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - snap[k] for k in now}


# --------------------------------------------------------------------------
# one job, as `cli run <workflow>` builds it
# --------------------------------------------------------------------------


def absorbed_failures(tmp_folder: str) -> List[str]:
    """Everything in a job's records that means the program absorbed a
    failure: the guarantees count each such job as failed."""
    bad = []
    path = os.path.join(tmp_folder, "failures.json")
    if os.path.exists(path):
        for rec in load_json(path).get("records", []):
            bad.append(f"failures.json: task={rec.get('task')} block="
                       f"{rec.get('block_id')} resolution={rec.get('resolution')} "
                       f"quarantined={rec.get('quarantined')}")
    for mf in glob.glob(os.path.join(tmp_folder, "*.success.json")):
        doc = load_json(mf)
        if doc.get("overflow_blocks"):
            bad.append(f"{os.path.basename(mf)}: capacity overflow in blocks "
                       f"{doc['overflow_blocks'][:8]}")
    path = os.path.join(tmp_folder, "io_metrics.json")
    if os.path.exists(path):
        for task, m in (load_json(path).get("tasks") or {}).items():
            for key in ("host_staged_fallbacks", "unsharded_fallbacks"):
                if m.get(key):
                    bad.append(f"io_metrics.json: {task}: {key}={m[key]}")
    return bad


class Runner:
    def __init__(self, spec: dict, seed: int, target: str, work: str):
        self.spec, self.seed, self.target, self.work = spec, seed, target, work
        self.config = spec["config"]
        self.traffic = {**self.config["data"], **spec["cell"]["traffic"]}
        self.store_in = os.path.join(work, "in.zarr")
        self.store_out = os.path.join(work, "out.zarr")

    def params(self, job, tag: str) -> tuple:
        p = dict(self.config["params"])
        p.update(input_path=self.store_in, input_key=f"vol{job.volume}",
                 output_path=self.store_out)
        outputs = {}
        for name, param in self.config["outputs"].items():
            p[param] = f"{name}_{tag}"
            outputs[name] = (self.store_out, p[param])
        if job.roi_begin is not None:
            p.update(roi_begin=list(job.roi_begin), roi_end=list(job.roi_end))
        return p, outputs

    def run(self, job, tag: str) -> dict:
        """One whole job: a fresh workflow object, tmp_folder and output
        keys, built and run the way ``cli.cmd_run`` does."""
        from cluster_tools_tpu import cli
        from cluster_tools_tpu.parallel.mesh import configure_compile_cache
        from cluster_tools_tpu.runtime.task import build

        tmp = os.path.join(self.work, "jobs", tag)
        os.makedirs(tmp, exist_ok=True)
        with open(os.path.join(tmp, "global.config"), "w") as f:
            json.dump(self.config.get("global_config", {}), f)
        params, outputs = self.params(job, tag)
        rec = dict(job=job, tag=tag, tmp=tmp, outputs=outputs, ok=False, error=None)
        rec["t0"] = time.monotonic()
        try:
            configure_compile_cache()
            cls = cli._resolve(self.config["workflow"])
            wf = cls(tmp_folder=tmp, config_dir=tmp,
                     max_jobs=int(self.config.get("max_jobs", 4)),
                     target=self.target, **params)
            rec["ok"] = bool(build([wf]))
        except Exception as e:  # a job that raises is a failed job, not a crash
            rec["error"] = f"{type(e).__name__}: {e}"
            say("job raised:\n" + traceback.format_exc()[-3000:])
        rec["t1"] = time.monotonic()
        rec["absorbed"] = absorbed_failures(tmp)
        rec["completed"] = rec["ok"] and not rec["absorbed"]
        return rec


# --------------------------------------------------------------------------
# the traced run's extra spans (set from here, around the program's doorways)
# --------------------------------------------------------------------------


class IoSpans:
    """Times every read and write through the container doorway
    (``io/containers.py::Dataset.__getitem__`` / ``__setitem__``), resolved
    by name, in the traced run only."""

    def __init__(self):
        self.spans: List[tuple] = []   # (kind, t0, t1) on time.monotonic()
        self._lock = threading.Lock()
        self._undo = []

    def __enter__(self):
        import jax
        from cluster_tools_tpu.io import containers

        def wrap(cls, attr, kind):
            inner = getattr(cls, attr)

            def timed(this, *a, **kw):
                t0 = time.monotonic()
                with jax.profiler.TraceAnnotation("bench.io." + kind):
                    try:
                        return inner(this, *a, **kw)
                    finally:
                        with self._lock:
                            self.spans.append((kind, t0, time.monotonic()))

            setattr(cls, attr, timed)
            self._undo.append((cls, attr, inner))

        wrap(containers.Dataset, "__getitem__", "read")
        wrap(containers.Dataset, "__setitem__", "write")
        return self

    def __exit__(self, *exc):
        for cls, attr, inner in self._undo:
            setattr(cls, attr, inner)


def device_memory_now() -> dict:
    """``memory_stats()`` of the fullest chip, as JAX reports them now."""
    import jax

    rows = [d.memory_stats() or {} for d in jax.devices()]
    return max(rows, key=lambda r: r.get("bytes_in_use", 0) + r.get("bytes_reserved", 0))


class MemorySampler(threading.Thread):
    """Reads the chip's memory ten times a second while the window runs.

    On this TPU runtime a loaded program's temporaries are *reserved* beside
    the allocator (``bytes_reserved``), not allocated from it, so what a chip
    holds at a moment is ``bytes_in_use + bytes_reserved``.  The runtime
    keeps a peak of each, and the two peaks need not fall together; the
    largest sum that was read at one moment is a reading, their sum only an
    upper bound.  ``peak`` is that largest sum (0 where the backend gives no
    statistics)."""

    def __init__(self, period: float = 0.1):
        super().__init__(daemon=True)
        self.period, self.peak, self.at_peak, self.n = period, 0, {}, 0
        self._done = threading.Event()

    def run(self):
        while not self._done.wait(self.period):
            self.read()

    def read(self):
        row = device_memory_now()
        held = int(row.get("bytes_in_use", 0)) + int(row.get("bytes_reserved", 0))
        self.n += 1
        if held > self.peak:
            self.peak, self.at_peak = held, row

    def close(self) -> "MemorySampler":
        self._done.set()
        self.join()
        self.read()
        return self


# --------------------------------------------------------------------------
# per-layer metrics: one small reader each, found by name
# --------------------------------------------------------------------------


def load_by_file(kind: str, name: str):
    """``<kind>/<name>.py`` as a module (a name may hold dots): a metric's
    reader under ``metrics``, a configuration's comparison under
    ``comparisons``."""
    mod_spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}",
        os.path.join(HERE, kind, name + ".py"))
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    return load_by_file("metrics", metric)


def read_per_layer(spec: dict, traced: dict) -> Dict[str, dict]:
    out = {}
    for m in spec["per_layer"]:
        base = os.path.join(HERE, "metrics", m["name"])
        meta = load_json(base + ".json")
        value = load_reader(m["name"]).read(traced, meta)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------
# a whole run
# --------------------------------------------------------------------------


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, shrink=None, warm_up_only: bool = False):
    """Everything a run does but parse arguments and print.  ``require_chip``
    is False, and ``shrink`` cuts the cell to a size the CPU can hold, only
    in ``test_correct.py``, which drives the rest of a run with the timed
    path broken underneath.  ``warm_up_only`` is the child of a run that
    found no marker: it runs the cell's first job, leaves the marker beside
    the cache entries and returns nothing."""
    spec = load_cell(workload)
    if shrink is not None:
        shrink(spec)
    cell = spec["cell"]

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # what the deployment sets in the program's environment (its documented
    # switches), before the program is imported
    os.environ.update({k: str(v) for k, v in spec["config"].get("env", {}).items()})
    import jax

    from cluster_tools_tpu.parallel.mesh import configure_compile_cache, use_cpu_backend

    if not require_chip:
        use_cpu_backend("benchmark/test_correct.py")
    cache_dir = configure_compile_cache()
    # the harness's own process keeps every program of the cell in the cache:
    # a bound that holds them all, and no program too small or too quick to
    # be written (else it would compile again inside every run's window)
    # (an unbounded cache, JAX's default, is left as it is: entries written
    # without a bound carry no access times, and a bound set later could not
    # be kept over them)
    if 0 <= jax.config.jax_compilation_cache_max_size < CACHE_MAX_BYTES:
        jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    counter = CompileCounter()

    # a chip belongs to one process: the child has to be through before this
    # process first asks JAX for its devices
    marker = os.path.join(cache_dir, f"bench-{workload}-{source_digest(spec)}.json")
    child_warms = require_chip and not warm_up_only
    if child_warms and not is_warm(marker, cache_dir):
        say("no marker for this cell beside the cache: warm-up job in a child process")
        child = subprocess.run(
            [sys.executable, "-m", "benchmark.run", "--workload", workload, "--seed",
             str(seed), "--seconds", "0", "--trace", "0", "--warm-up-only"],
            cwd=ROOT, stdout=sys.stderr)
        if child.returncode != 0 or not is_warm(marker, cache_dir):
            say(f"the warm-up job did not complete (exit code {child.returncode})")
            raise SystemExit(child.returncode or 3)

    devices = jax.devices()
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices))
    say(f"devices: {device}; compile cache at {cache_dir}")
    if require_chip and (device["platform"] != "tpu" or device["count"] < cell["chips"]):
        say(f"this cell needs {cell['chips']} TPU chip(s); found {device}. "
            "There is no CPU fallback.")
        raise SystemExit(2)
    peaks = load_json(HERE, "peaks.json")
    if require_chip and device["kind"] not in peaks:
        say(f"device kind {device['kind']!r} is not in benchmark/peaks.json")
        raise SystemExit(2)
    target = "tpu" if require_chip else "local"

    work = os.path.join(WORK, workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        runner = Runner(spec, seed, target, work)
        volumes = make_volumes(runner)
        if warm_up_only or (not child_warms and not is_warm(marker, cache_dir)):
            warm_up(runner, counter, cache_dir, marker)
        if warm_up_only:
            return None
        return _window(runner, volumes, seconds, trace, device, counter,
                       peaks.get(device["kind"]))
    finally:
        shutil.rmtree(work, ignore_errors=True)


def is_warm(marker: str, cache_dir: str) -> bool:
    """The marker says which cache entries the cell's warm-up job wrote; a
    cache that lost one of them (evicted, another machine) is not warm."""
    if not os.path.exists(marker):
        return False
    return all(os.path.exists(os.path.join(cache_dir, n))
               for n in load_json(marker)["entries"])


def make_volumes(runner: "Runner") -> Dict[int, "object"]:
    """Set-up: the cell's volumes, from the seed, into the input store."""
    from cluster_tools_tpu.utils.volume_utils import file_reader

    from . import data

    t = time.monotonic()
    traffic_p = runner.traffic
    volumes = {}
    store = file_reader(runner.store_in)
    chunks = tuple(runner.config["store"]["chunks"])
    for i in range(int(traffic_p["volumes"])):
        vol = data.membrane_volume(runner.seed, i, traffic_p["volume_shape"],
                                   traffic_p["cells"])
        store.create_dataset(f"vol{i}", shape=vol.shape, chunks=chunks,
                             dtype="float32")[...] = vol
        volumes[i] = vol
    row = device_memory_now()
    say(f"{len(volumes)} volume(s) of {traffic_p['volume_shape']} made and stored "
        f"in {time.monotonic() - t:.1f}s; the chip's memory after the generator: "
        + ", ".join(f"{k} {row.get(k)}" for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_reserved", "peak_bytes_reserved")))
    return volumes


def warm_up(runner: "Runner", counter: CompileCounter, cache_dir: str, marker: str) -> None:
    """The cell's first job, run so that its programs are compiled into the
    persistent cache; the marker lists the entries it wrote."""
    from . import traffic

    before = set(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else set()
    snap = counter.snapshot()
    rec = runner.run(next(traffic.jobs(runner.traffic, runner.seed)), "warmup")
    delta = counter.since(snap)
    say(f"warm-up job: {rec['t1'] - rec['t0']:.1f}s, ok={rec['completed']}, compile "
        f"requests {delta['requests']}, cache hits {delta['hits']}, compile+load "
        f"{delta['compile_or_load_s']:.1f}s")
    shutil.rmtree(runner.store_out, ignore_errors=True)
    if not rec["completed"]:
        return
    new = sorted(n for n in set(os.listdir(cache_dir)) - before if n.endswith("-cache"))
    sizes = [os.path.getsize(os.path.join(cache_dir, n)) for n in new]
    say(f"cache entries written: {len(new)}, {sum(sizes) / 2**20:.1f} MiB "
        f"(largest {max(sizes, default=0) / 2**20:.1f} MiB)")
    with open(marker, "w") as f:
        json.dump({"entries": new, "bytes": sum(sizes)}, f)


def _window(runner, volumes, seconds, trace, device, counter, peak_row) -> dict:
    import jax

    from . import traffic

    spec, seed, work = runner.spec, runner.seed, runner.work
    cell, config = spec["cell"], spec["config"]
    comparison = load_by_file("comparisons", config["comparison"])
    job_iter = traffic.jobs(runner.traffic, seed)

    # -- the window ---------------------------------------------------------
    io_spans = IoSpans() if trace else contextlib.nullcontext()
    trace_dir = os.path.join(work, "profile")
    ctt = None
    if trace:
        from cluster_tools_tpu.runtime import trace as ctt

        ctt.configure(enabled=True, trace_dir=os.path.join(work, "ctt_trace"))
    done: List[dict] = []
    snap = counter.snapshot()
    n_missed = len(counter.missed)
    memory = MemorySampler()
    memory.start()
    with io_spans:
        t_window = time.monotonic()
        setup_s = t_window - T_PROCESS
        while True:
            job = next(job_iter)
            tracing_this = trace and not done
            if tracing_this:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                t_trace0 = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.job"):
                rec = runner.run(job, f"j{job.index}")
            if tracing_this:
                jax.profiler.stop_trace()
                rec["trace_window"] = (t_trace0, time.monotonic())
                # the profiler's stop is not part of any job
                t_window += time.monotonic() - rec["t1"]
            done.append(rec)
            say(f"job {job.index}: {rec['t1'] - rec['t0']:.2f}s, "
                f"{'completed' if rec['completed'] else 'FAILED'}"
                + (f" {rec['error'] or rec['absorbed'][:2]}" if not rec["completed"] else ""))
            if time.monotonic() - t_window >= seconds:
                break
        elapsed = time.monotonic() - t_window
    in_window = counter.since(snap)
    missed_in_window = counter.missed[n_missed:]
    say(f"window: {elapsed:.2f}s, {len(done)} job(s); compile requests "
        f"{in_window['requests']}, persistent-cache hits {in_window['hits']}, "
        f"misses {in_window['misses']}, programs that could not use the cache "
        f"{in_window['uncached']}, reading programs back {in_window['load_s']:.2f}s"
        + (f"; missed: {missed_in_window}" if missed_in_window else ""))
    memory.close()
    device["memory_peak_bytes"] = memory.peak
    row = device_memory_now()
    say(f"device memory peak {memory.peak} bytes: the most held at one moment of the "
        f"window ({memory.n} readings), allocator {memory.at_peak.get('bytes_in_use')} + "
        f"reserved beside it (loaded programs' temporaries) "
        f"{memory.at_peak.get('bytes_reserved')}; the runtime's own peaks: allocator "
        f"{row.get('peak_bytes_in_use')}, reserved {row.get('peak_bytes_reserved')}")

    # -- correct: what the jobs stored, against the reference ---------------
    completed = [r for r in done if r["completed"]]
    t = time.monotonic()
    counts = comparison.check_jobs(cell, config, completed, volumes, seed)
    counts["compiles_in_window"] = in_window["misses"] + in_window["uncached"]
    limits = dict(comparison.LIMITS, compiles_in_window=0)
    checks = {k: {"value": int(v), "limit": limits[k]} for k, v in counts.items()}
    correct = bool(completed) and all(c["value"] <= c["limit"] for c in checks.values())
    say(f"reference comparison of {len(completed)} job(s): {time.monotonic() - t:.1f}s")

    voxels = sum(r["job"].voxels for r in completed)
    values = {"voxels_per_s": voxels / 1e6 / elapsed, "setup_s": setup_s}
    result = dict(correct=correct, attempted=len(done),
                  failed=len(done) - len(completed))
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec["end_to_end"]
        }
    else:
        from . import reduce_trace

        rec = done[0]
        t = time.monotonic()
        reduced = reduce_trace.reduce_dir(trace_dir)
        io = [(a, b) for _, a, b in io_spans.spans]
        in_job = reduce_trace.union(reduce_trace.clip(io, rec["t0"], rec["t1"]))
        traced = dict(
            spec=spec, job=rec, trace=reduced, peaks=peak_row,
            io_spans=io,
            io_seconds=sum(b - a for a, b in in_job),
            runtime_spans=ctt._get().snapshot_events() if ctt else [],
        )
        result["metrics"] = read_per_layer(spec, traced)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduce_trace.breakdown(reduced, traced)
        say(f"trace: covered {reduced.window_s:.2f}s of the {rec['t1'] - rec['t0']:.2f}s "
            f"job (all of it), {reduced.n_device_events} device events, reduced in "
            f"{time.monotonic() - t:.1f}s")
    result["device"] = device
    result["checks"] = checks
    for k, c in checks.items():
        say(f"compared {k} = {c['value']} (limit {c['limit']})")
    say(f"correct = {correct}")
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--warm-up-only", action="store_true",
                   help="what a run that finds no marker starts as its child")
    args = p.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      warm_up_only=args.warm_up_only)
    if result is None:
        return 0
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
