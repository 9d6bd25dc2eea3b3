#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the system starts on the chip.

Drives the main path once, through the entry points a user calls
(``python -m cluster_tools_tpu.cli run ...`` and ``python -m
cluster_tools_tpu.serve``), at BASELINE config 2's geometry (64^3 blocks,
halo 32, connectivity 1, ``dt_max_distance=32``), on data made from
``--seed``, and checks every output against a plain reference (scipy, a
numpy solve, the same task on the CPU backend).  One process per chip: this
parent never imports JAX; every phase is a child, one after the other.

    python chip_smoke.py                one chip (what the driver runs)
    python chip_smoke.py --chips 4      the mesh path only, on four chips
    python chip_smoke.py --rehearse     every phase, tiny, on the CPU backend

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``;
earlier lines say what each phase did.  The seconds they carry are smoke
timings, not benchmark numbers.  Exit code 0 only when every phase passed on
a TPU; ``--rehearse`` checks the control flow and always ends ``"ok": false``.

No fault is injected, so any ``degraded:*`` / quarantine / retry record, any
watershed capacity overflow and any attributed reduce-plane degrade is the
chip refusing something: the smoke fails on each.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".scratch", "chip_smoke")
REPORT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
NATIVE_SO = os.path.join(ROOT, "native", "libct_native.so")
#: the driver allows 1200 s, compilation included
BUDGET_S = 1150.0
THRESHOLD = 0.5
REQUEUE_EXIT_CODE = 114  # runtime/supervision.py: a drained server's rc
#: the fused task's `execution`: "fused" = the one-program monolith, "split" =
#: the four-program chain.  The compile rehearsal (CHANGES.md PR 24) showed
#: both compile and the chain buys no compile time, so the default it is.
FUSED_EXECUTION = "fused"

T0 = time.monotonic()
_CHILDREN: list = []


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


class PhaseFailed(Exception):
    pass


# --------------------------------------------------------------------------
# geometry
# --------------------------------------------------------------------------


def geometry(rehearse: bool) -> dict:
    """BASELINE config 2's block geometry is never cut; only the volume's
    extent is (and, for the rehearsal, everything — it checks control flow)."""
    if rehearse:
        return dict(block=16, halo=8, extent=32, em_shape=[16, 16, 128],
                    n_objects=6, em_objects=6)
    # 512^3 does not fit the 1200 s limit cold: the watershed and fused
    # programs alone take minutes to compile (CHANGES.md PR 24), and a 512^3
    # volume is swept three times (cli + two served requests).  The cut is
    # in the extent only.
    return dict(block=64, halo=32, extent=256, em_shape=[64, 64, 512],
                n_objects=32, em_objects=24)


# --------------------------------------------------------------------------
# children
# --------------------------------------------------------------------------


def child_env(platform: str, n_cpu_devices: int = 1, extra: dict = None,
              reference: bool = False) -> dict:
    """Environment of a child.  ``platform`` is ``"default"`` (whatever JAX
    finds — the chip, where there is one) or ``"cpu"``; ``reference`` marks
    the children that compute what a phase is compared with."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # JAX's own switches: log each compile and each persistent-cache hit
    env["JAX_LOG_COMPILES"] = "1"
    env.setdefault("TPU_LOG_DIR", "disabled")
    if reference:
        # their CPU programs stay out of the persistent cache: it is
        # bounded, and it is the phases' programs that must still be there
        # when the server asks for them
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            env.get("XLA_FLAGS", ""),
        ).strip()
        if n_cpu_devices > 1:
            flags += f" --xla_force_host_platform_device_count={n_cpu_devices}"
        env["XLA_FLAGS"] = flags.strip()
    env.update(extra or {})
    return env


def start_child(name: str, argv: list, env: dict) -> dict:
    log = os.path.join(WORK, "logs", f"{name}.log")
    os.makedirs(os.path.dirname(log), exist_ok=True)
    fh = open(log, "wb")
    proc = subprocess.Popen(
        argv, stdout=fh, stderr=subprocess.STDOUT, env=env, cwd=ROOT,
        start_new_session=True,
    )
    job = dict(name=name, proc=proc, log=log, fh=fh, t0=time.monotonic())
    _CHILDREN.append(job)
    return job


def wait_child(job: dict, timeout: float = None) -> int:
    if timeout is None:
        timeout = max(5.0, BUDGET_S - (time.monotonic() - T0))
    try:
        rc = job["proc"].wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        kill_job(job)
        raise PhaseFailed(
            f"{job['name']}: still running after {timeout:.0f}s — killed "
            f"(log tail: {tail(job['log'])})"
        )
    job["wall"] = time.monotonic() - job["t0"]
    job["fh"].close()
    return rc


def kill_job(job: dict) -> None:
    proc = job["proc"]
    if proc.poll() is None:
        for sig in (signal.SIGTERM, signal.SIGKILL):
            try:
                os.killpg(proc.pid, sig)
            except (ProcessLookupError, PermissionError):
                break
            try:
                proc.wait(timeout=10)
                break
            except subprocess.TimeoutExpired:
                continue
    if not job["fh"].closed:
        job["fh"].close()


def run_child(name: str, argv: list, env: dict) -> dict:
    job = start_child(name, argv, env)
    job["rc"] = wait_child(job)
    return job


def helper_argv(what: str, **args) -> list:
    return [sys.executable, os.path.abspath(__file__), "--child", what,
            json.dumps(args)]


def tail(path: str, n: int = 1500) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


def last_json_line(path: str):
    try:
        with open(path, errors="replace") as f:
            lines = [l for l in f.read().splitlines() if l.startswith("{")]
        return json.loads(lines[-1]) if lines else None
    except (OSError, ValueError):
        return None


# --------------------------------------------------------------------------
# what a phase's own records say
# --------------------------------------------------------------------------

_COMPILE_RE = re.compile(r"Finished XLA compilation of (\S+) in ([0-9.]+) sec")
_HIT_RE = re.compile(r"Persistent compilation cache hit for '([^']+)'")


def jax_log_stats(text: str) -> dict:
    compiles = _COMPILE_RE.findall(text)
    hits = _HIT_RE.findall(text)
    return dict(
        compile_s=round(sum(float(s) for _, s in compiles), 1),
        compiles=len(compiles),
        cache_hits=len(hits),
        programs=sorted({n for n, _ in compiles}),
        slowest=sorted(
            ((round(float(s), 1), n) for n, s in compiles), reverse=True
        )[:3],
    )


def read_text(path: str, offset: int = 0) -> str:
    with open(path, "rb") as f:
        f.seek(offset)
        return f.read().decode(errors="replace")


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir) if not n.startswith("."))
    except OSError:
        return 0


def cache_mib(cache_dir: str) -> float:
    try:
        return round(sum(
            os.path.getsize(os.path.join(cache_dir, n))
            for n in os.listdir(cache_dir)
        ) / 2**20, 1)
    except OSError:
        return 0.0


def absorbed_failures(tmp_folder: str) -> list:
    """Everything in a run's records that means the program absorbed a
    failure.  With no fault injected each one is the chip refusing
    something, so the smoke treats it as a failure of the phase."""
    bad = []
    path = os.path.join(tmp_folder, "failures.json")
    if os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
        for rec in doc.get("records", []):
            bad.append(
                f"failures.json: task={rec.get('task')} "
                f"block={rec.get('block_id')} sites={rec.get('sites')} "
                f"quarantined={rec.get('quarantined')} "
                f"resolution={rec.get('resolution')} "
                f"error={str(rec.get('error'))[-300:]!r}"
            )
    for mf in glob.glob(os.path.join(tmp_folder, "*.success.json")):
        with open(mf) as f:
            doc = json.load(f)
        if doc.get("overflow_blocks"):
            bad.append(
                f"{os.path.basename(mf)}: watershed capacity overflow in "
                f"blocks {doc['overflow_blocks'][:16]}"
            )
        solver = doc.get("solver") or {}
        if solver.get("degraded") or solver.get("degraded_plane"):
            bad.append(f"{os.path.basename(mf)}: solve degraded: {solver}")
    path = os.path.join(tmp_folder, "io_metrics.json")
    if os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
        for task, m in (doc.get("tasks") or {}).items():
            for key in ("host_staged_fallbacks", "unsharded_fallbacks"):
                if m.get(key):
                    bad.append(f"io_metrics.json: {task}: {key}={m[key]}")
    return bad


_DEV_RE = re.compile(r"(executor|mesh)\.devices=\[([^\]]*)\]")
_KERNELS_RE = re.compile(r"kernels=(\{[^}]*\})")


def task_logs(tmp_folder: str) -> str:
    return "\n".join(
        read_text(p) for p in sorted(glob.glob(os.path.join(tmp_folder, "*.log")))
    )


def devices_used(tmp_folder: str) -> list:
    found = []
    for _, body in _DEV_RE.findall(task_logs(tmp_folder)):
        found += [d.strip(" '\"") for d in body.split(",") if d.strip()]
    return found


def check_phase_records(phase: str, tmp_folder: str, platform: str,
                        n_devices: int = None) -> dict:
    bad = absorbed_failures(tmp_folder)
    if bad:
        more = f"\n  ... and {len(bad) - 3} more" if len(bad) > 3 else ""
        raise PhaseFailed(
            f"{phase}: the run absorbed failures although no fault was "
            "injected:\n  " + "\n  ".join(bad[:3]) + more
        )
    devs = devices_used(tmp_folder)
    wrong = sorted({d for d in devs if not d.startswith(platform + ":")})
    if wrong:
        raise PhaseFailed(
            f"{phase}: logs show devices other than {platform}: {wrong}"
        )
    if not devs:
        raise PhaseFailed(f"{phase}: no executor.devices/mesh.devices in the logs")
    if n_devices is not None and len(set(devs)) != n_devices:
        raise PhaseFailed(
            f"{phase}: expected {n_devices} devices, logs show {sorted(set(devs))}"
        )
    logs = task_logs(tmp_folder)
    kernels = sorted(set(_KERNELS_RE.findall(logs)))
    # the tasks' success manifests carry memory_stats() peaks per device
    peaks = []
    for mf in sorted(glob.glob(os.path.join(tmp_folder, "*.success.json"))):
        with open(mf) as f:
            memory = json.load(f).get("device_memory")
        if memory:
            peaks.append(memory)
    return dict(devices=sorted(set(devs)), kernels=kernels, peaks=peaks)


# --------------------------------------------------------------------------
# phases through the real entry points
# --------------------------------------------------------------------------


class Smoke:
    def __init__(self, args):
        self.args = args
        self.rehearse = bool(args.rehearse)
        self.chips = int(args.chips)
        self.seed = int(args.seed)
        self.geo = geometry(self.rehearse)
        self.platform = "cpu" if self.rehearse else "tpu"
        self.target = "local" if self.rehearse else "tpu"
        self.device = None
        self.cache_dir = None
        self.background: list = []
        self.vol = os.path.join(WORK, "vol.zarr")
        self.em = os.path.join(WORK, "em.zarr")
        self.out_cli = os.path.join(WORK, "out_cli.zarr")
        self.summary: dict = {"phases": {}}

    # -- plumbing ---------------------------------------------------------
    def accel_env(self) -> dict:
        """Environment of a child that computes: the chip (JAX's default)
        or, rehearsing, the CPU backend with as many virtual devices."""
        if self.rehearse:
            return child_env("cpu", self.chips)
        return child_env("default")

    def one_device_env(self) -> dict:
        """One device of the same host, for the --chips 4 comparisons:
        placed from outside, through the TPU runtime's own variables."""
        if self.rehearse:
            return child_env("cpu", 1)
        return child_env("default", extra={
            "TPU_VISIBLE_CHIPS": "0", "TPU_VISIBLE_DEVICES": "0",
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1", "TPU_HOST_BOUNDS": "1,1,1",
        })

    def cli_config(self, name: str, params: dict, target: str = None) -> tuple:
        tmp = os.path.join(WORK, f"tmp_{name}")
        cfg_dir = os.path.join(WORK, f"config_{name}")
        os.makedirs(cfg_dir, exist_ok=True)
        with open(os.path.join(cfg_dir, "global.config"), "w") as f:
            json.dump({"block_shape": [self.geo["block"]] * 3}, f)
        path = os.path.join(WORK, f"{name}.json")
        with open(path, "w") as f:
            json.dump({
                "tmp_folder": tmp, "config_dir": cfg_dir, "max_jobs": 8,
                "target": target or self.target, "params": params,
            }, f, indent=1)
        return path, tmp

    def cli_argv(self, workflow: str, cfg_path: str) -> list:
        return [sys.executable, "-m", "cluster_tools_tpu.cli", "run",
                workflow, "--config", cfg_path]

    def run_cli(self, name: str, workflow: str, params: dict, env: dict = None,
                n_devices: int = None) -> dict:
        cfg_path, tmp = self.cli_config(name, params)
        before = cache_entries(self.cache_dir)
        job = run_child(name, self.cli_argv(workflow, cfg_path),
                        env or self.accel_env())
        text = read_text(job["log"])
        if job["rc"] != 0 or "SUCCESS" not in text:
            raise PhaseFailed(
                f"{name}: `cli run {workflow}` rc={job['rc']}\n{tail(job['log'], 3000)}"
            )
        info = check_phase_records(name, tmp, self.platform, n_devices)
        info.update(jax_log_stats(text))
        info.update(
            wall_s=round(job["wall"], 1), tmp_folder=tmp,
            cache_entries_written=cache_entries(self.cache_dir) - before,
        )
        self.report_phase(name, info)
        return info

    def report_phase(self, name: str, info: dict) -> None:
        self.summary["phases"][name] = info
        say(
            f"phase {name}: wall {info.get('wall_s')}s cold, compiling "
            f"{info.get('compile_s')}s in {info.get('compiles')} program(s), "
            f"persistent-cache hits {info.get('cache_hits')} / misses "
            f"{info.get('compiles', 0) - info.get('cache_hits', 0)}, entries "
            f"written {info.get('cache_entries_written')} (cache now "
            f"{cache_mib(self.cache_dir)} MiB) — smoke timings, not "
            "benchmark numbers"
        )
        if info.get("slowest"):
            say(f"  slowest compiles: {info['slowest']}")
        if info.get("devices"):
            say(f"  devices: {info['devices']}; kernels: {info.get('kernels')}")
        if info.get("peaks"):
            say(f"  device peak bytes: {info['peaks'][-1]}")

    def start_check(self, name: str, what: str, **kw) -> None:
        """Comparisons with the plain reference run as CPU children beside
        the next chip phase; all are collected before the verdict."""
        self.background.append(
            start_child(name, helper_argv(what, **kw),
                        child_env("cpu", reference=True))
        )

    def collect_checks(self) -> None:
        failed = []
        for job in self.background:
            rc = wait_child(job)
            doc = last_json_line(job["log"]) or {}
            self.summary["phases"][job["name"]] = doc
            if rc != 0 or not doc.get("ok"):
                failed.append(f"{job['name']}: rc={rc} {doc or tail(job['log'])}")
            else:
                say(f"check {job['name']}: ok {doc.get('detail', '')}")
        self.background = []
        if failed:
            raise PhaseFailed("reference checks failed:\n  " + "\n  ".join(failed))

    # -- phases -----------------------------------------------------------
    def phase_device(self) -> None:
        if os.path.exists(NATIVE_SO):
            # a copied .so is not "built from what git would commit"
            os.remove(NATIVE_SO)
        env = self.accel_env()
        job = run_child("device", helper_argv("device"), env)
        doc = last_json_line(job["log"])
        if job["rc"] != 0 or not doc:
            raise PhaseFailed(
                f"device: rc={job['rc']} — JAX could not start "
                f"(or this is not a checkout)\n{tail(job['log'], 2500)}"
            )
        self.device = {k: doc[k] for k in ("platform", "kind", "count")}
        self.cache_dir = doc["compile_cache_dir"]
        say(
            f"phase device: {doc['platform']} / {doc['kind']} x{doc['count']}, "
            f"jax {doc['jax']}, compile cache at {doc['compile_cache_dir']} "
            f"({cache_entries(self.cache_dir)} entries there now), "
            f"native/libct_native.so rebuilt from ct_native.cpp in this run: "
            f"{doc['native_rebuilt']} ({job['wall']:.1f}s)"
        )
        self.summary["phases"]["device"] = doc
        if not doc["native_rebuilt"] or not os.path.exists(NATIVE_SO):
            raise PhaseFailed("device: native/libct_native.so did not build")
        if doc["platform"] != self.platform:
            raise PhaseFailed(
                f"device: JAX found platform {doc['platform']!r}, this run "
                f"needs {self.platform!r}"
            )
        if doc["count"] != self.chips:
            raise PhaseFailed(
                f"device: {doc['count']} device(s), this run needs {self.chips}"
            )

    def phase_data(self) -> dict:
        g = self.geo
        return start_child("data", helper_argv(
            "data", vol=self.vol, em=self.em, seed=self.seed,
            extent=g["extent"], block=g["block"], n_objects=g["n_objects"],
            em_shape=g["em_shape"], em_objects=g["em_objects"],
            with_em=self.chips == 1,
        ), child_env("cpu", reference=True))

    def finish_data(self, job: dict) -> None:
        rc = wait_child(job)
        doc = last_json_line(job["log"])
        if rc != 0 or not doc:
            raise PhaseFailed(f"data: rc={rc}\n{tail(job['log'], 2500)}")
        say(f"data: {doc} ({job['wall']:.1f}s, seed {self.seed})")
        self.summary["data"] = doc

    def ws_params(self, out_path: str, key: str, **extra) -> dict:
        g = self.geo
        p = dict(
            input_path=self.vol, input_key="boundaries",
            output_path=out_path, output_key=key,
            block_shape=[g["block"]] * 3, halo=[g["halo"]] * 3,
            threshold=THRESHOLD, dt_max_distance=float(g["halo"]),
            connectivity=1,
        )
        p.update(extra)
        return p

    def cc_params(self, out_path: str, key: str) -> dict:
        return dict(
            input_path=self.vol, input_key="boundaries",
            output_path=out_path, output_key=key,
            threshold=THRESHOLD, threshold_mode="less",
            block_shape=[self.geo["block"]] * 3, connectivity=1,
        )

    def fused_params(self, out_path: str, ws_key: str, cc_key: str) -> dict:
        g = self.geo
        return dict(
            input_path=self.vol, input_key="boundaries", output_path=out_path,
            ws_key=ws_key, cc_key=cc_key, threshold=THRESHOLD,
            halo=g["halo"], dt_max_distance=float(g["halo"]),
            block_shape=[g["block"]] * 3, execution=FUSED_EXECUTION,
        )

    def start_ws_reference(self) -> None:
        """The same watershed task on a corner of 2x2x2 blocks, by a child
        on the CPU backend with the portable XLA kernels — and the fill
        machinery the chip resolves to, set through its existing switch."""
        g = self.geo
        corner = 2 * g["block"]
        cfg_path, tmp = self.cli_config("ws_reference", self.ws_params(
            self.out_cli, "ws_reference", impl="xla",
            roi_begin=[0, 0, 0], roi_end=[corner] * 3,
        ), target="local")
        fill = "dense" if self.rehearse else "capacity"
        job = start_child(
            "ws_reference", self.cli_argv("watershed", cfg_path),
            child_env("cpu", extra={"CT_FILL_MODE": fill}, reference=True),
        )
        job["tmp"] = tmp
        self.ws_reference = job

    def finish_ws_reference(self) -> None:
        job = self.ws_reference
        rc = wait_child(job)
        if rc != 0 or "SUCCESS" not in read_text(job["log"]):
            raise PhaseFailed(
                f"ws_reference (cpu, impl=xla): rc={rc}\n{tail(job['log'], 2500)}"
            )
        info = check_phase_records("ws_reference", job["tmp"], "cpu")
        say(f"ws_reference on the CPU backend, beside the chip phases: "
            f"{info['kernels']}")

    def phase_cc(self) -> None:
        self.run_cli("cc", "connected_components",
                     self.cc_params(self.out_cli, "cc"))
        self.start_check("check_cc", "check_labels", vol=self.vol,
                         out=self.out_cli, cc_key="cc")

    def phase_watershed(self) -> None:
        info = self.run_cli("watershed", "watershed",
                            self.ws_params(self.out_cli, "ws"))
        want = "xla" if self.rehearse else "pallas"
        if not any(f"'impl': '{want}'" in k for k in info["kernels"]):
            raise PhaseFailed(
                f"watershed: impl=auto resolved to {info['kernels']}, "
                f"expected the {want} kernels on {self.platform}"
            )

    def phase_fused(self) -> None:
        say(f"fused: execution={FUSED_EXECUTION!r} (the rehearsal showed it "
            "compilable; the split chain compiles no faster)")
        self.run_cli("fused", "fused_segmentation", self.fused_params(
            self.out_cli, "fused_ws", "fused_cc"))
        self.start_check("check_fused", "check_labels", vol=self.vol,
                         out=self.out_cli, ws_key="fused_ws", cc_key="fused_cc")

    def phase_multicut(self) -> None:
        g = self.geo
        out = os.path.join(WORK, "out_mc.zarr")
        say(f"multicut: synthetic-EM volume {g['em_shape']} "
            f"({g['em_objects']} cells, exact ground truth)")
        info = self.run_cli("multicut", "multicut", dict(
            input_path=self.em, input_key="boundaries",
            ws_path=out, ws_key="ws", output_path=out, output_key="seg",
            block_shape=[g["block"]] * 3, halo=[g["halo"]] * 3,
            threshold=THRESHOLD, beta=0.5, n_scales=1,
        ))
        # device RAG extraction must have run: its program compiled.  (The
        # block subproblems are too small for the contraction engine's
        # accelerator branch, which `auto` takes from 65,536 edges on; the
        # check phase below runs that branch on the whole graph.)
        need = ["device_edge_aggregate"]
        missing = [n for n in need
                   if not any(n in p for p in info["programs"])]
        if missing:
            raise PhaseFailed(
                f"multicut: never compiled {missing}; compiled {info['programs']}"
            )
        for mf in sorted(glob.glob(os.path.join(info["tmp_folder"],
                                                "solve_*.success.json"))):
            with open(mf) as f:
                doc = json.load(f)
            solver = doc.get("solver") or {}
            say(f"  {os.path.basename(mf).split('.')[0]}: plane="
                f"{solver.get('reduce_plane', 'single-host solve')} "
                f"sharded={solver.get('sharded')} energy={doc.get('energy')}")
        self.start_check("check_multicut", "check_multicut", em=self.em,
                         out=out, tmp=info["tmp_folder"])

    def phase_serve(self) -> None:
        g = self.geo
        base = os.path.join(WORK, "srv")
        out = os.path.join(WORK, "out_srv.zarr")
        argv = [sys.executable, "-m", "cluster_tools_tpu.serve",
                "--base-dir", base, "--max-workers", "1"]
        if not self.rehearse:
            argv.append("--tpu")
        before = cache_entries(self.cache_dir)
        server = start_child("serve", argv, self.accel_env())
        try:
            port = self.wait_endpoint(server, base)
            url = f"http://127.0.0.1:{port}"

            def request(rid, workflow, params):
                offset = os.path.getsize(server["log"])
                t0 = time.monotonic()
                tmp = os.path.join(WORK, f"req_{rid}")
                http_json(url + "/submit", dict(
                    tenant="smoke", request_id=rid, workflow=workflow,
                    config=dict(
                        tmp_folder=tmp, target=self.target, max_jobs=8,
                        global_config={"block_shape": [g["block"]] * 3},
                        params=params,
                    ),
                ))
                rec = self.poll_request(server, url, rid)
                if rec.get("state") != "done":
                    raise PhaseFailed(f"serve: request {rid} ended {rec}")
                info = check_phase_records(f"serve:{rid}", tmp, self.platform)
                info.update(jax_log_stats(read_text(server["log"], offset)))
                info["wall_s"] = round(time.monotonic() - t0, 1)
                say(f"  request {rid}: done in {info['wall_s']}s, compiling "
                    f"{info['compile_s']}s, persistent-cache hits "
                    f"{info['cache_hits']}, devices {info['devices']}")
                return info

            r_cc = request("cc1", "connected_components",
                           self.cc_params(out, "cc"))
            r_ws1 = request("ws1", "watershed", self.ws_params(out, "ws"))
            # ws1's output is compared now: ws2 is the SAME request under a
            # new request_id (the server's program cache keys on the task's
            # whole config), so it writes the same dataset again
            check = run_child("check_serve_ws1", helper_argv(
                "check_equal", pairs=[[self.out_cli, "ws", out, "ws"]],
            ), child_env("cpu", reference=True))
            doc = last_json_line(check["log"]) or {}
            if check["rc"] != 0 or not doc.get("ok"):
                raise PhaseFailed(f"serve: ws1 output: {doc or tail(check['log'])}")
            say(f"  ws1 output: {doc['detail']}")
            progs1 = http_json(url + "/healthz")["programs"]
            r_ws2 = request("ws2", "watershed", self.ws_params(out, "ws"))
            progs2 = http_json(url + "/healthz")["programs"]
            say(f"  /healthz programs after ws1: {progs1}; after ws2: {progs2}")
            if not (progs2["hits"] > progs1["hits"]
                    and progs2["misses"] == progs1["misses"]):
                raise PhaseFailed(
                    "serve: the repeated watershed request did not ride the "
                    f"server's program cache: {progs1} -> {progs2}"
                )
            if r_ws1["cache_hits"] < 1:
                raise PhaseFailed(
                    "serve: no persistent-cache hit while serving the "
                    "watershed the cli phase had already compiled "
                    f"(compiled here: {r_ws1['slowest']})"
                )
            self.second_member_probe(base)
            os.killpg(server["proc"].pid, signal.SIGTERM)
            rc = wait_child(server, timeout=120)
            if rc != REQUEUE_EXIT_CODE:
                raise PhaseFailed(
                    f"serve: SIGTERM drain exited {rc}, expected "
                    f"{REQUEUE_EXIT_CODE}\n{tail(server['log'])}"
                )
        finally:
            kill_job(server)
        info = jax_log_stats(read_text(server["log"]))
        info.update(
            wall_s=round(server["wall"], 1), requests=3, drain_rc=rc,
            cache_entries_written=cache_entries(self.cache_dir) - before,
            devices=sorted(set(r_cc["devices"] + r_ws1["devices"]
                               + r_ws2["devices"])),
            kernels=r_ws1["kernels"],
        )
        self.report_phase("serve", info)
        self.start_check(
            "check_serve", "check_equal",
            pairs=[[self.out_cli, "cc", out, "cc"],
                   [self.out_cli, "ws", out, "ws"]],
        )

    def wait_endpoint(self, server: dict, base: str) -> int:
        path = os.path.join(base, "server.json")
        while True:
            if server["proc"].poll() is not None:
                raise PhaseFailed(
                    f"serve: exited {server['proc'].returncode} before "
                    f"binding\n{tail(server['log'], 2500)}"
                )
            if os.path.exists(path):
                try:
                    with open(path) as f:
                        return int(json.load(f)["port"])
                except (ValueError, KeyError):
                    pass  # being written
            if time.monotonic() - T0 > BUDGET_S:
                raise PhaseFailed("serve: no endpoint inside the time limit")
            time.sleep(0.2)

    def poll_request(self, server: dict, url: str, rid: str) -> dict:
        while True:
            if server["proc"].poll() is not None:
                raise PhaseFailed(
                    f"serve: died during {rid}\n{tail(server['log'], 2500)}"
                )
            rec = http_json(f"{url}/request/{rid}")
            if rec.get("state") not in ("queued", "running"):
                return rec
            if time.monotonic() - T0 > BUDGET_S:
                raise PhaseFailed(f"serve: {rid} not done inside the time limit")
            time.sleep(0.5)

    def second_member_probe(self, base: str) -> None:
        """A chip belongs to one process: a second ``serve --tpu`` beside the
        live one must exit non-zero at start, with the reason (what a second
        fleet member on one chip would do).  Rehearsing, there is no TPU at
        all, which takes the same exit."""
        job = start_child("second_member", [
            sys.executable, "-m", "cluster_tools_tpu.serve",
            "--base-dir", base + "_second", "--tpu",
        ], self.accel_env())
        try:
            rc = wait_child(job, timeout=120)
        finally:
            kill_job(job)
        text = read_text(job["log"])
        if rc == 0 or "cannot open the accelerator" not in text:
            raise PhaseFailed(
                f"serve: a second --tpu member did not fail fast (rc={rc})\n"
                f"{tail(job['log'])}"
            )
        reason = [l for l in text.splitlines()
                  if "cannot open the accelerator" in l][-1]
        say(f"  second --tpu member: rc={rc} in {job['wall']:.1f}s — {reason[:300]}")

    # -- the two runs -----------------------------------------------------
    def run_one_chip(self) -> None:
        data = self.phase_data()
        self.phase_device()
        self.finish_data(data)
        self.start_ws_reference()
        self.phase_cc()
        self.phase_watershed()
        self.phase_fused()
        self.phase_multicut()
        self.phase_serve()
        self.finish_ws_reference()
        g = self.geo
        self.start_check(
            "check_watershed", "check_labels", vol=self.vol, out=self.out_cli,
            ws_key="ws", ref_key="ws_reference", corner=2 * g["block"],
        )
        self.collect_checks()

    def run_four_chips(self) -> None:
        """Only what exists across chips, and what it is compared with: the
        blockwise sweep sharded over the devices and the fused step with
        sp=<chips>, each against the same program on one device."""
        data = self.phase_data()
        self.phase_device()
        self.finish_data(data)
        n = self.chips
        one_env = self.one_device_env()
        probe = run_child("one_device_probe", helper_argv("device"), one_env)
        doc = last_json_line(probe["log"]) or {}
        say(f"one-device child sees: {doc.get('platform')} x{doc.get('count')}")
        if probe["rc"] != 0 or doc.get("count") != 1:
            raise PhaseFailed(
                "could not give a child exactly one device of this host\n"
                + tail(probe["log"], 2500)
            )
        # the one-device runs come first: their programs are the one-chip
        # smoke's, so where the persistent cache still holds those they cost
        # no compile, and reading them first keeps them from being evicted
        # by the mesh programs before they were used (the cache is bounded)
        out_one = os.path.join(WORK, "out_one.zarr")
        self.run_cli("watershed_one", "watershed",
                     self.ws_params(out_one, "ws"), env=one_env, n_devices=1)
        self.run_cli("fused_one", "fused_segmentation",
                     self.fused_params(out_one, "fused_ws", "fused_cc"),
                     env=one_env, n_devices=1)
        mesh = self.run_cli("watershed_mesh", "watershed",
                            self.ws_params(self.out_cli, "ws"), n_devices=n)
        self.require_all_devices_used("watershed_mesh", mesh, n)
        self.start_check("check_watershed_mesh", "check_equal",
                         pairs=[[self.out_cli, "ws", out_one, "ws"]],
                         bijection=True)
        mesh = self.run_cli(
            "fused_mesh", "fused_segmentation",
            self.fused_params(self.out_cli, "fused_ws", "fused_cc"),
            n_devices=n)
        self.require_all_devices_used("fused_mesh", mesh, n)
        # the merged components are one partition whatever the mesh; the
        # watershed fragments are per shard (cut at the slab faces), so the
        # mesh's are held to the two invariants and to scipy, not to sp=1
        self.start_check(
            "check_fused_mesh", "check_equal",
            pairs=[[self.out_cli, "fused_cc", out_one, "fused_cc"]],
            bijection=True)
        self.start_check("check_fused", "check_labels", vol=self.vol,
                         out=self.out_cli, ws_key="fused_ws", cc_key="fused_cc")
        self.collect_checks()

    def require_all_devices_used(self, name: str, info: dict, n: int) -> None:
        for mf in glob.glob(os.path.join(info["tmp_folder"],
                                         "fused_segmentation.*.success.json")):
            with open(mf) as f:
                mesh = json.load(f).get("mesh")
            say(f"  {name}: the step ran on mesh {mesh!r} (halo ppermute + "
                "union-find all_gather across the slabs)")
            if mesh != f"sp={n}":
                raise PhaseFailed(f"{name}: mesh {mesh!r}, expected 'sp={n}'")
        peaks = info["peaks"][-1] if info["peaks"] else {}
        say(f"  {name}: per-device memory_stats() peak bytes: {peaks}")
        if self.rehearse:
            return  # the CPU backend reports no memory_stats
        if len(peaks) != n or not all(
                v.get("peak_bytes_in_use", 0) > 0 for v in peaks.values()):
            raise PhaseFailed(
                f"{name}: expected a non-zero peak on all {n} devices, got {peaks}"
            )


def http_json(url: str, payload: dict = None, timeout: float = 60.0) -> dict:
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
        method="GET" if payload is None else "POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        raise PhaseFailed(f"http {e.code} from {url}: {e.read().decode()[:500]}")


# --------------------------------------------------------------------------
# helper children (these may import numpy, scipy and the package; the
# comparisons run on the CPU backend and never touch the chip)
# --------------------------------------------------------------------------


def child_device(_: dict) -> dict:
    t_start = time.time()
    import jax

    from cluster_tools_tpu import native
    from cluster_tools_tpu.parallel.mesh import configure_compile_cache

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    rebuilt = bool(native.available()) and os.path.exists(NATIVE_SO) and (
        os.path.getmtime(NATIVE_SO) >= t_start - 1.0
    )
    return dict(
        ok=True, platform=devices[0].platform, kind=devices[0].device_kind,
        count=len(devices), jax=jax.__version__,
        default_backend=jax.default_backend(),
        compile_cache_dir=cache_dir, native_rebuilt=rebuilt,
    )


def _reader(path: str):
    from cluster_tools_tpu.utils.volume_utils import file_reader

    return file_reader(path)


def child_data(a: dict) -> dict:
    from cluster_tools_tpu.utils.synthetic import synthetic_em_volume

    chunks = (a["block"],) * 3
    # noise=0: the generator clips its noise at 0, which leaves a sixth of
    # the voxels on an exact plateau — no boundary predictor's output looks
    # like that, and ties there are broken differently by the two fill
    # machineries (ROADMAP D10)
    bnd, _, _ = synthetic_em_volume(
        shape=(a["extent"],) * 3, n_objects=a["n_objects"],
        sampling=(1.0, 1.0, 1.0), boundary_width=2.0, noise=0.0, smooth=0.7,
        with_mask=False, seed=a["seed"],
    )
    f = _reader(a["vol"])
    f.create_dataset("boundaries", shape=bnd.shape, chunks=chunks,
                     dtype="float32")[...] = bnd
    out = dict(ok=True, volume=list(bnd.shape),
               foreground=round(float((bnd < THRESHOLD).mean()), 4))
    if a["with_em"]:
        bnd, gt, _ = synthetic_em_volume(
            shape=tuple(a["em_shape"]), n_objects=a["em_objects"],
            sampling=(1.0, 1.0, 1.0), boundary_width=2.0, noise=0.0,
            smooth=0.7, with_mask=False, seed=a["seed"] + 1,
        )
        f = _reader(a["em"])
        f.create_dataset("boundaries", shape=bnd.shape, chunks=chunks,
                         dtype="float32")[...] = bnd
        f.create_dataset("gt", shape=gt.shape, chunks=chunks,
                         dtype="uint64")[...] = gt
        out["em_volume"] = list(bnd.shape)
    return out


def _pairs(a, b):
    """Distinct (a, b) label pairs over the voxels where both are given."""
    import numpy as np

    a = np.asarray(a).ravel().astype(np.uint64)
    b = np.asarray(b).ravel().astype(np.uint64)
    ua, ia = np.unique(a, return_inverse=True)
    ub, ib = np.unique(b, return_inverse=True)
    code = np.unique(ia.astype(np.int64) * len(ub) + ib)
    return ua[code // len(ub)], ub[code % len(ub)]


def _bijection(got, want) -> str:
    """'' when the two labelings are the same partition (label bijection,
    background identical) — the repo's own oracle (tests/helpers.py)."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return f"shapes differ: {got.shape} vs {want.shape}"
    if not np.array_equal(got == 0, want == 0):
        return f"background differs in {int(((got == 0) != (want == 0)).sum())} voxels"
    fg = got != 0
    pa, pb = _pairs(got[fg], want[fg])
    if len(np.unique(pa)) != len(pa) or len(np.unique(pb)) != len(pb):
        return (f"not a bijection: {len(pa)} label pairs over "
                f"{len(np.unique(pa))} / {len(np.unique(pb))} labels")
    return ""


def _foreground_components(vol_path: str):
    import numpy as np
    import scipy.ndimage as ndi

    bnd = np.asarray(_reader(vol_path)["boundaries"][...])
    fg = bnd < THRESHOLD
    comp, n = ndi.label(fg, structure=ndi.generate_binary_structure(3, 1))
    return fg, comp, n


def _ws_invariants(ws, fg, comp) -> str:
    """Every foreground voxel labelled; every fragment inside one
    scipy.ndimage.label component of the foreground."""
    import numpy as np

    unlabelled = int((ws[fg] == 0).sum())
    if unlabelled:
        return f"{unlabelled} foreground voxels unlabelled"
    frag, _ = _pairs(ws[fg], comp[fg])
    if len(np.unique(frag)) != len(frag):
        return (f"{len(frag) - len(np.unique(frag))} fragment(s) span more "
                "than one foreground component")
    return ""


def child_check_labels(a: dict) -> dict:
    """A phase's label outputs against scipy: ``cc_key`` must be scipy's
    partition of the foreground, ``ws_key`` must pass the two watershed
    invariants and, where ``ref_key`` is given, equal the CPU/XLA run on
    the ``corner``^3 first voxels up to a label bijection."""
    import numpy as np

    fg, comp, n = _foreground_components(a["vol"])
    f = _reader(a["out"])
    notes = []
    if a.get("cc_key"):
        err = _bijection(np.asarray(f[a["cc_key"]][...]), comp)
        if err:
            return dict(ok=False, detail="cc: " + err)
        notes.append(f"cc partition equals scipy's ({n} components)")
    if a.get("ws_key"):
        ws = np.asarray(f[a["ws_key"]][...])
        err = _ws_invariants(ws, fg, comp)
        if err:
            return dict(ok=False, detail="ws: " + err)
        notes.append(f"{len(np.unique(ws)) - 1} watershed fragments, all "
                     "foreground labelled, each inside one component")
        if a.get("ref_key"):
            c = a["corner"]
            corner = ws[:c, :c, :c]
            ref = np.asarray(f[a["ref_key"]][:c, :c, :c])
            err = _bijection(corner, ref)
            if err:
                return dict(ok=False, detail=f"ws {c}^3 corner vs CPU/XLA: {err}")
            notes.append(
                f"{c}^3 corner equals the CPU/XLA run up to bijection "
                f"(bit-identical: {bool(np.array_equal(corner, ref))})")
    return dict(ok=True, detail="; ".join(notes))


def child_check_equal(a: dict) -> dict:
    import numpy as np

    notes = []
    for p1, k1, p2, k2 in a["pairs"]:
        x = np.asarray(_reader(p1)[k1][...])
        y = np.asarray(_reader(p2)[k2][...])
        same = bool(np.array_equal(x, y))
        if a.get("bijection"):
            err = _bijection(x, y)
            if err:
                return dict(ok=False, detail=f"{k1} vs {k2}: {err}")
            notes.append(f"{k1}: same partition (bit-identical: {same})")
        elif not same:
            return dict(ok=False, detail=f"{k1} vs {k2}: "
                        f"{int((x != y).sum())} voxels differ")
        else:
            notes.append(f"{k2} == {k1} bit for bit")
    return dict(ok=True, detail="; ".join(notes))


def child_check_multicut(a: dict) -> dict:
    """VI / adapted-RAND against the exact ground truth within the bounds of
    tests/test_synthetic_em.py, and the multicut energy against a
    single-host numpy solve of the same problem within the 2% the repo's
    solver tests allow (tests/test_contraction.py)."""
    import numpy as np

    from cluster_tools_tpu.ops import multicut as mc
    from cluster_tools_tpu.ops.contraction import gaec_parallel
    from cluster_tools_tpu.tasks.costs import costs_path
    from cluster_tools_tpu.tasks.evaluation import contingency_metrics
    from cluster_tools_tpu.tasks.graph import load_global_graph

    seg = np.asarray(_reader(a["out"])["seg"][...])
    ws = np.asarray(_reader(a["out"])["ws"][...])
    gt = np.asarray(_reader(a["em"])["gt"][...])
    ok = (seg > 0) & (gt > 0)
    s, g = seg[ok], gt[ok]
    us, si = np.unique(s, return_inverse=True)
    ug, gi = np.unique(g, return_inverse=True)
    code, counts = np.unique(si.astype(np.int64) * len(ug) + gi,
                             return_counts=True)
    m = contingency_metrics(
        np.stack([code // len(ug), code % len(ug)], axis=1), counts
    )
    vi = m["vi_split"] + m["vi_merge"]
    are = m["adapted_rand_error"]
    detail = (f"{len(np.unique(ws)) - 1} fragments -> {len(us)} segments for "
              f"{len(ug)} cells: VI {vi:.3f} (<1.0), adapted RAND error "
              f"{are:.3f} (<0.15)")
    if not (vi < 1.0 and are < 0.15):
        return dict(ok=False, detail=detail)

    _, _, edges, _ = load_global_graph(a["tmp"])
    edges = edges.astype(np.int64)
    costs = np.load(costs_path(a["tmp"])).astype(np.float64)
    with open(glob.glob(os.path.join(a["tmp"], "solve_global.*.success.json"))[0]) as f:
        e_run = float(json.load(f)["energy"])
    n = int(edges.max()) + 1
    e_ref = mc.multicut_energy(
        edges, costs, gaec_parallel(n, edges, costs, impl="numpy")
    )
    # the contraction engine's accelerator branch, asked for by name (one
    # device program over the whole graph), against the same numpy solve
    e_dev = mc.multicut_energy(
        edges, costs, gaec_parallel(n, edges, costs, impl="jax")
    )
    detail += (f"; energy {e_run:.3f}, device contraction {e_dev:.3f} vs "
               f"single-host numpy solve {e_ref:.3f} over {len(edges)} edges "
               "(each within 2%)")
    bar = e_ref + 0.02 * abs(e_ref)
    return dict(ok=e_run <= bar and e_dev <= bar, detail=detail)


CHILDREN = {
    "device": child_device, "data": child_data,
    "check_labels": child_check_labels, "check_equal": child_check_equal,
    "check_multicut": child_check_multicut,
}


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the mesh path and its one-device comparisons")
    p.add_argument("--seed", type=int, default=0, help="seed of the data")
    p.add_argument("--rehearse", action="store_true",
                   help="every phase, tiny, on the CPU backend; never a pass")
    p.add_argument("--child", nargs=2, metavar=("WHAT", "JSON"),
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.child:
        what, blob = args.child
        sys.path.insert(0, ROOT)
        print(json.dumps(CHILDREN[what](json.loads(blob))), flush=True)
        return 0

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    smoke = Smoke(args)
    g = smoke.geo
    say(f"chips={smoke.chips} seed={smoke.seed} rehearse={smoke.rehearse}; "
        f"volume {g['extent']}^3 in {g['block']}^3 blocks, halo {g['halo']} "
        f"(outer {g['block'] + 2 * g['halo']}^3), connectivity 1, "
        f"dt_max_distance {g['halo']}")
    if not smoke.rehearse:
        say("cut: the volume is 256^3, not 512^3 — a cold-cache 512^3 run does "
            "not fit 1200 s; block geometry and phases are uncut")
    ok = False
    try:
        if smoke.chips == 1:
            smoke.run_one_chip()
        else:
            smoke.run_four_chips()
        if smoke.rehearse:
            say("rehearsal: all phases passed")
        else:
            ok = True
    except PhaseFailed as e:
        say(f"FAILED — {e}")
    except KeyboardInterrupt:
        say("FAILED — interrupted")
    finally:
        for job in _CHILDREN:
            kill_job(job)
        smoke.summary.update(ok=ok, device=smoke.device,
                             wall_s=round(time.monotonic() - T0, 1))
        try:
            os.makedirs(REPORT, exist_ok=True)
            with open(os.path.join(REPORT, "summary.json"), "w") as f:
                json.dump(smoke.summary, f, indent=1, default=str)
            for job in _CHILDREN:
                with open(os.path.join(REPORT, job["name"] + ".log.tail"), "w") as f:
                    f.write(tail(job["log"], 20000))
        except OSError as e:
            say(f"could not write the report under {REPORT}: {e}")
    say(f"total {time.monotonic() - T0:.1f}s of the 1200 s allowed")
    print(json.dumps({"ok": ok, "device": smoke.device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
