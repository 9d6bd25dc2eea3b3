"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): the reference's
``target='local'`` doubled as the fake cluster backend; here the fake mesh is
JAX's forced host-platform device count, so multi-device sharding/collective
code paths are exercised on CPU without TPU hardware.
"""

import os

# force CPU even on a machine with an accelerator: tests run on the virtual
# CPU mesh only (SURVEY.md §4); the chip is reached through chip_smoke.py
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# the config write wins over anything that set jax_platforms before us
jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run"
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection chaos tests (run via `make chaos`; also "
        "marked slow so tier-1 skips them)",
    )


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def inject():
    """Install a fault-injection config for the duration of one test and
    restore the (disabled) env-driven injector afterwards."""
    from cluster_tools_tpu.runtime import faults

    yield faults.configure
    faults.reset()


def _child_serve_pids():
    """Pids of live ``cluster_tools_tpu.serve`` processes whose parent is
    THIS test process — the leak signature: a serve-spawning test that
    raised before its ``finally`` reap."""
    me = os.getpid()
    out = []
    try:
        proc_entries = os.listdir("/proc")
    except OSError:
        return out  # no /proc (non-Linux host): nothing to reap
    for pid in proc_entries:
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().decode(errors="replace").replace("\x00", " ")
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
        except OSError:
            continue
        if "cluster_tools_tpu.serve" not in cmd:
            continue
        # ppid is field 4, after the parenthesized (and possibly
        # space-containing) comm field
        try:
            ppid = int(stat.rsplit(")", 1)[1].split()[1])
        except (IndexError, ValueError):
            continue
        if ppid == me:
            out.append(int(pid))
    return out


@pytest.fixture(autouse=True)
def _reap_leaked_servers():
    """Backstop for leaked resident servers: any ``serve`` subprocess this
    test spawned and did not reap is SIGKILLed after the test.  A stray
    server burns CPU for the rest of the suite — past tier-1 timeouts with
    ZERO failures traced to exactly this — so the guard is unconditional
    and loud."""
    import signal
    import sys
    import time

    yield
    leaked = _child_serve_pids()
    for pid in leaked:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            continue
    for pid in leaked:
        # reap the zombie so later /proc scans (and the chaos suite's
        # stray-server asserts) don't count a corpse as a live server
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                done, _ = os.waitpid(pid, os.WNOHANG)
            except ChildProcessError:
                break
            if done:
                break
            time.sleep(0.05)
    if leaked:
        print(
            f"\n[conftest] reaped {len(leaked)} leaked serve process(es): "
            f"{leaked}", file=sys.stderr,
        )
