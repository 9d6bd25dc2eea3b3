"""Multi-host (DCN) execution: ``jax.distributed`` wiring + process launcher.

The reference scaled past one machine by submitting slurm/LSF array jobs that
only ever talked through the shared filesystem (SURVEY.md §2d).  The
TPU-native equivalent is a **multi-process JAX program**: every host runs the
same SPMD program, ``jax.distributed.initialize`` wires the processes into
one runtime over DCN, and the global ``Mesh`` simply spans all hosts'
devices — collectives ride ICI within a slice and DCN across hosts, with no
code change in the ops (the same ``shard_map`` programs run unmodified).

Three pieces live here:

- :func:`initialize` — ``jax.distributed.initialize`` wrapper that can pin
  the CPU platform first (the local fake-pod workers must not each try to
  open the one chip, see ``tests/conftest.py``),
- :func:`pod_mesh` — a mesh over **all** processes' devices (the multi-host
  form of :func:`~cluster_tools_tpu.parallel.mesh.make_mesh`),
- :func:`launch_workers` / :func:`worker_main` — a subprocess launcher that
  runs an N-process CPU pod on one machine, used by the multi-process test
  (the CI stand-in for a real v5p pod, mirroring how the reference's
  ``target='local'`` stood in for slurm, SURVEY.md §4) and by
  ``__graft_entry__.dryrun_multiprocess``.
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

_ENV_COORD = "CT_MP_COORDINATOR"
_ENV_NPROC = "CT_MP_NUM_PROCESSES"
_ENV_PID = "CT_MP_PROCESS_ID"
_ENV_TARGET = "CT_MP_TARGET"


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    platform: Optional[str] = None,
) -> None:
    """Join this process into the distributed JAX runtime.

    On a real pod (GKE/TPU VM) all arguments are discovered from the
    environment and may be omitted.  ``platform='cpu'`` pins the CPU backend
    *before* initialization — required for the local fake-pod tests, where
    every worker would otherwise try to open the same accelerator.
    """
    import jax

    if platform is not None:
        os.environ["JAX_PLATFORMS"] = platform
        jax.config.update("jax_platforms", platform)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def pod_mesh(
    axis_names: Sequence[str] = ("dp", "sp"),
    grid: Optional[Sequence[int]] = None,
):
    """Mesh spanning every device of every process in the distributed job.

    Identical in shape-semantics to :func:`make_mesh`, but always over the
    *global* device list — after :func:`initialize`, ``jax.devices()``
    contains all hosts' devices and the returned mesh crosses DCN.
    Collective layout: keep the ``sp`` (spatial/halo) axis within a host
    where possible; ``jax.devices()`` orders devices process-major, so the
    default factoring puts the fastest-varying (last) mesh axis across
    devices of the same process.
    """
    import jax

    from .mesh import make_mesh

    return make_mesh(
        len(jax.devices()), axis_names=axis_names, grid=grid, devices=jax.devices()
    )


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def collectives_supported(deadline_s: float = 30.0) -> Tuple[bool, str]:
    """Probe whether this runtime can execute a cross-process collective.

    The collective reduce plane must know *before* committing to device
    hops: old jaxlib CPU backends accept ``jax.distributed.initialize``
    but abort the first multi-process computation with "Multiprocess
    computations aren't implemented on the CPU backend" (the env the
    test_multihost skips document).  The probe runs one tiny jitted
    ``psum`` over a 1-D pod mesh — the exact op class the reduce plane
    dispatches — with ``deadline_s`` of patience (a deadline, per ctlint
    CT015: a wedged probe must degrade, not hang the solve).  Returns
    ``(supported, reason)``; single-process runtimes are trivially
    supported (in-process collectives over the local mesh always work).

    Deterministic across the worker group: every process probes the same
    op on the same backend, so all workers pick the same reduce plane.
    """
    import jax

    if jax.process_count() <= 1:
        return True, "single-process runtime"
    import threading

    import numpy as np

    out: Dict[str, object] = {}

    def _probe():
        try:
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            from .mesh import SIBLING_AXIS, sibling_mesh

            mesh = sibling_mesh()
            sharding = NamedSharding(mesh, P(SIBLING_AXIS))
            n = int(mesh.devices.size)
            x = jax.make_array_from_callback(
                (n,), sharding,
                lambda idx: jnp.ones(np.zeros(n)[idx].shape, jnp.float32),
            )
            total = jax.jit(
                lambda a: a.sum(),
                out_shardings=NamedSharding(mesh, P()),
            )(x)
            ok = float(np.asarray(total)) == float(n)
            out["result"] = (ok, "ok" if ok else "probe sum mismatch")
        except Exception as e:  # the documented old-jaxlib abort lands here
            out["result"] = (False, f"{type(e).__name__}: {e}"[:200])

    t = threading.Thread(target=_probe, daemon=True)
    t.start()
    t.join(timeout=max(1.0, float(deadline_s)))
    if t.is_alive():
        return False, f"collective probe exceeded {deadline_s:g}s deadline"
    return out.get("result", (False, "probe thread died"))


def launch_workers(
    num_processes: int,
    target: str,
    devices_per_process: int = 1,
    timeout: float = 600.0,
    extra_env: Optional[Dict[str, str]] = None,
) -> List[Tuple[int, str, str]]:
    """Run ``target`` (``"module:function"``) in an N-process local CPU pod.

    Spawns ``num_processes`` Python subprocesses, each pinned to the CPU
    platform with ``devices_per_process`` virtual devices, joined through a
    ``jax.distributed`` coordinator on a free localhost port.  The target
    function runs in every process after initialization (classic SPMD).

    Returns ``[(returncode, stdout, stderr), ...]`` per process; on timeout
    every worker's process group is killed and a ``TimeoutError`` carrying
    the partial per-worker output is raised (:func:`collect_workers`).
    This is the DCN analogue of the reference's LocalTask fake-cluster:
    real multi-process collectives, one machine.
    """
    coord = f"127.0.0.1:{free_port()}"
    # workers must be able to import this package regardless of their cwd
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    procs = []
    for pid in range(num_processes):
        env = dict(os.environ)
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        env.update(
            {
                _ENV_COORD: coord,
                _ENV_NPROC: str(num_processes),
                _ENV_PID: str(pid),
                _ENV_TARGET: target,
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": (
                    env.get("XLA_FLAGS", "")
                    + f" --xla_force_host_platform_device_count={devices_per_process}"
                ).strip(),
            }
        )
        if extra_env:
            env.update(extra_env)
        procs.append(
            subprocess.Popen(
                [
                    sys.executable,
                    "-c",
                    "from cluster_tools_tpu.parallel.multihost import worker_main; "
                    "worker_main()",
                ],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                start_new_session=True,
            )
        )
    return collect_workers(procs, timeout)


#: grace between SIGTERM and SIGKILL when tearing down timed-out workers:
#: long enough to flush logs/heartbeats, short enough not to stall teardown
TERM_GRACE_S = 5.0


def _signal_process_group(p: subprocess.Popen, sig: int) -> None:
    """Deliver ``sig`` to the worker's whole process group (workers are
    session leaders via ``start_new_session=True``, so pgid == pid) —
    signalling only the leader would orphan grandchildren as zombies."""
    try:
        os.killpg(p.pid, sig)
    except (ProcessLookupError, PermissionError, OSError):
        try:
            p.send_signal(sig)
        except OSError:
            pass


def _kill_process_group(p: subprocess.Popen) -> None:
    _signal_process_group(p, signal.SIGKILL)


def _terminate_process_groups(
    procs: List[subprocess.Popen], grace_s: float = TERM_GRACE_S
) -> None:
    """SIGTERM -> grace -> SIGKILL escalation for every live worker group:
    workers get ``grace_s`` (collectively, not per worker) to flush logs
    and heartbeats — a drain-aware worker exits cleanly here — before the
    groups are killed hard.  The final SIGKILL goes to EVERY group, even
    ones whose leader already exited: a grandchild that survived the
    SIGTERM would otherwise keep the output pipes open forever (the
    zombie-with-no-logs failure the escalation must not reintroduce)."""
    live = [p for p in procs if p.poll() is None]
    for p in live:
        _signal_process_group(p, signal.SIGTERM)
    deadline = time.monotonic() + max(0.0, grace_s)
    while time.monotonic() < deadline:
        if all(p.poll() is not None for p in live):
            break
        time.sleep(0.05)
    for p in live:
        _kill_process_group(p)


def collect_workers(
    procs: List[subprocess.Popen], timeout: float,
    term_grace_s: float = TERM_GRACE_S,
) -> List[Tuple[int, str, str]]:
    """Wait for every worker, returning ``(returncode, stdout, stderr)``
    per process.  On timeout, every worker's *process group* is terminated
    with a SIGTERM -> ``term_grace_s`` -> SIGKILL escalation (workers get a
    chance to flush logs and heartbeats; no zombie grandchildren keep the
    pipes open) and whatever partial stdout/stderr the workers produced is
    collected and surfaced in the raised ``TimeoutError`` — a hung pod must
    leave its logs behind, not vanish into a bare ``TimeoutExpired``."""
    results = []
    try:
        for i, p in enumerate(procs):
            try:
                out, err = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                _terminate_process_groups(procs, term_grace_s)
                tails = []
                for j, q in enumerate(procs):
                    try:
                        qo, qe = q.communicate(timeout=10.0)
                    except Exception:
                        qo, qe = "", ""
                    tails.append(
                        f"-- worker {j} (rc={q.returncode}) --\n"
                        f"stdout tail:\n{(qo or '')[-800:]}\n"
                        f"stderr tail:\n{(qe or '')[-800:]}"
                    )
                raise TimeoutError(
                    f"multihost worker {i} exceeded timeout={timeout:g}s; "
                    f"killed all {len(procs)} worker process group(s).  "
                    "Partial output:\n" + "\n".join(tails)
                )
            results.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                _kill_process_group(p)
    return results


def worker_main() -> None:
    """Entry point of a :func:`launch_workers` subprocess.

    Reads the coordinator/process config from the environment, pins the CPU
    platform (beating the sitecustomize's own config write), joins the
    distributed runtime, and calls the target function.
    """
    import importlib

    coord = os.environ[_ENV_COORD]
    nproc = int(os.environ[_ENV_NPROC])
    pid = int(os.environ[_ENV_PID])
    target = os.environ[_ENV_TARGET]

    initialize(
        coordinator_address=coord,
        num_processes=nproc,
        process_id=pid,
        platform="cpu",
    )
    mod_name, fn_name = target.split(":")
    fn = getattr(importlib.import_module(mod_name), fn_name)
    # worker-lifetime span on the unified trace timeline (docs/
    # OBSERVABILITY.md): active only when the launcher exported
    # CTT_TRACE=<dir>; the flush is best-effort (targets that flush
    # themselves — the reduce-tree worker — just rewrite the same shard)
    from ..runtime import trace as trace_mod

    try:
        with trace_mod.span("worker.main", worker=pid, target=target):
            fn()
    finally:
        # flush on the failure path too — the shard of the worker that
        # DIED is the one the post-mortem timeline needs most
        try:
            trace_mod.flush()
        except Exception:
            pass


def cc_pod_demo() -> None:
    """SPMD demo/test body: distributed CC + exact EDT across process cuts.

    Every process holds a z-slab of one volume; connected components are
    merged across the process (DCN) cuts by the same
    :func:`~cluster_tools_tpu.parallel.distributed_ccl.
    distributed_connected_components` program that runs single-host — only
    the mesh spans further.  The mesh-exact EDT
    (:mod:`~cluster_tools_tpu.parallel.distributed_edt`) then proves the
    all-to-all reshard rides DCN too.  Each process validates both results
    against scipy oracles and prints ``CC_POD_OK``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from scipy import ndimage

    from .distributed_ccl import distributed_connected_components

    mesh = pod_mesh(axis_names=("sp",))
    sp = int(mesh.devices.size)
    pid = jax.process_index()

    # deterministic volume, generated identically in every process
    rng = np.random.default_rng(7)
    mask_np = rng.random((sp * 8, 24, 24)) > 0.35  # dense: components span cuts
    sharding = NamedSharding(mesh, P("sp"))
    mask = jax.make_array_from_callback(
        mask_np.shape, sharding, lambda idx: jnp.asarray(mask_np[idx])
    )

    labels = distributed_connected_components(mask, mesh, sp_axis="sp")
    # replicate so every process can fetch the full result
    replicated = jax.jit(
        lambda x: x, out_shardings=NamedSharding(mesh, P(None))
    )(labels)
    ours = np.asarray(replicated)

    ref, nref = ndimage.label(mask_np)
    assert (ours > 0).sum() == (ref > 0).sum()
    fwd: dict = {}
    for o, r in zip(ours.ravel().tolist(), ref.ravel().tolist()):
        if o > 0:
            assert fwd.setdefault(o, r) == r, "label split across components"
    assert len(fwd) == nref, (len(fwd), nref)
    # prove the merge crossed a process boundary: some component must span
    # the cut between the first and second process's slabs
    slab = mask_np.shape[0] // sp
    cut_lo, cut_hi = ours[slab - 1], ours[slab]
    spans = set(cut_lo[cut_lo > 0].ravel()) & set(cut_hi[cut_hi > 0].ravel())
    assert spans, "no component spans the process-boundary cut"

    # the all-to-all reshard rides DCN too: the mesh-exact EDT must match
    # scipy across every process cut (x extent divisible by sp for the flip)
    from .distributed_edt import distributed_distance_transform

    emask_np = rng.random((sp * 4, 12, 8 * sp)) > 0.05
    emask_np[0, 0, 0] = False
    emask = jax.make_array_from_callback(
        emask_np.shape, sharding, lambda idx: jnp.asarray(emask_np[idx])
    )
    dist = jax.jit(
        lambda m: distributed_distance_transform(m, mesh, sp_axis="sp"),
        out_shardings=NamedSharding(mesh, P(None)),
    )(emask)
    want = ndimage.distance_transform_edt(emask_np)
    assert np.allclose(np.asarray(dist), want, rtol=1e-5, atol=1e-3), (
        "pod EDT deviates from the scipy oracle"
    )
    print(
        f"CC_POD_OK pid={pid} processes={jax.process_count()} "
        f"devices={sp} components={nref} spanning={len(spans)} edt_ok=1",
        flush=True,
    )
