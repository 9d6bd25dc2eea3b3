"""Silent-failure supervision unit tests (ISSUE 3, docs/ROBUSTNESS.md
"Silent failures"): heartbeat staleness -> lost-job resubmission, per-block
deadline -> hung quarantine + speculative re-execution, checksum
verify/repair round-trips, injector determinism for the hang / corrupt /
job_loss fault classes, the failures.json lock, and the multihost
timeout-with-partial-logs collection.  Tier-1: no sleep longer than ~1 s."""

import json
import os
import subprocess
import threading
import time

import numpy as np
import pytest

from cluster_tools_tpu.io.containers import ChunkCorruptionError
from cluster_tools_tpu.runtime import faults
from cluster_tools_tpu.runtime.executor import (
    BlockwiseExecutor,
    region_verifier,
)
from cluster_tools_tpu.runtime.faults import FaultInjector, InjectedFault
from cluster_tools_tpu.runtime.supervision import (
    FirstWins,
    HeartbeatWriter,
    Watchdog,
    array_digest,
    heartbeat_path,
    pid_alive,
    read_heartbeat,
    write_heartbeat,
)
from cluster_tools_tpu.utils import function_utils as fu
from cluster_tools_tpu.utils.volume_utils import Blocking, file_reader


@pytest.fixture(autouse=True)
def _reset_injector():
    yield
    faults.reset()
    faults.set_current_task(None)


# -- injector: the three new fault classes ------------------------------------


def test_injector_hang_gating_and_determinism():
    cfg = {"faults": [{"site": "load", "kind": "hang", "blocks": [2],
                       "seconds": 0.15, "fail_attempts": 1}]}
    inj = FaultInjector(cfg)
    t0 = time.monotonic()
    inj.maybe_hang("load", 1)       # other block: no sleep
    inj.maybe_hang("store", 2)      # other site: no sleep
    assert time.monotonic() - t0 < 0.1
    t0 = time.monotonic()
    inj.maybe_hang("load", 2)       # first attempt: sleeps
    assert time.monotonic() - t0 >= 0.14
    t0 = time.monotonic()
    inj.maybe_hang("load", 2)       # attempt 2 > fail_attempts: no sleep
    assert time.monotonic() - t0 < 0.1


def test_injector_hang_site_validation():
    with pytest.raises(ValueError, match="hang fault site"):
        FaultInjector({"faults": [{"site": "kernel", "kind": "hang"}]})


def test_injector_chunk_corrupt_gating():
    inj = FaultInjector(
        {"faults": [{"site": "io_write", "kind": "corrupt", "blocks": [3],
                     "fail_attempts": 2}]}
    )
    assert not inj.chunk_corrupt("io_write", 1)
    assert inj.chunk_corrupt("io_write", 3)
    assert inj.chunk_corrupt("io_write", 3)
    assert not inj.chunk_corrupt("io_write", 3)  # attempts exhausted
    with pytest.raises(ValueError, match="corrupt fault site"):
        FaultInjector({"faults": [{"site": "load", "kind": "corrupt"}]})
    with pytest.raises(ValueError, match="corrupt fault mode"):
        FaultInjector({"faults": [{"site": "io_read", "kind": "corrupt",
                                   "mode": "nonsense"}]})
    # read-site rot returns the mode (truthy) so boolean callers work
    inj2 = FaultInjector(
        {"faults": [{"site": "io_read", "kind": "corrupt",
                     "mode": "sidecar"}]}
    )
    assert inj2.chunk_corrupt("io_read") == "sidecar"
    assert inj2.chunk_corrupt("io_read") is None


def test_injector_job_loss_gating():
    inj = FaultInjector(
        {"faults": [{"site": "submit", "kind": "job_loss",
                     "fail_attempts": 2}]}
    )
    assert inj.lose_job()
    assert inj.lose_job()
    assert not inj.lose_job()  # the third submission goes through
    with pytest.raises(ValueError, match="job_loss faults"):
        FaultInjector({"faults": [{"site": "load", "kind": "job_loss"}]})


def test_injector_tasks_filter():
    faults.set_current_task("graph.12ab34cd")
    inj = FaultInjector(
        {"faults": [{"site": "load", "kind": "error", "tasks": ["watershed"],
                     "fail_attempts": 1}]}
    )
    inj.maybe_fail("load", 0)  # wrong task: no fire, no attempt consumed
    faults.set_current_task("watershed.deadbeef")
    with pytest.raises(InjectedFault):
        inj.maybe_fail("load", 0)
    inj.maybe_fail("load", 0)  # fail_attempts consumed


def test_block_context_threadlocal():
    assert faults.current_block_id() is None
    with faults.block_context(7):
        assert faults.current_block_id() == 7
        seen = []
        t = threading.Thread(
            target=lambda: seen.append(faults.current_block_id())
        )
        t.start()
        t.join()
        assert seen == [None]  # other threads are not polluted
        with faults.block_context(9):
            assert faults.current_block_id() == 9
        assert faults.current_block_id() == 7
    assert faults.current_block_id() is None


# -- heartbeats ---------------------------------------------------------------


def test_heartbeat_roundtrip(tmp_path):
    folder = str(tmp_path)
    assert read_heartbeat(folder, "t") is None
    write_heartbeat(folder, "t")
    hb = read_heartbeat(folder, "t")
    assert hb["pid"] == os.getpid()
    assert abs(hb["time"] - time.time()) < 5.0
    # torn heartbeat (kill mid-write before atomic writes) -> None
    with open(heartbeat_path(folder, "t"), "w") as f:
        f.write('{"time": 1')
    assert read_heartbeat(folder, "t") is None


def test_heartbeat_writer_beats(tmp_path):
    folder = str(tmp_path)
    w = HeartbeatWriter(folder, "job", interval_s=0.05).start()
    try:
        first = read_heartbeat(folder, "job")["time"]
        deadline = time.time() + 2.0
        while time.time() < deadline:
            if read_heartbeat(folder, "job")["time"] > first:
                break
            time.sleep(0.02)
        assert read_heartbeat(folder, "job")["time"] > first
    finally:
        w.stop()
    # after stop the beats cease
    last = read_heartbeat(folder, "job")["time"]
    time.sleep(0.15)
    assert read_heartbeat(folder, "job")["time"] == last


def test_pid_alive():
    assert pid_alive(os.getpid())
    p = subprocess.Popen(["true"])
    p.wait()
    assert not pid_alive(p.pid)


# -- watchdog + first-wins ----------------------------------------------------


def test_watchdog_fires_once_per_token():
    fired = []
    w = Watchdog(0.1, 0.02, lambda tok, info, el: fired.append((tok, info)))
    w.start()
    try:
        w.register("a", block_id=1, stage="load")
        w.register("b", block_id=2, stage="load")
        w.clear("b")  # finished in time: must never fire
        deadline = time.time() + 2.0
        while time.time() < deadline and not fired:
            time.sleep(0.02)
        time.sleep(0.2)  # more periods: "a" must not fire again
    finally:
        w.stop()
    assert [t for t, _ in fired] == ["a"]
    assert fired[0][1]["block_id"] == 1


def test_first_wins_commit_protocol():
    c = FirstWins()
    assert c.commit(1, "x") == FirstWins.WIN
    assert c.commit(1, "x") == FirstWins.AGREE
    assert c.commit(1, "y") == FirstWins.MISMATCH
    assert c.commit(2, "y") == FirstWins.WIN


def test_first_wins_withdraw_releases_failed_claim():
    c = FirstWins()
    assert c.commit(1, "x") == FirstWins.WIN
    c.withdraw(1, "x")  # the winner's store failed: claim released
    assert c.commit(1, "z") == FirstWins.WIN  # re-attempt claims fresh
    c.withdraw(1, "other")  # wrong digest: not the holder, no-op
    assert c.commit(1, "z") == FirstWins.AGREE


def test_array_digest_bit_sensitivity():
    a = np.arange(8, dtype=np.float32)
    b = a.copy()
    assert array_digest([a]) == array_digest([b])
    b.view(np.uint8)[0] ^= 1
    assert array_digest([a]) != array_digest([b])
    # dtype and shape are part of the identity
    assert array_digest([a]) != array_digest([a.astype(np.float64)])
    assert array_digest([a]) != array_digest([a.reshape(2, 4)])


# -- executor: hung blocks, speculation, checksum repair ----------------------


def _executor_case(n_blocks_axis=16):
    shape, bshape = (n_blocks_axis, 8, 8), (8, 8, 8)
    data = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
    blocking = Blocking(shape, bshape)
    blocks = [blocking.get_block(i) for i in range(blocking.n_blocks)]
    ex = BlockwiseExecutor(target="local", backoff_base=1e-4)
    return shape, bshape, data, blocks, ex


def test_executor_hung_block_quarantined_and_speculated(tmp_path):
    """A load stuck past block_deadline_s is detected within one watchdog
    period, quarantined, and resolved by a speculative duplicate — the run
    finishes correctly well before the hung call would have returned on a
    larger grid."""
    fp = str(tmp_path / "failures.json")
    faults.configure(
        {"faults": [{"site": "load", "kind": "hang", "blocks": [1],
                     "seconds": 0.7, "fail_attempts": 1}]}
    )
    shape, _, data, blocks, ex = _executor_case()
    out = np.zeros(shape, np.float32)
    t0 = time.monotonic()
    summary = ex.map_blocks(
        lambda x: x + 1, blocks,
        lambda b: (data[b.bb],),
        lambda b, raw: out.__setitem__(b.bb, np.asarray(raw)),
        # above a cold compile of the kernel on a loaded box (block 0 waits
        # for it and would be called hung too), well under the 0.7 s hang
        block_deadline_s=0.3,
        watchdog_period_s=0.05,
        failures_path=fp,
        task_name="hang_unit",
    )
    elapsed = time.monotonic() - t0
    np.testing.assert_array_equal(out, data + 1)
    assert summary["n_hung"] >= 1 and summary["n_speculated"] == 1
    assert summary["n_failed"] == 0
    # detection latency: hung within deadline + period (+ slack), not after
    # the 0.7 s sleep ended
    assert elapsed < 3.0
    rec = {r["block_id"]: r for r in json.load(open(fp))["records"]}[1]
    assert rec["quarantined"] and rec["resolved"]
    assert rec["sites"].get("hung", 0) >= 1


def test_executor_speculative_duplicate_agreement(tmp_path):
    """Both copies of a hung block complete (the hang is shorter than the
    run): the duplicate must AGREE with the winner bit-for-bit and the
    block resolves without a quarantine recompute."""
    fp = str(tmp_path / "failures.json")
    faults.configure(
        {"faults": [{"site": "store", "kind": "hang", "blocks": [0],
                     "seconds": 0.5, "fail_attempts": 1}]}
    )
    shape, _, data, blocks, ex = _executor_case()
    out = np.zeros(shape, np.float32)
    lock = threading.Lock()
    stores, done = [], []

    def store(b, raw):
        with lock:
            stores.append(int(b.block_id))
        out[b.bb] = np.asarray(raw)

    summary = ex.map_blocks(
        lambda x: x * 2, blocks,
        lambda b: (data[b.bb],),
        store,
        on_block_done=lambda b: done.append(int(b.block_id)),
        block_deadline_s=0.3,    # as above; the hang is 0.5 s
        watchdog_period_s=0.05,
        failures_path=fp,
        task_name="spec_unit",
    )
    np.testing.assert_array_equal(out, data * 2)
    assert summary["n_speculated"] == 1
    rec = {r["block_id"]: r for r in json.load(open(fp))["records"]}[0]
    assert rec["resolved"]
    # one of the two copies won the store, the other skipped it after the
    # digest agreement — block 0 must not have been stored twice, and its
    # success marker is written exactly once (by the agreeing copy, after
    # arbitration settled)
    assert stores.count(0) == 1
    assert done.count(0) == 1
    assert rec.get("duplicate") == "agreed"


def test_executor_corrupt_store_repaired_by_verify_retry(tmp_path):
    """A chunk bit-flipped on storage after a successful write is caught by
    the post-store digest verify and repaired by the store retry —
    bit-identical output, fault class attributed."""
    fp = str(tmp_path / "failures.json")
    shape, bshape, data, blocks, ex = _executor_case()
    f = file_reader(os.path.join(str(tmp_path), "x.zarr"))
    ds = f.create_dataset("out", shape=shape, chunks=bshape, dtype="float32")
    faults.configure(
        {"faults": [{"site": "io_write", "kind": "corrupt", "blocks": [1],
                     "fail_attempts": 1}]}
    )
    summary = ex.map_blocks(
        lambda x: x * 2, blocks,
        lambda b: (data[b.bb],),
        lambda b, raw: ds.__setitem__(b.bb, np.asarray(raw)),
        store_verify_fn=region_verifier(ds),
        failures_path=fp,
        task_name="corrupt_unit",
    )
    np.testing.assert_array_equal(ds[...], data * 2)
    assert summary["n_failed"] == 0 and summary["n_quarantined"] == 0
    rec = {r["block_id"]: r for r in json.load(open(fp))["records"]}[1]
    assert rec["resolved"]
    assert rec["sites"].get("corrupt", 0) >= 1


def test_executor_persistent_corruption_repaired_by_quarantine(tmp_path):
    """Corruption outlasting the store retry budget quarantines the block;
    the end-of-run recompute through the same compiled kernel restores
    bit-identical data."""
    fp = str(tmp_path / "failures.json")
    shape, bshape, data, blocks, ex = _executor_case()
    f = file_reader(os.path.join(str(tmp_path), "y.zarr"))
    ds = f.create_dataset("out", shape=shape, chunks=bshape, dtype="float32")
    faults.configure(
        {"faults": [{"site": "io_write", "kind": "corrupt", "blocks": [0],
                     "fail_attempts": 3}]}  # > io retry budget of 3 attempts
    )
    summary = ex.map_blocks(
        lambda x: x * 3, blocks,
        lambda b: (data[b.bb],),
        lambda b, raw: ds.__setitem__(b.bb, np.asarray(raw)),
        store_verify_fn=region_verifier(ds),
        failures_path=fp,
        task_name="corrupt_unit2",
    )
    np.testing.assert_array_equal(ds[...], data * 3)
    assert summary["n_quarantined"] == 1 and summary["n_failed"] == 0
    rec = {r["block_id"]: r for r in json.load(open(fp))["records"]}[0]
    assert rec["quarantined"] and rec["resolved"]
    assert rec["sites"].get("corrupt", 0) >= 1


# -- container checksum round-trip --------------------------------------------


def test_checksum_verify_and_repair_roundtrip(tmp_path, inject):
    path = os.path.join(str(tmp_path), "c.zarr")
    f = file_reader(path)
    ds = f.create_dataset("x", shape=(16, 8, 8), chunks=(8, 8, 8),
                          dtype="uint64")
    blk = np.arange(512, dtype=np.uint64).reshape(8, 8, 8)
    bb = (slice(0, 8),) * 3
    inject({"faults": [{"site": "io_write", "kind": "corrupt",
                        "fail_attempts": 1}]})
    ds[bb] = blk  # first write: silently bit-flipped after the sidecar
    with pytest.raises(ChunkCorruptionError, match="chunk corruption"):
        ds[bb]
    with pytest.raises(ChunkCorruptionError):
        ds.verify_region(bb)
    ds[bb] = blk  # repair: clean re-write
    ds.verify_region(bb)
    np.testing.assert_array_equal(ds[bb], blk)


def test_checksum_async_paths_verify(tmp_path, inject):
    """read_async/write_async go through the same digest machinery as the
    sync paths — prefetched IO is not a hole in the fault model."""
    path = os.path.join(str(tmp_path), "a.zarr")
    f = file_reader(path)
    ds = f.create_dataset("x", shape=(8, 8, 8), chunks=(8, 8, 8),
                          dtype="float32")
    blk = np.random.default_rng(0).random((8, 8, 8)).astype(np.float32)
    bb = (slice(0, 8),) * 3
    ds.write_async(bb, blk).result()
    np.testing.assert_array_equal(ds.read_async(bb).result(), blk)
    inject({"faults": [{"site": "io_write", "kind": "corrupt",
                        "fail_attempts": 1}]})
    ds.write_async(bb, blk).result()  # corrupted on landing
    with pytest.raises(ChunkCorruptionError):
        ds.read_async(bb).result()


def test_checksum_overlap_invalidation(tmp_path):
    """A partial overwrite must invalidate the stale enclosing digest —
    otherwise a later valid full read trips a false corruption alarm."""
    path = os.path.join(str(tmp_path), "o.zarr")
    f = file_reader(path)
    ds = f.create_dataset("x", shape=(16, 8, 8), chunks=(8, 8, 8),
                          dtype="float32")
    full = np.random.default_rng(1).random((16, 8, 8)).astype(np.float32)
    ds[...] = full
    ds[0:8, 0:8, 0:8] = full[0:8] + 1  # stales the full-volume digest
    out = ds[...]  # must NOT raise
    np.testing.assert_array_equal(out[8:], full[8:])
    # the block region itself is freshly digested and verifiable
    ds.verify_region((slice(0, 8),) * 3)


def test_checksum_memory_container(inject):
    from cluster_tools_tpu.io.containers import MemoryContainer

    f = MemoryContainer.open(f"memory://chk_{os.getpid()}")
    ds = f.create_dataset("x", shape=(8, 8), chunks=(8, 8), dtype="int64")
    blk = np.arange(64, dtype=np.int64).reshape(8, 8)
    inject({"faults": [{"site": "io_write", "kind": "corrupt",
                        "fail_attempts": 1}]})
    ds[:, :] = blk
    with pytest.raises(ChunkCorruptionError):
        ds[:, :]
    ds[:, :] = blk
    np.testing.assert_array_equal(ds[:, :], blk)


def test_region_verifier_none_for_h5(tmp_path):
    h5py = pytest.importorskip("h5py")  # noqa: F841
    path = os.path.join(str(tmp_path), "t.h5")
    f = file_reader(path)
    ds = f.create_dataset("x", shape=(8, 8), chunks=(8, 8), dtype="float32")
    assert region_verifier(ds) is None
    f.close()


def test_checksums_env_kill_switch(tmp_path, monkeypatch, inject):
    path = os.path.join(str(tmp_path), "k.zarr")
    f = file_reader(path)
    ds = f.create_dataset("x", shape=(8, 8), chunks=(8, 8), dtype="float32")
    monkeypatch.setenv("CTT_CHECKSUMS", "0")
    inject({"faults": [{"site": "io_write", "kind": "corrupt",
                        "fail_attempts": 1}]})
    blk = np.ones((8, 8), np.float32)
    ds[:, :] = blk
    # disabled: the corruption lands undetected (and no sidecar exists)
    assert not np.array_equal(ds[:, :], blk)
    assert not os.path.isdir(os.path.join(path, "x", ".ctt_checksums"))


# -- failures.json lock -------------------------------------------------------


def test_record_failures_concurrent_writers(tmp_path):
    """The lock-file read-modify-write must not drop records under
    concurrent writers (two cluster jobs reporting at the same moment)."""
    path = str(tmp_path / "failures.json")
    n_threads, per_thread = 8, 8

    def writer(t):
        for i in range(per_thread):
            fu.record_failures(
                path, f"task{t}",
                [{"block_id": i, "sites": {"host": 1}, "error": "x",
                  "quarantined": False, "resolved": False}],
            )

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = json.load(open(path))["records"]
    assert len(recs) == n_threads * per_thread
    assert not os.path.exists(path + ".lock")


def test_file_lock_breaks_stale_lock(tmp_path):
    path = str(tmp_path / "f.json")
    lock = path + ".lock"
    with open(lock, "w") as f:
        f.write("99999")
    old = time.time() - 120
    os.utime(lock, (old, old))
    with fu.file_lock(path, timeout_s=5.0, stale_s=60.0):
        pass  # stale lock from a dead holder was broken, not waited out
    assert not os.path.exists(lock)


def test_file_lock_breaks_dead_holder_immediately(tmp_path):
    """A SIGKILLed same-host holder leaves a FRESH lock file; its waiter
    must break it via the dead-pid probe, not sit out timeout_s (the
    adopter re-running a mid-run-killed request hits exactly this on
    io_metrics.json)."""
    import socket
    import sys

    path = str(tmp_path / "f.json")
    lock = path + ".lock"
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()  # a real, definitely-dead pid of ours to stamp
    with open(lock, "w") as f:
        f.write(f"{socket.gethostname()}:{proc.pid}:1:0.5")
    t0 = time.monotonic()
    with fu.file_lock(path, timeout_s=30.0, stale_s=60.0):
        pass
    assert time.monotonic() - t0 < 5.0
    assert not os.path.exists(lock)


def test_file_lock_dead_holder_probe_is_conservative(tmp_path):
    """Tokens the probe cannot vouch for — our own pid (a sibling thread),
    another host's pid, torn/garbage tokens — must NOT be broken early;
    they stay on the stale/timeout ladder."""
    import socket

    lock = str(tmp_path / "f.json.lock")
    host = socket.gethostname()
    for token in (
        f"{host}:{os.getpid()}:1:0.1",   # this process: alive by definition
        f"not-{host}:424242:1:0.1",      # cross-host: unprobeable
        "garbage",                        # torn token
        f"{host}:notanint:1:0.1",        # unparsable pid
    ):
        with open(lock, "w") as f:
            f.write(token)
        assert not fu._lock_holder_dead(lock)


# -- multihost timeout collection ---------------------------------------------


def test_collect_workers_timeout_kills_group_and_keeps_logs():
    from cluster_tools_tpu.parallel.multihost import collect_workers

    procs = [
        subprocess.Popen(
            ["bash", "-c", f"echo partial-{i}; echo err-{i} >&2; sleep 60"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            start_new_session=True,
        )
        for i in range(2)
    ]
    t0 = time.monotonic()
    with pytest.raises(TimeoutError) as exc:
        collect_workers(procs, timeout=0.5)
    assert time.monotonic() - t0 < 15.0
    msg = str(exc.value)
    # the partial output survived the kill
    assert "partial-0" in msg and "partial-1" in msg and "err-1" in msg
    for p in procs:
        assert p.poll() is not None  # no zombie workers


def test_collect_workers_normal_path():
    from cluster_tools_tpu.parallel.multihost import collect_workers

    procs = [
        subprocess.Popen(
            ["bash", "-c", "echo ok"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            start_new_session=True,
        )
    ]
    results = collect_workers(procs, timeout=30.0)
    assert results[0][0] == 0 and "ok" in results[0][1]


# -- cluster supervisor: lost jobs & resubmission -----------------------------


class _ScriptedSubmitter:
    """Fake scheduler: each submit() runs the next scripted behavior;
    is_running reports what the script says (the scheduler can lie)."""

    flavor = "scripted"

    def __init__(self, behaviors):
        self.behaviors = list(behaviors)
        self.submits = 0
        self.cancelled = []
        self._running = {}

    def submit(self, script_path, job_name, out_path, cfg):
        b = self.behaviors[min(self.submits, len(self.behaviors) - 1)]
        self.submits += 1
        job_id = f"j{self.submits}"
        self._running[job_id] = b.get("running", True)
        if b.get("action"):
            b["action"]()
        return job_id

    def is_running(self, job_id):
        return self._running.get(job_id, False)

    def cancel(self, job_id):
        self.cancelled.append(job_id)


def _supervise(submitter, tmp_path, cfg_extra=None, uid="task.abcd1234"):
    from cluster_tools_tpu.runtime.cluster import supervise_job

    tmp_folder = str(tmp_path / "tmp")
    os.makedirs(tmp_folder, exist_ok=True)
    result_path = os.path.join(tmp_folder, "result.json")
    cfg = {
        "poll_interval_s": 0.05,
        "result_grace_s": 0.2,
        "heartbeat_timeout_s": 0.4,
        "heartbeat_interval_s": 0.05,
        "max_resubmits": 2,
        "submit_timeout_s": 60,
    }
    cfg.update(cfg_extra or {})
    t0 = time.monotonic()
    sup = supervise_job(
        submitter,
        script_path="/dev/null",
        job_name=uid,
        out_path=os.path.join(tmp_folder, "job.out"),
        result_path=result_path,
        tmp_folder=tmp_folder,
        uid=uid,
        cfg=cfg,
        logger=None,
    )
    return sup, time.monotonic() - t0, tmp_folder, result_path


def _write_result(path, payload=None):
    with open(path, "w") as f:
        json.dump(payload or {"ok": True, "result": {}}, f)


def test_supervisor_resubmits_scheduler_lost_job(tmp_path):
    """The scheduler claims the job runs forever but nothing heartbeats:
    the supervisor declares it lost after heartbeat_timeout_s and resubmits
    — WITHOUT waiting out submit_timeout_s — and the resubmission's result
    completes the task.  The loss is auditable in supervisor.log and
    failures.json."""
    tmp_folder = str(tmp_path / "tmp")
    result_path = os.path.join(tmp_folder, "result.json")
    uid = "task.abcd1234"

    def good_job():
        # the healthy resubmission heartbeats and delivers a result
        os.makedirs(tmp_folder, exist_ok=True)
        write_heartbeat(tmp_folder, uid)
        _write_result(result_path)

    sub = _ScriptedSubmitter([
        {"running": True},            # lost: runs per scheduler, no beats
        {"running": True, "action": good_job},
    ])
    sup, elapsed, tmp_folder, _ = _supervise(sub, tmp_path, uid=uid)
    assert sup["resubmits"] == 1 and sub.submits == 2
    assert sup["job_ids"] == ["j1", "j2"]
    assert "j1" in sub.cancelled  # the zombie was cancelled before resubmit
    assert elapsed < 10.0  # heartbeat path, not submit_timeout_s=60
    with open(os.path.join(tmp_folder, "cluster", "supervisor.log")) as f:
        log = f.read()
    assert "declared lost" in log and "resubmitting (1/2)" in log
    doc = json.load(open(os.path.join(tmp_folder, "failures.json")))
    rec = next(r for r in doc["records"] if r["task"] == uid)
    assert rec["sites"]["job_loss"] == 1 and rec["resolved"]


def test_supervisor_dead_pid_detected_fast(tmp_path):
    """A fresh heartbeat whose pid is dead on this host is a loss signal
    even before the staleness timeout — same-host detection is instant."""
    tmp_folder = str(tmp_path / "tmp")
    result_path = os.path.join(tmp_folder, "result.json")
    uid = "task.abcd1234"
    dead = subprocess.Popen(["true"])
    dead.wait()

    def dead_worker():
        os.makedirs(tmp_folder, exist_ok=True)
        fu.atomic_write_json(
            heartbeat_path(tmp_folder, uid),
            {"time": time.time(), "pid": dead.pid,
             "host": __import__("socket").gethostname()},
        )

    sub = _ScriptedSubmitter([
        {"running": True, "action": dead_worker},
        {"running": True,
         "action": lambda: _write_result(result_path)},
    ])
    # huge staleness timeout: only the pid check can catch this quickly
    sup, elapsed, *_ = _supervise(
        sub, tmp_path, cfg_extra={"heartbeat_timeout_s": 300}, uid=uid
    )
    assert sup["resubmits"] == 1
    assert elapsed < 10.0


def test_supervisor_vanished_job_resubmitted(tmp_path):
    """A job that leaves the queue without a result (crashed node, purged
    array index) is resubmitted after the result grace, not raised at the
    first occurrence."""
    result_holder = {}

    sub = _ScriptedSubmitter([
        {"running": False},  # gone immediately, no result
        {"running": True,
         "action": lambda: _write_result(result_holder["path"])},
    ])
    tmp_folder = str(tmp_path / "tmp")
    result_holder["path"] = os.path.join(tmp_folder, "result.json")
    sup, elapsed, *_ = _supervise(sub, tmp_path)
    assert sup["resubmits"] == 1 and sub.submits == 2


def test_supervisor_gives_up_after_max_resubmits(tmp_path):
    sub = _ScriptedSubmitter([{"running": True}])  # every incarnation lost
    with pytest.raises(RuntimeError, match="giving up"):
        _supervise(sub, tmp_path, cfg_extra={"max_resubmits": 1})
    assert sub.submits == 2  # original + 1 resubmission


def test_supervisor_job_loss_injection_end_to_end(tmp_path, inject):
    """The job_loss fault class: the first submission is swallowed (the
    fake scheduler never even sees it), heartbeat supervision finds it and
    the resubmission — a real submit — completes."""
    tmp_folder = str(tmp_path / "tmp")
    result_path = os.path.join(tmp_folder, "result.json")
    inject({"faults": [{"site": "submit", "kind": "job_loss",
                        "fail_attempts": 1}]})
    sub = _ScriptedSubmitter([
        {"running": True, "action": lambda: _write_result(result_path)},
    ])
    sup, elapsed, *_ = _supervise(sub, tmp_path)
    assert sup["resubmits"] == 1
    assert sub.submits == 1  # the swallowed submission never reached it
    assert sup["job_ids"][0].startswith("lost:")
