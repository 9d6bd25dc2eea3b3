"""Logging and success markers.

The reference coordinated completion through log files: workers wrote
``log_block_success`` / ``log_job_success`` lines that the driver's
``check_jobs`` grepped (SURVEY.md §2d, §5.5).  We keep the same two-level
success-marker contract (it is the resume mechanism), but markers are JSON
manifests rather than grep-able log lines.
"""

from __future__ import annotations

import contextlib
import datetime
import json
import logging
import os
import random
import sys
import threading
import time
from typing import Iterable, List, Optional

_LOGGERS = {}
_LOCK = threading.Lock()


def get_logger(name: str = "cluster_tools_tpu", log_file: Optional[str] = None):
    with _LOCK:
        key = (name, log_file)
        if key in _LOGGERS:
            return _LOGGERS[key]
        logger = logging.getLogger(name if log_file is None else f"{name}:{log_file}")
        logger.setLevel(logging.INFO)
        logger.propagate = False
        fmt = logging.Formatter("%(asctime)s %(levelname)s %(name)s: %(message)s")
        handler = (
            logging.FileHandler(log_file)
            if log_file
            else logging.StreamHandler(sys.stderr)
        )
        handler.setFormatter(fmt)
        logger.addHandler(handler)
        _LOGGERS[key] = logger
        return logger


def log(msg: str, log_file: Optional[str] = None):
    get_logger(log_file=log_file).info(msg)


def atomic_write_json(path: str, doc, default=None) -> None:
    """Write JSON via a temp file + ``os.replace`` so readers never observe
    a torn document — a kill mid-write leaves the old file (or nothing),
    never half a manifest."""
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, default=default)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def read_json_if_valid(path: str):
    """Parse a JSON file; return None for missing or torn (unparseable)
    files — torn manifests are treated as not-done, never as fatal."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def backoff_delay(attempt: int, base: float, cap: float) -> float:
    """Capped exponential backoff with full jitter (0.5x-1x): the one
    retry-delay policy shared by the executor's per-block IO retries, the
    scheduler submit retries, and the task-level re-runs — jitter keeps N
    workers recovering from a shared outage from thundering-herd retrying
    at the same instant."""
    import random

    return min(cap, base * (2 ** attempt)) * (0.5 + 0.5 * random.random())


def cap_traceback(tb: str, max_chars: int = 2000) -> str:
    """Tail-capped traceback (the last lines carry the error) so failure
    manifests aggregating hundreds of blocks stay bounded."""
    if len(tb) <= max_chars:
        return tb
    return "... [truncated] ...\n" + tb[-max_chars:]


def failures_path(tmp_folder: str) -> str:
    """The per-run structured failure manifest (shared by all tasks)."""
    return os.path.join(tmp_folder, "failures.json")


def _hostname() -> str:
    global _HOSTNAME
    if _HOSTNAME is None:
        import socket

        _HOSTNAME = socket.gethostname()
    return _HOSTNAME


_HOSTNAME: Optional[str] = None


def _lock_holder_dead(lock: str) -> bool:
    """True when ``lock``'s token names a pid on THIS host that no longer
    exists — a SIGKILLed holder whose lock would otherwise pin every
    waiter for the full ``timeout_s``.  A token from another host (shared
    filesystem), an unparsable/torn token, or a live-or-unprobeable pid
    all answer False: the stale/timeout ladder handles those — pid reuse
    can only make a dead holder look alive (conservative), never a live
    holder look dead."""
    try:
        with open(lock) as f:
            token = f.read()
    except OSError:
        return False
    parts = token.split(":")
    if len(parts) != 4 or parts[0] != _hostname():
        return False
    try:
        pid = int(parts[1])
    except ValueError:
        return False
    if pid == os.getpid():
        return False  # another thread of this process: alive by definition
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return True
    except OSError:
        pass
    return False


@contextlib.contextmanager
def file_lock(path: str, timeout_s: float = 30.0, stale_s: float = 60.0):
    """Advisory cross-process lock via an ``O_CREAT|O_EXCL`` lock file
    (works on the shared filesystems cluster jobs coordinate over, where
    ``fcntl`` locks are unreliable).  A lock whose same-host holder pid is
    dead is broken immediately (:func:`_lock_holder_dead` — a SIGKILLed
    holder must not make its adopter wait out the full timeout); a lock
    older than ``stale_s`` is broken (its cross-host holder died between
    create and unlink); after ``timeout_s`` the lock is stolen rather than
    raising — the callers guard best-effort bookkeeping on failure paths,
    where blocking forever or raising would mask the real error."""
    lock = path + ".lock"
    # unique ownership token: release must only unlink OUR lock file — a
    # holder whose lock was stolen (timeout/stale break) must not remove
    # the thief's lock and cascade the loss of mutual exclusion.  The
    # host:pid prefix is what the dead-holder probe parses.
    token = (
        f"{_hostname()}:{os.getpid()}:{threading.get_ident()}"
        f":{random.random()}"
    )
    deadline = time.time() + float(timeout_s)
    while True:
        try:
            fd = os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            os.write(fd, token.encode())
            os.close(fd)
            break
        except FileExistsError:
            try:
                stale = time.time() - os.path.getmtime(lock) > float(stale_s)
            except OSError:
                continue  # holder released between exists-check and stat
            if stale or time.time() > deadline or _lock_holder_dead(lock):
                # atomic steal: rename first — exactly one of N waiters
                # wins the rename, so two waiters can never both break the
                # same lock and then break each other's fresh locks
                grave = f"{lock}.stolen.{os.getpid()}.{threading.get_ident()}"
                try:
                    os.rename(lock, grave)
                    os.unlink(grave)
                except OSError:
                    pass  # another waiter stole it first; re-acquire
                continue
            time.sleep(0.005 + 0.01 * random.random())
    try:
        yield
    finally:
        try:
            with open(lock) as f:
                if f.read() == token:
                    os.unlink(lock)
        except OSError:
            pass


#: failures.json record schema: 2 adds per-record ``schema_version`` /
#: ``hostname`` / ``pid`` (records merged from concurrent cluster jobs stay
#: attributable to the process that wrote them) and the optional
#: ``resolution`` / ``resource`` degradation fields (docs/ROBUSTNESS.md).
FAILURES_SCHEMA_VERSION = 2


def record_failures(path: str, task_name: str, records) -> None:
    """Merge block-failure records into ``failures.json`` (atomic).

    Schema: ``{"version": 2, "records": [{"task", "block_id",
    "sites": {site: attempts}, "error", "quarantined", "resolved",
    "schema_version", "hostname", "pid", ...}]}`` (optional fields:
    ``resolution``, ``resource``, ``job_id``/``job_ids``, ``duplicate``).
    Records are keyed by (task, block_id): a resumed run's record replaces
    the stale one from before the restart.  Each record is stamped with the
    recording process's hostname + pid, so records merged from concurrent
    cluster jobs stay attributable.  The read-modify-write runs under a
    lock file so two cluster jobs recording failures at the same moment
    cannot drop each other's records.
    """
    import socket

    host, pid = socket.gethostname(), os.getpid()
    with file_lock(path):
        doc = read_json_if_valid(path) or {}
        existing = {
            (r.get("task"), r.get("block_id")): r
            for r in doc.get("records", [])
        }
        for rec in records:
            rec = dict(rec)
            rec["task"] = task_name
            rec.setdefault("schema_version", FAILURES_SCHEMA_VERSION)
            rec.setdefault("hostname", host)
            rec.setdefault("pid", pid)
            existing[(task_name, rec.get("block_id"))] = rec
        merged = sorted(
            existing.values(),
            key=lambda r: (str(r.get("task")), r.get("block_id") or 0),
        )
        atomic_write_json(
            path, {"version": FAILURES_SCHEMA_VERSION, "records": merged}
        )


def io_metrics_path(tmp_folder: str) -> str:
    """The per-run chunk-IO metrics manifest, next to ``failures.json``."""
    return os.path.join(tmp_folder, "io_metrics.json")


def _merge_counters(old, new):
    """Numbers add, lists append, groups of counters (the ``compile`` block)
    merge key by key; anything else is replaced."""
    if isinstance(new, (int, float)) and isinstance(old, (int, float)):
        return old + new
    if isinstance(new, list) and isinstance(old, list):
        return old + new
    if isinstance(new, dict) and isinstance(old, dict):
        merged = dict(old)
        for k, v in new.items():
            merged[k] = _merge_counters(old.get(k), v)
        return merged
    return new


def record_io_metrics(path: str, task_name: str, metrics) -> None:
    """Merge one task's chunk-IO counter deltas into ``io_metrics.json``.

    Schema: ``{"version": 2, "tasks": {uid: {counter: total, ...}},
    "provenance": {uid: {"host:pid": {"host", "pid", "last_updated",
    "merges", "counters"}}}}``.  Counters merge *additively* per task uid —
    a resumed run's second pass, or concurrent cluster job processes
    writing over the shared filesystem, accumulate into one total (same
    file-lock discipline as :func:`record_failures`).  The additive merge
    alone makes a cluster worker's delta indistinguishable from the
    submitter's, so every merge also stamps a **provenance** entry for the
    writing process: which host:pid contributed, when it last wrote, how
    many times it merged, and which counter keys it moved — multi-process
    runs stay attributable per contributor.  Derived figures (hit rate,
    bytes saved) are computed at render time by
    ``scripts/failures_report.py``, never stored.
    """
    import socket

    with file_lock(path):
        doc = read_json_if_valid(path) or {}
        # version 2 = the provenance map; the tasks schema is unchanged,
        # so version-1 readers keep working
        doc["version"] = max(2, int(doc.get("version") or 1))
        tasks = doc.setdefault("tasks", {})
        cur = dict(tasks.get(task_name) or {})
        moved = []
        for k, v in dict(metrics).items():
            cur[k] = _merge_counters(cur.get(k), v)
            if not isinstance(v, (int, float)) or v:
                moved.append(str(k))
        tasks[task_name] = cur
        host, pid = socket.gethostname(), os.getpid()
        prov = doc.setdefault("provenance", {}).setdefault(task_name, {})
        entry = dict(prov.get(f"{host}:{pid}") or {})
        entry.update({
            "host": host,
            "pid": pid,
            "last_updated": _now(),
            "merges": int(entry.get("merges", 0)) + 1,
            "counters": sorted(set(entry.get("counters") or []) | set(moved)),
        })
        prov[f"{host}:{pid}"] = entry
        atomic_write_json(path, doc)


def _marker_dir(tmp_folder: str, task_name: str) -> str:
    d = os.path.join(tmp_folder, "markers", task_name)
    os.makedirs(d, exist_ok=True)
    return d


def log_block_success(tmp_folder: str, task_name: str, block_id: int):
    """Record that one block of a task finished (block-level resume grain).
    Atomic: a kill mid-write must not leave a torn marker that a resumed
    run would count as done."""
    path = os.path.join(_marker_dir(tmp_folder, task_name), f"block_{block_id}.json")
    atomic_write_json(path, {"block_id": block_id, "time": _now()})


def log_job_success(tmp_folder: str, task_name: str, job_id: int):
    path = os.path.join(_marker_dir(tmp_folder, task_name), f"job_{job_id}.json")
    atomic_write_json(path, {"job_id": job_id, "time": _now()})


def blocks_done(tmp_folder: str, task_name: str) -> List[int]:
    """Block ids with a *valid* success marker.  Torn markers (partial
    writes from a kill predating atomic markers, or filesystem damage) are
    pruned and reported as not-done so the block re-runs."""
    d = _marker_dir(tmp_folder, task_name)
    out = []
    for fname in os.listdir(d):
        if fname.startswith("block_") and fname.endswith(".json"):
            block_id = int(fname[len("block_"):-len(".json")])
            if read_json_if_valid(os.path.join(d, fname)) is None:
                try:
                    os.remove(os.path.join(d, fname))
                except OSError:
                    pass
                continue
            out.append(block_id)
    return sorted(out)


def jobs_done(tmp_folder: str, task_name: str) -> List[int]:
    d = _marker_dir(tmp_folder, task_name)
    return sorted(
        int(f[len("job_"):-len(".json")])
        for f in os.listdir(d)
        if f.startswith("job_") and f.endswith(".json")
    )


def clean_up_for_retry(tmp_folder: str, task_name: str):
    """Drop job-level markers so a failed task re-checks its blocks."""
    d = _marker_dir(tmp_folder, task_name)
    for fname in os.listdir(d):
        if fname.startswith("job_"):
            os.remove(os.path.join(d, fname))


def clear_block_markers(tmp_folder: str, task_name: str):
    """Drop ALL of a task's markers — block grain included.

    Used when the data the markers describe no longer exists: an in-memory
    handoff output (docs/PERFORMANCE.md "Task-graph fusion") dies with its
    process, so markers a previous process wrote would make a resumed run
    skip blocks whose results were never stored anywhere.
    """
    d = _marker_dir(tmp_folder, task_name)
    for fname in os.listdir(d):
        if fname.startswith(("block_", "job_")):
            try:
                os.remove(os.path.join(d, fname))
            except OSError:
                pass


def _now() -> str:
    return datetime.datetime.now().isoformat()


def python_executable() -> str:
    """Interpreter for re-executing framework entry points in batch jobs."""
    return sys.executable
