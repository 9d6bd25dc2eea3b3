"""One-screen post-mortem of a run's ``failures.json`` (docs/ROBUSTNESS.md).

Usage::

    python scripts/failures_report.py <tmp_folder | failures.json>
    python scripts/failures_report.py --trace <tmp_folder | trace_summary.json>
    python scripts/failures_report.py --json <tmp_folder> [--no-lint]
    python scripts/failures_report.py --lint <lint.json | ->
    make failures-report TMP=/path/to/tmp_folder

Per task: block counts, per-site failed-attempt totals, resolutions
(recovered / degraded:split / requeued:preempt / ...), quarantines, and the
unresolved block ids an operator has to chase — plus host/pid attribution
when records came from more than one process (schema v2).

When the run recorded chunk-IO metrics (``io_metrics.json``, written next
to ``failures.json`` by the task runtime — docs/PERFORMANCE.md "Chunk-aware
I/O"), a second section renders each task's cache hit rate, bytes read from
storage vs bytes served, and the bytes the cache saved — with per-process
provenance (which host:pid contributed which counters, and when) for
multi-process runs (io_metrics.json schema v2).

``--trace`` renders the unified-timeline aggregates
(``trace_summary.json``, written by a ``CTT_TRACE=1`` run next to
``io_metrics.json`` — docs/OBSERVABILITY.md): per-site latency percentiles
(p50/p95/p99), instant counts, the task-DAG critical path, and per-process
utilization.  The default report appends the same section when a summary
exists.

``--json`` emits ONE machine-readable document for the whole run —
failure summaries + io_metrics (with provenance) + the trace summary +
a fresh ctlint pass over the repo (skippable with ``--no-lint``) — so CI
and the service mode consume the post-mortem without scraping text.
Exit code 1 when the run has unresolved failures or the lint pass found
findings.

``--lint`` renders a ctlint findings document (docs/ANALYSIS.md) instead:
``python -m cluster_tools_tpu.lint --json > lint.json`` then point this at
it (or pipe with ``-``).  Exit code 1 when the document carries findings —
same contract as the linter itself.
"""

from __future__ import annotations

import json
import os
import sys
from collections import Counter, defaultdict


def load_records(path: str):
    if os.path.isdir(path):
        path = os.path.join(path, "failures.json")
    with open(path) as f:
        doc = json.load(f)
    return path, doc.get("version"), doc.get("records", [])


def load_io_metrics(failures_json_path: str, with_provenance: bool = False):
    """Per-task chunk-IO counters from the sibling ``io_metrics.json``
    ({} when the run recorded none — the report stays failures-only).
    ``with_provenance`` returns ``(tasks, provenance)`` instead."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(failures_json_path)),
        "io_metrics.json",
    )
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    tasks = doc.get("tasks", {}) or {}
    if with_provenance:
        return tasks, doc.get("provenance", {}) or {}
    return tasks


def load_journal_stats(failures_json_path: str):
    """Aggregate stats of the service mode's durable submission journal
    (``journal.log`` next to ``failures.json`` — docs/SERVING.md
    "Durability"), or None when the run has no journal.

    The frame scanner mirrors ``runtime/journal.py`` (MAGIC + u32 length
    + u32 crc32 + compact-JSON payload) on purpose: this report must work
    stdlib-only on a bare login node, like the progress view.  A torn
    tail is counted, never fatal — the same truncate-and-warn posture the
    journal's own reader takes.
    """
    import struct
    import zlib

    path = os.path.join(
        os.path.dirname(os.path.abspath(failures_json_path)), "journal.log"
    )
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        return None
    header = struct.Struct("<4sII")
    records, off = [], 0
    while True:
        head = data[off:off + header.size]
        if len(head) < header.size:
            break
        magic, length, crc = header.unpack(head)
        if magic != b"CTJ1" or length > (16 << 20):
            break
        payload = data[off + header.size:off + header.size + length]
        if len(payload) < length or zlib.crc32(payload) != crc:
            break
        try:
            rec = json.loads(payload)
        except ValueError:
            break
        if not isinstance(rec, dict):
            break
        records.append(rec)
        off += header.size + length
    by_type = Counter(str(r.get("type")) for r in records)
    return {
        "path": path,
        "bytes": len(data),
        "n_records": len(records),
        "by_type": dict(by_type),
        # a dispatched record with attempt > 1 is a replayed re-run of an
        # acknowledged request (the crash-loop budget's evidence)
        "n_replays": sum(
            1 for r in records
            if r.get("type") == "dispatched" and int(r.get("attempt") or 1) > 1
        ),
        "n_quarantined": int(by_type.get("quarantined", 0)),
        "torn_tail_bytes": len(data) - off,
    }


def format_journal_stats(j) -> list:
    """Render the submission-journal block: record counts per lifecycle
    type, replays, quarantines, and torn-tail evidence."""
    types = ", ".join(
        f"{t}={n}" for t, n in sorted((j.get("by_type") or {}).items())
    )
    lines = [
        f"submission journal (journal.log): {j.get('n_records', 0)} "
        f"record(s), {_human_bytes(float(j.get('bytes', 0)))}"
        + (f" ({types})" if types else "")
    ]
    if j.get("n_replays"):
        lines.append(
            f"  {j['n_replays']} replayed dispatch(es) — acknowledged "
            "work re-run after a restart"
        )
    if j.get("n_quarantined"):
        lines.append(
            f"  {j['n_quarantined']} quarantined request(s) "
            "(quarantined:crash_loop — see the failure records above)"
        )
    if j.get("torn_tail_bytes"):
        lines.append(
            f"  torn tail: {j['torn_tail_bytes']} byte(s) after the last "
            "intact record (a crash mid-append; replay truncates it)"
        )
    return lines


def load_scrub_stats(failures_json_path: str):
    """The self-healing plane's state (``scrub_state.json`` next to
    ``failures.json`` — docs/SERVING.md "Self-healing"): scrub coverage
    and findings plus the verifying-reader and lineage-repair counters.
    None for runs without a scrubber."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(failures_json_path)),
        "scrub_state.json",
    )
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    return doc if isinstance(doc, dict) else None


def format_scrub_stats(s) -> list:
    """Render the scrub block: bytes/regions verified at rest, corruption
    found and its fate, and the read-side counters the scrub
    cross-checks."""
    reader = s.get("reader") or {}
    rep = s.get("repair") or {}
    lines = [
        f"scrubber (scrub_state.json): {s.get('scanned_regions', 0)} "
        f"region(s) / {_human_bytes(float(s.get('scanned_bytes', 0)))} "
        f"verified at rest, {s.get('passes', 0)} full pass(es)"
        + (f", coverage {s['coverage']:.0%} of current pass"
           if s.get("coverage") is not None else "")
    ]
    if s.get("found_corrupt"):
        lines.append(
            f"  at-rest corruption: {s['found_corrupt']} found, "
            f"{s.get('repaired', 0)} repaired from lineage, "
            f"{s.get('unrepairable', 0)} unrepairable"
        )
    if reader.get("corrupt_detected") or reader.get("sidecars_adopted") \
            or reader.get("strict_missing"):
        lines.append(
            f"  verifying reader: {reader.get('corrupt_detected', 0)} "
            f"corrupt read(s) detected, "
            f"{reader.get('repaired_reads', 0)} healed in-line, "
            f"{reader.get('unrepairable_reads', 0)} raised typed; "
            f"{reader.get('sidecars_adopted', 0)} sidecar(s) adopted, "
            f"{reader.get('strict_missing', 0)} strict refusal(s)"
        )
    if rep.get("unrepairable"):
        lines.append(
            f"  {rep['unrepairable']} region(s) quarantined as "
            "unrepairable (quarantined:unrepairable — operator action "
            "needed: the lineage could not heal them)"
        )
    return lines


def _human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GiB"  # pragma: no cover - loop always returns


def format_io_metrics(tasks, provenance=None) -> list:
    """Render per-task cache effectiveness lines (hit rate, bytes saved)
    and, when the task ran compiled sweeps, the dispatch-amortization
    figures of the sharded executor (docs/PERFORMANCE.md "Sharded
    sweeps"): batches dispatched, blocks per dispatch, the time the
    dispatch loop stalled on un-overlapped loads, and the overlap
    efficiency (1 - stall / sweep wall time)."""
    lines = ["chunk-IO metrics (io_metrics.json):"]
    for task in sorted(tasks):
        m = tasks[task] or {}
        hits = int(m.get("hits", 0))
        misses = int(m.get("misses", 0))
        looked = hits + misses
        has_cache = looked or m.get("bytes_served") or m.get("direct_reads")
        if has_cache:
            rate = f"{100.0 * hits / looked:.1f}%" if looked else "n/a"
            stored = float(m.get("bytes_from_storage", 0))
            served = float(m.get("bytes_served", 0))
            saved = max(0.0, served - stored)
            lines.append(
                f"[{task}]  hit rate {rate} ({hits}/{looked}), "
                f"coalesced {int(m.get('coalesced', 0))}, "
                f"storage {_human_bytes(stored)} -> served "
                f"{_human_bytes(served)} (saved {_human_bytes(saved)})"
            )
        else:
            lines.append(f"[{task}]")
        if m.get("direct_reads"):
            lines.append(
                f"  uncached direct reads: {int(m['direct_reads'])}"
            )
        published = int(m.get("handoffs_published", 0))
        served = int(m.get("handoffs_served", 0))
        spilled = int(m.get("handoffs_spilled", 0))
        fallbacks = int(m.get("handoff_fallbacks", 0))
        # a spill inside THIS task's snapshot window reconciles bytes
        # another task counted, so the per-task delta can be negative —
        # clamp for display (the spill itself shows in the spilled count;
        # sums across tasks still net to the true figure)
        not_stored = max(0.0, float(m.get("bytes_not_stored", 0)))
        if published or served or spilled or fallbacks \
                or m.get("bytes_not_stored"):
            # task-graph fusion (docs/PERFORMANCE.md): in-memory targets
            # this task published/consumed, how many spilled to storage,
            # and the intermediate bytes that never touched the store
            lines.append(
                f"  handoffs: {published} published, {served} served "
                f"in-memory, {spilled} spilled "
                f"({_human_bytes(float(m.get('bytes_spilled', 0)))}), "
                f"{fallbacks} fallback read(s), "
                f"{_human_bytes(not_stored)} never stored"
            )
        # solver attribution (docs/PERFORMANCE.md "Distributed
        # agglomeration"): contraction-engine calls/rounds/edge movement,
        # plus the reduce tree's level counts and degradations when the
        # solve ran sharded
        calls = int(m.get("solver_calls", 0))
        tree_rounds = int(m.get("tree_rounds", 0))
        if calls or tree_rounds:
            rounds = int(m.get("solver_rounds", 0)) + tree_rounds
            lines.append(
                f"  solver: {calls} solve(s), {rounds} contraction "
                f"round(s), edges {int(m.get('solver_edges_in', 0))} -> "
                f"{int(m.get('solver_edges_out', 0))} surviving"
            )
        sharded = int(m.get("sharded_solves", 0))
        if sharded or m.get("unsharded_fallbacks"):
            lines.append(
                f"  reduce tree: {sharded} sharded solve(s), "
                f"{int(m.get('solve_shards', 0))} shard(s) over "
                f"{int(m.get('solve_levels', 0))} level(s), "
                f"boundary edges {int(m.get('boundary_edges_in', 0))} -> "
                f"{int(m.get('boundary_edges_out', 0))} at root, "
                f"solve {float(m.get('tree_solve_s', 0.0)):.2f}s / merge "
                f"{float(m.get('tree_merge_s', 0.0)):.2f}s, "
                f"{int(m.get('unsharded_fallbacks', 0))} unsharded "
                "fallback(s)"
            )
        batches = int(m.get("batches_dispatched", 0))
        if batches:
            blocks = int(m.get("blocks_dispatched", 0))
            wait = float(m.get("dispatch_wait_s", 0.0))
            sweep = float(m.get("sweep_s", 0.0))
            per = blocks / batches
            overlap = (
                f"{100.0 * max(0.0, 1.0 - wait / sweep):.1f}%"
                if sweep > 0 else "n/a"
            )
            lines.append(
                f"  dispatches: {batches} batch(es), "
                f"{per:.1f} blocks/dispatch, "
                f"dispatch wait {wait:.2f}s, overlap efficiency {overlap}"
            )
        ragged = int(m.get("ragged_batches", 0))
        if ragged:
            # ragged paged sweeps (docs/PERFORMANCE.md "Ragged sweeps"):
            # mixed-shape / partial batches that ran as one program via
            # the paged block pool instead of per-block fallback
            lines.append(
                f"  ragged: {ragged} of those batch(es) paged "
                f"(mixed-shape/partial lanes), "
                f"{int(m.get('lanes_padded', 0))} padding lane(s) "
                f"discarded, {int(m.get('pages_in_use', 0))} pool "
                f"page(s) in use"
            )
        comp = m.get("compile") or {}
        if comp:
            # did this task recompile, and which program
            # (docs/OBSERVABILITY.md "Compiles")
            missed = comp.get("programs_missed") or []
            lines.append(
                f"  compiles: {int(comp.get('requests', 0))} cache "
                f"request(s), {int(comp.get('cache_hits', 0))} hit(s), "
                f"{int(comp.get('cache_misses', 0))} miss(es), "
                f"{int(comp.get('uncached', 0))} uncached; trace "
                f"{float(comp.get('trace_s', 0.0)):.2f}s, lower "
                f"{float(comp.get('lower_s', 0.0)):.2f}s, compile "
                f"{float(comp.get('backend_s', 0.0)):.2f}s, cache read "
                f"{float(comp.get('cache_load_s', 0.0)):.2f}s"
                + (f"; compiled: {', '.join(missed[:8])}"
                   + (f" (+{len(missed) - 8} more)" if len(missed) > 8 else "")
                   if missed else "")
            )
        # multi-process attribution (io_metrics.json schema v2): when more
        # than one process merged into this task's counters, say which
        # host:pid contributed what — the additive totals alone cannot
        contributors = (provenance or {}).get(task) or {}
        if len(contributors) > 1:
            for key in sorted(contributors):
                c = contributors[key]
                counters = c.get("counters") or []
                shown = ", ".join(counters[:6]) + (
                    ", ..." if len(counters) > 6 else ""
                )
                lines.append(
                    f"  contributed by {key} (x{int(c.get('merges', 1))}, "
                    f"last {c.get('last_updated', '?')}): {shown}"
                )
    return lines


def load_trace_summary(failures_json_path: str):
    """The run's ``trace_summary.json`` (written next to io_metrics.json by
    a ``CTT_TRACE=1`` run — docs/OBSERVABILITY.md), or {}."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(failures_json_path)),
        "trace_summary.json",
    )
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def format_trace_summary(summ) -> list:
    """Render the unified-timeline aggregates: per-site latency
    percentiles, instants, the critical path, per-process utilization, and
    the executor overlap cross-check (docs/OBSERVABILITY.md)."""
    lines = [
        f"trace summary (trace_summary.json): {int(summ.get('n_events', 0))} "
        f"event(s) from {int(summ.get('n_processes', 0))} process(es)"
        + (f", {int(summ['dropped'])} dropped" if summ.get("dropped") else "")
    ]
    sites = summ.get("sites") or {}
    if sites:
        # self_s: a site's seconds outside the spans nested in it (its
        # total where a summary predates the column)
        lines.append("  site                     count    p50_ms    p99_ms"
                     "    total_s     self_s")
        for name in sorted(sites):
            s = sites[name]
            lines.append(
                f"  {name:<24} {int(s.get('count', 0)):>5}"
                f" {float(s.get('p50_ms', 0)):>9.3f}"
                f" {float(s.get('p99_ms', 0)):>9.3f}"
                f" {float(s.get('total_s', 0)):>10.3f}"
                f" {float(s.get('self_s', s.get('total_s', 0))):>10.3f}"
            )
    instants = summ.get("instants") or {}
    if instants:
        lines.append(
            "  instants: " + ", ".join(
                f"{name}={n}" for name, n in sorted(instants.items())
            )
        )
    cp = summ.get("critical_path")
    if cp:
        lines.append(
            f"  critical path ({float(cp.get('total_s', 0)):.3f}s): "
            + " -> ".join(
                f"{uid} ({cp.get('task_s', {}).get(uid, 0):.3f}s)"
                for uid in cp.get("tasks", [])
            )
        )
    for p in summ.get("processes") or []:
        busy = p.get("busy_s_by_cat") or {}
        busy_str = ", ".join(
            f"{c}={v:.2f}s" for c, v in sorted(busy.items())
        )
        lines.append(
            f"  [{p.get('process')}] {int(p.get('events', 0))} event(s) "
            f"over {float(p.get('wall_s', 0)):.3f}s wall: {busy_str}"
        )
    overlap = summ.get("overlap")
    if overlap:
        lines.append(
            f"  executor overlap: sweep {overlap.get('sweep_s', 0):.3f}s, "
            f"batch wait {overlap.get('batch_wait_s', 0):.3f}s, "
            f"efficiency {100.0 * overlap.get('overlap_efficiency', 0):.1f}%"
        )
    return lines


def summarize(records):
    """Per-task summary dicts, sorted by task name."""
    by_task = defaultdict(list)
    for rec in records:
        by_task[str(rec.get("task"))].append(rec)
    out = []
    for task in sorted(by_task):
        recs = by_task[task]
        sites: Counter = Counter()
        resolutions: Counter = Counter()
        hosts = set()
        unresolved = []
        n_quarantined = 0
        for r in recs:
            for site, n in (r.get("sites") or {}).items():
                sites[site] += int(n)
            if r.get("quarantined"):
                n_quarantined += 1
            res = r.get("resolution")
            if res:
                resolutions[res] += 1
            elif r.get("resolved"):
                resolutions["recovered"] += 1
            if not r.get("resolved"):
                unresolved.append(r.get("block_id"))
            if r.get("hostname"):
                hosts.add(f"{r['hostname']}:{r.get('pid', '?')}")
        out.append({
            "task": task,
            "n_records": len(recs),
            "sites": dict(sites),
            "resolutions": dict(resolutions),
            "n_quarantined": n_quarantined,
            "unresolved": sorted(
                (b for b in unresolved if b is not None), key=int
            ) + ([None] if None in unresolved else []),
            "hosts": sorted(hosts),
        })
    return out


def format_report(path, version, summaries, io_tasks=None, provenance=None,
                  trace_summary=None, journal_stats=None,
                  scrub_stats=None) -> str:
    lines = [f"failures report: {path} (schema v{version})", ""]
    if not summaries:
        lines.append("no failure records — clean run")
        if io_tasks:
            lines.extend(["", *format_io_metrics(io_tasks, provenance)])
        if trace_summary:
            lines.extend(["", *format_trace_summary(trace_summary)])
        if journal_stats:
            lines.extend(["", *format_journal_stats(journal_stats)])
        if scrub_stats:
            lines.extend(["", *format_scrub_stats(scrub_stats)])
        return "\n".join(lines)
    n_unresolved = sum(len(s["unresolved"]) for s in summaries)
    all_hosts = sorted({h for s in summaries for h in s["hosts"]})
    for s in summaries:
        lines.append(f"[{s['task']}]  {s['n_records']} record(s), "
                     f"{s['n_quarantined']} quarantined")
        if s["sites"]:
            site_str = ", ".join(
                f"{site}={n}" for site, n in sorted(s["sites"].items())
            )
            lines.append(f"  failed attempts by site: {site_str}")
        if s["resolutions"]:
            res_str = ", ".join(
                f"{r}={n}" for r, n in sorted(s["resolutions"].items())
            )
            lines.append(f"  resolutions: {res_str}")
        if s["unresolved"]:
            lines.append(f"  UNRESOLVED blocks: {s['unresolved']}")
        if len(all_hosts) > 1 and s["hosts"]:
            lines.append(f"  recorded by: {', '.join(s['hosts'])}")
        lines.append("")
    verdict = (
        "every failure was absorbed (retry / quarantine / degrade / requeue)"
        if n_unresolved == 0
        else f"{n_unresolved} unit(s) stayed UNRESOLVED — the run raised"
    )
    lines.append(verdict)
    if io_tasks:
        lines.extend(["", *format_io_metrics(io_tasks, provenance)])
    if trace_summary:
        lines.extend(["", *format_trace_summary(trace_summary)])
    if journal_stats:
        lines.extend(["", *format_journal_stats(journal_stats)])
    if scrub_stats:
        lines.extend(["", *format_scrub_stats(scrub_stats)])
    return "\n".join(lines)


def format_lint_report(doc) -> str:
    """Render a ctlint ``--json`` document: per-rule counts, findings
    grouped by file, and the suppression debt."""
    findings = doc.get("findings", []) or []
    counts = doc.get("counts", {}) or {}
    lines = [
        f"ctlint report (schema v{doc.get('version')}): "
        f"{len(findings)} finding(s) in {doc.get('n_files', '?')} file(s)"
    ]
    if counts:
        lines.append(
            "  by rule: " + ", ".join(
                f"{rule}={n}" for rule, n in sorted(counts.items())
            )
        )
    if doc.get("n_suppressed"):
        lines.append(
            f"  suppressed (visible debt): {int(doc['n_suppressed'])}"
        )
    by_file = defaultdict(list)
    for f in findings:
        by_file[str(f.get("file"))].append(f)
    for path in sorted(by_file):
        lines.append("")
        lines.append(f"[{path}]")
        for f in sorted(by_file[path], key=lambda r: int(r.get("line", 0))):
            lines.append(
                f"  {f.get('line')}:{f.get('col')} {f.get('rule')} "
                f"{f.get('message')}"
            )
    if not findings:
        lines.append("  clean — every contract holds")
    return "\n".join(lines)


def run_repo_lint():
    """A fresh ctlint pass over the repo's package (docs/ANALYSIS.md), as
    the linter's own ``--json`` document — or None when the package cannot
    be found/parsed (report consumers treat null as "lint not run")."""
    try:
        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        sys.path.insert(0, repo_root)
        from cluster_tools_tpu.lint.core import findings_to_json, run_lint

        pkg = os.path.join(repo_root, "cluster_tools_tpu")
        findings, stats = run_lint([pkg])
        return findings_to_json(findings, stats)
    except Exception:
        return None


def build_json_report(tmp_folder: str, with_lint: bool = True):
    """The machine-readable run report: every observability plane this run
    produced, in one document (docs/OBSERVABILITY.md)."""
    fpath = os.path.join(tmp_folder, "failures.json")
    error = None
    try:
        _, version, records = load_records(fpath)
    except (OSError, ValueError) as e:
        version, records = None, []
        # only a MISSING manifest is clean (same contract as the text
        # report): a present-but-unparseable one is crash evidence and
        # must surface as an error, not as n_records=0
        if os.path.exists(fpath):
            error = f"torn failures manifest: {e}"
    io_tasks, provenance = load_io_metrics(fpath, with_provenance=True)
    summaries = summarize(records)
    doc = {
        "version": 1,
        "tmp_folder": os.path.abspath(tmp_folder),
        "failures": {
            "schema_version": version,
            "error": error,
            "n_records": len(records),
            "n_unresolved": sum(len(s["unresolved"]) for s in summaries),
            "tasks": summaries,
        },
        "io_metrics": {"tasks": io_tasks, "provenance": provenance},
        "trace": load_trace_summary(fpath) or None,
        # the service mode's durable submission journal (docs/SERVING.md
        # "Durability"): records, replays, quarantines, torn-tail
        # truncations — null for runs without a journal
        "journal": load_journal_stats(fpath),
        # the self-healing plane (docs/SERVING.md "Self-healing"): scrub
        # coverage/findings + verifying-reader + lineage-repair counters
        # — null for runs without a scrubber
        "scrub": load_scrub_stats(fpath),
        "lint": run_repo_lint() if with_lint else None,
    }
    return doc


def main(argv) -> int:
    if len(argv) > 1 and argv[1] == "--lint":
        if len(argv) != 3:
            print(__doc__.strip(), file=sys.stderr)
            return 2
        try:
            raw = (
                sys.stdin.read() if argv[2] == "-"
                else open(argv[2]).read()
            )
            doc = json.loads(raw)
        except (OSError, ValueError) as e:
            print(f"cannot read lint document: {e}", file=sys.stderr)
            return 2
        print(format_lint_report(doc))
        return 1 if doc.get("findings") else 0
    if len(argv) > 1 and argv[1] == "--trace":
        if len(argv) != 3:
            print(__doc__.strip(), file=sys.stderr)
            return 2
        spath = (
            os.path.join(argv[2], "trace_summary.json")
            if os.path.isdir(argv[2])
            else argv[2]
        )
        try:
            with open(spath) as f:
                summ = json.load(f)
        except (OSError, ValueError) as e:
            print(f"cannot read trace summary: {e}", file=sys.stderr)
            return 1
        print("\n".join(format_trace_summary(summ)))
        return 0
    if len(argv) > 1 and argv[1] == "--json":
        args = [a for a in argv[2:] if a != "--no-lint"]
        if len(args) != 1:
            print(__doc__.strip(), file=sys.stderr)
            return 2
        doc = build_json_report(args[0], with_lint="--no-lint" not in argv)
        print(json.dumps(doc, indent=2))
        bad = (
            doc["failures"]["error"]
            or doc["failures"]["n_unresolved"]
            or (doc["lint"] or {}).get("findings")
        )
        return 1 if bad else 0
    if len(argv) != 2:
        print(__doc__.strip(), file=sys.stderr)
        return 2
    fpath = (
        os.path.join(argv[1], "failures.json")
        if os.path.isdir(argv[1])
        else argv[1]
    )
    try:
        path, version, records = load_records(argv[1])
    except (OSError, ValueError) as e:
        # a clean run writes no failures.json but may still have recorded
        # chunk-IO metrics worth a post-mortem.  Only a MISSING manifest is
        # clean — a present-but-unparseable (torn) one is exactly the kind
        # of crash evidence this report exists to surface, and must keep
        # its error + nonzero exit
        io_tasks, provenance = load_io_metrics(fpath, with_provenance=True)
        if io_tasks and not os.path.exists(fpath):
            print("no failures manifest — clean run")
            print("\n".join(format_io_metrics(io_tasks, provenance)))
            trace_summary = load_trace_summary(fpath)
            if trace_summary:
                print()
                print("\n".join(format_trace_summary(trace_summary)))
            return 0
        print(f"cannot read failures manifest: {e}", file=sys.stderr)
        return 1
    io_tasks, provenance = load_io_metrics(path, with_provenance=True)
    print(
        format_report(
            path, version, summarize(records), io_tasks, provenance,
            load_trace_summary(path), load_journal_stats(path),
            load_scrub_stats(path),
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
