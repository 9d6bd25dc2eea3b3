"""What the program says about itself, read beside the reduced trace.

The program names its stages on the device (``jax.named_scope``: the names
in ``STAGES``) and its phases on the host (``runtime/trace.py`` spans, which
also open a ``jax.profiler.TraceAnnotation`` each).  Both reach the
profiler's ``.xplane.pb``; neither reaches ``jax.profiler.ProfileData``,
which shows an event's own stats only.  A device operation's scope path is
the stat ``tf_op`` on its event *metadata*, so this module reads the file's
wire format itself: standard library only, no schema, the few fields named
below.  ``python -m pytest tests/test_program_trace.py`` checks it against
``testdata/small.xplane.pb``.

Readers of ``metrics/`` that use this module return nothing where the
program carries no stage name or recorded no span (a program from before
the names, ``selfcheck``'s spanless ``traced``).
"""

from __future__ import annotations

import functools
import glob
import os
import sys
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from . import reduce_trace

#: the program's stage scopes, outermost level.  ``ws.flow.*`` and
#: ``ws.fill.*`` lie inside ``ws.flow`` / ``ws.fill``; seed CCL lies inside
#: ``ws.seeds`` and counts as seeds, the stitch's merge inside ``step.stitch``
STAGES = ("step.halo", "step.globalize", "step.stitch", "step.count",
          "edt", "ws.seeds", "ws.flow", "ws.fill", "ccl.tile", "ccl.merge")

Span = Tuple[str, float, float, int, dict]   # name, start, end, thread, args


def trace_file(traced: dict) -> Optional[str]:
    """The traced job's ``.xplane.pb``.  Leans on ``run.py``'s layout: the
    job's ``tmp`` is ``<work>/jobs/<tag>`` and the profile goes to
    ``<work>/profile``.  Handing the path over in ``traced`` is a
    ``benchmark`` PR's edit (PERF.md section 7); until then, None where the
    job has no ``tmp`` (``selfcheck``) or no profile lies there."""
    tmp = (traced.get("job") or {}).get("tmp")
    if not tmp:
        return None
    work = os.path.dirname(os.path.dirname(tmp))
    found = sorted(glob.glob(os.path.join(work, "profile", "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


# --------------------------------------------------------------------------
# the XSpace wire format, as far as it is read here
#   XSpace.planes = 1
#   XPlane: name 2, lines 3, event_metadata 4 (map), stat_metadata 5 (map)
#   XLine: name 2, timestamp_ns 3, events 4
#   XEvent: metadata_id 1, offset_ps 2, duration_ps 3
#   XEventMetadata: name 2, stats 5;  XStatMetadata: name 2
#   XStat: metadata_id 1, str_value 5, ref_value 7 (a stat_metadata's name)
# --------------------------------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace message")


def _map_entry(buf) -> Tuple[int, object]:
    key, value = 0, b""
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


@functools.lru_cache(maxsize=4)
def _read(path: str) -> dict:
    """One pass over the file: ``ops`` maps a device operation's HLO text
    (its event metadata's name, which is what ``reduce_trace.Op.text``
    holds) to its ``tf_op`` and ``source`` stats; ``host`` lists the events
    of ``/host:CPU`` as (name, start s, duration s), on the clock
    ``reduce_trace`` uses (``ProfileData.start_ns``)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    ops: Dict[str, Dict[str, str]] = {}
    host: List[Tuple[str, float, float]] = []
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, lines, events_md, stats_md = "", [], [], {}
        for field, v in _fields(plane):
            if field == 2:
                name = _text(v)
            elif field == 3:
                lines.append(v)
            elif field == 4:
                events_md.append(_map_entry(v))
            elif field == 5:
                key, md = _map_entry(v)
                stats_md[key] = next(
                    (_text(x) for f2, x in _fields(md) if f2 == 2), "")
        if reduce_trace.DEVICE_PLANE.match(name):
            for _, md in events_md:
                text, stats = "", {}
                for f2, x in _fields(md):
                    if f2 == 2:
                        text = _text(x)
                    elif f2 == 5:
                        stat = dict(_fields(x))
                        key = stats_md.get(stat.get(1))
                        if key in ("tf_op", "source"):
                            stats[key] = (_text(stat[5]) if 5 in stat
                                          else stats_md.get(stat.get(7), ""))
                if stats:
                    ops[text] = stats
        elif name == "/host:CPU":
            names = {key: next((_text(x) for f2, x in _fields(md) if f2 == 2), "")
                     for key, md in events_md}
            for line in lines:
                t0_ns, events = 0, []
                for f2, x in _fields(line):
                    if f2 == 3:
                        t0_ns = x
                    elif f2 == 4:
                        events.append(x)
                for ev in events:
                    e = dict(_fields(ev))
                    host.append((names.get(e.get(1), ""),
                                 (t0_ns + e.get(2, 0) * 1e-3) * 1e-9,
                                 e.get(3, 0) * 1e-12))
    return {"ops": ops, "host": host}


def op_scopes(path: str) -> Dict[str, str]:
    """HLO text of a device operation -> its ``tf_op``: the scope path of
    the instruction (``jit(f)/jit(sort)/sort`` for ``%sort.6`` in the small
    trace), without the ``:<type>`` tail.  Keyed by the whole text and not
    by the instruction's name, which two programs of one trace may share.
    Parsed once per file and kept."""
    return {text: stats["tf_op"].rsplit(":", 1)[0]
            for text, stats in _read(path)["ops"].items() if "tf_op" in stats}


def stage_of(tf_op: Optional[str], stages: Sequence[str] = STAGES) -> Optional[str]:
    """The outermost component of a scope path that is one of ``stages``."""
    for part in (tf_op or "").split("/"):
        if part in stages:
            return part
    return None


# --------------------------------------------------------------------------
# the main program's device operations, by stage
# --------------------------------------------------------------------------


def main_ops(traced: dict, stages: Sequence[str] = STAGES
             ) -> Optional[List[Tuple[reduce_trace.Op, Optional[str]]]]:
    """(operation, stage) for every operation of the main program, leaves
    and containers, by start.  A leaf whose ``tf_op`` names no stage (XLA's
    expanders drop the path) inherits the stage of the innermost ``while`` /
    ``call`` / ``conditional`` event that contains it.  None where there is
    no trace file, no main program, or no stage name anywhere in it."""
    path = trace_file(traced)
    red = traced["trace"]
    main = reduce_trace.main_module(red)
    if path is None or main is None:
        return None
    key = (path, id(red), tuple(stages))
    if _main_ops_kept.get("key") == key:
        return _main_ops_kept["value"]   # five readers ask for the same
    _main_ops_kept.update(key=key, value=None)
    scopes = op_scopes(path)
    runs = [(s, s + d) for name, s, d in red.modules if name == main]
    mine = sorted((op for op in red.ops
                   if any(a <= op.start < b for a, b in runs)),
                  key=lambda op: (op.start, -op.dur))
    out, open_ = [], []   # open_: (end, stage) of the containers around
    for op in mine:
        while open_ and open_[-1][0] <= op.start:
            open_.pop()
        stage = (stage_of(scopes.get(op.text), stages)
                 or (open_[-1][1] if open_ else None))
        out.append((op, stage))
        if op.opcode in reduce_trace.CONTAINERS:
            open_.append((op.start + op.dur, stage))
    if not any(stage for _, stage in out):
        return None
    _describe(path, out)
    _main_ops_kept["value"] = out
    return out


_main_ops_kept: dict = {}


def stage_seconds(traced: dict, stages: Sequence[str]) -> Optional[float]:
    """Device seconds of the main program's leaf operations under ``stages``."""
    ops = main_ops(traced)
    if ops is None:
        return None
    total = sum(op.dur for op, stage in ops
                if stage in stages and op.opcode not in reduce_trace.CONTAINERS)
    return total or None


def unscoped_share(traced: dict) -> Optional[float]:
    """Share (%) of the main program's leaf-operation time under no stage."""
    ops = main_ops(traced)
    if ops is None:
        return None
    leaves = [(op, stage) for op, stage in ops
              if op.opcode not in reduce_trace.CONTAINERS]
    total = sum(op.dur for op, _ in leaves)
    if total <= 0:
        return None
    return 100.0 * sum(op.dur for op, stage in leaves if stage is None) / total


_described = set()


def _describe(path: str, ops) -> None:
    """Once per trace file, on standard error: the operations that took
    most device time with their stage, scope path and source line, and every
    unscoped one over half a second.  PERF.md section 5 is written from
    these lines."""
    if path in _described:
        return
    _described.add(path)
    stats = _read(path)["ops"]
    per: Dict[str, list] = {}
    for op, stage in ops:
        if op.opcode in reduce_trace.CONTAINERS:
            continue
        row = per.setdefault(f"{op.opcode}:{op.name}", [0.0, 0, stage, op.text])
        row[0] += op.dur
        row[1] += 1
    rows = sorted(per.items(), key=lambda kv: -kv[1][0])
    shown = rows[:16] + [r for r in rows[16:] if r[1][2] is None and r[1][0] > 0.5]
    for key, (secs, n, stage, text) in shown:
        s = stats.get(text, {})
        print(f"[program_trace] {secs:8.3f}s x{n:<6} {key:<28} stage={stage} "
              f"tf_op={s.get('tf_op', '')[-110:]} source={s.get('source', '')}",
              file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# the program's host spans, on the trace's clock
# --------------------------------------------------------------------------


def job_spans(traced: dict) -> List[Span]:
    """The program's spans (``traced["runtime_spans"]``) that lie inside
    the traced job, on the program's own monotonic clock."""
    job = traced.get("job") or {}
    lo, hi = job.get("t0", float("-inf")), job.get("t1", float("inf"))
    return [(ev["name"], ev["ts"], ev["ts"] + ev["dur"], ev.get("tid", 0),
             ev.get("args") or {})
            for ev in traced.get("runtime_spans") or []
            if ev.get("ph") == "X" and ev["ts"] >= lo and ev["ts"] + ev["dur"] <= hi]


def union_seconds(traced: dict, names: Sequence[str]) -> Optional[float]:
    """Seconds of the traced job inside spans of these names (their union,
    over threads and nesting).  None where the program recorded none."""
    found = [(a, b) for name, a, b, _, _ in job_spans(traced) if name in names]
    if not found:
        return None
    return sum(b - a for a, b in reduce_trace.union(found))


def clock_shift(traced: dict) -> Optional[float]:
    """Seconds to add to the program's monotonic clock to land on the
    trace's: from the first ``task.run`` annotation in the file's
    ``/host:CPU`` plane and the ring's first ``task.run`` (the program opens
    both within microseconds); the harness's tie, ``bench.job`` starts at
    the job's ``t0``, where there is no annotation.  Says on standard error
    how far the two ties lie apart."""
    job = traced.get("job") or {}
    red = traced["trace"]
    tie = red.window[0] - job["t0"] if "t0" in job else None
    path = trace_file(traced)
    ring = sorted(a for name, a, _, _, _ in job_spans(traced) if name == "task.run")
    if path is None or not ring:
        return tie
    marks = sorted(s for name, s, _ in _read(path)["host"]
                   if name == "task.run" and s >= red.window[0])
    if not marks:
        return tie
    shift = marks[0] - ring[0]
    if tie is not None and path not in _described_ties:
        _described_ties.add(path)
        print(f"[program_trace] clock: task.run annotation - ring = {shift:.6f}s, "
              f"bench.job - job t0 = {tie:.6f}s, the harness's tie is off by "
              f"{(tie - shift) * 1e3:.3f} ms", file=sys.stderr, flush=True)
        per: Dict[str, list] = {}
        for name, a, b, _, _ in job_spans(traced):
            row = per.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] += b - a
        for name, (n, secs) in sorted(per.items(), key=lambda kv: -kv[1][1]):
            print(f"[program_trace] span {name:<22} x{n:<4} {secs:9.3f}s",
                  file=sys.stderr, flush=True)
    return shift


_described_ties = set()


def host_spans(traced: dict) -> List[Span]:
    """:func:`job_spans` on the trace's clock; ``[]`` where the program
    recorded none."""
    spans = job_spans(traced)
    shift = clock_shift(traced) if spans else None
    if shift is None:
        return []
    return [(name, a + shift, b + shift, tid, args) for name, a, b, tid, args in spans]


def idle_gaps(red: reduce_trace.Reduced) -> List[Tuple[float, float]]:
    """The traced window less the union of the device's operations."""
    busy = reduce_trace.union(reduce_trace.clip(
        [(op.start, op.start + op.dur) for op in red.ops], *red.window))
    gaps, at = [], red.window[0]
    for a, b in busy + [(red.window[1], red.window[1])]:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    return gaps
