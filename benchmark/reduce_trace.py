"""From a profiler trace (``.xplane.pb``) to busy / idle time, time per
device operation, and what the host was doing in the idle gaps.

Reads the trace with nothing but JAX (``jax.profiler.ProfileData``).  What a
TPU trace looks like (read off one by hand, PERF.md section 6): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
execution of a compiled program, named ``jit_<name>(<fingerprint>)``) and
``XLA Ops`` (one event per HLO operation, named by its whole HLO text, so
the opcode and every shape can be read from the name); the host's threads
are lines of ``/host:CPU``, and ``jax.profiler.TraceAnnotation`` spans land
on the line of the thread that opened them.  Times are nanoseconds on one
timeline.  ``python -m benchmark.selfcheck`` checks this file against the
small recorded trace in ``testdata/``.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
#: HLO operations that only hold other operations; their time is their
#: children's, which the line lists too
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Op:
    name: str        # the HLO instruction's name, e.g. "sort.6"
    opcode: str      # e.g. "sort", "fusion", "custom-call"
    text: str        # the whole HLO text of the event
    start: float     # seconds on the trace's timeline
    dur: float       # seconds


@dataclass
class Reduced:
    window: Tuple[float, float]           # seconds on the trace's timeline
    busy_s: float                         # union of device-op intervals, mean over chips
    n_chips: int
    n_device_events: int
    ops: List[Op] = field(default_factory=list)          # chip 0, inside the window
    modules: List[Tuple[str, float, float]] = field(default_factory=list)  # (name, start, dur), chip 0
    host: List[Tuple[str, float, float]] = field(default_factory=list)     # host spans named bench.*

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def opcode_of(text: str) -> str:
    body = text.split(" = ", 1)[-1]
    m = _OPCODE.search(" " + body)
    return m.group(1) if m else "?"


def shapes_bytes(text: str) -> int:
    """Bytes of every array shape written in an HLO text: the result and
    each operand once."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES[dtype]
    return total


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def reduce_file(path: str, window_span: str = "bench.job") -> Reduced:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    host: List[Tuple[str, float, float]] = []
    chips: Dict[int, dict] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = chips.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        chip["ops"].append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        chip["modules"].append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    if not chips:
        raise ValueError(f"{path}: no /device:TPU:<n> plane, so no device in this trace")
    spans = [(s, s + d) for n, s, d in host if n == window_span]
    if spans:
        window = (min(a for a, _ in spans), max(b for _, b in spans))
    else:
        every = [(s, s + d) for c in chips.values() for _, s, d in c["ops"] + c["modules"]]
        window = (min(a for a, _ in every), max(b for _, b in every))
    busy = []
    for c in chips.values():
        merged = union(clip([(s, s + d) for _, s, d in c["ops"]], *window))
        busy.append(sum(b - a for a, b in merged))
    first = chips[min(chips)]
    ops = []
    for text, s, d in first["ops"]:
        if s + d > window[0] and s < window[1]:
            name = text.split(" = ", 1)[0].lstrip("%")
            ops.append(Op(name, opcode_of(text), text, s, d))
    return Reduced(
        window=window, busy_s=sum(busy) / len(busy), n_chips=len(chips),
        n_device_events=sum(len(c["ops"]) for c in chips.values()), ops=ops,
        modules=[m for m in first["modules"] if m[1] + m[2] > window[0] and m[1] < window[1]],
        host=host,
    )


def reduce_dir(trace_dir: str) -> Reduced:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(found[-1])


def main_module(red: Reduced) -> Optional[str]:
    """The compiled program that held the device longest in the window."""
    total: Dict[str, float] = {}
    for name, _, d in red.modules:
        total[name] = total.get(name, 0.0) + d
    return max(total, key=total.get) if total else None


def leaf_ops(red: Reduced) -> List[Op]:
    return [op for op in red.ops if op.opcode not in CONTAINERS]


def breakdown(red: Reduced, traced: dict) -> dict:
    """The ten device operations that took most time (summed by opcode and
    instruction name), and the device's idle time by what the host was
    doing: every idle gap is named by the span that was open through most
    of it (the harness's ``bench.*`` spans and the runtime's own spans,
    innermost first), the gaps of one name are summed, and the ten names
    with most idle time are listed."""
    per: Dict[str, float] = {}
    for op in leaf_ops(red):
        key = f"{op.opcode}:{op.name}"
        per[key] = per.get(key, 0.0) + op.dur
    device_ops = sorted(per.items(), key=lambda kv: -kv[1])[:10]

    merged = union(clip([(op.start, op.start + op.dur) for op in red.ops], *red.window))
    gaps, at = [], red.window[0]
    for a, b in merged + [(red.window[1], red.window[1])]:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)

    # host spans on the trace's timeline: the harness's annotations are
    # there already; the runtime's are on time.monotonic(), which the job's
    # own start ties to the bench.job annotation
    spans = [(n, s, s + d) for n, s, d in red.host if n != "bench.job"]
    job = traced.get("job")
    if job is not None:
        shift = red.window[0] - job["t0"]
        for ev in traced.get("runtime_spans", []):
            if ev.get("ph") == "X":
                spans.append((ev["name"], ev["ts"] + shift, ev["ts"] + ev["dur"] + shift))

    def doing(a: float, b: float) -> str:
        best, best_len = "host: no span open", float("inf")
        for n, s, e in spans:
            # innermost first: the shortest span that covers most of the gap
            if min(b, e) - max(a, s) > 0.5 * (b - a) and e - s < best_len:
                best, best_len = n, e - s
        return best

    idle: Dict[str, float] = {}
    for a, b in gaps:
        name = doing(a, b)
        idle[name] = idle.get(name, 0.0) + (b - a)
    idle_gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps]}
