"""Every chip of a traced job: busy seconds and collective seconds per chip.

``reduce_trace.Reduced`` carries chip 0's operations and the mean of the
chips' busy seconds, which is all a one-chip cell needs.  A cell on several
chips also asks how far the chips lie apart and what the operations that
exist only between them cost, so the readers of ``chip_skew_share`` and
``collective_device_s`` read the traced job's ``.xplane.pb`` again here, with
nothing but JAX, for all of its ``/device:TPU:<n>`` planes.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

from . import program_trace, reduce_trace

#: HLO opcodes of the operations that move data between chips; the
#: asynchronous forms (``-start`` / ``-done``) count with them
COLLECTIVES = ("collective-permute", "all-gather", "all-reduce", "all-to-all",
               "reduce-scatter")


def is_collective(opcode: str) -> bool:
    return opcode.removesuffix("-start").removesuffix("-done") in COLLECTIVES


@functools.lru_cache(maxsize=2)
def _chips(path: str, window: Tuple[float, float], main: Optional[str]) -> Dict[int, dict]:
    import jax

    lo, hi = window
    out: Dict[int, dict] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        m = reduce_trace.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops, runs = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                       for ev in line.events]
            elif line.name == "XLA Modules":
                runs = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events if ev.name == main
                        and (ev.start_ns + ev.duration_ns) * 1e-9 > lo
                        and ev.start_ns * 1e-9 < hi]
        busy = reduce_trace.union(reduce_trace.clip([(s, s + d) for _, s, d in ops], lo, hi))
        collective = sum(
            d for text, s, d in ops
            if lo <= s < hi and any(a <= s < b for a, b in runs)
            and is_collective(reduce_trace.opcode_of(text)))
        out[int(m.group(1))] = dict(busy_s=sum(b - a for a, b in busy),
                                    collective_s=collective, main_runs=len(runs))
    return out


def per_chip(traced: dict) -> Optional[Dict[int, dict]]:
    """chip -> ``busy_s`` (union of its operations inside the traced
    window), ``collective_s`` (device seconds of the main program's
    collective operations on it), ``main_runs`` (executions of the main
    program on it).  None where the traced job left no trace file."""
    path = program_trace.trace_file(traced)
    if path is None:
        return None
    red = traced["trace"]
    return _chips(path, tuple(red.window), reduce_trace.main_module(red))
