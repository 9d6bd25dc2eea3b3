"""Share of the device's idle time that no phase span of the program covers."""

from benchmark import program_trace, reduce_trace


def read(traced, meta):
    red = traced["trace"]
    phases = [(a, b) for name, a, b, _, _ in program_trace.host_spans(traced)
              if name not in meta["envelopes"]]
    gaps = program_trace.idle_gaps(red)
    idle = sum(b - a for a, b in gaps)
    if not phases or idle <= 0:
        return None
    open_ = reduce_trace.union(phases)
    covered = sum(b - a for lo, hi in gaps for a, b in reduce_trace.clip(open_, lo, hi))
    return 100.0 * (idle - covered) / idle
