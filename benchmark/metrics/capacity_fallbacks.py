"""Executions x soft-capacity branches that took the volume-sized fallback."""

from benchmark import work_trace


def read(traced, meta):
    runs = work_trace.executions(traced)
    if not runs:
        return None
    return sum(any(rec.get(name) == 1 for rec in records)
               for _, records in runs for name in meta["fallbacks"])
