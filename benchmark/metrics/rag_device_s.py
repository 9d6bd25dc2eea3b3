"""Device seconds of the leaf operations under the `rag.*` stage scopes."""

from benchmark import multicut_trace


def read(traced, meta):
    ops = multicut_trace.scoped_ops(traced, meta["stages"])
    if ops is None:
        return None
    return sum(op.dur for op, _ in ops) or None
