"""Seconds the main program's executions held the device in the traced job."""

from benchmark import reduce_trace


def read(traced, meta):
    red = traced["trace"]
    main = reduce_trace.main_module(red)
    if main is None:
        return None
    return sum(d for name, _, d in red.modules if name == main)
