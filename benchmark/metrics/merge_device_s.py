"""Device seconds of the main program's leaf operations under `ccl.merge`, `step.globalize` or `step.count`."""

from benchmark import program_trace


def read(traced, meta):
    return program_trace.stage_seconds(traced, meta["stages"])
