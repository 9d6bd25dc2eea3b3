"""Device seconds of the main program's leaf operations under `step.halo`."""

from benchmark import program_trace


def read(traced, meta):
    return program_trace.stage_seconds(traced, meta["stages"])
