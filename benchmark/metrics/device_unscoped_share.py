"""Share of the main program's device time under none of the stage scopes."""

from benchmark import program_trace


def read(traced, meta):
    return program_trace.unscoped_share(traced)
