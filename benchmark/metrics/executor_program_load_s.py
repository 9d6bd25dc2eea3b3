"""Seconds of the traced job spent reading the executor's sweep programs
back from the step store."""

from benchmark import program_trace


def read(traced, meta):
    return program_trace.union_seconds(traced, meta["spans"])
