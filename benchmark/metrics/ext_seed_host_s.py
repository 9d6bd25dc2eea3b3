"""Seconds of the traced job inside pass two's host work on external seeds."""

from benchmark import program_trace


def read(traced, meta):
    return program_trace.union_seconds(traced, meta["spans"])
