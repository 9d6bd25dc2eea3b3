"""Seconds of the traced job in the backend's compile-or-load."""

from benchmark import program_trace


def read(traced, meta):
    return program_trace.union_seconds(traced, meta["spans"])
