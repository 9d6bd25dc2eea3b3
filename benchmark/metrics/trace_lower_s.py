"""Seconds of the traced job spent tracing and lowering programs."""

from benchmark import program_trace


def read(traced, meta):
    return program_trace.union_seconds(traced, meta["spans"])
