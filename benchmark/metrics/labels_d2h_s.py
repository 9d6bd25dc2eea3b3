"""Seconds the label volumes take from the device to uint64 on the host."""

from benchmark import program_trace


def read(traced, meta):
    return program_trace.union_seconds(traced, meta["spans"])
