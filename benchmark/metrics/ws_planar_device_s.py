"""Seconds the per-section watershed's named sweep program held the device
in the traced job."""

from benchmark import two_pass_trace


def read(traced, meta):
    return two_pass_trace.module_seconds(traced, meta["module"])
