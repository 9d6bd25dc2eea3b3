"""Seconds of the traced job inside the `task.run` spans of the per-section
watershed's task."""

from benchmark import multicut_trace


def read(traced, meta):
    return multicut_trace.task_seconds(traced, meta["tasks"])
