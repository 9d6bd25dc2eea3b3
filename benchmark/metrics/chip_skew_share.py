"""100 * (busiest chip's busy seconds - least busy chip's) / traced window."""

from benchmark import chips_trace


def read(traced, meta):
    chips = chips_trace.per_chip(traced)
    window_s = traced["trace"].window_s
    if not chips or len(chips) < 2 or window_s <= 0:
        return None
    busy = [c["busy_s"] for c in chips.values()]
    return 100.0 * (max(busy) - min(busy)) / window_s
