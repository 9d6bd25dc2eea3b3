"""Device seconds of the main program's collective operations, mean over chips."""

from benchmark import chips_trace


def read(traced, meta):
    chips = chips_trace.per_chip(traced)
    if not chips:
        return None
    total = sum(c["collective_s"] for c in chips.values())
    return total / len(chips) if total > 0 else None
