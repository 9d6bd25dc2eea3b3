"""Share of the traced job's wall before its main program first ran."""

from benchmark import reduce_trace


def read(traced, meta):
    red = traced["trace"]
    main = reduce_trace.main_module(red)
    if main is None or red.window_s <= 0:
        return None
    first = min(s for name, s, _ in red.modules if name == main)
    return 100.0 * max(first - red.window[0], 0.0) / red.window_s
