"""100 * (1 - busy / window) of the traced job."""


def read(traced, meta):
    red = traced["trace"]
    if red.window_s <= 0 or red.busy_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
