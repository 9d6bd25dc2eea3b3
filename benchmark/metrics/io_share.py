"""Share of the traced job's wall inside container reads and writes."""


def read(traced, meta):
    job = traced["job"]
    wall = job["t1"] - job["t0"]
    if not traced["io_spans"] or wall <= 0:
        return None
    return 100.0 * traced["io_seconds"] / wall
