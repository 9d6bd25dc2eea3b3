"""Share of the traced job's wall inside the program's own storage spans."""

from benchmark import program_trace


def read(traced, meta):
    job = traced["job"]
    wall = job["t1"] - job["t0"]
    inside = program_trace.union_seconds(traced, meta["spans"])
    if inside is None or wall <= 0:
        return None
    return 100.0 * inside / wall
