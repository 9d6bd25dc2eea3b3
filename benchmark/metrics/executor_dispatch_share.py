"""Share of the traced job's wall inside the executor's dispatch spans."""

from benchmark import run


def read(traced, meta):
    # the same reading as io_span_share, of the spans this metric's file names
    return run.load_reader("io_span_share").read(traced, meta)
