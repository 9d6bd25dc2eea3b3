"""The RAG scan's share of its bandwidth roofline.

Least time = the bytes the scans must move once over the chip's HBM
bandwidth; share = least time over the device seconds of the leaf operations
under ``rag.scan``.  Returns nothing where no such operation ran or the
program recorded no block span to take the shapes from.
"""

from benchmark import multicut_trace, program_trace


def scan_bytes(shape, inner, with_values: bool) -> int:
    """Compulsory bytes of one block's scan (``ops/rag.py``): the block's
    labels (int32, densified) and values (float32) read once, and one
    (lo, hi) int32 pair, with values a float32 besides, written for every
    adjacent voxel pair the block owns: along an axis the inner extent plus
    the upper halo plane where there is one, the inner extent across."""
    n = 1
    for s in shape:
        n *= s
    pairs = 0
    for axis in range(len(shape)):
        along = min(inner[axis] + 1, shape[axis]) - 1
        across = 1
        for d in range(len(shape)):
            if d != axis:
                across *= inner[d]
        pairs += along * across
    per_voxel, per_pair = (8, 12) if with_values else (4, 8)
    return n * per_voxel + pairs * per_pair


def read(traced, meta):
    ops = multicut_trace.scoped_ops(traced, [meta["stage"]])
    blocks = [(args, meta["spans"][name])
              for name, _, _, _, args in program_trace.job_spans(traced)
              if name in meta["spans"] and "shape" in args and "inner" in args]
    if ops is None or not blocks:
        return None
    busy = sum(op.dur for op, _ in ops)
    if busy <= 0:
        return None
    total = sum(scan_bytes(a["shape"], a["inner"], with_values)
                for a, with_values in blocks)
    return 100.0 * total / traced["peaks"]["hbm_bytes_per_s"] / busy
