"""The Mosaic kernels' share of their bandwidth roofline.

Least time = bytes each call must move once (its result and each operand,
from the shapes in its HLO text) over the chip's HBM bandwidth; share =
least time over the summed device time of those calls.  Finds nothing to
read where no Mosaic custom call ran, and then returns nothing.
"""

from benchmark import reduce_trace


def kernel_bytes(text: str) -> int:
    # result and operands only: the attributes after the operand list repeat
    # the operands' shapes (operand_layout_constraints)
    return reduce_trace.shapes_bytes(text.split("), custom_call_target=", 1)[0])


def read(traced, meta):
    red = traced["trace"]
    target = meta["custom_call_target"]
    calls = [op for op in red.ops
             if op.opcode == "custom-call" and target in op.text]
    busy = sum(op.dur for op in calls)
    if not calls or busy <= 0:
        return None
    least = sum(kernel_bytes(op.text) for op in calls) / traced["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / busy
