"""Share of device busy time in sort / scan / gather / scatter operations."""

from benchmark import reduce_trace


def read(traced, meta):
    red = traced["trace"]
    ops = reduce_trace.leaf_ops(red)
    total = sum(op.dur for op in ops)
    if total <= 0:
        return None
    opcodes = set(meta["opcodes"])
    patterns = meta["name_patterns"]
    kinds = ["kind=" + k for k in meta["fusion_kinds"]]
    hit = sum(
        op.dur for op in ops
        if op.opcode in opcodes
        or (op.opcode == "fusion" and (any(p in op.name for p in patterns)
                                       or any(k in op.text for k in kinds)))
    )
    return 100.0 * hit / total
