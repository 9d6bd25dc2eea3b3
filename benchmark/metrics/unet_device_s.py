"""Device seconds of the network's levels in the traced job's main program."""

import sys

from benchmark import program_trace, reduce_trace


def scopes(config: dict) -> tuple:
    """The level scopes of the configuration's network."""
    levels = int(config["params"]["model"]["num_levels"])
    return (tuple(f"unet.enc{l}" for l in range(levels))
            + tuple(f"unet.dec{l}" for l in range(levels - 1)) + ("unet.head",))


def read(traced, meta):
    names = tuple(s for s in scopes(traced["spec"]["config"]) if s.startswith(meta["prefix"]))
    ops = program_trace.main_ops(traced, names)
    if ops is None:
        return None
    per = dict.fromkeys(names + (None,), 0.0)
    for op, stage in ops:
        if op.opcode not in reduce_trace.CONTAINERS:
            per[stage] += op.dur
    for name, secs in per.items():
        print(f"[unet_device_s] {name or 'no scope'}: {secs:.6f} s", file=sys.stderr, flush=True)
    return sum(secs for name, secs in per.items() if name) or None
