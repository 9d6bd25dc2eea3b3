"""Device seconds of the main program's leaf operations under `ws.fill.harvest`."""

import collections
import statistics
import sys

from benchmark import program_trace, reduce_trace


def read(traced, meta):
    ops = program_trace.main_ops(traced, tuple(meta["stages"]))
    if ops is None:
        return None
    leaves = [op for op, stage in ops
              if stage in meta["stages"] and op.opcode not in reduce_trace.CONTAINERS]
    _list_axes([op for op, _ in ops if op.opcode == "while"], leaves)
    return sum(op.dur for op in leaves) or None


def _inside(op, w):
    return w.start <= op.start and op.start + op.dur <= w.start + w.dur


def _list_axes(whiles, leaves):
    """On standard error, for PERF.md section 5: the harvest's loops in
    order, one an axis, each with its trips (how often most of its
    operations ran; 16 would be the whole padded list) and its seconds, then
    what the scope holds outside them (ids, compactions).  A ``while`` event
    carries no scope path of its own in a TPU trace: the loops are those
    that hold the scope's leaves, so an axis without a face, whose loop runs
    no trip, is not listed."""
    loops = [w for w in whiles if any(_inside(op, w) for op in leaves)]
    for i, w in enumerate(loops):
        runs = collections.Counter(op.name for op in leaves if _inside(op, w))
        print(f"[ws_fill_harvest] axis loop {i + 1}: {w.name} "
              f"x{statistics.mode(runs.values())} of 16  {w.dur:.3f}s",
              file=sys.stderr, flush=True)
    if loops:
        total = sum(op.dur for op in leaves)
        looped = sum(w.dur for w in loops)
        print(f"[ws_fill_harvest] loops {looped:.3f}s, outside them "
              f"{total - looped:.3f}s", file=sys.stderr, flush=True)
