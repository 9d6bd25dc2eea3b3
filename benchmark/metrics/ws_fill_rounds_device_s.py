"""Device seconds of the main program's leaf operations under `ws.fill.rounds`."""

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
    _list_rounds([op for op, _ in ops if op.opcode == "while"], leaves)
    return sum(op.dur for op in leaves) or None


def _inside(op, w):
    return w.start <= op.start and op.start + op.dur <= w.start + w.dur


def _list_rounds(whiles, leaves):
    """On standard error, for PERF.md section 5: every round of the loop
    with its seconds and, for each of its inner loops in order, the trips
    (how often most of its operations ran) and the seconds.  A ``while``
    event carries no scope path of its own in a TPU trace: the loops are
    those that hold the scope's leaves, the longest of them the round loop."""
    whiles = [w for w in whiles if any(_inside(op, w) for op in leaves)]
    if not whiles:
        return
    outer = max(whiles, key=lambda w: w.dur)
    inner = [w for w in whiles if w is not outer
             and not any(_inside(w, x) for x in whiles if x is not outer and x is not w)]
    rounds = []
    for w in inner:
        if not rounds or w.name in {x.name for x, _ in rounds[-1]}:
            rounds.append([])
        runs = collections.Counter(op.name for op in leaves if _inside(op, w))
        rounds[-1].append((w, statistics.mode(runs.values())))
    for i, loops in enumerate(rounds):
        end = rounds[i + 1][0][0].start if i + 1 < len(rounds) else outer.start + outer.dur
        print(f"[ws_fill_rounds] round {i + 1}: {end - loops[0][0].start:7.3f}s  "
              + "  ".join(f"{w.name} x{trips} {w.dur:.3f}s" for w, trips in loops),
              file=sys.stderr, flush=True)
