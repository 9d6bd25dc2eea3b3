"""Device seconds of the main program's leaf operations under `ws.flow.chase`."""

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
    _list_hops([op for op, _ in ops if op.opcode == "while"], leaves)
    return sum(op.dur for op in leaves) or None


def _inside(op, w):
    return w.start <= op.start and op.start + op.dur <= w.start + w.dur


def _say(line):
    print(f"[ws_flow_chase] {line}", file=sys.stderr, flush=True)


def _list_hops(whiles, leaves):
    """On standard error, for PERF.md section 5: every loop of the scope
    that lies in no other (the chase's hop loop; a program with capacity
    tiers under a ``vmap`` runs one a tier) with its hops and seconds; under
    it every hop with its trips (how often most of the operations of its
    trip loop ran; 16 would be the whole buffer) and seconds, where the hops
    are loops of trips; then what the scope holds outside the loops.  A
    ``while`` event carries no scope path of its own in a TPU trace: the
    loops are those that hold the scope's leaves, a hop's trip loop the one
    directly inside the hop loop that takes most of its time."""
    loops = [w for w in whiles if any(_inside(op, w) for op in leaves)]
    if not loops:
        return

    def directly_in(w):
        inner = [x for x in loops if x is not w and _inside(x, w)]
        return [x for x in inner
                if not any(_inside(x, y) for y in inner if y is not x)]

    def runs(w):
        """How often most of the leaves ran that lie in ``w`` itself."""
        inner = directly_in(w)
        counts = collections.Counter(
            op.name for op in leaves
            if _inside(op, w) and not any(_inside(op, x) for x in inner))
        return statistics.mode(counts.values()) if counts else 0

    outer = [w for w in loops if not any(_inside(w, x) for x in loops if x is not w)]
    for i, w in enumerate(outer):
        by_name = collections.defaultdict(list)
        for x in directly_in(w):
            by_name[x.name].append(x)
        hops = max(by_name.values(), key=lambda xs: sum(x.dur for x in xs), default=[])
        if not hops:
            n = runs(w)
            _say(f"loop {i + 1}: {w.name} {n} hops at the full width  {w.dur:.3f}s"
                 f"  ({w.dur / max(n, 1):.4f}s a hop)")
            continue
        _say(f"loop {i + 1}: {w.name} {len(hops)} hops  {w.dur:.3f}s  "
             f"{sum(runs(x) for x in hops)} trips")
        for j, x in enumerate(hops):
            end = hops[j + 1].start if j + 1 < len(hops) else w.start + w.dur
            _say(f"  hop {j + 1}: {x.name} x{runs(x)} of 16  {end - x.start:.3f}s")
    total = sum(op.dur for op in leaves)
    looped = sum(op.dur for op in leaves if any(_inside(op, w) for w in outer))
    _say(f"loops {looped:.3f}s, outside them {total - looped:.3f}s")
