"""The work record: what a watershed / CCL device program counted while it ran.

The tiled kernels decide their own cost from the data: loops run a number of
trips read from live counts, branches (``tile_ccl.lane_cond``) pick a
volume-sized fallback when a per-tile table overflows or a capacity tier by
the live count, and every list is a static capacity that a count either fits
or trips.  The counts exist as loop state, as predicate operands, as what
``_compact`` returns and as what ``lane_cond`` says the device ran; this
module is the one table of their names, so that a program can hand them out
beside its labels as one small ``int32`` vector and the host can read them
back by name (docs/OBSERVABILITY.md "The work record").

Inside a program a part of it collects ``{name: int32 scalar}`` under the
constants below and the program's edge packs the whole dict once
(:func:`pack`); a name the program did not count reads ``UNSET`` (-1), which
no reader counts.  Records of programs that run one after another over the
same data (a CCL inside a watershed, the split step's stages) combine with
:func:`merge`.  On the host
:func:`unpack` gives one dict a shard or lane, :func:`total` one dict for a
pass of many lanes, :func:`tripped` the capacities behind a raised overflow
flag.  Nothing here is an option: every program counts always.
"""

from __future__ import annotations

from functools import reduce
from typing import Dict, Iterable, List, Mapping

import numpy as np

UNSET = -1

# -- flow: ops/tile_ws.py::_ws_flow_core ------------------------------------
#: (code, tile) exit incidences handed to the chase (collect_negative_values)
FLOW_EXITS = "flow.exits"
#: the fullest of the six strip families before the value dedup (each is
#: compacted against the exit capacity on its own)
FLOW_EXIT_FAMILY_MAX = "flow.exit_family_max"
#: chase_exits: hops run, chunk trips summed over the hops, chains running at
#: a hop's start summed over the hops, hops of one trip (the tail)
FLOW_CHASE_HOPS = "flow.chase_hops"
FLOW_CHASE_TRIPS = "flow.chase_trips"
FLOW_CHASE_LIVE = "flow.chase_live"
FLOW_CHASE_TAIL_HOPS = "flow.chase_tail_hops"
#: _remap_exits: entries of the fullest tile's remap table, and whether the
#: volume-sized gather ran in the Mosaic kernel's place
FLOW_REMAP_TILE_MAX = "flow.remap_tile_max"
FLOW_REMAP_FALLBACK = "flow.remap_fallback"

# -- fill: ops/tile_ws.py::_ws_fill_core ------------------------------------
#: basin faces found per axis (before the cap)
FILL_FACES_Z = "fill.faces_z"
FILL_FACES_Y = "fill.faces_y"
FILL_FACES_X = "fill.faces_x"
FILL_FACES = (FILL_FACES_Z, FILL_FACES_Y, FILL_FACES_X)
#: dense fill: chunk trips of the three harvest loops together
FILL_HARVEST_TRIPS = "fill.harvest_trips"
#: dense fill: seedless basins the rounds start with
FILL_BASINS = "fill.basins"
#: dense fill: rounds run, chunk trips of a pass summed over the rounds (a
#: round walks its prefix four times), faces live at a round's start summed
#: over the rounds, trips of the closure loop summed over the rounds
FILL_ROUNDS = "fill.rounds"
FILL_ROUND_TRIPS = "fill.round_trips"
FILL_LIVE_FACES = "fill.live_faces"
FILL_CLOSURE_TRIPS = "fill.closure_trips"
#: capacity fill: unique basin adjacencies after the dedup
FILL_ADJACENCIES = "fill.adjacencies"
#: capacity fill with the Mosaic kernels: its own remap tables
FILL_REMAP_TILE_MAX = "fill.remap_tile_max"
FILL_REMAP_FALLBACK = "fill.remap_fallback"

# -- CCL: ops/tile_ccl.py::label_components_tiled ----------------------------
#: the fullest axis's run-deduped face pairs, the unique merged edges
CCL_PAIRS = "ccl.pairs"
CCL_EDGES = "ccl.edges"
CCL_REMAP_TILE_MAX = "ccl.remap_tile_max"
CCL_REMAP_FALLBACK = "ccl.remap_fallback"

# -- seeds: ops/tile_ws.py::_dt_seeds_core (its CCL under seeds.*) ----------
SEEDS_PAIRS = "seeds.pairs"
SEEDS_EDGES = "seeds.edges"
SEEDS_REMAP_TILE_MAX = "seeds.remap_tile_max"
SEEDS_REMAP_FALLBACK = "seeds.remap_fallback"

# -- mesh step: parallel/pipeline.py ----------------------------------------
#: watershed fragments / foreground components of a shard where the step
#: compacts labels (max_labels_per_shard)
STEP_FRAGMENTS = "step.fragments"
STEP_COMPONENTS = "step.components"

# -- branches: ops/tile_ccl.py::lane_cond, at every cond of the kernels -------
#: volume-sized fallback branches (the ``*.remap_fallback`` conds) the device
#: ran, and conds that ran both sides because the lanes of a ``vmap``
#: disagreed; facts of the batch, not of a lane: every lane's record carries
#: the batch's (:func:`branches`)
BRANCH_FALLBACKS_RUN = "branch.fallbacks_run"
BRANCH_BOTH_SIDES = "branch.both_sides"
BRANCH = (BRANCH_FALLBACKS_RUN, BRANCH_BOTH_SIDES)

# -- the overflow flag, by what tripped -------------------------------------
OVER_EXIT = "over.exit"
OVER_FACE = "over.face"
OVER_BASIN = "over.basin"
OVER_ADJ = "over.adj"
OVER_EDGE = "over.edge"
OVER_LABELS = "over.labels"
OVER_HOPS = "over.hops"
OVER_ROUNDS = "over.rounds"
OVER = (OVER_EXIT, OVER_FACE, OVER_BASIN, OVER_ADJ, OVER_EDGE, OVER_LABELS,
        OVER_HOPS, OVER_ROUNDS)

# -- the static capacities the counts are read against ----------------------
CAP_EXIT = "cap.exit"
CAP_FACE = "cap.face"
CAP_BASIN = "cap.basin"
CAP_ADJ = "cap.adj"
CAP_PAIR = "cap.pair"
CAP_EDGE = "cap.edge"
CAP_SEED_PAIR = "cap.seed_pair"
CAP_SEED_EDGE = "cap.seed_edge"
CAP_TABLE = "cap.table"
CAP_LABELS = "cap.labels"

#: the vector's order
NAMES = (
    FLOW_EXITS, FLOW_EXIT_FAMILY_MAX, FLOW_CHASE_HOPS, FLOW_CHASE_TRIPS,
    FLOW_CHASE_LIVE, FLOW_CHASE_TAIL_HOPS, FLOW_REMAP_TILE_MAX,
    FLOW_REMAP_FALLBACK,
    *FILL_FACES, FILL_HARVEST_TRIPS, FILL_BASINS, FILL_ROUNDS,
    FILL_ROUND_TRIPS, FILL_LIVE_FACES, FILL_CLOSURE_TRIPS,
    FILL_ADJACENCIES, FILL_REMAP_TILE_MAX, FILL_REMAP_FALLBACK,
    CCL_PAIRS, CCL_EDGES, CCL_REMAP_TILE_MAX, CCL_REMAP_FALLBACK,
    SEEDS_PAIRS, SEEDS_EDGES, SEEDS_REMAP_TILE_MAX, SEEDS_REMAP_FALLBACK,
    STEP_FRAGMENTS, STEP_COMPONENTS, *BRANCH,
    *OVER,
    CAP_EXIT, CAP_FACE, CAP_BASIN, CAP_ADJ, CAP_PAIR, CAP_EDGE, CAP_SEED_PAIR,
    CAP_SEED_EDGE, CAP_TABLE, CAP_LABELS,
)

#: count -> the capacity it is read against, where it has one.  A hard
#: capacity raises the overflow flag when passed; the table capacity is soft:
#: passing it selects a ``*_fallback`` branch (1 where the lane's predicate
#: asks for the volume-sized gather; under ``vmap`` the device runs it for
#: the batch where any lane asks, which ``branch.fallbacks_run`` counts; -1
#: where the program has no such branch, ``impl="xla"``) and the labels stay
#: exact.
CAPACITY = {
    FLOW_EXITS: CAP_EXIT, FLOW_EXIT_FAMILY_MAX: CAP_EXIT,
    FILL_FACES_Z: CAP_FACE, FILL_FACES_Y: CAP_FACE, FILL_FACES_X: CAP_FACE,
    FILL_BASINS: CAP_BASIN, FILL_ADJACENCIES: CAP_ADJ,
    CCL_PAIRS: CAP_PAIR, CCL_EDGES: CAP_EDGE,
    SEEDS_PAIRS: CAP_SEED_PAIR, SEEDS_EDGES: CAP_SEED_EDGE,
    STEP_FRAGMENTS: CAP_LABELS, STEP_COMPONENTS: CAP_LABELS,
    FLOW_REMAP_TILE_MAX: CAP_TABLE, FILL_REMAP_TILE_MAX: CAP_TABLE,
    CCL_REMAP_TILE_MAX: CAP_TABLE, SEEDS_REMAP_TILE_MAX: CAP_TABLE,
}

#: sums that could pass 2**31 at the largest capacities (256 hops of 2**24
#: slots) are carried in the vector in units of this many slots, every hop
#: or round rounded up; :func:`unpack` gives slots again (Python ints)
UNITS = {FLOW_CHASE_LIVE: 16, FILL_LIVE_FACES: 16}

#: what the seed CCL's counts are called in a watershed program's record
SEED_CCL = {
    CCL_PAIRS: SEEDS_PAIRS, CCL_EDGES: SEEDS_EDGES,
    CCL_REMAP_TILE_MAX: SEEDS_REMAP_TILE_MAX,
    CCL_REMAP_FALLBACK: SEEDS_REMAP_FALLBACK,
    CAP_PAIR: CAP_SEED_PAIR, CAP_EDGE: CAP_SEED_EDGE,
}

#: a tripped ``over.*`` bit -> (the capacity's name as the kernels' keyword
#: and the blockwise tasks' option, the counts read against it: their sizes
#: are ``CAPACITY``'s, and a loop bound has none)
OVERFLOWS = {
    OVER_EXIT: ("exit_cap", (FLOW_EXITS, FLOW_EXIT_FAMILY_MAX)),
    OVER_FACE: ("face_cap (fill_cap in the capacity fill)", FILL_FACES),
    OVER_BASIN: ("basin_cap", (FILL_BASINS,)),
    OVER_ADJ: ("adj_cap", (FILL_ADJACENCIES,)),
    OVER_EDGE: ("pair_cap / edge_cap",
                (CCL_PAIRS, CCL_EDGES, SEEDS_PAIRS, SEEDS_EDGES)),
    OVER_LABELS: ("max_labels_per_shard", (STEP_FRAGMENTS, STEP_COMPONENTS)),
    OVER_HOPS: ("max_hops of the exit chase", (FLOW_CHASE_HOPS,)),
    OVER_ROUNDS: ("fill_rounds / the union-find's round bound",
                  (FILL_ROUNDS,)),
}
#: a bit that something other than its capacity can raise: what that is, for
#: :func:`tripped` to say where every count fits
NOT_THE_CAPACITY = {
    OVER_BASIN: "a basin code whose terminal voxel does not hold it (the "
                "dense fill gives such a code no id); no capacity mends "
                "that: use fill_mode=capacity",
}

#: combined over lanes by the largest, not the sum
_BY_MAX = frozenset(
    n for n in NAMES if n.startswith("cap.") or n.endswith("_tile_max")
    or n == FLOW_EXIT_FAMILY_MAX or n in BRANCH
)


def pack(counts: Mapping[str, object]):
    """``{name: scalar}`` -> ``int32[len(NAMES)]`` inside a program; a name
    not given reads ``UNSET``.  A name outside the table is a bug."""
    import jax.numpy as jnp

    unknown = set(counts) - set(NAMES)
    if unknown:
        raise KeyError(f"not in work.NAMES: {sorted(unknown)}")
    return jnp.stack([
        jnp.asarray(counts.get(name, UNSET)).astype(jnp.int32).reshape(())
        for name in NAMES
    ])


def join(*parts: Mapping[str, object]) -> Dict[str, object]:
    """The parts of one program's record as one dict, before :func:`pack`:
    an ``over.*`` bit that several parts raise is their OR, the ``branch.*``
    counts of several parts are added, every other name is counted by one
    part (a capacity given twice is the same)."""
    out: Dict[str, object] = {}
    for part in parts:
        for name, v in part.items():
            if name in OVER:
                v = v > 0
                if name in out:
                    v = out[name] | v
            elif name in BRANCH and name in out:
                v = out[name] + v
            out[name] = v
    return out


def branches(ran, fallback: bool = False) -> Dict[str, object]:
    """One cond's part of the record from what ``lane_cond`` says the device
    ran, ``(true side, false side)``: whether it ran both, and, for a
    ``fallback`` cond (its true side the volume-sized gather), whether it
    ran that."""
    import jax.numpy as jnp

    took, left = ran
    counts = {BRANCH_BOTH_SIDES: (took & left).astype(jnp.int32)}
    if fallback:
        counts[BRANCH_FALLBACKS_RUN] = took.astype(jnp.int32)
    return counts


def merge(*records):
    """Records of programs run one after another over the same data: a name
    is counted by one of them, the others hold ``UNSET`` there; the
    ``branch.*`` counts of those that made them are added."""
    import jax.numpy as jnp

    added = np.array([name in BRANCH for name in NAMES])

    def two(a, b):
        both = added & (a != UNSET) & (b != UNSET)
        return jnp.where(both, a + b, jnp.maximum(a, b))

    return reduce(two, records)


def as_seed_ccl(record):
    """A CCL program's record as the seed CCL's part of a watershed
    program's: its counts and capacities under their ``SEED_CCL`` names, the
    ``ccl.*`` names left for the foreground's CCL to count, its ``over.*``
    bits as they are."""
    import jax.numpy as jnp

    source = {new: old for old, new in SEED_CCL.items()}
    kept = np.array([name not in SEED_CCL for name in NAMES])
    index = np.array([NAMES.index(source.get(name, name)) for name in NAMES])
    return jnp.where(kept, record[index], UNSET)


def scaled(count, name: str):
    """``count`` in the unit the vector carries ``name`` in, rounded up."""
    unit = UNITS[name]
    return (count + (unit - 1)) // unit


def any_over(counts: Mapping[str, object]):
    """The single overflow flag: the OR of the ``over.*`` bits given."""
    flag = None
    for name in OVER:
        if name in counts:
            bit = counts[name] > 0
            flag = bit if flag is None else flag | bit
    return flag


def unpack(array) -> List[Dict[str, int]]:
    """A record, or any stack of them (``(dp, shards, K)`` from the mesh
    step, ``(lanes, K)`` from a sweep), as dicts in row order; sums carried
    in units are slots again."""
    rows = np.asarray(array).reshape(-1, len(NAMES))
    out = []
    for row in rows:
        rec = {name: int(v) for name, v in zip(NAMES, row)}
        for name, unit in UNITS.items():
            if rec[name] > 0:
                rec[name] *= unit
        out.append(rec)
    return out


def total(rows: Iterable[Mapping[str, int]]) -> Dict[str, int]:
    """One dict for many lanes: counts and flags summed (a flag's sum is the
    number of lanes it was raised in), capacities and per-tile maxima by the
    largest; ``UNSET`` entries are left out and a name no lane counted stays
    ``UNSET``."""
    out = {name: UNSET for name in NAMES}
    for rec in rows:
        for name in NAMES:
            v = rec.get(name, UNSET)
            if v == UNSET:
                continue
            if out[name] == UNSET:
                out[name] = v
            elif name in _BY_MAX:
                out[name] = max(out[name], v)
            else:
                out[name] += v
    return out


def tripped(rec: Mapping[str, int]) -> List[str]:
    """The capacities behind a record's raised overflow bits, one line each:
    which, its size, the count that passed it and the option that raises it
    (or, where every count fits, what else raises that bit)."""
    lines = []
    for bit, (option, counts) in OVERFLOWS.items():
        if rec.get(bit, 0) <= 0:
            continue
        counted = [n for n in counts if rec.get(n, UNSET) != UNSET]
        seen = ", ".join(f"{n} = {rec[n]}" for n in counted)
        sizes = sorted({CAPACITY[n] for n in counts if n in CAPACITY})
        size = ", ".join(f"{c} = {rec[c]}" for c in sizes
                         if rec.get(c, UNSET) != UNSET)
        fits = bit in NOT_THE_CAPACITY and all(
            rec[n] <= rec.get(CAPACITY[n], UNSET) for n in counted)
        lines.append(f"{bit}: {seen or 'no count'}"
                     + (f" against {size}" if size else "")
                     + (f"; {NOT_THE_CAPACITY[bit]}" if fits
                        else f"; raise {option}"))
    return lines
