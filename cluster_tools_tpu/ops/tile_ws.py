"""Two-level seeded watershed: in-tile pointer flow + small basin graphs.

Round-2's ``seeded_watershed`` (ops/watershed.py) resolves the steepest-
descent pointer forest with full-volume pointer jumping and grows labels into
unseeded basins one voxel ring per iteration — both dominated by the TPU's
~165M elem/s random-gather rate (see ops/tile_ccl.py for the measurements).
This module keeps the exact same *descent semantics* (lex-min ``(height,
flat_index)`` over the closed neighborhood — the reference's
``vigra.watershedsNew`` per-block behavior, SURVEY.md §2a "watershed") but
restructures the resolution:

1. **Descent directions** (dense XLA): each voxel stores a 3-bit code for
   which neighbor it drains to — no pointer table, no gathers.
2. **In-tile flow** (``pallas_kernels.tile_ws_propagate_pallas``): labels
   flow along the pointer forest *inside* (16, 16, 128) VMEM tiles as dense
   select/shift steps to a fixpoint.  Each voxel ends with its basin's seed
   label, the code of its unseeded in-tile terminal, or an *exit code*
   naming the voxel its path leaves the tile through.
3. **Exit chase** (XLA, small): unique exit codes are collected from tile
   boundary strips (capacity-compacted), then chased across tiles, each
   code's chain alone, over a compacted list of the chains still running
   (:func:`chase_exits`): a hop costs what its live chains cost.  Basins
   are object-scale, so most chains are a few hops and the long tail is a
   handful of chains.
4. **Apply**: per-tile value-remap tables (the ops/tile_ccl machinery) or a
   gather fallback.
5. **Unseeded-basin fill**: instead of ring-growing, basins without seeds
   merge into their neighbor across the *lowest saddle* (Boruvka rounds) —
   minimum-spanning-forest watershed semantics, strictly closer to
   priority-flood than the old relaxation.  Two machines compute it
   (``CT_FILL_MODE``, default ``auto`` = substrate-aware): ``dense``
   (auto on cpu only) runs sort-free scatter-min rounds over the
   once-harvested basin faces and a compact basin table, with exact
   per-pair min saddles (:func:`fill_unseeded_basins_dense`);
   ``capacity`` (auto on tpu AND gpu) runs the rounds on a compacted
   basin-boundary edge list with run-start saddle sampling (~1/18 the
   transient memory, not exact).  Basins with no seeded reachable
   neighbor keep label 0 (legacy behavior).  ``CT_FILL_MODE`` is the
   one environment variable the kernels read: at the public entry points,
   OUTSIDE jit, and folded into the compile key — flipping it mid-process
   retraces, no ``jax.clear_caches()`` needed.

When every basin is seeded (e.g. the oracle test's fully-seeded minima) the
result is bit-identical to the legacy kernel; only unseeded-basin fill order
differs.

**Flood axes.**  Every stage takes a static set of axes the flood runs along:
``VOLUME`` = (0, 1, 2), the 6-neighbourhood, or ``PLANES`` = (1, 2), one
flood per z-plane (upstream's ``apply_dt_2d`` + ``apply_ws_2d``, the
watershed task's ``two_d``).  Along an axis outside the set nothing is
compared or joined: the EDT has no window there, maxima and seed plateaus are
taken within a plane, no voxel drains across it, so no exit crosses it and
its strips are not collected, and the fill harvests no face there
(``fill.faces_z`` reads 0).  The shape stays 3-D: a block's planes are
flooded side by side in the same tiles, and a label (the seed's least flat
index + 1) names one plane.
"""

from __future__ import annotations

import os
from functools import partial, reduce
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import work
from .ccl import _match_vma, _shift, _true_like
from .pallas_kernels import WS_OFFS, apply_remap_pallas
from .tile_ccl import (
    BIG,
    DEFAULT_TABLE_CAP,
    _auto_cap,
    _compact,
    scatter_set,
    _round_up,
    _shift1,
    _tile_for,
    _tile_id_of,
    build_remap_tables,
    label_components_tiled,
    lane_cond,
    resolve_impl,
    run_capacity_tiered,
)

_BIGF = np.float32(3e38)

DEFAULT_EXIT_CAP = 1 << 21
DEFAULT_FILL_CAP = 1 << 21
# unique unseeded-basin adjacencies (deduped (a, b) pairs), not face
# voxels — object-scale, so orders of magnitude below FILL_CAP
DEFAULT_ADJ_CAP = 1 << 18

#: the flood's axes: the 6-neighbourhood, or one flood per z-plane
VOLUME = (0, 1, 2)
PLANES = (1, 2)


def _flood_axes(axes) -> Tuple[int, ...]:
    """``axes`` as one of the two sets the kernels know."""
    axes = tuple(int(a) for a in axes)
    if axes not in (VOLUME, PLANES):
        raise ValueError(f"flood axes {axes}: expected {VOLUME} or {PLANES}")
    return axes


def _auto_fill_rounds(n_pad: int) -> int:
    """Default Boruvka round bound for the unseeded-basin fill.

    A round at least halves the unseeded component count, so
    ``ceil(log2(n)) + 1`` rounds suffice for ANY input (components can
    never exceed voxels).  The bound is a while-loop max trip count —
    generous values cost nothing at runtime (the loop exits on
    convergence) and nothing in program size.  The old fixed 16 silently
    under-covered volumes with more than 2^16 unseeded basins: the 512³
    host-substrate rehearsal measured 80,902 distinct basins and the fill
    correctly raised its overflow flag at exactly this bound —
    caught before any chip window paid for it (r5).
    """
    return max(16, int(np.ceil(np.log2(max(2, n_pad)))) + 1)


def _resolve_fill_mode(fill_mode: Optional[str]) -> str:
    """Resolve the unseeded-basin fill machinery to ``dense``/``capacity``.

    ``None`` reads ``CT_FILL_MODE`` (default ``auto``).  ``auto`` is
    substrate-aware because the two machines' cost models invert across
    backends:

    - ``dense`` on the **cpu** backend only: sort-free scatter-min Boruvka
      over the harvested basin faces and a compact basin table — exact
      min saddles, capacities derived from the volume's size
      (:func:`fill_unseeded_basins_dense`).
    - ``capacity`` everywhere else (tpu AND gpu): compacted lists +
      dedup sorts, saddles SAMPLED at run starts.  It is the cheaper
      machine on the chip and not an exact one: the benchmark's
      configuration sets ``CT_FILL_MODE=dense`` because ``capacity``
      fails its comparison with a plain flood (PERF.md section 7).

    Resolved OUTSIDE the jit boundary so the value is part of the compile
    key: flipping the env var mid-process retraces instead of silently
    reusing the previously compiled mode.
    """
    if fill_mode is None:
        fill_mode = os.environ.get("CT_FILL_MODE", "auto")
    if fill_mode == "auto":
        fill_mode = "dense" if jax.default_backend() == "cpu" else "capacity"
    if fill_mode not in ("dense", "capacity"):
        raise ValueError(
            f"CT_FILL_MODE must be auto/capacity/dense, got {fill_mode!r}"
        )
    return fill_mode


def _sortable_float_key(f: jnp.ndarray) -> jnp.ndarray:
    """Monotone float32 -> int32 key (total order, NaN-free inputs)."""
    u = lax.bitcast_convert_type(f.astype(jnp.float32), jnp.int32)
    return u ^ ((u >> 31) & jnp.int32(0x7FFFFFFF))


def descent_directions(
    height: jnp.ndarray,
    is_seed: jnp.ndarray,
    valid: jnp.ndarray,
    axes: Tuple[int, ...] = VOLUME,
) -> jnp.ndarray:
    """Code 0..6 of each voxel's steepest-descent target (0 = self).

    Identical tiebreak to ``watershed._descent_pointers``: lexicographic min
    of ``(height, flat_index)`` over the closed neighborhood along ``axes``
    (6 neighbours, or 4 within a plane); seeds and invalid voxels are
    terminals.  Dense shifts only.
    """
    shape = height.shape
    n = int(np.prod(shape))
    z, y, x = shape
    idx = jnp.arange(n, dtype=jnp.int32).reshape(shape)
    h = jnp.where(valid, height.astype(jnp.float32), _BIGF)

    best_h = h
    best_i = idx
    best_d = jnp.zeros(shape, jnp.int32)
    for code, off in enumerate(WS_OFFS, start=1):
        if not any(off[a] for a in axes):
            continue
        nh = h
        ni = idx
        for ax, s in enumerate(off):
            if s:
                nh = _shift(nh, -s, ax, _BIGF)
                ni = _shift(ni, -s, ax, jnp.int32(n))
        better = (nh < best_h) | ((nh == best_h) & (ni < best_i))
        best_h = jnp.where(better, nh, best_h)
        best_i = jnp.where(better, ni, best_i)
        best_d = jnp.where(better, jnp.int32(code), best_d)
    return jnp.where(is_seed | ~valid, 0, best_d)


def tile_ws_propagate_xla(
    dirs: jnp.ndarray, sv: jnp.ndarray, tile: Tuple[int, int, int]
) -> jnp.ndarray:
    """Portable in-tile pointer flow; the formulation is substrate-aware.

    Output contract (both formulations, bit-identical — oracle-locked in
    tests/test_tile_ws.py): each voxel ends with its in-tile path
    terminal's value — seed label, unseeded-terminal code ``-gidx-2``, or
    the exit code of the FIRST out-of-tile hop.

    - off-TPU (cpu and anything else): **pointer jumping** — the in-tile
      successor table composed to closure in O(log path) rounds of
      gathers over L1/L2-resident ``tz*ty*tx`` tables; 5.4× the stepping
      recurrence on the host (docs/PERFORMANCE.md r5).
    - on TPU: the **per-hop dense stepping** recurrence
      (same math as the Mosaic kernel) — dense shifts ride full VPU/HBM
      bandwidth while random gathers run ~165M elem/s regardless of
      locality, so O(path) vectorized rounds beat O(log path) gather
      rounds there.  This path only matters when the portable kernels run
      on-chip (the impl="xla" fallback rung); impl="auto" uses the Mosaic
      kernel.

    The choice is made at trace time from ``jax.default_backend()`` —
    part of program identity per backend, like every other
    substrate-aware selection in this module.
    """
    if _xla_flow_variant() == "stepping":
        return _tile_ws_propagate_stepping(dirs, sv, tile)
    return _tile_ws_propagate_jump(dirs, sv, tile)


def _xla_flow_variant() -> str:
    """The portable flow formulation for this process's default backend."""
    return "stepping" if jax.default_backend() == "tpu" else "pointer_jump"


def _flow_tile_setup(dirs: jnp.ndarray, sv: jnp.ndarray, tile):
    """Shared tile scatter/gather plumbing for both flow formulations:
    returns ``(gidx, dirs_t, sv_t, from_tiles)`` — the tiled global flat
    indices, tiled inputs, and the inverse layout transform.  One home so
    a layout change cannot drift the oracle-locked formulations apart."""
    z, y, x = dirs.shape
    tz, ty, tx = tile
    gz, gy, gx = z // tz, y // ty, x // tx

    def to_tiles(a):
        return (
            a.reshape(gz, tz, gy, ty, gx, tx)
            .transpose(0, 2, 4, 1, 3, 5)
            .reshape(gz * gy * gx, tz, ty, tx)
        )

    def from_tiles(a):
        return (
            a.reshape(gz, gy, gx, tz, ty, tx)
            .transpose(0, 3, 1, 4, 2, 5)
            .reshape(z, y, x)
        )

    idx = jnp.arange(z * y * x, dtype=jnp.int32).reshape(z, y, x)
    return to_tiles(idx), to_tiles(dirs), to_tiles(sv), from_tiles


def _tile_ws_propagate_stepping(
    dirs: jnp.ndarray, sv: jnp.ndarray, tile: Tuple[int, int, int]
) -> jnp.ndarray:
    """Per-hop dense stepping recurrence (the Mosaic kernel's math)."""
    from .pallas_kernels import ws_propagate_step

    _, y, x = dirs.shape
    gidx, dirs_t, sv_t, from_tiles = _flow_tile_setup(dirs, sv, tile)
    terminal = dirs_t == 0
    value = jnp.where(
        sv_t > 0, sv_t, jnp.where(terminal & (sv_t == 0), -gidx - 2, 0)
    ).astype(jnp.int32)

    def cond(s):
        return s[1]

    def body(s):
        v, _ = s
        v2 = ws_propagate_step(v, dirs_t, gidx, (1, 2, 3), y, x)
        return v2, jnp.any(v2 != v)

    value, _ = lax.while_loop(cond, body, (value, _true_like(value)))
    return from_tiles(value)


def _tile_ws_propagate_jump(
    dirs: jnp.ndarray, sv: jnp.ndarray, tile: Tuple[int, int, int]
) -> jnp.ndarray:
    """Pointer-jumping formulation: successor table composed to closure.

    Voxels whose descent target leaves the tile become pseudo-terminals
    carrying their exit code, so closure over ``nxt`` reaches exactly the
    same fixpoint the stepping recurrence does.
    """
    z, y, x = dirs.shape
    tz, ty, tx = tile
    gz, gy, gx = z // tz, y // ty, x // tx
    gidx, dirs_t, sv_t, from_tiles = _flow_tile_setup(dirs, sv, tile)

    # per-code offsets as lookup tables indexed by the direction code
    offs = np.concatenate([[[0, 0, 0]], np.asarray(WS_OFFS)]).astype(np.int32)
    oz = jnp.asarray(offs[:, 0])[dirs_t]
    oy = jnp.asarray(offs[:, 1])[dirs_t]
    ox = jnp.asarray(offs[:, 2])[dirs_t]
    cz = lax.broadcasted_iota(jnp.int32, dirs_t.shape, 1)
    cy = lax.broadcasted_iota(jnp.int32, dirs_t.shape, 2)
    cx = lax.broadcasted_iota(jnp.int32, dirs_t.shape, 3)
    tzc, tyc, txc = cz + oz, cy + oy, cx + ox
    inb = (
        (tzc >= 0) & (tzc < tz) & (tyc >= 0) & (tyc < ty)
        & (txc >= 0) & (txc < tx)
    )
    self_flat = (cz * ty + cy) * tx + cx
    tgt_flat = (tzc * ty + tyc) * tx + txc
    terminal = dirs_t == 0
    # exit code: -(global flat index of the out-of-tile target) - 2
    foff = (oz * y + oy) * x + ox
    exit_code = -(gidx + foff) - 2
    pseudo_term = terminal | ~inb
    nxt = jnp.where(pseudo_term, self_flat, tgt_flat)
    val = jnp.where(
        sv_t > 0,
        sv_t,
        jnp.where(
            terminal & (sv_t == 0),
            -gidx - 2,
            jnp.where(~inb & ~terminal, exit_code, 0),
        ),
    ).astype(jnp.int32)

    nt = gz * gy * gx
    nxt = nxt.reshape(nt, tz * ty * tx)
    val = val.reshape(nt, tz * ty * tx)

    def cond(s):
        return s[1]

    def body(s):
        p, _ = s
        p2 = jnp.take_along_axis(p, p, axis=1)
        return p2, jnp.any(p2 != p)

    nxt, _ = lax.while_loop(cond, body, (nxt, _true_like(nxt)))
    out = jnp.where(val != 0, val, jnp.take_along_axis(val, nxt, axis=1))
    return from_tiles(out.reshape(nt, tz, ty, tx))


def _strip_entries(values: jnp.ndarray, tile, axis: int, side: int):
    """(value, tile_id) arrays for one family of tile-boundary slabs."""
    t = tile[axis]
    n = values.shape[axis]
    start = 0 if side == 0 else t - 1
    sl = lax.slice_in_dim(values, start, n, stride=t, axis=axis)
    shape = sl.shape
    tz, ty, tx = tile
    div = [tz, ty, tx]
    ids = []
    for ax in range(3):
        io = lax.broadcasted_iota(jnp.int32, shape, ax)
        if ax == axis:
            ids.append(io)  # slab index == tile index along the sliced axis
        else:
            ids.append(io // div[ax])
    z, y, x = values.shape
    gy, gx = y // ty, x // tx
    tid = (ids[0] * gy + ids[1]) * gx + ids[2]
    return sl, tid


def collect_negative_values(
    values: jnp.ndarray, tile: Tuple[int, int, int], cap: int,
    axes: Tuple[int, ...] = VOLUME,
):
    """Deduped (value, tile_id) pairs for negative labels on tile boundaries.

    Every cross-tile fragment touches a boundary strip of each tile it
    occupies, so this covers all (tile, value) incidences needed for exits
    and fill remaps; a flood along ``axes`` crosses tile faces across those
    axes only, so only their strips are read.  Returns ``(vals, tids,
    overflow, n_kept, family_max, counts)``: the deduped pairs, whether they
    or one strip family passed ``cap``, how many pairs there are, how many
    entries the fullest family held and the dedup tier's branch in the work
    record.
    """
    vs, ts = [], []
    overflow = _match_vma(jnp.zeros((), jnp.int32), values)
    n_total = family_max = overflow
    for axis in axes:
        for side in (0, 1):
            sl, tid = _strip_entries(values, tile, axis, side)
            # a family can never hold more entries than its strip has
            # voxels, so capping at the strip size is FREE headroom-wise
            # and stops thin families (x strips are volume/128) from
            # being padded to the full exit capacity — at 512^3 this
            # nearly halves the concat the dedup sort below runs over
            fam_cap = max(1024, min(cap, int(np.prod(sl.shape))))
            neg = sl <= -2
            dedup_axis = 2 if axis != 2 else 1
            prev = _shift1(sl, dedup_axis, -1)
            prev_t = _shift1(tid, dedup_axis, -1)
            keep = neg & ((sl != prev) | (tid != prev_t))
            (v, t_), kept = _compact(keep, (sl, tid), fam_cap, BIG)
            overflow = jnp.maximum(
                overflow, (kept > fam_cap).astype(jnp.int32)
            )
            n_total = n_total + jnp.minimum(kept, fam_cap)
            family_max = jnp.maximum(family_max, kept)
            vs.append(v)
            ts.append(t_)
    v = jnp.concatenate(vs)
    t_ = jnp.concatenate(ts)
    # the value-dedup sort runs at the static sum-of-family-caps concat
    # size (≤ 6*cap; ~half of it at 512³ thanks to the strip-size bounds
    # above) — tier it like the merge cores (shared rationale in
    # run_capacity_tiered)
    (cv, ct, n_kept), tier = run_capacity_tiered(
        (v, t_), n_total, cap, _collect_core, 2, 0, values
    )
    overflow = jnp.maximum(overflow, (n_kept > cap).astype(jnp.int32))
    return cv, ct, overflow > 0, n_kept, family_max, tier


def _collect_core(v, t_, cap, _max_rounds, _vma_like):
    """Sort-dedup one (value, tile) tier; outputs sized ``cap``."""
    v, t_ = lax.sort((v, t_), num_keys=2)
    dup = (v == _shift1(v, 0, BIG)) & (t_ == _shift1(t_, 0, BIG))
    keep = (~dup) & (v < BIG)
    (cv, ct), n_kept = _compact(keep, (v, t_), cap, BIG)
    return cv, ct, n_kept


def value_join(
    query_vals: jnp.ndarray, table_vals: jnp.ndarray, table_finals: jnp.ndarray
) -> Tuple[jnp.ndarray, dict]:
    """For each query value, the table's final (or the query itself if
    absent), and the tier's branch in the work record.

    Sort-based join — ``searchsorted`` lowers to a binary-search gather chain
    that measured ~50x slower than a sort at these sizes on TPU.

    Both operands are static-capacity buffers (``BIG``-padded), so the
    usual 1/16 tier applies, slot-aligned: when the live counts fit, both
    sides compact, the join runs small, and results scatter back to their
    query slots (absent/padded queries keep their identity mapping either
    way).
    """
    nq = query_vals.shape[0]
    nt = table_vals.shape[0]
    small_q = max(16384, nq // 16)
    small_t = max(16384, nt // 16)
    if small_q < nq and small_t < nt:
        n_q = (query_vals < BIG).sum()
        n_t = (table_vals < BIG).sum()

        def _small(args):
            qv, tv, tf = args
            (cq, slots), _ = _compact(
                qv < BIG, (qv, jnp.arange(nq, dtype=jnp.int32)), small_q, BIG
            )
            (ctv, ctf), _ = _compact(tv < BIG, (tv, tf), small_t, BIG)
            res = _value_join_core(cq, ctv, ctf)
            return scatter_set(qv, slots, res)

        def _big(args):
            return _value_join_core(*args)

        out, ran = lane_cond(
            (n_q <= small_q) & (n_t <= small_t), _small, _big,
            (query_vals, table_vals, table_finals),
        )
        return out, work.branches(ran)
    return _value_join_core(query_vals, table_vals, table_finals), {}


def _value_join_core(query_vals, table_vals, table_finals):
    nq = query_vals.shape[0]
    nt = table_vals.shape[0]
    keys = jnp.concatenate([table_vals, query_vals])
    is_query = jnp.concatenate(
        [jnp.zeros((nt,), jnp.int32), jnp.ones((nq,), jnp.int32)]
    )
    payload = jnp.concatenate([table_finals, query_vals])
    slot = jnp.concatenate(
        [jnp.full((nt,), -1, jnp.int32), jnp.arange(nq, dtype=jnp.int32)]
    )
    keys, is_query, payload, slot = lax.sort(
        (keys, is_query, payload, slot), num_keys=2
    )
    pos = jnp.arange(nt + nq, dtype=jnp.int32)
    last_tbl = lax.cummax(jnp.where(is_query == 0, pos, -1))
    tbl_key = keys[jnp.clip(last_tbl, 0, nt + nq - 1)]
    tbl_fin = payload[jnp.clip(last_tbl, 0, nt + nq - 1)]
    res = jnp.where((last_tbl >= 0) & (tbl_key == keys), tbl_fin, keys)
    out = jnp.zeros((nq,), jnp.int32)
    out = scatter_set(out, jnp.where(is_query == 1, slot, nq), res)
    return out


def chase_exits(values: jnp.ndarray, codes: jnp.ndarray, max_hops: int = 256):
    """Resolve exit codes by following values across tiles.

    ``codes``: negative codes (``BIG``-padded).  Returns ``(finals,
    unconverged, counts)``: the final value each code's chain reaches (a seed
    label (>0), 0, or the unseeded terminal code of its basin), a flag that
    is True when a chain needed more than ``max_hops`` gathers, and the
    chase's part of the work record (:mod:`.work`): hops, the hops' trips
    and live chains summed, the hops of one trip.  Callers must
    fold the flag into their overflow report: the slots of the chains still
    running then keep their own codes (every finished chain has its final).

    ``codes`` is a STATIC capacity buffer, and a hop costs what its live
    chains cost, not what the buffer holds.  The live codes (``<= -2``) are
    compacted once into a list ``(slot, g)`` whose first ``n_live`` entries
    are chains: the code's slot and the voxel it names.  A hop walks that
    prefix in chunks of ``cap / 16`` slots under a loop of ``ceil(n_live /
    chunk)`` trips — one compiled body, the trip count read from the data.
    A trip gathers ``values[g]`` for its chunk; a chain is finished where the
    value is no code, or names ``g`` itself (a seedless terminal), and its
    value goes to its ``slot`` of the output, which starts as ``codes`` so
    that padding and non-active slots keep their value.  The survivors
    ``(slot, -value - 2)`` are compacted in place at the running count, which
    never passes the chunk's own start.  Each chain is chased alone, so the
    finals are those of a chase of every slot on every hop.  Under ``vmap`` a
    lane takes the hops and the trips of the lane with most of each, and its
    counts are those of the lane run alone.

    Cost on the chip (TPU v5e; PERF.md section 5 has the traced runs): about
    half the buffer is live on the 384³ step's shard, four fifths of the
    chains end within four hops, and the tail that sets the hop count is a
    few thousand chains, then a few dozen: one trip a hop.
    """
    n = values.size
    flat = values.ravel()
    cap = codes.shape[0]
    chunk = -(-cap // 16)
    (slots, gs), n_live = _compact(
        codes <= -2, (jnp.arange(cap, dtype=jnp.int32), -codes - 2), cap, 0
    )
    # room for 16 whole chunks: the last one's slice never clamps
    slots, gs = (jnp.pad(x, (0, 16 * chunk - cap)) for x in (slots, gs))
    offs = jnp.arange(chunk, dtype=jnp.int32)

    def cond(s):
        _, n_live, _, (hops, _, _, _) = s
        return (n_live > 0) & (hops < max_hops)

    def hop(s):
        lists, n_live, out, (hops, trips, live, tail_hops) = s
        n_trips = (n_live + chunk - 1) // chunk

        def trip(k, c):
            lists, n_kept, out = c
            slot, g = (
                lax.dynamic_slice(x, (k * chunk,), (chunk,)) for x in lists
            )
            live = k * chunk + offs < n_live
            val = flat[jnp.clip(g, 0, n - 1)]
            done = live & ((val > -2) | (val == -g - 2))
            out = scatter_set(out, jnp.where(done, slot, cap), val)
            packed, n_keep = _compact(
                live & ~done, (slot, -val - 2), chunk, 0
            )
            lists = tuple(
                lax.dynamic_update_slice(buf, x, (n_kept,))
                for buf, x in zip(lists, packed)
            )
            return lists, n_kept + n_keep, out

        lists, n_kept, out = lax.fori_loop(
            0, n_trips, trip, (lists, jnp.zeros_like(n_live), out),
        )
        return lists, n_kept, out, (
            hops + 1, trips + n_trips,
            live + work.scaled(n_live, work.FLOW_CHASE_LIVE),
            tail_hops + (n_trips == 1),
        )

    zero = jnp.zeros_like(n_live)
    _, n_left, finals, (hops, trips, live, tail_hops) = lax.while_loop(
        cond, hop, ((slots, gs), n_live, codes, (zero, zero, zero, zero))
    )
    return finals, n_left > 0, {
        work.FLOW_CHASE_HOPS: hops, work.FLOW_CHASE_TRIPS: trips,
        work.FLOW_CHASE_LIVE: live, work.FLOW_CHASE_TAIL_HOPS: tail_hops,
    }


def _resolve_codes_gather(values: jnp.ndarray, codes, finals) -> jnp.ndarray:
    """Fallback apply: scatter code resolutions into a voxel-indexed table."""
    n = values.size
    table = _match_vma(-jnp.arange(n, dtype=jnp.int32) - 2, values)
    pos = jnp.where(codes <= -2, -codes - 2, n)
    table = scatter_set(table, pos, finals)
    flat = values.ravel()
    looked = table[jnp.clip(-flat - 2, 0, n - 1)]
    return jnp.where(flat <= -2, looked, flat).reshape(values.shape)


def fill_unseeded_basins(
    labels: jnp.ndarray,
    height: jnp.ndarray,
    fill_cap: int = DEFAULT_FILL_CAP,
    max_rounds: Optional[int] = None,
    adj_cap: Optional[int] = None,
    axes: Tuple[int, ...] = VOLUME,
):
    """Merge unseeded basins across their lowest saddles (Boruvka rounds).

    ``labels``: >0 seeded basin label, <= -2 unseeded basin code, 0 invalid.
    Returns ``(edge_vals, edge_finals, overflow, counts)`` — the remap (old
    basin code -> final label, 0 if unreachable) for every unseeded basin
    seen on a boundary, for the caller to apply, and the fill's part of the
    work record (:mod:`.work`): faces per axis, adjacencies, the flag split
    by what tripped.

    Cost structure (r4, full story in docs/PERFORMANCE.md): face-voxel
    collection keeps the generous ``fill_cap`` (noise robustness); the
    Boruvka rounds run on the *deduplicated basin adjacency list*
    (``adj_cap``, object-scale) with each round's min-edge selection as
    two int32 scatter-mins rather than a sort; and the whole
    dedup+rounds machine is capacity-tiered (``run_capacity_tiered``) so
    the common few-unseeded-basins case executes at 1/16 size.
    Overflowing ``adj_cap`` raises the overflow flag like every other
    capacity.  ``max_rounds=None`` resolves to the always-sufficient
    volume-scaled bound (:func:`_auto_fill_rounds`).  Faces are taken
    along ``axes`` only (a plane's flood has none across planes).
    """
    if max_rounds is None:
        max_rounds = _auto_fill_rounds(labels.size)
    h = height.astype(jnp.float32)
    evs_a, evs_b, evs_h = [], [], []
    counts = {work.FILL_FACES[axis]: 0 for axis in VOLUME}
    n_total = _match_vma(jnp.zeros((), jnp.int32), labels)
    for axis in axes:
        na = labels.shape[axis]
        a = lax.slice_in_dim(labels, 0, na - 1, axis=axis)
        b = lax.slice_in_dim(labels, 1, na, axis=axis)
        ha = lax.slice_in_dim(h, 0, na - 1, axis=axis)
        hb = lax.slice_in_dim(h, 1, na, axis=axis)
        saddle = _sortable_float_key(jnp.maximum(ha, hb))
        flag = (a != b) & (a != 0) & (b != 0) & ((a < 0) | (b < 0))
        dedup_axis = 2 if axis != 2 else 1
        keep = flag & (
            (a != _shift1(a, dedup_axis, 0)) | (b != _shift1(b, dedup_axis, 0))
        )
        (pa, pb, ph), kept = _compact(keep, (a, b, saddle), fill_cap, BIG)
        counts[work.FILL_FACES[axis]] = kept
        n_total = n_total + jnp.minimum(kept, fill_cap)
        evs_a.append(pa)
        evs_b.append(pb)
        evs_h.append(ph)
    a = jnp.concatenate(evs_a)
    b = jnp.concatenate(evs_b)
    hk = jnp.concatenate(evs_h)

    # Default adjacency capacity must stay well below the raw 3*fill_cap
    # candidate buffer or the dedup buys nothing, but "object-scale"
    # undershoots: the r5 512³ host rehearsal MEASURED 1.77M unique
    # adjacencies on the bench synthetic (n/85 — 80,902 unseeded basins
    # averaging ~22 distinct neighbors each, dense seeding makes small
    # basins touch many seeded labels), so the old n/128 truncated and
    # flagged the whole headline run.  n/32 gives ~2.7x headroom over
    # that measurement while staying ~11x under the raw buffer at 512³
    # (3 * fill_cap = 3 * 2^24 ≈ 50.3M vs n/32 ≈ 4.7M); the
    # DEFAULT_ADJ_CAP floor covers pure-noise small volumes.  Overflow is
    # flagged; adversarial regimes should raise adj_cap explicitly.
    if adj_cap is None:
        adj_cap = min(
            len(axes) * fill_cap, max(DEFAULT_ADJ_CAP, labels.size // 32)
        )

    # Capacity tiering: a realistic seeded volume (few unseeded basins)
    # would pay the full 3*fill_cap dedup sort on ~all padding — the
    # common case runs the whole dedup+Boruvka machine at 1/16 size
    # (rationale + the shared threshold live in
    # tile_ccl.run_capacity_tiered).
    (edge_vals, edge_finals, n_adj, adj_over, unconverged), tier = (
        run_capacity_tiered(
            (a, b, hk), n_total, adj_cap, _fill_core, 2, max_rounds, labels
        )
    )
    counts = work.join(counts, tier, {
        work.FILL_ADJACENCIES: n_adj,
        work.OVER_FACE: reduce(jnp.logical_or, [
            counts[work.FILL_FACES[axis]] > fill_cap for axis in axes]),
        work.OVER_ADJ: adj_over, work.OVER_ROUNDS: unconverged,
        work.CAP_FACE: fill_cap, work.CAP_ADJ: adj_cap,
    })
    return edge_vals, edge_finals, work.any_over(counts), counts


def fill_unseeded_basins_dense(
    values: jnp.ndarray,
    height: jnp.ndarray,
    max_rounds: Optional[int] = None,
    face_cap: Optional[int] = None,
    axes: Tuple[int, ...] = VOLUME,
):
    """Sort-free unseeded-basin fill: scatter-min Boruvka rounds over a
    compact basin table and the live prefix of one face list.

    Same MSF semantics as :func:`fill_unseeded_basins` with the saddle per
    basin pair the exact minimum over every shared face voxel (the
    capacity fill samples run-start saddles — see the ``keep`` flags
    there), and still NO SORTS anywhere.  Two one-time passes keep every
    round off the volume:

    - the per-axis basin-face candidate set is harvested ONCE (an O(n)
      cumsum compact of the face positions, not a sort; endpoints, saddles
      and edge ids then gathered a chunk at a time, up to the axis's count)
      — sound because a face can only LEAVE the edge set as basins merge,
      never join it;
    - the seedless basins get DENSE ids: a basin's code names its terminal
      voxel, so ``cumsum(values == own code)`` ranks the terminals in flat
      order, and the face endpoints are rewritten once from codes to
      ``-id - 2``.  The union table ``P``, the per-round ``best`` tables,
      the 2-cycle break and the jump-to-closure loop then all have
      ``basin_cap`` entries, not one per voxel.  The rank is monotone in
      the terminal index, so every tie-break (the smaller terminal stays
      the root of a 2-cycle) picks what a voxel-indexed table picks: the
      labels, the flag and the number of rounds are the same integers.

    The rounds then cost what their live faces cost.  The three axes' faces
    are ONE list ``(va, vb, sad, eid)`` whose first ``n_live`` slots are
    the faces that can still matter.  Every pass of a round (lowest saddle
    per basin, first face among the ties, the winners' hook) walks that
    prefix in chunks of ``face_cap / 16`` slots under a loop of
    ``ceil(n_live / chunk)`` trips — one compiled body, the trip count read
    from the data.  After a round's closure the list is re-compacted in
    place: a face whose resolved sides are equal, or with no seedless basin
    left on either side, is gone for good, and the survivors carry their
    RESOLVED endpoints (``P`` is closed, so they resolve through every
    later table as the original ones would; no pass resolves through
    ``P``).  ``eid``, ``sad`` and both tie-breaks travel with the face:
    the integers are those of three lists padded to ``face_cap``
    (``tests/test_dense_fill.py::_fill_n_table_reference``).

    Capacities, both derived from ``n`` and both REPORTED through the
    overflow flag when exceeded, never silent: ``face_cap`` (default
    ``max(2^16, n/6)`` with a 2^24 ceiling) bounds each axis's harvest —
    ≥1.8× the measured ~9%/axis load while n/6 governs (n ≲ 100M),
    narrowing to ~1.4× at 512³ where the int32-memory ceiling binds;
    ``basin_cap = min(n, max(2^16, n/16))`` bounds the seedless basins
    (more than n/16 of them means basins under 16 voxels on average, whose
    faces overflow ``face_cap`` first unless they are isolated voxels).
    A code whose terminal voxel does not carry it (not what the flow phase
    produces) has no id and raises the same flag.  An input with exact
    height ties (a clipped or quantised boundary map: every plateau voxel a
    basin of its own) truncates the harvest at the default ``face_cap`` and
    raises it too (ROADMAP D4).

    Cost on the chip (TPU v5e, the 384³ step's 448×384×384 shard: n = 66M,
    ``face_cap`` 11.0M, ``basin_cap`` 4.1M; PERF.md section 5 has the
    traced run).  A round is, per LIVE face, six gathers from
    ``basin_cap``-sized tables, four ``scatter-min`` and six ``scatter``
    (two hooks, four of the re-compaction), plus the closure loop on the
    table; nothing in it is volume-sized or ``face_cap``-sized.  Traced
    on one volume: five rounds of 14, 6, 2, 1 and 0 trips (9.6M live faces
    of 33M slots, about 0.4 of them alive a round later) take 2.8 s where
    the padded lists took 17.7 s; a trip is 0.09 s, a chunk's gather from a
    4.1M-entry table running at 60M elements/s.  Paid once a job, 4.4 s
    of the fill's 7.2 on that volume: the harvest 3.1 s (its three loops
    1.2 s = 4 + 6 + 5 trips of 16, a trip's six gathers from 66M-entry
    tables 0.078 s, where eighteen gathers over lists padded to
    ``face_cap`` took 4.1 s; the four n-sized compaction scatters 1.3 s
    and their sorts 0.65 s) and the final resolve's volume-sized gather
    1.2 s.
    Faces are harvested along ``axes`` only: a plane's flood has none
    across planes, so its list holds two axes' faces and ``fill.faces_z``
    reads 0.

    Memory: one list of four ``len(axes) * face_cap`` int32 arrays, four
    ``basin_cap`` tables and two volume-sized int32 temporaries (the rank
    before the rounds, the code table after them); of the harvest only
    one axis's compacted face positions (``face_cap`` slots) live beside
    the list.

    ``values``: >0 seeded label, <= -2 unseeded terminal code
    (``-flat_index - 2``), 0 invalid, and **-1 for masked/padded voxels**
    (what :func:`seeded_watershed_tiled` actually passes by fill time).
    -1 voxels are hookable neighbors: the edge predicate admits
    (unseeded, -1) faces and an unseeded basin whose lowest saddle
    touches one adopts -1, which the caller's final ``values > 0`` squash
    maps to background 0 — the same adopt-to-0 semantics as the capacity
    path.  Callers must NOT assume invalid voxels sit out of saddle
    competition.  Returns ``(resolved_values, overflow_int32, counts)`` —
    per-voxel labels with every reachable unseeded basin resolved to its
    adopted seed label (unreachable basins keep their codes; callers zero
    them), overflow set when ``max_rounds`` rounds did not converge OR a
    face list or the basin table truncated, and the fill's part of the work
    record (:mod:`.work`, names ``fill.*``): what the harvest found, what
    the rounds walked, the flag split by what tripped.

    Selected by ``fill_mode="dense"`` (``CT_FILL_MODE``), or by the
    substrate-aware ``auto`` default on the cpu backend — resolution
    happens pre-jit in :func:`_resolve_fill_mode`.
    """
    shape = values.shape
    n = int(np.prod(shape))
    v = values.ravel()
    h = _sortable_float_key(height.astype(jnp.float32)).ravel()
    i32max = jnp.iinfo(jnp.int32).max
    if face_cap is None:
        face_cap = min(1 << 24, max(1 << 16, n // 6))
    basin_cap = min(n, max(1 << 16, n // 16))
    if max_rounds is None:
        max_rounds = _auto_fill_rounds(n)

    # P[id] = current label of basin id: a seed label, -1, or the -id - 2
    # of the basin it was joined to; ids resolve through it, seeds are
    # terminal by value
    P0 = _match_vma(-jnp.arange(basin_cap, dtype=jnp.int32) - 2, values)

    def resolve_flat(P, x):
        return jnp.where(x <= -2, P[jnp.clip(-x - 2, 0, basin_cap - 1)], x)

    chunk = -(-face_cap // 16)
    with jax.named_scope("ws.fill.harvest"):
        # ---- one-time dense basin ids ----
        # a seedless basin's code is its terminal's own index, so the
        # terminals are the voxels that carry their own code; their rank in
        # flat order is the basin's id.  term_pos[id] leads back to the code
        # after the rounds.
        flat_idx = _match_vma(jnp.arange(n, dtype=jnp.int32), values)
        is_term = v == -flat_idx - 2
        (term_pos,), n_basins = _compact(is_term, (flat_idx,), basin_cap, n)
        # a code without an id (its terminal does not carry it) counts with
        # the basins that found no room in the table
        no_id = n_basins > basin_cap
        term_id = jnp.where(
            is_term, jnp.cumsum(is_term.astype(jnp.int32)) - 1, -1
        )

        def to_id(x):
            """Face endpoint: code -> ``-id - 2`` as ``P0`` resolves it (an
            id past a truncated table reads the table's last entry); seeds,
            -1 and 0 as they are.  Also: whether some code here has no id
            (its terminal does not carry it)."""
            coded = x <= -2
            tid = term_id[jnp.clip(-x - 2, 0, n - 1)]
            return (
                jnp.where(coded, jnp.maximum(-tid - 2, -basin_cap - 1), x),
                jnp.any(coded & (tid < 0)),
            )

        # ---- one-time face harvest (round-invariant superset) ----
        # a face is a candidate edge iff the ORIGINAL codes differ, both are
        # nonzero, and at least one side is an unseeded basin; merging only
        # shrinks this set, so harvesting once is exact.  eid = axis * n +
        # voxel index is globally distinct and seen identically from both
        # sides, so the min-edge graph is a forest plus 2-cycles (the
        # classic distinct-weight Boruvka argument, as in _fill_core).
        # The flood axes' faces go into ONE list (va, vb, sad, eid) of 16
        # chunks an axis (>= face_cap slots each) whose first n_live slots
        # are the faces.  An axis's face positions are compacted (padding: n) and
        # walked in chunks up to their count, as the rounds walk the list:
        # a chunk's endpoints, saddles and ids land at the running count
        # plus the chunk's start, its tail past the count (0, 0, the pad's
        # saddle and eid) over slots that the next axis overwrites.  No
        # slot past n_live is read.
        lists = tuple(
            _match_vma(jnp.zeros((16 * len(axes) * chunk,), jnp.int32), values)
            for _ in range(4)
        )
        n_live = harvest_trips = _match_vma(jnp.zeros((), jnp.int32), values)
        faces = [0, 0, 0]
        for axis in axes:
            nb = _shift(values, -1, axis, jnp.int32(0)).ravel()
            ok0 = (
                (v != nb) & (v != 0) & (nb != 0)
                & ((v <= -2) | (nb <= -2))
            )
            (idx_c,), n_faces = _compact(ok0, (flat_idx,), face_cap, n)
            faces[axis] = n_faces
            n_kept = jnp.minimum(n_faces, face_cap)
            n_trips = (n_kept + chunk - 1) // chunk
            # room for 16 whole chunks: the last one's slice never clamps
            idx_c = jnp.pad(
                idx_c, (0, 16 * chunk - face_cap), constant_values=n
            )
            stride = int(np.prod(shape[axis + 1:], dtype=np.int64))

            def harvest(k, c):
                lists, no_id = c
                idx = lax.dynamic_slice(idx_c, (k * chunk,), (chunk,))
                pad = idx >= n
                ia = jnp.clip(idx, 0, n - 1)
                ib = jnp.clip(idx + stride, 0, n - 1)
                va, bad_a = to_id(jnp.where(pad, 0, v[ia]))
                vb, bad_b = to_id(jnp.where(pad, 0, v[ib]))
                sad = jnp.maximum(h[ia], h[ib])
                eid = jnp.int32(axis) * jnp.int32(n) + idx
                lists = tuple(
                    lax.dynamic_update_slice(buf, x, (n_live + k * chunk,))
                    for buf, x in zip(lists, (va, vb, sad, eid))
                )
                return lists, no_id | bad_a | bad_b

            lists, no_id = lax.fori_loop(
                0, n_trips, harvest, (lists, no_id)
            )
            n_live = n_live + n_kept
            harvest_trips = harvest_trips + n_trips
        faces_over = reduce(
            jnp.logical_or, [faces[axis] > face_cap for axis in axes]
        )
    me_idx = _match_vma(jnp.arange(basin_cap, dtype=jnp.int32), values)
    slot = jnp.arange(chunk, dtype=jnp.int32)

    def take(lists, n_live, k):
        """Chunk ``k`` of the face list, and which of its slots are faces."""
        a, b, sad, eid = (
            lax.dynamic_slice(x, (k * chunk,), (chunk,)) for x in lists
        )
        return a, b, sad, eid, k * chunk + slot < n_live

    def round_cond(s):
        _, changed, it, _, _, _ = s
        return changed & (it < max_rounds)

    def round_body(s):
        # the list holds RESOLVED endpoints: ids of roots, seed labels, -1
        # (the harvest's under P0, every later round's by the rewrite
        # below), so no pass resolves through P.  Every pass walks the live
        # prefix only, chunk by chunk; each is complete over all chunks
        # before the next starts (a basin's best_h must be final before its
        # ties are taken), and neither min nor the one winner's set depends
        # on the order of the chunks.
        P, _, it, lists, n_live, (round_trips, live_faces, closure_trips) = s
        trips = (n_live + chunk - 1) // chunk

        def sides(k):
            a, b, sad, eid, face = take(lists, n_live, k)
            face = face & (a != b)
            return [
                (src, dst, sad, eid, face & (src <= -2),
                 jnp.clip(-src - 2, 0, basin_cap - 1))
                for src, dst in ((a, b), (b, a))
            ]

        def lowest_saddle(k, best_h):
            for src, _, sad, _, m, _ in sides(k):
                g = jnp.where(m, -src - 2, basin_cap)
                best_h = best_h.at[g].min(sad, mode="drop")
            return best_h

        def first_face(k, best_e):
            for src, _, sad, eid, m, gsafe in sides(k):
                tie = m & (best_h[gsafe] == sad)
                gt = jnp.where(tie, -src - 2, basin_cap)
                best_e = best_e.at[gt].min(eid, mode="drop")
            return best_e

        def hook(k, P2):
            # eid names one face and src one of its sides, so best_e[src]
            # == eid only where this side tied at best_h[src] and won
            for src, dst, _, eid, m, gsafe in sides(k):
                win = m & (best_e[gsafe] == eid)
                gw = jnp.where(win, -src - 2, basin_cap)
                P2 = P2.at[gw].set(dst, mode="drop")
            return P2

        unset = _match_vma(
            jnp.full((basin_cap,), i32max, jnp.int32), values
        )
        best_h = lax.fori_loop(0, trips, lowest_saddle, unset)
        best_e = lax.fori_loop(0, trips, first_face, unset)
        P2 = lax.fori_loop(0, trips, hook, P)
        # break 2-cycles (two roots that picked the same edge from both
        # sides): the smaller id, which is the smaller terminal index,
        # stays a root
        me = me_idx
        tgt = jnp.clip(-P2 - 2, 0, basin_cap - 1)
        mutual = (P2 <= -2) & (P2[tgt] == (-me - 2)) & (me < tgt)
        P2 = jnp.where(mutual, -me - 2, P2)
        # pointer-jump to CLOSURE, not a fixed count: a partially
        # compressed table would let the next round's resolution expose
        # intermediate codes, and a non-root's re-hook would then
        # overwrite (sever) an already-contracted MSF union — the exact-
        # semantics claim depends on every round starting from true roots
        def comp_cond(t):
            _, ch, _ = t
            return ch

        def comp_body(t):
            p, _, jumps = t
            p2 = resolve_flat(p, p)
            return p2, jnp.any(p2 != p), jumps + 1

        P2, _, closure_trips = lax.while_loop(
            comp_cond, comp_body, (P2, _true_like(P2), closure_trips)
        )
        changed = jnp.any(P2 != P)

        # drop the dead, rewrite the living: a face whose resolved sides
        # are equal never parts again (merging only coarsens), and one with
        # no basin on either side never hooks (seed labels and -1 are
        # final).  P2 is closed, so a survivor's resolved endpoints resolve
        # through every later table as its original ones would.  Compacted
        # in place, chunk by chunk: a chunk's survivors land at the running
        # count, which never passes the chunk's own start.
        def drop_dead(k, c):
            kept, n_kept = c
            a, b, sad, eid, face = take(kept, n_live, k)
            ra = resolve_flat(P2, a)
            rb = resolve_flat(P2, b)
            keep = face & (ra != rb) & ((ra <= -2) | (rb <= -2))
            packed, n_keep = _compact(keep, (ra, rb, sad, eid), chunk, 0)
            kept = tuple(
                lax.dynamic_update_slice(buf, x, (n_kept,))
                for buf, x in zip(kept, packed)
            )
            return kept, n_kept + n_keep

        kept, n_kept = lax.fori_loop(
            0, trips, drop_dead, (lists, jnp.zeros_like(n_live))
        )
        return P2, changed, it + 1, kept, n_kept, (
            round_trips + trips,
            live_faces + work.scaled(n_live, work.FILL_LIVE_FACES),
            closure_trips,
        )

    zero = jnp.zeros_like(n_live)
    with jax.named_scope("ws.fill.rounds"):
        P, unconverged, rounds, _, _, walked = lax.while_loop(
            round_cond, round_body,
            (P0, _true_like(v), jnp.int32(0), lists, n_live,
             (zero, zero, zero)),
        )
        round_trips, live_faces, closure_trips = walked
    # ---- back to the voxels: ids -> codes at the terminals' positions,
    # then one volume-sized gather as the codes name those positions ----
    with jax.named_scope("ws.fill.resolve"):
        root_pos = term_pos[jnp.clip(-P - 2, 0, basin_cap - 1)]
        code_table = scatter_set(
            -flat_idx - 2, term_pos, jnp.where(P <= -2, -root_pos - 2, P)
        )
        resolved = jnp.where(
            v <= -2, code_table[jnp.clip(-v - 2, 0, n - 1)], v
        ).reshape(shape)
    trunc = faces_over | no_id
    counts = {
        **dict(zip(work.FILL_FACES, faces)),
        work.FILL_HARVEST_TRIPS: harvest_trips,
        work.FILL_BASINS: n_basins, work.FILL_ROUNDS: rounds,
        work.FILL_ROUND_TRIPS: round_trips, work.FILL_LIVE_FACES: live_faces,
        work.FILL_CLOSURE_TRIPS: closure_trips,
        work.OVER_FACE: faces_over, work.OVER_BASIN: no_id,
        work.OVER_ROUNDS: unconverged,
        work.CAP_FACE: face_cap, work.CAP_BASIN: basin_cap,
    }
    return resolved, (unconverged | trunc).astype(jnp.int32), counts


def _fill_core(a, b, hk, adj_cap, max_rounds, vma_like):
    """Dedup + dense ids + Boruvka rounds over one capacity tier.

    Returns ``(edge_vals, edge_finals, n_adj, adj_over, unconverged)`` with
    the first two sized ``2 * adj_cap``, then the adjacency count, whether it
    passed the tier's capacity and whether the rounds ran out; ``vma_like``
    carries the shard_map varying-axes signature for freshly created arrays.
    """
    # dedup to unique (a, b) adjacencies with their min saddle: ascending
    # sort puts each pair's lowest saddle first and the BIG padding last
    sa, sb, sh = lax.sort((a, b, hk), num_keys=3)
    first = (sa != _shift1(sa, 0, BIG)) | (sb != _shift1(sb, 0, BIG))
    keep_adj = first & (sa < BIG)
    (a, b, hk), n_adj = _compact(keep_adj, (sa, sb, sh), adj_cap, BIG)

    # dense ids over all endpoint values
    m2 = a.shape[0] * 2
    vals = jnp.concatenate([a, b])
    slots = jnp.arange(m2, dtype=jnp.int32)
    sv, ss = lax.sort((vals, slots), num_keys=1)
    is_new = sv != _shift1(sv, 0, -BIG)
    rank = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    uniq = scatter_set(jnp.full((m2,), jnp.int32(BIG)), rank, sv)
    dense = scatter_set(jnp.zeros((m2,), jnp.int32), ss, rank)
    da, db = dense[: a.shape[0]], dense[a.shape[0]:]
    edge_pad = a >= BIG

    parent = _match_vma(jnp.arange(m2, dtype=jnp.int32), vma_like)

    def round_cond(s):
        _, changed, it = s
        return changed & (it < max_rounds)

    eid = jnp.arange(a.shape[0], dtype=jnp.int32)
    # composite weight (saddle, edge_id): globally distinct and seen
    # identically from both endpoints, so the min-edge graph is a forest
    # plus 2-cycles only (the classic Boruvka distinct-weight argument) —
    # ties on raw saddle height cannot form longer hook cycles.  The
    # lexicographic min per root is computed as TWO int32 scatter-mins
    # (saddle, then edge-id among saddle ties) instead of a 4-array sort:
    # a full sort is ~10x the cost of a gather/scatter pass on the TPU
    # (docs/PERFORMANCE.md "Where the time goes"), so each Boruvka round
    # drops from sort-bound to a handful of gather-class passes.

    def round_body(s):
        P, _, it = s
        ra = P[da]
        rb = P[db]
        alive = (ra != rb) & (~edge_pad)
        # orient every edge both ways; only negative-valued roots hook
        live_a = alive & (uniq[ra] <= -2)
        live_b = alive & (uniq[rb] <= -2)
        np_ = P.shape[0]
        # init with int32 max, NOT BIG: sortable keys of saddles >= 2.0
        # exceed 2^30 and must still win the scatter-min
        i32max = jnp.iinfo(jnp.int32).max
        best_h = jnp.full((np_,), jnp.int32(i32max))
        best_h = best_h.at[jnp.where(live_a, ra, np_)].min(hk, mode="drop")
        best_h = best_h.at[jnp.where(live_b, rb, np_)].min(hk, mode="drop")
        tie_a = live_a & (best_h[ra] == hk)
        tie_b = live_b & (best_h[rb] == hk)
        best_e = jnp.full((np_,), jnp.int32(i32max))
        best_e = best_e.at[jnp.where(tie_a, ra, np_)].min(eid, mode="drop")
        best_e = best_e.at[jnp.where(tie_b, rb, np_)].min(eid, mode="drop")
        # per root exactly one (edge, side) attains the lexicographic min —
        # except the two sides of ONE edge when both its roots pick it,
        # which is precisely the 2-cycle the break below resolves
        win_a = tie_a & (best_e[ra] == eid)
        win_b = tie_b & (best_e[rb] == eid)
        parent2 = jnp.arange(np_, dtype=jnp.int32)
        parent2 = parent2.at[jnp.where(win_a, ra, np_)].set(
            jnp.where(win_a, rb, 0), mode="drop"
        )
        parent2 = parent2.at[jnp.where(win_b, rb, np_)].set(
            jnp.where(win_b, ra, 0), mode="drop"
        )
        # break 2-cycles: the lower id stays a root
        pp = parent2[parent2]
        me = jnp.arange(np_, dtype=jnp.int32)
        parent2 = jnp.where((pp == me) & (me < parent2), me, parent2)
        # jump to CLOSURE, not a fixed count: a round's hook forest can
        # chain arbitrarily many roots (monotone saddle runs), and a
        # partially-composed P would let the next round hook from
        # intermediate nodes — splitting one component's members across
        # different final seeds.  P stays closed inductively: P0 is the
        # identity, and composing a closed P through a closed parent2
        # yields true roots only.
        def comp_cond(t):
            _, ch = t
            return ch

        def comp_body(t):
            p, _ = t
            p2 = p[p]
            return p2, jnp.any(p2 != p)

        parent2, _ = lax.while_loop(
            comp_cond, comp_body, (parent2, _true_like(parent2))
        )
        newP = parent2[P]
        return newP, jnp.any(newP != P), it + 1

    parent, unconverged, _ = lax.while_loop(
        round_cond, round_body, (parent, _true_like(da), jnp.int32(0))
    )
    # a max_rounds exit leaves basins mid-chain: report, never hide (the
    # caller folds both flags into its overflow)
    root_val = uniq[parent]
    final_of = jnp.where(root_val > 0, root_val, 0)
    # remap for every unseeded endpoint value
    edge_vals = uniq
    edge_finals = jnp.where(uniq <= -2, final_of, uniq)
    return edge_vals, edge_finals, n_adj, n_adj > adj_cap, unconverged


def seeded_watershed_tiled(
    height: jnp.ndarray,
    seeds: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    impl: str = "auto",
    tile: Optional[Tuple[int, int, int]] = None,
    exit_cap: Optional[int] = None,
    fill_cap: Optional[int] = None,
    table_cap: int = DEFAULT_TABLE_CAP,
    interpret: bool = False,
    adj_cap: Optional[int] = None,
    fill_rounds: Optional[int] = None,
    fill_mode: Optional[str] = None,
    axes: Tuple[int, ...] = VOLUME,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Seeded watershed with the two-level tile machinery: ``(labels,
    overflow, work)``, the last the program's work record (:mod:`.work`).

    Contract matches :func:`~cluster_tools_tpu.ops.watershed.seeded_watershed`
    (labels int32, 0 outside mask / unreachable) up to unseeded-basin fill
    order: unseeded basins take the label across their lowest saddle
    (minimum-spanning-forest watershed) rather than ring-growing.  Returns
    ``(labels, overflow)``.

    Sparse-seed / noise-heavy regimes (many unseeded basins) may overflow
    the fill capacities or need more than ``fill_rounds`` Boruvka rounds
    (a round at least halves the unseeded component count; the ``None``
    default resolves to ``max(16, ceil(log2(n)) + 1)`` — sufficient for
    ANY basin count, see :func:`_auto_fill_rounds`); the overflow flag
    reports capacity truncation and ``adj_cap`` is the knob to raise.

    ``fill_mode``: ``dense``/``capacity``/``None`` (= ``CT_FILL_MODE``,
    default substrate-aware ``auto`` — see :func:`_resolve_fill_mode`),
    resolved HERE, outside jit, so flipping the variable mid-process
    retraces instead of reusing a stale cache entry.

    ``axes``: the flood's axes (:data:`VOLUME` or :data:`PLANES`).
    """
    return _seeded_watershed_tiled_jit(
        height, seeds, mask, impl=impl, tile=tile, exit_cap=exit_cap,
        fill_cap=fill_cap, table_cap=table_cap, interpret=interpret,
        adj_cap=adj_cap, fill_rounds=fill_rounds,
        fill_mode=_resolve_fill_mode(fill_mode), axes=_flood_axes(axes),
    )


@partial(
    jax.jit,
    static_argnames=(
        "impl", "tile", "exit_cap", "fill_cap", "table_cap", "interpret",
        "adj_cap", "fill_rounds", "fill_mode", "axes",
    ),
)
def _seeded_watershed_tiled_jit(
    height: jnp.ndarray,
    seeds: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    impl: str = "auto",
    tile: Optional[Tuple[int, int, int]] = None,
    exit_cap: Optional[int] = None,
    fill_cap: Optional[int] = None,
    table_cap: int = DEFAULT_TABLE_CAP,
    interpret: bool = False,
    adj_cap: Optional[int] = None,
    fill_rounds: Optional[int] = None,
    fill_mode: str = "capacity",
    axes: Tuple[int, ...] = VOLUME,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(labels, overflow, work)``: the program packs its parts' counts
    into the one work record (:mod:`.work`)."""
    # The body is flow-phase + fill-phase cores so the split execution mode
    # (parallel/split_pipeline.py) can jit each phase as its OWN program —
    # composing them here compiles the identical fused program.
    values, h, flow_overflow, flow_counts = _ws_flow_core(
        height, seeds, mask, impl=impl, tile=tile, exit_cap=exit_cap,
        table_cap=table_cap, interpret=interpret, axes=axes,
    )
    out, fill_overflow, fill_counts = _ws_fill_core(
        values, h, height.shape, impl=impl, tile=tile, exit_cap=exit_cap,
        fill_cap=fill_cap, table_cap=table_cap, interpret=interpret,
        adj_cap=adj_cap, fill_rounds=fill_rounds, fill_mode=fill_mode,
        axes=axes,
    )
    record = work.pack(work.join(flow_counts, fill_counts))
    return out, flow_overflow | fill_overflow, record


def resolved_modes(impl: str = "auto") -> dict:
    """What the watershed tasks will actually compile in this process for
    their ``impl`` config — kernel family, flow formulation, fill
    machinery — for their logs.  A run's log must say which program ran:
    ``auto`` means Mosaic kernels on a TPU and the portable XLA twins
    elsewhere, and the two share no compiled code.  ``legacy`` / ``host``
    are not tiled kernels and have none of these modes."""
    if impl in ("legacy", "host"):
        return {"impl": impl}
    kernels = resolve_impl(impl)
    return {
        "impl": kernels,
        "flow": "mosaic_in_tile" if kernels == "pallas" else _xla_flow_variant(),
        "fill_mode": _resolve_fill_mode(None),
    }


def _ws_static_plan(shape, tile, exit_cap, fill_cap, axes=VOLUME):
    """Tile/padded geometry + capacity defaults, shared by the fused program
    and the split-phase programs so both compile identical caps.  The exit
    capacity's floor is the strips a flood along ``axes`` can exit through:
    about n/4 of the voxels for the 6-neighbourhood's six families, n/7 for
    a plane's four (no z strips)."""
    z, y, x = shape
    tile = _tile_for(shape) if tile is None else tile
    tz, ty, tx = tile
    zp, yp, xp = _round_up(z, tz), _round_up(y, ty), _round_up(x, tx)
    if zp * yp * xp >= BIG:
        raise ValueError(
            f"padded volume {(zp, yp, xp)} has >= 2**30 voxels; shard it"
        )
    n_pad = zp * yp * xp
    if exit_cap is None:
        # n/3 >= the total strip voxel count for the default tile, so exits
        # can never overflow below ~6M voxels (n/6 for a plane's four strip
        # families, 2/16 + 2/128 of the voxels, below ~12M).  ABOVE that the loads keep
        # scaling with the volume (measured on bench-like box-filtered
        # noise, fractions size-constant 96³→160³ and smoothing-
        # insensitive: exit candidates ~8% of voxels SUMMED over the six
        # strip families — docs/PERFORMANCE.md "512³ capacity audit"), so
        # the old 2^21 ceiling would truncate a 512³ run by ~6x.  The
        # overflow check is PER FAMILY (each compact is capped separately);
        # the largest family carries ~2.5% of voxels, so n/12 leaves ~3x
        # per-family headroom up to the 2^24 ceiling (int32 buffers,
        # ~600MB transient at 512³).  The ~8% total only picks the
        # capacity TIER, never the flag.
        exit_cap = min(1 << 24, max(
            _auto_cap(n_pad, DEFAULT_EXIT_CAP, 3 if axes == VOLUME else 6),
            n_pad // 12,
        ))
    if fill_cap is None:
        # fill edges can reach ~n/2 per axis in pure-noise/sparse-seed
        # regimes (overflow-flagged); the proportional floor covers the
        # measured ~9%-per-axis bench-like load with ~2.5x margin
        fill_cap = min(
            1 << 24, max(_auto_cap(n_pad, DEFAULT_FILL_CAP, 1), n_pad // 8)
        )
    return tile, (zp, yp, xp), exit_cap, fill_cap


@jax.named_scope("ws.flow")
def _ws_flow_core(
    height: jnp.ndarray,
    seeds: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    *,
    impl: str,
    tile: Optional[Tuple[int, int, int]],
    exit_cap: Optional[int],
    table_cap: int,
    interpret: bool,
    axes: Tuple[int, ...] = VOLUME,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, dict]:
    """Flow phase: tile-pad, descent directions, in-tile flow, exit chase +
    remap, for a flood along ``axes`` (the in-tile flow and the chase follow
    the directions, which never point off those axes).  Returns ``(values, h, overflow, counts)`` at TILE-PADDED shape:
    >0 seeded label, <= -2 unseeded terminal code, -1 masked/padded, plus the
    padded float32 heights the fill phase needs and the flow's part of the
    work record (:mod:`.work`, names ``flow.*``)."""
    if height.ndim != 3:
        raise ValueError("seeded_watershed_tiled expects a 3-D volume")
    impl = resolve_impl(impl)
    z, y, x = height.shape
    tile, (zp, yp, xp), exit_cap, _ = _ws_static_plan(
        height.shape, tile, exit_cap, 0, axes
    )
    tz, ty, tx = tile
    padded = (zp != z) or (yp != y) or (xp != x)
    valid = jnp.ones(height.shape, bool) if mask is None else mask.astype(bool)
    h = height.astype(jnp.float32)
    s = seeds.astype(jnp.int32)
    if padded:
        pads = ((0, zp - z), (0, yp - y), (0, xp - x))
        h = jnp.pad(h, pads, constant_values=_BIGF)
        s = jnp.pad(s, pads)
        valid = jnp.pad(valid, pads)

    with jax.named_scope("ws.flow.descent"):
        dirs = descent_directions(h, s > 0, valid, axes)
        sv = jnp.where(valid, s, -1)

    with jax.named_scope("ws.flow.propagate"):
        if impl == "pallas":
            from .pallas_kernels import tile_ws_propagate_pallas

            values = tile_ws_propagate_pallas(
                dirs, sv, tile=tile, interpret=interpret
            )
        else:
            values = tile_ws_propagate_xla(dirs, sv, tile)

    # cross-tile exits: collect, chase, remap
    with jax.named_scope("ws.flow.exits"):
        codes, code_tiles, exit_over, n_exits, family_max, tier = (
            collect_negative_values(values, tile, exit_cap, axes)
        )
    with jax.named_scope("ws.flow.chase"):
        finals, chase_unconverged, counts = chase_exits(values, codes)
    values, remap_counts = _remap_exits(
        values, codes, code_tiles, finals, impl, tile, table_cap, interpret
    )
    counts = work.join(counts, tier, remap_counts, {
        work.FLOW_EXITS: n_exits, work.FLOW_EXIT_FAMILY_MAX: family_max,
        work.OVER_EXIT: exit_over, work.OVER_HOPS: chase_unconverged,
        work.CAP_EXIT: exit_cap, work.CAP_TABLE: table_cap,
    })
    return values, h, exit_over | chase_unconverged, counts


@jax.named_scope("ws.flow.exits")
def _remap_exits(values, codes, code_tiles, finals, impl, tile, table_cap,
                 interpret):
    """Write every chased exit code's final value back into ``values``;
    also, where the Mosaic kernel can run: the fullest tile's table entries
    and whether the gather ran in its place."""
    zp, yp, xp = values.shape
    tz, ty, tx = tile
    n_tiles = (zp // tz) * (yp // ty) * (xp // tx)

    if impl == "pallas":
        changed = (codes <= -2) & (finals != codes)
        tids = jnp.where(changed, code_tiles, jnp.int32(BIG))
        old_tbl, new_tbl, tbl_overflow, tile_max, tier = build_remap_tables(
            tids, codes, finals, n_tiles, table_cap=table_cap
        )
        values, ran = lane_cond(
            tbl_overflow, _gather_codes, _apply_tables(tile, table_cap, interpret),
            (values, (old_tbl, new_tbl), (codes, finals)),
        )
        return values, work.join(tier, work.branches(ran, fallback=True), {
            work.FLOW_REMAP_TILE_MAX: tile_max,
            work.FLOW_REMAP_FALLBACK: tbl_overflow,
        })
    return _resolve_codes_gather(values, codes, finals), {}


def _gather_codes(args):
    """The remap fallback's side of ``lane_cond``: the volume-sized gather."""
    values, _, (codes, finals) = args
    return _resolve_codes_gather(values, codes, finals)


def _apply_tables(tile, table_cap, interpret):
    """The remap's Mosaic side of ``lane_cond``: the per-tile tables."""

    def fast(args):
        values, (old_tbl, new_tbl), _ = args
        return apply_remap_pallas(
            values, old_tbl, new_tbl, tile=tile, cap=table_cap,
            interpret=interpret,
        )

    return fast


@jax.named_scope("ws.fill")
def _ws_fill_core(
    values: jnp.ndarray,
    h: jnp.ndarray,
    orig_shape: Tuple[int, int, int],
    *,
    impl: str,
    tile: Optional[Tuple[int, int, int]],
    exit_cap: Optional[int],
    fill_cap: Optional[int],
    table_cap: int,
    interpret: bool,
    adj_cap: Optional[int],
    fill_rounds: Optional[int],
    fill_mode: str,
    axes: Tuple[int, ...] = VOLUME,
) -> Tuple[jnp.ndarray, jnp.ndarray, dict]:
    """Fill phase: unseeded-basin fill across lowest saddles (fill_mode
    selects the machinery — see :func:`_resolve_fill_mode`), remap, squash
    leftovers to 0, crop the tile padding back to ``orig_shape``.  Returns
    ``(labels, overflow, counts)``, the last the fill's part of the work
    record (:mod:`.work`, names ``fill.*``)."""
    impl = resolve_impl(impl)
    z, y, x = orig_shape
    tile, (zp, yp, xp), exit_cap, fill_cap = _ws_static_plan(
        orig_shape, tile, exit_cap, fill_cap, axes
    )
    tz, ty, tx = tile
    padded = (zp != z) or (yp != y) or (xp != x)
    if values.shape != (zp, yp, xp):
        raise ValueError(
            f"fill phase expects tile-padded values {(zp, yp, xp)}, "
            f"got {values.shape}"
        )
    if fill_rounds is None:
        fill_rounds = _auto_fill_rounds(zp * yp * xp)
    if fill_mode == "dense":
        with jax.named_scope("ws.fill.dense"):
            values, fill_unconv, counts = fill_unseeded_basins_dense(
                values, h, max_rounds=fill_rounds, axes=axes
            )
        overflow = fill_unconv > 0
        out = jnp.where(values > 0, values, 0).astype(jnp.int32)
        if padded:
            out = out[:z, :y, :x]
        return out, overflow, counts
    with jax.named_scope("ws.fill.capacity"):
        fill_vals, fill_finals, overflow, counts = fill_unseeded_basins(
            values, h, fill_cap=fill_cap, max_rounds=fill_rounds,
            adj_cap=adj_cap, axes=axes,
        )
    n_tiles = (zp // tz) * (yp // ty) * (xp // tx)

    if impl == "pallas":
        # tiles needing a basin's entry: strip incidences + the terminal's tile
        bvals, btiles, b_overflow, _, _, collect_tier = collect_negative_values(
            values, tile, exit_cap, axes
        )
        overflow = overflow | b_overflow
        # map each (value, tile) incidence to its fill final
        bfin, join_tier = value_join(bvals, fill_vals, fill_finals)
        # terminal-tile incidences for interior basins
        tvals = fill_vals
        t_of = _tile_id_of(jnp.where(tvals <= -2, -tvals - 2, 0), (zp, yp, xp), tile)
        ttiles = jnp.where(tvals <= -2, t_of, jnp.int32(BIG))
        all_vals = jnp.concatenate([bvals, tvals])
        all_fin = jnp.concatenate([bfin, jnp.where(tvals <= -2, fill_finals, tvals)])
        all_tiles = jnp.concatenate(
            [jnp.where((bvals <= -2) & (bfin != bvals), btiles, jnp.int32(BIG)),
             jnp.where((tvals <= -2) & (fill_finals != tvals), ttiles, jnp.int32(BIG))]
        )
        old2, new2, tbl_overflow2, tile_max2, table_tier = build_remap_tables(
            all_tiles, all_vals, all_fin, n_tiles, table_cap=table_cap
        )
        values, ran = lane_cond(
            tbl_overflow2, _gather_codes,
            _apply_tables(tile, table_cap, interpret),
            (values, (old2, new2), (fill_vals, fill_finals)),
        )
        counts = work.join(
            counts, collect_tier, join_tier, table_tier,
            work.branches(ran, fallback=True), {
                work.OVER_EXIT: b_overflow, work.CAP_EXIT: exit_cap,
                work.FILL_REMAP_TILE_MAX: tile_max2,
                work.FILL_REMAP_FALLBACK: tbl_overflow2,
            },
        )
    else:
        values = _resolve_codes_gather(values, fill_vals, fill_finals)

    # leftover negatives (basins with no seeded reachable neighbor) -> 0
    out = jnp.where(values > 0, values, 0).astype(jnp.int32)
    if padded:
        out = out[:z, :y, :x]
    return out, overflow, counts


@jax.named_scope("ws.seeds")
def _dt_seeds_core(
    boundaries: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    dist: Optional[jnp.ndarray],
    *,
    threshold: float,
    sigma_seeds: float,
    min_seed_distance: float,
    sampling,
    dt_max_distance: Optional[float],
    impl: str,
    tile,
    pair_cap: Optional[int],
    edge_cap: Optional[int],
    table_cap: int,
    interpret: bool,
    axes: Tuple[int, ...] = VOLUME,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Seed phase of the DT watershed: threshold -> (capped) EDT -> optional
    smoothing -> maxima plateaus -> seed CCL.  Returns ``(seeds, valid,
    overflow, work)`` at the input shape — the split execution mode
    (parallel/split_pipeline.py) jits this as its own program; the fused
    ``dt_watershed_tiled`` inlines it.  ``work`` is the seed CCL's work
    record (:mod:`.work`) under the names ``seeds.*``.  Along ``axes``
    only: for :data:`PLANES` the EDT, the smoothing, the maxima and the
    plateaus are each a plane's own (``sampling``'s z entry unused)."""
    from .edt import distance_transform_squared
    from .filters import gaussian_smooth
    from .watershed import local_maxima

    impl = resolve_impl(impl)
    valid = jnp.ones(boundaries.shape, bool) if mask is None else mask.astype(bool)
    fg = (boundaries < threshold) & valid
    if dist is None:
        # "xla" must stay Mosaic-free end-to-end; other modes let the EDT
        # pick its own fast path ("pallas" lacks an interpret plumb, so not
        # forwarded)
        dist = distance_transform_squared(
            fg, sampling=sampling, max_distance=dt_max_distance,
            impl="xla" if impl == "xla" else "auto", axes=axes,
        )
    else:
        # caller-supplied squared distances (e.g. the mesh-exact transform
        # from parallel.distributed_edt); zero them outside the foreground
        # so seed maxima stay inside basins
        dist = jnp.where(fg, dist.astype(jnp.float32), 0.0)
    if sigma_seeds > 0:
        if axes != VOLUME:
            # an infinite voxel size along the other axes: no blur there
            samp = sampling or (1.0,) * boundaries.ndim
            sampling = tuple(float(s) if a in axes else float("inf")
                             for a, s in enumerate(samp))
        dist = gaussian_smooth(dist, sigma_seeds, sampling=sampling)
    maxima = (
        local_maxima(dist, 1, axes=axes)
        & fg
        & (dist >= min_seed_distance * min_seed_distance)
    )
    raw, seed_overflow, record = label_components_tiled(
        maxima, impl=impl, tile=tile, pair_cap=pair_cap, edge_cap=edge_cap,
        table_cap=table_cap, interpret=interpret, axes=axes,
    )
    n = int(np.prod(boundaries.shape))
    seeds = jnp.where(raw == n, 0, raw + 1).astype(jnp.int32)
    return seeds, valid, seed_overflow, work.as_seed_ccl(record)


def dt_watershed_tiled(
    boundaries: jnp.ndarray,
    threshold: float = 0.25,
    sigma_seeds: float = 0.0,
    min_seed_distance: float = 0.0,
    sampling: Optional[Tuple[float, ...]] = None,
    mask: Optional[jnp.ndarray] = None,
    dist: Optional[jnp.ndarray] = None,
    dt_max_distance: Optional[float] = None,
    impl: str = "auto",
    tile: Optional[Tuple[int, int, int]] = None,
    pair_cap: Optional[int] = None,
    edge_cap: Optional[int] = None,
    exit_cap: Optional[int] = None,
    fill_cap: Optional[int] = None,
    table_cap: int = DEFAULT_TABLE_CAP,
    interpret: bool = False,
    adj_cap: Optional[int] = None,
    fill_rounds: Optional[int] = None,
    fill_mode: Optional[str] = None,
    axes: Tuple[int, ...] = VOLUME,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused distance-transform watershed on the two-level machinery.

    The same pipeline as
    :func:`~cluster_tools_tpu.ops.watershed.distance_transform_watershed`
    (threshold -> capped EDT -> seeds = CCL of DT maxima plateaus -> seeded
    watershed; reference ``_ws_block``, SURVEY.md §2a "watershed") with the
    seed CCL and the flood running on the tiled kernels.  3-D only,
    connectivity 1.  Returns ``(labels, overflow, work)``; labels are
    ``seed_rep + 1`` flat-index based, 0 outside mask/unreached; ``work`` is
    the program's work record (:mod:`.work`: what its loops walked, how full
    its capacities ran, which fallback branch it took).

    ``dist``: optional precomputed *squared* distances (e.g. the mesh-exact
    transform from :mod:`cluster_tools_tpu.parallel.distributed_edt`); when
    given, the internal EDT (and ``dt_max_distance``) is skipped.

    ``fill_mode``: explicit machinery selection; ``None`` resolves
    ``CT_FILL_MODE`` here, OUTSIDE jit, so the env value is part of the
    compile key (see :func:`_resolve_fill_mode`).

    ``axes``: :data:`VOLUME` (the 6-neighbourhood), or :data:`PLANES`, one
    watershed per z-plane from the EDT on (upstream's ``apply_dt_2d`` +
    ``apply_ws_2d``; the task's ``two_d``): the planes are flooded side by
    side in the same program and a label names one plane.
    """
    return _dt_watershed_tiled_jit(
        boundaries, threshold=threshold, sigma_seeds=sigma_seeds,
        min_seed_distance=min_seed_distance, sampling=sampling, mask=mask,
        dist=dist, dt_max_distance=dt_max_distance, impl=impl, tile=tile,
        pair_cap=pair_cap, edge_cap=edge_cap, exit_cap=exit_cap,
        fill_cap=fill_cap, table_cap=table_cap, interpret=interpret,
        adj_cap=adj_cap, fill_rounds=fill_rounds,
        fill_mode=_resolve_fill_mode(fill_mode), axes=_flood_axes(axes),
    )


@partial(
    jax.jit,
    static_argnames=(
        "threshold", "sigma_seeds", "min_seed_distance", "sampling",
        "dt_max_distance", "impl", "tile", "pair_cap", "edge_cap",
        "exit_cap", "fill_cap", "table_cap", "interpret", "adj_cap",
        "fill_rounds", "fill_mode", "axes",
    ),
)
def _dt_watershed_tiled_jit(
    boundaries: jnp.ndarray,
    threshold: float = 0.25,
    sigma_seeds: float = 0.0,
    min_seed_distance: float = 0.0,
    sampling: Optional[Tuple[float, ...]] = None,
    mask: Optional[jnp.ndarray] = None,
    dist: Optional[jnp.ndarray] = None,
    dt_max_distance: Optional[float] = None,
    impl: str = "auto",
    tile: Optional[Tuple[int, int, int]] = None,
    pair_cap: Optional[int] = None,
    edge_cap: Optional[int] = None,
    exit_cap: Optional[int] = None,
    fill_cap: Optional[int] = None,
    table_cap: int = DEFAULT_TABLE_CAP,
    interpret: bool = False,
    adj_cap: Optional[int] = None,
    fill_rounds: Optional[int] = None,
    fill_mode: str = "capacity",
    axes: Tuple[int, ...] = VOLUME,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    seeds, valid, seed_overflow, seed_record = _dt_seeds_core(
        boundaries, mask, dist, threshold=threshold, sigma_seeds=sigma_seeds,
        min_seed_distance=min_seed_distance, sampling=sampling,
        dt_max_distance=dt_max_distance, impl=impl, tile=tile,
        pair_cap=pair_cap, edge_cap=edge_cap, table_cap=table_cap,
        interpret=interpret, axes=axes,
    )
    labels, ws_overflow, record = _seeded_watershed_tiled_jit(
        boundaries, seeds, mask=valid, impl=impl, tile=tile,
        exit_cap=exit_cap, fill_cap=fill_cap, table_cap=table_cap,
        interpret=interpret, adj_cap=adj_cap, fill_rounds=fill_rounds,
        fill_mode=fill_mode, axes=axes,
    )
    return labels, seed_overflow | ws_overflow, work.merge(record, seed_record)


def dt_watershed_seeded_tiled(
    boundaries: jnp.ndarray,
    ext_seeds: jnp.ndarray,
    threshold: float = 0.25,
    sigma_seeds: float = 0.0,
    min_seed_distance: float = 0.0,
    sampling: Optional[Tuple[float, ...]] = None,
    mask: Optional[jnp.ndarray] = None,
    dt_max_distance: Optional[float] = None,
    impl: str = "auto",
    tile: Optional[Tuple[int, int, int]] = None,
    pair_cap: Optional[int] = None,
    edge_cap: Optional[int] = None,
    exit_cap: Optional[int] = None,
    fill_cap: Optional[int] = None,
    table_cap: int = DEFAULT_TABLE_CAP,
    interpret: bool = False,
    adj_cap: Optional[int] = None,
    fill_rounds: Optional[int] = None,
    fill_mode: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Two-pass-mode DT watershed on the tiled machinery.

    Same contract as
    :func:`~cluster_tools_tpu.ops.watershed.dt_watershed_seeded`
    (checkerboard pass two, SURVEY.md §3.5): ``ext_seeds`` (int32, dense
    1..K, 0 = none) are neighbor labels from pass one; internal DT seeds are
    planted where no external seed sits.  Output values > N are external
    (+N offset, N = voxel count); 1..N are new internal fragments.  Returns
    ``(labels, overflow, work)``, the last the program's work record
    (:mod:`.work`).

    ``fill_mode`` as in :func:`dt_watershed_tiled` — resolved pre-jit so
    the env value joins the compile key.
    """
    return _dt_watershed_seeded_tiled_jit(
        boundaries, ext_seeds, threshold=threshold, sigma_seeds=sigma_seeds,
        min_seed_distance=min_seed_distance, sampling=sampling, mask=mask,
        dt_max_distance=dt_max_distance, impl=impl, tile=tile,
        pair_cap=pair_cap, edge_cap=edge_cap, exit_cap=exit_cap,
        fill_cap=fill_cap, table_cap=table_cap, interpret=interpret,
        adj_cap=adj_cap, fill_rounds=fill_rounds,
        fill_mode=_resolve_fill_mode(fill_mode),
    )


@partial(
    jax.jit,
    static_argnames=(
        "threshold", "sigma_seeds", "min_seed_distance", "sampling",
        "dt_max_distance", "impl", "tile", "pair_cap", "edge_cap",
        "exit_cap", "fill_cap", "table_cap", "interpret", "adj_cap",
        "fill_rounds", "fill_mode",
    ),
)
def _dt_watershed_seeded_tiled_jit(
    boundaries: jnp.ndarray,
    ext_seeds: jnp.ndarray,
    threshold: float = 0.25,
    sigma_seeds: float = 0.0,
    min_seed_distance: float = 0.0,
    sampling: Optional[Tuple[float, ...]] = None,
    mask: Optional[jnp.ndarray] = None,
    dt_max_distance: Optional[float] = None,
    impl: str = "auto",
    tile: Optional[Tuple[int, int, int]] = None,
    pair_cap: Optional[int] = None,
    edge_cap: Optional[int] = None,
    exit_cap: Optional[int] = None,
    fill_cap: Optional[int] = None,
    table_cap: int = DEFAULT_TABLE_CAP,
    interpret: bool = False,
    adj_cap: Optional[int] = None,
    fill_rounds: Optional[int] = None,
    fill_mode: str = "capacity",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    n = int(np.prod(boundaries.shape))
    internal, valid, seed_overflow, seed_record = _dt_seeds_core(
        boundaries, mask, None, threshold=threshold, sigma_seeds=sigma_seeds,
        min_seed_distance=min_seed_distance, sampling=sampling,
        dt_max_distance=dt_max_distance, impl=impl, tile=tile,
        pair_cap=pair_cap, edge_cap=edge_cap, table_cap=table_cap,
        interpret=interpret,
    )
    # the merge is part of seeding (``ws.seeds`` is the stage the readers
    # know); ``ws.ext_seeds`` names it inside
    with jax.named_scope("ws.seeds"), jax.named_scope("ws.ext_seeds"):
        ext = ext_seeds.astype(jnp.int32)
        # external seeds dominate; internal ids live in 1..N, external in N+1..
        seeds = jnp.where(ext > 0, ext + jnp.int32(n), internal)
    labels, ws_overflow, record = _seeded_watershed_tiled_jit(
        boundaries, seeds, mask=valid, impl=impl, tile=tile,
        exit_cap=exit_cap, fill_cap=fill_cap, table_cap=table_cap,
        interpret=interpret, adj_cap=adj_cap, fill_rounds=fill_rounds,
        fill_mode=fill_mode,
    )
    return labels, seed_overflow | ws_overflow, work.merge(record, seed_record)

