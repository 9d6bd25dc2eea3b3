"""Two-level connected-components labeling: VMEM tiles + small edge lists.

Round-2's ``label_components`` (ops/ccl.py) is a single-level label-equivalence
fixpoint whose hook/compress steps are full-volume random gathers and
scatters.  Measured on a TPU v5-lite chip those run at ~165M elements/s —
~70x slower than a dense shift pass — making CCL the dominant cost of the
north-star fused step.  This module is the TPU-native redesign:

1. **Tile phase** (``pallas_kernels.tile_ccl_pallas``): exact CCL *within*
   (16, 16, 128) VMEM tiles by dense 6-neighbor min-propagation of global
   flat indices — zero gathers, one HBM round trip for the whole volume.
2. **Face phase** (this module, pure XLA): equivalences can only cross tile
   faces.  Face voxel pairs are extracted with strided slices, de-duplicated
   first along runs (dense compare), then by value (one small 2-key sort),
   and compacted with cumsum+scatter into fixed-size edge arrays (the data-
   dependent edge count lives in *capacity* parameters with overflow flags,
   keeping shapes static for XLA).
3. **Union-find** on the deduped edge list: pointer-jump + hook-min rounds on
   arrays of ``edge_cap`` elements — thousands of times smaller than the
   volume.
4. **Resolve**: roots are scattered into a parent table at endpoint positions
   only, and the final per-voxel relabel is either a per-tile value-remap in
   VMEM (``apply_remap_pallas`` — face-touching fragments per tile are few)
   or a single full gather on the XLA fallback path.

The reference delegated this to vigra's serial two-pass union-find per block
plus ``nifty.ufd`` merges over a filesystem (SURVEY.md §2a
connected_components, §2b); here the same two-level idea (local labeling +
boundary merge) is mapped onto the TPU memory hierarchy instead of a cluster.

All steps run under ``jit``/``shard_map`` (vma-safe carries via the ccl
helpers).  Overflow of any capacity is reported, never silently wrong.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import work
from .ccl import _match_vma, _shift, _true_like, label_components

BIG = 2**30  # background sentinel during the padded/tiled phase

DEFAULT_TILE = (16, 16, 128)
DEFAULT_PAIR_CAP = 1 << 21
# ceiling for unique merged face edges.  Was 1<<19: the measured pair load
# on bench-like volumes is ~0.6% of voxels and size-constant, which
# projects to ~1M at 512³ — over the old ceiling with no margin.  n//128
# still rules below ~250M voxels, so behavior only changes at very large
# single-shard volumes (docs/PERFORMANCE.md "512³ capacity audit").
DEFAULT_EDGE_CAP = 1 << 21
DEFAULT_TABLE_CAP = 64


def resolve_impl(impl: str) -> str:
    """Which kernels the tiled CCL and watershed compile for ``impl``:
    ``pallas`` (Mosaic VMEM kernels) or ``xla`` (their portable twins).
    ``auto`` is ``pallas`` exactly when the default backend is a TPU;
    ``tiled`` is the mesh step's name for ``xla``.  Called while tracing,
    from what the caller passed and the platform alone."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "tiled":
        return "xla"
    return impl


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def _auto_cap(n_voxels: int, default: int, divisor: int) -> int:
    """Volume-scaled capacity: static (shape-derived), bounded by ``default``.

    Tiny volumes (tests, the driver dry-run) would otherwise pay the full
    multi-million-element sort/compact overhead of benchmark-scale caps.
    The 16384 floor keeps adversarially dense small volumes (sparse seeds in
    pure noise: most strip voxels carry basin codes) inside capacity while
    still costing microseconds.
    """
    return max(16384, min(default, _round_up(n_voxels // divisor, 1024)))


def _tile_for(shape: Tuple[int, int, int]) -> Tuple[int, int, int]:
    """Pick a lane-aligned tile; tiny axes get padded up to one tile."""
    z, y, x = shape
    return (min(16, _round_up(z, 8)), min(16, _round_up(y, 8)), 128)


def tile_local_labels_xla(
    mask: jnp.ndarray, tile: Tuple[int, int, int],
    axes: Tuple[int, ...] = (0, 1, 2),
) -> jnp.ndarray:
    """Per-tile CCL via the legacy kernel, vmapped — CPU/fallback path.

    Same contract as ``tile_ccl_pallas``: global flat indices, ``BIG``
    background; ``axes=(1, 2)`` labels each z-plane of a tile on its own.
    """
    z, y, x = mask.shape
    tz, ty, tx = tile
    gz, gy, gx = z // tz, y // ty, x // tx
    tiles = (
        mask.reshape(gz, tz, gy, ty, gx, tx)
        .transpose(0, 2, 4, 1, 3, 5)
        .reshape(gz * gy * gx, tz, ty, tx)
    )
    nloc = tz * ty * tx
    if tuple(axes) == (1, 2):
        planes = jax.vmap(jax.vmap(lambda m: label_components(m, connectivity=1)))(
            tiles
        )
        plane = jnp.arange(tz, dtype=jnp.int32).reshape(1, tz, 1, 1) * (ty * tx)
        local = jnp.where(planes == ty * tx, nloc, planes + plane)
    else:
        local = jax.vmap(lambda m: label_components(m, connectivity=1))(tiles)
    # local rep -> global flat index, elementwise
    tid = jnp.arange(gz * gy * gx, dtype=jnp.int32).reshape(-1, 1, 1, 1)
    ti = tid // (gy * gx)
    tj = (tid // gx) % gy
    tk = tid % gx
    lz = local // (ty * tx)
    ly = (local // tx) % ty
    lx = local % tx
    glob = ((ti * tz + lz) * y + tj * ty + ly) * x + tk * tx + lx
    glob = jnp.where(local == nloc, jnp.int32(BIG), glob.astype(jnp.int32))
    return (
        glob.reshape(gz, gy, gx, tz, ty, tx)
        .transpose(0, 3, 1, 4, 2, 5)
        .reshape(z, y, x)
    )


@jax.custom_batching.custom_vmap
def scatter_set(buf: jnp.ndarray, idx: jnp.ndarray, vals: jnp.ndarray):
    """``buf.at[idx].set(vals, mode="drop")`` on 1-D arrays, with the batched
    form written out.

    Alone it is that scatter and compiles to the same program.  Under
    ``vmap`` (the blockwise executor's lanes) XLA's TPU pipeline rewrites a
    scatter with a batch dimension into one flat scatter over ``lanes *
    len(buf)`` slots, and the instructions it makes carry no ``op_name``:
    on the chip the compactions of a blockwise program then show under no
    stage scope (a sixth of its device time, PERF.md section 6, PR 37).
    The rule below is that rewrite done here, under the caller's
    ``jax.named_scope``, so the scatter keeps its stage."""
    return buf.at[idx].set(vals, mode="drop")


@scatter_set.def_vmap
def _scatter_set_lanes(axis_size, in_batched, buf, idx, vals):
    lanes, m = axis_size, buf.shape[-1]
    if lanes * m >= 2 ** 31 - 1:  # flat positions would leave int32
        plain = jax.vmap(
            lambda b, i, v: b.at[i].set(v, mode="drop"),
            in_axes=[0 if b else None for b in in_batched],
        )
        return plain(buf, idx, vals), True
    buf, idx, vals = (
        x if b else jnp.broadcast_to(x, (lanes,) + x.shape)
        for x, b in zip((buf, idx, vals), in_batched)
    )
    idx = jnp.where(idx < 0, idx + m, idx)  # as indexing wraps a negative
    lane0 = jnp.arange(lanes, dtype=idx.dtype)[:, None] * m
    flat = jnp.where((idx >= 0) & (idx < m), idx + lane0, lanes * m)
    out = buf.reshape(-1).at[flat.reshape(-1)].set(
        vals.reshape(-1), mode="drop"
    )
    return out.reshape(lanes, m), True


def lane_cond(pred, true_fn, false_fn, operands):
    """``lax.cond(pred, true_fn, false_fn, operands)`` that stays a branch
    under ``vmap``; returns ``(out, ran)``, ``ran`` the pair (true side
    ran, false side ran) of scalar bools.

    Alone it is that cond and compiles to the same program, with ``ran``
    ``(pred, ~pred)``.  Under ``vmap`` (the blockwise executor's lanes)
    ``lax.cond`` with a batched predicate becomes a select, and the device
    computes both sides for every lane; the rule below branches on the
    lanes together instead: ``vmap(true_fn)`` runs where any lane's
    predicate holds, ``vmap(false_fn)`` where any lane's does not, and the
    select takes each lane's side, so both run only where the lanes
    disagree.  Every lane gets the side its own predicate picks, so the
    results are those of the select; only a side no lane asked for is left
    out.  ``ran`` then says what the device ran for the batch (unbatched:
    one value for all lanes).

    The branches may not close over a value that is batched (the rule
    cannot see it): pass such values in ``operands``."""

    @jax.custom_batching.custom_vmap
    def cond(pred, operands):
        return lax.cond(pred, true_fn, false_fn, operands), (pred, ~pred)

    @cond.def_vmap
    def _lanes(axis_size, in_batched, pred, operands):
        pred_batched, ops_batched = in_batched
        in_axes = (jax.tree.map(lambda b: 0 if b else None, ops_batched),)
        on_lanes, off_lanes = (
            jax.vmap(fn, in_axes=in_axes, axis_size=axis_size)
            for fn in (true_fn, false_fn)
        )
        if not pred_batched:
            out = lax.cond(pred, on_lanes, off_lanes, operands)
            ran = (pred, ~pred)
        else:
            # each side once in the program, run where a lane asks for it;
            # the side nobody asked for leaves zeros the select never picks
            shapes = jax.eval_shape(on_lanes, operands)

            def nothing(_):
                return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

            def select(a, b):
                return jnp.where(pred.reshape(pred.shape + (1,) * (a.ndim - 1)), a, b)

            ran = (jnp.any(pred), ~jnp.all(pred))
            out = jax.tree.map(
                select, lax.cond(ran[0], on_lanes, nothing, operands),
                lax.cond(ran[1], off_lanes, nothing, operands))
        return (out, ran), (jax.tree.map(lambda _: True, out), (False, False))

    return cond(pred, operands)


def _compact(
    flags: jnp.ndarray, values: Tuple[jnp.ndarray, ...], cap: int, fill: int
):
    """Pack ``values[i][flags]`` into ``cap``-sized arrays (cumsum+scatter).

    Returns (packed_values, n_kept).  Entries beyond ``cap`` are dropped —
    callers must check ``n_kept > cap`` for overflow.  This replaces
    ``jnp.nonzero(size=...)``, whose sort-based lowering measured ~10x
    slower on TPU.
    """
    flat = flags.ravel()
    pos = jnp.cumsum(flat.astype(jnp.int32)) - 1
    dest = jnp.where(flat, pos, cap)
    dest = jnp.where(dest >= cap, cap, dest)
    out = []
    for v in values:
        buf = jnp.full((cap + 1,), fill, dtype=v.dtype)
        buf = scatter_set(buf, dest, v.ravel())
        out.append(buf[:cap])
    n_kept = jnp.where(flat.size > 0, pos[-1] + 1, 0).astype(jnp.int32)
    return tuple(out), n_kept


def run_capacity_tiered(arrays, n_total, big_cap, core, n_padded,
                        max_rounds, vma_like):
    """Run ``core(*arrays, cap, max_rounds, vma_like)`` at 1/16 capacity
    when the runtime entry count allows; returns ``(outputs, counts)``,
    the last the tier's branch in the work record (:func:`work.branches`).

    Every sort inside a merge core runs at its STATIC buffer size, so a
    typical volume (real entries ≪ capacity) would sort ~all padding.
    When ``n_total`` fits the small tier, the real entries are compacted
    (``BIG`` marks padding) and the ENTIRE core runs at that size; its
    capacity-proportional outputs (the first ``n_padded`` of the returned
    tuple) are padded back to the big-tier sizes with ``BIG``.  The small
    tier cannot overflow: its capacity equals its input capacity and
    dedup only shrinks.  Used by :func:`merge_face_pairs` and
    ``tile_ws``'s :func:`~cluster_tools_tpu.ops.tile_ws.fill_unseeded_basins`
    and :func:`~cluster_tools_tpu.ops.tile_ws.collect_negative_values`.
    Inline variants of the same 1/16 tier (they need slot-aligned
    scatter-back or shape-independent outputs rather than tail-padding)
    live in :func:`build_remap_tables` (this module) and
    ``tile_ws.value_join`` — retune the ratio in ALL of these together.
    (``tile_ws.chase_exits`` has no tier: it walks its live codes in
    chunks up to their count.)
    """
    small_n = min(big_cap, max(3 * 16384, arrays[0].shape[0] // 16))

    def _small(args):
        arrays, vma_like = args
        compacted, _ = _compact(arrays[0] < BIG, arrays, small_n, BIG)
        out = core(*compacted, small_n, max_rounds, vma_like)
        padded = tuple(
            jnp.pad(
                x, (0, (x.shape[0] // small_n) * big_cap - x.shape[0]),
                constant_values=BIG,
            )
            for x in out[:n_padded]
        )
        return padded + out[n_padded:]

    def _big(args):
        arrays, vma_like = args
        return core(*arrays, big_cap, max_rounds, vma_like)

    args = (tuple(arrays), vma_like)
    if small_n >= big_cap:
        return _big(args), {}
    out, ran = lane_cond(n_total <= small_n, _small, _big, args)
    return out, work.branches(ran)


def _face_pairs_axis(
    labels: jnp.ndarray, tile: Tuple[int, int, int], axis: int, pair_cap: int
):
    """Label pairs across tile boundaries along ``axis``, run-deduped."""
    t = tile[axis]
    n = labels.shape[axis]
    g = n // t
    if g <= 1:
        empty = jnp.full((pair_cap,), jnp.int32(BIG))
        return (empty, empty), jnp.int32(0)
    a = lax.slice_in_dim(labels, t - 1, n - 1, stride=t, axis=axis)
    b = lax.slice_in_dim(labels, t, n, stride=t, axis=axis)
    valid = (a < BIG) & (b < BIG)
    # run-dedup along the largest non-sliced axis: consecutive identical
    # (a, b) pairs come from the same fragment adjacency
    dedup_axis = 2 if axis != 2 else 1
    a_prev = _shift1(a, dedup_axis, -1)
    b_prev = _shift1(b, dedup_axis, -1)
    keep = valid & ((a != a_prev) | (b != b_prev))
    (pa, pb), n_kept = _compact(keep, (a, b), pair_cap, BIG)
    return (pa, pb), n_kept


def _shift1(x: jnp.ndarray, axis: int, fill: int) -> jnp.ndarray:
    """Shift by +1 along ``axis`` with ``fill`` shifted in (ccl._shift alias)."""
    return _shift(x, 1, axis, jnp.int32(fill))


def merge_face_pairs(
    labels: jnp.ndarray,
    tile: Tuple[int, int, int],
    pair_cap: int = DEFAULT_PAIR_CAP,
    edge_cap: int = DEFAULT_EDGE_CAP,
    max_rounds: int = 64,
    axes: Tuple[int, ...] = (0, 1, 2),
):
    """Union-find closure over tile-face equivalences (the faces across
    ``axes``: components within a plane join across no z face).

    ``labels``: per-tile global-flat-index labels (``BIG`` background).
    Returns ``(ea, eb, root_a, root_b, n_edges, overflow, counts)`` where
    ``ea/eb`` are the deduped edge endpoints (label values, ``BIG``-padded)
    and ``root_a/root_b`` their final merged roots.  ``overflow`` is True
    when a capacity was exceeded or the union-find hit ``max_rounds``
    unconverged (labels would be under-merged — callers re-run with bigger
    caps or fall back); ``counts`` is the merge's part of the work record
    (:mod:`.work`): the fullest axis's pairs, the edges, the flag split by
    what tripped and the capacity tier's branch.
    """
    pair_lists = []
    most_pairs = _match_vma(jnp.zeros((), jnp.int32), labels)
    n_total = _match_vma(jnp.zeros((), jnp.int32), labels)
    for axis in axes:
        (pa, pb), kept = _face_pairs_axis(labels, tile, axis, pair_cap)
        pair_lists.append((pa, pb))
        most_pairs = jnp.maximum(most_pairs, kept)
        n_total = n_total + jnp.minimum(kept, pair_cap)
    # the concat inherits the labels' varying-manual-axes type even when every
    # axis had a single tile (all-constant empty pair lists) — required for
    # the while_loop carries below under shard_map
    a = _match_vma(jnp.concatenate([p[0] for p in pair_lists]), labels)
    b = _match_vma(jnp.concatenate([p[1] for p in pair_lists]), labels)

    (ea, eb, root_a, root_b, n_edges, edge_over, unconverged), tier = (
        run_capacity_tiered(
            (a, b), n_total, edge_cap, _merge_core, 4, max_rounds, labels
        )
    )
    counts = work.join(tier, {
        work.CCL_PAIRS: most_pairs, work.CCL_EDGES: n_edges,
        work.OVER_EDGE: (most_pairs > pair_cap) | edge_over,
        work.OVER_ROUNDS: unconverged,
        work.CAP_PAIR: pair_cap, work.CAP_EDGE: edge_cap,
    })
    return ea, eb, root_a, root_b, n_edges, work.any_over(counts), counts


def _merge_core(a, b, edge_cap, max_rounds, vma_like):
    """Dedup + dense-id union-find over one capacity tier; outputs sized
    ``edge_cap`` (``BIG``-padded), then the edge count, whether it passed
    the tier's capacity and whether the union-find ran out of rounds."""
    # value-dedup: one small sort, duplicates & padding end up adjacent/last
    a, b = lax.sort((a, b), num_keys=2)
    dup = (a == _shift1(a, 0, -1)) & (b == _shift1(b, 0, -1))
    keep = (~dup) & (a < BIG)
    (ea, eb), n_edges = _compact(keep, (a, b), edge_cap, BIG)

    # compact endpoint labels to dense ids so the union-find's parent table
    # is edge-sized, not volume-sized: full pointer-doubling per round then
    # costs a couple of tiny gathers instead of touching a 500MB table
    m2 = 2 * edge_cap
    vals = jnp.concatenate([ea, eb])
    slots = jnp.arange(m2, dtype=jnp.int32)
    svals, sslots = lax.sort((vals, slots), num_keys=1)
    is_new = svals != _shift1(svals, 0, -1)
    rank = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    uniq = scatter_set(jnp.full((m2,), jnp.int32(BIG)), rank, svals)
    dense = scatter_set(jnp.zeros((m2,), jnp.int32), sslots, rank)
    da, db = dense[:edge_cap], dense[edge_cap:]

    parent = _match_vma(jnp.arange(m2, dtype=jnp.int32), vma_like)

    def cond(s):
        _, changed, it = s
        return changed & (it < max_rounds)

    def body(s):
        P, _, it = s
        ra = P[da]
        rb = P[db]
        lo = jnp.minimum(ra, rb)
        hi = jnp.maximum(ra, rb)
        P = P.at[hi].min(lo)
        P = P.at[da].min(lo)
        P = P.at[db].min(lo)
        # full path compression: the table is small, so doubling is cheap
        P = P[P]
        P = P[P]
        return P, jnp.any(ra != rb), it + 1

    parent, unconverged, _ = lax.while_loop(
        cond, body, (parent, _true_like(da), jnp.int32(0))
    )
    # a max_rounds exit leaves edges with differing roots: report, never hide
    # (the caller folds both flags into its overflow)
    # map dense roots back to label values
    root_a = uniq[parent[da]]
    root_b = uniq[parent[db]]
    root_a = jnp.where(ea < BIG, root_a, jnp.int32(BIG))
    root_b = jnp.where(eb < BIG, root_b, jnp.int32(BIG))
    return ea, eb, root_a, root_b, n_edges, n_edges > edge_cap, unconverged


def _tile_id_of(v: jnp.ndarray, shape, tile) -> jnp.ndarray:
    z, y, x = shape
    tz, ty, tx = tile
    gy, gx = y // ty, x // tx
    vz = v // (y * x)
    vy = (v // x) % y
    vx = v % x
    return ((vz // tz) * gy + (vy // ty)) * gx + (vx // tx)


def build_remap_tables(
    tile_ids: jnp.ndarray,
    old_vals: jnp.ndarray,
    new_vals: jnp.ndarray,
    n_tiles: int,
    table_cap: int = DEFAULT_TABLE_CAP,
):
    """Per-tile (old_label -> new_label) tables for the VMEM apply kernel.

    ``tile_ids``: which tile each entry belongs to (``BIG`` = drop the
    entry); duplicates of (tile, old) collapse to one slot.  Returns
    ``(old_tbl, new_tbl, overflow, tile_max, counts)`` with tables shaped
    ``(n_tiles, table_cap)``, unused slots holding -1; ``tile_max`` is the
    number of entries of the fullest tile, and ``overflow`` says that it
    passed ``table_cap`` (the caller then resolves by a gather instead);
    ``counts`` is the tier's branch in the work record.

    The sort runs at the static input size; table shapes don't depend on
    it, so the usual 1/16 capacity tier applies with no scatter-back —
    entries are just compacted first when the live count fits.
    """
    n_in = tile_ids.shape[0]
    small_n = max(16384, n_in // 16)
    args = (tile_ids, old_vals, new_vals)
    if small_n < n_in:
        n_live = (tile_ids < BIG).sum()

        def _small(args):
            compacted, _ = _compact(args[0] < BIG, args, small_n, BIG)
            return _remap_tables_core(*compacted, n_tiles, table_cap)

        def _big(args):
            return _remap_tables_core(*args, n_tiles, table_cap)

        out, ran = lane_cond(n_live <= small_n, _small, _big, args)
        return (*out, work.branches(ran))
    return (*_remap_tables_core(*args, n_tiles, table_cap), {})


def _remap_tables_core(tile_ids, old_vals, new_vals, n_tiles, table_cap):
    tid, v, r = lax.sort((tile_ids, old_vals, new_vals), num_keys=2)
    dup = (tid == _shift1(tid, 0, -1)) & (v == _shift1(v, 0, -1))
    valid = (tid < BIG) & (~dup)
    # within-tile slot rank counting only valid entries
    cnt = jnp.cumsum(valid.astype(jnp.int32))
    is_first = (tid != _shift1(tid, 0, -1)) & (tid < BIG)
    base = lax.cummax(jnp.where(is_first, cnt - valid.astype(jnp.int32), -1))
    slot = jnp.where(valid, cnt - 1 - base, table_cap)
    tile_max = jnp.max(jnp.where(valid, slot + 1, 0))
    dest = jnp.where(valid & (slot < table_cap), tid * table_cap + slot,
                     n_tiles * table_cap)
    old_tbl = jnp.full((n_tiles * table_cap + 1,), jnp.int32(-1))
    new_tbl = jnp.full((n_tiles * table_cap + 1,), jnp.int32(-1))
    old_tbl = scatter_set(old_tbl, dest, v)
    new_tbl = scatter_set(new_tbl, dest, r)
    return (
        old_tbl[:-1].reshape(n_tiles, table_cap),
        new_tbl[:-1].reshape(n_tiles, table_cap),
        tile_max > table_cap,
        tile_max,
    )


def resolve_labels_gather(
    labels: jnp.ndarray,
    ea: jnp.ndarray,
    eb: jnp.ndarray,
    root_a: jnp.ndarray,
    root_b: jnp.ndarray,
) -> jnp.ndarray:
    """Fallback resolve: scatter roots into a parent table, one full gather."""
    n = int(np.prod(labels.shape))
    P = _match_vma(jnp.arange(n + 1, dtype=jnp.int32), labels)
    P = scatter_set(P, jnp.minimum(ea, n), jnp.minimum(root_a, n))
    P = scatter_set(P, jnp.minimum(eb, n), jnp.minimum(root_b, n))
    flat = labels.ravel()
    out = P[jnp.minimum(flat, n)]
    return jnp.where(flat >= BIG, jnp.int32(BIG), out).reshape(labels.shape)


@partial(
    jax.jit,
    static_argnames=(
        "connectivity", "impl", "tile", "pair_cap", "edge_cap", "table_cap",
        "interpret", "axes",
    ),
)
def label_components_tiled(
    mask: jnp.ndarray,
    connectivity: int = 1,
    impl: str = "auto",
    tile: Optional[Tuple[int, int, int]] = None,
    pair_cap: Optional[int] = None,
    edge_cap: Optional[int] = None,
    table_cap: int = DEFAULT_TABLE_CAP,
    interpret: bool = False,
    axes: Tuple[int, ...] = (0, 1, 2),
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Two-level CCL of a 3-D bool mask: ``(labels, overflow, work)``.

    Same output contract as :func:`~cluster_tools_tpu.ops.ccl.label_components`
    — int32, foreground = flat index (in ``mask``'s own shape) of a canonical
    component representative, background = ``mask.size`` — plus an
    ``overflow`` bool: True when an internal capacity was exceeded and labels
    may be under-merged (raise the caps; results are otherwise still
    per-tile-consistent).  Unlike the legacy kernel the representative is the
    component's minimum index in the *padded, tiled* order, which is a
    canonical choice but not necessarily the minimum in array order.

    ``impl``: "pallas", "xla", "tiled" or "auto" (:func:`resolve_impl`).
    ``connectivity`` must be 1 (face connectivity) — callers needing the full
    neighborhood use the legacy kernel.  Capacities default to volume-scaled
    values (static, shape-derived); pass explicit caps for workloads with
    unusually many fragments per tile face.

    ``axes``: the axes voxels connect along; ``(1, 2)`` gives each z-plane's
    own 4-connected components (the per-plane watershed's seed plateaus).

    ``work`` is the program's work record (:mod:`.work`, names ``ccl.*``):
    the merge's counts and, where the Mosaic remap kernel can run, the
    fullest tile's table entries and whether the gather ran in its place.
    """
    if mask.ndim != 3:
        raise ValueError("label_components_tiled expects a 3-D mask")
    if connectivity != 1:
        raise ValueError("tiled CCL supports connectivity=1 only")
    impl = resolve_impl(impl)

    z, y, x = mask.shape
    tile = _tile_for(mask.shape) if tile is None else tile
    tz, ty, tx = tile
    zp, yp, xp = _round_up(z, tz), _round_up(y, ty), _round_up(x, tx)
    if zp * yp * xp >= BIG:
        raise ValueError(
            f"padded volume {(zp, yp, xp)} has >= 2**30 voxels; flat-index "
            "labels would collide with the background sentinel — shard the "
            "volume (parallel.distributed_ccl) instead"
        )
    padded = (zp != z) or (yp != y) or (xp != x)
    if pair_cap is None:
        pair_cap = _auto_cap(zp * yp * xp, DEFAULT_PAIR_CAP, 32)
    if edge_cap is None:
        edge_cap = _auto_cap(zp * yp * xp, DEFAULT_EDGE_CAP, 128)
    if impl == "pallas":
        from .pallas_kernels import apply_remap_pallas, tile_ccl_pallas

    # first level: every tile labelled on its own
    with jax.named_scope("ccl.tile"):
        m = mask.astype(bool)
        if padded:
            m = jnp.pad(m, ((0, zp - z), (0, yp - y), (0, xp - x)))
        if impl == "pallas":
            labels = tile_ccl_pallas(m, tile=tile, interpret=interpret, axes=axes)
        else:
            labels = tile_local_labels_xla(m, tile, axes)

    # second level: equivalences across tile faces, solved and applied
    with jax.named_scope("ccl.merge"):
        ea, eb, root_a, root_b, n_edges, overflow, counts = merge_face_pairs(
            labels, tile, pair_cap=pair_cap, edge_cap=edge_cap, axes=axes
        )
        counts[work.CAP_TABLE] = table_cap

        if impl == "pallas":
            n_tiles = (zp // tz) * (yp // ty) * (xp // tx)
            v = jnp.concatenate([ea, eb])
            r = jnp.concatenate([root_a, root_b])
            changed = (v < BIG) & (r != v)
            tids = jnp.where(
                changed, _tile_id_of(v, (zp, yp, xp), tile), jnp.int32(BIG)
            )
            old_tbl, new_tbl, tbl_overflow, tile_max, tier = build_remap_tables(
                tids, v, r, n_tiles, table_cap=table_cap
            )

            def fast(args):
                labels, (old_tbl, new_tbl), _ = args
                return apply_remap_pallas(
                    labels, old_tbl, new_tbl, tile=tile, cap=table_cap,
                    interpret=interpret,
                )

            def slow(args):
                labels, _, edges = args
                return resolve_labels_gather(labels, *edges)

            resolved, ran = lane_cond(
                tbl_overflow, slow, fast,
                (labels, (old_tbl, new_tbl), (ea, eb, root_a, root_b)),
            )
            counts = work.join(counts, tier, work.branches(ran, fallback=True), {
                work.CCL_REMAP_TILE_MAX: tile_max,
                work.CCL_REMAP_FALLBACK: tbl_overflow,
            })
        else:
            resolved = resolve_labels_gather(labels, ea, eb, root_a, root_b)

        n_orig = z * y * x
        if padded:
            resolved = resolved[:z, :y, :x]
            # padded-flat representative -> original-flat representative
            vz = resolved // (yp * xp)
            vy = (resolved // xp) % yp
            vx = resolved % xp
            orig = ((vz * y + vy) * x + vx).astype(jnp.int32)
            out = jnp.where(resolved >= BIG, jnp.int32(n_orig), orig)
        else:
            out = jnp.where(resolved >= BIG, jnp.int32(n_orig), resolved)
    return out, overflow, work.pack(counts)
