"""Pallas TPU kernels for the tile-level phase of two-level labeling.

Why these exist: profiling the round-2 fused step on a real v5-lite chip
showed the label fixpoints (``ops/ccl.py`` hook+compress, ``ops/watershed.py``
pointer resolve) spending essentially all their time in full-volume random
gathers/scatters, which the TPU executes at ~165M elements/s regardless of
locality or table size — ~70x slower per pass than a dense shift.  A v5-lite
chip measured: 6-neighbor dense min sweep over 512^3 = ~16ms; one random
gather over the same array = ~850ms.  The fix is architectural: do ALL
data-dependent iteration inside VMEM tiles with dense shift/min steps (this
module), and reduce the cross-tile problem to small edge lists handled with
sorts and sub-millisecond scatters (``tile_ccl.py``).

Kernels:

- :func:`tile_ccl_pallas` — exact connected-components labeling *within* each
  (tz, ty, tx) tile: iterated 6-neighbor min-propagation of global flat
  indices in VMEM to a fixpoint (``lax.while_loop`` in-kernel).  No gathers:
  shifts are static slices.  The volume crosses HBM exactly once each way.
- :func:`apply_remap_pallas` — applies a per-tile value remap table
  (old_label -> new_label, <= cap entries per tile) with an unrolled
  compare-select loop in VMEM: the cross-tile merge touches only labels that
  appear on tile faces, so each tile's table is tiny and value-matching
  replaces a full-volume gather.

Tile shape: last dim 128 (TPU lane width), middle dims sized so a tile is a
few vreg rows — (16, 16, 128) by default, 128KB of int32 per tile.

The reference (SURVEY.md §2b) got per-block CCL from vigra's serial C++
union-find; this is the TPU-native replacement, not a translation.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import typeof
from .ccl import _shift

# Sentinel must exceed any global flat index (volumes are int32-bounded
# anyway: > 2**31 voxels per shard is rejected upstream).
BIG = 2**30

# watershed pointer-propagation: value read from outside the tile
WS_MARKER = -(2**30)

# descent-direction codes 1..6 in this order; 0 = self (terminal)
WS_OFFS = ((-1, 0, 0), (1, 0, 0), (0, -1, 0), (0, 1, 0), (0, 0, -1), (0, 0, 1))


def _out_struct(shape, dtype, *like) -> jax.ShapeDtypeStruct:
    """Output aval for ``pallas_call`` whose varying-manual-axes match ``like``.

    Under ``shard_map(check_vma=True)`` (the default) ``pallas_call`` refuses a
    plain ``ShapeDtypeStruct`` — the output's ``vma`` must be stated.  The
    kernels here are purely per-shard, so the output varies over exactly the
    axes their inputs vary over.
    """
    vma = frozenset()
    for a in like:
        v = getattr(typeof(a), "vma", None)
        if v:
            vma = vma | v
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _ccl_kernel(tile_shape, axes, mask_ref, out_ref):
    tz, ty, tx = tile_shape
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)
    ny = pl.num_programs(1) * ty
    nx = pl.num_programs(2) * tx
    mask = mask_ref[:] > 0
    gz = lax.broadcasted_iota(jnp.int32, tile_shape, 0) + i * tz
    gy = lax.broadcasted_iota(jnp.int32, tile_shape, 1) + j * ty
    gx = lax.broadcasted_iota(jnp.int32, tile_shape, 2) + k * tx
    gidx = (gz * ny + gy) * nx + gx
    lab = jnp.where(mask, gidx, jnp.int32(BIG))

    def nmin(l):
        m = l
        for ax in axes:
            m = jnp.minimum(m, _shift(l, 1, ax, jnp.int32(BIG)))
            m = jnp.minimum(m, _shift(l, -1, ax, jnp.int32(BIG)))
        return m

    def cond(s):
        return s[1]

    def body(s):
        l, _ = s
        # two propagation steps per convergence check: halves the number of
        # full-tile reductions on the critical path
        l1 = jnp.minimum(l, jnp.where(mask, nmin(l), jnp.int32(BIG)))
        l2 = jnp.minimum(l1, jnp.where(mask, nmin(l1), jnp.int32(BIG)))
        return l2, jnp.any(l2 != l)

    lab, _ = lax.while_loop(cond, body, (lab, True))
    out_ref[:] = lab


@partial(jax.jit, static_argnames=("tile", "interpret", "axes"))
def tile_ccl_pallas(
    mask: jnp.ndarray,
    tile: Tuple[int, int, int] = (16, 16, 128),
    interpret: bool = False,
    axes: Tuple[int, ...] = (0, 1, 2),
) -> jnp.ndarray:
    """Exact per-tile CCL of a 3-D bool mask; labels are global flat indices.

    Shape must be divisible by ``tile`` (callers pad).  Foreground voxels get
    the minimum global flat index of their *within-tile* component;
    background gets ``BIG``.  Cross-tile merging is ``tile_ccl.py``'s job.
    Voxels connect across the faces of ``axes`` only: ``(1, 2)`` labels
    each z-plane of the tile on its own (4-connected).
    """
    z, y, x = mask.shape
    tz, ty, tx = tile
    assert z % tz == 0 and y % ty == 0 and x % tx == 0, (mask.shape, tile)
    return pl.pallas_call(
        partial(_ccl_kernel, tile, tuple(axes)),
        out_shape=_out_struct((z, y, x), jnp.int32, mask),
        grid=(z // tz, y // ty, x // tx),
        in_specs=[
            pl.BlockSpec(tile, lambda i, j, k: (i, j, k), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec(
            tile, lambda i, j, k: (i, j, k), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        name="tile_ccl",
    )(mask.astype(jnp.int32))


def ws_propagate_step(value, dirs, gidx, axes, ny, nx):
    """One step of label flow along descent pointers (shared kernel/XLA math).

    Every voxel whose direction code is ``d`` copies the value of its descent
    target (the neighbor at ``WS_OFFS[d-1]``); terminals (code 0) keep their
    value.  A copy that would read outside the tile (the shifted-in
    ``WS_MARKER``) resolves to the *exit code* ``-(target_gidx + 2)`` instead,
    freezing the fragment until the cross-tile chase resolves it.

    ``axes`` maps the three spatial offsets onto array axes (kernel: (0,1,2);
    XLA tiled fallback: trailing axes of a batched array); ``ny``/``nx`` are
    the *global* volume dims for flat-index arithmetic.
    """
    new = value
    for code, off in enumerate(WS_OFFS, start=1):
        foff = (off[0] * ny + off[1]) * nx + off[2]
        v_t = value
        for ax, s in zip(axes, off):
            if s:
                v_t = _shift(v_t, -s, ax, jnp.int32(WS_MARKER))
        sel = dirs == code
        exit_code = -(gidx + jnp.int32(foff)) - 2
        new = jnp.where(
            sel,
            jnp.where(v_t == jnp.int32(WS_MARKER), exit_code, v_t),
            new,
        )
    return new


def _ws_kernel(tile_shape, dir_ref, seed_ref, out_ref):
    tz, ty, tx = tile_shape
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)
    ny = pl.num_programs(1) * ty
    nx = pl.num_programs(2) * tx
    gz = lax.broadcasted_iota(jnp.int32, tile_shape, 0) + i * tz
    gy = lax.broadcasted_iota(jnp.int32, tile_shape, 1) + j * ty
    gx = lax.broadcasted_iota(jnp.int32, tile_shape, 2) + k * tx
    gidx = (gz * ny + gy) * nx + gx
    dirs = dir_ref[:]
    sv = seed_ref[:]  # -1 invalid, 0 unseeded, >0 seed label
    terminal = dirs == 0
    value = jnp.where(
        sv > 0, sv, jnp.where(terminal & (sv == 0), -gidx - 2, jnp.int32(0))
    )

    def cond(s):
        return s[1]

    def body(s):
        v, _ = s
        v2 = ws_propagate_step(v, dirs, gidx, (0, 1, 2), ny, nx)
        return v2, jnp.any(v2 != v)

    value, _ = lax.while_loop(cond, body, (value, True))
    out_ref[:] = value


@partial(jax.jit, static_argnames=("tile", "interpret"))
def tile_ws_propagate_pallas(
    dirs: jnp.ndarray,
    seeds_or_invalid: jnp.ndarray,
    tile: Tuple[int, int, int] = (16, 16, 128),
    interpret: bool = False,
) -> jnp.ndarray:
    """In-tile watershed label flow along a descent-direction field.

    ``dirs``: int32 codes (0 = terminal/self, 1..6 = ``WS_OFFS``).
    ``seeds_or_invalid``: int32, -1 = masked out, 0 = no seed, >0 = seed id.
    Output per voxel: seed label (>0), 0 (invalid), ``-(t + 2)`` (drains to
    the unseeded in-tile terminal ``t``), or ``-(g + 2)`` for an exit whose
    target voxel ``g`` lies in another tile (resolved by ``tile_ws``).
    """
    z, y, x = dirs.shape
    tz, ty, tx = tile
    assert z % tz == 0 and y % ty == 0 and x % tx == 0
    return pl.pallas_call(
        partial(_ws_kernel, tile),
        out_shape=_out_struct((z, y, x), jnp.int32, dirs, seeds_or_invalid),
        grid=(z // tz, y // ty, x // tx),
        in_specs=[
            pl.BlockSpec(tile, lambda i, j, k: (i, j, k), memory_space=pltpu.VMEM),
            pl.BlockSpec(tile, lambda i, j, k: (i, j, k), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            tile, lambda i, j, k: (i, j, k), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        name="tile_ws_propagate",
    )(dirs.astype(jnp.int32), seeds_or_invalid.astype(jnp.int32))


def _edt_kernel(axis, radius, w, big, x_ref, out_ref):
    g = x_ref[:]
    n = g.shape[axis]

    def body(i, g):
        c = jnp.float32(w) * (2.0 * i.astype(jnp.float32) + 1.0)
        lo = _shift(g, 1, axis, jnp.float32(big)) + c
        hi = _shift(g, -1, axis, jnp.float32(big)) + c
        return jnp.minimum(g, jnp.minimum(lo, hi))

    out_ref[:] = lax.fori_loop(0, min(radius, n - 1), body, g)


@partial(jax.jit, static_argnames=("axis", "radius", "w", "big", "interpret"))
def edt_cascade_pallas(
    f: jnp.ndarray,
    axis: int,
    radius: int,
    w: float,
    big: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """Parabolic erosion cascade along one axis, iterated in VMEM.

    The XLA formulation runs ``radius`` dependent full-volume passes through
    HBM (~5ms each at 512^3 — an EDT capped at halo=32 costs ~0.5s);
    keeping each line's whole extent in VMEM makes the cascade compute-bound
    instead.  Blocks span the full processed axis, so no cross-block halo
    exists.  Shapes must divide the tile; callers pad (values ``big`` pad
    correctly: they never win a ``min``).
    """
    z, y, x = f.shape
    if axis == 0:
        tile = (z, 8, 128)
    elif axis == 1:
        tile = (8, y, 128)
    else:
        tile = (8, 8, x)
    tz, ty, tx = tile
    assert z % tz == 0 and y % ty == 0 and x % tx == 0, (f.shape, tile)
    return pl.pallas_call(
        partial(_edt_kernel, axis, radius, w, big),
        out_shape=_out_struct((z, y, x), jnp.float32, f),
        grid=(z // tz, y // ty, x // tx),
        in_specs=[
            pl.BlockSpec(tile, lambda i, j, k: (i, j, k), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec(
            tile, lambda i, j, k: (i, j, k), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        name="edt_cascade",
    )(f.astype(jnp.float32))


def _apply_kernel(cap, old_ref, new_ref, lab_ref, out_ref):
    lab = lab_ref[:]
    # unrolled compare-select over the tile's remap entries; slots beyond the
    # tile's fragment count hold old = -1 which never matches a label
    for c in range(cap):
        o = old_ref[0, 0, c]
        nw = new_ref[0, 0, c]
        lab = jnp.where(lab == o, nw, lab)
    out_ref[:] = lab


@partial(jax.jit, static_argnames=("tile", "cap", "interpret"))
def apply_remap_pallas(
    labels: jnp.ndarray,
    old_tbl: jnp.ndarray,
    new_tbl: jnp.ndarray,
    tile: Tuple[int, int, int] = (16, 16, 128),
    cap: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-tile value remap: labels equal to old_tbl[t, c] become new_tbl[t, c].

    ``old_tbl``/``new_tbl`` are (n_tiles, cap) int32, tiles in z-major grid
    order; unused slots must hold -1.  Labels not present in the tile's table
    pass through unchanged.
    """
    z, y, x = labels.shape
    tz, ty, tx = tile
    gz, gy, gx = z // tz, y // ty, x // tx
    assert old_tbl.shape == (gz * gy * gx, cap), (old_tbl.shape, (gz * gy * gx, cap))
    # (n_tiles, 1, cap) so the block's trailing dims equal the array's —
    # the Mosaic block-shape divisibility rule for non-(8,128) tails
    old3 = old_tbl.reshape(-1, 1, cap)
    new3 = new_tbl.reshape(-1, 1, cap)

    def tbl_map(i, j, k):
        return ((i * gy + j) * gx + k, 0, 0)

    return pl.pallas_call(
        partial(_apply_kernel, cap),
        out_shape=_out_struct((z, y, x), jnp.int32, old_tbl, new_tbl, labels),
        grid=(gz, gy, gx),
        in_specs=[
            pl.BlockSpec((1, 1, cap), tbl_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, cap), tbl_map, memory_space=pltpu.VMEM),
            pl.BlockSpec(tile, lambda i, j, k: (i, j, k), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            tile, lambda i, j, k: (i, j, k), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        name="apply_remap",
    )(old3, new3, labels)
