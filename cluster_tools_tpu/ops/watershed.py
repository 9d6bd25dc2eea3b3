"""Seeded watershed as a dense steepest-descent + pointer-jumping kernel.

The reference ran ``vigra.analysis.watershedsNew`` (C++; its default "turbo"
algorithm is a union-find/steepest-descent watershed) per block with halo
(SURVEY.md §2a "watershed", §3.1).  The TPU redesign computes the same basin
decomposition with dense, fixed-shape steps:

1. **descent pointers**: every voxel points at the lexicographic minimum of
   ``(height, flat_index)`` over its closed neighborhood — the index tiebreak
   makes the pointer graph acyclic on plateaus; seeds and masked-out voxels
   point at themselves,
2. **resolve**: pointer-jumping ``ptr = ptr[ptr]`` to fixpoint — every voxel
   reaches the self-loop (seed or basin minimum) its steepest path drains to,
3. **fill**: basins whose minimum is not a seed (shallow minima that didn't
   clear the seed threshold) are absorbed by iteratively letting unlabeled
   voxels adopt the label of their lowest labeled neighbor (region growing
   ordered by height, a dense relaxation of priority-flood).

All three are shift/gather iterations in ``lax.while_loop`` — one compiled
program, vmappable over a block batch.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .ccl import _shift_nd, _neighbor_offsets, _compress, _true_like, label_components, finalize_labels

_BIG = np.float32(3e38)  # numpy: no backend init at import


def _descent_pointers(
    height: jnp.ndarray,
    is_seed: jnp.ndarray,
    valid: jnp.ndarray,
    connectivity: int,
) -> jnp.ndarray:
    """Flat index of the lex-min (height, index) closed-neighborhood element."""
    shape = height.shape
    n = int(np.prod(shape))
    idx = jnp.arange(n, dtype=jnp.int32).reshape(shape)
    h = jnp.where(valid, height, _BIG)

    best_h = h
    best_i = idx
    for off in _neighbor_offsets(len(shape), connectivity):
        for o in (off, tuple(-x for x in off)):
            nh = _shift_nd(h, o, _BIG)
            ni = _shift_nd(idx, o, jnp.int32(n))
            better = (nh < best_h) | ((nh == best_h) & (ni < best_i))
            best_h = jnp.where(better, nh, best_h)
            best_i = jnp.where(better, ni, best_i)
    ptr = jnp.where(is_seed | ~valid, idx, best_i)
    return ptr.ravel()


@partial(jax.jit, static_argnames=("connectivity",))
def seeded_watershed(
    height: jnp.ndarray,
    seeds: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    connectivity: int = 1,
) -> jnp.ndarray:
    """Grow ``seeds`` (int32, 0 = unlabeled) over ``height`` basins.

    Returns int32 labels, 0 only outside ``mask`` (if given) or in regions
    unreachable from any seed.  Matches steepest-descent watershed semantics
    (vigra's default) up to the deterministic (height, index) plateau
    tiebreak.

    Caveat: the unseeded-basin fill below is an unordered relaxation — an
    unseeded basin adopts whatever labeled neighbor reaches it first, which
    can cross a *higher* ridge than the basin's true lowest saddle (measured
    on synthetic EM: ~35% fragment impurity vs ~6.5% for the saddle-ordered
    fill).  :func:`cluster_tools_tpu.ops.tile_ws.seeded_watershed_tiled`
    implements the height-ordered (minimum-spanning-forest) fill and is the
    default task/pipeline kernel, in 3-D and per plane (the task's
    ``two_d``, ``axes=PLANES``); this function remains for connectivity > 1
    (``impl="legacy"`` too), for the size filter's regrowth, and as the
    fully-seeded oracle.
    """
    shape = height.shape
    n = int(np.prod(shape))
    valid = (
        jnp.ones(shape, bool) if mask is None else mask.astype(bool)
    )
    is_seed = (seeds > 0) & valid
    ptr = _descent_pointers(height.astype(jnp.float32), is_seed, valid, connectivity)
    ptr = _compress(ptr, jnp.int32(n))
    lab = seeds.ravel()[jnp.clip(ptr, 0, n - 1)].astype(jnp.int32)
    lab = jnp.where(valid.ravel(), lab, 0)

    # fill unseeded basins: unlabeled voxels adopt the label of their lowest
    # labeled neighbor, iterated to fixpoint
    h = jnp.where(valid, height.astype(jnp.float32), _BIG)
    offsets = []
    for off in _neighbor_offsets(len(shape), connectivity):
        offsets.append(off)
        offsets.append(tuple(-x for x in off))

    def fill_cond(state):
        lab, changed = state
        return changed

    def fill_body(state):
        lab, _ = state
        lab3 = lab.reshape(shape)
        best_h = jnp.full(shape, _BIG)
        best_l = jnp.zeros(shape, jnp.int32)
        for off in offsets:
            nh = _shift_nd(h, off, _BIG)
            nl = _shift_nd(lab3, off, jnp.int32(0))
            cand = nl > 0
            better = cand & (nh < best_h)
            best_h = jnp.where(better, nh, best_h)
            best_l = jnp.where(better, nl, best_l)
        take = (lab3 == 0) & valid & (best_l > 0)
        new = jnp.where(take, best_l, lab3).ravel()
        return new, jnp.any(new != lab)

    lab, _ = lax.while_loop(fill_cond, fill_body, (lab, _true_like(lab)))
    return lab.reshape(shape)


@partial(jax.jit, static_argnames=("connectivity", "axes"))
def local_maxima(
    x: jnp.ndarray, connectivity: int = 1, axes: Optional[Tuple[int, ...]] = None
) -> jnp.ndarray:
    """Boolean mask of (plateau) local maxima: x >= all neighbors.  With
    ``axes`` (connectivity 1) the neighbours along those axes only: maxima
    of each plane for ``axes=(1, 2)``."""
    shape = x.shape
    m = jnp.ones(shape, bool)
    neg_big = jnp.float32(-3e38)
    xf = x.astype(jnp.float32)
    offsets = _neighbor_offsets(len(shape), connectivity)
    if axes is not None:
        if connectivity != 1:
            raise ValueError("local_maxima: axes needs connectivity 1")
        offsets = [o for o in offsets if any(o[a] for a in axes)]
    for off in offsets:
        for o in (off, tuple(-x_ for x_ in off)):
            m &= xf >= _shift_nd(xf, o, neg_big)
    return m


@partial(
    jax.jit,
    static_argnames=(
        "sigma_seeds", "connectivity", "sampling", "two_d", "dt_max_distance"
    ),
)
def distance_transform_watershed(
    boundaries: jnp.ndarray,
    threshold: float = 0.25,
    sigma_seeds: float = 0.0,
    min_seed_distance: float = 0.0,
    sampling: Optional[Tuple[float, ...]] = None,
    mask: Optional[jnp.ndarray] = None,
    connectivity: int = 1,
    two_d: bool = False,
    dt_max_distance: Optional[float] = None,
) -> jnp.ndarray:
    """Fused per-block distance-transform watershed (the flagship kernel).

    One compiled program reproducing the reference's ``_ws_block`` pipeline
    (SURVEY.md §2a "watershed": threshold -> vigra DT -> seeds = labeled DT
    maxima -> ``vigra.watershedsNew`` on the boundary map), redesigned as
    dense XLA steps:

        fg    = boundaries < threshold          (non-boundary region)
        dist  = separable squared EDT of fg     (anisotropic ``sampling``)
        seeds = CCL of DT local-maxima plateaus
        out   = steepest-descent watershed of ``boundaries`` from seeds

    ``two_d=True`` runs the whole pipeline independently per z-slice (the
    reference's 2-D mode for anisotropic EM volumes), with per-slice label
    offsets keeping labels unique across the block.  The watershed task
    takes this path for ``two_d`` only with ``connectivity`` > 1 or
    ``impl="legacy"``: at connectivity 1 it runs the tiled kernels' planar
    form (``ops/tile_ws.py``, ``axes=PLANES``), whose fill is the exact
    lowest-saddle flood and not the relaxation of :func:`seeded_watershed`.
    Labels are block-local (min-voxel flat index based); callers globalize
    by block offset.  vmap over a leading batch axis for mesh-wide
    execution.

    ``dt_max_distance`` caps the EDT at that physical distance (values below
    the cap stay exact; the cascade cost drops from O(extent) to O(cap) per
    axis).  Seeds beyond the cap merge into plateau components — pass a cap
    comfortably above the expected object radius (e.g. the halo).
    """
    from .edt import distance_transform_squared
    from .filters import gaussian_smooth

    valid = jnp.ones(boundaries.shape, bool) if mask is None else mask.astype(bool)
    if two_d:
        samp2 = None if sampling is None else tuple(sampling[1:])
        lab = jax.vmap(
            lambda b2, m2: distance_transform_watershed(
                b2,
                threshold,
                sigma_seeds,
                min_seed_distance,
                sampling=samp2,
                mask=m2,
                connectivity=connectivity,
                two_d=False,
                dt_max_distance=dt_max_distance,
            )
        )(boundaries, valid)
        per_slice = int(np.prod(boundaries.shape[1:]))
        offs = (
            jnp.arange(boundaries.shape[0], dtype=jnp.int32) * per_slice
        ).reshape((-1,) + (1,) * (boundaries.ndim - 1))
        return jnp.where(lab > 0, lab + offs, 0)

    fg = (boundaries < threshold) & valid
    # impl="xla": the legacy kernel is the predictable fallback and runs
    # under vmap (entry(), executor batches) where the Mosaic EDT lifting
    # is untested on this hardware; the tiled pipeline uses the VMEM EDT
    dist = distance_transform_squared(
        fg, sampling=sampling, max_distance=dt_max_distance, impl="xla"
    )
    if sigma_seeds > 0:
        dist = gaussian_smooth(dist, sigma_seeds, sampling=sampling)
    # dist is the *squared* EDT, so the seed floor compares squared
    seeds = dt_seeds(
        dist,
        fg,
        min_distance=min_seed_distance * min_seed_distance,
        connectivity=connectivity,
    )
    return seeded_watershed(
        boundaries, seeds, mask=valid, connectivity=connectivity
    )


@partial(jax.jit, static_argnames=("connectivity", "max_label", "planes"))
def filter_small_segments(
    labels: jnp.ndarray,
    height: jnp.ndarray,
    min_size: jnp.ndarray,
    connectivity: int = 1,
    max_label: Optional[int] = None,
    planes: bool = False,
) -> jnp.ndarray:
    """Remove segments below ``min_size`` voxels and grow survivors into the
    freed space (reference: vigra ``sizeFilterSegInplace`` inside
    ``_ws_block``, SURVEY.md §2a "watershed").

    ``labels`` must be flat-index-based, values in [0, max_label] (default
    ``max_label`` = block voxel count; pass ``2 * N`` for the two-pass
    external-id encoding of :func:`dt_watershed_seeded`); sizes are counted
    with a dense ``segment_sum`` over the block, small segments are cleared,
    and the watershed fill relaxation re-grows the remaining labels —
    within each z-plane where ``planes`` (the per-plane watershed's labels,
    none of which lies in two planes).
    """
    n = int(np.prod(labels.shape)) if max_label is None else int(max_label)
    flat = labels.ravel().astype(jnp.int32)
    sizes = jax.ops.segment_sum(
        jnp.ones_like(flat), jnp.clip(flat, 0, n), num_segments=n + 1
    )
    small = (sizes[jnp.clip(flat, 0, n)] < min_size) & (flat > 0)
    kept = jnp.where(small, 0, flat).reshape(labels.shape)
    # regrow: freed voxels adopt the label of their lowest labeled neighbor
    if planes:
        return jax.vmap(
            lambda h, k, m: seeded_watershed(h, k, mask=m, connectivity=connectivity)
        )(height, kept, labels > 0)
    grown = seeded_watershed(
        height, kept, mask=labels > 0, connectivity=connectivity
    )
    return grown


@partial(
    jax.jit,
    static_argnames=("sigma_seeds", "connectivity", "sampling", "dt_max_distance"),
)
def dt_watershed_seeded(
    boundaries: jnp.ndarray,
    ext_seeds: jnp.ndarray,
    threshold: float = 0.25,
    sigma_seeds: float = 0.0,
    min_seed_distance: float = 0.0,
    sampling: Optional[Tuple[float, ...]] = None,
    mask: Optional[jnp.ndarray] = None,
    connectivity: int = 1,
    dt_max_distance: Optional[float] = None,
) -> jnp.ndarray:
    """DT watershed honoring pre-existing external seeds (two-pass mode).

    The reference's ``two_pass_watershed.py`` runs a checkerboard: pass-two
    blocks seed from already-labeled pass-one neighbors so labels agree
    across block faces without a stitching task (SURVEY.md §3.5).  Here
    ``ext_seeds`` (int32, 0 = none, values 1..K dense) are the neighbor
    labels visible in this block's halo; internal DT seeds are planted where
    no external seed sits, and basins drain to whichever seed their steepest
    path reaches.

    Returns int32 labels: values > N are external ids (+N offset, N = block
    voxel count); values in 1..N are new internal fragments (flat-index
    based).  Callers split on N to map back.
    """
    from .edt import distance_transform_squared
    from .filters import gaussian_smooth

    n = int(np.prod(boundaries.shape))
    valid = jnp.ones(boundaries.shape, bool) if mask is None else mask.astype(bool)
    fg = (boundaries < threshold) & valid
    dist = distance_transform_squared(
        fg, sampling=sampling, max_distance=dt_max_distance, impl="xla"
    )
    if sigma_seeds > 0:
        dist = gaussian_smooth(dist, sigma_seeds, sampling=sampling)
    internal = dt_seeds(
        dist,
        fg,
        min_distance=min_seed_distance * min_seed_distance,
        connectivity=connectivity,
    )
    ext = ext_seeds.astype(jnp.int32)
    # external seeds dominate; internal ids live in 1..N, external in N+1..
    seeds = jnp.where(ext > 0, ext + jnp.int32(n), internal)
    return seeded_watershed(boundaries, seeds, mask=valid, connectivity=connectivity)


@partial(jax.jit, static_argnames=("connectivity",))
def dt_seeds(
    dist: jnp.ndarray,
    mask: jnp.ndarray,
    min_distance: float = 0.0,
    connectivity: int = 1,
) -> jnp.ndarray:
    """Watershed seeds: connected components of DT local-maxima plateaus.

    Mirrors the reference's ``_ws_block`` seed construction (maxima of the
    distance transform, labeled; SURVEY.md §2a "watershed").
    """
    maxima = local_maxima(dist, connectivity) & mask & (dist >= min_distance)
    raw = label_components(maxima, connectivity=connectivity)
    return finalize_labels(raw)
