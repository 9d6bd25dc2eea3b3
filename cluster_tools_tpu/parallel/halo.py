"""Device-side halo (ghost-zone) exchange over a sharded spatial axis.

The reference implemented halos as *overlapping filesystem reads*: every
block job independently re-read up to ``halo`` voxels of its neighbors' data
from the shared N5 store (SURVEY.md §2c "Halo/ghost-zone exchange").  On a
mesh the neighbor data already sits in the neighbor device's HBM, so the halo
is a nearest-neighbor ``lax.ppermute`` over ICI — the same communication
pattern as ring/context-parallel attention, applied to a spatial axis
(SURVEY.md §5.7).

All functions here must be called *inside* ``jax.shard_map`` with ``x`` being
the local shard.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


@jax.named_scope("step.halo")
def exchange_halo(
    x: jnp.ndarray,
    halo: int,
    axis: int,
    axis_name: str,
    axis_size: int,
    fill=0,
) -> jnp.ndarray:
    """Pad the local shard with ``halo`` slabs from its mesh neighbors.

    Returns an array whose extent along ``axis`` is ``x.shape[axis] + 2*halo``.
    At the mesh ends (rank 0 low side, rank S-1 high side) the halo is filled
    with ``fill`` — matching the reference's border-clipped halo semantics
    where kernels receive a validity mask / padded border instead.

    ``axis_size`` is the static size of the mesh axis (shard_map callers know
    it from the mesh).
    """
    if halo <= 0:
        return x
    if x.shape[axis] < halo:
        raise ValueError(
            f"shard extent {x.shape[axis]} along axis {axis} smaller than halo {halo}"
        )
    n = int(axis_size)
    idx = lax.axis_index(axis_name)
    lo_slab = lax.slice_in_dim(x, 0, halo, axis=axis)
    hi_slab = lax.slice_in_dim(x, x.shape[axis] - halo, x.shape[axis], axis=axis)
    # my low rows -> previous rank's high halo; my high rows -> next rank's low
    halo_hi = lax.ppermute(
        lo_slab, axis_name, [(i, i - 1) for i in range(1, n)]
    )
    halo_lo = lax.ppermute(
        hi_slab, axis_name, [(i, i + 1) for i in range(n - 1)]
    )
    # ppermute zero-fills ranks that receive nothing; rewrite with `fill` when
    # a non-zero border fill is requested (e.g. +inf heights, True masks)
    if not (isinstance(fill, (int, float)) and fill == 0):
        halo_hi = jnp.where(idx == n - 1, jnp.full_like(halo_hi, fill), halo_hi)
        halo_lo = jnp.where(idx == 0, jnp.full_like(halo_lo, fill), halo_lo)
    return jnp.concatenate([halo_lo, x, halo_hi], axis=axis)


def crop_halo(x: jnp.ndarray, halo: int, axis: int) -> jnp.ndarray:
    """Inverse of :func:`exchange_halo`: drop ``halo`` slabs from both ends."""
    if halo <= 0:
        return x
    return lax.slice_in_dim(x, halo, x.shape[axis] - halo, axis=axis)


def neighbor_face(
    x: jnp.ndarray,
    axis: int,
    axis_name: str,
    axis_size: int,
    direction: int = -1,
    fill=0,
) -> jnp.ndarray:
    """The 1-voxel face of the neighboring shard adjacent to this shard.

    ``direction=-1`` returns the *previous* rank's last slab (the face just
    below this shard's first voxel); ``direction=+1`` the next rank's first
    slab.  Used by the distributed label merge to emit cross-shard
    equivalences without a full halo exchange.
    """
    n = int(axis_size)
    idx = lax.axis_index(axis_name)
    if direction == -1:
        slab = lax.slice_in_dim(x, x.shape[axis] - 1, x.shape[axis], axis=axis)
        out = lax.ppermute(slab, axis_name, [(i, i + 1) for i in range(n - 1)])
        edge = idx == 0
    elif direction == 1:
        slab = lax.slice_in_dim(x, 0, 1, axis=axis)
        out = lax.ppermute(slab, axis_name, [(i, i - 1) for i in range(1, n)])
        edge = idx == n - 1
    else:
        raise ValueError(f"direction must be +/-1, got {direction}")
    if not (isinstance(fill, (int, float)) and fill == 0):
        out = jnp.where(edge, jnp.full_like(out, fill), out)
    return out
