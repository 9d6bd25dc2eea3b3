"""Euclidean distance transform as a separable, dense device kernel.

The reference used ``vigra.filters.distanceTransform`` (C++ Felzenszwalb-style
lower-envelope scan; SURVEY.md §2b).  The envelope scan is inherently
sequential per line and hostile to a vector unit, so this redesign uses the
*parabolic erosion cascade* (van den Boomgaard's decomposition of quadratic
structuring functions): the per-axis min-plus transform

    g[i] = min_j ( f[j] + w * (i - j)^2 )

equals ``r`` iterated erosions with the 3-tap kernel ``[c_i, 0, c_i]`` where
``c_i = w * (2i - 1)`` — because the k smallest odd increments sum to
``w * k^2``, a voxel reached over offset ``k`` accumulates exactly the
parabola cost.  Each iteration is an elementwise min of three shifted arrays:
no (n, n) intermediate, pure VPU work, fused by XLA into a few
bandwidth-bound loops.  ``r = n`` gives the exact transform; smaller ``r``
gives the transform capped at radius ``r`` per axis (all values below the cap
are exact) — the natural choice inside blockwise pipelines where distances
beyond the block/halo scale are meaningless.

Supports anisotropic ``sampling`` (e.g. CREMI's (40, 4, 4) nm voxels).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# numpy (not jnp) so importing this module never triggers jax backend
# initialization — with the TPU plugin registered that would dial the chip
# at import time
_BIG = np.float32(1e12)

# cascade iterations are sequential full-volume passes; above this radius the
# one-shot broadcast min-plus (O(n) parallel work per output, fully fusable)
# wins over an O(radius)-deep dependent-kernel chain
_CASCADE_MAX_RADIUS = 160


def _edt_1d_axis_bcast(f: jnp.ndarray, axis: int, w: float) -> jnp.ndarray:
    """One-shot min-plus: g[..., i] = min_j f[..., j] + w*(i-j)^2 along axis."""
    n = f.shape[axis]
    f = jnp.moveaxis(f, axis, -1)
    i = jnp.arange(n, dtype=jnp.float32)
    dist = (i[:, None] - i[None, :]) ** 2 * jnp.float32(w)  # [j, i]
    g = jnp.min(f[..., :, None] + dist, axis=-2)
    return jnp.moveaxis(g, -1, axis)


def _edt_1d_axis(f: jnp.ndarray, axis: int, w: float, radius: int) -> jnp.ndarray:
    """Parabolic erosion along ``axis``: min_j f[j] + w*(i-j)^2, |i-j| <= radius."""
    n = f.shape[axis]
    radius = min(radius, n - 1)
    if radius <= 0:
        return f
    if radius > _CASCADE_MAX_RADIUS:
        return _edt_1d_axis_bcast(f, axis, w)
    pad_shape = list(f.shape)
    pad_shape[axis] = 1
    pad = jnp.full(pad_shape, _BIG, dtype=f.dtype)

    def shift(x, direction):
        if direction > 0:
            body = lax.slice_in_dim(x, 0, n - 1, axis=axis)
            return jnp.concatenate([pad, body], axis=axis)
        body = lax.slice_in_dim(x, 1, n, axis=axis)
        return jnp.concatenate([body, pad], axis=axis)

    def body(i, g):
        c = jnp.float32(w) * (2.0 * i.astype(jnp.float32) + 1.0)
        lo = shift(g, +1) + c
        hi = shift(g, -1) + c
        return jnp.minimum(g, jnp.minimum(lo, hi))

    return lax.fori_loop(0, radius, body, f)


@partial(jax.jit, static_argnames=("sampling", "radii", "impl", "interpret"))
@jax.named_scope("edt")
def _dt_squared_impl(
    mask: jnp.ndarray,
    sampling: Tuple[float, ...],
    radii: Tuple[int, ...],
    impl: str = "auto",
    interpret: bool = False,
) -> jnp.ndarray:
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    f = jnp.where(mask, _BIG, jnp.float32(0.0))
    if impl == "pallas" and mask.ndim == 3:
        return _dt_squared_pallas(f, sampling, radii, interpret=interpret)
    for axis in range(mask.ndim):
        f = _edt_1d_axis(f, axis, float(sampling[axis]) ** 2, radii[axis])
    return jnp.minimum(f, _BIG)


def _pad_to_mosaic_tiles(f: jnp.ndarray):
    """Pad a 3-D array up to the Mosaic (8, 8, 128) tile multiples with
    +BIG (pad values never win a min).  Returns (padded, original_shape)."""
    z, y, x = f.shape
    zp = -(-z // 8) * 8
    yp = -(-y // 8) * 8
    xp = -(-x // 128) * 128
    if (zp, yp, xp) != (z, y, x):
        f = jnp.pad(
            f, ((0, zp - z), (0, yp - y), (0, xp - x)), constant_values=_BIG
        )
    return f, (z, y, x)


def _pallas_axis_cascade(
    f: jnp.ndarray, axis: int, w: float, radius: int, interpret: bool = False
) -> jnp.ndarray:
    """One VMEM erosion cascade along ``axis`` (padded lanes cropped after)."""
    from .pallas_kernels import edt_cascade_pallas

    f, (z, y, x) = _pad_to_mosaic_tiles(f)
    f = edt_cascade_pallas(f, axis, radius, w, float(_BIG), interpret=interpret)
    return f[:z, :y, :x]


@jax.named_scope("edt")
def edt_axis_pass(
    f: jnp.ndarray, axis: int, w: float, radius: int, impl: str = "auto"
) -> jnp.ndarray:
    """One separable min-plus (parabolic erosion) pass along ``axis``.

    Public building block for composed transforms — in particular the
    mesh-distributed exact EDT, which reshards the volume between per-axis
    passes (:mod:`cluster_tools_tpu.parallel.distributed_edt`).  ``w`` is
    the squared per-axis voxel size; ``radius`` caps the pass (values up to
    the cap exact).
    """
    radius = min(int(radius), f.shape[axis] - 1)
    if radius <= 0:
        return f
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas" and f.ndim == 3:
        return _pallas_axis_cascade(f, axis, float(w), radius)
    return _edt_1d_axis(f, axis, float(w), radius)


def _dt_squared_pallas(
    f: jnp.ndarray,
    sampling: Tuple[float, ...],
    radii: Tuple[int, ...],
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-axis VMEM erosion cascades, one shared pad across all three axes
    (see :func:`_pad_to_mosaic_tiles`)."""
    from .pallas_kernels import edt_cascade_pallas

    f, (z, y, x) = _pad_to_mosaic_tiles(f)
    for axis in range(3):
        r = min(radii[axis], f.shape[axis] - 1)
        if r > 0:
            f = edt_cascade_pallas(
                f, axis, r, float(sampling[axis]) ** 2, float(_BIG),
                interpret=interpret,
            )
    return jnp.minimum(f[:z, :y, :x], _BIG)


def _norm_sampling(ndim: int, sampling) -> Tuple[float, ...]:
    if sampling is None:
        return (1.0,) * ndim
    sampling = tuple(float(s) for s in np.atleast_1d(sampling))
    if len(sampling) == 1:
        sampling = sampling * ndim
    if len(sampling) != ndim:
        raise ValueError(f"sampling {sampling} has wrong rank for ndim {ndim}")
    return sampling


def distance_transform_squared(
    mask: jnp.ndarray,
    sampling: Optional[Sequence[float]] = None,
    max_distance: Optional[float] = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """Squared EDT of a boolean mask: distance to the nearest background voxel.

    Foreground voxels get the squared distance to the nearest ``False`` voxel;
    background voxels get 0.  If the block contains no background, foreground
    saturates at a large constant (callers clip or don't care — matches the
    halo-read semantics where blocks always see some context).  ``sampling``
    may be a scalar, list, tuple, or array of per-axis voxel sizes.

    ``max_distance`` caps the transform: values up to the cap are exact,
    larger distances saturate (at least ``max_distance**2``).  Inside
    blockwise pipelines pass the halo/seed scale — the cascade cost is linear
    in the per-axis radius, so a cap turns O(n) iterations into O(cap).

    ``impl``: "auto" (VMEM cascade kernel on TPU, XLA elsewhere), "pallas",
    or "xla".
    """
    sampling = _norm_sampling(mask.ndim, sampling)
    if max_distance is None:
        radii = tuple(n - 1 for n in mask.shape)
    else:
        radii = tuple(
            int(np.ceil(float(max_distance) / s)) for s in sampling
        )
    return _dt_squared_impl(mask, sampling, radii, impl=impl)


def distance_transform(
    mask: jnp.ndarray,
    sampling: Optional[Sequence[float]] = None,
    max_distance: Optional[float] = None,
) -> jnp.ndarray:
    """Exact Euclidean distance transform (sqrt of the squared EDT)."""
    return jnp.sqrt(
        distance_transform_squared(mask, sampling=sampling, max_distance=max_distance)
    )
