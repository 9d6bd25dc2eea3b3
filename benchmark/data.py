"""The benchmark's seeded data: membrane maps of Voronoi cells, made on the device.

The picture is that of the program's ``utils/synthetic.py`` (Voronoi cells, a
membrane map that falls off as ``exp(-distance / width)`` from the cell
interfaces, a little Gaussian smoothing, no additive noise), but the
yardstick's data must not change when the program's generator does, and
every run of every later check pays for it, so it is written again here for
speed: the distance of a voxel to its cell's boundary is the least of its
distances to the bisector planes (exact for convex cells), which needs no
distance transform, and the whole volume is made in one jitted call from the
seed.

The centres are drawn uniformly over the volume, as ``utils/synthetic.py``
draws them for ``chip_smoke.py``, so cells come in every size and a seed's
job can take a tenth longer than another's.  That spread is the workload's:
the data are not shaped to steady a metric (PERF.md section 6 has the
lattice that an earlier draft used, and why it went).

The program never sees this module; it reads the volumes from the store.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def fold_seed(seed: int, *salt: int) -> np.random.Generator:
    """A generator for ``--seed`` (any whole number up to a little over
    2**31) and a purpose; the same seed gives the same stream."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, *[int(s) for s in salt]])


def cell_centres(seed: int, index: int, shape, cells: int) -> np.ndarray:
    """``cells`` centres drawn uniformly over ``shape``."""
    rng = fold_seed(seed, 1, index)
    return (rng.random((int(cells), 3)) * np.asarray(shape, np.float64)).astype(np.float32)


@partial(jax.jit, static_argnames=("shape", "slab", "width", "smooth"))
def _membranes(centres, *, shape, slab, width, smooth):
    nz, ny, nx = shape
    n = centres.shape[0]
    # |c_i - c_j| for the bisector planes; the diagonal never wins
    gap = jnp.sqrt(((centres[:, None, :] - centres[None, :, :]) ** 2).sum(-1))
    gap = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, gap)
    ay = jnp.arange(ny, dtype=jnp.float32)[None, :, None]
    ax = jnp.arange(nx, dtype=jnp.float32)[None, None, :]

    def one_slab(z0):
        az = (z0 + jnp.arange(slab, dtype=jnp.float32))[:, None, None]
        d2 = (
            (az[None] - centres[:, 0, None, None, None]) ** 2
            + (ay[None] - centres[:, 1, None, None, None]) ** 2
            + (ax[None] - centres[:, 2, None, None, None]) ** 2
        )  # [n, slab, ny, nx]
        own = jnp.argmin(d2, axis=0)
        d2_own = jnp.min(d2, axis=0)
        # gap between the voxel's own centre and every other one, by a
        # one-hot product (a gather of rows is slow on the chip)
        onehot = (own[None] == jnp.arange(n)[:, None, None, None]).astype(jnp.float32)
        gap_own = jnp.einsum("nzyx,nm->mzyx", onehot, jnp.where(jnp.isinf(gap), 0.0, gap))
        to_plane = (d2 - d2_own[None]) / (2.0 * jnp.maximum(gap_own, 1e-6))
        to_plane = jnp.where(onehot > 0, jnp.inf, to_plane)
        return jnp.exp(-jnp.min(to_plane, axis=0) / width)

    z0s = jnp.arange(0, nz, slab, dtype=jnp.float32)
    vol = jax.lax.map(one_slab, z0s).reshape(nz, ny, nx)
    if smooth > 0:
        r = max(1, int(4.0 * smooth + 0.5))
        k = jnp.exp(-0.5 * (jnp.arange(-r, r + 1, dtype=jnp.float32) / smooth) ** 2)
        k = k / k.sum()
        for axis in range(3):
            v = jnp.moveaxis(vol, axis, 0)
            p = jnp.pad(v, ((r, r), (0, 0), (0, 0)), mode="edge")
            v = sum(k[i] * p[i : i + v.shape[0]] for i in range(2 * r + 1))
            vol = jnp.moveaxis(v, 0, axis)
    return jnp.clip(vol, 0.0, 1.0).astype(jnp.float32)


def membrane_volume(seed: int, index: int, shape, cells: int,
                    width: float = 2.0, smooth: float = 0.7) -> np.ndarray:
    """Boundary map (float32 in [0, 1], high on membranes) number ``index``
    of ``seed``, made on JAX's default device and fetched to the host."""
    shape = tuple(int(s) for s in shape)
    # one slab of z at a time where a thicker one's temporaries ([cells, slab,
    # ny, nx] float32, a few at once) would pass 64 MiB each: what the
    # generator reserves on the chip stays far under what a job does
    slab = next((s for s in (4, 2) if shape[0] % s == 0
                 and int(cells) * s * shape[1] * shape[2] * 4 <= 1 << 26), 1)
    centres = jnp.asarray(cell_centres(seed, index, shape, cells))
    vol = _membranes(centres, shape=shape, slab=slab, width=float(width),
                     smooth=float(smooth))
    out = np.asarray(vol)
    vol.delete()
    return out
