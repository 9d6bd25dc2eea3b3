"""Deterministic synthetic EM volumes with exact ground truth.

The reference's test strategy is anchored on a CREMI-derived EM crop
(SURVEY.md §4): anisotropic sampling (40, 4, 4) nm, cell-body objects with
membrane boundaries, an ignore mask.  No real data ships with this repo, so
this generator produces the same *shape* of problem with a known answer:

- ground truth = anisotropic Voronoi cells of Poisson-sampled centers
  (convex-ish polyhedra, columnar under the z-anisotropy — the right
  geometry class for sectioned EM at this scale),
- boundary map = exponential falloff from the inter-cell interfaces with
  optional smoothing and additive noise (membrane-like ridges),
- mask = inscribed ellipsoid (the "bounding nucleus / padding" pattern).

Everything derives from one rng seed; the GT is exact by construction, so
end-to-end segmentation quality (VI / adapted-RAND vs GT) is a meaningful
assertion rather than a smoke check.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def synthetic_em_volume(
    shape: Tuple[int, int, int] = (24, 96, 96),
    n_objects: int = 12,
    sampling: Sequence[float] = (40.0, 4.0, 4.0),
    boundary_width: float = 2.0,
    noise: float = 0.05,
    smooth: float = 0.7,
    with_mask: bool = True,
    seed: int = 0,
):
    """Returns ``(boundaries float32 [0,1], gt uint64, mask bool)``.

    ``boundary_width`` is the membrane falloff scale in (in-plane) voxel
    units.  Labels are 1..n_objects, 0 only outside the mask.
    """
    rng = np.random.default_rng(seed)
    shape = tuple(int(s) for s in shape)
    samp = np.asarray(sampling, np.float64)

    # Poisson-sampled centers in physical coordinates
    phys = np.array(shape) * samp
    centers = rng.random((n_objects, 3)) * phys

    # per-axis physical coordinates, broadcast to (z, y, x) on use: a
    # materialised (z, y, x, 3) grid costs 400 MB at 256^3 and most of the
    # generator's time (same values, summed in the same z, y, x order)
    az, ay, ax = (
        (np.arange(n) * s)[sl]
        for n, s, sl in zip(
            shape, samp,
            ((slice(None), None, None), (None, slice(None), None),
             (None, None, slice(None))),
        )
    )

    # nearest-center distances -> GT cells (anisotropic Voronoi)
    d = np.full(shape, np.inf)
    gt = np.zeros(shape, np.uint64)
    for i, c in enumerate(centers):
        di = np.sqrt(
            ((az - c[0]) ** 2 + (ay - c[1]) ** 2) + (ax - c[2]) ** 2
        )
        closer = di < d
        d = np.where(closer, di, d)
        gt[closer] = i + 1

    # membrane map: voxel-space falloff from the exact GT interfaces (a
    # physical-metric falloff would fade z-interfaces by the anisotropy —
    # the nearest voxel to a z-interface sits half a 40nm step away)
    from scipy import ndimage

    # single-sided marking: membranes are ONE voxel thick (the lower-index
    # voxel of each differing pair) — hole-free for 6-connected paths, and
    # thin membranes keep the ambiguous-ownership band small relative to the
    # cells (the quality metrics are computed over every voxel)
    interfaces = np.zeros(shape, bool)
    for axis in range(3):
        a = [slice(None)] * 3
        b = [slice(None)] * 3
        a[axis] = slice(0, -1)
        b[axis] = slice(1, None)
        diff = gt[tuple(a)] != gt[tuple(b)]
        interfaces[tuple(a)] |= diff
    # mild z-weighting keeps membranes one-ish section thick, as in
    # section-imaged EM
    vox_dist = ndimage.distance_transform_edt(~interfaces, sampling=(2.0, 1.0, 1.0))
    boundaries = np.exp(-vox_dist / max(boundary_width, 1e-6))
    if smooth > 0:
        boundaries = ndimage.gaussian_filter(boundaries, smooth)
    if noise > 0:
        boundaries = boundaries + rng.normal(0, noise, shape)
    boundaries = np.clip(boundaries, 0.0, 1.0).astype(np.float32)

    if with_mask:
        rz, ry, rx = (
            ((a / p) * 2.0 - 1.0) ** 2 for a, p in zip((az, ay, ax), phys)
        )
        mask = ((rz + ry) + rx) <= 1.0
    else:
        mask = np.ones(shape, bool)
    gt = np.where(mask, gt, 0).astype(np.uint64)
    return boundaries, gt, mask


def grid_rag(
    g: int = 16, seed: int = 0, mu: float = 0.2, sigma: float = 1.0
) -> Tuple[int, np.ndarray, np.ndarray]:
    """Grid-adjacency RAG (the shape of watershed-fragment graphs) with
    noisy signed costs: mostly-attractive with repulsive salt, nothing
    planted — the adversarial regime for greedy-order differences between
    agglomeration solvers.  Returns ``(n_nodes, edges [m, 2], costs [m])``.
    Shared by the contraction oracle tests and bench's solver-scale record
    so both measure the same instance family."""
    rng = np.random.default_rng(seed)
    n = g**3
    ids = np.arange(n).reshape(g, g, g)
    parts = []
    for ax in range(3):
        a = np.moveaxis(ids, ax, 0)[:-1].ravel()
        b = np.moveaxis(ids, ax, 0)[1:].ravel()
        parts.append(np.stack([a, b], 1))
    edges = np.concatenate(parts)
    costs = rng.normal(mu, sigma, len(edges))
    return n, edges, costs
