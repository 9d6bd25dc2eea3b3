"""The plain reference of the per-section (2-D) blockwise watershed.

numpy / scipy only; imports nothing of the program (only the single-pass
reference beside it, ``benchmark/reference.py``, whose EDT, plateaus,
descent and flood are taken here one plane at a time: a plane is a stack of
one, along whose length-one axis nothing is compared or joined).

What the configuration ``ws_2d_cremi_125`` states (upstream's
``apply_dt_2d`` + ``apply_ws_2d``), and what is held here, per z-plane of a
*unit* (one block with its in-plane halo, clipped by the stack, padded at
the far side with 1.0):

* ``fg = boundaries < threshold`` in float32; the squared distance of every
  ``fg`` voxel to the nearest voxel of its plane outside ``fg``, over a
  window of ``radius`` voxels along y and along x, in exact integers
  (in-plane sampling (1, 1)); ``_FAR`` where the window holds none;
* seeds: the 4-connected plateaus of that distance's local maxima inside
  ``fg`` (a voxel no lower than any of its four in-plane neighbours);
* every voxel drains to the lowest (height, then flat index) voxel of its
  closed 4-neighbourhood; a basin without a seed joins its neighbour across
  its lowest saddle, lowest first, equal saddles by the face that comes
  first in (axis, position); two seeded basins never join.

No voxel is compared with, joined to or drained into another plane, so a
fragment lies in one plane.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

from benchmark import reference as ref


def in_plane_radius(dt_max_distance: float, sampling: Sequence[float]) -> int:
    """The EDT window in voxels: ``dt_max_distance`` in units of the in-plane
    voxel, which has to be (1, 1) here (z's entry is not used)."""
    if tuple(float(s) for s in sampling[1:]) != (1.0, 1.0):
        raise ValueError(f"in-plane sampling {tuple(sampling[1:])} is not (1, 1)")
    return int(np.ceil(float(dt_max_distance)))


def plane_seeds(plane: np.ndarray, *, threshold: float, radius: int
                ) -> Tuple[np.ndarray, int]:
    """Seed plateaus of one plane (2-D ``height``), labelled 1..n."""
    fg = plane[None] < np.float32(threshold)
    dist = ref.windowed_edt_sq(fg, radius)
    seeds, n = ref.seed_plateaus(fg, dist)
    return seeds[0], int(n)


def flood_unit(height: np.ndarray, *, threshold: float, radius: int
               ) -> Tuple[np.ndarray, np.ndarray, int]:
    """The reference's fragments of a unit, plane by plane: ``(tree, seeds,
    n_seeds)``; ``seeds`` numbers every plateau of the unit (plane after
    plane, 1..n_seeds) and ``tree`` holds for every voxel the seed whose
    fragment it belongs to (0 where none reaches it)."""
    tree = np.zeros(height.shape, np.int64)
    seeds = np.zeros(height.shape, np.int64)
    n_seeds = 0
    for z in range(height.shape[0]):
        s, n = plane_seeds(height[z], threshold=threshold, radius=radius)
        t = ref.reference_flood(height[z][None], s[None], n)[0]
        seeds[z] = np.where(s > 0, s + n_seeds, 0)
        tree[z] = np.where(t > 0, t + n_seeds, 0)
        n_seeds += n
    return tree, seeds, n_seeds


def descent_differs(height: np.ndarray, labels: np.ndarray) -> np.ndarray:
    """True where a voxel's label differs from that of the voxel it drains
    to, the lowest (height, flat index) of its closed 4-neighbourhood."""
    return np.stack([ref.descent_target_differs(height[z][None], labels[z][None])[0]
                     for z in range(height.shape[0])])


def place_labels(tree: np.ndarray, seeds: np.ndarray, n_seeds: int) -> np.ndarray:
    """``tree`` numbered as the program numbers a unit's fragments: the least
    flat index in the unit of the seed's voxels, plus one (0 where none)."""
    place = np.zeros(n_seeds + 1, np.int64)
    at = np.flatnonzero(seeds.ravel() > 0)[::-1]
    place[seeds.ravel()[at]] = at + 1      # the least flat index wins
    return place[tree]
