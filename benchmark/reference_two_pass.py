"""The plain reference of the two-pass (checkerboard) blockwise watershed.

numpy / scipy only; imports nothing of the program (only the single-pass
reference beside it, ``benchmark/reference.py``, for what the two share:
the store reader, the seeds' plateaus, the descent and the flood).

What the configuration ``ws_two_pass_cremi_125`` states, and what is held
here, per *unit* (one block with its halo, clipped by the stack, padded at
the far side with 1.0):

* ``fg = boundaries < threshold`` in float32; the squared distance of every
  ``fg`` voxel to the nearest voxel outside ``fg`` under ``sampling``
  (``sum((s_a * d_a) ** 2)``, exact integers for integer sampling) over a
  window of ``radii`` voxels per axis (``ceil(dt_max_distance / s_a)``);
  internal seeds = 6-connected plateaus of its local maxima inside ``fg``.
* **Pass one** (blocks whose grid position sums to an even number) floods
  from its internal seeds alone.  **Pass two** (odd sums) floods from the
  labels that even-parity blocks stored inside its halo, as external seeds
  that dominate (an internal seed voxel under an external seed is the
  external seed's), plus its internal seeds elsewhere; a voxel reached from
  an external seed carries that label unchanged.
* The flood is the single-pass reference's: steepest descent by (height,
  flat index) with every seed voxel a sink, a seedless basin joined across
  its exact lowest saddle, two seeded basins never joined.  All voxels of
  one external label are one seed, wherever they lie.
* A new label is ``block number * (voxels of an outer block + 1) + place``
  with ``place`` in ``1 .. voxels of an outer block``.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark import reference as ref

_FAR = ref._FAR


# --------------------------------------------------------------------------
# geometry: the block grid, parities, a block's unit
# --------------------------------------------------------------------------


def block_grid(shape: Sequence[int], block: Sequence[int]) -> Tuple[int, ...]:
    return tuple(-(-int(s) // int(b)) for s, b in zip(shape, block))


def blocks_of(shape: Sequence[int], block: Sequence[int]) -> List[Tuple[int, Tuple[int, ...]]]:
    """(block number, grid position) of every block, numbered in C order of
    the grid, as the program's ``Blocking`` numbers them."""
    grid = block_grid(shape, block)
    return list(enumerate(itertools.product(*(range(g) for g in grid))))


def parity_of(pos: Sequence[int]) -> int:
    return int(sum(pos)) % 2


def unit_bounds(pos, shape, block, halo):
    """Inner block and outer block (clipped by the stack) of grid position
    ``pos``: ``(lo, hi, olo, ohi)`` in stack coordinates."""
    lo = [p * b for p, b in zip(pos, block)]
    hi = [min(l + b, s) for l, b, s in zip(lo, block, shape)]
    olo = [max(l - h, 0) for l, h in zip(lo, halo)]
    ohi = [min(e + h, s) for e, h, s in zip(hi, halo, shape)]
    return lo, hi, olo, ohi


def outer_shape(block, halo) -> Tuple[int, ...]:
    return tuple(int(b) + 2 * int(h) for b, h in zip(block, halo))


def unit_labels(ws: np.ndarray, pos, block, halo):
    """The stored labels over the unit of grid position ``pos`` (0 in the
    padding), ``even`` (True where a voxel belongs to an even-parity block
    of the grid, the padding counted on as if the grid went on), ``inner``
    (the block itself inside the unit) and the padding that took the
    clipped outer block to block + 2 * halo."""
    lo, hi, olo, ohi = unit_bounds(pos, ws.shape, block, halo)
    outer = outer_shape(block, halo)
    pad = [(0, o - (b - a)) for o, a, b in zip(outer, olo, ohi)]
    labels = np.pad(ws[tuple(slice(a, b) for a, b in zip(olo, ohi))], pad)
    grids = np.ix_(*((np.arange(a, a + o) // b)
                     for a, o, b in zip(olo, outer, block)))
    inner = tuple(slice(l - o, h - o) for l, h, o in zip(lo, hi, olo))
    return labels, sum(grids) % 2 == 0, inner, pad


def unit_of(vol: np.ndarray, ws: np.ndarray, pos, block, halo):
    """The unit of grid position ``pos`` as the kernel saw it: ``height``
    (outer block, padded at the far side with 1.0), and ``labels``,
    ``even``, ``inner`` as :func:`unit_labels` gives them."""
    labels, even, inner, pad = unit_labels(ws, pos, block, halo)
    _, _, olo, ohi = unit_bounds(pos, vol.shape, block, halo)
    height = np.pad(vol[tuple(slice(a, b) for a, b in zip(olo, ohi))], pad,
                    constant_values=np.float32(1.0))
    return height, labels, even, inner


# --------------------------------------------------------------------------
# the reference itself
# --------------------------------------------------------------------------


def window_radii(dt_max_distance: float, sampling: Sequence[float]) -> Tuple[int, ...]:
    return tuple(int(np.ceil(float(dt_max_distance) / float(s))) for s in sampling)


def windowed_edt_sq(fg: np.ndarray, sampling: Sequence[int],
                    radii: Sequence[int]) -> np.ndarray:
    """Squared distance (int32) of every ``fg`` voxel to the nearest voxel
    outside ``fg`` whose offset is at most ``radii[a]`` voxels along axis
    ``a``, an offset of ``d`` voxels along ``a`` counting ``(sampling[a] *
    d) ** 2``; ``_FAR`` where there is none; 0 outside ``fg``.  Beyond the
    array there is nothing.  Exact: separable min-plus over the window, in
    integers (``sampling`` has to be whole numbers)."""
    if any(float(s) != int(s) for s in sampling):
        raise ValueError(f"sampling {sampling} is not in whole numbers")
    f = np.where(fg, _FAR, np.int32(0)).astype(np.int32)
    for axis in range(f.ndim):
        w = int(sampling[axis]) ** 2
        f = np.moveaxis(f, axis, 0)
        out = f.copy()
        for k in range(1, min(int(radii[axis]), f.shape[0] - 1) + 1):
            cost = np.int32(w * k * k)
            np.minimum(out[k:], f[:-k] + cost, out=out[k:])
            np.minimum(out[:-k], f[k:] + cost, out=out[:-k])
        f = np.moveaxis(np.minimum(out, _FAR), 0, axis)
    return np.ascontiguousarray(f)


def dense_external(labels: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """External labels (uint64, 0 = none) as dense ids 1..K in rising order
    of the label, and the table that takes an id back to its label."""
    table = np.unique(labels[labels > 0])
    dense = np.zeros(labels.shape, np.int64)
    if len(table):
        dense = np.searchsorted(table, labels).astype(np.int64) + 1
        dense[labels == 0] = 0
    return dense, table


def unit_seeds(height: np.ndarray, ext: np.ndarray, *, threshold: float,
               sampling: Sequence[int], radii: Sequence[int]
               ) -> Tuple[np.ndarray, int, int]:
    """Seeds of one unit: internal plateaus 1..n_int where no external seed
    lies, external seed k (dense, 1..K) as ``n_int + k``.  Returns
    ``(seeds, n_int, n_seeds)``."""
    fg = height < np.float32(threshold)
    dist = windowed_edt_sq(fg, sampling, radii)
    internal, n_int = ref.seed_plateaus(fg, dist)
    seeds = np.where(ext > 0, ext + n_int, internal.astype(np.int64))
    return seeds, int(n_int), int(n_int + ext.max(initial=0))


def flood_unit(height: np.ndarray, ext_labels: np.ndarray, *, threshold: float,
               sampling: Sequence[int], radii: Sequence[int]
               ) -> Tuple[np.ndarray, np.ndarray, int, np.ndarray]:
    """The reference's fragments of one unit: ``(tree, seeds, n_int,
    table)``; ``tree`` holds for every voxel the seed (as :func:`unit_seeds`
    numbers them) whose fragment it belongs to, ``table`` takes an external
    seed ``n_int + k`` to its label ``table[k - 1]``.  Pass one is the case
    of no external label."""
    ext, table = dense_external(ext_labels)
    seeds, n_int, n_seeds = unit_seeds(height, ext, threshold=threshold,
                                       sampling=sampling, radii=radii)
    tree = ref.reference_flood(height, seeds, n_seeds)
    return tree, seeds, n_int, table


def encode(block_number: int, n_outer: int, place: np.ndarray) -> np.ndarray:
    """A new label: ``block number * (n_outer + 1) + place`` (uint64), 0
    where ``place`` is 0."""
    place = np.asarray(place).astype(np.uint64)
    return np.where(place > 0,
                    np.uint64(block_number) * np.uint64(n_outer + 1) + place,
                    np.uint64(0))


def block_of_label(labels: np.ndarray, n_outer: int) -> np.ndarray:
    """The block number a new label was born in (meaningless for 0)."""
    return (np.asarray(labels, np.uint64) // np.uint64(n_outer + 1)).astype(np.int64)


def reference_labels(vol: np.ndarray, *, block, halo, threshold: float,
                     sampling, dt_max_distance: float) -> np.ndarray:
    """The whole two-pass watershed, plainly: pass one over the even blocks,
    then pass two over the odd ones, each unit flooded whole and its block
    stored.  A new fragment's place is the least flat index in the unit of
    its seed's voxels, plus one (the program may number a fragment by any
    place of its own: what is compared is the partition and the block
    number).  For tests at small sizes."""
    radii = window_radii(dt_max_distance, sampling)
    outer = outer_shape(block, halo)
    n_outer = int(np.prod(outer))
    ws = np.zeros(vol.shape, np.uint64)
    for parity in (0, 1):
        for number, pos in blocks_of(vol.shape, block):
            if parity_of(pos) != parity:
                continue
            height, labels, even, inner = unit_of(vol, ws, pos, block, halo)
            ext_labels = np.where(even, labels, np.uint64(0)) if parity else np.zeros_like(labels)
            tree, seeds, n_int, table = flood_unit(
                height, ext_labels, threshold=threshold, sampling=sampling, radii=radii)
            place = np.zeros(n_int + 1, np.int64)
            at = np.flatnonzero((seeds.ravel() > 0) & (seeds.ravel() <= n_int))[::-1]
            place[seeds.ravel()[at]] = at + 1      # the least flat index wins
            new = encode(number, n_outer,
                         np.where(tree <= n_int, place[np.minimum(tree, n_int)], 0))
            old = np.concatenate([[np.uint64(0)], table])[np.maximum(tree - n_int, 0)]
            out = np.where(tree > n_int, old, new)
            lo, hi, _, _ = unit_bounds(pos, vol.shape, block, halo)
            ws[tuple(slice(a, b) for a, b in zip(lo, hi))] = out[inner]
    return ws


# --------------------------------------------------------------------------
# one unit of a job against the reference; every limit is 0
# --------------------------------------------------------------------------


def compare_unit(height: np.ndarray, labels: np.ndarray, even: np.ndarray,
                 inner: Tuple[slice, slice, slice], *, odd: bool, threshold: float,
                 sampling, radii) -> Dict[str, int]:
    """One unit's stored labels against the reference.

    ``height`` is the unit as the kernel saw it, ``labels`` what the store
    holds over the same extent (the block's own labels inside ``inner``, its
    neighbours' around it), ``even`` where an even-parity block owns the
    voxel.  For a unit of pass two (``odd``) the external seeds are the
    stored labels under ``even``.  Only ``inner`` is compared: it is what
    this unit's flood stored."""
    ext_labels = np.where(even, labels, np.uint64(0)) if odd else np.zeros_like(labels)
    tree, seeds, n_int, table = flood_unit(height, ext_labels, threshold=threshold,
                                           sampling=sampling, radii=radii)
    ws_in, seeds_in, tree_in = labels[inner], seeds[inner], tree[inner]
    out: Dict[str, int] = {}

    # internal seeds <-> new fragments, one to one, as far as the block shows
    at = (seeds_in > 0) & (seeds_in <= n_int)
    s_ids, s_lab = seeds_in[at], ws_in[at]
    first = np.zeros(n_int + 1, ws_in.dtype)
    first[s_ids] = s_lab
    torn = np.unique(s_ids[first[s_ids] != s_lab])       # a plateau with two labels
    seen = np.unique(s_ids)
    labels_of_seeds = first[seen]
    uniq, counts = np.unique(labels_of_seeds, return_counts=True)
    shared = int(np.count_nonzero(np.isin(labels_of_seeds, uniq[counts > 1])))
    face = np.zeros(ws_in.shape, bool)
    for axis in range(3):
        sl = [slice(None)] * 3
        for side in (0, -1):
            sl[axis] = side
            face[tuple(sl)] = True
    # a fragment that does not touch the block's faces is whole here, so its
    # one seed is in here too (an external fragment enters through a face)
    whole = np.setdiff1d(np.unique(ws_in[ws_in > 0]), np.unique(ws_in[face]))
    seedless = len(np.setdiff1d(whole, labels_of_seeds))
    out["ws_seed_mismatch"] = int(len(torn) + shared + seedless)

    # the descent: away from the seeds every voxel is labelled as the voxel
    # it drains to; looked at one voxel inside the block's faces, so that
    # both ends of every pair were stored by this unit
    grow = tuple(slice(max(s.start - 1, 0), s.stop + 1) for s in inner)
    differs = ref.descent_target_differs(height[grow], labels[grow])
    core = tuple(slice(s.start - g.start + 1, s.stop - g.start - 1)
                 for s, g in zip(inner, grow))
    out["ws_descent_mismatch"] = int(np.count_nonzero(
        differs[core] & (seeds[grow][core] == 0) & (labels[grow][core] > 0)))

    # the flood: the reference's fragments and the stored ones are the same
    # partition of the block
    both = (tree_in > 0) & (ws_in > 0)
    pairs = np.unique(np.stack([tree_in[both], ws_in[both].astype(np.int64)]), axis=1)
    _, per_tree = np.unique(pairs[0], return_counts=True)
    _, per_label = np.unique(pairs[1], return_counts=True)
    out["ws_flood_mismatch"] = int(np.count_nonzero(per_tree > 1)
                                   + np.count_nonzero(per_label > 1))

    # the continuation: a voxel the reference floods from an external seed
    # carries that seed's label, and no other voxel carries an external one
    from_ext = tree_in > n_int
    want = np.concatenate([[np.uint64(0)], table])[np.maximum(tree_in - n_int, 0)]
    is_ext = np.isin(ws_in, table) if len(table) else np.zeros(ws_in.shape, bool)
    out["ws_ext_seed_mismatch"] = int(np.count_nonzero(
        (from_ext & (ws_in != want)) | (~from_ext & (tree_in > 0) & is_ext)))
    return out
