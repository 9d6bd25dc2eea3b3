"""The comparison ``ws_planar``: the labels that whole per-section (2-D)
watershed jobs stored, against the plain reference
(``benchmark/reference_2d.py``).

Whole stack, every completed job: the label volume can be read back
(``labels_missing``); every foreground voxel is labelled
(``ws_unlabelled_fg``); no fragment's foreground lies in two components of
its plane's foreground (``ws_fragments_across_components``, components
4-connected within a plane); no label is found in two planes
(``ws_label_in_two_planes``); and every block holds only labels born in it
(``ws_foreign_label_in_block``: block number = label // (voxels of an outer
block + 1), place in 1..that).

On ``check_units`` blocks a job, drawn from the seed, each whole and as the
kernel saw it (the block with its in-plane halo, clipped by the stack,
padded with 1.0): ``ws_seed_mismatch`` (a plateau with two labels, two
plateaus with one, a fragment wholly inside the block without its seed),
``ws_descent_mismatch`` (voxels one voxel inside the block's in-plane faces,
no seed, labelled otherwise than the voxel they drain to) and
``ws_flood_mismatch`` (a fragment of the reference's flood with two labels,
or a label on two of its fragments).  Nothing is cut inside a unit's
planes, so nothing is left undecided.

Every number is a count of exact integers, and every limit is 0.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np
from scipy import ndimage as ndi

from benchmark import reference as ref
from benchmark import reference_2d as ref2d
from benchmark import reference_two_pass as grid
from benchmark.data import fold_seed

#: 4-connected within a plane, nothing across planes
_STRUCT_PLANE = np.zeros((3, 3, 3), bool)
_STRUCT_PLANE[1] = ndi.generate_binary_structure(2, 1)

#: see PERF.md section 2 for the readings (program: 0 on every seed; the
#: bfloat16 control: hundreds to thousands)
LIMITS = {
    "labels_missing": 0,
    "ws_unlabelled_fg": 0,
    "ws_fragments_across_components": 0,
    "ws_label_in_two_planes": 0,
    "ws_foreign_label_in_block": 0,
    "ws_seed_mismatch": 0,
    "ws_descent_mismatch": 0,
    "ws_flood_mismatch": 0,
}


def labels_in_two_planes(ws: np.ndarray) -> int:
    """Labels (not 0) found in more than one z-plane."""
    per_plane = np.concatenate([np.unique(ws[z]) for z in range(ws.shape[0])])
    uniq, counts = np.unique(per_plane[per_plane > 0], return_counts=True)
    return int(np.count_nonzero(counts > 1))


def foreign_labels(ws: np.ndarray, number: int, pos, block, halo, n_outer: int) -> int:
    """Labels in block ``number`` that were not born in it."""
    lo, hi, _, _ = grid.unit_bounds(pos, ws.shape, block, halo)
    own = np.unique(ws[tuple(slice(a, b) for a, b in zip(lo, hi))])
    own = own[own > 0]
    place = own - np.uint64(number) * np.uint64(n_outer + 1)
    return int(np.count_nonzero((grid.block_of_label(own, n_outer) != number)
                                | (place < 1) | (place > np.uint64(n_outer))))


def compare_unit(height: np.ndarray, labels: np.ndarray, inner, *, threshold: float,
                 radius: int) -> Dict[str, int]:
    """One unit's stored labels (``labels`` over the unit, its neighbours'
    around the block) against the reference, over the block ``inner``."""
    tree, seeds, n_seeds = ref2d.flood_unit(height, threshold=threshold, radius=radius)
    ws_in, seeds_in, tree_in = labels[inner], seeds[inner], tree[inner]
    out: Dict[str, int] = {}

    at = seeds_in > 0
    s_ids, s_lab = seeds_in[at], ws_in[at]
    first = np.zeros(n_seeds + 1, ws_in.dtype)
    first[s_ids] = s_lab
    torn = np.unique(s_ids[first[s_ids] != s_lab])        # a plateau, two labels
    seen = np.unique(s_ids)
    labels_of_seeds = first[seen]
    uniq, counts = np.unique(labels_of_seeds, return_counts=True)
    shared = int(np.count_nonzero(np.isin(labels_of_seeds, uniq[counts > 1])))
    # a fragment away from the block's in-plane faces is whole here, so its
    # one seed is in here too
    face = np.zeros(ws_in.shape, bool)
    face[:, [0, -1], :] = True
    face[:, :, [0, -1]] = True
    whole = np.setdiff1d(np.unique(ws_in[ws_in > 0]), np.unique(ws_in[face]))
    seedless = len(np.setdiff1d(whole, labels_of_seeds))
    out["ws_seed_mismatch"] = int(len(torn) + shared + seedless)

    # the descent, one voxel inside the block's in-plane faces: both ends of
    # every pair were stored by this unit
    differs = ref2d.descent_differs(height[inner], ws_in)
    core = (slice(None), slice(1, -1), slice(1, -1))
    out["ws_descent_mismatch"] = int(np.count_nonzero(
        differs[core] & (seeds_in[core] == 0) & (ws_in[core] > 0)))

    # the flood: the reference's fragments and the stored ones are the same
    # partition of the block
    both = (tree_in > 0) & (ws_in > 0)
    pairs = np.unique(np.stack([tree_in[both], ws_in[both].astype(np.int64)]), axis=1)
    _, per_tree = np.unique(pairs[0], return_counts=True)
    _, per_label = np.unique(pairs[1], return_counts=True)
    out["ws_flood_mismatch"] = int(np.count_nonzero(per_tree > 1)
                                   + np.count_nonzero(per_label > 1))
    return out


def check_job(vol: np.ndarray, ws: np.ndarray, cfg: dict, rng, n_units: int) -> Dict[str, int]:
    p = cfg["params"]
    block, halo = list(p["block_shape"]), list(p["halo"])
    thr = float(p["threshold"])
    radius = ref2d.in_plane_radius(float(p["dt_max_distance"]), p["sampling"])
    n_outer = int(np.prod(grid.outer_shape(block, halo)))
    fg = vol < np.float32(thr)
    comp, _ = ndi.label(fg, structure=_STRUCT_PLANE)
    out = {
        "ws_unlabelled_fg": int(np.count_nonzero(fg & (ws == 0))),
        "ws_fragments_across_components": ref.fragments_across_components(ws, fg, comp),
        "ws_label_in_two_planes": labels_in_two_planes(ws),
    }
    del comp, fg
    blocks = grid.blocks_of(vol.shape, block)
    picked = [blocks[i] for i in rng.choice(len(blocks), min(n_units, len(blocks)),
                                            replace=False)]

    def one(item):
        number, pos = item
        r = {"ws_foreign_label_in_block": foreign_labels(ws, number, pos, block, halo,
                                                         n_outer)}
        if item in picked:
            height, labels, _, inner = grid.unit_of(vol, ws, pos, block, halo)
            r.update(compare_unit(height, labels, inner, threshold=thr, radius=radius))
        return r

    with ThreadPoolExecutor(4) as pool:
        for r in pool.map(one, blocks):
            ref.merge_counts(out, r)
    return out


def check_jobs(cell: dict, cfg: dict, done: List[dict], volumes: Dict[int, np.ndarray],
               seed: int) -> Dict[str, int]:
    """``done``: the completed jobs, each ``{"job", "outputs": {"ws": (path,
    key)}}``.  Returns the summed counts, one entry per key of LIMITS."""
    totals: Dict[str, int] = {k: 0 for k in LIMITS}
    for rec in done:
        job = rec["job"]
        path, key = rec["outputs"]["ws"]
        try:
            ws = ref.read_zarr(path, key)
        except (OSError, ValueError, KeyError):
            totals["labels_missing"] += 1
            continue
        ref.merge_counts(totals, check_job(volumes[job.volume], ws, cfg,
                                           fold_seed(seed, 3, job.index),
                                           int(cell["check_units"])))
    return totals
