"""The comparison ``ws_two_pass``: the labels that whole two-pass watershed
jobs stored, against the plain reference (``benchmark/reference_two_pass.py``).

Whole stack, every completed job: the label volume can be read back
(``labels_missing``); every foreground voxel is labelled
(``ws_unlabelled_fg``); no fragment's foreground lies in two components of
the stack's foreground (``ws_fragments_across_components``); and every block
holds only labels it may hold (``ws_foreign_label_in_block``: an even block
any label that was not born in it; an odd block a label that was neither
born in it nor stored by an even-parity block inside its halo, which is
where pass two takes its external seeds from).

On ``check_units`` blocks a job, drawn from the seed, half of them even and
half odd, each whole and as the kernel saw it (the block with its halo,
clipped by the stack, padded with 1.0; an odd block with the external seeds
read back from the store): ``ws_seed_mismatch``, ``ws_descent_mismatch``,
``ws_flood_mismatch`` as ``ws_labels`` counts them, and
``ws_ext_seed_mismatch``: voxels that the reference floods from an external
seed and the job labelled otherwise, or the reverse.  A unit is not cut: a
flat outer block is about the size of one of ``ws_labels``' boxes with its
margin, so nothing is left undecided.

Every number is a count of exact integers, and every limit is 0.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np
from scipy import ndimage as ndi

from benchmark import reference as ref
from benchmark import reference_two_pass as ref2
from benchmark.data import fold_seed

_STRUCT6 = ndi.generate_binary_structure(3, 1)

#: see PERF.md section 2 for the readings (program: 0 on every seed; the
#: bfloat16 control: hundreds to thousands)
LIMITS = {
    "labels_missing": 0,
    "ws_unlabelled_fg": 0,
    "ws_fragments_across_components": 0,
    "ws_foreign_label_in_block": 0,
    "ws_seed_mismatch": 0,
    "ws_descent_mismatch": 0,
    "ws_flood_mismatch": 0,
    "ws_ext_seed_mismatch": 0,
}


def foreign_labels(ws: np.ndarray, number: int, pos, block, halo, n_outer: int) -> int:
    """Labels in block ``number`` that it may not hold."""
    lo, hi, _, _ = ref2.unit_bounds(pos, ws.shape, block, halo)
    own = np.unique(ws[tuple(slice(a, b) for a, b in zip(lo, hi))])
    own = own[own > 0]
    place = own - np.uint64(number) * np.uint64(n_outer + 1)
    foreign = own[(ref2.block_of_label(own, n_outer) != number)
                  | (place < 1) | (place > np.uint64(n_outer))]
    if ref2.parity_of(pos) and len(foreign):
        labels, even, _, _ = ref2.unit_labels(ws, pos, block, halo)
        foreign = np.setdiff1d(foreign, np.unique(labels[even]))
    return int(len(foreign))


def pick_units(blocks, rng, n_units: int):
    """``n_units`` blocks, half of each parity as far as there are any."""
    picked = []
    for parity, n in ((0, n_units - n_units // 2), (1, n_units // 2)):
        side = [b for b in blocks if ref2.parity_of(b[1]) == parity]
        picked += [side[i] for i in rng.choice(len(side), min(n, len(side)), replace=False)]
    return picked


def check_job(vol: np.ndarray, ws: np.ndarray, cfg: dict, rng, n_units: int) -> Dict[str, int]:
    p = cfg["params"]
    block, halo = list(p["block_shape"]), list(p["halo"])
    thr, sampling = float(p["threshold"]), [int(s) for s in p["sampling"]]
    radii = ref2.window_radii(float(p["dt_max_distance"]), sampling)
    n_outer = int(np.prod(ref2.outer_shape(block, halo)))
    fg = vol < np.float32(thr)
    comp, _ = ndi.label(fg, structure=_STRUCT6)
    out = {
        "ws_unlabelled_fg": int(np.count_nonzero(fg & (ws == 0))),
        "ws_fragments_across_components": ref.fragments_across_components(ws, fg, comp),
    }
    del comp, fg
    blocks = ref2.blocks_of(vol.shape, block)
    picked = pick_units(blocks, rng, n_units)

    def one(item):
        number, pos = item
        r = {"ws_foreign_label_in_block": foreign_labels(ws, number, pos, block, halo, n_outer)}
        if item in picked:
            height, labels, even, inner = ref2.unit_of(vol, ws, pos, block, halo)
            r.update(ref2.compare_unit(height, labels, even, inner,
                                       odd=bool(ref2.parity_of(pos)), threshold=thr,
                                       sampling=sampling, radii=radii))
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
