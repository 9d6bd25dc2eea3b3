"""The comparison ``ws_labels_slabs``: one volume segmented as z-slabs, one
slab a chip, by the fused mesh step; what the jobs stored against the plain
reference.

The number of slabs is the cell's ``chips`` (the task takes every chip of
its host and ``decomposition: "slab"`` cuts z into that many), never a
constant.  Whatever the configuration states of the whole volume is
compared over the whole volume: the connected components are
``scipy.ndimage.label``'s partition of its foreground, cuts or no cuts,
which is what holds the cross-chip merge to exactness; every foreground
voxel is labelled; no fragment lies in two components; no label lies in two
slabs.  The watershed is per slab: a slab is flooded on its own planes plus
``halo`` planes of its neighbours' *real* boundary map (1.0 beyond the
volume's two ends), so its seeds, descent and fill are compared on boxes
of 128^3 that lie inside one slab, cut out of that unit: a box's margin
reaches into the neighbour's boundary map as far as the halo and never
into the neighbour's labels.  Each interior cut gets a box against it from
either side; the rest of ``check_units`` lie anywhere.  Every number is a
count that has to be 0.

A box is compared as ``reference.compare_watershed_unit`` compares a cut
(the same three counts by the same rules), but for the seeds: there they
come from the cut's own distance transform, which is exact only one voxel
beyond the box, so a maximum in the cut's margin can come out displaced, and
the voxels that drain to it in the unit drain past it in the cut, on to a
seed inside the box (seed 2147486004 read ``ws_flood_mismatch`` = 1 that way
on the chip, PERF.md section 6, PR 31).  Here the distance transform is
taken over a cut wider by the window again, so the seeds handed to the flood
are the unit's own everywhere in the cut.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Tuple

import numpy as np
from scipy import ndimage as ndi

from benchmark import reference as ref
from benchmark.data import fold_seed

_STRUCT6 = ndi.generate_binary_structure(3, 1)
BOX = 128

#: every comparison is of exact integers; PERF.md section 2 has the readings
LIMITS = {
    "cc_mismatch_voxels": 0,
    "ws_unlabelled_fg": 0,
    "ws_labels_in_two_slabs": 0,
    "ws_fragments_across_components": 0,
    "ws_seed_mismatch": 0,
    "ws_descent_mismatch": 0,
    "ws_flood_mismatch": 0,
    "labels_missing": 0,
}


def pick_boxes(shape, slabs: int, rng: np.random.Generator, n_units: int,
               box: int = BOX) -> List[Tuple[int, int, int]]:
    """Low corners of the boxes to compare: first, for every interior cut,
    one box that ends at it and one that starts at it (their place in y and
    x drawn from ``rng``), then boxes drawn from the rest, ``n_units`` in
    all.  A box never spans a cut: z is tiled slab by slab."""
    thick = shape[0] // slabs
    z_slots = [list(range(s * thick, (s + 1) * thick, box)) for s in range(slabs)]
    yx = [(y, x) for y in range(0, shape[1], box) for x in range(0, shape[2], box)]
    picked: Dict[Tuple[int, int, int], None] = {}   # ordered, no box twice
    for cut in range(1, slabs):
        for z in (z_slots[cut - 1][-1], z_slots[cut][0]):
            picked[(z,) + yx[int(rng.integers(len(yx)))]] = None
    rest = [(z,) + p for slots in z_slots for z in slots for p in yx
            if (z,) + p not in picked]
    for i in rng.permutation(len(rest)):
        picked[rest[int(i)]] = None
    return list(picked)[:n_units]


def unit_of_box(vol, ws, lo, slabs: int, halo: int, margin: int, box: int = BOX):
    """``(height, labels, inner, corner)`` of one box, cut out of its slab's
    unit with ``margin`` voxels around it: the unit is the slab's planes
    with ``halo`` planes either side, the volume's own where it has them and
    1.0 beyond its ends; labels are the slab's own and 0 outside it (None
    where ``ws`` is); ``corner`` is the cut's low corner in the volume."""
    thick = vol.shape[0] // slabs
    z0 = lo[0] // thick * thick
    z1 = z0 + thick
    hi = [min(lo[0] + box, z1), min(lo[1] + box, vol.shape[1]),
          min(lo[2] + box, vol.shape[2])]
    rlo = [max(lo[0] - margin, z0 - halo), max(lo[1] - margin, 0), max(lo[2] - margin, 0)]
    rhi = [min(hi[0] + margin, z1 + halo), min(hi[1] + margin, vol.shape[1]),
           min(hi[2] + margin, vol.shape[2])]
    yx = (slice(rlo[1], rhi[1]), slice(rlo[2], rhi[2]))

    def cut(arr, a, b, fill):
        zin = slice(max(rlo[0], a), min(rhi[0], b))
        pad = ((zin.start - rlo[0], rhi[0] - zin.stop), (0, 0), (0, 0))
        return np.pad(arr[(zin,) + yx], pad, constant_values=fill)

    height = cut(vol, 0, vol.shape[0], np.float32(1.0))
    labels = None if ws is None else cut(ws, z0, z1, 0)
    inner = tuple(slice(l - r, h - r) for l, h, r in zip(lo, hi, rlo))
    return height, labels, inner, rlo


def unit_seeds(vol, lo, slabs: int, halo: int, threshold: float, radius: int,
               corner, shape):
    """The unit's own seeds on the cut at ``corner`` of ``shape``: plateaus
    of the maxima of the windowed EDT, taken over a cut wider by the window
    (where the unit goes on that far), so that every distance in the
    narrower cut and one voxel around it is the unit's."""
    wide, _, _, wide_corner = unit_of_box(vol, None, lo, slabs, halo, 2 * (radius + 1))
    fg = wide < np.float32(threshold)
    seeds, n_seeds = ref.seed_plateaus(fg, ref.windowed_edt_sq(fg, radius))
    crop = tuple(slice(c - w, c - w + n) for c, w, n in zip(corner, wide_corner, shape))
    return np.ascontiguousarray(seeds[crop]), n_seeds


def compare_cut(height, ws, seeds, n_seeds: int, inner) -> Dict[str, int]:
    """``reference.compare_watershed_unit`` for a cut of a unit
    (``stored_only_inner`` false), with the seeds handed in; only ``inner``
    is compared."""
    ws_in, seeds_in = ws[inner], seeds[inner]
    rim = np.ones(seeds.shape, bool)
    rim[1:-1, 1:-1, 1:-1] = False

    # seeds <-> fragments, one to one, as far as the inner part can show
    at = seeds_in > 0
    s_ids, s_lab = seeds_in[at], ws_in[at]
    first = np.zeros(n_seeds + 1, ws.dtype)
    first[s_ids] = s_lab
    torn = np.unique(s_ids[first[s_ids] != s_lab])      # a plateau with two labels
    seen = np.unique(s_ids)
    labels_of_seeds = first[seen]
    # a plateau that is whole here shares its label with no other (two cut
    # pieces of one plateau rightly share theirs)
    closed = np.setdiff1d(seen, np.unique(seeds[rim]))
    found, counts = np.unique(labels_of_seeds, return_counts=True)
    shared = int(np.count_nonzero(np.isin(first[closed], found[counts > 1])))
    # a fragment that does not touch the inner part's faces is whole here,
    # so its one seed has to be in here too
    face = np.ones(ws_in.shape, bool)
    face[1:-1, 1:-1, 1:-1] = False
    whole = np.setdiff1d(np.unique(ws_in[ws_in > 0]), np.unique(ws_in[face]))
    out = {"ws_seed_mismatch":
           int(len(torn) + shared + len(np.setdiff1d(whole, labels_of_seeds)))}

    # away from the seeds every voxel is labelled as the voxel it drains to;
    # looked at one voxel inside the inner part's faces, so that both ends
    # of every pair were stored by this unit
    grow = tuple(slice(max(s.start - 1, 0), s.stop + 1) for s in inner)
    core = tuple(slice(s.start - g.start + 1, s.stop - g.start - 1)
                 for s, g in zip(inner, grow))
    differs = ref.descent_target_differs(height[grow], ws[grow])[core]
    out["ws_descent_mismatch"] = int(np.count_nonzero(
        differs & (seeds[grow][core] == 0) & (ws[grow][core] > 0)))

    # the fill: no fragment of the reference's flood carries two labels
    tree = ref.reference_flood(height, seeds, n_seeds, cut_inner=inner)[inner]
    both = (tree > 0) & (ws_in > 0)
    pairs = np.unique(np.stack([tree[both], ws_in[both].astype(np.int64)]), axis=1)
    _, per_tree = np.unique(pairs[0], return_counts=True)
    out["ws_flood_mismatch"] = int(np.count_nonzero(per_tree > 1))
    return out


def check_slab_job(vol, ws, cc, cfg, slabs: int, rng, n_units: int) -> Dict[str, int]:
    p = cfg["params"]
    halo, thr = int(p["halo"]), float(p["threshold"])
    radius = int(p["dt_max_distance"])
    if vol.shape[0] % slabs:
        raise ValueError(f"{slabs} slabs do not divide {vol.shape[0]} planes")
    thick = vol.shape[0] // slabs
    own = [slice(s * thick, (s + 1) * thick) for s in range(slabs)]
    fg = vol < np.float32(thr)

    def one(lo):
        height, labels, inner, corner = unit_of_box(vol, ws, lo, slabs, halo, radius + 1)
        seeds, n_seeds = unit_seeds(vol, lo, slabs, halo, thr, radius, corner,
                                    height.shape)
        return compare_cut(height, labels, seeds, n_seeds, inner)

    def labels_of(sl):
        found = np.unique(ws[sl])
        return found[found > 0]

    with ThreadPoolExecutor(2 * slabs + 4) as pool:
        # the slabs' label sets and the boxes need no components: they run
        # beside the whole volume's labelling
        per_slab = [pool.submit(labels_of, sl) for sl in own]
        units = [pool.submit(one, lo) for lo in pick_boxes(vol.shape, slabs, rng, n_units)]
        comp, n = ndi.label(fg, structure=_STRUCT6)
        out = ref.compare_components(fg, cc, comp=comp, n=n)
        out["ws_unlabelled_fg"] = int(np.count_nonzero(fg & (ws == 0)))
        labels = np.concatenate([f.result() for f in per_slab])
        out["ws_labels_in_two_slabs"] = int(len(labels) - len(np.unique(labels)))
        # a label lies in one slab (counted above), so slab by slab is the
        # whole volume's count
        across = pool.map(
            lambda sl: ref.fragments_across_components(ws[sl], fg[sl], comp[sl]), own)
        out["ws_fragments_across_components"] = int(sum(across))
        for f in units:
            ref.merge_counts(out, f.result())
    return out


def check_jobs(cell: dict, cfg: dict, done: List[dict], volumes: Dict[int, np.ndarray],
               seed: int) -> Dict[str, int]:
    """``done``: the completed jobs, each ``{"job", "outputs": {name: (path,
    key)}}``.  Returns the summed counts, one entry per key of LIMITS."""
    totals: Dict[str, int] = {"labels_missing": 0}
    n_units, slabs = int(cell["check_units"]), int(cell["chips"])
    for rec in done:
        job = rec["job"]
        read = {}
        for name, (path, key) in rec["outputs"].items():
            try:
                read[name] = ref.read_zarr(path, key)
            except (OSError, ValueError, KeyError):
                totals["labels_missing"] += 1
        if len(read) != len(rec["outputs"]):
            continue
        part = check_slab_job(volumes[job.volume], read["ws"], read["cc"], cfg, slabs,
                              fold_seed(seed, 3, job.index), n_units)
        ref.merge_counts(totals, part)
    return totals
