"""The comparison ``ws_labels``: watershed fragments (``ws``) and connected
components (``cc``) that whole jobs stored, against the plain reference.

A configuration names its comparison (``"comparison": "ws_labels"``) and the
harness loads ``comparisons/<name>.py`` by file, as it loads a metric's
reader; the module gives ``LIMITS`` and ``check_jobs``.  It runs once the
window has closed and the device's peak has been read, on the host.
Whatever is global is compared over the whole of every completed job's
labels (foreground covered, components, no label in two blocks, no fragment
in two components); the watershed's seeds, descent and fill are compared
unit by unit on a sample of units drawn from the seed (``check_units`` per
job in the cell's file).  Every number is a count that has to be 0;
``LIMITS`` holds them so that a run prints each beside its limit.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np
from scipy import ndimage as ndi

from benchmark import reference as ref
from benchmark.data import fold_seed

_STRUCT6 = ndi.generate_binary_structure(3, 1)

#: every comparison is of exact integers; see PERF.md section 2 for the
#: readings (program: 0 on every seed; control: thousands)
LIMITS = {
    "cc_mismatch_voxels": 0,
    "ws_unlabelled_fg": 0,
    "ws_labels_in_two_blocks": 0,
    "ws_fragments_across_components": 0,
    "ws_seed_mismatch": 0,
    "ws_descent_mismatch": 0,
    "ws_flood_mismatch": 0,
    "labels_missing": 0,
}


def _unit_blockwise(vol, ws_roi, roi_lo, block_lo, block, halo):
    """One block of a blockwise job as the kernel saw it: block + halo,
    clipped by the volume, padded at the far side with 1.0."""
    outer = [b + 2 * h for b, h in zip(block, halo)]
    olo = [max(l - h, 0) for l, h in zip(block_lo, halo)]
    ohi = [min(l + b + h, s) for l, b, h, s in zip(block_lo, block, halo, vol.shape)]
    height = vol[tuple(slice(a, b) for a, b in zip(olo, ohi))]
    pad = [(0, o - s) for o, s in zip(outer, height.shape)]
    height = np.pad(height, pad, constant_values=np.float32(1.0))
    inner = tuple(slice(l - o, l - o + b) for l, o, b in zip(block_lo, olo, block))
    ws = np.zeros(outer, ws_roi.dtype)
    ws[inner] = ws_roi[tuple(
        slice(l - r, l - r + b) for l, r, b in zip(block_lo, roi_lo, block)
    )]
    return height, ws, inner


def check_blockwise_job(vol, ws_roi, job, cfg, rng, n_units) -> Dict[str, int]:
    p = cfg["params"]
    block, halo = list(p["block_shape"]), list(p["halo"])
    thr, radius = float(p["threshold"]), int(p["dt_max_distance"])
    lo = job.roi_begin or (0, 0, 0)
    roi = tuple(slice(l, l + s) for l, s in zip(lo, job.shape))
    out = {"ws_unlabelled_fg": int(np.count_nonzero(
        (vol[roi] < np.float32(thr)) & (ws_roi == 0)))}
    grid = [range(l, l + s, b) for l, s, b in zip(lo, job.shape, block)]
    blocks = [(z, y, x) for z in grid[0] for y in grid[1] for x in grid[2]]
    picked = [blocks[i] for i in rng.choice(len(blocks), min(n_units, len(blocks)), replace=False)]
    # a label is its block's number and a place in that block: none is in two
    per_block = [np.unique(ws_roi[tuple(slice(c - l, c - l + b) for c, l, b in zip(blk, lo, block))])
                 for blk in blocks]
    labels = np.concatenate(per_block)
    labels = labels[labels > 0]
    out["ws_labels_in_two_blocks"] = int(len(labels) - len(np.unique(labels)))

    def one(block_lo):
        # every block: its fragments against the components of the foreground
        # that the kernel saw (the block with its halo); the picked ones
        # also against the reference's seeds, descent and fill
        height, ws, inner = _unit_blockwise(vol, ws_roi, lo, block_lo, block, halo)
        fg = height < np.float32(thr)
        comp, _ = ndi.label(fg, structure=_STRUCT6)
        r = {"ws_fragments_across_components":
             ref.fragments_across_components(ws[inner], fg[inner], comp[inner])}
        if block_lo in picked:
            r.update(ref.compare_watershed_unit(height, ws, threshold=thr, radius=radius,
                                                inner=inner, stored_only_inner=True))
            r.pop("ws_unlabelled_fg")
        return r

    with ThreadPoolExecutor(4) as pool:
        for r in pool.map(one, blocks):
            ref.merge_counts(out, r)
    return out


def check_fused_job(vol, ws, cc, job, cfg, rng, n_units) -> Dict[str, int]:
    p = cfg["params"]
    halo, thr = int(p["halo"]), float(p["threshold"])
    radius = int(p["dt_max_distance"])
    fg = vol < np.float32(thr)
    comp, n = ndi.label(fg, structure=_STRUCT6)
    out = ref.compare_components(fg, cc, comp=comp, n=n)
    out["ws_unlabelled_fg"] = int(np.count_nonzero(fg & (ws == 0)))
    out["ws_fragments_across_components"] = ref.fragments_across_components(ws, fg, comp)
    del comp
    # the step's one unit is the volume with `halo` slabs of 1.0 at both
    # ends of z (one device: nothing to exchange).  It is looked at in
    # boxes of 128^3 with the EDT window + 1 around them.
    box, margin = 128, radius + 1
    slots = [range(0, s, box) for s in vol.shape]
    boxes = [(z, y, x) for z in slots[0] for y in slots[1] for x in slots[2]]
    picked = [boxes[i] for i in rng.choice(len(boxes), min(n_units, len(boxes)), replace=False)]

    def one(lo):
        hi = [min(l + box, s) for l, s in zip(lo, vol.shape)]
        # region in volume coordinates; z may reach into the padding
        rlo = [lo[0] - margin if lo[0] - margin >= -halo else -halo,
               max(lo[1] - margin, 0), max(lo[2] - margin, 0)]
        rhi = [min(hi[0] + margin, vol.shape[0] + halo),
               min(hi[1] + margin, vol.shape[1]), min(hi[2] + margin, vol.shape[2])]
        zin = slice(max(rlo[0], 0), min(rhi[0], vol.shape[0]))
        yx = (slice(rlo[1], rhi[1]), slice(rlo[2], rhi[2]))
        zpad = ((zin.start - rlo[0], rhi[0] - zin.stop), (0, 0), (0, 0))
        height = np.pad(vol[(zin,) + yx], zpad, constant_values=np.float32(1.0))
        labels = np.pad(ws[(zin,) + yx], zpad)
        inner = tuple(slice(l - r, h - r) for l, h, r in zip(lo, hi, rlo))
        r = ref.compare_watershed_unit(height, labels, threshold=thr, radius=radius,
                                       inner=inner, stored_only_inner=False)
        r.pop("ws_unlabelled_fg")
        return r

    with ThreadPoolExecutor(4) as pool:
        for r in pool.map(one, picked):
            ref.merge_counts(out, r)
    return out


def check_jobs(cell: dict, cfg: dict, done: List[dict], volumes: Dict[int, np.ndarray],
               seed: int) -> Dict[str, int]:
    """``done``: the completed jobs, each ``{"job", "outputs": {name: (path,
    key)}}``.  Returns the summed counts, one entry per key of LIMITS that
    this cell compares."""
    totals: Dict[str, int] = {"labels_missing": 0}
    n_units = int(cell["check_units"])
    for rec in done:
        job = rec["job"]
        rng = fold_seed(seed, 3, job.index)
        lo = job.roi_begin or (0, 0, 0)
        bb = tuple(slice(l, l + s) for l, s in zip(lo, job.shape))
        read = {}
        for name, (path, key) in rec["outputs"].items():
            try:
                read[name] = ref.read_zarr(path, key, bb)
            except (OSError, ValueError, KeyError):
                totals["labels_missing"] += 1
        if len(read) != len(rec["outputs"]):
            continue
        vol = volumes[job.volume]
        if "cc" in read:
            part = check_fused_job(vol, read["ws"], read["cc"], job, cfg, rng, n_units)
        else:
            part = check_blockwise_job(vol, read["ws"], job, cfg, rng, n_units)
        ref.merge_counts(totals, part)
    return totals
