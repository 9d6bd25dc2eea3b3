"""The plain reference and the comparison that decides ``correct``.

Imports nothing of the program and takes nothing it made but the label
volumes it wrote to the output store, which are read back here by a zarr
reader of the benchmark's own (so what is compared is what is on disk, not
what a cache of the program holds).  numpy and scipy only; runs on the host.

What the configurations state, and what is held here:

* ``fg = boundaries < threshold`` in float32; connected components of ``fg``
  (6-neighbourhood) are ``scipy.ndimage.label``'s partition, exactly.
* The distance-transform watershed of one *unit* (the whole volume for the
  fused step, one outer block = block + halo for the blockwise sweep; an
  outer block clipped by the volume's far side is padded there with 1.0):
  squared EDT of ``fg`` over a window of ``dt_max_distance`` voxels per axis
  (exact integers), seeds = 6-connected plateaus of its local maxima inside
  ``fg``, one fragment per seed, every voxel draining to the lowest voxel
  (height, then flat index) of its closed 6-neighbourhood.  A basin whose
  lowest voxel is no seed joins the neighbour across its lowest saddle (the
  lowest of ``max(height)`` over the faces it shares with other basins;
  equal saddles by the face that comes first in (axis, position)), as a
  flood from the seeds would fill it; so every fragment's foreground lies
  in one component of ``fg``.

Each comparison is a count of voxels, seeds or fragments that break one of
these; every limit is 0 (exact integers, nothing approximate).
"""

from __future__ import annotations

import json
import os
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
from scipy import ndimage as ndi

#: stands for "no background voxel inside the window"; above any finite value
#: (3 * 32**2) and far from int32 overflow when an offset's square is added
_FAR = np.int32(1 << 28)
_STRUCT6 = ndi.generate_binary_structure(3, 1)


# --------------------------------------------------------------------------
# reading the output store back (zarr v2, C order, gzip/zlib or raw chunks)
# --------------------------------------------------------------------------


def read_zarr(path: str, key: str, bb: Optional[Sequence[slice]] = None,
              threads: int = 8) -> np.ndarray:
    """Read ``bb`` (default: everything) of a zarr v2 array from its files."""
    root = os.path.join(path, key)
    with open(os.path.join(root, ".zarray")) as f:
        meta = json.load(f)
    if meta.get("order", "C") != "C" or meta.get("filters"):
        raise ValueError(f"{root}: only C-order, unfiltered zarr v2 is read here")
    shape = tuple(meta["shape"])
    chunks = tuple(meta["chunks"])
    dtype = np.dtype(meta["dtype"])
    comp = meta.get("compressor")
    sep = meta.get("dimension_separator", ".")
    fill = meta.get("fill_value") or 0
    if comp is not None and comp.get("id") not in ("gzip", "zlib"):
        raise ValueError(f"{root}: compressor {comp} is not read here")
    bb = tuple(bb) if bb is not None else tuple(slice(0, s) for s in shape)
    lo = [s.start or 0 for s in bb]
    hi = [s.stop if s.stop is not None else n for s, n in zip(bb, shape)]
    out = np.full([h - l for l, h in zip(lo, hi)], fill, dtype)
    grid = [range(l // c, (h - 1) // c + 1) for l, h, c in zip(lo, hi, chunks)]

    def one(idx):
        name = os.path.join(root, sep.join(str(i) for i in idx))
        try:
            with open(name, "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            return  # a chunk never written reads as the fill value
        if comp is not None:
            raw = zlib.decompress(raw, 47)  # gzip or zlib header
        chunk = np.frombuffer(raw, dtype).reshape(chunks)
        src, dst = [], []
        for i, c, l, h in zip(idx, chunks, lo, hi):
            a, b = max(i * c, l), min((i + 1) * c, h)
            src.append(slice(a - i * c, b - i * c))
            dst.append(slice(a - l, b - l))
        out[tuple(dst)] = chunk[tuple(src)]

    cells = [(i, j, k) for i in grid[0] for j in grid[1] for k in grid[2]]
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(one, cells))
    return out


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------


def windowed_edt_sq(fg: np.ndarray, radius: int) -> np.ndarray:
    """Squared distance (int32) of every ``fg`` voxel to the nearest voxel
    outside ``fg`` whose offset is at most ``radius`` along every axis;
    ``_FAR`` where there is none; 0 outside ``fg``.  Beyond the array there
    is nothing.  Exact: separable min-plus over the window."""
    f = np.where(fg, _FAR, np.int32(0)).astype(np.int32)
    for axis in range(f.ndim):
        f = np.moveaxis(f, axis, 0)
        out = f.copy()
        for k in range(1, min(radius, f.shape[0] - 1) + 1):
            kk = np.int32(k * k)
            np.minimum(out[k:], f[:-k] + kk, out=out[k:])
            np.minimum(out[:-k], f[k:] + kk, out=out[:-k])
        f = np.moveaxis(np.minimum(out, _FAR), 0, axis)
    return np.ascontiguousarray(f)


def seed_plateaus(fg: np.ndarray, dist: np.ndarray) -> Tuple[np.ndarray, int]:
    """6-connected plateaus of the local maxima of ``dist`` inside ``fg``
    (a voxel no lower than each of its six neighbours; beyond the array
    there is nothing), labelled 1..n."""
    maxima = fg.copy()
    for axis in range(3):
        a = [slice(None)] * 3
        b = [slice(None)] * 3
        a[axis], b[axis] = slice(0, -1), slice(1, None)
        a, b = tuple(a), tuple(b)
        maxima[a] &= dist[a] >= dist[b]
        maxima[b] &= dist[b] >= dist[a]
    return ndi.label(maxima, structure=_STRUCT6)


def descent_target_differs(height: np.ndarray, labels: np.ndarray) -> np.ndarray:
    """True where a voxel's label differs from the label of the voxel it
    drains to: the lowest (height, then flat index) of its closed
    6-neighbourhood.  A watershed labels both alike, seeds apart."""
    # neighbours in rising flat-index order; a tie keeps the earlier one, and
    # the voxel itself sits between -x and +x in that order
    order = [(0, -1), (1, -1), (2, -1), None, (2, 1), (1, 1), (0, 1)]
    best_h = np.full(height.shape, np.inf, np.float32)
    best_l = np.zeros(labels.shape, labels.dtype)
    for step in order:
        if step is None:
            cand_h, cand_l = height, labels
            sl_dst = sl_src = (slice(None),) * 3
        else:
            axis, d = step
            dst = [slice(None)] * 3
            src = [slice(None)] * 3
            if d < 0:
                dst[axis], src[axis] = slice(1, None), slice(0, -1)
            else:
                dst[axis], src[axis] = slice(0, -1), slice(1, None)
            sl_dst, sl_src = tuple(dst), tuple(src)
            cand_h, cand_l = height[sl_src], labels[sl_src]
        better = cand_h < best_h[sl_dst]
        best_h[sl_dst] = np.where(better, cand_h, best_h[sl_dst])
        best_l[sl_dst] = np.where(better, cand_l, best_l[sl_dst])
    return best_l != labels


def descent_roots(height: np.ndarray, seeds: np.ndarray) -> np.ndarray:
    """Flat index of the voxel every voxel drains to in the end: it follows
    the lowest (height, then flat index) voxel of its closed 6-neighbourhood
    until it reaches a seed voxel or a voxel that is its own lowest."""
    idx = np.arange(height.size, dtype=np.int64).reshape(height.shape)
    best_h, best_i = height.copy(), idx.copy()
    for axis in range(3):
        for d in (-1, 1):
            dst, src = [slice(None)] * 3, [slice(None)] * 3
            if d < 0:
                dst[axis], src[axis] = slice(1, None), slice(0, -1)
            else:
                dst[axis], src[axis] = slice(0, -1), slice(1, None)
            dst, src = tuple(dst), tuple(src)
            ch, ci = height[src], idx[src]
            better = (ch < best_h[dst]) | ((ch == best_h[dst]) & (ci < best_i[dst]))
            best_h[dst] = np.where(better, ch, best_h[dst])
            best_i[dst] = np.where(better, ci, best_i[dst])
    p = best_i.ravel()
    at_seed = np.flatnonzero(seeds.ravel() > 0)
    p[at_seed] = at_seed
    while True:
        q = p[p]
        if np.array_equal(q, p):
            return p.reshape(height.shape)
        p = q


def reference_flood(height: np.ndarray, seeds: np.ndarray, n_seeds: int,
                    cut_inner: Optional[Tuple[slice, slice, slice]] = None) -> np.ndarray:
    """The seed (1..n_seeds) whose fragment every voxel belongs to.

    Basins by steepest descent; a basin without a seed joins its neighbour
    across its lowest saddle, lowest saddles first, and two basins that both
    hold a seed never join (Kruskal's forest on the basins, every seed a
    root).  Where ``height`` is a cut of its unit, ``cut_inner`` is the part
    whose distances are exact (the EDT window fits around it).  What the cut
    cannot decide is left 0: a seedless basin that touches the cut's faces,
    a plateau that is not wholly inside ``cut_inner`` (beyond it a maximum
    may be the cut's own), and whatever joins either."""
    root = descent_roots(height, seeds).ravel()
    sflat = seeds.ravel()
    # nodes: 1..n_seeds the seeded basins (all of a plateau's voxels are one),
    # above them one node for every seedless basin
    node = sflat[root].astype(np.int64)
    lone = np.flatnonzero((root == np.arange(root.size)) & (sflat == 0))
    slot = np.zeros(root.size, np.int64)
    slot[lone] = n_seeds + 1 + np.arange(len(lone))
    node = np.where(node > 0, node, slot[root]).reshape(height.shape)
    n_nodes = n_seeds + 1 + len(lone)
    undecided = np.zeros(n_nodes, bool)
    if cut_inner is not None:
        rim = np.ones(height.shape, bool)
        rim[1:-1, 1:-1, 1:-1] = False
        on_rim = np.unique(node[rim])
        undecided[on_rim[on_rim > n_seeds]] = True
        outside = np.ones(height.shape, bool)
        outside[cut_inner] = False
        undecided[np.unique(seeds[outside])] = True
        undecided[0] = False
    flat = np.arange(height.size, dtype=np.int64).reshape(height.shape)
    ea, eb, ew, ef = [], [], [], []
    for axis in range(3):
        a, b = [slice(None)] * 3, [slice(None)] * 3
        a[axis], b[axis] = slice(0, -1), slice(1, None)
        a, b = tuple(a), tuple(b)
        na, nb = node[a], node[b]
        edge = (na != nb) & ((na > n_seeds) | (nb > n_seeds))
        ea.append(np.minimum(na[edge], nb[edge]))
        eb.append(np.maximum(na[edge], nb[edge]))
        ew.append(np.maximum(height[a], height[b])[edge])
        ef.append(axis * height.size + flat[a][edge])
    ea, eb, ew, ef = (np.concatenate(x) for x in (ea, eb, ew, ef))
    # faces in rising (saddle, axis, position of the face's first voxel): a
    # total order, so the forest is one and the same whoever builds it.  Two
    # faces under one high voxel have the same saddle, so ties are common
    order = np.lexsort((ef, ew))
    ea, eb = ea[order], eb[order]
    # of the faces that two basins share only the first can join them
    _, first = np.unique(ea * n_nodes + eb, return_index=True)
    first.sort()
    ea, eb = ea[first], eb[first]
    parent = list(range(n_nodes))
    seed_of = list(range(n_seeds + 1)) + [0] * len(lone)
    own = np.where(undecided, 0, np.arange(n_nodes))  # a plateau's own basin stays its own

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for a, b in zip(ea.tolist(), eb.tolist()):
        ra, rb = find(a), find(b)
        if ra == rb or (seed_of[ra] and seed_of[rb]):
            continue
        parent[rb] = ra
        seed_of[ra] = seed_of[ra] or seed_of[rb]
        undecided[ra] |= undecided[rb]
    table = own
    for x in range(n_seeds + 1, n_nodes):
        r = find(x)
        table[x] = 0 if undecided[r] else seed_of[r]
    return table[node]


# --------------------------------------------------------------------------
# the comparisons; each returns counts, and every limit is 0
# --------------------------------------------------------------------------


def compare_components(fg: np.ndarray, cc: np.ndarray, comp=None, n=None
                       ) -> Dict[str, int]:
    """``cc`` against scipy's partition of ``fg``: voxels whose label does
    not follow a one-to-one map of components, background included."""
    if comp is None:
        comp, n = ndi.label(fg, structure=_STRUCT6)
    cc = np.asarray(cc)
    table = np.zeros(n + 1, cc.dtype)
    table[comp.ravel()] = cc.ravel()  # one representative label a component
    table[0] = 0
    wrong = int(np.count_nonzero(table[comp] != cc))
    merged = n - len(np.unique(table[1:])) if n else 0  # two components, one label
    return {"cc_mismatch_voxels": wrong + int(merged)}


def compare_watershed_unit(height: np.ndarray, ws: np.ndarray, *,
                           threshold: float, radius: int,
                           inner: Tuple[slice, slice, slice],
                           stored_only_inner: bool) -> Dict[str, int]:
    """One unit's watershed labels against the reference.

    ``height`` is the unit as the kernel saw it (with its halo and padding)
    or, for a unit too large to take at once, a cut of it that reaches
    ``radius + 1`` voxels beyond ``inner``; ``ws`` the stored labels of the
    same extent; ``radius`` the EDT window.  Only ``inner`` is compared.
    ``stored_only_inner`` says that ``height`` is the whole unit (a block
    with its halo, of which the job stored ``inner``); otherwise it is a
    cut, and a plateau that touches its faces may go on outside it.
    """
    fg = height < np.float32(threshold)
    dist = windowed_edt_sq(fg, radius)
    seeds, n_seeds = seed_plateaus(fg, dist)
    ws_in = ws[inner]
    fg_in = fg[inner]
    seeds_in = seeds[inner]
    out = {"ws_unlabelled_fg": int(np.count_nonzero(fg_in & (ws_in == 0)))}

    # seeds <-> fragments, one to one, as far as the inner part can show
    at = seeds_in > 0
    s_ids = seeds_in[at]
    s_lab = ws_in[at]
    first = np.zeros(n_seeds + 1, ws.dtype)
    first[s_ids] = s_lab
    torn = np.unique(s_ids[first[s_ids] != s_lab])  # a plateau with two labels
    seen = np.unique(s_ids)
    labels_of_seeds = first[seen]
    if stored_only_inner:
        closed = seen
    else:
        # two cut pieces of one plateau rightly share a label
        rim = np.ones(seeds.shape, bool)
        rim[1:-1, 1:-1, 1:-1] = False
        closed = np.setdiff1d(seen, np.unique(seeds[rim]))
    # a plateau that is whole here shares its label with no other
    _, counts = np.unique(labels_of_seeds, return_counts=True)
    dup = np.unique(labels_of_seeds)[counts > 1]
    shared = int(np.count_nonzero(np.isin(first[closed], dup)))
    # a fragment that does not touch the inner part's faces is whole here,
    # so its one seed has to be in here too
    face = np.zeros(ws_in.shape, bool)
    for axis in range(3):
        sl = [slice(None)] * 3
        for side in (0, -1):
            sl[axis] = side
            face[tuple(sl)] = True
    whole = np.setdiff1d(np.unique(ws_in[ws_in > 0]), np.unique(ws_in[face]))
    seedless = len(np.setdiff1d(whole, labels_of_seeds))
    out["ws_seed_mismatch"] = int(len(torn) + shared + seedless)

    # the flood: away from the seeds every voxel is labelled as the voxel
    # it drains to.  Looked at one voxel inside the inner part's faces, so
    # that both ends of every pair were stored by this unit.
    grow = tuple(slice(max(s.start - 1, 0), s.stop + 1) for s in inner)
    differs = descent_target_differs(height[grow], ws[grow])
    core = tuple(
        slice(s.start - g.start + 1, s.stop - g.start - 1)
        for s, g in zip(inner, grow)
    )
    seeds_g = seeds[grow][core] > 0
    labelled = ws[grow][core] > 0
    out["ws_descent_mismatch"] = int(
        np.count_nonzero(differs[core] & ~seeds_g & labelled)
    )

    # the fill: the reference's fragments and the stored ones are the same
    # partition of the inner part.  A fragment of the reference with two
    # labels; and, where the unit is whole (in a cut two pieces of one
    # plateau rightly share a label), a label on two of its fragments.
    tree = reference_flood(height, seeds, n_seeds,
                           cut_inner=None if stored_only_inner else inner)[inner]
    both = (tree > 0) & (ws_in > 0)
    pairs = np.unique(np.stack([tree[both], ws_in[both].astype(np.int64)]), axis=1)
    _, per_tree = np.unique(pairs[0], return_counts=True)
    _, per_label = np.unique(pairs[1], return_counts=True)
    out["ws_flood_mismatch"] = int(np.count_nonzero(per_tree > 1)) + (
        int(np.count_nonzero(per_label > 1)) if stored_only_inner else 0)
    return out


def fragments_across_components(ws: np.ndarray, fg: np.ndarray, comp: np.ndarray) -> int:
    """Labels whose foreground voxels lie in more than one component."""
    m = fg & (ws > 0)
    w, c = ws[m], comp[m]
    order = np.argsort(w, kind="stable")
    w, c = w[order], c[order]
    split = (w[1:] == w[:-1]) & (c[1:] != c[:-1])
    return int(len(np.unique(w[1:][split])))


def merge_counts(total: Dict[str, int], part: Dict[str, int]) -> None:
    for k, v in part.items():
        total[k] = total.get(k, 0) + int(v)
