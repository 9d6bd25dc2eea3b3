"""The comparison ``mc_labels``: fragments (``ws``) and the multicut
segmentation (``seg``) that whole-workflow jobs stored, against the plain
references.

The fragments are held to everything ``ws_labels`` holds the fused step's
watershed to, through ``ws_labels_slabs``'s own function for a volume of
one slab (the same counts by the same rules, with the boxes' seeds taken
from the unit and not from the cut: ``ws_labels``'s own boxes read a false
``ws_flood_mismatch`` 1 on seed 2147490011, PERF.md section 7).  The job
stores no connected components: the count that would compare them, and the
count of labels in two slabs, are left out.  The segmentation is held to the
guarantees of ``configs/mc_fused_384.json``, over the whole volume:

``mc_fragments_split``        fragments whose voxels carry two segment
                              labels, and foreground (a fragment's voxels)
                              labelled 0: the segmentation is a merge of the
                              stored fragments
``mc_segments_disconnected``  segments whose fragments are not connected in
                              the reference's RAG of the stored fragments
``mc_rag_edge_mismatch``      edges of the job's graph (``tmp/graph/
                              graph.npz``) missing from the reference's RAG
                              of the stored fragments, extra to it, or with
                              another number of voxel faces
``mc_cost_mismatch``          edges whose cost (``tmp/graph/costs.npy``)
                              lies further from the reference's float64
                              cost than a float32 sum of the edge's faces
                              can (``COST_TOLERANCE`` and its comment)
``mc_energy_gap_ppm``         how much worse than the reference's own solve
                              the job's partition is, both under the
                              reference's float64 costs: max(0, E(job) -
                              E(reference)) / |E(reference)|, in parts per
                              million, rounded up to a whole number
``labels_missing``            label volumes, or the job's graph and costs,
                              that cannot be read back

The graph and costs are what the program leaves in the job's ``tmp_folder``
as upstream leaves its problem in the tmp store; they are read with numpy
alone.
"""

from __future__ import annotations

import importlib.util
import math
import os
import sys
from typing import Dict, List

import numpy as np

from benchmark import reference as ref
from benchmark import reference_multicut as mc
from benchmark.data import fold_seed

#: a job's cost against the reference's float64 cost, edge by edge.  The
#: program sums an edge's values in float32 on the device (block by block,
#: the blocks merged count-weighted in float64) and takes the logit in
#: float32.  A float32 sum of n values, in whatever order, is off by at most
#: n * 2^-24 of itself, and so is the mean p; the logit turns a relative
#: error r of p into r / (1 - p) of cost.  The flat part is the float32
#: logit itself: on the chip the cost of an edge whose mean is clipped at
#: 1e-5 reads 1.06e-4 off float64's 11.5129 on every seed (the chip's log
#: and divide are not correctly rounded), 106-142 of some 400,000 edges a
#: job pass ISSUE 35's 1e-4 that way and none is further off than 1.07e-4;
#: on the CPU backend no edge is further off than 7e-6 (PERF.md section
#: 2).  So an edge of n faces may differ by COST_TOLERANCE + n * 2^-24 /
#: (1 - p).  An input rounded to bfloat16 moves the cost of an edge of n
#: faces by about 3e-3 / sqrt(n), so most edges under 100 faces (nine in
#: ten of all edges) fail it
COST_TOLERANCE = 2e-4
_F32_ROUNDOFF = 2.0 ** -24

#: the counts are of exact integers and have to be 0.  The energy gap: the
#: program solves by blocks and then the reduced graph, the reference the
#: whole graph at once, so the two may stop in different local minima; the
#: readings and the reason for this limit are in PERF.md section 2
LIMITS = {
    "ws_unlabelled_fg": 0,
    "ws_fragments_across_components": 0,
    "ws_seed_mismatch": 0,
    "ws_descent_mismatch": 0,
    "ws_flood_mismatch": 0,
    "mc_fragments_split": 0,
    "mc_segments_disconnected": 0,
    "mc_rag_edge_mismatch": 0,
    "mc_cost_mismatch": 0,
    "mc_energy_gap_ppm": 1000,
    "labels_missing": 0,
}


def _ws_labels_slabs():
    """``comparisons/ws_labels_slabs.py``, loaded by file as the harness
    loads it."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ws_labels_slabs.py")
    spec = importlib.util.spec_from_file_location("benchmark.comparisons.ws_labels_slabs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def fragment_segments(ws: np.ndarray, seg: np.ndarray, nodes: np.ndarray):
    """``(segment of every node, count)``: the one segment label of each
    fragment in ``nodes`` (sorted), and how many fragments carry two labels
    or, being foreground, the label 0."""
    fg = ws != 0
    idx = np.searchsorted(nodes, ws[fg])
    s = seg[fg]
    first = np.zeros(len(nodes), seg.dtype)
    first[idx] = s  # one voxel's label a fragment
    bad = first == 0
    bad[idx[s != first[idx]]] = True
    return first, int(np.count_nonzero(bad))


def check_multicut(vol, ws, seg, tmp, beta: float) -> Dict[str, int]:
    """One job's segmentation and artefacts against the reference."""
    out = {"labels_missing": 0}
    uv, mean, faces = mc.edge_means(ws, vol)
    costs = mc.probs_to_costs(mean, beta)
    nodes = np.unique(ws)
    nodes = nodes[nodes != 0]
    edges = np.searchsorted(nodes, uv)
    node_seg, out["mc_fragments_split"] = fragment_segments(ws, seg, nodes)
    out["mc_segments_disconnected"] = mc.connected_in(edges, node_seg)
    e_job = mc.energy(edges, costs, node_seg)
    e_ref = mc.energy(edges, costs, mc.solve(len(nodes), edges, costs))
    gap = max(0.0, e_job - e_ref) / max(abs(e_ref), 1e-12)
    out["mc_energy_gap_ppm"] = int(math.ceil(gap * 1e6))
    try:
        with np.load(os.path.join(tmp, "graph", "graph.npz")) as f:
            job_uv, job_faces = f["uv"].astype(np.uint64), f["sizes"].astype(np.int64)
        job_costs = np.load(os.path.join(tmp, "graph", "costs.npy")).astype(np.float64)
    except (OSError, ValueError, KeyError):
        out["labels_missing"] += 1
        return out
    # both lists are unique rows sorted by (u, v): walk them as sets
    mine = {(int(u), int(v)): i for i, (u, v) in enumerate(uv.tolist())}
    theirs = {(int(u), int(v)): i for i, (u, v) in enumerate(job_uv.tolist())}
    both = sorted(set(mine) & set(theirs))
    a = np.array([mine[k] for k in both], np.int64)
    b = np.array([theirs[k] for k in both], np.int64)
    out["mc_rag_edge_mismatch"] = (
        len(mine) + len(theirs) - 2 * len(both)
        + int(np.count_nonzero(faces[a] != job_faces[b])))
    if len(job_costs) != len(job_uv):
        out["mc_cost_mismatch"] = len(job_uv)
        return out
    off = np.abs(costs[a] - job_costs[b])
    p = np.clip(mean[a], 0.0, 1.0 - 1e-5)
    allowed = COST_TOLERANCE + faces[a] * _F32_ROUNDOFF / (1.0 - p)
    out["mc_cost_mismatch"] = int(np.count_nonzero(off > allowed))
    print(f"[mc_labels] {len(nodes)} fragments, {len(uv)} edges, energy job {e_job:.3f} "
          f"reference {e_ref:.3f}; cost off by at most {off.max(initial=0):.3e}, "
          f"{int(np.count_nonzero(off > COST_TOLERANCE))} edges over {COST_TOLERANCE:g}, "
          f"largest share of an edge's bound {(off / allowed).max(initial=0):.3f}, "
          f"largest edge {int(faces.max(initial=0))} faces", file=sys.stderr, flush=True)
    return out


def check_jobs(cell: dict, cfg: dict, done: List[dict], volumes: Dict[int, np.ndarray],
               seed: int) -> Dict[str, int]:
    """``done``: the completed jobs, each ``{"job", "tmp", "outputs": {name:
    (path, key)}}``.  Returns the summed counts; the energy gap is the
    largest of the jobs'."""
    slabs = _ws_labels_slabs()
    totals: Dict[str, int] = {"labels_missing": 0, "mc_energy_gap_ppm": 0}
    n_units = int(cell["check_units"])
    beta = float(cfg["params"].get("beta", 0.5))
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
        vol, ws = volumes[job.volume], read["ws"]
        # the job stores no components: the fragments stand in the argument's
        # place and the count that would compare them is dropped, as is the
        # count of labels in two slabs of one
        part = slabs.check_slab_job(vol, ws, ws, cfg, 1,
                                    fold_seed(seed, 3, job.index), n_units)
        for not_stored in ("cc_mismatch_voxels", "ws_labels_in_two_slabs"):
            part.pop(not_stored)
        mine = check_multicut(vol, ws, read["seg"], rec.get("tmp") or "", beta)
        gap = max(totals["mc_energy_gap_ppm"], mine.pop("mc_energy_gap_ppm"))
        ref.merge_counts(totals, part)
        ref.merge_counts(totals, mine)
        totals["mc_energy_gap_ppm"] = gap
    return totals
