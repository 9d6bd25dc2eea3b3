"""The plain reference of the multicut deployment (``mc_fused_384``).

numpy only, float64, nothing of the program imported: what upstream's
``MulticutSegmentationWorkflow`` computes between the fragments and the
merged labels, written down as plainly as it can be.

* :func:`rag`: the region-adjacency graph of a label volume, from all
  6-neighbour pairs of unequal non-zero labels, with the number of voxel
  faces of every edge (upstream: ``nifty.distributed`` block graphs merged).
* :func:`edge_means`: per edge the mean over its faces of
  ``max(b[x], b[y])``, the boundary-map accumulation of upstream's
  ``block_edge_features`` (its first feature column).
* :func:`probs_to_costs`: ``log((1 - p) / p) + log((1 - beta) / beta)``.
* :func:`energy`: the multicut objective of a partition, the summed costs
  of the cut edges.
* :func:`solve`: greedy additive edge contraction, then Kernighan-Lin.

Departures from upstream, each because the deployment's program states it:

* costs: upstream's ``transform_probabilities_to_costs`` first squeezes the
  probabilities into [0.001, 0.999] by an affine map; the program (and so
  this file) clips them to [1e-5, 1 - 1e-5] and leaves the rest alone
  (``tasks/costs.py``'s documented transform).  No size weighting
  (``weighting_scheme`` null, upstream's default for a boundary map).
* solver: upstream runs nifty's Kernighan-Lin (greedy-additive warm start)
  on every subproblem and on the reduced global problem; this reference
  runs the same pair once, on the whole graph, with no decomposition.  It
  is a yardstick for the energy the hierarchical solve reaches, not a copy
  of its route.  Its Kernighan-Lin gives a move sequence up 10 moves after
  the sequence's best prefix (nifty walks every node of both sets).
* label 0 is no node (the program's and upstream's ignore label).
"""

from __future__ import annotations

import heapq
from typing import Dict, Tuple

import numpy as np

# --------------------------------------------------------------------------
# graph and costs
# --------------------------------------------------------------------------


def _face_pairs(labels: np.ndarray, axis: int):
    """(lo label, hi label, index mask) of the faces along ``axis`` that
    separate two unequal non-zero labels."""
    a = [slice(None)] * labels.ndim
    b = [slice(None)] * labels.ndim
    a[axis], b[axis] = slice(0, -1), slice(1, None)
    u, v = labels[tuple(a)], labels[tuple(b)]
    at = np.nonzero((u != v) & (u != 0) & (v != 0))
    u, v = u[at], v[at]
    return np.minimum(u, v), np.maximum(u, v), at, tuple(a), tuple(b)


def rag(labels: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``(uv, faces)``: the edges as rows of two labels (``u < v``, sorted
    by ``u`` then ``v``) and the voxel faces of each."""
    uv, _, faces = _rag_with_means(labels, None)
    return uv, faces


def edge_means(labels: np.ndarray, boundary: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(uv, mean, faces)``: per edge the float64 mean over its faces of
    the larger of the two voxels' boundary values."""
    return _rag_with_means(labels, boundary)


def _rag_with_means(labels, boundary):
    los, his, vals = [], [], []
    for axis in range(labels.ndim):
        lo, hi, at, a, b = _face_pairs(labels, axis)
        los.append(lo)
        his.append(hi)
        if boundary is not None:
            vals.append(np.maximum(boundary[a][at], boundary[b][at]).astype(np.float64))
    lo = np.concatenate(los).astype(np.uint64)
    hi = np.concatenate(his).astype(np.uint64)
    if len(lo) == 0:
        return np.zeros((0, 2), np.uint64), np.zeros(0), np.zeros(0, np.int64)
    order = np.lexsort((hi, lo))
    lo, hi = lo[order], hi[order]
    first = np.ones(len(lo), bool)
    first[1:] = (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])
    starts = np.flatnonzero(first)
    faces = np.diff(np.append(starts, len(lo))).astype(np.int64)
    uv = np.stack([lo[starts], hi[starts]], axis=1)
    mean = None
    if boundary is not None:
        mean = np.add.reduceat(np.concatenate(vals)[order], starts) / faces
    return uv, mean, faces


def probs_to_costs(probs: np.ndarray, beta: float = 0.5, eps: float = 1e-5) -> np.ndarray:
    p = np.clip(np.asarray(probs, np.float64), eps, 1.0 - eps)
    return np.log((1.0 - p) / p) + np.log((1.0 - beta) / beta)


def energy(edges: np.ndarray, costs: np.ndarray, node_labels: np.ndarray) -> float:
    """Summed costs of the edges whose two nodes carry different labels;
    ``edges`` in node indices."""
    cut = node_labels[edges[:, 0]] != node_labels[edges[:, 1]]
    return float(np.asarray(costs, np.float64)[cut].sum())


def connected_in(edges: np.ndarray, node_labels: np.ndarray) -> int:
    """Segments (values of ``node_labels``) whose nodes are not connected
    by ``edges`` that stay inside the segment."""
    n = len(node_labels)
    parent = np.arange(n)

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    inside = node_labels[edges[:, 0]] == node_labels[edges[:, 1]]
    for u, v in edges[inside]:
        ru, rv = find(int(u)), find(int(v))
        if ru != rv:
            parent[max(ru, rv)] = min(ru, rv)
    roots = np.array([find(i) for i in range(n)])
    pieces = {}
    for seg, root in zip(node_labels.tolist(), roots.tolist()):
        pieces.setdefault(seg, set()).add(root)
    return sum(1 for roots_of in pieces.values() if len(roots_of) > 1)


# --------------------------------------------------------------------------
# the plain solve: greedy additive edge contraction, then Kernighan-Lin
# --------------------------------------------------------------------------


def greedy_additive(n: int, edges: np.ndarray, costs: np.ndarray) -> np.ndarray:
    """Contract the most attractive edge while one is positive; the costs of
    edges that a contraction makes parallel add up."""
    adj = [dict() for _ in range(n)]
    for (u, v), c in zip(edges.tolist(), np.asarray(costs, np.float64).tolist()):
        if u != v:
            adj[u][v] = adj[u].get(v, 0.0) + c
            adj[v][u] = adj[u][v]
    parent = list(range(n))
    heap = [(-c, u, v) for u in range(n) for v, c in adj[u].items() if u < v and c > 0]
    heapq.heapify(heap)
    while heap:
        negc, u, v = heapq.heappop(heap)
        if parent[u] != u or parent[v] != v or adj[u].get(v) != -negc:
            continue  # stale: a node gone, or the edge's cost has changed
        if len(adj[u]) < len(adj[v]):
            u, v = v, u
        parent[v] = u  # v into u
        del adj[u][v]
        for w, c in adj[v].items():
            if w == u:
                continue
            del adj[w][v]
            total = adj[u].get(w, 0.0) + c
            adj[u][w] = adj[w][u] = total
            if total > 0:
                heapq.heappush(heap, (-total, min(u, w), max(u, w)))
        adj[v] = {}
    out = np.empty(n, np.int64)
    for i in range(n):
        r = i
        while parent[r] != r:
            r = parent[r]
        out[i] = r
    return np.unique(out, return_inverse=True)[1]


def _two_set_refine(a, b, adj, labels, la: int, lb: int, patience: int = 10):
    """The Kernighan-Lin inner loop on two neighbouring sets: move one node
    at a time across the cut, the move with the largest gain first, each
    node at most once, negative gains too; keep the prefix of the sequence
    that gained most (the sequence is given up ``patience`` moves after its
    best prefix).  A node can be moved once it touches the other set, so
    the walk starts from the nodes along the cut and spreads from there.
    Returns ``(gain, moved nodes)``; ``labels`` is updated."""
    side = {}  # the nodes moved so far; every other node is where labels says

    def at(y):
        return side.get(y, labels[y])

    def gain(x):
        # what x cuts now towards the other set, less what it would cut
        # towards its own
        sx, g = at(x), 0.0
        for y, c in adj[x].items():
            sy = at(y)
            if sy == la or sy == lb:
                g += c if sy != sx else -c
        return g

    small, other = (a, lb) if len(a) <= len(b) else (b, la)
    free = set()
    for x in small:
        across = [y for y in adj[x] if labels[y] == other]
        if across:
            free.add(x)
            free.update(across)
    gains = {}
    for x in free:  # gain(x) with nothing moved yet, spelled out: the hot loop
        sx, g = labels[x], 0.0
        for y, c in adj[x].items():
            sy = labels[y]
            if sy == la or sy == lb:
                g += c if sy != sx else -c
        gains[x] = g
    moved, total, best, best_at = [], 0.0, 0.0, 0
    while free and len(moved) - best_at < patience:
        x = max(free, key=lambda k: (gains[k], -k))
        free.discard(x)
        total += gains[x]
        sx = side[x] = lb if labels[x] == la else la
        moved.append(x)
        for y, c in adj[x].items():
            if y in side or labels[y] not in (la, lb):
                continue
            if y in free:
                gains[y] += -2.0 * c if labels[y] == sx else 2.0 * c
            else:
                free.add(y)
                gains[y] = gain(y)
        if total > best + 1e-12:
            best, best_at = total, len(moved)
    for x in moved[:best_at]:
        labels[x] = side[x]
    return best, moved[:best_at]


def kernighan_lin(n: int, edges: np.ndarray, costs: np.ndarray, labels: np.ndarray,
                  max_sweeps: int = 20) -> np.ndarray:
    """Refine ``labels``: for every pair of sets that an edge connects, join
    them if that lowers the energy, else run the two-set refinement; sweep
    until nothing gains.  After the first sweep only the pairs with a set
    that the sweep before changed are looked at again."""
    costs = np.asarray(costs, np.float64)
    adj = [dict() for _ in range(n)]
    for (u, v), c in zip(edges.tolist(), costs.tolist()):
        if u != v:
            adj[u][v] = adj[u].get(v, 0.0) + c
            adj[v][u] = adj[u][v]
    labels = np.asarray(labels, np.int64).tolist()
    members: Dict[int, set] = {}
    for x, lab in enumerate(labels):
        members.setdefault(lab, set()).add(x)
    changed = set(members)
    for _ in range(max_sweeps):
        lab = np.asarray(labels)
        lu, lv = lab[edges[:, 0]], lab[edges[:, 1]]
        pairs = np.unique(np.stack([np.minimum(lu, lv), np.maximum(lu, lv)], axis=1)[lu != lv], axis=0)
        gained, now = 0.0, set()
        for la, lb in pairs.tolist():
            if (la not in changed and lb not in changed) or la not in members or lb not in members:
                continue
            a, b = members[la], members[lb]
            small, other = (a, lb) if len(a) <= len(b) else (b, la)
            cut = sum(c for x in small for y, c in adj[x].items() if labels[y] == other)
            if cut > 1e-12:  # joining removes a cut that costs
                for x in b:
                    labels[x] = la
                a |= members.pop(lb)
                gained += cut
                now.update((la, lb))
                continue
            gain, moved = _two_set_refine(a, b, adj, labels, la, lb)
            if moved:
                for x in moved:  # labels[x] is the set x went to
                    (a if labels[x] == la else b).add(x)
                    (b if labels[x] == la else a).discard(x)
                for key in (la, lb):
                    if not members[key]:
                        del members[key]
                gained += gain
                now.update((la, lb))
        changed = now
        if gained <= 1e-9:
            break
    return np.unique(np.asarray(labels), return_inverse=True)[1]


def solve(n: int, edges: np.ndarray, costs: np.ndarray) -> np.ndarray:
    return kernighan_lin(n, edges, costs, greedy_additive(n, edges, costs))
