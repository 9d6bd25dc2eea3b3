"""Region-adjacency-graph extraction kernels.

TPU-native replacement for the capability the reference got from the
``nifty.distributed`` C++ layer (SURVEY.md §2a "graph", §2b): per-block RAG
extraction from a label volume, plus per-edge accumulation of boundary-map
statistics.

Design: the bandwidth-heavy part — scanning every axis-adjacent voxel pair of
a block and emitting (min-label, max-label, boundary-value) triples — is a
jitted, static-shape device kernel (:func:`axis_edge_scan`).  The
variable-size part — deduplicating pairs into an edge list and accumulating
per-edge statistics — runs on host with vectorized numpy (:func:`block_rag`),
because per-block edge counts are data-dependent and small (≲ 3·|block|)
while the scan touches every voxel.  This mirrors the reference's split, where
C++ did the scan and serialized small per-block graphs to N5.

Halo convention for blockwise extraction: each block is read with a +1 voxel
halo on its *upper* faces only.  For the scan along axis ``a`` the input is
sliced to the inner extent along every other axis and inner+1 along ``a`` —
so every voxel-face pair of the volume is owned by exactly one block and
per-edge counts add up correctly across blocks.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# per-edge accumulated statistics, in column order
FEATURE_NAMES = ("mean", "min", "max", "count", "variance")

# device programs of this module dispatched by the process, by what they
# extract (docs/OBSERVABILITY.md "Multicut"): ``BaseTask.run`` puts each
# task's share in its manifest and in io_metrics.json
_COUNTERS = {"rag_dispatches": 0, "rag_cap_retries": 0}
_COUNTERS_LOCK = threading.Lock()


def dispatch_snapshot() -> Dict[str, int]:
    with _COUNTERS_LOCK:
        return dict(_COUNTERS)


def dispatch_delta(snap: Dict[str, int]) -> Dict[str, int]:
    with _COUNTERS_LOCK:
        return {k: v - snap.get(k, 0) for k, v in _COUNTERS.items()}


def _count(**deltas: int) -> None:
    with _COUNTERS_LOCK:
        for k, v in deltas.items():
            _COUNTERS[k] += int(v)


@partial(jax.jit, static_argnames=("axis", "with_values"))
def axis_edge_scan(
    seg: jnp.ndarray,
    values: Optional[jnp.ndarray],
    axis: int,
    with_values: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Scan adjacent voxel pairs along one axis.

    For every pair ``(x, x+e_axis)`` with two *different, non-zero* labels,
    emits the pair (as min/max) and, if ``with_values``, the boundary value
    ``max(values[x], values[x+e_axis])`` (the boundary-map accumulation
    convention).  Returns flat ``(lo, hi, val, valid)`` of static length
    ``prod(shape)/shape[axis]*(shape[axis]-1)``; invalid slots have
    ``lo == hi == 0``.
    """
    ndim = seg.ndim
    sl_a = tuple(slice(0, -1) if d == axis else slice(None) for d in range(ndim))
    sl_b = tuple(slice(1, None) if d == axis else slice(None) for d in range(ndim))
    with jax.named_scope("rag.scan"):
        u = seg[sl_a].ravel()
        v = seg[sl_b].ravel()
        valid = (u != v) & (u != 0) & (v != 0)
        lo = jnp.where(valid, jnp.minimum(u, v), 0)
        hi = jnp.where(valid, jnp.maximum(u, v), 0)
        if with_values:
            va = values[sl_a].ravel()
            vb = values[sl_b].ravel()
            val = jnp.where(valid, jnp.maximum(va, vb), 0)
        else:
            val = jnp.zeros_like(lo, dtype=jnp.float32)
    return lo, hi, val, valid


@partial(jax.jit, static_argnames=("edge_cap", "with_values", "inner_shape"))
def device_edge_aggregate(
    seg: jnp.ndarray,
    values: Optional[jnp.ndarray],
    edge_cap: int,
    with_values: bool = True,
    inner_shape: Optional[Tuple[int, ...]] = None,
):
    """Sorted, deduplicated RAG edges + per-edge stats, entirely on device.

    Replaces the host-side ``np.unique(pairs, axis=0)`` in :func:`block_rag`
    (1-2s per 128^3 block, after a device->host transfer of every adjacent
    pair) with one multi-operand device sort + segmented reductions — the
    same sort-compact machinery as ops/tile_ccl.

    ``seg``: int32 labels (0 = background) — callers with uint64 global ids
    densify first.  Returns ``(lo, hi, count, vsum, vsumsq, vmin, vmax,
    shift, n_edges)`` — ``vsumsq`` is the second moment about ``shift``
    (the global value mean; see the in-body comment)
    with static length ``edge_cap`` (slots past ``n_edges`` hold lo=hi=0);
    ``n_edges > edge_cap`` means overflow (results truncated).
    """
    INT_MAX = jnp.int32(np.iinfo(np.int32).max)
    inner = tuple(inner_shape) if inner_shape is not None else seg.shape
    los, his, vals = [], [], []
    # the scan and the three axes' pair lists laid end to end are one
    # stage, ``rag.scan``: labels and values in, (lo, hi, val) out
    with jax.named_scope("rag.scan"):
        for axis in range(seg.ndim):
            # the block-ownership halo convention (module docstring):
            # inner+1 along the scan axis, inner along the others
            bb = tuple(
                slice(0, min(inner[d] + 1, seg.shape[d]))
                if d == axis
                else slice(0, inner[d])
                for d in range(seg.ndim)
            )
            lo, hi, val, valid = axis_edge_scan(
                seg[bb], None if values is None else values[bb], axis,
                with_values=with_values,
            )
            los.append(jnp.where(valid, lo, INT_MAX))
            his.append(jnp.where(valid, hi, INT_MAX))
            vals.append(val)
        lo = jnp.concatenate(los).astype(jnp.int32)
        hi = jnp.concatenate(his).astype(jnp.int32)
        val = jnp.concatenate(vals).astype(jnp.float32)
    with jax.named_scope("rag.aggregate"):
        return _aggregate_sorted(lo, hi, val, edge_cap, with_values)


def _aggregate_sorted(lo, hi, val, edge_cap: int, with_values: bool):
    """The ``rag.aggregate`` stage of :func:`device_edge_aggregate`: one
    two-key sort of the pair list, then every edge's pair and count read off
    its run and, with values, segmented reductions per edge."""
    from jax import lax

    INT_MAX = jnp.int32(np.iinfo(np.int32).max)
    lo, hi, val = lax.sort((lo, hi, val), num_keys=2)
    valid = lo != INT_MAX
    is_first = valid & (
        (lo != jnp.concatenate([INT_MAX[None], lo[:-1]]))
        | (hi != jnp.concatenate([INT_MAX[None], hi[:-1]]))
    )
    seg_id = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    n_edges = jnp.where(valid.any(), seg_id[-1] + 1, 0)
    # the list is sorted by edge, so an edge is a run of it: the run's first
    # slot by a binary search of the run ids, its pair read there, its count
    # the distance to the next run's first slot.  No scatter: the three that
    # stood here cost 20 ms a dispatch on a TPU v5e, all of a graph block's
    # device time (PERF.md section 6, PR 35)
    slots = jnp.arange(edge_cap, dtype=jnp.int32)
    first = jnp.searchsorted(seg_id, slots, side="left").astype(jnp.int32)
    n_valid = jnp.sum(valid.astype(jnp.int32))
    following = jnp.minimum(jnp.concatenate([first[1:], n_valid[None]]), n_valid)
    live = slots < n_edges
    at = jnp.minimum(first, lo.shape[0] - 1)
    count = jnp.where(live, following - first, 0)
    out_lo = jnp.where(live, lo[at], 0)
    out_hi = jnp.where(live, hi[at], 0)
    if with_values:
        sid = jnp.where(valid, jnp.minimum(seg_id, edge_cap), edge_cap)
        vsum = jax.ops.segment_sum(
            jnp.where(valid, val, 0.0), sid, num_segments=edge_cap + 1
        )[:-1]
        # second moment about the GLOBAL value mean, not zero: for values
        # clustered away from 0 (8-bit intensities, probabilities near 1)
        # E[x^2] - mean^2 in float32 is catastrophic cancellation — shifting
        # makes both accumulated terms proportional to the spread instead
        shift = jnp.sum(jnp.where(valid, val, 0.0)) / jnp.maximum(
            jnp.sum(valid.astype(jnp.float32)), 1.0
        )
        d = val - shift
        vsumsq = jax.ops.segment_sum(
            jnp.where(valid, d * d, 0.0), sid, num_segments=edge_cap + 1
        )[:-1]
        vmin = jax.ops.segment_min(
            jnp.where(valid, val, jnp.float32(np.inf)), sid,
            num_segments=edge_cap + 1,
        )[:-1]
        vmax = jax.ops.segment_max(
            jnp.where(valid, val, jnp.float32(-np.inf)), sid,
            num_segments=edge_cap + 1,
        )[:-1]
    else:
        shift = jnp.float32(0.0)
        vsum = vsumsq = vmin = vmax = jnp.zeros((edge_cap,), jnp.float32)
    return out_lo, out_hi, count, vsum, vsumsq, vmin, vmax, shift, n_edges


def _densify_labels(seg: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host-densify a label block to int32 ids: returns ``(dense, table)``
    with ``table[dense] == seg`` and ``table[0] == 0`` (background keeps
    slot 0).  Shared by every device RAG path so the int32 guard and the
    dtype-preserving zero-prepend stay in one place."""
    uniq = np.unique(seg)
    if uniq[0] != 0:
        # dtype-preserving prepend: a bare [0] would promote uint64
        # labels to float64 and corrupt ids above 2**53
        uniq = np.concatenate([np.zeros(1, uniq.dtype), uniq])
    if len(uniq) >= 2**31:
        raise ValueError("block has too many labels for int32 densification")
    return np.searchsorted(uniq, seg).astype(np.int32), uniq


@partial(jax.jit, static_argnames=("edge_cap", "inner_shape"))
def device_rag_costs(
    seg: jnp.ndarray,
    values: jnp.ndarray,
    edge_cap: int,
    beta,
    inner_shape: Optional[Tuple[int, ...]] = None,
):
    """Fused RAG -> costs -> dense remap, one jitted program.

    Extends :func:`device_edge_aggregate` with the two host stages every
    graph workflow used to run between extraction and solve:

    - the ``probs_to_costs`` transform (tasks/costs.py) on the per-edge mean
      boundary value, computed in-program from the segment sums,
    - dense node remapping: the unique edge-endpoint labels are compacted on
      device (one more sort over the 2*edge_cap endpoint slots — edge-scale,
      not voxel-scale) and the edge list is rewritten in dense node indices,
      eliminating the host ``np.unique(uv)`` + remap round-trip.

    Returns ``(node_table, n_nodes, lo_dense, hi_dense, costs, count,
    mean, n_edges)``; ``node_table`` has static length ``2 * edge_cap``
    (slots past ``n_nodes`` hold int32 max) and carries the dense->seg-label
    mapping.  ``beta`` is a traced scalar (no recompile per value).
    """
    from jax import lax

    INT_MAX = jnp.int32(np.iinfo(np.int32).max)
    (lo, hi, count, vsum, _vsumsq, _vmin, _vmax, _shift,
     n_edges) = device_edge_aggregate(
        seg, values, edge_cap, with_values=True, inner_shape=inner_shape
    )
    valid = jnp.arange(edge_cap) < n_edges
    with jax.named_scope("rag.costs"):
        mean = jnp.where(valid, vsum / jnp.maximum(count, 1), 0.0)
        eps = jnp.float32(1e-5)
        p = jnp.clip(mean, eps, 1.0 - eps)
        beta = jnp.clip(jnp.asarray(beta, jnp.float32), eps, 1.0 - eps)
        costs = jnp.where(
            valid, jnp.log((1.0 - p) / p) + jnp.log((1.0 - beta) / beta), 0.0
        )
    # dense node compaction over the endpoint slots (sort-compact idiom)
    lab = jnp.concatenate(
        [jnp.where(valid, lo, INT_MAX), jnp.where(valid, hi, INT_MAX)]
    )
    lab = lax.sort(lab)
    lvalid = lab != INT_MAX
    is_first = lvalid & (lab != jnp.concatenate([INT_MAX[None], lab[:-1]]))
    nid = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    n_nodes = jnp.where(lvalid.any(), nid[-1] + 1, 0)
    node_table = jnp.full((2 * edge_cap,), INT_MAX, jnp.int32).at[
        jnp.where(is_first, nid, 2 * edge_cap - 1)
    ].min(jnp.where(is_first, lab, INT_MAX))
    lo_dense = jnp.where(
        valid, jnp.searchsorted(node_table, lo).astype(jnp.int32), 0
    )
    hi_dense = jnp.where(
        valid, jnp.searchsorted(node_table, hi).astype(jnp.int32), 0
    )
    return node_table, n_nodes, lo_dense, hi_dense, costs, count, mean, n_edges


def block_rag_fused(
    seg: np.ndarray,
    values: np.ndarray,
    beta: float = 0.5,
    inner_shape: Optional[Sequence[int]] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Solver-ready block problem straight from the label volume.

    One device program (:func:`device_rag_costs`) extracts the RAG,
    deduplicates edges, turns mean boundary values into signed multicut
    costs, and compacts node ids — the host sees only edge-scale arrays.
    ``seg`` may be any integer dtype; labels that do not fit int32 take the
    densify-first path of :func:`block_rag` internally.

    Returns ``(nodes, edges, costs, sizes, mean)``: ``nodes`` the original
    labels (dense index -> label, sorted ascending), ``edges`` int64 [m, 2]
    in dense indices, ``costs`` float32 (``probs_to_costs`` with ``beta``),
    ``sizes`` int64 contact counts, ``mean`` float32 mean boundary value.
    """
    if seg.ndim != 3:
        raise ValueError("block_rag_fused expects a 3-D block")
    inner = tuple(inner_shape) if inner_shape is not None else seg.shape
    orig_table = None
    # dtype bound first: skips the O(voxels) host max() scan entirely for
    # label dtypes that cannot trip the int32 guard
    if seg.dtype.kind not in "iu" or (
        np.iinfo(seg.dtype).max >= np.iinfo(np.int32).max
        and seg.size
        and int(seg.max()) >= np.iinfo(np.int32).max
    ):
        # uint64 global ids: densify on host first (the _block_rag_device
        # path), then map the node table back at the end
        seg, orig_table = _densify_labels(seg)
    seg_j = jnp.asarray(np.ascontiguousarray(seg).astype(np.int32, copy=False))
    vals_j = jnp.asarray(values, jnp.float32)

    cap = 1 << 14
    while True:
        (node_table, n_nodes, lo, hi, costs, count, mean,
         n_edges) = device_rag_costs(
            seg_j, vals_j, cap, float(beta), inner_shape=inner
        )
        n = int(n_edges)
        _count(rag_dispatches=1, rag_cap_retries=n > cap)
        if n <= cap:
            break
        while cap < n:
            cap *= 2
    k = int(n_nodes)
    # fetched whole and cut on the host (see _block_rag_device)
    node_table, lo, hi, costs, count, mean = jax.device_get(
        (node_table, lo, hi, costs, count, mean))
    nodes = node_table[:k].astype(np.int64)
    if orig_table is not None:
        nodes = orig_table[nodes]
    edges = np.stack([lo[:n], hi[:n]], axis=1).astype(np.int64)
    return (
        nodes,
        edges,
        costs[:n].astype(np.float32),
        count[:n].astype(np.int64),
        mean[:n].astype(np.float32),
    )


def block_rag(
    seg: np.ndarray,
    values: Optional[np.ndarray] = None,
    inner_shape: Optional[Sequence[int]] = None,
    return_nodes: bool = False,
):
    """Extract the RAG of one block: unique undirected edges + edge sizes
    (+ per-edge boundary statistics if ``values`` given).

    ``seg`` may include a +1 upper-face halo; pass the halo-free extent as
    ``inner_shape`` and each axis scan is restricted per the module halo
    convention (each voxel pair owned by exactly one block).

    Returns ``(uv, sizes, feats)``:

    - ``uv``     uint64 [m, 2], lexsorted, ``uv[:, 0] < uv[:, 1]``, label 0
      (background / ignore) excluded,
    - ``sizes``  int64 [m], number of voxel-face contacts per edge,
    - ``feats``  float32 [m, 5] per-edge (mean, min, max, count, variance) of the
      boundary values, or None.

    With ``return_nodes`` a fourth element is appended: the sorted unique
    non-zero labels of the *inner* (halo-free) region — the block's node
    set, computed from the extraction's own label pass instead of a second
    host ``np.unique`` over the voxels (the graph task used to re-scan).

    3-D blocks dedup on device (:func:`device_edge_aggregate` — one sort +
    segmented reductions instead of shipping every adjacent pair to the host
    for ``np.unique``); other ranks use the host path
    (:func:`_block_rag_host`, also the device path's parity oracle).
    """
    inner = tuple(inner_shape) if inner_shape is not None else seg.shape
    if seg.ndim == 3:
        out = _block_rag_device(seg, values, inner, return_nodes=return_nodes)
    else:
        out = _block_rag_host(seg, values, inner)
        if return_nodes:
            inner_bb = tuple(slice(0, s) for s in inner)
            nodes = np.unique(np.asarray(seg[inner_bb]))
            out = out + (nodes[nodes != 0],)
    return out


def _block_rag_host(
    seg: np.ndarray, values: Optional[np.ndarray], inner: Tuple[int, ...]
) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Host-dedup RAG extraction (np.unique over all adjacent pairs)."""
    with_values = values is not None
    seg_j = jnp.asarray(seg)
    val_j = jnp.asarray(values, dtype=jnp.float32) if with_values else None
    los, his, vals = [], [], []
    for axis in range(seg.ndim):
        bb = tuple(
            slice(0, min(inner[d] + 1, seg.shape[d]))
            if d == axis
            else slice(0, inner[d])
            for d in range(seg.ndim)
        )
        lo, hi, val, valid = axis_edge_scan(
            seg_j[bb], None if val_j is None else val_j[bb], axis, with_values
        )
        _count(rag_dispatches=1)
        valid = np.asarray(valid)
        los.append(np.asarray(lo)[valid])
        his.append(np.asarray(hi)[valid])
        if with_values:
            vals.append(np.asarray(val)[valid])
    lo = np.concatenate(los)
    hi = np.concatenate(his)
    if len(lo) == 0:
        uv = np.zeros((0, 2), np.uint64)
        feats = np.zeros((0, len(FEATURE_NAMES)), np.float32) if with_values else None
        return uv, np.zeros(0, np.int64), feats
    pairs = np.stack([lo, hi], axis=1).astype(np.uint64)
    uv, inv, sizes = np.unique(
        pairs, axis=0, return_inverse=True, return_counts=True
    )
    inv = inv.ravel()
    if not with_values:
        return uv, sizes.astype(np.int64), None
    v = np.concatenate(vals).astype(np.float64)
    m = len(uv)
    s = np.zeros(m, np.float64)
    np.add.at(s, inv, v)
    sq = np.zeros(m, np.float64)
    np.add.at(sq, inv, v * v)
    mn = np.full(m, np.inf)
    np.minimum.at(mn, inv, v)
    mx = np.full(m, -np.inf)
    np.maximum.at(mx, inv, v)
    mean = s / sizes
    var = np.maximum(sq / sizes - mean * mean, 0.0)
    feats = np.stack(
        [mean, mn, mx, sizes.astype(np.float64), var], axis=1
    ).astype(np.float32)
    return uv, sizes.astype(np.int64), feats


def _block_rag_device(
    seg: np.ndarray,
    values: Optional[np.ndarray],
    inner: Tuple[int, ...],
    return_nodes: bool = False,
):
    """Device-dedup path of :func:`block_rag` (3-D blocks).

    Labels are densified on host (one unique over the block's voxels — tiny
    next to a unique over every adjacent *pair*), aggregated on device, and
    mapped back to the original uint64 ids.  The static edge capacity starts
    at a power-of-two estimate and doubles on overflow, so each capacity
    bucket compiles once per process.
    """
    with_values = values is not None
    dense, uniq = _densify_labels(seg)
    # a block at the volume's upper faces comes without its halo plane
    # there: padded with background (no pair holds a 0) to the shape of an
    # inner block, so that one compiled program serves every block of a
    # grid instead of one per combination of faces
    pad = [(0, max(i + 1 - s, 0)) for i, s in zip(inner, dense.shape)]
    if any(p for _, p in pad):
        dense = np.pad(dense, pad)
        values = None if values is None else np.pad(values, pad)
    vals_j = None if values is None else jnp.asarray(values, jnp.float32)

    cap = 1 << 14
    while True:
        (lo, hi, count, vsum, vsumsq, vmin, vmax, shift,
         n_edges) = device_edge_aggregate(
            jnp.asarray(dense), vals_j, cap, with_values=with_values,
            inner_shape=tuple(inner),
        )
        n = int(n_edges)
        _count(rag_dispatches=1, rag_cap_retries=n > cap)
        if n <= cap:
            break
        while cap < n:
            cap *= 2
    # fetched whole and cut on the host: a slice of a device array to a
    # length that the data decide would compile a program per length
    lo, hi, count = jax.device_get((lo, hi, count))
    lo = lo[:n].astype(np.int64)
    hi = hi[:n].astype(np.int64)
    sizes = count[:n].astype(np.int64)
    uv = np.stack([uniq[lo], uniq[hi]], axis=1).astype(np.uint64)
    nodes: Tuple = ()
    if return_nodes:
        # inner node set from the dense table (int32 pass over the inner
        # region, cheaper than re-uniquing the original-dtype labels)
        inner_bb = tuple(slice(0, s) for s in inner)
        inner_ids = np.unique(dense[inner_bb])
        inner_lab = uniq[inner_ids]
        nodes = (inner_lab[inner_lab != 0],)
    if not with_values:
        return (uv, sizes, None) + nodes
    vsum, vsumsq, vmin, vmax = jax.device_get((vsum, vsumsq, vmin, vmax))
    s = np.asarray(vsum[:n], np.float64)
    sq = np.asarray(vsumsq[:n], np.float64)
    mean = s / np.maximum(sizes, 1)
    # sq is the second moment about the global shift c:
    # var = E[(x-c)^2] - (mean-c)^2
    c = float(shift)
    var = np.maximum(sq / np.maximum(sizes, 1) - (mean - c) ** 2, 0.0)
    feats = np.stack(
        [
            mean,
            np.asarray(vmin[:n], np.float64),
            np.asarray(vmax[:n], np.float64),
            sizes.astype(np.float64),
            var,
        ],
        axis=1,
    ).astype(np.float32)
    return (uv, sizes, feats) + nodes


def merge_edge_lists(edge_lists) -> Tuple[np.ndarray, np.ndarray]:
    """Merge per-block ``(uv, sizes)`` lists into one global edge list.

    Returns ``(uv, sizes)`` with unique lexsorted rows; sizes summed across
    blocks (each voxel-face contact is counted by exactly one block, per the
    module halo convention).
    """
    uvs = [uv for uv, _ in edge_lists if len(uv)]
    if not uvs:
        return np.zeros((0, 2), np.uint64), np.zeros(0, np.int64)
    all_uv = np.concatenate(uvs)
    all_sz = np.concatenate([sz for _, sz in edge_lists if len(sz)])
    uv, inv = np.unique(all_uv, axis=0, return_inverse=True)
    sizes = np.zeros(len(uv), np.int64)
    np.add.at(sizes, inv.ravel(), all_sz)
    return uv, sizes


def merge_feature_lists(uv_global: np.ndarray, parts) -> np.ndarray:
    """Weighted merge of per-block edge features onto the global edge list.

    ``parts`` iterates ``(uv, feats)`` with feats columns
    :data:`FEATURE_NAMES`.  Mean is count-weighted; min/max are reduced;
    counts are summed; variance merges through the streaming (Chan)
    parallel combine — running mean + second moment about it — which stays
    accurate for large-mean data where the naive E[x^2] - mean^2
    reconstruction cancels catastrophically.  Edges absent from all parts
    get zeros.
    """
    m = len(uv_global)

    from .. import native

    merged = native.merge_edge_features(parts, uv_global)
    if merged is not None:
        mean, m2, mn, mx, cnt = merged
    else:
        mean = np.zeros(m, np.float64)
        m2 = np.zeros(m, np.float64)
        mn = np.full(m, np.inf)
        mx = np.full(m, -np.inf)
        cnt = np.zeros(m, np.float64)
        for uv, feats in parts:
            if len(uv) == 0:
                continue
            feats = np.asarray(feats)
            if feats.ndim != 2 or feats.shape[1] != len(FEATURE_NAMES):
                raise ValueError(
                    f"edge-feature block has shape {feats.shape}, expected "
                    f"(m, {len(FEATURE_NAMES)}) {FEATURE_NAMES} — regenerate "
                    "per-block features written by an older format"
                )
            ids = find_edge_ids(uv_global, uv)
            ok = ids >= 0
            ids = ids[ok]
            f = feats[ok].astype(np.float64)
            nb = f[:, 3]
            pos = nb > 0
            ids, f, nb = ids[pos], f[pos], nb[pos]
            # the streaming combine below uses fancy-index updates, which
            # are last-write-wins on duplicate ids — enforce the per-part
            # uniqueness every producer (np.unique output) guarantees
            # rather than corrupt counts silently
            if len(ids) != len(np.unique(ids)):
                raise ValueError(
                    "edge-feature part contains duplicate edge rows — "
                    "merge duplicates (np.unique per block) before "
                    "merge_feature_lists"
                )
            na = cnt[ids]
            ntot = na + nb
            delta = f[:, 0] - mean[ids]
            mean[ids] += delta * nb / ntot
            m2[ids] += f[:, 4] * nb + delta * delta * na * nb / ntot
            np.minimum.at(mn, ids, f[:, 1])
            np.maximum.at(mx, ids, f[:, 2])
            cnt[ids] = ntot
    has = cnt > 0
    var = np.zeros(m, np.float64)
    var[has] = np.maximum(m2[has] / cnt[has], 0.0)
    mean = np.where(has, mean, 0.0)
    mn[~has] = 0.0
    mx[~has] = 0.0
    return np.stack([mean, mn, mx, cnt, var], axis=1).astype(np.float32)


def find_edge_ids(uv_sorted: np.ndarray, uv_query: np.ndarray) -> np.ndarray:
    """Row-index of each query edge in a lexsorted unique edge array.

    Works on original (uint64) or dense labels; missing edges map to -1.
    Implemented via a structured-view searchsorted, avoiding overflow of
    packed keys for large label spaces.
    """
    if len(uv_query) == 0:
        return np.zeros(0, np.int64)
    if len(uv_sorted) == 0:
        return np.full(len(uv_query), -1, np.int64)
    # structured dtype: field-wise *numeric* comparison (a raw-bytes void
    # view would compare little-endian integers in byte order and silently
    # mis-sort any label >= 256)
    dt = uv_sorted.dtype
    struct_dt = np.dtype([("u", dt), ("v", dt)])

    def as_struct(arr):
        s = np.empty(len(arr), dtype=struct_dt)
        s["u"] = arr[:, 0]
        s["v"] = arr[:, 1]
        return s

    av = as_struct(uv_sorted)
    qv = as_struct(uv_query.astype(dt, copy=False))
    idx = np.searchsorted(av, qv)
    idx_c = np.clip(idx, 0, len(av) - 1)
    found = av[idx_c] == qv
    return np.where(found, idx_c, -1).astype(np.int64)
