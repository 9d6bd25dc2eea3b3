"""Round-based parallel edge contraction — the vectorized agglomeration core.

The sequential solvers in :mod:`.multicut` (GAEC heap) and
:mod:`.agglomeration` (average-linkage heap) contract ONE edge per step:
O(E log E) pops through a Python heap with dict-of-dict neighbor merges.
That is fine for the reduced subproblems of the hierarchical multicut but
cannot scale to the 512³ headline's ~800k fragments / multi-million-edge
RAGs, and none of it vectorizes.

This module replaces the *mechanism* (one edge at a time) while keeping the
*policy* (contract the most attractive edge first) approximately, via the
classic mutual-best-edge matching (Boruvka-style rounds, the same scheme as
the tile_ws basin-merge rounds):

    repeat until no contractible edge remains:
      1. every node picks its best incident contractible edge
         (max cost for GAEC, min mean-probability for average linkage;
         ties broken toward the smallest edge id — documented, total order)
      2. edges selected by BOTH endpoints contract (the picks form a
         matching, so the union step is a single parent[hi] = lo scatter —
         pointer depth 1, no find loops)
      3. endpoints remap through the new roots; parallel edges merge by
         segment-sum re-aggregation (costs add for GAEC; (weight·size,
         size) sums for average linkage)

    Progress: the globally best contractible edge is mutual-best by
    construction (any competitor at either endpoint would be globally
    better), so every round contracts ≥1 edge and the loop terminates in
    ≤ n rounds; on real RAGs the matching contracts a constant fraction of
    nodes per round, giving O(log n) rounds of O(E) vectorized work.

The result is not always bit-identical to the sequential greedy order (two
simultaneous contractions see each other's pre-merge costs), but on
multicut instances the energy tracks sequential GAEC within a couple of
percent and unambiguous instances produce identical partitions — both
regression-tested against the heap oracle.

Three implementations behind the ``impl="auto"`` ladder, mirroring the
volume kernels' substrate dispatch:

- ``"jax"``    device rounds under one jit: static edge capacity,
               ``lax.while_loop``, scatter-max best-edge selection, one
               2-key ``lax.sort`` + segment-sum per round for the
               re-aggregation (the :func:`..ops.rag.device_edge_aggregate`
               machinery) — for graphs already device-resident (fused
               RAG→costs→solve path).
- ``"native"`` the same rounds in C++ (``native/ct_native.cpp:
               ct_parallel_contract``) — the host fast path.
- ``"numpy"``  the vectorized reference implementation and the parity
               oracle for both of the above.

``impl="auto"`` resolves device-JAX on an accelerator backend for a graph of
65,536 edges or more (``_DEVICE_MIN_EDGES``), else native when the library
loads, else numpy; the sequential heap solvers
remain available as ``impl="heap"`` (and are the quality oracle in tests).
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import numpy as np

_ACCEL_PLATFORMS = ("tpu",)


# -- process-wide solver metrics ---------------------------------------------
# Same snapshot/delta pattern as the executor's dispatch counters and the
# chunk cache: the task runtime snapshots around run_impl and merges the
# delta into io_metrics.json, so every solve stops being a black box next
# to the instrumented I/O and dispatch paths (docs/PERFORMANCE.md
# "Distributed agglomeration").  ``solver_rounds`` is counted by the numpy
# reference rung (the native rung is bit-parity with it but does not
# report its loop count; the jax rung's count lives on device).

_METRICS_LOCK = threading.Lock()
_SOLVER_COUNTERS = {
    "solver_calls": 0,      # parallel_contraction invocations
    "solver_rounds": 0,     # contraction rounds (numpy rung)
    "solver_edges_in": 0,   # edges entering the solves
    "solver_edges_out": 0,  # inter-cluster edges remaining after them
}


def solver_snapshot() -> Dict[str, float]:
    """Current process-wide contraction-solver counters (monotonic; diff
    two snapshots with :func:`solver_delta` to attribute a task's share)."""
    with _METRICS_LOCK:
        return dict(_SOLVER_COUNTERS)


def solver_delta(snapshot: Dict[str, float]) -> Dict[str, float]:
    """Counter movement since ``snapshot`` (same keys)."""
    cur = solver_snapshot()
    return {k: cur[k] - snapshot.get(k, 0) for k in cur}


def _record_solver_metrics(**deltas) -> None:
    with _METRICS_LOCK:
        for k, v in deltas.items():
            _SOLVER_COUNTERS[k] += int(v)


#: a solve of fewer edges stays on the host whatever the backend.  The device
#: program is a loop of some tens of rounds, each a handful of scatters and
#: a sort that cost milliseconds however few slots they hold: 216 block
#: subproblems of about 1.3 k edges took 176 ms each on a TPU v5e, 38 s of a
#: job, where the host rungs take under a millisecond (PERF.md section 6,
#: PR 35).  Where the device first wins has not been measured (ROADMAP R5).
_DEVICE_MIN_EDGES = 1 << 16


def _resolve_impl(impl: str, n_edges: int) -> str:
    if impl != "auto":
        return impl
    if jax.default_backend() in _ACCEL_PLATFORMS and n_edges >= _DEVICE_MIN_EDGES:
        return "jax"
    from .. import native

    return "native" if native.available() else "numpy"


def _relabel_consecutive(roots: np.ndarray) -> np.ndarray:
    _, labels = np.unique(roots, return_inverse=True)
    return labels.astype(np.int64)


def sum_by_key(
    key: np.ndarray, payload: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Group-by-key payload-column sums: ``(unique_keys_sorted, sums)``.

    Stable argsort + bincount instead of ``np.unique(return_inverse)``:
    same groups, same original-order accumulation — THE documented
    summation order of the contraction engine (the native kernel
    reproduces it for bit-parity, and the reduce tree's frontier/merge
    aggregation reuses it so hierarchical solves stay bit-comparable) —
    about 2x faster per round."""
    order = np.argsort(key, kind="stable")
    ks = key[order]
    first = np.ones(len(ks), bool)
    first[1:] = ks[1:] != ks[:-1]
    uniq = ks[first]
    inv = np.empty(len(ks), np.int64)
    inv[order] = np.cumsum(first) - 1
    out = np.empty((len(uniq), payload.shape[1]), np.float64)
    for c in range(payload.shape[1]):
        out[:, c] = np.bincount(inv, weights=payload[:, c], minlength=len(uniq))
    return uniq, out


def _canonical_edges(
    n_nodes: int, edges: np.ndarray, payload: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Canonical (lo < hi) unique edges with payload columns summed over
    parallel edges; rows lexsorted — edge id == row index, the documented
    tie-break order."""
    u = np.minimum(edges[:, 0], edges[:, 1])
    v = np.maximum(edges[:, 0], edges[:, 1])
    keep = u != v
    u, v, payload = u[keep], v[keep], payload[keep]
    if len(u) == 0:
        return (
            np.zeros(0, np.int64),
            np.zeros(0, np.int64),
            np.zeros((0, payload.shape[1]), np.float64),
        )
    key = u.astype(np.int64) * np.int64(n_nodes) + v.astype(np.int64)
    uniq, out = sum_by_key(key, payload)
    return (uniq // n_nodes).astype(np.int64), (uniq % n_nodes).astype(np.int64), out


def _contract_rounds_numpy(
    n_nodes: int,
    edges: np.ndarray,
    payload: np.ndarray,
    mode: str,
    threshold: float,
) -> np.ndarray:
    """Vectorized reference implementation of the round scheme.

    ``payload``: [m, k] float64 columns summed on merge.  Priority is
    ``payload[:, 0]`` for k == 1 (GAEC cost) and
    ``payload[:, 0] / payload[:, 1]`` for k == 2 (size-weighted mean).
    ``mode="max"`` contracts while priority > threshold (GAEC);
    ``mode="min"`` while priority < threshold (average linkage).
    """
    n_nodes = int(n_nodes)
    labels = np.arange(n_nodes, dtype=np.int64)
    u, v, payload = _canonical_edges(n_nodes, edges, payload)
    sign = 1.0 if mode == "max" else -1.0
    thr = sign * float(threshold)
    rounds = 0

    while len(u):
        prio = payload[:, 0] if payload.shape[1] == 1 else (
            payload[:, 0] / np.maximum(payload[:, 1], 1e-300)
        )
        prio = sign * prio  # always maximize
        elig = prio > thr
        if not elig.any():
            break
        eid = np.arange(len(u), dtype=np.int64)
        # step 1: per-node best priority over incident contractible edges
        best_p = np.full(n_nodes, -np.inf)
        np.maximum.at(best_p, u[elig], prio[elig])
        np.maximum.at(best_p, v[elig], prio[elig])
        # among priority-ties, the smallest edge id wins (documented order)
        best_e = np.full(n_nodes, len(u), dtype=np.int64)
        cand_u = elig & (prio == best_p[u])
        cand_v = elig & (prio == best_p[v])
        np.minimum.at(best_e, u[cand_u], eid[cand_u])
        np.minimum.at(best_e, v[cand_v], eid[cand_v])
        # step 2: mutual picks form a matching -> depth-1 union
        mutual = elig & (best_e[u] == eid) & (best_e[v] == eid)
        rounds += 1
        root = np.arange(n_nodes, dtype=np.int64)
        root[v[mutual]] = u[mutual]
        labels = root[labels]
        # step 3: remap + re-aggregate parallel edges
        u, v, payload = _canonical_edges(
            n_nodes, np.stack([root[u], root[v]], axis=1), payload
        )
    _record_solver_metrics(solver_rounds=rounds)
    return _relabel_consecutive(labels)


# ---------------------------------------------------------------------------
# device implementation: the same rounds under one jit
# ---------------------------------------------------------------------------


def _contract_rounds_jax(
    n_nodes: int,
    edges: np.ndarray,
    payload: np.ndarray,
    mode: str,
    threshold: float,
) -> np.ndarray:
    import jax.numpy as jnp

    # canonicalize on host first: parallel input edges MUST merge before
    # round 1 (GAEC's additive contract — a [+1, -2] duplicate pair is net
    # repulsive), and self loops drop here, so the device program starts
    # from the same unique edge set as the numpy/native rungs
    eu, ev, payload = _canonical_edges(n_nodes, edges, payload)
    m = len(eu)
    cap = 1 << max(4, int(np.ceil(np.log2(max(m, 1)))))
    # n_nodes is a static jit argument; bucket it to the next power of two
    # so block subproblems of every distinct size share a handful of
    # compiled programs instead of one XLA compile per size
    n_pad = 1 << max(4, int(np.ceil(np.log2(max(n_nodes, 1)))))
    u = np.full(cap, n_pad, np.int32)
    v = np.full(cap, n_pad, np.int32)
    u[:m] = eu
    v[:m] = ev
    pay = np.zeros((cap, payload.shape[1]), np.float32)
    pay[:m] = payload
    labels = _device_contract(
        jnp.asarray(u), jnp.asarray(v), jnp.asarray(pay),
        jnp.float32(threshold), int(n_pad), mode, payload.shape[1],
    )
    labels = np.asarray(labels)[:n_nodes].astype(np.int64)
    return _relabel_consecutive(labels)


@partial(jax.jit, static_argnames=("n_nodes", "mode", "k"))
def _device_contract(u, v, pay, threshold, n_nodes, mode, k):
    """One jitted program: while any node still has a contractible edge,
    scatter-max best-edge selection -> matching -> parent scatter ->
    2-key sort re-aggregation.  Same pointer-jumping/segment-sum idiom as
    ops/unionfind.py and ops/rag.py::device_edge_aggregate."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    cap = u.shape[0]
    n = n_nodes
    sign = jnp.float32(1.0 if mode == "max" else -1.0)
    thr = sign * threshold
    NEG = jnp.float32(-np.inf)
    SENT = jnp.int32(n)  # padding sentinel node id

    def prio_of(pay):
        if k == 1:
            p = pay[:, 0]
        else:
            p = pay[:, 0] / jnp.maximum(pay[:, 1], jnp.float32(1e-30))
        return sign * p

    def cond(state):
        u, v, pay, labels, progressed = state
        return progressed

    def body(state):
        u, v, pay, labels, _ = state
        active = u != SENT
        prio = jnp.where(active, prio_of(pay), NEG)
        elig = active & (prio > thr)
        eid = jnp.arange(cap, dtype=jnp.int32)
        drop_u = jnp.where(elig, u, SENT)
        drop_v = jnp.where(elig, v, SENT)
        best_p = jnp.full((n + 1,), NEG).at[drop_u].max(prio, mode="drop")
        best_p = best_p.at[drop_v].max(prio, mode="drop")
        cand_u = jnp.where(elig & (prio == best_p[u]), u, SENT)
        cand_v = jnp.where(elig & (prio == best_p[v]), v, SENT)
        best_e = jnp.full((n + 1,), cap, jnp.int32).at[cand_u].min(
            eid, mode="drop"
        )
        best_e = best_e.at[cand_v].min(eid, mode="drop")
        mutual = elig & (best_e[u] == eid) & (best_e[v] == eid)
        # matching -> single scatter, depth-1 parents
        root = jnp.arange(n + 1, dtype=jnp.int32).at[
            jnp.where(mutual, v, SENT)
        ].set(jnp.where(mutual, u, SENT), mode="drop")
        labels = root[labels]
        # remap + canonicalize; contracted-away self edges -> sentinel
        ru = root[u]
        rv = root[v]
        lo = jnp.minimum(ru, rv)
        hi = jnp.maximum(ru, rv)
        dead = (lo == hi) | ~active
        lo = jnp.where(dead, SENT, lo)
        hi = jnp.where(dead, SENT, hi)
        # parallel-edge merge: 2-key sort + segment sums (rag.py idiom)
        ops = lax.sort((lo, hi) + tuple(pay[:, c] for c in range(k)), num_keys=2)
        lo, hi = ops[0], ops[1]
        cols = ops[2:]
        valid = lo != SENT
        is_first = valid & (
            (lo != jnp.concatenate([SENT[None], lo[:-1]]))
            | (hi != jnp.concatenate([SENT[None], hi[:-1]]))
        )
        seg = jnp.cumsum(is_first.astype(jnp.int32)) - 1
        sid = jnp.where(valid, seg, cap)
        new_u = jnp.full((cap + 1,), SENT, jnp.int32).at[sid].min(
            jnp.where(valid, lo, SENT), mode="drop"
        )[:cap]
        new_v = jnp.full((cap + 1,), SENT, jnp.int32).at[sid].min(
            jnp.where(valid, hi, SENT), mode="drop"
        )[:cap]
        new_pay = jnp.stack(
            [
                jax.ops.segment_sum(
                    jnp.where(valid, c, 0.0), sid, num_segments=cap + 1
                )[:cap]
                for c in cols
            ],
            axis=1,
        )
        return new_u, new_v, new_pay, labels, jnp.any(mutual)

    labels0 = jnp.arange(n + 1, dtype=jnp.int32)
    with jax.named_scope("mc.contract"):
        u, v, pay, labels, _ = lax.while_loop(
            cond, body, (u, v, pay, labels0, jnp.bool_(True))
        )
    return labels[:n]


# ---------------------------------------------------------------------------
# per-lane frontier rounds: the reduce tree's fused level program
# ---------------------------------------------------------------------------


def lane_frontier_rounds(u, v, pay, f_node, f_ghost, f_pay, threshold,
                         *, n_pad, mode, k):
    """One reduce-tree group as a device computation: canonical
    aggregation + mutual-best contraction rounds with frontier abstention,
    the exact :func:`..parallel.reduce_tree.frontier_contraction` scheme
    in f64/int64 on device.  ``vmap`` this over the padded lanes of a tree
    level and wrap it in a ``shard_map`` + ``all_gather`` to get the
    collective reduce plane's one-dispatch-per-level program
    (docs/PERFORMANCE.md "Collective reduce plane").

    Bit-identity contract (property-tested in tests/test_reduce_plane.py):
    every float op mirrors the numpy reference — f64 payloads (run under
    ``jax.experimental.enable_x64``), stable sorts whose equal-key order
    matches ``sum_by_key``'s stable argsort, and sequential scatter-adds
    whose per-segment accumulation order equals ``np.bincount``'s
    original-index order, so parallel-edge and frontier re-aggregation
    round identically and the mutual-best float comparisons see the same
    bits.  Ties break toward the smallest edge id, where ids are the
    canonical sorted rank — the same documented order as the host rungs.

    Inputs are fixed-capacity lanes (the ragged-pool marshalling idiom):
    ``u``/``v`` ``[We]`` int64 endpoints with ``n_pad`` as the padding
    sentinel, ``pay`` ``[We, k]`` f64, frontier ``f_node``/``f_ghost``/
    ``f_pay`` ``[Wf]``/``[Wf, k]`` with the same sentinel on ``f_node``.
    Static: ``n_pad`` (node capacity), ``mode``, ``k``.  Returns
    ``(labels [n_pad] raw roots, rounds)`` — the caller crops to the real
    member count and applies the consecutive relabel on host.
    """
    import jax.numpy as jnp
    from jax import lax

    We = u.shape[0]
    Wf = f_node.shape[0]
    n = n_pad
    sign = 1.0 if mode == "max" else -1.0
    thr = sign * threshold
    NEG = -jnp.inf
    SENT = jnp.int64(n)
    BIGK = jnp.int64(2 ** 62)

    def prio_of(p):
        if k == 1:
            return sign * p[:, 0]
        return sign * (p[:, 0] / jnp.maximum(p[:, 1], 1e-300))

    def agg_edges(u, v, pay):
        # _canonical_edges on device: lo<hi canonicalization, self/pad
        # edges to the sentinel, stable 2-key sort (== the host's single
        # lo*n+hi key), segment compaction so the surviving edge ids are
        # the sorted ranks, and in-order scatter-adds for the payload sums
        lo = jnp.minimum(u, v)
        hi = jnp.maximum(u, v)
        dead = (lo == hi) | (u == SENT)
        lo = jnp.where(dead, SENT, lo)
        hi = jnp.where(dead, SENT, hi)
        ops = lax.sort((lo, hi) + tuple(pay[:, c] for c in range(k)),
                       num_keys=2, is_stable=True)
        lo, hi = ops[0], ops[1]
        cols = ops[2:]
        valid = lo != SENT
        is_first = valid & (
            (lo != jnp.concatenate([SENT[None], lo[:-1]]))
            | (hi != jnp.concatenate([SENT[None], hi[:-1]]))
        )
        seg = jnp.cumsum(is_first.astype(jnp.int64)) - 1
        sid = jnp.where(valid, seg, We)
        new_u = jnp.full((We + 1,), SENT, jnp.int64).at[sid].min(
            jnp.where(valid, lo, SENT), mode="drop")[:We]
        new_v = jnp.full((We + 1,), SENT, jnp.int64).at[sid].min(
            jnp.where(valid, hi, SENT), mode="drop")[:We]
        new_pay = jnp.stack(
            [jnp.zeros((We + 1,)).at[sid].add(
                jnp.where(valid, c, 0.0), mode="drop")[:We]
             for c in cols], axis=1)
        return new_u, new_v, new_pay

    def agg_frontier(fn, fg, fpay):
        # _aggregate_frontier on device: the same fn*mult+fg key (mult
        # recomputed per call over the live entries, like the host) and
        # the same stable-sort + in-order summation
        valid = fn != SENT
        mult = jnp.maximum(jnp.max(jnp.where(valid, fg, -1)) + 1, 1)
        key = jnp.where(valid, fn * mult + fg, BIGK)
        ops = lax.sort((key,) + tuple(fpay[:, c] for c in range(k)),
                       num_keys=1, is_stable=True)
        key = ops[0]
        cols = ops[1:]
        valid = key != BIGK
        is_first = valid & (key != jnp.concatenate([BIGK[None], key[:-1]]))
        seg = jnp.cumsum(is_first.astype(jnp.int64)) - 1
        sid = jnp.where(valid, seg, Wf)
        key_seg = jnp.full((Wf + 1,), BIGK, jnp.int64).at[sid].min(
            jnp.where(valid, key, BIGK), mode="drop")[:Wf]
        live = key_seg != BIGK
        new_fn = jnp.where(live, key_seg // mult, SENT)
        new_fg = jnp.where(live, key_seg % mult, jnp.int64(0))
        new_fpay = jnp.stack(
            [jnp.zeros((Wf + 1,)).at[sid].add(
                jnp.where(valid, c, 0.0), mode="drop")[:Wf]
             for c in cols], axis=1)
        return new_fn, new_fg, new_fpay

    u, v, pay = agg_edges(u, v, pay)
    f_node, f_ghost, f_pay = agg_frontier(f_node, f_ghost, f_pay)

    def cond(state):
        return state[-1]

    def body(state):
        u, v, pay, fn, fg, fpay, labels, rounds, _ = state
        active = u != SENT
        prio = jnp.where(active, prio_of(pay), NEG)
        elig = active & (prio > thr)
        eid = jnp.arange(We, dtype=jnp.int64)
        best_p = jnp.full((n + 1,), NEG).at[
            jnp.where(elig, u, SENT)].max(prio, mode="drop")
        best_p = best_p.at[jnp.where(elig, v, SENT)].max(prio, mode="drop")
        # external competition: the frontier raises best_p but never
        # places a candidate edge id — the node abstains if it wins
        factive = fn != SENT
        fprio = jnp.where(factive, prio_of(fpay), NEG)
        felig = factive & (fprio > thr)
        best_p = best_p.at[jnp.where(felig, fn, SENT)].max(
            fprio, mode="drop")
        cand_u = jnp.where(elig & (prio == best_p[u]), u, SENT)
        cand_v = jnp.where(elig & (prio == best_p[v]), v, SENT)
        best_e = jnp.full((n + 1,), We, jnp.int64).at[cand_u].min(
            eid, mode="drop")
        best_e = best_e.at[cand_v].min(eid, mode="drop")
        mutual = elig & (best_e[u] == eid) & (best_e[v] == eid)
        progressed = jnp.any(mutual)
        root = jnp.arange(n + 1, dtype=jnp.int64).at[
            jnp.where(mutual, v, SENT)].set(
            jnp.where(mutual, u, SENT), mode="drop")
        labels = root[labels]
        u2, v2, pay2 = agg_edges(root[u], root[v], pay)
        fn2, fg2, fpay2 = agg_frontier(root[fn], fg, fpay)
        return (u2, v2, pay2, fn2, fg2, fpay2, labels,
                rounds + progressed.astype(jnp.int64), progressed)

    labels0 = jnp.arange(n + 1, dtype=jnp.int64)
    state = (u, v, pay, f_node, f_ghost, f_pay, labels0, jnp.int64(0),
             jnp.bool_(True))
    state = lax.while_loop(cond, body, state)
    return state[6][:n], state[7]


# ---------------------------------------------------------------------------
# dispatch + public entry points
# ---------------------------------------------------------------------------


def parallel_contraction(
    n_nodes: int,
    edges: np.ndarray,
    payload: np.ndarray,
    mode: str,
    threshold: float,
    impl: str = "auto",
) -> np.ndarray:
    """Run the round engine; returns int64 labels 0..k-1.

    See the module docstring for ``mode``/``payload`` semantics and the
    ``impl`` ladder.  ``impl="heap"`` is rejected here (the heap solvers
    have their own entry points with richer signatures).
    """
    n_nodes = int(n_nodes)
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    if mode not in ("max", "min"):
        raise ValueError(f"mode must be 'max' or 'min', got {mode!r}")
    if n_nodes == 0 or len(edges) == 0:
        return np.arange(n_nodes, dtype=np.int64)
    payload = np.asarray(payload, dtype=np.float64).reshape(len(edges), -1)

    labels = None
    resolved = _resolve_impl(impl, len(edges))
    if resolved == "jax":
        labels = _contract_rounds_jax(n_nodes, edges, payload, mode, threshold)
    elif resolved == "native":
        from .. import native

        labels = native.parallel_contract(
            n_nodes, edges, payload, mode == "max", threshold
        )
        if labels is None:
            if impl == "native":
                raise RuntimeError(
                    "native library unavailable for impl='native'"
                )
            resolved = "numpy"
    if labels is None:
        if resolved != "numpy":
            raise ValueError(f"unknown impl {impl!r}")
        labels = _contract_rounds_numpy(n_nodes, edges, payload, mode, threshold)
    # observability (docs/PERFORMANCE.md "Distributed agglomeration"):
    # edges-in vs surviving inter-cluster edges, per solve
    _record_solver_metrics(
        solver_calls=1,
        solver_edges_in=len(edges),
        solver_edges_out=int(
            (labels[edges[:, 0]] != labels[edges[:, 1]]).sum()
        ),
    )
    return labels


def gaec_parallel(
    n_nodes: int,
    edges: np.ndarray,
    costs: np.ndarray,
    stop_cost: float = 0.0,
    impl: str = "auto",
) -> np.ndarray:
    """Parallel GAEC: round-based contraction of mutually-best positive
    edges; parallel edges merge additively.  Drop-in for
    :func:`..ops.multicut.greedy_additive` (same contract, approximate
    greedy order — energy within a couple percent on RAG instances)."""
    if impl == "heap":
        from .multicut import greedy_additive

        return greedy_additive(n_nodes, edges, costs, stop_cost)
    costs = np.asarray(costs, dtype=np.float64).reshape(-1, 1)
    return parallel_contraction(
        n_nodes, edges, costs, "max", float(stop_cost), impl=impl
    )


def average_parallel(
    n_nodes: int,
    edges: np.ndarray,
    probs: np.ndarray,
    sizes: Optional[np.ndarray] = None,
    threshold: float = 0.5,
    impl: str = "auto",
) -> np.ndarray:
    """Parallel average-linkage agglomeration: contract mutually-cheapest
    edges while the size-weighted mean boundary probability is below
    ``threshold``.  Drop-in for
    :func:`..ops.agglomeration.average_agglomeration`."""
    if impl == "heap":
        from .agglomeration import average_agglomeration

        return average_agglomeration(
            n_nodes, edges, probs,
            np.ones(len(edges)) if sizes is None else sizes, threshold,
        )
    probs = np.asarray(probs, dtype=np.float64)
    s = (
        np.ones(len(probs), np.float64)
        if sizes is None
        else np.maximum(np.asarray(sizes, np.float64), 1e-12)
    )
    payload = np.stack([probs * s, s], axis=1)
    return parallel_contraction(
        n_nodes, edges, payload, "min", float(threshold), impl=impl
    )
