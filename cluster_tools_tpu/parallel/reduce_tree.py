"""Distributed agglomeration: shard the global solve over an octant reduce tree.

The hierarchical multicut (tasks/multicut.py) funnels every boundary edge of
the reduced RAG into ONE process for the final ``SolveGlobal`` — the last
stage that cannot scale past a single host (ROADMAP item 3).  This module
shards that solve:

1. **Partition** the graph's nodes into ``solver_shards`` spatially
   contiguous shards — Morton order over the owning blocks' grid positions,
   so each shard is an octant-shaped run of the block grid and the edges
   crossing shards are (near-)minimal boundary faces.
2. **Solve locally per shard** with *frontier-aware* contraction rounds
   (:func:`frontier_contraction`, the same mutual-best-edge rounds as
   :mod:`..ops.contraction`): the shard's still-external boundary edges
   compete in every node's best-pick but can never match, so a node whose
   strongest affinity crosses the shard boundary ABSTAINS — its merge is
   deferred to the tree level where that edge becomes internal and is
   decided with fully aggregated context — instead of being absorbed into
   an interior cluster the global solver would have cut.  This is what
   keeps the sharded energy within 0.1% of the single-host solve
   (boundary-blind leaf solves lose 1-3% on the solver-scale bench
   instances; measured in ``make bench-solve``).  Contraction can merge
   but never split, so a leaf that under-merges is always repairable
   higher up; edges a level leaves cut stay in the problem as
   (net-repulsive) context for its ancestors.
3. **Merge up a reduce tree** of configurable ``fanout`` ("Near-Optimal
   Wafer-Scale Reduce", PAPERS.md): at each level, groups of ``fanout``
   children fuse — only the edges between their spans become internal and
   are solved, everything still crossing a group boundary relabels through
   the children's contractions and moves up.  The root sees the fully
   contracted global graph, exactly like the single-host hierarchical
   scheme — composed with the per-shard contraction rounds the way
   "Composing Distributed Computations Through Task and Kernel Fusion"
   (PAPERS.md) argues fused pipelines should: no materialized global
   problem between the stages.

Every step is deterministic: shards and groups are processed in index
order, member supernodes ascend, parallel-edge accumulation reuses the
documented tie-break order of :func:`..ops.contraction._canonical_edges`,
and label offsets are assigned in group order *after* all of a level's
solves finish — thread scheduling cannot reorder anything observable, so
the merged labeling is reproducible across reruns and across the
in-process vs worker-group drivers.

Two drivers share the exact same level steps:

- :func:`sharded_solve` — in-process, group solves fanned out on a thread
  pool (the contraction engine releases the GIL in its native/jax rungs);
- :func:`solve_over_workers` — the inter-host form: a
  :func:`~cluster_tools_tpu.parallel.multihost.launch_workers` worker
  group (each worker joins the ``jax.distributed`` runtime, the same
  wiring as a real pod), leaf shards and merge groups dealt round-robin
  over workers, boundary-edge packets exchanged through the run's scratch
  directory (atomic ``os.replace`` publishes — the DCN-analogue data
  plane this runtime inherits from the reference's shared-filesystem
  cluster heritage).  The merge bookkeeping (cheap, O(E)) is replicated
  on every worker from the same packets, so all workers advance through
  bit-identical level states.

:func:`solve_with_reduce_tree` is the attributed entry point tasks call
(``SolveGlobal``, ``SolveLiftedGlobal``, agglomerative clustering, the
stitching ``merge_mode='multicut'`` seam): ``solver_shards=1`` is the
degenerate single-host path, and ANY sharded failure — a killed worker, a
timed-out reduce hop, an injected ``solve`` fault — degrades to the
single-host solver with a ``degraded:unsharded_solve`` record in
``failures.json`` (riding the PR 2-4 retry/quarantine/drain stack), so the
sharded path can never produce a worse outcome than not having it.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..runtime import trace as trace_mod
from ..utils import function_utils as fu

#: env knobs of the worker-group driver (inherited by the workers)
_ENV_DIR = "CT_RT_DIR"
_ENV_WAIT = "CT_RT_WAIT_S"

#: default patience of a worker polling for a sibling's packet before it
#: declares the reduce hop lost and exits nonzero (the driver then degrades
#: to the unsharded solve)
DEFAULT_HOP_WAIT_S = 120.0


class ShardedSolveError(RuntimeError):
    """The sharded solve could not complete (worker death, lost packet,
    malformed shard state).  Callers degrade to the single-host solver."""


#: the degrade ladders below absorb resource, deadline and worker faults;
#: these are defects in the program (a moved API, a bad call) and must
#: surface — a ladder that swallows them keeps a dead plane "passing"
_PROGRAMMING_ERRORS = (ImportError, AttributeError, TypeError)


def _host_impl(impl: Optional[str] = None) -> str:
    """Concrete host-side contraction impl (``native``/``numpy``), never
    ``auto``: ``auto``'s accelerator probe initializes the XLA client,
    which must not happen inside reduce-tree workers (see
    :func:`reduce_worker_main`)."""
    if impl and impl not in ("auto", "host"):
        return impl
    from .. import native

    return "native" if native.available() else "numpy"


# -- process-wide solver metrics ---------------------------------------------
# Same snapshot/delta pattern as the executor's dispatch counters: the task
# runtime snapshots around run_impl and merges the delta into
# io_metrics.json, so the sharded solve's per-level work is observable per
# task (docs/PERFORMANCE.md "Distributed agglomeration").

_METRICS_LOCK = threading.Lock()
_SOLVE_COUNTERS = {
    "sharded_solves": 0,        # sharded_solve invocations (any driver)
    "unsharded_fallbacks": 0,   # degraded:unsharded_solve degradations
    "solve_shards": 0,          # leaf shards solved
    "solve_levels": 0,          # reduce-tree levels traversed
    "tree_rounds": 0,           # frontier-contraction rounds across nodes
    "tree_solve_s": 0.0,        # wall time inside per-group solver calls
    "tree_merge_s": 0.0,        # wall time relabeling/merging boundary edges
    "boundary_edges_in": 0,     # edges entering the reduce tree (leaf level)
    "boundary_edges_out": 0,    # edges surviving to the root solve
    # -- collective reduce plane (docs/PERFORMANCE.md) --
    "collective_hops": 0,          # per-level all_gather exchanges
    "packet_fallbacks": 0,         # degraded:packet_plane degradations
    "bytes_over_interconnect": 0,  # bytes the collective hops moved
    "contraction_dispatches": 0,   # host round dispatches + level programs
}


def solve_snapshot() -> Dict[str, float]:
    """Current process-wide reduce-tree counters (monotonic; diff two
    snapshots with :func:`solve_delta` to attribute a task's share)."""
    with _METRICS_LOCK:
        return dict(_SOLVE_COUNTERS)


def solve_delta(snapshot: Dict[str, float]) -> Dict[str, float]:
    """Counter movement since ``snapshot`` (same keys)."""
    cur = solve_snapshot()
    return {k: cur[k] - snapshot.get(k, 0) for k in cur}


def _record_solve_metrics(**deltas) -> None:
    with _METRICS_LOCK:
        for k, v in deltas.items():
            _SOLVE_COUNTERS[k] += v


# -- tree topology ------------------------------------------------------------


def reduce_tree_levels(n_shards: int, fanout: int) -> List[List[Tuple[int, ...]]]:
    """Merge-group plan: one entry per tree level above the leaves.

    ``levels[0]`` is the LEAF level — one singleton group per shard, the
    "run contraction locally per shard" stage (it is where the bulk of the
    edges contract, in parallel).  Each later level's groups are tuples of
    *previous-level node indices*, ``fanout`` consecutive children fusing
    per group — Morton-contiguous shards merge with their spatial
    neighbors first — until the last level's single root group.
    ``n_shards == 1`` yields just the root level, one (trivial) global
    solve.
    """
    n_shards = int(n_shards)
    fanout = int(fanout)
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if fanout < 2:
        raise ValueError(f"fanout must be >= 2, got {fanout}")
    levels: List[List[Tuple[int, ...]]] = [
        [(s,) for s in range(n_shards)]
    ]
    width = n_shards
    while width > 1:
        groups = [
            tuple(range(i, min(i + fanout, width)))
            for i in range(0, width, fanout)
        ]
        levels.append(groups)
        width = len(groups)
    return levels


# -- shard partitions ---------------------------------------------------------


def morton_argsort(positions: np.ndarray) -> np.ndarray:
    """Indices sorting integer grid ``positions`` [k, d] along the Z-order
    curve (bit interleave, axis 0 most significant within each bit plane —
    the same octant-contiguity the executor's Morton sweep uses)."""
    pos = np.asarray(positions, dtype=np.int64)
    if pos.ndim != 2:
        raise ValueError(f"positions must be [k, d], got shape {pos.shape}")
    if len(pos) == 0:
        return np.zeros(0, np.int64)
    nbits = max(1, int(pos.max()).bit_length())
    codes = np.zeros(len(pos), dtype=np.int64)
    d = pos.shape[1]
    for bit in range(nbits):
        for ax in range(d):
            codes |= ((pos[:, ax] >> bit) & 1) << (bit * d + (d - 1 - ax))
    return np.argsort(codes, kind="stable")


def morton_node_shards(positions: np.ndarray, n_shards: int) -> np.ndarray:
    """Shard id per row of ``positions``: Morton-sort the grid positions and
    split the curve into ``n_shards`` near-equal contiguous runs — each
    shard is an octant-shaped neighborhood of the grid."""
    order = morton_argsort(positions)
    shards = np.empty(len(order), np.int64)
    shards[order] = (
        np.arange(len(order), dtype=np.int64) * int(n_shards) // max(1, len(order))
    )
    return shards


def contiguous_node_shards(n_nodes: int, n_shards: int) -> np.ndarray:
    """Id-range partition: node ids assigned blockwise by supervoxel
    labeling order.  The fallback for callers without block geometry (the
    stitching face graph, synthetic bench instances) — blockwise label
    assignment makes consecutive ids spatial neighbors, so contiguous
    ranges approximate the Morton octants."""
    n_nodes = int(n_nodes)
    k = max(1, min(int(n_shards), max(1, n_nodes)))
    return np.arange(n_nodes, dtype=np.int64) * k // max(1, n_nodes)


# -- the level machinery (shared by both drivers) -----------------------------


def _as_payload(costs: np.ndarray, m: int) -> np.ndarray:
    payload = np.asarray(costs, dtype=np.float64)
    if payload.ndim == 1:
        payload = payload.reshape(-1, 1)
    if len(payload) != m:
        raise ValueError(f"payload rows {len(payload)} != edges {m}")
    return payload


def _aggregate_frontier(
    f_node: np.ndarray, f_ghost: np.ndarray, f_payload: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Merge parallel frontier edges per (member node, ghost) pair via the
    contraction engine's own :func:`..ops.contraction.sum_by_key` — one
    implementation of the load-bearing accumulation order."""
    if len(f_node) == 0:
        return f_node, f_ghost, f_payload
    from ..ops.contraction import sum_by_key

    mult = np.int64(int(f_ghost.max()) + 1)
    key = f_node.astype(np.int64) * mult + f_ghost.astype(np.int64)
    uniq, out = sum_by_key(key, f_payload)
    return (
        (uniq // mult).astype(np.int64),
        (uniq % mult).astype(np.int64),
        out,
    )


def frontier_contraction(
    n_nodes: int,
    edges: np.ndarray,
    payload: np.ndarray,
    f_node: np.ndarray,
    f_ghost: np.ndarray,
    f_payload: np.ndarray,
    mode: str = "max",
    threshold: float = 0.0,
) -> np.ndarray:
    """Mutual-best contraction rounds with frontier abstention.

    The same rounds as :func:`..ops.contraction._contract_rounds_numpy`
    (per-node best-pick -> mutual matching -> depth-1 union -> canonical
    re-aggregation; ties toward the smallest edge id), except that the
    still-external *frontier* edges — ``f_node`` (member endpoint, local
    id) to ``f_ghost`` (the remote supernode, an opaque key) with
    ``f_payload`` columns — compete in the best-pick scatter but can never
    match: a node whose best incident edge is external abstains this
    round, deferring its merge to the ancestor tree level where the edge
    becomes internal.  Frontier edges re-aggregate as internal contraction
    merges their member endpoints, so their priorities stay consistent
    with what the merge level will see.  Deterministic; returns int64
    labels 0..k-1 over the ``n_nodes`` members.
    """
    n = int(n_nodes)
    sign = 1.0 if mode == "max" else -1.0
    thr = sign * float(threshold)
    labels = np.arange(n, dtype=np.int64)
    if n == 0 or len(edges) == 0:
        return labels
    from ..ops.contraction import _canonical_edges

    u, v, payload = _canonical_edges(n, edges, payload)
    f_node = np.asarray(f_node, dtype=np.int64)
    f_ghost = np.asarray(f_ghost, dtype=np.int64)
    f_payload = _as_payload(f_payload, len(f_node))
    f_node, f_ghost, f_payload = _aggregate_frontier(f_node, f_ghost, f_payload)
    rounds = 0

    def prio_of(pay):
        if pay.shape[1] == 1:
            p = pay[:, 0]
        else:
            p = pay[:, 0] / np.maximum(pay[:, 1], 1e-300)
        return sign * p

    while len(u):
        prio = prio_of(payload)
        elig = prio > thr
        if not elig.any():
            break
        eid = np.arange(len(u), dtype=np.int64)
        best_p = np.full(n, -np.inf)
        np.maximum.at(best_p, u[elig], prio[elig])
        np.maximum.at(best_p, v[elig], prio[elig])
        if len(f_node):
            fprio = prio_of(f_payload)
            felig = fprio > thr
            if felig.any():
                # external competition: raises best_p but never places a
                # candidate edge id -> the node abstains if it wins
                np.maximum.at(best_p, f_node[felig], fprio[felig])
        best_e = np.full(n, len(u), dtype=np.int64)
        cand_u = elig & (prio == best_p[u])
        cand_v = elig & (prio == best_p[v])
        np.minimum.at(best_e, u[cand_u], eid[cand_u])
        np.minimum.at(best_e, v[cand_v], eid[cand_v])
        mutual = elig & (best_e[u] == eid) & (best_e[v] == eid)
        if not mutual.any():
            break
        rounds += 1
        root = np.arange(n, dtype=np.int64)
        root[v[mutual]] = u[mutual]
        labels = root[labels]
        u, v, payload = _canonical_edges(
            n, np.stack([root[u], root[v]], axis=1), payload
        )
        if len(f_node):
            f_node, f_ghost, f_payload = _aggregate_frontier(
                root[f_node], f_ghost, f_payload
            )
    # one host-driven dispatch per mutual-best round — the figure the
    # collective plane's one-dispatch-per-level program is measured against
    _record_solve_metrics(tree_rounds=rounds, contraction_dispatches=rounds)
    _, out = np.unique(labels, return_inverse=True)
    return out.astype(np.int64)


def default_tree_solver(
    mode: str = "max", threshold: float = 0.0, impl: str = "auto"
) -> Callable:
    """The default per-tree-node solver: frontier-aware contraction rounds
    (GAEC for ``mode='max'``, average linkage for ``'min'``).  Lifted edges
    at a node route to the lifted GAEC (boundary-blind: the lifted
    objective has no frontier formulation yet); a node with no frontier
    and no lifted edges runs the plain contraction engine (jax/native/
    numpy ladder — device rounds where an accelerator mesh is available).
    """

    def solve(n, edges, payload, frontier, lifted_edges, lifted_payload):
        if lifted_edges is not None and len(lifted_edges):
            from ..ops.multicut import lifted_greedy_additive

            return lifted_greedy_additive(
                n, edges, payload[:, 0], lifted_edges, lifted_payload[:, 0]
            )
        if len(edges) == 0:
            return np.arange(n, dtype=np.int64)
        if frontier is not None and len(frontier[0]):
            return frontier_contraction(
                n, edges, payload, *frontier, mode=mode, threshold=threshold
            )
        from ..ops.contraction import parallel_contraction

        return parallel_contraction(n, edges, payload, mode, threshold, impl=impl)

    return solve


class _TreeState:
    """Mutable per-level solve state: the current contracted problem."""

    __slots__ = (
        "n", "edges", "payload", "ledges", "lpayload", "owner", "node_to_cur",
    )

    def __init__(self, n_nodes, edges, payload, ledges, lpayload, node_shard):
        self.n = int(n_nodes)
        self.edges = edges
        self.payload = payload
        self.ledges = ledges
        self.lpayload = lpayload
        self.owner = np.asarray(node_shard, dtype=np.int64).copy()
        self.node_to_cur = np.arange(self.n, dtype=np.int64)


def _aggregate(n_new: int, edges: np.ndarray, payload: np.ndarray):
    """Canonical (lo<hi) unique edges with payload summed over parallels —
    the deterministic accumulation order of the contraction engine."""
    from ..ops.contraction import _canonical_edges

    if len(edges) == 0:
        return edges.reshape(0, 2), payload.reshape(0, payload.shape[-1])
    u, v, pay = _canonical_edges(n_new, edges, payload)
    return np.stack([u, v], axis=1), pay


def _group_problem(
    state: _TreeState,
    children: Tuple[int, ...],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, Optional[tuple],
           Optional[np.ndarray], Optional[np.ndarray], int]:
    """Extract one merge group's subproblem from the level state:
    ``(members, sub_edges, sub_payload, frontier, sub_le, sub_lp,
    n_internal)``.  ``members`` are the group's supernodes (ascending —
    the deterministic local index); ``frontier`` is the ``(f_node,
    f_ghost, f_payload)`` still-external edge context or None.  Shared by
    the host solver path (:func:`_solve_group`) and the collective
    plane's lane marshalling — both rungs see byte-identical problems."""
    members = np.flatnonzero(np.isin(state.owner, children))
    if len(members) == 0:
        return (members, np.zeros((0, 2), np.int64),
                np.zeros((0, state.payload.shape[-1])), None, None, None, 0)

    def side_masks(edges):
        in_u = np.isin(state.owner[edges[:, 0]], children)
        in_v = np.isin(state.owner[edges[:, 1]], children)
        return in_u, in_v

    in_u, in_v = (
        side_masks(state.edges) if len(state.edges) else
        (np.zeros(0, bool), np.zeros(0, bool))
    )
    e_mask = in_u & in_v
    sub_edges = np.searchsorted(members, state.edges[e_mask])
    sub_payload = state.payload[e_mask]
    cross = in_u ^ in_v
    frontier = None
    if cross.any():
        ce = state.edges[cross]
        member_side = in_u[cross]
        f_node = np.searchsorted(
            members, np.where(member_side, ce[:, 0], ce[:, 1])
        )
        f_ghost = np.where(member_side, ce[:, 1], ce[:, 0])
        frontier = (f_node, f_ghost, state.payload[cross])
    sub_le, sub_lp = None, None
    if state.ledges is not None and len(state.ledges):
        lin_u, lin_v = side_masks(state.ledges)
        l_mask = lin_u & lin_v
        sub_le = np.searchsorted(members, state.ledges[l_mask])
        sub_lp = state.lpayload[l_mask]
    return (members, sub_edges, sub_payload, frontier, sub_le, sub_lp,
            int(e_mask.sum()))


def _solve_group(
    state: _TreeState,
    children: Tuple[int, ...],
    solver: Callable,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Solve one merge group: ``(members, sub_labels, n_internal_edges)``.

    The group's *frontier* — edges with exactly one endpoint inside the
    span, keyed by the remote supernode id — is handed to the solver so it
    can defer boundary-best nodes (:func:`frontier_contraction`)."""
    members, sub_edges, sub_payload, frontier, sub_le, sub_lp, n_int = (
        _group_problem(state, children)
    )
    if len(members) == 0:
        return members, np.zeros(0, np.int64), 0
    labels = np.asarray(
        solver(len(members), sub_edges, sub_payload, frontier, sub_le, sub_lp),
        dtype=np.int64,
    )
    if len(labels) != len(members):
        raise ShardedSolveError(
            f"group solver returned {len(labels)} labels for "
            f"{len(members)} supernodes"
        )
    return members, labels, n_int


def _apply_level(
    state: _TreeState,
    groups: List[Tuple[int, ...]],
    results: Dict[int, Tuple[np.ndarray, np.ndarray]],
) -> int:
    """Fold one level's group solutions into the state (deterministic:
    offsets assigned in group order, edges re-aggregated canonically).
    Returns the number of supernodes after the level."""
    new_map = np.full(len(state.owner), -1, np.int64)
    owner_new: List[int] = []
    offset = 0
    for gi in range(len(groups)):
        members, labels = results[gi]
        k = int(labels.max()) + 1 if len(labels) else 0
        new_map[members] = offset + labels
        owner_new.extend([gi] * k)
        offset += k
    if (new_map < 0).any():
        raise ShardedSolveError("level left supernodes unmapped")
    state.node_to_cur = new_map[state.node_to_cur]
    state.edges, state.payload = _aggregate(
        offset, new_map[state.edges], state.payload
    )
    if state.ledges is not None and len(state.ledges):
        state.ledges, state.lpayload = _aggregate(
            offset, new_map[state.ledges], state.lpayload
        )
    state.owner = np.asarray(owner_new, dtype=np.int64)
    return offset


def _final_labels(state: _TreeState) -> np.ndarray:
    """Compose the per-level relabelings down to original nodes (dense)."""
    _, labels = np.unique(state.node_to_cur, return_inverse=True)
    return labels.astype(np.int64)


# -- collective reduce plane --------------------------------------------------
# Boundary-edge packets as device collectives (ROADMAP item 2d, the thesis
# of "Near-Optimal Wafer-Scale Reduce" and "Large Scale Distributed Linear
# Algebra With TPUs", PAPERS.md): a tree level's merge groups are dealt as
# padded lanes over the 1-D sibling mesh, each device contracts its lanes
# with the fused on-device round program (ops/contraction.py
# lane_frontier_rounds — convergence predicate inside lax.while_loop, so a
# level costs ONE dispatch instead of one per mutual-best round), and one
# in-program all_gather over the sibling axis replaces the npz packet
# exchange.  Ragged group problems marshal to fixed lanes through the
# PR-14/16 page-table + valid-extent descriptors and stage through the
# resident device pool, so a warm re-solve of the same problem pays zero
# h2d.  Bit-identical to the host rungs by construction (the kernel's
# documented contract); any failure degrades to the filesystem packet
# plane, attributed ``degraded:packet_plane``.

#: plane selection: operator env overrides the task knob
#: (``auto`` | ``collective`` | ``packet``)
_ENV_PLANE = "CT_REDUCE_PLANE"
#: force-disable switch — plane init refuses, exercising the attributed
#: init-failure rung (the bench's fallback arm, chaos drills)
_ENV_COLLECTIVES_OFF = "CT_COLLECTIVES_DISABLED"
#: wall-clock budget for one level's collective program (dispatch + the
#: all_gather hop); a level that exceeds it degrades to the packet plane
_ENV_HOP_DEADLINE = "CT_HOP_DEADLINE_S"
DEFAULT_HOP_DEADLINE_S = 60.0
#: ``reduce_plane='auto'`` floor: below this many live edges the jit
#: compile + d2h overhead outweighs the dispatch savings, stay on host
_ENV_AUTO_MIN_EDGES = "CT_REDUCE_PLANE_MIN_EDGES"
_AUTO_MIN_EDGES = 20_000

#: lane-capacity floors — capacities quantize to powers of two above
#: these so the compiled-program population stays bounded (the same
#: policy as the ragged pool's ``_quantize_pages``)
_MIN_LANE_NODES = 64
_MIN_LANE_EDGES = 128


def _pow2_at_least(n: int, floor: int) -> int:
    cap = int(floor)
    while cap < int(n):
        cap *= 2
    return cap


def _hop_deadline_s(explicit: Optional[float] = None) -> float:
    if explicit is not None:
        return float(explicit)
    return float(os.environ.get(_ENV_HOP_DEADLINE, DEFAULT_HOP_DEADLINE_S))


def _record_packet_degrade(
    failures_path: Optional[str], task_name: str, err: BaseException,
    record: bool = True,
) -> None:
    """Attribute one collective→packet degradation: the
    ``packet_fallbacks`` counter (→ io_metrics via the task's solve
    delta), a trace instant, and — when ``record`` — a resolved
    failures.json record at the ``hop`` site.  ctlint CT015 enforces that
    every ``degraded:packet_plane`` site routes through a
    ``record_failures`` writer; this helper is that one site.  ``record``
    is False only for ``reduce_plane='auto'`` picking the supported rung
    up front (not a runtime failure, counter-only)."""
    _record_solve_metrics(packet_fallbacks=1)
    trace_mod.instant(
        "degraded:packet_plane", task=task_name,
        error=f"{type(err).__name__}: {err}"[:200],
    )
    if not record or not failures_path:
        return
    try:
        fu.record_failures(failures_path, task_name, [{
            "block_id": None,
            "sites": {"hop": 1},
            "error": fu.cap_traceback(f"{type(err).__name__}: {err}"),
            "quarantined": False,
            "resolved": True,
            "resolution": "degraded:packet_plane",
        }])
    except Exception:
        pass  # attribution is best effort; the solve must still land


class CollectiveReducePlane:
    """One tree level as one collective device program.

    Construction is the degrade ladder's first rung: it raises (→ packet
    plane) when collectives are force-disabled, the ``hop`` fault site
    fires, fewer than two devices are visible, or the payload shape has
    no device kernel.  ``solve_level`` marshals every group of a level
    into fixed-capacity lanes, stages them through the resident device
    pool, and runs the jitted ``shard_map`` program under a wall-clock
    hop deadline; its failures are the ladder's second rung.

    Everything numeric runs under the thread-local
    ``jax.enable_x64`` context — staging included: without
    it ``device_put``/``jnp.zeros`` silently downcast f64→f32 and the
    bit-identity contract breaks.
    """

    def __init__(
        self,
        mode: str,
        threshold: float,
        k: int,
        *,
        hop_deadline_s: Optional[float] = None,
        n_devices: Optional[int] = None,
    ):
        from ..runtime import faults as faults_mod

        if os.environ.get(_ENV_COLLECTIVES_OFF):
            raise ShardedSolveError(
                f"collectives force-disabled ({_ENV_COLLECTIVES_OFF})"
            )
        # init-failure injection rung: a `hop` error fault here models
        # jax.distributed refusing to wire the plane up
        faults_mod.get_injector().maybe_fail("hop")
        if mode not in ("max", "min"):
            raise ShardedSolveError(f"unsupported mode {mode!r}")
        if int(k) not in (1, 2):
            raise ShardedSolveError(
                f"no device kernel for payload width {k} (expected 1 or 2)"
            )
        from jax.sharding import NamedSharding, PartitionSpec

        from .mesh import sibling_mesh

        self.mode = mode
        self.threshold = float(threshold)
        self.k = int(k)
        self.hop_deadline_s = _hop_deadline_s(hop_deadline_s)
        self.mesh = sibling_mesh(n_devices)
        self.ndev = int(self.mesh.devices.size)
        if self.ndev < 2:
            raise ShardedSolveError(
                "collective plane needs >= 2 devices on the sibling mesh"
            )
        self._replicated = NamedSharding(self.mesh, PartitionSpec())
        from .mesh import SIBLING_AXIS

        self._lane_sharded = NamedSharding(
            self.mesh, PartitionSpec(SIBLING_AXIS)
        )
        self._dev_key = tuple(
            d.id for d in self.mesh.devices.reshape(-1)
        )
        self._programs: Dict[tuple, Callable] = {}

    # -- the per-level program (cached per node capacity) -------------------

    def _program(self, Wn: int) -> Callable:
        prog = self._programs.get((Wn,))
        if prog is not None:
            return prog
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from ..compat import shard_map
        from ..ops.contraction import lane_frontier_rounds
        from .mesh import SIBLING_AXIS

        mode, k = self.mode, self.k

        def per_device(up, vp, pp, fnp, fgp, fpp, tabs, thr):
            # tabs [local_lanes, 6]: this device's lanes' page slots, one
            # per pool — the ragged page-table indirection on device
            def one_lane(t):
                return lane_frontier_rounds(
                    up[t[0]], vp[t[1]], pp[t[2]],
                    fnp[t[3]], fgp[t[4]], fpp[t[5]],
                    thr, n_pad=Wn, mode=mode, k=k,
                )

            labels, rounds = jax.vmap(one_lane)(tabs)
            # THE reduce hop: every sibling's lane labels in one gather
            # over the interconnect — the packet exchange, minus the
            # filesystem
            labels = lax.all_gather(labels, SIBLING_AXIS, tiled=True)
            rounds = lax.all_gather(rounds, SIBLING_AXIS, tiled=True)
            return labels, rounds

        prog = jax.jit(shard_map(
            per_device, mesh=self.mesh,
            in_specs=(P(),) * 6 + (P(SIBLING_AXIS), P()),
            out_specs=(P(), P()),
            check_vma=False,
        ))
        self._programs[(Wn,)] = prog
        return prog

    # -- lane marshalling ---------------------------------------------------

    def _marshal(self, probs: List[tuple]):
        """Pack the level's group problems into one 6-spec ragged batch:
        fixed ``(We,)``/``(We,k)``/``(Wf,)``/``(Wf,k)`` pages, one page
        per lane, lane count padded to a multiple of the device count —
        page-table + valid-extent descriptors exactly like the executor's
        ragged sweeps, so the device pool's content-addressed staging
        dedupes warm re-solves to zero h2d."""
        from .block_pool import RaggedArgSpec, RaggedBatch, _quantize_pages

        Wn = _pow2_at_least(
            max(len(m) for _, m, _, _, _ in probs), _MIN_LANE_NODES
        )
        We = _pow2_at_least(
            max(max((len(e) for _, _, e, _, _ in probs), default=0), 1),
            _MIN_LANE_EDGES,
        )
        Wf = _pow2_at_least(
            max(max((len(f[0]) for _, _, _, _, f in probs
                     if f is not None), default=0), 1),
            _MIN_LANE_EDGES,
        )
        lanes = -(-len(probs) // self.ndev) * self.ndev
        k = self.k
        # (page_shape, dtype, fill) per pool: u, v, pay, f_node, f_ghost,
        # f_pay.  Wn is the kernel's padding sentinel for endpoints.
        layout = [
            ((We,), np.int64, Wn), ((We,), np.int64, Wn),
            ((We, k), np.float64, 0.0),
            ((Wf,), np.int64, Wn), ((Wf,), np.int64, 0),
            ((Wf, k), np.float64, 0.0),
        ]
        specs, pools, tables, valids = [], [], [], []
        for shape, dtype, fill in layout:
            cap = _quantize_pages(1 + len(probs))
            pool = np.full((cap,) + shape, fill, dtype)
            specs.append(RaggedArgSpec(
                (1,) * len(shape), shape, np.dtype(dtype).name,
                fill if isinstance(fill, float) else int(fill), cap,
            ))
            pools.append(pool)
            tables.append(np.zeros((lanes, 1), np.int32))
            valids.append(np.zeros((lanes, len(shape)), np.int32))
        for li, (gi, members, sub_edges, sub_payload, frontier) in enumerate(
            probs
        ):
            m = len(sub_edges)
            pools[0][1 + li, :m] = sub_edges[:, 0] if m else 0
            pools[1][1 + li, :m] = sub_edges[:, 1] if m else 0
            pools[2][1 + li, :m] = sub_payload
            rows = [(m,), (m,), (m, k)]
            if frontier is not None:
                f_node, f_ghost, f_pay = frontier
                fm = len(f_node)
                pools[3][1 + li, :fm] = f_node
                pools[4][1 + li, :fm] = f_ghost
                pools[5][1 + li, :fm] = np.asarray(f_pay, np.float64)
                rows += [(fm,), (fm,), (fm, k)]
            else:
                rows += [(0,), (0,), (0, k)]
            for a, extent in enumerate(rows):
                tables[a][li, 0] = 1 + li
                valids[a][li] = extent
        rb = RaggedBatch(
            specs, pools, tables, valids, n_lanes=len(probs), width=lanes,
            pages_in_use=6 * len(probs),
        )
        return rb, Wn

    # -- one level, one dispatch, one hop -----------------------------------

    def solve_level(
        self,
        state: _TreeState,
        groups: List[Tuple[int, ...]],
        *,
        level: int,
        deadline_s: Optional[float] = None,
    ) -> Tuple[Dict[int, Tuple[np.ndarray, np.ndarray]], int]:
        """Solve every group of one tree level collectively; returns the
        ``{group: (members, labels)}`` results for :func:`_apply_level`
        plus the level's internal-edge total.  ``deadline_s`` caps the
        whole dispatch+hop (default: the plane's hop deadline) — a level
        that cannot make the deadline raises :class:`ShardedSolveError`
        and the caller degrades to the packet plane."""
        deadline = self.hop_deadline_s if deadline_s is None else float(
            deadline_s
        )
        results: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        probs: List[tuple] = []
        internal_total = 0
        for gi, children in enumerate(groups):
            (members, sub_edges, sub_payload, frontier, sub_le, sub_lp,
             n_int) = _group_problem(state, children)
            if sub_le is not None and len(sub_le):
                from ..ops.multicut import lifted_frontier_capable

                if not lifted_frontier_capable():
                    raise ShardedSolveError(
                        "lifted edges have no frontier formulation — "
                        "collective plane refuses the group"
                    )
            internal_total += n_int
            if len(members) == 0:
                results[gi] = (members, np.zeros(0, np.int64))
                continue
            probs.append((gi, members, sub_edges, sub_payload, frontier))
        if not probs:
            return results, internal_total
        raw = self._dispatch(probs, level, deadline)
        for li, (gi, members, _, _, _) in enumerate(probs):
            lane = raw[li, : len(members)]
            # the kernel returns raw union roots; the consecutive relabel
            # is the same np.unique the host rung applies
            _, labels = np.unique(lane, return_inverse=True)
            results[gi] = (members, labels.astype(np.int64))
        return results, internal_total

    def _dispatch(
        self, probs: List[tuple], level: int, deadline: float
    ) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        from ..runtime import faults as faults_mod
        from .device_pool import get_device_pool

        rb, Wn = self._marshal(probs)
        injector = faults_mod.get_injector()
        box: Dict[str, object] = {}

        def run():
            try:
                # thread-local x64: staging AND the call must both see it,
                # and this worker thread is where both happen
                with jax.enable_x64():
                    # hop chaos: a hang here is a wedged interconnect the
                    # deadline must notice; an error a failed collective
                    injector.maybe_hang("hop", block_id=level)
                    injector.maybe_fail("hop", block_id=level)
                    sb = get_device_pool().stage(
                        rb, self._dev_key, self._replicated, block_id=level
                    )
                    tabs = jax.device_put(
                        np.concatenate(sb.tables, axis=1).astype(np.int32),
                        self._lane_sharded,
                    )
                    thr = jnp.float64(self.threshold)
                    prog = self._program(Wn)
                    labels, rounds = prog(*sb.pools, tabs, thr)
                    box["labels"] = np.asarray(jax.device_get(labels))
                    box["rounds"] = np.asarray(jax.device_get(rounds))
                    box["staged"] = sb.staged_bytes
            # marshalled across the thread boundary: the caller re-raises
            # non-Exception BaseExceptions (DrainInterrupt) verbatim below
            except BaseException as e:  # ctlint: disable=CT006
                box["error"] = e

        t = threading.Thread(
            target=run, name=f"collective-hop-l{level}", daemon=True
        )
        with trace_mod.span(
            "solve.collective_level", level=level, groups=len(probs),
            devices=self.ndev,
        ):
            t.start()
            t.join(timeout=deadline)
        if t.is_alive():
            raise ShardedSolveError(
                f"collective hop deadline: level {level} program exceeded "
                f"{deadline:g}s"
            )
        if "error" in box:
            err = box["error"]
            if not isinstance(err, Exception) or isinstance(
                err, _PROGRAMMING_ERRORS
            ):
                raise err  # DrainInterrupt etc. and program defects pass through
            raise ShardedSolveError(
                f"collective level {level} failed: "
                f"{type(err).__name__}: {err}"
            ) from err
        out = box["labels"]
        rounds = box["rounds"]
        # interconnect accounting: the all_gather hands every device all
        # other devices' shard — (ndev-1)/ndev of the gathered bytes moved
        # over the fabric
        moved = int(
            (out.nbytes + np.asarray(rounds).nbytes)
            * (self.ndev - 1) // self.ndev
        )
        _record_solve_metrics(
            collective_hops=1,
            contraction_dispatches=1,
            bytes_over_interconnect=moved,
            tree_rounds=int(np.asarray(rounds).sum()),
        )
        return out


# -- in-process driver --------------------------------------------------------


def sharded_solve(
    n_nodes: int,
    edges: np.ndarray,
    payload: np.ndarray,
    node_shard: np.ndarray,
    *,
    fanout: int = 2,
    solver: Optional[Callable] = None,
    mode: str = "max",
    threshold: float = 0.0,
    lifted_edges: Optional[np.ndarray] = None,
    lifted_payload: Optional[np.ndarray] = None,
    max_workers: int = 1,
    reduce_plane: str = "auto",
    hop_deadline_s: Optional[float] = None,
    failures_path: Optional[str] = None,
    task_name: str = "sharded_solve",
) -> Tuple[np.ndarray, Dict]:
    """Shard-contract-merge in one process.  Returns ``(labels, info)``:
    int64 labels 0..k-1 over the original nodes and the per-level stats
    dict the calling task surfaces in its success manifest.

    ``solver(n, edges, payload, frontier, lifted_edges, lifted_payload)
    -> labels`` runs once per tree node (default:
    :func:`default_tree_solver`; ``frontier`` is the ``(f_node, f_ghost,
    f_payload)`` still-external edge context, or None).  Group solves
    within a level are independent and fan out on a thread pool
    (``max_workers``); the result is invariant to their completion order.

    ``reduce_plane`` picks the level engine (``CT_REDUCE_PLANE``
    overrides): ``collective`` demands the
    :class:`CollectiveReducePlane` (one device program + one all_gather
    hop per level) and attributes ``degraded:packet_plane`` if it cannot
    run; ``auto`` uses it when it is eligible (≥ 2 devices, ≥
    ``CT_REDUCE_PLANE_MIN_EDGES`` live edges, default solver, no lifted
    edges) and otherwise stays on the host path silently; ``packet``
    never touches devices.  Either way the labels are bit-identical —
    the plane choice is pure performance.  ``hop_deadline_s`` caps each
    level's collective dispatch (``CT_HOP_DEADLINE_S``, default
    :data:`DEFAULT_HOP_DEADLINE_S`).
    """
    n_nodes = int(n_nodes)
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    payload = _as_payload(payload, len(edges))
    node_shard = np.asarray(node_shard, dtype=np.int64)
    if len(node_shard) != n_nodes:
        raise ValueError(
            f"node_shard has {len(node_shard)} entries for {n_nodes} nodes"
        )
    custom_solver = solver is not None
    if solver is None:
        solver = default_tree_solver(mode, threshold)
    ledges = (
        np.asarray(lifted_edges, dtype=np.int64).reshape(-1, 2)
        if lifted_edges is not None
        else None
    )
    lpayload = (
        _as_payload(lifted_payload, len(ledges)) if ledges is not None else None
    )

    n_shards = int(node_shard.max()) + 1 if n_nodes else 1
    levels = reduce_tree_levels(n_shards, fanout)
    state = _TreeState(n_nodes, edges, payload, ledges, lpayload, node_shard)

    plane_req = os.environ.get(_ENV_PLANE) or (reduce_plane or "auto")
    if plane_req not in ("auto", "collective", "packet"):
        raise ValueError(
            f"reduce_plane must be auto|collective|packet, got {plane_req!r}"
        )
    hop_deadline = _hop_deadline_s(hop_deadline_s)
    plane: Optional[CollectiveReducePlane] = None
    if plane_req != "packet":
        has_lifted = ledges is not None and len(ledges) > 0
        auto_floor = int(
            os.environ.get(_ENV_AUTO_MIN_EDGES, _AUTO_MIN_EDGES)
        )
        if plane_req == "collective" or (
            not custom_solver and not has_lifted and len(edges) >= auto_floor
        ):
            try:
                if custom_solver or has_lifted:
                    raise ShardedSolveError(
                        "collective plane needs the default solver and "
                        "no lifted edges"
                    )
                plane = CollectiveReducePlane(
                    mode, threshold, payload.shape[1],
                    hop_deadline_s=hop_deadline,
                )
            except _PROGRAMMING_ERRORS:
                raise
            except Exception as e:
                # init-failure rung: attributed when the plane was
                # demanded, counter-only when auto was probing
                _record_packet_degrade(
                    failures_path, task_name, e,
                    record=(plane_req == "collective"),
                )

    info: Dict = {
        "sharded": True,
        "shards": n_shards,
        "fanout": int(fanout),
        "reduce_plane": "collective" if plane is not None else "host",
        "levels": [],
    }
    _record_solve_metrics(
        sharded_solves=1,
        solve_shards=n_shards,
        boundary_edges_in=len(edges),
    )

    from concurrent.futures import ThreadPoolExecutor

    # the merge queue: group results land here as solves finish; guarded by
    # the merge lock because pool threads publish concurrently.  Offsets
    # are assigned later, in group order, so completion order is invisible.
    merge_lock = threading.Lock()

    for li, groups in enumerate(levels):
        edges_in = len(state.edges)
        results: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        internal_total = 0
        # the level spans double as the solve_s/merge_s clocks
        # (docs/OBSERVABILITY.md): one timing source, and a traced run
        # shows every reduce-tree level as its own timeline extent
        solve_span = trace_mod.begin(
            "solve.level_solve", level=li, groups=len(groups),
            edges_in=int(edges_in),
        )
        level_plane = "host"
        if plane is not None:
            try:
                results, internal_total = plane.solve_level(
                    state, groups, level=li, deadline_s=hop_deadline
                )
                level_plane = "collective"
            except _PROGRAMMING_ERRORS:
                raise
            except Exception as e:
                # runtime rung of the degrade ladder (hop deadline, a
                # failed collective, pool exhaustion): this and every
                # remaining level re-solve on the host path — the plane
                # was live, so the degradation is always attributed
                _record_packet_degrade(failures_path, task_name, e)
                info["degraded_plane"] = f"{type(e).__name__}: {e}"[:200]
                info["reduce_plane"] = "host"
                plane = None
                results = {}
                internal_total = 0
        if level_plane == "host":

            def run_group(gi, _groups=groups, _li=li):
                with trace_mod.span("solve.group", level=_li, group=gi):
                    members, labels, n_int = _solve_group(
                        state, _groups[gi], solver
                    )
                with merge_lock:
                    results[gi] = (members, labels)
                return n_int

            if max_workers > 1 and len(groups) > 1:
                with ThreadPoolExecutor(max_workers=int(max_workers)) as pool:
                    internal_total = sum(
                        pool.map(run_group, range(len(groups)))
                    )
            else:
                internal_total = sum(
                    run_group(gi) for gi in range(len(groups))
                )
        t_solve = solve_span.end()

        merge_span = trace_mod.begin("solve.level_merge", level=li)
        _apply_level(state, groups, results)
        t_merge = merge_span.end()
        info["levels"].append({
            "level": li,
            "groups": len(groups),
            "plane": level_plane,
            "edges_in": int(edges_in),
            "internal_edges": int(internal_total),
            "edges_out": int(len(state.edges)),
            "solve_s": round(t_solve, 6),
            "merge_s": round(t_merge, 6),
        })
        _record_solve_metrics(
            solve_levels=1, tree_solve_s=t_solve, tree_merge_s=t_merge
        )

    _record_solve_metrics(boundary_edges_out=len(state.edges))
    info["boundary_edges_root"] = int(len(state.edges))
    return _final_labels(state), info


# -- worker-group driver (inter-host reduce hops) -----------------------------


def _packet_path(scratch: str, level: int, group: int) -> str:
    return os.path.join(scratch, f"packet_l{level}_g{group}.npz")


def _publish_npz(path: str, **arrays) -> None:
    """Atomic packet publish: a reader either sees the whole packet or no
    packet — half-written reduce hops cannot exist."""
    tmp = f"{path}.{os.getpid()}.tmp"
    np.savez(tmp, **arrays)
    if not tmp.endswith(".npz") and os.path.exists(tmp + ".npz"):
        tmp = tmp + ".npz"
    os.replace(tmp, path)


def _worker_pid_path(scratch: str, worker: int) -> str:
    return os.path.join(scratch, f"worker_{int(worker)}.json")


def _read_worker_os_pid(pid_path: str) -> Optional[int]:
    """The OS pid a reduce worker advertised at boot, or None while the
    file has not landed yet (the worker may still be initializing)."""
    try:
        with open(pid_path) as f:
            return int(json.load(f)["os_pid"])
    except (OSError, ValueError, KeyError, TypeError):
        return None


def _wait_npz(
    path: str,
    wait_s: float,
    *,
    deadline: Optional[float] = None,
    owner_pid_path: Optional[str] = None,
) -> Dict[str, np.ndarray]:
    """Poll for a sibling's packet with ``wait_s`` of patience per hop —
    re-armed for every packet, so a worker whose own (possibly long) solve
    consumed wall time still grants its siblings the full window.

    Two fast-fail guards bound the worst case (a worker dying *between*
    publishing level L and reading level L+1 used to burn the full
    patience window per remaining hop — levels × patience):

    - ``deadline`` (absolute ``time.monotonic()``) caps the TOTAL wait of
      the enclosing level: however many packets are still missing, the
      level fails in one window.
    - ``owner_pid_path`` points at the publishing worker's boot-time pid
      record; a ~4/s same-host liveness probe (``os.kill(pid, 0)``, the
      PR-19 file_lock fast-break idiom) surfaces a dead publisher in a
      quarter second, naming the pid instead of "worker death?".
    """
    hop_deadline = time.monotonic() + wait_s
    if deadline is not None:
        hop_deadline = min(hop_deadline, float(deadline))
    next_probe = time.monotonic() + 0.25
    while True:
        if os.path.exists(path):
            try:
                with np.load(path, allow_pickle=False) as f:
                    return {k: f[k] for k in f.files}
            except (OSError, ValueError) as e:
                # packets publish via os.replace, so a torn file here is
                # real corruption, not a mid-write read
                raise ShardedSolveError(f"unreadable packet {path}: {e}")
        now = time.monotonic()
        if now > hop_deadline:
            raise ShardedSolveError(
                f"reduce hop lost: packet {os.path.basename(path)} did not "
                f"arrive within {wait_s:g}s"
                + (" (level deadline)" if deadline is not None
                   and hop_deadline == float(deadline) else "")
                + " (worker death?)"
            )
        if owner_pid_path is not None and now >= next_probe:
            next_probe = now + 0.25
            owner_pid = _read_worker_os_pid(owner_pid_path)
            if owner_pid is not None:
                try:
                    os.kill(owner_pid, 0)
                except ProcessLookupError:
                    raise ShardedSolveError(
                        f"reduce hop lost: worker owning "
                        f"{os.path.basename(path)} (os pid {owner_pid}) "
                        f"is dead"
                    )
                except (PermissionError, OSError):
                    pass  # alive but unprobeable — keep the deadlines
        time.sleep(0.02)


def _group_owner(level: int, group: int, n_workers: int) -> int:
    """Deterministic round-robin deal of tree nodes over the worker group."""
    return int(group) % max(1, int(n_workers))


def reduce_worker_main() -> None:
    """SPMD body of one reduce-tree worker (entered through
    :func:`~cluster_tools_tpu.parallel.multihost.worker_main`, i.e. after
    ``jax.distributed.initialize`` joined this process into the worker
    group).  Solves the leaf shards and merge groups this worker owns,
    publishes their packets, and replays every level from all packets so
    its state stays bit-identical to its siblings'.  Worker 0 publishes the
    final labels.

    A worker that FAILS (lost hop, bad packet) flushes its traceback and
    then SIGKILLs itself: a normal exit would run ``jax.distributed``'s
    shutdown barrier, which blocks until the runtime's ~100 s heartbeat
    timeout aborts the process when a sibling is already dead — turning
    an 8-second degrade into a two-minute stall.  ``DrainInterrupt`` is a
    BaseException and still propagates normally."""
    import sys
    import traceback

    try:
        _reduce_worker_body()
    except Exception:
        import signal as signal_mod

        traceback.print_exc()
        sys.stderr.flush()
        sys.stdout.flush()
        try:
            # the shard of a FAILING worker is the one the post-mortem
            # needs most (it shows the hop wait that never returned) —
            # flush before the self-SIGKILL
            trace_mod.flush()
        except Exception:
            pass
        os.kill(os.getpid(), signal_mod.SIGKILL)


def _reduce_worker_body() -> None:
    from ..runtime import faults as faults_mod
    from . import multihost

    scratch = os.environ[_ENV_DIR]
    pid = int(os.environ[multihost._ENV_PID])
    n_workers = int(os.environ[multihost._ENV_NPROC])
    hop_wait_s = float(os.environ.get(_ENV_WAIT, DEFAULT_HOP_WAIT_S))
    # boot-time pid record: siblings probe it to fast-fail on this
    # worker's death instead of burning their hop patience (_wait_npz)
    fu.atomic_write_json(
        _worker_pid_path(scratch, pid), {"os_pid": os.getpid()}
    )
    # solver-worker lifetime span (docs/OBSERVABILITY.md): tracing is on
    # only when the driver exported CTT_TRACE=<dir>, pointing this process
    # at the submitter's shard directory
    worker_span = trace_mod.begin(
        "solve.worker", worker=pid, workers=n_workers
    )

    with open(os.path.join(scratch, "meta.json")) as f:
        meta = json.load(f)
    with np.load(os.path.join(scratch, "problem.npz"), allow_pickle=False) as f:
        edges = f["edges"].astype(np.int64)
        payload = f["payload"].astype(np.float64)
        node_shard = f["node_shard"].astype(np.int64)
        ledges = f["lifted_edges"].astype(np.int64) if "lifted_edges" in f.files else None
        lpayload = f["lifted_payload"].astype(np.float64) if "lifted_payload" in f.files else None

    # chaos crossing: a `solve` fault targeted at this worker id models a
    # host lost mid-reduce — die like hardware (SIGKILL, no cleanup, no
    # packet), so siblings see a lost hop and the driver degrades
    try:
        faults_mod.get_injector().maybe_fail("solve", block_id=pid)
    except Exception:
        import signal as signal_mod

        os.kill(os.getpid(), signal_mod.SIGKILL)

    n_nodes = int(meta["n_nodes"])
    # resolve the contraction impl WITHOUT the jax backend probe: touching
    # the XLA client from inside a multi-process distributed runtime hangs
    # on jaxlib CPU backends without multiprocess collectives (the same
    # limitation the test_multihost env-skip covers) — and the tree-node
    # solves are host work here anyway (native C++ rung, numpy fallback)
    solver = default_tree_solver(
        meta["mode"], float(meta["threshold"]), impl=_host_impl(meta.get("impl"))
    )
    levels = reduce_tree_levels(int(meta["n_shards"]), int(meta["fanout"]))
    state = _TreeState(n_nodes, edges, payload, ledges, lpayload, node_shard)

    # plane choice is made ONCE, deterministically, before the levels:
    # every worker runs the same probe on the same backend, so the group
    # either all takes the collective path (SPMD level programs over the
    # pod mesh, no packets) or all exchanges filesystem packets.  A
    # worker cannot switch rungs mid-solve — its siblings would wait on
    # packets that are never coming.
    plane: Optional[CollectiveReducePlane] = None
    plane_reason = "packet plane requested"
    plane_req = str(meta.get("reduce_plane", "packet"))
    hop_deadline = _hop_deadline_s(meta.get("hop_deadline_s"))
    if plane_req in ("auto", "collective"):
        supported, reason = multihost.collectives_supported(
            deadline_s=hop_deadline
        )
        if not supported:
            # the known old-jaxlib CPU backends take initialize() but
            # abort the first multi-process collective — degrade here,
            # before any level committed to device hops
            plane_reason = f"collectives unsupported: {reason}"
        elif ledges is not None and len(ledges):
            plane_reason = "lifted edges have no frontier formulation"
        else:
            try:
                plane = CollectiveReducePlane(
                    meta["mode"], float(meta["threshold"]),
                    payload.shape[1] if payload.ndim > 1 else 1,
                    hop_deadline_s=hop_deadline,
                )
                plane_reason = "collective"
            except Exception as e:
                plane_reason = f"plane init failed: {e}"[:200]

    for li, groups in enumerate(levels):
        if plane is not None:
            # the collective rung: ONE SPMD program solves every group of
            # the level on the pod mesh and the in-program all_gather IS
            # the reduce hop — no packets, no polling.  Any failure here
            # is a worker failure (SIGKILL via reduce_worker_main); the
            # driver retries the whole solve on the packet plane.
            results, _ = plane.solve_level(
                state, groups, level=li, deadline_s=hop_deadline
            )
            _apply_level(state, groups, results)
            try:
                trace_mod.flush()
            except Exception:
                pass
            continue
        # solve + publish the groups dealt to this worker
        for gi in range(len(groups)):
            if _group_owner(li, gi, n_workers) != pid:
                continue
            with trace_mod.span(
                "solve.group", level=li, group=gi, worker=pid
            ):
                members, labels, n_int = _solve_group(
                    state, groups[gi], solver
                )
            _publish_npz(
                _packet_path(scratch, li, gi),
                members=members, labels=labels,
                n_internal=np.int64(n_int),
            )
        # collect every group's packet (the reduce hop) and fold the
        # level.  The level deadline is armed AFTER this worker's own
        # solves: however many siblings' packets are still missing, a
        # dead group fails within ONE patience window, not one per hop.
        level_deadline = time.monotonic() + hop_wait_s
        results = {}
        for gi in range(len(groups)):
            # the hop wait is the inter-host latency PAPERS.md's wafer-
            # scale-reduce analysis says must be measured per hop — one
            # span per awaited packet, worker- and level-attributed
            with trace_mod.span(
                "solve.hop_wait", level=li, group=gi, worker=pid
            ):
                pkt = _wait_npz(
                    _packet_path(scratch, li, gi), hop_wait_s,
                    deadline=level_deadline,
                    owner_pid_path=_worker_pid_path(
                        scratch, _group_owner(li, gi, n_workers)
                    ),
                )
            results[gi] = (
                pkt["members"].astype(np.int64),
                pkt["labels"].astype(np.int64),
            )
        _apply_level(state, groups, results)
        # crash-safe: each level's flush rewrites the full shard, so a
        # worker killed at level N leaves its spans through level N-1 —
        # but a tracing write failure must never fail a healthy worker
        try:
            trace_mod.flush()
        except Exception:
            pass

    if pid == 0:
        _publish_npz(
            os.path.join(scratch, "result.npz"),
            labels=_final_labels(state),
            # root residual for the driver's observability counters (its
            # own snapshot cannot see this process's state)
            boundary_edges_root=np.int64(len(state.edges)),
            # which rung actually ran, for the driver's attribution
            plane_used=np.str_(
                "collective" if plane is not None else "packet"
            ),
            plane_reason=np.str_(plane_reason),
        )
    worker_span.end()
    try:
        trace_mod.flush()
    except Exception:
        pass
    print(f"REDUCE_TREE_OK pid={pid} workers={n_workers}", flush=True)


def solve_over_workers(
    n_nodes: int,
    edges: np.ndarray,
    payload: np.ndarray,
    node_shard: np.ndarray,
    *,
    fanout: int = 2,
    mode: str = "max",
    threshold: float = 0.0,
    lifted_edges: Optional[np.ndarray] = None,
    lifted_payload: Optional[np.ndarray] = None,
    n_workers: int = 2,
    scratch_dir: str,
    timeout: Optional[float] = None,
    hop_wait_s: Optional[float] = None,
    impl: Optional[str] = None,
    reduce_plane: str = "packet",
    hop_deadline_s: Optional[float] = None,
) -> Tuple[np.ndarray, Dict]:
    """Run the reduce tree over a :func:`multihost.launch_workers` group.

    The problem is staged once into ``scratch_dir``; each worker joins the
    ``jax.distributed`` runtime, solves the shards/groups it owns, and the
    boundary-edge packets between levels are the inter-host hops.  Raises
    :class:`ShardedSolveError` on any worker failure or lost packet — the
    caller's cue to degrade to the single-host solve.

    ``reduce_plane`` ∈ ``packet|auto|collective``: with ``auto`` or
    ``collective`` the workers probe
    :func:`multihost.collectives_supported` once at boot and — where the
    backend can run multi-process collectives — replace the packet
    exchange with SPMD level programs over the pod mesh
    (:class:`CollectiveReducePlane`); otherwise all workers
    deterministically stay on packets.  ``info["reduce_plane"]`` reports
    the rung that actually ran, ``info["plane_reason"]`` why.
    """
    from .multihost import launch_workers

    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    payload = _as_payload(payload, len(edges))
    node_shard = np.asarray(node_shard, dtype=np.int64)
    n_shards = int(node_shard.max()) + 1 if int(n_nodes) else 1
    os.makedirs(scratch_dir, exist_ok=True)
    for stale in os.listdir(scratch_dir):
        if stale.startswith(("packet_", "result", "worker_")):
            try:
                os.unlink(os.path.join(scratch_dir, stale))
            except OSError:
                pass
    arrays = {"edges": edges, "payload": payload, "node_shard": node_shard}
    if lifted_edges is not None and len(lifted_edges):
        arrays["lifted_edges"] = np.asarray(lifted_edges, np.int64)
        arrays["lifted_payload"] = _as_payload(
            lifted_payload, len(arrays["lifted_edges"])
        )
    _publish_npz(os.path.join(scratch_dir, "problem.npz"), **arrays)
    fu.atomic_write_json(
        os.path.join(scratch_dir, "meta.json"),
        {
            "n_nodes": int(n_nodes),
            "n_shards": n_shards,
            "fanout": int(fanout),
            "mode": mode,
            "threshold": float(threshold),
            "impl": impl or "host",
            "reduce_plane": str(reduce_plane),
            "hop_deadline_s": _hop_deadline_s(hop_deadline_s),
        },
    )

    if timeout is None:
        # driver patience for the whole worker group; must outlast the
        # workers' own per-hop wait so a lost packet surfaces as a worker
        # rc, not a group kill
        timeout = float(os.environ.get("CT_RT_TIMEOUT_S", "600"))
    group_span = trace_mod.begin(
        "solve.worker_group", workers=int(n_workers), shards=n_shards
    )
    extra_env = {
        _ENV_DIR: scratch_dir,
        # explicit arg > operator env > default — launch_workers
        # applies extra_env over os.environ, so the env knob must
        # be threaded through here to reach the workers at all
        _ENV_WAIT: str(
            hop_wait_s if hop_wait_s is not None
            else os.environ.get(_ENV_WAIT, DEFAULT_HOP_WAIT_S)
        ),
    }
    if trace_mod.enabled() and trace_mod.trace_dir():
        # a traced driver hands the workers its shard directory — the env
        # value both enables tracing and pins the directory, so a run
        # enabled programmatically (configure()) still traces its workers
        extra_env[trace_mod.ENV_VAR] = trace_mod.trace_dir()
    try:
        results = launch_workers(
            int(n_workers),
            "cluster_tools_tpu.parallel.reduce_tree:reduce_worker_main",
            timeout=timeout,
            extra_env=extra_env,
        )
    except TimeoutError as e:
        group_span.end(error=True)
        raise ShardedSolveError(f"worker group timed out: {e}") from e
    failed = [
        (pid, rc, (err or "")[-500:])
        for pid, (rc, _, err) in enumerate(results)
        if rc != 0
    ]
    if failed:
        group_span.end(error=True)
        raise ShardedSolveError(
            "worker(s) died during the sharded solve: "
            + "; ".join(f"pid {p} rc={rc}" for p, rc, _ in failed)
            + "\n" + "\n".join(t for _, _, t in failed)
        )
    result_path = os.path.join(scratch_dir, "result.npz")
    if not os.path.exists(result_path):
        group_span.end(error=True)
        raise ShardedSolveError("worker group finished without a result packet")
    with np.load(result_path, allow_pickle=False) as f:
        labels = f["labels"].astype(np.int64)
        root_edges = int(f["boundary_edges_root"]) \
            if "boundary_edges_root" in f.files else 0
        plane_used = str(f["plane_used"]) if "plane_used" in f.files \
            else "packet"
        plane_reason = str(f["plane_reason"]) if "plane_reason" in f.files \
            else ""
    wall = group_span.end()
    levels = reduce_tree_levels(n_shards, fanout)
    info = {
        "sharded": True,
        "shards": n_shards,
        "fanout": int(fanout),
        "workers": int(n_workers),
        "reduce_plane": plane_used,
        "plane_reason": plane_reason,
        "levels": [{"level": i, "groups": len(g)} for i, g in enumerate(levels)],
        "wall_s": round(wall, 4),
        "boundary_edges_root": root_edges,
        # contraction rounds tick inside the worker processes — invisible
        # to this process's counters, so manifests of worker-group solves
        # report rounds=0 by design (the root residual above is shipped
        # back explicitly for the same reason)
    }
    _record_solve_metrics(
        sharded_solves=1, solve_shards=n_shards,
        solve_levels=len(levels), boundary_edges_in=len(edges),
        boundary_edges_out=root_edges, tree_solve_s=wall,
    )
    return labels, info


# -- the attributed task entry point ------------------------------------------


def solve_with_reduce_tree(
    n_nodes: int,
    edges: np.ndarray,
    payload: np.ndarray,
    *,
    node_shard: Optional[np.ndarray],
    solver_shards: int,
    fanout: int,
    failures_path: str,
    task_name: str,
    unsharded: Callable[[], np.ndarray],
    solver: Optional[Callable] = None,
    mode: str = "max",
    threshold: float = 0.0,
    lifted_edges: Optional[np.ndarray] = None,
    lifted_payload: Optional[np.ndarray] = None,
    workers: int = 1,
    scratch_dir: Optional[str] = None,
    worker_timeout: Optional[float] = None,
    max_workers: int = 1,
    reduce_plane: str = "auto",
    hop_deadline_s: Optional[float] = None,
) -> Tuple[np.ndarray, Dict]:
    """Sharded solve with the single-host path as the degenerate case AND
    the degrade fallback.  Returns ``(labels, info)``.

    ``node_shard`` may be the partition array, a zero-arg callable
    building it (resolved inside the fallback ladder — partition
    construction re-opens block geometry and must not be able to fail the
    task), or None (nothing to shard by: single-host, no failure record).

    ``solver_shards <= 1`` (or a graph too small to shard) runs
    ``unsharded()`` directly — today's behavior, bit for bit.  Otherwise the
    reduce tree runs (in-process, or over a ``workers``-process
    :mod:`..parallel.multihost` group when ``workers > 1``; the worker
    path always uses the default frontier-aware solver — a custom
    ``solver`` callback cannot cross process boundaries); ANY failure in
    it — a killed worker, a lost reduce hop, an injected ``solve`` fault —
    is recorded in ``failures.json`` with resolution
    ``degraded:unsharded_solve`` and the single-host solver produces the
    answer, so the result is exactly what the unsharded run would have
    computed (docs/ROBUSTNESS.md "Graceful degradation").
    ``DrainInterrupt`` is a BaseException and passes through: a preemption
    mid-solve drains, it does not burn a fallback.

    ``reduce_plane``/``hop_deadline_s`` pick the level engine (see
    :func:`sharded_solve`): ``collective`` rides the degrade ladder
    collective → packet plane → unsharded, each rung attributed
    (``degraded:packet_plane`` / ``degraded:unsharded_solve``); ``auto``
    takes the best supported rung; ``packet`` never touches devices.
    """
    shards = int(solver_shards or 1)
    if shards <= 1 or node_shard is None or int(n_nodes) == 0 \
            or len(edges) == 0:
        return unsharded(), {"sharded": False, "shards": 1}
    no_partition = False
    try:
        from ..runtime import faults as faults_mod

        faults_mod.get_injector().maybe_fail("solve")
        # the partition may be a thunk (tasks re-open block geometry to
        # build it): resolve it INSIDE the ladder, so an unreachable store
        # or a torn block-nodes file degrades instead of failing the task
        if callable(node_shard):
            node_shard = node_shard()
            if node_shard is None:
                # legitimately nothing to shard by (no block geometry) —
                # single-host, but not a failure worth attributing
                no_partition = True
                raise ShardedSolveError("no block geometry to shard by")
        plane_req = os.environ.get(_ENV_PLANE) or (reduce_plane or "auto")
        if int(workers) > 1:
            if scratch_dir is None:
                raise ShardedSolveError(
                    "worker-group solve needs a scratch_dir for the hops"
                )

            def worker_solve(rp):
                return solve_over_workers(
                    n_nodes, edges, payload, node_shard,
                    fanout=fanout, mode=mode, threshold=threshold,
                    lifted_edges=lifted_edges, lifted_payload=lifted_payload,
                    n_workers=int(workers), scratch_dir=scratch_dir,
                    timeout=worker_timeout, reduce_plane=rp,
                    hop_deadline_s=hop_deadline_s,
                )

            if plane_req != "collective":
                return worker_solve(plane_req)
            # demanded collective: one retry rung on the packet plane
            # before the unsharded ladder below — a mid-solve collective
            # failure (hop deadline, failed gather → worker SIGKILL)
            # re-runs the whole group on packets, bit-identically
            try:
                labels, winfo = worker_solve("collective")
            except ShardedSolveError as hop_err:
                _record_packet_degrade(failures_path, task_name, hop_err)
                labels, winfo = worker_solve("packet")
                winfo["degraded_plane"] = str(hop_err)[:200]
                return labels, winfo
            if winfo.get("reduce_plane") != "collective":
                # the workers degraded up front (unsupported backend /
                # init failure) — attribute it here, once, driver-side
                _record_packet_degrade(
                    failures_path, task_name,
                    ShardedSolveError(
                        winfo.get("plane_reason") or "collective plane "
                        "unavailable in the worker group"
                    ),
                )
            return labels, winfo
        return sharded_solve(
            n_nodes, edges, payload, node_shard,
            fanout=fanout, solver=solver, mode=mode, threshold=threshold,
            lifted_edges=lifted_edges, lifted_payload=lifted_payload,
            max_workers=max_workers, reduce_plane=plane_req,
            hop_deadline_s=hop_deadline_s, failures_path=failures_path,
            task_name=task_name,
        )
    except _PROGRAMMING_ERRORS:
        raise
    except Exception as e:
        if no_partition:
            return unsharded(), {"sharded": False, "shards": 1}
        # the fallback ladder: anything short of a drain degrades to the
        # single-host solve, attributed like every other degradation —
        # and lands on the trace timeline next to the solve latency it
        # causes (docs/OBSERVABILITY.md)
        _record_solve_metrics(unsharded_fallbacks=1)
        trace_mod.instant(
            "degraded:unsharded_solve", task=task_name,
            error=f"{type(e).__name__}: {e}"[:200],
        )
        tb = fu.cap_traceback(
            f"{type(e).__name__}: {e}"
        )
        try:
            fu.record_failures(failures_path, task_name, [{
                "block_id": None,
                "sites": {"solve": 1},
                "error": tb,
                "quarantined": False,
                "resolved": True,
                "resolution": "degraded:unsharded_solve",
            }])
        except Exception:
            pass  # attribution is best effort; the solve must still land
        labels = unsharded()
        return labels, {
            "sharded": False,
            "shards": shards,
            "degraded": "unsharded_solve",
            "error": str(e)[:300],
        }
