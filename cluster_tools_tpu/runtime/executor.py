"""Blockwise executor: maps the block grid onto the device mesh.

This is the TPU-native replacement for the reference's job machinery
(``prepare_jobs`` / ``submit_jobs`` / ``wait_for_jobs`` in SURVEY.md §2a):
instead of serializing per-job JSON configs and submitting slurm array jobs,
the driver batches blocks into device-sized groups, streams them host->HBM
with a double-buffered prefetch pipeline, and runs one jitted, vmapped kernel
per batch with the batch axis sharded across the mesh.

The pipeline per batch:

    host threads: read blocks (+halo) from chunked storage, pad to the
                  static outer shape                               [IO bound]
    device:       one compiled program over the batch, batch axis sharded
                  across devices                                   [compute]
    host threads: crop inner blocks, write to chunked storage      [IO bound]

Reads for batch i+1 overlap compute for batch i (prefetch depth 2); writes
are fire-and-forget futures drained promptly in a bounded window.

Sweep modes (docs/PERFORMANCE.md "Sharded sweeps"): the historical
``per_block`` path compiles ``jit(vmap(kernel))`` at width ``n_devices *
device_batch`` — one dispatch per block on a single-device host, each
paying dispatch + host-sync overhead behind the dispatch lock.  The
``sharded`` mode instead executes a whole Morton batch of blocks as ONE
``shard_map`` program over the device mesh
(:func:`~cluster_tools_tpu.parallel.batch_shard.batched_shard_map`): the
stacked batch axis is split across devices, each device vmaps the kernel
over its sub-batch, and the dispatch lock is held once per batch.  The
default ``sweep_mode="auto"`` picks sharded when the mesh has >= 2 devices
or the sweep has at least one full sharded batch.  Sharded output is
bit-identical to the per-block path (per-lane vmap numerics are width-
independent; asserted by tests/test_sharded.py and ``bench.py --sweep``),
and the per-block program remains the degrade/speculation fallback: a
sharded batch that hits a device OOM or a hung device falls back to
per-block execution for its blocks, attributed in ``failures.json`` as
``resolution="degraded:unsharded"``.

Fault tolerance (docs/ROBUSTNESS.md): per-block loads and stores retry with
exponential backoff + jitter; blocks that exhaust their retries (or whose
outputs fail validation — NaN/inf, or a task-supplied ``validate_fn``) are
*quarantined*: the batch and the run continue, and quarantined blocks are
re-attempted at the end on a reduced-batch path (the block replicated to the
batch width through the *same* compiled kernel, so a recovered block is
bit-identical to an undisturbed run).  Every block that ever failed is
recorded in a structured ``failures.json`` manifest (block id, per-site
attempt counts, capped traceback, resolution); blocks that stay failed after
the quarantine pass raise with their ids attributed.  Block-level success
markers give the same resume grain as the reference's ``log_block_success``
— ``done_block_ids`` filters them built-in.

Silent failures (docs/ROBUSTNESS.md "Silent failures"): ``block_deadline_s``
arms a watchdog that detects *hung* blocks (stuck IO, wedged kernel) within
one watchdog period of the deadline, quarantines them, and speculatively
re-executes them through the same compiled kernel — first result wins, with
a bit-identity check when both copies complete.  ``store_verify_fn`` (built
by :func:`region_verifier` from a checksummed dataset) re-reads each stored
region so a chunk corrupted on storage is repaired by a re-store (retry) or
a recompute (quarantine) while the writer still owns the block.

Graceful degradation (docs/ROBUSTNESS.md "Graceful degradation"): resource
exhaustion — host/device OOM (``MemoryError``, XLA ``RESOURCE_EXHAUSTED``)
and a full filesystem (``ENOSPC``/``EDQUOT``) — is *classified*
(:func:`classify_resource_error`) and routed to a degrade policy instead of
same-size retries (re-running the exact allocation that just failed only
burns the retry budget): the block waits for headroom and re-executes once
at full size through the same compiled kernel (``degraded:backpressure``),
then — for call sites that declare ``splittable=True`` — recursively
re-executes as 2^d halo-correct sub-blocks through the same kernel down to
``min_block_shape``, reassembled via the task's own store path
(``degraded:split``).  A byte-budget admission controller additionally caps
the bytes of in-flight batches and backpressures the store drain when
host-memory or disk headroom runs low.  Preemption: SIGTERM/SIGUSR1 flip a
process-wide drain latch; the sweep stops claiming batches, finishes
in-flight work, flushes markers + ``failures.json``, and raises
:class:`~cluster_tools_tpu.runtime.supervision.DrainInterrupt` so the entry
point exits with ``REQUEUE_EXIT_CODE`` and the supervisor requeues the job.
"""

from __future__ import annotations

import contextlib
import errno
import functools
import hashlib
import inspect
import itertools
import math
import os
import threading
import time
import traceback
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor, Future
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..io import chunk_cache as chunk_cache_mod
from ..io.containers import ChunkCorruptionError
from . import admission as admission_mod
from . import handoff as handoff_mod
from ..utils import function_utils as fu
from ..utils.volume_utils import Block, Blocking
from . import faults as faults_mod
from . import trace as trace_mod
from .supervision import (
    DrainInterrupt,
    FirstWins,
    Watchdog,
    array_digest,
    disk_free_fraction,
    drain_reason,
    drain_requested,
    host_mem_available_bytes,
    host_mem_available_fraction,
    install_drain_handler,
)


# canonical device-selection policy lives in parallel/mesh.py
from ..parallel.mesh import backend_devices as get_devices
from ..parallel.batch_shard import (
    batched_shard_map,
    ragged_shard_map,
    resolve_sharded_batch,
    use_sharded_sweep,
)
from ..parallel import block_pool as block_pool_mod
from ..parallel import device_pool as device_pool_mod
from ..parallel import step_cache


# -- process-wide dispatch metrics -------------------------------------------
# Mirrors io/chunk_cache.py's snapshot/delta counters: the task runtime
# snapshots around run_impl and merges the delta into io_metrics.json, so
# the dispatch-amortization win of the sharded sweep is observable per task
# (docs/PERFORMANCE.md "Sharded sweeps"), not just in bench.

_METRICS_LOCK = threading.Lock()
_DISPATCH_COUNTERS = {
    "batches_dispatched": 0,   # compiled-program executions (batch grain)
    "blocks_dispatched": 0,    # blocks carried by those executions
    "dispatch_wait_s": 0.0,    # dispatch loop stalled on un-overlapped loads
    "sweep_s": 0.0,            # total map_blocks wall time
    # ragged paged sweeps (docs/PERFORMANCE.md "Ragged sweeps"): batches
    # that ran mixed-shape/partial work as one program via the paged
    # block pool, the synthetic padding lanes they carried (discarded on
    # d2h), and the real pool pages those dispatches referenced
    "ragged_batches": 0,
    "lanes_padded": 0,
    "pages_in_use": 0,
}


def dispatch_snapshot() -> Dict[str, float]:
    """Current process-wide dispatch counters (monotonic; diff two
    snapshots with :func:`dispatch_delta` to attribute a task's share)."""
    with _METRICS_LOCK:
        return dict(_DISPATCH_COUNTERS)


def dispatch_delta(snapshot: Dict[str, float]) -> Dict[str, float]:
    """Counter movement since ``snapshot`` (same keys)."""
    cur = dispatch_snapshot()
    return {k: cur[k] - snapshot.get(k, 0) for k in cur}


def _record_dispatch_metrics(batches: int, blocks: int, wait_s: float,
                             sweep_s: float, ragged_batches: int = 0,
                             lanes_padded: int = 0,
                             pages_in_use: int = 0) -> None:
    with _METRICS_LOCK:
        _DISPATCH_COUNTERS["batches_dispatched"] += int(batches)
        _DISPATCH_COUNTERS["blocks_dispatched"] += int(blocks)
        _DISPATCH_COUNTERS["dispatch_wait_s"] += float(wait_s)
        _DISPATCH_COUNTERS["sweep_s"] += float(sweep_s)
        _DISPATCH_COUNTERS["ragged_batches"] += int(ragged_batches)
        _DISPATCH_COUNTERS["lanes_padded"] += int(lanes_padded)
        _DISPATCH_COUNTERS["pages_in_use"] += int(pages_in_use)


#: bound on one executor's compiled-program cache (see
#: :meth:`BlockwiseExecutor._cached_program`); a sweep holds at most a few
#: programs (sharded, ragged, per-block fallback, sub-block), the rest is
#: headroom for executors reused across many kernels.
_PROGRAM_CACHE_SIZE = 16

#: bound on a server-scoped shared cache (docs/SERVING.md): programs for
#: the repeat-request working set of a resident server.
SHARED_PROGRAM_CACHE_SIZE = 64


class _Unfreezable(Exception):
    """A captured value that cannot participate in a kernel identity."""


def _freeze(obj, seen: set, depth: int = 0):
    """A hashable, value-equal snapshot of ``obj`` for kernel-identity
    keys, or :class:`_Unfreezable`.  Containers and callables recurse
    (bounded, cycle-guarded); arrays / datasets / arbitrary objects refuse
    — a kernel closing over them only ever hits the instance cache."""
    if depth > 16:
        raise _Unfreezable("nesting too deep")
    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return obj
    if isinstance(obj, np.generic):
        return ("np", obj.dtype.name, obj.item())
    oid = id(obj)
    if oid in seen:
        raise _Unfreezable("cyclic capture")
    seen = seen | {oid}
    if isinstance(obj, (tuple, list)):
        return ("seq", tuple(_freeze(v, seen, depth + 1) for v in obj))
    if isinstance(obj, (set, frozenset)):
        return ("set", frozenset(_freeze(v, seen, depth + 1) for v in obj))
    if isinstance(obj, dict):
        return ("map", tuple(
            (_freeze(k, seen, depth + 1), _freeze(v, seen, depth + 1))
            for k, v in sorted(obj.items(), key=lambda kv: repr(kv[0]))
        ))
    if isinstance(obj, functools.partial):
        return (
            "partial",
            _freeze(obj.func, seen, depth + 1),
            _freeze(obj.args, seen, depth + 1),
            _freeze(obj.keywords, seen, depth + 1),
        )
    # named code objects a kernel commonly captures via function-local
    # imports (``import jax.numpy as jnp`` inside run_impl makes jnp a
    # closure CELL): stable within one process, identified by name
    if inspect.ismodule(obj):
        return ("module", obj.__name__)
    if inspect.isbuiltin(obj) or isinstance(obj, np.ufunc):
        return ("builtin", getattr(obj, "__module__", None), obj.__name__)
    if isinstance(obj, type):
        return ("type", obj.__module__, obj.__qualname__)
    if inspect.ismethod(obj):
        return (
            "method",
            _freeze(obj.__func__, seen, depth + 1),
            _freeze(obj.__self__, seen, depth + 1),
        )
    if isinstance(obj, np.dtype):
        return ("dtype", obj.name)
    if inspect.isfunction(obj):
        cells = ()
        if obj.__closure__:
            vals = []
            for cell in obj.__closure__:
                try:
                    vals.append(_freeze(cell.cell_contents, seen, depth + 1))
                except ValueError:  # empty cell
                    vals.append(("empty-cell",))
            cells = tuple(vals)
        return (
            "fn", obj.__module__, obj.__qualname__,
            _freeze_code(obj.__code__, seen, depth + 1),
            cells,
            _freeze(obj.__defaults__, seen, depth + 1),
            _freeze(obj.__kwdefaults__, seen, depth + 1),
        )
    raise _Unfreezable(type(obj).__name__)


def _freeze_code(code, seen: set, depth: int):
    """Behavioral snapshot of a code object: bytecode alone is NOT enough
    (two kernels calling np.minimum vs np.maximum differ only in
    ``co_names``; nested lambdas differ only in their own consts), so the
    freeze carries the referenced names and recurses into nested code."""
    if depth > 16:
        raise _Unfreezable("code nesting too deep")
    consts = tuple(
        _freeze_code(c, seen, depth + 1) if inspect.iscode(c)
        else _freeze(c, seen, depth + 1)
        for c in code.co_consts
    )
    return ("code", code.co_code, code.co_names, consts)


def kernel_identity(kernel: Callable) -> Optional[tuple]:
    """A hashable identity for ``kernel`` that two *different* callables
    share exactly when their code AND captured values are equal: module /
    qualname / bytecode / recursively frozen closure cells and defaults.
    This is what lets a server-scoped :class:`ProgramCache` serve a warm
    compiled program to a repeat request whose task rebuilt its kernel
    closure (docs/SERVING.md).  Returns None when any captured value
    cannot be frozen (model checkpoints, datasets, ad-hoc objects) — such
    kernels stay instance-scoped, which is always safe.

    Module-level globals the kernel references are NOT part of the
    identity (they are not captured cells); the shared cache therefore
    assumes module code is stable within the server process — true for a
    resident server, and why the batch CLI keeps instance scope.
    Captured dicts freeze by sorted content — Python ``==`` semantics —
    so a kernel whose *trace* depends on dict insertion order (iterating
    ``cfg.items()`` into order-sensitive float accumulation) is outside
    the contract; request configs parsed from JSON documents have stable
    order anyway.
    """
    try:
        return _freeze(kernel, set())
    except _Unfreezable:
        return None


def identity_digest(identity: tuple) -> str:
    """sha256 of a :func:`kernel_identity`, the same in every process: a
    frozen set's elements are put in order first (their iteration order
    follows string hashing, which differs from process to process)."""

    def canonical(v):
        if isinstance(v, frozenset):
            return ("set", tuple(sorted((canonical(e) for e in v), key=repr)))
        if isinstance(v, tuple):
            return tuple(canonical(e) for e in v)
        return v

    return hashlib.sha256(repr(canonical(identity)).encode()).hexdigest()


class _KeptSweepProgram:
    """The sharded sweep program of a kernel whose identity freezes, kept
    across executors, tasks and jobs by ``parallel/step_cache.py``: looked
    up at its first call for each input signature, from the stacked batch's
    shapes, dtypes and shardings, in the process, then in the step store,
    and only then built (``batched_shard_map(...).lower(...).compile()``).
    What the process keeps is the compiled program alone: the kernel's
    captured values are plain (that is what freezing them proved), so
    nothing a task owns outlives it.  With ``replicated``, the sweep's
    replicated operand leads the arguments; its leaves enter the signature
    and the key by shape, dtype and sharding, never by value, so another
    value of it runs the same program.  ``info`` is the last look-up's
    ``{from, key, load_s, store_bytes, fallback}``."""

    def __init__(self, kernel: Callable, identity: tuple, mesh: Mesh, batch: int,
                 replicated: bool = False):
        self._kernel, self._mesh, self._batch = kernel, mesh, int(batch)
        self._replicated = bool(replicated)
        self._digest = identity_digest(identity)
        self._ready: Dict[tuple, Callable] = {}
        self.info: Optional[Dict[str, Any]] = None

    def __call__(self, *args):
        leaves, tree = jax.tree_util.tree_flatten(args)
        signature = (tree,) + tuple((a.shape, a.dtype, a.sharding) for a in leaves)
        program = self._ready.get(signature)
        if program is None:
            program, self.info = step_cache.program_for(
                self._mesh, args, self._digest, self._batch,
                lambda: batched_shard_map(self._kernel, self._mesh, self._batch,
                                          replicated=self._replicated),
            )
            self._ready[signature] = program
        return program(*args)


class ProgramCache:
    """Thread-safe bounded LRU of compiled program wrappers.

    Instance-scoped by default (``by_identity=False``): keys include
    ``id(kernel)``, entries strongly reference the kernel so the id stays
    valid, and the cache dies with its executor — a cached wrapper can pin
    a task's captured state (e.g. a model checkpoint), so it must not
    outlive the task (the PR-7 rationale).

    ``by_identity=True`` is the server-scoped promotion (docs/SERVING.md):
    keys use :func:`kernel_identity` + the program's mode/width/devices
    key, so repeat requests through a resident server skip the per-shape
    compile even though every request builds a fresh kernel closure.  The
    LRU bound is what bounds the pinned closures; the resident server is
    exactly the owner that wants warm programs pinned.
    """

    def __init__(self, max_size: int = _PROGRAM_CACHE_SIZE,
                 by_identity: bool = False):
        self.max_size = int(max_size)
        self.by_identity = bool(by_identity)
        self._lock = threading.Lock()
        self._entries: "OrderedDict" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.unkeyed = 0  # identity-mode lookups that could not be keyed

    def kernel_key(self, kernel: Callable):
        if not self.by_identity:
            return id(kernel)
        key = kernel_identity(kernel)
        if key is None:
            with self._lock:
                self.unkeyed += 1
        return key

    def get_or_build(self, kernel: Callable, kernel_key, key: tuple,
                     builder: Callable):
        cache_key = (kernel_key, key)
        with self._lock:
            hit = self._entries.get(cache_key)
            if hit is not None:
                self._entries.move_to_end(cache_key)
                self.hits += 1
                return hit[1]
        # compile outside the lock (it can take seconds); a racing builder
        # of the same program is harmless — last one in wins the slot.  The
        # entry holds a strong ref to the kernel, which keeps an id() key
        # component valid for the entry's lifetime.
        prog = builder()
        with self._lock:
            self.misses += 1
            self._entries[cache_key] = (kernel, prog)
            self._entries.move_to_end(cache_key)
            while len(self._entries) > self.max_size:
                self._entries.popitem(last=False)
        return prog

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "programs": len(self._entries),
                "max_size": self.max_size,
                "hits": self.hits,
                "misses": self.misses,
                "unkeyed": self.unkeyed,
            }


#: the optional process-wide shared program cache.  Installed by the
#: resident server (``runtime/server.py``) so every executor any request
#: task builds shares one identity-keyed cache; batch entry points never
#: install one, keeping the PR-7 instance scope (and its lifetime safety)
#: for one-shot runs.
_SHARED_PROGRAM_CACHE: Optional[ProgramCache] = None


def install_shared_program_cache(
    cache: Optional[ProgramCache],
) -> Optional[ProgramCache]:
    """Install (or, with None, uninstall) the process-wide shared program
    cache; returns the previous one."""
    global _SHARED_PROGRAM_CACHE
    prev = _SHARED_PROGRAM_CACHE
    _SHARED_PROGRAM_CACHE = cache
    return prev


def shared_program_cache() -> Optional[ProgramCache]:
    return _SHARED_PROGRAM_CACHE


def get_mesh(
    target: str = "local",
    n_devices: Optional[int] = None,
    axis_name: str = "blocks",
) -> Mesh:
    devs = get_devices(target, n_devices)
    return Mesh(np.array(devs), (axis_name,))


#: errnos that mean "storage is full", not "storage is broken"
_DISK_FULL_ERRNOS = (errno.ENOSPC, errno.EDQUOT)


def classify_resource_error(exc: BaseException) -> Optional[str]:
    """``"oom"`` / ``"enospc"`` when ``exc`` (or anything on its
    cause/context chain) is a resource-exhaustion failure, else None.

    - ``MemoryError`` — host allocator failure (numpy, stacking, IO
      buffers),
    - XLA's ``RESOURCE_EXHAUSTED`` / out-of-memory runtime errors, matched
      by type name + message so no jaxlib-version-specific import is
      needed,
    - ``OSError`` with ``ENOSPC``/``EDQUOT`` — shared filesystem full.

    Retrying these at the same size re-runs the exact allocation that just
    failed; callers route them to the degrade policy instead.
    """
    seen = set()
    while exc is not None and id(exc) not in seen:
        seen.add(id(exc))
        if isinstance(exc, MemoryError):
            return "oom"
        if isinstance(exc, OSError) and exc.errno in _DISK_FULL_ERRNOS:
            return "enospc"
        msg = str(exc)
        if type(exc).__name__ == "XlaRuntimeError" and (
            "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg
        ):
            return "oom"
        # older jaxlibs surface allocator failures as a plain RuntimeError
        # carrying the status name; arbitrary exception types that merely
        # MENTION the string are not classified
        if isinstance(exc, RuntimeError) and "RESOURCE_EXHAUSTED" in msg:
            return "oom"
        exc = exc.__cause__ or exc.__context__
    return None


class SubBlock(Block):
    """A degrade-split fragment of a parent block (same ``block_id``).
    Load/store callbacks that pad to a static batch shape can detect these
    (:func:`is_sub_block`) and size buffers per-block instead — sub-blocks
    never enter a stacked batch, so the static-shape contract does not
    apply to them."""


def is_sub_block(block: Block) -> bool:
    return isinstance(block, SubBlock)


def split_block(
    block: Block,
    halo: Optional[Sequence[int]] = None,
    min_shape: Optional[Sequence[int]] = None,
) -> Optional[List[Block]]:
    """Split ``block``'s inner region into up to 2^d halo-correct
    sub-blocks (each axis halved where both halves stay >= ``min_shape``).

    Sub-blocks keep the parent's ``block_id`` (markers, fault targeting and
    failure attribution stay at the parent grain) and get outer boxes of
    ``sub_inner ± halo`` clamped to the parent's outer box — which is the
    volume clamp, since the parent's outer box is itself the volume-clamped
    ``inner ± halo``.  ``halo`` defaults to the parent's own per-axis halo
    (max over the two sides, so border clipping does not shrink it); pass
    it explicitly for single-block axes, where both sides are clipped and
    nothing can be derived.  Returns None when no axis can split.
    """
    nd = len(block.begin)
    if halo is None:
        halo = tuple(
            max(b - ob, oe - e)
            for b, ob, e, oe in zip(
                block.begin, block.outer_begin, block.end, block.outer_end
            )
        )
    halo = tuple(int(h) for h in halo)
    min_shape = tuple(
        max(1, int(m)) for m in (min_shape or (1,) * nd)
    )
    axes_intervals = []
    any_cut = False
    for ax in range(nd):
        lo, hi = block.begin[ax], block.end[ax]
        half = (hi - lo) // 2
        if half >= min_shape[ax] and (hi - lo) - half >= min_shape[ax]:
            axes_intervals.append([(lo, lo + half), (lo + half, hi)])
            any_cut = True
        else:
            axes_intervals.append([(lo, hi)])
    if not any_cut:
        return None
    subs = []
    for combo in itertools.product(*axes_intervals):
        begin = tuple(c[0] for c in combo)
        end = tuple(c[1] for c in combo)
        outer_begin = tuple(
            max(ob, b - h) for ob, b, h in zip(block.outer_begin, begin, halo)
        )
        outer_end = tuple(
            min(oe, e + h) for oe, e, h in zip(block.outer_end, end, halo)
        )
        subs.append(SubBlock(block.block_id, begin, end, outer_begin, outer_end))
    return subs


def morton_order(blocks: Sequence[Block]) -> List[Block]:
    """Reorder ``blocks`` along a Morton/Z-order curve of the block grid.

    Locality-aware sweep scheduling (docs/PERFORMANCE.md "Chunk-aware
    I/O"): raster order walks a whole grid row before returning to a
    neighborhood, so by the time the next row reads the shared boundary
    chunks they have been evicted from the decompressed-chunk cache.
    Z-order keeps consecutive blocks (and therefore consecutive executor
    batches) spatially adjacent — every aligned 2x2x2 octant of the grid is
    visited contiguously — so halo reads land while their neighbors'
    chunks are still resident.

    Grid positions are recovered from the blocks' own ``begin`` coordinates
    (per-axis rank over the distinct values), so ROI-restricted and
    parity-filtered block lists order correctly without a Blocking handle.
    Deterministic: a pure permutation keyed on grid position.
    """
    blocks = list(blocks)
    if len(blocks) < 3:
        return blocks
    nd = len(blocks[0].begin)
    rank = []
    for ax in range(nd):
        values = sorted({int(b.begin[ax]) for b in blocks})
        rank.append({v: i for i, v in enumerate(values)})
    nbits = max(
        1, max(len(r) - 1 for r in rank).bit_length()
    )

    def code(b: Block) -> int:
        c = 0
        for bit in range(nbits):
            for ax in range(nd):
                c |= ((rank[ax][int(b.begin[ax])] >> bit) & 1) << (
                    bit * nd + ax
                )
        return c

    return sorted(blocks, key=code)


def check_finite_outputs(block: Block, out) -> Optional[str]:
    """Built-in output validator: any non-finite value in a float leaf is a
    corrupt kernel output (the classic silent NaN-producing-kernel failure)."""
    for leaf in jax.tree_util.tree_leaves(out):
        a = np.asarray(leaf)
        if a.dtype.kind == "f" and not np.isfinite(a).all():
            return "non-finite values (NaN/inf) in kernel output"
    return None


def region_verifier(
    dataset, bb_of: Optional[Callable[[Block], Any]] = None
) -> Optional[Callable[[Block], None]]:
    """Build a ``store_verify_fn`` for :meth:`BlockwiseExecutor.map_blocks`
    from a dataset with digest sidecars: read the block's stored region back
    and raise :class:`~cluster_tools_tpu.io.containers.ChunkCorruptionError`
    if its bytes no longer match the recorded checksum.  Returns None for
    datasets without checksum support (HDF5), so call sites wire it
    unconditionally.

    Wiring a verifier also declares the dataset a **block-product store**
    for the self-healing plane (docs/SERVING.md "Self-healing"): its
    reads fall under the verifying reader's missing-sidecar policy
    (``io/verified.py``), and the returned callable carries the dataset +
    geometry (``.dataset`` / ``.bb_of``) so the executor can register
    per-block lineage (``runtime/repair.py``) after each verified store —
    call sites wire ONE knob and get detection, policy, scrub, and repair
    together."""
    verify = getattr(dataset, "verify_region", None)
    if verify is None:
        return None
    from ..io import verified as verified_mod

    verified_mod.mark_product(dataset)
    if bb_of is None:
        bb_of = lambda block: block.bb  # noqa: E731 - trivial default

    def store_verify(block: Block) -> None:
        verify(bb_of(block))

    store_verify.dataset = dataset
    store_verify.bb_of = bb_of
    return store_verify


def validate_labels(block: Block, out) -> Optional[str]:
    """Validator for label-producing kernels: negative (signed) or
    saturated (unsigned) label values are the integer shadows of a corrupt
    kernel — a NaN cast to int yields exactly these.  Float leaves are
    covered by ``map_blocks``' built-in ``check_finite`` pass, not here."""
    for leaf in jax.tree_util.tree_leaves(out):
        a = np.asarray(leaf)
        if a.size == 0:
            continue
        if a.dtype.kind == "i" and int(a.min()) < 0:
            return "negative label values (corrupt kernel output)"
        if a.dtype.kind == "u" and bool((a == np.iinfo(a.dtype).max).any()):
            return "saturated label values (corrupt kernel output)"
    return None


class BlockwiseExecutor:
    """Run a per-block kernel over a list of blocks, batched across devices.

    ``kernel`` is a pure function over one block's arrays; it is vmapped,
    jitted, and the batch axis is sharded over the mesh.  ``load_fn(block)``
    returns the kernel's input arrays for one block (already padded to a
    uniform shape); ``store_fn(block, outputs)`` persists one block's outputs
    (each already a numpy array).
    """

    def __init__(
        self,
        target: str = "local",
        n_devices: Optional[int] = None,
        device_batch: int = 1,
        io_threads: int = 8,
        max_retries: int = 2,
        backoff_base: float = 0.05,
        backoff_max: float = 5.0,
    ):
        self.target = target
        self.devices = get_devices(target, n_devices)
        self.n_devices = len(self.devices)
        self.device_batch = int(device_batch)
        self.batch_size = self.n_devices * self.device_batch
        self.mesh = Mesh(np.array(self.devices), ("blocks",))
        self.io_threads = io_threads
        self.max_retries = int(max_retries)
        self.backoff_base = float(backoff_base)
        self.backoff_max = float(backoff_max)
        # compiled-program reuse across sweeps OF THIS EXECUTOR: repeated
        # map_blocks calls with the same kernel callable (bench re-sweeps,
        # resident service workers holding a warm executor) skip the
        # per-shape compile — the same 10x cold-vs-warm tax ROADMAP item 4
        # records for the solver.  Instance-scoped on purpose: the cached
        # wrapper strongly references its kernel closure (which can pin a
        # task's captured state, e.g. a model checkpoint), so the cache
        # must die with the executor, not outlive the task process-wide.
        # Two routes take precedence for kernels whose identity freezes
        # (captured values plain, nothing a task owns): under a resident
        # server its SHARED identity-keyed cache
        # (install_shared_program_cache, docs/SERVING.md); elsewhere, on
        # the sharded route, the process level and step store of
        # parallel/step_cache.py (_KeptSweepProgram), which keep only the
        # compiled program.  The per-block, vmap and ragged programs and
        # every kernel that captures an array stay here.
        self._program_cache = ProgramCache(_PROGRAM_CACHE_SIZE)

    def _program_lookup(self, kernel: Callable) -> Callable:
        """Resolve the cache route for ``kernel`` ONCE (the identity
        freeze walks the whole closure — per sweep, not per batch) and
        return a ``(key, builder) -> program`` lookup bound to it."""
        shared = shared_program_cache()
        if shared is not None:
            kernel_key = shared.kernel_key(kernel)
            if kernel_key is not None:
                return functools.partial(
                    shared.get_or_build, kernel, kernel_key
                )
        return functools.partial(
            self._program_cache.get_or_build, kernel, id(kernel)
        )

    def _cached_program(self, kernel: Callable, key: tuple,
                        builder: Callable):
        return self._program_lookup(kernel)(key, builder)

    # -- retry/backoff machinery ------------------------------------------
    def _backoff(self, attempt: int) -> float:
        return fu.backoff_delay(attempt, self.backoff_base, self.backoff_max)

    def _io_with_retries(
        self, site: str, block: Block, fn: Callable,
        on_error: Optional[Callable[[Exception], None]] = None,
    ):
        """Run ``fn`` with injection + retries.  Returns
        ``(value, attempts, traceback_or_None, resource_class_or_None)``;
        the caller quarantines on a non-None traceback.  A resource-
        classified failure (OOM / ENOSPC) short-circuits the retry loop —
        re-running the same allocation at the same size only burns the
        budget; the degrade policy owns it.  ``on_error`` observes each
        caught exception (failure-class attribution, e.g. counting
        ChunkCorruptionErrors)."""
        injector = faults_mod.get_injector()
        voxels = int(np.prod(block.outer_shape))
        last_tb = None
        for k in range(self.max_retries + 1):
            try:
                injector.maybe_fail(site, block.block_id, voxels=voxels)
                injector.maybe_hang(site, block.block_id)
                return fn(), k + 1, None, None
            except Exception as e:
                if on_error is not None:
                    try:
                        on_error(e)
                    except Exception:
                        pass
                last_tb = fu.cap_traceback(traceback.format_exc())
                resource = classify_resource_error(e)
                if resource is not None:
                    return None, k + 1, last_tb, resource
                if k < self.max_retries:
                    time.sleep(self._backoff(k))
        return None, self.max_retries + 1, last_tb, None

    def map_blocks(
        self,
        kernel: Callable,
        blocks: Sequence[Block],
        load_fn: Callable[[Block], Tuple],
        store_fn: Optional[Callable[[Block, Any], None]] = None,
        on_block_done: Optional[Callable[[Block], None]] = None,
        prefetch: int = 2,
        done_block_ids: Optional[Iterable[int]] = None,
        validate_fn: Optional[Callable[[Block, Any], Optional[str]]] = None,
        check_finite: bool = True,
        failures_path: Optional[str] = None,
        task_name: str = "map_blocks",
        block_deadline_s: Optional[float] = None,
        watchdog_period_s: Optional[float] = None,
        speculate: bool = True,
        store_verify_fn: Optional[Callable[[Block], None]] = None,
        splittable: bool = False,
        split_halo: Optional[Sequence[int]] = None,
        min_block_shape: Optional[Sequence[int]] = None,
        degrade_wait_s: float = 5.0,
        inflight_byte_budget: Optional[int] = None,
        mem_headroom_fraction: float = 0.05,
        disk_headroom_fraction: float = 0.02,
        schedule: str = "morton",
        sweep_mode: str = "auto",
        sharded_batch: Optional[int] = None,
        ragged: str = "auto",
        page_shape: Optional[Sequence[int]] = None,
        device_pool: str = "auto",
        device_pool_bytes: Optional[int] = None,
        replicated_operand: Any = None,
    ) -> Dict[str, int]:
        """Execute ``kernel`` over ``blocks``; see class docstring.

        ``done_block_ids`` — block ids to skip (success-marker resume grain).
        ``validate_fn(block, outputs) -> Optional[str]`` — extra output
        validation; a non-None message quarantines the block for re-compute.
        ``check_finite`` — built-in NaN/inf validation of float outputs.
        ``failures_path`` — where to record the ``failures.json`` manifest.
        ``block_deadline_s`` — per-block wall-clock budget: a watchdog
        thread declares blocks whose load/compute/store exceeds it *hung*
        (recorded + quarantined within one ``watchdog_period_s``, default
        ``deadline/4``) and, when ``speculate``, launches a duplicate
        re-execution through the same compiled kernel — first result wins,
        and if both copies complete they must agree bit-for-bit (a
        disagreement is recorded as a ``determinism`` failure and the block
        is recomputed).  ``store_verify_fn(block)`` — post-store integrity
        check (see :func:`region_verifier`); a ChunkCorruptionError it
        raises makes the store retry (re-write repairs the corrupt chunk),
        then quarantine (recompute repairs it).

        Graceful degradation (module docstring): a resource-classified
        failure (OOM / ENOSPC) skips same-size retries and enters the
        degrade ladder — wait for memory/disk headroom (up to
        ``degrade_wait_s``), re-execute once at full size, then, when
        ``splittable``, recursively re-execute as halo-correct sub-blocks
        down to ``min_block_shape`` through the same kernel (jitted per
        sub-shape), stored via the task's own ``store_fn``.  ``splittable``
        is a *contract*: ``load_fn``/``store_fn``/``kernel`` must be pure
        functions of the block geometry at any shape (no fixed-shape
        padding), and the kernel must be shape-local so sub-block outputs
        tile to the unsplit result bit-identically (voxelwise/copy-like
        kernels; NOT label-flood kernels whose encoding depends on the
        outer shape).  ``split_halo`` defaults to the per-block derived
        halo.  ``inflight_byte_budget`` caps the bytes of loaded-but-
        unstored batches (None = 25% of MemAvailable at start, 0 =
        disabled); ``mem_headroom_fraction`` / ``disk_headroom_fraction``
        backpressure the store drain when host memory / the manifest
        filesystem run low.

        ``schedule`` — sweep order: ``"morton"`` (default) reorders blocks
        (and therefore the batches) along a Z-order curve of the block grid
        so consecutive batches share boundary chunks while they are still
        resident in the decompressed-chunk cache (:func:`morton_order`);
        ``"given"`` keeps the caller's order.  Per-block outputs are
        independent, so the order never changes results — only IO locality.

        ``sweep_mode`` — ``"per_block"`` (the historical path: one
        ``jit(vmap)`` dispatch per ``n_devices * device_batch`` blocks —
        per *block* on a single-device host), ``"sharded"`` (one
        ``shard_map`` program per Morton batch of ``sharded_batch`` blocks
        over the mesh, holding the dispatch lock once per batch — see the
        module docstring), or ``"auto"`` (default: sharded when the mesh
        has >= 2 devices or the sweep fills at least one sharded batch).
        ``sharded_batch`` — blocks per sharded program (None = ``max(2 *
        n_devices * device_batch, 8)``, rounded up to a device multiple).
        Sharded output is bit-identical to the per-block path; a sharded
        batch that fails with a resource/device error (site ``dispatch``)
        or hangs falls its blocks back to per-block execution, attributed
        ``resolution="degraded:unsharded"``.

        ``ragged`` — mixed-shape handling on the sharded path
        (docs/PERFORMANCE.md "Ragged sweeps"): ``"auto"`` (default) packs
        batches the dense program cannot take — mixed-shape lanes from
        un-padded loads, partial final batches, and (for ``splittable``
        call sites) degrade-split sub-blocks — through the paged block
        pool (:mod:`~cluster_tools_tpu.parallel.block_pool`) and runs
        them as ONE descriptor-driven program per batch, synthetic
        padding lanes discarded on d2h; ``"on"`` additionally forces
        uniform full batches through the ragged program; ``"off"``
        restores the historical behavior (mixed-shape batches and split
        sub-blocks execute per-block, attributed
        ``degraded:unsharded``).  Partial uniform batches pack with the
        lane shape as the page, so every real lane sees exactly the
        bytes per-block dispatch would have seen (any kernel, bit-
        identical); mixed-SHAPE lanes run at the batch's page-aligned
        shape, which is only guaranteed bit-identical on each lane's
        stored region for shape-local kernels — the same contract as
        ``splittable``, and why call sites with shape-dependent label
        encodings keep padding in ``load_fn`` (their batches stay
        uniform and dense).  ``page_shape`` overrides the pool's page
        tile (default: chunk-scale, see
        :func:`~cluster_tools_tpu.parallel.block_pool.
        default_page_shape`); set it to the dataset chunk shape for
        chunk-aligned pooling (uniform-lane batches keep the exact
        lane-shape page regardless — the any-kernel guarantee above is
        unconditional).  Ragged dispatches are attributed in the
        dispatch counters (``ragged_batches`` / ``lanes_padded`` /
        ``pages_in_use`` in io_metrics.json) and on the trace timeline
        (``executor.dispatch`` spans with ``grain="ragged"``).

        ``device_pool`` — HBM-resident staging of ragged batches
        (docs/PERFORMANCE.md "Device-resident data plane"): ``"auto"``
        (default) stages ragged batches through the persistent
        content-addressed device page pool
        (:mod:`~cluster_tools_tpu.parallel.device_pool`) when the ragged
        path is active and ``CTT_DEVICE_POOL`` is not 0 — pages whose
        bytes are already resident cost zero h2d traffic; ``"off"``
        restores the per-batch ``device_put`` staging.  A staging
        RESOURCE_EXHAUSTED rides the degrade ladder (evict the resident
        arenas, retry, then per-batch host staging for that batch,
        attributed ``resolution="degraded:host_staged"`` once per sweep)
        — bit-identical either way.  ``device_pool_bytes`` caps the
        resident allocation (None: ``CTT_DEVICE_POOL_BYTES``, default
        256 MiB).  Traffic is attributed in the device-plane counters
        (``h2d_bytes`` / ``d2h_bytes`` / ``device_pool_hits`` /
        ``bytes_not_staged`` in io_metrics.json) and host-staged uploads
        on the timeline (``executor.h2d`` spans — absent on the
        resident-pool happy path).

        ``replicated_operand`` — a pytree of arrays that is the same for
        every block (a network's weights): placed on the mesh once per
        sweep, replicated, and passed to ``kernel`` as its FIRST argument
        on every route (sharded, ragged, per-block, split sub-blocks), so
        the kernel is called ``kernel(operand, *load_fn(block))``.  The
        programs take it as an argument instead of capturing it as
        constants: a kernel that captures only plain values then keeps its
        sharded program across jobs (:class:`_KeptSweepProgram`) whatever
        the operand's values.  None (default): ``kernel(*load_fn(block))``
        and the programs of a sweep without one.

        Raises RuntimeError naming every block that stays failed after the
        end-of-run quarantine pass, and
        :class:`~cluster_tools_tpu.runtime.supervision.DrainInterrupt`
        when a drain (SIGTERM/SIGUSR1) was requested — in-flight work is
        finished, markers and manifests flushed, remaining blocks left for
        the resumed run.
        """
        if done_block_ids:
            done = {int(b) for b in done_block_ids}
            blocks = [b for b in blocks if int(b.block_id) not in done]
        if schedule == "morton":
            blocks = morton_order(blocks)
        elif schedule not in ("given", None):
            raise ValueError(
                f"unknown schedule {schedule!r} (expected 'morton' or 'given')"
            )
        sharded_width = resolve_sharded_batch(
            self.n_devices, self.batch_size, sharded_batch
        )
        use_sharded = use_sharded_sweep(
            sweep_mode, self.n_devices, len(blocks), sharded_width
        )
        if ragged not in ("auto", "on", "off"):
            raise ValueError(
                f"unknown ragged mode {ragged!r} "
                "(expected 'auto', 'on' or 'off')"
            )
        # the paged block pool is a sharded-path feature: per_block mode
        # dispatches per block anyway, so raggedness costs it nothing
        use_ragged = use_sharded and ragged != "off"
        ragged_pool = (
            block_pool_mod.PagedBlockPool() if use_ragged else None
        )
        if device_pool not in ("auto", "on", "off"):
            raise ValueError(
                f"unknown device_pool mode {device_pool!r} "
                "(expected 'auto', 'on' or 'off')"
            )
        # the resident HBM pool rides the ragged path (its page tables are
        # the re-addressing mechanism); the process kill switch wins over
        # any per-call mode
        dev_pool = (
            device_pool_mod.get_device_pool(device_pool_bytes)
            if use_ragged and device_pool != "off"
            and device_pool_mod.device_pool_enabled()
            else None
        )
        if page_shape is not None:
            page_shape = tuple(int(p) for p in page_shape)
        if not blocks:
            return {"n_blocks": 0, "n_quarantined": 0, "n_failed": 0}
        # preemption-aware draining: SIGTERM/SIGUSR1 flip a latch instead
        # of killing us; the sweep checks it at batch boundaries
        install_drain_handler()
        injector = faults_mod.get_injector()
        deadline = float(block_deadline_s or 0.0)
        block_by_id = {int(b.block_id): b for b in blocks}
        bs0 = self.batch_size
        bs = sharded_width if use_sharded else bs0
        n_batches = math.ceil(len(blocks) / bs)
        sharding = NamedSharding(self.mesh, P("blocks"))
        # page pools of ragged batches are broadcast to every device (each
        # lane gathers from the whole pool); tables/valid shard over blocks
        replicated = NamedSharding(self.mesh, P())
        dev_key = tuple(d.id for d in self.devices)
        # the replicated operand, on the mesh once per sweep: ``lead`` goes
        # in front of every program call's block arguments, and the
        # programs built for it are cached apart (``operand_tag``)
        has_operand = replicated_operand is not None
        lead = (
            (jax.device_put(replicated_operand, replicated),)
            if has_operand else ()
        )
        operand_tag = ("replicated",) if has_operand else ()

        def _vmap_program():
            def vmapped(*args):
                in_axes = (None,) * len(lead) + (0,) * (len(args) - len(lead))
                return jax.vmap(kernel, in_axes=in_axes)(*args)

            # named as ``jax.vmap(kernel)`` is; with an operand, it arrives
            # committed to ``replicated`` and the stacked inputs to
            # ``sharding``, and jit follows them
            vmapped.__name__ = jax.vmap(kernel).__name__
            placed = {} if lead else {"in_shardings": sharding}
            return jax.jit(vmapped, out_shardings=sharding, **placed)

        # the cache route (shared identity-keyed under a resident server,
        # else this executor's instance cache) is resolved once per sweep:
        # the identity freeze walks the kernel's whole closure
        cached_program = self._program_lookup(kernel)

        kept = None
        if use_sharded and shared_program_cache() is None:
            identity = kernel_identity(kernel)
            if identity is not None:
                kept = _KeptSweepProgram(kernel, identity, self.mesh, bs,
                                         replicated=has_operand)
        if kept is not None:
            batched_kernel = kept
        elif use_sharded:
            batched_kernel = cached_program(
                ("sharded", bs, dev_key) + operand_tag,
                lambda: batched_shard_map(kernel, self.mesh, bs,
                                          replicated=has_operand),
            )
        else:
            # width is carried by the input shapes, not the wrapper: one
            # cached jit(vmap) serves every batch width of this kernel
            batched_kernel = cached_program(
                ("vmap", dev_key) + operand_tag, _vmap_program
            )
        # the sweep span doubles as the sweep_s clock (docs/OBSERVABILITY.md):
        # trace spans are the one timing source in runtime/ (CT008), and a
        # begin/end pair still measures with the tracer off so the
        # io_metrics counters keep working
        sweep_span = trace_mod.begin(
            "executor.sweep", task=task_name, n_blocks=len(blocks),
            sharded=bool(use_sharded),
        )
        dispatch_stats = {
            "batches": 0, "blocks": 0, "wait_s": 0.0,
            "ragged_batches": 0, "lanes_padded": 0, "pages_in_use": 0,
        }
        stats_lock = threading.Lock()

        def _note_dispatch(n_blocks_dispatched: int, rb=None) -> None:
            with stats_lock:
                dispatch_stats["batches"] += 1
                dispatch_stats["blocks"] += int(n_blocks_dispatched)
                if rb is not None:
                    dispatch_stats["ragged_batches"] += 1
                    dispatch_stats["lanes_padded"] += rb.lanes_padded
                    dispatch_stats["pages_in_use"] += rb.pages_in_use

        # per-block failure bookkeeping (threads: IO pool + dispatch loop)
        failures: Dict[int, Dict[str, Any]] = {}
        fail_lock = threading.Lock()
        quarantined_ids: set = set()
        # blocks whose SHARDED batch failed (device OOM at the dispatch, or
        # hung in the compute stage): they fall back to per-block execution
        # and are attributed "degraded:unsharded" when that resolves them
        sharded_failed_ids: set = set()

        def note_failure(block, site, attempts, error, quarantine,
                         resource=None):
            if quarantine or error is not None:
                # attribution-plane crossing: the failure lands on the
                # timeline next to the latency it caused
                trace_mod.instant(
                    f"fault:{site}", block=int(block.block_id),
                    task=task_name, quarantined=bool(quarantine),
                    resource=resource,
                )
            with fail_lock:
                rec = failures.setdefault(
                    int(block.block_id),
                    {
                        "block_id": int(block.block_id),
                        "sites": {},
                        "error": None,
                        "quarantined": False,
                        "resolved": True,
                    },
                )
                rec["sites"][site] = rec["sites"].get(site, 0) + int(attempts)
                if error is not None:
                    rec["error"] = error
                if resource is not None:
                    # the resource CLASS (oom/enospc), steering the degrade
                    # ladder and counted per class for the post-mortem
                    rec["resource"] = resource
                    rec["sites"][resource] = rec["sites"].get(resource, 0) + 1
                if quarantine:
                    rec["quarantined"] = True
                    rec["resolved"] = False
                    quarantined_ids.add(int(block.block_id))

        def mark_resolved(block, resolution=None):
            if resolution is not None:
                trace_mod.instant(
                    resolution, block=int(block.block_id), task=task_name
                )
            with fail_lock:
                rec = failures.get(int(block.block_id))
                if rec is not None:
                    rec["resolved"] = True
                    if resolution is not None:
                        rec["resolution"] = resolution

        def unsharded_tag(block, resolved_by_fallback):
            """``"degraded:unsharded"`` when the PER-BLOCK path actually
            resolved a block whose sharded batch failed — a late-finishing
            sharded primary that wins its own commit is NOT a fallback, so
            a transient hang must not misreport one."""
            if not use_sharded or not resolved_by_fallback:
                return None
            with fail_lock:
                fell = int(block.block_id) in sharded_failed_ids
            return "degraded:unsharded" if fell else None

        def validate(block, out) -> Optional[str]:
            if check_finite:
                err = check_finite_outputs(block, out)
                if err:
                    return err
            if validate_fn is not None:
                return validate_fn(block, out)
            return None

        # -- hang defense: watchdog + speculative duplicates ----------------
        # in-flight (block, stage) work registers with a watchdog; overdue
        # work is recorded as hung + quarantined, and a duplicate of the
        # block runs through the same compiled kernel — FirstWins arbitrates.
        # ALL dispatches of the compiled kernel share one lock: the program
        # is sharded across every device, and two concurrent executions of a
        # multi-device program deadlock XLA's collective rendezvous (each
        # waits for all participants) — the devices are a serial resource,
        # so serializing dispatch costs nothing and removes the hazard.
        dispatch_lock = threading.Lock()
        speculated: set = set()
        commits = FirstWins()

        # the per-block program: in per_block mode it IS the main program
        # (quarantine re-attempts replicate the block to the batch width
        # through the same compiled kernel); in sharded mode it is the
        # degrade/speculation fallback — one block's share of the batch,
        # a strictly smaller allocation than the sharded program, compiled
        # lazily because a clean sharded sweep never needs it.  Per-lane
        # vmap numerics are width-independent, so recovery through it stays
        # bit-identical to the sharded result (tests/test_sharded.py).
        fallback_state: Dict[str, Any] = {}

        def _per_block_kernel():
            if not use_sharded:
                return batched_kernel, bs
            kern = fallback_state.get("kernel")
            if kern is None:
                kern = cached_program(
                    ("vmap", dev_key) + operand_tag, _vmap_program
                )
                fallback_state["kernel"] = kern
            return kern, bs0

        def _exec_single(val):
            """One block through the per-block program; returns its output
            tree as numpy arrays."""
            kern, width = _per_block_kernel()
            stacked = tuple(np.stack([x] * width) for x in val)
            device_pool_mod.record_h2d(sum(int(a.nbytes) for a in stacked))
            stacked = tuple(jax.device_put(a, sharding) for a in stacked)
            # span starts AFTER the lock is held — same grain semantics as
            # the sharded path, so executor.dispatch never bills another
            # dispatch's lock wait regardless of which path emitted it
            with dispatch_lock:
                with trace_mod.span("executor.dispatch", n_blocks=1,
                                    task=task_name, grain="per_block"):
                    out = kern(*lead, *stacked)
            _note_dispatch(1)
            out_np = jax.tree_util.tree_map(lambda a: np.asarray(a)[0], out)
            device_pool_mod.record_d2h(sum(
                int(a.nbytes) for a in jax.tree_util.tree_leaves(out_np)
            ))
            return out_np

        # one degraded:host_staged record per sweep (the counter still
        # ticks per fallen-back batch): pool exhaustion is a sweep-level
        # condition, not a per-block fault
        device_fallback = {"recorded": False}

        def _stage_ragged_inputs(rb, block_id):
            """Device inputs + compiled program for one ragged batch.
            With the resident pool on, pages already in HBM are re-
            addressed instead of re-uploaded (the device-resident data
            plane); pool exhaustion — after its internal evict+retry rung
            — falls THIS batch back to per-batch host staging, attributed
            ``degraded:host_staged``.  Bit-identical either way: the same
            page bytes reach the same descriptor-driven program."""
            if dev_pool is not None:
                try:
                    sb = dev_pool.stage(
                        rb, dev_key, replicated, block_id=block_id
                    )
                except device_pool_mod.DevicePoolExhausted as e:
                    device_pool_mod.bump("host_staged_fallbacks")
                    trace_mod.instant(
                        "degraded:host_staged", task=task_name,
                        block=int(block_id),
                    )
                    if not device_fallback["recorded"] and failures_path:
                        device_fallback["recorded"] = True
                        try:
                            fu.record_failures(
                                failures_path,
                                f"{task_name}.device_pool",
                                [{
                                    "block_id": None,
                                    "sites": {"h2d": 1},
                                    "error": fu.cap_traceback(str(e)),
                                    "quarantined": False,
                                    "resolved": True,
                                    "resolution": "degraded:host_staged",
                                }],
                            )
                        except Exception:
                            pass
                else:
                    rep, shd = sb.flat_inputs()
                    # the pools are already resident; only the (tiny)
                    # remapped tables + valid extents cross the host bus
                    device_pool_mod.record_h2d(
                        sum(int(a.nbytes) for a in shd)
                    )
                    dev_inputs = lead + tuple(rep) + tuple(
                        jax.device_put(a, sharding) for a in shd
                    )
                    prog = cached_program(
                        ("ragged", dev_key) + sb.key() + operand_tag,
                        lambda sb=sb: ragged_shard_map(
                            kernel, self.mesh, sb.width, sb.specs,
                            replicated=has_operand,
                        ),
                    )
                    return dev_inputs, prog
            # host staging: the per-batch device_put of pools + tables
            # (the pre-pool path, and the ladder's fallback rung) — a
            # REAL h2d transfer, visible on the timeline
            rep, shd = rb.flat_inputs()
            with trace_mod.span(
                "executor.h2d", task=task_name, nbytes=int(rb.nbytes),
                grain="ragged",
            ):
                dev_inputs = lead + tuple(
                    jax.device_put(a, replicated) for a in rep
                ) + tuple(
                    jax.device_put(a, sharding) for a in shd
                )
            device_pool_mod.record_h2d(rb.nbytes)
            prog = cached_program(
                ("ragged", dev_key) + rb.key() + operand_tag,
                lambda rb=rb: ragged_shard_map(
                    kernel, self.mesh, rb.width, rb.specs,
                    replicated=has_operand,
                ),
            )
            return dev_inputs, prog

        spec_pool: Optional[ThreadPoolExecutor] = None
        spec_futures: List[Future] = []
        watchdog: Optional[Watchdog] = None
        _tokens = itertools.count()

        @contextlib.contextmanager
        def _watched(block, stage, origin="primary"):
            if watchdog is None:
                yield
                return
            token = next(_tokens)
            watchdog.register(
                token, block_id=int(block.block_id), stage=stage, origin=origin
            )
            try:
                yield
            finally:
                watchdog.clear(token)

        class _PreIssueFailed(Exception):
            pass

        def load_block(block, pre=None, pre_tb=None, pre_resource=None,
                       origin="primary"):
            """Load one block with retries; returns arrays or None
            (quarantined).  ``pre`` is an already-issued load_fn result
            consumed by the first attempt (batch reads are issued together
            so the storage layer runs the chunk IO concurrently).  Resource-
            classified failures (OOM/ENOSPC) skip the same-size retries and
            quarantine straight into the degrade ladder."""
            last_tb, attempts = None, 0
            voxels = int(np.prod(block.outer_shape))
            with contextlib.ExitStack() as stack:
                stack.enter_context(_watched(block, "load", origin))
                stack.enter_context(
                    faults_mod.block_context(int(block.block_id))
                )
                # per-block load span covers the whole retry ladder: the
                # latency an operator chases is time-to-loaded, not
                # per-attempt time.  task passed explicitly: hot-path spans
                # must not pay the thread-local context lookup per block
                stack.enter_context(trace_mod.span(
                    "executor.load", block=int(block.block_id),
                    origin=origin, task=task_name,
                ))
                for k in range(self.max_retries + 1):
                    attempts = k + 1
                    try:
                        injector.maybe_fail(
                            "load", block.block_id, voxels=voxels
                        )
                        injector.maybe_hang("load", block.block_id)
                        if k == 0 and pre_tb is not None:
                            last_tb = pre_tb
                            raise _PreIssueFailed()
                        per = pre if (k == 0 and pre is not None) else load_fn(block)
                        val = tuple(
                            x.result() if hasattr(x, "result") else x for x in per
                        )
                    except _PreIssueFailed:
                        if pre_resource is not None:
                            note_failure(
                                block, "load", attempts, last_tb,
                                quarantine=True, resource=pre_resource,
                            )
                            return None
                        if k < self.max_retries:
                            time.sleep(self._backoff(k))
                    except Exception as e:
                        last_tb = fu.cap_traceback(traceback.format_exc())
                        resource = classify_resource_error(e)
                        if resource is not None:
                            note_failure(
                                block, "load", attempts, last_tb,
                                quarantine=True, resource=resource,
                            )
                            return None
                        if k < self.max_retries:
                            time.sleep(self._backoff(k))
                    else:
                        if attempts > 1:
                            note_failure(block, "load", attempts - 1, None, False)
                        return val
            note_failure(block, "load", attempts, last_tb, quarantine=True)
            return None

        # service mode (docs/SERVING.md): store_fn may publish block-grain
        # artifact handoffs, and those identities are namespaced by the
        # thread-local request context — capture it on the sweep's thread
        # and re-enter it on every pool-submitted worker (loads, stores,
        # speculative re-runs), or a resident server's concurrent requests
        # over the same paths could resolve each other's intermediates
        _req_ctx = admission_mod.current_request()

        def _scoped(fn):
            def run(*a, **kw):
                with admission_mod.request_scope(_req_ctx):
                    return fn(*a, **kw)
            return run

        def load_batch(batch_idx: int):
            """Load one batch; returns ``(blocks, kind, payload)`` where
            ``kind`` routes the dispatch: ``"dense"`` (stacked arrays for
            the uniform-shape program), ``"ragged"`` (a packed
            :class:`~cluster_tools_tpu.parallel.block_pool.RaggedBatch`),
            ``"mixed"`` (per-lane values the pool could not pack — the
            per-block program owns them), or ``"empty"``."""
            batch = blocks[batch_idx * bs : (batch_idx + 1) * bs]
            # load_fn may return futures (e.g. io.prefetch.async_loader's
            # tensorstore read futures): issue EVERY read of the batch first,
            # then resolve — the storage layer runs the chunk IO concurrently
            issued = []
            for b in batch:
                try:
                    with faults_mod.block_context(int(b.block_id)):
                        issued.append((load_fn(b), None, None))
                except Exception as e:
                    issued.append(
                        (None, fu.cap_traceback(traceback.format_exc()),
                         classify_resource_error(e))
                    )
            ok_blocks, per_block = [], []
            for b, (pre, pre_tb, pre_res) in zip(batch, issued):
                val = load_block(b, pre=pre, pre_tb=pre_tb, pre_resource=pre_res)
                if val is None:
                    continue
                # kernel-dispatch fault hook (resource model: this block's
                # share of the batch does not fit): an injected compute
                # OOM routes the block to the degrade ladder pre-dispatch,
                # keeping the rest of the batch intact
                try:
                    injector.maybe_fail(
                        "compute", b.block_id,
                        voxels=int(np.prod(b.outer_shape)),
                    )
                except Exception as e:
                    note_failure(
                        b, "compute", 1,
                        fu.cap_traceback(traceback.format_exc()),
                        quarantine=True,
                        resource=classify_resource_error(e),
                    )
                    continue
                ok_blocks.append(b)
                per_block.append(val)
            if not ok_blocks:
                return [], "empty", None
            vals = [tuple(np.asarray(x) for x in val) for val in per_block]
            n_args = len(vals[0])
            uniform = all(
                len({v[i].shape for v in vals}) == 1 for i in range(n_args)
            )
            full = len(vals) == bs
            if use_ragged and (not uniform or not full or ragged == "on"):
                # mixed-shape lanes, a partial batch (ragged tail or
                # quarantine holes), or a forced ragged sweep: pack through
                # the paged block pool — one descriptor-driven program
                # instead of the per-block fallback; padding lanes are
                # synthesized by the pool and discarded on d2h
                try:
                    return ok_blocks, "ragged", ragged_pool.pack(
                        vals, bs, page_shape=page_shape
                    )
                except ValueError:
                    if uniform:
                        # uniform lanes the pool refuses (exotic dtypes):
                        # the dense repeat-pad path below handles them
                        # exactly as before the pool existed
                        pass
                    else:
                        # mixed-shape lanes that cannot pack: per-block
                        # execution owns them
                        return ok_blocks, "mixed", vals
            if not uniform:
                # ragged="off" (or per_block mode): mixed shapes cannot
                # stack — the per-block program owns them
                return ok_blocks, "mixed", vals
            # pad the partial batch (tail, or quarantine-induced holes) by
            # repeating the last block so the compiled shape stays static;
            # padded outputs are dropped
            n_pad = bs - len(vals)
            if n_pad:
                vals = vals + [vals[-1]] * n_pad
            arrays = tuple(
                np.stack([pb[i] for pb in vals]) for i in range(n_args)
            )
            return ok_blocks, "dense", arrays

        finished_ids: set = set()

        def _register_lineage(blk):
            """Self-healing lineage (docs/SERVING.md, runtime/repair.py):
            after a verified store, record how to recompute THIS block —
            re-load the producing inputs, re-run the per-block program,
            re-store through the ordinary sidecar-recording write path —
            keyed by the product region the verifier just checked.  Best
            effort: lineage must never fail a completed block."""
            ds = getattr(store_verify_fn, "dataset", None) \
                if store_verify_fn is not None else None
            if ds is None or store_fn is None:
                return
            bb_of = getattr(store_verify_fn, "bb_of", None) \
                or (lambda b: b.bb)

            def recompute(b=blk):
                with faults_mod.block_context(int(b.block_id)):
                    # async loaders return futures; resolve them exactly
                    # like load_block does before the kernel sees them
                    val = tuple(
                        x.result() if hasattr(x, "result") else x
                        for x in load_fn(b)
                    )
                    out = _exec_single(val)
                    err = validate(b, out)
                    if err is not None:
                        raise RuntimeError(
                            f"lineage recompute of block {b.block_id} "
                            f"failed validation: {err}"
                        )
                    store_fn(b, out)

            try:
                from . import repair as repair_mod

                repair_mod.register_producer(
                    ds, bb_of(blk), recompute, task=task_name,
                    block_id=int(blk.block_id),
                    failures_path=failures_path,
                )
            except Exception:
                pass

        def finish_block(blk):
            """Completion side effects (success marker + block_done kill
            point) at most ONCE per block — with speculation, two copies of
            a block can both reach a happy end (uncontended-looking winner
            plus a later-agreeing duplicate) and must not double-fire."""
            with fail_lock:
                if int(blk.block_id) in finished_ids:
                    return
                finished_ids.add(int(blk.block_id))
            _register_lineage(blk)
            if on_block_done is not None:
                on_block_done(blk)
            injector.kill_point("block_done")

        def handle_block_output(blk, block_out, origin="primary"):
            """Corrupt-injection, validation, duplicate arbitration, store
            (with retries + integrity verify), marker.  Never raises —
            failures (including programming errors in the validate/marker
            hooks) quarantine the block, keeping every error attributed to
            its block id."""
            bid = int(blk.block_id)
            try:
                block_out = injector.corrupt("kernel", blk.block_id, block_out)
                err = validate(blk, block_out)
                if err is not None:
                    note_failure(blk, "validate", 1, err, quarantine=True)
                    return
                if store_fn is not None:
                    corrupt_seen = [0]
                    dup_state = {"verdict": None, "digest": None,
                                 "contended": False}

                    def _classify(exc):
                        if isinstance(exc, ChunkCorruptionError):
                            corrupt_seen[0] += 1

                    def _store_and_verify():
                        # first-wins gate, decided at the LAST moment before
                        # the write: this copy may have been declared hung
                        # and overtaken by a speculative duplicate while it
                        # was stuck on the way here.  With the watchdog
                        # armed EVERY copy registers its digest — a
                        # duplicate spawned after an uncontended-looking
                        # primary passed this point must still find the
                        # claim.  Decided once; store retries reuse it.
                        if dup_state["verdict"] is None:
                            if watchdog is not None:
                                with fail_lock:
                                    dup_state["contended"] = bid in speculated
                                dup_state["digest"] = array_digest(
                                    jax.tree_util.tree_leaves(block_out)
                                )
                                dup_state["verdict"] = commits.commit(
                                    bid, dup_state["digest"]
                                )
                            else:
                                dup_state["verdict"] = FirstWins.WIN
                        if dup_state["verdict"] != FirstWins.WIN:
                            return  # arbitrated below, nothing to store
                        store_fn(blk, block_out)
                        if store_verify_fn is not None:
                            store_verify_fn(blk)

                    with contextlib.ExitStack() as stack:
                        stack.enter_context(_watched(blk, "store", origin))
                        stack.enter_context(faults_mod.block_context(bid))
                        stack.enter_context(trace_mod.span(
                            "executor.store", block=bid, origin=origin,
                            task=task_name,
                        ))
                        _, attempts, tb, store_resource = self._io_with_retries(
                            "store", blk, _store_and_verify, on_error=_classify
                        )
                    if dup_state["verdict"] == FirstWins.AGREE:
                        # this copy confirms the stored winner bit-for-bit:
                        # resolved without a second store (also the
                        # arbitration path after a mismatch — a third copy
                        # agreeing with the winner validates it).  A
                        # contended winner deferred the completion side
                        # effects to this settling point; finish_block
                        # de-duplicates against a winner that already ran
                        # them (it looked uncontended when it decided).
                        # The stored winner is the OTHER copy: when this
                        # agreeing copy is the primary, a speculative
                        # per-block duplicate won — that is the sharded ->
                        # per-block fallback, attributed as such.
                        mark_resolved(
                            blk, unsharded_tag(blk, origin == "primary")
                        )
                        with fail_lock:
                            rec = failures.get(bid)
                            if rec is not None:
                                rec["duplicate"] = "agreed"
                        finish_block(blk)
                        return
                    if dup_state["verdict"] == FirstWins.MISMATCH:
                        note_failure(
                            blk, "determinism", 1,
                            "speculative duplicate disagreed with the first "
                            "result (nondeterministic kernel or corrupted "
                            "data); block left unresolved for recompute",
                            quarantine=True,
                        )
                        return
                    if corrupt_seen[0]:
                        # attribute the fault class: the store "failures"
                        # were chunk corruption caught by the digest verify
                        note_failure(
                            blk, "corrupt", corrupt_seen[0], None,
                            quarantine=False,
                        )
                    if tb is not None:
                        if dup_state["digest"] is not None:
                            # the WIN claim's store never landed: release it
                            # so the quarantine recompute is not misread as
                            # a duplicate of a result that does not exist
                            commits.withdraw(bid, dup_state["digest"])
                        note_failure(blk, "store", attempts, tb,
                                     quarantine=True, resource=store_resource)
                        return
                    if attempts > 1:
                        note_failure(
                            blk, "store", attempts - 1, None, quarantine=False
                        )
                    # this copy stored the result: only a SPECULATIVE win
                    # came through the per-block fallback program
                    mark_resolved(
                        blk, unsharded_tag(blk, origin == "speculative")
                    )
                    if not dup_state["contended"]:
                        # a contended winner defers the success marker to the
                        # duplicate's AGREE above: a mismatch must not leave
                        # a marker a resumed run would trust (if the other
                        # copy dies instead, the unmarked block is merely
                        # recomputed on resume — safe)
                        finish_block(blk)
                else:
                    mark_resolved(blk)
                    finish_block(blk)
            except Exception:
                # site "hook", not "store": the store path itself retries
                # and records above — only validate_fn/on_block_done/corrupt
                # programming errors land here
                note_failure(
                    blk,
                    "hook",
                    1,
                    fu.cap_traceback(traceback.format_exc()),
                    quarantine=True,
                )
                return

        def speculative_rerun(blk):
            """Duplicate execution of a hung block: fresh load, the
            per-block program (the same compiled kernel in per_block mode;
            the per-block fallback twin in sharded mode), and a first-wins
            commit against the (possibly still stuck) original."""
            try:
                with trace_mod.span(
                    "executor.speculate", block=int(blk.block_id),
                    task=task_name,
                ):
                    val = load_block(blk, origin="speculative")
                    if val is None:
                        return
                    out0 = _exec_single(val)
                    handle_block_output(blk, out0, origin="speculative")
            except Exception:
                note_failure(
                    blk, "speculate", 1,
                    fu.cap_traceback(traceback.format_exc()),
                    quarantine=False,
                )

        if deadline > 0:
            spec_pool = ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="speculate"
            )

            def _on_hung(token, info, elapsed):
                bid = int(info["block_id"])
                blk = block_by_id[bid]
                note_failure(
                    blk, "hung", 1,
                    f"block exceeded block_deadline_s={deadline:g}s in "
                    f"stage {info['stage']} ({elapsed:.2f}s elapsed)",
                    quarantine=True,
                )
                if not speculate or info.get("origin") != "primary":
                    return
                with fail_lock:
                    if use_sharded and info.get("stage") == "compute":
                        # a hung device stalls the whole sharded program:
                        # this block's recovery is a sharded -> per-block
                        # fallback, attributed degraded:unsharded
                        sharded_failed_ids.add(bid)
                    if bid in speculated:
                        return
                    speculated.add(bid)
                spec_futures.append(spec_pool.submit(_scoped(speculative_rerun), blk))

            watchdog = Watchdog(
                deadline,
                watchdog_period_s or max(0.02, deadline / 4.0),
                _on_hung,
            ).start()

        # -- byte-budget admission control + headroom backpressure ----------
        # in-flight = loaded-but-not-yet-stored batch bytes; the budget caps
        # it (default: a quarter of MemAvailable at sweep start), and low
        # host-memory / manifest-filesystem headroom drains the pending
        # store window before the next batch is admitted.
        if inflight_byte_budget is None:
            avail = host_mem_available_bytes()
            budget = int(avail * 0.25) if avail else 0
            # tenant-tagged budgets (docs/SERVING.md): under a service-mode
            # request context, the auto budget is additionally capped at
            # the running request's share of its tenant's byte quota — one
            # tenant's sweep cannot claim the whole host envelope away
            # from its neighbors.  An explicit inflight_byte_budget (the
            # operator's word) is never overridden.
            tenant_cap = admission_mod.ambient_byte_cap()
            if tenant_cap:
                budget = min(budget, int(tenant_cap)) if budget \
                    else int(tenant_cap)
            if budget and chunk_cache_mod.cache_enabled():
                # the decompressed-chunk cache is co-resident host memory:
                # subtract its byte budget from the same headroom probe so
                # cache + in-flight batches together stay inside the
                # 25%-of-MemAvailable envelope (floored at a quarter of the
                # probe so tiny hosts keep making progress)
                budget = max(
                    budget - chunk_cache_mod.get_chunk_cache().max_bytes,
                    budget // 4,
                )
            live_handoff = handoff_mod.live_bytes()
            if budget and live_handoff:
                # in-memory handoff targets (docs/PERFORMANCE.md
                # "Task-graph fusion") are co-resident too — same envelope,
                # same floor
                budget = max(budget - live_handoff, budget // 4)
        else:
            budget = int(inflight_byte_budget)
        inflight = {"bytes": 0}
        admission_lock = threading.Lock()
        backpressure = {"waits": 0}
        headroom_path = (
            os.path.dirname(os.path.abspath(failures_path))
            if failures_path else None
        )
        drained = False

        def _release_inflight(nbytes):
            with admission_lock:
                inflight["bytes"] -= nbytes

        def _admit(nbytes, write_futures):
            """Admission gate for one loaded batch: drain pending stores
            until the byte budget fits (the current batch is always
            admitted — progress beats the cap) and while memory/disk
            headroom is below threshold.  Low host memory additionally
            flushes completed in-memory handoff targets to their storage
            spill paths (docs/PERFORMANCE.md "Task-graph fusion") — the
            degrade ladder prefers releasing recoverable resident bytes
            over stalling the sweep."""
            waited = False
            mem = host_mem_available_fraction()
            if mem is not None and mem < mem_headroom_fraction:
                # BEFORE the pending-store drain (which may be empty —
                # in-memory sinks complete their stores immediately):
                # completed handoffs are safe to flush (storage becomes
                # the source of truth; consumers fall back transparently)
                # and free real headroom
                handoff_mod.spill_for_headroom()
            while write_futures:
                with admission_lock:
                    over = budget and inflight["bytes"] + nbytes > budget
                mem = host_mem_available_fraction()
                low_mem = mem is not None and mem < mem_headroom_fraction
                disk = (
                    disk_free_fraction(headroom_path) if headroom_path else None
                )
                low_disk = disk is not None and disk < disk_headroom_fraction
                if not (over or low_mem or low_disk):
                    break
                waited = True
                write_futures.pop(0).result()
            if waited:
                backpressure["waits"] += 1
            with admission_lock:
                inflight["bytes"] += nbytes

        try:
            with ThreadPoolExecutor(max_workers=self.io_threads) as pool:
                pending_loads: List[Future] = [
                    pool.submit(_scoped(load_batch), i)
                    for i in range(min(prefetch, n_batches))
                ]
                write_futures: List[Future] = []
                for i in range(n_batches):
                    if drain_requested():
                        # stop claiming batches; in-flight loads/stores are
                        # finished below, markers+manifests flushed, and the
                        # sweep exits through DrainInterrupt for a requeue
                        drained = True
                        break
                    # the wait span doubles as the wait_s clock: the IO the
                    # double-buffering failed to hide, and (traced) the gap
                    # Perfetto shows between consecutive dispatch spans.
                    # Sub-100us waits are measured (the counter needs them)
                    # but not recorded — a fully-overlapped sweep must not
                    # pay one timeline event per batch for a non-stall
                    wait_span = trace_mod.begin(
                        "executor.batch_wait", task=task_name, batch=i
                    )
                    batch, kind, payload = pending_loads.pop(0).result()
                    waited = wait_span.end(discard=True)
                    if waited > 1e-4:
                        wait_span.end()
                    with stats_lock:
                        dispatch_stats["wait_s"] += waited
                    if i + prefetch < n_batches:
                        pending_loads.append(
                            pool.submit(_scoped(load_batch), i + prefetch)
                        )
                    # prompt drain: surface finished stores (and any programming
                    # error in the store path, with its batch's block ids) now,
                    # not at the end of the run
                    while write_futures and write_futures[0].done():
                        write_futures.pop(0).result()
                    if not batch:
                        continue  # every block of this batch was quarantined
                    if kind == "mixed":
                        # lanes neither the dense nor the ragged program can
                        # take (pool off or unpackable): the per-block
                        # program owns them — on the sharded path that is a
                        # degrade, attributed like every other fallback
                        mixed_bytes = sum(
                            int(x.nbytes) for val in payload for x in val
                        )
                        _admit(mixed_bytes, write_futures)

                        def run_mixed(batch=batch, vals=payload,
                                      nbytes=mixed_bytes):
                            try:
                                for blk, val in zip(batch, vals):
                                    bid = int(blk.block_id)
                                    if use_sharded:
                                        note_failure(
                                            blk, "pack", 1,
                                            "mixed-shape lanes with the "
                                            "ragged pool unavailable; "
                                            "executed per-block",
                                            quarantine=True,
                                        )
                                        with fail_lock:
                                            sharded_failed_ids.add(bid)
                                    try:
                                        out0 = _exec_single(val)
                                    except Exception:
                                        note_failure(
                                            blk, "compute", 1,
                                            fu.cap_traceback(
                                                traceback.format_exc()
                                            ),
                                            quarantine=True,
                                        )
                                        continue
                                    handle_block_output(blk, out0)
                                    if use_sharded:
                                        with fail_lock:
                                            rec = failures.get(bid)
                                            done = bool(
                                                rec and rec["resolved"]
                                            )
                                        if done:
                                            mark_resolved(
                                                blk, "degraded:unsharded"
                                            )
                            finally:
                                _release_inflight(nbytes)

                        write_futures.append(
                            pool.submit(_scoped(run_mixed))
                        )
                        while len(write_futures) > 2:
                            write_futures.pop(0).result()
                        continue
                    rb = payload if kind == "ragged" else None
                    if rb is not None:
                        batch_bytes = rb.nbytes
                    else:
                        arrays = payload
                        batch_bytes = sum(int(a.nbytes) for a in arrays)
                    _admit(batch_bytes, write_futures)
                    if rb is not None:
                        dev_inputs, prog = _stage_ragged_inputs(
                            rb, batch[0].block_id
                        )
                    else:
                        with trace_mod.span(
                            "executor.h2d", task=task_name,
                            nbytes=int(batch_bytes), grain="dense",
                        ):
                            dev_inputs = lead + tuple(
                                jax.device_put(a, sharding) for a in arrays
                            )
                        device_pool_mod.record_h2d(batch_bytes)
                        prog = batched_kernel
                    try:
                        if use_sharded:
                            # batch-grain fault surface: a device OOM or a
                            # wedged device takes down the whole sharded
                            # program, not one block — the 'dispatch' site
                            # models it (registered as compute so the
                            # watchdog's hung-batch detection covers it)
                            with contextlib.ExitStack() as stack:
                                for blk in batch:
                                    stack.enter_context(
                                        _watched(blk, "compute")
                                    )
                                batch_voxels = sum(
                                    int(np.prod(b.outer_shape))
                                    for b in batch
                                )
                                injector.maybe_fail(
                                    "dispatch", batch[0].block_id,
                                    voxels=batch_voxels,
                                )
                                injector.maybe_hang(
                                    "dispatch", batch[0].block_id
                                )
                        # take the dispatch lock BEFORE starting the blocks'
                        # compute clocks: waiting behind a (possibly cold-
                        # compiling) speculative dispatch is not this batch's
                        # wall time, and must not cascade into false hangs
                        with dispatch_lock, contextlib.ExitStack() as stack:
                            span_args = dict(
                                task=task_name, n_blocks=len(batch),
                                grain=(
                                    "ragged" if rb is not None
                                    else "sharded" if use_sharded
                                    else "batch"
                                ),
                            )
                            if rb is not None:
                                # ragged-lane attribution on the timeline:
                                # how much of the dispatch was padding
                                span_args["lanes_padded"] = rb.lanes_padded
                            stack.enter_context(trace_mod.span(
                                "executor.dispatch", **span_args
                            ))
                            for blk in batch:
                                stack.enter_context(_watched(blk, "compute"))
                            out = prog(*dev_inputs)
                        _note_dispatch(len(batch), rb)
                    except Exception as e:
                        # a compute failure poisons the whole batch; quarantine
                        # all of it — the reduced-batch pass isolates the
                        # culprit, and a resource-classified failure (device
                        # OOM) steers every member into the degrade ladder.
                        # In sharded mode the batch falls back to per-block
                        # execution (site 'dispatch', degraded:unsharded).
                        tb = fu.cap_traceback(traceback.format_exc())
                        resource = classify_resource_error(e)
                        site = "dispatch" if use_sharded else "compute"
                        for blk in batch:
                            note_failure(blk, site, 1, tb,
                                         quarantine=True, resource=resource)
                        if use_sharded:
                            with fail_lock:
                                sharded_failed_ids.update(
                                    int(b.block_id) for b in batch
                                )
                        _release_inflight(batch_bytes)
                        continue

                    def store_batch(batch=batch, out=out, nbytes=batch_bytes,
                                    rb=rb):
                        # the device->host copy happens HERE, on the IO pool, so
                        # the dispatch loop is free to enqueue the next batch
                        # while this one's outputs stream back.  This copy is
                        # also where a kernel wedged at RUNTIME blocks (the
                        # jitted call above returns at dispatch — async), so
                        # it is the stage the compute watchdog must cover.
                        try:
                            with contextlib.ExitStack() as stack:
                                # this copy is where a wedged kernel blocks
                                # (dispatch is async): the span is the
                                # timeline's true per-batch compute extent
                                stack.enter_context(trace_mod.span(
                                    "executor.d2h", task=task_name,
                                    n_blocks=len(batch),
                                ))
                                for blk in batch:
                                    stack.enter_context(_watched(blk, "compute"))
                                out_np = jax.tree_util.tree_map(np.asarray, out)
                            device_pool_mod.record_d2h(sum(
                                int(a.nbytes)
                                for a in jax.tree_util.tree_leaves(out_np)
                            ))
                            if rb is not None:
                                # the execution is complete once the copy
                                # above lands: the pool's host buffers are
                                # safe to recycle for later batches
                                rb.release()
                            for j, blk in enumerate(batch):
                                block_out = jax.tree_util.tree_map(
                                    lambda a: (
                                        a[j] if rb is None
                                        # ragged lane: crop the page-aligned
                                        # output back to the lane's valid
                                        # extent (padding lanes never reach
                                        # here — only real blocks iterate)
                                        else rb.crop(j, a[j])
                                    ),
                                    out_np,
                                )
                                handle_block_output(blk, block_out)
                        finally:
                            _release_inflight(nbytes)

                    write_futures.append(pool.submit(_scoped(store_batch)))
                    # backpressure: each pending store closure pins its batch's
                    # DEVICE output buffers until its d2h copy runs, so the bound
                    # must be a small constant (not thread-count) or HBM fills
                    # with undrained outputs
                    while len(write_futures) > 2:
                        write_futures.pop(0).result()
                for f in write_futures:
                    f.result()

                # settle speculative duplicates before judging what is still
                # unresolved (the list can grow while we drain: a primary still
                # stuck past its deadline fires the watchdog mid-drain)
                i_spec = 0
                while i_spec < len(spec_futures):
                    spec_futures[i_spec].result()
                    i_spec += 1
                if watchdog is not None:
                    watchdog.stop()
                if spec_pool is not None:
                    spec_pool.shutdown(wait=True)

                # -- degrade ladder: headroom wait + split machinery ------------

                def _wait_for_headroom(resource):
                    """Bounded backpressure before a degrade re-attempt:
                    transient exhaustion (a sibling job's spike, a filling
                    scratch disk being cleaned) often clears within
                    seconds; a healthy (or unmeasurable) host returns
                    immediately."""
                    deadline_t = time.monotonic() + max(0.0, degrade_wait_s)
                    while time.monotonic() < deadline_t:
                        if resource == "enospc":
                            frac = (
                                disk_free_fraction(headroom_path)
                                if headroom_path else None
                            )
                            if frac is None or frac > disk_headroom_fraction:
                                return
                        else:
                            frac = host_mem_available_fraction()
                            if frac is None or frac > mem_headroom_fraction:
                                return
                        time.sleep(min(0.2, max(0.01, degrade_wait_s / 20.0)))

                # the SAME kernel function, unbatched + jitted: jit caches
                # one compiled twin per distinct sub-block shape, each a
                # smaller allocation than the batch program — the point
                sub_jit = cached_program(("sub",), lambda: jax.jit(kernel))

                def _sub_exec(val):
                    device_pool_mod.record_h2d(
                        sum(int(np.asarray(x).nbytes) for x in val)
                    )
                    with dispatch_lock:
                        out = sub_jit(*lead, *val)
                    _note_dispatch(1)
                    out_np = jax.tree_util.tree_map(np.asarray, out)
                    device_pool_mod.record_d2h(sum(
                        int(a.nbytes)
                        for a in jax.tree_util.tree_leaves(out_np)
                    ))
                    return out_np

                split_stats = {"splits": 0, "max_depth": 0, "sub_blocks": 0}

                def _load_sub(sub):
                    """Load one sub-block with retries.  Returns
                    ``("ok", val)``, ``("recurse", None)`` (a resource
                    failure: the caller splits one level deeper), or
                    ``("fail", None)`` (attributed, permanently failed)."""
                    voxels = int(np.prod(sub.outer_shape))
                    val, last_tb = None, None
                    for k in range(self.max_retries + 1):
                        try:
                            injector.maybe_fail(
                                "load", sub.block_id, voxels=voxels
                            )
                            injector.maybe_hang("load", sub.block_id)
                            per = load_fn(sub)
                            val = tuple(
                                x.result() if hasattr(x, "result") else x
                                for x in per
                            )
                            break
                        except Exception as e:
                            last_tb = fu.cap_traceback(traceback.format_exc())
                            if classify_resource_error(e) is not None:
                                return "recurse", None
                            if k < self.max_retries:
                                time.sleep(self._backoff(k))
                    if val is None:
                        note_failure(sub, "load", 1, last_tb, quarantine=True)
                        return "fail", None
                    return "ok", val

                def _store_sub(sub, out, depth, tracker):
                    """Validate + store (+ integrity verify) one sub-block's
                    output with retries; a resource failure waits for
                    headroom and recurses one level deeper."""
                    voxels = int(np.prod(sub.outer_shape))
                    err = validate(sub, out)
                    if err is not None:
                        note_failure(sub, "validate", 1, err, quarantine=True)
                        return False
                    if store_fn is None:
                        return True

                    def _store():
                        store_fn(sub, out)
                        if store_verify_fn is not None:
                            store_verify_fn(sub)

                    last_tb = None
                    for k in range(self.max_retries + 1):
                        try:
                            injector.maybe_fail(
                                "store", sub.block_id, voxels=voxels
                            )
                            injector.maybe_hang("store", sub.block_id)
                            _store()
                            return True
                        except Exception as e:
                            last_tb = fu.cap_traceback(traceback.format_exc())
                            resource = classify_resource_error(e)
                            if resource is not None:
                                _wait_for_headroom(resource)
                                return _split_and_run(sub, depth + 1,
                                                      tracker)
                            if k < self.max_retries:
                                time.sleep(self._backoff(k))
                    note_failure(sub, "store", 1, last_tb, quarantine=True)
                    return False

                def _run_sub(sub, depth, tracker, val=None):
                    """One sub-block through load -> kernel -> validate ->
                    store(+verify); a resource failure at any stage recurses
                    one level deeper.  Failures are attributed to the parent
                    block id (sub-blocks carry it).  ``val`` skips the load
                    when the caller already holds the arrays (the ragged
                    sub path falling back after a failed dispatch must not
                    re-read storage — or burn load-fault attempts)."""
                    voxels = int(np.prod(sub.outer_shape))
                    with faults_mod.block_context(int(sub.block_id)):
                        if val is None:
                            status, val = _load_sub(sub)
                            if status == "recurse":
                                return _split_and_run(sub, depth + 1,
                                                      tracker)
                            if status == "fail":
                                return False
                        # compute at the sub shape
                        try:
                            injector.maybe_fail(
                                "compute", sub.block_id, voxels=voxels
                            )
                            out = _sub_exec(val)
                        except Exception as e:
                            tb = fu.cap_traceback(traceback.format_exc())
                            if classify_resource_error(e) is not None:
                                return _split_and_run(sub, depth + 1,
                                                      tracker)
                            note_failure(sub, "compute", 1, tb, quarantine=True)
                            return False
                        return _store_sub(sub, out, depth, tracker)

                def _run_subs_ragged(subs, depth, tracker):
                    """All sub-blocks of one split parent through the paged
                    block pool: mixed sub-shapes pack into ragged batches
                    and execute as ONE program per batch instead of one
                    ``jit`` dispatch per sub-block (docs/PERFORMANCE.md
                    "Ragged sweeps") — the split ladder's semantics are
                    unchanged: per-lane resource failures recurse deeper,
                    and a failed ragged dispatch falls the chunk back to
                    the per-sub path (the same program the unsplit
                    quarantine pass uses)."""
                    ok = True
                    ready = []
                    for sub in subs:
                        with faults_mod.block_context(int(sub.block_id)):
                            status, val = _load_sub(sub)
                            if status == "recurse":
                                ok &= _split_and_run(sub, depth + 1, tracker)
                                continue
                            if status == "fail":
                                ok = False
                                continue
                            try:
                                injector.maybe_fail(
                                    "compute", sub.block_id,
                                    voxels=int(np.prod(sub.outer_shape)),
                                )
                            except Exception as e:
                                tb = fu.cap_traceback(traceback.format_exc())
                                if classify_resource_error(e) is not None:
                                    ok &= _split_and_run(sub, depth + 1,
                                                         tracker)
                                    continue
                                note_failure(sub, "compute", 1, tb,
                                             quarantine=True)
                                ok = False
                                continue
                            ready.append((sub, tuple(
                                np.asarray(x) for x in val
                            )))
                    for start in range(0, len(ready), bs):
                        chunk = ready[start:start + bs]
                        width = min(
                            bs,
                            -(-len(chunk) // self.n_devices) * self.n_devices,
                        )
                        try:
                            rb = ragged_pool.pack(
                                [val for _, val in chunk], width,
                                page_shape=page_shape,
                            )
                            # split sub-blocks stage through the resident
                            # pool too (half-size pages of a split parent
                            # are fresh content, but the fill page and
                            # repeated retries hit)
                            dev_inputs, prog = _stage_ragged_inputs(
                                rb, chunk[0][0].block_id
                            )
                            injector.maybe_fail(
                                "dispatch", chunk[0][0].block_id,
                                voxels=sum(
                                    int(np.prod(s.outer_shape))
                                    for s, _ in chunk
                                ),
                            )
                            injector.maybe_hang(
                                "dispatch", chunk[0][0].block_id
                            )
                            with dispatch_lock:
                                with trace_mod.span(
                                    "executor.dispatch", task=task_name,
                                    n_blocks=len(chunk), grain="ragged",
                                    lanes_padded=rb.lanes_padded,
                                ):
                                    out = prog(*dev_inputs)
                            out_np = jax.tree_util.tree_map(np.asarray, out)
                            device_pool_mod.record_d2h(sum(
                                int(a.nbytes)
                                for a in jax.tree_util.tree_leaves(out_np)
                            ))
                            rb.release()
                            _note_dispatch(len(chunk), rb)
                        except Exception:
                            # the ragged sub dispatch failed (device OOM, a
                            # wedged device, an unpackable chunk): the
                            # unchanged per-sub fallback owns these lanes,
                            # reusing the values already in hand
                            for sub, val in chunk:
                                ok &= _run_sub(sub, depth, tracker, val=val)
                            continue
                        for j, (sub, _) in enumerate(chunk):
                            block_out = jax.tree_util.tree_map(
                                lambda a, j=j: rb.crop(j, np.asarray(a)[j]),
                                out_np,
                            )
                            with faults_mod.block_context(int(sub.block_id)):
                                ok &= _store_sub(sub, block_out, depth,
                                                 tracker)
                    return ok

                def _split_and_run(blk, depth=1, tracker=None):
                    """Recursive 2^d halo-correct split of ``blk``; True when
                    every sub-block landed (the parent's stored region is then
                    exactly the reassembled sub-results).  ``tracker`` records
                    the depth THIS parent block actually reached (the sweep-
                    wide maximum lives in ``split_stats``)."""
                    subs = split_block(blk, halo=split_halo,
                                       min_shape=min_block_shape)
                    if subs is None:
                        note_failure(
                            blk, "split", 1,
                            "resource exhaustion persisted at "
                            f"min_block_shape={tuple(min_block_shape or ())} "
                            "— cannot split further",
                            quarantine=True,
                        )
                        return False
                    split_stats["splits"] += 1
                    split_stats["max_depth"] = max(split_stats["max_depth"], depth)
                    split_stats["sub_blocks"] += len(subs)
                    if tracker is not None:
                        tracker["depth"] = max(tracker.get("depth", 0), depth)
                    if use_ragged:
                        # split sub-blocks stay on the sharded path: one
                        # ragged program per parent instead of 2^d per-shape
                        # jit dispatches (docs/PERFORMANCE.md "Ragged
                        # sweeps")
                        return _run_subs_ragged(subs, depth, tracker)
                    return all(_run_sub(sub, depth, tracker) for sub in subs)

                # -- quarantine pass: reduced-batch re-attempts -----------------
                # re-run each still-unresolved quarantined block alone,
                # replicated to the batch width through the SAME compiled kernel
                # — bit-identical results, and a batch-poisoning block is
                # isolated to itself.  Blocks a speculative duplicate (or a
                # late-finishing hung primary) already resolved are skipped.
                # Resource-exhausted blocks enter here as the degrade ladder:
                # wait for headroom, full-size re-attempt, then (splittable
                # call sites) recursive sub-block re-execution.
                with fail_lock:
                    unresolved_q = {
                        b for b in quarantined_ids if not failures[b]["resolved"]
                    }
                degraded_ids: set = set()
                for blk in [b for b in blocks if int(b.block_id) in unresolved_q]:
                    if drained or drain_requested():
                        drained = True
                        break
                    bid = int(blk.block_id)
                    with fail_lock:
                        resource = failures[bid].get("resource")
                    if resource is not None:
                        degraded_ids.add(bid)
                        _wait_for_headroom(resource)
                    val = load_block(blk)
                    if val is not None:
                        ok = False
                        try:
                            injector.maybe_fail(
                                "compute", blk.block_id,
                                voxels=int(np.prod(blk.outer_shape)),
                            )
                            out0 = _exec_single(val)
                            ok = True
                        except Exception as e:
                            tb = fu.cap_traceback(traceback.format_exc())
                            note_failure(
                                blk, "compute", 1, tb, quarantine=True,
                                resource=classify_resource_error(e),
                            )
                        if ok:
                            handle_block_output(blk, out0)
                    # ladder outcome: a resolved resource block recovered via
                    # the headroom wait; a still-unresolved one splits (when
                    # the call site declared the kernel split-safe).  A block
                    # whose SHARDED batch failed resolved through the
                    # per-block fallback — attribute that, not backpressure.
                    with fail_lock:
                        rec = failures[bid]
                        resolved_now = rec["resolved"]
                        resource = rec.get("resource")
                        fell_back = bid in sharded_failed_ids
                    if resolved_now:
                        if fell_back:
                            mark_resolved(blk, "degraded:unsharded")
                        elif resource is not None:
                            mark_resolved(blk, "degraded:backpressure")
                        continue
                    if resource is not None and splittable:
                        tracker = {"depth": 0}
                        if _split_and_run(blk, tracker=tracker):
                            mark_resolved(blk, "degraded:split")
                            with fail_lock:
                                rec = failures[bid]
                                rec["split_depth"] = tracker["depth"]
                            finish_block(blk)

        finally:
            # the watchdog and speculation pool must not outlive the
            # sweep, even when a load/store future propagates an error
            if watchdog is not None:
                watchdog.stop()
            if spec_pool is not None:
                spec_pool.shutdown(wait=True)
            _record_dispatch_metrics(
                dispatch_stats["batches"],
                dispatch_stats["blocks"],
                dispatch_stats["wait_s"],
                sweep_span.end(
                    n_batches=dispatch_stats["batches"],
                    n_quarantined=len(quarantined_ids),
                ),
                ragged_batches=dispatch_stats["ragged_batches"],
                lanes_padded=dispatch_stats["lanes_padded"],
                pages_in_use=dispatch_stats["pages_in_use"],
            )

        unresolved = sorted(
            b for b, rec in failures.items() if not rec["resolved"]
        )
        if failures_path and failures:
            fu.record_failures(
                failures_path,
                task_name,
                [failures[b] for b in sorted(failures)],
            )
        if drained:
            # graceful drain: everything dispatched was finished and
            # markered; what is left belongs to the requeued/resumed run.
            reason = drain_reason() or "drain requested"
            remaining = sorted(
                int(b.block_id) for b in blocks
                if int(b.block_id) not in finished_ids
            )
            if failures_path:
                # keyed under "<task>.drain": records merge by
                # (task, block_id), and (task, None) is already used by the
                # supervisor's job_loss record (and "<task>.preempt" by its
                # requeue record) — a drain must not overwrite either
                fu.record_failures(
                    failures_path,
                    f"{task_name}.drain",
                    [{
                        "block_id": None,
                        "sites": {"preempt": 1},
                        "error": reason,
                        "quarantined": False,
                        "resolved": True,
                        "resolution": "requeued:preempt",
                        "remaining_blocks": len(remaining),
                    }],
                )
            raise DrainInterrupt(reason, remaining)
        if unresolved:
            details = "\n".join(
                f"-- block {b} (sites {failures[b]['sites']}) --\n"
                f"{failures[b]['error']}"
                for b in unresolved[:5]
            )
            raise RuntimeError(
                f"{task_name}: {len(unresolved)}/{len(blocks)} blocks failed "
                f"permanently after retries + quarantine re-attempts "
                f"(ids: {unresolved})"
                + (f"; see {failures_path}" if failures_path else "")
                + f"; first errors:\n{details}"
            )
        summary = {
            "n_blocks": len(blocks),
            "n_quarantined": len(quarantined_ids),
            "n_failed": 0,
            "sweep_mode": "sharded" if use_sharded else "per_block",
            "n_dispatches": dispatch_stats["batches"],
        }
        if sharded_failed_ids:
            summary["n_unsharded"] = len(sharded_failed_ids)
        if dispatch_stats["ragged_batches"]:
            summary["n_ragged_batches"] = dispatch_stats["ragged_batches"]
            summary["n_lanes_padded"] = dispatch_stats["lanes_padded"]
            summary["pages_in_use"] = dispatch_stats["pages_in_use"]
        if kept is not None and kept.info is not None:
            # which level gave the sweep its program (the last signature's)
            summary["program"] = kept.info
        if dev_pool is not None:
            summary["device_pool"] = "on"
            summary["device_pool_resident_bytes"] = dev_pool.resident_bytes()
        if deadline > 0:
            summary["n_hung"] = sum(
                1 for rec in failures.values() if "hung" in rec["sites"]
            )
            summary["n_speculated"] = len(speculated)
        if degraded_ids or split_stats["splits"] or backpressure["waits"]:
            summary["n_degraded"] = len(degraded_ids)
            summary["n_split"] = split_stats["splits"]
            summary["n_sub_blocks"] = split_stats["sub_blocks"]
            summary["split_depth"] = split_stats["max_depth"]
            summary["n_backpressure_waits"] = backpressure["waits"]
        return summary
