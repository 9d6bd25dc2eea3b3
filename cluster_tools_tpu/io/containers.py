"""Chunked-array containers: the framework's inter-stage data plane.

The reference used z5py (C++ N5/zarr bindings) plus h5py as its entire
inter-job data plane (SURVEY.md §2d).  Here the same role is played by
**tensorstore** (Google's C++ chunked-array library, zarr + N5 drivers) with
h5py for HDF5 inputs, behind one small uniform API:

    f = open_container("/data/seg.n5")          # or .zarr / .h5
    ds = f.create_dataset("labels", shape=..., chunks=..., dtype="uint64")
    ds[bb] = block          # numpy in / numpy out
    arr = ds[bb]

Datasets are addressed by key (group paths like ``volumes/raw`` work).
``__getitem__``/``__setitem__`` are synchronous numpy round-trips;
``read_async``/``write_async`` return storage-level futures, consumed by the
bounded-window pipelines in :mod:`cluster_tools_tpu.io.prefetch` and by
``BlockwiseExecutor``'s batch assembly.

Data integrity (docs/ROBUSTNESS.md "Silent failures"): every stored block
region gets a CRC32 digest *sidecar* (``<dataset>/.ctt_checksums/`` for
zarr/N5, in-memory for ``memory://``), written after the data lands.  Reads
whose bounding box exactly matches a recorded region are verified against
the digest; a mismatch raises the typed :class:`ChunkCorruptionError`, which
the executor treats as a retriable-then-repairable fault (re-store, or
recompute the owning block through the same compiled kernel).  Writes that
overlap a recorded region invalidate its stale digest.  The async
``read_async``/``write_async`` paths verify/record on ``.result()`` — the
same sites and accounting as the synchronous paths, so prefetched IO is not
a hole in the fault model.  ``CTT_CHECKSUMS=0`` disables the whole layer
(HDF5 never has it: a single shared file has no place for per-region
sidecars).

Chunk-aware reads (docs/PERFORMANCE.md "Chunk-aware I/O"): tensorstore
``Dataset`` region reads are assembled from the process-wide decompressed-
chunk cache (:mod:`.chunk_cache`) — only miss-chunks hit storage, with
single-flight deduplication across concurrent halo reads.  Writes evict
every overlapping chunk; faulted or corruption-failing reads never leave
chunks resident; ``verify_region`` and the raw ``_read_back`` path bypass
the cache so integrity checks always see storage bytes.  ``CTT_CHUNK_CACHE=0``
restores the direct-read behavior exactly.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import zlib
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from . import chunk_cache as _chunk_cache

try:
    import tensorstore as ts
except ImportError:  # pragma: no cover - tensorstore is expected in this image
    ts = None

try:
    import h5py
except ImportError:  # pragma: no cover
    h5py = None


_ZARR_EXTS = (".zarr", ".zr", ".n5")
_H5_EXTS = (".h5", ".hdf5", ".hdf")

_faults_mod = None


def _faults():
    global _faults_mod
    if _faults_mod is None:
        from ..runtime import faults as _fm

        _faults_mod = _fm
    return _faults_mod


_trace_mod = None


def _trace():
    # lazily bound once, as _faults is: runtime/ imports this package
    global _trace_mod
    if _trace_mod is None:
        from ..runtime import trace as _tm

        _trace_mod = _tm
    return _trace_mod


def _inject(site: str, voxels: Optional[int] = None) -> Optional[int]:
    """Fault-injection hook for the container IO layer (sites ``io_read`` /
    ``io_write``; see runtime/faults.py).  A no-op unless an injector is
    configured — chaos tests exercise the executor's load/store retries
    against storage-level failures through this.  The block id is inherited
    from the executor's thread-local :func:`~...runtime.faults.block_context`
    and returned so async completions can reuse it.  ``voxels`` (the write's
    element count, when the caller knows it) feeds the ``min_voxels`` gate
    of resource faults — full-size writes fail, split sub-writes fit."""
    fm = _faults()
    block_id = fm.current_block_id()
    fm.get_injector().maybe_fail(site, block_id, voxels=voxels)
    return block_id


def _hang(site: str, block_id: Optional[int]) -> None:
    _faults().get_injector().maybe_hang(site, block_id)


def checksums_enabled() -> bool:
    """Digest sidecars on stored regions (default on); ``CTT_CHECKSUMS=0``
    is the kill switch for workloads where the extra sidecar IO hurts."""
    return os.environ.get("CTT_CHECKSUMS", "1").lower() not in (
        "0", "false", "off",
    )


class ChunkCorruptionError(RuntimeError):
    """A stored region's bytes no longer match its digest sidecar: the data
    was corrupted *on storage* after a successful write (bit rot, torn
    chunk, misbehaving storage layer).  The executor treats this as
    retriable (re-read), then repairable (re-store / recompute the owning
    block through the same compiled kernel)."""

    def __init__(self, label: str, region, expected, actual):
        self.label = label
        self.region = tuple(region)
        self.expected = expected
        self.actual = actual
        super().__init__(
            f"chunk corruption in {label} region "
            + "x".join(f"[{a}:{b}]" for a, b in self.region)
            + f": stored digest {expected}, read digest {actual}"
        )


def _norm_region(bb, shape) -> Optional[Tuple[Tuple[int, int], ...]]:
    """Resolve a numpy-style index to ``((start, stop), ...)`` per axis, or
    None when it is not a plain step-1 slice box (fancy/int indexing has no
    region identity to checksum)."""
    if bb is Ellipsis:
        return tuple((0, int(s)) for s in shape)
    if isinstance(bb, slice):
        bb = (bb,)
    if not isinstance(bb, tuple):
        return None
    if any(b is Ellipsis for b in bb):
        i = next(j for j, b in enumerate(bb) if b is Ellipsis)
        bb = bb[:i] + (slice(None),) * (len(shape) - len(bb) + 1) + bb[i + 1:]
    if len(bb) < len(shape):
        bb = bb + (slice(None),) * (len(shape) - len(bb))
    if len(bb) != len(shape):
        return None
    out = []
    for sl, s in zip(bb, shape):
        if not isinstance(sl, slice) or sl.step not in (None, 1):
            return None
        start, stop, _ = sl.indices(int(s))
        out.append((int(start), max(int(start), int(stop))))
    return tuple(out)


def _region_shape(region) -> Tuple[int, ...]:
    return tuple(b - a for a, b in region)


def _regions_overlap(r1, r2) -> bool:
    return len(r1) == len(r2) and all(
        a1 < b2 and a2 < b1 for (a1, b1), (a2, b2) in zip(r1, r2)
    )


def _crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes())


class _ChecksumIndex:
    """Digest sidecars for stored regions: one tiny JSON per region under
    ``<dataset>/.ctt_checksums/`` (filesystem containers) or an in-memory
    dict (``memory://``).  Per-region files keep parallel block writers
    conflict-free — the same reason block writes must tile whole chunks.

    The set of on-disk region keys is cached per index (seeded by ONE
    ``listdir`` on first write, then maintained incrementally), so the
    overlap-invalidation scan is an in-memory set walk instead of a
    directory listing per block write — a run storing N blocks would
    otherwise pay O(N^2) filesystem work.  Regions recorded by *other*
    handles after seeding are invisible to the scan; that only matters for
    concurrently-overlapping writers, which the chunk-alignment contract
    already forbids."""

    def __init__(self, dirpath: Optional[str] = None):
        self._dir = dirpath
        self._mem: Optional[Dict] = {} if dirpath is None else None
        self._fs_keys: Optional[set] = None  # lazy on-disk region cache
        self._lock = threading.Lock()

    def _known_regions(self) -> set:
        """Cached set of regions with an on-disk sidecar (call under
        ``_lock``); seeded once from the directory."""
        if self._fs_keys is None:
            keys = set()
            if self._dir is not None and os.path.isdir(self._dir):
                for fname in os.listdir(self._dir):
                    r = self._parse(fname)
                    if r is not None:
                        keys.add(r)
            self._fs_keys = keys
        return self._fs_keys

    @staticmethod
    def _key(region) -> str:
        return "r_" + "_".join(f"{a}-{b}" for a, b in region)

    @staticmethod
    def _parse(name: str):
        if not (name.startswith("r_") and name.endswith(".json")):
            return None
        try:
            return tuple(
                (int(p.split("-")[0]), int(p.split("-")[1]))
                for p in name[2:-len(".json")].split("_")
            )
        except (ValueError, IndexError):
            return None

    def record(self, region, value: np.ndarray) -> None:
        entry = {
            "algo": "crc32",
            "crc": _crc(value),
            "dtype": value.dtype.str,
            "shape": list(value.shape),
        }
        self.invalidate_overlaps(region)
        if self._mem is not None:
            with self._lock:
                self._mem[region] = entry
            return
        os.makedirs(self._dir, exist_ok=True)
        path = os.path.join(self._dir, self._key(region) + ".json")
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "w") as f:
            json.dump(entry, f)
        os.replace(tmp, path)
        with self._lock:
            self._known_regions().add(region)

    def lookup(self, region) -> Optional[Dict]:
        if self._mem is not None:
            with self._lock:
                return self._mem.get(region)
        path = os.path.join(self._dir, self._key(region) + ".json")
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def invalidate_overlaps(self, region) -> None:
        """Drop digests of regions intersecting ``region`` — a partial
        overwrite makes them stale, and a stale digest would turn a later
        valid read into a false corruption alarm.  Walks the cached key
        set, not the directory (see class docstring)."""
        if self._mem is not None:
            with self._lock:
                for r in [r for r in self._mem if _regions_overlap(r, region)]:
                    del self._mem[r]
            return
        if self._dir is None:
            return
        with self._lock:
            known = self._known_regions()
            hits = [r for r in known if _regions_overlap(r, region)]
            for r in hits:
                known.discard(r)
        for r in hits:
            try:
                os.unlink(os.path.join(self._dir, self._key(r) + ".json"))
            except OSError:
                pass

    def drop(self, region) -> None:
        """Delete ONE region's sidecar (the injected sidecar-loss fault
        rides this; stale-overlap semantics stay with
        :meth:`invalidate_overlaps`)."""
        if self._mem is not None:
            with self._lock:
                self._mem.pop(region, None)
            return
        if self._dir is None:
            return
        with self._lock:
            self._known_regions().discard(region)
        try:
            os.unlink(os.path.join(self._dir, self._key(region) + ".json"))
        except OSError:
            pass

    def regions(self) -> list:
        """Every region with a recorded sidecar.  Filesystem indexes
        answer from the DIRECTORY — the scrubber's work list must see
        sidecars written by other processes/handles, not this handle's
        incremental cache — memory indexes from the dict."""
        if self._mem is not None:
            with self._lock:
                return list(self._mem)
        out = []
        if self._dir is not None and os.path.isdir(self._dir):
            for fname in os.listdir(self._dir):
                r = self._parse(fname)
                if r is not None:
                    out.append(r)
        return out


# async completion hooks (verify / record digest) ride on prefetch's
# future-mapping adapter — the async IO paths stay inside the same fault
# model as the sync ones, at the moment the data is actually consumed
from .prefetch import _MappedFuture as _WrappedFuture  # noqa: E402


class _ChecksumOps:
    """Shared digest behavior for datasets that support it.  Subclasses
    provide ``_read_back(bb)`` (raw region read, no injection) and
    ``_write_raw(bb, value)`` (raw write, no sidecar) plus ``_checksums``
    and ``_label`` attributes."""

    #: read-site tag carried into typed ``corrupt:<site>`` errors
    #: (io/verified.py): "storage" for stored datasets, "memory" for the
    #: in-memory container, "handoff" for live handoff targets, "spill"
    #: for a handoff's storage spill copy (stamped by ``spill()``)
    _read_site = "storage"

    def _read_back(self, bb) -> np.ndarray:  # pragma: no cover - abstract
        raise NotImplementedError

    def _write_raw(self, bb, value) -> None:  # pragma: no cover - abstract
        raise NotImplementedError

    def _after_write(self, bb, value: np.ndarray, block_id) -> None:
        """Record the region digest, then apply any injected silent
        corruption (bit-flip the stored bytes — or delete the fresh
        sidecar, ``mode='sidecar'`` — so only checksum verification, or
        its absence, can tell)."""
        region = _norm_region(bb, self.shape)
        if region is not None and checksums_enabled():
            if value.shape == _region_shape(region):
                self._checksums.record(region, value)
            else:
                # broadcast / scalar fill: no digestable identity, but any
                # previous digest for this box is now stale
                self._checksums.invalidate_overlaps(region)
        mode = _faults().get_injector().chunk_corrupt("io_write", block_id)
        if mode == "sidecar":
            if region is not None:
                self._checksums.drop(region)
        elif mode:
            bad = np.ascontiguousarray(value).copy()
            if bad.size and bad.dtype.itemsize:
                bad.reshape(-1).view(np.uint8)[0] ^= 0x01
            self._write_raw(bb, bad)

    def _apply_read_rot(self, bb, block_id) -> None:
        """Injected at-rest damage surfacing at the read site
        (``kind='corrupt'`` at ``io_read``, runtime/faults.py): flip one
        STORED byte of the region (sidecar untouched) or delete its
        sidecar (``mode='sidecar'``) just before the read proceeds — the
        verifying reader must catch the former, the missing-sidecar
        policy decides the latter."""
        mode = _faults().get_injector().chunk_corrupt("io_read", block_id)
        if not mode:
            return
        region = _norm_region(bb, self.shape)
        if region is None:
            return
        if mode == "sidecar":
            self._checksums.drop(region)
            return
        bad = np.ascontiguousarray(self._read_back(bb)).copy()
        if bad.size and bad.dtype.itemsize:
            bad.reshape(-1).view(np.uint8)[0] ^= 0x01
            self._write_raw(bb, bad)
            # the rot lives on STORAGE: resident clean chunks must not
            # shadow it, or the read under test never sees the damage
            inval = getattr(self, "_invalidate_cached_region", None)
            if inval is not None:
                inval(bb)

    def _postread(self, bb, arr: np.ndarray, evict=None) -> np.ndarray:
        """The verifying-reader tail of every region read
        (:mod:`cluster_tools_tpu.io.verified`): digest verification, the
        per-store missing-sidecar policy, and the lineage-repair hook on
        mismatch.  Returns the (possibly repaired and re-read) array;
        raises the typed ``corrupt:<site>`` error when the bytes stay
        bad."""
        from . import verified as _verified

        return _verified.postread(self, bb, arr, evict=evict)

    def _verify_read(self, bb, arr: np.ndarray) -> None:
        if not checksums_enabled():
            return
        region = _norm_region(bb, self.shape)
        if region is None:
            return
        entry = self._checksums.lookup(region)
        if entry is None:
            return
        if (
            list(entry.get("shape", [])) != list(arr.shape)
            or entry.get("dtype") != arr.dtype.str
        ):
            return  # stale sidecar (shape/dtype drifted): not verifiable
        actual = _crc(arr)
        if actual != entry.get("crc"):
            raise ChunkCorruptionError(self._label, region, entry.get("crc"), actual)

    def verify_region(self, bb) -> None:
        """Read back a stored region and check it against its digest
        sidecar; raises :class:`ChunkCorruptionError` on mismatch, no-op
        when no digest exists.  The executor's store path calls this so
        corruption is caught while the writer still holds the clean data
        (retry) or can recompute it (quarantine repair)."""
        if not checksums_enabled():
            return
        region = _norm_region(bb, self.shape)
        if region is None or self._checksums.lookup(region) is None:
            return
        self._verify_read(bb, np.asarray(self._read_back(bb)))

    def checksum_regions(self) -> list:
        """Every region with a recorded digest sidecar — the scrubber's
        work list (``runtime/scrub.py``).  Disk truth for filesystem-
        backed indexes: sidecars written by other handles/processes are
        visible."""
        return self._checksums.regions()

    def checksum_entry(self, bb) -> Optional[Dict]:
        """The digest sidecar entry for ``bb``'s exact region (``crc`` /
        ``dtype`` / ``shape``), or None when unrecorded — lets the
        scrubber budget bytes without reading the data."""
        region = _norm_region(bb, self.shape)
        return None if region is None else self._checksums.lookup(region)


# numpy dtype -> zarr v2 dtype string
def _zarr_dtype(dtype) -> str:
    return np.dtype(dtype).newbyteorder("<").str


def _n5_dtype(dtype) -> str:
    return np.dtype(dtype).name


class _CachedReadPlan:
    """Phase-1 state of a chunk-assembled region read: the resolved region
    plus one (key, chunk_box, kind, handle) step per covering chunk, where
    owned miss-chunks carry their already-issued tensorstore futures."""

    __slots__ = ("region", "steps")

    def __init__(self, region, steps):
        self.region = region
        self.steps = steps


class Dataset(_ChecksumOps):
    """A chunked dataset backed by tensorstore."""

    def __init__(self, store, attrs_path: Optional[str] = None,
                 checksum_dir: Optional[str] = None, label: str = ""):
        self._store = store
        self._attrs_path = attrs_path
        self._checksums = _ChecksumIndex(
            checksum_dir
            if checksum_dir is not None
            else (os.path.join(os.path.dirname(attrs_path), ".ctt_checksums")
                  if attrs_path else None)
        )
        self._label = label or (attrs_path or "<dataset>")
        # chunk-cache identity: the container path + key, stable across
        # handle instances in this process (two open_container calls on the
        # same store must share — and mutually invalidate — cache entries);
        # anonymous store-only datasets fall back to per-instance identity
        self._cache_id = (
            self._label if (label or attrs_path) else f"ts-anon-{id(self)}"
        )

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._store.shape)

    @property
    def dtype(self):
        return np.dtype(self._store.dtype.numpy_dtype)

    @property
    def chunks(self) -> Tuple[int, ...]:
        return tuple(self._store.chunk_layout.read_chunk.shape)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def _read_back(self, bb) -> np.ndarray:
        # raw storage read, no cache: verify_region / region_verifier must
        # check the bytes on DISK, not a resident copy
        return np.asarray(self._store[bb].read().result())

    def _write_raw(self, bb, value) -> None:
        self._store[bb].write(value).result()

    # -- chunk-assembled reads (docs/PERFORMANCE.md "Chunk-aware I/O") ------
    def _chunk_cover(self, region):
        """[(cache_key, chunk_box), ...] covering ``region``, or None when
        the dataset has no usable chunk grid."""
        chunks = self.chunks
        shape = self.shape
        if (
            not chunks
            or len(chunks) != len(shape)
            or any(int(c) <= 0 for c in chunks)
        ):
            return None
        ranges = [
            range(a // c, (b + c - 1) // c) if b > a else range(0)
            for (a, b), c in zip(region, chunks)
        ]
        cover = []
        for idx in itertools.product(*ranges):
            box = tuple(
                (i * c, min((i + 1) * c, s))
                for i, c, s in zip(idx, chunks, shape)
            )
            cover.append(((self._cache_id, idx), box))
        return cover

    def _begin_cached_read(self, bb):
        """Phase 1 (issue) of a cache-assembled read: take a HIT/OWNER/WAIT
        ticket per covering chunk and issue one tensorstore read per owned
        miss-chunk — every miss of the region is in flight together.
        Returns None when the read cannot go through the cache (kill
        switch, zero budget, fancy indexing, chunkless store).

        Owner tokens are settled by a done-callback on the storage future —
        when the READ lands, not when (or whether) anyone resolves the
        plan.  A ``read_async`` future dropped without ``.result()`` (an
        abandoned retry attempt, an early-exiting prefetch consumer) must
        not strand later readers of the same chunks on an unsettled
        in-flight token."""
        if not _chunk_cache.cache_enabled():
            return None
        cache = _chunk_cache.get_chunk_cache()
        if cache.max_bytes <= 0:
            return None
        region = _norm_region(bb, self.shape)
        if region is None:
            return None
        # bulk-read bypass: a region that would consume over half the
        # budget cannot be cached without flushing the resident halo
        # working set the cache exists to keep (and gains nothing from
        # per-chunk assembly) — serve it as one direct storage read
        region_bytes = int(
            np.prod([b - a for a, b in region], dtype=np.int64)
        ) * self.dtype.itemsize
        if region_bytes > cache.max_bytes // 2:
            return None
        cover = self._chunk_cover(region)
        if cover is None:
            return None
        steps = []
        for key, box in cover:
            kind, handle = cache.get_or_begin(key)
            if kind == cache.OWNER:
                cbb = tuple(slice(a, b) for a, b in box)
                try:
                    fut = self._store[cbb].read()
                except Exception as e:
                    cache.fail(key, handle, e)
                    raise

                def _settle(f, key=key, token=handle):
                    try:
                        cache.complete(key, token, np.asarray(f.result()))
                    except Exception as e:
                        cache.fail(key, token, e)

                fut.add_done_callback(_settle)
            steps.append((key, box, kind, handle))
        # an exception mid-loop leaves already-issued owners to their
        # callbacks: every begun token settles itself, no waiter can hang
        return _CachedReadPlan(region, steps)

    def _finish_cached_read(self, plan: _CachedReadPlan) -> np.ndarray:
        """Phase 2 (resolve): wait for the in-flight chunk loads (owned
        ones settle via their storage-future callbacks) and assemble the
        region from chunk slices.  A waiter stalled past the patience
        window (:func:`~cluster_tools_tpu.io.chunk_cache.stall_wait_s`)
        falls back to an independent direct read, so one wedged storage
        call cannot serialize every consumer of a chunk behind it — the
        hang defense's speculative re-execution stays independent of the
        read it is routing around.  The first chunk failure is raised
        after the loop, keeping shared tokens consistent."""
        from ..runtime import trace as trace_mod

        cache = _chunk_cache.get_chunk_cache()
        region = plan.region
        patience = _chunk_cache.stall_wait_s()
        out = np.empty(_region_shape(region), self.dtype)
        first_exc: Optional[BaseException] = None
        # one assembly span per region read (not per chunk — a halo'd read
        # covers dozens): hit/miss/coalesced-wait composition in the args,
        # duration = the storage latency the cache failed to hide
        # (docs/OBSERVABILITY.md).  The composition scans are gated on the
        # tracer so the default-off hot read path stays a true no-op
        if trace_mod.enabled():
            n_hits = sum(
                1 for _k, _b, kind, _h in plan.steps if kind == cache.HIT
            )
            n_waits = sum(
                1 for _k, _b, kind, _h in plan.steps if kind == cache.WAIT
            )
            assemble_span = trace_mod.span(
                "chunk_cache.assemble", n_chunks=len(plan.steps),
                hits=n_hits, misses=len(plan.steps) - n_hits - n_waits,
                waits=n_waits,
            )
        else:
            assemble_span = trace_mod.span("chunk_cache.assemble")
        with assemble_span:
            for key, box, kind, handle in plan.steps:
                if first_exc is not None:
                    # fail fast: owner tokens settle via their storage-future
                    # callbacks regardless, so there is nothing to wait out —
                    # waiting (or stall-fallback-reading) chunks whose bytes
                    # will be discarded only delays the error
                    continue
                try:
                    if kind == cache.HIT:
                        chunk = handle
                    else:
                        try:
                            if kind == cache.WAIT:
                                # the single-flight wait: time spent behind
                                # ANOTHER reader's in-flight storage read
                                with trace_mod.span("chunk_cache.wait"):
                                    chunk = cache.wait(
                                        handle, timeout=patience
                                    )
                            else:
                                chunk = cache.wait(handle, timeout=patience)
                        except _chunk_cache.ChunkWaitTimeout:
                            cbb = tuple(slice(a, b) for a, b in box)
                            chunk = np.asarray(
                                self._store[cbb].read().result()
                            )
                            cache.record_stall_fallback(chunk.nbytes)
                except Exception as e:
                    first_exc = e
                    continue
                src, dst = [], []
                for (ra, rb), (ca, cb) in zip(region, box):
                    lo, hi = max(ra, ca), min(rb, cb)
                    src.append(slice(lo - ca, hi - ca))
                    dst.append(slice(lo - ra, hi - ra))
                out[tuple(dst)] = chunk[tuple(src)]
        if first_exc is not None:
            raise first_exc
        cache.record_served(out.nbytes)
        return out

    def _evict_plan(self, plan: _CachedReadPlan) -> None:
        _chunk_cache.get_chunk_cache().invalidate(
            [key for key, _b, _k, _h in plan.steps]
        )

    def _invalidate_cached_region(self, bb) -> None:
        """Write coherence: drop every cached chunk the write overlaps —
        AFTER the write (and any injected silent corruption) landed, so the
        cache never shadows what storage holds.  Runs even with the kill
        switch flipped: entries cached while it was on must not survive a
        write."""
        cache = _chunk_cache.get_chunk_cache()
        region = _norm_region(bb, self.shape)
        cover = None if region is None else self._chunk_cover(region)
        if cover is None:
            cache.invalidate_dataset(self._cache_id)
            return
        cache.invalidate([key for key, _box in cover])

    def __getitem__(self, bb) -> np.ndarray:
        # the doorway's own span (io.read / io.write): every caller's reads
        # and writes, timed where they happen (docs/OBSERVABILITY.md)
        with _trace().span("io.read", key=self._label) as sp:
            bid = _inject("io_read")
            _hang("io_read", bid)
            self._apply_read_rot(bb, bid)
            plan = self._begin_cached_read(bb)
            if plan is None:
                arr = np.asarray(self._store[bb].read().result())
                _chunk_cache.get_chunk_cache().record_direct(arr.nbytes)
                arr = self._postread(bb, arr)
            else:
                arr = self._finish_cached_read(plan)
                # a failed digest verify must not leave the bad chunks
                # resident: the verifying reader evicts before attempting
                # lineage repair
                arr = self._postread(
                    bb, arr, evict=lambda: self._evict_plan(plan)
                )
            sp.note(nbytes=int(arr.nbytes))
            return arr

    def __setitem__(self, bb, value) -> None:
        with _trace().span("io.write", key=self._label) as sp:
            bid = _inject("io_write", voxels=getattr(value, "size", None))
            _hang("io_write", bid)
            value = np.asarray(value, dtype=self.dtype)
            sp.note(nbytes=int(value.nbytes))
            try:
                self._store[bb].write(value).result()
                self._after_write(bb, value, bid)
            finally:
                # in a finally: a write that RAISES may still have landed
                # some chunks (partial multi-chunk store, ENOSPC mid-region,
                # sidecar failure after the data landed) — stale pre-write
                # entries must not outlive any of those either
                self._invalidate_cached_region(bb)

    def read_async(self, bb):
        """Start an async read; returns a future with ``.result()`` -> numpy.
        Injection fires at issue (same accounting as ``__getitem__``);
        digest verification runs on ``.result()``, where the data lands.
        Cache-assembled reads issue their miss-chunk storage reads at call
        time (so a batch's chunk IO is in flight together) and assemble +
        verify on ``.result()``."""
        bid = _inject("io_read")
        self._apply_read_rot(bb, bid)
        plan = self._begin_cached_read(bb)
        if plan is None:
            fut = self._store[bb].read()

            def finish(raw):
                _hang("io_read", bid)
                arr = np.asarray(raw)
                _chunk_cache.get_chunk_cache().record_direct(arr.nbytes)
                return self._postread(bb, arr)

            return _WrappedFuture(fut, finish)

        def finish_cached(_):
            _hang("io_read", bid)
            arr = self._finish_cached_read(plan)
            return self._postread(
                bb, arr, evict=lambda: self._evict_plan(plan)
            )

        return _WrappedFuture(_ImmediateFuture(None), finish_cached)

    def write_async(self, bb, value):
        bid = _inject("io_write", voxels=getattr(value, "size", None))
        value = np.asarray(value, dtype=self.dtype)
        fut = self._store[bb].write(value)
        # evict when the STORAGE write lands, not when (or whether) the
        # caller resolves the future — an abandoned write_async must not
        # leave stale pre-write chunks resident (the write-side twin of
        # the read path's owner-token callbacks)
        fut.add_done_callback(lambda _f: self._invalidate_cached_region(bb))

        def finish(_):
            _hang("io_write", bid)
            try:
                # resolve the storage write INSIDE the guarded region: a
                # failed multi-chunk write may still have landed some
                # chunks, and the sidecar/corruption hook can raise after
                # the data landed — stale entries must survive neither
                fut.result()
                self._after_write(bb, value, bid)
            finally:
                self._invalidate_cached_region(bb)
            return None

        return _WrappedFuture(_ImmediateFuture(None), finish)

    # -- attributes (json sidecar, mirroring z5py/zarr .zattrs) -------------
    @property
    def attrs(self) -> Dict:
        if self._attrs_path is None or not os.path.exists(self._attrs_path):
            return {}
        with open(self._attrs_path) as f:
            return json.load(f)

    def update_attrs(self, **kwargs) -> None:
        if self._attrs_path is None:
            raise RuntimeError("dataset has no attribute store")
        attrs = self.attrs
        attrs.update(kwargs)
        # atomic: a kill mid-write must not tear the sidecar (it is shared
        # with external zarr/N5 readers)
        tmp = f"{self._attrs_path}.tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "w") as f:
            json.dump(attrs, f, indent=2, default=_json_default)
        os.replace(tmp, self._attrs_path)


def _json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not json-serializable: {type(o)}")


class _ImmediateFuture:
    """Future-shim for backends whose reads/writes complete synchronously."""

    def __init__(self, v):
        self._v = v

    def result(self):
        return self._v


def _clamp_chunks(chunks, shape):
    """Chunks capped at the dataset shape — the creation rule, reused by
    existing-dataset validation so both paths compare like for like."""
    return tuple(int(min(c, s)) for c, s in zip(chunks, shape))


def _check_existing(
    key, have_shape, have_dtype, want_shape, want_dtype,
    have_chunks=None, want_chunks=None,
):
    if tuple(have_shape) != tuple(int(s) for s in want_shape) or np.dtype(
        have_dtype
    ) != np.dtype(want_dtype):
        raise ValueError(
            f"dataset {key!r} exists with shape {tuple(have_shape)} / dtype "
            f"{np.dtype(have_dtype)}, requested {tuple(want_shape)} / "
            f"{np.dtype(want_dtype)}"
        )
    if have_chunks is None or want_chunks is None:
        return
    have_chunks = tuple(int(c) for c in have_chunks)
    want_chunks = tuple(int(c) for c in want_chunks)
    # race safety (SURVEY.md §5.2): parallel block writes are conflict-free
    # only when every written block tiles whole chunks — i.e. the requested
    # block grid is a per-axis integer multiple of the existing chunks.
    # Finer-than-existing blocks would share chunks between writers.
    if len(have_chunks) != len(want_chunks) or any(
        w % h for w, h in zip(want_chunks, have_chunks)
    ):
        raise ValueError(
            f"dataset {key!r} exists with chunks {have_chunks}, requested "
            f"{want_chunks} — blocks must tile whole chunks (per-axis "
            "integer multiples) for chunk-aligned parallel writes; use a "
            "matching block_shape or a fresh dataset"
        )



class ZarrContainer:
    """A zarr (v2) or N5 container on the local filesystem, via tensorstore."""

    def __init__(self, path: str, mode: str = "a"):
        if ts is None:
            raise ImportError("tensorstore is required for zarr/n5 containers")
        self.path = os.path.abspath(path)
        self.mode = mode
        self.is_n5 = self.path.endswith(".n5")
        self._cache: Dict[str, Dataset] = {}
        self._lock = threading.Lock()
        if mode != "r":
            os.makedirs(self.path, exist_ok=True)
            marker = os.path.join(
                self.path, "attributes.json" if self.is_n5 else ".zgroup"
            )
            if not os.path.exists(marker):
                # atomic (CT002): concurrent jobs opening the same container
                # race this creation; a reader must see a whole marker
                tmp = f"{marker}.tmp.{os.getpid()}.{threading.get_ident()}"
                with open(tmp, "w") as f:
                    json.dump(
                        {"n5": "2.0.0"} if self.is_n5 else {"zarr_format": 2}, f
                    )
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, marker)

    # -- internal ----------------------------------------------------------
    def _spec(self, key: str, metadata: Optional[dict] = None, create: bool = False):
        spec = {
            "driver": "n5" if self.is_n5 else "zarr",
            "kvstore": {"driver": "file", "path": os.path.join(self.path, key)},
            "recheck_cached_data": "open",
        }
        if metadata is not None:
            spec["metadata"] = metadata
        if create:
            spec["create"] = True
            spec["open"] = True
        return spec

    def _attrs_path(self, key: str) -> str:
        fname = "attributes.json" if self.is_n5 else ".zattrs"
        return os.path.join(self.path, key, fname)

    # -- public api --------------------------------------------------------
    def create_dataset(
        self,
        key: str,
        shape: Sequence[int],
        chunks: Sequence[int],
        dtype,
        compression: Optional[str] = "gzip",
        exist_ok: bool = True,
        fill_value: int = 0,
    ) -> Dataset:
        if self.mode == "r":
            raise PermissionError(f"container {self.path} opened read-only")
        shape = [int(s) for s in shape]
        chunks = list(_clamp_chunks(chunks, shape))
        if self.is_n5:
            comp = {"type": compression if compression else "raw"}
            # the N5 spec stores dimensions fastest-varying-first (F-order);
            # we write spec-compliant metadata and present C-order through a
            # tensorstore transpose in _open_store, so z5py/Java-N5 readers
            # see the same axis order as our numpy API
            metadata = {
                "dimensions": shape[::-1],
                "blockSize": chunks[::-1],
                "dataType": _n5_dtype(dtype),
                "compression": comp,
            }
        else:
            comp = (
                {"id": "zlib", "level": 1}
                if compression == "gzip"
                else None
            )
            metadata = {
                "shape": shape,
                "chunks": chunks,
                "dtype": _zarr_dtype(dtype),
                "compressor": comp,
                "fill_value": fill_value,
            }
        try:
            store = self._open_store(key, metadata, create=True)
            # a FRESH dataset now lives at this identity: chunks cached
            # under it belong to a deleted/recreated predecessor (e.g. an
            # output store torn down and rebuilt between in-process runs)
            # and must not be served against the new data
            _chunk_cache.get_chunk_cache().invalidate_dataset(
                f"{self.path}:{key}"
            )
        except ValueError:
            if not exist_ok:
                raise
            store = self._open_store(key)
            _check_existing(
                key, store.shape, store.dtype.numpy_dtype, shape, dtype,
                have_chunks=store.chunk_layout.read_chunk.shape,
                want_chunks=chunks,
            )
        ds = Dataset(store, self._attrs_path(key), label=f"{self.path}:{key}")
        with self._lock:
            self._cache[key] = ds
        return ds

    def _open_store(self, key, metadata=None, create=False):
        store = ts.open(self._spec(key, metadata, create=create)).result()
        if self.is_n5:
            # present C-order over the spec-mandated F-order on-disk layout
            store = store.T
        return store

    def require_dataset(self, key: str, **kwargs) -> Dataset:
        # create_dataset's exist_ok path validates shape/dtype of an existing
        # dataset against the request, which a bare self[key] would skip
        return self.create_dataset(key, exist_ok=True, **kwargs)

    def __getitem__(self, key: str) -> Dataset:
        with self._lock:
            if key in self._cache:
                return self._cache[key]
        store = self._open_store(key)
        ds = Dataset(store, self._attrs_path(key), label=f"{self.path}:{key}")
        with self._lock:
            self._cache[key] = ds
        return ds

    def __contains__(self, key: str) -> bool:
        d = os.path.join(self.path, key)
        if self.is_n5:
            return os.path.exists(os.path.join(d, "attributes.json"))
        return os.path.exists(os.path.join(d, ".zarray"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def close(self):
        pass


class _H5Dataset:
    """Adapter giving h5py datasets the same surface as :class:`Dataset`.
    No digest sidecars (one shared .h5 file has no safe place for per-region
    metadata under parallel writers), so no ``verify_region`` — callers
    probe for the attribute."""

    def __init__(self, ds):
        self._ds = ds

    shape = property(lambda self: tuple(self._ds.shape))
    dtype = property(lambda self: self._ds.dtype)
    ndim = property(lambda self: self._ds.ndim)

    @property
    def chunks(self):
        return tuple(self._ds.chunks) if self._ds.chunks else tuple(self._ds.shape)

    def __getitem__(self, bb):
        bid = _inject("io_read")
        _hang("io_read", bid)
        return self._ds[bb]

    def __setitem__(self, bb, value):
        bid = _inject("io_write", voxels=getattr(value, "size", None))
        _hang("io_write", bid)
        self._ds[bb] = value

    def read_async(self, bb):
        bid = _inject("io_read")
        _hang("io_read", bid)
        return _ImmediateFuture(self._ds[bb])

    def write_async(self, bb, value):
        bid = _inject("io_write", voxels=getattr(value, "size", None))
        _hang("io_write", bid)
        self._ds[bb] = value
        return _ImmediateFuture(None)

    @property
    def attrs(self):
        return dict(self._ds.attrs)

    def update_attrs(self, **kwargs):
        self._ds.attrs.update(kwargs)


class H5Container:
    def __init__(self, path: str, mode: str = "a"):
        if h5py is None:
            raise ImportError("h5py is required for hdf5 containers")
        self.path = path
        self._f = h5py.File(path, mode)

    def create_dataset(self, key, shape, chunks, dtype, compression="gzip", exist_ok=True, fill_value=0):
        if key in self._f:
            if not exist_ok:
                raise ValueError(f"dataset {key} exists")
            ds = self._f[key]
            _check_existing(
                key, ds.shape, ds.dtype, shape, dtype,
                have_chunks=ds.chunks,
                want_chunks=_clamp_chunks(chunks, shape),
            )
            return _H5Dataset(ds)
        ds = self._f.create_dataset(
            key,
            shape=tuple(shape),
            chunks=_clamp_chunks(chunks, shape),
            dtype=dtype,
            compression=compression,
            fillvalue=fill_value,
        )
        return _H5Dataset(ds)

    def require_dataset(self, key, **kwargs):
        return self.create_dataset(key, exist_ok=True, **kwargs)

    def __getitem__(self, key):
        return _H5Dataset(self._f[key])

    def __contains__(self, key):
        return key in self._f

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._f.close()
        return False

    def close(self):
        self._f.close()


class MemoryContainer:
    """In-memory container (tests and tiny pipelines)."""

    _registry: Dict[str, "MemoryContainer"] = {}
    _registry_lock = threading.Lock()

    def __init__(self, path: str = "", mode: str = "a"):
        self.path = path
        self._data: Dict[str, "_MemDataset"] = {}

    @classmethod
    def open(cls, path: str, mode: str = "a") -> "MemoryContainer":
        with cls._registry_lock:
            if path not in cls._registry:
                cls._registry[path] = cls(path)
            return cls._registry[path]

    def create_dataset(self, key, shape, chunks, dtype, compression=None, exist_ok=True, fill_value=0):
        if key in self._data:
            if not exist_ok:
                raise ValueError(f"dataset {key} exists")
            ds = self._data[key]
            _check_existing(
                key, ds.shape, ds.dtype, shape, dtype,
                have_chunks=ds.chunks, want_chunks=chunks,
            )
            return ds
        ds = _MemDataset(np.full(tuple(shape), fill_value, dtype=dtype), tuple(chunks))
        self._data[key] = ds
        return ds

    def require_dataset(self, key, **kwargs):
        return self.create_dataset(key, exist_ok=True, **kwargs)

    def __getitem__(self, key):
        return self._data[key]

    def __contains__(self, key):
        return key in self._data

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def close(self):
        pass


class _MemDataset(_ChecksumOps):
    _read_site = "memory"

    def __init__(self, arr: np.ndarray, chunks: Tuple[int, ...]):
        self._arr = arr
        self.chunks = chunks
        self._attrs: Dict = {}
        self._checksums = _ChecksumIndex(None)
        self._label = "memory://"

    shape = property(lambda self: self._arr.shape)
    dtype = property(lambda self: self._arr.dtype)
    ndim = property(lambda self: self._arr.ndim)

    def _read_back(self, bb):
        return self._arr[bb].copy()

    def _write_raw(self, bb, value):
        self._arr[bb] = value

    def __getitem__(self, bb):
        bid = _inject("io_read")
        _hang("io_read", bid)
        self._apply_read_rot(bb, bid)
        arr = self._arr[bb].copy()
        return self._postread(bb, arr)

    def __setitem__(self, bb, value):
        bid = _inject("io_write", voxels=getattr(value, "size", None))
        _hang("io_write", bid)
        value = np.asarray(value, dtype=self._arr.dtype)
        self._arr[bb] = value
        self._after_write(bb, value, bid)

    def read_async(self, bb):
        bid = _inject("io_read")
        _hang("io_read", bid)
        self._apply_read_rot(bb, bid)
        arr = self._arr[bb].copy()
        return _ImmediateFuture(self._postread(bb, arr))

    def write_async(self, bb, value):
        bid = _inject("io_write", voxels=getattr(value, "size", None))
        _hang("io_write", bid)
        value = np.asarray(value, dtype=self._arr.dtype)
        self._arr[bb] = value
        self._after_write(bb, value, bid)
        return _ImmediateFuture(None)

    @property
    def attrs(self):
        return dict(self._attrs)

    def update_attrs(self, **kwargs):
        self._attrs.update(kwargs)


class HandoffDataset(_ChecksumOps):
    """A ``memory://``-backed handoff twin of a chunked storage dataset
    (docs/PERFORMANCE.md "Task-graph fusion").

    Producer tasks write blocks into host RAM through the same numpy
    dataset surface the storage-backed :class:`Dataset` exposes, and
    consumer tasks resolve the live handle through
    :mod:`cluster_tools_tpu.runtime.handoff` instead of opening the store —
    the producer->consumer hop skips the storage round-trip entirely.

    Contracts preserved from the storage path:

    - **fault hooks** — every boundary method carries the ``io_read`` /
      ``io_write`` injection + hang hooks (CT004), so chaos reaches the
      in-memory data plane exactly like the storage one,
    - **integrity** — writes record in-memory CRC32 region digests
      (``verify_region`` / the executor's ``region_verifier`` work
      unchanged, including the injected silent-corruption path),
    - **spill** — :meth:`spill` flushes the array chunk-by-chunk through
      the real dataset's write path (digest sidecars recorded per region,
      each region verified back), then delegates every subsequent access
      to the stored copy and releases the RAM.  After a spill, storage is
      the single source of truth.
    """

    _read_site = "handoff"

    def __init__(self, shape, chunks, dtype, store_factory, label: str,
                 fill_value: int = 0):
        shape = tuple(int(s) for s in shape)
        self._arr = np.full(shape, fill_value, dtype=np.dtype(dtype))
        self.chunks = _clamp_chunks(chunks, shape)
        self._checksums = _ChecksumIndex(None)
        self._label = label
        self._store_factory = store_factory
        self._spilled_ds = None
        self._spill_state_lock = threading.Lock()
        self._spill_started = False
        # accumulated bytes counted into the process-wide bytes_not_stored
        # counter; a later spill reconciles them (they DID reach storage)
        self.not_stored_bytes = 0

    # every accessor SNAPSHOTS self._arr before branching: a concurrent
    # spill publishes the storage delegate and then drops the array, so a
    # reader must hold its own reference (the snapshot's bytes stay valid
    # under GC) instead of re-reading the attribute after the check

    @property
    def shape(self):
        arr = self._arr
        return tuple(arr.shape) if arr is not None else self._spilled_ds.shape

    @property
    def dtype(self):
        arr = self._arr
        return arr.dtype if arr is not None else self._spilled_ds.dtype

    ndim = property(lambda self: len(self.shape))

    @property
    def nbytes(self) -> int:
        arr = self._arr
        return 0 if arr is None else int(arr.nbytes)

    def _handoff_counters(self):
        from ..runtime import handoff as _h

        return _h.get_registry()

    def _read_back(self, bb):
        arr = self._arr
        if arr is None:
            return self._spilled_ds._read_back(bb)
        return arr[bb].copy()

    def _write_raw(self, bb, value):
        arr = self._arr
        if arr is None:
            self._spilled_ds._write_raw(bb, value)
        else:
            arr[bb] = value

    def __getitem__(self, bb):
        arr = self._arr
        if arr is None:
            return self._spilled_ds[bb]
        bid = _inject("io_read")
        _hang("io_read", bid)
        self._apply_read_rot(bb, bid)
        out = arr[bb].copy()
        return self._postread(bb, out)

    def __setitem__(self, bb, value):
        arr = self._arr
        if arr is None:
            self._spilled_ds[bb] = value
            return
        bid = _inject("io_write", voxels=getattr(value, "size", None))
        _hang("io_write", bid)
        value = np.asarray(value, dtype=arr.dtype)
        arr[bb] = value
        self._after_write(bb, value, bid)
        self.not_stored_bytes += int(value.nbytes)
        self._handoff_counters().bump("bytes_not_stored", int(value.nbytes))

    def read_async(self, bb):
        arr = self._arr
        if arr is None:
            return self._spilled_ds.read_async(bb)
        bid = _inject("io_read")
        _hang("io_read", bid)
        self._apply_read_rot(bb, bid)
        out = arr[bb].copy()
        return _ImmediateFuture(self._postread(bb, out))

    def write_async(self, bb, value):
        arr = self._arr
        if arr is None:
            return self._spilled_ds.write_async(bb, value)
        bid = _inject("io_write", voxels=getattr(value, "size", None))
        _hang("io_write", bid)
        value = np.asarray(value, dtype=arr.dtype)
        arr[bb] = value
        self._after_write(bb, value, bid)
        self.not_stored_bytes += int(value.nbytes)
        self._handoff_counters().bump("bytes_not_stored", int(value.nbytes))
        return _ImmediateFuture(None)

    def verify_region(self, bb) -> None:
        if self._arr is None:
            verify = getattr(self._spilled_ds, "verify_region", None)
            if verify is not None:
                verify(bb)
            return
        super().verify_region(bb)

    def spill(self) -> int:
        """Flush to the storage spill path and delegate from now on.
        Chunk-aligned regions go through the real dataset's write path (one
        digest sidecar per region, like any block store) and are verified
        back, so the stored copy is checksummed before the RAM is released.
        Returns the bytes freed (0 when already spilled/spilling)."""
        with self._spill_state_lock:
            if self._spill_started:
                return 0
            self._spill_started = True
        try:
            arr = self._arr
            ds = self._store_factory()
            regions = []
            ranges = [
                range(0, s, c) for s, c in zip(arr.shape, self.chunks)
            ]
            for begin in itertools.product(*ranges):
                bb = tuple(
                    slice(b, min(b + c, s))
                    for b, c, s in zip(begin, self.chunks, arr.shape)
                )
                ds[bb] = arr[bb]
                regions.append(bb)
            verify = getattr(ds, "verify_region", None)
            if verify is not None:
                for bb in regions:
                    verify(bb)
        except BaseException:
            # a half-written flush must stay retriable: release the guard
            # so the NEXT attempt re-writes every region — otherwise a
            # retry would short-circuit to "done" over a storage copy with
            # fill-value holes
            with self._spill_state_lock:
                self._spill_started = False
            raise
        freed = int(arr.nbytes)
        # the spilled copy keeps the handoff's product identity: reads
        # from it carry the "spill" corruption site, and the producer's
        # missing-sidecar policy travels with the data
        try:
            ds._read_site = "spill"
            pol = getattr(self, "_product_policy", None)
            if pol is not None:
                ds._product_policy = pol
        except AttributeError:
            pass
        # publish the delegate before dropping the array: concurrent
        # readers hold either the array ref (still valid bytes) or see the
        # stored copy — never neither
        self._spilled_ds = ds
        self._arr = None
        return freed

    @property
    def attrs(self) -> Dict:
        ds = self._spilled_ds
        return ds.attrs if ds is not None else {}

    def update_attrs(self, **kwargs) -> None:
        ds = self._spilled_ds
        if ds is None:
            raise RuntimeError(
                "in-memory handoff datasets carry no attribute store"
            )
        ds.update_attrs(**kwargs)


def open_container(path: str, mode: str = "a"):
    """Open a container by extension (SURVEY.md: ``vu.file_reader``)."""
    if path.startswith("memory://"):
        return MemoryContainer.open(path, mode)
    lower = path.lower()
    if lower.endswith(_ZARR_EXTS):
        return ZarrContainer(path, mode)
    if lower.endswith(_H5_EXTS):
        return H5Container(path, mode)
    raise ValueError(
        f"cannot infer container format from {path!r} "
        f"(expected one of {_ZARR_EXTS + _H5_EXTS} or memory://)"
    )
