"""Compiled programs, kept: in the process and beside the compile cache.

Two kinds of program live here: the fused mesh step (``tasks/fused.py``,
:func:`step_for`) and the executor's sharded sweep programs
(``runtime/executor.py``, :func:`program_for`; the two-pass watershed's
``jit_sharded_ws_block`` / ``jit_sharded_ws_block_seeded`` among them).
Both used to be made afresh for every job, so every job traced and lowered
each program to find out which persistent-cache entry was its own, then
read that entry back.  Here a compiled program gets a key made of what it
is *built from* (computable in milliseconds, nothing traced), and is looked
up under it before anything is traced:

1. in the process: a two-entry LRU (``runtime/executor.py::ProgramCache``;
   two, because a loaded program's temporaries stay reserved on the chip;
   the two passes' programs fill it);
2. in the step store, ``<compile cache dir>/steps/<key>``: the executable as
   ``jax.experimental.serialize_executable`` writes it (compressed, as
   JAX's own entries are), with its trees and the key document, loaded
   straight onto the mesh's devices;
3. else built as before (``make_ws_ccl_step(...).lower(x).compile()``,
   ``batched_shard_map(...).lower(*xs).compile()``, through JAX's own
   persistent cache) and written to the store.

A sweep program comes here only where its kernel's identity freezes
(``runtime/executor.py::kernel_identity``: code and captured values, all
plain), and its key holds a digest of that identity: two kernels that read
the same values share a program, a kernel that reads another threshold or
capacity does not, and a kernel that captures an array or a dataset stays
in its executor's own cache.  What the process keeps is the compiled
program, which holds nothing a task owns.

The store exists where the process has a persistent compile cache directory
and nowhere else; nothing switches it.  It keeps :data:`STORE_STEPS` = 4
entries: one tree's one-chip cells write three (the fused step, shared by
``fused384.volumes`` and ``multicut384.volumes``, and the two sweep
programs of ``twopass125.volumes``).  Every way out of it is the build: a
missing, truncated, foreign or mismatching entry, a ``serialize`` or a load
that raises all end in a built program and an overwritten entry.  A wrong
hit would be silently wrong labels, so the key document holds everything
that reaches the lowering (:func:`key_document`) and is compared field by
field on a hit, not only by its hash.  The key sees *files* and captured
values: code patched in memory (a test's ``monkeypatch`` of ``ops/*``) is
invisible to it, so such a test runs its jobs with no store and calls
:func:`forget` around them (``tests/helpers.py::programs_built_here``).
Clearing the store by hand is deleting ``<compile cache dir>/steps/``.
"""

from __future__ import annotations

import contextlib
import functools
import glob
import hashlib
import json
import os
import pickle
import threading
import time
import zlib
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np

from ..runtime import trace as trace_mod

#: the package whose sources the key digests
PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: ready programs a process keeps: a loaded program's temporaries stay
#: reserved on the chip (4.8 GB for the 384^3 step)
PROCESS_STEPS = 2
#: entries the store keeps (80-100 MiB each at 384^3); JAX's own size
#: bound does not see these files
STORE_STEPS = 4
#: a writer's temp file older than this was left by a killed process
_STALE_TEMP_S = 600.0

_lock = threading.Lock()
_counters = {"process_hits": 0, "store_hits": 0, "builds": 0, "fallbacks": 0}
_process = None   # the process level, made on first use


@functools.lru_cache(maxsize=None)
def package_digest(root: str = PACKAGE_ROOT) -> str:
    """sha256 over every ``*.py`` under ``root`` (relative path + bytes),
    made once a process."""
    h = hashlib.sha256()
    for path in sorted(glob.glob(os.path.join(root, "**", "*.py"), recursive=True)):
        with open(path, "rb") as f:
            h.update(os.path.relpath(path, root).encode() + b"\0" + f.read() + b"\0")
    return h.hexdigest()


def _describe(x) -> dict:
    return {"shape": list(x.shape), "dtype": str(np.dtype(x.dtype)),
            "spec": list(x.sharding.spec)}


def key_document(mesh, x, execution: str, build_args: dict,
                 package_root: str = PACKAGE_ROOT) -> dict:
    """Everything that can change the program compiled for input ``x`` (an
    array or a ``jax.ShapeDtypeStruct`` with its ``NamedSharding``; a tuple
    of them for a program of several inputs), as plain JSON: the package's
    sources, JAX and the backend's build, the mesh (axis names, shape,
    device ids in order), the inputs (shape, dtype, partition spec),
    ``execution`` and every argument of the program's builder, what the
    kernel switches resolved to (``ops/tile_ws.py::resolved_modes`` of the
    builder's ``impl``, which carries ``CT_FILL_MODE``; a builder without
    one is an executor kernel, whose own ``impl`` is in its identity and
    whose resolution reads what ``"auto"``'s does), and what else reaches
    the lowering from outside the arguments."""
    import jaxlib

    from ..ops.tile_ws import resolved_modes

    devices = list(mesh.devices.flat)
    client = devices[0].client
    inputs = ({"inputs": [_describe(a) for a in x]} if isinstance(x, tuple)
              else {"input": _describe(x)})
    doc = {
        "sources": package_digest(package_root),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": client.platform,
        "platform_version": client.platform_version,
        "device_kind": devices[0].device_kind,
        "mesh": {"axis_names": list(mesh.axis_names),
                 "shape": list(mesh.devices.shape),
                 "device_ids": [d.id for d in devices]},
        **inputs,
        "execution": execution,
        "build": dict(build_args),
        # "auto" resolves by jax.default_backend(), not by the mesh's devices
        "modes": resolved_modes(build_args.get("impl", "auto")),
        "lowering": {
            "jax_enable_x64": bool(jax.config.jax_enable_x64),
            "jax_default_matmul_precision": jax.config.jax_default_matmul_precision,
            "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""),
            "LIBTPU_INIT_ARGS": os.environ.get("LIBTPU_INIT_ARGS", ""),
        },
    }
    # as it reads back from an entry: tuples are lists, keys are strings
    return json.loads(json.dumps(doc, sort_keys=True))


def digest(document: dict) -> str:
    text = json.dumps(document, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(text.encode()).hexdigest()[:32]


def store_dir() -> Optional[str]:
    """``<compile cache dir>/steps`` where this process has a persistent
    compile cache, else None: observed from ``jax.config``, no switch."""
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir or not jax.config.jax_enable_compilation_cache:
        return None
    return os.path.join(cache_dir, "steps")


class Unusable(Exception):
    """An entry that is there and cannot be used; ``str()`` names why."""


def _codec():
    """``(name, compress, decompress)``: zstandard where it is installed
    (what JAX's cache uses then), else zlib.  A serialized TPU executable
    shrinks severalfold; an entry names its codec."""
    try:
        import zstandard
    except ImportError:
        return "zlib", lambda raw: zlib.compress(raw, 1), zlib.decompress
    return ("zstd", zstandard.ZstdCompressor().compress,
            zstandard.ZstdDecompressor().decompress)


def load(directory: str, key: str, document: dict, x, note=lambda **kw: None
         ) -> Tuple[object, int]:
    """The entry ``key`` as a loaded ``jax.stages.Compiled`` for ``x``'s
    devices (``x`` an input or a tuple of them, as for
    :func:`key_document`), and the entry's bytes; ``(None, 0)`` where there
    is none.  Raises :class:`Unusable` for an entry that cannot be trusted.
    ``note`` takes the seconds of the pieces (read, decompress, deserialize
    + load)."""
    from jax.experimental.serialize_executable import deserialize_and_load

    path = os.path.join(directory, key)
    t0 = time.monotonic()
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except FileNotFoundError:
        return None, 0
    except OSError as e:
        raise Unusable(f"unreadable:{type(e).__name__}")
    try:
        # the store holds only what save() wrote, as JAX's cache beside it
        entry = pickle.loads(raw)
        stored, packed = entry["key_document"], entry["executable"]
        trees = entry["in_tree"], entry["out_tree"]
        codec, crc = entry["codec"], entry["crc32"]
    except Exception as e:   # truncated, or not an entry at all
        raise Unusable(f"unreadable:{type(e).__name__}")
    if stored != document:
        fields = sorted(set(stored) | set(document)) if isinstance(stored, dict) else []
        differs = [k for k in fields if stored.get(k) != document.get(k)]
        raise Unusable("key_mismatch:" + ",".join(differs or ["document"]))
    name, _, decompress = _codec()
    if codec != name:
        raise Unusable(f"unreadable:codec_{codec}")
    if zlib.crc32(packed) != crc:
        raise Unusable("damaged:crc32")
    t1 = time.monotonic()
    try:
        payload = decompress(packed)
    except Exception as e:
        raise Unusable(f"damaged:{type(e).__name__}")
    t2 = time.monotonic()
    xs = x if isinstance(x, tuple) else (x,)
    devices = list(xs[0].sharding.mesh.devices.flat)
    try:
        step = deserialize_and_load(payload, *trees, backend=devices[0].client,
                                    execution_devices=devices)
        expects, _ = step.input_shardings
    except Exception as e:   # whatever the backend refuses: build instead
        raise Unusable(f"load:{type(e).__name__}")
    if len(expects) != len(xs) or not all(
            e.is_equivalent_to(a.sharding, a.ndim) for e, a in zip(expects, xs)):
        raise Unusable("load:input_sharding")
    note(read_s=round(t1 - t0, 6), decompress_s=round(t2 - t1, 6),
         deserialize_load_s=round(time.monotonic() - t2, 6),
         executable_bytes=len(payload))
    with contextlib.suppress(OSError):
        os.utime(path)   # the store keeps its most recently used entries
    return step, len(raw)


def save(directory: str, key: str, document: dict, compiled) -> int:
    """Write ``compiled`` as entry ``key`` (temp + ``os.replace``: a reader
    sees the old entry, the new one, or none) and prune the store to its
    :data:`STORE_STEPS` most recently used entries.  Returns the bytes."""
    from jax.experimental.serialize_executable import serialize

    payload, in_tree, out_tree = serialize(compiled)
    codec, compress, _ = _codec()
    packed = compress(payload)
    raw = pickle.dumps({"key_document": document, "in_tree": in_tree,
                        "out_tree": out_tree, "codec": codec,
                        "executable": packed, "crc32": zlib.crc32(packed)},
                       protocol=5)
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, key)
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        with open(tmp, "wb") as f:
            f.write(raw)
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise
    _prune(directory)
    return len(raw)


def _prune(directory: str) -> None:
    entries, now = [], time.time()
    for name in os.listdir(directory):
        path = os.path.join(directory, name)
        with contextlib.suppress(OSError):   # another process prunes too
            mtime = os.stat(path).st_mtime
            if ".tmp." not in name:
                entries.append((mtime, path))
            elif now - mtime > _STALE_TEMP_S:
                os.unlink(path)
    for _, path in sorted(entries)[:-STORE_STEPS]:
        with contextlib.suppress(OSError):
            os.unlink(path)


def _process_level():
    global _process
    with _lock:
        if _process is None:
            # runtime/executor.py imports parallel/ at its own import
            from ..runtime.executor import ProgramCache

            _process = ProgramCache(PROCESS_STEPS)
        return _process


def forget() -> None:
    """Drop every ready program of the process: for tests that patch the
    program in memory, which the key cannot see (the store they switch off
    by running without a persistent compile cache)."""
    global _process
    with _lock:
        _process = None


def step_for(mesh, x, execution: str, builder: Callable, build_args: dict
             ) -> Tuple[Callable, Dict[str, object]]:
    """The ready step ``builder(mesh, **build_args)`` for input ``x``: from
    the process, else (``execution="fused"``: the one-program step) from the
    store, else built and compiled for ``x``; the split chain is four
    programs and keeps the process level only.  Returns ``(step, info)``
    with ``info`` = ``{from, key, load_s, store_bytes, fallback}`` as the
    task's manifest carries it; spans ``fused.step_load`` /
    ``fused.step_build`` / ``fused.step_store`` lie around the three pieces
    of work."""
    return _ready(key_document(mesh, x, execution, build_args), "fused.step",
                  lambda: builder(mesh, **build_args),
                  (x,) if execution == "fused" else None)


def program_for(mesh, xs: tuple, kernel_digest: str, batch: int,
                builder: Callable) -> Tuple[Callable, Dict[str, object]]:
    """The executor's sharded sweep program ``builder()`` (a jitted
    function of the stacked batch ``xs``) compiled for ``xs``: from the
    process, else from the store, else built; ``kernel_digest`` is
    :func:`~cluster_tools_tpu.runtime.executor.identity_digest` of the
    kernel it maps.  Returns ``(program, info)`` as :func:`step_for` does;
    spans ``executor.program_load`` / ``_build`` / ``_store``."""
    document = key_document(mesh, tuple(xs), "sharded",
                            {"kernel": kernel_digest, "batch": int(batch)})
    return _ready(document, "executor.program", builder, tuple(xs))


def _ready(document: dict, span: str, build: Callable, compile_for
           ) -> Tuple[Callable, Dict[str, object]]:
    """The lookup behind :func:`step_for` and :func:`program_for`: the
    process level under the document's digest, then (where ``compile_for``,
    the arguments to compile for, is given) the store, then ``build()``,
    compiled for ``compile_for`` and written to the store."""
    key = digest(document)
    info = {"from": "process", "key": key, "load_s": 0.0, "store_bytes": 0,
            "fallback": None}

    def on_miss():
        directory = store_dir() if compile_for is not None else None
        step = None
        if directory is not None:
            step = _read_store(directory, key, document, compile_for, info, span)
        if step is not None:
            info["from"] = "store"
            return step
        info["from"] = "built"
        compiles = trace_mod.compile_snapshot()
        with trace_mod.span(span + "_build", key=key):
            step = build()
            if compile_for is not None:
                step = step.lower(*compile_for).compile()
        if directory is not None:
            handed_over = trace_mod.compile_delta(compiles)["cache_hits"] > 0
            _write_store(directory, key, document, step, handed_over, info, span)
        return step

    step = _process_level().get_or_build(None, span, (key,), on_miss)
    counter = {"process": "process_hits", "store": "store_hits",
               "built": "builds"}[info["from"]]
    with _lock:
        _counters[counter] += 1
        _counters["fallbacks"] += info["fallback"] is not None
    return step, info


def _read_store(directory: str, key: str, document: dict, xs: tuple,
                info: dict, span: str):
    """The entry as a loaded program, or None with ``info["fallback"]``
    naming why an entry that is there cannot be used."""
    step = None
    with trace_mod.begin(span + "_load", key=key) as sp:
        try:
            step, info["store_bytes"] = load(directory, key, document, xs,
                                             note=sp.note)
            sp.note(nbytes=info["store_bytes"])
        except Unusable as e:
            info["fallback"] = str(e)
    info["load_s"] = round(sp.elapsed_s, 6)
    return step


def _write_store(directory: str, key: str, document: dict, step,
                 handed_over: bool, info: dict, span: str) -> None:
    """Write the built step as entry ``key``; where that cannot be done,
    ``info["fallback"]`` says why (after the reason the store was left
    for, if there was one), and the entry that could not be used goes.
    ``handed_over``: JAX's persistent cache gave the build an executable it
    had deserialized.  XLA's CPU client cannot serialize such a one again:
    what it writes lacks the compiled functions, loads all the same, and
    fails when it runs (``tests/test_fused_step_cache.py`` holds the
    trial); the TPU's can (PERF.md section 6, PR 34).  Compiling a trial
    program here instead would be a compile inside a job."""
    if handed_over and step.runtime_executable().client.platform == "cpu":
        not_stored = "store:deserialized_executable"
    else:
        not_stored = None
        with trace_mod.span(span + "_store", key=key) as sp:
            try:
                info["store_bytes"] = save(directory, key, document, step)
                sp.note(nbytes=info["store_bytes"])
            except Exception as e:   # serialize refuses hoisted constants
                not_stored = f"store:{type(e).__name__}"
    if not_stored is not None:
        if info["fallback"] is not None:
            with contextlib.suppress(OSError):
                os.unlink(os.path.join(directory, key))
        info["fallback"] = ";".join(filter(None, (info["fallback"], not_stored)))


def totals() -> Dict[str, int]:
    """Look-ups of this process so far by the level that answered, and how
    many of them left the store for the build with a reason."""
    with _lock:
        return dict(_counters)


def delta(snap: Dict[str, int]) -> Dict[str, int]:
    """What moved since ``snap`` (a :func:`totals`), as ``io_metrics.json``
    carries it per task under ``step_cache``; empty where no step was
    looked up."""
    now = totals()
    moved = {k: now[k] - snap[k] for k in now}
    return moved if any(moved.values()) else {}
