"""Device-mesh construction.

The reference mapped blocks to slurm array jobs (``BaseClusterTask.
prepare_jobs``, SURVEY.md §2a); here the "cluster" is a ``jax.sharding.Mesh``.
Two axes cover this framework's parallelism:

- ``dp`` — data parallel over independent volumes / block batches,
- ``sp`` — spatial parallel: contiguous slabs of one volume, with halo
  exchange and label-merge collectives over ICI (the analogue of sequence /
  context parallelism for 3-D space, SURVEY.md §5.7).

Multi-host pods extend the same mesh over DCN via ``jax.distributed`` — the
mesh abstraction is identical, only the device list grows.  See
:mod:`~cluster_tools_tpu.parallel.multihost` for the wiring
(``initialize`` + ``pod_mesh``) and the local fake-pod launcher the tests
use.
"""

from __future__ import annotations

import logging
import os
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

logger = logging.getLogger(__name__)

#: default home of JAX's persistent compile cache: a fixed path inside the
#: checkout (the path is part of the cache key, so it must never move)
_DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Place JAX's persistent compile cache; returns the directory in use.

    Called by the entry points (``cli``, ``serve`` — and so by every fleet
    member — and ``bench.py``) before their first compile, never at import.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
    sets nothing; otherwise the cache lives at ``<checkout>/.jax_cache``.
    The tiled watershed takes minutes to compile for the chip; without
    this every ``cli run`` and every server restart pays that again.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_COMPILE_CACHE)
    return _DEFAULT_COMPILE_CACHE


def use_cpu_backend(why: str) -> None:
    """The entry points' explicit choice of the CPU backend for a process
    whose work does not target the accelerator (``target != "tpu"``, a
    server started without ``--tpu``).  Must run before the first backend
    initialisation.  Said out loud when it overrides what ``JAX_PLATFORMS``
    / JAX's default would have picked — kernel selection keys on
    ``jax.default_backend()``, so a process that computes on CPU devices
    must also have the CPU as its default backend."""
    if jax.config.jax_platforms != "cpu":
        logger.warning(
            "%s: computing on the CPU backend (jax_platforms=cpu); use "
            "target 'tpu' / --tpu to compute on the accelerator", why,
        )
        jax.config.update("jax_platforms", "cpu")


def _pick_grid(n: int, n_axes: int) -> Tuple[int, ...]:
    """Factor ``n`` devices into a mesh grid, favoring the last (sp) axis."""
    if n_axes == 1:
        return (n,)
    # give sp (last axis) the largest power-of-two factor, dp the rest
    sp = 1
    m = n
    while m % 2 == 0 and sp < n // 2:
        sp *= 2
        m //= 2
    if sp == 1:
        sp = n  # odd n: everything on sp, dp=1
    dp = n // sp
    grid = [1] * n_axes
    grid[-1] = sp
    grid[0] = dp
    return tuple(grid)


def backend_devices(target: str = "local", n_devices: Optional[int] = None):
    """Devices for a mesh: ``local`` = CPU (the fake-cluster test backend,
    honoring ``xla_force_host_platform_device_count``), ``tpu`` = TPU chips.
    Neither falls back to the other's devices: a target whose backend is
    not there raises."""
    if target == "tpu":
        devs = [d for d in jax.devices() if d.platform == "tpu"]
        if not devs:
            raise RuntimeError(
                "target='tpu' but no TPU devices are visible (jax found "
                f"{sorted({d.platform for d in jax.devices()})})"
            )
    elif target == "local":
        devs = jax.devices("cpu")
    else:
        raise ValueError(f"unknown target {target!r}")
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devs)} available"
            )
        devs = devs[:n_devices]
    return devs


def describe_devices(devices) -> list:
    """``["tpu:0", ...]`` — how the tasks' logs name the devices a sweep or
    a mesh ran on (``executor.devices=`` / ``mesh.devices=``)."""
    return [f"{d.platform}:{d.id}" for d in np.asarray(devices).flat]


def device_peak_bytes(devices) -> Dict[str, Dict[str, int]]:
    """Peak device memory per device so far in this process, from
    ``memory_stats()``: ``peak_bytes_in_use`` (the allocator's) and
    ``peak_bytes_reserved`` (on the TPU runtime a loaded program's
    temporaries are reserved beside the allocator, not allocated from it,
    so the first alone leaves out most of what a step holds).  Empty where
    the backend reports neither, as on the CPU.  The tasks put it into
    their success manifest as ``device_memory`` after a sweep or a mesh
    step, so that "everything landed on device 0" is visible."""
    peaks = {}
    for name, d in zip(describe_devices(devices), np.asarray(devices).flat):
        stats = d.memory_stats() or {}
        row = {k: int(stats[k])
               for k in ("peak_bytes_in_use", "peak_bytes_reserved")
               if k in stats}
        if row:
            peaks[name] = row
    return peaks


def make_mesh(
    n_devices: Optional[int] = None,
    axis_names: Sequence[str] = ("dp", "sp"),
    grid: Optional[Sequence[int]] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a mesh over the available devices.

    ``grid`` pins the per-axis sizes; otherwise devices are factored so the
    spatial axis gets the largest power-of-two share (halo exchange and the
    label-merge all_gather ride the densest axis).
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devices)} available"
            )
        devices = devices[:n_devices]
    n = len(devices)
    if grid is None:
        grid = _pick_grid(n, len(axis_names))
    if int(np.prod(grid)) != n:
        raise ValueError(f"grid {grid} does not cover {n} devices")
    dev_array = np.array(devices).reshape(grid)
    return Mesh(dev_array, tuple(axis_names))


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


#: the reduce tree's sibling axis: tree groups of one level are dealt over
#: this 1-D mesh and their labels exchanged with an in-program all_gather
#: (docs/PERFORMANCE.md "Collective reduce plane")
SIBLING_AXIS = "sib"


def sibling_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over every visible device, axis :data:`SIBLING_AXIS` — the
    collective reduce plane's hop fabric.  In-process this spans the local
    (possibly ``xla_force_host_platform_device_count`` virtual) devices; in
    a ``jax.distributed`` pod it spans the global device list, so the same
    level program moves the boundary packets over ICI/DCN instead of the
    filesystem."""
    return make_mesh(n_devices=n_devices, axis_names=(SIBLING_AXIS,))
