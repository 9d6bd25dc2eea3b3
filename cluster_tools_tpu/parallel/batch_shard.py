"""Batch-sharded sweep execution: one compiled program per block batch.

``BlockwiseExecutor.map_blocks`` historically compiled ``jit(vmap(kernel))``
at width ``n_devices * device_batch`` — on a single-device host that is one
compiled dispatch *per block*, serialized behind the XLA dispatch lock, so
dispatch + host-sync overhead caps sweep throughput far below memory
bandwidth (ROADMAP item 2).  This module supplies the sharded alternative,
the standard TPU-native shape (the fluid-flow TPU framework of
arXiv:2108.11076 runs its whole grid as one sharded program per step):

- :func:`batched_shard_map` — a whole Morton batch of blocks becomes ONE
  compiled program over the named device mesh: ``shard_map`` splits the
  stacked batch axis across devices and ``vmap`` runs the per-block kernel
  over each device's sub-batch.  The dispatch lock is held once per batch
  instead of once per block.
- :func:`exchange_batch_halo` — device-side halo exchange along the batch
  axis for batches whose blocks form a contiguous run along one spatial
  axis (slab sweeps): each block's halo is reconstructed from its batch
  neighbor's resident data (local slicing inside a device's sub-batch, one
  ``ppermute`` across device boundaries — the :mod:`.halo` pattern applied
  to the batch axis), so interior halos never touch storage at all.
- :func:`sharded_slab_sweep` — a reference driver for the slab-run case:
  host reads load each slab ONCE (no overlapping reads); the sharded
  program rebuilds every interior halo on device, bit-identical to
  per-block overlapped reads.

The generic executor path stacks halo'd outer regions host-side (the
decompressed-chunk cache already dedups the overlapping halo reads, see
docs/PERFORMANCE.md "Chunk-aware I/O"); the device-side exchange is the
further step for contiguous-run sweeps where even the cache lookup can be
skipped.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..compat import shard_map
from . import device_pool as device_pool_mod


def mesh_n_devices(mesh: Mesh) -> int:
    return int(np.prod(mesh.devices.shape))


def slab_sweep_device_feed_ok(
    shape: Sequence[int], extent: int, halo: int
) -> bool:
    """True when the batch geometry allows the inner-only-load device feed:
    axis-0 decomposes into whole slabs of ``extent`` (no ragged tail — tails
    would need per-block host reads anyway) and the halo fits inside one
    slab so :func:`exchange_batch_halo` can rebuild every interior halo from
    batch-neighbor data alone."""
    size = int(shape[0])
    return (
        extent > 0
        and 0 <= halo <= extent
        and size >= extent
        and size % extent == 0
    )


def resolve_sharded_batch(
    n_devices: int,
    base_batch: int,
    sharded_batch: Optional[int] = None,
) -> int:
    """The sharded batch width: ``sharded_batch`` (rounded up to a device
    multiple), or a default of ``max(2 * base_batch, 8)`` — big enough that
    dispatch overhead amortizes, always divisible by the mesh size so every
    device holds an equal sub-batch."""
    if sharded_batch is not None:
        b = max(1, int(sharded_batch))
    else:
        b = max(2 * int(base_batch), 8)
    b = max(b, n_devices)
    return ((b + n_devices - 1) // n_devices) * n_devices


def use_sharded_sweep(
    sweep_mode: str, n_devices: int, n_blocks: int, batch: int
) -> bool:
    """Resolve the ``sweep_mode`` knob: ``"sharded"`` / ``"per_block"``
    force a path; ``"auto"`` picks sharded when the mesh has >= 2 devices
    (per-block dispatch would leave all but one idle behind the dispatch
    lock) or the sweep has at least one full sharded batch of blocks (the
    dispatch-amortization regime) — single-block sweeps stay per-block."""
    if sweep_mode == "per_block":
        return False
    if sweep_mode == "sharded":
        return True
    if sweep_mode == "auto":
        return n_blocks > 1 and (n_devices >= 2 or n_blocks >= batch)
    raise ValueError(
        f"unknown sweep_mode {sweep_mode!r} "
        "(expected 'auto', 'sharded' or 'per_block')"
    )


def batched_shard_map(
    kernel: Callable,
    mesh: Mesh,
    batch: int,
    axis_name: str = "blocks",
    check_vma: bool = False,
):
    """One compiled dispatch for a stacked batch of blocks, sharded over
    ``mesh``.

    ``kernel`` is the per-block function; the returned callable takes the
    same arguments stacked to ``[batch, ...]`` and runs ``vmap(kernel)``
    over each device's ``batch / n_devices`` sub-batch inside one
    ``shard_map`` program — the whole batch is a single XLA execution, so
    the executor's dispatch lock is held once per batch instead of once per
    block.  Per-lane numerics are those of ``vmap``, independent of the
    batch width, which is what makes the sharded sweep bit-identical to the
    per-block path (asserted by tests/test_sharded.py and ``bench.py
    --sweep``).

    ``check_vma=False`` for the same reason as ``parallel/pipeline.py``:
    kernels carrying ``while_loop``/pallas bodies trip the static
    replication checker; only the advisory check is off, the collectives (none here unless the kernel
    adds them) are unaffected.
    """
    n = mesh_n_devices(mesh)
    batch = int(batch)
    if batch % n:
        raise ValueError(
            f"sharded batch {batch} is not divisible by the {n}-device mesh"
        )

    def _sharded_batch_body(*args):
        return jax.vmap(kernel)(*args)

    # the compiled program carries its kernel's name (``jit_sharded_<name>``
    # on a trace's "XLA Modules" line), so that two sweeps of one job can be
    # told apart there
    _sharded_batch_body.__name__ = "sharded_" + getattr(
        kernel, "__name__", "kernel"
    )
    spec = P(axis_name)
    return jax.jit(
        shard_map(
            _sharded_batch_body,
            mesh=mesh,
            in_specs=spec,
            out_specs=spec,
            check_vma=check_vma,
        )
    )


def ragged_shard_map(
    kernel: Callable,
    mesh: Mesh,
    batch: int,
    specs: Sequence,
    axis_name: str = "blocks",
    check_vma: bool = False,
):
    """One compiled dispatch for a *ragged* (mixed-shape) batch of blocks,
    driven by the paged block pool's descriptors (:mod:`.block_pool`,
    docs/PERFORMANCE.md "Ragged sweeps").

    ``specs`` is one :class:`~cluster_tools_tpu.parallel.block_pool.
    RaggedArgSpec` per kernel argument.  The returned callable takes, in
    order: one page pool ``[pool_pages, *page_shape]`` per arg (replicated
    to every device), then per arg a page table ``[batch, pages_per_lane]``
    and a valid-extent array ``[batch, ndim]`` (both sharded over the
    batch axis).  Inside one ``shard_map`` program each device vmaps over
    its lanes: a lane gathers its pages from the pool, reassembles the
    dense page-aligned array, masks everything beyond its valid extent
    with the spec's fill value, and runs the kernel — so the Ragged Paged
    Attention shape (fixed pages + ragged metadata, arXiv:2604.15464)
    executes variable-shape block work as ONE XLA execution.

    The reconstruction is pure value movement (gather / reshape /
    transpose / select — no arithmetic), so a lane's kernel input is
    bit-equal to the host-padded array the dense path would have built at
    the same padded shape; per-lane numerics are ``vmap``'s, independent
    of the batch width, which is what keeps the ragged path bit-identical
    to per-block execution on the lanes' stored regions
    (tests/test_ragged.py).  ``check_vma=False`` for the same reason as
    :func:`batched_shard_map`.
    """
    n = mesh_n_devices(mesh)
    batch = int(batch)
    if batch % n:
        raise ValueError(
            f"ragged batch {batch} is not divisible by the {n}-device mesh"
        )
    specs = tuple(specs)

    def _reassemble(pool, table, valid, spec):
        nd = len(spec.grid)
        pages = pool[table]  # [pages_per_lane, *page_shape]
        # grid-major tiles -> dense: (g0..gd, p0..pd) interleaved to
        # (g0, p0, g1, p1, ...) then flattened per axis
        x = pages.reshape(spec.grid + spec.page_shape)
        perm = []
        for ax in range(nd):
            perm.extend((ax, nd + ax))
        x = x.transpose(perm).reshape(spec.padded_shape)
        mask = None
        for ax in range(nd):
            m = lax.broadcasted_iota(
                jnp.int32, spec.padded_shape, ax
            ) < valid[ax]
            mask = m if mask is None else (mask & m)
        fill = jnp.asarray(spec.fill, x.dtype)
        return jnp.where(mask, x, fill)

    def _sharded_body(*flat):
        pools = flat[: len(specs)]
        lanes = flat[len(specs):]  # (table, valid) per arg

        def _lane(*lane_flat):
            args = []
            for i, spec in enumerate(specs):
                table, valid = lane_flat[2 * i], lane_flat[2 * i + 1]
                args.append(_reassemble(pools[i], table, valid, spec))
            return kernel(*args)

        # pools are closed over (vmap broadcasts them across lanes)
        return jax.vmap(_lane)(*lanes)

    spec_in = (
        tuple(P() for _ in specs)
        + tuple(P(axis_name) for _ in specs for _ in range(2))
    )
    return jax.jit(
        shard_map(
            _sharded_body,
            mesh=mesh,
            in_specs=spec_in,
            out_specs=P(axis_name),
            check_vma=check_vma,
        )
    )


def exchange_batch_halo(
    x: jnp.ndarray,
    halo: int,
    axis: int,
    axis_name: str,
    axis_size: int,
    lo_edge: Optional[jnp.ndarray] = None,
    hi_edge: Optional[jnp.ndarray] = None,
    fill=0,
) -> jnp.ndarray:
    """Device-side halo reconstruction along the *batch* axis.

    ``x`` is the local sub-batch ``[b, *spatial]`` of a stacked batch whose
    blocks form a contiguous run along spatial ``axis`` (block ``i+1``
    starts where block ``i`` ends).  Each block's missing halo along that
    axis is its batch neighbor's edge slab: for blocks interior to the
    sub-batch a local slice, across device boundaries one nearest-neighbor
    ``ppermute`` (the :func:`..halo.exchange_halo` pattern applied to the
    batch axis).  ``lo_edge`` / ``hi_edge`` are the run-end slabs (shape =
    one block's halo slab) the host supplies for the globally first / last
    block — read from storage when the run borders more volume, or the
    task's border fill at the volume edge; without them the ends are filled
    with ``fill`` (matching :func:`..halo.exchange_halo` border semantics).

    Returns ``[b, ...]`` with the extent along ``axis`` grown by
    ``2 * halo`` — exactly the stack of halo'd outer regions per-block
    overlapped reads would have produced, without re-reading any interior
    halo from storage.  Must be called inside ``shard_map``.
    """
    if halo <= 0:
        return x
    ax = axis + 1  # x carries the batch axis in front
    extent = x.shape[ax]
    if extent < halo:
        raise ValueError(
            f"block extent {extent} along axis {axis} smaller than halo {halo}"
        )
    n = int(axis_size)
    idx = lax.axis_index(axis_name)
    lo_slabs = lax.slice_in_dim(x, 0, halo, axis=ax)
    hi_slabs = lax.slice_in_dim(x, extent - halo, extent, axis=ax)
    # device-boundary slabs: my first block's low slab -> previous device
    # (as its succ), my last block's high slab -> next device (as its pred);
    # ppermute zero-fills the mesh ends
    first_lo = lax.slice_in_dim(lo_slabs, 0, 1, axis=0)
    last_hi = lax.slice_in_dim(hi_slabs, x.shape[0] - 1, x.shape[0], axis=0)
    from_prev = lax.ppermute(
        last_hi, axis_name, [(i, i + 1) for i in range(n - 1)]
    )
    from_next = lax.ppermute(
        first_lo, axis_name, [(i, i - 1) for i in range(1, n)]
    )

    def _edge(slab, edge_val, is_edge):
        if edge_val is None:
            if isinstance(fill, (int, float)) and fill == 0:
                return slab  # ppermute already zero-filled the mesh end
            edge_val = jnp.full(slab.shape[1:], fill, x.dtype)
        return jnp.where(is_edge, edge_val[None].astype(x.dtype), slab)

    from_prev = _edge(from_prev, lo_edge, idx == 0)
    from_next = _edge(from_next, hi_edge, idx == n - 1)
    # per-block pred/succ: neighbors inside the sub-batch are local slices
    pred = jnp.concatenate(
        [from_prev, lax.slice_in_dim(hi_slabs, 0, x.shape[0] - 1, axis=0)],
        axis=0,
    )
    succ = jnp.concatenate(
        [lax.slice_in_dim(lo_slabs, 1, x.shape[0], axis=0), from_next],
        axis=0,
    )
    return jnp.concatenate([pred, x, succ], axis=ax)


def sharded_slab_sweep(
    vol,
    kernel: Callable,
    mesh: Mesh,
    extent: int,
    halo: int,
    batch: Optional[int] = None,
    fill=0.0,
    axis_name: str = "blocks",
    keep_on_device: bool = False,
):
    """Sweep ``vol`` decomposed into axis-0 slabs of ``extent`` as
    batch-sharded programs with device-side halo exchange.

    Each batch of consecutive slabs is loaded WITHOUT its axis-0 halos
    (every voxel is read exactly once); the sharded program reconstructs
    all interior halos on device via :func:`exchange_batch_halo` and runs
    ``vmap(kernel)`` over the halo'd slabs — ``kernel`` receives
    ``[extent + 2*halo, ...]`` exactly as per-slab overlapped reads would
    have produced it (volume ends padded with ``fill``), so the result is
    bit-identical to the per-block path.  Ragged final batches are padded
    with synthetic slabs whose leading rows carry the true ``hi_edge`` (so
    the last real slab still sees its correct halo) and the padded outputs
    are dropped.  Returns the per-slab kernel outputs stacked along axis 0.

    ``vol`` may be a host :class:`numpy.ndarray` (each batch's stack is
    uploaded, counted as ``h2d_bytes``) or an already device-resident
    :class:`jax.Array` — e.g. the payload of a device handoff
    (:func:`~cluster_tools_tpu.runtime.handoff.resolve_device_arrays`) — in
    which case batches are sliced and stacked on device and the skipped
    upload is counted as ``bytes_not_staged``.  With ``keep_on_device=True``
    the result stays a :class:`jax.Array` (no device-to-host copy), ready
    to feed the next device consumer or a device handoff publish; the
    default materializes the host array and counts ``d2h_bytes``.
    """
    n_dev = mesh_n_devices(mesh)
    size = int(vol.shape[0])
    if size % extent:
        raise ValueError(
            f"volume extent {size} is not a multiple of the slab extent "
            f"{extent} (run the ragged tail per-block)"
        )
    if halo > extent:
        raise ValueError(f"halo {halo} exceeds the slab extent {extent}")
    n_slabs = size // extent
    if batch is None:
        batch = min(n_slabs, max(n_dev, 8))
    batch = ((int(batch) + n_dev - 1) // n_dev) * n_dev

    on_device = isinstance(vol, jax.Array)
    xp = jnp if on_device else np
    slab_shape = (extent,) + tuple(vol.shape[1:])
    edge_shape = (halo,) + tuple(vol.shape[1:])
    itemsize = np.dtype(vol.dtype).itemsize

    def _body(stack, lo, hi):
        halod = exchange_batch_halo(
            stack, halo, 0, axis_name, n_dev,
            lo_edge=lo, hi_edge=hi, fill=fill,
        )
        return jax.vmap(kernel)(halod)

    spec = P(axis_name)
    prog = jax.jit(
        shard_map(
            _body,
            mesh=mesh,
            in_specs=(spec, P(), P()),
            out_specs=spec,
            check_vma=False,
        )
    )

    from ..runtime import trace as trace_mod

    fill_edge = xp.full(edge_shape, fill, vol.dtype)
    outs = []
    for start in range(0, n_slabs, batch):
        idxs = list(range(start, min(start + batch, n_slabs)))
        stack = xp.stack([vol[i * extent:(i + 1) * extent] for i in idxs])
        lo = (
            vol[start * extent - halo:start * extent]
            if start > 0 else fill_edge
        )
        end = idxs[-1] + 1
        hi = (
            vol[end * extent:end * extent + halo]
            if end < n_slabs else fill_edge
        )
        n_pad = batch - len(idxs)
        if n_pad:
            # padding slabs lead with the real hi edge so the last REAL
            # slab's device-side succ halo is still its true neighbor data;
            # the rest of the pad (and its outputs) are discarded
            if on_device:
                tail = jnp.full(
                    (extent - halo,) + slab_shape[1:], 0, vol.dtype
                )
                pad = jnp.concatenate([hi, tail], axis=0)
            else:
                pad = np.zeros(slab_shape, vol.dtype)
                pad[:halo] = hi
            stack = xp.concatenate(
                [stack, xp.stack([pad] * n_pad)], axis=0
            )
        feed_bytes = int(np.prod(stack.shape)) * itemsize
        if on_device:
            device_pool_mod.bump("bytes_not_staged", feed_bytes)
        else:
            device_pool_mod.record_h2d(feed_bytes)
        # one span per sharded slab program — the device-halo twin of the
        # executor's dispatch spans (docs/OBSERVABILITY.md)
        with trace_mod.span(
            "shard.slab_batch", start=start, n_slabs=len(idxs),
            feed="device" if on_device else "host",
        ):
            out = prog(stack, lo, hi)
            if not keep_on_device:
                out = np.asarray(out)
                device_pool_mod.record_d2h(int(out.nbytes))
        outs.append(out[: len(idxs)])
    if keep_on_device:
        return jnp.concatenate(outs, axis=0)
    return np.concatenate(outs, axis=0)
