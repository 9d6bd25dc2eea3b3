"""The fused, mesh-sharded watershed+CCL step — the framework's "train step".

The reference's north-star workload (BASELINE.json) is: blockwise
distance-transform watershed + connected components, with the two-pass
union-find label merge, end-to-end to globally merged labels.  In the
reference that was five luigi tasks and thousands of filesystem round-trips;
here it is **one compiled SPMD program** over a ``(dp, sp...)`` mesh:

- ``dp`` shards a batch of independent volumes (block batches),
- one or more spatial axes shard each volume into slabs (z) or a full
  2-D/3-D spatial decomposition (z × y × x) — the teravoxel layout,
- halo exchange (``ppermute`` over ICI, one per sharded axis — corners fill
  correctly because each exchange forwards the previously received halo),
- the fused DT-watershed kernel runs per shard,
- watershed fragments stitch across every cut by face consensus, and the
  thresholded foreground is labeled with globally consistent components via
  the distributed union-find merge (``all_gather`` + pointer jumping),
- a ``psum`` over the whole mesh yields global statistics.

This module is what ``__graft_entry__.dryrun_multichip`` compiles and runs.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple, Union

import jax

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..compat import shard_map
from ..ops import work
from ..ops.ccl import _match_vma, relabel_consecutive
from ..ops.watershed import distance_transform_watershed
from .distributed_ccl import (
    ShardAxis,
    linearized_shard_rank,
    merge_labels_by_pairs,
    sharded_label_components,
    sp_axes_for_mesh,
)
from .halo import crop_halo, exchange_halo, neighbor_face
from .mesh import mesh_axis_sizes


@jax.named_scope("step.stitch")
def _stitch_ws_fragments(
    ws: jnp.ndarray,
    vol: jnp.ndarray,
    axes: Sequence[ShardAxis],
    rank: jnp.ndarray,
    span: int,
    threshold: float,
) -> jnp.ndarray:
    """Merge watershed fragments across every sharded cut by face consensus.

    The device-resident form of the reference's two-pass/stitching semantics
    (SURVEY.md §3.5, ``stitching``): two fragments facing each other across
    a shard boundary merge when the boundary evidence at their contact is
    weak — ``max`` of the two sides' boundary values below ``threshold``.
    The equivalences ride the same gather + union-find + remap tail as the
    distributed CCL merge.
    """
    pairs = []
    for a, name, size in axes:
        mine_l = lax.slice_in_dim(ws, 0, 1, axis=a).ravel()
        theirs_l = neighbor_face(ws, a, name, size, direction=-1).ravel()
        mine_b = lax.slice_in_dim(vol, 0, 1, axis=a).ravel()
        theirs_b = neighbor_face(
            vol, a, name, size, direction=-1, fill=1.0
        ).ravel()
        val = jnp.maximum(mine_b, theirs_b)
        ok = (mine_l > 0) & (theirs_l > 0) & (val < threshold)
        pairs.append(
            jnp.stack(
                [
                    jnp.where(ok, theirs_l, jnp.int32(-1)),
                    jnp.where(ok, mine_l, jnp.int32(-1)),
                ],
                axis=1,
            )
        )
    return merge_labels_by_pairs(
        ws, jnp.concatenate(pairs, axis=0), axes, rank, span
    )


def exchange_all(x, halo: int, sp_axes: Sequence[ShardAxis], fill):
    """One ``ppermute`` per sharded axis; later exchanges forward the halos
    received by earlier ones, so diagonal (corner) regions arrive with the
    correct neighbor-of-neighbor data."""
    for a, name, size in sp_axes:
        x = exchange_halo(x, halo, a, name, size, fill=fill)
    return x


@jax.named_scope("step.globalize")
def globalize_fragments(
    ws: jnp.ndarray,
    halo: int,
    sp_axes: Sequence[ShardAxis],
    rank: jnp.ndarray,
    n_pad: int,
    max_labels_per_shard: Optional[int],
) -> Tuple[jnp.ndarray, int, Optional[jnp.ndarray], dict]:
    """Crop the halo and make watershed fragment ids unique over the mesh
    by shard rank: ``(ws, span, overflow, counts)``, shared by the fused step
    and the split chain's fill stage.  With a compaction cap, fragment ids
    are densified first so the label space is ``n_shards * cap`` instead of
    ``n_shards * padded_voxels`` (the int32 ceiling that blocked teravoxel
    volumes); ``overflow`` is then the int32 flag of a shard with more
    fragments than the cap, else None, and ``counts`` the shard's fragments
    against the cap for the work record (``ops/work.py``), else empty."""
    n_shards = int(np.prod([s for _, _, s in sp_axes]))
    for a, _, _ in sp_axes:
        ws = crop_halo(ws, halo, a)
    if max_labels_per_shard is None:
        if n_shards * n_pad >= 2**31:
            raise ValueError(
                f"{n_shards} shards of {n_pad} padded voxels overflow "
                "int32 labels; pass max_labels_per_shard"
            )
        return (jnp.where(ws > 0, ws + rank * jnp.int32(n_pad), 0), n_pad,
                None, {})
    cap = int(max_labels_per_shard)
    if n_shards * (cap + 1) >= 2**31:
        raise ValueError(
            f"{n_shards} shards x {cap} ws fragments overflow int32"
        )
    # ws fragment ids are PADDED-volume flat indices (+1), which exceed
    # the halo-cropped labels.size — pass the padded span or the bitmap
    # fast path silently never engages here
    ws, n_frag = relabel_consecutive(
        ws, max_labels=cap, value_bound=n_pad + 1
    )
    ws = jnp.where(ws > 0, ws + rank * jnp.int32(cap + 1), 0)
    return ws, cap + 1, (n_frag > cap).astype(jnp.int32), {
        work.STEP_FRAGMENTS: n_frag, work.OVER_LABELS: n_frag > cap,
        work.CAP_LABELS: cap,
    }


def shard_records(
    rows: Sequence[jnp.ndarray], sp_axes: Sequence[ShardAxis]
) -> jnp.ndarray:
    """A shard's work records (``ops/work.py``), one row a local volume, as
    its block of the step's ``(B,) + spatial mesh sizes + (K,)`` output: a
    size-1 axis per sharded mesh axis, so that the labels' ``out_specs`` fit
    and no row meets another shard's."""
    return jnp.stack(rows).reshape(
        (len(rows),) + (1,) * len(sp_axes) + (len(work.NAMES),)
    )


@jax.named_scope("step.count")
def count_foreground(
    cc_lab: jnp.ndarray, sp_axes: Sequence[ShardAxis], dp_axis: str
) -> jnp.ndarray:
    """Global foreground voxel count over the full mesh (dp and all sp
    axes).  Summed in float32: an int32 psum would wrap past 2**31 global
    foreground voxels (the teravoxel layouts this step supports); f32 is
    exact below 2**24 per shard and ~1e-7 relative beyond."""
    n_fg = jnp.sum(cc_lab > 0).astype(jnp.float32)
    for _, name, _ in sp_axes:
        n_fg = lax.psum(n_fg, name)
    return lax.psum(n_fg, dp_axis)


def _ws_ccl_shard(
    boundaries: jnp.ndarray,
    *,
    sp_axes: Tuple[ShardAxis, ...],
    dp_axis: str,
    halo: int,
    threshold: float,
    connectivity: int,
    dt_max_distance: Optional[float],
    min_seed_distance: float,
    max_labels_per_shard: Optional[int],
    impl: str,
    exact_edt: bool,
    stitch_ws_threshold: Optional[float],
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-device body: local shard is ``(local_batch,) + local_volume``.

    ``sp_axes`` holds ``(volume_axis, mesh_axis_name, mesh_axis_size)`` per
    sharded spatial axis (volume axes count WITHOUT the batch axis).  The
    last output is the shard's work record (``ops/work.py``), one row a
    local volume, a size-1 axis per sharded mesh axis in between: it is
    this shard's own and meets no collective.
    """
    local_b = boundaries.shape[0]
    n_shards = int(np.prod([s for _, _, s in sp_axes]))
    rank = linearized_shard_rank(sp_axes)
    # the tiled (two-level VMEM) kernels are 3-D/connectivity-1 only; the
    # legacy dense fixpoint covers the rest (2-D volumes included)
    tiled_ok = (
        impl != "legacy" and connectivity == 1 and boundaries.ndim - 1 == 3
    )
    if exact_edt and not tiled_ok:
        # make_ws_ccl_step rejects legacy/connectivity mismatches up front,
        # but the volume rank is only known here — refuse rather than hand
        # back halo-capped seeds the caller opted out of
        raise ValueError(
            "exact_edt requires the tiled kernels, which are 3-D only "
            f"(got a {boundaries.ndim - 1}-D volume)"
        )

    ws_out = []
    cc_out = []
    records = []
    # per-shard ws-compaction overflow (varies over the mesh); cc overflow
    # arrives already sp-reduced from sharded_label_components
    ws_overflow = _match_vma(jnp.zeros((), jnp.int32), boundaries)
    cc_overflow = None
    # static Python loop over the (small) local batch: collectives inside the
    # body run once per volume on every rank in lockstep
    for b in range(local_b):
        vol = boundaries[b]
        # border fill = 1.0 (pure boundary) so basins never leak out of the
        # volume
        padded = exchange_all(vol, halo, sp_axes, fill=1.0)
        if tiled_ok:
            from ..ops.tile_ws import dt_watershed_tiled

            dist_pad = None
            if exact_edt:
                # globally exact squared EDT (all-to-all reshard per axis
                # pass, distributed_edt) instead of the halo-capped
                # per-shard transform; halo-exchange the distances so the
                # padded watershed window sees them too (fill 0 = the
                # outside-volume border is background, matching the
                # boundary fill of 1.0 above)
                from .distributed_edt import sharded_distance_transform_squared

                dist_sq = sharded_distance_transform_squared(
                    vol < threshold,
                    shard_axes=sp_axes,
                    # keep the documented dt_max_distance contract: caps
                    # stay capped (exactness here means exact ACROSS shard
                    # cuts, not uncapped); None = truly global radii
                    max_distance=dt_max_distance,
                    impl="xla" if impl in ("xla", "tiled") else "auto",
                )
                dist_pad = exchange_all(dist_sq, halo, sp_axes, fill=0.0)
            ws, ws_over, record = dt_watershed_tiled(
                padded,
                threshold=threshold,
                dist=dist_pad,
                dt_max_distance=dt_max_distance,
                min_seed_distance=min_seed_distance,
                impl=impl,
            )
            ws_overflow = jnp.maximum(ws_overflow, ws_over.astype(jnp.int32))
        else:
            ws = distance_transform_watershed(
                padded,
                threshold=threshold,
                min_seed_distance=min_seed_distance,
                connectivity=connectivity,
                dt_max_distance=dt_max_distance,
            )
            record = work.pack({})  # the dense fixpoint counts nothing
        ws, ws_span, frag_over, frag_counts = globalize_fragments(
            ws, halo, sp_axes, rank, int(np.prod(padded.shape)),
            max_labels_per_shard,
        )
        if frag_over is not None:
            ws_overflow = jnp.maximum(ws_overflow, frag_over)
        if stitch_ws_threshold is not None and n_shards > 1:
            # cross-shard fragment merge: the "stitch" of BASELINE config 3,
            # device-resident (skipped at 1 shard — no cuts exist, and the
            # relabel table would be pure overhead)
            ws = _stitch_ws_fragments(
                ws, vol, sp_axes, rank, ws_span, float(stitch_ws_threshold)
            )
        ws_out.append(ws)

        # globally merged connected components of the foreground mask — the
        # two-pass union-find merge as ICI collectives
        cc, cc_over, cc_record = sharded_label_components(
            vol < threshold,
            shard_axes=sp_axes,
            connectivity=connectivity,
            max_labels_per_shard=max_labels_per_shard,
            return_overflow=True,
            impl=impl,
        )
        records.append(work.merge(record, work.pack(frag_counts), cc_record))
        cc_over = cc_over.astype(jnp.int32)
        cc_overflow = (
            cc_over if cc_overflow is None else jnp.maximum(cc_overflow, cc_over)
        )
        cc_out.append(cc)

    ws_lab = jnp.stack(ws_out)
    cc_lab = jnp.stack(cc_out)
    n_fg = count_foreground(cc_lab, sp_axes, dp_axis)
    # mesh-wide label-compaction overflow flag (always False w/o compaction);
    # its all-reduces sit with the count's, the step's other global scalar
    with jax.named_scope("step.count"):
        for _, name, _ in sp_axes:
            ws_overflow = lax.pmax(ws_overflow, name)
        overflow = jnp.maximum(ws_overflow, cc_overflow)
        overflow = lax.pmax(overflow, dp_axis) > 0
    return ws_lab, cc_lab, n_fg, overflow, shard_records(records, sp_axes)


def make_ws_ccl_step(
    mesh: Mesh,
    halo: int = 4,
    threshold: float = 0.3,
    connectivity: int = 1,
    dp_axis: str = "dp",
    sp_axis: Union[str, Sequence[str]] = "sp",
    dt_max_distance: Optional[float] = None,
    min_seed_distance: float = 0.0,
    max_labels_per_shard: Optional[int] = None,
    impl: str = "auto",
    exact_edt: bool = False,
    stitch_ws_threshold: Optional[float] = None,
):
    """Compile the fused step for ``mesh``.

    Returns a jitted function ``step(boundaries)`` taking a float32 batch of
    volumes ``(B,) + volume`` with ``B % dp == 0``; the batch axis is
    sharded over ``dp``.  ``sp_axis`` may be one mesh axis name (the
    volume's z axis sharded in slabs) or a sequence of names (the leading
    volume axes sharded over the respective mesh axes — a full 2-D/3-D
    spatial decomposition; each sharded extent must divide).  Output:
    ``(ws_labels, cc_labels, n_foreground, overflow, work)`` with labels
    sharded like the input and the scalars replicated; ``n_foreground`` is
    float32 (exact below 2**24 per shard; an int32 count would wrap past
    2**31 global foreground voxels); ``overflow`` is True when any shard
    exceeded ``max_labels_per_shard``, a tiled-kernel capacity, or a
    compaction cap (labels unreliable — raise the cap or add shards);
    ``work`` is the shards' work records, int32 ``(B,) + spatial mesh sizes
    + (len(work.NAMES),)``, each shard's row its own (``ops/work.py``:
    ``work.unpack`` gives one dict a shard, ``work.tripped`` names what
    raised ``overflow``).

    ``impl`` selects the per-shard kernels: "auto" (two-level VMEM tile
    machinery, Mosaic on TPU / portable XLA elsewhere — the fast path),
    "pallas"/"xla"/"tiled" to force a tiled variant, or "legacy" (round-2
    dense fixpoint kernels).

    ``exact_edt``: seed the watershed from the *globally exact* EDT
    (mesh-distributed, all-to-all reshard per axis pass) instead of the
    halo-capped per-shard transform — no halo saturation artifacts in the
    seeds.  Requires the tiled kernels (not "legacy") and connectivity=1;
    the reshard target's local extent must divide by each sharded mesh-axis
    size.

    ``stitch_ws_threshold``: when set, watershed fragments facing each other
    across the spatial cuts merge where the boundary evidence at the
    contact is below the threshold (face consensus — the device-resident
    form of the reference's two-pass/stitching step), so the returned
    ``ws_labels`` are globally merged rather than per-shard.
    """
    if exact_edt and (impl == "legacy" or connectivity != 1):
        # the legacy dense-fixpoint branch never reads the flag — refuse
        # rather than silently hand back the halo-capped seeds the caller
        # opted out of
        raise ValueError(
            "exact_edt requires the tiled kernels (impl != 'legacy') and "
            "connectivity=1"
        )
    names = (sp_axis,) if isinstance(sp_axis, str) else tuple(sp_axis)
    sp_axes = sp_axes_for_mesh(mesh, sp_axis)
    body = partial(
        _ws_ccl_shard,
        sp_axes=sp_axes,
        dp_axis=dp_axis,
        halo=halo,
        threshold=threshold,
        connectivity=connectivity,
        dt_max_distance=dt_max_distance,
        min_seed_distance=min_seed_distance,
        max_labels_per_shard=max_labels_per_shard,
        impl=impl,
        exact_edt=exact_edt,
        stitch_ws_threshold=stitch_ws_threshold,
    )
    # check_vma=False: the per-shard body runs Pallas kernels whose in-kernel
    # loop carries mix ref loads (vma-tagged) with constants (untagged), and
    # this JAX version's vma propagation drops the tag across concatenate
    # inside pallas tracing — the static check then rejects a correct
    # program ("carry input {V:sp} vs output" on the EDT cascade).  The
    # collectives (ppermute halo, all_gather merge, psum stats) are
    # unaffected; only the static replication *check* is off.
    spec = P(dp_axis, *names)
    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=spec,
        out_specs=(spec, spec, P(), P(), spec),
        check_vma=False,
    )
    # the name the compiled module, its cache entry and every trace carry:
    # jit_ws_ccl_step (named on the function itself: a wrapper of its own
    # costs the trace half a second a job, PERF.md PR 28)
    sharded.__name__ = sharded.__qualname__ = "ws_ccl_step"
    return jax.jit(sharded)
