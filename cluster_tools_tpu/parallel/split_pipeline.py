"""Split execution mode: the fused ws+cc step as a chain of per-stage
jitted SPMD programs with device-resident (HBM-pinned) intermediates.

Why this exists: on an earlier, shared accelerator set-up the fused
monolith's compile exceeded every operational cap (Mosaic >=600s, portable
XLA >=440s for a ~4.5-6.3k-line HLO that XLA:CPU compiles in 19s —
docs/PERFORMANCE.md round-4 log), while the per-stage programs are
individually in the class of the tiled CCL (~1.4k lines).  Splitting the
step into four programs whose intermediates never leave the device keeps
the step deployable where the monolith's compile time is the binding
constraint (ROADMAP S3 records what the local chip's compiler does with
each):

1. ``seeds``   — halo exchange, (optionally mesh-exact) EDT, maxima,
                 seed CCL (collectives: ppermute halo, EDT reshard).
2. ``flow``    — descent directions, in-tile VMEM flow, exit chase +
                 remap (no collectives).
3. ``fill``    — unseeded-basin fill, remap, halo crop, fragment-id
                 globalization, cross-shard stitch (collectives:
                 all_gather merge).
4. ``cc``      — distributed CCL of the foreground + global stats
                 (collectives: all_gather merge, psum).

Each stage is its own ``jax.jit(shard_map(...))`` over the same mesh and
specs as the fused step (``make_ws_ccl_step``); outputs equal the fused
step's bit-for-bit on every oracle in tests/test_split_pipeline.py.  The
cost is a few host dispatches per batch instead of one — measured on the
8-device CPU mesh the overhead is small compared to any stage's compute
(recorded by ``bench.py``'s split path and the A/B test).

Intermediates are donated where consumed (``padded`` to flow, ``values``/
``h`` to fill) so peak HBM stays in the fused step's class.

Reference mapping (SURVEY.md §3.5): this IS the reference's five-task
blockwise decomposition (write block -> ws block -> merge faces ->
merge assignments -> write relabeled) re-cut on program-compile
boundaries instead of luigi-task/filesystem boundaries.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..compat import shard_map
from ..ops import work
from ..ops.ccl import _match_vma
from ..ops.tile_ccl import DEFAULT_TABLE_CAP
from ..ops.tile_ws import (
    _dt_seeds_core,
    _resolve_fill_mode,
    _ws_flow_core,
    _ws_fill_core,
)
from .distributed_ccl import (
    linearized_shard_rank,
    sharded_label_components,
    sp_axes_for_mesh,
)
from .pipeline import (
    _stitch_ws_fragments,
    count_foreground,
    exchange_all,
    globalize_fragments,
    shard_records,
)


class SplitWsCclStep:
    """Callable chain of per-stage programs; see the module docstring.

    ``step(boundaries)`` returns ``(ws_labels, cc_labels, n_foreground,
    overflow, work)`` — the same contract as the fused step from
    ``make_ws_ccl_step``; the shards' work records (``ops/work.py``) pass
    from stage to stage beside the overflow flag, each stage adding what it
    counted.  ``stages`` maps stage name to its jitted
    function for individual compile-probing / cache warming; ``run_staged``
    exposes per-stage sync points for stage-resolved timing.
    """

    def __init__(self, stages, runner):
        self.stages = stages
        self._runner = runner

    def __call__(self, boundaries):
        return self._runner(boundaries, sync=None)

    def run_staged(self, boundaries, sync):
        """Run with ``sync(name, *arrays)`` called after dispatching each
        stage — pass a blocking sync to time stages individually."""
        return self._runner(boundaries, sync=sync)


def make_ws_ccl_split(
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
    fill_mode: Optional[str] = None,
) -> SplitWsCclStep:
    """Build the split-mode twin of ``make_ws_ccl_step`` for ``mesh``.

    Same arguments and output contract as the fused builder; ``impl`` is
    restricted to the tiled kernel family ("auto"/"pallas"/"xla"/"tiled")
    because the split exists to deploy the tiled path on compile-capped
    backends — "legacy" has no phase seams to cut (its fused program is
    small enough to compile everywhere).  3-D volumes, connectivity 1.

    ``fill_mode``: as in ``dt_watershed_tiled`` — ``None`` resolves
    ``CT_FILL_MODE`` here, at build time, so the env value is fixed into
    the stage programs.
    """
    if impl == "legacy":
        raise ValueError("split mode covers the tiled kernels only")
    if connectivity != 1:
        raise ValueError("split mode supports connectivity=1 only")
    names = (sp_axis,) if isinstance(sp_axis, str) else tuple(sp_axis)
    sp_axes = sp_axes_for_mesh(mesh, sp_axis)
    n_shards = int(np.prod([s for _, _, s in sp_axes]))
    fill_mode = _resolve_fill_mode(fill_mode)
    spec = P(dp_axis, *names)
    rep = P()

    def _smap(name, body, in_specs, out_specs, donate=()):
        sharded = shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        # the name the compiled module and every trace carry: jit_ws_ccl_<stage>
        sharded.__name__ = sharded.__qualname__ = f"ws_ccl_{name}"
        return jax.jit(sharded, donate_argnums=donate)

    def _reduce_all(v):
        for _, name, _ in sp_axes:
            v = lax.pmax(v, name)
        return lax.pmax(v, dp_axis)

    # ---- stage 1: halo exchange + EDT + maxima + seed CCL ----
    def seeds_body(boundaries):
        if boundaries.ndim - 1 != 3:
            raise ValueError("split mode expects 3-D volumes")
        local_b = boundaries.shape[0]
        pad_out, seed_out, rows = [], [], []
        ovf = _match_vma(jnp.zeros((), jnp.int32), boundaries)
        for b in range(local_b):
            vol = boundaries[b]
            padded = exchange_all(vol, halo, sp_axes, fill=1.0)
            dist_pad = None
            if exact_edt:
                from .distributed_edt import (
                    sharded_distance_transform_squared,
                )

                dist_sq = sharded_distance_transform_squared(
                    vol < threshold,
                    shard_axes=sp_axes,
                    max_distance=dt_max_distance,
                    impl="xla" if impl in ("xla", "tiled") else "auto",
                )
                dist_pad = exchange_all(dist_sq, halo, sp_axes, fill=0.0)
            seeds, _, s_ovf, record = _dt_seeds_core(
                padded, None, dist_pad, threshold=threshold,
                sigma_seeds=0.0, min_seed_distance=min_seed_distance,
                sampling=None, dt_max_distance=dt_max_distance,
                impl=impl, tile=None, pair_cap=None, edge_cap=None,
                table_cap=DEFAULT_TABLE_CAP, interpret=False,
            )
            ovf = jnp.maximum(ovf, s_ovf.astype(jnp.int32))
            pad_out.append(padded)
            seed_out.append(seeds)
            rows.append(record)
        return (jnp.stack(pad_out), jnp.stack(seed_out), _reduce_all(ovf),
                shard_records(rows, sp_axes))

    # ---- stage 2: descent + in-tile flow + exit chase/remap ----
    def flow_body(padded, seeds, ovf_in, rec_in):
        local_b = padded.shape[0]
        val_out, h_out, rows = [], [], []
        ovf = ovf_in
        for b in range(local_b):
            values, h, o, counts = _ws_flow_core(
                padded[b], seeds[b], None, impl=impl, tile=None,
                exit_cap=None, table_cap=DEFAULT_TABLE_CAP, interpret=False,
            )
            ovf = jnp.maximum(ovf, o.astype(jnp.int32))
            val_out.append(values)
            h_out.append(h)
            rows.append(work.pack(counts))
        # pmax so the replicated out_spec is honest (check_vma is off —
        # an unreduced per-shard flag would silently take one shard's copy)
        return (jnp.stack(val_out), jnp.stack(h_out), _reduce_all(ovf),
                work.merge(rec_in, shard_records(rows, sp_axes)))

    # ---- stage 3: fill + halo crop + globalize + stitch ----
    def fill_body(values, h, boundaries, ovf_in, rec_in):
        local_b = values.shape[0]
        rank = linearized_shard_rank(sp_axes)
        pad_shape = tuple(
            boundaries.shape[1 + i]
            + (2 * halo if i in [a for a, _, _ in sp_axes] else 0)
            for i in range(3)
        )
        n_pad = int(np.prod(pad_shape))
        ws_out, rows = [], []
        ovf = ovf_in
        for b in range(local_b):
            ws, o, counts = _ws_fill_core(
                values[b], h[b], pad_shape, impl=impl, tile=None,
                exit_cap=None, fill_cap=None, table_cap=DEFAULT_TABLE_CAP,
                interpret=False, adj_cap=None, fill_rounds=None,
                fill_mode=fill_mode,
            )
            ovf = jnp.maximum(ovf, o.astype(jnp.int32))
            ws, ws_span, frag_over, frag_counts = globalize_fragments(
                ws, halo, sp_axes, rank, n_pad, max_labels_per_shard
            )
            rows.append(work.pack(work.join(counts, frag_counts)))
            if frag_over is not None:
                ovf = jnp.maximum(ovf, frag_over)
            if stitch_ws_threshold is not None and n_shards > 1:
                ws = _stitch_ws_fragments(
                    ws, boundaries[b], sp_axes, rank, ws_span,
                    float(stitch_ws_threshold),
                )
            ws_out.append(ws)
        return (jnp.stack(ws_out), _reduce_all(ovf),
                work.merge(rec_in, shard_records(rows, sp_axes)))

    # ---- stage 4: distributed CC of the foreground + global stats ----
    def cc_body(boundaries, ovf_in, rec_in):
        local_b = boundaries.shape[0]
        cc_out, rows = [], []
        ovf = ovf_in
        for b in range(local_b):
            vol = boundaries[b]
            cc, cc_over, record = sharded_label_components(
                vol < threshold,
                shard_axes=sp_axes,
                connectivity=connectivity,
                max_labels_per_shard=max_labels_per_shard,
                return_overflow=True,
                impl=impl,
            )
            ovf = jnp.maximum(ovf, cc_over.astype(jnp.int32))
            cc_out.append(cc)
            rows.append(record)
        cc_lab = jnp.stack(cc_out)
        n_fg = count_foreground(cc_lab, sp_axes, dp_axis)
        overflow = _reduce_all(ovf) > 0
        return cc_lab, n_fg, overflow, work.merge(rec_in, shard_records(rows, sp_axes))

    stages = {
        "seeds": _smap("seeds", seeds_body, (spec,), (spec, spec, rep, spec)),
        # donate the padded volume (consumed by flow) and values/h
        # (consumed by fill) so peak HBM stays in the fused step's class
        "flow": _smap(
            "flow", flow_body, (spec, spec, rep, spec),
            (spec, spec, rep, spec), donate=(0, 1),
        ),
        "fill": _smap(
            "fill", fill_body, (spec, spec, spec, rep, spec),
            (spec, rep, spec), donate=(0, 1),
        ),
        "cc": _smap("cc", cc_body, (spec, rep, spec), (spec, rep, rep, spec)),
    }

    def runner(boundaries, sync=None):
        padded, seeds, ovf, rec = stages["seeds"](boundaries)
        if sync is not None:
            sync("seeds", seeds)
        values, h, ovf, rec = stages["flow"](padded, seeds, ovf, rec)
        if sync is not None:
            sync("flow", values)
        ws_lab, ovf, rec = stages["fill"](values, h, boundaries, ovf, rec)
        if sync is not None:
            sync("fill", ws_lab)
        cc_lab, n_fg, overflow, rec = stages["cc"](boundaries, ovf, rec)
        if sync is not None:
            sync("cc", cc_lab)
        return ws_lab, cc_lab, n_fg, overflow, rec

    return SplitWsCclStep(stages, runner)
