"""Split execution mode (parallel/split_pipeline.py): per-stage programs
must reproduce the fused step bit-for-bit, on every mesh topology the fused
tests cover, and the chain's dispatch overhead on the CPU mesh must stay
small (the on-chip decision between fused and split is then a single A/B —
r4 verdict item #2)."""

import time

import jax
import numpy as np
import pytest
from scipy import ndimage

from cluster_tools_tpu.parallel import make_mesh
from cluster_tools_tpu.parallel.mesh import backend_devices, mesh_axis_sizes
from cluster_tools_tpu.parallel.pipeline import make_ws_ccl_step
from cluster_tools_tpu.parallel.split_pipeline import make_ws_ccl_split

from .helpers import assert_labels_equivalent


def _mesh(axis_names=("sp",), n=None):
    devs = backend_devices("local")
    n = n or len(devs)
    return make_mesh(n, axis_names=axis_names, devices=devs)


def _run_both(mesh, vol, **kw):
    fused = make_ws_ccl_step(mesh, **kw)
    split = make_ws_ccl_split(mesh, **kw)
    f = jax.block_until_ready(fused(vol))
    s = jax.block_until_ready(split(vol))
    return f, s


def _assert_same(f, s):
    ws_f, cc_f, n_f, ov_f, rec_f = f
    ws_s, cc_s, n_s, ov_s, rec_s = s
    # the stages' records, merged from program to program, are the step's
    np.testing.assert_array_equal(np.asarray(rec_s), np.asarray(rec_f))
    np.testing.assert_array_equal(np.asarray(ws_s), np.asarray(ws_f))
    np.testing.assert_array_equal(np.asarray(cc_s), np.asarray(cc_f))
    assert int(n_s) == int(n_f)
    assert bool(ov_s) == bool(ov_f)


def test_split_matches_fused_dp_sp(rng):
    mesh = _mesh(("dp", "sp"))
    sizes = mesh_axis_sizes(mesh)
    dp, sp = sizes["dp"], sizes["sp"]
    vol = rng.random((dp, sp * 8, 16, 16)).astype(np.float32)
    f, s = _run_both(mesh, vol, halo=2, threshold=0.5)
    _assert_same(f, s)
    assert not bool(f[3])
    # the cc labels are real: scipy oracle per batch element
    cc = np.asarray(f[1])
    for i in range(vol.shape[0]):
        expected, _ = ndimage.label(
            vol[i] < 0.5, structure=ndimage.generate_binary_structure(3, 1)
        )
        assert_labels_equivalent(cc[i], expected)


@pytest.mark.slow  # tier-2 (make tier2): ~21 s of XLA compiles; parity
# variant — split-vs-fused stays tier-1 via _dp_sp.
def test_split_matches_fused_stitch_compaction(rng):
    mesh = _mesh(("dp", "sp"))
    sizes = mesh_axis_sizes(mesh)
    dp, sp = sizes["dp"], sizes["sp"]
    vol = rng.random((dp, sp * 8, 16, 16)).astype(np.float32)
    f, s = _run_both(
        mesh, vol, halo=2, threshold=0.5, max_labels_per_shard=2048,
        stitch_ws_threshold=0.5,
    )
    _assert_same(f, s)
    assert not bool(f[3])


@pytest.mark.slow  # tier-2 (make tier2): ~20 s of XLA compiles; parity
# variant — split-vs-fused stays tier-1 via _dp_sp.
def test_split_matches_fused_two_axis_exact_edt(rng):
    mesh = _mesh(("dp", "spz", "spy"))
    sizes = mesh_axis_sizes(mesh)
    dp, sz, sy = sizes["dp"], sizes["spz"], sizes["spy"]
    vol = rng.random((dp, sz * 8, sy * 8, 8 * sz * sy)).astype(np.float32)
    f, s = _run_both(
        mesh, vol, halo=2, threshold=0.5, sp_axis=("spz", "spy"),
        exact_edt=True, stitch_ws_threshold=0.5,
    )
    _assert_same(f, s)
    assert not bool(f[3])


def test_split_single_device_mesh(rng):
    """The 1x1 (dp, sp) mesh — the single-chip benchmark topology."""
    mesh = make_mesh(1, axis_names=("dp", "sp"), devices=backend_devices("local"))
    vol = rng.random((1, 24, 16, 16)).astype(np.float32)
    f, s = _run_both(mesh, vol, halo=2, threshold=0.5, dt_max_distance=2.0)
    _assert_same(f, s)


def test_split_overflow_flag_propagates(rng):
    """A cap small enough to trip in the fill stage must surface in the
    final output even though the flag crosses three program boundaries."""
    mesh = _mesh(("dp", "sp"))
    sizes = mesh_axis_sizes(mesh)
    dp, sp = sizes["dp"], sizes["sp"]
    vol = rng.random((dp, sp * 8, 16, 16)).astype(np.float32)
    split = make_ws_ccl_split(
        mesh, halo=2, threshold=0.5, max_labels_per_shard=4
    )
    *_, overflow, _ = jax.block_until_ready(split(vol))
    assert bool(overflow)


@pytest.mark.slow  # tier-2 (make tier2): ~24 s of XLA compiles; the
# split-vs-fused parity tests keep the split pipeline in tier-1
def test_split_stage_programs_and_overhead(rng):
    """Per-stage sync points work and the split chain's wall-clock stays
    within a generous factor of the fused program on the CPU mesh — the
    dispatch-overhead half of the on-chip fused-vs-split A/B."""
    mesh = _mesh(("dp", "sp"))
    sizes = mesh_axis_sizes(mesh)
    dp, sp = sizes["dp"], sizes["sp"]
    vol = rng.random((dp, sp * 12, 24, 24)).astype(np.float32)
    fused = make_ws_ccl_step(mesh, halo=2, threshold=0.5)
    split = make_ws_ccl_split(mesh, halo=2, threshold=0.5)

    stage_names = []
    out = split.run_staged(
        vol, sync=lambda name, *arrs: (
            stage_names.append(name), jax.block_until_ready(arrs)
        )
    )
    jax.block_until_ready(out)
    assert stage_names == ["seeds", "flow", "fill", "cc"]

    # warm both, then best-of-3 each
    jax.block_until_ready(fused(vol))
    jax.block_until_ready(split(vol))

    def best(fn):
        ts = []
        for _ in range(3):
            t0 = time.monotonic()
            jax.block_until_ready(fn(vol))
            ts.append(time.monotonic() - t0)
        return min(ts)

    t_fused, t_split = best(fused), best(split)
    # CPU-substrate guardrail, not a perf claim: catches a pathological
    # dispatch/copy regression (e.g. an intermediate bouncing via host)
    # while staying robust to the 2-core CI box's noise
    assert t_split < 3.0 * t_fused + 0.25, (t_split, t_fused)
