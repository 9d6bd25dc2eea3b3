"""Two-level (tile + face-merge) CCL vs the scipy oracle.

The tiled path is the TPU performance kernel for the north-star fused step
(SURVEY.md §2a connected_components; BASELINE config 1); on CPU the tile
phase runs the portable XLA fallback while the *merge machinery* — face-pair
extraction, run/value dedup, capacity compaction, dense-id union-find — is
identical to the TPU path, so these tests exercise everything except the
Mosaic kernels themselves (covered by the interpret-mode test).
"""

import numpy as np
import pytest
import scipy.ndimage as ndi

import jax
import jax.numpy as jnp

from cluster_tools_tpu.ops.ccl import finalize_labels
from cluster_tools_tpu.ops.tile_ccl import (
    BIG,
    _remap_tables_core,
    build_remap_tables,
    label_components_tiled,
    resolve_impl,
)
from .helpers import assert_labels_equivalent, random_blobs


def _check(mask, **kw):
    lab, overflow, _ = label_components_tiled(jnp.asarray(mask), **kw)
    assert not bool(overflow)
    lab = np.asarray(lab)
    n = mask.size
    assert (lab[~mask] == n).all()
    ref, _ = ndi.label(mask, structure=ndi.generate_binary_structure(3, 1))
    assert_labels_equivalent(np.asarray(finalize_labels(jnp.asarray(lab))), ref)


def _border_plateaus(shape):
    """Seed-like plateaus touching every border of the volume."""
    z, y, x = shape
    mask = np.zeros(shape, bool)
    mask[0, 0, :7] = True            # ridge along x at the corner
    mask[5:8, 5:8, 5:8] = True       # cube plateau
    mask[z - 1, :, x - 1] = True     # edge line on the far border
    mask[:, y - 1, 0] = True         # and one down the z axis
    mask[12, 12, 20] = True          # singleton
    mask[12, 12, 22] = True          # near-but-separate singleton
    return mask


@pytest.mark.parametrize(
    "shape,p",
    [
        ((32, 32, 128), 0.5),
        ((48, 48, 256), 0.3),
        ((16, 16, 128), 0.7),
        ((64, 64, 128), 0.08),
        # what the watershed's seed CCL labels: 1-2 % scattered maxima, and
        # plateaus on the borders (p None), both at shapes that pad
        ((32, 48, 40), 0.02),
        ((24, 24, 40), None),
    ],
)
def test_tiled_vs_scipy(rng, shape, p):
    mask = _border_plateaus(shape) if p is None else rng.random(shape) < p
    _check(mask, impl="xla")


def test_tiled_nondivisible_shapes(rng):
    # padding path: shapes that are not tile multiples
    _check(rng.random((33, 47, 130)) < 0.5, impl="xla")
    _check(rng.random((10, 10, 50)) < 0.6, impl="xla")


def test_tiled_blobs(rng):
    _check(random_blobs(rng, (40, 48, 140), p=0.45), impl="xla")


@pytest.mark.parametrize("shape", [(16, 16, 128), (32, 16, 128), (8, 8, 16)])
def test_tiled_empty_full(shape):
    empty = np.zeros(shape, bool)
    lab, ovf, _ = label_components_tiled(jnp.asarray(empty), impl="xla")
    assert not bool(ovf) and (np.asarray(lab) == empty.size).all()
    full = np.ones(shape, bool)
    lab, ovf, _ = label_components_tiled(jnp.asarray(full), impl="xla")
    assert not bool(ovf)
    lab = np.asarray(lab)
    assert len(np.unique(lab)) == 1  # one component
    assert lab[0, 0, 0] < full.size  # a voxel of the volume, not padding


# The capacity tiers (run_capacity_tiered and its inline twins) choose at run
# time between one machine at two sizes, so a caller can never see which ran.
# Each site is driven with capacities large enough that it really tiers, once
# with a live count that fits the small tier and once with one that does not.


def _n_face_positions(mask, tile):
    """Face positions with foreground on both sides of a tile boundary: an
    upper bound of merge_face_pairs' ``n_total`` (run-dedup only removes)."""
    total = 0
    for axis, t in enumerate(tile):
        a = np.take(mask, range(t - 1, mask.shape[axis] - 1, t), axis=axis)
        b = np.take(mask, range(t, mask.shape[axis], t), axis=axis)
        total += int((a & b).sum())
    return total


@pytest.mark.parametrize("p,fits", [(0.3, True), (0.7, False)])
def test_merge_face_pairs_tier_is_invisible(rng, p, fits):
    shape, tile, cap = (64, 64, 256), (8, 8, 128), 65536
    small_n = max(3 * 16384, 3 * cap // 16)
    assert small_n < cap  # the merge tiers at these capacities
    mask = rng.random(shape) < p
    # foreground on even x only: no two face positions are neighbours along
    # the run-dedup axis, so the bound above IS n_total
    mask[:, :, 1::2] = False
    assert (_n_face_positions(mask, tile) <= small_n) == fits
    _check(mask, impl="xla", tile=tile, pair_cap=cap, edge_cap=cap)


@pytest.mark.parametrize("n_live,fits", [(1000, True), (20000, False)])
def test_build_remap_tables_tier_matches_core(n_live, fits):
    n_in, n_tiles, table_cap = 32768, 512, 128
    small_n = max(16384, n_in // 16)
    assert small_n < n_in and (n_live <= small_n) == fits
    r = np.random.default_rng(3)
    tids = np.full(n_in, BIG, np.int32)
    old = np.full(n_in, BIG, np.int32)
    new = np.full(n_in, BIG, np.int32)
    slots = r.choice(n_in, size=n_live, replace=False)  # live entries scattered
    tids[slots] = r.integers(0, n_tiles, size=n_live)
    old[slots] = r.integers(0, 1 << 20, size=n_live)
    new[slots] = r.integers(0, 1 << 20, size=n_live)
    args = (jnp.asarray(tids), jnp.asarray(old), jnp.asarray(new))
    got = build_remap_tables(*args, n_tiles, table_cap=table_cap)
    want = _remap_tables_core(*args, n_tiles, table_cap)
    assert not bool(want[2])
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # and the tables hold what went in: one slot for each (tile, old) pair
    pairs = np.unique(np.stack([tids[slots], old[slots]]), axis=1)
    assert int((np.asarray(got[0]) >= 0).sum()) == pairs.shape[1]


@pytest.mark.parametrize(
    "impl,backend,want",
    [
        ("tiled", "cpu", "xla"),
        ("tiled", "tpu", "xla"),
        ("xla", "tpu", "xla"),
        ("pallas", "cpu", "pallas"),
        ("auto", "tpu", "pallas"),
        ("auto", "cpu", "xla"),
        ("auto", "gpu", "xla"),
    ],
)
def test_resolve_impl(monkeypatch, impl, backend, want):
    """One resolver decides which kernels the tiled CCL and watershed
    compile: ``tiled`` is ``xla``, ``auto`` goes by the platform."""
    from cluster_tools_tpu.ops.tile_ws import resolved_modes

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_impl(impl) == want
    modes = resolved_modes(impl)
    assert modes["impl"] == want
    assert set(modes) == {"impl", "flow", "fill_mode"}


def test_tiled_alias_runs_the_xla_kernels(rng):
    mask = jnp.asarray(rng.random((20, 24, 130)) < 0.4)
    a, _, _ = label_components_tiled(mask, impl="tiled")
    b, _, _ = label_components_tiled(mask, impl="xla")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tiled_overflow_flag(rng):
    # absurdly small capacities must raise the overflow flag, not mislabel
    mask = rng.random((32, 32, 256)) < 0.5
    _, overflow, _ = label_components_tiled(
        jnp.asarray(mask), impl="xla", pair_cap=16, edge_cap=8
    )
    assert bool(overflow)


def test_tiled_spanning_component():
    # a single line spanning every tile along x: exercises chained merges
    mask = np.zeros((16, 16, 512), bool)
    mask[8, 8, :] = True
    mask[3, 3, 5] = True
    lab, ovf, _ = label_components_tiled(jnp.asarray(mask), impl="xla")
    assert not bool(ovf)
    lab = np.asarray(lab)
    line = lab[8, 8, :]
    assert len(np.unique(line)) == 1
    assert lab[3, 3, 5] != line[0]


def test_pallas_kernels_interpret(rng):
    # Mosaic kernels in interpreter mode: exact same kernel code as TPU
    from cluster_tools_tpu.ops.pallas_kernels import (
        apply_remap_pallas,
        tile_ccl_pallas,
    )

    mask = rng.random((16, 16, 256)) < 0.5
    lab = np.asarray(
        tile_ccl_pallas(jnp.asarray(mask), tile=(16, 16, 128), interpret=True)
    )
    # within-tile correctness vs scipy per tile
    for k in range(2):
        sub = mask[:, :, k * 128 : (k + 1) * 128]
        lsub = lab[:, :, k * 128 : (k + 1) * 128]
        ref, ncomp = ndi.label(sub, structure=ndi.generate_binary_structure(3, 1))
        reps = []
        for c in range(1, ncomp + 1):
            vals = np.unique(lsub[ref == c])
            assert len(vals) == 1
            reps.append(vals[0])
        assert len(set(reps)) == ncomp

    # apply kernel: remap two labels in tile 0, one in tile 1
    old = np.full((2, 64), -1, np.int32)
    new = np.full((2, 64), -1, np.int32)
    src = np.unique(lab[:, :, :128][mask[:, :, :128]])[:2]
    old[0, :2] = src
    new[0, :2] = [7, 9]
    out = np.asarray(
        apply_remap_pallas(
            jnp.asarray(lab),
            jnp.asarray(old),
            jnp.asarray(new),
            tile=(16, 16, 128),
            cap=64,
            interpret=True,
        )
    )
    assert (out[lab == src[0]] == 7).all()
    assert (out[lab == src[1]] == 9).all()
    untouched = ~np.isin(lab, src)
    assert (out[untouched] == lab[untouched]).all()


def test_tiled_full_pallas_interpret(rng):
    # end-to-end tiled CCL with the pallas impl in interpret mode
    mask = rng.random((16, 32, 256)) < 0.4
    lab, ovf, _ = label_components_tiled(jnp.asarray(mask), impl="pallas", interpret=True)
    assert not bool(ovf)
    ref, _ = ndi.label(mask, structure=ndi.generate_binary_structure(3, 1))
    assert_labels_equivalent(
        np.asarray(finalize_labels(jnp.asarray(np.asarray(lab)))), ref
    )
