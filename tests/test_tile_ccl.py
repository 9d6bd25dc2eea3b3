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

import jax.numpy as jnp

from cluster_tools_tpu.ops.ccl import finalize_labels
from cluster_tools_tpu.ops.tile_ccl import label_components_tiled
from .helpers import assert_labels_equivalent, random_blobs


def _check(mask, **kw):
    lab, overflow = label_components_tiled(jnp.asarray(mask), **kw)
    assert not bool(overflow)
    lab = np.asarray(lab)
    n = mask.size
    assert (lab[~mask] == n).all()
    ref, _ = ndi.label(mask, structure=ndi.generate_binary_structure(3, 1))
    assert_labels_equivalent(np.asarray(finalize_labels(jnp.asarray(lab))), ref)


@pytest.mark.parametrize(
    "shape,p",
    [
        ((32, 32, 128), 0.5),
        ((48, 48, 256), 0.3),
        ((16, 16, 128), 0.7),
        ((64, 64, 128), 0.08),
    ],
)
def test_tiled_vs_scipy(rng, shape, p):
    _check(rng.random(shape) < p, impl="xla")


def test_tiled_nondivisible_shapes(rng):
    # padding path: shapes that are not tile multiples
    _check(rng.random((33, 47, 130)) < 0.5, impl="xla")
    _check(rng.random((10, 10, 50)) < 0.6, impl="xla")


def test_tiled_blobs(rng):
    _check(random_blobs(rng, (40, 48, 140), p=0.45), impl="xla")


def test_tiled_empty_full():
    empty = np.zeros((16, 16, 128), bool)
    lab, ovf = label_components_tiled(jnp.asarray(empty), impl="xla")
    assert not bool(ovf) and (np.asarray(lab) == empty.size).all()
    full = np.ones((32, 16, 128), bool)
    lab, ovf = label_components_tiled(jnp.asarray(full), impl="xla")
    assert not bool(ovf)
    lab = np.asarray(lab)
    assert len(np.unique(lab)) == 1  # one component


def test_tiled_overflow_flag(rng):
    # absurdly small capacities must raise the overflow flag, not mislabel
    mask = rng.random((32, 32, 256)) < 0.5
    _, overflow = label_components_tiled(
        jnp.asarray(mask), impl="xla", pair_cap=16, edge_cap=8
    )
    assert bool(overflow)


def test_tiled_spanning_component():
    # a single line spanning every tile along x: exercises chained merges
    mask = np.zeros((16, 16, 512), bool)
    mask[8, 8, :] = True
    mask[3, 3, 5] = True
    lab, ovf = label_components_tiled(jnp.asarray(mask), impl="xla")
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
    lab, ovf = label_components_tiled(jnp.asarray(mask), impl="pallas", interpret=True)
    assert not bool(ovf)
    ref, _ = ndi.label(mask, structure=ndi.generate_binary_structure(3, 1))
    assert_labels_equivalent(
        np.asarray(finalize_labels(jnp.asarray(np.asarray(lab)))), ref
    )
