"""Real-data-shaped validation (r2 VERDICT #4): synthetic EM with exact GT
through the full MulticutSegmentationWorkflow, scored with the evaluation
tasks (VI + adapted-RAND) — the reference's CREMI oracle pattern
(SURVEY.md §4) without shipping data.  Covers anisotropic (40, 4, 4)
sampling, masks, and the 2-D per-slice mode.
"""

import json
import os

import numpy as np
import pytest

from cluster_tools_tpu.runtime.task import build
from cluster_tools_tpu.utils.synthetic import synthetic_em_volume
from cluster_tools_tpu.utils.volume_utils import file_reader
from cluster_tools_tpu.workflows import MulticutSegmentationWorkflow
from cluster_tools_tpu.tasks.evaluation import EvaluationWorkflow


@pytest.fixture
def workspace(tmp_path):
    tmp_folder = str(tmp_path / "tmp")
    config_dir = str(tmp_path / "config")
    os.makedirs(config_dir, exist_ok=True)
    with open(os.path.join(config_dir, "global.config"), "w") as f:
        json.dump({"block_shape": [8, 32, 32]}, f)
    return tmp_folder, config_dir, str(tmp_path)


def test_generator_is_deterministic_and_exact():
    b1, g1, m1 = synthetic_em_volume(shape=(16, 64, 64), n_objects=6, seed=3)
    b2, g2, m2 = synthetic_em_volume(shape=(16, 64, 64), n_objects=6, seed=3)
    np.testing.assert_array_equal(g1, g2)
    np.testing.assert_allclose(b1, b2)
    assert set(np.unique(g1[m1])) <= set(range(1, 7))
    assert (g1[~m1] == 0).all()
    # membrane contrast: interface voxels are clearly brighter than the
    # cell-interior band (anisotropic cells are thin in voxel units, so the
    # interior band sits only a few voxels off the interface)
    from scipy import ndimage

    interfaces = np.zeros(g1.shape, bool)
    for axis in range(3):
        a = [slice(None)] * 3
        b = [slice(None)] * 3
        a[axis] = slice(0, -1)
        b[axis] = slice(1, None)
        diff = (g1[tuple(a)] != g1[tuple(b)]) & (g1[tuple(a)] > 0) & (g1[tuple(b)] > 0)
        interfaces[tuple(a)] |= diff
    inner = (ndimage.distance_transform_edt(~interfaces) > 3) & m1 & (g1 > 0)
    assert b1[interfaces].mean() > 0.55
    assert b1[interfaces].mean() > b1[inner].mean() + 0.15


def test_generator_output_is_pinned():
    """The volumes the quality bounds (and chip_smoke.py's data) rest on:
    digest of the ground truth and mask as the generator made them before
    its coordinate grid went from a materialised (z, y, x, 3) array to
    broadcast axes (PR 24) — a faster generator must stay the same
    generator, bit for bit.  (The boundary map derives from the ground
    truth through scipy and ``exp``, untouched by that change and not
    pinned: a libm may round its last bit differently.)"""
    import hashlib

    _, g, m = synthetic_em_volume(shape=(16, 64, 64), n_objects=6, seed=3)
    assert hashlib.sha256(g.tobytes() + m.tobytes()).hexdigest() == (
        "c5b7da6e1afc1857c2caaf4e9bc9d4a8a3b98f744a67cee7370fcd69d170d57e"
    )


def _run_e2e(workspace, two_d: bool):
    tmp_folder, config_dir, root = workspace
    shape = (24, 96, 96)
    boundaries, gt, mask = synthetic_em_volume(
        shape=shape, n_objects=5, sampling=(40.0, 4.0, 4.0),
        boundary_width=2.0, smooth=0.3, noise=0.03, seed=7,
    )
    path = os.path.join(root, "em.zarr")
    f = file_reader(path)
    f.create_dataset("boundaries", shape=shape, chunks=(8, 32, 32),
                     dtype="float32")[...] = boundaries
    f.create_dataset("gt", shape=shape, chunks=(8, 32, 32),
                     dtype="uint64")[...] = gt
    f.create_dataset("mask", shape=shape, chunks=(8, 32, 32),
                     dtype="uint8")[...] = mask.astype(np.uint8)

    wf = MulticutSegmentationWorkflow(
        tmp_folder=tmp_folder,
        config_dir=config_dir,
        max_jobs=2,
        target="local",
        input_path=path,
        input_key="boundaries",
        ws_path=path,
        ws_key="sv",
        output_path=path,
        output_key="seg",
        mask_path=path,
        mask_key="mask",
        block_shape=[8, 32, 32],
        halo=[2, 8, 8],
        threshold=0.5,
        sigma_seeds=1.0,
        min_seed_distance=2.0,
        sampling=[2.0, 1.0, 1.0],
        two_d=two_d,
        beta=0.5,
        n_scales=1,
        agglomerator="greedy-additive",
    )
    assert build([wf])

    ev = EvaluationWorkflow(
        tmp_folder=os.path.join(tmp_folder, "eval"),
        config_dir=config_dir,
        max_jobs=2,
        target="local",
        input_path=path,
        input_key="seg",
        labels_path=path,
        labels_key="gt",
        block_shape=[8, 32, 32],
    )
    assert build([ev])
    with open(os.path.join(tmp_folder, "eval", "evaluation.json")) as fh:
        measures = json.load(fh)
    return measures, np.asarray(file_reader(path)["seg"][:]), gt, mask


def _evaluate_seg(tmp_folder, config_dir, path):
    ev = EvaluationWorkflow(
        tmp_folder=os.path.join(tmp_folder, "eval"),
        config_dir=config_dir, max_jobs=2, target="local",
        input_path=path, input_key="seg",
        labels_path=path, labels_key="gt",
        block_shape=[8, 32, 32],
    )
    assert build([ev])
    with open(os.path.join(tmp_folder, "eval", "evaluation.json")) as fh:
        return json.load(fh)


@pytest.mark.slow  # tier-2 (make tier2): ~29 s of XLA compiles; the fused
# variant below keeps the synthetic-EM multicut path in tier-1
def test_multicut_on_synthetic_em_3d(workspace):
    measures, seg, gt, mask = _run_e2e(workspace, two_d=False)
    # quality against exact GT: VI well under 1 bit total, adapted-RAND
    # error small — the 8 Voronoi cells must be essentially recovered
    assert measures["vi_split"] + measures["vi_merge"] < 1.0, measures
    assert measures["adapted_rand_error"] < 0.15, measures
    assert (seg[~mask] == 0).all()


def test_multicut_on_synthetic_em_2d_mode(workspace):
    measures, seg, gt, mask = _run_e2e(workspace, two_d=True)
    # per-slice watershed (the reference's anisotropic mode) still recovers
    # the objects after agglomeration, to a looser bound
    assert measures["vi_split"] + measures["vi_merge"] < 1.5, measures
    assert measures["adapted_rand_error"] < 0.25, measures


@pytest.mark.slow  # tier-2 (make tier2): ~18 s of XLA compiles; the fused
# fast path on synthetic EM — the 2d_mode variant stays tier-1.
def test_multicut_on_fused_fragments(workspace):
    """The fused fast path composes with the flagship chain: stitched fused
    watershed fragments feed MulticutSegmentationWorkflow(skip_ws=True) and
    the result stays within the quality envelope."""
    from cluster_tools_tpu.tasks.fused import FusedSegmentationLocal

    tmp_folder, config_dir, root = workspace
    shape = (24, 96, 96)
    boundaries, gt, _ = synthetic_em_volume(
        shape=shape, n_objects=5, sampling=(40.0, 4.0, 4.0),
        boundary_width=2.0, smooth=0.3, noise=0.03, seed=7,
    )
    # no mask here: the fused step's mask plumbing is exercised at the ops
    # level; this test covers composition with the flagship chain
    path = os.path.join(root, "emf.zarr")
    f = file_reader(path)
    f.create_dataset("boundaries", shape=shape, chunks=(8, 32, 32),
                     dtype="float32")[...] = boundaries
    f.create_dataset("gt", shape=shape, chunks=(8, 32, 32),
                     dtype="uint64")[...] = gt

    fused = FusedSegmentationLocal(
        tmp_folder=tmp_folder, config_dir=config_dir, max_jobs=2,
        input_path=path, input_key="boundaries",
        output_path=path, ws_key="sv",
        threshold=0.5, halo=2, min_seed_distance=2.0,
        stitch_ws_threshold=0.5, max_labels_per_shard=8192,
        block_shape=[8, 32, 32],
    )
    assert build([fused])

    wf = MulticutSegmentationWorkflow(
        tmp_folder=tmp_folder, config_dir=config_dir, max_jobs=2,
        target="local",
        input_path=path, input_key="boundaries",
        ws_path=path, ws_key="sv", skip_ws=True,
        output_path=path, output_key="seg",
        block_shape=[8, 32, 32],
        beta=0.5, n_scales=1, agglomerator="greedy-additive",
    )
    assert build([wf])

    measures = _evaluate_seg(tmp_folder, config_dir, path)
    assert measures["vi_split"] + measures["vi_merge"] < 1.5, measures
    assert measures["adapted_rand_error"] < 0.25, measures
