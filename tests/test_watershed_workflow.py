"""Integration tests: blockwise watershed tasks (single- and two-pass)
against structural oracles (SURVEY.md §4: consistency checks rather than
exact label equality for watershed workflows)."""

import json
import os

import numpy as np
import pytest
import scipy.ndimage as ndi

from cluster_tools_tpu.runtime.task import build
from cluster_tools_tpu.tasks.watershed import WatershedWorkflow
from cluster_tools_tpu.utils.volume_utils import file_reader


@pytest.fixture
def workspace(tmp_path):
    tmp_folder = str(tmp_path / "tmp")
    config_dir = str(tmp_path / "config")
    os.makedirs(config_dir, exist_ok=True)
    with open(os.path.join(config_dir, "global.config"), "w") as f:
        json.dump({"block_shape": [16, 16, 16]}, f)
    return tmp_folder, config_dir, str(tmp_path)


def _boundary_volume(rng, shape=(32, 32, 32)):
    """Smooth random field in [0, 1]: ridges act as boundaries."""
    x = rng.random(shape)
    x = ndi.gaussian_filter(x, 2.0)
    lo, hi = x.min(), x.max()
    return ((x - lo) / (hi - lo)).astype(np.float32)


def _run_ws(workspace, vol, two_pass, **params):
    tmp_folder, config_dir, root = workspace
    out_key = params.pop("output_key", "labels")
    path = os.path.join(root, "ws.zarr")
    f = file_reader(path)
    ds = f.require_dataset(
        "boundaries", shape=vol.shape, chunks=(16, 16, 16), dtype="float32"
    )
    ds[...] = vol
    wf = WatershedWorkflow(
        tmp_folder=tmp_folder,
        config_dir=config_dir,
        max_jobs=2,
        target="local",
        input_path=path,
        input_key="boundaries",
        output_path=path,
        output_key=out_key,
        block_shape=[16, 16, 16],
        halo=[4, 4, 4],
        two_pass=two_pass,
        threshold=0.5,
        **params,
    )
    assert build([wf])
    return np.asarray(file_reader(path)[out_key][:])


def test_single_pass_labels_everything(rng, workspace):
    vol = _boundary_volume(rng)
    labels = _run_ws(workspace, vol, two_pass=False)
    assert labels.shape == vol.shape
    assert (labels > 0).all()  # no mask: every voxel drains to some basin
    # labels are unique per block: no label spans two blocks
    for z in (16,):
        lo, hi = labels[z - 1], labels[z]
        assert not np.intersect1d(np.unique(lo), np.unique(hi)).size


def test_two_pass_stitches_across_faces(rng, workspace):
    vol = _boundary_volume(rng)
    labels = _run_ws(workspace, vol, two_pass=True)
    assert (labels > 0).all()
    # some basins must span a block face (the whole point of two-pass)
    spans = 0
    for axis in range(3):
        lo = np.take(labels, 15, axis=axis)
        hi = np.take(labels, 16, axis=axis)
        spans += np.intersect1d(np.unique(lo), np.unique(hi)).size
    assert spans > 0, "no label crosses any block face"
    # labels should be (almost all) single connected regions; cropping a
    # halo-computed basin to the inner block can split a few — same artifact
    # as the reference's blockwise watershed
    struct = ndi.generate_binary_structure(3, 3)
    uniq = [lab for lab in np.unique(labels) if lab != 0]
    split = sum(
        1 for lab in uniq if ndi.label(labels == lab, structure=struct)[1] != 1
    )
    assert split / len(uniq) < 0.05, f"{split}/{len(uniq)} labels fragmented"


@pytest.mark.slow  # tier-2 (make tier2): ~23 s of XLA compiles; resume
# idempotency is covered tier-1 by test_cc_workflow_resume — the two-pass
# stitching property itself stays tier-1 via _stitches_across_faces.
def test_two_pass_resume_is_idempotent(rng, workspace):
    vol = _boundary_volume(rng)
    labels1 = _run_ws(workspace, vol, two_pass=True)
    # second build: all targets exist, nothing reruns, output unchanged
    labels2 = _run_ws(workspace, vol, two_pass=True)
    np.testing.assert_array_equal(labels1, labels2)


def test_size_filter_removes_small_fragments(rng, workspace):
    # single block, no halo: the per-block size floor holds exactly (with
    # halo+crop, a >=N outer segment can shrink below N in the inner crop)
    from cluster_tools_tpu.ops.watershed import (
        distance_transform_watershed,
        filter_small_segments,
    )
    import jax.numpy as jnp

    vol = _boundary_volume(rng, shape=(24, 24, 24))
    lab = distance_transform_watershed(jnp.asarray(vol), threshold=0.5)
    filtered = np.asarray(
        filter_small_segments(lab, jnp.asarray(vol), jnp.int32(20))
    )
    uniq, counts = np.unique(filtered[filtered > 0], return_counts=True)
    assert len(uniq) > 0
    assert counts.min() >= 20
    # filtering must not *create* labels
    assert np.isin(uniq, np.unique(np.asarray(lab))).all()


def test_ws_task_large_block_capped_edt(workspace, rng):
    """A >160-extent block must run through the capped erosion-cascade EDT.

    Before the halo-derived ``dt_max_distance`` default (VERDICT r2 #5), an
    uncapped 256-extent block selected the O(n^2) broadcast min-plus, which
    materializes an (..., 256, 256) intermediate per line — BASELINE-shape
    blocks could not run through the *task* path at all.
    """
    vol = _boundary_volume(rng, (8, 8, 256))
    tmp_folder, config_dir, root = workspace
    path = os.path.join(root, "ws_big.zarr")
    f = file_reader(path)
    ds = f.require_dataset(
        "boundaries", shape=vol.shape, chunks=(8, 8, 256), dtype="float32"
    )
    ds[...] = vol
    wf = WatershedWorkflow(
        tmp_folder=tmp_folder,
        config_dir=config_dir,
        max_jobs=1,
        target="local",
        input_path=path,
        input_key="boundaries",
        output_path=path,
        output_key="labels",
        block_shape=[8, 8, 256],
        halo=[2, 2, 8],
        two_pass=False,
        threshold=0.5,
    )
    assert build([wf])
    labels = np.asarray(file_reader(path)["labels"][:])
    fg = vol < 0.5
    # the flood covers ridges too (vigra semantics): everything is labeled
    assert (labels[fg] > 0).mean() > 0.95
    assert len(np.unique(labels[labels > 0])) > 1


def test_ws_task_config_respects_explicit_dt_cap(workspace, rng):
    from cluster_tools_tpu.tasks.watershed import WatershedBase

    cfg = dict(WatershedBase.default_task_config())
    assert cfg["dt_max_distance"] is None  # halo-derived by default
    cfg["halo"] = [4, 4, 4]
    cfg["threshold"] = 0.5
    kp = WatershedBase.__new__(WatershedBase)._kernel_params(cfg)
    assert kp["dt_max_distance"] == 8.0  # floor dominates a 4-voxel halo
    cfg["dt_max_distance"] = 12.5
    kp = WatershedBase.__new__(WatershedBase)._kernel_params(cfg)
    assert kp["dt_max_distance"] == 12.5


@pytest.mark.slow  # tier-2 (make tier2): ~32 s of XLA compiles; threshold
# agglomeration also runs under the multicut/synthetic-EM tier-1 tests
def test_agglomerate_threshold_merges_fragments(rng, workspace):
    """reference watershed/agglomerate.py: in-block average-linkage merge of
    fragments under the mean-boundary threshold."""
    vol = _boundary_volume(rng)
    plain = _run_ws(workspace, vol, two_pass=False)
    merged = _run_ws(
        workspace, vol, two_pass=False, agglomerate_threshold=0.9,
        output_key="labels_agg",
    )
    n_plain = len(np.unique(plain[plain > 0]))
    n_merged = len(np.unique(merged[merged > 0]))
    assert 0 < n_merged < n_plain, (n_merged, n_plain)
    assert (merged > 0).all()
    # a conservative threshold must merge nothing
    same = _run_ws(
        workspace, vol, two_pass=False, agglomerate_threshold=0.0,
        output_key="labels_noop",
    )
    assert len(np.unique(same[same > 0])) == n_plain


def test_agglomerate_threshold_refused_for_two_pass(workspace):
    """The workflow must refuse BEFORE pass one runs (and checkpoints)
    agglomerated even blocks that pass two would then mix with
    un-agglomerated labels."""
    from cluster_tools_tpu.tasks.watershed import WatershedWorkflow

    tmp_folder, config_dir, root = workspace
    wf = WatershedWorkflow(
        tmp_folder=tmp_folder,
        config_dir=config_dir,
        max_jobs=2,
        target="local",
        input_path="x.zarr",
        input_key="b",
        output_path="x.zarr",
        output_key="labels",
        two_pass=True,
        agglomerate_threshold=0.5,
    )
    with pytest.raises(NotImplementedError, match="not supported"):
        wf.requires()


def test_host_impl_runs_reference_style_pipeline(rng, workspace):
    """impl='host' (ops/host.py, the reference's per-job scipy compute) is a
    real selectable path: foreground fragments exist, background stays 0,
    and the CC twin matches scipy exactly."""
    from cluster_tools_tpu.ops.host import host_ws_ccl

    vol = _boundary_volume(rng)
    labels = _run_ws(workspace, vol, two_pass=False, impl="host")
    fg = vol < 0.5
    assert labels.shape == vol.shape
    assert (labels[~fg] == 0).all()
    assert (labels[fg] > 0).mean() > 0.95  # watershed_ift floods foreground

    ws, cc, n_fg = host_ws_ccl(vol, 0.5, dt_max_distance=4.0)
    assert n_fg == int(fg.sum())
    want, n_want = ndi.label(fg)
    got_ids = np.unique(cc[fg])
    assert len(got_ids) == n_want
    # component partition identical (relabel-invariant comparison)
    first = {g: want[cc == g][0] for g in got_ids}
    for g, w in first.items():
        assert (want[cc == g] == w).all()


def test_host_impl_refuses_unsupported_combinations(workspace, rng):
    """size_filter has no host twin: the task must fail loudly (build()
    returns False), not silently skip the filter."""
    tmp_folder, config_dir, root = workspace
    vol = _boundary_volume(rng)
    path = os.path.join(root, "ws.zarr")
    f = file_reader(path)
    ds = f.require_dataset(
        "boundaries", shape=vol.shape, chunks=(16, 16, 16), dtype="float32"
    )
    ds[...] = vol
    wf = WatershedWorkflow(
        tmp_folder=tmp_folder,
        config_dir=config_dir,
        max_jobs=2,
        target="local",
        input_path=path,
        input_key="boundaries",
        output_path=path,
        output_key="sf",
        block_shape=[16, 16, 16],
        halo=[4, 4, 4],
        two_pass=False,
        threshold=0.5,
        impl="host",
        size_filter=10,
    )
    assert not build([wf])


def test_host_impl_refused_for_two_pass(workspace, rng):
    """Two-pass needs the seeded device kernel for pass two; a scipy pass
    one + device pass two hybrid must not be stitched silently."""
    tmp_folder, config_dir, root = workspace
    vol = _boundary_volume(rng)
    path = os.path.join(root, "ws.zarr")
    f = file_reader(path)
    ds = f.require_dataset(
        "boundaries", shape=vol.shape, chunks=(16, 16, 16), dtype="float32"
    )
    ds[...] = vol
    wf = WatershedWorkflow(
        tmp_folder=tmp_folder,
        config_dir=config_dir,
        max_jobs=2,
        target="local",
        input_path=path,
        input_key="boundaries",
        output_path=path,
        output_key="tp",
        block_shape=[16, 16, 16],
        halo=[4, 4, 4],
        two_pass=True,
        threshold=0.5,
        impl="host",
    )
    assert not build([wf])


@pytest.mark.slow  # tier-2 (make tier2): ~22 s of XLA compiles; knob
# plumbing is also covered by the tile_ws knob tests in tier-1
def test_capacity_knobs_reach_the_tiled_kernel(rng, workspace):
    # a starved fill_rounds must surface as the task's loud overflow
    # warning (in the per-task LOG FILE — the task logger doesn't
    # propagate) — proving the config knob actually reaches the kernel
    # (the round-4 regression was knobs silently unreachable from the
    # task API).  Raw noise with a high min_seed_distance leaves many
    # unseeded basins, so one Boruvka round cannot converge.
    import glob

    def all_logs():
        return "".join(
            open(p).read()
            for p in glob.glob(os.path.join(workspace[0], "*.log"))
        )

    vol = rng.random((32, 32, 32)).astype(np.float32)
    # negative control: default caps on the same volume stay clean — so
    # the overflow below can ONLY come from the knob reaching the kernel
    labels = _run_ws(
        workspace, vol, two_pass=False, impl="xla",
        min_seed_distance=2.0, output_key="labels_ctrl",
    )
    assert labels.shape == vol.shape
    assert "overflowed" not in all_logs()
    labels = _run_ws(
        workspace, vol, two_pass=False, impl="xla",
        min_seed_distance=2.0, fill_rounds=1,
        output_key="labels_knobs",
    )
    assert labels.shape == vol.shape
    assert "overflowed" in all_logs()


def test_cap_knobs_pick_keys_by_name_and_ignore_a_stale_seed_cap():
    """A config file written before PR 33 may still carry ``seed_cap`` (the
    sparse seed labeler's knob, deleted with it): the key is ignored, the
    others arrive, and what the kernel entry points accept is what the task
    forwards."""
    import inspect

    from cluster_tools_tpu.ops.tile_ws import dt_watershed_tiled
    from cluster_tools_tpu.tasks.watershed import WatershedBase, _tiled_cap_knobs

    cfg = dict(WatershedBase.default_task_config(), seed_cap=4096,
               fill_cap=1 << 20, table_cap=32)
    knobs = _tiled_cap_knobs(cfg)
    assert knobs == {"fill_cap": 1 << 20, "table_cap": 32}
    accepted = set(inspect.signature(dt_watershed_tiled).parameters)
    assert "seed_cap" not in accepted
    every = _tiled_cap_knobs(dict.fromkeys(cfg, 1))
    assert set(every) <= accepted and len(every) == 7
