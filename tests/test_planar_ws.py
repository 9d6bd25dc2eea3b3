"""The per-section (2-D) watershed on the tiled kernels' planar form, held
against the benchmark's plain reference at a size the CPU runs.

The cell ``ws2d125.volumes`` (``benchmark/configs/ws_2d_cremi_125.json``)
cut down: the same workflow through ``cli``'s builder with ``two_d``,
sampling (10, 1, 1), flat blocks with an in-plane halo of the EDT window and
none in z, a ragged last block row on every axis, the same comparison
(``benchmark/comparisons/ws_planar.py``) over every block, and the same
reference (``benchmark/reference_2d.py``).  One device, so that the sweep
is the sharded one of the chip; two jobs in one process, so that the second
finds the kept program.  Beside it, the 3-D tiled kernel's labels against
the hashes its parent produced (the planar form is a static set of axes
threaded through code the 3-D flood shares).
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import data, reference as ref, reference_2d as ref2d
from benchmark import reference_two_pass as grid
from benchmark.comparisons import ws_planar
from cluster_tools_tpu.ops import work

SHAPE, BLOCK, HALO = (29, 40, 72), (8, 16, 32), (0, 16, 16)
DT_MAX = 16.0
PARAMS = dict(
    two_d=True, threshold=0.5, sampling=[10, 1, 1], halo=list(HALO),
    dt_max_distance=DT_MAX, block_shape=list(BLOCK), connectivity=1, impl="auto",
    sigma_seeds=0, size_filter=0, device_batch=4,
)


@pytest.fixture(scope="module")
def one_device(tmp_path_factory):
    """One CPU device, the exact fill, JAX's cache and the step store in a
    directory of the module's own (everything put back afterwards)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from cluster_tools_tpu.parallel import step_cache
    from cluster_tools_tpu.runtime import executor as executor_mod

    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs")
    config = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    cc.reset_cache()
    step_cache.forget()
    built = []
    inner = executor_mod.batched_shard_map

    def named(kernel, *a, **k):
        built.append(kernel.__name__)
        return inner(kernel, *a, **k)

    with pytest.MonkeyPatch.context() as patch:
        one = jax.devices("cpu")[:1]
        patch.setattr(executor_mod, "get_devices", lambda *a, **k: one)
        patch.setattr(executor_mod, "batched_shard_map", named)
        patch.setenv("CT_FILL_MODE", "dense")
        yield built
    for n, value in config.items():
        jax.config.update(n, value)
    cc.reset_cache()
    step_cache.forget()


def run_job(root, path, tag, traced=False):
    from cluster_tools_tpu import cli
    from cluster_tools_tpu.runtime import trace
    from cluster_tools_tpu.runtime.task import build

    tmp = os.path.join(root, f"tmp_{tag}")
    os.makedirs(tmp)
    with open(os.path.join(tmp, "global.config"), "w") as f:
        json.dump({"block_shape": list(BLOCK)}, f)
    trace.configure(enabled=traced, trace_dir=os.path.join(root, f"trace_{tag}"))
    try:
        wf = cli._resolve("watershed")(
            tmp_folder=tmp, config_dir=tmp, max_jobs=4, target="local",
            input_path=path, input_key="vol", output_path=path,
            output_key=f"ws_{tag}", **PARAMS)
        ok = build([wf])
        spans = trace._get().snapshot_events()
    finally:
        trace.reset()
    with open(os.path.join(tmp, f"{wf.uid}.success.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(tmp, "io_metrics.json")) as f:
        metrics = {uid.split(".")[0]: m for uid, m in json.load(f)["tasks"].items()}
    return dict(ok=ok, tmp=tmp, spans=spans, manifest=manifest, metrics=metrics,
                ws=ref.read_zarr(path, f"ws_{tag}"))


@pytest.fixture(scope="module")
def jobs(one_device, tmp_path_factory):
    """Two ``watershed`` jobs with ``two_d`` on one seeded stack, the first
    traced; the names of the sweep programs that were built."""
    from cluster_tools_tpu.utils.volume_utils import file_reader

    root = str(tmp_path_factory.mktemp("planar"))
    vol = data.membrane_volume(2147493701, 0, SHAPE, 12)
    path = os.path.join(root, "data.zarr")
    file_reader(path).create_dataset(
        "vol", shape=vol.shape, chunks=BLOCK, dtype="float32")[...] = vol
    first = run_job(root, path, "first", traced=True)
    second = run_job(root, path, "second")
    return dict(vol=vol, first=first, second=second, built=list(one_device))


def reference_labels(vol):
    """The whole blockwise per-section watershed, plainly: every block's unit
    flooded plane by plane, its block stored, a fragment numbered as the
    program numbers it (block number, least flat index of its seed + 1)."""
    radius = ref2d.in_plane_radius(DT_MAX, PARAMS["sampling"])
    n_outer = int(np.prod(grid.outer_shape(BLOCK, HALO)))
    ws = np.zeros(vol.shape, np.uint64)
    for number, pos in grid.blocks_of(vol.shape, BLOCK):
        height, _, _, inner = grid.unit_of(vol, ws, pos, BLOCK, HALO)
        tree, seeds, n = ref2d.flood_unit(height, threshold=0.5, radius=radius)
        out = grid.encode(number, n_outer, ref2d.place_labels(tree, seeds, n))
        lo, hi, _, _ = grid.unit_bounds(pos, vol.shape, BLOCK, HALO)
        ws[tuple(slice(a, b) for a, b in zip(lo, hi))] = out[inner]
    return ws


def test_the_job_completes_with_nothing_absorbed(jobs):
    from benchmark import run

    for job in (jobs["first"], jobs["second"]):
        assert job["ok"]
        assert run.absorbed_failures(job["tmp"]) == []


def test_every_count_of_the_comparison_is_zero_on_every_block(jobs):
    n_blocks = len(grid.blocks_of(SHAPE, BLOCK))
    counts = ws_planar.check_job(jobs["vol"], jobs["first"]["ws"], {"params": PARAMS},
                                 np.random.default_rng(0), n_blocks)
    assert set(counts) == set(ws_planar.LIMITS) - {"labels_missing"}
    assert counts == {k: 0 for k in counts}, counts


def test_the_stored_labels_are_the_references_to_the_label(jobs):
    want = reference_labels(jobs["vol"])
    np.testing.assert_array_equal(jobs["first"]["ws"], want)
    np.testing.assert_array_equal(jobs["second"]["ws"], want)
    assert ws_planar.labels_in_two_planes(want) == 0


def test_the_sweep_is_the_named_planar_program_kept_across_jobs(jobs):
    """``two_d`` selects the planar kernel, whose sharded program carries its
    name; the second job of the process finds it there."""
    assert set(jobs["built"]) == {"ws_block_planar"}
    first, second = (jobs[k]["metrics"]["watershed"]["step_cache"]
                     for k in ("first", "second"))
    assert first == {"process_hits": 0, "store_hits": 0, "builds": 1, "fallbacks": 0}
    assert second == {"process_hits": 1, "store_hits": 0, "builds": 0, "fallbacks": 0}


def test_each_lanes_record_has_no_z_face_and_the_pass_counts_its_planes(jobs):
    passes = [ev["args"] for ev in jobs["first"]["spans"]
              if ev.get("ph") == "X" and ev["name"] == "ws.pass"]
    assert len(passes) == 1
    args = passes[0]
    rows = args["work"]
    assert len(rows) == len(grid.blocks_of(SHAPE, BLOCK))
    assert all(r[work.FILL_FACES_Z] == 0 for r in rows)
    assert sum(r[work.FILL_FACES_Y] + r[work.FILL_FACES_X] for r in rows) > 0
    assert not any(r[n] > 0 for r in rows for n in work.OVER)
    assert args["two_d"] is True
    assert args["planes"] == args["lanes"] * BLOCK[0]
    manifest = jobs["first"]["manifest"]["passes"]["watershed"]
    assert manifest["two_d"] is True and manifest["planes"] == args["planes"]
    assert manifest["work"][work.FILL_FACES_Z] == 0


def test_a_3d_sweep_floods_no_plane_alone():
    from cluster_tools_tpu.tasks.watershed import _pass_counters

    out = _pass_counters({"n_dispatches": 1}, [], (8, 48, 64), True, set(), {})
    assert out["two_d"] is False and out["planes"] == 0


def test_two_d_with_two_pass_is_still_refused():
    from cluster_tools_tpu.tasks import watershed as ws_mod

    wf = ws_mod.WatershedWorkflow(
        tmp_folder="/nonexistent", config_dir="", target="local", two_pass=True,
        two_d=True, input_path="x", input_key="x", output_path="x", output_key="x")
    with pytest.raises(NotImplementedError, match="two-pass"):
        wf.requires()


# --------------------------------------------------------------------------
# the kernels
# --------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [3, 2147493702])
def test_planar_kernel_twins_flood_as_the_reference_does(seed):
    """Pallas (interpreted) and XLA twins bit-identical, and both the
    reference's labels to the label: each plane alone, a fragment numbered
    by its seed's least flat index + 1, so no label in two planes."""
    from cluster_tools_tpu.ops.tile_ws import PLANES, dt_watershed_tiled

    vol = data.membrane_volume(seed, 0, (8, 40, 128), 6)
    got = {}
    for impl, interpret in (("xla", False), ("pallas", True)):
        lab, ovf, rec = dt_watershed_tiled(
            jnp.asarray(vol), threshold=0.5, sampling=(10.0, 1.0, 1.0),
            dt_max_distance=8.0, impl=impl, interpret=interpret, fill_mode="dense",
            axes=PLANES)
        assert not bool(ovf)
        assert work.unpack(rec)[0][work.FILL_FACES_Z] == 0
        got[impl] = np.asarray(lab)
    np.testing.assert_array_equal(got["xla"], got["pallas"])
    tree, seeds, n = ref2d.flood_unit(vol, threshold=0.5, radius=8)
    np.testing.assert_array_equal(got["xla"], ref2d.place_labels(tree, seeds, n))


def test_planar_capacity_fill_keeps_to_the_planes():
    """The capacity fill (the TPU's default) has a planar form too: no
    label in two planes, every basin's descent its plane's."""
    from cluster_tools_tpu.ops.tile_ws import PLANES, dt_watershed_tiled

    vol = data.membrane_volume(5, 0, (8, 40, 128), 6)
    lab, ovf, rec = dt_watershed_tiled(
        jnp.asarray(vol), threshold=0.5, dt_max_distance=8.0, impl="xla",
        fill_mode="capacity", axes=PLANES)
    lab = np.asarray(lab)
    assert not bool(ovf) and work.unpack(rec)[0][work.FILL_FACES_Z] == 0
    assert ws_planar.labels_in_two_planes(lab) == 0
    _, seeds, _ = ref2d.flood_unit(vol, threshold=0.5, radius=8)
    differs = ref2d.descent_differs(vol, lab)
    assert not (differs & (seeds == 0) & (lab > 0)).any()


def test_the_size_filter_regrows_each_plane_from_its_own_labels():
    """``size_filter`` on planar labels: small fragments go, the freed
    voxels regrow from their own plane, so still no label in two planes."""
    from cluster_tools_tpu.ops.tile_ws import PLANES, dt_watershed_tiled
    from cluster_tools_tpu.ops.watershed import filter_small_segments

    vol = jnp.asarray(data.membrane_volume(5, 0, (8, 40, 128), 6))
    lab, _, _ = dt_watershed_tiled(vol, threshold=0.5, dt_max_distance=8.0,
                                   impl="xla", fill_mode="dense", axes=PLANES)
    got = np.asarray(filter_small_segments(lab, vol, jnp.int32(40), planes=True))
    lab = np.asarray(lab)
    _, sizes = np.unique(lab[lab > 0], return_counts=True)
    assert (sizes < 40).any()                       # something to filter
    _, sizes = np.unique(got[got > 0], return_counts=True)
    assert (sizes >= 40).all()
    np.testing.assert_array_equal(got > 0, lab > 0)
    assert ws_planar.labels_in_two_planes(got) == 0


def test_legacy_two_d_stays_for_eight_connectivity():
    """``connectivity`` 2 keeps the dense fixpoint per plane (the tiled
    kernels are face-connected only); its labels keep to their planes."""
    from cluster_tools_tpu.ops.watershed import distance_transform_watershed

    vol = data.membrane_volume(11, 0, (4, 32, 32), 4)
    lab = np.asarray(distance_transform_watershed(
        jnp.asarray(vol), threshold=0.5, connectivity=2, two_d=True,
        dt_max_distance=8.0))
    assert (lab[vol < 0.5] > 0).all()
    assert ws_planar.labels_in_two_planes(lab) == 0


#: sha256 of the 3-D tiled kernel's labels on the parent of the planar form
#: (commit 6d6d3dc), seed 2147484411, a 24 x 40 x 136 stack of 9 cells
PARENT_3D = {
    ("xla", "dense"): "c3f05eb8b1c04c675e44a8d364d5a6960295caa229f262e7c15b32fb3f5157cb",
    ("xla", "capacity"): "6691e21b16b3792813e1e3030f8fbbc9fe501057d3d46f826fd05a7315e3531d",
    ("pallas", "dense"): "c3f05eb8b1c04c675e44a8d364d5a6960295caa229f262e7c15b32fb3f5157cb",
}


@pytest.mark.parametrize("impl,fill_mode", sorted(PARENT_3D))
def test_the_3d_kernel_is_bit_identical_to_its_parent(impl, fill_mode):
    from cluster_tools_tpu.ops.tile_ws import dt_watershed_tiled

    vol = data.membrane_volume(2147484411, 0, (24, 40, 136), 9)
    lab, ovf, _ = dt_watershed_tiled(
        jnp.asarray(vol), threshold=0.5, sampling=(10.0, 1.0, 1.0),
        dt_max_distance=16.0, impl=impl, interpret=(impl == "pallas"),
        fill_mode=fill_mode)
    assert not bool(ovf)
    digest = hashlib.sha256(np.asarray(lab).tobytes()).hexdigest()
    assert digest == PARENT_3D[(impl, fill_mode)]
