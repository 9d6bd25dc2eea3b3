"""The slab deployment (`fused4x384.volumes.sp4`) at a size the CPU holds.

``tests/conftest.py`` forces 8 host devices and the fused task takes every
device of its target, so here a job is 8 z-slabs.  The cell is driven
through the harness's own ``run_cell`` on the test's copy of it (``chips`` =
the CPU devices found, each slab as thick as the shrunk halo), as
``benchmark/test_correct.py`` drives its cells: the same workflow, store,
reader, reference and comparison (``benchmark/comparisons/ws_labels_slabs.py``)
that decide ``correct`` on the chip.  A sound run reads 0 everywhere; then
the comparison is shown to fail on what exists only across slabs.

Keep this file under some twenty tests: xdist's ``loadfile`` hands files
out by their number of tests, largest first, so a file with more is one of
the six that start together, and this one's compiles then starve the
150 ms deadlines of ``tests/test_supervision.py`` beside it (seen: 2 runs of
3 failed there with 28 tests here).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark import control, data, run
from benchmark import reference as ref
from cluster_tools_tpu.tasks.fused import collective_bytes

from .helpers import programs_built_here

CELL = "fused4x384.volumes.sp4"
HALO = 16
SEED = 2147483659   # past 32 signed bits, as the driver's seeds are


def n_slabs():
    return len(jax.devices("cpu"))


@pytest.fixture(scope="module", autouse=True)
def harness_leaves_the_process_as_it_found_it(tmp_path_factory):
    """``run_cell`` is a process's entry point: it sets the deployment's
    environment, turns JAX's persistent compile cache on (every program, no
    bound) and takes over the compiler's log.  Here it runs inside a worker
    that goes on to other test files, so the cache lives in a directory of
    this module's own and everything is put back afterwards."""
    import logging

    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_compilation_cache_max_size",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    config = {n: getattr(jax.config, n) for n in names}
    environ = dict(os.environ)
    log = logging.getLogger("jax._src.compiler")
    logger = (log.level, log.propagate, list(log.handlers))
    cache = str(tmp_path_factory.mktemp("jax_cache"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    cc.reset_cache()
    yield
    os.environ.clear()
    os.environ.update(environ)
    for n, value in config.items():
        jax.config.update(n, value)
    cc.reset_cache()
    log.setLevel(logger[0])
    log.propagate, log.handlers[:] = logger[1], logger[2]


def shrink(spec):
    config, cell = spec["config"], spec["cell"]
    cell["chips"] = n_slabs()
    shape = [HALO * cell["chips"], 64, 64]
    sized = {"volume_shape": shape, "block_shape": [32, 32, 32],
             "cells": 2 * cell["chips"]}
    config["data"] = dict(sized)
    cell["traffic"].update(sized)
    config["store"]["chunks"] = [32, 32, 32]
    config["global_config"] = {"block_shape": [32, 32, 32]}
    config["params"].update(block_shape=[32, 32, 32], halo=HALO,
                            dt_max_distance=float(HALO))
    cell["check_units"] = 64   # every box of every slab


def drive(seed=SEED):
    return run.run_cell(CELL, seed, seconds=0.1, trace=False, require_chip=False,
                        shrink=shrink)


def bad(result):
    return {k: c["value"] for k, c in result["checks"].items()
            if c["value"] > c["limit"] and k != "compiles_in_window"}


def test_sound_run_reads_zero_everywhere():
    result = drive()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    compared = {k: c["value"] for k, c in result["checks"].items()}
    compared.pop("compiles_in_window")
    slabs = run.load_by_file("comparisons", "ws_labels_slabs")
    assert compared == dict.fromkeys(slabs.LIMITS, 0)
    assert result["device"]["count"] == n_slabs() >= 2
    assert result["metrics"]["voxels_per_s"]["value"] > 0


# -- the comparison shown to fail on what exists only across slabs ----------


def halo_not_exchanged(monkeypatch):
    """Interior slabs are fed 1.0 where the neighbour's planes belong."""
    from cluster_tools_tpu.parallel import pipeline

    def padded(x, halo, axis, axis_name, axis_size, fill=0):
        pad = [(0, 0)] * x.ndim
        pad[axis] = (halo, halo)
        return jnp.pad(x, pad, constant_values=fill)

    monkeypatch.setattr(pipeline, "exchange_halo", padded)
    return {"ws_seed_mismatch", "ws_descent_mismatch", "ws_flood_mismatch"}


def merge_skipped_at_one_cut(monkeypatch):
    """One slab never sees the face below it: no label pair crosses that cut."""
    from cluster_tools_tpu.parallel import distributed_ccl

    inner = distributed_ccl.neighbor_face

    def blind(x, axis, axis_name, axis_size, direction=-1, fill=0):
        out = inner(x, axis, axis_name, axis_size, direction=direction, fill=fill)
        return jnp.where(lax.axis_index(axis_name) == axis_size // 2,
                         jnp.zeros_like(out), out)

    monkeypatch.setattr(distributed_ccl, "neighbor_face", blind)
    return {"cc_mismatch_voxels"}


def two_slabs_share_a_label(monkeypatch):
    """One voxel of the second slab carries a fragment label of the first."""
    from cluster_tools_tpu.io import containers

    inner = containers.Dataset.__setitem__

    def shared(self, bb, value):
        value = np.asarray(value)
        if value.dtype == np.uint64 and ":ws_" in self._label:
            value = value.copy()
            thick = value.shape[0] // n_slabs()
            first, second = value[:thick], value[thick:2 * thick]
            second[tuple(np.argwhere(second > 0)[0])] = first[first > 0][0]
        return inner(self, bb, value)

    monkeypatch.setattr(containers.Dataset, "__setitem__", shared)
    return {"ws_labels_in_two_slabs"}


def input_rounded_to_bfloat16(monkeypatch):
    """The control: the nearest precision below the float32 that the
    configuration states, at the container doorway."""
    from cluster_tools_tpu.io import containers

    inner = containers.Dataset.__getitem__

    def rounded(self, bb):
        out = inner(self, bb)
        return control.round_to_bfloat16(out) if out.dtype == np.float32 else out

    monkeypatch.setattr(containers.Dataset, "__getitem__", rounded)
    return set()


@pytest.mark.parametrize("fault", [
    halo_not_exchanged, merge_skipped_at_one_cut, two_slabs_share_a_label,
    input_rounded_to_bfloat16], ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault, monkeypatch):
    expected = fault(monkeypatch)
    with programs_built_here():   # two of the faults patch the step's program
        result = drive()
    assert not result["correct"]
    found = bad(result)
    assert found, result["checks"]
    assert not expected or expected & set(found), (expected, found)


def test_sharded_components_equal_one_device_components_as_a_partition():
    from cluster_tools_tpu.parallel.mesh import backend_devices, make_mesh
    from cluster_tools_tpu.parallel.pipeline import make_ws_ccl_step

    devices = backend_devices("local")
    shape = (HALO * len(devices), 64, 64)
    vol = data.membrane_volume(SEED, 0, shape, 2 * len(devices))
    got = {}
    for n in (len(devices), 1):
        mesh = make_mesh(axis_names=("dp", "sp"), grid=(1, n), devices=devices[:n])
        step = make_ws_ccl_step(mesh, halo=HALO, threshold=0.5, sp_axis="sp",
                                dt_max_distance=float(HALO), impl="auto")
        _, cc, _, overflow, _ = step(vol[None])
        assert not bool(overflow)
        got[n] = np.asarray(cc[0])
    many, one = got[len(devices)], got[1]
    assert np.array_equal(many > 0, one > 0)
    pairs = np.unique(np.stack([many.ravel(), one.ravel()]), axis=1)
    assert len(pairs[0]) == len(np.unique(many)) == len(np.unique(one))
    # and both are scipy's partition of the whole volume's foreground
    assert ref.compare_components(vol < np.float32(0.5), many) == {"cc_mismatch_voxels": 0}


# -- the comparison's own cuts ------------------------------------------------


@pytest.fixture(scope="module")
def slabs_mod():
    return run.load_by_file("comparisons", "ws_labels_slabs")


@pytest.mark.parametrize("slabs", [1, 2, 4, 8])
def test_every_interior_cut_has_a_box_on_either_side(slabs_mod, slabs):
    shape, n_units = (384 * slabs, 384, 384), 2 * (slabs - 1) + 2
    boxes = slabs_mod.pick_boxes(shape, slabs, data.fold_seed(5, 3, 0), n_units)
    assert len(boxes) == len(set(boxes)) == n_units
    starts = {z for z, _, _ in boxes}
    for cut in range(384, shape[0], 384):
        assert cut in starts and cut - 128 in starts
    for z, y, x in boxes:                 # no box spans a cut
        assert z // 384 == (z + 127) // 384 and y % 128 == x % 128 == 0
    again = slabs_mod.pick_boxes(shape, slabs, data.fold_seed(5, 3, 0), n_units)
    assert again == boxes                 # from the seed alone


def test_a_box_sees_the_neighbours_boundary_map_and_never_its_labels(slabs_mod):
    vol = np.random.default_rng(0).random((64, 24, 24)).astype(np.float32) * 0.9
    ws = np.arange(1, vol.size + 1, dtype=np.uint64).reshape(vol.shape)
    halo, margin = 8, 9
    # second of four slabs: planes 16..31; its unit is planes 8..39
    height, labels, inner, corner = slabs_mod.unit_of_box(vol, ws, (16, 0, 0), 4, halo, margin)
    assert height.shape == labels.shape == (32, 24, 24) and corner == [8, 0, 0]
    assert inner == (slice(8, 24), slice(0, 24), slice(0, 24))
    assert np.array_equal(height, vol[8:40])           # real planes either side
    assert np.array_equal(labels[8:24], ws[16:32])
    assert not labels[:8].any() and not labels[24:].any()
    # first slab: 1.0 below the volume, the neighbour's planes above
    height, labels, inner, corner = slabs_mod.unit_of_box(vol, ws, (0, 0, 0), 4, halo, margin)
    assert corner == [-8, 0, 0]
    assert (height[:8] == 1.0).all() and np.array_equal(height[8:], vol[:24])
    assert np.array_equal(labels[8:24], ws[:16]) and not labels[24:].any()
    assert inner[0] == slice(8, 24)


def test_one_slab_is_the_one_chip_comparison(slabs_mod):
    """With ``chips`` 1 the unit is the volume padded with 1.0 at both ends
    of z, as ``ws_labels`` cuts it."""
    vol = np.random.default_rng(1).random((40, 40, 40)).astype(np.float32)
    ws = np.ones(vol.shape, np.uint64)
    height, labels, inner, _ = slabs_mod.unit_of_box(vol, ws, (0, 0, 0), 1, 16, 17)
    assert height.shape == (72, 40, 40) and inner[0] == slice(16, 56)
    assert (height[:16] == 1.0).all() and (height[56:] == 1.0).all()
    assert not labels[:16].any() and not labels[56:].any()


@pytest.fixture(scope="module")
def flooded_unit():
    """One 96^3 volume flooded by the step on one device (halo 16: the unit
    is the volume with 16 planes of 1.0 either end of z), and the unit's own
    seeds, taken over the whole unit at once."""
    from cluster_tools_tpu.parallel.mesh import backend_devices, make_mesh
    from cluster_tools_tpu.parallel.pipeline import make_ws_ccl_step

    vol = data.membrane_volume(SEED, 0, (96, 96, 96), 12)
    mesh = make_mesh(axis_names=("dp", "sp"), grid=(1, 1),
                     devices=backend_devices("local")[:1])
    step = make_ws_ccl_step(mesh, halo=HALO, threshold=0.5, sp_axis="sp",
                            dt_max_distance=float(HALO), impl="auto")
    ws = np.asarray(step(vol[None])[0][0]).astype(np.uint64)
    unit = np.pad(vol, ((HALO, HALO), (0, 0), (0, 0)), constant_values=np.float32(1.0))
    fg = unit < np.float32(0.5)
    seeds, _ = ref.seed_plateaus(fg, ref.windowed_edt_sq(fg, HALO))
    return vol, ws, seeds


@pytest.mark.parametrize("lo", [(0, 0, 0), (32, 64, 32)])
def test_a_box_is_flooded_from_the_units_own_seeds(slabs_mod, flooded_unit, lo):
    """The cut's own distance transform displaces maxima in its margin
    (what read ``ws_flood_mismatch`` = 1 on the chip); a cut wider by the
    window gives the unit's seeds.  Handed the cut's own seeds,
    ``compare_cut`` counts what the reference's unit comparison counts."""
    vol, ws, of_unit = flooded_unit
    height, labels, inner, corner = slabs_mod.unit_of_box(vol, ws, lo, 1, HALO, HALO + 1,
                                                          box=32)
    seeds, n_seeds = slabs_mod.unit_seeds(vol, lo, 1, HALO, 0.5, HALO, corner,
                                          height.shape)
    crop = tuple(slice(c + pad, c + pad + n)
                 for c, pad, n in zip(corner, (HALO, 0, 0), height.shape))
    assert np.array_equal(seeds > 0, of_unit[crop] > 0)
    fg = height < np.float32(0.5)
    own, n_own = ref.seed_plateaus(fg, ref.windowed_edt_sq(fg, HALO))
    assert not np.array_equal(own > 0, of_unit[crop] > 0)   # the margin's are not the unit's
    theirs = ref.compare_watershed_unit(height, labels, threshold=0.5, radius=HALO,
                                        inner=inner, stored_only_inner=False)
    theirs.pop("ws_unlabelled_fg")
    assert slabs_mod.compare_cut(height, labels, own, n_own, inner) == theirs
    assert slabs_mod.compare_cut(height, labels, seeds, n_seeds, inner) == {
        "ws_seed_mismatch": 0, "ws_descent_mismatch": 0, "ws_flood_mismatch": 0}


def test_compare_cut_counts_a_fragment_of_the_flood_with_two_labels(slabs_mod, flooded_unit):
    vol, ws, _ = flooded_unit
    lo = (32, 32, 32)
    height, labels, inner, corner = slabs_mod.unit_of_box(vol, ws, lo, 1, HALO, HALO + 1,
                                                          box=32)
    seeds, n_seeds = slabs_mod.unit_seeds(vol, lo, 1, HALO, 0.5, HALO, corner,
                                          height.shape)
    torn = labels.copy()
    inside = torn[inner]                        # a view: edits land in torn
    found, sizes = np.unique(inside[inside > 0], return_counts=True)
    upper_half = (inside == found[sizes.argmax()]) & (np.arange(32)[:, None, None] >= 16)
    inside[upper_half] += np.uint64(1 << 40)    # the largest fragment, two labels
    got = slabs_mod.compare_cut(height, torn, seeds, n_seeds, inner)
    assert got["ws_flood_mismatch"] + got["ws_descent_mismatch"] + got["ws_seed_mismatch"] > 0


# -- what the program reckons and records -------------------------------------


def test_collective_bytes_of_the_cell_and_of_one_chip():
    got = collective_bytes((1536, 384, 384), (4,), 32)
    assert got == {"cuts": 3, "halo_bytes": 3 * 2 * 32 * 384 * 384 * 4,
                   "gathered_pair_bytes": 4 * (384 * 384 // 8) * 8}
    assert collective_bytes((384, 384, 384), (1,), 32) == {
        "cuts": 0, "halo_bytes": 0, "gathered_pair_bytes": 0}
    # a 2 x 2 grid: two cuts along each axis; y forwards the z halos it received
    grid = collective_bytes((128, 128, 64), (2, 2), 8)
    assert grid["cuts"] == 4
    assert grid["halo_bytes"] == 4 * (2 * 2 * 8 * 64 * 64 + 2 * 2 * 8 * (64 + 16) * 64)


def test_the_task_manifest_carries_the_collectives(tmp_path):
    from cluster_tools_tpu.runtime.task import build
    from cluster_tools_tpu.tasks.fused import FusedSegmentationLocal
    from cluster_tools_tpu.utils.volume_utils import file_reader

    tmp = str(tmp_path / "tmp")
    os.makedirs(tmp)
    with open(os.path.join(tmp, "global.config"), "w") as f:
        json.dump({}, f)
    path = str(tmp_path / "v.zarr")
    shape = (4 * n_slabs(), 32, 32)
    vol = np.random.default_rng(7).random(shape).astype(np.float32)
    file_reader(path).create_dataset("b", shape=shape, chunks=(16, 16, 16),
                                     dtype="float32")[...] = vol
    task = FusedSegmentationLocal(
        tmp_folder=tmp, config_dir=tmp, max_jobs=1, input_path=path, input_key="b",
        output_path=path, ws_key="ws", cc_key="cc", threshold=0.5, halo=4,
        block_shape=[16, 16, 16])
    assert build([task])
    doc = task.output().read()
    assert doc["mesh"] == f"sp={n_slabs()}"
    assert doc["collectives"] == collective_bytes(shape, (n_slabs(),), 4)
    assert doc["collectives"]["cuts"] == n_slabs() - 1


# -- the readers that look at every chip ---------------------------------------


def test_readers_return_nothing_on_a_one_chip_trace():
    from benchmark import chips_trace, reduce_trace

    small = os.path.join(run.HERE, "testdata", "small.xplane.pb")
    red = reduce_trace.reduce_file(small)
    chips = chips_trace._chips(small, tuple(red.window), reduce_trace.main_module(red))
    assert list(chips) == [0] and chips[0]["main_runs"] == 2
    assert abs(chips[0]["busy_s"] - red.busy_s) < 1e-12
    assert chips[0]["collective_s"] == 0
    # no job record, so no trace file: every new reader returns nothing
    traced = dict(trace=red, job={}, runtime_spans=[])
    for name in ("collective_device_s", "chip_skew_share", "halo_device_s",
                 "merge_device_s", "h2d_s"):
        meta = run.load_json(run.HERE, "metrics", name + ".json")
        assert run.load_reader(name).read(traced, meta) is None, name


def test_skew_and_collective_seconds_from_per_chip_rows(monkeypatch):
    from benchmark import chips_trace, reduce_trace

    red = reduce_trace.Reduced(window=(0.0, 10.0), busy_s=5.0, n_chips=4,
                               n_device_events=0)
    rows = {i: dict(busy_s=b, collective_s=c, main_runs=1)
            for i, (b, c) in enumerate([(4.0, 1.0), (6.0, 0.2), (5.0, 0.6), (5.0, 0.2)])}
    monkeypatch.setattr(chips_trace, "per_chip", lambda traced: rows)
    traced = dict(trace=red)
    assert run.load_reader("chip_skew_share").read(traced, {}) == pytest.approx(20.0)
    assert run.load_reader("collective_device_s").read(traced, {}) == pytest.approx(0.5)
    monkeypatch.setattr(chips_trace, "per_chip", lambda traced: {0: rows[0]})
    assert run.load_reader("chip_skew_share").read(traced, {}) is None


def test_which_operations_count_as_collectives():
    from benchmark import chips_trace

    for opcode, moves in [
            ("collective-permute-start", True), ("collective-permute-done", True),
            ("all-gather", True), ("all-reduce-start", True), ("fusion", False),
            ("copy-start", False), ("reduce", False)]:
        assert chips_trace.is_collective(opcode) is moves, opcode
