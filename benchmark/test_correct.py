"""The comparison that decides ``correct`` is shown to fail (pytest file).

    python -m pytest benchmark/test_correct.py -q        (CPU, ~2 minutes)

Not part of tier-1 (``tests/``).  Each test skips the harness's look for a
chip and drives the rest of a run (``run.run_cell``) at a size the CPU can
hold: the same workflows, traffic generator, store, reader and reference,
with 32^3 blocks and halo 16 in place of 64^3 and 32.

* sound: both cells come out ``correct``.
* control: the input rounded to bfloat16 before the program reads it (the
  nearest precision below the float32 that the configurations state, and
  the step that would tempt a later PR: half the bytes to read and to move
  to the device).  On the chip at the cells' own size: PERF.md section 2.
* faults, planted under the timed path: a job that stores nothing for half
  of its blocks (a step that leaves its state unchanged / half of the batch
  left out), and labels altered where they are produced.
"""

import json
import os

import numpy as np
import pytest

from benchmark import control, run

#: the benchmark's cell, and the blockwise ROI cell that is kept as test data
#: (``testdata/blockwise512.rois.json`` says why it is no cell today)
CELLS = ["fused384.volumes", "blockwise512.rois"]
TESTDATA = os.path.join(run.HERE, "testdata")


@pytest.fixture(autouse=True)
def cells_from_testdata(monkeypatch):
    """``run.load_cell`` also finds a cell kept under ``testdata/``."""
    from_manifest = run.load_cell

    def load_cell(name):
        path = os.path.join(TESTDATA, name + ".json")
        if not os.path.exists(path):
            return from_manifest(name)
        with open(path) as f:
            kept = json.load(f)
        bench = run.load_json(run.ROOT, "BENCHMARK.json")
        return dict(name=name, cell=kept["cell"], config=kept["config"], bench=bench,
                    end_to_end=bench["end_to_end"], per_layer=[])

    monkeypatch.setattr(run, "load_cell", load_cell)


def shrink(spec):
    config, cell = spec["config"], spec["cell"]
    config["data"] = {"volume_shape": [64, 64, 64], "block_shape": [32, 32, 32],
                      "cells": 8}
    config["store"]["chunks"] = [32, 32, 32]
    config["global_config"] = {"block_shape": [32, 32, 32]}
    p = config["params"]
    p["block_shape"] = [32, 32, 32]
    p["halo"] = [16, 16, 16] if isinstance(p["halo"], list) else 16
    p["dt_max_distance"] = 16.0
    if "device_batch" in p:
        p["device_batch"] = 4
    if cell["traffic"]["roi_blocks"]:
        cell["traffic"]["roi_blocks"] = [2, 2, 2]
    cell["check_units"] = 64


def drive(cell, seed=11):
    return run.run_cell(cell, seed, seconds=0.1, trace=False, require_chip=False,
                        shrink=shrink)


def bad(result):
    """The comparisons of labels that failed (a compile inside the window
    fails a run too, but shows nothing about the comparison)."""
    return {k: c["value"] for k, c in result["checks"].items()
            if c["value"] > c["limit"] and k != "compiles_in_window"}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result = drive(cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["metrics"]["voxels_per_s"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_bfloat16_input_is_not_correct(cell):
    with control.bfloat16_reads():
        result = drive(cell)
    assert not result["correct"]
    assert bad(result), result["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_fault_half_of_the_labels_never_stored(cell, monkeypatch):
    from cluster_tools_tpu.io import containers

    inner = containers.Dataset.__setitem__

    def half(self, bb, value):
        value = np.asarray(value)
        if value.dtype == np.uint64:
            value = value.copy()
            value[: value.shape[0] // 2] = 0   # the state it was created with
        return inner(self, bb, value)

    monkeypatch.setattr(containers.Dataset, "__setitem__", half)
    result = drive(cell)
    assert not result["correct"]
    assert bad(result).get("ws_unlabelled_fg", 0) > 0 or result["failed"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_fault_labels_altered_where_they_are_produced(cell, monkeypatch):
    from cluster_tools_tpu.io import containers

    inner = containers.Dataset.__setitem__

    def altered(self, bb, value):
        value = np.asarray(value)
        if value.dtype == np.uint64 and value.size > 4096:
            value = value.copy()
            c = tuple(s // 2 for s in value.shape)
            value[c[0] - 2: c[0] + 2, c[1] - 2: c[1] + 2, c[2] - 2: c[2] + 2] += 1
        return inner(self, bb, value)

    monkeypatch.setattr(containers.Dataset, "__setitem__", altered)
    result = drive(cell)
    assert not result["correct"]
    assert bad(result), result["checks"]


def test_reference_flood_fills_a_seedless_basin_over_its_lowest_saddle():
    """Three basins along x: seeds in the outer two, none in the middle one,
    whose pass to the right (0.4) is lower than the one to the left (0.6)."""
    from benchmark import reference as ref

    profile = np.array([0.1, 0.2, 0.6, 0.3, 0.25, 0.4, 0.2, 0.1], np.float32)
    height = np.broadcast_to(profile, (3, 3, 8)).copy()
    seeds = np.zeros(height.shape, np.int32)
    seeds[:, :, 0], seeds[:, :, 7] = 1, 2
    tree = ref.reference_flood(height, seeds, 2)
    assert tree[1, 1].tolist() == [1, 1, 1, 2, 2, 2, 2, 2]
    # as a cut whose distances are exact from x = 0 to 5: the middle basin
    # touches the faces and the right plateau lies outside, so both are undecided
    cut = ref.reference_flood(height, seeds, 2,
                              cut_inner=(slice(0, 3), slice(0, 3), slice(0, 6)))
    assert cut[1, 1].tolist() == [1, 1, 1, 0, 0, 0, 0, 0]


def test_fragments_across_components_counts_a_fragment_in_two():
    from benchmark import reference as ref

    fg = np.ones((1, 1, 7), bool)
    fg[0, 0, 3] = False
    comp = np.array([[[1, 1, 1, 0, 2, 2, 2]]])
    sound = np.array([[[5, 5, 6, 7, 7, 8, 8]]], np.uint64)
    assert ref.fragments_across_components(sound, fg, comp) == 0
    leaked = np.array([[[5, 5, 6, 6, 6, 8, 8]]], np.uint64)
    assert ref.fragments_across_components(leaked, fg, comp) == 1


def test_reference_flood_breaks_a_tie_of_saddles_by_the_face_that_comes_first():
    """A seedless basin between two passes of one height (as under one high
    voxel that borders two basins): the face first in (axis, position) joins."""
    from benchmark import reference as ref

    profile = np.array([0.1, 0.5, 0.3, 0.5, 0.1], np.float32)
    height = np.broadcast_to(profile, (3, 3, 5)).copy()
    seeds = np.zeros(height.shape, np.int32)
    seeds[:, :, 0], seeds[:, :, 4] = 1, 2
    assert ref.reference_flood(height, seeds, 2)[1, 1].tolist() == [1, 1, 1, 2, 2]
    mirrored = ref.reference_flood(height[:, :, ::-1].copy(), seeds[:, :, ::-1].copy(), 2)
    assert mirrored[1, 1].tolist() == [2, 2, 2, 1, 1]
