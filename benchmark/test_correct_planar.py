"""The comparison ``ws_planar`` is shown to fail (pytest file).

    python -m pytest benchmark/test_correct_planar.py -q        (CPU, ~1 minute)

Not part of tier-1 (``tests/test_planar_ws.py`` holds the sound job
there).  As ``test_correct.py``: each test skips the harness's look for a
chip and drives the rest of a run (``run.run_cell``) of the cell
``ws2d125.volumes`` at a size the CPU holds: a stack of 29 x 40 x 72 at
10:1:1, blocks of 8 x 16 x 32 with an in-plane halo of 16, every block
compared.

* sound: the cell comes out ``correct``.
* control: the input rounded to bfloat16 before the program reads it.  On
  the chip at the cell's own size: PERF.md section 2.
* faults, each with the count that has to catch it: a label copied into a
  second plane where it is stored, labels smeared through a membrane where
  they are stored, and a seed the program drops (the seed plateau of least
  flat index in every block, taken out of the seeds' CCL inside the
  program, which is then built anew).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import control, run
from benchmark.test_correct import bad

CELL = "ws2d125.volumes"


def shrink(spec):
    config, cell = spec["config"], spec["cell"]
    small = {"volume_shape": [29, 40, 72], "block_shape": [8, 16, 32], "cells": 12}
    config["data"] = dict(small)
    cell["traffic"].update(small)
    config["store"]["chunks"] = [8, 16, 32]
    config["global_config"] = {"block_shape": [8, 16, 32]}
    config["params"].update(block_shape=[8, 16, 32], halo=[0, 16, 16],
                            dt_max_distance=16.0)
    cell["check_units"] = 36


def drive(seed=2147493711):
    return run.run_cell(CELL, seed, seconds=0.1, trace=False, require_chip=False,
                        shrink=shrink)


def patch_label_writes(monkeypatch, alter):
    """``alter(block)`` on every uint64 block the program stores."""
    from cluster_tools_tpu.io import containers

    inner = containers.Dataset.__setitem__

    def write(self, bb, value):
        value = np.asarray(value)
        if value.dtype == np.uint64 and value.ndim == 3 and value.any():
            value = alter(value.copy())
        return inner(self, bb, value)

    monkeypatch.setattr(containers.Dataset, "__setitem__", write)


def test_sound_run_is_correct():
    result = drive()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["checks"]) >= {
        "ws_unlabelled_fg", "ws_fragments_across_components", "ws_label_in_two_planes",
        "ws_foreign_label_in_block", "ws_seed_mismatch", "ws_descent_mismatch",
        "ws_flood_mismatch"}


def test_control_bfloat16_input_is_not_correct():
    with control.bfloat16_reads():
        result = drive()
    assert not result["correct"]
    assert bad(result), result["checks"]


def test_fault_a_label_copied_into_a_second_plane(monkeypatch):
    def copied(block):
        if block.shape[0] > 1:
            values, counts = np.unique(block[1][block[1] > 0], return_counts=True)
            if len(values):
                block[1][block[1] == values[np.argmax(counts)]] = block[0].max()
        return block

    patch_label_writes(monkeypatch, copied)
    result = drive()
    assert not result["correct"]
    assert bad(result).get("ws_label_in_two_planes", 0) > 0, result["checks"]


def test_fault_a_fragment_smeared_across_a_membrane(monkeypatch):
    def smeared(block):
        if block.shape[2] >= 16:
            c = block.shape[2] // 2
            block[:, :, c: c + 8] = block[:, :, c - 1: c]   # across whatever lies there
        return block

    patch_label_writes(monkeypatch, smeared)
    result = drive()
    assert not result["correct"]
    assert bad(result).get("ws_fragments_across_components", 0) > 0, result["checks"]


def test_fault_a_dropped_seed(monkeypatch):
    from cluster_tools_tpu.ops import tile_ws
    from tests.helpers import programs_built_here

    inner = tile_ws.label_components_tiled

    def dropping(mask, **kw):
        raw, overflow, record = inner(mask, **kw)
        first = jnp.min(raw)   # the plateau of least flat index
        return jnp.where(raw == first, jnp.int32(mask.size), raw), overflow, record

    monkeypatch.setattr(tile_ws, "label_components_tiled", dropping)
    jax.clear_caches()
    try:
        with programs_built_here():
            result = drive()
    finally:
        jax.clear_caches()
    assert not result["correct"]
    assert bad(result).get("ws_seed_mismatch", 0) > 0, result["checks"]
