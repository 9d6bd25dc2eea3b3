"""The comparison ``ws_two_pass`` is shown to fail (pytest file).

    python -m pytest benchmark/test_correct_two_pass.py -q        (CPU, ~1 minute)

Not part of tier-1 (``tests/test_two_pass_aniso.py`` holds the sound job
there).  As ``test_correct.py``: each test skips the harness's look for a
chip and drives the rest of a run (``run.run_cell``) of the cell
``twopass125.volumes`` at a size the CPU holds: a stack of 29 x 40 x 72 at
10:1:1, blocks of 8 x 16 x 32 with a halo of (2, 16, 16), every block
compared.

* sound: the cell comes out ``correct``.
* control: the input rounded to bfloat16 before the program reads it.  On
  the chip at the cell's own size: PERF.md section 2.
* faults, planted under the timed path, each with the count that has to
  catch it: an external seed that pass two never saw, an external id
  rewritten on its way into pass two, and labels smeared through a membrane
  where they are stored.
"""

import numpy as np
import pytest

from benchmark import control, run
from benchmark.test_correct import bad

CELL = "twopass125.volumes"


def shrink(spec):
    config, cell = spec["config"], spec["cell"]
    small = {"volume_shape": [29, 40, 72], "block_shape": [8, 16, 32], "cells": 12}
    config["data"] = dict(small)
    cell["traffic"].update(small)
    config["store"]["chunks"] = [8, 16, 32]
    config["global_config"] = {"block_shape": [8, 16, 32]}
    config["params"].update(block_shape=[8, 16, 32], halo=[2, 16, 16],
                            dt_max_distance=16.0)
    cell["check_units"] = 36


def drive(seed=2147493711):
    return run.run_cell(CELL, seed, seconds=0.1, trace=False, require_chip=False,
                        shrink=shrink)


def most_common_label(labels):
    values, counts = np.unique(labels[labels > 0], return_counts=True)
    return values[np.argmax(counts)]


def patch_label_reads(monkeypatch, alter):
    """``alter(labels)`` on every uint64 block the program reads through the
    container doorway: what pass two reads of pass one's labels.  The
    comparison reads the store with a reader of its own."""
    from cluster_tools_tpu.io import containers

    inner = containers.Dataset.__getitem__

    def read(self, bb):
        out = inner(self, bb)
        if out.dtype == np.uint64 and out.any():
            out = alter(out.copy())
        return out

    monkeypatch.setattr(containers.Dataset, "__getitem__", read)


def test_sound_run_is_correct():
    result = drive()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["checks"]) >= {
        "ws_unlabelled_fg", "ws_fragments_across_components", "ws_foreign_label_in_block",
        "ws_seed_mismatch", "ws_descent_mismatch", "ws_flood_mismatch",
        "ws_ext_seed_mismatch"}


def test_control_bfloat16_input_is_not_correct():
    with control.bfloat16_reads():
        result = drive()
    assert not result["correct"]
    assert bad(result), result["checks"]


def test_fault_a_dropped_external_seed(monkeypatch):
    def drop(labels):
        labels[labels == most_common_label(labels)] = 0
        return labels

    patch_label_reads(monkeypatch, drop)
    result = drive()
    assert not result["correct"]
    assert bad(result).get("ws_ext_seed_mismatch", 0) > 0, result["checks"]


def test_fault_an_external_id_rewritten(monkeypatch):
    def rewrite(labels):
        labels[labels == most_common_label(labels)] += np.uint64(1)
        return labels

    patch_label_reads(monkeypatch, rewrite)
    result = drive()
    assert not result["correct"]
    found = bad(result)
    assert found.get("ws_foreign_label_in_block", 0) > 0, result["checks"]
    assert found.get("ws_ext_seed_mismatch", 0) > 0, result["checks"]


def test_fault_a_label_leaked_through_a_membrane(monkeypatch):
    from cluster_tools_tpu.io import containers

    inner = containers.Dataset.__setitem__

    def smeared(self, bb, value):
        value = np.asarray(value)
        if value.dtype == np.uint64 and value.ndim == 3 and value.shape[2] >= 16:
            value = value.copy()
            c = value.shape[2] // 2
            value[:, :, c: c + 8] = value[:, :, c - 1: c]   # across whatever lies there
        return inner(self, bb, value)

    monkeypatch.setattr(containers.Dataset, "__setitem__", smeared)
    result = drive()
    assert not result["correct"]
    assert bad(result).get("ws_fragments_across_components", 0) > 0, result["checks"]
