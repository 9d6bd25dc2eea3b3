"""The comparison ``unet_pred`` is shown to fail (pytest file).

    python -m pytest benchmark/test_correct_inference.py -q        (CPU, ~2 minutes)

Not part of tier-1 (``tests/test_unet3d_gcr.py`` holds the sound job
there).  As ``test_correct.py``: each test skips the harness's look for a
chip and drives the rest of a run (``run.run_cell``) of the cell
``unet125.volumes`` at a size the CPU holds: a stack of 29 x 40 x 72 at
10:1:1, blocks of 8 x 16 x 32 with halo (2, 8, 8), the network at
``f_maps`` 8 and 3 levels with pools (1, 2, 2), every block compared.

* sound: the cell comes out ``correct``, every count 0.
* control: every conv operand rounded to float8 (e4m3) before the
  network's own bfloat16: the nearest precision below the configuration's.
  On the chip at the cell's own size: PERF.md section 2.
* faults, each with the count that has to catch it: a block stored one
  voxel shifted, every block computed from its neighbour's window, a
  block never stored, and a forward that drops every GroupNorm's scale, or
  every GroupNorm's bias, or the head's bias (the checkpoint job's drawn
  weights catch these three; the task's own random weights cannot).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.test_correct import bad

CELL = "unet125.volumes"


def shrink(spec):
    config, cell = spec["config"], spec["cell"]
    small = {"volume_shape": [29, 40, 72], "block_shape": [8, 16, 32], "cells": 12}
    config["data"] = dict(small)
    cell["traffic"].update(small)
    config["store"]["chunks"] = [8, 16, 32]
    config["global_config"] = {"block_shape": [8, 16, 32]}
    config["params"].update(block_shape=[8, 16, 32], halo=[2, 8, 8])
    config["params"]["model"].update(f_maps=8, num_levels=3)
    cell["check_units"] = 36


def drive(seed=2147493711):
    return run.run_cell(CELL, seed, seconds=0.1, trace=False, require_chip=False,
                        shrink=shrink)


def float8_operands(monkeypatch):
    """Every conv operand of the network rounded to float8 (e4m3), then
    taken on as the network's bfloat16."""
    from cluster_tools_tpu.models import unet_gcr

    monkeypatch.setattr(unet_gcr, "_operand",
                        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16))


def patch_map_writes(monkeypatch, alter):
    """``alter(value)`` on every block of the map the program stores (the
    4-D float32 writes); a None from it drops the write."""
    from cluster_tools_tpu.io import containers

    inner = containers.Dataset.__setitem__

    def write(self, bb, value):
        value = np.asarray(value)
        if value.dtype == np.float32 and value.ndim == 4:
            value = alter(value.copy())
            if value is None:
                return None
        return inner(self, bb, value)

    monkeypatch.setattr(containers.Dataset, "__setitem__", write)


def test_sound_run_is_correct():
    result = drive()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["checks"]) >= {
        "pred_missing", "pred_nonfinite", "pred_out_of_range", "pred_logit_off"}
    assert all(c["value"] == 0 for c in result["checks"].values()), result["checks"]


def test_control_float8_operands_is_not_correct(monkeypatch):
    float8_operands(monkeypatch)
    result = drive()
    assert not result["correct"]
    assert set(bad(result)) == {"pred_logit_off", "ckpt_logit_off"}, result["checks"]


def test_fault_a_block_stored_one_voxel_shifted(monkeypatch):
    patch_map_writes(monkeypatch, lambda v: np.roll(v, 1, axis=2))
    result = drive()
    assert not result["correct"]
    assert bad(result).get("pred_logit_off", 0) > 0, result["checks"]


def test_fault_blocks_computed_from_their_neighbours_window(monkeypatch):
    from cluster_tools_tpu.io import containers

    inner = containers.Dataset.__getitem__

    def neighbour(self, bb):
        # the raw window of the block beside it along x, same extent
        if len(self.shape) == 3 and isinstance(bb, tuple) and len(bb) == 3:
            a, b = bb[2].start or 0, bb[2].stop
            step = 32 if b + 32 <= self.shape[2] else -32
            bb = bb[:2] + (slice(a + step, b + step),)
        return inner(self, bb)

    monkeypatch.setattr(containers.Dataset, "__getitem__", neighbour)
    result = drive()
    assert not result["correct"]
    assert bad(result).get("pred_logit_off", 0) > 0, result["checks"]


def test_fault_a_block_never_stored(monkeypatch):
    dropped = []

    def drop_first(value):
        if dropped:
            return value
        dropped.append(value.shape)
        return None

    patch_map_writes(monkeypatch, drop_first)
    result = drive()
    assert dropped
    assert not result["correct"]
    assert bad(result).get("pred_missing", 0) > 0, result["checks"]


@pytest.mark.parametrize("leaf,value", [("groupnorm/scale", 1.0), ("groupnorm/bias", 0.0),
                                        ("final_conv/bias", 0.0)])
def test_fault_a_forward_that_drops_an_affine_or_the_bias(monkeypatch, leaf, value):
    """What the program computes from a checkpoint whose ``leaf``s it
    ignores: as if each were at its trivial value."""
    import flax.traverse_util as tu

    from cluster_tools_tpu.tasks import inference

    inner = inference.load_checkpoint

    def ignoring(*a, **kw):
        flat = tu.flatten_dict(inner(*a, **kw))
        return tu.unflatten_dict({k: np.full_like(v, value) if "/".join(k).endswith(leaf)
                                  else v for k, v in flat.items()})

    monkeypatch.setattr(inference, "load_checkpoint", ignoring)
    result = drive()
    assert not result["correct"]
    assert set(bad(result)) == {"ckpt_logit_off"}, result["checks"]


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
