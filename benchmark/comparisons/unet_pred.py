"""The comparison ``unet_pred``: the boundary maps that whole inference jobs
stored, against the plain reference (``benchmark/reference_unet.py``).

Whole stack, every completed job (the stored ``(1, z, y, x)`` float32 map,
read back from its files): ``pred_missing`` (voxels of chunks never written;
every voxel where the map cannot be read at all), ``pred_nonfinite`` (NaN or
inf among the written voxels) and ``pred_out_of_range`` (finite values
outside [0, 1]).

On ``check_units`` whole blocks a job, drawn from the seed, each as the
kernel saw it (the outer block clipped by the stack, normalised by the
configuration's ``normalize_range``, padded at the far side with 0 to the
outer shape; the weights the job recorded): ``pred_logit_off``, the inner
voxels whose stored value, taken back to a logit (``log p - log1p(-p)`` in
float64), lies further than ``LOGIT_TOL`` from the reference's float32
logit.  Where the reference's logit lies beyond +-``LOGIT_SATURATED`` the
stored probability is compared instead, within ``PROB_TOL``.  The largest
deviation of each unit goes to standard error as ``[unet_pred]`` lines.

The timed jobs run on the task's own random weights, whose GroupNorm
affines are the identity and whose head has no bias.  So after the window
one more whole job runs, on the first job's stack, with every leaf drawn
from the seed (``reference_unet.draw_weights``: no affine and no bias
trivial) and handed to the task as its ``checkpoint_path``, the route of a
trained network: ``ckpt_job_failed`` (1 where it did not complete) and
``ckpt_logit_off``, ``pred_logit_off`` on ``CKPT_UNITS`` of its blocks
against the reference on the drawn weights; its map's missing, non-finite
and out-of-range voxels count with the timed jobs'.

Every number is a count, and every limit is 0.
"""

from __future__ import annotations

import contextlib
import gc
import math
import os
import sys
from typing import Dict, List

import numpy as np

from benchmark import reference as ref
from benchmark import reference_two_pass as grid
from benchmark import reference_unet as unet
from benchmark.data import fold_seed

LIMITS = {
    "pred_missing": 0,
    "pred_nonfinite": 0,
    "pred_out_of_range": 0,
    "pred_logit_off": 0,
    "ckpt_job_failed": 0,
    "ckpt_logit_off": 0,
}
#: blocks of the checkpoint job compared with the reference: what it adds to
#: the timed jobs' units is the weights, which every block reads alike
CKPT_UNITS = 1

#: |stored logit - reference logit| allowed.  The program rounds every conv
#: operand to bfloat16 (8 bits of mantissa) and accumulates in float32; the
#: reference is float32 at the highest precision.  Over the 17 convs at the
#: published widths that rounding moves a logit by 0.009 at the median and
#: at most 0.11 on the CPU (windows of 8 x 64 x 128 and 16 x 128 x 128);
#: PERF.md section 2 has the chip's readings.  Float8 operands (e4m3, 4
#: bits), the nearest coarser precision, move it by 0.12 at the median and
#: by more than 0.75 at one voxel in a hundred, so a run in that precision
#: fails by thousands of voxels a unit
LOGIT_TOL = 0.5
#: beyond this the float32 probability cannot carry the logit back: at 12,
#: 1 - p is 6.1e-6, held to 2^-24 / 6.1e-6 = 1 % of itself
LOGIT_SATURATED = 12.0
#: the probability's allowance there: what LOGIT_TOL is worth in p at the
#: saturation point (sigmoid' (12) = 6.1e-6)
PROB_TOL = LOGIT_TOL * (1.0 / (1.0 + math.exp(-LOGIT_SATURATED))) * (
    1.0 / (1.0 + math.exp(LOGIT_SATURATED)))


def logits_off(stored: np.ndarray, reference: np.ndarray) -> Dict[str, float]:
    """``stored`` probabilities against ``reference`` logits, same shape:
    the count of voxels off, and the largest deviation of the logit where it
    is compared."""
    p = stored.astype(np.float64)
    r = reference.astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        dev = np.abs(np.log(p) - np.log1p(-p) - r)
    saturated = np.abs(r) > LOGIT_SATURATED
    off = np.where(saturated, np.abs(p - 1.0 / (1.0 + np.exp(-r))) > PROB_TOL,
                   ~(dev <= LOGIT_TOL))   # NaN is off
    shown = dev[~saturated & np.isfinite(dev)]
    return {"off": int(np.count_nonzero(off)),
            "max": float(np.nanmax(shown)) if shown.size else 0.0,
            "p999": float(np.nanquantile(shown, 0.999)) if shown.size else 0.0,
            "saturated": int(np.count_nonzero(saturated))}


def check_job(vol: np.ndarray, pred: np.ndarray, missing: np.ndarray, weights, cfg: dict,
              rng, n_units: int, label: str) -> Dict[str, int]:
    p = cfg["params"]
    model = p["model"]
    block, halo = list(p["block_shape"]), list(p["halo"])
    mult = np.prod(np.array(unet.pool_factors(model)), axis=0)
    outer = [-(-(b + 2 * h) // int(m)) * int(m) for b, h, m in zip(block, halo, mult)]
    written = ~missing
    finite = np.isfinite(pred)
    out = {
        "pred_missing": int(np.count_nonzero(missing)),
        "pred_nonfinite": int(np.count_nonzero(written & ~finite)),
        "pred_out_of_range": int(np.count_nonzero(
            written & finite & ((pred < 0) | (pred > 1)))),
        "pred_logit_off": 0,
    }
    blocks = grid.blocks_of(vol.shape, block)
    picked = rng.choice(len(blocks), min(n_units, len(blocks)), replace=False)
    for i in sorted(int(i) for i in picked):
        number, pos = blocks[i]
        lo, hi, olo, ohi = grid.unit_bounds(pos, vol.shape, block, halo)
        x = unet.unit_input(vol, olo, ohi, outer, p["normalize_range"])
        logits = unet.forward_logits(x, weights, model)
        inner = (slice(None),) + tuple(slice(a - o, b - o) for a, b, o in zip(lo, hi, olo))
        got = logits_off(pred[(slice(None),) + tuple(slice(a, b) for a, b in zip(lo, hi))],
                         logits[inner])
        out["pred_logit_off"] += got["off"]
        print(f"[unet_pred] {label} block {number}: off {got['off']}, largest |logit - "
              f"reference| {got['max']:.6f}, 99.9th percentile {got['p999']:.6f}, "
              f"saturated {got['saturated']}", file=sys.stderr, flush=True)
    return out


@contextlib.contextmanager
def not_written_to_the_cache():
    """Programs compiled inside are not written to JAX's persistent cache.
    The task's sweep program holds its weights as constants, so the
    checkpoint job's program is the seed's own: compiled in every run, and
    an entry a seed (the weights and more) would crowd out the cell's own."""
    import jax

    before = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", float("inf"))
    try:
        yield
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


def run_checkpoint_job(cell: dict, cfg: dict, rec: dict, seed: int):
    """One more whole job on ``rec``'s stack, built as the harness builds
    the timed ones, with ``checkpoint_path`` set to weights drawn from the
    seed (written beside the output store).  Returns its record and the
    weights."""
    import jax

    from benchmark import run

    work = os.path.dirname(rec["outputs"]["pred"][0])
    path = os.path.join(work, "drawn.weights.npz")
    weights = unet.draw_weights(cfg["params"]["model"], fold_seed(seed, 4))
    unet.write_weights(path, weights)
    config = dict(cfg, params=dict(cfg["params"], checkpoint_path=path))
    target = "tpu" if jax.devices()[0].platform == "tpu" else "local"
    runner = run.Runner({"config": config, "cell": cell}, seed, target, work)
    with not_written_to_the_cache():
        done = runner.run(rec["job"], "checkpoint")
    print(f"[unet_pred] checkpoint job: {done['t1'] - done['t0']:.2f}s, completed "
          f"{done['completed']}", file=sys.stderr, flush=True)
    return done, weights


def check_record(rec: dict, weights, volumes, cfg: dict, rng, n_units: int,
                 label: str) -> Dict[str, int]:
    """One job's map, read back, against the reference on ``weights`` (the
    job's recorded ones where None)."""
    vol = volumes[rec["job"].volume]
    path, key = rec["outputs"]["pred"]
    try:
        pred, missing = unet.read_zarr_nd(path, key)
        if weights is None:
            weights = unet.recorded_weights(rec["tmp"])
    except (OSError, ValueError, KeyError):
        return {"pred_missing": int(np.prod(vol.shape))}
    return check_job(vol, pred, missing, weights, cfg, rng, n_units, label)


def check_jobs(cell: dict, cfg: dict, done: List[dict], volumes: Dict[int, np.ndarray],
               seed: int) -> Dict[str, int]:
    """``done``: the completed jobs, each ``{"job", "tmp", "outputs":
    {"pred": (path, key)}}``.  Returns the summed counts, one entry per key
    of LIMITS."""
    import jax

    totals: Dict[str, int] = {k: 0 for k in LIMITS}
    ckpt = drawn = None
    if done:
        ckpt, drawn = run_checkpoint_job(cell, cfg, done[0], seed)
        totals["ckpt_job_failed"] = int(not ckpt["completed"])
    # the programs of the jobs are done with: what their executables reserve
    # on the chip goes back before the reference's slabs need it
    jax.clear_caches()
    gc.collect()
    row = jax.devices()[0].memory_stats() or {}
    print(f"[unet_pred] the chip after the window's programs were dropped: bytes_in_use "
          f"{row.get('bytes_in_use')}, bytes_reserved {row.get('bytes_reserved')}",
          file=sys.stderr, flush=True)
    for rec in done:
        index = rec["job"].index
        ref.merge_counts(totals, check_record(
            rec, None, volumes, cfg, fold_seed(seed, 3, index), int(cell["check_units"]),
            f"job {index}"))
    if ckpt is not None and ckpt["completed"]:
        counts = check_record(ckpt, drawn, volumes, cfg, fold_seed(seed, 4, 1), CKPT_UNITS,
                              "checkpoint job")
        counts["ckpt_logit_off"] = counts.pop("pred_logit_off", 0)
        ref.merge_counts(totals, counts)
    return totals
