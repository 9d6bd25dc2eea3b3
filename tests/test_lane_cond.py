"""``ops/tile_ccl.py::lane_cond``: a tiled kernel's ``lax.cond`` under the
blockwise executor's ``vmap`` runs the side its lanes ask for, and only
that side where they agree.

The kernels run on the CPU with the Mosaic kernels interpreted, so that
every cond of the chip's program is there: the remap tables' tier and their
volume-sized fallback (the seed CCL's and the foreground CCL's, the exits',
the capacity fill's), the merge's and the strips' capacity tiers.  Three
batches a kernel: every lane's tables under ``table_cap``, every lane's over
a small one, and a batch whose lanes disagree.  Each lane's labels and its
own counts are those of the lane run alone; ``branch.*`` says what the
device ran for the batch.  Unbatched, the program is a plain ``lax.cond``'s.
"""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark import data
from cluster_tools_tpu.ops import tile_ccl, tile_ws, work

SHAPE = (8, 32, 128)        # two tiles of (8, 16, 128)
LANES = ((1, 4), (2, 10), (3, 24))   # (seed, cells): tables of three sizes

#: a fallback cond's predicate and the count it reads against table_cap
FALLBACKS = {
    work.FLOW_REMAP_FALLBACK: work.FLOW_REMAP_TILE_MAX,
    work.FILL_REMAP_FALLBACK: work.FILL_REMAP_TILE_MAX,
    work.CCL_REMAP_FALLBACK: work.CCL_REMAP_TILE_MAX,
    work.SEEDS_REMAP_FALLBACK: work.SEEDS_REMAP_TILE_MAX,
}

#: kernel -> (the fallback its batches are sized by, the kernel at a cap)
KERNELS = {
    "ccl": (work.CCL_REMAP_FALLBACK, lambda cap: lambda v: (
        tile_ccl.label_components_tiled(
            v < 0.5, impl="pallas", interpret=True, table_cap=cap))),
    "ws3d": (work.FLOW_REMAP_FALLBACK, lambda cap: partial(
        tile_ws.dt_watershed_tiled, threshold=0.5, dt_max_distance=8.0,
        impl="pallas", interpret=True, fill_mode="capacity",
        axes=tile_ws.VOLUME, table_cap=cap)),
    "ws2d": (work.FLOW_REMAP_FALLBACK, lambda cap: partial(
        tile_ws.dt_watershed_tiled, threshold=0.5, dt_max_distance=8.0,
        impl="pallas", interpret=True, fill_mode="dense",
        axes=tile_ws.PLANES, table_cap=cap)),
}


@pytest.fixture(scope="module")
def lanes():
    return np.stack([data.membrane_volume(seed, 0, SHAPE, cells)
                     for seed, cells in LANES])


@pytest.fixture(scope="module")
def tile_max(lanes):
    """kernel -> each lane's fullest tile of the cond the batches are sized
    by (a count the table's size does not change)."""
    out = {}
    for name, (fallback, make) in KERNELS.items():
        kernel = make(64)
        out[name] = [work.unpack(kernel(jnp.asarray(v))[2])[0][FALLBACKS[fallback]]
                     for v in lanes]
        assert len(set(out[name])) == len(LANES) and min(out[name]) > 1, out
    return out


def _cap(batch, counts):
    """The table_cap at which every lane fits, none does, or the two
    emptiest lanes fit and the fullest does not."""
    return {"under": max(counts), "over": 1,
            "mixed": sorted(counts)[1]}[batch]


@pytest.mark.parametrize("batch", ["under", "over", "mixed"])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_each_lane_is_the_lane_run_alone_and_the_branches_are_the_batchs(
        lanes, tile_max, name, batch):
    fallback, make = KERNELS[name]
    cap = _cap(batch, tile_max[name])
    kernel = make(cap)
    alone = [kernel(jnp.asarray(v)) for v in lanes]
    labels, overflow, records = jax.jit(jax.vmap(kernel))(jnp.asarray(lanes))
    rows = work.unpack(records)
    for i, (lab, ovf, rec) in enumerate(alone):
        np.testing.assert_array_equal(np.asarray(labels[i]), np.asarray(lab))
        assert not bool(ovf) and not bool(overflow[i])
        own = work.unpack(rec)[0]
        # unbatched: what the lane asked for is what ran, one side a cond
        asked = [own[n] for n in FALLBACKS if own[n] != work.UNSET]
        assert own[work.BRANCH_FALLBACKS_RUN] == sum(asked)
        assert own[work.BRANCH_BOTH_SIDES] == 0
        for n in work.NAMES:
            if n not in work.BRANCH:
                assert rows[i][n] == own[n], (i, n)
    preds = {n: [work.unpack(r)[0][n] for _, _, r in alone] for n in FALLBACKS}
    preds = {n: p for n, p in preds.items() if work.UNSET not in p}
    assert preds[fallback] == [int(t > cap) for t in tile_max[name]]
    assert set(preds[fallback]) == {"under": {0}, "over": {1}, "mixed": {0, 1}}[batch]
    # batched: a fallback ran where any lane asked for it, both sides of a
    # cond only where the lanes disagree (the tiers agree at this size)
    ran = sum(any(p) for p in preds.values())
    both = sum(any(p) and not all(p) for p in preds.values())
    for row in rows:
        assert row[work.BRANCH_FALLBACKS_RUN] == ran
        assert row[work.BRANCH_BOTH_SIDES] == both
    assert work.total(rows)[work.BRANCH_FALLBACKS_RUN] == ran


def _plain_cond(pred, true_fn, false_fn, operands):
    return lax.cond(pred, true_fn, false_fn, operands), (pred, ~pred)


def _compiled(lowered):
    """XLA's program for the CPU, without what numbers or places it: the
    instructions' ids and source metadata, how often a computation was
    cloned."""
    text = lowered.compile().as_text()
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(.+\n)*", "\n", text)
    return re.sub(r"(\.clone)+\b", "", re.sub(r"[._]\d+\b", "", text))


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_alone_the_program_is_a_plain_conds(lanes, monkeypatch, name):
    """Unbatched, ``lane_cond`` lowers to its ``lax.cond``: no custom-vmap
    call is left, one ``case`` a cond, and XLA compiles the program that the
    plain conds give.  (The StableHLO text itself differs: the custom-vmap
    call is lowered as it was traced, so it keeps what a side computes and
    does not return, and outlines helper functions of its own; XLA removes
    both.)"""
    _, make = KERNELS[name]

    def lowered():
        jax.clear_caches()
        return jax.jit(make(64)).lower(jnp.asarray(lanes[0]))

    ours = lowered()
    text = ours.as_text()
    assert "custom_vmap" not in text
    monkeypatch.setattr(tile_ccl, "lane_cond", _plain_cond)
    monkeypatch.setattr(tile_ws, "lane_cond", _plain_cond)
    plain = lowered()
    assert text.count("stablehlo.case") == plain.as_text().count("stablehlo.case") > 0
    assert _compiled(ours) == _compiled(plain)


def test_the_branches_are_taken_per_device_under_the_executors_shard_map(
        lanes, tile_max):
    """Four lanes on two devices: each device's two lanes agree, so no
    device runs both sides, and only the second runs the fallback."""
    from cluster_tools_tpu.parallel.batch_shard import batched_shard_map
    from cluster_tools_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(axis_names=("blocks",), grid=(2,), devices=jax.devices()[:2])
    low, high = np.argsort(tile_max["ccl"])[[0, -1]]
    four = lanes[[low, low, high, high]]
    kernel = KERNELS["ccl"][1](tile_max["ccl"][low])
    _, _, records = batched_shard_map(kernel, mesh, 4, axis_name="blocks")(
        jnp.asarray(four))
    rows = work.unpack(records)
    assert [r[work.CCL_REMAP_FALLBACK] for r in rows] == [0, 0, 1, 1]
    assert [r[work.BRANCH_FALLBACKS_RUN] for r in rows] == [0, 0, 1, 1]
    assert [r[work.BRANCH_BOTH_SIDES] for r in rows] == [0, 0, 0, 0]


@pytest.mark.parametrize("batched_pred", [False, True])
def test_a_predicate_the_lanes_share_is_one_cond_over_the_lanes(batched_pred):
    """Under ``vmap`` with the predicate unbatched the cond stays one cond
    over the vmapped sides; batched, the sides the lanes ask for."""
    def kernel(p, x):
        out, ran = tile_ccl.lane_cond(
            p, lambda x: x * 2, lambda x: x - 1, x)
        return out, jnp.stack(ran)

    x = jnp.arange(6.0).reshape(3, 2)
    if batched_pred:
        p = jnp.array([True, False, True])
        out, ran = jax.vmap(kernel)(p, x)
        want_ran = [[True, True]] * 3
    else:
        p = jnp.array(True)
        out, ran = jax.vmap(kernel, in_axes=(None, 0))(p, x)
        want_ran = [[True, False]] * 3
    want = np.where(np.asarray(jnp.broadcast_to(p, (3,)))[:, None],
                    np.asarray(x) * 2, np.asarray(x) - 1)
    np.testing.assert_array_equal(np.asarray(out), want)
    np.testing.assert_array_equal(np.asarray(ran), want_ran)


def test_branch_counts_add_up_across_parts_and_programs():
    """``join`` (parts of one program) and ``merge`` (programs run one
    after another) add the ``branch.*`` counts; lanes take the largest."""
    one = {work.BRANCH_FALLBACKS_RUN: 1, work.BRANCH_BOTH_SIDES: 0}
    joined = work.join(one, {work.BRANCH_FALLBACKS_RUN: 1,
                             work.BRANCH_BOTH_SIDES: 1})
    assert joined == {work.BRANCH_FALLBACKS_RUN: 2, work.BRANCH_BOTH_SIDES: 1}
    seeds = work.as_seed_ccl(work.pack(one))
    merged = work.unpack(work.merge(work.pack(joined), seeds,
                                    work.pack({work.CCL_PAIRS: 3})))[0]
    assert merged[work.BRANCH_FALLBACKS_RUN] == 3
    assert merged[work.BRANCH_BOTH_SIDES] == 1 and merged[work.CCL_PAIRS] == 3
    lanes = work.total([merged, merged])
    assert lanes[work.BRANCH_FALLBACKS_RUN] == 3 and lanes[work.CCL_PAIRS] == 6
