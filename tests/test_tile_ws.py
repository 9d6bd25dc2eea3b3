"""Two-level (tile + basin-graph) watershed vs the legacy kernel and oracles.

Covers the TPU fast path's portable half (XLA tile phase + exit chase +
saddle-union fill) and the Mosaic kernels in interpreter mode.  Descent
semantics must be bit-identical to ``ops.watershed.seeded_watershed`` when
every basin is seeded; unseeded-basin fill is minimum-spanning-forest
(lowest-saddle) order, checked by property tests (reference semantics:
SURVEY.md §2a "watershed" — vigra floods every voxel from the seed set).
"""

import numpy as np
import pytest
import scipy.ndimage as ndi

import jax
import jax.numpy as jnp

from cluster_tools_tpu.ops.watershed import local_maxima, seeded_watershed
from cluster_tools_tpu.ops.tile_ws import seeded_watershed_tiled


def test_all_minima_seeded_matches_legacy(rng):
    # fully seeded: no fill; must equal the legacy kernel bit for bit
    shape = (16, 16, 128)
    height = rng.permutation(np.prod(shape)).reshape(shape).astype(np.float32)
    minima = np.asarray(local_maxima(jnp.asarray(-height)))
    seeds = np.zeros(shape, np.int32)
    seeds[minima] = np.arange(1, minima.sum() + 1)
    legacy = np.asarray(seeded_watershed(jnp.asarray(height), jnp.asarray(seeds)))
    got, ovf, _ = seeded_watershed_tiled(
        jnp.asarray(height), jnp.asarray(seeds), impl="xla"
    )
    assert not bool(ovf)
    np.testing.assert_array_equal(np.asarray(got), legacy)


def test_all_voxels_labeled_sparse_seeds(rng):
    shape = (24, 24, 130)  # padding path too
    height = rng.random(shape).astype(np.float32)
    seeds = np.zeros(shape, np.int32)
    seeds[4, 4, 10] = 1
    seeds[20, 20, 100] = 2
    got, ovf, _ = seeded_watershed_tiled(
        jnp.asarray(height), jnp.asarray(seeds), impl="xla"
    )
    assert not bool(ovf)
    got = np.asarray(got)
    assert (got > 0).all()
    assert set(np.unique(got)) <= {1, 2}
    assert got[4, 4, 10] == 1 and got[20, 20, 100] == 2


def test_regions_connected(rng):
    shape = (20, 20, 128)
    height = rng.random(shape).astype(np.float32)
    seeds = np.zeros(shape, np.int32)
    seeds[2, 2, 10] = 1
    seeds[17, 17, 100] = 2
    seeds[2, 17, 60] = 3
    got, _, _ = seeded_watershed_tiled(
        jnp.asarray(height), jnp.asarray(seeds), impl="xla"
    )
    got = np.asarray(got)
    for l in (1, 2, 3):
        region = got == l
        if region.any():
            _, n = ndi.label(region, structure=ndi.generate_binary_structure(3, 1))
            assert n == 1, f"label {l} split into {n} pieces"


def test_respects_mask(rng):
    shape = (16, 16, 128)
    height = rng.random(shape).astype(np.float32)
    mask = np.ones(shape, bool)
    mask[:, :, 64] = False  # wall splits the volume
    seeds = np.zeros(shape, np.int32)
    seeds[8, 8, 10] = 1
    seeds[8, 8, 100] = 2
    got, _, _ = seeded_watershed_tiled(
        jnp.asarray(height), jnp.asarray(seeds), jnp.asarray(mask), impl="xla"
    )
    got = np.asarray(got)
    assert (got[~mask] == 0).all()
    assert (got[:, :, :64][mask[:, :, :64]] == 1).all()
    assert (got[:, :, 65:][mask[:, :, 65:]] == 2).all()


def test_unreachable_basin_stays_zero(rng):
    # an unseeded pocket enclosed by mask keeps label 0 (legacy behavior)
    shape = (16, 16, 128)
    height = rng.random(shape).astype(np.float32)
    mask = np.ones(shape, bool)
    mask[4:9, 4:9, 30] = False
    mask[4:9, 4:9, 40] = False
    mask[4:9, [4, 8], 31:40] = False
    mask[[4, 8], 4:9, 31:40] = False
    seeds = np.zeros(shape, np.int32)
    seeds[1, 1, 1] = 1
    got, _, _ = seeded_watershed_tiled(
        jnp.asarray(height), jnp.asarray(seeds), jnp.asarray(mask), impl="xla"
    )
    got = np.asarray(got)
    pocket = np.zeros(shape, bool)
    pocket[5:8, 5:8, 31:40] = True
    assert (got[pocket & mask] == 0).all()
    # everything connected to the seed is labeled 1
    outside = mask.copy()
    outside[3:10, 3:10, 29:41] = False
    assert (got[outside] == 1).all()


def test_pallas_interpret_matches_xla(rng):
    shape = (16, 32, 128)
    height = rng.random(shape).astype(np.float32)
    seeds = np.zeros(shape, np.int32)
    pts = rng.integers(0, [16, 32, 128], size=(5, 3))
    for i, p in enumerate(pts):
        seeds[tuple(p)] = i + 1
    a, ovf_a, _ = seeded_watershed_tiled(
        jnp.asarray(height), jnp.asarray(seeds), impl="xla"
    )
    b, ovf_b, _ = seeded_watershed_tiled(
        jnp.asarray(height), jnp.asarray(seeds), impl="pallas", interpret=True
    )
    assert not bool(ovf_a) and not bool(ovf_b)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_overflow_flag(rng, monkeypatch):
    # pin the capacity fill: fill_cap only exists there (the dense
    # default has no candidate caps — exit_cap alone would still trip,
    # but this test exists to cover the FILL capacity class)
    monkeypatch.setenv("CT_FILL_MODE", "capacity")
    jax.clear_caches()
    height = rng.random((32, 32, 128)).astype(np.float32)
    seeds = np.zeros((32, 32, 128), np.int32)
    seeds[0, 0, 0] = 1
    _, ovf, _ = seeded_watershed_tiled(
        jnp.asarray(height), jnp.asarray(seeds), impl="xla",
        exit_cap=8, fill_cap=8,
    )
    assert bool(ovf)
    jax.clear_caches()


# The chase walks a compacted, shrinking list of live chains in chunks of
# cap / 16 slots (one path for every buffer size), so the cases below pick
# the live count against the chunk (cap 1000 -> chunk 63, 16 chunks = 1008
# slots: the lists' padding is exercised too) and the chains against the hops.

_CHASE_CAP = 1000
_CHASE_LONGEST = 42  # gathers the tail chain of _chase_volume("tail") needs


def _chase_volume(chains):
    """A 4096-voxel volume of acyclic chains, flat.  The top 512 voxels are
    finals: labels, 0, and seedless terminals that name themselves.
    ``flat``: every other voxel holds a label (all chains end at the first
    gather).  ``tail``: voxels point 512 ahead (at most 8 gathers) but for
    one chain of 41 links from voxel 0 on."""
    n = 4096
    values = np.zeros(n, np.int32)
    for g in range(3584, n):
        values[g] = (0, (g % 97) + 1, -g - 2)[g % 3]
    for g in range(3584):
        values[g] = (g % 89) + 1 if chains == "flat" else -(g + 512) - 2
    if chains == "tail":
        for g in range(40):
            values[g] = -(g + 1) - 2
        values[40] = -4000 - 2  # voxel 4000 holds a label
    return values


def _chase_codes(values, n_live, seed, first_voxel=None):
    """``cap`` slots: ``n_live`` codes at random slots, the rest ``BIG``
    padding and some -1 (neither is a chain)."""
    from cluster_tools_tpu.ops.tile_ws import BIG

    rng_ = np.random.default_rng(seed)
    codes = np.full(_CHASE_CAP, BIG, np.int32)
    codes[rng_.choice(_CHASE_CAP, 50, replace=False)] = -1
    live = rng_.choice(_CHASE_CAP, n_live, replace=False)
    codes[live] = -(rng_.integers(64, values.size, size=n_live) + 2)
    if first_voxel is not None and n_live:
        codes[live[0]] = -first_voxel - 2
    return codes


def _chase_oracle(values, codes, max_hops=None):
    """(finals, gathers of the longest chain, the chase's work counts): each
    live code followed alone in numpy; a chain past ``max_hops`` gathers
    keeps its code.  The counts are what ``chase_exits`` records (names from
    ``ops/work.py``): a hop gathers for every chain still running, in trips
    of a sixteenth of the buffer; the live sum in units of 16, every hop
    rounded up; a hop of one trip is a hop of the tail."""
    from cluster_tools_tpu.ops import work

    finals, lengths = codes.copy(), []
    for i, code in enumerate(codes):
        if code > -2:
            continue
        g, hops = -code - 2, 1
        while values[g] <= -2 and values[g] != -g - 2:
            g, hops = -values[g] - 2, hops + 1
        lengths.append(hops)
        if max_hops is None or hops <= max_hops:
            finals[i] = values[g]
    longest = max(lengths, default=0)
    chunk = -(-len(codes) // 16)
    running = [sum(n >= hop for n in lengths)
               for hop in range(1, min(longest, max_hops or longest) + 1)]
    trips = [-(-n // chunk) for n in running]
    unit = work.UNITS[work.FLOW_CHASE_LIVE]
    counts = {
        work.FLOW_CHASE_HOPS: len(running),
        work.FLOW_CHASE_TRIPS: sum(trips),
        work.FLOW_CHASE_LIVE: sum(-(-n // unit) for n in running),
        work.FLOW_CHASE_TAIL_HOPS: sum(t == 1 for t in trips),
    }
    return finals, longest, counts


def _assert_counts(got, want, lane=None):
    """A part of a work record (traced scalars by name) against ints."""
    got = {k: int(np.asarray(v) if lane is None else np.asarray(v)[lane])
           for k, v in got.items()}
    assert got == want


@pytest.mark.parametrize("chains", ["flat", "tail"])
@pytest.mark.parametrize("n_live", [0, 1, 40, 200, _CHASE_CAP])
def test_chase_exits_matches_oracle(n_live, chains):
    """Finals slot for slot against a numpy chain-following oracle: no live
    code, one, under a chunk, several chunks with a ragged last one, every
    slot live; all chains done at the first gather, and one chain far longer
    than the rest (hops of one trip after the others' few)."""
    from cluster_tools_tpu.ops.tile_ws import chase_exits

    values = _chase_volume(chains)
    codes = _chase_codes(
        values, n_live, seed=n_live, first_voxel=0 if chains == "tail" else None
    )
    want, longest, want_counts = _chase_oracle(values, codes)
    assert longest == (0 if n_live == 0 else
                       1 if chains == "flat" else _CHASE_LONGEST)
    finals, unconverged, counts = chase_exits(
        jnp.asarray(values.reshape(16, 16, 16)), jnp.asarray(codes)
    )
    assert not bool(unconverged)
    # the live slots' finals, and padding / non-active slots untouched
    np.testing.assert_array_equal(np.asarray(finals), want)
    # and the work it says it did: hops, trips, live chains, tail hops
    _assert_counts(counts, want_counts)


@pytest.mark.parametrize("max_hops,flag", [(_CHASE_LONGEST - 1, True),
                                           (_CHASE_LONGEST, False)])
def test_chase_exits_unconverged_flag(max_hops, flag):
    """A chain that needs more than ``max_hops`` gathers raises the flag and
    keeps its code; every chain that ended has its final."""
    from cluster_tools_tpu.ops.tile_ws import chase_exits

    values = _chase_volume("tail")
    codes = _chase_codes(values, 200, seed=7, first_voxel=0)
    want, longest, want_counts = _chase_oracle(values, codes, max_hops=max_hops)
    assert longest == _CHASE_LONGEST
    finals, unconverged, counts = chase_exits(
        jnp.asarray(values.reshape(16, 16, 16)), jnp.asarray(codes),
        max_hops=max_hops,
    )
    assert bool(unconverged) == flag
    np.testing.assert_array_equal(np.asarray(finals), want)
    _assert_counts(counts, want_counts)


def test_chase_exits_lanes_match_alone():
    """Under ``vmap`` (the blockwise executor's lanes) every lane takes the
    hops and trips of the lane with most: lanes with different live counts
    and chain lengths, one of them empty, equal the lanes run alone."""
    from cluster_tools_tpu.ops.tile_ws import chase_exits

    lanes = [("tail", 200, 0), ("flat", _CHASE_CAP, None), ("flat", 0, None),
             ("tail", 40, None)]
    values = np.stack([_chase_volume(c) for c, _, _ in lanes])
    codes = np.stack([
        _chase_codes(v, n_live, seed=i, first_voxel=first)
        for i, (v, (_, n_live, first)) in enumerate(zip(values, lanes))
    ])
    finals, unconverged, counts = jax.vmap(chase_exits)(
        jnp.asarray(values.reshape(-1, 16, 16, 16)), jnp.asarray(codes)
    )
    assert not np.asarray(unconverged).any()
    for lane, (v, c) in enumerate(zip(values, codes)):
        alone, _, _ = chase_exits(jnp.asarray(v.reshape(16, 16, 16)), jnp.asarray(c))
        np.testing.assert_array_equal(np.asarray(finals)[lane], np.asarray(alone))
        want, _, want_counts = _chase_oracle(v, c)
        np.testing.assert_array_equal(np.asarray(alone), want)
        # a lane's counts are its own, whatever the longest lane ran
        _assert_counts(counts, want_counts, lane)


# The capacity tiers choose at run time between one machine at two sizes, so
# a caller can never see which ran (tests/test_tile_ccl.py has the merge's and
# the remap tables').  Each site below is driven with buffers large enough
# that it really tiers, once with a live count that fits the small tier and
# once with one that does not.


@pytest.mark.parametrize("n_t,n_q", [(300, 500), (300, 20000), (20000, 500)])
def test_value_join_small_tier_matches_core(rng, n_t, n_q):
    """value_join's tiered path (compact both sides -> join -> scatter
    back) only engages above 16*16384 capacities — drive it directly
    against the untiered core.  The small tier (16448 slots a side) is
    taken when both live counts fit, the big one when either does not."""
    from cluster_tools_tpu.ops.tile_ws import (
        BIG, _value_join_core, value_join,
    )

    cap = 16 * 16384 + 1024
    rng_ = np.random.default_rng(1)
    table = np.full(cap, BIG, np.int32)
    finals = np.full(cap, BIG, np.int32)
    tv = -(rng_.choice(50000, size=n_t, replace=False).astype(np.int32) + 2)
    table[:n_t] = np.sort(tv)
    finals[:n_t] = rng_.integers(1, 100, size=n_t)
    queries = np.full(cap, BIG, np.int32)
    # some hit the table, the rest miss
    queries[:n_q] = -(rng_.integers(0, 100000, size=n_q).astype(np.int32) + 2)

    import jax.numpy as jnp

    got = np.asarray(value_join(
        jnp.asarray(queries), jnp.asarray(table), jnp.asarray(finals)))
    want = np.asarray(_value_join_core(
        jnp.asarray(queries), jnp.asarray(table), jnp.asarray(finals)))
    np.testing.assert_array_equal(got, want)
    # semantic spot-check: hits map to finals, misses to themselves
    lut = {int(v): int(f) for v, f in zip(table[:n_t], finals[:n_t])}
    for i in range(n_q):
        assert got[i] == lut.get(int(queries[i]), int(queries[i])), i


@pytest.mark.parametrize("p,fits", [(0.1, True), (0.42, False)])
def test_collect_negative_values_tier_matches_oracle(rng, p, fits):
    """The (value, tile) dedup behind the exits and the fill remaps, with a
    capacity at which it tiers, against the set numpy finds on the strips."""
    from cluster_tools_tpu.ops.tile_ws import BIG, collect_negative_values

    shape, tile, cap = (32, 64, 256), (16, 16, 128), 65536
    n = int(np.prod(shape))
    # every code distinct, so no run collapses and the dedup only drops the
    # strips' shared edges: n_total is the strips' negative voxels, counted
    # once per strip family they lie on
    values = np.where(
        rng.random(shape) < p, -np.arange(n).reshape(shape) - 2, 7
    ).astype(np.int32)
    idx = np.indices(shape)
    # the six strip families: first and last plane of a tile along each axis
    strips = [idx[a] % tile[a] == e for a in range(3) for e in (0, tile[a] - 1)]
    neg = values <= -2
    n_total = sum(int((neg & m).sum()) for m in strips)
    # the 1/16 tier of the six families' concatenated buffers
    small_n = max(3 * 16384, (4 * 32768 + 2 * 4096) // 16)
    assert small_n < cap and (n_total <= small_n) == fits

    cv, ct, overflow, _, _ = collect_negative_values(jnp.asarray(values), tile, cap)
    cv, ct = np.asarray(cv), np.asarray(ct)
    assert not bool(overflow)
    tid = (idx[0] // 16 * (shape[1] // 16) + idx[1] // 16) * (shape[2] // 128) \
        + idx[2] // 128
    sel = neg & np.logical_or.reduce(strips)
    want = sorted(zip(values[sel].tolist(), tid[sel].tolist()))
    live = cv < BIG
    assert sorted(zip(cv[live].tolist(), ct[live].tolist())) == want
    assert live[: len(want)].all() and not live[len(want):].any()


def test_sparse_seed_noise_fill_knobs(rng, monkeypatch):
    """Sparse seeds in a noise-heavy volume exceed the default fill
    capacities (many small unseeded basins) — the overflow flag must say
    so, and the public knobs (adj_cap, fill_rounds) must be enough to
    complete the fill with every voxel labeled by a seed.  Pinned to the
    CAPACITY fill: the dense default has no fill/adj caps to exercise."""
    monkeypatch.setenv("CT_FILL_MODE", "capacity")
    jax.clear_caches()
    height = rng.random((64, 64, 64)).astype(np.float32)
    seeds = np.zeros((64, 64, 64), np.int32)
    seeds[8, 8, 8] = 1
    seeds[50, 50, 50] = 2
    seg, ovf, _ = seeded_watershed_tiled(
        jnp.asarray(height), jnp.asarray(seeds), impl="xla",
        # measured at this size/seed: ~154k face voxels per axis, ~273k
        # unique adjacencies, ~38k unseeded basins -> 2^19 caps fit
        fill_cap=1 << 19, adj_cap=1 << 19, fill_rounds=32,
    )
    seg = np.asarray(seg)
    assert not bool(ovf)
    assert (seg > 0).all()
    assert set(np.unique(seg)) == {1, 2}
    jax.clear_caches()


def test_dt_watershed_seeded_tiled_external_encoding(rng):
    """Two-pass mode: external seeds dominate their basins and come back
    with the +N offset; unseeded regions get internal flat-index fragments
    (same contract as the legacy dt_watershed_seeded)."""
    from cluster_tools_tpu.ops.tile_ws import dt_watershed_seeded_tiled

    shape = (16, 16, 128)
    n = int(np.prod(shape))
    b = rng.random(shape).astype(np.float32) * 0.2
    b[:, :, 60:68] = 0.95  # a wall splits the volume in x
    ext = np.zeros(shape, np.int32)
    ext[2:6, 2:6, 2:6] = 3  # pass-one neighbor label (dense id 3)
    lab, ovf, _ = dt_watershed_seeded_tiled(
        jnp.asarray(b), jnp.asarray(ext), threshold=0.5, impl="xla"
    )
    assert not bool(ovf)
    lab = np.asarray(lab)
    # the external basin keeps id 3 + N across the left side
    assert (lab[2:6, 2:6, 2:6] == 3 + n).all()
    left = lab[:, :, :60]
    assert ((left == 3 + n) | ((left >= 1) & (left <= n))).all()
    # right of the wall is unreachable from the external seed: internal only
    right = lab[:, :, 68:]
    assert (right <= n).all() and (right >= 0).all()
    assert (right > 0).any()


def test_dt_watershed_tiled_precomputed_dist_identity(rng):
    """dist= plumb: supplying the same capped EDT the function would compute
    internally must give the identical segmentation."""
    import jax.numpy as jnp

    from cluster_tools_tpu.ops.edt import distance_transform_squared
    from cluster_tools_tpu.ops.tile_ws import dt_watershed_tiled

    vol = rng.random((24, 16, 16)).astype(np.float32)
    fg = jnp.asarray(vol < 0.5)
    dist = distance_transform_squared(fg, max_distance=4.0)
    internal, ovf1, _ = dt_watershed_tiled(
        jnp.asarray(vol), threshold=0.5, dt_max_distance=4.0, impl="xla"
    )
    supplied, ovf2, _ = dt_watershed_tiled(
        jnp.asarray(vol), threshold=0.5, dist=dist, impl="xla"
    )
    np.testing.assert_array_equal(np.asarray(internal), np.asarray(supplied))
    assert bool(ovf1) == bool(ovf2) is False


@pytest.mark.parametrize("smooth", [0, 6])
def test_propagate_formulations_bit_identical(rng, smooth):
    """The substrate-aware flow formulations (pointer jumping off-TPU,
    dense stepping on-TPU) must be bit-identical — the on-chip xla rung
    compiles whichever its backend selects, so divergence would make the
    portable path's results substrate-dependent."""
    from cluster_tools_tpu.ops.tile_ws import (
        _tile_ws_propagate_jump,
        _tile_ws_propagate_stepping,
        descent_directions,
    )

    h = rng.random((32, 32, 128)).astype(np.float32)
    for _ in range(smooth):
        for ax in range(3):
            h = (np.roll(h, 1, ax) + h + np.roll(h, -1, ax)) / 3
    seeds = (
        (rng.random(h.shape) < 0.001).astype(np.int32)
        * np.arange(1, h.size + 1).reshape(h.shape).astype(np.int32)
    )
    valid = rng.random(h.shape) < 0.95
    dirs = descent_directions(
        jnp.asarray(h), jnp.asarray(seeds > 0), jnp.asarray(valid)
    )
    sv = jnp.where(jnp.asarray(valid), jnp.asarray(seeds), -1)
    tile = (16, 16, 128)
    a = np.asarray(_tile_ws_propagate_jump(dirs, sv, tile))
    b = np.asarray(_tile_ws_propagate_stepping(dirs, sv, tile))
    np.testing.assert_array_equal(a, b)
