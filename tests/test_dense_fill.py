"""fill_unseeded_basins_dense: sort-free scatter-min Boruvka fill.

Oracle: a direct numpy simulation of the SAME Boruvka-MSF rule (each
unseeded component repeatedly attaches across its minimum incident
(saddle, edge-id) composite weight) computed over EXACT per-face saddle
minima — the semantics both fill implementations target; the dense fill
must match it bit-for-bit since it examines every face voxel.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax import lax

from cluster_tools_tpu.ops.ccl import _match_vma, _shift, _true_like
from cluster_tools_tpu.ops.tile_ccl import _compact
from cluster_tools_tpu.ops.tile_ws import (
    _auto_fill_rounds,
    _sortable_float_key,
    fill_unseeded_basins_dense,
)


@pytest.fixture
def rng():
    return np.random.default_rng(5)


def _boruvka_oracle(values, height, max_rounds=16):
    """Numpy mirror of the dense fill's round rule (distinct composite
    weights (saddle_key, eid); hooks only from unseeded roots)."""
    shape = values.shape
    n = values.size
    v = values.ravel()
    hkey = np.asarray(_sortable_float_key(jnp.asarray(height))).reshape(shape)
    P = -np.arange(n, dtype=np.int64) - 2

    def resolve(x):
        x = x.copy()
        for _ in range(64):
            m = x <= -2
            nx = x.copy()
            nx[m] = P[(-x[m] - 2)]
            if (nx == x).all():
                break
            x = nx
        return x

    for _ in range(max_rounds):
        rv = resolve(v).reshape(shape)
        # exact edge list: every face, weight (saddle, eid)
        edges = []
        for axis in range(3):
            sl = [slice(None)] * 3
            sl_a = list(sl)
            sl_a[axis] = slice(0, shape[axis] - 1)
            sl_b = list(sl)
            sl_b[axis] = slice(1, None)
            a = rv[tuple(sl_a)].ravel()
            b = rv[tuple(sl_b)].ravel()
            ha = hkey[tuple(sl_a)].ravel()
            hb = hkey[tuple(sl_b)].ravel()
            idx3 = np.arange(n, dtype=np.int64).reshape(shape)
            eid = (axis * n + idx3[tuple(sl_a)].ravel())
            ok = (a != b) & (a != 0) & (b != 0)
            sad = np.maximum(ha, hb)
            edges.append((a[ok], b[ok], sad[ok], eid[ok]))
        a = np.concatenate([e[0] for e in edges])
        b = np.concatenate([e[1] for e in edges])
        sad = np.concatenate([e[2] for e in edges])
        eid = np.concatenate([e[3] for e in edges])
        # per unseeded root: lexicographic min (saddle, eid) over incident
        best = {}
        for src, dst in ((a, b), (b, a)):
            for s_, d_, w_, e_ in zip(src, dst, sad, eid):
                if s_ <= -2:
                    key = (w_, e_)
                    if s_ not in best or key < best[s_][0]:
                        best[s_] = (key, d_)
        if not best:
            break
        P2 = P.copy()
        for root, (_, target) in best.items():
            P2[-root - 2] = target
        # 2-cycle break: mutual pairs keep the smaller terminal as root
        for root, (_, target) in best.items():
            if target <= -2 and -target - 2 in [
                -r - 2 for r in best
            ]:
                tkey = best.get(target)
                if tkey is not None and tkey[1] == root:
                    ga, gb = -root - 2, -target - 2
                    if ga < gb:
                        P2[ga] = root
        # compress
        for _ in range(64):
            m = P2 <= -2
            nxt = P2.copy()
            nxt[m] = P2[np.clip(-P2[m] - 2, 0, n - 1)]
            if (nxt == P2).all():
                break
            P2 = nxt
        if (P2 == P).all():
            break
        P = P2
    out = resolve(v).reshape(shape)
    return out


def _mk_case(rng, shape, seed_frac):
    height = rng.random(shape).astype(np.float32)
    n = int(np.prod(shape))
    # values: mimic post-exit-resolution volume labels — per-basin codes
    # from a real descent would be ideal; a synthetic partition works for
    # the fill contract: assign each voxel the code/label of its region
    from scipy import ndimage

    smooth = ndimage.gaussian_filter(height, 1.2)
    # watershed-ish partition: local minima as terminals
    minima = (smooth == ndimage.minimum_filter(smooth, 3))
    term_ids = np.flatnonzero(minima.ravel())
    # nearest-terminal partition
    lab, _ = ndimage.label(minima)
    basin = ndimage.distance_transform_edt(
        ~minima, return_distances=False, return_indices=True
    )
    flat_term = np.ravel_multi_index(
        [basin[i].ravel() for i in range(3)], shape
    )
    seeded = rng.random(len(term_ids)) < seed_frac
    code_of = {}
    next_seed = 1
    for i, t in enumerate(term_ids):
        if seeded[i]:
            code_of[t] = next_seed
            next_seed += 1
        else:
            code_of[t] = -int(t) - 2
    vals = np.array(
        [code_of.get(int(t), 0) for t in flat_term], np.int32
    ).reshape(shape)
    return vals, height


@pytest.mark.parametrize("seed_frac", [0.5, 0.15])
def test_dense_fill_matches_exact_oracle(rng, seed_frac):
    shape = (8, 9, 10)
    vals, height = _mk_case(rng, shape, seed_frac)
    got, unconv, _ = fill_unseeded_basins_dense(
        jnp.asarray(vals), jnp.asarray(height)
    )
    assert int(unconv) == 0
    want = _boruvka_oracle(vals, height)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_dense_fill_all_seeded_identity(rng):
    shape = (6, 6, 12)
    vals = rng.integers(1, 5, size=shape).astype(np.int32)
    height = rng.random(shape).astype(np.float32)
    got, unconv, _ = fill_unseeded_basins_dense(
        jnp.asarray(vals), jnp.asarray(height)
    )
    assert int(unconv) == 0
    np.testing.assert_array_equal(np.asarray(got), vals)


def test_dense_fill_unreachable_keeps_code(rng):
    # an unseeded basin fenced by invalid (0) voxels cannot adopt a label
    shape = (5, 5, 8)
    vals = np.zeros(shape, np.int32)
    vals[0, 0, 0] = -(0) - 2  # its own flat index 0 -> code -2
    vals[4, 4, :] = 7  # a seeded region far away, disconnected by zeros
    height = rng.random(shape).astype(np.float32)
    got, unconv, _ = fill_unseeded_basins_dense(
        jnp.asarray(vals), jnp.asarray(height)
    )
    assert int(unconv) == 0
    assert int(np.asarray(got)[0, 0, 0]) == -2
    assert (np.asarray(got)[4, 4, :] == 7).all()


def test_dense_mode_through_watershed(rng, monkeypatch):
    """CT_FILL_MODE=dense end-to-end: all voxels labeled, seeds kept, and
    the segmentation matches the capacity fill where both are exact
    (singleton contacts regime isn't guaranteed here, so compare only the
    labeled-coverage property and seed preservation)."""
    from cluster_tools_tpu.ops.tile_ws import seeded_watershed_tiled

    shape = (24, 24, 130)
    height = rng.random(shape).astype(np.float32)
    seeds = np.zeros(shape, np.int32)
    seeds[4, 4, 10] = 1
    seeds[20, 20, 100] = 2
    monkeypatch.setenv("CT_FILL_MODE", "dense")
    jax.clear_caches()
    got, ovf, _ = seeded_watershed_tiled(
        jnp.asarray(height), jnp.asarray(seeds), impl="xla"
    )
    assert not bool(ovf)
    got = np.asarray(got)
    assert (got > 0).all()
    assert set(np.unique(got)) <= {1, 2}
    assert got[4, 4, 10] == 1 and got[20, 20, 100] == 2
    monkeypatch.delenv("CT_FILL_MODE")
    jax.clear_caches()


def _chain_case(L, toward=1):
    """A monotone saddle corridor: seed 1 — B1 — ... — B_L — seed 2 with
    strictly increasing heights, so every basin's min edge points toward
    seed 1 and round one hooks a chain of depth L.  Exact answer: ALL
    basins adopt seed 1.  Depth L >> 8 regresses the fixed-jump-count
    compression bug (partially composed tables let later rounds hook from
    intermediate nodes and split the component across seeds).
    ``toward=2``: decreasing heights, every min edge points toward seed 2,
    so the answer hangs on the LAST face of the corridor's list."""
    shape = (3, 3, L + 2)
    vals = np.zeros(shape, np.int32)  # 0 = invalid everywhere off-corridor
    vals[1, 1, 0] = 1
    vals[1, 1, L + 1] = 2
    flat = np.arange(np.prod(shape)).reshape(shape)
    for i in range(1, L + 1):
        vals[1, 1, i] = -int(flat[1, 1, i]) - 2  # its own terminal code
    ramp = np.linspace(0.1, 0.9, L + 2).astype(np.float32)
    height = np.broadcast_to(ramp if toward == 1 else ramp[::-1], shape)
    return vals, np.ascontiguousarray(height)


@pytest.mark.parametrize("L", [20, 40])
def test_dense_fill_deep_chain(L):
    vals, height = _chain_case(L)
    got, unconv, _ = fill_unseeded_basins_dense(
        jnp.asarray(vals), jnp.asarray(height)
    )
    assert int(unconv) == 0
    got = np.asarray(got)
    assert (got[1, 1, 1:-1] == 1).all(), got[1, 1]
    assert got[1, 1, 0] == 1 and got[1, 1, -1] == 2


@pytest.mark.parametrize("L", [20, 40])
def test_capacity_fill_deep_chain(L):
    from cluster_tools_tpu.ops.tile_ws import (
        _resolve_codes_gather,
        fill_unseeded_basins,
    )

    vals, height = _chain_case(L)
    fv, ff, ovf, _ = fill_unseeded_basins(jnp.asarray(vals), jnp.asarray(height))
    assert not bool(ovf)
    got = np.asarray(
        _resolve_codes_gather(jnp.asarray(vals), fv, ff)
    )
    assert (got[1, 1, 1:-1] == 1).all(), got[1, 1]


def test_mode_env_flip_retraces_without_clear_caches(rng, monkeypatch):
    """r5 contract: CT_FILL_MODE is resolved OUTSIDE jit and folded into
    the compile key, so flipping it mid-process retraces — the old
    trace-time read silently kept the previously compiled machinery
    unless the caller knew to jax.clear_caches() (r4 advisor finding).
    Both machines are MSF-exact in the singleton-seed regime here, so the
    outputs must agree AND the explicit-kwarg selection must match the
    env selection."""
    from cluster_tools_tpu.ops.tile_ws import seeded_watershed_tiled

    shape = (16, 16, 130)
    height = rng.random(shape).astype(np.float32)
    seeds = np.zeros(shape, np.int32)
    seeds[2, 2, 5] = 1
    seeds[13, 13, 120] = 2
    h, s = jnp.asarray(height), jnp.asarray(seeds)

    from cluster_tools_tpu.ops.tile_ws import _seeded_watershed_tiled_jit

    monkeypatch.setenv("CT_FILL_MODE", "capacity")
    cap_out, cap_ovf, _ = seeded_watershed_tiled(h, s, impl="xla")
    assert not bool(cap_ovf)  # the equality premise: both paths exact here
    # NO clear_caches: the env flip alone must select the dense machinery
    # — proven by a fresh jit-cache entry, not just by equal outputs
    before = _seeded_watershed_tiled_jit._cache_size()
    monkeypatch.setenv("CT_FILL_MODE", "dense")
    dense_out, dense_ovf, _ = seeded_watershed_tiled(h, s, impl="xla")
    assert not bool(dense_ovf)
    assert _seeded_watershed_tiled_jit._cache_size() == before + 1, (
        "env flip did not retrace: stale mode silently reused"
    )
    np.testing.assert_array_equal(np.asarray(dense_out), np.asarray(cap_out))
    # the kwarg spelling is the SAME compile key as the env spelling:
    # cache size must not move (a third entry would mean key drift)
    kw_out, kw_ovf, _ = seeded_watershed_tiled(h, s, impl="xla", fill_mode="dense")
    assert not bool(kw_ovf)
    assert _seeded_watershed_tiled_jit._cache_size() == before + 1, (
        "kwarg spelling compiled a separate cache entry: key drift"
    )
    np.testing.assert_array_equal(np.asarray(kw_out), np.asarray(dense_out))


# ---------------------------------------------------------------------------
# the compact basin table against the table as large as the volume
# ---------------------------------------------------------------------------


def _fill_n_table_reference(values, height, face_cap=None):
    """The fill as it was before the basins had dense ids and before the
    rounds walked a live prefix, kept here as the plain reference: the union
    table ``P``, ``best_h`` / ``best_e``, the 2-cycle break and the closure
    loop have one entry per VOXEL and are indexed by a basin's terminal
    position, and every round passes over three lists padded to
    ``face_cap``, resolving the ORIGINAL endpoints.  Same harvest, same
    rounds, same tie-breaks; the fill must give the same integers.

    Returns ``(resolved, flag, live)``: ``live[r]`` counts, at the start of
    round ``r``, the faces that can still matter (sides resolved unequal, a
    seedless basin on one of them): what the fill's live prefix holds."""
    shape = values.shape
    n = int(np.prod(shape))
    v = values.ravel()
    h = _sortable_float_key(height.astype(jnp.float32)).ravel()
    i32max = jnp.iinfo(jnp.int32).max
    if face_cap is None:
        face_cap = min(1 << 24, max(1 << 16, n // 6))
    max_rounds = _auto_fill_rounds(n)
    P0 = _match_vma(-jnp.arange(n, dtype=jnp.int32) - 2, values)

    def resolve_flat(P, x):
        return jnp.where(x <= -2, P[jnp.clip(-x - 2, 0, n - 1)], x)

    flat_idx = _match_vma(jnp.arange(n, dtype=jnp.int32), values)
    trunc = _match_vma(jnp.zeros((), jnp.int32), values)
    faces = []
    for axis in range(3):
        nb = _shift(values, -1, axis, jnp.int32(0)).ravel()
        ok0 = (v != nb) & (v != 0) & (nb != 0) & ((v <= -2) | (nb <= -2))
        (idx_c,), n_faces = _compact(ok0, (flat_idx,), face_cap, n)
        trunc = jnp.maximum(trunc, (n_faces > face_cap).astype(jnp.int32))
        stride = int(np.prod(shape[axis + 1:], dtype=np.int64))
        pad = idx_c >= n
        ia = jnp.clip(idx_c, 0, n - 1)
        ib = jnp.clip(idx_c + stride, 0, n - 1)
        va = jnp.where(pad, 0, v[ia])
        vb = jnp.where(pad, 0, v[ib])
        sad = jnp.maximum(h[ia], h[ib])
        eid = jnp.where(pad, i32max, jnp.int32(axis) * jnp.int32(n) + idx_c)
        faces.append((va, vb, sad, eid, pad))

    def round_cond(s):
        _, changed, it, _ = s
        return changed & (it < max_rounds)

    def round_body(s):
        P, _, it, n_live = s
        best_h = _match_vma(jnp.full((n,), i32max, jnp.int32), values)
        best_e = _match_vma(jnp.full((n,), i32max, jnp.int32), values)
        sides = []
        for va, vb, sad, eid, pad in faces:
            ra = resolve_flat(P, va)
            rb = resolve_flat(P, vb)
            live = ~pad & (ra != rb)
            n_live = n_live.at[it].add(
                jnp.sum(live & ((ra <= -2) | (rb <= -2)), dtype=jnp.int32)
            )
            sides.append((ra, rb, sad, live, eid))
            sides.append((rb, ra, sad, live, eid))
        for src, dst, sad, live, eid in sides:
            m = live & (src <= -2)
            g = jnp.where(m, -src - 2, n)
            best_h = best_h.at[g].min(jnp.where(m, sad, i32max), mode="drop")
        for src, dst, sad, live, eid in sides:
            m = live & (src <= -2)
            tie = m & (best_h[jnp.clip(-src - 2, 0, n - 1)] == sad)
            gt = jnp.where(tie, -src - 2, n)
            best_e = best_e.at[gt].min(jnp.where(tie, eid, i32max), mode="drop")
        P2 = P
        for src, dst, sad, live, eid in sides:
            m = live & (src <= -2)
            gsafe = jnp.clip(-src - 2, 0, n - 1)
            win = m & (best_h[gsafe] == sad) & (best_e[gsafe] == eid)
            gw = jnp.where(win, -src - 2, n)
            P2 = P2.at[gw].set(jnp.where(win, dst, 0), mode="drop")
        me = flat_idx
        tgt = jnp.clip(-P2 - 2, 0, n - 1)
        mutual = (P2 <= -2) & (P2[tgt] == (-me - 2)) & (me < tgt)
        P2 = jnp.where(mutual, -me - 2, P2)

        def comp_body(t):
            p, _ = t
            p2 = resolve_flat(p, p)
            return p2, jnp.any(p2 != p)

        P2, _ = lax.while_loop(
            lambda t: t[1], comp_body, (P2, _true_like(P2))
        )
        return P2, jnp.any(P2 != P), it + 1, n_live

    n_live0 = _match_vma(jnp.zeros((max_rounds,), jnp.int32), values)
    P, unconverged, _, n_live = lax.while_loop(
        round_cond, round_body, (P0, _true_like(v), jnp.int32(0), n_live0)
    )
    resolved = resolve_flat(P, v).reshape(shape)
    flag = jnp.maximum(unconverged.astype(jnp.int32), trunc)
    return resolved, flag, n_live


def _masked_case(seed, shape, seed_frac):
    """A random partition with seedless basins, and a corner and half a slab of
    masked (-1) voxels cut through it: basins that border them may adopt -1."""
    vals, height = _mk_case(np.random.default_rng(seed), shape, seed_frac)
    vals = vals.copy()
    flat = np.arange(vals.size).reshape(shape)
    cut = np.zeros(shape, bool)
    cut[:2, :3, :] = True
    cut[shape[0] // 2:, : shape[1] // 2, shape[2] // 2] = True
    # a code must keep naming a voxel that carries it: mask no terminal
    cut &= vals != -flat - 2
    vals[cut] = -1
    return vals, height


def _two_cycle_case():
    """Two seedless basins A, B in a corridor between two seeds, the A|B
    saddle lower than A's and B's saddles to their seeds: in round one both
    roots pick the A|B face, from both sides.  The rule keeps the smaller
    terminal (A's) as the root; round two joins the pair to seed 1 over
    the lower of the two outer saddles.  A second pair C, D lies fenced by
    invalid voxels: it reaches no seed and keeps the ROOT's code, which
    shows which of the two the rule kept."""
    shape = (3, 3, 8)
    vals = np.zeros(shape, np.int32)
    flat = np.arange(np.prod(shape)).reshape(shape)
    vals[1, 1, 0:2] = 1
    vals[1, 1, 2:4] = -int(flat[1, 1, 3]) - 2  # A: terminal at x = 3
    vals[1, 1, 4:6] = -int(flat[1, 1, 4]) - 2  # B: terminal at x = 4
    vals[1, 1, 6:8] = 2
    vals[0, 0, 2:4] = -int(flat[0, 0, 3]) - 2  # C
    vals[0, 0, 4:6] = -int(flat[0, 0, 4]) - 2  # D
    height = np.zeros(shape, np.float32)
    height[1, 1] = [0.0, 0.7, 0.7, 0.1, 0.1, 0.8, 0.8, 0.0]
    height[0, 0] = height[1, 1]
    return vals, height


def _plateau_case():
    """ROADMAP D4's input at the fill's door: a boundary map whose clipped
    noise leaves a sixth of the heights at exactly 0.0
    (``utils/synthetic.py``), through the seed and flow phases.  Every
    plateau voxel is a basin of its own, the face lists truncate at the
    default ``face_cap`` and the flag is raised: a fault this fill keeps
    exactly as the padded lists have it."""
    from cluster_tools_tpu.ops import tile_ws
    from cluster_tools_tpu.utils.synthetic import synthetic_em_volume

    boundaries, _, _ = synthetic_em_volume(
        (64, 64, 64), n_objects=12, sampling=(1, 1, 1), with_mask=False, seed=1
    )
    assert 0.1 < (boundaries == 0.0).mean() < 0.25
    b = jnp.asarray(boundaries)
    kernels = dict(
        impl="xla", tile=None, table_cap=tile_ws.DEFAULT_TABLE_CAP,
        interpret=False,
    )
    seeds, valid, _, _ = tile_ws._dt_seeds_core(
        b, None, None, threshold=0.5, sigma_seeds=0.0, min_seed_distance=0.0,
        sampling=None, dt_max_distance=None, pair_cap=None, edge_cap=None,
        **kernels,
    )
    vals, height, _, _ = tile_ws._ws_flow_core(
        b, seeds, valid, exit_cap=None, **kernels
    )
    return np.asarray(vals), np.asarray(height)


def _columns_case():
    """A partition that is constant along y: no face on axis 1, so the
    harvest walks that axis in no trip at all and axis 2's faces land right
    behind axis 0's.  The heights repeat along y too: every saddle ties
    across the y copies of a face and the edge id decides."""
    flat_vals, flat_height = _mk_case(np.random.default_rng(31), (12, 1, 14), 0.3)
    ny = 5
    z, x = np.divmod(-flat_vals[:, 0, :] - 2, 14)
    codes = -(z * ny * 14 + x) - 2  # the terminal's copy at y = 0
    plane = np.where(flat_vals[:, 0, :] <= -2, codes, flat_vals[:, 0, :])
    vals = np.repeat(plane[:, None, :], ny, axis=1).astype(np.int32)
    height = np.repeat(flat_height, ny, axis=1)
    return vals, np.ascontiguousarray(height)


def _axis_faces(vals):
    """Candidate faces of each axis, as the harvest counts them."""
    out = []
    for axis in range(3):
        a = np.moveaxis(vals, axis, 0)
        v, nb = a[:-1], a[1:]
        out.append(int(np.sum(
            (v != nb) & (v != 0) & (nb != 0) & ((v <= -2) | (nb <= -2))
        )))
    return out


#: name -> (inputs, ``face_cap`` (None: the default), the flag both raise,
#: the chunks the live faces span at the start of each round (None: not held))
_EQUALITY_CASES = {
    "seeds_0.5": (lambda: _masked_case(11, (14, 15, 16), 0.5), None, 0, (1, 1, 0)),
    "seeds_0.15": (lambda: _masked_case(12, (14, 15, 16), 0.15), None, 0, None),
    "seeds_0.02": (lambda: _masked_case(13, (16, 18, 20), 0.02), None, 0, None),
    "two_cycle": (_two_cycle_case, None, 0, None),
    "deep_chain": (lambda: _chain_case(40), None, 0, (1, 0)),
    # several chunks in round one, one in round two
    "chunks_7_1": (lambda: _masked_case(11, (14, 15, 16), 0.5), 4000, 0, (7, 1, 0)),
    # fewer chunks every round, never one
    "chunks_9_6_4_3": (
        lambda: _masked_case(22, (10, 11, 12), 0.02), 1600, 0, (9, 6, 4, 3, 0)
    ),
    # the list empties after round one; round two finds nothing and ends the loop
    "deep_chain_chunks_14_0": (lambda: _chain_case(40), 41, 0, (14, 0)),
    "two_cycle_chunks_4_2": (_two_cycle_case, 4, 0, (4, 2, 0)),
    "plateau_d4": (_plateau_case, None, 1, None),
    "face_cap_truncated": (lambda: _masked_case(12, (14, 15, 16), 0.15), 200, 1, None),
    "face_cap_truncated_one_slot": (_two_cycle_case, 1, 1, None),
    # the harvest's edges (``_HARVEST``): each axis's face positions are
    # walked in chunks up to their count
    "harvest_axis_without_face": (_columns_case, 640, 0, None),
    "harvest_under_one_chunk": (
        lambda: _masked_case(11, (14, 15, 16), 0.5), 9600, 0, (3, 1, 0)
    ),
    "harvest_exact_chunks": (
        lambda: _masked_case(11, (14, 15, 16), 0.5), 4624, 0, None
    ),
    # 16 does not divide face_cap: a chunk is 3 slots, 16 of them 48, and
    # the last chunk's slice reaches past face_cap.  The chain leans toward
    # seed 2, so the last face kept decides every label: all 41 fit ...
    "harvest_odd_face_cap_full": (lambda: _chain_case(40, toward=2), 41, 0, None),
    # ... or the list is cut at 37 and the flag goes up
    "harvest_truncated_odd_face_cap": (
        lambda: _chain_case(40, toward=2), 37, 1, None
    ),
}

#: name -> (faces of each axis, the harvest's trips for each axis)
_HARVEST = {
    "harvest_axis_without_face": ((125, 0, 150), (4, 0, 4)),
    "harvest_under_one_chunk": ((578, 521, 531), (1, 1, 1)),
    "harvest_exact_chunks": ((2 * 289, 521, 531), (2, 2, 2)),
    "harvest_odd_face_cap_full": ((0, 0, 41), (0, 0, 14)),
    "harvest_truncated_odd_face_cap": ((0, 0, 41), (0, 0, 13)),
    "deep_chain_chunks_14_0": ((0, 0, 41), (0, 0, 14)),
}


@pytest.mark.parametrize("case", sorted(_EQUALITY_CASES))
def test_compact_table_equals_n_table(case):
    make, face_cap, raised, chunks = _EQUALITY_CASES[case]
    vals, height = make()
    got, flag, _ = fill_unseeded_basins_dense(
        jnp.asarray(vals), jnp.asarray(height), face_cap=face_cap
    )
    want, want_flag, live = _fill_n_table_reference(
        jnp.asarray(vals), jnp.asarray(height), face_cap=face_cap
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(flag) == int(want_flag) == raised
    assert (vals <= -2).any()  # the case has seedless basins at all
    if chunks is not None:
        chunk = -(-(face_cap or 1 << 16) // 16)
        spans = [-(-int(x) // chunk) for x in np.asarray(live)]
        assert tuple(spans[: len(chunks)]) == chunks
    if case in _HARVEST:
        faces, trips = _HARVEST[case]
        chunk = -(-face_cap // 16)
        assert tuple(_axis_faces(vals)) == faces
        assert tuple(-(-min(f, face_cap) // chunk) for f in faces) == trips
    if case == "harvest_odd_face_cap_full":
        assert (np.asarray(got)[1, 1] == [1] + [2] * 41).all()
    if case == "harvest_truncated_odd_face_cap":
        # faces 0..36 are kept: B1..B37 end at seed 1, B38..B40 are cut off
        assert (np.asarray(got)[1, 1, :38] == 1).all()
        assert (np.asarray(got)[1, 1, 38:41] <= -2).all()
    if case == "two_cycle":
        assert (np.asarray(got)[1, 1] == [1, 1, 1, 1, 1, 1, 2, 2]).all()
        c_code = -(0 * 24 + 0 * 8 + 3) - 2
        assert (np.asarray(got)[0, 0] == [0, 0] + [c_code] * 4 + [0, 0]).all()
    if case.startswith("seeds"):
        assert (vals == -1).any()
    if case == "plateau_d4":
        # with room for every face the same input converges: D4's flag is
        # the lists' truncation, not the rounds' tie-break
        n = vals.size
        _, roomy, _ = fill_unseeded_basins_dense(
            jnp.asarray(vals), jnp.asarray(height), face_cap=n
        )
        assert int(roomy) == 0


@pytest.mark.parametrize("extra_basins", [0, 1])
def test_basin_table_overflow_raises_flag(extra_basins):
    """More seedless basins than ``basin_cap`` (2^16 below 2^20 voxels)
    raises the overflow return; exactly ``basin_cap`` of them does not.
    Every voxel is a basin of its own here but a seeded head of the
    volume, and ``face_cap`` is given room so that only the basin table
    can truncate."""
    shape = (41, 41, 41)
    n = int(np.prod(shape))
    basin_cap = 1 << 16
    assert basin_cap < n
    k = basin_cap + extra_basins
    vals = -np.arange(n, dtype=np.int32) - 2
    vals[: n - k] = 1
    height = np.random.default_rng(3).random(shape).astype(np.float32)
    got, flag, _ = fill_unseeded_basins_dense(
        jnp.asarray(vals.reshape(shape)), jnp.asarray(height), face_cap=n
    )
    assert int(flag) == extra_basins
    if not extra_basins:
        assert (np.asarray(got) == 1).all()


def test_code_without_terminal_raises_flag():
    """A code whose terminal voxel does not carry it has no dense id: the
    fill reports it through the flag and never resolves it silently."""
    vals, height = _two_cycle_case()
    vals[1, 1, 3] = -1  # A's terminal is masked; x = 2 still carries A's code
    _, flag, _ = fill_unseeded_basins_dense(jnp.asarray(vals), jnp.asarray(height))
    assert int(flag) == 1


def _two_blocks(face_cap=None):
    a = _masked_case(21, (10, 11, 12), 0.15)
    b = _masked_case(22, (10, 11, 12), 0.02)
    vals = np.stack([a[0], b[0]])
    height = np.stack([a[1], b[1]])
    want = [
        _fill_n_table_reference(jnp.asarray(v), jnp.asarray(h), face_cap=face_cap)
        for v, h in zip(vals, height)
    ]
    return vals, height, want


#: ``face_cap`` 400 makes a chunk 25 slots: the two blocks' 863 and 829 faces
#: are 35 and 34 chunks in round one and 20 and 24 in round two
@pytest.mark.parametrize("face_cap", [None, 400])
def test_compact_table_under_vmap(face_cap):
    """Two blocks with different basin counts as lanes of one program (the
    blockwise executor's form): each lane equals its own n-table run.  With
    the small ``face_cap`` the lanes' loops run different trip counts in
    every round."""
    vals, height, want = _two_blocks(face_cap)
    got, flag, _ = jax.vmap(partial(fill_unseeded_basins_dense, face_cap=face_cap))(
        jnp.asarray(vals), jnp.asarray(height)
    )
    for lane in range(2):
        np.testing.assert_array_equal(
            np.asarray(got[lane]), np.asarray(want[lane][0])
        )
        assert int(flag[lane]) == int(want[lane][1]) == 0
    if face_cap is not None:
        chunk = -(-face_cap // 16)
        spans = [[-(-int(x) // chunk) for x in np.asarray(w[2])[:2]] for w in want]
        assert spans == [[35, 20], [34, 24]]


@pytest.mark.parametrize("face_cap", [None, 400])
@pytest.mark.parametrize("check_vma", [False, True])
def test_compact_table_under_shard_map(check_vma, face_cap):
    """The mesh step's form: the per-shard body under ``shard_map`` on a
    one-device (dp, sp) mesh.  The pipeline turns the vma check off (its
    Pallas kernels); with it on, every fresh table and list of the fill must
    carry the data's varying axes (``_match_vma``).  With the small
    ``face_cap`` the list is 35 chunks in round one."""
    from jax.sharding import PartitionSpec

    from cluster_tools_tpu.compat import shard_map
    from cluster_tools_tpu.parallel.mesh import backend_devices, make_mesh

    mesh = make_mesh(
        1, axis_names=("dp", "sp"), devices=backend_devices("local")[:1]
    )
    vals, height, want = _two_blocks(face_cap)

    def body(v, h):
        out, flag, _ = fill_unseeded_basins_dense(v[0], h[0], face_cap=face_cap)
        return out[None], lax.pmax(flag, ("dp", "sp"))

    spec = PartitionSpec("dp", "sp")
    step = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec, PartitionSpec()), check_vma=check_vma,
    ))
    got, flag = step(jnp.asarray(vals[:1]), jnp.asarray(height[:1]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0][0]))
    assert int(flag) == 0


def test_harvest_gathers_are_chunk_sized():
    """No gather of ``ws.fill.harvest`` takes ``face_cap`` or more indices:
    the harvest reads its endpoints, saddles and ids a chunk at a time, in
    a loop whose trips follow the count (six gathers for each axis)."""
    shape, face_cap = (14, 15, 16), 1600
    assert int(np.prod(shape)) > face_cap
    closed = jax.make_jaxpr(
        partial(fill_unseeded_basins_dense, face_cap=face_cap)
    )(jnp.zeros(shape, jnp.int32), jnp.zeros(shape, jnp.float32))
    found = []

    def walk(jaxpr, stack):
        for eqn in jaxpr.eqns:
            path = f"{stack}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "gather":
                found.append((path, eqn.invars[1].aval.shape[0]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, path)

    walk(closed.jaxpr, "")
    harvest = [slots for path, slots in found if "ws.fill.harvest" in path]
    assert len(harvest) == 18
    assert set(harvest) == {-(-face_cap // 16)}
    # the walk does see a gather as large as that where there is one
    assert any(slots >= face_cap for _, slots in found)
