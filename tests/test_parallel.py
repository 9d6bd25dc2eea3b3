"""Tests for the mesh-parallel layer: halo exchange, distributed CCL, the
fused sharded step, and the driver entry points — all on the virtual
8-device CPU mesh (SURVEY.md §4 "implication for the rebuild")."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from scipy import ndimage

from cluster_tools_tpu.parallel import (
    distributed_connected_components,
    exchange_halo,
    make_mesh,
    mesh_axis_sizes,
)
from cluster_tools_tpu.compat import shard_map
from cluster_tools_tpu.parallel.mesh import backend_devices
from cluster_tools_tpu.parallel.pipeline import make_ws_ccl_step

from .helpers import assert_labels_equivalent, random_blobs


def _mesh(axis_names=("sp",), n=None):
    devs = backend_devices("local")
    n = n or len(devs)
    return make_mesh(n, axis_names=axis_names, devices=devs)


def test_exchange_halo_matches_pad():
    mesh = _mesh(("sp",))
    sp = mesh_axis_sizes(mesh)["sp"]
    z = sp * 6
    x = np.arange(z * 4 * 4, dtype=np.float32).reshape(z, 4, 4)
    halo = 2

    fn = shard_map(
        lambda v: exchange_halo(v, halo, 0, "sp", sp, fill=-1.0),
        mesh=mesh,
        in_specs=P("sp"),
        out_specs=P("sp"),
    )
    out = np.asarray(fn(x))
    # shard s gets rows [s*6-2, (s+1)*6+2) with -1 padding at volume ends
    slab = z // sp
    parts = []
    for s in range(sp):
        lo, hi = s * slab - halo, (s + 1) * slab + halo
        pad_lo, pad_hi = max(0, -lo), max(0, hi - z)
        core = x[max(0, lo) : min(z, hi)]
        part = np.concatenate(
            [np.full((pad_lo, 4, 4), -1.0), core, np.full((pad_hi, 4, 4), -1.0)]
        )
        parts.append(part)
    expect = np.concatenate(parts)
    np.testing.assert_array_equal(out, expect)


def test_distributed_ccl_vs_scipy(rng):
    mesh = _mesh(("sp",))
    sp = mesh_axis_sizes(mesh)["sp"]
    shape = (sp * 8, 24, 24)
    mask = random_blobs(rng, shape, p=0.4)
    labels = np.asarray(
        distributed_connected_components(mask, mesh, sp_axis="sp")
    )
    expected, _ = ndimage.label(mask, structure=ndimage.generate_binary_structure(3, 1))
    assert_labels_equivalent(labels, expected)


def test_distributed_ccl_pair_dedup_and_fallback(rng):
    """merge_labels_by_pairs' pre-collective dedup only engages above its
    16384-row floor, which the small workflow tests never reach — drive a
    face large enough for the dedup branch, and force the full-size
    fallback with a tiny pair_cap; both must match scipy exactly."""
    import cluster_tools_tpu.parallel.distributed_ccl as dc

    mesh = _mesh(("sp",))
    sp = mesh_axis_sizes(mesh)["sp"]
    # face = 136*136 = 18496 > 16384: the dedup branch compiles AND runs
    shape = (sp * 4, 136, 136)
    mask = random_blobs(rng, shape, p=0.45)
    expected, _ = ndimage.label(
        mask, structure=ndimage.generate_binary_structure(3, 1)
    )

    labels = np.asarray(
        distributed_connected_components(mask, mesh, sp_axis="sp")
    )
    assert_labels_equivalent(labels, expected)

    # force the fallback: a tiny cap makes n_max exceed it on any
    # non-trivial mask, so the pmax-agreed full-size branch must run.
    # Different shape than above so a cached trace of the unpatched
    # function cannot serve the call.
    shape_fb = (sp * 4, 140, 140)
    mask_fb = random_blobs(rng, shape_fb, p=0.45)
    expected_fb, _ = ndimage.label(
        mask_fb, structure=ndimage.generate_binary_structure(3, 1)
    )
    orig = dc.merge_labels_by_pairs

    def tiny_cap(glob, pairs, axes, rank, span, pair_cap=None):
        # unique cross-face pairs for this mask measure ~50-70 per shard:
        # a cap of 16 guarantees n_max > pair_cap and the fallback runs
        return orig(glob, pairs, axes, rank, span, pair_cap=16)

    dc.merge_labels_by_pairs = tiny_cap
    try:
        labels_fb = np.asarray(
            distributed_connected_components(mask_fb, mesh, sp_axis="sp")
        )
    finally:
        dc.merge_labels_by_pairs = orig
    assert_labels_equivalent(labels_fb, expected_fb)


def test_distributed_ccl_component_spanning_all_shards():
    mesh = _mesh(("sp",))
    sp = mesh_axis_sizes(mesh)["sp"]
    shape = (sp * 4, 8, 8)
    mask = np.zeros(shape, bool)
    mask[:, 3, 3] = True  # one rod through every shard
    mask[0, 0, 0] = True  # plus an isolated voxel
    labels = np.asarray(distributed_connected_components(mask, mesh))
    rod = labels[:, 3, 3]
    assert (rod == rod[0]).all() and rod[0] > 0
    assert labels[0, 0, 0] > 0 and labels[0, 0, 0] != rod[0]
    assert (labels[~mask] == 0).all()


def test_distributed_ccl_two_axis_sharding(rng):
    # one volume sharded along BOTH z and y — a (2, 4) spatial decomposition
    mesh = _mesh(("spz", "spy"))
    sizes = mesh_axis_sizes(mesh)
    sz, sy = sizes["spz"], sizes["spy"]
    shape = (sz * 6, sy * 6, 20)
    mask = random_blobs(rng, shape, p=0.45)
    labels = np.asarray(
        distributed_connected_components(mask, mesh, sp_axis=("spz", "spy"))
    )
    expected, _ = ndimage.label(mask, structure=ndimage.generate_binary_structure(3, 1))
    assert_labels_equivalent(labels, expected)


@pytest.mark.parametrize("connectivity", [2, 3])
def test_distributed_ccl_full_connectivity(rng, connectivity):
    """Diagonal adjacency must stitch across the shard cuts too."""
    mesh = _mesh(("sp",))
    sp = mesh_axis_sizes(mesh)["sp"]
    shape = (sp * 6, 20, 20)
    mask = random_blobs(rng, shape, p=0.25)
    labels = np.asarray(
        distributed_connected_components(
            mask, mesh, sp_axis="sp", connectivity=connectivity
        )
    )
    expected, _ = ndimage.label(
        mask, structure=ndimage.generate_binary_structure(3, connectivity)
    )
    assert_labels_equivalent(labels, expected)


@pytest.mark.slow  # tier-2 (make tier2): ~40 s of XLA compiles; the
# full-connectivity and pair-dedup tests keep distributed CCL in tier-1
def test_distributed_ccl_two_axis_diagonal_shards(rng):
    """Connectivity 3 on a 2-axis decomposition: voxels meeting only at the
    corner shared by four diagonal shards must merge."""
    mesh = _mesh(("spz", "spy"))
    sizes = mesh_axis_sizes(mesh)
    sz, sy = sizes["spz"], sizes["spy"]
    shape = (sz * 4, sy * 4, 8)
    # two voxels diagonal across BOTH shard cuts (shards (0,0) and (1,1))
    mask = np.zeros(shape, bool)
    mask[3, 3, 2] = True
    mask[4, 4, 3] = True
    labels = np.asarray(
        distributed_connected_components(
            mask, mesh, sp_axis=("spz", "spy"), connectivity=3
        )
    )
    assert labels[3, 3, 2] == labels[4, 4, 3] != 0
    # and a random oracle check across the same decomposition
    mask = random_blobs(rng, shape, p=0.25)
    labels = np.asarray(
        distributed_connected_components(
            mask, mesh, sp_axis=("spz", "spy"), connectivity=3
        )
    )
    expected, _ = ndimage.label(
        mask, structure=ndimage.generate_binary_structure(3, 3)
    )
    assert_labels_equivalent(labels, expected)


def test_distributed_ccl_compacted_labels(rng):
    # per-shard compaction: same result, label space capped at shards*cap
    mesh = _mesh(("sp",))
    sp = mesh_axis_sizes(mesh)["sp"]
    shape = (sp * 8, 24, 24)
    mask = random_blobs(rng, shape, p=0.4)
    labels = np.asarray(
        distributed_connected_components(
            mask, mesh, sp_axis="sp", max_labels_per_shard=512
        )
    )
    expected, _ = ndimage.label(mask, structure=ndimage.generate_binary_structure(3, 1))
    assert_labels_equivalent(labels, expected)
    assert labels.max() < sp * 513, "labels escaped the compacted space"


def test_sharded_ccl_overflow_flag():
    # a shard with more components than the cap must raise the overflow flag
    from cluster_tools_tpu.parallel.distributed_ccl import sharded_label_components

    mesh = _mesh(("sp",))
    sp = mesh_axis_sizes(mesh)["sp"]
    shape = (sp * 8, 9, 9)
    mask = np.zeros(shape, bool)
    mask[::2, ::2, ::2] = True  # isolated voxels: ~81 components per shard

    def body(m):
        return sharded_label_components(
            m,
            axis_name="sp",
            axis_size=sp,
            max_labels_per_shard=8,
            return_overflow=True,
        )[:2]

    _, overflow = shard_map(
        body, mesh=mesh, in_specs=P("sp"), out_specs=(P("sp"), P())
    )(mask)
    assert bool(overflow)


@pytest.mark.slow  # tier-2 (make tier2): ~23 s of XLA compiles; shape/dtype
# variant of the ws_ccl step — _stitched_fragments keeps the path tier-1.
def test_ws_ccl_step_shapes_and_consistency(rng):
    mesh = _mesh(("dp", "sp"))
    sizes = mesh_axis_sizes(mesh)
    dp, sp = sizes["dp"], sizes["sp"]
    b, z, y, x = dp, sp * 8, 16, 16
    vol = rng.random((b, z, y, x)).astype(np.float32)
    step = make_ws_ccl_step(mesh, halo=2, threshold=0.5)
    ws, cc, n_fg, overflow, _ = jax.block_until_ready(step(vol))
    ws, cc = np.asarray(ws), np.asarray(cc)
    assert ws.shape == vol.shape and cc.shape == vol.shape
    assert int(n_fg) == int((cc > 0).sum())
    assert not bool(overflow)
    # merged CC labels must match scipy on each batch element
    for i in range(b):
        expected, _ = ndimage.label(
            vol[i] < 0.5, structure=ndimage.generate_binary_structure(3, 1)
        )
        assert_labels_equivalent(cc[i], expected)
    # compacted-label mode: identical segmentation, bounded label space
    step_c = make_ws_ccl_step(mesh, halo=2, threshold=0.5, max_labels_per_shard=2048)
    ws2, cc2, n_fg2, overflow2 = jax.block_until_ready(step_c(vol))
    assert int(n_fg2) == int(n_fg)
    assert not bool(overflow2)
    for i in range(b):
        assert_labels_equivalent(np.asarray(cc2)[i], cc[i])
        assert_labels_equivalent(np.asarray(ws2)[i], ws[i])
    # an absurdly small cap must trip the overflow flag
    step_o = make_ws_ccl_step(mesh, halo=2, threshold=0.5, max_labels_per_shard=4)
    *_, overflow3 = jax.block_until_ready(step_o(vol))
    assert bool(overflow3)


@pytest.mark.parametrize("impl", ["auto", "legacy"])
def test_ws_ccl_step_single_device_mesh(rng, impl):
    """The 1x1 (dp, sp) mesh — the single-chip benchmark topology.

    Regression: with ``sp_size == 1`` the distributed CCL's early return
    skipped the overflow-flag reduction, leaving it sp-varying against a
    replicated out_spec — every impl failed to trace.  The multi-device
    tests can't see this because their axes are > 1.
    """
    mesh = make_mesh(1, axis_names=("dp", "sp"), devices=backend_devices("local"))
    vol = rng.random((1, 24, 16, 16)).astype(np.float32)
    step = make_ws_ccl_step(
        mesh, halo=2, threshold=0.5, dt_max_distance=2.0, impl=impl
    )
    ws, cc, n_fg, overflow, _ = jax.block_until_ready(step(vol))
    cc = np.asarray(cc)
    assert int(n_fg) == int((cc > 0).sum())
    assert not bool(overflow)
    expected, _ = ndimage.label(
        vol[0] < 0.5, structure=ndimage.generate_binary_structure(3, 1)
    )
    assert_labels_equivalent(cc[0], expected)


def test_graft_entry_single_chip():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == args[0].shape
    assert int(jnp.max(out)) > 0  # produced some labels


@pytest.mark.slow  # tier-2 (make tier2): ~25 s; full-graph compile smoke of
# the driver entry (also exercised by the verify drive).
def test_graft_entry_dryrun():
    import __graft_entry__ as g

    g.dryrun_multichip(len(backend_devices("local")))


def test_reshard_axis_roundtrip():
    """all-to-all shard transposition: values identical to the unsharded
    volume under both layouts, and a z->x->z round trip is the identity."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from cluster_tools_tpu.parallel.mesh import make_mesh
    from cluster_tools_tpu.parallel.reshard import transpose_sharding

    mesh = make_mesh(4, axis_names=("sp",))
    rng = np.random.default_rng(3)
    vol = jnp.asarray(rng.random((8, 12, 16)).astype(np.float32))
    vz = jax.device_put(vol, NamedSharding(mesh, P("sp")))
    vx = transpose_sharding(vz, mesh, "sp", from_axis=0, to_axis=2)
    np.testing.assert_allclose(np.asarray(vx), np.asarray(vol))
    # the output really is sharded along x now
    shard_shapes = {s.data.shape for s in vx.addressable_shards}
    assert shard_shapes == {(8, 12, 4)}
    back = transpose_sharding(vx, mesh, "sp", from_axis=2, to_axis=0)
    np.testing.assert_allclose(np.asarray(back), np.asarray(vol))
    assert {s.data.shape for s in back.addressable_shards} == {(2, 12, 16)}


def test_distributed_edt_exact_vs_scipy(rng):
    """Globally EXACT EDT on a sharded volume — distances must match the
    single-shot scipy transform everywhere (no halo saturation), including
    anisotropic sampling."""
    from cluster_tools_tpu.parallel import distributed_distance_transform

    mesh = _mesh(("sp",))
    sp = mesh_axis_sizes(mesh)["sp"]
    shape = (sp * 6, 12, 8 * sp)
    mask = rng.random(shape) < 0.97  # sparse background: long exact distances
    mask[0, 0, 0] = False            # guarantee some background
    for sampling in (None, (3.0, 1.0, 1.5)):
        got = np.asarray(
            distributed_distance_transform(mask, mesh, sampling=sampling)
        )
        want = ndimage.distance_transform_edt(mask, sampling=sampling)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_distributed_edt_capped(rng):
    from cluster_tools_tpu.parallel import distributed_distance_transform

    mesh = _mesh(("sp",))
    sp = mesh_axis_sizes(mesh)["sp"]
    shape = (sp * 6, 12, 8 * sp)
    mask = rng.random(shape) < 0.9
    cap = 3.0
    got = np.asarray(
        distributed_distance_transform(mask, mesh, max_distance=cap)
    )
    want = ndimage.distance_transform_edt(mask)
    exact = want <= cap
    np.testing.assert_allclose(got[exact], want[exact], rtol=1e-5, atol=1e-4)
    assert (got[~exact] >= cap - 1e-4).all()


def test_ws_ccl_step_exact_edt(rng):
    """exact_edt=True: the fused step seeds from the mesh-exact EDT; the
    merged-CC side and consistency invariants must be unaffected."""
    mesh = _mesh(("dp", "sp"))
    sizes = mesh_axis_sizes(mesh)
    dp, sp = sizes["dp"], sizes["sp"]
    b, z, y, x = dp, sp * 8, 16, 8 * sp  # x divisible by sp for the reshard
    vol = rng.random((b, z, y, x)).astype(np.float32)
    step = make_ws_ccl_step(mesh, halo=2, threshold=0.5, exact_edt=True)
    ws, cc, n_fg, overflow, _ = jax.block_until_ready(step(vol))
    ws, cc = np.asarray(ws), np.asarray(cc)
    assert not bool(overflow)
    assert (ws.shape == vol.shape) and int(n_fg) == int((cc > 0).sum())
    for i in range(b):
        expected, _ = ndimage.label(
            vol[i] < 0.5, structure=ndimage.generate_binary_structure(3, 1)
        )
        assert_labels_equivalent(cc[i], expected)


def test_distributed_edt_two_axis_decomposition(rng):
    """Exact EDT on a (2, 4) spatial decomposition: both sharded axes'
    passes run at full extent via chained reshards."""
    from cluster_tools_tpu.parallel import distributed_distance_transform

    mesh = _mesh(("spz", "spy"))
    sizes = mesh_axis_sizes(mesh)
    sz, sy = sizes["spz"], sizes["spy"]
    shape = (sz * 4, sy * 4, 8 * sz * sy)
    mask = rng.random(shape) < 0.95
    mask[0, 0, 0] = False
    got = np.asarray(
        distributed_distance_transform(
            mask, mesh, sp_axis=("spz", "spy"), sampling=(2.0, 1.0, 1.0)
        )
    )
    want = ndimage.distance_transform_edt(mask, sampling=(2.0, 1.0, 1.0))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.slow  # tier-2 (make tier2): ~18 s of XLA compiles; the
# stitched path stays tier-1 via test_ws_ccl_step_stitched_with_compaction
# and test_ws_ccl_step_two_axis_decomposition.
def test_ws_ccl_step_stitched_fragments(rng):
    """stitch_ws_threshold: fragments facing each other across shard cuts
    with weak boundary evidence must merge — returned ws_labels are
    globally consistent across every cut (BASELINE config 3's stitch,
    device-resident)."""
    mesh = _mesh(("dp", "sp"))
    sizes = mesh_axis_sizes(mesh)
    dp, sp = sizes["dp"], sizes["sp"]
    b, z, y, x = dp, sp * 8, 12, 12
    # one deep basin spanning every shard: low boundary everywhere inside a
    # tube, high outside
    vol = np.full((b, z, y, x), 0.9, np.float32)
    vol[:, :, 4:8, 4:8] = 0.05
    step = make_ws_ccl_step(
        mesh, halo=2, threshold=0.5, stitch_ws_threshold=0.5
    )
    ws, cc, n_fg, overflow, _ = jax.block_until_ready(step(vol))
    ws = np.asarray(ws)
    assert not bool(overflow)
    slab = z // sp
    for i in range(b):
        for s in range(1, sp):
            lo, hi = ws[i, s * slab - 1], ws[i, s * slab]
            both = (lo > 0) & (hi > 0) & (vol[i, s * slab - 1] < 0.5) & (
                vol[i, s * slab] < 0.5
            )
            assert both.any(), "test volume must have contact at the cut"
            assert (lo[both] == hi[both]).all(), (
                f"cut {s}: stitched ws labels differ across the boundary"
            )
    # unstitched control: the same volume keeps per-shard fragment ids
    # (only meaningful when a cut exists)
    if sp > 1:
        step0 = make_ws_ccl_step(mesh, halo=2, threshold=0.5)
        ws0 = np.asarray(jax.block_until_ready(step0(vol))[0])
        s = sp // 2
        lo, hi = ws0[0, s * slab - 1], ws0[0, s * slab]
        both = (lo > 0) & (hi > 0)
        assert not np.intersect1d(lo[both], hi[both]).size


def test_ws_ccl_step_stitched_with_compaction(rng):
    mesh = _mesh(("dp", "sp"))
    sizes = mesh_axis_sizes(mesh)
    dp, sp = sizes["dp"], sizes["sp"]
    b, z, y, x = dp, sp * 8, 12, 12
    vol = rng.random((b, z, y, x)).astype(np.float32)
    step = make_ws_ccl_step(
        mesh, halo=2, threshold=0.5, stitch_ws_threshold=0.5,
        max_labels_per_shard=2048,
    )
    ws, cc, n_fg, overflow, _ = jax.block_until_ready(step(vol))
    assert not bool(overflow)
    ws = np.asarray(ws)
    slab = z // sp
    # every weak-evidence contact pair must carry the same merged id
    for i in range(b):
        for s in range(1, sp):
            lo, hi = ws[i, s * slab - 1], ws[i, s * slab]
            weak = (
                (lo > 0) & (hi > 0)
                & (np.maximum(vol[i, s * slab - 1], vol[i, s * slab]) < 0.5)
            )
            assert (lo[weak] == hi[weak]).all()


def test_ws_ccl_step_two_axis_decomposition(rng):
    """The fused step on a (dp, spz, spy) mesh — a full 2-D spatial
    decomposition of each volume, with stitched watershed fragments and
    merged CC labels consistent across BOTH families of cuts."""
    mesh = _mesh(("dp", "spz", "spy"))
    sizes = mesh_axis_sizes(mesh)
    dp, sz, sy = sizes["dp"], sizes["spz"], sizes["spy"]
    b, z, y, x = dp, sz * 8, sy * 8, 8 * sz * sy  # x divides for exact_edt
    vol = rng.random((b, z, y, x)).astype(np.float32)
    step = make_ws_ccl_step(
        mesh, halo=2, threshold=0.5, sp_axis=("spz", "spy"),
        stitch_ws_threshold=0.5, max_labels_per_shard=4096,
    )
    ws, cc, n_fg, overflow, _ = jax.block_until_ready(step(vol))
    ws, cc = np.asarray(ws), np.asarray(cc)
    assert not bool(overflow)
    assert int(n_fg) == int((cc > 0).sum())
    for i in range(b):
        expected, _ = ndimage.label(
            vol[i] < 0.5, structure=ndimage.generate_binary_structure(3, 1)
        )
        assert_labels_equivalent(cc[i], expected)
    # stitched ws: weak-evidence contacts agree across both cut families
    for i in range(b):
        for s in range(1, sz):
            lo, hi = ws[i, s * (z // sz) - 1], ws[i, s * (z // sz)]
            weak = (
                (lo > 0) & (hi > 0)
                & (np.maximum(
                    vol[i, s * (z // sz) - 1], vol[i, s * (z // sz)]
                ) < 0.5)
            )
            assert (lo[weak] == hi[weak]).all(), "z-cut stitch broken"
        for s in range(1, sy):
            lo, hi = ws[i, :, s * (y // sy) - 1], ws[i, :, s * (y // sy)]
            weak = (
                (lo > 0) & (hi > 0)
                & (np.maximum(
                    vol[i, :, s * (y // sy) - 1], vol[i, :, s * (y // sy)]
                ) < 0.5)
            )
            assert (lo[weak] == hi[weak]).all(), "y-cut stitch broken"


def test_ws_ccl_step_two_axis_exact_edt(rng):
    mesh = _mesh(("dp", "spz", "spy"))
    sizes = mesh_axis_sizes(mesh)
    dp, sz, sy = sizes["dp"], sizes["spz"], sizes["spy"]
    b, z, y, x = dp, sz * 8, sy * 8, 8 * sz * sy
    vol = rng.random((b, z, y, x)).astype(np.float32)
    step = make_ws_ccl_step(
        mesh, halo=2, threshold=0.5, sp_axis=("spz", "spy"), exact_edt=True,
    )
    ws, cc, n_fg, overflow, _ = jax.block_until_ready(step(vol))
    assert not bool(overflow)
    assert int(n_fg) == int((np.asarray(cc) > 0).sum())


def _assert_shards_identical(arr, what):
    """Dynamic twin of the disabled static vma check: an output promised
    replicated (out_spec P()) must hold the SAME bytes on every device."""
    shards = arr.addressable_shards
    ref = np.asarray(shards[0].data)
    for s in shards[1:]:
        np.testing.assert_array_equal(
            np.asarray(s.data), ref,
            err_msg=f"{what}: replicated output differs across devices — "
            "an sp-varying value escaped a replicated out_spec "
            "(the check_vma=False exception must be re-audited)",
        )


def test_replicated_outputs_fence(rng):
    """VERDICT r3 weak #2 / next #8: the two Pallas-bearing shard_maps run
    with check_vma=False (JAX 0.9 vma propagation rejects correct kernels);
    this fence re-checks the replication promise DYNAMICALLY by comparing
    per-device bytes of every output the fused step promises replicated.

    Re-enable condition (tracked): when shard_map(check_vma=True) accepts
    pallas_call outputs whose kernels mix ref loads with constants in loop
    carries (fixed vma propagation through concatenate), flip the two
    check_vma=False sites in parallel/pipeline.py and
    parallel/distributed_ccl.py and retire this test to a regression.
    """
    mesh = _mesh(("dp", "sp"))
    sizes = mesh_axis_sizes(mesh)
    dp, sp = sizes["dp"], sizes["sp"]
    b, z, y, x = dp, sp * 8, 8, 16
    vol = rng.random((b, z, y, x)).astype(np.float32)
    step = make_ws_ccl_step(
        mesh, halo=2, threshold=0.5, stitch_ws_threshold=0.5,
    )
    ws, cc, n_fg, overflow, _ = jax.block_until_ready(step(vol))
    _assert_shards_identical(n_fg, "n_foreground")
    _assert_shards_identical(overflow, "overflow")


def test_replication_fence_detects_varying_escape():
    """The fence itself must be able to catch the bug class it guards: a
    deliberately sp-varying scalar returned through a replicated out_spec
    under check_vma=False shows differing per-device bytes."""
    mesh = _mesh(("sp",))

    def body(x):
        # sp-varying scalar (the shard rank), NOT reduced over the mesh —
        # exactly the round-3 overflow-flag bug class
        return jax.lax.axis_index("sp").astype(jnp.float32)

    leaked = jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=P("sp"), out_specs=P(),
            check_vma=False,
        )
    )(jnp.zeros((mesh_axis_sizes(mesh)["sp"],), jnp.float32))
    shards = leaked.addressable_shards
    vals = {float(np.asarray(s.data)) for s in shards}
    assert len(vals) > 1, (
        "expected the un-reduced rank to differ across devices; if this "
        "fails the fence has lost its sensitivity"
    )
    with pytest.raises(AssertionError):
        _assert_shards_identical(leaked, "leaked rank")


# -- entry-point device / compile-cache policy (parallel/mesh.py) -------------


def test_backend_devices_never_hands_back_another_backends_devices():
    """``target="tpu"`` with no TPU raises (naming what jax did find);
    ``"local"`` is the CPU backend's devices and nothing else."""
    with pytest.raises(RuntimeError, match="no TPU devices are visible"):
        backend_devices("tpu")
    assert {d.platform for d in backend_devices("local")} == {"cpu"}
    with pytest.raises(ValueError):
        backend_devices("gpu")


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_is_placed_from_outside(monkeypatch, restore_cache_dir):
    """Where JAX_COMPILATION_CACHE_DIR is set the package sets nothing (jax
    reads it); where it is not, the cache is <checkout>/.jax_cache — a fixed
    path, because the path is part of the cache key."""
    import os

    from cluster_tools_tpu.parallel import mesh as mesh_mod

    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert mesh_mod.configure_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir is None  # untouched

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert mesh_mod.configure_compile_cache() == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(repo, ".jax_cache")


def test_use_cpu_backend_says_so_only_when_it_overrides(caplog):
    from cluster_tools_tpu.parallel import mesh as mesh_mod

    # tests run with jax_platforms=cpu: nothing to override, nothing said
    with caplog.at_level("WARNING", logger=mesh_mod.__name__):
        mesh_mod.use_cpu_backend("target='local'")
    assert not caplog.records
    assert jax.config.jax_platforms == "cpu"
