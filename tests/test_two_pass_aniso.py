"""The two-pass (checkerboard) watershed on an anisotropic flat stack, held
against the benchmark's plain reference at a size the CPU runs.

The cell ``twopass125.volumes`` (``benchmark/configs/
ws_two_pass_cremi_125.json``) cut down: the same workflow through
``cli``'s builder, sampling (10, 1, 1), flat blocks with a halo of the EDT
window in voxels, a ragged last block row on every axis, the same
comparison (``benchmark/comparisons/ws_two_pass.py``) over every block, and
the same reference (``benchmark/reference_two_pass.py``).
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import data, reference as ref, reference_two_pass as ref2
from benchmark.comparisons import ws_two_pass

from .helpers import assert_labels_equivalent

SHAPE, BLOCK, HALO = (29, 40, 72), (8, 16, 32), (2, 16, 16)
SAMPLING, DT_MAX = (10, 1, 1), 16.0
PARAMS = dict(
    two_pass=True, threshold=0.5, sampling=list(SAMPLING), halo=list(HALO),
    dt_max_distance=DT_MAX, block_shape=list(BLOCK), connectivity=1, impl="auto",
    sigma_seeds=0, size_filter=0, device_batch=4,
)


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """One traced ``watershed`` job with ``two_pass`` on a seeded stack,
    under the exact fill as the deployment sets it."""
    from cluster_tools_tpu import cli
    from cluster_tools_tpu.runtime import trace
    from cluster_tools_tpu.runtime.task import build
    from cluster_tools_tpu.utils.volume_utils import file_reader

    root = str(tmp_path_factory.mktemp("two_pass"))
    vol = data.membrane_volume(2147493701, 0, SHAPE, 12)
    path = os.path.join(root, "data.zarr")
    file_reader(path).create_dataset(
        "vol", shape=vol.shape, chunks=BLOCK, dtype="float32")[...] = vol
    tmp = os.path.join(root, "tmp")
    os.makedirs(tmp)
    with open(os.path.join(tmp, "global.config"), "w") as f:
        json.dump({"block_shape": list(BLOCK)}, f)
    was = os.environ.get("CT_FILL_MODE")
    os.environ["CT_FILL_MODE"] = "dense"
    trace.configure(enabled=True, trace_dir=os.path.join(root, "trace"))
    try:
        wf = cli._resolve("watershed")(
            tmp_folder=tmp, config_dir=tmp, max_jobs=4, target="local",
            input_path=path, input_key="vol", output_path=path, output_key="ws",
            **PARAMS)
        ok = build([wf])
        spans = trace._get().snapshot_events()
    finally:
        trace.reset()
        if was is None:
            del os.environ["CT_FILL_MODE"]
        else:
            os.environ["CT_FILL_MODE"] = was
    return dict(ok=ok, vol=vol, tmp=tmp, spans=spans, uid=wf.uid,
                ws=ref.read_zarr(path, "ws"))


def test_the_job_completes_with_nothing_absorbed(job):
    from benchmark import run

    assert job["ok"]
    assert run.absorbed_failures(job["tmp"]) == []


def test_every_count_of_the_comparison_is_zero_on_every_block(job):
    cfg = {"params": PARAMS}
    n_blocks = len(ref2.blocks_of(SHAPE, BLOCK))
    counts = ws_two_pass.check_job(job["vol"], job["ws"], cfg,
                                   np.random.default_rng(0), 2 * n_blocks)
    assert set(counts) == set(ws_two_pass.LIMITS) - {"labels_missing"}
    assert counts == {k: 0 for k in counts}, counts


def test_the_stored_labels_are_the_references_partition(job):
    want = ref2.reference_labels(job["vol"], block=BLOCK, halo=HALO, threshold=0.5,
                                 sampling=SAMPLING, dt_max_distance=DT_MAX)
    assert_labels_equivalent(job["ws"], want)
    # and a new label names the block it was born in, here as there
    n_outer = int(np.prod(ref2.outer_shape(BLOCK, HALO)))
    np.testing.assert_array_equal(ref2.block_of_label(job["ws"], n_outer),
                                  ref2.block_of_label(want, n_outer))


def test_fragments_continue_across_block_faces_with_one_id(job):
    """What two passes are for: an odd block holds labels born in its even
    neighbours, an even block only its own."""
    n_outer = int(np.prod(ref2.outer_shape(BLOCK, HALO)))
    carried = 0
    for number, pos in ref2.blocks_of(SHAPE, BLOCK):
        lo, hi, _, _ = ref2.unit_bounds(pos, SHAPE, BLOCK, HALO)
        born = np.unique(ref2.block_of_label(
            job["ws"][tuple(slice(a, b) for a, b in zip(lo, hi))], n_outer))
        if ref2.parity_of(pos) == 0:
            assert born.tolist() == [number]
        else:
            carried += int(np.count_nonzero(born != number))
    assert carried > 0


def test_the_manifest_and_io_metrics_carry_each_passes_counters(job):
    with open(os.path.join(job["tmp"], f"{job['uid']}.success.json")) as f:
        passes = json.load(f)["passes"]
    assert list(passes) == ["watershed", "two_pass_watershed"]
    n_outer = int(np.prod(ref2.outer_shape(BLOCK, HALO)))
    for name, doc in passes.items():
        assert doc["n_blocks"] == 18 and doc["overflow_blocks"] == []
        assert doc["dispatches"] >= 1
        assert doc["outer_voxels"] == 18 * n_outer
        assert doc["padded_voxels"] >= doc["outer_voxels"] > doc["inner_voxels"]
    assert sum(d["inner_voxels"] for d in passes.values()) == int(np.prod(SHAPE))
    assert "n_ext_labels" not in passes["watershed"]
    assert passes["two_pass_watershed"]["n_ext_labels"] > 0
    with open(os.path.join(job["tmp"], "io_metrics.json")) as f:
        assert json.load(f)["tasks"][job["uid"]]["passes"] == passes


def test_the_passes_and_pass_twos_host_work_are_spans(job):
    by_name = {}
    for ev in job["spans"]:
        if ev.get("ph") == "X":
            by_name.setdefault(ev["name"], []).append(ev.get("args") or {})
    assert [a["parity"] for a in by_name["ws.pass"]] == [0, 1]
    for args in by_name["ws.pass"]:
        assert args["n_blocks"] == 18 and args["lanes"] >= 18
        assert args["padded_voxels"] >= args["outer_voxels"] > args["inner_voxels"]
    assert len(by_name["ws2.ext_seeds"]) == len(by_name["ws2.relabel"]) == 18
    assert all(a["n_ext"] > 0 and a["nbytes"] > 0 for a in by_name["ws2.ext_seeds"])


def test_a_pass_carries_one_work_record_a_real_lane(job):
    """The kernels' work records (``ops/work.py``) ride the executor's
    plumbing beside the labels: the ``ws.pass`` span keeps one row a block
    that ran, none for a padding lane, and the pass's manifest their sum."""
    from cluster_tools_tpu.ops import work

    passes = [ev["args"] for ev in job["spans"]
              if ev.get("ph") == "X" and ev["name"] == "ws.pass"]
    with open(os.path.join(job["tmp"], f"{job['uid']}.success.json")) as f:
        manifest = json.load(f)["passes"]
    blocks = dict(ref2.blocks_of(SHAPE, BLOCK))
    for args, doc in zip(passes, manifest.values()):
        rows = args["work"]
        of_parity = sorted(n for n, pos in blocks.items()
                           if ref2.parity_of(pos) == args["parity"])
        assert [r["block"] for r in rows] == of_parity and len(rows) == 18
        assert args["lanes"] > len(rows)          # the padding lanes are gone
        assert all(set(work.NAMES) <= set(r) for r in rows)
        assert all(r[work.FLOW_CHASE_HOPS] >= 0 and r[work.FILL_ROUNDS] >= 1
                   and r[work.CAP_EXIT] > 0 for r in rows)
        assert not any(r[n] > 0 for r in rows for n in work.OVER)
        assert doc["work"] == work.total(rows)
        assert doc["work"][work.FLOW_EXITS] == sum(r[work.FLOW_EXITS] for r in rows)
        assert doc["work"][work.CAP_EXIT] == rows[0][work.CAP_EXIT]


def test_the_checkerboard_refuses_two_d_and_agglomeration_in_one_place():
    from cluster_tools_tpu.tasks import watershed as ws_mod

    for bad in ({"two_d": True}, {"agglomerate_threshold": 0.5}):
        with pytest.raises(NotImplementedError, match="two-pass"):
            ws_mod._refuse_checkerboard_hybrids(bad)
        wf = ws_mod.WatershedWorkflow(
            tmp_folder="/nonexistent", config_dir="", target="local", two_pass=True,
            input_path="x", input_key="x", output_path="x", output_key="x", **bad)
        with pytest.raises(NotImplementedError, match="two-pass"):
            wf.requires()
    ws_mod._refuse_checkerboard_hybrids({"two_d": False, "impl": "auto"})


# --------------------------------------------------------------------------
# the kernels under anisotropic sampling
# --------------------------------------------------------------------------


def _unit(seed, shape=(12, 32, 128)):
    """A boundary map with external seeds in a halo of (2, 8, 8), as pass
    two's load hands them to the kernel."""
    vol = data.membrane_volume(seed, 0, shape, 6)
    ext = np.zeros(shape, np.int32)
    rng = np.random.default_rng(seed)
    ext[:2] = rng.integers(1, 9, (2,) + shape[1:])
    ext[:, :8] = rng.integers(9, 17, (shape[0], 8, shape[2]))
    ext[:, :, -8:] = rng.integers(17, 25, shape[:2] + (8,))
    return vol, ext


@pytest.mark.parametrize("seed", [3, 2147493702])
def test_seeded_kernel_pallas_and_xla_twins_are_bit_identical(seed):
    from cluster_tools_tpu.ops.tile_ws import dt_watershed_seeded_tiled

    vol, ext = _unit(seed)
    kw = dict(threshold=0.5, sampling=(10.0, 1.0, 1.0), dt_max_distance=8.0,
              fill_mode="dense")
    got = {}
    for impl, interpret in (("xla", False), ("pallas", True)):
        lab, ovf, _ = dt_watershed_seeded_tiled(
            jnp.asarray(vol), jnp.asarray(ext), impl=impl, interpret=interpret, **kw)
        assert not bool(ovf)
        got[impl] = np.asarray(lab)
    np.testing.assert_array_equal(got["xla"], got["pallas"])
    # external seeds keep their id (+N) where they lie, and dominate
    n = vol.size
    np.testing.assert_array_equal(got["xla"][ext > 0], ext[ext > 0] + n)


@pytest.mark.parametrize("seed", [3, 2147493702])
def test_seeded_kernel_floods_as_the_reference_does(seed):
    from cluster_tools_tpu.ops.tile_ws import dt_watershed_seeded_tiled

    vol, ext = _unit(seed)
    lab, _, _ = dt_watershed_seeded_tiled(
        jnp.asarray(vol), jnp.asarray(ext), impl="xla", threshold=0.5,
        sampling=(10.0, 1.0, 1.0), dt_max_distance=8.0, fill_mode="dense")
    lab = np.asarray(lab)
    tree, _, n_int, _ = ref2.flood_unit(
        vol, ext.astype(np.uint64), threshold=0.5, sampling=SAMPLING,
        radii=ref2.window_radii(8.0, SAMPLING))
    # external seed k is n_int + k there and N + k here; the rest a bijection
    np.testing.assert_array_equal(tree > n_int, lab > vol.size)
    np.testing.assert_array_equal((tree - n_int)[tree > n_int],
                                  (lab - vol.size)[lab > vol.size])
    assert_labels_equivalent(np.where(lab > vol.size, 0, lab),
                             np.where(tree > n_int, 0, tree))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_edt_under_whole_number_sampling_is_in_exact_integers(impl):
    from cluster_tools_tpu.ops.edt import _dt_squared_impl

    vol = data.membrane_volume(7, 0, (16, 40, 128), 5)
    fg = vol < np.float32(0.5)
    radii = ref2.window_radii(32.0, SAMPLING)
    assert radii == (4, 32, 32)
    got = np.asarray(_dt_squared_impl(
        jnp.asarray(fg), tuple(float(s) for s in SAMPLING), radii, impl=impl,
        interpret=(impl == "pallas")))
    want = ref2.windowed_edt_sq(fg, SAMPLING, radii)
    near = want < ref._FAR
    np.testing.assert_array_equal(got[near], want[near].astype(np.float32))
    assert (got[~near] >= 1e12).all()
    # 100 dz^2 + dy^2 + dx^2: one plane off counts a hundred in-plane steps
    assert 100 in want and 1 in want
