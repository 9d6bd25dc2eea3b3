"""The executor's sharded sweep programs, kept across jobs
(``runtime/executor.py::_KeptSweepProgram`` over ``parallel/step_cache.py``):
two two-pass jobs in one process, what a changed parameter does to the key,
which kernels stay in their executor's own cache, and every way out of the
store ending in a build.

One device, so that a pass of the 16-block grid is one full, dense sharded
batch of 8 lanes as on the chip (the conftest mesh's 8 devices would make it
a partial, ragged one).  The module keeps JAX's persistent compile cache in
a directory of its own and puts everything back afterwards.
"""

import json
import os
import pickle
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import data
from cluster_tools_tpu.parallel import step_cache
from cluster_tools_tpu.runtime import executor as executor_mod
from cluster_tools_tpu.runtime import trace
from cluster_tools_tpu.runtime.executor import BlockwiseExecutor, kernel_identity
from cluster_tools_tpu.utils.volume_utils import Blocking, file_reader

from .helpers import programs_built_here

SHAPE, BLOCK, HALO = (32, 32, 64), (8, 16, 32), (2, 8, 8)
PARAMS = dict(threshold=0.5, sampling=[10, 1, 1], halo=list(HALO), dt_max_distance=16.0,
              block_shape=list(BLOCK), impl="auto", device_batch=4)
PROGRAMS = ("watershed", "two_pass_watershed")


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs")
    config = {n: getattr(jax.config, n) for n in names}
    cache = str(tmp_path_factory.mktemp("jax_cache"))
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_enable_compilation_cache", True)
    # JAX itself keeps nothing here, so a build compiles and can be stored
    # (a program its cache handed over is not stored on the CPU)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    cc.reset_cache()
    step_cache.forget()
    with pytest.MonkeyPatch.context() as patch:
        one = jax.devices("cpu")[:1]
        patch.setattr(executor_mod, "get_devices", lambda *a, **k: one)
        patch.setenv("CT_FILL_MODE", "dense")
        yield cache
    for n, value in config.items():
        jax.config.update(n, value)
    cc.reset_cache()
    step_cache.forget()


@pytest.fixture(scope="module")
def stack(cache_dir, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("stack"))
    path = os.path.join(root, "data.zarr")
    vol = data.membrane_volume(2147493701, 0, SHAPE, 12)
    file_reader(path).create_dataset(
        "vol", shape=SHAPE, chunks=BLOCK, dtype="float32")[...] = vol
    return root, path


def run_job(stack, tag, workflow="watershed", **params):
    """One job of ``cli``'s ``watershed`` workflow under a fresh output key:
    its labels, each pass's manifest block and ``io_metrics`` counters, and
    the spans and compile requests it made."""
    from cluster_tools_tpu import cli
    from cluster_tools_tpu.runtime.task import build

    root, path = stack
    tmp = os.path.join(root, f"tmp_{tag}")
    os.makedirs(tmp)
    with open(os.path.join(tmp, "global.config"), "w") as f:
        json.dump({"block_shape": list(BLOCK)}, f)
    trace.configure(enabled=True)
    try:
        wf = cli._resolve(workflow)(
            tmp_folder=tmp, config_dir=tmp, max_jobs=4, target="local",
            input_path=path, input_key="vol", output_path=path,
            output_key=f"ws_{tag}", **dict(PARAMS, **params))
        assert build([wf]), f"job {tag} failed (see logs)"
        spans = [e["name"] for e in trace._get().snapshot_events() if e["ph"] == "X"]
    finally:
        trace.reset()
    with open(os.path.join(tmp, "io_metrics.json")) as f:
        metrics = {uid.split(".")[0]: m for uid, m in json.load(f)["tasks"].items()}
    with open(os.path.join(tmp, f"{wf.uid}.success.json")) as f:
        manifest = json.load(f)
    passes = manifest.get("passes") or {"watershed": manifest}
    return dict(ws=file_reader(path, "r")[f"ws_{tag}"][...], metrics=metrics,
                passes={name: doc["step_cache"] for name, doc in passes.items()},
                spans=spans)


@pytest.fixture(scope="module")
def three_jobs(stack):
    """A build, a process hit and (process level dropped) a store hit of
    both passes' programs; each job writes another output key."""
    step_cache.forget()
    built = run_job(stack, "built", two_pass=True)
    process = run_job(stack, "process", two_pass=True)
    step_cache.forget()
    store = run_job(stack, "store", two_pass=True)
    return built, process, store


@pytest.mark.parametrize("name", PROGRAMS)
def test_first_job_builds_and_stores_each_program(three_jobs, cache_dir, name):
    built = three_jobs[0]
    block = built["passes"][name]
    assert block["from"] == "built" and block["fallback"] is None
    assert os.path.getsize(os.path.join(cache_dir, "steps", block["key"])) == \
        block["store_bytes"] > 0
    assert built["metrics"][name]["step_cache"] == {
        "process_hits": 0, "store_hits": 0, "builds": 1, "fallbacks": 0}
    assert built["metrics"][name]["compile"]["requests"] >= 1
    for span in ("executor.program_build", "executor.program_store", "jax.trace"):
        assert span in built["spans"], span


@pytest.mark.parametrize("name", PROGRAMS)
def test_second_job_takes_both_programs_from_the_process(three_jobs, name):
    built, process, _ = three_jobs
    block = process["passes"][name]
    assert block == {"from": "process", "key": built["passes"][name]["key"],
                     "load_s": 0.0, "store_bytes": 0, "fallback": None}
    assert process["metrics"][name]["step_cache"] == {
        "process_hits": 1, "store_hits": 0, "builds": 0, "fallbacks": 0}
    # no compile request: nothing traced, lowered, compiled or read back
    assert "compile" not in process["metrics"][name]
    assert not {s for s in process["spans"]
                if s.startswith(("jax.", "executor.program_"))}


@pytest.mark.parametrize("name", PROGRAMS)
def test_a_new_process_level_reads_each_program_from_the_store(three_jobs, name):
    built, _, store = three_jobs
    block = store["passes"][name]
    assert block["from"] == "store" and block["fallback"] is None
    assert block["key"] == built["passes"][name]["key"] and block["load_s"] > 0
    assert store["metrics"][name]["step_cache"]["store_hits"] == 1
    assert "compile" not in store["metrics"][name]
    assert "executor.program_load" in store["spans"]
    assert not {"jax.trace", "executor.program_build"} & set(store["spans"])


def test_labels_equal_a_fresh_run(three_jobs, stack):
    """Whatever level gave the programs, the labels are those of a job that
    built them with no process level and no store."""
    with programs_built_here():
        fresh = run_job(stack, "fresh", two_pass=True)
    assert {b["from"] for b in fresh["passes"].values()} == {"built"}
    assert {b["store_bytes"] for b in fresh["passes"].values()} == {0}
    for job in three_jobs:
        assert np.array_equal(job["ws"], fresh["ws"])


@pytest.mark.parametrize("change, source", [
    ({}, "process"),
    ({"threshold": 0.4}, "built"),
    ({"exit_cap": 4096}, "built"),
    ({"min_seed_distance": 1.0}, "built"),
], ids=["same", "threshold", "exit_cap", "min_seed_distance"])
def test_what_the_kernel_reads_is_its_key(three_jobs, stack, change, source):
    """Pass one's program, asked for again by a single-pass job of the same
    parity: found where the parameters are the ones it read, built anew
    where one of them changed."""
    tag = "_".join(["p"] + [f"{k}{v}" for k, v in change.items()])
    step_cache.forget()
    run_job(stack, "again" + tag, pass_parity=0)   # pass one's program, back in
    job = run_job(stack, tag, pass_parity=0, **change)
    block = job["passes"]["watershed"]
    assert block["from"] == source
    same_key = block["key"] == three_jobs[0]["passes"]["watershed"]["key"]
    assert same_key == (source == "process")


# -- which kernels are kept, at the executor ----------------------------------


def _blocks():
    blocking = Blocking((32, 32, 32), (16, 16, 16))
    return [blocking.get_block(i) for i in range(blocking.n_blocks)]


def _sweep(kernel):
    """One sharded sweep of ``kernel`` over a grid of 8 blocks (one full
    batch on one device): the summary, the executor and the output."""
    vol = np.random.default_rng(3).random((32, 32, 32), np.float32)
    out = np.zeros_like(vol)

    def store(b, raw):
        out[b.bb] = np.asarray(raw)

    ex = BlockwiseExecutor(target="local")
    summary = ex.map_blocks(kernel, _blocks(), lambda b: (vol[b.bb],), store,
                            sweep_mode="sharded", sharded_batch=8, task_name="kept")
    return summary, ex, out


def plain(b):
    return jnp.where(b < 0.5, b, jnp.float32(2.0))


def _capturing_an_array():
    table = jnp.arange(4, dtype=jnp.float32)

    def kernel(b):
        return b + table[1]

    return kernel


@pytest.mark.parametrize("make, kept", [
    (lambda: plain, True), (_capturing_an_array, False)],
    ids=["plain_values", "captures_an_array"])
def test_a_kernel_that_captures_an_array_stays_in_its_executor(cache_dir, make, kept):
    kernel = make()
    assert (kernel_identity(kernel) is not None) == kept
    before = step_cache.totals()
    summary, ex, out = _sweep(kernel)
    moved = step_cache.delta(before)
    assert summary["sweep_mode"] == "sharded" and summary["n_dispatches"] == 1
    assert ("program" in summary) == kept
    assert bool(moved) == kept
    in_executor = {key[0] for _, key in ex._program_cache._entries}
    assert ("sharded" in in_executor) != kept
    vol = np.random.default_rng(3).random((32, 32, 32), np.float32)
    np.testing.assert_array_equal(out, np.asarray(kernel(jnp.asarray(vol))))


# -- every way out of the store is the build ----------------------------------


def _flip_a_byte_of_the_executable(entry):
    packed = bytearray(entry["executable"])
    packed[len(packed) // 2] ^= 0xFF
    entry["executable"] = bytes(packed)
    return "damaged:crc32"


def _one_field_of_the_document_differs(entry):
    entry["key_document"]["inputs"][0]["dtype"] = "float16"
    return "key_mismatch:inputs"


@pytest.mark.parametrize("damage", [_flip_a_byte_of_the_executable,
                                    _one_field_of_the_document_differs],
                         ids=lambda f: f.__name__.strip("_"))
def test_an_entry_that_cannot_be_trusted_costs_a_build(cache_dir, damage):
    shutil.rmtree(os.path.join(cache_dir, "steps"), ignore_errors=True)
    step_cache.forget()
    sound, _, want = _sweep(plain)
    assert sound["program"]["from"] == "built"
    path = os.path.join(cache_dir, "steps", sound["program"]["key"])
    with open(path, "rb") as f:
        entry = pickle.loads(f.read())
    reason = damage(entry)
    with open(path, "wb") as f:
        f.write(pickle.dumps(entry))
    step_cache.forget()
    before = step_cache.totals()
    summary, _, got = _sweep(plain)
    info = summary["program"]
    assert info["from"] == "built" and info["fallback"].startswith(reason), info
    assert step_cache.delta(before) == {
        "process_hits": 0, "store_hits": 0, "builds": 1, "fallbacks": 1}
    np.testing.assert_array_equal(got, want)
    # the entry is written again: the next process level reads it
    step_cache.forget()
    summary, _, got = _sweep(plain)
    assert summary["program"]["from"] == "store"
    np.testing.assert_array_equal(got, want)


def test_identity_digest_orders_a_frozen_set():
    from cluster_tools_tpu.runtime.executor import identity_digest

    a = ("fn", frozenset({("x", 1), ("y", 2), "z"}))
    b = ("fn", frozenset({"z", ("y", 2), ("x", 1)}))
    assert identity_digest(a) == identity_digest(b)
    assert identity_digest(a) != identity_digest(("fn", frozenset({"z"})))


# -- a replicated operand (a network's weights), on every route ---------------


def with_operand(op, b):
    return b * op["scale"] + op["shift"][1]


def _operand(scale=3.0):
    return {"scale": jnp.float32(scale), "shift": jnp.arange(4, dtype=jnp.float32)}


#: route -> (map_blocks arguments, blocks swept, faults injected)
OPERAND_ROUTES = {
    "sharded": (dict(sweep_mode="sharded"), 8, None),
    "vmap": (dict(sweep_mode="per_block"), 8, None),
    "ragged": (dict(sweep_mode="sharded"), 5, None),
    "per_block_fallback": (dict(sweep_mode="sharded"), 8, {
        "site": "dispatch", "kind": "oom", "blocks": "first", "fail_attempts": 1}),
    "split_sub_blocks": (dict(sweep_mode="per_block"), 8, {
        "site": "load", "kind": "oom", "min_voxels": 1000, "fail_attempts": 10**6}),
    "split_ragged": (dict(sweep_mode="sharded"), 8, {
        "site": "load", "kind": "oom", "min_voxels": 1000, "fail_attempts": 10**6}),
}


def _operand_sweep(kernel, operand, route, inject=None):
    """``kernel(operand, block)`` over the 8-block grid (or its first
    blocks) along ``route``: the summary, the executor, what was stored and
    what was swept."""
    kw, n_blocks, fault = OPERAND_ROUTES[route]
    blocks = executor_mod.morton_order(_blocks())[:n_blocks]
    if fault is not None:
        fault = dict(fault)
        if fault.get("blocks") == "first":
            fault["blocks"] = [int(blocks[0].block_id)]
        inject({"seed": 3, "faults": [fault]})
    vol = np.random.default_rng(3).random((32, 32, 32), np.float32)
    out = np.full_like(vol, np.nan)

    def store(b, raw):
        out[b.bb] = np.asarray(raw)[b.inner_in_outer_bb]

    ex = BlockwiseExecutor(target="local", backoff_base=1e-4)
    summary = ex.map_blocks(
        kernel, blocks, lambda b: (vol[b.outer_bb],), store, sharded_batch=8,
        task_name="operand", splittable=True, split_halo=(0, 0, 0),
        min_block_shape=(4, 4, 4), degrade_wait_s=0.01,
        replicated_operand=operand, **kw)
    swept = np.zeros(vol.shape, bool)
    for b in blocks:
        swept[b.bb] = True
    return summary, ex, out, vol, swept


@pytest.mark.parametrize("route", list(OPERAND_ROUTES))
def test_a_replicated_operand_reaches_the_kernel_on_every_route(cache_dir, inject, route):
    summary, _, out, vol, swept = _operand_sweep(with_operand, _operand(), route, inject)
    assert summary["n_failed"] == 0
    np.testing.assert_array_equal(out[swept], vol[swept] * np.float32(3.0) + np.float32(1.0))
    assert np.isnan(out[~swept]).all()
    shown = {
        "sharded": summary.get("program") is not None,
        "vmap": summary["sweep_mode"] == "per_block",
        "ragged": summary.get("n_ragged_batches") == 1,
        "per_block_fallback": summary.get("n_unsharded") == 8,
        "split_sub_blocks": summary.get("n_split") == 8,
        "split_ragged": summary.get("n_split") == 8 and summary.get("n_ragged_batches"),
    }[route]
    assert shown, summary


def test_another_operand_value_runs_the_same_kept_program(cache_dir):
    """The operand is the program's argument: a sweep with other values
    finds the program the first one left in the process, and computes with
    its own values."""
    step_cache.forget()
    first, *_ = _operand_sweep(with_operand, _operand(3.0), "sharded")
    second, _, out, vol, _ = _operand_sweep(with_operand, _operand(-2.0), "sharded")
    assert first["program"]["from"] in ("built", "store")
    assert second["program"]["from"] == "process"
    assert second["program"]["key"] == first["program"]["key"]
    np.testing.assert_array_equal(out, vol * np.float32(-2.0) + np.float32(1.0))


def test_an_operand_kernel_that_captures_an_array_stays_in_its_executor(cache_dir):
    table = jnp.arange(4, dtype=jnp.float32)

    def kernel(op, b):
        return b * op["scale"] + table[1]

    assert kernel_identity(kernel) is None
    before = step_cache.totals()
    summary, ex, out, vol, _ = _operand_sweep(kernel, _operand(), "sharded")
    assert "program" not in summary and not step_cache.delta(before)
    in_executor = {key for _, key in ex._program_cache._entries}
    dev_key = tuple(d.id for d in ex.devices)
    assert ("sharded", 8, dev_key, "replicated") in in_executor
    np.testing.assert_array_equal(out, vol * np.float32(3.0) + np.float32(1.0))


def test_the_key_holds_the_operand_by_shape_never_by_value(cache_dir):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("blocks",))
    stacked = jax.device_put(np.zeros((8, 4, 4, 4), np.float32),
                             NamedSharding(mesh, P("blocks")))

    def document(operand):
        placed = jax.device_put(operand, NamedSharding(mesh, P()))
        return step_cache.key_document(mesh, (placed, stacked), "sharded",
                                       {"kernel": "k", "batch": 8})

    assert document(_operand(3.0)) == document(_operand(-2.0))
    assert document(_operand()) != document({"scale": jnp.float32(3.0)})
    assert len(document(_operand())["inputs"]) == 3
    assert "in_tree" in document(_operand())
    assert "in_tree" not in step_cache.key_document(
        mesh, (stacked,), "sharded", {"kernel": "k", "batch": 8})


def test_a_sweep_without_an_operand_lowers_the_program_it_lowered_before(cache_dir):
    """A kernel given no operand gets the sharded program it got before the
    operand existed: ``jit(shard_map(vmap(kernel)))``, named for the
    kernel."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cluster_tools_tpu.compat import shard_map
    from cluster_tools_tpu.parallel.batch_shard import batched_shard_map

    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("blocks",))
    x = jax.ShapeDtypeStruct((8, 16, 16, 16), jnp.float32,
                             sharding=NamedSharding(mesh, P("blocks")))

    def sharded_plain(*args):
        return jax.vmap(plain)(*args)

    before = jax.jit(shard_map(sharded_plain, mesh=mesh, in_specs=P("blocks"),
                               out_specs=P("blocks"), check_vma=False))
    assert batched_shard_map(plain, mesh, 8).lower(x).as_text() == \
        before.lower(x).as_text()


def test_a_package_function_swapped_in_memory_enters_the_key(cache_dir, monkeypatch):
    """A control or a test that swaps one of the package's top-level
    functions (the U-Net's ``_operand`` at float8) gets a key of its own,
    one per replacement, and the sound key back once it is undone."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cluster_tools_tpu.models import unet_gcr

    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("blocks",))
    stacked = jax.device_put(np.zeros((8, 4, 4, 4), np.float32),
                             NamedSharding(mesh, P("blocks")))

    def document():
        return step_cache.key_document(mesh, (stacked,), "sharded",
                                       {"kernel": "k", "batch": 8})

    def swapped(doc):
        return [r for r in doc.get("replaced", []) if r not in sound.get("replaced", [])]

    sound = document()
    monkeypatch.setattr(unet_gcr, "_operand",
                        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16))
    float8 = document()
    monkeypatch.setattr(unet_gcr, "_operand",
                        lambda a: a.astype(jnp.float16).astype(jnp.bfloat16))
    float16 = document()
    monkeypatch.undo()
    assert [r[:2] for r in swapped(float8)] == [["cluster_tools_tpu.models.unet_gcr",
                                                 "_operand"]]
    assert swapped(float8)[0][2]["qualname"].endswith("<lambda>")
    assert step_cache.digest(float8) != step_cache.digest(float16) != step_cache.digest(sound)
    assert document() == sound
    # a decorated function is seen through ``__wrapped__``: not a swap
    assert not any(r[1] == "package_digest" for r in sound.get("replaced", []))
