"""The compiled mesh step, kept (``parallel/step_cache.py``): its key, the
process level, the store beside the compile cache, and every way out of the
store ending in the build.

The module keeps JAX's persistent compile cache in a directory of its own
(``--dist loadfile`` keeps a file in one process; the worker goes on to
other files, so everything is put back afterwards).
"""

import glob
import json
import os
import pickle
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from cluster_tools_tpu.parallel import step_cache
from cluster_tools_tpu.parallel.mesh import make_mesh
from cluster_tools_tpu.parallel.pipeline import make_ws_ccl_step
from cluster_tools_tpu.runtime import trace
from cluster_tools_tpu.runtime.task import build
from cluster_tools_tpu.utils.volume_utils import file_reader

SHAPE = (32, 32, 32)
BUILD = dict(halo=4, threshold=0.5, sp_axis="sp", dt_max_distance=4.0,
             min_seed_distance=0.0, max_labels_per_shard=None, impl="auto",
             exact_edt=False, stitch_ws_threshold=None)


@pytest.fixture(scope="module", autouse=True)
def cache_dir(tmp_path_factory):
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    config = {n: getattr(jax.config, n) for n in names}
    cache = str(tmp_path_factory.mktemp("jax_cache"))
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_enable_compilation_cache", True)
    # JAX itself keeps no program here, so every build compiles (a step that
    # its cache hands over is another case: the last test of (d))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    cc.reset_cache()
    step_cache.forget()
    yield cache
    for n, value in config.items():
        jax.config.update(n, value)
    cc.reset_cache()
    step_cache.forget()


@pytest.fixture
def steps(cache_dir):
    """The store's directory, empty, and no ready step in the process."""
    directory = os.path.join(cache_dir, "steps")
    shutil.rmtree(directory, ignore_errors=True)
    step_cache.forget()
    return directory


def mesh_of(ids, names=("dp", "sp")):
    devices = [jax.devices("cpu")[i] for i in ids]
    return make_mesh(axis_names=names, grid=(1, len(ids)), devices=devices)


def input_of(mesh, shape=(1,) + SHAPE, dtype=np.float32):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, PartitionSpec(*mesh.axis_names)))


def volume(mesh, seed=7):
    vol = np.random.default_rng(seed).random((1,) + SHAPE).astype(np.float32)
    return jax.device_put(vol, input_of(mesh).sharding)


def labels(step, x):
    ws, cc, _, overflow, _ = jax.block_until_ready(step(x))
    assert not bool(overflow)
    return np.asarray(ws), np.asarray(cc)


def look_up(mesh, x, build_args=BUILD, builder=make_ws_ccl_step):
    """``step_for`` with the compile requests it caused."""
    snap = trace.compile_snapshot()
    step, info = step_cache.step_for(mesh, x, "fused", builder, build_args)
    return step, info, trace.compile_delta(snap)


def tiny_step(mesh, **args):
    """A program of four operations with the step's outputs, for what does
    not depend on the program: it compiles in a tenth of a second."""
    def step(x):
        fg = x < args["threshold"]
        ids = jnp.cumsum(fg.astype(jnp.int32), axis=-1)
        return (jnp.where(fg, ids, 0), fg.astype(jnp.int32), fg.sum(),
                jnp.zeros((), bool), jnp.zeros((1, 1, 1), jnp.int32))

    return jax.jit(step)


# -- (a) the key --------------------------------------------------------------


def key_of(mesh=None, x=None, execution="fused", build=BUILD, root=None):
    mesh = mesh_of((0, 1, 2, 3)) if mesh is None else mesh
    x = input_of(mesh) if x is None else x
    root = root or step_cache.PACKAGE_ROOT
    return step_cache.digest(step_cache.key_document(mesh, x, execution, build, root))


CHANGED_BUILD = dict(halo=8, threshold=0.25, sp_axis="spz", dt_max_distance=8.0,
                     min_seed_distance=1.5, max_labels_per_shard=4096, impl="xla",
                     exact_edt=True, stitch_ws_threshold=0.75)


@pytest.mark.parametrize("arg", sorted(BUILD))
def test_key_changes_with_each_builder_argument(arg):
    assert key_of(build=dict(BUILD, **{arg: CHANGED_BUILD[arg]})) != key_of()


def _other_shape():
    mesh = mesh_of((0, 1, 2, 3))
    return dict(mesh=mesh, x=input_of(mesh, shape=(1, 64, 32, 32)))


def _other_dtype():
    mesh = mesh_of((0, 1, 2, 3))
    return dict(mesh=mesh, x=input_of(mesh, dtype=np.float16))


def _other_spec():
    mesh = mesh_of((0, 1, 2, 3))
    return dict(mesh=mesh, x=jax.ShapeDtypeStruct(
        (1,) + SHAPE, np.float32,
        sharding=NamedSharding(mesh, PartitionSpec(None, "sp"))))


@pytest.mark.parametrize("other", [
    _other_shape, _other_dtype, _other_spec,
    lambda: dict(mesh=mesh_of((0, 1))),                       # mesh shape
    lambda: dict(mesh=mesh_of((3, 2, 1, 0))),                 # device order
    lambda: dict(mesh=mesh_of((4, 5, 6, 7))),                 # other devices
    lambda: dict(mesh=mesh_of((0, 1, 2, 3), names=("dp", "spz"))),
    lambda: dict(execution="split"),
], ids=["shape", "dtype", "spec", "mesh_shape", "device_order", "device_ids",
        "axis_names", "execution"])
def test_key_changes_with_the_input_the_mesh_and_the_execution(other):
    assert key_of(**other()) != key_of()


def test_key_changes_with_the_resolved_fill_mode(monkeypatch):
    monkeypatch.setenv("CT_FILL_MODE", "dense")
    dense = key_of()
    monkeypatch.setenv("CT_FILL_MODE", "capacity")
    assert key_of() != dense


@pytest.mark.parametrize("name", ["XLA_FLAGS", "LIBTPU_INIT_ARGS"])
def test_key_changes_with_the_flags_that_reach_the_compiler(monkeypatch, name):
    before = key_of()
    monkeypatch.setenv(name, os.environ.get(name, "") + " --xla_dump_to=/nowhere")
    assert key_of() != before


@pytest.mark.parametrize("name, value", [
    ("jax_enable_x64", True), ("jax_default_matmul_precision", "highest")])
def test_key_changes_with_the_config_that_reaches_the_lowering(name, value):
    before, was = key_of(), getattr(jax.config, name)
    jax.config.update(name, value)
    try:
        assert key_of() != before
    finally:
        jax.config.update(name, was)


def test_key_changes_with_one_byte_of_a_source_file_and_with_nothing_else(tmp_path):
    root = tmp_path / "pkg"
    (root / "ops").mkdir(parents=True)
    (root / "ops" / "kernel.py").write_bytes(b"x = 1\n")
    (root / "__init__.py").write_bytes(b"")
    first = key_of(root=str(root))
    # made once a process: the same root is not read again
    (root / "ops" / "kernel.py").write_bytes(b"x = 2\n")
    assert key_of(root=str(root)) == first
    step_cache.package_digest.cache_clear()
    changed = key_of(root=str(root))
    assert changed != first
    # what is no source does not count; a moved source does
    (root / "notes.txt").write_bytes(b"nothing the compiler sees")
    step_cache.package_digest.cache_clear()
    assert key_of(root=str(root)) == changed
    os.rename(root / "ops" / "kernel.py", root / "kernel.py")
    step_cache.package_digest.cache_clear()
    assert key_of(root=str(root)) != changed


def test_key_is_stable_and_its_document_is_plain_json():
    mesh = mesh_of((0, 1, 2, 3))
    doc = step_cache.key_document(mesh, input_of(mesh), "fused", BUILD)
    assert json.loads(json.dumps(doc)) == doc
    assert step_cache.digest(doc) == key_of() == key_of(mesh=mesh_of((0, 1, 2, 3)))
    assert doc["mesh"] == {"axis_names": ["dp", "sp"], "shape": [1, 4],
                           "device_ids": [0, 1, 2, 3]}
    assert doc["build"] == json.loads(json.dumps(BUILD))
    assert set(doc["lowering"]) == {"jax_enable_x64", "jax_default_matmul_precision",
                                    "XLA_FLAGS", "LIBTPU_INIT_ARGS"}
    assert doc["jax"] == jax.__version__ and doc["platform"] == "cpu"
    # an array and its description give the same key
    assert key_of(mesh=mesh, x=volume(mesh)) == key_of()


# -- the task: process level, store level, split ------------------------------


def run_fused(root, tag, **params):
    """One fused job on a fixed volume over every CPU device: its labels,
    its manifest's ``step_cache`` block and its ``io_metrics`` entry."""
    from cluster_tools_tpu.tasks.fused import FusedSegmentationLocal

    tmp = os.path.join(root, f"tmp_{tag}")
    os.makedirs(tmp)
    with open(os.path.join(tmp, "global.config"), "w") as f:
        json.dump({}, f)
    path = os.path.join(root, f"{tag}.zarr")
    vol = np.random.default_rng(7).random(SHAPE).astype(np.float32)
    file_reader(path).create_dataset(
        "b", shape=SHAPE, chunks=(16, 16, 16), dtype="float32")[...] = vol
    config = dict(threshold=0.5, halo=4, block_shape=[16, 16, 16])
    config.update(params)
    trace.configure(enabled=True)
    try:
        task = FusedSegmentationLocal(
            tmp_folder=tmp, config_dir=tmp, max_jobs=1, input_path=path,
            input_key="b", output_path=path, ws_key="ws", cc_key="cc", **config)
        assert build([task]), "fused task failed (see logs)"
        spans = [e["name"] for e in trace._get().snapshot_events() if e["ph"] == "X"]
    finally:
        trace.reset()
    (mf,) = glob.glob(os.path.join(tmp, "fused_segmentation.*.success.json"))
    with open(mf) as f:
        manifest = json.load(f)
    with open(os.path.join(tmp, "io_metrics.json")) as f:
        (metrics,) = json.load(f)["tasks"].values()
    r = file_reader(path, "r")
    return dict(ws=r["ws"][...], cc=r["cc"][...], step_cache=manifest["step_cache"],
                metrics=metrics, spans=spans)


def same_labels(a, b):
    return np.array_equal(a["ws"], b["ws"]) and np.array_equal(a["cc"], b["cc"])


@pytest.fixture(scope="module")
def three_jobs(cache_dir, tmp_path_factory):
    """A build, a process hit and (process level dropped) a store hit of one
    step, as a task sees them."""
    root = str(tmp_path_factory.mktemp("jobs"))
    step_cache.forget()
    built = run_fused(root, "built")
    process = run_fused(root, "process")
    step_cache.forget()
    store = run_fused(root, "store")
    return built, process, store


def test_first_job_builds_and_writes_the_entry(three_jobs, cache_dir):
    built = three_jobs[0]
    block = built["step_cache"]
    assert block["from"] == "built" and block["fallback"] is None
    entry = os.path.join(cache_dir, "steps", block["key"])
    assert os.path.getsize(entry) == block["store_bytes"] > 0
    assert built["metrics"]["step_cache"] == {
        "process_hits": 0, "store_hits": 0, "builds": 1, "fallbacks": 0}
    assert "ws_ccl_step" in str(built["metrics"]["compile"]["programs_missed"])
    for name in ("fused.step_load", "fused.step_build", "fused.step_store",
                 "jax.trace", "jax.backend_compile"):
        assert name in built["spans"], name


def test_second_job_of_the_process_takes_the_ready_step(three_jobs):
    built, process, _ = three_jobs
    block = process["step_cache"]
    assert block == dict(block, **{"from": "process", "key": built["step_cache"]["key"],
                                   "load_s": 0.0, "store_bytes": 0, "fallback": None})
    assert process["metrics"]["step_cache"]["process_hits"] == 1
    # nothing traced, lowered, compiled or read back for it
    assert "compile" not in process["metrics"]
    assert not {"jax.trace", "jax.lower", "jax.backend_compile", "fused.step_load",
                "fused.step_build", "fused.step_store"} & set(process["spans"])
    assert "fused.dispatch" in process["spans"]
    assert same_labels(process, built)


def test_a_new_process_level_reads_the_step_from_the_store(three_jobs):
    built, _, store = three_jobs
    block = store["step_cache"]
    assert block["from"] == "store" and block["fallback"] is None
    assert block["key"] == built["step_cache"]["key"]
    assert block["store_bytes"] == built["step_cache"]["store_bytes"]
    assert block["load_s"] > 0
    assert store["metrics"]["step_cache"]["store_hits"] == 1
    assert "compile" not in store["metrics"]          # zero compile requests
    assert "fused.step_load" in store["spans"]
    assert not {"jax.trace", "fused.step_build", "fused.step_store"} & set(store["spans"])
    assert same_labels(store, built)
    assert block["totals"]["store_hits"] >= 1 and block["totals"]["builds"] >= 1


def test_labels_equal_a_build_with_the_cache_bypassed(three_jobs):
    """The step as the parent commit made it: a fresh ``jax.jit`` called."""
    mesh = mesh_of(range(8))
    x = volume(mesh)
    ws, cc = labels(make_ws_ccl_step(mesh, **BUILD), x)
    for job in three_jobs:
        # the task stores uint64; the step's own labels are narrower
        assert np.array_equal(job["ws"], ws[0].astype(np.uint64))
        assert np.array_equal(job["cc"], cc[0].astype(np.uint64))


def test_process_level_keeps_two_steps(steps):
    mesh = mesh_of((0,))
    x = volume(mesh)
    assert step_cache.PROCESS_STEPS == 2
    sources = []
    for threshold in (0.5, 0.6, 0.7, 0.5):
        _, info, _ = look_up(mesh, x, dict(BUILD, threshold=threshold), tiny_step)
        sources.append(info["from"])
    # the third step pushed the first out: it comes back from the store
    assert sources == ["built", "built", "built", "store"]
    _, info, _ = look_up(mesh, x, dict(BUILD, threshold=0.7), tiny_step)
    assert info["from"] == "process"
    assert step_cache._process_level().stats()["programs"] == 2


def test_split_execution_keeps_the_process_level_only(three_jobs, steps, tmp_path):
    first = run_fused(str(tmp_path), "split_a", execution="split")
    second = run_fused(str(tmp_path), "split_b", execution="split")
    assert first["step_cache"]["from"] == "built"
    assert first["step_cache"]["store_bytes"] == 0 and not os.path.exists(steps)
    assert "fused.step_load" not in first["spans"]
    assert second["step_cache"]["from"] == "process"
    assert first["step_cache"]["key"] != three_jobs[0]["step_cache"]["key"]
    assert same_labels(first, second) and same_labels(first, three_jobs[0])


# -- (c) the store on a 1x1 and a 1x4 mesh ------------------------------------


@pytest.mark.parametrize("ids", [(0,), (0, 1, 2, 3)], ids=["1x1", "1x4"])
def test_store_round_trip_is_bit_identical(steps, ids):
    mesh = mesh_of(ids)
    x = volume(mesh)
    step, info, _ = look_up(mesh, x)
    assert info["from"] == "built" and info["fallback"] is None
    built = labels(step, x)
    step_cache.forget()
    step, info, compiles = look_up(mesh, x)
    assert info["from"] == "store" and info["fallback"] is None
    assert info["store_bytes"] == os.path.getsize(os.path.join(steps, info["key"]))
    assert compiles["requests"] == 0 and compiles["trace_s"] == 0
    loaded = labels(step, x)
    assert np.array_equal(loaded[0], built[0]) and np.array_equal(loaded[1], built[1])
    fresh = labels(make_ws_ccl_step(mesh, **BUILD), x)
    assert np.array_equal(loaded[0], fresh[0]) and np.array_equal(loaded[1], fresh[1])


# -- (d) every way out of the store is the build -------------------------------


def _truncate(path, other):
    with open(path, "rb") as f:
        raw = f.read()
    with open(path, "wb") as f:
        f.write(raw[: len(raw) // 2])
    return "unreadable:"


def _flip_a_byte_of_the_executable(path, other):
    with open(path, "rb") as f:
        entry = pickle.loads(f.read())
    packed = bytearray(entry["executable"])
    packed[len(packed) // 2] ^= 0xFF
    entry["executable"] = bytes(packed)
    with open(path, "wb") as f:
        f.write(pickle.dumps(entry))
    return "damaged:crc32"


def _one_field_of_the_document_differs(path, other):
    with open(path, "rb") as f:
        entry = pickle.loads(f.read())
    entry["key_document"]["jaxlib"] += ".post1"
    with open(path, "wb") as f:
        f.write(pickle.dumps(entry))
    return "key_mismatch:jaxlib"


def _written_for_other_device_ids(path, other):
    """The entry of the same step on devices 4-7, under this step's name."""
    _, info, _ = look_up(other, volume(other), builder=tiny_step)
    assert info["from"] == "built"
    os.replace(os.path.join(os.path.dirname(path), info["key"]), path)
    return "key_mismatch:mesh"


def _not_an_entry(path, other):
    with open(path, "wb") as f:
        f.write(b"\x00" * 4096)
    return "unreadable:"


DAMAGES = [_truncate, _flip_a_byte_of_the_executable, _one_field_of_the_document_differs,
           _written_for_other_device_ids, _not_an_entry]


@pytest.mark.parametrize("damage", DAMAGES, ids=lambda f: f.__name__.strip("_"))
def test_an_entry_that_cannot_be_trusted_costs_a_rebuild(steps, damage):
    mesh = mesh_of((0, 1, 2, 3))
    x = volume(mesh)
    step, info, _ = look_up(mesh, x, builder=tiny_step)
    sound = labels(step, x)
    path = os.path.join(steps, info["key"])
    reason = damage(path, mesh_of((4, 5, 6, 7)))
    step_cache.forget()
    before = step_cache.totals()
    step, info, compiles = look_up(mesh, x, builder=tiny_step)
    assert info["from"] == "built" and info["fallback"].startswith(reason), info
    assert compiles["requests"] >= 1
    after = step_cache.totals()
    assert after["fallbacks"] == before["fallbacks"] + 1
    assert after["builds"] == before["builds"] + 1
    got = labels(step, x)
    assert np.array_equal(got[0], sound[0]) and np.array_equal(got[1], sound[1])
    # the entry is repaired: the next process level reads it
    step_cache.forget()
    step, info, _ = look_up(mesh, x, builder=tiny_step)
    assert info["from"] == "store" and info["fallback"] is None
    got = labels(step, x)
    assert np.array_equal(got[0], sound[0]) and np.array_equal(got[1], sound[1])


def test_a_serialize_that_raises_leaves_a_built_step_and_no_entry(steps, monkeypatch):
    from jax.experimental import serialize_executable

    def refuses(compiled):
        raise NotImplementedError("serialize_executables with const_args")

    monkeypatch.setattr(serialize_executable, "serialize", refuses)
    mesh = mesh_of((0,))
    x = volume(mesh)
    step, info, _ = look_up(mesh, x, builder=tiny_step)
    assert info["from"] == "built"
    assert info["fallback"] == "store:NotImplementedError"
    assert info["store_bytes"] == 0 and not os.path.exists(steps)
    monkeypatch.undo()
    sound = labels(tiny_step(mesh, **BUILD), x)
    got = labels(step, x)
    assert np.array_equal(got[0], sound[0]) and np.array_equal(got[1], sound[1])
    # the step the process holds is as good as any; a new process level
    # builds again and this time writes
    step_cache.forget()
    _, info, _ = look_up(mesh, x, builder=tiny_step)
    assert info["from"] == "built" and info["fallback"] is None
    assert os.listdir(steps) == [info["key"]]


def test_a_load_that_raises_is_a_rebuild(steps, monkeypatch):
    from jax.experimental import serialize_executable

    mesh = mesh_of((0,))
    x = volume(mesh)
    look_up(mesh, x, builder=tiny_step)
    step_cache.forget()

    def refuses(*a, **kw):
        raise RuntimeError("the backend refuses this executable")

    monkeypatch.setattr(serialize_executable, "deserialize_and_load", refuses)
    _, info, _ = look_up(mesh, x, builder=tiny_step)
    assert info["from"] == "built" and info["fallback"] == "load:RuntimeError"


def test_a_step_that_jaxs_cache_handed_over_is_not_stored_on_the_cpu(steps):
    """JAX's persistent cache gives the build an executable it deserialized;
    on the CPU backend that one stays out of the store (the next test says
    why), and the entry that could not be used goes."""
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        mesh = mesh_of((0, 1))
        x = volume(mesh)
        args = dict(BUILD, threshold=0.125)
        step, info, compiles = look_up(mesh, x, args, tiny_step)
        assert info["from"] == "built" and info["fallback"] is None
        assert compiles["cache_hits"] == 0             # compiled here: stored
        sound = labels(step, x)
        path = os.path.join(steps, info["key"])
        _truncate(path, None)
        step_cache.forget()
        step, info, compiles = look_up(mesh, x, args, tiny_step)
        assert info["from"] == "built" and compiles["cache_hits"] == 1
        assert compiles["cache_misses"] == 0           # and nothing compiled for it
        got = labels(step, x)
        assert np.array_equal(got[0], sound[0]) and np.array_equal(got[1], sound[1])
        assert info["fallback"].startswith("unreadable:")
        assert info["fallback"].endswith(";store:deserialized_executable")
        assert not os.path.exists(path)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def test_the_cpu_client_does_not_serialize_what_it_deserialized():
    """The reason for the rule above, held here so that a JAX that repairs
    it is noticed (the rule's CPU branch then has no user left): a program
    serialized, loaded, serialized again and loaded again fails when it
    runs.  On a TPU v5e the same round trip runs (PERF.md section 6, PR 34)."""
    from jax.experimental.serialize_executable import deserialize_and_load, serialize

    x = jnp.arange(8, dtype=jnp.float32)
    step = jax.jit(lambda v: jnp.sin(v) + 1).lower(x).compile()
    for _ in range(2):
        payload, in_tree, out_tree = serialize(step)
        step = deserialize_and_load(payload, in_tree, out_tree,
                                    execution_devices=jax.devices("cpu")[:1])
    with pytest.raises(jax.errors.JaxRuntimeError, match="not found"):
        jax.block_until_ready(step(x))


# -- (e) no persistent compile cache, no store ---------------------------------


@pytest.mark.parametrize("name, value", [
    ("jax_compilation_cache_dir", None), ("jax_enable_compilation_cache", False)])
def test_without_a_persistent_compile_cache_there_is_no_store(steps, name, value):
    was = getattr(jax.config, name)
    jax.config.update(name, value)
    try:
        assert step_cache.store_dir() is None
        mesh = mesh_of((0,))
        x = volume(mesh)
        _, info, _ = look_up(mesh, x, builder=tiny_step)
        assert info == dict(info, **{"from": "built", "load_s": 0.0, "store_bytes": 0,
                                     "fallback": None})
        _, info, _ = look_up(mesh, x, builder=tiny_step)
        assert info["from"] == "process"
    finally:
        jax.config.update(name, was)
    assert not os.path.exists(steps)
    assert step_cache.store_dir() == steps


# -- (g) the store's bound and its writes ---------------------------------------


@pytest.fixture(scope="module")
def small_compiled():
    x = jax.ShapeDtypeStruct((8,), np.float32)
    return jax.jit(lambda v: v + 1).lower(x).compile()


def test_store_keeps_its_most_recently_used_entries(tmp_path, small_compiled):
    directory = str(tmp_path / "steps")
    now = time.time()
    n = step_cache.STORE_STEPS + 3
    for i in range(n):
        step_cache.save(directory, f"key{i}", {"n": i}, small_compiled)
        os.utime(os.path.join(directory, f"key{i}"), (now - 100 + i, now - 100 + i))
    # the oldest that is left is used again, then one more entry arrives
    kept = sorted(os.listdir(directory))
    assert len(kept) <= step_cache.STORE_STEPS + 1
    oldest = kept[0]
    os.utime(os.path.join(directory, oldest), (now, now))
    step_cache.save(directory, "newest", {"n": -1}, small_compiled)
    left = set(os.listdir(directory))
    assert len(left) == step_cache.STORE_STEPS
    assert {"newest", oldest, f"key{n - 1}"} <= left and "key0" not in left


def test_a_killed_writers_temp_file_goes_and_a_live_one_stays(tmp_path, small_compiled):
    directory = str(tmp_path / "steps")
    os.makedirs(directory)
    stale, live = (os.path.join(directory, n) for n in ("a.tmp.1.1", "b.tmp.2.2"))
    for path in (stale, live):
        with open(path, "wb") as f:
            f.write(b"half an entry")
    old = time.time() - 2 * step_cache._STALE_TEMP_S
    os.utime(stale, (old, old))
    step_cache.save(directory, "key", {}, small_compiled)
    assert sorted(os.listdir(directory)) == ["b.tmp.2.2", "key"]


def test_a_write_that_fails_leaves_the_old_entry_and_no_temp_file(
        tmp_path, small_compiled, monkeypatch):
    directory = str(tmp_path / "steps")
    step_cache.save(directory, "key", {"v": 1}, small_compiled)
    with open(os.path.join(directory, "key"), "rb") as f:
        old = f.read()

    def fails(src, dst):
        raise OSError("no space left on device")

    monkeypatch.setattr(os, "replace", fails)
    with pytest.raises(OSError):
        step_cache.save(directory, "key", {"v": 2}, small_compiled)
    monkeypatch.undo()
    assert os.listdir(directory) == ["key"]
    with open(os.path.join(directory, "key"), "rb") as f:
        assert f.read() == old


def test_store_write_failure_is_a_named_fallback_not_a_failed_job(steps, monkeypatch):
    def fails(src, dst):
        raise OSError("no space left on device")

    mesh = mesh_of((0,))
    x = volume(mesh)
    monkeypatch.setattr(os, "replace", fails)
    step, info, _ = look_up(mesh, x, builder=tiny_step)
    monkeypatch.undo()
    assert info["from"] == "built" and info["fallback"] == "store:OSError"
    labels(step, x)


def test_step_cache_module_lints_clean():
    from cluster_tools_tpu.lint.core import run_lint

    findings, _ = run_lint([step_cache.__file__])
    assert findings == []
