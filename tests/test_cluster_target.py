"""Cluster-scheduler targets (slurm/lsf) driven end-to-end against stub
scheduler binaries — the submission/polling/result machinery is real, only
``sbatch``/``squeue`` are fakes that run the job script as a local
background process (SURVEY.md §7 L2': the reference's Slurm/LSF trio)."""

import json
import os
import stat
import subprocess
import sys

import numpy as np
import pytest

from cluster_tools_tpu.runtime.task import build, get_task_cls
from cluster_tools_tpu.utils.volume_utils import file_reader


@pytest.fixture
def workspace(tmp_path):
    tmp_folder = str(tmp_path / "tmp")
    config_dir = str(tmp_path / "config")
    os.makedirs(config_dir, exist_ok=True)
    with open(os.path.join(config_dir, "global.config"), "w") as f:
        json.dump({"block_shape": [16, 16, 16]}, f)
    return tmp_folder, config_dir, str(tmp_path)


def _write_stub(path, body):
    with open(path, "w") as f:
        f.write("#!/bin/bash\n" + body)
    os.chmod(path, os.stat(path).st_mode | stat.S_IEXEC)


@pytest.fixture
def fake_slurm(tmp_path, monkeypatch):
    """Stub sbatch/squeue/scancel (shared helper, tests/helpers.py): sbatch
    launches the script detached and prints its pid as the job id; squeue
    -h -j <pid> prints a row while the process lives.  JAX_PLATFORMS=cpu is
    exported so the remote runner computes on the CPU backend."""
    from .helpers import stub_slurm_bins

    bindir = stub_slurm_bins(str(tmp_path / "fakebin"))
    monkeypatch.setenv("PATH", f"{bindir}:{os.environ['PATH']}")
    return bindir


@pytest.fixture
def fake_lsf(tmp_path, monkeypatch):
    """Stub bsub/bjobs: bsub reads the script from stdin, launches it
    detached, and prints 'Job <pid> is ...'; bjobs prints a RUN row while
    the process lives and 'is not found' after."""
    bindir = tmp_path / "fakebin_lsf"
    bindir.mkdir()
    _write_stub(
        str(bindir / "bsub"),
        "script=$(mktemp)\ncat > \"$script\"\n"
        "out=/dev/null\n"
        'prev=""\n'
        'for a in "$@"; do if [ "$prev" = "-o" ]; then out="$a"; fi; '
        'prev="$a"; done\n'
        'JAX_PLATFORMS=cpu setsid bash "$script" > "$out" 2>&1 &\n'
        'echo "Job <$!> is submitted to default queue."\n',
    )
    _write_stub(
        str(bindir / "bjobs"),
        'pid="${@: -1}"\n'
        'if kill -0 "$pid" 2>/dev/null; then\n'
        '  echo "$pid user RUN normal host1 host2 jobname"\n'
        "else\n"
        '  echo "Job <$pid> is not found" >&2\n'
        "  exit 255\n"
        "fi\n",
    )
    monkeypatch.setenv("PATH", f"{bindir}:{os.environ['PATH']}")
    return str(bindir)


def test_threshold_task_on_lsf_target(rng, workspace, fake_lsf):
    """The LSF trio member end-to-end: bsub takes the script on stdin,
    bjobs liveness rows are parsed, 'is not found' means finished."""
    from cluster_tools_tpu.tasks import thresholded_components as tc

    tmp_folder, config_dir, root = workspace
    data = rng.random((24, 24, 24)).astype(np.float32)
    path = os.path.join(root, "cl_lsf.zarr")
    f = file_reader(path)
    f.require_dataset("raw", shape=data.shape, chunks=(16, 16, 16),
                      dtype="float32")[...] = data

    cls = get_task_cls(tc, "Threshold", "lsf")
    assert cls.target == "lsf" and cls.__name__ == "ThresholdLSF"
    t = cls(
        tmp_folder=tmp_folder,
        config_dir=config_dir,
        max_jobs=2,
        input_path=path,
        input_key="raw",
        output_path=path,
        output_key="mask",
        threshold=0.5,
        block_shape=[16, 16, 16],
        poll_interval_s=0.5,
        submit_timeout_s=240,
        result_grace_s=2.0,
    )
    assert build([t])
    np.testing.assert_array_equal(
        file_reader(path)["mask"][:], (data > 0.5).astype(np.uint8)
    )


def test_threshold_task_on_slurm_target(rng, workspace, fake_slurm):
    """A real task class runs via target='slurm': spec + sbatch script are
    written, the (stub) scheduler executes the runner remotely, the
    submitter polls to completion, and the output matches local."""
    from cluster_tools_tpu.tasks import thresholded_components as tc

    tmp_folder, config_dir, root = workspace
    data = rng.random((24, 24, 24)).astype(np.float32)
    path = os.path.join(root, "cl.zarr")
    f = file_reader(path)
    f.require_dataset("raw", shape=data.shape, chunks=(16, 16, 16),
                      dtype="float32")[...] = data

    cls = get_task_cls(tc, "Threshold", "slurm")
    assert cls.target == "slurm"
    t = cls(
        tmp_folder=tmp_folder,
        config_dir=config_dir,
        max_jobs=2,
        input_path=path,
        input_key="raw",
        output_path=path,
        output_key="mask",
        threshold=0.5,
        block_shape=[16, 16, 16],
        poll_interval_s=0.5,
        submit_timeout_s=240,
    )
    assert build([t])
    np.testing.assert_array_equal(
        file_reader(path)["mask"][:], (data > 0.5).astype(np.uint8)
    )
    # the scheduler artifacts exist and the script is a real sbatch script
    cdir = os.path.join(tmp_folder, "cluster")
    scripts = [s for s in os.listdir(cdir) if s.endswith(".sh")]
    assert scripts
    with open(os.path.join(cdir, scripts[0])) as fh:
        assert "cluster_runner" in fh.read()
    # the chunk IO ran in the WORKER process, which must have recorded its
    # own io_metrics delta into the shared manifest (the submitter only
    # polls and has nothing to record)
    import json as _json
    from cluster_tools_tpu.utils import function_utils as fu

    io_doc = _json.load(open(fu.io_metrics_path(tmp_folder)))
    worker = io_doc["tasks"][t.uid]
    assert worker["misses"] > 0 or worker["direct_reads"] > 0


def test_cluster_remote_failure_surfaces(workspace, fake_slurm):
    """A remote crash must fail the task with the remote error, not hang
    or succeed silently."""
    from cluster_tools_tpu.tasks import thresholded_components as tc

    tmp_folder, config_dir, root = workspace
    cls = get_task_cls(tc, "Threshold", "slurm")
    t = cls(
        tmp_folder=tmp_folder,
        config_dir=config_dir,
        max_jobs=1,
        input_path=os.path.join(root, "missing.zarr"),  # remote will crash
        input_key="raw",
        output_path=os.path.join(root, "out.zarr"),
        output_key="mask",
        threshold=0.5,
        block_shape=[16, 16, 16],
        poll_interval_s=0.5,
        submit_timeout_s=240,
        result_grace_s=2.0,  # stubs run on local FS: no NFS lag to wait out
    )
    assert not build([t])  # task failed, DAG reports failure


def test_is_running_tristate():
    """Probe semantics: running row -> True, clean empty -> False, purged
    job ('Invalid job id' after MinJobAge) -> False, any other nonzero
    exit -> None (unknown; the poll loop bounds consecutive unknowns)."""
    from cluster_tools_tpu.runtime.cluster import LSFSubmitter, SlurmSubmitter
    import cluster_tools_tpu.runtime.cluster as cl

    def with_probe(stdout, stderr, rc, fn):
        class R:
            pass
        R.stdout, R.stderr, R.returncode = stdout, stderr, rc
        orig = cl.subprocess.run
        cl.subprocess.run = lambda *a, **k: R()
        try:
            return fn()
        finally:
            cl.subprocess.run = orig

    s = SlurmSubmitter()
    assert with_probe("123 RUNNING\n", "", 0, lambda: s.is_running("123")) is True
    assert with_probe("", "", 0, lambda: s.is_running("123")) is False
    assert with_probe(
        "", "slurm_load_jobs error: Invalid job id specified\n", 1,
        lambda: s.is_running("123")) is False
    assert with_probe("", "socket timed out\n", 1,
                      lambda: s.is_running("123")) is None

    b = LSFSubmitter()
    assert with_probe("123  user  RUN  q  h1 h2 jn\n", "", 0,
                      lambda: b.is_running("123")) is True
    assert with_probe("123  user  DONE  q  h1 h2 jn\n", "", 0,
                      lambda: b.is_running("123")) is False
    assert with_probe("", "Job <123> is not found\n", 255,
                      lambda: b.is_running("123")) is False
    assert with_probe("", "lsf comm failure\n", 255,
                      lambda: b.is_running("123")) is None


def test_spec_serialization_rejects_unserializable(tmp_path):
    """Numpy params coerce to plain values; arbitrary objects fail at
    SUBMIT time with a clear error, not stringified on the remote node."""
    from cluster_tools_tpu.runtime.cluster import _spec_default

    assert json.loads(json.dumps(
        {"t": np.float32(0.5), "n": np.int64(3), "a": np.arange(2)},
        default=_spec_default)) == {"t": 0.5, "n": 3, "a": [0, 1]}
    with pytest.raises(TypeError, match="not JSON-serializable"):
        json.dumps({"bad": object()}, default=_spec_default)


def test_submitter_command_lines(tmp_path):
    """The sbatch/bsub command construction: resource knobs map to the
    scheduler's flags (reference config keys partition/time/mem)."""
    from cluster_tools_tpu.runtime.cluster import LSFSubmitter, SlurmSubmitter

    calls = {}

    def fake_run(cmd, **kw):
        calls["cmd"] = cmd

        class R:
            stdout = "123\n"
            returncode = 0
        return R()

    import cluster_tools_tpu.runtime.cluster as cl

    orig = cl.subprocess.run
    cl.subprocess.run = fake_run
    try:
        jid = SlurmSubmitter().submit(
            "/x/job.sh", "job", "/x/out",
            {"partition": "gpu", "time_limit": 90, "mem_limit": 8},
        )
    finally:
        cl.subprocess.run = orig
    assert jid == "123"
    cmd = calls["cmd"]
    assert cmd[:2] == ["sbatch", "--parsable"]
    assert "-p" in cmd and "gpu" in cmd
    assert "-t" in cmd and "90" in cmd
    assert "--mem" in cmd and "8192M" in cmd
    assert cmd[-1] == "/x/job.sh"


def test_workflow_accepts_cluster_target(workspace):
    """WorkflowBase must accept target='slurm'/'lsf' (it used to refuse)."""
    from cluster_tools_tpu.tasks.thresholded_components import (
        ThresholdedComponentsWorkflow,
    )

    tmp_folder, config_dir, root = workspace
    wf = ThresholdedComponentsWorkflow(
        tmp_folder=tmp_folder,
        config_dir=config_dir,
        max_jobs=1,
        target="slurm",
        input_path="/nonexistent",
        input_key="raw",
        output_path="/nonexistent",
        output_key="out",
        threshold=0.5,
        assignment_key="a",
    )
    assert wf.target == "slurm"
    with pytest.raises(ValueError, match="unknown target"):
        ThresholdedComponentsWorkflow(
            tmp_folder=tmp_folder, config_dir=config_dir, max_jobs=1,
            target="pbs", input_path="x", input_key="y",
            output_path="z", output_key="w", threshold=0.5,
            assignment_key="a",
        )
