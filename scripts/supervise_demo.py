"""Supervision smoke-check (`make supervise-demo`, docs/ROBUSTNESS.md).

Runs the watershed workflow on the *cluster* target against a stub slurm
scheduler (the same sbatch/squeue fakes the tests use — jobs are detached
local processes), with an injected ``job_loss`` fault: the first submission
is swallowed, the stub scheduler keeps reporting it as running, and only
heartbeat supervision can find it.  The demo prints the supervisor's
resubmission log and the ``failures.json`` attribution so an operator can
see the whole detection -> resubmit -> recover loop in one screenful.

Self-contained: writes synthetic data, stubs, and all scratch under a
temporary directory.  A demo of the supervision plane, not of the compute:
it pins the CPU backend on purpose and never opens the chip.
"""

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from cluster_tools_tpu.runtime import faults  # noqa: E402
from cluster_tools_tpu.runtime.supervision import (  # noqa: E402
    REQUEUE_EXIT_CODE,
    DrainInterrupt,
)
from cluster_tools_tpu.runtime.task import build, get_task_cls  # noqa: E402
from cluster_tools_tpu.utils import function_utils as fu  # noqa: E402
from cluster_tools_tpu.utils.volume_utils import file_reader  # noqa: E402
from tests.helpers import stub_slurm_bins  # noqa: E402


def main():
    root = tempfile.mkdtemp(prefix="ctt_supervise_demo_")
    tmp_folder = os.path.join(root, "tmp")
    config_dir = os.path.join(root, "config")
    os.makedirs(config_dir, exist_ok=True)
    bindir = stub_slurm_bins(os.path.join(root, "fakebin"))
    os.environ["PATH"] = f"{bindir}:{os.environ['PATH']}"

    fu.atomic_write_json(
        os.path.join(config_dir, "global.config"),
        {
            "block_shape": [8, 8, 8],
            # supervision knobs: the batch script heartbeats the moment
            # the job starts, so 6 s of silence while the scheduler
            # claims RUNNING means the job is lost
            "heartbeat_interval_s": 0.3,
            "heartbeat_timeout_s": 6.0,
            "max_resubmits": 2,
            "poll_interval_s": 0.3,
            "result_grace_s": 2.0,
            "submit_timeout_s": 300,
        },
    )

    # synthetic boundary map with a clear membrane
    rng = np.random.default_rng(7)
    bmap = (0.05 + 0.02 * rng.random((16, 16, 16))).astype(np.float32)
    bmap[:, 7:9, :] = 0.95
    path = os.path.join(root, "data.zarr")
    f = file_reader(path)
    f.create_dataset(
        "bmap", shape=bmap.shape, chunks=(8, 8, 8), dtype="float32"
    )[...] = bmap

    # swallow the first scheduler submission: the stub scheduler will keep
    # reporting the phantom job as running — only heartbeats can tell
    faults.configure(
        {"faults": [{"site": "submit", "kind": "job_loss",
                     "fail_attempts": 1}]}
    )

    from cluster_tools_tpu.tasks import watershed as ws_mod

    cls = get_task_cls(ws_mod, "Watershed", "slurm")
    task = cls(
        tmp_folder=tmp_folder,
        config_dir=config_dir,
        max_jobs=4,
        input_path=path,
        input_key="bmap",
        output_path=path,
        output_key="ws",
        threshold=0.5,
        halo=[2, 2, 2],
    )
    print(f"demo workspace: {root}")
    print("submitting watershed to the stub scheduler with one injected "
          "job loss ...\n")
    try:
        ok = build([task])
    except DrainInterrupt as e:
        # drain safety (CT006): a SIGTERM mid-demo exits with the requeue
        # code, same protocol as the production entry points
        print(f"DRAINED ({e.reason}); exiting {REQUEUE_EXIT_CODE}")
        return REQUEUE_EXIT_CODE

    print("=" * 72)
    print("supervisor resubmission log "
          f"({os.path.join(tmp_folder, 'cluster', 'supervisor.log')}):")
    print("=" * 72)
    with open(os.path.join(tmp_folder, "cluster", "supervisor.log")) as fh:
        print(fh.read().rstrip())

    fpath = os.path.join(tmp_folder, "failures.json")
    if os.path.exists(fpath):
        print("\n" + "=" * 72)
        print(f"failures.json attribution ({fpath}):")
        print("=" * 72)
        with open(fpath) as fh:
            doc = json.load(fh)
        for rec in doc["records"]:
            if rec["sites"].get("job_loss"):
                print(json.dumps(rec, indent=2))

    n_labels = len(np.unique(file_reader(path, "r")["ws"][...]))
    print("\n" + "=" * 72)
    print(f"workflow {'SUCCEEDED' if ok else 'FAILED'}: watershed produced "
          f"{n_labels} labels after the lost job was resubmitted")
    print("=" * 72)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
