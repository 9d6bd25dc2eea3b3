"""The control of ``correct``, and its readings on the chip.

    python3 -m benchmark.control --workload <cell> --seeds 1,2,3 [--control bf16] [--seconds 1]

Runs the cell once per seed in one process (one set-up of the chip, one
compile) and prints, per seed, every number compared beside its limit.
With ``--control bf16`` the program reads its input rounded to bfloat16:
the nearest precision below the float32 that the configurations state,
switched on under the program at the container doorway.  Without it, these
are sound runs of the program: the lower readings.  The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys

import numpy as np


def round_to_bfloat16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (ties to even) and
    widened again; plain numpy, so that the control compiles nothing."""
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32)
    bits = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
    return (bits & np.uint32(0xFFFF0000)).view(np.float32)


@contextlib.contextmanager
def bfloat16_reads():
    """Every float32 read through ``io/containers.py::Dataset.__getitem__``
    comes back rounded to bfloat16 (and widened again)."""
    from cluster_tools_tpu.io import containers

    inner = containers.Dataset.__getitem__

    def rounded(self, bb):
        out = inner(self, bb)
        return round_to_bfloat16(out) if out.dtype == np.float32 else out

    containers.Dataset.__getitem__ = rounded
    try:
        yield
    finally:
        containers.Dataset.__getitem__ = inner


def main(argv=None) -> int:
    from . import run

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--control", choices=("none", "bf16"), default="none")
    p.add_argument("--seconds", type=float, default=1.0)
    args = p.parse_args(argv)
    ctx = bfloat16_reads if args.control == "bf16" else contextlib.nullcontext
    for seed in (int(s) for s in args.seeds.split(",")):
        with ctx():
            result = run.run_cell(args.workload, seed, args.seconds, trace=False)
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": result["correct"], "failed": result["failed"],
                          "checks": {k: c["value"] for k, c in result["checks"].items()},
                          "metrics": result["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
