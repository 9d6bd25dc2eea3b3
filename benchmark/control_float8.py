"""The coarser-precision control of ``unet125.volumes``' ``correct``, on the chip.

    python3 -m benchmark.control_float8 --seeds 1,2,3 [--seconds 1]

``benchmark.control --control bf16`` rounds the input to bfloat16, which the
network does itself by design, so it shows nothing there.  This control
rounds every conv operand of the network to float8 (e4m3) before the
network's own bfloat16 (``models/unet_gcr.py::_operand``): the nearest
precision below the configuration's bfloat16 operands with float32
accumulation.  Runs the cell once per seed in one process and prints, per
seed, every number compared.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    import jax.numpy as jnp

    from cluster_tools_tpu.models import unet_gcr

    from . import run

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--seconds", type=float, default=1.0)
    args = p.parse_args(argv)
    unet_gcr._operand = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    for seed in (int(s) for s in args.seeds.split(",")):
        result = run.run_cell("unet125.volumes", seed, args.seconds, trace=False)
        print(json.dumps({"seed": seed, "control": "float8", "correct": result["correct"],
                          "failed": result["failed"],
                          "checks": {k: c["value"] for k, c in result["checks"].items()},
                          "metrics": result["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
