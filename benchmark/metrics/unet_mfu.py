"""The network's forward FLOPs over its device seconds, as a share of the
chip's bf16 peak."""

import sys

from benchmark import program_trace, reference_unet, run


def read(traced, meta):
    passes = [args for name, _, _, _, args in program_trace.job_spans(traced)
              if name == meta["span"] and args.get("lanes")]
    seconds = run.load_reader("unet_device_s").read(traced, run.load_json(
        run.HERE, "metrics", "unet_device_s.json"))
    if not passes or not seconds:
        return None
    model = traced["spec"]["config"]["params"]["model"]
    flops = sum(a["lanes"] * reference_unet.forward_flops(model, a["outer_shape"])
                for a in passes)
    print(f"[unet_mfu] {flops:.6e} FLOPs over {seconds:.6f} device s "
          f"({flops / seconds:.6e} FLOP/s); the program counts "
          f"{sum(a.get('flops', 0) for a in passes):.6e}",
          file=sys.stderr, flush=True)
    return 100.0 * flops / seconds / traced["peaks"]["bf16_flops_per_s"]
