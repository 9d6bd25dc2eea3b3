"""Share of the voxels computed that are halo, tile padding or lane padding."""

from benchmark import program_trace


def read(traced, meta):
    passes = [args for name, _, _, _, args in program_trace.job_spans(traced)
              if name == meta["span"] and args.get("padded_voxels")]
    if not passes:
        return None
    padded = sum(a["padded_voxels"] for a in passes)
    return 100.0 * (1.0 - sum(a["inner_voxels"] for a in passes) / padded)
