"""512³ headline-geometry rehearsal on the host substrate.

Runs the full TPU-shaped program — capacity fill, the four-program
split chain, halo 32 — at the REAL bench geometry (512³) on XLA:CPU,
and FAILS on any overflow flag.  This is the run that
caught two headline-scale cap bugs in round 5 (fill_rounds' 2^16 bound
vs 80,902 measured basins; adj_cap n/128 vs the measured n/85 unique
adjacency load — docs/PERFORMANCE.md "512³ host-substrate rehearsal"),
either of which would otherwise have cost a chip run an overflow-flagged
result.

Needs ~40 GB RAM and ~15-25 min on a 2-core box (the synth volume
dominates).  Run before any chip campaign and after any capacity /
round-bound / fill change:

    python scripts/rehearse_512.py [extent]

A rehearsal tool: it pins the CPU backend on purpose and never opens the
chip (``chip_smoke.py`` is what runs there).
"""

import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

os.environ["CT_FILL_MODE"] = "capacity"  # the TPU-shaped machinery

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import jax.numpy as jnp

T0 = time.monotonic()


def log(m):
    print(f"[+{time.monotonic() - T0:.1f}s] {m}", flush=True)


def main():
    from cluster_tools_tpu.parallel.mesh import make_mesh
    from cluster_tools_tpu.parallel.split_pipeline import make_ws_ccl_split

    ext = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    halo = 32
    # MUST track bench.py's synthetic exactly (same env knob): the whole
    # point is validating the caps at the headline run's basin statistics
    passes = int(os.environ.get("CT_BENCH_SYNTH_PASSES", "12"))
    log(f"synthesizing {ext}^3 CREMI-like volume ({passes} box passes/axis)")

    @jax.jit
    def synth(key):
        v = jax.random.uniform(key, (1, ext, ext, ext), jnp.float32)
        for axis in range(1, 4):
            for _ in range(passes):
                v = (v + jnp.roll(v, 1, axis) + jnp.roll(v, -1, axis)) / 3.0
        lo, hi = v.min(), v.max()
        return (v - lo) / jnp.maximum(hi - lo, 1e-6)

    vol = jax.block_until_ready(synth(jax.random.PRNGKey(0)))
    log(f"volume ready {vol.shape}")

    mesh = make_mesh(1, axis_names=("dp", "sp"), devices=jax.devices("cpu")[:1])
    split = make_ws_ccl_split(
        mesh, halo=halo, threshold=0.45, dt_max_distance=float(halo),
        min_seed_distance=2.0, impl="xla", stitch_ws_threshold=0.45,
    )
    marks = [("start", time.monotonic())]

    def sync(name, *arrs):
        jax.block_until_ready(arrs)
        marks.append((name, time.monotonic()))
        log(f"stage {name} done")

    out = split.run_staged(vol, sync)
    ws, cc, n_fg, overflow, _ = jax.block_until_ready(out)
    total = time.monotonic() - marks[0][1]
    for (pn, pt), (nn, nt) in zip(marks, marks[1:]):
        log(f"  {nn}: {nt - pt:.1f}s")
    log(
        f"TOTAL chain {total:.1f}s = {vol.size / total / 1e6:.2f}M vox/s "
        "(cold, incl. compiles)"
    )
    log(
        f"n_fg={int(n_fg)} ({int(n_fg) / vol.size:.3f} of volume), "
        f"overflow={bool(overflow)}"
    )
    if bool(overflow):
        log("REHEARSAL FAILED: a capacity truncated or a bound was hit at "
            "headline scale — bisect with the per-stage overflow outputs "
            "before any chip run")
        raise SystemExit(1)
    ws0 = np.asarray(ws[0])
    cc0 = np.asarray(cc[0])
    log(
        f"ws fragments: {len(np.unique(ws0[ws0 > 0])):,}; "
        f"cc components: {len(np.unique(cc0[cc0 > 0])):,}"
    )
    log(f"{ext}^3 capacity-path rehearsal PASSED (host substrate)")


if __name__ == "__main__":
    main()
