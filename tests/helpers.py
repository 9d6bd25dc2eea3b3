"""Shared test helpers."""

import contextlib

import numpy as np


@contextlib.contextmanager
def programs_built_here():
    """Jobs inside build their programs from the code as it is in memory
    now: the process holds no ready program (the fused step, the executor's
    sweep programs) and there is no step store.  ``parallel/step_cache.py``
    keys a compiled program on source *files*, on the values its kernel
    captures and on the package's top-level functions swapped in memory,
    not on other module code the kernel calls (a method, a constant), so a
    test that patches the program (``ops/*``) in memory, or wants to see
    the build, says so; the patched program does not outlive the block
    either."""
    import jax

    from cluster_tools_tpu.parallel import step_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    step_cache.forget()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        step_cache.forget()


def assert_labels_equivalent(a: np.ndarray, b: np.ndarray):
    """Assert two labelings are equal up to a bijection of label values.

    Background (0) must match exactly.  This is the reference's oracle
    comparison for blockwise-vs-single-shot labelings (SURVEY.md §4).
    """
    a = np.asarray(a)
    b = np.asarray(b)
    assert a.shape == b.shape
    np.testing.assert_array_equal(a == 0, b == 0, err_msg="background differs")
    fg = a != 0
    if not fg.any():
        return
    pairs = np.stack([a[fg].ravel(), b[fg].ravel()], axis=1)
    uniq = np.unique(pairs, axis=0)
    # bijection: each a-label maps to exactly one b-label and vice versa
    ua, ca = np.unique(uniq[:, 0], return_counts=True)
    ub, cb = np.unique(uniq[:, 1], return_counts=True)
    assert (ca == 1).all(), f"non-injective a->b for labels {ua[ca > 1][:10]}"
    assert (cb == 1).all(), f"non-injective b->a for labels {ub[cb > 1][:10]}"


def random_blobs(rng, shape, p=0.5, smooth=1):
    """Random binary mask with some spatial correlation."""
    x = rng.random(shape)
    from scipy.ndimage import gaussian_filter

    x = gaussian_filter(x, smooth)
    return x > np.quantile(x, 1 - p)


def stray_serve_pids():
    """Pids of live ``cluster_tools_tpu.serve`` processes on this host —
    the leaked-server guard: a stray resident server keeps burning CPU
    after its test/bench ends and is the prime suspect when tier-1 drifts
    toward its wall-clock ceiling."""
    import os

    out = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().decode(errors="replace")
        except OSError:
            continue
        if "cluster_tools_tpu.serve" in cmd.replace("\x00", " "):
            out.append(int(pid))
    return out


def reap_process(proc, timeout=30):
    """SIGKILL + wait a subprocess if it is still alive (the ``finally``
    guard every serve-spawning test/bench must run)."""
    if proc.poll() is None:
        proc.kill()
        try:
            proc.wait(timeout=timeout)
        except Exception:
            pass


def write_stub(path, body):
    """Write an executable shell stub (`#!/bin/bash` + body)."""
    import os
    import stat

    with open(path, "w") as f:
        f.write("#!/bin/bash\n" + body)
    os.chmod(path, os.stat(path).st_mode | stat.S_IEXEC)


def stub_slurm_bins(bindir):
    """Stub sbatch/squeue/scancel in ``bindir``: jobs are detached local
    processes, job id = pid.  sbatch launches the script detached (honoring
    -o) and prints the pid; squeue prints a row while the pid lives;
    scancel kills the process group.  Shared by the cluster-target tests,
    the chaos suite, and scripts/supervise_demo.py — prepend ``bindir`` to
    PATH to use it."""
    import os

    os.makedirs(bindir, exist_ok=True)
    write_stub(
        os.path.join(bindir, "sbatch"),
        # last argument is the script; flags before it are accepted+ignored
        'script="${@: -1}"\n'
        "out=/dev/null\n"
        'prev=""\n'
        'for a in "$@"; do if [ "$prev" = "-o" ]; then out="$a"; fi; '
        'prev="$a"; done\n'
        'JAX_PLATFORMS=cpu setsid bash "$script" > "$out" 2>&1 &\n'
        "echo $!\n",
    )
    write_stub(
        os.path.join(bindir, "squeue"),
        'pid="${@: -1}"\n'
        'if kill -0 "$pid" 2>/dev/null; then echo "RUNNING"; fi\n'
        "exit 0\n",
    )
    write_stub(
        os.path.join(bindir, "scancel"),
        'pid="${@: -1}"\n'
        'kill -9 "-$pid" 2>/dev/null || kill -9 "$pid" 2>/dev/null\n'
        "exit 0\n",
    )
    return bindir
