"""Subprocess driver for the chaos tests: run a MulticutSegmentationWorkflow
from a JSON spec file.  Faults are injected via the ``CTT_FAULTS`` env var
(runtime/faults.py), including hard kills — so this must be its own process.

Usage: python chaos_driver.py <spec.json>
Exit codes: 0 workflow ok, 1 workflow failed, KILL_EXIT_CODE (113) injected
kill, REQUEUE_EXIT_CODE (114) graceful drain after SIGTERM/preempt — rerun
with the same spec to resume.

A test tool: it pins the CPU backend on purpose (the chaos suite exercises
fault handling, not the chip).
"""

import json
import os
import sys


def main():
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")
    from cluster_tools_tpu.runtime.supervision import (
        REQUEUE_EXIT_CODE,
        DrainInterrupt,
        install_drain_handler,
    )
    from cluster_tools_tpu.runtime.task import build
    from cluster_tools_tpu.workflows import MulticutSegmentationWorkflow

    install_drain_handler()
    wf = MulticutSegmentationWorkflow(**spec)
    try:
        ok = build([wf])
    except DrainInterrupt as e:
        print(f"drained for requeue: {e}", file=sys.stderr)
        sys.exit(REQUEUE_EXIT_CODE)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
