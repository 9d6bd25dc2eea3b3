"""What the readers of the multicut cell's metrics share: the seconds of a
stage's tasks, and the device operations under a stage scope in whichever
compiled program holds them.

``program_trace.stage_seconds`` reads the main program only (the fused
step); the RAG's and the contraction's operations run in small programs of
their own (``jit_device_edge_aggregate``, ``jit__device_contract``), a few
hundred executions a job, so they are found by their scope path alone.
Returns nothing where the program has no such span or scope.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional, Sequence, Tuple

from . import program_trace, reduce_trace


def task_seconds(traced: dict, tasks: Sequence[str]) -> Optional[float]:
    """Seconds of the traced job inside the ``task.run`` spans of the tasks
    named ``tasks`` (``task_name`` in the span's arguments): their union."""
    found = [(a, b) for name, a, b, _, args in program_trace.job_spans(traced)
             if name == "task.run" and args.get("task_name") in tasks]
    if not found:
        return None
    return sum(b - a for a, b in reduce_trace.union(found))


def scoped_ops(traced: dict, stages: Sequence[str]
               ) -> Optional[List[Tuple[reduce_trace.Op, str]]]:
    """(leaf operation, stage) for every device operation of the traced job
    (chip 0, any program) whose scope path holds one of ``stages``."""
    path = program_trace.trace_file(traced)
    if path is None:
        return None
    scopes = program_trace.op_scopes(path)
    out = []
    for op in reduce_trace.leaf_ops(traced["trace"]):
        stage = program_trace.stage_of(scopes.get(op.text), stages)
        if stage is not None:
            out.append((op, stage))
    if out:
        _describe(path, out)
    return out or None


_described = set()


def _describe(path: str, ops) -> None:
    """Once per trace file and set of stages, on standard error: seconds and
    executions by stage and operation, for PERF.md section 5."""
    key = (path, tuple(sorted({stage for _, stage in ops})))
    if key in _described:
        return
    _described.add(key)
    per: Dict[Tuple[str, str], list] = {}
    for op, stage in ops:
        row = per.setdefault((stage, f"{op.opcode}:{op.name}"), [0.0, 0])
        row[0] += op.dur
        row[1] += 1
    for (stage, name), (secs, n) in sorted(per.items(), key=lambda kv: -kv[1][0])[:12]:
        print(f"[multicut_trace] {secs:8.4f}s x{n:<6} {name:<28} stage={stage}",
              file=sys.stderr, flush=True)
