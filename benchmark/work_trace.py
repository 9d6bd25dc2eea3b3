"""What the readers of the work record share: the records of the traced job.

The watershed / CCL device programs return, beside their labels, one small
vector of what they counted (``cluster_tools_tpu/ops/work.py``: live counts,
capacity fill, which fallback branch ran).  The tasks fetch it on every job
and, with the tracer on, put it on a span as the argument ``work``: a list
of dicts, one a shard on ``fused.wait`` (the mesh step), one a real lane on
``ws.pass`` (a sweep of the blockwise executor; ``block`` names the lane's
block).  A span is one execution of its program (a pass of several
dispatches counts as one).  A count the program did not make reads -1, and
no reader counts it.  Readers return nothing where no span carries a record
(a program from before the record).
"""

from __future__ import annotations

import sys
from typing import Dict, List, Tuple

from . import program_trace

SPANS = ("fused.wait", "ws.pass")

Execution = Tuple[str, List[Dict[str, int]]]   # span name, its records


def executions(traced: dict) -> List[Execution]:
    """(span, records) of every span of the traced job that carries records,
    in start order; says each record on standard error once a ``traced``."""
    spans = sorted((s for s in program_trace.job_spans(traced)
                    if s[0] in SPANS and s[4].get("work")), key=lambda s: s[1])
    found = [(name, list(args["work"])) for name, _, _, _, args in spans]
    if found and id(traced) not in _described:
        _described.add(id(traced))
        for n, (name, records) in enumerate(found):
            for rec in records:
                row = " ".join(f"{k}={v}" for k, v in rec.items() if v != -1)
                print(f"[work] execution {n} {name}: {row}", file=sys.stderr,
                      flush=True)
    return found


_described = set()


def counted(rec: Dict[str, int], name: str) -> int:
    """A count of a record, 0 where the program did not make it."""
    return max(0, rec.get(name, -1))


def chunk(rec: Dict[str, int], capacity: str) -> int:
    """The slots of one trip of a chunked walk, as the code derives them
    from the list's capacity: a sixteenth, rounded up."""
    return -(-counted(rec, capacity) // 16)
