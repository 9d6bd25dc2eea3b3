"""Executions x volume-sized fallback branches the device ran."""

import sys

from benchmark import work_trace


def read(traced, meta):
    total, found = 0, False
    for n, (span, records) in enumerate(work_trace.executions(traced)):
        ran = [rec[meta["ran"]] for rec in records if rec.get(meta["ran"], -1) >= 0]
        if not ran:
            continue   # a program from before the count
        both = max(work_trace.counted(rec, meta["both_sides"]) for rec in records)
        print(f"[fallback_branches_run] execution {n} {span}: "
              f"{meta['ran']}={max(ran)} {meta['both_sides']}={both}",
              file=sys.stderr, flush=True)
        total, found = total + max(ran), True
    return total if found else None
