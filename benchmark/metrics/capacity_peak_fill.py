"""The fullest hard capacity of the traced job, in percent of its size."""

from benchmark import work_trace


def read(traced, meta):
    fills = [100.0 * rec[count] / rec[capacity]
             for _, records in work_trace.executions(traced) for rec in records
             for count, capacity in meta["capacities"]
             if rec.get(count, -1) >= 0 and rec.get(capacity, -1) > 0]
    return max(fills) if fills else None
