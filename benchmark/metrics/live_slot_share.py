"""Share of the slots the chunked walks visited that held live work."""

import sys

from benchmark import work_trace


def read(traced, meta):
    live = slots = 0
    for n, (span, records) in enumerate(work_trace.executions(traced)):
        for rec in records:
            said = []
            for walk in meta["walks"]:
                if rec.get(walk["trips"], -1) < 0:
                    continue   # this program does not walk that list
                cap = work_trace.counted(rec, walk["capacity"])
                held = sum(work_trace.counted(rec, n) for n in walk["live"])
                held += sum(min(work_trace.counted(rec, n), cap)
                            for n in walk["live_up_to_capacity"])
                walked = (work_trace.counted(rec, walk["trips"])
                          * work_trace.chunk(rec, walk["capacity"]))
                live, slots = live + held, slots + walked
                said.append(f"{walk['trips']} {held} of {walked} slots")
            # what the walks' complement is made of: the hops of one trip
            # (the chase's tail), and the loops around the fill's walks
            said += [f"{name}={rec[name]}" for name in meta["tails"]
                     if rec.get(name, -1) >= 0]
            if said:
                print(f"[live_slot_share] execution {n} {span}"
                      + (f" block {rec['block']}" if "block" in rec else "")
                      + ": " + "; ".join(said), file=sys.stderr, flush=True)
    return 100.0 * live / slots if slots else None
