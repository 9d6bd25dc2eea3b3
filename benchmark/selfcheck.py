"""Checks of the yardstick itself that need no chip.

    python3 -m benchmark.selfcheck

* ``reduce_trace.py`` against the small trace recorded on a TPU v5e and kept
  in ``testdata/small.xplane.pb`` (three runs of one jitted program under a
  ``bench.job`` span; two of them start inside the span).
* every per-layer reader on that trace: a number where there is something
  to read, nothing where there is not.
* ``BENCHMARK.json`` against the files it names, each configuration's
  comparison among them.
"""

from __future__ import annotations

import os
import sys

from . import reduce_trace, run

HERE = os.path.dirname(os.path.abspath(__file__))


def check(what: str, ok: bool) -> None:
    print(("ok   " if ok else "FAIL ") + what)
    if not ok:
        check.failed += 1


check.failed = 0


def main() -> int:
    red = reduce_trace.reduce_file(os.path.join(HERE, "testdata", "small.xplane.pb"))
    check("one chip in the trace", red.n_chips == 1)
    check("window is the bench.job span (35.2 ms)", abs(red.window_s - 0.035247239) < 1e-9)
    check("two executions of the program inside the window", len(red.modules) == 2)
    check("busy is the union of 40 operations: 124.7 us",
          len(red.ops) == 40 and abs(red.busy_s - 1.24688e-4) < 1e-9)
    check("main module", (reduce_trace.main_module(red) or "").startswith("jit_f("))
    top = reduce_trace.breakdown(red, {})
    check("the sort took most of the device's time (105.6 us)",
          top["device_ops"][0][0] == "sort:sort.6"
          and abs(top["device_ops"][0][1] - 1.05582e-4) < 1e-9)
    check("idle time by what the host was doing: all of it with no span open",
          top["idle_gaps"][0][0] == "host: no span open" and len(top["idle_gaps"]) == 1
          and abs(top["idle_gaps"][0][1] - (red.window_s - red.busy_s)) < 1e-9)
    named = reduce_trace.breakdown(red, dict(
        job={"t0": 5.0}, runtime_spans=[{"ph": "X", "name": "task.run", "ts": 5.0, "dur": 0.02}]))
    check("a runtime span on the job's clock names the gaps it covers",
          named["idle_gaps"][0][0] == "task.run"
          and abs(named["idle_gaps"][0][1] - 0.022455376) < 1e-8 and len(named["idle_gaps"]) == 2)
    sort_op = next(op for op in red.ops if op.opcode == "sort")
    check("shapes in an HLO text: sort of f32+s32 [512,512], two results, two operands",
          reduce_trace.shapes_bytes(sort_op.text) == 4 * 512 * 512 * 4)
    check("opcode past a tuple shape and tiling annotations",
          reduce_trace.opcode_of("%s = (f32[2]{0:T(8)S(1)}, s32[2]{0}) sort(f32[2]{0} %a)") == "sort")
    check("union of overlapping intervals",
          reduce_trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)])

    traced = dict(trace=red, peaks={"hbm_bytes_per_s": 819e9},
                  job={"t0": 10.0, "t1": 10.0 + red.window_s}, io_spans=[(10.0, 10.01)],
                  io_seconds=0.01, runtime_spans=[])
    spec = dict(per_layer=run.load_json(os.path.dirname(HERE), "BENCHMARK.json")["per_layer"])
    got = run.read_per_layer(spec, traced)
    check("device_idle_share 99.65 %", abs(got["device_idle_share"]["value"] - 99.6462) < 1e-3)
    check("sort_scan_busy_share 84.76 % (sort + reduce-window)",
          abs(got["sort_scan_busy_share"]["value"] - 84.7588) < 1e-3)
    check("io_share 28.4 %", abs(got["io_share"]["value"] - 28.371) < 1e-2)
    check("program_load_share 29.8 %", abs(got["program_load_share"]["value"] - 29.77) < 0.05)
    check("step_device_s 124.8 us", abs(got["step_device_s"]["value"] - 1.24752e-4) < 1e-8)
    check("no Mosaic call in the trace: the roofline reader returns nothing",
          "pallas_kernels_roofline" not in got)
    roofline = run.load_reader("pallas_kernels_roofline")
    check("a Mosaic call's bytes: result + operand, not the layout constraints' copy",
          roofline.kernel_bytes(
              '%k.1 = s32[2,8,128]{2,1,0} custom-call(s32[2,8,128]{2,1,0} %a), '
              'custom_call_target="tpu_custom_call", '
              'operand_layout_constraints={s32[2,8,128]{2,1,0}}') == 2 * 2 * 8 * 128 * 4)

    bench = run.load_json(os.path.dirname(HERE), "BENCHMARK.json")
    for w in bench["workloads"]:
        spec = run.load_cell(w["name"])
        check(f"cell {w['name']}: files found, config and chips agree",
              spec["config"]["name"] == w["config"])
        comparison = run.load_by_file("comparisons", spec["config"]["comparison"])
        check(f"cell {w['name']}: comparison {spec['config']['comparison']} gives LIMITS and check_jobs",
              isinstance(comparison.LIMITS, dict) and callable(comparison.check_jobs))
    for m in bench["per_layer"]:
        meta = run.load_json(HERE, "metrics", m["name"] + ".json")
        # which cells report a metric is BENCHMARK.json's alone to say: a later
        # PR adds its cell there and may not edit the metric's file
        check(f"metric {m['name']}: file agrees with BENCHMARK.json",
              all(meta[k] == m[k] for k in m if k != "workloads"))
    return 1 if check.failed else 0


if __name__ == "__main__":
    sys.exit(main())
