# Developer entry points.
#   test            = lint, then tier-1 (fast; chaos excluded via the slow
#                     marker), then tier-2, then the full chaos suite
#   lint            = ctlint static analysis (docs/ANALYSIS.md): the
#                     executor-contract / atomic-write / lock-discipline /
#                     fault-coverage / jit-hygiene / drain-safety rules;
#                     exit 1 on findings (CI gate)
#   tier1           = the fast suite alone
#   tier2           = the slow-marked non-chaos tests: a handful of
#                     compile-heavy e2e variants (~2 min of XLA compiles)
#                     whose coverage overlaps faster tier-1 siblings; kept
#                     out of tier1 so the fast gate stays under its time
#                     budget, still part of `make test`
#   chaos           = the whole fault-injection suite, fixed seed — kills/
#                     resume, the silent-failure scenarios (hang, chunk
#                     corruption, job loss), and the resource-exhaustion /
#                     preemption scenario from the graceful-degradation layer
#   chaos-resource  = only the resource chaos: watershed->graph->multicut
#                     under seeded oom+enospc faults and a real SIGTERM
#                     mid-run (drain -> requeue-exit -> resume), asserting a
#                     bit-identical final segmentation (docs/ROBUSTNESS.md
#                     "Graceful degradation"); tier-1 stays fast because the
#                     chaos+slow markers keep it out of `tier1`
#   failures-report = one-screen post-mortem of a run's failures.json
#                     (pass TMP=/path/to/tmp_folder or .../failures.json),
#                     plus the per-task chunk-IO metrics when recorded and
#                     the trace summary when the run was traced
#                     (CTT_TRACE=1; docs/OBSERVABILITY.md); use
#                     `python scripts/failures_report.py --json TMP` for
#                     the machine-readable combined document
#   progress        = live run status from the heartbeat files and block
#                     markers (pass TMP=/path/to/tmp_folder): per-task
#                     state (done / in-flight / stalled? / failed), blocks
#                     markered, quarantines, stale-heartbeat warnings
#                     (docs/OBSERVABILITY.md); rc 1 when anything is
#                     stalled or failed
#   bench-io        = IO-amplification bench (docs/PERFORMANCE.md
#                     "Chunk-aware I/O"): the halo'd watershed sweep with
#                     the decompressed-chunk cache off vs on, asserting
#                     bit-identical outputs; cpu backend, <60 s
#   bench-fuse      = task-graph-fusion bench (docs/PERFORMANCE.md
#                     "Task-graph fusion"): the watershed->graph->costs->
#                     multicut workflow with in-memory handoffs off vs on,
#                     recording intermediate bytes written, wall time, and
#                     bit-identity into BENCH_r08.json; cpu backend (a
#                     <10 s correctness smoke twin runs inside tier1 via
#                     tests/test_handoff.py)
#   bench-sweep     = dispatch-amortization bench (docs/PERFORMANCE.md
#                     "Sharded sweeps"): per-block dispatch vs one sharded
#                     program per Morton batch at 64^3/16^3, recording
#                     throughput, dispatch counts, and bit-identity into
#                     BENCH_r07.json; cpu backend, <30 s (a <10 s smoke
#                     twin runs inside tier1 via tests/test_sharded.py)
#   bench-ragged    = ragged paged-pool bench (docs/PERFORMANCE.md "Ragged
#                     sweeps"): an edge/split-heavy sweep on a non-pow2
#                     27-block grid (clipped edges + 8 forced degrade-
#                     splits) run per-block vs through the paged block
#                     pool, recording compiled-dispatch counts (>=8x
#                     fewer), ragged-lane attribution, and bit-identity
#                     into BENCH_r11.json; cpu backend, <10 s (a smoke
#                     twin runs inside tier1 via tests/test_ragged.py)
#   bench-device    = device-resident data-plane bench (docs/PERFORMANCE.md
#                     "Device-resident data plane"): the BENCH_r11 ragged
#                     grid swept host-staged vs through the HBM-resident
#                     content-addressed page pool, recording h2d bytes
#                     (warm re-sweeps re-address resident pages), dispatch
#                     wall time, hit/reuse attribution, and bit-identity
#                     into BENCH_r12.json; cpu backend, <10 s (a smoke
#                     twin runs inside tier1 via tests/test_device_plane.py)
#   bench-solve     = distributed-agglomeration bench (docs/PERFORMANCE.md
#                     "Distributed agglomeration"): the >=100k-edge
#                     solver-scale instance solved single-host vs over the
#                     Morton-octant reduce tree (in-process + a 2-worker
#                     multihost group), recording the energy gap (<=0.1%),
#                     determinism, and bit-identity into BENCH_r09.json;
#                     cpu backend, <30 s (a <10 s smoke twin runs inside
#                     tier1 via tests/test_reduce_tree.py)
#   bench-reduce    = collective-reduce-plane bench (docs/PERFORMANCE.md
#                     "Collective reduce plane"): the >=100k-edge instance
#                     solved on the host level engine, the 2-worker
#                     filesystem packet plane, the collective plane (one
#                     jitted program + one all_gather hop per tree level;
#                     >=2x fewer dispatches/level, zero packet files), and
#                     the force-disabled fallback arm (degraded:
#                     packet_plane attributed, bit-identical) into
#                     BENCH_r16.json; cpu backend (a <10 s smoke twin
#                     runs inside tier1 via tests/test_reduce_plane.py)
#   bench-serve     = traffic-shaped service bench (docs/SERVING.md): an
#                     open-loop load generator (Poisson arrivals, mixed
#                     request classes, 2 tenants + an aggressor phase)
#                     against the resident server, recording p50/p99
#                     latency, throughput, the cold-vs-warm split, and
#                     per-tenant fairness into BENCH_r10.json; cpu
#                     backend (a <10 s smoke twin runs inside tier1 via
#                     tests/test_serve.py)
#   bench-fleet     = fleet supervised-traffic bench (docs/SERVING.md
#                     "Supervision"): open-loop Poisson two-tenant traffic
#                     against a supervised 3-member fleet with the GATEWAY
#                     child SIGKILLed mid-arrivals (restarted as
#                     incarnation 2 on the same port, routing view rebuilt
#                     cold from disk) and one member SIGKILLed (adopted by
#                     a survivor AND respawned on a fresh dir, serving
#                     again before the run ends), recording zero lost
#                     acknowledged requests (of >= 30 acked), gateway/
#                     member-kill p99 (within 3x the failover floor:
#                     warm p99 + one restart / detection window), and
#                     bit-identity into BENCH_r15.json; cpu backend,
#                     <90 s (the chaos e2e twin is
#                     tests/test_chaos.py -k fleet)
#   chaos-wedge     = only the gray-failure chaos: SIGSTOP a fleet member
#                     under live traffic — breaker opens, survivor adopts
#                     + mints the fence epoch, SIGCONT'd zombie
#                     self-drains rc 115 with zero double-execution
#   chaos-gateway   = only the supervisor chaos: SIGKILL the gateway child
#                     AND a member under live two-tenant traffic — the
#                     supervisor restarts the gateway as incarnation 2,
#                     every acked request completes with zero client
#                     resubmission, the dead member is adopted AND
#                     respawned on a fresh dir before the drain (rc 114)
#   bench-trajectory= aggregate the BENCH_r07..r16 headline numbers into
#                     one table (stdout + rewritten into docs/PERFORMANCE.md
#                     "Performance trajectory"), so the perf history is
#                     readable without opening ten JSON files
#   serve-smoke     = service-mode smoke (docs/SERVING.md): start the
#                     resident server, submit concurrent tiny workflows
#                     from two tenants, assert both complete with
#                     warm-cache reuse visible in io_metrics; <10 s, cpu
#   scrub-smoke     = self-healing smoke (docs/SERVING.md "Self-healing"):
#                     the <10 s tier-1 twin of the corruption chaos e2e —
#                     an in-process server completes a request, a stored
#                     block is rotted at rest, the scrubber finds and
#                     repairs it from lineage, and the output stays
#                     bit-identical; runs inside tier1 via
#                     tests/test_selfheal.py
#   supervise-demo  = smoke-check recipe: watershed workflow on the
#                     stub-slurm cluster target under an injected job loss,
#                     printing the supervisor's resubmission log
PY ?= python
CTT_CHAOS_SEED ?= 7
TMP ?= /tmp/ctt_run

.PHONY: test lint tier1 tier2 chaos chaos-resource chaos-wedge \
	chaos-gateway \
	failures-report progress \
	bench-io bench-sweep bench-fuse bench-ragged bench-device bench-solve \
	bench-reduce bench-serve bench-fleet \
	bench-trajectory serve-smoke scrub-smoke supervise-demo native clean

test: lint tier1 tier2 chaos

lint:
	$(PY) -m cluster_tools_tpu.lint

tier1:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

tier2:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'slow and not chaos' \
		--continue-on-collection-errors -p no:cacheprovider

chaos:
	JAX_PLATFORMS=cpu CTT_CHAOS_SEED=$(CTT_CHAOS_SEED) \
		$(PY) -m pytest tests/ -q -m chaos -p no:cacheprovider

chaos-resource:
	JAX_PLATFORMS=cpu CTT_CHAOS_SEED=$(CTT_CHAOS_SEED) \
		$(PY) -m pytest tests/test_chaos.py -q -m chaos \
		-k resource -p no:cacheprovider

chaos-wedge:
	JAX_PLATFORMS=cpu CTT_CHAOS_SEED=$(CTT_CHAOS_SEED) \
		$(PY) -m pytest tests/test_chaos.py -q -m chaos \
		-k sigstop -p no:cacheprovider

chaos-gateway:
	JAX_PLATFORMS=cpu CTT_CHAOS_SEED=$(CTT_CHAOS_SEED) \
		$(PY) -m pytest tests/test_chaos.py -q -m chaos \
		-k gateway -p no:cacheprovider

failures-report:
	$(PY) scripts/failures_report.py $(TMP)

progress:
	$(PY) scripts/progress.py $(TMP)

bench-io:
	JAX_PLATFORMS=cpu $(PY) bench.py --io

bench-sweep:
	JAX_PLATFORMS=cpu $(PY) bench.py --sweep

bench-fuse:
	JAX_PLATFORMS=cpu $(PY) bench.py --fuse

bench-ragged:
	JAX_PLATFORMS=cpu $(PY) bench.py --ragged

bench-device:
	JAX_PLATFORMS=cpu $(PY) bench.py --device-plane

bench-solve:
	JAX_PLATFORMS=cpu $(PY) bench.py --solve

bench-reduce:
	JAX_PLATFORMS=cpu $(PY) bench.py --reduce-plane

bench-serve:
	JAX_PLATFORMS=cpu $(PY) bench.py --serve

bench-fleet:
	JAX_PLATFORMS=cpu $(PY) bench.py --fleet

serve-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_serve.py -q \
		-k serve_smoke -p no:cacheprovider

scrub-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_selfheal.py -q \
		-k scrub_smoke -p no:cacheprovider

bench-trajectory:
	$(PY) scripts/bench_trajectory.py --write

supervise-demo:
	JAX_PLATFORMS=cpu $(PY) scripts/supervise_demo.py

native:
	$(MAKE) -C native

clean:
	$(MAKE) -C native clean
