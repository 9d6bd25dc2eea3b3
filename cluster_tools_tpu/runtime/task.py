"""Task runtime: a small DAG engine with idempotent, resumable tasks.

TPU-native replacement for the reference's ``cluster_tools/cluster_tasks.py``
(SURVEY.md §2a "Task runtime"): there, ``BaseClusterTask(luigi.Task)`` mapped
blocks to slurm/LSF/local *jobs* communicating over the shared filesystem,
with success-log targets for resume.  Here there is no external scheduler —
the "cluster" is the device mesh — so the runtime keeps only the parts that
still earn their place:

- the **DAG** of tasks with ``requires()`` and idempotent skip-if-done
  (``luigi.build`` -> :func:`build`),
- the **success-manifest target** per task (resume grain: task), plus
  block-level markers inside a task (resume grain: block, matching the
  reference's ``log_block_success`` / ``clean_up_for_retry`` semantics),
- the **config system**: ``global.config`` + ``<task_name>.config`` JSON files
  in a ``config_dir``, with ``default_task_config()`` per task and
  ``get_config()`` aggregation on workflows (SURVEY.md §5.6),
- the **target trio** pattern: every op module exposes ``<Op>Local`` /
  ``<Op>TPU`` classes (reference: Local/Slurm/LSF) selected by name in
  :class:`WorkflowBase`; the difference is only which devices back the mesh.

Execution of the per-block compute happens inside ``run_impl`` via the
:class:`~cluster_tools_tpu.runtime.executor.BlockwiseExecutor`, which batches
blocks across the mesh — the TPU analogue of ``prepare_jobs``/``submit_jobs``.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
import traceback
from typing import Any, Dict, Iterable, List, Optional, Sequence

from ..utils import function_utils as fu
from ..utils import task_utils as tu
from . import trace as trace_mod


class SuccessTarget:
    """A success manifest file: the task's luigi-style output target.

    Written atomically (temp file + ``os.replace``) and validated on read:
    a kill mid-write must leave either no manifest or the previous one —
    a torn manifest counts as NOT done, so resume re-runs the task instead
    of crashing on (or worse, trusting) half a JSON document.
    """

    def __init__(self, tmp_folder: str, task_name: str):
        self.path = os.path.join(tmp_folder, f"{task_name}.success.json")

    def exists(self) -> bool:
        return fu.read_json_if_valid(self.path) is not None

    def write(self, payload: Optional[Dict[str, Any]] = None):
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        doc = {"time": trace_mod.walltime()}
        if payload:
            doc.update(payload)
        fu.atomic_write_json(self.path, doc, default=tu._default)

    def read(self) -> Dict[str, Any]:
        doc = fu.read_json_if_valid(self.path)
        if doc is None:
            raise FileNotFoundError(
                f"no valid success manifest at {self.path} (missing or torn)"
            )
        return doc


class MemoryTarget:
    """A typed in-memory output target (docs/PERFORMANCE.md "Task-graph
    fusion"): the declaration that a task's output lives in host RAM,
    keyed by the dataset/artifact identity a storage consumer would have
    opened, with spill-to-storage as the universal fallback.

    Declared through :meth:`BaseTask.handoff_dataset` (chunked volumes) or
    :meth:`BaseTask.save_handoff_arrays` (npz/npy artifacts); backed by the
    process-wide registry in :mod:`cluster_tools_tpu.runtime.handoff`.  The
    task's success manifest records one entry per target (``stored`` True
    when it spilled), and :meth:`BaseTask.complete` treats a memory-only
    manifest whose handle is gone — a process restart — as NOT done, so the
    DAG re-runs the producer instead of handing consumers a hole.
    """

    def __init__(self, entry):
        self.entry = entry

    @property
    def identity(self) -> str:
        return self.entry.identity

    def live(self) -> bool:
        """True while the payload is resident (and not spilled)."""
        return not self.entry.spilled and self.entry.obj is not None

    def stored(self) -> bool:
        """True once the payload has a storage copy (spilled)."""
        return bool(self.entry.spilled)


class BaseTask:
    """Base of all tasks.  Subclasses set ``task_name`` and define
    ``run_impl()``; backend subclasses (``<Op>Local`` / ``<Op>TPU``) only pin
    the execution ``target``.

    Common parameters mirror the reference: ``tmp_folder`` (scratch +
    markers), ``config_dir`` (JSON configs), ``max_jobs`` (here: max
    concurrent device batches / host IO workers).
    """

    task_name: str = "base"
    target: str = "local"  # backend: 'local' (CPU devices) or 'tpu'
    #: keys of ``run_impl()``'s result that go into ``io_metrics.json`` too,
    #: with the counters the base class gathers
    io_metrics_keys: tuple = ()

    def __init__(
        self,
        tmp_folder: str,
        config_dir: str,
        max_jobs: int = 1,
        dependencies: Optional[Sequence["BaseTask"]] = None,
        **params: Any,
    ):
        self.tmp_folder = tmp_folder
        self.config_dir = config_dir
        self.max_jobs = int(max_jobs)
        self.dependencies = list(dependencies or [])
        self.params = params
        os.makedirs(tmp_folder, exist_ok=True)
        # task identity includes a parameter hash (as luigi's did), so two
        # differently-parameterized instances of one task class in the same
        # tmp_folder get distinct targets, markers, and DAG-dedup keys
        h = hashlib.sha256(
            json.dumps(
                {"params": params, "target": self.target}, sort_keys=True, default=str
            ).encode()
        ).hexdigest()[:8]
        self.uid = f"{self.task_name}.{h}"
        self.logger = fu.get_logger(
            self.uid, os.path.join(tmp_folder, f"{self.uid}.log")
        )
        # in-memory output targets declared during run_impl (docs/
        # PERFORMANCE.md "Task-graph fusion"); finalized into the success
        # manifest by run()
        self._memory_targets: List[MemoryTarget] = []

    # -- config ------------------------------------------------------------
    @staticmethod
    def default_task_config() -> Dict[str, Any]:
        return {"threads_per_job": 1, "device_batch": 1}

    @staticmethod
    def default_retry_config() -> Dict[str, Any]:
        """Fault-tolerance knobs honored for every task (docs/ROBUSTNESS.md):
        ``max_retries`` task-level re-runs in :func:`build` (0 = fail fast),
        ``retry_backoff_s`` base of the capped exponential task backoff,
        ``io_retries`` / ``io_backoff_s`` per-block load/store retries inside
        :class:`~cluster_tools_tpu.runtime.executor.BlockwiseExecutor`,
        ``io_threads`` the executor's host IO pool width (None = derive
        from ``max_jobs``, the historical default), ``block_schedule`` the
        sweep order (``"morton"`` Z-order locality scheduling for the
        decompressed-chunk cache, ``"given"`` to keep grid order),
        ``sweep_mode`` the executor dispatch shape (``"auto"`` — sharded
        when the mesh has >= 2 devices or the sweep fills a sharded batch —
        ``"sharded"``: one compiled program per Morton batch over the
        device mesh, or ``"per_block"``: the historical
        one-dispatch-per-block path; docs/PERFORMANCE.md "Sharded
        sweeps") with ``sharded_batch`` the blocks per sharded program
        (None = auto),
        ``block_deadline_s`` / ``watchdog_period_s`` the hung-block deadline
        + speculative re-execution (None disables), the cluster-target
        supervision knobs ``heartbeat_interval_s`` / ``heartbeat_timeout_s``
        / ``max_resubmits`` / ``max_preempt_resubmits``
        (``runtime/cluster.py``), and the graceful-degradation knobs
        ``allow_block_split`` (OOM'd blocks re-execute as halo-correct
        sub-blocks — only for shape-local kernels, see the executor's
        ``splittable`` contract), ``min_block_shape`` (split floor),
        ``degrade_wait_s`` (bounded headroom wait before a degrade
        re-attempt) and ``inflight_byte_budget`` (admission cap; None =
        auto from MemAvailable, 0 = off).  ``memory_handoffs`` (default
        off) enables task-graph fusion (docs/PERFORMANCE.md): intermediate
        outputs declared through :meth:`handoff_dataset` /
        :meth:`save_handoff_arrays` stay in host RAM and downstream tasks
        consume them without a storage round-trip, with spill-to-storage
        (byte-budget admission, headroom probes, forced ``spill`` faults)
        as the universal fallback.  ``device_pool`` (``"auto"``/``"on"``/
        ``"off"``) and ``device_pool_bytes`` drive the HBM-resident page
        pool on ragged sweeps, and ``device_handoffs`` (default off) keeps
        :meth:`save_handoff_device_arrays` outputs resident in device
        memory for fused consumers — the device-resident data plane
        (docs/PERFORMANCE.md), with host staging / the memory rung as the
        ladder below and ``CTT_DEVICE_POOL=0`` as the kill switch.
        ``solver_shards`` / ``reduce_fanout`` /
        ``solver_workers`` shard the global agglomeration/multicut solve
        over an octant reduce tree (docs/PERFORMANCE.md "Distributed
        agglomeration"; ``parallel/reduce_tree.py``): ``solver_shards=1``
        keeps today's single-host solve, ``>1`` partitions the graph by
        Morton block octants, runs frontier-aware contraction per shard,
        and merges boundary edges up a ``reduce_fanout``-ary tree —
        in-process, or over a ``solver_workers``-process multihost worker
        group; any sharded failure degrades back to the single-host solve
        (``degraded:unsharded_solve`` in failures.json)."""
        return {
            "max_retries": 0,
            "retry_backoff_s": 1.0,
            "io_retries": 2,
            "io_backoff_s": 0.05,
            "io_threads": None,
            "block_schedule": "morton",
            "sweep_mode": "auto",
            "sharded_batch": None,
            "block_deadline_s": None,
            "watchdog_period_s": None,
            "heartbeat_interval_s": 5.0,
            "heartbeat_timeout_s": 0.0,
            "max_resubmits": 2,
            "max_preempt_resubmits": 3,
            "allow_block_split": False,
            "min_block_shape": None,
            "degrade_wait_s": 5.0,
            "inflight_byte_budget": None,
            "memory_handoffs": False,
            "device_pool": "auto",
            "device_pool_bytes": None,
            "device_handoffs": False,
            "solver_shards": 1,
            "reduce_fanout": 2,
            "solver_workers": 1,
        }

    @staticmethod
    def default_global_config() -> Dict[str, Any]:
        return {
            "block_shape": [64, 64, 64],
            "roi_begin": None,
            "roi_end": None,
            "halo": None,
        }

    def get_config(self) -> Dict[str, Any]:
        defaults = dict(self.default_global_config())
        defaults.update(self.default_retry_config())
        defaults.update(self.default_task_config())
        config = tu.load_task_config(self.config_dir, self.task_name, defaults)
        config.update(self.params)
        return config

    # -- DAG protocol ------------------------------------------------------
    def requires(self) -> List["BaseTask"]:
        return self.dependencies

    def output(self) -> SuccessTarget:
        return SuccessTarget(self.tmp_folder, self.uid)

    def run_impl(self):  # pragma: no cover - abstract
        raise NotImplementedError

    def run(self):
        from . import faults as faults_mod
        from . import handoff as handoff_mod
        from ..io import chunk_cache

        from . import executor as executor_mod

        from ..ops import contraction as contraction_mod
        from ..ops import rag as rag_mod
        from ..parallel import device_pool as device_pool_mod
        from ..parallel import reduce_tree as reduce_tree_mod
        from ..parallel import step_cache as step_cache_mod

        self.logger.info(f"start {self.task_name} (target={self.target})")
        # unified tracing plane (docs/OBSERVABILITY.md): every task of a run
        # shards its spans into <tmp_folder>/trace/; first writer pins the
        # directory, an operator CTT_TRACE=<dir> pin always wins
        if trace_mod.enabled():
            trace_mod.set_trace_dir(
                os.path.join(self.tmp_folder, trace_mod.TRACE_DIRNAME)
            )
        # the task.run span doubles as the runtime_s clock (CT008: trace
        # spans are the one timing source in runtime/) and carries the
        # dependency uids the trace aggregator's critical path walks.
        # Entered as a context, it also opens the profiler annotation that
        # ties the ring's monotonic clock to a device trace's
        run_span = trace_mod.begin(
            "task.run", task=self.uid, task_name=self.task_name,
            deps=[d.uid for d in self.dependencies],
        )
        try:
            with run_span:
                # fault specs with a "tasks" filter target the running
                # task's uid
                faults_mod.set_current_task(self.uid)
                io_snap = chunk_cache.snapshot()
                disp_snap = executor_mod.dispatch_snapshot()
                handoff_snap = handoff_mod.snapshot()
                device_snap = device_pool_mod.snapshot()
                solver_snap = contraction_mod.solver_snapshot()
                rag_snap = rag_mod.dispatch_snapshot()
                tree_snap = reduce_tree_mod.solve_snapshot()
                compile_snap = trace_mod.compile_snapshot()
                step_snap = step_cache_mod.totals()
                try:
                    result = self.run_impl() or {}
                    # finalize in-memory targets INSIDE the task context:
                    # forced `spill` faults filter on the producing task's uid
                    handoff_records = self._finalize_handoffs()
                finally:
                    faults_mod.set_current_task(None)
        except BaseException:
            # a failing task still leaves its spans behind: the error'd
            # task.run span and everything below it flush now, so the
            # timeline of a crashed run shows exactly where it died
            self._flush_trace()
            raise
        # everything after run_impl returned (the counters' movement into
        # the manifest and io_metrics.json, the success target, the trace
        # flush) is the task.finalize span; it follows task.run, whose
        # seconds stay the task's own work
        with trace_mod.span("task.finalize", task=self.uid):
            result["runtime_s"] = run_span.elapsed_s
            result["target"] = self.target
            if handoff_records:
                # the DAG engine's resume contract (complete()): a memory-only
                # record whose handle died with this process re-runs the task
                result["handoffs"] = handoff_records
            # chunk-IO + dispatch + handoff attribution: the counters' movement
            # during this task, surfaced in the success manifest AND merged
            # (additively, across resumed runs and cluster job processes) into
            # the run-wide io_metrics.json next to failures.json — so the
            # sharded sweep's dispatch amortization and the fusion layer's
            # avoided storage round-trips are observable per task
            # (docs/PERFORMANCE.md "Sharded sweeps" / "Task-graph fusion")
            io_metrics = chunk_cache.delta(io_snap)
            dispatch_metrics = executor_mod.dispatch_delta(disp_snap)
            if any(dispatch_metrics.values()):
                io_metrics.update(dispatch_metrics)
            handoff_metrics = handoff_mod.delta(handoff_snap)
            if any(handoff_metrics.values()):
                io_metrics.update(handoff_metrics)
            # device-plane attribution (docs/PERFORMANCE.md "Device-resident
            # data plane"): h2d/d2h traffic, resident-pool hit rates, and the
            # bytes fused consumers never re-staged, per task
            device_metrics = device_pool_mod.delta(device_snap)
            if any(device_metrics.values()):
                io_metrics.update(device_metrics)
            # solver attribution: contraction-engine calls/rounds/edge counts
            # plus the reduce tree's per-level solve/merge movement, so the
            # global solve is as observable as the I/O and dispatch paths
            # (docs/PERFORMANCE.md "Distributed agglomeration")
            solver_metrics = contraction_mod.solver_delta(solver_snap)
            if any(solver_metrics.values()):
                io_metrics.update(solver_metrics)
            tree_metrics = reduce_tree_mod.solve_delta(tree_snap)
            if any(tree_metrics.values()):
                io_metrics.update(tree_metrics)
            # device programs of the RAG extraction this task dispatched
            # (docs/OBSERVABILITY.md "Multicut")
            rag_metrics = rag_mod.dispatch_delta(rag_snap)
            if any(rag_metrics.values()):
                io_metrics.update(rag_metrics)
            # did this task trace, lower, compile or read a program back, and
            # which (docs/OBSERVABILITY.md "Compiles")
            compile_metrics = trace_mod.compile_delta(compile_snap)
            if any(compile_metrics.values()):
                io_metrics["compile"] = compile_metrics
            # which level gave a mesh step to this task: process, store or a
            # build (docs/OBSERVABILITY.md "The step cache")
            step_metrics = step_cache_mod.delta(step_snap)
            if step_metrics:
                io_metrics["step_cache"] = step_metrics
            io_metrics.update(
                {k: result[k] for k in self.io_metrics_keys if k in result}
            )
            if any(io_metrics.values()):
                result["io_metrics"] = io_metrics
                try:
                    fu.record_io_metrics(
                        fu.io_metrics_path(self.tmp_folder), self.uid, io_metrics
                    )
                except Exception:
                    self.logger.warning(
                        f"io_metrics recording failed:\n{traceback.format_exc()}"
                    )
            self.output().write(result)
            # flush this process's trace shard and (re)stitch the run timeline
            # so trace.json + trace_summary.json track the run as it executes;
            # the restitch re-reads every shard, so it is throttled to once per
            # MERGE_MIN_INTERVAL_S per process — build() always merges at the
            # end, so the finished timeline is current regardless
            self._flush_trace(merge=True)
        self.logger.info(
            f"done {self.task_name} in {result['runtime_s']:.2f}s"
        )

    def _flush_trace(self, merge: bool = False) -> None:
        """Best-effort trace shard flush (+ optional timeline re-merge):
        observability must never fail a run."""
        if not trace_mod.enabled():
            return
        try:
            trace_mod.flush()
            if merge:
                trace_mod.write_timeline(
                    self.tmp_folder,
                    min_interval_s=trace_mod.MERGE_MIN_INTERVAL_S,
                )
        except Exception:
            self.logger.warning(
                f"trace flush failed:\n{traceback.format_exc()}"
            )

    # -- block-level resume helpers ---------------------------------------
    def blocks_done(self) -> List[int]:
        # markers stamped by ANOTHER process's in-memory run describe data
        # that died with it (docs/PERFORMANCE.md "Task-graph fusion") —
        # cleared here regardless of how THIS run stores its output
        from . import handoff

        if handoff.invalidate_stale_markers(self.tmp_folder, self.uid):
            self.logger.info(
                f"{self.task_name}: cleared block markers from a previous "
                "process's in-memory run (outputs no longer exist)"
            )
        return fu.blocks_done(self.tmp_folder, self.uid)

    def log_block_success(self, block_id: int):
        fu.log_block_success(self.tmp_folder, self.uid, block_id)

    @property
    def failures_path(self) -> str:
        """The run's shared ``failures.json`` manifest (docs/ROBUSTNESS.md)."""
        return fu.failures_path(self.tmp_folder)

    def clean_up_for_retry(self):
        """Clear stale partial state before a re-run (the reference's
        ``clean_up_for_retry``): job-level markers go; valid block-level
        markers are kept — the re-run resumes at block grain.  Torn block
        markers are pruned as a side effect of :meth:`blocks_done`."""
        fu.clean_up_for_retry(self.tmp_folder, self.uid)
        self.blocks_done()

    # -- in-memory handoff targets (docs/PERFORMANCE.md "Task-graph fusion") --
    def _handoffs_on(self) -> bool:
        """Task-graph fusion applies when the ``memory_handoffs`` config
        knob is set, the process-level kill switch (``CTT_HANDOFF``) is on,
        and the task does not cross a host boundary (cluster targets run
        their payload in a separate process whose memory dies before the
        submitter-side consumer runs)."""
        if self.target in _CLUSTER_TARGETS:
            return False
        from . import handoff

        if not handoff.handoff_enabled():
            return False
        try:
            cfg = self.get_config()
        except Exception:
            return False
        return bool(cfg.get("memory_handoffs", False))

    def declare_handoff_producer(self) -> bool:
        """Call at the top of ``run_impl`` in tasks that publish
        *artifact* handoffs from block-grain work (per-block npz/npy
        writers under :meth:`host_block_map`): returns whether handoffs
        are on, and stamps this task's marker directory with the process
        token — any later run in a different process (whatever its knob
        or spill path) clears the markers before trusting them, because
        the data they describe dies with this process
        (:func:`~cluster_tools_tpu.runtime.handoff.invalidate_stale_markers`,
        checked inside :meth:`blocks_done`).  Dataset producers get the
        same guard from :meth:`handoff_dataset`.
        """
        if not self._handoffs_on():
            return False
        from . import handoff

        handoff.invalidate_stale_markers(self.tmp_folder, self.uid)
        handoff.mark_memory_producer(self.tmp_folder, self.uid)
        return True

    def handoff_dataset(self, path, key, shape, chunks, dtype,
                        fill_value: int = 0):
        """Declare a chunked-volume output as a :class:`MemoryTarget` and
        return the dataset to write through.

        With handoffs off (the default) this is exactly
        ``file_reader(path).require_dataset(...)`` — the storage path,
        bit-for-bit.  With handoffs on, the returned dataset is the
        in-memory ``memory://`` twin
        (:class:`~cluster_tools_tpu.io.containers.HandoffDataset`) unless
        the target spills at birth (byte-budget admission, a forced
        ``spill`` fault, or a spilled predecessor at the same identity) —
        then it is the real storage dataset and every write lands
        checksummed as usual.

        Contract (docs/ANALYSIS.md CT007): a declaring call site must pass
        the full spill wiring — ``path``/``key`` plus the ``shape`` /
        ``chunks`` / ``dtype`` needed to create the storage twin — and the
        module must wire the returned handle into a post-store
        ``region_verifier`` so integrity verification covers the in-memory
        data plane too.
        """
        from ..utils.volume_utils import file_reader
        from . import handoff

        if not self._handoffs_on():
            # a previous run's live payload at this identity must not
            # shadow the fresh STORAGE bytes this run is about to write
            handoff.discard(handoff.dataset_identity(path, key))
            return file_reader(path).require_dataset(
                key, shape=shape, chunks=chunks, dtype=dtype
            )

        # markers stamped by a previous process's in-memory run are stale
        # on EVERY acquire path — including spill-at-birth, whose storage
        # twin starts empty where those markers claim blocks are done
        handoff.invalidate_stale_markers(self.tmp_folder, self.uid)
        ds, entry = handoff.acquire_dataset(
            path, key, shape=shape, chunks=chunks, dtype=dtype,
            producer=self.uid, failures_path=self.failures_path,
            fill_value=fill_value,
        )
        self._memory_targets.append(MemoryTarget(entry))
        if not entry.spilled:
            # output lives in THIS process's memory: stamp the markers so
            # any later process invalidates them before trusting them
            handoff.mark_memory_producer(self.tmp_folder, self.uid)
        return ds

    def save_handoff_arrays(self, path, **arrays):
        """Publish named arrays as the artifact a storage consumer would
        have loaded from ``path`` (npz).  With handoffs off this is a plain
        ``np.savez`` — today's behavior.  With handoffs on the arrays stay
        in host RAM (read-only) unless admission or a forced ``spill``
        fault writes the file (+ CRC sidecar) through."""
        from . import handoff

        if not self._handoffs_on():
            import numpy as np

            # drop any previous run's live payload AND spill sidecar for
            # this identity: the plain file this run writes is the truth,
            # and a stale CRC would flag the fresh bytes as corruption
            handoff.forget_artifact(path)
            np.savez(path, **arrays)
            return
        entry = handoff.publish_arrays(
            path, arrays, producer=self.uid,
            failures_path=self.failures_path,
        )
        self._memory_targets.append(MemoryTarget(entry))

    def save_handoff_array(self, path, array):
        """Single-array (`.npy`) twin of :meth:`save_handoff_arrays`."""
        from . import handoff

        if not self._handoffs_on():
            import numpy as np

            handoff.forget_artifact(path)
            np.save(path, array)
            return
        entry = handoff.publish_arrays(
            path, {"data": array}, producer=self.uid,
            failures_path=self.failures_path,
        )
        self._memory_targets.append(MemoryTarget(entry))

    def _device_handoffs_on(self) -> bool:
        """Device-rung handoffs: the ``device_handoffs`` config knob on
        top of everything :meth:`_handoffs_on` already requires, plus the
        ``CTT_DEVICE_POOL`` process kill switch."""
        if not self._handoffs_on():
            return False
        from ..parallel import device_pool

        if not device_pool.device_pool_enabled():
            return False
        try:
            cfg = self.get_config()
        except Exception:
            return False
        return bool(cfg.get("device_handoffs", False))

    def save_handoff_device_arrays(self, path, **arrays):
        """Device-rung twin of :meth:`save_handoff_arrays`
        (docs/PERFORMANCE.md "Device-resident data plane"): with
        ``device_handoffs`` on, the named arrays (jax arrays stay
        resident; host arrays are uploaded) live in DEVICE memory under
        the artifact identity, and a fused consumer's
        :func:`~cluster_tools_tpu.runtime.handoff.resolve_device_arrays`
        serves them without a single host byte.  The ladder below is
        automatic: the knob (or kill switch) off lands on the memory rung
        / plain npz exactly like :meth:`save_handoff_arrays`, and a
        resource failure at publish falls back to the memory rung
        attributed ``degraded:host_staged``.

        Contract (docs/ANALYSIS.md CT007): a device-handoff declaration
        must carry its spill wiring — the registry needs ``producer`` and
        ``failures_path`` to demote, spill, and attribute without the
        task on the stack; this method passes both."""
        from . import handoff

        if not self._device_handoffs_on():
            import numpy as np

            # jax payloads land on host here — the one d2h the ladder costs
            return self.save_handoff_arrays(path, **{
                k: np.asarray(v) for k, v in arrays.items()
            })
        entry = handoff.publish_device_arrays(
            path, arrays, producer=self.uid,
            failures_path=self.failures_path,
        )
        self._memory_targets.append(MemoryTarget(entry))

    def save_handoff_device_array(self, path, array):
        """Single-array (`.npy`) twin of
        :meth:`save_handoff_device_arrays`."""
        from . import handoff

        if not self._device_handoffs_on():
            import numpy as np

            return self.save_handoff_array(path, np.asarray(array))
        entry = handoff.publish_device_arrays(
            path, {"data": array}, producer=self.uid,
            failures_path=self.failures_path,
        )
        self._memory_targets.append(MemoryTarget(entry))

    def _finalize_handoffs(self) -> List[Dict[str, Any]]:
        """Mark this run's declared targets complete; returns the manifest
        records :meth:`complete` validates on resume.  Runs while the fault
        injector's current-task context is still set, so ``spill`` faults
        can target tasks."""
        if not self._memory_targets:
            return []
        from . import handoff

        return handoff.finalize_task(self._memory_targets, self.uid)

    def complete(self) -> bool:
        """Luigi-style completeness with handoff resolution: the success
        manifest must exist AND every memory-only output it records must
        still be live in this process's registry.  A memory-only manifest
        whose handle is gone (process restart) is invalidated — manifest
        and block markers removed — so the DAG re-runs the producer
        instead of handing consumers a hole; spilled outputs stay complete
        because storage holds the (checksummed) truth."""
        doc = fu.read_json_if_valid(self.output().path)
        if doc is None:
            return False
        stale = [h for h in doc.get("handoffs", []) if not h.get("stored")]
        if stale:
            from . import handoff

            # resolvable = live in memory OR spilled since the manifest
            # was written (a post-completion headroom spill leaves a valid
            # checksummed storage copy — not a reason to recompute).  Under
            # service mode the identity must also belong to THIS request's
            # namespace: a resubmitted request must never trust a manifest
            # whose memory-only outputs live under a previous request's id
            # (its consumers resolve through the new namespace and would
            # find a hole) — docs/SERVING.md.
            stale = [
                h for h in stale
                if not (
                    handoff.in_current_namespace(h.get("identity"))
                    and handoff.is_resolvable(h.get("identity"))
                )
            ]
        if not stale:
            return True
        self.logger.info(
            f"{self.task_name}: {len(stale)} memory-only handoff output(s) "
            "no longer live (process restart?) — re-running the task"
        )
        try:
            os.remove(self.output().path)
        except OSError:
            pass
        fu.clear_block_markers(self.tmp_folder, self.uid)
        return False

    def host_block_map(
        self,
        block_ids: Sequence[int],
        process,
        store_verify_fn=None,
        blocking=None,
    ) -> int:
        """Run ``process(block_id)`` for every block without a success
        marker, on the host IO thread pool, marking each success.

        The common scaffold of host-side blockwise tasks (thin-slab scans,
        relabel writes, artifact dumps): resume-filtering, pooling, and
        error propagation live here so every task behaves identically.
        All failures are surfaced (not just the first): every failed block
        is recorded in ``failures.json`` (same schema as the executor's,
        tracebacks capped) and a RuntimeError lists every failed block id.
        Returns the number of blocks run.

        Hardened-executor knobs (docs/ROBUSTNESS.md, docs/ANALYSIS.md
        CT001): the per-block retry budget (``io_retries`` /
        ``io_backoff_s``), the hung-block deadline (``block_deadline_s`` /
        ``watchdog_period_s``) and the sweep order (``block_schedule``) are
        *derived from the task config* — call sites never re-plumb them
        (the declarative-wiring direction of ROADMAP item 5).  The two
        wirings that cannot be derived come from the call site: a
        ``store_verify_fn(block)`` post-store integrity check (build it
        with :func:`~cluster_tools_tpu.runtime.executor.region_verifier`;
        verification failures retry, so a corrupt chunk is repaired by the
        re-run while the writer still owns the block) and the ``blocking``
        (which resolves block ids to geometry for the verifier and enables
        the Morton locality schedule).  Resource-classified failures
        (OOM/ENOSPC) skip the same-size retries — re-running the exact
        allocation that just failed only burns the budget.
        """
        from . import admission as admission_mod
        from .supervision import (
            DrainInterrupt,
            Watchdog,
            drain_reason,
            drain_requested,
        )
        from .executor import classify_resource_error, morton_order

        try:
            cfg = self.get_config()
        except Exception:
            cfg = {}
        io_retries = max(0, int(cfg.get("io_retries", 2) or 0))
        io_backoff = float(cfg.get("io_backoff_s", 0.05) or 0.0)
        deadline = float(cfg.get("block_deadline_s") or 0.0)
        period = cfg.get("watchdog_period_s")
        schedule = str(cfg.get("block_schedule") or "morton")

        done = set(self.blocks_done())
        todo = [b for b in block_ids if b not in done]
        if blocking is not None and schedule == "morton":
            # same Z-order locality scheduling as the device executor:
            # consecutive blocks share boundary chunks while they are
            # still resident in the decompressed-chunk cache
            todo = [
                int(b.block_id)
                for b in morton_order([blocking.get_block(i) for i in todo])
            ]
        errors: List[tuple] = []
        skipped_for_drain: List[int] = []
        hung: Dict[int, str] = {}
        completed: set = set()
        watchdog: Optional[Watchdog] = None
        if deadline > 0:
            def _on_hung(token, info, elapsed):
                hung[int(info["block_id"])] = (
                    f"block exceeded block_deadline_s={deadline:g}s on the "
                    f"host path ({elapsed:.2f}s elapsed)"
                )

            watchdog = Watchdog(
                deadline,
                float(period) if period else max(0.02, deadline / 4.0),
                _on_hung,
            ).start()

        # service mode (docs/SERVING.md): the ambient request context is
        # thread-local, but process() may publish block-grain artifact
        # handoffs from THIS pool's worker threads — capture the context
        # here and re-enter it per block, or those identities would lose
        # their request namespace and concurrent requests over the same
        # dataset paths could resolve each other's intermediates
        req_ctx = admission_mod.current_request()

        def wrapped(block_id):
            if drain_requested():
                # drain latch flipped (SIGTERM): stop claiming blocks; the
                # ones already processed keep their markers for the resume
                skipped_for_drain.append(block_id)
                return
            last_tb, attempts = None, 0
            # the span covers the whole retry ladder — the latency an
            # operator chases is time-to-markered, not per-attempt time
            with admission_mod.request_scope(req_ctx), trace_mod.span(
                "host.block", block=int(block_id), task=self.uid
            ):
                for k in range(io_retries + 1):
                    attempts = k + 1
                    if watchdog is not None:
                        watchdog.register(
                            (block_id, k), block_id=int(block_id), stage="host"
                        )
                    try:
                        process(block_id)
                        if store_verify_fn is not None and blocking is not None:
                            # post-store integrity check: a corruption
                            # raises, and the retry re-runs process ->
                            # re-writes the block -> repairs the corrupt
                            # chunk
                            store_verify_fn(blocking.get_block(block_id))
                    except Exception as e:
                        last_tb = fu.cap_traceback(traceback.format_exc())
                        if classify_resource_error(e) is not None:
                            break  # same-size retries re-run the failed alloc
                        if k < io_retries:
                            time.sleep(fu.backoff_delay(k, io_backoff, 5.0))
                    else:
                        completed.add(block_id)
                        self.log_block_success(block_id)
                        if store_verify_fn is not None and blocking is not None:
                            # self-healing lineage (runtime/repair.py): a
                            # verified host-path store registers its
                            # recompute — re-run process() and re-verify —
                            # so read-time/scrub corruption of this block
                            # heals without an operator.  Best effort.
                            try:
                                from . import repair as repair_mod

                                ds = getattr(
                                    store_verify_fn, "dataset", None
                                )
                                blk = blocking.get_block(block_id)
                                bb_of = getattr(
                                    store_verify_fn, "bb_of", None
                                ) or (lambda b: b.bb)
                                if ds is not None:
                                    def recompute(b=block_id):
                                        process(b)
                                        store_verify_fn(
                                            blocking.get_block(b)
                                        )

                                    repair_mod.register_producer(
                                        ds, bb_of(blk), recompute,
                                        task=self.uid,
                                        block_id=int(block_id),
                                        failures_path=self.failures_path,
                                    )
                            except Exception:
                                pass
                        return
                    finally:
                        if watchdog is not None:
                            watchdog.clear((block_id, k))
            trace_mod.instant(
                "fault:host", block=int(block_id), task=self.uid
            )
            errors.append((block_id, last_tb, attempts))

        from concurrent.futures import ThreadPoolExecutor

        try:
            with ThreadPoolExecutor(max_workers=max(1, self.max_jobs)) as pool:
                list(pool.map(wrapped, todo))
        finally:
            if watchdog is not None:
                watchdog.stop()
        records = [
            {
                "block_id": int(b),
                "sites": {"host": int(attempts)},
                "error": tb,
                "quarantined": False,
                "resolved": False,
            }
            for b, tb, attempts in sorted(errors)
        ]
        records += [
            {
                "block_id": int(b),
                "sites": {"hung": 1},
                "error": msg,
                "quarantined": False,
                # a hung block that eventually finished (and markered) is
                # resolved; one that never did is the operator's to chase
                "resolved": b in completed,
            }
            for b, msg in sorted(hung.items())
            if not any(b == eb for eb, _, _ in errors)
        ]
        if records:
            fu.record_failures(self.failures_path, self.uid, records)
        if skipped_for_drain:
            # a drain outranks block errors: the requeued run retries them
            # anyway, and burning task-level retries on a preemption would
            # turn a graceful eviction into a spurious failure
            raise DrainInterrupt(
                drain_reason() or "drain requested",
                skipped_for_drain + [b for b, _, _ in errors],
            )
        if errors:
            failed_ids = sorted(b for b, _, _ in errors)
            detail = "\n".join(
                f"-- block {b} --\n{tb}" for b, tb, _ in errors[:5]
            )
            raise RuntimeError(
                f"{self.task_name}: {len(errors)}/{len(todo)} blocks failed "
                f"(ids: {failed_ids}); see {self.failures_path}; "
                f"first tracebacks:\n{detail}"
            )
        return len(todo)


class DummyTask(BaseTask):
    """No-op dependency placeholder (reference: ``DummyTask``)."""

    task_name = "dummy"

    def __init__(self, tmp_folder: str = "/tmp/ctt_dummy", config_dir: str = "", **kw):
        super().__init__(tmp_folder, config_dir, **kw)

    def run_impl(self):
        return {}


_TARGET_SUFFIX = {"local": "Local", "tpu": "TPU"}
_CLUSTER_TARGETS = ("slurm", "lsf")


def _check_target(target: str) -> None:
    if target not in _TARGET_SUFFIX and target not in _CLUSTER_TARGETS:
        raise ValueError(
            f"unknown target {target!r}, expected one of "
            f"{sorted(_TARGET_SUFFIX) + list(_CLUSTER_TARGETS)}"
        )


def get_task_cls(module, base_name: str, target: str):
    """Resolve ``<Op><Target>`` in an op module (reference: ``WorkflowBase``'s
    ``getattr(module, name + 'Local'/'Slurm'/'LSF')``).

    ``slurm``/``lsf`` targets are synthesized on demand: the task's Local
    variant wrapped into a batch-submitting class (``runtime/cluster.py``)
    — every task gains the cluster backends without per-module
    boilerplate.  Compute-side workloads should still run on the mesh;
    the cluster targets exist for ingest (SURVEY.md §7 L2' note).
    """
    _check_target(target)
    if target in _CLUSTER_TARGETS:
        from .cluster import make_cluster_task

        local_cls = getattr(module, base_name + "Local")
        return make_cluster_task(local_cls, target)
    return getattr(module, base_name + _TARGET_SUFFIX[target])


class WorkflowBase(BaseTask):
    """Base for workflow tasks: selects backend classes by ``target`` and
    chains sub-tasks (reference: ``WorkflowBase`` in workflows.py)."""

    task_name = "workflow"

    def __init__(self, *args, target: str = "local", **kwargs):
        _check_target(target)
        # set before super().__init__ so the uid hash sees the real target
        self.target = target
        super().__init__(*args, **kwargs)

    def run_impl(self):
        return {}


def _task_retry_knobs(task: BaseTask) -> tuple:
    """(max_retries, backoff_base_s) from the task's config; tolerant of
    tasks whose config cannot be loaded (defaults: fail fast)."""
    try:
        cfg = task.get_config()
        return (
            int(cfg.get("max_retries", 0) or 0),
            float(cfg.get("retry_backoff_s", 1.0) or 0.0),
        )
    except Exception:
        return 0, 1.0


def _run_with_retries(task: BaseTask) -> bool:
    """One task to completion: ``max_retries`` re-runs with capped
    exponential backoff, clearing stale partial state between attempts
    (``clean_up_for_retry`` — valid block markers survive, so each retry
    resumes at block grain rather than recomputing the task from scratch)."""
    max_retries, backoff = _task_retry_knobs(task)
    for attempt in range(max_retries + 1):
        if attempt:
            delay = fu.backoff_delay(attempt - 1, backoff, 60.0)
            task.logger.warning(
                f"retry {attempt}/{max_retries} for {task.task_name} "
                f"after {delay:.1f}s backoff"
            )
            try:
                task.clean_up_for_retry()
            except Exception:
                task.logger.warning(
                    f"clean_up_for_retry failed:\n{traceback.format_exc()}"
                )
            time.sleep(delay)
        try:
            task.run()
        except Exception:
            task.logger.error(
                f"task {task.task_name} failed (attempt {attempt + 1}/"
                f"{max_retries + 1}):\n{traceback.format_exc()}"
            )
            continue
        if task.output().exists():
            return True
        task.logger.error(f"task {task.task_name} produced no target")
    return False


def build(tasks: Sequence[BaseTask], rerun: bool = False) -> bool:
    """Run a task DAG to completion (reference: ``luigi.build``).

    Topologically executes ``requires()`` dependencies first, skipping tasks
    whose success target already exists (idempotent resume).  A failed task
    (after its ``max_retries`` re-runs) does NOT abort the DAG: only its
    downstream dependents are skipped, independent branches keep running —
    one bad branch no longer throws away hours of progress elsewhere, and
    the manifests it did produce still shrink the eventual re-run.  Returns
    True only if every task succeeded (matching luigi's boolean contract).

    Preemption (docs/ROBUSTNESS.md "Graceful degradation"): once the drain
    latch is flipped (SIGTERM/SIGUSR1), no further task starts and
    :class:`~cluster_tools_tpu.runtime.supervision.DrainInterrupt`
    propagates — it is a ``BaseException``, so the per-task retry loop
    cannot mistake a preemption for a flaky task.  Finished tasks keep
    their manifests; the requeued run resumes behind them.
    """
    from .supervision import DrainInterrupt, drain_reason, drain_requested
    order: List[BaseTask] = []
    seen = set()
    deps_of: Dict[tuple, List[tuple]] = {}

    def _key(task: BaseTask) -> tuple:
        return (type(task).__name__, task.uid, task.tmp_folder)

    def visit(task: BaseTask, stack: tuple):
        key = _key(task)
        if key in stack:
            raise RuntimeError(f"dependency cycle at {key}")
        if key in seen:
            return
        deps_of[key] = [_key(dep) for dep in task.requires()]
        for dep in task.requires():
            visit(dep, stack + (key,))
        seen.add(key)
        order.append(task)

    for t in tasks:
        visit(t, ())

    # the DAG-engine span: brackets every task.run of this build, so the
    # timeline shows scheduling gaps (skip checks, retry backoffs) between
    # tasks, not just the tasks themselves (docs/OBSERVABILITY.md)
    build_span = trace_mod.begin("task.build", n_tasks=len(order))
    failed: set = set()
    for task in order:
        key = _key(task)
        # completeness first: a task whose target already exists is done,
        # even when an upstream failed (luigi semantics) — its own
        # dependents still get their real input.  complete() additionally
        # validates in-memory handoff outputs: a memory-only manifest
        # whose handle died with its process re-runs (docs/PERFORMANCE.md
        # "Task-graph fusion")
        if task.complete() and not rerun:
            task.logger.info(f"skip {task.task_name}: target exists")
            continue
        blocked = [d for d in deps_of[key] if d in failed]
        if blocked:
            task.logger.error(
                f"skip {task.task_name}: upstream failed "
                f"({[d[0] for d in blocked]})"
            )
            failed.add(key)
            continue
        if drain_requested():
            raise DrainInterrupt(drain_reason() or "drain requested")
        if _run_with_retries(task):
            from . import faults as faults_mod

            faults_mod.get_injector().kill_point("task_done")
        else:
            failed.add(key)
    build_span.end(n_failed=len(failed))
    if trace_mod.enabled() and order:
        # the build span itself must reach the timeline: flush through the
        # last task's tmp_folder (where the run's shard directory lives)
        try:
            trace_mod.flush()
            trace_mod.write_timeline(order[-1].tmp_folder)
        except Exception:
            pass
    return not failed
