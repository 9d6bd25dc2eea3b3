"""Service mode (docs/SERVING.md): per-tenant admission + DRR fairness,
request-namespace isolation of handoffs, the resident server end-to-end
(the ``make serve-smoke`` tier-1 scenario), typed rejection attribution,
and the operator progress view.  CPU-only, tier-1 fast."""

import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from cluster_tools_tpu.runtime import admission, faults, handoff
from cluster_tools_tpu.runtime.admission import (
    REJECT_BYTES,
    REJECT_DEADLINE,
    REJECT_DRAINING,
    REJECT_FAULT,
    REJECT_QUEUE,
    AdmissionController,
    AdmissionError,
    Request,
    TenantQuota,
)
from cluster_tools_tpu.utils import function_utils as fu
from cluster_tools_tpu.utils.volume_utils import file_reader

from .helpers import stray_serve_pids as _stray_serve_pids

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_process_state():
    handoff.reset()
    faults.configure(None)
    yield
    handoff.reset()
    faults.configure(None)


def _req(tenant, rid, est_bytes=0, deadline_s=None):
    return Request(tenant=tenant, request_id=rid, est_bytes=est_bytes,
                   deadline_s=deadline_s)


# -- admission: quotas + typed backpressure -----------------------------------


def test_queue_depth_quota_rejects_typed():
    ctl = AdmissionController(
        quotas={"t": TenantQuota(max_queue_depth=2)}
    )
    ctl.submit(_req("t", "a"))
    ctl.submit(_req("t", "b"))
    with pytest.raises(AdmissionError) as ei:
        ctl.submit(_req("t", "c"))
    assert ei.value.code == REJECT_QUEUE
    assert ei.value.tenant == "t"
    snap = ctl.snapshot()["t"]
    assert snap["queued"] == 2 and snap["rejected"] == 1


def test_oversized_request_rejected_outright_not_queued():
    ctl = AdmissionController(
        quotas={"t": TenantQuota(max_bytes_in_flight=100)}
    )
    with pytest.raises(AdmissionError) as ei:
        ctl.submit(_req("t", "big", est_bytes=101))
    assert ei.value.code == REJECT_BYTES
    assert ctl.snapshot()["t"]["queued"] == 0  # never silently queued


def test_inflight_and_byte_quotas_gate_dispatch():
    ctl = AdmissionController(
        quotas={"t": TenantQuota(max_inflight=1, max_bytes_in_flight=100)}
    )
    ctl.submit(_req("t", "a", est_bytes=60))
    ctl.submit(_req("t", "b", est_bytes=60))
    first = ctl.next_request(timeout=1.0)
    assert first is not None and first.request_id == "a"
    # inflight quota (1) blocks b until a releases
    assert ctl.next_request(timeout=0.1) is None
    ctl.release(first)
    second = ctl.next_request(timeout=1.0)
    assert second is not None and second.request_id == "b"
    snap = ctl.snapshot()["t"]
    assert snap["dispatched"] == 2 and snap["completed"] == 1


def test_dispatch_computes_per_request_byte_cap():
    ctl = AdmissionController(
        quotas={"t": TenantQuota(max_inflight=2,
                                 max_bytes_in_flight=1000)}
    )
    ctl.submit(_req("t", "a", est_bytes=10))
    ctl.submit(_req("t", "b", est_bytes=10))
    a = ctl.next_request(timeout=1.0)
    assert a.byte_cap == 1000  # alone: the whole tenant quota
    b = ctl.next_request(timeout=1.0)
    assert b.byte_cap == 500  # sharing with a sibling: half


def test_deadline_expiry_rejected_at_dispatch():
    rejected = []
    ctl = AdmissionController(
        on_reject=lambda r, t, code, detail: rejected.append((t, code)),
    )
    ctl.submit(_req("t", "stale", deadline_s=0.01))
    ctl.submit(_req("t", "fresh"))
    time.sleep(0.05)
    nxt = ctl.next_request(timeout=1.0)
    assert nxt is not None and nxt.request_id == "fresh"
    assert ("t", REJECT_DEADLINE) in rejected
    assert ctl.snapshot()["t"]["rejected"] == 1


def test_drain_rejects_submits_and_stops_dispatch():
    ctl = AdmissionController()
    ctl.submit(_req("t", "queued-before-drain"))
    ctl.begin_drain()
    with pytest.raises(AdmissionError) as ei:
        ctl.submit(_req("t", "late"))
    assert ei.value.code == REJECT_DRAINING
    # queued requests stay queued (the restarted server's clients
    # resubmit); dispatch stops too
    assert ctl.next_request(timeout=0.1) is None
    assert ctl.queued() == 1


def test_drr_interleaves_aggressor_with_well_behaved():
    """The fairness property the serve bench measures: an aggressor
    flooding its queue cannot starve a well-behaved tenant — DRR serves
    both in rotation."""
    ctl = AdmissionController(
        default_quota=TenantQuota(max_inflight=100, max_queue_depth=100)
    )
    for i in range(6):
        ctl.submit(_req("aggressor", f"agg-{i}"))
    for i in range(3):
        ctl.submit(_req("good", f"good-{i}"))
    order = [ctl.next_request(timeout=1.0).tenant for _ in range(6)]
    # strict alternation while both are backlogged (equal quanta)
    assert order[:6] == ["aggressor", "good"] * 3


def test_drr_quantum_weights_byte_throughput():
    """Quantum weights the byte share, not the request count: with
    equal-size requests costing 2 credits, a quantum-2 tenant affords one
    per visit while a quantum-1 tenant needs two visits per dispatch."""
    cost2 = 2 * admission.BYTE_COST_UNIT
    ctl = AdmissionController(
        quotas={
            "heavy": TenantQuota(max_inflight=100, max_queue_depth=100,
                                 max_bytes_in_flight=1 << 40, quantum=2.0),
            "light": TenantQuota(max_inflight=100, max_queue_depth=100,
                                 max_bytes_in_flight=1 << 40, quantum=1.0),
        }
    )
    for i in range(8):
        ctl.submit(_req("heavy", f"h{i}", est_bytes=cost2))
        ctl.submit(_req("light", f"l{i}", est_bytes=cost2))
    got = [ctl.next_request(timeout=1.0).tenant for _ in range(9)]
    assert got.count("heavy") == 6 and got.count("light") == 3


# -- the injected admission fault ---------------------------------------------


def test_reject_fault_is_tenant_targeted_and_bounded():
    faults.configure({
        "seed": 11,
        "faults": [{"site": "admit", "kind": "reject",
                    "tenants": ["tenant-b"], "fail_attempts": 2}],
    })
    inj = faults.get_injector()
    assert not inj.maybe_reject("tenant-a")
    assert inj.maybe_reject("tenant-b")
    assert inj.maybe_reject("tenant-b")
    assert not inj.maybe_reject("tenant-b")  # fail_attempts exhausted


def test_reject_fault_requires_admit_site():
    with pytest.raises(ValueError):
        faults.configure({
            "faults": [{"site": "load", "kind": "reject"}],
        })


# -- request-namespace isolation of handoffs ----------------------------------


def test_handoff_identities_namespaced_by_request():
    base = handoff.dataset_identity("/data/vol.zarr", "seg")
    with admission.request_context("alice", "req-1"):
        ns = handoff.dataset_identity("/data/vol.zarr", "seg")
    assert ns == f"req:req-1::{base}"
    assert handoff.identity_namespace(ns) == "req-1"
    assert handoff.identity_namespace(base) is None
    with admission.request_context("alice", "req-1"):
        assert handoff.in_current_namespace(ns)
        assert not handoff.in_current_namespace(base)
    with admission.request_context("bob", "req-2"):
        assert not handoff.in_current_namespace(ns)
    assert handoff.in_current_namespace(base)  # batch mode: both None


def test_concurrent_requests_cannot_resolve_each_others_intermediates(
        tmp_path):
    """Two requests over the SAME artifact path: request 2 must never see
    request 1's in-memory payload — its namespace misses, and the load
    falls through to storage (which does not exist here)."""
    path = os.path.join(str(tmp_path), "inter.npz")
    payload = {"a": np.arange(5, dtype=np.uint64)}
    with admission.request_context("alice", "r1"):
        handoff.publish_arrays(path, payload, producer="t.0")
        got = handoff.load_arrays(path)
        np.testing.assert_array_equal(got["a"], payload["a"])
    with admission.request_context("bob", "r2"):
        with pytest.raises(FileNotFoundError):
            handoff.load_arrays(path)


def test_request_scope_reenters_context_on_worker_thread():
    seen = {}
    with admission.request_context("alice", "r9", byte_cap=123):
        ctx = admission.current_request()

        def worker():
            with admission.request_scope(ctx):
                seen["ns"] = handoff.dataset_identity("/d.zarr", "k")
                seen["cap"] = admission.ambient_byte_cap()

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert seen["ns"].startswith("req:r9::")
    assert seen["cap"] == 123


def test_flush_namespace_writes_datasets_back_and_release_drops_all(
        tmp_path):
    path = os.path.join(str(tmp_path), "out.zarr")
    with admission.request_context("alice", "r5"):
        ds, entry = handoff.acquire_dataset(
            path, "seg", shape=(8, 8, 8), chunks=(4, 4, 4),
            dtype="uint64", producer="t.0",
        )
        ds[:] = np.arange(512, dtype=np.uint64).reshape(8, 8, 8)
        entry.complete = True
        art = os.path.join(str(tmp_path), "private.npz")
        handoff.publish_arrays(art, {"a": np.ones(4)}, producer="t.0")
    flushed = handoff.flush_namespace("r5")
    assert flushed == 512 * 8
    stored = np.asarray(file_reader(path)["seg"][...])
    np.testing.assert_array_equal(
        stored, np.arange(512, dtype=np.uint64).reshape(8, 8, 8)
    )
    # artifacts are request-private: not flushed, dropped with the ns
    assert not os.path.exists(art)
    assert handoff.release_request("r5") == 2
    assert handoff.live_entries() == 0


# -- the resident server ------------------------------------------------------


def _serve_payload(base, data, tenant, rid, out_key, block=8):
    return dict(
        tenant=tenant,
        request_id=rid,
        workflow="connected_components",
        config=dict(
            tmp_folder=os.path.join(base, "req_" + rid),
            global_config={"block_shape": [block] * 3},
            params=dict(
                input_path=data, input_key="mask",
                output_path=data, output_key=out_key,
                threshold=0.5,
            ),
        ),
    )


def _start_server(base, **kw):
    from cluster_tools_tpu.runtime.server import PipelineServer, ServeClient

    kw.setdefault("max_workers", 2)
    server = PipelineServer(base_dir=os.path.join(base, "srv"), **kw).start()
    return server, ServeClient(server.host, server.port)


def _mk_input(base, shape=(16, 16, 16), seed=0):
    rng = np.random.default_rng(seed)
    vol = (rng.random(shape) > 0.5).astype("float32")
    data = os.path.join(base, "data.zarr")
    src = file_reader(data).create_dataset(
        "mask", shape=vol.shape, chunks=(8, 8, 8), dtype="float32")
    src[...] = vol
    return data


def test_serve_smoke_two_tenants_warm_cache(tmp_path):
    """The ``make serve-smoke`` scenario: two tenants submit concurrent
    tiny workflows against one resident server; both complete, outputs
    agree, a warm resubmission shows chunk-cache reuse in io_metrics,
    and no handoff entries outlive their requests."""
    base = str(tmp_path)
    data = _mk_input(base)
    server, client = _start_server(
        base, tenants={"alice": {}, "bob": {}},
    )
    try:
        client.submit(**_serve_payload(base, data, "alice", "a1", "seg_a"))
        client.submit(**_serve_payload(base, data, "bob", "b1", "seg_b"))
        rec_a = client.wait("a1", timeout_s=120)
        rec_b = client.wait("b1", timeout_s=120)
        assert rec_a["state"] == "done", rec_a
        assert rec_b["state"] == "done", rec_b

        # warm resubmission: same shapes + input, compiled programs and
        # chunk cache resident — reuse must be visible in io_metrics
        client.submit(**_serve_payload(base, data, "alice", "a2", "seg_a2"))
        rec_w = client.wait("a2", timeout_s=120)
        assert rec_w["state"] == "done", rec_w
        with open(os.path.join(base, "req_a2", "io_metrics.json")) as f:
            io_doc = json.load(f)
        hits = sum(
            t.get("hits", 0) for t in io_doc["tasks"].values()
        )
        misses = sum(
            t.get("misses", 0) for t in io_doc["tasks"].values()
        )
        assert hits > 0, io_doc
        assert misses == 0  # every input chunk served from the warm cache

        status = client.status()
        tenants = status["server"]["tenants"]
        assert tenants["alice"]["completed"] == 2
        assert tenants["bob"]["completed"] == 1
        assert status["server"]["handoffs"]["live_entries"] == 0
        assert status["rc"] == 0

        seg_a = np.asarray(file_reader(data)["seg_a"][...])
        seg_b = np.asarray(file_reader(data)["seg_b"][...])
        np.testing.assert_array_equal(seg_a, seg_b)
    finally:
        server.stop()
    # the leaked-server guard: no stray resident serve process outlives
    # the smoke test on this host — leaked servers burn CPU for the rest
    # of the suite and are the prime suspect when tier-1 drifts toward
    # its wall-clock ceiling
    assert _stray_serve_pids() == []


def test_injected_admit_fault_leaves_no_partial_state(tmp_path):
    """A fault-rejected request is attributed in failures.json
    (resolution rejected:fault) and leaves nothing behind: no tmp
    folder, no markers, no handoff entries."""
    base = str(tmp_path)
    data = _mk_input(base)
    faults.configure({
        "seed": 3,
        "faults": [{"site": "admit", "kind": "reject",
                    "tenants": ["bob"], "fail_attempts": 1}],
    })
    server, client = _start_server(base, tenants={"alice": {}, "bob": {}})
    try:
        from cluster_tools_tpu.runtime.server import ServeRejected

        entries_before = handoff.live_entries()
        with pytest.raises(ServeRejected) as ei:
            client.submit(**_serve_payload(base, data, "bob", "b1", "seg"))
        assert ei.value.code == REJECT_FAULT
        assert ei.value.http_status == 429
        # no partial state: the request never got a tmp folder or record
        assert not os.path.exists(os.path.join(base, "req_b1"))
        assert handoff.live_entries() == entries_before
        assert client.request("b1") is None
        # attributed in the server's failures.json, resolved (the
        # rejection IS the resolution — not an unresolved failure)
        doc = fu.read_json_if_valid(
            fu.failures_path(os.path.join(base, "srv")))
        recs = [r for r in doc["records"]
                if r.get("task") == "server.bob"]
        assert recs and recs[0]["resolution"] == REJECT_FAULT
        assert recs[0]["resolved"] is True
        assert recs[0]["sites"] == {"admit": 1}
        # /status rc stays 0: a typed rejection is not an unresolved
        # failure
        assert client.status()["rc"] == 0
        # the sibling tenant is untouched
        client.submit(**_serve_payload(base, data, "alice", "a1", "seg_a"))
        assert client.wait("a1", timeout_s=120)["state"] == "done"
    finally:
        server.stop()


def test_duplicate_and_unknown_requests_rejected(tmp_path):
    """Submission is idempotent per (request_id, payload): the SAME
    payload under a live/done id answers from the record (the durable-ack
    contract — a client retry must never re-run or bounce), while a
    DIFFERENT payload under the same id is a real collision and stays
    rejected:duplicate."""
    base = str(tmp_path)
    data = _mk_input(base, shape=(8, 8, 8))
    server, client = _start_server(base, max_workers=1)
    try:
        from cluster_tools_tpu.runtime.server import ServeRejected

        client.submit(**_serve_payload(base, data, "t", "r1", "seg1"))
        # retry of the acknowledged submission: idempotent 200, no re-run
        doc = client.submit(**_serve_payload(base, data, "t", "r1", "seg1"))
        assert doc["idempotent"] is True
        assert doc["state"] in ("queued", "running", "done")
        # same id, different payload: a collision, typed and attributed
        with pytest.raises(ServeRejected) as ei:
            client.submit(**_serve_payload(base, data, "t", "r1", "OTHER"))
        assert ei.value.code == admission.REJECT_DUPLICATE
        assert client.status()["server"]["tenants"]["t"]["rejected"] == 1
        with pytest.raises(ServeRejected) as ei:
            client.submit(tenant="t", request_id="r2",
                          workflow="definitely_not_a_workflow")
        assert ei.value.http_status == 400
        rec = client.wait("r1", timeout_s=120)
        assert rec["state"] == "done"
        # a duplicate resubmit of the COMPLETED id answers idempotently
        # from the recorded result
        doc = client.submit(**_serve_payload(base, data, "t", "r1", "seg1"))
        assert doc == {
            "request_id": "r1", "state": "done", "idempotent": True,
            "run_s": rec["run_s"], "total_s": rec["total_s"],
        }
    finally:
        server.stop()


def test_server_queue_quota_backpressure_http(tmp_path):
    """Queue-depth quota surfaces as typed HTTP 429 backpressure."""
    base = str(tmp_path)
    data = _mk_input(base, shape=(8, 8, 8))
    server, client = _start_server(
        base,
        tenants={"t": {"max_queue_depth": 1, "max_inflight": 1}},
        max_workers=1,
    )
    try:
        from cluster_tools_tpu.runtime.server import ServeRejected

        # r1 dispatches, r2 fills the queue, r3 must bounce
        client.submit(**_serve_payload(base, data, "t", "r1", "s1"))
        client.submit(**_serve_payload(base, data, "t", "r2", "s2"))
        codes = set()
        try:
            client.submit(**_serve_payload(base, data, "t", "r3", "s3"))
        except ServeRejected as e:
            codes.add((e.code, e.http_status))
        assert codes == {(REJECT_QUEUE, 429)}
        assert client.wait("r1", timeout_s=120)["state"] == "done"
        assert client.wait("r2", timeout_s=120)["state"] == "done"
        # the backpressure protocol is back-off-and-resubmit THE SAME id:
        # a rejected record must not poison r3 into rejected:duplicate
        client.submit(**_serve_payload(base, data, "t", "r3", "s3"))
        assert client.wait("r3", timeout_s=120)["state"] == "done"
    finally:
        server.stop()


# -- the durable submission journal (docs/SERVING.md "Durability") ------------


def _journal_of(base):
    from cluster_tools_tpu.runtime import journal as journal_mod

    return journal_mod.journal_path(os.path.join(base, "srv"))


def test_restart_replays_completed_requests_idempotently(tmp_path):
    """A restarted server rebuilds completed requests from the journal:
    duplicate resubmits of a done id answer idempotently from the
    recorded result, and tenant counters survive the restart."""
    base = str(tmp_path)
    data = _mk_input(base, shape=(8, 8, 8))
    payload = _serve_payload(base, data, "alice", "a1", "seg")
    server, client = _start_server(base, tenants={"alice": {}})
    try:
        client.submit(**payload)
        rec = client.wait("a1", timeout_s=120)
        assert rec["state"] == "done"
    finally:
        server.stop()

    server2, client2 = _start_server(base, tenants={"alice": {}})
    try:
        # the record came back from the journal, not from client memory
        rec2 = client2.request("a1")
        assert rec2["state"] == "done" and rec2["replayed"] is True
        assert rec2["run_s"] == rec["run_s"]
        doc = client2.submit(**payload)
        assert doc["idempotent"] is True and doc["state"] == "done"
        # counters reconstructed from replay: quotas + operator view stay
        # correct across the restart
        snap = client2.status()["server"]["tenants"]["alice"]
        assert snap["submitted"] == 1 and snap["completed"] == 1
        # ... and a DIFFERENT payload under the done id is still a
        # collision
        from cluster_tools_tpu.runtime.server import ServeRejected

        with pytest.raises(ServeRejected) as ei:
            client2.submit(**_serve_payload(base, data, "alice", "a1",
                                            "other_key"))
        assert ei.value.code == admission.REJECT_DUPLICATE
    finally:
        server2.stop()


def test_replay_reenqueues_acknowledged_incomplete_request(tmp_path):
    """An accepted-but-never-run request (the SIGKILL window) is re-run
    by the restarted server with its original tenant/payload — the 200
    was a durable promise, no client resubmission needed."""
    from cluster_tools_tpu.runtime import journal as journal_mod
    from cluster_tools_tpu.runtime.server import _payload_fingerprint

    base = str(tmp_path)
    data = _mk_input(base, shape=(8, 8, 8))
    payload = _serve_payload(base, data, "bob", "b1", "seg_b")
    os.makedirs(os.path.join(base, "srv"), exist_ok=True)
    j = journal_mod.Journal(_journal_of(base))
    j.recover()
    j.append_transition(
        journal_mod.ACCEPTED, "b1", tenant="bob", payload=payload,
        fingerprint=_payload_fingerprint(payload),
    )
    j.close()

    server, client = _start_server(base, tenants={"bob": {}})
    try:
        health = client.healthz()["journal"]
        assert health["reenqueued"] == 1 and health["quarantined"] == 0
        rec = client.wait("b1", timeout_s=120)
        assert rec["state"] == "done" and rec["replayed"] is True
        out = np.asarray(file_reader(data)["seg_b"][...])
        assert out.shape == (8, 8, 8)
        assert client.healthz()["journal"]["replay_backlog"] == 0
        assert handoff.live_entries() == 0
    finally:
        server.stop()


def test_replay_quarantines_crash_looping_request(tmp_path):
    """Crash-loop defense: a journaled request whose dispatch count has
    reached max_replay_attempts is quarantined at boot — typed
    quarantined:crash_loop in failures.json, idempotent 'quarantined'
    answers for same-payload resubmits — instead of re-running."""
    from cluster_tools_tpu.runtime import journal as journal_mod
    from cluster_tools_tpu.runtime.server import (
        QUARANTINE_CRASH_LOOP,
        ServeRejected,
        _payload_fingerprint,
    )

    base = str(tmp_path)
    data = _mk_input(base, shape=(8, 8, 8))
    payload = _serve_payload(base, data, "eve", "p1", "seg_p")
    os.makedirs(os.path.join(base, "srv"), exist_ok=True)
    j = journal_mod.Journal(_journal_of(base))
    j.recover()
    j.append_transition(
        journal_mod.ACCEPTED, "p1", tenant="eve", payload=payload,
        fingerprint=_payload_fingerprint(payload),
    )
    for attempt in (1, 2):
        j.append_transition(
            journal_mod.DISPATCHED, "p1", tenant="eve", attempt=attempt,
        )
    j.close()

    server, client = _start_server(
        base, tenants={"eve": {}}, max_replay_attempts=2,
    )
    try:
        rec = client.request("p1")
        assert rec["state"] == "quarantined"
        assert rec["code"] == QUARANTINE_CRASH_LOOP
        health = client.healthz()["journal"]
        assert health["quarantined"] == 1 and health["reenqueued"] == 0
        # same payload: idempotent answer, never re-run; different
        # payload: collision
        doc = client.submit(**payload)
        assert doc["idempotent"] is True and doc["state"] == "quarantined"
        with pytest.raises(ServeRejected) as ei:
            client.submit(**_serve_payload(base, data, "eve", "p1", "zz"))
        assert ei.value.code == admission.REJECT_DUPLICATE
        # attributed: quarantined + resolved (the quarantine IS the
        # resolution — the server defended itself), so /status rc stays 0
        doc = fu.read_json_if_valid(
            fu.failures_path(os.path.join(base, "srv")))
        recs = [r for r in doc["records"]
                if r.get("task") == "server.eve"
                and r.get("block_id") == "request:p1"]
        assert recs and recs[0]["resolution"] == QUARANTINE_CRASH_LOOP
        assert recs[0]["quarantined"] is True
        assert recs[0]["resolved"] is True
        assert recs[0]["sites"] == {"journal_replay": 2}
        assert client.status()["rc"] == 0
        # the journal itself records the quarantine, so the NEXT restart
        # answers from the terminal record instead of re-deciding
        from cluster_tools_tpu.runtime import journal as jm

        folded = jm.fold(jm.scan(_journal_of(base))[0])
        assert folded["p1"]["state"] == jm.QUARANTINED
    finally:
        server.stop()


def test_replay_tolerates_torn_journal_tail(tmp_path):
    """A torn tail (SIGKILL mid-append) never refuses boot: the intact
    prefix replays, the torn bytes are truncated and surfaced in the
    health block."""
    from cluster_tools_tpu.runtime import journal as journal_mod
    from cluster_tools_tpu.runtime.server import _payload_fingerprint

    base = str(tmp_path)
    data = _mk_input(base, shape=(8, 8, 8))
    payload = _serve_payload(base, data, "t", "r1", "seg")
    os.makedirs(os.path.join(base, "srv"), exist_ok=True)
    jpath = _journal_of(base)
    j = journal_mod.Journal(jpath)
    j.recover()
    j.append_transition(
        journal_mod.ACCEPTED, "r1", tenant="t", payload=payload,
        fingerprint=_payload_fingerprint(payload),
    )
    j.append_transition(journal_mod.ACCEPTED, "r2", tenant="t",
                        payload={"workflow": "connected_components"})
    j.close()
    with open(jpath, "r+b") as f:
        f.truncate(os.path.getsize(jpath) - 7)  # tear r2's record

    server, client = _start_server(base)
    try:
        health = client.healthz()["journal"]
        assert health["torn_bytes_truncated"] > 0
        assert health["reenqueued"] == 1  # r1 survived, r2 never acked
        assert client.request("r2") is None
        assert client.wait("r1", timeout_s=120)["state"] == "done"
    finally:
        server.stop()


def test_progress_renders_server_view(tmp_path):
    """Satellite: ``make progress TMP=<server base>`` renders the
    per-tenant admission view alongside the block-marker table."""
    base = str(tmp_path)
    data = _mk_input(base, shape=(8, 8, 8))
    server, client = _start_server(base, tenants={"alice": {}})
    try:
        client.submit(**_serve_payload(base, data, "alice", "a1", "seg"))
        client.wait("a1", timeout_s=120)
    finally:
        server.stop()

    spec = importlib.util.spec_from_file_location(
        "ctt_progress", os.path.join(REPO_ROOT, "scripts", "progress.py"))
    prog = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(prog)

    doc = prog.collect_progress(os.path.join(base, "srv"))
    assert doc["server"] is not None
    assert doc["server"]["tenants"]["alice"]["completed"] == 1
    assert "server" not in {t["uid"] for t in doc["tasks"]}
    text = prog.format_progress(doc)
    assert "tenant alice" in text
    assert "1 completed" in text
    # the dead server warns: its pid is gone now (same host), so the
    # operator view flips to stale + rc 1
    doc2 = prog.collect_progress(os.path.join(base, "srv"))
    server_view = doc2["server"]
    if server_view["pid"] is not None and not prog._pid_alive(
            server_view["pid"]):
        assert server_view["stale"]


def test_progress_and_report_render_journal_plane(tmp_path):
    """Satellites: ``make progress`` renders the journal pulse (replayed /
    re-enqueued / quarantined) and ``failures_report.py --json`` carries a
    ``journal`` block, so the one machine-readable document covers the
    durability plane."""
    from cluster_tools_tpu.runtime import journal as journal_mod

    base = str(tmp_path)
    data = _mk_input(base, shape=(8, 8, 8))
    server, client = _start_server(base, tenants={"alice": {}})
    try:
        client.submit(**_serve_payload(base, data, "alice", "a1", "seg"))
        client.wait("a1", timeout_s=120)
    finally:
        server.stop()
    srv = os.path.join(base, "srv")

    spec = importlib.util.spec_from_file_location(
        "ctt_progress2", os.path.join(REPO_ROOT, "scripts", "progress.py"))
    prog = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(prog)
    doc = prog.collect_progress(srv)
    j = doc["server"]["journal"]
    # accepted + dispatched + completed for the one request
    assert j["appended"] == 3 and j["replay_backlog"] == 0
    text = prog.format_progress(doc)
    assert "journal:" in text and "3 record(s) appended" in text

    spec = importlib.util.spec_from_file_location(
        "ctt_failrep", os.path.join(REPO_ROOT, "scripts",
                                    "failures_report.py"))
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    jdoc = rep.build_json_report(srv, with_lint=False)
    jblock = jdoc["journal"]
    assert jblock["n_records"] == 3
    assert jblock["by_type"] == {
        "accepted": 1, "dispatched": 1, "completed": 1,
    }
    assert jblock["n_replays"] == 0 and jblock["n_quarantined"] == 0
    assert jblock["torn_tail_bytes"] == 0
    # format-drift guard: the report's stdlib scanner and the runtime's
    # reader must agree record for record
    recs, good, torn = journal_mod.scan(journal_mod.journal_path(srv))
    assert len(recs) == jblock["n_records"] and torn == 0
    assert good == jblock["bytes"]
    # a run without a journal reports null (batch runs unchanged)
    assert rep.build_json_report(base, with_lint=False)["journal"] is None
    # the self-healing plane rides the same document: the resident
    # server's scrubber wrote scrub_state.json next to failures.json
    sblock = jdoc["scrub"]
    assert sblock is not None
    for key in ("passes", "scanned_regions", "scanned_bytes",
                "found_corrupt", "repaired", "unrepairable", "reader",
                "repair"):
        assert key in sblock, key
    assert sblock["found_corrupt"] == 0 and sblock["unrepairable"] == 0
    # a run without a scrubber reports null
    assert rep.build_json_report(base, with_lint=False)["scrub"] is None


def test_serve_cli_status_requires_endpoint(tmp_path):
    from cluster_tools_tpu import serve as serve_cli

    with pytest.raises(FileNotFoundError):
        serve_cli.cmd_status(str(tmp_path))


def test_serve_tpu_without_a_tpu_exits_at_start_with_the_reason(tmp_path):
    """``serve --tpu`` opens the accelerator BEFORE it binds: a member that
    cannot have the chip (none here; on a chip machine, a sibling holding
    it) exits non-zero at start and says why — it never binds, so nothing
    can be routed to it and fail at the first request instead."""
    base = tmp_path / "srv"
    proc = subprocess.run(
        [sys.executable, "-m", "cluster_tools_tpu.serve",
         "--base-dir", str(base), "--tpu"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(os.path.dirname(__file__)),
             os.environ.get("PYTHONPATH", "")])},
    )
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert "cannot open the accelerator" in proc.stderr
    assert "no TPU devices are visible" in proc.stderr
    assert not (base / "server.json").exists()  # never bound
