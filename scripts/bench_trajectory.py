"""Aggregate the scattered BENCH_r07..r16 headline numbers into one table.

Usage::

    python scripts/bench_trajectory.py            # print the table
    python scripts/bench_trajectory.py --write    # + rewrite the
        "Performance trajectory" block of docs/PERFORMANCE.md (between the
        BENCH-TRAJECTORY markers)
    make bench-trajectory

Each bench round left a differently-shaped JSON next to the repo root (the
raw records the focused benches write; r01-r06, the driver-wrapper records
of the earlier accelerator set-up, were deleted in PR 24).  This script
knows each shape, extracts one headline row per round, and renders a
markdown table — the perf trajectory readable without opening ten files.
Unreadable or unknown records degrade to an explanatory row, never a crash.
All of them are CPU-backend records: counts, not speeds.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(ROOT, "docs", "PERFORMANCE.md")
MARK_BEGIN = "<!-- BENCH-TRAJECTORY:BEGIN (generated by scripts/bench_trajectory.py) -->"
MARK_END = "<!-- BENCH-TRAJECTORY:END -->"


def _fmt(value: float) -> str:
    return f"{value:,.0f}" if abs(value) >= 100 else f"{value:g}"


def _unknown_row(doc: dict) -> dict:
    return {
        "metric": str(doc.get("metric", "?")),
        "headline": "no extractor for this record shape",
        "backend": doc.get("backend", "?"),
        "detail": "",
    }


def _sweep_row(doc: dict) -> dict:
    """r07: sharded-sweep dispatch amortization."""
    return {
        "metric": doc.get("metric", "sharded_sweep_dispatch"),
        "headline": (
            f"{doc.get('dispatch_reduction', '?')}x fewer dispatches, "
            f"{doc.get('throughput_ratio', '?')}x sweep throughput"
        ),
        "backend": doc.get("backend", "?"),
        "detail": (
            f"bit_identical={doc.get('bit_identical')}; "
            f"{doc.get('sharded', {}).get('blocks_per_dispatch', '?')} "
            "blocks/dispatch"
        ),
    }


def _fuse_row(doc: dict) -> dict:
    """r08: task-graph fusion intermediate-write elimination."""
    off = doc.get("handoffs_off", {}) or {}
    on = doc.get("handoffs_on", {}) or {}
    return {
        "metric": doc.get("metric", "task_graph_fusion_workflow"),
        "headline": (
            f"{_fmt(off.get('intermediate_bytes_written') or 0)} B "
            f"intermediate storage -> "
            f"{_fmt(on.get('intermediate_bytes_written') or 0)} B"
        ),
        "backend": doc.get("backend", "?"),
        "detail": (
            f"{on.get('handoffs_served', '?')} handoffs served in-memory; "
            f"bit_identical={doc.get('bit_identical')}"
        ),
    }


def _solve_row(doc: dict) -> dict:
    """r09: distributed agglomeration over the octant reduce tree."""
    tree = doc.get("reduce_tree", {}) or {}
    wg = doc.get("worker_group", {}) or {}
    return {
        "metric": doc.get("metric", "distributed_agglomeration_solve"),
        "headline": (
            f"{_fmt(doc.get('n_edges') or 0)}-edge solve sharded "
            f"{doc.get('solver_shards', '?')} ways, energy gap "
            f"{tree.get('energy_gap_pct', '?')}%"
        ),
        "backend": doc.get("backend", "?"),
        "detail": (
            f"{wg.get('workers', '?')}-worker group bit_identical="
            f"{wg.get('bit_identical_to_in_process')}; "
            f"deterministic={tree.get('deterministic_across_reruns')}"
        ),
    }


def _serve_row(doc: dict) -> dict:
    """r10: the first traffic-shaped row — the resident multi-tenant
    server under open-loop Poisson load (docs/SERVING.md)."""
    warm = doc.get("warm_aggregate", {}) or {}
    fair = doc.get("fairness", {}) or {}
    return {
        "metric": doc.get("metric", "service_mode_traffic"),
        "headline": (
            f"warm p50 {warm.get('p50_s', '?')}s / p99 "
            f"{warm.get('p99_s', '?')}s at {doc.get('throughput_rps', '?')} "
            f"req/s; {doc.get('cached_shape_class', '?')} warm "
            f"{doc.get('warm_speedup_cached_shape', '?')}x over cold"
        ),
        "backend": doc.get("backend", "?"),
        "detail": (
            f"steady p99 x{fair.get('p99_ratio_under_aggressor', '?')} "
            f"under aggressor ({(fair.get('aggressor') or {}).get('rejected_typed', '?')} "
            f"typed 429s); bit_identical={doc.get('bit_identical')}; "
            f"drain rc={doc.get('drain_rc')}"
        ),
    }


def _ragged_row(doc: dict) -> dict:
    """r11: the ragged paged block pool — edge/split-heavy sweeps keeping
    the one-program-per-batch shape on non-pow2 grids."""
    rg = doc.get("ragged", {}) or {}
    return {
        "metric": doc.get("metric", "ragged_paged_sweep"),
        "headline": (
            f"{doc.get('dispatch_reduction', '?')}x fewer dispatches on a "
            f"{'x'.join(str(g) for g in doc.get('grid', []))} clipped grid "
            f"with {doc.get('forced_split_blocks', '?')} forced splits"
        ),
        "backend": doc.get("backend", "?"),
        "detail": (
            f"{rg.get('dispatches', '?')} ragged dispatches vs "
            f"{(doc.get('per_block') or {}).get('dispatches', '?')} "
            f"per-block ({rg.get('n_sub_blocks', '?')} sub-blocks, "
            f"{rg.get('lanes_padded', '?')} pad lanes); "
            f"bit_identical={doc.get('bit_identical')}"
        ),
    }


def _device_row(doc: dict) -> dict:
    """r12: the device-resident data plane — warm re-sweeps re-address
    HBM-resident pages instead of re-uploading them."""
    dev = doc.get("device_resident", {}) or {}
    host = doc.get("host_staged", {}) or {}
    return {
        "metric": doc.get("metric", "device_resident_data_plane"),
        "headline": (
            f"{doc.get('h2d_reduction', '?')}x fewer h2d bytes on the "
            f"{'x'.join(str(g) for g in doc.get('grid', []))} grid "
            f"(warm sweep {_fmt(dev.get('h2d_bytes') or 0)}B vs "
            f"{_fmt(host.get('h2d_bytes') or 0)}B host-staged)"
        ),
        "backend": doc.get("backend", "?"),
        "detail": (
            f"{dev.get('device_pool_hits', '?')} resident-page hits over "
            f"{dev.get('device_batches_staged', '?')} staged batches, "
            f"{_fmt(dev.get('resident_bytes') or 0)}B resident; "
            f"wall x{doc.get('wall_ratio', '?')}; "
            f"bit_identical={doc.get('bit_identical')}"
        ),
    }


def _fleet_row(doc: dict) -> dict:
    """r13: the serving fleet — kill -9 one member under live two-tenant
    traffic, zero lost acknowledged requests (docs/SERVING.md "Fleet")."""
    warm = doc.get("warm", {}) or {}
    kill = doc.get("kill_phase", {}) or {}
    aff = doc.get("affinity", {}) or {}
    acc = doc.get("acceptance", {}) or {}
    return {
        "metric": doc.get("metric", "fleet_failover_traffic"),
        "headline": (
            f"kill -9 of 1/{doc.get('members', '?')} members: "
            f"{len(doc.get('lost_acked') or [])} lost of "
            f"{doc.get('acked', '?')} acked; kill-phase p99 "
            f"{kill.get('p99_s', '?')}s vs warm {warm.get('p99_s', '?')}s "
            f"(x{doc.get('kill_p99_over_warm_p99', '?')})"
        ),
        "backend": doc.get("backend", "?"),
        "detail": (
            f"affinity hit_rate {aff.get('hit_rate', '?')}; "
            f"{len(doc.get('adoptions') or [])} adoption(s); "
            f"bit_identical={doc.get('bit_identical')}; "
            f"drain rc={doc.get('drain_rc')}; "
            f"zero_lost={acc.get('zero_lost_acked')}"
        ),
    }


def _grayfail_row(doc: dict) -> dict:
    """r14: gray-failure defense — SIGSTOP (wedge) one member under live
    traffic: breaker-open latency, hedge win rate, fenced zombie exit
    (docs/SERVING.md "Gray failures")."""
    warm = doc.get("warm", {}) or {}
    wedge = doc.get("wedge_phase", {}) or {}
    steady = wedge.get("steady_tenant", {}) or {}
    hedge = wedge.get("hedge", {}) or {}
    zombie = wedge.get("zombie", {}) or {}
    kill = doc.get("kill_phase", {}) or {}
    acc = doc.get("acceptance", {}) or {}
    return {
        "metric": doc.get("metric", "fleet_grayfail_traffic"),
        "headline": (
            f"SIGSTOP wedge of 1/{doc.get('members', '?')} members: "
            f"{len(doc.get('lost_acked') or [])} lost of "
            f"{doc.get('acked', '?')} acked; steady-tenant p99 "
            f"{steady.get('p99_s', '?')}s vs warm {warm.get('p99_s', '?')}s "
            f"(x{doc.get('wedge_steady_p99_over_warm_p99', '?')}); "
            f"breaker open in {wedge.get('breaker_open_latency_s', '?')}s"
        ),
        "backend": doc.get("backend", "?"),
        "detail": (
            f"hedge win_rate {hedge.get('win_rate', '?')} "
            f"({hedge.get('launched', '?')} launched); fenced zombie "
            f"exit={zombie.get('fenced_exit')} (epoch "
            f"{zombie.get('fence_epoch', '?')}); kill-phase p99 "
            f"{kill.get('p99_s', '?')}s "
            f"(x{doc.get('kill_p99_over_warm_p99', '?')}); "
            f"{len(doc.get('adoptions') or [])} adoption(s); "
            f"bit_identical={doc.get('bit_identical')}; "
            f"zero_lost={acc.get('zero_lost_acked')}"
        ),
    }


def _supervised_row(doc: dict) -> dict:
    """r15: the fleet supervisor — SIGKILL the gateway child AND a member
    under live traffic: incarnation-bumped restart, journal adoption plus
    fresh-dir respawn, the healed member serving before the run ends
    (docs/SERVING.md "Supervision")."""
    warm = doc.get("warm", {}) or {}
    gk = doc.get("gateway_kill_phase", {}) or {}
    mk = doc.get("member_kill_phase", {}) or {}
    acc = doc.get("acceptance", {}) or {}
    return {
        "metric": doc.get("metric", "fleet_supervised_traffic"),
        "headline": (
            f"kill -9 of the GATEWAY (+1 member): "
            f"{len(doc.get('lost_acked') or [])} lost of "
            f"{doc.get('acked', '?')} acked; gateway-kill p99 "
            f"{gk.get('p99_s', '?')}s vs warm {warm.get('p99_s', '?')}s "
            f"(x{doc.get('gateway_kill_p99_over_floor', '?')} the "
            f"failover floor); rebooted as incarnation "
            f"{gk.get('incarnation', '?')} in "
            f"{gk.get('restart_latency_s', '?')}s"
        ),
        "backend": doc.get("backend", "?"),
        "detail": (
            f"member {mk.get('victim', '?')} adopted by "
            f"{mk.get('adopter', '?')} AND respawned as "
            f"{mk.get('replacement', '?')} (fresh_dir={mk.get('fresh_dir')}, "
            f"served={acc.get('respawned_member_served')}); member-kill "
            f"p99 {mk.get('p99_s', '?')}s "
            f"(x{doc.get('member_kill_p99_over_floor', '?')} floor); "
            f"bit_identical={doc.get('bit_identical')}; "
            f"zero_lost={acc.get('zero_lost_acked')}"
        ),
    }


def _reduce_row(doc: dict) -> dict:
    """r16: the collective reduce plane — one jitted program + one
    all_gather hop per tree level instead of per-round host dispatches
    and filesystem packets (docs/PERFORMANCE.md "Collective reduce
    plane")."""
    host = doc.get("host_arm", {}) or {}
    coll = doc.get("collective_arm", {}) or {}
    fb = doc.get("fallback_arm", {}) or {}
    return {
        "metric": doc.get("metric", "collective_reduce_plane"),
        "headline": (
            f"{_fmt(doc.get('n_edges') or 0)}-edge solve, "
            f"{doc.get('solver_shards', '?')} shards: "
            f"{coll.get('dispatches_per_level', '?')} dispatch/level "
            f"collective vs {host.get('dispatches_per_level', '?')} host "
            f"(x{doc.get('dispatch_ratio_host_over_collective', '?')} "
            f"fewer); {coll.get('collective_hops', '?')} hops for "
            f"{doc.get('tree_levels', '?')} levels, "
            f"{coll.get('packet_files_written', '?')} packet files"
        ),
        "backend": doc.get("backend", "?"),
        "detail": (
            f"{_fmt(coll.get('bytes_over_interconnect') or 0)} B over "
            f"interconnect; bit_identical="
            f"{coll.get('bit_identical_to_host')}; fallback arm "
            f"{fb.get('resolutions')} bit_identical="
            f"{fb.get('bit_identical_to_host')}; "
            f"accepted={doc.get('accepted')}"
        ),
    }


#: per-metric extractors
_RAW_ROWS = {
    "sharded_sweep_dispatch": _sweep_row,
    "task_graph_fusion_workflow": _fuse_row,
    "distributed_agglomeration_solve": _solve_row,
    "service_mode_traffic": _serve_row,
    "ragged_paged_sweep": _ragged_row,
    "device_resident_data_plane": _device_row,
    "fleet_failover_traffic": _fleet_row,
    "fleet_grayfail_traffic": _grayfail_row,
    "fleet_supervised_traffic": _supervised_row,
    "collective_reduce_plane": _reduce_row,
}


def extract_row(path: str) -> dict:
    round_no = int(re.search(r"BENCH_r(\d+)", os.path.basename(path)).group(1))
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return {
            "round": round_no, "metric": "?", "backend": "?",
            "headline": f"unreadable ({e.__class__.__name__})", "detail": "",
        }
    row = _RAW_ROWS.get(str(doc.get("metric")), _unknown_row)(doc)
    row["round"] = round_no
    return row


def collect_rows() -> list:
    paths = sorted(glob.glob(os.path.join(ROOT, "BENCH_r*.json")))
    return [extract_row(p) for p in paths]


def render_table(rows: list) -> str:
    lines = [
        "| round | metric | headline | backend | detail |",
        "|---|---|---|---|---|",
    ]
    for r in sorted(rows, key=lambda r: r["round"]):
        lines.append(
            f"| r{r['round']:02d} | {r['metric']} | {r['headline']} | "
            f"{r['backend']} | {r['detail']} |"
        )
    return "\n".join(lines)


def write_doc(table: str, doc_path: str = DOC) -> bool:
    """Replace the marker-delimited block in docs/PERFORMANCE.md (atomic:
    temp + os.replace, like every shared-file write in this repo)."""
    with open(doc_path) as f:
        text = f.read()
    if MARK_BEGIN not in text or MARK_END not in text:
        print(
            f"markers not found in {doc_path}; not writing", file=sys.stderr
        )
        return False
    head, rest = text.split(MARK_BEGIN, 1)
    _, tail = rest.split(MARK_END, 1)
    new = f"{head}{MARK_BEGIN}\n{table}\n{MARK_END}{tail}"
    tmp = f"{doc_path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        f.write(new)
    os.replace(tmp, doc_path)
    return True


def main(argv) -> int:
    rows = collect_rows()
    if not rows:
        print("no BENCH_r*.json files found", file=sys.stderr)
        return 1
    table = render_table(rows)
    print(table)
    if "--write" in argv:
        if not write_doc(table):
            return 1
        print(f"\nwrote trajectory table into {DOC}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
