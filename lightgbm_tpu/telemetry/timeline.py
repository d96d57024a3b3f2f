"""Cross-rank merged timelines + critical-path attribution (rank 0).

aggregate.py already gathers per-rank summaries to rank 0; since PR 15
each summary also carries the rank's per-iteration records (and, in
trace mode, its raw span events). This module is rank 0's sink for
them: spans are re-based onto rank 0's clock with the offsets
clock.py learned from the heartbeat lane, given ``pid = rank`` so one
Chrome/Perfetto file shows one track per rank, and merged via
``write_merged_trace``. On top of the same records it computes the
**critical path** of every iteration:

In synchronous SPMD every rank's iteration wall converges to the
slowest rank's, but each rank spends the difference *waiting inside a
blocking phase* (``collective`` / ``host_sync`` / ``record_fetch`` /
``mask_sync`` / ``dist_hist_exchange``), not computing. Per iteration and per blocking
phase, the minimum time any rank spent there is that phase's intrinsic
cost; everything a rank spends above the minimum is wait:

    wait_r    = sum_p max(0, phases_r[p] - min_s phases_s[p])
    compute_r = sum(all phases_r) - wait_r

The rank with the least wait is the **critical rank** — the one every
other rank was waiting for. That turns the aggregate straggler flag
into an attribution: a ``delay_ms`` fault on rank 1 shows up as rank
0's wait and rank 1 being critical. Since the recorder's phases do not
nest and cover >=95% of iteration wall, ``compute_r + wait_r`` sums to
the iteration wall within the coverage slack — the acceptance check.

Single-process runs never touch this module (aggregate's tick is gated
on a real group); non-zero cost only exists on rank 0 at tick
boundaries.
"""
from __future__ import annotations

import json
import os
import threading
from collections import deque
from typing import Dict, List, Optional

__all__ = ["BLOCKING_PHASES", "ingest", "attribute_pending",
           "attribute_iteration", "critical_path", "per_rank_totals",
           "merged_trace_events", "write_merged_trace", "snapshot",
           "reset"]

# phases whose time includes waiting on peers; everything above the
# fleet-minimum in one of these is attributed as collective-wait
BLOCKING_PHASES = ("collective", "host_sync", "record_fetch", "mask_sync",
                   "dist_hist_exchange")

_MAX_ATTRIBUTIONS = 4096
_MAX_PENDING_ITERS = 1024


def _span_cap() -> int:
    try:
        return max(256, int(os.environ.get(
            "LGBM_TPU_TIMELINE_SPANS", "20000") or 20000))
    except ValueError:
        return 20000


_lock = threading.Lock()
_state = {
    "pending": {},       # iteration -> {rank: {wall_s, phases, ts}}
    "spans": {},         # rank -> deque of re-based chrome events
    "iter_marks": {},    # rank -> deque of synthesized iteration events
    "ranks": set(),
    "attributions": deque(maxlen=_MAX_ATTRIBUTIONS),
    "totals": {},        # rank -> {compute_s, wait_s, iters}
}


def ingest(rank: int, iter_records: Optional[List[dict]],
           spans: Optional[List[dict]] = None,
           offset_s: float = 0.0) -> None:
    """Fold one rank's shipped iteration records (and optionally raw
    span events) into the store. ``offset_s`` is the peer's clock
    offset (clock.offset_s): peer timestamps minus the offset land on
    this process's time base."""
    rank = int(rank)
    off_us = float(offset_s) * 1e6
    with _lock:
        _state["ranks"].add(rank)
        for rec in iter_records or ():
            it = rec.get("iteration")
            if not isinstance(it, int):
                continue
            ent = {"wall_s": float(rec.get("wall_s") or 0.0),
                   "phases": dict(rec.get("phases") or {})}
            ts = rec.get("ts")
            if ts is not None:
                ent["ts"] = float(ts) - float(offset_s)
            _state["pending"].setdefault(it, {})[rank] = ent
        if spans:
            dq = _state["spans"].setdefault(
                rank, deque(maxlen=_span_cap()))
            for ev in spans:
                ev = dict(ev)
                ev["pid"] = rank
                try:
                    ev["ts"] = float(ev.get("ts", 0.0)) - off_us
                except (TypeError, ValueError):
                    continue
                dq.append(ev)
        # bound the pending map: an iteration some rank never reports
        # (kill, shrink) must not pin memory forever
        while len(_state["pending"]) > _MAX_PENDING_ITERS:
            del _state["pending"][min(_state["pending"])]


def attribute_iteration(iteration: int,
                        per_rank: Dict[int, dict]) -> dict:
    """Pure critical-path decomposition of one iteration (unit-testable
    without any distributed state). ``per_rank`` maps rank ->
    {"wall_s", "phases"}."""
    mins: Dict[str, float] = {}
    for name in BLOCKING_PHASES:
        vals = [float((rec.get("phases") or {}).get(name, 0.0))
                for rec in per_rank.values()]
        if any(v > 0 for v in vals):
            mins[name] = min(vals)
    ranks = {}
    for rank, rec in per_rank.items():
        phases = rec.get("phases") or {}
        total = sum(float(v) for v in phases.values())
        wait = sum(max(0.0, float(phases.get(name, 0.0)) - floor)
                   for name, floor in mins.items())
        ranks[int(rank)] = {
            "compute_s": round(total - wait, 6),
            "wait_s": round(wait, 6),
            "wall_s": round(float(rec.get("wall_s") or 0.0), 6)}
    critical = min(sorted(ranks),
                   key=lambda r: (ranks[r]["wait_s"], r))
    return {"iteration": int(iteration), "critical_rank": critical,
            "ranks": ranks}


def attribute_pending(world: int) -> List[dict]:
    """Attribute every pending iteration for which all ``world`` ranks
    have reported; returns the new rows (aggregate attaches them to the
    fleet event)."""
    rows: List[dict] = []
    with _lock:
        ready = sorted(it for it, per_rank in _state["pending"].items()
                       if len(per_rank) >= int(world))
        for it in ready:
            per_rank = _state["pending"].pop(it)
            row = attribute_iteration(it, per_rank)
            rows.append(row)
            _state["attributions"].append(row)
            for rank, ent in row["ranks"].items():
                tot = _state["totals"].setdefault(
                    rank, {"compute_s": 0.0, "wait_s": 0.0, "iters": 0})
                tot["compute_s"] += ent["compute_s"]
                tot["wait_s"] += ent["wait_s"]
                tot["iters"] += 1
            # synthesized per-iteration marks give summary-mode merges
            # (no span ring shipped) a timeline track per rank
            for rank, ent in per_rank.items():
                if ent.get("ts") is None or rank in _state["spans"]:
                    continue
                dq = _state["iter_marks"].setdefault(
                    rank, deque(maxlen=_span_cap()))
                dq.append({
                    "name": "iteration", "ph": "X",
                    "ts": (ent["ts"] - ent["wall_s"]) * 1e6,
                    "dur": ent["wall_s"] * 1e6, "pid": rank, "tid": 0,
                    "args": {"index": it,
                             "phases": {k: round(float(v), 6)
                                        for k, v in
                                        (ent.get("phases") or {}).items()}},
                })
    return rows


def critical_path(last: Optional[int] = None) -> List[dict]:
    """Attribution rows, oldest first (``last`` trims to the newest N)."""
    with _lock:
        rows = list(_state["attributions"])
    return rows[-last:] if last else rows


def per_rank_totals() -> Dict[int, dict]:
    """Cumulative per-rank compute/wait seconds over every attributed
    iteration (dist_smoke's ``critical_path`` payload)."""
    with _lock:
        return {r: {"compute_s": round(t["compute_s"], 6),
                    "wait_s": round(t["wait_s"], 6), "iters": t["iters"]}
                for r, t in _state["totals"].items()}


def merged_trace_events() -> List[dict]:
    """All re-based events plus process_name metadata, ready for a
    Chrome trace doc. Empty when nothing was ingested."""
    with _lock:
        ranks = sorted(_state["ranks"])
        body: List[dict] = []
        for rank in ranks:
            body.extend(_state["spans"].get(rank, ()))
            if rank not in _state["spans"]:
                body.extend(_state["iter_marks"].get(rank, ()))
    if not body:
        return []
    meta = [{"name": "process_name", "ph": "M", "pid": rank,
             "args": {"name": f"rank {rank}"}} for rank in ranks]
    body.sort(key=lambda ev: ev.get("ts", 0.0))
    return meta + body


def write_merged_trace(path: str) -> Optional[str]:
    """Write the merged fleet trace as Chrome trace-event JSON; returns
    ``path``, or None when there is nothing to write."""
    events = merged_trace_events()
    if not events:
        return None
    with open(path, "w") as fh:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, fh)
    return path


def snapshot() -> dict:
    """JSON-able summary for postmortem bundles."""
    with _lock:
        ranks = sorted(_state["ranks"])
        spans = {r: len(_state["spans"].get(r, ()))
                 for r in ranks}
    return {"ranks": ranks, "spans_per_rank": spans,
            "totals": {str(r): t for r, t in per_rank_totals().items()},
            "critical_path": critical_path(last=256)}


def reset() -> None:
    with _lock:
        _state["pending"].clear()
        _state["spans"].clear()
        _state["iter_marks"].clear()
        _state["ranks"].clear()
        _state["attributions"].clear()
        _state["totals"].clear()
