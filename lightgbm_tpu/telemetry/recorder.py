"""Per-iteration phase breakdown for training.

The trainer brackets each boosting iteration with `iteration(i)` and the
hot sites inside it (gradient compute, learner dispatch, host syncs,
score updates, collectives) with `phase(name)`. The recorder accumulates
per-phase seconds twice: into the CURRENT iteration (reported by
`last_iteration()`, streamed by the `record_telemetry` callback) and
into run totals (reported by `phase_breakdown()`).

Canonical phase names, so breakdowns from different paths diff cleanly:

    boost_avg   gradient   quantize   bagging    hist      split
    partition   grow_dispatch         grow_fused feature_mask
    mask_sync   record_fetch          tree_replay          valid_update
    host_sync   score_update          sentry     collective
    eval        stream_wait           dist_hist_exchange
    fused_step_build

`grow_fused` is the vmap-batched multiclass dispatch: all K per-class
trees of one iteration as ONE batched whole-tree program
(device_learner.train_batched, `grow_program=fused_tree`).

`stream_wait` is the out-of-core pipeline's blocking H2D residue
(io/stream.py): near-zero means the double buffer hid the transfers.
`dist_hist_exchange` brackets the host-loop data-parallel/voting
histogram allreduce — in row-sharded pods it is the ONLY cross-host
traffic inside an iteration, so its share of wall is the network bill.

One program can fuse several (the device learners grow the whole tree in
one dispatch — that is `grow_dispatch`; the blocking fetch of its split
records is `record_fetch`, which is the wait for the device on the
synchronous paths; on the pipelined fused path the host meets the
running program earlier, in `mask_sync`, the feature mask's device round
trip, and `record_fetch` is short; rebuilding the host tree is
`tree_replay`; `host_sync` is the serial learner's per-split host loop);
free-form names are accepted. Phases must NOT nest — each second should
be attributed exactly once, so `phase_sum / wall` is a meaningful
coverage ratio. Phases recorded outside an open iteration (engine-side
eval, a save-triggered materialize, the once-a-booster
`fused_step_build`) count toward run totals but not toward iteration
wall/coverage.

Every hook ALWAYS enters a profiler annotation (`lgbm/<name>`; the
iteration a `StepTraceAnnotation("lgbm/iteration", step_num=i)`, so the
phases of one boosting iteration share its step number), which the
profiler gates itself: about a microsecond with no session open.
Disabled (default) that is all a hook does — no clock read, no lock, no
recorder state (tests/test_telemetry.py counts the clock reads).
Enabled, the same enter/exit also times the block once, for the totals
here and the span ring.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from .spans import PREFIX, add_event

__all__ = ["enable", "enabled", "iteration", "phase", "last_iteration",
           "phase_breakdown", "reset"]

_enabled = False
_lock = threading.Lock()
_totals: Dict[str, list] = {}       # name -> [seconds, calls]
_iter_count = 0
_iter_wall = 0.0
_phase_in_iter = 0.0
_last: Optional[dict] = None
_cur: Optional[dict] = None         # {"index", "t0", "phases"}


def enable(flag: bool = True) -> None:
    global _enabled
    _enabled = bool(flag)


def enabled() -> bool:
    return _enabled


def _step_annotation(index: int):
    return StepTraceAnnotation(PREFIX + "iteration", step_num=index)


class _IterCtx:
    __slots__ = ("index", "annotation")

    def __init__(self, index: int):
        self.index = index
        self.annotation = _step_annotation(index)

    def __enter__(self):
        global _cur
        self.annotation.__enter__()
        _cur = {"index": self.index, "t0": time.perf_counter(),
                "phases": {}}
        return self

    def __exit__(self, *exc):
        global _cur, _iter_count, _iter_wall, _phase_in_iter, _last
        cur, _cur = _cur, None
        now = time.perf_counter()
        self.annotation.__exit__(*exc)
        if cur is None:            # reentrant/forced-closed: nothing open
            return False
        wall = now - cur["t0"]
        with _lock:
            _iter_count += 1
            _iter_wall += wall
            _phase_in_iter += sum(cur["phases"].values())
            _last = {"iteration": cur["index"], "wall_s": wall,
                     "phases": dict(cur["phases"])}
        add_event("iteration", wall, t0=cur["t0"], index=cur["index"])
        return False


class _PhaseCtx:
    __slots__ = ("name", "t0", "annotation")

    def __init__(self, name: str):
        self.name = name
        self.annotation = TraceAnnotation(PREFIX + name)

    def __enter__(self):
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.annotation.__exit__(*exc)
        with _lock:
            ent = _totals.setdefault(self.name, [0.0, 0])
            ent[0] += dt
            ent[1] += 1
            if _cur is not None:
                phases = _cur["phases"]
                phases[self.name] = phases.get(self.name, 0.0) + dt
        add_event(self.name, dt, t0=self.t0)
        return False


def iteration(index: int):
    """Bracket one boosting iteration (GBDT.train_one_iter owns this)."""
    if not _enabled:
        return _step_annotation(index)
    return _IterCtx(index)


def phase(name: str):
    """Attribute a block to `name` within the current iteration."""
    if not _enabled:
        return TraceAnnotation(PREFIX + name)
    return _PhaseCtx(name)


def last_iteration() -> Optional[dict]:
    """The most recently closed iteration's {iteration, wall_s, phases}
    (the `record_telemetry` callback's feed)."""
    with _lock:
        return None if _last is None else {
            "iteration": _last["iteration"], "wall_s": _last["wall_s"],
            "phases": dict(_last["phases"])}


def phase_breakdown() -> dict:
    """Run-total breakdown: per-phase seconds/calls, iteration count and
    wall, and `coverage` = in-iteration phase seconds / iteration wall
    (the >=90% acceptance metric; None before any iteration closes)."""
    with _lock:
        phases = {k: {"secs": round(v[0], 6), "calls": v[1]}
                  for k, v in sorted(_totals.items())}
        wall, psum, n = _iter_wall, _phase_in_iter, _iter_count
    return {"phases": phases, "iterations": n,
            "wall_s": round(wall, 6), "phase_sum_s": round(psum, 6),
            "coverage": round(psum / wall, 4) if wall > 0 else None}


def reset() -> None:
    global _iter_count, _iter_wall, _phase_in_iter, _last, _cur
    with _lock:
        _totals.clear()
        _iter_count = 0
        _iter_wall = 0.0
        _phase_in_iter = 0.0
        _last = None
        _cur = None
