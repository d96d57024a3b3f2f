"""Nestable spans: the profiler's annotations, plus a Chrome trace-event
ring in trace mode.

A span is a `with telemetry.spans.span("name"):` block. It ALWAYS enters
a `jax.profiler.TraceAnnotation("lgbm/<name>")`, so any profiler session
(`jax.profiler.trace`, `LGBM_TPU_XLA_TRACE`, the benchmark's `--trace 1`)
shows the program's spans on the profiler's clock beside the device's
timeline; the profiler gates these itself, and with no session open one
costs about a microsecond.

In trace mode the span is also timed on `time.perf_counter()` and lands
in a bounded ring buffer (newest win; default 65536 events,
`LGBM_TPU_TRACE_RING` overrides), exported as Chrome/Perfetto
trace-event JSON via `dump_trace(path)` — load the file in
chrome://tracing or ui.perfetto.dev. Below trace mode a span reads no
clock, takes no lock and keeps no state. Thread identity rides on each
ring event (`tid`), so concurrent serving threads render as separate
tracks; nesting within a thread is inferred from the timestamps, the
standard trace-event semantics.

`stage(counter, name)` is a span for work done once a Dataset or learner
(bin finding, binning, bundling, the learner's packing and H2D): the
same annotation and ring event, and its seconds also go into a
`setup_*_seconds` counter whatever the mode, like the compile seconds.
Never inside an iteration.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Dict, List

from jax.profiler import TraceAnnotation

from .counters import add_seconds

__all__ = ["PREFIX", "span", "stage", "add_event", "enable", "enabled",
           "events", "clear", "dump_trace", "epoch", "set_pid", "pid"]

# every annotation of this package in a profiler trace starts with this
PREFIX = "lgbm/"

_enabled = False
_lock = threading.Lock()
_events = deque(maxlen=max(16, int(os.environ.get(
    "LGBM_TPU_TRACE_RING", 65536))))

# perf_counter -> unix epoch at import: every event's `ts` lands on the
# wall clock (microseconds since the unix epoch), a base that is common
# across processes — which is what merging per-rank traces requires.
# Monotonicity within the process is preserved (the offset is constant).
_EPOCH = time.time() - time.perf_counter()

# trace `pid` override: the distributed bootstrap sets this to the rank
# so per-rank dumps load side-by-side in Perfetto (one track per rank)
# even before rank 0 merges them. None = real os.getpid().
_pid = None


def epoch() -> float:
    """The constant perf_counter -> unix-seconds offset used for `ts`."""
    return _EPOCH


def set_pid(value) -> None:
    """Override the `pid` stamped on trace events (bootstrap passes the
    rank; None restores the real process id)."""
    global _pid
    _pid = None if value is None else int(value)


def pid() -> int:
    return os.getpid() if _pid is None else _pid


def enable(flag: bool = True) -> None:
    global _enabled
    _enabled = bool(flag)


def enabled() -> bool:
    return _enabled


class _Span:
    """The annotation plus one ring event (trace mode); a set-up stage
    also feeds its seconds counter. One pair of clock reads serves all."""
    __slots__ = ("name", "args", "t0", "annotation", "counter")

    def __init__(self, name: str, args: Dict, counter: str = None):
        self.name = name
        self.args = args
        self.counter = counter
        self.annotation = TraceAnnotation(PREFIX + name, **args)

    def __enter__(self):
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.annotation.__exit__(*exc)
        if self.counter is not None:
            add_seconds(self.counter, dt)
        add_event(self.name, dt, t0=self.t0, **self.args)
        return False


def span(name: str, **args):
    """Context manager marking a block as `lgbm/<name>` in any profiler
    session and, in trace mode, timing it as one ring event. `args`
    become the event's `args` payload (small JSON-able values only)."""
    if not _enabled:
        return TraceAnnotation(PREFIX + name, **args)
    return _Span(name, args)


def stage(counter: str, name: str):
    """Bracket one set-up stage: a `lgbm/<name>` span whose seconds are
    also added to `counter` whatever the telemetry mode (once a Dataset
    or learner: keep it out of iterations)."""
    return _Span(name, {}, counter)


def add_event(name: str, dur_s: float, t0: float = None, **args) -> None:
    """Record an already-timed block (the recorder's phases reuse their
    own clock reads through this instead of double-timing)."""
    if not _enabled:
        return
    if t0 is None:
        t0 = time.perf_counter() - dur_s
    ev = {"name": name, "ph": "X", "ts": (t0 + _EPOCH) * 1e6,
          "dur": dur_s * 1e6, "pid": pid(),
          "tid": threading.get_ident()}
    if args:
        ev["args"] = args
    with _lock:
        _events.append(ev)


def events() -> List[dict]:
    """Snapshot of the ring (oldest first)."""
    with _lock:
        return list(_events)


def clear() -> None:
    with _lock:
        _events.clear()


def dump_trace(path: str) -> str:
    """Write the ring as a Chrome trace-event JSON file; returns `path`.
    Timestamps are wall-clock microseconds (unix epoch base), so dumps
    from different ranks share one time base and load side-by-side."""
    meta = [{"name": "process_name", "ph": "M", "pid": pid(),
             "args": {"name": (f"rank {_pid}" if _pid is not None
                               else f"pid {os.getpid()}")}}]
    doc = {"traceEvents": meta + events(), "displayTimeUnit": "ms"}
    with open(path, "w") as fh:
        json.dump(doc, fh)
    return path
