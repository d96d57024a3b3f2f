"""Process-wide counters, gauges, and XLA compile-event accounting.

Three kinds of state:

* **Counters/gauges** — a thread-safe name->number registry
  (`incr`/`add_seconds`/`set_gauge`). Always writable: low-frequency
  producers (collective retries in resilience/faults.py, serving
  compiles) count unconditionally so forensic counters exist even with
  telemetry off; HOT-path producers (per-request transfer bytes) gate on
  `is_active()`, flipped by `telemetry.set_mode`.
* **XLA compile events** — a jax monitoring listener recording every
  trace/lower/backend-compile duration event in the process, by event
  name and (where JAX names it) by jitted function, with accumulated
  seconds. This is the grown-up version of the counter
  `tests/test_serving.py` used to keep private: serving tests and
  telemetry tests now import `compile_events()` from here.
* **Set-up stages** — `setup_*_seconds`, fed by `spans.stage(counter,
  name)` round work done once a Dataset or learner, unconditionally
  like the compile seconds.
* **Peak host RSS** — read live from getrusage at snapshot time.

Prometheus text exposition (`prometheus_text`) renders all of it plus
caller-supplied extras; the serving `/metrics` endpoint is a thin wrapper
over it.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Dict, List, Optional

__all__ = ["incr", "add_seconds", "set_gauge", "get", "is_active",
           "set_active", "snapshot", "reset", "install_compile_listener",
           "compile_events", "compile_seconds", "uncounted",
           "compile_seconds_by_function", "peak_rss_bytes",
           "prometheus_text"]

_lock = threading.Lock()
_counters: Dict[str, float] = {}
_gauges: Dict[str, float] = {}
_active = False


def set_active(flag: bool) -> None:
    """Hot-path gate (telemetry.set_mode owns this): per-request counter
    sites check `is_active()` before paying the registry lock."""
    global _active
    _active = bool(flag)


def is_active() -> bool:
    return _active


def incr(name: str, n: float = 1) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def add_seconds(name: str, seconds: float) -> None:
    incr(name, float(seconds))


def set_gauge(name: str, value: float) -> None:
    with _lock:
        _gauges[name] = float(value)


def get(name: str, default: float = 0) -> float:
    with _lock:
        return _counters.get(name, _gauges.get(name, default))


def reset() -> None:
    """Clear counters/gauges (compile-event history is process-lifetime
    ground truth and survives; tests mark a baseline length instead)."""
    with _lock:
        _counters.clear()
        _gauges.clear()


# -- XLA compile events -----------------------------------------------------
_compile_events: List[str] = []
_compile_seconds: Dict[str, float] = {}
_compile_seconds_by_fun: Dict[str, Dict[str, float]] = {}
_listener_state = {"installed": False, "available": True}
_held: List[List[str]] = []        # one list an open `uncounted()`


def _on_duration_event(name: str, *args, **kw) -> None:
    if "compile" not in name:
        return
    if _held:
        with _lock:
            for held in _held:
                held.append(name)
        return
    secs = float(args[0]) if args else 0.0
    fun = kw.get("fun_name")     # JAX names the jitted function on its
    _compile_events.append(name)    # trace / lower / compile events
    with _lock:
        _compile_seconds[name] = _compile_seconds.get(name, 0.0) + secs
        if fun:
            by_event = _compile_seconds_by_fun.setdefault(str(fun), {})
            by_event[name] = by_event.get(name, 0.0) + secs


def install_compile_listener() -> bool:
    """Idempotently register the jax monitoring listener. Returns whether
    compile events are being recorded (False on jax versions without the
    private monitoring module — callers fall back to cache counters)."""
    if _listener_state["installed"]:
        return True
    if not _listener_state["available"]:
        return False
    try:
        from jax._src import monitoring as _monitoring
        _monitoring.register_event_duration_secs_listener(_on_duration_event)
        _listener_state["installed"] = True
        return True
    except ImportError:
        _listener_state["available"] = False
        return False


@contextlib.contextmanager
def uncounted():
    """Compile events inside are kept out of `compile_events()` and
    `compile_seconds()`, and handed back instead: the block's value is
    the list of their names. For a probe of a program that has already
    run (`GBDT.fused_stage_map`), which must not add to the counters of
    the run it looks at, and which reports a lowering or a compile it
    did not expect. Installs the listener. Process-wide, like the
    listener: a compile on another thread meanwhile lands in the list
    too."""
    install_compile_listener()
    held: List[str] = []
    with _lock:
        _held.append(held)
    try:
        yield held
    finally:
        with _lock:
            _held[:] = [h for h in _held if h is not held]


def compile_events() -> List[str]:
    """The LIVE list of compile-related XLA duration events seen by this
    process (installs the listener on first call). Callers snapshot with
    `len()` before an operation and compare after — the no-recompile
    acceptance pattern from the serving tests."""
    install_compile_listener()
    return _compile_events


def compile_seconds() -> Dict[str, float]:
    """Accumulated compile seconds per XLA event name."""
    install_compile_listener()
    with _lock:
        return dict(_compile_seconds)


def compile_seconds_by_function() -> Dict[str, Dict[str, float]]:
    """{jitted function's name: {XLA event name: seconds}} — WHAT was
    traced, lowered or compiled, where `compile_seconds()` says only how
    long. A compile inside a steady-state window names its culprit here
    (`step_impl` is the fused tree program)."""
    install_compile_listener()
    with _lock:
        return {fun: dict(by_event)
                for fun, by_event in _compile_seconds_by_fun.items()}


def peak_rss_bytes() -> int:
    try:
        import resource
        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(ru) * 1024      # linux reports kilobytes
    except Exception:              # pragma: no cover - non-posix
        return 0


def snapshot() -> dict:
    with _lock:
        counters = dict(_counters)
        gauges = dict(_gauges)
        by_event = dict(_compile_seconds)
    gauges["peak_rss_bytes"] = peak_rss_bytes()
    return {
        "counters": counters,
        "gauges": gauges,
        "compile": {"events": len(_compile_events),
                    "seconds": round(sum(by_event.values()), 6),
                    "by_event": {k: round(v, 6)
                                 for k, v in sorted(by_event.items())}},
    }


# -- Prometheus text exposition --------------------------------------------
_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def _metric_name(name: str) -> str:
    return "lgbm_tpu_" + _NAME_RE.sub("_", str(name))


def _split_labels(name: str):
    """`serve_version_requests{version="v3"}` -> (family, `{...}`).
    Plain names pass through with an empty label set; the label block is
    already Prometheus syntax and is appended verbatim after the
    sanitized family name."""
    name = str(name)
    brace = name.find("{")
    if brace < 0:
        return name, ""
    return name[:brace], name[brace:]


def prometheus_text(extra_counters: Optional[Dict] = None,
                    latency: Optional[Dict[str, dict]] = None,
                    extra_gauges: Optional[Dict] = None) -> str:
    """Render everything as Prometheus text format (version 0.0.4).
    `latency` takes serving-stats histogram snapshots ({name: {count,
    mean_ms, p50_ms, p95_ms, p99_ms}}) and renders them as summaries."""
    snap = snapshot()
    lines: List[str] = []
    typed = set()                    # families already TYPE-declared:
    # labeled series of one family share a single TYPE line

    def emit(name: str, kind: str, value) -> None:
        family, labels = _split_labels(name)
        mname = _metric_name(family)
        if mname not in typed:
            typed.add(mname)
            lines.append(f"# TYPE {mname} {kind}")
        lines.append(f"{mname}{labels} {value}")

    merged_counters = dict(snap["counters"])
    merged_counters.update(extra_counters or {})
    for key in sorted(merged_counters):
        family, labels = _split_labels(key)
        emit(family + "_total" + labels, "counter", merged_counters[key])
    emit("compile_events_total", "counter", snap["compile"]["events"])
    emit("compile_seconds_total", "counter", snap["compile"]["seconds"])
    merged_gauges = dict(snap["gauges"])
    merged_gauges.update(extra_gauges or {})
    for key in sorted(merged_gauges):
        emit(key, "gauge", merged_gauges[key])
    for key in sorted(latency or {}):
        hist = latency[key]
        family, labels = _split_labels(key)
        mname = _metric_name(family) + "_seconds"
        if mname not in typed:
            typed.add(mname)
            lines.append(f"# TYPE {mname} summary")
        for quantile, field in (("0.5", "p50_ms"), ("0.95", "p95_ms"),
                                ("0.99", "p99_ms")):
            qlabels = (labels[:-1] + f',quantile="{quantile}"}}' if labels
                       else f'{{quantile="{quantile}"}}')
            lines.append(f'{mname}{qlabels} {hist[field] / 1e3}')
        total_s = hist["mean_ms"] * hist["count"] / 1e3
        lines.append(f"{mname}_sum{labels} {total_s}")
        lines.append(f"{mname}_count{labels} {hist['count']}")
    return "\n".join(lines) + "\n"
