"""Telemetry: structured tracing + metrics shared by training and serving.

Three layers. Every span, phase and iteration hook always enters a
`jax.profiler` annotation named `lgbm/<name>` (about a microsecond when
no profiler session is open), so any profiler trace shows the program's
own spans on the device trace's clock; what the modes below switch is
the bookkeeping on top:

* `spans` — nestable spans; in trace mode also a ring buffer with
  Chrome/Perfetto trace-event export (`telemetry.dump_trace(path)`).
* `counters` — process-wide counters/gauges (XLA compile events +
  seconds by event and by jitted function, set-up stage seconds, device
  transfer bytes, collective retries, peak host RSS) with Prometheus
  text exposition (`prometheus_text`, the serving `/metrics` endpoint).
* `recorder` — per-iteration phase breakdown (gradient, hist, split,
  partition, score_update, record_fetch, ...) consumed by
  `telemetry_summary()` and the `record_telemetry` callback.

Inside the tree program the stages are `jax.named_scope("lgbm.<stage>")`
(`STAGES`); `stage_map(hlo_text)` maps a compiled module's instructions
to them, which is how a device trace (whose events carry instruction
names and nothing else) is read by stage, and
`table_copies_in_split_loop(hlo_text)` counts the copies of the packed
table the compiler left inside the split loop (none, while the table
is updated in place), and `hist_plane_elems_per_row(hlo_text)` sizes the
one-hot planes the histogram stages write out (F x 256 a row unfactored).

Built on top of those, the flight-recorder layer: `events` (durable
structured per-iteration JSONL stream, `LGBM_TPU_EVENTS=path`),
`watchdogs` (slow-iteration / overlap-regression / grad-norm-spike
monitors) and `aggregate` (per-rank summaries gathered to rank 0 with
a straggler detector). tools/run_report.py renders the event stream as
a markdown run report.

Modes (`telemetry` config param, `LGBM_TPU_TELEMETRY` env — env wins):

* ``off``     every hook is its profiler annotation and nothing else (no
  clock read, no lock, no state); the float path is byte-for-byte
  unchanged (compile events and set-up stage seconds still accumulate —
  they are process-lifetime forensics, not a hot path).
* ``summary`` recorder + hot-path counters on: per-iteration phase
  accounting, `telemetry_summary()` one-line JSON.
* ``trace``   summary plus the span ring: every phase/span lands in the
  trace buffer for `dump_trace`.

See docs/Observability.md.
"""
from __future__ import annotations

import math
import os
import re
from typing import Dict, Optional, Tuple

from ..utils import log
from . import (aggregate, bundle, clock, counters, events, recorder,
               spans, timeline, watchdogs)
from .spans import span

__all__ = ["counters", "recorder", "spans", "span", "events", "watchdogs",
           "aggregate", "bundle", "clock", "timeline", "mode", "set_mode",
           "enabled", "resolve_mode", "configure", "dump_trace",
           "telemetry_summary", "phase_breakdown", "prometheus_text",
           "record_iteration", "reset", "xla_trace_active",
           "note_grow_dispatches", "STAGES", "stage_map",
           "table_copies_in_split_loop", "hist_plane_elems_per_row",
           "rank_pair_plane_elems"]

MODES = ("off", "summary", "trace")
_mode = "off"

# -- stages inside the tree program -----------------------------------------
# `jax.named_scope("lgbm.<stage>")` in models/device_learner.py and
# ops/split.py; tests/test_trace_spans.py holds the scopes in the code
# and this tuple to each other. Scopes are metadata: they change neither
# the compiled computation nor the persistent compile cache's key.
STAGES = ("gradients", "root_hist", "leaf_select", "go_left", "partition",
          "table_update", "child_hist", "split_scan", "split_epilogue",
          "score_update")

_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*\bop_name=\"([^\"]*)\"", re.M)
_STAGE_IN_OP_NAME = re.compile(r"lgbm\.(\w+)")
_RUNG_IN_OP_NAME = re.compile(r"\brung_(\d+)")


def stage_map(hlo_text: str) -> Dict[str, Tuple[str, Optional[int]]]:
    """{instruction name: (stage, rung or None)} of a compiled module's
    text (`jit(f).lower(...).compile().as_text()`): the instructions
    whose `op_name` runs through a `lgbm.<stage>` scope, under the
    innermost such scope, with the index of the enclosing `rung_<r>`
    scope (the compact core's window ladder) as the rung. A device
    trace names its "XLA Ops" events by the instruction's text, which
    starts with this name; that is the join. Instructions the compiler
    made itself (copies round a `while` or `conditional`) carry no
    `op_name` and are not in the map."""
    out = {}
    for name, op_name in _HLO_INSTRUCTION.findall(hlo_text):
        stages = _STAGE_IN_OP_NAME.findall(op_name)
        if stages:
            rung = _RUNG_IN_OP_NAME.findall(op_name)
            out[name] = (stages[-1], int(rung[-1]) if rung else None)
    return out


_HLO_CALLED = re.compile(
    r"(?:body|condition|calls|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
_U32_TABLE = re.compile(r"u32\[(\d+),(\d+)\]")


def _computations(hlo_text: str) -> Dict[str, list]:
    """{computation name: its instruction lines} of a module's text."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            cur = line.split("(")[0].split()[-1].lstrip("%")
            comps[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps


def _split_loops(comps: Dict[str, list]) -> list:
    """The `while` instructions whose `op_name` runs through no
    `lgbm.<stage>` scope: the split loop (the others are loops INSIDE a
    stage)."""
    return [line for lines in comps.values() for line in lines
            if re.search(r"\bwhile\(", line)
            and not re.search(r'op_name="[^"]*lgbm\.', line)]


def _reached_from_body(comps: Dict[str, list], loop: str) -> set:
    """Names of the computations a `while` instruction's body reaches."""
    todo = [re.search(r"body=%?([\w.\-]+)", loop).group(1)]
    reached = set()
    while todo:
        comp = todo.pop()
        if comp not in reached and comp in comps:
            reached.add(comp)
            for one, many in _HLO_CALLED.findall("\n".join(comps[comp])):
                todo.extend([one] if one else
                            [c.strip().lstrip("%") for c in many.split(",")])
    return reached


def table_copies_in_split_loop(hlo_text: str) -> Dict[str, int]:
    """{computation: table-shaped copies in it} over the computations a
    compiled module's split loop reaches; empty where the packed table
    is updated in place, which is what the gauge
    `table_copies_in_split_loop` (the sum) says. The table is the
    largest `u32[rows,words]` of the split loop's carry; a copy is an
    instruction `= u32[rows,words]{...} copy(`. The compiler puts one
    where a buffer crosses a `conditional`: a whole-table copy a split
    (PERF.md §6, PR 30). A module with no such loop reads empty."""
    comps = _computations(hlo_text)
    out: Dict[str, int] = {}
    for loop in _split_loops(comps):
        tables = _U32_TABLE.findall(loop.split(" while(")[0])
        if not tables:
            continue
        rows, words = max(tables, key=lambda t: int(t[0]) * int(t[1]))
        copy = re.compile(r"=\s*u32\[%s,%s\]\{[^}]*\}\s+copy\("
                          % (rows, words))
        for comp in _reached_from_body(comps, loop):
            count = sum(1 for line in comps[comp] if copy.search(line))
            if count:
                out[comp] = count
    return out


_HIST_STAGES = ("root_hist", "child_hist")
_FUSION_CALL = re.compile(r"\bfusion\(([^)]*)\).*\bcalls=%?([\w.\-]+)")
_PRODUCT = re.compile(r"\bconvolution\(%?([\w.\-]+),.*\bdim_labels=(\w+)_")
_ARRAY_DIMS = re.compile(r"\w+\[(\d+(?:,\d+)*)\]")


def _result_dims(lines: list) -> Dict[str, list]:
    """{instruction name: the dimensions of the one array it produces}
    over a computation's lines (an instruction producing a tuple is left
    out: no product reads a tuple)."""
    out = {}
    for line in lines:
        name, _, rest = line.strip().partition(" = ")
        found = _ARRAY_DIMS.match(rest)
        if found:
            name = name.replace("ROOT ", "").lstrip("%")
            out[name] = [int(d) for d in found.group(1).split(",")]
    return out


def hist_plane_elems_per_row(hlo_text: str) -> int:
    """The widest array a histogram product of a compiled module reads
    from memory, in elements a histogrammed row: over the fusions under
    a `lgbm.root_hist` / `lgbm.child_hist` scope that hold a
    `convolution` (the TPU compiler's form of the one-hot contraction),
    the rows are the length the convolution contracts, and over the
    fusion's operands with a dimension of that length the count is the
    operand's elements over the rows. What a fusion computes inside it
    is never stored, so this is the one-hot plane as it is written out
    and read back: F x 256 with the unfactored one-hot; with the
    factored one (ops/histogram.py) F x LO_BINS, the `lo` codes
    broadcast over their columns, and the codes' own F once the
    compiler builds the planes inside the product's fusion. 0 for a module with no such product (a CPU
    module's contraction is a `dot`) (PERF.md §6, PR 32)."""
    comps = _computations(hlo_text)
    widest = 0
    for lines in comps.values():
        dims_here = None
        for line in lines:
            found = _HLO_INSTRUCTION.match(line)
            call = found and _FUSION_CALL.search(line)
            if not call:
                continue
            stages = _STAGE_IN_OP_NAME.findall(found.group(2))
            body = comps.get(call.group(2), [])
            product = _PRODUCT.search("\n".join(body))
            if (not stages or stages[-1] not in _HIST_STAGES
                    or not product):
                continue
            lhs = _result_dims(body).get(product.group(1))
            if not lhs:
                continue
            rows = lhs[product.group(2).index("f")]
            if dims_here is None:
                dims_here = _result_dims(lines)
            for operand in call.group(1).split(","):
                dims = dims_here.get(operand.strip().lstrip("%"), [])
                if rows in dims:
                    widest = max(widest, math.prod(dims) // rows)
    return widest


_RANK_BUCKET_IN_OP_NAME = re.compile(r"\brank_bucket_(\d+)")


def rank_pair_plane_elems(hlo_text: str) -> int:
    """The widest pair plane a compiled module stores, in elements:
    over the instructions under `lgbm.gradients` whose `op_name` runs
    through a `rank_bucket_<L>` scope (objectives/objective.py) and
    that are not inside a fusion (what a fusion computes inside it is
    never stored), the largest result whose last two dimensions are
    both L. The objective's PAIR_SLICE_ELEMS bounds it whatever the
    table; 0 where the compiler builds every plane inside a fusion, and
    for a module with no such scope."""
    comps = _computations(hlo_text)
    fused = {call.group(2) for lines in comps.values() for line in lines
             for call in [_FUSION_CALL.search(line)] if call}
    widest = 0
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            found = _HLO_INSTRUCTION.match(line)
            bucket = found and _RANK_BUCKET_IN_OP_NAME.findall(
                found.group(2))
            if not bucket or "gradients" not in _STAGE_IN_OP_NAME.findall(
                    found.group(2)):
                continue
            rest = line.partition(" = ")[2]
            result = rest[:rest.index(")") + 1] if rest.startswith("(") \
                else rest.split(" ", 1)[0]
            for dims in _ARRAY_DIMS.findall(result):
                dims = [int(d) for d in dims.split(",")]
                if dims[-2:] == [int(bucket[-1])] * 2:
                    widest = max(widest, math.prod(dims))
    return widest


# -- XLA timeline (jax.profiler) under trace mode ---------------------------
# Opt-in via LGBM_TPU_XLA_TRACE=<dir>: entering trace mode starts a
# jax.profiler trace session writing the XLA device timeline next to the
# host spans; leaving trace mode (or dump_trace) stops it. With the env
# var unset — or any mode below trace — this is never consulted, so the
# off-mode byte path is unchanged.
_xla_trace = {"active": False, "dir": ""}


def _xla_trace_start() -> None:
    path = os.environ.get("LGBM_TPU_XLA_TRACE", "").strip()
    if not path or _xla_trace["active"]:
        return
    try:
        import jax
        jax.profiler.start_trace(path)
    except Exception as exc:          # profiler backend unavailable
        log.warning("LGBM_TPU_XLA_TRACE: profiler start failed: %s", exc)
        return
    _xla_trace["active"] = True
    _xla_trace["dir"] = path
    log.info("XLA profiler trace started (dir %s)", path)


def _xla_trace_stop() -> None:
    if not _xla_trace["active"]:
        return
    try:
        import jax
        jax.profiler.stop_trace()
        log.info("XLA profiler trace written to %s", _xla_trace["dir"])
    except Exception as exc:  # pragma: no cover - stop raced the runtime
        log.warning("LGBM_TPU_XLA_TRACE: profiler stop failed: %s", exc)
    _xla_trace["active"] = False


def xla_trace_active() -> bool:
    return _xla_trace["active"]


def mode() -> str:
    return _mode


def enabled() -> bool:
    return _mode != "off"


def set_mode(new_mode: str) -> str:
    """Switch the process-wide telemetry mode, flipping the layer gates.
    Lives entirely OUTSIDE compiled programs, so flipping it never
    invalidates a jit cache (the warm-jit A/B overhead tests rely on
    this, same as the non-finite sentry flag)."""
    global _mode
    new_mode = (new_mode or "off").strip().lower()
    if new_mode not in MODES:
        raise ValueError(
            f"telemetry mode must be one of {'/'.join(MODES)}, "
            f"got {new_mode!r}")
    _mode = new_mode
    active = new_mode != "off"
    recorder.enable(active)
    counters.set_active(active)
    events.enable(active)
    spans.enable(new_mode == "trace")
    if new_mode == "trace":
        _xla_trace_start()
    else:
        _xla_trace_stop()
    if active:
        counters.install_compile_listener()
    return _mode


def resolve_mode(param: str = "") -> str:
    """The ONE resolution point of the telemetry knobs: the
    LGBM_TPU_TELEMETRY env var when set, else the config param."""
    env = os.environ.get("LGBM_TPU_TELEMETRY", "").strip().lower()
    return env if env else (str(param or "off").strip().lower())


def configure(param: str = "", explicit: bool = False) -> str:
    """Apply a training config's `telemetry` param (GBDT init calls
    this). A default-off param does not stomp a mode set programmatically
    via `set_mode` unless the user passed it explicitly or the env var
    forces a value."""
    resolved = resolve_mode(param)
    if (explicit or resolved != "off"
            or os.environ.get("LGBM_TPU_TELEMETRY")):
        if resolved != _mode:
            set_mode(resolved)
    return _mode


def dump_trace(path: str) -> str:
    """Export the span ring as Chrome trace-event JSON; returns `path`.
    An active jax.profiler session (LGBM_TPU_XLA_TRACE) is stopped
    first so the XLA timeline is flushed next to the host spans."""
    _xla_trace_stop()
    return spans.dump_trace(path)


def note_grow_dispatches(dispatches: float, trees: float = 0.0) -> None:
    """Growth-program dispatch accounting (the O(leaves)->O(1) fused
    growth acceptance metric, ROADMAP item 5a): bump the raw
    `grow_dispatches` / `grow_trees` counters and refresh the derived
    `grow_dispatches_per_tree` gauge. Device learners hold the gauge at
    O(1) (one whole-tree program, <= 3 with replay bookkeeping); the
    serial host loop pays ~num_leaves per tree. Counted unconditionally
    (low frequency, forensic) like the collective-retry counters."""
    counters.incr("grow_dispatches", dispatches)
    if trees:
        counters.incr("grow_trees", trees)
        counters.set_gauge(
            "grow_dispatches_per_tree",
            counters.get("grow_dispatches")
            / max(counters.get("grow_trees"), 1.0))


def telemetry_summary() -> dict:
    """One JSON-able dict with everything: mode, counters/gauges (peak
    RSS included), compile-event aggregates, and the run's phase
    breakdown. tools/chaos_bench.py prints slices of this."""
    out = {"telemetry": _mode}
    out.update(counters.snapshot())
    out["phase_breakdown"] = recorder.phase_breakdown()
    return out


def phase_breakdown() -> dict:
    return recorder.phase_breakdown()


def prometheus_text(serving_snapshot=None, cache_info=None,
                    slo=None, drift=None) -> str:
    """Prometheus text for the serving `/metrics` endpoint: process
    counters + compile events + the serving stack's counters/latency
    histograms (per-version series labeled `{version="..."}`) +
    compiled-predictor cache gauges + SLO burn-rate gauges (fast/slow
    window p99, error rate, burning flags) + drift-monitor gauges +
    (on rank 0, once an aggregation tick landed) the fleet-merged
    counters and per-rank skew gauges."""
    extra_counters, latency, extra_gauges = {}, {}, {}
    if serving_snapshot:
        extra_counters.update(serving_snapshot.get("counters") or {})
        latency.update(serving_snapshot.get("latency") or {})
        for ver, vs in (serving_snapshot.get("versions") or {}).items():
            label = f'{{version="{ver}"}}'
            extra_counters[f"serve_version_requests{label}"] = \
                vs.get("requests", 0)
            extra_counters[f"serve_version_errors{label}"] = \
                vs.get("errors", 0)
            if vs.get("latency"):
                latency[f"serve_version_request{label}"] = vs["latency"]
    if cache_info:
        extra_gauges.update({f"predictor_cache_{k}": v
                             for k, v in cache_info.items()})
    if slo:
        extra_gauges["serve_slo_p99_ms"] = slo.get("slo_p99_ms", 0.0)
        extra_gauges["serve_slo_error_rate"] = \
            slo.get("slo_error_rate", 0.0)
        for win in ("fast", "slow"):
            ws = slo.get(win) or {}
            label = f'{{window="{win}"}}'
            extra_gauges[f"serve_slo_window_p99_ms{label}"] = \
                ws.get("p99_ms", 0.0)
            extra_gauges[f"serve_slo_window_error_rate{label}"] = \
                ws.get("error_rate", 0.0)
            extra_gauges[f"serve_slo_window_burning{label}"] = \
                1.0 if ws.get("burning") else 0.0
    if drift:
        extra_gauges["serve_drift_fires"] = drift.get("fires", 0)
        worst = max(drift.get("psi", {}).values(), default=0.0)
        extra_gauges["serve_drift_psi_worst"] = worst
        extra_gauges["serve_drift_psi_threshold"] = \
            drift.get("threshold", 0.0)
    fleet_counters, fleet_gauges = aggregate.prometheus_extras()
    extra_counters.update(fleet_counters)
    extra_gauges.update(fleet_gauges)
    return counters.prometheus_text(extra_counters or None, latency or None,
                                    extra_gauges or None)


def record_iteration(rec: dict) -> None:
    """Feed one assembled iteration record through the watchdogs and
    into the flight recorder (GBDT.train_one_iter owns the assembly).
    No-op while events are off."""
    if not events.enabled():
        return
    watchdogs.observe(rec)
    events.iteration_record(rec)


def reset() -> None:
    """Clear accumulated state (mode unchanged). Benches call this after
    warmup so breakdowns cover only the timed window."""
    recorder.reset()
    counters.reset()
    spans.clear()
    events.reset()
    watchdogs.reset()
    aggregate.reset()
    clock.reset()
    timeline.reset()
    bundle.reset()


try:
    set_mode(resolve_mode())
except ValueError as _exc:       # bad env value: warn, stay off
    log.warning("LGBM_TPU_TELEMETRY: %s", _exc)
