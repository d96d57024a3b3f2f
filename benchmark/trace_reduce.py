"""From the profiler's `.xplane.pb` to the numbers the per-layer metrics
read. Read with `jax.profiler.ProfileData` and nothing else.

The traced window is K whole iterations: from the start of one tree
program on the device to the start of the K-th after it, so each
iteration counts its program and the gap that follows it. A tree program
is an event of the device's "XLA Modules" line whose name contains
MODULE_HINT (the jitted function of `make_fused_step`; XLA names the
module `jit_step_impl`). Busy time is the union of the "XLA Ops" events
inside the window (of the module events where a device has no ops line).
"""
from collections import defaultdict
from pathlib import Path
import re

MODULE_HINT = "step_impl"
DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:"
ATTRIBUTED_GAPS = 64      # the longest idle gaps are named by host span
LAYOUT = re.compile(r"\{[^{}]*\}")
NAME_CHARS = 160


def newest_xplane(trace_dir):
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_planes(path):
    """{plane name: {line name: [(name, start_ns, end_ns), ...]}}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    planes = {}
    for plane in data.planes:
        lines = defaultdict(list)
        for line in plane.lines:
            for ev in line.events:
                lines[line.name].append(
                    (ev.name, float(ev.start_ns),
                     float(ev.start_ns) + float(ev.duration_ns)))
        planes[plane.name] = dict(lines)
    return planes


def union(intervals, lo, hi):
    """Merged, clipped, sorted intervals inside [lo, hi]."""
    merged = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def complement(merged, lo, hi):
    gaps, at = [], lo
    for a, b in merged:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def short_name(op):
    """XLA's text of an operation without its layouts, cut to NAME_CHARS:
    the full text of a `while` runs to thousands of characters."""
    return LAYOUT.sub("", op)[:NAME_CHARS]


def self_times(ops, lo, hi):
    """{short name: ns} inside [lo, hi], each event counted without the
    events nested in it: the ops line holds a `while` or `conditional`
    and, inside its span, the operations of its body."""
    out = defaultdict(float)
    stack = []                        # [name, end, self_ns]
    for name, a, b in sorted(((n, max(a, lo), min(b, hi))
                              for n, a, b in ops),
                             key=lambda e: (e[1], -e[2])):
        if b <= a:
            continue
        while stack and stack[-1][1] <= a:
            done = stack.pop()
            out[short_name(done[0])] += done[2]
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    for done in stack:
        out[short_name(done[0])] += done[2]
    return out


def _host_span_at(host_events, t):
    """Name of the shortest host event that covers time t."""
    best = None
    for name, a, b in host_events:
        if a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "no_host_span"


def reduce_planes(planes, iterations, chips=1, device_prefix=DEVICE_PREFIX,
                  module_hint=MODULE_HINT):
    """Numbers of the traced window, or None where the trace holds no
    device plane with enough tree programs."""
    devices = sorted(n for n in planes if n.startswith(device_prefix)
                     and MODULES_LINE in planes[n])[:chips]
    if not devices:
        return None
    host_events = [ev for n, lines in planes.items()
                   if n.startswith(HOST_PREFIX)
                   for evs in lines.values() for ev in evs
                   if ev[2] > ev[1]]
    per_dev = []
    for dev in devices:
        lines = planes[dev]
        programs = sorted((ev for ev in lines[MODULES_LINE]
                           if module_hint in ev[0]), key=lambda e: e[1])
        if len(programs) < iterations + 1:
            return None
        lo, hi = programs[0][1], programs[iterations][1]
        ops = lines.get(OPS_LINE) or lines[MODULES_LINE]
        busy = union(((a, b) for _, a, b in ops), lo, hi)
        idle = complement(busy, lo, hi)
        gaps = []
        for k in range(iterations):
            a, b = programs[k][2], programs[k + 1][1]
            gaps.append(sum(y - x for x, y in complement(
                union(busy, a, b), a, b)) if b > a else 0.0)
        by_op = self_times(ops, lo, hi)
        by_span = defaultdict(float)
        idle.sort(key=lambda ab: ab[0] - ab[1])
        for a, b in idle[:ATTRIBUTED_GAPS]:
            by_span[_host_span_at(host_events, (a + b) / 2)] += b - a
        if idle[ATTRIBUTED_GAPS:]:
            by_span["shorter_gaps_unattributed"] = sum(
                b - a for a, b in idle[ATTRIBUTED_GAPS:])
        per_dev.append({
            "window_ns": hi - lo,
            "busy_ns": sum(b - a for a, b in busy),
            "program_ns": sum(p[2] - p[1] for p in programs[:iterations])
            / iterations,
            "gap_ns": sum(gaps) / iterations,
            "by_op": by_op, "by_span": by_span})
    n = len(per_dev)

    def mean(key):
        return sum(d[key] for d in per_dev) / n

    def top(key):
        total = defaultdict(float)
        for d in per_dev:
            for name, ns in d[key].items():
                total[name] += ns / n
        return [[name, ns / 1e9] for name, ns in
                sorted(total.items(), key=lambda kv: -kv[1])[:10]]

    return {"window_s": mean("window_ns") / 1e9,
            "busy_s": mean("busy_ns") / 1e9,
            "tree_program_ms": mean("program_ns") / 1e6,
            "iter_gap_ms": mean("gap_ns") / 1e6,
            "iterations": iterations,
            "device_ops": top("by_op"), "idle_gaps": top("by_span")}


def reduce_file(path, iterations, chips=1, **kw):
    return reduce_planes(read_planes(path), iterations, chips, **kw)
