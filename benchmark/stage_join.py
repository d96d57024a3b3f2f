"""The tree program's device time by stage: a device trace joined to the
program's map of the executable that ran.

A device trace names each "XLA Ops" event by the text of the instruction
it ran (`%fusion.878 = (u32[65536,11]...`) and carries no scope. The map
(`GBDT.fused_stage_map()`, built by `lightgbm_tpu.telemetry.stage_map`)
gives each instruction name its `lgbm.<stage>` scope. `stage_times`
joins the two over the tree program's own intervals, with
`trace_reduce`'s planes, module events and self times, so its
`tree_program_ms` is `trace_reduce`'s.

`python tools/stage_table.py <trace dir> <map.json>` prints the table
of a trace an operator took with their own profiler session;
docs/Observability.md has the recipe.
"""
from collections import defaultdict
import re

from benchmark.trace_reduce import (DEVICE_PREFIX, MODULE_HINT, MODULES_LINE,
                                    OPS_LINE, self_times)

TOP_UNSTAGED = 10
INSTRUCTION_NAME = re.compile(r"^%?([\w.\-]+)\s*=")


def instruction_name(op):
    """The instruction an "XLA Ops" event ran: the name its text starts
    with (the whole text where it is no instruction's)."""
    found = INSTRUCTION_NAME.match(op)
    return found.group(1) if found else op


def stage_times(planes, stage_of, chips=1, count=None):
    """Milliseconds a tree program by stage, averaged over the first
    `count` programs (all where None) of each of the first `chips`
    devices: {"trees", "tree_program_ms", "stage_ms": {stage: ms} over
    every stage the map names, "unstaged_ms", "idle_ms",
    "unstaged_tree_pct", "unstaged_ops": the longest unstaged
    operations, [[short name, ms]]}.

    `planes` is `trace_reduce.read_planes`'s; `stage_of` is
    {instruction name: (stage, rung)}. Only "XLA Ops" events inside a
    program's interval count, by self time; an event whose instruction
    the map leaves out is unstaged. `idle_ms` is the program's time in
    which no operation runs, so the stages, unstaged and idle sum to
    `tree_program_ms`. None where the map is empty (a trace without a
    map, or a map from a cache entry older than the scopes) or a device
    holds fewer such programs than asked for."""
    if not stage_of:
        return None
    devices = sorted(n for n in planes if n.startswith(DEVICE_PREFIX)
                     and MODULES_LINE in planes[n])[:chips]
    per_dev = []
    for dev in devices:
        lines = planes[dev]
        programs = sorted((ev for ev in lines[MODULES_LINE]
                           if MODULE_HINT in ev[0]), key=lambda e: e[1])
        programs = programs[:count]
        if not programs or (count is not None and len(programs) < count):
            return None
        ops = lines.get(OPS_LINE, [])
        stage_ns = defaultdict(float)
        unstaged = defaultdict(float)
        total_ns = busy_ns = 0.0
        for _, lo, hi in programs:
            total_ns += hi - lo
            for name, ns in self_times(ops, lo, hi).items():
                busy_ns += ns
                stage = stage_of.get(instruction_name(name))
                if stage:
                    stage_ns[stage[0]] += ns
                else:
                    unstaged[name] += ns
        n = len(programs)
        per_dev.append({
            "tree_ns": total_ns / n, "idle_ns": (total_ns - busy_ns) / n,
            "stage_ns": {k: v / n for k, v in stage_ns.items()},
            "unstaged": {k: v / n for k, v in unstaged.items()},
            "trees": n})
    if not per_dev:
        return None
    d = len(per_dev)

    def mean_ms(get):
        return sum(get(p) for p in per_dev) / d / 1e6

    tree_ms = mean_ms(lambda p: p["tree_ns"])
    unstaged_ms = mean_ms(lambda p: sum(p["unstaged"].values()))
    ops = defaultdict(float)
    for p in per_dev:
        for name, ns in p["unstaged"].items():
            ops[name] += ns / d / 1e6
    stages = sorted({stage for stage, _ in stage_of.values()})
    return {
        "trees": per_dev[0]["trees"],
        "tree_program_ms": tree_ms,
        "stage_ms": {s: mean_ms(lambda p, s=s: p["stage_ns"].get(s, 0.0))
                     for s in stages},
        "unstaged_ms": unstaged_ms,
        "idle_ms": mean_ms(lambda p: p["idle_ns"]),
        "unstaged_tree_pct":
            100.0 * unstaged_ms / tree_ms if tree_ms else None,
        "unstaged_ops": [[name, ms] for name, ms in sorted(
            ops.items(), key=lambda kv: -kv[1])[:TOP_UNSTAGED]]}
