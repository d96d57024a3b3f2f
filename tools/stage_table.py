#!/usr/bin/env python
"""The tree program's device time by stage, from a profiler trace and
the program's map of the executable that ran.

    python tools/stage_table.py <trace dir | .xplane.pb> <map.json>
        [--trees K] [--chips N] [--json]
    python tools/stage_table.py --cell <workload> --seed <n>
        [--trees K] [--out DIR] [--json]

The first form reads a trace an operator took. The map is
`GBDT.fused_stage_map()` written as JSON in the process that ran the
traced iterations, after them (a probe answered from JAX's caches,
counted in no compile counter):

    json.dump(booster._gbdt.fused_stage_map(), open("map.json", "w"))

The second sets a cell of the benchmark up as its run does
(`benchmark/traffic/train.py::first_steps`), traces K + 1 iterations
from an idle device, then takes the map and joins; with `--out` it
writes `map.json` and `stages.json` (the join, the probe's seconds and
the compile events it counted) there. It needs the chip: a CPU trace
holds no device plane, so it reads no stage there.

Prints a row a stage (ms a tree and its share of the tree program),
then unstaged (instructions the map has no stage for), the program's
idle time and its longest unstaged operations; `--json` prints
`benchmark.stage_join.stage_times`'s dict instead. The programs read
are the first `--trees` `jit_step_impl` module events (all by default
in the first form, 2 in the second) on each of the first `--chips`
devices. docs/Observability.md has the recipe.
"""
import argparse
import json
from pathlib import Path
import shutil
import sys
import time

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def xplane_of(path):
    from benchmark import trace_reduce
    path = Path(path)
    return path if path.is_file() else trace_reduce.newest_xplane(path)


def table(out):
    tree = out["tree_program_ms"]
    rows = ["| stage | ms a tree | % of the tree |", "|---|---|---|"]
    named = sorted(out["stage_ms"].items(), key=lambda kv: -kv[1])
    for name, ms in named + [("unstaged", out["unstaged_ms"]),
                             ("idle", out["idle_ms"])]:
        rows.append(f"| {name} | {ms:.3f} | {100 * ms / tree:.2f} |")
    rows.append(f"| tree program ({out['trees']} trees) | {tree:.3f} | 100 |")
    rows.append("")
    rows.append("Longest unstaged operations (ms a tree):")
    rows.extend(f"- {ms:.3f} `{name}`" for name, ms in out["unstaged_ops"])
    return "\n".join(rows)


def run_cell(root, workload, seed, trees, out_dir=None):
    """One cell's set-up, `trees` + 1 traced iterations, the probe and
    the join: the join's dict (None where no stage can be read) and
    what the run cost."""
    from benchmark import spec, stage_join, trace_reduce
    cell = spec.load_cell(root, workload)
    spec.apply_xla_flags(cell["config"])
    import jax
    from benchmark.traffic import train
    from lightgbm_tpu.telemetry import counters

    counters.install_compile_listener()
    phases = {}
    state = train.first_steps(cell, seed, phases)
    booster = state["booster"]
    gbdt = booster._gbdt
    trace_dir = Path(root) / ".stage_trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.block_until_ready(gbdt.score_updater.score)
    tick = time.perf_counter()
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    for _ in range(trees + 1):
        with jax.profiler.TraceAnnotation("bench_update"):
            booster.update()
    jax.block_until_ready(gbdt.score_updater.score)
    jax.profiler.stop_trace()
    traced_s = time.perf_counter() - tick
    events, seconds = len(counters.compile_events()), \
        counters.compile_seconds()
    tick = time.perf_counter()
    stage_of = gbdt.fused_stage_map()
    probe_s = time.perf_counter() - tick
    planes = trace_reduce.read_planes(trace_reduce.newest_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    out = stage_join.stage_times(planes, stage_of, chips=cell["chips"],
                                 count=trees)
    reduced = trace_reduce.reduce_planes(planes, trees, cell["chips"])
    cost = {"cell": workload, "seed": seed, "phases": phases,
            "traced_s": traced_s, "probe_s": probe_s,
            "probe_events_counted": len(counters.compile_events()) - events,
            "probe_seconds_counted": counters.compile_seconds() != seconds,
            "map_entries": len(stage_of),
            "map_stages": sorted({s for s, _ in stage_of.values()}),
            "trace_reduce_tree_program_ms":
                reduced["tree_program_ms"] if reduced else None}
    if out_dir is not None:
        out_dir = Path(out_dir)
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / "map.json").write_text(json.dumps(stage_of))
        (out_dir / "stages.json").write_text(
            json.dumps({"stages": out, **cost}, indent=1))
    return out, cost


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?")
    ap.add_argument("map", nargs="?")
    ap.add_argument("--cell")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--out")
    ap.add_argument("--root", default=str(ROOT))
    ap.add_argument("--trees", type=int, default=None)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    if args.cell:
        if args.seed is None or args.trace:
            ap.error("--cell takes --seed and no trace or map")
        out, cost = run_cell(Path(args.root), args.cell, args.seed,
                             args.trees or 2, args.out)
        print(json.dumps(cost), file=sys.stderr)
    else:
        if not (args.trace and args.map):
            ap.error("give a trace and a map, or --cell")
        from benchmark import stage_join, trace_reduce
        with open(args.map) as fh:
            stage_of = {name: tuple(v) for name, v in json.load(fh).items()}
        out = stage_join.stage_times(
            trace_reduce.read_planes(xplane_of(args.trace)), stage_of,
            chips=args.chips, count=args.trees)
    if out is None:
        print("no stage can be read: an empty map, no device plane, or "
              "fewer tree programs on a device of the trace than asked "
              "for", file=sys.stderr)
        return 1
    print(json.dumps(out) if args.json else table(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
