#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children. Exits non-zero and prints no result line
without a TPU (or with fewer chips than the cell asks for), and in a
directory that does not hold the program. Everything that belongs to one
cell, configuration, traffic mix or per-layer metric is a file of its
own under `benchmark/`, found by the name in `BENCHMARK.json`; see
`benchmark/README.md`.
"""
import time
T0 = time.perf_counter()

import argparse    # noqa: E402
import json        # noqa: E402
from pathlib import Path    # noqa: E402
import sys         # noqa: E402

CODE_ROOT = Path(__file__).resolve().parent.parent


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def judge(readings, limits):
    """[(name, value, limit, ok)] for every number compared; a number
    without a stated limit is an error, not a pass."""
    rows = []
    for name, value in readings.items():
        if name not in limits:
            raise SystemExit(f"no limit stated for compared number {name}")
        limit = limits[name]
        rows.append((name, value, limit, bool(value <= limit)))
    return rows


def main(argv=None, root=None, allow_cpu=False):
    args = parse_args(argv)
    if str(CODE_ROOT) not in sys.path:
        sys.path.insert(0, str(CODE_ROOT))
    from benchmark import drive, spec
    root = Path(root) if root else CODE_ROOT
    cell = spec.load_cell(root, args.workload)
    spec.apply_xla_flags(cell["config"])
    res = drive.run(cell, args.seed, args.seconds, args.trace, T0, root,
                    allow_cpu)
    ctx = res["ctx"]
    if args.trace:
        metrics = {}
        for m in cell["per_layer"]:
            value = spec.load_layer_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = res["end_to_end"]
        missing = [m["name"] for m in cell["end_to_end"]
                   if m["name"] not in values]
        if missing:
            raise SystemExit(f"traffic {cell['traffic']['kind']!r} does not "
                             f"measure {missing}")
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    checks = judge(res["readings"], cell["workload"]["limits"])
    correct = all(ok for *_, ok in checks) and res["failed"] == 0
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics,
            "device": ctx["device"]}
    if args.trace and ctx["trace"]:
        line["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                             "idle_gaps": ctx["trace"]["idle_gaps"]}
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit, _ in checks}
    phases = dict(res["phases"], window_s=ctx["window"]["seconds"],
                  iterations=ctx["window"]["iterations"])
    if ctx["work"]:
        phases.update(ctx["work"])
    print(json.dumps({"phases": phases}), flush=True)
    for ev in res["compile_events_in_window"]:
        print(f"compile inside the window: {ev}", file=sys.stderr)
    for name, value, limit, ok in checks:
        print(f"check {name}: {value!r} limit {limit!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
