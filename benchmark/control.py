#!/usr/bin/env python3
"""The readings the limits of `correct` are set from, at a cell's own
size, for several seeds in one process (one compile):

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

For each seed it runs the program's own first steps (the same set-up as
a benchmark run), and reads every compared number for
  sound       the program as the configuration states it;
  control     the reference put in the program's place with gradients
              and hessians rounded to bfloat16, the nearest precision
              below the float32 the configuration states;
  half_rows   the reference in the program's place with the second half
              of the rows left out (sums, counts and means over the rest);
  frozen      the program's outputs with the last checked step returning
              its state unchanged (its score row is the one before);
  altered     the program's outputs with one leaf output, drawn from the
              seed, moved by ALTER_BY of the median leaf output;
  half_features  the program itself with `feature_fraction=0.5`, a
              departure from the stated "no feature sampling": every
              split is searched over half the columns.
The benchmark's own runs never run this. One JSON line per seed.
"""
import argparse
import copy
import gc
import json
from pathlib import Path
import sys
import time

import numpy as np

CODE_ROOT = Path(__file__).resolve().parent.parent
ALTER_BY = 0.1


def plant_frozen(outputs):
    bad = copy.copy(outputs)
    bad.scores = list(outputs.scores)
    bad.scores[-1] = bad.scores[-2] if len(bad.scores) > 1 \
        else np.zeros_like(bad.scores[0])
    return bad


def plant_altered(outputs, seed):
    r = np.random.default_rng([int(seed), 0xA17E])
    bad = copy.copy(outputs)
    bad.trees = [dict(t) for t in outputs.trees]
    tree = bad.trees[int(r.integers(1, len(bad.trees)))
                     if len(bad.trees) > 1 else 0]
    n = int(tree["num_leaves"])
    values = np.array(tree["leaf_value"], dtype=np.float64)
    values[int(r.integers(n))] += ALTER_BY * float(
        np.median(np.abs(values[:n])))
    tree["leaf_value"] = values
    return bad


def variants(reference, outputs, seed):
    """{variant: readings} for one seed's first steps."""
    half = np.zeros(reference.n, bool)
    half[:reference.n // 2] = True
    return {
        "sound": reference.follow(outputs),
        "control": reference.follow(
            reference.emulate(outputs.trees, grad_dtype="bfloat16")),
        "half_rows": reference.follow(
            reference.emulate(outputs.trees, rows_used=half)),
        "frozen": reference.follow(plant_frozen(outputs)),
        "altered": reference.follow(plant_altered(outputs, seed)),
    }


def main(argv=None, root=None, allow_cpu=False):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    if str(CODE_ROOT) not in sys.path:
        sys.path.insert(0, str(CODE_ROOT))
    from benchmark import drive, spec
    from benchmark.traffic import train
    cell = spec.load_cell(Path(root) if root else CODE_ROOT, args.workload)
    spec.apply_xla_flags(cell["config"])
    devices = drive.find_devices(cell["chips"], allow_cpu)
    for seed in (int(s) for s in args.seeds.split(",")):
        start = time.perf_counter()
        state = train.first_steps(cell, seed, {})
        train_set = state.pop("booster").train_set
        gc.collect()
        narrowed = train.boost(train_set, dict(
            state["params"], feature_fraction=0.5), state["steps"], {},
            state["ref"])
        del narrowed["booster"], train_set
        gc.collect()
        reference = train.build_reference(state, seed)
        out = variants(reference, state["outputs"], seed)
        out["half_features"] = reference.follow(narrowed["outputs"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "platform": devices[0].platform,
                          "seconds": time.perf_counter() - start, **out}),
              flush=True)
        del state, reference
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
