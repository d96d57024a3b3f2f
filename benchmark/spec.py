"""Finds, by the names in `BENCHMARK.json`, the files that belong to one
cell: its workload file, its configuration, its traffic mix, its table
generator, its plain reference and the readers of its per-layer metrics.
Data files are read from `root` (the checkout that holds
`BENCHMARK.json`); code is the harness's own."""
import importlib
import json
import os
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def load_cell(root, name):
    """Everything one cell is made of, as a dict."""
    root = Path(root)
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"workload {name!r} is not in BENCHMARK.json")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == entry["config"])
    workload = load_json(root / "benchmark" / "workloads" / f"{name}.json")
    config = load_json(root / conf_entry["file"])
    traffic = load_json(root / "benchmark" / "traffic"
                        / f"{entry['traffic']}.json")
    for key in ("config", "traffic", "chips"):
        if workload[key] != entry[key]:
            raise SystemExit(f"{name}: workload file and BENCHMARK.json "
                             f"disagree on {key}")

    def in_cell(metric):
        return name in metric.get("workloads", [name])

    return {
        "name": name, "chips": entry["chips"], "run_seconds":
        bench["run_seconds"], "workload": workload, "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in bench["per_layer"] if in_cell(m)]}


def apply_xla_flags(config):
    """Adds the configuration's `xla_flags` to XLA_FLAGS. XLA reads the
    variable when JAX first starts a backend, so this runs before the
    program is imported."""
    have = os.environ.get("XLA_FLAGS", "").split()
    for flag in config.get("xla_flags", []):
        if flag not in have:
            have.append(flag)
    if have:
        os.environ["XLA_FLAGS"] = " ".join(have)


def load_generator(name):
    return importlib.import_module(f"benchmark.datagen.{name}")


def load_reference(name):
    """The plain reference a configuration names under `reference`
    (`reference/<name>.py`: `Reference`, `Outputs`, `TREE_KEYS`)."""
    return importlib.import_module(f"benchmark.reference.{name}")


def load_layer_metric(name):
    """The reader module of one per-layer metric, found by its name
    (`.` in a metric's name is `__` in the file's)."""
    return importlib.import_module(
        "benchmark.layer_metrics." + name.replace(".", "__"))


def layer_metric_names(here=HERE):
    """The metrics that have a reader file under `here`/layer_metrics."""
    return sorted(p.stem.replace("__", ".")
                  for p in (Path(here) / "layer_metrics").glob("*.py")
                  if not p.stem.startswith("_"))
