"""What the benchmark's tests share: a temporary checkout with one tiny
cell. They run on the CPU at tiny sizes (`tests/conftest.py` holds JAX
to the CPU and keeps the compile cache off), so no time, rate or share
read here is a device number. This directory has no `conftest.py`: 27
tier-1 files do `from conftest import ...`, and a second module of that
name would shadow theirs."""
import contextlib
import importlib.util
import io
import json
from pathlib import Path
import re
import sys

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

TINY_ROWS = 20_000
TINY_LEAVES = 15

from benchmark import drive, spec  # noqa: E402

# -- the shape of `BENCHMARK.json` and of the files it names -------------
# Every check takes the root it reads, so that it is run on the checkout
# and on a copy with entries appended (`test_benchmark_appends.py`): a PR
# that adds appends, so a check may pin what stands as a prefix or by
# name, and never a length, a last place or the absence of a key
# (`benchmark/README.md`).
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# What is accepted up to PR 35, in its order. A later PR's entries follow
# these and are named nowhere here; only a `benchmark` PR extends the lists.
STANDS = {
    "configs": ["higgs", "criteo-share", "msltr"],
    "workloads": ["higgs-train", "criteo-train", "msltr-train"],
    "per_layer": [
        "device_idle_pct", "peak_hbm_gib", "train_iter_mfu", "iter_gap_ms",
        "tree_program_ms", "tree_program_roofline",
        "grow_dispatches_per_tree", "compile_s", "dataset_construct_s",
        "find_bin_s", "bin_data_s", "learner_build_s", "trace_lower_s",
        "backend_compile_s", "bundle_s", "tiled_partition_row_share",
        "table_bytes_per_row", "missing_split_share", "rank_pair_fill_share",
        "rank_pair_positions_per_row", "objective_init_s",
        "rung_row_inflation"]}
# The cells each standing metric lists; every name of STANDS["per_layer"]
# that is not here (the first sixteen among them) is every training
# cell's and keeps no list.
LISTED = {"missing_split_share": ["criteo-train"],
          "rank_pair_fill_share": ["msltr-train"],
          "rank_pair_positions_per_row": ["msltr-train"],
          "objective_init_s": ["msltr-train"]}


def load_bench(root):
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def reader_module(root, name):
    """One metric's reader, from its file under `root`."""
    stem = name.replace(".", "__")
    found = importlib.util.spec_from_file_location(
        f"_reader_{stem}",
        Path(root) / "benchmark/layer_metrics" / f"{stem}.py")
    mod = importlib.util.module_from_spec(found)
    found.loader.exec_module(mod)
    return mod


def check_names_units_and_key_sets(root):
    bench = load_bench(root)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    seen = set()
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[section]:
            assert NAME.match(entry["name"]), entry
            assert (section, entry["name"]) not in seen
            seen.add((section, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry
                assert entry["better"] in ("lower", "higher")
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e
    assert 1 <= bench["run_seconds"] <= 51


def check_every_file_is_found_by_name(root):
    """Every cell's files resolve, its configuration's entry says what
    its file says, its limits are there; reader files and `per_layer`
    entries match one to one and say the same."""
    bench = load_bench(root)
    for w in bench["workloads"]:
        cell = spec.load_cell(root, w["name"])
        conf = cell["config"]
        assert conf["name"] == w["config"]
        assert hasattr(drive.load_traffic(cell["traffic"]["kind"]), "run")
        if "generator" in conf:
            assert hasattr(spec.load_generator(conf["generator"]),
                           "generate")
        ref = spec.load_reference(conf.get("reference", "gbdt_reference"))
        assert all(hasattr(ref, k)
                   for k in ("Reference", "Outputs", "TREE_KEYS"))
        entry = next(c for c in bench["configs"] if c["name"] == w["config"])
        assert entry["reduced"] == conf["reduced"]
        assert entry["source"] == conf["source"]
        assert cell["workload"]["limits"]
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert spec.layer_metric_names(Path(root) / "benchmark") \
        == sorted(declared)
    for name, m in declared.items():
        mod = reader_module(root, name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"]), name


def check_what_stands_is_a_prefix(root):
    """What the benchmark holds stands where it stood, in its order, each
    cell on its configuration and each metric with the list of cells it
    had (or with none); whatever follows is free."""
    bench = load_bench(root)
    for section, names in STANDS.items():
        assert [e["name"] for e in bench[section]][:len(names)] == names
    # the i-th cell that stands is on the i-th configuration that stands
    for cell, conf in zip(bench["workloads"], STANDS["configs"]):
        assert cell == {"name": cell["name"], "config": conf,
                        "traffic": "train_window", "chips": 1,
                        "why": cell["why"]}
    for m in bench["per_layer"][:len(STANDS["per_layer"])]:
        assert m.get("workloads") == LISTED.get(m["name"]), m


def check_workloads_lists_are_sound(root):
    """A metric may list its cells. A list names cells that exist and
    that report the end-to-end metric it moves, and a cell reports
    exactly the metrics that have no list or list it."""
    bench = load_bench(root)
    cells = [w["name"] for w in bench["workloads"]]
    for section in ("end_to_end", "per_layer"):
        for m in bench[section]:
            assert set(m.get("workloads", cells)) <= set(cells), m
            assert m.get("workloads", cells), m
    for name in cells:
        cell = spec.load_cell(root, name)
        for section in ("end_to_end", "per_layer"):
            assert cell[section] == [
                m for m in bench[section]
                if name in m.get("workloads", cells)]
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"]
        assert all(m["moves"] in reported for m in cell["per_layer"])


STRUCTURE = {fn.__name__[len("check_"):]: fn for fn in (
    check_names_units_and_key_sets, check_every_file_is_found_by_name,
    check_what_stands_is_a_prefix, check_workloads_lists_are_sound)}


def write_tiny_root(tmp, rows=TINY_ROWS, leaves=TINY_LEAVES):
    """A temporary checkout's data files: the `higgs` configuration cut
    to a tiny size and one cell on it, with the real traffic mix, limits
    and metric lists."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((ROOT / "benchmark/configs/higgs.json").read_text())
    conf.update(name="tiny", rows=rows)
    conf["params"]["num_leaves"] = leaves
    work = json.loads((ROOT / "benchmark/workloads/higgs-train.json")
                      .read_text())
    work["config"] = "tiny"
    bench["configs"] = [{"name": "tiny", "source": conf["source"],
                         "file": "benchmark/configs/tiny.json",
                         "reduced": [], "why": "tiny rehearsal"}]
    bench["workloads"] = [{"name": "tiny-train", "config": "tiny",
                           "traffic": "train_window", "chips": 1,
                           "why": "tiny rehearsal"}]
    for sub in ("configs", "workloads", "traffic"):
        (tmp / "benchmark" / sub).mkdir(parents=True)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "benchmark/configs/tiny.json").write_text(json.dumps(conf))
    (tmp / "benchmark/workloads/tiny-train.json").write_text(
        json.dumps(work))
    (tmp / "benchmark/traffic/train_window.json").write_text(
        (ROOT / "benchmark/traffic/train_window.json").read_text())
    return tmp


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return write_tiny_root(tmp_path_factory.mktemp("tiny"))


def run_cell(root, workload, seed, seconds=0.5, trace=0):
    """One run of the harness on the CPU; the result line, parsed."""
    from benchmark import run
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, allow_cpu=True)
    assert rc == 0
    return json.loads([ln for ln in out.getvalue().splitlines()
                       if ln.startswith("{")][-1])
