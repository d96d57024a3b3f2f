"""A PR that adds appends, and edits no file that stands (PR 33;
`benchmark/README.md`). A copy of the benchmark's data files with a
made-up configuration, a cell on it and a per-layer metric that lists
that cell appended, and nothing else changed, passes every structural
check the real checkout passes: a check that pins a length, a last
place or the absence of a key fails here, in the PR that writes it."""
import json
import shutil

import pytest

import bench_rehearsal
from bench_rehearsal import ROOT, STRUCTURE

from benchmark import spec

READER = '''"""A made-up reader that finds nothing to read."""
LAYER = "tree program"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    return None
'''


@pytest.fixture(scope="module")
def appended_root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("appended")
    for sub in ("configs", "workloads", "traffic", "layer_metrics"):
        shutil.copytree(ROOT / "benchmark" / sub, tmp / "benchmark" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    bench = bench_rehearsal.load_bench(ROOT)
    conf = json.loads((ROOT / "benchmark/configs/higgs.json").read_text())
    conf.update(name="appended", reference="gbdt_reference")
    work = json.loads((ROOT / "benchmark/workloads/higgs-train.json")
                      .read_text())
    work["config"] = "appended"
    bench["configs"].append({
        "name": "appended", "source": conf["source"],
        "file": "benchmark/configs/appended.json",
        "reduced": conf["reduced"], "why": "made up"})
    bench["workloads"].append({
        "name": "appended-train", "config": "appended",
        "traffic": "train_window", "chips": 1, "why": "made up"})
    bench["per_layer"].append({
        "name": "appended.metric", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "tree program",
        "moves": "train_row_trees_per_s", "workloads": ["appended-train"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "benchmark/configs/appended.json").write_text(json.dumps(conf))
    (tmp / "benchmark/workloads/appended-train.json").write_text(
        json.dumps(work))
    (tmp / "benchmark/layer_metrics/appended__metric.py").write_text(READER)
    return tmp


@pytest.mark.parametrize("check", sorted(STRUCTURE))
def test_structure_holds_with_entries_appended(appended_root, check):
    STRUCTURE[check](appended_root)


def test_listed_metric_is_its_cells_alone(appended_root):
    def names(cell):
        return [m["name"] for m in
                spec.load_cell(appended_root, cell)["per_layer"]]

    unlisted = [m["name"] for m in
                bench_rehearsal.load_bench(appended_root)["per_layer"]
                if "workloads" not in m]
    assert names("appended-train") == unlisted + ["appended.metric"]
    assert "appended.metric" not in names("higgs-train")
    assert "appended.metric" not in names("criteo-train")
    # what the checkout's own cells report is what they report without
    # the appended entries
    for cell in ("higgs-train", "criteo-train"):
        assert names(cell) == [
            m["name"] for m in spec.load_cell(ROOT, cell)["per_layer"]]


@pytest.mark.parametrize("section, break_it", [
    ("configs", lambda b: b["configs"].insert(0, b["configs"].pop())),
    ("workloads", lambda b: b["workloads"].pop(0)),
    ("per_layer", lambda b: b["per_layer"].insert(3, b["per_layer"].pop())),
    ("per_layer", lambda b: b["per_layer"][0].update(
        workloads=["higgs-train"])),
])
def test_what_stands_is_still_pinned(appended_root, tmp_path, section,
                                     break_it):
    """The prefix check fails when an entry that stands moves, goes or
    takes a list."""
    bench = bench_rehearsal.load_bench(appended_root)
    break_it(bench)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(AssertionError):
        bench_rehearsal.check_what_stands_is_a_prefix(tmp_path)
