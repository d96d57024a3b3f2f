"""`table_bytes_per_row` and `missing_split_share` (PR 33): the packed
table's bytes a row, from the program's gauge, and the share of splits
on a feature with a missing type, from its counters `splits` /
`splits_on_missing_feature`. The rehearsals that hold them in a traced
line are `test_benchmark_criteo.py`'s (84 B and the share; the metric's
list rewritten to the tiny cell) and `test_benchmark_tiled_partition.py`'s
(44 B, and no share: the tiny `higgs` cell is in no list). CPU: counts
only."""
import pytest

import bench_rehearsal
from bench_rehearsal import ROOT

from benchmark import spec

READERS = ("table_bytes_per_row", "missing_split_share")


@pytest.mark.parametrize("name", READERS)
def test_reader_without_the_counters_reads_nothing(monkeypatch, name):
    """As on a program that feeds no such counter: None, never 0, and
    the line leaves the metric out."""
    from lightgbm_tpu.telemetry import counters
    monkeypatch.setattr(counters, "get", lambda key, default=0: default)
    assert spec.load_layer_metric(name).read({}) is None


def test_readers_give_the_gauge_and_the_share(monkeypatch):
    from lightgbm_tpu.telemetry import counters
    have = {"table_bytes_per_row": 84.0, "splits": 762.0,
            "splits_on_missing_feature": 254.0}
    monkeypatch.setattr(counters, "get",
                        lambda key, default=0: have.get(key, default))
    assert spec.load_layer_metric("table_bytes_per_row").read({}) == 84.0
    assert spec.load_layer_metric("missing_split_share").read({}) \
        == 100 * 254 / 762
    # splits counted and none on a feature with a missing type (a table
    # with no missing value): nothing, not 0
    have["splits_on_missing_feature"] = 0.0
    assert spec.load_layer_metric("missing_split_share").read({}) is None


def check_entries_follow_what_stood_and_one_lists_its_cell(root):
    bench = bench_rehearsal.load_bench(root)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("tiled_partition_row_share") \
        < names.index("table_bytes_per_row") \
        < names.index("missing_split_share")
    entry = {m["name"]: m for m in bench["per_layer"]}
    assert entry["table_bytes_per_row"] == {
        "name": "table_bytes_per_row", "unit": "B", "better": "lower",
        "source": "program_counter", "layer": "tree program",
        "moves": "train_row_trees_per_s"}
    assert entry["missing_split_share"] == {
        "name": "missing_split_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "tree program",
        "moves": "train_row_trees_per_s", "workloads": ["criteo-train"]}


def test_entries_follow_what_stood_and_one_lists_its_cell():
    check_entries_follow_what_stood_and_one_lists_its_cell(ROOT)
