"""`tiled_partition_row_share` (PR 27): the share of partitioned rows
that go through the tiled partition, read from the program's counters
`partition_tiled_rows` / `partition_rows`. CPU, tiny size: counts only;
the partition is the chip's on every platform (the scan, tiled over
`SCATTER_TILE_ROWS`; PR 31), and the tiny cell's windows are under one
tile until the test shrinks it."""
from pathlib import Path

import bench_rehearsal
from bench_rehearsal import ROOT, tiny_root  # noqa: F401 (a fixture)

from benchmark import spec

NAME = "tiled_partition_row_share"


def test_reader_without_the_counters_reads_nothing(monkeypatch):
    """As on the parent commit: None, never 0, and the line leaves the
    metric out."""
    from lightgbm_tpu.telemetry import counters
    monkeypatch.setattr(counters, "get", lambda key, default=0: default)
    assert spec.load_layer_metric(NAME).read({}) is None


def test_reader_gives_the_share_of_the_counted_rows(monkeypatch):
    from lightgbm_tpu.telemetry import counters
    have = {"partition_rows": 88_000_000.0, "partition_tiled_rows": 66e6}
    monkeypatch.setattr(counters, "get",
                        lambda key, default=0: have.get(key, default))
    assert spec.load_layer_metric(NAME).read({}) == 75.0
    # rows counted and none tiled (the chunk core, windows of one tile):
    # nothing, since the harness prints no metric at 0
    have["partition_tiled_rows"] = 0.0
    assert spec.load_layer_metric(NAME).read({}) is None


def check_per_layer_list_and_reader_files_match_one_to_one(root):
    bench = bench_rehearsal.load_bench(root)
    declared = [m["name"] for m in bench["per_layer"]]
    assert sorted(declared) == spec.layer_metric_names(
        Path(root) / "benchmark")
    # held by name: where it stands is `check_what_stands_is_a_prefix`'s,
    # and later entries come after it
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    mod = bench_rehearsal.reader_module(root, NAME)
    assert entry == {"name": NAME, "unit": mod.UNIT, "better": "higher",
                     "source": mod.SOURCE, "layer": mod.LAYER,
                     "moves": mod.MOVES}
    assert (mod.LAYER, mod.SOURCE, mod.MOVES) == (
        "tree program", "program_counter", "train_row_trees_per_s")


def test_per_layer_list_and_reader_files_match_one_to_one():
    check_per_layer_list_and_reader_files_match_one_to_one(ROOT)


def test_traced_line_of_a_tiling_learner_holds_the_share(
        tiny_root, monkeypatch):  # noqa: F811
    """The masked learner the tiny cell gets by default moves no rows and
    counts nothing; the compact core, with the tile forced
    under the upper rungs of its ladder (4096, 8192, 16384, 20000),
    counts every split's parent rows and tiles those over 4096."""
    from lightgbm_tpu.models import device_learner as dl
    from lightgbm_tpu.telemetry import counters
    monkeypatch.setenv("LGBM_TPU_STRATEGY", "compact")
    monkeypatch.setattr(dl, "SCATTER_TILE_ROWS", 4096)
    counters.reset()
    # something for `missing_split_share` to read, were it asked: it lists
    # its cells and the tiny cell is in no list, so it is not
    counters.incr("splits_on_missing_feature", 5.0)
    line = bench_rehearsal.run_cell(tiny_root, "tiny-train", 2**31 + 27,
                                    seconds=1, trace=1)
    assert line["correct"] is True
    share = line["metrics"][NAME]
    assert share["unit"] == "%" and 0 < share["value"] < 100
    assert share["value"] == (100 * counters.get("partition_tiled_rows")
                              / counters.get("partition_rows"))
    # the packed row of 28 one-byte codes: 7 code words, 3 gradient
    # words, the row id
    assert line["metrics"]["table_bytes_per_row"] == {"value": 44.0,
                                                      "unit": "B"}
    assert counters.get("splits") > 0
    assert "missing_split_share" not in line["metrics"]
