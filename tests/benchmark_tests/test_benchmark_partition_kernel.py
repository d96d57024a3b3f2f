"""`partition_kernel_row_share`: the share of partitioned rows whose tiles
go through the Pallas lane compaction, read from the program's counters
`partition_kernel_rows` / `partition_rows`. CPU, tiny size: counts only.
Off the TPU the scan partitions and the kernel counts nothing, so the
traced line below has the learner report a TPU-held table to see the
counter reach the line."""
from pathlib import Path

import bench_rehearsal
from bench_rehearsal import ROOT, tiny_root  # noqa: F401 (a fixture)

from benchmark import spec

NAME = "partition_kernel_row_share"


def test_reader_without_the_counters_reads_nothing(monkeypatch):
    """As on the parent commit, which has no such counter: None, never
    0, and the line leaves the metric out."""
    from lightgbm_tpu.telemetry import counters
    monkeypatch.setattr(counters, "get", lambda key, default=0: default)
    assert spec.load_layer_metric(NAME).read({}) is None


def test_reader_gives_the_share_of_the_counted_rows(monkeypatch):
    from lightgbm_tpu.telemetry import counters
    have = {"partition_rows": 88_000_000.0, "partition_kernel_rows": 22e6}
    monkeypatch.setattr(counters, "get",
                        lambda key, default=0: have.get(key, default))
    assert spec.load_layer_metric(NAME).read({}) == 25.0
    # rows counted and none through the kernel (off the TPU): nothing,
    # since the harness prints no metric at 0
    have["partition_kernel_rows"] = 0.0
    assert spec.load_layer_metric(NAME).read({}) is None


def check_partition_kernel_entry_is_every_training_cells(root):
    """Held by name, with no `workloads` list: every cell that reports
    `train_row_trees_per_s` reports it, those that later PRs add too."""
    bench = bench_rehearsal.load_bench(root)
    declared = [m["name"] for m in bench["per_layer"]]
    assert sorted(declared) == spec.layer_metric_names(
        Path(root) / "benchmark")
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    mod = bench_rehearsal.reader_module(root, NAME)
    assert entry == {"name": NAME, "unit": mod.UNIT, "better": "higher",
                     "source": mod.SOURCE, "layer": mod.LAYER,
                     "moves": mod.MOVES}
    assert (mod.UNIT, mod.LAYER, mod.SOURCE, mod.MOVES) == (
        "%", "tree program", "program_counter", "train_row_trees_per_s")


def test_partition_kernel_entry_is_every_training_cells():
    check_partition_kernel_entry_is_every_training_cells(ROOT)


def test_traced_line_of_a_tpu_held_table_holds_the_share(
        tiny_root, monkeypatch):  # noqa: F811
    """The compact core with its table reported on the TPU: every split's
    tile is one the kernel takes at the tiny cell's width, so the share
    is 100 and the counter is the partitioned rows."""
    from lightgbm_tpu.models import device_learner as dl
    from lightgbm_tpu.telemetry import counters
    monkeypatch.setenv("LGBM_TPU_STRATEGY", "compact")
    monkeypatch.setattr(dl.DeviceTreeLearner, "_table_platform",
                        lambda self: "tpu")
    counters.reset()
    line = bench_rehearsal.run_cell(tiny_root, "tiny-train", 2**31 + 41,
                                    seconds=1, trace=1)
    assert line["correct"] is True
    assert line["metrics"][NAME] == {"value": 100.0, "unit": "%"}
    assert counters.get("partition_kernel_rows") \
        == counters.get("partition_rows") > 0
