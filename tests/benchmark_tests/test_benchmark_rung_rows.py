"""`rung_row_inflation` (PR 35): rows the step loops inside the tree
program's rungs ran over the rows the splits needed, from the program's
counters `rung_rows_run` / `rung_rows_needed`. CPU: counts only."""
import bench_rehearsal
from bench_rehearsal import ROOT

from benchmark import spec

NAME = "rung_row_inflation"


def test_reader_without_the_counters_reads_nothing(monkeypatch):
    """As on a program from before the counters, or a core that has no
    rungs (the masked core): None, and the line leaves the metric out."""
    from lightgbm_tpu.telemetry import counters
    monkeypatch.setattr(counters, "get", lambda key, default=0: default)
    assert spec.load_layer_metric(NAME).read({}) is None


def test_reader_gives_rows_run_over_rows_needed(monkeypatch):
    from lightgbm_tpu.telemetry import counters
    have = {"rung_rows_run": 142.0e6, "rung_rows_needed": 135.4e6}
    monkeypatch.setattr(counters, "get",
                        lambda key, default=0: have.get(key, default))
    assert spec.load_layer_metric(NAME).read({}) == 142.0e6 / 135.4e6


def check_entry_follows_what_stood_and_is_every_training_cells(root):
    """The entry by name, after what stood before it, and with no list of
    cells (PR 36): every training cell reports it, those of later PRs
    too, as the compact core feeds it whatever the table."""
    bench = bench_rehearsal.load_bench(root)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("objective_init_s") < names.index(NAME)
    mod = bench_rehearsal.reader_module(root, NAME)
    assert next(m for m in bench["per_layer"] if m["name"] == NAME) == {
        "name": NAME, "unit": mod.UNIT, "better": "lower",
        "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES}
    assert (mod.UNIT, mod.LAYER, mod.SOURCE, mod.MOVES) == (
        "x", "tree program", "program_counter", "train_row_trees_per_s")
    for cell in ("higgs-train", "criteo-train", "msltr-train"):
        assert NAME in [m["name"] for m in
                        spec.load_cell(root, cell)["per_layer"]]


def test_entry_follows_what_stood_and_is_every_training_cells():
    check_entry_follows_what_stood_and_is_every_training_cells(ROOT)


def test_compact_core_feeds_the_counters_and_the_masked_core_does_not():
    """The program's side of the reader: a compact tree counts what its
    rungs ran and needed (over 1 where a window is wider than its leaf),
    a masked tree has no rungs and counts nothing."""
    import numpy as np
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.device_learner import DeviceTreeLearner
    from lightgbm_tpu.telemetry import counters
    r = np.random.RandomState(35)
    x = r.randn(3000, 5).astype(np.float32)
    y = (x[:, 0] + 0.3 * r.randn(3000) > 0).astype(np.float64)
    g = (r.rand(3000) - 0.5).astype(np.float32)
    h = (0.1 + r.rand(3000)).astype(np.float32)
    cfg = Config({"objective": "binary", "num_leaves": 7, "max_bin": 31,
                  "verbosity": -1})
    read = spec.load_layer_metric(NAME).read
    for strategy, feeds in (("masked", False), ("compact", True)):
        counters.reset()
        DeviceTreeLearner(cfg, Dataset(x, config=cfg, label=y),
                          strategy=strategy).train(g, h)
        assert (read({}) is not None) == feeds, strategy
    assert read({}) > 1.0
