"""`criteo-share` and its cell `criteo-train` (PR 29): the table's
generator, the cell's files, and a rehearsal of the cell on the CPU at a
tiny size, with the program's gauge and counters that came with it and
the two per-layer metrics that read them (PR 33). CPU, tiny sizes: counts
and arithmetic only. The float32 cancellation the cell guards
needs sums of ~1e6 and is `tests/test_split_small_child.py`'s to catch.
"""
import json

import numpy as np
import pytest

import bench_rehearsal
from bench_rehearsal import ROOT

from benchmark import spec
from benchmark.datagen import criteo_like

CONF = json.loads((ROOT / "benchmark/configs/criteo-share.json").read_text())
BENCH = bench_rehearsal.load_bench(ROOT)
GEN = CONF["generator_params"]
F = CONF["features"]
ROWS = 300_000
SEED = 2**31 + 2029


@pytest.fixture(scope="module")
def table():
    return criteo_like.generate(SEED, ROWS, F, GEN)


def row_keys(x, y):
    """One integer a row, blind to the rows' order, NaN included."""
    bits = np.ascontiguousarray(x).view(np.uint32).astype(np.uint64)
    weights = np.arange(1, x.shape[1] + 1, dtype=np.uint64) * 2654435761
    return np.sort((bits * weights).sum(axis=1) + y.astype(np.uint64))


def test_generator_is_deterministic(table):
    x, y = criteo_like.generate(SEED, ROWS, F, GEN)
    assert np.array_equal(x, table[0], equal_nan=True)
    assert np.array_equal(y, table[1])
    assert x.dtype == np.float32 and x.shape == (ROWS, F)


def test_every_seed_is_a_shuffle_of_one_table(table):
    x, y = criteo_like.generate(SEED + 1, ROWS, F, GEN)
    assert not np.array_equal(x[:1000], table[0][:1000], equal_nan=True)
    assert np.array_equal(row_keys(x, y), row_keys(*table))


@pytest.mark.parametrize("group", ["int", "ctr", "count", "dense"])
def test_stated_shares_hold(table, group):
    x, y = table
    cols, mu, sigma, nan_share, spike_share = criteo_like.column_laws(F, GEN)
    part = x[:, cols[group]]
    nan = np.isnan(part).mean(axis=0)
    assert np.abs(nan - nan_share[cols[group]]).max() < 0.01
    if group == "int":
        assert nan.min() == 0.0 and 0.7 < nan.max() < 0.8
        assert np.nanmin(part) == 0.0
        assert (np.nan_to_num(part) == np.rint(np.nan_to_num(part))).all()
        # heavy tails: the largest value is far above the median
        assert (np.nanmax(part, axis=0)
                > 20 * np.nanmedian(part, axis=0) + 20).all()
    if group in ("int", "count"):
        # the spike is a floor: rounding sends small draws to 0 as well
        zero = (part == 0).mean(axis=0)
        assert (zero > spike_share[cols[group]]
                * (1 - nan_share[cols[group]]) - 0.01).all()
    if group == "count":
        # heavy clickers: beyond tail_z of the narrowest count columns
        # nearly every row is a click, against a few per cent overall
        tails = criteo_like.tail_columns(GEN, cols)
        beyond = np.exp(mu[tails] + sigma[tails] * GEN["tail_z"]) + 1.0
        in_tail = (x[:, tails] > beyond).any(axis=1)
        assert 5 <= in_tail.sum() <= 60
        assert y[in_tail].mean() > 0.6
    if group == "ctr":
        at_prior = (part == np.float32(GEN["ctr_prior"])).mean(axis=0)
        assert np.abs(at_prior - spike_share[cols[group]]).max() < 0.01
        assert part.min() >= 0.0 and part.max() <= 1.0
        assert (np.median(part, axis=0) < 0.2).all()      # skewed left
    if group == "dense":
        assert abs(float(part[:, 0].mean())) < 0.02
        assert 0.0 <= part[:, 1].min() and part[:, 1].max() < 1.0
    assert 0.01 < float(y.mean()) < 0.08          # a few per cent click


def check_cell_resolves_and_states_its_deployment(root):
    """What `BENCHMARK.json` and `spec.load_cell` say of `criteo-share`
    and `criteo-train` under `root`, found by name
    (`test_benchmark_appends.py` runs this on a copy with entries
    appended too)."""
    bench = bench_rehearsal.load_bench(root)
    cell = spec.load_cell(root, "criteo-train")
    conf, entry = cell["config"], next(
        c for c in bench["configs"] if c["name"] == "criteo-share")
    gen = conf["generator_params"]
    assert cell["chips"] == 1 and cell["traffic"]["kind"] == "train"
    assert conf["reduced"] == entry["reduced"] == ["rows"]
    assert conf["source"] == entry["source"] and len(conf["source"]) <= 200
    # every width is the source's; only the rows are cut, in the steps
    # the issue gives
    pub = conf["published"]
    assert conf["features"] == pub["features"] == 67
    for key in ("num_leaves", "learning_rate", "max_bin", "objective"):
        assert conf["params"][key] == pub[key]
    assert set(conf["params"]) == {"objective", "num_leaves", "max_bin",
                                   "learning_rate", "metric", "verbosity"}
    assert conf["rows"] in range(8_000_000, 10_000_001, 500_000)
    assert conf["rows"] < pub["rows"]
    assert sum(gen[f"{g}_cols"] for g in criteo_like.GROUPS) == 67
    for key in ("deployment", "reduced_why", "assumed", "guarantees",
                "xla_flags_why", "bins_seed", "bins_rows"):
        assert conf[key], key
    assert any("real" in line and "not here" in line
               for line in conf["assumed"])
    assert set(cell["workload"]["limits"]) == {
        "leaf_count_mismatch", "leaf_value_gap", "loss_gap",
        "update_norm_gap", "split_gain_shortfall", "steps_missing",
        "compiles_in_window", "nonfinite_score"}
    # the cell reports every per-layer metric that lists no cells or
    # lists it, `missing_split_share` (its table has NaN columns) among
    # them
    bench_rehearsal.check_workloads_lists_are_sound(root)
    assert "missing_split_share" in [m["name"] for m in cell["per_layer"]]


def test_cell_resolves_and_states_its_deployment():
    check_cell_resolves_and_states_its_deployment(ROOT)


def test_new_entries_are_appended_and_nothing_moved():
    """What the benchmark had stands where it stood, as a prefix: the
    configurations, the cells and the per-layer names of
    `bench_rehearsal.STANDS` in their order. Whatever follows is free,
    and is held to what the harness needs of it: files found by name,
    `reduced` and `source` the configuration file's, limits present.
    (Both are `STRUCTURE` checks, which the appended copy meets in
    `test_benchmark_appends.py`.)"""
    bench_rehearsal.check_what_stands_is_a_prefix(ROOT)
    bench_rehearsal.check_every_file_is_found_by_name(ROOT)


# -- a rehearsal of the cell ---------------------------------------------
TINY = "tiny-criteo-train"


@pytest.fixture(scope="module")
def tiny_criteo_root(tmp_path_factory):
    """A temporary checkout's data files: `criteo-share` cut to a tiny
    size and one cell on it, with the real traffic mix, limits, generator
    and metric lists."""
    tmp = tmp_path_factory.mktemp("tiny-criteo")
    conf = dict(CONF, name="tiny-criteo", rows=20_000, bins_rows=20_000)
    conf["params"] = dict(CONF["params"], num_leaves=15)
    work = json.loads((ROOT / "benchmark/workloads/criteo-train.json")
                      .read_text())
    work["config"] = "tiny-criteo"
    bench = dict(BENCH)
    # a metric listed for `criteo-train` is listed for its tiny cell
    bench["per_layer"] = [
        dict(m, workloads=[TINY]) if "criteo-train" in m.get("workloads", [])
        else m for m in BENCH["per_layer"]]
    bench["configs"] = [{"name": "tiny-criteo", "source": conf["source"],
                         "file": "benchmark/configs/tiny-criteo.json",
                         "reduced": ["rows"], "why": "tiny rehearsal"}]
    bench["workloads"] = [{"name": TINY, "config": "tiny-criteo",
                           "traffic": "train_window", "chips": 1,
                           "why": "tiny rehearsal"}]
    for sub in ("configs", "workloads", "traffic"):
        (tmp / "benchmark" / sub).mkdir(parents=True)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "benchmark/configs/tiny-criteo.json").write_text(
        json.dumps(conf))
    (tmp / f"benchmark/workloads/{TINY}.json").write_text(json.dumps(work))
    (tmp / "benchmark/traffic/train_window.json").write_text(
        (ROOT / "benchmark/traffic/train_window.json").read_text())
    return tmp


def run_tiny(root, trace=0):
    return bench_rehearsal.run_cell(root, TINY, SEED, trace=trace)


def failing(line):
    return {k for k, c in line["checks"].items() if c["value"] > c["limit"]}


def test_rehearsal_reads_correct_and_feeds_the_new_counters(
        tiny_criteo_root, monkeypatch):
    """The compact core packs a table (the masked core the tiny cell gets
    by default packs none): 17 code words + 3 gradient words + the row
    id; and the table's NaN columns take their share of the splits."""
    from lightgbm_tpu.telemetry import counters
    monkeypatch.setenv("LGBM_TPU_STRATEGY", "compact")
    counters.reset()
    line = run_tiny(tiny_criteo_root, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert failing(line) == set()
    assert line["checks"]["leaf_count_mismatch"]["value"] == 0
    assert counters.get("table_bytes_per_row") == 84.0
    assert 0 < counters.get("splits_on_missing_feature") \
        < counters.get("splits")
    # and the traced line holds the two metrics that read them
    assert line["metrics"]["table_bytes_per_row"] == {"value": 84.0,
                                                      "unit": "B"}
    assert line["metrics"]["missing_split_share"] == {
        "value": 100 * counters.get("splits_on_missing_feature")
        / counters.get("splits"), "unit": "%"}


def test_rehearsal_comes_out_false_under_a_planted_fault(
        tiny_criteo_root, monkeypatch):
    from lightgbm_tpu.models.tree import Tree
    real = Tree.apply_shrinkage
    # the core the rehearsal above has compiled already
    monkeypatch.setenv("LGBM_TPU_STRATEGY", "compact")

    def apply_shrinkage(self, rate):
        real(self, rate)
        # a tenth of the median leaf, as `control.py`'s `altered`
        self.leaf_value[1] += 0.1 * np.median(
            np.abs(self.leaf_value[:self.num_leaves]))

    monkeypatch.setattr(Tree, "apply_shrinkage", apply_shrinkage)
    line = run_tiny(tiny_criteo_root)
    assert line["correct"] is False
    assert failing(line) == {"leaf_value_gap"}
