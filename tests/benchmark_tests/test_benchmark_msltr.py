"""`msltr` and its cell `msltr-train` (PR 34): the table's generator, the
cell's files, the three per-layer readers that came with it, and a
rehearsal of the cell on the CPU at a tiny size: `correct` true, and
false by at least one reading under the control and under each planted
fault of `lambdarank_reference`. CPU, tiny sizes: counts and arithmetic
only."""
import json

import numpy as np
import pytest

import bench_rehearsal
from bench_rehearsal import ROOT

from benchmark import control, spec
from benchmark.datagen import msltr_like
from benchmark.reference import lambdarank_reference
from benchmark.traffic import train

CONF = json.loads((ROOT / "benchmark/configs/msltr.json").read_text())
BENCH = bench_rehearsal.load_bench(ROOT)
WORK = json.loads((ROOT / "benchmark/workloads/msltr-train.json")
                  .read_text())
GEN = CONF["generator_params"]
F = CONF["features"]
SEED = 2**31 + 3401
READERS = ("rank_pair_fill_share", "rank_pair_positions_per_row",
           "objective_init_s")


@pytest.mark.parametrize("check", sorted(bench_rehearsal.STRUCTURE))
def test_structure_holds_with_the_new_entries(check):
    bench_rehearsal.STRUCTURE[check](ROOT)


def check_cell_resolves_and_states_its_deployment(root):
    """What `BENCHMARK.json` and `spec.load_cell` say of `msltr` and
    `msltr-train` under `root`, found by name: `test_benchmark_appends.py`
    runs every `check_*(root)` of this file on a copy with entries
    appended, so nothing here may lean on a place or a length."""
    bench = bench_rehearsal.load_bench(root)
    cell = spec.load_cell(root, "msltr-train")
    conf = cell["config"]
    gen = conf["generator_params"]
    entry = next(c for c in bench["configs"] if c["name"] == "msltr")
    assert cell["chips"] == 1 and cell["traffic"]["kind"] == "train"
    assert conf["reduced"] == entry["reduced"] == ["rows"]
    assert conf["source"] == entry["source"] and len(conf["source"]) < 200
    assert "MS LTR" in conf["source"] and "Experiments.rst" in conf["source"]
    pub = conf["published"]
    # every width and parameter is the source's; the rows are raised
    assert conf["features"] == pub["features"] == 137
    for key in ("objective", "num_leaves", "learning_rate", "max_bin",
                "min_data_in_leaf", "min_sum_hessian_in_leaf"):
        assert conf["params"][key] == pub[key], key
    assert set(conf["params"]) == {
        "objective", "num_leaves", "learning_rate", "max_bin",
        "min_data_in_leaf", "min_sum_hessian_in_leaf", "metric",
        "verbosity"}
    assert conf["rows"] == gen["table_rows"] == 3_771_125 > pub["rows"]
    assert gen["queries"] == 31_531 and gen["longest"] == 1_251
    assert conf["reference"] == "lambdarank_reference"
    for key in ("deployment", "reduced_why", "assumed", "guarantees",
                "xla_flags_why", "bins_seed", "bins_rows"):
        assert conf[key], key
    assert any("not here" in line for line in conf["assumed"])
    assert any("all pairs" in line for line in conf["guarantees"])
    assert set(cell["workload"]["limits"]) == {
        "leaf_count_mismatch", "leaf_value_gap", "ndcg_gap",
        "update_norm_gap", "split_gain_shortfall", "steps_missing",
        "compiles_in_window", "nonfinite_score"}
    names = [m["name"] for m in cell["per_layer"]]
    assert set(READERS) <= set(names) and "missing_split_share" not in names
    for other in ("higgs-train", "criteo-train"):
        theirs = [m["name"] for m in spec.load_cell(root, other)["per_layer"]]
        assert not set(READERS) & set(theirs)


def test_cell_resolves_and_states_its_deployment():
    check_cell_resolves_and_states_its_deployment(ROOT)


def test_reference_imports_nothing_of_the_program():
    src = (ROOT / "benchmark/reference/lambdarank_reference.py").read_text()
    assert "lightgbm_tpu" not in src.split('"""', 2)[2]


# -- the table -----------------------------------------------------------
def test_query_lengths_are_the_stated_law():
    lengths = msltr_like.query_lengths(GEN)
    assert len(lengths) == 31_531 and lengths.sum() == CONF["rows"]
    assert lengths.min() == 1 and lengths.max() == 1_251
    assert abs(lengths.mean() - 119.6) < 0.01
    assert 1.4 < np.mean(lengths ** 2.0) / lengths.mean() ** 2 < 1.55
    assert np.sum(lengths == 1) > 50 and np.sum(lengths > 1024) >= 1
    assert np.array_equal(lengths, msltr_like.query_lengths(GEN))


@pytest.fixture(scope="module")
def table():
    return msltr_like.generate(SEED, CONF["bins_rows"], F, GEN)


def query_keys(x, y, group):
    """One integer a query, blind to the queries' order."""
    bits = np.ascontiguousarray(x).view(np.uint32).astype(np.uint64)
    weights = np.arange(1, x.shape[1] + 1, dtype=np.uint64) * 2654435761
    rows = (bits * weights).sum(axis=1) + y.astype(np.uint64)
    starts = np.concatenate(([0], np.cumsum(group)[:-1]))
    return np.sort(np.add.reduceat(rows, starts) + group.astype(np.uint64))


def test_group_sums_to_rows_in_both_calls(table):
    x, y, fields = table
    assert x.shape == (CONF["bins_rows"], F) and x.dtype == np.float32
    assert fields["group"].sum() == CONF["bins_rows"] == len(y)
    small = msltr_like.generate(SEED, 5_000, F, GEN)
    assert small[2]["group"].sum() == 5_000 == len(small[1])
    # whole queries of the table's list, the last one cut to fit
    lengths = msltr_like.query_lengths(GEN)
    held = len(fields["group"])
    assert sorted(fields["group"])[1:] == sorted(
        np.sort(lengths[:held - 1]).tolist()
        + [fields["group"].sum() - lengths[:held - 1].sum()])[1:]


def test_every_seed_shuffles_whole_queries_of_one_table(table):
    x, y, fields = table
    x2, y2, fields2 = msltr_like.generate(SEED + 1, CONF["bins_rows"], F,
                                          GEN)
    assert not np.array_equal(fields["group"][:50], fields2["group"][:50])
    assert sorted(fields["group"]) == sorted(fields2["group"])
    assert np.array_equal(query_keys(x, y, fields["group"]),
                          query_keys(x2, y2, fields2["group"]))
    again = msltr_like.generate(SEED, CONF["bins_rows"], F, GEN)
    assert np.array_equal(again[0], x) and np.array_equal(again[1], y)


def test_labels_and_columns_are_as_assumed(table):
    x, y, fields = table
    assert not np.isnan(x).any()
    shares = np.bincount(y.astype(np.int64), minlength=5) / len(y)
    assert np.abs(shares - GEN["label_shares"]).max() < 0.01
    kinds = len(msltr_like.KINDS)
    assert kinds * len(msltr_like.FIELDS) + len(msltr_like.PAGE) == F
    for f, share in enumerate(GEN["field_empty_share"]):
        cols = x[:, f * kinds:(f + 1) * kinds]
        law = [k[1] for k in msltr_like.KINDS]
        body = cols[:, [j for j, name in enumerate(law) if name != "idf"]]
        # an empty field is 0 in all its columns but the query's idf
        assert abs((body == 0).all(axis=1).mean() - share) < 0.01
        counts = cols[:, [j for j, name in enumerate(law)
                          if name == "count"]]
        assert (counts == np.rint(counts)).all() and counts.min() == 0
        idf = cols[:, law.index("idf")]
        starts = np.concatenate(([0], np.cumsum(fields["group"])[:-1]))
        assert np.array_equal(idf, np.repeat(idf[starts], fields["group"]))
    # the label is learnable from the columns, and not trivially
    signal = np.corrcoef(x[:, kinds * 4 + 21], y)[0, 1]    # whole-doc BM25
    assert 0.1 < signal < 0.6
    g = fields["group"]
    starts = np.concatenate(([0], np.cumsum(g)[:-1]))
    alike = np.maximum.reduceat(y, starts) == np.minimum.reduceat(y, starts)
    assert np.sum(alike & (g > 1)) >= 1 and np.sum(g == 1) >= 1


# -- the readers ---------------------------------------------------------
@pytest.mark.parametrize("name", READERS)
def test_reader_without_the_counters_reads_nothing(monkeypatch, name):
    from lightgbm_tpu.telemetry import counters
    monkeypatch.setattr(counters, "get", lambda key, default=0: default)
    assert spec.load_layer_metric(name).read({"window": {"rows": 5}}) is None
    assert spec.load_layer_metric(name).read({}) is None


def test_readers_give_the_shares_and_the_seconds(monkeypatch):
    from lightgbm_tpu.telemetry import counters
    have = {"rank_pair_positions_real": 300.0,
            "rank_pair_positions_evaluated": 800.0,
            "setup_objective_init_seconds": 1.5}
    monkeypatch.setattr(counters, "get",
                        lambda key, default=0: have.get(key, default))
    ctx = {"window": {"rows": 40}}
    assert spec.load_layer_metric("rank_pair_fill_share").read(ctx) == 37.5
    assert spec.load_layer_metric("rank_pair_positions_per_row").read(ctx) \
        == 20.0
    assert spec.load_layer_metric("objective_init_s").read(ctx) == 1.5


# -- a rehearsal of the cell ---------------------------------------------
TINY = "tiny-msltr-train"


@pytest.fixture(scope="module")
def tiny_msltr_root(tmp_path_factory):
    """A temporary checkout's data files: `msltr` cut to 20,000 rows in
    170 queries of 1 to 600 documents, one cell on it, with the real
    traffic mix, limits, generator, reference and metric lists.
    `min_sum_hessian_in_leaf=100` would stop a tree of 20,000 rows at
    its root, so the tiny cell takes the program's default."""
    tmp = tmp_path_factory.mktemp("tiny-msltr")
    conf = dict(CONF, name="tiny-msltr", rows=20_000, bins_rows=5_000)
    conf["params"] = dict(CONF["params"], num_leaves=15,
                          min_sum_hessian_in_leaf=1e-3)
    conf["generator_params"] = dict(GEN, queries=170, table_rows=20_000,
                                    longest=600)
    work = dict(WORK, config="tiny-msltr")
    bench = dict(BENCH)
    bench["per_layer"] = [
        dict(m, workloads=[TINY]) if "msltr-train" in m.get("workloads", [])
        else m for m in BENCH["per_layer"] if "criteo-train"
        not in m.get("workloads", [])]
    bench["configs"] = [{"name": "tiny-msltr", "source": conf["source"],
                         "file": "benchmark/configs/tiny-msltr.json",
                         "reduced": ["rows"], "why": "tiny rehearsal"}]
    bench["workloads"] = [{"name": TINY, "config": "tiny-msltr",
                           "traffic": "train_window", "chips": 1,
                           "why": "tiny rehearsal"}]
    for sub in ("configs", "workloads", "traffic"):
        (tmp / "benchmark" / sub).mkdir(parents=True)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "benchmark/configs/tiny-msltr.json").write_text(json.dumps(conf))
    (tmp / f"benchmark/workloads/{TINY}.json").write_text(json.dumps(work))
    (tmp / "benchmark/traffic/train_window.json").write_text(
        (ROOT / "benchmark/traffic/train_window.json").read_text())
    return tmp


def failing(checks):
    return {k for k, c in checks.items() if c["value"] > c["limit"]}


def test_rehearsal_reads_correct_and_reports_the_new_metrics(
        tiny_msltr_root):
    from lightgbm_tpu.telemetry import counters
    counters.reset()
    line = bench_rehearsal.run_cell(tiny_msltr_root, TINY, SEED, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert failing(line["checks"]) == set()
    assert line["checks"]["leaf_count_mismatch"]["value"] == 0
    assert counters.get("rank_queries") == 170
    assert counters.get("rank_buckets") >= 4
    fill = line["metrics"]["rank_pair_fill_share"]
    assert fill["unit"] == "%" and 20 < fill["value"] < 100
    assert line["metrics"]["rank_pair_positions_per_row"]["value"] \
        == counters.get("rank_pair_positions_evaluated") / 20_000
    assert line["metrics"]["objective_init_s"]["value"] > 0
    assert line["metrics"]["grow_dispatches_per_tree"]["value"] == 1.0


@pytest.fixture(scope="module")
def followed(tiny_msltr_root):
    """The tiny cell's first steps and its reference, for the control
    and the planted faults."""
    cell = spec.load_cell(tiny_msltr_root, TINY)
    state = train.first_steps(cell, SEED, {})
    del state["booster"]
    reference, sound = train.check_first_steps(state, SEED)
    return reference, state["outputs"], sound


def over(readings):
    return {k for k, v in readings.items()
            if not v <= WORK["limits"][k]}


def test_sound_steps_pass_and_the_table_bites(followed):
    reference, outputs, sound = followed
    assert over(sound) == set()
    # tied scores inside a query from the second step on
    starts = reference.starts
    s = outputs.scores[0]
    tied = [len(np.unique(s[a:a + c])) < c
            for a, c in zip(starts, reference.counts) if c > 1]
    assert np.mean(tied) > 0.5


def test_control_fails_by_one_limit_and_not_by_each(followed):
    reference, outputs, _ = followed
    readings = control.variants(reference, outputs, SEED)
    assert over(readings["sound"]) == set()
    assert over(readings["control"]) \
        and "leaf_count_mismatch" not in over(readings["control"])
    assert "leaf_count_mismatch" in over(readings["half_rows"])
    assert "update_norm_gap" in over(readings["frozen"])
    assert "leaf_value_gap" in over(readings["altered"])


def test_each_step_is_held_to_the_row_reported_before_it(followed):
    """`follow` takes a step's gradients at the program's own row of the
    step before: a first row that is off fails the step that made it
    (`update_norm_gap`), and a second tree that is right FOR THAT ROW
    reads no leaf off, near-ties and all."""
    reference, outputs, _ = followed
    noise = np.random.default_rng(5).normal(0.0, 0.05, reference.n)
    row0 = (outputs.scores[0] + noise).astype(np.float32)
    g, h = reference.gradients(row0.astype(np.float64))
    leaf_of, stats, _ = reference._leaf_stats(outputs.trees[1], g, h,
                                              None, False)
    second = dict(outputs.trees[1],
                  leaf_value=reference._leaf_outputs(stats))
    row1 = (row0 + second["leaf_value"][leaf_of]).astype(np.float32)
    got = reference.follow(lambdarank_reference.Outputs(
        [outputs.trees[0], second], [row0, row1]))
    assert got["update_norm_gap"] > WORK["limits"]["update_norm_gap"]
    assert got["leaf_value_gap"] < 1e-5 and got["leaf_count_mismatch"] == 0


def test_ndcg_gap_sums_the_queries_by_size_and_not_by_sign(followed):
    """A query whose NDCG@10 rose and one whose fell do not cancel."""
    reference, outputs, _ = followed
    last = outputs.scores[-1].astype(np.float64)
    mine = reference.ndcg(last)
    # every other query's order reversed, the rest ranked by their labels
    moved = np.where(reference.qid % 2 == 0, -last, reference.y)
    theirs = reference.ndcg(moved)
    assert mine.shape == (len(reference.counts),)
    assert (theirs > mine).any() and (theirs < mine).any()
    bad = control.copy.copy(outputs)
    bad.scores = outputs.scores[:-1] + [moved.astype(np.float32)]
    got = reference.follow(bad)["ndcg_gap"]
    assert got == pytest.approx(
        np.mean(np.abs(theirs - mine)) / np.mean(mine), rel=1e-4)
    assert got > 1.01 * abs(theirs.mean() - mine.mean()) / mine.mean()


@pytest.mark.parametrize("fault", lambdarank_reference.FAULTS)
def test_each_planted_fault_fails_a_reading(followed, fault):
    reference, outputs, _ = followed
    bad = reference.follow(reference.emulate(outputs.trees, fault=fault))
    assert over(bad), fault
    assert bad["leaf_count_mismatch"] == 0      # wrong in the sums only
