"""`allstate` and its cell `allstate-train` (PR 37): the table's generator,
the cell's files, the three per-layer readers that came with it, the
column-sparse reference against `gbdt_reference`, and a rehearsal of the
cell on the CPU at a small size: `correct` true, and false by at least
one reading under the bfloat16 control, under each planted fault of
`control.py`, and under the program with EFB's FixHistogram dropped.
CPU, small sizes: counts and arithmetic only."""
import json

import numpy as np
import pytest
import scipy.sparse as sp

import bench_rehearsal
from bench_rehearsal import ROOT

from benchmark import control, spec
from benchmark.datagen import allstate_like
from benchmark.reference import gbdt_reference, sparse_gbdt_reference
from benchmark.traffic import train

CONF = json.loads((ROOT / "benchmark/configs/allstate.json").read_text())
BENCH = bench_rehearsal.load_bench(ROOT)
WORK = json.loads((ROOT / "benchmark/workloads/allstate-train.json")
                  .read_text())
GEN = CONF["generator_params"]
F = CONF["features"]
SEED = 2**31 + 3701
READERS = ("host_code_bytes_per_row", "hist_expansion_ratio",
           "bundled_feature_share")


@pytest.mark.parametrize("check", sorted(bench_rehearsal.STRUCTURE))
def test_structure_holds_with_the_new_entries(check):
    bench_rehearsal.STRUCTURE[check](ROOT)


def check_cell_resolves_and_states_its_deployment(root):
    """What `BENCHMARK.json` and `spec.load_cell` say of `allstate` and
    `allstate-train` under `root`, found by name (run on the appended
    copy too, so nothing here leans on a place or a length)."""
    bench = bench_rehearsal.load_bench(root)
    cell = spec.load_cell(root, "allstate-train")
    conf = cell["config"]
    entry = next(c for c in bench["configs"] if c["name"] == "allstate")
    assert cell["chips"] == 1 and cell["traffic"]["kind"] == "train"
    assert conf["reduced"] == entry["reduced"] and set(conf["reduced"]) \
        <= {"rows"}
    assert conf["source"] == entry["source"] and len(conf["source"]) < 200
    assert "Allstate" in conf["source"] \
        and "Experiments.rst" in conf["source"]
    pub = conf["published"]
    # every width and parameter is the source's
    assert conf["features"] == pub["features"] == 4_228
    if "rows" not in conf["reduced"]:
        assert conf["rows"] == pub["rows"] == 13_184_290
    for key in ("objective", "num_leaves", "learning_rate", "max_bin",
                "min_data_in_leaf", "min_sum_hessian_in_leaf"):
        assert conf["params"][key] == pub[key], key
    # EFB at the reference's defaults, stated
    assert (conf["params"]["enable_bundle"], conf["params"][
        "max_conflict_rate"], conf["params"]["sparse_threshold"]) \
        == (True, 0.0, 0.8)
    assert conf["reference"] == "sparse_gbdt_reference"
    for key in ("deployment", "assumed", "guarantees", "xla_flags_why",
                "bins_seed", "bins_rows"):
        assert conf[key], key
    assert any("not here" in line for line in conf["assumed"])
    assert set(cell["workload"]["limits"]) == {
        "leaf_count_mismatch", "leaf_value_gap", "loss_gap",
        "update_norm_gap", "split_gain_shortfall", "steps_missing",
        "compiles_in_window", "nonfinite_score"}
    names = [m["name"] for m in cell["per_layer"]]
    assert set(READERS) <= set(names)
    for other in ("higgs-train", "criteo-train", "msltr-train"):
        theirs = [m["name"] for m in spec.load_cell(root, other)["per_layer"]]
        assert not set(READERS) & set(theirs)


def test_cell_resolves_and_states_its_deployment():
    check_cell_resolves_and_states_its_deployment(ROOT)


def test_reference_imports_nothing_of_the_program():
    src = (ROOT / "benchmark/reference/sparse_gbdt_reference.py").read_text()
    assert "lightgbm_tpu" not in src.split('"""', 2)[2]


# -- the table -----------------------------------------------------------
ROWS = 50_000


@pytest.fixture(scope="module")
def table():
    return allstate_like.generate(SEED, ROWS, F, GEN)


def test_table_is_one_hot_sparse_as_assumed(table):
    x, y = table
    numeric, fields = allstate_like.layout(F, GEN)
    assert sp.isspmatrix_csr(x) and x.dtype == np.float32
    assert x.shape == (ROWS, F) and numeric == 12 and len(fields) == 19
    assert np.all(np.diff(x.indptr) == numeric + len(fields))
    cols = x.indices.reshape(ROWS, -1)
    vals = x.data.reshape(ROWS, -1)
    assert np.array_equal(cols[:, :numeric],
                          np.tile(np.arange(numeric), (ROWS, 1)))
    for i, (name, first, n) in enumerate(fields):
        assert (cols[:, numeric + i] >= first).all() \
            and (cols[:, numeric + i] < first + n).all(), name
    assert (vals[:, numeric:] == 1.0).all() and not np.isnan(vals).any()
    assert abs(y.mean() - GEN["positive_rate"]) < 0.002
    # NVVar columns sit at their constant for a share of the rows
    at_const = vals[:, numeric - 4:numeric] == np.float32(GEN["nv_constant"])
    assert abs(at_const.mean() - GEN["nv_constant_share"]) < 0.01


def test_vehicle_fields_meet_only_through_a_vehicle(table):
    """A vehicle's make, model, Cat fields, OrdCat and model year are the
    vehicle's own: a function of Blind_Submodel in every row."""
    x, _ = table
    numeric, fields = allstate_like.layout(F, GEN)
    cols = x.indices.reshape(ROWS, -1)[:, numeric:]
    names = [name for name, _, _ in fields]
    vehicle = cols[:, names.index(allstate_like.VEHICLE)]
    for i, name in enumerate(names):
        pairs = np.unique(np.stack([vehicle, cols[:, i]]), axis=1)
        one_each = len(np.unique(pairs[0])) == pairs.shape[1]
        assert one_each == (name not in ("Calendar_Year", "NVCat")), name


def test_every_seed_shuffles_the_same_rows(table):
    x, y = table
    x2, y2 = allstate_like.generate(SEED + 1, ROWS, F, GEN)

    def keys(a, b):
        rows = a.indices.reshape(ROWS, -1).astype(np.uint64)
        bits = a.data.reshape(ROWS, -1).view(np.uint32).astype(np.uint64)
        mix = (rows * 2654435761 + bits) * np.arange(
            1, rows.shape[1] + 1, dtype=np.uint64)
        return np.sort(mix.sum(axis=1) + b.astype(np.uint64))

    assert not np.array_equal(x.indices[:500], x2.indices[:500])
    assert np.array_equal(keys(x, y), keys(x2, y2))
    again = allstate_like.generate(SEED, ROWS, F, GEN)
    assert (again[0] != x).nnz == 0 and np.array_equal(again[1], y)


def test_generator_refuses_a_program_that_builds_the_plane(monkeypatch):
    """On a program without the nonzero path the cell stops at once: that
    program would fill a (rows, 4,228) byte plane first, 55.7 GB."""
    from lightgbm_tpu.io.dataset import Dataset

    def plane_first(self, csc, reference):
        """The parent's order: the (N, F) plane, then plan and encode."""
        self.binned = self._scatter_nonzeros(*self._bin_nonzeros(csc))
        self.columns = (reference.columns if reference is not None
                        else self._plan_bundles())
        self.bundled = self._encode_bundles() if self.columns else None

    assert allstate_like._program_bins_from_nonzeros()
    monkeypatch.setattr(Dataset, "_construct_sparse", plane_first)
    with pytest.raises(SystemExit):
        allstate_like.generate(SEED, 1_000, F, GEN)


def test_the_bins_rows_plan_every_column_with_no_conflict():
    """On the configuration's own bin rows every one of the 4,228 columns
    is used, and the bundles the plan finds exclusive there hold no
    conflict on rows it never saw."""
    import lightgbm_tpu as lgb
    bx, by = allstate_like.generate(CONF["bins_seed"], CONF["bins_rows"],
                                    F, GEN)
    ref = lgb.Dataset(bx, by, params=dict(CONF["params"])).construct()
    inner = ref._inner
    assert inner.num_features == F
    assert sum(c.is_bundle for c in inner.columns) >= 17
    x, y = allstate_like.generate(SEED, 150_000, F, GEN)
    csc = x.tocsc()
    for col in inner.columns:
        if col.is_bundle:
            away = np.zeros(x.shape[0], np.int32)
            for j in col.features:
                f = inner.used_features[j]
                away[csc.indices[csc.indptr[f]:csc.indptr[f + 1]]] += 1
            assert away.max() <= 1


# -- the readers ---------------------------------------------------------
@pytest.mark.parametrize("name", READERS)
def test_reader_without_the_gauge_reads_nothing(monkeypatch, name):
    from lightgbm_tpu.telemetry import counters
    monkeypatch.setattr(counters, "get", lambda key, default=0: default)
    assert spec.load_layer_metric(name).read({}) is None


def test_readers_give_the_gauges(monkeypatch):
    from lightgbm_tpu.telemetry import counters
    have = {"host_code_bytes_per_row": 65.0, "hist_expansion_ratio": 260.2,
            "bundled_feature_share": 98.9}
    monkeypatch.setattr(counters, "get",
                        lambda key, default=0: have.get(key, default))
    for name in READERS:
        assert spec.load_layer_metric(name).read({}) == have[name]


# -- the column-sparse reference -----------------------------------------
def test_sparse_reference_reads_what_gbdt_reference_reads():
    """The same three trees of the program, followed by `gbdt_reference`
    on the table as a plane and by the column-sparse reference on its
    nonzeros: the same readings, to rounding."""
    import jax
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(0)
    n = 6_000
    x = sp.random(n, 120, density=0.05, random_state=1,
                  dtype=np.float32).tolil()
    x[:, 0] = rng.standard_normal((n, 1)).astype(np.float32)
    x[3, 5] = np.nan
    x = x.tocsr()
    x.data[x.data > 0.5] = 1.0
    dense = np.asarray(x.todense(), np.float32)
    y = (dense[:, 0] + dense[:, 1] + rng.standard_normal(n) > 1) \
        .astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "learning_rate": 0.1, "min_data_in_leaf": 5, "metric": "none"}
    booster = lgb.Booster(params=params,
                          train_set=lgb.Dataset(x, y, params=params))
    scores = []
    for _ in range(3):
        booster.update()
        scores.append(np.asarray(jax.device_get(
            booster._gbdt.score_updater.score))[0].copy())
    trees = [train.tree_arrays(t, gbdt_reference.TREE_KEYS)
             for t in booster._gbdt.models[:3]]
    outputs = gbdt_reference.Outputs(trees, scores)
    for min_data in (5, 0):      # with counts in the grid, and without
        stated = dict(params, min_data_in_leaf=min_data)
        plane = gbdt_reference.Reference(dense, y, stated, 7).follow(
            outputs)
        cols = sparse_gbdt_reference.Reference(x, y, stated, 7).follow(
            outputs)
        assert plane.keys() == cols.keys()
        for key, value in plane.items():
            assert cols[key] == pytest.approx(value, rel=1e-7, abs=1e-12), \
                (min_data, key)


# -- a rehearsal of the cell ---------------------------------------------
TINY = "tiny-allstate-train"
TINY_F = 600


@pytest.fixture(scope="module")
def tiny_allstate_root(tmp_path_factory):
    """A temporary checkout's data files: `allstate` cut to 20,000 rows
    and 600 columns (the vehicle fields narrowed, every other field as it
    is; a floor of 1e-3 a vehicle so that the bin rows see each), one
    cell on it, with the real traffic mix, limits, generator, reference
    and metric lists. `min_sum_hessian_in_leaf=100` would stop a tree of
    20,000 rows at its root, so the tiny cell takes the program's
    default."""
    tmp = tmp_path_factory.mktemp("tiny-allstate")
    narrow = {"Blind_Make": 10, "Blind_Model": 100}
    fields = [[name, narrow.get(name, n)] for name, n in GEN["fields"]]
    rest = TINY_F - len(GEN["numeric"]) - sum(
        n for name, n in fields if name != allstate_like.VEHICLE)
    fields = [[name, rest if name == allstate_like.VEHICLE else n]
              for name, n in fields]
    conf = dict(CONF, name="tiny-allstate", rows=20_000, features=TINY_F,
                bins_rows=20_000, generator_params=dict(
                    GEN, fields=fields, vehicle_floor=1e-3))
    conf["params"] = dict(CONF["params"], num_leaves=15,
                          min_sum_hessian_in_leaf=1e-3)
    work = dict(WORK, config="tiny-allstate")
    bench = dict(BENCH)
    bench["per_layer"] = [
        dict(m, workloads=[TINY]) if "allstate-train" in m.get("workloads",
                                                               [])
        else m for m in BENCH["per_layer"] if not m.get("workloads")
        or "allstate-train" in m["workloads"]]
    bench["configs"] = [{"name": "tiny-allstate", "source": conf["source"],
                         "file": "benchmark/configs/tiny-allstate.json",
                         "reduced": [], "why": "tiny rehearsal"}]
    bench["workloads"] = [{"name": TINY, "config": "tiny-allstate",
                           "traffic": "train_window", "chips": 1,
                           "why": "tiny rehearsal"}]
    for sub in ("configs", "workloads", "traffic"):
        (tmp / "benchmark" / sub).mkdir(parents=True)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "benchmark/configs/tiny-allstate.json").write_text(
        json.dumps(conf))
    (tmp / f"benchmark/workloads/{TINY}.json").write_text(json.dumps(work))
    (tmp / "benchmark/traffic/train_window.json").write_text(
        (ROOT / "benchmark/traffic/train_window.json").read_text())
    return tmp


def failing(checks):
    return {k for k, c in checks.items() if c["value"] > c["limit"]}


def test_rehearsal_reads_correct_and_reports_the_new_metrics(
        tiny_allstate_root):
    from lightgbm_tpu.telemetry import counters
    counters.reset()
    line = bench_rehearsal.run_cell(tiny_allstate_root, TINY, SEED, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert failing(line["checks"]) == set()
    assert line["checks"]["leaf_count_mismatch"]["value"] == 0
    got = {k: line["metrics"][k]["value"] for k in READERS}
    # the bundled columns' bytes a row, not F + C: no plane was built
    assert 30 < got["host_code_bytes_per_row"] < 100
    assert 5 < got["hist_expansion_ratio"] < TINY_F
    assert 80 < got["bundled_feature_share"] < 100
    assert line["metrics"]["grow_dispatches_per_tree"]["value"] == 1.0


@pytest.fixture(scope="module")
def followed(tiny_allstate_root):
    """The tiny cell's first steps and its reference, for the control
    and the planted faults."""
    cell = spec.load_cell(tiny_allstate_root, TINY)
    state = train.first_steps(cell, SEED, {})
    assert state["booster"].train_set._inner._binned is None
    del state["booster"]
    reference, sound = train.check_first_steps(state, SEED)
    return cell, state, reference, sound


def over(readings):
    return {k for k, v in readings.items()
            if not v <= WORK["limits"][k]}


def test_sound_steps_pass_and_the_control_and_faults_fail(followed):
    _, state, reference, sound = followed
    assert over(sound) == set()
    readings = control.variants(reference, state["outputs"], SEED)
    assert over(readings["control"]) \
        and "leaf_count_mismatch" not in over(readings["control"])
    assert "leaf_count_mismatch" in over(readings["half_rows"])
    assert "update_norm_gap" in over(readings["frozen"])
    assert "leaf_value_gap" in over(readings["altered"])


def test_dropped_fix_histogram_fails_a_reading(followed, monkeypatch):
    """The planted EFB fault: the program with the elided default bin of
    a bundle member left at zero (FixHistogram dropped), so every bundled
    feature's split scan reads a histogram short of its default rows."""
    from lightgbm_tpu.ops import bundle
    cell, state, reference, _ = followed
    real = bundle.expand_column_hist

    def unfixed(col_hist, totals, hist_idx, f_elide, f_default):
        return real(col_hist, totals * 0, hist_idx, f_elide * 0, f_default)

    import jax
    monkeypatch.setattr(bundle, "expand_column_hist", unfixed)
    jax.clear_caches()            # the step is traced anew, unfixed
    bad = train.first_steps(cell, SEED, {})
    jax.clear_caches()
    del bad["booster"]
    got = reference.follow(bad["outputs"])
    assert over(got), got
