"""End-to-end training tests, modeled on the reference's test strategy
(reference: tests/python_package_test/test_engine.py — metric-threshold
assertions per objective + structural checks)."""
import numpy as np
import pytest

import lightgbm_tpu as lgb

from conftest import make_binary, make_multiclass, make_ranking, make_regression


def _logloss(y, p):
    p = np.clip(p, 1e-15, 1 - 1e-15)
    return float(np.mean(-(y * np.log(p) + (1 - y) * np.log(1 - p))))


def _auc(y, s):
    order = np.argsort(s)
    ranks = np.empty(len(s))
    ranks[order] = np.arange(1, len(s) + 1)
    pos = y > 0
    npos, nneg = pos.sum(), (~pos).sum()
    return float((ranks[pos].sum() - npos * (npos + 1) / 2) / (npos * nneg))


def test_binary():
    x, y = make_binary()
    params = {"objective": "binary", "metric": "binary_logloss",
              "num_leaves": 31, "learning_rate": 0.1, "verbosity": -1}
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=12, verbose_eval=False)
    pred = bst.predict(x)
    assert _logloss(y, pred) < 0.32
    assert _auc(y, pred) > 0.95


def test_regression():
    x, y = make_regression()
    params = {"objective": "regression", "metric": "l2", "verbosity": -1}
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=30, verbose_eval=False)
    pred = bst.predict(x)
    mse = float(np.mean((y - pred) ** 2))
    assert mse < 0.4


# slow: l1/huber objective variants of test_regression (91s compile on the 1-core tier-1 host; full CI runs them)
@pytest.mark.slow
def test_regression_l1_and_huber():
    x, y = make_regression()
    for obj in ("regression_l1", "huber", "fair", "quantile"):
        params = {"objective": obj, "verbosity": -1}
        ds = lgb.Dataset(x, y, free_raw_data=False)
        bst = lgb.train(params, ds, num_boost_round=20, verbose_eval=False)
        pred = bst.predict(x)
        mae = float(np.mean(np.abs(y - pred)))
        assert mae < 1.3, (obj, mae)


# slow: three objective variants in one compile-bound sweep (26s)
@pytest.mark.slow
def test_poisson_gamma_tweedie():
    r = np.random.RandomState(5)
    n, f = 1500, 6
    x = r.randn(n, f)
    mu = np.exp(0.4 * x[:, 0] + 0.2 * x[:, 1])
    y = r.poisson(mu).astype(np.float64)
    for obj in ("poisson", "tweedie"):
        ds = lgb.Dataset(x, y, free_raw_data=False)
        bst = lgb.train({"objective": obj, "verbosity": -1}, ds,
                        num_boost_round=20, verbose_eval=False)
        pred = bst.predict(x)
        assert pred.min() >= 0
        assert np.corrcoef(pred, mu)[0, 1] > 0.7
    ygam = np.maximum(y, 0.1)
    ds = lgb.Dataset(x, ygam, free_raw_data=False)
    bst = lgb.train({"objective": "gamma", "verbosity": -1}, ds,
                    num_boost_round=20, verbose_eval=False)
    assert bst.predict(x).min() > 0


def test_multiclass():
    x, y = make_multiclass()
    params = {"objective": "multiclass", "num_class": 4,
              "metric": "multi_logloss", "verbosity": -1}
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=15, verbose_eval=False)
    pred = bst.predict(x)
    assert pred.shape == (len(y), 4)
    np.testing.assert_allclose(pred.sum(axis=1), 1.0, rtol=1e-4)
    acc = float(np.mean(np.argmax(pred, axis=1) == y))
    assert acc > 0.85


# slow: ova variant of test_multiclass (40s compile)
@pytest.mark.slow
def test_multiclassova():
    x, y = make_multiclass()
    params = {"objective": "multiclassova", "num_class": 4, "verbosity": -1}
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=12, verbose_eval=False)
    pred = bst.predict(x)
    acc = float(np.mean(np.argmax(pred, axis=1) == y))
    assert acc > 0.8


def test_cross_entropy():
    x, y = make_binary()
    yq = np.where(y > 0, 0.9, 0.1)  # probabilistic labels
    for obj in ("cross_entropy", "cross_entropy_lambda"):
        ds = lgb.Dataset(x, yq, free_raw_data=False)
        bst = lgb.train({"objective": obj, "verbosity": -1}, ds,
                        num_boost_round=15, verbose_eval=False)
        pred = bst.predict(x)
        assert _auc(y, pred) > 0.9


def test_lambdarank():
    x, y, group = make_ranking()
    params = {"objective": "lambdarank", "metric": "ndcg",
              "eval_at": [3, 5], "verbosity": -1}
    ds = lgb.Dataset(x, y, group=group, free_raw_data=False)
    vds = lgb.Dataset(x, y, group=group, reference=ds, free_raw_data=False)
    evals = {}
    bst = lgb.train(params, ds, num_boost_round=30, valid_sets=[vds],
                    valid_names=["val"], evals_result=evals,
                    verbose_eval=False)
    ndcg = evals["val"]["ndcg@5"]
    assert ndcg[-1] > 0.70
    assert ndcg[-1] >= ndcg[0] - 1e-6


def test_missing_value_handle():
    r = np.random.RandomState(1)
    n = 1000
    x = r.randn(n, 3)
    y = (x[:, 0] > 0).astype(np.float64)
    x[r.rand(n) < 0.3, 0] = np.nan  # 30% missing in the informative feature
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train({"objective": "binary", "verbosity": -1}, ds,
                    num_boost_round=30, verbose_eval=False)
    pred = bst.predict(x)
    assert _auc(y, pred) > 0.85
    # NaN rows at predict time are handled
    x2 = x.copy()
    x2[:, 0] = np.nan
    pred2 = bst.predict(x2)
    assert np.all(np.isfinite(pred2))


def test_missing_value_zero_as_missing():
    r = np.random.RandomState(2)
    n = 1000
    x = np.zeros((n, 2))
    mask = r.rand(n) < 0.5
    x[mask, 0] = r.randn(mask.sum()) + 3
    y = mask.astype(np.float64)
    ds = lgb.Dataset(x, y, params={"zero_as_missing": True},
                     free_raw_data=False)
    bst = lgb.train({"objective": "binary", "zero_as_missing": True,
                     "verbosity": -1}, ds, num_boost_round=20,
                    verbose_eval=False)
    assert _auc(y, bst.predict(x)) > 0.95


def test_categorical_feature():
    r = np.random.RandomState(3)
    n = 2000
    cat = r.randint(0, 8, n).astype(np.float64)
    noise = r.randn(n, 2)
    x = np.column_stack([cat, noise])
    effect = np.array([2.0, -1.0, 0.5, 3.0, -2.0, 0.0, 1.0, -0.5])
    y = effect[cat.astype(int)] + 0.1 * r.randn(n)
    ds = lgb.Dataset(x, y, categorical_feature=[0], free_raw_data=False)
    bst = lgb.train({"objective": "regression", "verbosity": -1,
                     "min_data_in_leaf": 20}, ds,
                    num_boost_round=40, verbose_eval=False)
    pred = bst.predict(x)
    assert float(np.mean((y - pred) ** 2)) < 0.2


# slow: multi-valid multi-metric callback sweep (87s compile); test_early_stopping_first_metric_only keeps the path covered
@pytest.mark.slow
def test_early_stopping():
    x, y = make_binary(3000)
    xt, yt = x[:2000], y[:2000]
    xv, yv = x[2000:], y[2000:]
    params = {"objective": "binary", "metric": "binary_logloss",
              "verbosity": -1, "num_leaves": 63}
    ds = lgb.Dataset(xt, yt, free_raw_data=False)
    vds = lgb.Dataset(xv, yv, reference=ds, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=80, valid_sets=[vds],
                    early_stopping_rounds=5, verbose_eval=False)
    assert bst.best_iteration > 0
    assert bst.current_iteration() <= 80


def test_continued_training():
    x, y = make_binary()
    params = {"objective": "binary", "metric": "binary_logloss",
              "verbosity": -1}
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst1 = lgb.train(params, ds, num_boost_round=10, verbose_eval=False)
    model_str = bst1.model_to_string()
    ll1 = _logloss(y, bst1.predict(x))
    ds2 = lgb.Dataset(x, y, free_raw_data=False)
    bst2 = lgb.train(params, ds2, num_boost_round=10,
                     init_model=lgb.Booster(model_str=model_str),
                     verbose_eval=False)
    assert bst2.num_trees() == 20
    ll2 = _logloss(y, bst2.predict(x))
    assert ll2 < ll1


def test_bagging_and_feature_fraction():
    x, y = make_binary()
    params = {"objective": "binary", "bagging_fraction": 0.6,
              "bagging_freq": 1, "feature_fraction": 0.7,
              "verbosity": -1}
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=30, verbose_eval=False)
    assert _auc(y, bst.predict(x)) > 0.9


def test_dart():
    x, y = make_binary()
    params = {"objective": "binary", "boosting": "dart", "drop_rate": 0.3,
              "verbosity": -1}
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=30, verbose_eval=False)
    assert _auc(y, bst.predict(x)) > 0.9


def test_goss():
    x, y = make_binary()
    params = {"objective": "binary", "boosting": "goss", "top_rate": 0.3,
              "other_rate": 0.2, "verbosity": -1}
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=30, verbose_eval=False)
    assert _auc(y, bst.predict(x)) > 0.9


def test_rf():
    x, y = make_binary()
    params = {"objective": "binary", "boosting": "rf",
              "bagging_fraction": 0.7, "bagging_freq": 1,
              "feature_fraction": 0.8, "verbosity": -1}
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=20, verbose_eval=False)
    assert _auc(y, bst.predict(x)) > 0.85


def test_monotone_constraints():
    r = np.random.RandomState(6)
    n = 2000
    x = r.rand(n, 2)
    y = 3 * x[:, 0] + r.randn(n) * 0.1
    params = {"objective": "regression", "monotone_constraints": [1, 0],
              "verbosity": -1}
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=30, verbose_eval=False)
    grid = np.linspace(0.05, 0.95, 30)
    for fixed in (0.2, 0.8):
        test_x = np.column_stack([grid, np.full(30, fixed)])
        pred = bst.predict(test_x)
        assert np.all(np.diff(pred) >= -1e-6)


def test_max_depth():
    x, y = make_binary()
    params = {"objective": "binary", "max_depth": 3, "num_leaves": 63,
              "verbosity": -1}
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=10, verbose_eval=False)
    for tree in bst._gbdt.models:
        assert tree.depth() <= 3


def test_custom_objective_fobj():
    x, y = make_binary()
    ds = lgb.Dataset(x, y, free_raw_data=False)

    def fobj(preds, train_data):
        labels = train_data.get_label()
        p = 1.0 / (1.0 + np.exp(-preds))
        return p - labels, p * (1 - p)

    bst = lgb.train({"verbosity": -1, "metric": "none"}, ds, num_boost_round=30,
                    fobj=fobj, verbose_eval=False)
    pred_raw = bst.predict(x, raw_score=True)
    assert _auc(y, pred_raw) > 0.9


def test_cv():
    x, y = make_binary()
    ds = lgb.Dataset(x, y, free_raw_data=False)
    res = lgb.cv({"objective": "binary", "metric": "binary_logloss",
                  "verbosity": -1}, ds, num_boost_round=10, nfold=3,
                 verbose_eval=False)
    assert "binary_logloss-mean" in res
    assert len(res["binary_logloss-mean"]) == 10
    assert res["binary_logloss-mean"][-1] < res["binary_logloss-mean"][0]


def test_weights():
    x, y = make_binary()
    w = np.where(y > 0, 2.0, 1.0)
    ds = lgb.Dataset(x, y, weight=w, free_raw_data=False)
    bst = lgb.train({"objective": "binary", "verbosity": -1}, ds,
                    num_boost_round=20, verbose_eval=False)
    assert _auc(y, bst.predict(x)) > 0.9


def test_feature_importance():
    x, y = make_binary()
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train({"objective": "binary", "verbosity": -1}, ds,
                    num_boost_round=10, verbose_eval=False)
    imp_split = bst.feature_importance("split")
    imp_gain = bst.feature_importance("gain")
    assert imp_split.sum() > 0
    assert imp_gain.sum() > 0
    # informative features dominate
    assert imp_split[:4].sum() > imp_split[4:].sum()


def test_constant_features():
    x, y = make_binary(500)
    x = np.hstack([x, np.ones((500, 2))])  # two constant columns
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train({"objective": "binary", "verbosity": -1}, ds,
                    num_boost_round=10, verbose_eval=False)
    imp = bst.feature_importance()
    assert imp[-1] == 0 and imp[-2] == 0


def test_refit():
    x, y = make_binary()
    ds = lgb.Dataset(x, y, free_raw_data=False)
    bst = lgb.train({"objective": "binary", "verbosity": -1}, ds,
                    num_boost_round=10, verbose_eval=False)
    x2, y2 = make_binary(seed=99)
    new_bst = bst.refit(x2, y2)
    assert new_bst.num_trees() == bst.num_trees()
    assert _auc(y2, new_bst.predict(x2)) > 0.8


def test_device_strategies_agree_exactly():
    """masked vs compact whole-tree strategies must produce identical
    models without bagging (same histograms, same scans; host-oracle
    pattern of the reference's GPU_DEBUG_COMPARE)."""
    import os
    import lightgbm_tpu as lgb
    r = np.random.RandomState(9)
    x = r.randn(3000, 7).astype(np.float32)
    x[r.rand(3000, 7) < 0.1] = np.nan
    y = (np.nan_to_num(x[:, 0]) + 0.5 * np.nan_to_num(x[:, 1]) > 0).astype(float)

    def run(strategy):
        os.environ["LGBM_TPU_STRATEGY"] = strategy
        try:
            b = lgb.Booster(
                params={"objective": "binary", "num_leaves": 31,
                        "verbosity": -1, "min_data_in_leaf": 5},
                train_set=lgb.Dataset(x, y))
            for _ in range(4):
                b.update()
            return b
        finally:
            os.environ.pop("LGBM_TPU_STRATEGY", None)

    bm, bc = run("masked"), run("compact")
    for tm, tc in zip(bm._gbdt.models, bc._gbdt.models):
        assert tm.num_leaves == tc.num_leaves
        for i in range(tm.num_leaves - 1):
            assert int(tm.split_feature[i]) == int(tc.split_feature[i])
            assert int(tm.threshold_in_bin[i]) == int(tc.threshold_in_bin[i])
    np.testing.assert_allclose(
        bm.predict(x[:300], raw_score=True),
        bc.predict(x[:300], raw_score=True), rtol=1e-5, atol=1e-6)


def test_device_strategies_agree_4bit_packing():
    """max_bin <= 16 switches the compact buffer to 4-bit nibble packing
    (reference: src/io/dense_nbits_bin.hpp Dense4bitsBin); the packed
    program must agree with the masked strategy exactly."""
    import os
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models.device_learner import DeviceTreeLearner
    r = np.random.RandomState(11)
    x = r.randn(2500, 9).astype(np.float32)
    x[r.rand(2500, 9) < 0.08] = np.nan
    y = (np.nan_to_num(x[:, 0]) - np.nan_to_num(x[:, 2]) > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 14,
              "verbosity": -1, "min_data_in_leaf": 5}

    def run(strategy):
        os.environ["LGBM_TPU_STRATEGY"] = strategy
        try:
            b = lgb.Booster(params=params, train_set=lgb.Dataset(x, y))
            for _ in range(3):
                b.update()
            return b
        finally:
            os.environ.pop("LGBM_TPU_STRATEGY", None)

    bm, bc = run("masked"), run("compact")
    lrn = bc._gbdt.learner
    assert isinstance(lrn, DeviceTreeLearner) and lrn.item_bits == 4, \
        "max_bin=14 must select nibble packing"
    for tm, tc in zip(bm._gbdt.models, bc._gbdt.models):
        assert tm.num_leaves == tc.num_leaves
        for i in range(tm.num_leaves - 1):
            assert int(tm.split_feature[i]) == int(tc.split_feature[i])
            assert int(tm.threshold_in_bin[i]) == int(tc.threshold_in_bin[i])
    np.testing.assert_allclose(
        bm.predict(x[:300], raw_score=True),
        bc.predict(x[:300], raw_score=True), rtol=1e-5, atol=1e-6)


def test_bag_compaction_routing_and_quality():
    """Fused bagging with subset compaction (reference subset-copy mode,
    gbdt.cpp:727-792): the tree trains on a physically gathered bag and
    out-of-bag rows get leaves from the rec-replay router. Invariants:
    the internal score vector must equal tree-traversal predictions
    exactly (routing correctness), and quality must match the
    non-compacted weight-mode path (fp-tie plateaus make structural
    equality too strict across the two summation orders)."""
    import os
    import jax
    import lightgbm_tpu as lgb
    r = np.random.RandomState(5)
    x = r.randn(4000, 7).astype(np.float32)
    y = (x[:, 0] + 0.4 * x[:, 1] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "bagging_fraction": 0.4, "bagging_freq": 1,
              "min_data_in_leaf": 5}

    def run():
        os.environ["LGBM_TPU_STRATEGY"] = "compact"
        try:
            b = lgb.Booster(params=params, train_set=lgb.Dataset(x, y))
            for _ in range(5):
                b.update()
            return b
        finally:
            os.environ.pop("LGBM_TPU_STRATEGY", None)

    b1 = run()
    score = np.asarray(jax.device_get(b1._gbdt.score_updater.score[0]))
    pred = b1.predict(x, raw_score=True)
    np.testing.assert_allclose(score, pred, rtol=0, atol=1e-5)

    os.environ["LGBM_TPU_NO_BAG_COMPACT"] = "1"
    try:
        b2 = run()
    finally:
        os.environ.pop("LGBM_TPU_NO_BAG_COMPACT", None)
    auc1 = _auc(y, pred)
    auc2 = _auc(y, b2.predict(x, raw_score=True))
    assert auc1 > 0.9 and abs(auc1 - auc2) < 0.02, (auc1, auc2)
    for t1, t2 in zip(b1._gbdt.models, b2._gbdt.models):
        assert t1.num_leaves == t2.num_leaves


def test_fused_goss_device_sampling():
    """GOSS fused into the device step (reference goss.hpp sampling +
    subset speed mode): rank-exact top_k/other_k selection, amplified
    gradients, compacted growth, rec-replay routing for unsampled rows.
    The internal score must equal tree-traversal predictions and the
    model must learn."""
    import os
    import jax
    import lightgbm_tpu as lgb
    r = np.random.RandomState(5)
    x = r.randn(4000, 7).astype(np.float32)
    y = (x[:, 0] + 0.4 * x[:, 1] > 0).astype(float)
    params = {"objective": "binary", "boosting": "goss", "num_leaves": 31,
              "top_rate": 0.2, "other_rate": 0.1, "verbosity": -1,
              "learning_rate": 0.5, "min_data_in_leaf": 5}
    os.environ["LGBM_TPU_STRATEGY"] = "compact"
    try:
        b = lgb.Booster(params=params, train_set=lgb.Dataset(x, y))
        for _ in range(5):
            b.update()
    finally:
        os.environ.pop("LGBM_TPU_STRATEGY", None)
    # warmup (first 1/learning_rate = 2 iters) runs the plain step,
    # after which GOSS sampling kicks in (reference goss.hpp:143-144)
    assert set(b._gbdt._fused_step) == {False, True}, \
        "GOSS must run warmup (plain) and sampled fused steps"
    score = np.asarray(jax.device_get(b._gbdt.score_updater.score[0]))
    pred = b.predict(x, raw_score=True)
    np.testing.assert_allclose(score, pred, rtol=0, atol=1e-5)
    assert _auc(y, pred) > 0.95


def test_window_step2_matches_default(monkeypatch):
    """The window ladder is a matter of speed alone: at 20,000 rows, where
    the ladder's constant (step 2) gives four rungs, three boosting
    iterations grow the trees of the coarsest ladder (one rung over the
    floor: every split of more than 4,096 rows takes the whole table as
    its window), text for text."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models import device_learner as dl
    r = np.random.RandomState(31)
    x = r.randn(20000, 6).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 2] + 0.3 * r.randn(20000) > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 15,
              "verbosity": -1, "min_data_in_leaf": 5}
    monkeypatch.setenv("LGBM_TPU_STRATEGY", "compact")
    windows = set()
    real = dl.partition_window

    def spy(win, key3, tile_rows=None):
        windows.add(win.shape[0])
        return real(win, key3, tile_rows)

    monkeypatch.setattr(dl, "partition_window", spy)

    def run(step):
        # the step is no static of the jitted growth program, so a
        # program traced under another ladder must not be handed back
        dl.grow_tree_compact.clear_cache()
        if step:
            monkeypatch.setattr(dl, "WINDOW_STEP", step)
        windows.clear()
        b = lgb.Booster(params=params, train_set=lgb.Dataset(x, y))
        for _ in range(3):
            b.update()
        assert b._gbdt.learner.strategy == "compact"
        return [t.to_string() for t in b._gbdt.models], sorted(windows)

    assert dl.WINDOW_STEP == 2
    fine, fine_windows = run(None)
    assert fine_windows == dl._size_classes(20000) == [
        4096, 8192, 16384, 20000]
    coarse, coarse_windows = run(1 << 30)
    dl.grow_tree_compact.clear_cache()
    assert coarse_windows == [4096, 20000]
    assert len(fine) == 3 and fine == coarse


def test_lru_histogram_pool_matches_dense():
    """The slot-capped LRU histogram pool (role of the reference's
    HistogramPool, feature_histogram.hpp:654-831) must grow identical
    trees to the dense one-slot-per-leaf pool, even under heavy eviction
    (6 slots for 31 leaves -> constant misses + direct sibling rebuilds)."""
    import os
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models.device_learner import DeviceTreeLearner
    r = np.random.RandomState(21)
    x = r.randn(2500, 6).astype(np.float32)
    y = (x[:, 0] + 0.3 * x[:, 1] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 31,
              "verbosity": -1, "min_data_in_leaf": 5}

    def run(pool_slots):
        os.environ["LGBM_TPU_STRATEGY"] = "compact"
        try:
            b = lgb.Booster(params=params, train_set=lgb.Dataset(x, y))
            lrn = b._gbdt.learner
            assert isinstance(lrn, DeviceTreeLearner)
            lrn.pool_slots = pool_slots
            for _ in range(3):
                b.update()
            return b
        finally:
            os.environ.pop("LGBM_TPU_STRATEGY", None)

    bd, bp = run(0), run(6)
    for td, tp in zip(bd._gbdt.models, bp._gbdt.models):
        assert td.num_leaves == tp.num_leaves
        for i in range(td.num_leaves - 1):
            assert int(td.split_feature[i]) == int(tp.split_feature[i])
            assert int(td.threshold_in_bin[i]) == int(tp.threshold_in_bin[i])
    np.testing.assert_allclose(
        bd.predict(x[:200], raw_score=True),
        bp.predict(x[:200], raw_score=True), rtol=1e-4, atol=1e-5)


def test_fused_iteration_matches_generic_path():
    """The single-program fused device iteration must equal the generic
    (multi-dispatch) path tree-for-tree."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models import gbdt as gbdt_mod
    r = np.random.RandomState(4)
    x = r.randn(3000, 6).astype(np.float32)
    y = (x[:, 0] + 0.4 * x[:, 1] ** 2 + r.randn(3000) * 0.4 > 0.2).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 10}

    b1 = lgb.Booster(params=params, train_set=lgb.Dataset(x, y))
    for _ in range(4):
        b1.update()
    assert b1._gbdt._fused_step is not None, "fused path not taken"

    orig = gbdt_mod.GBDT._fused_eligible
    gbdt_mod.GBDT._fused_eligible = lambda self: False
    try:
        b2 = lgb.Booster(params=params, train_set=lgb.Dataset(x, y))
        for _ in range(4):
            b2.update()
    finally:
        gbdt_mod.GBDT._fused_eligible = orig
    np.testing.assert_allclose(
        b1.predict(x[:500], raw_score=True),
        b2.predict(x[:500], raw_score=True), rtol=1e-5, atol=1e-6)


def test_missing_value_handle_na_exact():
    """reference: tests/python_package_test/test_engine.py:142
    test_missing_value_handle_na — one split must isolate the NaN row."""
    import lightgbm_tpu as lgb
    x = np.array([0, 1, 2, 3, 4, 5, 6, 7, np.nan]).reshape(-1, 1)
    y = np.array([1, 1, 1, 1, 0, 0, 0, 0, 1], dtype=float)
    params = {"objective": "regression", "metric": "auc", "verbosity": -1,
              "boost_from_average": False, "min_data_in_leaf": 1,
              "num_leaves": 2, "learning_rate": 1, "min_data_in_bin": 1,
              "zero_as_missing": False}
    bst = lgb.train(params, lgb.Dataset(x, y), num_boost_round=1)
    pred = bst.predict(x)
    np.testing.assert_allclose(pred, y, atol=1e-6)


def test_missing_value_handle_zero_exact():
    """reference: test_engine.py:174 test_missing_value_handle_zero —
    zero_as_missing=True routes both 0 and NaN with the missing bin."""
    import lightgbm_tpu as lgb
    x = np.array([0, 1, 2, 3, 4, 5, 6, 7, np.nan]).reshape(-1, 1)
    y = np.array([0, 1, 1, 1, 0, 0, 0, 0, 0], dtype=float)
    params = {"objective": "regression", "metric": "auc", "verbosity": -1,
              "boost_from_average": False, "min_data_in_leaf": 1,
              "num_leaves": 2, "learning_rate": 1, "min_data_in_bin": 1,
              "zero_as_missing": True}
    bst = lgb.train(params, lgb.Dataset(x, y), num_boost_round=1)
    pred = bst.predict(x)
    np.testing.assert_allclose(pred, y, atol=1e-6)


def test_missing_value_handle_none_exact():
    """reference: test_engine.py:206 test_missing_value_handle_none —
    use_missing=False treats NaN like the smallest bin."""
    import lightgbm_tpu as lgb
    x = np.array([0, 1, 2, 3, 4, 5, 6, 7, np.nan]).reshape(-1, 1)
    y = np.array([0, 1, 1, 1, 0, 0, 0, 0, 0], dtype=float)
    params = {"objective": "regression", "metric": "auc", "verbosity": -1,
              "boost_from_average": False, "min_data_in_leaf": 1,
              "num_leaves": 2, "learning_rate": 1, "min_data_in_bin": 1,
              "use_missing": False}
    bst = lgb.train(params, lgb.Dataset(x, y), num_boost_round=1)
    pred = bst.predict(x)
    assert abs(pred[0] - pred[1]) < 1e-9
    assert abs(pred[-1] - pred[0]) < 1e-9


def test_categorical_handle_exact():
    """reference: test_engine.py:239 test_categorical_handle — 8 distinct
    categories, alternating labels, one one-hot split per round."""
    import lightgbm_tpu as lgb
    x = np.arange(8, dtype=float).reshape(-1, 1)
    y = np.array([0, 1, 0, 1, 0, 1, 0, 1], dtype=float)
    params = {"objective": "regression", "metric": "auc", "verbosity": -1,
              "boost_from_average": False, "min_data_in_leaf": 1,
              "num_leaves": 2, "learning_rate": 1, "min_data_in_bin": 1,
              "min_data_per_group": 1, "cat_smooth": 1, "cat_l2": 0,
              "max_cat_to_onehot": 1, "zero_as_missing": True}
    bst = lgb.train(params, lgb.Dataset(x, y, categorical_feature=[0]),
                    num_boost_round=8)
    pred = bst.predict(x)
    np.testing.assert_allclose(pred, y, atol=1e-5)


def test_categorical_handle_na_exact():
    """reference: test_engine.py:276 test_categorical_handle_na — NaN
    category must separate cleanly from category 0."""
    import lightgbm_tpu as lgb
    x = np.array([0, np.nan, 0, np.nan, 0, np.nan]).reshape(-1, 1)
    y = np.array([0, 1, 0, 1, 0, 1], dtype=float)
    params = {"objective": "regression", "metric": "auc", "verbosity": -1,
              "boost_from_average": False, "min_data_in_leaf": 1,
              "num_leaves": 2, "learning_rate": 1, "min_data_in_bin": 1,
              "min_data_per_group": 1, "cat_smooth": 1, "cat_l2": 0,
              "max_cat_to_onehot": 1, "zero_as_missing": False}
    bst = lgb.train(params, lgb.Dataset(x, y, categorical_feature=[0]),
                    num_boost_round=1)
    pred = bst.predict(x)
    np.testing.assert_allclose(pred, y, atol=1e-6)


def test_early_stopping_first_metric_only():
    """first_metric_only: the stopper tracks only the first metric even
    when a second metric keeps improving (reference callback.py:221)."""
    x, y = make_binary(2400)
    xt, yt, xv, yv = x[:1600], y[:1600], x[1600:], y[1600:]
    params = {"objective": "binary", "metric": ["binary_logloss", "auc"],
              "first_metric_only": True, "verbosity": -1}
    ds = lgb.Dataset(xt, yt, free_raw_data=False)
    vds = lgb.Dataset(xv, yv, reference=ds, free_raw_data=False)
    evals = {}
    bst = lgb.train(params, ds, num_boost_round=60, valid_sets=[vds],
                    valid_names=["val"], early_stopping_rounds=5,
                    evals_result=evals, verbose_eval=False)
    assert bst.best_iteration > 0
    # both metrics were still recorded
    assert "binary_logloss" in evals["val"] and "auc" in evals["val"]


def test_booster_attr():
    """attr/set_attr string attributes (reference: basic.py:2717/:2733)."""
    x, y = make_binary(300)
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    lgb.Dataset(x, y), num_boost_round=2)
    assert bst.attr("foo") is None
    bst.set_attr(foo="bar", n="1")
    assert bst.attr("foo") == "bar" and bst.attr("n") == "1"
    bst.set_attr(foo=None)
    assert bst.attr("foo") is None
    with pytest.raises(ValueError):
        bst.set_attr(k=7)


def test_model_from_string_roundtrip():
    """model_from_string replaces the model in-place (reference
    basic.py:2241)."""
    x, y = make_binary(600)
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    lgb.Dataset(x, y), num_boost_round=4)
    s = bst.model_to_string()
    bst2 = lgb.train({"objective": "binary", "verbosity": -1},
                     lgb.Dataset(x[:100], y[:100]), num_boost_round=1)
    bst2.model_from_string(s, verbose=False)
    np.testing.assert_allclose(bst.predict(x), bst2.predict(x), rtol=1e-9)


def test_get_leaf_output_matches_pred_leaf():
    """Summing get_leaf_output over pred_leaf assignments reproduces the
    raw prediction (reference: test_engine.py pred-leaf invariants)."""
    x, y = make_binary(800)
    bst = lgb.train({"objective": "binary", "verbosity": -1,
                     "num_leaves": 7}, lgb.Dataset(x, y), num_boost_round=3)
    leaves = bst.predict(x[:50], pred_leaf=True).astype(int)
    raw = bst.predict(x[:50], raw_score=True)
    manual = np.array(
        [sum(bst.get_leaf_output(t, leaves[i, t])
             for t in range(leaves.shape[1])) for i in range(50)])
    np.testing.assert_allclose(manual, raw, atol=1e-6)


def test_get_split_value_histogram():
    """reference: test_engine.py:1473 — histogram over a feature's used
    split values; categorical features rejected."""
    x, y = make_binary(1200)
    bst = lgb.train({"objective": "binary", "verbosity": -1,
                     "num_leaves": 15}, lgb.Dataset(x, y),
                    num_boost_round=10)
    # some feature must be split on; find one from importances
    f = int(np.argmax(bst.feature_importance("split")))
    hist, edges = bst.get_split_value_histogram(f)
    assert hist.sum() > 0 and len(edges) == len(hist) + 1
    # by-name lookup agrees with by-index
    name = bst.feature_name()[f]
    hist2, edges2 = bst.get_split_value_histogram(name)
    np.testing.assert_array_equal(hist, hist2)
    # xgboost-style output keeps only non-empty bins
    ret = bst.get_split_value_histogram(f, xgboost_style=True)
    vals = np.asarray(ret)
    assert (vals[:, 1] > 0).all()
    # categorical feature -> error (reference behavior)
    xc = np.column_stack([np.random.RandomState(0).randint(0, 8, 500),
                          np.random.RandomState(1).randn(500)])
    yc = (xc[:, 0] > 3).astype(float)
    bc = lgb.train({"objective": "binary", "verbosity": -1,
                    "min_data_per_group": 1},
                   lgb.Dataset(xc, yc, categorical_feature=[0]),
                   num_boost_round=2)
    with pytest.raises(lgb.LightGBMError):
        bc.get_split_value_histogram(0)


def test_set_reference_rebins_to_template():
    """set_reference re-aligns an unconstructed/constructed dataset to the
    reference's bin mappers (reference: basic.py:1319)."""
    x, y = make_binary(1000)
    ds_train = lgb.Dataset(x, y, free_raw_data=False)
    ds_train.construct()
    x2, y2 = make_binary(400, seed=9)
    ds_other = lgb.Dataset(x2, y2, free_raw_data=False)
    ds_other.construct()          # constructed standalone first
    ds_other.set_reference(ds_train)
    ds_other.construct()
    # aligned bin mappers: identical bin upper bounds per feature
    a = ds_train._inner.bin_mappers
    b = ds_other._inner.bin_mappers
    for ma, mb in zip(a, b):
        np.testing.assert_array_equal(
            np.asarray(ma.bin_upper_bound), np.asarray(mb.bin_upper_bound))
    # freed raw data -> error, like the reference
    ds3 = lgb.Dataset(x2, y2)     # free_raw_data=True
    ds3.construct()
    with pytest.raises(lgb.LightGBMError):
        ds3.set_reference(ds_train)


def test_init_model_from_file_seeds_scores_and_valids():
    """Continuation from a model FILE must seed training scores and valid
    updaters with the loaded trees (deserialized trees need their binned
    routing reconstructed — rebin_inner)."""
    x, y = make_binary(1500)
    xt, yt, xv, yv = x[:1000], y[:1000], x[1000:], y[1000:]
    params = {"objective": "binary", "metric": "binary_logloss",
              "verbosity": -1}
    ds = lgb.Dataset(xt, yt, free_raw_data=False)
    bst1 = lgb.train(dict(params), ds, num_boost_round=6)
    import tempfile, os
    path = os.path.join(tempfile.mkdtemp(), "cont.txt")
    bst1.save_model(path)

    evals = {}
    vds = lgb.Dataset(xv, yv, reference=ds, free_raw_data=False)
    bst2 = lgb.train(dict(params), ds, num_boost_round=4,
                     init_model=path, valid_sets=[vds],
                     valid_names=["val"], evals_result=evals,
                     verbose_eval=False)
    assert bst2.current_iteration() == 10
    # the first continuation eval must already include the 6 loaded trees:
    # it must beat the logloss of an untrained model by a wide margin and
    # be close to bst1's own valid logloss
    def logloss(b):
        p = np.clip(b.predict(xv), 1e-9, 1 - 1e-9)
        return float(-np.mean(yv * np.log(p) + (1 - yv) * np.log(1 - p)))
    first_eval = evals["val"]["binary_logloss"][0]
    assert abs(first_eval - logloss(bst1)) < 0.05, (first_eval, logloss(bst1))
    # and the final model must improve on the 6-tree model
    assert logloss(bst2) < logloss(bst1) + 1e-9


def _dummy_obj(preds, train_data):
    return np.ones(len(preds)), np.ones(len(preds))


def _constant_metric(preds, train_data):
    return ("error", 0.0, False)


# slow: metric-alias matrix compiles one eval program per alias (64s); individual metrics are covered by their own tests
@pytest.mark.slow
def test_metric_aliasing_matrix():
    """reference: test_engine.py:1072 test_metrics — the params/args/fobj/
    feval metric-resolution matrix for lgb.cv."""
    x, y = make_binary(500)
    ds = lgb.Dataset(x, y, free_raw_data=False)
    pv = {"verbosity": -1}
    p_obj = {"objective": "binary", "verbosity": -1}
    p_obj_err = {"objective": "binary", "metric": "binary_error",
                 "verbosity": -1}
    p_obj_multi = {"objective": "binary",
                   "metric": ["binary_logloss", "binary_error"],
                   "verbosity": -1}
    p_err = {"metric": "binary_error", "verbosity": -1}
    p_multi = {"metric": ["binary_logloss", "binary_error"],
               "verbosity": -1}

    def res(params=p_obj, **kw):
        return lgb.cv(dict(params), ds, num_boost_round=2, nfold=3,
                      verbose_eval=False, **kw)

    # no fobj, no feval: default / params / args / args-overwrites-params
    assert "binary_logloss-mean" in res()
    assert "binary_error-mean" in res(params=p_obj_err)
    assert "binary_logloss-mean" in res(metrics="binary_logloss")
    assert "binary_error-mean" in res(metrics="binary_error")
    r = res(params=p_obj_multi)
    assert "binary_logloss-mean" in r and "binary_error-mean" in r
    r = res(metrics=["binary_logloss", "binary_error"])
    assert "binary_logloss-mean" in r and "binary_error-mean" in r
    # 'None' aliases remove the default metric
    for na in ("None", "na", "null", "custom"):
        assert len(res(metrics=na)) == 0
    assert len(res(metrics=["None"])) == 0

    # fobj: no default metric unless requested
    assert len(res(params=pv, fobj=_dummy_obj)) == 0
    assert "binary_error-mean" in res(params=p_err, fobj=_dummy_obj)
    assert "binary_error-mean" in res(params=pv, fobj=_dummy_obj,
                                      metrics="binary_error")
    r = res(params=p_multi, fobj=_dummy_obj)
    assert "binary_logloss-mean" in r and "binary_error-mean" in r

    # feval joins whatever internal metrics resolve
    r = res(feval=_constant_metric)
    assert "binary_logloss-mean" in r and "error-mean" in r
    r = res(params=p_obj_err, feval=_constant_metric)
    assert "binary_error-mean" in r and "error-mean" in r
    r = res(params=p_obj_multi, feval=_constant_metric)
    assert ("binary_logloss-mean" in r and "binary_error-mean" in r
            and "error-mean" in r)
    # feval only, internal metrics removed
    r = res(metrics="None", feval=_constant_metric)
    assert list(r.keys()) == ["error-mean", "error-stdv"]


def test_model_size_many_trees():
    """reference: test_engine.py:1447 test_model_size — a model string
    with replicated trees loads, reports the right tree count, and
    truncated prediction matches. (The reference pads past 2 GiB to probe
    C-side 32-bit offsets; scaled down here — the engine is not
    offset-limited, and a 2 GiB string is pure wall on this box.)"""
    x, y = make_regression(400)
    bst = lgb.train({"verbosity": -1, "objective": "regression"},
                    lgb.Dataset(x, y), num_boost_round=2)
    pred = bst.predict(x)
    s = bst.model_to_string()
    one_tree = s[s.find("Tree=1"):s.find("end of trees")]
    one_tree = one_tree.replace("Tree=1", "Tree={}")
    multiplier = 100
    total = multiplier + 2
    big = (s[:s.find("tree_sizes")]
           + "\n\n"
           + s[s.find("Tree=0"):s.find("end of trees")]
           + (one_tree * multiplier).format(*range(2, total))
           + s[s.find("end of trees"):]
           + " " * (1 << 20))
    bst.model_from_string(big, verbose=False)
    assert bst.num_trees() == total
    np.testing.assert_allclose(bst.predict(x, num_iteration=2), pred)


def test_mean_average_precision_alias():
    """reference: config.cpp:104 — 'mean_average_precision' resolves to
    the map ranking metric; values land in [0, 1] and improve."""
    x, y, group = make_ranking(40)
    evals = {}
    ds = lgb.Dataset(x, y, group=group, free_raw_data=False)
    vds = lgb.Dataset(x, y, group=group, free_raw_data=False,
                      reference=ds)
    lgb.train({"objective": "lambdarank",
               "metric": "mean_average_precision", "eval_at": [3],
               "verbosity": -1}, ds, num_boost_round=5,
              valid_sets=[vds], valid_names=["val"],
              evals_result=evals, verbose_eval=False)
    key = [k for k in evals["val"] if k.startswith("map")]
    assert key, list(evals["val"])
    vals = evals["val"][key[0]]
    assert all(0.0 <= v <= 1.0 for v in vals)
    assert vals[-1] >= vals[0] - 1e-9


def test_trivial_features_dropped():
    """Constant columns never get split on (reference: used_feature
    filtering in DatasetLoader)."""
    x, y = make_binary(500)
    x = np.column_stack([x, np.zeros(500), np.full(500, 3.0)])
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    lgb.Dataset(x, y), num_boost_round=5)
    imp = bst.feature_importance("split")
    assert imp[-1] == 0 and imp[-2] == 0
    assert imp.sum() > 0


def test_predict_num_iteration_slices():
    """Prediction with start_iteration/num_iteration equals summing the
    per-tree contributions of exactly that slice."""
    x, y = make_binary(700)
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    lgb.Dataset(x, y), num_boost_round=6)
    full = bst.predict(x, raw_score=True)
    a = bst.predict(x, raw_score=True, num_iteration=3)
    b = bst.predict(x, raw_score=True, start_iteration=3, num_iteration=3)
    base = full - (a + b)
    # the init score (boost_from_average) rides both slice predictions
    np.testing.assert_allclose(base, np.full_like(base, base[0]), atol=1e-5)


def test_pandas_categorical_roundtrip(tmp_path):
    """reference: test_engine.py test_pandas_categorical — category
    dtype columns auto-map to categorical features, the category lists
    ride the model file (pandas_categorical trailer), and prediction on
    a frame with a DIFFERENT category order still aligns codes."""
    pd = pytest.importorskip("pandas")
    r = np.random.RandomState(21)
    n = 1200
    cats = ["red", "green", "blue", "black"]
    c = r.choice(cats, n)
    xnum = r.randn(n)
    eff = {"red": 2.0, "green": -1.0, "blue": 0.5, "black": -2.0}
    y = (np.vectorize(eff.get)(c) + xnum + r.randn(n) * 0.3 > 0).astype(float)
    df = pd.DataFrame({"c": pd.Categorical(c, categories=cats),
                       "x": xnum})
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    lgb.Dataset(df, y), num_boost_round=8)
    pred = bst.predict(df)
    acc = np.mean((pred > 0.5) == (y > 0))
    assert acc > 0.85, acc

    # model file carries the category lists
    path = str(tmp_path / "pcat.txt")
    bst.save_model(path)
    assert "pandas_categorical:" in open(path).read()
    bst2 = lgb.Booster(model_file=path)
    assert bst2.pandas_categorical == [cats]

    # a frame whose categorical carries a DIFFERENT category order must
    # re-align to the stored lists, not its own codes
    df_shuffled = pd.DataFrame({
        "c": pd.Categorical(c, categories=list(reversed(cats))),
        "x": xnum})
    np.testing.assert_allclose(bst2.predict(df_shuffled), pred, rtol=1e-6)

    # unseen category at predict time -> missing (NaN), not a crash
    df_unseen = df.head(10).copy()
    df_unseen["c"] = pd.Categorical(["purple"] * 10,
                                    categories=["purple"])
    p_unseen = bst2.predict(df_unseen)
    assert np.isfinite(p_unseen).all()


def test_pandas_categorical_int_categories(tmp_path):
    """Integer category values must survive the JSON trailer as ints:
    after save/load, predict on the original frame is unchanged (string-
    ified categories would re-align to nothing -> all-missing)."""
    pd = pytest.importorskip("pandas")
    r = np.random.RandomState(4)
    n = 800
    c = r.choice([10, 20, 30], n)
    df = pd.DataFrame({7: pd.Categorical(c), 0: r.randn(n)})
    y = ((c == 20) | (df[0].values > 1)).astype(float)
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    lgb.Dataset(df, y), num_boost_round=6)
    pred = bst.predict(df)
    assert np.mean((pred > 0.5) == (y > 0)) > 0.9
    path = str(tmp_path / "m.txt")
    bst.save_model(path)
    bst2 = lgb.Booster(model_file=path)
    assert bst2.pandas_categorical == [[10, 20, 30]]
    np.testing.assert_allclose(bst2.predict(df), pred, rtol=1e-6)
    # int-labeled columns: the auto-detected categorical is column 7 at
    # POSITION 0 — importances must show the categorical, not column 0
    assert bst.feature_importance("split")[0] > 0


def test_save_load_copy_pickle():
    """reference: test_engine.py test_save_load_copy_pickle — pickle,
    copy and deepcopy all preserve predictions (via the model string;
    the live training engine is not serializable)."""
    import copy
    import pickle
    x, y = make_binary(600)
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    lgb.Dataset(x, y), num_boost_round=4)
    ref = bst.predict(x)
    for clone in (pickle.loads(pickle.dumps(bst)), copy.copy(bst),
                  copy.deepcopy(bst)):
        np.testing.assert_allclose(clone.predict(x), ref, rtol=1e-9)
        assert clone.num_trees() == bst.num_trees()


def test_sklearn_model_pickles():
    """Fitted sklearn wrappers must pickle (the most common deployment
    path for sklearn users)."""
    import pickle
    x, y = make_binary(500)
    m = lgb.LGBMClassifier(n_estimators=4, verbosity=-1).fit(x, y)
    m2 = pickle.loads(pickle.dumps(m))
    np.testing.assert_array_equal(m2.predict(x), m.predict(x))
    np.testing.assert_allclose(m2.predict_proba(x), m.predict_proba(x),
                               rtol=1e-9)


def test_train_on_dataset_subset():
    """reference: test_engine.py test_init_with_subset / test_sliced_data
    — a row subset of a constructed Dataset trains with the parent's bin
    mappers."""
    x, y = make_binary(1000)
    ds = lgb.Dataset(x, y, free_raw_data=False)
    ds.construct()
    idx = np.arange(0, 1000, 2)
    sub = ds.subset(idx)
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    sub, num_boost_round=5)
    acc = np.mean((bst.predict(x) > 0.5) == (y > 0))
    assert acc > 0.8, acc
    assert sub.num_data() == 500
    # subset rows carry their metadata slice
    np.testing.assert_array_equal(sub.get_label(), y[idx])


def test_max_bin_by_feature():
    """reference: test_engine.py test_max_bin_by_feature — per-feature
    bin caps land in the mappers and the model still trains."""
    x, y = make_binary(800)
    ds = lgb.Dataset(x, y, params={"max_bin_by_feature":
                                   [4] + [255] * (x.shape[1] - 1)},
                     free_raw_data=False)
    bst = lgb.train({"objective": "binary", "verbosity": -1}, ds,
                    num_boost_round=3)
    nb = [len(m.bin_upper_bound) for m in ds._inner.bin_mappers]
    assert nb[0] <= 4 and max(nb[1:]) > 4
    assert bst.num_trees() == 3


def test_cv_fpreproc():
    """reference: test_engine.py test_fpreproc — the preprocessing hook
    sees each fold's train/valid sets and can rewrite params."""
    x, y = make_binary(600)
    seen = []

    def fpreproc(dtrain, dtest, params):
        seen.append((dtrain.num_data(), dtest.num_data()))
        params["learning_rate"] = 0.05
        return dtrain, dtest, params

    res = lgb.cv({"objective": "binary", "verbosity": -1},
                 lgb.Dataset(x, y, free_raw_data=False),
                 num_boost_round=3, nfold=3, fpreproc=fpreproc,
                 verbose_eval=False)
    assert len(seen) == 3
    assert all(tr + te == 600 for tr, te in seen)
    assert "binary_logloss-mean" in res


def test_continue_train_dart():
    """reference: test_engine.py test_continue_train_dart — DART
    continuation from an init_model keeps improving."""
    x, y = make_regression(1200)
    params = {"objective": "regression", "boosting": "dart",
              "drop_rate": 0.2, "verbosity": -1, "metric": "l2"}
    ds = lgb.Dataset(x, y, free_raw_data=False)
    b1 = lgb.train(dict(params), ds, num_boost_round=8)
    b2 = lgb.train(dict(params), ds, num_boost_round=8,
                   init_model=b1)
    assert b2.current_iteration() == 16
    mse1 = float(np.mean((b1.predict(x) - y) ** 2))
    mse2 = float(np.mean((b2.predict(x) - y) ** 2))
    assert mse2 < mse1 + 1e-9, (mse1, mse2)


def test_continue_train_multiclass():
    """reference: test_engine.py test_continue_train_multiclass — the
    per-class tree layout survives continuation."""
    x, y = make_multiclass(900, k=3)
    params = {"objective": "multiclass", "num_class": 3,
              "verbosity": -1}
    ds = lgb.Dataset(x, y, free_raw_data=False)
    b1 = lgb.train(dict(params), ds, num_boost_round=5)
    b2 = lgb.train(dict(params), ds, num_boost_round=5, init_model=b1)
    assert b2.num_trees() == 30       # (5+5) iterations x 3 classes
    p = b2.predict(x)
    assert p.shape == (900, 3)
    acc1 = np.mean(np.argmax(b1.predict(x), axis=1) == y)
    acc2 = np.mean(np.argmax(p, axis=1) == y)
    assert acc2 >= acc1 - 1e-9


def test_multiclass_prediction_early_stopping():
    """reference: test_engine.py test_multiclass_prediction_early_stopping
    — margin-based early stop changes nothing when the margin is huge
    and stays close with a sane margin."""
    x, y = make_multiclass(900, k=3)
    bst = lgb.train({"objective": "multiclass", "num_class": 3,
                     "verbosity": -1}, lgb.Dataset(x, y),
                    num_boost_round=10)
    base = bst.predict(x)
    p1 = bst.predict(x, pred_early_stop=True, pred_early_stop_freq=5,
                     pred_early_stop_margin=1.5)
    assert np.mean(np.argmax(p1, 1) == np.argmax(base, 1)) > 0.95
    p2 = bst.predict(x, pred_early_stop=True, pred_early_stop_freq=5,
                     pred_early_stop_margin=1e30)
    np.testing.assert_allclose(p2, base, rtol=1e-6)


def test_contribs_sum_to_raw_prediction():
    """reference: test_engine.py test_contribs — TreeSHAP contributions
    (+ expected value column) sum to the raw score for every row."""
    x, y = make_binary(700)
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    lgb.Dataset(x, y), num_boost_round=6)
    contrib = bst.predict(x[:200], pred_contrib=True)
    assert contrib.shape == (200, x.shape[1] + 1)
    np.testing.assert_allclose(contrib.sum(axis=1),
                               bst.predict(x[:200], raw_score=True),
                               rtol=1e-5, atol=1e-6)


def test_subset_preserves_groups_and_multiclass_init_score():
    """Subset keeps ranking query structure (whole-query folds) and
    slices a flat class-major multiclass init_score per class block."""
    x, y, group = make_ranking(30)
    ds = lgb.Dataset(x, y, group=group, free_raw_data=False)
    ds.construct()
    # keep the first 10 whole queries (20 docs each)
    sub = ds.subset(np.arange(10 * 20))
    assert np.array_equal(sub.get_group(), np.full(10, 20))
    bst = lgb.train({"objective": "lambdarank", "verbosity": -1,
                     "metric": "ndcg", "eval_at": [3]}, sub,
                    num_boost_round=3)
    assert bst.num_trees() == 3

    # multiclass flat init_score: class-major blocks slice per class
    xm, ym = make_multiclass(300, k=3)
    init = np.arange(900, dtype=np.float64)       # (3, 300) flattened
    dsm = lgb.Dataset(xm, ym, init_score=init, free_raw_data=False)
    dsm.construct()
    subm = dsm.subset(np.arange(0, 300, 2))
    got = np.asarray(subm.get_init_score()).reshape(3, 150)
    np.testing.assert_array_equal(got, init.reshape(3, 300)[:, ::2])


def test_reference_chain():
    """reference: test_engine.py test_reference_chain — valid sets chained
    off a train set (and off each other) share one binning and evaluate."""
    x, y = make_binary(1500)
    ds = lgb.Dataset(x[:900], y[:900], free_raw_data=False)
    v1 = lgb.Dataset(x[900:1200], y[900:1200], reference=ds,
                     free_raw_data=False)
    v2 = lgb.Dataset(x[1200:], y[1200:], reference=v1,
                     free_raw_data=False)
    evals = {}
    lgb.train({"objective": "binary", "metric": "binary_logloss",
               "verbosity": -1}, ds, num_boost_round=4,
              valid_sets=[v1, v2], valid_names=["a", "b"],
              evals_result=evals, verbose_eval=False)
    assert len(evals["a"]["binary_logloss"]) == 4
    assert len(evals["b"]["binary_logloss"]) == 4
    for m in (v1._inner.bin_mappers, v2._inner.bin_mappers):
        for ma, mb in zip(ds._inner.bin_mappers, m):
            assert ma.bin_upper_bound == mb.bin_upper_bound


def test_node_level_subcol():
    """reference: test_engine.py test_node_level_subcol —
    feature_fraction_bynode changes the model but keeps quality; bynode
    differs from tree-level sampling."""
    x, y = make_binary(1200)
    p = {"objective": "binary", "metric": "binary_logloss",
         "verbosity": -1, "seed": 5}
    base = lgb.train(dict(p), lgb.Dataset(x, y, free_raw_data=False),
                     num_boost_round=8).predict(x)
    bynode = lgb.train(dict(p, feature_fraction_bynode=0.5),
                       lgb.Dataset(x, y, free_raw_data=False),
                       num_boost_round=8).predict(x)
    bytree = lgb.train(dict(p, feature_fraction=0.5),
                       lgb.Dataset(x, y, free_raw_data=False),
                       num_boost_round=8).predict(x)
    assert not np.allclose(base, bynode)
    assert not np.allclose(bynode, bytree)
    for pred in (bynode, bytree):
        assert np.mean((pred > 0.5) == (y > 0)) > 0.75


def test_forced_bins_engine(tmp_path):
    """reference: test_engine.py test_forced_bins — forced bin
    boundaries from JSON land in the mappers and steer thresholds,
    and survive max_bin truncation with priority over data bounds."""
    import json
    x, y = make_regression(800)
    forced = [{"feature": 0, "bin_upper_bound": [-0.5, 0.0, 0.5]}]
    fpath = str(tmp_path / "forced.json")
    with open(fpath, "w") as fh:
        json.dump(forced, fh)
    ds = lgb.Dataset(x, y, params={"forcedbins_filename": fpath},
                     free_raw_data=False)
    bst = lgb.train({"objective": "regression", "verbosity": -1,
                     "forcedbins_filename": fpath}, ds,
                    num_boost_round=3)
    ub = ds._inner.bin_mappers[0].bin_upper_bound
    for b in (-0.5, 0.0, 0.5):
        assert any(abs(u - b) < 1e-12 for u in ub), (b, ub[:8])
    assert bst.num_trees() == 3
    # forced bounds survive saturation: tiny max_bin still keeps them
    ds2 = lgb.Dataset(x, y, params={"forcedbins_filename": fpath,
                                    "max_bin": 8},
                      free_raw_data=False)
    ds2.construct()
    ub2 = ds2._inner.bin_mappers[0].bin_upper_bound
    assert len(ub2) <= 8
    for b in (-0.5, 0.5):
        assert any(abs(u - b) < 1e-12 for u in ub2), (b, ub2)


def test_parameter_constraint_validation():
    """Schema range constraints are enforced like the reference's CHECK
    macros (config.h doc tags): clear errors, not downstream crashes."""
    x, y = make_binary(200)
    for bad in ({"num_leaves": 1}, {"learning_rate": -0.5},
                {"bagging_fraction": 1.5}, {"feature_fraction": 0.0},
                {"max_bin": 1}, {"min_data_in_leaf": -3}):
        with pytest.raises(lgb.LightGBMError, match="Parameter"):
            lgb.train({"objective": "binary", "verbosity": -1, **bad},
                      lgb.Dataset(x, y), num_boost_round=1)
    # boundary values the constraints permit still train
    bst = lgb.train({"objective": "binary", "verbosity": -1,
                     "num_leaves": 2, "bagging_fraction": 1.0,
                     "feature_fraction": 1.0},
                    lgb.Dataset(x, y), num_boost_round=1)
    assert bst.num_trees() == 1
