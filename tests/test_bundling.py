"""EFB (exclusive feature bundling) tests.

Covers the greedy grouping (reference: dataset.cpp:69-145 FindGroups), the
column encoding/expansion round trip, and end-to-end training parity: with
max_conflict_rate=0 bundles are truly exclusive, so the bundled device
learner must reproduce the unbundled host learner's model exactly.
"""
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.bundling import (MAX_COL_BINS, encode_bundle,
                                      expansion_arrays, find_bundles,
                                      plan_columns)
from lightgbm_tpu.io.dataset import Dataset


def test_find_bundles_exclusive():
    n = 1000
    masks = [np.zeros(n, bool) for _ in range(4)]
    masks[0][:300] = True
    masks[1][300:600] = True     # exclusive with 0 -> same bundle
    masks[2][100:400] = True     # conflicts with both
    masks[3][600:900] = True     # exclusive with 0,1
    bundles = find_bundles([np.flatnonzero(m) for m in masks],
                           [10, 10, 10, 10],
                           max_conflict_rate=0.0, sample_cnt=n)
    merged = sorted(sorted(b) for b in bundles if len(b) > 1)
    assert any({0, 1}.issubset(set(b)) for b in merged)
    assert all(2 not in b for b in merged)


def test_find_bundles_bin_budget():
    n = 100
    masks = [np.zeros(n, bool) for _ in range(3)]
    bundles = find_bundles([np.flatnonzero(m) for m in masks],
                           [200, 200, 200],
                           max_conflict_rate=0.0, sample_cnt=n)
    # 199 + 199 > 255 non-default codes: no pair fits one uint8 column
    assert all(len(b) == 1 for b in bundles)


def _onehot_frame(n, k, rng, dense=3, nvals=2):
    """One-hot block with few distinct non-zero values so the bundle's
    255-code column budget fits all k indicator features."""
    cat = rng.randint(0, k, n)
    oh = np.zeros((n, k))
    oh[np.arange(n), cat] = rng.randint(1, nvals + 1, n).astype(float)
    x = np.concatenate([rng.randn(n, dense), oh], axis=1)
    return x, cat


def test_dataset_builds_bundles(rng):
    x, _ = _onehot_frame(2000, 12, rng)
    ds = Dataset(x, config=Config({"verbose": -1}), label=np.zeros(2000))
    assert ds.columns is not None
    sizes = sorted(len(c.features) for c in ds.columns)
    # the 12 exclusive one-hot columns bundle together; dense ones stay solo
    assert sizes[-1] >= 10
    assert ds.bundled is not None
    assert ds.bundled.shape[1] == len(ds.columns)
    assert ds.bundled.shape[1] < ds.num_features


def test_encode_expand_roundtrip(rng):
    """Column histogram expansion must reproduce per-feature histograms."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.bundle import expand_column_hist
    x, _ = _onehot_frame(3000, 8, rng)
    ds = Dataset(x, config=Config({"verbose": -1}), label=np.zeros(3000))
    assert ds.columns is not None
    codes, f_col, f_base, f_elide, hist_idx, col_bins = ds.bundle_arrays()
    g = rng.randn(ds.num_data).astype(np.float32)
    h = np.ones(ds.num_data, np.float32)
    gh = np.stack([g, h, np.ones_like(g)], axis=1)

    # reference histograms from the logical view
    B = ds.max_num_bins
    want = np.zeros((ds.num_features, B, 3), np.float32)
    for j in range(ds.num_features):
        for b in range(B):
            m = ds.binned[:, j] == b
            want[j, b] = gh[m].sum(axis=0)

    ch = np.zeros((len(ds.columns), col_bins, 3), np.float32)
    bc = np.asarray(codes)
    for ci in range(len(ds.columns)):
        for b in range(col_bins):
            m = bc[:, ci] == b
            ch[ci, b] = gh[m].sum(axis=0)
    totals = gh.sum(axis=0)
    got = np.asarray(expand_column_hist(
        jnp.asarray(ch), jnp.asarray(totals), hist_idx,
        f_elide, jnp.asarray(np.array(
            [ds.bin_mappers[f].default_bin for f in ds.used_features],
            np.int32))))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_bundled_training_matches_host(rng):
    x, cat = _onehot_frame(3000, 10, rng)
    y = (x[:, 0] + 0.3 * cat - 1.5 + rng.randn(3000) * 0.5 > 0).astype(float)
    params = dict(objective="binary", num_leaves=15, learning_rate=0.1,
                  verbose=-1, max_conflict_rate=0.0)
    ds = lgb.Dataset(x, label=y)
    bst = lgb.train(params, ds, num_boost_round=5)
    assert ds._inner.columns is not None
    p_dev = bst.predict(x)

    os.environ["LGBM_TPU_HOST_LEARNER"] = "1"
    try:
        ds2 = lgb.Dataset(x, label=y)
        bst2 = lgb.train(params, ds2, num_boost_round=5)
        p_host = bst2.predict(x)
    finally:
        os.environ.pop("LGBM_TPU_HOST_LEARNER", None)
    np.testing.assert_allclose(p_dev, p_host, rtol=1e-5, atol=1e-6)
