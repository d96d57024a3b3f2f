"""A small child split off a large node keeps its own precision.

The split scan sums both sides of every threshold from the bins (a
prefix, a strict suffix, and the mass that rides with the missing
direction). Formed as `leaf total - other side` in float32, a child
whose hessian sum is 1-10 off a node whose sum is ~1e6 is rounding noise
of the parent, and its leaf output with it (PERF.md §7, fault 3: 34.4
for 0.426). The reference sums in double; float64 NumPy is the yardstick
here. CPU, toy sizes: arithmetic only.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import split as split_ops

F, B = 4, 64
NBINS = 60                      # bins in use; the rest is padding
MISSING = {"none": 0, "zero": 1, "nan": 2}
DEFAULT_BIN = 7                 # the zero bin of a zero-missing feature
TOL = 1e-5


def _parent(rng):
    """(F, B, 3) float64 bins of a node with H ~1e6, G ~0 net, ~4M rows:
    no threshold inside it is worth a split."""
    h = rng.uniform(12_000.0, 22_000.0, (F, NBINS))
    h *= 1.0e6 / h.sum(axis=1, keepdims=True)
    g = rng.standard_normal((F, NBINS)) * 0.3
    c = np.round(h * 4.0)
    hist = np.zeros((F, B, 3))
    hist[:, :NBINS] = np.stack([g, h, c], axis=-1)
    return hist


def _plant(hist, case, missing, rng):
    """Puts a child of H 1-10 and |G| ~15 into feature 1 of `hist`, where
    `case` says, and evens out the other features' totals. Returns the
    bins (float64) that belong to the small child."""
    small = np.zeros(B, bool)
    last = NBINS - 1            # the NaN bin of a NaN-missing feature
    if case == "low_bins":
        small[:3] = True
    elif case == "high_bins":
        top = last if missing == "nan" else NBINS
        small[top - 3:top] = True
    elif case == "nan_bin_alone":
        small[last] = True
    else:
        raise ValueError(case)
    if missing == "zero":
        assert not small[DEFAULT_BIN]
    k = int(small.sum())
    hist[1, small, 0] = -rng.uniform(3.0, 6.0, k)
    hist[1, small, 1] = rng.uniform(1.0, 3.0, k)
    hist[1, small, 2] = rng.integers(20, 30, k)
    # every feature histograms the same rows: one total
    for j in (0, 2, 3):
        hist[j, 0] += hist[1].sum(axis=0) - hist[j].sum(axis=0)
    return small


CASES = [(m, c) for m in MISSING for c in ("low_bins", "high_bins")] \
    + [("nan", "nan_bin_alone")]


@pytest.mark.parametrize("missing, case", CASES)
def test_child_sums_and_outputs_follow_float64(missing, case):
    rng = np.random.default_rng([2029, MISSING[missing], len(case)])
    hist = _parent(rng)
    small = _plant(hist, case, missing, rng)
    # the handed-down leaf total is a float32 sum of its own, as the
    # parent's split record's is
    totals = hist[1].sum(axis=0).astype(np.float32)
    f_missing = np.zeros(F, np.int32)
    f_missing[1] = MISSING[missing]
    res = split_ops.find_best_split(
        jnp.asarray(hist, jnp.float32), totals[0], totals[1], totals[2],
        jnp.full(F, NBINS, jnp.int32), jnp.asarray(f_missing),
        jnp.full(F, DEFAULT_BIN, jnp.int32), jnp.ones(F, bool),
        jnp.zeros(F, jnp.int32), jnp.float32(-np.inf), jnp.float32(np.inf),
        num_bins=B, l1=0.0, l2=0.0, max_delta_step=0.0,
        min_data_in_leaf=20, min_sum_hessian=1e-3, min_gain_to_split=0.0)
    assert int(res.feature) == 1 and float(res.gain) > 3.0

    # float64 sums of the two children the program chose
    t, dleft = int(res.threshold), bool(res.default_left)
    bins = np.arange(B)
    rides = np.zeros(B, bool)
    if missing == "zero":
        rides[DEFAULT_BIN] = True
    elif missing == "nan":
        rides[NBINS - 1] = True
    left = ((bins <= t) & ~rides) | (rides & dleft)
    assert (np.array_equal(small[:NBINS], left[:NBINS])
            or np.array_equal(small[:NBINS], ~left[:NBINS])), \
        "the scan did not isolate the planted child"
    for side, got in ((left, (res.left_sum_grad, res.left_sum_hess,
                              res.left_count, res.left_output)),
                      (~left, (res.right_sum_grad, res.right_sum_hess,
                               res.right_count, res.right_output))):
        g, h, c = hist[1, side].sum(axis=0)
        g_size = np.abs(hist[1, side, 0]).sum()
        assert abs(float(got[0]) - g) <= TOL * g_size
        assert abs(float(got[1]) - h) <= TOL * h
        assert float(got[2]) == c
        assert abs(float(got[3]) + g / h) <= TOL * g_size / h
    h_small = hist[1, small, 1].sum()
    assert 1.0 <= h_small <= 10.0


@pytest.mark.parametrize("seed", [2029, 2030])
def test_running_sums_keep_each_side_to_its_own_size(seed):
    """The prefix and the strict suffix: a tail of H ~2 above bins that
    hold 1e6, and a head of H ~2 below them."""
    rng = np.random.default_rng(seed)
    x = _parent(rng)
    for part in (slice(0, 2), slice(NBINS - 2, NBINS)):
        x[:, part, 0] = -rng.uniform(3.0, 6.0, (F, 2))
        x[:, part, 1] = rng.uniform(0.5, 1.5, (F, 2))
        x[:, part, 2] = rng.integers(10, 15, (F, 2))
    x32 = x.astype(np.float32)
    x64 = x32.astype(np.float64)
    pre, suf = split_ops._prefix_and_strict_suffix(jnp.asarray(x32))
    want_pre = np.cumsum(x64, axis=1)
    want_suf = np.cumsum(x64[:, ::-1], axis=1)[:, ::-1] - x64
    size = np.abs(x64)
    size_pre = np.cumsum(size, axis=1)
    size_suf = np.cumsum(size[:, ::-1], axis=1)[:, ::-1] - size
    assert np.all(np.abs(np.asarray(pre) - want_pre) <= TOL * size_pre)
    assert np.all(np.abs(np.asarray(suf) - want_suf) <= TOL * size_suf)
    # the small sides themselves, not only the bound
    assert 1.0 <= want_suf[0, NBINS - 3, 1] <= 3.0
    assert 1.0 <= want_pre[0, 1, 1] <= 3.0


def _heavy_tailed(n, seed):
    """Count-like columns with NaN and zero masses, one rare pocket."""
    r = np.random.default_rng(seed)
    x = np.empty((n, 6), np.float32)
    for j in range(6):
        col = np.round(np.exp(r.normal(1.0 + 0.3 * j, 1.6, n)))
        col[r.random(n) < 0.15 * j] = 0.0
        if j % 2:
            col[r.random(n) < 0.1 * j] = np.nan
        x[:, j] = col
    logit = -3.0 + 0.4 * np.log1p(np.nan_to_num(x[:, 0])) \
        - 0.3 * np.isnan(x[:, 1]) + 2.5 * (x[:, 2] > 400)
    y = (r.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
    return x, y


def test_heavy_tailed_table_device_learner_matches_host_learner(
        monkeypatch):
    """`lgb.train` on a heavy-tailed, missing-valued table: the device
    learner grows the host-loop learner's (models/serial_learner.py)
    trees, and the first tree's leaves hold what float64 sums of their
    own rows give."""
    x, y = _heavy_tailed(50_000, 29)
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "min_data_in_leaf": 20, "learning_rate": 0.1}

    def train():
        return lgb.train(dict(params), lgb.Dataset(x, y),
                         num_boost_round=3)

    dev = train()
    monkeypatch.setenv("LGBM_TPU_HOST_LEARNER", "1")
    host = train()
    np.testing.assert_allclose(dev.predict(x, raw_score=True),
                               host.predict(x, raw_score=True),
                               rtol=1e-4, atol=1e-5)

    tree = dev._gbdt.models[0]
    leaves = dev.predict(x, pred_leaf=True, num_iteration=1).reshape(-1)
    p = float(np.mean(y, dtype=np.float64))
    init = np.log(p / (1.0 - p))
    g, h = p - y.astype(np.float64), np.full(len(y), p * (1.0 - p))
    n_leaves = int(tree.num_leaves)
    want = -np.bincount(leaves, g, n_leaves) \
        / np.bincount(leaves, h, n_leaves) * 0.1
    got = np.asarray(tree.leaf_value[:n_leaves], np.float64) - init
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    assert np.max(np.abs(got - want) / scale) < 1e-4
    assert np.array_equal(np.asarray(tree.leaf_count[:n_leaves]),
                          np.bincount(leaves, minlength=n_leaves))
    missing_kinds = {(int(d) >> 2) & 3
                     for d in tree.decision_type[:n_leaves - 1]}
    assert missing_kinds & {1, 2}, "no split on a missing-valued feature"
