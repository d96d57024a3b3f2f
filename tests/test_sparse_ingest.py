"""Sparse (CSR/CSC) ingest without densification.

Round 4 (VERDICT weak #6): the reference bins sparse input directly
(src/io/sparse_bin.hpp:73); here the CSC structure feeds per-column
find-bin and the code fill, and the only dense object ever built is the
(N, F) uint8/16 code matrix — the designed post-bin storage. These tests
pin (a) exact equivalence with the dense ingest path, (b) the memory
bound at Bosch-like shape, (c) the sparse paths of the C API surface.
"""
import numpy as np
import pytest
import scipy.sparse as sp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Dataset as InnerDataset


def _sparse_problem(n=3000, f=40, density=0.05, seed=3):
    rng = np.random.RandomState(seed)
    x = sp.random(n, f, density=density, random_state=rng,
                  data_rvs=lambda k: rng.randn(k) * 2).tocsr()
    dense = np.asarray(x.todense())
    y = (dense[:, 0] - 0.5 * dense[:, 1] + 0.2 * rng.randn(n) > 0
         ).astype(np.float64)
    return x, dense, y


def test_sparse_ingest_binned_matches_dense():
    x, dense, y = _sparse_problem()
    cfg = Config({"objective": "binary", "verbosity": -1})
    ds_s = InnerDataset(x, config=cfg, label=y)
    ds_d = InnerDataset(dense, config=cfg, label=y)
    assert ds_s.num_data == ds_d.num_data
    assert ds_s.num_total_features == ds_d.num_total_features
    assert ds_s.used_features == ds_d.used_features
    for ms, md in zip(ds_s.bin_mappers, ds_d.bin_mappers):
        assert ms.num_bin == md.num_bin
        assert ms.missing_type == md.missing_type
        np.testing.assert_allclose(ms.bin_upper_bound, md.bin_upper_bound)
    np.testing.assert_array_equal(ds_s.binned, ds_d.binned)


def test_sparse_ingest_sampled_matches_dense():
    # force the row-sampling path (bin_construct_sample_cnt < n)
    x, dense, y = _sparse_problem(n=5000)
    cfg = Config({"objective": "binary", "verbosity": -1,
                  "bin_construct_sample_cnt": 1000})
    ds_s = InnerDataset(x, config=cfg, label=y)
    ds_d = InnerDataset(dense, config=cfg, label=y)
    for ms, md in zip(ds_s.bin_mappers, ds_d.bin_mappers):
        assert ms.num_bin == md.num_bin
        np.testing.assert_allclose(ms.bin_upper_bound, md.bin_upper_bound)
    np.testing.assert_array_equal(ds_s.binned, ds_d.binned)


def test_sparse_ingest_nan_and_zero_as_missing():
    x, dense, y = _sparse_problem(n=2000, f=10, density=0.2)
    # explicit NaNs ride the sparse structure
    x = x.tolil()
    x[5, 2] = np.nan
    x[17, 2] = np.nan
    x = x.tocsr()
    dense[5, 2] = np.nan
    dense[17, 2] = np.nan
    for params in ({"verbosity": -1},
                   {"verbosity": -1, "zero_as_missing": True}):
        cfg = Config(dict(params, objective="binary"))
        ds_s = InnerDataset(x, config=cfg, label=y)
        ds_d = InnerDataset(dense, config=cfg, label=y)
        for ms, md in zip(ds_s.bin_mappers, ds_d.bin_mappers):
            assert ms.missing_type == md.missing_type
            assert ms.num_bin == md.num_bin
        np.testing.assert_array_equal(ds_s.binned, ds_d.binned)


def test_sparse_training_matches_dense():
    x, dense, y = _sparse_problem()
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5}
    bs = lgb.train(params, lgb.Dataset(x, y), num_boost_round=5)
    bd = lgb.train(params, lgb.Dataset(dense, y), num_boost_round=5)
    assert bs.model_to_string() == bd.model_to_string()
    # sparse predict (single batch) agrees with dense predict
    np.testing.assert_allclose(bs.predict(x), bd.predict(dense),
                               rtol=1e-6, atol=1e-9)


def test_sparse_predict_batching():
    # > one 65536-row batch through the sparse predict path
    n, f = 70000, 12
    rng = np.random.RandomState(9)
    x = sp.random(n, f, density=0.05, random_state=rng,
                  data_rvs=lambda k: rng.randn(k)).tocsr()
    dense = np.asarray(x.todense())
    y = (dense[:, 0] + 0.3 * rng.randn(n) > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, lgb.Dataset(x, y),
                    num_boost_round=3)
    np.testing.assert_allclose(bst.predict(x), bst.predict(dense),
                               rtol=1e-6, atol=1e-9)


def test_sparse_ingest_memory_bound():
    """Bosch-like shape: 200k x 600 at 1% density. Densified float64
    ingest would allocate 960 MB; the sparse path must stay under a
    small multiple of the u8 code matrix (120 MB)."""
    import tracemalloc
    n, f = 200_000, 600
    rng = np.random.RandomState(11)
    x = sp.random(n, f, density=0.01, random_state=rng,
                  data_rvs=lambda k: rng.randn(k)).tocsr()
    y = rng.randint(0, 2, n).astype(np.float64)
    cfg = Config({"objective": "binary", "verbosity": -1,
                  "enable_bundle": False})
    tracemalloc.start()
    base, _ = tracemalloc.get_traced_memory()
    ds = InnerDataset(x, config=cfg, label=y)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    extra = peak - base
    assert ds.binned.nbytes == n * len(ds.used_features)
    assert extra < 400 * 1024 * 1024, \
        f"sparse ingest allocated {extra / 1e6:.0f} MB peak"


def test_capi_csr_create_and_predict():
    """The C-ABI CSR entry points feed the sparse path end-to-end."""
    from lightgbm_tpu import capi_impl as ci
    x, dense, y = _sparse_problem(n=1500, f=20, density=0.1)
    csr = x.tocsr()
    h = ci.dataset_create_from_csr(
        memoryview(csr.indptr.astype(np.int32)), 2,
        memoryview(csr.indices.astype(np.int32)),
        memoryview(csr.data.astype(np.float64)), 1,
        len(csr.indptr), csr.nnz, x.shape[1],
        "objective=binary verbosity=-1", None)
    ci.dataset_set_field(h, "label", memoryview(y.astype(np.float32)),
                         len(y), 0)
    bh = ci.booster_create(h, "objective=binary num_leaves=15 verbosity=-1")
    for _ in range(3):
        ci.booster_update_one_iter(bh)
    raw = ci.booster_predict_for_csr(
        bh, memoryview(csr.indptr.astype(np.int32)), 2,
        memoryview(csr.indices.astype(np.int32)),
        memoryview(csr.data.astype(np.float64)), 1,
        len(csr.indptr), csr.nnz, x.shape[1], 0, -1, "")
    preds = np.frombuffer(raw, dtype=np.float64)
    # same model trained via the python path on the dense matrix
    bd = lgb.train({"objective": "binary", "num_leaves": 15,
                    "verbosity": -1}, lgb.Dataset(dense, y),
                   num_boost_round=3)
    np.testing.assert_allclose(preds, bd.predict(dense),
                               rtol=1e-6, atol=1e-9)
    ci.booster_free(bh)
    ci.dataset_free(h)


def test_capi_streaming_sparse_push():
    """PushRowsByCSR accumulates sparse chunks; materialization never
    builds a dense float matrix when every push was sparse."""
    from lightgbm_tpu import capi_impl as ci
    x, dense, y = _sparse_problem(n=1200, f=15, density=0.1)
    csr = x.tocsr()
    h = ci.dataset_create_from_sampled_column(
        x.shape[0], x.shape[1], "objective=binary verbosity=-1")
    half = 600
    for start in (0, half):
        chunk = csr[start:start + half]
        ci.dataset_push_rows_by_csr(
            h, memoryview(chunk.indptr.astype(np.int32)), 2,
            memoryview(chunk.indices.astype(np.int32)),
            memoryview(chunk.data.astype(np.float64)), 1,
            len(chunk.indptr), chunk.nnz, x.shape[1], start)
    ds = ci._get(h)
    assert ds.buf is None, "sparse pushes must not allocate the dense buffer"
    assert sp.issparse(ds._assembled())
    ci.dataset_set_field(h, "label", memoryview(y.astype(np.float32)),
                         len(y), 0)
    bh = ci.booster_create(h, "objective=binary num_leaves=15 verbosity=-1")
    ci.booster_update_one_iter(bh)
    ref = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, lgb.Dataset(dense, y),
                    num_boost_round=1)
    from lightgbm_tpu.basic import Booster
    bst = ci._get(bh)
    assert isinstance(bst, Booster)
    assert bst.model_to_string() == ref.model_to_string()
    ci.booster_free(bh)
    ci.dataset_free(h)


# -- sparse tables that bundle: binned and bundled from their nonzeros ---
# (PR 37) No (N, F) code plane is built: the bundled (N, C) codes come
# straight from the CSC columns, and `binned` is made on the first read.
# Byte for byte what the dense-plane path gives.
def _one_hot_problem(n=4000, fields=(5, 30, 200, 7), seed=5):
    """Two numeric columns with zeros (their zero bin is not bin 0), one
    sparse numeric column, and one-hot fields: EFB bundles the levels.
    A few NaN ride the sparse structure."""
    rng = np.random.default_rng(seed)
    blocks = []
    for k in fields:
        level = rng.choice(k, n, p=rng.dirichlet(np.full(k, 0.5)))
        blocks.append(sp.csr_matrix((np.ones(n), (np.arange(n), level)),
                                    shape=(n, k)))
    num = rng.standard_normal((n, 3))
    num[:, :2][rng.random((n, 2)) < 0.3] = 0.0
    num[:, 2][rng.random(n) < 0.95] = 0.0
    x = sp.hstack([sp.csr_matrix(num)] + blocks).tolil()
    x[3, 0] = np.nan
    x[9, 1] = np.nan
    x = x.tocsr()
    y = (np.asarray(x[:, 0].todense()).ravel() > 0.2).astype(np.float64)
    return x, np.asarray(x.todense()), y


def _same_dataset(a, b):
    for ma, mb in zip(a.bin_mappers, b.bin_mappers):
        assert ma.to_dict() == mb.to_dict()
    assert a.used_features == b.used_features
    assert ([(c.features, c.bases, c.num_bins) for c in a.columns]
            == [(c.features, c.bases, c.num_bins) for c in b.columns])
    assert a.bundled.dtype == b.bundled.dtype
    np.testing.assert_array_equal(a.bundled, b.bundled)


@pytest.mark.parametrize("params", [
    {}, {"max_conflict_rate": 0.05}, {"zero_as_missing": True},
    {"max_bin": 15}], ids=["defaults", "conflicts", "zero_as_missing",
                           "max_bin_15"])
def test_bundled_sparse_matches_the_dense_plane(params):
    from lightgbm_tpu.telemetry import counters
    x, dense, y = _one_hot_problem()
    cfg = Config(dict(params, objective="binary", verbosity=-1))
    ds_s = InnerDataset(x, config=cfg, label=y)
    held = counters.get("host_code_bytes_per_row")
    ds_d = InnerDataset(dense, config=cfg, label=y)
    assert ds_s.columns is not None and ds_s._binned is None
    _same_dataset(ds_s, ds_d)
    if params.get("max_conflict_rate"):
        # rows where two members of one bundle are away from default:
        # the last member pushed wins on both paths, so the view is
        # built from the nonzeros kept, and the gauge counts them
        away = [[ds_d.binned[:, j] != ds_d.bin_mappers[
            ds_d.used_features[j]].default_bin for j in c.features]
            for c in ds_d.columns if c.is_bundle]
        assert any((np.sum(a, axis=0) > 1).any() for a in away)
        assert ds_s._nz is not None
        assert held > ds_s.bundled.shape[1] + len(ds_s._nz[1]) / len(y)
    else:
        # no conflict row: the view is decoded from the bundled codes
        assert ds_s._nz is None and held == ds_s.bundled.shape[1]
    zero_bins = [ds_s._zero_bin(c.features[0]) for c in ds_s.columns
                 if not c.is_bundle]
    assert any(zero_bins)          # a single-feature column off bin 0
    np.testing.assert_array_equal(ds_s.binned, ds_d.binned)


@pytest.mark.parametrize("sample_cnt", [20_000, 200_000])
def test_bundled_sparse_plans_on_the_dense_planes_sample(sample_cnt):
    """Above 50,000 rows a CSR table and its dense twin find their bins
    and plan their bundles on one sample of rows, `_bin_sample_rows`:
    the same plan and the same codes, whether the sample is part of the
    table or all of it."""
    x, dense, y = _one_hot_problem(n=60_000)
    cfg = Config({"objective": "binary", "verbosity": -1,
                  "bin_construct_sample_cnt": sample_cnt})
    ds_s = InnerDataset(x, config=cfg, label=y)
    ds_d = InnerDataset(dense, config=cfg, label=y)
    assert ds_s.columns is not None
    _same_dataset(ds_s, ds_d)
    np.testing.assert_array_equal(ds_s.binned, ds_d.binned)


def test_bundled_sparse_on_a_reference_takes_its_plan():
    x, dense, y = _one_hot_problem()
    cfg = Config({"objective": "binary", "verbosity": -1})
    ref = InnerDataset(dense[:2500], config=cfg, label=y[:2500])
    ds_s = InnerDataset(x[2500:], config=cfg, label=y[2500:],
                        reference=ref)
    ds_d = InnerDataset(dense[2500:], config=cfg, label=y[2500:],
                        reference=ref)
    assert ds_s.columns is ref.columns and ds_s._binned is None
    _same_dataset(ds_s, ds_d)
    np.testing.assert_array_equal(ds_s.binned, ds_d.binned)


def test_bundled_sparse_trains_as_the_dense_plane():
    x, dense, y = _one_hot_problem()
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5}
    bs = lgb.train(params, lgb.Dataset(x, y), num_boost_round=3)
    bd = lgb.train(params, lgb.Dataset(dense, y), num_boost_round=3)
    assert bs.model_to_string() == bd.model_to_string()
    assert bs._gbdt.train_set._binned is None


def test_bundled_sparse_builds_no_code_plane():
    """100,000 rows x 1,002 columns, 9 stored a row: an (N, F) byte plane
    would take 100 MB; the bundled path holds the nonzeros and the
    bundled columns, and reads C bytes a row (`host_code_bytes_per_row`)."""
    import tracemalloc
    from lightgbm_tpu.telemetry import counters
    n = 100_000
    x, y = _sparse_one_hot(n)
    cfg = Config({"objective": "binary", "verbosity": -1})
    tracemalloc.start()
    base, _ = tracemalloc.get_traced_memory()
    ds = InnerDataset(x, config=cfg, label=y)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    plane = n * ds.num_features
    assert ds._binned is None and ds.bundled is not None
    assert ds.num_features > 900 and ds.bundled.shape[1] < 30
    assert peak - base < plane / 4, f"{(peak - base) / 1e6:.0f} MB"
    assert counters.get("host_code_bytes_per_row") == ds.bundled.shape[1]


def _sparse_one_hot(n, fields=(500, 300, 150, 40, 6, 4), seed=8):
    """Two numeric columns and one-hot fields, the CSR built from its
    coordinates: no dense frame at any point."""
    rng = np.random.default_rng(seed)
    idx, at = [np.tile(np.arange(2), (n, 1))], 2
    for k in fields:
        idx.append(at + rng.choice(k, (n, 1), p=rng.dirichlet(np.ones(k))))
        at += k
    idx = np.hstack(idx)
    vals = np.hstack([rng.standard_normal((n, 2)),
                      np.ones((n, len(fields)))])
    x = sp.csr_matrix((vals.ravel(), idx.ravel(),
                       np.arange(0, idx.size + 1, idx.shape[1])),
                      shape=(n, at))
    return x, (vals[:, 0] > 0).astype(np.float64)
