"""Concurrent prediction through the C ABI.

The reference's contract (reference: src/c_api.cpp:98 — the lock scope
around Boosting ends before Predict) is that concurrent *readers* run in
parallel while mutation serializes. Our engine is the embedded
Python/JAX runtime behind the GIL, so the C layer converts reader
concurrency into BATCHING instead: concurrent LGBM_*SingleRow predict
calls enqueue GIL-free and a dispatcher thread executes one vectorized
predict per waiting group (capi/c_api.cpp PredictDispatcher). These
tests pin the contract:

  * correctness: results under heavy thread concurrency are identical
    to the bulk dense predict, for dense and CSR single rows;
  * error isolation: a failing request (bad handle) reports through its
    own caller's LGBM_GetLastError without poisoning neighbors;
  * real coalescing: LGBM_TPU_PredictDispatchStats shows the N requests
    were served in fewer than N vectorized calls (the throughput claim —
    k callers share one interpreter round-trip — made observable).
"""
import ctypes
import os
import subprocess
import threading

import numpy as np
import pytest

from conftest import make_binary

LIB_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "capi", "lib_lightgbm_tpu.so")


@pytest.fixture(scope="module")
def lib():
    if not os.path.exists(LIB_PATH):
        r = subprocess.run(["make", "-C", os.path.dirname(LIB_PATH)],
                           capture_output=True)
        if r.returncode != 0:
            pytest.skip("C API lib build failed")
    lib = ctypes.CDLL(LIB_PATH)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    return lib


def _check(lib, rc):
    assert rc == 0, lib.LGBM_GetLastError().decode()


@pytest.fixture(scope="module")
def booster(lib):
    x, y = make_binary(700, 8)
    xf = np.ascontiguousarray(x, dtype=np.float64)
    yl = np.ascontiguousarray(y, dtype=np.float32)
    ds = ctypes.c_void_p()
    _check(lib, lib.LGBM_DatasetCreateFromMat(
        xf.ctypes.data_as(ctypes.c_void_p), 1, 700, 8, 1, b"max_bin=63",
        None, ctypes.byref(ds)))
    _check(lib, lib.LGBM_DatasetSetField(
        ds, b"label", yl.ctypes.data_as(ctypes.c_void_p), 700, 0))
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=15 verbosity=-1",
        ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(8):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    # bulk predictions = the ground truth each concurrent single-row
    # result must reproduce exactly
    bulk = np.zeros(700, dtype=np.float64)
    n64 = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, xf.ctypes.data_as(ctypes.c_void_p), 1, 700, 8, 1, 0, -1, b"",
        ctypes.byref(n64), bulk.ctypes.data_as(
            ctypes.POINTER(ctypes.c_double))))
    bulk_raw = np.zeros(700, dtype=np.float64)
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, xf.ctypes.data_as(ctypes.c_void_p), 1, 700, 8, 1, 1, -1, b"",
        ctypes.byref(n64), bulk_raw.ctypes.data_as(
            ctypes.POINTER(ctypes.c_double))))
    return bst, xf, bulk, bulk_raw


def _dispatch_stats(lib):
    r = ctypes.c_int64()
    b = ctypes.c_int64()
    m = ctypes.c_int64()
    _check(lib, lib.LGBM_TPU_PredictDispatchStats(
        ctypes.byref(r), ctypes.byref(b), ctypes.byref(m)))
    return r.value, b.value, m.value


def test_concurrent_single_row_dense(lib, booster):
    bst, xf, bulk, _ = booster
    reqs0, batches0, _ = _dispatch_stats(lib)
    n_threads, per_thread = 8, 50
    errors = []
    barrier = threading.Barrier(n_threads)

    def worker(tid):
        try:
            out = (ctypes.c_double * 1)()
            olen = ctypes.c_int64()
            barrier.wait()
            for i in range(per_thread):
                ridx = (tid * per_thread + i) % xf.shape[0]
                row = np.ascontiguousarray(xf[ridx])
                _check(lib, lib.LGBM_BoosterPredictForMatSingleRow(
                    bst, row.ctypes.data_as(ctypes.c_void_p), 1, 8, 1,
                    0, -1, b"", ctypes.byref(olen), out))
                assert olen.value == 1
                assert abs(out[0] - bulk[ridx]) < 1e-12, (tid, i, ridx)
        except Exception as e:  # surface thread failures in the main test
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[0]

    reqs1, batches1, max_batch = _dispatch_stats(lib)
    n_new = reqs1 - reqs0
    assert n_new == n_threads * per_thread
    # the contract under test: concurrency coalesced — the 400 requests
    # took FEWER than 400 vectorized predicts (i.e. some batch had >1
    # row). On a GIL engine this is the parallel-reader throughput win.
    assert batches1 - batches0 < n_new, (
        f"no coalescing: {n_new} requests -> {batches1 - batches0} batches")
    assert max_batch >= 2


def test_concurrent_csr_single_row_matches_dense(lib, booster):
    bst, xf, bulk, _ = booster
    n_threads, per_thread = 4, 25
    errors = []

    def worker(tid):
        try:
            out = (ctypes.c_double * 1)()
            olen = ctypes.c_int64()
            for i in range(per_thread):
                ridx = (tid * per_thread + i) % xf.shape[0]
                row = np.ascontiguousarray(xf[ridx])
                nz = np.nonzero(row)[0].astype(np.int32)
                indptr = np.array([0, len(nz)], dtype=np.int32)
                vals = np.ascontiguousarray(row[nz])
                _check(lib, lib.LGBM_BoosterPredictForCSRSingleRow(
                    bst, indptr.ctypes.data_as(ctypes.c_void_p), 2,
                    nz.ctypes.data_as(ctypes.c_void_p),
                    vals.ctypes.data_as(ctypes.c_void_p), 1,
                    ctypes.c_int64(2), ctypes.c_int64(len(nz)),
                    ctypes.c_int64(8), 0, -1, b"",
                    ctypes.byref(olen), out))
                assert abs(out[0] - bulk[ridx]) < 1e-12, (tid, i, ridx)
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[0]


def test_concurrent_mixed_configs_and_error_isolation(lib, booster):
    """Different predict configs (normal vs raw) batch separately but
    coexist; a bogus handle fails only its own caller."""
    bst, xf, bulk, bulk_raw = booster
    errors = []

    def good(raw):
        try:
            out = (ctypes.c_double * 1)()
            olen = ctypes.c_int64()
            for i in range(30):
                row = np.ascontiguousarray(xf[i])
                _check(lib, lib.LGBM_BoosterPredictForMatSingleRow(
                    bst, row.ctypes.data_as(ctypes.c_void_p), 1, 8, 1,
                    1 if raw else 0, -1, b"", ctypes.byref(olen), out))
                if raw:
                    assert abs(out[0] - bulk_raw[i]) < 1e-12
                else:
                    assert abs(out[0] - bulk[i]) < 1e-12
        except Exception as e:
            errors.append(e)

    def bad():
        try:
            out = (ctypes.c_double * 1)()
            olen = ctypes.c_int64()
            row = np.zeros(8)
            for _ in range(10):
                rc = lib.LGBM_BoosterPredictForMatSingleRow(
                    ctypes.c_void_p(0xdead0), row.ctypes.data_as(
                        ctypes.c_void_p), 1, 8, 1, 0, -1, b"",
                    ctypes.byref(olen), out)
                assert rc != 0
                assert lib.LGBM_GetLastError().decode() != ""
        except Exception as e:
            errors.append(e)

    threads = ([threading.Thread(target=good, args=(False,)),
                threading.Thread(target=good, args=(True,)),
                threading.Thread(target=bad)])
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[0]


def test_fork_after_dispatch_respawns_worker(lib):
    """fork() kills the dispatcher's worker thread; the child must
    re-spawn it (per-pid latch + atfork mutex protocol) instead of
    queueing forever. Fresh process so the fork happens with a live
    dispatcher and nothing else."""
    code = r"""
import ctypes, os, sys, numpy as np
lib = ctypes.CDLL(%r)
lib.LGBM_GetLastError.restype = ctypes.c_char_p
rng = np.random.RandomState(1)
x = rng.randn(300, 6); y = (x[:, 0] > 0).astype(np.float32)
xf = np.ascontiguousarray(x, dtype=np.float64)
ds = ctypes.c_void_p()
assert lib.LGBM_DatasetCreateFromMat(
    xf.ctypes.data_as(ctypes.c_void_p), 1, 300, 6, 1, b"", None,
    ctypes.byref(ds)) == 0
assert lib.LGBM_DatasetSetField(
    ds, b"label", y.ctypes.data_as(ctypes.c_void_p), 300, 0) == 0
bst = ctypes.c_void_p()
assert lib.LGBM_BoosterCreate(
    ds, b"objective=binary num_leaves=7 verbosity=-1",
    ctypes.byref(bst)) == 0
fin = ctypes.c_int()
for _ in range(3):
    assert lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)) == 0

def single_row(i):
    out = (ctypes.c_double * 1)()
    n = ctypes.c_int64()
    row = np.ascontiguousarray(xf[i])
    rc = lib.LGBM_BoosterPredictForMatSingleRow(
        bst, row.ctypes.data_as(ctypes.c_void_p), 1, 6, 1, 0, -1, b"",
        ctypes.byref(n), out)
    assert rc == 0, lib.LGBM_GetLastError()
    return out[0]

before = single_row(5)          # spawns the dispatcher worker
pid = os.fork()
if pid == 0:                    # child: worker thread did not survive
    try:
        assert abs(single_row(5) - before) < 1e-12
        os._exit(0)
    except BaseException:
        os._exit(1)
_, status = os.waitpid(pid, 0)
assert status == 0, f"child failed: {status}"
assert abs(single_row(5) - before) < 1e-12   # parent still fine
print("OK")
"""
    code = code % LIB_PATH
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(["python", "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-2000:])
    assert "OK" in r.stdout


def test_dispatch_disabled_fallback(lib):
    """LGBM_TPU_PREDICT_BATCH=0 must take the direct path (fresh process:
    the env is latched at first predict)."""
    code = r"""
import ctypes, os, numpy as np
lib = ctypes.CDLL(%r)
lib.LGBM_GetLastError.restype = ctypes.c_char_p
rng = np.random.RandomState(0)
x = rng.randn(200, 5); y = (x[:, 0] > 0).astype(np.float32)
xf = np.ascontiguousarray(x, dtype=np.float64)
ds = ctypes.c_void_p()
assert lib.LGBM_DatasetCreateFromMat(
    xf.ctypes.data_as(ctypes.c_void_p), 1, 200, 5, 1, b"", None,
    ctypes.byref(ds)) == 0, lib.LGBM_GetLastError()
assert lib.LGBM_DatasetSetField(
    ds, b"label", y.ctypes.data_as(ctypes.c_void_p), 200, 0) == 0
bst = ctypes.c_void_p()
assert lib.LGBM_BoosterCreate(
    ds, b"objective=binary num_leaves=7 verbosity=-1",
    ctypes.byref(bst)) == 0
fin = ctypes.c_int()
for _ in range(3):
    assert lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)) == 0
bulk = np.zeros(200, dtype=np.float64)
n = ctypes.c_int64()
assert lib.LGBM_BoosterPredictForMat(
    bst, xf.ctypes.data_as(ctypes.c_void_p), 1, 200, 5, 1, 0, -1, b"",
    ctypes.byref(n), bulk.ctypes.data_as(
        ctypes.POINTER(ctypes.c_double))) == 0
out = (ctypes.c_double * 1)()
row = np.ascontiguousarray(xf[7])
assert lib.LGBM_BoosterPredictForMatSingleRow(
    bst, row.ctypes.data_as(ctypes.c_void_p), 1, 5, 1, 0, -1, b"",
    ctypes.byref(n), out) == 0
assert abs(out[0] - bulk[7]) < 1e-12
r = ctypes.c_int64(); b = ctypes.c_int64(); m = ctypes.c_int64()
assert lib.LGBM_TPU_PredictDispatchStats(
    ctypes.byref(r), ctypes.byref(b), ctypes.byref(m)) == 0
assert r.value == 0, "direct path must not touch the dispatcher"
print("OK")
""" % LIB_PATH
    env = dict(os.environ, LGBM_TPU_PREDICT_BATCH="0",
               JAX_PLATFORMS="cpu")
    r = subprocess.run(["python", "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
