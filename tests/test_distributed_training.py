"""One full data-parallel training step over real multi-process
jax.distributed (2 local CPU processes, 1 device each): gradients ->
local histograms -> psum_scatter column-tiled reduction -> candidate
election -> local partition, the reference DataParallelTreeLearner
communication pattern (data_parallel_tree_learner.cpp:149-200 +
SyncUpGlobalBestSplit) — but across REAL process boundaries, not the
virtual single-process mesh tests/test_parallel.py uses.

The grown tree must match a single-device run on the same inputs (up to
equal-gain plateaus, same tolerance story as test_parallel.py).
"""
import os
import pickle
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import os, pickle, sys
import numpy as np
import jax
import jax.numpy as jnp

rank = int(sys.argv[1]); port = sys.argv[2]; out = sys.argv[3]
# bootstrap selects gloo for CPU cross-process collectives BEFORE the
# backend exists, then joins the process group
from lightgbm_tpu.distributed import bootstrap
bootstrap.initialize(f"127.0.0.1:{port}", 2, rank)
assert jax.process_count() == 2 and len(jax.devices()) == 2

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Dataset
from lightgbm_tpu.models.device_learner import (DeviceTreeLearner,
                                                grow_tree_chunk,
                                                grow_tree_chunk_core,
                                                grow_tree_compact,
                                                grow_tree_compact_core)

# both ranks build the identical full dataset (binning is deterministic)
r = np.random.RandomState(7)
n, f = 2000, 8
x = r.randn(n, f)
y = (1.5 * x[:, 0] - x[:, 1] + r.randn(n) * 0.5 > 0).astype(np.float64)
cfg = Config({"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "max_bin": 63, "min_data_in_leaf": 20})
ds = Dataset(x, config=cfg, label=y)
lrn = DeviceTreeLearner(cfg, ds, strategy="compact", device_place=False)
assert ds.bundle_arrays() is None   # scatter mode needs identity mapping

# logistic gradients from score 0
g = (0.5 - y).astype(np.float32)
h = np.full(n, 0.25, np.float32)
w = np.ones(n, np.float32)
mask_np = np.ones(f, bool)
key_np = np.asarray(jax.random.PRNGKey(0))

shards = 2
local_n = n // shards
assert local_n * shards == n
meta = (lrn.f_numbins, lrn.f_missing, lrn.f_default, lrn.f_monotone,
        lrn.f_penalty, lrn.f_categorical, lrn.f_col, lrn.f_base,
        lrn.f_elide, lrn.scan_plan)
statics = dict(c_cols=lrn.c_cols, item_bits=lrn.item_bits,
               pool_slots=lrn.pool_slots, scatter_cols=shards,
               **lrn._statics())

mesh = Mesh(np.array(jax.devices()), ("data",))
rsh = NamedSharding(mesh, P("data", None))
vsh = NamedSharding(mesh, P("data"))
rep = NamedSharding(mesh, P())
lo, hi = rank * local_n, (rank + 1) * local_n

def gshard(arr2d):
    return jax.make_array_from_process_local_data(rsh, arr2d[lo:hi])

def gvec(arr1d):
    return jax.make_array_from_process_local_data(vsh, arr1d[lo:hi])

def grep(arr):
    return jax.make_array_from_process_local_data(rep, arr)

cp = gshard(np.asarray(lrn.codes_pack))
cr = gshard(np.asarray(lrn.codes_row))
gg, hh, ww = gvec(g), gvec(h), gvec(w)
mask_g, key_g = grep(mask_np), grep(key_np)

def local(cp_l, cr_l, g_l, h_l, w_l, mask, key):
    rec, _rec_cat, _leaf, k, tot = grow_tree_compact_core(
        cp_l, cr_l, g_l, h_l, w_l, mask, *meta, key,
        axis_name="data", **statics)
    return rec, k, tot

fn = jax.jit(shard_map(
    local, mesh=mesh,
    in_specs=(P("data", None), P("data", None), P("data"), P("data"),
              P("data"), P(), P()),
    out_specs=(P(), P(), P()), check_vma=False))
rec, k, tot = jax.device_get(fn(cp, cr, gg, hh, ww, mask_g, key_g))

# single-device oracle on the full data, same inputs and statics
rec_s = k_s = None
if rank == 0:
    rec_1, _rc, _leaf, k_1, tot_1 = grow_tree_compact(
        jnp.asarray(lrn.codes_pack), jnp.asarray(lrn.codes_row),
        jnp.asarray(g), jnp.asarray(h), jnp.asarray(w),
        jnp.asarray(mask_np), *meta, jnp.asarray(key_np),
        c_cols=lrn.c_cols, item_bits=lrn.item_bits,
        pool_slots=lrn.pool_slots, **lrn._statics())
    rec_s, k_s = jax.device_get((rec_1, k_1))
    np.testing.assert_allclose(np.asarray(tot_1), np.asarray(tot),
                               rtol=1e-5)

# ---- chunk core (psum mode) across REAL process boundaries ----
statics_k = dict(c_cols=lrn.c_cols, item_bits=lrn.item_bits,
                 chunk_rows=1024, **lrn._statics())

def local_k(cp_l, cr_l, g_l, h_l, w_l, mask, key):
    rec, _rec_cat, _leaf, k, tot = grow_tree_chunk_core(
        cp_l, cr_l, g_l, h_l, w_l, mask, *meta, key,
        axis_name="data", **statics_k)
    return rec, k

fnk = jax.jit(shard_map(
    local_k, mesh=mesh,
    in_specs=(P("data", None), P("data", None), P("data"), P("data"),
              P("data"), P(), P()),
    out_specs=(P(), P()), check_vma=False))
reck, kk = jax.device_get(fnk(cp, cr, gg, hh, ww, mask_g, key_g))

reck_s = kk_s = None
if rank == 0:
    rk_1, _rc, _leaf, kk_1, _t = grow_tree_chunk(
        jnp.asarray(lrn.codes_pack), jnp.asarray(lrn.codes_row),
        jnp.asarray(g), jnp.asarray(h), jnp.asarray(w),
        jnp.asarray(mask_np), *meta, jnp.asarray(key_np),
        c_cols=lrn.c_cols, item_bits=lrn.item_bits, chunk_rows=1024,
        **lrn._statics())
    reck_s, kk_s = jax.device_get((rk_1, kk_1))

# ---- categorical step: the winner's (B,) left-bin mask rides the ----
# ---- candidate election across REAL process boundaries           ----
r2 = np.random.RandomState(23)
# the categorical column carries real signal so the k-vs-rest search
# WINS some splits — otherwise the mask transport would go unexercised
cat_col = (y * 4 + r2.randint(0, 4, n)).astype(np.float64)
xc = np.column_stack([cat_col, x])
cfgc = Config({"objective": "binary", "num_leaves": 15, "verbosity": -1,
               "max_bin": 63, "min_data_in_leaf": 20})
dsc = Dataset(xc, config=cfgc, label=y, categorical_feature=[0])
lrnc = DeviceTreeLearner(cfgc, dsc, strategy="compact", device_place=False)
assert dsc.bundle_arrays() is None
metac = (lrnc.f_numbins, lrnc.f_missing, lrnc.f_default, lrnc.f_monotone,
         lrnc.f_penalty, lrnc.f_categorical, lrnc.f_col, lrnc.f_base,
         lrnc.f_elide, lrnc.scan_plan)
staticsc = dict(c_cols=lrnc.c_cols, item_bits=lrnc.item_bits,
                pool_slots=lrnc.pool_slots, scatter_cols=shards,
                **lrnc._statics())
assert staticsc["cat_statics"] is not None

def localc(cp_l, cr_l, g_l, h_l, w_l, mask, key):
    rec, rec_cat, _leaf, k, tot = grow_tree_compact_core(
        cp_l, cr_l, g_l, h_l, w_l, mask, *metac, key,
        axis_name="data", **staticsc)
    return rec, rec_cat, k, tot

maskc_np = np.ones(xc.shape[1], bool)
cpc = gshard(np.asarray(lrnc.codes_pack))
crc = gshard(np.asarray(lrnc.codes_row))
maskc_g = grep(maskc_np)
fnc = jax.jit(shard_map(
    localc, mesh=mesh,
    in_specs=(P("data", None), P("data", None), P("data"), P("data"),
              P("data"), P(), P()),
    out_specs=(P(), P(), P(), P()), check_vma=False))
recc, recc_cat, kc, totc = jax.device_get(
    fnc(cpc, crc, gg, hh, ww, maskc_g, key_g))

recc_s = kc_s = recc_cat_s = None
if rank == 0:
    rc_1, rcc_1, _leaf, kc_1, _t = grow_tree_compact(
        jnp.asarray(lrnc.codes_pack), jnp.asarray(lrnc.codes_row),
        jnp.asarray(g), jnp.asarray(h), jnp.asarray(w),
        jnp.asarray(maskc_np), *metac, jnp.asarray(key_np),
        c_cols=lrnc.c_cols, item_bits=lrnc.item_bits,
        pool_slots=lrnc.pool_slots, **lrnc._statics())
    recc_s, recc_cat_s, kc_s = jax.device_get((rc_1, rcc_1, kc_1))

with open(out, "wb") as fh:
    pickle.dump({"rec": np.asarray(rec), "k": int(k),
                 "rec_s": None if rec_s is None else np.asarray(rec_s),
                 "k_s": None if k_s is None else int(k_s),
                 "reck": np.asarray(reck), "kk": int(kk),
                 "reck_s": None if reck_s is None else np.asarray(reck_s),
                 "kk_s": None if kk_s is None else int(kk_s),
                 "recc": np.asarray(recc),
                 "recc_cat": np.asarray(recc_cat), "kc": int(kc),
                 "recc_s": None if recc_s is None else np.asarray(recc_s),
                 "recc_cat_s": (None if recc_cat_s is None
                                else np.asarray(recc_cat_s)),
                 "kc_s": None if kc_s is None else int(kc_s)}, fh)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.mark.slow
@pytest.mark.distributed
def test_two_process_data_parallel_training_step(tmp_path):
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = ""           # 1 device per process
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    outs = [tmp_path / f"step_{r}.pkl" for r in range(2)]
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), str(port), str(outs[r])],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        for r in range(2)]
    for p in procs:
        _, err = p.communicate(timeout=600)
        assert p.returncode == 0, err.decode()[-3000:]

    with open(outs[0], "rb") as fh:
        r0 = pickle.load(fh)
    with open(outs[1], "rb") as fh:
        r1 = pickle.load(fh)

    # both processes hold the identical replicated split records
    assert r0["k"] == r1["k"] > 0
    np.testing.assert_array_equal(r0["rec"], r1["rec"])

    # distributed tree == single-device tree (equal-gain plateaus aside:
    # same tolerance story as tests/test_parallel.py)
    R_LEAF, R_FEAT, R_THR, _, R_GAIN = 0, 1, 2, 3, 4
    rec, rec_s, k = r0["rec"], r0["rec_s"], r0["k"]
    assert k == r0["k_s"]
    for i in range(k):
        assert rec[i, R_LEAF] == rec_s[i, R_LEAF], i
        gd, gs = rec[i, R_GAIN], rec_s[i, R_GAIN]
        assert abs(gd - gs) <= 1e-4 * max(1.0, abs(gs)), (i, gd, gs)
        if (rec[i, R_FEAT] != rec_s[i, R_FEAT]
                or rec[i, R_THR] != rec_s[i, R_THR]):
            assert abs(gd - gs) <= 2e-5 * max(1.0, abs(gs)), \
                (i, "split differs beyond a tie plateau")

    # chunk core (psum): replicated records across processes and
    # agreement with the single-device chunk run (tolerance as above)
    assert r0["kk"] == r1["kk"] > 0
    np.testing.assert_array_equal(r0["reck"], r1["reck"])
    assert r0["kk"] == r0["kk_s"]
    for i in range(r0["kk"]):
        gd, gs = r0["reck"][i, R_GAIN], r0["reck_s"][i, R_GAIN]
        assert abs(gd - gs) <= 1e-4 * max(1.0, abs(gs)), (i, gd, gs)

    # categorical step: replicated records + masks across processes,
    # at least one elected categorical winner, single-device agreement
    assert r0["kc"] == r1["kc"] > 0
    np.testing.assert_array_equal(r0["recc"], r1["recc"])
    np.testing.assert_array_equal(r0["recc_cat"], r1["recc_cat"])
    recc, kc = r0["recc"], r0["kc"]
    cat_rows = [i for i in range(kc)
                if recc[i, R_FEAT] == 0 and r0["recc_cat"][i].sum() > 0]
    assert cat_rows, "no categorical split crossed the election"
    assert kc == r0["kc_s"]
    for i in range(kc):
        gd, gs = recc[i, R_GAIN], r0["recc_s"][i, R_GAIN]
        assert abs(gd - gs) <= 1e-4 * max(1.0, abs(gs)), (i, gd, gs)
        if (recc[i, R_FEAT] == r0["recc_s"][i, R_FEAT] == 0
                and not np.array_equal(r0["recc_cat"][i],
                                       r0["recc_cat_s"][i])):
            # differing left-bin subsets are legal only on an equal-gain
            # plateau (same escape as the numeric block above)
            assert abs(gd - gs) <= 2e-5 * max(1.0, abs(gs)), \
                (i, "cat mask differs beyond a tie plateau")
