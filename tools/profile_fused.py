"""Decompose the FUSED boosting iteration's wall clock.

The round-5 scaling probe put bare tree growth at ~354 ms/tree
(1M x 28 x 255, compact+sort) while the full fused `update()` measured
~1.27 s/iter in bench.py — a ~0.9 s/iter gap that sits OUTSIDE the grow
program. This tool splits one fused iteration into:

  dispatch   - fused_step() call until all output handles exist
               (async dispatch + any blocking H2D of small args)
  program    - block_until_ready on the new score (device wall of the
               whole fused program, overlapped with dispatch)
  fetch      - device_get of (rec, rec_cat, k): D2H round-trip
  replay     - host replay_tree + shrinkage + bookkeeping

Usage: python tools/profile_fused.py [rows] [iters]
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.config import Config  # noqa: E402
from lightgbm_tpu.io.dataset import Dataset  # noqa: E402
from lightgbm_tpu.models.gbdt import create_boosting  # noqa: E402

N = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
ITERS = int(sys.argv[2]) if len(sys.argv) > 2 else 10

r = np.random.RandomState(17)
F = 28
x = r.randn(N, F).astype(np.float32)
w = r.randn(F) * (r.rand(F) > 0.4)
y = ((x @ w * 0.3 + r.randn(N)) > 0).astype(np.float64)

cfg = Config({"objective": "binary", "num_leaves": 255, "max_bin": 63,
              "metric": "none", "min_data_in_leaf": 20, "verbosity": -1})
ds = Dataset(x, config=cfg, label=y)
bst = create_boosting(cfg, ds)
assert bst._fused_eligible(), "fused path not eligible for this config"
print(f"backend={jax.default_backend()} N={N} "
      f"partition={bst.learner._partition_mode} "
      f"strategy={bst.learner.strategy}", flush=True)

# one full warm iteration (compiles the fused program)
t0 = time.time()
bst.train_one_iter()
print(f"warmup iter (incl compile) {time.time()-t0:.1f}s", flush=True)

acc = {}


def mark(name, t0):
    t1 = time.time()
    acc[name] = acc.get(name, 0.0) + (t1 - t0)
    return t1


done = 0
for it in range(ITERS):
    cfgc = bst.config
    init_score = bst._boost_from_average(0, True)
    fused_step = bst._fused_step[False]
    rng = np.random.RandomState(
        (cfgc.feature_fraction_seed + bst.iter) % (2**31 - 1))
    fmask = bst.learner._feature_mask(rng)
    if not getattr(bst.learner, "cat_in_program", False):
        fmask = fmask & np.asarray(bst.learner.f_categorical == 0)

    t = time.time()
    base_mask = jnp.asarray(fmask)
    tree_key = jax.random.PRNGKey(bst.iter)
    freq = max(cfgc.bagging_freq, 1)
    bag_key = jax.random.PRNGKey(
        (cfgc.bagging_seed + (bst.iter // freq)) % (2**31 - 1))
    shr = jnp.float32(bst.shrinkage_rate)
    t = mark("arg_put", t)

    new_score, rec, rec_cat, leaf_id, k_dev, _finite = fused_step(
        bst.score_updater.score[0], base_mask, tree_key, bag_key, shr)
    t = mark("dispatch", t)

    new_score.block_until_ready()
    t = mark("program", t)

    if rec_cat is None:
        rec_h, k = jax.device_get((rec, k_dev))
        rec_cat_h = None
    else:
        rec_h, rec_cat_h, k = jax.device_get((rec, rec_cat, k_dev))
    k = int(k)
    t = mark("fetch", t)
    if k == 0:
        # the real path (_train_one_iter_fused) delegates a no-split
        # iteration to the generic stop bookkeeping; for a timing probe
        # just stop — replaying an empty record would produce garbage
        print(f"iter {it}: no split found — stopping profile", flush=True)
        break

    tree = bst.learner.replay_tree(rec_h, k, rec_cat_h)
    tree.apply_shrinkage(bst.shrinkage_rate)
    t = mark("replay", t)

    bst.score_updater.score = bst.score_updater.score.at[0].set(new_score)
    bst.models.append(tree)
    bst.iter += 1
    done = it + 1
    t = mark("commit", t)

total = sum(acc.values())
done = max(done, 1)
for kk, v in acc.items():
    print(f"{kk:10s} {v/done*1e3:9.1f} ms/iter", flush=True)
print(f"{'TOTAL':10s} {total/done*1e3:9.1f} ms/iter "
      f"(~{N*done/total/1e6:.2f}M row-trees/s)", flush=True)
