"""Decompose per-tree cost: time grow_tree_compact at several num_leaves
and row counts to split fixed-per-split vs O(N)-per-split components.

Usage: python tools/scaling_probe.py [rows]
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from lightgbm_tpu.config import Config  # noqa: E402
from lightgbm_tpu.io.dataset import Dataset  # noqa: E402
from lightgbm_tpu.models.device_learner import DeviceTreeLearner  # noqa: E402

N = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
F = 28
r = np.random.RandomState(17)
x = r.randn(N, F).astype(np.float32)
w = r.randn(F) * (r.rand(F) > 0.4)
y = ((x @ w * 0.3 + r.randn(N)) > 0).astype(np.float64)
g = jax.numpy.asarray((r.rand(N) - 0.5).astype(np.float32))
h = jax.numpy.asarray((0.1 + r.rand(N)).astype(np.float32))

print(f"backend={jax.default_backend()} N={N}", flush=True)


def probe(n_rows, leaves):
    cfg = Config({"objective": "binary", "num_leaves": leaves, "max_bin": 63,
                  "min_data_in_leaf": 20, "verbosity": -1})
    ds = Dataset(x[:n_rows], config=cfg, label=y[:n_rows])
    lrn = DeviceTreeLearner(cfg, ds, strategy="compact")
    gn, hn = g[:n_rows], h[:n_rows]
    t0 = time.time()
    lrn.train(gn, hn)
    compile_s = time.time() - t0
    reps = 3
    t0 = time.time()
    for i in range(reps):
        lrn.train(gn, hn, iter_seed=i + 1)
    dt = (time.time() - t0) / reps
    print(f"N={n_rows:8d} L={leaves:4d}  {dt*1e3:9.1f} ms/tree  "
          f"({dt/max(leaves-1,1)*1e3:7.2f} ms/split)  "
          f"compile+1st {compile_s:.1f}s", flush=True)


# L-scaling at fixed N: intercept = fixed per-tree cost, slope = per-split
for leaves in (2, 15, 63, 255):
    probe(N, leaves)
# N-scaling at fixed L: discriminates latency-fixed per-split overhead
# (flat ms/split) from N-proportional overhead like whole-carry copies
# through the switch/while boundary (ms/split tracking N)
for n_rows in (131072, 262144, 524288):
    if n_rows < N:
        probe(n_rows, 255)
