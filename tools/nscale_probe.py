"""Locate the superlinear term: bare grow cost at L=255 vs N.

Round-5 data: 1M x 255 trains at 354 ms/tree (bare grow) and the bench
sustains 1.30M row-trees/s, but 10.5M x 255 measured 12.8 s/tree —
~4x worse than linear scaling predicts. This probes N in {1, 2, 4, 8,
10.5}M at L=255 so the knee (HBM pressure? ladder copy cost? spills?)
shows up as a slope change. Windows: peak device memory is ~2.2x the
packed buffer (N+wmax rows x (CW+4) u32 words, double-buffered through
the while carry) + codes; at 10.5M that is ~2 GB of a 16 GB part, so a
knee well below that points at copies/latency, not capacity.

NSCALE_STREAM=chunked|goss runs the same probe through the out-of-core
pipeline (io/stream.py) so resident vs streamed knees are A/B-able.
Each N emits one machine-readable JSON line:

    {"probe": "nscale", "rows": N, "row_trees_per_s": ...,
     "mode": "resident"|"streamed", "peak_device_bytes": ...}

Usage: python tools/nscale_probe.py [max_rows] [reps]
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.config import Config  # noqa: E402
from lightgbm_tpu.io.dataset import Dataset  # noqa: E402
from lightgbm_tpu.models.device_learner import DeviceTreeLearner  # noqa: E402

MAXN = int(sys.argv[1]) if len(sys.argv) > 1 else 10_500_000
REPS = int(sys.argv[2]) if len(sys.argv) > 2 else 3
F = 28
STREAM = os.environ.get("NSCALE_STREAM", "off")

print(f"backend={jax.default_backend()} maxN={MAXN} stream={STREAM}",
      flush=True)

r = np.random.RandomState(17)
w = r.randn(F) * (r.rand(F) > 0.4)

for n in (1_000_000, 2_000_000, 4_000_000, 8_000_000, 10_500_000):
    if n > MAXN:
        break
    x = r.randn(n, F).astype(np.float32)
    y = ((x @ w * 0.3 + r.randn(n)) > 0).astype(np.float64)
    pd = {"objective": "binary", "num_leaves": 255, "max_bin": 63,
          "min_data_in_leaf": 20, "verbosity": -1}
    if STREAM != "off":
        pd["stream_mode"] = STREAM
        pd["stream_chunk_rows"] = int(
            os.environ.get("NSCALE_CHUNK_ROWS", 0))
    cfg = Config(pd)
    ds = Dataset(x, config=cfg, label=y)
    del x
    lrn = DeviceTreeLearner(cfg, ds)
    g = jnp.asarray((r.rand(n) - 0.5).astype(np.float32))
    h = jnp.asarray((0.1 + r.rand(n)).astype(np.float32))
    t0 = time.time()
    lrn.train(g, h)
    compile_s = time.time() - t0
    t0 = time.time()
    for i in range(REPS):
        lrn.train(g, h, iter_seed=i + 1)
    dt = (time.time() - t0) / REPS
    print(f"N={n:9d} L=255 part={lrn._partition_mode}  "
          f"{dt*1e3:9.1f} ms/tree  ({dt/254*1e3:6.2f} ms/split, "
          f"{n/dt/1e6:6.2f}M row-trees/s)  compile+1st {compile_s:.1f}s",
          flush=True)
    acct = lrn.device_data_bytes()
    print(json.dumps({
        "probe": "nscale",
        "rows": n,
        "row_trees_per_s": round(n / dt, 1),
        "mode": acct["mode"],
        "peak_device_bytes": acct["bytes"],
        "ms_per_tree": round(dt * 1e3, 1),
        "compile_s": round(compile_s, 1),
    }), flush=True)
    del ds, lrn, g, h
