"""Out-of-core streaming A/B: resident vs chunked vs GOSS working-set
training on a synthetic 2M-row binary problem (ISSUE 7 acceptance: the
chunked pipeline within 1.5x of resident throughput while peak device
bytes drop >= 2x).

All three runs use the same chunk growth core so the A/B isolates the
streaming layer itself (resident auto-selection would otherwise flip
strategies with N and confound the comparison): `resident` holds
codes_t + the packed row buffers on device as usual, `chunked` streams
every row from the host wire store per iteration through the
double-buffered H2D pipeline (io/stream.py), and `goss` keeps the
top-gradient working set device-resident while the sampled tail
streams. Peak device bytes use the learners' own `device_data_bytes`
accounting (in-program temporaries common to all modes excluded).

Emits ONE `stream_ab` JSON line, like tools/microbench_rows.py.

Usage: python tools/microbench_stream.py [rows] [trees]
Env: STREAM_ROWS / STREAM_TREES / STREAM_FEATURES / STREAM_LEAVES /
     STREAM_CHUNK_ROWS / STREAM_QUANTIZED=1
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

N = int(sys.argv[1]) if len(sys.argv) > 1 else \
    int(os.environ.get("STREAM_ROWS", 2_000_000))
TREES = int(sys.argv[2]) if len(sys.argv) > 2 else \
    int(os.environ.get("STREAM_TREES", 3))
F = int(os.environ.get("STREAM_FEATURES", 28))
LEAVES = int(os.environ.get("STREAM_LEAVES", 255))
CHUNK_ROWS = int(os.environ.get("STREAM_CHUNK_ROWS", 0))
QUANTIZED = os.environ.get("STREAM_QUANTIZED", "0") == "1"

print(f"backend={jax.default_backend()} N={N} F={F} L={LEAVES} "
      f"trees={TREES} quantized={QUANTIZED}", flush=True)

r = np.random.RandomState(17)
w = r.randn(F) * (r.rand(F) > 0.4)
x = r.randn(N, F).astype(np.float32)
y = ((x @ w * 0.3 + r.randn(N)) > 0).astype(np.float64)
g_np = (r.rand(N) - 0.5).astype(np.float32)
h_np = (0.1 + r.rand(N)).astype(np.float32)

BASE = {"objective": "binary", "num_leaves": LEAVES, "max_bin": 63,
        "min_data_in_leaf": 20, "verbosity": -1}
if QUANTIZED:
    BASE.update(quantized_grad=True, grad_bits=8)


def run(mode):
    pd = dict(BASE)
    if mode != "resident":
        pd["stream_mode"] = mode
        pd["stream_chunk_rows"] = CHUNK_ROWS
        if mode == "goss":
            pd["boosting"] = "goss"
    cfg = Config(pd)
    ds = Dataset(x, config=cfg, label=y)
    lrn = DeviceTreeLearner(cfg, ds,
                            strategy="chunk" if mode == "resident"
                            else None)
    g = jnp.asarray(g_np)
    h = jnp.asarray(h_np)
    if mode == "goss":
        # the GOSS working set pins the top |g*h| rows across trees
        # (in training the booster hands this down every iteration)
        top_k = max(1, int(N * float(BASE.get("top_rate", 0.2))))
        order = np.argsort(-np.abs(g_np * h_np), kind="stable")
        lrn.stream_note_top(np.sort(order[:top_k]).astype(np.int32))
        bag = np.sort(np.concatenate(
            [order[:top_k],
             r.choice(order[top_k:], max(1, int(N * 0.1)),
                      replace=False)])).astype(np.int32)
    else:
        bag = None
    t0 = time.time()
    lrn.train(g, h, bag_indices=bag)
    compile_s = time.time() - t0
    t0 = time.time()
    for i in range(TREES):
        lrn.train(g, h, bag_indices=bag, iter_seed=i + 1)
    dt = (time.time() - t0) / TREES
    acct = lrn.device_data_bytes()
    shard = lrn._shard
    out = {
        "ms_per_tree": round(dt * 1e3, 1),
        "row_trees_per_s": round(N / dt, 1),
        "peak_device_bytes": acct["bytes"],
        "acct_mode": acct["mode"],
        "overlap_fraction": (round(shard.overlap_fraction(), 4)
                             if shard is not None
                             and shard.overlap_fraction() is not None
                             else None),
        "h2d_bytes_per_tree": (int(shard.h2d_bytes // (TREES + 1))
                               if shard is not None else None),
        "compile_s": round(compile_s, 1),
    }
    print(f"{mode:9s} {out['ms_per_tree']:9.1f} ms/tree  "
          f"peak {out['peak_device_bytes']/1e6:8.1f} MB  "
          f"overlap {out['overlap_fraction']}", flush=True)
    del ds, lrn, g, h
    return out


res = {m: run(m) for m in ("resident", "chunked", "goss")}

ratio = (res["chunked"]["ms_per_tree"] / res["resident"]["ms_per_tree"]
         if res["resident"]["ms_per_tree"] > 0 else None)
mem_drop = (res["resident"]["peak_device_bytes"]
            / max(res["chunked"]["peak_device_bytes"], 1))
print(json.dumps({
    "bench": "stream_ab",
    "backend": jax.default_backend(),
    "rows": N, "features": F, "leaves": LEAVES, "trees": TREES,
    "quantized": QUANTIZED,
    "resident": res["resident"],
    "chunked": res["chunked"],
    "goss": res["goss"],
    "chunked_vs_resident_time": round(ratio, 3) if ratio else None,
    "peak_bytes_drop": round(mem_drop, 2),
}))
