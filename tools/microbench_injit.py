"""True on-device per-op costs: repeat each op K times inside ONE jitted
fori_loop, so dispatch overhead is paid once. This is what decides
the per-split cost model of the device tree learner (the while_loop body in
models/device_learner.py runs these exact primitives back to back).

Usage: python tools/microbench_injit.py [rows] [reps]
"""
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

N = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
REPS = int(sys.argv[2]) if len(sys.argv) > 2 else 20
F = 28
B = 64

r = np.random.RandomState(0)
codes = jnp.asarray(r.randint(0, B, (N, F), dtype=np.uint8))
codes_t = jnp.asarray(np.ascontiguousarray(np.asarray(codes).T))
gh = jnp.asarray(np.stack(
    [r.randn(N), r.rand(N), np.ones(N)], 1).astype(np.float32))
idx = jnp.asarray(r.permutation(N).astype(np.int32))
keys = jnp.asarray(r.randint(0, 3, N, dtype=np.int8))
g1 = jnp.asarray(r.randn(N).astype(np.float32))


def timed(name, make_body, *args, reps=REPS):
    """make_body(i, args) -> array whose first element folds into the carry
    (prevents DCE); the op must depend on the carry via `i` where possible."""
    @jax.jit
    def run(*a):
        def body(i, acc):
            out = make_body(i, a)
            return acc + out.ravel()[0].astype(jnp.float32)
        return jax.lax.fori_loop(0, reps, body, jnp.float32(0.0))

    out = run(*args)          # compile + warm
    np.asarray(jax.device_get(out))
    t0 = time.time()
    out = run(*args)
    np.asarray(jax.device_get(out))
    dt = (time.time() - t0) / reps * 1e3
    print(f"{name:46s} {dt:8.3f} ms")
    return dt


from lightgbm_tpu.ops.histogram import build_histogram  # noqa: E402
from lightgbm_tpu.ops.pallas.histogram_kernel import (  # noqa: E402
    build_histogram_pallas_t)

print(f"backend={jax.default_backend()} N={N} F={F} B={B} reps={REPS}")

timed("gather rows (N,F) by perm", lambda i, a: jnp.take(
    a[0], jnp.roll(a[1], i), axis=0).astype(jnp.float32), codes, idx)
timed("argsort int8 stable (N)", lambda i, a: jnp.argsort(
    jnp.roll(a[0], i), stable=True).astype(jnp.float32), keys)
timed("cumsum int32 (N)", lambda i, a: jnp.cumsum(
    jnp.roll(a[0], i).astype(jnp.int32)).astype(jnp.float32), keys)
timed("scatter int32 .at[perm].set (N)", lambda i, a: jnp.zeros(
    N, jnp.int32).at[jnp.roll(a[0], i)].set(a[0]).astype(jnp.float32),
    idx)
timed("hist XLA one-hot (N,28,B64)", lambda i, a: build_histogram(
    a[0], jnp.roll(a[1], i, axis=0), B, use_pallas=False), codes, gh)
for cr in (1024, 4096, 8192):
    timed(f"hist pallas chunk={cr} (N,28,B64)",
          lambda i, a, cr=cr: build_histogram_pallas_t(
              a[0], jnp.roll(a[1], i, axis=0), B, chunk_rows=cr),
          codes_t, gh)
