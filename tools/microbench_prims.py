"""Measure TPU primitive costs that decide the histogram algorithm design:
random gather, argsort, stable-key sort, cumsum streams, one-hot matmul,
column slice. Informs the device learner architecture."""
import sys
import time
import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))

N = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
F = 28
r = np.random.RandomState(0)


def _sync(o):
    # force a real device->host readback
    leaf = jax.tree_util.tree_leaves(o)[0]
    np.asarray(jax.device_get(leaf.ravel()[:1] if hasattr(leaf, 'ravel') else leaf))


def bench(name, fn, *args, iters=20):
    o = fn(*args)
    _sync(o)
    t0 = time.time()
    for _ in range(iters):
        o = fn(*args)
    _sync(o)
    dt = (time.time() - t0) / iters * 1e3
    print(f"{name:42s} {dt:8.3f} ms")
    return dt


g = jnp.asarray(r.randn(N).astype(np.float32))
idx = jnp.asarray(r.permutation(N).astype(np.int32))
codes = jnp.asarray(r.randint(0, 64, (N, F), dtype=np.uint8))
codes_t = jnp.asarray(np.ascontiguousarray(codes.T))
keys = jnp.asarray(r.randint(0, 3, N, dtype=np.int8))
leaf = jnp.asarray(r.randint(0, 255, N, dtype=np.int32))
gh = jnp.asarray(np.stack([r.randn(N), r.randn(N), np.ones(N)], 1).astype(np.float32))

print(f"N={N}")
bench("gather f32 by perm (N)", jax.jit(lambda g, i: jnp.take(g, i)), g, idx)
bench("gather rows (N,F) by perm", jax.jit(lambda c, i: jnp.take(c, i, axis=0)), codes, idx)
bench("argsort int8 keys (N)", jax.jit(lambda k: jnp.argsort(k, stable=True)), keys)
bench("sort f32 (N)", jax.jit(lambda g: jnp.sort(g)), g)
bench("cumsum f32 (N)", jax.jit(lambda g: jnp.cumsum(g)), g)
bench("masked stream hist per-bin VPU (F=1)",
      # the python sum() IS the candidate being measured (unrolled 8-way
      # masked reduction vs one-hot matmul). lint: disable=determinism
      jax.jit(lambda c, g: sum(jnp.sum(jnp.where(c[0] == b, g, 0.)) for b in range(8))),
      codes_t, g)
bench("column slice from (F,N)",
      jax.jit(lambda ct: jax.lax.dynamic_slice_in_dim(ct, 5, 1, 0)[0].astype(jnp.int32)),
      codes_t)
bench("leaf one-hot matmul (N,256)@(N,3)",
      jax.jit(lambda l, gh: jax.lax.dot_general(
          (l[:, None] == jnp.arange(256, dtype=jnp.int32)[None, :]).astype(jnp.bfloat16),
          gh.astype(jnp.bfloat16),
          dimension_numbers=(((0,), (0,)), ((), ())),
          preferred_element_type=jnp.float32)), leaf, gh)

# full one-hot hist (current XLA path) for reference
from lightgbm_tpu.ops.histogram import build_histogram
bench("one-hot hist XLA (N,28,B64) f32",
      jax.jit(lambda c, gh: build_histogram(c, gh, 64, use_pallas=False)), codes, gh)
bench("one-hot hist pallas (N,28,B64)",
      jax.jit(lambda c, gh: build_histogram(c, gh, 64, use_pallas=True)), codes, gh)

# compaction-design primitives
bench("scatter f32 by perm .at[perm].set",
      jax.jit(lambda g, i: jnp.zeros_like(g).at[i].set(g)), g, idx, iters=5)
for W in (4096, 65536, 1048576):
    if W > N:
        continue
    kw = keys[:W]
    bench(f"argsort i8 stable (W={W})",
          jax.jit(lambda k: jnp.argsort(k, stable=True)), kw, iters=10)
    iw = idx[:W]
    bench(f"gather rows + hist bf16-ish (W={W})",
          jax.jit(lambda c, i, gh: build_histogram(
              jnp.take(c, i, axis=0), gh[:len(i)], 64, use_pallas=False)),
          codes, iw, gh, iters=10)

