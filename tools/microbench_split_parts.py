"""Per-split cost decomposition by window class.

The compact growth loop's per-split work at window size W is:
  partition (stable 3-way reorder of the (W, D) packed buffer)
  + smaller-child histogram (half window)
  + the 2-child split-scan chain ((F, B) VPU ops, W-independent)
  + carry bookkeeping.
This times each piece inside ONE jitted fori_loop per (piece, W) so
dispatch overhead is paid once — the numbers are the on-chip costs the
while_loop body pays. Decides the sort-vs-scan partition default and
locates the fixed per-split overhead (docs/DESIGN.md §6a).

Usage: python tools/microbench_split_parts.py [max_window] [reps]
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

MAXW = int(sys.argv[1]) if len(sys.argv) > 1 else 1_048_576
REPS = int(sys.argv[2]) if len(sys.argv) > 2 else 20
F = 28
B = 64
D = 11          # 7 packed u8 code words + 3 gh words + row id

r = np.random.RandomState(0)


def rot(x, i):
    # cache-defeating rotation by a traced offset. jnp.roll(x, traced_i)
    # hits a lowering-cache KeyError in this jax version (_roll_dynamic
    # closed_call missing from cached_primitive_lowerings when the same
    # shape lowers twice in one module); an explicit modulo gather is the
    # same access pattern through the ordinary take path.
    n = x.shape[0]
    return jnp.take(x, (jnp.arange(n) + i) % n, axis=0)


def timed(name, make_body, *args, reps=REPS):
    @jax.jit
    def run(*a):
        def body(i, acc):
            out = make_body(i, a)
            return acc + out.ravel()[0].astype(jnp.float32)
        return jax.lax.fori_loop(0, reps, body, jnp.float32(0.0))

    out = run(*args)
    np.asarray(jax.device_get(out))
    t0 = time.time()
    out = run(*args)
    np.asarray(jax.device_get(out))
    dt = (time.time() - t0) / reps * 1e3
    print(f"  {name:42s} {dt:8.3f} ms", flush=True)
    return dt


def part_sort(i, a):
    win, key3 = a
    order = jnp.argsort(rot(key3, i).astype(jnp.int8), stable=True)
    return jnp.take(win, order, axis=0).astype(jnp.float32)


def part_scan(i, a):
    # full 3-way rank computation (invalid rows ranked after the valid
    # streams) so destinations stay a true permutation under the rolled
    # key pattern; production (device_learner) has invalid rows at the
    # tail and skips the third cumsum — this measures a slight superset
    win, key3 = a
    k = rot(key3, i)
    go_left = k == 0
    valid = k < 2
    il = go_left.astype(jnp.int32)
    ir = (valid & ~go_left).astype(jnp.int32)
    iv = (~valid).astype(jnp.int32)
    n0 = jnp.sum(il)
    n1 = jnp.sum(ir)
    dl = jnp.cumsum(il) - 1
    dr = n0 + jnp.cumsum(ir) - 1
    dv = n0 + n1 + jnp.cumsum(iv) - 1
    dest = jnp.where(go_left, dl, jnp.where(valid, dr, dv))
    return jnp.zeros_like(win).at[dest].set(
        win, unique_indices=True).astype(jnp.float32)


def hist_half(i, a):
    from lightgbm_tpu.ops.histogram import build_histogram
    codes, gh = a
    return build_histogram(codes, rot(gh, i), B,
                           use_pallas=False)


def scan_chain(i, a):
    from lightgbm_tpu.ops import split as split_ops
    hist2, nb, miss, dflt, mask, mono = a
    hist2 = rot(hist2, i)

    def one(hist):
        tot = hist.sum(axis=(0, 1))
        rel, t, use_m1, prefix = split_ops.per_feature_best(
            hist, tot[0], tot[1], tot[2], nb, miss, dflt, mask, mono,
            jnp.float32(-np.inf), jnp.float32(np.inf), None, None,
            num_bins=B, l1=0.0, l2=0.0, max_delta_step=0.0,
            min_data_in_leaf=20, min_sum_hessian=1e-3,
            min_gain_to_split=0.0)
        feat = jnp.argmax(rel).astype(jnp.int32)
        res = split_ops.materialize_split(
            feat, rel, t, use_m1, prefix,
            jnp.float32(-np.inf), jnp.float32(np.inf),
            l1=0.0, l2=0.0, max_delta_step=0.0)
        return res.gain

    return jax.vmap(one)(hist2)


print(f"backend={jax.default_backend()} maxW={MAXW} F={F} B={B} "
      f"D={D} reps={REPS}", flush=True)

# W-independent split-scan chain (2 children vmapped)
hist2 = jnp.asarray(r.rand(2, F, B, 3).astype(np.float32))
nb = jnp.full((F,), B, jnp.int32)
miss = jnp.zeros((F,), jnp.int32)
dflt = jnp.zeros((F,), jnp.int32)
mask = jnp.ones((F,), bool)
mono = jnp.zeros((F,), jnp.int32)
print("split-scan chain (W-independent):")
timed("scan2 per_feature_best+materialize", scan_chain,
      hist2, nb, miss, dflt, mask, mono)

w = 4096
while w <= MAXW:
    print(f"W={w}:")
    win = jnp.asarray(r.randint(0, 2**32, (w, D), dtype=np.uint32))
    key3 = jnp.asarray(
        np.where(np.arange(w) >= int(w * 0.8), 2,
                 (r.rand(w) < 0.4).astype(np.int32)).astype(np.int32))
    timed("partition argsort+take", part_sort, win, key3)
    timed("partition cumsum+scatter", part_scan, win, key3)
    half = (w + 1) // 2
    codes = jnp.asarray(r.randint(0, B, (half, F), dtype=np.uint8))
    gh = jnp.asarray(np.stack(
        [r.randn(half), r.rand(half), np.ones(half)], 1).astype(np.float32))
    timed("hist one-hot (half window)", hist_half, codes, gh)
    w *= 4
