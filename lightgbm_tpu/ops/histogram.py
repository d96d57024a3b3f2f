"""Device histogram construction.

Role of the reference's hottest loops — Bin::ConstructHistogram
(reference: src/io/dense_bin.hpp:71-195, 4-way unrolled scalar scatter) and
the OpenCL kernels (src/treelearner/ocl/histogram256.cl, local-memory float
atomics). TPUs have no fast scatter-atomics, so the TPU-native formulation is
a one-hot contraction on the MXU: for a row chunk C,

    hist[f*B+b, k] += sum_n onehot[n, f*B+b] * gh[n, k]

i.e. a (FB, C) x (C, 3) matmul per chunk, accumulated over chunks with
lax.scan. The (gradient, hessian, count) triple rides the tiny K=3 axis;
padding rows carry gh = 0 so buckets can be padded freely.

A fused Pallas kernel (ops/pallas/histogram_kernel.py) implements the same
contract without materializing the one-hot in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas import histogram_kernel as _pallas_hist

# floor of the derived chunk ladder: shapes with F*B >= 4M/floor elements
# resolve to exactly this, keeping the historical behavior bit-identical
_CHUNK_FLOOR = 2048
_CHUNK_CEIL = 32768


def resolve_chunk_size(chunk_size: int, f: int, num_bins: int) -> int:
    """Row-chunk size for the one-hot contraction.

    chunk_size > 0 wins (explicit caller / Config.hist_chunk_size);
    otherwise derived from the contraction shape: the (FB, C) x (C, 3)
    matmul under-fills the MXU when F*B is small, so the chunk grows to
    keep ~2^22 one-hot elements per pass (clamped to [2048, 32768],
    multiple of 256).
    """
    if chunk_size and int(chunk_size) > 0:
        return int(chunk_size)
    c = (1 << 22) // max(int(f) * int(num_bins), 1)
    c = max(_CHUNK_FLOOR, min(_CHUNK_CEIL, c))
    return -(-c // 256) * 256


def _hist_chunk(binned_chunk: jax.Array, gh_chunk: jax.Array, num_bins: int) -> jax.Array:
    """One-hot contraction for one chunk.

    binned_chunk: (C, F) int8/int16 bin codes
    gh_chunk:     (C, 3) f32 (grad, hess, valid-count)
    returns       (F, B, 3) f32 partial histogram
    """
    c, f = binned_chunk.shape
    iota = jnp.arange(num_bins, dtype=jnp.int32)
    onehot = (binned_chunk.astype(jnp.int32)[:, :, None] == iota[None, None, :])
    # (FB, C) @ (C, 3) on the MXU. The one-hot is bf16-exact; gh is split
    # into bf16 hi + lo parts so each product is a fast single-pass bf16
    # matmul while the sum keeps ~f32 fidelity (rel err ~8e-7 vs
    # HIGHEST). Plain DEFAULT would round gradients to
    # bf16, whose absolute error survives sibling subtraction
    # (subtract_histogram) disproportionately for small leaves; HIGHEST
    # costs ~40% more MXU time.
    onehot2d = onehot.reshape(c, f * num_bins).astype(jnp.bfloat16)
    gh_hi = gh_chunk.astype(jnp.bfloat16)
    gh_lo = (gh_chunk - gh_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    dn = (((0,), (0,)), ((), ()))
    hist = (jax.lax.dot_general(onehot2d, gh_hi, dimension_numbers=dn,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(onehot2d, gh_lo, dimension_numbers=dn,
                                  preferred_element_type=jnp.float32))
    return hist.reshape(f, num_bins, 3)


@functools.partial(jax.jit, static_argnames=("num_bins", "chunk_size", "use_pallas"))
def build_histogram(binned_rows: jax.Array, gh: jax.Array, num_bins: int,
                    chunk_size: int = 0, use_pallas: bool = False) -> jax.Array:
    """Full histogram for a padded row window.

    binned_rows: (P, F) gathered bin codes for the leaf's rows (pad rows
                 arbitrary — their gh must be zero).
    gh:          (P, 3) f32 (grad, hess, valid) — valid is 0.0 on pad rows.
    chunk_size:  0 = resolve via Config/env/shape (resolve_chunk_size).
    Returns (F, B, 3) f32: per (feature, bin): [sum_grad, sum_hess, count].
    """
    if use_pallas:
        return _pallas_hist.build_histogram_pallas(binned_rows, gh, num_bins)
    p, f = binned_rows.shape
    chunk_size = resolve_chunk_size(chunk_size, f, num_bins)
    if p <= chunk_size:
        return _hist_chunk(binned_rows, gh, num_bins)
    n_chunks = (p + chunk_size - 1) // chunk_size
    pad = n_chunks * chunk_size - p
    if pad:
        binned_rows = jnp.pad(binned_rows, ((0, pad), (0, 0)))
        gh = jnp.pad(gh, ((0, pad), (0, 0)))
    binned_rows = binned_rows.reshape(n_chunks, chunk_size, f)
    gh = gh.reshape(n_chunks, chunk_size, 3)

    def body(acc, chunk):
        b, g = chunk
        return acc + _hist_chunk(b, g, num_bins), None

    # the carry is seeded from the FIRST chunk (not zeros) so its type
    # carries the data's varying-manual-axes when this runs inside a
    # shard_map region (a replicated zeros carry + varying per-chunk
    # additions fails shard_map's carry type check); outside shard_map
    # it is the same arithmetic with one add saved
    init = _hist_chunk(binned_rows[0], gh[0], num_bins)
    hist, _ = jax.lax.scan(body, init, (binned_rows[1:], gh[1:]))
    return hist


def accumulate_histogram(acc: jax.Array, binned_rows: jax.Array,
                         gh: jax.Array, num_bins: int,
                         use_pallas: bool = False) -> jax.Array:
    """Streamed-accumulation hook: fold one row chunk's histogram into a
    running (F, B, 3) total — the seam the out-of-core pipeline
    (io/stream.py feeding the chunk core's prebuilt-data path) uses to
    build the root histogram chunk-wise. Integer (quantized) totals are
    chunk-grouping-independent (int32 addition is associative); float
    totals depend on grouping only through f32 addition order, which is
    exact whenever the per-chunk sums are exactly representable. The
    accumulator dtype picks the pipeline: int32 routes to the exact
    quantized contraction."""
    if acc.dtype == jnp.int32:
        return acc + build_histogram_quantized(
            binned_rows, gh, num_bins, use_pallas=use_pallas)
    return acc + build_histogram(binned_rows, gh, num_bins,
                                 use_pallas=use_pallas)


@jax.jit
def subtract_histogram(parent: jax.Array, child: jax.Array) -> jax.Array:
    """Sibling histogram by subtraction (reference:
    src/treelearner/feature_histogram.hpp:75-81 FeatureHistogram::Subtract).
    Dtype-preserving: on the quantized path (int32 histograms) the
    subtraction is bit-exact integer arithmetic — no catastrophic
    cancellation for small siblings of large parents."""
    return parent - child


def _hist_chunk_q(binned_chunk: jax.Array, ghq_chunk: jax.Array,
                  num_bins: int) -> jax.Array:
    """Integer one-hot contraction for one chunk.

    binned_chunk: (C, F) int bin codes
    ghq_chunk:    (C, 3) int8/int32 [qg, qh, valid]
    returns       (F, B, 3) int32 EXACT partial histogram

    ONE matmul where the float path needs the bf16 hi/lo pair: the
    one-hot is cast to the operand dtype (i8 rides the MXU's native int8
    path) and the int32 accumulator is exact, so there is no split-
    precision correction pass and no rounding of the per-bin sums.
    """
    c, f = binned_chunk.shape
    iota = jnp.arange(num_bins, dtype=jnp.int32)
    onehot = (binned_chunk.astype(jnp.int32)[:, :, None] == iota[None, None, :])
    onehot2d = onehot.reshape(c, f * num_bins).astype(ghq_chunk.dtype)
    dn = (((0,), (0,)), ((), ()))
    hist = jax.lax.dot_general(onehot2d, ghq_chunk, dimension_numbers=dn,
                               preferred_element_type=jnp.int32)
    return hist.reshape(f, num_bins, 3)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "chunk_size", "use_pallas"))
def build_histogram_quantized(binned_rows: jax.Array, ghq: jax.Array,
                              num_bins: int, chunk_size: int = 0,
                              use_pallas: bool = False) -> jax.Array:
    """Integer histogram for a padded row window (quantized-grad path).

    binned_rows: (P, F) bin codes (pad rows arbitrary — their ghq rows
                 must be zero, i.e. valid == 0).
    ghq:         (P, 3) int8/int32 [qg, qh, valid] from ops/quantize.
    Returns (F, B, 3) int32 EXACT [sum_qg, sum_qh, count]: chunk order
    cannot change the result (integer addition is associative), unlike
    the float path where the scan order perturbs low bits.
    """
    if use_pallas:
        return _pallas_hist.build_histogram_pallas_quantized(
            binned_rows, ghq, num_bins)
    p, f = binned_rows.shape
    chunk_size = resolve_chunk_size(chunk_size, f, num_bins)
    if p <= chunk_size:
        return _hist_chunk_q(binned_rows, ghq, num_bins)
    n_chunks = (p + chunk_size - 1) // chunk_size
    pad = n_chunks * chunk_size - p
    if pad:
        binned_rows = jnp.pad(binned_rows, ((0, pad), (0, 0)))
        ghq = jnp.pad(ghq, ((0, pad), (0, 0)))
    binned_rows = binned_rows.reshape(n_chunks, chunk_size, f)
    ghq = ghq.reshape(n_chunks, chunk_size, 3)

    def body(acc, chunk):
        b, g = chunk
        return acc + _hist_chunk_q(b, g, num_bins), None

    # carry seeded from the FIRST chunk for the same shard_map varying-
    # manual-axes reason as the float path above
    init = _hist_chunk_q(binned_rows[0], ghq[0], num_bins)
    hist, _ = jax.lax.scan(body, init, (binned_rows[1:], ghq[1:]))
    return hist


@functools.partial(jax.jit, static_argnames=("num_bins", "bucket",
                                             "grad_bits", "chunk_size"))
def gather_and_build_quantized(binned: jax.Array, indices_buf: jax.Array,
                               gh_packed: jax.Array, begin: jax.Array,
                               count: jax.Array, num_bins: int, bucket: int,
                               grad_bits: int,
                               chunk_size: int = 0) -> jax.Array:
    """Quantized analog of gather_and_build: gather the leaf's packed
    (qg|qh) int32 rows and build the exact integer histogram."""
    from . import quantize as quant_ops
    window = jax.lax.dynamic_slice(indices_buf, (begin,), (bucket,))
    valid = (jnp.arange(bucket, dtype=jnp.int32) < count)
    rows = jnp.take(binned, window, axis=0)
    ghq = quant_ops.gh_operand(jnp.take(gh_packed, window), valid, grad_bits)
    return build_histogram_quantized(rows, ghq, num_bins,
                                     chunk_size=chunk_size)


@functools.partial(jax.jit, static_argnames=("num_bins", "bucket",
                                             "chunk_size"))
def gather_and_build(binned: jax.Array, indices_buf: jax.Array, grad: jax.Array,
                     hess: jax.Array, begin: jax.Array, count: jax.Array,
                     num_bins: int, bucket: int,
                     chunk_size: int = 0) -> jax.Array:
    """Gather a leaf's rows from the partition buffer and build its histogram.

    binned:      (N, F) full binned matrix
    indices_buf: (N + max_bucket,) int32 partition permutation (padded tail)
    begin/count: scalars (leaf slice in the partition buffer)
    bucket:      static padded window size >= count
    """
    window = jax.lax.dynamic_slice(indices_buf, (begin,), (bucket,))
    valid = (jnp.arange(bucket, dtype=jnp.int32) < count)
    rows = jnp.take(binned, window, axis=0)
    g = jnp.take(grad, window) * valid
    h = jnp.take(hess, window) * valid
    gh = jnp.stack([g, h, valid.astype(jnp.float32)], axis=1)
    return build_histogram(rows, gh, num_bins, chunk_size=chunk_size)
