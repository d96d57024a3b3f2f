"""Device histogram construction.

Role of the reference's hottest loops — Bin::ConstructHistogram
(reference: src/io/dense_bin.hpp:71-195, 4-way unrolled scalar scatter) and
the OpenCL kernels (src/treelearner/ocl/histogram256.cl, local-memory float
atomics). TPUs have no fast scatter-atomics, so the TPU-native formulation is
a one-hot contraction on the MXU. The one-hot is FACTORED: a bin code is
`hi * LO_BINS + lo`, and

    hist[f, hi*LO_BINS + lo, k] = sum_n [hi_n,f == hi] [lo_n,f == lo] gh[n, k]

so a row chunk C is, per group of G = 128 / LO_BINS features, ONE product
(C, K*G*b)^T x (C, 128): the right side is the group's `lo` one-hot (a
full MXU tile wide), the left side its `hi` one-hot times the K operand
columns, and the histogram is the f == f' diagonal blocks of the result.
A row builds F * (LO_BINS + b*K) plane elements, not F * num_bins, and no
product is 3 columns wide. The raw products are accumulated over chunks
with lax.scan and the diagonal is taken once after it. The (gradient,
hessian, count) triple rides the K axis; padding rows carry gh = 0 so
buckets can be padded freely.

A fused Pallas kernel (ops/pallas/histogram_kernel.py) implements the same
contract with the unfactored one-hot in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas import histogram_kernel as _pallas_hist

# floor and ceiling of the derived chunk ladder (rows a scan step)
_CHUNK_FLOOR = 2048
_CHUNK_CEIL = 32768
# plane elements a scan step may build: 4,096 rows at 67 x 256 and 8,192
# at 28 x 256 are each cell's fastest, and at 8,192 x 67 x 256 the step's
# planes leave the chip's fast memory and a row costs 3.4x (PERF.md §6)
_CHUNK_PLANE_ELEMS = 3 << 23

_MXU_COLS = 128
# bins the low part of a code counts (code = hi * LO_BINS + lo), so a
# product's right side holds _MXU_COLS / LO_BINS features. Chosen on the
# chip at both cells' widths (PERF.md §6, PR 32), as SCATTER_TILE_ROWS was
LO_BINS = 64


def _factors(f: int, num_bins: int):
    """(groups, features a group, hi values) of the factored one-hot."""
    per_group = _MXU_COLS // LO_BINS
    return -(-f // per_group), per_group, -(-num_bins // LO_BINS)


def resolve_chunk_size(chunk_size: int, f: int, num_bins: int) -> int:
    """Row-chunk size for the one-hot contraction.

    chunk_size > 0 wins (explicit caller / Config.hist_chunk_size);
    otherwise derived from the width of the two planes a row builds,
    F * (LO_BINS + 6 * hi values): the longest power of two (the window
    ladder's rungs are powers of two, so none is padded) whose planes
    stay within _CHUNK_PLANE_ELEMS, so that a scan step's products are
    long against its fixed costs (the accumulator read and written once
    a step), clamped to [2048, 32768].
    """
    if chunk_size and int(chunk_size) > 0:
        return int(chunk_size)
    groups, per_group, hi_bins = _factors(f, num_bins)
    width = groups * per_group * (LO_BINS + 6 * hi_bins)
    c = max(_CHUNK_PLANE_ELEMS // width, 1)
    c = 1 << (c.bit_length() - 1)
    return max(_CHUNK_FLOOR, min(_CHUNK_CEIL, c))


def _factored_product(binned_chunk: jax.Array, operand: jax.Array,
                      num_bins: int, acc_dtype) -> jax.Array:
    """The factored one-hot contraction of one chunk, un-rearranged.

    binned_chunk: (C, F) int bin codes; a code outside [0, num_bins)
                  lands in no kept bin
    operand:      (C, K) columns to sum by bin, in the product's type
    returns       (groups, K*G*b, G*LO_BINS) acc_dtype: per feature group
                  the product of every (column, feature, hi) with every
                  (feature', lo); `_unfactor` keeps feature == feature'
    """
    c, f = binned_chunk.shape
    k = operand.shape[1]
    groups, per_group, hi_bins = _factors(f, num_bins)
    codes = binned_chunk.astype(jnp.int32)
    if groups * per_group != f:
        codes = jnp.pad(codes, ((0, 0), (0, groups * per_group - f)))
    codes = codes.reshape(c, groups, per_group)
    # arithmetic shift: a negative code keeps a negative hi and matches none
    hi = codes >> (LO_BINS.bit_length() - 1)
    lo = codes & (LO_BINS - 1)
    lo_hot = (lo[..., None] == jnp.arange(LO_BINS, dtype=jnp.int32)
              ).astype(operand.dtype)
    hi_hot = hi[..., None] == jnp.arange(hi_bins, dtype=jnp.int32)
    # the operand's columns outermost: with K innermost the TPU compiler
    # writes the hi plane out once more, broadcast over K (PERF.md §6, PR 32)
    left = jnp.where(hi_hot.reshape(c, groups, 1, per_group * hi_bins),
                     operand[:, None, :, None], jnp.zeros((), operand.dtype))
    return jnp.einsum(
        "cgm,cgn->gmn", left.reshape(c, groups, k * per_group * hi_bins),
        lo_hot.reshape(c, groups, per_group * LO_BINS),
        preferred_element_type=acc_dtype)


def _unfactor(raw: jax.Array, f: int, num_bins: int) -> jax.Array:
    """(F, num_bins, K) histogram out of `_factored_product`'s sum: the
    feature == feature' diagonal blocks, bins back in code order."""
    groups, per_group, hi_bins = _factors(f, num_bins)
    k = raw.shape[1] // (per_group * hi_bins)
    blocks = raw.reshape(groups, k, per_group, hi_bins, per_group, LO_BINS)
    diag = jnp.diagonal(blocks, axis1=2, axis2=4)     # (groups, K, b, a, G)
    hist = diag.transpose(0, 4, 2, 3, 1).reshape(
        groups * per_group, hi_bins * LO_BINS, k)
    return hist[:f, :num_bins]


def _hist_chunk(binned_chunk: jax.Array, gh_chunk: jax.Array, num_bins: int) -> jax.Array:
    """Raw factored product of one float chunk.

    binned_chunk: (C, F) int8/uint8/int16 bin codes
    gh_chunk:     (C, 3) f32 (grad, hess, valid-count)
    returns       `_factored_product`'s f32 result over six columns: the
                  bf16 head of gh beside its bf16 remainder
    """
    # gh is split into a bf16 head + remainder so the product is a fast
    # single-pass bf16 matmul while the sum keeps ~f32 fidelity (rel err
    # ~8e-7 vs HIGHEST). Plain DEFAULT would round gradients to bf16,
    # whose absolute error survives sibling subtraction
    # (subtract_histogram) disproportionately for small leaves. The head
    # is a reduce_precision, which no flag lets the compiler fold away:
    # as float32(bfloat16(gh)) it is gh itself under XLA's default
    # --xla_allow_excess_precision, and the remainder 0 (PERF.md §7).
    head = jax.lax.reduce_precision(gh_chunk, exponent_bits=8,
                                    mantissa_bits=7)
    operand = jnp.concatenate([head, gh_chunk - head],
                              axis=1).astype(jnp.bfloat16)
    return _factored_product(binned_chunk, operand, num_bins, jnp.float32)


def _hist_chunk_q(binned_chunk: jax.Array, ghq_chunk: jax.Array,
                  num_bins: int) -> jax.Array:
    """Raw factored product of one integer chunk.

    binned_chunk: (C, F) int bin codes
    ghq_chunk:    (C, 3) int8/int32 [qg, qh, valid]
    returns       `_factored_product`'s int32 result, EXACT: the planes
                  are in the operand's type (i8 rides the MXU's native
                  int8 path) and the int32 accumulator does not round, so
                  there is no head and remainder.
    """
    return _factored_product(binned_chunk, ghq_chunk, num_bins, jnp.int32)


def _sum_chunks(chunk_product, binned_rows: jax.Array, gh: jax.Array,
                num_bins: int, chunk_size: int) -> jax.Array:
    """`chunk_product` summed over the row chunks of a padded window,
    then un-rearranged: (F, num_bins, K)."""
    p, f = binned_rows.shape
    chunk_size = resolve_chunk_size(chunk_size, f, num_bins)
    if p <= chunk_size:
        return _unfactor(chunk_product(binned_rows, gh, num_bins), f,
                         num_bins)
    n_chunks = (p + chunk_size - 1) // chunk_size
    pad = n_chunks * chunk_size - p
    if pad:
        binned_rows = jnp.pad(binned_rows, ((0, pad), (0, 0)))
        gh = jnp.pad(gh, ((0, pad), (0, 0)))
    binned_rows = binned_rows.reshape(n_chunks, chunk_size, f)
    gh = gh.reshape(n_chunks, chunk_size, gh.shape[1])

    def body(acc, chunk):
        b, g = chunk
        return acc + chunk_product(b, g, num_bins), None

    # the carry is seeded from the FIRST chunk (not zeros) so its type
    # carries the data's varying-manual-axes when this runs inside a
    # shard_map region (a replicated zeros carry + varying per-chunk
    # additions fails shard_map's carry type check); outside shard_map
    # it is the same arithmetic with one add saved
    init = chunk_product(binned_rows[0], gh[0], num_bins)
    raw, _ = jax.lax.scan(body, init, (binned_rows[1:], gh[1:]))
    return _unfactor(raw, f, num_bins)


def window_chunk(x: jax.Array, row0, size: int, ragged: bool) -> jax.Array:
    """Rows [row0, row0 + size) of `x`, each at its own place, for a
    traced row0. `ragged` (static) says the slice may overrun `x`: the
    last chunk of a window that is no multiple of the chunk. It is then
    read from further up and rolled back, so that the rows `x` has keep
    their places in the chunk (and its sums their order) and the places
    past the end of `x` hold rows no range includes."""
    if not ragged:
        return jax.lax.dynamic_slice_in_dim(x, row0, size)
    at = jnp.minimum(row0, x.shape[0] - size)
    return jnp.roll(jax.lax.dynamic_slice_in_dim(x, at, size), at - row0,
                    axis=0)


def build_histogram_range(load, rows: int, begin, count, num_features: int,
                          num_bins: int, quantized: bool = False,
                          chunk_size: int = 0) -> jax.Array:
    """`build_histogram` (or, `quantized`, `build_histogram_quantized`)
    of the rows [begin, begin + count) of a window of `rows` rows
    (traced; the range lies inside the window), for the work of those
    rows and not of the window: `_sum_chunks` over the chunks that meet
    the range alone. The other chunks' rows carry gh = 0 and add exact
    zeros; the chunk grid is `_sum_chunks`' own, multiples of the chunk
    from the window's first row, and the chunks that are summed are
    summed in its order, so the sum is the whole window's, bit for bit.
    `load(row0, size, keep)` gives the chunk's (codes, operand) from its
    first row, its (static) length and the rows of it the range holds,
    the operand zero on every other row; the window is never held whole.
    The carry is seeded from the first summed chunk, as `_sum_chunks`
    seeds its own, and an empty range sums that one chunk of zeros. The
    trip count differs from shard to shard under shard_map, so no
    collective may enter `load`."""
    chunk_product = _hist_chunk_q if quantized else _hist_chunk
    chunk = resolve_chunk_size(chunk_size, num_features, num_bins)
    begin = jnp.asarray(begin, jnp.int32)
    end = begin + jnp.asarray(count, jnp.int32)

    def product(row0, size):
        j = row0 + jnp.arange(size, dtype=jnp.int32)
        return chunk_product(*load(row0, size, (j >= begin) & (j < end)),
                             num_bins)

    if rows <= chunk:
        raw = product(jnp.int32(0), rows)
    else:
        n_chunks = -(-rows // chunk)
        first = jnp.minimum(begin // chunk, n_chunks - 1)
        stop = jnp.minimum((end + chunk - 1) // chunk, n_chunks)
        raw = jax.lax.fori_loop(
            first + 1, stop,
            lambda i, acc: acc + product(i * chunk, chunk),
            product(first * chunk, chunk))
    hist = _unfactor(raw, num_features, num_bins)
    return hist if quantized else hist[..., :3] + hist[..., 3:]


def rows_loader(binned_rows: jax.Array, gh: jax.Array):
    """`build_histogram_range`'s `load` for a window held as arrays:
    (P, F) codes and a (P, K) operand."""
    def load(row0, size, keep):
        ragged = binned_rows.shape[0] % size != 0
        return (window_chunk(binned_rows, row0, size, ragged),
                jnp.where(keep[:, None],
                          window_chunk(gh, row0, size, ragged),
                          jnp.zeros((), gh.dtype)))
    return load


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
    hist = _sum_chunks(_hist_chunk, binned_rows, gh, num_bins, chunk_size)
    return hist[..., :3] + hist[..., 3:]


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
    return _sum_chunks(_hist_chunk_q, binned_rows, ghq, num_bins, chunk_size)


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
