"""Fused per-split device program.

One split of leaf-wise growth = partition + child histogram + sibling
subtraction + two split scans. The reference runs these as separate host
phases (serial_tree_learner.cpp:400-605); a GPU pays a kernel launch per
phase, and a TPU pays a host round-trip. Fusing them into a single
jitted program leaves exactly ONE dispatch and ONE small host fetch
(left_count + two winner tuples) per split — the histograms stay on device
for the children's future splits.

The left child's histogram is built fresh from the parent window (rows not
going left contribute zero weight); the right child's comes from parent
subtraction (reference FeatureHistogram::Subtract). Numerical and
categorical partition decisions are both evaluated and selected by a scalar
flag — no control flow divergence under jit.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import quantize as quant_ops
from . import split as split_ops
from .histogram import build_histogram, build_histogram_quantized
from .partition import decide_left


class FusedStepOut(NamedTuple):
    indices_buf: jax.Array
    left_count: jax.Array
    left_hist: jax.Array
    right_hist: jax.Array
    left_res: split_ops.SplitResult
    right_res: split_ops.SplitResult


def _scan(hist, sg, sh, cnt, meta, min_c, max_c, scan_kwargs, cost=None):
    (f_numbins, f_missing, f_default, feature_mask, monotone, penalty) = meta
    return split_ops.find_best_split.__wrapped__(
        hist, sg, sh, cnt, f_numbins, f_missing, f_default, feature_mask,
        monotone, min_c, max_c, penalty, cost, **scan_kwargs)


def _route_and_partition(indices_buf, binned, iparams, cat_bitset,
                         *, bucket):
    """The ONE copy of the per-split routing + stable partition shared
    by the float and quantized fused steps (any drift would silently
    mis-route one path). Returns (begin, window, rows, valid, go_left,
    new_buf, left_count)."""
    begin, count, feature, threshold = (iparams[0], iparams[1], iparams[2],
                                        iparams[3])
    default_left = iparams[4] > 0
    missing_type = iparams[5]
    default_bin = iparams[6]
    numbins_f = iparams[7]
    is_categorical = iparams[8] > 0
    window = jax.lax.dynamic_slice(indices_buf, (begin,), (bucket,))
    pos = jnp.arange(bucket, dtype=jnp.int32)
    valid = pos < count
    rows = jnp.take(binned, window, axis=0)           # (bucket, F)

    fbins = jnp.take_along_axis(
        rows, jnp.full((bucket, 1), feature, jnp.int32), axis=1)[:, 0]
    fbins = fbins.astype(jnp.int32)
    num_left = decide_left(fbins, threshold, default_left, missing_type,
                           default_bin, numbins_f)
    word = cat_bitset[jnp.clip(fbins // 32, 0, cat_bitset.shape[0] - 1)]
    cat_left = (((word >> (fbins % 32)) & 1) == 1) \
        & (fbins // 32 < cat_bitset.shape[0])
    go_left = jnp.where(is_categorical, cat_left, num_left)

    key = jnp.where(valid, jnp.where(go_left, 0, 1), 2).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    new_window = window[order]
    left_count = jnp.sum((key == 0).astype(jnp.int32))
    new_buf = jax.lax.dynamic_update_slice(indices_buf, new_window, (begin,))
    return begin, window, rows, valid, go_left, new_buf, left_count


@functools.partial(
    jax.jit,
    static_argnames=("bucket", "num_bins", "hist_chunk", "use_pallas"),
    donate_argnames=("indices_buf",))
def fused_split_step(
    indices_buf: jax.Array,      # (N + max_bucket,) partition permutation
    binned: jax.Array,           # (N, F)
    grad: jax.Array, hess: jax.Array,
    iparams: jax.Array,          # (15,) int32: [begin, count, feature,
                                 #  threshold, default_left, missing_type,
                                 #  default_bin, numbins_f(split feature),
                                 #  is_categorical, bitset words 0..5]
    cat_bitset: jax.Array,       # (8,) int32 bitset words
    fparams: jax.Array,          # (10,) f32: [lsum_g, lsum_h, lcnt,
                                 #  rsum_g, rsum_h, rcnt, lmin, lmax,
                                 #  rmin, rmax]
    parent_hist: jax.Array,                       # (F, B, 3)
    feature_meta,                 # tuple of (F,) arrays + mask + penalty
    child_costs=None,             # (2, F) CEGB costs for (left, right)
    *,
    bucket: int, num_bins: int,
    l1: float, l2: float, max_delta_step: float,
    min_data_in_leaf: int, min_sum_hessian: float, min_gain_to_split: float,
    hist_chunk: int = 0, use_pallas: bool = False,
) -> FusedStepOut:
    left_sums = fparams[0:3]
    right_sums = fparams[3:6]
    lmin, lmax, rmin, rmax = fparams[6], fparams[7], fparams[8], fparams[9]
    (begin, window, rows, valid, go_left, new_buf,
     left_count) = _route_and_partition(indices_buf, binned, iparams,
                                        cat_bitset, bucket=bucket)

    # left-child histogram from the (already gathered) parent rows
    w = (valid & go_left)
    g = jnp.take(grad, window) * w
    h = jnp.take(hess, window) * w
    gh = jnp.stack([g, h, w.astype(jnp.float32)], axis=1)
    left_hist = build_histogram(rows, gh, num_bins, chunk_size=hist_chunk,
                                use_pallas=use_pallas)
    right_hist = parent_hist - left_hist

    scan_kwargs = dict(
        num_bins=num_bins, l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf, min_sum_hessian=min_sum_hessian,
        min_gain_to_split=min_gain_to_split)
    lcost = child_costs[0] if child_costs is not None else None
    rcost = child_costs[1] if child_costs is not None else None
    left_res = _scan(left_hist, left_sums[0], left_sums[1], left_sums[2],
                     feature_meta, lmin, lmax, scan_kwargs, lcost)
    right_res = _scan(right_hist, right_sums[0], right_sums[1], right_sums[2],
                      feature_meta, rmin, rmax, scan_kwargs, rcost)
    return FusedStepOut(new_buf, left_count, left_hist, right_hist,
                        left_res, right_res)


@functools.partial(
    jax.jit,
    static_argnames=("bucket", "num_bins", "hist_chunk", "use_pallas"))
def fused_root_step(
    indices_buf: jax.Array, binned: jax.Array,
    grad: jax.Array, hess: jax.Array, count: jax.Array,
    feature_meta, root_cost=None,
    *, bucket: int, num_bins: int,
    l1: float, l2: float, max_delta_step: float,
    min_data_in_leaf: int, min_sum_hessian: float, min_gain_to_split: float,
    hist_chunk: int = 0, use_pallas: bool = False,
):
    """Root histogram + scan; returns (hist, totals(3,), SplitResult)."""
    window = jax.lax.dynamic_slice(indices_buf, (0,), (bucket,))
    valid = jnp.arange(bucket, dtype=jnp.int32) < count
    rows = jnp.take(binned, window, axis=0)
    g = jnp.take(grad, window) * valid
    h = jnp.take(hess, window) * valid
    gh = jnp.stack([g, h, valid.astype(jnp.float32)], axis=1)
    hist = build_histogram(rows, gh, num_bins, chunk_size=hist_chunk,
                           use_pallas=use_pallas)
    totals = hist[0].sum(axis=0)
    scan_kwargs = dict(
        num_bins=num_bins, l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf, min_sum_hessian=min_sum_hessian,
        min_gain_to_split=min_gain_to_split)
    res = _scan(hist, totals[0], totals[1], totals[2], feature_meta,
                jnp.float32(-jnp.inf), jnp.float32(jnp.inf), scan_kwargs,
                root_cost)
    return hist, totals, res


# ---------------------------------------------------------------------------
# Quantized-gradient fused steps: the same one-dispatch-per-split contract,
# but (grad, hess) arrive pre-discretized as ONE packed int32 lane per row
# (ops/quantize.py), histograms build with a single integer one-hot
# contraction and live in the pool as EXACT int32 — sibling subtraction is
# bit-exact integer arithmetic — and the split scans rescale the leaf's
# sums back to f32 with the iteration's (g_scale, h_scale) before gain
# computation. The jit caches key on grad_bits (the hist operand dtype).
# ---------------------------------------------------------------------------


def _dequant_scan(hist_q, scales, sg, sh, cnt, meta, min_c, max_c,
                  scan_kwargs, cost=None):
    hist = quant_ops.dequantize_histogram(hist_q, scales[0], scales[1])
    return _scan(hist, sg, sh, cnt, meta, min_c, max_c, scan_kwargs, cost)


@functools.partial(
    jax.jit,
    static_argnames=("bucket", "num_bins", "grad_bits", "hist_chunk",
                     "use_pallas"),
    donate_argnames=("indices_buf",))
def fused_split_step_q(
    indices_buf: jax.Array,
    binned: jax.Array,
    gh_packed: jax.Array,        # (N,) int32 packed (qg << 16 | qh)
    iparams: jax.Array,
    cat_bitset: jax.Array,
    fparams: jax.Array,
    parent_hist: jax.Array,      # (F, B, 3) int32 EXACT parent histogram
    scales: jax.Array,           # (2,) f32 [g_scale, h_scale]
    feature_meta,
    child_costs=None,
    *,
    bucket: int, num_bins: int, grad_bits: int,
    l1: float, l2: float, max_delta_step: float,
    min_data_in_leaf: int, min_sum_hessian: float, min_gain_to_split: float,
    hist_chunk: int = 0, use_pallas: bool = False,
) -> FusedStepOut:
    left_sums = fparams[0:3]
    right_sums = fparams[3:6]
    lmin, lmax, rmin, rmax = fparams[6], fparams[7], fparams[8], fparams[9]
    (begin, window, rows, valid, go_left, new_buf,
     left_count) = _route_and_partition(indices_buf, binned, iparams,
                                        cat_bitset, bucket=bucket)

    w = (valid & go_left)
    ghq = quant_ops.gh_operand(jnp.take(gh_packed, window), w, grad_bits)
    left_hist = build_histogram_quantized(rows, ghq, num_bins,
                                          chunk_size=hist_chunk,
                                          use_pallas=use_pallas)
    # bit-exact integer sibling subtraction (FeatureHistogram::Subtract):
    # a 10-row child of a 1M-row parent loses NOTHING here, where the f32
    # path's subtraction leaves ~(parent magnitude * 1e-7) of noise
    right_hist = parent_hist - left_hist

    scan_kwargs = dict(
        num_bins=num_bins, l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf, min_sum_hessian=min_sum_hessian,
        min_gain_to_split=min_gain_to_split)
    lcost = child_costs[0] if child_costs is not None else None
    rcost = child_costs[1] if child_costs is not None else None
    left_res = _dequant_scan(left_hist, scales, left_sums[0], left_sums[1],
                             left_sums[2], feature_meta, lmin, lmax,
                             scan_kwargs, lcost)
    right_res = _dequant_scan(right_hist, scales, right_sums[0],
                              right_sums[1], right_sums[2], feature_meta,
                              rmin, rmax, scan_kwargs, rcost)
    return FusedStepOut(new_buf, left_count, left_hist, right_hist,
                        left_res, right_res)


@functools.partial(
    jax.jit,
    static_argnames=("bucket", "num_bins", "grad_bits", "hist_chunk",
                     "use_pallas"))
def fused_root_step_q(
    indices_buf: jax.Array, binned: jax.Array,
    gh_packed: jax.Array, scales: jax.Array, count: jax.Array,
    feature_meta, root_cost=None,
    *, bucket: int, num_bins: int, grad_bits: int,
    l1: float, l2: float, max_delta_step: float,
    min_data_in_leaf: int, min_sum_hessian: float, min_gain_to_split: float,
    hist_chunk: int = 0, use_pallas: bool = False,
):
    """Quantized root: integer histogram + dequantized scan; returns
    (hist_q int32, f32 totals(3,), SplitResult)."""
    window = jax.lax.dynamic_slice(indices_buf, (0,), (bucket,))
    valid = jnp.arange(bucket, dtype=jnp.int32) < count
    rows = jnp.take(binned, window, axis=0)
    ghq = quant_ops.gh_operand(jnp.take(gh_packed, window), valid, grad_bits)
    hist_q = build_histogram_quantized(rows, ghq, num_bins,
                                       chunk_size=hist_chunk,
                                       use_pallas=use_pallas)
    # leaf totals in f32 come from the SAME dequantized sums the scans
    # see, so prefix/complement identities hold exactly
    totals = quant_ops.dequantize_histogram(
        hist_q[0].sum(axis=0), scales[0], scales[1])
    scan_kwargs = dict(
        num_bins=num_bins, l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf, min_sum_hessian=min_sum_hessian,
        min_gain_to_split=min_gain_to_split)
    res = _dequant_scan(hist_q, scales, totals[0], totals[1], totals[2],
                        feature_meta, jnp.float32(-jnp.inf),
                        jnp.float32(jnp.inf), scan_kwargs, root_cost)
    return hist_q, totals, res


# ---------------------------------------------------------------------------
# whole-tree split-loop formulation (models/device_learner.py growth cores)

def run_split_loop(cond, body, state, num_steps: int,
                   program: str = "per_split"):
    """Run a growth core's leaf-wise split loop under the selected
    `grow_program` formulation.

    ``per_split`` is the classic data-dependent ``lax.while_loop`` —
    exits the moment no leaf has positive gain. ``fused_tree`` is a
    fixed-trip ``lax.scan`` over ``num_steps`` (= num_leaves - 1, the
    most splits a tree can take) whose body is gated by ``lax.cond``.
    Both lower to ONE device program per tree; the scan form has a
    STATIC trip count, which is what makes the whole-tree program
    batchable with ``vmap`` (large-K multiclass: K trees, one dispatch)
    and gives XLA a loop it can fully unroll/schedule.

    Bit-exactness: unbatched ``lax.cond`` executes only the taken
    branch, so once ``cond(state)`` goes False the identity arm carries
    the state through the remaining trips untouched — ``k`` stops
    advancing and the split records can never be overwritten; the
    result is bit-identical to the while_loop form. Under ``vmap`` the
    cond lowers to a select that runs both arms; the speculative body
    arm only writes into the carry COPY of an already-stopped tree,
    which the select discards (XLA clamps dynamic-slice indices, so
    garbage state cannot fault).
    """
    if program != "fused_tree":
        return jax.lax.while_loop(cond, body, state)

    def _trip(st, _):
        return jax.lax.cond(cond(st), body, lambda s: s, st), None

    out, _ = jax.lax.scan(_trip, state, None, length=num_steps)
    return out


def run_once_if(pred, body, state):
    """`body(state)` where `pred` holds, `state` untouched where it does
    not: a ``lax.while_loop`` of one trip or none, and not a
    ``lax.cond``. Chosen, like the forms above, for what the compiler
    aliases: a ``while`` must alias its carry from init to result, so a
    ``dynamic_update_slice`` of a large buffer in `body` happens in
    place, where the TPU compiler copies a buffer on its way into a
    ``conditional`` and again on its way out (the compact core's packed
    table: a whole-table copy a split and another a rung; PERF.md §6,
    PR 30). `body` returns the structure it is given."""
    _, out = jax.lax.while_loop(
        lambda s: s[0],
        lambda s: (jnp.bool_(False), body(s[1])),
        (jnp.asarray(pred, jnp.bool_), state))
    return out
