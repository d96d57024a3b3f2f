"""Vectorized best-split search over (feature, bin, missing-direction).

Behavioral equivalent of the reference's per-feature threshold sweeps
(reference: src/treelearner/feature_histogram.hpp:91-116
FindBestThresholdNumerical and :508-648 FindBestThresholdSequence), recast as
a fully-vectorized cumsum + masked argmax over the whole (F, B) plane — ideal
VPU work, no data-dependent control flow.

Semantics reproduced:
  * two sweeps = two missing directions. dir=-1 accumulates from the right
    (missing goes LEFT, default_left=True); dir=+1 from the left (missing
    goes RIGHT). Ties prefer dir=-1, and within dir=-1 the larger threshold,
    within dir=+1 the smaller (loop orders + strict-> comparisons in the
    reference).
  * MissingType::Zero skips the default(zero) bin in both accumulations so
    the zero bin always travels with the missing direction.
  * MissingType::NaN keeps the NaN bin (last bin) out of the dir=-1 right
    accumulation so NaN travels left there; in dir=+1 it stays right.
  * L1 soft-thresholding, L2, max_delta_step clamp, monotone-constraint
    rejection and min/max output clamps (feature_histogram.hpp:446-490).
  * min_data_in_leaf / min_sum_hessian_in_leaf feasibility masks.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


class SplitResult(NamedTuple):
    """Winning split for one leaf (all scalars, device)."""
    gain: jax.Array          # f32, NEG_INF if no valid split
    feature: jax.Array       # int32 inner feature index
    threshold: jax.Array     # int32 bin threshold (left: bin <= thr)
    default_left: jax.Array  # bool
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array
    left_count: jax.Array    # f32 (exact integers)
    right_sum_grad: jax.Array
    right_sum_hess: jax.Array
    right_count: jax.Array
    left_output: jax.Array
    right_output: jax.Array


def _threshold_l1(s, l1):
    return jnp.sign(s) * jnp.maximum(0.0, jnp.abs(s) - l1)


def _leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step):
    out = -_threshold_l1(sum_grad, l1) / (sum_hess + l2)
    # max_delta_step <= 0 means unbounded (traced-scalar-safe clip)
    limit = jnp.where(max_delta_step > 0.0, max_delta_step, jnp.inf)
    return jnp.clip(out, -limit, limit)


def _leaf_output_constrained(sum_grad, sum_hess, l1, l2, max_delta_step,
                             min_c, max_c):
    return jnp.clip(_leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step),
                    min_c, max_c)


def _gain_given_output(sum_grad, sum_hess, l1, l2, output):
    sg_l1 = _threshold_l1(sum_grad, l1)
    return -(2.0 * sg_l1 * output + (sum_hess + l2) * output * output)


def leaf_split_gain(sum_grad, sum_hess, l1, l2, max_delta_step):
    """Objective value of keeping a node whole (reference GetLeafSplitGain)."""
    out = _leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step)
    return _gain_given_output(sum_grad, sum_hess, l1, l2, out)


def _split_gains(gl, hl, gr, hr, l1, l2, mds, min_c, max_c, mono):
    """Candidate gain; monotone violations -> 0 (reference GetSplitGains)."""
    lo = _leaf_output_constrained(gl, hl, l1, l2, mds, min_c, max_c)
    ro = _leaf_output_constrained(gr, hr, l1, l2, mds, min_c, max_c)
    gain = (_gain_given_output(gl, hl, l1, l2, lo)
            + _gain_given_output(gr, hr, l1, l2, ro))
    violate = ((mono > 0) & (lo > ro)) | ((mono < 0) & (lo < ro))
    return jnp.where(violate, 0.0, gain)


def _prefix_and_strict_suffix(x):
    """Running sums of `x` (F, B, C) along the bins: the inclusive prefix
    over [0..t] and the strict suffix over [t+1..B-1], each summed from
    its own bins alone (never `whole - other side`), so each is accurate
    to its own size. The suffix is a shifted inclusive sum and not
    `inclusive - own bin`, which cancels where a small tail follows a
    large bin."""
    pre = jnp.cumsum(x, axis=1)
    suf = jax.lax.cumsum(x, axis=1, reverse=True)
    suf = jnp.concatenate([suf[:, 1:], jnp.zeros_like(suf[:, :1])], axis=1)
    return pre, suf


@jax.named_scope("lgbm.split_scan")
def per_feature_best(
    hist: jax.Array,            # (F, B, 3) f32 [sum_grad, sum_hess, count]
    sum_grad: jax.Array,        # scalar: leaf total gradient
    sum_hess: jax.Array,        # scalar: leaf total hessian
    num_data: jax.Array,        # scalar f32: leaf row count
    feature_num_bins: jax.Array,  # (F,) int32 per-feature bin counts
    feature_missing: jax.Array,   # (F,) int32 MissingType (0/1/2)
    feature_default_bins: jax.Array,  # (F,) int32 default (zero) bin
    feature_mask: jax.Array,    # (F,) bool — sampled-in features
    monotone: jax.Array,        # (F,) int32 constraints (-1/0/1)
    min_constraint: jax.Array,  # scalar leaf output min (monotone prop)
    max_constraint: jax.Array,  # scalar leaf output max
    feature_penalty: jax.Array = None,  # (F,) gain multiplier
                                 # (feature_contri; reference
                                 # feature_histogram.hpp:88 gain *= penalty)
    feature_cost: jax.Array = None,     # (F,) subtractive CEGB cost
                                 # (reference cegb DetlaGain terms)
    *,
    num_bins: int,
    l1: float, l2: float, max_delta_step: float,
    min_data_in_leaf: int, min_sum_hessian: float, min_gain_to_split: float,
):
    """Per-feature best (gain, threshold, default_left) plus the prefix
    tensors needed to materialize a winner. This is the unit the parallel
    learners reduce over (reference: the per-feature OMP loop in
    FindBestSplitsFromHistograms, serial_tree_learner.cpp:524-605)."""
    f, b, _ = hist.shape
    tgrid = jnp.arange(b, dtype=jnp.int32)[None, :]          # thresholds (1, B)
    nbins = feature_num_bins[:, None]                        # (F, 1)
    is_nan = (feature_missing[:, None] == 2)
    is_zero = (feature_missing[:, None] == 1)
    default_b = feature_default_bins[:, None]

    # The (grad, hess, count) channels ride one (F, B, 3) array through
    # the accumulations and the two missing-directions stack into one
    # leading axis, so the whole sweep is one pair of running sums + one
    # stacked gain chain instead of 6 + 2 — this chain runs per split
    # inside the whole-tree loop, where op count is latency
    # (docs/DESIGN.md 6a-r3).
    #
    # Both sides of every threshold are SUMMED FROM THE BINS, never formed
    # as `leaf total - other side`: a child whose hessian sum is ~3 off a
    # node whose sum is ~1e6 would otherwise be float32 rounding noise of
    # the parent (the reference sums in double, hist_t). Each side is a
    # prefix or a strict suffix of the scanned bins plus the mass that
    # rides with the missing direction, so its error is relative to its
    # own size. The two sides add up to the histogram's own total, which
    # equals the leaf's handed-down total only to float32 rounding (the
    # counts, exact integers below 2^24, still add up exactly).

    # Zero-missing mode: the default bin never enters either accumulation;
    # its mass rides with the missing side. NaN mode: the NaN bin (last)
    # stays out of the dir=-1 suffix, so NaN goes left there.
    skip = is_zero & (tgrid == default_b)
    nan_excl = is_nan & (tgrid >= nbins - 1)                  # NaN bin mask
    riding = (skip | nan_excl)[:, :, None]
    scanned = jnp.where(riding, 0.0, hist)
    # the skipped zero bin's or the NaN bin's (g, h, count): (F, 1, 3)
    miss = jnp.sum(jnp.where(riding, hist, 0.0), axis=1, keepdims=True)

    pre, suf = _prefix_and_strict_suffix(scanned)            # (F, B, 3) each

    # dir=+1: missing rides right; dir=-1: missing rides left. At the
    # valid thresholds (t < nbins - 1) the NaN bin lies above t, so `pre`
    # holds none of it in either direction.
    left2 = jnp.stack([pre, pre + miss])                     # (2, F, B, 3)
    right2 = jnp.stack([suf + miss, suf])

    # valid threshold ranges per feature (reference loop bounds):
    #   dir=+1: t in [0, nb-2]; NaN mode unchanged (NaN bin can sit alone
    #           on the right at t = nb-2).
    #   dir=-1: t in [0, nb-2]; NaN mode: t in [0, nb-3] (right side would
    #           be empty at nb-2 since NaN is excluded there).
    base_valid = (tgrid < nbins - 1) & feature_mask[:, None] & (nbins > 1)
    zero_skip_t = is_zero & (tgrid == default_b)               # not a candidate
    valid2 = jnp.stack([base_valid & ~zero_skip_t,
                        base_valid & ~zero_skip_t
                        & ~(is_nan & (tgrid >= nbins - 2))])   # (2, F, B)

    ok2 = (valid2
           & (left2[..., 2] >= min_data_in_leaf)
           & (right2[..., 2] >= min_data_in_leaf)
           & (left2[..., 1] >= min_sum_hessian)
           & (right2[..., 1] >= min_sum_hessian))
    gains2 = _split_gains(left2[..., 0], left2[..., 1],
                          right2[..., 0], right2[..., 1], l1, l2,
                          max_delta_step, min_constraint, max_constraint,
                          monotone[None, :, None])
    gains2 = jnp.where(ok2, gains2, NEG_INF)
    gains_p1, gains_m1 = gains2[0], gains2[1]

    gain_shift = leaf_split_gain(sum_grad, sum_hess, l1, l2, max_delta_step)
    min_gain_shift = gain_shift + min_gain_to_split
    gains_p1 = jnp.where(gains_p1 > min_gain_shift, gains_p1, NEG_INF)
    gains_m1 = jnp.where(gains_m1 > min_gain_shift, gains_m1, NEG_INF)

    # tie-breaking: dir=-1 prefers larger threshold -> argmax over reversed
    # bins; dir=+1 prefers smaller -> plain argmax. Across dirs prefer -1.
    def pick(gains, prefer_large_t):
        per_f = jnp.max(gains, axis=1)
        if prefer_large_t:
            t_best = (b - 1) - jnp.argmax(gains[:, ::-1], axis=1)
        else:
            t_best = jnp.argmax(gains, axis=1)
        return per_f, t_best.astype(jnp.int32)

    best_f_m1, best_t_m1 = pick(gains_m1, True)
    best_f_p1, best_t_p1 = pick(gains_p1, False)

    use_m1 = best_f_m1 >= best_f_p1
    per_feature_gain = jnp.where(use_m1, best_f_m1, best_f_p1)
    per_feature_t = jnp.where(use_m1, best_t_m1, best_t_p1)
    # relative gains (reference: output->gain -= min_gain_shift), then the
    # feature_contri multiplier and CEGB cost subtraction
    per_feature_rel = jnp.where(per_feature_gain > NEG_INF / 2,
                                per_feature_gain - min_gain_shift, NEG_INF)
    if feature_penalty is not None:
        per_feature_rel = jnp.where(per_feature_rel > NEG_INF / 2,
                                    per_feature_rel * feature_penalty,
                                    per_feature_rel)
    if feature_cost is not None:
        per_feature_rel = jnp.where(per_feature_rel > NEG_INF / 2,
                                    per_feature_rel - feature_cost,
                                    per_feature_rel)
    prefix = (pre, suf, miss)
    return per_feature_rel, per_feature_t, use_m1, prefix


@jax.named_scope("lgbm.split_scan")
def materialize_split(feat, per_feature_rel, per_feature_t, use_m1, prefix,
                      min_constraint, max_constraint,
                      *, l1, l2, max_delta_step) -> SplitResult:
    """Build the full SplitResult for one chosen feature."""
    pre, suf, miss = prefix
    gain = per_feature_rel[feat]
    thr = per_feature_t[feat]
    dleft = use_m1[feat]
    # both children from the bins, as the scan formed them
    missing_mass = miss[feat, 0]
    left = pre[feat, thr] + jnp.where(dleft, missing_mass, 0.0)
    right = suf[feat, thr] + jnp.where(dleft, 0.0, missing_mass)
    lg, lh, lc = left[0], left[1], left[2]
    rg, rh, rc = right[0], right[1], right[2]
    lo = _leaf_output_constrained(lg, lh, l1, l2, max_delta_step,
                                  min_constraint, max_constraint)
    ro = _leaf_output_constrained(rg, rh, l1, l2, max_delta_step,
                                  min_constraint, max_constraint)
    return SplitResult(gain, feat.astype(jnp.int32), thr, dleft,
                       lg, lh, lc, rg, rh, rc, lo, ro)


@functools.partial(
    jax.jit,
    static_argnames=("num_bins",))
def find_best_split(
    hist: jax.Array, sum_grad: jax.Array, sum_hess: jax.Array,
    num_data: jax.Array, feature_num_bins: jax.Array,
    feature_missing: jax.Array, feature_default_bins: jax.Array,
    feature_mask: jax.Array, monotone: jax.Array,
    min_constraint: jax.Array, max_constraint: jax.Array,
    feature_penalty: jax.Array = None, feature_cost: jax.Array = None,
    *, num_bins: int, l1: float, l2: float, max_delta_step: float,
    min_data_in_leaf: int, min_sum_hessian: float, min_gain_to_split: float,
) -> SplitResult:
    per_feature_rel, per_feature_t, use_m1, prefix = per_feature_best(
        hist, sum_grad, sum_hess, num_data, feature_num_bins,
        feature_missing, feature_default_bins, feature_mask, monotone,
        min_constraint, max_constraint, feature_penalty, feature_cost,
        num_bins=num_bins, l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf, min_sum_hessian=min_sum_hessian,
        min_gain_to_split=min_gain_to_split)
    feat = jnp.argmax(per_feature_rel).astype(jnp.int32)
    return materialize_split(
        feat, per_feature_rel, per_feature_t, use_m1, prefix,
        min_constraint, max_constraint,
        l1=l1, l2=l2, max_delta_step=max_delta_step)


def calculate_leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step):
    """Public helper (reference CalculateSplittedLeafOutput)."""
    return _leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step)


@functools.partial(
    jax.jit,
    static_argnames=("num_bins",))
def find_best_split_quantized(
    hist_q: jax.Array, g_scale: jax.Array, h_scale: jax.Array,
    sum_grad: jax.Array, sum_hess: jax.Array,
    num_data: jax.Array, feature_num_bins: jax.Array,
    feature_missing: jax.Array, feature_default_bins: jax.Array,
    feature_mask: jax.Array, monotone: jax.Array,
    min_constraint: jax.Array, max_constraint: jax.Array,
    feature_penalty: jax.Array = None, feature_cost: jax.Array = None,
    *, num_bins: int, l1: float, l2: float, max_delta_step: float,
    min_data_in_leaf: int, min_sum_hessian: float, min_gain_to_split: float,
) -> SplitResult:
    """Quantized-histogram split scan: rescale the leaf's EXACT integer
    (sum_qg, sum_qh, count) sums back to f32 with the iteration's scales
    BEFORE gain computation, then run the identical vectorized sweep.
    The integer domain carries construction and sibling subtraction; the
    gain arithmetic stays in f32 where the reference's formulas live.
    """
    from .quantize import dequantize_histogram
    hist = dequantize_histogram(hist_q, g_scale, h_scale)
    return find_best_split.__wrapped__(
        hist, sum_grad, sum_hess, num_data, feature_num_bins,
        feature_missing, feature_default_bins, feature_mask, monotone,
        min_constraint, max_constraint, feature_penalty, feature_cost,
        num_bins=num_bins, l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf, min_sum_hessian=min_sum_hessian,
        min_gain_to_split=min_gain_to_split)


class CatSplitResult(NamedTuple):
    gain: jax.Array
    feature: jax.Array
    left_mask: jax.Array     # (B,) bool — inner bins routed left
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array
    left_count: jax.Array
    right_sum_grad: jax.Array
    right_sum_hess: jax.Array
    right_count: jax.Array
    left_output: jax.Array
    right_output: jax.Array


@jax.named_scope("lgbm.split_scan")
def per_feature_best_categorical(
    hist: jax.Array, sum_grad: jax.Array, sum_hess: jax.Array,
    num_data: jax.Array, feature_num_bins: jax.Array,
    feature_missing: jax.Array, feature_mask: jax.Array,
    min_constraint: jax.Array, max_constraint: jax.Array,
    feature_penalty: jax.Array = None,
    *, num_bins: int, l1: float, l2: float, cat_l2: float, cat_smooth: float,
    max_delta_step: float, min_data_in_leaf: int, min_sum_hessian: float,
    min_gain_to_split: float, max_cat_threshold: int, max_cat_to_onehot: int,
    min_data_per_group: int,
):
    """Per-feature categorical k-vs-rest best gains (reference:
    feature_histogram.hpp:118-279 FindBestThresholdCategorical).

    One-hot mode for small cardinality; otherwise bins are sorted by
    grad/(hess+cat_smooth) and prefixes from both ends are scanned (bounded
    by max_cat_threshold). Vectorized over features x sorted-positions.
    Deviation noted: the reference's min_data_per_group *running-group*
    accumulation is approximated by the per-candidate right-count check.

    Returns (rel_gains (F,), aux) where rel_gains are min_gain_shift-
    relative (penalty-scaled) gains comparable to per_feature_best's, and
    aux holds what materialize_cat_split needs to build the winner's
    left-bin mask. Split out from the monolithic jit so the whole-tree
    device program can merge categorical and numerical candidates in one
    traced scan (the device analog of SerialTreeLearner._merge_categorical).
    """
    f, b, _ = hist.shape
    g = hist[:, :, 0]
    h = hist[:, :, 1]
    c = hist[:, :, 2]
    bgrid = jnp.arange(b, dtype=jnp.int32)[None, :]
    nbins = feature_num_bins[:, None]
    # used_bin = num_bin - 1 + (missing_type == None): the trailing
    # overflow/NaN bin is not a candidate unless the feature is "full"
    is_full = (feature_missing[:, None] == 0)
    used_bin = nbins - 1 + is_full.astype(jnp.int32)
    bin_ok = bgrid < used_bin

    gain_shift = leaf_split_gain(sum_grad, sum_hess, l1, l2, max_delta_step)
    min_gain_shift = gain_shift + min_gain_to_split
    use_onehot = (feature_num_bins <= max_cat_to_onehot)

    def gains_for(gl, hl, eff_l2, ok):
        gr = sum_grad - gl
        hr = sum_hess - hl
        gains = _split_gains(gl, hl, gr, hr, l1, eff_l2, max_delta_step,
                             min_constraint, max_constraint, 0)
        return jnp.where(ok, gains, NEG_INF)

    # ---- one-hot mode: left = single bin --------------------------------
    oh_ok = (bin_ok
             & (c >= min_data_in_leaf) & (h >= min_sum_hessian)
             & ((num_data - c) >= min_data_in_leaf)
             & ((sum_hess - h) >= min_sum_hessian))
    # reference computes gain(other, bin) == gain(bin, other); symmetric
    oh_gains = gains_for(g, h, l2, oh_ok)
    oh_gains = jnp.where(oh_gains > min_gain_shift, oh_gains, NEG_INF)
    oh_best = jnp.max(oh_gains, axis=1)
    oh_t = jnp.argmax(oh_gains, axis=1).astype(jnp.int32)

    # ---- sorted mode ----------------------------------------------------
    # (g, h, c) ride one (F, B, 3) array through the sort-gather, the
    # roll and the cumsum, and the two walk directions stack on a
    # leading axis — one gather + one cumsum + one gain chain instead of
    # 3/6/2 (bit-identical; this runs per split in the device loop)
    eff_l2 = l2 + cat_l2
    valid_sorted = bin_ok & (c >= cat_smooth)
    ctr = jnp.where(valid_sorted, g / (h + cat_smooth), jnp.inf)
    order = jnp.argsort(ctr, axis=1)                    # (F, B) bins by ctr
    hs = jnp.take_along_axis(hist, order[:, :, None], axis=1)
    v_s = jnp.take_along_axis(valid_sorted, order, axis=1)
    n_valid = jnp.sum(v_s.astype(jnp.int32), axis=1, keepdims=True)
    hs = jnp.where(v_s[:, :, None], hs, 0.0)
    max_num_cat = jnp.minimum(max_cat_threshold, (n_valid + 1) // 2)
    pos = jnp.arange(b, dtype=jnp.int32)[None, :]

    # dir=-1 walks from the high-ctr end: flip, then rotate so valid
    # entries lead (they sit at the tail after the flip)
    shift = b - n_valid[:, 0]
    roll_idx = (pos + shift[:, None]) % b

    def roll_rows(x):
        return jnp.take_along_axis(x, roll_idx, axis=1)

    hr = jnp.take_along_axis(hs[:, ::-1, :], roll_idx[:, :, None], axis=1)
    v_r = roll_rows(v_s[:, ::-1])

    hd2 = jnp.stack([hs, hr])                           # (2, F, B, 3)
    vd2 = jnp.stack([v_s, v_r])
    left2 = jnp.cumsum(hd2, axis=2)
    gl2, hl2, cl2 = left2[..., 0], left2[..., 1], left2[..., 2]
    ok2 = (vd2 & (pos < max_num_cat)
           & (cl2 >= min_data_in_leaf) & (hl2 >= min_sum_hessian)
           & ((num_data - cl2)
              >= jnp.maximum(min_data_in_leaf, min_data_per_group))
           & ((sum_hess - hl2) >= min_sum_hessian))
    gains2 = gains_for(gl2, hl2, eff_l2, ok2)
    gains2 = jnp.where(gains2 > min_gain_shift, gains2, NEG_INF)
    best2 = jnp.max(gains2, axis=2)
    ti2 = jnp.argmax(gains2, axis=2).astype(jnp.int32)
    (fwd_best, bwd_best), (fwd_t, bwd_t) = best2, ti2

    use_fwd = fwd_best >= bwd_best
    sort_best = jnp.where(use_fwd, fwd_best, bwd_best)
    sort_t = jnp.where(use_fwd, fwd_t, bwd_t)

    per_gain = jnp.where(use_onehot, oh_best, sort_best)
    per_gain = jnp.where(feature_mask, per_gain, NEG_INF)
    rel = jnp.where(per_gain > NEG_INF / 2,
                    per_gain - min_gain_shift, NEG_INF)
    if feature_penalty is not None:
        rel = jnp.where(rel > NEG_INF / 2, rel * feature_penalty, rel)
    order_r = roll_rows(order[:, ::-1])
    aux = (use_onehot, oh_t, sort_t, use_fwd, order, v_s, order_r, v_r)
    return rel, aux


@jax.named_scope("lgbm.split_scan")
def materialize_cat_split(feat, rel, aux, hist,
                          sum_grad, sum_hess, num_data,
                          min_constraint, max_constraint,
                          *, l1, l2, cat_l2,
                          max_delta_step) -> CatSplitResult:
    """Build the full CatSplitResult (incl. the left-bin mask over inner
    bins) for one chosen categorical feature."""
    use_onehot, oh_t, sort_t, use_fwd, order, v_s, order_r, v_r = aux
    b = hist.shape[1]
    g = hist[:, :, 0]
    h = hist[:, :, 1]
    c = hist[:, :, 2]
    gain = rel[feat]

    pos_b = jnp.arange(b, dtype=jnp.int32)
    onehot_mask = (pos_b == oh_t[feat])
    k = sort_t[feat]
    sel_sorted = (pos_b <= k)
    fwd_mask = jnp.zeros(b, dtype=bool).at[order[feat]].set(
        sel_sorted & v_s[feat])
    bwd_mask = jnp.zeros(b, dtype=bool).at[order_r[feat]].set(
        sel_sorted & v_r[feat])
    sorted_mask = jnp.where(use_fwd[feat], fwd_mask, bwd_mask)
    left_mask = jnp.where(use_onehot[feat], onehot_mask, sorted_mask)

    lg = jnp.sum(jnp.where(left_mask, g[feat], 0.0))
    lh = jnp.sum(jnp.where(left_mask, h[feat], 0.0))
    lc = jnp.sum(jnp.where(left_mask, c[feat], 0.0))
    rg = sum_grad - lg
    rh = sum_hess - lh
    rc = num_data - lc
    w_l2 = jnp.where(use_onehot[feat], l2, l2 + cat_l2)
    lo = jnp.clip(-_threshold_l1(lg, l1) / (lh + w_l2),
                  min_constraint, max_constraint)
    ro = jnp.clip(-_threshold_l1(rg, l1) / (rh + w_l2),
                  min_constraint, max_constraint)
    limit = jnp.where(max_delta_step > 0, max_delta_step, jnp.inf)
    lo = jnp.clip(lo, -limit, limit)
    ro = jnp.clip(ro, -limit, limit)
    return CatSplitResult(gain, feat.astype(jnp.int32), left_mask,
                          lg, lh, lc, rg, rh, rc, lo, ro)


@functools.partial(
    jax.jit,
    static_argnames=("num_bins",))
def find_best_split_categorical(
    hist: jax.Array, sum_grad: jax.Array, sum_hess: jax.Array,
    num_data: jax.Array, feature_num_bins: jax.Array,
    feature_missing: jax.Array, feature_mask: jax.Array,
    min_constraint: jax.Array, max_constraint: jax.Array,
    *, num_bins: int, l1: float, l2: float, cat_l2: float, cat_smooth: float,
    max_delta_step: float, min_data_in_leaf: int, min_sum_hessian: float,
    min_gain_to_split: float, max_cat_threshold: int, max_cat_to_onehot: int,
    min_data_per_group: int,
) -> CatSplitResult:
    """Whole-leaf categorical winner (host-loop learner entry point)."""
    rel, aux = per_feature_best_categorical(
        hist, sum_grad, sum_hess, num_data, feature_num_bins,
        feature_missing, feature_mask, min_constraint, max_constraint,
        num_bins=num_bins, l1=l1, l2=l2, cat_l2=cat_l2,
        cat_smooth=cat_smooth, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf, min_sum_hessian=min_sum_hessian,
        min_gain_to_split=min_gain_to_split,
        max_cat_threshold=max_cat_threshold,
        max_cat_to_onehot=max_cat_to_onehot,
        min_data_per_group=min_data_per_group)
    feat = jnp.argmax(rel).astype(jnp.int32)
    return materialize_cat_split(
        feat, rel, aux, hist, sum_grad, sum_hess, num_data,
        min_constraint, max_constraint,
        l1=l1, l2=l2, cat_l2=cat_l2, max_delta_step=max_delta_step)
