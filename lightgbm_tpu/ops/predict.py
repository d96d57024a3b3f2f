"""Vectorized tree traversal on device.

Equivalent of the reference's per-row traversal loops (reference:
src/io/tree.cpp:115-207 AddPredictionToScore, tree.h:221-293 Decision) recast
as fixed-trip-count gather iterations: all N rows advance one tree level per
step; finished rows (negative node = leaf) freeze. No data-dependent control
flow, so the whole ensemble scoring jits cleanly.

Trees are tensorized into padded arrays. Two threshold spaces exist like the
reference: bin thresholds for training-time scoring of binned datasets
(DecisionInner) and real-valued thresholds for raw-feature prediction
(Decision).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2
K_ZERO_THRESHOLD = 1e-35


class EnsembleArrays(NamedTuple):
    """Padded (T, max_nodes)/(T, max_leaves) ensemble tensors."""
    split_feature: jax.Array    # (T, M) int32
    threshold: jax.Array        # (T, M) f64-as-f32 real thresholds
    threshold_bin: jax.Array    # (T, M) int32 bin thresholds
    decision_type: jax.Array    # (T, M) int32
    left_child: jax.Array       # (T, M) int32
    right_child: jax.Array      # (T, M) int32
    leaf_value: jax.Array       # (T, L) f32
    cat_boundaries: jax.Array   # (T, C+1) int32
    cat_threshold: jax.Array    # (T, W) int32 (uint32 bitset words)
    cat_boundaries_inner: jax.Array
    cat_threshold_inner: jax.Array
    max_depth: int


def _bucket_up(v: int) -> int:
    """Next power of two: shape-bucketing so growing ensembles reuse the
    same compiled program instead of recompiling per tree count."""
    out = 1
    while out < v:
        out *= 2
    return out


def trees_to_arrays(trees: Sequence, dtype=jnp.float32,
                    bucket: bool = False) -> EnsembleArrays:
    """Tensorize trees into padded ensemble arrays.

    bucket=True additionally pads every shape axis (tree count, nodes,
    leaves, categorical widths) up to the next power of two. Padding
    trees are single-leaf with value 0, so summed predictions are
    unchanged — but a predict called every few iterations of a growing
    booster then compiles O(log T) programs instead of O(T). Do NOT bucket when the OUTPUT shape depends on the
    tree axis (leaf-index prediction)."""
    t_real = len(trees)
    t_count = _bucket_up(t_real) if bucket else t_real
    bk = _bucket_up if bucket else (lambda v: v)
    max_nodes = bk(max(max(t.num_leaves - 1, 1) for t in trees))
    max_leaves = bk(max(t.num_leaves for t in trees))
    max_cats = bk(max(max(t.num_cat, 0) for t in trees))
    max_words = bk(max(max(len(t.cat_threshold), 1) for t in trees))
    max_words_in = bk(max(max(len(t.cat_threshold_inner), 1) for t in trees))

    def pad2(get, shape, dt):
        out = np.zeros((t_count,) + shape, dtype=dt)
        for i, tr in enumerate(trees):
            v = get(tr)
            out[i, : len(v)] = v
        return out

    sf = pad2(lambda t: t.split_feature[: max(t.num_leaves - 1, 0)], (max_nodes,), np.int32)
    th = pad2(lambda t: t.threshold[: max(t.num_leaves - 1, 0)], (max_nodes,), np.float64)
    tb = pad2(lambda t: t.threshold_in_bin[: max(t.num_leaves - 1, 0)], (max_nodes,), np.int32)
    dt_ = pad2(lambda t: t.decision_type[: max(t.num_leaves - 1, 0)], (max_nodes,), np.int32)
    lc = pad2(lambda t: t.left_child[: max(t.num_leaves - 1, 0)], (max_nodes,), np.int32)
    rc = pad2(lambda t: t.right_child[: max(t.num_leaves - 1, 0)], (max_nodes,), np.int32)
    lv = pad2(lambda t: t.leaf_value[: t.num_leaves], (max_leaves,), np.float64)
    cb = pad2(lambda t: np.asarray(t.cat_boundaries, dtype=np.int64), (max_cats + 2,), np.int32)
    ct = pad2(lambda t: np.asarray(t.cat_threshold, dtype=np.int64), (max_words,), np.int64)
    cbi = pad2(lambda t: np.asarray(t.cat_boundaries_inner, dtype=np.int64), (max_cats + 2,), np.int32)
    cti = pad2(lambda t: np.asarray(t.cat_threshold_inner, dtype=np.int64), (max_words_in,), np.int64)
    # single-leaf trees: make node 0 route to leaf 0 both sides
    for i, tr in enumerate(trees):
        if tr.num_leaves == 1:
            lc[i, 0] = -1
            rc[i, 0] = -1
    # bucket-padding trees are single-leaf with value 0 (no-ops)
    for i in range(t_real, t_count):
        lc[i, 0] = -1
        rc[i, 0] = -1
    max_depth = max(t.depth() for t in trees)
    max_depth = max(1, int(np.ceil(max(1, max_depth) / 8)) * 8)
    return EnsembleArrays(
        jnp.asarray(sf), jnp.asarray(th.astype(np.float32)), jnp.asarray(tb),
        jnp.asarray(dt_), jnp.asarray(lc), jnp.asarray(rc),
        jnp.asarray(lv.astype(np.float64).astype(dtype)),
        jnp.asarray(cb), jnp.asarray(ct & 0xFFFFFFFF, dtype=jnp.uint32).astype(jnp.int32),
        jnp.asarray(cbi), jnp.asarray(cti & 0xFFFFFFFF, dtype=jnp.uint32).astype(jnp.int32),
        max_depth,
    )


def padded_tree_class(arrays: EnsembleArrays, classes) -> jax.Array:
    """(T_pad,) tree->class map for predict_raw_ensemble: real trees take
    `classes`, bucket-padding trees map to class 0 (their leaf value is 0,
    so they add nothing). Lives next to the bucketing so every caller of
    trees_to_arrays(bucket=True) shares one padding invariant."""
    t_pad = arrays.split_feature.shape[0]
    tc = np.zeros(t_pad, dtype=np.int32)
    classes = np.asarray(classes, dtype=np.int32)
    tc[:len(classes)] = classes
    return jnp.asarray(tc)


def _traverse_one_tree_binned(binned, feat_missing, feat_default, feat_numbins,
                              sf, tb, dtp, lc, rc, cbi, cti, max_depth):
    """All rows walk one tree over binned codes (DecisionInner semantics)."""
    n = binned.shape[0]
    node = jnp.zeros(n, dtype=jnp.int32)

    def body(_, node):
        live = node >= 0
        node_c = jnp.maximum(node, 0)
        f = sf[node_c]
        fbin = jnp.take_along_axis(binned, f[:, None], axis=1)[:, 0].astype(jnp.int32)
        thr = tb[node_c]
        dt = dtp[node_c]
        is_cat = (dt & 1) > 0
        default_left = (dt & 2) > 0
        mt = (dt >> 2) & 3
        mtype_f = feat_missing[f]
        numbin_f = feat_numbins[f]
        default_f = feat_default[f]
        is_missing = jnp.where(
            mt == MISSING_ZERO, fbin == default_f,
            jnp.where(mt == MISSING_NAN, fbin == numbin_f - 1, False))
        num_left = jnp.where(is_missing, default_left, fbin <= thr)
        # categorical: bitset membership on inner bins
        cat_idx = thr
        lo = cbi[jnp.clip(cat_idx, 0, cbi.shape[0] - 1)]
        hi = cbi[jnp.clip(cat_idx + 1, 0, cbi.shape[0] - 1)]
        word_idx = lo + fbin // 32
        in_range = (fbin // 32) < (hi - lo)
        word = cti[jnp.clip(word_idx, 0, cti.shape[0] - 1)]
        cat_left = in_range & (((word >> (fbin % 32)) & 1) == 1)
        go_left = jnp.where(is_cat, cat_left, num_left)
        nxt = jnp.where(go_left, lc[node_c], rc[node_c])
        return jnp.where(live, nxt, node)

    node = jax.lax.fori_loop(0, max_depth, body, node)
    return ~node  # leaf indices (rows stuck at depth cap return garbage only
                  # if max_depth < true depth, which trees_to_arrays prevents)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_binned_leaf(binned, feat_missing, feat_default, feat_numbins,
                        sf, tb, dtp, lc, rc, cbi, cti, *, max_depth):
    return _traverse_one_tree_binned(binned, feat_missing, feat_default,
                                     feat_numbins, sf, tb, dtp, lc, rc,
                                     cbi, cti, max_depth)


def predict_binned_tree_values(binned, feat_missing, feat_default,
                               feat_numbins, tree, dtype=jnp.float32):
    """Per-row leaf values of a single (host) Tree over binned data.

    bucket=True: this runs once per ITERATION per valid set during
    training (ScoreUpdater.add_tree), and without bucketing every
    distinct (num_leaves, cat-width) pair retraces predict_binned_leaf
    — a compile each. Bucketing collapses the shapes to O(log L) programs; the output indexes tree
    0 only, so padding trees never contribute."""
    arr = trees_to_arrays([tree], dtype=dtype, bucket=True)
    leaves = predict_binned_leaf(
        binned, feat_missing, feat_default, feat_numbins,
        arr.split_feature[0], arr.threshold_bin[0], arr.decision_type[0],
        arr.left_child[0], arr.right_child[0],
        arr.cat_boundaries_inner[0], arr.cat_threshold_inner[0],
        max_depth=arr.max_depth)
    return arr.leaf_value[0][leaves]


def _traverse_one_tree_raw(x, sf, th, dtp, lc, rc, cb, ct, max_depth):
    """All rows walk one tree over raw feature values (Decision semantics)."""
    n = x.shape[0]
    node = jnp.zeros(n, dtype=jnp.int32)

    def body(_, node):
        live = node >= 0
        node_c = jnp.maximum(node, 0)
        f = sf[node_c]
        fval = jnp.take_along_axis(x, f[:, None], axis=1)[:, 0]
        thr = th[node_c]
        dt = dtp[node_c]
        is_cat = (dt & 1) > 0
        default_left = (dt & 2) > 0
        mt = (dt >> 2) & 3
        is_nan = jnp.isnan(fval)
        fval_n = jnp.where(is_nan & (mt != MISSING_NAN), 0.0, fval)
        is_missing = jnp.where(
            mt == MISSING_ZERO, jnp.abs(fval_n) <= K_ZERO_THRESHOLD,
            jnp.where(mt == MISSING_NAN, jnp.isnan(fval_n), False))
        num_left = jnp.where(is_missing, default_left, fval_n <= thr)
        # categorical on raw int values
        ival = jnp.where(is_nan, -1, fval).astype(jnp.int32)
        cat_idx = thr.astype(jnp.int32)
        lo = cb[jnp.clip(cat_idx, 0, cb.shape[0] - 1)]
        hi = cb[jnp.clip(cat_idx + 1, 0, cb.shape[0] - 1)]
        word_idx = lo + ival // 32
        in_range = (ival >= 0) & ((ival // 32) < (hi - lo))
        word = ct[jnp.clip(word_idx, 0, ct.shape[0] - 1)]
        cat_left = in_range & (((word >> (ival % 32)) & 1) == 1)
        go_left = jnp.where(is_cat, cat_left, num_left)
        nxt = jnp.where(go_left, lc[node_c], rc[node_c])
        return jnp.where(live, nxt, node)

    node = jax.lax.fori_loop(0, max_depth, body, node)
    return ~node


@functools.partial(jax.jit, static_argnames=("max_depth", "num_class"))
def predict_raw_ensemble(x: jax.Array, arrays: EnsembleArrays,
                         tree_class: jax.Array, *, max_depth: int,
                         num_class: int) -> jax.Array:
    """Raw scores (N, num_class): sum of per-class tree outputs."""
    n = x.shape[0]

    def per_tree(carry, tree_idx):
        scores = carry
        leaves = _traverse_one_tree_raw(
            x, arrays.split_feature[tree_idx], arrays.threshold[tree_idx],
            arrays.decision_type[tree_idx], arrays.left_child[tree_idx],
            arrays.right_child[tree_idx], arrays.cat_boundaries[tree_idx],
            arrays.cat_threshold[tree_idx], max_depth)
        vals = arrays.leaf_value[tree_idx][leaves]
        k = tree_class[tree_idx]
        scores = scores.at[:, k].add(vals)
        return scores, None

    init = jnp.zeros((n, num_class), dtype=jnp.float32)
    t_count = arrays.split_feature.shape[0]
    scores, _ = jax.lax.scan(per_tree, init, jnp.arange(t_count))
    return scores


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_leaf_index_ensemble(x: jax.Array, arrays: EnsembleArrays,
                                *, max_depth: int) -> jax.Array:
    """(N, T) leaf index per tree (pred_leaf=True)."""
    def per_tree(_, tree_idx):
        leaves = _traverse_one_tree_raw(
            x, arrays.split_feature[tree_idx], arrays.threshold[tree_idx],
            arrays.decision_type[tree_idx], arrays.left_child[tree_idx],
            arrays.right_child[tree_idx], arrays.cat_boundaries[tree_idx],
            arrays.cat_threshold[tree_idx], max_depth)
        return None, leaves

    t_count = arrays.split_feature.shape[0]
    _, leaves = jax.lax.scan(per_tree, None, jnp.arange(t_count))
    return leaves.T
