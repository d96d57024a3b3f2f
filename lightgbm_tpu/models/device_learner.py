"""Whole-tree-on-device leaf-wise learner.

The host-loop learner (serial_learner.py) mirrors the reference's phase
structure (serial_tree_learner.cpp:173-237) and pays one host round-trip per
split, and every distinct leaf size recompiles a bucket shape. This learner is the TPU-native answer flagged in
SURVEY.md §7 ("leaf-wise growth is inherently dynamic-shape"): grow the
ENTIRE tree inside one jitted `lax.while_loop` with static shapes.

Design deltas vs the reference's DataPartition/HistogramPool machinery:

* No permutation buffer. Row membership is a dense (N,) `leaf_id` vector;
  a split rewrites it with a masked `where` — O(N) elementwise, no sort.
* Histograms are built over the FULL row set with per-row weights
  `gh * (leaf_id == leaf)`. O(N) per split instead of O(leaf), but the
  histogram path runs at HBM speed on the MXU (ops/pallas), so N x (L-1)
  work is orders of magnitude cheaper than L-1 host syncs.
* The histogram pool (feature_histogram.hpp:654-831) becomes a dense
  (L, F, B, 3) device array: parent slot is overwritten by the left child,
  the right child is parent - left (FeatureHistogram::Subtract semantics).
* Per-split records (split leaf, feature, bin, gain, child stats) are
  written into (L-1,) arrays; the host replays them into a `Tree` after the
  loop — one device->host transfer per tree.
* Leaf-wise leaf selection = argmax over the (L,) per-leaf best-gain array,
  exactly the `best_split_per_leaf_` argmax of the reference.

Monotone constraints propagate like serial_tree_learner.cpp:771-852 (basic
mode); depth limits gate stored gains. Categorical splits run INSIDE the
whole-tree program (one-hot and sorted k-vs-rest, the device analog of
feature_histogram.hpp:118-279): each leaf's scan merges the numerical and
categorical winners, the winning left-bin mask lives in a (L, B) store and
is recorded per split for host replay into bitset tree nodes. The sharded
modes carry categoricals too: psum/voting scan replicated reduced
histograms (masks replicate for free), and the sliced scatter/feature-
parallel elections transport the winner's mask inside the candidate
payload. Forced splits and CEGB fall back to the host-loop learner
(create_tree_learner picks).
"""
from __future__ import annotations

import contextlib
import functools
from typing import List, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint
from jax.extend.core import Primitive
from jax.interpreters import mlir

from ..config import Config
from ..io.binning import BIN_CATEGORICAL
from ..io.dataset import Dataset
from ..io.stream import DeviceDataShard
from ..ops import bundle as bundle_ops
from ..ops import quantize as quant_ops
from ..ops import split as split_ops
from ..ops.fused import run_once_if, run_split_loop
from ..ops.partition import decide_left
from ..ops.pallas.histogram_kernel import build_histogram_pallas_t
from ..ops.pallas import partition_kernel
from .. import telemetry
from ..telemetry import spans as telem_spans
from ..telemetry import recorder as telem
from ..utils import log
from ..utils.log import LightGBMError
from ..utils.envs import flag, strategy_env, use_pallas_env
from .tree import Tree

NEG_INF = split_ops.NEG_INF
_POOL_BYTE_LIMIT = 2 << 30


def _env(name, default):
    import os
    return os.environ.get(name, default)


# Per-leaf best-split state lives in ONE (L, 12) f32 array (the device
# analog of the reference's best_split_per_leaf_) so each update is a single
# row write instead of 12 tiny scatters. feat/thr ride as exact small f32.
B_GAIN, B_FEAT, B_THR, B_DLEFT, B_LSG, B_LSH, B_LCNT, B_RSG, B_RSH, \
    B_RCNT, B_LOUT, B_ROUT = range(12)

# Per-split records: ONE (L-1, 13) f32 array fetched to host in a single
# transfer per tree and replayed into a Tree.
R_LEAF, R_FEAT, R_THR, R_DLEFT, R_GAIN, R_LSG, R_LSH, R_LCNT, R_RSG, \
    R_RSH, R_RCNT, R_LOUT, R_ROUT = range(13)


class _Carry(NamedTuple):
    k: jax.Array
    leaf_id: jax.Array
    pool: jax.Array
    depth: jax.Array
    leaf_min: jax.Array
    leaf_max: jax.Array
    best: jax.Array          # (L, 12) f32
    best_cat: jax.Array      # (L, B|1) f32 0/1 left-bin masks
    rec: jax.Array           # (L-1, 13) f32
    rec_cat: jax.Array       # (L-1, B|1) f32
    key: jax.Array


def _merge_num_cat(res: split_ops.SplitResult, cres) -> tuple:
    """Merge the numerical and categorical split candidates of one leaf —
    the in-program analog of SerialTreeLearner._merge_categorical: the
    better gain wins. Returns (merged SplitResult, (B,) f32 left-bin mask)
    where the mask is all-zero when the numerical candidate wins (the
    store/transport convention shared by every growth mode)."""
    cat_wins = cres.gain > res.gain
    merged = split_ops.SplitResult(
        gain=jnp.where(cat_wins, cres.gain, res.gain),
        feature=jnp.where(cat_wins, cres.feature, res.feature),
        threshold=jnp.where(cat_wins, 0, res.threshold),
        default_left=jnp.where(cat_wins, False, res.default_left),
        left_sum_grad=jnp.where(
            cat_wins, cres.left_sum_grad, res.left_sum_grad),
        left_sum_hess=jnp.where(
            cat_wins, cres.left_sum_hess, res.left_sum_hess),
        left_count=jnp.where(cat_wins, cres.left_count, res.left_count),
        right_sum_grad=jnp.where(
            cat_wins, cres.right_sum_grad, res.right_sum_grad),
        right_sum_hess=jnp.where(
            cat_wins, cres.right_sum_hess, res.right_sum_hess),
        right_count=jnp.where(
            cat_wins, cres.right_count, res.right_count),
        left_output=jnp.where(
            cat_wins, cres.left_output, res.left_output),
        right_output=jnp.where(
            cat_wins, cres.right_output, res.right_output))
    cm = jnp.where(cat_wins, cres.left_mask.astype(jnp.float32), 0.0)
    return merged, cm


def _hist_t(codes_t, gh, num_bins, use_pallas, hist_chunk=0):
    if use_pallas:
        return build_histogram_pallas_t(codes_t, gh, num_bins)
    from ..ops.histogram import build_histogram
    return build_histogram(jnp.swapaxes(codes_t, 0, 1), gh, num_bins,
                           chunk_size=hist_chunk, use_pallas=False)


def _hist_t_q(codes_t, ghq, num_bins, use_pallas, hist_chunk=0):
    """Quantized histogram over transposed codes: EXACT int32 sums from
    ONE integer one-hot contraction (no bf16 hi/lo pair)."""
    if use_pallas:
        from ..ops.pallas.histogram_kernel import \
            build_histogram_pallas_quantized_t
        return build_histogram_pallas_quantized_t(codes_t, ghq, num_bins)
    from ..ops.histogram import build_histogram_quantized
    return build_histogram_quantized(jnp.swapaxes(codes_t, 0, 1), ghq,
                                     num_bins, chunk_size=hist_chunk,
                                     use_pallas=False)


def _tree_helpers(base_mask, f_numbins, f_missing, f_default, f_monotone,
                  f_penalty, f_elide, scan_plan, *, num_bins, max_depth,
                  l1, l2, max_delta_step, min_data_in_leaf, min_sum_hessian,
                  min_gain_to_split, bynode_k,
                  f_categorical=None, cat_statics=None, dequant=None):
    """Shared pieces of both growth strategies: per-node feature sampling,
    the (plane + scan + materialize) split search, and per-leaf best-state
    stores with depth gating.

    scan_plan (ops/bundle.py split_scan_plan) says which planes the scan
    reads. None: the column histogram is the per-feature one and is
    scanned as it is. Otherwise every width class of features gets a
    plane as wide as its bin counts, expanded from the column histogram
    with the class's own map (FixHistogram included); the per-feature
    results go back to feature order, so the argmax over features picks
    what one (F, num_bins) plane would, lowest index first on ties.

    cat_statics = (cat_l2, cat_smooth, max_cat_threshold,
    max_cat_to_onehot, min_data_per_group) switches the scan into merged
    numerical+categorical mode: each leaf evaluates both searches (the
    categorical one over its own plane of num_bins) and the better gain
    wins (the in-program analog
    of SerialTreeLearner._merge_categorical). scan then returns
    (SplitResult, left-bin mask) where the mask is all-zero for a numerical
    winner; without cat_statics the mask is a (1,) placeholder.

    dequant (quantized-grad path): maps an EXACT int32 column histogram
    to f32 with the iteration's scales right before the split scan — the
    integer domain carries construction, pooling and sibling subtraction,
    the gain arithmetic stays f32."""
    f = f_numbins.shape[0]
    has_cat = cat_statics is not None
    cat_b = num_bins if has_cat else 1
    scan_kwargs = dict(
        num_bins=num_bins, l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf, min_sum_hessian=min_sum_hessian,
        min_gain_to_split=min_gain_to_split)
    if has_cat:
        is_cat = f_categorical != 0
        cat_l2, cat_smooth, max_cat_threshold, max_cat_to_onehot, \
            min_data_per_group = cat_statics
        cat_kwargs = dict(
            scan_kwargs, cat_l2=cat_l2, cat_smooth=cat_smooth,
            max_cat_threshold=max_cat_threshold,
            max_cat_to_onehot=max_cat_to_onehot,
            min_data_per_group=min_data_per_group)

    def node_mask(key):
        if bynode_k <= 0:
            return base_mask
        u = jnp.where(base_mask, jax.random.uniform(key, (f,)), jnp.inf)
        kth = jnp.sort(u)[bynode_k - 1]
        return base_mask & (u <= kth)

    # each plane: (feature ids or None for all, expansion map or None
    # for the column histogram itself, the features' per-feature arrays)
    per_f = (f_numbins, f_missing, f_default, f_monotone, f_penalty,
             f_elide)
    if scan_plan is None:
        inv = None
        num_planes = [(None, None, per_f)]
        cat_plane = (None, None, per_f) if has_cat else None
    else:
        inv, num_classes, cat_class = scan_plan
        num_planes = [(ids, idx, tuple(jnp.take(a, ids) for a in per_f))
                      for ids, idx in num_classes]
        cat_plane = None
        if has_cat:
            ids, idx = cat_class
            cat_plane = (ids, idx, tuple(jnp.take(a, ids) for a in per_f))

    def plane_of(col_hist, totals, idx, meta):
        if idx is None:
            return col_hist
        return bundle_ops.expand_column_hist(col_hist, totals, idx,
                                             meta[5], meta[2])

    def numerical_best(col_hist, totals, sg, sh, cnt, mn, mx, fmask):
        outs = []
        for ids, idx, meta in num_planes:
            nb_k, miss_k, def_k, mono_k, pen_k, _ = meta
            if ids is None:
                fmask_k = fmask & ~is_cat if has_cat else fmask
            else:
                fmask_k = jnp.take(fmask, ids)
            outs.append(split_ops.per_feature_best(
                plane_of(col_hist, totals, idx, meta), sg, sh, cnt, nb_k,
                miss_k, def_k, fmask_k, mono_k, mn, mx, pen_k, None,
                **scan_kwargs))
        mat = functools.partial(split_ops.materialize_split,
                                min_constraint=mn, max_constraint=mx,
                                l1=l1, l2=l2, max_delta_step=max_delta_step)
        if inv is None:
            rel, t, use_m1, prefix = outs[0]
            feat = jnp.argmax(rel).astype(jnp.int32)
            return mat(feat, rel, t, use_m1, prefix)
        # back to feature order (categorical features last in `inv`),
        # then the winner from its own class's prefix tensors
        rels = [o[0] for o in outs]
        if cat_plane is not None:
            rels.append(jnp.full(cat_plane[0].shape, NEG_INF, jnp.float32))
        feat = jnp.argmax(jnp.concatenate(rels)[inv]).astype(jnp.int32)
        pos = inv[feat]
        res, start = None, 0
        for rel_k, t_k, m1_k, prefix_k in outs:
            f_k = rel_k.shape[0]
            r = mat(jnp.clip(pos - start, 0, f_k - 1), rel_k, t_k, m1_k,
                    prefix_k)
            res = r if res is None else jax.tree.map(
                functools.partial(jnp.where, pos >= start), r, res)
            start += f_k
        if res is None:           # every feature is categorical
            z = jnp.float32(0.0)
            return split_ops.SplitResult(
                jnp.float32(NEG_INF), feat, jnp.int32(0), jnp.bool_(False),
                z, z, z, z, z, z, z, z)
        return res._replace(feature=feat)

    @jax.named_scope("lgbm.split_scan")
    def scan(col_hist, sg, sh, cnt, mn, mx, fmask):
        if dequant is not None:
            col_hist = dequant(col_hist)
        totals = jnp.stack([sg, sh, cnt])
        res = numerical_best(col_hist, totals, sg, sh, cnt, mn, mx, fmask)
        if cat_plane is None:
            return res, jnp.zeros((cat_b,), jnp.float32)
        ids, idx, meta = cat_plane
        nb_c, miss_c, _, _, pen_c, _ = meta
        hist = plane_of(col_hist, totals, idx, meta)
        fmask_c = fmask & is_cat if ids is None else jnp.take(fmask, ids)
        crel, caux = split_ops.per_feature_best_categorical(
            hist, sg, sh, cnt, nb_c, miss_c, fmask_c,
            mn, mx, pen_c, **cat_kwargs)
        cfeat = jnp.argmax(crel).astype(jnp.int32)
        cres = split_ops.materialize_cat_split(
            cfeat, crel, caux, hist, sg, sh, cnt, mn, mx,
            l1=l1, l2=l2, cat_l2=cat_l2, max_delta_step=max_delta_step)
        if ids is not None:
            cres = cres._replace(feature=jnp.take(ids, cfeat))
        return _merge_num_cat(res, cres)

    def _best_row(res: split_ops.SplitResult, child_depth) -> jax.Array:
        gain = res.gain
        if max_depth > 0:
            gain = jnp.where(child_depth >= max_depth, NEG_INF, gain)
        return jnp.stack([
            gain, res.feature.astype(jnp.float32),
            res.threshold.astype(jnp.float32),
            res.default_left.astype(jnp.float32),
            res.left_sum_grad, res.left_sum_hess, res.left_count,
            res.right_sum_grad, res.right_sum_hess, res.right_count,
            res.left_output, res.right_output])

    def store_best(best: jax.Array, best_cat: jax.Array, i,
                   res: split_ops.SplitResult, cm, child_depth):
        return (best.at[i].set(_best_row(res, child_depth)),
                best_cat.at[i].set(cm))

    def scan2(col_hist2, sg2, sh2, cnt2, mn2, mx2, keys2):
        """Both children's split scans in one vectorized pass."""
        fmask2 = jax.vmap(node_mask)(keys2)
        return jax.vmap(scan)(col_hist2, sg2, sh2, cnt2, mn2, mx2, fmask2)

    return node_mask, scan, store_best, scan2, _best_row


def search2_simple(scan2, best_row):
    """The unsharded 2-child search: scan both children, format best
    rows. Sharded modes replace this with election-aware variants of the
    same signature (search2_rows in grow_tree_compact_core)."""
    def search2(col_hist2, sg2, sh2, cnt2, mn2, mx2, keys2, child_depth):
        res2, cm2 = scan2(col_hist2, sg2, sh2, cnt2, mn2, mx2, keys2)
        rows = jax.vmap(
            functools.partial(best_row, child_depth=child_depth))(res2)
        return rows, cm2
    return search2


@jax.named_scope("lgbm.split_epilogue")
def split_epilogue(*, k, key, l, new_id, row, feat, f_monotone,
                   leaf_min, leaf_max, depth, rec, rec_cat, best, best_cat,
                   hist_l, hist_r, search2):
    """The split bookkeeping every growth strategy shares (one copy;
    divergence here silently forks the strategies): monotone-constraint
    propagation (basic mode, serial_tree_learner.cpp:771-852), depth
    update, split-record append, and the two children's re-scan via
    `search2` (which carries the sharded modes' election when present;
    the scan itself is the `lgbm.split_scan` stage inside this one).
    Returns the updated (key, leaf_min, leaf_max, depth, rec, rec_cat,
    best, best_cat)."""
    mono_f = f_monotone[feat]
    best_cat_l = best_cat[l]
    mid = (row[B_LOUT] + row[B_ROUT]) * 0.5
    pmin, pmax = leaf_min[l], leaf_max[l]
    lmin = jnp.where(mono_f < 0, jnp.maximum(pmin, mid), pmin)
    lmax = jnp.where(mono_f > 0, jnp.minimum(pmax, mid), pmax)
    rmin = jnp.where(mono_f > 0, jnp.maximum(pmin, mid), pmin)
    rmax = jnp.where(mono_f < 0, jnp.minimum(pmax, mid), pmax)
    leaf_min = leaf_min.at[l].set(lmin).at[new_id].set(rmin)
    leaf_max = leaf_max.at[l].set(lmax).at[new_id].set(rmax)
    child_depth = depth[l] + 1
    depth = depth.at[l].set(child_depth).at[new_id].set(child_depth)

    rec_row = jnp.concatenate([
        jnp.stack([l.astype(jnp.float32), row[B_FEAT], row[B_THR],
                   row[B_DLEFT], row[B_GAIN]]),
        row[B_LSG:]])
    rec = rec.at[k].set(rec_row)
    rec_cat = rec_cat.at[k].set(best_cat_l)

    key, kl, kr = jax.random.split(key, 3)
    rows2, cm2 = search2(jnp.stack([hist_l, hist_r]),
                         jnp.stack([row[B_LSG], row[B_RSG]]),
                         jnp.stack([row[B_LSH], row[B_RSH]]),
                         jnp.stack([row[B_LCNT], row[B_RCNT]]),
                         jnp.stack([lmin, rmin]), jnp.stack([lmax, rmax]),
                         jnp.stack([kl, kr]), child_depth)
    i2 = jnp.stack([l, new_id])
    best = best.at[i2].set(rows2)
    best_cat = best_cat.at[i2].set(cm2)
    return key, leaf_min, leaf_max, depth, rec, rec_cat, best, best_cat


@functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "num_bins", "col_bins", "max_depth",
                     "bynode_k", "use_pallas", "cat_statics", "quant_bits",
                     "hist_chunk", "grow_program"))
def grow_tree(codes_t: jax.Array,         # (C, N) column codes (EFB view)
              grad: jax.Array, hess: jax.Array,   # (N,)
              w: jax.Array,               # (N,) bagging weight (0/1)
              base_mask: jax.Array,       # (F,) bool feature sample
              f_numbins, f_missing, f_default, f_monotone,  # (F,) int32
              f_penalty,                  # (F,) f32 gain multipliers
              f_categorical,              # (F,) int32 1 = categorical
              f_col, f_base, f_elide,     # (F,) int32 EFB maps
              scan_plan,                  # ops/bundle.py split_scan_plan
              rng_key,                    # PRNG key for by-node sampling
              *, num_leaves: int, num_bins: int, col_bins: int,
              max_depth: int,
              l1: float, l2: float, max_delta_step: float,
              min_data_in_leaf: int, min_sum_hessian: float,
              min_gain_to_split: float, bynode_k: int, use_pallas: bool,
              cat_statics=None, quant_bits: int = 0, hist_chunk: int = 0,
              grow_program: str = "per_split"):
    c_cols, n = codes_t.shape
    f = f_numbins.shape[0]
    L = num_leaves
    has_cat = cat_statics is not None
    cat_b = num_bins if has_cat else 1
    # quant_bits > 0 switches the whole histogram pipeline to the
    # quantized-gradient formulation (ops/quantize.py): the gh operand,
    # the pool and the sibling subtraction are EXACT int32, and the split
    # scans dequantize with the iteration's scales. The jit cache keys on
    # quant_bits (the hist dtype), so the float program is untouched.
    if quant_bits:
        rng_key, qkey = jax.random.split(rng_key)
        packed, s_g, s_h = quant_ops.quantize_gh_core(
            grad * w, hess * w, qkey, grad_bits=quant_bits)
        gh = quant_ops.gh_operand(packed, w > 0, quant_bits)  # (N, 3) int
        scale3 = quant_ops.dequant_scale3(s_g, s_h)

        def dequant(hq):
            return hq.astype(jnp.float32) * scale3

        def hist_fn(ghx):
            return _hist_t_q(codes_t, ghx, col_bins, use_pallas, hist_chunk)
    else:
        gh = jnp.stack([grad * w, hess * w, w], axis=1)     # (N, 3)
        dequant = None

        def hist_fn(ghx):
            return _hist_t(codes_t, ghx, col_bins, use_pallas, hist_chunk)
    node_mask, scan, store_best, scan2, best_row = _tree_helpers(
        base_mask, f_numbins, f_missing, f_default, f_monotone, f_penalty,
        f_elide, scan_plan,
        num_bins=num_bins, max_depth=max_depth, l1=l1, l2=l2,
        max_delta_step=max_delta_step, min_data_in_leaf=min_data_in_leaf,
        min_sum_hessian=min_sum_hessian, min_gain_to_split=min_gain_to_split,
        bynode_k=bynode_k, f_categorical=f_categorical,
        cat_statics=cat_statics, dequant=dequant)

    # ---- root ------------------------------------------------------------
    with jax.named_scope("lgbm.root_hist"):
        hist0 = hist_fn(gh)
        totals = hist0[0].sum(axis=0)           # (3,): sum_g, sum_h, cnt
        if quant_bits:
            totals = dequant(totals)
    root_key, loop_key = jax.random.split(rng_key)
    root_res, root_cm = scan(hist0, totals[0], totals[1], totals[2],
                             jnp.float32(-np.inf), jnp.float32(np.inf),
                             node_mask(root_key))

    best = jnp.full((L, 12), NEG_INF, jnp.float32) \
        .at[:, B_FEAT:].set(0.0)
    best_cat = jnp.zeros((L, cat_b), jnp.float32)
    # the depth argument is the stored leaf's own depth (a leaf at depth d
    # may split iff d < max_depth, reference _splittable); root sits at 0
    best, best_cat = store_best(best, best_cat, 0, root_res, root_cm,
                                jnp.int32(0))
    # pool dtype follows the histogram dtype: int32 on the quantized path
    # (parent - child below is then bit-exact integer subtraction)
    pool = jnp.zeros((L, c_cols, col_bins, 3), hist0.dtype).at[0].set(hist0)
    rec = jnp.zeros((L - 1, 13), jnp.float32)
    zi = functools.partial(jnp.zeros, dtype=jnp.int32)
    carry = _Carry(
        k=jnp.int32(0), leaf_id=jnp.zeros(n, jnp.int32), pool=pool,
        depth=zi(L),
        leaf_min=jnp.full((L,), -np.inf, jnp.float32),
        leaf_max=jnp.full((L,), np.inf, jnp.float32),
        best=best, best_cat=best_cat, rec=rec,
        rec_cat=jnp.zeros((L - 1, cat_b), jnp.float32), key=loop_key)

    def cond(c: _Carry):
        return (c.k < L - 1) & (jnp.max(c.best[:, B_GAIN]) > 1e-10)

    def body(c: _Carry) -> _Carry:
        with jax.named_scope("lgbm.leaf_select"):
            b = c.best
            l = jnp.argmax(b[:, B_GAIN]).astype(jnp.int32)
            row = b[l]
            new_id = c.k + 1
            feat = row[B_FEAT].astype(jnp.int32)
            thr = row[B_THR].astype(jnp.int32)
            dleft = row[B_DLEFT] > 0.5

        with jax.named_scope("lgbm.go_left"):
            col = jax.lax.dynamic_slice_in_dim(
                codes_t, f_col[feat], 1, axis=0)[0]
            fbins = bundle_ops.logical_bins_for_feature(
                col.astype(jnp.int32), f_base[feat], f_default[feat],
                f_numbins[feat], f_elide[feat])
            go_left = decide_left(fbins, thr, dleft, f_missing[feat],
                                  f_default[feat], f_numbins[feat])
            if has_cat:
                # categorical routing: left iff the row's logical bin is in
                # the winning left-bin mask (CategoricalDecisionInner)
                cmask = c.best_cat[l]
                cat_left = cmask[jnp.clip(fbins, 0, cat_b - 1)] > 0.5
                go_left = jnp.where(f_categorical[feat] != 0, cat_left,
                                    go_left)
        with jax.named_scope("lgbm.partition"):
            parent = c.leaf_id == l
            lmask = parent & go_left
            leaf_id = jnp.where(parent & ~go_left, new_id, c.leaf_id)

        with jax.named_scope("lgbm.child_hist"):
            ghl = gh * lmask[:, None].astype(gh.dtype)
            hist_l = hist_fn(ghl)
            hist_r = c.pool[l] - hist_l
            pool = c.pool.at[l].set(hist_l).at[new_id].set(hist_r)

        (key, leaf_min, leaf_max, depth, rec2, rec_cat2, best2,
         best_cat2) = split_epilogue(
            k=c.k, key=c.key, l=l, new_id=new_id, row=row,
            feat=feat, f_monotone=f_monotone,
            leaf_min=c.leaf_min, leaf_max=c.leaf_max, depth=c.depth,
            rec=c.rec, rec_cat=c.rec_cat, best=b, best_cat=c.best_cat,
            hist_l=hist_l, hist_r=hist_r,
            search2=search2_simple(scan2, best_row))
        return _Carry(new_id, leaf_id, pool, depth, leaf_min, leaf_max,
                      best2, best_cat2, rec2, rec_cat2, key)

    out = run_split_loop(cond, body, carry, L - 1, grow_program)
    return (out.rec, out.rec_cat if has_cat else None,
            out.leaf_id, out.k, totals)


class _CarryC(NamedTuple):
    k: jax.Array
    data: jax.Array          # (N + Wmax, D) u32 packed rows grouped by leaf
    pos_leaf: jax.Array      # (N + Wmax,) leaf id per physical POSITION
    leaf_begin: jax.Array    # (L,)
    leaf_phys: jax.Array     # (L,) physical rows in the window
    pool: jax.Array          # (K, C, B, 3) — K == L unless slot-capped
    slot_of: jax.Array       # (L,) pool slot of each leaf, -1 = evicted
    slot_owner: jax.Array    # (K,) leaf owning each slot, -1 = free
    slot_last: jax.Array     # (K,) last-use step per slot (LRU clock)
    depth: jax.Array
    leaf_min: jax.Array
    leaf_max: jax.Array
    best: jax.Array          # (L, 12) f32
    best_cat: jax.Array      # (L, B|1) f32 0/1 left-bin masks
    rec: jax.Array           # (L-1, 13) f32
    rec_cat: jax.Array       # (L-1, B|1) f32
    key: jax.Array


# Ratio of one rung of the compact core's window ladder to the next. A
# smaller step = tighter windows (less wasted per-split work, ~step/2 mean
# inflation) but more traced rungs (compile time). 2 measured fastest on
# the chip (754k vs 679k row-trees/s at step 4, 1M x 255 leaves:
# docs/DESIGN.md 6a-r3) and is what both benchmark cells run. It is no
# static of the jitted growth programs: a test that changes it clears
# their caches, as for SCATTER_TILE_ROWS.
WINDOW_STEP = 2


def _size_classes(n: int, min_bucket: int = 4096):
    """Padded window-size ladder of the compact core's split dispatch:
    min_bucket, then WINDOW_STEP times the last while under n, then n."""
    ws = []
    wcur = min_bucket
    while wcur < n:
        ws.append(wcur)
        wcur *= WINDOW_STEP
    ws.append(n)
    return ws


def rung_rows(pcount, lphys, left_small, ladder, tile_rows: int, chunk: int,
              bounded: bool = True):
    """(rows run, rows needed) by the two step loops of the compact
    core's rungs over a tree's splits, on the host from what a rung
    holds: `pcount` the parent's rows on this device, `lphys` those that
    go left, `left_small` whether the left child is the one that is
    histogrammed. Run: the rows of the partition's tiles (the whole
    window where it is one scatter) plus the rows of the histogram's
    chunks (the half window, or the whole one where the smaller side
    does not fit it; all of it where it is one product), with the loops'
    trip counts as `_scan_partition_tiled` and
    ops/histogram.py::build_histogram_range read them from the split;
    `bounded=False` gives what the loops ran when they went to the
    rung's static width (before PR 35). Needed: the parent's rows plus
    the histogrammed child's, the work model's own counts. A pooled
    miss's second histogram is not in the records and is not counted."""
    pcount = np.asarray(pcount, np.int64)
    lphys = np.asarray(lphys, np.int64)
    ladder = np.asarray(ladder, np.int64)
    wsz = ladder[np.minimum(np.searchsorted(ladder, pcount), len(ladder) - 1)]
    s_begin = np.where(left_small, 0, lphys)
    s_count = np.where(left_small, lphys, pcount - lphys)
    tiles = wsz // tile_rows
    if bounded:
        # whole tiles up to the last that holds a row of the leaf; the
        # ragged last step of the top rung only past them
        tiled = np.where(pcount > tiles * tile_rows, wsz,
                         -(-pcount // tile_rows) * tile_rows)
    else:
        tiled = wsz
    partition = np.where(wsz <= tile_rows, wsz, tiled)
    half = (wsz + 1) // 2
    in_half = s_count <= half
    rows = np.where(in_half, half, wsz)
    off = np.where(in_half, s_begin - np.clip(s_begin, 0, wsz - half),
                   s_begin)
    n_chunks = -(-rows // chunk)
    if bounded:
        first = np.minimum(off // chunk, n_chunks - 1)
        stop = np.minimum(-(-(off + s_count) // chunk), n_chunks)
        n_chunks = np.maximum(stop - first, 1)
    histogram = np.where(rows <= chunk, rows, n_chunks * chunk)
    return (float(partition.sum() + histogram.sum()),
            float((pcount + s_count).sum()))


def _unpack_codes(words: jax.Array, c_cols: int, item_bits: int) -> jax.Array:
    """(W, CW) u32 packed codes -> (W, c_cols) i32."""
    per = 32 // item_bits
    shifts = (jnp.arange(per, dtype=jnp.uint32) * item_bits)[None, None, :]
    u = (words[:, :, None] >> shifts) & jnp.uint32((1 << item_bits) - 1)
    return u.reshape(words.shape[0], words.shape[1] * per)[:, :c_cols] \
            .astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("c_cols", "item_bits",
                     "num_leaves", "num_bins", "col_bins", "max_depth",
                     "bynode_k", "use_pallas",
                     "pool_slots", "trivial_weights",
                     "cat_statics", "quant_bits", "quant_renew",
                     "grow_program"))
def grow_tree_compact(
        codes_pack: jax.Array,       # (N, CW) u32: packed column codes
        codes_row: jax.Array,        # (N, C) u8/u16 for the root pass
        grad: jax.Array, hess: jax.Array, w: jax.Array,
        base_mask: jax.Array,
        f_numbins, f_missing, f_default, f_monotone, f_penalty,
        f_categorical, f_col, f_base, f_elide, scan_plan, rng_key,
        *, c_cols: int, item_bits: int,
        num_leaves: int, num_bins: int, col_bins: int, max_depth: int,
        l1: float, l2: float, max_delta_step: float,
        min_data_in_leaf: int, min_sum_hessian: float,
        min_gain_to_split: float, bynode_k: int, use_pallas: bool,
        pool_slots: int = 0,
        trivial_weights: bool = False, cat_statics=None,
        quant_bits: int = 0, quant_renew: bool = True,
        grow_program: str = "per_split"):
    return grow_tree_compact_core(
        codes_pack, codes_row, grad, hess, w, base_mask,
        f_numbins, f_missing, f_default, f_monotone, f_penalty,
        f_categorical, f_col, f_base, f_elide, scan_plan, rng_key,
        c_cols=c_cols, item_bits=item_bits, num_leaves=num_leaves,
        num_bins=num_bins, col_bins=col_bins, max_depth=max_depth,
        l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf, min_sum_hessian=min_sum_hessian,
        min_gain_to_split=min_gain_to_split, bynode_k=bynode_k,
        use_pallas=use_pallas,
        axis_name=None, pool_slots=pool_slots,
        trivial_weights=trivial_weights,
        cat_statics=cat_statics, quant_bits=quant_bits,
        quant_renew=quant_renew, grow_program=grow_program)


def make_voting_search(*, axis_name, voting_k, c_cols, col_bins,
                       base_mask, f_numbins, f_missing, f_default,
                       f_monotone, f_penalty, f_elide,
                       f_categorical, has_cat, cat_statics,
                       helper_kwargs):
    """PV-Tree 2-stage voting reduction + search, shared by the
    compact and chunk growth cores (the voting seam of
    voting_parallel_tree_learner.cpp:170-260): per split, every
    shard scans its LOCAL histograms with 1/D-scaled data gates,
    votes for its top-k features, the vote psum elects 2k global
    candidates, and ONLY the elected features' histograms are
    reduced — O(2k*B) communication per split instead of O(F*B).
    Deterministic and replicated on every shard, so no best-split
    broadcast is needed. Returns (reduce_hist, search_row,
    search2_rows); reduce_hist is the identity (histograms stay
    local until election)."""
    num_bins = helper_kwargs["num_bins"]
    l1 = helper_kwargs["l1"]
    l2 = helper_kwargs["l2"]
    max_delta_step = helper_kwargs["max_delta_step"]
    min_data_in_leaf = helper_kwargs["min_data_in_leaf"]
    min_sum_hessian = helper_kwargs["min_sum_hessian"]
    min_gain_to_split = helper_kwargs["min_gain_to_split"]
    cat_b = num_bins if has_cat else 1
    f_all = int(f_numbins.shape[0])
    assert f_all == c_cols, \
        "voting mode requires identity feature->column mapping"
    n_elect = min(2 * voting_k, f_all)
    # the reference scales the local gates by machine count
    # (voting_parallel_tree_learner.cpp:57-59)
    d_v = jax.lax.psum(1, axis_name)
    (node_mask, _, _, _, best_row) = _tree_helpers(
        base_mask, f_numbins, f_missing, f_default, f_monotone,
        f_penalty, f_elide, None, **helper_kwargs)

    def identity_idx(nb):
        """(k, col_bins) expansion map of k features in columns 0..k-1
        of a flattened (k * col_bins + 1, 3) histogram."""
        k = nb.shape[0]
        bins = jnp.arange(col_bins, dtype=jnp.int32)[None, :]
        return jnp.where(
            bins < nb[:, None],
            jnp.arange(k, dtype=jnp.int32)[:, None] * col_bins + bins,
            k * col_bins)
    scan_kwargs_local = dict(
        num_bins=num_bins, l1=l1, l2=l2, max_delta_step=max_delta_step,
        # integer division for the count gate, exactly the
        # reference's local_config (voting_parallel:58-59)
        min_data_in_leaf=jnp.asarray(min_data_in_leaf,
                                     jnp.int32) // d_v,
        min_sum_hessian=min_sum_hessian / d_v,
        min_gain_to_split=min_gain_to_split)
    scan_kwargs_global = dict(
        num_bins=num_bins, l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf,
        min_sum_hessian=min_sum_hessian,
        min_gain_to_split=min_gain_to_split)
    if has_cat:
        # categorical candidates ride the same vote/elect/reduce
        # pipeline: local rel gains merge the categorical search
        # (scaled gates, like the numerical local config) and the
        # elected global scan re-runs both searches on the psum'd
        # histograms. Every shard computes the identical elected
        # scan, so the winning left-bin mask is replicated — no
        # mask transport is needed in voting mode.
        is_cat_v = f_categorical != 0
        cat_l2_v, cat_smooth_v, max_cat_threshold_v, \
            max_cat_to_onehot_v, min_data_per_group_v = cat_statics
        cat_extra = dict(
            cat_l2=cat_l2_v, cat_smooth=cat_smooth_v,
            max_cat_threshold=max_cat_threshold_v,
            max_cat_to_onehot=max_cat_to_onehot_v,
            min_data_per_group=min_data_per_group_v)
        cat_kwargs_local = dict(scan_kwargs_local, **cat_extra)
        cat_kwargs_global = dict(scan_kwargs_global, **cat_extra)

    def _local_rel(col_hist_l, fmask):
        """Per-feature local best gains from the shard's histograms."""
        lt = col_hist_l[0].sum(axis=0)        # local (sg, sh, cnt)
        hist = bundle_ops.expand_column_hist(
            col_hist_l, lt, identity_idx(f_numbins), f_elide, f_default)
        rel, _, _, _ = split_ops.per_feature_best(
            hist, lt[0], lt[1], lt[2], f_numbins, f_missing, f_default,
            fmask & ~is_cat_v if has_cat else fmask, f_monotone,
            jnp.float32(-np.inf),
            jnp.float32(np.inf), f_penalty, None, **scan_kwargs_local)
        if has_cat:
            crel, _ = split_ops.per_feature_best_categorical(
                hist, lt[0], lt[1], lt[2], f_numbins, f_missing,
                fmask & is_cat_v, jnp.float32(-np.inf),
                jnp.float32(np.inf), f_penalty, **cat_kwargs_local)
            rel = jnp.maximum(rel, crel)
        return rel                            # (F,)

    def _vote(rel):
        """Exactly-k vote mask from local rel gains (lax.top_k ties
        break by index, same as the host learner — a >=kth threshold
        would let gain ties cast extra votes)."""
        _, top_idx = jax.lax.top_k(rel, min(voting_k, f_all))
        return jnp.zeros(f_all, jnp.float32).at[top_idx].add(
            jnp.where(rel[top_idx] > NEG_INF / 2, 1.0, 0.0))

    def _elected_scan(col_hist_l, elect, sg, sh, cnt, mn, mx, fmask,
                      child_depth):
        """Reduce elected features' histograms and find the winner."""
        hist_e = jax.lax.psum(jnp.take(col_hist_l, elect, axis=0),
                              axis_name)      # (2k, B, 3) global
        nb_e = jnp.take(f_numbins, elect)
        hist_f = bundle_ops.expand_column_hist(
            hist_e, jnp.stack([sg, sh, cnt]), identity_idx(nb_e),
            jnp.take(f_elide, elect), jnp.take(f_default, elect))
        fmask_e = jnp.take(fmask, elect)
        if has_cat:
            is_cat_e = jnp.take(is_cat_v, elect)
        rel, t, use_m1, prefix = split_ops.per_feature_best(
            hist_f, sg, sh, cnt, nb_e, jnp.take(f_missing, elect),
            jnp.take(f_default, elect),
            fmask_e & ~is_cat_e if has_cat else fmask_e,
            jnp.take(f_monotone, elect), mn, mx,
            jnp.take(f_penalty, elect), None, **scan_kwargs_global)
        fe = jnp.argmax(rel).astype(jnp.int32)
        res = split_ops.materialize_split(
            fe, rel, t, use_m1, prefix, mn, mx,
            l1=l1, l2=l2, max_delta_step=max_delta_step)
        if has_cat:
            crel, caux = split_ops.per_feature_best_categorical(
                hist_f, sg, sh, cnt, nb_e, jnp.take(f_missing, elect),
                fmask_e & is_cat_e, mn, mx,
                jnp.take(f_penalty, elect), **cat_kwargs_global)
            cfe = jnp.argmax(crel).astype(jnp.int32)
            cres = split_ops.materialize_cat_split(
                cfe, crel, caux, hist_f, sg, sh, cnt, mn, mx,
                l1=l1, l2=l2, cat_l2=cat_l2_v,
                max_delta_step=max_delta_step)
            res, cm = _merge_num_cat(res, cres)
        else:
            cm = jnp.zeros((cat_b,), jnp.float32)
        row = best_row(res, child_depth)
        # map the elected-subset index back to the real feature id
        sub_f = res.feature.astype(jnp.int32)
        return row.at[B_FEAT].set(
            jnp.take(elect, sub_f).astype(jnp.float32)), cm

    def reduce_hist(h):
        return h                               # stays local

    def search_row(col_hist, sg, sh, cnt, mn, mx, key, child_depth):
        fmask = node_mask(key)
        rel = _local_rel(col_hist, fmask)
        votes = jax.lax.psum(_vote(rel), axis_name)
        elect = jnp.argsort(
            -votes, stable=True)[:n_elect].astype(jnp.int32)
        return _elected_scan(col_hist, elect, sg, sh, cnt, mn, mx,
                             fmask, child_depth)

    # batched 2-child elected reduction: ONE (2, 2k, B, 3) psum per
    # split instead of two sequential ones — half the collective
    # latency on real ICI. XLA:CPU's collective rendezvous fatally
    # aborts on the batched form under the virtual mesh (hard 40s
    # timeout, observed round 2), so the choice is keyed on the backend.
    voting_batched = jax.default_backend() == "tpu"

    def search2_rows(col_hist2, sg2, sh2, cnt2, mn2, mx2, keys2,
                     child_depth):
        fmask2 = jax.vmap(node_mask)(keys2)
        rel2 = jax.vmap(_local_rel)(col_hist2, fmask2)
        votes2 = jax.lax.psum(jax.vmap(_vote)(rel2), axis_name)
        elect2 = jnp.argsort(
            -votes2, axis=1,
            stable=True)[:, :n_elect].astype(jnp.int32)
        if voting_batched:
            rows2, cm2 = jax.vmap(
                _elected_scan,
                in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None))(
                col_hist2, elect2, sg2, sh2, cnt2, mn2, mx2, fmask2,
                child_depth)
        else:
            pairs = [
                _elected_scan(col_hist2[i], elect2[i], sg2[i], sh2[i],
                              cnt2[i], mn2[i], mx2[i], fmask2[i],
                              child_depth)
                for i in range(2)]
            rows2 = jnp.stack([p[0] for p in pairs])
            cm2 = jnp.stack([p[1] for p in pairs])
        return rows2, cm2
    return reduce_hist, search_row, search2_rows


def _quant_prepare(grad, hess, w, rng_key, *, quant_bits, quant_renew,
                   n_total, axis_name):
    """Quantized working-row preparation shared by the compact and chunk
    cores: split the RNG exactly like the masked strategy does (so a
    renew-off run quantizes bit-identically to it), discretize
    (grad*w, hess*w) at the STORAGE resolution (16-bit under leaf
    re-quantization — the packed word's field width, free bits — else
    grad_bits), and, when renewing, measure the root's stored-int maxes
    for the initial requant ratio (pmax'd so every shard agrees).

    Returns (rng_key, packed (N,) int32, s_g, s_h, root_max (2,) f32 or
    None)."""
    rng_key, qkey = jax.random.split(rng_key)
    sbits = quant_ops.storage_bits(quant_bits, quant_renew)
    if axis_name is not None:
        packed, s_g, s_h = quant_ops.quantize_gh_pmax(
            grad * w, hess * w, qkey, grad_bits=sbits, n_total=n_total,
            axis_name=axis_name)
    else:
        packed, s_g, s_h = quant_ops.quantize_gh_core(
            grad * w, hess * w, qkey, grad_bits=sbits)
    if not quant_renew:
        return rng_key, packed, s_g, s_h, None
    qg, qh = quant_ops.unpack_gh(packed)
    m = jnp.stack([jnp.max(jnp.abs(qg)), jnp.max(jnp.abs(qh))]) \
        .astype(jnp.float32)
    if axis_name is not None:
        m = jax.lax.pmax(m, axis_name)
    return rng_key, packed, s_g, s_h, m


def _quant_gh_words(packed: jax.Array, w: jax.Array,
                    gw: int) -> jax.Array:
    """The working row's gh section: ONE u32 word (the packed (qg|qh)
    lane) when weights are trivial, or two words (packed | 0/1 weight)
    when pad/out-of-bag rows must be fenced out of the count lane —
    either way 1-2 words where the float layout bitcasts three."""
    pk = jax.lax.bitcast_convert_type(packed, jnp.uint32)[:, None]
    if gw == 1:
        return pk
    return jnp.concatenate([pk, (w > 0).astype(jnp.uint32)[:, None]],
                           axis=1)


def _quant_win_operand(win, vmask, *, cw, gw, quant_bits, qcap_op,
                       r_g, r_h):
    """(W, 3) integer histogram operand from a packed row window: the
    stored (qg|qh) word re-quantized to the leaf's ratio (1.0 = fixed
    root scale). The weighted layout folds the 0/1 weight word into the
    validity mask so w=0 rows stay off the count lane."""
    pk = jax.lax.bitcast_convert_type(win[:, cw], jnp.int32)
    if gw == 2:
        vmask = vmask & (win[:, cw + 1] != 0)
    return quant_ops.gh_operand_scaled(pk, vmask, quant_bits, qcap_op,
                                       r_g, r_h)


def _quant_side_maxes(win, go_left, vmask, *, cw, gw):
    """(2, 2) f32 [[max|qg|, max|qh|] left, [..] right] over a window's
    valid rows — measured during the partition pass (which reads every
    parent row anyway) to seed each child's leaf-local requant ratio."""
    pk = jax.lax.bitcast_convert_type(win[:, cw], jnp.int32)
    qg, qh = quant_ops.unpack_gh(pk)
    if gw == 2:
        vmask = vmask & (win[:, cw + 1] != 0)
    a = jnp.stack([jnp.abs(qg), jnp.abs(qh)], axis=1).astype(jnp.float32)
    left = jnp.max(jnp.where((go_left & vmask)[:, None], a, 0.0), axis=0)
    right = jnp.max(jnp.where((~go_left & vmask)[:, None], a, 0.0), axis=0)
    return jnp.stack([left, right])


def make_scatter_reduce_q(axis_name, D, c_cols, wire):
    """Quantized rendering of the DP scatter mode's histogram collective
    (the reference's ReduceScatter, data_parallel_tree_learner.cpp:149-
    164): psum_scatter TWO integer lanes [sum_qg, sum_qh] — int16 wire
    when the shard-sum bound quant_max * N fits (1/3 the f32 triple's
    bytes), int32 otherwise (2/3) — and reconstruct the count lane from
    the hessian lane via the leaf's replicated global count:
    cnt_bin = round(qh_bin * leaf_n / qh_tot). Exact for constant-
    hessian objectives; for varying hessians the min_data gate becomes
    approximate — the same class of deviation the host DP learner's
    compact allreduce documents."""
    cs = -(-c_cols // D)
    c_pad = cs * D

    def reduce_q(h_int, leaf_n, qh_tot_q):
        payload = h_int[:, :, :2].astype(wire)
        payload = jnp.pad(payload, ((0, c_pad - c_cols), (0, 0), (0, 0)))
        sl = jax.lax.psum_scatter(payload, axis_name, scatter_dimension=0,
                                  tiled=True).astype(jnp.int32)
        cnt = jnp.round(sl[:, :, 1].astype(jnp.float32)
                        * (leaf_n / jnp.maximum(qh_tot_q, 1.0))) \
            .astype(jnp.int32)
        return jnp.concatenate([sl, cnt[:, :, None]], axis=2)
    return reduce_q


def grow_tree_compact_core(
        codes_pack: jax.Array, codes_row: jax.Array,
        grad: jax.Array, hess: jax.Array, w: jax.Array,
        base_mask: jax.Array,
        f_numbins, f_missing, f_default, f_monotone, f_penalty,
        f_categorical, f_col, f_base, f_elide, scan_plan, rng_key,
        *, c_cols: int, item_bits: int,
        num_leaves: int, num_bins: int, col_bins: int, max_depth: int,
        l1: float, l2: float, max_delta_step: float,
        min_data_in_leaf: int, min_sum_hessian: float,
        min_gain_to_split: float, bynode_k: int, use_pallas: bool,
        axis_name=None, pool_slots: int = 0, scatter_cols: int = 0,
        feature_shards: int = 0, voting_k: int = 0,
        trivial_weights: bool = False, cat_statics=None,
        quant_bits: int = 0, quant_renew: bool = True,
        quant_total_rows: int = 0, grow_program: str = "per_split"):
    """Compaction-based whole-tree growth: O(leaf-size) work per split.

    The masked strategy in grow_tree pays a full O(N) histogram pass per
    split — ruinous at Higgs scale. This variant keeps the reference's
    DataPartition idea (data_partition.hpp:20-205) on device, but instead
    of a permutation of row IDS it physically reorders one packed
    (N, CW + 4) u32 buffer (bit-packed codes | bitcast grad,hess,weight |
    row id). Random access is latency-bound on TPU (~14ns/row regardless
    of width), so moving WHOLE rows once per split costs the same as
    moving bare indices — and then every window read (feature column,
    histogram input, gh) is a contiguous dynamic_slice at HBM bandwidth
    instead of a full-table gather. The histogram is built from the
    SMALLER child's contiguous half-window after the partition (sibling =
    parent - smaller, FeatureHistogram::Subtract). Dynamic leaf sizes meet
    XLA's static shapes through a small ladder of padded window classes
    (x4 steps), each traced once: the rungs run one after another, each
    a `while` of one trip or none (ops/fused.py run_once_if), so that
    the packed buffer is a loop's carry from the tree's first split to
    its last and every window is written back in place. (Dispatched
    with lax.switch, the buffer crossed a `conditional` and the TPU
    compiler copied all of it once a split on the way in and once on
    the way out: PERF.md §6, PR 30.)

    pool_slots caps the histogram pool at K slots with on-device LRU
    eviction — the role of the reference's HistogramPool
    (src/treelearner/feature_histogram.hpp:654-831), which lets
    num_leaves scale far past pool memory. On a parent-histogram miss
    the sibling is rebuilt by a direct masked pass over the larger
    child's window instead of the subtraction trick. 0 = dense (one
    slot per leaf, no evictions ever).

    scatter_cols (= shard count, 0 = off) switches the data-parallel
    histogram reduction from replicating psum to the reference's comm
    pattern (data_parallel_tree_learner.cpp:149-200): lax.psum_scatter
    tiles the column axis so each shard owns C/D columns of every
    histogram (pool memory /D, reduce traffic ~halved), runs the split
    scan on its slice only, and the global winner is elected from a
    tiny (D, 12) all_gather of per-shard candidates — the analog of
    SyncUpGlobalBestSplit. Requires identity column mapping (no EFB
    bundles) and no by-node feature sampling; callers gate on that.

    quant_bits > 0 switches the working row to the quantized layout:
    the gh section is ONE u32 (qg<<16|qh) word (trivial weights) or two
    (packed | 0/1 weight) — 2 words/row less transport than the f32
    triple on every partition move and histogram read — the pool is
    EXACT int32, sibling subtraction is integer, and the scans read
    leaf-dequantized f32 copies. quant_renew turns on leaf-wise
    re-quantization (rows stored at 16-bit, operands re-discretized to
    grad_bits per leaf range; see ops/quantize.py); off = fixed root
    scale, bit-identical to the masked strategy's quantization. In
    scatter mode the histogram collective becomes the two-integer-lane
    reduce-scatter of make_scatter_reduce_q. The float path's program
    is untouched (all layout switches are jit statics).
    """
    n = grad.shape[0]
    cw = codes_pack.shape[1]
    L = num_leaves
    has_cat = cat_statics is not None
    cat_b = num_bins if has_cat else 1
    # K=1 cannot hold both children of a split (the second allocation
    # would evict the first and corrupt the sibling subtraction)
    K = max(2, pool_slots) if 0 < pool_slots < L else L
    pooled = K < L
    quant = quant_bits > 0
    if not quant:
        gh = jnp.stack([grad * w, hess * w, w], axis=1)
    helper_kwargs = dict(
        num_bins=num_bins, max_depth=max_depth, l1=l1, l2=l2,
        max_delta_step=max_delta_step, min_data_in_leaf=min_data_in_leaf,
        min_sum_hessian=min_sum_hessian, min_gain_to_split=min_gain_to_split,
        bynode_k=bynode_k)
    scatter = (scatter_cols > 1 and axis_name is not None
               and feature_shards == 0)
    # feature-parallel: rows replicated, every shard builds histograms
    # ONLY for its column slice (no histogram collective at all — the
    # local slice over all rows IS the global histogram); the winner is
    # elected exactly like scatter mode (feature_parallel_tree_learner
    # .cpp:33-76 + SyncUpGlobalBestSplit role)
    fp = feature_shards > 1 and axis_name is not None
    voting = voting_k > 0 and axis_name is not None and not (scatter or fp)
    sliced = scatter or fp
    per_w = 32 // item_bits

    # quantized packed rows (quant_bits > 0): the gh section of the
    # working row is ONE u32 (qg<<16|qh) word (two under non-trivial
    # weights) instead of the three bitcast f32 words; histograms are
    # EXACT int32 from the integer contraction; scans dequantize at
    # leaf-local scales (quant_renew). Supported reductions: serial,
    # DP psum, DP scatter (int16/int32 two-lane reduce-scatter).
    assert not (quant and (voting or fp)), \
        "quantized packed rows: voting/feature-parallel modes fall back " \
        "to the host learners (create_tree_learner gates)"
    renew = quant and quant_renew
    if quant:
        n_total = quant_total_rows or n
        qcap_op = quant_ops.quant_max(quant_bits, n_total)
        rng_key, gh_packed, q_sg, q_sh, root_max = _quant_prepare(
            grad, hess, w, rng_key, quant_bits=quant_bits,
            quant_renew=quant_renew, n_total=n_total, axis_name=axis_name)
        gw = 1 if trivial_weights else 2

        def q_ratios(leaf_max):
            """(r_g, r_h) leaf-local operand rescale from stored maxes;
            fixed 1.0 when renewal is off."""
            if not renew:
                return jnp.float32(1.0), jnp.float32(1.0)
            return (quant_ops.requant_ratio(leaf_max[0], qcap_op),
                    quant_ops.requant_ratio(leaf_max[1], qcap_op))

        def q_dequant(h_int, r_g, r_h):
            return h_int.astype(jnp.float32) * quant_ops.dequant_scale3(
                q_sg * r_g, q_sh * r_h)

        if scatter:
            reduce_q = make_scatter_reduce_q(
                axis_name, scatter_cols, c_cols,
                quant_ops.wire_dtype(quant_bits, n_total))
    else:
        gw = 3

    if voting:
        reduce_hist, search_row, search2_rows = make_voting_search(
            axis_name=axis_name, voting_k=voting_k, c_cols=c_cols,
            col_bins=col_bins, base_mask=base_mask,
            f_numbins=f_numbins, f_missing=f_missing,
            f_default=f_default, f_monotone=f_monotone,
            f_penalty=f_penalty, f_elide=f_elide,
            f_categorical=f_categorical, has_cat=has_cat,
            cat_statics=cat_statics, helper_kwargs=helper_kwargs)
    elif not sliced:
        (node_mask, scan, store_best, scan2,
         best_row) = _tree_helpers(
            base_mask, f_numbins, f_missing, f_default, f_monotone,
            f_penalty, f_elide, scan_plan,
            f_categorical=f_categorical, cat_statics=cat_statics,
            **helper_kwargs)

        def reduce_hist(h):
            return jax.lax.psum(h, axis_name) if axis_name is not None else h

        def search_row(col_hist, sg, sh, cnt, mn, mx, key, child_depth):
            res, cm = scan(col_hist, sg, sh, cnt, mn, mx, node_mask(key))
            return best_row(res, child_depth), cm

        search2_rows = search2_simple(scan2, best_row)
    else:
        D = scatter_cols if scatter else feature_shards
        (reduce_hist, search_row, search2_rows, cs, shard,
         start) = make_sliced_search(
            axis_name=axis_name, fp=fp, D=D,
            c_cols=c_cols, col_bins=col_bins, item_bits=item_bits,
            base_mask=base_mask, f_numbins=f_numbins, f_missing=f_missing,
            f_default=f_default, f_monotone=f_monotone,
            f_penalty=f_penalty, f_elide=f_elide,
            f_categorical=f_categorical, has_cat=has_cat,
            cat_statics=cat_statics, helper_kwargs=helper_kwargs)

    hist_cols = cs if fp else c_cols   # width of branch-built histograms
    if fp:
        cs_words = cs // per_w
        assert cw >= cs_words * D, \
            "feature-parallel needs codes packed to the padded column count"
        w0 = (shard * cs_words).astype(jnp.int32)

        def decode_for_hist(words2d):
            wsl = jax.lax.dynamic_slice(
                words2d, (jnp.int32(0), w0), (words2d.shape[0], cs_words))
            return _unpack_codes(wsl, cs, item_bits)
    else:
        def decode_for_hist(words2d):
            return _unpack_codes(words2d[:, :cw], c_cols, item_bits)

    classes = _size_classes(n)
    wmax = classes[-1]
    thresholds = jnp.asarray(np.array(classes[:-1], np.int32))
    d_cols = cw + gw + 1

    # packed working buffer: codes | gh section | row id, padded by wmax
    # (gh section: three bitcast f32 words on the float path, one packed
    # int word — two with a weight word — on the quantized path); the
    # table's one write that is not a split's
    with jax.named_scope("lgbm.table_update"):
        if quant:
            gh_u = _quant_gh_words(gh_packed, w, gw)
        else:
            gh_u = jax.lax.bitcast_convert_type(gh, jnp.uint32)  # (N, 3)
        ids = jnp.arange(n, dtype=jnp.uint32)[:, None]
        data0 = jnp.concatenate([codes_pack, gh_u, ids], axis=1)
        data0 = jnp.concatenate(
            [data0, jnp.zeros((wmax, d_cols), jnp.uint32)], axis=0)

    # ---- root ------------------------------------------------------------
    from ..ops.histogram import (build_histogram, build_histogram_quantized,
                                 build_histogram_range, window_chunk)
    with jax.named_scope("lgbm.root_hist"):
        if quant:
            r0_g, r0_h = q_ratios(root_max) if renew else q_ratios(None)
            ghq0 = quant_ops.gh_operand_scaled(
                gh_packed, w > 0, quant_bits, qcap_op, r0_g, r0_h)
            hist0 = build_histogram_quantized(codes_row, ghq0, col_bins,
                                              use_pallas=use_pallas)
            if scatter:
                # exact global int totals first (3 scalars), then the
                # two-lane reduce-scatter with count reconstruction
                tot_q = jax.lax.psum(hist0[0].sum(axis=0), axis_name)
                totals = q_dequant(tot_q, r0_g, r0_h)
                hist0 = reduce_q(hist0, totals[2],
                                 tot_q[1].astype(jnp.float32))
            else:
                if axis_name is not None:
                    hist0 = jax.lax.psum(hist0, axis_name)
                totals = q_dequant(hist0[0].sum(axis=0), r0_g, r0_h)
            hist0_scan = q_dequant(hist0, r0_g, r0_h)
        elif fp:
            # rows are replicated: totals come straight from gh, and the
            # root histogram is built from this shard's column slice only
            totals = gh.sum(axis=0)
            cr = codes_row
            if cr.shape[1] < cs * D:
                cr = jnp.pad(cr, ((0, 0), (0, cs * D - cr.shape[1])))
            cr_sl = jax.lax.dynamic_slice(
                cr, (jnp.int32(0), (shard * cs).astype(jnp.int32)), (n, cs))
            hist0 = build_histogram(cr_sl, gh, col_bins, use_pallas=use_pallas)
        else:
            hist0 = build_histogram(codes_row, gh, col_bins,
                                    use_pallas=use_pallas)
            if scatter or voting:
                # global totals first (the post-reduce histogram is a column
                # slice / stays local), then reduce per mode
                totals = jax.lax.psum(hist0[0].sum(axis=0), axis_name)
                hist0 = reduce_hist(hist0)
            else:
                hist0 = reduce_hist(hist0)
                totals = hist0[0].sum(axis=0)
        if not quant:
            hist0_scan = hist0
    pool_c = hist0.shape[0]
    root_key, loop_key = jax.random.split(rng_key)
    row0, cm0 = search_row(hist0_scan, totals[0], totals[1], totals[2],
                           jnp.float32(-np.inf), jnp.float32(np.inf),
                           root_key, jnp.int32(0))

    zi = functools.partial(jnp.zeros, dtype=jnp.int32)
    best = jnp.full((L, 12), NEG_INF, jnp.float32).at[:, B_FEAT:].set(0.0)
    best = best.at[0].set(row0)
    best_cat = jnp.zeros((L, cat_b), jnp.float32).at[0].set(cm0)
    # pool dtype follows the histogram dtype: int32 on the quantized
    # path (sibling subtraction below is then exact integer arithmetic)
    with jax.named_scope("lgbm.root_hist"):
        pool = jnp.zeros((K, pool_c, col_bins, 3), hist0.dtype) \
            .at[0].set(hist0)
    rec = jnp.zeros((L - 1, 13), jnp.float32)
    carry = _CarryC(
        k=jnp.int32(0),
        data=data0,
        pos_leaf=jnp.zeros(n + wmax, jnp.int32),
        leaf_begin=zi(L), leaf_phys=zi(L).at[0].set(n),
        pool=pool,
        slot_of=jnp.full((L,), -1, jnp.int32).at[0].set(0),
        slot_owner=jnp.full((K,), -1, jnp.int32).at[0].set(0),
        slot_last=zi(K),
        depth=zi(L),
        leaf_min=jnp.full((L,), -np.inf, jnp.float32),
        leaf_max=jnp.full((L,), np.inf, jnp.float32),
        best=best, best_cat=best_cat, rec=rec,
        rec_cat=jnp.zeros((L - 1, cat_b), jnp.float32), key=loop_key)

    def cond(c: _CarryC):
        return (c.k < L - 1) & (jnp.max(c.best[:, B_GAIN]) > 1e-10)

    hist_dtype = jnp.int32 if quant else jnp.float32

    def make_rung(wsz: int):
        half = (wsz + 1) // 2

        def rung(data, begin, pcount, row, cat_mask, need_other, rq_g, rq_h):
            """One split of a leaf whose rows fit a window of `wsz`:
            `data` with the window partitioned in place, and the small
            results (left count, child histograms, `qmax2` under
            `renew`)."""
            feat = row[B_FEAT].astype(jnp.int32)

            with jax.named_scope("lgbm.partition"):
                win = jax.lax.dynamic_slice(data, (begin, 0), (wsz, d_cols))
                valid = jnp.arange(wsz, dtype=jnp.int32) < pcount
            with jax.named_scope("lgbm.go_left"):
                go_left = packed_go_left(
                    win, feat, row[B_THR].astype(jnp.int32),
                    row[B_DLEFT] > 0.5, f_numbins, f_missing, f_default,
                    f_col, f_base, f_elide, item_bits=item_bits,
                    f_categorical=f_categorical if has_cat else None,
                    cat_mask=cat_mask) & valid
                if renew:
                    # each child's stored-int maxes seed its leaf-local
                    # requant ratio (measured here: the window is in hand)
                    qmax2 = _quant_side_maxes(win, go_left, valid,
                                              cw=cw, gw=gw)

            # stable partition of the window (reference DataPartition::
            # Split): overrun rows past pcount get key 2; the full 3-way
            # compaction is identity on them (they are already tail-
            # contiguous), so they return to their slots untouched
            with jax.named_scope("lgbm.partition"):
                key3 = jnp.where(valid, jnp.where(go_left, 0, 1), 2)
                win_sorted = partition_window(win, key3)
            with jax.named_scope("lgbm.table_update"):
                data = jax.lax.dynamic_update_slice(data, win_sorted,
                                                    (begin, 0))
            with jax.named_scope("lgbm.partition"):
                lphys = jnp.sum(go_left.astype(jnp.int32))
                rphys = pcount - lphys
            # pos_leaf / leaf_begin / leaf_phys updates happen OUTSIDE the
            # rung (the body computes them from lphys): the table is the
            # one large buffer a rung carries

            # LOCAL histogram of the GLOBALLY smaller child (all shards
            # must hist the same side so the cross-shard sum is one
            # child's histogram; the choice key is the replicated global
            # count from the split record). Fast path: the side fits the
            # contiguous half window; fallback (possible only when local
            # physical share is skewed vs the global choice under
            # bagging/sharding): masked pass over the full window.
            with jax.named_scope("lgbm.child_hist"):
                left_small = row[B_LCNT] <= row[B_RCNT]
                s_begin = jnp.where(left_small, 0, lphys)
                s_count = jnp.where(left_small, lphys, rphys)

                def win_operands(rows2d, vbool):
                    """(codes, operand) of packed rows for the histogram
                    of their `vbool` rows: the one layout dispatch (float
                    triple vs packed int)."""
                    s_codes = decode_for_hist(rows2d[:, :cw])
                    if quant:
                        return s_codes, _quant_win_operand(
                            rows2d, vbool, cw=cw, gw=gw, quant_bits=quant_bits,
                            qcap_op=qcap_op, r_g=rq_g, r_h=rq_h)
                    return s_codes, jax.lax.bitcast_convert_type(
                        rows2d[:, cw:cw + 3], jnp.float32) \
                        * vbool.astype(jnp.float32)[:, None]

                def win_hist(first_row, rows, range_begin, range_count):
                    """Histogram of the rows [range_begin, range_begin +
                    range_count) of the `rows` rows of the sorted window
                    from `first_row`: the chunks that meet the range
                    are read out of the packed window, decoded and
                    contracted one by one (build_histogram_range), so a
                    rung works for the child's rows, not for its window.
                    The Pallas kernel takes the stretch whole."""
                    if use_pallas:
                        j = jnp.arange(rows, dtype=jnp.int32)
                        s_codes, s_gh = win_operands(
                            jax.lax.dynamic_slice(win_sorted, (first_row, 0),
                                                  (rows, d_cols)),
                            (j >= range_begin)
                            & (j < range_begin + range_count))
                        build = (build_histogram_quantized if quant
                                 else build_histogram)
                        return build(s_codes, s_gh, col_bins, use_pallas=True)

                    def load(row0, size, keep):
                        return win_operands(
                            window_chunk(win_sorted, first_row + row0, size,
                                         rows % size != 0), keep)

                    return build_histogram_range(
                        load, rows, range_begin, range_count, hist_cols,
                        col_bins, quantized=quant)

                def hist_half(_):
                    start = jnp.clip(s_begin, 0, wsz - half)
                    return win_hist(start, half, s_begin - start, s_count)

                def hist_range(range_begin, range_count):
                    # the full window: a side the half window cannot hold
                    return win_hist(0, wsz, range_begin, range_count)

                if trivial_weights and axis_name is None:
                    # all-ones weights single-chip: record counts equal
                    # physical counts, so the smaller side always fits the
                    # contiguous half window — the masked full-window
                    # fallback (and its extra compiled histogram program
                    # per window class) is statically dead
                    hist_small = hist_half(None)
                else:
                    hist_small = jax.lax.cond(
                        s_count <= half, hist_half,
                        lambda _: hist_range(s_begin, s_count), operand=None)

                # pooled mode, parent-histogram miss: the sibling cannot come
                # from subtraction, so build the LARGER child's histogram
                # directly with a masked pass over the window (reference
                # HistogramPool miss -> ConstructHistograms re-run)
                if pooled:
                    o_begin = jnp.where(left_small, lphys, 0)
                    o_count = pcount - s_count
                    hist_other = jax.lax.cond(
                        need_other, lambda _: hist_range(o_begin, o_count),
                        lambda _: jnp.zeros((hist_cols, col_bins, 3),
                                            hist_dtype),
                        operand=None)
                else:
                    hist_other = jnp.zeros((hist_cols, col_bins, 3),
                                           hist_dtype)
            out = (data, lphys, hist_small, hist_other)
            return out + (qmax2,) if renew else out
        return rung

    rungs = [make_rung(wsz) for wsz in classes]

    def body(c: _CarryC, qx=None):
        with jax.named_scope("lgbm.leaf_select"):
            b = c.best
            l = jnp.argmax(b[:, B_GAIN]).astype(jnp.int32)
            row = b[l]
            new_id = c.k + 1
            feat = row[B_FEAT].astype(jnp.int32)
            pcount = c.leaf_phys[l]
            slot_l = c.slot_of[l]
            have_parent = slot_l >= 0
            j = jnp.sum((pcount > thresholds).astype(jnp.int32))
        if renew:
            # the leaf's operand ratio comes from maxes recorded at its
            # CREATION (replicated), so the rung needs no collective
            scale_of, leafmax = qx
            rq_g, rq_h = q_ratios(leafmax[l])
        else:
            rq_g = rq_h = jnp.float32(1.0)
        # the dispatch over the window ladder belongs to `leaf_select`
        # (which leaf, which rung); each rung names its own stages. The
        # rungs run one after another, each once if it is the leaf's and
        # not at all otherwise (run_once_if), so that the table is the
        # carry of a `while` all the way and is updated in place; the
        # small results start as zeros and the rung that runs sets them
        with jax.named_scope("lgbm.leaf_select"):
            zeros_hist = jnp.zeros((hist_cols, col_bins, 3), hist_dtype)
            st = (c.data, jnp.int32(0), zeros_hist, zeros_hist)
            if renew:
                st += (jnp.zeros((2, 2), jnp.float32),)
            cat_mask = c.best_cat[l] if has_cat else None
            begin = c.leaf_begin[l]
            need_other = ~have_parent
            for r, rung in enumerate(rungs):
                with jax.named_scope(f"rung_{r}"):
                    st = run_once_if(
                        j == r,
                        lambda s, rung=rung: rung(
                            s[0], begin, pcount, row, cat_mask,
                            need_other, rq_g, rq_h),
                        st)
        data, lphys, hist_small, hist_other = st[:4]
        if renew:
            qmax2 = st[4]
            if axis_name is not None:
                qmax2 = jax.lax.pmax(qmax2, axis_name)
        with jax.named_scope("lgbm.table_update"):
            rphys = pcount - lphys
            leaf_begin = c.leaf_begin.at[new_id].set(begin + lphys)
            leaf_phys = c.leaf_phys.at[l].set(lphys).at[new_id].set(rphys)
            # O(N) elementwise pos_leaf rewrite (fuses to one in-place pass;
            # cheaper than carrying the update through the rungs)
            posv = jnp.arange(n + wmax, dtype=jnp.int32)
            pos_leaf = jnp.where(
                (posv >= begin) & (posv < begin + lphys), l,
                jnp.where((posv >= begin + lphys) & (posv < begin + pcount),
                          new_id, c.pos_leaf))
        with jax.named_scope("lgbm.child_hist"):
            left_small = row[B_LCNT] <= row[B_RCNT]
            if axis_name is not None:
                # cross-shard histogram reduction: psum replicates (dense
                # equivalent of the reference's reduce-scatter, scan runs
                # identically everywhere); scatter mode IS the reference's
                # pattern (each shard owns its column tile). The miss-path
                # histogram reduces alongside so no shard ever takes a
                # collective the others skip.
                if quant and scatter:
                    # two integer lanes on the wire; counts reconstructed
                    # from the hessian lane + the replicated global count
                    s_cnt_g = jnp.where(left_small, row[B_LCNT], row[B_RCNT])
                    s_qh_g = jnp.where(left_small, row[B_LSH], row[B_RSH]) \
                        * (q_sh * rq_h)
                    hist_small = reduce_q(hist_small, s_cnt_g, s_qh_g)
                    if pooled:
                        o_cnt_g = row[B_LCNT] + row[B_RCNT] - s_cnt_g
                        o_qh_g = (row[B_LSH] + row[B_RSH]) * (q_sh * rq_h) \
                            - s_qh_g
                        hist_other = reduce_q(hist_other, o_cnt_g, o_qh_g)
                else:
                    hist_small = reduce_hist(hist_small)
                    if pooled:
                        hist_other = reduce_hist(hist_other)

            parent = (c.pool[jnp.clip(slot_l, 0, K - 1)] if pooled
                      else c.pool[l])
            if renew:
                # re-express the parent pool entry in the split's ratio
                # before subtraction (counts pass through exact)
                parent = quant_ops.rescale_histogram(
                    parent, rq_g / scale_of[l, 0], rq_h / scale_of[l, 1])
            sibling = jnp.where(have_parent, parent - hist_small, hist_other) \
                if pooled else parent - hist_small
            hist_l = jnp.where(left_small, hist_small, sibling)
            hist_r = jnp.where(left_small, sibling, hist_small)

            # pool slot bookkeeping: l reuses its parent slot when cached,
            # otherwise allocates; new_id always allocates. Allocation takes
            # a free slot first, else evicts the least-recently-used (the
            # reference HistogramPool's Get/Move semantics).
            step = new_id
            if pooled:
                iarangeK = jnp.arange(K, dtype=jnp.int32)

                def alloc(slot_of, slot_owner, slot_last, forbid, want):
                    score = jnp.where(slot_owner < 0, jnp.int32(-1), slot_last)
                    score = jnp.where(iarangeK == forbid,
                                      jnp.iinfo(jnp.int32).max, score)
                    s = jnp.argmin(score).astype(jnp.int32)
                    old = slot_owner[s]
                    safe_old = jnp.clip(old, 0, L - 1)
                    slot_of = slot_of.at[safe_old].set(
                        jnp.where(want & (old >= 0), -1, slot_of[safe_old]))
                    return s, slot_of

                s_l_new, slot_of = alloc(c.slot_of, c.slot_owner, c.slot_last,
                                         jnp.int32(-1), ~have_parent)
                s_l = jnp.where(have_parent, slot_l, s_l_new)
                slot_of = slot_of.at[l].set(s_l)
                slot_owner = c.slot_owner.at[s_l].set(l)
                slot_last = c.slot_last.at[s_l].set(step)
                s_r, slot_of = alloc(slot_of, slot_owner, slot_last, s_l,
                                     jnp.bool_(True))
                slot_of = slot_of.at[new_id].set(s_r)
                slot_owner = slot_owner.at[s_r].set(new_id)
                slot_last = slot_last.at[s_r].set(step)
            else:
                s_l, s_r = l, new_id
                slot_of = c.slot_of
                slot_owner, slot_last = c.slot_owner, c.slot_last
            pool = c.pool.at[s_l].set(hist_l).at[s_r].set(hist_r)

            if quant:
                # scans read f32: dequantize the children at the split's
                # leaf-local scale (the pool keeps the exact integers)
                hist_l_s = q_dequant(hist_l, rq_g, rq_h)
                hist_r_s = q_dequant(hist_r, rq_g, rq_h)
            else:
                hist_l_s, hist_r_s = hist_l, hist_r
        (key, leaf_min, leaf_max, depth, rec2, rec_cat2, best2,
         best_cat2) = split_epilogue(
            k=c.k, key=c.key, l=l, new_id=new_id, row=row,
            feat=feat, f_monotone=f_monotone,
            leaf_min=c.leaf_min, leaf_max=c.leaf_max, depth=c.depth,
            rec=c.rec, rec_cat=c.rec_cat, best=b, best_cat=c.best_cat,
            hist_l=hist_l_s, hist_r=hist_r_s, search2=search2_rows)
        c2 = _CarryC(new_id, data, pos_leaf, leaf_begin, leaf_phys,
                     pool, slot_of, slot_owner, slot_last,
                     depth, leaf_min, leaf_max, best2, best_cat2,
                     rec2, rec_cat2, key)
        if renew:
            scale2 = jnp.stack([rq_g, rq_h])
            return c2, (scale_of.at[l].set(scale2).at[new_id].set(scale2),
                        leafmax.at[l].set(qmax2[0]).at[new_id]
                        .set(qmax2[1]))
        return c2, None

    if renew:
        scale0 = jnp.ones((L, 2), jnp.float32) \
            .at[0].set(jnp.stack([r0_g, r0_h]))
        leafmax0 = jnp.zeros((L, 2), jnp.float32).at[0].set(root_max)
        out, _ = run_split_loop(
            lambda t: cond(t[0]), lambda t: body(t[0], t[1]),
            (carry, (scale0, leafmax0)), L - 1, grow_program)
    else:
        out = run_split_loop(cond, lambda cc: body(cc)[0], carry,
                             L - 1, grow_program)
    # final row -> leaf map: scatter physical-position leaves onto row ids
    with jax.named_scope("lgbm.row_leaf"):
        row_ids = out.data[:n, d_cols - 1].astype(jnp.int32)
        leaf_id = jnp.zeros(n, jnp.int32).at[row_ids].set(
            out.pos_leaf[:n], unique_indices=True)
    return (out.rec, out.rec_cat if has_cat else None,
            leaf_id, out.k, totals)


class _CarryK(NamedTuple):
    k: jax.Array
    data: jax.Array          # (N + CH, D) u32 packed rows grouped by leaf
    scratch: jax.Array       # (N + CH, D) u32 right-segment staging
    pos_leaf: jax.Array      # (N + CH,) leaf id per physical position
    leaf_begin: jax.Array    # (L,)
    leaf_phys: jax.Array     # (L,)
    pool: jax.Array          # (L, C, B, 3) dense histogram pool
    depth: jax.Array
    leaf_min: jax.Array
    leaf_max: jax.Array
    best: jax.Array          # (L, 12) f32
    best_cat: jax.Array      # (L, B|1) f32
    rec: jax.Array           # (L-1, 13) f32
    rec_cat: jax.Array       # (L-1, B|1) f32
    key: jax.Array


@functools.partial(
    jax.jit,
    static_argnames=("c_cols", "item_bits",
                     "num_leaves", "num_bins", "col_bins", "max_depth",
                     "bynode_k", "use_pallas",
                     "chunk_rows", "fuse_hist", "feature_shards",
                     "cat_statics", "trivial_weights", "quant_bits",
                     "quant_renew", "data_prebuilt", "grow_program"))
def grow_tree_chunk(
        codes_pack: jax.Array, codes_row: jax.Array,
        grad: jax.Array, hess: jax.Array, w: jax.Array,
        base_mask: jax.Array,
        f_numbins, f_missing, f_default, f_monotone, f_penalty,
        f_categorical, f_col, f_base, f_elide, scan_plan, rng_key,
        *, c_cols: int, item_bits: int,
        num_leaves: int, num_bins: int, col_bins: int, max_depth: int,
        l1: float, l2: float, max_delta_step: float,
        min_data_in_leaf: int, min_sum_hessian: float,
        min_gain_to_split: float, bynode_k: int, use_pallas: bool,
        chunk_rows: int = 65536,
        fuse_hist: bool = True, feature_shards: int = 0,
        cat_statics=None, trivial_weights: bool = False,
        quant_bits: int = 0, quant_renew: bool = True,
        data_prebuilt: bool = False, grow_program: str = "per_split"):
    return grow_tree_chunk_core(
        codes_pack, codes_row, grad, hess, w, base_mask,
        f_numbins, f_missing, f_default, f_monotone, f_penalty,
        f_categorical, f_col, f_base, f_elide, scan_plan, rng_key,
        c_cols=c_cols, item_bits=item_bits, num_leaves=num_leaves,
        num_bins=num_bins, col_bins=col_bins, max_depth=max_depth,
        l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf, min_sum_hessian=min_sum_hessian,
        min_gain_to_split=min_gain_to_split, bynode_k=bynode_k,
        use_pallas=use_pallas, chunk_rows=chunk_rows,
        fuse_hist=fuse_hist, feature_shards=feature_shards,
        axis_name=None, cat_statics=cat_statics,
        trivial_weights=trivial_weights, quant_bits=quant_bits,
        quant_renew=quant_renew, data_prebuilt=data_prebuilt,
        grow_program=grow_program)


def grow_tree_chunk_core(
        codes_pack: jax.Array, codes_row: jax.Array,
        grad: jax.Array, hess: jax.Array, w: jax.Array,
        base_mask: jax.Array,
        f_numbins, f_missing, f_default, f_monotone, f_penalty,
        f_categorical, f_col, f_base, f_elide, scan_plan, rng_key,
        *, c_cols: int, item_bits: int,
        num_leaves: int, num_bins: int, col_bins: int, max_depth: int,
        l1: float, l2: float, max_delta_step: float,
        min_data_in_leaf: int, min_sum_hessian: float,
        min_gain_to_split: float, bynode_k: int, use_pallas: bool,
        chunk_rows: int = 65536,
        fuse_hist: bool = True, feature_shards: int = 0,
        scatter_cols: int = 0, voting_k: int = 0,
        axis_name=None, cat_statics=None, trivial_weights: bool = False,
        quant_bits: int = 0, quant_renew: bool = True,
        quant_total_rows: int = 0, data_prebuilt: bool = False,
        grow_program: str = "per_split"):
    """Ladder-free whole-tree growth over fixed-size chunks.

    The compact strategy resolves dynamic leaf sizes with a ladder of
    padded window classes, and every class duplicates the rung's
    program. This variant has no ladder: a split of a p-row leaf runs
    ceil(p / CH) iterations of fixed-(CH, D)-shaped inner fori loops, so
    one traced partition program serves every leaf size, and the
    per-split fixed cost is a handful of chunk passes instead of the
    rung machinery. Both cores keep the packed working buffer a loop's
    carry throughout, so that every update of it is a
    dynamic_update_slice XLA aliases in place (the compact core since
    PR 30: its rungs are `while` loops, no `conditional`).

    Correctness of the in-place movement (reference DataPartition::Split
    semantics, stable 3-way):
      * pass B (forward over chunks): chunk i's rows are read before any
        write that can touch them — left writes land in
        [begin, begin+loff[i]+CH) which never reaches past chunk i's own
        region (loff[i] <= i*CH), and are merge-masked to exactly
        lcnt[i] rows so rows of later chunks are preserved; right
        segments stage front-aligned at chunk i's own location in a
        scratch buffer.
      * pass C (forward): staged right segments place at
        begin + L_tot + roff[i], merge-masked to rcnt[i] rows, so the
        garbage tail never leaks into the next leaf.
      * rows past the leaf end (other leaves' rows in the final chunk)
        carry partition key 2 and are never written.
    The smaller child's histogram accumulates over its chunks after the
    move (sibling = parent - smaller, FeatureHistogram::Subtract).

    axis_name enables the sharded modes, all four of the compact
    core's reductions:
      * data-parallel psum (rows sharded; root and smaller-child
        histograms psum-replicate and every shard runs the identical
        scan — data_parallel_tree_learner.cpp:149-164 in its
        replicated rendering);
      * scatter_cols > 1: the reference comm pattern — per-chunk
        histograms accumulate full-width locally, ONE lax.psum_scatter
        per split tiles the column axis so each shard scans only the
        C/D columns it owns, and the winner is elected from a (D, 12+B)
        all_gather of candidate rows (make_sliced_search;
        data_parallel_tree_learner.cpp:149-200 + SyncUpGlobalBestSplit);
      * voting_k > 0: PV-Tree 2-stage voting — local scan + top-k vote,
        elect 2k, reduce only the elected features' histograms
        (make_voting_search; voting_parallel_tree_learner.cpp:170-260);
      * feature_shards > 1: feature-parallel (rows replicated,
        histograms built and scanned per column slice, winners elected
        via make_sliced_search — feature_parallel_tree_learner.cpp:33-76).
    The LRU-capped histogram pool stays on the compact strategy.

    data_prebuilt=True is the streaming entry (io/stream.py +
    DeviceTreeLearner's stream assembly): `codes_pack` is then the
    ALREADY-ASSEMBLED (n + CH, cw + gw + 1) working buffer data0
    (`[packed codes | gh words | row id]`, CH zero-pad rows) and
    `codes_row` a dummy — the core skips its in-program data0 build and
    accumulates the root histogram chunk-wise over the buffer with the
    same contraction the split loop uses, so no full-N `codes_pack` /
    `codes_row` device copies ever exist. Everything downstream of the
    root (carry, split loop, epilogue) is the identical program, which
    is what makes streamed output bit-identical to resident growth
    (serial only; the sharded modes keep their resident inputs).
    """
    from ..ops.histogram import build_histogram, build_histogram_quantized
    n = grad.shape[0]
    L = num_leaves
    CH = int(chunk_rows)
    maxch = -(-n // CH)
    has_cat = cat_statics is not None
    cat_b = num_bins if has_cat else 1
    quant = quant_bits > 0
    if data_prebuilt:
        # serial streaming, or streamed data-parallel over the plain
        # psum lane (each shard's buffer holds its own rows; per-leaf
        # histograms are the only cross-shard exchange)
        assert feature_shards <= 1 and scatter_cols <= 1 \
            and voting_k <= 0, \
            "data_prebuilt runs the serial or plain-psum DP chunk core"
        cw = codes_pack.shape[1] - ((1 if trivial_weights else 2)
                                    if quant else 3) - 1
        assert codes_pack.shape[0] == n + CH, \
            "prebuilt data0 must carry CH zero-pad rows"
    else:
        cw = codes_pack.shape[1]
    if not quant and not data_prebuilt:
        gh = jnp.stack([grad * w, hess * w, w], axis=1)
    helper_kwargs = dict(
        num_bins=num_bins, max_depth=max_depth, l1=l1, l2=l2,
        max_delta_step=max_delta_step, min_data_in_leaf=min_data_in_leaf,
        min_sum_hessian=min_sum_hessian, min_gain_to_split=min_gain_to_split,
        bynode_k=bynode_k)
    fp = feature_shards > 1 and axis_name is not None
    scatter = scatter_cols > 1 and axis_name is not None and not fp
    voting = voting_k > 0 and axis_name is not None and not (scatter or fp)
    per_w = 32 // item_bits

    # quantized packed rows: same layout + leaf-requant scheme as the
    # compact core (see grow_tree_compact_core); the supported sharded
    # reductions are serial, DP psum and DP scatter
    assert not (quant and (voting or fp)), \
        "quantized packed rows: voting/feature-parallel modes fall back " \
        "to the host learners (create_tree_learner gates)"
    renew = quant and quant_renew
    if quant:
        n_total = quant_total_rows or n
        qcap_op = quant_ops.quant_max(quant_bits, n_total)
        rng_key, gh_packed, q_sg, q_sh, root_max = _quant_prepare(
            grad, hess, w, rng_key, quant_bits=quant_bits,
            quant_renew=quant_renew, n_total=n_total, axis_name=axis_name)
        gw = 1 if trivial_weights else 2

        def q_ratios(leaf_max):
            if not renew:
                return jnp.float32(1.0), jnp.float32(1.0)
            return (quant_ops.requant_ratio(leaf_max[0], qcap_op),
                    quant_ops.requant_ratio(leaf_max[1], qcap_op))

        def q_dequant(h_int, r_g, r_h):
            return h_int.astype(jnp.float32) * quant_ops.dequant_scale3(
                q_sg * r_g, q_sh * r_h)

        if scatter:
            reduce_q = make_scatter_reduce_q(
                axis_name, scatter_cols, c_cols,
                quant_ops.wire_dtype(quant_bits, n_total))
    else:
        gw = 3
    d_cols = cw + gw + 1
    if fp:
        # feature-parallel: rows replicated, each shard builds and scans
        # only its word-aligned column slice; the winner is elected from
        # the all_gather of candidate rows (make_sliced_search)
        (reduce_hist, search_row, search2, cs, shard,
         _start) = make_sliced_search(
            axis_name=axis_name, fp=True, D=feature_shards,
            c_cols=c_cols, col_bins=col_bins, item_bits=item_bits,
            base_mask=base_mask, f_numbins=f_numbins, f_missing=f_missing,
            f_default=f_default, f_monotone=f_monotone,
            f_penalty=f_penalty, f_elide=f_elide,
            f_categorical=f_categorical, has_cat=has_cat,
            cat_statics=cat_statics, helper_kwargs=helper_kwargs)
        cs_words = cs // per_w
        assert cw >= cs_words * feature_shards, \
            "feature-parallel needs codes packed to the padded column count"
        w0 = (shard * cs_words).astype(jnp.int32)
        hist_w = cs

        def decode_hist_cols(words2d):
            wsl = jax.lax.dynamic_slice(
                words2d, (jnp.int32(0), w0), (words2d.shape[0], cs_words))
            return _unpack_codes(wsl, cs, item_bits)
    elif scatter:
        # per-chunk histograms accumulate FULL-width locally; one
        # psum_scatter per split hands each shard its column slice
        (reduce_hist, search_row, search2, cs, shard,
         _start) = make_sliced_search(
            axis_name=axis_name, fp=False, D=scatter_cols,
            c_cols=c_cols, col_bins=col_bins, item_bits=item_bits,
            base_mask=base_mask, f_numbins=f_numbins, f_missing=f_missing,
            f_default=f_default, f_monotone=f_monotone,
            f_penalty=f_penalty, f_elide=f_elide,
            f_categorical=f_categorical, has_cat=has_cat,
            cat_statics=cat_statics, helper_kwargs=helper_kwargs)
        hist_w = cs

        def decode_hist_cols(words2d):
            return _unpack_codes(words2d[:, :cw], c_cols, item_bits)
    elif voting:
        reduce_hist, search_row, search2 = make_voting_search(
            axis_name=axis_name, voting_k=voting_k, c_cols=c_cols,
            col_bins=col_bins, base_mask=base_mask,
            f_numbins=f_numbins, f_missing=f_missing,
            f_default=f_default, f_monotone=f_monotone,
            f_penalty=f_penalty, f_elide=f_elide,
            f_categorical=f_categorical, has_cat=has_cat,
            cat_statics=cat_statics, helper_kwargs=helper_kwargs)
        hist_w = c_cols

        def decode_hist_cols(words2d):
            return _unpack_codes(words2d[:, :cw], c_cols, item_bits)
    else:
        (node_mask, scan, store_best, scan2,
         best_row) = _tree_helpers(
            base_mask, f_numbins, f_missing, f_default, f_monotone,
            f_penalty, f_elide, scan_plan,
            f_categorical=f_categorical, cat_statics=cat_statics,
            **helper_kwargs)
        hist_w = c_cols

        def decode_hist_cols(words2d):
            return _unpack_codes(words2d[:, :cw], c_cols, item_bits)

        def search_row(col_hist, sg, sh, cnt, mn, mx, key, child_depth):
            res, cm = scan(col_hist, sg, sh, cnt, mn, mx, node_mask(key))
            return best_row(res, child_depth), cm

        search2 = search2_simple(scan2, best_row)

        if axis_name is not None:
            def reduce_hist(h):
                return jax.lax.psum(h, axis_name)
        else:
            def reduce_hist(h):
                return h

    if data_prebuilt:
        # the streaming layer assembled data0 on device (gh words from
        # the SAME _quant_prepare key in the quantized case, so the
        # in-program scale/key derivation above stays the one source)
        data0 = codes_pack
    else:
        if quant:
            gh_u = _quant_gh_words(gh_packed, w, gw)
        else:
            gh_u = jax.lax.bitcast_convert_type(gh, jnp.uint32)
        ids = jnp.arange(n, dtype=jnp.uint32)[:, None]
        data0 = jnp.concatenate([codes_pack, gh_u, ids], axis=1)
        data0 = jnp.concatenate(
            [data0, jnp.zeros((CH, d_cols), jnp.uint32)], axis=0)

    if data_prebuilt and quant:
        # chunk-wise root accumulation over the prebuilt buffer: same
        # per-chunk contraction as the split loop's chunk_hist. The
        # int32 partial sums make the grouping change exactly
        # associative, so this equals the resident full-N build
        # bit-for-bit.
        r0_g, r0_h = q_ratios(root_max)
        iota_root = jnp.arange(CH, dtype=jnp.int32)

        from ..ops.histogram import accumulate_histogram

        def root_chunk(i, acc):
            win = jax.lax.dynamic_slice(
                data0, (i * CH, jnp.int32(0)), (CH, data0.shape[1]))
            count = jnp.clip(n - i * CH, 0, CH)
            codes = decode_hist_cols(win[:, :cw])
            operand = _quant_win_operand(
                win, iota_root < count, cw=cw, gw=gw,
                quant_bits=quant_bits, qcap_op=qcap_op,
                r_g=r0_g, r_h=r0_h)
            return accumulate_histogram(acc, codes, operand, col_bins,
                                        use_pallas=use_pallas)

        hist0 = jax.lax.fori_loop(
            0, maxch, root_chunk,
            jnp.zeros((hist_w, col_bins, 3), jnp.int32))
        if axis_name is not None:
            hist0 = jax.lax.psum(hist0, axis_name)
        totals = q_dequant(hist0[0].sum(axis=0), r0_g, r0_h)
        hist0_scan = q_dequant(hist0, r0_g, r0_h)
    elif data_prebuilt:
        # float path: f32 adds are NOT associative, so chunk-wise
        # accumulation would regroup the resident full-N contraction and
        # break bit-identity for arbitrary gradients. data0 already
        # holds every row, so run the identical full-N build on a
        # transient decode (same shapes/values as the resident
        # codes_row + gh operands; freed after the root build).
        hist0 = build_histogram(
            decode_hist_cols(data0[:n]),
            jax.lax.bitcast_convert_type(data0[:n, cw:cw + 3],
                                         jnp.float32),
            col_bins, use_pallas=use_pallas)
        hist0 = reduce_hist(hist0)
        totals = hist0[0].sum(axis=0)
    elif quant:
        r0_g, r0_h = q_ratios(root_max)
        ghq0 = quant_ops.gh_operand_scaled(
            gh_packed, w > 0, quant_bits, qcap_op, r0_g, r0_h)
        hist0 = build_histogram_quantized(codes_row, ghq0, col_bins,
                                          use_pallas=use_pallas)
        if scatter:
            tot_q = jax.lax.psum(hist0[0].sum(axis=0), axis_name)
            totals = q_dequant(tot_q, r0_g, r0_h)
            hist0 = reduce_q(hist0, totals[2], tot_q[1].astype(jnp.float32))
        else:
            if axis_name is not None:
                hist0 = jax.lax.psum(hist0, axis_name)
            totals = q_dequant(hist0[0].sum(axis=0), r0_g, r0_h)
        hist0_scan = q_dequant(hist0, r0_g, r0_h)
    elif fp:
        # rows replicated: totals come straight from gh; root histogram
        # from this shard's column slice only
        totals = gh.sum(axis=0)
        cr = codes_row
        if cr.shape[1] < cs * feature_shards:
            cr = jnp.pad(
                cr, ((0, 0), (0, cs * feature_shards - cr.shape[1])))
        cr_sl = jax.lax.dynamic_slice(
            cr, (jnp.int32(0), (shard * cs).astype(jnp.int32)), (n, cs))
        hist0 = build_histogram(cr_sl, gh, col_bins, use_pallas=use_pallas)
    else:
        hist0 = build_histogram(codes_row, gh, col_bins,
                                use_pallas=use_pallas)
        if scatter or voting:
            # global totals first: the post-reduce histogram is a
            # column slice (scatter) / stays local (voting)
            totals = jax.lax.psum(hist0[0].sum(axis=0), axis_name)
            hist0 = reduce_hist(hist0)
        else:
            hist0 = reduce_hist(hist0)
            totals = hist0[0].sum(axis=0)
    if not quant:
        hist0_scan = hist0
    root_key, loop_key = jax.random.split(rng_key)
    row0, cm0 = search_row(hist0_scan, totals[0], totals[1], totals[2],
                           jnp.float32(-np.inf), jnp.float32(np.inf),
                           root_key, jnp.int32(0))
    best = jnp.full((L, 12), NEG_INF, jnp.float32).at[:, B_FEAT:].set(0.0)
    best = best.at[0].set(row0)
    best_cat = jnp.zeros((L, cat_b), jnp.float32).at[0].set(cm0)
    zi = functools.partial(jnp.zeros, dtype=jnp.int32)
    carry = _CarryK(
        k=jnp.int32(0), data=data0, scratch=jnp.zeros_like(data0),
        pos_leaf=jnp.zeros(n + CH, jnp.int32),
        leaf_begin=zi(L), leaf_phys=zi(L).at[0].set(n),
        pool=jnp.zeros((L, hist_w, col_bins, 3), hist0.dtype).at[0]
            .set(hist0),
        depth=zi(L),
        leaf_min=jnp.full((L,), -np.inf, jnp.float32),
        leaf_max=jnp.full((L,), np.inf, jnp.float32),
        best=best, best_cat=best_cat,
        rec=jnp.zeros((L - 1, 13), jnp.float32),
        rec_cat=jnp.zeros((L - 1, cat_b), jnp.float32), key=loop_key)

    iota_ch = jnp.arange(CH, dtype=jnp.int32)

    def cond(c: _CarryK):
        return (c.k < L - 1) & (jnp.max(c.best[:, B_GAIN]) > 1e-10)

    def body(c: _CarryK, qx=None):
        b = c.best
        l = jnp.argmax(b[:, B_GAIN]).astype(jnp.int32)
        row = b[l]
        new_id = c.k + 1
        feat = row[B_FEAT].astype(jnp.int32)
        thr = row[B_THR].astype(jnp.int32)
        dleft = row[B_DLEFT] > 0.5
        cmask = c.best_cat[l] if has_cat else None
        begin = c.leaf_begin[l]
        p = c.leaf_phys[l]
        nch = -(-p // CH)
        if renew:
            scale_of, leafmax = qx
            rq_g, rq_h = q_ratios(leafmax[l])
        else:
            rq_g = rq_h = jnp.float32(1.0)
        # the GLOBALLY smaller child (replicated record counts) decides
        # which side's rows accumulate the fused histogram
        left_small = row[B_LCNT] <= row[B_RCNT]
        # scatter accumulates chunks FULL-width locally (the one
        # psum_scatter afterwards maps it to this shard's hist_w slice);
        # every other mode accumulates at pool width directly
        acc_w = c_cols if scatter else hist_w
        hist_zero = jnp.zeros((acc_w, col_bins, 3),
                              jnp.int32 if quant else jnp.float32)

        @jax.named_scope("lgbm.child_hist")
        def chunk_hist(rows_win, count):
            codes = decode_hist_cols(rows_win[:, :cw])
            if quant:
                ghq = _quant_win_operand(
                    rows_win, iota_ch < count, cw=cw, gw=gw,
                    quant_bits=quant_bits, qcap_op=qcap_op,
                    r_g=rq_g, r_h=rq_h)
                return build_histogram_quantized(codes, ghq, col_bins,
                                                 use_pallas=use_pallas)
            v = (iota_ch < count).astype(jnp.float32)
            ghw = jax.lax.bitcast_convert_type(
                rows_win[:, cw:cw + 3], jnp.float32) * v[:, None]
            return build_histogram(codes, ghw, col_bins,
                                   use_pallas=use_pallas)

        # pass B: per chunk — read, decide, local 3-way stable partition,
        # exact-write lefts forward into data, stage rights in scratch;
        # when the LEFT child is the smaller one its histogram fuses in
        # (the chunk's left segment sits at win_s[:lc]) so no later pass
        # re-reads those rows
        fuse = fuse_hist

        def pass_b(i, acc):
            if renew:
                data, scratch, lrun, rcnt, hist, qmx = acc
            else:
                data, scratch, lrun, rcnt, hist = acc
            start = begin + i * CH
            win = jax.lax.dynamic_slice(data, (start, 0), (CH, d_cols))
            valid = iota_ch < (p - i * CH)
            gl = packed_go_left(
                win, feat, thr, dleft, f_numbins, f_missing, f_default,
                f_col, f_base, f_elide, item_bits=item_bits,
                f_categorical=f_categorical if has_cat else None,
                cat_mask=cmask) & valid
            if renew:
                qmx = jnp.maximum(
                    qmx, _quant_side_maxes(win, gl, valid, cw=cw, gw=gw))
            key3 = jnp.where(gl, 0, jnp.where(valid, 1, 2))
            # the chunk core's own partition, a stable argsort + row
            # gather of one fixed chunk: on `higgs-train` (12M x 28 x 255,
            # one TPU v5 lite; builder's chip runs, PR 31) it reads
            # 579,169 row-trees/s against 446,614 with the compact core's
            # scan (partition_window) in its place, `correct` true and
            # the compared numbers the same digit for digit. The gather
            # does not compile at a window of the whole table (24.4 GB
            # for the chip's 15.75, PR 27), which is why it lives here
            # and not in partition_window.
            with jax.named_scope("lgbm.partition"):
                win_s = jnp.take(
                    win, jnp.argsort(key3.astype(jnp.int8), stable=True),
                    axis=0)
            lc = jnp.sum(gl.astype(jnp.int32))
            vc = jnp.sum(valid.astype(jnp.int32))
            d_old = jax.lax.dynamic_slice(
                data, (begin + lrun, 0), (CH, d_cols))
            merged = jnp.where((iota_ch < lc)[:, None], win_s, d_old)
            data = jax.lax.dynamic_update_slice(
                data, merged, (begin + lrun, 0))
            win_pad = jnp.concatenate(
                [win_s, jnp.zeros((CH, d_cols), jnp.uint32)], axis=0)
            rights = jax.lax.dynamic_slice(
                win_pad, (lc, 0), (CH, d_cols))
            scratch = jax.lax.dynamic_update_slice(
                scratch, rights, (start, 0))
            if fuse:
                hist = hist + jax.lax.cond(
                    left_small, lambda _: chunk_hist(win_s, lc),
                    lambda _: hist_zero, operand=None)
            out = (data, scratch, lrun + lc, rcnt.at[i].set(vc - lc), hist)
            return out + (qmx,) if renew else out

        acc0 = (c.data, c.scratch, jnp.int32(0), zi(maxch), hist_zero)
        if renew:
            acc0 = acc0 + (jnp.zeros((2, 2), jnp.float32),)
            data, scratch, lphys, rcnt, hist_small, qmax2 = \
                jax.lax.fori_loop(0, nch, pass_b, acc0)
            if axis_name is not None:
                qmax2 = jax.lax.pmax(qmax2, axis_name)
        else:
            data, scratch, lphys, rcnt, hist_small = jax.lax.fori_loop(
                0, nch, pass_b, acc0)
        rphys = p - lphys
        roff = jnp.cumsum(rcnt) - rcnt

        # pass C: place staged right segments after the left block; when
        # the RIGHT child is smaller its histogram fuses here (chunk i's
        # rights sit at seg[:rcnt[i]])
        def pass_c(i, acc):
            data, hist = acc
            seg = jax.lax.dynamic_slice(
                scratch, (begin + i * CH, 0), (CH, d_cols))
            dst = begin + lphys + roff[i]
            d_old = jax.lax.dynamic_slice(data, (dst, 0), (CH, d_cols))
            merged = jnp.where((iota_ch < rcnt[i])[:, None], seg, d_old)
            data = jax.lax.dynamic_update_slice(data, merged, (dst, 0))
            if fuse:
                hist = hist + jax.lax.cond(
                    left_small, lambda _: hist_zero,
                    lambda _: chunk_hist(seg, rcnt[i]), operand=None)
            return data, hist

        data, hist_small = jax.lax.fori_loop(
            0, nch, pass_c, (data, hist_small))

        if not fuse:
            # separate smaller-child histogram pass (post-move layout)
            sb = begin + jnp.where(left_small, 0, lphys)
            sc = jnp.where(left_small, lphys, rphys)

            def pass_h(i, hist):
                start = sb + i * CH
                win = jax.lax.dynamic_slice(data, (start, 0),
                                            (CH, d_cols))
                return hist + chunk_hist(win, sc - i * CH)

            hist_small = jax.lax.fori_loop(0, -(-sc // CH), pass_h,
                                           hist_zero)
        # psum / psum_scatter-to-slice / identity (fp, voting, serial)
        if quant and scatter:
            s_cnt_g = jnp.where(left_small, row[B_LCNT], row[B_RCNT])
            s_qh_g = jnp.where(left_small, row[B_LSH], row[B_RSH]) \
                * (q_sh * rq_h)
            hist_small = reduce_q(hist_small, s_cnt_g, s_qh_g)
        elif quant:
            if axis_name is not None:
                hist_small = jax.lax.psum(hist_small, axis_name)
        else:
            hist_small = reduce_hist(hist_small)

        parent = c.pool[l]
        if renew:
            parent = quant_ops.rescale_histogram(
                parent, rq_g / scale_of[l, 0], rq_h / scale_of[l, 1])
        sibling = parent - hist_small
        hist_l = jnp.where(left_small, hist_small, sibling)
        hist_r = jnp.where(left_small, sibling, hist_small)
        pool = c.pool.at[l].set(hist_l).at[new_id].set(hist_r)

        leaf_begin = c.leaf_begin.at[new_id].set(begin + lphys)
        leaf_phys = c.leaf_phys.at[l].set(lphys).at[new_id].set(rphys)
        posv = jnp.arange(n + CH, dtype=jnp.int32)
        pos_leaf = jnp.where(
            (posv >= begin) & (posv < begin + lphys), l,
            jnp.where((posv >= begin + lphys) & (posv < begin + p),
                      new_id, c.pos_leaf))

        if quant:
            hist_l_s = q_dequant(hist_l, rq_g, rq_h)
            hist_r_s = q_dequant(hist_r, rq_g, rq_h)
        else:
            hist_l_s, hist_r_s = hist_l, hist_r
        (key, leaf_min, leaf_max, depth, rec2, rec_cat2, best2,
         best_cat2) = split_epilogue(
            k=c.k, key=c.key, l=l, new_id=new_id, row=row,
            feat=feat, f_monotone=f_monotone,
            leaf_min=c.leaf_min, leaf_max=c.leaf_max, depth=c.depth,
            rec=c.rec, rec_cat=c.rec_cat, best=b, best_cat=c.best_cat,
            hist_l=hist_l_s, hist_r=hist_r_s, search2=search2)
        c2 = _CarryK(new_id, data, scratch, pos_leaf, leaf_begin,
                     leaf_phys, pool, depth, leaf_min, leaf_max,
                     best2, best_cat2, rec2, rec_cat2, key)
        if renew:
            scale2 = jnp.stack([rq_g, rq_h])
            return c2, (scale_of.at[l].set(scale2).at[new_id].set(scale2),
                        leafmax.at[l].set(qmax2[0]).at[new_id]
                        .set(qmax2[1]))
        return c2, None

    if renew:
        scale0 = jnp.ones((L, 2), jnp.float32) \
            .at[0].set(jnp.stack([r0_g, r0_h]))
        leafmax0 = jnp.zeros((L, 2), jnp.float32).at[0].set(root_max)
        out, _ = run_split_loop(
            lambda t: cond(t[0]), lambda t: body(t[0], t[1]),
            (carry, (scale0, leafmax0)), L - 1, grow_program)
    else:
        out = run_split_loop(cond, lambda cc: body(cc)[0], carry,
                             L - 1, grow_program)
    with jax.named_scope("lgbm.row_leaf"):
        row_ids = out.data[:n, d_cols - 1].astype(jnp.int32)
        leaf_id = jnp.zeros(n, jnp.int32).at[row_ids].set(
            out.pos_leaf[:n], unique_indices=True)
    return (out.rec, out.rec_cat if has_cat else None,
            leaf_id, out.k, totals)


def make_sliced_search(*, axis_name, fp, D, c_cols, col_bins, item_bits,
                       base_mask, f_numbins, f_missing, f_default,
                       f_monotone, f_penalty, f_elide, f_categorical,
                       has_cat, cat_statics, helper_kwargs):
    """Feature-sliced scan + candidate election, shared by the compact
    core's scatter/feature-parallel modes and the chunk core's
    feature-parallel mode: every shard searches only the columns it owns
    (after the reduce-scatter in scatter mode — fp=False — or built
    directly over its slice in feature-parallel mode — fp=True), then
    the winner is elected from an all_gather of per-shard candidate rows
    (SyncUpGlobalBestSplit role). Returns (reduce_hist, search_row,
    search2_rows, cs, shard, start)."""
    f_all = int(f_numbins.shape[0])
    assert f_all == c_cols, \
        "sliced modes require identity feature->column mapping"
    if fp:
        # slice boundaries fall on packed-word boundaries so the
        # window decode can slice words directly
        cs = padded_shard_cols(c_cols, D, item_bits)
    else:
        cs = -(-c_cols // D)            # columns per shard (padded)
    c_pad = cs * D
    shard = jax.lax.axis_index(axis_name)
    start = (shard * cs).astype(jnp.int32)

    def pad1(a, fill):
        return jnp.pad(a, (0, c_pad - f_all), constant_values=fill)

    def sl(a):
        return jax.lax.dynamic_slice_in_dim(a, start, cs)

    mask_sl = sl(pad1(base_mask, False))
    nb_sl = sl(pad1(f_numbins, 1))
    miss_sl = sl(pad1(f_missing, 0))
    def_sl = sl(pad1(f_default, 0))
    mono_sl = sl(pad1(f_monotone, 0))
    pen_sl = sl(pad1(f_penalty, 1.0))
    elide_sl = sl(pad1(f_elide, 0))
    cat_sl = sl(pad1(f_categorical, 0)) if has_cat else None
    # identity mapping: the slice's column histogram is its features'
    # own, scanned as it is
    (_, scan_sl, _, _, best_row) = _tree_helpers(
        mask_sl, nb_sl, miss_sl, def_sl, mono_sl, pen_sl, elide_sl,
        None, f_categorical=cat_sl, cat_statics=cat_statics,
        **helper_kwargs)

    if fp:
        def reduce_hist(h):
            return h     # already the local slice over ALL rows
    else:
        def reduce_hist(h):
            h = jnp.pad(h, ((0, c_pad - c_cols), (0, 0), (0, 0)))
            return jax.lax.psum_scatter(
                h, axis_name, scatter_dimension=0, tiled=True)

    def _elect(row, cm):
        # the candidate row carries its (B,) categorical left-bin
        # mask through the election so every shard can route the
        # partition on a categorical winner it does not own
        # (SyncUpGlobalBestSplit's serialized cat_threshold role,
        # split_info.hpp:22-193)
        payload = jnp.concatenate([row, cm])     # (12 + cat_b,)
        rows = jax.lax.all_gather(payload, axis_name)
        win = rows[jnp.argmax(rows[:, B_GAIN])]
        return win[:12], win[12:]

    def search_row(col_hist, sg, sh, cnt, mn, mx, key, child_depth):
        res, cm = scan_sl(col_hist, sg, sh, cnt, mn, mx, mask_sl)
        row = best_row(res, child_depth)
        row = row.at[B_FEAT].add(start.astype(jnp.float32))
        return _elect(row, cm)

    def search2_rows(col_hist2, sg2, sh2, cnt2, mn2, mx2, keys2,
                     child_depth):
        res2, cm2 = jax.vmap(scan_sl)(
            col_hist2, sg2, sh2, cnt2, mn2, mx2,
            jnp.broadcast_to(mask_sl, (2,) + mask_sl.shape))
        rows = jax.vmap(
            functools.partial(best_row, child_depth=child_depth))(res2)
        rows = rows.at[:, B_FEAT].add(start.astype(jnp.float32))
        payload = jnp.concatenate([rows, cm2], axis=1)   # (2, 12+cat_b)
        g = jax.lax.all_gather(payload, axis_name)       # (D, 2, .)
        win = jnp.argmax(g[:, :, B_GAIN], axis=0)        # (2,)
        sel = g[win, jnp.arange(2)]
        return sel[:, :12], sel[:, 12:]

    return reduce_hist, search_row, search2_rows, cs, shard, start


# The most rows one scatter of the scan partition takes: the largest power
# of two in the cheapest regime of the TPU's row scatter. The compiler has
# two row scatters and chooses by the count of indices, whatever the row's
# width (the partition compiled for a described v5e at 3 to 40 words a
# row, PR 27): up to 2**18 indices it sorts them on chip and writes
# row-major tiles, past that it scatters row by row. u32[W, 11] alone under
# jit, ns a window row (builder's chip runs, PR 27): one scatter 14.2 at
# W = 2**18, 103-110 at W = 2**19..12,000,000 (at 5 and 21 words a row:
# 14.0 and 14.4, then 90.6 and 119.2); a 2**22-row window in tiles of
# 2**13 / 14 / 15 / 16 / 17 / 18 rows 7.3 / 6.4 / 8.4 / 8.2 / 11.8 / 12.8;
# the tree program's own rungs, untiled, 6.8 at 2**16 rows, 10.3 at 2**17,
# 11.8 at 2**18. On the TPU a tile goes through the Pallas kernel instead
# (`_partition_tile`), which holds the tile's output in VMEM: 2**16 rows
# keep it inside its budget up to 80 words a row.
SCATTER_TILE_ROWS = 1 << 16


def _rows_minor(x: jax.Array) -> jax.Array:
    """Pin a (rows, words) buffer to the packed table's own device layout
    (rows minor). The TPU compiler's fast scatter wants its (tile, words)
    operand words-minor, a word row padded to 128 lanes; unpinned, layout
    assignment hands that layout on through the slices to every
    window-sized buffer of the tiled partition, 512 B a row for 44."""
    return with_layout_constraint(x, Layout(major_to_minor=(1, 0)))


def _scan_partition(win: jax.Array, key3: jax.Array):
    """The window in the stable 3-way order of key3, and the counts of
    classes 0 and 1: per-class exclusive ranks via cumsum, then ONE row
    scatter."""
    is0 = key3 == 0
    is1 = key3 == 1
    i0 = is0.astype(jnp.int32)
    i1 = is1.astype(jnp.int32)
    i2 = (key3 == 2).astype(jnp.int32)
    n0 = jnp.sum(i0)
    n1 = jnp.sum(i1)
    d0 = jnp.cumsum(i0) - 1
    d1 = n0 + jnp.cumsum(i1) - 1
    d2 = n0 + n1 + jnp.cumsum(i2) - 1
    dest = jnp.where(is0, d0, jnp.where(is1, d1, d2))
    return (jnp.zeros_like(win).at[dest].set(win, unique_indices=True),
            n0, n1)


def _partition_tile_tpu(tile: jax.Array, key3: jax.Array):
    """`_partition_tile`'s lowering for the TPU: the Pallas lane
    compaction where `partition_kernel.engaged` for the tile's shape,
    else the scan."""
    if not partition_kernel.engaged("tpu", *tile.shape):
        return _scan_partition(tile, key3)
    s, c0, c1 = partition_kernel.partition_tile(_rows_minor(tile), key3)
    return _rows_minor(s), c0, c1


def _partition_tile_eager(tile: jax.Array, key3: jax.Array):
    """`_partition_tile` run op by op (`jax.disable_jit`): by the platform
    of the device that holds the tile."""
    if next(iter(tile.devices())).platform == "tpu":
        return _partition_tile_tpu(tile, key3)
    return _scan_partition(tile, key3)


# One primitive, lowered per platform: the kernel is traced only when
# the program is lowered for the TPU (`lax.platform_dependent` traces
# every branch, and the kernel's trace would cost each CPU lowering of
# a rung ~0.3 s). The rules look their functions up when they run.
_partition_tile_p = Primitive("lgbm_partition_tile")
_partition_tile_p.multiple_results = True
_partition_tile_p.def_abstract_eval(
    lambda tile, key3: (tile.update(),) + 2 * (key3.update(
        shape=(), dtype=jnp.dtype(jnp.int32), weak_type=False),))
_partition_tile_p.def_impl(lambda *a: _partition_tile_eager(*a))
mlir.register_lowering(_partition_tile_p,
                       mlir.lower_fun(lambda *a: _scan_partition(*a)))
mlir.register_lowering(_partition_tile_p,
                       mlir.lower_fun(lambda *a: _partition_tile_tpu(*a)),
                       platform="tpu")


def _partition_tile(tile: jax.Array, key3: jax.Array):
    """`_scan_partition` of one tile (key-2 rows its tail), by the Pallas
    lane compaction (ops/pallas/partition_kernel.py) where the program is
    lowered for the TPU and the kernel takes the tile's shape, and by the
    scan elsewhere. The platform is the lowering's, which is that of the
    device that holds the packed table: a Mosaic kernel does not run on
    the CPU backend, and the scan is the kernel's oracle there."""
    return tuple(_partition_tile_p.bind(tile, key3))


def _scan_partition_tiled(win: jax.Array, key3: jax.Array,
                          tile_rows: int) -> jax.Array:
    """_scan_partition of a window of more than `tile_rows` rows with no
    scatter larger than a tile: each tile is partitioned alone
    (`_partition_tile`: on the TPU by the Pallas kernel), and its
    class-0 and class-1 pieces are appended at running offsets by masked
    whole-tile read-modify-writes (as the chunk core's pass_b places its
    lefts). PRECONDITION: the key-2 rows are the window's tail
    (`valid = arange < pcount` in both callers), so they stay where the
    input has them. No write is clamped: a tile's class-0 piece starts
    at or before the tile itself, and its class-1 write starts at the
    earlier tiles' class-0 and class-1 rows plus the later tiles'
    class-0 rows, at most rows - size. By the same precondition the
    loop ends with the last tile that holds a row of class 0 or 1: a
    tile of key-2 rows alone writes nothing (both its masks are empty),
    so the work goes with the leaf's rows and not with the window."""
    rows, d = win.shape
    tiles, rem = divmod(rows, tile_rows)
    win = _rows_minor(win)
    n0 = jnp.sum((key3 == 0).astype(jnp.int32))
    live = jnp.sum((key3 < 2).astype(jnp.int32))

    def step(acc, start, size):
        out, o0, o1 = acc
        j = jnp.arange(size, dtype=jnp.int32)
        tile = _rows_minor(jax.lax.dynamic_slice(win, (start, 0),
                                                 (size, d)))
        s, c0, c1 = _partition_tile(
            tile, jax.lax.dynamic_slice(key3, (start,), (size,)))
        s = _rows_minor(s)
        # [0, c0) of the sorted tile to out[o0:], then [c0, c0 + c1) to
        # out[o1:]; o1 >= n0 >= c0, so neither start is clamped from below
        for at, lo, hi in ((o0, 0, c0), (o1 - c0, c0, c0 + c1)):
            old = jax.lax.dynamic_slice(out, (at, 0), (size, d))
            out = _rows_minor(jax.lax.dynamic_update_slice(
                out, _rows_minor(jnp.where(((j >= lo) & (j < hi))[:, None],
                                           s, old)), (at, 0)))
        return out, o0 + c0, o1 + c1

    acc = jax.lax.fori_loop(
        0, jnp.minimum((live + tile_rows - 1) // tile_rows, tiles),
        lambda i, acc: step(acc, i * tile_rows, tile_rows),
        (win, jnp.int32(0), n0))
    if rem:     # only the top rung, n itself, is not a power of two
        acc = run_once_if(live > tiles * tile_rows,
                          lambda acc: step(acc, tiles * tile_rows, rem), acc)
    return acc[0]


@jax.named_scope("lgbm.partition")
def partition_window(win: jax.Array, key3: jax.Array,
                     tile_rows: Optional[int] = None) -> jax.Array:
    """Stable 3-way reorder of a (W, D) u32 window by key3 in {0,1,2}
    (reference DataPartition::Split role), the compact core's partition:
    tile by tile where the window holds more than SCATTER_TILE_ROWS rows,
    and then only for key-2 rows that are the window's tail (see
    _scan_partition_tiled), each tile by `_partition_tile` (the Pallas
    lane compaction on the TPU, per-class exclusive ranks via cumsum +
    one row scatter elsewhere; no sort passes either way). The chunk
    core sorts its fixed chunks instead (pass B, with the reading that
    keeps it). `tile_rows` is for tests."""
    if tile_rows is None:
        tile_rows = SCATTER_TILE_ROWS
    if win.shape[0] > tile_rows:
        return _scan_partition_tiled(win, key3, tile_rows)
    return _partition_tile(win, key3)[0]


@jax.named_scope("lgbm.go_left")
def packed_go_left(win: jax.Array, feat, thr, dleft,
                   f_numbins, f_missing, f_default, f_col, f_base, f_elide,
                   *, item_bits: int, f_categorical=None,
                   cat_mask=None) -> jax.Array:
    """Decode feature `feat`'s codes from a packed u32 row window and
    apply the split decision — the one copy of the unpack + logical-bin +
    decide_left sequence shared by the partition branches and the
    out-of-bag router (any drift between them would silently mis-route).

    cat_mask (B,) enables categorical routing: when `feat` is categorical
    the row goes left iff its logical bin is set in the mask (the bitset
    semantics of CategoricalDecisionInner / partition_step_categorical)."""
    per = 32 // item_bits
    mask = jnp.uint32((1 << item_bits) - 1)
    n_r = win.shape[0]
    word = (f_col[feat] // per).astype(jnp.int32)
    sub = (f_col[feat] % per).astype(jnp.uint32)
    col32 = jax.lax.dynamic_slice(win, (0, word), (n_r, 1))[:, 0]
    col = ((col32 >> (sub * item_bits)) & mask).astype(jnp.int32)
    fbins = bundle_ops.logical_bins_for_feature(
        col, f_base[feat], f_default[feat], f_numbins[feat], f_elide[feat])
    num_left = decide_left(fbins, thr, dleft, f_missing[feat],
                           f_default[feat], f_numbins[feat])
    if cat_mask is None:
        return num_left
    cat_left = cat_mask[jnp.clip(fbins, 0, cat_mask.shape[0] - 1)] > 0.5
    return jnp.where(f_categorical[feat] != 0, cat_left, num_left)


def fused_step_surface(step_impl, make_args, obj_keys):
    """What every `make_fused_step` returns: `step(score_row, base_mask,
    tree_key, bag_key, shrinkage)` runs the jitted `step_impl` on the
    arguments `make_args` builds at CALL time (so a rebuilt code buffer
    is never silently shadowed by a stale snapshot). `step.impl` and
    `step.obj_keys` are the contract surface for tests and tools
    (program-size pinning). On the same five arguments, `step.lower(...)`
    lowers the program, `step.compiled_text(...)` is the text of the
    compiled module, and four probes read it: `step.stage_map(...)`
    maps its instructions to the `lgbm.<stage>` scopes
    (telemetry.stage_map),
    `step.table_copies(...)` counts the copies of the packed table
    inside the compiled split loop, by computation
    (telemetry.table_copies_in_split_loop),
    `step.hist_plane_elems(...)` sizes the one-hot plane the histogram
    products read, in elements a row
    (telemetry.hist_plane_elems_per_row; it feeds the gauge of that
    name), and `step.rank_pair_plane_elems(...)` the widest pair plane
    stored under a `rank_bucket_<L>` scope of `lgbm.gradients`, in
    elements (telemetry.rank_pair_plane_elems: 0 where the compiler
    builds the ranking objective's planes inside its fusions) — for a
    reader of a device trace and for tests, never on the hot path. The
    probes share one text per argument signature; on the arguments of
    a call that has run, lowering and compiling are hits of JAX's own
    caches and hand back the executable that ran (`GBDT.fused_stage_map`
    keeps their events out of the compile counters and reports a miss).
    A warm
    persistent compile cache hands back the executable as it was
    compiled, so a map from a cache entry older than the scopes comes
    back without them: `step.missing_scopes(...)` names the stages the
    lowered program has and the executable lacks
    (telemetry.scopes_missing)."""
    def step(*args):
        return step_impl(*make_args(*args))

    def lower(*args):
        return step_impl.lower(*make_args(*args))

    text = {}                 # {argument signature: compiled text}

    def compiled_text(*args):
        full = make_args(*args)
        leaves, tree = jax.tree.flatten(full)
        key = (tree, tuple((jax.typeof(x), getattr(x, "sharding", None))
                           for x in leaves))
        if key not in text:
            compiled = step_impl.lower(*full).compile().as_text()
            text.clear()
            text[key] = compiled
        return text[key]

    step.impl = step_impl
    step.obj_keys = obj_keys
    step.lower = lower
    step.compiled_text = compiled_text
    step.stage_map = lambda *args: telemetry.stage_map(compiled_text(*args))

    def missing_scopes(*args):
        return telemetry.scopes_missing(
            lower(*args).as_text(debug_info=True), compiled_text(*args))

    step.missing_scopes = missing_scopes
    step.table_copies = lambda *args: telemetry.table_copies_in_split_loop(
        compiled_text(*args))

    def hist_plane_elems(*args):
        elems = telemetry.hist_plane_elems_per_row(compiled_text(*args))
        telemetry.counters.set_gauge("hist_plane_elems_per_row", elems)
        return elems

    step.hist_plane_elems = hist_plane_elems
    step.rank_pair_plane_elems = lambda *args: \
        telemetry.rank_pair_plane_elems(compiled_text(*args))
    return step


def objective_buffer_names(objective):
    """Names of the objective's device buffers (label, weights,
    transformed labels, lambdarank's segment tensors ...) read inside
    get_gradients. The fused steps pass these as jit ARGUMENTS via a
    trace-time attribute swap so they lower as parameters instead of HLO
    constants — the same payload/cache argument as the code buffers.
    Objectives declare them via device_buffer_names(); the per-row
    heuristic remains for duck-typed custom objectives."""
    fn = getattr(objective, "device_buffer_names", None)
    if fn is not None:
        return list(fn())
    n = getattr(objective, "num_data", None)
    if not n:
        return []
    return sorted(
        k for k, v in vars(objective).items()
        if isinstance(v, jax.Array) and v.ndim >= 1 and v.shape[0] == n)


@contextlib.contextmanager
def swapped_attrs(obj, names, values):
    saved = [getattr(obj, k) for k in names]
    for k, v in zip(names, values):
        setattr(obj, k, v)
    try:
        yield
    finally:
        for k, v in zip(names, saved):
            setattr(obj, k, v)


def exact_k_bag_weights(bag_key: jax.Array, n: int, bag_k: int) -> jax.Array:
    """0/1 weight vector with exactly bag_k ones, deterministic per key
    (reference Bagging, gbdt.cpp:210-276)."""
    u = jax.random.uniform(bag_key, (n,))
    cut = jnp.sort(u)[bag_k - 1]
    return (u <= cut).astype(jnp.float32)


def goss_sample(g, h, bag_key, n: int, top_k: int, other_k: int,
                multiply: float):
    """The ONE copy of in-program GOSS sampling (reference
    src/boosting/goss.hpp:60-117), shared by the serial and the
    feature-parallel fused steps: rank-based exact top_k by |g*h|
    (gradient ties cannot change the subset size), exactly other_k of
    the rest uniformly, amplified by `multiply` (goss.hpp:91). Returns
    (g, h, w, bag_idx, oob_idx) — amplified gradients, 0/1 weights, and
    the in-bag / out-of-bag row ids for bag compaction."""
    gmag = jnp.abs(g * h)
    ridx = jnp.argsort(-gmag, stable=True)
    top_idx, rest = ridx[:top_k], ridx[top_k:]
    perm = jnp.argsort(jax.random.uniform(bag_key, (n - top_k,)))
    other_idx = jnp.take(rest, perm[:other_k])
    oob_idx = jnp.take(rest, perm[other_k:])
    bag_idx = jnp.concatenate([top_idx, other_idx])
    amp = jnp.ones((n,), jnp.float32).at[other_idx].set(
        jnp.float32(multiply), unique_indices=True)
    w = jnp.zeros((n,), jnp.float32).at[bag_idx].set(
        1.0, unique_indices=True)
    return g * amp, h * amp, w, bag_idx, oob_idx


def route_rows_by_rec(codes_pack_rows: jax.Array, rec: jax.Array,
                      k: jax.Array, f_numbins, f_missing, f_default,
                      f_col, f_base, f_elide, *, item_bits: int,
                      num_leaves: int, rec_cat=None,
                      f_categorical=None) -> jax.Array:
    """Assign rows to leaves by replaying the (L-1, 13) split records.

    The role of the reference's out-of-bag AddPredictionToScore: rows that
    did not participate in training still need their leaf. Each replayed
    split streams ONE packed code column over the rows (no gathers), so
    the whole pass costs O(rows * splits) sequential-bandwidth work —
    cheap next to growing the tree itself."""
    n_r = codes_pack_rows.shape[0]

    def body(i, leaf):
        r = rec[i]
        do = i < k
        go_left = packed_go_left(
            codes_pack_rows, r[R_FEAT].astype(jnp.int32),
            r[R_THR].astype(jnp.int32), r[R_DLEFT] > 0.5,
            f_numbins, f_missing, f_default, f_col, f_base, f_elide,
            item_bits=item_bits, f_categorical=f_categorical,
            cat_mask=None if rec_cat is None else rec_cat[i])
        at = leaf == r[R_LEAF].astype(jnp.int32)
        return jnp.where(do & at & ~go_left, i + 1, leaf)

    return jax.lax.fori_loop(0, num_leaves - 1, body,
                             jnp.zeros(n_r, jnp.int32))


def leaf_values_from_rec(rec: jax.Array, k: jax.Array, L: int) -> jax.Array:
    """On-device replay of the (L-1, 13) split records into the final (L,)
    leaf-value vector: split i rewrites its leaf with lout and writes rout
    into leaf i+1 (the same ids the host replay assigns)."""
    def body(i, lv):
        do = i < k
        leaf = rec[i, R_LEAF].astype(jnp.int32)
        lv = lv.at[leaf].set(jnp.where(do, rec[i, R_LOUT], lv[leaf]))
        lv = lv.at[i + 1].set(jnp.where(do, rec[i, R_ROUT], lv[i + 1]))
        return lv
    return jax.lax.fori_loop(0, L - 1, body, jnp.zeros((L,), jnp.float32))


def padded_shard_cols(c_cols: int, shards: int, item_bits: int) -> int:
    """Word-aligned per-shard column width for feature-parallel slicing:
    ceil(c_cols / shards) rounded up to a whole packed u32 word. The ONE
    copy used by the learner's packing and the core's slice math."""
    per = 32 // item_bits
    cs = -(-c_cols // shards)
    return -(-cs // per) * per


def padded_device_bins(raw_bins: int) -> int:
    """Pow2-padded on-device bin count (min 16) — the one copy of the
    padding rule used for device_bins, col_device_bins and the pool
    plan. raw_bins <= 256 always pads to <= 256, so u8 storage holds."""
    return 1 << max(4, (int(raw_bins) - 1).bit_length())


def resolve_strategy(config: Config, dataset: Dataset,
                     forced: Optional[str] = None) -> str:
    """Growth-strategy selection shared by __init__ and supports():
    compaction pays off once O(N)-per-split masked passes dominate;
    small data stays on the simpler masked program. 'chunk' is the
    ladder-free fixed-chunk formulation (opt-in pending on-chip A/B);
    it requires the dense histogram pool, so LRU-capped configs fall
    back to compact."""
    strat = forced or strategy_env()
    stream = str(getattr(config, "stream_mode", "off") or "off")
    if stream in ("chunked", "goss"):
        # streaming assembles the chunk core's working buffer from host
        # chunks; masked has no chunk seam to hook, and an LRU-capped
        # pool cannot take the per-chunk accumulation. Loud errors
        # beat a silent fallback to a non-streaming core.
        if strat == "masked":
            raise LightGBMError(
                "stream_mode=%s requires the chunk growth core; the "
                "masked strategy has no chunk seam (unset "
                "LGBM_TPU_STRATEGY=masked or turn streaming off)"
                % stream)
        _, pool_slots = plan_histogram_pool(config, dataset)
        if pool_slots > 0:
            raise LightGBMError(
                "stream_mode=%s needs the dense histogram pool but "
                "num_leaves=%d exceeds the histogram_pool_size budget "
                "(LRU pool has no chunk seam); raise "
                "histogram_pool_size or reduce num_leaves"
                % (stream, int(config.num_leaves)))
        return "chunk"
    if strat == "auto":
        # the quantized pipeline rides every strategy: masked (int pool
        # + dequant-hook scans) below the compaction threshold, packed
        # compact/chunk (one-word (qg|qh) rows) above it
        strat = "compact" if dataset.num_data >= 65536 else "masked"
    if strat == "chunk":
        _, pool_slots = plan_histogram_pool(config, dataset)
        if pool_slots > 0:
            # silent here: supports() probes this speculatively; __init__
            # logs the actual fallback once
            strat = "compact"
    return strat


def plan_histogram_pool(config: Config, dataset: Dataset):
    """(slot_bytes, pool_slots): the LRU histogram-pool budget math
    (reference HistogramPool, feature_histogram.hpp:654-831) — the ONE
    copy used by both __init__ and the supports() capability check.
    histogram_pool_size is the reference's knob (MB, < 0 = no explicit
    limit); without it we default to a 1 GiB HBM budget. pool_slots == 0
    means the dense one-slot-per-leaf pool fits."""
    if dataset.columns:
        ncols = max(1, len(dataset.columns))
        raw_bins = max(c.num_bins for c in dataset.columns)
    else:
        ncols = max(1, dataset.num_features)
        raw_bins = int(dataset.max_num_bins)
    slot_bytes = ncols * padded_device_bins(raw_bins) * 12
    if config.histogram_pool_size and config.histogram_pool_size > 0:
        budget = int(config.histogram_pool_size * (1 << 20))
    else:
        budget = 1 << 30
    k_cap = max(8, budget // slot_bytes)
    L = int(config.num_leaves)
    return slot_bytes, (k_cap if L > k_cap else 0)


class DeviceTreeLearner:
    """Drop-in TreeLearner whose Train runs one jitted program per tree."""

    # make_fused_step(goss=...) is implemented (in-program sampling);
    # subclasses without it override to False
    supports_fused_goss = True

    def __init__(self, config: Config, dataset: Dataset,
                 strategy: Optional[str] = None, device_place: bool = True):
        with telem_spans.stage("setup_learner_build_seconds",
                               "learner_build"):
            self._build(config, dataset, strategy, device_place)

    def _build(self, config: Config, dataset: Dataset,
               strategy: Optional[str], device_place: bool) -> None:
        # device_place=False keeps the compact buffers host-side so a
        # sharding subclass can place them itself without a device
        # round-trip (DeviceDataParallelTreeLearner)
        self.config = config
        self.dataset = dataset
        (self.f_numbins, self.f_missing, self.f_default,
         self.f_categorical, self.f_monotone) = dataset.feature_meta_arrays()
        self._f_missing_host = np.asarray(self.f_missing)
        # categorical splits run inside the whole-tree program (scan-level
        # merge); gbdt's fused path checks cat_in_program before masking
        # categorical features out of the feature sample
        self._has_cat = bool(np.any(np.asarray(self.f_categorical)))
        self.cat_in_program = self._has_cat
        self.num_features = dataset.num_features
        self.num_bins = int(dataset.max_num_bins)
        self.device_bins = padded_device_bins(self.num_bins)
        # out-of-core streaming: the binned matrix stays host-side in
        # the packed wire format and chunks onto the device per
        # iteration (io/stream.py); no device-resident codes_t /
        # codes_pack / codes_row copies exist in this mode
        self.stream_mode = str(getattr(config, "stream_mode", "off")
                               or "off")
        stream_on = self.stream_mode != "off"
        bundle = dataset.bundle_arrays()
        if bundle is not None:
            codes, f_col, f_base, f_elide, hist_idx, col_bins = bundle
            self.codes_t = (None if stream_on else
                            jnp.asarray(jnp.swapaxes(codes, 0, 1)))  # (C, N)
            self.f_col, self.f_base, self.f_elide = f_col, f_base, f_elide
            self.col_device_bins = padded_device_bins(int(col_bins))
            n_cols = len(dataset.columns)
            hi = bundle_ops.respace_hist_idx(
                hist_idx, n_cols, int(col_bins), self.col_device_bins,
                self.device_bins)
            self.scan_plan, plane_elems = bundle_ops.split_scan_plan(
                hi, self.f_numbins, self.f_categorical, n_cols,
                self.col_device_bins)
        else:
            if stream_on or getattr(dataset, "row_shard", None) is not None:
                # streaming holds no resident codes; a row-sharded
                # (dist_shard_mode=rows) dataset has only its local block
                # host-side and always runs the compact/chunk strategy,
                # which reads codes_pack/codes_row — the (F, N) masked-
                # strategy view would need the full matrix
                self.codes_t = None
            else:
                binned = dataset.device_binned()
                self.codes_t = jnp.asarray(
                    jnp.swapaxes(binned, 0, 1))  # (F, N)
            nf = self.num_features
            self.f_col = jnp.arange(nf, dtype=jnp.int32)
            self.f_base = jnp.zeros(nf, jnp.int32)
            self.f_elide = jnp.zeros(nf, jnp.int32)
            self.col_device_bins = self.device_bins
            # feature j is column j: its histogram is scanned as it is
            self.scan_plan, plane_elems = None, nf * self.device_bins
        # the (feature, bin) positions one child's split scan reads; how
        # much wider a plane of every feature at the device bins would be
        # than the column histogram (a property of the plan); and the
        # share of the features that share a column with others
        columns = dataset.columns or []
        telemetry.counters.set_gauge("split_scan_plane_elems", plane_elems)
        telemetry.counters.set_gauge(
            "hist_expansion_ratio",
            self.num_features * self.device_bins
            / max((len(columns) or self.num_features)
                  * self.col_device_bins, 1))
        telemetry.counters.set_gauge(
            "bundled_feature_share",
            100.0 * sum(len(c.features) for c in columns if c.is_bundle)
            / max(self.num_features, 1))
        contri = config.feature_contri or []
        pen = np.array([contri[fr] if fr < len(contri) else 1.0
                        for fr in dataset.used_features], dtype=np.float32)
        self.f_penalty = jnp.asarray(pen)
        # the XLA one-hot contraction is the default even on TPU: the
        # Pallas kernel lost to it in the builder's 2026-08-01 v5e run
        # (a dated hypothesis; not measured on today's code)
        self._use_pallas = use_pallas_env()
        # quantized-gradient training: >0 switches every growth strategy
        # to exact int32 histograms (jit caches key on this static);
        # quant_renew enables the packed cores' leaf-wise re-quantization
        self.quant_bits = config.quant_bits
        self.quant_renew = bool(getattr(config, "quant_renew", True))
        self.hist_chunk = int(config.hist_chunk_size or 0)
        requested = strategy or strategy_env()
        self.strategy = resolve_strategy(config, dataset, strategy)
        if requested == "chunk" and self.strategy != "chunk":
            log.warning("chunk strategy needs the dense histogram pool; "
                        "using compact (LRU-capped) instead")
        if (self.strategy == "masked" and dataset.num_data >= 262144
                and int(config.num_leaves) >= 127):
            # the masked program's compile ran past 19 minutes at
            # 1M x 255 on a v5e (builder-run, 2026-08-01, not re-measured);
            # auto never picks it at this scale, so this is an explicit
            # opt-in
            log.warning(
                "masked strategy at %d rows x %d leaves compiles very "
                "slowly; compact or chunk is strongly recommended",
                dataset.num_data, int(config.num_leaves))
        self.chunk_rows = max(8192, int(_env("LGBM_TPU_CHUNK", "65536")))
        # LRU-capped histogram pool: when the dense (L,C,B,3) pool would
        # exceed the budget, the compact strategy runs with K LRU slots
        # and rebuilds sibling histograms on miss
        _, self.pool_slots = plan_histogram_pool(config, dataset)
        self._shard: Optional[DeviceDataShard] = None
        if self.strategy in ("compact", "chunk"):
            host_codes = (dataset.bundled if dataset.bundled is not None
                          else dataset.binned)
            host_codes = np.asarray(host_codes)
            # bit-pack column codes into u32 words for the physically
            # reordered working buffer (8 4-bit, 4 u8, or 2 u16 codes per
            # word). The 4-bit form is the reference's Dense4bitsBin
            # (src/io/dense_nbits_bin.hpp) — usable whenever every
            # column's codes fit a nibble (max_bin <= 16), halving HBM
            # traffic per partition pass.
            # decide from DECLARED per-column bin counts, not the data:
            # a data-dependent choice would let rank-partitioned shards
            # disagree on the packed layout (divergent traced programs)
            if dataset.columns:
                declared_bins = max(c.num_bins for c in dataset.columns)
            else:
                declared_bins = int(dataset.max_num_bins)
            if host_codes.dtype.itemsize == 2:
                self.item_bits = 16
            elif declared_bins <= 16:
                self.item_bits = 4
            else:
                self.item_bits = 8
            self.c_cols = host_codes.shape[1]
            packed = self.pack_codes(host_codes)
            # bytes a row of the working table the partition moves:
            # packed code words + gradient words (the trivial-weight,
            # unbagged layout) + the row id
            self.table_row_bytes = 4 * (
                packed.shape[1] + (1 if self.quant_bits > 0 else 3) + 1)
            telemetry.counters.set_gauge("table_bytes_per_row",
                                         self.table_row_bytes)
            if stream_on:
                # host wire store + double-buffered H2D chunk pipeline;
                # the device never holds a full copy of the binned rows
                self.codes_row = None
                self.codes_pack = None
                self._shard = DeviceDataShard(
                    packed, item_bits=self.item_bits,
                    c_cols=self.c_cols,
                    chunk_rows=int(getattr(
                        config, "stream_chunk_rows", 0) or 0),
                    core_chunk_rows=self.chunk_rows)
            elif device_place:
                self.codes_row = jnp.asarray(host_codes)      # (N, C)
                self.codes_pack = jnp.asarray(packed)
            else:
                self.codes_row = host_codes
                self.codes_pack = packed
        else:
            self.codes_row = None
            self.codes_pack = None
            self.item_bits = 8
            self.c_cols = int(self.codes_t.shape[0])
        self._ones_w = None
        self.last_leaf_id: Optional[jax.Array] = None
        self._leaf_id_host: Optional[np.ndarray] = None
        self._bag_mask_host: Optional[np.ndarray] = None
        # streaming per-iteration context (assembled data0 + subset ids)
        # and the GOSS working-set hint handed down by the booster
        self._stream_ctx: Optional[dict] = None
        self._stream_top_hint: Optional[np.ndarray] = None
        self._stream_jits: dict = {}
        # vmap-batched multiclass growth (train_batched): jitted
        # class-batched grow programs keyed by K, and the per-class leaf
        # routing of the last batched iteration
        self._batched_fns: dict = {}
        self._batched_leaf_ids: Optional[jax.Array] = None

    def pack_codes(self, host_codes: np.ndarray,
                   col_target: Optional[int] = None) -> np.ndarray:
        """Bit-pack (N, C) column codes into u32 words for the compact
        working buffer. col_target pads the column capacity (the
        feature-parallel learner needs word-aligned per-shard slices)."""
        nrow, ncol = host_codes.shape
        want = max(ncol, col_target or 0)
        if self.item_bits == 4:
            npairs = ((want + 7) // 8) * 4          # byte pairs per row
            byte_arr = np.zeros((nrow, npairs * 2), dtype=np.uint8)
            byte_arr[:, :ncol] = host_codes
            packed_bytes = (byte_arr[:, 0::2]
                            | (byte_arr[:, 1::2] << 4)).astype(np.uint8)
            return np.ascontiguousarray(packed_bytes).view(np.uint32)
        per = 32 // self.item_bits
        padded = np.zeros((nrow, ((want + per - 1) // per) * per),
                          dtype=np.uint8 if self.item_bits == 8
                          else np.uint16)
        padded[:, :ncol] = host_codes
        return np.ascontiguousarray(padded).view(np.uint32)

    # ------------------------------------------------------------------
    @staticmethod
    def unsupported_reason(config: Config, dataset: Dataset,
                           strategy: Optional[str] = None,
                           categorical_ok: bool = True) -> Optional[str]:
        """Static capability check: None when the whole-tree device
        program can train this config, else why not (unsupported configs
        use the host-loop learner; create_tree_learner says so).
        categorical_ok=False lets a caller opt out of device categorical
        handling (no in-tree caller does since round 3 wired categoricals
        into every sharded mode; kept for API stability)."""
        if not categorical_ok and any(
                dataset.bin_mappers[fr].bin_type == BIN_CATEGORICAL
                for fr in dataset.used_features):
            return "caller opted out of device categorical handling"
        if config.forcedsplits_filename:
            return "forced splits run only on the host-loop learner"
        if config.cegb_tradeoff > 0 and (
                config.cegb_penalty_split > 0
                or bool(config.cegb_penalty_feature_coupled)
                or bool(config.cegb_penalty_feature_lazy)):
            return "CEGB penalties run only on the host-loop learner"
        # pool footprint via the same plan __init__ uses: the compact
        # strategy caps at K LRU slots, only the masked strategy's dense
        # (L, C, B, 3) pool can blow up. `strategy` lets callers that
        # force a strategy (DeviceDataParallelTreeLearner forces compact)
        # check the learner they will actually build.
        slot_bytes, pool_slots = plan_histogram_pool(config, dataset)
        strat = resolve_strategy(config, dataset, strategy)
        if strat == "compact" and pool_slots > 0:
            slots = pool_slots
        else:
            slots = int(config.num_leaves)
        if slots * slot_bytes > _POOL_BYTE_LIMIT:
            return ("the histogram pool (%d slots x %d bytes) exceeds the "
                    "device budget" % (slots, slot_bytes))
        return None

    @staticmethod
    def supports(config: Config, dataset: Dataset,
                 strategy: Optional[str] = None,
                 categorical_ok: bool = True) -> bool:
        return DeviceTreeLearner.unsupported_reason(
            config, dataset, strategy, categorical_ok) is None

    def _statics(self):
        cfg = self.config
        bynode_k = 0
        if 0.0 < cfg.feature_fraction_bynode < 1.0:
            bynode_k = max(1, int(self.num_features * cfg.feature_fraction_bynode))
        # a hashable tuple (jit static): (cat_l2, cat_smooth,
        # max_cat_threshold, max_cat_to_onehot, min_data_per_group)
        cat_statics = None
        if self._has_cat:
            cat_statics = (float(cfg.cat_l2), float(cfg.cat_smooth),
                           int(cfg.max_cat_threshold),
                           int(cfg.max_cat_to_onehot),
                           int(cfg.min_data_per_group))
        return dict(
            cat_statics=cat_statics,
            num_leaves=int(cfg.num_leaves), num_bins=self.device_bins,
            col_bins=self.col_device_bins,
            max_depth=int(cfg.max_depth), l1=float(cfg.lambda_l1),
            l2=float(cfg.lambda_l2),
            max_delta_step=float(cfg.max_delta_step),
            min_data_in_leaf=int(cfg.min_data_in_leaf),
            min_sum_hessian=float(cfg.min_sum_hessian_in_leaf),
            min_gain_to_split=float(cfg.min_gain_to_split),
            bynode_k=bynode_k, use_pallas=self._use_pallas,
            grow_program=str(getattr(cfg, "grow_program", "per_split")))

    def _feature_mask(self, rng: np.random.RandomState) -> np.ndarray:
        frac = self.config.feature_fraction
        mask = np.ones(self.num_features, dtype=bool)
        if 0.0 < frac < 1.0:
            k = max(1, int(self.num_features * frac))
            chosen = rng.choice(self.num_features, k, replace=False)
            mask[:] = False
            mask[chosen] = True
        return mask

    # ------------------------------------------------------------------
    def train(self, grad: jax.Array, hess: jax.Array,
              bag_indices: Optional[np.ndarray] = None,
              iter_seed: int = 0) -> Tree:
        cfg = self.config
        ds = self.dataset
        n = ds.num_data
        if bag_indices is None:
            if self._ones_w is None:
                self._ones_w = jnp.ones(n, jnp.float32)
            w = self._ones_w
            self._bag_mask_host = None
        else:
            wv = np.zeros(n, dtype=np.float32)
            wv[bag_indices] = 1.0
            w = jnp.asarray(wv)
            self._bag_mask_host = wv > 0
        rng = np.random.RandomState(
            (cfg.feature_fraction_seed + iter_seed) % (2**31 - 1))
        base_mask = jnp.asarray(self._feature_mask(rng))
        key = jax.random.PRNGKey(iter_seed)

        if self._shard is not None:
            # assemble the streamed working buffer BEFORE grow_dispatch:
            # the shard attributes its blocking residue to the
            # stream_wait recorder phase, and phases must not nest
            self._stream_ctx = self._stream_assemble(
                grad, hess, w, key, bag_indices)

        with telem.phase("grow_dispatch"):
            rec, rec_cat, leaf_id, n_splits, _ = self._run_grow(
                grad, hess, w, base_mask, key)
        telemetry.note_grow_dispatches(1.0, trees=1.0)

        self.last_leaf_id = leaf_id
        self._leaf_id_host = None
        with telem.phase("record_fetch"):
            if rec_cat is None:
                rec_h, k = jax.device_get((rec, n_splits))
                rec_cat_h = None
            else:
                rec_h, rec_cat_h, k = jax.device_get(
                    (rec, rec_cat, n_splits))
        k = int(k)
        if k == 0:
            log.warning("No further splits with positive gain")
        with telem.phase("tree_replay"):
            return self.replay_tree(rec_h, k, rec_cat_h)

    # -- vmap-batched multiclass growth --------------------------------
    def supports_batched_k(self) -> bool:
        """Whether train_batched can grow all K per-class trees of one
        boosting iteration as ONE batched device program. Requires the
        fused-tree growth program (the fixed-trip scan is what makes the
        whole-tree program vmappable — a data-dependent while_loop has
        no batch rule), the masked strategy (one shared dense code
        buffer; the packed strategies' LRU pool ladder is per-tree
        state), and resident data."""
        return (type(self) is DeviceTreeLearner
                and self.strategy == "masked"
                and self._shard is None
                and str(getattr(self.config, "grow_program",
                                "per_split")) == "fused_tree")

    def _batched_grow_fn(self, num_class: int):
        """jit(vmap(grow_tree)) over the class axis, cached per K. The
        code buffer and row weights are shared (in_axes=None); per-class
        gradients, hessians, feature masks, and RNG keys are batched —
        so per-class quant scales (derived in-program from grad/hess and
        the key) ride as batched operands automatically."""
        fn = self._batched_fns.get(num_class)
        if fn is not None:
            return fn
        statics = self._statics()
        meta = (self.f_numbins, self.f_missing, self.f_default,
                self.f_monotone, self.f_penalty, self.f_categorical,
                self.f_col, self.f_base, self.f_elide, self.scan_plan)
        quant_bits, hist_chunk = self.quant_bits, self.hist_chunk

        def one(codes_t, g, h, w, base_mask, key):
            return grow_tree(codes_t, g, h, w, base_mask, *meta, key,
                             quant_bits=quant_bits, hist_chunk=hist_chunk,
                             **statics)

        fn = jax.jit(jax.vmap(one, in_axes=(None, 0, 0, None, 0, 0)))
        self._batched_fns[num_class] = fn
        return fn

    def train_batched(self, grad: jax.Array, hess: jax.Array,
                      bag_indices: Optional[np.ndarray] = None,
                      iter_seed0: int = 0) -> List[Tree]:
        """Grow the K per-class trees of one boosting iteration as ONE
        batched device dispatch (large-K multiclass: K trees/iteration
        used to cost K grow dispatches + K host syncs).

        Seeds match train() exactly: class k uses
        iter_seed = iter_seed0 + k for both the feature-fraction
        RandomState and the PRNGKey, so the batched program is
        bit-identical to the per-class loop. Per-class leaf routing
        lands in self._batched_leaf_ids; the caller installs row k as
        last_leaf_id before each per-class score update."""
        cfg = self.config
        n = self.dataset.num_data
        K = int(grad.shape[0])
        if bag_indices is None:
            if self._ones_w is None:
                self._ones_w = jnp.ones(n, jnp.float32)
            w = self._ones_w
            self._bag_mask_host = None
        else:
            wv = np.zeros(n, dtype=np.float32)
            wv[bag_indices] = 1.0
            w = jnp.asarray(wv)
            self._bag_mask_host = wv > 0
        masks = np.stack([
            self._feature_mask(np.random.RandomState(
                (cfg.feature_fraction_seed + iter_seed0 + k) % (2**31 - 1)))
            for k in range(K)])
        base_masks = jnp.asarray(masks)
        keys = jnp.stack([jax.random.PRNGKey(iter_seed0 + k)
                          for k in range(K)])
        fn = self._batched_grow_fn(K)
        with telem.phase("grow_fused"):
            rec, rec_cat, leaf_ids, n_splits, _ = fn(
                self.codes_t, grad, hess, w, base_masks, keys)
        telemetry.note_grow_dispatches(1.0, trees=float(K))
        self._batched_leaf_ids = leaf_ids
        self.last_leaf_id = None
        self._leaf_id_host = None
        with telem.phase("record_fetch"):
            if rec_cat is None:
                rec_h, ks = jax.device_get((rec, n_splits))
                rec_cat_h = None
            else:
                rec_h, rec_cat_h, ks = jax.device_get(
                    (rec, rec_cat, n_splits))
        trees = []
        with telem.phase("tree_replay"):
            for k in range(K):
                kk = int(ks[k])
                if kk == 0:
                    log.warning("No further splits with positive gain")
                trees.append(self.replay_tree(
                    rec_h[k], kk,
                    None if rec_cat_h is None else rec_cat_h[k]))
        return trees

    def _grow_fn_kwargs(self, trivial_weights: bool = False):
        """(grow fn, strategy-specific kwargs) for the packed strategies.
        trivial_weights asserts the weight vector reaching the grower is
        all-ones; only the compact strategy consumes it (it drops the
        masked full-window histogram fallback), and only below 2**24
        rows where the float32 record counts that pick the smaller side
        are exact integers."""
        trivial = trivial_weights and self.dataset.num_data < (1 << 24)
        if self.strategy == "chunk":
            return grow_tree_chunk, dict(
                c_cols=self.c_cols, item_bits=self.item_bits,
                chunk_rows=self.chunk_rows,
                fuse_hist=not flag("LGBM_TPU_CHUNK_NO_FUSE_HIST"),
                trivial_weights=trivial,
                quant_bits=self.quant_bits, quant_renew=self.quant_renew)
        return grow_tree_compact, dict(
            c_cols=self.c_cols, item_bits=self.item_bits,
            pool_slots=self.pool_slots, trivial_weights=trivial,
            quant_bits=self.quant_bits, quant_renew=self.quant_renew)

    def _run_grow(self, grad, hess, w, base_mask, key):
        """The grow-program invocation; sharded subclasses override this
        single hook and inherit the rest of train()."""
        if self._stream_ctx is not None:
            return self._run_grow_streamed(base_mask, key)
        if self.strategy in ("compact", "chunk"):
            grow, kw = self._grow_fn_kwargs(
                trivial_weights=w is self._ones_w)
            return grow(
                self.codes_pack, self.codes_row, grad, hess, w, base_mask,
                self.f_numbins, self.f_missing, self.f_default,
                self.f_monotone, self.f_penalty, self.f_categorical,
                self.f_col, self.f_base,
                self.f_elide, self.scan_plan, key, **kw, **self._statics())
        return grow_tree(
            self.codes_t, grad, hess, w, base_mask,
            self.f_numbins, self.f_missing, self.f_default,
            self.f_monotone, self.f_penalty, self.f_categorical,
            self.f_col, self.f_base,
            self.f_elide, self.scan_plan, key,
            quant_bits=self.quant_bits, hist_chunk=self.hist_chunk,
            **self._statics())

    # -- out-of-core streaming (io/stream.py) --------------------------
    def _stream_init_fn(self, rows_n: int, trivial: bool):
        """jit that builds the (rows_n + CH, d_cols) u32 working buffer
        with the gh words + row-id columns filled and the code section
        zeroed (chunk writes fill it). The quantized path runs
        _quant_prepare with the SAME rng_key the growth core re-derives
        its scales from, so the core stays the one source of key/scale
        derivation and the assembled gh words match it bit-for-bit."""
        jkey = ("init", rows_n, trivial)
        fn = self._stream_jits.get(jkey)
        if fn is None:
            quant = self.quant_bits > 0
            gw = (1 if trivial else 2) if quant else 3
            cw = int(self._shard.code_words)
            CH = int(self.chunk_rows)
            d_cols = cw + gw + 1
            qb, qr = self.quant_bits, self.quant_renew

            def init(grad, hess, w, rng_key):
                if quant:
                    _, gh_packed, _, _, _ = _quant_prepare(
                        grad, hess, w, rng_key, quant_bits=qb,
                        quant_renew=qr, n_total=rows_n, axis_name=None)
                    gh_u = _quant_gh_words(gh_packed, w, gw)
                else:
                    gh_u = jax.lax.bitcast_convert_type(
                        jnp.stack([grad * w, hess * w, w], axis=1),
                        jnp.uint32)
                ids = jnp.arange(rows_n, dtype=jnp.uint32)[:, None]
                tail = jnp.concatenate([gh_u, ids], axis=1)
                buf = jnp.zeros((rows_n + CH, d_cols), jnp.uint32)
                return jax.lax.dynamic_update_slice(
                    buf, tail, (jnp.int32(0), jnp.int32(cw)))

            fn = jax.jit(init)
            self._stream_jits[jkey] = fn
        return fn

    def _stream_write(self, data0, chunk, start: int):
        """Donated contiguous chunk write: data0[start:start+rows, :CW]
        = chunk. Chunks are exact-sized (the tail chunk keeps its
        natural shape), so the write never clamps."""
        jkey = ("write", int(chunk.shape[0]),
                tuple(int(d) for d in data0.shape))
        fn = self._stream_jits.get(jkey)
        if fn is None:
            fn = jax.jit(
                lambda buf, ck, s: jax.lax.dynamic_update_slice(
                    buf, ck, (s, jnp.int32(0))),
                donate_argnums=(0,))
            self._stream_jits[jkey] = fn
        return fn(data0, chunk, jnp.int32(start))

    def _stream_scatter(self, data0, rows, pos):
        """Donated scatter write of packed code rows into subset-local
        positions (GOSS working-set hits and streamed misses)."""
        jkey = ("scatter", int(rows.shape[0]),
                tuple(int(d) for d in data0.shape))
        fn = self._stream_jits.get(jkey)
        if fn is None:
            fn = jax.jit(
                lambda buf, r, p: buf.at[p, :r.shape[1]].set(
                    r, unique_indices=True),
                donate_argnums=(0,))
            self._stream_jits[jkey] = fn
        return fn(data0, rows, pos)

    def _stream_assemble(self, grad, hess, w, key, bag_indices):
        """Build the chunk core's pre-assembled data0 on device.

        stream_mode=chunked (or a GOSS warmup iteration): every wire row
        streams through the double buffer into its own slot — pure data
        movement, so the grown tree is bit-identical to resident
        training for any stream_chunk_rows. stream_mode=goss with a
        sampled bag: the bag compacts to a subset buffer; pinned
        working-set rows are gathered on device (no H2D), the rest
        stream, and the next iteration's top-gradient rows are re-pinned
        from the assembled buffer before it is consumed."""
        shard = self._shard
        n = self.dataset.num_data
        if self.stream_mode == "goss" and bag_indices is not None:
            idx = np.sort(np.asarray(
                jax.device_get(bag_indices)).astype(np.int64))
            jidx = jnp.asarray(idx)
            g = jnp.take(grad, jidx)
            h = jnp.take(hess, jidx)
            wv = jnp.ones(idx.size, jnp.float32)
            # the compacted bag is all-ones by construction; mirror the
            # _grow_fn_kwargs exactness bound so assembly and core agree
            # on the static gh-word layout
            trivial = n < (1 << 24)
        else:
            idx = None
            g, h, wv = grad, hess, w
            trivial = (w is self._ones_w) and n < (1 << 24)
        rows_n = n if idx is None else int(idx.size)
        data0 = self._stream_init_fn(rows_n, trivial)(g, h, wv, key)
        shard.track_buffer("data0", int(data0.nbytes))
        if idx is None:
            for s, cnt, dev in shard.iter_chunks():
                data0 = self._stream_write(data0, dev, s)
        else:
            ws_ids, ws_rows = shard.working_set()
            if ws_ids.size:
                hit = np.isin(idx, ws_ids.astype(np.int64),
                              assume_unique=True)
                hit_pos = np.nonzero(hit)[0].astype(np.int32)
                miss_pos = np.nonzero(~hit)[0].astype(np.int32)
                if hit_pos.size:
                    cache_pos = np.searchsorted(
                        ws_ids, idx[hit_pos]).astype(np.int32)
                    rows = jnp.take(ws_rows, jnp.asarray(cache_pos),
                                    axis=0)
                    data0 = self._stream_scatter(
                        data0, rows, jnp.asarray(hit_pos))
            else:
                miss_pos = np.arange(idx.size, dtype=np.int32)
            if miss_pos.size:
                for s, cnt, dev in shard.iter_chunks(
                        row_ids=idx[miss_pos]):
                    data0 = self._stream_scatter(
                        data0, dev, jnp.asarray(miss_pos[s:s + cnt]))
            self._stream_refresh_ws(data0, idx)
        return {"data0": data0, "idx": idx, "g": g, "h": h, "w": wv,
                "trivial": trivial}

    def _stream_refresh_ws(self, data0, idx) -> None:
        """Re-pin the booster's top-gradient hint as the next working
        set, gathering packed code rows straight out of the assembled
        buffer (zero extra H2D — the rows are already on device)."""
        top = self._stream_top_hint
        self._stream_top_hint = None
        if top is None or not top.size:
            return
        top = np.sort(np.asarray(top).astype(np.int64))
        top = top[np.isin(top, idx, assume_unique=True)]
        if not top.size:
            return
        pos = np.searchsorted(idx, top).astype(np.int32)
        cw = int(self._shard.code_words)
        jkey = ("wsgather", int(pos.size),
                tuple(int(d) for d in data0.shape))
        fn = self._stream_jits.get(jkey)
        if fn is None:
            fn = jax.jit(lambda buf, p: buf[p, :cw])
            self._stream_jits[jkey] = fn
        self._shard.pin_working_set(top.astype(np.int32),
                                    fn(data0, jnp.asarray(pos)))

    def _run_grow_streamed(self, base_mask, key):
        """Grow from the pre-assembled streamed buffer: the chunk core
        runs with data_prebuilt=True (codes_pack arg IS data0, codes_row
        a dummy) and is otherwise the identical program — root histogram
        grouping aside, which the chunk-wise accumulation keeps exact
        for both the int32 and the exact-arithmetic float cases."""
        ctx = self._stream_ctx
        self._stream_ctx = None
        grow, kw = self._grow_fn_kwargs(trivial_weights=ctx["trivial"])
        kw["data_prebuilt"] = True
        dummy_row = jnp.zeros((1, 1), jnp.uint8)
        rec, rec_cat, leaf_id, n_splits, totals = grow(
            ctx["data0"], dummy_row, ctx["g"], ctx["h"], ctx["w"],
            base_mask, self.f_numbins, self.f_missing, self.f_default,
            self.f_monotone, self.f_penalty, self.f_categorical,
            self.f_col, self.f_base, self.f_elide, self.scan_plan, key,
            **kw, **self._statics())
        if ctx["idx"] is not None:
            leaf_id = self._stream_full_leaf_id(
                ctx["idx"], leaf_id, rec, rec_cat, n_splits)
        self._shard.release_buffer("data0")
        return rec, rec_cat, leaf_id, n_splits, totals

    def _stream_full_leaf_id(self, idx, leaf_sub, rec, rec_cat, k):
        """Full-row leaf assignment for the compacted GOSS bag: in-bag
        rows take the core's ids; out-of-bag rows replay the split
        records chunk-by-chunk from the wire store (the streamed
        counterpart of the reference's out-of-bag
        AddPredictionToScore)."""
        n = self.dataset.num_data
        full = jnp.zeros(n, jnp.int32).at[jnp.asarray(idx)].set(
            leaf_sub, unique_indices=True)
        mask = np.ones(n, dtype=bool)
        mask[idx] = False
        oob = np.nonzero(mask)[0]
        if not oob.size:
            return full
        # emit_phase=False: this routing runs inside grow_dispatch and
        # recorder phases must not nest (bytes are still counted)
        for s, cnt, dev in self._shard.iter_chunks(
                row_ids=oob, emit_phase=False):
            lc = self._stream_route(dev, rec, rec_cat, k)
            full = full.at[jnp.asarray(
                oob[s:s + cnt].astype(np.int64))].set(
                    lc, unique_indices=True)
        return full

    def _stream_route(self, rows, rec, rec_cat, k):
        jkey = ("route", int(rows.shape[0]))
        fn = self._stream_jits.get(jkey)
        if fn is None:
            item_bits = self.item_bits
            L = int(self.config.num_leaves)
            f_meta = (self.f_numbins, self.f_missing, self.f_default,
                      self.f_col, self.f_base, self.f_elide)
            f_cat = self.f_categorical if self._has_cat else None

            def route(rows, rec, rec_cat, kk):
                return route_rows_by_rec(
                    rows, rec, kk, *f_meta, item_bits=item_bits,
                    num_leaves=L, rec_cat=rec_cat,
                    f_categorical=f_cat)

            fn = jax.jit(route)
            self._stream_jits[jkey] = fn
        return fn(rows, rec, rec_cat, k)

    def stream_note_top(self, top_ids) -> None:
        """Booster hook (GOSS sampling): the row ids whose |g*h| ranks
        highest this iteration — the working set to pin for the next.
        No-op unless this learner streams."""
        if self._shard is None:
            return
        self._stream_top_hint = np.asarray(
            jax.device_get(top_ids)).astype(np.int64)

    def stream_state(self):
        """Checkpointable streaming state (None when not streaming)."""
        if self._shard is None:
            return None
        return self._shard.stream_state()

    def load_stream_state(self, st) -> None:
        if self._shard is not None and st:
            self._shard.load_stream_state(st)

    def device_data_bytes(self) -> dict:
        """Model-tracked device bytes of the row data this learner holds
        — the streamed-vs-resident A/B quantity. In-program temporaries
        common to both modes (scratch, position arrays, the histogram
        pool) are excluded. Resident counts the live input buffers plus
        the in-program data0 copy that coexists with them during
        growth; streamed reports the shard high-water mark (data0 +
        in-flight chunks + pinned working set)."""
        if self._shard is not None:
            return {"mode": "streamed",
                    "bytes": int(max(self._shard.peak_bytes,
                                     self._shard.live_bytes()))}
        total = 0
        for a in (self.codes_t, self.codes_pack, self.codes_row):
            if a is not None and hasattr(a, "nbytes"):
                total += int(a.nbytes)
        if self.strategy == "chunk" and self.codes_pack is not None:
            total += ((self.dataset.num_data + self.chunk_rows)
                      * self.table_row_bytes)
        return {"mode": "resident", "bytes": int(total)}

    def _table_platform(self) -> str:
        """Platform of the device that holds the packed table: the one the
        tree program is lowered for, which decides whether its partition
        runs the Pallas kernel (`_partition_tile`)."""
        return next(iter(self.codes_pack.devices())).platform

    def _count_partition_rows(self, rec) -> None:
        """Program counters of how often the tiled partition engages:
        `partition_rows`, the parent rows (by the split records' counts)
        over the tree's splits, and `partition_tiled_rows`, the same over
        the splits whose window — the smallest rung of the growth core's
        ladder that holds this device's share of the parent — is more
        than one scatter tile. The chunk core sorts its chunks and the
        masked core moves no rows: neither tiles, and the masked core
        counts nothing. Beside them, for the compact core: what its
        rungs' step loops ran and what the splits needed, in rows
        (`rung_rows_run` and `rung_rows_needed`, `rung_rows`); and
        `partition_kernel_rows`, the parent rows of the splits whose
        tiles the Pallas kernel partitions: `partition_kernel.engaged`
        for the table's platform, the rung's tile and the table's width,
        the function `_partition_tile` calls. It mirrors that selection
        from the host and does not observe the program take it."""
        if self.strategy == "masked":
            return
        parent = rec[:, R_LCNT].astype(np.float64) + rec[:, R_RCNT]
        telemetry.counters.incr("partition_rows", float(parent.sum()))
        if self.strategy != "compact":
            return
        local_n = getattr(self, "local_n", self.dataset.num_data)
        ladder = np.asarray(_size_classes(local_n))
        share = np.ceil(parent * (local_n / self.dataset.num_data))
        rung = ladder[np.minimum(np.searchsorted(ladder, share),
                                 len(ladder) - 1)]
        telemetry.counters.incr(
            "partition_tiled_rows",
            float(parent[rung > SCATTER_TILE_ROWS].sum()))
        platform, words = self._table_platform(), self.table_row_bytes // 4
        taken = np.array([partition_kernel.engaged(
            platform, int(min(r, SCATTER_TILE_ROWS)), words) for r in rung],
            bool)
        telemetry.counters.incr("partition_kernel_rows",
                                float(parent[taken].sum()))
        # exact on one device's full-data trees (the records' counts are
        # the rows); under sharding or bagging an estimate by the share
        from ..ops.histogram import resolve_chunk_size
        run, needed = rung_rows(
            share, np.minimum(np.rint(rec[:, R_LCNT] * (share / parent)),
                              share),
            rec[:, R_LCNT] <= rec[:, R_RCNT], ladder, SCATTER_TILE_ROWS,
            resolve_chunk_size(0, self.c_cols, self.col_device_bins))
        telemetry.counters.incr("rung_rows_run", run)
        telemetry.counters.incr("rung_rows_needed", needed)

    def _count_missing_splits(self, rec) -> None:
        """Program counters of how often the default-direction path
        decides a split: `splits`, and `splits_on_missing_feature`, those
        whose feature has missing type zero or NaN."""
        kinds = self._f_missing_host[rec[:, R_FEAT].astype(np.int64)]
        telemetry.counters.incr("splits", float(len(rec)))
        telemetry.counters.incr("splits_on_missing_feature",
                                float(np.count_nonzero(kinds)))

    def replay_tree(self, rec_h, k: int, rec_cat_h=None) -> Tree:
        """Materialize a host Tree from the fetched (L-1, 13) split-record
        array (the one device->host transfer per tree). rec_cat_h carries
        the categorical winners' (L-1, B) left-bin masks; a split whose
        feature is categorical replays as a bitset node."""
        from .serial_learner import _make_bitset
        ds = self.dataset
        rec_h = np.asarray(rec_h)
        self._count_partition_rows(rec_h[:k])
        self._count_missing_splits(rec_h[:k])
        tree = Tree(self.config.num_leaves)
        for i in range(k):
            r = rec_h[i]
            inner_f = int(r[R_FEAT])
            real_f = ds.inner_to_real(inner_f)
            mapper = ds.bin_mappers[real_f]
            if mapper.bin_type == BIN_CATEGORICAL and rec_cat_h is not None:
                bins = [int(bb) for bb in
                        np.nonzero(np.asarray(rec_cat_h[i]) > 0.5)[0]]
                inner_bits = _make_bitset(bins)
                cats = [mapper.bin_2_categorical[b] for b in bins
                        if b < len(mapper.bin_2_categorical)]
                real_bits = _make_bitset(cats)
                tree.split_categorical(
                    int(r[R_LEAF]), inner_f, real_f,
                    [int(wd) for wd in inner_bits],
                    [int(wd) for wd in real_bits],
                    float(r[R_LOUT]), float(r[R_ROUT]),
                    int(round(float(r[R_LCNT]))),
                    int(round(float(r[R_RCNT]))),
                    float(r[R_LSH]), float(r[R_RSH]),
                    float(r[R_GAIN]), mapper.missing_type)
                continue
            thr_bin = int(r[R_THR])
            tree.split(
                int(r[R_LEAF]), inner_f, real_f, thr_bin,
                ds.real_threshold(inner_f, thr_bin),
                float(r[R_LOUT]), float(r[R_ROUT]),
                int(round(float(r[R_LCNT]))),
                int(round(float(r[R_RCNT]))),
                float(r[R_LSH]), float(r[R_RSH]),
                float(r[R_GAIN]), mapper.missing_type,
                bool(r[R_DLEFT] > 0.5))
        return tree

    # ------------------------------------------------------------------
    def make_fused_step(self, objective, goss=None, bagging=True):
        """One boosting iteration as a single device program: gradients ->
        bag/GOSS sampling -> whole-tree growth -> on-device leaf-value
        replay -> score update. Every extra dispatch is a host round
        trip the device idles through, so the fused step leaves exactly
        one small D2H fetch (the split records) per iteration.

        goss = (top_k, other_k, multiply): gradient-based one-side
        sampling on device (reference src/boosting/goss.hpp) — keep the
        top_k rows by |g*h|, sample other_k of the rest uniformly and
        amplify their gradients by `multiply`; the tree then trains on
        the compacted (top_k + other_k)-row subset.

        Returns step(score_row, base_mask, tree_key, bag_key, shrinkage)
        -> (new_score_row, rec, rec_cat, leaf_id, num_splits, finite) —
        `finite` is the in-program on_nonfinite sentry reduction over the
        updated score row, so guarded runs cost no extra dispatch.
        """
        statics = self._statics()
        n = self.dataset.num_data
        cfg = self.config
        use_compact = self.strategy in ("compact", "chunk")
        meta = (self.f_numbins, self.f_missing, self.f_default,
                self.f_monotone, self.f_penalty, self.f_categorical,
                self.f_col, self.f_base,
                self.f_elide, self.scan_plan)
        if goss is not None:
            top_k, other_k, multiply = goss
            bag_on = True
            bag_k = min(n, top_k + other_k)
        elif not bagging:
            # GOSS warmup: train on ALL rows even if bagging params are
            # set (reference GOSS replaces bagging outright)
            bag_on = False
            bag_k = n
        else:
            bag_on = cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0
            bag_k = max(1, int(n * cfg.bagging_fraction))
        L = statics["num_leaves"]
        # bag compaction (reference subset-copy bagging, gbdt.cpp:727-792):
        # physically gather the bag once per iteration so every per-split
        # window scales with the bag, not N; out-of-bag rows get their
        # leaf from a rec-replay routing pass
        bag_compact = (use_compact and bag_on and bag_k < n
                       and not flag("LGBM_TPU_NO_BAG_COMPACT"))
        if use_compact:
            # bag-compacted and full-data fused paths hand the grower an
            # all-ones weight vector; GOSS/bagging without compaction
            # carries 0/1 weights and keeps the masked fallback
            grow, grow_kw = self._grow_fn_kwargs(
                trivial_weights=bag_compact
                or (goss is None and not bag_on))
        else:
            grow, grow_kw = grow_tree, dict(quant_bits=self.quant_bits,
                                            hist_chunk=self.hist_chunk)

        obj_keys = objective_buffer_names(objective)

        # `step_impl` is a name the benchmark reads: it finds the tree
        # program in a device trace by the module `jit_step_impl`
        # (benchmark/trace_reduce.py MODULE_HINT; tests/test_trace_spans.py
        # pins it)
        @jax.jit
        def step_impl(codes_pack, codes_row, obj_bufs, score_row,
                      base_mask, tree_key, bag_key, shrinkage):
            # the code buffers (and the objective's device buffers) are
            # explicit ARGUMENTS, not closure captures: closed-over
            # device arrays lower as HLO constants, which baked the
            # whole dataset into the program — 120.5 MB of StableHLO at
            # 1M x 28 x 255 (codes ~112 MB + objective vectors ~8 MB)
            # vs 0.24 MB with everything as args — bloating the
            # remote-compile payload and keying the persistent compile
            # cache on the dataset bytes instead of just shapes. Masked
            # strategy passes (codes_t, codes_t).
            # tests/test_program_size.py pins the property.
            with swapped_attrs(objective, obj_keys, obj_bufs), \
                    jax.named_scope("lgbm.gradients"):
                g, h = objective.get_gradients(score_row)
            bag_idx = oob_idx = None
            if goss is not None:
                g, h, w, bag_idx, oob_idx = goss_sample(
                    g, h, bag_key, n, top_k, other_k, multiply)
            elif bag_on:
                w = exact_k_bag_weights(bag_key, n, bag_k)
                inbag = w > 0
            else:
                w = jnp.ones((n,), jnp.float32)
            if bag_compact:
                if bag_idx is None:
                    order = jnp.argsort(
                        jnp.where(inbag, 0, 1).astype(jnp.int8),
                        stable=True)
                    bag_idx, oob_idx = order[:bag_k], order[bag_k:]
                rec, rec_cat, leaf_b, k, _ = grow(
                    jnp.take(codes_pack, bag_idx, axis=0),
                    jnp.take(codes_row, bag_idx, axis=0),
                    jnp.take(g, bag_idx), jnp.take(h, bag_idx),
                    jnp.ones((bag_k,), jnp.float32), base_mask,
                    *meta, tree_key, **grow_kw, **statics)
                # the out-of-bag rows' leaves, and every row's in place
                with jax.named_scope("lgbm.row_leaf"):
                    leaf_o = route_rows_by_rec(
                        jnp.take(codes_pack, oob_idx, axis=0), rec, k,
                        self.f_numbins, self.f_missing, self.f_default,
                        self.f_col, self.f_base, self.f_elide,
                        item_bits=self.item_bits, num_leaves=L,
                        rec_cat=rec_cat, f_categorical=self.f_categorical)
                    leaf_id = jnp.zeros(n, jnp.int32) \
                        .at[bag_idx].set(leaf_b, unique_indices=True) \
                        .at[oob_idx].set(leaf_o, unique_indices=True)
            elif use_compact:
                rec, rec_cat, leaf_id, k, _ = grow(
                    codes_pack, codes_row, g, h, w, base_mask,
                    *meta, tree_key, **grow_kw, **statics)
            else:
                rec, rec_cat, leaf_id, k, _ = grow(
                    codes_pack, g, h, w, base_mask, *meta, tree_key,
                    **grow_kw, **statics)

            # on-device leaf-value replay avoids any H2D of leaf values.
            # The k == 0 gate makes the returned score EXACTLY the input
            # score on a no-split iteration, so the pipelined caller
            # (gbdt._train_one_iter_fused) can commit it before k is
            # fetched and still match the reference's stop semantics.
            with jax.named_scope("lgbm.score_update"):
                lv = leaf_values_from_rec(rec, k, L)
                delta = jnp.take(lv, jnp.clip(leaf_id, 0, L - 1)) \
                    * shrinkage
                delta = jnp.where(k > 0, delta, jnp.zeros_like(delta))
                new_score = score_row + delta
                # in-program non-finite sentry: any NaN/inf gradient or
                # leaf output propagates into the updated score, so one
                # reduction INSIDE the program covers the whole fused
                # iteration and a guarded run adds zero extra dispatches
                finite = jnp.all(jnp.isfinite(new_score))
            return new_score, rec, rec_cat, leaf_id, k, finite

        def make_args(*step_args):
            codes_args = ((self.codes_pack, self.codes_row) if use_compact
                          else (self.codes_t, self.codes_t))
            obj_bufs = tuple(getattr(objective, k) for k in obj_keys)
            return (*codes_args, obj_bufs, *step_args)

        return fused_step_surface(step_impl, make_args, obj_keys)

    # ------------------------------------------------------------------
    def leaf_rows(self, leaf: int) -> np.ndarray:
        """IN-BAG row indices of a leaf after training (leaf renewal path).

        last_leaf_id routes every row (out-of-bag included), but leaf
        renewal must use in-bag rows only, matching the reference's
        RenewTreeOutput over the data partition (serial_tree_learner.cpp:
        855-893) and SerialTreeLearner.leaf_rows."""
        if self._leaf_id_host is None:
            self._leaf_id_host = np.asarray(jax.device_get(self.last_leaf_id))
        in_leaf = self._leaf_id_host == leaf
        if self._bag_mask_host is not None:
            in_leaf = in_leaf & self._bag_mask_host
        return np.nonzero(in_leaf)[0]
