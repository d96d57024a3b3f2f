"""Serial (single-device) leaf-wise tree learner.

Equivalent of the reference SerialTreeLearner (reference:
src/treelearner/serial_tree_learner.cpp:173-893): leaf-wise growth with
histogram subtraction. TPU-native execution model: the tree loop runs on
host (tiny bookkeeping), while each step dispatches three jitted device
programs — partition (stable-sort window), histogram build (MXU one-hot
contraction, smaller child only), and the vectorized split scan. Dynamic
leaf sizes are handled by padding windows to power-of-two buckets so XLA
sees a small, fixed set of shapes.

Histogram-cache choreography (parent moved to larger child, smaller built
fresh, larger = parent - smaller) matches serial_tree_learner.cpp:400-605.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..config import Config
from ..io.binning import BIN_CATEGORICAL
from ..io.dataset import Dataset
from ..ops import fused as fused_ops
from ..ops import histogram as hist_ops
from ..ops import partition as part_ops
from ..ops import split as split_ops
from .. import telemetry
from ..telemetry import recorder as telem
from ..utils import log
from ..utils.envs import use_pallas_env
from .tree import Tree

_MIN_BUCKET = 256


def _bucket(count: int, cap: int) -> int:
    b = _MIN_BUCKET
    while b < count:
        b *= 2
    return min(b, cap)


class _LeafState:
    __slots__ = ("begin", "count", "sum_grad", "sum_hess", "depth",
                 "hist", "split", "min_c", "max_c")

    def __init__(self, begin, count, sum_grad, sum_hess, depth,
                 min_c=-np.inf, max_c=np.inf):
        self.begin = begin
        self.count = count
        self.sum_grad = sum_grad
        self.sum_hess = sum_hess
        self.depth = depth
        self.hist = None         # device (F, B, 3)
        self.split = None        # host dict of the best split, or None
        self.min_c = min_c
        self.max_c = max_c


class SerialTreeLearner:
    def __init__(self, config: Config, dataset: Dataset):
        self.config = config
        self.dataset = dataset
        self.binned = dataset.device_binned()
        (self.f_numbins, self.f_missing, self.f_default,
         self.f_categorical, self.f_monotone) = dataset.feature_meta_arrays()
        self.num_features = dataset.num_features
        self.num_bins = int(dataset.max_num_bins)
        # pad bin axis to a lane-friendly size
        b = 1 << max(4, (self.num_bins - 1).bit_length())
        self.device_bins = min(b, 256) if self.num_bins <= 256 else b
        n = dataset.num_data
        self.max_bucket = _bucket(n, 1 << 30)
        self._has_categorical = any(
            dataset.bin_mappers[f].bin_type == BIN_CATEGORICAL
            for f in dataset.used_features)
        # XLA's fused one-hot contraction measured faster than the Pallas
        # kernel on v5e (a dated reading, 2026-08-01); opt-in only.
        self._use_pallas = use_pallas_env()
        # quantized-gradient training (ops/quantize.py): per-iteration
        # int discretization, exact integer histograms, bit-exact sibling
        # subtraction; 0 = float path (default, unchanged)
        self._quant_bits = config.quant_bits
        self._hist_chunk = int(config.hist_chunk_size or 0)
        self._gh_packed = None
        self._gh_scales = None
        # per-tree hoisted device masks (reset at every train() entry)
        self._meta_cache = None
        self._cat_mask_cache = None
        self._mono_enabled = bool(np.any(np.asarray(self.f_monotone) != 0))
        # feature_contri gain multipliers (reference FeatureMetainfo penalty)
        contri = config.feature_contri or []
        if contri:
            pen = np.array(
                [contri[f] if f < len(contri) else 1.0
                 for f in dataset.used_features], dtype=np.float32)
            self._feature_penalty = jnp.asarray(pen)
        else:
            self._feature_penalty = None
        # CEGB (reference cost_effective_gradient_boosting.hpp): coupled
        # penalties are charged once per feature across the whole model;
        # lazy per-row costs are approximated per-leaf by count.
        self._cegb_enabled = (config.cegb_tradeoff > 0 and (
            config.cegb_penalty_split > 0
            or bool(config.cegb_penalty_feature_coupled)
            or bool(config.cegb_penalty_feature_lazy)))
        if self._cegb_enabled:
            nf = self.num_features
            coupled = config.cegb_penalty_feature_coupled or []
            lazy = config.cegb_penalty_feature_lazy or []
            self._cegb_coupled = np.array(
                [coupled[f] if f < len(coupled) else 0.0
                 for f in dataset.used_features])
            self._cegb_lazy = np.array(
                [lazy[f] if f < len(lazy) else 0.0
                 for f in dataset.used_features])
            self._cegb_feature_used = np.zeros(nf, dtype=bool)
        # forced splits: BFS JSON replayed at the top of every tree
        # (reference: serial_tree_learner.cpp:607-769 ForceSplits)
        self._forced_splits = None
        if config.forcedsplits_filename:
            import json
            with open(config.forcedsplits_filename) as fh:
                self._forced_splits = json.load(fh)

    # ------------------------------------------------------------------
    def _scan_args(self):
        cfg = self.config
        return dict(
            num_bins=self.device_bins,
            l1=float(cfg.lambda_l1), l2=float(cfg.lambda_l2),
            max_delta_step=float(cfg.max_delta_step),
            min_data_in_leaf=int(cfg.min_data_in_leaf),
            min_sum_hessian=float(cfg.min_sum_hessian_in_leaf),
            min_gain_to_split=float(cfg.min_gain_to_split),
        )

    def _feature_mask(self, rng: np.random.RandomState) -> np.ndarray:
        frac = self.config.feature_fraction
        mask = np.ones(self.num_features, dtype=bool)
        if 0.0 < frac < 1.0:
            k = max(1, int(self.num_features * frac))
            chosen = rng.choice(self.num_features, k, replace=False)
            mask[:] = False
            mask[chosen] = True
        return mask

    def _node_feature_mask(self, base_mask: np.ndarray,
                           rng: np.random.RandomState) -> jax.Array:
        frac = self.config.feature_fraction_bynode
        if 0.0 < frac < 1.0:
            k = max(1, int(self.num_features * frac))
            chosen = rng.choice(self.num_features, k, replace=False)
            node_mask = np.zeros(self.num_features, dtype=bool)
            node_mask[chosen] = True
            return jnp.asarray(base_mask & node_mask)
        return jnp.asarray(base_mask)

    # ------------------------------------------------------------------
    def _build_hist(self, indices_buf, grad, hess, begin: int, count: int):
        return hist_ops.gather_and_build(
            self.binned, indices_buf, grad, hess,
            jnp.int32(begin), jnp.int32(count),
            num_bins=self.device_bins, bucket=_bucket(count, self.max_bucket),
            chunk_size=self._hist_chunk)

    def _hist_f32(self, hist):
        """Leaf histogram as f32 for scan consumers: identity on the
        float path, scale-rescaled dequantization on the quantized path
        (the pool itself stays exact int32)."""
        if self._quant_bits and hist is not None:
            from ..ops.quantize import dequantize_histogram
            return dequantize_histogram(hist, *self._gh_scales)
        return hist

    def _scan_leaf(self, leaf: _LeafState, feature_mask) -> dict:
        """Run the split scan for a leaf; returns a host-side split record."""
        res = split_ops.find_best_split(
            self._hist_f32(leaf.hist), jnp.float32(leaf.sum_grad),
            jnp.float32(leaf.sum_hess),
            jnp.float32(leaf.count), self.f_numbins, self.f_missing,
            self.f_default, feature_mask & (self.f_categorical == 0),
            self.f_monotone, jnp.float32(leaf.min_c), jnp.float32(leaf.max_c),
            **self._scan_args())
        rec = self._fetch_split(res)
        if self._has_categorical:
            cres = split_ops.find_best_split_categorical(
                self._hist_f32(leaf.hist), jnp.float32(leaf.sum_grad),
                jnp.float32(leaf.sum_hess), jnp.float32(leaf.count),
                self.f_numbins, self.f_missing,
                feature_mask & (self.f_categorical == 1),
                jnp.float32(leaf.min_c), jnp.float32(leaf.max_c),
                **self._cat_scan_args())
            crec = self._fetch_split(cres, categorical=True)
            if crec["gain"] > rec["gain"]:
                rec = crec
        return rec

    def _cat_scan_args(self):
        cfg = self.config
        return dict(
            num_bins=self.device_bins,
            l1=float(cfg.lambda_l1), l2=float(cfg.lambda_l2),
            cat_l2=float(cfg.cat_l2), cat_smooth=float(cfg.cat_smooth),
            max_delta_step=float(cfg.max_delta_step),
            min_data_in_leaf=int(cfg.min_data_in_leaf),
            min_sum_hessian=float(cfg.min_sum_hessian_in_leaf),
            min_gain_to_split=float(cfg.min_gain_to_split),
            max_cat_threshold=int(cfg.max_cat_threshold),
            max_cat_to_onehot=int(cfg.max_cat_to_onehot),
            min_data_per_group=int(cfg.min_data_per_group),
        )

    @staticmethod
    def _fetch_split(res, categorical: bool = False) -> dict:
        with telem.phase("host_sync"):
            vals = jax.device_get(res)
        rec = {
            "gain": float(vals.gain),
            "feature": int(vals.feature),
            "threshold": 0 if categorical else int(vals.threshold),
            "default_left": False if categorical else bool(vals.default_left),
            "left_sum_grad": float(vals.left_sum_grad),
            "left_sum_hess": float(vals.left_sum_hess),
            "left_count": int(round(float(vals.left_count))),
            "right_sum_grad": float(vals.right_sum_grad),
            "right_sum_hess": float(vals.right_sum_hess),
            "right_count": int(round(float(vals.right_count))),
            "left_output": float(vals.left_output),
            "right_output": float(vals.right_output),
            "categorical": categorical,
        }
        if categorical:
            mask = np.asarray(vals.left_mask)
            rec["cat_bitset_inner"] = _make_bitset(
                [int(i) for i in np.nonzero(mask)[0]])
        return rec

    # ------------------------------------------------------------------
    def train(self, grad: jax.Array, hess: jax.Array,
              bag_indices: Optional[np.ndarray] = None,
              iter_seed: int = 0) -> Tree:
        """Grow one tree. Per split: ONE fused device program (partition +
        left-child histogram + sibling subtraction + both child scans) and
        ONE small host fetch — see ops/fused.py."""
        cfg = self.config
        ds = self.dataset
        n = ds.num_data
        bag_cnt = n if bag_indices is None else len(bag_indices)
        indices_buf = part_ops.make_indices_buffer(n, self.max_bucket, bag_indices)
        rng = np.random.RandomState(
            (cfg.feature_fraction_seed + iter_seed) % (2**31 - 1))
        base_mask = self._feature_mask(rng)
        self._numerical_mask_np = base_mask  # node-level resample below

        tree = Tree(cfg.num_leaves)
        # per-tree hoisted caches (base_mask changes per tree)
        self._meta_cache = None
        self._cat_mask_cache = None
        root_cost = self._cegb_cost(bag_cnt)
        if self._quant_bits:
            # per-iteration (per-class: each class's tree quantizes its
            # own gradient vector) discretization with stochastic
            # rounding; one packed int32 lane per row rides the whole
            # tree, histograms are exact int32
            from ..ops import quantize as quant_ops
            qkey = jax.random.PRNGKey(
                (cfg.feature_fraction_seed * 9973 + 2 * iter_seed + 1)
                % (2**31 - 1))
            with telem.phase("quantize"):
                self._gh_packed, s_g, s_h = quant_ops.quantize_gh(
                    grad, hess, qkey, grad_bits=self._quant_bits)
            self._gh_scales = (s_g, s_h)
            self._scales_vec = jnp.stack([s_g, s_h])
            with telem.phase("hist"):
                root_hist, totals_dev, root_res = \
                    fused_ops.fused_root_step_q(
                        indices_buf, self.binned, self._gh_packed,
                        self._scales_vec, jnp.int32(bag_cnt),
                        self._fused_meta(base_mask, rng),
                        None if root_cost is None
                        else jnp.asarray(root_cost),
                        bucket=_bucket(bag_cnt, self.max_bucket),
                        grad_bits=self._quant_bits,
                        hist_chunk=self._hist_chunk,
                        use_pallas=self._use_pallas, **self._scan_args())
        else:
            with telem.phase("hist"):
                root_hist, totals_dev, root_res = fused_ops.fused_root_step(
                    indices_buf, self.binned, grad, hess,
                    jnp.int32(bag_cnt), self._fused_meta(base_mask, rng),
                    None if root_cost is None else jnp.asarray(root_cost),
                    bucket=_bucket(bag_cnt, self.max_bucket),
                    hist_chunk=self._hist_chunk,
                    use_pallas=self._use_pallas, **self._scan_args())
        telemetry.note_grow_dispatches(1.0)
        with telem.phase("host_sync"):
            totals = jax.device_get(totals_dev)
        root = _LeafState(0, bag_cnt, float(totals[0]), float(totals[1]), 0)
        root.hist = root_hist
        root.split = self._fetch_split(jax.device_get(root_res))
        if self._has_categorical:
            self._merge_categorical(root, base_mask, rng)
        leaves: Dict[int, _LeafState] = {0: root}

        if self._forced_splits is not None:
            indices_buf = self._replay_forced_splits(
                tree, leaves, indices_buf, grad, hess, base_mask, rng)

        for _split_idx in range(cfg.num_leaves - 1):
            # pick the splittable leaf with max gain (leaf-wise growth)
            best_leaf, best_gain = -1, 1e-10
            for li, st in leaves.items():
                if st.split is not None and st.split["gain"] > best_gain:
                    best_leaf, best_gain = li, st.split["gain"]
            if best_leaf < 0:
                if _split_idx == 0:
                    log.warning(
                        "No further splits with positive gain, best gain: %f",
                        best_gain)
                break
            indices_buf = self._apply_split(
                tree, leaves, best_leaf, indices_buf, grad, hess,
                base_mask, rng)

        self.indices_buf = indices_buf
        self.leaves = leaves
        # the host loop pays ~num_leaves growth-program dispatches per
        # tree — the O(leaves) baseline the fused device program beats
        telemetry.note_grow_dispatches(0.0, trees=1.0)
        return tree

    def _fused_meta(self, base_mask, rng):
        # per-tree constant unless per-node feature resampling is on:
        # rebuilding it per split paid a fresh base-mask H2D plus two
        # device mask ops for every split in the tree. Caching is
        # rng-neutral — _node_feature_mask only draws from rng when
        # feature_fraction_bynode is active, exactly when we skip the
        # cache. train() clears the cache at tree start.
        if self._meta_cache is not None:
            return self._meta_cache
        mask = self._node_feature_mask(base_mask, rng) & (self.f_categorical == 0)
        meta = (self.f_numbins, self.f_missing, self.f_default, mask,
                self.f_monotone, self._feature_penalty)
        if not (0.0 < self.config.feature_fraction_bynode < 1.0):
            self._meta_cache = meta
        return meta

    def _cegb_cost(self, count: int) -> Optional[np.ndarray]:
        if not self._cegb_enabled:
            return None
        cfg = self.config
        cost = np.full(self.num_features,
                       cfg.cegb_tradeoff * cfg.cegb_penalty_split * count)
        cost += np.where(self._cegb_feature_used, 0.0,
                         cfg.cegb_tradeoff * self._cegb_coupled)
        cost += cfg.cegb_tradeoff * self._cegb_lazy * count
        return cost.astype(np.float32)

    def _merge_categorical(self, st: "_LeafState", base_mask, rng) -> None:
        """Categorical split search runs as a separate (rarer) program and
        merges with the numerical winner on host."""
        # base_mask is fixed for the whole tree, so the categorical
        # device mask is too (hoisted out of the split loop; train()
        # clears the cache at tree start)
        if self._cat_mask_cache is None:
            self._cat_mask_cache = (jnp.asarray(base_mask)
                                    & (self.f_categorical == 1))
        feature_mask = self._cat_mask_cache
        telemetry.note_grow_dispatches(1.0)
        cres = split_ops.find_best_split_categorical(
            self._hist_f32(st.hist), jnp.float32(st.sum_grad),
            jnp.float32(st.sum_hess),
            jnp.float32(st.count), self.f_numbins, self.f_missing,
            feature_mask, jnp.float32(st.min_c), jnp.float32(st.max_c),
            **self._cat_scan_args())
        crec = self._fetch_split(jax.device_get(cres), categorical=True)
        if st.split is None or crec["gain"] > st.split["gain"]:
            st.split = crec

    def _apply_split(self, tree: Tree, leaves: Dict[int, _LeafState],
                     leaf_id: int, indices_buf, grad, hess,
                     base_mask, rng):
        ds = self.dataset
        st = leaves[leaf_id]
        sp = st.split
        inner_f = sp["feature"]
        real_f = ds.inner_to_real(inner_f)
        mapper = ds.bin_mappers[real_f]
        bucket = _bucket(st.count, self.max_bucket)

        # children constraints; monotone propagation (basic mode,
        # reference serial_tree_learner.cpp:771-852)
        lmin, lmax, rmin, rmax = st.min_c, st.max_c, st.min_c, st.max_c
        mono = int(np.asarray(self.f_monotone)[inner_f]) if self._mono_enabled else 0
        if mono != 0:
            mid = (sp["left_output"] + sp["right_output"]) / 2.0
            if mono > 0:
                lmax, rmin = min(lmax, mid), max(rmin, mid)
            else:
                lmin, rmax = max(lmin, mid), min(rmax, mid)

        bits = np.zeros(8, dtype=np.uint32)
        if sp["categorical"]:
            src = sp["cat_bitset_inner"][:8]
            bits[: len(src)] = src
        iparams = np.zeros(15, dtype=np.int32)
        iparams[:9] = [st.begin, st.count, inner_f, sp["threshold"],
                       int(sp["default_left"]), mapper.missing_type,
                       mapper.default_bin, mapper.num_bin,
                       int(sp["categorical"])]
        fparams = np.asarray(
            [sp["left_sum_grad"], sp["left_sum_hess"], sp["left_count"],
             sp["right_sum_grad"], sp["right_sum_hess"], sp["right_count"],
             lmin, lmax, rmin, rmax], dtype=np.float32)
        if self._cegb_enabled:
            child_costs = jnp.asarray(np.stack([
                self._cegb_cost(sp["left_count"]),
                self._cegb_cost(sp["right_count"])]))
            self._cegb_feature_used[inner_f] = True
        else:
            child_costs = None
        telemetry.note_grow_dispatches(1.0)
        with telem.phase("partition"):
            if self._quant_bits:
                out = fused_ops.fused_split_step_q(
                    indices_buf, self.binned, self._gh_packed,
                    jnp.asarray(iparams), jnp.asarray(bits.view(np.int32)),
                    jnp.asarray(fparams), st.hist, self._scales_vec,
                    self._fused_meta(base_mask, rng), child_costs,
                    bucket=bucket, grad_bits=self._quant_bits,
                    hist_chunk=self._hist_chunk,
                    use_pallas=self._use_pallas, **self._scan_args())
            else:
                out = fused_ops.fused_split_step(
                    indices_buf, self.binned, grad, hess,
                    jnp.asarray(iparams), jnp.asarray(bits.view(np.int32)),
                    jnp.asarray(fparams), st.hist,
                    self._fused_meta(base_mask, rng), child_costs,
                    bucket=bucket, hist_chunk=self._hist_chunk,
                    use_pallas=self._use_pallas, **self._scan_args())

        # ONE host fetch per split: left_count + the two winner tuples
        with telem.phase("host_sync"):
            left_cnt, left_rec_raw, right_rec_raw = jax.device_get(
                (out.left_count, out.left_res, out.right_res))
        left_cnt = int(left_cnt)
        if left_cnt != sp["left_count"]:
            log.debug("partition/scan count mismatch: %d vs %d",
                      left_cnt, sp["left_count"])

        # tree bookkeeping (leaf_id keeps left, new leaf is right)
        if not sp["categorical"]:
            thr_real = ds.real_threshold(inner_f, sp["threshold"])
            new_leaf = tree.split(
                leaf_id, inner_f, real_f, sp["threshold"], thr_real,
                sp["left_output"], sp["right_output"], sp["left_count"],
                sp["right_count"], sp["left_sum_hess"], sp["right_sum_hess"],
                sp["gain"], mapper.missing_type, sp["default_left"])
        else:
            inner_bits = sp["cat_bitset_inner"]
            cats = [mapper.bin_2_categorical[b]
                    for b in _bits_set(inner_bits)
                    if b < len(mapper.bin_2_categorical)]
            real_bits = _make_bitset(cats)
            new_leaf = tree.split_categorical(
                leaf_id, inner_f, real_f,
                [int(w) for w in inner_bits], [int(w) for w in real_bits],
                sp["left_output"], sp["right_output"], sp["left_count"],
                sp["right_count"], sp["left_sum_hess"], sp["right_sum_hess"],
                sp["gain"], mapper.missing_type)

        left = _LeafState(st.begin, sp["left_count"], sp["left_sum_grad"],
                          sp["left_sum_hess"], st.depth + 1, lmin, lmax)
        right = _LeafState(st.begin + sp["left_count"], sp["right_count"],
                           sp["right_sum_grad"], sp["right_sum_hess"],
                           st.depth + 1, rmin, rmax)
        left.hist = out.left_hist
        right.hist = out.right_hist
        left.split = (self._fetch_split(left_rec_raw)
                      if self._splittable(left, tree) else None)
        right.split = (self._fetch_split(right_rec_raw)
                       if self._splittable(right, tree) else None)
        if self._has_categorical:
            if left.split is not None:
                self._merge_categorical(left, base_mask, rng)
            if right.split is not None:
                self._merge_categorical(right, base_mask, rng)
        st.hist = None  # release parent histogram
        if left.split is None:
            left.hist = None
        if right.split is None:
            right.hist = None

        leaves[leaf_id] = left
        leaves[tree.num_leaves - 1] = right
        assert tree.num_leaves - 1 == new_leaf
        return out.indices_buf

    def _replay_forced_splits(self, tree, leaves, indices_buf, grad, hess,
                              base_mask, rng):
        """Apply the forced-split JSON breadth-first before normal growth."""
        cfg = self.config
        ds = self.dataset
        queue = [(0, self._forced_splits)]
        while queue and tree.num_leaves < cfg.num_leaves:
            leaf_id, node = queue.pop(0)
            if node is None or "feature" not in node:
                continue
            real_f = int(node["feature"])
            if real_f not in ds.used_features:
                log.warning("Forced split feature %d unavailable; skipping",
                            real_f)
                continue
            inner_f = ds.used_features.index(real_f)
            mapper = ds.bin_mappers[real_f]
            bin_thr = mapper.value_to_bin(float(node["threshold"]))
            bin_thr = min(bin_thr, mapper.num_bin - 2)
            st = leaves[leaf_id]
            sp = self._gather_split_at(st, inner_f, bin_thr)
            if sp is None:
                continue
            st.split = sp
            indices_buf = self._apply_split(
                tree, leaves, leaf_id, indices_buf, grad, hess,
                base_mask, rng)
            right_leaf = tree.num_leaves - 1
            if "left" in node:
                queue.append((leaf_id, node["left"]))
            if "right" in node:
                queue.append((right_leaf, node["right"]))
        return indices_buf

    def _gather_split_at(self, st: _LeafState, inner_f: int,
                         bin_thr: int) -> Optional[dict]:
        """Split record for a FIXED (feature, bin) from the leaf histogram
        (reference: feature_histogram.hpp:281-419 GatherInfoForThreshold)."""
        cfg = self.config
        hrow = np.asarray(
            jax.device_get(self._hist_f32(st.hist)[inner_f]),
            dtype=np.float64)
        nb = int(np.asarray(self.f_numbins)[inner_f])
        lg, lh, lc = hrow[: bin_thr + 1].sum(axis=0)
        rg, rh, rc = st.sum_grad - lg, st.sum_hess - lh, st.count - lc
        if lc < 1 or rc < 1:
            return None

        def tl1(s):
            return np.sign(s) * max(0.0, abs(s) - cfg.lambda_l1)

        def output(g, h):
            o = -tl1(g) / (h + cfg.lambda_l2)
            if cfg.max_delta_step > 0:
                o = float(np.clip(o, -cfg.max_delta_step, cfg.max_delta_step))
            return float(np.clip(o, st.min_c, st.max_c))

        def gain_part(g, h, o):
            return -(2.0 * tl1(g) * o + (h + cfg.lambda_l2) * o * o)

        lo, ro = output(lg, lh), output(rg, rh)
        gain_shift = gain_part(
            st.sum_grad, st.sum_hess,
            output(st.sum_grad, st.sum_hess))
        gain = gain_part(lg, lh, lo) + gain_part(rg, rh, ro) - gain_shift
        return {
            "gain": float(gain), "feature": inner_f, "threshold": int(bin_thr),
            "default_left": False,
            "left_sum_grad": float(lg), "left_sum_hess": float(lh),
            "left_count": int(round(lc)),
            "right_sum_grad": float(rg), "right_sum_hess": float(rh),
            "right_count": int(round(rc)),
            "left_output": lo, "right_output": ro, "categorical": False,
        }

    def _splittable(self, leaf: _LeafState, tree: Tree) -> bool:
        cfg = self.config
        if leaf.count < 2 * cfg.min_data_in_leaf:
            return False
        if leaf.sum_hess < 2 * cfg.min_sum_hessian_in_leaf:
            return False
        if cfg.max_depth > 0 and leaf.depth >= cfg.max_depth:
            return False
        return True

    # ------------------------------------------------------------------
    def leaf_rows(self, leaf_id: int) -> np.ndarray:
        """Row indices of a leaf after training (for leaf renewal)."""
        st = self.leaves[leaf_id]
        window = jax.device_get(
            jax.lax.dynamic_slice(self.indices_buf, (st.begin,),
                                  (max(st.count, 1),)))
        return window[: st.count]


def _env(name, default):
    import os
    return os.environ.get(name, default)


def _bits_set(words: np.ndarray):
    out = []
    for wi, w in enumerate(np.asarray(words, dtype=np.uint32)):
        w = int(w)
        for b in range(32):
            if (w >> b) & 1:
                out.append(wi * 32 + b)
    return out


def _make_bitset(values) -> np.ndarray:
    if not values:
        return np.zeros(1, dtype=np.uint32)
    n_words = max(values) // 32 + 1
    out = np.zeros(n_words, dtype=np.uint32)
    for v in values:
        out[v // 32] |= np.uint32(1 << (v % 32))
    return out
