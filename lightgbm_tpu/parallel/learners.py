"""Distributed tree learners: data-parallel, feature-parallel, voting-parallel.

The reference's three parallel modes (reference: src/treelearner/
{data,feature,voting}_parallel_tree_learner.cpp) re-expressed on a TPU mesh:

* **FeatureParallelTreeLearner** — all rows on every device, features
  sharded. The reference partitions features per machine, finds local bests
  and allreduces the winner (feature_parallel_tree_learner.cpp:33-76,
  SyncUpGlobalBestSplit). Here the binned matrix and histograms carry a
  `P(None, 'feature')` sharding and the UNCHANGED serial compute runs under
  jit — GSPMD partitions the one-hot contraction and bin scans by feature
  and inserts the argmax-allreduce automatically. The transport layer of the
  reference (network.cpp) has no equivalent code: it is the XLA compiler.

* **DataParallelTreeLearner** — rows sharded, every split does a
  cross-device histogram reduction (reference:
  data_parallel_tree_learner.cpp:149-164 ReduceScatter of all histograms).
  Implemented as explicit shard_map programs: each shard keeps a *local*
  partition-index buffer over its own rows, builds a local histogram on the
  MXU, and a `psum` over the 'data' axis yields the global histogram
  (rides ICI; psum_scatter variant for the sharded-scan path).

* **VotingParallelTreeLearner** — data-parallel with 2-stage voting
  (reference: voting_parallel_tree_learner.cpp:170-260 PV-Tree): each shard
  elects its local top-k features by gain, votes are summed with a psum,
  and only the globally-elected 2k features' histograms are reduced,
  making communication O(k·B) instead of O(F·B).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..io.dataset import Dataset
from ..models.device_learner import (DeviceTreeLearner,
                                     fused_step_surface,
                                     objective_buffer_names,
                                     padded_shard_cols, swapped_attrs)
from ..models.serial_learner import SerialTreeLearner, _bucket, _MIN_BUCKET
from ..models.tree import Tree
from ..ops import histogram as hist_ops
from ..ops import split as split_ops
from ..resilience import faults
from ..telemetry import counters as telem_counters
from ..telemetry import recorder as telem
from ..telemetry import spans as telem_spans
from ..utils import log
from ..utils.envs import dp_reduce_mode_env
from .mesh import make_mesh


class FeatureParallelTreeLearner(SerialTreeLearner):
    """Feature-sharded learner: serial algorithm + GSPMD shardings."""

    def __init__(self, config: Config, dataset: Dataset,
                 mesh: Optional[Mesh] = None):
        super().__init__(config, dataset)
        self.mesh = mesh or make_mesh(axis_name="feature")
        s = self.mesh.devices.size
        f = int(self.binned.shape[1])
        pad_f = (-f) % s
        if pad_f:
            # pad features so the shard axis divides them; padded features
            # are trivial (1 bin) and masked out of every scan
            self.binned = jnp.pad(self.binned, ((0, 0), (0, pad_f)))
            self.f_numbins = jnp.pad(self.f_numbins, (0, pad_f),
                                     constant_values=1)
            self.f_missing = jnp.pad(self.f_missing, (0, pad_f))
            self.f_default = jnp.pad(self.f_default, (0, pad_f))
            self.f_categorical = jnp.pad(self.f_categorical, (0, pad_f))
            self.f_monotone = jnp.pad(self.f_monotone, (0, pad_f))
        self.num_features = f + pad_f
        fsh = NamedSharding(self.mesh, P(None, "feature"))
        vsh = NamedSharding(self.mesh, P("feature"))
        self.binned = jax.device_put(self.binned, fsh)
        self.f_numbins = jax.device_put(self.f_numbins, vsh)
        self.f_missing = jax.device_put(self.f_missing, vsh)
        self.f_default = jax.device_put(self.f_default, vsh)
        self.f_categorical = jax.device_put(self.f_categorical, vsh)
        self.f_monotone = jax.device_put(self.f_monotone, vsh)

    def _feature_mask(self, rng) -> np.ndarray:
        mask = super()._feature_mask(rng)
        if len(mask) < self.num_features:  # padded features never sampled
            mask = np.concatenate(
                [mask, np.zeros(self.num_features - len(mask), dtype=bool)])
        return mask


def _sharded_chunk_opt_in(learner) -> str:
    """The ONE copy of the sharded learners' chunk opt-in: honor
    LGBM_TPU_STRATEGY=chunk when the learner class supports the chunk
    core (all four reductions since round 4: DP psum, DP scatter,
    voting, FP sliced), warn when it cannot."""
    from ..utils.envs import strategy_env
    want = strategy_env()
    capable = getattr(learner, "_chunk_capable", True)
    if want == "chunk" and not capable:
        log.warning("%s does not support the chunk strategy; "
                    "using compact", type(learner).__name__)
    return "chunk" if (want == "chunk" and capable) else "compact"


def _dp_pspec(mesh):
    return NamedSharding(mesh, P("data"))


class DataParallelTreeLearner(SerialTreeLearner):
    """Row-sharded learner with explicit local partitions + psum histograms."""

    def __init__(self, config: Config, dataset: Dataset,
                 mesh: Optional[Mesh] = None):
        super().__init__(config, dataset)
        self.mesh = mesh or make_mesh(axis_name="data")
        self.shards = int(self.mesh.devices.size)
        n = dataset.num_data
        if getattr(dataset, "row_shard", None) is not None:
            log.fatal(
                "the host-loop data-parallel learner needs the full "
                "binned matrix on every rank, but this dataset is row-"
                "sharded (dist_shard_mode=rows, rows %d:%d of %d). Only "
                "the device data-parallel learner trains on row-sharded "
                "ingest; fix the config it fell back for, or use "
                "dist_shard_mode=replicated",
                dataset.row_shard[0], dataset.row_shard[1], n)
        self.local_n = -(-n // self.shards)
        pad = self.local_n * self.shards - n
        binned_np = dataset.binned
        if pad:
            binned_np = np.pad(binned_np, ((0, pad), (0, 0)))
        self.n_pad = n + pad
        self.max_local_bucket = _bucket(self.local_n, 1 << 30)
        rsh = NamedSharding(self.mesh, P("data", None))
        # host -> shards directly: a jnp.asarray first would commit the
        # whole matrix to device 0 before resharding
        self.binned = jax.device_put(
            np.asarray(binned_np).reshape(self.shards, self.local_n, -1), rsh)
        self._build_sharded_fns()

    # -- shard_map programs --------------------------------------------
    def _build_sharded_fns(self):
        mesh = self.mesh
        num_bins = self.device_bins

        def hist_fn(binned_l, idx_l, grad_l, hess_l, begin_l, count_l, *, bucket):
            binned_l = binned_l[0]
            idx_l = idx_l[0]
            grad_l = grad_l[0]
            hess_l = hess_l[0]
            window = jax.lax.dynamic_slice(idx_l, (begin_l[0],), (bucket,))
            valid = jnp.arange(bucket, dtype=jnp.int32) < count_l[0]
            rows = jnp.take(binned_l, window, axis=0)
            g = jnp.take(grad_l, window) * valid
            h = jnp.take(hess_l, window) * valid
            gh = jnp.stack([g, h, valid.astype(jnp.float32)], axis=1)
            local = hist_ops.build_histogram(rows, gh, num_bins)
            # the reference reduce-scatters histograms across machines
            # (data_parallel_tree_learner.cpp:149-164); psum is the dense
            # equivalent over ICI and leaves the result replicated for the
            # scan that follows
            return jax.lax.psum(local, "data")

        def part_fn(idx_buf, binned_l, begin_l, count_l, feat, thr, dleft,
                    mtype, dbin, nbins, *, bucket):
            from ..ops.partition import decide_left
            idx_l = idx_buf[0]
            binned_l = binned_l[0]
            window = jax.lax.dynamic_slice(idx_l, (begin_l[0],), (bucket,))
            valid = jnp.arange(bucket, dtype=jnp.int32) < count_l[0]
            fbins = binned_l[window, feat].astype(jnp.int32)
            go_left = decide_left(fbins, thr, dleft, mtype, dbin, nbins)
            key = jnp.where(valid, jnp.where(go_left, 0, 1), 2).astype(jnp.int32)
            order = jnp.argsort(key, stable=True)
            new_window = window[order]
            left_cnt = jnp.sum((key == 0).astype(jnp.int32))
            new_idx = jax.lax.dynamic_update_slice(idx_l, new_window,
                                                   (begin_l[0],))
            return new_idx[None], left_cnt[None]

        def hist_fn_q(binned_l, idx_l, packed_l, begin_l, count_l, leaf_n,
                      *, bucket):
            """Quantized-gradient local histogram + COMPACT int32
            allreduce (reference ReduceScatter role, quantized rendering):
            each shard builds its exact int32 (F, B, 3) histogram from
            the packed (qg|qh) rows, but the collective moves only TWO
            int32 lanes [sum_qg, sum_qh] — the count lane is dropped from
            the wire (2/3 the bytes of the float path's f32 triple, with
            exact integer summation instead of f32 rounding) and
            reconstructed from the hessian lane via the leaf's exact
            global count: cnt_bin = round(qh_bin * leaf_n / qh_total).
            Exact for constant-hessian objectives (every row quantizes to
            the same qh); for varying hessians the min_data gate becomes
            approximate, the same class of deviation as the reference's
            hessian-derived counts."""
            from ..ops import quantize as quant_ops
            binned_l = binned_l[0]
            idx_l = idx_l[0]
            packed_row = packed_l[0]
            window = jax.lax.dynamic_slice(idx_l, (begin_l[0],), (bucket,))
            valid = jnp.arange(bucket, dtype=jnp.int32) < count_l[0]
            rows = jnp.take(binned_l, window, axis=0)
            ghq = quant_ops.gh_operand(jnp.take(packed_row, window), valid,
                                       self._quant_bits)
            local = hist_ops.build_histogram_quantized(rows, ghq, num_bins)
            payload = local[:, :, :2]                 # (F, B, 2) int32
            glob = jax.lax.psum(payload, "data")
            qh_tot = glob[0, :, 1].sum().astype(jnp.float32)
            cnt = jnp.round(
                glob[:, :, 1].astype(jnp.float32)
                * (leaf_n / jnp.maximum(qh_tot, 1.0))).astype(jnp.int32)
            return jnp.concatenate([glob, cnt[:, :, None]], axis=2)

        self._hist_fns: Dict[int, object] = {}
        self._hist_fns_q: Dict[int, object] = {}
        self._part_fns: Dict[int, object] = {}

        def get_hist_fn_q(bucket):
            if bucket not in self._hist_fns_q:
                f = shard_map(
                    functools.partial(hist_fn_q, bucket=bucket), mesh=mesh,
                    in_specs=(P("data", None, None), P("data", None),
                              P("data", None), P("data"), P("data"), P()),
                    out_specs=P())
                self._hist_fns_q[bucket] = jax.jit(f)
            return self._hist_fns_q[bucket]

        self._get_hist_fn_q = get_hist_fn_q

        def get_hist_fn(bucket):
            if bucket not in self._hist_fns:
                f = shard_map(
                    functools.partial(hist_fn, bucket=bucket), mesh=mesh,
                    in_specs=(P("data", None, None), P("data", None),
                              P("data", None), P("data", None),
                              P("data"), P("data")),
                    out_specs=P())
                self._hist_fns[bucket] = jax.jit(f)
            return self._hist_fns[bucket]

        def get_part_fn(bucket):
            if bucket not in self._part_fns:
                f = shard_map(
                    functools.partial(part_fn, bucket=bucket), mesh=mesh,
                    in_specs=(P("data", None), P("data", None, None),
                              P("data"), P("data"), P(), P(), P(), P(), P(),
                              P()),
                    out_specs=(P("data", None), P("data")))
                self._part_fns[bucket] = jax.jit(f)
            return self._part_fns[bucket]

        self._get_hist_fn = get_hist_fn
        self._get_part_fn = get_part_fn

    # -- learner overrides ---------------------------------------------
    def train(self, grad, hess, bag_indices=None, iter_seed: int = 0):
        # reshape row-vectors to (S, local_n) shards
        rsh = NamedSharding(self.mesh, P("data", None))
        pad = self.n_pad - self.dataset.num_data
        if pad:
            grad = jnp.pad(grad, (0, pad))
            hess = jnp.pad(hess, (0, pad))
        self._grad2 = jax.device_put(
            grad.reshape(self.shards, self.local_n), rsh)
        self._hess2 = jax.device_put(
            hess.reshape(self.shards, self.local_n), rsh)
        if self._quant_bits:
            # per-iteration discretization (ops/quantize.py): every shard
            # holds one packed int32 (qg|qh) lane per row, histograms and
            # their allreduce ride exact integers
            from ..ops import quantize as quant_ops
            qkey = jax.random.PRNGKey((2 * iter_seed + 1) % (2**31 - 1))
            packed, s_g, s_h = quant_ops.quantize_gh(
                grad, hess, qkey, grad_bits=self._quant_bits)
            self._packed2 = jax.device_put(
                packed.reshape(self.shards, self.local_n), rsh)
            self._qscales = (s_g, s_h)
        # local index buffers per shard
        bufs = np.zeros((self.shards, self.local_n + self.max_local_bucket),
                        dtype=np.int32)
        counts = np.zeros(self.shards, dtype=np.int64)
        n = self.dataset.num_data
        if bag_indices is None:
            for s in range(self.shards):
                hi = min(self.local_n, n - s * self.local_n)
                bufs[s, :hi] = np.arange(hi, dtype=np.int32)
                counts[s] = max(hi, 0)
        else:
            shard_of = bag_indices // self.local_n
            local_of = bag_indices % self.local_n
            for s in range(self.shards):
                rows = local_of[shard_of == s]
                bufs[s, : len(rows)] = rows
                counts[s] = len(rows)
        self._idx_buf = jax.device_put(bufs, rsh)
        self._leaf_begin: Dict[int, np.ndarray] = {0: np.zeros(self.shards, np.int64)}
        self._leaf_count: Dict[int, np.ndarray] = {0: counts}
        return self._train_from_root(iter_seed)

    def _train_from_root(self, iter_seed):
        """Run the shared leaf-wise loop with sharded primitives."""
        from ..models.tree import Tree
        cfg = self.config
        rng = np.random.RandomState(
            (cfg.feature_fraction_seed + iter_seed) % (2**31 - 1))
        base_mask = self._feature_mask(rng)
        tree = Tree(cfg.num_leaves)

        class _St:  # mirrors serial _LeafState with per-shard ranges
            pass

        def mk_state(leaf_id, sum_grad, sum_hess, depth, min_c, max_c):
            st = _St()
            st.leaf_id = leaf_id
            st.sum_grad = sum_grad
            st.sum_hess = sum_hess
            st.depth = depth
            st.min_c, st.max_c = min_c, max_c
            st.hist = None
            st.split = None
            return st

        def build_hist(leaf_id):
            # host-collective boundary (histogram allreduce): dispatched
            # through the fault layer so injected transport failures land
            # here and transient ones retry with backoff (the programs
            # are side-effect-free, so a re-dispatch is always safe)
            begins = self._leaf_begin[leaf_id]
            cnts = self._leaf_count[leaf_id]
            bucket = _bucket(max(int(cnts.max()), 1), self.max_local_bucket)
            # forensic counter (unconditional, once per leaf): the
            # reduced histogram's payload — the role the reference's
            # ReduceScatter buffer plays; quantized ships 2 int32 lanes,
            # float 3 f32 lanes (4 bytes each either way)
            f = int(self.binned.shape[-1])
            lanes = 2 if self._quant_bits else 3
            telem_counters.incr("dist_reduce_scatter_bytes",
                                f * self.device_bins * lanes * 4)
            with telem.phase("dist_hist_exchange"):
                if self._quant_bits:
                    fn = self._get_hist_fn_q(bucket)
                    return faults.run_collective(
                        lambda: fn(self.binned, self._idx_buf,
                                   self._packed2,
                                   jnp.asarray(begins, jnp.int32),
                                   jnp.asarray(cnts, jnp.int32),
                                   jnp.float32(float(cnts.sum()))),
                        site="dp_hist")
                fn = self._get_hist_fn(bucket)
                return faults.run_collective(
                    lambda: fn(self.binned, self._idx_buf, self._grad2,
                               self._hess2, jnp.asarray(begins, jnp.int32),
                               jnp.asarray(cnts, jnp.int32)),
                    site="dp_hist")

        root_hist = build_hist(0)
        totals = np.asarray(
            jax.device_get(root_hist[0].sum(axis=0)), dtype=np.float64)
        if self._quant_bits:
            s_g, s_h = jax.device_get(self._qscales)
            totals = np.array([totals[0] / float(s_g),
                               totals[1] / float(s_h), totals[2]])
        root = mk_state(0, float(totals[0]), float(totals[1]), 0,
                        -np.inf, np.inf)
        root.hist = root_hist
        root.count = int(self._leaf_count[0].sum())
        root.split = self._scan_state(root, base_mask, rng)
        leaves = {0: root}

        for _ in range(cfg.num_leaves - 1):
            best_leaf, best_gain = -1, 1e-10
            for li, st in leaves.items():
                if st.split is not None and st.split["gain"] > best_gain:
                    best_leaf, best_gain = li, st.split["gain"]
            if best_leaf < 0:
                break
            self._apply_split_dp(tree, leaves, best_leaf, base_mask, rng,
                                 build_hist, mk_state)
        self.leaves = leaves
        return tree

    def _scan_state(self, st, base_mask, rng):
        mask = (self._node_feature_mask(base_mask, rng)
                & (self.f_categorical == 0))
        if self._quant_bits:
            s_g, s_h = self._qscales
            res = split_ops.find_best_split_quantized(
                st.hist, s_g, s_h, jnp.float32(st.sum_grad),
                jnp.float32(st.sum_hess), jnp.float32(st.count),
                self.f_numbins, self.f_missing, self.f_default, mask,
                self.f_monotone, jnp.float32(st.min_c),
                jnp.float32(st.max_c), **self._scan_args())
        else:
            res = split_ops.find_best_split(
                st.hist, jnp.float32(st.sum_grad), jnp.float32(st.sum_hess),
                jnp.float32(st.count), self.f_numbins, self.f_missing,
                self.f_default, mask,
                self.f_monotone, jnp.float32(st.min_c),
                jnp.float32(st.max_c), **self._scan_args())
        return self._fetch_split(res)

    def _apply_split_dp(self, tree, leaves, leaf_id, base_mask, rng,
                        build_hist, mk_state):
        ds = self.dataset
        st = leaves[leaf_id]
        sp = st.split
        inner_f = sp["feature"]
        real_f = ds.inner_to_real(inner_f)
        mapper = ds.bin_mappers[real_f]
        begins = self._leaf_begin[leaf_id]
        cnts = self._leaf_count[leaf_id]
        bucket = _bucket(max(int(cnts.max()), 1), self.max_local_bucket)
        fn = self._get_part_fn(bucket)
        with telem_spans.span("dp_partition", leaf=int(leaf_id),
                              bucket=bucket):
            new_buf, left_cnts = faults.run_collective(
                lambda: fn(
                    self._idx_buf, self.binned,
                    jnp.asarray(begins, jnp.int32),
                    jnp.asarray(cnts, jnp.int32),
                    jnp.int32(inner_f), jnp.int32(sp["threshold"]),
                    jnp.bool_(sp["default_left"]),
                    jnp.int32(mapper.missing_type),
                    jnp.int32(mapper.default_bin),
                    jnp.int32(mapper.num_bin)),
                site="dp_partition")
        self._idx_buf = new_buf
        left_cnts = np.asarray(jax.device_get(left_cnts), dtype=np.int64)

        thr_real = ds.real_threshold(inner_f, sp["threshold"])
        new_leaf = tree.split(
            leaf_id, inner_f, real_f, sp["threshold"], thr_real,
            sp["left_output"], sp["right_output"], sp["left_count"],
            sp["right_count"], sp["left_sum_hess"], sp["right_sum_hess"],
            sp["gain"], mapper.missing_type, sp["default_left"])

        self._leaf_begin[new_leaf] = begins + left_cnts
        self._leaf_count[new_leaf] = cnts - left_cnts
        self._leaf_count[leaf_id] = left_cnts

        left = mk_state(leaf_id, sp["left_sum_grad"], sp["left_sum_hess"],
                        st.depth + 1, st.min_c, st.max_c)
        left.count = sp["left_count"]
        right = mk_state(new_leaf, sp["right_sum_grad"], sp["right_sum_hess"],
                         st.depth + 1, st.min_c, st.max_c)
        right.count = sp["right_count"]
        smaller, larger = ((left, right) if left.count <= right.count
                          else (right, left))
        self._compute_child_hists(st, smaller, larger, build_hist)
        for child in (smaller, larger):
            child.split = (self._scan_state(child, base_mask, rng)
                           if child.hist is not None else None)
        leaves[leaf_id] = left
        leaves[new_leaf] = right

    def _compute_child_hists(self, st, smaller, larger, build_hist):
        if self._splittable_dp(smaller):
            smaller.hist = build_hist(smaller.leaf_id)
        if self._splittable_dp(larger):
            larger.hist = (hist_ops.subtract_histogram(st.hist, smaller.hist)
                           if smaller.hist is not None
                           else build_hist(larger.leaf_id))
        st.hist = None

    def _splittable_dp(self, st) -> bool:
        cfg = self.config
        return (st.count >= 2 * cfg.min_data_in_leaf
                and st.sum_hess >= 2 * cfg.min_sum_hessian_in_leaf
                and (cfg.max_depth <= 0 or st.depth < cfg.max_depth))

    def leaf_rows(self, leaf_id: int) -> np.ndarray:
        """Global row ids of a leaf (for leaf renewal)."""
        bufs = np.asarray(jax.device_get(self._idx_buf))
        out = []
        for s in range(self.shards):
            b = int(self._leaf_begin[leaf_id][s])
            c = int(self._leaf_count[leaf_id][s])
            out.append(bufs[s, b:b + c].astype(np.int64) + s * self.local_n)
        return np.concatenate(out) if out else np.zeros(0, np.int64)


class VotingParallelTreeLearner(DataParallelTreeLearner):
    """Data-parallel + top-k feature election (PV-Tree).

    Communication per split is O(2k·B): each shard votes for its local
    top-k features from its LOCAL histogram, votes are psum'd, and only the
    elected features' histograms are globally reduced
    (reference: voting_parallel_tree_learner.cpp:170-260).
    """

    def _build_sharded_fns(self):
        super()._build_sharded_fns()
        mesh = self.mesh
        num_bins = self.device_bins
        cfg = self.config
        top_k = max(1, int(cfg.top_k))
        scan_kwargs = self._scan_args()

        def vote_hist_fn(binned_l, idx_l, grad_l, hess_l, begin_l, count_l,
                         sum_g, sum_h, n_total, nbins, missing, defaults,
                         mask, mono, *, bucket):
            binned_l = binned_l[0]
            idx_l = idx_l[0]
            window = jax.lax.dynamic_slice(idx_l, (begin_l[0],), (bucket,))
            valid = jnp.arange(bucket, dtype=jnp.int32) < count_l[0]
            rows = jnp.take(binned_l, window, axis=0)
            g = jnp.take(grad_l[0], window) * valid
            h = jnp.take(hess_l[0], window) * valid
            gh = jnp.stack([g, h, valid.astype(jnp.float32)], axis=1)
            local_hist = hist_ops.build_histogram(rows, gh, num_bins)
            # local voting on LOCAL histogram with globally-scaled
            # constraints (reference scales min_data by 1/num_machines,
            # voting_parallel_tree_learner.cpp:57-59)
            local_n = jnp.sum(valid.astype(jnp.float32))
            local_g = local_hist[0, :, 0].sum()
            local_h = local_hist[0, :, 1].sum()
            rel, _, _, _ = split_ops.per_feature_best(
                local_hist, local_g, local_h, local_n, nbins, missing,
                defaults, mask, mono, jnp.float32(-jnp.inf),
                jnp.float32(jnp.inf),
                **{**scan_kwargs,
                   # the reference scales BOTH local gates by machine
                   # count (voting_parallel_tree_learner.cpp:58-59)
                   "min_data_in_leaf":
                       scan_kwargs["min_data_in_leaf"] // self.shards,
                   "min_sum_hessian":
                       scan_kwargs["min_sum_hessian"] / self.shards})
            f = rel.shape[0]
            k = min(top_k, f)
            _, top_idx = jax.lax.top_k(rel, k)
            votes = jnp.zeros(f, jnp.float32).at[top_idx].add(
                jnp.where(rel[top_idx] > split_ops.NEG_INF / 2, 1.0, 0.0))
            votes = jax.lax.psum(votes, "data")
            # elect global top-2k, reduce only their histograms
            k2 = min(2 * k, f)
            _, elected = jax.lax.top_k(votes, k2)
            elected_hist = jax.lax.psum(local_hist[elected], "data")
            # scatter back into a full-size (F, B, 3) global hist; the scan
            # masks non-elected features out via elected_mask
            full = jnp.zeros((f, num_bins, 3), jnp.float32)
            full = full.at[elected].set(elected_hist)
            elected_mask = jnp.zeros(f, bool).at[elected].set(True)
            return full, elected_mask

        def vote_hist_fn_q(binned_l, idx_l, packed_l, begin_l, count_l,
                           scale3, nbins, missing, defaults, mask, mono,
                           *, bucket):
            """Quantized PV-Tree election: the local histogram is EXACT
            int32 (one integer contraction), local voting scans its
            dequantized rendering (local counts stay exact), and the
            reduced collective — the only cross-shard histogram traffic —
            moves the elected 2k features' int32 histograms."""
            from ..ops import quantize as quant_ops
            binned_l = binned_l[0]
            idx_l = idx_l[0]
            window = jax.lax.dynamic_slice(idx_l, (begin_l[0],), (bucket,))
            valid = jnp.arange(bucket, dtype=jnp.int32) < count_l[0]
            rows = jnp.take(binned_l, window, axis=0)
            ghq = quant_ops.gh_operand(jnp.take(packed_l[0], window), valid,
                                       self._quant_bits)
            local_q = hist_ops.build_histogram_quantized(rows, ghq, num_bins)
            local_hist = local_q.astype(jnp.float32) * scale3
            local_n = jnp.sum(valid.astype(jnp.float32))
            local_g = local_hist[0, :, 0].sum()
            local_h = local_hist[0, :, 1].sum()
            rel, _, _, _ = split_ops.per_feature_best(
                local_hist, local_g, local_h, local_n, nbins, missing,
                defaults, mask, mono, jnp.float32(-jnp.inf),
                jnp.float32(jnp.inf),
                **{**scan_kwargs,
                   "min_data_in_leaf":
                       scan_kwargs["min_data_in_leaf"] // self.shards,
                   "min_sum_hessian":
                       scan_kwargs["min_sum_hessian"] / self.shards})
            f = rel.shape[0]
            k = min(top_k, f)
            _, top_idx = jax.lax.top_k(rel, k)
            votes = jnp.zeros(f, jnp.float32).at[top_idx].add(
                jnp.where(rel[top_idx] > split_ops.NEG_INF / 2, 1.0, 0.0))
            votes = jax.lax.psum(votes, "data")
            k2 = min(2 * k, f)
            _, elected = jax.lax.top_k(votes, k2)
            # int32 collective: exact integer reduction of the elected
            # features' histograms (O(2k*B) int32 lanes on the wire)
            elected_q = jax.lax.psum(local_q[elected], "data")
            elected_hist = elected_q.astype(jnp.float32) * scale3
            full = jnp.zeros((f, num_bins, 3), jnp.float32)
            full = full.at[elected].set(elected_hist)
            elected_mask = jnp.zeros(f, bool).at[elected].set(True)
            return full, elected_mask

        self._vote_fns: Dict[int, object] = {}
        self._vote_fns_q: Dict[int, object] = {}

        def get_vote_fn(bucket):
            if bucket not in self._vote_fns:
                fn = shard_map(
                    functools.partial(vote_hist_fn, bucket=bucket), mesh=mesh,
                    in_specs=(P("data", None, None), P("data", None),
                              P("data", None), P("data", None), P("data"),
                              P("data"), P(), P(), P(), P(), P(), P(), P(),
                              P()),
                    out_specs=(P(), P()))
                self._vote_fns[bucket] = jax.jit(fn)
            return self._vote_fns[bucket]

        def get_vote_fn_q(bucket):
            if bucket not in self._vote_fns_q:
                fn = shard_map(
                    functools.partial(vote_hist_fn_q, bucket=bucket),
                    mesh=mesh,
                    in_specs=(P("data", None, None), P("data", None),
                              P("data", None), P("data"), P("data"),
                              P(), P(), P(), P(), P(), P()),
                    out_specs=(P(), P()))
                self._vote_fns_q[bucket] = jax.jit(fn)
            return self._vote_fns_q[bucket]

        self._get_vote_fn = get_vote_fn
        self._get_vote_fn_q = get_vote_fn_q

    def _scan_state(self, st, base_mask, rng):
        # build voting histogram instead of the dense psum one
        begins = self._leaf_begin[st.leaf_id]
        cnts = self._leaf_count[st.leaf_id]
        bucket = _bucket(max(int(cnts.max()), 1), self.max_local_bucket)
        fmask = self._node_feature_mask(base_mask, rng) & (self.f_categorical == 0)
        # forensic counter: votes (one f32 lane per feature) + the
        # elected 2k features' int32 histogram triples — the PV-Tree
        # O(2k*B) wire payload
        f = int(self.binned.shape[-1])
        k2 = min(2 * max(1, int(self.config.top_k)), f)
        telem_counters.incr("dist_reduce_scatter_bytes",
                            f * 4 + k2 * self.device_bins * 3 * 4)
        with telem.phase("dist_hist_exchange"):
            if self._quant_bits:
                from ..ops.quantize import dequant_scale3
                fn = self._get_vote_fn_q(bucket)
                full_hist, elected_mask = faults.run_collective(
                    lambda: fn(
                        self.binned, self._idx_buf, self._packed2,
                        jnp.asarray(begins, jnp.int32),
                        jnp.asarray(cnts, jnp.int32),
                        dequant_scale3(*self._qscales), self.f_numbins,
                        self.f_missing, self.f_default, fmask,
                        self.f_monotone),
                    site="vote_hist")
            else:
                fn = self._get_vote_fn(bucket)
                full_hist, elected_mask = faults.run_collective(
                    lambda: fn(
                        self.binned, self._idx_buf, self._grad2,
                        self._hess2,
                        jnp.asarray(begins, jnp.int32),
                        jnp.asarray(cnts, jnp.int32),
                        jnp.float32(st.sum_grad), jnp.float32(st.sum_hess),
                        jnp.float32(st.count), self.f_numbins,
                        self.f_missing,
                        self.f_default, fmask, self.f_monotone),
                    site="vote_hist")
        res = split_ops.find_best_split(
            full_hist, jnp.float32(st.sum_grad), jnp.float32(st.sum_hess),
            jnp.float32(st.count), self.f_numbins, self.f_missing,
            self.f_default, fmask & elected_mask, self.f_monotone,
            jnp.float32(st.min_c), jnp.float32(st.max_c), **self._scan_args())
        return self._fetch_split(res)

    def _compute_child_hists(self, st, smaller, larger, build_hist):
        # voting cannot use parent-minus-sibling subtraction (elected
        # feature sets differ per leaf); _scan_state builds its own
        # vote-reduced histogram, so children just get a go-ahead marker
        st.hist = None
        for child in (smaller, larger):
            child.hist = "voting" if self._splittable_dp(child) else None


class DeviceDataParallelTreeLearner(DeviceTreeLearner):
    """Whole-tree data-parallel learner: rows sharded over a 1-D 'data'
    mesh, the ENTIRE leaf-wise tree (partition + histograms + scans) grown
    inside one jitted shard_map program.

    The reference's per-split communication — ReduceScatter of all local
    histograms plus an Allreduce of the best split (reference:
    src/treelearner/data_parallel_tree_learner.cpp:149-164, :246
    SyncUpGlobalBestSplit) — maps to ONE collective over the smaller
    child's (C, B, 3) histogram per split. Two reduction modes:

    * psum (fallback): the histogram is summed and replicated; every
      shard runs the identical argmax/scan, so the global-best sync
      costs nothing extra.
    * reduce-scatter (default when the dataset has no EFB bundles and
      no by-node sampling): lax.psum_scatter tiles the histogram's
      column axis across shards — each shard owns C/D columns of every
      pool slot (pool memory /D, ~half the reduce traffic), scans its
      slice, and the winner is elected from a (D, 12) all_gather of
      candidate rows, exactly the reference's comm pattern.

    Each shard physically partitions only its own rows (local
    DataPartition semantics, :256-262 global leaf counts come from the
    reduced histograms). No host round-trips inside a tree.
    """

    _chunk_capable = True

    def __init__(self, config: Config, dataset: Dataset,
                 mesh: Optional[Mesh] = None):
        # LGBM_TPU_STRATEGY=chunk opts the sharded program into the
        # switch-free chunk core; resolve_strategy may fall chunk back
        # to compact (LRU-capped pool), so read self.strategy afterwards
        super().__init__(config, dataset,
                         strategy=_sharded_chunk_opt_in(self),
                         device_place=False)
        self.mesh = mesh or make_mesh(axis_name="data")
        self.shards = int(self.mesh.devices.size)
        # reduce-scatter mode needs the identity feature->column mapping
        # and shard-independent feature masks (see grow_tree_compact_core
        # / grow_tree_chunk_core — both cores carry the scatter seam)
        mode = dp_reduce_mode_env()
        self.scatter_cols = (
            self.shards if (mode != "psum"
                            and dataset.bundle_arrays() is None
                            and not (0.0 < config.feature_fraction_bynode
                                     < 1.0)
                            and self.shards > 1)
            else 0)
        n = dataset.num_data
        self.local_n = -(-n // self.shards)
        self.n_pad = self.local_n * self.shards

        if self._shard is not None:
            # streamed: no resident codes — train() assembles one
            # working buffer per local mesh device from the host wire
            # store (_train_streamed)
            pass
        elif getattr(dataset, "row_shard", None) is not None:
            # rows-mode ingest: this host's arrays hold ONLY its row
            # block; lift them onto the global mesh with zero cross-host
            # traffic (every device receives exactly its own rows)
            self.codes_pack = self._global_from_local(self.codes_pack)
            self.codes_row = self._global_from_local(self.codes_row)
        else:
            # place the packed buffers row-sharded and padded (the base
            # class kept them host-side); pad rows carry zero codes and
            # are fenced off by w == 0 inside the step
            pad = self.n_pad - n
            rsh = NamedSharding(self.mesh, P("data", None))
            cp, cr = self.codes_pack, self.codes_row
            if pad:
                cp = np.pad(cp, ((0, pad), (0, 0)))
                cr = np.pad(cr, ((0, pad), (0, 0)))
            # host -> shards directly (the base class kept both host-side)
            self.codes_pack = jax.device_put(cp, rsh)
            self.codes_row = jax.device_put(cr, rsh)
        self._meta = (self.f_numbins, self.f_missing, self.f_default,
                      self.f_monotone, self.f_penalty, self.f_categorical,
                      self.f_col, self.f_base, self.f_elide, self.scan_plan)
        self._tree_w_fn = None

    # -- row-sharded ingest (dist_shard_mode=rows) ---------------------
    def _local_mesh_positions(self):
        """(mesh position, device) pairs of this process's devices along
        the 'data' axis — position p owns global rows [p*local_n,
        (p+1)*local_n)."""
        me = jax.process_index()
        return [(p, d) for p, d in enumerate(self.mesh.devices.flat)
                if d.process_index == me]

    def _global_from_local(self, block) -> jax.Array:
        """Lift this host's (local rows, C) ingest block onto the global
        'data' mesh: each locally-owned mesh position takes its own
        local_n-row slice (zero-padded at the global tail) and
        `make_array_from_single_device_arrays` stitches the per-device
        pieces into one row-sharded global array — no collective, the
        code matrix never crosses the wire. Requires the block to start
        on a local_n boundary and to cover every position this
        process's devices own (`ingest.load_sharded` aligns blocks to
        the local device count, so both hold by construction)."""
        from ..utils.log import LightGBMError
        begin, end = self.dataset.row_shard
        n = self.dataset.num_data
        local_n = self.local_n
        if begin % local_n:
            raise LightGBMError(
                f"row-sharded ingest block starts at row {begin}, not a "
                f"multiple of the per-device block ({local_n} rows = "
                f"ceil({n} rows / {self.shards} devices)); re-ingest "
                "with ingest.load_sharded so blocks align to device "
                "boundaries")
        block = np.asarray(block)
        bufs = []
        for p, dev in self._local_mesh_positions():
            lo = p * local_n - begin
            if lo < 0 or (lo >= block.shape[0] and p * local_n < n):
                raise LightGBMError(
                    f"row-sharded ingest block {begin}:{end} does not "
                    f"cover mesh position {p} (rows {p * local_n}:"
                    f"{(p + 1) * local_n}) owned by this process — the "
                    "ingest world and the training mesh disagree; "
                    "re-ingest (ingest.reshard) after any world-size "
                    "change")
            sl = block[max(lo, 0):lo + local_n]
            if sl.shape[0] < local_n:
                sl = np.pad(sl, ((0, local_n - sl.shape[0]), (0, 0)))
            bufs.append(jax.device_put(sl, dev))
        return jax.make_array_from_single_device_arrays(
            (self.n_pad, int(block.shape[1])),
            NamedSharding(self.mesh, P("data", None)), bufs)

    def _count_hist_wire(self, n_splits: int) -> None:
        """Analytic reduce-scatter byte accounting for the in-program
        per-leaf histogram exchange (the collective lives inside the
        jitted tree program, so unlike the host-loop learners there is
        no host boundary to count at): root + one smaller-child
        histogram per split, (C, B, 3) lanes of 4 bytes (int32 when
        quantized, f32 otherwise)."""
        telem_counters.incr(
            "dist_reduce_scatter_bytes",
            (int(n_splits) + 1) * int(self.c_cols)
            * int(self.device_bins) * 3 * 4)

    def replay_tree(self, rec_h, k: int, rec_cat_h=None):
        # every grown tree passes through here (generic, fused and
        # streamed paths), so this is the one host point that sees the
        # split count the wire accounting needs
        self._count_hist_wire(int(k))
        return super().replay_tree(rec_h, k, rec_cat_h)

    # ------------------------------------------------------------------
    def _grow_statics(self):
        # quantized statics: rows carry w=0 pads (and per-shard bag
        # masks), so the packed layout keeps the weight word; the
        # overflow cap and the scatter wire dtype bound on GLOBAL rows
        quant_kw = dict(quant_bits=self.quant_bits,
                        quant_renew=self.quant_renew,
                        quant_total_rows=self.n_pad)
        if self.strategy == "chunk":
            from ..utils.envs import flag
            return dict(c_cols=self.c_cols, item_bits=self.item_bits,
                        chunk_rows=self.chunk_rows,
                        fuse_hist=not flag("LGBM_TPU_CHUNK_NO_FUSE_HIST"),
                        scatter_cols=self.scatter_cols,
                        **quant_kw, **self._statics())
        return dict(c_cols=self.c_cols, item_bits=self.item_bits,
                    pool_slots=self.pool_slots,
                    scatter_cols=self.scatter_cols,
                    **quant_kw, **self._statics())

    def _sharded_tree_fn(self, with_bag_key: bool, allow_bagging=True,
                         goss=None):
        """shard_map'd whole-tree program. with_bag_key=True computes the
        per-shard bag weights inside the program (fused path); False takes
        an explicit (n_pad,) weight vector (generic path). allow_bagging
        =False forces full-data growth regardless of bagging params (the
        GOSS-warmup contract). goss=(top_rate, other_rate) switches the
        in-program sampling to per-shard GOSS: each shard keeps its local
        top rows by |g*h| and amplifies a uniform sample of the rest —
        the reference's distributed behavior (BaggingHelper runs on each
        machine's local partition, goss.hpp:60-117 under num_machines>1),
        so no global top-k collective is needed."""
        from ..models.device_learner import (grow_tree_chunk_core, grow_tree_compact_core)
        grow_core = (grow_tree_chunk_core if self.strategy == "chunk" else grow_tree_compact_core)
        statics = self._grow_statics()
        meta = self._meta
        cfg = self.config
        n = self.dataset.num_data
        local_n = self.local_n
        bag_on = (goss is None and allow_bagging and cfg.bagging_freq > 0
                  and cfg.bagging_fraction < 1.0)
        frac = float(cfg.bagging_fraction)

        def local(cp_l, cr_l, g_l, h_l, w_or_key, base_mask, key):
            i = jax.lax.axis_index("data")
            pos = jnp.arange(local_n, dtype=jnp.int32)
            real = jnp.clip(n - i * local_n, 0, local_n)
            alive = pos < real
            if with_bag_key and goss is not None:
                top_rate, other_rate = goss
                realf = real.astype(jnp.float32)
                top_l = jnp.maximum(1, (realf * top_rate).astype(jnp.int32))
                other_l = jnp.maximum(
                    1, (realf * other_rate).astype(jnp.int32))
                # exact local top_l by |g*h| (rank-based like the
                # single-chip fused GOSS; pads carry gmag 0 and sit after
                # equal-key alive rows in the stable sort)
                gmag = jnp.abs(g_l * h_l) * alive.astype(jnp.float32)
                ridx = jnp.argsort(-gmag, stable=True)
                rank_of = jnp.zeros(local_n, jnp.int32).at[ridx].set(pos)
                is_top = (rank_of < top_l) & alive
                u = jnp.where(
                    alive & ~is_top,
                    jax.random.uniform(
                        jax.random.fold_in(w_or_key, i), (local_n,)),
                    jnp.inf)
                cut = jnp.sort(u)[other_l - 1]
                # alive/~is_top guard: on a degenerate shard (all padding,
                # or fewer rest-rows than other_l) cut is inf and a bare
                # u <= cut would select pad and top rows
                is_other = (u <= cut) & alive & ~is_top
                mult = ((realf - top_l.astype(jnp.float32))
                        / jnp.maximum(other_l, 1).astype(jnp.float32))
                amp = jnp.where(is_other, mult, 1.0)
                g_l = g_l * amp
                h_l = h_l * amp
                w_l = (is_top | is_other).astype(jnp.float32)
            elif with_bag_key:
                if bag_on:
                    # per-shard exact-count bagging over the shard's real
                    # rows (reference bags each machine's local partition,
                    # gbdt.cpp:210-276 under num_machines > 1)
                    u = jnp.where(
                        alive,
                        jax.random.uniform(
                            jax.random.fold_in(w_or_key, i), (local_n,)),
                        jnp.inf)
                    k_local = jnp.maximum(
                        1, (real.astype(jnp.float32) * frac)
                        .astype(jnp.int32))
                    cut = jnp.sort(u)[k_local - 1]
                    # the alive guard matters on an all-padding shard
                    # (real == 0): u is all-inf there and (u <= cut) would
                    # otherwise select every pad row
                    w_l = ((u <= cut) & alive).astype(jnp.float32)
                else:
                    w_l = alive.astype(jnp.float32)
            else:
                w_l = w_or_key * alive.astype(jnp.float32)
            rec, rec_cat, leaf_id, ks, tot = grow_core(
                cp_l, cr_l, g_l, h_l, w_l, base_mask, *meta, key,
                axis_name="data", **statics)
            # rec_cat (the categorical winners' left-bin masks) is
            # replicated: psum mode scans identical reduced histograms
            # everywhere, scatter mode transports the mask through the
            # candidate election. Placeholder zeros keep the output
            # pytree uniform when the dataset has no categoricals.
            if rec_cat is None:
                rec_cat = jnp.zeros((rec.shape[0], 1), jnp.float32)
            return rec, rec_cat, leaf_id, ks, tot

        w_spec = P() if with_bag_key else P("data")
        return shard_map(
            local, mesh=self.mesh,
            in_specs=(P("data", None), P("data", None), P("data"),
                      P("data"), w_spec, P(), P()),
            out_specs=(P(), P(), P("data"), P(), P()), check_vma=False)

    # ------------------------------------------------------------------
    def train(self, grad: jax.Array, hess: jax.Array,
              bag_indices: Optional[np.ndarray] = None,
              iter_seed: int = 0) -> Tree:
        cfg = self.config
        n = self.dataset.num_data
        pad = self.n_pad - n
        if bag_indices is None:
            wv = np.ones(self.n_pad, dtype=np.float32)
            if pad:
                wv[n:] = 0.0
            self._bag_mask_host = None
        else:
            wv = np.zeros(self.n_pad, dtype=np.float32)
            wv[bag_indices] = 1.0
            self._bag_mask_host = wv[:n] > 0
        rng = np.random.RandomState(
            (cfg.feature_fraction_seed + iter_seed) % (2**31 - 1))
        base_mask = jnp.asarray(self._feature_mask(rng))
        key = jax.random.PRNGKey(iter_seed)
        if self._shard is not None:
            return self._train_streamed(grad, hess, wv, base_mask, key)
        if self._tree_w_fn is None:
            fn = self._sharded_tree_fn(with_bag_key=False)
            nn, npad = n, self.n_pad

            @jax.jit
            def run(cp, cr, g, h, w, mask, k):
                g = jnp.pad(g, (0, npad - nn))
                h = jnp.pad(h, (0, npad - nn))
                rec, rec_cat, leaf_id, ks, tot = fn(cp, cr, g, h, w, mask, k)
                return rec, rec_cat, leaf_id[:nn], ks, tot
            self._tree_w_fn = run
        rec, rec_cat, leaf_id, n_splits, _ = self._tree_w_fn(
            self.codes_pack, self.codes_row, grad, hess, jnp.asarray(wv),
            base_mask, key)
        self.last_leaf_id = leaf_id
        self._leaf_id_host = None
        if self._has_cat:
            rec_h, rec_cat_h, k = jax.device_get((rec, rec_cat, n_splits))
        else:
            rec_h, k = jax.device_get((rec, n_splits))
            rec_cat_h = None
        k = int(k)
        if k == 0:
            log.warning("No further splits with positive gain")
        return self.replay_tree(rec_h, k, rec_cat_h)

    # -- streamed (out-of-core) data-parallel path ---------------------
    def _host_rows(self, arr, lo: int, hi: int) -> np.ndarray:
        """np.float32 rows [lo:hi) of an (N,) row vector that is either
        process-local or a global row-sharded jax array (the score-
        derived gradients after the first distributed iteration). A
        sharded slice must be covered by ONE addressable shard — true by
        construction: the device at mesh position p holds exactly the
        rows position p's working buffer needs."""
        if isinstance(arr, np.ndarray):
            return np.asarray(arr[lo:hi], dtype=np.float32)
        if getattr(arr, "is_fully_addressable", True):
            return np.asarray(jax.device_get(arr))[lo:hi].astype(
                np.float32, copy=False)
        for s in arr.addressable_shards:
            sl = s.index[0]
            start = sl.start or 0
            stop = arr.shape[0] if sl.stop is None else sl.stop
            if start <= lo and hi <= stop:
                return np.asarray(jax.device_get(s.data))[
                    lo - start:hi - start].astype(np.float32, copy=False)
        from ..utils.log import LightGBMError
        raise LightGBMError(
            f"streamed data-parallel assembly: rows {lo}:{hi} are not "
            "addressable on this process (the gradient sharding does "
            "not match the 'data' mesh row blocks)")

    def _dp_stream_init(self, local_n: int, d_cols: int, cw: int):
        """Per-device jit building one (local_n + CH, d_cols) u32
        working buffer: gh words [g*w, h*w, w] + LOCAL row ids at column
        cw, code section zeroed (chunk writes fill it). Float layout
        only — create_tree_learner rejects quant x stream x data."""
        jkey = ("dp_init", local_n, d_cols, cw)
        fn = self._stream_jits.get(jkey)
        if fn is None:
            CH = int(self.chunk_rows)

            def init(g, h, w):
                gh_u = jax.lax.bitcast_convert_type(
                    jnp.stack([g * w, h * w, w], axis=1), jnp.uint32)
                ids = jnp.arange(local_n, dtype=jnp.uint32)[:, None]
                tail = jnp.concatenate([gh_u, ids], axis=1)
                buf = jnp.zeros((local_n + CH, d_cols), jnp.uint32)
                return jax.lax.dynamic_update_slice(
                    buf, tail, (jnp.int32(0), jnp.int32(cw)))

            fn = jax.jit(init)
            self._stream_jits[jkey] = fn
        return fn

    def _streamed_tree_fn(self):
        """jitted shard_map'd prebuilt chunk-core program: each shard's
        buffer already holds its own rows (codes + gh words), per-leaf
        histogram psums over 'data' are the only cross-shard exchange."""
        fn = getattr(self, "_stream_dp_fn", None)
        if fn is not None:
            return fn
        from ..models.device_learner import grow_tree_chunk_core
        statics = dict(self._grow_statics())
        statics["scatter_cols"] = 0   # prebuilt runs the plain psum lane
        statics["data_prebuilt"] = True
        meta = self._meta
        nn = self.dataset.num_data

        def local(buf_l, g_l, h_l, w_l, base_mask, key):
            dummy_row = jnp.zeros((1, 1), jnp.uint8)
            rec, rec_cat, leaf_id, ks, tot = grow_tree_chunk_core(
                buf_l, dummy_row, g_l, h_l, w_l, base_mask, *meta, key,
                axis_name="data", **statics)
            if rec_cat is None:
                rec_cat = jnp.zeros((rec.shape[0], 1), jnp.float32)
            return rec, rec_cat, leaf_id, ks, tot

        smapped = shard_map(
            local, mesh=self.mesh,
            in_specs=(P("data", None), P("data"), P("data"), P("data"),
                      P(), P()),
            out_specs=(P(), P(), P("data"), P(), P()), check_vma=False)

        @jax.jit
        def run(data0, g, h, w, mask, k):
            rec, rec_cat, leaf_id, ks, tot = smapped(
                data0, g, h, w, mask, k)
            return rec, rec_cat, leaf_id[:nn], ks, tot

        self._stream_dp_fn = run
        return run

    def _train_streamed(self, grad, hess, wv, base_mask, key):
        """stream_mode=chunked x data-parallel: every local mesh device
        gets its own (local_n + CH, d_cols) working buffer assembled
        from the host wire store (with dist_shard_mode=rows the local
        block IS everything this host stores), the per-device buffers
        join into one row-sharded global array, and the chunk core runs
        prebuilt under shard_map. The code matrix and the float rows
        never cross hosts — per-leaf histogram psums are the only
        cross-host bytes."""
        from ..utils.log import LightGBMError
        shard = self._shard
        n = self.dataset.num_data
        local_n = self.local_n
        CH = int(self.chunk_rows)
        cw = int(shard.code_words)
        d_cols = cw + 3 + 1           # codes | g*w, h*w, w | row id
        row_shard = getattr(self.dataset, "row_shard", None)
        shard_begin = int(row_shard[0]) if row_shard is not None else 0
        mine = self._local_mesh_positions()
        shard.track_buffer("data0",
                           len(mine) * (local_n + CH) * d_cols * 4)
        bufs, g_parts, h_parts, w_parts = [], [], [], []
        for p, dev in mine:
            lo = p * local_n
            hi = min(lo + local_n, n)
            rows = max(hi - lo, 0)
            gp = np.zeros(local_n, np.float32)
            hp = np.zeros(local_n, np.float32)
            if rows:
                gp[:rows] = self._host_rows(grad, lo, hi)
                hp[:rows] = self._host_rows(hess, lo, hi)
            wp = np.asarray(wv[lo:lo + local_n], dtype=np.float32)
            gj = jax.device_put(gp, dev)
            hj = jax.device_put(hp, dev)
            wj = jax.device_put(wp, dev)
            buf = self._dp_stream_init(local_n, d_cols, cw)(gj, hj, wj)
            if rows:
                wire_lo = lo - shard_begin
                if wire_lo < 0 or wire_lo + rows > shard.num_rows:
                    raise LightGBMError(
                        f"streamed assembly: mesh position {p} needs "
                        f"global rows {lo}:{hi} but this host's wire "
                        f"store holds rows {shard_begin}:"
                        f"{shard_begin + shard.num_rows} — re-ingest "
                        "(ingest.reshard) after any world-size change")
                for s, cnt, dv in shard.iter_chunks(
                        row_ids=np.arange(wire_lo, wire_lo + rows),
                        device=dev):
                    buf = self._stream_write(buf, dv, s)
            bufs.append(buf)
            g_parts.append(gj)
            h_parts.append(hj)
            w_parts.append(wj)
        rsh = NamedSharding(self.mesh, P("data", None))
        vsh = NamedSharding(self.mesh, P("data"))
        mk = jax.make_array_from_single_device_arrays
        data0 = mk((self.shards * (local_n + CH), d_cols), rsh, bufs)
        gg = mk((self.n_pad,), vsh, g_parts)
        hh = mk((self.n_pad,), vsh, h_parts)
        ww = mk((self.n_pad,), vsh, w_parts)
        try:
            rec, rec_cat, leaf_id, n_splits, _ = self._streamed_tree_fn()(
                data0, gg, hh, ww, base_mask, key)
        finally:
            shard.release_buffer("data0")
        self.last_leaf_id = leaf_id
        self._leaf_id_host = None
        if self._has_cat:
            rec_h, rec_cat_h, k = jax.device_get((rec, rec_cat, n_splits))
        else:
            rec_h, k = jax.device_get((rec, n_splits))
            rec_cat_h = None
        k = int(k)
        if k == 0:
            log.warning("No further splits with positive gain")
        return self.replay_tree(rec_h, k, rec_cat_h)

    # ------------------------------------------------------------------
    def make_fused_step(self, objective, goss=None, bagging=True):
        """Fused sharded boosting iteration (see DeviceTreeLearner
        .make_fused_step): gradients auto-shard over the score, the tree
        grows under shard_map with per-split psum, the score update is
        elementwise over the sharded leaf assignment."""
        from ..models.device_learner import leaf_values_from_rec
        n = self.dataset.num_data
        npad = self.n_pad
        L = int(self.config.num_leaves)
        # fused GOSS runs per shard (local top-k + amplification, the
        # reference's per-machine BaggingHelper semantics); rates come
        # from config, counts are derived from each shard's real rows
        goss_rates = None
        if goss is not None:
            goss_rates = (float(self.config.top_rate),
                          float(self.config.other_rate))
        fn = self._sharded_tree_fn(with_bag_key=True,
                                   allow_bagging=bagging,
                                   goss=goss_rates)

        has_cat = self._has_cat

        obj_keys = objective_buffer_names(objective)

        # `step_impl` is a name the benchmark reads (it finds the tree
        # program in a device trace by the module `jit_step_impl`; see
        # the serial make_fused_step)
        @jax.jit
        def step_impl(codes_pack, codes_row, obj_bufs, score_row,
                      base_mask, tree_key, bag_key, shrinkage):
            # codes + objective buffers as args, not closure constants —
            # see the serial make_fused_step note (compile payload)
            with swapped_attrs(objective, obj_keys, obj_bufs), \
                    jax.named_scope("lgbm.gradients"):
                g, h = objective.get_gradients(score_row)
            g = jnp.pad(g, (0, npad - n))
            h = jnp.pad(h, (0, npad - n))
            rec, rec_cat, leaf_id_pad, k, _ = fn(
                codes_pack, codes_row,
                g, h, bag_key, base_mask, tree_key)
            leaf_id = leaf_id_pad[:n]
            with jax.named_scope("lgbm.score_update"):
                lv = leaf_values_from_rec(rec, k, L)
                delta = jnp.take(lv, jnp.clip(leaf_id, 0, L - 1)) \
                    * shrinkage
                new_score = score_row + delta
                # in-program sentry reduction (see the serial step
                # contract)
                finite = jnp.all(jnp.isfinite(new_score))
            return (new_score, rec, rec_cat if has_cat else None,
                    leaf_id, k, finite)

        def make_args(*step_args):
            obj_bufs = tuple(getattr(objective, k) for k in obj_keys)
            return (self.codes_pack, self.codes_row, obj_bufs, *step_args)

        return fused_step_surface(step_impl, make_args, obj_keys)


class DeviceVotingParallelTreeLearner(DeviceDataParallelTreeLearner):
    """Whole-tree voting-parallel learner (PV-Tree) on the device: the
    data-parallel shard_map program with per-split two-stage voting —
    local top-k election by locally-scanned gains, vote psum, and a
    reduction of ONLY the elected 2k features' histograms
    (voting_parallel_tree_learner.cpp:170-260). Communication per split
    is O(2k*B), constant in feature count. Both growth cores carry the
    voting seam (make_voting_search), so LGBM_TPU_STRATEGY=chunk works
    here too."""

    def __init__(self, config: Config, dataset: Dataset,
                 mesh: Optional[Mesh] = None):
        super().__init__(config, dataset, mesh)
        self.scatter_cols = 0              # voting replaces the scatter
        self.voting_k = max(1, int(config.top_k))

    def _grow_statics(self):
        d = super()._grow_statics()
        d["voting_k"] = self.voting_k
        return d


class DeviceFeatureParallelTreeLearner(DeviceTreeLearner):
    """Whole-tree feature-parallel learner: rows REPLICATED, columns
    partitioned — each shard builds histograms only for its word-aligned
    column slice (the local slice over all rows IS the global histogram,
    so there is no histogram collective at all) and the best split is
    elected from a (D, 12) all_gather of per-shard candidates — the
    reference FeatureParallelTreeLearner's exact communication shape
    (feature_parallel_tree_learner.cpp:33-76, SyncUpGlobalBestSplit),
    with the entire leaf-wise tree grown inside one shard_map program
    instead of one host round-trip per split."""

    supports_fused_goss = True    # rows replicated: single-chip GOSS

    def __init__(self, config: Config, dataset: Dataset,
                 mesh: Optional[Mesh] = None):
        super().__init__(config, dataset,
                         strategy=_sharded_chunk_opt_in(self),
                         device_place=False)
        self.mesh = mesh or make_mesh(axis_name="feature")
        self.shards = int(self.mesh.devices.size)
        cs = padded_shard_cols(self.c_cols, self.shards, self.item_bits)
        self._c_pad = cs * self.shards
        # repack with word-aligned per-shard column capacity
        host_codes = np.asarray(self.codes_row)
        self.codes_pack = jnp.asarray(self.pack_codes(
            host_codes, col_target=self._c_pad))
        self.codes_row = jnp.asarray(host_codes)
        self._meta = (self.f_numbins, self.f_missing, self.f_default,
                      self.f_monotone, self.f_penalty, self.f_categorical,
                      self.f_col, self.f_base, self.f_elide, self.scan_plan)
        self._tree_fn = None

    def _grow_statics(self):
        if self.strategy == "chunk":
            from ..utils.envs import flag
            return dict(c_cols=self.c_cols, item_bits=self.item_bits,
                        chunk_rows=self.chunk_rows,
                        fuse_hist=not flag("LGBM_TPU_CHUNK_NO_FUSE_HIST"),
                        feature_shards=self.shards,
                        **self._statics())
        return dict(c_cols=self.c_cols, item_bits=self.item_bits,
                    pool_slots=self.pool_slots,
                    feature_shards=self.shards,
                    **self._statics())

    def _sharded_tree_fn(self):
        from ..models.device_learner import (grow_tree_chunk_core,
                                             grow_tree_compact_core)
        grow_core = (grow_tree_chunk_core if self.strategy == "chunk"
                     else grow_tree_compact_core)
        statics = self._grow_statics()
        meta = self._meta

        def local(cp, cr, g, h, w, base_mask, key):
            rec, rec_cat, leaf_id, ks, tot = grow_core(
                cp, cr, g, h, w, base_mask, *meta, key,
                axis_name="feature", **statics)
            # replicated: the elected candidate row carries the winning
            # categorical mask (see _elect in grow_tree_compact_core)
            if rec_cat is None:
                rec_cat = jnp.zeros((rec.shape[0], 1), jnp.float32)
            return rec, rec_cat, leaf_id, ks, tot

        reps = (P(),) * 7
        return shard_map(local, mesh=self.mesh, in_specs=reps,
                         out_specs=(P(), P(), P(), P(), P()),
                         check_vma=False)

    def _run_grow(self, grad, hess, w, base_mask, key):
        if self._tree_fn is None:
            self._tree_fn = jax.jit(self._sharded_tree_fn())
        rec, rec_cat, leaf_id, k, tot = self._tree_fn(
            self.codes_pack, self.codes_row, grad, hess, w, base_mask, key)
        return (rec, rec_cat if self._has_cat else None, leaf_id, k, tot)

    def make_fused_step(self, objective, goss=None, bagging=True):
        """Fused boosting iteration over the feature mesh: one sharded
        whole-tree program per iteration (rows replicated, columns
        sliced), same contract as DeviceTreeLearner.make_fused_step.

        goss = (top_k, other_k, multiply): rows are REPLICATED on every
        shard, so GOSS is the single-chip in-program sampling verbatim
        (global exact top_k by |g*h| + uniform rest + amplification,
        reference src/boosting/goss.hpp) — computed once in the outer
        jit and handed to the shard_map replicated."""
        from ..models.device_learner import leaf_values_from_rec
        cfg = self.config
        n = self.dataset.num_data
        L = int(cfg.num_leaves)
        if goss is not None:
            top_k, other_k, multiply = goss
            bag_on = False
        else:
            bag_on = (bagging and cfg.bagging_freq > 0
                      and cfg.bagging_fraction < 1.0)
            bag_k = max(1, int(n * cfg.bagging_fraction))
        fn = self._sharded_tree_fn()

        has_cat = self._has_cat

        obj_keys = objective_buffer_names(objective)

        # `step_impl` is a name the benchmark reads (it finds the tree
        # program in a device trace by the module `jit_step_impl`; see
        # the serial make_fused_step)
        @jax.jit
        def step_impl(codes_pack, codes_row, obj_bufs, score_row,
                      base_mask, tree_key, bag_key, shrinkage):
            # codes + objective buffers as args, not closure constants —
            # see the serial make_fused_step note (compile payload)
            with swapped_attrs(objective, obj_keys, obj_bufs), \
                    jax.named_scope("lgbm.gradients"):
                g, h = objective.get_gradients(score_row)
            if goss is not None:
                from ..models.device_learner import goss_sample
                g, h, w, _, _ = goss_sample(
                    g, h, bag_key, n, top_k, other_k, multiply)
            elif bag_on:
                from ..models.device_learner import exact_k_bag_weights
                w = exact_k_bag_weights(bag_key, n, bag_k)
            else:
                w = jnp.ones((n,), jnp.float32)
            rec, rec_cat, leaf_id, k, _ = fn(codes_pack, codes_row,
                                             g, h, w, base_mask, tree_key)
            with jax.named_scope("lgbm.score_update"):
                lv = leaf_values_from_rec(rec, k, L)
                delta = jnp.take(lv, jnp.clip(leaf_id, 0, L - 1)) \
                    * shrinkage
                new_score = score_row + delta
                # in-program sentry reduction (see the serial step
                # contract)
                finite = jnp.all(jnp.isfinite(new_score))
            return (new_score, rec, rec_cat if has_cat else None,
                    leaf_id, k, finite)

        def make_args(*step_args):
            obj_bufs = tuple(getattr(objective, k) for k in obj_keys)
            return (self.codes_pack, self.codes_row, obj_bufs, *step_args)

        return fused_step_surface(step_impl, make_args, obj_keys)


def create_tree_learner(config: Config, dataset: Dataset,
                        mesh: Optional[Mesh] = None):
    """Factory: {serial, feature, data, voting} (reference:
    src/treelearner/tree_learner.cpp:13-36 CreateTreeLearner). Each mode
    prefers its whole-tree-on-device variant (the reference composes device
    x parallelism the same way, tree_learner.cpp:24-33 GPU templates) and
    falls back to the host-loop learner for unsupported configs."""
    import os
    from ..models.device_learner import DeviceTreeLearner
    from ..utils.log import LightGBMError
    host_only = os.environ.get("LGBM_TPU_HOST_LEARNER", "0") == "1"
    name = config.tree_learner
    stream = str(getattr(config, "stream_mode", "off") or "off")
    rows_sharded = getattr(dataset, "row_shard", None) is not None
    stream_matrix = (
        "supported combinations: stream_mode=chunked|goss with "
        "tree_learner=serial (any quant_bits), and stream_mode=chunked "
        "with tree_learner=data (float path, quant_bits=0)")
    if rows_sharded and name not in ("data", "data_parallel"):
        raise LightGBMError(
            "this dataset is row-sharded (dist_shard_mode=rows): each "
            "host holds only its own row block, which only tree_learner"
            "=data can train on (per-leaf histograms are the cross-host "
            f"exchange); tree_learner={name} would silently train on a "
            "fraction of the data — use tree_learner=data or "
            "dist_shard_mode=replicated")
    if rows_sharded and host_only:
        raise LightGBMError(
            "dist_shard_mode=rows is incompatible with "
            "LGBM_TPU_HOST_LEARNER=1: the host-loop data-parallel "
            "learner needs the full binned matrix on every rank")
    if stream != "off":
        # streaming exists in the serial device chunk learner and (for
        # the float chunked mode) the device data-parallel learner; a
        # silent fallback to a resident learner would defeat the whole
        # point of the mode, so misconfigurations fail loudly
        if name in ("data", "data_parallel"):
            if stream != "chunked":
                raise LightGBMError(
                    f"stream_mode={stream} with tree_learner={name} is "
                    "not supported: the GOSS working-set compaction is "
                    "a single-program optimisation with no sharded "
                    f"counterpart; {stream_matrix}")
            if config.quant_bits:
                raise LightGBMError(
                    f"quant_bits={config.quant_bits} with stream_mode="
                    f"{stream} and tree_learner={name} is not "
                    "supported: the streamed assembly derives "
                    "quantization scales from local gradient maxima "
                    "while the distributed resident core psums them "
                    "globally, so the two would grow different trees; "
                    f"set quant_bits=0 or stream_mode=off; "
                    f"{stream_matrix}")
            if host_only:
                raise LightGBMError(
                    f"stream_mode={stream} is incompatible with "
                    "LGBM_TPU_HOST_LEARNER=1 (the host-loop learners "
                    "have no streaming path)")
            if not DeviceTreeLearner.supports(config, dataset,
                                              strategy="chunk"):
                raise LightGBMError(
                    f"stream_mode={stream} with tree_learner={name} "
                    "needs the device chunk learner but this config is "
                    "unsupported by it (forced splits / CEGB / pool "
                    "budget); fix the config or set stream_mode=off")
            return DeviceDataParallelTreeLearner(config, dataset, mesh)
        if name not in ("serial",):
            raise LightGBMError(
                f"stream_mode={stream} with tree_learner={name} has no "
                "streaming path (the feature/voting learners shard or "
                "elect by feature and need resident codes); "
                f"{stream_matrix}")
        if host_only:
            raise LightGBMError(
                f"stream_mode={stream} is incompatible with "
                "LGBM_TPU_HOST_LEARNER=1 (the host-loop learner has no "
                "streaming path)")
        if not DeviceTreeLearner.supports(config, dataset):
            raise LightGBMError(
                f"stream_mode={stream} needs the device chunk learner "
                "but this config is unsupported by it (forced splits / "
                "CEGB / pool budget); fix the config or set "
                "stream_mode=off")
        return DeviceTreeLearner(config, dataset)

    def host_loop(cls, reason, *args):
        # the one place a run leaves the whole-tree device programs: say
        # so, or a benchmark (and chip_smoke.py) measures the wrong learner
        log.warning("tree_learner=%s trains on the host-loop %s, not the "
                    "whole-tree device program: %s", name, cls.__name__,
                    reason)
        return cls(config, dataset, *args)

    def device_reason(strategy=None, identity_only=False):
        """Why the device learner cannot take this config (None = it
        can). identity_only: the feature/voting device learners need the
        identity feature->column mapping (no EFB bundles), the float row
        layout and no by-node sampling."""
        if host_only:
            return "LGBM_TPU_HOST_LEARNER=1"
        if identity_only:
            if dataset.bundle_arrays() is not None:
                return "the dataset is EFB-bundled"
            if config.quant_bits:
                return "quantized gradients"
            if 0.0 < config.feature_fraction_bynode < 1.0:
                return "feature_fraction_bynode sampling"
        return DeviceTreeLearner.unsupported_reason(config, dataset,
                                                    strategy=strategy)

    if name in ("serial",):
        reason = device_reason()
        if reason is None:
            return DeviceTreeLearner(config, dataset)
        return host_loop(SerialTreeLearner, reason)
    if name in ("feature", "feature_parallel"):
        reason = device_reason("compact", identity_only=True)
        if reason is None:
            return DeviceFeatureParallelTreeLearner(config, dataset, mesh)
        return host_loop(FeatureParallelTreeLearner, reason, mesh)
    if name in ("data", "data_parallel"):
        # the DP device learner always runs the compact strategy; check
        # the learner that will actually be built
        reason = device_reason("compact")
        if reason is None:
            return DeviceDataParallelTreeLearner(config, dataset, mesh)
        if rows_sharded:
            raise LightGBMError(
                "dist_shard_mode=rows needs the device data-parallel "
                f"learner, but this config is unsupported by it ({reason})"
                "; fix the config or use dist_shard_mode=replicated")
        return host_loop(DataParallelTreeLearner, reason, mesh)
    if name in ("voting", "voting_parallel"):
        # device PV-Tree also needs a feature count the 2k election
        # actually reduces, and more than one shard
        n_shards = (mesh.devices.size if mesh is not None
                    else len(jax.devices()))
        reason = device_reason("compact", identity_only=True)
        if reason is None and dataset.num_features <= 2 * max(
                1, int(config.top_k)):
            reason = "top_k elects every feature (nothing to reduce)"
        if reason is None and n_shards <= 1:
            reason = "one device (no election to hold)"
        if reason is None:
            return DeviceVotingParallelTreeLearner(config, dataset, mesh)
        return host_loop(VotingParallelTreeLearner, reason, mesh)
    log.fatal("Unknown tree learner %s", name)
