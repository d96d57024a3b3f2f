"""GBDT boosting orchestrator + DART / GOSS / RF variants.

Equivalent of the reference boosting layer (reference: src/boosting/gbdt.cpp,
dart.hpp, goss.hpp, rf.hpp, gbdt_model_text.cpp). The per-iteration flow
mirrors GBDT::TrainOneIter (gbdt.cpp:368-451): boost-from-average on the
first iteration, objective gradients, bagging, one tree per class, leaf
renewal, shrinkage, score update, metric eval.

TPU mapping: scores and gradients live on device as (K, N) f32; gradient
computation is one fused jitted op; score updates run the vectorized binned
traversal (ops/predict.py); only the tiny tree structures and split decisions
ride on host.
"""
from __future__ import annotations

import math
import threading
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .. import telemetry
from ..config import Config
from ..io.dataset import Dataset
from ..metrics import create_metrics
from ..objectives import create_objective
from ..objectives.objective import MAPE
from ..ops import predict as predict_ops
from ..ops import quantize as quantize_ops
from ..resilience import faults
from ..telemetry import counters as telem_counters
from ..telemetry import recorder as telem
from ..utils import log
from ..utils.envs import flag, pipeline_env
from .serial_learner import SerialTreeLearner
from .tree import Tree

K_EPSILON = 1e-15
MODEL_VERSION = "v3"


def _host_global(arr) -> Optional[np.ndarray]:
    """Host copy of a device array that may span processes. Addressable
    arrays fetch directly; process-spanning ones (row-sharded scores on
    a real multi-host mesh) replicate through a collective — so when a
    process group is active EVERY rank must reach this call in the same
    order (distributed/checkpoint.py runs capture on all ranks)."""
    if arr is None:
        return None
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(jax.device_get(arr))
    from jax.experimental import multihost_utils
    gathered = faults.run_collective(
        lambda: multihost_utils.process_allgather(arr, tiled=True),
        site="host_global")
    return np.asarray(gathered)


def _threshold_l1_np(s: float, l1: float) -> float:
    return math.copysign(max(0.0, abs(s) - l1), s)


def _grad_norm_summary(grad, hess) -> dict:
    """Host L2/max summary of the iteration's gradient pair for the
    flight recorder. Costs one device fetch — callers gate on
    telemetry.events.enabled()."""
    g = np.asarray(jax.device_get(grad), dtype=np.float64)
    h = np.asarray(jax.device_get(hess), dtype=np.float64)
    return {"grad_l2": float(np.linalg.norm(g)),
            "grad_max_abs": float(np.max(np.abs(g))) if g.size else 0.0,
            "hess_l2": float(np.linalg.norm(h))}


class ScoreUpdater:
    """Per-dataset raw scores (reference: src/boosting/score_updater.hpp)."""

    def __init__(self, dataset: Dataset, num_class: int):
        self.dataset = dataset
        n = dataset.num_data
        init = np.zeros((num_class, n), dtype=np.float32)
        self.has_init_score = dataset.metadata.init_score is not None
        if self.has_init_score:
            s = np.asarray(dataset.metadata.init_score, dtype=np.float32)
            if s.size == n * num_class:
                init = s.reshape(num_class, n)
            else:
                init = np.tile(s.reshape(1, n), (num_class, 1))
        self._score = jnp.asarray(init)
        self._host_cache: Optional[np.ndarray] = None
        (self.f_numbins, self.f_missing, self.f_default,
         _, _) = dataset.feature_meta_arrays()

    # `score` is a property so that EVERY mutation — the .at updates
    # below AND the direct assignments from the fused/pipelined paths —
    # invalidates the cached host copy exactly once.
    @property
    def score(self) -> jax.Array:
        return self._score

    @score.setter
    def score(self, value: jax.Array) -> None:
        self._score = value
        self._host_cache = None

    def add_constant(self, val: float, class_id: int) -> None:
        self.score = self.score.at[class_id].add(jnp.float32(val))

    def add_tree(self, tree: Tree, class_id: int) -> None:
        if not getattr(tree, "inner_valid", True):
            # deserialized trees (init_model / BoosterMerge continuation)
            # carry raw thresholds only; reconstruct binned routing first
            tree.rebin_inner(self.dataset)
        vals = predict_ops.predict_binned_tree_values(
            self.dataset.device_binned(), self.f_missing, self.f_default,
            self.f_numbins, tree)
        self.score = self.score.at[class_id].add(vals)

    def add_tree_by_leaf_id(self, tree: Tree, leaf_id, class_id: int) -> None:
        """Score update from the device learner's row->leaf assignment:
        a (N,) gather instead of re-walking the tree (the role of the
        reference's in-bag AddScore(tree_learner) fast path,
        score_updater.hpp:84)."""
        leaf_vals = jnp.asarray(
            np.asarray(tree.leaf_value[:max(tree.num_leaves, 1)],
                       dtype=np.float32))
        self.score = self.score.at[class_id].add(
            jnp.take(leaf_vals, jnp.clip(leaf_id, 0, tree.num_leaves - 1)))

    def multiply(self, factor: float, class_id: int) -> None:
        self.score = self.score.at[class_id].multiply(jnp.float32(factor))

    def host_scores(self) -> np.ndarray:
        """Host f64 copy of the scores, cached per score version: multi-
        metric / multi-valid eval of one iteration fetches the device
        array ONCE instead of a fresh device_get + f64 convert per
        metric. Routed through `_host_global` because a multi-process
        data-parallel run row-shards the score across hosts — the gather
        is a collective there, so every rank evaluates metrics in the
        same order (they already do: eval runs lock-step per iteration).
        Callers treat the returned array as read-only."""
        if self._host_cache is None:
            self._host_cache = np.asarray(
                _host_global(self._score), dtype=np.float64)
            if telem_counters.is_active():
                telem_counters.incr("transfer_d2h_bytes",
                                    self._score.size * 4)
        return self._host_cache


class GBDT:
    """The boosting engine (reference: src/boosting/gbdt.cpp GBDT)."""

    average_output = False

    def __init__(self, config: Config, train_set: Optional[Dataset],
                 objective=None):
        self.config = config
        self.train_set = train_set
        # fused-iteration pipelining (round 5): the most recent fused
        # iteration's split records may still be in flight on device;
        # `models` materializes them on read (see the property below).
        # The lock keeps concurrent READERS (the C ABI's thread-safety
        # contract: prediction may run concurrently with anything) from
        # double-materializing one stash; mutation calls themselves are
        # serialized by the caller, as in the reference.
        self._pending_fused = None
        self._pend_lock = threading.Lock()
        self._pipeline = pipeline_env()
        self._models: List[Tree] = []
        self.iter = 0
        self.num_init_iteration = 0
        self.shrinkage_rate = config.learning_rate
        self.objective = objective
        self.valid_sets: List[Dataset] = []
        self.valid_names: List[str] = []
        self.valid_updaters: List[ScoreUpdater] = []
        self.valid_metrics: List[List] = []
        self.train_metrics: List = []
        self.best_iteration = 0
        self.label_idx = 0
        self.loaded_parameter = ""
        self._sentry_retrying = False
        self._ev_grad_norms = None
        # tensorized-ensemble cache: trees_to_arrays is O(T*M) host work
        # plus a device upload, and back-to-back predicts on a static
        # model were re-paying it every call. Keyed on a model
        # fingerprint (length + last-tree identity + an explicit
        # generation for in-place leaf edits), so growth, rollback and
        # refit all invalidate. The serving registry warms through the
        # same cache.
        self._ensemble_cache: Dict = {}
        self._ensemble_gen = 0

        if train_set is not None:
            self._init_train(train_set)

    # ------------------------------------------------------------------
    @property
    def models(self) -> List[Tree]:
        """The host-side tree list. With fused-iteration pipelining the
        newest tree's split records may still be on device; any read
        materializes them first, so every consumer (predict, save,
        rollback, cv, plotting, the C API) sees a consistent model."""
        if self._pending_fused is not None:
            self._materialize_pending()
        return self._models

    @models.setter
    def models(self, value: List[Tree]) -> None:
        if self._pending_fused is not None:
            self._materialize_pending()
        self._models = value

    def _materialize_pending(self) -> None:
        """Fetch + replay the in-flight fused iteration (if any). If that
        iteration found no split, training should have stopped there:
        rewind iter/score (its score delta was gated to 0 in-program, so
        the restore is a no-op numerically) and run the generic path at
        that iteration so the reference's stop bookkeeping — constant
        boost-from-average tree on a first-iteration stop, warning,
        model trimming — happens even when no further train_one_iter
        call is coming (e.g. the no-split iteration was the last one
        dispatched and the stop is discovered by a save/predict)."""
        with self._pend_lock:
            pend = self._pending_fused
            if pend is None:
                return
            self._pending_fused = None
        if self._materialize_one(pend):
            self.score_updater.score = pend[4]
            self.iter = pend[6]
            self._train_one_iter_generic()

    def _materialize_one(self, pend) -> bool:
        """Replay one stashed fused iteration into a host tree. Returns
        True when the iteration found no split (k == 0)."""
        rec, rec_cat, leaf_id, k_dev, _score_before, init_score, it, \
            shrinkage = pend
        # the blocking fetch of this tree's records; everything after it
        # is host work. Synchronous path: the wait for the tree program.
        # Pipelined path: the program it would wait for ended while the
        # host sat in `mask_sync` (see _train_one_iter_fused), so this
        # reads under a millisecond there
        with telem.phase("record_fetch"):
            if rec_cat is None:
                rec_h, k = jax.device_get((rec, k_dev))
                rec_cat_h = None
            else:
                rec_h, rec_cat_h, k = jax.device_get((rec, rec_cat, k_dev))
        k = int(k)
        if k == 0:
            return True
        with telem.phase("tree_replay"):
            tree = self.learner.replay_tree(rec_h, k, rec_cat_h)
            tree.apply_shrinkage(shrinkage)
            if abs(init_score) > K_EPSILON:
                tree.add_bias(init_score)
        self.learner.last_leaf_id = leaf_id
        self.learner._leaf_id_host = None
        self.learner._bag_mask_host = None
        self._last_leaf_ids[0] = leaf_id
        self._last_leaf_ids_iter = it
        if self.valid_updaters:
            with telem.phase("valid_update"):
                for vu in self.valid_updaters:
                    vu.add_tree(tree, 0)
        self._models.append(tree)
        return False

    def _init_train(self, train_set: Dataset) -> None:
        cfg = self.config
        telemetry.configure(getattr(cfg, "telemetry", "off"),
                            explicit="telemetry" in getattr(cfg, "raw", {}))
        # resolved config rides along in any postmortem bundle (a dict
        # assignment — free when bundling is off)
        telemetry.bundle.set_context(
            "config", {str(k): str(v)
                       for k, v in sorted(getattr(cfg, "raw", {}).items())})
        if self.objective is None and cfg.objective != "none":
            self.objective = create_objective(cfg.objective, cfg)
        if self.objective is not None:
            with telemetry.spans.stage("setup_objective_init_seconds",
                                       "objective_init"):
                self.objective.init(train_set.metadata, train_set.num_data)
            self.num_class = self.objective.num_model_per_iteration
        else:
            self.num_class = max(1, cfg.num_class)
        self.num_tree_per_iteration = self.num_class
        from ..parallel.learners import create_tree_learner
        self.learner = create_tree_learner(cfg, train_set)
        self.score_updater = ScoreUpdater(train_set, self.num_class)
        self.num_data = train_set.num_data
        self.train_metrics = create_metrics(cfg.metric, cfg, cfg.objective)
        for m in self.train_metrics:
            m.init(train_set.metadata, train_set.num_data)
        self._bag_rng = np.random.RandomState(cfg.bagging_seed % (2**31 - 1))
        self._bag_indices: Optional[np.ndarray] = None
        self._last_leaf_ids: Dict[int, Any] = {}
        self._last_leaf_ids_iter = -1
        self._fused_step = None
        self._fused_last = None
        self._class_need_train = [
            self.objective.class_need_train(k) if self.objective else True
            for k in range(self.num_class)]
        self.feature_names = train_set.feature_names
        self.max_feature_idx = train_set.num_total_features - 1

    # ------------------------------------------------------------------
    def add_valid(self, valid_set: Dataset, name: str) -> None:
        self.valid_sets.append(valid_set)
        self.valid_names.append(name)
        vu = ScoreUpdater(valid_set, self.num_class)
        # a valid set added after trees already exist (init_model / merge
        # continuation, or add_valid mid-training) must see their scores
        per = max(self.num_tree_per_iteration, 1)
        for it in range(len(self.models) // per):
            for k in range(per):
                vu.add_tree(self.models[it * per + k], k)
        self.valid_updaters.append(vu)
        metrics = create_metrics(self.config.metric, self.config,
                                 self.config.objective)
        for m in metrics:
            m.init(valid_set.metadata, valid_set.num_data)
        self.valid_metrics.append(metrics)

    # ------------------------------------------------------------------
    def _boost_from_average(self, class_id: int, update_scorer: bool) -> float:
        cfg = self.config
        # _models + pending check (NOT the materializing property): this
        # runs at the top of every iteration, and materializing here
        # would serialize the pipelined fused path
        if (self._models or self._pending_fused is not None
                or self.score_updater.has_init_score
                or self.objective is None):
            return 0.0
        if not (cfg.boost_from_average or self.train_set.num_features == 0):
            if self.objective.name in ("regression_l1", "quantile", "mape"):
                log.warning("Disabling boost_from_average in %s may cause the "
                            "slow convergence", self.objective.name)
            return 0.0
        init_score = self.objective.boost_from_score(class_id)
        if abs(init_score) > K_EPSILON:
            if update_scorer:
                self.score_updater.add_constant(init_score, class_id)
                for vu in self.valid_updaters:
                    vu.add_constant(init_score, class_id)
            log.info("Start training from score %f", init_score)
            return init_score
        return 0.0

    def _compute_gradients(self):
        """objective->GetGradients over the whole score tensor. This is
        the gradient fault-injection boundary (resilience/faults.py):
        an active plan may poison the returned pair, which the sentries
        below must then catch."""
        score = self.score_updater.score
        if self.num_class == 1:
            g, h = self.objective.get_gradients(score[0])
            g, h = g[None, :], h[None, :]
        else:
            g, h = self.objective.get_gradients(score)
        plan = faults.active_plan()
        if plan is not None:
            g, h = plan.inject_gradients(g, h, self.iter)
        return g, h

    # -- non-finite sentries (resilience/sentries.py) -------------------
    def _sentry_enabled(self) -> bool:
        return getattr(self.config, "on_nonfinite", "off") \
            not in ("off", "", "none")

    def _apply_nonfinite_policy(self, what: str) -> str:
        """Host-side policy dispatch once a guard trips. Returns 'skip'
        (drop the iteration) or 'retry' (previous iteration rolled back,
        recompute and go again); policy 'raise' raises."""
        from ..resilience.sentries import NonFiniteError
        pol = self.config.on_nonfinite
        if pol == "raise":
            raise NonFiniteError(
                f"non-finite {what} detected at iteration {self.iter}; "
                "set on_nonfinite=skip_iter/rollback to continue instead")
        # only roll back when a previous iteration remains afterwards:
        # rolling back to an EMPTY model would replay boost-from-average
        # with shifted bias bookkeeping
        if pol == "rollback" and self.iter > 0 \
                and len(self.models) > self.num_tree_per_iteration:
            log.warning("non-finite %s at iteration %d: rolling back one "
                        "iteration", what, self.iter)
            telemetry.events.emit("rollback", iteration=self.iter,
                                  what=what, reason="non_finite")
            self.rollback_one_iter()
            return "retry"
        log.warning("non-finite %s at iteration %d: skipping iteration",
                    what, self.iter)
        telemetry.events.emit("skip_iter", iteration=self.iter, what=what,
                              reason="non_finite")
        return "skip"

    def _guard_gradients(self, grad, hess, recompute=None):
        """One fused isfinite reduction over (grad, hess); returns the
        (possibly recomputed) pair, or None when the iteration should be
        skipped. `recompute` re-derives the pair after a rollback (None
        for custom-fobj gradients, which cannot be recomputed here)."""
        if not self._sentry_enabled():
            return grad, hess
        from ..resilience import sentries
        for _ in range(2):
            if sentries.all_finite(grad, hess):
                return grad, hess
            act = self._apply_nonfinite_policy("gradients/hessians")
            if act != "retry" or recompute is None:
                return None
            grad, hess = recompute()
        raise sentries.NonFiniteError(
            f"non-finite gradients persist at iteration {self.iter} "
            "after rollback")

    def _guard_tree(self, tree) -> bool:
        """Host check over the new tree's leaf outputs. True = usable;
        False = drop the tree (policy skip/rollback); raises on 'raise'."""
        if not self._sentry_enabled() or tree.num_leaves <= 1:
            return True
        vals = np.asarray(tree.leaf_value[:tree.num_leaves],
                          dtype=np.float64)
        if np.isfinite(vals).all():
            return True
        from ..resilience.sentries import NonFiniteError
        if self.config.on_nonfinite == "raise":
            raise NonFiniteError(
                f"non-finite leaf outputs at iteration {self.iter}")
        log.warning("non-finite leaf outputs at iteration %d: dropping "
                    "tree", self.iter)
        return False

    def _bagging(self, iteration: int):
        """Row sampling per iteration (reference gbdt.cpp:210-276)."""
        cfg = self.config
        n = self.num_data
        if cfg.bagging_freq <= 0 or cfg.bagging_fraction >= 1.0:
            if (cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0) \
                    and cfg.bagging_freq > 0:
                pass  # balanced bagging handled below
            else:
                return None
        if iteration % max(cfg.bagging_freq, 1) != 0 and self._bag_indices is not None:
            return self._bag_indices
        if (cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0) \
                and self.objective is not None and self.objective.name == "binary":
            pos = np.nonzero(self.train_set.label > 0)[0]
            neg = np.nonzero(self.train_set.label <= 0)[0]
            kp = max(1, int(len(pos) * cfg.pos_bagging_fraction))
            kn = max(1, int(len(neg) * cfg.neg_bagging_fraction))
            idx = np.concatenate([
                self._bag_rng.choice(pos, kp, replace=False),
                self._bag_rng.choice(neg, kn, replace=False)])
        else:
            k = max(1, int(n * cfg.bagging_fraction))
            idx = self._bag_rng.choice(n, k, replace=False)
        idx = np.sort(idx).astype(np.int32)
        self._bag_indices = idx
        return idx

    # ------------------------------------------------------------------
    def _fused_eligible(self) -> bool:
        """Whether the single-program device iteration applies (plain GBDT,
        single-class jittable objective, device learner, plain bagging)."""
        from .device_learner import DeviceTreeLearner
        if self.__class__ is GOSS and not getattr(
                self.learner, "supports_fused_goss", False):
            # every current device learner carries in-program GOSS; the
            # guard protects future device learners that opt out
            return False
        plan = faults.active_plan()
        if plan is not None and plan.has_gradient_faults:
            # gradient faults inject at the host boundary
            # (_compute_gradients); the fused step computes gradients
            # in-program, so route through the generic path
            return False
        if getattr(self.config, "stream_mode", "off") != "off":
            # streamed assembly is a host-driven H2D loop per iteration;
            # the fused whole-iteration program has no seam for it
            return False
        return (self.__class__ in (GBDT, GOSS)
                and isinstance(self.learner, DeviceTreeLearner)
                and self.objective is not None
                and not self.objective.is_renew_tree_output
                and self.num_class == 1
                and self.num_tree_per_iteration == 1
                and self._class_need_train[0]
                and self.train_set.num_features > 0
                and self.config.pos_bagging_fraction >= 1.0
                and self.config.neg_bagging_fraction >= 1.0)

    def _batched_k_eligible(self) -> bool:
        """Whether this iteration's K per-class trees can grow as one
        vmap-batched device program (DeviceTreeLearner.train_batched).
        Plain multiclass GBDT only — DART/GOSS/RF keep the per-class
        loop — and every class must actually train this iteration.
        LGBM_TPU_NO_VMAP_K is the escape hatch."""
        if (self.__class__ is not GBDT
                or self.num_tree_per_iteration <= 1
                or flag("LGBM_TPU_NO_VMAP_K")):
            return False
        if (self.objective is None
                or self.objective.is_renew_tree_output
                or self.train_set.num_features == 0
                or not all(self._class_need_train)):
            return False
        sup = getattr(self.learner, "supports_batched_k", None)
        return bool(sup and sup())

    def _train_one_iter_fused(self) -> bool:
        """One boosting iteration as one device program + one small fetch
        (see DeviceTreeLearner.make_fused_step)."""
        cfg = self.config
        with telem.phase("boost_avg"):
            init_score = self._boost_from_average(0, True)
        goss_params = self._fused_goss()
        # GOSS replaces bagging outright (goss.hpp overrides Bagging):
        # its warmup step must train on ALL rows even when bagging
        # params are set
        bagging = not self._is_goss()
        if self._fused_step is None:
            self._fused_step = {}
        fkey = goss_params is not None
        if fkey not in self._fused_step:
            with telem.phase("fused_step_build"):
                self._fused_step[fkey] = self.learner.make_fused_step(
                    self.objective, goss=goss_params, bagging=bagging)
        fused_step = self._fused_step[fkey]
        self._fused_last = fkey
        with telem.phase("feature_mask"):     # host work only
            rng = np.random.RandomState(
                (cfg.feature_fraction_seed + self.iter) % (2**31 - 1))
            fmask = self.learner._feature_mask(rng)
        # the mask's device round trip. On the pipelined path this is
        # where the host waits for the device: the compare and the
        # upload queue behind the tree program still running, so the
        # phase lasts about as long as that program (measured on the
        # chip, PERF.md §5) — a blocking phase, not host work
        with telem.phase("mask_sync"):
            if not getattr(self.learner, "cat_in_program", False):
                # learners without in-program categorical splitting (the
                # parallel device learners, and any learner whose table
                # has no categorical feature) must not sample cat
                # features: a compare on the device, read back
                fmask = fmask & np.asarray(self.learner.f_categorical == 0)
            base_mask = jnp.asarray(fmask)       # an H2D a tree
        tree_key = jax.random.PRNGKey(self.iter)
        # same bag key for bagging_freq consecutive iterations == reference
        # re-bags only on iter % freq == 0 and reuses the bag otherwise;
        # GOSS resamples EVERY iteration (goss.hpp has no freq notion)
        freq = 1 if self._fused_goss() else max(cfg.bagging_freq, 1)
        bag_key = jax.random.PRNGKey(
            (cfg.bagging_seed + (self.iter // freq)) % (2**31 - 1))
        score_before = self.score_updater.score
        with telem.phase("grow_dispatch"):
            new_score, rec, rec_cat, leaf_id, k_dev, finite_dev = fused_step(
                score_before[0], base_mask, tree_key, bag_key,
                jnp.float32(self.shrinkage_rate))
        telemetry.note_grow_dispatches(1.0, trees=1.0)

        if self._sentry_enabled():
            # the finite flag is computed INSIDE the fused program (one
            # reduction over the updated score row — any non-finite
            # gradient or leaf output propagates into it), so guarding
            # the iteration adds zero extra dispatches; the bool() here
            # is the policy decision's unavoidable host sync
            with telem.phase("sentry"):
                finite = bool(finite_dev)
            if not finite:
                act = self._apply_nonfinite_policy("fused iteration outputs")
                if act == "retry" and not self._sentry_retrying:
                    self._sentry_retrying = True
                    try:
                        return self._train_one_iter_fused()
                    finally:
                        self._sentry_retrying = False
                self.iter += 1   # skip: nothing committed, nothing stashed
                return False

        pend = (rec, rec_cat, leaf_id, k_dev, score_before, init_score,
                self.iter, self.shrinkage_rate)

        if self._pipeline:
            # Pipelined (TPU default): commit the score immediately (the
            # program gates the delta to 0 when k == 0, so this is safe
            # before k is known), stash the record handles, and replay
            # the PREVIOUS iteration's tree while this program runs on
            # device — hiding the record-fetch round trip and the host
            # replay.
            with telem.phase("score_update"):
                self.score_updater.score = score_before.at[0].set(new_score)
            with self._pend_lock:
                prev = self._pending_fused
                self._pending_fused = pend
            self.iter += 1
            prev_stopped = (prev is not None
                            and self._materialize_one(prev))
            if prev_stopped:
                # the PREVIOUS iteration found no split, so training
                # should already have stopped there. Its score delta was
                # 0, so the in-flight program saw identical gradients
                # and is pure waste: discard it, rewind to the no-split
                # iteration's OWN index (the generic re-run must use its
                # seeds — prev's feature mask found no split; this
                # iteration's fresh mask might), and let the generic
                # path produce the reference's stop bookkeeping
                # (constant init-score tree on a first-iteration stop,
                # warning, model trimming).
                with self._pend_lock:
                    self._pending_fused = None
                self.score_updater.score = prev[4]
                self.iter = prev[6]
                return self._train_one_iter_generic()
            return False

        stopped = self._materialize_one(pend)
        if stopped:
            # delegate the stop bookkeeping (constant init-score tree on a
            # first-iteration stop, warning, model trimming) to the generic
            # path so both paths produce identical final models
            return self._train_one_iter_generic()
        with telem.phase("score_update"):
            self.score_updater.score = score_before.at[0].set(new_score)
        self.iter += 1
        return False

    def fused_stage_map(self) -> dict:
        """{instruction name: (stage, rung)} of the tree program this
        booster's last fused iteration ran (telemetry.stage_map): what a
        profiler trace of the run is read by stage with
        (`benchmark/stage_join.py`). Lowers and compiles the step again
        on arguments made as that iteration made its own, which JAX's
        caches answer with the executable that ran; the compile events
        this fires are kept out of `counters.compile_events()` and
        `compile_seconds()` (`counters.uncounted`). For after a traced
        window, never inside one. {} where no fused iteration has run;
        and, with a warning, where the caches missed (a lowering or a
        backend compile fired: the probe cost a compile, and its
        executable is not the one that ran) or the executable came from
        a persistent compile cache entry older than one of the
        program's stage scopes (its map would read them as unstaged)."""
        if not self._fused_step or self._fused_last not in self._fused_step:
            return {}
        step = self._fused_step[self._fused_last]
        fmask = self.learner._feature_mask(np.random.RandomState(0))
        with telemetry.counters.uncounted() as fired:
            args = (self.score_updater.score[0], jnp.asarray(fmask),
                    jax.random.PRNGKey(0), jax.random.PRNGKey(0),
                    jnp.float32(self.shrinkage_rate))
            missing = step.missing_scopes(*args)
            stage_of = {} if missing else step.stage_map(*args)
        misses = sorted({e for e in fired if "backend_compile" in e
                         or "to_mlir_module" in e})
        if misses:
            log.warning(
                "fused_stage_map: JAX's caches did not hold the tree "
                "program that ran (%s fired, counted nowhere); no map",
                ", ".join(misses))
            return {}
        if missing:
            log.warning(
                "fused_stage_map: the tree program that ran carries no "
                "%s scope (a persistent compile cache entry compiled "
                "before it); no map", ", ".join(sorted(missing)))
        return stage_of

    def _fused_goss(self):
        """GOSS sampling parameters for the fused step; None for plain
        bagging (the GOSS subclass overrides)."""
        return None

    def _is_goss(self) -> bool:
        return False

    # ------------------------------------------------------------------
    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        """One boosting iteration; returns True when training should stop
        (no tree with >1 leaf was produced)."""
        ev_on = telemetry.events.enabled()
        if ev_on:
            coll0 = (telem_counters.get("collective_dispatches"),
                     telem_counters.get("collective_retries"))
            self._ev_grad_norms = None
        with telem.iteration(self.iter):
            if gradients is None and hessians is None \
                    and self._fused_eligible():
                stop = self._train_one_iter_fused()
            else:
                stop = self._train_one_iter_generic(gradients, hessians)
        if ev_on:
            self._emit_iteration_event(stop, coll0)
        return stop

    def _emit_iteration_event(self, stop: bool, coll0) -> None:
        """Assemble this iteration's flight-recorder record: recorder
        phases, grad/hess norms (generic path), quantization plan,
        stream overlap/peaks, and collective deltas. Events-gated — the
        off path never reaches here."""
        rec: Dict[str, Any] = {}
        last = telem.last_iteration()
        if last is not None:
            rec.update(last)
        else:
            rec["iteration"] = self.iter - (0 if stop else 1)
        if stop:
            rec["stop"] = True
        if self._ev_grad_norms is not None:
            rec["grad_norms"] = self._ev_grad_norms
        cfg = self.config
        if getattr(cfg, "quantized_grad", False):
            rec["quant"] = {
                "grad_bits": int(cfg.grad_bits),
                "renew": bool(getattr(cfg, "quant_renew", False)),
                "storage_bits": quantize_ops.storage_bits(
                    int(cfg.grad_bits),
                    bool(getattr(cfg, "quant_renew", False)))}
        shard = getattr(self.learner, "_shard", None)
        if shard is not None:
            overlap = shard.overlap_fraction()
            rec["stream"] = {
                "overlap_fraction": (None if overlap is None
                                     else round(overlap, 4)),
                "peak_bytes": int(getattr(shard, "peak_bytes", 0)),
                "h2d_bytes": int(getattr(shard, "h2d_bytes", 0))}
        d0, r0 = coll0
        dispatches = telem_counters.get("collective_dispatches") - d0
        retries = telem_counters.get("collective_retries") - r0
        if dispatches or retries:
            rec["collectives"] = {"dispatches": int(dispatches),
                                  "retries": int(retries)}
        telemetry.record_iteration(rec)

    def _train_one_iter_generic(self, gradients=None, hessians=None) -> bool:
        init_scores = [0.0] * self.num_tree_per_iteration
        with telem.phase("gradient"):
            if gradients is None or hessians is None:
                for k in range(self.num_tree_per_iteration):
                    init_scores[k] = self._boost_from_average(k, True)
                grad, hess = self._compute_gradients()
            else:
                grad = jnp.asarray(gradients, dtype=jnp.float32).reshape(
                    self.num_tree_per_iteration, self.num_data)
                hess = jnp.asarray(hessians, dtype=jnp.float32).reshape(
                    self.num_tree_per_iteration, self.num_data)

            guarded = self._guard_gradients(
                grad, hess,
                self._compute_gradients if gradients is None else None)
        if guarded is None:
            self.iter += 1   # skipped: seeds keep moving, no tree/score
            return False
        grad, hess = guarded
        if telemetry.events.enabled():
            self._ev_grad_norms = _grad_norm_summary(grad, hess)

        with telem.phase("bagging"):
            bag_indices = self._bagging(self.iter)
        batched_trees = None
        if self._batched_k_eligible():
            # vmap-batched multiclass: all K per-class trees of this
            # iteration grow as ONE batched device program (per-class
            # seeds derived exactly as the per-class loop derives them,
            # so the models are bit-identical)
            batched_trees = self.learner.train_batched(
                grad, hess, bag_indices,
                iter_seed0=self.iter * self.num_tree_per_iteration)
        should_continue = False
        sentry_dropped = False
        for k in range(self.num_tree_per_iteration):
            new_tree = Tree(2)
            if self._class_need_train[k] and self.train_set.num_features > 0:
                if batched_trees is not None:
                    new_tree = batched_trees[k]
                    # _update_score routes by last_leaf_id: install class
                    # k's routing row from the batched program
                    self.learner.last_leaf_id = \
                        self.learner._batched_leaf_ids[k]
                    self.learner._leaf_id_host = None
                else:
                    new_tree = self.learner.train(
                        grad[k], hess[k], bag_indices,
                        iter_seed=self.iter * self.num_tree_per_iteration + k)
                if not self._guard_tree(new_tree):
                    new_tree = Tree(2)
                    sentry_dropped = True
            if new_tree.num_leaves > 1:
                should_continue = True
                if (self.objective is not None
                        and self.objective.is_renew_tree_output):
                    self._renew_tree_output(new_tree, k)
                new_tree.apply_shrinkage(self.shrinkage_rate)
                self._update_score(new_tree, k)
                if abs(init_scores[k]) > K_EPSILON:
                    new_tree.add_bias(init_scores[k])
            else:
                if len(self.models) < self.num_tree_per_iteration:
                    if not self._class_need_train[k] and self.objective is not None:
                        output = self.objective.boost_from_score(k)
                    else:
                        output = init_scores[k]
                    new_tree.as_constant_tree(output)
                    self.score_updater.add_constant(output, k)
                    for vu in self.valid_updaters:
                        vu.add_constant(output, k)
            self.models.append(new_tree)

        if not should_continue:
            if sentry_dropped and \
                    len(self.models) > self.num_tree_per_iteration:
                # every tree of this iteration was dropped by the sentry:
                # treat as a skipped iteration, not end of training
                del self.models[-self.num_tree_per_iteration:]
                self.iter += 1
                return False
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            if len(self.models) > self.num_tree_per_iteration:
                del self.models[-self.num_tree_per_iteration:]
            return True
        self.iter += 1
        return False

    def _update_score(self, tree: Tree, class_id: int) -> None:
        with telem.phase("score_update"):
            self._update_score_inner(tree, class_id)

    def _update_score_inner(self, tree: Tree, class_id: int) -> None:
        leaf_id = getattr(self.learner, "last_leaf_id", None)
        if leaf_id is not None:
            self.score_updater.add_tree_by_leaf_id(tree, leaf_id, class_id)
            # remember the routing so rollback_one_iter subtracts along the
            # exact same path (EFB bundle-conflict rows can route
            # differently under tree traversal than under the partition)
            self._last_leaf_ids[class_id] = leaf_id
            self._last_leaf_ids_iter = self.iter
        else:
            self.score_updater.add_tree(tree, class_id)
            self._last_leaf_ids.pop(class_id, None)
        for vu in self.valid_updaters:
            vu.add_tree(tree, class_id)

    def _renew_tree_output(self, tree: Tree, class_id: int) -> None:
        """Leaf re-fit for L1-family objectives (reference:
        serial_tree_learner.cpp:855-893 RenewTreeOutput)."""
        scores = np.asarray(jax.device_get(
            self.score_updater.score[class_id]), dtype=np.float64)
        label = np.asarray(self.train_set.label, dtype=np.float64)
        if isinstance(self.objective, MAPE):
            weights = self.objective.leaf_renew_weight
        else:
            weights = self.train_set.metadata.weight
        for leaf in range(tree.num_leaves):
            rows = self.learner.leaf_rows(leaf)
            if len(rows) == 0:
                continue
            residuals = label[rows] - scores[rows]
            w = weights[rows] if weights is not None else None
            tree.set_leaf_output(
                leaf, self.objective.renew_leaf_output(residuals, w))

    def rollback_one_iter(self) -> None:
        if self.iter <= 0:
            return
        self.invalidate_ensemble_cache()
        for k in range(self.num_tree_per_iteration):
            tree = self.models[len(self.models) - self.num_tree_per_iteration + k]
            tree.apply_shrinkage(-1.0)
            leaf_id = (self._last_leaf_ids.get(k)
                       if self._last_leaf_ids_iter == self.iter - 1 else None)
            if leaf_id is not None and tree.num_leaves > 1:
                self.score_updater.add_tree_by_leaf_id(tree, leaf_id, k)
            else:
                self.score_updater.add_tree(tree, k)
            for vu in self.valid_updaters:
                vu.add_tree(tree, k)
        self._last_leaf_ids.clear()
        del self.models[-self.num_tree_per_iteration:]
        self.iter -= 1

    # ------------------------------------------------------------------
    def eval_metrics(self) -> Dict[str, List]:
        """(dataset_name, metric_name, value, higher_better) tuples."""
        # valid_updaters receive the pending tree only at materialization
        # (train scores are committed at dispatch, so only the VALID side
        # lags): sync here so per-iteration eval and early stopping see
        # iteration N with N trees, exactly like the synchronous path
        self._materialize_pending()
        out = []
        if self.train_metrics:
            scores = self.score_updater.host_scores()
            s = scores[0] if self.num_class == 1 else scores
            for m in self.train_metrics:
                for name, val in zip(m.names, m.eval(s, self.objective)):
                    out.append(("training", name, val, m.higher_better))
        for vi, (vset, vname, vup) in enumerate(
                zip(self.valid_sets, self.valid_names, self.valid_updaters)):
            scores = vup.host_scores()
            s = scores[0] if self.num_class == 1 else scores
            for m in self.valid_metrics[vi]:
                for name, val in zip(m.names, m.eval(s, self.objective)):
                    out.append((vname, name, val, m.higher_better))
        return out

    # ------------------------------------------------------------------
    def num_trees(self) -> int:
        return len(self.models)

    @property
    def current_iteration(self) -> int:
        return len(self.models) // max(self.num_tree_per_iteration, 1)

    def invalidate_ensemble_cache(self) -> None:
        """Drop cached tensorized ensembles. The cache key already tracks
        tree-list growth/shrinkage; call this for IN-PLACE leaf edits
        (refit, set_leaf_output, DART renormalization) that the
        fingerprint cannot see."""
        self._ensemble_gen += 1
        self._ensemble_cache.clear()

    def ensemble_arrays(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0, bucket: bool = True):
        """Cached (EnsembleArrays, tree_class, n_models) for the model
        slice. Repeated predicts on an unchanged model reuse one
        tensorization + device upload instead of re-running
        trees_to_arrays per call; tree growth changes the fingerprint and
        naturally misses. tree_class is None for bucket=False (leaf-index
        prediction must not pad the tree axis)."""
        models = self._used_models(num_iteration, start_iteration)
        if not models:
            return None, None, 0
        fp = (len(self._models), id(self._models[-1]), self._ensemble_gen)
        key = (fp, start_iteration, len(models), bucket)
        hit = self._ensemble_cache.get(key)
        if hit is None:
            arrays = predict_ops.trees_to_arrays(models, bucket=bucket)
            tc = (predict_ops.padded_tree_class(
                arrays, np.arange(len(models)) % self.num_tree_per_iteration)
                if bucket else None)
            hit = (arrays, tc, len(models))
            if len(self._ensemble_cache) >= 16:   # bound stale slices
                self._ensemble_cache.clear()
            self._ensemble_cache[key] = hit
        return hit

    def predict_raw(self, x: np.ndarray, num_iteration: Optional[int] = None,
                    start_iteration: int = 0) -> np.ndarray:
        """(N, K) raw scores over raw feature values."""
        x = np.ascontiguousarray(np.asarray(x, dtype=np.float32))
        if x.ndim == 1:
            x = x.reshape(1, -1)
        arrays, tc, n_models = self.ensemble_arrays(
            num_iteration, start_iteration, bucket=True)
        if not n_models:
            return np.zeros((x.shape[0], self.num_class))
        out = predict_ops.predict_raw_ensemble(
            jnp.asarray(x), arrays, tc,
            max_depth=arrays.max_depth, num_class=self.num_class)
        out = np.asarray(jax.device_get(out), dtype=np.float64)
        if self.average_output:
            out /= max(1, n_models // self.num_tree_per_iteration)
        return out

    def predict_raw_early_stop(self, x: np.ndarray, num_iteration=None,
                               freq: int = 10, margin: float = 10.0,
                               start_iteration: int = 0) -> np.ndarray:
        """Raw scores with prediction early stopping (reference:
        src/boosting/prediction_early_stop.cpp): every `freq` trees, rows
        whose decision margin exceeds `margin` stop accumulating — binary
        margin = 2|score|, multiclass = top1 - top2."""
        x = np.asarray(x, dtype=np.float32)
        if x.ndim == 1:
            x = x.reshape(1, -1)
        models = self._used_models(num_iteration, start_iteration)
        k = self.num_tree_per_iteration
        n = x.shape[0]
        scores = np.zeros((n, self.num_class))
        active = np.arange(n)
        step = max(1, freq) * k
        for start in range(0, len(models), step):
            if len(active) == 0:
                break
            chunk = models[start:start + step]
            # no bucketing here: x[active] shrinks every round, so the
            # changing row count forces a recompile regardless — padded
            # trees would only add traversal work
            arrays = predict_ops.trees_to_arrays(chunk)
            tree_class = jnp.asarray(
                (np.arange(len(chunk), dtype=np.int32) + start) % k)
            out = predict_ops.predict_raw_ensemble(
                jnp.asarray(x[active]), arrays, tree_class,
                max_depth=arrays.max_depth, num_class=self.num_class)
            scores[active] += np.asarray(jax.device_get(out))
            if self.num_class == 1:
                m = 2.0 * np.abs(scores[active, 0])
            else:
                srt = np.sort(scores[active], axis=1)
                m = srt[:, -1] - srt[:, -2]
            active = active[m <= margin]
        return scores

    def predict(self, x, num_iteration=None, raw_score=False,
                pred_leaf=False, pred_contrib=False, start_iteration=0,
                pred_early_stop=False, pred_early_stop_freq=10,
                pred_early_stop_margin=10.0):
        if pred_leaf:
            arrays, _, _ = self.ensemble_arrays(
                num_iteration, start_iteration, bucket=False)
            x = np.asarray(x, dtype=np.float32)
            if x.ndim == 1:
                x = x.reshape(1, -1)
            leaves = predict_ops.predict_leaf_index_ensemble(
                jnp.asarray(x), arrays, max_depth=arrays.max_depth)
            return np.asarray(jax.device_get(leaves))
        if pred_contrib:
            return self.predict_contrib(x, num_iteration)
        if pred_early_stop:
            raw = self.predict_raw_early_stop(
                x, num_iteration, pred_early_stop_freq,
                pred_early_stop_margin, start_iteration)
        else:
            raw = self.predict_raw(x, num_iteration, start_iteration)
        if raw_score:
            return raw[:, 0] if self.num_class == 1 else raw
        if self.objective is not None:
            converted = self.objective.convert_output(jnp.asarray(raw.T))
            out = np.asarray(jax.device_get(converted)).T
        else:
            out = raw
        return out[:, 0] if self.num_class == 1 else out

    def predict_contrib(self, x, num_iteration=None) -> np.ndarray:
        """TreeSHAP feature contributions (reference: tree.cpp:669-713
        PredictContrib). Host implementation — irregular recursion."""
        from .treeshap import predict_contrib
        return predict_contrib(self, x, num_iteration)

    def _used_models(self, num_iteration, start_iteration=0) -> List[Tree]:
        total_iter = len(self.models) // max(self.num_tree_per_iteration, 1)
        start_iteration = max(0, min(start_iteration, total_iter))
        start = start_iteration * self.num_tree_per_iteration
        if num_iteration is not None and num_iteration > 0:
            end = min((start_iteration + num_iteration)
                      * self.num_tree_per_iteration, len(self.models))
        else:
            end = len(self.models)
        return self.models[start:end]

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        n = self.max_feature_idx + 1
        out = np.zeros(n, dtype=np.float64)
        models = self._used_models(iteration)
        for tree in models:
            for node in range(tree.num_leaves - 1):
                if importance_type == "split":
                    out[tree.split_feature[node]] += 1.0
                else:
                    if tree.split_gain[node] > 0:
                        out[tree.split_feature[node]] += tree.split_gain[node]
        return out

    def refit_leaves(self, leaf_preds: np.ndarray, decay_rate: float) -> None:
        """Refit leaf values on new data keeping structure (reference:
        gbdt.cpp:298-321 RefitTree + FitByExistingTree): new_value =
        decay * old + (1 - decay) * regularized mean-gradient estimate.
        Gradients come from this booster's own objective/score context;
        the leaf update itself runs through `_refit_leaves_apply`."""
        grad, hess = self._compute_gradients()
        self._refit_leaves_apply(leaf_preds, grad, hess, decay_rate)

    def refit_leaves_on(self, dataset: Dataset, leaf_preds: np.ndarray,
                        decay_rate: float) -> None:
        """In-place `task=refit` against NEW data: gradients of the
        objective at its zero-score init over `dataset` — the same
        context the historical rebuild-a-Booster path produced (a fresh
        ScoreUpdater starts at zero), so the leaf values match it bit
        for bit — then one in-place leaf update on THIS model."""
        cfg = self.config
        obj = (create_objective(cfg.objective, cfg)
               if cfg.objective != "none" else None)
        if obj is None:
            raise ValueError("refit requires an objective "
                             "(objective=none has no gradients)")
        obj.init(dataset.metadata, dataset.num_data)
        num_class = obj.num_model_per_iteration
        score = jnp.zeros((num_class, dataset.num_data), dtype=jnp.float32)
        if num_class == 1:
            g, h = obj.get_gradients(score[0])
            g, h = g[None, :], h[None, :]
        else:
            g, h = obj.get_gradients(score)
        self._refit_leaves_apply(leaf_preds, g, h, decay_rate,
                                 num_tree_per_iteration=num_class)

    def _refit_leaves_apply(self, leaf_preds, grad, hess,
                            decay_rate: float,
                            num_tree_per_iteration: Optional[int] = None
                            ) -> None:
        """Shared refit tail: ONE ensemble-cache invalidation, then the
        device segment-sum program (continual/refit.py — one dispatch,
        leaf stats psum'd across ranks when row-sharded) or the
        historical host loop (LGBM_TPU_HOST_REFIT=1, the parity
        reference)."""
        per_iter = (num_tree_per_iteration if num_tree_per_iteration
                    else self.num_tree_per_iteration)
        self.invalidate_ensemble_cache()
        from ..continual import refit as continual_refit
        cfg = self.config
        if continual_refit.device_refit_enabled():
            continual_refit.refit_leaves_device(
                self.models, leaf_preds, grad, hess,
                lambda_l1=cfg.lambda_l1, lambda_l2=cfg.lambda_l2,
                max_delta_step=cfg.max_delta_step, decay_rate=decay_rate,
                shrinkage_rate=self.shrinkage_rate,
                num_tree_per_iteration=per_iter)
            return
        self._refit_leaves_host(leaf_preds, grad, hess, decay_rate,
                                per_iter)

    def _refit_leaves_host(self, leaf_preds, grad, hess,
                           decay_rate: float,
                           num_tree_per_iteration: int) -> None:
        """The original host per-leaf loop, kept as the device path's
        parity reference (tests/test_continual_refit.py)."""
        g = np.asarray(jax.device_get(grad))
        h = np.asarray(jax.device_get(hess))
        cfg = self.config
        for ti, tree in enumerate(self.models):
            k = ti % num_tree_per_iteration
            leaves = leaf_preds[:, ti]
            for leaf in range(tree.num_leaves):
                rows = np.nonzero(leaves == leaf)[0]
                if len(rows) == 0:
                    continue
                sg = float(g[k][rows].sum())
                sh = float(h[k][rows].sum())
                out = -_threshold_l1_np(sg, cfg.lambda_l1) / (sh + cfg.lambda_l2)
                if cfg.max_delta_step > 0:
                    out = float(np.clip(out, -cfg.max_delta_step,
                                        cfg.max_delta_step))
                old = float(tree.leaf_value[leaf])
                tree.set_leaf_output(
                    leaf, decay_rate * old
                    + (1.0 - decay_rate) * out * self.shrinkage_rate)

    # -- serving drift baseline (serving/drift.py) ---------------------
    def drift_baseline(self) -> Optional[Dict[str, Any]]:
        """Training-time drift baseline for serving: per-feature bin
        occupancy over the train set plus the *converted* train-score
        distribution (the same objective transform serving applies by
        default, so served predictions are directly comparable).
        Cached after the first call; None for model-only boosters (no
        train_set to baseline). The model text never changes — the CLI
        writes this to a ``<model>.drift.json`` sidecar."""
        if getattr(self, "train_set", None) is None \
                or getattr(self, "score_updater", None) is None:
            return None
        cached = getattr(self, "_drift_baseline", None)
        if cached is not None:
            return cached
        from ..serving import drift as serve_drift
        raw = _host_global(self.score_updater.score)   # (num_class, n)
        scores = raw
        if raw is not None and self.objective is not None:
            scores = np.asarray(jax.device_get(
                self.objective.convert_output(jnp.asarray(raw))))
        self._drift_baseline = serve_drift.compute_baseline(
            self.train_set, scores=scores)
        return self._drift_baseline

    # -- training-state capture/restore (resilience/checkpoint.py) -----
    def capture_state(self) -> Dict[str, Any]:
        """Live training state beyond the model text: everything a
        resumed run needs to continue bit-identically. Reading `models`
        first materializes any in-flight fused iteration, so the capture
        is a consistent iteration boundary."""
        if getattr(self, "_bag_rng", None) is None:
            log.fatal("checkpointing requires a booster constructed with "
                      "a train_set (model-only boosters have no training "
                      "state; use save_model instead)")
        _ = self.models
        st: Dict[str, Any] = {
            "iter": int(self.iter),
            "shrinkage_rate": float(self.shrinkage_rate),
            "best_iteration": int(self.best_iteration),
            "num_init_iteration": int(self.num_init_iteration),
            "bag_rng": self._bag_rng.get_state(),
            "bag_indices": (None if self._bag_indices is None
                            else np.asarray(self._bag_indices)),
            "train_score": (_host_global(self.score_updater.score)
                            if getattr(self, "score_updater", None)
                            is not None else None),
            "valid_scores": [_host_global(vu.score)
                             for vu in self.valid_updaters],
        }
        if isinstance(self, DART):
            st["dart"] = {"tree_weights": list(self._tree_weights),
                          "sum_weight": float(self._sum_weight),
                          "drop_rng": self._drop_rng.get_state()}
        stream = getattr(self.learner, "stream_state", lambda: None)()
        if stream is not None:
            st["stream"] = stream
        # serving drift baseline rides the checkpoint once computed
        # (cheap: it is a small dict of occupancy vectors) — a restore
        # can hand it straight to the serving registry
        if getattr(self, "_drift_baseline", None) is not None:
            st["drift_baseline"] = self._drift_baseline
        return st

    def restore_state(self, st: Dict[str, Any]) -> None:
        """Inverse of capture_state, applied after the model trees have
        been restored. Scores come back bit-exact from the stored f32
        arrays (NOT replayed through the trees: replay re-associates the
        float adds and the boost-from-average constant, which breaks
        kill-and-resume parity)."""
        if getattr(self, "_bag_rng", None) is None:
            log.fatal("restoring a checkpoint requires a booster "
                      "constructed with a train_set")
        self.iter = int(st["iter"])
        self.shrinkage_rate = float(st["shrinkage_rate"])
        self.best_iteration = int(st["best_iteration"])
        self.num_init_iteration = int(st["num_init_iteration"])
        self._bag_rng.set_state(st["bag_rng"])
        self._bag_indices = (None if st.get("bag_indices") is None
                             else np.asarray(st["bag_indices"],
                                             dtype=np.int32))
        if st.get("train_score") is not None \
                and getattr(self, "score_updater", None) is not None:
            self.score_updater.score = jnp.asarray(
                np.asarray(st["train_score"], dtype=np.float32))
        vs = st.get("valid_scores") or []
        if vs and len(vs) == len(self.valid_updaters):
            for vu, arr in zip(self.valid_updaters, vs):
                vu.score = jnp.asarray(np.asarray(arr, dtype=np.float32))
        elif self.valid_updaters:
            log.warning(
                "checkpoint carries %d valid-set scores, booster has %d "
                "valid sets: rebuilding scores by tree replay", len(vs),
                len(self.valid_updaters))
            per = max(self.num_tree_per_iteration, 1)
            for i, vset in enumerate(self.valid_sets):
                vu = ScoreUpdater(vset, self.num_class)
                for it in range(len(self._models) // per):
                    for k in range(per):
                        vu.add_tree(self._models[it * per + k], k)
                self.valid_updaters[i] = vu
        if "dart" in st and isinstance(self, DART):
            d = st["dart"]
            self._tree_weights = list(d["tree_weights"])
            self._sum_weight = float(d["sum_weight"])
            self._drop_rng.set_state(d["drop_rng"])
        if st.get("stream") is not None and hasattr(
                self.learner, "load_stream_state"):
            self.learner.load_stream_state(st["stream"])
        if isinstance(st.get("drift_baseline"), dict):
            self._drift_baseline = st["drift_baseline"]
        self._last_leaf_ids.clear()
        self._last_leaf_ids_iter = -1
        self.invalidate_ensemble_cache()

    # -- model serialization -------------------------------------------
    def save_model_to_string(self, start_iteration: int = 0,
                             num_iteration: int = -1) -> str:
        """reference: gbdt_model_text.cpp:250 SaveModelToString."""
        lines = ["tree", f"version={MODEL_VERSION}",
                 f"num_class={self.num_class}",
                 f"num_tree_per_iteration={self.num_tree_per_iteration}",
                 f"label_index={self.label_idx}",
                 f"max_feature_idx={self.max_feature_idx}"]
        if self.objective is not None:
            lines.append(f"objective={self.objective.to_string()}")
        if self.average_output:
            lines.append("average_output")
        lines.append("feature_names=" + " ".join(self.feature_names))
        if self.config.monotone_constraints:
            lines.append("monotone_constraints=" + " ".join(
                str(c) for c in self.config.monotone_constraints))
        feature_infos = (self.train_set.feature_infos() if self.train_set
                         else getattr(self, "_feature_infos", []))
        lines.append("feature_infos=" + " ".join(feature_infos))

        models = self._used_models(
            num_iteration if num_iteration > 0 else None, start_iteration)
        tree_strs = []
        for i, tree in enumerate(models):
            s = f"Tree={i}\n" + tree.to_string() + "\n"
            tree_strs.append(s)
        sizes = [len(s) for s in tree_strs]
        lines.append("tree_sizes=" + " ".join(str(s) for s in sizes))
        lines.append("")
        body = "\n".join(lines) + "\n" + "".join(tree_strs)
        body += "end of trees\n"
        imp = self.feature_importance("split")
        pairs = [(int(imp[i]), self.feature_names[i])
                 for i in range(len(imp)) if imp[i] > 0]
        pairs.sort(key=lambda p: -p[0])
        body += "\nfeature importances:\n"
        for v, name in pairs:
            body += f"{name}={v}\n"
        body += "\nparameters:\n" + self.config.to_string() + "\n"
        body += "end of parameters\n"
        return body

    def save_model(self, filename: str, num_iteration: int = -1,
                   start_iteration: int = 0) -> None:
        from ..io.file_io import open_file
        with open_file(filename, "w") as f:
            f.write(self.save_model_to_string(start_iteration, num_iteration))

    @classmethod
    def load_model_from_string(cls, text: str,
                               config: Optional[Config] = None) -> "GBDT":
        """reference: gbdt_model_text.cpp:365 LoadModelFromString."""
        from ..objectives.objective import parse_objective_from_model
        config = config or Config()
        booster = cls(config, None)
        header, _, rest = text.partition("Tree=0")
        kv = {}
        for line in header.splitlines():
            if "=" in line:
                k, _, v = line.partition("=")
                kv[k.strip()] = v.strip()
        booster.num_class = int(kv.get("num_class", 1))
        booster.num_tree_per_iteration = int(kv.get("num_tree_per_iteration", 1))
        booster.label_idx = int(kv.get("label_index", 0))
        booster.max_feature_idx = int(kv.get("max_feature_idx", 0))
        booster.feature_names = kv.get("feature_names", "").split()
        booster._feature_infos = kv.get("feature_infos", "").split()
        booster.average_output = "average_output" in header.split("\n")
        if "objective" in kv:
            config.num_class = booster.num_class
            booster.objective = parse_objective_from_model(kv["objective"], config)
        # parse trees
        tree_blocks = ("Tree=0" + rest).split("end of trees")[0]
        chunks = tree_blocks.split("Tree=")
        for chunk in chunks:
            chunk = chunk.strip()
            if not chunk:
                continue
            body = chunk.split("\n", 1)[1] if "\n" in chunk else ""
            booster.models.append(Tree.from_string(body))
        booster.num_init_iteration = (len(booster.models)
                                      // max(booster.num_tree_per_iteration, 1))
        booster.iter = 0
        return booster

    @classmethod
    def load_model(cls, filename: str,
                   config: Optional[Config] = None) -> "GBDT":
        from ..io.file_io import open_file
        with open_file(filename) as f:
            return cls.load_model_from_string(f.read(), config)

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> dict:
        """reference: gbdt_model_text.cpp:28 DumpModel (JSON)."""
        models = self._used_models(num_iteration, start_iteration)
        return {
            "name": "tree",
            "version": MODEL_VERSION,
            "num_class": self.num_class,
            "num_tree_per_iteration": self.num_tree_per_iteration,
            "label_index": self.label_idx,
            "max_feature_idx": self.max_feature_idx,
            "objective": (self.objective.to_string() if self.objective else ""),
            "average_output": self.average_output,
            "feature_names": list(self.feature_names),
            "feature_importances": {
                self.feature_names[i]: float(v)
                for i, v in enumerate(self.feature_importance("split"))
                if v > 0},
            "tree_info": [
                dict(tree_index=i, **t.to_json()) for i, t in enumerate(models)],
        }


class DART(GBDT):
    """Dropout boosting (reference: src/boosting/dart.hpp)."""

    def __init__(self, config, train_set, objective=None):
        super().__init__(config, train_set, objective)
        self._drop_rng = np.random.RandomState(
            (config.drop_seed) % (2**31 - 1))
        self._tree_weights: List[float] = []
        self._sum_weight = 0.0
        self.shrinkage_rate = config.learning_rate

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        drop_index = self._drop_trees()
        stop = super().train_one_iter(gradients, hessians)
        if not stop:
            self._normalize(drop_index)
        return stop

    def _drop_trees(self) -> List[int]:
        cfg = self.config
        drop_index: List[int] = []
        n_iter = self.iter
        if self._drop_rng.rand() >= cfg.skip_drop and n_iter > 0:
            drop_rate = cfg.drop_rate
            if cfg.uniform_drop:
                if cfg.max_drop > 0:
                    drop_rate = min(drop_rate, cfg.max_drop / max(n_iter, 1))
                for i in range(n_iter):
                    if self._drop_rng.rand() < drop_rate:
                        drop_index.append(self.num_init_iteration + i)
                        if cfg.max_drop > 0 and len(drop_index) >= cfg.max_drop:
                            break
            else:
                inv_avg = len(self._tree_weights) / max(self._sum_weight, 1e-20)
                if cfg.max_drop > 0:
                    drop_rate = min(
                        drop_rate, cfg.max_drop * inv_avg / max(self._sum_weight, 1e-20))
                for i in range(n_iter):
                    if self._drop_rng.rand() < drop_rate * self._tree_weights[i] * inv_avg:
                        drop_index.append(self.num_init_iteration + i)
                        if cfg.max_drop > 0 and len(drop_index) >= cfg.max_drop:
                            break
        # un-apply dropped trees from train scores
        for i in drop_index:
            for k in range(self.num_tree_per_iteration):
                tree = self.models[i * self.num_tree_per_iteration + k]
                tree.apply_shrinkage(-1.0)
                self.score_updater.add_tree(tree, k)
        k_drop = len(drop_index)
        if not cfg.xgboost_dart_mode:
            self.shrinkage_rate = cfg.learning_rate / (1.0 + k_drop)
        else:
            self.shrinkage_rate = (cfg.learning_rate if k_drop == 0 else
                                   cfg.learning_rate / (cfg.learning_rate + k_drop))
        self._drop_index = drop_index
        return drop_index

    def _normalize(self, drop_index: List[int]) -> None:
        cfg = self.config
        self.invalidate_ensemble_cache()
        k = float(len(drop_index))
        for i in drop_index:
            for c in range(self.num_tree_per_iteration):
                tree = self.models[i * self.num_tree_per_iteration + c]
                if not cfg.xgboost_dart_mode:
                    tree.apply_shrinkage(1.0 / (k + 1.0))
                    for vu in self.valid_updaters:
                        vu.add_tree(tree, c)
                    tree.apply_shrinkage(-k)
                    self.score_updater.add_tree(tree, c)
                    tree.apply_shrinkage(-1.0 / k if k else 1.0)
                else:
                    tree.apply_shrinkage(self.shrinkage_rate)
                    for vu in self.valid_updaters:
                        vu.add_tree(tree, c)
                    tree.apply_shrinkage(-(1.0 + k) / k if k else 1.0)
                    self.score_updater.add_tree(tree, c)
                    tree.apply_shrinkage(-k / (1.0 + k))
            if not cfg.uniform_drop and self._tree_weights:
                ti = i - self.num_init_iteration
                self._sum_weight -= self._tree_weights[ti] * (1.0 / (k + 1.0))
                self._tree_weights[ti] *= k / (k + 1.0)
        self._tree_weights.append(self.shrinkage_rate)
        self._sum_weight += self.shrinkage_rate


class GOSS(GBDT):
    """Gradient-based one-side sampling (reference: src/boosting/goss.hpp)."""

    def _goss_sample(self):
        """Top |g*h| rows kept; others sampled with gradient amplification
        (reference goss.hpp:91 BaggingHelper)."""
        cfg = self.config
        grad, hess = self._last_grad_hess
        g = np.abs(np.asarray(jax.device_get(grad)) *
                   np.asarray(jax.device_get(hess))).sum(axis=0)
        n = self.num_data
        top_k, other_k, multiply = self._goss_params()
        order = np.argsort(-g, kind="stable")
        top_idx = order[:top_k]
        rest = order[top_k:]
        sampled = self._bag_rng.choice(
            len(rest), min(other_k, len(rest)), replace=False)
        other_idx = rest[sampled]
        self._goss_amplify = (other_idx, multiply)
        if hasattr(self.learner, "stream_note_top"):
            # streamed working-set policy: the top-|g*h| rows are the
            # ones worth keeping device-resident for the next iteration
            # (goss_working_set caps how many; 0 = the full top set)
            ws_k = int(getattr(self.config, "goss_working_set", 0) or 0)
            ws_k = top_k if ws_k <= 0 else min(ws_k, top_k)
            self.learner.stream_note_top(
                np.sort(top_idx[:ws_k]).astype(np.int32))
        idx = np.sort(np.concatenate([top_idx, other_idx])).astype(np.int32)
        return idx

    def _is_goss(self) -> bool:
        return True

    def _goss_params(self):
        cfg = self.config
        n = self.num_data
        top_k = max(1, int(n * cfg.top_rate))
        other_k = max(1, int(n * cfg.other_rate))
        multiply = (n - top_k) / max(other_k, 1)
        return (top_k, other_k, float(multiply))

    def _fused_goss(self):
        # the reference trains on ALL rows for the first 1/learning_rate
        # iterations before sampling kicks in (goss.hpp:143-144)
        if self.iter < int(1.0 / max(self.config.learning_rate, 1e-12)):
            return None
        return self._goss_params()

    def _train_one_iter_generic(self, gradients=None,
                                hessians=None) -> bool:
        # compute gradients first so GOSS sampling can see them
        init_scores = [0.0] * self.num_tree_per_iteration
        with telem.phase("gradient"):
            if gradients is None or hessians is None:
                for k in range(self.num_tree_per_iteration):
                    init_scores[k] = self._boost_from_average(k, True)
                grad, hess = self._compute_gradients()
            else:
                grad = jnp.asarray(gradients, dtype=jnp.float32).reshape(
                    self.num_tree_per_iteration, self.num_data)
                hess = jnp.asarray(hessians, dtype=jnp.float32).reshape(
                    self.num_tree_per_iteration, self.num_data)
            guarded = self._guard_gradients(
                grad, hess,
                self._compute_gradients if gradients is None else None)
        if guarded is None:
            self.iter += 1
            return False
        grad, hess = guarded
        self._last_grad_hess = (grad, hess)
        if telemetry.events.enabled():
            self._ev_grad_norms = _grad_norm_summary(grad, hess)
        with telem.phase("bagging"):
            if self._fused_goss() is None:
                # reference warmup: no subsampling for the first
                # 1/learning_rate iterations (goss.hpp:143-144)
                bag_indices = None
            else:
                bag_indices = self._goss_sample()
                other_idx, multiply = self._goss_amplify
                amp = jnp.ones(self.num_data, dtype=jnp.float32).at[
                    jnp.asarray(other_idx)].set(float(multiply))
                grad = grad * amp[None, :]
                hess = hess * amp[None, :]

        should_continue = False
        sentry_dropped = False
        for k in range(self.num_tree_per_iteration):
            new_tree = Tree(2)
            if self._class_need_train[k] and self.train_set.num_features > 0:
                new_tree = self.learner.train(
                    grad[k], hess[k], bag_indices,
                    iter_seed=self.iter * self.num_tree_per_iteration + k)
                if not self._guard_tree(new_tree):
                    new_tree = Tree(2)
                    sentry_dropped = True
            if new_tree.num_leaves > 1:
                should_continue = True
                if (self.objective is not None
                        and self.objective.is_renew_tree_output):
                    self._renew_tree_output(new_tree, k)
                new_tree.apply_shrinkage(self.shrinkage_rate)
                self._update_score(new_tree, k)
                if abs(init_scores[k]) > K_EPSILON:
                    new_tree.add_bias(init_scores[k])
            else:
                if len(self.models) < self.num_tree_per_iteration:
                    output = init_scores[k]
                    new_tree.as_constant_tree(output)
                    self.score_updater.add_constant(output, k)
                    for vu in self.valid_updaters:
                        vu.add_constant(output, k)
            self.models.append(new_tree)
        if not should_continue:
            if sentry_dropped and \
                    len(self.models) > self.num_tree_per_iteration:
                del self.models[-self.num_tree_per_iteration:]
                self.iter += 1
                return False
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            if len(self.models) > self.num_tree_per_iteration:
                del self.models[-self.num_tree_per_iteration:]
            return True
        self.iter += 1
        return False


class RF(GBDT):
    """Random forest mode (reference: src/boosting/rf.hpp): bagging
    mandatory, no shrinkage, fixed gradients from the init score, averaged
    output."""

    average_output = True

    def __init__(self, config, train_set, objective=None):
        super().__init__(config, train_set, objective)
        self.shrinkage_rate = 1.0
        # gradients computed once from constant init scores
        init_scores = [self._boost_from_average(k, False)
                       for k in range(self.num_tree_per_iteration)]
        self._rf_init_scores = init_scores
        tmp = jnp.asarray(
            np.tile(np.asarray(init_scores, dtype=np.float32)[:, None],
                    (1, self.num_data)))
        if self.num_class == 1:
            g, h = self.objective.get_gradients(tmp[0])
            self._rf_grad, self._rf_hess = g[None, :], h[None, :]
        else:
            self._rf_grad, self._rf_hess = self.objective.get_gradients(tmp)

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        if self.objective is None:
            log.fatal("RF mode does not support custom objective")
        bag_indices = self._bagging(self.iter)
        grad, hess = self._rf_grad, self._rf_hess
        should_continue = False
        prev_iters = self.iter
        for k in range(self.num_tree_per_iteration):
            new_tree = Tree(2)
            if self._class_need_train[k] and self.train_set.num_features > 0:
                new_tree = self.learner.train(
                    grad[k], hess[k], bag_indices,
                    iter_seed=self.iter * self.num_tree_per_iteration + k)
            if new_tree.num_leaves > 1:
                should_continue = True
                if self.objective.is_renew_tree_output:
                    self._renew_tree_output_rf(new_tree, k)
                # running average: score = (score*t + tree)/(t+1)
                if prev_iters > 0:
                    self.score_updater.multiply(
                        prev_iters / (prev_iters + 1.0), k)
                    for vu in self.valid_updaters:
                        vu.multiply(prev_iters / (prev_iters + 1.0), k)
                new_tree.apply_shrinkage(1.0 / (prev_iters + 1.0))
                self._update_score(new_tree, k)
                new_tree.apply_shrinkage(prev_iters + 1.0)
            self.models.append(new_tree)
        if not should_continue:
            log.warning("Stopped training: no splittable leaves (RF)")
            if len(self.models) > self.num_tree_per_iteration:
                del self.models[-self.num_tree_per_iteration:]
            return True
        self.iter += 1
        return False

    def _renew_tree_output_rf(self, tree, class_id):
        init = self._rf_init_scores[class_id]
        label = np.asarray(self.train_set.label, dtype=np.float64)
        weights = self.train_set.metadata.weight
        for leaf in range(tree.num_leaves):
            rows = self.learner.leaf_rows(leaf)
            if len(rows) == 0:
                continue
            residuals = label[rows] - init
            w = weights[rows] if weights is not None else None
            tree.set_leaf_output(
                leaf, self.objective.renew_leaf_output(residuals, w))


def create_boosting(config: Config, train_set: Optional[Dataset],
                    objective=None) -> GBDT:
    """Factory (reference: src/boosting/boosting.cpp:35 CreateBoosting)."""
    name = config.boosting
    if name in ("gbdt", "gbrt", "plain"):
        return GBDT(config, train_set, objective)
    if name == "dart":
        return DART(config, train_set, objective)
    if name == "goss":
        return GOSS(config, train_set, objective)
    if name in ("rf", "random_forest"):
        return RF(config, train_set, objective)
    log.fatal("Unknown boosting type %s", name)
