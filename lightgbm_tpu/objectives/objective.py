"""All objective functions as jitted device math.

Each objective mirrors the reference class of the same config name
(reference: src/objective/{regression,binary,multiclass,xentropy,rank}_objective.hpp)
— same gradients/hessians, boost-from-score, output transform and leaf-renewal
semantics, restructured as whole-array jax ops instead of OMP loops.

Scores/gradients for K classes use shape (K, N) (reference uses the same
class-major flattening, multiclass_objective.hpp:88 idx = num_data*k + i).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import log

K_EPSILON = 1e-15


def _to_f32(x):
    return jnp.asarray(x, dtype=jnp.float32)


def _percentile(values: np.ndarray, weights: Optional[np.ndarray],
                alpha: float) -> float:
    """Weighted percentile, reference semantics (regression_objective.hpp:20-76
    PercentileFun/WeightedPercentileFun)."""
    n = len(values)
    if n == 0:
        return 0.0
    if weights is None:
        if n <= 1:
            return float(values[0])
        order = np.argsort(values, kind="stable")
        float_pos = (1.0 - alpha) * n
        pos = int(math.floor(float_pos))
        if pos < 1:
            return float(values[order[0]])
        if pos >= n:
            return float(values[order[n - 1]])
        bias = float_pos - pos
        v1 = float(values[order[pos - 1]])
        v2 = float(values[order[pos]])
        return v1 * (1.0 - bias) + v2 * bias
    order = np.argsort(values, kind="stable")
    w = weights[order]
    v = values[order]
    cum = np.cumsum(w) - 0.5 * w
    threshold = alpha * np.sum(w)
    idx = int(np.searchsorted(cum, threshold, side="left"))
    idx = min(max(idx, 0), n - 1)
    if idx > 0 and cum[idx] > threshold:
        # interpolate like the reference's weighted percentile
        c1, c2 = cum[idx - 1], cum[idx]
        if c2 > c1:
            t = (threshold - c1) / (c2 - c1)
            return float(v[idx - 1] * (1 - t) + v[idx] * t)
    return float(v[idx])


class Objective:
    """Base objective (reference: include/LightGBM/objective_function.h)."""

    name = "none"

    def __init__(self, config):
        self.config = config
        self.num_class = 1
        self.label: Optional[np.ndarray] = None
        self.weight = None

    # -- lifecycle ------------------------------------------------------
    def init(self, metadata, num_data: int) -> None:
        self.num_data = num_data
        self.label = metadata.label
        self.weight = metadata.weight
        self._label_dev = _to_f32(self.label) if self.label is not None else None
        self._weight_dev = _to_f32(self.weight) if self.weight is not None else None

    # -- core -----------------------------------------------------------
    def get_gradients(self, score: jax.Array):
        raise NotImplementedError

    def device_buffer_names(self):
        """Attribute names of the device buffers get_gradients reads.
        The fused training step passes these as jit ARGUMENTS (via a
        trace-time attribute swap) so they lower as parameters instead
        of per-dataset HLO constants — see device_learner
        objective_buffer_names. Default: every nontrivial device array
        attribute (covers label/weight/transformed-label vectors AND
        shaped buffers like lambdarank's (Q, L) segment tensors)."""
        return sorted(
            k for k, v in vars(self).items()
            if isinstance(v, jax.Array) and v.ndim >= 1 and v.size >= 256)

    def boost_from_score(self, class_id: int) -> float:
        return 0.0

    def convert_output(self, scores: jax.Array) -> jax.Array:
        return scores

    @property
    def is_constant_hessian(self) -> bool:
        return False

    @property
    def num_model_per_iteration(self) -> int:
        return self.num_class

    @property
    def is_renew_tree_output(self) -> bool:
        return False

    def renew_leaf_output(self, residuals: np.ndarray,
                          weights: Optional[np.ndarray]) -> float:
        raise NotImplementedError

    def class_need_train(self, class_id: int) -> bool:
        return True

    def to_string(self) -> str:
        return self.name

    def _apply_weight(self, grad, hess):
        if self._weight_dev is not None:
            return grad * self._weight_dev, hess * self._weight_dev
        return grad, hess


# ----------------------------------------------------------------------
class RegressionL2(Objective):
    """reference: regression_objective.hpp:78 RegressionL2loss."""
    name = "regression"

    def __init__(self, config):
        super().__init__(config)
        self.sqrt = bool(getattr(config, "reg_sqrt", False))

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.sqrt:
            lbl = np.sign(self.label) * np.sqrt(np.abs(self.label))
            self._label_dev = _to_f32(lbl)
            self._trans_label = lbl
        else:
            self._trans_label = self.label

    def get_gradients(self, score):
        grad = score - self._label_dev
        hess = jnp.ones_like(score)
        return self._apply_weight(grad, hess)

    @property
    def is_constant_hessian(self) -> bool:
        return self.weight is None

    def boost_from_score(self, class_id):
        if self.weight is not None:
            return float(np.sum(self._trans_label * self.weight) / np.sum(self.weight))
        return float(np.mean(self._trans_label))

    def convert_output(self, scores):
        if self.sqrt:
            return jnp.sign(scores) * scores * scores
        return scores

    def to_string(self):
        return f"{self.name} sqrt" if self.sqrt else self.name


class RegressionL1(RegressionL2):
    """reference: regression_objective.hpp:189 RegressionL1loss."""
    name = "regression_l1"

    def get_gradients(self, score):
        diff = score - self._label_dev
        grad = jnp.sign(diff)
        hess = jnp.ones_like(score)
        return self._apply_weight(grad, hess)

    def boost_from_score(self, class_id):
        return _percentile(np.asarray(self.label, dtype=np.float64),
                           self.weight, 0.5)

    @property
    def is_renew_tree_output(self) -> bool:
        return True

    def renew_leaf_output(self, residuals, weights):
        return _percentile(residuals, weights, 0.5)

    @property
    def is_constant_hessian(self) -> bool:
        return self.weight is None


class Huber(RegressionL2):
    """reference: regression_objective.hpp:275 RegressionHuberLoss."""
    name = "huber"

    def __init__(self, config):
        super().__init__(config)
        self.alpha = float(config.alpha)

    def get_gradients(self, score):
        diff = score - self._label_dev
        grad = jnp.where(jnp.abs(diff) <= self.alpha, diff,
                         jnp.sign(diff) * self.alpha)
        hess = jnp.ones_like(score)
        return self._apply_weight(grad, hess)

    @property
    def is_constant_hessian(self) -> bool:
        return self.weight is None


class Fair(RegressionL2):
    """reference: regression_objective.hpp:337 RegressionFairLoss."""
    name = "fair"

    def __init__(self, config):
        super().__init__(config)
        self.c = float(config.fair_c)

    def get_gradients(self, score):
        x = score - self._label_dev
        ax = jnp.abs(x)
        grad = self.c * x / (ax + self.c)
        hess = self.c * self.c / ((ax + self.c) ** 2)
        return self._apply_weight(grad, hess)

    @property
    def is_constant_hessian(self) -> bool:
        return False


class Poisson(RegressionL2):
    """reference: regression_objective.hpp:384 RegressionPoissonLoss (log link)."""
    name = "poisson"

    def __init__(self, config):
        super().__init__(config)
        self.max_delta_step = float(config.poisson_max_delta_step)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.any(self.label < 0):
            log.fatal("[poisson]: at least one target label is negative")

    def get_gradients(self, score):
        grad = jnp.exp(score) - self._label_dev
        hess = jnp.exp(score + self.max_delta_step)
        return self._apply_weight(grad, hess)

    def boost_from_score(self, class_id):
        mean = RegressionL2.boost_from_score(self, class_id)
        return math.log(max(mean, 1e-20))

    def convert_output(self, scores):
        return jnp.exp(scores)

    @property
    def is_constant_hessian(self) -> bool:
        return False


class Quantile(RegressionL2):
    """reference: regression_objective.hpp:464 RegressionQuantileloss."""
    name = "quantile"

    def __init__(self, config):
        super().__init__(config)
        self.alpha = float(config.alpha)

    def get_gradients(self, score):
        delta = score - self._label_dev
        grad = jnp.where(delta >= 0, 1.0 - self.alpha, -self.alpha)
        hess = jnp.ones_like(score)
        return self._apply_weight(grad, hess)

    def boost_from_score(self, class_id):
        return _percentile(np.asarray(self.label, dtype=np.float64),
                           self.weight, self.alpha)

    @property
    def is_renew_tree_output(self) -> bool:
        return True

    def renew_leaf_output(self, residuals, weights):
        return _percentile(residuals, weights, self.alpha)

    @property
    def is_constant_hessian(self) -> bool:
        return self.weight is None


class MAPE(RegressionL1):
    """reference: regression_objective.hpp:562 RegressionMAPELOSS."""
    name = "mape"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        label = np.asarray(self.label, dtype=np.float64)
        w = 1.0 / np.maximum(1.0, np.abs(label))
        if self.weight is not None:
            w = w * self.weight
        self._mape_w = w
        self._mape_w_dev = _to_f32(w)

    def get_gradients(self, score):
        diff = score - self._label_dev
        grad = jnp.sign(diff) * self._mape_w_dev
        hess = self._mape_w_dev
        return grad, hess

    def boost_from_score(self, class_id):
        return _percentile(np.asarray(self.label, dtype=np.float64),
                           self._mape_w, 0.5)

    def renew_leaf_output(self, residuals, weights):
        # weights here are the MAPE weights gathered per-leaf by the caller
        return _percentile(residuals, weights, 0.5)

    @property
    def leaf_renew_weight(self):
        return self._mape_w

    @property
    def is_constant_hessian(self) -> bool:
        return False


class Gamma(Poisson):
    """reference: regression_objective.hpp:661 RegressionGammaLoss."""
    name = "gamma"

    def get_gradients(self, score):
        inv = jnp.exp(-score)
        grad = 1.0 - self._label_dev * inv
        hess = self._label_dev * inv
        return self._apply_weight(grad, hess)


class Tweedie(Poisson):
    """reference: regression_objective.hpp:696 RegressionTweedieLoss."""
    name = "tweedie"

    def __init__(self, config):
        super().__init__(config)
        self.rho = float(config.tweedie_variance_power)

    def get_gradients(self, score):
        e1 = jnp.exp((1.0 - self.rho) * score)
        e2 = jnp.exp((2.0 - self.rho) * score)
        grad = -self._label_dev * e1 + e2
        hess = (-self._label_dev * (1.0 - self.rho) * e1
                + (2.0 - self.rho) * e2)
        return self._apply_weight(grad, hess)


# ----------------------------------------------------------------------
class BinaryLogloss(Objective):
    """reference: binary_objective.hpp:21 BinaryLogloss."""
    name = "binary"

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        if self.sigmoid <= 0:
            log.fatal("Sigmoid parameter %f should be greater than zero",
                      self.sigmoid)
        self.is_unbalance = bool(config.is_unbalance)
        self.scale_pos_weight = float(config.scale_pos_weight)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        is_pos = self.label > 0
        cnt_pos = int(np.sum(is_pos))
        cnt_neg = num_data - cnt_pos
        self.need_train = cnt_pos > 0 and cnt_neg > 0
        if not self.need_train:
            log.warning("Contains only one class")
        w_neg, w_pos = 1.0, 1.0
        if self.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
        w_pos *= self.scale_pos_weight
        self._signed_label = _to_f32(np.where(is_pos, 1.0, -1.0))
        self._label_weight = _to_f32(np.where(is_pos, w_pos, w_neg))
        self._pavg = (np.sum(self.weight[is_pos]) / np.sum(self.weight)
                      if self.weight is not None
                      else cnt_pos / max(1, num_data))

    def get_gradients(self, score):
        lbl = self._signed_label
        response = -lbl * self.sigmoid / (1.0 + jnp.exp(lbl * self.sigmoid * score))
        abs_r = jnp.abs(response)
        grad = response * self._label_weight
        hess = abs_r * (self.sigmoid - abs_r) * self._label_weight
        return self._apply_weight(grad, hess)

    def boost_from_score(self, class_id):
        pavg = min(max(self._pavg, K_EPSILON), 1.0 - K_EPSILON)
        return math.log(pavg / (1.0 - pavg)) / self.sigmoid

    def convert_output(self, scores):
        return 1.0 / (1.0 + jnp.exp(-self.sigmoid * scores))

    def class_need_train(self, class_id):
        return self.need_train

    def to_string(self):
        return f"{self.name} sigmoid:{self.sigmoid:g}"


class CrossEntropy(Objective):
    """reference: xentropy_objective.hpp:44 CrossEntropy."""
    name = "cross_entropy"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.any((self.label < 0) | (self.label > 1)):
            log.fatal("[%s]: label must be in [0, 1]", self.name)

    def get_gradients(self, score):
        z = 1.0 / (1.0 + jnp.exp(-score))
        grad = z - self._label_dev
        hess = z * (1.0 - z)
        return self._apply_weight(grad, hess)

    def boost_from_score(self, class_id):
        if self.weight is not None:
            pavg = float(np.sum(self.label * self.weight) / np.sum(self.weight))
        else:
            pavg = float(np.mean(self.label))
        pavg = min(max(pavg, K_EPSILON), 1.0 - K_EPSILON)
        return math.log(pavg / (1.0 - pavg))

    def convert_output(self, scores):
        return 1.0 / (1.0 + jnp.exp(-scores))


class CrossEntropyLambda(Objective):
    """reference: xentropy_objective.hpp:148 CrossEntropyLambda."""
    name = "cross_entropy_lambda"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.any((self.label < 0) | (self.label > 1)):
            log.fatal("[%s]: label must be in [0, 1]", self.name)

    def get_gradients(self, score):
        if self._weight_dev is None:
            z = 1.0 / (1.0 + jnp.exp(-score))
            return z - self._label_dev, z * (1.0 - z)
        w = self._weight_dev
        y = self._label_dev
        epf = jnp.exp(score)
        hhat = jnp.log1p(epf)
        z = 1.0 - jnp.exp(-w * hhat)
        enf = 1.0 / epf
        grad = (1.0 - y / z) * w / (1.0 + enf)
        c = 1.0 / (1.0 - z)
        d = 1.0 + epf
        a = w * epf / (d * d)
        d2 = c - 1.0
        b = (c / (d2 * d2)) * (1.0 + w * epf - c)
        hess = a * (1.0 + y * b)
        return grad, hess

    def boost_from_score(self, class_id):
        if self.weight is not None:
            havg = float(np.sum(self.label * self.weight) / np.sum(self.weight))
        else:
            havg = float(np.mean(self.label))
        return math.log(max(math.exp(havg) - 1.0, K_EPSILON))

    def convert_output(self, scores):
        return jnp.log1p(jnp.exp(scores))


# ----------------------------------------------------------------------
class MulticlassSoftmax(Objective):
    """reference: multiclass_objective.hpp:24 MulticlassSoftmax."""
    name = "multiclass"

    def __init__(self, config):
        super().__init__(config)
        self.num_class = int(config.num_class)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        label_int = self.label.astype(np.int32)
        if np.any((label_int < 0) | (label_int >= self.num_class)):
            log.fatal("Label must be in [0, %d) for multiclass", self.num_class)
        self._label_int = _to_f32(label_int)
        counts = np.bincount(label_int, minlength=self.num_class,
                             weights=self.weight)
        total = counts.sum()
        self._class_probs = counts / max(total, 1e-10)

    def get_gradients(self, score):
        # score: (K, N)
        p = jax.nn.softmax(score, axis=0)
        onehot = (jnp.arange(self.num_class, dtype=jnp.float32)[:, None]
                  == self._label_int[None, :])
        grad = p - onehot
        hess = 2.0 * p * (1.0 - p)
        if self._weight_dev is not None:
            grad = grad * self._weight_dev[None, :]
            hess = hess * self._weight_dev[None, :]
        return grad, hess

    def boost_from_score(self, class_id):
        return math.log(max(K_EPSILON, self._class_probs[class_id]))

    def convert_output(self, scores):
        return jax.nn.softmax(scores, axis=0)

    def class_need_train(self, class_id):
        p = self._class_probs[class_id]
        return K_EPSILON < abs(p) < 1.0 - K_EPSILON

    def to_string(self):
        return f"{self.name} num_class:{self.num_class}"


class MulticlassOVA(Objective):
    """reference: multiclass_objective.hpp:180 MulticlassOVA."""
    name = "multiclassova"

    def __init__(self, config):
        super().__init__(config)
        self.num_class = int(config.num_class)
        self.sigmoid = float(config.sigmoid)
        self._binary = [BinaryLogloss(config) for _ in range(self.num_class)]

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        label_int = self.label.astype(np.int32)
        self._onehot = (np.arange(self.num_class)[:, None]
                        == label_int[None, :]).astype(np.float32)

        class _Meta:
            pass

        for k, b in enumerate(self._binary):
            m = _Meta()
            m.label = self._onehot[k]
            m.weight = self.weight
            b.init(m, num_data)

    def get_gradients(self, score):
        grads, hesses = [], []
        for k, b in enumerate(self._binary):
            g, h = b.get_gradients(score[k])
            grads.append(g)
            hesses.append(h)
        return jnp.stack(grads), jnp.stack(hesses)

    def boost_from_score(self, class_id):
        return self._binary[class_id].boost_from_score(0)

    def convert_output(self, scores):
        return 1.0 / (1.0 + jnp.exp(-self.sigmoid * scores))

    def to_string(self):
        return (f"{self.name} num_class:{self.num_class} "
                f"sigmoid:{self.sigmoid:g}")


# ----------------------------------------------------------------------
# Live pair elements of one slice of a bucket: a slice holds as many whole
# queries as fit under it (at least one), so no tensor the gradient pass
# builds is larger than this many elements, whatever the table. Chosen
# on the chip, where the pass reads the same from 2**21 to 2**24 (the
# compiler builds the planes inside its fusions) and 2**23 holds two
# queries of the longest bucket `msltr` has (PERF.md §6, PR 34); a
# constant, not a parameter.
PAIR_SLICE_ELEMS = 1 << 23
MIN_BUCKET_LEN = 8


def bucket_length(count: int) -> int:
    """Padded length of a query of `count` documents: the power of two
    over it, from MIN_BUCKET_LEN up."""
    return max(MIN_BUCKET_LEN, 1 << (int(count) - 1).bit_length())


class LambdarankNDCG(Objective):
    """LambdaRank with NDCG weighting (reference: rank_objective.hpp:23).

    The equations are the reference's (rank_objective.hpp:83-190), all
    pairs of different labels of every query, with the exact sigmoid in
    the place of its table (accuracy >= table). The layout is the
    chip's:

    * Queries are grouped into buckets by padded length
      (`bucket_length`); a bucket of B queries at length L evaluates
      B x L x L pair positions. Queries that can give no pair (one
      document, or all labels alike) stay out of the pair work and get
      zero gradients. The plan is made once, in `init`, from the query
      boundaries and labels.
    * A bucket's queries go through in slices of a fixed count
      (`lax.map`), so the live pair tensors stay under
      PAIR_SLICE_ELEMS elements at every table size.
    * A query's rows are contiguous, so a bucket reads its scores,
      labels and gains as one slice a query from the per-row vectors and
      holds only (start, count, 1 / max DCG) a query: flat attributes
      `_rank_start_<L>`, `_rank_count_<L>`, `_rank_inv_max_dcg_<L>`,
      every one named by `device_buffer_names` whatever its size, so the
      fused step takes them as jit arguments and two tables with the
      same query lengths compile to one program.
    * No sort: a document's rank is the count of the query's documents
      that beat it (a higher score, or an equal one earlier in the
      query: the stable descending order), one more reduction over the
      pair plane, which also spares the inverse permutation.
    * Each document's lambda is the sum over its partners of a signed
      pair plane (c_ij = lambda_ij where l_i > l_j, -lambda_ji where
      l_i < l_j), so the plane is reduced along one axis only.
    * The write-back to rows is one gather: every row knows its place
      in the buckets' concatenated output (`_rank_row_pos`; rows of
      queries without pairs point at one trailing zero), where lambda
      and hessian lie side by side.
    """
    name = "lambdarank"

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        self.norm = bool(config.lambdamart_norm)
        self.optimize_pos_at = int(config.max_position)
        self.label_gain = np.asarray(config.label_gain, dtype=np.float64)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        qb = metadata.query_boundaries
        if qb is None:
            log.fatal("Lambdarank tasks require query information")
        self.query_boundaries = np.asarray(qb, dtype=np.int64)
        starts = self.query_boundaries[:-1]
        counts = np.diff(self.query_boundaries)
        self.num_queries = len(counts)
        qid = np.repeat(np.arange(self.num_queries), counts)
        gains = self.label_gain[self.label.astype(np.int32)]

        # max DCG at top-k per query (reference DCGCalculator::
        # CalMaxDCGAtK): gains sorted descending inside each query
        order = np.lexsort((-gains, qid))
        place = np.arange(num_data) - starts[qid]
        top = place < self.optimize_pos_at
        max_dcg = np.bincount(
            qid[top], gains[order][top] / np.log2(place[top] + 2.0),
            self.num_queries)
        inv_max_dcg = np.divide(1.0, max_dcg, out=np.zeros_like(max_dcg),
                                where=max_dcg > 0)

        # a query gives a pair only where two of its labels differ
        lo = np.minimum.reduceat(self.label, starts)
        hi = np.maximum.reduceat(self.label, starts)
        can_pair = (counts > 1) & (hi > lo)
        lengths = np.array([bucket_length(c) for c in counts], np.int64)

        self._gain_dev = _to_f32(gains)
        self._buckets = []        # (L, queries a slice, slices)
        slot = np.zeros(self.num_queries, np.int64)
        filled = evaluated = 0
        for L in np.unique(lengths[can_pair]).tolist():
            members = np.nonzero(can_pair & (lengths == L))[0]
            per = min(len(members), max(1, PAIR_SLICE_ELEMS // (L * L)))
            slices = -(-len(members) // per)

            def padded(values, dtype):
                out = np.zeros(slices * per, dtype)
                out[:len(members)] = values[members]
                return jnp.asarray(out)

            setattr(self, f"_rank_start_{L}", padded(starts, np.int32))
            setattr(self, f"_rank_count_{L}", padded(counts, np.int32))
            setattr(self, f"_rank_inv_max_dcg_{L}",
                    padded(inv_max_dcg, np.float32))
            self._buckets.append((L, per, slices))
            # where a query's L outputs start in the buckets'
            # concatenated output
            slot[members] = filled + np.arange(len(members)) * L
            filled += slices * per * L
            evaluated += slices * per * L * L
        # rows of queries without pairs point at the trailing zero
        self._rank_row_pos = jnp.asarray(
            np.where(can_pair[qid], slot[qid] + place, filled), jnp.int32)
        self.max_bucket_len = max((b[0] for b in self._buckets), default=0)

        from ..telemetry import counters
        counters.set_gauge("rank_queries", self.num_queries)
        counters.set_gauge("rank_buckets", len(self._buckets))
        counters.set_gauge("rank_pair_positions_real",
                           int(np.sum(counts[can_pair] ** 2)))
        counters.set_gauge("rank_pair_positions_evaluated", evaluated)
        self._grad_fn = jax.jit(self._gradients_impl)

    def device_buffer_names(self):
        """Every buffer `_gradients_impl` reads, whatever its size: a
        bucket of a few queries is a jit argument like the rest."""
        names = ["_label_dev", "_gain_dev", "_rank_row_pos"]
        if self._weight_dev is not None:
            names.append("_weight_dev")
        for L, _, _ in self._buckets:
            names += [f"_rank_start_{L}", f"_rank_count_{L}",
                      f"_rank_inv_max_dcg_{L}"]
        return sorted(names)

    def _slice_gradients(self, L, score, label, gain, start, count, inv):
        """(S, L, 2): lambda and hessian of the S queries of one slice.
        `start`, `count`, `inv` are (S,); the row vectors are padded by
        the longest bucket so a slice of L never runs off their end.
        In a pair plane (S, L, L) the document whose lambda is summed
        runs along the last axis and its partner along the middle one,
        which the sums run over."""
        def rows_of(vec):
            return jax.vmap(
                lambda s: jax.lax.dynamic_slice(vec, (s,), (L,)))(start)

        def mine(v):
            return v[:, None, :]

        def theirs(v):
            return v[:, :, None]

        at = jnp.arange(L, dtype=jnp.int32)
        valid = at[None, :] < count[:, None]                      # (S, L)
        s = rows_of(score)
        lbl = jnp.where(valid, rows_of(label), -1.0)
        g = rows_of(gain)
        place = at[None, :]

        # rank = how many documents of the query beat this one: the
        # place in the stable descending sort, with no sort
        beats = theirs(valid) & (
            (theirs(s) > mine(s))
            | ((theirs(s) == mine(s)) & (theirs(place) < mine(place))))
        rank = jnp.sum(beats, axis=1, dtype=jnp.int32)
        disc = jnp.where(valid, 1.0 / jnp.log2(2.0 + rank.astype(jnp.float32)),
                         0.0)

        delta_s = mine(s) - theirs(s)
        higher = mine(lbl) > theirs(lbl)
        paired = mine(valid) & theirs(valid) & (mine(lbl) != theirs(lbl))
        delta_ndcg = (jnp.abs(mine(g) - theirs(g))
                      * jnp.abs(mine(disc) - theirs(disc))
                      * inv[:, None, None])
        if self.norm:
            best = jnp.max(jnp.where(valid, s, -jnp.inf), axis=1)
            worst = jnp.min(jnp.where(valid, s, jnp.inf), axis=1)
            delta_ndcg = jnp.where(
                (best != worst)[:, None, None],
                delta_ndcg / (0.01 + jnp.abs(delta_s)), delta_ndcg)
        # a document's share of its pair with a partner: lambda_ij where
        # it holds the higher label, -lambda_ji where the lower
        toward = jnp.where(higher, delta_s, -delta_s)
        p = 1.0 / (1.0 + jnp.exp(self.sigmoid * toward))  # GetSigmoid
        pull = jnp.where(paired, self.sigmoid * delta_ndcg * p, 0.0)
        lam = jnp.sum(jnp.where(higher, -pull, pull), axis=1)
        hes = jnp.sum(pull * (self.sigmoid * (1.0 - p)), axis=1)
        if self.norm:
            # -2 x the sum of lambda_ij over the query's pairs: every
            # pair stands twice in the plane, once from each side
            sum_lambdas = jnp.sum(pull, axis=(1, 2))
            factor = jnp.where(
                sum_lambdas > 0,
                jnp.log2(1.0 + sum_lambdas) / jnp.maximum(sum_lambdas, 1e-20),
                1.0)
            lam = lam * factor[:, None]
            hes = hes * factor[:, None]
        return jnp.stack((lam, hes), axis=2)

    def _gradients_impl(self, score):
        tail = jnp.zeros((self.max_bucket_len,), jnp.float32)
        score_p, label_p, gain_p = (
            jnp.concatenate((v, tail))
            for v in (score, self._label_dev, self._gain_dev))
        parts = []
        for L, per, slices in self._buckets:
            with jax.named_scope(f"rank_bucket_{L}"):
                start, count, inv = (
                    getattr(self, f"_rank_{k}_{L}").reshape(slices, per)
                    for k in ("start", "count", "inv_max_dcg"))

                def one(xs, L=L):
                    return self._slice_gradients(
                        L, score_p, label_p, gain_p, *xs)

                if slices == 1:
                    out = one((start[0], count[0], inv[0]))
                else:
                    out = jax.lax.map(one, (start, count, inv))
                parts.append(out.reshape(-1, 2))
        # lambda and hessian side by side, so that a row's two numbers
        # come with one gathered index (5.7 ns a row on the chip for
        # 30.3 as two gathers and 15.9 as a tiled scatter; PR 34)
        parts.append(jnp.zeros((1, 2), jnp.float32))
        both = jnp.take(jnp.concatenate(parts), self._rank_row_pos, axis=0)
        return self._apply_weight(both[:, 0], both[:, 1])

    def get_gradients(self, score):
        import jax.core as _core
        if isinstance(score, _core.Tracer):
            # already under a jit trace (the fused step): call the impl
            # directly so the swapped buffer tracers flow through —
            # dispatching into the cached inner jit would splice its
            # previously-traced jaxpr with the buffers as constants
            return self._gradients_impl(score)
        return self._grad_fn(score)


# ----------------------------------------------------------------------
class NoneObjective(Objective):
    """objective=none: gradients supplied externally (custom fobj)."""
    name = "custom"

    def get_gradients(self, score):
        log.fatal("objective=none requires externally-supplied gradients")


_CLASSES = {
    "regression": RegressionL2,
    "regression_l1": RegressionL1,
    "huber": Huber,
    "fair": Fair,
    "poisson": Poisson,
    "quantile": Quantile,
    "mape": MAPE,
    "gamma": Gamma,
    "tweedie": Tweedie,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "cross_entropy": CrossEntropy,
    "cross_entropy_lambda": CrossEntropyLambda,
    "lambdarank": LambdarankNDCG,
}

OBJECTIVE_NAMES = sorted(_CLASSES)


def create_objective(name: str, config) -> Optional[Objective]:
    """Factory (reference: objective_function.cpp:15-50); None for custom."""
    name = str(name).lower()
    if name in ("none", "null", "custom", "na"):
        return None
    cls = _CLASSES.get(name)
    if cls is None:
        log.fatal("Unknown objective type name: %s", name)
    obj = cls(config)
    return obj


def parse_objective_from_model(text: str, config) -> Optional[Objective]:
    """Recreate an objective from its model-file string, e.g.
    'binary sigmoid:1' or 'multiclass num_class:3'."""
    parts = text.strip().split()
    if not parts:
        return None
    name = parts[0]
    for tok in parts[1:]:
        if ":" in tok:
            k, v = tok.split(":", 1)
            if k == "num_class":
                config.num_class = int(v)
            elif k == "sigmoid":
                config.sigmoid = float(v)
    return create_objective(name, config)
