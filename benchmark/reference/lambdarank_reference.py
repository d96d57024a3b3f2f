"""Plain reference for the first boosting steps of a LambdaMART ranker
(`objective=lambdarank`).

NumPy on the host, float64, nothing imported from `lightgbm_tpu`. Like
`gbdt_reference` (whose routing, leaf sums and split-gain grid it uses
as they stand) it follows the program's trees: it routes every raw row
by the real-valued thresholds, computes ITS OWN lambdas and hessians,
and works out what each leaf has to hold and what the step has to add.
It takes no bins, codes, gradients, bucket plan or ranks from the
program.

Unlike `gbdt_reference`, `follow` checks each step FROM THE SCORE ROW
THE PROGRAM REPORTED BEFORE IT (zeros before the first), not from a
score row of its own carried forward. A lambda is not a continuous
function of the scores: it goes with the documents' ranks, and two
documents whose scores differ in the last float32 digit change places
between the program's row and a float64 row that is sound to the same
digit; `lambdamart_norm` divides by 0.01 + |ds| besides. Carried
forward, that reads as a leaf off by up to 8.5e-4 of the median leaf
on a sound run (seed 3000034905 on the chip, one of thirteen; 1.15e-4
on the others) where the program's arithmetic on the scores it really
had is within 2.1e-5 (PERF.md section 2). So every step is held to
what its own state asks for: its gradients are taken at the program's
row of the step before, its leaves have to hold what those ask, its
row has to move by the norm of that update, and every query's NDCG@10
after it has to be the one that update gives. The chain starts at
zeros, which nobody reports, so a step that is right from a state that
was checked is right. `emulate`, the walk in the program's place, runs
free from its own scores as a program does.

The gradients are the reference's (LightGBM v2.3.1 `rank_objective.hpp`),
query by query, in blocks of queries of near-equal length (sorted by
length; nothing to do with the program's buckets). For a query with
scores s, labels l, gains g = `label_gain[l]`: r_i = the place of i in
the STABLE descending sort of s (ties keep document order),
d_i = 1 / log2(2 + r_i), Z = 1 / (DCG of the labels sorted descending,
cut at `max_position`; 0 where that DCG is 0). For every ordered pair
with l_i > l_j: ds = s_i - s_j, D = (g_i - g_j) |d_i - d_j| Z, divided
by (0.01 + |ds|) where `lambdamart_norm` and the query's best and worst
scores differ; p = 1 / (1 + exp(sigma ds)); lambda_ij = -sigma D p;
h_ij = sigma^2 D p (1 - p); lambda_i += lambda_ij, lambda_j -=
lambda_ij, h_i += h_ij, h_j += h_ij. With `lambdamart_norm` and
S = -2 x (sum of lambda_ij) > 0 all of the query's lambdas and hessians
are scaled by log2(1 + S) / S. All pairs; the exact sigmoid. A row's
weight (`fields["weight"]`) multiplies its lambda and its hessian.

In the place of a loss it reads NDCG@10 query by query (ties ranked by
document order; a query with no relevant document counts 1, as
LightGBM's metric has it): `ndcg_gap` is the mean over all queries of
the distance between the NDCG@10 of the program's score row and of the
reference's own, over the reference's mean NDCG@10. A query's NDCG
moves only when two of its documents change places, so the distances
are summed by size and not by sign: one query up and one down do not
cancel, and the reading grows with the count of queries whose order
the program's arithmetic changed.

`emulate` is the same walk put in the program's place, optionally with
gradients rounded to bfloat16 (the control), with part of the rows left
out, or with one of FAULTS planted in the gradients.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.reference import gbdt_reference as base
from benchmark.reference.gbdt_reference import Outputs, TREE_KEYS  # noqa: F401

BLOCK_ELEMS = 1 << 20       # pair positions a block of queries evaluates
NDCG_AT = 10
FAULTS = (
    "long_queries_out",      # the longest queries' pairs left out
    "discount_by_position",  # d_i from the document's place, not its rank
    "norm_out",              # lambdamart_norm left out
)


def _check_params(params):
    for key, want in (("objective", "lambdarank"), ("lambda_l1", 0.0),
                      ("max_delta_step", 0.0), ("min_gain_to_split", 0.0)):
        if params.get(key, want) != want:
            raise ValueError(f"reference does not model {key}="
                             f"{params[key]!r}")


def _blocks(counts):
    """Queries of two documents or more, longest first, cut into runs
    whose padded planes stay under BLOCK_ELEMS positions."""
    order = np.argsort(-counts, kind="stable")
    order = order[counts[order] > 1]
    out, a = [], 0
    while a < len(order):
        width = int(counts[order[a]])
        b = a + max(1, BLOCK_ELEMS // (width * width))
        out.append(order[a:b])
        a = b
    return out


def stable_ranks(s):
    """Place of each column in the stable descending sort of its row."""
    order = np.argsort(-s, axis=1, kind="stable")
    rank = np.empty_like(order)
    np.put_along_axis(rank, order,
                      np.broadcast_to(np.arange(s.shape[1]), s.shape), 1)
    return rank


def _block_gradients(s, lbl, gain, valid, inv, sigma, norm, by_position):
    """(lambda, hessian), each (B, c), of a block of B queries padded to
    c documents; `valid` marks the real ones (padded scores are -inf,
    padded labels -1)."""
    rank = np.broadcast_to(np.arange(s.shape[1]), s.shape) if by_position \
        else stable_ranks(s)
    disc = np.where(valid, 1.0 / np.log2(2.0 + rank), 0.0)
    s = np.where(valid, s, 0.0)
    ds = s[:, :, None] - s[:, None, :]
    pair = (lbl[:, :, None] > lbl[:, None, :]) & valid[:, None, :]
    big_d = (gain[:, :, None] - gain[:, None, :]) \
        * np.abs(disc[:, :, None] - disc[:, None, :]) * inv[:, None, None]
    if norm:
        best = np.max(np.where(valid, s, -np.inf), axis=1)
        worst = np.min(np.where(valid, s, np.inf), axis=1)
        spread = (best != worst)[:, None, None]
        big_d = np.where(spread, big_d / (0.01 + np.abs(ds)), big_d)
    with np.errstate(over="ignore"):
        p = 1.0 / (1.0 + np.exp(sigma * ds))
    lam_ij = np.where(pair, -sigma * big_d * p, 0.0)
    h_ij = np.where(pair, sigma * sigma * big_d * p * (1.0 - p), 0.0)
    lam = lam_ij.sum(axis=2) - lam_ij.sum(axis=1)
    hes = h_ij.sum(axis=2) + h_ij.sum(axis=1)
    if norm:
        total = -2.0 * lam_ij.sum(axis=(1, 2))
        factor = np.where(total > 0, np.log2(1.0 + np.maximum(total, 0.0))
                          / np.where(total > 0, total, 1.0), 1.0)
        lam *= factor[:, None]
        hes *= factor[:, None]
    return lam, hes


class Reference(base.Reference):
    """The raw table with its queries, the stated parameters, and the
    reference's own grid of candidate thresholds."""

    def __init__(self, x, y, params, seed, fields=None, with_grid=True):
        _check_params(params)
        group = np.asarray((fields or {})["group"], np.int64)
        weight = (fields or {}).get("weight")
        self.weight = None if weight is None else \
            np.asarray(weight, np.float64)
        self.x = x
        self.y = y.astype(np.float64)
        self.n, self.f = x.shape
        if group.sum() != self.n:
            raise ValueError("group sizes do not sum to the rows")
        self.lr = float(params["learning_rate"])
        self.l2 = float(params.get("lambda_l2", 0.0))
        self.min_data = int(params.get("min_data_in_leaf", 20))
        self.min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
        self.sigma = float(params.get("sigmoid", 1.0))
        self.norm = bool(params.get("lambdamart_norm", True))
        self.max_position = int(params.get("max_position", 20))
        label_gain = params.get("label_gain") or \
            [float((1 << i) - 1) for i in range(31)]
        self.gain = np.asarray(label_gain, np.float64)[self.y.astype(np.int64)]
        self.counts = group
        self.starts = np.concatenate(([0], np.cumsum(group)[:-1]))
        self.qid = np.repeat(np.arange(len(group)), group)
        self.place = np.arange(self.n) - self.starts[self.qid]
        self.max_dcg_at_train = self._max_dcg(self.max_position)
        self.max_dcg_at_metric = self._max_dcg(NDCG_AT)
        self.blocks = _blocks(group)
        self.codes = None
        if with_grid:
            self._make_grid(seed)

    def _max_dcg(self, k):
        """DCG of each query's labels sorted descending, cut at k."""
        order = np.lexsort((-self.gain, self.qid))
        top = self.place < k
        return np.bincount(
            self.qid[top],
            self.gain[order][top] / np.log2(self.place[top] + 2.0),
            len(self.counts))

    # -- the objective -------------------------------------------------
    def gradients(self, score, dtype="float64", fault=None):
        """Lambdas and hessians of float64 scores, one entry a row.
        With `dtype="bfloat16"` each is rounded to bfloat16; `fault`
        plants one of FAULTS."""
        if fault is not None and fault not in FAULTS:
            raise ValueError(fault)
        g, h = np.zeros(self.n), np.zeros(self.n)
        inv_all = np.divide(1.0, self.max_dcg_at_train,
                            out=np.zeros(len(self.counts)),
                            where=self.max_dcg_at_train > 0)
        longest = int(self.counts.max())
        # the queries over the last power of two under the longest one
        long_from = 1 << ((longest - 1).bit_length() - 1)

        def one(queries):
            width = int(self.counts[queries[0]])
            at = np.arange(width)
            valid = at[None, :] < self.counts[queries][:, None]
            rows = np.where(valid, self.starts[queries][:, None] + at, 0)
            inv = inv_all[queries]
            if fault == "long_queries_out":
                inv = np.where(self.counts[queries] > long_from, 0.0, inv)
            lam, hes = _block_gradients(
                np.where(valid, score[rows], -np.inf),
                np.where(valid, self.y[rows], -1.0), self.gain[rows],
                valid, inv, self.sigma,
                self.norm and fault != "norm_out",
                fault == "discount_by_position")
            g[rows[valid]] = lam[valid]
            h[rows[valid]] = hes[valid]

        with ThreadPoolExecutor(base.THREADS) as pool:
            list(pool.map(one, self.blocks))
        if self.weight is not None:
            g, h = g * self.weight, h * self.weight
        if dtype == "bfloat16":
            import ml_dtypes
            g = g.astype(ml_dtypes.bfloat16).astype(np.float64)
            h = h.astype(ml_dtypes.bfloat16).astype(np.float64)
        elif dtype != "float64":
            raise ValueError(dtype)
        return g, h

    def ndcg(self, score):
        """NDCG@10 of every query; ties by document order."""
        order = np.lexsort((np.arange(self.n), -score, self.qid))
        top = self.place < NDCG_AT
        dcg = np.bincount(
            self.qid[top],
            self.gain[order][top] / np.log2(self.place[top] + 2.0),
            len(self.counts))
        best = self.max_dcg_at_metric
        return np.where(best > 0, dcg / np.where(best > 0, best, 1.0), 1.0)

    # -- the walk ------------------------------------------------------
    def walk(self, trees, grad_dtype="float64", rows_used=None,
             with_hist=False, fault=None, before=None):
        """Follow `trees` from a zero score (`lambdarank` boosts from
        none). Per tree: stats (3, L), leaf outputs, every query's
        NDCG@10 after the step, the update's norm, and the split gains
        (chosen, best). With `before` (a score row a tree) each step
        starts from the row given for it; without, the walk runs free,
        in a program's place, and holds its row in float32 as one does,
        so that the next step's ranks are those of the row it reports."""
        score = np.zeros(self.n)
        steps = []
        for i, tree in enumerate(trees):
            if before is not None:
                score = before[i]
            g, h = self.gradients(score, grad_dtype, fault)
            leaf_of, stats, hist = self._leaf_stats(
                tree, g, h, rows_used, with_hist)
            out = self._leaf_outputs(stats)
            delta = out[leaf_of]
            score = score + delta
            if before is None:
                score = score.astype(np.float32).astype(np.float64)
            chosen, best = self._split_gains(tree, stats, hist)
            steps.append({
                "stats": stats, "leaf_output": out,
                "ndcg": self.ndcg(score),
                "update_norm": float(np.sqrt(np.sum(delta * delta))),
                "score": score, "gain_chosen": chosen, "gain_best": best})
        return 0.0, steps

    def _leaf_outputs(self, stats):
        # a leaf no used row reaches (a planted fault's) holds nothing
        with np.errstate(divide="ignore", invalid="ignore"):
            out = -stats[0] / (stats[1] + self.l2) * self.lr
        return np.where(stats[2] > 0, np.nan_to_num(out), 0.0)

    def emulate(self, trees, grad_dtype="float64", rows_used=None,
                fault=None):
        """The reference in the program's place: what a program growing
        these trees would hand over, computed in `grad_dtype`, over
        `rows_used` only (None = every row), with `fault` planted."""
        _, steps = self.walk(trees, grad_dtype, rows_used, fault=fault)
        out_trees, scores = [], []
        for tree, st in zip(trees, steps):
            t = dict(tree)
            t["leaf_value"] = st["leaf_output"]
            t["leaf_count"] = st["stats"][2].astype(np.int64)
            out_trees.append(t)
            scores.append(st["score"].astype(np.float32))
        return Outputs(out_trees, scores)

    def follow(self, outputs):
        """The numbers that decide `correct`: the program's `outputs`
        against the reference's walk over the same trees, each step
        from the score row the program reported before it."""
        rows = [s.astype(np.float64) for s in outputs.scores]
        before = ([np.zeros(self.n)] + rows)[:len(outputs.trees)]
        trees = outputs.trees[:len(before)]
        _, steps = self.walk(trees, with_hist=True, before=before)
        count_mismatch, value_gap, ndcg_gap, update_gap = 0, 0.0, 0.0, 0.0
        chosen_sum, best_sum = 0.0, 0.0
        for tree, st, s, prev in zip(trees, steps, rows, before):
            L = int(tree["num_leaves"])
            count_mismatch += int(np.sum(
                tree["leaf_count"][:L] != st["stats"][2].astype(np.int64)))
            want = st["leaf_output"]
            got = tree["leaf_value"][:L]
            scale = np.maximum(np.abs(want), np.median(np.abs(want)))
            value_gap = max(value_gap,
                            float(np.max(np.abs(got - want) / scale)))
            ndcg_gap = max(ndcg_gap, float(
                np.mean(np.abs(self.ndcg(s) - st["ndcg"]))
                / np.mean(st["ndcg"])))
            d = s - prev
            norm = float(np.sqrt(np.sum(d * d)))
            update_gap = max(update_gap,
                             abs(norm - st["update_norm"])
                             / st["update_norm"])
            ok = np.isfinite(st["gain_best"])
            chosen_sum += float(st["gain_chosen"][ok].sum())
            best_sum += float(st["gain_best"][ok].sum())
        return {
            "leaf_count_mismatch": count_mismatch,
            "leaf_value_gap": value_gap,
            "ndcg_gap": ndcg_gap,
            "update_norm_gap": update_gap,
            "split_gain_shortfall": 1.0 - chosen_sum / best_sum,
        }
