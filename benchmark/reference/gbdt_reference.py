"""Plain reference for the first boosting steps of a binary GBDT.

NumPy on the host, float64, nothing imported from `lightgbm_tpu`. It is
given the raw table made from the seed, the configuration's stated
parameters, and what the timed path produced in its first steps: the
trees (structure, leaf values, leaf counts) and the score row after each
step. It takes no bin boundaries, codes or gradients from the program.

A greedy tree is a chain of arg-max decisions, so two sound growers part
ways at the first near-tie and every later number differs by the tie,
not by a fault. So the reference follows the program's trees the way a
served model's reference follows its served tokens: it routes every raw
row through each tree by the real-valued thresholds, works out ITS OWN
gradients from ITS OWN scores, and from them what each leaf has to hold
(count, output), what each step's loss and update norm are, and, over
its own grid of candidate thresholds, the best gain every split node
could have had. The leaf outputs the reference carries forward are its
own, never the program's.

`emulate` is the same walk put in the program's place: it returns what a
program would have produced on those trees, optionally with gradients
rounded to bfloat16 (the control) or with part of the rows left out (a
planted fault).
"""
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
import os

import numpy as np

THREADS = min(8, os.cpu_count() or 1)
CHUNK = 262_144
NAN_CODE = 255
GRID_SAMPLE = 200_000
GRID_BINS = 255


@dataclass
class Outputs:
    """What a program produced in its first steps."""
    trees: list            # dicts of arrays, see TREE_KEYS
    scores: list           # float32 [rows] after each step
    extra: dict = field(default_factory=dict)


TREE_KEYS = ("num_leaves", "split_feature", "threshold", "decision_type",
             "left_child", "right_child", "leaf_value", "leaf_count")


def _pmap(fn, jobs):
    with ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(fn, jobs))


def _row_ranges(n):
    per = -(-n // THREADS)
    return [(a, min(a + per, n)) for a in range(0, n, per)]


def _check_params(params):
    for key, want in (("objective", "binary"), ("lambda_l1", 0.0),
                      ("max_delta_step", 0.0), ("min_gain_to_split", 0.0),
                      ("sigmoid", 1.0), ("boost_from_average", True)):
        if params.get(key, want) != want:
            raise ValueError(f"reference does not model {key}="
                             f"{params[key]!r}")


def route(x, a, b, tree):
    """Leaf index of rows a..b of the raw table under `tree`."""
    sf = tree["split_feature"]
    thr = tree["threshold"]
    dt = tree["decision_type"].astype(np.int32)
    if (dt & 1).any():
        raise ValueError("reference routes numerical splits only")
    miss_type, default_left = (dt >> 2) & 3, (dt & 2) != 0
    left, right = tree["left_child"], tree["right_child"]
    m = b - a
    if tree["num_leaves"] <= 1:
        return np.zeros(m, np.int32)
    node = np.zeros(m, np.int32)
    active = np.arange(m)
    while active.size:
        nd = node[active]
        v = x[a + active, sf[nd]].astype(np.float64)
        nan = np.isnan(v)
        mt = miss_type[nd]
        v = np.where(nan & (mt != 2), 0.0, v)
        missing = ((mt == 2) & nan) | ((mt == 1) & (np.abs(v) <= 1e-35))
        go_left = np.where(missing, default_left[nd], v <= thr[nd])
        nxt = np.where(go_left, left[nd], right[nd])
        node[active] = nxt
        active = active[nxt >= 0]
    return ~node


def gradients(score, y, dtype="float64"):
    """Binary log-loss gradient and hessian of float64 scores. With
    `dtype="bfloat16"` each is rounded to bfloat16, as a one-pass bf16
    matrix unit would read them; the sums stay wide."""
    p = 1.0 / (1.0 + np.exp(-score))
    g, h = p - y, p * (1.0 - p)
    if dtype == "bfloat16":
        import ml_dtypes
        g = g.astype(ml_dtypes.bfloat16).astype(np.float64)
        h = h.astype(ml_dtypes.bfloat16).astype(np.float64)
    elif dtype != "float64":
        raise ValueError(dtype)
    return g, h


def logloss(score, y):
    return float(np.mean(np.logaddexp(0.0, score) - y * score))


def init_score(y):
    p = float(np.mean(y, dtype=np.float64))
    return float(np.log(p / (1.0 - p)))


class Reference:
    """The raw table, the stated parameters, and the reference's own grid
    of candidate thresholds (equal-frequency edges of a sample drawn from
    the seed; NaN has a code of its own)."""

    def __init__(self, x, y, params, seed, with_grid=True):
        _check_params(params)
        self.x = x
        self.y = y.astype(np.float64)
        self.n, self.f = x.shape
        self.lr = float(params["learning_rate"])
        self.l2 = float(params.get("lambda_l2", 0.0))
        self.min_data = int(params.get("min_data_in_leaf", 20))
        self.min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
        self.codes = None
        if with_grid:
            self._make_grid(seed)

    def _make_grid(self, seed):
        r = np.random.default_rng([int(seed), 0x5EED])
        take = np.sort(r.choice(self.n, min(GRID_SAMPLE, self.n),
                                replace=False))
        sample = self.x[take]
        self.codes = np.empty((self.f, self.n), np.uint8)
        self.n_edges = np.zeros(self.f, np.int64)
        qs = np.arange(1, GRID_BINS - 1) / (GRID_BINS - 1)

        def one(j):
            s = sample[:, j]
            s = s[~np.isnan(s)]
            edges = np.unique(np.quantile(s, qs).astype(np.float32)) \
                if s.size else np.zeros(0, np.float32)
            edges = edges[:NAN_CODE - 1]
            col = np.ascontiguousarray(self.x[:, j])
            code = np.searchsorted(edges, col, side="left")
            code[np.isnan(col)] = NAN_CODE
            self.codes[j] = code
            self.n_edges[j] = len(edges)

        _pmap(one, range(self.f))

    # -- one tree ------------------------------------------------------
    def _buffers(self, L, hist_on):
        """Per-thread accumulators, made once: a fresh large array costs
        a page fault per 4 KiB, which on a small VM is most of the time."""
        ranges = _row_ranges(self.n)
        if getattr(self, "_buf_key", None) != (L, hist_on):
            self._buf_key = (L, hist_on)
            self._hist = [np.empty((3, self.f, L * 256)) if hist_on else None
                          for _ in ranges]
            self._node_hist = (np.empty((L - 1, 3, self.f, 256))
                               if hist_on and L > 1 else None)
        return ranges

    def _leaf_stats(self, tree, g, h, rows_used, with_hist):
        """Per-leaf (sum g, sum h, count) over `rows_used`, every row's
        leaf, and (optionally) per-leaf histograms over the grid, shaped
        (3, F, L, 256)."""
        L = int(tree["num_leaves"])
        leaf_of = np.empty(self.n, np.int32)
        hist_on = with_hist and self.codes is not None
        ranges = self._buffers(L, hist_on)

        def one(job):
            (lo, hi), hist = job
            stats = np.zeros((3, L))
            if hist_on:
                hist.fill(0.0)
            key = np.empty(CHUNK, np.int64)
            for a in range(lo, hi, CHUNK):
                b = min(a + CHUNK, hi)
                leaf = route(self.x, a, b, tree)
                leaf_of[a:b] = leaf
                if rows_used is not None:
                    rows = np.nonzero(rows_used[a:b])[0]
                    leaf, ga, ha = leaf[rows], g[a:b][rows], h[a:b][rows]
                else:
                    rows, ga, ha = None, g[a:b], h[a:b]
                stats[0] += np.bincount(leaf, ga, L)
                stats[1] += np.bincount(leaf, ha, L)
                stats[2] += np.bincount(leaf, minlength=L)
                if not hist_on:
                    continue
                k = key[:leaf.size]
                for j in range(self.f):
                    code = self.codes[j, a:b]
                    np.multiply(leaf, 256, out=k)
                    np.add(k, code if rows is None else code[rows], out=k)
                    hist[0, j] += np.bincount(k, ga, L * 256)
                    hist[1, j] += np.bincount(k, ha, L * 256)
                    hist[2, j] += np.bincount(k, minlength=L * 256)
            return stats

        stats = sum(_pmap(one, zip(ranges, self._hist)))
        hist = None
        if hist_on:
            hist = self._hist[0]
            for part in self._hist[1:len(ranges)]:
                hist += part
            hist = hist.reshape(3, self.f, L, 256)
        return leaf_of, stats, hist

    def _leaf_outputs(self, stats):
        return -stats[0] / (stats[1] + self.l2) * self.lr

    def _split_gains(self, tree, stats, hist):
        """(gain of the program's split, best gain over the reference's
        grid) at every split node, both from the reference's own sums."""
        n_int = int(tree["num_leaves"]) - 1
        if n_int < 1:
            return np.zeros(0), np.zeros(0)
        lc, rc = tree["left_child"][:n_int], tree["right_child"][:n_int]
        leaf_s = stats.T                                  # (L, 3)
        node_s = np.zeros((n_int, 3))
        # children are numbered after their parents
        for i in range(n_int - 1, -1, -1):
            for c in (lc[i], rc[i]):
                node_s[i] += leaf_s[~c] if c < 0 else node_s[c]

        def child(c):
            return leaf_s[~c] if c < 0 else node_s[c]

        def term(g, h):
            return g * g / (h + self.l2)

        lch = np.stack([child(c) for c in lc])
        rch = np.stack([child(c) for c in rc])
        parent = term(node_s[:, 0], node_s[:, 1])
        chosen = term(lch[:, 0], lch[:, 1]) + term(rch[:, 0], rch[:, 1]) \
            - parent
        if hist is None:
            return chosen, np.full(n_int, np.nan)
        nh = self._node_hist                              # (n, 3, F, 256)
        for i in range(n_int - 1, -1, -1):
            a, b = (hist[:, :, ~c, :] if c < 0 else nh[c]
                    for c in (lc[i], rc[i]))
            np.add(a, b, out=nh[i])
        valid_b = np.arange(NAN_CODE)[None, :] < self.n_edges[:, None]
        best = np.full(n_int, -np.inf)
        step = 16
        for i0 in range(0, n_int, step):
            part = nh[i0:i0 + step]
            m = part.shape[0]
            nan_mass = part[..., NAN_CODE:]               # (m, 3, F, 1)
            left = np.cumsum(part[..., :NAN_CODE], axis=-1)
            total = node_s[i0:i0 + step].reshape(m, 3, 1, 1)
            for lft in (left, left + nan_mass):
                rgt = total - lft
                ok = (valid_b[None]
                      & (lft[:, 2] >= self.min_data)
                      & (rgt[:, 2] >= self.min_data)
                      & (lft[:, 1] >= self.min_hess)
                      & (rgt[:, 1] >= self.min_hess))
                with np.errstate(divide="ignore", invalid="ignore"):
                    gain = np.where(ok, term(lft[:, 0], lft[:, 1])
                                    + term(rgt[:, 0], rgt[:, 1]), -np.inf)
                best[i0:i0 + step] = np.maximum(
                    best[i0:i0 + step], gain.reshape(m, -1).max(axis=1))
        return chosen, best - parent

    # -- the walk ------------------------------------------------------
    def walk(self, trees, grad_dtype="float64", rows_used=None,
             with_hist=False):
        """Follow `trees` from the reference's own initial score. Yields
        per tree: stats (3, L), leaf outputs, loss after the step, the
        update's norm, and the split gains (chosen, best)."""
        init = init_score(self.y)
        score = np.full(self.n, init)
        steps = []
        for tree in trees:
            g, h = gradients(score, self.y, grad_dtype)
            leaf_of, stats, hist = self._leaf_stats(
                tree, g, h, rows_used, with_hist)
            out = self._leaf_outputs(stats)
            delta = out[leaf_of]
            score = score + delta
            chosen, best = self._split_gains(tree, stats, hist)
            steps.append({
                "stats": stats, "leaf_output": out,
                "loss": logloss(score, self.y),
                "update_norm": float(np.sqrt(np.sum(delta * delta))),
                "score": score, "gain_chosen": chosen, "gain_best": best})
        return init, steps

    def emulate(self, trees, grad_dtype="float64", rows_used=None):
        """The reference in the program's place: what a program growing
        these trees would hand over, computed in `grad_dtype` and over
        `rows_used` only (None = every row)."""
        init, steps = self.walk(trees, grad_dtype, rows_used)
        out_trees, scores = [], []
        for i, (tree, st) in enumerate(zip(trees, steps)):
            t = dict(tree)
            t["leaf_value"] = st["leaf_output"] + (init if i == 0 else 0.0)
            t["leaf_count"] = st["stats"][2].astype(np.int64)
            out_trees.append(t)
            scores.append(st["score"].astype(np.float32))
        return Outputs(out_trees, scores)

    def follow(self, outputs):
        """The numbers that decide `correct`: the program's `outputs`
        against the reference's walk over the same trees."""
        init, steps = self.walk(outputs.trees, with_hist=True)
        y = self.y
        count_mismatch, value_gap, loss_gap, update_gap = 0, 0.0, 0.0, 0.0
        chosen_sum, best_sum = 0.0, 0.0
        prev = np.full(self.n, init)
        for i, (tree, st, s) in enumerate(zip(outputs.trees, steps,
                                              outputs.scores)):
            L = int(tree["num_leaves"])
            count_mismatch += int(np.sum(
                tree["leaf_count"][:L] != st["stats"][2].astype(np.int64)))
            want = st["leaf_output"]
            # the first tree carries the initial score as a bias
            got = tree["leaf_value"][:L] - (init if i == 0 else 0.0)
            scale = np.maximum(np.abs(want), np.median(np.abs(want)))
            value_gap = max(value_gap,
                            float(np.max(np.abs(got - want) / scale)))
            s = s.astype(np.float64)
            loss_gap = max(loss_gap,
                           abs(logloss(s, y) - st["loss"]) / st["loss"])
            d = s - prev
            norm = float(np.sqrt(np.sum(d * d)))
            update_gap = max(update_gap,
                             abs(norm - st["update_norm"])
                             / st["update_norm"])
            prev = s
            ok = np.isfinite(st["gain_best"])
            chosen_sum += float(st["gain_chosen"][ok].sum())
            best_sum += float(st["gain_best"][ok].sum())
        return {
            "leaf_count_mismatch": count_mismatch,
            "leaf_value_gap": value_gap,
            "loss_gap": loss_gap,
            "update_norm_gap": update_gap,
            "split_gain_shortfall": 1.0 - chosen_sum / best_sum,
        }
