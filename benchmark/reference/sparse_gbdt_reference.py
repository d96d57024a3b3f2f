"""Plain column-sparse reference for the first boosting steps of a binary
GBDT, for a table too wide to hold as a plane.

The mathematics is `gbdt_reference`'s, whose walk, leaf outputs, chosen
gains, `follow` and `emulate` it takes as they stand (NumPy float64,
nothing imported from `lightgbm_tpu`). What it does otherwise is hold
the table as its columns' stored entries (CSC) and never as an (N, F)
or (F, N) array:

- a tree routes the rows by reading only its split features' columns:
  each node takes the stored values of its feature at its own rows, and
  every other row of the node holds 0;
- the grid of candidate thresholds is `gbdt_reference`'s (equal-frequency
  edges of the same sample drawn from the seed, zeros and all); a row's
  grid code is stored for its nonzeros alone, and each column has one
  code for the value 0;
- a leaf's grid histogram of a column is summed from that column's
  nonzeros, and the code of 0 takes the leaf's total less the rest;
- each column keeps as many slots as it has codes (a 0/1 column three:
  0, 1 and NaN), never a 256-bin plane.

On a dense table it gives `gbdt_reference`'s readings (held by
`tests/benchmark_tests/test_benchmark_allstate.py`).
"""
import numpy as np
import scipy.sparse as sp

from .gbdt_reference import (GRID_BINS, GRID_SAMPLE, NAN_CODE, Outputs,
                             TREE_KEYS, _pmap)
from . import gbdt_reference

__all__ = ["Outputs", "Reference", "TREE_KEYS"]

SLOT_BLOCK = 2048        # slots at most: a block's sums stay in cache
ENTRIES = 1 << 21        # stored entries a pass: its buffers are reused,
                         # since a fresh large array costs a page fault
                         # per 4 KiB where the host has no huge pages


class Reference(gbdt_reference.Reference):
    """The raw table as CSC, the stated parameters, and the grid."""

    def __init__(self, x, y, params, seed, with_grid=True):
        csc = sp.csc_matrix(x)
        csc.sort_indices()
        self.indptr, self.rows, self.values = csc.indptr, csc.indices, \
            csc.data
        super().__init__(csc, y, params, seed, with_grid=False)
        self.x = None       # routed by columns, never by rows
        if with_grid:
            self._make_grid(seed, x)

    # -- the grid ------------------------------------------------------
    def _make_grid(self, seed, x):
        r = np.random.default_rng([int(seed), 0x5EED])
        take = np.sort(r.choice(self.n, min(GRID_SAMPLE, self.n),
                                replace=False))
        sample = sp.csc_matrix(sp.csr_matrix(x)[take])
        sample.sort_indices()
        qs = np.arange(1, GRID_BINS - 1) / (GRID_BINS - 1)
        self.n_edges = np.zeros(self.f, np.int64)
        self.zero_code = np.zeros(self.f, np.int64)
        codes = np.empty(len(self.rows), np.uint8)

        def one(j):
            s = np.zeros(len(take), self.values.dtype)
            part = slice(sample.indptr[j], sample.indptr[j + 1])
            s[sample.indices[part]] = sample.data[part]
            s = s[~np.isnan(s)]
            edges = np.unique(np.quantile(s, qs).astype(np.float32)) \
                if s.size else np.zeros(0, np.float32)
            edges = edges[:NAN_CODE - 1]
            lo, hi = self.indptr[j], self.indptr[j + 1]
            col = self.values[lo:hi]
            code = np.searchsorted(edges, col, side="left")
            code[np.isnan(col)] = NAN_CODE
            codes[lo:hi] = code
            self.zero_code[j] = np.searchsorted(edges, 0.0, side="left")
            self.n_edges[j] = len(edges)

        _pmap(one, range(self.f))
        # each column's slots: its codes 0..n_edges, then NaN; every
        # stored entry's slot, made once
        width = self.n_edges + 2
        self.slot0 = np.concatenate(([0], np.cumsum(width)[:-1]))
        self.slots = int(width.sum())
        self.codes = np.empty(len(self.rows),
                              np.uint16 if self.slots < 2**16 else np.int32)

        def slot(j):
            lo, hi = self.indptr[j], self.indptr[j + 1]
            out = self.codes[lo:hi]
            np.add(codes[lo:hi], self.slot0[j], out=out, casting="unsafe")
            out[codes[lo:hi] == NAN_CODE] = self.slot0[j] \
                + self.n_edges[j] + 1

        _pmap(slot, range(self.f))

    def _column_blocks(self):
        """(first column, end column, first slot, slots) of blocks of
        whole columns, of at most SLOT_BLOCK slots or one column."""
        f0 = 0
        while f0 < self.f:
            f1 = int(np.searchsorted(self.slot0, self.slot0[f0]
                                     + SLOT_BLOCK, side="right")) - 1
            f1 = min(max(f1, f0 + 1), self.f)
            s0 = self.slot0[f0]
            yield f0, f1, s0, (self.slot0[f1] if f1 < self.f
                               else self.slots) - s0
            f0 = f1

    # -- routing -------------------------------------------------------
    def route_all(self, tree):
        """Leaf index of every row under `tree`, by `gbdt_reference.route`'s
        rule, one node at a time over the node's own rows: the rows its
        feature does not store hold 0 and go one way together."""
        leaf_of = np.zeros(self.n, np.int32)
        if tree["num_leaves"] <= 1:
            return leaf_of
        dt = tree["decision_type"].astype(np.int32)
        if (dt & 1).any():
            raise ValueError("reference routes numerical splits only")
        miss_type, default_left = (dt >> 2) & 3, (dt & 2) != 0

        def left(node, v):
            """`gbdt_reference.route`'s way for the float64 values `v`."""
            nan = np.isnan(v)
            mt = miss_type[node]
            if mt != 2:
                v = np.where(nan, 0.0, v)
            missing = (nan if mt == 2 else
                       np.abs(v) <= 1e-35 if mt == 1 else
                       np.zeros(len(v), bool))
            return np.where(missing, default_left[node],
                            v <= tree["threshold"][node])

        todo = [(0, np.arange(self.n, dtype=np.int32))]
        while todo:
            node, rows = todo.pop()
            j = int(tree["split_feature"][node])
            lo, hi = self.indptr[j], self.indptr[j + 1]
            if hi - lo == self.n:                       # a dense column
                go_left = left(node, self.values[lo:hi][rows]
                               .astype(np.float64))
            else:
                # look the fewer up among the more: the node's rows among
                # the column's stored rows, or the other way round
                stored, vals = self.rows[lo:hi], self.values[lo:hi]
                go_left = np.full(len(rows), left(node, np.zeros(1))[0])
                if len(rows) < len(stored):
                    at, hit = _at_rows(rows, stored)
                    go_left[hit] = left(node, vals[at[hit]]
                                        .astype(np.float64))
                else:
                    at, hit = _at_rows(stored, rows)
                    go_left[at[hit]] = left(node, vals[hit]
                                            .astype(np.float64))
            for child, part in ((tree["left_child"][node], rows[go_left]),
                                (tree["right_child"][node],
                                 rows[~go_left])):
                if child < 0:
                    leaf_of[part] = ~child
                else:
                    todo.append((int(child), part))
        return leaf_of

    # -- one tree ------------------------------------------------------
    def _leaf_stats(self, tree, g, h, rows_used, with_hist):
        """Per-leaf (sum g, sum h, count) over `rows_used`, every row's
        leaf, and (optionally) per-leaf grid histograms in slots,
        shaped (3, L, slots)."""
        L = int(tree["num_leaves"])
        leaf_of = self.route_all(tree)
        if rows_used is not None:
            g, h = np.where(rows_used, g, 0.0), np.where(rows_used, h, 0.0)
            one = rows_used.astype(np.float64)
        else:
            one = None
        stats = np.stack([np.bincount(leaf_of, g, L),
                          np.bincount(leaf_of, h, L),
                          np.bincount(leaf_of, one, L)])
        if not (with_hist and self.codes is not None):
            return leaf_of, stats, None
        if rows_used is not None:
            raise ValueError("grid histograms are over every row")
        hist = np.zeros((3, L, self.slots))
        counted = self.min_data > 0     # else no count can bar a split
        key = np.empty(ENTRIES, np.intp)
        lf = np.empty(ENTRIES, np.int32)
        w = np.empty((2, ENTRIES))
        acc = np.empty((3, min(SLOT_BLOCK, self.slots) * L))
        for f0, f1, s0, span in self._column_blocks():
            sums = acc[:, :span * L]
            sums.fill(0.0)
            for a in range(self.indptr[f0], self.indptr[f1], ENTRIES):
                b = min(a + ENTRIES, self.indptr[f1])
                m, at = b - a, self.rows[a:b]
                np.take(leaf_of, at, out=lf[:m], mode="clip")
                np.take(g, at, out=w[0, :m], mode="clip")
                np.take(h, at, out=w[1, :m], mode="clip")
                k = key[:m]          # (slot - s0) * L + leaf
                np.subtract(self.codes[a:b], s0, out=k, casting="unsafe")
                k *= L
                k += lf[:m]
                for i in range(2):
                    sums[i] += np.bincount(k, w[i, :m], span * L)
                if counted:
                    sums[2] += np.bincount(k, minlength=span * L)
            hist[:, :, s0:s0 + span] = sums.reshape(3, span, L) \
                .transpose(0, 2, 1)
        # the code of 0: the leaf's total less its column's nonzeros
        stored = np.add.reduceat(hist, self.slot0, axis=2)   # (3, L, F)
        hist[:, :, self.slot0 + self.zero_code] += stats[:, :, None] - stored
        if not counted:
            hist[2] = 0.0
        return leaf_of, stats, hist

    def _split_gains(self, tree, stats, hist):
        """(gain of the program's split, best gain over the grid) at
        every split node, both from the reference's own sums."""
        chosen, best = super()._split_gains(tree, stats, None)
        if hist is None or not len(chosen):
            return chosen, best
        n_int = len(chosen)
        lc, rc = tree["left_child"][:n_int], tree["right_child"][:n_int]
        node_s = np.zeros((n_int, 3))
        node_h = np.empty((n_int, 3, self.slots))
        # children are numbered after their parents
        for i in range(n_int - 1, -1, -1):
            for c in (lc[i], rc[i]):
                node_s[i] += stats[:, ~c] if c < 0 else node_s[c]
            a, b = (hist[:, ~c] if c < 0 else node_h[c]
                    for c in (lc[i], rc[i]))
            np.add(a, b, out=node_h[i])

        def term(g, h):
            return g * g / (h + self.l2)

        # a split after code b of column j: b < n_edges[j]; its left
        # side is the column's slots up to b
        col = np.repeat(np.arange(self.f), self.n_edges + 2)
        valid = np.arange(self.slots) - self.slot0[col] < self.n_edges[col]
        nan_slot = (self.slot0 + self.n_edges + 1)[col]
        first = self.slot0[col]
        best = np.full(n_int, -np.inf)
        for i in range(n_int):
            run = np.cumsum(node_h[i], axis=1)
            left = run - (run[:, first - 1] * (first > 0))
            total = node_s[i][:, None]
            for lft in (left, left + node_h[i][:, nan_slot]):
                rgt = total - lft
                ok = (valid & (lft[2] >= self.min_data)
                      & (rgt[2] >= self.min_data)
                      & (lft[1] >= self.min_hess)
                      & (rgt[1] >= self.min_hess))
                with np.errstate(divide="ignore", invalid="ignore"):
                    gain = np.where(ok, term(lft[0], lft[1])
                                    + term(rgt[0], rgt[1]), -np.inf)
                best[i] = max(best[i], float(gain.max()))
        return chosen, best - term(node_s[:, 0], node_s[:, 1])


def _at_rows(stored, rows):
    """Where the sorted `stored` rows fall in the sorted `rows`, and
    which of them are there."""
    at = np.searchsorted(rows, stored)
    hit = at < len(rows)
    hit[hit] = rows[at[hit]] == stored[hit]
    return at, hit
