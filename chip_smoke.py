#!/usr/bin/env python
"""Chip smoke: the Higgs-shaped train -> predict -> serve path, once, on
the accelerator, through the entry points a user calls.

    python chip_smoke.py                  # the driver's check: TPU or exit 1
    python chip_smoke.py --all            # + the five shared-code legs
    python chip_smoke.py --legs train255 --rows 10500000 --iters 2
    python chip_smoke.py --cpu-rehearsal  # tiny sizes on the CPU, says so

One process, no children, no LGBM_TPU_* variable (the reference leg
forces the host-loop learner the way the tests do, and unsets it again).
A failed default leg raises: the traceback and a non-zero exit code are
the report. Only when every requested leg passed, the last two lines of
stdout are JSON: the leg results, then the driver's result line,
`{"ok": true, "device": {"platform", "kind", "count"}}` and nothing
else in it. No time printed here is a metric.
"""
import argparse
import json
import os
import sys
import tempfile
import time
import traceback
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu.cli import _serve
from lightgbm_tpu.models.device_learner import DeviceTreeLearner
from lightgbm_tpu.models.serial_learner import SerialTreeLearner
from lightgbm_tpu.ops import histogram as hist_ops
from lightgbm_tpu.ops.pallas import histogram_kernel as pallas_hist
from lightgbm_tpu.parallel.learners import DeviceDataParallelTreeLearner
from lightgbm_tpu.telemetry import counters

DEFAULT_LEGS = ("histogram", "train255", "train63", "reference", "predict",
                "serve", "kernels", "cache", "fourchip")
ALL_LEGS = ("categorical", "lambdarank", "multiclass", "quantized",
            "stream")
N_FEATURES = 28
# Device histograms are sums of bf16 hi + lo halves accumulated in f32
# (rel err ~1e-6 per bin); the split gains built from them decide 254
# splits per tree, so leaf outputs agree to ~1e-4 of the score scale
# unless a near-tie gain flips a split — which the leg reports as such.
REFERENCE_RTOL = 1e-3


def make_higgs_like(n, f, seed=17, w=None, n_cat=0, card=64, n_classes=1):
    """Synthetic stand-in with Higgs-like statistics: mixed informative /
    noise features, moderately separable classes. Pass `w` to draw a new
    sample from the SAME ground-truth function (e.g. a held-out valid set)
    without perturbing the default stream. n_cat > 0 converts the
    LAST n_cat columns into categorical features (cardinality `card`)
    with per-category target effects — the Expo/Allstate-style
    categorical-heavy shape (reference docs/Experiments.rst datasets).

    `w` is a `(w_num, cat_tables)` tuple; `cat_tables` is `[]` at
    n_cat=0."""
    r = np.random.RandomState(seed)
    x = r.randn(n, f).astype(np.float32)
    if w is None:
        w_num = r.randn(f) * (r.rand(f) > 0.4)
        cat_tables = [r.randn(card) * 0.5 for _ in range(n_cat)]
        w = (w_num, cat_tables)
    w_num, cat_tables = w
    if cat_tables:
        # categorical columns must not leak their pre-overwrite Gaussian
        # draws into the label (unobservable noise would depress the
        # categorical run's AUC)
        w_num = w_num.copy()
        w_num[f - len(cat_tables):] = 0.0
    logit = x @ w_num * 0.3 + 0.2 * x[:, 0] * x[:, 1] - 0.1 * x[:, 2] ** 2
    for j in range(len(cat_tables)):
        cats = r.randint(0, card, n)
        x[:, f - len(cat_tables) + j] = cats
        logit += cat_tables[j][cats]
    if n_classes > 1:
        # large-K multiclass variant: margin quantiles become balanced
        # K-class labels (class 0 = lowest margin). The one-vs-rest
        # structure keeps an AUC-style gate usable — class-0 margin vs
        # (label == 0) is the same separability the binary label has.
        noisy = logit + r.randn(n) * 1.5
        edges = np.quantile(noisy, np.linspace(0, 1, n_classes + 1)[1:-1])
        y = np.searchsorted(edges, noisy).astype(np.float64)
        return x, y, w
    y = (logit + r.randn(n) * 1.5 > 0).astype(np.float64)
    return x, y, w


def make_ranking_like(n_queries, docs_per_query, f, seed=17, w=None):
    """Synthetic learning-to-rank set: query-grouped docs with graded
    relevance 0..4. Per-query context vectors shift the document score
    so ranking signal is intra-query (the shape LambdaRank exploits);
    pass `w` to draw a held-out sample from the SAME ground truth."""
    r = np.random.RandomState(seed)
    n = n_queries * docs_per_query
    x = r.randn(n, f).astype(np.float32)
    if w is None:
        w = r.randn(f) * (r.rand(f) > 0.4)
    ctx = np.repeat(r.randn(n_queries, 1) * 0.5, docs_per_query, axis=0)
    score = x @ w * 0.4 + 0.2 * x[:, 0] * x[:, 1] + ctx[:, 0] \
        + r.randn(n) * 0.8
    # grade into 0..4 by global quantile so every query mixes grades
    edges = np.quantile(score, [0.5, 0.75, 0.9, 0.97])
    y = np.digitize(score, edges).astype(np.float64)
    group = np.full(n_queries, docs_per_query, dtype=np.int64)
    return x, y, group, w


def host_predict_raw(models, x):
    """Vectorized numpy ensemble traversal (numerical + categorical
    bitset splits; no-NaN data — exactly this script's generators). Keeps
    ALL evaluation off the device: a mid-training predict would
    otherwise compile a fresh ensemble program per tree-count."""
    out = np.zeros(x.shape[0], dtype=np.float64)
    for t in models:
        if t.num_leaves <= 1:
            out += float(t.leaf_value[0])
            continue
        sf = np.asarray(t.split_feature, dtype=np.int32)
        thr = np.asarray(t.threshold, dtype=np.float64)
        lc = np.asarray(t.left_child, dtype=np.int32)
        rc = np.asarray(t.right_child, dtype=np.int32)
        lv = np.asarray(t.leaf_value, dtype=np.float64)
        iscat = (np.asarray(t.decision_type, dtype=np.int32) & 1) != 0
        cat_lo = np.asarray(t.cat_boundaries, dtype=np.int64)
        cat_words = np.asarray(t.cat_threshold or [0], dtype=np.uint32)
        node = np.zeros(x.shape[0], dtype=np.int32)
        active = np.ones(x.shape[0], dtype=bool)
        while active.any():
            idx = np.nonzero(active)[0]
            nd = node[idx]
            v = x[idx, sf[nd]]
            go_left = v <= thr[nd]
            cn = iscat[nd]
            if cn.any():
                # categorical bitset routing (tree._cat_contains,
                # vectorized): out-of-range or negative values go right
                ci = thr[nd].astype(np.int64)
                vi = np.where(cn & (v >= 0), v, 0).astype(np.int64)
                word = vi // 32
                nwords = cat_lo[np.clip(ci + 1, 0, len(cat_lo) - 1)] \
                    - cat_lo[np.clip(ci, 0, len(cat_lo) - 1)]
                inb = cn & (v >= 0) & (word < nwords)
                wofs = np.clip(cat_lo[np.clip(ci, 0, len(cat_lo) - 1)]
                               + word, 0, len(cat_words) - 1)
                bit = (cat_words[wofs] >> (vi % 32).astype(np.uint32)) & 1
                go_left = np.where(cn, inb & (bit == 1), go_left)
            node[idx] = np.where(go_left, lc[nd], rc[nd])
            active[idx] = node[idx] >= 0
        out += lv[~node]
    return out


def rank_auc(scores, labels):
    """Tie-aware (mid-rank) AUC: few-tree models collapse many rows onto
    identical score sums; ordinal ranks would credit tied pos/neg pairs
    0-or-1 by row order instead of 0.5."""
    _, inv, counts = np.unique(scores, return_inverse=True,
                               return_counts=True)
    avg_rank = np.cumsum(counts) - counts + (counts + 1) / 2.0
    ranks = avg_rank[inv]
    pos = labels > 0
    return float((ranks[pos].sum() - pos.sum() * (pos.sum() + 1) / 2)
                 / max(pos.sum() * (~pos).sum(), 1))


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run at tiny sizes on the CPU (not a chip result)")
    ap.add_argument("--all", action="store_true",
                    help="also run " + ", ".join(ALL_LEGS))
    ap.add_argument("--legs", default="",
                    help="comma list overriding which legs run")
    ap.add_argument("--rows", type=int, default=0,
                    help="train-leg rows (default 1,000,000)")
    ap.add_argument("--iters", type=int, default=0,
                    help="train-leg iterations (default 10)")
    return ap.parse_args()


class Smoke:
    def __init__(self, args, tmp):
        self.rehearsal = tiny = args.cpu_rehearsal
        self.rows = args.rows or (2048 if tiny else 1_000_000)
        self.iters = args.iters or (2 if tiny else 10)
        self.leaves = 7 if tiny else 255
        self.valid_rows = 1024 if tiny else 100_000
        self.ref_rows = 2048 if tiny else 65_536
        self.side_rows = 2048 if tiny else 65_536     # --all legs
        self.min_auc = 0.60 if tiny else 0.75
        self.tmp = tmp
        self.results = {}
        self._model255 = None         # (booster, xv) from train255
        self.compile_events = counters.compile_events()

    # -- helpers -------------------------------------------------------
    def params(self, **over):
        p = {"objective": "binary", "num_leaves": self.leaves,
             "learning_rate": 0.1, "max_bin": 255, "metric": "none",
             "min_data_in_leaf": 20, "verbosity": 0}
        p.update(over)
        return p

    def higgs(self, rows):
        """Train rows plus held-out rows from the same ground truth."""
        x, y, w = make_higgs_like(rows, N_FEATURES)
        xv, yv, _ = make_higgs_like(self.valid_rows, N_FEATURES, seed=4242,
                                    w=w)
        return x, y, xv, yv

    def train(self, params, x, y, iters, **ds_kw):
        """lgb.train, then materialise: the pipelined iteration leaves
        one tree in flight until the model is read."""
        d0, t0 = counters.get("grow_dispatches"), counters.get("grow_trees")
        bst = lgb.train(params, lgb.Dataset(x, y, **ds_kw),
                        num_boost_round=iters, verbose_eval=False)
        models = bst._gbdt.models
        jax.block_until_ready(bst._gbdt.score_updater.score)
        trees = counters.get("grow_trees") - t0
        per_tree = (counters.get("grow_dispatches") - d0) / max(trees, 1.0)
        return bst, models, per_tree

    def check_model(self, models, xv, yv):
        """Full-width trees, finite scores, held-out AUC over the gate."""
        leaves = sorted({t.num_leaves for t in models})
        assert leaves == [self.leaves], f"tree leaf counts {leaves}"
        raw = host_predict_raw(models, xv)
        assert np.isfinite(raw).all()
        auc = rank_auc(raw, yv)
        assert auc >= self.min_auc, f"held-out AUC {auc:.4f}"
        return round(auc, 4)

    def model_file(self, bst):
        path = os.path.join(self.tmp, "model255.txt")
        if not os.path.exists(path):
            bst.save_model(path)
        return path

    # -- default legs --------------------------------------------------
    def leg_histogram(self):
        """The factored one-hot contraction's VALUES against NumPy
        float64 (a product shape the TPU compiler has not been seen to
        get right is not covered by a CPU test: PERF.md §6, PR 29), at
        XLA's default flags, so a gradient remainder the compiler folded
        away fails here too. Whole chunks and a ragged window whose last
        rows are padding (gh 0, arbitrary codes); and, of both, a range
        that starts past the first chunk and ends inside the last, summed
        over its own chunks alone (`build_histogram_range`, the compact
        core's child histogram): to float64 like the others, and equal
        to the whole window's sum with the rows outside it masked."""
        r = np.random.RandomState(5)
        out = {}
        for f, bins in ((28, 256), (67, 256), (5, 64), (9, 255)):
            chunk = 256 if self.rehearsal else hist_ops.resolve_chunk_size(
                0, f, bins)
            for rows in (4 * chunk, 3 * chunk + 777):
                codes = r.randint(0, bins, (rows, f)).astype(np.uint8)
                gh = np.stack([r.randn(rows), r.rand(rows) + 0.1,
                               np.ones(rows)], axis=1).astype(np.float32)
                ghq = np.stack([r.randint(-127, 128, rows),
                                r.randint(0, 128, rows),
                                np.ones(rows, np.int64)], axis=1).astype(np.int8)
                if rows % chunk:
                    gh[-300:] = 0
                    ghq[-300:] = 0
                want, mass = np.zeros((2, f, bins, 3))
                want_q = np.zeros((f, bins, 3), np.int64)
                for j in range(f):
                    np.add.at(want[j], codes[:, j], gh.astype(np.float64))
                    np.add.at(mass[j], codes[:, j], np.abs(gh, dtype=np.float64))
                    np.add.at(want_q[j], codes[:, j], ghq.astype(np.int64))
                got = np.asarray(hist_ops.build_histogram(
                    jnp.asarray(codes), jnp.asarray(gh), bins,
                    chunk_size=chunk))
                # a bf16 head and a bf16 remainder keep 16 bits of each
                # addend; the float32 sums add less than that again
                excess = float(np.max(np.abs(got - want) - 2.0 ** -16 * mass))
                assert excess <= 1e-6, (f, bins, rows, excess)
                got_q = np.asarray(hist_ops.build_histogram_quantized(
                    jnp.asarray(codes), jnp.asarray(ghq), bins,
                    chunk_size=chunk))
                assert np.array_equal(got_q, want_q), (f, bins, rows)
                begin, count = chunk + 123, rows - chunk - 123 - 450
                inside = ((np.arange(rows) >= begin)
                          & (np.arange(rows) < begin + count))[:, None]
                want_r, mass_r = np.zeros((2, f, bins, 3))
                want_rq = np.zeros((f, bins, 3), np.int64)
                for j in range(f):
                    np.add.at(want_r[j], codes[:, j],
                              (gh * inside).astype(np.float64))
                    np.add.at(mass_r[j], codes[:, j],
                              np.abs(gh * inside, dtype=np.float64))
                    np.add.at(want_rq[j], codes[:, j],
                              (ghq * inside).astype(np.int64))
                ranged = jax.jit(
                    lambda c, g, b, n, quantized:
                    hist_ops.build_histogram_range(
                        hist_ops.rows_loader(c, g), rows, b, n, f, bins,
                        quantized=quantized, chunk_size=chunk),
                    static_argnames="quantized")
                got_r = np.asarray(ranged(jnp.asarray(codes), jnp.asarray(gh),
                                          begin, count, quantized=False))
                excess_r = float(np.max(np.abs(got_r - want_r)
                                        - 2.0 ** -16 * mass_r))
                assert excess_r <= 1e-6, (f, bins, rows, excess_r)
                whole = np.asarray(hist_ops.build_histogram(
                    jnp.asarray(codes), jnp.asarray(gh * inside), bins,
                    chunk_size=chunk))
                assert np.array_equal(got_r, whole), (f, bins, rows)
                got_rq = np.asarray(ranged(
                    jnp.asarray(codes), jnp.asarray(ghq), begin, count,
                    quantized=True))
                assert np.array_equal(got_rq, want_rq), (f, bins, rows)
                out[f"{f}x{bins}_rows{rows}"] = {
                    "float_max_abs_err": float(np.abs(got - want).max()),
                    "int8": "equal",
                    "range_float_max_abs_err":
                        float(np.abs(got_r - want_r).max()),
                    "range_vs_whole": "equal", "range_int8": "equal"}
        return out

    def _train_leg(self, max_bin):
        x, y, xv, yv = self.higgs(self.rows)
        bst, models, per_tree = self.train(
            self.params(max_bin=max_bin), x, y, self.iters)
        gbdt, learner = bst._gbdt, bst._gbdt.learner
        chose = {"learner": type(learner).__name__,
                 "strategy": learner.strategy,
                 "fused_step": bool(gbdt._fused_step),
                 "pipeline": bool(gbdt._pipeline),
                 "grow_dispatches_per_tree": per_tree,
                 "device_bins": int(learner.device_bins)}
        print(f"  chose: {chose}", flush=True)
        assert type(learner) is DeviceTreeLearner, chose
        assert chose["fused_step"], "fused iteration did not engage"
        assert per_tree == 1.0, chose
        if not self.rehearsal and self.rows >= 65_536:
            assert chose["strategy"] == "compact", chose
            assert chose["pipeline"], chose
        assert len(models) == self.iters, len(models)
        out = dict(chose, rows=self.rows, iters=self.iters,
                   max_bin=max_bin, leaves=self.leaves,
                   valid_auc=self.check_model(models, xv, yv))
        return bst, xv, out

    def leg_train255(self):
        bst, xv, out = self._train_leg(255)
        self._model255 = (bst, xv)
        return out

    def model255(self):
        if self._model255 is None:       # --legs predict / serve alone
            self.leg_train255()
        return self._model255

    def leg_train63(self):
        return self._train_leg(63)[2]

    def leg_reference(self):
        """First three trees: device learner vs the plain host-loop
        reference on the same data, compared on held-out raw scores."""
        x, y, xv, _ = self.higgs(self.ref_rows)
        n_trees = 3
        params = self.params()
        dev, dev_models, _ = self.train(params, x, y, n_trees)
        os.environ["LGBM_TPU_HOST_LEARNER"] = "1"
        try:
            ref, ref_models, _ = self.train(params, x, y, n_trees)
        finally:
            del os.environ["LGBM_TPU_HOST_LEARNER"]
        assert type(ref._gbdt.learner) is SerialTreeLearner
        assert type(dev._gbdt.learner) is not SerialTreeLearner
        a = host_predict_raw(dev_models, xv)
        b = host_predict_raw(ref_models, xv)
        rel = float(np.abs(a - b).max() / np.abs(b).max())
        flipped = [
            i for i, (td, tr) in enumerate(zip(dev_models, ref_models))
            if list(td.split_feature) != list(tr.split_feature)
            or not np.array_equal(np.asarray(td.threshold),
                                  np.asarray(tr.threshold))]
        out = {"rows": self.ref_rows, "trees": n_trees,
               "strategy": dev._gbdt.learner.strategy,
               "max_rel_diff": rel, "trees_with_flipped_split": flipped}
        print(f"  {out}", flush=True)
        assert rel <= REFERENCE_RTOL, (
            f"device vs host-loop raw scores differ by {rel:.3g} of the "
            f"score scale (> {REFERENCE_RTOL}); trees whose split "
            f"structure differs (a near-tie flipped a split): {flipped}")
        return out

    def leg_predict(self):
        bst, xv = self.model255()
        raw = host_predict_raw(bst._gbdt.models, xv)
        host = 1.0 / (1.0 + np.exp(-raw))
        dev = np.asarray(bst.predict(xv))
        assert dev.shape == (xv.shape[0],), dev.shape
        err = float(np.abs(dev - host).max())
        assert err <= 1e-5, f"device predict vs host traversal: {err}"
        again = np.asarray(
            lgb.Booster(model_file=self.model_file(bst)).predict(xv))
        assert np.array_equal(dev, again), \
            f"reloaded model differs by {np.abs(dev - again).max()}"
        return {"rows": int(xv.shape[0]), "max_abs_err_vs_host": err,
                "model_file_roundtrip": "identical"}

    def leg_serve(self):
        bst, xv = self.model255()
        httpd = _serve({"task": "serve", "input_model": self.model_file(bst),
                        "serve_port": "0"}, block=False)
        try:
            url = f"http://127.0.0.1:{httpd.server_address[1]}/predict"
            want = np.asarray(bst.predict(xv[:256]))
            before = len(self.compile_events)
            for n in (1, 8, 256):
                req = urllib.request.Request(
                    url, json.dumps({"rows": xv[:n].tolist()}).encode(),
                    {"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=120) as resp:
                    got = np.asarray(json.load(resp)["predictions"])
                np.testing.assert_allclose(got, want[:n], atol=1e-6)
            fresh = self.compile_events[before:]
            assert not fresh, f"XLA compiled after warm-up: {fresh}"
            donate = httpd.app.registry.predictor.donate_input
        finally:
            httpd.shutdown()
            httpd.server_close()
            httpd.app.close()
        return {"requests": [1, 8, 256], "compiles_after_warmup": 0,
                "donate_input": bool(donate)}

    def leg_cache(self):
        """Where the persistent compile cache is, and whether this
        process read from it (a second run must) or filled it."""
        cache_dir = lgb.compile_cache_dir()
        assert cache_dir, "no persistent compile cache is configured"
        secs = counters.compile_seconds()
        hits = self.compile_events.count(
            "/jax/compilation_cache/compile_time_saved_sec")
        entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
            else 0
        out = {"dir": cache_dir, "persistent_cache_hits": hits,
               "entries_on_disk": entries,
               "xla_compile_seconds": round(sum(
                   v for k, v in secs.items()
                   if "compilation_cache" not in k), 1),
               "compile_seconds_saved_by_cache": round(secs.get(
                   "/jax/compilation_cache/compile_time_saved_sec", 0.0), 1)}
        assert hits > 0 or entries > 0, out
        return out

    def leg_fourchip(self):
        devices = jax.devices()
        if len(devices) < 4:
            return (f"did not run: {len(devices)} device(s) visible, "
                    "needs >= 4")
        x, y, xv, yv = self.higgs(self.rows)
        bst, models, per_tree = self.train(
            self.params(max_bin=63, tree_learner="data"), x, y, self.iters)
        learner = bst._gbdt.learner
        assert type(learner) is DeviceDataParallelTreeLearner, type(learner)
        assert learner.mesh.devices.size == len(devices), learner.mesh
        shard_rows = {str(s.device): int(s.data.shape[0])
                      for s in learner.codes_pack.addressable_shards}
        local_n = -(-self.rows // len(devices))
        assert len(shard_rows) == len(devices), shard_rows
        assert set(shard_rows.values()) == {local_n}, shard_rows
        return {"learner": type(learner).__name__,
                "mesh_devices": int(learner.mesh.devices.size),
                "rows_per_device": shard_rows, "strategy": learner.strategy,
                "grow_dispatches_per_tree": per_tree,
                "valid_auc": self.check_model(models, xv, yv)}

    def leg_kernels(self):
        """Each Pallas entry point (opt-in, LGBM_TPU_PALLAS=1), compiled
        by Mosaic — never interpreted on the chip — at the train legs'
        shapes, against the XLA formulation the default path uses."""
        interpret = self.rehearsal           # the chip compiles
        window = 2048 if self.rehearsal else 65_536
        r = np.random.RandomState(3)
        gh = np.stack([r.randn(window), r.rand(window) + 0.1,
                       np.ones(window)], axis=1).astype(np.float32)
        ghq = np.stack([r.randint(-127, 128, window),
                        r.randint(0, 128, window),
                        np.ones(window, np.int64)], axis=1).astype(np.int8)
        verdict = ("interpreted (rehearsal), matches XLA" if interpret
                   else "compiled by Mosaic, matches XLA")
        out = {}
        for bins in (64, 256):
            codes = r.randint(0, bins, (window, N_FEATURES)).astype(np.uint8)
            codes_t = jnp.asarray(codes.T.copy())
            want = np.asarray(hist_ops.build_histogram(
                jnp.asarray(codes), jnp.asarray(gh), bins))
            got = np.asarray(pallas_hist.build_histogram_pallas_t(
                codes_t, jnp.asarray(gh), bins, interpret=interpret))
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-2)
            out[f"hist_float_{bins}"] = verdict
            want = np.asarray(hist_ops.build_histogram_quantized(
                jnp.asarray(codes), jnp.asarray(ghq), bins))
            got = np.asarray(pallas_hist.build_histogram_pallas_quantized_t(
                codes_t, jnp.asarray(ghq), bins, interpret=interpret))
            assert np.array_equal(got, want)
            out[f"hist_int8_{bins}"] = verdict
        return out

    # -- --all legs: which first benchmark cells can run at all --------
    def _side_leg(self, params, x, y, iters=2, **ds_kw):
        bst, models, per_tree = self.train(params, x, y, iters, **ds_kw)
        learner = bst._gbdt.learner
        assert models and all(t.num_leaves > 1 for t in models)
        pred = np.asarray(bst.predict(x[:1024]))
        assert np.isfinite(pred).all()
        return {"learner": type(learner).__name__,
                "strategy": getattr(learner, "strategy", None),
                "trees": len(models), "grow_dispatches_per_tree": per_tree,
                "leaves": sorted({t.num_leaves for t in models})}

    def leg_categorical(self):
        x, y, _ = make_higgs_like(
            self.side_rows, N_FEATURES, n_cat=8, card=64)
        return self._side_leg(
            self.params(), x, y,
            categorical_feature=list(range(N_FEATURES - 8, N_FEATURES)))

    def leg_lambdarank(self):
        # ceil: 3,277 queries x 20 docs stays above the compact threshold
        x, y, group, _ = make_ranking_like(
            -(-self.side_rows // 20), 20, N_FEATURES)
        return self._side_leg(self.params(objective="lambdarank"), x, y,
                              group=group)

    def leg_multiclass(self):
        x, y, _ = make_higgs_like(
            self.side_rows, N_FEATURES, n_classes=5)
        return self._side_leg(
            self.params(objective="multiclass", num_class=5), x, y)

    def leg_quantized(self):
        x, y, _ = make_higgs_like(self.side_rows, N_FEATURES)
        return self._side_leg(
            self.params(quantized_grad=True, grad_bits=8), x, y)

    def leg_stream(self):
        x, y, _ = make_higgs_like(self.side_rows, N_FEATURES)
        return self._side_leg(self.params(stream_mode="chunked"), x, y)

    # -- runner --------------------------------------------------------
    def run(self, legs):
        for name in legs:
            print(f"[leg {name}] start", flush=True)
            t0 = time.time()
            try:
                out = getattr(self, "leg_" + name)()
            except Exception:
                # only the --all legs are independent of each other: a
                # failed one is recorded and makes the exit code non-zero
                if name not in ALL_LEGS:
                    raise
                traceback.print_exc()
                out = "FAIL"
            self.results[name] = out
            status = ("FAIL" if out == "FAIL" else
                      "SKIP" if isinstance(out, str) else "PASS")
            print(f"[leg {name}] {status} in {time.time() - t0:.1f}s "
                  f"(not a metric): {json.dumps(out)}", flush=True)


def main():
    args = parse_args()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__}  platform={device['platform']}  "
          f"device_kind={device['kind']}  device_count={device['count']}",
          flush=True)
    if args.cpu_rehearsal:
        if device["platform"] != "cpu":
            sys.exit("--cpu-rehearsal needs JAX_PLATFORMS=cpu")
        print("CPU REHEARSAL at tiny sizes: checks the script and the "
              "control flow, says nothing about the chip", flush=True)
    elif device["platform"] != "tpu":
        print("no TPU: chip_smoke.py does not fall back to "
              f"{device['platform']} (rehearse with --cpu-rehearsal)",
              file=sys.stderr)
        sys.exit(1)
    lgbm_env = sorted(k for k in os.environ if k.startswith("LGBM_TPU_"))
    if lgbm_env:
        sys.exit(f"unset {lgbm_env}: the smoke runs the defaults")

    legs = ([s for s in args.legs.split(",") if s] if args.legs
            else list(DEFAULT_LEGS) + (list(ALL_LEGS) if args.all else []))
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        smoke = Smoke(args, tmp)
        smoke.run(legs)
    failed = [k for k, v in smoke.results.items() if v == "FAIL"]
    if failed:
        print(f"FAILED legs: {failed}", file=sys.stderr)
        sys.exit(1)
    # the leg results get their own line: the last line is the driver's
    # contract and holds exactly "ok" and "device"
    print(json.dumps({"rehearsal": bool(args.cpu_rehearsal),
                      "legs": smoke.results}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
