#!/usr/bin/env python
"""Benchmark: Higgs-style binary classification training throughput.

Mirrors the reference's headline benchmark setup (docs/Experiments.rst:103:
Higgs 10.5M x 28, 255 leaves, 500 iters, 238.5 s on 2x E5-2670v3 =>
22.0M row-trees/sec). We train the same shape of problem (28 features,
255 leaves, 63 bins like the GPU experiments) on a size that fits the bench
budget and report throughput in row-trees/sec vs that baseline.

Runs on whatever device JAX selects and says which: a CPU run happens only
because the caller set JAX_PLATFORMS=cpu (and sized it with BENCH_ROWS /
BENCH_ITERS), and any failure exits non-zero.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
diagnostic fields: "backend" / "device_kind" / "device_count" (from
jax.devices()), "degraded" (true off-TPU — the value is then NOT
comparable to the baseline), "rows", "iters", "valid_auc", and "sec_to_auc"
(wall seconds of update() calls — warmup + first-jit compile included,
see "warmup_secs" — until held-out AUC first reached BENCH_AUC_TARGET;
null if never reached; mirrors the reference's time-to-AUC headline,
docs/Experiments.rst:106: 238.5 s to AUC 0.845154).
"""
import json
import os
import sys
import time

import numpy as np

N_ROWS = int(os.environ.get("BENCH_ROWS", 1_000_000))
N_FEATURES = 28
N_ITERS = int(os.environ.get("BENCH_ITERS", 50))
WARMUP_ITERS = int(os.environ.get("BENCH_WARMUP", 5))
BASELINE_ROWTREES_PER_SEC = 10_500_000 * 500 / 238.505  # reference Higgs CPU
AUC_TARGET = float(os.environ.get("BENCH_AUC_TARGET", 0.75))
EVAL_EVERY = int(os.environ.get("BENCH_EVAL_EVERY", 10))
N_VALID = int(os.environ.get("BENCH_VALID_ROWS", 100_000))


N_CAT = int(os.environ.get("BENCH_CAT_FEATURES", 0))
CAT_CARD = int(os.environ.get("BENCH_CAT_CARD", 64))


def make_higgs_like(n, f, seed=17, w=None, n_cat=0, card=64, n_classes=1):
    """Synthetic stand-in with Higgs-like statistics: mixed informative /
    noise features, moderately separable classes. Pass `w` to draw a new
    sample from the SAME ground-truth function (e.g. a held-out valid set)
    without perturbing the default stream, which (at n_cat=0) is
    bit-identical to the rounds 1-2 training sets. n_cat > 0 converts the
    LAST n_cat columns into categorical features (cardinality `card`)
    with per-category target effects — the Expo/Allstate-style
    categorical-heavy shape (reference docs/Experiments.rst datasets).

    `w` is a `(w_num, cat_tables)` tuple (since round 3; previously a
    bare ndarray) — callers replaying a returned `w_true` must unpack
    it, even at n_cat=0 where `cat_tables` is just `[]`."""
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


def ndcg_at_k(scores, labels, group, k=10):
    """Host NDCG@k over contiguous query blocks (metrics/metric.py
    semantics: 2^rel-1 gains, log2 discounts, ideal-normalized; queries
    with no relevant docs score 1)."""
    out, pos = [], 0
    for cnt in group:
        s = scores[pos:pos + cnt]
        rel = labels[pos:pos + cnt]
        pos += cnt
        top = np.argsort(-s, kind="stable")[:k]
        disc = 1.0 / np.log2(np.arange(2, len(top) + 2))
        dcg = float((((2.0 ** rel[top]) - 1) * disc).sum())
        ideal = np.sort(rel)[::-1][:k]
        idcg = float((((2.0 ** ideal) - 1)
                      * (1.0 / np.log2(np.arange(2, len(ideal) + 2)))).sum())
        out.append(dcg / idcg if idcg > 0 else 1.0)
    return float(np.mean(out))


def host_predict_raw(models, x):
    """Vectorized numpy ensemble traversal (numerical + categorical
    bitset splits; no-NaN data — exactly this bench's generator). Keeps
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


def _run_lambdarank(device, degraded, num_leaves, time_budget, lgb):
    """BENCH_OBJECTIVE=lambdarank scenario: query-grouped synthetic,
    LambdarankNDCG objective, held-out ndcg@10 target in the JSON line
    (ROADMAP item 4 — perf claims beyond binary Higgs). Emits the same
    one-line JSON shape as the Higgs path with `valid_ndcg10` /
    `ndcg_target` / `sec_to_ndcg` standing in for the AUC trio."""
    import lightgbm_tpu  # noqa: F401 - lgb already imported by caller
    docs_q = int(os.environ.get("BENCH_DOCS_PER_QUERY", 20))
    n_queries = max(N_ROWS // docs_q, 10)
    n_rows = n_queries * docs_q
    nq_valid = max(min(N_VALID, n_rows // 10) // docs_q, 5)
    ndcg_target = float(os.environ.get("BENCH_NDCG_TARGET", 0.72))
    x, y, group, w_true = make_ranking_like(n_queries, docs_q, N_FEATURES)
    xv, yv, gv, _ = make_ranking_like(nq_valid, docs_q, N_FEATURES,
                                      seed=4242, w=w_true)
    params = {
        "objective": "lambdarank",
        "num_leaves": num_leaves,
        "learning_rate": 0.1,
        "max_bin": 63,
        "metric": "none",
        "verbosity": -1,
        "min_data_in_leaf": 20,
    }
    quantized = os.environ.get("BENCH_QUANTIZED", "0") == "1"
    if quantized:
        params.update(quantized_grad=True,
                      grad_bits=int(os.environ.get("BENCH_GRAD_BITS", 8)))
    ds = lgb.Dataset(x, y, group=group)
    ds.construct()
    booster = lgb.Booster(params=params, train_set=ds)
    t_warm = time.time()
    for _ in range(WARMUP_ITERS):
        booster.update()
    warmup_secs = time.time() - t_warm
    sys.stderr.write(f"lambdarank warmup ({WARMUP_ITERS} iters) "
                     f"{warmup_secs:.1f}s\n")
    t_train, sec_to_ndcg, done_iters = 0.0, None, 0
    t_loop0 = time.time()
    for i in range(N_ITERS):
        t0 = time.time()
        booster.update()
        t_train += time.time() - t0
        done_iters = i + 1
        stop = (time_budget > 0 and time.time() - t_loop0 >= time_budget
                and done_iters >= 3)
        if (sec_to_ndcg is None and not stop and done_iters < N_ITERS
                and done_iters % EVAL_EVERY == 0):
            nd = ndcg_at_k(host_predict_raw(booster._gbdt.models, xv),
                           yv, gv, k=10)
            if nd >= ndcg_target:
                sec_to_ndcg = round(warmup_secs + t_train, 3)
                sys.stderr.write(f"iter {done_iters}: ndcg@10 {nd:.4f} "
                                 f">= {ndcg_target}\n")
        if stop:
            break
    valid_ndcg = ndcg_at_k(host_predict_raw(booster._gbdt.models, xv),
                           yv, gv, k=10)
    if sec_to_ndcg is None and valid_ndcg >= ndcg_target:
        sec_to_ndcg = round(warmup_secs + t_train, 3)
    sys.stderr.write(f"valid ndcg@10 ({nq_valid} queries): "
                     f"{valid_ndcg:.4f}\n")
    rowtrees_per_sec = (n_rows * done_iters / t_train
                        if t_train > 0 else 0.0)
    from lightgbm_tpu import telemetry
    print(json.dumps({
        "metric": "lambdarank_train_throughput",
        "value": round(rowtrees_per_sec, 1),
        "unit": "row-trees/sec",
        "vs_baseline": 0.0,          # no reference ranking baseline
        "degraded": degraded,
        **device,
        "rows": n_rows,
        "queries": n_queries,
        "docs_per_query": docs_q,
        "iters": done_iters,
        "num_leaves": num_leaves,
        "valid_ndcg10": round(valid_ndcg, 5),
        "ndcg_target": ndcg_target,
        "sec_to_ndcg": sec_to_ndcg,
        "warmup_secs": round(warmup_secs, 3),
        "quantized": quantized,
        "telemetry": telemetry.mode(),
        "phase_breakdown": (telemetry.phase_breakdown()
                            if telemetry.enabled() else None),
    }))


def main():
    t_setup = time.time()
    import jax
    dev = jax.devices()[0]
    device = {"backend": dev.platform, "device_kind": dev.device_kind,
              "device_count": len(jax.devices())}
    num_leaves = 255
    time_budget = float(os.environ.get("BENCH_TIME_BUDGET", 0))
    # BENCH_STRATEGY: explicit growth-strategy lever for the trajectory
    # (masked | compact | chunk)
    if os.environ.get("BENCH_STRATEGY"):
        os.environ["LGBM_TPU_STRATEGY"] = os.environ["BENCH_STRATEGY"]
    import lightgbm_tpu as lgb
    sys.stderr.write(f"device: {device}\n")
    knobs = {k: os.environ[k] for k in
             ("LGBM_TPU_STRATEGY", "LGBM_TPU_WINDOW_STEP",
              "LGBM_TPU_PACK_WORDS", "LGBM_TPU_PALLAS",
              "LGBM_TPU_DP_REDUCE", "LGBM_TPU_PARTITION",
              "LGBM_TPU_CHUNK", "LGBM_TPU_CHUNK_NO_FUSE_HIST",
              "LGBM_TPU_HIST_CHUNK", "LGBM_TPU_TELEMETRY",
              "BENCH_CAT_FEATURES", "BENCH_QUANTIZED",
              "BENCH_GRAD_BITS", "BENCH_STRATEGY",
              "BENCH_TELEMETRY", "BENCH_STREAM",
              "BENCH_CHUNK_ROWS", "BENCH_DIST_SHARD",
              "BENCH_GROW_PROGRAM", "BENCH_NUM_CLASS") if k in os.environ}
    sys.stderr.write(f"rows={N_ROWS} iters={N_ITERS} knobs={knobs}\n")

    # an off-TPU run is not comparable to the 22M row-trees/s baseline:
    # flag it machine-readably
    degraded = device["backend"] != "tpu"
    # ranking scenario: BENCH_OBJECTIVE=lambdarank swaps in the
    # query-grouped synthetic + ndcg@10 gate
    if os.environ.get("BENCH_OBJECTIVE", "binary") == "lambdarank":
        return _run_lambdarank(device, degraded, num_leaves,
                               time_budget, lgb)
    # large-K multiclass scenario (ROADMAP item 5b): BENCH_NUM_CLASS=K
    # trains K per-class trees per iteration; combined with
    # BENCH_GROW_PROGRAM=fused_tree and the masked strategy all K trees
    # dispatch as ONE vmap-batched program (device_learner.train_batched)
    num_class = int(os.environ.get("BENCH_NUM_CLASS", "1"))
    n_valid = min(N_VALID, max(N_ROWS // 10, 1000))
    x, y, w_true = make_higgs_like(N_ROWS, N_FEATURES, n_cat=N_CAT,
                                   card=CAT_CARD, n_classes=num_class)
    xv, yv, _ = make_higgs_like(n_valid, N_FEATURES, seed=4242, w=w_true,
                                n_cat=N_CAT, card=CAT_CARD,
                                n_classes=num_class)
    params = {
        "objective": "binary",
        "num_leaves": num_leaves,
        "learning_rate": 0.1,
        "max_bin": 63,
        "metric": "none",
        "verbosity": -1,
        "min_data_in_leaf": 20,
    }
    if num_class > 1:
        params.update(objective="multiclass", num_class=num_class)
    # growth-loop formulation lever (per_split | fused_tree): the A/B
    # for the single-program tree-growth trajectory (BENCH_r06)
    grow_program = os.environ.get("BENCH_GROW_PROGRAM", "")
    if grow_program:
        params.update(grow_program=grow_program)
    # quantized-gradient A/B lever: BENCH_QUANTIZED=1 trains with int
    # histograms (one i8 contraction instead of the bf16 hi/lo pair)
    quantized = os.environ.get("BENCH_QUANTIZED", "0") == "1"
    grad_bits = int(os.environ.get("BENCH_GRAD_BITS", 8))
    if quantized:
        params.update(quantized_grad=True, grad_bits=grad_bits)
    hist_dtype = f"int{grad_bits}" if quantized else "bf16x2"
    # out-of-core streaming A/B levers: BENCH_STREAM=chunked|goss turns
    # on the host-wire H2D pipeline (io/stream.py); BENCH_CHUNK_ROWS
    # sets stream_chunk_rows (0 derives from LGBM_TPU_CHUNK)
    stream_mode = os.environ.get("BENCH_STREAM", "off")
    stream_chunk_rows = int(os.environ.get("BENCH_CHUNK_ROWS", 0))
    if stream_mode != "off":
        params.update(stream_mode=stream_mode,
                      stream_chunk_rows=stream_chunk_rows)
        if stream_mode == "goss":
            params.update(boosting="goss")
    # telemetry lever: BENCH_TELEMETRY=summary|trace (or the package-wide
    # LGBM_TPU_TELEMETRY env) turns on the per-iteration phase recorder;
    # the breakdown is emitted as the `phase_breakdown` JSON field
    if os.environ.get("BENCH_TELEMETRY"):
        params.update(telemetry=os.environ["BENCH_TELEMETRY"])
    # row-sharded ingest lever: BENCH_DIST_SHARD=rows|replicated routes
    # dataset construction through distributed ingest (single-process
    # that is plain local construction, byte-identical to Dataset(x, y);
    # under a multi-process bootstrap each host keeps only its rows when
    # =rows) and reports the stored host bytes in the JSON line
    dist_shard = os.environ.get("BENCH_DIST_SHARD", "")
    if dist_shard:
        params.update(dist_shard_mode=dist_shard)
    cat_cols = list(range(N_FEATURES - N_CAT, N_FEATURES)) if N_CAT else []
    if dist_shard:
        from lightgbm_tpu.distributed import ingest
        ds = ingest.wrap_train_set(ingest.load_sharded(
            x, label=y, params=params, categorical=cat_cols or None))
    else:
        ds = lgb.Dataset(x, y, categorical_feature=cat_cols or None)
    ds.construct()
    sys.stderr.write(f"setup {time.time()-t_setup:.1f}s\n")

    booster = lgb.Booster(params=params, train_set=ds)
    t_warm = time.time()
    for wi in range(WARMUP_ITERS):
        booster.update()
        sys.stderr.write(
            f"warmup iter {wi+1}/{WARMUP_ITERS} at "
            f"{time.time()-t_warm:.1f}s\n")
        sys.stderr.flush()
    warmup_secs = time.time() - t_warm
    sys.stderr.write(
        f"warmup ({WARMUP_ITERS} iters, incl. compile) {warmup_secs:.1f}s\n")
    from lightgbm_tpu import telemetry
    if telemetry.enabled():
        # breakdown should cover the TIMED loop only: drop the warmup
        # iterations' phases (first-jit compile stalls live there)
        telemetry.recorder.reset()
    if telemetry.events.enabled():
        # same for the flight recorder: ring/counters restart at the
        # timed loop (the JSONL sink stays open — warmup records remain
        # on disk for forensics, the summary block below excludes them)
        telemetry.events.reset()
        telemetry.watchdogs.reset()

    def gate_score(models, xx):
        # multiclass: the models list interleaves classes iteration-major,
        # so class 0's ensemble is models[0::num_class]; the gate is the
        # one-vs-rest AUC of the class-0 margin (same ground-truth
        # separability as the binary label)
        trees = models[0::num_class] if num_class > 1 else models
        return host_predict_raw(trees, xx)

    yv_gate = (yv == 0).astype(np.float64) if num_class > 1 else yv
    y_gate = (y == 0).astype(np.float64) if num_class > 1 else y

    # timed loop: the clock accumulates update() wall only; held-out AUC is
    # evaluated off-clock every EVAL_EVERY iters to find sec_to_auc (the
    # reference's headline is time-to-AUC, docs/Experiments.rst:106).
    # sec_to_auc counts the warmup iterations' wall too (their trees also
    # move the AUC), so it includes the first-jit compile cost.
    t_train = 0.0
    sec_to_auc = None
    done_iters = 0
    prog_every = 1 if N_ITERS <= 60 else max(1, N_ITERS // 50)
    t_loop0 = time.time()
    for i in range(N_ITERS):
        t0 = time.time()
        booster.update()
        t_train += time.time() - t0
        done_iters = i + 1
        if (i + 1) % prog_every == 0:
            # per-iter progress: a killed/deadlined run still leaves a
            # readable partial-throughput trail in the battery log
            sys.stderr.write(
                f"iter {i+1}/{N_ITERS} train_wall={t_train:.1f}s\n")
            sys.stderr.flush()
        # time-capped run (explicit BENCH_TIME_BUDGET): stop once the
        # budget is spent, but never before 3 iters of throughput signal. The post-loop final eval still scores the
        # model, so a gate first met on the stopping iteration is
        # credited there (sec_to_auc fallback below).
        # budget counts the whole loop wall (off-clock evals included) so
        # a time-capped run actually finishes near its cap
        stop = (time_budget > 0 and time.time() - t_loop0 >= time_budget
                and i + 1 >= 3)
        # the final-model eval below is the last scheduled check, so skip
        # the mid-loop one on the last/stopping iteration (no duplicate
        # predict)
        if (sec_to_auc is None and EVAL_EVERY and not stop
                and i + 1 < N_ITERS and (i + 1) % EVAL_EVERY == 0):
            mid_auc = rank_auc(gate_score(booster._gbdt.models, xv),
                               yv_gate)
            if mid_auc >= AUC_TARGET:
                sec_to_auc = round(warmup_secs + t_train, 3)
                sys.stderr.write(
                    f"iter {i+1}: valid AUC {mid_auc:.4f} >= "
                    f"{AUC_TARGET} at {sec_to_auc}s train wall "
                    f"(incl. {warmup_secs:.1f}s warmup+compile)\n")
        if stop:
            sys.stderr.write(
                f"time budget {time_budget:.0f}s reached after "
                f"{done_iters} iters\n")
            break
    iters_per_sec = done_iters / t_train if t_train > 0 else 0.0
    # K trees land per iteration in multiclass, so row-trees/s scales by K
    rowtrees_per_sec = N_ROWS * iters_per_sec * max(num_class, 1)

    # growth-strategy + working-row diagnostics for the trajectory: the
    # packed strategies report the physical row width (codes words + gh
    # section + id, x4 bytes); masked has no reordered row buffer
    learner = booster._gbdt.learner
    strategy = getattr(learner, "strategy", type(learner).__name__)
    # transfer-overlap fraction of the streaming pipeline (1.0 = every
    # H2D byte hidden behind dispatch/compute; None when not streaming)
    shard = getattr(learner, "_shard", None)
    overlap = shard.overlap_fraction() if shard is not None else None
    bytes_per_row = None
    if getattr(learner, "codes_pack", None) is not None:
        gh_words = 3
        if getattr(learner, "quant_bits", 0):
            gh_words = 1 if quantized and strategy in ("compact", "chunk") \
                and params.get("bagging_freq", 0) == 0 else 2
        bytes_per_row = (int(learner.codes_pack.shape[1]) + gh_words + 1) * 4

    valid_auc = rank_auc(gate_score(booster._gbdt.models, xv), yv_gate)
    if sec_to_auc is None and valid_auc >= AUC_TARGET:
        sec_to_auc = round(warmup_secs + t_train, 3)
    sys.stderr.write(f"valid AUC ({len(yv)} held-out): {valid_auc:.4f}\n")
    # sanity: the model must actually learn
    train_auc = rank_auc(
        gate_score(booster._gbdt.models, x[:100_000]), y_gate[:100_000])
    sys.stderr.write(f"train AUC (100k sample): {train_auc:.4f}\n")
    assert train_auc > 0.60, "model failed to learn"

    print(json.dumps({
        "metric": "higgs_like_train_throughput",
        "value": round(rowtrees_per_sec, 1),
        "unit": "row-trees/sec",
        "vs_baseline": 0.0 if degraded else
            round(rowtrees_per_sec / BASELINE_ROWTREES_PER_SEC, 4),
        "degraded": degraded,
        **device,
        "rows": N_ROWS,
        "iters": done_iters,
        "num_leaves": num_leaves,
        "cat_features": N_CAT,
        "valid_auc": round(valid_auc, 5),
        "auc_target": AUC_TARGET,
        "sec_to_auc": sec_to_auc,
        "warmup_secs": round(warmup_secs, 3),
        # histogram-path diagnostics so the trajectory distinguishes the
        # float (bf16 hi/lo) and quantized (integer) pipelines
        "quantized": quantized,
        "hist_dtype": hist_dtype,
        "strategy": strategy,
        "bytes_per_row": bytes_per_row,
        # single-program growth trajectory (BENCH_r06): the loop
        # formulation under test plus the dispatch-count proof —
        # grow_dispatches_per_tree is ~1 for whole-tree device programs
        # (1/K with the vmap-batched multiclass program), ~num_leaves
        # for the serial host loop
        "num_class": num_class,
        "grow_program": str(getattr(
            booster._gbdt.config, "grow_program", "per_split")),
        "grow_dispatches": telemetry.counters.get("grow_dispatches"),
        "grow_trees": telemetry.counters.get("grow_trees"),
        "grow_dispatches_per_tree": round(telemetry.counters.get(
            "grow_dispatches_per_tree"), 4),
        # out-of-core streaming diagnostics (stream_mode off => overlap
        # null): transfer_overlap_fraction is 1 - stream_wait/stream
        # wall from the shard's own counters
        "stream_mode": stream_mode,
        # distributed-ingest diagnostics (BENCH_DIST_SHARD lever; null
        # otherwise): peak_host_bytes is this rank's stored binned
        # matrix + label/weight — the number rows-sharding shrinks
        "shard_mode": dist_shard or None,
        "peak_host_bytes": (
            int(getattr(ds._inner, "_ingest_host_bytes", 0)) or
            (int(ds._inner.binned.nbytes) + int(np.asarray(y).nbytes))
            if dist_shard and getattr(ds, "_inner", None) is not None
            and getattr(ds._inner, "binned", None) is not None else None),
        "chunk_rows": (int(shard.chunk_rows) if shard is not None
                       else stream_chunk_rows),
        "transfer_overlap_fraction": (round(overlap, 4)
                                      if overlap is not None else None),
        # per-iteration phase accounting over the timed loop (telemetry
        # recorder; None with telemetry off). `coverage` is phase seconds
        # over iteration wall — the >=90% acceptance metric.
        "telemetry": telemetry.mode(),
        "phase_breakdown": (telemetry.phase_breakdown()
                            if telemetry.enabled() else None),
        # flight-recorder digest (telemetry/events.py; null with events
        # off): where the JSONL landed plus the headline health signals
        # a fleet dashboard wants without parsing the stream
        "events_file": telemetry.events.sink_path(),
        "run_report": ({
            "events": sum(telemetry.events.counts().values()),
            "stragglers": telemetry.events.counts().get("straggler", 0),
            "watchdog_fires": sum(telemetry.watchdogs.fired().values()),
            "overlap": (round(overlap, 4) if overlap is not None
                        else None),
        } if telemetry.events.enabled() else None),
    }))
    telemetry.events.flush()


if __name__ == "__main__":
    # hard deadline: die with a traceback (exit != 0) before any outer
    # timeout kills the process silently
    deadline = int(os.environ.get("BENCH_DEADLINE", 0))
    if deadline > 0:
        import signal

        def _on_alarm(signum, frame):
            raise TimeoutError(f"bench exceeded {deadline}s deadline")
        signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(deadline)
    main()
