#!/usr/bin/env python
"""Serving microbench: online-inference latency + throughput.

Trains a small model, loads it into the serving stack (registry warm-up +
micro-batcher), then drives closed-loop traffic from several client
threads and reports tail latency and row throughput.

Prints ONE JSON line: {"metric", "value",
"unit", "vs_baseline"} plus diagnostics ("p50_ms", "p95_ms", "p99_ms",
"compiles_after_warm", "backend", ...). vs_baseline is null: the source
paper benchmarks training only; this record seeds the serving baseline.

Env knobs: SERVE_BENCH_SECS (default 3), SERVE_BENCH_CLIENTS (8),
SERVE_BENCH_ROWS_PER_REQ (1), SERVE_BENCH_MAX_BATCH (256),
SERVE_BENCH_DELAY_MS (2), SERVE_BENCH_TRAIN_ROWS (5000),
SERVE_BENCH_LEAVES (31), SERVE_BENCH_TREES (10) — raise the last three
on a real accelerator for a production-shaped ensemble; the defaults
keep a cold-CPU run inside a CI budget (serving latency is dominated by
dispatch + batch shape, not ensemble size, once compiled).

Cold-start measurement (the fleet restart story): SERVE_BENCH_CACHE_DIR
points the registry at a persistent export cache
(fleet/export_cache.py). The JSON line then carries
`time_to_first_prediction_s` (model load -> first answered request) and
`export_cache_hit` (true when the warm-up restored serialized
executables instead of compiling). Run twice with the same dir: the
first run populates, the second demonstrates the zero-compile restart.
`LGBM_TPU_SERVE_NO_STAGING=1` A/Bs the staged-buffer flush path.

The JSON line also carries the serving observability A/B, measured on
THIS one process so jit caches stay warm (the same flip pattern the
training telemetry guard uses), from raw latency samples (the
LatencyHistogram's log2 buckets are too coarse for a 2% comparison):
`trace_overhead_pct` is the warm-tail cost of sampled request tracing
(rate 0.1) + drift windows over summary-mode serving — the marginal
bill for this PR-era observability; `telemetry_overhead_pct` is the
same configuration against a fully telemetry-dark process (so it
includes summary mode's pre-existing recorder/counter cost).
Interleaved mode triples + median-of-segments + a p90..p99 tail band
keep both numbers stable on a noisy shared box; `trace_overhead_ms` /
`telemetry_overhead_ms` carry the same deltas in absolute terms for
dual-gate (<N% OR <N ms) guards. `SERVE_BENCH_TRACE_REQS` (default
400) sizes each segment; 0 skips the A/B.
"""
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import lightgbm_tpu as lgb
from lightgbm_tpu.serving import ModelRegistry, ServingApp
from lightgbm_tpu.serving.stats import LatencyHistogram

DUR_SECS = float(os.environ.get("SERVE_BENCH_SECS", 3))
CLIENTS = int(os.environ.get("SERVE_BENCH_CLIENTS", 8))
ROWS_PER_REQ = int(os.environ.get("SERVE_BENCH_ROWS_PER_REQ", 1))
MAX_BATCH = int(os.environ.get("SERVE_BENCH_MAX_BATCH", 256))
DELAY_MS = float(os.environ.get("SERVE_BENCH_DELAY_MS", 2.0))
TRAIN_ROWS = int(os.environ.get("SERVE_BENCH_TRAIN_ROWS", 5000))
N_LEAVES = int(os.environ.get("SERVE_BENCH_LEAVES", 31))
N_TREES = int(os.environ.get("SERVE_BENCH_TREES", 10))
TRACE_REQS = int(os.environ.get("SERVE_BENCH_TRACE_REQS", 400))
N_FEATURES = 28


def _trace_overhead(app, bst, x):
    """Warm-tail A/B on one process through the full predict() path
    (router + SLO + drift + batcher). Returns a dict of overhead
    fields: marginal (tracing + drift over summary mode) and total
    (same vs telemetry off), each as a percentage and as an absolute
    ms delta. See module docstring for the methodology."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.serving import trace as serve_trace
    from lightgbm_tpu.serving.drift import DriftMonitor

    baseline = bst._gbdt.drift_baseline()
    # full-batch requests flush immediately (no max_delay timer in the
    # measurement), so the A/B compares execute+overhead, not jitter
    block = x[:MAX_BATCH]
    drift_mon = DriftMonitor(baseline) if baseline else None

    # production-shaped sampling, set once — tracing is additionally
    # gated on events.enabled(), so the telemetry-mode flip below turns
    # it on/off per request without resetting the sampling accumulator
    serve_trace.configure(0.1)

    # three-point measurement: 0 = telemetry off, 1 = summary mode
    # only, 2 = summary + sampled tracing + drift windows. 2-vs-1 is
    # the marginal cost of the serving-path observability; 2-vs-0 is
    # the total bill against a telemetry-dark process.
    def one(mode: int) -> float:
        if mode == 2:
            telemetry.set_mode("summary")
            app.drift = drift_mon
        elif mode == 1:
            telemetry.set_mode("summary")
            app.drift = None
        else:
            telemetry.set_mode("off")
            app.drift = None
        t = time.perf_counter()
        app.predict({"rows": block})
        return time.perf_counter() - t

    def tail(lat) -> float:
        # warm tail estimate: mean of the p90..p99 band. A single p99
        # order statistic on a shared box flips by tens of percent on
        # whichever scheduler spike straddles the cut; averaging the
        # band keeps the tail focus with ~30x the samples behind it
        lat = sorted(lat)
        lo, hi = int(0.90 * len(lat)), max(int(0.99 * len(lat)), 1)
        return sum(lat[lo:hi]) / max(hi - lo, 1)

    for _ in range(32):                    # discard: settles the path
        one(False), one(True)
    # interleaved off/on pairs (scheduler + CPU-frequency noise hits
    # both sides alike), in several segments; the reported overhead is
    # the MEDIAN of per-segment p99 deltas — a single p99 order
    # statistic on a shared box is at the mercy of whichever ~1%-rate
    # scheduler spike straddles the cut, the median of five is not
    # GC pauses are ms-scale at ~1% request rate — exactly the p99
    # neighborhood. They are environment, not telemetry: park the
    # collector for the measurement, collect between segments.
    import gc
    marginal, total = [], []
    marginal_ms, total_ms = [], []
    for _seg in range(5):
        gc.collect()
        gc.disable()
        try:
            lat = {0: [], 1: [], 2: []}
            for i in range(TRACE_REQS):
                # alternate triple order: background work kicked off
                # by one mode (drift worker wake) spills into whichever
                # request follows — split that evenly
                for m in ([0, 1, 2] if i % 2 else [2, 1, 0]):
                    lat[m].append(one(m))
        finally:
            gc.enable()
        t0, t1, t2 = tail(lat[0]), tail(lat[1]), tail(lat[2])
        marginal.append((t2 - t1) / max(t1, 1e-9) * 100.0)
        total.append((t2 - t0) / max(t0, 1e-9) * 100.0)
        marginal_ms.append((t2 - t1) * 1e3)
        total_ms.append((t2 - t0) * 1e3)
    telemetry.set_mode("off")
    serve_trace.configure(0.0)
    app.drift = None
    if drift_mon is not None:
        drift_mon.close()
    med = lambda v: sorted(v)[len(v) // 2]  # noqa: E731
    # absolute deltas ride along so guards can use the PR-5 dual gate
    # (<N% OR <N ms): on a sub-ms serving path a scheduler blip is a
    # large percentage but a tiny absolute cost
    return {"trace_overhead_pct": round(med(marginal), 2),
            "trace_overhead_ms": round(med(marginal_ms), 4),
            "telemetry_overhead_pct": round(med(total), 2),
            "telemetry_overhead_ms": round(med(total_ms), 4)}


def main() -> None:
    r = np.random.RandomState(0)
    x = r.randn(TRAIN_ROWS, N_FEATURES).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.3 * r.randn(len(x)) > 0)
    bst = lgb.train(
        {"objective": "binary", "num_leaves": N_LEAVES, "verbosity": -1,
         "max_bin": 63},
        lgb.Dataset(x, y.astype(np.float64), free_raw_data=False),
        num_boost_round=N_TREES, verbose_eval=False)

    cache_dir = os.environ.get("SERVE_BENCH_CACHE_DIR", "")
    export_cache = None
    if cache_dir:
        from lightgbm_tpu.fleet import ExportCache
        export_cache = ExportCache(cache_dir)
    registry = ModelRegistry(
        warm_buckets=(ROWS_PER_REQ, MAX_BATCH), export_cache=export_cache)
    app = ServingApp(registry, max_batch=MAX_BATCH, max_delay_ms=DELAY_MS,
                     max_queue_rows=MAX_BATCH * 16)
    t0 = time.perf_counter()
    registry.load(bst)
    warm_secs = time.perf_counter() - t0
    compiles_warm = registry.predictor.compile_count
    # time-to-first-prediction: load + warm-up + one real answered
    # request — the cold-start number a restarting replica cares about
    app.batcher.submit(x[:ROWS_PER_REQ], timeout_ms=10_000)
    ttfp_secs = time.perf_counter() - t0
    export_cache_hit = bool(
        export_cache is not None
        and export_cache.last_restore.get("restored", 0) > 0
        and compiles_warm == 0)

    hist = LatencyHistogram()
    hist_lock = threading.Lock()
    stop = threading.Event()
    counts = [0] * CLIENTS
    errors = [0] * CLIENTS

    def client(ci: int) -> None:
        rs = np.random.RandomState(ci)
        while not stop.is_set():
            req = x[rs.randint(0, len(x) - ROWS_PER_REQ)
                    :][:ROWS_PER_REQ]
            t = time.perf_counter()
            try:
                app.batcher.submit(req, timeout_ms=10_000)
            except Exception:
                errors[ci] += 1
                continue
            with hist_lock:
                hist.record(time.perf_counter() - t)
            counts[ci] += 1

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(CLIENTS)]
    bench_t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(DUR_SECS)
    stop.set()
    for t in threads:
        t.join(timeout=5.0)
    elapsed = time.perf_counter() - bench_t0
    overhead = (_trace_overhead(app, bst, x) if TRACE_REQS > 0
                else {"trace_overhead_pct": None, "trace_overhead_ms": None,
                      "telemetry_overhead_pct": None,
                      "telemetry_overhead_ms": None})
    app.close()

    total_reqs = sum(counts)
    snap = hist.snapshot()
    print(json.dumps({
        "metric": "serve_throughput",
        "value": round(total_reqs * ROWS_PER_REQ / max(elapsed, 1e-9), 1),
        "unit": "rows/sec",
        "vs_baseline": None,
        "p50_ms": round(snap["p50_ms"], 3),
        "p95_ms": round(snap["p95_ms"], 3),
        "p99_ms": round(snap["p99_ms"], 3),
        "mean_ms": round(snap["mean_ms"], 3),
        "requests": total_reqs,
        "errors": sum(errors),
        "clients": CLIENTS,
        "rows_per_request": ROWS_PER_REQ,
        "max_batch": MAX_BATCH,
        "max_delay_ms": DELAY_MS,
        "warmup_secs": round(warm_secs, 3),
        "time_to_first_prediction_s": round(ttfp_secs, 3),
        "export_cache_hit": export_cache_hit,
        "export_cache_restore": (dict(export_cache.last_restore)
                                 if export_cache is not None else None),
        "compiles_after_warm":
            registry.predictor.compile_count - compiles_warm,
        **overhead,
        "staging": not bool(os.environ.get("LGBM_TPU_SERVE_NO_STAGING")),
        "batches": app.stats.get("serve_batches"),
        "backend": jax.default_backend(),
    }))


if __name__ == "__main__":
    main()
