"""Telemetry subsystem tests: span nesting/export round-trip, counters
under concurrent batcher threads, recorder phase sums vs wall time, the
telemetry=off overhead guard, float-path invariance, and the serving
/metrics Prometheus exposition."""
import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from conftest import make_binary
from lightgbm_tpu import telemetry
from lightgbm_tpu.telemetry import counters, recorder, spans


@pytest.fixture(autouse=True)
def _telemetry_off_after():
    """Telemetry mode is process-wide: every test starts and ends off
    with accumulated state cleared."""
    telemetry.set_mode("off")
    telemetry.reset()
    yield
    telemetry.set_mode("off")
    telemetry.reset()


def _train(params=None, num_boost_round=6, n=600, seed=7):
    x, y = make_binary(n=n, f=10, seed=seed)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "metric": "none"}
    base.update(params or {})
    return lgb.train(base, lgb.Dataset(x, y, free_raw_data=False),
                     num_boost_round=num_boost_round, verbose_eval=False)


# ---------------------------------------------------------------------------
# modes + null hooks

def test_mode_gating_and_null_hooks():
    from jax.profiler import StepTraceAnnotation, TraceAnnotation
    assert telemetry.mode() == "off"
    # off: a hook is the profiler's own annotation and nothing else
    assert type(recorder.phase("x")) is TraceAnnotation
    assert type(recorder.iteration(0)) is StepTraceAnnotation
    assert type(spans.span("x")) is TraceAnnotation
    telemetry.set_mode("summary")
    assert type(recorder.phase("x")) is not TraceAnnotation
    assert type(spans.span("x")) is TraceAnnotation    # the ring needs trace
    telemetry.set_mode("trace")
    assert type(spans.span("x")) is not TraceAnnotation
    with pytest.raises(ValueError):
        telemetry.set_mode("verbose")


def test_config_param_resolution(monkeypatch):
    assert telemetry.resolve_mode("summary") == "summary"
    monkeypatch.setenv("LGBM_TPU_TELEMETRY", "trace")
    assert telemetry.resolve_mode("summary") == "trace"   # env wins
    monkeypatch.delenv("LGBM_TPU_TELEMETRY")
    # invalid param value is rejected at Config level
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError):
        from lightgbm_tpu.config import Config
        Config({"telemetry": "everything"})


# ---------------------------------------------------------------------------
# spans

def test_span_nesting_and_export_roundtrip(tmp_path):
    telemetry.set_mode("trace")
    with spans.span("outer", kind="test"):
        with spans.span("inner_a"):
            time.sleep(0.002)
        with spans.span("inner_b"):
            time.sleep(0.002)
    path = telemetry.dump_trace(str(tmp_path / "trace.json"))
    with open(path) as fh:
        doc = json.load(fh)
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert {"outer", "inner_a", "inner_b"} <= set(evs)
    # dump_trace prepends a process_name metadata row (ph == "M") so
    # multi-rank dumps label themselves in the trace viewer
    assert evs["process_name"]["ph"] == "M"
    for ev in evs.values():
        if ev["ph"] == "M":
            continue
        assert ev["ph"] == "X" and ev["dur"] >= 0 and "ts" in ev
        assert ev["pid"] == os.getpid()
    outer, ia, ib = evs["outer"], evs["inner_a"], evs["inner_b"]
    # nested spans are contained within the outer interval (trace-viewer
    # nesting is inferred exactly from this)
    for inner in (ia, ib):
        assert inner["ts"] >= outer["ts"] - 1
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert outer["args"] == {"kind": "test"}
    # round-trip: clearing empties the ring
    spans.clear()
    assert spans.events() == []


def test_span_ring_is_bounded():
    telemetry.set_mode("trace")
    cap = spans._events.maxlen
    for i in range(cap + 50):
        spans.add_event(f"e{i}", 0.0)
    assert len(spans.events()) == cap


# ---------------------------------------------------------------------------
# counters

def test_counters_concurrent_exactness():
    telemetry.set_mode("summary")
    threads = [threading.Thread(
        target=lambda: [counters.incr("hammer") for _ in range(5000)])
        for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert counters.get("hammer") == 40000


def test_counters_under_concurrent_batcher_threads():
    from lightgbm_tpu.serving import ModelRegistry, ServingApp
    telemetry.set_mode("summary")
    bst = _train(num_boost_round=4, n=400)
    x, _ = make_binary(n=32, f=10, seed=3)
    reg = ModelRegistry(warm_buckets=(4,))
    reg.load(bst)
    app = ServingApp(reg, max_delay_ms=1.0)
    try:
        n_threads, per = 6, 10
        errors = []

        def client():
            try:
                for i in range(per):
                    out = app.predict({"rows": x[i % 8: i % 8 + 2].tolist()})
                    assert out["num_rows"] == 2
            except Exception as exc:   # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=client)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        snap = app.stats.snapshot()
        # every submitted row is accounted exactly once despite
        # concurrent flush/submit interleavings
        assert snap["counters"]["serve_rows"] == n_threads * per * 2
        assert snap["counters"]["serve_requests"] == n_threads * per
        assert "serve_queue_wait" in snap["latency"]
        # hot-path telemetry counters saw the uploads
        assert counters.get("transfer_h2d_bytes") > 0
    finally:
        app.close()


@pytest.mark.parametrize("kinds, feats, on_missing", [
    ([0, 0, 0], [0, 1, 2, 2], 0),      # no feature has a missing type
    ([0, 1, 2], [0, 1, 2, 2], 3),      # zero- and NaN-missing features
    ([2, 2, 2], [], 0),                # a tree that did not split
])
def test_missing_split_counters(kinds, feats, on_missing):
    """`splits` and `splits_on_missing_feature` (PR 29): fed once a tree
    from the split records' features and the features' missing types."""
    from types import SimpleNamespace
    from lightgbm_tpu.models import device_learner as dl
    rec = np.zeros((len(feats), 13), np.float32)
    rec[:, dl.R_FEAT] = feats
    learner = SimpleNamespace(_f_missing_host=np.asarray(kinds))
    before = (counters.get("splits"),
              counters.get("splits_on_missing_feature"))
    dl.DeviceTreeLearner._count_missing_splits(learner, rec)
    assert counters.get("splits") - before[0] == len(feats)
    assert counters.get("splits_on_missing_feature") - before[1] \
        == on_missing


def test_compile_events_shared_counter():
    """The serving tests' XLA ground-truth counter now lives in
    telemetry.counters: a fresh jit compile appends events."""
    import jax
    import jax.numpy as jnp
    events = counters.compile_events()
    before = len(events)
    # a never-before-seen shape+computation forces a real compile
    probe = jax.jit(lambda a: (a * 3.14159).sum() + before)
    probe(jnp.arange(17, dtype=jnp.float32))
    assert len(events) > before
    assert any("compile" in name for name in events[before:])
    secs = counters.compile_seconds()
    assert secs and all(v >= 0 for v in secs.values())


def test_peak_rss_gauge_present():
    snap = counters.snapshot()
    assert snap["gauges"]["peak_rss_bytes"] > 0


# ---------------------------------------------------------------------------
# recorder

def test_recorder_phase_sums_cover_wall():
    """Acceptance: with telemetry=summary the per-iteration phase sum
    covers >=90% of measured iteration wall."""
    telemetry.set_mode("summary")
    bst = _train({"telemetry": "summary"})
    bd = telemetry.phase_breakdown()
    assert bd["iterations"] == 6
    assert bd["wall_s"] > 0
    assert bd["coverage"] is not None and bd["coverage"] >= 0.9, bd
    assert "grow_dispatch" in bd["phases"] or "hist" in bd["phases"]
    assert bst.num_trees() == 6
    # the one-line summary carries the same breakdown + counters
    summary = telemetry.telemetry_summary()
    assert summary["telemetry"] == "summary"
    assert summary["phase_breakdown"]["iterations"] == 6
    json.dumps(summary)     # JSON-able end to end


def test_recorder_last_iteration_and_callback():
    telemetry.set_mode("summary")
    x, y = make_binary(n=400, f=8, seed=11)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "telemetry": "summary"},
              lgb.Dataset(x, y), num_boost_round=3, verbose_eval=False,
              callbacks=[lgb.record_telemetry(period=1)])
    last = recorder.last_iteration()
    assert last is not None and last["iteration"] == 2
    assert last["wall_s"] > 0 and last["phases"]


def test_trace_mode_dumps_training_trace(tmp_path):
    telemetry.set_mode("trace")
    _train({"telemetry": "trace"}, num_boost_round=3, n=400)
    path = telemetry.dump_trace(str(tmp_path / "train.json"))
    with open(path) as fh:
        doc = json.load(fh)
    names = {e["name"] for e in doc["traceEvents"]}
    assert "iteration" in names
    assert names & {"grow_dispatch", "hist", "host_sync"}


# ---------------------------------------------------------------------------
# float-path invariance + overhead guard

def test_float_path_unchanged_by_telemetry():
    """telemetry=summary must not perturb training numerics: the model
    (trees + importances) is byte-for-byte identical to telemetry=off.
    Only the saved `parameters:` section may differ (it echoes the
    telemetry param itself)."""
    def trees_text(bst):
        return bst._gbdt.save_model_to_string(0, -1).split(
            "\nparameters:")[0]
    m_off = trees_text(_train(num_boost_round=5))
    telemetry.set_mode("summary")
    m_sum = trees_text(_train({"telemetry": "summary"},
                              num_boost_round=5))
    assert m_off == m_sum


def test_telemetry_off_overhead_under_2pct(monkeypatch):
    """What `off` costs, as counts (it was a wall-clock A/B, which a
    loaded CPU host cannot hold): N iterations with the mode off leave no
    recorder state, no ring event and no flight-recorder event, and no
    module of telemetry/ reads the clock — a hook is its profiler
    annotation, about a microsecond with no session open. The same
    booster under `summary` then records every iteration."""
    import sys
    from lightgbm_tpu.telemetry import events
    x, y = make_binary(n=2000, f=10, seed=5)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 15,
                       "verbosity": -1}, lgb.Dataset(x, y))
    bst.update()                   # compile; make_fused_step
    _ = bst._gbdt.models
    telemetry.reset()

    real_clock = time.perf_counter
    clock_reads = []

    def counting_clock():
        caller = sys._getframe(1).f_globals.get("__name__", "")
        if caller.startswith("lightgbm_tpu.telemetry"):
            clock_reads.append(caller)
        return real_clock()

    monkeypatch.setattr(time, "perf_counter", counting_clock)
    k = 5
    assert telemetry.mode() == "off"
    for _ in range(k):
        bst.update()
    _ = bst._gbdt.models           # flush any pipelined iteration
    assert clock_reads == []
    breakdown = telemetry.phase_breakdown()
    assert breakdown["iterations"] == 0 and breakdown["phases"] == {}
    assert spans.events() == []
    assert events.counts() == {}
    assert recorder.last_iteration() is None

    telemetry.set_mode("summary")
    for _ in range(k):
        bst.update()
    _ = bst._gbdt.models
    assert clock_reads            # the same sites, now timed
    breakdown = telemetry.phase_breakdown()
    assert breakdown["iterations"] == k
    assert breakdown["phases"]["record_fetch"]["calls"] == k
    assert "host_sync" not in breakdown["phases"]


# ---------------------------------------------------------------------------
# exposition

def test_prometheus_metrics_endpoint_parseable():
    from lightgbm_tpu.serving import ModelRegistry, ServingApp
    telemetry.set_mode("summary")
    bst = _train(num_boost_round=4, n=400)
    x, _ = make_binary(n=8, f=10, seed=3)
    reg = ModelRegistry(warm_buckets=(4,))
    reg.load(bst)
    app = ServingApp(reg, max_delay_ms=1.0)
    try:
        app.predict({"rows": x[:3].tolist()})
        text = app.metrics_text()
    finally:
        app.close()
    # parseable Prometheus text: every sample line is "name[{labels}] value"
    samples = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith("# TYPE ")
            continue
        name, value = line.rsplit(" ", 1)
        samples[name] = float(value)
    assert samples["lgbm_tpu_serve_requests_total"] >= 1
    assert samples["lgbm_tpu_serve_rows_total"] >= 3
    assert "lgbm_tpu_compile_events_total" in samples
    assert "lgbm_tpu_compile_seconds_total" in samples
    assert samples["lgbm_tpu_peak_rss_bytes"] > 0
    assert "lgbm_tpu_predictor_cache_entries" in samples
    # latency histograms render as summaries with quantiles
    assert 'lgbm_tpu_serve_request_seconds{quantile="0.5"}' in samples
    assert samples["lgbm_tpu_serve_request_seconds_count"] >= 1
    assert 'lgbm_tpu_serve_queue_wait_seconds{quantile="0.95"}' in samples


def test_metrics_over_http():
    from lightgbm_tpu.serving import ModelRegistry, ServingApp
    from lightgbm_tpu.serving.server import run_http_server
    import urllib.request
    bst = _train(num_boost_round=4, n=400)
    reg = ModelRegistry(warm_buckets=(1,))
    reg.load(bst)
    app = ServingApp(reg, max_delay_ms=1.0)
    httpd = run_http_server(app, port=0, background=True)
    try:
        host, port = httpd.server_address[:2]
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        assert "lgbm_tpu_compile_events_total" in body
    finally:
        httpd.shutdown()
        httpd.server_close()
        app.close()


# ---------------------------------------------------------------------------
# tier-1 dots guard (tools/check_tier1_dots.py)

def _load_dots_tool():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "check_tier1_dots.py")
    spec = importlib.util.spec_from_file_location("check_tier1_dots", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tier1_dots_guard(tmp_path):
    tool = _load_dots_tool()
    log = ("platform linux -- Python\n"
           "....s..F..x.. [ 10%]\n"
           "..........\n"
           "no dots on this line: 1.5s\n"
           "...... [100%]\n")
    assert tool.count_dots(log) == 26
    ok_log = tmp_path / "ok.log"
    ok_log.write_text(log)
    assert tool.main(["x", str(ok_log), "10"]) == 0
    assert tool.main(["x", str(ok_log), "27"]) == 1       # regression
    empty = tmp_path / "empty.log"
    empty.write_text("collected 0 items\n")
    assert tool.main(["x", str(empty), "1"]) == 2
    assert tool.main(["x", str(tmp_path / "missing.log"), "1"]) == 2
