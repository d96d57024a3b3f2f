"""Flight recorder + fleet aggregation tests (PR 9 observability).

Fast tier-1 coverage: event staging/sink/ring semantics, watchdog
monitors over synthetic iteration records, the straggler detector's
pure ingest path, per-version serving metrics, the PR-7 distributed
counters, run-report rendering from a real run's JSONL, the phase-docs
lint, off-mode byte-identity and the events-ON warm overhead guard.
The two-process straggler acceptance (delay_ms on rank 1 -> rank-0
`straggler` event + skew table) is slow+distributed-tagged.
"""
import importlib.util
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from conftest import make_binary
from lightgbm_tpu import telemetry
from lightgbm_tpu.telemetry import aggregate, counters, events, watchdogs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_off_after():
    """Telemetry (mode, counters, events sink, watchdog windows) is
    process-wide: every test starts and ends off and cleared."""
    telemetry.set_mode("off")
    telemetry.reset()
    events.set_sink(None)
    yield
    telemetry.set_mode("off")
    telemetry.reset()
    events.set_sink(None)


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _train(params=None, num_boost_round=6, n=500, valid=False, **kw):
    x, y = make_binary(n=n, f=10, seed=7)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "metric": "binary_logloss" if valid else "none"}
    base.update(params or {})
    ds = lgb.Dataset(x, y, free_raw_data=False)
    if valid:
        kw.update(valid_sets=[ds], valid_names=["training"])
    return lgb.train(base, ds, num_boost_round=num_boost_round,
                     verbose_eval=False, **kw)


# ---------------------------------------------------------------------------
# events: gating, staging, sink, ring

def test_events_off_is_noop():
    assert not events.enabled()
    events.emit("checkpoint", iteration=1)
    events.iteration_record({"iteration": 0, "wall_s": 0.1})
    assert events.events() == []
    assert events.counts() == {}


def test_events_follow_telemetry_mode():
    telemetry.set_mode("summary")
    assert events.enabled()
    telemetry.set_mode("off")
    assert not events.enabled()


def test_events_staging_attach_and_jsonl_sink(tmp_path):
    telemetry.set_mode("summary")
    path = str(tmp_path / "ev.jsonl")
    events.set_sink(path)
    events.iteration_record({"iteration": 0, "wall_s": 0.01})
    # staged record is visible in the ring but not yet on disk
    assert events.events("iteration")[0]["iteration"] == 0
    events.attach_metrics([("valid_1", "auc", 0.9, True)])
    events.emit("checkpoint", iteration=0, path="x")  # discrete, direct
    events.iteration_record({"iteration": 1, "wall_s": 0.01})  # flushes 0
    events.flush()                                             # flushes 1
    lines = [json.loads(l) for l in open(path)]
    assert [l["kind"] for l in lines] == ["checkpoint", "iteration",
                                          "iteration"]
    it0 = [l for l in lines if l["kind"] == "iteration"][0]
    assert it0["metrics"] == {"valid_1:auc": 0.9}
    # reset clears ring/counts but keeps the sink open (bench warmup)
    events.reset()
    assert events.counts() == {} and events.sink_path() == path
    events.emit("fault", fault="nan_grad")
    assert sum(1 for _ in open(path)) == 4


def test_events_ring_bounded():
    telemetry.set_mode("summary")
    cap = events._ring.maxlen
    assert cap >= 64
    for i in range(cap + 50):
        events.emit("fault", i=i)
    ring = events.events()
    assert len(ring) == cap
    assert ring[-1]["i"] == cap + 49           # newest win
    assert events.counts()["fault"] == cap + 50  # counts see everything


# ---------------------------------------------------------------------------
# watchdogs

def _rec(i, wall=0.01, overlap=None, gnorm=None):
    rec = {"iteration": i, "wall_s": wall}
    if overlap is not None:
        rec["stream"] = {"overlap_fraction": overlap}
    if gnorm is not None:
        rec["grad_norms"] = {"grad_l2": gnorm}
    return rec


def test_watchdogs_fire_on_anomalies():
    telemetry.set_mode("summary")
    watchdogs.configure("")            # defaults
    for i in range(6):                 # healthy baseline (>= MIN_SAMPLES)
        watchdogs.observe(_rec(i, wall=0.01, overlap=0.9, gnorm=5.0))
    assert watchdogs.fired() == {}
    watchdogs.observe(_rec(6, wall=0.2))           # 20x median wall
    watchdogs.observe(_rec(7, overlap=0.1))        # < 0.5x median overlap
    watchdogs.observe(_rec(8, gnorm=500.0))        # 100x median grad norm
    assert watchdogs.fired() == {"slow_iter": 1, "overlap": 1,
                                 "grad_spike": 1}
    kinds = {(e["monitor"]) for e in events.events("watchdog")}
    assert kinds == {"slow_iter", "overlap", "grad_spike"}
    assert counters.get("watchdog_fires") == 3


def test_watchdogs_config_off_and_custom(monkeypatch):
    telemetry.set_mode("summary")
    watchdogs.configure("off")
    for i in range(10):
        watchdogs.observe(_rec(i, wall=10.0 if i > 6 else 0.01))
    assert watchdogs.fired() == {}
    # env-driven custom factor + arm_loss_guard
    monkeypatch.setenv("LGBM_TPU_WATCHDOGS",
                       "slow_iter=50,arm_loss_guard=1")
    watchdogs.reset()                  # drops cached config -> re-parse
    assert watchdogs.loss_guard_requested()
    for i in range(6):
        watchdogs.observe(_rec(i, wall=0.01))
    watchdogs.observe(_rec(6, wall=0.2))   # 20x < custom 50x: no fire
    assert watchdogs.fired() == {}


def test_arm_loss_guard_appends_callback(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_WATCHDOGS", "arm_loss_guard=1")
    watchdogs.reset()
    telemetry.set_mode("summary")
    bst = _train(num_boost_round=3, valid=True)
    assert bst.current_iteration() == 3   # guard observed, never rolled


# ---------------------------------------------------------------------------
# aggregate: pure ingest + straggler detection + exposition

def _summary(rank, arrival, iters=4, mean=0.02):
    return {"rank": rank, "iteration": 7, "arrival_ts": arrival,
            "iters": iters, "iter_wall_s": mean * iters,
            "mean_iter_s": mean, "phases": {"hist": 0.01},
            "counters": {"dist_wire_bytes": 100 * (rank + 1),
                         "collective_dispatches": 2}}


def test_aggregate_ingest_detects_straggler(monkeypatch):
    telemetry.set_mode("summary")
    monkeypatch.setenv("LGBM_TPU_STRAGGLER_MS", "100")
    t0 = 1000.0
    table = aggregate._ingest([_summary(0, t0), _summary(1, t0 + 0.01),
                               _summary(2, t0 + 0.5)])
    by_rank = {r["rank"]: r for r in table}
    assert not by_rank[0]["straggler"] and not by_rank[1]["straggler"]
    assert by_rank[2]["straggler"]
    assert by_rank[2]["arrival_skew_s"] == pytest.approx(0.49, abs=1e-6)
    stragglers = events.events("straggler")
    assert len(stragglers) == 1 and stragglers[0]["rank"] == 2
    fleet = events.events("fleet")
    assert len(fleet) == 1 and len(fleet[0]["skew_table"]) == 3
    assert "phases" not in fleet[0]["skew_table"][0]
    assert counters.get("stragglers_detected") == 1
    # fleet counters are summed across ranks and exposed as fleet_*
    extra_counters, extra_gauges = aggregate.prometheus_extras()
    assert extra_counters["fleet_dist_wire_bytes"] == 600
    assert extra_counters["fleet_collective_dispatches"] == 6
    assert extra_gauges['rank_arrival_skew_seconds{rank="2"}'] \
        == pytest.approx(0.49, abs=1e-6)
    assert extra_gauges["fleet_stragglers_detected"] == 1
    # and rendered with labels in the rank-0 Prometheus exposition
    text = telemetry.prometheus_text()
    assert "lgbm_tpu_fleet_dist_wire_bytes_total 600" in text
    assert 'lgbm_tpu_rank_mean_iter_seconds{rank="0"}' in text


def test_aggregate_disabled_paths(monkeypatch):
    # single-process: never a collective, whatever the knobs say
    telemetry.set_mode("summary")
    assert not aggregate.enabled()
    assert aggregate.maybe_tick(7) is None
    monkeypatch.setenv("LGBM_TPU_AGG_PERIOD", "0")
    assert aggregate.period() == 0 and not aggregate.enabled()


# ---------------------------------------------------------------------------
# PR-7 distributed counters (satellite): exact wire arithmetic + gauges

def test_dist_wire_byte_arithmetic_single_process():
    from lightgbm_tpu.io.distributed import _allgather_host_bytes
    payload = b"x" * 23
    b0 = counters.get("dist_wire_bytes")
    g0 = counters.get("dist_allgathers")
    assert _allgather_host_bytes(payload) == [payload]
    # single process: wire = max_len * nproc + 8 * nproc, where the
    # framed payload carries the 8-byte iteration-epoch header
    assert counters.get("dist_wire_bytes") - b0 == (len(payload) + 8) + 8
    assert counters.get("dist_allgathers") - g0 == 1


def test_dist_gauges_in_exposition():
    # bootstrap.initialize sets these; the exposition must render them
    counters.set_gauge("dist_rank", 0)
    counters.set_gauge("dist_process_count", 2)
    text = telemetry.prometheus_text()
    lines = dict(l.rsplit(" ", 1) for l in text.strip().splitlines()
                 if not l.startswith("#"))
    assert float(lines["lgbm_tpu_dist_rank"]) == 0.0
    assert float(lines["lgbm_tpu_dist_process_count"]) == 2.0


# ---------------------------------------------------------------------------
# real training runs: records, resilience events, invariance, overhead

def test_training_iteration_records(tmp_path):
    telemetry.set_mode("summary")
    path = str(tmp_path / "run.jsonl")
    events.set_sink(path)
    _train(num_boost_round=5, valid=True)
    lines = [json.loads(l) for l in open(path)]
    iters = [l for l in lines if l["kind"] == "iteration"]
    assert [r["iteration"] for r in iters] == list(range(5))
    for r in iters:
        assert r["wall_s"] > 0 and r["phases"]
        assert r["metrics"]["training:binary_logloss"] > 0
    # logloss decreases over the run
    curve = [r["metrics"]["training:binary_logloss"] for r in iters]
    assert curve[-1] < curve[0]


def test_generic_path_records_grad_norms(tmp_path):
    # a custom objective forces the generic path, where gradients are
    # host-visible and the record carries their norm summary (the fused
    # step computes gradients in-program — no norms there)
    def fobj(preds, ds):
        y = ds.get_label()
        p = 1.0 / (1.0 + np.exp(-preds))
        return p - y, p * (1.0 - p)
    telemetry.set_mode("summary")
    events.set_sink(str(tmp_path / "g.jsonl"))
    _train(num_boost_round=3, fobj=fobj)
    events.flush()
    iters = events.events("iteration")
    assert iters and all(
        r.get("grad_norms", {}).get("grad_l2", 0) > 0 for r in iters)
    assert all(r["grad_norms"]["hess_l2"] > 0 for r in iters)


def test_fault_and_skip_iter_events():
    from lightgbm_tpu.resilience import faults
    telemetry.set_mode("summary")
    faults.install("nan_grad@iter=1,frac=0.5")
    try:
        bst = _train({"on_nonfinite": "skip_iter"}, num_boost_round=4)
    finally:
        faults.clear()
    # 4 update calls, one skipped: one fewer tree
    assert bst.current_iteration() == 3
    c = events.counts()
    assert c.get("fault", 0) >= 1 and c.get("skip_iter", 0) >= 1
    skip = events.events("skip_iter")[0]
    assert skip["reason"] == "non_finite"


def test_float_path_byte_identical_with_events_on(tmp_path, monkeypatch):
    def trees_text(bst):
        return bst._gbdt.save_model_to_string(0, -1).split(
            "\nparameters:")[0]
    m_off = trees_text(_train(num_boost_round=5))
    telemetry.set_mode("summary")
    events.set_sink(str(tmp_path / "inv.jsonl"))
    m_on = trees_text(_train({"telemetry": "summary"}, num_boost_round=5))
    assert m_off == m_on
    # full deep-trace stack (span ring + bundle capture armed) must not
    # perturb the model bytes either
    monkeypatch.delenv("LGBM_TPU_XLA_TRACE", raising=False)
    monkeypatch.setenv("LGBM_TPU_BUNDLE_DIR", str(tmp_path / "bundles"))
    telemetry.set_mode("trace")
    m_trace = trees_text(_train({"telemetry": "trace"}, num_boost_round=5))
    assert m_off == m_trace


def test_events_on_overhead_under_2pct(tmp_path):
    """What the flight recorder adds, as counts (it was a wall-clock A/B
    against everything off): with events on, one iteration record an
    iteration — no more — on the sink, each with the phases the fused
    iteration is split into; with everything off, none."""
    x, y = make_binary(n=2000, f=10, seed=5)
    ds = lgb.Dataset(x, y)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 15,
                       "verbosity": -1}, ds)
    bst.add_valid(lgb.Dataset(x[:200], y[:200], reference=ds), "v")
    bst.update()                        # compile
    k = 5
    for _ in range(k):
        bst.update()
    _ = bst._gbdt.models
    assert events.counts() == {}
    telemetry.set_mode("summary")
    sink = tmp_path / "ovh.jsonl"
    events.set_sink(str(sink))
    first = bst._gbdt.iter
    for _ in range(k):
        bst.update()
    _ = bst._gbdt.models
    events.flush()                      # the last record is staged
    events.set_sink(None)
    assert events.counts() == {"iteration": k}
    records = [json.loads(line) for line in sink.read_text().splitlines()]
    assert [r["kind"] for r in records] == ["iteration"] * k
    assert [r["iteration"] for r in records] == list(range(first, first + k))
    for rec in records:
        assert {"feature_mask", "mask_sync", "record_fetch",
                "valid_update", "tree_replay",
                "grow_dispatch"} <= set(rec["phases"])
        assert "host_sync" not in rec["phases"]


# ---------------------------------------------------------------------------
# serving: per-version counters + swap/warmup events

def test_serving_per_version_metrics_and_events():
    from lightgbm_tpu.serving import ModelRegistry, ServingApp
    from lightgbm_tpu.serving.registry import ModelNotFound
    telemetry.set_mode("summary")
    bst = _train(num_boost_round=3, n=300)
    x, _ = make_binary(n=8, f=10, seed=3)
    reg = ModelRegistry(warm_buckets=(4,))
    ver = reg.load(bst)
    assert events.counts().get("serve_warmup") == 1
    swap = events.events("serve_swap")[0]
    assert swap["version"] == ver and swap["previous"] is None
    app = ServingApp(reg, max_delay_ms=1.0)
    try:
        for _ in range(2):
            app.predict({"rows": x[:3].tolist()})
        with pytest.raises(ModelNotFound):
            app.predict({"rows": x[:3].tolist(), "version": "nope"})
        snap = app.stats_snapshot()
        text = app.metrics_text()
    finally:
        app.close()
    assert snap["versions"][ver]["requests"] == 2
    assert snap["versions"][ver]["errors"] == 0
    assert snap["versions"][ver]["latency"]["count"] == 2
    assert snap["versions"]["nope"] == {
        "requests": 1, "errors": 1, "latency": None}
    samples = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith("# TYPE ")
            continue
        name, value = line.rsplit(" ", 1)
        samples[name] = float(value)
    assert samples[
        f'lgbm_tpu_serve_version_requests_total{{version="{ver}"}}'] == 2
    assert samples[
        'lgbm_tpu_serve_version_errors_total{version="nope"}'] == 1
    assert samples[
        f'lgbm_tpu_serve_version_request_seconds_count{{version="{ver}"}}'
    ] == 2
    q50 = (f'lgbm_tpu_serve_version_request_seconds'
           f'{{version="{ver}",quantile="0.5"}}')
    assert q50 in samples


# ---------------------------------------------------------------------------
# tools: run report + phase-docs lint

def test_run_report_from_real_run(tmp_path):
    telemetry.set_mode("summary")
    path = str(tmp_path / "run.jsonl")
    events.set_sink(path)
    _train(num_boost_round=5, valid=True)
    events.emit("checkpoint", iteration=4, path="m.ckpt")
    events.flush()
    rr = _load_tool("run_report")
    s = rr.summarize(path)
    assert s["iterations"] == 5 and s["wall_s"] > 0
    assert "training:binary_logloss" in s["metrics"]
    md = rr.render(s)
    for section in ("# Training run report", "## Phase waterfall",
                    "## Metric curves", "## Event timeline",
                    "binary_logloss", "checkpoint"):
        assert section in md, f"missing {section!r}"
    out = tmp_path / "report.md"
    assert rr.main([path, "-o", str(out)]) == 0
    assert out.read_text() == md


def test_run_report_skew_table_rendering(tmp_path):
    # synthetic fleet event -> skew table section (the rank-0 JSONL
    # shape the two-process test produces)
    path = tmp_path / "fleet.jsonl"
    rows = [{"rank": 0, "iteration": 3, "iters": 4, "mean_iter_s": 0.02,
             "arrival_skew_s": -0.15, "straggler": False},
            {"rank": 1, "iteration": 3, "iters": 4, "mean_iter_s": 0.02,
             "arrival_skew_s": 0.15, "straggler": True}]
    path.write_text(
        json.dumps({"kind": "fleet", "ts": 1.0, "ranks": 2,
                    "iteration": 3, "skew_table": rows}) + "\n"
        + "{torn line")
    rr = _load_tool("run_report")
    s = rr.summarize(str(path))
    assert s["skew_table"] == rows     # torn line skipped, table found
    md = rr.render(s)
    assert "## Per-rank skew" in md and "YES" in md


def test_phase_docs_lint_in_sync():
    cpd = _load_tool("check_phase_docs")
    undocumented, phantom = cpd.check()
    assert undocumented == set(), (
        f"add these phases to docs/Observability.md: {undocumented}")
    assert phantom == set(), (
        f"documented phases never recorded: {phantom}")
    assert cpd.main() == 0


# ---------------------------------------------------------------------------
# slow: two-process straggler acceptance
# ---------------------------------------------------------------------------

_STRAGGLER_WORKER = r"""
import os, sys
import numpy as np
rank = int(sys.argv[1]); port = sys.argv[2]
import jax
from lightgbm_tpu.distributed import bootstrap, ingest
bootstrap.initialize(f"127.0.0.1:{port}", 2, rank)
assert bootstrap.is_distributed()
import lightgbm_tpu as lgb
from lightgbm_tpu import engine

r = np.random.RandomState(7)
n, f = 1200, 6
x = r.randn(n, f)
y = (1.5 * x[:, 0] - x[:, 1] + r.randn(n) * 0.5 > 0).astype(np.float64)
params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
          "max_bin": 63, "min_data_in_leaf": 20, "tree_learner": "data",
          "metric": "none"}
ds = ingest.wrap_train_set(ingest.load_sharded(x, label=y, params=params))
engine.train(dict(params), ds, num_boost_round=4, verbose_eval=False)
"""


@pytest.mark.slow
@pytest.mark.distributed
def test_two_process_straggler_detection(tmp_path):
    """Acceptance: delay_ms injected on rank 1 -> rank 0 emits a
    `straggler` event naming rank 1 and the run report renders the
    per-rank skew table from rank 0's JSONL alone."""
    script = tmp_path / "worker.py"
    script.write_text(_STRAGGLER_WORKER)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    ev_paths = [tmp_path / f"r{r}.jsonl" for r in range(2)]
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ""
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["LGBM_TPU_TELEMETRY"] = "summary"
        env["LGBM_TPU_EVENTS"] = str(ev_paths[r])
        env["LGBM_TPU_AGG_PERIOD"] = "2"
        env["LGBM_TPU_STRAGGLER_MS"] = "100"
        if r == 1:
            # 300 ms per-iteration delay at the engine's train_iter
            # fault site; with 2 ranks the median splits it into a
            # +/-150 ms arrival skew -> over the 100 ms threshold
            env["LGBM_TPU_FAULT_SPEC"] = "delay_ms=300"
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), str(port)],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
            text=True))
    for p in procs:
        _, err = p.communicate(timeout=600)
        assert p.returncode == 0, err[-3000:]
    lines = [json.loads(l) for l in open(ev_paths[0])]
    stragglers = [l for l in lines if l["kind"] == "straggler"]
    assert stragglers, "rank 0 never flagged the delayed rank"
    assert all(e["rank"] == 1 for e in stragglers)
    assert all(e["arrival_skew_s"] > 0.1 for e in stragglers)
    fleet = [l for l in lines if l["kind"] == "fleet"]
    assert fleet and len(fleet[-1]["skew_table"]) == 2
    # the run report renders the skew table from rank 0's JSONL alone
    rr = _load_tool("run_report")
    md = rr.render(rr.summarize(str(ev_paths[0])))
    assert "## Per-rank skew" in md and "YES" in md
    # rank 1's own stream has iteration records but no straggler verdict
    r1_kinds = {json.loads(l)["kind"] for l in open(ev_paths[1])}
    assert "iteration" in r1_kinds and "straggler" not in r1_kinds
