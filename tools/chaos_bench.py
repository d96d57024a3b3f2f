#!/usr/bin/env python
"""Chaos bench: guard overhead, kill-and-resume parity, faulted recovery.

Emits ONE JSON line (`chaos_bench`) like the other tools/ benches:

* ``guard_overhead_pct`` — per-iteration cost of ``on_nonfinite``
  guarding on a CLEAN run (the sentry is one fused isfinite lane +
  a scalar fetch; the acceptance budget is < 2%).
* ``resume_parity`` — training checkpointed at the midpoint and
  resumed produces bit-identical model text to the uninterrupted run.
* ``faulted_completed`` / ``auc_delta`` — a run with NaN gradients
  injected mid-training under ``on_nonfinite=rollback`` completes
  within ``auc_delta <= 0.005`` of the clean run.
* ``collective_retries`` / ``collective_dispatches`` — telemetry
  counters from the collective-retry path, exercised by a
  ``fail_collective@n=2`` probe through ``faults.run_collective``
  (transient failures must be retried, counted, and survive).

``python tools/chaos_bench.py dist_kill`` runs the elastic-training
scenario instead (one ``dist_kill`` JSON line): a two-process
localhost run under supervision (``tools/dist_smoke.py`` plumbing),
rank 1 hard-killed mid-train via the ``kill_rank@iter=`` fault verb;
reports the survivor's detection latency, the recovery outcome
(shrink to single-host + resume from the last rank-0 checkpoint), and
whether the recovered model text is bit-identical to a single-host run
resumed from that same checkpoint. The group runs with summary
telemetry and a bundle root, so the scenario also reports the
postmortem bundles left behind (the victim's ``kill_rank`` capture and
the survivor's pre-teardown ``rank_failure`` capture) and whether
tools/run_report.py can render a critical path from the survivor's
bundle alone.

``python tools/chaos_bench.py fleet_kill`` runs the serving-fleet
scenario (one ``fleet_kill`` JSON line): a 3-replica in-process fleet
behind the fleet gateway (tools/serve_storm.py plumbing) under mixed-
priority storm traffic, one replica hard-killed at the halfway mark.
Reports gateway ejections/retries and asserts the client-visible
error rate stays below the fleet's own shed rate.

Usage: python tools/chaos_bench.py [dist_kill|fleet_kill]
Env:   CHAOS_ROWS (6000), CHAOS_FEATURES (20), CHAOS_ITERS (24),
       CHAOS_WARMUP (4), CHAOS_LEAVES (15) — defaults sized for a
       1-core CPU CI host; raise them on real hardware. The dist_kill
       scenario uses the DIST_* knobs of tools/dist_smoke.py.
"""
import json
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import lightgbm_tpu as lgb                      # noqa: E402
from lightgbm_tpu import engine                 # noqa: E402
from lightgbm_tpu.callback import checkpoint    # noqa: E402
from lightgbm_tpu.resilience import faults      # noqa: E402
from lightgbm_tpu.telemetry import counters as telem_counters  # noqa: E402

N = int(os.environ.get("CHAOS_ROWS", 6000))
F = int(os.environ.get("CHAOS_FEATURES", 20))
ITERS = int(os.environ.get("CHAOS_ITERS", 24))
WARMUP = int(os.environ.get("CHAOS_WARMUP", 4))
LEAVES = int(os.environ.get("CHAOS_LEAVES", 15))


def make_data(seed=7):
    r = np.random.RandomState(seed)
    x = r.randn(N, F)
    logit = x[:, 0] * 1.5 - x[:, 1] + 0.5 * x[:, 2] * x[:, 3]
    y = (logit + r.randn(N) * 0.5 > 0).astype(np.float64)
    return x, y


def auc(scores, label):
    order = np.argsort(scores)
    lab = label[order]
    n1 = lab.sum()
    n0 = len(lab) - n1
    ranks = np.arange(1, len(lab) + 1)
    return float((ranks[lab > 0].sum() - n1 * (n1 + 1) / 2) / (n0 * n1))


def measure_overhead(x, y, k=None):
    """Per-iteration cost of the non-finite sentry on a clean run,
    measured on ONE booster: warm up, time k guard-off iterations, flip
    the sentry on (it lives OUTSIDE the compiled device step, so no jit
    cache is invalidated), burn one iteration to compile the tiny
    finite-reduce lane, time k guard-on iterations. A fresh booster per
    config would recompile its fused step inside the timed window and
    measure XLA, not the guard."""
    k = k or max(4, (ITERS - WARMUP - 1) // 2)
    params = {"objective": "binary", "num_leaves": LEAVES,
              "verbosity": -1}
    bst = lgb.Booster(params, lgb.Dataset(x, y, free_raw_data=False))

    def timed(n):
        t0 = time.monotonic()
        for _ in range(n):
            bst.update()
        _ = bst._gbdt.models    # flush any pipelined fused iteration
        return (time.monotonic() - t0) / n

    for _ in range(WARMUP):
        bst.update()
    _ = bst._gbdt.models
    t_base = timed(k)
    bst._gbdt.config.on_nonfinite = "rollback"
    bst.update()                # compile the isfinite reduction lane
    _ = bst._gbdt.models
    t_guard = timed(k)
    return t_base, t_guard


# -- dist_kill scenario -------------------------------------------------
# elastic-training kill probe; rank semantics in the worker:
#   0 .. world-1 — the supervised group (the LAST rank installs
#                  kill_rank@iter=kill_iter)
#   -1           — the baseline resuming from the same checkpoint on a
#                  virtual mesh sized like the post-shrink group (the
#                  caller sets --xla_force_host_platform_device_count)
_KILL_WORKER = r"""
import json, os, sys, time
import numpy as np
rank = int(sys.argv[1]); port = sys.argv[2]; out = sys.argv[3]
ckpt_dir = sys.argv[4]; kill_iter = int(sys.argv[5])
N, F, ITERS, LEAVES = (int(v) for v in sys.argv[6:10])
world = int(sys.argv[10]); shard_mode = sys.argv[11]
import jax
from lightgbm_tpu.distributed import bootstrap, ingest, supervisor
if rank >= 0:
    bootstrap.initialize(f"127.0.0.1:{port}", world, rank, supervise=True)
    supervisor.start_supervision(heartbeat_ms=100,
                                 collective_timeout_ms=30000)
import lightgbm_tpu as lgb
from lightgbm_tpu import engine
from lightgbm_tpu.callback import checkpoint
from lightgbm_tpu.resilience import faults
from lightgbm_tpu.telemetry import counters

r = np.random.RandomState(7)
x = r.randn(N, F)
y = (1.5 * x[:, 0] - x[:, 1] + r.randn(N) * 0.5 > 0).astype(np.float64)
params = {"objective": "binary", "num_leaves": LEAVES, "verbosity": -1,
          "max_bin": 63, "min_data_in_leaf": 20, "tree_learner": "data",
          "metric": "none", "on_rank_failure": "shrink",
          "dist_shard_mode": shard_mode}
if rank < 0:
    # baseline: fresh train resumed from the SAME checkpoint on a
    # virtual mesh with as many devices as the post-shrink group has —
    # same mesh shape => bit-identical continuation
    src = os.path.join(ckpt_dir, sys.argv[12])
    bst = engine.train(dict(params), lgb.Dataset(x, y),
                       num_boost_round=ITERS, verbose_eval=False,
                       resume_from=src)
else:
    if rank == world - 1:
        faults.install(f"kill_rank@iter={kill_iter}")
    ds = ingest.wrap_train_set(ingest.load_sharded(x, label=y,
                                                   params=params))
    bst = engine.train(params, ds, num_boost_round=ITERS,
                       verbose_eval=False,
                       callbacks=[checkpoint(ckpt_dir,
                                             checkpoint_freq=2)])
payload = {"model": bst.model_to_string(),
           "shrinks": counters.get("shrinks"),
           "world_after": bootstrap.process_count(),
           "rank_failures": counters.get("rank_failures"),
           "heartbeat_probes": counters.get("heartbeat_probes"),
           "shrink_unix": counters.get("last_shrink_unix")}
with open(out, "w") as fh:
    json.dump(payload, fh)
"""


def _bundle_report(root):
    """Inventory the postmortem bundles a kill scenario left behind:
    completeness via run_report's manifest validator, plus whether the
    survivor's pre-teardown bundle ALONE yields a rendered critical
    path (the bundle is the whole input — no event stream)."""
    import run_report                               # tools/ on sys.path
    _, index, skipped = run_report._resolve_bundle_dir(root)
    reasons = sorted({str(row.get("reason")) for row in index})
    report_cp = False
    for row in index:
        if row.get("reason") != "rank_failure":
            continue
        summ = run_report.summarize(os.path.join(root, row["name"]))
        report_cp = bool(summ["critical_path"]) \
            and bool(summ["trace_digest"])
        break
    return {"complete": len(index), "torn": len(skipped),
            "reasons": reasons,
            "kill_bundle": "kill_rank" in reasons,
            "pre_teardown_bundle": "rank_failure" in reasons,
            "report_from_bundle_ok": report_cp}


def _kill_scenario(world, shard_mode):
    """One kill-and-continue measurement: `world` supervised processes,
    the last rank dies mid-run, the survivors shrink to world-1 and
    finish the boosting budget; the baseline resumes the same
    checkpoint on a (world-1)-device virtual mesh. Returns the JSON
    payload dict."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import subprocess
    import dist_smoke                           # noqa: E402 — plumbing
    kill_iter = 3
    n, f = dist_smoke.N, dist_smoke.F
    iters, leaves = max(6, dist_smoke.ITERS * 2), dist_smoke.LEAVES
    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="dist_kill_") as tmp:
        script = os.path.join(tmp, "worker.py")
        with open(script, "w") as fh:
            fh.write(_KILL_WORKER)
        ckpt_dir = os.path.join(tmp, "ckpt")
        port = dist_smoke._free_port()
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = (dist_smoke.REPO + os.pathsep
                             + env.get("PYTHONPATH", ""))
        env["XLA_FLAGS"] = ""            # 1 device per process
        # deep-trace stack: per-iteration aggregation feeds rank 0's
        # timeline store; the bundle root collects the victim's
        # kill_rank capture and the survivor's pre-teardown capture
        bundle_dir = os.path.join(tmp, "bundles")
        env["LGBM_TPU_TELEMETRY"] = "summary"
        env["LGBM_TPU_AGG_PERIOD"] = "1"
        env["LGBM_TPU_BUNDLE_DIR"] = bundle_dir
        outs = [os.path.join(tmp, f"r{i}.json") for i in range(world)]
        args = [ckpt_dir, kill_iter, n, f, iters, leaves, world,
                shard_mode]
        procs = [subprocess.Popen(
            [sys.executable, script, str(r), str(port), outs[r]]
            + [str(a) for a in args],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
            text=True) for r in range(world)]
        # the victim's observed exit stamps t_kill for detection latency
        victim = procs[world - 1]
        t_kill = None
        while t_kill is None:
            if victim.poll() is not None:
                t_kill = time.time()
            else:
                time.sleep(0.002)
        errs = []
        for p in procs[:-1]:
            _, err = p.communicate(timeout=600)
            errs.append(err)
        victim.communicate(timeout=60)
        for i, p in enumerate(procs[:-1]):
            if p.returncode != 0:
                raise RuntimeError(
                    f"survivor {i} failed:\n{errs[i][-3000:]}")
        kill_code = victim.returncode
        with open(outs[0]) as fh:
            r0 = json.load(fh)
        # baseline: resume from the checkpoint the recovery used — the
        # newest one at kill time (kill at iteration `kill_iter`,
        # freq 2 => iteration kill_iter - 1) — on world-1 devices
        ckpt_name = f"ckpt_iter_{kill_iter - 1:07d}.ckpt"
        envb = dict(env)
        for k in ("LGBM_TPU_TELEMETRY", "LGBM_TPU_AGG_PERIOD",
                  "LGBM_TPU_BUNDLE_DIR"):
            envb.pop(k, None)       # baseline: plain resume, no capture
        if world > 2:
            envb["XLA_FLAGS"] = ("--xla_force_host_platform_device_count"
                                 f"={world - 1}")
        vout = os.path.join(tmp, "baseline.json")
        dist_smoke._run(script, [-1, 0, vout] + args + [ckpt_name], envb)
        with open(vout) as fh:
            base = json.load(fh)
        bundles = _bundle_report(bundle_dir)
    detect_ms = (None if not r0.get("shrink_unix") else
                 round((r0["shrink_unix"] - t_kill) * 1e3, 1))
    return {
        "rows": n, "features": f, "iters": iters,
        "world": world, "survivors": world - 1,
        "shard_mode": shard_mode,
        "kill_iter": kill_iter, "kill_code": kill_code,
        "detection_ms": detect_ms,
        "recovered": bool(r0.get("shrinks") == 1 and r0["model"]
                          and int(r0.get("world_after", 0)) == world - 1),
        "rank_failures": int(r0.get("rank_failures", 0)),
        "heartbeat_probes": int(r0.get("heartbeat_probes", 0)),
        "parity_vs_resume": bool(r0["model"] == base["model"]),
        "bundles": bundles,
        "wall_secs": round(time.time() - t0, 1),
    }


def dist_kill_main():
    """Kill scenarios, one JSON line each: the 2-process shrink-to-
    single-host path (`dist_kill`) and the 3-process rows-sharded
    N-1 path (`dist_kill_n1`: survivors re-form a 2-process group
    in-process and `ingest.reshard` redistributes the dead rank's
    rows). CHAOS_DIST_WORLDS=2 skips the 3-process scenario."""
    two = _kill_scenario(2, "replicated")
    two["parity_vs_single_host_resume"] = two.pop("parity_vs_resume")
    print(json.dumps({"dist_kill": two}))
    if os.environ.get("CHAOS_DIST_WORLDS", "3") != "2":
        print(json.dumps({"dist_kill_n1": _kill_scenario(3, "rows")}))


def fleet_kill_main():
    """Serving-fleet chaos (`fleet_kill` JSON line): a 3-replica
    in-process fleet (tools/serve_storm.py plumbing) under mixed-
    priority storm load loses one replica cold at the halfway mark.
    The gateway must notice (connect failure -> ejection), retries
    must land on the survivors, and the client-visible error rate must
    stay below the fleet's own shed rate — losing a replica should
    cost less than ordinary admission control does."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "serve_storm",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "serve_storm.py"))
    storm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(storm)

    secs = float(os.environ.get("CHAOS_FLEET_SECS", 4.0))
    fleet = storm.build_fleet(3, booster=storm.train_storm_model())
    retries0 = int(telem_counters.get("gateway_retries"))
    ejections0 = int(telem_counters.get("gateway_ejections"))
    victim = {}
    try:
        time.sleep(0.2)
        point = storm.run_storm(
            fleet.gw_url, secs, clients=8, rows_per_req=4,
            stable=fleet.stable,
            mid_hook=lambda: victim.update(
                url=fleet.kill_replica(1), at_s=round(secs / 2, 2)))
        stats = fleet.gateway.stats()
    finally:
        fleet.stop()

    retries = int(telem_counters.get("gateway_retries")) - retries0
    ejections = int(telem_counters.get("gateway_ejections")) - ejections0
    victim_rep = next((r for r in stats["replicas"]
                       if r["url"] == victim.get("url")), {})
    shed_total = sum(point["shed"].values())
    shed_rate = shed_total / point["requests"] if point["requests"] else 0.0
    print(json.dumps({"fleet_kill": {
        "replicas": 3, "victim": victim, "secs": point["secs"],
        "requests": point["requests"], "ok": point["ok"],
        "rows_per_s": point["rows_per_s"], "p99_ms": point["p99_ms"],
        "errors": point["errors"], "error_rate": point["error_rate"],
        "shed": point["shed"], "shed_rate": round(shed_rate, 4),
        "gateway_retries": retries, "gateway_ejections": ejections,
        "victim_ejected": bool(not victim_rep.get("healthy", True)
                               or ejections >= 1),
        "retries_landed": bool(retries >= 1 and point["ok"] > 0),
        "errors_below_shed": bool(point["errors"] < max(shed_total, 1)),
    }}))


def main():
    x, y = make_data()
    faults.clear()

    # -- guard overhead on the clean path -------------------------------
    t_base, t_guard = measure_overhead(x, y)
    overhead_pct = 100.0 * (t_guard - t_base) / max(t_base, 1e-12)

    # -- kill-and-resume parity ----------------------------------------
    half = max(2, ITERS // 2)
    ckpt_dir = tempfile.mkdtemp(prefix="chaos_ckpt_")
    try:
        params = {"objective": "binary", "num_leaves": LEAVES,
                  "verbosity": -1}
        full = engine.train(dict(params), lgb.Dataset(x, y),
                            num_boost_round=ITERS, verbose_eval=False)
        engine.train(dict(params), lgb.Dataset(x, y),
                     num_boost_round=half, verbose_eval=False,
                     callbacks=[checkpoint(ckpt_dir,
                                           checkpoint_freq=half)])
        resumed = engine.train(dict(params), lgb.Dataset(x, y),
                               num_boost_round=ITERS, verbose_eval=False,
                               resume_from=ckpt_dir)
        parity = (full._gbdt.save_model_to_string(0, -1)
                  == resumed._gbdt.save_model_to_string(0, -1))
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    # -- faulted recovery ----------------------------------------------
    a_clean = auc(full.predict(x), y)
    faults.install(f"nan_grad@iter={half},frac=0.05")
    params_rb = {"objective": "binary", "num_leaves": LEAVES,
                 "verbosity": -1, "on_nonfinite": "rollback"}
    faulted = engine.train(params_rb, lgb.Dataset(x, y),
                           num_boost_round=ITERS, verbose_eval=False)
    faults.clear()
    preds = faulted.predict(x)
    a_faulted = auc(preds, y)
    delta = abs(a_clean - a_faulted)

    # -- collective retry probe ----------------------------------------
    # single-host runs never reach a real collective site, so exercise
    # faults.run_collective directly: two injected transient failures
    # must retry (counted by the telemetry counters) and then succeed
    faults.install("fail_collective@n=2")
    collective_ok = faults.run_collective(lambda: "ok",
                                          site="chaos_probe") == "ok"
    faults.clear()
    retries = int(telem_counters.get("collective_retries"))
    dispatches = int(telem_counters.get("collective_dispatches"))

    print(json.dumps({
        "chaos_bench": {
            "rows": N, "features": F, "iters": ITERS,
            "leaves": LEAVES,
            "base_iter_ms": round(t_base * 1e3, 3),
            "guard_iter_ms": round(t_guard * 1e3, 3),
            "guard_overhead_pct": round(overhead_pct, 2),
            "resume_parity": bool(parity),
            "auc_clean": round(a_clean, 5),
            "auc_faulted": round(a_faulted, 5),
            "auc_delta": round(delta, 5),
            "faulted_completed": bool(np.isfinite(preds).all()
                                      and delta <= 0.005),
            "collective_probe_ok": bool(collective_ok and retries >= 2),
            "collective_retries": retries,
            "collective_dispatches": dispatches,
        }}))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "dist_kill":
        dist_kill_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "fleet_kill":
        fleet_kill_main()
    else:
        main()
