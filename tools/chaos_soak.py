#!/usr/bin/env python
"""Chaos soak: a seeded, deterministic fault schedule over train and
serve episodes, asserting the robustness invariants end to end.

Four episodes, every one bounded by a wall-clock budget (a deadlock IS
a failure) and all parameterized by ``--seed`` so a red run replays
exactly:

* ``preempt`` — a two-process supervised run; ONE rank is armed with
  the ``preempt@iter=K`` fault verb (the deterministic stand-in for a
  SIGTERM eviction notice). The per-iteration preempt vote must carry
  the flag to the peer over the all-gather lane so BOTH ranks write the
  same emergency checkpoint and exit 76; a relaunch with
  ``num_boost_round=None`` must read ``target_rounds`` from the
  manifest and finish BIT-IDENTICAL to the uninterrupted clean run.
  The preempt incident must leave a complete postmortem bundle.
* ``iter_retry`` — single-process host data-parallel learner under
  ``LGBM_TPU_ITER_RETRY=1`` with an injected transient collective
  failure: the whole iteration is rolled back and replayed
  (``iter_retries`` counted) and the final model is bit-identical to
  the unfaulted run.
* ``rejoin`` — two-process run, rank 1 hard-killed mid-train
  (``kill_rank@iter=``); the survivor shrinks, holds the elastic
  rejoin window open, a replacement process dials in
  (``rejoin_as_replacement``), the group re-forms at world 2 and both
  members finish with parity vs the never-killed clean run. The kill
  must leave the victim's ``kill_rank`` bundle and the survivor's
  pre-teardown capture.
* ``serve`` — an in-process serving fleet: gateway hedging beats a
  stalled replica (hedge win counted), a torn manifest read keeps the
  previously applied revision (``manifest_torn`` counted), a
  ``fail_request`` fault surfaces as an application error without
  taking the replica down, and ``/healthz`` answers throughout.

Emits ONE JSON line (``chaos_soak``); exit code 0 iff every invariant
held.

Usage: python tools/chaos_soak.py [--seed 1]
Env:   SOAK_ROWS (1200), SOAK_FEATURES (8), SOAK_ITERS (6),
       SOAK_LEAVES (7) — sized for a 1-core CPU CI host.
"""
import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N = int(os.environ.get("SOAK_ROWS", 1200))
F = int(os.environ.get("SOAK_FEATURES", 8))
ITERS = int(os.environ.get("SOAK_ITERS", 6))
LEAVES = int(os.environ.get("SOAK_LEAVES", 7))

# per-episode wall budgets (seconds). A hang is an invariant violation,
# not a slow run — subprocess timeouts below back these with hard kills.
BUDGETS = {"preempt": 300.0, "iter_retry": 180.0,
           "rejoin": 300.0, "serve": 60.0}

# one worker source for every distributed role in the schedule:
#   clean       — the uninterrupted 2-rank reference run
#   preempt     — 2-rank run; the victim's env installs preempt@iter=K,
#                 the vote spreads it, both ranks exit 76
#   resume      — relaunch with num_boost_round=None: the round budget
#                 comes from the emergency checkpoint's target_rounds
#   rejoin      — 2-rank run; the victim's env installs kill_rank@iter=,
#                 the survivor shrinks then grows back when the
#                 replacement knocks
#   replacement — dials a survivor (argv[10]) and joins the re-formed
#                 group; state arrives via the ordinary resume broadcast
_WORKER = r"""
import json, os, sys
import numpy as np
role = sys.argv[1]; rank = int(sys.argv[2]); port = sys.argv[3]
out = sys.argv[4]; ckpt_dir = sys.argv[5]
N, F, ITERS, LEAVES = (int(v) for v in sys.argv[6:10])
import jax
from lightgbm_tpu.distributed import bootstrap, ingest, supervisor
if role == "replacement":
    supervisor.rejoin_as_replacement(sys.argv[10])
else:
    bootstrap.initialize(f"127.0.0.1:{port}", 2, rank, supervise=True)
    supervisor.start_supervision(heartbeat_ms=100,
                                 collective_timeout_ms=30000)
import lightgbm_tpu as lgb
from lightgbm_tpu import engine
from lightgbm_tpu.callback import checkpoint
from lightgbm_tpu.telemetry import counters

r = np.random.RandomState(7)
x = r.randn(N, F)
y = (1.5 * x[:, 0] - x[:, 1] + r.randn(N) * 0.5 > 0).astype(np.float64)
params = {"objective": "binary", "num_leaves": LEAVES, "verbosity": -1,
          "max_bin": 63, "min_data_in_leaf": 20, "tree_learner": "data",
          "metric": "none", "on_rank_failure": "shrink"}
ds = ingest.wrap_train_set(ingest.load_sharded(x, label=y, params=params))
cbs = [checkpoint(ckpt_dir, checkpoint_freq=2)]
if role == "resume":
    bst = engine.train(params, ds, num_boost_round=None,
                       verbose_eval=False, resume_from=ckpt_dir,
                       callbacks=cbs)
elif role == "replacement":
    bst = engine.train(params, ds, num_boost_round=ITERS,
                       verbose_eval=False, resume_from=ckpt_dir,
                       callbacks=cbs)
else:
    # clean / preempt / rejoin: the preempt role never reaches the
    # payload dump (the iteration boundary exits 76 first)
    bst = engine.train(params, ds, num_boost_round=ITERS,
                       verbose_eval=False, callbacks=cbs)
    if role == "preempt":
        raise SystemExit(99)        # unreachable when the verb fires
payload = {"model": bst.model_to_string(),
           "world_after": bootstrap.process_count(),
           "rejoins": int(counters.get("rejoins")),
           "rank_failures": int(counters.get("rank_failures"))}
with open(out, "w") as fh:
    json.dump(payload, fh)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _env(extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = ""            # 1 device per process
    if extra:
        env.update(extra)
    return env


def _spawn(script, role, rank, port, out, ckpt, env, extra_args=()):
    args = [sys.executable, script, role, str(rank), str(port), out,
            ckpt, str(N), str(F), str(ITERS), str(LEAVES)]
    args += [str(a) for a in extra_args]
    return subprocess.Popen(args, env=env, stdout=subprocess.DEVNULL,
                            stderr=subprocess.PIPE, text=True)


def _wait(proc, what, timeout):
    _, err = proc.communicate(timeout=timeout)
    return proc.returncode, err


def _bundles(root, want_reason):
    """Postmortem completeness for an episode's incidents: every bundle
    parses (torn == 0) and the expected capture reason is present."""
    try:
        import run_report
        _, index, skipped = run_report._resolve_bundle_dir(root)
    except Exception as exc:   # noqa: BLE001 — report the gap, not a crash
        return {"complete": 0, "torn": -1, "reasons": [],
                "ok": False, "error": str(exc)}
    reasons = sorted({str(row.get("reason")) for row in index})
    return {"complete": len(index), "torn": len(skipped),
            "reasons": reasons,
            "ok": bool(index) and not skipped and want_reason in reasons}


def _clean_reference(script, tmp):
    """The uninterrupted 2-rank run every parity invariant compares
    against (shared by the preempt and rejoin episodes)."""
    port = _free_port()
    ckpt = os.path.join(tmp, "ckpt_clean")
    outs = [os.path.join(tmp, f"clean_r{i}.json") for i in range(2)]
    procs = [_spawn(script, "clean", r, port, outs[r], ckpt, _env())
             for r in range(2)]
    for i, p in enumerate(procs):
        code, err = _wait(p, "clean", 280)
        if code != 0:
            raise RuntimeError(f"clean rank {i} failed:\n{err[-3000:]}")
    with open(outs[0]) as fh:
        return json.load(fh)["model"]


def episode_preempt(script, tmp, preempt_iter, clean_model):
    t0 = time.time()
    port = _free_port()
    ckpt = os.path.join(tmp, "ckpt_preempt")
    bundles = os.path.join(tmp, "bundles_preempt")
    base = {"LGBM_TPU_PREEMPT_SYNC": "1", "LGBM_TPU_BUNDLE_DIR": bundles}
    outs = [os.path.join(tmp, f"pre_r{i}.json") for i in range(2)]
    procs = [
        _spawn(script, "preempt", 0, port, outs[0], ckpt, _env(base)),
        # only the victim gets the eviction notice; the vote must carry
        # it to rank 0 so both exit at the SAME iteration boundary
        _spawn(script, "preempt", 1, port, outs[1], ckpt, _env(
            dict(base, LGBM_TPU_FAULT_SPEC=f"preempt@iter={preempt_iter}"))),
    ]
    codes = [_wait(p, "preempt", 280)[0] for p in procs]

    from lightgbm_tpu.distributed.checkpoint import \
        DistributedCheckpointManager
    data = DistributedCheckpointManager(ckpt).latest()
    meta = dict(data.meta) if data is not None else {}

    port2 = _free_port()
    routs = [os.path.join(tmp, f"res_r{i}.json") for i in range(2)]
    rprocs = [_spawn(script, "resume", r, port2, routs[r], ckpt, _env())
              for r in range(2)]
    rerr = [_wait(p, "resume", 280) for p in rprocs]
    resume_model = None
    if all(c == 0 for c, _ in rerr):
        with open(routs[0]) as fh:
            resume_model = json.load(fh)["model"]
    wall = time.time() - t0
    bun = _bundles(bundles, "preempt")
    rep = {
        "episode": "preempt",
        "preempt_iter": preempt_iter,
        "exit_codes": codes,
        "checkpoint_iteration": (None if data is None
                                 else int(data.iteration)),
        "target_rounds": meta.get("target_rounds"),
        "preempt_reason": meta.get("preempt_reason"),
        "resume_parity": bool(resume_model == clean_model),
        "bundles": bun,
        "wall_s": round(wall, 1), "budget_s": BUDGETS["preempt"],
    }
    rep["ok"] = bool(codes == [76, 76]
                     and meta.get("preempted") is True
                     and meta.get("target_rounds") == ITERS
                     and int(data.iteration) == preempt_iter
                     and rep["resume_parity"] and bun["ok"]
                     and wall <= BUDGETS["preempt"])
    return rep


def episode_iter_retry(retry_n):
    """In-process: the host DP learner's histogram allreduce fails
    transiently inside the iteration fence; the iteration is replayed
    from captured state and the model stays bit-identical."""
    t0 = time.time()
    import lightgbm_tpu as lgb
    from lightgbm_tpu import engine
    from lightgbm_tpu.resilience import faults
    from lightgbm_tpu.telemetry import counters as telem_counters

    r = np.random.RandomState(7)
    x = r.randn(N, F)
    y = (1.5 * x[:, 0] - x[:, 1] + r.randn(N) * 0.5 > 0).astype(
        np.float64)
    params = {"objective": "binary", "num_leaves": LEAVES,
              "verbosity": -1, "max_bin": 63, "tree_learner": "data",
              "metric": "none"}
    saved = {k: os.environ.get(k)
             for k in ("LGBM_TPU_HOST_LEARNER", "LGBM_TPU_ITER_RETRY")}
    os.environ["LGBM_TPU_HOST_LEARNER"] = "1"
    os.environ["LGBM_TPU_ITER_RETRY"] = "1"
    try:
        faults.clear()
        clean = engine.train(dict(params),
                             lgb.Dataset(x, y, free_raw_data=False),
                             num_boost_round=ITERS, verbose_eval=False)
        before = int(telem_counters.get("iter_retries"))
        faults.install(f"fail_collective@n={retry_n}", seed=3)
        bst = engine.train(dict(params),
                           lgb.Dataset(x, y, free_raw_data=False),
                           num_boost_round=ITERS, verbose_eval=False)
        fired = [e for e in faults.active_plan().events
                 if e.startswith("fail_collective")]
        faults.clear()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    retries = int(telem_counters.get("iter_retries")) - before
    parity = (clean._gbdt.save_model_to_string(0, -1)
              == bst._gbdt.save_model_to_string(0, -1))
    wall = time.time() - t0
    return {
        "episode": "iter_retry", "injected_failures": retry_n,
        "faults_fired": len(fired), "iter_retries": retries,
        "parity": bool(parity),
        "wall_s": round(wall, 1), "budget_s": BUDGETS["iter_retry"],
        "ok": bool(parity and retries >= 1 and len(fired) == retry_n
                   and wall <= BUDGETS["iter_retry"]),
    }


def episode_rejoin(script, tmp, kill_iter, clean_model):
    t0 = time.time()
    port = _free_port()
    rejoin_port = _free_port()
    ckpt = os.path.join(tmp, "ckpt_rejoin")
    bundles = os.path.join(tmp, "bundles_rejoin")
    base = {"LGBM_TPU_ELASTIC_REJOIN": "1",
            "LGBM_TPU_REJOIN_PORT": str(rejoin_port),
            "LGBM_TPU_REJOIN_WAIT_MS": "60000",
            "LGBM_TPU_BUNDLE_DIR": bundles}
    outs = [os.path.join(tmp, f"rj_r{i}.json") for i in range(3)]
    survivor = _spawn(script, "rejoin", 0, port, outs[0], ckpt,
                      _env(base))
    victim = _spawn(script, "rejoin", 1, port, outs[1], ckpt, _env(
        dict(base, LGBM_TPU_FAULT_SPEC=f"kill_rank@iter={kill_iter}")))
    # launch the replacement only after the victim is really gone — the
    # newcomer's dial loop rides out the survivor's detect + teardown
    kill_code, _ = _wait(victim, "victim", 280)
    replacement = _spawn(script, "replacement", 1, port, outs[2], ckpt,
                         _env(base),
                         extra_args=[f"127.0.0.1:{rejoin_port}"])
    s_code, s_err = _wait(survivor, "survivor", 280)
    r_code, r_err = _wait(replacement, "replacement", 120)
    if s_code != 0:
        raise RuntimeError(f"survivor failed:\n{s_err[-3000:]}")
    if r_code != 0:
        raise RuntimeError(f"replacement failed:\n{r_err[-3000:]}")
    with open(outs[0]) as fh:
        surv = json.load(fh)
    with open(outs[2]) as fh:
        repl = json.load(fh)
    wall = time.time() - t0
    bun = _bundles(bundles, "kill_rank")
    rep = {
        "episode": "rejoin", "kill_iter": kill_iter,
        "kill_code": kill_code,
        "world_after": int(surv["world_after"]),
        "rank_failures": int(surv["rank_failures"]),
        "rejoins": int(surv["rejoins"]) + int(repl["rejoins"]),
        "parity": bool(surv["model"] == repl["model"] == clean_model),
        "bundles": bun,
        "wall_s": round(wall, 1), "budget_s": BUDGETS["rejoin"],
    }
    rep["ok"] = bool(kill_code == 137 and rep["world_after"] == 2
                     and rep["rank_failures"] >= 1 and rep["rejoins"] >= 2
                     and rep["parity"] and bun["ok"]
                     and wall <= BUDGETS["rejoin"])
    return rep


def episode_serve(hedge_ms):
    """In-process serving fleet: hedging past a stalled replica, torn
    manifest containment, a fail_request fault surfacing as an app
    error (replica stays up), and the /healthz floor throughout."""
    import threading
    import urllib.request

    import lightgbm_tpu as lgb
    from lightgbm_tpu.fleet import FleetGateway
    from lightgbm_tpu.fleet.manifest import (ManifestFollower,
                                             ManifestPublisher)
    from lightgbm_tpu.resilience import faults
    from lightgbm_tpu.serving import (ModelRegistry, ServingApp,
                                      make_http_server)
    from lightgbm_tpu.telemetry import counters as telem_counters

    t0 = time.time()
    r = np.random.RandomState(7)
    x = r.randn(400, F)
    y = (1.5 * x[:, 0] - x[:, 1] + r.randn(400) * 0.5 > 0).astype(
        np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": LEAVES,
                     "verbosity": -1},
                    lgb.Dataset(x, y, free_raw_data=False),
                    num_boost_round=3, verbose_eval=False)
    reg = ModelRegistry()
    reg.load(bst, version="v1")
    app = ServingApp(reg, max_batch=16, max_delay_ms=2.0)
    httpd = make_http_server(app, port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    live = "http://%s:%d" % httpd.server_address[:2]

    stall = socket.socket()
    stall.bind(("127.0.0.1", 0))
    stall.listen(8)
    held = []

    def _hold():
        while True:
            try:
                held.append(stall.accept()[0])
            except OSError:
                return

    threading.Thread(target=_hold, daemon=True).start()
    stalled = "http://127.0.0.1:%d" % stall.getsockname()[1]

    def _healthz_ok():
        with urllib.request.urlopen(live + "/healthz", timeout=5) as f:
            return json.loads(f.read()).get("status") == "ok"

    try:
        gw = FleetGateway(replicas=[{"url": stalled, "weight": 9.0},
                                    {"url": live, "weight": 1.0}],
                          hedge_s=hedge_ms / 1e3, timeout_s=5.0)
        wins0 = int(telem_counters.get("gateway_hedge_wins"))
        hedged0 = int(telem_counters.get("gateway_hedged_requests"))
        healthz = [_healthz_ok()]
        code, body = gw.predict({"rows": x[:2].tolist()})
        hedge_ok = code == 200 and len(body["predictions"]) == 2
        wins = int(telem_counters.get("gateway_hedge_wins")) - wins0
        hedged = int(telem_counters.get("gateway_hedged_requests")) \
            - hedged0

        # torn manifest: half a JSON doc keeps the previous revision
        with tempfile.TemporaryDirectory(prefix="soak_mani_") as mtmp:
            v1 = os.path.join(mtmp, "v1.txt")
            bst.save_model(v1)
            mpath = os.path.join(mtmp, "manifest.json")
            app2 = ServingApp(ModelRegistry(), max_batch=16, start=False)
            follower = ManifestFollower(app2, mpath, poll_s=0.1)
            ManifestPublisher(mpath).seed({"v1": v1}, stable="v1")
            applied = follower.poll_once()
            with open(mpath, "rb") as fh:
                full = fh.read()
            with open(mpath, "wb") as fh:
                fh.write(full[: len(full) // 2])
            torn0 = int(telem_counters.get("manifest_torn"))
            no_apply = follower.poll_once() is False
            torn = int(telem_counters.get("manifest_torn")) - torn0
            kept = app2.registry.latest == "v1"
            app2.close()
        torn_detected = bool(applied and no_apply and torn >= 1 and kept)

        # fail_request: the serving batcher's fault site answers with an
        # app error; the replica must stay up and serve the next request
        faults.install("fail_request@n=1")
        try:
            req = urllib.request.Request(
                live + "/predict",
                data=json.dumps({"rows": x[:2].tolist()}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            try:
                with urllib.request.urlopen(req, timeout=10) as f:
                    first_status = f.status
            except urllib.error.HTTPError as exc:
                first_status = exc.code
            fired = any(e.startswith("fail_request")
                        for e in faults.active_plan().events)
        finally:
            faults.clear()
        healthz.append(_healthz_ok())
        code2, body2 = gw.predict({"rows": x[:2].tolist()})
        healthz.append(_healthz_ok())
        recovered = code2 == 200 and len(body2["predictions"]) == 2
    finally:
        stall.close()
        for c in held:
            c.close()
        httpd.shutdown()
        httpd.server_close()
        app.close()
    wall = time.time() - t0
    return {
        "episode": "serve", "hedge_ms": hedge_ms,
        "hedged_requests": hedged, "hedge_wins": wins,
        "torn_detected": torn_detected,
        "fail_request_fired": bool(fired),
        "fail_request_status": int(first_status),
        "recovered_after_fault": bool(recovered),
        "healthz_ok": bool(all(healthz)),
        "wall_s": round(wall, 1), "budget_s": BUDGETS["serve"],
        "ok": bool(hedge_ok and wins >= 1 and hedged >= 1
                   and torn_detected and fired and recovered
                   and all(healthz) and first_status >= 500
                   and wall <= BUDGETS["serve"]),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    opts = ap.parse_args()
    rng = np.random.RandomState(opts.seed)
    # the deterministic schedule: where each fault lands this run
    schedule = {
        "preempt_iter": int(2 + rng.randint(0, 3)),     # 2..4
        "retry_n": int(1 + rng.randint(0, 2)),          # 1..2
        "kill_iter": int(3),
        "hedge_ms": int(60 + 10 * rng.randint(0, 4)),   # 60..90
    }
    t0 = time.time()
    episodes = []
    with tempfile.TemporaryDirectory(prefix="chaos_soak_") as tmp:
        script = os.path.join(tmp, "worker.py")
        with open(script, "w") as fh:
            fh.write(_WORKER)
        clean_model = _clean_reference(script, tmp)
        for name, fn in (
                ("preempt", lambda: episode_preempt(
                    script, tmp, schedule["preempt_iter"], clean_model)),
                ("iter_retry", lambda: episode_iter_retry(
                    schedule["retry_n"])),
                ("rejoin", lambda: episode_rejoin(
                    script, tmp, schedule["kill_iter"], clean_model)),
                ("serve", lambda: episode_serve(schedule["hedge_ms"]))):
            try:
                episodes.append(fn())
            except Exception as exc:   # noqa: BLE001 — a red episode,
                episodes.append({      # not a dead harness
                    "episode": name, "ok": False,
                    "error": f"{type(exc).__name__}: {exc}"[:800]})
    ok = all(e.get("ok") for e in episodes)
    print(json.dumps({"chaos_soak": {
        "seed": opts.seed, "ok": bool(ok),
        "rows": N, "features": F, "iters": ITERS, "leaves": LEAVES,
        "schedule": schedule,
        "episodes": episodes,
        "wall_secs": round(time.time() - t0, 1),
    }}))
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
