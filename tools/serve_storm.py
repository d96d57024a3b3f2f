#!/usr/bin/env python
"""Fleet capacity storm: drive 1->N replicas to saturation, measure.

The fleet tier's acceptance number is a *measured curve*, not a guess
(PAPERS.md 1809.04559 discipline: committed, reproducible measurement
over anecdote). This tool builds a local fleet — N in-process
``ServingApp`` replicas behind the real ``fleet.gateway`` with the
real ``fleet.manifest`` as the deploy artifact — and storms it with
closed-loop mixed-priority traffic until admission control bites.

One JSON line per replica count::

    {"replicas": 2, "rows_per_s": ..., "p50_ms": ..., "p99_ms": ...,
     "requests": ..., "ok": ..., "errors": ..., "error_rate": ...,
     "shed": {"pinned": ..., "versioned": ..., "shadow": ...},
     "shed_fraction": {...per-class shed/requests...},
     "slo_burns": ..., "secs": ..., "clients": ...}

What makes the curve honest on a 1-core CI host: each replica's
throughput ceiling is its flush cadence (``max_batch`` rows every
``max_delay_ms``), far below the CPU's predict limit for a tiny model,
so adding replicas genuinely adds capacity until the host saturates —
the same shape a TPU pod fleet shows when replicas are accelerator-
bound. ``--out`` writes the curve to a file.

Replicas share one export cache directory, so replica 2..N restore
replica 1's compiled predictors — fleet builds are compile-once.

Usage::

    python tools/serve_storm.py                      # 1,2,3 replicas
    python tools/serve_storm.py --replicas 2 --secs 2 --clients 6
    python tools/serve_storm.py --out fleet_curve.json

Env: STORM_FEATURES (16), STORM_ROWS (2000) size the demo model.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FEATURES = int(os.environ.get("STORM_FEATURES", 16))
ROWS = int(os.environ.get("STORM_ROWS", 2000))

# closed-loop priority mix: mostly SLO traffic, a versioned-replay and
# a shadow-mirror share (client k's request uses MIX[k % len(MIX)])
MIX = ("pinned", "pinned", "pinned", "versioned", "pinned", "shadow",
       "pinned", "versioned", "pinned", "shadow")


def train_storm_model():
    """Tiny binary model, deterministic."""
    import numpy as np
    import lightgbm_tpu as lgb
    r = np.random.RandomState(7)
    x = r.randn(ROWS, FEATURES).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float64)
    return lgb.train({"objective": "binary", "num_leaves": 15,
                      "verbosity": -1, "max_bin": 63},
                     lgb.Dataset(x, y, free_raw_data=False),
                     num_boost_round=5, verbose_eval=False)


class Fleet:
    """Handle over an in-process fleet: N replicas + gateway + manifest."""

    def __init__(self, workdir):
        self.workdir = workdir
        self.apps = []
        self.httpds = []
        self.urls = []
        self.followers = []
        self.manifest_path = os.path.join(workdir, "fleet_manifest.json")
        self.gateway = None
        self.gw_httpd = None
        self.gw_url = None
        self.stable = "v1"

    def kill_replica(self, index: int) -> str:
        """Hard-stop one replica's HTTP server (chaos hook): from the
        gateway's side this is a connect failure, exactly what a died
        process looks like. Returns the victim URL."""
        httpd = self.httpds[index]
        url = self.urls[index]
        httpd.shutdown()
        httpd.server_close()
        self.apps[index].close()
        return url

    def stop(self):
        if self.gateway is not None:
            self.gateway.stop()
        if self.gw_httpd is not None:
            self.gw_httpd.shutdown()
            self.gw_httpd.server_close()
        for f in self.followers:
            f.stop()
        for i, httpd in enumerate(self.httpds):
            try:
                httpd.shutdown()
                httpd.server_close()
            except OSError:
                pass
            try:
                self.apps[i].close()
            except Exception:   # noqa: BLE001 — already killed is fine
                pass


def build_fleet(n_replicas: int, booster=None, workdir=None, *,
                max_batch: int = 64, max_delay_ms: float = 20.0,
                queue_rows: int = 24, slo_p99_ms: float = 150.0,
                timeout_ms: float = 2000.0,
                warm_buckets=(8, 32)) -> Fleet:
    """N in-process replicas (threaded HTTP servers, shared export
    cache) converged from one manifest, fronted by a FleetGateway."""
    from lightgbm_tpu.fleet import ExportCache, FleetGateway
    from lightgbm_tpu.fleet.manifest import (ManifestFollower,
                                             ManifestPublisher)
    from lightgbm_tpu.fleet.gateway import make_gateway_server
    from lightgbm_tpu.serving import (LoadShedder, ModelRegistry,
                                      PredictorCache, ServingApp,
                                      SloMonitor, make_http_server)

    workdir = workdir or tempfile.mkdtemp(prefix="lgbm_storm_")
    os.makedirs(workdir, exist_ok=True)
    fleet = Fleet(workdir)
    model_path = os.path.join(workdir, "model.txt")
    if not os.path.exists(model_path):
        (booster or train_storm_model()).save_model(model_path)
    cache_dir = os.path.join(workdir, "xcache")

    for i in range(n_replicas):
        registry = ModelRegistry(predictor=PredictorCache(),
                                 warm_buckets=warm_buckets,
                                 export_cache=ExportCache(cache_dir))
        slo = SloMonitor(p99_ms=slo_p99_ms, fast_window_s=2.0,
                         slow_window_s=20.0)
        shed = LoadShedder(slo=slo, refresh_s=0.1)
        app = ServingApp(registry, slo=slo, shed=shed,
                         max_batch=max_batch, max_delay_ms=max_delay_ms,
                         max_queue_rows=queue_rows,
                         default_timeout_ms=timeout_ms)
        httpd = make_http_server(app, port=0)
        threading.Thread(target=httpd.serve_forever, daemon=True,
                         name=f"storm-replica-{i}").start()
        fleet.apps.append(app)
        fleet.httpds.append(httpd)
        fleet.urls.append("http://%s:%d" % httpd.server_address[:2])

    # ONE deploy artifact: every replica converges from the manifest
    # (models + stable), and the gateway reads its replica set from it
    publisher = ManifestPublisher(fleet.manifest_path)
    publisher.seed({"v1": model_path}, stable="v1",
                   replicas=[{"url": u, "weight": 1.0}
                             for u in fleet.urls])
    for app in fleet.apps:
        follower = ManifestFollower(app, fleet.manifest_path, poll_s=0.25)
        follower.poll_once()
        follower.start()
        fleet.followers.append(follower)
    # first replica's promote/demote decisions publish back to the fleet
    publisher.bind_router(fleet.apps[0].router, fleet.apps[0].registry)

    fleet.gateway = FleetGateway(manifest_path=fleet.manifest_path,
                                 retries=1, backoff_s=0.01, eject_s=0.5,
                                 health_period_s=0.2, timeout_s=5.0)
    fleet.gw_httpd = make_gateway_server(fleet.gateway, port=0)
    threading.Thread(target=fleet.gw_httpd.serve_forever, daemon=True,
                     name="storm-gateway").start()
    fleet.gateway.start_health_loop()
    fleet.gw_url = "http://%s:%d" % fleet.gw_httpd.server_address[:2]
    return fleet


def _post(url: str, payload: dict, timeout: float = 10.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def run_storm(gw_url: str, secs: float, clients: int = 8,
              rows_per_req: int = 8, stable: str = "v1",
              num_features: int = FEATURES, mid_hook=None) -> dict:
    """Closed-loop mixed-priority storm against the gateway. `mid_hook`
    (chaos scenarios) runs once at the halfway mark from the caller's
    thread — e.g. to kill a replica mid-storm."""
    import numpy as np
    from lightgbm_tpu.telemetry import counters as telem_counters

    rs = np.random.RandomState(11)
    pool = rs.randn(256, num_features).astype(np.float32)
    burns0 = telem_counters.get("slo_burns")
    stop = threading.Event()
    lock = threading.Lock()
    agg = {"requests": {p: 0 for p in ("pinned", "versioned", "shadow")},
           "shed": {p: 0 for p in ("pinned", "versioned", "shadow")},
           "ok": 0, "ok_rows": 0, "errors": 0, "lat_ms": []}

    def client(ci: int) -> None:
        k = ci
        while not stop.is_set():
            priority = MIX[k % len(MIX)]
            k += clients
            start = (k * rows_per_req) % (256 - rows_per_req)
            payload = {"rows": pool[start:start + rows_per_req].tolist(),
                       "priority": priority}
            if priority == "versioned":
                payload["version"] = stable
            t0 = time.monotonic()
            try:
                code, _ = _post(gw_url + "/predict", payload)
            except urllib.error.HTTPError as exc:
                code = exc.code
                exc.read()
            except Exception:   # noqa: BLE001 — gateway down/timeouts
                code = -1
            dt_ms = (time.monotonic() - t0) * 1e3
            with lock:
                agg["requests"][priority] += 1
                if code == 200:
                    agg["ok"] += 1
                    agg["ok_rows"] += rows_per_req
                    agg["lat_ms"].append(dt_ms)
                elif code == 429:
                    agg["shed"][priority] += 1
                else:
                    agg["errors"] += 1

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    if mid_hook is not None:
        time.sleep(secs / 2)
        mid_hook()
        time.sleep(secs / 2)
    else:
        time.sleep(secs)
    stop.set()
    for t in threads:
        t.join(timeout=5.0)
    elapsed = time.monotonic() - t0

    lats = sorted(agg["lat_ms"])

    def pct(q: float) -> float:
        return round(lats[min(len(lats) - 1, int(q * len(lats)))], 3) \
            if lats else 0.0

    total = sum(agg["requests"].values())
    shed_fraction = {
        p: round(agg["shed"][p] / agg["requests"][p], 4)
        if agg["requests"][p] else 0.0
        for p in agg["shed"]}
    return {"rows_per_s": round(agg["ok_rows"] / elapsed, 1),
            "p50_ms": pct(0.50), "p99_ms": pct(0.99),
            "requests": total, "ok": agg["ok"], "errors": agg["errors"],
            "error_rate": round(agg["errors"] / total, 4) if total else 0.0,
            "shed": dict(agg["shed"]), "shed_fraction": shed_fraction,
            "slo_burns": telem_counters.get("slo_burns") - burns0,
            "secs": round(elapsed, 3), "clients": clients,
            "rows_per_req": rows_per_req}


def storm_curve(replica_counts, secs: float = 3.0, clients: int = 8,
                rows_per_req: int = 8, booster=None,
                fleet_kwargs=None) -> list:
    """One measurement per replica count, same model + export cache +
    offered load throughout — the only variable is the fleet size."""
    booster = booster or train_storm_model()
    workdir = tempfile.mkdtemp(prefix="lgbm_storm_")
    curve = []
    for n in replica_counts:
        fleet = build_fleet(n, booster=booster,
                            workdir=os.path.join(workdir, f"n{n}"),
                            **(fleet_kwargs or {}))
        try:
            # let followers/health settle so the first requests route
            time.sleep(0.2)
            point = run_storm(fleet.gw_url, secs, clients=clients,
                              rows_per_req=rows_per_req,
                              stable=fleet.stable)
        finally:
            fleet.stop()
        point = {"replicas": n, **point}
        print(json.dumps(point), flush=True)
        curve.append(point)
    return curve


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--replicas", default="1,2,3",
                    help="comma-separated replica counts to measure")
    ap.add_argument("--secs", type=float, default=3.0,
                    help="storm duration per replica count")
    ap.add_argument("--clients", type=int, default=12)
    ap.add_argument("--rows", type=int, default=8,
                    help="rows per request")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-delay-ms", type=float, default=20.0)
    ap.add_argument("--queue-rows", type=int, default=24)
    ap.add_argument("--out", default="",
                    help="write the full curve JSON here")
    args = ap.parse_args()
    counts = [int(v) for v in args.replicas.split(",") if v]
    curve = storm_curve(
        counts, secs=args.secs, clients=args.clients,
        rows_per_req=args.rows,
        fleet_kwargs={"max_batch": args.max_batch,
                      "max_delay_ms": args.max_delay_ms,
                      "queue_rows": args.queue_rows})
    if args.out:
        doc = {"format": "lgbm_tpu_fleet_storm", "version": 1,
               "tool": "tools/serve_storm.py",
               "settings": {"secs": args.secs, "clients": args.clients,
                            "rows_per_req": args.rows,
                            "max_batch": args.max_batch,
                            "max_delay_ms": args.max_delay_ms,
                            "queue_rows": args.queue_rows,
                            "features": FEATURES},
               "curve": curve}
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(json.dumps({"written": args.out,
                          "monotone_rows_per_s": all(
                              curve[i]["rows_per_s"] <
                              curve[i + 1]["rows_per_s"]
                              for i in range(len(curve) - 1))}))


if __name__ == "__main__":
    main()
