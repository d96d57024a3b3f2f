#!/usr/bin/env python
"""Sharding-overhead curves on the virtual CPU mesh (VERDICT r4 #4).

Real multi-chip hardware is unavailable here, so absolute scaling can't
be measured — but the *overhead* a sharded program adds as D grows can:
on a 1-core host every virtual device timeshares the same core, so
per-tree wall at D devices ≈ (compute, unchanged total) + (partition +
collective + program overhead that grows with D). Flat-ish curves mean
the sharding machinery is cheap; a blow-up localizes where multi-chip
efficiency would go. The reference's analog is its measured 16-machine
speedups (reference docs/Experiments.rst:216-230) — this is the
strongest proxy this environment can produce, and it complements the
measured bytes-per-split table (tools/comm_probe.py, DESIGN.md §4c).

Usage: python tools/mesh_scaling_probe.py [rows] [iters]
Writes one JSON line per (mode, D) to stdout; run it on an idle host.
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def child(mode: str, rows: int, iters: int) -> None:
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import create_boosting

    r = np.random.RandomState(7)
    x = r.randn(rows, 28).astype(np.float32)
    y = (x[:, 0] + 0.4 * x[:, 1] * x[:, 2] + 0.5 * r.randn(rows)
         > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "min_data_in_leaf": 20, "verbosity": -1}
    if mode != "serial":
        params["tree_learner"] = {"dp": "data", "voting": "voting",
                                  "fp": "feature"}[mode]
    cfg = Config(params)
    ds = Dataset(x, config=cfg, label=y)
    b = create_boosting(cfg, ds)
    b.train_one_iter()           # compile + first tree (off-clock)
    t0 = time.time()
    for _ in range(iters):
        b.train_one_iter()
    dt = (time.time() - t0) / iters
    print(json.dumps({"sec_per_tree": dt}))


def run(mode: str, devices: int, rows: int, iters: int):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", mode,
         str(rows), str(iters)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=3600)
    assert r.returncode == 0, (mode, devices, r.stderr[-1500:])
    sec = json.loads(r.stdout.strip().splitlines()[-1])["sec_per_tree"]
    return sec


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
        return
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 200_000
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    base = None
    for mode, dlist in (("serial", [1]), ("dp", [1, 2, 4, 8]),
                        ("fp", [2, 4, 8]), ("voting", [2, 4, 8])):
        for d in dlist:
            sec = run(mode, d, rows, iters)
            if mode == "serial":
                base = sec
            print(json.dumps({
                "mode": mode, "devices": d, "rows": rows,
                "sec_per_tree": round(sec, 3),
                "overhead_vs_serial": round(sec / base, 3) if base else None,
            }), flush=True)


if __name__ == "__main__":
    main()
