#!/usr/bin/env python
"""Distributed smoke: two-process localhost training vs virtual mesh.

Launches the full multi-host topology on one machine — two
`jax.distributed` processes with one CPU device each (gloo collectives)
— trains a small data-parallel model through `lightgbm_tpu.distributed`
(bootstrap + sharded ingest + rank-0 checkpointing), and compares the
model text against the single-process virtual-mesh run
(``XLA_FLAGS=--xla_force_host_platform_device_count=2``), which must be
BIT-IDENTICAL (same mesh shape => same XLA program).

A second, wider pair (``DIST_MEM_FEATURES`` columns) pins the
row-sharded memory claim: ``dist_shard_mode=rows`` keeps each host's
own binned block, so the stored bytes per rank must drop vs replicated
ingest — at 96 u8 columns + float64 labels the 2-rank ratio is
(96+8)/(96/2+8) ≈ 1.86 — while the model stays equal (quantized lanes
bit-identical; float compared by train AUC, the paper's tolerance).

Emits ONE JSON line (`dist_smoke`) like the other tools/ benches:

* ``dist_parity`` / ``quant_parity`` — two-process model text ==
  virtual-mesh text (replicated ingest, float and grad_bits=8)
* ``shard_mode`` + ``peak_host_bytes_per_rank`` + ``host_bytes_ratio``
  — the rows-vs-replicated memory pair above
* ``rows_quant_parity`` / ``rows_float_auc_delta`` — model-equality
  half of the memory pair
* ``wire_breakdown`` — per-mode cross-host bytes split into the
  all-gather lane (`dist_wire_bytes`: ingest + checkpoint barriers)
  and the histogram-exchange lane (`dist_reduce_scatter_bytes`); rows
  mode moves the ingest bytes to ~labels-only, leaving histograms as
  the only per-iteration traffic
* ``collective_dispatches`` / ``collective_retries`` — host-collective
  counters from the bootstrap/barrier sites (resilience/faults.py)
* ``clock_skew_ms`` + ``critical_path`` — the deep-trace pair: the
  float run supervises with a 50 ms heartbeat (clock alignment from
  the probe timestamps, telemetry/clock.py) and aggregates every
  iteration, so rank 0's timeline store can attribute each iteration
  into per-rank compute vs collective-wait (telemetry/timeline.py)

Usage: python tools/dist_smoke.py
Env:   DIST_ROWS (2000), DIST_FEATURES (8), DIST_ITERS (3),
       DIST_LEAVES (15), DIST_QUANT (1 to include the quantized pass),
       DIST_MEM_FEATURES (96, the memory-pair width; 0 skips the pair)
       — defaults sized for a 1-core CPU CI host.
"""
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = int(os.environ.get("DIST_ROWS", 2000))
F = int(os.environ.get("DIST_FEATURES", 8))
ITERS = int(os.environ.get("DIST_ITERS", 3))
LEAVES = int(os.environ.get("DIST_LEAVES", 15))
RUN_QUANT = os.environ.get("DIST_QUANT", "1") == "1"
MEM_F = int(os.environ.get("DIST_MEM_FEATURES", 96))

_WORKER = r"""
import json, os, sys
import numpy as np
rank = int(sys.argv[1]); port = sys.argv[2]; out = sys.argv[3]
quantized = sys.argv[4] == "1"
N, F, ITERS, LEAVES = (int(v) for v in sys.argv[5:9])
shard_mode = sys.argv[9]
deep = os.environ.get("DIST_SMOKE_TELEMETRY") == "1"
if deep:                       # before telemetry import resolves mode
    os.environ["LGBM_TPU_TELEMETRY"] = "summary"
    os.environ.setdefault("LGBM_TPU_AGG_PERIOD", "1")
import jax
from lightgbm_tpu.distributed import bootstrap, ingest, supervisor
if rank >= 0:
    bootstrap.initialize(f"127.0.0.1:{port}", 2, rank)
    assert bootstrap.is_distributed() and len(jax.devices()) == 2
    if deep:
        supervisor.start_supervision(50.0)
import lightgbm_tpu as lgb
from lightgbm_tpu.telemetry import counters


def auc(y, s):
    y = np.asarray(y, dtype=np.float64)
    s = np.asarray(s, dtype=np.float64)
    order = np.argsort(s, kind="mergesort")
    sv = s[order]
    r = np.arange(1, len(s) + 1, dtype=np.float64)
    j = 0
    while j < len(sv):                      # average ranks over ties
        k = j
        while k + 1 < len(sv) and sv[k + 1] == sv[j]:
            k += 1
        r[j:k + 1] = 0.5 * ((j + 1) + (k + 1))
        j = k + 1
    ranks = np.empty(len(s))
    ranks[order] = r
    npos = float((y > 0).sum()); nneg = float(len(y) - npos)
    if npos == 0 or nneg == 0:
        return 1.0
    return (ranks[y > 0].sum() - npos * (npos + 1) / 2.0) / (npos * nneg)


r = np.random.RandomState(7)
x = r.randn(N, F)
y = (1.5 * x[:, 0] - x[:, 1] + r.randn(N) * 0.5 > 0).astype(np.float64)
params = {"objective": "binary", "num_leaves": LEAVES, "verbosity": -1,
          "max_bin": 63, "min_data_in_leaf": 20, "tree_learner": "data",
          "metric": "none"}
if quantized:
    params.update(quantized_grad=True, grad_bits=8)
if shard_mode != "replicated":
    params["dist_shard_mode"] = shard_mode
ds = ingest.wrap_train_set(ingest.load_sharded(x, label=y, params=params))
bst = lgb.train(params, ds, num_boost_round=ITERS, verbose_eval=False)
txt = bst.model_to_string()
pred = np.asarray(bst.predict(x), dtype=np.float64).reshape(-1)
payload = {"model": txt,
           "auc": float(auc(y, pred)),
           "shard_mode": shard_mode,
           "host_bytes": int(getattr(ds._inner, "_ingest_host_bytes", 0)),
           "wire_bytes": counters.get("dist_wire_bytes"),
           "reduce_scatter_bytes": counters.get("dist_reduce_scatter_bytes"),
           "allgathers": counters.get("dist_allgathers"),
           "dispatches": counters.get("collective_dispatches"),
           "retries": counters.get("collective_retries")}
if deep and rank >= 0:
    import time as _time
    _time.sleep(0.3)           # a few more heartbeat clock samples
    from lightgbm_tpu.telemetry import clock, timeline
    supervisor.stop_supervision()
    payload["clock_skew_ms"] = clock.max_abs_skew_ms()
    payload["critical_path"] = {
        str(r): ent for r, ent in timeline.per_rank_totals().items()}
with open(out, "w") as fh:
    json.dump(payload, fh)
"""


def _canon(model_text):
    """Model text minus the params dump's `[dist_shard_mode: ...]` line:
    the shard mode is an ingest/placement choice, so it is the one line
    allowed to differ between the rows and replicated runs — the trees
    themselves must be bit-identical."""
    return "\n".join(ln for ln in model_text.splitlines()
                     if not ln.startswith("[dist_shard_mode:"))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = ""            # 1 device per process
    return env


def _run(script, args, env, timeout=600):
    p = subprocess.run([sys.executable, script] + [str(a) for a in args],
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    if p.returncode != 0:
        raise RuntimeError(f"worker failed:\n{p.stderr[-3000:]}")


def _dist2(script, tmp, tag, quant, mode, n, f, extra_env=None):
    """One 2-process localhost run; returns both rank payloads."""
    port = _free_port()
    env = _env()
    if extra_env:
        env.update(extra_env)
    outs = [os.path.join(tmp, f"{tag}_r{i}.json") for i in range(2)]
    args = [quant, n, f, ITERS, LEAVES, mode]
    procs = [subprocess.Popen(
        [sys.executable, script, str(r), str(port), outs[r]]
        + [str(a) for a in args],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True) for r in range(2)]
    for p in procs:
        _, err = p.communicate(timeout=600)
        if p.returncode != 0:
            for q in procs:
                q.kill()
            raise RuntimeError(f"dist worker ({tag}) failed:\n{err[-3000:]}")
    res = []
    for path in outs:
        with open(path) as fh:
            res.append(json.load(fh))
    return res


def _pair(script, tmp, quant, deep=False):
    """One parity measurement: 2-process localhost vs virtual mesh.
    With deep=True the two dist workers run the deep-trace stack
    (summary telemetry + supervision + per-iteration aggregation)."""
    extra = {"DIST_SMOKE_TELEMETRY": "1"} if deep else None
    r0, r1 = _dist2(script, tmp, f"p{quant}", quant, "replicated", N, F,
                    extra_env=extra)
    envv = _env()
    envv["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    vout = os.path.join(tmp, f"v_{quant}.json")
    _run(script, [-1, 0, vout, quant, N, F, ITERS, LEAVES, "replicated"],
         envv)
    with open(vout) as fh:
        v = json.load(fh)
    parity = (r0["model"] == r1["model"] == v["model"])
    return parity, r0, r1


def main():
    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="dist_smoke_") as tmp:
        script = os.path.join(tmp, "worker.py")
        with open(script, "w") as fh:
            fh.write(_WORKER)
        parity, r0, r1 = _pair(script, tmp, "0", deep=True)
        quant_parity = None
        if RUN_QUANT:
            quant_parity, _, _ = _pair(script, tmp, "1")
        mem = None
        if MEM_F > 0:
            rep = _dist2(script, tmp, "mem_rep", "0", "replicated", N,
                         MEM_F)[0]
            row0, row1 = _dist2(script, tmp, "mem_rows", "0", "rows", N,
                                MEM_F)
            qrep = qrows = None
            if RUN_QUANT:
                qrep = _dist2(script, tmp, "mem_qrep", "1", "replicated",
                              N, MEM_F)[0]
                qrows = _dist2(script, tmp, "mem_qrows", "1", "rows", N,
                               MEM_F)[0]
            peak = max(row0["host_bytes"], row1["host_bytes"])
            mem = {
                "shard_mode": "rows",
                "mem_features": MEM_F,
                "peak_host_bytes_per_rank": {
                    "replicated": int(rep["host_bytes"]),
                    "rows": int(peak)},
                "host_bytes_ratio": round(rep["host_bytes"]
                                          / max(1, peak), 3),
                "rows_float_auc_delta": round(
                    abs(row0["auc"] - rep["auc"]), 6),
                "rows_float_parity": _canon(row0["model"])
                                     == _canon(rep["model"]),
                "rows_quant_parity": (None if qrep is None
                                      else _canon(qrows["model"])
                                      == _canon(qrep["model"])),
                "wire_breakdown": {
                    "replicated": {
                        "allgather_bytes": int(rep["wire_bytes"]),
                        "reduce_scatter_bytes":
                            int(rep["reduce_scatter_bytes"])},
                    "rows": {
                        "allgather_bytes": int(row0["wire_bytes"]),
                        "reduce_scatter_bytes":
                            int(row0["reduce_scatter_bytes"])}},
            }
    out = {
        "rows": N, "features": F, "iters": ITERS, "leaves": LEAVES,
        "processes": 2,
        "dist_parity": bool(parity),
        "quant_parity": quant_parity,
        "wire_bytes_per_host": int(r0["wire_bytes"]),
        "allgathers": int(r0["allgathers"]),
        "collective_dispatches": int(r0["dispatches"]),
        "collective_retries": int(r0["retries"]),
        "clock_skew_ms": round(max(r0.get("clock_skew_ms", 0.0),
                                   r1.get("clock_skew_ms", 0.0)), 4),
        "critical_path": r0.get("critical_path") or {},
    }
    if mem is not None:
        out.update(mem)
    out["wall_secs"] = round(time.time() - t0, 1)
    print(json.dumps({"dist_smoke": out}))


if __name__ == "__main__":
    main()
