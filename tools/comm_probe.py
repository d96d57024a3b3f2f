#!/usr/bin/env python
"""Measured collective-traffic accounting for the sharded tree learners.

The reference publishes its per-split communication costs as a design
table: DataParallel reduce-scatters all C*B histogram bins then
allreduces one best split (reference:
src/treelearner/data_parallel_tree_learner.cpp:149-164, :246), while
VotingParallel reduces only the 2k elected features' bins (reference:
src/treelearner/voting_parallel_tree_learner.cpp:203-260). This probe
produces the equivalent table for OUR learners by measurement, not by
model: it runs one fused sharded boosting iteration per mode on a
D-device virtual CPU mesh with --xla_dump_to, then parses the compiled
HLO of the fused step for collective ops (all-reduce / reduce-scatter /
all-gather / collective-permute) and reports their shapes and bytes,
split into "per-split" (inside the tree-growth while body — executed
once per split) and "per-tree" (everything else).

Usage:
    python tools/comm_probe.py                 # all modes, D=8, table
    python tools/comm_probe.py --json          # machine-readable
    python tools/comm_probe.py --mode dp-scatter --devices 8 --rows 65536

The child re-exec (one per mode) is CPU-pinned with
xla_force_host_platform_device_count, exactly like tests/conftest.py —
no TPU needed; collective SHAPES are backend-independent (the same HLO
ops ride ICI on a real mesh).
"""
import argparse
import json
import os
import re
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
               "s32": 4, "u64": 8, "u32": 4, "s16": 2, "u16": 2,
               "s8": 1, "u8": 1, "pred": 1}

COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute")


def child(mode: str, rows: int, features: int, leaves: int) -> None:
    """Run ONE fused sharded boosting iteration in the given mode (the
    process env must already pin CPU + device count + dump dir)."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.parallel.mesh import make_mesh
    from lightgbm_tpu.parallel.learners import (
        DeviceDataParallelTreeLearner, DeviceVotingParallelTreeLearner)

    r = np.random.RandomState(11)
    x = r.randn(rows, features).astype(np.float32)
    y = (x[:, 0] + 0.4 * x[:, 1] * x[:, 2] + 0.3 * r.randn(rows)
         > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": leaves,
              "min_data_in_leaf": 5, "max_bin": 63, "verbosity": -1}
    if mode == "voting":
        params["top_k"] = 8
    cfg = Config(params)
    ds = Dataset(x, config=cfg, label=y)
    booster = create_boosting(cfg, ds)
    mesh = make_mesh(axis_name="data")
    if mode == "voting":
        booster.learner = DeviceVotingParallelTreeLearner(cfg, ds, mesh)
    else:
        booster.learner = DeviceDataParallelTreeLearner(cfg, ds, mesh)
        want = 0 if mode == "dp-psum" else booster.learner.shards
        assert booster.learner.scatter_cols == want, (
            mode, booster.learner.scatter_cols)
    stop = booster.train_one_iter()
    assert not stop and booster.models[0].num_leaves > 1
    print(f"child {mode}: tree with {booster.models[0].num_leaves} leaves")


def parse_dump(dump_dir: str, module_hint: str = "step_impl"):
    """Collect collective ops from the fused-step module's optimized HLO.

    Returns a list of dicts: op, shapes (tuple results included), bytes,
    per_split. Classification uses the instruction's preserved jax
    metadata (op_name contains "while/body" for ops inside the
    tree-growth loop) — robust against XLA's computation
    cloning/renaming, which defeats name-based computation walks."""
    cands = [f for f in os.listdir(dump_dir)
             if f.endswith("after_optimizations.txt") and module_hint in f]
    if not cands:
        cands = sorted(
            (f for f in os.listdir(dump_dir)
             if f.endswith("after_optimizations.txt")),
            key=lambda f: -os.path.getsize(os.path.join(dump_dir, f)))[:1]
    assert cands, f"no optimized HLO dumped in {dump_dir}"
    text = open(os.path.join(dump_dir, cands[0])).read()

    ops = []
    inst_re = re.compile(
        r"=\s*(\([^)]*\)|\w+\[[\d,]*\]\S*)\s+("
        + "|".join(COLLECTIVES) + r")\(")
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in text.splitlines():
        m = inst_re.search(line)
        if not m:
            continue
        shapes_s, op = m.group(1), m.group(2)
        shapes, nbytes = [], 0
        for sm in shape_re.finditer(shapes_s):
            dtype, dims_s = sm.group(1), sm.group(2)
            dims = [int(d) for d in dims_s.split(",") if d] or [1]
            n_elem = 1
            for d in dims:
                n_elem *= d
            shapes.append(f"{dtype}{dims}")
            nbytes += n_elem * DTYPE_BYTES.get(dtype, 4)
        om = re.search(r'op_name="([^"]*)"', line)
        op_name = om.group(1) if om else ""
        ops.append({
            "op": op, "shapes": shapes, "bytes": nbytes,
            "per_split": "while/body" in op_name, "op_name": op_name,
        })
    return ops, cands[0]


def run_mode(mode, devices, rows, features, leaves):
    import shutil
    dump = tempfile.mkdtemp(prefix=f"comm_{mode}_")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "device_count" not in f and "dump" not in f]
    flags += [f"--xla_force_host_platform_device_count={devices}",
              f"--xla_dump_to={dump}", "--xla_dump_hlo_as_text"]
    env["XLA_FLAGS"] = " ".join(flags)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # persistent-cache hits skip compilation AND the dump; force a
    # fresh compile so the HLO always lands in dump_dir
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    if mode == "dp-psum":
        env["LGBM_TPU_DP_REDUCE"] = "psum"
    else:
        env.pop("LGBM_TPU_DP_REDUCE", None)
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", mode,
             "--rows", str(rows), "--features", str(features),
             "--leaves", str(leaves)],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=3600)
        assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
        ops, module = parse_dump(dump)
        return {"mode": mode, "devices": devices, "rows": rows,
                "features": features, "leaves": leaves, "module": module,
                "ops": ops,
                "per_split_bytes": sum(o["bytes"] for o in ops
                                       if o["per_split"]),
                "per_tree_bytes": sum(o["bytes"] for o in ops
                                      if not o["per_split"])}
    finally:
        shutil.rmtree(dump, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["dp-psum", "dp-scatter", "voting"],
                    default=None)
    ap.add_argument("--child", default=None)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--features", type=int, default=64)
    ap.add_argument("--leaves", type=int, default=31)
    ap.add_argument("--json", action="store_true")
    a = ap.parse_args()
    if a.child:
        child(a.child, a.rows, a.features, a.leaves)
        return
    modes = [a.mode] if a.mode else ["dp-psum", "dp-scatter", "voting"]
    results = [run_mode(m, a.devices, a.rows, a.features, a.leaves)
               for m in modes]
    if a.json:
        print(json.dumps(results))
        return
    for res in results:
        print(f"\n== {res['mode']} (D={res['devices']}, "
              f"{res['rows']}x{res['features']}, L={res['leaves']}) "
              f"[{res['module']}]")
        for o in res["ops"]:
            tag = "per-split" if o["per_split"] else "per-tree "
            print(f"  {tag} {o['op']:<18} {','.join(o['shapes'])} "
                  f"= {o['bytes']:,} B   ({o['op_name']})")
        print(f"  TOTAL per-split: {res['per_split_bytes']:,} B   "
              f"per-tree: {res['per_tree_bytes']:,} B")


if __name__ == "__main__":
    main()
