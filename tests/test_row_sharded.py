"""Row-sharded pod training (`dist_shard_mode=rows`): shard math,
reshard-after-shrink row redistribution, the loud learner-gating
matrix, and the slow two/three-process acceptance runs — rows-sharded
training bit-identical to replicated ingest at a fraction of the host
bytes, streamed chunked ingest composing with the distributed mesh,
and an elastic kill continuing at N-1 hosts through the in-process
re-bootstrap + `ingest.reshard`.

Fast tests are host-side only (no process spawning) and stay tier-1;
everything that spawns a process group is slow+distributed-tagged.
"""
import json
import os
import socket
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# fast: shard row-range math
# ---------------------------------------------------------------------------

def test_shard_row_block_non_dividing_worlds():
    from lightgbm_tpu.distributed.ingest import shard_row_block
    for n in (1, 5, 7, 100, 101, 103):
        for w in (1, 2, 3, 4):
            blocks = [shard_row_block(n, r, w) for r in range(w)]
            assert blocks[0][0] == 0
            assert max(hi for _, hi in blocks) == n
            for (lo, hi), (lo2, _hi2) in zip(blocks, blocks[1:]):
                # contiguous; short/empty tail blocks clip at n
                assert lo2 == min(hi, lo2) and hi >= lo
            # ceil split: every block but the tail has the same size
            sizes = [hi - lo for lo, hi in blocks if hi > lo]
            assert len(set(sizes[:-1])) <= 1


def test_shard_row_block_granularity_aligns_device_blocks():
    """`granularity` = per-process device count: block starts (and all
    non-tail block sizes) must land on per-device multiples so a rank's
    rows map exactly onto its own mesh positions."""
    from lightgbm_tpu.distributed.ingest import shard_row_block
    for n in (10, 97, 100, 1023):
        for w in (2, 3):
            for g in (2, 4):
                per_dev = -(-n // (w * g))
                blocks = [shard_row_block(n, r, w, granularity=g)
                          for r in range(w)]
                assert max(hi for _, hi in blocks) == n
                for lo, hi in blocks:
                    assert lo % (per_dev * g) == 0 or lo == n
                # no overlap, full cover
                got = sorted(blocks)
                assert got[0][0] == 0
                for (_, hi), (lo2, _) in zip(got, got[1:]):
                    assert lo2 == min(hi, lo2)


def test_reshard_redistributes_lost_rank_rows(monkeypatch):
    """World 3 -> 2 after a dead rank: `reshard` re-invokes the sharded
    loader for the CURRENT group, so the survivor's row block widens to
    absorb its share of the lost rank's rows."""
    from lightgbm_tpu.distributed import ingest
    calls = []

    def fake_load_partition(block, cfg, label_local=None,
                            weight_local=None, categorical=None,
                            params=None, feature_names=None,
                            shard_mode=None, row_begin=None,
                            num_total_rows=None):
        calls.append({"lo": row_begin, "hi": row_begin + block.shape[0],
                      "mode": shard_mode, "total": num_total_rows,
                      "label_rows": (0 if label_local is None
                                     else len(label_local))})
        return types.SimpleNamespace()

    monkeypatch.setattr(ingest, "load_partition", fake_load_partition)
    # pin the device granularity: the CI conftest forces a multi-device
    # virtual host, which would rescale the expected row ranges
    import jax
    monkeypatch.setattr(jax, "local_device_count", lambda: 1)
    world = {"n": 3, "r": 1}
    monkeypatch.setattr(ingest.bootstrap, "process_count",
                        lambda: world["n"])
    monkeypatch.setattr(ingest.bootstrap, "rank", lambda: world["r"])

    x = np.arange(200.0).reshape(100, 2)
    y = np.arange(100.0)
    ds = ingest.load_sharded(
        x, label=y, params={"dist_shard_mode": "rows", "verbosity": -1})
    # world 3: local_n = ceil(100/3) = 34 -> rank 1 owns rows 34:68
    assert (calls[-1]["lo"], calls[-1]["hi"]) == (34, 68)
    assert calls[-1]["mode"] == "rows" and calls[-1]["total"] == 100
    assert calls[-1]["label_rows"] == 34

    # rank 2 dies; survivors re-rank 0,1 of 2 and reshard
    world["n"], world["r"] = 2, 1
    ingest.reshard(ds)
    # world 2: local_n = 50 -> rank 1 now owns rows 50:100 (half the
    # dead rank's rows moved here)
    assert (calls[-1]["lo"], calls[-1]["hi"]) == (50, 100)
    assert calls[-1]["total"] == 100 and calls[-1]["label_rows"] == 50


# ---------------------------------------------------------------------------
# fast: loud gating of unsupported combinations
# ---------------------------------------------------------------------------

def _tiny_dataset(cfg):
    from lightgbm_tpu.io.dataset import Dataset
    r = np.random.RandomState(0)
    return Dataset(r.randn(60, 3), config=cfg,
                   label=(r.randn(60) > 0).astype(np.float64))


def test_stream_gating_names_keys_feature_and_voting():
    """The streaming learner matrix rejection must NAME the offending
    config keys and list the supported combinations — not a bare
    rejection (the bug this PR fixes)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.parallel.learners import create_tree_learner
    from lightgbm_tpu.utils.log import LightGBMError
    for name in ("feature", "voting"):
        cfg = Config({"tree_learner": name, "stream_mode": "chunked",
                      "verbosity": -1, "min_data_in_leaf": 5})
        ds = _tiny_dataset(cfg)
        with pytest.raises(LightGBMError) as ei:
            create_tree_learner(cfg, ds)
        msg = str(ei.value)
        assert f"tree_learner={name}" in msg
        assert "stream_mode=chunked" in msg
        assert "supported combinations" in msg


def test_stream_gating_names_keys_quant_and_goss_data_learner():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.parallel.learners import create_tree_learner
    from lightgbm_tpu.utils.log import LightGBMError
    # quantized gradients x streamed data-parallel: local vs global
    # quantization scales would diverge -> loud reject naming both keys
    cfg = Config({"tree_learner": "data", "stream_mode": "chunked",
                  "quantized_grad": True, "grad_bits": 8,
                  "verbosity": -1, "min_data_in_leaf": 5})
    ds = _tiny_dataset(cfg)
    with pytest.raises(LightGBMError) as ei:
        create_tree_learner(cfg, ds)
    msg = str(ei.value)
    assert "quant_bits=8" in msg and "tree_learner=data" in msg
    assert "supported combinations" in msg
    # GOSS working-set streaming has no sharded counterpart
    cfg = Config({"tree_learner": "data", "stream_mode": "goss",
                  "boosting": "goss", "verbosity": -1,
                  "min_data_in_leaf": 5})
    ds = _tiny_dataset(cfg)
    with pytest.raises(LightGBMError) as ei:
        create_tree_learner(cfg, ds)
    assert "stream_mode=goss" in str(ei.value)
    assert "supported combinations" in str(ei.value)


def test_row_sharded_dataset_requires_data_learner():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.parallel.learners import create_tree_learner
    from lightgbm_tpu.utils.log import LightGBMError
    cfg = Config({"tree_learner": "serial", "verbosity": -1,
                  "min_data_in_leaf": 5})
    ds = _tiny_dataset(cfg)
    ds.row_shard = (0, 120)            # pretend: local block of a pod
    with pytest.raises(LightGBMError) as ei:
        create_tree_learner(cfg, ds)
    msg = str(ei.value)
    assert "dist_shard_mode=rows" in msg and "tree_learner=serial" in msg


def test_config_rejects_rows_with_feature_parallel():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError):
        Config({"dist_shard_mode": "rows", "tree_learner": "feature",
                "verbosity": -1})
    with pytest.raises(LightGBMError):
        Config({"dist_shard_mode": "bogus", "verbosity": -1})


# ---------------------------------------------------------------------------
# slow: real process groups over localhost
# ---------------------------------------------------------------------------

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _dist_env(virtual_devices=0):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={virtual_devices}"
        if virtual_devices else "")
    return env


_TRAIN_WORKER = r"""
import json, sys
import numpy as np
rank = int(sys.argv[1]); port = sys.argv[2]; out = sys.argv[3]
mode = sys.argv[4]; stream = sys.argv[5]; quant = sys.argv[6] == "1"
import jax
from lightgbm_tpu.distributed import bootstrap, ingest
if rank >= 0:
    bootstrap.initialize(f"127.0.0.1:{port}", 2, rank)
    assert bootstrap.is_distributed() and len(jax.devices()) == 2
import lightgbm_tpu as lgb
r = np.random.RandomState(7)
n, f = 1200, 10
x = r.randn(n, f)
y = (1.5 * x[:, 0] - x[:, 1] + r.randn(n) * 0.5 > 0).astype(np.float64)
params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "max_bin": 63, "min_data_in_leaf": 20, "tree_learner": "data",
          "metric": "none", "dist_shard_mode": mode}
if stream != "off":
    params["stream_mode"] = stream
if quant:
    params.update(quantized_grad=True, grad_bits=8)
ds = ingest.wrap_train_set(ingest.load_sharded(x, label=y, params=params))
bst = lgb.train(params, ds, num_boost_round=3, verbose_eval=False)
# the shard mode (and stream mode) are placement choices, allowed to
# differ in the params dump; the trees must be bit-identical
txt = "\n".join(l for l in bst.model_to_string().splitlines()
                if not l.startswith("[dist_shard_mode:"))
payload = {"model": txt,
           "host_bytes": int(getattr(ds._inner, "_ingest_host_bytes", 0))}
with open(out, "w") as fh:
    json.dump(payload, fh)
"""


def _launch_pair(script, outs, mode, stream, quant, timeout=600):
    port = _free_port()
    env = _dist_env()
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), str(port), str(outs[r]),
         mode, stream, quant],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True) for r in range(2)]
    for p in procs:
        _, err = p.communicate(timeout=timeout)
        assert p.returncode == 0, err[-3000:]
    res = []
    for o in outs:
        with open(o) as fh:
            res.append(json.load(fh))
    return res


@pytest.mark.slow
@pytest.mark.distributed
@pytest.mark.parametrize("quant", ["0", "1"],
                         ids=["float", "quantized_grad8"])
def test_rows_sharded_bit_identical_to_replicated(tmp_path, quant):
    """Acceptance: quantized (and float) row-sharded two-process
    training grows the SAME trees as replicated ingest — the histogram
    exchange is the only thing that crosses hosts — while each rank
    stores fewer bytes than the replicated full matrix."""
    script = tmp_path / "worker.py"
    script.write_text(_TRAIN_WORKER)
    rep = _launch_pair(script,
                       [tmp_path / f"rep_{r}.json" for r in range(2)],
                       "replicated", "off", quant)
    rows = _launch_pair(script,
                        [tmp_path / f"rows_{r}.json" for r in range(2)],
                        "rows", "off", quant)
    assert len(rows[0]["model"]) > 500
    assert rows[0]["model"] == rows[1]["model"], "ranks disagree"
    assert rows[0]["model"] == rep[0]["model"], \
        "row-sharded model != replicated-ingest model"
    assert max(r["host_bytes"] for r in rows) < rep[0]["host_bytes"], \
        "rows mode did not shrink the per-rank host footprint"


@pytest.mark.slow
@pytest.mark.distributed
def test_streamed_chunked_composes_with_distributed(tmp_path):
    """Acceptance: stream_mode=chunked x two-process distributed — the
    per-device streamed buffer assembly runs under the mesh, both
    ingest modes and the single-process virtual mesh agree bit-exactly
    (same program, different topology)."""
    script = tmp_path / "worker.py"
    script.write_text(_TRAIN_WORKER)
    rows = _launch_pair(script,
                        [tmp_path / f"srows_{r}.json" for r in range(2)],
                        "rows", "chunked", "0")
    rep = _launch_pair(script,
                       [tmp_path / f"srep_{r}.json" for r in range(2)],
                       "replicated", "chunked", "0")
    vout = tmp_path / "svirt.json"
    p = subprocess.run(
        [sys.executable, str(script), "-1", "0", str(vout),
         "replicated", "chunked", "0"],
        env=_dist_env(virtual_devices=2), capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    with open(vout) as fh:
        virt = json.load(fh)
    assert len(rows[0]["model"]) > 500
    assert rows[0]["model"] == rows[1]["model"], "ranks disagree"
    assert rows[0]["model"] == rep[0]["model"], \
        "streamed rows-sharded != streamed replicated"
    assert rows[0]["model"] == virt["model"], \
        "streamed two-process != streamed virtual mesh"


_KILL_WORKER = r"""
import json, sys
import numpy as np
rank = int(sys.argv[1]); port = sys.argv[2]; out = sys.argv[3]
ckpt_dir = sys.argv[4]; world = int(sys.argv[5])
import jax
from lightgbm_tpu.distributed import bootstrap, ingest, supervisor
bootstrap.initialize(f"127.0.0.1:{port}", world, rank, supervise=True)
supervisor.start_supervision(heartbeat_ms=100,
                             collective_timeout_ms=30000)
import lightgbm_tpu as lgb
from lightgbm_tpu import engine
from lightgbm_tpu.callback import checkpoint
from lightgbm_tpu.resilience import faults
from lightgbm_tpu.telemetry import counters
r = np.random.RandomState(7)
n, f = 1200, 8
x = r.randn(n, f)
y = (1.5 * x[:, 0] - x[:, 1] + r.randn(n) * 0.5 > 0).astype(np.float64)
params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "max_bin": 63, "min_data_in_leaf": 20, "tree_learner": "data",
          "metric": "none", "dist_shard_mode": "rows",
          "on_rank_failure": "shrink"}
if rank == world - 1:
    faults.install("kill_rank@iter=3")
ds = ingest.wrap_train_set(ingest.load_sharded(x, label=y, params=params))
bst = engine.train(params, ds, num_boost_round=6, verbose_eval=False,
                   callbacks=[checkpoint(ckpt_dir, checkpoint_freq=2)])
payload = {"model": bst.model_to_string(),
           "shrinks": counters.get("shrinks"),
           "world_after": bootstrap.process_count()}
with open(out, "w") as fh:
    json.dump(payload, fh)
"""


@pytest.mark.slow
@pytest.mark.distributed
@pytest.mark.chaos
def test_elastic_kill_continues_at_n_minus_1(tmp_path):
    """Acceptance: a 3-process rows-sharded group loses its last rank
    mid-run; the two survivors re-form a 2-process group IN-PROCESS
    (supervisor re-bootstrap), `ingest.reshard` redistributes the dead
    rank's rows, and training finishes at N-1 — not single-host."""
    script = tmp_path / "worker.py"
    script.write_text(_KILL_WORKER)
    ckpt = tmp_path / "ck"
    port = _free_port()
    env = _dist_env()
    outs = [tmp_path / f"k_{r}.json" for r in range(3)]
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), str(port), str(outs[r]),
         str(ckpt), "3"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True) for r in range(3)]
    errs = {}
    for r, p in enumerate(procs):
        _, errs[r] = p.communicate(timeout=600)
    assert procs[2].returncode != 0, "victim was not killed"
    for r in (0, 1):
        assert procs[r].returncode == 0, f"survivor {r}:\n" \
            + errs[r][-3000:]
    res = []
    for r in (0, 1):
        with open(outs[r]) as fh:
            res.append(json.load(fh))
    assert res[0]["shrinks"] == 1 and res[1]["shrinks"] == 1
    assert res[0]["world_after"] == 2 and res[1]["world_after"] == 2, \
        "survivors fell back to single-host instead of re-forming"
    assert res[0]["model"] == res[1]["model"], \
        "re-formed group diverged between survivors"
    assert len(res[0]["model"]) > 500
