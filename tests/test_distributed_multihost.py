"""Multi-host topology: bootstrap, sharded ingest, rank-0 checkpoints,
and the acceptance bar — two-process localhost (`jax.distributed` +
gloo CPU collectives) data-parallel training is BIT-IDENTICAL to the
single-process virtual-mesh run for float and quantized configs, and a
kill-and-resume of both processes reproduces the uninterrupted model.

Fast tests cover the host-side topology logic (rank resolution, env
precedence, ceil row blocks, single-process fallbacks of every entry
point) and stay in tier-1; everything that spawns processes is
slow+distributed-tagged (compile-bound CI host).
"""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# fast: bootstrap config surface
# ---------------------------------------------------------------------------

def test_resolve_rank_explicit_and_hostname():
    from lightgbm_tpu.distributed import bootstrap
    entries = ["10.0.0.1:12400", "127.0.0.1:12400"]
    # explicit machine_rank short-circuits detection
    assert bootstrap.resolve_rank(entries, 0) == 0
    assert bootstrap.resolve_rank(entries, 1) == 1
    # hostname detection: 127.0.0.1 is always a local name
    assert bootstrap.resolve_rank(entries, -1) == 1
    assert bootstrap.resolve_rank(["10.9.9.9:1", "10.9.9.8:2"], -1) is None


def test_initialize_from_config_precedence(monkeypatch):
    from lightgbm_tpu.distributed import bootstrap
    calls = []
    monkeypatch.setattr(
        bootstrap, "initialize",
        lambda c, n, p, supervise=False: calls.append((c, n, p)))
    # single machine: no-op
    bootstrap.initialize_from_config("", num_machines=1)
    bootstrap.initialize_from_config("host:1", num_machines=1)
    assert calls == []
    # machines list: coordinator = entry 0, rank by explicit override
    bootstrap.initialize_from_config("a:1,b:2", machine_rank=1)
    assert calls[-1] == ("a:1", 2, 1)
    # explicit coordinator + machine_rank (no machines list)
    bootstrap.initialize_from_config(num_machines=3, machine_rank=2,
                                     coordinator="c:9")
    assert calls[-1] == ("c:9", 3, 2)
    # env trio wins over everything
    monkeypatch.setenv("LGBM_TPU_COORDINATOR", "env:7")
    monkeypatch.setenv("LGBM_TPU_NUM_PROCESSES", "4")
    monkeypatch.setenv("LGBM_TPU_PROCESS_ID", "3")
    bootstrap.initialize_from_config("a:1,b:2", machine_rank=0)
    assert calls[-1] == ("env:7", 4, 3)


def test_config_has_machine_rank_and_coordinator():
    from lightgbm_tpu.config import Config
    c = Config({"verbosity": -1})
    assert c.machine_rank == -1 and c.coordinator == ""
    c = Config({"process_id": 2, "coordinator_address": "h:12400",
                "verbosity": -1})
    assert c.machine_rank == 2 and c.coordinator == "h:12400"


def test_single_process_identity():
    from lightgbm_tpu.distributed import bootstrap
    assert bootstrap.process_count() == 1
    assert bootstrap.rank() == 0
    assert not bootstrap.is_distributed()
    bootstrap.barrier("noop")          # must be a no-op, not a hang
    mesh = bootstrap.global_mesh()
    assert mesh.axis_names == ("data",)
    # the learners' default mesh IS the bootstrap mesh (one authority)
    from lightgbm_tpu.parallel.mesh import make_mesh
    assert make_mesh(axis_name="data") is bootstrap.global_mesh("data")


# ---------------------------------------------------------------------------
# fast: ingest row blocks + single-process fallbacks
# ---------------------------------------------------------------------------

def test_shard_row_block_ceil_matches_learner():
    from lightgbm_tpu.distributed.ingest import shard_row_block
    for n, w in [(10, 3), (8, 2), (7, 4), (5, 8), (100, 1)]:
        local_n = -(-n // w)           # the device learner's shard size
        covered = []
        for r in range(w):
            lo, hi = shard_row_block(n, r, w)
            assert hi - lo <= local_n
            if r < w - 1 and hi < n:
                assert hi - lo == local_n
            covered.extend(range(lo, hi))
        assert covered == list(range(n))


def test_load_sharded_single_process_bit_identical():
    from lightgbm_tpu.distributed import ingest
    from lightgbm_tpu.io.dataset import Dataset
    r = np.random.RandomState(3)
    x = r.randn(300, 4)
    y = (x[:, 0] > 0).astype(np.float64)
    params = {"objective": "binary", "verbosity": -1}
    a = ingest.load_sharded(x, label=y, params=params)
    from lightgbm_tpu.config import Config
    b = Dataset(x, config=Config(params), label=y)
    np.testing.assert_array_equal(a.binned, b.binned)
    assert [m.num_bin for m in a.bin_mappers] == \
        [m.num_bin for m in b.bin_mappers]


def test_distributed_checkpoint_single_process_roundtrip(tmp_path):
    import lightgbm_tpu as lgb
    from lightgbm_tpu import engine
    from lightgbm_tpu.distributed.checkpoint import (
        DistributedCheckpointManager, restore_for_resume)
    r = np.random.RandomState(5)
    x = r.randn(300, 4)
    y = (x[:, 0] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 4, "verbosity": -1}
    bst = engine.train(dict(params), lgb.Dataset(x, y, free_raw_data=False),
                       num_boost_round=2, verbose_eval=False)
    mgr = DistributedCheckpointManager(str(tmp_path / "ck"))
    path = mgr.save(bst)
    assert os.path.exists(path)
    assert mgr.latest() is not None
    fresh = lgb.Booster(params, lgb.Dataset(x, y, free_raw_data=False))
    data = restore_for_resume(fresh, str(tmp_path / "ck"))
    assert data.iteration == 2
    assert fresh._gbdt.save_model_to_string(0, -1) == \
        bst._gbdt.save_model_to_string(0, -1)


def test_wire_byte_counters_single_process():
    # single-process allgather degenerates to identity but still counts
    from lightgbm_tpu.io.distributed import _allgather_host_bytes
    from lightgbm_tpu.telemetry import counters
    before = counters.get("dist_wire_bytes")
    chunks = _allgather_host_bytes(b"hello")
    assert chunks == [b"hello"]
    assert counters.get("dist_wire_bytes") > before
    assert counters.get("dist_allgathers") >= 1


# ---------------------------------------------------------------------------
# slow: real two-process topology over localhost
# ---------------------------------------------------------------------------

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _dist_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = ""              # 1 device per process
    return env


_TRAIN_WORKER = r"""
import os, sys
import numpy as np
rank = int(sys.argv[1]); port = sys.argv[2]; out = sys.argv[3]
mode = sys.argv[4]           # train | half | resume
quantized = sys.argv[5] == "1"
ckpt_dir = sys.argv[6]
import jax
from lightgbm_tpu.distributed import bootstrap, ingest
if rank >= 0:
    bootstrap.initialize(f"127.0.0.1:{port}", 2, rank)
    assert bootstrap.is_distributed() and len(jax.devices()) == 2
import lightgbm_tpu as lgb
from lightgbm_tpu import engine
from lightgbm_tpu.callback import checkpoint

r = np.random.RandomState(7)
n, f = 2000, 8
x = r.randn(n, f)
y = (1.5 * x[:, 0] - x[:, 1] + r.randn(n) * 0.5 > 0).astype(np.float64)
params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "max_bin": 63, "min_data_in_leaf": 20, "tree_learner": "data",
          "metric": "none"}
if quantized:
    params.update(quantized_grad=True, grad_bits=8)

def make_ds():
    return ingest.wrap_train_set(
        ingest.load_sharded(x, label=y, params=params))

TOTAL, HALF = 4, 2
if mode == "train":
    bst = engine.train(dict(params), make_ds(), num_boost_round=TOTAL,
                       verbose_eval=False)
elif mode == "half":
    # checkpointed run, killed (process exit) right after the barrier
    # of the HALF-iteration checkpoint
    bst = engine.train(dict(params), make_ds(), num_boost_round=HALF,
                       verbose_eval=False,
                       callbacks=[checkpoint(ckpt_dir,
                                             checkpoint_freq=HALF)])
    sys.exit(0)
elif mode == "resume":
    # non-zero ranks wait at the resume barrier; rank 0 broadcasts the
    # checkpoint bytes; all ranks restore bit-exact scores and finish
    bst = engine.train(dict(params), make_ds(), num_boost_round=TOTAL,
                       verbose_eval=False, resume_from=ckpt_dir)
else:
    raise SystemExit(f"bad mode {mode}")
with open(out, "w") as fh:
    fh.write(bst.model_to_string())
"""


def _launch_pair(script, port, outs, mode, quant, ckpt, timeout=600):
    env = _dist_env()
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), str(port), str(outs[r]),
         mode, quant, str(ckpt)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True) for r in range(2)]
    for p in procs:
        _, err = p.communicate(timeout=timeout)
        assert p.returncode == 0, err[-3000:]


def _run_virtual(script, out, mode, quant, ckpt, timeout=600):
    env = _dist_env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    p = subprocess.run(
        [sys.executable, str(script), "-1", "0", str(out), mode, quant,
         str(ckpt)],
        env=env, capture_output=True, text=True, timeout=timeout)
    assert p.returncode == 0, p.stderr[-3000:]


@pytest.mark.slow
@pytest.mark.distributed
@pytest.mark.parametrize("quant", ["0", "1"],
                         ids=["float", "quantized_grad8"])
def test_two_process_parity_vs_virtual_mesh(tmp_path, quant):
    """Acceptance: two-process localhost DP training == single-process
    virtual-mesh run, bit-identical model text (same mesh shape =>
    same XLA program; only shard placement differs)."""
    script = tmp_path / "worker.py"
    script.write_text(_TRAIN_WORKER)
    outs = [tmp_path / f"m2p_{r}.txt" for r in range(2)]
    _launch_pair(script, _free_port(), outs, "train", quant, "-")
    _run_virtual(script, tmp_path / "m1p.txt", "train", quant, "-")
    m0 = outs[0].read_text()
    m1 = outs[1].read_text()
    mv = (tmp_path / "m1p.txt").read_text()
    assert len(m0) > 500
    assert m0 == m1, "ranks disagree on the trained model"
    assert m0 == mv, "two-process model != virtual-mesh model"


@pytest.mark.slow
@pytest.mark.distributed
def test_two_process_kill_and_resume_bit_identical(tmp_path):
    """Acceptance: rank-0 checkpoint + resume barrier survives killing
    both processes after the midpoint checkpoint; the resumed final
    model is bit-identical to the uninterrupted two-process run."""
    script = tmp_path / "worker.py"
    script.write_text(_TRAIN_WORKER)
    ckpt = tmp_path / "ck"
    # uninterrupted run
    outs_full = [tmp_path / f"full_{r}.txt" for r in range(2)]
    _launch_pair(script, _free_port(), outs_full, "train", "0", "-")
    # checkpointed run, both processes exit after the midpoint barrier
    outs_half = [tmp_path / f"half_{r}.txt" for r in range(2)]
    _launch_pair(script, _free_port(), outs_half, "half", "0", ckpt)
    assert (ckpt.exists() and os.listdir(ckpt)), "rank 0 wrote no checkpoint"
    # both processes come back and resume through the broadcast restore
    outs_res = [tmp_path / f"res_{r}.txt" for r in range(2)]
    _launch_pair(script, _free_port(), outs_res, "resume", "0", ckpt)
    full = outs_full[0].read_text()
    res0 = outs_res[0].read_text()
    res1 = outs_res[1].read_text()
    assert len(full) > 500
    assert res0 == res1, "resumed ranks disagree"
    assert res0 == full, "kill-and-resume diverged from uninterrupted run"
