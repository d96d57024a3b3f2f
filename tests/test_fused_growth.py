"""Single-program tree growth (`grow_program=fused_tree`) tests.

ISSUE-17 acceptance surface: the fixed-trip `lax.scan` formulation of
the growth cores must grow BIT-IDENTICAL trees to the `per_split`
`while_loop` formulation (float and quantized, compact and chunk
strategies, categorical splits, min_data_in_leaf stops), the
vmap-batched multiclass program must match the per-class loop, and the
dispatch counters must prove the O(leaves) -> O(1) win: <= 3
growth-program dispatches per tree on the device learner, exactly 1/K
per tree when K classes batch through one vmapped program.

Parity contract (docs/Quick-Start.md "Single-program growth"):
predictions, split features/thresholds/children and leaf values are
bit-exact across `grow_program` and across the vmap batching; the
`split_gain` DISPLAY metadata may drift ~1 ulp (XLA reassociates the
gain arithmetic when the loop lowering changes), which never affects
routing — the canonical model text elides gains (and the dependent
tree_sizes byte counts) and the gains are separately pinned allclose.
"""
import re

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.models.device_learner import DeviceTreeLearner
from lightgbm_tpu.telemetry import counters

from conftest import make_binary

BASE = {
    "objective": "binary",
    "num_leaves": 15,
    "max_bin": 63,
    "learning_rate": 0.2,
    "min_data_in_leaf": 20,
    "verbosity": -1,
    "metric": "none",
    "seed": 7,
}


_VOLATILE = re.compile(
    r"^(split_gain=.*|tree_sizes=.*|\[grow_program: .*\])$", re.M)


def _canon(txt: str) -> str:
    """Model text with the documented-parity fields elided (split_gain,
    the tree_sizes byte counts that depend on the gains' decimal
    rendering, and the grow_program parameter echo)."""
    return _VOLATILE.sub("<elided>", txt)


def _gains(txt: str):
    return [float(v) for line in re.findall(r"^split_gain=(.*)$", txt,
                                            re.M) for v in line.split()]


def _assert_parity(txt_a, pred_a, txt_b, pred_b, gain_rtol=1e-4):
    np.testing.assert_array_equal(pred_a, pred_b)
    assert _canon(txt_a) == _canon(txt_b)
    np.testing.assert_allclose(_gains(txt_a), _gains(txt_b),
                               rtol=gain_rtol)


def _train(params, x, y, n_iter=3, categorical=None):
    ds = lgb.Dataset(x, y, categorical_feature=categorical or "auto",
                     free_raw_data=False)
    bst = lgb.train(dict(params), ds, num_boost_round=n_iter)
    return bst, bst.model_to_string()


def _ab(params, x, y, monkeypatch, strategy, n_iter=3, categorical=None):
    """Train the same config under per_split and fused_tree; return the
    (model string, predictions) pair for each."""
    monkeypatch.setenv("LGBM_TPU_STRATEGY", strategy)
    out = []
    for program in ("per_split", "fused_tree"):
        p = dict(params, grow_program=program)
        bst, txt = _train(p, x, y, n_iter=n_iter, categorical=categorical)
        out.append((txt, bst.predict(x, raw_score=True)))
    return out


# ---------------------------------------------------------------------------
# bit-exactness: fused_tree vs per_split
# ---------------------------------------------------------------------------

def test_fused_bitexact_compact_float_categorical(monkeypatch):
    """Compact strategy, float gradients, a categorical feature and a
    tight min_data_in_leaf (early stop path inside the scan)."""
    x, y = make_binary(n=1200, f=8)
    x[:, 0] = np.random.RandomState(3).randint(0, 6, len(x))
    (txt_a, pred_a), (txt_b, pred_b) = _ab(
        dict(BASE, min_data_in_leaf=60), x, y, monkeypatch,
        strategy="compact", categorical=[0])
    _assert_parity(txt_a, pred_a, txt_b, pred_b)


def test_fused_bitexact_chunk_quantized(monkeypatch):
    """Chunk strategy with quantized gradients — the integer-domain
    scan must replay the exact same splits."""
    x, y = make_binary(n=1200, f=8)
    monkeypatch.setenv("LGBM_TPU_CHUNK", "512")
    (txt_a, pred_a), (txt_b, pred_b) = _ab(
        dict(BASE, quantized_grad=True, grad_bits=16), x, y,
        monkeypatch, strategy="chunk")
    _assert_parity(txt_a, pred_a, txt_b, pred_b)


@pytest.mark.slow
def test_fused_bitexact_masked_float_and_quant(monkeypatch):
    """Masked (dense) strategy, both gradient domains."""
    x, y = make_binary(n=1500, f=10)
    for extra in ({}, {"quantized_grad": True, "grad_bits": 8}):
        (txt_a, pred_a), (txt_b, pred_b) = _ab(
            dict(BASE, **extra), x, y, monkeypatch, strategy="masked")
        _assert_parity(txt_a, pred_a, txt_b, pred_b)


# ---------------------------------------------------------------------------
# vmap-batched multiclass
# ---------------------------------------------------------------------------

def _train_multiclass(x, y, k, monkeypatch, batched, n_iter=2, **extra):
    if batched:
        monkeypatch.delenv("LGBM_TPU_NO_VMAP_K", raising=False)
    else:
        monkeypatch.setenv("LGBM_TPU_NO_VMAP_K", "1")
    params = dict(BASE, objective="multiclass", num_class=k,
                  grow_program="fused_tree", **extra)
    return _train(params, x, y, n_iter=n_iter)


# vmap over >= 8 classes batches the histogram contraction into a dot
# that XLA:CPU (jaxlib 0.9.0) cannot run
_xfail_cpu_batched_bf16_dot = pytest.mark.xfail(
    jax.default_backend() == "cpu", raises=jax.errors.JaxRuntimeError,
    reason="XLA:CPU: 'UNIMPLEMENTED: Unsupported element type for "
           "DotThunk::Execute: BF16 x BF16 = F32'")


@_xfail_cpu_batched_bf16_dot
def test_vmap_k8_matches_per_class_loop(monkeypatch):
    """One vmapped program for all 8 per-class trees must produce
    bit-identical predictions and tree structure to 8 sequential
    dispatches (split_gain documented-parity, as everywhere).

    Uses a min_gain_to_split above the float32 noise floor: the ~1 ulp
    gain reassociation under vmap can flip the argmax between two
    splits whose TRUE gains tie at ~1e-6 (both choices are
    equivalent-quality noise splits) — the documented contract prunes
    that degenerate band rather than pinning which noise split wins.
    Small gains amplify the ulp through cancellation, hence the wider
    (still display-only) gain tolerance."""
    r = np.random.RandomState(7)
    centers = r.randn(8, 8) * 1.2
    yi = r.randint(0, 8, 800)
    x = centers[yi] + r.randn(800, 8)
    y = yi.astype(np.float64)
    monkeypatch.setenv("LGBM_TPU_STRATEGY", "masked")
    bst_loop, txt_loop = _train_multiclass(x, y, 8, monkeypatch,
                                           batched=False,
                                           min_gain_to_split=1e-3)
    bst_vmap, txt_vmap = _train_multiclass(x, y, 8, monkeypatch,
                                           batched=True,
                                           min_gain_to_split=1e-3)
    _assert_parity(txt_loop, bst_loop.predict(x, raw_score=True),
                   txt_vmap, bst_vmap.predict(x, raw_score=True),
                   gain_rtol=2e-3)


@pytest.mark.slow
@_xfail_cpu_batched_bf16_dot
def test_vmap_k100_smoke(monkeypatch):
    """Large-K: 100 per-class trees through ONE batched program per
    iteration, counters prove it."""
    r = np.random.RandomState(5)
    y = (np.arange(400) % 100).astype(np.float64)   # every class present
    centers = r.randn(100, 6) * 2.5
    x = centers[y.astype(int)] + r.randn(400, 6)
    monkeypatch.setenv("LGBM_TPU_STRATEGY", "masked")
    telemetry.reset()
    bst, _ = _train_multiclass(x, y, 100, monkeypatch, batched=True,
                               n_iter=1, num_leaves=7)
    assert len(bst._gbdt.models) == 100
    pred = bst.predict(x[:50])
    assert pred.shape == (50, 100)
    assert np.all(np.isfinite(pred))
    np.testing.assert_allclose(pred.sum(axis=1), 1.0, rtol=1e-5)
    assert counters.get("grow_trees") == 100.0
    assert counters.get("grow_dispatches") == 1.0
    assert counters.get("grow_dispatches_per_tree") == pytest.approx(0.01)


# ---------------------------------------------------------------------------
# dispatch accounting
# ---------------------------------------------------------------------------

def test_fused_tree_dispatches_per_tree_within_contract(monkeypatch):
    """Device learner, fused program: the committed perf contract is
    <= 3 growth dispatches per tree (measured: exactly 1)."""
    x, y = make_binary(n=1000, f=8)
    monkeypatch.setenv("LGBM_TPU_STRATEGY", "masked")
    telemetry.reset()
    bst, _ = _train(dict(BASE, grow_program="fused_tree"), x, y, n_iter=4)
    assert isinstance(bst._gbdt.learner, DeviceTreeLearner)
    assert counters.get("grow_trees") == 4.0
    assert counters.get("grow_dispatches_per_tree") <= 3.0


@pytest.mark.slow
def test_serial_host_loop_dispatch_count_is_per_split(monkeypatch):
    """The host-loop learner dispatches O(leaves) programs per tree —
    the gauge documents the gap the fused program closes. Also pins the
    per-tree hoists: meta/categorical masks are built once per tree,
    not once per split."""
    x, y = make_binary(n=800, f=8)
    monkeypatch.setenv("LGBM_TPU_HOST_LEARNER", "1")
    telemetry.reset()
    bst, txt = _train(dict(BASE), x, y, n_iter=2)
    lrn = bst._gbdt.learner
    assert type(lrn).__name__ == "SerialTreeLearner"
    assert counters.get("grow_trees") == 2.0
    # root fused step + one apply_split per split: > 3 by construction
    assert counters.get("grow_dispatches_per_tree") > 3.0
    assert lrn._meta_cache is not None      # hoisted, not per-split
    # determinism across the cache: a second identical train matches
    _, txt2 = _train(dict(BASE), x, y, n_iter=2)
    assert txt == txt2
