"""Quantized-gradient histogram pipeline tests.

Covers the ISSUE-2 acceptance surface: property tests of quantized vs
f64-reference histograms (error bounded by the quantization step as a
function of grad_bits), bit-exactness of integer sibling subtraction,
the Pallas integer kernel vs the XLA integer contraction, AUC parity of
quantized vs float training, and the distributed learners' int32
histogram collectives (payload dtype/size asserted).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Dataset as InnerDataset
from lightgbm_tpu.models.gbdt import create_boosting
from lightgbm_tpu.ops import histogram as hist_ops
from lightgbm_tpu.ops import quantize as quant_ops
from lightgbm_tpu.ops.pallas import histogram_kernel as pallas_kernel

from conftest import make_binary


def _auc(y, s):
    order = np.argsort(s)
    ranks = np.empty(len(s))
    ranks[order] = np.arange(1, len(s) + 1)
    pos = y > 0
    return float((ranks[pos].sum() - pos.sum() * (pos.sum() + 1) / 2)
                 / (pos.sum() * (~pos).sum()))


def _quantized_inputs(n=4000, f=6, b=32, bits=8, seed=3):
    r = np.random.RandomState(seed)
    codes = jnp.asarray(r.randint(0, b, (n, f), dtype=np.uint8))
    grad = jnp.asarray(r.randn(n).astype(np.float32))
    hess = jnp.asarray(r.rand(n).astype(np.float32))
    packed, s_g, s_h = quant_ops.quantize_gh(
        grad, hess, jax.random.PRNGKey(seed), grad_bits=bits)
    ghq = quant_ops.gh_operand(packed, jnp.ones(n, bool), bits)
    return codes, grad, hess, packed, ghq, s_g, s_h


# ---------------------------------------------------------------------------
# quantization primitives
# ---------------------------------------------------------------------------

def test_pack_roundtrip_and_range():
    _, _, _, packed, _, _, _ = _quantized_inputs(bits=8)
    qg, qh = quant_ops.unpack_gh(packed)
    assert bool(jnp.all(quant_ops.pack_gh(qg, qh) == packed))
    qmax = quant_ops.quant_max(8, 4000)
    assert int(jnp.max(jnp.abs(qg))) <= qmax
    assert int(jnp.max(jnp.abs(qh))) <= qmax


def test_quant_max_overflow_cap():
    # a 16-bit request at huge N degrades so int32 sums cannot overflow
    assert quant_ops.quant_max(8, 100_000) == 127
    assert quant_ops.quant_max(16, 1 << 20) == (1 << 30) // (1 << 20)
    n = 1 << 20
    assert quant_ops.quant_max(16, n) * n <= (1 << 30)


def test_operand_dtype_by_bits():
    assert quant_ops.operand_dtype(8) == jnp.int8
    assert quant_ops.operand_dtype(16) == jnp.int32


@pytest.mark.parametrize("bits", [8, 16])
def test_integer_histogram_exact_sums(bits):
    """The single integer contraction must equal an int64 scatter-add
    EXACTLY — no rounding anywhere in the integer domain."""
    codes, _, _, _, ghq, _, _ = _quantized_inputs(bits=bits)
    hq = np.asarray(hist_ops.build_histogram_quantized(
        codes, ghq, 32, chunk_size=512), dtype=np.int64)
    cn = np.asarray(codes)
    ghn = np.asarray(ghq, dtype=np.int64)
    for fi in range(cn.shape[1]):
        for lane in range(3):
            ref = np.zeros(32, np.int64)
            np.add.at(ref, cn[:, fi], ghn[:, lane])
            assert np.array_equal(ref, hq[fi, :, lane]), (fi, lane)


@pytest.mark.parametrize("begin,count", [(0, 0), (700, 200), (500, 1100),
                                         (2500, 1500), (0, 4000)])
@pytest.mark.parametrize("bits", [8, 16])
def test_integer_histogram_of_a_row_range_exact_sums(bits, begin, count):
    """The ranged sum (the compact core's child histogram) in the
    integer domain: the chunks that meet [begin, begin + count) of a
    window of 7 chunks and a ragged eighth, equal to an int64
    scatter-add over those rows EXACTLY, int8 and int32 operands."""
    codes, _, _, _, ghq, _, _ = _quantized_inputs(bits=bits)
    got = np.asarray(jax.jit(
        lambda c, g, b, n: hist_ops.build_histogram_range(
            hist_ops.rows_loader(c, g), 4000, b, n, 6, 32, quantized=True,
            chunk_size=512))(codes, ghq, begin, count), dtype=np.int64)
    assert got.shape == (6, 32, 3)
    cn = np.asarray(codes)[begin:begin + count]
    ghn = np.asarray(ghq, dtype=np.int64)[begin:begin + count]
    ref = np.zeros((6, 32, 3), np.int64)
    for fi in range(6):
        np.add.at(ref[fi], cn[:, fi], ghn)
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("bits", [8, 16])
def test_quantized_vs_f64_reference_error_bound(bits):
    """Property: per-bin |dequantized - f64 reference| <= cnt_bin / s
    (stochastic rounding moves each row by strictly less than one
    quantization step)."""
    codes, grad, hess, _, ghq, s_g, s_h = _quantized_inputs(bits=bits)
    hq = hist_ops.build_histogram_quantized(codes, ghq, 32)
    deq = np.asarray(quant_ops.dequantize_histogram(hq, s_g, s_h),
                     dtype=np.float64)
    cn = np.asarray(codes)
    cnt = np.asarray(hq, np.float64)[..., 2]
    for lane, (vec, scale) in enumerate(
            [(np.asarray(grad, np.float64), float(s_g)),
             (np.asarray(hess, np.float64), float(s_h))]):
        for fi in range(cn.shape[1]):
            ref = np.zeros(32, np.float64)
            np.add.at(ref, cn[:, fi], vec)
            bound = cnt[fi] / scale + 1e-9
            assert np.all(np.abs(deq[fi, :, lane] - ref) <= bound), \
                (bits, lane, fi)


def test_error_shrinks_with_grad_bits():
    """16-bit quantization must be strictly tighter than 8-bit on the
    same data (the scale grows with the bit budget)."""
    errs = {}
    for bits in (8, 16):
        codes, grad, _, _, ghq, s_g, s_h = _quantized_inputs(bits=bits)
        hq = hist_ops.build_histogram_quantized(codes, ghq, 32)
        deq = np.asarray(quant_ops.dequantize_histogram(hq, s_g, s_h),
                         dtype=np.float64)
        cn = np.asarray(codes)
        ref = np.zeros((cn.shape[1], 32), np.float64)
        for fi in range(cn.shape[1]):
            np.add.at(ref[fi], cn[:, fi], np.asarray(grad, np.float64))
        errs[bits] = np.abs(deq[..., 0] - ref).max()
    assert errs[16] < errs[8]


def test_sibling_subtraction_bit_exact():
    """parent - left == right as INTEGERS for any partition — the f32
    path only guarantees this to rounding error."""
    codes, _, _, _, ghq, _, _ = _quantized_inputs(bits=8)
    r = np.random.RandomState(11)
    mask = jnp.asarray(r.rand(codes.shape[0]) < 0.31)
    parent = hist_ops.build_histogram_quantized(codes, ghq, 32)
    left = hist_ops.build_histogram_quantized(
        codes, ghq * mask[:, None].astype(ghq.dtype), 32)
    right = hist_ops.build_histogram_quantized(
        codes, ghq * (~mask)[:, None].astype(ghq.dtype), 32)
    sib = hist_ops.subtract_histogram(parent, left)
    assert sib.dtype == jnp.int32
    assert bool(jnp.all(sib == right))


def test_pallas_quantized_kernel_matches_xla():
    codes, _, _, _, ghq, _, _ = _quantized_inputs(n=3000, f=10, bits=8)
    want = hist_ops.build_histogram_quantized(codes, ghq, 32)
    got = pallas_kernel.build_histogram_pallas_quantized(
        codes, ghq, 32, interpret=True)
    assert got.dtype == jnp.int32
    assert bool(jnp.all(got == want))
    # grad_bits > 8 stores int32, which Mosaic cannot contract on the MXU
    ghq16 = _quantized_inputs(n=3000, f=10, bits=16)[4]
    with pytest.raises(ValueError, match="int8"):
        pallas_kernel.build_histogram_pallas_quantized(
            codes, ghq16, 32, interpret=True)


# ---------------------------------------------------------------------------
# chunk-size satellite
# ---------------------------------------------------------------------------

def test_resolve_chunk_size():
    # explicit wins
    assert hist_ops.resolve_chunk_size(1024, 28, 64) == 1024
    # derived from the factored planes' width: a power of two, the two
    # cells' fastest on the chip (PERF.md §6, PR 32), the floor for a
    # wide shape and the ceiling for a narrow one
    assert hist_ops.resolve_chunk_size(0, 28, 256) == 8192
    assert hist_ops.resolve_chunk_size(0, 67, 256) == 4096
    assert hist_ops.resolve_chunk_size(0, 500, 256) == 2048
    assert hist_ops.resolve_chunk_size(0, 4, 16) == 32768


def test_chunk_size_does_not_change_histogram():
    codes, grad, hess, _, _, _, _ = _quantized_inputs(n=5000, f=4, b=16)
    gh = jnp.stack([grad, hess, jnp.ones_like(grad)], axis=1)
    a = np.asarray(hist_ops.build_histogram(codes, gh, 16, chunk_size=512))
    b = np.asarray(hist_ops.build_histogram(codes, gh, 16, chunk_size=0))
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_hist_chunk_size_param_trains():
    x, y = make_binary(n=3000)
    cfg = Config({"objective": "binary", "num_leaves": 7,
                  "hist_chunk_size": 512, "verbosity": -1})
    ds = InnerDataset(x, config=cfg, label=y)
    b = create_boosting(cfg, ds)
    for _ in range(3):
        b.train_one_iter()
    assert len(b.models) == 3


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

def test_config_quant_knobs():
    assert Config({}).quant_bits == 0          # float path is the default
    cfg = Config({"use_quantized_grad": True, "grad_bits": 16})
    assert cfg.quantized_grad is True and cfg.quant_bits == 16
    assert Config({"quantized_grad": True}).quant_bits == 8


# ---------------------------------------------------------------------------
# end-to-end training parity
# ---------------------------------------------------------------------------

def _train_auc(x, y, extra, host_learner, rounds=12):
    import os
    old = os.environ.get("LGBM_TPU_HOST_LEARNER")
    os.environ["LGBM_TPU_HOST_LEARNER"] = "1" if host_learner else "0"
    try:
        params = {"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 5, "verbosity": -1}
        params.update(extra)
        cfg = Config(params)
        ds = InnerDataset(x, config=cfg, label=y)
        b = create_boosting(cfg, ds)
        for _ in range(rounds):
            b.train_one_iter()
        return _auc(y, b.predict_raw(x)[:, 0]), b
    finally:
        if old is None:
            os.environ.pop("LGBM_TPU_HOST_LEARNER", None)
        else:
            os.environ["LGBM_TPU_HOST_LEARNER"] = old


@pytest.mark.parametrize("host_learner", [False, True],
                         ids=["device", "host"])
def test_auc_parity_quantized_vs_float(host_learner):
    """|AUC(quantized) - AUC(float)| <= 0.005 on a fixed seed (the
    bench-shaped binary problem, both tree learners)."""
    x, y = make_binary(n=8000)
    auc_f, bf = _train_auc(x, y, {}, host_learner)
    auc_q, bq = _train_auc(
        x, y, {"quantized_grad": True, "grad_bits": 8}, host_learner)
    assert abs(auc_f - auc_q) <= 0.005, (auc_f, auc_q)
    # both actually learned
    assert auc_f > 0.9 and auc_q > 0.9


def test_quantized_uses_masked_device_strategy():
    """Serial quantized training stays on the whole-tree device learner
    with the masked (int-pool) strategy, jit-cache-keyed on quant_bits."""
    from lightgbm_tpu.models.device_learner import DeviceTreeLearner
    from lightgbm_tpu.parallel.learners import create_tree_learner
    x, y = make_binary(n=3000)
    cfg = Config({"objective": "binary", "quantized_grad": True,
                  "verbosity": -1})
    ds = InnerDataset(x, config=cfg, label=y)
    learner = create_tree_learner(cfg, ds)
    assert isinstance(learner, DeviceTreeLearner)
    assert learner.strategy == "masked"
    assert learner.quant_bits == 8


def test_quantized_grad_16_trains():
    x, y = make_binary(n=4000)
    auc_q, _ = _train_auc(
        x, y, {"quantized_grad": True, "grad_bits": 16}, False, rounds=8)
    assert auc_q > 0.9


# ---------------------------------------------------------------------------
# distributed learners: int32 collective payloads
# ---------------------------------------------------------------------------

def _record_psums(monkeypatch):
    records = []
    real_psum = jax.lax.psum

    def rec_psum(x, axis_name, **kw):
        for leaf in jax.tree_util.tree_leaves(x):
            records.append((tuple(getattr(leaf, "shape", ())),
                            getattr(leaf, "dtype", None)))
        return real_psum(x, axis_name, **kw)

    monkeypatch.setattr(jax.lax, "psum", rec_psum)
    return records


def _train_parallel(x, y, tree_learner, quantized):
    params = {"objective": "binary", "tree_learner": tree_learner,
              "num_leaves": 15, "min_data_in_leaf": 5, "verbosity": -1}
    if quantized:
        params.update(quantized_grad=True, grad_bits=8)
    cfg = Config(params)
    ds = InnerDataset(x, config=cfg, label=y)
    b = create_boosting(cfg, ds)
    for _ in range(3):
        b.train_one_iter()
    return b


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs multi-device")
def test_data_parallel_quantized_int32_payload(monkeypatch):
    """The host DP learner's quantized histogram allreduce must move
    int32 lanes — and only TWO of them (the count lane stays off the
    wire: 2/3 the bytes of the float path's f32 triple). Forced to the
    host learner: since the packed-row tentpole the DEVICE DP learner
    takes quantized configs too (covered by the scatter payload test in
    test_quantized_rows.py)."""
    monkeypatch.setenv("LGBM_TPU_HOST_LEARNER", "1")
    x, y = make_binary(n=4000)
    records = _record_psums(monkeypatch)
    b = _train_parallel(x, y, "data", quantized=True)
    from lightgbm_tpu.parallel.learners import DataParallelTreeLearner
    assert type(b.learner) is DataParallelTreeLearner
    hist_payloads = [(s, d) for s, d in records if len(s) == 3]
    assert hist_payloads, "no histogram collective traced"
    for shape, dtype in hist_payloads:
        assert dtype == jnp.int32, (shape, dtype)
        assert shape[2] == 2, shape      # [sum_qg, sum_qh], no count lane
    f, bins, _ = hist_payloads[0][0]
    quant_bytes = f * bins * 2 * 4
    float_bytes = f * bins * 3 * 4
    assert quant_bytes * 3 == float_bytes * 2
    # sanity: the model still learns through the compact reduction
    assert _auc(y, b.predict_raw(x)[:, 0]) > 0.85


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs multi-device")
def test_data_parallel_float_payload_unchanged(monkeypatch):
    """Float DP stays on the f32 triple — the default path is untouched.
    (Forces the host-loop DP learner, the like-for-like comparison with
    the quantized payload test; the device DP learner reduces via
    psum_scatter instead.)"""
    monkeypatch.setenv("LGBM_TPU_HOST_LEARNER", "1")
    x, y = make_binary(n=4000)
    records = _record_psums(monkeypatch)
    _train_parallel(x, y, "data", quantized=False)
    hist_payloads = [(s, d) for s, d in records if len(s) == 3]
    assert hist_payloads
    assert all(d == jnp.float32 and s[2] == 3 for s, d in hist_payloads)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs multi-device")
def test_voting_quantized_int32_payload(monkeypatch):
    """Quantized voting reduces the elected features' histograms as
    int32 (votes themselves stay a tiny f32 vector)."""
    x, y = make_binary(n=4000)
    records = _record_psums(monkeypatch)
    b = _train_parallel(x, y, "voting", quantized=True)
    hist_payloads = [(s, d) for s, d in records if len(s) == 3]
    assert hist_payloads, "no elected-histogram collective traced"
    assert all(d == jnp.int32 for s, d in hist_payloads), hist_payloads
    assert _auc(y, b.predict_raw(x)[:, 0]) > 0.85


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs multi-device")
def test_data_parallel_quantized_tree_quality():
    """Quantized DP trees agree with quantized single-device training to
    AUC tolerance (the reduction changes only summation order, which is
    exact in the integer domain; counts are hessian-derived)."""
    x, y = make_binary(n=6000)
    b_dp = _train_parallel(x, y, "data", quantized=True)
    b_serial = _train_parallel(x, y, "serial", quantized=True)
    auc_dp = _auc(y, b_dp.predict_raw(x)[:, 0])
    auc_s = _auc(y, b_serial.predict_raw(x)[:, 0])
    assert abs(auc_dp - auc_s) <= 0.01, (auc_dp, auc_s)


# ---------------------------------------------------------------------------
# host-score caching satellite
# ---------------------------------------------------------------------------

def test_host_scores_cached_per_iteration():
    x, y = make_binary(n=2000)
    cfg = Config({"objective": "binary", "num_leaves": 7, "verbosity": -1,
                  "metric": ["auc", "binary_logloss"]})
    ds = InnerDataset(x, config=cfg, label=y)
    b = create_boosting(cfg, ds)
    b.train_one_iter()
    su = b.score_updater
    s1 = su.host_scores()
    assert su.host_scores() is s1          # second fetch: cache hit
    b.train_one_iter()                     # any score mutation invalidates
    s2 = su.host_scores()
    assert s2 is not s1
    assert not np.allclose(s1, s2)
