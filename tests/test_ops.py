"""Device op tests: histogram, split scan, partition — against numpy oracles
(the host-oracle pattern from the reference's GPU_DEBUG_COMPARE,
gpu_tree_learner.cpp:996-1019)."""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops import histogram as hist_ops
from lightgbm_tpu.ops import partition as part_ops
from lightgbm_tpu.ops import split as split_ops


def _ref_histogram(binned, g, h, valid, num_bins):
    n, f = binned.shape
    out = np.zeros((f, num_bins, 3))
    for i in range(n):
        if not valid[i]:
            continue
        for j in range(f):
            b = binned[i, j]
            out[j, b, 0] += g[i]
            out[j, b, 1] += h[i]
            out[j, b, 2] += 1
    return out


def test_histogram_matches_oracle():
    r = np.random.RandomState(0)
    n, f, b = 500, 5, 16
    binned = r.randint(0, b, size=(n, f)).astype(np.uint8)
    g = r.randn(n).astype(np.float32)
    h = r.rand(n).astype(np.float32)
    valid = np.ones(n, dtype=bool)
    valid[450:] = False
    gh = np.stack([g * valid, h * valid, valid.astype(np.float32)], axis=1)
    got = np.asarray(hist_ops.build_histogram(
        jnp.asarray(binned), jnp.asarray(gh), num_bins=b))
    want = _ref_histogram(binned, g, h, valid, b)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_histogram_chunked_matches():
    r = np.random.RandomState(1)
    n, f, b = 5000, 3, 8
    binned = r.randint(0, b, size=(n, f)).astype(np.uint8)
    gh = r.randn(n, 3).astype(np.float32)
    gh[:, 2] = 1.0
    a = np.asarray(hist_ops.build_histogram(
        jnp.asarray(binned), jnp.asarray(gh), num_bins=b, chunk_size=512))
    c = np.asarray(hist_ops.build_histogram(
        jnp.asarray(binned), jnp.asarray(gh), num_bins=b, chunk_size=8192))
    np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-3)


def _histogram_case(f, num_bins, rows, code_dtype, seed):
    """Codes, float and int8 operands and their float64 / int64 sums.
    The last rows are padding: operand 0 and arbitrary codes (negative
    ones where the type has them)."""
    r = np.random.RandomState(seed)
    codes = r.randint(0, num_bins, size=(rows, f)).astype(code_dtype)
    gh = np.stack([r.randn(rows), r.rand(rows), np.ones(rows)],
                  axis=1).astype(np.float32)
    ghq = np.stack([r.randint(-127, 128, rows), r.randint(0, 128, rows),
                    np.ones(rows, np.int64)], axis=1).astype(np.int8)
    pad = max(1, rows // 10)
    gh[-pad:] = 0
    ghq[-pad:] = 0
    if np.issubdtype(code_dtype, np.signedinteger):
        codes[-pad:] = -1 - codes[-pad:]
    want = np.zeros((f, num_bins, 3))
    want_q = np.zeros((f, num_bins, 3), np.int64)
    kept = codes[:-pad].astype(np.int64)
    for j in range(f):
        np.add.at(want[j], kept[:, j], gh[:-pad].astype(np.float64))
        np.add.at(want_q[j], kept[:, j], ghq[:-pad].astype(np.int64))
    return codes, gh, ghq, want, want_q


_HIST_CHUNK = 64
# (F, num_bins) x rows below, at and above one chunk (the last neither a
# multiple of it nor of 8); the codes' type goes round with the case
_HIST_GRID = [
    pytest.param(f, b, rows, (np.uint8, np.int16, np.int8)[
        (i + j + k) % (3 if b <= 128 else 2)], id=f"{f}x{b}-rows{rows}")
    for i, f in enumerate((1, 5, 28, 67))
    for j, b in enumerate((2, 16, 63, 255, 256))
    for k, rows in enumerate((40, 64, 150))]


@pytest.mark.parametrize("f,num_bins,rows,code_dtype", _HIST_GRID)
def test_histogram_grid_matches_float64(f, num_bins, rows, code_dtype):
    codes, gh, _, want, _ = _histogram_case(f, num_bins, rows, code_dtype,
                                            seed=f * num_bins + rows)
    got = np.asarray(hist_ops.build_histogram(
        jnp.asarray(codes), jnp.asarray(gh), num_bins=num_bins,
        chunk_size=_HIST_CHUNK))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("f,num_bins,rows,code_dtype", _HIST_GRID)
def test_quantized_histogram_grid_is_exact(f, num_bins, rows, code_dtype):
    codes, _, ghq, _, want_q = _histogram_case(
        f, num_bins, rows, code_dtype, seed=f * num_bins + rows)
    got = np.asarray(hist_ops.build_histogram_quantized(
        jnp.asarray(codes), jnp.asarray(ghq), num_bins=num_bins,
        chunk_size=_HIST_CHUNK))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want_q)


# (begin, count) in a window of 5 chunks of 64 rows and, ragged, of 4 and
# 37 rows more: no row, rows of one chunk, over one and over three chunk
# edges, from a chunk's first row, up to the window's last row (a right
# child), into the ragged last chunk, and the whole window
_HIST_RANGES = [(100, 0), (0, 0), (70, 30), (40, 60), (60, 140), (128, 64),
                (200, 120), (1, 319), (0, 320)]
_RAGGED_RANGES = [(290, 67), (330, 27), (0, 357), (256, 0)]


def _ranged(codes, operand, rows, begin, count, num_bins, quantized):
    import jax
    return np.asarray(jax.jit(
        lambda c, g, b, n: hist_ops.build_histogram_range(
            hist_ops.rows_loader(c, g), rows, b, n, codes.shape[1],
            num_bins, quantized=quantized, chunk_size=_HIST_CHUNK))(
        jnp.asarray(codes), jnp.asarray(operand), begin, count))


@pytest.mark.parametrize("rows,begin,count",
                         [(320,) + r for r in _HIST_RANGES]
                         + [(357,) + r for r in _RAGGED_RANGES])
def test_ranged_histogram_equals_the_masked_whole_window(rows, begin, count):
    """`build_histogram_range` sums the chunks that meet the range and
    no other, on the whole window's chunk grid and in its order: bit
    for bit the whole-window histogram with the other rows' operand 0,
    float32 and int32, the ragged last chunk (read from further up and
    rolled back) included."""
    codes, gh, ghq, _, _ = _histogram_case(5, 63, rows, np.uint8,
                                           seed=rows + begin)
    pad = max(1, rows // 10)                  # no padding rows here
    gh[-pad:], ghq[-pad:] = gh[:pad], ghq[:pad]
    inside = ((np.arange(rows) >= begin)
              & (np.arange(rows) < begin + count))[:, None]
    whole = np.asarray(hist_ops.build_histogram(
        jnp.asarray(codes), jnp.asarray(gh * inside), num_bins=63,
        chunk_size=_HIST_CHUNK))
    got = _ranged(codes, gh, rows, begin, count, 63, False)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, whole)
    assert (count > 0) == bool(np.any(got))
    whole_q = np.asarray(hist_ops.build_histogram_quantized(
        jnp.asarray(codes), jnp.asarray(ghq * inside.astype(np.int8)),
        num_bins=63, chunk_size=_HIST_CHUNK))
    got_q = _ranged(codes, ghq, rows, begin, count, 63, True)
    assert got_q.dtype == np.int32
    np.testing.assert_array_equal(got_q, whole_q)


@pytest.mark.parametrize("begin,count,chunks",
                         [(100, 0, 1), (70, 30, 1), (40, 60, 2),
                          (60, 140, 4), (128, 64, 1), (0, 320, 5),
                          (290, 67, 2)])
def test_ranged_histogram_runs_the_chunks_of_the_range(monkeypatch, begin,
                                                       count, chunks):
    """The loop's trip count is read from the range: run eagerly (a
    Python loop), it contracts the chunks that hold a row of the range,
    and one chunk of zeros where the range is empty."""
    import jax
    rows = 357 if begin + count > 320 else 320
    codes, gh, _, _, _ = _histogram_case(5, 63, rows, np.uint8, seed=9)
    calls = []
    real = hist_ops._hist_chunk
    monkeypatch.setattr(
        hist_ops, "_hist_chunk",
        lambda c, g, b: calls.append(c.shape[0]) or real(c, g, b))
    with jax.disable_jit():
        hist_ops.build_histogram_range(
            hist_ops.rows_loader(jnp.asarray(codes), jnp.asarray(gh)), rows,
            begin, count, 5, 63, chunk_size=_HIST_CHUNK)
    assert calls == [_HIST_CHUNK] * chunks


@pytest.mark.parametrize("f,num_bins", [(28, 256), (5, 64)])
def test_histogram_at_the_derived_chunk(f, num_bins):
    """Two chunks and a ragged third at the chunk the shape resolves to."""
    chunk = hist_ops.resolve_chunk_size(0, f, num_bins)
    codes, gh, ghq, want, want_q = _histogram_case(
        f, num_bins, 2 * chunk + 77, np.uint8, seed=f)
    got = np.asarray(hist_ops.build_histogram(
        jnp.asarray(codes), jnp.asarray(gh), num_bins=num_bins))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    got_q = np.asarray(hist_ops.build_histogram_quantized(
        jnp.asarray(codes), jnp.asarray(ghq), num_bins=num_bins))
    np.testing.assert_array_equal(got_q, want_q)


def test_subtraction():
    r = np.random.RandomState(2)
    parent = r.randn(4, 8, 3).astype(np.float32)
    child = r.randn(4, 8, 3).astype(np.float32)
    got = np.asarray(hist_ops.subtract_histogram(
        jnp.asarray(parent), jnp.asarray(child)))
    np.testing.assert_allclose(got, parent - child, rtol=1e-6)


def _ref_best_split(hist, sum_g, sum_h, n, num_bins_f, l2, min_data, min_hess):
    """Brute-force simple split finder (no missing, no l1) for oracles."""
    best = (-1e30, -1, -1)
    for f in range(hist.shape[0]):
        for t in range(num_bins_f[f] - 1):
            gl = hist[f, : t + 1, 0].sum()
            hl = hist[f, : t + 1, 1].sum()
            cl = hist[f, : t + 1, 2].sum()
            gr, hr, cr = sum_g - gl, sum_h - hl, n - cl
            if cl < min_data or cr < min_data or hl < min_hess or hr < min_hess:
                continue
            gain = gl * gl / (hl + l2) + gr * gr / (hr + l2)
            if gain > best[0]:
                best = (gain, f, t)
    return best


def test_split_scan_matches_bruteforce():
    r = np.random.RandomState(3)
    f, b = 6, 16
    hist = np.abs(r.randn(f, b, 3)).astype(np.float32)
    hist[:, :, 0] = r.randn(f, b)
    # force identical totals per feature (all features see all rows): the
    # scan sums both children from the bins, so the bins have to add up
    # to the leaf's totals. Hessians and counts (positive sums) are
    # scaled; a gradient sum may be near zero, so its gap goes into bin 0
    totals = hist[0].sum(axis=0)
    for j in range(1, f):
        hist[j, :, 1:] *= totals[1:] / hist[j, :, 1:].sum(axis=0)
        hist[j, 0, 0] += totals[0] - hist[j, :, 0].sum()
    sum_g, sum_h, n = totals
    nbins = np.full(f, b, dtype=np.int32)
    res = split_ops.find_best_split(
        jnp.asarray(hist), jnp.float32(sum_g), jnp.float32(sum_h),
        jnp.float32(n), jnp.asarray(nbins), jnp.zeros(f, jnp.int32),
        jnp.zeros(f, jnp.int32), jnp.ones(f, bool), jnp.zeros(f, jnp.int32),
        jnp.float32(-np.inf), jnp.float32(np.inf),
        num_bins=b, l1=0.0, l2=1.0, max_delta_step=0.0,
        min_data_in_leaf=1, min_sum_hessian=1e-3, min_gain_to_split=0.0)
    want_gain, want_f, want_t = _ref_best_split(
        hist.astype(np.float64), sum_g, sum_h, n, nbins, 1.0, 1, 1e-3)
    parent_gain = sum_g ** 2 / (sum_h + 1.0)
    got_gain = float(res.gain) + parent_gain  # res.gain is relative
    assert int(res.feature) == want_f
    assert int(res.threshold) == want_t
    np.testing.assert_allclose(got_gain, want_gain, rtol=1e-3)


def test_split_scan_min_data_constraint():
    f, b = 1, 4
    hist = np.zeros((f, b, 3), dtype=np.float32)
    hist[0, 0] = [5.0, 2.0, 2.0]   # tiny left bin
    hist[0, 1] = [-5.0, 50.0, 100.0]
    hist[0, 2] = [3.0, 50.0, 100.0]
    totals = hist[0].sum(axis=0)
    res = split_ops.find_best_split(
        jnp.asarray(hist), jnp.float32(totals[0]), jnp.float32(totals[1]),
        jnp.float32(totals[2]), jnp.asarray([b], jnp.int32),
        jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.int32),
        jnp.ones(1, bool), jnp.zeros(1, jnp.int32),
        jnp.float32(-np.inf), jnp.float32(np.inf),
        num_bins=b, l1=0.0, l2=0.0, max_delta_step=0.0,
        min_data_in_leaf=50, min_sum_hessian=1e-3, min_gain_to_split=0.0)
    # only threshold t=1 leaves >= 50 rows on both sides
    assert int(res.threshold) == 1


def test_split_missing_nan_direction():
    """NaN bin mass must flow to the default side chosen by the sweep."""
    f, b = 1, 5
    hist = np.zeros((f, b, 3), dtype=np.float32)
    # bins 0..2 regular, bin 4 = NaN bin (num_bin=5 incl nan); bin 3 unused
    hist[0, 0] = [10.0, 10.0, 10.0]
    hist[0, 1] = [-10.0, 10.0, 10.0]
    hist[0, 2] = [8.0, 10.0, 10.0]
    hist[0, 4] = [20.0, 5.0, 5.0]   # NaN rows with positive grads
    totals = hist[0].sum(axis=0)
    res = split_ops.find_best_split(
        jnp.asarray(hist), jnp.float32(totals[0]), jnp.float32(totals[1]),
        jnp.float32(totals[2]), jnp.asarray([b], jnp.int32),
        jnp.asarray([2], jnp.int32),  # MissingType::NaN
        jnp.zeros(1, jnp.int32), jnp.ones(1, bool), jnp.zeros(1, jnp.int32),
        jnp.float32(-np.inf), jnp.float32(np.inf),
        num_bins=b, l1=0.0, l2=0.0, max_delta_step=0.0,
        min_data_in_leaf=1, min_sum_hessian=0.0, min_gain_to_split=0.0)
    # verify left+right sums partition the parent exactly
    np.testing.assert_allclose(
        float(res.left_sum_grad + res.right_sum_grad), totals[0], rtol=1e-5)
    np.testing.assert_allclose(
        float(res.left_count + res.right_count), totals[2], rtol=1e-6)


def test_partition_stable_and_counts():
    r = np.random.RandomState(4)
    n, f = 300, 3
    binned = r.randint(0, 8, size=(n, f)).astype(np.uint8)
    buf = part_ops.make_indices_buffer(n, 512)
    new_buf, left_cnt = part_ops.partition_step(
        buf, jnp.asarray(binned), jnp.int32(0), jnp.int32(n),
        jnp.int32(1), jnp.int32(3), jnp.bool_(False), jnp.int32(0),
        jnp.int32(0), jnp.int32(8), bucket=512)
    new_buf = np.asarray(new_buf)
    left_cnt = int(left_cnt)
    want_left = np.nonzero(binned[:, 1] <= 3)[0]
    assert left_cnt == len(want_left)
    # stability: left side keeps original relative order
    np.testing.assert_array_equal(np.sort(new_buf[:left_cnt]), want_left)
    got_left = new_buf[:left_cnt]
    assert np.all(np.diff(got_left) > 0)  # stable partition of sorted input
    # all rows still present exactly once
    np.testing.assert_array_equal(np.sort(new_buf[:n]), np.arange(n))


def test_partition_preserves_overrun_region():
    n = 100
    binned = np.zeros((n, 1), dtype=np.uint8)
    binned[:50, 0] = 1
    buf = part_ops.make_indices_buffer(n, 256)
    # partition only the first 60 rows with a window that overruns into rows 60+
    new_buf, left_cnt = part_ops.partition_step(
        buf, jnp.asarray(binned), jnp.int32(0), jnp.int32(60),
        jnp.int32(0), jnp.int32(0), jnp.bool_(False), jnp.int32(0),
        jnp.int32(0), jnp.int32(2), bucket=256)
    new_buf = np.asarray(new_buf)
    # rows 60..99 untouched
    np.testing.assert_array_equal(new_buf[60:100], np.arange(60, 100))
    # rows 50..59 have bin 0 -> left; rows 0..49 bin 1 -> right
    assert int(left_cnt) == 10
    np.testing.assert_array_equal(new_buf[:10], np.arange(50, 60))


@pytest.mark.parametrize("num_bins", [16, 64, 128])
def test_pallas_histogram_interpret_parity(num_bins):
    """Execute the Pallas kernel in interpret mode (chip_smoke.py's
    kernels leg compiles it with Mosaic on the chip) and compare against
    the XLA one-hot path — the GPU_DEBUG_COMPARE host-oracle pattern
    (reference: gpu_tree_learner.cpp:996-1019)."""
    from lightgbm_tpu.ops.pallas import histogram_kernel as pk
    r = np.random.RandomState(7)
    n, f = 3000, 11          # non-multiples of chunk_rows / FEAT_TILE
    binned = r.randint(0, num_bins, size=(n, f)).astype(np.uint8)
    g = r.randn(n).astype(np.float32)
    h = r.rand(n).astype(np.float32)
    valid = np.ones(n, dtype=bool)
    valid[2700:] = False
    gh = np.stack([g * valid, h * valid, valid.astype(np.float32)], axis=1)
    got = np.asarray(pk.build_histogram_pallas(
        jnp.asarray(binned), jnp.asarray(gh), num_bins, interpret=True))
    want = np.asarray(hist_ops.build_histogram(
        jnp.asarray(binned), jnp.asarray(gh), num_bins=num_bins,
        use_pallas=False))
    # XLA path sums via split-bf16 passes, the kernel in f32 — allow the
    # ~1e-5 relative drift between the two float paths
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-3)
    # and against the scalar oracle for absolute ground truth
    ref = _ref_histogram(binned, g, h, valid, num_bins)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_pallas_histogram_transposed_layout_interpret():
    from lightgbm_tpu.ops.pallas import histogram_kernel as pk
    r = np.random.RandomState(8)
    n, f, b = 2048, 8, 32
    binned = r.randint(0, b, size=(n, f)).astype(np.uint8)
    gh = np.stack([r.randn(n), r.rand(n), np.ones(n)], axis=1).astype(np.float32)
    got = np.asarray(pk.build_histogram_pallas_t(
        jnp.asarray(binned.T.copy()), jnp.asarray(gh), b, interpret=True))
    want = _ref_histogram(binned, gh[:, 0], gh[:, 1], np.ones(n, bool), b)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_bucketed_predict_matches_unbucketed():
    """Shape-bucketed ensemble tensorization (compile-cache reuse across
    growing tree counts) must not change predictions: padding trees are
    single-leaf zeros."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import predict as predict_ops

    r = np.random.RandomState(3)
    x = r.randn(400, 5).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1] > 0).astype(np.float64)
    ds = lgb.Dataset(x, y)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1, "metric": "none"}, ds,
                    num_boost_round=5)
    models = bst._gbdt.models
    a_plain = predict_ops.trees_to_arrays(models)
    a_bucket = predict_ops.trees_to_arrays(models, bucket=True)
    # 5 trees bucket to 8; node/leaf axes to powers of two
    assert a_bucket.split_feature.shape[0] == 8
    assert a_plain.split_feature.shape[0] == 5
    tc_plain = jnp.zeros(5, jnp.int32)
    tc_bucket = jnp.zeros(8, jnp.int32)
    out_p = predict_ops.predict_raw_ensemble(
        jnp.asarray(x), a_plain, tc_plain,
        max_depth=a_plain.max_depth, num_class=1)
    out_b = predict_ops.predict_raw_ensemble(
        jnp.asarray(x), a_bucket, tc_bucket,
        max_depth=a_bucket.max_depth, num_class=1)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_b),
                               rtol=1e-6, atol=1e-7)
    # the public predict path (bucketed) agrees with per-row host replay
    pred = bst.predict(x, raw_score=True)
    host = np.array([sum(t.predict_row(row) for t in models) for row in x])
    np.testing.assert_allclose(pred, host, rtol=1e-5, atol=1e-6)


def test_histogram_multichunk_inside_shard_map():
    """The scanned multi-chunk path (window > chunk_size) must build
    inside a shard_map region: its carry is seeded from the first chunk
    so it carries the data's varying manual axes (a replicated zeros
    carry fails shard_map's scan carry type check — this was invisible
    until a host-loop learner met a >2048-row window on a mesh)."""
    import jax
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    r = np.random.RandomState(0)
    rows = r.randint(0, 64, (8 * 4096, 13)).astype(np.uint8)
    gh = r.randn(8 * 4096, 3).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))

    # chunk pinned BELOW the local window so the scanned multi-chunk
    # path stays exercised (the derived default would single-chunk 4096
    # local rows for this shape)
    def f(b, g):
        return jax.lax.psum(
            hist_ops.build_histogram(b, g, 64, chunk_size=2048), "data")

    fn = jax.jit(shard_map(f, mesh=mesh,
                           in_specs=(P("data", None), P("data", None)),
                           out_specs=P()))
    got = np.asarray(fn(rows, gh))
    want = np.asarray(hist_ops.build_histogram(
        jnp.asarray(rows), jnp.asarray(gh), 64, chunk_size=2048))
    np.testing.assert_allclose(got, want, atol=2e-3)
