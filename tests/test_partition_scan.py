"""The sort-free partition (cumsum ranks + one row scatter, tile by tile
over SCATTER_TILE_ROWS) is the compact core's partition off the TPU:
held here to a stable argsort's order, tiled and untiled, and to the
trees of a core that moves no rows. On the TPU each tile goes through
the Pallas lane compaction (ops/pallas/partition_kernel.py), held here
in interpret mode to the scan, bit for bit, tile by tile and in the
tiled window."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest


@functools.partial(jax.jit, static_argnames=("tile_rows",))
def _partition_jit(win, key3, tile_rows):
    from lightgbm_tpu.models.device_learner import partition_window
    return partition_window(win, key3, tile_rows=tile_rows)


def _toy(seed, n, f=6):
    """Rows, labels and one gradient pair for a learner-level case."""
    r = np.random.RandomState(seed)
    x = r.randn(n, f).astype(np.float32)
    y = ((x[:, 0] - 0.5 * x[:, 1] + 0.3 * r.randn(n)) > 0).astype(np.float64)
    g = jnp.asarray((r.rand(n) - 0.5).astype(np.float32))
    h = jnp.asarray((0.1 + r.rand(n)).astype(np.float32))
    return x, y, g, h


def _grow_compact(x, y, g, h):
    """Tree text of one DeviceTreeLearner(strategy="compact") tree."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.device_learner import DeviceTreeLearner
    cfg = Config({"objective": "binary", "num_leaves": 15, "max_bin": 63,
                  "min_data_in_leaf": 20, "verbosity": -1})
    lrn = DeviceTreeLearner(cfg, Dataset(x, config=cfg, label=y),
                            strategy="compact")
    return lrn.train(g, h).to_string()


def test_compact_learner_identical_trees_with_scan_partition(monkeypatch):
    """With nothing set in the environment, on any backend, the compact
    core partitions by the scan, and grows the tree of the masked core,
    which moves no rows: text for text under exact-arithmetic gradients,
    where the cores' different summation orders give the same sums."""
    from test_chunk_strategy import exact_grads, grow_tree_with
    from lightgbm_tpu.models import device_learner as dl
    monkeypatch.delenv("LGBM_TPU_STRATEGY", raising=False)
    r = np.random.RandomState(23)
    x, y, _, _ = _toy(23, 3000)
    g, h = exact_grads(r, 3000)
    scanned = []
    real = dl._scan_partition

    def spy(win, key3):
        scanned.append(win.shape[0])
        return real(win, key3)

    monkeypatch.setattr(dl, "_scan_partition", spy)
    # traced anew, so that the spy sees this learner's windows
    dl.grow_tree_compact.clear_cache()
    compact = grow_tree_with(monkeypatch, "compact", x, y, g, h)
    dl.grow_tree_compact.clear_cache()
    assert sorted(set(scanned)) == dl._size_classes(3000) == [3000]
    assert compact == grow_tree_with(monkeypatch, "masked", x, y, g, h)


def _keys(pattern, w, rng):
    """key3 of one window: 0 = left, 1 = right, 2 = the overrun rows past
    the leaf's count, which both callers of partition_window make the
    window's tail (`valid = arange < pcount`)."""
    left = rng.rand(w) < 0.4
    pcount = {"mixed_tail": (2 * w) // 3, "all_left": w - 5,
              "all_right": w - 5, "empty_tail": w, "ragged": w}[pattern]
    if pattern == "all_left":
        left[:] = True
    if pattern == "all_right":
        left[:] = False
    return np.where(np.arange(w) < pcount, np.where(left, 0, 1),
                    2).astype(np.int32)


@pytest.mark.parametrize("pattern", ["mixed_tail", "all_left", "all_right",
                                     "empty_tail", "ragged"])
@pytest.mark.parametrize("d_cols", [5, 11])
@pytest.mark.parametrize("t", [64, 256])
@pytest.mark.parametrize("w", [1000, 4096, 12000])
def test_tiled_scan_partition_equals_untiled(w, t, d_cols, pattern):
    """Bit for bit, tile by tile: full tiles, the ragged last tile, a
    side with no rows, and key-2 rows only as the tail (the precondition
    of the tiled path, which leaves them where the input has them)."""
    if pattern == "ragged":
        w -= 37                      # never a multiple of 64 or 256
        assert w % t
    rng = np.random.RandomState(w + t + d_cols)
    win = jnp.asarray(rng.randint(0, 2**32, size=(w, d_cols),
                                  dtype=np.uint64).astype(np.uint32))
    key3 = jnp.asarray(_keys(pattern, w, rng))
    tiled = _partition_jit(win, key3, t)
    np.testing.assert_array_equal(
        np.asarray(tiled), np.asarray(_partition_jit(win, key3, w)))
    # the reference: the rows in a stable sort's order of their keys
    np.testing.assert_array_equal(
        np.asarray(tiled),
        np.asarray(win)[np.argsort(np.asarray(key3), kind="stable")])


_T, _W = 256, 2048
# the leaf's rows in a window of eight tiles, and in one of eight tiles
# and a ragged ninth step (the top rung): none, one, a row short of a
# tile, a tile, a row over, two tiles, a row short of the window, all
_COUNTS = [(_W, c) for c in (0, 1, _T - 1, _T, _T + 1, 2 * _T, _W - 1, _W)] \
    + [(_W + 91, c) for c in (0, _T + 1, _W - 1, _W, _W + 1, _W + 91)]


@pytest.mark.parametrize("w,count", _COUNTS)
def test_tiled_scan_partition_for_every_count_of_leaf_rows(w, count):
    """The tile loop ends with the last tile that holds a row of the
    leaf (and the ragged last step runs only for a row past the whole
    tiles): bit for bit `_scan_partition` of the whole window at every
    count, the skipped tiles' rows where the input has them."""
    from lightgbm_tpu.models.device_learner import _scan_partition
    rng = np.random.RandomState(w + count)
    win = jnp.asarray(rng.randint(0, 2**32, size=(w, 5),
                                  dtype=np.uint64).astype(np.uint32))
    key3 = jnp.asarray(np.where(np.arange(w) < count,
                                (rng.rand(w) < 0.6).astype(np.int32), 2))
    np.testing.assert_array_equal(
        np.asarray(_partition_jit(win, key3, _T)),
        np.asarray(jax.jit(_scan_partition)(win, key3)[0]))


@pytest.mark.parametrize("w,count", _COUNTS)
def test_tiled_scan_partition_runs_the_tiles_of_the_leaf(monkeypatch, w,
                                                         count):
    """The trip count is read from the keys: run eagerly (a Python
    loop), the tiled partition scatters ceil(count / tile) whole tiles
    and the ragged step only for a row of the leaf past them."""
    from lightgbm_tpu.models import device_learner as dl
    scattered = []
    real = dl._scan_partition
    monkeypatch.setattr(
        dl, "_scan_partition",
        lambda win, key3: scattered.append(win.shape[0]) or real(win, key3))
    key3 = jnp.asarray(np.where(np.arange(w) < count, 1, 2).astype(np.int32))
    with jax.disable_jit():
        dl.partition_window(jnp.zeros((w, 3), jnp.uint32), key3,
                            tile_rows=_T)
    whole = min(-(-count // _T), w // _T)
    assert scattered == [_T] * whole + [w % _T] * (count > whole * _T)


def test_compact_learner_identical_trees_with_tiled_scan(monkeypatch):
    """The learner's own path with the tile forced under the window: the
    tiled branches grow the untiled scan's trees, text for text."""
    from lightgbm_tpu.models import device_learner as dl
    toy = _toy(29, 5000)
    tiled_windows = []
    real = dl._scan_partition_tiled

    def spy(win, key3, tile_rows):
        tiled_windows.append((win.shape[0], tile_rows))
        return real(win, key3, tile_rows)

    monkeypatch.setattr(dl, "_scan_partition_tiled", spy)

    def grow(tile_rows):
        # the tile is no static of the jitted growth program, so a
        # program traced under another tile must not be handed back
        dl.grow_tree_compact.clear_cache()
        monkeypatch.setattr(dl, "SCATTER_TILE_ROWS", tile_rows)
        return _grow_compact(*toy)

    untiled = grow(1 << 18)
    assert tiled_windows == []
    tiled = grow(512)
    dl.grow_tree_compact.clear_cache()
    # both rungs of the ladder, 4096 and n itself (ragged: 5000 = 9 x 512
    # + 392), went tile by tile
    assert sorted(set(tiled_windows)) == [(4096, 512), (5000, 512)]
    assert tiled == untiled


def _walk_rung_rows(pcounts, lefts, left_small, ladder, tile, chunk, bounded):
    """(rows run, rows needed) by a plain walk over a tree's splits: what
    `rung_rows` has to give."""
    run = needed = 0
    for p, l, small_left in zip(pcounts, lefts, left_small):
        wsz = next(w for w in ladder if w >= p)
        s_begin, s_count = (0, l) if small_left else (l, p - l)
        whole, rem = divmod(wsz, tile)
        if wsz <= tile or not bounded:
            run += wsz
        else:
            run += min(-(-p // tile), whole) * tile
            run += rem if p > whole * tile else 0
        half = (wsz + 1) // 2
        rows, off = (half, s_begin - min(s_begin, wsz - half)) \
            if s_count <= half else (wsz, s_begin)
        chunks = -(-rows // chunk)
        if bounded:
            first = min(off // chunk, chunks - 1)
            chunks = max(min(-(-(off + s_count) // chunk), chunks) - first, 1)
        run += rows if rows <= chunk else chunks * chunk
        needed += p + s_count
    return float(run), float(needed)


@pytest.mark.parametrize("bounded", [True, False])
def test_rung_row_counters_of_a_recorded_tree(monkeypatch, bounded):
    """`rung_rows_run` / `rung_rows_needed`, fed once a tree from the
    split records, against the plain walk over the same records, with
    the tile and the chunk forced under the windows (ladder 4096, 8192,
    9000) so that both loops have trips to save; `bounded=False` is the
    same arithmetic for loops that run to the rung's width."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.models import device_learner as dl
    from lightgbm_tpu.models.device_learner import R_LCNT, R_RCNT
    from lightgbm_tpu.ops import histogram as hist_ops
    monkeypatch.setattr(dl, "SCATTER_TILE_ROWS", 512)
    monkeypatch.setattr(hist_ops, "_CHUNK_FLOOR", 128)
    monkeypatch.setattr(hist_ops, "_CHUNK_CEIL", 128)
    recs = []
    real = dl.DeviceTreeLearner._count_partition_rows
    monkeypatch.setattr(
        dl.DeviceTreeLearner, "_count_partition_rows",
        lambda self, rec: recs.append(np.array(rec)) or real(self, rec))
    if not bounded:
        real_rows = dl.rung_rows
        monkeypatch.setattr(
            dl, "rung_rows",
            lambda *a, **kw: real_rows(*a, **dict(kw, bounded=False)))
    telemetry.counters.reset()
    dl.grow_tree_compact.clear_cache()
    _grow_compact(*_toy(31, 9000))
    dl.grow_tree_compact.clear_cache()
    rec, = recs
    assert len(rec) == 14
    left = rec[:, R_LCNT].astype(np.int64)
    parent = left + rec[:, R_RCNT].astype(np.int64)
    want = _walk_rung_rows(parent, left, 2 * left <= parent,
                           dl._size_classes(9000), 512, 128, bounded)
    got = (telemetry.counters.get("rung_rows_run"),
           telemetry.counters.get("rung_rows_needed"))
    assert got == want
    assert want[1] == parent.sum() + np.minimum(left, parent - left).sum()
    # a tile and a chunk or two of slack a split (1.12 at this size);
    # to the rungs' widths the loops ran 2.9 rows a needed row
    assert (1.0 < got[0] / got[1] < 1.2) if bounded \
        else (got[0] / got[1] > 2.5)


def test_rung_row_inflation_of_a_12m_row_tree_of_255_leaves():
    """On `higgs-train`'s ladder (12,000,000 rows, tile 2**16, chunk
    8,192) with a leaf-wise tree of its shape, 254 splits that sum to
    about 101M parent rows and 34M smaller-child rows a tree (PERF.md
    §5, PR 32): the loops to the rungs' widths ran near the records'
    1.53 rows a needed row, the bounded ones run under 1.1."""
    from lightgbm_tpu.models import device_learner as dl
    leaves, pcounts, lefts = [12_000_000], [], []
    shares = (0.22, 0.335, 0.45, 0.31, 0.38)
    for i in range(254):
        p = leaves.pop(int(np.argmax(leaves)))
        small = int(p * shares[i % len(shares)])
        left = small if i % 2 else p - small
        pcounts.append(p)
        lefts.append(left)
        leaves += [left, p - left]
    pcounts, lefts = np.array(pcounts), np.array(lefts)
    ladder = dl._size_classes(12_000_000)
    assert 95e6 < pcounts.sum() < 108e6
    for bounded, lo, hi in ((False, 1.45, 1.62), (True, 1.0, 1.1)):
        run, needed = dl.rung_rows(pcounts, lefts, 2 * lefts <= pcounts,
                                   ladder, 1 << 16, 8192, bounded=bounded)
        assert (run, needed) == _walk_rung_rows(
            pcounts, lefts, 2 * lefts <= pcounts, ladder, 1 << 16, 8192,
            bounded)
        assert lo < run / needed < hi, (bounded, run / needed)


@jax.jit
def _kernel_jit(win, key3):
    from lightgbm_tpu.ops.pallas.partition_kernel import partition_tile
    return partition_tile(win, key3, interpret=True)


@functools.lru_cache(maxsize=4)
def _random_tile(rows, words):
    rng = np.random.RandomState(rows * 64 + words)
    return jnp.asarray(rng.randint(0, 2**32, size=(rows, words),
                                   dtype=np.uint64).astype(np.uint32))


def _tile_keys(pattern, tail, rows):
    """key3 of one tile: the leaf's rows by `pattern` (0 left, 1 right),
    then a key-2 tail of `tail` of the tile (the rows past the leaf's
    count, which both callers make the tile's tail)."""
    rng = np.random.RandomState(rows + int(100 * tail))
    i = np.arange(rows)
    side = {"all_left": np.zeros(rows, bool),
            "all_right": np.ones(rows, bool),
            "alternating": i % 2 == 1,
            "left_1pct": rng.rand(rows) >= 0.01,
            "left_99pct": rng.rand(rows) >= 0.99,
            # runs of 1 to 300 rows a side
            "runs": np.repeat(np.arange(rows) % 2 == 1,
                              rng.randint(1, 301, rows))[:rows]}[pattern]
    live = rows - int(round(rows * tail))
    return jnp.asarray(np.where(i < live, side.astype(np.int32), 2)
                       .astype(np.int32))


_PATTERNS = ["all_left", "all_right", "alternating", "left_1pct",
             "left_99pct", "runs"]
_WIDTHS = (3, 11, 21, 39)     # under a vreg's 8 sublanes, and over it
# Each case reaches a path of its own: every key pattern at every width
# on a ragged tile of three grid steps (5000 rows, padded); a tile under
# one grid step (128 rows) and one of two whole steps (4096) at every
# width; an all-key-2 tile (every pattern is then the same keys), once
# a width under one step and on the ragged tile; and one whole 2**16-row
# tile, since interpret mode copies the resident output at each of the
# kernel's stores (the chip's own run, tools/microbench_partition.py,
# holds 2**16-row tiles at 11, 21 and 39 words to the scan).
_TILE_CASES = [(words, 5000, pattern, tail)
               for words in _WIDTHS
               for pattern in _PATTERNS
               for tail in (0.0, 0.3)] \
    + [(words, rows, pattern, 0.3)
       for words in _WIDTHS
       for rows in (128, 4096)
       for pattern in ("alternating", "runs")] \
    + [(words, rows, "all_left", 1.0)
       for words in _WIDTHS
       for rows in (128, 5000)] \
    + [(11, 1 << 16, "runs", 0.3)]


@pytest.mark.parametrize("words, rows, pattern, tail", _TILE_CASES)
def test_partition_kernel_is_the_scan_bit_for_bit(words, rows, pattern,
                                                  tail):
    """The lane compaction in interpret mode against `_scan_partition`:
    the same rows in the same order, the key-2 tail where it was, and
    the same counts of classes 0 and 1."""
    from lightgbm_tpu.models.device_learner import _scan_partition
    win = _random_tile(rows, words)
    key3 = _tile_keys(pattern, tail, rows)
    got, c0, c1 = _kernel_jit(win, key3)
    want, n0, n1 = jax.jit(_scan_partition)(win, key3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (int(c0), int(c1)) == (int(n0), int(n1))


@pytest.mark.parametrize("pattern", ["mixed_tail", "all_left", "empty_tail",
                                     "ragged"])
@pytest.mark.parametrize("w, t", [(8192, 2048),     # whole tiles
                                  (12000, 4096)])   # and a ragged last one
def test_tiled_partition_with_the_kernel_is_the_scan(monkeypatch, w, t,
                                                     pattern):
    """`_scan_partition_tiled` with the kernel (interpret mode) in its
    tile step: the XLA path's window, bit for bit, full tiles, the
    ragged last step and the tiles past the leaf's rows included."""
    from lightgbm_tpu.models import device_learner as dl
    from lightgbm_tpu.ops.pallas.partition_kernel import partition_tile
    if pattern == "ragged":
        w -= 37
    rng = np.random.RandomState(w + t)
    win = jnp.asarray(rng.randint(0, 2**32, size=(w, 11),
                                  dtype=np.uint64).astype(np.uint32))
    key3 = jnp.asarray(_keys(pattern, w, rng))
    want = np.asarray(_partition_jit(win, key3, t))
    tiles = []

    def kernel(tile, key3):
        tiles.append(tile.shape[0])
        return partition_tile(tile, key3, interpret=True)

    monkeypatch.setattr(dl, "_partition_tile", kernel)
    got = jax.jit(lambda a, k: dl.partition_window(a, k, tile_rows=t))(
        win, key3)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert sorted(set(tiles)) == sorted({t, w % t} - {0})


def test_partition_off_the_tpu_is_the_scan():
    """Lowered for the CPU, the partition holds no Mosaic kernel and is
    the scan's row scatter: the kernel's branch is the TPU's alone."""
    from lightgbm_tpu.models import device_learner as dl
    txt = jax.jit(lambda w, k: dl.partition_window(w, k)).lower(
        jax.ShapeDtypeStruct((4096, 11), jnp.uint32),
        jax.ShapeDtypeStruct((4096,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" not in txt
    assert " scatter(" in txt
