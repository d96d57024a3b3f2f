"""The sort-free partition (cumsum ranks + one row scatter, tile by tile
over SCATTER_TILE_ROWS) is the compact core's one partition on every
platform: held here to a stable argsort's order, tiled and untiled, and
to the trees of a core that moves no rows."""
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
