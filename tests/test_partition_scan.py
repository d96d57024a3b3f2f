"""The sort-free partition (cumsum ranks + one row scatter) is the TPU
default on the compact strategy; CPU runs default to argsort+take, so
this is where the scan formulation is held to the same trees."""
import numpy as np
import jax.numpy as jnp


def test_compact_learner_identical_trees_with_scan_partition(monkeypatch):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.device_learner import DeviceTreeLearner

    r = np.random.RandomState(23)
    n, f = 3000, 6
    x = r.randn(n, f).astype(np.float32)
    y = ((x[:, 0] - 0.5 * x[:, 1] + 0.3 * r.randn(n)) > 0).astype(np.float64)
    g = jnp.asarray((r.rand(n) - 0.5).astype(np.float32))
    h = jnp.asarray((0.1 + r.rand(n)).astype(np.float32))

    def grow(mode):
        if mode:
            monkeypatch.setenv("LGBM_TPU_PARTITION", mode)
        else:
            monkeypatch.delenv("LGBM_TPU_PARTITION", raising=False)
        cfg = Config({"objective": "binary", "num_leaves": 15,
                      "max_bin": 63, "min_data_in_leaf": 20,
                      "verbosity": -1})
        ds = Dataset(x, config=cfg, label=y)
        lrn = DeviceTreeLearner(cfg, ds, strategy="compact")
        assert lrn._partition_mode == (mode or "sort")
        tree = lrn.train(g, h)
        return tree.to_string()

    assert grow("scan") == grow(None)
