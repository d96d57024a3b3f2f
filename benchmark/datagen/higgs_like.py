"""Higgs-shaped table: dense float32 columns, a binary label from a fixed
ground-truth function plus noise.

The label model is that of the yardstick from before the chip
(`bench.py::make_higgs_like`, numeric columns only; PR 31 took the file
out, and `chip_smoke.py::make_higgs_like` is what is left of it), with
two changes. The columns are drawn as float32 directly, in parallel
blocks (the legacy `RandomState.randn(...).astype` costs six times as
long). And neither the ground-truth weights nor the rows come from
`--seed`: the weights are `params["truth_seed"]`'s and the table is
`params["table_seed"]`'s, which every seed shuffles (`_blocks.py`), so
the trees, and with them the work of an iteration, are alike from seed
to seed.
"""
import numpy as np

from ._blocks import fill_blocks


def generate(seed, rows, features, params):
    """(x float32 [rows, features], y float32 [rows]) from `seed` and the
    configuration's fixed `params`."""
    truth = np.random.default_rng(int(params["truth_seed"]))
    w = truth.standard_normal(features) * (truth.random(features) > 0.4)
    w = (w * 0.3).astype(np.float32)
    noise = np.float32(params["label_noise"])

    def block(r, n):
        x = r.standard_normal((n, features), dtype=np.float32)
        logit = x @ w
        logit += 0.2 * x[:, 0] * x[:, 1]
        logit -= 0.1 * x[:, 2] ** 2
        logit += r.standard_normal(n, dtype=np.float32) * noise
        return x, logit > 0

    return fill_blocks(seed, params["table_seed"], rows, features, block)
