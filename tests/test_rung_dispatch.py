"""The compact core's dispatch over its window ladder: each rung a
`while` of one trip or none (`ops.fused.run_once_if`), so that the packed
table is a loop's carry all the way and is updated in place (PERF.md §6,
PR 30). Held here to the dispatch it replaced: the same rungs under
`lax.cond`, which executes the one taken branch as `lax.switch` did, grow
the same trees bit for bit. What the TPU's compiler makes of the form is
`tests/test_tpu_compile_partition.py`'s to say."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.fused import run_once_if

from conftest import make_binary
from test_partition_scan import _toy


@pytest.mark.parametrize("pred,trips", [(True, 1), (False, 0)])
def test_run_once_if_runs_once_or_never(pred, trips):
    @jax.jit
    def probe(p, table, small):
        return run_once_if(
            p, lambda s: {"trips": s["trips"] + 1,
                          "table": jax.lax.dynamic_update_slice(
                              s["table"], s["table"][:2] + 7, (3, 0)),
                          "small": s["small"] * 2.0},
            {"trips": jnp.int32(0), "table": table, "small": small})

    table = jnp.arange(40, dtype=jnp.uint32).reshape(8, 5)
    small = jnp.float32(1.5)
    out = probe(jnp.bool_(pred), table, small)
    assert int(out["trips"]) == trips
    want = np.asarray(table).copy()
    if pred:
        want[3:5] = want[:2] + 7
    np.testing.assert_array_equal(np.asarray(out["table"]), want)
    assert float(out["small"]) == (3.0 if pred else 1.5)


def test_run_once_if_is_a_while_and_no_conditional():
    txt = jax.jit(lambda p, t: run_once_if(p, lambda s: s + 1, t)).lower(
        jnp.bool_(True), jnp.zeros((4, 3), jnp.uint32)).as_text()
    assert "stablehlo.while" in txt
    assert "stablehlo.case" not in txt and "stablehlo.if" not in txt


def _cond_dispatch(pred, body, state):
    """The dispatch the rung loops replaced, one rung at a time."""
    return jax.lax.cond(pred, body, lambda s: s, state)


def _serial_case(params, n, *, trivial, pool_slots=None):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models import device_learner as dl

    def grow(monkeypatch):
        x, y, g, h = _toy(n, n)
        cfg = Config(dict({"objective": "binary", "num_leaves": 15,
                           "max_bin": 63, "min_data_in_leaf": 20,
                           "verbosity": -1}, **params))
        lrn = dl.DeviceTreeLearner(cfg, Dataset(x, config=cfg, label=y),
                                   strategy="compact")
        if pool_slots:
            lrn.pool_slots = pool_slots
        w = jnp.ones(n, jnp.float32)
        if trivial:
            lrn._ones_w = w
        # the dispatch is no static of the jitted growth program
        dl.grow_tree_compact.clear_cache()
        rec, _cat, leaf_id, k, totals = jax.device_get(lrn._run_grow(
            g, h, w, jnp.ones(6, bool), jax.random.PRNGKey(5)))
        dl.grow_tree_compact.clear_cache()
        assert int(k) == 14
        # a split on every rung of the ladder
        parents = rec[:int(k), dl.R_LCNT] + rec[:int(k), dl.R_RCNT]
        ladder = np.asarray(dl._size_classes(n))
        assert set(np.searchsorted(ladder, parents)) == set(
            range(len(ladder)))
        return {"rec": rec, "leaf_id": leaf_id, "k": k, "totals": totals}
    return grow


def _sharded_case(tree_learner):
    from test_parallel import _train

    def grow(monkeypatch):
        x, y = make_binary(n=6000, f=8, seed=4)
        b = _train(x, y, tree_learner, rounds=2)
        assert "Device" in type(b.learner).__name__
        assert b.learner.strategy == "compact"
        out = {"score": np.asarray(jax.device_get(b.score_updater.score))}
        for i, t in enumerate(b.models):
            for key in ("split_feature", "threshold_in_bin", "split_gain",
                        "internal_count", "leaf_value", "leaf_count"):
                out[f"{i}.{key}"] = np.asarray(getattr(t, key))
        return out
    return grow


CASES = {
    # the cells' path: float rows, all-ones weights, four rungs
    "float": _serial_case({}, 20000, trivial=True),
    # per-row weights: the masked full-window histogram stays in the rungs
    "float_scan": _serial_case({}, 5000, trivial=False),
    # `qmax2` among the rung's results
    "quantized_renew": _serial_case(
        {"quantized_grad": True, "grad_bits": 8}, 5000, trivial=True),
    # `hist_other` among them: the parent's histogram evicted
    "pooled": _serial_case({}, 5000, trivial=False, pool_slots=3),
    # collectives round the dispatch, none inside it
    "data_parallel": _sharded_case("data"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_rung_loops_grow_the_conditional_dispatch_s_trees(monkeypatch, case):
    from lightgbm_tpu.models import device_learner as dl
    grow = CASES[case]
    assert dl.run_once_if is run_once_if
    loops = grow(monkeypatch)
    monkeypatch.setattr(dl, "run_once_if", _cond_dispatch)
    conds = grow(monkeypatch)
    assert loops.keys() == conds.keys()
    for key in loops:
        np.testing.assert_array_equal(loops[key], conds[key], err_msg=key)
