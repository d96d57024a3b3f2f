"""The fused training program must stay shape-keyed.

Round-4 finding: closed-over device arrays lower as HLO constants, so a
fused step that captures the code buffers or the objective's label
vectors bakes the DATASET into the program (120.5 MB of StableHLO at
1M x 28 before the fix, 0.24 MB after). This test pins the property by
lowering the real fused step at a moderate shape and bounding the
module size — any regression that re-embeds an (N,)-sized buffer blows
the bound by an order of magnitude.
"""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest
import scipy.sparse as sp

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Dataset
from lightgbm_tpu.models.device_learner import (DeviceTreeLearner,
                                                objective_buffer_names)
from lightgbm_tpu.objectives.objective import create_objective


def _lowered_size(objective_name, n=100_000, f=10, **meta):
    rng = np.random.RandomState(0)
    x = rng.randn(n, f).astype(np.float32)
    y = (meta.pop("label_fn", lambda v: (v[:, 0] > 0).astype(np.float64)))(x)
    cfg = Config({"objective": objective_name, "num_leaves": 31,
                  "verbosity": -1})
    ds = Dataset(x, config=cfg, label=y)
    group = meta.pop("group", None)
    if group is not None:
        ds.metadata.set_group(group)
    lrn = DeviceTreeLearner(cfg, ds, strategy="chunk")
    obj = create_objective(objective_name, cfg)
    obj.init(ds.metadata, n)
    step = lrn.make_fused_step(obj)
    keys = step.obj_keys
    bufs = tuple(getattr(obj, k) for k in keys)
    low = step.impl.lower(lrn.codes_pack, lrn.codes_row, bufs,
                       jnp.zeros((n,), jnp.float32),
                       jnp.ones((f,), bool), jax.random.PRNGKey(0),
                       jax.random.PRNGKey(1), jnp.float32(0.1))
    return len(low.as_text()), keys


def test_binary_fused_program_has_no_dataset_constants():
    size, keys = _lowered_size("binary")
    # n=100k: one embedded f32 row vector alone would add ~0.8 MB of
    # hex text on top of the ~0.2 MB clean program, so the bound must
    # sit BELOW clean + one embedded vector
    assert size < 600_000, f"fused program grew to {size/1e6:.2f} MB"
    assert "_label_dev" in keys and "_signed_label" in keys


def test_lambdarank_fused_program_has_no_dataset_constants():
    n = 50_000
    size, keys = _lowered_size(
        "lambdarank", n=n,
        label_fn=lambda v: np.clip(v[:, 0].round() + 1, 0, 3),
        group=np.full(n // 50, 50))
    # n=50k: one embedded f32 vector adds ~0.4 MB over the ~0.25 MB
    # clean program
    assert size < 500_000, f"fused program grew to {size/1e6:.2f} MB"
    # one bucket (every query 50 documents -> 64): its three buffers
    # and the per-row vectors are all jit arguments
    assert {"_rank_start_64", "_rank_count_64", "_rank_inv_max_dcg_64",
            "_rank_row_pos", "_label_dev", "_gain_dev"} <= set(keys)


def _ranking_step_text(seed):
    """StableHLO of the fused step on 3,004 queries, one of them long
    (700 documents: a bucket of one query, every buffer of it a handful
    of elements), with labels drawn from `seed`."""
    counts = np.concatenate(([700], np.full(3000, 16), [1, 1, 5]))
    n = int(counts.sum())
    rng = np.random.RandomState(seed)
    x = np.random.RandomState(0).randn(n, 6).astype(np.float32)
    y = rng.randint(0, 4, n).astype(np.float64)
    cfg = Config({"objective": "lambdarank", "num_leaves": 15,
                  "verbosity": -1})
    ds = Dataset(x, config=cfg, label=y)
    ds.metadata.set_group(counts)
    lrn = DeviceTreeLearner(cfg, ds, strategy="chunk")
    obj = create_objective("lambdarank", cfg)
    obj.init(ds.metadata, n)
    step = lrn.make_fused_step(obj)
    args = (jnp.zeros((n,), jnp.float32), jnp.ones((6,), bool),
            jax.random.PRNGKey(0), jax.random.PRNGKey(1), jnp.float32(0.1))
    return step.lower(*args).as_text(), step, args, obj


def test_lambdarank_buckets_are_parameters_and_tables_share_a_program():
    """Every bucket's buffers are jit arguments whatever their size, so
    the module holds no per-dataset constant: two tables with the same
    query lengths and other labels lower to the same text (one compile
    cache key), and the text stays small."""
    text_a, step, args, obj = _ranking_step_text(1)
    text_b, _, _, _ = _ranking_step_text(2)
    assert text_a == text_b
    assert len(text_a) < 900_000, f"{len(text_a) / 1e6:.2f} MB"
    assert [b[0] for b in obj._buckets] == [8, 16, 1024]
    keys = set(step.obj_keys)
    for L in (8, 16, 1024):
        assert {f"_rank_start_{L}", f"_rank_count_{L}",
                f"_rank_inv_max_dcg_{L}"} <= keys
    assert obj._rank_start_1024.size == 1       # far under 256 elements
    # and the compiled step stores no pair plane over the live bound
    from lightgbm_tpu.objectives.objective import PAIR_SLICE_ELEMS
    from lightgbm_tpu.telemetry import counters
    plane = step.rank_pair_plane_elems(*args)
    assert 0 < plane <= PAIR_SLICE_ELEMS
    assert counters.get("rank_pair_plane_elems") == plane
    assert 3000 * 16 * 16 <= PAIR_SLICE_ELEMS     # one slice holds them


def test_objective_buffer_names_cover_per_row_arrays():
    rng = np.random.RandomState(1)
    n = 2000
    x = rng.randn(n, 5).astype(np.float32)
    y = np.abs(x[:, 0])
    cfg = Config({"objective": "regression", "verbosity": -1})
    ds = Dataset(x, config=cfg, label=y,
                 weight=np.linspace(0.5, 1.5, n))
    obj = create_objective("regression", cfg)
    obj.init(ds.metadata, n)
    names = objective_buffer_names(obj)
    assert "_label_dev" in names and "_weight_dev" in names


def _scan_arrays(txt):
    """(op, result element count) of every instruction of a compiled
    module's text whose `op_name` runs through `lgbm.split_scan`."""
    out = []
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\w+\[([\d,]*)\]"
                         r"\S*\s+([\w\-]+)\(.*op_name=\"([^\"]*)\"",
                         txt, re.M):
        if "lgbm.split_scan" in m.group(3):
            dims = [int(d) for d in m.group(1).split(",") if d]
            out.append((m.group(2), int(np.prod(dims)) if dims else 1))
    return out


def _grow_text(lrn, n):
    grow, kw = lrn._grow_fn_kwargs(trivial_weights=True)
    per_row = jnp.zeros((n,), jnp.float32)
    f = lrn.num_features
    return grow.lower(
        lrn.codes_pack, lrn.codes_row, per_row, per_row, per_row,
        jnp.ones((f,), bool), lrn.f_numbins, lrn.f_missing, lrn.f_default,
        lrn.f_monotone, lrn.f_penalty, lrn.f_categorical, lrn.f_col,
        lrn.f_base, lrn.f_elide, lrn.scan_plan, jax.random.PRNGKey(0),
        **kw, **lrn._statics()).compile().as_text()


@pytest.mark.parametrize("table", ["bundled", "dense"])
def test_split_scan_reads_no_plane_of_every_feature_at_the_device_bins(
        table):
    """The compiled tree program's split scan: on a bundled table no
    array of F x device bins positions (each width class of features
    has a plane as wide as its bin counts), on a table with no bundles
    no gather of a plane (the column histogram is scanned as it is);
    `split_scan_plane_elems` the positions one child's scan reads."""
    from lightgbm_tpu.telemetry import counters
    rng = np.random.RandomState(2)
    n = 3000
    if table == "bundled":
        levels = rng.randint(0, 100, n)
        x = sp.hstack([sp.csr_matrix((np.ones(n), (np.arange(n), levels)),
                                     shape=(n, 100)),
                       sp.csr_matrix(rng.randn(n, 3))]).tocsr()
    else:
        x = rng.randn(n, 40).astype(np.float32)
    cfg = Config({"objective": "binary", "num_leaves": 7, "verbosity": -1,
                  "enable_bundle": True, "max_conflict_rate": 0.0})
    ds = Dataset(x, config=cfg, label=(rng.rand(n) < 0.3).astype(float))
    lrn = DeviceTreeLearner(cfg, ds, strategy="compact")
    f, b = lrn.num_features, lrn.device_bins
    arrays = _scan_arrays(_grow_text(lrn, n))
    assert arrays, "no instruction under lgbm.split_scan"
    elems = counters.get("split_scan_plane_elems")
    if table == "bundled":
        # the levels (some with too few rows to split are dropped) in a
        # class of two bins, the three numbers at the device bins
        assert f > 90 and lrn.scan_plan is not None
        assert elems == (f - 3) * 2 + 3 * b
        assert max(size for _, size in arrays) < f * b
    else:
        assert lrn.scan_plan is None and elems == f * b
        gathers = [size for op, size in arrays if op == "gather"]
        assert not gathers or max(gathers) < f, gathers
