"""`LambdarankNDCG` by query-length bucket, held to the plain reference
(`benchmark/reference/lambdarank_reference.py`: NumPy float64, query by
query, ranks by a stable sort; it knows nothing of buckets, slices or
the count-based ranks), on seeded heavy-tailed tables at a test's size:
lengths that span five buckets and more than one slice of a bucket,
queries of one document, queries with all labels alike, tied scores.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.models.device_learner import swapped_attrs
from lightgbm_tpu.objectives import objective as objective_mod
from lightgbm_tpu.telemetry import counters

from benchmark.reference import lambdarank_reference as ref_mod

SLICE_ELEMS = 1 << 14       # several slices a bucket at a test's size


def ranking_table(seed, weighted=False):
    """Lengths 1..300 over the buckets 8, 16, 32, 64, 128 and 512; the
    first queries are the cases a plan can get wrong."""
    r = np.random.default_rng(seed)
    counts = np.concatenate(([1, 300, 1, 5, 2, 129, 64, 8, 9],
                             np.rint(np.exp(r.normal(3.0, 0.8, 150)))
                             .clip(1, 120).astype(np.int64)))
    bounds = np.concatenate(([0], np.cumsum(counts)))
    n = int(bounds[-1])
    y = r.choice(5, n, p=[.52, .32, .13, .02, .01]).astype(np.float64)
    y[bounds[3]:bounds[4]] = 1.0             # five documents, labels alike
    y[bounds[1]:bounds[1] + 3] = [4, 0, 2]   # the long query can pair
    # scores on a grid of 0.1: many ties inside a query, as after a tree
    score = np.round(r.standard_normal(n), 1).astype(np.float32)
    weight = r.uniform(0.5, 2.0, n) if weighted else None
    return counts, bounds, y, score, weight


def make_objective(params, bounds, y, weight):
    class Meta:
        label, query_boundaries = y, bounds
    Meta.weight = weight
    cfg = Config(dict(params, objective="lambdarank", verbosity=-1))
    obj = objective_mod.create_objective("lambdarank", cfg)
    obj.init(Meta, len(y))
    return obj


def reference_gradients(params, counts, y, score, weight):
    fields = {"group": counts}
    if weight is not None:
        fields["weight"] = weight
    ref = ref_mod.Reference(
        np.zeros((len(y), 1), np.float32), y,
        dict(params, objective="lambdarank", learning_rate=0.1), 0,
        fields=fields, with_grid=False)
    return ref.gradients(score.astype(np.float64))


CASES = {
    "defaults": ({}, False),
    "norm_off": ({"lambdamart_norm": False}, False),
    "weights": ({}, True),
    "weights_norm_off": ({"lambdamart_norm": False}, True),
    "max_position_under_most_queries": ({"max_position": 3}, False),
    "max_position_over_every_query": ({"max_position": 1000}, False),
    "sigmoid_2": ({"sigmoid": 2.0}, False),
}


@pytest.fixture()
def small_slices(monkeypatch):
    monkeypatch.setattr(objective_mod, "PAIR_SLICE_ELEMS", SLICE_ELEMS)


@pytest.mark.parametrize("how", ["eager", "fused_trace"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_gradients_match_the_plain_reference(small_slices, case, how):
    params, weighted = CASES[case]
    counts, bounds, y, score, weight = ranking_table(11, weighted)
    obj = make_objective(params, bounds, y, weight)
    lengths = [b[0] for b in obj._buckets]
    assert len(lengths) >= 4 and max(b[2] for b in obj._buckets) > 1
    if how == "eager":
        g, h = obj.get_gradients(jnp.asarray(score))
    else:
        # as the fused step calls it: every buffer a jit argument
        keys = obj.device_buffer_names()

        @jax.jit
        def traced(bufs, s):
            with swapped_attrs(obj, keys, bufs):
                return obj.get_gradients(s)

        g, h = traced(tuple(getattr(obj, k) for k in keys),
                      jnp.asarray(score))
    want_g, want_h = reference_gradients(params, counts, y, score, weight)
    # float32 rounding of sums of up to 300 terms of either sign
    np.testing.assert_allclose(np.asarray(g), want_g, rtol=2e-5,
                               atol=3e-6 * np.abs(want_g).max())
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=2e-5,
                               atol=3e-6 * np.abs(want_h).max())
    # the queries that can give no pair: zero, and counted
    for q in (0, 2, 3):
        assert not np.asarray(g)[bounds[q]:bounds[q + 1]].any()
        assert not np.asarray(h)[bounds[q]:bounds[q + 1]].any()


def test_the_plan_counts_what_it_lays_out(small_slices):
    counts, bounds, y, _, _ = ranking_table(11)
    obj = make_objective({}, bounds, y, None)
    lo = np.minimum.reduceat(y, bounds[:-1])
    hi = np.maximum.reduceat(y, bounds[:-1])
    pairs = (counts > 1) & (hi > lo)
    assert counters.get("rank_queries") == len(counts)
    # the rows of a query that can give no pair point past every
    # bucket's output, at the trailing zero
    filled = sum(L * per * slices for L, per, slices in obj._buckets)
    no_pairs = np.asarray(obj._rank_row_pos)[bounds[:-1]] == filled
    assert (no_pairs == ~pairs).all() and np.sum(no_pairs) >= 3
    assert counters.get("rank_buckets") == len(obj._buckets)
    assert counters.get("rank_pair_positions_real") \
        == np.sum(counts[pairs] ** 2)
    assert counters.get("rank_pair_positions_evaluated") \
        == sum(L * L * per * slices for L, per, slices in obj._buckets)
    for L, per, slices in obj._buckets:
        # a slice's live pair plane stays under the constant (a query
        # longer than its square root is a slice of its own)
        assert per * L * L <= max(SLICE_ELEMS, L * L)
        members = np.sum(pairs & (np.array(
            [objective_mod.bucket_length(c) for c in counts]) == L))
        assert per == min(members, max(1, SLICE_ELEMS // (L * L)))
        assert (slices - 1) * per < members <= slices * per
    # every buffer is a named jit argument, whatever its size
    names = obj.device_buffer_names()
    assert all(getattr(obj, k).ndim >= 1 for k in names)
    assert min(getattr(obj, k).size for k in names) < 256


def test_one_bucket_where_all_queries_are_alike():
    r = np.random.default_rng(3)
    counts = np.full(40, 20)
    bounds = np.arange(41) * 20
    y = r.integers(0, 4, 800).astype(np.float64)
    obj = make_objective({}, bounds, y, None)
    assert [b[0] for b in obj._buckets] == [32]
    score = r.standard_normal(800).astype(np.float32)
    g, h = obj.get_gradients(jnp.asarray(score))
    want_g, want_h = reference_gradients({}, counts, y, score, None)
    np.testing.assert_allclose(np.asarray(g), want_g, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("norm", [True, False])
def test_trained_trees_are_those_of_the_reference_gradients(small_slices,
                                                            norm):
    """`engine.train` with the objective against `engine.train` handed
    the reference's gradients (every query on its own, no bucket: the
    values the one-length layout gave) as a custom objective: the same
    splits, leaf values to float32 rounding. (The model TEXT differs in
    the last digits of a leaf value: the sums run in another order.)"""
    counts, bounds, y, _, _ = ranking_table(5)
    r = np.random.default_rng(5)
    x = r.standard_normal((len(y), 6)).astype(np.float32)
    x[:, 0] += 0.8 * y.astype(np.float32)
    x[:, 1] -= 0.5 * y.astype(np.float32)
    params = {"objective": "lambdarank", "num_leaves": 7, "verbosity": -1,
              "min_data_in_leaf": 5, "lambdamart_norm": norm,
              "learning_rate": 0.1, "metric": "none"}

    def fobj(score, data):
        g, h = reference_gradients({"lambdamart_norm": norm}, counts, y,
                                   np.asarray(score, np.float32), None)
        return g.astype(np.float32), h.astype(np.float32)

    own = lgb.train(params, lgb.Dataset(x, y, group=counts),
                    num_boost_round=3)
    handed = lgb.train(dict(params, objective="none"),
                       lgb.Dataset(x, y, group=counts), num_boost_round=3,
                       fobj=fobj)
    a, b = own.dump_model()["tree_info"], handed.dump_model()["tree_info"]
    assert len(a) == len(b) == 3

    def walk(node, other):
        if "leaf_value" in node:
            assert "leaf_value" in other
            assert node["leaf_value"] == pytest.approx(
                other["leaf_value"], rel=2e-4, abs=1e-7)
            return
        assert node["split_feature"] == other["split_feature"]
        assert node["threshold"] == other["threshold"]
        walk(node["left_child"], other["left_child"])
        walk(node["right_child"], other["right_child"])

    for ta, tb in zip(a, b):
        walk(ta["tree_structure"], tb["tree_structure"])
