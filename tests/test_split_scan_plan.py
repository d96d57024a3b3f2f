"""The split scan reads each feature's own bins (ops/bundle.py
split_scan_plan, models/device_learner.py _tree_helpers): on a bundled
table every width class of features is scanned in a plane as wide as its
bin counts, and a table with no bundles has its column histogram scanned
as it is. Held here against the full expansion, `expand_column_hist` to
one (F, device bins) plane and `per_feature_best` over it, both called
directly: the same per-feature (gain, threshold, default direction) and
the same winner. CPU, small sizes."""
import functools

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.bundling import ColumnSpec, expansion_arrays
from lightgbm_tpu.io.dataset import Dataset
from lightgbm_tpu.models import device_learner as dl
from lightgbm_tpu.ops import bundle as bundle_ops
from lightgbm_tpu.ops import split as split_ops

NEG_INF = split_ops.NEG_INF
DEVICE_BINS = 256
SCAN = dict(num_bins=DEVICE_BINS, l1=0.1, l2=1.0, max_delta_step=0.0,
            min_data_in_leaf=3, min_sum_hessian=1e-3, min_gain_to_split=0.0)
CAT = (10.0, 10.0, 32, 4, 5)      # cat_l2, cat_smooth, max_cat_threshold,
#                                   max_cat_to_onehot, min_data_per_group


class _Mapper:
    def __init__(self, num_bin, default_bin):
        self.num_bin, self.default_bin = num_bin, default_bin


def _table(case, rows=6000, seed=7):
    """Column histograms of a table of three single columns (255, 40 and
    2 bins) and two bundles, one of 24 two-bin levels and one of seven
    members of 3 to 33 bins, built from rows (so every column sums to
    the leaf's totals), and the (F, DEVICE_BINS) expansion map."""
    r = np.random.RandomState(seed)
    mid = case != "default_bins_zero"
    singles = [(255, 0), (40, 0), (2, 0)]
    level = [(2, 0)] * 24
    mixed = [(3, 1), (5, 2), (9, 4), (17, 8), (33, 16), (2, 1), (2, 0)]
    if not mid:
        mixed = [(nb, 0) for nb, _ in mixed]
    feats = singles + level + mixed
    mappers = [_Mapper(nb, d) for nb, d in feats]
    cols, j = [], 0
    for nb, _ in singles:
        cols.append(ColumnSpec([j], [0], nb))
        j += 1
    for group in (level, mixed):
        ids = list(range(j, j + len(group)))
        bases, base = [], 1
        for nb, _ in group:
            bases.append(base)
            base += nb - 1
        cols.append(ColumnSpec(ids, bases, base))
        j += len(group)
    f = len(feats)
    f_col, f_base, f_elide, hist_idx, col_bins = expansion_arrays(
        cols, list(range(f)), mappers, f, max(nb for nb, _ in feats))
    col_device_bins = dl.padded_device_bins(col_bins)
    hi = bundle_ops.respace_hist_idx(hist_idx, len(cols), col_bins,
                                     col_device_bins, DEVICE_BINS)

    quant = case == "dequantized"
    g = (r.randint(-7, 8, rows) if quant else r.randn(rows) + 0.4)
    h = (r.randint(1, 8, rows) if quant else r.uniform(0.1, 0.3, rows))
    gh = np.stack([g, h, np.ones(rows)], axis=1)
    col_hist = np.zeros((len(cols), col_device_bins, 3))
    for ci, col in enumerate(cols):
        if not col.is_bundle:
            nb = col.num_bins
            codes = np.minimum(r.geometric(min(1.0, 4.0 / nb), rows) - 1,
                               nb - 1)
        else:
            # rows at no member's non-default bin hold code 0
            codes = np.where(r.rand(rows) < 0.3, 0,
                             r.randint(1, col.num_bins, rows))
        np.add.at(col_hist[ci], codes, gh)
    totals = gh.sum(axis=0)
    if quant:
        scale3 = jnp.asarray([0.013, 0.021, 1.0], jnp.float32)
        col_hist = jnp.asarray(col_hist, jnp.int32)
        totals = totals * np.asarray(scale3)
    else:
        scale3 = None
        col_hist = jnp.asarray(col_hist, jnp.float32)

    missing = {"missing_zero": 1, "missing_nan": 2}.get(case, 0)
    meta = dict(
        f_numbins=np.array([nb for nb, _ in feats], np.int32),
        f_missing=np.full(f, missing, np.int32),
        f_default=np.array([d for _, d in feats], np.int32),
        f_monotone=(r.randint(-1, 2, f) if case == "monotone"
                    else np.zeros(f)).astype(np.int32),
        f_penalty=(r.uniform(0.5, 1.5, f) if case == "penalty"
                   else np.ones(f)).astype(np.float32),
        f_elide=np.asarray(f_elide, np.int32),
        f_categorical=np.array([int(case == "categorical" and k == 1)
                                for k in range(f)], np.int32))
    return dict(col_hist=col_hist, totals=jnp.asarray(totals, jnp.float32),
                hi=hi, n_cols=len(cols), col_bins=col_device_bins,
                scale3=scale3, meta={k: jnp.asarray(v)
                                     for k, v in meta.items()})


def _full_expansion(t, fmask, mn, mx, has_cat):
    """The scan as one (F, DEVICE_BINS) plane: per-feature (rel, t,
    use_m1) and the winner (the categorical search over the same plane
    where the table has a categorical feature)."""
    m = t["meta"]
    col_hist = t["col_hist"]
    if t["scale3"] is not None:
        col_hist = col_hist.astype(jnp.float32) * t["scale3"]
    sg, sh, cnt = t["totals"]
    hist = bundle_ops.expand_column_hist(
        col_hist, t["totals"], jnp.asarray(t["hi"]), m["f_elide"],
        m["f_default"])
    is_cat = m["f_categorical"] != 0
    rel, thr, use_m1, prefix = split_ops.per_feature_best(
        hist, sg, sh, cnt, m["f_numbins"], m["f_missing"], m["f_default"],
        fmask & ~is_cat, m["f_monotone"], mn, mx, m["f_penalty"], None,
        **SCAN)
    feat = jnp.argmax(rel).astype(jnp.int32)
    res = split_ops.materialize_split(
        feat, rel, thr, use_m1, prefix, mn, mx, l1=SCAN["l1"], l2=SCAN["l2"],
        max_delta_step=SCAN["max_delta_step"])
    if has_cat:
        cat_kw = dict(SCAN, cat_l2=CAT[0], cat_smooth=CAT[1],
                      max_cat_threshold=CAT[2], max_cat_to_onehot=CAT[3],
                      min_data_per_group=CAT[4])
        crel, caux = split_ops.per_feature_best_categorical(
            hist, sg, sh, cnt, m["f_numbins"], m["f_missing"],
            fmask & is_cat, mn, mx, m["f_penalty"], **cat_kw)
        cres = split_ops.materialize_cat_split(
            jnp.argmax(crel).astype(jnp.int32), crel, caux, hist, sg, sh,
            cnt, mn, mx, l1=SCAN["l1"], l2=SCAN["l2"], cat_l2=CAT[0],
            max_delta_step=SCAN["max_delta_step"])
        res, _ = dl._merge_num_cat(res, cres)
        rel = jnp.where(is_cat, crel, rel)
    return rel, thr, use_m1, res


def _planned(t, plan, base_mask, bynode_k, has_cat):
    m = t["meta"]
    scale3 = t["scale3"]
    dequant = None if scale3 is None else (
        lambda hq: hq.astype(jnp.float32) * scale3)
    kw = {k: v for k, v in SCAN.items()}
    return dl._tree_helpers(
        base_mask, m["f_numbins"], m["f_missing"], m["f_default"],
        m["f_monotone"], m["f_penalty"], m["f_elide"], plan,
        max_depth=0, bynode_k=bynode_k, f_categorical=m["f_categorical"],
        cat_statics=CAT if has_cat else None, dequant=dequant, **kw)


def _compare(t, plan, case, exact=False):
    f = t["meta"]["f_numbins"].shape[0]
    has_cat = bool(np.any(np.asarray(t["meta"]["f_categorical"])))
    r = np.random.RandomState(3)
    base = jnp.asarray(r.rand(f) < 0.7 if case == "mask_and_bynode"
                       else np.ones(f, bool))
    bynode_k = 11 if case == "mask_and_bynode" else 0
    # monotone: the leaf's output bounds about its own output, as a
    # constrained parent hands them down, so that some children clamp
    out = -float(t["totals"][0]) / (float(t["totals"][1]) + SCAN["l2"])
    mn, mx = ((jnp.float32(out - 0.3), jnp.float32(out + 0.2))
              if case == "monotone"
              else (jnp.float32(-np.inf), jnp.float32(np.inf)))
    node_mask, scan, *_ = _planned(t, plan, base, bynode_k, has_cat)
    fmask = node_mask(jax.random.PRNGKey(5))
    sg, sh, cnt = t["totals"]
    want_rel, want_t, want_m1, want = jax.jit(
        functools.partial(_full_expansion, has_cat=has_cat))(
        t, fmask, mn, mx)
    got, _ = jax.jit(scan)(t["col_hist"], sg, sh, cnt, mn, mx, fmask)
    # each feature alone: the scan's winner under a one-feature mask
    alone = jax.jit(jax.vmap(lambda m1: scan(
        t["col_hist"], sg, sh, cnt, mn, mx, m1)[0]))(
        jnp.eye(f, dtype=bool) & fmask[None, :])
    want_rel, want_t, want_m1 = map(np.asarray, (want_rel, want_t, want_m1))
    valid = want_rel > NEG_INF / 2
    assert valid.sum() >= 5, "the case leaves too few candidates to judge"
    num = valid & ~np.asarray(t["meta"]["f_categorical"], bool)
    got_gain = np.asarray(alone.gain)
    if exact:
        np.testing.assert_array_equal(got_gain, want_rel)
    else:
        assert np.all(got_gain[~valid] < NEG_INF / 2)
        np.testing.assert_allclose(
            got_gain[valid], want_rel[valid], rtol=1e-6,
            atol=1e-6 * np.abs(want_rel[valid]).max())
    np.testing.assert_array_equal(np.asarray(alone.threshold)[num],
                                  want_t[num])
    np.testing.assert_array_equal(np.asarray(alone.default_left)[num],
                                  want_m1[num])
    for name in ("feature", "threshold", "default_left"):
        assert int(getattr(got, name)) == int(getattr(want, name)), name
    for name in split_ops.SplitResult._fields[4:] + ("gain",):
        a, b = float(getattr(got, name)), float(getattr(want, name))
        if exact:
            assert a == b, name
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9,
                                       err_msg=name)
    return got


CASES = ["default_bins_zero", "default_bins_mid", "missing_zero",
         "missing_nan", "mask_and_bynode", "monotone", "penalty",
         "dequantized", "categorical"]


@pytest.mark.parametrize("case", CASES)
def test_class_planes_match_the_full_expansion(case):
    t = _table(case)
    m = t["meta"]
    plan, elems = bundle_ops.split_scan_plan(
        t["hi"], m["f_numbins"], m["f_categorical"], t["n_cols"],
        t["col_bins"])
    inv, num_classes, cat_class = plan
    widths = sorted(idx.shape[1] for _, idx in num_classes)
    f = m["f_numbins"].shape[0]
    # 2, 3, 5, 9, 17, 33 and 40, 255 bins
    assert widths == [2, 4, 8, 16, 32, 64, 256]
    if case == "categorical":
        # the 40-bin column scanned in a class of its own as well
        assert cat_class[1].shape == (1, DEVICE_BINS)
    else:
        assert cat_class is None
    assert elems < f * DEVICE_BINS / 4
    assert sorted(np.asarray(inv)) == list(range(f))
    _compare(t, plan, case)


def test_dense_plan_is_the_column_histogram_bit_for_bit():
    """No bundle: the column histogram already is the per-feature one,
    and the scan reads it with no gather, to the bit what the full
    expansion through the identity map gives."""
    r = np.random.RandomState(11)
    nbins = np.array([255, 40, 2, 17, 255, 9, 3, 128], np.int32)
    f = nbins.size
    bins = np.arange(DEVICE_BINS)[None, :]
    hi = np.where(bins < nbins[:, None],
                  np.arange(f)[:, None] * DEVICE_BINS + bins,
                  f * DEVICE_BINS).astype(np.int32)
    col_hist = np.zeros((f, DEVICE_BINS, 3), np.float32)
    for j, nb in enumerate(nbins):
        cnt = r.multinomial(5000, np.full(nb, 1.0 / nb))
        col_hist[j, :nb] = np.stack(
            [r.randn(nb) * np.sqrt(cnt) + 0.2 * cnt, 0.2 * cnt, cnt], 1)
    totals = col_hist[0].sum(axis=0)
    zeros = jnp.zeros(f, jnp.int32)
    t = dict(col_hist=jnp.asarray(col_hist), totals=jnp.asarray(totals),
             hi=hi, scale3=None,
             meta=dict(f_numbins=jnp.asarray(nbins), f_missing=zeros,
                       f_default=zeros, f_monotone=zeros,
                       f_penalty=jnp.ones(f, jnp.float32), f_elide=zeros,
                       f_categorical=zeros))
    plan, elems = bundle_ops.split_scan_plan(hi, nbins, zeros, f,
                                             DEVICE_BINS)
    assert plan is None and elems == f * DEVICE_BINS
    _compare(t, None, "dense", exact=True)


def _one_hot_table(rows, seed=0):
    """About 600 columns: one-hot levels of five categorical fields (2,
    3, 40, 150 and 400 levels, the widest ones skewed) and four numeric
    columns, one with NaNs; a label from one level and one number."""
    r = np.random.RandomState(seed)
    blocks = []
    for k in (2, 3, 40, 150, 400):
        p = 0.5 * r.dirichlet(np.full(k, 0.3)) + 0.5 / k
        c = r.choice(k, rows, p=p)
        blocks.append(sp.csr_matrix((np.ones(rows), (np.arange(rows), c)),
                                    shape=(rows, k)))
    num = r.randn(rows, 4).astype(np.float32)
    num[r.rand(rows) < 0.1, 1] = np.nan
    num[:, 2] = np.round(num[:, 2] * 3)
    x = sp.hstack(blocks + [sp.csr_matrix(num)]).tocsr()
    lin = (np.asarray(x[:, 46].todense()).ravel() + np.nan_to_num(num[:, 0])
           + 0.5 * r.randn(rows))
    return x, (lin > 0.6).astype(np.float64)


def _one_class_plan(lrn):
    """The parent's scan as a plan: every feature in one class at the
    device bins, the (F, device bins) expansion map in feature order."""
    inv, classes, cat_class = lrn.scan_plan
    zero = len(lrn.dataset.columns) * lrn.col_device_bins
    ids = np.concatenate([np.asarray(c[0]) for c in classes])
    idx = np.concatenate([np.pad(np.asarray(c[1]),
                                 ((0, 0), (0, lrn.device_bins - c[1].shape[1])),
                                 constant_values=zero) for c in classes])
    order = np.argsort(ids)
    return (jnp.arange(ids.size, dtype=jnp.int32),
            ((jnp.asarray(ids[order], jnp.int32),
              jnp.asarray(idx[order], jnp.int32)),), None)


def test_whole_tree_on_a_one_hot_table_is_the_full_expansions():
    """The compact core on a ~20,000 x 600 one-hot table, bundled: the
    tree grown with the class planes against the tree grown with one
    plane of every feature at the device bins (the full expansion, as a
    plan of one class): the same features, thresholds, directions and
    counts, leaf values to 1e-6."""
    from lightgbm_tpu.telemetry import counters
    x, y = _one_hot_table(20_000)
    cfg = Config({"objective": "binary", "num_leaves": 31, "verbosity": -1,
                  "min_data_in_leaf": 5, "enable_bundle": True,
                  "max_conflict_rate": 0.0, "sparse_threshold": 0.8})
    ds = Dataset(x, config=cfg, label=y)
    lrn = dl.DeviceTreeLearner(cfg, ds, strategy="compact")
    f = ds.num_features
    assert f > 550 and len(ds.columns) < 20
    _, classes, cat_class = lrn.scan_plan
    elems = sum(idx.size for _, idx in classes)
    assert counters.get("split_scan_plane_elems") == elems < f * 16
    grow, kw = lrn._grow_fn_kwargs(trivial_weights=True)
    g = jnp.asarray((0.5 - y).astype(np.float32))
    h = jnp.full(y.size, 0.25, jnp.float32)
    w = jnp.ones(y.size, jnp.float32)
    recs = []
    for plan in (lrn.scan_plan, _one_class_plan(lrn)):
        rec, _, _, k, _ = grow(
            lrn.codes_pack, lrn.codes_row, g, h, w, jnp.ones(f, bool),
            lrn.f_numbins, lrn.f_missing, lrn.f_default, lrn.f_monotone,
            lrn.f_penalty, lrn.f_categorical, lrn.f_col, lrn.f_base,
            lrn.f_elide, plan, jax.random.PRNGKey(0), **kw,
            **lrn._statics())
        recs.append(np.asarray(rec)[:int(k)])
    got, want = recs
    assert len(got) == len(want) == 30
    exact = [dl.R_LEAF, dl.R_FEAT, dl.R_THR, dl.R_DLEFT, dl.R_LCNT,
             dl.R_RCNT]
    np.testing.assert_array_equal(got[:, exact], want[:, exact])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-9)
    # the tree splits on bundled levels, not only on the numbers
    assert np.any(np.asarray(lrn.f_elide)[got[:, dl.R_FEAT].astype(int)])
