"""Allstate-shaped table: insurance claim records, about a dozen dense
numeric columns and nineteen categorical fields one-hot encoded into
4,216 sparse 0/1 columns, and a rare binary claim label. The Kaggle
records are not here: every field, cardinality, law and share below is
assumed, stated in the configuration's file, and drawn from `table_seed`
and `truth_seed` alone.

A row is a vehicle (`Blind_Submodel`, drawn with heavy-tailed weights and
a floor) on a policy. The vehicle fields are the vehicle's own: its make,
model, the twelve `Cat` fields and `OrdCat` (properties of the model) and
its model year. So two levels of vehicle fields meet in a row only
through a vehicle that has both: a plan that saw every vehicle saw every
pair that can meet, and the bundles it finds exclusive stay exclusive
over the whole table (`max_conflict_rate=0` holds for every row). The
policy fields (`Calendar_Year`, `NVCat`) are drawn apart from the vehicle,
each level over a fifth of the rows, so no level of theirs is sparse
enough to bundle. The last level of every `Cat` field and of `OrdCat` is
the missing value, a level of its own.

`x` is a `scipy.sparse.csr_matrix`, float32: the numeric columns first,
then each field's one-hot block in the order of the configuration's
`fields`, one stored 1.0 a field a row. `--seed` shuffles whole blocks
and the rows in them (`_blocks.py`'s scheme), so every seed holds the
same rows, and with them the same work.
"""
from concurrent.futures import ThreadPoolExecutor
import math
import os

import numpy as np
import scipy.sparse as sp

from ._blocks import BLOCK

VEHICLE = "Blind_Submodel"


def _weights(r, k, sigma):
    w = np.exp(sigma * r.standard_normal(k))
    return w / w.sum()


def _cover_then_draw(r, n_items, n_levels, p):
    """A level for each of `n_items`: the first `n_levels` items take one
    level each (every level is held), the rest draw from `p`."""
    out = r.choice(n_levels, n_items, p=p)
    out[:n_levels] = r.permutation(n_levels)
    return out


def layout(features, params):
    """(numeric column count, [(field, first column, levels)])."""
    numeric = len(params["numeric"])
    fields, at = [], numeric
    for name, levels in params["fields"]:
        fields.append((name, at, int(levels)))
        at += int(levels)
    if at != features:
        raise ValueError(f"{at} columns for {features} features")
    return numeric, fields


def vehicles(params):
    """The fixed catalogue: each vehicle's weight and its level in every
    vehicle field, from `table_seed`."""
    r = np.random.default_rng([int(params["table_seed"]), 1])
    levels = dict(params["fields"])
    k = levels[VEHICLE]
    floor = float(params["vehicle_floor"])
    if k * floor >= 1.0:
        raise ValueError(f"a floor of {floor} for {k} vehicles")
    weight = floor + (1.0 - k * floor) * _weights(
        r, k, float(params["vehicle_sigma"]))
    n_model, n_make = levels["Blind_Model"], levels["Blind_Make"]
    model = _cover_then_draw(r, k, n_model, _weights(r, n_model, 1.0))
    make_of_model = _cover_then_draw(r, n_model, n_make,
                                     _weights(r, n_make, 1.0))
    of = {VEHICLE: np.arange(k), "Blind_Model": model,
          "Blind_Make": make_of_model[model]}
    for name, n in params["fields"]:
        if name.startswith("Cat") or name == "OrdCat":
            of[name] = _cover_then_draw(
                r, n_model, n, _weights(r, n, float(params["level_sigma"])))[
                    model]
    years = levels["Model_Year"]
    bell = np.exp(-0.5 * ((np.arange(years) - 0.7 * years) / 6.0) ** 2)
    of["Model_Year"] = _cover_then_draw(r, k, years, bell / bell.sum())
    return weight, of


def truth(features, params, weight, of):
    """The fixed label model: a weight on each column, so that a row's
    signal is the sum of its stored values' weights; and the threshold
    on signal + noise that gives the positive rate."""
    r = np.random.default_rng(int(params["truth_seed"]))
    numeric, fields = layout(features, params)
    w = np.zeros(features)
    w[:numeric] = r.standard_normal(numeric) * float(params["numeric_weight"])
    for name, first, n in fields:
        w[first:first + n] = r.standard_normal(n) * float(
            params["field_weight"].get(name, params["field_weight"]["*"]))
    # the signal's mean and spread over the table, exactly: a vehicle
    # part, a part of each policy field, the numeric columns
    vehicle = np.zeros(len(weight))
    for name, first, n in fields:
        if name in of:
            vehicle += w[first + of[name]]
    mean = float(weight @ vehicle)
    var = float(weight @ (vehicle - mean) ** 2)
    for name, first, n in fields:
        if name not in of:
            p = np.asarray(params["policy_shares"][name])
            part = w[first:first + n]
            mean += float(p @ part)
            var += float(p @ (part - p @ part) ** 2)
    # Var columns standard normal; NVVar columns c, or c + |z| for a
    # share 1 - s of the rows
    nv = len(params["nv_constant"])
    s = float(params["nv_constant_share"])
    col_mean = np.zeros(numeric)
    col_var = np.ones(numeric)
    col_mean[numeric - nv:] = (np.asarray(params["nv_constant"])
                               + (1 - s) * math.sqrt(2 / math.pi))
    col_var[numeric - nv:] = (1 - s) - (1 - s) ** 2 * 2 / math.pi
    mean += float(w[:numeric] @ col_mean)
    var += float(w[:numeric] ** 2 @ col_var)
    scale = float(params["signal_sd"]) / math.sqrt(var)
    spread = math.sqrt(1.0 + float(params["signal_sd"]) ** 2)
    from statistics import NormalDist
    cut = spread * NormalDist().inv_cdf(1.0 - float(params["positive_rate"]))
    return w * scale, mean * scale, cut


def _program_bins_from_nonzeros():
    """Whether the program bins and bundles a sparse table from its
    nonzeros, read from what it does: a small table of two one-hot
    fields of 32 levels has to hold fewer code bytes a row than it has
    columns (its gauge `host_code_bytes_per_row`). One that fills a
    (rows, columns) byte plane first would need 55.7 GB at the cell's
    rows and be killed for it, after minutes; the cell refuses it at
    once instead."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.telemetry import counters
    r = np.random.default_rng(0)
    n, levels = 2_000, 32
    cols = np.column_stack([r.integers(0, levels, n),
                            levels + r.integers(0, levels, n)]).ravel()
    x = sp.csr_matrix((np.ones(cols.size, np.float32), cols,
                       np.arange(0, cols.size + 1, 2)),
                      shape=(n, 2 * levels))
    lgb.Dataset(x, r.integers(0, 2, n).astype(np.float32),
                params={"verbosity": -1}).construct()
    return counters.get("host_code_bytes_per_row", 2 * levels) \
        < 2 * levels


def generate(seed, rows, features, params):
    """(x csr_matrix float32 [rows, features], y float32 [rows]) from
    `seed` and the configuration's fixed `params`."""
    if not _program_bins_from_nonzeros():
        raise SystemExit("allstate_like: this program builds an (N, F) "
                         "code plane from a sparse table; the cell cannot "
                         "run on it")
    numeric, fields = layout(features, params)
    weight, of = vehicles(params)
    w, mean, cut = truth(features, params, weight, of)
    w32 = w.astype(np.float32)
    cdf = np.cumsum(weight)
    cdf /= cdf[-1]
    nv_const = np.asarray(params["nv_constant"], np.float32)
    nv_share = float(params["nv_constant_share"])
    n_nv = len(nv_const)
    width = numeric + len(fields)
    policy = [(i, first, np.cumsum(params["policy_shares"][name]))
              for i, (name, first, n) in enumerate(fields) if name not in of]

    def make_block(r, n):
        cols = np.empty((n, width), np.int32)
        vals = np.ones((n, width), np.float32)
        cols[:, :numeric] = np.arange(numeric, dtype=np.int32)
        z = r.standard_normal((n, numeric), dtype=np.float32)
        # NVVar columns: most rows at one value, the rest spread round it
        at_const = r.random((n, n_nv)) < nv_share
        z[:, numeric - n_nv:] = np.where(
            at_const, nv_const, nv_const + np.abs(z[:, numeric - n_nv:]))
        vals[:, :numeric] = z
        v = np.minimum(np.searchsorted(cdf, r.random(n)), len(cdf) - 1)
        for i, (name, first, _) in enumerate(fields):
            if name in of:
                cols[:, numeric + i] = first + of[name][v]
        for i, first, share in policy:
            u = r.random(n) * share[-1]
            cols[:, numeric + i] = first + np.searchsorted(share, u)
        signal = (z @ w32[:numeric]
                  + w32[cols[:, numeric:]].sum(axis=1) - np.float32(mean))
        noise = r.standard_normal(n, dtype=np.float32)
        return cols, vals, (signal + noise > cut).astype(np.float32)

    cols = np.empty((rows, width), np.int32)
    vals = np.empty((rows, width), np.float32)
    y = np.empty(rows, np.float32)
    sizes = np.full(-(-rows // BLOCK), BLOCK)
    sizes[-1] = rows - BLOCK * (len(sizes) - 1)
    children = np.random.SeedSequence(int(params["table_seed"])).spawn(
        len(sizes))
    order = np.random.default_rng(int(seed)).permutation(len(sizes))
    starts = np.concatenate(([0], np.cumsum(sizes[order])[:-1]))

    def one(job):
        start, block = job
        bc, bv, by = make_block(np.random.default_rng(children[block]),
                                int(sizes[block]))
        at = np.random.default_rng([int(seed), int(block)]).permutation(
            len(by))
        cols[start:start + len(by)] = bc[at]
        vals[start:start + len(by)] = bv[at]
        y[start:start + len(by)] = by[at]

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(one, zip(starts, order)))
    indptr = np.arange(0, rows * width + 1, width,
                       dtype=np.int64 if rows * width >= 2**31 else np.int32)
    x = sp.csr_matrix((vals.reshape(-1), cols.reshape(-1), indptr),
                      shape=(rows, features))
    return x, y
