"""Criteo-shaped table: the 67 dense columns the reference's parallel
experiment trains on (13 integer columns, 26 categorical columns each
replaced by a CTR and a count, two more dense columns), and a click
label from a fixed ground-truth model. The real click logs are not
here: every law and share below is assumed, stated in the
configuration's file, and drawn from `table_seed` alone.

Column j of a group of k gets its parameter at j / (k - 1) of the way
between the two ends of the range the configuration gives, so a test can
read the stated shares back.

  integer  round(exp(mu + sigma z)): heavy-tailed non-negative counts;
           a share of NaN from ~0 to ~0.75 and a further mass at 0
  CTR      sigmoid(a + b z) in [0, 1], skewed to the left, with a spike
           at the prior (categories seen too rarely to have a rate)
  count    round(exp(mu + sigma z)) with a mass at 0
  dense    one standard-normal column, one uniform column

The label's logit is linear in each column's latent normal `z` (where
the value is neither missing nor at its spike), with weights from
`truth_seed`, an offset for a missing or spiked value, and a click rate
that rises with the logarithm of a few integer columns; its intercept
puts the positive rate at a few per cent. The narrowest count columns
carry heavy clickers instead: a value in the far tail (a few rows in
100,000) is nearly always a click, and nothing else in the column tells.
Binning gives such values bins of their own, so a tree splits a leaf of
tens of rows straight off a node of a million: the leaves a float32
`total - other side` in the split scan gets wrong.
"""
import numpy as np

from ._blocks import fill_blocks

GROUPS = ("int", "ctr", "count", "dense")
PIECE = 8192      # rows drawn at a time: two (PIECE, 67) planes stay in cache


def _spread(ends, k):
    return np.linspace(ends[0], ends[1], k, dtype=np.float32)


def layout(features, params):
    """{group: column slice}; the groups' widths are the configuration's
    and must add up to `features`."""
    widths = [int(params[f"{g}_cols"]) for g in GROUPS]
    if sum(widths) != features:
        raise ValueError(f"{widths} columns for {features} features")
    edges = np.concatenate(([0], np.cumsum(widths)))
    return {g: slice(int(edges[i]), int(edges[i + 1]))
            for i, g in enumerate(GROUPS)}


def column_laws(features, params):
    """Per-column parameters, float32 [features] each: location and scale
    of the latent normal, the share set to NaN, the share set to the
    spike (0 for integer and count columns, the prior for CTR ones)."""
    cols = layout(features, params)
    mu, nan_share, spike_share = (
        np.zeros(features, np.float32) for _ in range(3))
    sigma = np.ones(features, np.float32)
    for g in ("int", "ctr", "count"):
        k = cols[g].stop - cols[g].start
        mu[cols[g]] = _spread(params[f"{g}_mu"], k)
        sigma[cols[g]] = _spread(params[f"{g}_sigma"], k)
        spike_share[cols[g]] = _spread(params[f"{g}_spike_share"], k)
    nan_share[cols["int"]] = _spread(
        params["int_nan_share"], cols["int"].stop - cols["int"].start)
    return cols, mu, sigma, nan_share, spike_share


def truth(features, params, cols):
    """The fixed label model: per-column weight on the latent normal,
    offsets for a NaN and for a spiked value, and the integer columns
    whose logarithm raises the click rate. (The tail columns' lift is one
    number, `tail_lift`.)"""
    r = np.random.default_rng(int(params["truth_seed"]))
    w = (r.standard_normal(features) * (r.random(features) > 0.4)
         * float(params["z_weight"]))
    nan_w = r.standard_normal(features) * float(params["nan_weight"])
    spike_w = r.standard_normal(features) * float(params["spike_weight"])
    log_w = np.zeros(features)
    k = cols["int"].stop - cols["int"].start
    log_w[cols["int"]] = np.where(np.arange(k) % 3 == 0,
                                  float(params["int_log_weight"]), 0.0)
    # the tail columns carry no other label: their weight goes
    w[tail_columns(params, cols)] = 0.0
    return tuple(a.astype(np.float32) for a in (w, nan_w, spike_w, log_w))


def tail_columns(params, cols):
    """The first `tail_cols` count columns, the narrowest: few enough
    distinct values that binning gives each of the largest a bin of its
    own. Their extreme values (latent normal above `tail_z`) lift the
    click's logit by `tail_lift`, and they carry no other label."""
    start = cols["count"].start
    return np.arange(start, start + int(params["tail_cols"]))


def generate(seed, rows, features, params):
    """(x float32 [rows, features], y float32 [rows]) from `seed` and the
    configuration's fixed `params`."""
    cols, mu, sigma, nan_share, spike_share = column_laws(features, params)
    w, nan_w, spike_w, log_w = truth(features, params, cols)
    bias = np.float32(params["label_bias"])
    ints, ctrs, counts = cols["int"], cols["ctr"], cols["count"]
    spike_at = np.zeros(features, np.float32)
    spike_at[ctrs] = np.float32(params["ctr_prior"])
    uniform_col = cols["dense"].stop - 1
    below_spike = nan_share + spike_share
    tails = tail_columns(params, cols)
    tail_z = np.float32(params["tail_z"])
    tail_lift = np.float32(params["tail_lift"])

    def piece(r, x, y):
        """Fills one piece of a block: small enough to stay in cache
        through the passes below."""
        n = len(y)
        z = r.standard_normal((n, features), dtype=np.float32)
        u = r.random((n, features), dtype=np.float32)
        is_nan = u[:, ints] < nan_share[ints]
        spiked = u < below_spike
        spiked[:, ints] &= ~is_nan
        in_tail = (z[:, tails] > tail_z) & ~spiked[:, tails]

        np.multiply(z, sigma, out=x)
        x += mu
        for part in (ints, counts):
            np.exp(x[:, part], out=x[:, part])
            np.rint(x[:, part], out=x[:, part])
        np.negative(x[:, ctrs], out=x[:, ctrs])
        np.exp(x[:, ctrs], out=x[:, ctrs])
        x[:, ctrs] += 1.0
        np.reciprocal(x[:, ctrs], out=x[:, ctrs])
        x[:, uniform_col] = u[:, uniform_col]
        np.copyto(x, spike_at, where=spiked)

        # one term a column: the weight on the latent normal, or the
        # offset of a spiked or a missing value
        z *= w
        np.copyto(z, spike_w, where=spiked)
        np.copyto(z[:, ints], nan_w[ints], where=is_nan)
        logit = z.sum(axis=1)
        seen = np.log1p(x[:, ints])
        seen[is_nan] = 0.0
        logit += seen @ log_w[ints]
        logit += tail_lift * np.count_nonzero(in_tail, axis=1)
        logit += bias
        x[:, ints][is_nan] = np.nan
        y[:] = r.random(n, dtype=np.float32) < 1.0 / (1.0 + np.exp(-logit))

    def block(r, n):
        x = np.empty((n, features), np.float32)
        y = np.empty(n, np.float32)
        for a in range(0, n, PIECE):
            piece(r, x[a:a + PIECE], y[a:a + PIECE])
        return x, y

    return fill_blocks(seed, params["table_seed"], rows, features, block)
