"""Device-side EFB helpers: column-histogram expansion and row routing.

Counterpart of the reference's per-group histogram offsets + FixHistogram
(reference: src/io/dataset.cpp:820-960 ConstructHistograms works per
feature-GROUP; FeatureHistogram reads its subfeature's offset slice and
Dataset::FixHistogram (dataset.h:419) reconstructs the elided default bin
by subtraction from the leaf totals). Both steps are static gathers /
elementwise math — ideal XLA work. The split scan expands each width
class of features to a plane of its own width (`split_scan_plan`), as
the reference's FeatureHistogram reads only its feature's own bins.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def respace_hist_idx(hist_idx, n_cols: int, raw_col_bins: int,
                     col_bins: int, num_bins: int) -> np.ndarray:
    """The (F, logical bins) expansion map of `io/bundling.py`, whose
    flat indices step by the raw column bin count, re-spaced for columns
    padded to `col_bins` and widened to `num_bins`; invalid positions
    point at the trailing zero slot n_cols * col_bins."""
    hi = np.asarray(hist_idx).astype(np.int64)
    zero_slot = n_cols * col_bins
    hi = np.where(hi == n_cols * raw_col_bins, zero_slot,
                  hi // raw_col_bins * col_bins + hi % raw_col_bins)
    return np.pad(hi, ((0, 0), (0, num_bins - hi.shape[1])),
                  constant_values=zero_slot).astype(np.int32)


def split_scan_plan(hist_idx, f_numbins, f_categorical, n_cols: int,
                    col_bins: int):
    """The planes the split scan reads, decided once from the expansion
    map `hist_idx` (F, B) into the flattened (n_cols * col_bins + 1, 3)
    column histogram. Returns (plan, plane_elems).

    plan is None where the column histogram already is the per-feature
    one: feature j in column j, bin b at j * col_bins + b, B == col_bins
    (nothing bundles); the scan then reads it as it is. Otherwise plan =
    (inv, num_classes, cat_class). Each of num_classes is (feat, idx):
    the numerical features whose bin count pads to one power of two W
    (at least 2), ascending, and their (F_k, W) columns of `hist_idx`;
    cat_class is the same for the categorical features at all B bins,
    or None; inv (F,) is each feature's position in the classes laid end
    to end, numerical first. plane_elems is the (feature, bin) positions
    one child's scan reads, summed over its planes.
    """
    hist_idx = np.asarray(hist_idx)
    nf, nb = hist_idx.shape
    nbins = np.asarray(f_numbins).astype(np.int64)
    bins = np.arange(nb)[None, :]
    ident = np.where(bins < nbins[:, None],
                     np.arange(nf)[:, None] * nb + bins, nf * nb)
    if n_cols == nf and col_bins == nb and np.array_equal(hist_idx, ident):
        return None, nf * nb
    cat = np.asarray(f_categorical) != 0
    width = np.array([min(nb, 1 << max(1, (int(n) - 1).bit_length()))
                      for n in nbins], np.int64)
    num_classes = []
    for w in np.unique(width[~cat]):
        feat = np.flatnonzero(~cat & (width == w))
        num_classes.append((feat, hist_idx[feat, :w]))
    cat_class = None
    if cat.any():
        feat = np.flatnonzero(cat)
        cat_class = (feat, hist_idx[feat])
    order = np.concatenate([c[0] for c in num_classes]
                           + ([cat_class[0]] if cat_class else []))
    inv = np.empty(nf, np.int32)
    inv[order] = np.arange(nf)
    elems = sum(c[1].size for c in num_classes) + (
        cat_class[1].size if cat_class else 0)

    def dev(c):
        return (jnp.asarray(c[0], jnp.int32), jnp.asarray(c[1], jnp.int32))

    plan = (jnp.asarray(inv), tuple(dev(c) for c in num_classes),
            dev(cat_class) if cat_class else None)
    return plan, int(elems)


def expand_column_hist(col_hist: jax.Array,       # (C, Bc, 3)
                       totals: jax.Array,         # (3,) leaf sums
                       hist_idx: jax.Array,       # (F, B) int32 flat index
                       f_elide: jax.Array,        # (F,) int32 0/1
                       f_default: jax.Array,      # (F,) int32 default bin
                       ) -> jax.Array:
    """Column histograms -> per-feature histograms (F, B, 3).

    hist_idx points into the flattened (C*Bc, 3) array with one trailing
    zero slot for invalid positions; elided default bins are reconstructed
    as totals - sum(other bins), the FixHistogram identity.
    """
    c, bc, _ = col_hist.shape
    flat = jnp.concatenate(
        [col_hist.reshape(c * bc, 3), jnp.zeros((1, 3), col_hist.dtype)])
    fh = flat[hist_idx]                               # (F, B, 3)
    rem = totals[None, :] - fh.sum(axis=1)            # (F, 3)
    b = fh.shape[1]
    donehot = (jnp.arange(b, dtype=jnp.int32)[None, :]
               == f_default[:, None]).astype(fh.dtype)       # (F, B)
    fix = donehot[:, :, None] * rem[:, None, :] * f_elide[:, None, None]
    return fh + fix


def logical_bins_for_feature(col_codes: jax.Array, base, default_bin,
                             num_bins_f, elide) -> jax.Array:
    """Map a column's raw codes to one subfeature's logical bins.

    For single-feature columns (elide == 0) codes ARE the bins. For bundle
    members, codes in [base, base + nbin - 2] unmap to the feature's
    non-default bins; anything else means 'this feature at its default'.
    """
    j = col_codes - base
    inside = (j >= 0) & (j < num_bins_f - 1)
    logical = j + (j >= default_bin).astype(col_codes.dtype)
    bundled = jnp.where(inside, logical, default_bin)
    return jnp.where(elide > 0, bundled, col_codes)
