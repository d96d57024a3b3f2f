"""MSLR-WEB30K-shaped table: judged query-document rows in whole queries,
137 columns of the kinds the data set's description gives, and a
relevance label 0-4 from a fixed latent model. The real logs are not
here: every law and share below is assumed, stated in the
configuration's file, and drawn from `table_seed` alone.

Queries. `queries` lengths from one heavy-tailed law (log-normal, with a
small share of very short queries), scaled and rounded so that they sum
to `table_rows`, the longest set to `longest` and the shortest to 1
(`query_lengths`). A call for fewer rows takes the leading whole queries
of that list, the last one cut to fit. `--seed` decides the order of the
queries in the table, never the rows of a query: the multiset of
lengths, and with it the program's bucket shapes and work, is every
seed's.

Columns: 25 kinds over each of 5 text fields (body, anchor, title, url,
whole document) and 12 page-level columns. A document has one
standard-normal signal `s`; each field sees it through its own noise
(a_f = rho_f s + sqrt(1 - rho_f^2) e_f) and is empty for a share of the
documents, when all its columns are 0.

  count    round(exp(mu + sd a)): term counts, few distinct values
  ratio    sigmoid(mu + sd a) in [0, 1]: normalised frequencies
  length   round(exp(mu + sd e)): stream lengths, not relevance
  idf      exp(mu + sd q): one value a QUERY, the same for its documents
  tfidf    exp(mu + sd a) x the field's idf
  bool     a > cut: boolean-model match
  score    max(0, mu + sd a): BM25-like
  logprob  -exp(mu - sd a): language-model scores, negative

The label: latent = sqrt(wq) q + sqrt(ws) s + sqrt(1 - wq - ws) e with
q one standard normal a query, cut at fixed normal quantiles to the
shares of `label_shares` (0 most common). No NaN anywhere.
"""
from concurrent.futures import ThreadPoolExecutor
import os
from statistics import NormalDist

import numpy as np

FIELDS = ("body", "anchor", "title", "url", "whole")
# (kind, law, mu, sd): the 25 columns of one field
KINDS = (
    ("covered_terms", "count", 0.9, 0.5),
    ("covered_ratio", "ratio", 0.5, 1.2),
    ("stream_length", "length", 4.0, 1.3),
    ("idf", "idf", 1.5, 0.6),
    ("tf_sum", "count", 1.6, 0.9), ("tf_min", "count", -0.4, 0.8),
    ("tf_max", "count", 1.2, 0.9), ("tf_mean", "count", 0.6, 0.8),
    ("tf_var", "count", 0.8, 1.1),
    ("ntf_sum", "ratio", -2.0, 1.0), ("ntf_min", "ratio", -4.0, 1.0),
    ("ntf_max", "ratio", -2.5, 1.0), ("ntf_mean", "ratio", -3.0, 1.0),
    ("ntf_var", "ratio", -4.5, 1.2),
    ("tfidf_sum", "tfidf", 1.6, 0.9), ("tfidf_min", "tfidf", -0.4, 0.8),
    ("tfidf_max", "tfidf", 1.2, 0.9), ("tfidf_mean", "tfidf", 0.6, 0.8),
    ("tfidf_var", "tfidf", 0.8, 1.1),
    ("boolean_model", "bool", 0.3, 1.0),
    ("vector_space", "ratio", -1.0, 1.0),
    ("bm25", "score", 6.0, 5.0),
    ("lmir_abs", "logprob", 2.0, 0.5), ("lmir_dir", "logprob", 2.2, 0.5),
    ("lmir_jm", "logprob", 2.1, 0.5),
)
# (name, law, mu, sd, weight on the document's signal): page-level columns
PAGE = (
    ("url_slashes", "count", 1.1, 0.4, 0.0),
    ("url_length", "count", 3.6, 0.5, 0.0),
    ("inlinks", "count", 2.0, 2.2, 0.5),
    ("outlinks", "count", 3.0, 1.2, 0.0),
    ("pagerank", "count", 4.0, 1.5, 0.4),
    ("siterank", "count", 5.0, 1.8, 0.3),
    ("quality", "count", 4.5, 0.6, 0.3),
    ("quality2", "count", 3.5, 0.8, 0.2),
    ("query_url_clicks", "count", -1.5, 2.0, 0.7),
    ("url_clicks", "count", 1.0, 2.5, 0.5),
    ("dwell_time", "count", 2.0, 2.0, 0.5),
    ("is_homepage", "bool", 1.3, 1.0, 0.2),
)
IDF_MU, IDF_SD = next((mu, sd) for _, law, mu, sd in KINDS if law == "idf")
QUERIES_PER_BLOCK = 256
PIECE = 8192


def query_lengths(params):
    """int64 [queries]: the table's query lengths, in the table's own
    order, from `table_seed` alone."""
    q, total = int(params["queries"]), int(params["table_rows"])
    longest = int(params["longest"])
    r = np.random.default_rng([int(params["table_seed"]), 0x51E5])
    raw = np.exp(float(params["length_sigma"]) * r.standard_normal(q))
    short = r.random(q) < float(params["short_share"])
    raw[short] = r.random(q)[short] * float(params["short_scale"])

    def lengths(k):
        return np.clip(np.rint(raw * k), 1, longest).astype(np.int64)

    lo, hi = 0.0, float(total)
    for _ in range(64):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if lengths(mid).sum() <= total else (lo, mid)
    out = lengths(lo)
    out[np.argmax(raw)] = longest
    out[np.argmin(raw)] = 1
    # what rounding and the two ends left over, one row a query a round
    short_by = total - int(out.sum())
    while short_by:
        room = np.nonzero((out >= 8) & (out < longest))[0][:abs(short_by)]
        if not len(room):
            raise ValueError(
                f"{q} queries of 1..{longest} cannot hold {total}")
        out[room] += np.sign(short_by)
        short_by -= np.sign(short_by) * len(room)
    return out


def _column_laws(features):
    """One row a column: (law, mu, sd, field or -1, weight on s)."""
    laws = [(law, mu, sd, f, 0.0) for f in range(len(FIELDS))
            for _, law, mu, sd in KINDS]
    laws += [(law, mu, sd, -1, w) for _, law, mu, sd, w in PAGE]
    if len(laws) != features:
        raise ValueError(f"{len(laws)} columns for {features} features")
    return laws


def _make_block(r, counts, laws, params):
    """(x, y) of the queries of one block, in the table's order."""
    n, nq = int(counts.sum()), len(counts)
    rho = np.asarray(params["field_rho"], np.float32)
    empty_share = np.asarray(params["field_empty_share"], np.float32)
    wq, ws = float(params["label_query_weight"]), \
        float(params["label_signal_weight"])
    shares = np.cumsum(params["label_shares"])[:-1]
    cuts = np.array([NormalDist().inv_cdf(float(c)) for c in shares],
                    np.float32)
    qid = np.repeat(np.arange(nq), counts)
    q_level = r.standard_normal(nq, dtype=np.float32)
    q_idf = r.standard_normal((nq, len(FIELDS)), dtype=np.float32)
    # a query's idf leans on its level, so query-level columns tell
    q_idf = 0.6 * q_level[:, None] + 0.8 * q_idf
    x = np.empty((n, len(laws)), np.float32)
    y = np.empty(n, np.float32)
    for a in range(0, n, PIECE):
        b = min(a + PIECE, n)
        m = b - a
        s = r.standard_normal(m, dtype=np.float32)
        e = r.standard_normal((m, len(FIELDS)), dtype=np.float32)
        field = rho * s[:, None] + np.sqrt(1 - rho * rho) * e
        empty = r.random((m, len(FIELDS)), dtype=np.float32) < empty_share
        z = r.standard_normal((m, len(laws)), dtype=np.float32)
        idf = np.exp(IDF_MU + IDF_SD * q_idf[qid[a:b]])
        out = x[a:b]
        for j, (law, mu, sd, f, w) in enumerate(laws):
            base = field[:, f] if f >= 0 else w * s
            v = mu + sd * (0.7 * base + 0.7 * z[:, j])
            if law == "count":
                col = np.rint(np.exp(v))
            elif law == "ratio":
                col = 1.0 / (1.0 + np.exp(-v))
            elif law == "length":
                col = np.rint(np.exp(mu + sd * z[:, j])) + 1.0
            elif law == "idf":
                col = idf[:, f]
            elif law == "tfidf":
                col = np.exp(v) * idf[:, f]
            elif law == "bool":
                col = (base + 0.5 * z[:, j] > mu).astype(np.float32)
            elif law == "score":
                col = np.maximum(v, 0.0)
            else:                               # logprob
                col = -np.exp(mu - sd * 0.7 * base + 0.3 * z[:, j])
            if f >= 0 and law != "idf":
                col = np.where(empty[:, f], 0.0, col)
            out[:, j] = col
        latent = (np.sqrt(wq) * q_level[qid[a:b]] + np.sqrt(ws) * s
                  + np.sqrt(1.0 - wq - ws)
                  * r.standard_normal(m, dtype=np.float32))
        y[a:b] = np.searchsorted(cuts, latent)
    return x, y


def generate(seed, rows, features, params):
    """(x float32 [rows, features], y float32 [rows] in 0..4,
    {"group": int64 query sizes that sum to rows})."""
    laws = _column_laws(features)
    lengths = query_lengths(params)
    ends = np.cumsum(lengths)
    if rows > ends[-1]:
        raise ValueError(f"the table holds {ends[-1]} rows, not {rows}")
    held = int(np.searchsorted(ends, rows)) + 1       # whole queries + a cut
    sizes = lengths[:held].copy()
    sizes[-1] -= ends[held - 1] - rows
    order = np.random.default_rng(int(seed)).permutation(held)
    dest = np.empty(held, np.int64)
    dest[order] = np.concatenate(([0], np.cumsum(sizes[order])[:-1]))
    x = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    n_blocks = -(-len(lengths) // QUERIES_PER_BLOCK)
    children = np.random.SeedSequence(int(params["table_seed"])) \
        .spawn(n_blocks)

    def one(block):
        q0 = block * QUERIES_PER_BLOCK
        q1 = min(q0 + QUERIES_PER_BLOCK, held)
        # a block is drawn whole, so a query's rows depend on
        # `table_seed` alone, whatever the call's `rows` cuts off
        counts = lengths[q0:q0 + QUERIES_PER_BLOCK]
        bx, by = _make_block(np.random.default_rng(children[block]),
                             counts, laws, params)
        src = np.concatenate(([0], np.cumsum(counts)))
        for q in range(q0, q1):
            c = sizes[q]
            x[dest[q]:dest[q] + c] = bx[src[q - q0]:src[q - q0] + c]
            y[dest[q]:dest[q] + c] = by[src[q - q0]:src[q - q0] + c]

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(one, range(-(-held // QUERIES_PER_BLOCK))))
    return x, y, {"group": sizes[order]}
