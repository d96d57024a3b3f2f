"""Dataset: binned feature matrix + metadata, host & device views.

Equivalent surface to the reference Dataset/DatasetLoader/Metadata
(reference: include/LightGBM/dataset.h:41-641, src/io/dataset_loader.cpp).
TPU-first storage decision: instead of per-group Bin objects (dense/sparse/
4-bit variants, src/io/*_bin.hpp), the binned matrix is ONE dense (N, F)
uint8/uint16 device array — XLA-friendly static shape, rows gatherable for
leaf-wise histogram work. Sparse inputs are binned from their nonzeros:
where the EFB plan bundles anything, the (N, C) bundled codes are built
straight from the CSC columns and the per-feature (N, F) view is made
only when something asks for it (`binned`); otherwise the nonzeros are
scattered into the (N, F) code matrix.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..config import Config
from ..telemetry import counters as telemetry_counters
from ..telemetry import spans as telem_spans
from ..utils import log
from .binning import (BIN_CATEGORICAL, BIN_NUMERICAL, MISSING_NAN,
                      MISSING_NONE, MISSING_ZERO, BinMapper,
                      load_forced_bounds, mapper_from_sample_column,
                      resolve_ignore_set)
from .bundling import bundle_codes, encode_bundle


def resolve_categorical_set(spec, feature_names) -> set:
    """categorical_feature spec (indices / names / 'name:x') -> column
    index set — the one copy shared by the in-memory, sparse and
    two-round loaders."""
    cats = set()
    for c in (spec or []):
        if isinstance(c, str):
            if c.startswith("name:"):
                c = c[5:]
            if c in feature_names:
                cats.add(feature_names.index(c))
        else:
            cats.add(int(c))
    return cats


class Metadata:
    """Labels, weights, query boundaries, init scores
    (reference: dataset.h:41-250, src/io/metadata.cpp)."""

    def __init__(self, num_data: int):
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None
        self.init_score: Optional[np.ndarray] = None

    def set_label(self, label) -> None:
        label = np.asarray(label, dtype=np.float64).reshape(-1)
        log.check(len(label) == self.num_data, "label length mismatch")
        self.label = label

    def set_weight(self, weight) -> None:
        if weight is None:
            self.weight = None
            return
        weight = np.asarray(weight, dtype=np.float64).reshape(-1)
        log.check(len(weight) == self.num_data, "weight length mismatch")
        self.weight = weight

    def set_group(self, group) -> None:
        """group = per-query row counts -> cumulative boundaries."""
        if group is None:
            self.query_boundaries = None
            return
        group = np.asarray(group, dtype=np.int64).reshape(-1)
        log.check(int(group.sum()) == self.num_data,
                  "sum of group counts != num_data")
        self.query_boundaries = np.concatenate(
            [[0], np.cumsum(group)]).astype(np.int32)

    def set_init_score(self, init_score) -> None:
        if init_score is None:
            self.init_score = None
            return
        self.init_score = np.asarray(init_score, dtype=np.float64)

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


class Dataset:
    """Binned training data.

    Core construction flow mirrors DatasetLoader::LoadFromFile/
    ConstructFromSampleData (reference: dataset_loader.cpp:168-722): sample
    rows -> per-feature BinMapper::FindBin -> bin every value.
    """

    def __init__(self, data: np.ndarray, config: Optional[Config] = None,
                 label=None, weight=None, group=None, init_score=None,
                 feature_names: Optional[List[str]] = None,
                 categorical_feature: Optional[Sequence] = None,
                 reference: Optional["Dataset"] = None,
                 params: Optional[Dict[str, Any]] = None,
                 bin_mappers=None):
        self.config = config or Config(params or {})
        data, sparse = self._prep_data(data)
        self.num_data, self.num_total_features = (
            sparse.shape if sparse is not None else data.shape)
        self.metadata = Metadata(self.num_data)
        if label is not None:
            self.metadata.set_label(label)
        self.metadata.set_weight(weight)
        self.metadata.set_group(group)
        self.metadata.set_init_score(init_score)
        self.feature_names = (list(feature_names) if feature_names
                              else [f"Column_{i}" for i in range(self.num_total_features)])
        self.reference = reference
        self.row_shard: Optional[Tuple[int, int]] = None

        if reference is not None:
            self.bin_mappers = reference.bin_mappers
            self.used_features = reference.used_features
            self.max_num_bins = reference.max_num_bins
            self.feature_names = reference.feature_names
        elif bin_mappers is not None:
            # precomputed mappers (distributed bin finding,
            # io/distributed.py): bin the local partition directly
            self.bin_mappers = list(bin_mappers)
            self.used_features = [i for i, m in enumerate(self.bin_mappers)
                                  if not m.is_trivial]
            self.max_num_bins = max(
                [self.bin_mappers[i].num_bin for i in self.used_features],
                default=1)
        else:
            cat_idx = self._resolve_categorical(categorical_feature)
            with telem_spans.stage("setup_find_bin_seconds",
                                   "dataset/find_bin"):
                self.bin_mappers = (
                    self._build_mappers_sparse(sparse, cat_idx)
                    if sparse is not None
                    else self._build_mappers(data, cat_idx))
            self.used_features = [i for i, m in enumerate(self.bin_mappers)
                                  if not m.is_trivial]
            if not self.used_features:
                log.warning("All features are trivial (constant); nothing to train on")
            self.max_num_bins = max(
                [self.bin_mappers[i].num_bin for i in self.used_features], default=1)

        # EFB: plan storage columns and encode the bundled matrix
        # (reference: dataset.cpp:69-225 FindGroups/FastFeatureBundling).
        # self.binned stays the logical per-feature view for generic
        # consumers; the device learner trains on the narrower bundle view.
        if sparse is not None:
            self._construct_sparse(sparse, reference)
        else:
            with telem_spans.stage("setup_bin_data_seconds",
                                   "dataset/bin_data"):
                self.binned = self._bin_data(data)
            with telem_spans.stage("setup_bundle_seconds",
                                   "dataset/bundle"):
                self.columns = (reference.columns if reference is not None
                                else self._plan_bundles())
                self.bundled = (self._encode_bundles() if self.columns
                                else None)
        held = sum(a.nbytes for a in (self._binned, self.bundled,
                                      *(self._nz or ())) if a is not None)
        telemetry_counters.set_gauge("host_code_bytes_per_row",
                                     held / max(self.num_data, 1))
        # raw column stats used for leaf renewal on some objectives
        self._device_cache: Dict[str, Any] = {}

    # -- the logical (N, F) view ----------------------------------------
    # A sparse table that bundles holds the bundled (N, C) codes alone;
    # its per-feature view is built here, on the first read, for the
    # consumers that want one (subset, merge, continual update, drift,
    # dump), decoded from the bundled codes. Where a row of the table
    # holds two members of one bundle the last one pushed won, so the
    # nonzeros' codes (`_nz`) are kept to build the view from instead.
    # Training reads `bundled`.
    _binned: Optional[np.ndarray] = None
    _nz: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None
    _view_from_bundled = False

    @property
    def binned(self) -> np.ndarray:
        if self._binned is None and self._view_from_bundled:
            self._binned = (self._scatter_nonzeros(*self._nz)
                            if self._nz is not None
                            else self._decode_bundles())
        return self._binned

    @binned.setter
    def binned(self, value) -> None:
        self._binned, self._nz, self._view_from_bundled = value, None, False

    # ------------------------------------------------------------------
    @classmethod
    def from_binned(cls, binned: np.ndarray, bin_mappers, config,
                    label=None, weight=None, group=None, init_score=None,
                    feature_names=None, row_shard=None) -> "Dataset":
        """Construct from an already-binned code matrix + its mappers —
        the two-round loader's entry (io/two_round.py round 2 bins
        chunks straight into `binned`; the float matrix never existed,
        reference dataset_loader.cpp:168 two_round role). `binned` holds
        the NON-trivial features' columns, in mapper order.

        `row_shard=(begin, num_total_rows)` marks a rank-partitioned
        dataset (distributed/ingest.py `dist_shard_mode=rows`): `binned`
        then holds only this host's contiguous row block starting at
        global row `begin`, while `num_data`, labels and weights stay
        GLOBAL — metrics, objectives and scores span all rows, only the
        code matrix is partitioned. EFB bundling is skipped (the bundle
        plan is data-dependent and would diverge across ranks) and
        `device_binned()` is unavailable."""
        self = cls.__new__(cls)
        self.config = config
        if row_shard is not None:
            begin, total = int(row_shard[0]), int(row_shard[1])
            log.check(0 <= begin <= total
                      and begin + binned.shape[0] <= total,
                      "row_shard block out of range")
            self.row_shard = (begin, begin + int(binned.shape[0]))
            self.num_data = total
        else:
            self.row_shard = None
            self.num_data = int(binned.shape[0])
        self.num_total_features = len(bin_mappers)
        self.metadata = Metadata(self.num_data)
        if label is not None:
            self.metadata.set_label(label)
        self.metadata.set_weight(weight)
        self.metadata.set_group(group)
        self.metadata.set_init_score(init_score)
        self.feature_names = (list(feature_names) if feature_names else
                              [f"Column_{i}"
                               for i in range(self.num_total_features)])
        self.reference = None
        self.bin_mappers = list(bin_mappers)
        self.used_features = [i for i, m in enumerate(self.bin_mappers)
                              if not m.is_trivial]
        if not self.used_features:
            log.warning("All features are trivial (constant); "
                        "nothing to train on")
        self.max_num_bins = max(
            [self.bin_mappers[i].num_bin for i in self.used_features],
            default=1)
        assert binned.shape[1] == max(len(self.used_features), 1), \
            "binned width must match the non-trivial feature count"
        self.binned = binned
        self.columns = self._plan_bundles()
        self.bundled = self._encode_bundles() if self.columns else None
        self._device_cache: Dict[str, Any] = {}
        return self

    # ------------------------------------------------------------------
    @staticmethod
    def _prep_data(data):
        """Returns (dense, csc): exactly one is non-None. Sparse input is
        NEVER densified to a float matrix (the reference bins sparse
        input directly, src/io/sparse_bin.hpp:73 Push); it is canonical
        CSC for per-column nonzero iteration, and the only dense
        materialization downstream is the (N, F) uint8/16 code matrix —
        the designed post-bin storage."""
        try:
            import scipy.sparse as sp
            if sp.issparse(data):
                # float32 stays float32: binning reads each column as
                # float64 anyway, and a float64 copy of a wide table's
                # nonzeros is the largest thing its construction holds
                csc = data.tocsc()
                if csc.dtype not in (np.float32, np.float64):
                    csc = csc.astype(np.float64)
                csc.sum_duplicates()
                csc.sort_indices()
                return None, csc
        except ImportError:
            pass
        if hasattr(data, "values"):  # pandas
            data = data.values
        arr = np.asarray(data, dtype=np.float64)
        if arr.ndim == 1:
            arr = arr.reshape(-1, 1)
        return arr, None

    def _resolve_categorical(self, categorical_feature) -> set:
        return resolve_categorical_set(
            categorical_feature or self.config.categorical_feature,
            self.feature_names)

    def _build_mappers(self, data: np.ndarray, cat_idx: set) -> List[BinMapper]:
        cfg = self.config
        sample_rows = self._bin_sample_rows()
        forced_bounds = load_forced_bounds(cfg.forcedbins_filename)
        ignore = resolve_ignore_set(cfg.ignore_column, self.feature_names)
        mappers = []
        for f in range(self.num_total_features):
            if f in ignore:
                mappers.append(BinMapper.trivial())
                continue
            mappers.append(mapper_from_sample_column(
                data[sample_rows, f], len(sample_rows), cfg, f, cat_idx,
                forced_bounds))
        return mappers

    def _bin_data(self, data: np.ndarray) -> np.ndarray:
        n_used = len(self.used_features)
        dtype = np.uint8 if self.max_num_bins <= 256 else np.uint16
        out = np.zeros((self.num_data, max(n_used, 1)), dtype=dtype)
        for j, f in enumerate(self.used_features):
            out[:, j] = self.bin_mappers[f].values_to_bins(data[:, f]).astype(dtype)
        return out

    def _build_mappers_sparse(self, csc, cat_idx: set) -> List[BinMapper]:
        """Per-column find-bin straight off the CSC structure: only each
        column's sampled NONZERO values are handed to the mapper (zeros
        implied by the sample count — find_bin's sparse contract, the
        reference's DatasetLoader sampling + sparse_bin.hpp ingestion
        semantics). Peak extra memory is O(nnz of one column)."""
        cfg = self.config
        n = self.num_data
        sample_rows = self._bin_sample_rows()
        if len(sample_rows) == n:
            sample_rows = None
        forced_bounds = load_forced_bounds(cfg.forcedbins_filename)
        ignore = resolve_ignore_set(cfg.ignore_column, self.feature_names)
        indptr, indices, values = csc.indptr, csc.indices, csc.data
        mappers = []
        for f in range(self.num_total_features):
            if f in ignore:
                mappers.append(BinMapper.trivial())
                continue
            lo, hi = int(indptr[f]), int(indptr[f + 1])
            vals = values[lo:hi]
            if sample_rows is not None:
                rows = indices[lo:hi]
                at = np.searchsorted(sample_rows, rows)
                at[at >= len(sample_rows)] = 0
                vals = vals[sample_rows[at] == rows]
                total = len(sample_rows)
            else:
                total = n
            mappers.append(mapper_from_sample_column(
                vals, total, cfg, f, cat_idx, forced_bounds))
        return mappers

    def _bin_sample_rows(self) -> np.ndarray:
        """The sorted rows a table's bins are found on (and its bundles
        planned on): `bin_construct_sample_cnt` of them."""
        n = self.num_data
        sample_cnt = min(n, self.config.bin_construct_sample_cnt)
        if sample_cnt == n:
            return np.arange(n)
        rng = np.random.RandomState(self.config.data_random_seed)
        return np.sort(rng.choice(n, sample_cnt, replace=False))

    # -- sparse tables: O(nnz), and no (N, F) plane where they bundle ---
    def _construct_sparse(self, csc, reference) -> None:
        """Bins the nonzeros of the used columns, plans the bundles from
        the codes of the sampled rows, and builds the bundled (N, C)
        codes straight from them; the (N, F) code matrix is built only
        where nothing bundles (then it is what training reads). No dense
        float matrix ever exists."""
        with telem_spans.stage("setup_bin_data_seconds",
                               "dataset/bin_data"):
            nz = self._bin_nonzeros(csc)
        with telem_spans.stage("setup_bundle_seconds", "dataset/bundle"):
            self.columns = (reference.columns if reference is not None
                            else self._plan_bundles(nz))
            self.bundled, conflicts = (self._encode_bundles_sparse(*nz)
                                       if self.columns else (None, 0))
        if self.columns:
            self._view_from_bundled = True
            self._nz = nz if conflicts else None
            return
        with telem_spans.stage("setup_bin_data_seconds",
                               "dataset/bin_data"):
            self.binned = self._scatter_nonzeros(*nz)

    def _bin_nonzeros(self, csc):
        """(starts, rows, codes): the stored entries of the used columns
        in inner-feature order, each with its bin code; feature j's are
        `starts[j]:starts[j + 1]`."""
        dtype = np.uint8 if self.max_num_bins <= 256 else np.uint16
        indptr, indices, values = csc.indptr, csc.indices, csc.data
        spans = [(int(indptr[f]), int(indptr[f + 1]))
                 for f in self.used_features]
        starts = np.zeros(len(spans) + 1, np.int64)
        starts[1:] = np.cumsum([hi - lo for lo, hi in spans])
        every = self.used_features == list(range(self.num_total_features))
        rows = indices if every else np.empty(int(starts[-1]),
                                              indices.dtype)
        codes = np.empty(int(starts[-1]), dtype)
        for j, (f, (lo, hi)) in enumerate(zip(self.used_features, spans)):
            if not every:
                rows[starts[j]:starts[j + 1]] = indices[lo:hi]
            codes[starts[j]:starts[j + 1]] = \
                self.bin_mappers[f].values_to_bins(values[lo:hi])
        return starts, rows, codes

    def _zero_bin(self, j: int) -> int:
        return self.bin_mappers[self.used_features[j]].value_to_bin(0.0)

    def _scatter_nonzeros(self, starts, rows, codes) -> np.ndarray:
        """The (N, F) code matrix from the nonzeros' codes: each column
        starts at its zero-value bin and only the nonzeros are
        scattered."""
        out = np.empty((self.num_data, max(self.num_features, 1)),
                       dtype=codes.dtype)
        out[:] = [self._zero_bin(j) for j in range(self.num_features)] or 0
        for j in range(self.num_features):
            a, b = starts[j], starts[j + 1]
            out[rows[a:b], j] = codes[a:b]
        return out

    def _encode_bundles_sparse(self, starts, rows,
                               codes) -> Tuple[np.ndarray, int]:
        """The bundled (N, C) codes from the nonzeros' codes: what
        `_encode_bundles` gives on the (N, F) view, byte for byte. A
        single-feature column starts at its zero bin and takes its
        nonzeros; a bundle member writes `base + j` at its non-default
        rows, the last member pushed winning a conflict row, as
        `encode_bundle` does. Also gives how many writes met a row
        written already (conflicts)."""
        out = np.empty((self.num_data, len(self.columns)),
                       dtype=self._bundled_dtype())
        out[:] = [0 if col.is_bundle else self._zero_bin(col.features[0])
                  for col in self.columns]
        conflicts = 0
        for ci, col in enumerate(self.columns):
            for j, base in zip(col.features, col.bases):
                a, b = starts[j], starts[j + 1]
                if not col.is_bundle:
                    out[rows[a:b], ci] = codes[a:b]
                    continue
                default = self._default(j)
                if self._zero_bin(j) != default:
                    # the absent rows are not at the default bin: every
                    # row is written, so take the whole column
                    full = np.full(self.num_data, self._zero_bin(j),
                                   codes.dtype)
                    full[rows[a:b]] = codes[a:b]
                    at = np.flatnonzero(full != default)
                    bins = full[at]
                else:
                    keep = codes[a:b] != default
                    at, bins = rows[a:b][keep], codes[a:b][keep]
                # a member's codes are never 0: a row written already is
                # a conflict, and the last member pushed wins it
                conflicts += int(np.count_nonzero(out[at, ci]))
                out[at, ci] = bundle_codes(bins, base, default)
        return out, conflicts

    def _decode_bundles(self) -> np.ndarray:
        """The (N, F) code matrix from the bundled codes of a table where
        no row holds two members of one bundle: a member's rows are those
        whose code lies in its range, and its other rows sit at its
        default bin."""
        dtype = np.uint8 if self.max_num_bins <= 256 else np.uint16
        out = np.empty((self.num_data, max(self.num_features, 1)), dtype)
        out[:] = [self._default(j) for j in range(self.num_features)] or 0
        for ci, col in enumerate(self.columns):
            codes = self.bundled[:, ci]
            if not col.is_bundle:
                out[:, col.features[0]] = codes
                continue
            feature = np.zeros(col.num_bins, np.int64)   # code -> member
            value = np.zeros(col.num_bins, dtype)        # code -> its bin
            for j, base in zip(col.features, col.bases):
                k = np.arange(self.bin_mappers[
                    self.used_features[j]].num_bin - 1)
                feature[base + k] = j
                value[base + k] = k + (k >= self._default(j))
            at = np.flatnonzero(codes)
            out[at, feature[codes[at]]] = value[codes[at]]
        return out

    # ------------------------------------------------------------------
    def _plan_bundles(self, nz=None):
        """EFB column plan from a sample of the binned matrix, or of the
        nonzeros' codes `nz` (`_bin_nonzeros`) where there is no plane."""
        from .bundling import plan_columns
        cfg = self.config
        if (not cfg.enable_bundle or self.num_features <= 1
                or self.num_data == 0):
            return None
        if getattr(self, "row_shard", None) is not None:
            # rank-partitioned block: the bundle plan samples the DATA,
            # so each rank would plan different columns and the shards
            # would stop vstacking into one logical matrix — train on
            # the unbundled per-feature view instead
            return None
        # the rows the bins were found on, as the reference plans
        # (dataset.cpp FindGroups): a one-hot level the binning saw is
        # then seen by the plan, which would otherwise bundle it blind
        # and let the whole table conflict where the sample did not
        rows = self._bin_sample_rows()
        if nz is None:
            sample = (self.binned if len(rows) == self.num_data
                      else self.binned[rows])
            nondefault = [np.flatnonzero(sample[:, j] != self._default(j))
                          for j in range(self.num_features)]
        else:
            nondefault = [self._sample_nondefault(nz, j, rows)
                          for j in range(self.num_features)]
        cols = plan_columns(self.used_features, self.bin_mappers, nondefault,
                            len(rows), cfg.max_conflict_rate,
                            cfg.sparse_threshold)
        if all(len(c.features) == 1 for c in cols):
            return None
        return cols

    def _default(self, j: int) -> int:
        return self.bin_mappers[self.used_features[j]].default_bin

    def _sample_nondefault(self, nz, j, sample_rows) -> np.ndarray:
        """Indices into the (sorted) sampled rows where feature j is away
        from its default bin, from its nonzeros alone."""
        starts, rows, codes = nz
        a, b = starts[j], starts[j + 1]
        at = np.searchsorted(sample_rows, rows[a:b])
        hit = at < len(sample_rows)
        hit[hit] = sample_rows[at[hit]] == rows[a:b][hit]
        away = codes[a:b][hit] != self._default(j)
        if self._zero_bin(j) == self._default(j):
            return at[hit][away]
        mask = np.ones(len(sample_rows), bool)      # absent rows are away
        mask[at[hit][~away]] = False
        return np.flatnonzero(mask)

    def _bundled_dtype(self):
        col_bins = max(c.num_bins for c in self.columns)
        return np.uint8 if col_bins <= 256 else np.uint16

    def _encode_bundles(self) -> np.ndarray:
        dtype = self._bundled_dtype()
        out = np.zeros((self.num_data, len(self.columns)), dtype=dtype)
        for ci, col in enumerate(self.columns):
            if not col.is_bundle:
                out[:, ci] = self.binned[:, col.features[0]].astype(dtype)
                continue
            for j, base in zip(col.features, col.bases):
                m = self.bin_mappers[self.used_features[j]]
                encode_bundle(out[:, ci], self.binned[:, j], base,
                              m.default_bin)
        return out

    def bundle_arrays(self):
        """Device maps for the bundled view (None when unbundled):
        (bundled codes (N, C), f_col, f_base, f_elide, hist_idx, col_bins)."""
        if self.bundled is None:
            return None
        import jax.numpy as jnp
        if "bundle" not in self._device_cache:
            from .bundling import expansion_arrays
            f_col, f_base, f_elide, hist_idx, col_bins = expansion_arrays(
                self.columns, self.used_features, self.bin_mappers,
                self.num_features, self.max_num_bins)
            self._device_cache["bundle"] = (
                jnp.asarray(self.bundled), jnp.asarray(f_col),
                jnp.asarray(f_base), jnp.asarray(f_elide),
                jnp.asarray(hist_idx), col_bins)
        return self._device_cache["bundle"]

    @property
    def num_features(self) -> int:
        return len(self.used_features)

    @property
    def label(self):
        return self.metadata.label

    def feature_meta_arrays(self):
        """(num_bins, missing_type, default_bin, is_categorical, monotone)
        int32 arrays over *inner* (used) features, for the device ops."""
        import jax.numpy as jnp
        if "meta" not in self._device_cache:
            nb = np.array([self.bin_mappers[f].num_bin for f in self.used_features],
                          dtype=np.int32)
            mt = np.array([self.bin_mappers[f].missing_type for f in self.used_features],
                          dtype=np.int32)
            db = np.array([self.bin_mappers[f].default_bin for f in self.used_features],
                          dtype=np.int32)
            cat = np.array([self.bin_mappers[f].bin_type == BIN_CATEGORICAL
                            for f in self.used_features], dtype=np.int32)
            mono_all = self.config.monotone_constraints or []
            mono = np.array([mono_all[f] if f < len(mono_all) else 0
                             for f in self.used_features], dtype=np.int32)
            self._device_cache["meta"] = tuple(
                jnp.asarray(a) for a in (nb, mt, db, cat, mono))
        return self._device_cache["meta"]

    def device_binned(self):
        import jax.numpy as jnp
        if getattr(self, "row_shard", None) is not None:
            log.fatal(
                "device_binned: dataset is row-sharded "
                "(dist_shard_mode=rows holds rows %d:%d of %d on this "
                "host); the full code matrix exists on no single host. "
                "Consumers must run on the partitioned view or use "
                "dist_shard_mode=replicated", self.row_shard[0],
                self.row_shard[1], self.num_data)
        if "binned" not in self._device_cache:
            self._device_cache["binned"] = jnp.asarray(self.binned)
        return self._device_cache["binned"]

    def inner_to_real(self, inner: int) -> int:
        return self.used_features[inner]

    def real_threshold(self, inner_feature: int, bin_thr: int) -> float:
        """Bin threshold -> stored real threshold (reference
        Dataset::RealThreshold -> BinMapper::BinToValue)."""
        return self.bin_mappers[self.used_features[inner_feature]].bin_to_value(bin_thr)

    # ------------------------------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None) -> "Dataset":
        """Validation set binned with this dataset's mappers
        (reference: Dataset::CreateValid / CheckAlign)."""
        return Dataset(data, config=self.config, label=label, weight=weight,
                       group=group, init_score=init_score, reference=self)

    def feature_infos(self) -> List[str]:
        return [m.feature_info() for m in self.bin_mappers]

    # ------------------------------------------------------------------
    def save_binary(self, path: str) -> None:
        """Binary cache (reference: Dataset::SaveBinaryFile; ours is npz)."""
        import json
        mappers = json.dumps([m.to_dict() for m in self.bin_mappers])
        np.savez_compressed(
            path, binned=self.binned, mappers=mappers,
            used_features=np.asarray(self.used_features, dtype=np.int64),
            feature_names=np.asarray(self.feature_names, dtype=object),
            label=(self.metadata.label if self.metadata.label is not None
                   else np.zeros(0)),
            weight=(self.metadata.weight if self.metadata.weight is not None
                    else np.zeros(0)),
            query_boundaries=(self.metadata.query_boundaries
                              if self.metadata.query_boundaries is not None
                              else np.zeros(0, dtype=np.int32)),
            init_score=(self.metadata.init_score
                        if self.metadata.init_score is not None
                        else np.zeros(0)),
        )

    @classmethod
    def load_binary(cls, path: str, params: Optional[dict] = None) -> "Dataset":
        import json
        z = np.load(path, allow_pickle=True)
        obj = cls.__new__(cls)
        obj.config = Config(params or {})
        obj.binned = z["binned"]
        obj.num_data = obj.binned.shape[0]
        obj.bin_mappers = [BinMapper.from_dict(d) for d in json.loads(str(z["mappers"]))]
        obj.num_total_features = len(obj.bin_mappers)
        obj.used_features = [int(i) for i in z["used_features"]]
        obj.feature_names = [str(s) for s in z["feature_names"]]
        obj.max_num_bins = max(
            [obj.bin_mappers[i].num_bin for i in obj.used_features], default=1)
        obj.metadata = Metadata(obj.num_data)
        if len(z["label"]):
            obj.metadata.label = z["label"]
        if len(z["weight"]):
            obj.metadata.weight = z["weight"]
        if len(z["query_boundaries"]):
            obj.metadata.query_boundaries = z["query_boundaries"]
        if len(z["init_score"]):
            obj.metadata.init_score = z["init_score"]
        obj.reference = None
        obj.row_shard = None
        obj.columns = obj._plan_bundles()
        obj.bundled = obj._encode_bundles() if obj.columns else None
        obj._device_cache = {}
        return obj
