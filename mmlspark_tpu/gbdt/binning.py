"""Quantile feature binning — the framework's BinMapper.

TPU-native analog of LightGBM's ``BinMapper``/``GreedyFindBin`` (invoked by
the reference through ``LGBM_DatasetCreateFromMat``; SURVEY.md §2.2, §3.1).
Continuous features are discretized into at most ``max_bin`` integer bins via
per-feature upper bounds:

* if a feature has ≤ ``max_bin`` distinct values, bounds are midpoints
  between consecutive distinct values (exact, LightGBM-style);
* otherwise bounds are weighted quantiles over a sample.

Missing values (NaN) map to a dedicated trailing bin, so split finding can
route them independently — the static-shape counterpart of LightGBM's
default-direction handling.  Binning runs on host numpy (it is a one-time
preprocessing pass, like the reference's executor-side dataset aggregation);
the binned ``uint8``/``int32`` matrix is what ships to the TPU.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from ..core.schema import SparseColumn

#: a categorical column whose binned values stay under this is binned by
#: table lookup (64 M int32 slots at most), above it by binary search
_CAT_LUT_MAX = 1 << 26
#: rows a thread bins at a time in ``transform_packed``'s categorical pass
_CAT_BLOCK_ROWS = 1 << 20
#: rows a thread takes at a time over a sparse column's entries
_SPARSE_BLOCK_ROWS = 1 << 18


def _row_blocks(fn, rows: int, block: int = _SPARSE_BLOCK_ROWS) -> list:
    """``fn(a, b)`` over blocks of rows, in threads (numpy's passes over
    a block release the lock); the results in block order."""
    starts = range(0, rows, block)
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        return list(pool.map(
            lambda a: fn(a, min(a + block, rows)), starts))


@dataclass
class SparseBins:
    """A binned table kept sparse: entry ``k`` says that row
    ``row_ids()[k]`` holds bin ``bins[k]`` in column ``indices[k]``; a
    cell no entry names holds ``implicit_bin[column]`` (a sparse
    column's zeros).  What :meth:`BinMapper.bin_entries` makes of a
    :class:`SparseColumn`, and what ``gbdt/efb.py`` bundles from."""

    indptr: np.ndarray         # (rows + 1,) int64
    indices: np.ndarray        # (nnz,) column of each entry
    bins: np.ndarray           # (nnz,) bin of each entry
    implicit_bin: np.ndarray   # (f,) bin of a cell without entry
    shape: tuple

    @classmethod
    def from_dense(cls, bins: np.ndarray) -> "SparseBins":
        """Dense ``(n, f)`` bins as entries: a column's most frequent
        bin is left implicit, every other cell is an entry."""
        bins = np.asarray(bins)
        n, f = bins.shape
        implicit = np.asarray(
            [np.bincount(bins[:, j].astype(np.int64)).argmax()
             for j in range(f)], bins.dtype) if n else np.zeros(f, bins.dtype)
        cells = np.flatnonzero(bins != implicit[None, :])
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(cells // max(f, 1), minlength=n),
                  out=indptr[1:])
        return cls(indptr, (cells % max(f, 1)).astype(np.int32),
                   bins.reshape(-1)[cells], implicit, (n, f))

    def row_ids(self) -> np.ndarray:
        """The row of every entry, ``(nnz,)``."""
        return np.repeat(np.arange(self.shape[0]), np.diff(self.indptr))

    def column_entries(self) -> np.ndarray:
        """Entries per column, ``(f,)``; counted once."""
        if getattr(self, "_per_column", None) is None:
            self._per_column = np.bincount(self.indices,
                                           minlength=self.shape[1])
        return self._per_column

    def take_rows(self, idx: np.ndarray) -> "SparseBins":
        picked = SparseColumn(self.indptr, self.indices, self.bins,
                              self.shape)[idx]
        return SparseBins(picked.indptr, picked.indices, picked.values,
                          self.implicit_bin, picked.shape)

    def toarray(self) -> np.ndarray:
        out = np.empty(self.shape, self.bins.dtype)
        out[:] = self.implicit_bin[None, :]
        out[self.row_ids(), self.indices] = self.bins
        return out


#: the threshold of a split at a feature's top value bin, whose upper
#: bound is infinite: LightGBM's model text clamps it to this
THRESHOLD_MAX = 1e300


@dataclass
class BinMapper:
    """Per-feature binning spec: ``upper_bounds[f]`` sorted ascending.

    Categorical features (``categorical[f]``) bin by category identity
    instead: ``cat_values[f]`` lists the raw (non-negative integer) category
    per bin index, most-frequent first — the analog of LightGBM's
    categorical ``BinMapper`` (bin_type=categorical).  Unseen categories and
    NaN map to ``missing_bin``.
    """

    upper_bounds: List[np.ndarray]   # len f, each (num_bins_f - 1,) finite
    has_missing: np.ndarray          # (f,) bool
    num_total_bins: int              # B used for histogram sizing (max over f)
    missing_bin: int                 # index reserved for NaN (== B - 1)
    categorical: Optional[np.ndarray] = None   # (f,) bool
    cat_values: Optional[List[Optional[np.ndarray]]] = None  # raw cat per bin

    @property
    def num_features(self) -> int:
        return len(self.upper_bounds)

    @property
    def has_categorical(self) -> bool:
        return self.categorical is not None and bool(self.categorical.any())

    def is_categorical(self, j: int) -> bool:
        return self.categorical is not None and bool(self.categorical[j])

    def feature_num_bins(self, j: int) -> int:
        """Value bins actually used by feature j (excl. the missing bin)."""
        if self.is_categorical(j):
            return len(self.cat_values[j])
        return len(self.upper_bounds[j]) + 1

    @property
    def bin_dtype(self) -> np.dtype:
        """Narrowest integer dtype that holds every bin index (numpy dtype;
        jnp.asarray accepts it directly).  256 bins fit uint8 exactly — 4x
        less transfer/gather traffic than int32 in the training hot loop
        (grower gathers, histogram chunk reads)."""
        return np.dtype(np.uint8 if self.num_total_bins <= 256
                        else np.int32)

    def _fast_state(self, is64: bool):
        """Precomputed arrays for the native ``bin_columns`` kernel.

        For float32 inputs the float64 bounds are adjusted DOWN to the
        largest float32 ``c <= b``; then for every float32 value ``v``,
        ``c < v  ⇔  b < v`` (if ``c < v`` then ``v`` is a float32 above
        the largest float32 ≤ b, hence ``v > b``; conversely ``b < v``
        implies ``c ≤ b < v``), so uint8 bins from float32 comparisons
        match the float64 reference bit-exactly.  A uniform ``C``-cell
        grid per feature provides a starting hint; the kernel probes
        locally in both directions, so the hint only affects speed, never
        the result.  Features whose bounds pack > 32 deep into one cell
        (degenerate hint) use plain binary search instead.
        """
        key = "_fs64" if is64 else "_fs32"
        cached = getattr(self, key, None)
        if cached is not None:
            return cached
        f = self.num_features
        C = 2048
        nb = np.asarray([len(ub) for ub in self.upper_bounds], np.int32)
        m = max(int(nb.max()), 1) if f else 1
        dt = np.float64 if is64 else np.float32
        bext = np.full((f, m), np.inf, dt)
        lo = np.zeros(f, np.float32)
        scale = np.zeros(f, np.float32)
        base = np.zeros((f, C), np.int32)
        use_table = np.zeros(f, np.uint8)
        for j, ub in enumerate(self.upper_bounds):
            if len(ub) == 0 or self.is_categorical(j):
                continue
            if is64:
                c = ub
            else:
                c = ub.astype(np.float32)
                over = c.astype(np.float64) > ub
                c[over] = np.nextafter(c[over], np.float32(-np.inf))
            bext[j, :len(c)] = c
            span = float(c[-1]) - float(c[0])
            if len(c) >= 8 and span > 0 and np.isfinite(span):
                lo[j] = np.float32(c[0])
                with np.errstate(over="ignore"):
                    scale_j = np.float32(C / (span * (1 + 1e-6)))
                if not np.isfinite(scale_j):   # span below ~f32 tiny
                    continue
                scale[j] = scale_j
                edges = (float(lo[j])
                         + np.arange(C, dtype=np.float64) / float(scale[j]))
                b0 = np.searchsorted(c, edges.astype(c.dtype), side="left")
                top = np.searchsorted(
                    c, np.nextafter((edges + 1.0 / float(scale[j])
                                     ).astype(c.dtype), np.inf), side="left")
                if int((top - b0).max()) <= 32:
                    base[j] = b0
                    use_table[j] = 1
        state = (bext, nb, base, lo, scale, use_table)
        object.__setattr__(self, key, state)
        return state

    def transform_packed(self, X: np.ndarray) -> np.ndarray:
        """:meth:`transform` into the narrowest dtype via the native
        ``fastbin`` kernel (~0.2 s for the 400k×50 bench matrix vs ~3 s
        for numpy/torch searchsorted on one CPU core).  The uint8 output
        is what ships over the host↔device link: 4x fewer bytes than
        int32, and 4x fewer than shipping the raw f32 matrix to bin
        on-device.  Which side should bin has not been measured on a
        directly attached chip (ROADMAP S9).

        Exactness: identical output to :meth:`transform` (float64
        semantics) for float32 and float64 inputs; pinned by
        tests/test_gbdt.py's packed-parity test.
        """
        dt = self.bin_dtype
        if isinstance(X, SparseColumn):
            # the unbundled table of a sparse column is dense: (n, f)
            # bins (gbdt/efb.py writes the bundled one from the entries)
            return self.bin_entries(X).toarray()
        if dt != np.uint8 or X.dtype not in (np.float32, np.float64):
            # > 256 total bins (or exotic dtypes): torch's batched
            # searchsorted still beats the per-column numpy loop
            return self._transform_torch(X, dt)
        from .. import native
        if not native.bin_columns_available():
            return self._transform_torch(X, dt)
        is64 = X.dtype == np.float64
        bext, nb, base, lo, scale, use_table = self._fast_state(is64)
        Xc = np.ascontiguousarray(X)
        out = np.empty(X.shape, np.uint8)
        native.bin_columns(Xc, bext, nb, base, lo, scale, use_table,
                           self.missing_bin, out)
        if self.has_categorical:
            # blocks of rows in threads, every categorical column of a
            # block while it is warm: 26 whole-column passes over 3e7 rows
            # were most of a click log's binning
            cols = [int(j) for j in np.nonzero(self.categorical)[0]]
            luts = {j: self._cat_lut(j) for j in cols}

            def block(a):
                b = min(a + _CAT_BLOCK_ROWS, X.shape[0])
                for j in cols:
                    out[a:b, j] = self._transform_cat(X[a:b, j], j,
                                                      lut=luts[j])

            with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
                list(pool.map(block, range(0, X.shape[0], _CAT_BLOCK_ROWS)))
        return out

    def bin_entries(self, X: SparseColumn) -> SparseBins:
        """The bin of every entry of a sparse column and of its zeros,
        by :meth:`transform`'s rule (the first bound ``>= v``, float64
        compares; NaN to the missing bin): no dense array is made.
        Blocks of rows in threads; a column of one bound is one compare
        an entry, any other a binary search over the padded bounds."""
        if X.shape[1] != self.num_features:
            raise ValueError(
                f"Expected {self.num_features} features, got {X.shape[1]}")
        if self.has_categorical:
            raise NotImplementedError(
                "a sparse feature column with categorical slots is not "
                "supported: pass dense rows")
        f = self.num_features
        nb = np.asarray([len(ub) for ub in self.upper_bounds], np.int32)
        m = int(nb.max()) + 1 if f else 1
        bext = np.full((f, m), np.inf, np.float64)
        for j, ub in enumerate(self.upper_bounds):
            bext[j, :len(ub)] = ub
        flat = bext.reshape(-1)
        steps = int(m).bit_length()
        dt = self.bin_dtype
        out = np.empty(X.nnz, dt)

        def block(a, b):
            lo_e, hi_e = X.indptr[a], X.indptr[b]
            cols = X.indices[lo_e:hi_e]
            vals = X.values[lo_e:hi_e].astype(np.float64)
            nbc = nb[cols]
            res = (flat[cols.astype(np.int64) * m] < vals).astype(np.int32)
            deep = np.flatnonzero(nbc > 1)
            if deep.size:
                c = cols[deep].astype(np.int64) * m
                v = vals[deep]
                lo = np.zeros(deep.size, np.int64)
                hi = nbc[deep].astype(np.int64)
                for _ in range(steps):
                    mid = (lo + hi) >> 1
                    go = flat[c + mid] < v
                    lo = np.where(go, mid + 1, lo)
                    hi = np.where(go, hi, mid)
                res[deep] = lo
            res[np.isnan(vals)] = self.missing_bin
            out[lo_e:hi_e] = res

        _row_blocks(block, X.shape[0])
        implicit = np.asarray(
            [np.searchsorted(ub, 0.0, side="left")
             for ub in self.upper_bounds], dt)
        return SparseBins(X.indptr, X.indices, out, implicit, X.shape)

    def _transform_torch(self, X: np.ndarray, dt: np.dtype) -> np.ndarray:
        """Batched float64 searchsorted via torch — the fallback when the
        native kernel can't apply (non-uint8 bins, missing toolchain)."""
        if self.has_categorical:
            return self.transform(X).astype(dt)
        try:
            import torch
        except Exception:  # pragma: no cover - torch is baked into the image
            return self.transform(X).astype(dt)
        f = self.num_features
        maxlen = max((len(ub) for ub in self.upper_bounds), default=0)
        bounds = np.full((f, max(maxlen, 1)), np.inf, np.float64)
        for j, ub in enumerate(self.upper_bounds):
            bounds[j, :len(ub)] = ub
        Xt = torch.from_numpy(np.ascontiguousarray(X.T, dtype=np.float64))
        out = torch.searchsorted(torch.from_numpy(bounds), Xt, side="left")
        out = out.numpy().T.astype(dt)
        nan_mask = np.isnan(X)
        if nan_mask.any():
            out[nan_mask] = self.missing_bin
        return out

    def transform(self, X: np.ndarray) -> np.ndarray:
        """Map raw features to bin indices ``(n, f)``, NaN → missing_bin."""
        n, f = X.shape
        if f != self.num_features:
            raise ValueError(
                f"Expected {self.num_features} features, got {f}")
        out = np.empty((n, f), dtype=np.int32)
        for j in range(f):
            col = X[:, j]
            if self.is_categorical(j):
                out[:, j] = self._transform_cat(col, j)
                continue
            out[:, j] = np.searchsorted(self.upper_bounds[j], col, side="left")
            nan_mask = np.isnan(col)
            if nan_mask.any():
                out[nan_mask, j] = self.missing_bin
        return out

    def _cat_lut(self, j: int) -> Optional[np.ndarray]:
        """Raw category value -> bin as one array lookup: ``lut[v]`` for
        ``0 <= v < len(lut) - 1``, the last slot (every other value)
        holding the missing bin.  None where the binned values reach too
        high for a table (hashed ids near 2^31): binary search then."""
        cats = np.asarray(self.cat_values[j]).astype(np.int64)
        top = int(cats.max()) + 1 if len(cats) else 0
        if top > _CAT_LUT_MAX:
            return None
        lut = np.full(top + 1, self.missing_bin, np.int32)
        lut[cats] = np.arange(len(cats), dtype=np.int32)
        return lut

    def _transform_cat(self, col: np.ndarray, j: int,
                       lut: Optional[np.ndarray] = None) -> np.ndarray:
        vals = np.nan_to_num(col, nan=-1.0).astype(np.int64)
        if lut is None:
            lut = self._cat_lut(j)
        if lut is not None:
            other = len(lut) - 1
            vals[(vals < 0) | (vals > other)] = other
            return lut[vals]
        cats = self.cat_values[j]                       # bin -> raw value
        order = np.argsort(cats)
        sorted_cats = cats[order]
        pos = np.searchsorted(sorted_cats, vals)
        pos = np.clip(pos, 0, len(sorted_cats) - 1)
        hit = sorted_cats[pos] == vals
        bins = np.where(hit, order[pos], self.missing_bin)
        return bins.astype(np.int32)

    def bin_threshold_value(self, feature: int, bin_idx: int) -> float:
        """Real-valued threshold for a split at ``bin <= bin_idx``.

        Matches LightGBM's convention of storing the bin upper bound in the
        model file, so exported models score identically on raw features.
        The top value bin's bound is infinite, and LightGBM writes it as
        ``1e300`` (``Common::AvoidInf``): a split there isolates the
        missing bin, every finite value going left.
        """
        ub = self.upper_bounds[feature]
        if bin_idx >= len(ub):
            return THRESHOLD_MAX
        return float(ub[bin_idx])

    def feature_infos(self) -> List[str]:
        """LightGBM model-file ``feature_infos`` entries: [min:max] for
        numeric features, colon-joined category list for categorical."""
        infos = []
        for j, ub in enumerate(self.upper_bounds):
            if self.is_categorical(j):
                cats = np.sort(self.cat_values[j])
                infos.append(":".join(str(int(c)) for c in cats) or "none")
            elif len(ub) == 0:
                infos.append("none")
            else:
                infos.append(f"[{ub[0]:.6g}:{ub[-1]:.6g}]")
        return infos

    # -- serialization (ISSUE 18) -------------------------------------------

    def to_json(self) -> str:
        """Exact JSON round-trip of the bin ladder (ISSUE 18): the
        streaming-ingest spill and the refresh loop persist the ACTIVE
        model's mapper so binned uint8 segments stay interpretable
        across process death.  Bounds are float64 and Python's JSON
        float repr is shortest-round-trip, so
        ``from_json(m.to_json())`` reproduces every bound bit-exactly
        (binning, and therefore replay, is deterministic across the
        crash)."""
        import json
        doc = {
            "format": 1,
            "upper_bounds": [ub.tolist() for ub in self.upper_bounds],
            "has_missing": self.has_missing.astype(int).tolist(),
            "num_total_bins": int(self.num_total_bins),
            "missing_bin": int(self.missing_bin),
        }
        if self.categorical is not None:
            doc["categorical"] = self.categorical.astype(int).tolist()
            doc["cat_values"] = [
                None if cv is None else cv.tolist()
                for cv in (self.cat_values or [])]
        return json.dumps(doc, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "BinMapper":
        import json
        doc = json.loads(text)
        if doc.get("format") != 1:
            raise ValueError(
                f"unsupported BinMapper format {doc.get('format')!r}")
        cat = doc.get("categorical")
        return cls(
            upper_bounds=[np.asarray(ub, np.float64)
                          for ub in doc["upper_bounds"]],
            has_missing=np.asarray(doc["has_missing"], bool),
            num_total_bins=int(doc["num_total_bins"]),
            missing_bin=int(doc["missing_bin"]),
            categorical=None if cat is None else np.asarray(cat, bool),
            cat_values=None if cat is None else [
                None if cv is None else np.asarray(cv, np.float64)
                for cv in doc["cat_values"]])


def fit_bin_mapper(X: np.ndarray, max_bin: int = 255,
                   sample_cnt: int = 200000,
                   min_data_in_bin: int = 3,
                   seed: int = 0,
                   categorical_features: Optional[List[int]] = None
                   ) -> BinMapper:
    """Learn per-feature bin upper bounds (GreedyFindBin analog).

    ``max_bin`` counts value bins; one extra trailing bin is reserved for
    missing values, giving ``num_total_bins = max_bin + 1``.

    ``categorical_features``: column indexes binned by category identity
    (raw values must be non-negative integers, LightGBM's contract); the
    ``max_bin - 1`` most frequent categories get bins, the rest join the
    missing bin.
    """
    n, f = X.shape
    if n > sample_cnt:
        rng = np.random.default_rng(seed)
        idx = rng.choice(n, size=sample_cnt, replace=False)
        # sorted row gather: same sample set, sequential-ish memory access
        idx.sort()
        sample = X[idx]
    else:
        sample = X
    if isinstance(X, SparseColumn):
        if categorical_features:
            raise NotImplementedError(
                "a sparse feature column with categorical slots is not "
                "supported: pass dense rows")
        from ..core.profiler import get_profiler
        with get_profiler().region("bin.sparse_fit", rows=int(n),
                                   nnz=int(X.nnz), bytes=int(X.nbytes)):
            return _fit_sparse(sample, max_bin, min_data_in_bin)
    cat_set = set(int(c) for c in (categorical_features or []))
    for c in cat_set:
        if not 0 <= c < f:
            raise ValueError(
                f"categorical feature index {c} out of range [0, {f})")
    bounds: List[np.ndarray] = []
    has_missing = np.zeros(f, dtype=bool)
    categorical = np.zeros(f, dtype=bool)
    cat_values: List[Optional[np.ndarray]] = [None] * f
    for j in range(f):
        col = sample[:, j]
        nan = np.isnan(col)
        has_missing[j] = bool(nan.any())
        col = col[~nan]
        if j in cat_set:
            categorical[j] = True
            cat_values[j] = _find_categories(col, max_bin, j)
            bounds.append(np.empty(0, dtype=np.float64))
        else:
            bounds.append(_find_bounds(col, max_bin, min_data_in_bin))
    num_total_bins = max_bin + 1
    return BinMapper(upper_bounds=bounds, has_missing=has_missing,
                     num_total_bins=num_total_bins,
                     missing_bin=num_total_bins - 1,
                     categorical=categorical if cat_set else None,
                     cat_values=cat_values if cat_set else None)


def _fit_sparse(sample: SparseColumn, max_bin: int,
                min_data_in_bin: int) -> BinMapper:
    """:func:`fit_bin_mapper` on a sparse row sample: a column's bounds
    from its entries and the count of its zeros, never its dense
    column.  The same bounds as the dense rows give."""
    sn, f = sample.shape
    order = np.argsort(sample.indices, kind="stable")
    vals = sample.values[order]
    starts = np.zeros(f + 1, np.int64)
    np.cumsum(np.bincount(sample.indices, minlength=f), out=starts[1:])
    bounds: List[np.ndarray] = []
    has_missing = np.zeros(f, dtype=bool)
    for j in range(f):
        col = vals[starts[j]:starts[j + 1]]
        zeros = sn - col.size
        nan = np.isnan(col)
        if nan.any():
            has_missing[j] = True
            col = col[~nan]
        bounds.append(_bounds_of_sorted(np.sort(col), zeros, max_bin,
                                        min_data_in_bin))
    num_total_bins = max_bin + 1
    return BinMapper(upper_bounds=bounds, has_missing=has_missing,
                     num_total_bins=num_total_bins,
                     missing_bin=num_total_bins - 1)


def _find_categories(col: np.ndarray, max_bin: int, j: int) -> np.ndarray:
    if col.size and (col < 0).any():
        raise ValueError(
            f"Categorical feature {j} has negative values; categories must "
            "be non-negative integers (LightGBM contract)")
    ints = col.astype(np.int64)
    if col.size and not np.array_equal(ints, col):
        raise ValueError(
            f"Categorical feature {j} has non-integer values")
    vals, counts = np.unique(ints, return_counts=True)
    order = np.argsort(-counts, kind="stable")   # most frequent first
    return vals[order][:max_bin - 1].astype(np.int64)


def _find_bounds(col: np.ndarray, max_bin: int,
                 min_data_in_bin: int) -> np.ndarray:
    """One ``np.sort`` per column feeds BOTH the distinct-value census and
    the quantile cuts (``np.quantile``'s internal partition re-sorted every
    feature; on this box's single core that was ~40% of fit_bin_mapper).
    The quantile lerp reproduces ``np.quantile(..., method="linear")``
    bit-exactly, including its ``t >= 0.5`` rearrangement."""
    return _bounds_of_sorted(np.sort(col), 0, max_bin, min_data_in_bin)


def _bounds_of_sorted(s: np.ndarray, zeros: int, max_bin: int,
                      min_data_in_bin: int) -> np.ndarray:
    """Bounds of the column whose values are ``s`` (ascending) and
    ``zeros`` more zeros that ``s`` leaves out (a sparse column's; a
    dense column's ``s`` is all of it).  The zeros are counted where
    they sort, never written."""
    size = s.size + zeros
    if size == 0:
        return np.empty(0, dtype=np.float64)
    if s.size:
        change = np.empty(s.size, bool)
        change[0] = True
        np.not_equal(s[1:], s[:-1], out=change[1:])
        starts = np.nonzero(change)[0]
        distinct = s[starts]
        counts = np.diff(np.append(starts, s.size))
    else:
        distinct = np.empty(0, s.dtype)
        counts = np.empty(0, np.int64)
    # where the run of implicit zeros sits among the sorted values
    below = int(np.searchsorted(s, 0, side="left"))
    if zeros:
        at = int(np.searchsorted(distinct, 0, side="left"))
        if at < distinct.size and distinct[at] == 0:
            counts = counts.copy()
            counts[at] += zeros
        else:
            distinct = np.insert(distinct, at, 0)
            counts = np.insert(counts, at, zeros)
    if distinct.size <= 1:
        return np.empty(0, dtype=np.float64)
    if distinct.size <= max_bin:
        # Exact: midpoints between consecutive distinct values, but respect
        # min_data_in_bin by merging tiny bins (LightGBM does the same).
        mids = (distinct[:-1] + distinct[1:]) / 2.0
        if min_data_in_bin > 1 and size >= 2 * min_data_in_bin:
            keep, acc = [], 0
            for i in range(len(mids)):
                acc += counts[i]
                if acc >= min_data_in_bin:
                    keep.append(mids[i])
                    acc = 0
            mids = np.asarray(keep, dtype=np.float64)
        return np.asarray(mids, dtype=np.float64)

    def at_rank(k):
        """Value ``k`` of the whole sorted column."""
        if not zeros:
            return s[k]
        k = np.asarray(k)
        return np.where(k < below, s[np.minimum(k, s.size - 1)],
                        np.where(k < below + zeros, s.dtype.type(0),
                                 s[np.clip(k - zeros, 0, s.size - 1)]))

    # Quantile spacing over the empirical distribution.
    qs = np.linspace(0, 1, max_bin + 1)[1:-1]
    pos = qs * (size - 1)
    lo = pos.astype(np.int64)
    frac = pos - lo
    a = at_rank(lo)
    b = at_rank(np.minimum(lo + 1, size - 1))
    # np.quantile's _lerp: the diff stays in the COLUMN dtype, the lerp
    # itself promotes to float64 — fuzz-verified bit-exact for f32 and f64
    # columns (a pure-f64 lerp differs in the low bits on f32 columns)
    d = b - a
    cuts = np.where(frac >= 0.5, b - d * (1.0 - frac), a + d * frac)
    cuts = np.unique(cuts)
    return cuts.astype(np.float64)
