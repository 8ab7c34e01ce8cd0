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

#: a categorical column whose binned values stay under this is binned by
#: table lookup (64 M int32 slots at most), above it by binary search
_CAT_LUT_MAX = 1 << 26
#: rows a thread bins at a time in ``transform_packed``'s categorical pass
_CAT_BLOCK_ROWS = 1 << 20


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
        """
        ub = self.upper_bounds[feature]
        if bin_idx >= len(ub):
            # split isolating the top/missing bin: everything finite goes left
            return np.inf
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
    if col.size == 0:
        return np.empty(0, dtype=np.float64)
    s = np.sort(col)
    change = np.empty(s.size, bool)
    change[0] = True
    np.not_equal(s[1:], s[:-1], out=change[1:])
    starts = np.nonzero(change)[0]
    if starts.size <= 1:
        return np.empty(0, dtype=np.float64)
    if starts.size <= max_bin:
        # Exact: midpoints between consecutive distinct values, but respect
        # min_data_in_bin by merging tiny bins (LightGBM does the same).
        distinct = s[starts]
        counts = np.diff(np.append(starts, s.size))
        mids = (distinct[:-1] + distinct[1:]) / 2.0
        if min_data_in_bin > 1 and col.size >= 2 * min_data_in_bin:
            keep, acc = [], 0
            for i in range(len(mids)):
                acc += counts[i]
                if acc >= min_data_in_bin:
                    keep.append(mids[i])
                    acc = 0
            mids = np.asarray(keep, dtype=np.float64)
        return np.asarray(mids, dtype=np.float64)
    # Quantile spacing over the empirical distribution.
    qs = np.linspace(0, 1, max_bin + 1)[1:-1]
    pos = qs * (s.size - 1)
    lo = pos.astype(np.int64)
    frac = pos - lo
    a = s[lo]
    b = s[np.minimum(lo + 1, s.size - 1)]
    # np.quantile's _lerp: the diff stays in the COLUMN dtype, the lerp
    # itself promotes to float64 — fuzz-verified bit-exact for f32 and f64
    # columns (a pure-f64 lerp differs in the low bits on f32 columns)
    d = b - a
    cuts = np.where(frac >= 0.5, b - d * (1.0 - frac), a + d * frac)
    cuts = np.unique(cuts)
    return cuts.astype(np.float64)
