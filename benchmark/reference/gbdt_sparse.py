"""Plain reference for a fit cell whose rows are sparse: ``gbdt.py``'s
four checks worked out in numpy and float64 from the raw CSR rows.

It imports nothing of the program and knows nothing of bundles.  It is
given the raw rows as CSR (``indptr``, ``indices``, ``values``,
``shape``: a cell no entry names is 0), the labels, the bin the program's
mapper gave every ENTRY and every column's zeros (the table the timed
call was built from, before any bundling), and what the timed call
returned, as the LightGBM model text of the program's own export.  From
those, teacher-forced on the trees before each tree as ``gbdt.check_fit``
does:

(a) the rows that reach each node, by walking the RAW values down the
    exported real-valued thresholds, a split column read from the CSR
    (its entries, 0 elsewhere);
(b) each node's gradient and hessian sums, hence each leaf's value;
(c) at the sampled nodes the full (feature, bin) histogram of the rows in
    the node, a column's bins from its entries and the bin of its zeros
    from the node's totals less them, and the best admissible split;
(d) for a sample of columns, the bounds by the configuration's stated
    rule from the sample rows (zeros counted) and the bin of every entry
    and of zero, against the bins the program made.

A node's histogram holds a column's bins and no more: 4214 of the
configuration's 4228 columns have two, and a dense ``(4228, 256, 3)``
array a node (26 MB to clear, add up and search, 286 times a check) was
most of a check that took 145 s (PERF.md Findings, PR 33).  The columns
are laid out by the power of two that holds their bins (``Layout``); each
width's block is an ordinary ``(columns, width, 3)`` histogram that
``gbdt.best_split`` searches as it stands.

A bundling fault shows here as any other would: a value a bundle lost,
or a default bin not reconstituted, gives the program histograms, hence
splits, counts and leaves, that these sums do not give.

``precision="fp8"`` is the control: the split search and the leaves of a
learner whose gradients and hessians are rounded to float8_e4m3.
"""

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.reference import gbdt, gbdt_cat

NUM_BINS = 256
NOT_A_NUMBER = 1e30         # what a gap that is no number reads as
THREADS = min(12, os.cpu_count() or 1)


# ----------------------------------------------------------------- columns


def row_of_entry(X):
    return np.repeat(np.arange(X.shape[0], dtype=np.int32),
                     np.diff(X.indptr))


def columns(X, features, rows_of):
    """``{feature: (rows, entry ids)}`` of the asked columns' entries, in
    row order: one pass over the entries for all of them, a slice of the
    entries a thread."""
    feats = sorted(set(int(f) for f in features))
    slot = np.full(X.shape[1], len(feats), np.uint16)
    slot[feats] = np.arange(len(feats), dtype=np.uint16)
    edges = np.linspace(0, X.indices.size, THREADS + 1).astype(np.int64)

    def part(lo, hi):
        mine = slot[X.indices[lo:hi]]
        at = np.flatnonzero(mine < len(feats))
        mine = mine[at]
        order = np.argsort(mine, kind="stable")     # 16-bit keys: radix
        ends = np.cumsum(np.bincount(mine, minlength=len(feats)))
        return at[order] + lo, ends

    with ThreadPoolExecutor(THREADS) as pool:
        parts = list(pool.map(part, edges[:-1], edges[1:]))
    out = {}
    for k, f in enumerate(feats):
        at = np.concatenate([a[(e[k - 1] if k else 0):e[k]]
                             for a, e in parts])
        out[f] = (rows_of[at], at)
    return out


def dense_column(X, col, dtype=np.float32):
    rows, at = col
    out = np.zeros(X.shape[0], dtype)
    out[rows] = X.values[at]
    return out


# -------------------------------------------------------------------- walk


def column_at(X, col, rows):
    """One column's raw values at ``rows`` (ascending): its entries'
    values, 0 where it has none.  The shorter of the two row lists is
    looked up in the longer."""
    have, at = col
    if have.size == X.shape[0]:             # an entry in every row
        return X.values[at[rows]]
    out = np.zeros(rows.size, X.values.dtype)
    if not have.size or not rows.size:
        return out
    if have.size <= rows.size:
        pos = np.minimum(np.searchsorted(rows, have), rows.size - 1)
        hit = rows[pos] == have
        out[pos[hit]] = X.values[at[hit]]
    else:
        pos = np.minimum(np.searchsorted(have, rows), have.size - 1)
        hit = have[pos] == rows
        out[hit] = X.values[at[pos[hit]]]
    return out


def walk(tree, X, cols):
    """Leaf of every row, and the rows counted through each internal node
    (``x <= threshold`` goes left; a child ``c < 0`` is leaf ``~c``).  A
    node's children come after it, so one sweep in node order does, each
    node handing its rows (ascending) to its two children."""
    n = X.shape[0]
    if tree["num_leaves"] == 1:
        return np.zeros(n, np.int64), np.zeros(0, np.int64)
    feat, thr = tree["split_feature"], tree["threshold"]
    leaf_of_row = np.empty(n, np.int64)
    internal_count = np.zeros(len(feat), np.int64)
    rows_at = {0: np.arange(n, dtype=np.int32)}
    for i in range(len(feat)):
        rows = rows_at.pop(i)
        internal_count[i] = rows.size
        x = column_at(X, cols[int(feat[i])], rows)
        go_left = x.astype(np.float64) <= thr[i]
        for child, mine in ((tree["left"][i], rows[go_left]),
                            (tree["right"][i], rows[~go_left])):
            if child < 0:
                leaf_of_row[mine] = ~child
            else:
                rows_at[int(child)] = mine
    return leaf_of_row, internal_count


# -------------------------------------------------------------- histograms


def _build_native():
    """``hist_sparse.c`` compiled once into ``<checkout>/.bench_build``;
    None where that cannot be done."""
    import ctypes
    import hashlib
    import shutil
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "hist_sparse.c")
    with open(src, "rb") as fh:
        tag = hashlib.sha256(fh.read()).hexdigest()[:12]
    out_dir = os.path.join(os.path.dirname(os.path.dirname(here)),
                           ".bench_build")
    lib = os.path.join(out_dir, f"hist_sparse_{tag}.so")
    try:
        if not os.path.exists(lib):
            os.makedirs(out_dir, exist_ok=True)
            tmp = f"{lib}.{os.getpid()}.tmp"
            cc = next(c for c in ("cc", "gcc", "clang") if shutil.which(c))
            subprocess.run([cc, "-O2", "-shared", "-fPIC", "-o", tmp, src],
                           check=True, capture_output=True, timeout=120)
            os.replace(tmp, lib)
        fn = ctypes.CDLL(lib).node_hist_sparse
    except (OSError, StopIteration, subprocess.SubprocessError) as e:
        print(f"[reference] no native sparse histogram loop ({e}); using "
              "numpy", file=sys.stderr)
        return None
    fn.restype = None
    fn.argtypes = [ctypes.c_void_p] * 4 + [ctypes.c_int64] \
        + [ctypes.c_void_p] * 4
    return fn


_NATIVE = []


class Layout:
    """Where every column's bins lie in a node's one ``(cells, 3)``
    histogram.  A column uses the bins up to the highest that an entry
    of it, or its zeros, were given; columns are grouped by the power of
    two that holds those (at least 2), ascending within a group, so each
    group is a plain ``(columns, width, 3)`` block.  ``base[j]``: the
    cell of column ``j``'s bin 0."""

    def __init__(self, X, entry_bins, zero_bin):
        F = X.shape[1]
        edges = np.linspace(0, X.indices.size,
                            4 * THREADS + 1).astype(np.int64)

        def seen(lo, hi):
            key = X.indices[lo:hi].astype(np.int64) * NUM_BINS \
                + entry_bins[lo:hi]
            return np.bincount(key, minlength=F * NUM_BINS) > 0

        with ThreadPoolExecutor(THREADS) as pool:
            used = np.logical_or.reduce(
                list(pool.map(seen, edges[:-1], edges[1:])))
        used = used.reshape(F, NUM_BINS)
        used[np.arange(F), zero_bin] = True
        bins = NUM_BINS - np.argmax(used[:, ::-1], axis=1)
        width = np.maximum(2, 1 << np.ceil(np.log2(bins)).astype(np.int64))
        self.order = np.argsort(width, kind="stable")
        self.starts = np.cumsum(width[self.order]) - width[self.order]
        self.base = np.empty(F, np.int64)
        self.base[self.order] = self.starts
        self.cells = int(width.sum())
        self.zero_cell = self.base + np.asarray(zero_bin, np.int64)
        self.groups = []        # (columns, width, first cell, end cell)
        for w in np.unique(width):
            cols = self.order[width[self.order] == w]
            lo = int(self.base[cols[0]])
            self.groups.append((cols, int(w), lo, lo + int(w) * cols.size))

    def blocks(self, hist):
        """``(columns, (len(columns), width, 3) view)`` of each group."""
        for cols, w, lo, hi in self.groups:
            yield cols, hist[lo:hi].reshape(cols.size, w, 3)

    def column(self, hist, f):
        """``(width, 3)``: one column's bins."""
        for cols, w, lo, hi in self.groups:
            k = int(np.searchsorted(cols, f))
            if k < cols.size and cols[k] == f:
                return hist[lo + k * w:lo + (k + 1) * w]
        raise KeyError(f)


_POOL = []


def node_histogram(X, entry_bins, layout, rows, g, h, native=True):
    """``(layout.cells, 3)`` float64 sums of (g, h, 1) over ``rows`` (all
    rows for None): every column's bins from its entries, and the bin of
    its zeros from the node's totals less what its entries hold.  The
    loop is hist_sparse.c's, over slices of a large node's rows in
    threads; without a compiler, or with ``native=False``, one
    ``bincount`` a channel gives the same sums."""
    if not _NATIVE:
        _NATIVE.append(_build_native())
        _POOL.append(ThreadPoolExecutor(THREADS))
    fn = _NATIVE[0] if native else None
    g = np.ascontiguousarray(g, np.float64)
    h = np.ascontiguousarray(h, np.float64)
    idx = None if rows is None else np.ascontiguousarray(rows, np.int64)
    m = X.shape[0] if idx is None else idx.size
    if fn is not None and entry_bins.dtype == np.uint8 \
            and X.indices.dtype == np.int32 and X.indptr.dtype == np.int64:
        if idx is None:
            idx = np.arange(m, dtype=np.int64)
        parts = [p for p in np.array_split(
            idx, THREADS if m >= 1 << 16 else 1) if p.size]

        def one(p):
            out = np.zeros((layout.cells, 3), np.float64)
            fn(X.indptr.ctypes.data, X.indices.ctypes.data,
               entry_bins.ctypes.data, p.ctypes.data, p.size,
               g.ctypes.data, h.ctypes.data, layout.base.ctypes.data,
               out.ctypes.data)
            return out

        hist = sum(_POOL[0].map(one, parts) if len(parts) > 1
                   else map(one, parts),
                   np.zeros((layout.cells, 3), np.float64))
    else:
        if idx is None:
            at = slice(0, X.indices.size)
            r = row_of_entry(X)
        else:
            lens = X.indptr[idx + 1] - X.indptr[idx]
            starts = np.cumsum(lens) - lens
            at = (np.arange(int(lens.sum())) - np.repeat(starts, lens)
                  + np.repeat(X.indptr[idx], lens))
            r = np.repeat(idx, lens)
        key = layout.base[X.indices[at]] + entry_bins[at]
        size = layout.cells
        hist = np.stack([np.bincount(key, weights=g[r], minlength=size),
                         np.bincount(key, weights=h[r], minlength=size),
                         np.bincount(key, minlength=size).astype(np.float64)],
                        axis=-1)
    sel = slice(None) if rows is None else rows
    total = np.array([g[sel].sum(), h[sel].sum(), float(m)])
    held = np.empty((X.shape[1], 3), np.float64)
    held[layout.order] = np.add.reduceat(hist, layout.starts, axis=0)
    hist[layout.zero_cell] += total[None, :] - held
    return hist


def best_split(hist, layout, min_sum_hessian, min_data):
    """``gbdt.best_split`` over every group of the layout: ``(gain,
    feature, bin)``, the lower column on equal gains as one search of
    all columns would give.  Bins past a column's own are empty, so a
    threshold there leaves nothing on its right and is not admissible."""
    best = None
    for cols, block in layout.blocks(hist):
        gain, k, b = gbdt.best_split(block, min_sum_hessian, min_data)
        f = int(cols[k])
        if best is None or gain > best[0] or (gain == best[0]
                                              and f < best[1]):
            best = (gain, f, b)
    return best


# ---------------------------------------------------------------- binning


def check_bins(X, entry_bins, zero_bin, features, cols, binning, max_bin):
    """Cells of the sampled columns whose bin differs: every entry's, and
    the zeros' counted once a row without entry."""
    n = X.shape[0]
    idx = gbdt.bin_sample_rows(n, binning)
    bad = 0
    for f in features:
        rows, at = cols[f]
        col = dense_column(X, cols[f], X.values.dtype)
        bounds = gbdt_cat.bin_bounds(col if idx is None else col[idx],
                                     max_bin, binning["min_data_in_bin"])
        mine = np.searchsorted(bounds, X.values[at].astype(np.float64),
                               side="left")
        bad += int(np.count_nonzero(mine != entry_bins[at]))
        if int(np.searchsorted(bounds, 0.0, side="left")) != zero_bin[f]:
            bad += n - rows.size
    return bad


# ------------------------------------------------------------- the check


def check_fit(model_text, X, y, entry_bins, zero_bin, cfg, *, seed,
              expect_trees, sample_nodes, sample_features,
              precision="float64"):
    """Every number the comparison holds, for one returned fit; the names
    are ``gbdt.check_fit``'s.  ``X``: the CSR rows (``indptr``,
    ``indices``, ``values``, ``shape``); ``entry_bins``: the program's
    bin of every entry; ``zero_bin``: of every column's zeros.  With
    ``precision="fp8"`` the split gaps and the leaf gap are the
    control's."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    trees = gbdt.parse_model(model_text)
    lr = cfg["learning_rate"]
    admissible = (cfg["min_sum_hessian"], cfg["min_data"])
    n = X.shape[0]
    zero_bin = np.asarray(zero_bin, np.int64)
    rows_of = row_of_entry(X)
    feats = sorted(int(f) for f in np.random.default_rng(
        [int(seed), 0xB1]).choice(X.shape[1], size=min(
            sample_features, X.shape[1]), replace=False))
    used = set(feats)
    for tree in trees:
        if tree["num_leaves"] > 1:
            used.update(int(f) for f in tree["split_feature"])
    cols = columns(X, used, rows_of)
    layout = Layout(X, entry_bins, zero_bin)
    score = np.full(n, gbdt.init_score(y), np.float64)
    count_bad = 0
    leaf_gap = 0.0
    gain_gap = 0.0
    gaps, gap_at = [], []
    rows_histogrammed = 0
    for t, tree in enumerate(trees):
        leaf_of_row, internal_count = walk(tree, X, cols)
        g, h = gbdt.grad_hess(score, y)
        (gl, gi), (hl, hi) = gbdt.node_sums(tree, leaf_of_row, g, h)
        L = tree["num_leaves"]
        bias = score[0] if t == 0 else 0.0
        with np.errstate(invalid="ignore", divide="ignore"):
            step = -gl / hl * lr
        got = tree["leaf_value"] - bias
        scale = np.maximum(np.abs(step), np.median(np.abs(step)))
        if precision == "fp8":
            g_low, h_low = gbdt.round_fp8(g), gbdt.round_fp8(h)
            # the leaves the other learner would have given this tree
            (gl_low, _), (hl_low, _) = gbdt.node_sums(
                tree, leaf_of_row, g_low, h_low)
            got = -gl_low / hl_low * lr
        with np.errstate(invalid="ignore", divide="ignore"):
            off = np.abs(got - step) / scale
        # a leaf that is not a number (an empty leaf's 0 / 0) is no answer
        leaf_gap = max(leaf_gap, float(np.max(
            np.where(np.isfinite(off), off, NOT_A_NUMBER))))
        if L > 1:
            leaf_count = np.bincount(leaf_of_row, minlength=L)
            count_bad += int(np.count_nonzero(
                leaf_count != tree["leaf_count"]))
            count_bad += int(np.count_nonzero(
                internal_count != tree["internal_count"]))

            def tot(c, a_leaf, a_int):
                return a_leaf[~c] if c < 0 else a_int[c]

            chosen = np.empty(L - 1, np.float64)
            for i in range(L - 1):
                lc, rc = tree["left"][i], tree["right"][i]
                chosen[i] = (
                    gbdt.leaf_gain(tot(lc, gl, gi), tot(lc, hl, hi))
                    + gbdt.leaf_gain(tot(rc, gl, gi), tot(rc, hl, hi))
                    - gbdt.leaf_gain(gi[i], hi[i]))
            rel = np.abs(tree["split_gain"] - chosen) / np.maximum(
                chosen, np.median(chosen))
            gain_gap = max(gain_gap, float(rel.max()))
            # the sampled nodes are gbdt.check_fit's: most in the last
            # tree, its root among them, an eighth as many before it
            last = t == len(trees) - 1
            k = min(sample_nodes if last else sample_nodes // 8, L - 2)
            picks = sorted(int(i) for i in rng.choice(
                np.arange(1, L - 1), size=k, replace=False)) if k > 0 else []
            if last:
                picks = [0] + picks
            by_leaf = np.argsort(leaf_of_row, kind="stable")
            leaf_end = np.cumsum(leaf_count)
            leaves_under = [None] * (L - 1)
            for i in range(L - 2, -1, -1):
                leaves_under[i] = [
                    leaf for c in (tree["left"][i], tree["right"][i])
                    for leaf in ([~c] if c < 0 else leaves_under[c])]
            for i in picks:
                rows = None if i == 0 else gbdt_cat.node_rows(
                    leaves_under[i], leaf_of_row, by_leaf, leaf_count,
                    leaf_end)
                rows_histogrammed += n if rows is None else rows.size
                exact = node_histogram(X, entry_bins, layout, rows, g, h)
                best = best_split(exact, layout, *admissible)[0]
                if precision == "fp8":
                    # the gap of the split the lower precision puts
                    # first, read on the exact histogram
                    _, f, b = best_split(
                        node_histogram(X, entry_bins, layout, rows,
                                       g_low, h_low), layout, *admissible)
                    mine = gbdt.split_gain_at(
                        layout.column(exact, f)[None], 0, b)
                else:
                    mine = chosen[i]
                gaps.append(1.0 if not np.isfinite(mine) else
                            max(0.0, (best - mine) / best) if best > 0
                            else 0.0)
                gap_at.append((t, i))
        score = score + (tree["leaf_value"][leaf_of_row] - bias)
    worst = int(np.argmax(gaps)) if gaps else None
    return {
        "tree_count_gap": abs(len(trees) - expect_trees),
        "count_mismatch": count_bad,
        "bin_mismatch": check_bins(X, entry_bins, zero_bin, feats, cols,
                                   cfg["binning"], cfg["max_bin"]),
        "leaf_value_gap": leaf_gap,
        "split_gap_mean": float(np.mean(gaps)) if gaps else 0.0,
        # read beside them, not compared
        "split_gain_gap": gain_gap,
        "split_gap": gaps[worst] if gaps else 0.0,
        "split_gap_at": gap_at[worst] if gaps else None,
        "split_flip_share": float(np.mean(np.asarray(gaps) > gbdt.FLIP))
        if gaps else 0.0,
        "nodes_compared": len(gaps),
        "rows_histogrammed": rows_histogrammed,
        "leaves": [int(t["num_leaves"]) for t in trees],
        "positive_share": float(np.mean(y)),
    }
