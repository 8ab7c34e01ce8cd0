"""Plain reference for the fit cells: what a leaf-wise histogram GBDT has
to have produced, worked out in numpy and float64 from the raw rows.

It imports nothing of the program.  It is given what the timed call was
given (the raw float32 rows and labels, and the uint8 bins that
``engine.train`` took as its input) and what the timed call returned, as
the LightGBM model text of the program's own export.  From those it works
out, for every tree of the fit, teacher-forced on the trees before it:

* the rows that reach each node, by walking the RAW values down the
  exported real-valued thresholds (so the export, the partition and the
  binning all have to agree for the counts to match);
* each node's gradient and hessian sums from its own scores (binary log
  loss, boost-from-average), hence each leaf's value;
* at a sample of nodes, the root among them, the full (feature, bin)
  histogram of the rows in the node and the best admissible split, hence
  the gap by which the split the program chose lies below the best;
* for a sample of features, the bin bounds by the configuration's stated
  rule and the bin of every row, against the bins the program made.

``precision="fp8"`` is the control: the same best-split search with the
gradients and hessians rounded to float8_e4m3, the nearest precision
below the bfloat16 operands the configuration states.  It returns the gap
of the split that the lower precision would have put first.
"""

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

FEATURE_BLOCK = 32          # features a thread takes at a time (numpy)
NATIVE_BLOCK = 128          # ... (hist.c: 128 x 256 x 3 doubles sit in L2)
FLIP = 1e-9                 # a relative gain gap above float64 round-off


# ------------------------------------------------------------ model text


def parse_model(text):
    """The trees of a LightGBM v3 model text, as dicts of arrays."""
    body = text.split("end of trees")[0]
    trees = []
    for chunk in body.split("Tree=")[1:]:
        kv = {}
        for line in chunk.splitlines()[1:]:
            if "=" in line:
                k, _, v = line.partition("=")
                kv[k.strip()] = v.strip()

        def arr(key, dtype):
            return np.array(kv[key].split(), dtype=dtype) if kv.get(key) \
                else np.zeros(0, dtype)

        num_leaves = int(kv["num_leaves"])
        if int(kv.get("num_cat", 0)):
            raise ValueError("categorical splits are outside this reference")
        tree = {"num_leaves": num_leaves,
                "leaf_value": arr("leaf_value", np.float64)}
        if num_leaves > 1:
            tree.update(
                split_feature=arr("split_feature", np.int64),
                threshold=arr("threshold", np.float64),
                split_gain=arr("split_gain", np.float64),
                left=arr("left_child", np.int64),
                right=arr("right_child", np.int64),
                leaf_count=arr("leaf_count", np.int64),
                internal_count=arr("internal_count", np.int64))
        trees.append(tree)
    return trees


# ------------------------------------------------------------------ walk


def walk(tree, X):
    """Leaf of every row, and the rows counted through each internal node.

    ``x <= threshold`` goes left; a child ``c < 0`` is leaf ``~c``."""
    n = X.shape[0]
    if tree["num_leaves"] == 1:
        return np.zeros(n, np.int64), np.zeros(0, np.int64)
    feat, thr = tree["split_feature"], tree["threshold"]
    left, right = tree["left"], tree["right"]
    node = np.zeros(n, np.int64)
    rows = np.arange(n)
    internal_count = np.zeros(len(feat), np.int64)
    while rows.size:
        at = node[rows]
        internal_count += np.bincount(at, minlength=len(feat))
        go_left = X[rows, feat[at]] <= thr[at]
        nxt = np.where(go_left, left[at], right[at])
        node[rows] = nxt
        rows = rows[nxt >= 0]
    return ~node, internal_count


def node_sums(tree, leaf_of_row, *weights):
    """Per-leaf and per-internal-node sums of each weight vector."""
    L = tree["num_leaves"]
    out = []
    for w in weights:
        leaf = np.bincount(leaf_of_row, weights=w, minlength=L)
        internal = np.zeros(max(L - 1, 0), np.float64)

        def total(c, leaf=leaf, internal=internal):
            return leaf[~c] if c < 0 else internal[c]

        # children are always created after their parent, so a reverse
        # sweep meets every child before its parent
        for i in range(L - 2, -1, -1):
            internal[i] = (total(tree["left"][i]) + total(tree["right"][i]))
        out.append((leaf, internal))
    return out


# ------------------------------------------------------------ objective


def init_score(y):
    p = float(np.mean(y))
    return float(np.log(p / (1.0 - p)))


def grad_hess(score, y):
    p = 1.0 / (1.0 + np.exp(-score))
    return p - y, p * (1.0 - p)


def leaf_gain(g, h, l2=0.0):
    return g * g / (h + l2)


# ------------------------------------------------------------ histograms


def round_fp8(a):
    import ml_dtypes
    return a.astype(np.float32).astype(ml_dtypes.float8_e4m3fn).astype(
        np.float64)


def _build_native():
    """The C loop of hist.c, compiled once into ``<checkout>/.bench_build``
    (a fixed path inside the checkout); None where that cannot be done."""
    import ctypes
    import hashlib
    import shutil
    import subprocess

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "hist.c")
    with open(src, "rb") as fh:
        tag = hashlib.sha256(fh.read()).hexdigest()[:12]
    out_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".bench_build")
    lib = os.path.join(out_dir, f"hist_{tag}.so")
    try:
        if not os.path.exists(lib):
            os.makedirs(out_dir, exist_ok=True)
            tmp = f"{lib}.{os.getpid()}.tmp"
            cc = next(c for c in ("cc", "gcc", "clang") if shutil.which(c))
            subprocess.run([cc, "-O2", "-shared", "-fPIC", "-o", tmp, src],
                           check=True, capture_output=True, timeout=120)
            os.replace(tmp, lib)
        fn = ctypes.CDLL(lib).node_hist
    except (OSError, StopIteration, subprocess.SubprocessError) as e:
        print(f"[reference] no native histogram loop ({e}); using numpy",
              file=sys.stderr)
        return None
    fn.restype = None
    fn.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                   ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                   ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                   ctypes.c_void_p]
    return fn


_NATIVE = []


def node_histogram(bins, rows, g, h, num_bins=256, threads=None,
                   native=True):
    """``(F, num_bins, 3)`` float64 sums of (g, h, 1) over ``rows`` (all
    rows for None), blocks of features in threads.  The loop is hist.c's;
    without a compiler, or with ``native=False``, one ``bincount`` per
    feature and channel gives the same sums."""
    if not _NATIVE:
        _NATIVE.append(_build_native())
    fn = _NATIVE[0] if native else None
    F = bins.shape[1]
    hist = np.zeros((F, num_bins, 3), np.float64)
    threads = threads or min(8, os.cpu_count() or 1)
    if fn is not None and bins.dtype == np.uint8 and num_bins >= 256 \
            and bins.flags.c_contiguous and len(g) == len(h) == len(bins):
        g = np.ascontiguousarray(g, np.float64)
        h = np.ascontiguousarray(h, np.float64)
        idx = None if rows is None else np.ascontiguousarray(rows, np.int64)
        m = bins.shape[0] if idx is None else idx.size
        if idx is not None and m and not (
                0 <= int(idx.min()) and int(idx.max()) < bins.shape[0]):
            raise ValueError("row index outside the table")

        def block(f0):
            f1 = min(f0 + NATIVE_BLOCK, F)
            fn(bins.ctypes.data, F, None if idx is None else idx.ctypes.data,
               m, g.ctypes.data, h.ctypes.data, f0, f1, num_bins,
               hist[f0:f1].ctypes.data)

        step = NATIVE_BLOCK
    else:
        sub = bins if rows is None else bins[rows]
        gs = g if rows is None else g[rows]
        hs = h if rows is None else h[rows]

        def block(f0):
            cols = np.ascontiguousarray(sub[:, f0:f0 + FEATURE_BLOCK].T)
            for j, col in enumerate(cols):
                col = col.astype(np.intp)
                hist[f0 + j, :, 0] = np.bincount(col, weights=gs,
                                                 minlength=num_bins)
                hist[f0 + j, :, 1] = np.bincount(col, weights=hs,
                                                 minlength=num_bins)
                hist[f0 + j, :, 2] = np.bincount(col, minlength=num_bins)

        step = FEATURE_BLOCK
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(block, range(0, F, step)))
    return hist


def best_split(hist, min_sum_hessian, min_data, l2=0.0):
    """``(gain, feature, bin)`` of the best admissible ``bin <= b`` split
    (LightGBM's FindBestThreshold: both sides hold ``min_data`` rows and
    ``min_sum_hessian`` of hessian; the last bin cannot be a threshold).
    """
    cum = np.cumsum(hist, axis=1)
    tot = cum[0, -1]
    gl, hl, cl = cum[..., 0], cum[..., 1], cum[..., 2]
    gr, hr, cr = tot[0] - gl, tot[1] - hl, tot[2] - cl
    ok = ((cl >= max(min_data, 1)) & (cr >= max(min_data, 1))
          & (hl >= min_sum_hessian) & (hr >= min_sum_hessian))
    ok[:, -1] = False
    with np.errstate(divide="ignore", invalid="ignore"):
        gains = (leaf_gain(gl, hl, l2) + leaf_gain(gr, hr, l2)
                 - leaf_gain(tot[0], tot[1], l2))
    gains = np.where(ok, gains, -np.inf)
    flat = int(np.argmax(gains))
    f, b = divmod(flat, hist.shape[1])
    return float(gains[f, b]), f, b


def split_gain_at(hist, f, b, l2=0.0):
    """Gain of the split ``bin <= b`` on feature ``f``, admissible or not."""
    c = np.cumsum(hist[f], axis=0)
    tot = c[-1]
    return float(leaf_gain(c[b, 0], c[b, 1], l2)
                 + leaf_gain(tot[0] - c[b, 0], tot[1] - c[b, 1], l2)
                 - leaf_gain(tot[0], tot[1], l2))


# ---------------------------------------------------------------- binning


def bin_bounds(col_sample, max_bin):
    """Upper bounds by the configuration's rule (quantiles at k/max_bin),
    or midpoints between the distinct values where those are few."""
    distinct = np.unique(col_sample)
    if distinct.size <= 1:
        return np.empty(0, np.float64)
    if distinct.size <= max_bin:
        raise ValueError("few-valued features are outside this reference")
    qs = np.linspace(0, 1, max_bin + 1)[1:-1]
    return np.unique(np.quantile(col_sample, qs).astype(np.float64))


def bin_sample_rows(rows, binning):
    if rows <= binning["sample_rows"]:
        return None
    idx = np.random.default_rng(binning["seed"]).choice(
        rows, size=binning["sample_rows"], replace=False)
    idx.sort()
    return idx


def check_bins(X, bins, features, binning, max_bin):
    """Cells of the sampled feature columns whose bin differs."""
    idx = bin_sample_rows(X.shape[0], binning)
    bad = 0
    for f in features:
        col = X[:, f]
        bounds = bin_bounds(col if idx is None else col[idx], max_bin)
        mine = np.searchsorted(bounds, col.astype(np.float64), side="left")
        bad += int(np.count_nonzero(mine != bins[:, f]))
    return bad


# ------------------------------------------------------------- the check


def check_fit(model_text, X, y, bins, cfg, *, seed, expect_trees,
              sample_nodes, sample_features, precision="float64"):
    """Every number the comparison holds, for one returned fit.

    ``cfg``: ``learning_rate``, ``min_sum_hessian``, ``min_data``,
    ``max_bin``, ``binning``.  Returns a dict of plain numbers; with
    ``precision="fp8"`` the split gaps are the control's."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    trees = parse_model(model_text)
    lr = cfg["learning_rate"]
    admissible = (cfg["min_sum_hessian"], cfg["min_data"])
    score = np.full(X.shape[0], init_score(y), np.float64)
    count_bad = 0
    leaf_gap = 0.0
    gain_gap = 0.0
    gaps, gap_at = [], []
    rows_histogrammed = 0
    for t, tree in enumerate(trees):
        leaf_of_row, internal_count = walk(tree, X)
        g, h = grad_hess(score, y)
        (gl, gi), (hl, hi) = node_sums(tree, leaf_of_row, g, h)
        L = tree["num_leaves"]
        # leaf values: shrunk Newton step; the first tree carries the
        # boost-from-average bias in its leaves (LightGBM's AddBias)
        bias = score[0] if t == 0 else 0.0
        step = -gl / hl * lr
        got = tree["leaf_value"]
        scale = np.maximum(np.abs(step), np.median(np.abs(step)))
        leaf_gap = max(leaf_gap,
                       float(np.max(np.abs(got - bias - step) / scale)))
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
                chosen[i] = (leaf_gain(tot(lc, gl, gi), tot(lc, hl, hi))
                             + leaf_gain(tot(rc, gl, gi), tot(rc, hl, hi))
                             - leaf_gain(gi[i], hi[i]))
            rel = np.abs(tree["split_gain"] - chosen) / np.maximum(
                chosen, np.median(chosen))
            gain_gap = max(gain_gap, float(rel.max()))
            # sampled nodes, drawn from the seed: most in the last tree,
            # with its root (the node with most rows), since its
            # gradients are not the two constants that the first tree's
            # are (those are exact in any float, so no precision flips a
            # split there); an eighth as many in each tree before it
            last = t == len(trees) - 1
            k = min(sample_nodes if last else sample_nodes // 8, L - 2)
            picks = sorted(int(i) for i in rng.choice(
                np.arange(1, L - 1), size=k, replace=False)) if k > 0 else []
            if last:
                picks = [0] + picks
            # rows of each internal node: descend from the leaves
            parent = np.full(L - 1, -1, np.int64)
            leaf_parent = np.empty(L, np.int64)
            for i in range(L - 1):
                for c in (tree["left"][i], tree["right"][i]):
                    if c < 0:
                        leaf_parent[~c] = i
                    else:
                        parent[c] = i
            if precision == "fp8":
                g_low, h_low = round_fp8(g), round_fp8(h)
            for i in picks:
                under = np.zeros(L - 1, bool)
                under[i] = True
                for j in range(i + 1, L - 1):      # parents come first
                    under[j] = parent[j] >= 0 and under[parent[j]]
                rows = None if i == 0 else np.nonzero(
                    under[leaf_parent[leaf_of_row]])[0]
                rows_histogrammed += X.shape[0] if rows is None else rows.size
                exact = node_histogram(bins, rows, g, h)
                best = best_split(exact, *admissible)[0]
                if precision == "fp8":
                    # the gap of the split the lower precision puts
                    # first, read on the exact histogram
                    _, f, b = best_split(
                        node_histogram(bins, rows, g_low, h_low), *admissible)
                    mine = split_gain_at(exact, f, b)
                else:
                    mine = chosen[i]
                gaps.append(max(0.0, (best - mine) / best) if best > 0
                            else 0.0)
                gap_at.append((t, i))
        score = score + (got[leaf_of_row] - bias)
    worst = int(np.argmax(gaps)) if gaps else None
    feats = sorted(int(f) for f in rng.choice(
        X.shape[1], size=min(sample_features, X.shape[1]), replace=False))
    return {
        "tree_count_gap": abs(len(trees) - expect_trees),
        "count_mismatch": count_bad,
        "bin_mismatch": check_bins(X, bins, feats, cfg["binning"],
                                   cfg["max_bin"]),
        "leaf_value_gap": leaf_gap,
        "split_gap_mean": float(np.mean(gaps)) if gaps else 0.0,
        # read beside them, not compared: tried and found not to separate
        # the control (PERF.md), or bookkeeping
        "split_gain_gap": gain_gap,
        "split_gap": gaps[worst] if gaps else 0.0,
        "split_gap_at": gap_at[worst] if gaps else None,
        "split_flip_share": float(np.mean(np.asarray(gaps) > FLIP))
        if gaps else 0.0,
        "nodes_compared": len(gaps),
        "rows_histogrammed": rows_histogrammed,
    }
