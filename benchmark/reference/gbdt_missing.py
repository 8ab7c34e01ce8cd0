"""Plain reference for a fit cell whose table holds missing values (NaN):
``gbdt.py``'s four checks, with LightGBM's rule for a feature of missing
type NaN.

It imports nothing of the program, and takes ``gbdt.py``'s objective,
histogram and sampling helpers.  Given what the timed call was given (the
raw float32 rows, NaN included, the labels and the uint8 bins) and the
forest as the program's own LightGBM model text, it works out, for every
tree, teacher-forced on the trees before it:

* the rows that reach each node, by walking the RAW values down the
  exported thresholds and ``decision_type`` bits: a NaN goes by the
  default_left bit (bit 1) where the missing type (bits 2-3) is NaN, and
  right otherwise; every other value goes left iff ``x <= threshold``;
* each node's gradient and hessian sums from its own scores (binary log
  loss, boost-from-average), hence each leaf's value;
* at a sample of nodes, the full (feature, bin) histogram, NaN in the
  trailing (missing) bin, and the best admissible split over BOTH
  default directions in float64, hence the gap by which the program's
  split lies below it;
* for a sample of features, the bin bounds by the configuration's rule
  over the sample's present values and the bin of every row, NaN to the
  missing bin, against the bins the program made.

The split rule (``configs/bosch_missing.json`` states it): missing right,
left = bins <= b, as ``gbdt.best_split``; missing left, left = bins <= b
plus the missing bin, scored where the node holds missing rows and the
right side keeps a row; the missing bin is never a threshold.  The better
of the two wins (gains compared, so no tie rule enters a number here).

``precision="fp8"`` is ``gbdt.py``'s control: the same search with the
gradients and hessians in float8_e4m3, read as the gap of the split it
would put first.
"""

import numpy as np

from benchmark.reference import gbdt

MISSING_NAN = 2             # decision_type bits 2-3: missing type NaN


# ------------------------------------------------------------ model text


def parse_model(text):
    """``gbdt.parse_model``'s trees, each with its ``decision_type``."""
    trees = gbdt.parse_model(text)
    body = text.split("end of trees")[0]
    for tree, chunk in zip(trees, body.split("Tree=")[1:]):
        if tree["num_leaves"] <= 1:
            continue
        for line in chunk.splitlines():
            if line.startswith("decision_type="):
                tree["decision_type"] = np.array(
                    line.partition("=")[2].split(), np.int64)
    return trees


def nan_goes_left(decision_type):
    """Where a numeric node sends NaN LEFT: its default_left bit, where
    its missing type is NaN."""
    dt = np.asarray(decision_type, np.int64)
    return ((dt & 2) > 0) & (((dt >> 2) & 3) == MISSING_NAN)


# ------------------------------------------------------------------ walk


def walk(tree, X):
    """``gbdt.walk`` with NaN sent by each node's default direction."""
    n = X.shape[0]
    if tree["num_leaves"] == 1:
        return np.zeros(n, np.int64), np.zeros(0, np.int64)
    feat, thr = tree["split_feature"], tree["threshold"]
    left, right = tree["left"], tree["right"]
    nan_left = nan_goes_left(tree["decision_type"])
    node = np.zeros(n, np.int64)
    rows = np.arange(n)
    internal_count = np.zeros(len(feat), np.int64)
    while rows.size:
        at = node[rows]
        internal_count += np.bincount(at, minlength=len(feat))
        x = X[rows, feat[at]].astype(np.float64)
        go_left = np.where(np.isnan(x), nan_left[at], x <= thr[at])
        nxt = np.where(go_left, left[at], right[at])
        node[rows] = nxt
        rows = rows[nxt >= 0]
    return ~node, internal_count


# ------------------------------------------------------------ split search


def split_gains(hist, min_sum_hessian, min_data, l2=0.0):
    """``(2, F, B)`` gains of every admissible split: ``[0]`` missing
    right, ``[1]`` missing left; -inf where not admissible."""
    cum = np.cumsum(hist, axis=1)
    tot = cum[0, -1]
    nan = hist[:, -1:, :]
    out = np.full((2,) + hist.shape[:2], -np.inf)
    md = max(min_data, 1)
    for d, c in enumerate((cum, cum + nan)):
        gl, hl, cl = c[..., 0], c[..., 1], c[..., 2]
        gr, hr, cr = tot[0] - gl, tot[1] - hl, tot[2] - cl
        ok = ((cl >= md) & (cr >= md) & (hl >= min_sum_hessian)
              & (hr >= min_sum_hessian))
        ok[:, -1] = False
        if d == 1:
            ok &= nan[..., 2] > 0
        with np.errstate(divide="ignore", invalid="ignore"):
            g = (gbdt.leaf_gain(gl, hl, l2) + gbdt.leaf_gain(gr, hr, l2)
                 - gbdt.leaf_gain(tot[0], tot[1], l2))
        out[d] = np.where(ok, g, -np.inf)
    return out


def best_split(hist, min_sum_hessian, min_data, l2=0.0):
    """``(gain, direction, feature, bin)`` of the best admissible split
    over both directions (direction 1: NaN left)."""
    gains = split_gains(hist, min_sum_hessian, min_data, l2)
    d, f, b = np.unravel_index(int(np.argmax(gains)), gains.shape)
    return float(gains[d, f, b]), int(d), int(f), int(b)


def split_gain_at(hist, d, f, b, l2=0.0):
    """Gain of the split ``bin <= b`` on feature ``f``, NaN left where
    ``d``, admissible or not."""
    c = np.cumsum(hist[f], axis=0)
    tot = c[-1]
    left = c[b] + (hist[f, -1] if d else 0.0)
    return float(gbdt.leaf_gain(left[0], left[1], l2)
                 + gbdt.leaf_gain(tot[0] - left[0], tot[1] - left[1], l2)
                 - gbdt.leaf_gain(tot[0], tot[1], l2))


# ---------------------------------------------------------------- binning


def check_bins(X, bins, features, binning, max_bin):
    """Cells of the sampled feature columns whose bin differs: bounds by
    ``gbdt.bin_bounds`` over the sample's present values, NaN to the
    missing bin ``max_bin`` (the trailing bin of ``max_bin + 1``)."""
    idx = gbdt.bin_sample_rows(X.shape[0], binning)
    bad = 0
    for f in features:
        col = X[:, f]
        sample = col if idx is None else col[idx]
        bounds = gbdt.bin_bounds(sample[~np.isnan(sample)], max_bin)
        mine = np.searchsorted(bounds, col.astype(np.float64), side="left")
        mine[np.isnan(col)] = max_bin
        bad += int(np.count_nonzero(mine != bins[:, f]))
    return bad


# ------------------------------------------------------------- the check


def _node_rows(tree, leaf_of_row, i):
    """Rows of internal node ``i`` (all rows for the root)."""
    L = tree["num_leaves"]
    if i == 0:
        return None
    parent = np.full(L - 1, -1, np.int64)
    leaf_parent = np.empty(L, np.int64)
    for j in range(L - 1):
        for c in (tree["left"][j], tree["right"][j]):
            if c < 0:
                leaf_parent[~c] = j
            else:
                parent[c] = j
    under = np.zeros(L - 1, bool)
    under[i] = True
    for j in range(i + 1, L - 1):            # parents come first
        under[j] = parent[j] >= 0 and under[parent[j]]
    return np.nonzero(under[leaf_parent[leaf_of_row]])[0]


def check_fit(model_text, X, y, bins, cfg, *, seed, expect_trees,
              sample_nodes, sample_features, precision="float64"):
    """Every number the comparison holds, for one returned fit: the keys
    of ``gbdt.check_fit``, plus ``missing_left_nodes`` (internal nodes
    the export sends NaN left) and ``nan_rows_share`` (read beside them,
    not compared)."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    trees = parse_model(model_text)
    lr = cfg["learning_rate"]
    admissible = (cfg["min_sum_hessian"], cfg["min_data"])
    score = np.full(X.shape[0], gbdt.init_score(y), np.float64)
    count_bad = 0
    leaf_gap = 0.0
    gain_gap = 0.0
    gaps, gap_at = [], []
    rows_histogrammed = 0
    left_nodes = 0
    for t, tree in enumerate(trees):
        leaf_of_row, internal_count = walk(tree, X)
        g, h = gbdt.grad_hess(score, y)
        (gl, gi), (hl, hi) = gbdt.node_sums(tree, leaf_of_row, g, h)
        L = tree["num_leaves"]
        bias = score[0] if t == 0 else 0.0
        step = -gl / hl * lr
        got = tree["leaf_value"]
        scale = np.maximum(np.abs(step), np.median(np.abs(step)))
        leaf_gap = max(leaf_gap,
                       float(np.max(np.abs(got - bias - step) / scale)))
        if L > 1:
            left_nodes += int(nan_goes_left(tree["decision_type"]).sum())
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
                chosen[i] = (gbdt.leaf_gain(tot(lc, gl, gi), tot(lc, hl, hi))
                             + gbdt.leaf_gain(tot(rc, gl, gi),
                                              tot(rc, hl, hi))
                             - gbdt.leaf_gain(gi[i], hi[i]))
            rel = np.abs(tree["split_gain"] - chosen) / np.maximum(
                chosen, np.median(chosen))
            gain_gap = max(gain_gap, float(rel.max()))
            # as gbdt.check_fit: most nodes in the last tree, its root
            # among them, an eighth as many in each tree before it
            last = t == len(trees) - 1
            k = min(sample_nodes if last else sample_nodes // 8, L - 2)
            picks = sorted(int(i) for i in rng.choice(
                np.arange(1, L - 1), size=k, replace=False)) if k > 0 else []
            if last:
                picks = [0] + picks
            if precision == "fp8":
                g_low, h_low = gbdt.round_fp8(g), gbdt.round_fp8(h)
            for i in picks:
                rows = _node_rows(tree, leaf_of_row, i)
                rows_histogrammed += X.shape[0] if rows is None else rows.size
                exact = gbdt.node_histogram(bins, rows, g, h)
                best = best_split(exact, *admissible)[0]
                if precision == "fp8":
                    _, d, f, b = best_split(
                        gbdt.node_histogram(bins, rows, g_low, h_low),
                        *admissible)
                    mine = split_gain_at(exact, d, f, b)
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
        "split_gain_gap": gain_gap,
        "split_gap": gaps[worst] if gaps else 0.0,
        "split_gap_at": gap_at[worst] if gaps else None,
        "split_flip_share": float(np.mean(np.asarray(gaps) > gbdt.FLIP))
        if gaps else 0.0,
        "nodes_compared": len(gaps),
        "rows_histogrammed": rows_histogrammed,
        "missing_left_nodes": left_nodes,
    }
