"""Plain reference for a multiclass (softmax) fit: what a leaf-wise
histogram GBDT growing K class trees an iteration has to have produced,
worked out in numpy and float64 from the raw rows.

It imports nothing of the program; it takes ``gbdt.py``'s pieces (the
model text parser, the walk, the node sums, the histogram loop and the
split search), which import nothing of it either.  It is given what the
timed call was given (raw float32 rows, class labels, and the uint8 bins
``engine.train`` took) and what it returned, as the LightGBM model text
of the program's own export.  The text's ``num_class`` is K; tree ``t``
is class ``t mod K`` of iteration ``t div K`` (iteration-major,
class-minor).  Every iteration is teacher-forced from the exported trees
of the iterations before it: the softmax over the K scores gives, for
every class tree of the iteration, LightGBM's gradients

    g_k = p_k - [y == k],    h_k = K / (K - 1) * p_k * (1 - p_k),

all K from the scores at the START of the iteration (one gradient pass an
iteration, as LightGBM's multiclass objective computes them).  Scores
start at LightGBM's ``boost_from_average`` for softmax: class k at
log(max(1e-15, share of class k)), baked into class k's first tree.

On every class tree it makes ``gbdt.py``'s checks:

(a) the rows that reach each node, by walking the RAW values down the
    exported thresholds, against the exported counts;
(b) each leaf's value against the shrunk Newton step of its rows' sums;
(c) at sampled nodes, the full (feature, bin) histogram of the node's
    rows and the best admissible split: the gap by which the program's
    split lies below the best (in the last tree every internal node,
    whose histograms come from the smaller child's rows and the parent
    less that, as a leaf-wise learner makes them);
(d) for sampled features, the bins by the configuration's rule (for a
    column with at most ``max_bin`` distinct values in the row sample,
    midpoints between them merged until a bin holds ``min_data_in_bin``
    sample rows; else the quantiles at k/max_bin, whose duplicates, the
    zeros of a mostly-zero pixel column, collapse into one bound).

``precision="fp8"`` is the control: the split search on float8_e4m3
gradients and hessians, the gap of the split it would put first.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.reference import gbdt

EPSILON = 1e-15             # LightGBM's kEpsilon: the least class share
TREE_THREADS = 5            # class trees of an iteration checked at once


def parse_model(text):
    """``(num_class, trees)`` of a LightGBM v3 model text."""
    num_class = 1
    for line in text.split("Tree=")[0].splitlines():
        if line.startswith("num_class="):
            num_class = int(line.partition("=")[2])
    return num_class, gbdt.parse_model(text)


def init_scores(y, K):
    """Class k starts at log(share of class k)."""
    share = np.bincount(np.asarray(y, np.int64), minlength=K) / len(y)
    return np.log(np.maximum(EPSILON, share))


def softmax_grad_hess(score, y, K):
    """``(n, K)`` gradients and hessians at ``score`` (n, K)."""
    z = score - score.max(axis=1, keepdims=True)
    p = np.exp(z)
    p /= p.sum(axis=1, keepdims=True)
    onehot = np.zeros_like(p)
    onehot[np.arange(len(y)), np.asarray(y, np.int64)] = 1.0
    return p - onehot, K / (K - 1.0) * p * (1.0 - p)


# ---------------------------------------------------------------- binning


def bin_bounds(col_sample, max_bin, min_data_in_bin):
    """Upper bounds of one column by the configuration's rule."""
    distinct, counts = np.unique(col_sample, return_counts=True)
    if distinct.size <= 1:
        return np.empty(0, np.float64)
    if distinct.size <= max_bin:
        # in the column's own dtype, as the rule states: a float32
        # midpoint of two levels can be a level of a row outside the
        # sample, and float64's would put that row in the other bin
        two = distinct.dtype.type(2)
        mids = ((distinct[:-1] + distinct[1:]) / two).astype(np.float64)
        if min_data_in_bin > 1 and col_sample.size >= 2 * min_data_in_bin:
            keep, acc = [], 0
            for mid, c in zip(mids, counts[:-1]):
                acc += int(c)
                if acc >= min_data_in_bin:
                    keep.append(mid)
                    acc = 0
            mids = np.asarray(keep, np.float64)
        return mids
    qs = np.linspace(0, 1, max_bin + 1)[1:-1]
    return np.unique(np.quantile(col_sample, qs).astype(np.float64))


def check_bins(X, bins, features, binning, max_bin):
    """Cells of the sampled feature columns whose bin differs."""
    idx = gbdt.bin_sample_rows(X.shape[0], binning)
    bad = 0
    for f in features:
        col = X[:, f]
        bounds = bin_bounds(col if idx is None else col[idx], max_bin,
                            int(binning["min_data_in_bin"]))
        mine = np.searchsorted(bounds, col.astype(np.float64), side="left")
        bad += int(np.count_nonzero(mine != bins[:, f]))
    return bad


# ------------------------------------------------------------ node rows


class NodeRows:
    """The rows under each internal node of one tree, from the leaf of
    every row: rows sorted by leaf once, a node's rows the runs of the
    leaves below it."""

    def __init__(self, tree, leaf_of_row):
        L = tree["num_leaves"]
        self.order = np.argsort(leaf_of_row, kind="stable")
        self.start = np.zeros(L + 1, np.int64)
        np.cumsum(np.bincount(leaf_of_row, minlength=L), out=self.start[1:])
        self.leaves = [[] for _ in range(L - 1)]
        # children come after their parent: a reverse sweep meets every
        # child's leaf list before its parent's
        for i in range(L - 2, -1, -1):
            for c in (tree["left"][i], tree["right"][i]):
                self.leaves[i].extend([~c] if c < 0 else self.leaves[c])

    def count(self, c):
        """Rows under child reference ``c`` (a leaf if ``c < 0``)."""
        leaves = [~c] if c < 0 else self.leaves[c]
        return int(sum(self.start[l + 1] - self.start[l] for l in leaves))

    def rows(self, i):
        """Sorted rows under internal node ``i`` (None for the root)."""
        if i == 0:
            return None
        return np.sort(np.concatenate(
            [self.order[self.start[l]:self.start[l + 1]]
             for l in self.leaves[i]]))


def node_histograms(bins, rows, g, h, picks):
    """``{node: (F, B, 3) histogram}`` for the internal nodes ``picks``,
    each straight from its rows."""
    return {i: gbdt.node_histogram(bins, rows.rows(i), g, h) for i in picks}


def all_node_histograms(bins, tree, rows, g, h):
    """Histogram of every internal node, in node order (parents first):
    the root's from all rows, then at each split the smaller internal
    child's from its rows and the other's as the parent's less it.
    Yields ``(node, histogram)`` and keeps only the open frontier."""
    L = tree["num_leaves"]
    hists = {0: gbdt.node_histogram(bins, None, g, h)}
    for i in range(L - 1):
        parent = hists.pop(i)
        lc, rc = tree["left"][i], tree["right"][i]
        inner = [c for c in (lc, rc) if c >= 0]
        if len(inner) == 2:
            small, big = sorted(inner, key=rows.count)
            hists[small] = gbdt.node_histogram(bins, rows.rows(small), g, h)
            hists[big] = parent - hists[small]
        elif inner:
            hists[inner[0]] = gbdt.node_histogram(
                bins, rows.rows(inner[0]), g, h)
        yield i, parent


# ------------------------------------------------------------- the check


def check_fit(model_text, X, y, bins, cfg, *, seed, expect_trees,
              sample_nodes, sample_features, precision="float64"):
    """Every number the comparison holds, for one returned fit.

    ``cfg``: ``learning_rate``, ``min_sum_hessian``, ``min_data``,
    ``max_bin``, ``binning``.  Nodes compared: every internal node of the
    last tree, and ``sample_nodes // 8`` drawn from the seed in each
    other tree.  Returns a dict of plain numbers; with
    ``precision="fp8"`` the split gaps are the control's."""
    return _check_fit(model_text, X, y, bins, cfg, seed=seed,
                      expect_trees=expect_trees, sample_nodes=sample_nodes,
                      sample_features=sample_features, precision=precision)


def _check_tree(tree, X, bins, g, h, bias, lr, admissible, picks,
                precision):
    """One class tree's checks (a)-(c), given its class's gradients and
    the nodes to compare (None: every internal node).  A child that holds
    no hessian reads a gain of nan, as in gbdt.py."""
    with np.errstate(divide="ignore", invalid="ignore"):
        return _check_tree_in(tree, X, bins, g, h, bias, lr, admissible,
                              picks, precision)


def _check_tree_in(tree, X, bins, g, h, bias, lr, admissible, picks,
                   precision):
    leaf_of_row, internal_count = gbdt.walk(tree, X)
    (gl, gi), (hl, hi) = gbdt.node_sums(tree, leaf_of_row, g, h)
    L = tree["num_leaves"]
    step = -gl / hl * lr
    got = tree["leaf_value"]
    scale = np.maximum(np.abs(step), np.median(np.abs(step)))
    out = {"leaf_gap": float(np.max(np.abs(got - bias - step) / scale)),
           "scores": got[leaf_of_row] - bias, "count_bad": 0,
           "gain_gap": 0.0, "gaps": [], "rows": 0}
    if L <= 1:
        return out
    out["count_bad"] = (
        int(np.count_nonzero(np.bincount(leaf_of_row, minlength=L)
                             != tree["leaf_count"]))
        + int(np.count_nonzero(internal_count != tree["internal_count"])))

    def tot(c, a_leaf, a_int):
        return a_leaf[~c] if c < 0 else a_int[c]

    chosen = np.empty(L - 1, np.float64)
    for i in range(L - 1):
        lc, rc = tree["left"][i], tree["right"][i]
        chosen[i] = (gbdt.leaf_gain(tot(lc, gl, gi), tot(lc, hl, hi))
                     + gbdt.leaf_gain(tot(rc, gl, gi), tot(rc, hl, hi))
                     - gbdt.leaf_gain(gi[i], hi[i]))
    rel = np.abs(tree["split_gain"] - chosen) / np.maximum(
        chosen, np.median(chosen))
    out["gain_gap"] = float(rel.max())

    rows = NodeRows(tree, leaf_of_row)
    if picks is None:
        found = all_node_histograms(bins, tree, rows, g, h)
        out["rows"] = X.shape[0] + sum(
            min(rows.count(tree["left"][i]), rows.count(tree["right"][i]))
            for i in range(L - 1)
            if tree["left"][i] >= 0 and tree["right"][i] >= 0)
    else:
        found = node_histograms(bins, rows, g, h, picks).items()
        out["rows"] = sum(X.shape[0] if i == 0 else rows.count(i)
                          for i in picks)
    if precision == "fp8":
        g_low, h_low = gbdt.round_fp8(g), gbdt.round_fp8(h)
    for i, exact in found:
        best = gbdt.best_split(exact, *admissible)[0]
        if precision == "fp8":
            # the split the lower precision puts first, read on the
            # exact histogram
            _, f, b = gbdt.best_split(gbdt.node_histogram(
                bins, rows.rows(i), g_low, h_low), *admissible)
            mine = gbdt.split_gain_at(exact, f, b)
        else:
            mine = chosen[i]
        out["gaps"].append((max(0.0, (best - mine) / best) if best > 0
                            else 0.0, i))
    return out


def _check_fit(model_text, X, y, bins, cfg, *, seed, expect_trees,
               sample_nodes, sample_features, precision):
    rng = np.random.default_rng([int(seed), 0x5EED])
    K, trees = parse_model(model_text)
    y = np.asarray(y, np.int64)
    lr = cfg["learning_rate"]
    admissible = (cfg["min_sum_hessian"], cfg["min_data"])
    start = init_scores(y, K)
    score = np.tile(start, (X.shape[0], 1))
    count_bad = 0
    leaf_gap = gain_gap = 0.0
    gaps, gap_at = [], []
    rows_histogrammed = 0
    for it in range(-(-len(trees) // K)):
        g_all, h_all = softmax_grad_hess(score, y, K)
        ts = range(it * K, min((it + 1) * K, len(trees)))
        # the nodes compared, drawn from the seed tree by tree before any
        # tree is checked (the trees of an iteration are checked at once)
        picks = {}
        for t in ts:
            L = trees[t]["num_leaves"]
            if t == len(trees) - 1 or L <= 1:
                picks[t] = None
            else:
                picks[t] = sorted(int(i) for i in rng.choice(
                    L - 1, size=min(sample_nodes // 8, L - 1),
                    replace=False))

        def check(t):
            k = t - it * K
            return _check_tree(
                trees[t], X, bins, np.ascontiguousarray(g_all[:, k]),
                np.ascontiguousarray(h_all[:, k]),
                start[k] if it == 0 else 0.0, lr, admissible, picks[t],
                precision)

        with ThreadPoolExecutor(TREE_THREADS) as pool:
            done = list(pool.map(check, ts))
        for t, r in zip(ts, done):
            leaf_gap = max(leaf_gap, r["leaf_gap"])
            gain_gap = max(gain_gap, r["gain_gap"])
            count_bad += r["count_bad"]
            rows_histogrammed += r["rows"]
            for gap, i in r["gaps"]:
                gaps.append(gap)
                gap_at.append((t, i))
            score[:, t - it * K] += r["scores"]
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
        # read beside them, not compared
        "num_class": K,
        "split_gain_gap": gain_gap,
        "split_gap": gaps[worst] if gaps else 0.0,
        "split_gap_at": gap_at[worst] if gaps else None,
        "split_flip_share": float(np.mean(np.asarray(gaps) > gbdt.FLIP))
        if gaps else 0.0,
        "nodes_compared": len(gaps),
        "rows_histogrammed": rows_histogrammed,
    }
