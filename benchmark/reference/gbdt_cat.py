"""Plain reference for fit cells whose table has categorical columns: what
a leaf-wise histogram GBDT with LightGBM's categorical splits has to have
produced, worked out in numpy and float64 from the raw rows.

It imports nothing of the program; the numeric pieces (objective, node
sums, the histogram loop of hist.c, the numeric split search) are
``gbdt.py``'s.  What this file adds is everything a categorical column
changes:

* the model text's ``num_cat``, ``cat_boundaries``, ``cat_threshold`` and
  ``decision_type`` are parsed; a node with bit 0 of its decision type set
  sends a row left when the row's RAW value has its bit set in the node's
  bitset, and right otherwise (a value beyond the bitset included);
* at the sampled nodes the best admissible split is searched over the
  numeric columns (``bin <= b``) AND the categorical columns, by the rule
  the configuration states under ``categorical_split``: bins sorted by
  ``g / (h + cat_smooth)``, prefixes with the smaller side at most
  ``max_cat_threshold``, ``cat_l2`` in the gain, one bin against the rest
  at or under ``max_cat_to_onehot`` binned values;
* the bins of sampled columns are derived again for both kinds: a
  numeric column's bounds (quantiles, or midpoints where its values are
  few), a categorical column's value-to-bin map (the most frequent values
  of the row sample first).

Controls (``precision=``): ``"fp8"``, the same search with float8_e4m3
gradients and hessians; ``"cat_as_numeric"``, the search of a learner
without the categorical mechanism, to which a categorical column is its
codes in ascending order.  Both return the gap of the split such a
learner would have put first.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.reference import gbdt

WALK_BLOCK = 1 << 20        # rows a thread walks at a time
HIST_MIN_ROWS = 1 << 18     # a node's rows are split over threads above this
CAT_BIT = 1                 # decision_type bit 0: categorical split


# ------------------------------------------------------------ model text


def _numbers(text, dtype):
    if not text:
        return np.zeros(0, dtype)
    return np.fromstring(text, dtype=np.float64, sep=" ").astype(dtype)


def parse_model(text):
    """The trees of a LightGBM v3 model text, categorical splits included,
    as dicts of arrays.  A tree with ``num_cat`` > 0 also holds
    ``cat_boundaries`` and ``cat_threshold``; ``is_cat`` marks its
    categorical nodes, whose ``threshold`` is an index into the
    boundaries."""
    body = text.split("end of trees")[0]
    trees = []
    for chunk in body.split("Tree=")[1:]:
        kv = {}
        for line in chunk.split("\n")[1:]:
            k, eq, v = line.partition("=")
            if eq:
                kv[k.strip()] = v.strip()
        num_leaves = int(kv["num_leaves"])
        tree = {"num_leaves": num_leaves,
                "num_cat": int(kv.get("num_cat", 0)),
                "leaf_value": _numbers(kv.get("leaf_value"), np.float64)}
        if num_leaves > 1:
            tree.update(
                split_feature=_numbers(kv["split_feature"], np.int64),
                threshold=_numbers(kv["threshold"], np.float64),
                split_gain=_numbers(kv["split_gain"], np.float64),
                decision_type=_numbers(kv["decision_type"], np.int64),
                left=_numbers(kv["left_child"], np.int64),
                right=_numbers(kv["right_child"], np.int64),
                leaf_count=_numbers(kv["leaf_count"], np.int64),
                internal_count=_numbers(kv["internal_count"], np.int64))
            tree["is_cat"] = (tree["decision_type"] & CAT_BIT) > 0
            if tree["num_cat"]:
                tree["cat_boundaries"] = _numbers(kv["cat_boundaries"],
                                                  np.int64)
                tree["cat_threshold"] = _numbers(kv["cat_threshold"],
                                                 np.uint32)
            if int(tree["is_cat"].sum()) != tree["num_cat"]:
                raise ValueError("num_cat disagrees with decision_type")
        trees.append(tree)
    return trees


def node_categories(tree, i):
    """The raw values that node ``i``'s bitset sends left, ascending."""
    k = int(tree["threshold"][i])
    lo, hi = tree["cat_boundaries"][k], tree["cat_boundaries"][k + 1]
    words = tree["cat_threshold"][lo:hi]
    at = np.nonzero(words)[0]
    out = [w * 32 + b for w in at for b in range(32)
           if (int(words[w]) >> b) & 1]
    return np.asarray(out, np.int64)


# ------------------------------------------------------------------ walk


def _walk_block(tree, X):
    n = X.shape[0]
    feat, thr = tree["split_feature"], tree["threshold"]
    left, right = tree["left"], tree["right"]
    is_cat = tree["is_cat"]
    if tree["num_cat"]:
        bnd, words = tree["cat_boundaries"], tree["cat_threshold"]
        cat_k = np.where(is_cat, thr, 0).astype(np.int64)
        cat_lo = bnd[cat_k]
        cat_len = bnd[cat_k + 1] - cat_lo
    node = np.zeros(n, np.int64)
    rows = np.arange(n)
    internal_count = np.zeros(len(feat), np.int64)
    while rows.size:
        at = node[rows]
        internal_count += np.bincount(at, minlength=len(feat))
        x = X[rows, feat[at]]
        go_left = x <= thr[at]
        if tree["num_cat"]:
            c = np.nonzero(is_cat[at])[0]
            if c.size:
                xc = x[c]
                v = np.where(xc >= 0, xc, -1).astype(np.int64)
                w = v >> 5
                inside = (v >= 0) & (w < cat_len[at[c]]) & (xc == v)
                word = words[cat_lo[at[c]] + np.where(inside, w, 0)]
                go_left[c] = inside & (
                    (word >> (v & 31).astype(np.uint32)) & 1).astype(bool)
        nxt = np.where(go_left, left[at], right[at])
        node[rows] = nxt
        rows = rows[nxt >= 0]
    return ~node, internal_count


def walk(tree, X, threads=None):
    """Leaf of every row, and the rows counted through each internal node.

    Numeric node: ``x <= threshold`` goes left.  Categorical node: the raw
    value's bit in the node's bitset goes left, anything else right.  A
    child ``c < 0`` is leaf ``~c``.  Blocks of rows in threads."""
    n = X.shape[0]
    if tree["num_leaves"] == 1:
        return np.zeros(n, np.int64), np.zeros(0, np.int64)
    threads = threads or min(8, os.cpu_count() or 1)
    starts = range(0, n, WALK_BLOCK)
    with ThreadPoolExecutor(threads) as pool:
        parts = list(pool.map(
            lambda a: _walk_block(tree, X[a:a + WALK_BLOCK]), starts))
    return (np.concatenate([p[0] for p in parts]),
            np.sum([p[1] for p in parts], axis=0))


# ------------------------------------------------------------ histograms


def node_histogram(bins, rows, g, h, threads=None):
    """``gbdt.node_histogram``, with a large node's rows split over the
    threads: a narrow table is one block of features, which one thread
    would take alone."""
    threads = threads or min(8, os.cpu_count() or 1)
    m = bins.shape[0] if rows is None else rows.size
    if m < HIST_MIN_ROWS or threads == 1:
        return gbdt.node_histogram(bins, rows, g, h, threads=1)
    if rows is None:
        rows = np.arange(bins.shape[0], dtype=np.int64)
    parts = np.array_split(rows, threads)
    with ThreadPoolExecutor(threads) as pool:
        hists = list(pool.map(
            lambda r: gbdt.node_histogram(bins, r, g, h, threads=1), parts))
    return np.sum(hists, axis=0)


def node_rows(leaves, leaf_of_row, by_leaf, leaf_count, leaf_end):
    """Ascending row numbers of the rows in ``leaves``: a small node's
    from the rows sorted by leaf, a large one's by one pass over the
    table (sorting an eighth of the table costs more than the pass)."""
    if sum(int(leaf_count[leaf]) for leaf in leaves) * 8 > leaf_of_row.size:
        under = np.zeros(leaf_count.size, bool)
        under[leaves] = True
        return np.nonzero(under[leaf_of_row])[0]
    return np.sort(np.concatenate(
        [by_leaf[leaf_end[leaf] - leaf_count[leaf]:leaf_end[leaf]]
         for leaf in leaves]))


# ---------------------------------------------------------- split search


def best_cat_split(hist_f, value_bins, rule, min_sum_hessian, min_data):
    """Best admissible categorical split of one column at one node:
    ``(gain, mask)``, ``mask`` the bins it sends left, or None.
    ``hist_f``: (B, 3); the trailing bin never goes left."""
    B = hist_f.shape[0]
    tot = hist_f.sum(axis=0)
    listed = np.nonzero((hist_f[:, 2] > 0) & (np.arange(B) != B - 1))[0]
    if listed.size < 2:
        return None
    l2 = rule["cat_l2"]
    if value_bins <= rule["max_cat_to_onehot"]:
        left_sets = [[b] for b in listed]
        s = hist_f[listed]
        ok = np.ones(listed.size, bool)
    else:
        ratio = hist_f[listed, 0] / (hist_f[listed, 1] + rule["cat_smooth"])
        order = listed[np.argsort(ratio, kind="stable")]
        k = np.arange(1, order.size)          # a listed bin stays right
        left_sets = [order[:j] for j in k]
        s = np.cumsum(hist_f[order], axis=0)[:-1]
        ok = np.minimum(k, order.size - k) <= rule["max_cat_threshold"]
    r = tot - s
    ok = (ok & (s[:, 2] >= max(min_data, 1)) & (r[:, 2] >= max(min_data, 1))
          & (s[:, 1] >= min_sum_hessian) & (r[:, 1] >= min_sum_hessian))
    if not ok.any():
        return None
    with np.errstate(divide="ignore", invalid="ignore"):
        gains = (gbdt.leaf_gain(s[:, 0], s[:, 1], l2)
                 + gbdt.leaf_gain(r[:, 0], r[:, 1], l2)
                 - gbdt.leaf_gain(tot[0], tot[1], l2))
    gains = np.where(ok, gains, -np.inf)
    best = int(np.argmax(gains))
    mask = np.zeros(B, bool)
    mask[left_sets[best]] = True
    return float(gains[best]), mask


def mask_gain(hist_f, mask, l2):
    """Gain of sending ``mask``'s bins left, admissible or not."""
    tot = hist_f.sum(axis=0)
    s = hist_f[mask].sum(axis=0)
    r = tot - s
    return float(gbdt.leaf_gain(s[0], s[1], l2)
                 + gbdt.leaf_gain(r[0], r[1], l2)
                 - gbdt.leaf_gain(tot[0], tot[1], l2))


def best_split(hist, cat, value_bins, rule, min_sum_hessian, min_data):
    """Best admissible split over numeric and categorical columns:
    ``(gain, feature, bin or None, mask or None)``.  A categorical
    candidate is taken only if strictly better than the best numeric."""
    is_cat = np.zeros(hist.shape[0], bool)
    is_cat[cat] = True
    num = np.nonzero(~is_cat)[0]
    best = (-np.inf, -1, None, None)
    if num.size:
        gain, f, b = gbdt.best_split(hist[num], min_sum_hessian, min_data)
        best = (gain, int(num[f]), b, None)
    for f in cat:
        found = best_cat_split(hist[f], value_bins[f], rule,
                               min_sum_hessian, min_data)
        if found is not None and found[0] > best[0]:
            best = (found[0], int(f), None, found[1])
    return best


def split_gain(hist, split, rule):
    """Gain, on ``hist``, of a split that :func:`best_split` returned."""
    _, f, b, mask = split
    if mask is not None:
        return mask_gain(hist[f], mask, rule["cat_l2"])
    return gbdt.split_gain_at(hist, f, b)


def by_code(hist, cat, cat_values):
    """``hist`` as a learner without the categorical mechanism has it: a
    categorical column's binned values in ascending order of their codes,
    the trailing bin where it was."""
    out = hist.copy()
    for f in cat:
        order = np.argsort(cat_values[f], kind="stable")
        out[f, :order.size] = hist[f, order]
    return out


# ---------------------------------------------------------------- binning


def bin_bounds(col_sample, max_bin, min_data_in_bin):
    """A numeric column's upper bounds by the configuration's rule."""
    s = np.sort(col_sample)
    distinct, counts = np.unique(s, return_counts=True)
    if distinct.size <= 1:
        return np.empty(0, np.float64)
    if distinct.size > max_bin:
        qs = np.linspace(0, 1, max_bin + 1)[1:-1]
        return np.unique(np.quantile(col_sample, qs).astype(np.float64))
    mids = (distinct[:-1] + distinct[1:]) / 2.0
    if min_data_in_bin <= 1 or s.size < 2 * min_data_in_bin:
        return mids.astype(np.float64)
    kept, since = [], 0
    for m, cnt in zip(mids, counts[:-1]):
        since += cnt
        if since >= min_data_in_bin:
            kept.append(m)
            since = 0
    return np.asarray(kept, np.float64)


def category_bins(col_sample, max_bin):
    """A categorical column's binned values, bin 0 first: the
    ``max_bin - 1`` most frequent of the sample, equal counts by value."""
    vals, counts = np.unique(col_sample.astype(np.int64), return_counts=True)
    return vals[np.argsort(-counts, kind="stable")][:max_bin - 1]


def column_bins(col, sample, is_cat, binning, max_bin):
    """Every row's bin of one column, derived from the raw values."""
    part = col if sample is None else col[sample]
    if not is_cat:
        bounds = bin_bounds(part, max_bin, binning["min_data_in_bin"])
        return np.searchsorted(bounds, col.astype(np.float64),
                               side="left"), None
    values = category_bins(part, max_bin)
    order = np.argsort(values)
    v = col.astype(np.int64)
    pos = np.minimum(np.searchsorted(values[order], v), values.size - 1)
    hit = values[order][pos] == v
    return np.where(hit, order[pos], max_bin), values


def check_bins(X, bins, features, cat, binning, max_bin, threads=None):
    """``(cells of the sampled columns whose bin differs, {column: its
    binned values})`` for the categorical columns among them."""
    idx = gbdt.bin_sample_rows(X.shape[0], binning)
    cat = set(cat)

    def one(f):
        mine, values = column_bins(X[:, f], idx, f in cat, binning, max_bin)
        return int(np.count_nonzero(mine != bins[:, f])), values

    threads = threads or min(8, os.cpu_count() or 1)
    with ThreadPoolExecutor(threads) as pool:
        got = list(pool.map(one, features))
    return (sum(bad for bad, _ in got),
            {f: v for f, (_, v) in zip(features, got) if v is not None})


# ------------------------------------------------------------- the check


def sampled_columns(rng, features, cat, sample_features, min_categorical):
    """Columns whose bins are derived again: ``sample_features`` of them
    drawn from the seed, at least ``min_categorical`` categorical."""
    cat = np.asarray(sorted(cat), np.int64)
    k = min(sample_features, features)
    k_cat = min(min_categorical, cat.size, k)
    first = rng.choice(cat, size=k_cat, replace=False) if k_cat else cat[:0]
    rest = np.setdiff1d(np.arange(features), first)
    more = rng.choice(rest, size=k - k_cat, replace=False)
    return sorted(int(f) for f in np.concatenate([first, more]))


def check_fit(model_text, X, y, bins, cfg, *, seed, expect_trees,
              sample_nodes, sample_features, min_categorical=0,
              precision="float64"):
    """Every number the comparison holds, for one returned fit.

    ``cfg``: ``learning_rate``, ``min_sum_hessian``, ``min_data``,
    ``max_bin``, ``binning``, ``categorical`` (the categorical columns)
    and ``categorical_split`` (the configuration's rule).  The names are
    ``gbdt.check_fit``'s.  With ``precision`` ``"fp8"`` or
    ``"cat_as_numeric"`` the split gaps are that control's."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    trees = parse_model(model_text)
    lr = cfg["learning_rate"]
    rule = cfg["categorical_split"]
    cat = sorted(int(f) for f in cfg["categorical"])
    admissible = (cfg["min_sum_hessian"], cfg["min_data"])
    max_bin = cfg["max_bin"]
    n, F = X.shape

    # the bins of a sample of columns, and every categorical column's
    # binned values (the split search needs how many each has, the
    # cat_as_numeric control their codes): each from the raw values
    feats = sampled_columns(rng, F, cat, sample_features, min_categorical)
    bin_bad, cat_values = check_bins(X, bins, feats, cat, cfg["binning"],
                                     max_bin)
    idx = gbdt.bin_sample_rows(n, cfg["binning"])
    for f in cat:
        if f not in cat_values:
            col = X[:, f] if idx is None else X[idx, f]
            cat_values[f] = category_bins(col, max_bin)
    value_bins = {f: len(v) for f, v in cat_values.items()}

    score = np.full(n, gbdt.init_score(y), np.float64)
    count_bad = 0
    leaf_gap = 0.0
    gain_gap = 0.0
    gaps, gap_at = [], []
    rows_histogrammed = 0
    cat_nodes = internal_nodes = 0
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
            is_cat = tree["is_cat"]
            cat_nodes += int(is_cat.sum())
            internal_nodes += L - 1
            leaf_count = np.bincount(leaf_of_row, minlength=L)
            count_bad += int(np.count_nonzero(
                leaf_count != tree["leaf_count"]))
            count_bad += int(np.count_nonzero(
                internal_count != tree["internal_count"]))

            def tot(c, a_leaf, a_int):
                return a_leaf[~c] if c < 0 else a_int[c]

            # the gain of the split each node holds, from this file's own
            # sums; a categorical node's carries cat_l2, as the rule says
            chosen = np.empty(L - 1, np.float64)
            for i in range(L - 1):
                lc, rc = tree["left"][i], tree["right"][i]
                l2 = rule["cat_l2"] if is_cat[i] else 0.0
                chosen[i] = (
                    gbdt.leaf_gain(tot(lc, gl, gi), tot(lc, hl, hi), l2)
                    + gbdt.leaf_gain(tot(rc, gl, gi), tot(rc, hl, hi), l2)
                    - gbdt.leaf_gain(gi[i], hi[i], l2))
            rel = np.abs(tree["split_gain"] - chosen) / np.maximum(
                chosen, np.median(chosen))
            gain_gap = max(gain_gap, float(rel.max()))
            last = t == len(trees) - 1
            k = min(sample_nodes if last else sample_nodes // 8, L - 2)
            picks = sorted(int(i) for i in rng.choice(
                np.arange(1, L - 1), size=k, replace=False)) if k > 0 else []
            if last:
                picks = [0] + picks
            # rows of each internal node: the rows sorted by leaf, and the
            # leaves under the node (children come after their parent)
            by_leaf = np.argsort(leaf_of_row, kind="stable")
            leaf_end = np.cumsum(leaf_count)
            leaves_under = [None] * (L - 1)
            for i in range(L - 2, -1, -1):
                leaves_under[i] = [
                    leaf for c in (tree["left"][i], tree["right"][i])
                    for leaf in ([~c] if c < 0 else leaves_under[c])]
            if precision == "fp8":
                g_low, h_low = gbdt.round_fp8(g), gbdt.round_fp8(h)
            for i in picks:
                rows = None if i == 0 else node_rows(
                    leaves_under[i], leaf_of_row, by_leaf, leaf_count,
                    leaf_end)
                rows_histogrammed += n if rows is None else rows.size
                exact = node_histogram(bins, rows, g, h)
                best = best_split(exact, cat, value_bins, rule,
                                  *admissible)[0]
                if precision == "fp8":
                    low = best_split(node_histogram(bins, rows, g_low, h_low),
                                     cat, value_bins, rule, *admissible)
                    mine = split_gain(exact, low, rule) if low[1] >= 0 \
                        else 0.0
                elif precision == "cat_as_numeric":
                    # every column numeric: a categorical one is its
                    # binned codes ascending; the same rows on each side,
                    # so the gain is read where the split was found
                    mine = gbdt.best_split(by_code(exact, cat, cat_values),
                                           *admissible)[0]
                else:
                    mine = chosen[i]
                gaps.append(max(0.0, (best - mine) / best) if best > 0
                            else 0.0)
                gap_at.append((t, i))
        score = score + (got[leaf_of_row] - bias)
    worst = int(np.argmax(gaps)) if gaps else None
    return {
        "tree_count_gap": abs(len(trees) - expect_trees),
        "count_mismatch": count_bad,
        "bin_mismatch": bin_bad,
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
        "cat_split_share": cat_nodes / internal_nodes if internal_nodes
        else 0.0,
        "cat_bitset_words": int(sum(len(t.get("cat_threshold", ()))
                                    for t in trees)),
        "bin_columns": feats,
    }
