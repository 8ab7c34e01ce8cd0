"""Plain reference for a fit cell whose objective is lambdarank: what a
leaf-wise histogram GBDT has to have produced when its gradients are
LambdaMART's, worked out in numpy and float64 from the raw rows.

It imports nothing of the program.  Everything but the gradients is
``gbdt.py``'s and ``gbdt_cat.py``'s: the model text's trees, the walk of
the raw rows, node sums, the histogram loop of hist.c, the numeric split
search, the bins derived again (a numeric column of few values included).
It is ``gbdt.check_fit`` with the gradients replaced: teacher-forced on
the trees before each tree, scores starting at zero, it computes each
query's lambdas and hessians in LightGBM's own loop form, by the rule the
configuration states under ``ranking_gradient``:

* the query's documents sorted by falling score, ties by their position
  in the query;
* for ``i`` below the truncation level, for ``j > i`` in that order, where
  the two labels differ: with ``high`` the more relevant of the two,
  ``delta = |gain_high - gain_low| * |discount_i - discount_j| / max_dcg``,
  ``p = 1 / (1 + exp(sigma * (score_high - score_low)))``,
  ``lambda_high -= sigma * p * delta``, ``lambda_low += sigma * p * delta``,
  and both hessians ``+= sigma^2 * p * (1 - p) * delta``;
* ``gain = 2^label - 1``, ``discount = 1 / log2(2 + rank)``, ``max_dcg`` the
  query's ideal DCG at the truncation level; a row's hessian is at least
  the rule's floor.

Controls (``precision=``): ``"fp8"``, the same split search with
float8_e4m3 gradients and hessians; ``"pointwise"``, the search of a
learner without the mechanism, whose gradients are squared error on the
labels (``score - label``, hessian 1).  Both return the gap of the split
such a learner would have put first and, as ``leaf_value_gap``, the gap of
the leaf values it would have given the program's trees.
"""

import numpy as np

from benchmark.reference import gbdt, gbdt_cat


# ------------------------------------------------------------- gradients


def query_runs(query_ids):
    """``(order, starts, counts)``: the rows sorted by query, each query's
    documents in the order the table holds them, and the queries' runs."""
    order = np.argsort(query_ids, kind="stable")
    _, starts, counts = np.unique(np.asarray(query_ids)[order],
                                  return_index=True, return_counts=True)
    return order, starts, counts


def query_lambdas(score, label, rule):
    """One query's ``(lambdas, hessians)``, documents in the query's own
    order: the loop over ``i`` below the truncation level and ``j > i`` in
    ranked order, one row of the (truncation, documents) block a value
    of ``i``."""
    c = score.size
    sigma = float(rule["sigma"])
    trunc = min(int(rule["truncation_level"]), c)
    gain = 2.0 ** np.minimum(label, rule["max_label"]) - 1.0
    discount = 1.0 / np.log2(2.0 + np.arange(c))
    max_dcg = float(np.sum(np.sort(gain)[::-1][:trunc] * discount[:trunc]))
    inv = 1.0 / max_dcg if max_dcg > 0 else 0.0
    ranked = np.argsort(-score, kind="stable")
    ls, ss, gs = label[ranked], score[ranked], gain[ranked]
    i = np.arange(trunc)[:, None]
    j = np.arange(c)[None, :]
    pair = (j > i) & (ls[:trunc, None] != ls[None, :])
    i_high = ls[:trunc, None] > ls[None, :]
    diff = ss[:trunc, None] - ss[None, :]
    diff = np.where(i_high, diff, -diff)           # score_high - score_low
    delta = (np.abs(gs[:trunc, None] - gs[None, :])
             * np.abs(discount[:trunc, None] - discount[None, :]) * inv)
    p = 1.0 / (1.0 + np.exp(sigma * diff))
    lam = np.where(pair, sigma * p * delta, 0.0)
    hes = np.where(pair, sigma * sigma * p * (1.0 - p) * delta, 0.0)
    to_i = np.where(i_high, -lam, lam)             # high -= , low +=
    g = np.zeros(c)
    h = np.zeros(c)
    g[:trunc] += to_i.sum(axis=1)
    g -= to_i.sum(axis=0)
    h[:trunc] += hes.sum(axis=1)
    h += hes.sum(axis=0)
    out_g = np.empty(c)
    out_h = np.empty(c)
    out_g[ranked] = g
    out_h[ranked] = h
    return out_g, out_h


def lambdarank_grad_hess(score, y, runs, rule):
    """``(g, h)`` of every row, query by query."""
    order, starts, counts = runs
    g = np.zeros(score.size)
    h = np.zeros(score.size)
    s_sorted, y_sorted = score[order], y[order]
    for a, c in zip(starts.tolist(), counts.tolist()):
        lab = y_sorted[a:a + c]
        if c < 2 or lab.max() == lab.min():
            continue
        rows = order[a:a + c]
        g[rows], h[rows] = query_lambdas(s_sorted[a:a + c], lab, rule)
    return g, np.maximum(h, float(rule["hessian_floor"]))


def pointwise_grad_hess(score, y):
    return score - y, np.ones(score.size)


def ndcg_at(score, y, runs, k):
    """Mean NDCG@k over the queries with two documents or more and more
    than one label value."""
    order, starts, counts = runs
    s_sorted, y_sorted = score[order], y[order]
    total, used = 0.0, 0
    for a, c in zip(starts.tolist(), counts.tolist()):
        lab = y_sorted[a:a + c]
        if c < 2 or lab.max() == lab.min():
            continue
        gain = 2.0 ** lab - 1.0
        disc = 1.0 / np.log2(2.0 + np.arange(min(k, c)))
        ranked = np.argsort(-s_sorted[a:a + c], kind="stable")[:k]
        ideal = float(np.sum(np.sort(gain)[::-1][:k] * disc))
        total += float(np.sum(gain[ranked] * disc)) / ideal
        used += 1
    return total / max(used, 1)


# ---------------------------------------------------------------- binning


def check_bins(X, bins, features, binning, max_bin):
    """Cells of the sampled feature columns whose bin differs: numeric
    columns only, of many values or few (``gbdt_cat.check_bins``)."""
    return gbdt_cat.check_bins(X, bins, features, (), binning, max_bin)[0]


# ------------------------------------------------------------- the check


def check_fit(model_text, X, y, query_ids, bins, cfg, *, seed, expect_trees,
              sample_nodes, sample_features, precision="float64",
              also=None):
    """Every number the comparison holds, for one returned fit.

    ``cfg``: ``gbdt.check_fit``'s keys and ``ranking_gradient`` (the
    configuration's rule).  The names returned are ``gbdt.check_fit``'s.
    With ``precision`` ``"fp8"`` or ``"pointwise"`` the split gaps and
    the leaf gap are that control's.  ``also``: ``{name: fn(scores, y, query_ids)}``, each
    read on the fit's final scores beside this file's own NDCG@10."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    trees = gbdt_cat.parse_model(model_text)
    lr = cfg["learning_rate"]
    rule = cfg["ranking_gradient"]
    admissible = (cfg["min_sum_hessian"], cfg["min_data"])
    n = X.shape[0]
    runs = query_runs(query_ids)
    score = np.zeros(n, np.float64)
    count_bad = 0
    leaf_gap = 0.0
    gain_gap = 0.0
    gaps, gap_at = [], []
    rows_histogrammed = 0
    for t, tree in enumerate(trees):
        leaf_of_row, internal_count = gbdt_cat.walk(tree, X)
        g, h = lambdarank_grad_hess(score, y, runs, rule)
        (gl, gi), (hl, hi) = gbdt.node_sums(tree, leaf_of_row, g, h)
        L = tree["num_leaves"]
        step = -gl / hl * lr
        got = tree["leaf_value"]
        scale = np.maximum(np.abs(step), np.median(np.abs(step)))
        if precision == "fp8":
            g_low, h_low = gbdt.round_fp8(g), gbdt.round_fp8(h)
        elif precision == "pointwise":
            g_low, h_low = pointwise_grad_hess(score, y)
        if precision != "float64":
            # the leaves the other learner would have given this tree
            (gl_low, _), (hl_low, _) = gbdt.node_sums(
                tree, leaf_of_row, g_low, h_low)
            got = -gl_low / np.maximum(hl_low, rule["hessian_floor"]) * lr
        leaf_gap = max(leaf_gap, float(np.max(
            np.abs(got - step) / np.where(scale > 0, scale, 1.0))))
        got = tree["leaf_value"]
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
                exact = gbdt_cat.node_histogram(bins, rows, g, h)
                best = gbdt.best_split(exact, *admissible)[0]
                if precision in ("fp8", "pointwise"):
                    # the gap of the split the other learner puts first
                    # (admissible by its own hessians), read on the exact
                    # histogram
                    _, f, b = gbdt.best_split(
                        gbdt_cat.node_histogram(bins, rows, g_low, h_low),
                        *admissible)
                    mine = gbdt.split_gain_at(exact, f, b)
                else:
                    mine = chosen[i]
                gaps.append(max(0.0, (best - mine) / best) if best > 0
                            else 0.0)
                gap_at.append((t, i))
        score = score + got[leaf_of_row]
    worst = int(np.argmax(gaps)) if gaps else None
    feats = sorted(int(f) for f in rng.choice(
        X.shape[1], size=min(sample_features, X.shape[1]), replace=False))
    out = {
        "tree_count_gap": abs(len(trees) - expect_trees),
        "count_mismatch": count_bad,
        "bin_mismatch": check_bins(X, bins, feats, cfg["binning"],
                                   cfg["max_bin"]),
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
        "queries": int(len(runs[1])),
        "ndcg10": ndcg_at(score, y, runs, 10),
    }
    for name, fn in (also or {}).items():
        out[name] = fn(score, y, query_ids)
    return out
