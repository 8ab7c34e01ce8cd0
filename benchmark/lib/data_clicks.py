"""Seeded click-log rows: integer count columns followed by categorical
columns of hashed values, and a click label (benchmark/configs/criteo.json).

What is taken from the source is the SHAPE: how many columns of each
kind, and how many distinct values each categorical column has.  What
is assumed, and the configuration lists it under ``assumed``:

* a numeric column is the floor of a log-normal (a non-negative,
  heavy-tailed count), column ``j`` with its own location and spread, so
  that some columns have a dozen distinct values and some thousands; no
  cell is missing;
* a categorical column draws a RANK from a Zipf law truncated at the
  column's cardinality (``p(k) ~ k**-ZIPF_EXPONENT``), and the value in
  the table is ``perm[rank]``, ``perm`` a permutation of
  ``0..cardinality-1`` fixed by the seed: what a label encoder gives, so a
  frequent value is not a small code and the order of the codes means
  nothing;
* the label is Bernoulli of a logistic whose argument adds a few numeric
  terms, an effect per category on ``EFFECT_COLUMNS`` of the categorical
  columns (normal, fixed by the seed; among them the widest columns) and a
  bias set so that ``POSITIVE_SHARE`` of the labels are 1.

Rows are made in fixed blocks, each from its own child of
``SeedSequence(seed)``, as ``lib/data.planted_normal`` does: the table
depends on the seed and the shape only, never on the threads.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 32768
ZIPF_EXPONENT = 1.05
POSITIVE_SHARE = 0.256
#: categorical columns (by position among them) that carry an effect per
#: category, and its spread; the rest are noise columns
EFFECT_SPREAD = {2: 0.7, 11: 0.6, 20: 0.6, 15: 0.5, 3: 0.5, 0: 0.5,
                 6: 0.4, 9: 0.4}
EFFECT_COLUMNS = tuple(sorted(EFFECT_SPREAD))
#: numeric terms of the label, on t_j = (log1p(x_j) - loc_j) / spread_j
NUMERIC_LINEAR = {0: 0.9, 4: -0.7}
NUMERIC_PRODUCT = 0.6       # on t_1 * t_2
NUMERIC_SINE = 0.6          # on sin(2 t_3)


def _numeric_law(num_numeric):
    """(loc, spread) of each count column's logarithm: fixed, not seeded,
    from narrow (a dozen distinct values) to wide (thousands)."""
    j = np.arange(num_numeric)
    loc = 0.4 + 0.45 * (j % 7)
    spread = 0.5 + 0.22 * ((j * 5) % 9)
    return loc.astype(np.float32), spread.astype(np.float32)


def _zipf_cdf(cardinality):
    w = np.arange(1, cardinality + 1, dtype=np.float64) ** -ZIPF_EXPONENT
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return cdf


def click_log(seed, rows, cardinalities, num_numeric=13, threads=None):
    """``(X float32 (rows, num_numeric + len(cardinalities)), y float64
    (rows,) in {0, 1})``; categorical columns come after the numeric."""
    cards = [int(c) for c in cardinalities]
    if max(cards) >= 1 << 24:
        raise ValueError("a category code has to be exact in float32")
    if num_numeric < 6 or len(cards) <= max(EFFECT_COLUMNS):
        raise ValueError("the planted label needs 6 numeric and "
                         f"{max(EFFECT_COLUMNS) + 1} categorical columns")
    threads = threads or min(8, os.cpu_count() or 1)
    starts = list(range(0, rows, BLOCK_ROWS))
    root = np.random.SeedSequence(int(seed))
    children = root.spawn(len(starts) + len(cards) + 1)
    col_seeds = children[len(starts):len(starts) + len(cards)]
    loc, spread = _numeric_law(num_numeric)

    def tables(c):
        rng = np.random.default_rng(col_seeds[c])
        perm = rng.permutation(cards[c]).astype(np.int32)
        effect = None
        if c in EFFECT_SPREAD:      # indexed by RANK: drawn per category
            effect = (rng.standard_normal(cards[c], dtype=np.float32)
                      * np.float32(EFFECT_SPREAD[c]))
        return _zipf_cdf(cards[c]), perm, effect

    with ThreadPoolExecutor(threads) as pool:
        cdfs, perms, effects = zip(*pool.map(tables, range(len(cards))))

    F = num_numeric + len(cards)
    X = np.empty((rows, F), np.float32)
    y = np.empty(rows, np.float64)

    def block(i, bias, out_x, out_y):
        rng = np.random.default_rng(children[i])
        m = out_x.shape[0]
        z = rng.standard_normal((m, num_numeric), dtype=np.float32)
        num = np.floor(np.exp(loc + spread * z))
        out_x[:, :num_numeric] = num
        t = (np.log1p(num) - loc) / spread
        logit = np.zeros(m, np.float32)
        for j, a in NUMERIC_LINEAR.items():
            logit += np.float32(a) * t[:, j]
        logit += np.float32(NUMERIC_PRODUCT) * t[:, 1] * t[:, 2]
        logit += np.float32(NUMERIC_SINE) * np.sin(np.float32(2.0) * t[:, 3])
        u = rng.random((len(cards), m))
        for c in range(len(cards)):
            rank = np.searchsorted(cdfs[c], u[c], side="left")
            np.minimum(rank, cards[c] - 1, out=rank)
            out_x[:, num_numeric + c] = perms[c][rank]
            if effects[c] is not None:
                logit += effects[c][rank]
        if out_y is None:           # the calibration pass wants the logits
            return logit
        p = 1.0 / (1.0 + np.exp(-(logit.astype(np.float64) + bias)))
        out_y[:] = rng.random(m) < p
        return None

    # the bias that gives POSITIVE_SHARE on the first block's logits
    m0 = min(BLOCK_ROWS, rows)
    logit0 = block(0, 0.0, np.empty((m0, F), np.float32), None
                   ).astype(np.float64)
    lo, hi = -20.0, 20.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(logit0 + mid)))) < POSITIVE_SHARE:
            lo = mid
        else:
            hi = mid
    bias = 0.5 * (lo + hi)

    def fill(i):
        a, b = starts[i], min(starts[i] + BLOCK_ROWS, rows)
        block(i, bias, X[a:b], y[a:b])

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(len(starts))))
    return X, y


GENERATORS = {"click_log": click_log}
