"""Seeded one-hot coded rows, CSR out and never dense: a few numeric
columns followed by the one-hot blocks of categorical source columns, and
a rare binary label (benchmark/configs/allstate.json).

What is taken from the source is the SHAPE: rows, the count of columns
after one-hot coding, which source columns are numeric and which
categorical.  What is assumed, and the configuration lists it under
``assumed``:

* a categorical source column of ``k`` values is ``k`` adjacent 0/1
  columns of which every row sets exactly one; the value is drawn as a
  RANK from a Zipf law truncated at ``k`` (``p(r) ~ r**-ZIPF_EXPONENT``)
  and sits in column ``perm[rank]`` of its block, a permutation as
  ``lib/data_clicks`` has one;
* the numeric columns come first: ``dense`` of them standard normal with
  a spread and location per column (never 0), then ``sparse`` of them 0
  in ``SPARSE_ZERO_SHARE`` of the rows and a positive log-normal
  elsewhere, then two year columns of 3 and 29 distinct values;
* the label is Bernoulli of a logistic whose argument adds a few numeric
  terms, an effect per category on ``EFFECT_BLOCKS`` of the blocks
  (normal; the two widest among them) and a bias set so that
  ``POSITIVE_SHARE`` of the labels are 1.

The permutations and the effects are the CONFIGURATION's, drawn once from
``TABLE_SEED`` and the same for every seed: the seed draws the rows, not
the law they come from.  (Drawn from the seed they gave every seed
another label model, hence trees of another shape: 120 to 162 x 10^6 rows
histogrammed a fit and ``fit_tree_ms`` 6 325 to 6 970 on six seeds, a
spread of 6%, PERF.md Findings, PR 33.)  Rows are made in fixed blocks,
each from its own child of ``SeedSequence(seed)``: the table depends on
the seed and the shape only, never on the threads.  A row's entries are
in ascending column order.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 32768
ZIPF_EXPONENT = 1.05
POSITIVE_SHARE = 0.0072
SPARSE_ZERO_SHARE = 0.75
YEARS = (3, 29)             # distinct values of the two year columns
TABLE_SEED = 4228           # of the permutations and the effects
#: blocks (by position among them) that carry an effect per category,
#: and its spread; the rest are noise columns
EFFECT_SPREAD = {0: 0.5, 1: 0.6, 2: 0.5, 3: 0.4, 8: 0.4, 15: 0.5, 16: 0.4}
EFFECT_BLOCKS = tuple(sorted(EFFECT_SPREAD))
#: numeric terms of the label, on the standardised dense columns
NUMERIC_LINEAR = {0: 0.6, 3: -0.5}
NUMERIC_PRODUCT = 0.4       # on z_1 * z_2
NUMERIC_SINE = 0.4          # on sin(2 z_4)
SPARSE_TERM = 0.3           # on log1p of the first sparse column
YEAR_TERM = -0.04           # a step of the second year column


class Rows:
    """CSR rows: ``indptr`` (rows + 1,) int64, ``indices`` (nnz,) int32
    ascending within a row, ``values`` (nnz,) float32, ``shape``."""

    def __init__(self, indptr, indices, values, shape):
        self.indptr, self.indices, self.values = indptr, indices, values
        self.shape = shape


def _zipf_cdf(k):
    w = np.arange(1, k + 1, dtype=np.float64) ** -ZIPF_EXPONENT
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def onehot_rows(seed, rows, block_sizes, dense=8, sparse=4, threads=None):
    """``(Rows, y float64 (rows,) in {0, 1})`` of ``dense + sparse + 2``
    numeric columns followed by ``sum(block_sizes)`` one-hot columns."""
    sizes = [int(k) for k in block_sizes]
    if dense < 5 or sparse < 1 or len(sizes) <= max(EFFECT_BLOCKS):
        raise ValueError("the planted label needs 5 dense and 1 sparse "
                         f"numeric columns and {max(EFFECT_BLOCKS) + 1} "
                         "blocks")
    threads = threads or min(12, os.cpu_count() or 1)
    num = dense + sparse + len(YEARS)
    starts = list(range(0, rows, BLOCK_ROWS))
    children = np.random.SeedSequence(int(seed)).spawn(len(starts))
    tables = np.random.SeedSequence(TABLE_SEED).spawn(len(sizes))
    offsets = num + np.concatenate([[0], np.cumsum(sizes)[:-1]])
    features = num + int(sum(sizes))
    loc = (0.3 * (np.arange(dense) % 5) - 0.6).astype(np.float32)
    spread = (0.5 + 0.25 * ((np.arange(dense) * 3) % 7)).astype(np.float32)
    cdfs, perms, effects = [], [], []
    for c, k in enumerate(sizes):
        rng = np.random.default_rng(tables[c])
        cdfs.append(_zipf_cdf(k))
        perms.append(rng.permutation(k).astype(np.int32))
        effects.append(rng.standard_normal(k, dtype=np.float32)
                       * np.float32(EFFECT_SPREAD[c])
                       if c in EFFECT_SPREAD else None)
    width = num + len(sizes)        # entries of a row with no zero

    def block(i, bias):
        """One block's ``(indices (m, width), values, keep, y)``; with
        ``bias`` None the logits, for the calibration."""
        rng = np.random.default_rng(children[i])
        m = min(BLOCK_ROWS, rows - starts[i])
        idx = np.empty((m, width), np.int32)
        val = np.ones((m, width), np.float32)
        keep = np.ones((m, width), bool)
        idx[:, :num] = np.arange(num, dtype=np.int32)
        z = rng.standard_normal((m, dense), dtype=np.float32)
        x = loc + spread * z
        x[x == 0] = np.float32(1e-6)
        val[:, :dense] = x
        sp = np.exp(rng.standard_normal((m, sparse), dtype=np.float32))
        live = rng.random((m, sparse)) >= SPARSE_ZERO_SHARE
        val[:, dense:dense + sparse] = sp
        keep[:, dense:dense + sparse] = live
        years = np.stack([rng.integers(0, k, m) for k in YEARS], axis=1)
        val[:, dense + sparse:num] = (np.asarray([2005, 1981]) + years
                                      ).astype(np.float32)
        logit = np.zeros(m, np.float32)
        for j, a in NUMERIC_LINEAR.items():
            logit += np.float32(a) * z[:, j]
        logit += np.float32(NUMERIC_PRODUCT) * z[:, 1] * z[:, 2]
        logit += np.float32(NUMERIC_SINE) * np.sin(np.float32(2) * z[:, 4])
        logit += np.float32(SPARSE_TERM) * np.where(
            live[:, 0], np.log1p(sp[:, 0]), np.float32(0))
        logit += np.float32(YEAR_TERM) * years[:, 1].astype(np.float32)
        u = rng.random((len(sizes), m))
        for c, k in enumerate(sizes):
            rank = np.minimum(np.searchsorted(cdfs[c], u[c], side="left"),
                              k - 1)
            idx[:, num + c] = offsets[c] + perms[c][rank]
            if effects[c] is not None:
                logit += effects[c][rank]
        if bias is None:
            return logit
        p = 1.0 / (1.0 + np.exp(-(logit.astype(np.float64) + bias)))
        return idx[keep], val[keep], keep.sum(axis=1), rng.random(m) < p

    # the bias that gives POSITIVE_SHARE on the first block's logits
    logit0 = block(0, None).astype(np.float64)
    lo, hi = -30.0, 20.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(logit0 + mid)))) < POSITIVE_SHARE:
            lo = mid
        else:
            hi = mid
    bias = 0.5 * (lo + hi)

    with ThreadPoolExecutor(threads) as pool:
        parts = list(pool.map(lambda i: block(i, bias), range(len(starts))))
    indptr = np.zeros(rows + 1, np.int64)
    np.cumsum(np.concatenate([p[2] for p in parts]), out=indptr[1:])
    X = Rows(indptr, np.concatenate([p[0] for p in parts]),
             np.concatenate([p[1] for p in parts]), (rows, features))
    return X, np.concatenate([p[3] for p in parts]).astype(np.float64)


GENERATORS = {"onehot_rows": onehot_rows}
