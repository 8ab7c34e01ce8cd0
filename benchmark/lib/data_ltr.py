"""Seeded inputs for a cell whose rows come in groups: a learning-to-rank
table in the shape of a web-search LETOR set.  Documents are stored query
by query, as the source's ``train.txt`` is; every query has its own size;
labels are grades 0..4 of a latent relevance.

* Query sizes: a log-normal of sigma 0.7 around the mean the shape gives
  (rows over queries), rounded, clipped to 1 .. 4096 and moved by single
  documents until the sizes sum to the rows.
* Feature values: standard normal, as ``lib/data.py``'s; every eighth
  column (7, 15, ...) is rounded to halves, units or quarters, so that it
  holds a dozen or two distinct values.
* Relevance: ``lib/data.py``'s planted signal on the first four columns
  and its weak dense term, plus an effect per query and noise.  The grade
  is where the relevance falls among four cuts, the 96.0, 98.0, 99.0 and
  99.6% points of the first block's relevance: about 96% of documents are
  irrelevant and the grades 1..4 take 2, 1, 0.6 and 0.4%.  A query with a
  high effect has many relevant documents and most queries a few or none.

Rows are made in fixed blocks, each from its own child of
``SeedSequence(seed)``: the table depends on the seed and the shape only,
never on how many threads filled it.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 32768
SIZE_SIGMA = 0.7
MAX_QUERY = 4096
FEW_VALUED_EVERY = 8            # columns 7, 15, 23, ...
FEW_VALUED_STEPS = (2.0, 1.0, 4.0)
GRADE_POINTS = (0.96, 0.98, 0.99, 0.996)
QUERY_EFFECT = 0.7
NOISE = 0.5


def query_sizes(rng, rows, queries):
    """``queries`` sizes in 1 .. MAX_QUERY that sum to ``rows``."""
    if not queries <= rows <= queries * MAX_QUERY:
        raise ValueError("rows outside queries .. queries * MAX_QUERY")
    raw = rng.lognormal(0.0, SIZE_SIGMA, queries)
    sizes = np.clip(np.rint(raw * (rows / raw.sum())), 1,
                    MAX_QUERY).astype(np.int64)
    # single documents to or from queries drawn at random, until it fits
    while True:
        gap = rows - int(sizes.sum())
        if gap == 0:
            return sizes
        room = np.flatnonzero(sizes < MAX_QUERY if gap > 0 else sizes > 1)
        take = rng.choice(room, size=min(abs(gap), room.size), replace=False)
        sizes[take] += 1 if gap > 0 else -1


def ltr_queries(seed, rows, features, queries, threads=4):
    """``(X float32 (rows, features), y float64 (rows,) in 0..4,
    q int32 (rows,) query ids 0..queries-1, ascending)``."""
    if features < 4:
        raise ValueError("the planted signal needs at least 4 features")
    starts = list(range(0, rows, BLOCK_ROWS))
    root = np.random.SeedSequence(int(seed))
    children = root.spawn(len(starts) + 2)
    head = np.random.default_rng(children[-1])
    w = head.standard_normal(features, dtype=np.float32) \
        / np.float32(np.sqrt(features))
    sizes = query_sizes(np.random.default_rng(children[-2]), rows, queries)
    effect = head.standard_normal(queries).astype(np.float32) \
        * np.float32(QUERY_EFFECT)
    q = np.repeat(np.arange(queries, dtype=np.int32), sizes)
    few = np.arange(FEW_VALUED_EVERY - 1, features, FEW_VALUED_EVERY)
    steps = np.asarray(FEW_VALUED_STEPS, np.float32)[
        np.arange(few.size) % len(FEW_VALUED_STEPS)]
    X = np.empty((rows, features), np.float32)
    relevance = np.empty(rows, np.float32)

    def fill(i):
        a, b = starts[i], min(starts[i] + BLOCK_ROWS, rows)
        rng = np.random.default_rng(children[i])
        blk = X[a:b]
        rng.standard_normal(out=blk, dtype=np.float32)
        blk[:, few] = np.rint(blk[:, few] * steps) / steps
        noise = rng.standard_normal(b - a, dtype=np.float32)
        relevance[a:b] = (
            blk[:, 0] * np.float32(1.5) + blk[:, 1] * blk[:, 2]
            + np.sin(blk[:, 3] * np.float32(2.0)) + blk @ w
            + effect[q[a:b]] + noise * np.float32(NOISE))

    fill(0)
    cuts = np.quantile(relevance[:min(BLOCK_ROWS, rows)], GRADE_POINTS)
    with ThreadPoolExecutor(max(1, int(threads))) as pool:
        list(pool.map(fill, range(1, len(starts))))
    y = np.searchsorted(cuts, relevance, side="right").astype(np.float64)
    return X, y, q


GENERATORS = {"ltr_queries": ltr_queries}
