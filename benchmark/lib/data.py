"""Seeded inputs for the fit cells: dense standard-normal rows with a
planted signal, binary labels.

The signal is ``bench.py``'s (a strong linear feature, an interaction and
a sine on the first four columns, label noise) plus one weak dense linear
term over every column, so that a wide table has structure in all of its
features, as Epsilon does, and not in four of two thousand.

Rows are made in fixed blocks, each from its own child of
``SeedSequence(seed)``: the table depends on the seed and the shape only,
never on how many threads filled it.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 32768


def planted_normal(seed, rows, features, threads=4):
    """``(X float32 (rows, features), y float64 (rows,) in {0, 1})``."""
    if features < 4:
        raise ValueError("the planted signal needs at least 4 features")
    starts = list(range(0, rows, BLOCK_ROWS))
    root = np.random.SeedSequence(int(seed))
    children = root.spawn(len(starts) + 1)
    w = np.random.default_rng(children[-1]).standard_normal(
        features, dtype=np.float32) / np.float32(np.sqrt(features))
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float64)

    def fill(i):
        a, b = starts[i], min(starts[i] + BLOCK_ROWS, rows)
        rng = np.random.default_rng(children[i])
        blk = X[a:b]
        rng.standard_normal(out=blk, dtype=np.float32)
        noise = rng.standard_normal(b - a, dtype=np.float32)
        logits = (blk[:, 0] * np.float32(1.5) + blk[:, 1] * blk[:, 2]
                  + np.sin(blk[:, 3] * np.float32(2.0)) + blk @ w
                  + noise * np.float32(0.5))
        y[a:b] = logits > 0

    with ThreadPoolExecutor(max(1, int(threads))) as pool:
        list(pool.map(fill, range(len(starts))))
    return X, y


GENERATORS = {"planted_normal": planted_normal}
