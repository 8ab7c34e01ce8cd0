"""Seeded pixel rows for the multiclass fit cell: mnist8m's shape (28 x 28
grey levels in [0, 1], ten digit classes) made from the seed.

Each class has a prototype stroke map on the 28 x 28 grid: six strokes
whose ends lie in the central 14 x 14 box, and for every pixel the
probability that it is inked, falling off with its distance from the
nearest stroke and nil beyond four pixels of it.  The
prototypes are FIXED (``PROTOTYPE_SEED``, a constant of this module and
of the configuration, never ``--seed``): the seed draws the rows, not
the law they follow.  A row draws its class at MNIST's training shares,
a shift of up to two pixels each way (the deformation), and every pixel
once: inked with the shifted map's probability, and an inked pixel's
level is 255 (a saturated stroke core) a third of the time, else one of
1..254.  Values are ``level / 255`` in float32.

The inked share over the table is MNIST's, about 19%; the outer ring of
two pixels is inked under 0.1% of the time, and no column is constant
(every pixel keeps a floor of ``FLOOR``).  No cell is missing.

Rows are made in fixed blocks, each from its own child of
``SeedSequence(seed)``, in float32 throughout: the table depends on the
seed and the shape only, never on how many threads filled it, and no
float64 copy of it is ever made.
"""

import math
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from functools import lru_cache

import numpy as np

GRID = 28
BLOCK_ROWS = 32768
PROTOTYPE_SEED = 2007
#: MNIST's training images per digit, 0..9 (60 000 in all)
CLASS_COUNTS = (5923, 6742, 5958, 6131, 5842, 5421, 5918, 6265, 5851, 5949)
INKED_SHARE = 0.19
SATURATED = 1.0 / 3.0
FLOOR = 3e-4
SHIFT = 2                   # a row's map is moved by -2..2 pixels each way
STROKES = 6                 # strokes a prototype draws
SIGMA = 2.6                 # pixels: how fast the ink falls off a stroke
REACH = 4.0                 # pixels beyond which a stroke inks nothing


def _segment_distance(px, py, a, b):
    """Distance of every grid point from the segment ``a``-``b``."""
    d = b - a
    t = ((px - a[0]) * d[0] + (py - a[1]) * d[1]) / max(float(d @ d), 1e-9)
    t = np.clip(t, 0.0, 1.0)
    return np.hypot(px - (a[0] + t * d[0]), py - (a[1] + t * d[1]))


@lru_cache(maxsize=None)
def prototypes():
    """``(10, 28, 28)`` float64 ink probabilities of the class prototypes,
    scaled so that over the class shares and the shifts about
    ``INKED_SHARE`` of the pixels are inked."""
    rng = np.random.default_rng(PROTOTYPE_SEED)
    py, px = np.mgrid[0:GRID, 0:GRID].astype(np.float64)
    shape = []
    for _ in CLASS_COUNTS:
        dist = np.full((GRID, GRID), np.inf)
        for _ in range(STROKES):
            a, b = rng.uniform(7.0, 20.0, size=(2, 2))
            dist = np.minimum(dist, _segment_distance(px, py, a, b))
        shape.append(np.where(dist <= REACH, np.exp(-(dist / SIGMA) ** 2),
                              0.0))
    shape = np.stack(shape)
    share = np.asarray(CLASS_COUNTS, np.float64) / sum(CLASS_COUNTS)
    lo, hi = 0.0, 1.0 / shape.max()
    for _ in range(60):        # the scale that inks INKED_SHARE of pixels
        mid = (lo + hi) / 2.0
        p = np.minimum(mid * shape, 0.97) + FLOOR
        if float(share @ p.reshape(len(share), -1).mean(axis=1)) \
                < INKED_SHARE:
            lo = mid
        else:
            hi = mid
    return np.minimum(lo * shape, 0.97) + FLOOR


def _window(features):
    """Pixel indices of the central ``side x side`` window of the grid,
    ``side**2 == features`` (784: the whole grid)."""
    side = math.isqrt(int(features))
    if side * side != features or not 2 <= side <= GRID:
        raise ValueError(f"{features} pixel columns: a square window of "
                         f"the {GRID} x {GRID} grid is needed")
    lo = (GRID - side) // 2
    rows, cols = np.mgrid[lo:lo + side, lo:lo + side]
    return (rows * GRID + cols).ravel()


@lru_cache(maxsize=None)
def shifted_maps(features):
    """``(10 * 25, features)`` float32: each class's map under each shift,
    over the columns kept."""
    base = prototypes()
    pad = np.pad(base, ((0, 0), (SHIFT, SHIFT), (SHIFT, SHIFT)),
                 constant_values=FLOOR)
    keep = _window(features)
    maps = []
    for c in range(base.shape[0]):
        for dy in range(-SHIFT, SHIFT + 1):
            for dx in range(-SHIFT, SHIFT + 1):
                m = pad[c, SHIFT - dy:SHIFT - dy + GRID,
                        SHIFT - dx:SHIFT - dx + GRID]
                maps.append(m.ravel()[keep])
    return np.asarray(maps, np.float32)


def pixel_strokes(seed, rows, features, threads=None):
    """``(X float32 (rows, features) in [0, 1], y float64 (rows,) class
    ids 0..9)``.  Each thread fills its blocks through buffers of its own,
    made once: a block allocates nothing of its size (an allocator that
    hands freed memory back slowly would otherwise hold many blocks'
    worth of it at once)."""
    maps = shifted_maps(int(features))
    shifts = (2 * SHIFT + 1) ** 2
    share = np.asarray(CLASS_COUNTS, np.float64) / sum(CLASS_COUNTS)
    starts = list(range(0, rows, BLOCK_ROWS))
    children = np.random.SeedSequence(int(seed)).spawn(len(starts))
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float64)
    sat = np.float32(SATURATED)
    spread = np.float32((1.0 - SATURATED) / 254.0)
    local = threading.local()

    def fill(i):
        a, b = starts[i], min(starts[i] + BLOCK_ROWS, rows)
        if not hasattr(local, "u"):
            local.u = np.empty((BLOCK_ROWS, features), np.float32)
            local.p = np.empty((BLOCK_ROWS, features), np.float32)
            local.dark = np.empty((BLOCK_ROWS, features), bool)
        u, p, dark = local.u[:b - a], local.p[:b - a], local.dark[:b - a]
        rng = np.random.default_rng(children[i])
        cls = rng.choice(len(share), size=b - a, p=share)
        which = cls * shifts + rng.integers(0, shifts, size=b - a)
        rng.random(out=u, dtype=np.float32)
        np.take(maps, which, axis=0, out=p)
        np.greater_equal(u, p, out=dark)
        # one draw a cell: inked where u < p, and then u / p is uniform
        # on [0, 1): its first third saturates, the rest spreads over
        # the levels 1..254
        np.divide(u, p, out=u)
        np.subtract(u, sat, out=u)
        np.divide(u, spread, out=u)
        np.floor(u, out=u)
        np.minimum(u, np.float32(253.0), out=u)
        np.putmask(u, u < 0, np.float32(254.0))
        np.add(u, np.float32(1.0), out=u)
        blk = X[a:b]
        np.divide(u, np.float32(255.0), out=blk)
        np.putmask(blk, dark, np.float32(0.0))
        y[a:b] = cls

    threads = threads or min(16, os.cpu_count() or 1)
    with ThreadPoolExecutor(int(threads)) as pool:
        list(pool.map(fill, range(len(starts))))
    return X, y


GENERATORS = {"pixel_strokes": pixel_strokes}
