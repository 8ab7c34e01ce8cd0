"""Seeded inputs for a table that is mostly missing by station: Bosch's
production-line shape, binary labels.

A part passes some stations of the line and skips the rest; a station it
skipped leaves all of its columns empty (NaN) together.  The LAW is the
configuration's and fixed (``LAW_SEED``): how many columns each station
measures, a few dozen route templates (the stations each route visits),
their Zipf popularity, and the label's weights.  The seed draws the
rows: each part's route, its present values (standard normal), its
label noise.

The label reads the route and the present values: a per-route effect
(zero mean over the popularity), a per-station effect of being present
(centred on the station's presence), a weak dense term over every
present value, a strong term on the first column of the five most
visited stations, and noise; it is thresholded at 0, about 50/50.  So
the absence itself carries signal, as on the real line, and where a
node's missing rows belong differs from split to split.

Rows are made in fixed blocks, each from its own child of
``SeedSequence(seed)``: the table depends on the seed and the shape
only, never on how many threads filled it.  Each thread fills its
blocks through buffers of its own, made once.
"""

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 32768
LAW_SEED = 0xB05C4          # the configuration's law, not the run's seed
STATIONS = 52               # stations S0..S51 of lines L0..L3
ROUTES = 48                 # route templates
ZIPF = 1.1                  # popularity of the route templates
VISIT = (0.02, 0.3)         # a station's visit rate across templates
POPULAR = 16                # every station is on one of these routes


def law(features, stations=STATIONS, routes=ROUTES):
    """The configuration's fixed law for ``features`` columns: a dict of
    ``station_of`` (features,) int, ``visits`` (routes, stations) bool,
    ``popularity`` (routes,), ``route_effect`` (routes,),
    ``station_effect`` (stations,), ``w`` (features,) float32, ``strong``
    (column ids) and ``present_share``, the expected share of present
    cells."""
    if features < 4:
        raise ValueError("the planted signal needs at least 4 features")
    stations = max(2, min(stations, features // 2))
    rng = np.random.default_rng(LAW_SEED)
    # columns per station: at least 2, the rest by a skewed share
    share = rng.gamma(0.8, size=stations)
    extra = np.floor(share / share.sum() * (features - 2 * stations))
    sizes = 2 + extra.astype(np.int64)
    sizes[np.argsort(-share)[:features - int(sizes.sum())]] += 1
    station_of = np.repeat(np.arange(stations), sizes)
    rate = rng.uniform(*VISIT, size=stations)
    visits = rng.random((routes, stations)) < rate[None, :]
    visits[np.arange(routes), rng.integers(0, stations, routes)] = True
    lonely = ~visits[:POPULAR].any(axis=0)
    visits[rng.integers(0, POPULAR, int(lonely.sum())),
           np.flatnonzero(lonely)] = True
    popularity = 1.0 / np.arange(1, routes + 1) ** ZIPF
    popularity /= popularity.sum()
    presence = popularity @ visits                         # (stations,)
    route_effect = rng.standard_normal(routes)
    route_effect -= popularity @ route_effect
    station_effect = rng.standard_normal(stations) * 0.8
    w = (rng.standard_normal(features)
         / np.sqrt(features * 0.25)).astype(np.float32)
    first = np.searchsorted(station_of, np.arange(stations))
    strong = first[np.argsort(-presence)[:5]]
    return {"station_of": station_of, "visits": visits,
            "popularity": popularity, "presence": presence,
            "route_effect": route_effect, "station_effect": station_effect,
            "w": w, "strong": strong,
            "present_share": float(presence[station_of].mean())}


def station_routes(seed, rows, features, threads=4):
    """``(X float32 (rows, features) with NaN, y float64 (rows,) in
    {0, 1})``."""
    lw = law(features)
    station_of, visits = lw["station_of"], lw["visits"]
    cell_present = visits[:, station_of]                  # (routes, f)
    starts = list(range(0, rows, BLOCK_ROWS))
    children = np.random.SeedSequence(int(seed)).spawn(len(starts))
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float64)
    centred = (visits.astype(np.float64) - lw["presence"][None, :]) \
        @ lw["station_effect"]                            # (routes,)
    base = lw["route_effect"] + centred
    strong = lw["strong"]
    local = threading.local()

    def fill(i):
        a, b = starts[i], min(starts[i] + BLOCK_ROWS, rows)
        if not hasattr(local, "mask"):
            local.mask = np.empty((BLOCK_ROWS, features), bool)
            local.vals = np.empty((BLOCK_ROWS, features), np.float32)
        mask, vals = local.mask[:b - a], local.vals[:b - a]
        rng = np.random.default_rng(children[i])
        route = rng.choice(len(base), size=b - a, p=lw["popularity"])
        np.take(cell_present, route, axis=0, out=mask)
        blk = X[a:b]
        rng.standard_normal(out=blk, dtype=np.float32)
        noise = rng.standard_normal(b - a)
        np.multiply(blk, mask, out=vals)                  # absent -> 0
        logits = (base[route] + vals @ lw["w"]
                  + 1.2 * vals[:, strong].sum(axis=1)
                  + vals[:, strong[0]] * vals[:, strong[1]]
                  + 0.5 * noise)
        y[a:b] = logits > 0
        np.logical_not(mask, out=mask)
        np.copyto(blk, np.float32(np.nan), where=mask)

    with ThreadPoolExecutor(max(1, int(threads))) as pool:
        list(pool.map(fill, range(len(starts))))
    return X, y


GENERATORS = {"station_routes": station_routes}
