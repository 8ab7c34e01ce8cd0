"""``readings.py`` for a cell whose table is sparse rows bundled at
binning time: the program's numbers, the control's and each planted
fault's, at the cell's own size, several seeds in one process.

    python3 benchmark/checks/readings_sparse.py --workload allstate_fit \
        --seeds 101 102 --control 2 --faults 1 --out chiprun_out/r.jsonl

Beside ``readings.py``'s faults (``stale_state``, ``altered_leaf``,
``half_batch``; ``altered_split`` on a column that has a next bound, which
a 0/1 column has not) it plants the mechanism's own two,
each a fit made again through the window's own call:

* ``bundle_conflict``  two columns that are non-default together in many
                       rows forced into one bundle, the first winning
                       every such row: the loser is the column the sound
                       fit splits on most among the bundled ones, the
                       winner the commonest bundled column before it in
                       another bundle;
* ``default_dropped``  the bundle expansion without its last line: no
                       member's default bin is made up from the leaf's
                       totals, so every bundled column reads as if no row
                       held its default.

The unbundled control (the same fit on the ``(rows, 4228)`` bins) cannot
run at the cell's size: those bins are 55.7 GB.  At a size a test can
hold it is ``tests/test_sparse_efb.py``'s.

Needs the chip unless ``--rehearse``.  One JSON line a reading.
"""

import argparse
import importlib
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run as harness              # noqa: E402
from benchmark.checks import readings             # noqa: E402


def altered_split(booster, mapper):
    """One threshold of the last tree moved to its column's next bin
    bound: the first split, in node order, on a column that has one (a
    0/1 column has a single bound); failing that, the root's threshold
    moved past every value."""
    import copy
    out = copy.copy(booster)
    out.trees = list(booster.trees)
    t = copy.deepcopy(booster.trees[-1])
    for i in range(t.num_leaves - 1):
        bounds = mapper.upper_bounds[int(t.split_feature[i])]
        k = int(np.searchsorted(bounds, t.threshold[i]))
        if k + 1 < len(bounds):
            t.threshold[i] = bounds[k + 1]
            break
    else:
        t.threshold[0] = np.inf
    out.trees[-1] = t
    return out


def conflicting_table(state):
    """``state["bins"]`` with two co-occurring columns forced into one
    bundle of their own, first wins."""
    from mmlspark_tpu.gbdt import efb
    from mmlspark_tpu.gbdt.binning import SparseBins
    bins, mapper, X = state["bins"], state["mapper"], state["X"]
    spec = bins.spec
    bundled = [j for m in spec.bundles if len(m) > 1 for j in m]
    splits = np.bincount(np.concatenate(
        [t.split_feature[:t.num_leaves - 1] for t in state["booster"].trees]
    ).astype(np.int64), minlength=spec.num_features)
    per_column = np.bincount(X.indices, minlength=spec.num_features)
    # the lowest column wins a shared cell, so the column that loses its
    # rows has to be the later one: the most split on bundled column that
    # has a bundled column of another bundle before it, and of those the
    # commonest
    for victim in sorted(bundled, key=lambda j: (-splits[j],
                                                 -per_column[j])):
        before = [j for j in bundled if j < victim
                  and spec.bundle_of[j] != spec.bundle_of[victim]]
        if before:
            other = max(before, key=lambda j: per_column[j])
            break
    first, second = other, victim
    groups = [[j for j in m if j not in (victim, other)]
              for m in spec.bundles]
    groups = [m for m in groups if m] + [[first, second]]
    nb_of = [mapper.feature_num_bins(j) for j in range(spec.num_features)]
    forced = efb._spec_of(groups, nb_of, spec.default_of, bins.num_bins)
    entries = SparseBins(X.indptr, X.indices, state["entry_bins"],
                         state["zero_bin"], X.shape)
    table, conflict_rows, _ = efb.write_bundles(entries, forced,
                                                bins.missing_bin)
    return (efb.BundledTable(table, forced, bins.num_bins, bins.missing_bin,
                             conflict_rows),
            {"victim": int(victim), "other": int(other),
             "victim_splits": int(splits[victim]),
             "conflict_rows": int(conflict_rows)})


def expand_without_defaults(hist_b, efb):
    """``grower._efb_expand`` less its last line."""
    import jax.numpy as jnp
    f = efb.gather_idx.shape[0]
    flat = hist_b.reshape(-1, hist_b.shape[-1])
    hist = jnp.take(flat, efb.gather_idx.reshape(-1), axis=0)
    hist = hist.reshape(f, hist_b.shape[1], hist_b.shape[2])
    return hist * efb.valid[:, :, None]


def fit_with_defaults_dropped(fit):
    """``fit()`` with the expansion broken; the programs compiled around
    it are dropped before and after."""
    import jax
    from mmlspark_tpu.gbdt import grower
    real = grower._efb_expand
    jax.clear_caches()
    grower._efb_expand = expand_without_defaults
    try:
        return fit()
    finally:
        grower._efb_expand = real
        jax.clear_caches()


def fit_on(state, table):
    """The window's own call on another table (and, where that is
    shorter, on its first rows' labels)."""
    from mmlspark_tpu.gbdt import engine
    real = engine.train

    def swapped(bins, labels, *a, **kw):
        return real(table, labels[:len(table)], *a, **kw)

    engine.train = swapped
    try:
        return state["fit"]()
    finally:
        engine.train = real


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=2)
    ap.add_argument("--faults", type=int, default=1)
    ap.add_argument("--out", default=None)
    ap.add_argument("--bench-json",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from mmlspark_tpu.core.backend import configure_compile_cache
    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("needs a TPU (or --rehearse)", file=sys.stderr)
        return 2

    _, cell, config, traffic = harness.load_cell(args.bench_json,
                                                 args.workload)
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    sink = open(args.out, "a") if args.out else None

    def emit(**kw):
        kw.update(workload=cell["name"], platform=dev.platform,
                  device_kind=dev.device_kind, rehearsal=args.rehearse)
        line = json.dumps(kw, default=str)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    for k, seed in enumerate(args.seeds):
        ctx = harness.Context(cell, config, traffic, seed, args.rehearse,
                              False)
        t0 = time.perf_counter()
        state = driver.setup(ctx)
        booster = state["booster"]
        emit(seed=seed, reading="setup", setup_s=time.perf_counter() - t0,
             bin_s=ctx.counters["bin_s"],
             bundle_s=ctx.counters.get("bundle_s"),
             setup_rss_bytes=ctx.counters["setup_rss_bytes"],
             bundles=state["bins"].table.shape[1],
             moved=state["bins"].moved)

        def read(what, b, precision="float64", **also):
            st = dict(state, booster=b)
            driver.release(ctx, st)
            t1 = time.perf_counter()
            numbers = driver.check(ctx, st, precision=precision)
            emit(seed=seed, reading=what, check_s=time.perf_counter() - t1,
                 **numbers, **also)

        read("program", booster)
        if k < args.control:
            read("control_fp8", booster, precision="fp8")
        if k < args.faults:
            read("stale_state", readings.stale_state(booster, state["y"]))
            read("altered_leaf", readings.altered_leaf(booster))
            read("altered_split", altered_split(booster, state["mapper"]))
            half = state["rows"] // 2
            read("half_batch", fit_on(state, state["bins"][:half]))
            table, what = conflicting_table(state)
            read("bundle_conflict", fit_on(state, table), **what)
            read("default_dropped", fit_with_defaults_dropped(state["fit"]))
        del state, booster
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
