"""Driver ``fit_repeat_sparse``: ``fit_repeat``'s window on a one-hot
coded table that reaches the program as SPARSE rows and is bundled at
binning time (benchmark/traffic/fit_repeat_sparse.json).

Set-up makes the CSR rows from the seed (``lib/data_onehot``: never a
dense array), and then what ``LightGBMBase._fit`` makes of a sparse
feature column with ``enableBundle``, by the same calls: the bin mapper
from the sparse rows, the bin of every entry, the bundle plan and the
``(rows, G)`` bundled table (``gbdt/efb.bundle_for_training``), once.
The window repeats ``engine.train`` on that bundled table: no binning,
bundling, data generation or compile is inside it.  The window, the
end-to-end metric and the keys handed to the per-layer readers are
``fit_repeat``'s.

A program without the sparse ingest cannot run this cell: set-up says so
and exits 4 at once, before making any rows.

The exported text is kept under ``state["sparse_model_text"]``, not
``"model_text"``: ``metrics/sparse_fit_tree_mfu.py`` is this cell's
whole-step share (``lib/work.py``'s dense count would overstate the bins
read a hundredfold here).
"""

import importlib
import resource
import sys
import time

from benchmark.drivers.fit_repeat import (end_to_end,  # noqa: F401
                                          reference_config, window)
from benchmark.lib import data_onehot

CANNOT_RUN_EXIT = 4             # the program cannot run this configuration
BUNDLE_SPANS = ("bin.bundle_plan", "bin.bundle_build")


def _sizes(ctx):
    cfg = ctx.config
    over = cfg.get("rehearsal", {}) if ctx.rehearse else {}
    params = dict(cfg["params"])
    params.update(over.get("params", {}))
    blocks = [int(k) for k in cfg["onehot_blocks"].values()]
    cap = over.get("block_cap")
    if cap:
        blocks = [min(k, int(cap)) for k in blocks]
    return int(over.get("rows", cfg["rows"])), blocks, params


def setup(ctx):
    """Everything before the window; returns the state the window drives."""
    try:
        from mmlspark_tpu.core.schema import SparseColumn
        from mmlspark_tpu.gbdt.efb import (bundle_for_training,
                                           bundling_applies)
    except ImportError as e:
        # fail cleanly and at once, before any rows are made
        print(f"[bench] this program has no sparse ingest ({e}): it "
              "cannot run this cell", file=sys.stderr, flush=True)
        raise SystemExit(CANNOT_RUN_EXIT)
    from mmlspark_tpu import gbdt
    from mmlspark_tpu.core.profiler import get_profiler
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    from mmlspark_tpu.gbdt.objectives import get_objective

    rows, blocks, params = _sizes(ctx)
    dense = int(ctx.config["numeric_dense"])
    sparse = int(ctx.config["numeric_sparse"])
    trees = int(ctx.traffic["trees_per_fit"])
    with ctx.span("make_rows"):
        X, y = data_onehot.GENERATORS[ctx.config["data"]](
            ctx.seed, rows, blocks, dense, sparse)
    if not ctx.rehearse and X.shape[1] != int(ctx.config["features"]):
        raise ValueError(f"{X.shape[1]} columns made, the configuration "
                         f"states {ctx.config['features']}")

    est = getattr(gbdt, ctx.config["estimator"])(
        numIterations=trees, parallelism=ctx.traffic["parallelism"],
        **params)
    labels = est._prepare_labels(y)
    objective = get_objective(
        getattr(est, "_resolved_objective", None) or est.getObjective(),
        num_class=getattr(est, "_num_class", 1), **est._objective_kwargs())
    train_params = est._train_params()
    profiler = get_profiler()

    before = {s["id"] for s in profiler.spans()}
    t0 = time.perf_counter()
    with ctx.span("bin"):
        # as LightGBMBase._fit bins and bundles a sparse feature column
        column = SparseColumn(X.indptr, X.indices, X.values, X.shape)
        mapper = fit_bin_mapper(column, max_bin=est.getMaxBin(),
                                seed=est.getSeed())
        binned = mapper.bin_entries(column)
        bins = None
        if bundling_applies(mapper, train_params.enable_bundle):
            bins = bundle_for_training(
                binned, mapper, train_params.max_conflict_rate,
                train_params.seed, train_params.verbosity)
        if bins is None:
            raise ValueError("the configuration's table did not bundle")
    bin_s = time.perf_counter() - t0
    took = [s["end"] - s["start"] for s in profiler.spans()
            if s["name"] in BUNDLE_SPANS and s["id"] not in before]

    def fit():
        return engine.train(bins, labels, None, mapper, objective,
                            train_params, mesh=None)

    with ctx.span("warmup_fit"):
        booster = fit()
    ctx.counters["bin_s"] = bin_s
    if took:
        ctx.counters["bundle_s"] = sum(took)
    ctx.counters["last_fit_info"] = dict(engine.last_fit_info)
    ctx.counters["setup_rss_bytes"] = 1024 * resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss
    print(f"[bench] bundled {X.shape[1]} columns into "
          f"{bins.table.shape[1]} ({bins.moved} members moved out of the "
          f"sample's plan, {bins.conflict_rows} conflict rows), "
          f"{X.indices.size} entries; binning and bundling {bin_s:.1f} s; "
          f"peak resident set "
          f"{ctx.counters['setup_rss_bytes'] / 1e9:.2f} GB",
          file=sys.stderr, flush=True)
    return {"fit": fit, "X": X, "y": y, "trees": trees,
            "entry_bins": binned.bins, "zero_bin": binned.implicit_bin,
            "bins": bins, "booster": booster, "profiler": profiler,
            "est": est, "features": X.shape[1], "rows": rows,
            "num_bins": mapper.num_total_bins, "mapper": mapper,
            "onehot_blocks": blocks,
            "numeric_columns": dense + sparse + len(data_onehot.YEARS)}


def release(ctx, state):
    """Drop what holds device memory before the reference runs."""
    state["sparse_model_text"] = state["booster"].save_native_model_string()
    state.pop("fit")
    state.pop("booster")


def check(ctx, state, precision="float64"):
    """The comparison that decides ``correct``: the last fit the window
    returned, against the plain reference on the CSR rows."""
    ref = importlib.import_module(
        f"benchmark.reference.{ctx.config['reference']}")
    return ref.check_fit(
        state["sparse_model_text"], state["X"], state["y"],
        state["entry_bins"], state["zero_bin"],
        reference_config(ctx, state), seed=ctx.seed,
        expect_trees=state["trees"],
        sample_nodes=int(ctx.traffic["check_nodes"]),
        sample_features=int(ctx.traffic["check_bin_features"]),
        precision=precision)
