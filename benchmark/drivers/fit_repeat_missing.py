"""Driver ``fit_repeat_missing``: ``fit_repeat``'s window on a table that
is mostly missing (benchmark/traffic/fit_repeat_missing.json).

Set-up makes the rows from the seed (``lib/data_missing``: whole
stations empty together, NaN), builds the estimator from the
configuration's parameters, bins on the host (NaN to the missing bin),
and makes ONE warm-up ``engine.train`` call with the window's own tree
count.  The window repeats that call, and the end-to-end metric is
``fit_tree_ms``, as ``fit_repeat``'s.  Set-up also keeps
``empty_share``: the share of the table's cells that are NaN.

The exported text is kept under ``state["missing_model_text"]``:
``metrics/missing_fit_tree_mfu.py`` parses it with the reference that
reads ``decision_type``, and the comparison that decides ``correct`` is
``reference/gbdt_missing.py``'s.

A program whose ``GrowerConfig`` has no ``has_missing`` scans one
default direction only, sending every missing value right: it cannot
grow this cell's trees, and set-up says so and exits 4 at once, before
making any rows.
"""

import dataclasses
import importlib
import sys
import time

import numpy as np

from benchmark.drivers.fit_repeat import (_sizes,  # noqa: F401
                                          end_to_end, reference_config,
                                          window)
from benchmark.lib import data_missing

CANNOT_RUN_EXIT = 4             # the program cannot run this configuration


def setup(ctx):
    """Everything before the window; returns the state the window drives."""
    from mmlspark_tpu.gbdt.grower import GrowerConfig
    if "has_missing" not in {f.name for f in dataclasses.fields(
            GrowerConfig)}:
        # fail cleanly and at once, before any rows are made
        print("[bench] this program's split scan has one default "
              "direction (GrowerConfig has no has_missing): it cannot "
              "run this cell", file=sys.stderr, flush=True)
        raise SystemExit(CANNOT_RUN_EXIT)
    from mmlspark_tpu import gbdt
    from mmlspark_tpu.core.profiler import get_profiler
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    from mmlspark_tpu.gbdt.objectives import get_objective

    rows, features, params = _sizes(ctx)
    trees = int(ctx.traffic["trees_per_fit"])
    with ctx.span("make_rows"):
        X, y = data_missing.GENERATORS[ctx.config["data"]](
            ctx.seed, rows, features)

    est = getattr(gbdt, ctx.config["estimator"])(
        numIterations=trees, parallelism=ctx.traffic["parallelism"],
        **params)
    labels = est._prepare_labels(y)
    objective = get_objective(
        getattr(est, "_resolved_objective", None) or est.getObjective(),
        num_class=getattr(est, "_num_class", 1), **est._objective_kwargs())
    train_params = est._train_params()

    t0 = time.perf_counter()
    with ctx.span("bin"):
        mapper = fit_bin_mapper(X, max_bin=est.getMaxBin(),
                                seed=est.getSeed())
        bins = mapper.transform_packed(X)
    bin_s = time.perf_counter() - t0

    def fit():
        return engine.train(bins, labels, None, mapper, objective,
                            train_params, mesh=None)

    with ctx.span("warmup_fit"):
        booster = fit()
    ctx.counters["bin_s"] = bin_s
    ctx.counters["empty_share"] = float(
        np.count_nonzero(bins == mapper.missing_bin) / bins.size)
    ctx.counters["last_fit_info"] = dict(engine.last_fit_info)
    return {"fit": fit, "X": X, "y": y, "bins": bins, "trees": trees,
            "booster": booster, "profiler": get_profiler(),
            "est": est, "features": features, "rows": rows,
            "num_bins": mapper.num_total_bins, "mapper": mapper}


def release(ctx, state):
    """Drop what holds device memory before the reference runs."""
    state["missing_model_text"] = \
        state["booster"].save_native_model_string()
    state.pop("fit")
    state.pop("booster")


def check(ctx, state, precision="float64"):
    """The comparison that decides ``correct``: the last fit the window
    returned, against the plain reference that walks NaN by the export's
    default directions."""
    ref = importlib.import_module(
        f"benchmark.reference.{ctx.config['reference']}")
    return ref.check_fit(
        state["missing_model_text"], state["X"], state["y"], state["bins"],
        reference_config(ctx, state), seed=ctx.seed,
        expect_trees=state["trees"],
        sample_nodes=int(ctx.traffic["check_nodes"]),
        sample_features=int(ctx.traffic["check_bin_features"]),
        precision=precision)
