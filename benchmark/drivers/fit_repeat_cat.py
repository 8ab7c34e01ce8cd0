"""Driver ``fit_repeat_cat``: ``fit_repeat``'s window on a table with
categorical columns (benchmark/traffic/fit_repeat_cat.json).

Set-up is ``fit_repeat.setup`` with the two things a categorical
configuration adds: the rows come from ``lib/data_clicks`` (the
configuration's cardinalities), and the bin mapper is fitted with
``categorical_features`` exactly as ``LightGBMBase._fit`` passes them
(``categoricalSlotIndexes``).  The window, the end-to-end metric and the
keys handed to the per-layer readers are ``fit_repeat``'s.

The exported text is kept under ``state["cat_model_text"]``, not
``"model_text"``: ``metrics/fit_tree_mfu.py`` parses that key with a
reader that knows no categorical split, finds nothing and is left out;
``metrics/cat_fit_tree_mfu.py`` is this cell's whole-step share.
"""

import importlib
import sys
import time

from benchmark.drivers.fit_repeat import (_sizes, end_to_end,  # noqa: F401
                                          reference_config, window)
from benchmark.lib import data_clicks


FLOAT32_EXACT_ROWS = 1 << 24    # what a float32 row count holds exactly
CANNOT_RUN_EXIT = 4             # the program cannot run this configuration


def _cardinalities(ctx):
    cards = [int(c) for c in ctx.config["categorical_cardinalities"]]
    cap = ctx.config.get("rehearsal", {}).get("cardinality_cap") \
        if ctx.rehearse else None
    return [min(c, int(cap)) for c in cards] if cap else cards


def setup(ctx):
    """Everything before the window; returns the state the window drives."""
    from mmlspark_tpu import gbdt
    from mmlspark_tpu.core.profiler import get_profiler
    from mmlspark_tpu.gbdt import engine, grower
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    from mmlspark_tpu.gbdt.objectives import get_objective

    rows, features, params = _sizes(ctx)
    exact = getattr(grower, "EXACT_COUNT_ROWS", FLOAT32_EXACT_ROWS)
    if rows > exact:
        # fail cleanly and at once: such a program's exported counts are
        # off at every node over 2^24 rows (``count_mismatch``), and its
        # compile for 3e7 rows does not end (PERF.md Findings, PR 27)
        print(f"[bench] this program counts a node's rows exactly up to "
              f"{exact}; the configuration has {rows}: it cannot run "
              "this cell", file=sys.stderr, flush=True)
        raise SystemExit(CANNOT_RUN_EXIT)
    trees = int(ctx.traffic["trees_per_fit"])
    cards = _cardinalities(ctx)
    num_numeric = int(ctx.config["numeric_features"])
    if num_numeric + len(cards) != features:
        raise ValueError("numeric + categorical columns != features")
    with ctx.span("make_rows"):
        X, y = data_clicks.GENERATORS[ctx.config["data"]](
            ctx.seed, rows, cards, num_numeric)

    est = getattr(gbdt, ctx.config["estimator"])(
        numIterations=trees, parallelism=ctx.traffic["parallelism"],
        **params)
    labels = est._prepare_labels(y)
    objective = get_objective(
        getattr(est, "_resolved_objective", None) or est.getObjective(),
        num_class=getattr(est, "_num_class", 1), **est._objective_kwargs())
    train_params = est._train_params()
    cat_idx = sorted(set(est.getCategoricalSlotIndexes() or []))

    t0 = time.perf_counter()
    with ctx.span("bin"):
        mapper = fit_bin_mapper(X, max_bin=est.getMaxBin(),
                                seed=est.getSeed(),
                                categorical_features=cat_idx or None)
        bins = mapper.transform_packed(X)
    bin_s = time.perf_counter() - t0

    def fit():
        return engine.train(bins, labels, None, mapper, objective,
                            train_params, mesh=None)

    with ctx.span("warmup_fit"):
        booster = fit()
    ctx.counters["bin_s"] = bin_s
    ctx.counters["last_fit_info"] = dict(engine.last_fit_info)
    return {"fit": fit, "X": X, "y": y, "bins": bins, "trees": trees,
            "booster": booster, "profiler": get_profiler(),
            "est": est, "features": features, "rows": rows,
            "num_bins": mapper.num_total_bins, "mapper": mapper,
            "cat_idx": cat_idx}


def release(ctx, state):
    """Drop what holds device memory before the reference runs."""
    state["cat_model_text"] = state["booster"].save_native_model_string()
    state.pop("fit")
    state.pop("booster")


def check(ctx, state, precision="float64"):
    """The comparison that decides ``correct``: the last fit the window
    returned, against the plain reference."""
    ref = importlib.import_module(
        f"benchmark.reference.{ctx.config['reference']}")
    cfg = reference_config(ctx, state)
    cfg["categorical"] = state["cat_idx"]
    cfg["categorical_split"] = ctx.config["categorical_split"]
    return ref.check_fit(
        state["cat_model_text"], state["X"], state["y"], state["bins"],
        cfg, seed=ctx.seed, expect_trees=state["trees"],
        sample_nodes=int(ctx.traffic["check_nodes"]),
        sample_features=int(ctx.traffic["check_bin_features"]),
        min_categorical=int(ctx.traffic["check_bin_categorical"]),
        precision=precision)
