"""Driver ``fit_repeat``: the window holds only repeated ``engine.train``
calls on one binned table (benchmark/traffic/fit_repeat*.json).

Set-up makes the rows from the seed, builds the estimator from the
configuration's parameters, takes ``TrainParams``, the objective and the
mesh as ``LightGBMBase._fit`` does, bins on the host, and makes ONE
warm-up call with the window's own tree count (the scan length is a
static shape).  The window calls ``engine.train`` again while the clock
is under ``--seconds``; a call in flight finishes, none starts after.
"""

import importlib
import time

from benchmark.lib import data as bench_data


def _sizes(ctx):
    cfg = ctx.config
    over = cfg.get("rehearsal", {}) if ctx.rehearse else {}
    params = dict(cfg["params"])
    params.update(over.get("params", {}))
    return (int(over.get("rows", cfg["rows"])),
            int(over.get("features", cfg["features"])), params)


def setup(ctx):
    """Everything before the window; returns the state the window drives."""
    from mmlspark_tpu import gbdt
    from mmlspark_tpu.core.profiler import get_profiler
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    from mmlspark_tpu.gbdt.objectives import get_objective

    rows, features, params = _sizes(ctx)
    trees = int(ctx.traffic["trees_per_fit"])
    with ctx.span("make_rows"):
        X, y = bench_data.GENERATORS[ctx.config["data"]](
            ctx.seed, rows, features)

    est = getattr(gbdt, ctx.config["estimator"])(
        numIterations=trees, parallelism=ctx.traffic["parallelism"],
        **params)
    labels = est._prepare_labels(y)
    objective = get_objective(
        getattr(est, "_resolved_objective", None) or est.getObjective(),
        num_class=getattr(est, "_num_class", 1), **est._objective_kwargs())
    train_params = est._train_params()
    mesh = None
    if ctx.chips > 1:
        from mmlspark_tpu.gbdt.distributed import resolve_mesh
        mesh = resolve_mesh(ctx.traffic["parallelism"])

    t0 = time.perf_counter()
    with ctx.span("bin"):
        mapper = fit_bin_mapper(X, max_bin=est.getMaxBin(),
                                seed=est.getSeed())
        bins = mapper.transform_packed(X)
    bin_s = time.perf_counter() - t0

    def fit():
        return engine.train(bins, labels, None, mapper, objective,
                            train_params, mesh=mesh)

    with ctx.span("warmup_fit"):
        booster = fit()
    ctx.counters["bin_s"] = bin_s
    ctx.counters["last_fit_info"] = dict(engine.last_fit_info)
    return {"fit": fit, "X": X, "y": y, "bins": bins, "trees": trees,
            "booster": booster, "profiler": get_profiler(),
            "est": est, "features": features, "rows": rows,
            "num_bins": mapper.num_total_bins, "mapper": mapper}


def window(ctx, state, seconds):
    """Repeated fits for ``seconds``; returns the work done."""
    fit = state["fit"]
    compiles0 = state["profiler"].compile_seq()
    trees = 0
    fits = 0
    booster = None
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with ctx.span(f"fit{fits}"):
            booster = fit()
        trees += len(booster.trees)
        fits += 1
    window_s = time.perf_counter() - t0
    state["booster"] = booster
    ctx.counters["compiles_in_window"] = (
        state["profiler"].compile_seq() - compiles0)
    return {"window_s": window_s, "trees": trees, "fits": fits,
            "attempted": fits, "failed": 0}


def end_to_end(ctx, state, work):
    return {"fit_tree_ms": work["window_s"] * 1e3 / work["trees"]}


def release(ctx, state):
    """Drop what holds device memory before the reference runs."""
    state["model_text"] = state["booster"].save_native_model_string()
    state.pop("fit")
    state.pop("booster")


def reference_config(ctx, state):
    est = state["est"]
    return {"learning_rate": est.getLearningRate(),
            "min_sum_hessian": est.getMinSumHessianInLeaf(),
            "min_data": est.getMinDataInLeaf(),
            "max_bin": est.getMaxBin(),
            "binning": ctx.config["binning"]}


def check(ctx, state, precision="float64"):
    """The comparison that decides ``correct``: the last fit the window
    returned, against the plain reference."""
    ref = importlib.import_module(
        f"benchmark.reference.{ctx.config['reference']}")
    return ref.check_fit(
        state["model_text"], state["X"], state["y"], state["bins"],
        reference_config(ctx, state), seed=ctx.seed,
        expect_trees=state["trees"],
        sample_nodes=int(ctx.traffic["check_nodes"]),
        sample_features=int(ctx.traffic["check_bin_features"]),
        precision=precision)
