"""Driver ``fit_repeat_multi``: ``fit_repeat``'s window on a multiclass
table (benchmark/traffic/fit_repeat_multi.json).

Set-up makes the rows from the seed (``lib/data_pixels``), builds the
estimator from the configuration's parameters, resolves the class count
and the softmax objective as ``LightGBMBase._fit`` does, bins on the
host, and makes ONE warm-up ``engine.train`` call of the window's own
iteration count.  The window repeats that call; a fit returns K trees an
iteration, so ``iterations_per_fit`` iterations are K times as many
trees, and the end-to-end metric is per tree, as ``fit_repeat``'s.

Besides ``fit_repeat``'s counters, set-up keeps ``warmup_compile_s``: the
seconds the warm-up fit's ``train.build_step`` and ``train.launch`` spans
say went to tracing and to the backend compile (their
``jaxpr_trace_s`` and ``backend_compile_s``).

The exported text is kept under ``state["multi_model_text"]``:
``metrics/multiclass_fit_tree_mfu.py`` parses it with the class count.

A program whose ``train.fit`` does not say ``grow_bodies`` (it has no
``grower.GROW_TRACES``) unrolls the class loop into K grower bodies, and
at this configuration's size that program could not be built on a
one-chip machine: set-up says so and exits 4 at once, before making any
rows.
"""

import importlib
import sys
import time

from benchmark.drivers.fit_repeat import (end_to_end,  # noqa: F401
                                          reference_config, window)
from benchmark.lib import data_pixels

CANNOT_RUN_EXIT = 4             # the program cannot run this configuration
COMPILE_SPANS = ("train.build_step", "train.launch")
COMPILE_ATTRS = ("jaxpr_trace_s", "backend_compile_s")


def _sizes(ctx):
    cfg = ctx.config
    over = cfg.get("rehearsal", {}) if ctx.rehearse else {}
    params = dict(cfg["params"])
    params.update(over.get("params", {}))
    return (int(over.get("rows", cfg["rows"])),
            int(over.get("features", cfg["features"])), params)


def compile_seconds(spans):
    """Tracing and backend-compile seconds the given spans report."""
    return sum(float(s["attrs"].get(a, 0.0)) for s in spans
               if s["name"] in COMPILE_SPANS for a in COMPILE_ATTRS)


def setup(ctx):
    """Everything before the window; returns the state the window drives."""
    try:
        from mmlspark_tpu.gbdt.grower import GROW_TRACES  # noqa: F401
    except ImportError as e:
        # fail cleanly and at once, before any rows are made
        print(f"[bench] this program does not count its grower bodies "
              f"({e}): its class loop is unrolled, and it cannot run this "
              "cell", file=sys.stderr, flush=True)
        raise SystemExit(CANNOT_RUN_EXIT)
    from mmlspark_tpu import gbdt
    from mmlspark_tpu.core.profiler import get_profiler
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    from mmlspark_tpu.gbdt.objectives import get_objective

    rows, features, params = _sizes(ctx)
    iterations = int(ctx.traffic["iterations_per_fit"])
    with ctx.span("make_rows"):
        X, y = data_pixels.GENERATORS[ctx.config["data"]](
            ctx.seed, rows, features)

    est = getattr(gbdt, ctx.config["estimator"])(
        numIterations=iterations, parallelism=ctx.traffic["parallelism"],
        **params)
    labels = est._prepare_labels(y)
    # as LightGBMBase._fit resolves the class count of a multiclass fit
    num_class = max(getattr(est, "_num_class", 1), int(labels.max()) + 1)
    if num_class != int(ctx.config["num_class"]):
        raise ValueError(f"{num_class} classes made, the configuration "
                         f"states {ctx.config['num_class']}")
    objective = get_objective(
        getattr(est, "_resolved_objective", None) or est.getObjective(),
        num_class=num_class, **est._objective_kwargs())
    train_params = est._train_params()
    profiler = get_profiler()

    t0 = time.perf_counter()
    with ctx.span("bin"):
        mapper = fit_bin_mapper(X, max_bin=est.getMaxBin(),
                                seed=est.getSeed())
        bins = mapper.transform_packed(X)
    bin_s = time.perf_counter() - t0

    def fit():
        return engine.train(bins, labels, None, mapper, objective,
                            train_params, mesh=None)

    before = {s["id"] for s in profiler.spans()}
    with ctx.span("warmup_fit"):
        booster = fit()
    ctx.counters["bin_s"] = bin_s
    ctx.counters["warmup_compile_s"] = compile_seconds(
        s for s in profiler.spans() if s["id"] not in before)
    ctx.counters["last_fit_info"] = dict(engine.last_fit_info)
    return {"fit": fit, "X": X, "y": y, "bins": bins,
            "trees": iterations * num_class, "num_class": num_class,
            "booster": booster, "profiler": profiler, "est": est,
            "features": features, "rows": rows,
            "num_bins": mapper.num_total_bins, "mapper": mapper}


def release(ctx, state):
    """Drop what holds device memory before the reference runs."""
    state["multi_model_text"] = state["booster"].save_native_model_string()
    state.pop("fit")
    state.pop("booster")


def check(ctx, state, precision="float64"):
    """The comparison that decides ``correct``: the last fit the window
    returned, against the plain multiclass reference."""
    ref = importlib.import_module(
        f"benchmark.reference.{ctx.config['reference']}")
    return ref.check_fit(
        state["multi_model_text"], state["X"], state["y"], state["bins"],
        reference_config(ctx, state), seed=ctx.seed,
        expect_trees=state["trees"],
        sample_nodes=int(ctx.traffic["check_nodes"]),
        sample_features=int(ctx.traffic["check_bin_features"]),
        precision=precision)
