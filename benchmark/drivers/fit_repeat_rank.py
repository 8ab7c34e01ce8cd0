"""Driver ``fit_repeat_rank``: ``fit_repeat``'s window on a table whose
rows come in queries (benchmark/traffic/fit_repeat_rank.json).

Set-up is ``fit_repeat.setup`` with what a ranking configuration adds:
the rows, labels and query ids come from ``lib/data_ltr``, and the
ranker's gradient and ``ranking_info`` are taken as ``LightGBMBase._fit``
takes them (``_grad_fn_override``, ``_ranking_info``).  What depends on
the data alone is made once, as a search makes it once: the bins and the
packed query layout on the host.  What ``engine.train`` does with them
in every call stays in the window: the table's and the layout's upload,
the programs, fetch, finalize, the reference profile.  The window, the
end-to-end metric and the keys handed to the per-layer readers are
``fit_repeat``'s.

The exported text is kept under ``state["rank_model_text"]``, not
``"model_text"``: ``metrics/fit_tree_mfu.py`` finds nothing and is left
out; ``metrics/rank_fit_tree_mfu.py`` is this cell's whole-step share.
"""

import importlib
import sys
import time

from benchmark.drivers.fit_repeat import (_sizes, end_to_end,  # noqa: F401
                                          reference_config, window)
from benchmark.lib import data_ltr

CANNOT_RUN_EXIT = 4             # the program cannot run this configuration


def setup(ctx):
    """Everything before the window; returns the state the window drives."""
    from mmlspark_tpu import gbdt
    from mmlspark_tpu.core.profiler import get_profiler
    from mmlspark_tpu.gbdt import engine, ranking
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    from mmlspark_tpu.gbdt.objectives import get_objective

    if not hasattr(ranking, "LambdarankGrad"):
        # fail cleanly and at once: such a program pads every query to the
        # longest and compiles the query tensors in as constants
        print("[bench] this program's ranker has no query layout by size "
              "class (ranking.LambdarankGrad): it cannot run this cell",
              file=sys.stderr, flush=True)
        raise SystemExit(CANNOT_RUN_EXIT)
    rows, features, params = _sizes(ctx)
    queries = int((ctx.config["rehearsal"] if ctx.rehearse
                   else ctx.config)["queries"])
    trees = int(ctx.traffic["trees_per_fit"])
    with ctx.span("make_rows"):
        X, y, q = data_ltr.GENERATORS[ctx.config["data"]](
            ctx.seed, rows, features, queries)

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

    table, every_row = {est.getGroupCol(): q}, slice(None)
    t0 = time.perf_counter()
    with ctx.span("rank_pack"):
        grad = est._grad_fn_override(table, every_row, labels, None)
        ranking_info = est._ranking_info(table, every_row)
    ctx.counters["rank_pack_s"] = time.perf_counter() - t0

    def fit():
        return engine.train(bins, labels, None, mapper, objective,
                            train_params, grad_fn_override=grad,
                            ranking_info=ranking_info, mesh=None)

    with ctx.span("warmup_fit"):
        booster = fit()
    ctx.counters["bin_s"] = bin_s
    ctx.counters["last_fit_info"] = dict(engine.last_fit_info)
    return {"fit": fit, "X": X, "y": y, "q": q, "bins": bins,
            "trees": trees, "booster": booster, "profiler": get_profiler(),
            "est": est, "features": features, "rows": rows,
            "num_bins": mapper.num_total_bins, "mapper": mapper}


def release(ctx, state):
    """Drop what holds device memory before the reference runs."""
    state["rank_model_text"] = state["booster"].save_native_model_string()
    state.pop("fit")
    state.pop("booster")


def check(ctx, state, precision="float64"):
    """The comparison that decides ``correct``: the last fit the window
    returned, against the plain reference; the program's own NDCG@10 of
    the fit's scores is read beside the reference's."""
    from mmlspark_tpu.gbdt.ranking import ndcg_at_k
    ref = importlib.import_module(
        f"benchmark.reference.{ctx.config['reference']}")
    cfg = reference_config(ctx, state)
    cfg["ranking_gradient"] = ctx.config["ranking_gradient"]
    return ref.check_fit(
        state["rank_model_text"], state["X"], state["y"], state["q"],
        state["bins"], cfg, seed=ctx.seed, expect_trees=state["trees"],
        sample_nodes=int(ctx.traffic["check_nodes"]),
        sample_features=int(ctx.traffic["check_bin_features"]),
        precision=precision,
        also={"ndcg10_program": lambda s, y, q: ndcg_at_k(s, y, q, k=10)})
