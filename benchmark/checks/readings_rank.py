"""``readings.py`` for a cell whose fits are a ranker's: the program's
numbers, the controls' and each planted fault's, at the cell's own size,
several seeds in one process.

    python3 benchmark/checks/readings_rank.py --workload istella_fit \
        --seeds 101 102 103 --control 2 --faults 1 --out chiprun_out/r.jsonl

Beside ``readings.py``'s faults (``stale_state``: tree 2 is tree 1 again,
a ranker's trees carrying no bias; ``altered_leaf``; ``altered_split``;
``half_batch``, the gradient built for the half's queries) it plants this
mechanism's own, each a fit made with the ranker's gradient built wrongly:

* ``query_shift``    every query boundary moved by one row (a query's
                     first document belongs to the query before it);
* ``no_truncation``  every pair counts, whatever its ranks;
* ``unnormalised``   a pair's delta is not divided by the ideal DCG;

and reads two controls, each the gap of the split another learner would
have put first: ``control_fp8`` (float8_e4m3 gradients) and
``control_pointwise`` (no ranking mechanism: squared error on the labels).

Needs the chip unless ``--rehearse``.  One JSON line a reading.
"""

import argparse
import copy
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

NO_TRUNCATION = 1 << 20


def stale_state(booster):
    """Tree 2 := tree 1 (lambdarank's trees carry no bias)."""
    out = copy.copy(booster)
    out.trees = list(booster.trees)
    out.trees[-1] = copy.deepcopy(booster.trees[0])
    return out


def _regraded(kw, labels, rows=None, query_ids=None, truncation_level=None,
              normalise=True):
    """``kw`` of an ``engine.train`` call with the ranker's gradient built
    again: for the first ``rows`` rows, other query ids, another
    truncation level, or no division by the ideal DCG."""
    from mmlspark_tpu.gbdt.ranking import (LambdarankGrad,
                                           make_lambdarank_grad_fn)
    info = dict(kw["ranking_info"])
    q = np.asarray(info["query_ids"] if query_ids is None else query_ids)
    info["query_ids"] = q[:rows]
    grad = make_lambdarank_grad_fn(
        np.asarray(labels)[:rows], info["query_ids"], sigma=info["sigma"],
        truncation_level=truncation_level or info["truncation_level"])
    if not normalise:
        lay = grad.layout
        grad = LambdarankGrad(lay._replace(classes=tuple(
            (rows_, gains, labq, (invmax > 0).astype(np.float32))
            for rows_, gains, labq, invmax in lay.classes)),
            grad.objective)
    return dict(kw, grad_fn_override=grad, ranking_info=info)


def half_batch(real):
    def train(bins, labels, weights, *a, **kw):
        n = len(labels) // 2
        return real(bins[:n], labels[:n], weights, *a,
                    **_regraded(kw, labels, rows=n))
    return train


def query_shift(real):
    def train(bins, labels, *a, **kw):
        q = np.roll(np.asarray(kw["ranking_info"]["query_ids"]), 1)
        return real(bins, labels, *a, **_regraded(kw, labels, query_ids=q))
    return train


def no_truncation(real):
    def train(bins, labels, *a, **kw):
        return real(bins, labels, *a, **_regraded(
            kw, labels, truncation_level=NO_TRUNCATION))
    return train


def unnormalised(real):
    def train(bins, labels, *a, **kw):
        return real(bins, labels, *a,
                    **_regraded(kw, labels, normalise=False))
    return train


#: faults planted under ``engine.train``: ``wrap(real_train)``
TRAIN_FAULTS = {"half_batch": half_batch, "query_shift": query_shift,
                "no_truncation": no_truncation, "unnormalised": unnormalised}


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
    from mmlspark_tpu.gbdt import engine
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
        emit(seed=seed, reading="setup", setup_s=time.perf_counter() - t0,
             bin_s=ctx.counters["bin_s"],
             rank_pack_s=ctx.counters["rank_pack_s"])
        booster, mapper = state["booster"], state["mapper"]

        def read(what, b, precision="float64"):
            st = dict(state, booster=b)
            driver.release(ctx, st)
            t1 = time.perf_counter()
            numbers = driver.check(ctx, st, precision=precision)
            emit(seed=seed, reading=what, check_s=time.perf_counter() - t1,
                 **numbers)

        read("program", booster)
        if k < args.control:
            read("control_fp8", booster, precision="fp8")
            read("control_pointwise", booster, precision="pointwise")
        if k < args.faults:
            read("stale_state", stale_state(booster))
            read("altered_leaf", readings.altered_leaf(booster))
            read("altered_split", readings.altered_split(booster, mapper))
            real = engine.train
            for name, wrap in TRAIN_FAULTS.items():
                engine.train = wrap(real)
                try:
                    read(name, state["fit"]())
                finally:
                    engine.train = real
        del state, booster
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
