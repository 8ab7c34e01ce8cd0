"""``readings.py`` for a cell whose forests hold categorical splits: the
program's numbers, the controls' and each planted fault's, at the cell's
own size, several seeds in one process.

    python3 benchmark/checks/readings_cat.py --workload criteo_fit \
        --seeds 101 102 103 --control 2 --faults 1 --out chiprun_out/r.jsonl

Beside ``readings.py``'s faults (``stale_state``, ``altered_leaf``,
``altered_split`` on the last tree's first NUMERIC node, ``half_batch``)
it plants this mechanism's own:

* ``altered_bitset``  one category moved across one split: the most
                      frequent value of the last tree's first categorical
                      node's left set loses its bit;

and reads two controls, each the gap of the split another learner would
have put first: ``control_fp8`` (float8_e4m3 gradients) and
``control_cat_as_numeric`` (no categorical mechanism: a categorical
column is its codes in ascending order).

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

CAT_BIT = 1


def altered_split(booster, mapper):
    """``readings.altered_split`` on the last tree's first numeric node."""
    out = copy.copy(booster)
    out.trees = list(booster.trees)
    t = copy.deepcopy(booster.trees[-1])
    i = int(np.flatnonzero((t.decision_type & CAT_BIT) == 0)[0])
    bounds = mapper.upper_bounds[int(t.split_feature[i])]
    k = int(np.searchsorted(bounds, t.threshold[i]))
    t.threshold[i] = bounds[min(k + 1, len(bounds) - 1)]
    out.trees[-1] = t
    return out


def altered_bitset(booster, mapper):
    """One category moved across one split: in the last tree's first
    categorical node, the most frequent value that goes left (the lowest
    bin of its left set) goes right."""
    out = copy.copy(booster)
    out.trees = list(booster.trees)
    t = copy.deepcopy(booster.trees[-1])
    i = int(np.flatnonzero((t.decision_type & CAT_BIT) > 0)[0])
    lo = int(t.cat_boundaries[int(t.threshold[i])])
    hi = int(t.cat_boundaries[int(t.threshold[i]) + 1])
    for value in mapper.cat_values[int(t.split_feature[i])]:
        w, b = int(value) >> 5, np.uint32(int(value) & 31)
        if w < hi - lo and (t.cat_threshold[lo + w] >> b) & np.uint32(1):
            t.cat_threshold = t.cat_threshold.copy()
            t.cat_threshold[lo + w] &= ~(np.uint32(1) << b)
            break
    else:
        raise ValueError("the node's left set holds no binned value")
    out.trees[-1] = t
    return out


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
             bin_s=ctx.counters["bin_s"])
        booster, mapper = state["booster"], state["mapper"]

        def read(what, b, precision="float64"):
            st = dict(state, booster=b)
            driver.release(ctx, st)
            t1 = time.perf_counter()
            numbers = driver.check(ctx, st, precision=precision)
            numbers.pop("bin_columns", None)
            emit(seed=seed, reading=what, check_s=time.perf_counter() - t1,
                 **numbers)

        read("program", booster)
        if k < args.control:
            read("control_fp8", booster, precision="fp8")
            read("control_cat_as_numeric", booster,
                 precision="cat_as_numeric")
        if k < args.faults:
            read("altered_bitset", altered_bitset(booster, mapper))
            read("stale_state", readings.stale_state(booster, state["y"]))
            read("altered_leaf", readings.altered_leaf(booster))
            read("altered_split", altered_split(booster, mapper))
            real, keep = engine.train, state["rows"] // 2

            def half(bins, labels, weights, *a, **kw):
                return real(bins[:keep], labels[:keep], weights, *a, **kw)

            engine.train = half
            try:
                read("half_batch", state["fit"]())
            finally:
                engine.train = real
        del state, booster
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
