"""Readings that the limits of ``correct`` are set from, at a cell's own
size, several seeds in one process (set-up is long, so no run per seed).

    python3 benchmark/checks/readings.py --workload epsilon_fit \
        --seeds 101 102 103 --control 2 --faults 2 --out chiprun_out/r.jsonl

For each seed: the cell's set-up (rows, binning, one fit of the window's
own tree count through the window's own call), then the comparison on
what that fit returned: the program's reading.  For the first
``--control`` seeds also the control: the reference's split search in
float8_e4m3, the gap of the split it would put first.  For the first
``--faults`` seeds also each fault planted under the comparison:

* ``stale_state``   tree 2 grown from tree 1's gradients (a step that
                    returns its state unchanged): tree 1 again, without
                    its boost-from-average bias;
* ``half_batch``    the fit made on the first half of the rows;
* ``shard_only``    (cells on several chips) the fit made on the first
                    shard's rows alone: the exchange left out;
* ``altered_leaf``  two leaf values of the last tree exchanged;
* ``altered_split`` one threshold of the last tree moved to the next
                    bin bound.

``--override`` lays estimator parameters over the configuration's: with
``'{"quantizedGrad": "8"}'`` the program's own int8 path is the control.

Needs the chip unless ``--rehearse``.  One JSON line a reading.
"""

import argparse
import copy
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run as harness      # noqa: E402


def stale_state(booster, y):
    """Tree 2 := tree 1 less its bias."""
    out = copy.copy(booster)
    out.trees = list(booster.trees)
    p = float(np.mean(y))
    bias = np.log(p / (1 - p))
    t = copy.deepcopy(booster.trees[0])
    t.leaf_value = t.leaf_value - bias
    out.trees[-1] = t
    return out


def altered_leaf(booster):
    out = copy.copy(booster)
    out.trees = list(booster.trees)
    t = copy.deepcopy(booster.trees[-1])
    lo, hi = int(np.argmin(t.leaf_value)), int(np.argmax(t.leaf_value))
    t.leaf_value[[lo, hi]] = t.leaf_value[[hi, lo]]
    out.trees[-1] = t
    return out


def altered_split(booster, mapper):
    out = copy.copy(booster)
    out.trees = list(booster.trees)
    t = copy.deepcopy(booster.trees[-1])
    f = int(t.split_feature[0])
    bounds = mapper.upper_bounds[f]
    i = int(np.searchsorted(bounds, t.threshold[0]))
    t.threshold[0] = bounds[min(i + 1, len(bounds) - 1)]
    out.trees[-1] = t
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--bench-json",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--override", default=None,
                    help="JSON of estimator parameters laid over the "
                         "configuration's, e.g. the program's own lower "
                         "precision: '{\"quantizedGrad\": \"8\"}'")
    ap.add_argument("--label", default="program",
                    help="name of the plain reading (with --override)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import importlib

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
    if args.override:
        config["params"] = {**config["params"], **json.loads(args.override)}
        if "rehearsal" in config:
            config["rehearsal"].setdefault("params", {}).update(
                json.loads(args.override))
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
        setup_s = time.perf_counter() - t0
        booster = state["booster"]
        mapper = state["mapper"]

        def read(what, b, precision="float64"):
            state["booster"] = b
            st = dict(state)
            driver.release(ctx, st)
            t1 = time.perf_counter()
            numbers = driver.check(ctx, st, precision=precision)
            emit(seed=seed, reading=what, check_s=time.perf_counter() - t1,
                 **numbers)

        emit(seed=seed, reading="setup", setup_s=setup_s,
             bin_s=ctx.counters["bin_s"])
        read(args.label, booster)
        if k < args.control:
            read("control_fp8", booster, precision="fp8")
        if k < args.faults:
            read("stale_state", stale_state(booster, state["y"]))
            read("altered_leaf", altered_leaf(booster))
            read("altered_split", altered_split(booster, mapper))
            n = state["rows"]
            for name, keep in (("half_batch", n // 2),) + (
                    (("shard_only", n // ctx.chips),) if ctx.chips > 1
                    else ()):
                real = engine.train

                def part(bins, labels, weights, *a, _keep=keep, **kw):
                    return real(bins[:_keep], labels[:_keep], weights,
                                *a, **kw)

                engine.train = part
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
