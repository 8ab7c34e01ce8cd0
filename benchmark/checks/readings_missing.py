"""``readings.py`` for a cell whose table holds missing values: the
program's numbers, the control's and each planted fault's, at the cell's
own size, several seeds in one process.

    python3 benchmark/checks/readings_missing.py --workload bosch_missing_fit \
        --seeds 101 102 --control 2 --faults 1 --out readings.jsonl

The control is ``readings.py``'s (the reference's split search in
float8_e4m3).  The faults, each on the first ``--faults`` seeds:

* ``stale_state``, ``altered_leaf``, ``altered_split``, ``half_batch``
                    ``readings.py``'s;
* ``missing_right`` the fit made again with the program forced to the
                    one-direction scan (``engine._scans_missing`` off:
                    every missing value goes right), which compiles a
                    program of its own;
* ``dleft_dropped`` the program's forest with ``decision_type`` bit 1
                    (default_left) cleared in the export.

``--program 0`` leaves out the program's own reading (read by the cell's
runs).  Needs the chip unless ``--rehearse``.  One JSON line a reading.
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


def dleft_dropped(booster):
    """Every tree with bit 1 cleared where the missing type is NaN: the
    export says NaN goes right where the partition sent it left."""
    out = copy.copy(booster)
    out.trees = []
    for t in booster.trees:
        t = copy.deepcopy(t)
        dt = np.asarray(t.decision_type)
        nan_type = ((dt >> 2) & 3) == 2
        t.decision_type = np.where(nan_type & ((dt & 1) == 0), dt & ~2,
                                   dt).astype(dt.dtype)
        out.trees.append(t)
    return out


def broken_fits():
    """``{name: wrap}``: each wraps ``engine.train`` (the real one in,
    the broken one out) for a fault that needs a fit of its own."""
    from mmlspark_tpu.gbdt import engine

    def half_batch(real):
        def train(bins, labels, weights, *a, **kw):
            n = len(labels) // 2
            return real(bins[:n], labels[:n], weights, *a, **kw)
        return train

    def missing_right(real):
        def train(*a, **kw):
            sound = engine._scans_missing
            engine._scans_missing = lambda mapper, params: False
            try:
                return real(*a, **kw)
            finally:
                engine._scans_missing = sound
        return train

    return {"half_batch": half_batch, "missing_right": missing_right}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", type=int, default=1)
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

    for i, seed in enumerate(args.seeds):
        ctx = harness.Context(cell, config, traffic, seed, args.rehearse,
                              False)
        t0 = time.perf_counter()
        state = driver.setup(ctx)
        booster = state["booster"]

        def read(what, b, precision="float64"):
            state["booster"] = b
            st = dict(state)
            driver.release(ctx, st)
            t1 = time.perf_counter()
            numbers = driver.check(ctx, st, precision=precision)
            emit(seed=seed, reading=what, check_s=time.perf_counter() - t1,
                 **numbers)

        emit(seed=seed, reading="setup", setup_s=time.perf_counter() - t0,
             bin_s=ctx.counters["bin_s"],
             empty_share=ctx.counters["empty_share"],
             fit=ctx.counters["last_fit_info"])
        if args.program:
            read("program", booster)
        if i < args.control:
            read("control_fp8", booster, precision="fp8")
        if i < args.faults:
            read("stale_state", readings.stale_state(booster, state["y"]))
            read("altered_leaf", readings.altered_leaf(booster))
            read("altered_split",
                 readings.altered_split(booster, state["mapper"]))
            read("dleft_dropped", dleft_dropped(booster))
            real = engine.train
            for name, wrap in broken_fits().items():
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
