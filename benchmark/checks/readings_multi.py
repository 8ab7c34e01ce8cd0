"""``readings.py`` for a multiclass cell: the program's numbers, the
control's and each planted fault's, at the cell's own size, several
seeds in one process.

    python3 benchmark/checks/readings_multi.py --workload mnist8m_fit \
        --seeds 101 102 --control 2 --faults 1 --out readings.jsonl

The faults, each on the first ``--faults`` seeds:

* ``stale_state``      the second iteration's K trees are the first's
                       less their start scores (a step that returns its
                       state unchanged grows the same trees again);
* ``altered_leaf``,
  ``altered_split``    ``readings.py``'s, on the last tree;
* ``half_batch``       the fit made on the first half of the rows;
* ``class_swap``       class k's tree grown on class k+1's gradients
                       (the class axis rolled by one);
* ``sequential_grad``  the gradients recomputed after every class tree,
                       Gauss-Seidel, where LightGBM computes them once an
                       iteration for all K trees.

The last three are fits made again through the window's own call with
the program broken underneath (``engine.train``, its objective, or its
multiclass chunk program replaced), so each compiles a program of its
own.  ``--program 0`` leaves out the program's own reading (read by the
cell's runs).

Needs the chip unless ``--rehearse``.  One JSON line a reading.
"""

import argparse
import copy
import functools
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run as harness              # noqa: E402
from benchmark.checks import readings             # noqa: E402
from benchmark.reference import gbdt_multi        # noqa: E402


def stale_state(booster, y):
    """Every tree of the second iteration := its class's first tree less
    that class's start score."""
    K = booster.num_class
    start = gbdt_multi.init_scores(y, K)
    out = copy.copy(booster)
    out.trees = list(booster.trees)
    for k in range(K):
        t = copy.deepcopy(booster.trees[k])
        t.leaf_value = t.leaf_value - start[k]
        t.internal_value = t.internal_value - start[k]
        out.trees[K + k] = t
    return out


def swapped_objective(objective):
    """``objective`` with its class axis rolled: class k's tree fits class
    k+1's gradients."""
    import jax.numpy as jnp
    from mmlspark_tpu.gbdt.objectives import MulticlassObjective

    class ClassSwap(MulticlassObjective):
        def grad_hess_class_major(self, scores, labels, weights):
            g, h = super().grad_hess_class_major(scores, labels, weights)
            return jnp.roll(g, -1, axis=0), jnp.roll(h, -1, axis=0)

    return ClassSwap(objective.num_class)


def gauss_seidel_scan():
    """A stand-in for ``engine._boost_scan_multi`` that recomputes the
    softmax gradients after every class tree."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.gbdt import engine

    @functools.partial(jax.jit, static_argnames=("obj", "cfg", "lr", "K",
                                                  "has_val", "rf"))
    def scan(bins, scores, labels, weights, bag_masks, fi_stack, val_bins,
             val_scores, obj, cfg, lr, K, has_val, efb=None, rf=False):
        binsT = bins.T

        def grow_class(scores, xs):
            k, bag, fi = xs
            g, h = obj.grad_hess_class_major(scores, labels, weights)
            gh = jnp.stack([g[k] * bag, h[k] * bag, bag], axis=1)
            tree, row_leaf = engine._grow_tree_impl(bins, gh, fi, cfg, efb,
                                                    binsT=binsT)
            scores = scores.at[k].add(lr * tree.leaf_value[row_leaf])
            return scores, engine.apply_shrinkage(tree, lr)

        def body(scores, xs):
            bag, fi = xs
            bag = jnp.broadcast_to(bag, (scores.shape[1],))
            return jax.lax.scan(lambda s, k: grow_class(s, (k, bag, fi)),
                                scores, jnp.arange(K))

        scores, trees = jax.lax.scan(body, scores, (bag_masks, fi_stack))
        trees = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), trees)
        return (trees, scores, val_scores,
                jnp.zeros((bag_masks.shape[0], 0, K), jnp.float32))

    return scan


def broken_fits():
    """``{name: wrap}``: each wraps ``engine.train`` (the real one in,
    the broken one out) for a fault that needs a fit of its own."""
    from mmlspark_tpu.gbdt import engine

    def half_batch(real):
        def train(bins, labels, weights, *a, **kw):
            n = len(labels) // 2
            return real(bins[:n], labels[:n], weights, *a, **kw)
        return train

    def class_swap(real):
        def train(bins, labels, weights, mapper, objective, *a, **kw):
            return real(bins, labels, weights, mapper,
                        swapped_objective(objective), *a, **kw)
        return train

    def sequential_grad(real):
        def train(*a, **kw):
            sound = engine._boost_scan_multi
            engine._boost_scan_multi = gauss_seidel_scan()
            try:
                return real(*a, **kw)
            finally:
                engine._boost_scan_multi = sound
        return train

    return {"half_batch": half_batch, "class_swap": class_swap,
            "sequential_grad": sequential_grad}


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
             warmup_compile_s=ctx.counters["warmup_compile_s"])
        if args.program:
            read("program", booster)
        if i < args.control:
            read("control_fp8", booster, precision="fp8")
        if i < args.faults:
            read("stale_state", stale_state(booster, state["y"]))
            read("altered_leaf", readings.altered_leaf(booster))
            read("altered_split",
                 readings.altered_split(booster, state["mapper"]))
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
