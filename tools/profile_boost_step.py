"""Capture a jax.profiler trace of GBDT boost steps on the live backend.

Writes a perfetto/tensorboard trace under ``artifacts/trace_<backend>/`` and
prints device self time by the grower's named scopes
(``core.profiling.summarize_trace``) and the device's idle time by the
host's span (``core.profiling.idle_by_span``), so the hot spots are
visible without a UI.

Usage: python tools/profile_boost_step.py [--rows 400000] [--steps 3]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=400_000)
    ap.add_argument("--features", type=int, default=50)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import functools

    import jax.numpy as jnp
    import numpy as np
    from mmlspark_tpu.core.profiler import get_profiler
    from mmlspark_tpu.core.profiling import idle_by_span, summarize_trace
    from mmlspark_tpu.gbdt.grower import (GrowerConfig, grow_tree,
                                          make_feat_info)
    from mmlspark_tpu.gbdt.objectives import BinaryObjective

    backend = jax.default_backend()
    out_dir = args.out or f"artifacts/trace_{backend}"
    os.makedirs(out_dir, exist_ok=True)

    n, f = args.rows, args.features
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, f)).astype(np.float32)
    logits = X[:, 0] * 1.5 + X[:, 1] * X[:, 2] + np.sin(X[:, 3] * 2)
    y = (logits > 0).astype(np.float32)
    # uint8 bins + hoisted binsT: the PRODUCTION scan path's layout
    # (a per-step int32 transpose would dominate the trace and hide the
    # actual glue)
    bins = jnp.asarray(
        np.clip((X - X.min(0)) / (np.ptp(X, 0) + 1e-9) * 255, 0, 255),
        jnp.uint8)
    binsT = jnp.transpose(bins)
    labels = jnp.asarray(y)
    weights = jnp.ones(n, jnp.float32)
    bag = jnp.ones(n, jnp.float32)
    fi = jnp.asarray(make_feat_info(f))
    obj = BinaryObjective()
    obj.prepare(np.asarray(y), np.ones(n))
    cfg = GrowerConfig(num_leaves=31, num_bins=256)
    scores = jnp.zeros(n, jnp.float32)

    @jax.jit
    def boost_step(binsA, binsTA, scoresA):
        g, h = obj.grad_hess(scoresA, labels, weights)
        gh = jnp.stack([g * bag, h * bag, bag], axis=1)
        tree, row_leaf = grow_tree(binsA, gh, fi, cfg, binsT=binsTA)
        return tree, scoresA + 0.1 * tree.leaf_value[row_leaf]

    # warm-up/compile
    tree, scores = boost_step(bins, binsT, scores)
    jax.block_until_ready((tree, scores))
    t0 = time.perf_counter()
    for _ in range(3):
        tree, scores = boost_step(bins, binsT, scores)
    jax.block_until_ready((tree, scores))
    per_step = (time.perf_counter() - t0) / 3
    print(f"steady-state boost step: {per_step*1e3:.1f} ms")

    # the engine's span names, so that the idle table reads as a fit's
    prof = get_profiler()
    with jax.profiler.trace(out_dir):
        with prof.region("train.fit"):
            for _ in range(args.steps):
                with prof.region("train.launch"):
                    tree, scores = boost_step(bins, binsT, scores)
            with prof.region("train.device_wait"):
                jax.block_until_ready((tree, scores))
    print(f"trace written to {out_dir}")
    rows = summarize_trace(out_dir)
    if not rows:
        print("no .xplane.pb in the trace dir")
        return
    print(f"device self time by named scope over {args.steps} steps "
          f"(total {rows[-1][0]:.1f} ms):")
    for ms, name in rows[:-1]:
        print(f"  {ms / args.steps:9.2f} ms/step  {name[:100]}")
    idle = idle_by_span(out_dir)
    if idle:
        print(f"idle device time by the host's span (total "
              f"{idle[-1][0]:.1f} ms):")
        for ms, name in idle[:-1]:
            print(f"  {ms / args.steps:9.2f} ms/step  {name}")


if __name__ == "__main__":
    main()
