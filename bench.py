"""Benchmark: GBDT training throughput vs sklearn HistGradientBoosting (CPU).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

The headline metric is boosted rows/second for LightGBMClassifier training
(n_rows x n_iterations / wall_clock) on the accelerator jax selects.  With
no accelerator the run exits non-zero; ``--force-cpu`` is the explicit CI
mode and its result names ``"backend": "cpu"``.  The baseline is sklearn's
HistGradientBoostingClassifier — the same histogram-GBDT algorithm family,
measured live on this machine's CPU with matched hyper-parameters —
standing in for the reference's CPU LightGBM executor engine until real
reference numbers exist (BASELINE.md: "published": {}).

vs_baseline = sklearn_wall_clock / our_wall_clock  (>1 means faster).

The JSON line is ALWAYS emitted, even on partial failure, with an "error"
field and a non-zero exit.

Wide-data A/B (ISSUE 16): `--parallelism {data,voting,feature}` with
`--devices N` runs the same scenario under each distributed mode —
voting rides the voted-column select-ring, feature the split-broadcast
protocol — and the detail block records collective count and payload
bytes per reduce so the PV-Tree payload cut is machine-checkable:

  python bench.py --rows 8192 --features 2000 --iters 4 --devices 4 \
      --parallelism voting --skip-baseline --force-cpu
"""

import argparse
import json
import os
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for a quick sanity check")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--features", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--force-cpu", action="store_true",
                    help="run on the CPU backend (CI); without it a "
                         "missing accelerator is an error")
    ap.add_argument("--pass-through", default="",
                    help="passThroughArgs forwarded to the estimator "
                         "(A/B knobs, e.g. 'collective=ring'); empty "
                         "for the official configuration")
    ap.add_argument("--parallelism", default=None,
                    choices=("data", "voting", "feature"),
                    help="distributed mode for the wide-data A/B "
                         "(ISSUE 16); builds a mesh over --devices and "
                         "folds per-reduce payload accounting into "
                         "detail")
    ap.add_argument("--devices", type=int, default=None,
                    help="mesh size; on a CPU backend this forces the "
                         "host-platform device count before jax init")
    ap.add_argument("--top-k", type=int, default=32,
                    help="PV-Tree votes per shard (voting mode only)")
    ap.add_argument("--quantized-grad", default="off",
                    choices=("off", "16", "8"),
                    help="quantized-gradient A/B (ISSUE 17): train with "
                         "low-bit (g,h) grid codes and fold a same-config "
                         "f32 twin fit, a histogram-build micro A/B at "
                         "the committed pin, and vendored-dataset metric "
                         "parity into detail")
    ap.add_argument("--collective", default=None,
                    choices=("auto", "psum", "ring"),
                    help="override the distributed modes' collective "
                         "(default: ring for data/voting); the quantized "
                         "payload gate reads psum, whose wire slab is "
                         "dtype-priced — the ring always moves f32 lanes")
    ap.add_argument("--skip-baseline", action="store_true",
                    help="skip the sklearn baseline (the wide-data A/B "
                         "compares our own modes, and sklearn at "
                         "f=2000 dominates the wall clock)")
    args = ap.parse_args()

    n = args.rows or (20_000 if args.smoke else 400_000)
    f = args.features or (20 if args.smoke else 50)
    iters = args.iters or (5 if args.smoke else 50)
    leaves = 31

    result = {
        "metric": "lightgbm_train_boosted_rows_per_sec",
        "value": 0.0,
        "unit": "rows*iters/s",
        "vs_baseline": 0.0,
        "detail": {"rows": n, "features": f, "iterations": iters,
                   "num_leaves": leaves},
    }
    if args.parallelism:
        result["detail"]["parallelism"] = args.parallelism
    try:
        run_bench(args, n, f, iters, leaves, result)
    except KeyboardInterrupt:
        result["error"] = "KeyboardInterrupt"
        print(json.dumps(result), flush=True)
        raise
    except Exception as e:  # noqa: BLE001 — always emit the JSON line
        result["error"] = f"{type(e).__name__}: {e}"
        import traceback
        log(traceback.format_exc())
        print(json.dumps(result), flush=True)
        sys.exit(1)
    print(json.dumps(result), flush=True)


def run_bench(args, n, f, iters, leaves, result):
    import numpy as np
    rng = np.random.default_rng(0)
    log(f"generating data: {n}x{f}, {iters} iters")
    X = rng.normal(size=(n, f)).astype(np.float32)
    logits = (X[:, 0] * 1.5 + X[:, 1] * X[:, 2] + np.sin(X[:, 3] * 2)
              + rng.normal(size=n) * 0.5)
    y = (logits > 0).astype(np.float64)

    # --- the backend, decided BEFORE jax initializes in this process ----
    if args.force_cpu:
        if args.devices and args.devices > 1:
            # the host platform exposes ONE device unless forced; this
            # must land in XLA_FLAGS before the backend initializes
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={args.devices}")
        from mmlspark_tpu.core.backend import pin_cpu_backend
        pin_cpu_backend()
    import jax
    backend = jax.default_backend()
    if backend == "cpu" and not args.force_cpu:
        raise SystemExit(
            "bench.py: jax found no accelerator (backend 'cpu'); a device "
            "metric is not measured on the CPU — pass --force-cpu for the "
            "explicit CI mode")

    # --- baseline: sklearn HistGradientBoosting on CPU -----------------
    # best of three runs on BOTH sides: single-run wall clock on a small
    # shared box is noisy (sklearn observed 7.4-20s for the same fit)
    from sklearn.metrics import roc_auc_score
    if args.skip_baseline:
        sk_time = None
        result["detail"]["sklearn_skipped"] = True
        log("sklearn baseline skipped (--skip-baseline)")
    else:
        from sklearn.ensemble import HistGradientBoostingClassifier
        sk_times = []
        for _ in range(3):
            t0 = time.perf_counter()
            sk = HistGradientBoostingClassifier(
                max_iter=iters, learning_rate=0.1, max_leaf_nodes=leaves,
                max_bins=255, early_stopping=False,
                validation_fraction=None)
            sk.fit(X, y)
            sk_times.append(time.perf_counter() - t0)
        sk_time = min(sk_times)
        sk_auc = roc_auc_score(y, sk.predict_proba(X)[:, 1])
        log(f"sklearn: {sk_time:.2f}s (runs: "
            f"{', '.join(f'{t:.2f}' for t in sk_times)})  "
            f"AUC={sk_auc:.4f}")
        result["detail"].update(
            sklearn_wall_s=round(sk_time, 3),
            sklearn_runs=[round(t, 3) for t in sk_times],
            sklearn_train_auc=round(float(sk_auc), 5))

    # --- ours ----------------------------------------------------------
    from mmlspark_tpu.core.backend import configure_compile_cache
    log(f"compile cache: {configure_compile_cache()}")
    log(f"jax backend: {jax.default_backend()}, devices: {jax.devices()}")
    result["detail"]["backend"] = backend
    from mmlspark_tpu.gbdt import LightGBMClassifier

    kw = dict(learningRate=0.1, numLeaves=leaves, maxBin=255,
              minDataInLeaf=20, verbosity=0)
    mesh = None
    if args.parallelism:
        from mmlspark_tpu.core.mesh import build_mesh
        D = args.devices or len(jax.devices())
        devs = jax.devices()[:D]
        if args.parallelism == "feature":
            mesh = build_mesh(data=1, feature=D, devices=devs)
        else:
            mesh = build_mesh(data=D, feature=1, devices=devs)
            # data/voting layouts can ride the on-chip ring; feature
            # stays on its split-broadcast psum protocol
            kw["collective"] = "ring"
        kw["parallelism"] = args.parallelism
        if args.collective:
            kw["collective"] = args.collective
        if args.parallelism == "voting":
            kw["topK"] = args.top_k
        # leaf-wise trees never exceed depth numLeaves-1, so this pin is
        # a no-op on tree SHAPE — it exists so the committed artifact's
        # "collective count per tree <= max_depth + 1" gate is
        # well-defined (count == numLeaves == maxDepth + 1)
        kw["maxDepth"] = leaves - 1
        result["detail"].update(devices=D, max_depth=leaves - 1)
    if args.quantized_grad != "off":
        kw["quantizedGrad"] = args.quantized_grad
        result["detail"]["quantized_grad"] = args.quantized_grad
    if args.pass_through:
        kw["passThroughArgs"] = args.pass_through
        result["detail"]["pass_through"] = args.pass_through
    # warm-up: identical config so the timed fit is pure steady state
    # (boost step AND forest-pack kernels compiled, caches hot)
    log("warm-up / compile...")
    t0 = time.perf_counter()

    def fit_once():
        est = LightGBMClassifier(numIterations=iters, **kw)
        if mesh is not None:
            est = est.setMesh(mesh)
        return est.fit({"features": X, "label": y})

    fit_once()
    log(f"warm-up (incl compile): {time.perf_counter() - t0:.2f}s")

    our_times = []
    for _ in range(3):
        t0 = time.perf_counter()
        model = fit_once()
        our_times.append(time.perf_counter() - t0)
    our_time = min(our_times)
    # provenance: the RESOLVED histogram kernel + collective the fit ran —
    # the bench artifact must say which kernel produced the number
    from mmlspark_tpu.gbdt import engine as _engine
    result["detail"].update(_engine.last_fit_info)
    info = _engine.last_fit_info
    if "collective_count_per_tree" in info:
        # per-reduce payload: the number the 10-100x wide-data claim
        # rides on (ISSUE 16 acceptance reads these off the artifact)
        cnt = int(info["collective_count_per_tree"])
        payload = int(info["collective_payload_bytes_per_tree"])
        result["detail"].update(
            collective_payload_bytes_per_reduce=(
                round(payload / cnt, 1) if cnt else 0.0))
    out = model.transform({"features": X, "label": y})
    our_auc = roc_auc_score(y, np.asarray(out["probability"])[:, 1])
    log(f"ours: {our_time:.2f}s (runs: "
        f"{', '.join(f'{t:.2f}' for t in our_times)})  AUC={our_auc:.4f}")

    result["value"] = round(n * iters / our_time, 1)
    if sk_time is not None:
        result["vs_baseline"] = round(sk_time / our_time, 4)
    result["detail"].update(our_wall_s=round(our_time, 3),
                            our_runs=[round(t, 3) for t in our_times],
                            our_train_auc=round(float(our_auc), 5))

    if args.quantized_grad != "off":
        _quantized_ab(args, kw, mesh, iters, X, y, result)


def _quantized_ab(args, kw, mesh, iters, X, y, result):
    """Fold the ISSUE 17 acceptance numbers into ``detail``:

    * ``quantized_vs_f32`` — a same-config f32 twin fit: wall clock,
      train AUC and the journaled per-tree collective payload, so
      ``payload_ratio`` (quantized / f32 bytes on the wire) is
      machine-checkable straight off the artifact.
    * ``hist_build`` — the histogram-build micro A/B at the committed
      pin (32768 x 50, 256 bins, 8-bit grid): min-of-9 build time for
      f32 gh vs int16 grid codes through the same resolved kernel.
    * ``parity`` — eval-metric relative deltas (quantized vs f32) on
      the REAL vendored datasets under tests/benchmarks/data/.
    """
    import time

    import numpy as np
    from sklearn.metrics import roc_auc_score

    from mmlspark_tpu.gbdt import LightGBMClassifier
    from mmlspark_tpu.gbdt import engine as _engine

    log("quantized A/B: f32 twin fit...")
    kw_f32 = dict(kw)
    kw_f32["quantizedGrad"] = "off"

    def fit_f32():
        est = LightGBMClassifier(numIterations=iters, **kw_f32)
        if mesh is not None:
            est = est.setMesh(mesh)
        return est.fit({"features": X, "label": y})

    fit_f32()                                   # warm-up / compile
    t0 = time.perf_counter()
    model_f32 = fit_f32()
    f32_wall = time.perf_counter() - t0
    f32_info = dict(_engine.last_fit_info)
    out = model_f32.transform({"features": X, "label": y})
    f32_auc = roc_auc_score(y, np.asarray(out["probability"])[:, 1])
    ab = {"f32_wall_s": round(f32_wall, 3),
          "f32_train_auc": round(float(f32_auc), 5),
          "quant_train_auc": result["detail"]["our_train_auc"],
          "auc_rel_delta": round(
              abs(result["detail"]["our_train_auc"] - float(f32_auc))
              / max(abs(float(f32_auc)), 1e-12), 6)}
    qp = result["detail"].get("collective_payload_bytes_per_tree")
    fp = f32_info.get("collective_payload_bytes_per_tree")
    if qp is not None and fp is not None and int(fp) > 0:
        ab.update(payload_bytes_per_tree_quant=int(qp),
                  payload_bytes_per_tree_f32=int(fp),
                  payload_ratio=round(int(qp) / int(fp), 6))
    result["detail"]["quantized_vs_f32"] = ab
    log(f"quantized A/B: f32 twin {f32_wall:.2f}s "
        f"AUC={f32_auc:.4f} payload ratio="
        f"{ab.get('payload_ratio', 'n/a')}")
    result["detail"]["hist_build"] = _hist_build_micro()
    result["detail"]["parity"] = _vendored_parity(args.quantized_grad)


def _hist_build_micro():
    """Histogram-build micro A/B at the committed pin: one (n, f) bin
    matrix, f32 ``(g, h, 1)`` vs int16 grid codes at ``|code| <= 127``
    (the 8-bit grid — the packed-int64 single-add native mode), through
    whatever kernel ``method='auto'``-equivalent dispatch resolves for
    each dtype.  Min-of-9 on both sides."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.ops import histogram as H

    n, f, B, mc = 32768, 50, 256, 127
    rng = np.random.default_rng(3)
    bins = jnp.asarray(rng.integers(0, B, size=(n, f), dtype=np.uint8))
    ghf = jnp.asarray(np.stack([rng.normal(size=n),
                                np.abs(rng.normal(size=n)),
                                np.ones(n)], 1), jnp.float32)
    codes = rng.integers(-mc, mc + 1, size=(n, 2))
    ghq = jnp.asarray(np.concatenate([codes, np.ones((n, 1))], 1),
                      jnp.int16)
    method = "native" if H._native_available() and B <= 256 else "segment"
    f32_fn = jax.jit(lambda b, g: H.compute_histogram(b, g, B,
                                                      method=method))
    q_fn = jax.jit(lambda b, g: H.compute_histogram(b, g, B,
                                                    method=method,
                                                    max_code=mc))

    def best(fn, b, g):
        fn(b, g).block_until_ready()            # compile
        ts = []
        for _ in range(9):
            t0 = time.perf_counter()
            fn(b, g).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    tf, tq = best(f32_fn, bins, ghf), best(q_fn, bins, ghq)
    out = {"rows": n, "features": f, "bins": B, "max_code": mc,
           "method": method,
           "packed_accum": bool(H.packed_accum_ok(n, mc)),
           "f32_build_ms": round(tf * 1e3, 3),
           "quant_build_ms": round(tq * 1e3, 3),
           "speedup": round(tf / tq, 4)}
    log(f"hist build micro [{method}]: f32 {tf*1e3:.2f}ms vs "
        f"int {tq*1e3:.2f}ms -> {tf/tq:.2f}x")
    return out


def _vendored_parity(quantized_grad):
    """Quantized-vs-f32 eval parity on the REAL vendored datasets
    (tests/benchmarks/data): held-out AUC for the breast-cancer binary
    task, held-out RMSE for the diabetes regression — relative deltas
    the acceptance gate reads."""
    import gzip

    import numpy as np
    from sklearn.metrics import roc_auc_score

    from mmlspark_tpu.gbdt import LightGBMClassifier, LightGBMRegressor

    data_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tests", "benchmarks", "data")

    def load(name):
        with gzip.open(os.path.join(data_dir, name), "rt") as fh:
            fh.readline()
            rows = np.asarray([[float(v) for v in line.split(",")]
                               for line in fh])
        return rows[:, :-1].astype(np.float32), rows[:, -1]

    out = []
    X, y = load("breast_cancer.csv.gz")
    idx = np.random.default_rng(7).permutation(len(y))
    tr, te = idx[:400], idx[400:]
    aucs = {}
    # lr=0.05: parity configs boost gently so the comparison measures
    # the quantization grid, not single near-tie split flips that a
    # 0.1-rate trajectory amplifies on a 569-row table
    for qg in ("off", quantized_grad):
        m = LightGBMClassifier(numIterations=150, numLeaves=15,
                               learningRate=0.05, minDataInLeaf=10,
                               verbosity=0, seed=42,
                               quantizedGrad=qg).fit(
            {"features": X[tr], "label": y[tr]})
        pred = m.transform({"features": X[te]})
        aucs[qg] = float(roc_auc_score(
            y[te], np.asarray(pred["probability"])[:, 1]))
    out.append({"dataset": "breast_cancer", "metric": "auc",
                "f32": round(aucs["off"], 5),
                "quant": round(aucs[quantized_grad], 5),
                "rel_delta": round(
                    abs(aucs[quantized_grad] - aucs["off"])
                    / max(abs(aucs["off"]), 1e-12), 6)})
    X, y = load("diabetes.csv.gz")
    idx = np.random.default_rng(8).permutation(len(y))
    tr, te = idx[:310], idx[310:]
    rmses = {}
    for qg in ("off", quantized_grad):
        m = LightGBMRegressor(numIterations=120, numLeaves=7,
                              learningRate=0.05, minDataInLeaf=10,
                              verbosity=0, seed=42,
                              quantizedGrad=qg).fit(
            {"features": X[tr], "label": y[tr]})
        pred = np.asarray(m.transform({"features": X[te]})["prediction"])
        rmses[qg] = float(np.sqrt(np.mean((pred - y[te]) ** 2)))
    out.append({"dataset": "diabetes", "metric": "rmse",
                "f32": round(rmses["off"], 4),
                "quant": round(rmses[quantized_grad], 4),
                "rel_delta": round(
                    abs(rmses[quantized_grad] - rmses["off"])
                    / max(abs(rmses["off"]), 1e-12), 6)})
    for row in out:
        log(f"parity {row['dataset']}: f32 {row['f32']} vs quant "
            f"{row['quant']} (rel delta {row['rel_delta']})")
    return out


if __name__ == "__main__":
    main()
