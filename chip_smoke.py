"""chip_smoke.py — does the system still start on the chip?

Drives the main path once, in ONE process, through the entry points a user
calls, at the full width of the flagship (the ``bench.py`` shape: 400 000 x 50
float32, numLeaves=31, maxBin=255, minDataInLeaf=20, every other knob at its
default; only the iteration count is cut).  Phases, each of which fails the
run by raising:

  device   what jax reports; anything but a TPU is an error
  host     the native host-side binning extension built from source
  train    LightGBMClassifier.fit (auto-meshes over all chips when D > 1)
  score    model.transform, the jitted predictor, the independent walker
  serve    in-process HTTPServer + ScoringEngine answering POSTs
  mesh     (D > 1) per-chip memory placement, serial-vs-mesh forest parity
  kernels  every Pallas kernel through Mosaic against its XLA reference

Exit code 0 and the last stdout line ``{"ok": true, "device": {...}}`` mean
exactly one thing: every phase passed on a TPU.  Times and byte counts printed
on the way are smoke observations, not benchmark results.

``--rehearse`` runs the same phases at a tiny size on whatever backend jax has
(the CPU here, Pallas in interpret mode), names that platform, prints no result
line and exits 3: a rehearsal is never a chip pass.

    python chip_smoke.py                # on the chip, through the chip tool
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
"""

import faulthandler
import json
import shutil
import sys
import tempfile
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REHEARSAL_EXIT = 3

#: A kernel that deadlocks blocks inside the runtime, where no Python
#: handler runs: past this many seconds the watchdog thread dumps every
#: stack and kills the process, so the run ends inside its time limit.
DEADLINE_S = 1150

#: the flagship, and the tiny stand-in a rehearsal uses
FULL = dict(rows=400_000, features=50, iters=10, parity_rows=65_536,
            parity_iters=3, auc_band=(0.925, 0.940))
TINY = dict(rows=4_096, features=10, iters=3, parity_rows=2_048,
            parity_iters=2, auc_band=(0.80, 1.0))

FIT_KW = dict(learningRate=0.1, numLeaves=31, maxBin=255, minDataInLeaf=20,
              verbosity=0)


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def make_data(rows, features, seed=0):
    """bench.py's synthetic binary task."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, features)).astype(np.float32)
    logits = (X[:, 0] * 1.5 + X[:, 1] * X[:, 2] + np.sin(X[:, 3] * 2)
              + rng.normal(size=rows) * 0.5)
    return X, (logits > 0).astype(np.float64)


def peak_bytes():
    """peak_bytes_in_use per device, None where the backend reports none."""
    import jax
    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        out.append(None if not stats else stats.get("peak_bytes_in_use"))
    return out


# --------------------------------------------------------------- phases


def phase_device(rehearse):
    import importlib.metadata as md

    import jax
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax", "numpy"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = "absent"
    say("device", f"python {sys.version.split()[0]}  " + "  ".join(
        f"{k} {v}" for k, v in versions.items()))
    say("device", f"platform: {info['platform']}  device_kind: "
                  f"{info['kind']}  count: {info['count']}")
    if info["platform"] != "tpu" and not rehearse:
        say("device", "no TPU: this is not a chip run (use --rehearse for "
                      "the CPU rehearsal)")
        sys.exit(2)
    from mmlspark_tpu.core.backend import configure_compile_cache
    say("device", f"compile cache: {configure_compile_cache()}")
    return info


def phase_host():
    """Host binning is a layer of the chip path: with a toolchain present
    the native extension must have built (its numpy fallback is correct
    but hides a broken build)."""
    from mmlspark_tpu import native
    built = native.bin_columns_available()
    cxx = next((c for c in ("g++", "c++", "clang++") if shutil.which(c)),
               None)
    say("host", f"native.bin_columns_available(): {built}  (compiler: {cxx})")
    check(built or cxx is None,
          "a C++ compiler is present but native/fastbin.cc did not build; "
          "the compiler's stderr is in the log above")


def phase_train(cfg, on_tpu):
    import jax
    from sklearn.metrics import roc_auc_score

    from mmlspark_tpu.gbdt import LightGBMClassifier
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.ops.histogram import _auto_method

    X, y = make_data(cfg["rows"], cfg["features"])
    table = {"features": X, "label": y}
    d = len(jax.devices())

    def fit():
        # default histogram_method / collective / parallelism; rows >=
        # autoMeshMinRows, so D > 1 devices auto-mesh (the rehearsal
        # lowers the threshold to its own size to take the same path)
        return LightGBMClassifier(
            numIterations=cfg["iters"],
            autoMeshMinRows=min(65_536, cfg["rows"]), **FIT_KW).fit(table)

    t0 = time.perf_counter()
    fit()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    model = fit()
    warm_s = time.perf_counter() - t0
    peaks = peak_bytes()
    info = dict(engine.last_fit_info)
    say("train", f"last_fit_info: {json.dumps(info, sort_keys=True)}")
    say("train", f"histogram_method auto resolves to: {_auto_method()}")
    say("train", f"smoke observation: first fit {first_s:.1f} s (compile "
                 f"or cache load + fit), warm fit {warm_s:.2f} s, "
                 f"{cfg['iters']} iterations, D={d}")
    say("train", f"smoke observation: peak_bytes_in_use per device: {peaks}")

    check(info["backend"] == ("tpu" if on_tpu else jax.default_backend()),
          f"fit ran on backend {info['backend']!r}")
    check(info["collective_downgrade"] == "none"
          and info["quantized_downgrade"] == "none",
          f"fit was downgraded: {info}")
    out = model.transform(table)
    proba = np.asarray(out["probability"])
    check(proba.shape == (cfg["rows"], 2) and np.isfinite(proba).all(),
          f"transform gave shape {proba.shape} / non-finite values")
    auc = float(roc_auc_score(y, proba[:, 1]))
    lo, hi = cfg["auc_band"]
    say("train", f"train AUC {auc:.4f} (band {lo}-{hi})")
    check(lo <= auc <= hi, f"train AUC {auc:.4f} outside [{lo}, {hi}]")
    return X, y, model, np.asarray(out["rawPrediction"])[:, 1], peaks


def phase_score(X, model, on_tpu):
    from tests.test_golden_interop import _reference_predict

    pred = model.getModel().predictor()
    say("score", f"predictor.mode: {pred.mode}")
    # native is the CPU-only host scorer: on a chip anything but the
    # jitted walk is a hidden host path
    check(pred.mode == "jit" or not on_tpu,
          f"predictor on TPU resolved to {pred.mode!r}, not 'jit'")
    sample = X[:64]
    with tempfile.TemporaryDirectory() as tmp:
        path = f"{tmp}/model.txt"
        model.saveNativeModel(path)
        with open(path) as fh:
            text = fh.read()
    want = _reference_predict(text, sample)
    got = np.asarray(model.transform({"features": sample})["probability"])
    np.testing.assert_allclose(got[:, 1], want, rtol=1e-5, atol=1e-6)
    say("score", f"independent walker agrees on {len(sample)} rows of the "
                 f"saveNativeModel export (max |diff| "
                 f"{np.abs(got[:, 1] - want).max():.1e})")
    return pred


class _ShapeLog:
    """The predictor, remembering which padded batch sizes it scored."""

    def __init__(self, pred):
        self._pred = pred
        self.mode = pred.mode
        self.num_features = pred.num_features
        self.sizes = set()

    def __call__(self, X):
        self.sizes.add(int(X.shape[0]))
        return self._pred(X)


def phase_serve(X, margins, pred):
    from mmlspark_tpu.io.scoring import ColumnPlan, ScoringEngine
    from mmlspark_tpu.io.serving import HTTPServer

    bursts = (1, 2, 5, 12, 28)      # concurrent POSTs per burst
    logged = _ShapeLog(pred)
    # a first-time bucket compiles inside the request it serves
    server = HTTPServer(port=0, reply_timeout=300.0).start()
    engine = ScoringEngine(server, predictor=logged,
                           plan=ColumnPlan("features", X.shape[1]),
                           max_rows=64, latency_budget_ms=50.0).start()

    def post(i):
        req = urllib.request.Request(
            server.address, data=json.dumps(
                {"features": X[i].tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        # a non-200 raises HTTPError out of the phase: with
        # on_error="reply" that is how a scoring exception would show
        with urllib.request.urlopen(req, timeout=300) as resp:
            return i, resp.status, json.loads(resp.read())

    try:
        replies, row = [], 0
        with ThreadPoolExecutor(max(bursts)) as pool:
            for b in bursts:
                replies += list(pool.map(post, range(row, row + b)))
                row += b
        snap = engine.stats_snapshot()
    finally:
        engine.stop()
        server.stop()
    check(all(status == 200 for _, status, _ in replies),
          f"non-200 replies: {[r for r in replies if r[1] != 200]}")
    got = np.asarray([v for _, _, v in replies], np.float32)
    np.testing.assert_allclose(got, margins[[i for i, _, _ in replies]],
                               rtol=1e-5, atol=1e-6)
    say("serve", f"{len(replies)} POSTs, all 200, equal to the batch "
                 f"transform; padded batch sizes scored: "
                 f"{sorted(logged.sizes)}; counters {snap['counters']}")
    check(len(logged.sizes) > 1, "only one batch bucket was exercised")


def _auc_of(model, X, y):
    from sklearn.metrics import roc_auc_score
    p = np.asarray(model.transform({"features": X})["probability"])[:, 1]
    return float(roc_auc_score(y, p))


def phase_mesh(cfg, X, y, peaks):
    """resolve_mesh/prepare_arrays placed the flagship on every chip, and
    the meshed forest is the serial forest."""
    import jax

    from mmlspark_tpu.gbdt import LightGBMClassifier
    from mmlspark_tpu.gbdt import engine

    d = len(jax.devices())
    if all(p is not None for p in peaks):
        rows, f = cfg["rows"], cfg["features"]
        shard_bytes = rows // d * f                        # uint8 bins
        check(min(peaks) >= shard_bytes,
              f"a device peaked under its {shard_bytes}-byte bin shard: "
              f"{peaks}")
        # the first device also holds what one device handles alone:
        # the reference profile's 32 768-row sample (a chip's own peak is
        # small enough for that to show).  The table is not among it:
        # prepare_arrays puts each device's block straight from the host,
        # so no device holds the whole table
        staged = 2 * min(rows, 32_768) * f * 4
        rest = peaks[1:]
        check(max(rest) <= 2 * min(rest),
              f"per-device peaks differ by more than 2x: {peaks}")
        check(peaks[0] <= max(rest) + staged,
              f"the first device peaked over the others' peak plus the "
              f"{staged} bytes staged through it: {peaks}")
        say("mesh", f"per-device peaks after the flagship fit: devices 1.."
                    f"{d - 1} within {max(rest) / min(rest):.2f}x of each "
                    f"other, device 0 {peaks[0] - min(rest)} bytes over "
                    f"them ({staged} are staged through it)")
    else:
        say("mesh", "backend reports no memory stats: placement not checked")

    n = cfg["parity_rows"]
    table = {"features": X[:n], "label": y[:n]}
    kw = dict(numIterations=cfg["parity_iters"], autoMeshMinRows=n, **FIT_KW)
    serial = LightGBMClassifier(parallelism="serial", **kw).fit(table)
    check(engine.last_fit_info["collective_count_per_tree"] == "0",
          "the serial reference fit used collectives")
    meshed = LightGBMClassifier(**kw).fit(table)
    check(int(engine.last_fit_info["collective_count_per_tree"]) > 0,
          "the auto-meshed fit ran without collectives")
    st, mt = serial.getModel().trees, meshed.getModel().trees
    check(len(st) == len(mt), f"{len(st)} serial vs {len(mt)} mesh trees")
    for s, t in zip(st, mt):
        np.testing.assert_array_equal(s.split_feature, t.split_feature)
        np.testing.assert_allclose(s.leaf_value, t.leaf_value, rtol=1e-4,
                                   atol=1e-6)
    dm = float(np.abs(
        serial.getModel().predict_margin(X[:n])
        - meshed.getModel().predict_margin(X[:n])).max())
    say("mesh", f"serial-vs-mesh forest parity on {n} rows, D={d}: same "
                f"splits, max |dmargin| {dm:.2e}")
    return _auc_of(meshed, X[:n], y[:n])


def _report_match(name, got, want, rtol, atol):
    got, want = np.asarray(got), np.asarray(want)
    say("kernels", f"{name}: max |diff| {np.abs(got - want).max():.2e} "
                   f"(max |ref| {np.abs(want).max():.2e})")
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                               err_msg=name)


def _report_hist_match(name, got, want, n, num_bins):
    """A matmul histogram against the scatter reference.  The count
    channel must be EXACT: every row landed in its (feature, bin) cell.
    grad/hess pass the MXU as bf16 operands (measured on v5e: the builds
    differ from the scatter by ~0.0075*sqrt(rows per bin)), so they are
    held to that rounding and no tighter."""
    got, want = np.asarray(got), np.asarray(want)
    check(np.array_equal(got[..., 2], want[..., 2]),
          f"{name}: count channel differs from the scatter reference")
    _report_match(name, got[..., :2], want[..., :2], rtol=2e-2,
                  atol=2e-2 * np.sqrt(n / num_bins))


def phase_kernels(cfg, on_tpu, X, y, auc_psum):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from mmlspark_tpu.core.backend import pallas_interpret
    from mmlspark_tpu.core.mesh import DATA_AXIS
    from mmlspark_tpu.gbdt import LightGBMClassifier
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.grower import GrowerConfig, _build_sizes
    from mmlspark_tpu.ops.histogram import (compute_histogram,
                                            histogram_build)
    from mmlspark_tpu.ops.pallas_collectives import (ring_allreduce,
                                                     ring_allreduce_select)

    interp = pallas_interpret()
    rows, f, B = cfg["rows"], cfg["features"], 256
    rng = np.random.default_rng(1)
    say("kernels", f"pallas interpret mode: {interp}")

    # -- the dot16 build at every shape the flagship grower issues: the
    # bucket ladder and the root's full matrix (on the TPU the Mosaic
    # kernel; XLA's formulation of it in a rehearsal)
    sizes = _build_sizes(rows, GrowerConfig()) + [rows]
    build = histogram_build("dot16", B, quantized=False)
    say("kernels", f"method dot16 compiles: {build}")
    check(build == "dot16/mosaic" or not on_tpu,
          f"dot16 on the TPU compiles {build!r}, not the Mosaic kernel")
    for n in sizes:
        bins = jnp.asarray(rng.integers(0, B, size=(n, f), dtype=np.uint8))
        gh = jnp.asarray(np.stack([rng.normal(size=n),
                                   np.abs(rng.normal(size=n)),
                                   np.ones(n)], 1), jnp.float32)
        _report_hist_match(f"dot16 n={n}",
                           compute_histogram(bins, gh, B, method="dot16"),
                           compute_histogram(bins, gh, B, method="segment"),
                           n, B)

    d = len(jax.devices())
    if d == 1:
        say("kernels", "one device: the ring kernels need D > 1 and are "
                       "not exercised here")
        return

    # -- ring kernels over every chip, against lax.psum, launched twice
    # back to back (the second launch needs the first one's semaphores
    # drained)
    mesh = Mesh(np.asarray(jax.devices()), (DATA_AXIS,))
    spec = P(DATA_AXIS, None, None)

    def smap(fn, in_specs, out_specs=spec):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    hist = jnp.asarray(rng.normal(size=(d * f, B, 3)), jnp.float32)
    psum = smap(lambda a: jax.lax.psum(a, DATA_AXIS), spec)
    ring = smap(lambda a: ring_allreduce(a, DATA_AXIS, d, interpret=interp),
                spec)
    want = psum(hist)
    for launch in (1, 2):
        _report_match(f"_ring_allreduce_kernel dense ({f},{B},3) D={d} "
                      f"launch {launch}", ring(hist), want, 1e-5, 1e-4)
    k2 = min(40, f)
    cand = jnp.asarray(np.tile(rng.permutation(f)[:k2], d), jnp.int32)
    sel = smap(lambda a, c: ring_allreduce_select(a, c, DATA_AXIS, d,
                                                  interpret=interp),
               (spec, P(DATA_AXIS)))
    sel_ref = smap(lambda a, c: jax.lax.psum(jnp.take(a, c, axis=0),
                                             DATA_AXIS),
                   (spec, P(DATA_AXIS)))
    for launch in (1, 2):
        _report_match(f"_ring_allreduce_kernel voted select k2={k2} D={d} "
                      f"launch {launch}", sel(hist, cand),
                      sel_ref(hist, cand), 1e-5, 1e-4)

    # -- the wiring: collective=ring through the estimator, dense and
    # voted (data_only_mesh, LOGICAL device ids on the one-axis mesh)
    n = cfg["parity_rows"]
    X, y = X[:n], y[:n]
    table = {"features": X, "label": y}
    for extra in ({}, {"parallelism": "voting", "topK": 20}):
        model = LightGBMClassifier(
            numIterations=cfg["parity_iters"], autoMeshMinRows=n,
            collective="ring", **extra, **FIT_KW).fit(table)
        info = dict(engine.last_fit_info)
        auc = _auc_of(model, X, y)
        say("kernels", f"fit collective=ring {extra}: resolved "
                       f"{info['collective']} (downgrade "
                       f"{info['collective_downgrade']}), payload "
                       f"{info['collective_payload_bytes_per_tree']} B/tree,"
                       f" AUC {auc:.4f} vs psum {auc_psum:.4f}")
        check(info["collective"] == "ring"
              and info["collective_downgrade"] == "none",
              f"collective=ring resolved to {info}")
        check(abs(auc - auc_psum) <= 0.01,
              f"ring fit AUC {auc:.4f} far from the psum fit's "
              f"{auc_psum:.4f}")


# ----------------------------------------------------------------- main


def main(argv):
    rehearse = "--rehearse" in argv
    check(set(argv) <= {"--rehearse"}, f"unknown arguments: {argv}")
    cfg = TINY if rehearse else FULL
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    t_start = time.perf_counter()

    device = phase_device(rehearse)
    on_tpu = device["platform"] == "tpu"
    phase_host()
    X, y, model, margins, peaks = phase_train(cfg, on_tpu)
    pred = phase_score(X, model, on_tpu)
    phase_serve(X, margins, pred)
    auc_psum = None
    if device["count"] > 1:
        auc_psum = phase_mesh(cfg, X, y, peaks)
    phase_kernels(cfg, on_tpu, X, y, auc_psum)

    stray = [t.name for t in threading.enumerate()
             if t is not threading.main_thread() and not t.daemon]
    check(not stray, f"threads left running: {stray}")
    total = time.perf_counter() - t_start
    if rehearse:
        say("done", f"REHEARSAL complete in {total:.0f} s on platform "
                    f"{device['platform']} ({device['kind']} x "
                    f"{device['count']}): every phase ran; this is NOT a "
                    f"chip pass and prints no result")
        return REHEARSAL_EXIT
    say("done", f"all phases passed in {total:.0f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
