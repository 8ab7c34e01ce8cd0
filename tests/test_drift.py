"""Streaming data-quality & drift observability (ISSUE 15): the
sketch machinery (mergeable streaming sketches, PSI/JS), fit-time
reference-profile capture + registry persistence, the DriftMonitor's
live-traffic pipeline and alert state machine, the scoring-engine /
rollout wiring, the ChaosDrift injector, and the drift_report CLI.
Tier-1 smoke for tools/chaos_drift.py's contract."""

import argparse
import importlib.util
import json
import logging
import os
import queue
import time

import numpy as np
import pytest

from mmlspark_tpu.core.drift import (DriftConfig, DriftMonitor,
                                     drift_report_from_counters,
                                     peek_drift_monitor,
                                     set_drift_monitor,
                                     sketches_from_counters)
from mmlspark_tpu.core.sketch import (MatrixSketch, ReferenceProfile,
                                      StreamSketch,
                                      build_reference_profile,
                                      downsample_edges, js_divergence,
                                      merge_sketch_snapshots, psi)
from mmlspark_tpu.core.telemetry import (get_journal, get_registry,
                                         merge_snapshots)
from mmlspark_tpu.gbdt import LightGBMRegressor
from mmlspark_tpu.gbdt.binning import fit_bin_mapper
from mmlspark_tpu.io.chaos import ChaosDrift, ChaosPlan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        f"_tool_{name}", os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fitted():
    """One small fitted model + its training matrix; the fit captures
    the reference profile (the engine-side tentpole hook)."""
    rng = np.random.default_rng(15)
    X = rng.normal(size=(1200, 6)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]).astype(np.float64)
    booster = LightGBMRegressor(numIterations=6, numLeaves=15,
                                parallelism="serial", verbosity=0).fit(
        {"features": X, "label": y}).getModel()
    return X, y, booster


_LIVE_MONITORS = []


@pytest.fixture(autouse=True)
def monitor_thread_hygiene():
    """Every monitor created through drill_monitor gets its drain
    thread closed after the test — a suite-long accumulation of idle
    daemon threads is exactly the kind of ambient state later
    jax-heavy tests should not run under."""
    yield
    while _LIVE_MONITORS:
        try:
            _LIVE_MONITORS.pop().close()
        except Exception:
            pass


@pytest.fixture()
def monitor_cleanup():
    yield
    set_drift_monitor(None)


def drill_monitor(profile, **over):
    """Drill-grade config: every batch sketched, instant evaluation."""
    kw = dict(duty=1.0, eval_interval_s=0.0, min_rows=200)
    kw.update(over)
    mon = DriftMonitor(profile, DriftConfig(**kw))
    _LIVE_MONITORS.append(mon)
    return mon


# ------------------------------------------------------------- sketches


class TestStreamSketch:
    def test_counts_nan_inf_and_range(self):
        sk = StreamSketch([0.0, 1.0, 2.0], lo=0.0, hi=2.0)
        sk.update(np.array([-1.0, 0.5, 1.5, 3.0, np.nan, np.inf,
                            -np.inf], np.float32))
        assert sk.nan == 1
        assert sk.posinf == 1 and sk.neginf == 1
        assert sk.count == 6                    # non-NaN observations
        assert sk.below == 2                    # -1 and -inf
        assert sk.above == 2                    # 3 and +inf
        # buckets: (-inf,0], (0,1], (1,2], (2,inf)
        assert sk.counts.tolist() == [2, 1, 1, 2]
        assert sk.total == 7
        assert sk.null_rate() == pytest.approx(1 / 7)

    def test_moments_match_numpy(self):
        rng = np.random.default_rng(0)
        v = rng.normal(3.0, 2.0, size=5000)
        sk = StreamSketch([0.0])
        for part in np.array_split(v, 7):       # batched Welford
            sk.update(part)
        assert sk.mean == pytest.approx(v.mean(), rel=1e-9)
        assert sk.var == pytest.approx(v.var(), rel=1e-9)

    def test_snapshot_roundtrip_and_stable_keys(self):
        sk = StreamSketch([0.0, 1.0], lo=0.0, hi=1.0)
        sk.update(np.array([-1.0, 0.5, 2.0, np.nan]))
        snap = sk.snapshot()
        # keys are stringified bucket indices — the bit-stable wire
        # contract cross-process merges rely on
        assert set(snap["buckets"]) <= {"0", "1", "2"}
        back = StreamSketch.from_snapshot(snap, [0.0, 1.0], 0.0, 1.0)
        assert np.array_equal(back.counts, sk.counts)
        assert back.nan == sk.nan and back.count == sk.count
        assert back.mean == pytest.approx(sk.mean)

    def test_quantiles_from_buckets(self):
        edges = np.linspace(-3, 3, 25)
        sk = StreamSketch(edges)
        v = np.random.default_rng(1).normal(size=20000)
        sk.update(v)
        assert sk.quantile(0.5) == pytest.approx(
            np.quantile(v, 0.5), abs=0.3)
        assert sk.quantile(0.9) == pytest.approx(
            np.quantile(v, 0.9), abs=0.3)


class TestSketchMerging:
    """The satellite guarantee: merging K per-worker sketches yields
    the SAME counts and quantile buckets as one sketch over the
    concatenated rows, with bit-stable snapshot keys."""

    def test_kway_merge_equals_concatenated(self):
        rng = np.random.default_rng(2)
        X = rng.normal(size=(4000, 5)).astype(np.float32)
        X[rng.random(X.shape) < 0.03] = np.nan
        edges = [np.linspace(-2, 2, 17)] * 5
        whole = MatrixSketch(edges)
        whole.update(X)
        parts = []
        for chunk in np.array_split(X, 7):      # 7 "workers"
            m = MatrixSketch(edges)
            m.update(chunk)
            parts.append(m)
        for j in range(5):
            merged = merge_sketch_snapshots(
                [p.features[j].snapshot() for p in parts])
            one = whole.features[j].snapshot()
            assert merged["buckets"] == one["buckets"]
            assert merged["n"] == one["n"]
            assert merged["nan"] == one["nan"]
            # moments merge via Chan's formula: different association
            # order than the sequential pass, so approximate equality
            # (the bit-stable guarantee covers counts/buckets only)
            assert merged["mean"] == pytest.approx(one["mean"],
                                                   rel=1e-5)
            assert merged["m2"] == pytest.approx(one["m2"], rel=1e-4)

    def test_cross_process_merge_via_metrics_snapshots(self, fitted,
                                                       monitor_cleanup):
        """DriftMonitor.snapshot() blocks merge through the EXISTING
        telemetry merge (counters key-wise sum) and the merged
        counters reconstruct to the same sketch one monitor over all
        rows would hold — the 'merged across processes through the
        metrics scrape exactly like StageStats' contract."""
        X, _y, booster = fitted
        prof = booster.reference_profile
        halves = np.array_split(X, 3)
        monitors = []
        for part in halves:                     # 3 "worker processes"
            m = drill_monitor(prof)
            assert m.observe(part, np.asarray(
                booster.predict_margin(part)))
            m.flush()
            monitors.append(m)
        merged = merge_snapshots([m.snapshot() for m in monitors])
        one = drill_monitor(prof)
        one.observe(X, np.asarray(booster.predict_margin(X)))
        one.flush()
        single = one.snapshot()
        # every sketch counter merges exactly
        for k, v in single["counters"].items():
            assert merged["counters"].get(k) == v, k
        feats, margin = sketches_from_counters(merged["counters"],
                                               prof)
        assert sum(f.total for f in feats) == X.size
        rep = drift_report_from_counters(merged["counters"], prof)
        assert not rep["alerting"]
        assert rep["rows_observed"] == len(X)


class TestDivergences:
    def test_psi_and_js_basics(self):
        ref = np.array([100, 200, 300, 200, 100, 0])
        assert psi(ref, ref * 7) == pytest.approx(0.0, abs=1e-9)
        shifted = np.array([0, 10, 50, 200, 400, 340])
        assert psi(ref, shifted) > 0.5
        assert 0.0 <= js_divergence(ref, shifted) <= 1.0
        assert js_divergence(ref, ref) == pytest.approx(0.0, abs=1e-9)

    def test_nan_storm_moves_distribution(self):
        """The missing tally rides as a distribution slot: an all-NaN
        live feed is a huge PSI even though every finite value is
        on-distribution."""
        ref = StreamSketch([0.0, 1.0])
        ref.update(np.linspace(0, 1, 1000))
        live = StreamSketch([0.0, 1.0])
        live.update(np.full(1000, np.nan))
        assert psi(ref.dist_counts(), live.dist_counts()) > 1.0


# ----------------------------------------------------- reference profile


class TestReferenceProfile:
    def test_build_from_bins_matches_raw_counts(self):
        rng = np.random.default_rng(3)
        X = rng.normal(size=(3000, 4)).astype(np.float32)
        X[rng.random(X.shape) < 0.02] = np.nan
        mapper = fit_bin_mapper(X, max_bin=63)
        prof = build_reference_profile(
            mapper.transform_packed(X), mapper,
            rng.normal(size=3000))
        live = prof.live_matrix_sketch()
        live.update(X)
        for j in range(4):
            ref = prof.ref_feature(j)
            assert np.array_equal(ref.counts, live.features[j].counts)
            assert ref.nan == live.features[j].nan

    def test_json_roundtrip(self, fitted):
        _X, _y, booster = fitted
        prof = booster.reference_profile
        back = ReferenceProfile.from_json(prof.to_json())
        assert back.feature_names == prof.feature_names
        for a, b in zip(back.feature_edges, prof.feature_edges):
            assert np.array_equal(a, b)
        assert back.margin_sketch == prof.margin_sketch

    def test_downsample_edges_is_subset(self):
        edges = np.sort(np.random.default_rng(4).normal(size=200))
        coarse = downsample_edges(edges, 31)
        assert len(coarse) == 31
        assert np.isin(coarse, edges).all()
        assert coarse[0] == edges[0] and coarse[-1] == edges[-1]

    def test_fit_captures_profile_and_margin_baseline(self, fitted):
        X, _y, booster = fitted
        prof = booster.reference_profile
        assert prof is not None
        assert prof.num_features == X.shape[1]
        assert prof.meta["n_rows"] == len(X)
        # the bin-representative predict pass routes to the exact
        # leaves the raw rows would: training margins land dead-on
        # the reference margin distribution
        live = prof.live_margin_sketch()
        live.update(np.asarray(booster.predict_margin(X)))
        assert psi(prof.ref_margin().dist_counts(),
                   live.dist_counts()) < 0.05

    def test_env_gate_disables_capture(self, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_REF_PROFILE", "0")
        rng = np.random.default_rng(5)
        X = rng.normal(size=(300, 3)).astype(np.float32)
        y = X[:, 0].astype(np.float64)
        b = LightGBMRegressor(numIterations=3, numLeaves=7,
                              parallelism="serial", verbosity=0).fit(
            {"features": X, "label": y}).getModel()
        assert b.reference_profile is None


# --------------------------------- device counts (ISSUE 30; gbdt/engine.py)


def _profile_doc(profile):
    doc = json.loads(profile.to_json())
    for key in ("created", "fit_span"):
        doc["meta"].pop(key)
    return doc


def _count_case(case):
    """``(X, categorical columns, max_bin, mesh)`` of one case."""
    rng = np.random.default_rng(30)
    n = 40003 if case == "mesh4_pad_rows" else 36000
    X = rng.normal(size=(n, 7)).astype(np.float32)
    cats, max_bin, mesh = [], 63, None
    if case == "missing":
        X[rng.random(X.shape) < 0.07] = np.nan
    elif case == "categorical":
        cats = [1, 4]
        X[:, 1] = rng.integers(0, 40, n)
        X[:, 4] = rng.integers(0, 3, n) * 7
    elif case == "wide_bins":
        max_bin = 300
    elif case == "mesh4_pad_rows":
        import jax
        from jax.sharding import Mesh
        from mmlspark_tpu.core.mesh import DATA_AXIS, FEATURE_AXIS
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4, 1),
                    (DATA_AXIS, FEATURE_AXIS))
    return X, cats, max_bin, mesh


@pytest.mark.parametrize("case", ["numeric", "missing", "categorical",
                                  "wide_bins", "mesh4_pad_rows"])
def test_profile_from_device_counts_equals_the_host_tables(case):
    """The profile a fit leaves, its rows counted on the device from the
    table the fit uploaded, is the profile the host's column passes give:
    the same JSON but for the clock and the fit's id.  Over 32 768 rows,
    so the margins come from the sampled rows; on the mesh the rows are
    no multiple of 4 and the pad rows must not be counted."""
    from mmlspark_tpu.core.profiler import get_profiler
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.objectives import get_objective
    X, cats, max_bin, mesh = _count_case(case)
    # column 1's effect has no order: a categorical split's to find
    y = (X[:, 0] + np.nan_to_num(X[:, 2]) + 2 * (X[:, 1] % 3 == 0)
         > 1).astype(np.float32)
    mapper = fit_bin_mapper(X, max_bin=max_bin, seed=0,
                            categorical_features=cats)
    bins = mapper.transform_packed(X)
    assert bins.dtype == (np.int32 if case == "wide_bins" else np.uint8)
    names = [f"c{j}" for j in range(X.shape[1])]
    booster = engine.train(
        bins, y, None, mapper, get_objective("binary"),
        engine.TrainParams(num_iterations=3, num_leaves=7), names,
        mesh=mesh)
    span = [s for s in get_profiler().spans()
            if s["name"] == "train.reference_profile"][-1]
    assert span["attrs"] == {"rows": len(X), "counts": "device"}
    from_device = booster.reference_profile
    engine._capture_reference_profile(booster, bins, mapper, names)
    assert booster.reference_profile is not from_device
    assert _profile_doc(from_device) == _profile_doc(
        booster.reference_profile)
    assert from_device.meta["n_rows"] == len(X)
    if case == "categorical":
        assert any(t.num_cat for t in booster.trees)


def test_device_counts_are_exact_past_2p24_rows_a_bin():
    """A float32 sum stops counting at 2^24; the pass adds row chunks as
    int32 (ops/histogram.bin_counts)."""
    import jax.numpy as jnp
    from mmlspark_tpu.gbdt import engine
    n = (1 << 24) + 4099
    bins = np.full((n, 1), 3, np.uint8)
    bins[::4096] = 5
    counts = np.asarray(engine._table_bin_counts(jnp.asarray(bins), 8))
    assert counts.dtype == np.int32 and counts.shape == (1, 8)
    assert counts[0].tolist() == np.bincount(bins[:, 0],
                                             minlength=8).tolist()
    assert counts[0, 3] > 1 << 24


@pytest.mark.parametrize("dtype,num_bins", [(np.uint8, 256),
                                            (np.int32, 300)])
def test_representative_rows_is_the_tables_lookup(dtype, num_bins):
    """What an accelerator backend runs in place of the host's fancy
    index (the CPU backend keeps numpy rows for ``predict_margin``'s
    native walk): the same float32, NaN where the table has one."""
    from mmlspark_tpu.gbdt import engine
    rng = np.random.default_rng(2)
    table = rng.normal(size=(5, num_bins)).astype(np.float32)
    table[:, -1] = np.nan
    table[2, 7] = np.nan
    sample = rng.integers(0, num_bins, (700, 5)).astype(dtype)
    sample[:9, 2] = 7
    out = np.asarray(engine._representative_rows(sample, table))
    assert out.dtype == np.float32
    assert np.array_equal(out, table[np.arange(5), sample], equal_nan=True)


# ------------------------------------------------- registry persistence


class TestRegistryProfile:
    def test_publish_persists_and_load_attaches(self, fitted,
                                                tmp_path):
        from mmlspark_tpu.io.registry import ModelRegistry
        _X, _y, booster = fitted
        reg = ModelRegistry(str(tmp_path))
        v = reg.publish(booster, activate=True)
        e = reg.entry(v)
        assert e["profile_digest"].startswith("sha256:")
        assert os.path.exists(reg.profile_path(v))
        loaded = reg.load(v)
        assert loaded.reference_profile is not None
        assert loaded.reference_profile.feature_names == \
            booster.reference_profile.feature_names

    def test_legacy_entry_degrades_gracefully(self, fitted, tmp_path,
                                              caplog):
        from mmlspark_tpu.io.registry import ModelRegistry
        _X, _y, booster = fitted
        reg = ModelRegistry(str(tmp_path))
        # a raw-text publish is the digest-less legacy shape: no
        # profile recorded
        v = reg.publish(booster.save_native_model_string(),
                        activate=True)
        with caplog.at_level(logging.WARNING,
                             logger="mmlspark_tpu.io.registry"):
            loaded = reg.load(v)
        assert loaded.reference_profile is None
        assert any("no reference profile" in r.message
                   for r in caplog.records)

    def test_corrupt_profile_quarantines(self, fitted, tmp_path):
        from mmlspark_tpu.io.registry import (ModelCorruption,
                                              ModelRegistry)
        _X, _y, booster = fitted
        reg = ModelRegistry(str(tmp_path))
        v = reg.publish(booster, activate=True)
        path = reg.profile_path(v)
        with open(path, "r+b") as fh:
            fh.seek(16)
            fh.write(b"\xff")
        with pytest.raises(ModelCorruption):
            reg.load_profile(v)
        assert reg.entry(v)["promoted_state"] == "quarantined"

    def test_profile_write_is_atomic_discipline(self, fitted,
                                                tmp_path):
        """The profile file's bytes hash to the recorded digest (the
        same self-verifying contract as the model file) and no .tmp
        residue survives the publish."""
        from mmlspark_tpu.io.registry import ModelRegistry, sha256_hex
        _X, _y, booster = fitted
        reg = ModelRegistry(str(tmp_path))
        v = reg.publish(booster)
        with open(reg.profile_path(v), "rb") as fh:
            data = fh.read()
        want = reg.entry(v)["profile_digest"].split(":", 1)[-1]
        assert sha256_hex(data) == want
        assert not [p for p in os.listdir(os.path.join(
            str(tmp_path), "models")) if p.endswith(".tmp")]


# ------------------------------------------------------- drift monitor


class TestDriftMonitor:
    def test_clean_traffic_no_alert(self, fitted, monitor_cleanup):
        X, _y, booster = fitted
        mon = drill_monitor(booster.reference_profile)
        rng = np.random.default_rng(6)
        for _ in range(5):
            batch = X[rng.integers(0, len(X), 300)]
            assert mon.observe(batch, np.asarray(
                booster.predict_margin(batch)))
        rep = mon.report()
        assert not rep["alerting"]
        assert rep["rows_observed"] == 1500
        assert rep["gauges"]["psi_worst"] < 0.25

    def test_shift_detected_and_journaled(self, fitted,
                                          monitor_cleanup):
        X, _y, booster = fitted
        mon = drill_monitor(booster.reference_profile)
        seq0 = (get_journal().events()[-1]["seq"]
                if get_journal().events() else 0)
        Xd = X[:1000].copy()
        Xd[:, 3] += 4.0
        mon.observe(Xd, np.zeros(1000))
        rep = mon.report()
        assert "f3" in rep["alerting"]
        assert rep["worst_feature"] == "f3"
        onsets = [e for e in get_journal().events()
                  if e["ev"] == "drift_onset" and e["seq"] > seq0]
        assert any(e["signal"] == "f3" for e in onsets)
        # recovery: fresh clean window (epoch rotation) clears it
        mon.cfg.window_s = 0.05
        time.sleep(0.12)
        for _ in range(3):
            mon.observe(X[:500], np.zeros(500))
            mon.flush()
            time.sleep(0.06)
        rep2 = mon.report()
        assert "f3" not in rep2["alerting"]
        recov = [e for e in get_journal().events()
                 if e["ev"] == "drift_recovered" and e["seq"] > seq0]
        assert any(e["signal"] == "f3" for e in recov)

    def test_min_rows_guards_noise(self, fitted, monitor_cleanup):
        X, _y, booster = fitted
        mon = drill_monitor(booster.reference_profile, min_rows=500)
        Xd = X[:100].copy()
        Xd[:, 0] += 10.0
        mon.observe(Xd)
        rep = mon.report()
        assert not rep["alerting"]          # 100 rows < min_rows

    def test_duty_gate_skips_and_counts(self, fitted,
                                        monitor_cleanup):
        X, _y, booster = fitted
        mon = DriftMonitor(booster.reference_profile,
                           DriftConfig(duty=1e-4))
        _LIVE_MONITORS.append(mon)
        assert mon.observe(X[:200])          # first batch always in
        mon.flush()
        skipped = 0
        for _ in range(20):                  # cooldown armed: skipped
            if not mon.observe(X[:50]):
                skipped += 1
        assert skipped == 20
        assert mon.snapshot()["counters"]["rows_skipped"] == 1000

    def test_prediction_drift_flags(self, fitted, monitor_cleanup):
        X, _y, booster = fitted
        mon = drill_monitor(booster.reference_profile)
        # wildly shifted margins, on-distribution features
        mon.observe(X[:1000],
                    np.asarray(booster.predict_margin(X[:1000])) + 50)
        rep = mon.report()
        assert "_prediction_" in rep["alerting"]
        assert rep["gauges"]["psi_prediction"] > 0.25

    def test_slo_objectives_read_the_gauges(self, fitted,
                                            monitor_cleanup):
        from mmlspark_tpu.core.slo import SLOMonitor, default_objectives
        X, _y, booster = fitted
        mon = drill_monitor(booster.reference_profile)
        set_drift_monitor(mon)
        Xd = X[:600].copy()
        Xd[:, 2] += 5.0
        mon.observe(Xd)
        mon.report()
        objs = [o for o in default_objectives()
                if o.name in ("feature_drift", "prediction_drift")]
        slo = SLOMonitor(objs, fast_window_s=3.0, slow_window_s=6.0)
        for i in range(8):
            slo.sample(now=float(i))
        verdicts = slo.evaluate()
        assert verdicts["feature_drift"]["breach"]
        assert not verdicts["prediction_drift"]["breach"]

    def test_exposition_families(self, fitted, monitor_cleanup):
        X, _y, booster = fitted
        mon = drill_monitor(booster.reference_profile)
        mon.observe(X[:300], np.zeros(300))
        mon.flush()
        set_drift_monitor(mon)
        text = get_registry().render_prometheus()
        for fam in ("mmlspark_tpu_drift_psi",
                    "mmlspark_tpu_drift_js",
                    "mmlspark_tpu_drift_null_rate",
                    "mmlspark_tpu_drift_out_of_range_ratio",
                    "mmlspark_tpu_drift_alert",
                    "mmlspark_tpu_drift_rows_total",
                    "mmlspark_tpu_drift_enabled"):
            assert fam in text, fam
        assert 'signal="_prediction_"' in text
        set_drift_monitor(None)
        assert peek_drift_monitor() is None
        assert "mmlspark_tpu_drift_psi" not in \
            get_registry().render_prometheus()


# ------------------------------------------------- engine + rollout wiring


class _QueueServer:
    def __init__(self):
        self.request_queue = queue.Queue()
        self.replies = {}

    def reply(self, rid, body, status=200):
        self.replies[rid] = (body, status)


def _pump(server, eng_rows, rows, tag):
    for i, row in enumerate(rows):
        server.request_queue.put(
            (f"{tag}{eng_rows + i}",
             {"features": [float(v) for v in row]}))
    deadline = time.time() + 20
    while len(server.replies) < eng_rows + len(rows):
        assert time.time() < deadline, "pump timeout"
        time.sleep(0.005)
    return eng_rows + len(rows)


class TestScoringEngineWiring:
    def test_engine_observes_scored_batches(self, fitted,
                                            monitor_cleanup):
        from mmlspark_tpu.io.scoring import ColumnPlan, ScoringEngine
        X, _y, booster = fitted
        server = _QueueServer()
        mon = drill_monitor(booster.reference_profile)
        eng = ScoringEngine(server,
                            predictor=booster.predictor(
                                backend="auto"),
                            plan=ColumnPlan("features", X.shape[1]),
                            max_rows=64, latency_budget_ms=2.0,
                            num_scorers=1, num_repliers=0,
                            drift_monitor=mon).start()
        try:
            assert peek_drift_monitor() is mon
            _pump(server, 0, X[:400], "a")
        finally:
            eng.stop()
        rep = mon.report()
        assert rep["rows_observed"] == 400
        assert not rep["alerting"]
        # margins were observed too (the prediction sketch filled)
        assert rep["signals"][-1]["rows"] == 400


class TestTopologyScrapeMerge:
    def test_driver_scrape_merges_worker_drift_blocks(self, fitted,
                                                      monitor_cleanup):
        """The multiprocess driver's /metrics render folds the
        workers' beaconed drift blocks into one merged ns="drift"
        view (counters sum, gauges worst-of) — the topology half of
        the scrape-merge contract (the beacon transport itself rides
        the serving tests)."""
        from mmlspark_tpu.io.serving import MultiprocessHTTPServer
        X, _y, booster = fitted
        prof = booster.reference_profile
        srv = MultiprocessHTTPServer(num_workers=2,
                                     spawn_workers=False)
        blocks = []
        for k, part in enumerate(np.array_split(X[:600], 2)):
            m = drill_monitor(prof)
            m.observe(part, np.asarray(booster.predict_margin(part)))
            m.flush()
            m.evaluate(force=True)
            blocks.append(m.snapshot())
            srv.worker_drift[k] = blocks[-1]
        text = srv.render_metrics()
        assert 'ns="drift"' in text
        merged_rows = sum(b["counters"]["rows_observed"]
                          for b in blocks)
        assert (f'mmlspark_tpu_events_total{{event="rows_observed",'
                f'ns="drift"}} {merged_rows}') in text


class TestRolloutDriftGate:
    def test_drifting_feed_rolls_canary_back(self, fitted, tmp_path,
                                             monitor_cleanup):
        from mmlspark_tpu.io.registry import ModelRegistry
        from mmlspark_tpu.io.rollout import (RolloutConfig,
                                             RolloutController)
        X, y, booster = fitted
        reg = ModelRegistry(str(tmp_path))
        reg.publish(booster, activate=True)
        b2 = LightGBMRegressor(numIterations=9, numLeaves=15,
                               parallelism="serial", verbosity=0).fit(
            {"features": X, "label": y}).getModel()
        v2 = reg.publish(b2)
        cfg = RolloutConfig(canary_fraction=0.3, soak_s=60.0,
                            min_canary_rows=10 ** 6,
                            canary_deadline_ms=None,
                            fast_window_s=0.3, slow_window_s=0.6,
                            live_drift_threshold=0.25)
        ctl = RolloutController(reg, backend="auto", config=cfg)
        mon = drill_monitor(booster.reference_profile)
        ctl.attach_drift(mon)
        ctl.start_canary(v2)
        rids = [f"r{i}" for i in range(200)]
        # clean soak holds
        for _ in range(4):
            out = ctl.score_routed(X[:200], rids)
            mon.observe(X[:200], out)
            assert ctl.tick() == "soaking"
            time.sleep(0.12)
        # the FEED drifts under the soaking canary
        Xd = X[:200].copy()
        Xd[:, 1] += 5.0
        state = "soaking"
        for _ in range(15):
            out = ctl.score_routed(Xd, rids)
            mon.observe(Xd, out)
            state = ctl.tick()
            if state == "rolled_back":
                break
            time.sleep(0.12)
        assert state == "rolled_back"
        ev = [e for e in get_journal().events()
              if e["ev"] == "rollout_rolled_back"][-1]
        assert "canary_live_drift" in ev["reason"] \
            or "canary_prediction_drift" in ev["reason"]
        assert reg.entry(v2)["promoted_state"] == "rolled_back"


# ------------------------------------------------------- chaos injector


class TestChaosDrift:
    def test_after_rows_boundary_mid_batch(self):
        plan = ChaosPlan(3)
        d = ChaosDrift(plan, feature=1, shift=10.0, after_rows=25)
        X = np.zeros((40, 3), np.float32)
        out = d(X)
        assert (out[:25, 1] == 0).all()
        assert (out[25:, 1] == 10.0).all()
        assert (X[:, 1] == 0).all()           # input never mutated
        assert d.rows_injected == 15
        out2 = d(np.zeros((10, 3), np.float32))
        assert (out2[:, 1] == 10.0).all()     # fully past the cut

    def test_nan_injection_is_seeded_deterministic(self):
        X = np.zeros((200, 2), np.float32)
        outs = []
        for _ in range(2):
            d = ChaosDrift(ChaosPlan(9), feature=0, nan_rate=0.5)
            outs.append(np.isnan(d(X)[:, 0]))
        assert np.array_equal(outs[0], outs[1])
        assert 40 < outs[0].sum() < 160
        d2 = ChaosDrift(ChaosPlan(10), feature=0, nan_rate=0.5)
        assert not np.array_equal(outs[0], np.isnan(d2(X)[:, 0]))


# ------------------------------------------------------------ tools


class TestDriftReportCLI:
    def test_names_injected_feature_top(self, fitted, tmp_path,
                                        capsys, monitor_cleanup):
        X, _y, booster = fitted
        prof = booster.reference_profile
        mon = drill_monitor(prof)
        Xd = X[:800].copy()
        Xd[:, 4] *= 3.0
        mon.observe(Xd, np.zeros(800))
        mon.flush()
        ppath = tmp_path / "profile.json"
        cpath = tmp_path / "counters.json"
        ppath.write_text(prof.to_json())
        cpath.write_text(json.dumps(mon.snapshot()))
        tool = _tool("drift_report")
        assert tool.main(["--profile", str(ppath), "--counters",
                          str(cpath), "--top", "3"]) == 0
        out = capsys.readouterr().out
        assert "top drifter: f4" in out
        assert "ALERT" in out
        # --json mode round-trips the report schema
        assert tool.main(["--profile", str(ppath), "--counters",
                          str(cpath), "--json"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["worst_feature"] == "f4"

    def test_reads_committed_drill_artifact(self, capsys):
        art = os.path.join(REPO, "artifacts", "chaos_drift_r15.json")
        if not os.path.exists(art):
            pytest.skip("no committed chaos_drift artifact")
        tool = _tool("drift_report")
        assert tool.main(["--artifact", art,
                          "--scenario", "feature_shift"]) == 0
        out = capsys.readouterr().out
        with open(art) as fh:
            injected = json.load(fh)["scenarios"]["feature_shift"][
                "injected_feature"]
        assert f"top drifter: {injected}" in out


class TestCommittedDrillArtifact:
    def test_all_verdicts_pass(self):
        art = os.path.join(REPO, "artifacts", "chaos_drift_r15.json")
        if not os.path.exists(art):
            pytest.skip("no committed chaos_drift artifact")
        with open(art) as fh:
            a = json.load(fh)
        assert a["healthy"], [v for s in a["scenarios"].values()
                              for v in s["verdicts"] if not v["pass"]]
        assert a["verdicts_pass"] == a["verdicts_total"]
        sc = a["scenarios"]
        assert sc["feature_shift"]["detection_rows"] is not None
        assert "canary_live_drift" in \
            sc["canary_drift_rollback"]["rollback_reason"]


# -------------------------------------------------------- overhead (tier-1)


class TestSketchOverhead:
    def test_enabled_vs_disabled_p50_delta_under_3pct(self,
                                                      monitor_cleanup):
        """ISSUE 15 satellite: the drift-sketch hot path (duty-gated
        async pipeline) costs < 3% p50 on a closed-loop scoring burst
        — same discipline as the profiler's overhead gate.  Retries
        absorb ambient-load spikes on the shared 1-core box."""
        sentinel = _tool("perf_sentinel")
        args = argparse.Namespace(
            model_trees=12, outstanding=32, burst_duration=0.6,
            overhead_reps=3, overhead_duration=0.6)
        for _attempt in range(4):
            ab = sentinel.measure_sketch_overhead(args)
            if ab["overhead_pct"] < 3.0:
                break
        assert ab["overhead_pct"] < 3.0, ab
        assert ab["p50_ms_enabled"] > 0 and ab["p50_ms_disabled"] > 0
