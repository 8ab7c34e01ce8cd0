"""Perf tooling tests (ISSUE 12): the machine-readable trace-report
schema (round-trip pinned), the perf_report attribution math and live
smoke, and the tier-1 perf-sentinel drills — seeded 2x slowdown fires
``perf_regression`` (against both a calibrated baseline and the
committed r12 bench artifact), an unmodified tree stays green."""

import importlib.util
import json
import os
import time

import numpy as np
import pytest

from mmlspark_tpu.core import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"_tool_{name}", os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------- trace_report --format json


class TestTraceReportJSON:
    def _journal(self, tmp_path):
        j = telemetry.EventJournal(capacity=64)
        path = str(tmp_path / "j.jsonl")
        j.configure(path)
        tid = "cafe0123deadbeef"
        j.emit("form", rids=["r1"], trace_ids=[tid], rows=1,
               dur_ms=1.5)
        j.emit("decode", rids=["r1"], trace_ids=[tid], dur_ms=0.2)
        j.emit("score", rids=["r1"], trace_ids=[tid], rows=1,
               dur_ms=3.0)
        j.emit("reply", rids=["r1"], statuses=[200], dur_ms=0.4)
        j.emit("fit_begin", fit="f123")
        j.emit("boost_chunk", fit="f123", it_start=0, it_end=4,
               ms_per_tree=2.0)
        j.emit("fit_end", fit="f123", dur_s=1.0)
        j.configure(None)
        return path, tid

    def test_schema_round_trip(self, tmp_path, capsys):
        """The --format json document is stable, JSON-native, and
        byte-round-trips: the contract perf_report consumes."""
        trace_report = _load_tool("trace_report")
        path, tid = self._journal(tmp_path)
        rc = trace_report.main([path, "--trace-id", tid,
                                "--fit", "latest",
                                "--format", "json"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        # the round-trip: serialize → parse is identity
        assert json.loads(json.dumps(doc)) == doc
        assert doc["schema"] == "mmlspark_tpu.trace_timeline/v1"
        assert set(doc) == {"schema", "events_total", "event_counts",
                            "fits", "request", "fit"}
        assert doc["events_total"] == 7
        assert doc["event_counts"]["form"] == 1
        assert doc["fits"] == ["f123"]
        req = doc["request"]
        assert req["trace_id"] == tid and req["rid"] == "r1"
        assert req["complete"] is True
        assert [e["ev"] for e in req["events"]] == \
            ["form", "decode", "score", "reply"]
        fit = doc["fit"]
        assert fit["fit"] == "f123" and fit["complete"] is True

    def test_json_without_selectors(self, tmp_path, capsys):
        trace_report = _load_tool("trace_report")
        path, _tid = self._journal(tmp_path)
        assert trace_report.main([path, "--format", "json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["request"] is None and doc["fit"] is None
        assert doc["events_total"] == 7

    def test_text_mode_unchanged(self, tmp_path, capsys):
        trace_report = _load_tool("trace_report")
        path, tid = self._journal(tmp_path)
        assert trace_report.main([path, "--trace-id", tid]) == 0
        out = capsys.readouterr().out
        assert "complete=True" in out


# ----------------------------------------------------------- perf_report


class TestPerfReport:
    def test_attribution_math(self):
        """Hand-built phase totals: 9.0s of named phases under a 9.5s
        e2e → 94.7% attributed (the >= 90% acceptance shape); an
        unnamed phase shows in the table but not the fraction."""
        perf_report = _load_tool("perf_report")
        phases = {
            "scoring.e2e": {"total_s": 9.5, "count": 100},
            "scoring.form": {"total_s": 1.0, "count": 100},
            "scoring.decode": {"total_s": 1.0, "count": 100},
            "scoring.score": {"total_s": 6.0, "count": 100},
            "scoring.reply": {"total_s": 1.0, "count": 100},
            "mystery.phase": {"total_s": 0.4, "count": 5},
        }
        att = perf_report.attribution(phases)
        assert att["e2e_s"] == 9.5
        assert att["attributed_fraction"] == pytest.approx(
            9.0 / 9.5, abs=1e-4)
        assert att["attributed_fraction"] >= 0.9
        rows = {r["phase"]: r for r in att["top_phases"]}
        assert rows["scoring.score"]["share_of_e2e"] == \
            pytest.approx(6.0 / 9.5, abs=1e-3)
        assert rows["mystery.phase"]["attributed"] is False
        assert "scoring.e2e" not in rows

    def test_compile_ledger_separates_hit_from_miss(self):
        perf_report = _load_tool("perf_report")
        led = perf_report.compile_ledger({
            "dispatch": {"scoring": {"hits": 98, "misses": 2}},
            "jax_events": {"backend_compile":
                           {"count": 2, "total_s": 1.25}},
        })
        s = led["sites"]["scoring"]
        assert s["hits"] == 98 and s["misses"] == 2
        assert s["hit_ratio"] == pytest.approx(0.98)
        assert led["backend_compiles"] == 2
        assert led["compile_seconds_total"] >= 1.25

    def test_live_burst_end_to_end(self, tmp_path):
        """Drive a real engine burst, write a bench-artifact-shaped
        JSON, and run the CLI: attribution must cover >= 90% of e2e
        (the acceptance bar) and the ledger must show the warm cache."""
        import queue

        from mmlspark_tpu.core.profiler import get_profiler
        from mmlspark_tpu.gbdt import LightGBMRegressor
        from mmlspark_tpu.io.scoring import ColumnPlan, ScoringEngine
        perf_report = _load_tool("perf_report")
        prof = get_profiler()
        was = prof.enabled
        prof.configure(enabled=True)

        class Srv:
            def __init__(self):
                self.request_queue = queue.Queue()
                self.done = []

            def reply(self, rid, val, status=200):
                self.done.append(rid)
                return True

        # enough trees/features that each batch does real scoring work
        # — on a µs-scale toy model the per-batch glue (locks, list
        # builds) dominates and the fraction sits at the boundary,
        # which is measurement noise, not an attribution gap
        rng = np.random.default_rng(0)
        X = rng.normal(size=(400, 16)).astype(np.float32)
        y = (X[:, 0]).astype(np.float64)
        b = LightGBMRegressor(numIterations=48, numLeaves=15,
                              parallelism="serial", verbosity=0).fit(
            {"features": X, "label": y}).getModel()
        srv = Srv()
        n = 512
        for i in range(n):
            srv.request_queue.put(
                (str(i), {"features": X[i % len(X)].tolist()}))
        eng = ScoringEngine(srv, predictor=b.predictor(backend="auto"),
                            plan=ColumnPlan("features", X.shape[1]),
                            max_rows=64, latency_budget_ms=2.0,
                            num_scorers=1, num_repliers=0).start()
        deadline = time.monotonic() + 30
        while len(srv.done) < n and time.monotonic() < deadline:
            time.sleep(0.01)
        eng.stop()
        prof.configure(enabled=was)
        assert len(srv.done) == n
        artifact = {"telemetry": {
            "metrics_exposition":
                telemetry.get_registry().render_prometheus(),
            "journal_excerpt": [],
            "profile": prof.snapshot()}}
        apath = tmp_path / "bench.json"
        apath.write_text(json.dumps(artifact))
        report = perf_report.build_report(artifact)
        att = report["attribution"]
        assert att["e2e_s"] > 0
        assert att["attributed_fraction"] is not None
        assert att["attributed_fraction"] >= 0.9, att
        assert "scoring" in report["compile_ledger"]["sites"]
        # CLI smoke on the same artifact
        assert perf_report.main([str(apath), "--format", "json",
                                 "--flamegraph",
                                 str(tmp_path / "fg.txt")]) == 0


# ---------------------------------------------------------- perf_sentinel


SENTINEL_FAST = ["--stages", "codec_json,codec_binary", "--k", "3",
                 "--codec-reps", "800", "--skip-overhead"]


class TestPerfSentinel:
    def _regressions_in_journal(self):
        return [e for e in telemetry.get_journal().events()
                if e.get("ev") == "perf_regression"]

    def test_calibrate_then_clean_green(self, tmp_path, monkeypatch):
        """Unmodified tree: calibrate a baseline, re-run against it —
        exit 0, no perf_regression journaled.  On an injected clock
        (every reading a tick later, a sleep as long as it was asked):
        two runs of the same code then take the same time whatever the
        box is doing, where the wall clock read a codec stage 2.3x its
        own baseline under six test workers.  The same clock still sees
        a seeded 2x slowdown, so it has not blinded the gate."""
        sentinel = _load_tool("perf_sentinel")

        class Clock:
            now = 0.0

            def perf_counter(self):
                self.now += 1e-2
                return self.now

            def sleep(self, seconds):
                self.now += seconds

            def __getattr__(self, name):
                return getattr(time, name)
        monkeypatch.setattr(sentinel, "time", Clock())
        base = str(tmp_path / "base.json")
        assert sentinel.main(["--calibrate", "--out", base,
                              *SENTINEL_FAST]) == 0
        doc = json.load(open(base))
        assert doc["schema"] == "mmlspark_tpu.perf_sentinel/v1"
        assert set(doc["stages"]) == {"codec_json", "codec_binary"}
        before = len(self._regressions_in_journal())
        rc = sentinel.main(["--baseline", base, *SENTINEL_FAST])
        assert rc == 0
        assert len(self._regressions_in_journal()) == before
        monkeypatch.setenv(sentinel.SLOWDOWN_ENV, "codec_json=2.0")
        # a stage reads the clock three times, so the seeded region is
        # 5 ticks against 3: --rel as test_seeded_2x_slowdown_fires
        assert sentinel.main(["--baseline", base, "--rel", "1.4",
                              *SENTINEL_FAST]) != 0
        fired = self._regressions_in_journal()[before:]
        assert [e["stage"] for e in fired] == ["codec_json"]

    def test_seeded_2x_slowdown_fires(self, tmp_path, monkeypatch):
        """ISSUE 12 acceptance: a seeded 2x stage slowdown against the
        calibrated baseline exits nonzero and journals
        ``perf_regression``.  The fire threshold is pinned at 1.4 here
        (not the 1.8 default): on a loaded single-core box calibration
        noise can shave a seeded 2.0x down to ~1.7x measured, and this
        test is about the fire *mechanism*, not the default margin.
        The same noise can spike the un-seeded stage past 1.4x, so we
        assert the seeded stage is AMONG the regressions rather than
        the exact list (no-false-fire at the default threshold is
        covered by ``test_calibrate_then_clean_green``)."""
        sentinel = _load_tool("perf_sentinel")
        base = str(tmp_path / "base.json")
        assert sentinel.main(["--calibrate", "--out", base,
                              *SENTINEL_FAST]) == 0
        before = len(self._regressions_in_journal())
        monkeypatch.setenv(sentinel.SLOWDOWN_ENV, "codec_json=2.0")
        out = str(tmp_path / "run.json")
        rc = sentinel.main(["--baseline", base, "--out", out,
                            "--rel", "1.4", *SENTINEL_FAST])
        assert rc != 0
        events = self._regressions_in_journal()[before:]
        assert any(e["stage"] == "codec_json" for e in events)
        doc = json.load(open(out))
        assert doc["healthy"] is False
        fired = {r["stage"]: r for r in doc["regressions"]}
        assert "codec_json" in fired
        assert fired["codec_json"]["ratio"] >= 1.4
        # the worst-ratio gauge feeds the perf_latency_budget SLO
        snap = telemetry.get_registry().snapshot()
        assert snap["perf"]["gauges"]["worst_regression_ratio"] >= 1.4

    def test_seeded_2x_vs_committed_bench_artifact(self, tmp_path,
                                                   monkeypatch):
        """The acceptance drill verbatim: the committed bench
        artifact's ``codec_micro`` block is the baseline (r12 — the
        artifact benched on THIS container generation; r11 was benched
        on a ~1.5x slower box, so box-relative baselines MUST track
        the hardware the sentinel runs on), a seeded 2x slowdown on
        the codecs fires (nonzero exit + journal event)."""
        sentinel = _load_tool("perf_sentinel")
        r12 = os.path.join(REPO, "artifacts",
                           "bench_serving_r12.json")
        before = len(self._regressions_in_journal())
        monkeypatch.setenv(sentinel.SLOWDOWN_ENV,
                           "codec_json=2.0,codec_binary=2.0")
        rc = sentinel.main(["--baseline", r12, *SENTINEL_FAST])
        assert rc != 0
        events = self._regressions_in_journal()[before:]
        assert {e["stage"] for e in events} & {"codec_json",
                                               "codec_binary"}

    def test_unknown_stage_rejected(self):
        sentinel = _load_tool("perf_sentinel")
        with pytest.raises(SystemExit):
            sentinel.main(["--stages", "nope", "--skip-overhead"])

    def test_baseline_mapping_from_bench_artifact(self):
        sentinel = _load_tool("perf_sentinel")
        r11 = os.path.join(REPO, "artifacts",
                           "bench_serving_r11.json")
        baselines, kind = sentinel.load_baselines(r11)
        assert kind == "bench_serving"
        assert baselines["codec_json"] == pytest.approx(78.614)
        assert baselines["codec_binary"] == pytest.approx(9.637)

    def test_noise_floor_blocks_tiny_regressions(self):
        """The absolute floor: a 2x ratio on a sub-floor delta is NOT
        a regression (scheduler noise on µs-scale stages)."""
        sentinel = _load_tool("perf_sentinel")
        measured = {"codec_binary": {"median": 2.0, "runs": [2.0],
                                     "unit": "us"}}
        regs, checks = sentinel.compare(
            measured, {"codec_binary": 1.0}, rel=1.8)
        assert regs == []                 # delta 1µs < 3µs floor
        assert checks["codec_binary"]["regressed"] is False
        measured = {"codec_binary": {"median": 30.0, "runs": [30.0],
                                     "unit": "us"}}
        regs, _ = sentinel.compare(
            measured, {"codec_binary": 10.0}, rel=1.8)
        assert [r["stage"] for r in regs] == ["codec_binary"]
