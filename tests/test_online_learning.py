"""Self-healing online learning (ISSUE 18): streaming ingest with
crash-safe spill/replay, drift-triggered incremental refresh with
kill-anywhere recovery, registry GC, and the ramped drift injector."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from mmlspark_tpu.core.drift import DriftConfig, DriftMonitor
from mmlspark_tpu.core.slo import SLOMonitor, default_objectives
from mmlspark_tpu.core.telemetry import MetricsRegistry
from mmlspark_tpu.gbdt import fit_bin_mapper
from mmlspark_tpu.gbdt.binning import BinMapper
from mmlspark_tpu.gbdt.engine import TrainParams, train, \
    train_incremental
from mmlspark_tpu.gbdt.objectives import RegressionL2
from mmlspark_tpu.io.chaos import ChaosDrift, ChaosPlan
from mmlspark_tpu.io.ingest import IngestBuffer, IngestError
from mmlspark_tpu.io.refresh import RefreshConfig, RefreshController
from mmlspark_tpu.io.registry import ModelRegistry
from mmlspark_tpu.io.rollout import RolloutConfig, RolloutController


def _data(seed=0, n=800, f=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1]).astype(np.float64)
    return X, y


_PARAMS = dict(num_leaves=15, min_data_in_leaf=5,
               parallelism="serial", verbosity=0)


def _base_model(X, y, mapper, trees=8):
    return train(mapper.transform_packed(X), y, None, mapper,
                 RegressionL2(),
                 TrainParams(num_iterations=trees, **_PARAMS))


# ------------------------------------------------------------------ ingest


class TestIngestBuffer:
    def test_append_bins_immediately(self, tmp_path):
        X, y = _data()
        mapper = fit_bin_mapper(X, max_bin=63)
        ing = IngestBuffer(str(tmp_path / "ing"), mapper,
                           window_rows=500, reservoir_rows=100,
                           segment_rows=128, register=False)
        ing.append(X[:300], y[:300])
        bv, yv = ing.training_view()
        assert bv.dtype == np.uint8
        np.testing.assert_array_equal(
            bv[-300:], mapper.transform_packed(X[:300]))
        np.testing.assert_array_equal(yv[-300:], y[:300])
        assert ing.rows_seen == 300

    def test_window_and_reservoir_bound_memory(self, tmp_path):
        X, y = _data(n=3000)
        ing = IngestBuffer(str(tmp_path / "ing"),
                           fit_bin_mapper(X, max_bin=63),
                           window_rows=400, reservoir_rows=150,
                           segment_rows=100, register=False)
        for i in range(0, 3000, 250):
            ing.append(X[i:i + 250], y[i:i + 250])
        assert ing.rows_seen == 3000
        assert ing.rows_retained <= 400 + 150
        bv, yv = ing.training_view()
        assert len(bv) == ing.rows_retained
        # the window tail is exact recency
        np.testing.assert_array_equal(yv[-400:], y[-400:])

    def test_replay_after_kill_is_exact(self, tmp_path):
        """Reopening the spill dir reproduces window, reservoir and
        counters exactly as of the last durable segment; unspilled
        tail rows are the only loss (the documented contract)."""
        X, y = _data(n=2000)
        mapper = fit_bin_mapper(X, max_bin=63)
        d = str(tmp_path / "ing")
        ing = IngestBuffer(d, mapper, window_rows=600,
                           reservoir_rows=200, segment_rows=128,
                           seed=3, register=False)
        for i in range(0, 2000, 77):
            ing.append(X[i:i + 77], y[i:i + 77])
        durable = ing.rows_durable
        assert durable < 2000      # some tail is in flight
        # no clean shutdown happened: reopen == replay
        re1 = IngestBuffer(d, register=False)
        assert re1.rows_durable == durable
        # reference: a fresh buffer fed exactly the durable prefix
        ref = IngestBuffer(str(tmp_path / "ref"), mapper,
                           window_rows=600, reservoir_rows=200,
                           segment_rows=128, seed=3, register=False)
        ref.append(X[:durable], y[:durable])
        ref.flush()
        b1, y1 = re1.training_view()
        b2, y2 = ref.training_view()
        np.testing.assert_array_equal(b1, b2)
        np.testing.assert_array_equal(y1, y2)
        assert re1.stats.counter("segments_replayed") > 0

    def test_batch_boundary_invariance(self, tmp_path):
        """Retention decisions key on stream position, not batch
        shape: one big append == many small ones."""
        X, y = _data(n=1500)
        mapper = fit_bin_mapper(X, max_bin=63)
        kw = dict(window_rows=300, reservoir_rows=120,
                  segment_rows=100, seed=9, register=False)
        a = IngestBuffer(str(tmp_path / "a"), mapper, **kw)
        a.append(X, y)
        a.flush()
        b = IngestBuffer(str(tmp_path / "b"), mapper, **kw)
        for i in range(0, 1500, 37):
            b.append(X[i:i + 37], y[i:i + 37])
        b.flush()
        ba, ya = a.training_view()
        bb, yb = b.training_view()
        np.testing.assert_array_equal(ba, bb)
        np.testing.assert_array_equal(ya, yb)

    def test_compaction_bounds_disk_and_preserves_state(self, tmp_path):
        X, y = _data(n=2000)
        d = str(tmp_path / "ing")
        ing = IngestBuffer(d, fit_bin_mapper(X, max_bin=63),
                           window_rows=300, reservoir_rows=100,
                           segment_rows=64, max_segments=4,
                           register=False)
        for i in range(0, 2000, 100):
            ing.append(X[i:i + 100], y[i:i + 100])
        ing.flush()
        segs = [f for f in os.listdir(d) if f.startswith("seg_")]
        assert len(segs) <= 4 + 1
        before = ing.training_view()
        ing.compact()
        after = IngestBuffer(d, register=False).training_view()
        np.testing.assert_array_equal(before[0], after[0])
        np.testing.assert_array_equal(before[1], after[1])

    def test_mapper_mismatch_refused(self, tmp_path):
        X, y = _data()
        d = str(tmp_path / "ing")
        IngestBuffer(d, fit_bin_mapper(X, max_bin=63),
                     register=False).append(X[:100], y[:100])
        other = fit_bin_mapper(X * 2.0, max_bin=63)
        with pytest.raises(IngestError, match="different ladder"):
            IngestBuffer(d, other, register=False)

    def test_gapped_replay_refused(self, tmp_path):
        X, y = _data(n=1200)
        d = str(tmp_path / "ing")
        ing = IngestBuffer(d, fit_bin_mapper(X, max_bin=63),
                           segment_rows=100, register=False)
        ing.append(X, y)
        victim = sorted(f for f in os.listdir(d)
                        if f.startswith("seg_"))[3]
        os.unlink(os.path.join(d, victim))
        with pytest.raises(IngestError, match="missing"):
            IngestBuffer(d, register=False)

    def test_mapper_json_round_trip(self):
        X, _ = _data()
        X[::7, 2] = np.nan
        mapper = fit_bin_mapper(X, max_bin=63)
        rt = BinMapper.from_json(mapper.to_json())
        assert rt.to_json() == mapper.to_json()
        for a, b in zip(mapper.upper_bounds, rt.upper_bounds):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(
            rt.transform_packed(X), mapper.transform_packed(X))

    def test_exposition_families(self, tmp_path):
        X, y = _data()
        ing = IngestBuffer(str(tmp_path / "ing"),
                           fit_bin_mapper(X, max_bin=63),
                           register=False)
        ing.append(X[:50], y[:50])
        text = ing.render_prometheus()
        for fam in ("ingest_rows_total", "ingest_batches_total",
                    "ingest_segments_total", "ingest_retained_rows",
                    "ingest_rows_dropped_total",
                    "ingest_spilled_bytes_total"):
            assert f"# TYPE mmlspark_tpu_{fam} " in text


# ------------------------------------------------------------- chaos ramp


class TestChaosDriftRamp:
    def test_ramp_reaches_full_shift(self):
        drift = ChaosDrift(ChaosPlan(seed=5), feature=0, shift=4.0,
                           after_rows=10, ramp_rows=100)
        X = np.zeros((200, 3), np.float32)
        out = drift(X)
        np.testing.assert_array_equal(out[:10, 0], 0.0)
        # mid-ramp: row 10+j carries (j+1)/100 of the shift
        assert out[10, 0] == pytest.approx(4.0 * 1 / 100)
        assert out[59, 0] == pytest.approx(4.0 * 50 / 100)
        np.testing.assert_allclose(out[110:, 0], 4.0)
        assert (X == 0).all()      # input immutable

    def test_ramp_batch_boundary_invariant(self):
        rng = np.random.default_rng(2)
        X = rng.normal(size=(300, 4)).astype(np.float32)
        kw = dict(feature=1, shift=2.0, scale=1.5, after_rows=40,
                  ramp_rows=120)
        one = ChaosDrift(ChaosPlan(seed=7), **kw)(X)
        many = ChaosDrift(ChaosPlan(seed=7), **kw)
        parts = [many(X[i:i + 23]) for i in range(0, 300, 23)]
        np.testing.assert_array_equal(one, np.concatenate(parts))

    def test_step_mode_unchanged(self):
        """ramp_rows=0 keeps the PR-15 step semantics exactly."""
        X = np.ones((50, 2), np.float32)
        out = ChaosDrift(ChaosPlan(seed=1), feature=0, shift=1.0,
                         after_rows=20)(X)
        np.testing.assert_array_equal(out[:20, 0], 1.0)
        np.testing.assert_array_equal(out[20:, 0], 2.0)


# ---------------------------------------------------------- registry GC


class TestRegistryPrune:
    def _registry(self, tmp_path, versions=6):
        X, y = _data(n=300)
        mapper = fit_bin_mapper(X, max_bin=31)
        m = _base_model(X, y, mapper, trees=2)
        reg = ModelRegistry(str(tmp_path / "reg"))
        for _ in range(versions):
            reg.publish(m, activate=True)
        return reg

    def test_prune_deletes_old_retired(self, tmp_path):
        reg = self._registry(tmp_path, versions=6)
        # v1..v5 retired, v6 active
        pruned = reg.prune(keep_last=2)
        assert pruned == [1, 2, 3]
        for v in pruned:
            assert str(v) not in {str(k) for k in reg.entries()}
            assert not os.path.exists(reg.model_path(v))
            assert not os.path.exists(reg.profile_path(v))
        assert reg.active_version() == 6
        assert sorted(reg.entries()) == [4, 5, 6]
        # manifest-as-commit-point: a reopened registry agrees
        assert sorted(ModelRegistry(reg.root).entries()) == [4, 5, 6]
        assert reg.prune(keep_last=2) == []      # idempotent

    def test_quarantined_never_pruned(self, tmp_path):
        reg = self._registry(tmp_path, versions=5)
        reg.quarantine(2)
        pruned = reg.prune(keep_last=0)
        assert 2 not in pruned
        assert reg.entry(2)["promoted_state"] == "quarantined"
        assert os.path.exists(reg.model_path(2))

    def test_active_and_candidate_untouched(self, tmp_path):
        reg = self._registry(tmp_path, versions=4)
        X, y = _data(n=300)
        m = _base_model(X, y, fit_bin_mapper(X, max_bin=31), trees=2)
        cand = reg.publish(m)                    # candidate
        reg.prune(keep_last=0)
        assert reg.active_version() == 4
        assert cand in reg.entries()
        assert reg.entry(cand)["promoted_state"] == "candidate"

    def test_rolled_back_pruned_too(self, tmp_path):
        reg = self._registry(tmp_path, versions=3)
        reg.rollback()                            # v3 -> rolled_back
        pruned = reg.prune(keep_last=0)
        assert 3 in pruned and 1 in pruned
        assert reg.active_version() == 2


# ------------------------------------------- continued training x ckpt


_INCR_FIT_SCRIPT = r'''
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax; jax.config.update("jax_platforms", "cpu")
import numpy as np
from mmlspark_tpu.gbdt import fit_bin_mapper
from mmlspark_tpu.gbdt.engine import TrainParams, train, \
    train_incremental
from mmlspark_tpu.gbdt.objectives import RegressionL2
rng = np.random.default_rng(4)
X = rng.normal(size=(1500, 8)).astype(np.float32)
y = (X[:, 0] - 0.7 * X[:, 2]).astype(np.float64)
mapper = fit_bin_mapper(X, max_bin=63)
bins = mapper.transform_packed(X)
base_path = sys.argv[4]
kw = dict(num_leaves=15, min_data_in_leaf=5, parallelism="serial",
          verbosity=0)
if not os.path.exists(base_path):
    base = train(bins, y, None, mapper, RegressionL2(),
                 TrainParams(num_iterations=6, **kw))
    open(base_path, "w").write(base.save_native_model_string())
from mmlspark_tpu.gbdt.booster import Booster
base = Booster.load_native_model(base_path)
kill_at = int(sys.argv[2])
cbs = None
if kill_at >= 0:
    def killer(it, trees):
        if it >= kill_at:
            os._exit(37)   # simulated SIGKILL mid-boost: no cleanup
    cbs = [killer]
params = TrainParams(num_iterations=24, checkpoint_chunk=8,
                     checkpoint_dir=(sys.argv[1] if sys.argv[1] != "-"
                                     else ""), **kw)
merged = train_incremental(bins, y, mapper, init_booster=base,
                           objective=RegressionL2(), params=params,
                           callbacks=cbs)
open(sys.argv[3], "w").write(merged.save_native_model_string())
print("DONE", len(merged.trees))
'''


class TestIncrementalMidFitResume:
    """ISSUE 18 satellite: PR-4 resume tests only covered from-scratch
    fits; the checkpoint fingerprint also digests ``init_scores``, so
    a killed *incremental* fit must resume onto the SAME continued
    trajectory and the merged forest (init trees + new trees) must be
    bit-identical to an unkilled run."""

    def _run(self, tmp_path, ckpt, kill_at, out, check=True):
        sf = str(tmp_path / "incr_fit.py")
        if not os.path.exists(sf):
            with open(sf, "w") as fh:
                fh.write(_INCR_FIT_SCRIPT)
        repo = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["PYTHONPATH"] = repo + os.pathsep + \
            env.get("PYTHONPATH", "")
        r = subprocess.run(
            [sys.executable, sf, ckpt, str(kill_at), out,
             str(tmp_path / "base.txt")],
            env=env, capture_output=True, text=True, timeout=300)
        if check:
            assert r.returncode == 0, r.stderr[-3000:]
        return r

    def test_killed_incremental_fit_resumes_bit_identical(
            self, tmp_path):
        ck = str(tmp_path / "ck")
        r = self._run(tmp_path, ck, 10, str(tmp_path / "dead.txt"),
                      check=False)
        assert r.returncode == 37, r.stderr[-3000:]
        assert os.path.exists(
            os.path.join(ck, "boost_checkpoint.npz"))
        self._run(tmp_path, ck, -1, str(tmp_path / "resumed.txt"))
        self._run(tmp_path, "-", -1, str(tmp_path / "clean.txt"))
        resumed = open(tmp_path / "resumed.txt").read()
        clean = open(tmp_path / "clean.txt").read()
        assert resumed == clean
        assert "[num_iterations: 30]" in resumed  # 6 init + 24 new


# --------------------------------------------------------- refresh loop


def _drifted_feed(X, y, shift=3.0):
    Xd = X.copy()
    Xd[:, 0] += shift
    yd = (Xd[:, 0] + 0.5 * Xd[:, 1]).astype(np.float64)
    return Xd, yd


def _burning_slo(booster, Xd):
    """A private SLOMonitor whose feature/prediction-drift objectives
    read a drift monitor that has seen shifted traffic."""
    dmon = DriftMonitor(booster.reference_profile,
                        DriftConfig(duty=1.0, eval_interval_s=0.02,
                                    min_rows=100))
    dmon.observe(Xd, np.asarray(booster.predict_margin(Xd)))
    assert dmon.flush()
    dmon.evaluate(force=True)
    reg = MetricsRegistry()
    reg.register("drift", dmon)
    objs = [o for o in default_objectives()
            if o.name in ("feature_drift", "prediction_drift")]
    return SLOMonitor(objs, registry=reg, fast_window_s=3.0,
                      slow_window_s=6.0), dmon


class TestRefreshController:
    def _loop(self, tmp_path, **cfg_kw):
        X, y = _data(n=600, f=4)
        mapper = fit_bin_mapper(X, max_bin=63)
        base = _base_model(X, y, mapper, trees=6)
        assert base.reference_profile is not None
        registry = ModelRegistry(str(tmp_path / "reg"))
        registry.publish(base, activate=True)
        ingest = IngestBuffer(str(tmp_path / "ing"), mapper,
                              window_rows=800, reservoir_rows=200,
                              segment_rows=128, register=False)
        Xd, yd = _drifted_feed(X, y)
        for i in range(0, 600, 100):
            ingest.append(Xd[i:i + 100], yd[i:i + 100])
        slo, dmon = _burning_slo(base, Xd)
        cfg = RefreshConfig(hysteresis_evals=2, cooldown_s=30.0,
                            min_fit_rows=200, num_iterations=4,
                            **cfg_kw)
        return X, base, registry, ingest, slo, cfg

    def test_drift_triggers_fit_canary_promote(self, tmp_path):
        """The tier-1 smoke: drifting feed → hysteresis-debounced
        trigger → tiny incremental fit from ingest → candidate →
        canary → promote, all in-process."""
        X, base, registry, ingest, slo, cfg = self._loop(tmp_path)
        rollout = RolloutController(
            registry, config=RolloutConfig(canary_fraction=0.5,
                                           soak_s=0.0,
                                           min_canary_rows=10))
        try:
            refresh = RefreshController(
                str(tmp_path / "ref"), registry=registry,
                rollout=rollout, ingest=ingest, monitor=slo,
                config=cfg, register=False)
            seen = [refresh.poll(now=float(i)) for i in range(6)]
            assert seen[:2] == ["idle", "idle"]      # hysteresis
            assert "triggered" in seen and "canary" in seen
            v = refresh.candidate_version
            assert registry.entry(v)["promoted_state"] == "candidate"
            rollout.promote()
            assert refresh.poll(now=10.0) == "promoted"
            assert registry.active_version() == v
            merged = registry.load()
            assert len(merged.trees) == 6 + 4
            # episode cooldown absorbs the still-burning monitor
            assert refresh.poll(now=11.0) == "cooldown"
            text = refresh.render_prometheus()
            for fam in ("refresh_state", "refresh_episode",
                        "refresh_transitions_total",
                        "refresh_breach_streak",
                        "refresh_cooldown_seconds"):
                assert f"# TYPE mmlspark_tpu_{fam} " in text
        finally:
            rollout.stop()

    def test_fit_failure_backoff_then_gave_up(self, tmp_path):
        """Bounded-backoff retry wall: a deterministically failing fit
        retries with doubling backoff then lands in the GAVE_UP
        terminal (journaled), and reset() re-arms under cooldown."""
        X, base, registry, ingest, slo, cfg = self._loop(
            tmp_path, max_retries=2, backoff_s=2.0)
        refresh = RefreshController(
            str(tmp_path / "ref"), registry=registry, rollout=None,
            ingest=ingest, monitor=slo, config=cfg, register=False)

        def bomb(it, trees):
            raise RuntimeError("injected fit failure")

        refresh.fit_callbacks = [bomb]
        assert refresh.poll(now=0.0) == "idle"       # streak builds
        assert refresh.poll(now=1.0) == "idle"
        assert refresh.poll(now=2.0) == "triggered"
        assert refresh.poll(now=3.0) == "fitting"
        assert refresh.poll(now=4.0) == "backoff"    # attempt 1 failed
        assert refresh.poll(now=5.0) == "backoff"    # still waiting
        assert refresh.poll(now=6.0) == "backoff"    # attempt 2 failed
        assert refresh.poll(now=30.0) == "gave_up"   # attempt 3 > max
        assert refresh.state == "gave_up"
        assert refresh.poll(now=31.0) == "gave_up"   # terminal
        refresh.reset(now=40.0)
        assert refresh.state == "idle"
        assert refresh.poll(now=41.0) == "cooldown"

    def test_starved_trigger_waits_for_rows(self, tmp_path):
        X, y = _data(n=600, f=4)
        mapper = fit_bin_mapper(X, max_bin=63)
        base = _base_model(X, y, mapper, trees=4)
        registry = ModelRegistry(str(tmp_path / "reg"))
        registry.publish(base, activate=True)
        ingest = IngestBuffer(str(tmp_path / "ing"), mapper,
                              register=False)
        Xd, yd = _drifted_feed(X, y)
        ingest.append(Xd[:50], yd[:50])              # < min_fit_rows
        slo, _ = _burning_slo(base, Xd)
        refresh = RefreshController(
            str(tmp_path / "ref"), registry=registry, rollout=None,
            ingest=ingest, monitor=slo,
            config=RefreshConfig(hysteresis_evals=1, min_fit_rows=200),
            register=False)
        assert refresh.poll(now=0.0) == "idle"   # SLO window warming
        assert refresh.poll(now=1.0) == "triggered"
        assert refresh.poll(now=2.0) == "starved"
        ingest.append(Xd[50:400], yd[50:400])
        assert refresh.poll(now=3.0) == "fitting"


_REFRESH_KILL_SCRIPT = r'''
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax; jax.config.update("jax_platforms", "cpu")
import numpy as np
from mmlspark_tpu.core.drift import DriftConfig, DriftMonitor
from mmlspark_tpu.core.slo import SLOMonitor, default_objectives
from mmlspark_tpu.core.telemetry import MetricsRegistry
from mmlspark_tpu.gbdt import fit_bin_mapper
from mmlspark_tpu.gbdt.engine import TrainParams, train
from mmlspark_tpu.gbdt.objectives import RegressionL2
from mmlspark_tpu.io.ingest import IngestBuffer
from mmlspark_tpu.io.refresh import RefreshConfig, RefreshController
from mmlspark_tpu.io.registry import ModelRegistry

root, phase = sys.argv[1], sys.argv[2]
rng = np.random.default_rng(0)
X = rng.normal(size=(600, 4)).astype(np.float32)
y = (X[:, 0] + 0.5 * X[:, 1]).astype(np.float64)
kw = dict(num_leaves=15, min_data_in_leaf=5, parallelism="serial",
          verbosity=0)
reg_dir = os.path.join(root, "reg")
if not os.path.exists(reg_dir):
    mapper = fit_bin_mapper(X, max_bin=63)
    base = train(mapper.transform_packed(X), y, None, mapper,
                 RegressionL2(), TrainParams(num_iterations=6, **kw))
    ModelRegistry(reg_dir).publish(base, activate=True)
    IngestBuffer(os.path.join(root, "ing"), mapper,
                 window_rows=800, reservoir_rows=200,
                 segment_rows=128, register=False)
registry = ModelRegistry(reg_dir)
ingest = IngestBuffer(os.path.join(root, "ing"), register=False)
base = registry.load(1)
Xd = X.copy(); Xd[:, 0] += 3.0
yd = (Xd[:, 0] + 0.5 * Xd[:, 1]).astype(np.float64)
if phase == "kill":
    for i in range(0, 600, 100):
        ingest.append(Xd[i:i + 100], yd[i:i + 100])
dmon = DriftMonitor(base.reference_profile,
                    DriftConfig(duty=1.0, eval_interval_s=0.02,
                                min_rows=100))
dmon.observe(Xd, np.asarray(base.predict_margin(Xd)))
dmon.flush(); dmon.evaluate(force=True)
mreg = MetricsRegistry(); mreg.register("drift", dmon)
objs = [o for o in default_objectives()
        if o.name in ("feature_drift", "prediction_drift")]
slo = SLOMonitor(objs, registry=mreg, fast_window_s=3.0,
                 slow_window_s=6.0)
refresh = RefreshController(
    os.path.join(root, "ref"), registry=registry, rollout=None,
    ingest=ingest, monitor=slo,
    config=RefreshConfig(hysteresis_evals=1, min_fit_rows=200,
                         num_iterations=12, checkpoint_chunk=4),
    register=False)
if phase == "kill":
    def killer(it, trees):
        if it >= 6:
            os._exit(37)   # SIGKILL mid-incremental-fit, mid-episode
    refresh.fit_callbacks = [killer]
    for i in range(8):             # idle -> triggered -> fitting -> dead
        refresh.poll(now=float(i))
    print("UNREACHABLE"); sys.exit(3)
# phase == "recover": reopen the SAME dirs, resume the episode
assert refresh.state == "fitting", refresh.state
assert refresh.stats.counter("recoveries") == 1
out = refresh.poll(now=10.0)       # resumes fit from the checkpoint
assert out == "candidate", out
v = refresh.candidate_version
registry.activate(v)               # the gate's promote, minus canary
assert refresh.poll(now=11.0) == "promoted"
from mmlspark_tpu.io.registry import ModelRegistry as MR
assert len(registry.load(v).trees) == 6 + 12
print("RECOVERED", v)
'''


class TestRefreshKillRecovery:
    """SIGKILL the refresh subprocess mid-incremental-fit; a fresh
    process over the same directories must resume the committed
    episode (recovery journal + checkpointed fit) and land the
    refreshed model."""

    def _run(self, tmp_path, phase, check=True):
        sf = str(tmp_path / "refresh_kill.py")
        if not os.path.exists(sf):
            with open(sf, "w") as fh:
                fh.write(_REFRESH_KILL_SCRIPT)
        repo = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["PYTHONPATH"] = repo + os.pathsep + \
            env.get("PYTHONPATH", "")
        r = subprocess.run(
            [sys.executable, sf, str(tmp_path / "state"), phase],
            env=env, capture_output=True, text=True, timeout=300)
        if check:
            assert r.returncode == 0, \
                r.stdout[-2000:] + r.stderr[-3000:]
        return r

    def test_sigkill_mid_fit_recovers_and_promotes(self, tmp_path):
        r = self._run(tmp_path, "kill", check=False)
        assert r.returncode == 37, r.stdout[-2000:] + r.stderr[-3000:]
        state = json.loads(open(
            tmp_path / "state" / "ref" / "refresh_state.json").read())
        assert state["state"] == "fitting"
        ck = tmp_path / "state" / "ref" / "ckpt_0001"
        assert os.path.exists(str(ck / "boost_checkpoint.npz"))
        r = self._run(tmp_path, "recover")
        assert "RECOVERED" in r.stdout


# ------------------------------------------------ scoring-path tap


class TestIngestTap:
    def test_tap_sees_scored_rows(self, tmp_path):
        import queue as _q

        class _Srv:
            def __init__(self):
                self.request_queue = _q.Queue()
                self.replies = {}

            def reply(self, rid, val, status=200):
                self.replies[rid] = (val, status)
                return True

        from mmlspark_tpu.io.scoring import ColumnPlan, ScoringEngine
        X, y = _data(n=64, f=4)
        mapper = fit_bin_mapper(X, max_bin=63)
        base = _base_model(X, y, mapper, trees=2)
        ing = IngestBuffer(str(tmp_path / "ing"), mapper,
                           register=False)
        srv = _Srv()
        eng = ScoringEngine(
            srv, predictor=base.predictor(backend="auto"),
            plan=ColumnPlan("features", 4), max_rows=16,
            num_scorers=1, num_repliers=0,
            ingest_tap=lambda rows, m: ing.append(rows, m)).start()
        try:
            for i in range(32):
                srv.request_queue.put(
                    (str(i), {"features": X[i].tolist()}))
            import time as _t
            t0 = _t.time()
            while len(srv.replies) < 32 and _t.time() - t0 < 10:
                _t.sleep(0.01)
        finally:
            eng.stop()
        assert len(srv.replies) == 32
        assert ing.rows_seen == 32


# ------------------------------------------------- tap overhead (tier-1)


class TestIngestTapOverhead:
    #: the tap may cost this many times the CPU of scoring the same
    #: batch.  Read 1.6-2.0 on this box, alone and beside seven busy
    #: processes (43 us to bin and append 32 rows, 21 us to score them):
    #: a tap made 2x dearer reads 3.2 or more
    GATE = 2.75

    def test_tap_append_cost_stays_within_its_share_of_scoring(
            self, tmp_path, monkeypatch):
        """ISSUE 18 satellite, steadied (ISSUE 29): what the streaming-
        ingest tap (bin + append + spill) adds to a scoring batch, on
        the scoring thread's own CPU clock and relative to scoring that
        batch, least of five interleaved rounds each.  The p50 delta of
        a closed-loop burst that this replaces read 13-38% alone on an
        8-core box (a 43 us append on a 300 us request) and passed its
        3% limit only when one lucky round arrived; load never enters a
        thread's CPU clock.  The closed-loop A/B itself still runs once,
        for its plumbing, with no limit on its wall-clock number."""
        import argparse
        import importlib.util
        import time
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "_tool_perf_sentinel",
            os.path.join(repo, "tools", "perf_sentinel.py"))
        sentinel = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sentinel)
        args = argparse.Namespace(
            model_trees=12, outstanding=32, burst_duration=0.3,
            overhead_reps=1, overhead_duration=0.3)
        ab = sentinel.measure_ingest_overhead(args)
        assert ab["rows_ingested"] > 0
        assert ab["p50_ms_enabled"] > 0 and ab["p50_ms_disabled"] > 0

        booster, X = sentinel._model(args)
        predictor = booster.predictor(backend="auto")
        ing = IngestBuffer(str(tmp_path / "ingest"),
                           fit_bin_mapper(X, max_bin=63),
                           window_rows=50000, reservoir_rows=512,
                           segment_rows=4096, register=False)
        rows = X[:32]
        margins = np.asarray(predictor(rows), np.float64)

        def cpu_us(fn, reps=300):
            t0 = time.thread_time()
            for _ in range(reps):
                fn()
            return (time.thread_time() - t0) / reps * 1e6

        def tap_over_scoring():
            tap, score = [], []
            for _ in range(5):
                tap.append(cpu_us(lambda: ing.append(rows, margins)))
                score.append(cpu_us(lambda: predictor(rows)))
            return min(tap) / min(score), (tap, score)

        ratio, runs = tap_over_scoring()
        assert ratio < self.GATE, (ratio, runs)

        # the planted slowdown: an append that does its work twice
        real = IngestBuffer.append

        def twice(self, *a, **k):
            real(self, *a, **k)
            return real(self, *a, **k)
        monkeypatch.setattr(IngestBuffer, "append", twice)
        planted, runs = tap_over_scoring()
        assert planted > self.GATE, (planted, ratio, runs)
