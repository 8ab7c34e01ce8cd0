"""Serving hot path: CompiledPredictor dispatch-once scoring, the
pipelined ScoringEngine (deadline batching, padded buckets, stage
stats), prediction parity across every serving path, and the
accept-loop registration fix (ISSUE 1; Clipper-style adaptive batching
over the reference's Spark Serving micro-batch contract)."""

import json
import queue
import threading
import time
import unittest.mock as mock
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu.core.profiling import LatencyStats, StageStats
from mmlspark_tpu.gbdt import LightGBMClassifier, LightGBMRegressor
from mmlspark_tpu.io.scoring import ColumnPlan, ScoringEngine, next_pow2
from mmlspark_tpu.io.serving import (HTTPServer, MultiprocessHTTPServer,
                                     serve_forever)


@pytest.fixture(scope="module")
def model_and_data():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(1200, 8)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2]).astype(np.float64)
    # parallelism="serial" pins the in-process _boost_scan path (the
    # mesh path needs jax.shard_map, absent from this image's jax)
    m = LightGBMRegressor(numIterations=12, numLeaves=15,
                          parallelism="serial",
                          verbosity=0).fit({"features": X, "label": y})
    return m.getModel(), X


@pytest.fixture(scope="module")
def multiclass_model(model_and_data):
    _, X = model_and_data
    rng = np.random.default_rng(4)
    y = rng.integers(0, 3, size=len(X)).astype(np.float64)
    m = LightGBMClassifier(numIterations=6, numLeaves=7,
                           parallelism="serial",
                           verbosity=0).fit({"features": X, "label": y})
    return m.getModel()


def _post(addr, payload, timeout=15.0):
    req = urllib.request.Request(
        addr, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


class FakeServer:
    """Exchange-contract stub: a raw request queue + recorded replies."""

    def __init__(self):
        self.request_queue = queue.Queue()
        self.replies = []
        self._lock = threading.Lock()

    def reply(self, rid, val, status=200):
        with self._lock:
            self.replies.append((rid, val, status))
        return True


class TestCompiledPredictor:
    """Bit-exact margins for every batch size × every serving path
    (ISSUE 1 satellite: sizes {1, 3, 64, 1000} × {native, jit,
    padded-bucket})."""

    SIZES = (1, 3, 64, 1000)

    def _jit_predictor(self, booster):
        """Predictor forced onto the jitted path (native probe off)."""
        from mmlspark_tpu import native
        booster.invalidate_cache()
        with mock.patch.object(native, "predict_forest_available",
                               lambda: False):
            pred = booster.predictor()
        assert pred.mode == "jit"
        return pred

    @pytest.mark.parametrize("n", SIZES)
    def test_native_and_jit_paths_bit_exact(self, model_and_data, n):
        b, X = model_and_data
        Xn = X[:n]
        want = np.asarray(b.predict_margin(Xn))
        p_native = b.predictor()
        got_native = np.asarray(p_native(Xn))
        assert np.array_equal(got_native, want)
        p_jit = self._jit_predictor(b)
        assert np.array_equal(np.asarray(p_jit(Xn)), want)
        b.invalidate_cache()  # leave the module fixture cache fresh

    @pytest.mark.parametrize("n", SIZES)
    def test_padded_bucket_path_bit_exact(self, model_and_data, n):
        """Engine-style padded scoring: pad rows to the power-of-two
        bucket, score, slice — each row's walk is independent, so the
        sliced result is bitwise the unpadded one."""
        b, X = model_and_data
        Xn = X[:n]
        want = np.asarray(b.predict_margin(Xn))
        pred = b.predictor()
        bucket = next_pow2(n)
        Xp = np.zeros((bucket, X.shape[1]), np.float32)
        Xp[:n] = Xn
        got = np.asarray(pred(Xp))[:n]
        assert np.array_equal(got, want)

    @pytest.mark.parametrize("n", SIZES)
    def test_engine_score_path_bit_exact(self, model_and_data, n):
        """The exact batch → ColumnPlan decode → padded bucket → slice
        path ScoringEngine runs, without the HTTP hop."""
        b, X = model_and_data
        Xn = X[:n]
        want = np.asarray(b.predict_margin(Xn)).astype(np.float32)
        eng = ScoringEngine(FakeServer(), predictor=b.predictor(),
                            plan=ColumnPlan("features", X.shape[1]))
        batch = [(f"r{i}", {"features": Xn[i].tolist()})
                 for i in range(n)]
        pairs = eng._score_predictor(batch)
        assert [rid for rid, _ in pairs] == [f"r{i}" for i in range(n)]
        got = np.asarray([v for _, v in pairs], np.float32)
        assert np.array_equal(got, want)

    def test_multiclass_margins_bit_exact(self, multiclass_model,
                                          model_and_data):
        b = multiclass_model
        _, X = model_and_data
        want = np.asarray(b.predict_margin(X[:64]))
        assert want.shape == (64, 3)
        assert np.array_equal(np.asarray(b.predictor()(X[:64])), want)

    def test_num_iteration_resolved_once(self, model_and_data):
        b, X = model_and_data
        pred = b.predictor(num_iteration=5)
        want = np.asarray(b.predict_margin(X[:64], num_iteration=5))
        assert np.array_equal(np.asarray(pred(X[:64])), want)

    def test_shape_check_kept(self, model_and_data):
        b, _ = model_and_data
        with pytest.raises(ValueError, match="feature index"):
            b.predictor()(np.zeros((4, 2), np.float32))


class TestCacheInvalidation:
    """ISSUE 1 satellite: extended()/model-load start with a fresh
    stacked cache, and a stale CompiledPredictor raises instead of
    silently scoring the old forest."""

    def test_extended_resets_stacked_cache(self, model_and_data):
        b, X = model_and_data
        b.predict_margin(X[:4])          # populate the cache
        assert b._stacked is not None
        merged = b.extended(b)
        assert merged._stacked is None and merged._stacked_np is None
        # and the merged model scores with BOTH forests, not the cache
        want = 2 * (np.asarray(b.predict_margin(X[:8]))
                    - np.float32(b.init_score)) + np.float32(b.init_score)
        np.testing.assert_allclose(
            np.asarray(merged.predict_margin(X[:8])), want, rtol=1e-5)

    def test_model_load_resets_stacked_cache(self, model_and_data):
        from mmlspark_tpu.gbdt.booster import Booster
        b, X = model_and_data
        b.predict_margin(X[:4])
        loaded = Booster.load_native_model_string(
            b.save_native_model_string())
        assert loaded._stacked is None and loaded._stacked_np is None

    def test_stale_predictor_raises(self, model_and_data):
        b, X = model_and_data
        pred = b.predictor()
        pred(X[:4])                       # fresh: scores fine
        b.invalidate_cache()
        with pytest.raises(RuntimeError, match="stale"):
            pred(X[:4])
        # a rebuilt predictor works again
        assert np.array_equal(np.asarray(b.predictor()(X[:4])),
                              np.asarray(b.predict_margin(X[:4])))

    def test_tree_mutation_detected_even_without_token(self,
                                                       model_and_data):
        b, X = model_and_data
        pred = b.predictor()
        b.trees.append(b.trees[0])
        try:
            with pytest.raises(RuntimeError, match="stale"):
                pred(X[:4])
        finally:
            b.trees.pop()
            b.invalidate_cache()


class TestDeadlineBatching:
    def test_closes_on_latency_budget(self):
        """3 requests against max_rows=1000: the batch must close when
        the oldest request hits the budget, not park forever."""
        srv = FakeServer()
        eng = ScoringEngine(srv, predictor=lambda X: X[:, 0],
                            plan=ColumnPlan("features", 2),
                            max_rows=1000, latency_budget_ms=40.0)
        for i in range(3):
            srv.request_queue.put((f"r{i}", {"features": [float(i), 0.0]}))
        t0 = time.perf_counter()
        eng.start()
        try:
            deadline = time.time() + 5
            while len(srv.replies) < 3 and time.time() < deadline:
                time.sleep(0.01)
            elapsed = time.perf_counter() - t0
            assert len(srv.replies) == 3
            assert elapsed < 2.0          # budget is 40 ms, not forever
            snap = eng.stats_snapshot()
            assert snap["rows"] == 3
            assert snap["stages"]["e2e"]["count"] == 1  # ONE batch
        finally:
            eng.stop()

    def test_closes_on_max_rows(self):
        """8 pre-parked requests, max_rows=4, huge budget: two full
        batches close immediately on the row cap."""
        srv = FakeServer()
        eng = ScoringEngine(srv, predictor=lambda X: X[:, 0],
                            plan=ColumnPlan("features", 2),
                            max_rows=4, latency_budget_ms=10_000.0)
        for i in range(8):
            srv.request_queue.put((f"r{i}", {"features": [float(i), 0.0]}))
        t0 = time.perf_counter()
        eng.start()
        try:
            deadline = time.time() + 5
            while len(srv.replies) < 8 and time.time() < deadline:
                time.sleep(0.01)
            assert len(srv.replies) == 8
            assert time.perf_counter() - t0 < 5.0   # no budget wait
            snap = eng.stats_snapshot()
            assert snap["stages"]["e2e"]["count"] == 2  # 4 + 4
            form = snap["stages"]["batch_form"]
            assert form["p99_ms"] < 5_000
        finally:
            eng.stop()

    def test_malformed_row_does_not_poison_batch(self):
        """One bad payload co-batched with good ones gets its own 400;
        the good rows still score (code-review finding: a single
        misbehaving client must not 500 up to max_rows neighbors)."""
        srv = FakeServer()
        eng = ScoringEngine(srv, predictor=lambda X: X[:, 0] * 10,
                            plan=ColumnPlan("features", 2),
                            max_rows=8, latency_budget_ms=30.0)
        srv.request_queue.put(("bad", {"features": [1.0]}))     # width 1
        srv.request_queue.put(("g1", {"features": [1.0, 0.0]}))
        srv.request_queue.put(("g2", {"features": [2.0, 0.0]}))
        eng.start()
        try:
            deadline = time.time() + 5
            while len(srv.replies) < 3 and time.time() < deadline:
                time.sleep(0.01)
            by_rid = {r[0]: r for r in srv.replies}
            assert by_rid["bad"][2] == 400
            assert by_rid["g1"][2] == 200
            assert by_rid["g1"][1] == pytest.approx(10.0)
            assert by_rid["g2"][1] == pytest.approx(20.0)
        finally:
            eng.stop()

    def test_legacy_get_batch_only_server(self):
        """A duck-typed server exposing only the pre-engine
        get_batch()/reply() contract still drives the engine (the
        serve_forever shim promises existing callers run unchanged)."""

        class PullServer:
            def __init__(self):
                self._q = queue.Queue()
                self.replies = []

            def get_batch(self, max_rows=64, timeout=0.05):
                batch = []
                try:
                    batch.append(self._q.get(timeout=timeout))
                    while len(batch) < max_rows:
                        batch.append(self._q.get_nowait())
                except queue.Empty:
                    pass
                return batch

            def reply(self, rid, val, status=200):
                self.replies.append((rid, val, status))
                return True

        srv = PullServer()
        eng = ScoringEngine(srv, predictor=lambda X: X[:, 0] + 1,
                            plan=ColumnPlan("features", 2),
                            latency_budget_ms=5.0).start()
        try:
            srv._q.put(("a", {"features": [41.0, 0.0]}))
            deadline = time.time() + 5
            while not srv.replies and time.time() < deadline:
                time.sleep(0.01)
            assert srv.replies == [("a", pytest.approx(42.0), 200)]
        finally:
            eng.stop()

    def test_bad_request_replies_4xx_and_survives(self):
        """A malformed request must produce an error reply, not kill the
        scorer thread; later good requests still score."""
        srv = FakeServer()
        eng = ScoringEngine(srv, predictor=lambda X: X[:, 0],
                            plan=ColumnPlan("features", 2),
                            latency_budget_ms=5.0).start()
        try:
            srv.request_queue.put(("bad", {"wrong_key": 1}))
            deadline = time.time() + 5
            while not srv.replies and time.time() < deadline:
                time.sleep(0.01)
            assert srv.replies and srv.replies[0][2] == 400
            srv.request_queue.put(("good", {"features": [2.0, 0.0]}))
            while len(srv.replies) < 2 and time.time() < deadline:
                time.sleep(0.01)
            assert srv.replies[1][0] == "good"
            assert srv.replies[1][1] == pytest.approx(2.0)
            assert srv.replies[1][2] == 200
        finally:
            eng.stop()

    def test_scorer_exception_salvages_per_row(self):
        """A TRANSIENT predictor blow-up no longer 500s the batch: the
        engine retries row by row, so the rows score on the salvage
        pass and the worker keeps serving (ISSUE 3 resilience layer)."""
        calls = []

        def flaky(X):
            calls.append(len(X))
            if len(calls) == 1:
                raise RuntimeError("boom")
            return X[:, 0]

        srv = FakeServer()
        eng = ScoringEngine(srv, predictor=flaky,
                            plan=ColumnPlan("features", 2),
                            latency_budget_ms=5.0).start()
        try:
            srv.request_queue.put(("r1", {"features": [1.0, 0.0]}))
            deadline = time.time() + 5
            while not srv.replies and time.time() < deadline:
                time.sleep(0.01)
            assert srv.replies[0] == ("r1", pytest.approx(1.0), 200)
            assert eng.stats_snapshot()["counters"]["salvaged"] == 1
            srv.request_queue.put(("r2", {"features": [3.0, 0.0]}))
            while len(srv.replies) < 2 and time.time() < deadline:
                time.sleep(0.01)
            assert srv.replies[1] == ("r2", pytest.approx(3.0), 200)
        finally:
            eng.stop()

    def test_persistent_poison_row_fails_alone(self):
        """A payload that ALWAYS crashes the predictor gets its own 500
        after per-row salvage; co-batched neighbors still score."""

        def poisoned(X):
            if np.any(X[:, 0] == 666.0):
                raise RuntimeError("poison payload")
            return X[:, 0]

        srv = FakeServer()
        eng = ScoringEngine(srv, predictor=poisoned,
                            plan=ColumnPlan("features", 2),
                            max_rows=8, latency_budget_ms=30.0,
                            pad_buckets=False)
        # enqueue BEFORE start so all three land in ONE batch — the
        # salvage accounting below depends on them being co-batched
        srv.request_queue.put(("g1", {"features": [1.0, 0.0]}))
        srv.request_queue.put(("bad", {"features": [666.0, 0.0]}))
        srv.request_queue.put(("g2", {"features": [2.0, 0.0]}))
        eng.start()
        try:
            deadline = time.time() + 5
            while len(srv.replies) < 3 and time.time() < deadline:
                time.sleep(0.01)
            by_rid = {r[0]: r for r in srv.replies}
            assert by_rid["bad"][2] == 500
            assert by_rid["g1"][1] == pytest.approx(1.0)
            assert by_rid["g2"][1] == pytest.approx(2.0)
            snap = eng.stats_snapshot()
            assert snap["counters"]["salvaged"] == 2
        finally:
            eng.stop()


class TestColumnPlan:
    def test_vector_plan_contiguous(self):
        plan = ColumnPlan("features", 3)
        X = plan.decode([{"features": [1, 2, 3]}, {"features": [4, 5, 6]}])
        assert X.dtype == np.float32 and X.flags["C_CONTIGUOUS"]
        assert X.shape == (2, 3)

    def test_scalar_columns_plan(self):
        plan = ColumnPlan(["a", "b"])
        X = plan.decode([{"a": 1, "b": 2, "junk": 9}, {"a": 3, "b": 4}])
        assert X.tolist() == [[1.0, 2.0], [3.0, 4.0]]

    def test_feature_count_mismatch_raises(self):
        with pytest.raises(ValueError, match="features"):
            ColumnPlan("features", 4).decode([{"features": [1, 2]}])

    def test_decode_table_matches_decode(self):
        from mmlspark_tpu.io.serving import request_table
        batch = [("a", {"features": [1.0, 2.0]}),
                 ("b", {"features": [3.0, 4.0]})]
        plan = ColumnPlan("features", 2)
        t = request_table(batch)
        assert np.array_equal(plan.decode_table(t),
                              plan.decode([p for _, p in batch]))

    def test_decode_binary_views_single_row_zero_copy(self):
        """Binary wire (ISSUE 11): a one-entry batch passes the
        frombuffer view STRAIGHT through — no copy, no JSON path."""
        from mmlspark_tpu.io import wire
        plan = ColumnPlan("features", 4)
        row = np.arange(4, dtype=np.float32).reshape(1, 4)
        _k, _rid, view = wire.unpack_matrix(
            wire.pack_matrix("r", row))
        X = plan.decode([view])
        assert X is view                       # zero-copy
        assert np.array_equal(X, row)

    def test_decode_binary_batch_concatenates(self):
        from mmlspark_tpu.io.wire import BinaryReq
        plan = ColumnPlan("features", 3)
        rows = [np.full((1, 3), i, np.float32) for i in range(5)]
        rows[2] = BinaryReq(rows[2], 1000.0)   # deadline-wrapped entry
        X = plan.decode(rows)
        assert X.shape == (5, 3) and X.dtype == np.float32
        assert np.array_equal(X[:, 0], np.arange(5, dtype=np.float32))

    def test_decode_binary_width_mismatch_raises(self):
        plan = ColumnPlan("features", 4)
        with pytest.raises(ValueError, match="expects"):
            plan.decode([np.ones((1, 2), np.float32)])

    def test_request_table_reconstitutes_binary_payloads(self):
        """Transform-mode engines behind the binary exchange keep
        their column contract: binary row views come back as a
        ``features`` column in request_table."""
        from mmlspark_tpu.io.serving import request_table
        from mmlspark_tpu.io.wire import BinaryReq
        batch = [("a", np.asarray([[1.0, 2.0]], np.float32)),
                 ("b", BinaryReq(np.asarray([[3.0, 4.0]], np.float32),
                                 1000.0)),
                 ("c", {"features": [5.0, 6.0]})]
        t = request_table(batch)
        assert np.allclose(t["features"],
                           [[1, 2], [3, 4], [5, 6]])
        assert list(t["id"]) == ["a", "b", "c"]

    def test_binary_wire_scores_match_json_wire(self, model_and_data):
        """Bit-exact parity between the two wires: the SAME rows
        decoded from JSON payloads and from packed float32 blocks
        produce identical margins (and both equal predict_margin)."""
        from mmlspark_tpu.io import wire
        b, X = model_and_data
        plan = ColumnPlan("features", X.shape[1])
        pred = b.predictor()
        rows = X[:32]
        Xj = plan.decode([{"features": r.tolist()} for r in rows])
        views = [wire.unpack_matrix(
            wire.pack_matrix(str(i), rows[i:i + 1]))[2]
            for i in range(32)]
        Xb = plan.decode(views)
        assert np.array_equal(Xj, Xb)
        mj = np.asarray(pred(Xj))
        mb = np.asarray(pred(Xb))
        want = np.asarray(b.predict_margin(rows)).astype(np.float32)
        assert np.array_equal(mj, mb)
        assert np.allclose(mj, want, rtol=1e-6, atol=1e-6)


class TestBinaryReplyMode:
    def test_engine_skips_tolist_for_binary_wire_server(
            self, model_and_data):
        """A binary_wire exchange gets numpy values straight off the
        margin ndarray (no per-row tolist/_json_value build)."""
        from mmlspark_tpu.io.scoring import ScoringEngine
        b, X = model_and_data

        class BinServer(FakeServer):
            binary_wire = True

        srv = BinServer()
        eng = ScoringEngine(srv, predictor=b.predictor(),
                            plan=ColumnPlan("features", X.shape[1]))
        batch = [(str(i), {"features": X[i].tolist()})
                 for i in range(8)]
        pairs = eng._score_predictor(batch)
        want = np.asarray(b.predict_margin(X[:8])).astype(np.float32)
        for i, (rid, v) in enumerate(pairs):
            assert isinstance(v, np.floating), type(v)
            assert v == want[i]
        # the JSON-wire engine keeps returning plain floats
        eng2 = ScoringEngine(FakeServer(), predictor=b.predictor(),
                             plan=ColumnPlan("features", X.shape[1]))
        pairs2 = eng2._score_predictor(batch)
        assert all(isinstance(v, float) for _r, v in pairs2)
        assert [float(v) for _r, v in pairs] \
            == [v for _r, v in pairs2]


class TestServingSmoke:
    def test_http_end_to_end_concurrent_senders(self, model_and_data):
        """Tier-1-fast end-to-end smoke: 24 concurrent HTTP senders
        through HTTPServer + ScoringEngine; every client gets exactly
        its own row's margin (bit-exact vs predict_margin)."""
        b, X = model_and_data
        srv = HTTPServer().start()
        eng = ScoringEngine(srv, predictor=b.predictor(),
                            plan=ColumnPlan("features", X.shape[1]),
                            max_rows=64, latency_budget_ms=3.0,
                            num_scorers=2).start()
        try:
            results, errs = {}, []

            def client(i):
                try:
                    results[i] = _post(srv.address,
                                       {"features": X[i].tolist()})
                except Exception as e:  # noqa: BLE001
                    errs.append((i, e))

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(24)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(20)
            assert not errs
            want = np.asarray(b.predict_margin(X[:24])).astype(np.float32)
            got = np.asarray([results[i] for i in range(24)], np.float32)
            assert np.array_equal(got, want)
            snap = eng.stats_snapshot()
            assert snap["rows"] == 24
            for stage in ("batch_form", "queue_wait", "decode", "score",
                          "reply", "e2e"):
                assert snap["stages"][stage]["count"] >= 1, stage
        finally:
            eng.stop()
            srv.stop()

    def test_serve_forever_shim_raises_on_transform_bug(self):
        """Legacy error semantics preserved: a broken transform stops
        the loop and the exception surfaces from serve_forever, instead
        of being swallowed into per-request 500s (code-review
        finding)."""
        srv = HTTPServer().start()

        def bad_transform(t):
            raise KeyError("prediction")

        def client():
            try:
                _post(srv.address, {"features": [1.0]}, timeout=5)
            except Exception:  # noqa: BLE001 - 504/timeout expected
                pass

        th = threading.Thread(target=client, daemon=True)
        th.start()
        try:
            with pytest.raises(KeyError):
                serve_forever(srv, bad_transform, "prediction",
                              stop_event=threading.Event())
        finally:
            th.join(10)
            srv.stop()

    def test_pad_buckets_auto_skips_native(self, model_and_data):
        """Auto padding: on when the predictor resolved to jit (compile
        cache), off for the native kernel (phantom rows for nothing)."""
        b, _ = model_and_data
        fake = FakeServer()
        p_native = b.predictor(backend="native")
        eng_n = ScoringEngine(fake, predictor=p_native,
                              plan=ColumnPlan("features", 8))
        assert eng_n._pad_buckets is False
        b.invalidate_cache()
        eng_j = ScoringEngine(fake, predictor=b.predictor(backend="jit"),
                              plan=ColumnPlan("features", 8))
        assert eng_j._pad_buckets is True
        # plain callables (unknown backend) keep padding
        eng_l = ScoringEngine(fake, predictor=lambda X: X[:, 0],
                              plan=ColumnPlan("features", 8))
        assert eng_l._pad_buckets is True
        # explicit override wins
        eng_o = ScoringEngine(fake, predictor=b.predictor(backend="jit"),
                              plan=ColumnPlan("features", 8),
                              pad_buckets=False)
        assert eng_o._pad_buckets is False

    def test_serve_forever_shim_unchanged_api(self):
        """The legacy one-liner keeps working as a thin engine shim."""
        srv = HTTPServer().start()
        stop = threading.Event()

        def xform(t):
            return t.withColumn(
                "pred", np.asarray(t["features"]).sum(axis=1))

        th = threading.Thread(target=serve_forever,
                              args=(srv, xform, "pred"),
                              kwargs={"stop_event": stop}, daemon=True)
        th.start()
        try:
            out = _post(srv.address, {"features": [1.0, 2.5, 3.0]})
            assert out == pytest.approx(6.5)
        finally:
            stop.set()
            th.join(10)
            srv.stop()
        assert not th.is_alive()


class TestAcceptLoopRegistration:
    def test_garbage_peer_consumes_no_slot(self):
        """ADVICE r5 (now enforced by the transport handshake): a
        non-protocol peer is dropped at the magic preamble and must not
        register a session; a legit worker joining afterwards still
        gets slot 0 and serves."""
        import os
        import socket
        import subprocess
        import sys
        srv = MultiprocessHTTPServer(num_workers=1, spawn_workers=False,
                                     join_timeout=25.0)
        h, _, p = srv.exchange_address.rpartition(":")

        def garbage_peer(data):
            time.sleep(0.2)
            s = socket.create_connection(("127.0.0.1", int(p)))
            s.sendall(data)
            time.sleep(0.5)
            s.close()

        # one ASCII-garbage peer and one binary peer — neither speaks
        # the transport magic, so neither may register a session or
        # kill its handshake thread
        peers = [threading.Thread(target=garbage_peer, args=(d,),
                                  daemon=True)
                 for d in (b"NOT JSON AT ALL\n", b"\xff\xfe\x00binary")]
        for g in peers:
            g.start()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        code = ("import sys; from mmlspark_tpu.io.serving import "
                "join_exchange; join_exchange(sys.argv[1], 0, "
                "token=sys.argv[2])")
        proc = subprocess.Popen(
            [sys.executable, "-c", code, f"127.0.0.1:{p}", srv.token],
            env=env)
        try:
            srv.start()
            for g in peers:
                g.join(5)
            # only the AUTHED worker registered a transport session
            assert len(srv._ts.sessions) == 1
            assert srv.addresses[0]
            # and it actually serves
            done = threading.Event()

            def pump():
                while not done.is_set():
                    for rid, payload in srv.get_batch(timeout=0.1):
                        srv.reply(rid, {"y": payload["x"] + 1})
                        done.set()

            t = threading.Thread(target=pump, daemon=True)
            t.start()
            assert _post(srv.addresses[0], {"x": 41}) == {"y": 42}
            done.set()
            t.join(5)
        finally:
            srv.stop()
            proc.wait(timeout=15)


class TestStatsCounters:
    def test_latency_percentiles(self):
        s = LatencyStats(capacity=100)
        for v in range(1, 101):            # 1..100 ms
            s.record(v / 1000.0)
        snap = s.snapshot()
        assert snap["count"] == 100
        # log-bucket histogram estimates: within the ladder's ~±9%
        # relative resolution (count/total stay exact)
        assert snap["p50_ms"] == pytest.approx(50.0, rel=0.1)
        assert snap["p99_ms"] == pytest.approx(99.0, rel=0.1)
        assert snap["mean_ms"] == pytest.approx(50.5, abs=0.1)
        # the buckets are the mergeable representation: counts sum to
        # the sample count
        assert sum(snap["buckets"].values()) == 100

    def test_stage_stats_rows_per_s(self):
        st = StageStats()
        st.add_rows(100)
        time.sleep(0.05)
        st.add_rows(100)
        snap = st.snapshot()
        assert snap["rows"] == 200
        assert snap["rows_per_s"] > 0
        with st.time("decode"):
            pass
        assert st.snapshot()["stages"]["decode"]["count"] == 1
