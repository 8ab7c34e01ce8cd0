"""Unified telemetry (ISSUE 5): MetricsRegistry + Prometheus text
exposition on /metrics (single- and multi-process topologies),
correlated trace spans in the EventJournal with trace_report timeline
reconstruction, live training telemetry, and the observability
satellite fixes (StageStats snapshot consistency, summarize_trace mtime
selection, heartbeat gauge seeding, tool artifact schema)."""

import gzip
import importlib.util
import json
import os
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu.core import telemetry
from mmlspark_tpu.core.profiling import StageStats
from mmlspark_tpu.core.telemetry import (EventJournal, MetricsRegistry,
                                         merge_snapshots, read_journal,
                                         render_prometheus)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    """Import a tools/ script as a module (tools/ is not a package)."""
    spec = importlib.util.spec_from_file_location(
        f"_tool_{name}", os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- parser

_LINE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"          # metric name
    r"(?:\{([^}]*)\})?"                      # optional label set
    r" (-?(?:[0-9]*\.)?[0-9]+(?:[eE][+-]?[0-9]+)?|NaN|[+-]Inf)$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text):
    """Minimal Prometheus text-format parser: every non-comment line
    must be `name{labels} value`; raises on anything else.  Returns
    {(name, frozenset(label items)): float}."""
    out = {}
    for line in text.splitlines():
        if not line.strip() or line.startswith("#"):
            continue
        m = _LINE.match(line)
        assert m, f"invalid exposition line: {line!r}"
        name, labels_raw, value = m.groups()
        labels = {}
        if labels_raw:
            consumed = _LABEL.findall(labels_raw)
            # every byte of the label block must parse as k="v" pairs
            rebuilt = ",".join(f'{k}="{v}"' for k, v in consumed)
            assert rebuilt == labels_raw, \
                f"invalid label block: {labels_raw!r}"
            labels = dict(consumed)
        out[(name, frozenset(labels.items()))] = float(value)
    return out


def _samples(parsed, name):
    return {lab: v for (n, lab), v in parsed.items() if n == name}


def _scrape(addr, timeout=15.0):
    with urllib.request.urlopen(f"{addr}/metrics",
                                timeout=timeout) as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        return resp.read().decode("utf-8")


def _post(addr, payload, timeout=15.0):
    req = urllib.request.Request(
        addr, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


# ---------------------------------------------------------------- registry


class TestMetricsRegistry:
    def test_render_and_parse_round_trip(self):
        reg = MetricsRegistry()
        s = StageStats()
        s.incr("shed", 0)
        s.incr("salvaged", 3)
        s.set_gauge("depth", 7.5)
        s.timer("decode").record(0.002)
        s.add_rows(128)
        reg.register("scoring", s)
        parsed = parse_prometheus(reg.render_prometheus())
        key = frozenset({"ns": "scoring"}.items())
        assert parsed[("mmlspark_tpu_rows_total", key)] == 128
        assert parsed[("mmlspark_tpu_events_total",
                       frozenset({"ns": "scoring",
                                  "event": "salvaged"}.items()))] == 3
        assert parsed[("mmlspark_tpu_events_total",
                       frozenset({"ns": "scoring",
                                  "event": "shed"}.items()))] == 0
        assert parsed[("mmlspark_tpu_gauge",
                       frozenset({"ns": "scoring",
                                  "name": "depth"}.items()))] == 7.5
        assert parsed[("mmlspark_tpu_stage_latency_seconds_count",
                       frozenset({"ns": "scoring",
                                  "stage": "decode"}.items()))] == 1

    def test_register_replaces_and_unregister(self):
        reg = MetricsRegistry()
        a, b = StageStats(), StageStats()
        a.incr("x", 1)
        b.incr("x", 2)
        reg.register("ns1", a)
        reg.register("ns1", b)       # newest wins
        assert reg.snapshot()["ns1"]["counters"]["x"] == 2
        reg.unregister("ns1")
        assert reg.snapshot() == {}

    def test_label_escaping_stays_parseable(self):
        text = render_prometheus(
            {'we"ird\\ns': {"counters": {'e"v': 1}}})
        parsed = parse_prometheus(text)
        assert any(n == "mmlspark_tpu_events_total"
                   for n, _ in parsed)

    def test_bad_source_skipped_not_fatal(self):
        class Bad:
            def snapshot(self):
                raise RuntimeError("broken source")
        reg = MetricsRegistry()
        reg.register("bad", Bad())
        reg.register("ok", StageStats())
        assert "ok" in reg.snapshot() and "bad" not in reg.snapshot()

    def test_inf_gauge_renders_not_503(self):
        """One inf gauge must render as '+Inf', not kill the scrape
        with OverflowError (review finding)."""
        text = render_prometheus(
            {"ns1": {"gauges": {"worst_age": float("inf"),
                                "neg": float("-inf")}}})
        parsed = parse_prometheus(text)
        assert parsed[("mmlspark_tpu_gauge",
                       frozenset({"ns": "ns1",
                                  "name": "worst_age"}.items()))] \
            == float("inf")

    def test_merge_up_gauges_take_min(self):
        """Up-style health gauges aggregate with MIN: one degraded
        worker must show in the workers block (review finding)."""
        m = merge_snapshots([
            {"gauges": {"exchange_link_up": 0.0, "age_ms": 5.0}},
            {"gauges": {"exchange_link_up": 1.0, "age_ms": 9.0}}])
        assert m["gauges"]["exchange_link_up"] == 0.0
        assert m["gauges"]["age_ms"] == 9.0

    def test_merge_snapshots_aggregates(self):
        a = {"rows": 10, "rows_per_s": 5.0, "counters": {"shed": 1},
             "gauges": {"age": 3.0},
             "stages": {"score": {"count": 2, "total_s": 0.2,
                                  "p50_ms": 10.0, "p99_ms": 20.0}}}
        b = {"rows": 5, "rows_per_s": 2.5, "counters": {"shed": 2},
             "gauges": {"age": 9.0},
             "stages": {"score": {"count": 1, "total_s": 0.1,
                                  "p50_ms": 50.0, "p99_ms": 60.0}}}
        m = merge_snapshots([a, b])
        assert m["rows"] == 15 and m["counters"]["shed"] == 3
        assert m["gauges"]["age"] == 9.0          # worst-of
        assert m["stages"]["score"]["count"] == 3
        assert m["stages"]["score"]["p99_ms"] == 60.0

    def test_gauge_merge_policy_two_process(self):
        """ISSUE 20 satellite: the name-keyed gauge merge policy.
        Depth-style gauges (queue_depth, *_inflight) SUM — two workers
        each holding 3 queued requests is a backlog of 6, not 3;
        up-style gauges take MIN; level-style gauges keep worst-of
        MAX.  Pinned with a literal two-process merge so a policy
        regression cannot hide behind the aggregate."""
        from mmlspark_tpu.core.telemetry import gauge_merge_mode
        assert gauge_merge_mode("queue_depth") == "sum"
        assert gauge_merge_mode("fanout_inflight") == "sum"
        assert gauge_merge_mode("shards_awaited") == "sum"
        assert gauge_merge_mode("replies_depth") == "sum"
        assert gauge_merge_mode("worker_up") == "min"
        assert gauge_merge_mode("worker_busy") == "max"
        assert gauge_merge_mode("headroom_scoring") == "max"
        w0 = {"gauges": {"queue_depth": 3.0, "fanout_inflight": 2.0,
                         "worker_busy": 0.5, "worker_up": 1.0}}
        w1 = {"gauges": {"queue_depth": 3.0, "fanout_inflight": 1.0,
                         "worker_busy": 0.9, "worker_up": 0.0}}
        m = merge_snapshots([w0, w1])
        assert m["gauges"]["queue_depth"] == 6.0
        assert m["gauges"]["fanout_inflight"] == 3.0
        assert m["gauges"]["worker_busy"] == 0.9
        assert m["gauges"]["worker_up"] == 0.0


# ---------------------------------------------------------------- satellites


class TestStageStatsSnapshotConsistency:
    def test_snapshot_under_contention(self):
        """rows and rows_per_s are read under ONE lock acquisition —
        hammer add_rows from threads while snapshotting; every snapshot
        must be internally coherent (never rows>0 with a window that
        another thread already advanced past it)."""
        s = StageStats()
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                s.add_rows(1)

        threads = [threading.Thread(target=writer) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for _ in range(200):
                snap = s.snapshot()
                assert snap["rows"] >= 0
                assert snap["rows_per_s"] >= 0.0
        finally:
            stop.set()
            for t in threads:
                t.join(5)
        final = s.snapshot()
        assert final["rows"] == s.rows

    def test_heartbeat_age_gauge_seeded_at_start(self, tmp_path):
        from mmlspark_tpu.gbdt.elastic import (ElasticConfig,
                                               HeartbeatWatchdog)
        cfg = ElasticConfig(heartbeat_dir=str(tmp_path), process_id=0,
                            num_processes=1,
                            heartbeat_interval_s=10.0)
        wd = HeartbeatWatchdog(cfg).start()
        try:
            # BEFORE any tick completes: explicit zero, not missing
            snap = wd.stats.snapshot()
            assert snap["gauges"]["heartbeat_age_ms"] == 0.0
            assert snap["counters"]["heartbeat_stalls"] == 0
            assert snap["counters"]["peer_lost"] == 0
        finally:
            wd.stop()

    def test_lease_file_carries_fit_span(self, tmp_path):
        from mmlspark_tpu.gbdt.elastic import (ElasticConfig,
                                               HeartbeatWatchdog)
        cfg = ElasticConfig(heartbeat_dir=str(tmp_path), process_id=0,
                            num_processes=1)
        wd = HeartbeatWatchdog(cfg)
        os.makedirs(cfg.heartbeat_dir, exist_ok=True)
        telemetry.set_current_fit_span("feedface00000000")
        try:
            wd._touch()
        finally:
            telemetry.set_current_fit_span(None)
        content = open(wd.path_for(0)).read()
        assert "feedface00000000" in content


def _write_trace(dir_path, fname, ops, mtime):
    """A hand-made ``.xplane.pb``: one TPU plane whose "XLA Ops" line
    holds ``ops`` = ``(hlo name, op_name path or None, start_us,
    dur_us)``; the path rides the event METADATA's ``tf_op`` stat, as on
    the chip."""
    from jax.profiler import ProfileData
    os.makedirs(dir_path, exist_ok=True)
    events, metadata = [], []
    for i, (name, op_name, start_us, dur_us) in enumerate(ops, 1):
        events.append(f"events {{ metadata_id: {i} "
                      f"offset_ps: {int(start_us * 1e6)} "
                      f"duration_ps: {int(dur_us * 1e6)} }}")
        stat = (f'stats {{ metadata_id: 1 str_value: "{op_name}" }}'
                if op_name else "")
        metadata.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{name}" {stat} }} }}')
    text = ('planes { id: 1 name: "/device:TPU:0" '
            'lines { id: 1 name: "XLA Ops" ' + " ".join(events) + " } "
            + " ".join(metadata)
            + ' stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }')
    path = os.path.join(dir_path, fname)
    with open(path, "wb") as fh:
        fh.write(ProfileData.text_proto_to_serialized_xspace(text))
    os.utime(path, (mtime, mtime))
    return path


class TestSummarizeTrace:
    def test_selects_by_mtime_not_name_and_totals(self, tmp_path):
        from mmlspark_tpu.core.profiling import summarize_trace
        now = time.time()
        # lexicographically LAST but OLD — the pre-fix code picked this
        _write_trace(str(tmp_path), "zzz_old.xplane.pb",
                     [("%stale_op.1 = f32[] add()", None, 0, 9_000_000)],
                     now - 3600)
        # lexicographically first but NEWEST — must win
        _write_trace(str(tmp_path), "aaa_new.xplane.pb",
                     [("%fresh_op.1 = f32[] add()", None, 0, 2000),
                      ("%other_op.2 = f32[] add()", None, 2000, 1000)],
                     now)
        rows = summarize_trace(str(tmp_path))
        names = [n for _, n in rows]
        assert "fresh_op" in names and "stale_op" not in names
        # total_device_ms summary row alongside the per-op rows
        assert names[-1] == "total_device_ms"
        total = dict((n, ms) for ms, n in rows)["total_device_ms"]
        assert total == pytest.approx(3.0)

    def test_empty_dir_returns_empty(self, tmp_path):
        from mmlspark_tpu.core.profiling import summarize_trace
        assert summarize_trace(str(tmp_path)) == []

    def test_groups_self_time_by_named_scope(self, tmp_path):
        """A ``while`` spans its body's ops: its time is counted once,
        as theirs, under the scopes ``jax.named_scope`` named; ops of
        one scope add up whatever their HLO names."""
        from mmlspark_tpu.core.profiling import summarize_trace
        body = "jit(f)/while/body/closed_call/"
        _write_trace(str(tmp_path), "t.xplane.pb", [
            ("%while.7 = (f32[]) while()", None, 0, 10_000),
            ("%fusion.1 = f32[] fusion()",
             body + "root_hist/dot_general:", 1000, 3000),
            ("%all-reduce.2 = f32[] all-reduce()",
             body + "root_hist/reduce/psum:", 4000, 2000),
            ("%fusion.3 = f32[] fusion()",
             body + "cond/branch_2_fun/root_hist/jit(_take)/gather:",
             6000, 1000),
            ("%copy.4 = f32[] copy()", body + "add:", 7000, 500),
        ], time.time())
        got = dict((n, ms) for ms, n in summarize_trace(str(tmp_path)))
        assert got["root_hist"] == pytest.approx(4.0)
        assert got["root_hist/reduce"] == pytest.approx(2.0)
        assert got["jit(f)"] == pytest.approx(0.5)       # under no scope
        assert got["while"] == pytest.approx(3.5)        # its own time
        assert got["total_device_ms"] == pytest.approx(10.0)


class TestCompiledCopies:
    """``compiled_copies``: which copies a compiled program holds, read
    from its text before anything runs."""

    TEXT = """\
HloModule jit_grow_tree, is_scheduled=true

%fused_computation.9 (param_0: bf16[8192,200,48]) -> bf16[8192,200,48] {
  %param_0 = bf16[8192,200,48]{2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %copy.12 = bf16[8192,200,48]{1,2,0:T(8,128)(2,1)} copy(%param_0)
}

%body (p: (s32[], f32[255,200,256,3])) -> (s32[], f32[255,200,256,3]) {
  %copy.369 = f32[255,200,256,3]{3,2,1,0:T(8,128)} copy(%get-tuple-element.3981), metadata={op_name="jit(grow_tree)/while/body"}
  %fusion.228 = f32[255,200,256,3]{3,2,1,0:T(8,128)} fusion(%copy.369, %x), kind=kLoop, calls=%fused_computation.1
  %copy-start.3 = (u8[40000,200]{1,0}, u8[40000,200]{1,0}, u32[]) copy-start(%bins)
  %copy-done.3 = u8[40000,200]{1,0} copy-done(%copy-start.3)
  %copy.5 = s32[] copy(%i)
  %copy.6 = token[] copy(%tok)
}
"""

    def test_names_shapes_and_bytes_largest_first(self):
        from mmlspark_tpu.core.profiling import compiled_copies
        got = compiled_copies(self.TEXT)
        assert got == [
            ("copy.12", (8192, 200, 48), 8192 * 200 * 48 * 2),
            ("copy.369", (255, 200, 256, 3), 255 * 200 * 256 * 3 * 4),
            ("copy-start.3", (40000, 200), 40000 * 200),
            ("copy.5", (), 4),
        ]
        # the fusion that consumes a copy and copy-done are not copies
        assert all("fusion" not in n and "done" not in n for n, _, _ in got)

    def test_min_bytes_keeps_the_copies_worth_asking_about(self):
        from mmlspark_tpu.core.profiling import compiled_copies
        got = compiled_copies(self.TEXT, min_bytes=100 << 20)
        assert [n for n, _, _ in got] == ["copy.12", "copy.369"]

    @pytest.mark.parametrize("min_bytes,opcodes,want", [
        # every instruction that makes an array; a parameter names one
        (100 << 20, None, ["copy.12", "copy.369", "fusion.228"]),
        (0, ("fusion",), ["fusion.228"]),
        (0, ("copy-done",), ["copy-done.3"]),
        (1 << 40, None, []),
    ])
    def test_instructions_over_a_threshold_by_opcode(self, min_bytes,
                                                     opcodes, want):
        """``compiled_instructions``: the general form (PR 28 reads the
        histogram build's ``(rows, F, 16)`` temporaries with it)."""
        from mmlspark_tpu.core.profiling import compiled_instructions
        got = compiled_instructions(self.TEXT, min_bytes, opcodes)
        assert [n for n, _, _ in got] == want
        assert all(b == int(np.prod(sh)) * (2 if n == "copy.12" else
                                            1 if "done" in n else 4)
                   for n, sh, b in got)

    def test_reads_a_compiled_executable(self):
        """Takes what ``.lower(...).compile()`` returns as well as its
        text (which copies the CPU's compiler places is its business)."""
        import jax
        import jax.numpy as jnp
        from mmlspark_tpu.core.profiling import compiled_copies
        compiled = jax.jit(lambda x: x.T.reshape(-1)).lower(
            jax.ShapeDtypeStruct((64, 32), jnp.float32)).compile()
        rows = compiled_copies(compiled)
        assert all(isinstance(n, str) and b == 4 * int(np.prod(sh))
                   for n, sh, b in rows)
        assert compiled_copies(compiled, min_bytes=1 << 30) == []


# ---------------------------------------------------------------- journal


class TestEventJournal:
    def test_contended_emits_and_file_round_trip(self, tmp_path):
        j = EventJournal(capacity=10000)
        n_threads, per = 8, 250

        def writer(k):
            for i in range(per):
                j.emit("ev", thread=k, i=i)

        threads = [threading.Thread(target=writer, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(15)
        events = j.events()
        assert len(events) == n_threads * per
        seqs = [e["seq"] for e in events]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        path = str(tmp_path / "journal.jsonl")
        assert j.dump(path) == len(events)
        assert read_journal(path) == events

    def test_ring_is_bounded(self):
        j = EventJournal(capacity=16)
        for i in range(100):
            j.emit("ev", i=i)
        events = j.events()
        assert len(events) == 16
        assert events[-1]["i"] == 99

    def test_configure_mirrors_and_survives_torn_tail(self, tmp_path):
        path = str(tmp_path / "mirror.jsonl")
        j = EventJournal(capacity=8, path=path)
        j.emit("a", x=1)
        j.emit("b", x=2)
        j.configure(None)
        with open(path, "a") as fh:
            fh.write('{"ev": "torn...')     # crash mid-write
        back = read_journal(path)
        assert [e["ev"] for e in back] == ["a", "b"]

    def test_span_context_manager(self):
        j = EventJournal()
        with j.span("work", fit="f1"):
            pass
        kinds = [e["ev"] for e in j.events()]
        assert kinds == ["work_begin", "work_end"]
        assert j.events()[-1]["dur_ms"] >= 0


# ---------------------------------------------------------------- request trace


class TestRequestTracing:
    def _run_engine_burst(self, trace_payloads):
        import queue

        from mmlspark_tpu.io.scoring import ColumnPlan, ScoringEngine

        class Srv:
            def __init__(self):
                self.request_queue = queue.Queue()
                self.replies = []
                self._lock = threading.Lock()

            def reply(self, rid, val, status=200):
                with self._lock:
                    self.replies.append((rid, val, status))
                return True

        srv = Srv()
        eng = ScoringEngine(srv,
                            predictor=lambda X: X.sum(axis=1),
                            plan=ColumnPlan("features", 3),
                            num_scorers=1, num_repliers=0,
                            latency_budget_ms=2.0)
        for rid, payload in trace_payloads:
            srv.request_queue.put((rid, payload, time.perf_counter()))
        eng.start()
        try:
            deadline = time.time() + 10
            while len(srv.replies) < len(trace_payloads) \
                    and time.time() < deadline:
                time.sleep(0.01)
        finally:
            eng.stop()
        return srv

    def test_form_decode_score_reply_timeline(self):
        trace_report = _load_tool("trace_report")
        tid = telemetry.new_trace_id()
        payloads = [("r%d" % i, {"features": [1.0, 2.0, float(i)]})
                    for i in range(4)]
        payloads.append(("rT", {"features": [9.0, 9.0, 9.0],
                                "_trace_id": tid}))
        srv = self._run_engine_burst(payloads)
        assert len(srv.replies) == 5
        events = telemetry.get_journal().events()
        report = trace_report.request_timeline(events, tid)
        assert report["rid"] == "rT"
        assert report["complete"], report["stages"]
        order = [s for s in report["stages"]
                 if s in trace_report.REQUEST_STAGES]
        assert order == list(trace_report.REQUEST_STAGES)
        # minted-at-admission contract: the rid is a trace id too
        report2 = trace_report.request_timeline(events, "r1")
        assert report2["complete"]

    def test_shed_request_journaled(self):
        import queue

        from mmlspark_tpu.io.scoring import ColumnPlan, ScoringEngine

        class Srv:
            def __init__(self):
                self.request_queue = queue.Queue()
                self.replies = []

            def reply(self, rid, val, status=200):
                self.replies.append((rid, val, status))
                return True

        srv = Srv()
        eng = ScoringEngine(srv, predictor=lambda X: X.sum(axis=1),
                            plan=ColumnPlan("features", 3),
                            num_scorers=1, num_repliers=0,
                            shed_wait_ms=0.0)
        old = time.perf_counter() - 10.0   # waited "10s" already
        srv.request_queue.put(("shed-me", {"features": [1, 2, 3]}, old))
        eng.start()
        try:
            deadline = time.time() + 10
            while not srv.replies and time.time() < deadline:
                time.sleep(0.01)
        finally:
            eng.stop()
        assert srv.replies and srv.replies[0][2] == 503
        shed = [e for e in telemetry.get_journal().events()
                if e["ev"] == "shed"
                and "shed-me" in (e.get("rids") or [])]
        assert shed and "shed-me" in shed[0]["trace_ids"]


# ---------------------------------------------------------------- fit trace


class TestFitTelemetry:
    def test_fit_timeline_with_checkpoint_events(self, tmp_path):
        from mmlspark_tpu.gbdt.binning import fit_bin_mapper
        from mmlspark_tpu.gbdt.engine import (TrainParams, train,
                                              train_stats)
        from mmlspark_tpu.gbdt.objectives import get_objective
        trace_report = _load_tool("trace_report")

        rng = np.random.default_rng(0)
        X = rng.normal(size=(300, 5)).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
        mapper = fit_bin_mapper(X, max_bin=15)
        bins = mapper.transform_packed(X)
        before = train_stats.snapshot()["counters"]
        params = TrainParams(num_iterations=6, num_leaves=7,
                             verbosity=0,
                             checkpoint_dir=str(tmp_path / "ck"),
                             checkpoint_chunk=2)
        b = train(bins, y, None, mapper, get_objective("binary"),
                  params)
        assert len(b.trees) == 6

        events = telemetry.get_journal().events()
        report = trace_report.fit_timeline(events)   # newest fit
        assert report["complete"], report["kinds"]
        kinds = report["kinds"]
        assert kinds[0] == "fit_begin" and kinds[-1] == "fit_end"
        assert "boost_chunk" in kinds and "ckpt_saved" in kinds
        # every event of the timeline carries the SAME span id
        assert len({e["fit"] for e in report["events"]}) == 1
        # fit_end reports the forest it produced
        assert report["events"][-1]["trees"] == 6

        # live gauges moved
        snap = train_stats.snapshot()
        assert snap["gauges"]["ms_per_tree"] > 0
        assert snap["gauges"]["train_rows_per_s"] > 0
        assert snap["gauges"]["last_iteration"] == 6.0
        assert 0 < snap["gauges"]["train_loss"] < 1.0   # binary logloss
        after = snap["counters"]
        assert after["ckpt_saved"] - before["ckpt_saved"] == 2
        assert after["boost_chunks"] - before["boost_chunks"] == 3

        # boost_chunk fields: the histogram method is named
        bc = [e for e in report["events"] if e["ev"] == "boost_chunk"]
        assert all("hist_method" in e and e["ms_per_tree"] > 0
                   for e in bc)

    def test_fit_span_stamped_into_checkpoint_meta(self, tmp_path):
        from mmlspark_tpu.gbdt.engine import (_CKPT_FILE, TrainParams,
                                              train)
        from mmlspark_tpu.gbdt.binning import fit_bin_mapper
        from mmlspark_tpu.gbdt.objectives import get_objective
        rng = np.random.default_rng(1)
        X = rng.normal(size=(200, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float64)
        mapper = fit_bin_mapper(X, max_bin=15)
        bins = mapper.transform_packed(X)
        ck = str(tmp_path / "ck")
        meta_seen = {}
        orig_save = None

        # capture the meta mid-fit (the fit clears its checkpoint on
        # success, so read it through the save hook)
        import mmlspark_tpu.gbdt.engine as eng_mod
        orig_save = eng_mod._ckpt_save

        def spy(*a, **kw):
            orig_save(*a, **kw)
            with np.load(os.path.join(ck, _CKPT_FILE)) as z:
                meta_seen.update(json.loads(
                    bytes(z["__meta__"]).decode("utf-8")))

        eng_mod._ckpt_save = spy
        try:
            train(bins, y, None, mapper, get_objective("binary"),
                  TrainParams(num_iterations=4, num_leaves=7,
                              verbosity=0, checkpoint_dir=ck,
                              checkpoint_chunk=2))
        finally:
            eng_mod._ckpt_save = orig_save
        assert re.fullmatch(r"[0-9a-f]{16}", meta_seen.get("fit_span"))

    def test_monitor_loss_sampled_on_large_fits(self):
        """Beyond the row cap the train-loss gauge is computed on a
        strided sample — bounded D2H per boundary, not O(n) (review
        finding)."""
        from mmlspark_tpu.gbdt import engine as eng
        from mmlspark_tpu.gbdt.objectives import get_objective
        n = eng._MONITOR_LOSS_MAX_ROWS * 3
        rng = np.random.default_rng(0)
        scores = rng.normal(size=n).astype(np.float32)
        labels = (scores + rng.normal(size=n) > 0).astype(np.float64)
        eng._monitor_chunk(0, 2, 0.1, n, 1, "auto",
                           get_objective("binary"), scores, labels,
                           None)
        sampled = eng.train_stats.snapshot()["gauges"]["train_loss"]
        exact = get_objective("binary").train_loss(scores, labels)
        assert 0 < sampled < 1.5
        assert sampled == pytest.approx(exact, rel=0.1)

    def test_train_loss_objectives(self):
        from mmlspark_tpu.gbdt.objectives import get_objective
        binary = get_objective("binary")
        y = np.array([0.0, 1.0, 1.0, 0.0])
        perfect = np.array([-20.0, 20.0, 20.0, -20.0])
        awful = -perfect
        assert binary.train_loss(perfect, y) < 1e-6
        assert binary.train_loss(awful, y) > 5.0
        l2 = get_objective("regression")
        assert l2.train_loss(np.array([1.0, 2.0]),
                             np.array([1.0, 4.0])) == pytest.approx(2.0)
        # objectives without a closed form opt out, not crash
        assert get_objective("quantile").train_loss(perfect, y) is None


# ---------------------------------------------------------------- /metrics


class TestMetricsHTTPSingleProcess:
    def test_scrape_and_counter_monotonicity(self):
        from mmlspark_tpu.io.scoring import ColumnPlan, ScoringEngine
        from mmlspark_tpu.io.serving import HTTPServer
        srv = HTTPServer().start()
        eng = ScoringEngine(srv, predictor=lambda X: X.sum(axis=1),
                            plan=ColumnPlan("features", 4),
                            num_scorers=1, num_repliers=0).start()
        try:
            for i in range(3):
                _post(srv.address, {"features": [1.0, 2.0, 3.0,
                                                 float(i)]})
            first = parse_prometheus(_scrape(srv.address))
            key = frozenset({"ns": "scoring"}.items())
            assert first[("mmlspark_tpu_rows_total", key)] >= 3
            # load burst, then re-scrape: every counter is monotonic
            for i in range(8):
                _post(srv.address, {"features": [0.0, 0.0, 0.0,
                                                 float(i)]})
            second = parse_prometheus(_scrape(srv.address))
            for (name, lab), v in first.items():
                if name.endswith(("_total", "_count")):
                    assert second.get((name, lab), 0.0) >= v, \
                        f"counter went backwards: {name} {dict(lab)}"
            assert second[("mmlspark_tpu_rows_total", key)] >= 11
            # resilience counters are present as explicit zeros
            for ev in ("shed", "expired", "salvaged", "restarted"):
                assert (("mmlspark_tpu_events_total",
                         frozenset({"ns": "scoring",
                                    "event": ev}.items())) in second)
            # serving stage latencies are exposed as histograms
            stages = {dict(lab).get("stage")
                      for (n, lab) in second
                      if n == "mmlspark_tpu_stage_latency_seconds_bucket"}
            assert {"decode", "score", "reply", "e2e"} <= stages
            # every histogram carries the +Inf closing bucket
            for (n, lab) in second:
                if n != "mmlspark_tpu_stage_latency_seconds_bucket":
                    continue
                d = dict(lab)
                assert second[(n, frozenset({**d, "le": "+Inf"}
                                            .items()))] >= 0
        finally:
            eng.stop()
            srv.stop()


class TestMetricsHTTPMultiprocess:
    def test_single_scrape_sees_whole_topology(self):
        """Acceptance (ISSUE 5): one GET /metrics against the 2-process
        MultiprocessHTTPServer returns valid exposition with serving
        stage latencies, resilience counters, and worker-aggregated
        totals."""
        from mmlspark_tpu.io.scoring import ColumnPlan, ScoringEngine
        from mmlspark_tpu.io.serving import MultiprocessHTTPServer
        srv = MultiprocessHTTPServer(num_workers=2).start()
        eng = ScoringEngine(srv, predictor=lambda X: X.sum(axis=1),
                            plan=ColumnPlan("features", 3),
                            num_scorers=1, num_repliers=1).start()
        try:
            for i, addr in enumerate(srv.addresses * 2):
                got = _post(addr, {"features": [1.0, 1.0, float(i)]})
                assert got == pytest.approx(2.0 + i)
            text = _scrape(srv.addresses[0])
            parsed = parse_prometheus(text)     # valid exposition
            # driver-side scoring stats with stage latencies
            key = frozenset({"ns": "scoring"}.items())
            assert parsed[("mmlspark_tpu_rows_total", key)] >= 4
            stages = {dict(lab).get("stage")
                      for (n, lab) in parsed
                      if n == "mmlspark_tpu_stage_latency_seconds_bucket"}
            assert {"decode", "score", "reply"} <= stages
            # ISSUE 8 satellite: every worker slot exposes an up-style
            # gauge + beacon age, so a silent worker shows in 1 scrape
            for w in ("worker0", "worker1", "workers"):
                assert parsed[("mmlspark_tpu_gauge",
                               frozenset({"ns": w,
                                          "name": "worker_up"}
                                         .items()))] == 1.0
                assert (("mmlspark_tpu_gauge",
                         frozenset({"ns": w,
                                    "name": "last_beacon_age_ms"}
                                   .items())) in parsed)
            # resilience counters (seeded zeros still present)
            for ev in ("shed", "expired", "salvaged", "restarted"):
                assert (("mmlspark_tpu_events_total",
                         frozenset({"ns": "scoring",
                                    "event": ev}.items())) in parsed)
            # exchange counters
            assert (("mmlspark_tpu_events_total",
                     frozenset({"ns": "serving_exchange",
                                "event": "worker_deaths"}.items()))
                    in parsed)
            # worker-aggregated totals: the scraped worker reported its
            # stats on the scrape round-trip, so ns="workers" exists
            # and its parked count covers that worker's requests
            wkey = frozenset({"ns": "workers",
                              "event": "parked"}.items())
            assert parsed[("mmlspark_tpu_events_total", wkey)] >= 2
            per_worker = {dict(lab)["ns"]
                          for (n, lab) in parsed
                          if n == "mmlspark_tpu_events_total"
                          and dict(lab)["ns"].startswith("worker")}
            assert any(ns.startswith("worker")
                       and ns not in ("workers",) for ns in per_worker)
        finally:
            eng.stop()
            srv.stop()


# ---------------------------------------------------------------- artifacts


class TestToolArtifactSchema:
    def _assert_block(self, block):
        assert {"metrics_exposition", "journal_excerpt"} <= set(block)
        assert set(block) <= {"metrics_exposition", "journal_excerpt",
                              "profile"}
        assert isinstance(block["metrics_exposition"], str)
        parse_prometheus(block["metrics_exposition"])   # must be valid
        assert isinstance(block["journal_excerpt"], list)
        for rec in block["journal_excerpt"]:
            assert isinstance(rec, dict) and "ev" in rec and "ts" in rec

    def test_bench_serving_telemetry_block(self):
        bench = _load_tool("bench_serving")
        telemetry.get_journal().emit("artifact_probe")  # non-empty tail
        block = bench.telemetry_block()
        self._assert_block(block)
        # the exposition carries the train namespace at minimum (the
        # registry registers it at gbdt.engine import)
        assert 'ns="train"' in block["metrics_exposition"]
        # ISSUE 12: the bench artifact carries the continuous
        # profiler's snapshot for tools/perf_report.py
        assert isinstance(block["profile"], dict)
        assert "phases" in block["profile"]
        assert "dispatch" in block["profile"]

    def test_chaos_training_telemetry_block(self):
        chaos = _load_tool("chaos_training")
        stats_by_pid = {
            "0": {"train": {"rows": 0, "rows_per_s": 0.0,
                            "counters": {"ckpt_saved": 2,
                                         "ckpt_resumed": 1},
                            "gauges": {"ms_per_tree": 4.2},
                            "stages": {}},
                  "watchdog": {"rows": 0, "rows_per_s": 0.0,
                               "counters": {"heartbeat_stalls": 1,
                                            "peer_lost": 0},
                               "gauges": {"heartbeat_age_ms": 12.0},
                               "stages": {}},
                  "journal_tail": [{"ts": 2.0, "seq": 2,
                                    "ev": "ckpt_saved", "fit": "f0"}]},
            "1": {"train": {"rows": 0, "rows_per_s": 0.0,
                            "counters": {"ckpt_saved": 2,
                                         "ckpt_resumed": 0},
                            "gauges": {}, "stages": {}},
                  "watchdog": {"rows": 0, "rows_per_s": 0.0,
                               "counters": {}, "gauges": {},
                               "stages": {}},
                  "journal_tail": [{"ts": 1.0, "seq": 1,
                                    "ev": "fit_begin", "fit": "f0"}]},
        }
        block = chaos.telemetry_block(stats_by_pid)
        self._assert_block(block)
        parsed = parse_prometheus(block["metrics_exposition"])
        # gang-aggregated totals sum across controllers
        assert parsed[("mmlspark_tpu_events_total",
                       frozenset({"ns": "train_gang",
                                  "event": "ckpt_saved"}.items()))] == 4
        # journal excerpt is (ts, seq)-ordered across processes
        assert [e["ev"] for e in block["journal_excerpt"]] == \
            ["fit_begin", "ckpt_saved"]

    def test_trace_report_cli(self, tmp_path, capsys):
        trace_report = _load_tool("trace_report")
        j = EventJournal()
        j.emit("fit_begin", fit="abc")
        j.emit("boost_chunk", fit="abc", it_start=0, it_end=2,
               ms_per_tree=1.0, rows_per_s=10.0, hist_method="auto")
        j.emit("fit_end", fit="abc", dur_s=0.1, trees=2)
        path = str(tmp_path / "j.jsonl")
        j.dump(path)
        rc = trace_report.main([path, "--fit", "latest"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "fit span=abc complete=True" in out


# ------------------------------------------------------- ISSUE 8: histograms


class TestMergeableHistograms:
    def test_bucket_exposition_is_cumulative_and_parses(self):
        """_bucket rows carry le labels with CUMULATIVE counts closed
        by +Inf — the Prometheus histogram contract."""
        s = StageStats()
        t = s.timer("score")
        for v in (0.0011, 0.0012, 0.004, 0.5):
            t.record(v)
        parsed = parse_prometheus(
            render_prometheus({"ns1": s.snapshot()}))
        buckets = {
            dict(lab)["le"]: v for (n, lab), v in parsed.items()
            if n == "mmlspark_tpu_stage_latency_seconds_bucket"}
        assert buckets["+Inf"] == 4
        finite = sorted((float(le), c) for le, c in buckets.items()
                        if le != "+Inf")
        counts = [c for _, c in finite]
        assert counts == sorted(counts)          # cumulative
        assert counts[-1] <= buckets["+Inf"]
        key = frozenset({"ns": "ns1", "stage": "score"}.items())
        assert parsed[("mmlspark_tpu_stage_latency_seconds_count",
                       key)] == 4
        assert parsed[("mmlspark_tpu_stage_latency_seconds_sum",
                       key)] == pytest.approx(0.5063, abs=1e-3)

    def test_two_source_merge_is_exact(self):
        """ISSUE 8 satellite: cross-worker percentile aggregation is
        EXACT — merging two workers' snapshots yields bit-identical
        p50/p99 to a single accumulator that saw every sample (the
        sample-ring design could not legally combine worker p99s)."""
        import random

        from mmlspark_tpu.core.profiling import LatencyStats
        rng = random.Random(7)
        a, b, combined = LatencyStats(), LatencyStats(), LatencyStats()
        # deliberately skewed: worker a fast, worker b slow — the old
        # max-of-p99s bound is wrong in BOTH directions for p50
        for _ in range(400):
            v = rng.uniform(0.0005, 0.002)
            a.record(v)
            combined.record(v)
        for _ in range(100):
            v = rng.uniform(0.05, 0.4)
            b.record(v)
            combined.record(v)
        merged = merge_snapshots(
            [{"stages": {"e2e": a.snapshot()}},
             {"stages": {"e2e": b.snapshot()}}])["stages"]["e2e"]
        want = combined.snapshot()
        assert merged["p50_ms"] == want["p50_ms"]
        assert merged["p99_ms"] == want["p99_ms"]
        assert merged["count"] == want["count"] == 500
        assert merged["buckets"] == want["buckets"]
        # and the old conservative fallback still applies to sources
        # without buckets (hand-built dicts, version-skewed beacons)
        legacy = merge_snapshots(
            [{"stages": {"x": {"count": 1, "total_s": 0.1,
                               "p50_ms": 7.0, "p99_ms": 9.0}}},
             {"stages": {"x": {"count": 1, "total_s": 0.2,
                               "p50_ms": 5.0, "p99_ms": 11.0}}}])
        assert legacy["stages"]["x"]["p99_ms"] == 11.0
        # MIXED bucketed+bucketless sources drop the partial bucket
        # set entirely: rendering it under the full count would show
        # the bucketless samples as +Inf (>300s) outliers
        mixed = merge_snapshots(
            [{"stages": {"x": a.snapshot()}},
             {"stages": {"x": {"count": 1000, "total_s": 1.0,
                               "p50_ms": 1.0, "p99_ms": 2.0}}}])
        assert "buckets" not in mixed["stages"]["x"]
        assert mixed["stages"]["x"]["count"] == 1400


# --------------------------------------------------- ISSUE 8: journal mirror


class TestJournalRotation:
    def test_mirror_rotates_at_cap_without_losing_records(self,
                                                          tmp_path):
        path = str(tmp_path / "mirror.jsonl")
        j = EventJournal(capacity=64)
        j.configure(path, max_bytes=4096)
        for i in range(300):
            j.emit("ev", i=i, pad="x" * 40)
        j.configure(None)
        assert os.path.exists(path + ".1"), "no rotation happened"
        assert os.path.getsize(path) <= 4096 + 256
        cur = read_journal(path)
        prev = read_journal(path + ".1")
        both = prev + cur
        assert both, "both mirror generations empty"
        # the rotation boundary loses nothing: .1 tail and current head
        # are seq-contiguous, and the newest record is the last emit
        # (in .1 when the final emit itself triggered the rotation)
        seqs = [e["seq"] for e in both]
        assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
        assert both[-1]["i"] == 299
        # every record is pid-stamped for cross-process merges
        assert all(e["pid"] == os.getpid() for e in both)

    def test_dump_is_readable_after_emit(self, tmp_path):
        j = EventJournal(capacity=8)
        j.emit("a")
        path = str(tmp_path / "d.jsonl")
        assert j.dump(path) == 1          # fsync'd dump
        assert read_journal(path)[0]["ev"] == "a"


# ----------------------------------------------- ISSUE 8: docs drift guard


class TestMetricFamilyDocGuard:
    def _rendered_names(self):
        """Families + sample names from a REPRESENTATIVE exposition:
        a stage histogram, counters, gauges, rows, the SLO monitor
        families, the continuous profiler's families (seeded so every
        family renders — ISSUE 12), and the compile-probe info
        family."""
        from mmlspark_tpu.core.profiler import Profiler
        from mmlspark_tpu.core.slo import SLOMonitor
        reg = MetricsRegistry()
        s = StageStats()
        s.incr("shed", 0)
        s.set_gauge("depth", 1.0)
        s.timer("score").record(0.002)
        s.add_rows(1)
        reg.register("scoring", s)
        mon = SLOMonitor(registry=reg)
        reg.register_exposition("slo", mon.render_prometheus)
        prof = Profiler(enabled=True)
        prof.record_phase("scoring.score", 0.002)
        prof.count_dispatch("scoring", 1)
        prof._on_jax_duration(
            "/jax/core/compile/backend_compile_duration", 0.01)
        prof.record_memory("tpu:0", "bytes_in_use", 1 << 20)
        reg.register_exposition("profile", prof.render_prometheus)
        # the rollout controller's model-info family (ISSUE 14
        # satellite), rendered off a representative arm entry the way
        # io/rollout publishes the real one
        from mmlspark_tpu.io.rollout import render_model_info
        reg.register_exposition(
            "serving_model_info",
            lambda: render_model_info(
                [{"arm": "baseline", "version": 1,
                  "digest": "sha256:deadbeef"}]))
        # the drift monitor's families (ISSUE 15), rendered off a
        # minimal hand-built reference profile + one observed batch so
        # every mmlspark_tpu_drift_* family emits at least one sample
        from mmlspark_tpu.core.drift import DriftConfig, DriftMonitor
        from mmlspark_tpu.core.sketch import (ReferenceProfile,
                                              StreamSketch)
        rsk = StreamSketch([0.0, 1.0])
        rsk.update(np.array([0.2, 0.4, 0.6, 1.2]))
        msk = StreamSketch([0.0])
        msk.update(np.array([-0.5, 0.5]))
        prof = ReferenceProfile([[0.0, 1.0]], [rsk.snapshot()],
                                [0.0], msk.snapshot(),
                                feature_names=["f0"])
        dmon = DriftMonitor(prof, DriftConfig(duty=1.0,
                                              eval_interval_s=0.0,
                                              min_rows=1))
        dmon.observe(np.array([[0.5]], np.float32), np.array([0.1]))
        dmon.flush()
        dmon.close()            # no stray drain thread past this test
        reg.register_exposition("drift", dmon.render_prometheus)
        # the streaming-ingest and refresh-loop families (ISSUE 18),
        # rendered off a throwaway spill dir the way io/ingest and
        # io/refresh publish the real ones (both pre-register their
        # counters, so every family emits even on a fresh instance)
        import tempfile
        from mmlspark_tpu.gbdt import fit_bin_mapper
        from mmlspark_tpu.io.ingest import IngestBuffer
        from mmlspark_tpu.io.refresh import RefreshController
        from mmlspark_tpu.io.registry import ModelRegistry
        with tempfile.TemporaryDirectory() as td:
            ing = IngestBuffer(
                os.path.join(td, "ing"),
                fit_bin_mapper(np.array([[0.0], [1.0]], np.float32),
                               max_bin=4),
                register=False)
            ing.append(np.array([[0.5]], np.float32),
                       np.array([0.0]))
            ref = RefreshController(
                os.path.join(td, "ref"),
                registry=ModelRegistry(os.path.join(td, "reg")),
                rollout=None, ingest=ing, register=False)
            ing_text = ing.render_prometheus()
            ref_text = ref.render_prometheus()
        reg.register_exposition("ingest", lambda: ing_text)
        reg.register_exposition("refresh", lambda: ref_text)
        # the capacity monitor's families (ISSUE 20), rendered off a
        # hand-seeded monitor so every mmlspark_tpu_capacity_* family
        # emits at least one sample (the real one is seeded by
        # ensure_capacity_sampler at engine start)
        from mmlspark_tpu.core.capacity import CapacityMonitor
        cmon = CapacityMonitor(registry=reg)
        for g, v in (("headroom_scoring", 0.5), ("knee_scoring", 100.0),
                     ("load_scoring", 50.0), ("saturated_scoring", 0.0),
                     ("busy_scoring.score", 0.25)):
            cmon.stats.set_gauge(g, v)
        reg.register_exposition("capacity", cmon.render_prometheus)
        # the quantized-gradient resolution family (ISSUE 17),
        # rendered off a seeded last_fit_info the way gbdt/engine
        # publishes the real one
        from mmlspark_tpu.gbdt import engine as eng
        fit_info = dict(eng.last_fit_info)
        eng.last_fit_info.update(quantized_bits="16",
                                 quantized_max_code="10",
                                 quantized_wire="int16",
                                 quantized_downgrade="none")
        try:
            reg.register_exposition("train_quantized",
                                    eng._quantized_exposition)
            text = reg.render_prometheus()
        finally:
            eng.last_fit_info.clear()
            eng.last_fit_info.update(fit_info)
        families = set(re.findall(r"^# TYPE (\S+) \S+$", text,
                                  re.MULTILINE))
        samples = set(re.findall(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)\{", text,
                                 re.MULTILINE))
        return families, samples, text

    def test_every_rendered_family_is_documented(self):
        """Tier-1 guard (ISSUE 8 satellite): the exposition and
        docs/observability.md cannot drift — every family rendered by
        render_prometheus (including the SLO provider families) must be
        named in the doc, and every mmlspark_tpu_* name the doc claims
        must actually be rendered."""
        doc = open(os.path.join(REPO, "docs",
                                "observability.md")).read()
        families, samples, text = self._rendered_names()
        assert families, "representative exposition rendered nothing"
        missing = sorted(f for f in families if f not in doc)
        assert not missing, (
            f"metric families rendered but undocumented in "
            f"docs/observability.md: {missing}")
        # reverse direction: names the doc claims must exist (prefix
        # mentions like `mmlspark_tpu_slo_` are fine; concrete names
        # must be a rendered family or a derived sample name)
        claimed = {t for t in re.findall(r"mmlspark_tpu_[a-z0-9_]+",
                                         doc)
                   if not t.endswith("_")}
        known = families | samples
        for fam in families:
            known |= {f"{fam}_bucket", f"{fam}_sum", f"{fam}_count"}
        stale = sorted(c for c in claimed if c not in known)
        assert not stale, (
            f"docs/observability.md documents names that are not "
            f"rendered: {stale}")
