"""Rollout chaos drill (ISSUE 14 acceptance artifact): prove the
SLO-gated zero-downtime rollout's contract end to end —

A. **healthy_promote** — a clean canary over real HTTP traffic is
   auto-promoted by the gate; no reply is dropped, every reply is
   bit-exact against exactly ONE model version (no reply mixes trees
   from two versions), and /readyz + /metrics name the new version.
B. **faulty_canary_rollback** — a canary with injected scoring faults
   and latency (ChaosPredictor + a seeded slow wrapper) trips the
   fast-window burn and is auto-rolled-back: zero wrong answers (the
   canary's rows are rescored on the baseline), zero dropped requests,
   a ``rollout_rolled_back`` journal event and a crash-flight record.
C. **driver_kill_mid_cutover** — a driver process is SIGKILLed at the
   worst instants of the registry cutover (immediately before and
   immediately after the manifest commit); a fresh process recovers to
   ONE consistent, digest-verified active version either way.
D. **corrupted_entry** — a torn / bit-flipped registry model file is
   rejected by the digest at load, the entry is quarantined, and the
   gate refuses to canary it; the healthy active version is untouched.
E. **fleet_cutover** — a sharded fleet's two-phase
   ``load_version``/``activate_version`` flip under concurrent scoring
   traffic: every reduce equals exactly one version's reference margin
   (never a mix of tree-range shards from two models).

All injection is seeded (``ChaosPlan``): same seed, same fault
schedule.  Each scenario embeds its verdicts, the gate's SLO report,
and a trace excerpt (the rollout journal events + one reconstructed
request timeline).

Run: ``python tools/chaos_rollout.py --out artifacts/chaos_rollout_r14.json``
(~1 min wall on a 2-core CPU box).
"""

import argparse
import glob
import http.client
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_report  # noqa: E402  (tools/ sibling, not a package)


def post_once(addr, body, timeout=15.0):
    host, port = addr.replace("http://", "").rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("POST", "/", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        try:
            return resp.status, json.loads(raw)
        except ValueError:
            return resp.status, None
    finally:
        conn.close()


def get_json(addr, path, timeout=10.0):
    host, port = addr.replace("http://", "").rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        raw = resp.read()
        try:
            return resp.status, json.loads(raw)
        except ValueError:
            return resp.status, raw.decode("utf-8", "replace")
    finally:
        conn.close()


def verdict(ledger, name, ok, detail=""):
    ledger.append({"name": name, "pass": bool(ok), "detail": detail})
    print(f"  [{'PASS' if ok else 'FAIL'}] {name}"
          + (f" — {detail}" if detail else ""))


def rollout_journal_excerpt(max_events=40):
    from mmlspark_tpu.core.telemetry import get_journal
    keep = ("rollout_started", "rollout_promoted",
            "rollout_rolled_back", "slo_burn", "slo_recovered")
    return [e for e in get_journal().events() if e["ev"] in keep][
        -max_events:]


def build_models(seed):
    import numpy as np

    from mmlspark_tpu.gbdt import LightGBMRegressor
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(800, 8)).astype(np.float32)
    y = (X[:, 0] + 0.6 * X[:, 1] * X[:, 2]
         - 0.3 * X[:, 3]).astype(np.float64)
    b1 = LightGBMRegressor(numIterations=8, numLeaves=15,
                           parallelism="serial", verbosity=0).fit(
        {"features": X, "label": y}).getModel()
    b2 = LightGBMRegressor(numIterations=14, numLeaves=15,
                           parallelism="serial", verbosity=0).fit(
        {"features": X, "label": y}).getModel()
    w1 = np.asarray(b1.predict_margin(X), np.float32)
    w2 = np.asarray(b2.predict_margin(X), np.float32)
    assert not np.array_equal(w1, w2)
    return X, b1, b2, w1, w2


def client_loop(addr, X, stop, ledger, lock, interval=0.002):
    """One closed-loop client: POSTs rows round-robin, records every
    outcome explicitly (rid-keyed row index → classified later)."""
    k = 0
    me = threading.get_ident() % 997
    while not stop.is_set():
        i = (me * 31 + k) % len(X)
        body = json.dumps({"features": X[i].tolist()}).encode()
        try:
            status, val = post_once(addr, body)
            with lock:
                ledger.append((i, status, val))
        except OSError as e:
            with lock:
                ledger.append((i, -1, repr(e)))
        k += 1
        time.sleep(interval)


def classify_replies(ledger, w_list):
    """Count replies per matched version; anything that matches no
    version bit-exactly is WRONG."""
    import numpy as np
    counts = {f"v{j}": 0 for j in range(len(w_list))}
    wrong, errors = 0, 0
    for i, status, val in ledger:
        if status != 200:
            errors += 1
            continue
        v = np.float32(val)
        for j, w in enumerate(w_list):
            if v == w[i]:
                counts[f"v{j}"] += 1
                break
        else:
            wrong += 1
    return counts, wrong, errors


def scenario_healthy_promote(seed, verdicts):
    import numpy as np

    from mmlspark_tpu.io.registry import ModelRegistry
    from mmlspark_tpu.io.rollout import RolloutConfig, RolloutController
    from mmlspark_tpu.io.scoring import ScoringEngine
    from mmlspark_tpu.io.serving import HTTPServer

    print("scenario A: healthy canary auto-promotes")
    X, b1, b2, w1, w2 = build_models(seed)
    root = tempfile.mkdtemp(prefix="chaos_rollout_a_")
    reg = ModelRegistry(root)
    v1 = reg.publish(b1, activate=True)
    v2 = reg.publish(b2)
    ctl = RolloutController(reg, config=RolloutConfig(
        canary_fraction=0.35, soak_s=1.5, min_canary_rows=50,
        canary_deadline_ms=None, fast_window_s=2.0, slow_window_s=6.0,
        tick_s=0.2))
    srv = HTTPServer(port=0).start()
    ctl.install(srv)
    eng = ScoringEngine(srv, predictor=ctl, max_rows=32,
                        latency_budget_ms=2.0, num_scorers=2,
                        num_repliers=0).start()
    ctl.start()
    stop, lock, ledger = threading.Event(), threading.Lock(), []
    clients = [threading.Thread(
        target=client_loop, args=(srv.address, X, stop, ledger, lock),
        daemon=True) for _ in range(4)]
    slo_report = None
    try:
        for t in clients:
            t.start()
        time.sleep(0.6)                      # baseline traffic
        ctl.start_canary(v2)
        deadline = time.monotonic() + 20.0
        while ctl.state() != "steady" and time.monotonic() < deadline:
            if slo_report is None or ctl.state() == "canarying":
                slo_report = ctl.slo_report() or slo_report
            time.sleep(0.1)
        promoted = reg.active_version() == v2
        time.sleep(0.5)                      # post-promote traffic
        status, readyz = get_json(srv.address, "/readyz")
        status_m, metrics = get_json(srv.address, "/metrics")
    finally:
        stop.set()
        for t in clients:
            t.join(timeout=5)
        ctl.stop()
        eng.stop()
        srv.stop()
    counts, wrong, errors = classify_replies(ledger, [w1, w2])
    verdict(verdicts, "healthy_canary_auto_promoted",
            promoted and reg.entry(v2)["promoted_state"] == "active",
            f"active={reg.active_version()}")
    verdict(verdicts, "promote_zero_wrong_answers", wrong == 0,
            f"{len(ledger)} replies, counts={counts}, wrong={wrong}")
    verdict(verdicts, "promote_zero_dropped", errors == 0,
            f"non-200/conn errors={errors}")
    verdict(verdicts, "promote_traffic_spanned_both_versions",
            counts["v0"] > 0 and counts["v1"] > 0, str(counts))
    verdict(verdicts, "readyz_names_promoted_version",
            isinstance(readyz, dict)
            and readyz.get("model", {}).get("active_version") == v2,
            f"readyz model={readyz.get('model') if isinstance(readyz, dict) else readyz}")
    verdict(verdicts, "metrics_model_info_family_present",
            isinstance(metrics, str)
            and "mmlspark_tpu_serving_model_info{" in metrics
            and f'version="{v2}"' in metrics)
    evs = rollout_journal_excerpt()
    verdict(verdicts, "promote_journal_event",
            any(e["ev"] == "rollout_promoted"
                and e.get("version") == v2 for e in evs))
    # one reconstructed request timeline off the engine's journal
    from mmlspark_tpu.core.telemetry import get_journal
    timeline = None
    for e in reversed(get_journal().events()):
        if e["ev"] == "form" and e.get("rids"):
            timeline = trace_report.request_timeline(
                get_journal().events(), e["rids"][0])
            break
    verdict(verdicts, "trace_timeline_reconstructed",
            timeline is not None and timeline.get("events"))
    return {
        "registry_root": root, "versions": {"v1": v1, "v2": v2},
        "replies": {"total": len(ledger), **counts, "wrong": wrong,
                    "errors": errors},
        "slo_report": slo_report,
        "journal_excerpt": evs,
        "trace_timeline": timeline,
    }


class SlowChaosPredictor:
    """Seeded latency injection on top of ChaosPredictor semantics: a
    deterministic per-call stall pushing the canary past its
    deadline."""

    def __init__(self, inner, plan, stall_s=0.02, rate=0.8,
                 name="canary_slow"):
        self._inner = inner
        self._chan = plan.channel(name)
        self._stall_s = stall_s
        self._rate = rate
        self.stalls = 0
        if hasattr(inner, "mode"):
            self.mode = inner.mode

    def __call__(self, X):
        if self._chan.fire(self._rate):
            self.stalls += 1
            time.sleep(self._stall_s)
        return self._inner(X)


def scenario_faulty_canary(seed, verdicts):
    import numpy as np

    from mmlspark_tpu.io.chaos import ChaosPlan, ChaosPredictor
    from mmlspark_tpu.io.registry import ModelRegistry
    from mmlspark_tpu.io.rollout import RolloutConfig, RolloutController
    from mmlspark_tpu.io.scoring import ScoringEngine
    from mmlspark_tpu.io.serving import HTTPServer

    print("scenario B: faulty canary auto-rolled-back")
    X, b1, b2, w1, w2 = build_models(seed + 1)
    root = tempfile.mkdtemp(prefix="chaos_rollout_b_")
    reg = ModelRegistry(root)
    v1 = reg.publish(b1, activate=True)
    v2 = reg.publish(b2)
    plan = ChaosPlan(seed)
    ctl = RolloutController(reg, config=RolloutConfig(
        canary_fraction=0.35, soak_s=30.0, min_canary_rows=10**9,
        canary_deadline_ms=10.0, fast_window_s=2.0, slow_window_s=6.0,
        tick_s=0.2))
    # the injection: ~40% of canary batches raise, ~80% stall past the
    # canary deadline — both gate objectives burn
    ctl.canary_wrap = lambda p: SlowChaosPredictor(
        ChaosPredictor(p, plan, exc_rate=0.4, name="canary_exc"),
        plan, stall_s=0.03, rate=0.8)
    flight_dir = os.environ.get("MMLSPARK_TPU_FLIGHTREC_DIR") \
        or os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "artifacts")
    flights_before = set(glob.glob(
        os.path.join(flight_dir, "flightrec_*rollout_rolled_back*")))
    srv = HTTPServer(port=0).start()
    ctl.install(srv)
    eng = ScoringEngine(srv, predictor=ctl, max_rows=32,
                        latency_budget_ms=2.0, num_scorers=2,
                        num_repliers=0).start()
    ctl.start()
    stop, lock, ledger = threading.Event(), threading.Lock(), []
    clients = [threading.Thread(
        target=client_loop, args=(srv.address, X, stop, ledger, lock),
        daemon=True) for _ in range(4)]
    rolled_back = False
    slo_report = None
    try:
        for t in clients:
            t.start()
        time.sleep(0.4)
        ctl.start_canary(v2)
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if ctl.state() == "canarying":
                slo_report = ctl.slo_report() or slo_report
            else:
                rolled_back = True
                break
            time.sleep(0.1)
        time.sleep(0.4)                     # post-rollback traffic
    finally:
        stop.set()
        for t in clients:
            t.join(timeout=5)
        ctl.stop()
        eng.stop()
        srv.stop()
    counts, wrong, errors = classify_replies(ledger, [w1, w2])
    evs = rollout_journal_excerpt()
    rb_evs = [e for e in evs if e["ev"] == "rollout_rolled_back"
              and e.get("version") == v2]
    flights_after = set(glob.glob(
        os.path.join(flight_dir, "flightrec_*rollout_rolled_back*")))
    verdict(verdicts, "faulty_canary_auto_rolled_back",
            rolled_back
            and reg.entry(v2)["promoted_state"] == "rolled_back"
            and reg.active_version() == v1,
            f"state={reg.entry(v2)['promoted_state']}, "
            f"active={reg.active_version()}")
    verdict(verdicts, "rollback_zero_wrong_answers", wrong == 0,
            f"{len(ledger)} replies, counts={counts}, wrong={wrong} "
            "(canary faults rescored on baseline)")
    verdict(verdicts, "rollback_zero_dropped", errors == 0,
            f"non-200/conn errors={errors}")
    verdict(verdicts, "rollback_journal_event_with_slo_detail",
            bool(rb_evs)
            and rb_evs[-1].get("reason", "").startswith("slo_burn"),
            rb_evs[-1].get("reason", "") if rb_evs else "no event")
    verdict(verdicts, "rollback_flight_record_dumped",
            len(flights_after) > len(flights_before),
            f"{len(flights_after) - len(flights_before)} new record(s)")
    verdict(verdicts, "canary_errors_counted",
            ctl.stats.counter("canary_errors") > 0
            and ctl.stats.counter("canary_deadline_miss") > 0,
            f"errors={ctl.stats.counter('canary_errors')}, "
            f"deadline_miss={ctl.stats.counter('canary_deadline_miss')}")
    return {
        "registry_root": root, "versions": {"v1": v1, "v2": v2},
        "replies": {"total": len(ledger), **counts, "wrong": wrong,
                    "errors": errors},
        "injected": plan.counts(),
        "slo_report_at_rollback": slo_report,
        "journal_excerpt": evs,
    }


_KILL_CHILD_SRC = """
import os, signal, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"  # the parent may hold the chip
from mmlspark_tpu.io.registry import ModelRegistry
reg = ModelRegistry({root!r})
phase = {phase!r}
if phase == "before_commit":
    # die at the WORST instant: model state mutated in memory, the
    # manifest replace (the commit point) not yet issued
    reg.pre_commit_hook = lambda: os.kill(os.getpid(), signal.SIGKILL)
    reg.activate({version})
else:
    reg.activate({version})
    os.kill(os.getpid(), signal.SIGKILL)   # die right after commit
"""


def scenario_driver_kill(seed, verdicts):
    from mmlspark_tpu.io.registry import ModelRegistry

    print("scenario C: driver SIGKILL mid-cutover")
    X, b1, b2, w1, w2 = build_models(seed + 2)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    results = {}
    for phase in ("before_commit", "after_commit"):
        root = tempfile.mkdtemp(prefix=f"chaos_rollout_c_{phase}_")
        reg = ModelRegistry(root)
        v1 = reg.publish(b1, activate=True)
        v2 = reg.publish(b2)
        src = _KILL_CHILD_SRC.format(repo=repo, root=root,
                                     phase=phase, version=v2)
        proc = subprocess.run([sys.executable, "-c", src],
                              capture_output=True, timeout=120)
        killed = proc.returncode == -9
        # recovery: a fresh "process" opens the registry cold
        reg2 = ModelRegistry(root)
        active = reg2.active_version()
        expected = v1 if phase == "before_commit" else v2
        consistent = active == expected
        loadable = False
        digest_ok = False
        try:
            digest_ok = reg2.verify(active)
            booster = reg2.load(active)
            loadable = booster is not None and len(booster.trees) > 0
        except Exception as e:  # noqa: BLE001 - recorded as a failure
            results[phase] = {"error": repr(e)}
        verdict(verdicts, f"driver_kill_{phase}_recovers_consistent",
                killed and consistent and loadable and digest_ok,
                f"killed={killed}, active={active} "
                f"(expected {expected}), digest_ok={digest_ok}")
        results[phase] = {
            "child_killed": killed, "active_after_recovery": active,
            "expected_active": expected, "digest_verified": digest_ok,
            "loadable": loadable,
        }
    return results


def scenario_corrupted_entry(seed, verdicts):
    from mmlspark_tpu.io.chaos import ChaosPlan, corrupt_file
    from mmlspark_tpu.io.registry import (ModelCorruption,
                                          ModelRegistry, RegistryError)
    from mmlspark_tpu.io.rollout import RolloutConfig, RolloutController

    print("scenario D: corrupted registry entry quarantined")
    X, b1, b2, w1, w2 = build_models(seed + 3)
    results = {}
    plan = ChaosPlan(seed)
    for mode in ("bitflip", "torn"):
        root = tempfile.mkdtemp(prefix=f"chaos_rollout_d_{mode}_")
        reg = ModelRegistry(root)
        v1 = reg.publish(b1, activate=True)
        v2 = reg.publish(b2)
        corrupt_file(reg.model_path(v2), plan, mode=mode,
                     name=f"registry_{mode}")
        rejected = False
        try:
            reg.load(v2)
        except ModelCorruption:
            rejected = True
        quarantined = reg.entry(v2)["promoted_state"] == "quarantined"
        gate_refuses = False
        ctl = RolloutController(reg, config=RolloutConfig())
        try:
            ctl.start_canary(v2)
        except (ModelCorruption, RegistryError):
            gate_refuses = True
        baseline_ok = False
        try:
            baseline_ok = reg.load(v1) is not None and reg.verify(v1)
        except Exception:  # noqa: BLE001
            pass
        verdict(verdicts, f"corrupt_{mode}_rejected_by_digest",
                rejected and quarantined,
                f"state={reg.entry(v2)['promoted_state']}")
        verdict(verdicts, f"corrupt_{mode}_gate_refuses_canary",
                gate_refuses and ctl.state() == "steady")
        verdict(verdicts, f"corrupt_{mode}_active_version_unharmed",
                baseline_ok and reg.active_version() == v1)
        results[mode] = {"rejected": rejected,
                         "quarantined": quarantined,
                         "gate_refuses": gate_refuses,
                         "baseline_ok": baseline_ok}
    return results


def scenario_fleet_cutover(seed, verdicts):
    import numpy as np

    from mmlspark_tpu.io.fleet import PredictorFleet, ShardedPredictor
    from mmlspark_tpu.io.registry import ModelRegistry

    print("scenario E: fleet shard-consistent version cutover")
    X, b1, b2, w1f, w2f = build_models(seed + 4)
    Xs = X[:64]
    w1 = np.asarray(ShardedPredictor(b1, 2)(Xs), np.float32)
    w2 = np.asarray(ShardedPredictor(b2, 2)(Xs), np.float32)
    root = tempfile.mkdtemp(prefix="chaos_rollout_e_")
    reg = ModelRegistry(root)
    reg.publish(b1, activate=True)
    v2 = reg.publish(b2)
    fleet = PredictorFleet(b1, num_shards=2, spawn=False).start()
    results, mixed = [], 0
    stop = threading.Event()

    def loop():
        while not stop.is_set():
            try:
                results.append(np.asarray(fleet(Xs), np.float32))
            except Exception:  # noqa: BLE001 - counted via length
                break

    threads = [threading.Thread(target=loop, daemon=True)
               for _ in range(2)]
    try:
        parity_before = np.array_equal(
            np.asarray(fleet(Xs), np.float32), w1)
        ver = fleet.load_version(reg.model_path(v2))
        for t in threads:
            t.start()
        time.sleep(0.15)
        fleet.activate_version(ver)
        time.sleep(0.15)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        parity_after = np.array_equal(
            np.asarray(fleet(Xs), np.float32), w2)
        for r in results:
            if not (np.array_equal(r, w1) or np.array_equal(r, w2)):
                mixed += 1
    finally:
        stop.set()
        fleet.stop()
    verdict(verdicts, "fleet_cutover_bit_exact_both_sides",
            parity_before and parity_after)
    verdict(verdicts, "fleet_cutover_never_mixes_shard_versions",
            mixed == 0 and len(results) > 0,
            f"{len(results)} concurrent reduces, {mixed} mixed")
    return {"concurrent_reduces": len(results), "mixed": mixed,
            "model_file_from_registry": True}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="artifacts/chaos_rollout_r14.json")
    ap.add_argument("--seed", type=int, default=14)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from mmlspark_tpu.core.telemetry import host_info, record_flight

    t0 = time.time()
    verdicts = []
    scenarios = {}
    scenarios["healthy_promote"] = scenario_healthy_promote(
        args.seed, verdicts)
    scenarios["faulty_canary_rollback"] = scenario_faulty_canary(
        args.seed, verdicts)
    scenarios["driver_kill_mid_cutover"] = scenario_driver_kill(
        args.seed, verdicts)
    scenarios["corrupted_entry"] = scenario_corrupted_entry(
        args.seed, verdicts)
    scenarios["fleet_cutover"] = scenario_fleet_cutover(
        args.seed, verdicts)

    all_pass = all(v["pass"] for v in verdicts)
    artifact = {
        "run": "chaos_rollout",
        "round": 14,
        "seed": args.seed,
        "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "wall_s": round(time.time() - t0, 1),
        "host": host_info(),
        "scenarios": scenarios,
        "verdicts": verdicts,
        "all_pass": all_pass,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(artifact, fh, indent=1, default=str)
    print(f"\n{sum(v['pass'] for v in verdicts)}/{len(verdicts)} "
          f"verdicts pass → {args.out}")
    if not all_pass:
        record_flight("chaos_verdict_failure",
                      {"drill": "chaos_rollout",
                       "failed": [v["name"] for v in verdicts
                                  if not v["pass"]]})
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
