"""The fit's own phases as spans (ISSUE 25): ``Profiler.region`` (one
measurement, three faces: in-memory record, profiler annotation, phase
histogram), the spans every fit emits, and the named scopes its device
program carries."""

import glob
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core import telemetry
from mmlspark_tpu.core.profiler import Profiler, get_profiler
from mmlspark_tpu.gbdt import LightGBMClassifier, engine
from mmlspark_tpu.gbdt.binning import fit_bin_mapper
from mmlspark_tpu.gbdt.grower import GrowerConfig, make_feat_info
from mmlspark_tpu.gbdt.objectives import BinaryObjective, get_objective

ROOT = "train.fit"
#: span -> (how often a fit of one chunk emits it, the span it lies in)
FIT_SPANS = {
    "train.prepare": (1, ROOT), "train.label_stats": (1, "train.prepare"),
    "train.upload": (1, ROOT),
    "train.build_step": (1, ROOT), "train.launch": (1, ROOT),
    "train.upload_wait": (1, ROOT), "train.device_wait": (1, ROOT),
    "train.monitor": (1, ROOT),
    "train.fetch_trees": (1, ROOT),
    "train.fetch_wait": (1, "train.fetch_trees"),
    "train.finalize": (1, ROOT),
    "train.host_trees": (1, "train.finalize"),
    "train.booster": (1, "train.finalize"),
    "train.reference_profile": (1, ROOT),
    # the sampled rows are taken between the count pass's dispatch and
    # the wait for it: each of the two opens twice
    "train.refprofile_counts": (2, "train.reference_profile"),
    "train.refprofile_sample": (2, "train.reference_profile"),
    "train.refprofile_margins": (1, "train.reference_profile"),
    "train.refprofile_rollup": (1, "train.reference_profile"),
    "train.fit_attrs": (1, ROOT),
}
DEVICE_SCOPES = ("root_hist", "row_gather", "segment_hist", "partition",
                 "split_scan", "cache_update", "reduce", "score_update",
                 "gradients")


# ---------------------------------------------------------------- region


class TestRegion:
    def test_nesting_records_parent_and_closes_children_first(self):
        p = Profiler(enabled=True)
        with p.region("outer", rows=3) as attrs:
            with p.region("inner"):
                pass
            with p.region("inner"):
                pass
            attrs["trees"] = 2
        inner1, inner2, outer = p.spans()
        assert [s["name"] for s in (inner1, inner2, outer)] == \
            ["inner", "inner", "outer"]
        assert outer["parent"] is None
        assert inner1["parent"] == inner2["parent"] == outer["id"]
        assert len({inner1["id"], inner2["id"], outer["id"]}) == 3
        assert outer["attrs"] == {"rows": 3, "trees": 2}
        assert outer["start"] <= inner1["start"] <= inner1["end"] \
            <= inner2["start"] <= inner2["end"] <= outer["end"]

    def test_parent_is_per_thread(self):
        p = Profiler(enabled=True)
        inside = threading.Event()
        release = threading.Event()

        def other():
            with p.region("other.thread"):
                inside.set()
                assert release.wait(10)

        t = threading.Thread(target=other)
        t.start()
        assert inside.wait(10)
        with p.region("main.thread"):
            pass
        release.set()
        t.join(10)
        assert not t.is_alive()
        by_name = {s["name"]: s for s in p.spans()}
        # open at the same time, on two threads: neither is the other's
        assert by_name["main.thread"]["parent"] is None
        assert by_name["other.thread"]["parent"] is None

    def test_spans_of_one_fit_share_its_id(self):
        p = Profiler(enabled=True)
        telemetry.set_current_fit_span("feedface00000001")
        try:
            with p.region("a"):
                with p.region("b"):
                    pass
        finally:
            telemetry.set_current_fit_span(None)
        with p.region("c"):
            pass
        fits = {s["name"]: s["fit"] for s in p.spans()}
        assert fits == {"a": "feedface00000001", "b": "feedface00000001",
                        "c": None}

    def test_ring_is_bounded_and_spans_is_a_copy(self):
        p = Profiler(enabled=True)
        for i in range(p.SPAN_RING + 10):
            with p.region("r", i=i):
                pass
        spans = p.spans()
        assert len(spans) == p.SPAN_RING
        assert spans[-1]["attrs"]["i"] == p.SPAN_RING + 9   # newest kept
        assert spans[0]["attrs"]["i"] == 10                 # oldest gone
        spans.clear()
        assert len(p.spans()) == p.SPAN_RING

    def test_feeds_the_phase_histogram_and_the_snapshot(self):
        p = Profiler(enabled=True)
        n = p.SPAN_SNAPSHOT_TAIL + 3
        for i in range(n):
            with p.region("train.something", i=i):
                pass
        snap = p.snapshot()
        assert snap["phases"]["stages"]["train.something"]["count"] == n
        # the snapshot carries the newest few, JSON-able (flight records
        # embed it)
        assert [s["attrs"]["i"] for s in snap["spans"]] == \
            list(range(3, n))
        json.dumps(snap)

    def test_a_block_that_raises_is_still_recorded(self):
        p = Profiler(enabled=True)
        with pytest.raises(KeyError):
            with p.region("outer"):
                with p.region("fails"):
                    raise KeyError("x")
        assert [s["name"] for s in p.spans()] == ["fails", "outer"]
        with p.region("after"):
            pass
        assert p.spans()[-1]["parent"] is None    # the stack unwound

    def test_disabled_records_nothing_and_enters_no_annotation(
            self, monkeypatch):
        entered = []

        class Spy:
            def __init__(self, name):
                entered.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
        p = Profiler(enabled=False)
        with p.region("off", rows=1) as attrs:
            attrs["more"] = 2            # callers need no second path
        assert p.spans() == [] and entered == []
        assert p.snapshot()["phases"]["stages"] == {}
        p.configure(enabled=True)
        with p.region("on"):
            pass
        assert entered == ["on"] and len(p.spans()) == 1

    def test_phase_is_the_same_scoped_timer(self):
        p = Profiler(enabled=True)
        with p.phase("x.y"):
            pass
        assert p.spans()[0]["name"] == "x.y"

    def test_annotation_lies_in_the_profilers_trace(self, tmp_path):
        """Under ``jax.profiler.trace`` the region is in the
        ``.xplane.pb`` host plane under its own name, on the clock the
        device events have."""
        from jax.profiler import ProfileData
        p = Profiler(enabled=True)
        with jax.profiler.trace(str(tmp_path)):
            with p.region("train.region_under_test"):
                jnp.ones(8).block_until_ready()
        path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                          recursive=True)
        found = [(e.start_ns, e.duration_ns)
                 for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name == "train.region_under_test"]
        assert len(found) == 1
        span, = p.spans()
        # the same measurement: the two durations agree to a millisecond
        assert found[0][1] / 1e9 == pytest.approx(
            span["end"] - span["start"], abs=1e-3)

    def test_jax_seconds_reads_the_monitoring_sums(self):
        p = Profiler(enabled=True)
        assert p.jax_seconds("backend_compile") == 0.0
        p._on_jax_duration("/jax/core/compile/backend_compile_duration",
                           0.25)
        p._on_jax_duration("/jax/core/compile/backend_compile_duration",
                           0.5)
        assert p.jax_seconds("backend_compile") == pytest.approx(0.75)


# ------------------------------------------------------------- fit spans


def _table(n=60000, f=12, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    return X, y


@pytest.fixture(scope="module")
def fit_inputs():
    X, y = _table()
    # large enough that the phases, not the glue between them, are the fit
    est = LightGBMClassifier(numIterations=6, numLeaves=31, verbosity=0)
    mapper = fit_bin_mapper(X, max_bin=est.getMaxBin(), seed=est.getSeed())
    return {"bins": mapper.transform_packed(X),
            "labels": est._prepare_labels(y), "mapper": mapper,
            "objective": get_objective(est.getObjective(), num_class=1,
                                       **est._objective_kwargs()),
            "params": est._train_params()}


def _mesh4():
    from jax.sharding import Mesh
    from mmlspark_tpu.core.mesh import DATA_AXIS, FEATURE_AXIS
    return Mesh(np.asarray(jax.devices()[:4]).reshape(4, 1),
                (DATA_AXIS, FEATURE_AXIS))


def _fit(inp, mesh=None):
    """One ``engine.train`` call; returns (booster, its root, its other
    spans)."""
    prof = get_profiler()
    before = {s["id"] for s in prof.spans()}
    booster = engine.train(inp["bins"], inp["labels"], None, inp["mapper"],
                           inp["objective"], inp["params"], mesh=mesh)
    new = [s for s in prof.spans() if s["id"] not in before]
    root, = [s for s in new if s["name"] == "train.fit"]
    return booster, root, [s for s in new if s is not root]


@pytest.mark.parametrize("devices", [1, 4], ids=["serial", "mesh4"])
def test_fit_emits_every_span_once_inside_its_root(fit_inputs, devices):
    mesh = _mesh4() if devices == 4 else None
    _fit(fit_inputs, mesh)                   # warm: compiles are set-up
    # the children leave little of a fit unnamed; the best of three warm
    # fits, because the suite's other workers share these cores
    for _ in range(3):
        booster, root, spans = _fit(fit_inputs, mesh)
        covered = sum(s["end"] - s["start"] for s in spans
                      if s["parent"] == root["id"])
        if covered >= 0.95 * (root["end"] - root["start"]):
            break
    assert covered >= 0.95 * (root["end"] - root["start"])
    counts = {}
    for s in spans:
        counts[s["name"]] = counts.get(s["name"], 0) + 1
    assert counts == {name: n for name, (n, _) in FIT_SPANS.items()}
    by_id = {s["id"]: s for s in spans + [root]}
    for s in spans:
        parent = by_id[s["parent"]]
        assert parent["name"] == FIT_SPANS[s["name"]][1], s["name"]
        assert s["fit"] == root["fit"]
        assert parent["start"] <= s["start"] <= s["end"] <= parent["end"]
    assert root["fit"] is not None and root["parent"] is None
    # the capture, the export and the fetch are their children's: what
    # the children leave of each is under 20 ms a tree (ISSUE 35's rule
    # for the chip's cells, kept here at a size where it is easy)
    for name in ("train.reference_profile", "train.finalize",
                 "train.fetch_trees"):
        whole, = [s for s in spans if s["name"] == name]
        named = sum(s["end"] - s["start"] for s in spans
                    if s["parent"] == whole["id"])
        assert named > 0
        assert whole["end"] - whole["start"] - named < 0.020 * 6, name
    n, f = fit_inputs["bins"].shape
    a = root["attrs"]
    assert (a["trees"], a["rows"], a["features"], a["devices"]) == \
        (len(booster.trees), n, f, devices)
    by_name = {s["name"]: s for s in spans}
    assert by_name["train.upload"]["attrs"]["bytes"] >= n * f
    assert by_name["train.fetch_trees"]["attrs"]["bytes"] > 0
    assert by_name["train.reference_profile"]["attrs"]["rows"] == n
    assert by_name["train.device_wait"]["attrs"] == {"it": 0, "trees": 6}
    # the wait names the bytes the upload said went up, and the capture's
    # children where the rows were counted and how many features rolled up
    assert by_name["train.upload_wait"]["attrs"]["bytes"] == \
        by_name["train.upload"]["attrs"]["bytes"]
    assert by_name["train.prepare"]["attrs"] == {"rows": n}
    assert {s["attrs"]["where"] for s in spans
            if s["name"] == "train.refprofile_counts"} == {"device"}
    # one group a ladder length (categorical features one of their own)
    mapper = fit_inputs["mapper"]
    ladders = {-1 if mapper.is_categorical(j) else len(ub)
               for j, ub in enumerate(mapper.upper_bounds)}
    assert by_name["train.refprofile_rollup"]["attrs"] == \
        {"features": f, "ladders": len(ladders)}
    assert by_name["train.host_trees"]["attrs"] == {"trees": 6}
    if devices == 1:
        assert (a["collective_count"], a["collective_bytes"]) == (0, 0)
        assert by_name["train.launch"]["attrs"]["compile_misses"] == 0
        assert by_name["train.monitor"]["attrs"]["loss_rows"] == n
    else:
        # one psum of the histograms per split, and the counts' psums
        assert a["collective_count"] >= 6 * (31 - 1)
        assert a["collective_bytes"] >= 6 * 30 * f * 256 * 3 * 4


def _one_hot_table(n=4000, groups=3, width=6, seed=1):
    """Mutually exclusive columns, which EFB bundles, and one dense."""
    rng = np.random.default_rng(seed)
    X = np.zeros((n, groups * width + 1), np.float32)
    for g in range(groups):
        X[np.arange(n), g * width + rng.integers(0, width, n)] = 1.0
    X[:, -1] = rng.normal(size=n)
    return X, (X[:, 0] + X[:, -1] > 0.5).astype(np.float32)


@pytest.mark.parametrize("case,counts", [
    ("serial", "device"), ("mesh4", "device"), ("bundled", "device"),
    ("incremental", "host")])
def test_reference_profile_says_where_its_rows_were_counted(
        fit_inputs, case, counts):
    """``train.reference_profile``'s ``counts``: ``device`` where the fit
    left its table on the device (bundle columns too: their counts
    are expanded to features), ``host`` for a capture that is no fit's
    own (the merged forest's, after ``train_incremental``'s fit)."""
    inp = fit_inputs
    stats0 = engine.train_stats.snapshot()["counters"]
    if case == "bundled":
        X, y = _one_hot_table()
        est = LightGBMClassifier(numIterations=3, numLeaves=7,
                                 verbosity=0, enableBundle=True)
        mapper = fit_bin_mapper(X, max_bin=est.getMaxBin(),
                                seed=est.getSeed())
        from mmlspark_tpu.gbdt.efb import bundle_for_training
        dense = mapper.transform_packed(X)
        inp = dict(fit_inputs, bins=bundle_for_training(dense, mapper),
                   labels=est._prepare_labels(y), mapper=mapper,
                   params=est._train_params())
    prof = get_profiler()
    before = {s["id"] for s in prof.spans()}
    if case == "incremental":
        base = engine.train(inp["bins"], inp["labels"], None, inp["mapper"],
                            inp["objective"], inp["params"])
        before = {s["id"] for s in prof.spans()}
        booster = engine.train_incremental(
            inp["bins"], inp["labels"], inp["mapper"], init_booster=base,
            objective=inp["objective"], params=inp["params"])
    else:
        booster = engine.train(
            inp["bins"], inp["labels"], None, inp["mapper"],
            inp["objective"], inp["params"],
            mesh=_mesh4() if case == "mesh4" else None)
    new = [s for s in prof.spans() if s["id"] not in before]
    if case == "bundled":
        # fewer bytes than the table has cells: the bundles went up
        upload, = [s for s in new if s["name"] == "train.upload"]
        assert upload["attrs"]["bytes"] < dense.size
        # the profile of the bundled table is the unbundled table's,
        # which the host counts column by column
        from mmlspark_tpu.core.sketch import build_reference_profile
        host = build_reference_profile(dense, mapper)
        assert booster.reference_profile.feature_sketches \
            == host.feature_sketches
    spans = [s for s in new if s["name"] == "train.reference_profile"]
    # the incremental fit's own capture, then the merged forest's
    assert [s["attrs"]["counts"] for s in spans] == \
        (["device", "host"] if case == "incremental" else [counts])
    assert all(s["attrs"]["rows"] == inp["bins"].shape[0] for s in spans)
    assert booster.reference_profile is not None
    stats = engine.train_stats.snapshot()["counters"]
    done = {k: stats[k] - stats0[k]
            for k in ("ref_profiles", "ref_profiles_device")}
    assert done == {
        "serial": {"ref_profiles": 1, "ref_profiles_device": 1},
        "mesh4": {"ref_profiles": 1, "ref_profiles_device": 1},
        "bundled": {"ref_profiles": 1, "ref_profiles_device": 1},
        "incremental": {"ref_profiles": 3, "ref_profiles_device": 2},
    }[case]


@pytest.mark.parametrize("method,build", [("auto", "native"),
                                          ("dot16", "dot16/xla")])
def test_fit_names_the_histogram_build_it_compiled(fit_inputs, method,
                                                   build):
    """``hist_build`` / ``hist_build_rungs`` on ``train.fit`` and in
    ``last_fit_info``: the implementation the fit's programs compiled,
    and at how many of a tree's call sites (the root, each bucket rung
    and the chunk loop) the one-hot product stays on the chip: none on
    the CPU."""
    from mmlspark_tpu.gbdt.grower import GrowerConfig, _build_sizes
    params = fit_inputs["params"].__class__(
        **{**fit_inputs["params"].__dict__, "histogram_method": method})
    _, root, _ = _fit({**fit_inputs, "params": params})
    n = fit_inputs["bins"].shape[0]
    sizes = _build_sizes(n, GrowerConfig())
    sites = 1 + len(sizes) + (n > sizes[-1])
    a = root["attrs"]
    assert (a["hist_build"], a["hist_build_rungs"]) == (build, f"0/{sites}")
    assert (engine.last_fit_info["hist_build"],
            engine.last_fit_info["hist_build_rungs"]) == (build,
                                                          f"0/{sites}")


@pytest.mark.parametrize("case,want", [
    # root + rungs 2^11..2^16 + the chunk loop (2^11..2^19 before PR 34)
    ("epsilon", ("dot16/mosaic", 8, 8)),
    ("epsilon a chip of four", ("dot16/mosaic", 8, 8)),
    ("masked", ("dot16/mosaic", 1, 1)),         # every split a full pass
    ("quantized", ("dot16/xla", 0, 8)),         # int32 kernel is refused
    ("bundles over 256 bins", ("dot16/xla", 0, 8)),
    ("another method", ("segment", 0, 8)),
])
def test_hist_build_schedule_counts_the_fused_call_sites(monkeypatch, case,
                                                         want):
    """What the two attrs are made from, as the TPU decides it."""
    import mmlspark_tpu.ops.histogram as H
    from mmlspark_tpu.gbdt.grower import GrowerConfig, hist_build_schedule
    monkeypatch.setattr(H.jax, "default_backend", lambda: "tpu")
    cfg = GrowerConfig(num_leaves=255, num_bins=255)
    n = 400_000
    if case == "epsilon a chip of four":
        n = 100_000
    elif case == "masked":
        cfg = cfg.__class__(**{**cfg.__dict__, "compact_rows": False})
    elif case == "quantized":
        cfg = cfg.__class__(**{**cfg.__dict__, "quantized_bits": 8,
                               "quantized_max_code": 127})
    elif case == "bundles over 256 bins":
        cfg = cfg.__class__(**{**cfg.__dict__, "num_bins": 400})
    elif case == "another method":
        cfg = cfg.__class__(**{**cfg.__dict__, "hist_method": "segment"})
    got = hist_build_schedule(cfg, n)
    assert (got["build"], got["fused"], got["sites"]) == want


@pytest.mark.parametrize("devices", [1, 4], ids=["serial", "mesh4"])
def test_fit_counts_the_rows_its_segments_walked(fit_inputs, devices):
    """``seg_rows`` / ``seg_rows_walked`` / ``seg_chunked_nodes`` on
    ``train.fit``: a one-device fit's splits against the bucket ladder,
    from the returned trees' node counts; a mesh fit knows no shard's
    counts on the host and says nothing."""
    from mmlspark_tpu.gbdt.grower import GrowerConfig
    booster, root, _ = _fit(fit_inputs, _mesh4() if devices == 4 else None)
    a = root["attrs"]
    seg = {k: v for k, v in a.items() if k.startswith("seg_")}
    if devices == 4:
        assert seg == {}
        return
    assert sorted(seg) == ["seg_chunked_nodes", "seg_rows",
                           "seg_rows_walked"]
    n = fit_inputs["bins"].shape[0]
    # every tree's root is partitioned whole and every row lands in one
    # smaller child at most once a level
    assert seg["seg_rows"] >= len(booster.trees) * n
    assert seg["seg_rows_walked"] >= seg["seg_rows"]
    assert seg["seg_chunked_nodes"] == 0      # 60 000 rows fit a rung
    # the attrs are sums over the trees, and a tree's root is the first
    # segment it partitions
    per_tree = [engine._segment_walk_attrs([t], n, GrowerConfig().min_bucket)
                for t in booster.trees]
    assert sum(p["seg_rows"] for p in per_tree) == seg["seg_rows"]
    assert all(p["seg_rows"] >= t.internal_count[0] == n
               for p, t in zip(per_tree, booster.trees))


@pytest.mark.parametrize("devices", [1, 4], ids=["serial", "mesh4"])
def test_chunked_fit_emits_launch_wait_monitor_per_chunk(fit_inputs,
                                                         devices):
    calls = []
    prof = get_profiler()
    before = {s["id"] for s in prof.spans()}
    engine.train(fit_inputs["bins"], fit_inputs["labels"], None,
                 fit_inputs["mapper"], fit_inputs["objective"],
                 fit_inputs["params"].__class__(
                     **{**fit_inputs["params"].__dict__,
                        "num_iterations": 10}),
                 callbacks=[lambda it, trees: calls.append(it)],
                 mesh=_mesh4() if devices == 4 else None)
    new = sorted((s for s in prof.spans() if s["id"] not in before),
                 key=lambda s: s["start"])
    names = [s["name"] for s in new]
    assert len(calls) == 10
    # callbacks bound the chunk at 8 iterations: chunks of 8 and 2
    for per_chunk in ("train.launch", "train.device_wait",
                      "train.monitor"):
        assert names.count(per_chunk) == 2
    for per_fit in ("train.fit", "train.prepare", "train.upload",
                    "train.upload_wait", "train.fetch_trees",
                    "train.finalize", "train.reference_profile",
                    "train.fit_attrs"):
        assert names.count(per_fit) == 1
    # the table is waited for once, by the fit's first chunk: after its
    # launch has returned, before its device wait opens
    waits = [n for n in names if n in ("train.launch", "train.upload_wait",
                                       "train.device_wait")]
    assert waits == ["train.launch", "train.upload_wait",
                     "train.device_wait", "train.launch",
                     "train.device_wait"]
    launch, upload_wait, device_wait = [
        s for s in new if s["name"] in waits][:3]
    assert launch["end"] <= upload_wait["start"] \
        <= upload_wait["end"] <= device_wait["start"]


def test_a_categorical_fits_bitsets_lie_in_host_trees():
    """``train.cat_bitsets`` (one a tree with a categorical split) is a
    child of ``train.host_trees``, which is ``train.finalize``'s."""
    rng = np.random.default_rng(3)
    n = 4000
    cat = rng.integers(0, 12, n)
    X = np.column_stack([cat, rng.normal(size=n)]).astype(np.float32)
    y = (np.isin(cat, (1, 4, 7)) ^ (X[:, 1] > 1.0)).astype(np.float32)
    est = LightGBMClassifier(numIterations=3, numLeaves=7, verbosity=0,
                             categoricalSlotIndexes=[0])
    mapper = fit_bin_mapper(X, max_bin=est.getMaxBin(), seed=est.getSeed(),
                            categorical_features=[0])
    prof = get_profiler()
    before = {s["id"] for s in prof.spans()}
    booster = engine.train(
        mapper.transform_packed(X), est._prepare_labels(y), None, mapper,
        get_objective(est.getObjective(), num_class=1,
                      **est._objective_kwargs()), est._train_params())
    new = {s["id"]: s for s in prof.spans() if s["id"] not in before}
    bitsets = [s for s in new.values() if s["name"] == "train.cat_bitsets"]
    assert len(bitsets) == sum(t.num_cat > 0 for t in booster.trees) > 0
    for s in bitsets:
        host_trees = new[s["parent"]]
        assert host_trees["name"] == "train.host_trees"
        assert new[host_trees["parent"]]["name"] == "train.finalize"


def test_launch_counts_its_own_compiles():
    """``compile_misses`` is the ``compile_seq`` delta over the launch,
    and the seconds beside it are the monitoring sums' deltas."""
    prof = get_profiler()

    @jax.jit
    def fresh(x):                     # never compiled before this test
        return jnp.cos(x) * 3.25 + 1.5

    def run(scores, val_scores):
        return fresh(scores), scores, val_scores, None

    x = jnp.arange(16.0)
    for want in (1, 0):               # a miss, then a hit
        seq0 = prof.compile_seq()
        before = {s["id"] for s in prof.spans()}
        engine._dispatch_chunk(run, x, x, 0, 1, 0.0)
        launch, wait = [s for s in prof.spans() if s["id"] not in before]
        assert (launch["name"], wait["name"]) == \
            ("train.launch", "train.device_wait")
        assert launch["attrs"]["compile_misses"] == \
            prof.compile_seq() - seq0 == want
        assert (launch["attrs"]["backend_compile_s"] > 0) == bool(want)
        assert launch["attrs"]["jaxpr_trace_s"] >= 0


def test_disabled_profiler_a_fit_records_no_span(fit_inputs, monkeypatch):
    prof = get_profiler()
    entered = []
    real = jax.profiler.TraceAnnotation

    def spy(name, **kw):
        entered.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", spy)
    waited = []
    real_wait = jax.block_until_ready

    def wait_spy(x):
        waited.append(x)
        return real_wait(x)

    def fit():
        return engine.train(
            fit_inputs["bins"], fit_inputs["labels"], None,
            fit_inputs["mapper"], fit_inputs["objective"],
            fit_inputs["params"])

    monkeypatch.setattr(engine.jax, "block_until_ready", wait_spy)
    before = len(prof.spans())
    prof.configure(enabled=False)
    try:
        booster = fit()
    finally:
        prof.configure(enabled=True)
    assert len(booster.trees) == 6
    assert len(prof.spans()) == before
    assert [n for n in entered if n.startswith("train.")] == []
    # one wait a chunk, for its trees: the table's is the profiler's
    assert len(waited) == 1
    del waited[:]
    enabled = fit()
    assert len(waited) == 2
    # the spans observe the fit, they are no part of it
    assert enabled.save_native_model_string() == \
        booster.save_native_model_string()


# ------------------------------------------------------- idle by span


#: two fits on one device's clock; in the first the table arrives while
#: ``train.upload_wait`` is open and the device then works under
#: ``train.device_wait`` (3.5 .. 7.5 s), the capture's count pass runs at
#: 8.2 .. 8.6 s inside ``train.refprofile_counts``
HAND_ANNOTATIONS = [
    ("train.fit", 0.0, 10.0),
    ("train.prepare", 0.0, 1.0),
    ("train.upload", 1.0, 2.0),
    ("train.launch", 2.0, 2.5),
    ("train.upload_wait", 2.5, 3.5),
    ("train.device_wait", 3.5, 7.5),
    ("train.reference_profile", 8.0, 9.5),
    ("train.refprofile_counts", 8.0, 8.75),
    ("train.refprofile_rollup", 9.0, 9.5),
    ("train.fit", 12.0, 20.0),
    ("train.device_wait", 13.0, 19.0),
    # a span of another thread that outlives the trace's last fit
    ("bin.bundle_plan", 19.5, 25.0),
]
HAND_EVENTS = [
    (3.5, 5.0), (4.0, 7.25),            # nested: a loop and its body
    (8.25, 8.5),                        # the count pass
    (10.5, 11.0),                       # between the fits
    (12.5, 18.0),                       # enqueued before the wait opened
    (30.0, 31.0),                       # after the last fit: not counted
]


def test_idle_is_charged_to_the_innermost_open_span():
    from mmlspark_tpu.core.profiling import charge_idle
    got = dict((name, secs) for secs, name in
               charge_idle(HAND_EVENTS, HAND_ANNOTATIONS))
    assert got == pytest.approx({
        "train.prepare": 1.0, "train.upload": 1.0, "train.launch": 0.5,
        "train.upload_wait": 1.0,
        # the drain after the last op, 7.25 .. 7.5
        "train.device_wait": 0.25 + 1.0,
        # a gap cut at span boundaries: 7.5 .. 8.25 is the fit's until
        # the capture opens at 8.0, then the count pass's dispatch
        "train.refprofile_counts": 0.25 + 0.25,
        "train.reference_profile": 0.25,
        "train.refprofile_rollup": 0.5,
        "train.fit": 0.5 + 0.5 + 0.5 + 0.5,
        # 10 .. 12 outside any fit, less the op that ran there
        "between fits": 1.5,
        "bin.bundle_plan": 0.5,
    })
    # largest first, and nothing outside the fits' extent
    rows = charge_idle(HAND_EVENTS, HAND_ANNOTATIONS)
    assert [secs for secs, _ in rows] == sorted(
        (secs for secs, _ in rows), reverse=True)
    busy = (7.25 - 3.5) + 0.25 + 0.5 + 5.5
    assert sum(secs for secs, _ in rows) == pytest.approx(20.0 - busy)


@pytest.mark.parametrize("case", ["no fit", "no events", "unsorted"])
def test_idle_by_span_on_the_edges(case):
    from mmlspark_tpu.core.profiling import charge_idle
    if case == "no fit":
        assert charge_idle(HAND_EVENTS, [("train.upload", 0.0, 1.0)]) == []
    elif case == "no events":
        got = dict((n, s) for s, n in charge_idle(
            [], [("train.fit", 1.0, 3.0), ("train.upload", 1.0, 2.0)]))
        assert got == pytest.approx({"train.upload": 1.0, "train.fit": 1.0})
    else:
        shuffled = list(reversed(HAND_EVENTS))
        assert charge_idle(shuffled, HAND_ANNOTATIONS) == \
            charge_idle(HAND_EVENTS, HAND_ANNOTATIONS)


def test_idle_by_span_reads_a_fits_own_trace(fit_inputs, tmp_path):
    """On a real trace (the CPU's: its host threads' HLO-op events stand
    in for the device) the rows are the fit's own spans and add up to
    the fit's seconds less the busy union."""
    from mmlspark_tpu.core.profiling import idle_by_span, trace_tables
    assert idle_by_span(str(tmp_path)) == []
    _fit(fit_inputs)                          # compiles are no part of it
    with jax.profiler.trace(str(tmp_path)):
        _, root, spans = _fit(fit_inputs)
    rows = idle_by_span(str(tmp_path))
    total_ms, last = rows[-1]
    assert last == "total_idle_ms"
    assert total_ms == pytest.approx(sum(ms for ms, _ in rows[:-1]),
                                     abs=1e-2)
    names = {name for _, name in rows[:-1]}
    assert names <= {ROOT, *FIT_SPANS}
    # the host parts of a fit do not run on the device
    assert {"train.label_stats", "train.refprofile_rollup"} <= names
    fit_ms = (root["end"] - root["start"]) * 1e3
    assert 0 < total_ms <= fit_ms * 1.001
    # a host span with no device work in it is idle for all it lasts
    by_name = dict((name, ms) for ms, name in rows[:-1])
    rollup, = [s for s in spans if s["name"] == "train.refprofile_rollup"]
    assert by_name["train.refprofile_rollup"] == pytest.approx(
        (rollup["end"] - rollup["start"]) * 1e3, rel=0.05, abs=0.5)
    text = trace_tables(str(tmp_path))
    assert "idle device time by the host's span" in text
    assert "train.prepare" in text and "total_idle_ms" in text


# ---------------------------------------------------------- device scopes


@pytest.fixture(scope="module")
def lowered_op_names():
    """``op_name`` metadata of the compiled serial scan and of the mesh
    scan's lowering (the mesh one carries the collectives)."""
    import re
    n, f = 2000, 8
    obj = BinaryObjective()
    obj.prepare(np.zeros(n), np.ones(n))
    cfg = GrowerConfig(num_leaves=7, num_bins=256, hist_method="segment")
    fis = jnp.asarray(np.broadcast_to(make_feat_info(f), (2, f, 3)))
    args = (jnp.zeros((n, f), jnp.uint8), jnp.zeros(n), jnp.zeros(n),
            jnp.ones(n), jnp.ones((2, 1)), fis,
            jnp.zeros((1, f), jnp.uint8), jnp.zeros(1))
    serial = engine._boost_scan.lower(
        *args, obj=obj, cfg=cfg, lr=0.1, has_val=False).compile().as_text()
    from mmlspark_tpu.gbdt.distributed import (make_boost_scan,
                                               prepare_arrays)
    mesh = _mesh4()
    step = make_boost_scan(mesh, obj, cfg, 0.1, bag_sharded=False)
    bins_d, lab_d, w_d, real_d, scores, _, _ = prepare_arrays(
        np.zeros((n, f), np.uint8), np.zeros(n, np.float32),
        np.ones(n, np.float32), mesh, 1, 0.0)
    dn = 4
    from jax.sharding import NamedSharding, PartitionSpec as P
    vb = jax.device_put(jnp.zeros((dn, f), jnp.uint8),
                        NamedSharding(mesh, P("data", None)))
    vs = jax.device_put(jnp.zeros(dn), NamedSharding(mesh, P("data")))
    meshed = step.lower(bins_d, scores, lab_d, w_d, real_d,
                        jnp.ones((2, 1)), fis, vb, vs).compile().as_text()
    pat = re.compile(r'op_name="([^"]+)"')
    return {"serial": pat.findall(serial), "mesh": pat.findall(meshed)}


def test_a_rebuilt_mesh_step_lowers_to_the_same_program():
    """A step built anew (a fit with bundles, an entry the step table
    dropped, the next process) is traced anew; what it lowers to must
    not depend on how many programs the process traced before (a
    checkify error number did), or the persistent compile cache misses
    on every such fit."""
    from mmlspark_tpu.gbdt import distributed
    from mmlspark_tpu.gbdt.distributed import prepare_arrays
    # the builder itself, under the table that would hand the same step
    # back three times
    make_boost_scan = distributed.make_boost_scan.__wrapped__
    from jax.sharding import NamedSharding, PartitionSpec as P
    n, f = 1000, 6
    obj = BinaryObjective()
    obj.prepare(np.zeros(n), np.ones(n))
    cfg = GrowerConfig(num_leaves=5, num_bins=256, hist_method="segment")
    mesh = _mesh4()
    fis = jnp.asarray(np.broadcast_to(make_feat_info(f), (1, f, 3)))
    bins_d, lab_d, w_d, real_d, scores, _, _ = prepare_arrays(
        np.zeros((n, f), np.uint8), np.zeros(n, np.float32),
        np.ones(n, np.float32), mesh, 1, 0.0)
    vb = jax.device_put(jnp.zeros((4, f), jnp.uint8),
                        NamedSharding(mesh, P("data", None)))
    vs = jax.device_put(jnp.zeros(4), NamedSharding(mesh, P("data")))
    texts = set()
    for _ in range(3):
        step = make_boost_scan(mesh, obj, cfg, 0.1, bag_sharded=False)
        texts.add(step.lower(bins_d, scores, lab_d, w_d, real_d,
                             jnp.ones((1, 1)), fis, vb, vs).as_text())
    assert len(texts) == 1
    # and the sanitizer's checks are in when the config asks for them
    import dataclasses
    checked = make_boost_scan(
        mesh, obj, dataclasses.replace(cfg, debug_checks=True), 0.1,
        bag_sharded=False)
    assert checked.lower(bins_d, scores, lab_d, w_d, real_d,
                         jnp.ones((1, 1)), fis, vb, vs).as_text() \
        not in texts


@pytest.mark.parametrize("scope", DEVICE_SCOPES)
def test_device_scope_is_in_the_compiled_steps_metadata(lowered_op_names,
                                                        scope):
    from mmlspark_tpu.core.profiling import scope_of
    where = "mesh" if scope == "reduce" else "serial"
    scopes = {scope_of(name) for name in lowered_op_names[where]}
    assert any(scope in s.split("/") for s in scopes), sorted(scopes)
    if scope == "reduce":
        # the collectives carry it, nested in the stage that reduces
        assert "root_hist/reduce" in scopes
