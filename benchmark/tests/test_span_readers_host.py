"""The readers of the host half's spans against a hand-made span list: a
warm-up fit and two fits of the window, 2 trees each, with the children
and grandchildren a fit records since the spans got names (the wait for
the table, the work before the upload, the parts of the capture and of
the export)."""

import importlib
import types

import pytest

#: (name, parent's name, seconds), in the order a fit opens them; a child
#: starts where the one before it ended, inside its parent
TREE = (
    ("train.prepare", "train.fit", 0.4),
    ("train.upload", "train.fit", 0.5),
    ("train.launch", "train.fit", 0.01),
    ("train.upload_wait", "train.fit", 1.5),
    ("train.device_wait", "train.fit", 6.0),
    ("train.fetch_trees", "train.fit", 0.3),
    ("train.fetch_wait", "train.fetch_trees", 0.25),
    ("train.finalize", "train.fit", 0.6),
    ("train.host_trees", "train.finalize", 0.5),
    ("train.cat_bitsets", "train.host_trees", 0.2),
    ("train.cat_bitsets", "train.host_trees", 0.2),
    ("train.booster", "train.finalize", 0.05),
    ("train.reference_profile", "train.fit", 0.9),
    ("train.refprofile_counts", "train.reference_profile", 0.01),
    ("train.refprofile_sample", "train.reference_profile", 0.09),
    ("train.refprofile_counts", "train.reference_profile", 0.1),
    ("train.refprofile_sample", "train.reference_profile", 0.2),
    ("train.refprofile_margins", "train.reference_profile", 0.15),
    ("train.refprofile_rollup", "train.reference_profile", 0.3),
    ("train.fit_attrs", "train.fit", 0.1),
)
FIT_S = sum(took for _, parent, took in TREE if parent == "train.fit") + 0.2


def fit(first_id, t0, scale=1.0, leave_out=()):
    """One fit's spans, the root last (it closes last); a span's children
    fill it from its start, and the root ends 0.2 s after its last."""
    spans, ids, cursor = [], {"train.fit": first_id}, {first_id: t0}
    for i, (name, parent, took) in enumerate(TREE, start=1):
        if name in leave_out or parent not in ids:
            continue
        pid = ids[parent]
        start = cursor[pid]
        ids[name] = first_id + i
        cursor[pid] = cursor[first_id + i] = start
        cursor[pid] += took * scale
        spans.append({"id": first_id + i, "name": name, "start": start,
                      "end": start + took * scale, "parent": pid,
                      "fit": f"f{first_id}", "attrs": {}})
    spans.append({"id": first_id, "name": "train.fit", "start": t0,
                  "end": t0 + FIT_S * scale, "parent": None,
                  "fit": f"f{first_id}", "attrs": {"trees": 2}})
    return spans


class Profiler:
    def __init__(self, spans):
        self._spans = spans

    def spans(self):
        return list(self._spans)


def run_of(spans, fits=2, trees=4, window_s=2 * FIT_S + 1.0):
    return types.SimpleNamespace(
        state={"profiler": Profiler(spans)},
        work={"fits": fits, "trees": trees, "window_s": window_s})


def read(name, run):
    return importlib.import_module("benchmark.metrics." + name).read(run)


@pytest.fixture
def spans():
    # the warm-up is ten times as slow: a reader that took it in would
    # read ten times too much
    return fit(100, 0.0, scale=10.0) + fit(200, 200.0) + fit(300, 300.0)


# the window's two fits over 4 trees
EXPECT = {
    "fit_upload_wait_ms_per_tree": 2 * 1.5 / 4 * 1e3,
    "fit_device_wait_ms_per_tree": 2 * 6.0 / 4 * 1e3,
    "fit_prepare_ms_per_tree": 2 * (0.4 + 0.1) / 4 * 1e3,
    "refprofile_counts_ms_per_tree": 2 * (0.01 + 0.1) / 4 * 1e3,
    "refprofile_sample_ms_per_tree": 2 * (0.09 + 0.2 + 0.15) / 4 * 1e3,
    "refprofile_rollup_ms_per_tree": 2 * 0.3 / 4 * 1e3,
    "fit_host_trees_ms_per_tree": 2 * (0.5 + 0.05) / 4 * 1e3,
}
#: a span each reader reads; left out, the reader has nothing
ONE_OF = {
    "fit_upload_wait_ms_per_tree": ("train.upload_wait",),
    "fit_device_wait_ms_per_tree": ("train.device_wait",),
    "fit_prepare_ms_per_tree": ("train.prepare", "train.fit_attrs"),
    "refprofile_counts_ms_per_tree": ("train.refprofile_counts",),
    "refprofile_sample_ms_per_tree": ("train.refprofile_sample",
                                      "train.refprofile_margins"),
    "refprofile_rollup_ms_per_tree": ("train.refprofile_rollup",),
    "fit_host_trees_ms_per_tree": ("train.host_trees", "train.booster"),
}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_sums_its_spans_over_the_windows_fits(name, spans):
    assert read(name, run_of(spans)) == pytest.approx(EXPECT[name])


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_has_nothing_where_the_spans_are_not(name, spans):
    # the parent of the PR that named them: fits without these spans
    old = fit(1, 0.0, leave_out=ONE_OF[name]) \
        + fit(50, 50.0, leave_out=ONE_OF[name])
    assert read(name, run_of(old)) is None
    # a profiler without spans, no profiler, too few fits, an empty window
    no_spans = types.SimpleNamespace(
        state={"profiler": object()},
        work={"fits": 2, "trees": 4, "window_s": 23.0})
    assert read(name, no_spans) is None
    assert read(name, types.SimpleNamespace(
        state={}, work={"fits": 2, "trees": 4, "window_s": 23.0})) is None
    assert read(name, run_of(spans, fits=4)) is None
    assert read(name, run_of(spans, fits=0, trees=0)) is None


def test_the_old_readers_keep_reading_what_they_read(spans):
    """The parents of the new spans are read whole, children and all."""
    run = run_of(spans)
    assert read("fit_fetch_ms_per_tree", run) == \
        pytest.approx(2 * (0.3 + 0.6) / 4 * 1e3)
    assert read("fit_refprofile_ms_per_tree", run) == \
        pytest.approx(2 * 0.9 / 4 * 1e3)
    assert read("cat_bitsets_ms_per_tree", run) == \
        pytest.approx(2 * 0.4 / 4 * 1e3)
    # the roots' self time is the 0.2 s no child covers, and 1 s lies
    # between the fits: a grandchild takes nothing from it twice
    assert read("fit_unattributed_ms_per_tree", run) == \
        pytest.approx((2 * 0.2 + 1.0) / 4 * 1e3)


def test_named_children_leave_their_parents_little_self_time(spans):
    """Rule (e) of the issue on the hand-made fit: what a parent's
    children do not cover is its self time."""
    from benchmark.lib.spans import self_seconds, window_fits
    _, inside = window_fits(spans, 2)
    own = {}
    for s in inside:
        if s["name"] in ("train.reference_profile", "train.finalize",
                         "train.fetch_trees"):
            own[s["name"]] = own.get(s["name"], 0.0) + self_seconds(s, inside)
    assert own == pytest.approx({
        "train.reference_profile": 2 * 0.05, "train.finalize": 2 * 0.05,
        "train.fetch_trees": 2 * 0.05})
