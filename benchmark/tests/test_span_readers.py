"""Every span reader against a hand-made span list: a warm-up fit and
two fits of the window, 2 trees each, in a window of 23 s."""

import importlib
import types

import pytest

PHASES = ("train.upload", "train.build_step", "train.launch",
          "train.device_wait", "train.monitor", "train.fetch_trees",
          "train.finalize", "train.reference_profile")


def fit(first_id, t0, scale=1.0, leave_out=(), attrs=None):
    """One fit's spans as the program records them (children close, and
    so are listed, before their root).  Durations, in order of PHASES:
    1, .25, .5, 5, .5, .25, .25, 2 s, with .05 s between phases, times
    ``scale``; the root ends .2 s after the last child."""
    spans, t, i = [], t0 + 0.05 * scale, first_id + 1
    for name, took in zip(PHASES, (1, .25, .5, 5, .5, .25, .25, 2)):
        if name not in leave_out:
            spans.append({"id": i, "name": name, "start": t,
                          "end": t + took * scale, "parent": first_id,
                          "fit": f"f{first_id}", "attrs": {}})
            i += 1
        t += (took + 0.05) * scale
    spans.append({"id": first_id, "name": "train.fit", "start": t0,
                  "end": t + 0.15 * scale, "parent": None,
                  "fit": f"f{first_id}",
                  "attrs": dict(attrs or {}, trees=2)})
    return spans


class Profiler:
    def __init__(self, spans):
        self._spans = spans

    def spans(self):
        return list(self._spans)


def run_of(spans, fits=2, trees=4, window_s=23.0):
    return types.SimpleNamespace(
        state={"profiler": Profiler(spans)},
        work={"fits": fits, "trees": trees, "window_s": window_s})


def read(name, run):
    return importlib.import_module("benchmark.metrics." + name).read(run)


@pytest.fixture
def spans():
    coll = {"collective_bytes": 3000, "collective_count": 510}
    # the warm-up is ten times as slow: a reader that took it in would
    # read ten times too much
    return (fit(100, 0.0, scale=10.0, attrs=coll)
            + fit(200, 200.0, attrs=coll)
            # a grandchild, listed before its parent closes
            + [{"id": 399, "name": "train.launch", "start": 311.4,
                "end": 311.5, "parent": 302, "fit": "f300", "attrs": {}}]
            + fit(300, 310.0, attrs=coll))


# one fit: children 9.75 s, gaps 8 x .05 + .15 = .55 s, root 10.3 s;
# the window's two fits: 2 x each phase over 4 trees
EXPECT = {
    "fit_upload_ms_per_tree": 2 * 1.0 / 4 * 1e3,
    "fit_launch_ms_per_tree": (2 * (0.25 + 0.5) + 0.1) / 4 * 1e3,
    "fit_monitor_ms_per_tree": 2 * 0.5 / 4 * 1e3,
    "fit_fetch_ms_per_tree": 2 * (0.25 + 0.25) / 4 * 1e3,
    "fit_refprofile_ms_per_tree": 2 * 2.0 / 4 * 1e3,
    # self time 2 x .55 s, and 23 - 2 x 10.3 = 2.4 s between the fits
    "fit_unattributed_ms_per_tree": (2 * 0.55 + 2.4) / 4 * 1e3,
    "collective_bytes_per_tree": 2 * 3000 / 4,
    "collective_count_per_tree": 2 * 510 / 4,
}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_takes_the_windows_fits_only(name, spans):
    assert read(name, run_of(spans)) == pytest.approx(EXPECT[name])


@pytest.mark.parametrize("name,phase", [
    ("fit_upload_ms_per_tree", "train.upload"),
    ("fit_monitor_ms_per_tree", "train.monitor"),
    ("fit_refprofile_ms_per_tree", "train.reference_profile"),
])
def test_missing_span_reads_none(name, phase):
    spans = fit(1, 0.0, leave_out=(phase,)) + fit(20, 20.0,
                                                  leave_out=(phase,))
    assert read(name, run_of(spans)) is None
    # and what it took is now nobody's: unattributed grows by it
    whole = fit(1, 0.0) + fit(20, 20.0)
    assert read("fit_unattributed_ms_per_tree", run_of(spans)) > \
        read("fit_unattributed_ms_per_tree", run_of(whole))


def test_launch_reads_either_of_its_spans():
    spans = fit(1, 0.0, leave_out=("train.build_step",)) \
        + fit(20, 20.0, leave_out=("train.build_step",))
    assert read("fit_launch_ms_per_tree", run_of(spans)) == \
        pytest.approx(2 * 0.5 / 4 * 1e3)


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_nothing_to_read_is_none_not_an_error(name, spans):
    # the parent of the PR that added the spans: a profiler without them
    no_spans = types.SimpleNamespace(
        state={"profiler": object()},
        work={"fits": 2, "trees": 4, "window_s": 23.0})
    assert read(name, no_spans) is None
    no_profiler = types.SimpleNamespace(
        state={}, work={"fits": 2, "trees": 4, "window_s": 23.0})
    assert read(name, no_profiler) is None
    # fewer fits recorded than the window made, or an empty window
    assert read(name, run_of(spans, fits=4)) is None
    assert read(name, run_of(spans, fits=0, trees=0)) is None


def test_collectives_need_the_attribute():
    spans = fit(1, 0.0) + fit(20, 20.0)
    assert read("collective_bytes_per_tree", run_of(spans)) is None
    assert read("collective_count_per_tree", run_of(spans)) is None
