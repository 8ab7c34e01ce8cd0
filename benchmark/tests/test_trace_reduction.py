"""The trace reduction against a hand-made event list: every later PR
computes busy time, idle gaps and per-op time this way."""

import json
import os

import pytest

from benchmark.lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def case():
    with open(os.path.join(HERE, "fixtures", "events.json")) as fh:
        doc = json.load(fh)
    doc["events"] = [tuple(e) for e in doc["events"]]
    doc["spans"] = [tuple(s) for s in doc["spans"]]
    return doc


def test_merge_and_clip():
    assert trace.merge([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [[0, 2], [3, 4]]
    assert trace.clip([(0, 5), (6, 7)], 1, 6.5) == [(1, 5), (6, 6.5)]


def test_busy_union(case):
    # 100-100.5 (early, clipped), 101-104 (the while covers its body),
    # 107.5-108.5, 109.5-110 (clipped)
    got = trace.busy_seconds(case["events"], tuple(case["window"]))
    assert got == pytest.approx(case["expect"]["busy_s"])


def test_self_times_subtract_nested(case):
    lo, hi = case["window"]
    clipped = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
               for n, s, d in case["events"]]
    got = {trace.short(n): t for n, t in trace.self_times(clipped)}
    for name, want in case["expect"]["self_times"].items():
        assert got[name] == pytest.approx(want), name


def test_idle_gaps_named_by_span(case):
    gaps = trace.idle_gaps(case["events"], tuple(case["window"]),
                           case["spans"])
    assert len(gaps) == case["expect"]["gap_count"]
    name, seconds = gaps[0]
    assert name == case["expect"]["longest_gap"][0]
    assert seconds == pytest.approx(case["expect"]["longest_gap"][1])
    busy = trace.busy_seconds(case["events"], tuple(case["window"]))
    lo, hi = case["window"]
    assert busy + sum(s for _, s in gaps) == pytest.approx(hi - lo)


def test_reduce_takes_the_window_span(case):
    spans = case["spans"] + [("window",) + tuple(case["window"])]
    quiet = [("%x = f32[1]{0} add(%a, %b)", 100.0, 0.25)]
    out = trace.reduce({"/device:TPU:0": case["events"],
                        "/device:TPU:1": quiet}, spans)
    assert out["busiest"] == "/device:TPU:0"
    assert out["window_s"] == pytest.approx(10.0)
    assert out["busy_mean_s"] == pytest.approx((5.0 + 0.25) / 2)
    assert out["idle_gaps"][0][1] == pytest.approx(3.5)
    assert trace.reduce({}, spans) is None
