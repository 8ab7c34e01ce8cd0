"""The readers of ``bosch_missing_fit``'s per-layer metrics against
hand-made span lists (``test_span_readers.py``'s): a warm-up fit and two
fits of the window, 2 trees each.

    python -m pytest benchmark/tests/test_span_readers_missing.py -q
"""

import types

import pytest

from benchmark.tests.test_span_readers import fit, read, run_of

WARMUP = {"missing_splits": 900, "missing_left_splits": 900,
          "missing_rows_routed": 10 ** 9}


def spans_with(first, second):
    # the warm-up's counters are far off: a reader that took it in
    # would read them
    return (fit(100, 0.0, scale=10.0, attrs=WARMUP)
            + fit(200, 200.0, attrs=first) + fit(300, 310.0, attrs=second))


def test_missing_splits_per_tree_reads_the_windows_fits():
    run = run_of(spans_with(
        {"missing_splits": 300, "missing_left_splits": 120},
        {"missing_splits": 340, "missing_left_splits": 200}))
    assert read("missing_splits_per_tree", run) == (300 + 340) / 4


def test_missing_left_share_is_over_the_window():
    run = run_of(spans_with(
        {"missing_splits": 300, "missing_left_splits": 120},
        {"missing_splits": 340, "missing_left_splits": 200}))
    assert read("missing_left_share", run) == pytest.approx(
        100.0 * 320 / 640)


@pytest.mark.parametrize("name", ["missing_splits_per_tree",
                                  "missing_left_share"])
def test_a_fit_without_missing_values_reads_none(name):
    """A table without NaN (or a program without the counters): the
    roots carry no ``missing_*`` attributes, and the readers read None."""
    assert read(name, run_of(spans_with({}, {}))) is None
    assert read(name, run_of([], fits=0, trees=0)) is None


def test_a_window_of_no_missing_split_has_no_share():
    run = run_of(spans_with({"missing_splits": 0, "missing_left_splits": 0},
                            {"missing_splits": 0,
                             "missing_left_splits": 0}))
    assert read("missing_left_share", run) is None
    assert read("missing_splits_per_tree", run) == 0


def test_whole_step_share_reads_the_kept_text():
    """``missing_fit_tree_mfu`` parses the text the driver keeps, with its
    ``decision_type``; nothing kept reads None."""
    text = "\n".join([
        "tree", "version=v3", "num_class=1", "", "Tree=0", "num_leaves=3",
        "num_cat=0", "split_feature=0 1", "split_gain=1 1",
        "threshold=0.5 1e+300", "decision_type=10 8", "left_child=1 -1",
        "right_child=-3 -2", "leaf_value=0.1 0.2 0.3",
        "leaf_count=40 30 30", "internal_count=100 70", "", "",
        "end of trees"])
    peak = {"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
    run = types.SimpleNamespace(
        state={"missing_model_text": text, "features": 8, "num_bins": 256},
        work={"trees": 1, "window_s": 1.0}, peak=peak, chips=1)
    rows = 100 + 30 + 30                    # root, then each smaller child
    moved = rows * 8 + rows * 8 + 5 * 8 * 256 * 12
    assert read("missing_fit_tree_mfu", run) == pytest.approx(
        100.0 * moved / peak["hbm_bytes_per_s"])
    run.state = {}
    assert read("missing_fit_tree_mfu", run) is None
