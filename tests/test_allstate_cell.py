"""The one-hot cell ``allstate_fit`` (PR 33): ``correct`` true for the
program, false for the float8 control and for each of the six planted
faults (the tests of benchmark/tests/test_correct_sparse.py, counted
here), the generator, the least-columns work count, and the four new
per-layer readers on a hand-made span list.
"""

import json
import os
import types

import numpy as np
import pytest

from benchmark.tests.test_correct_sparse import (  # noqa: F401
    broken_train, fitted, test_altered_leaf_is_not_correct,
    test_altered_split_is_not_correct, test_bundle_conflict_is_not_correct,
    test_default_dropped_is_not_correct, test_float8_control_is_not_correct,
    test_half_batch_is_not_correct, test_native_loop_and_numpy_agree,
    test_sound_run_is_correct, test_state_left_unchanged_is_not_correct)
from benchmark.lib import data_onehot, work, work_sparse
from benchmark.tests.test_span_readers import Profiler, fit
from tests.test_istella_cell import TEXT, read

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCKS = [30, 50, 6, 4, 3, 7, 9, 5, 4, 3, 2, 6, 8, 5, 4, 3, 11]


def run_of(spans, state=None, counters=None, fits=2, trees=4,
           window_s=23.0):
    return types.SimpleNamespace(
        state=dict(state or {}, profiler=Profiler(spans)),
        counters=dict(counters or {}),
        work={"fits": fits, "trees": trees, "window_s": window_s},
        peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, chips=1)


def test_rows_depend_on_the_seed_and_the_shape_alone():
    X, y = data_onehot.onehot_rows(2147483659, 70_000, BLOCKS, threads=3)
    X2, y2 = data_onehot.onehot_rows(2147483659, 70_000, BLOCKS, threads=1)
    assert np.array_equal(X.indices, X2.indices)
    assert np.array_equal(X.values, X2.values) and np.array_equal(y, y2)
    other, _ = data_onehot.onehot_rows(5, 70_000, BLOCKS)
    assert not np.array_equal(other.indices[:1000], X.indices[:1000])
    assert X.shape == (70_000, 14 + sum(BLOCKS))
    assert X.indices.dtype == np.int32 and X.values.dtype == np.float32


def test_every_row_sets_one_column_of_each_block_in_column_order():
    X, y = data_onehot.onehot_rows(9, 50_000, BLOCKS)
    rows = np.repeat(np.arange(50_000), np.diff(X.indptr))
    # ascending within a row
    assert ((np.diff(X.indices) > 0) | (np.diff(rows) > 0)).all()
    at = 14
    for k in BLOCKS:
        mine = (X.indices >= at) & (X.indices < at + k)
        assert np.array_equal(np.bincount(rows[mine], minlength=50_000),
                              np.ones(50_000, int))
        assert (X.values[mine] == 1).all()
        at += k
    # the sparse numeric columns are zero (no entry) in 3 of 4 rows
    sparse = (X.indices >= 8) & (X.indices < 12)
    assert 0.22 < sparse.sum() / (4 * 50_000) < 0.28
    assert (X.values[X.indices < 14] != 0).all()
    assert 0.004 < y.mean() < 0.011


def test_the_configuration_states_the_published_shape():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "allstate.json")))
    blocks = list(cfg["onehot_blocks"].values())
    numeric = (cfg["numeric_dense"] + cfg["numeric_sparse"]
               + cfg["numeric_years"])
    assert numeric + sum(blocks) == cfg["features"] == 4228
    assert cfg["rows"] == cfg["source_rows"] == 13_184_290
    assert cfg["reduced"] == ["num_trees"]
    assert cfg["params"]["enableBundle"] is True
    assert cfg["params"]["maxConflictRate"] == 0.0
    assert sorted(blocks)[-2:] == [1302, 2728]
    # the least bundle columns any implementation needs, and the cells
    assert work_sparse.least_columns(blocks, numeric, 255) == (
        -(-(14 * 254 + 4214) // 255), 14 * 255 + 2 * 4214)


def test_sparse_fit_tree_mfu_counts_the_least_columns():
    from benchmark.reference import gbdt
    state = {"sparse_model_text": TEXT, "onehot_blocks": [300, 210],
             "numeric_columns": 2, "num_bins": 256}
    run = run_of([], state, trees=1, window_s=1e-3)
    (tree,) = gbdt.parse_model(TEXT)
    columns, cells = work_sparse.least_columns([300, 210], 2, 255)
    assert (columns, cells) == (4, 2 * 255 + 2 * 510)
    rows, nodes = work.rows_histogrammed(tree), 5
    moved = rows * columns + rows * 8 + nodes * cells * 12
    assert work_sparse.histogram_work([tree], [300, 210], 2, 255) == (
        rows * columns * 3, moved)
    assert read("sparse_fit_tree_mfu", run) == pytest.approx(
        100.0 * (moved / 819e9) / 1e-3)
    # far under the dense count at the same width
    assert moved < work.histogram_work([tree], 512, 256)[1] / 50
    # the accepted readers find nothing under this driver's key
    assert read("fit_tree_mfu", run) is None
    assert read("sparse_fit_tree_mfu", run_of([], {"model_text": TEXT})) \
        is None


def test_bundle_readers_on_a_hand_made_list():
    attrs = {"efb_bundles": 90, "efb_features": 4228,
             "hist_cache_bytes": 3312046080, "efb_conflict_rows": 0}
    spans = (fit(100, 0.0, scale=10.0, attrs=dict(attrs, efb_bundles=7))
             + fit(200, 200.0, attrs=attrs) + fit(300, 300.0, attrs=attrs))
    run = run_of(spans, counters={"bundle_s": 17.5})
    assert read("efb_bundle_columns", run) == 90      # not the warm-up's
    assert read("hist_cache_bytes", run) == 3312046080
    assert read("bundle_s", run) == 17.5


def test_bundle_readers_find_nothing_in_another_program():
    """The parent's spans carry neither attr and its driver no counter:
    a reader returns nothing and does not raise."""
    run = run_of(fit(100, 0.0) + fit(200, 200.0) + fit(300, 300.0))
    for name in ("efb_bundle_columns", "hist_cache_bytes", "bundle_s",
                 "sparse_fit_tree_mfu"):
        assert read(name, run) is None, name
    no_spans = types.SimpleNamespace(
        state={"profiler": object()}, counters={},
        work={"fits": 2, "trees": 4, "window_s": 1.0})
    assert read("efb_bundle_columns", no_spans) is None
    assert read("hist_cache_bytes", no_spans) is None
