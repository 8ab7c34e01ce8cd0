"""Sparse ingest and bundling at binning time (core/schema.SparseColumn,
gbdt/binning.py, gbdt/efb.py; ISSUE 33).

The normal path takes sparse rows: a CSR vector column is binned from its
entries with the zeros counted, never written; with ``enableBundle`` the
plan and the ``(n, G)`` table come straight from the entries, once, and
``engine.train`` bundles nothing.  At ``maxConflictRate`` 0 no row of the
WHOLE table may lose a value, whatever the plan's sample saw.
"""

import json

import numpy as np
import pytest

from mmlspark_tpu.core.profiler import get_profiler
from mmlspark_tpu.core.schema import (DataTable, SparseColumn,
                                      features_matrix)
from mmlspark_tpu.gbdt import LightGBMClassifier, efb, engine
from mmlspark_tpu.gbdt.binning import SparseBins, fit_bin_mapper


def _rows(n=6000, seed=0, blocks=(12, 40, 5, 3), nan=True):
    """One-hot blocks (a row sets one column of each), three dense
    numeric columns, one numeric column that is 0 in most rows and
    negative elsewhere, one of few values: dense float32 and the label."""
    rng = np.random.default_rng(seed)
    f = 5 + sum(blocks)
    X = np.zeros((n, f), np.float32)
    X[:, :3] = rng.normal(size=(n, 3))
    X[:, 3] = np.where(rng.random(n) < 0.7, 0.0, -np.abs(rng.normal(size=n)))
    X[:, 4] = rng.integers(0, 4, n)
    at, logit = 5, X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
    for k in blocks:
        w = np.arange(1, k + 1) ** -1.05
        own = np.minimum(np.searchsorted(np.cumsum(w) / w.sum(),
                                         rng.random(n)), k - 1)
        X[np.arange(n), at + own] = 1.0
        logit = logit + rng.normal(size=k)[own] * 0.8
        at += k
    if nan:
        X[::97, 1] = np.nan
    y = (rng.random(n) < 1 / (1 + np.exp(-np.nan_to_num(logit)))
         ).astype(np.float64)
    return X, y


class TestSparseColumn:
    def test_rows_select_and_come_back_dense(self):
        X, _ = _rows(300)
        S = SparseColumn.from_dense(X)
        assert S.shape == X.shape and S.nnz < X.size // 4
        for pick in (slice(10, 200), np.array([5, 2, 250]),
                     np.arange(300) % 3 == 0):
            np.testing.assert_array_equal(S[pick].toarray(np.float32),
                                          X[pick])

    def test_table_holds_it_and_other_stages_read_it_dense(self):
        X, y = _rows(200, nan=False)
        t = DataTable({"features": SparseColumn.from_dense(X), "label": y})
        assert isinstance(t["features"], SparseColumn)
        assert isinstance(t.slice(0, 50)["features"], SparseColumn)
        np.testing.assert_array_equal(features_matrix(t, "features"), X)
        assert isinstance(features_matrix(t, "features", sparse=True),
                          SparseColumn)
        with pytest.raises(ValueError):
            SparseColumn(np.zeros(3), np.zeros(5), np.zeros(4), (2, 9))


@pytest.mark.parametrize("sample_cnt", [200_000, 1500])
def test_sparse_and_dense_ingest_bin_alike(sample_cnt):
    """The same mapper and the same bins, whether the sample is the table
    or a draw from it: quantile bounds with the zeros counted where they
    sort, midpoints of few values, the missing bin."""
    X, _ = _rows()
    S = SparseColumn.from_dense(X)
    dense = fit_bin_mapper(X, sample_cnt=sample_cnt, seed=3)
    sparse = fit_bin_mapper(S, sample_cnt=sample_cnt, seed=3)
    assert dense.to_json() == sparse.to_json()
    want = dense.transform_packed(X)
    got = sparse.transform_packed(S)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)
    entries = sparse.bin_entries(S)
    np.testing.assert_array_equal(entries.toarray(), want)
    np.testing.assert_array_equal(SparseBins.from_dense(want).toarray(),
                                  want)
    assert {s["name"] for s in get_profiler().spans()} >= {"bin.sparse_fit"}


def test_plan_and_table_from_entries_equal_the_dense_route():
    X, _ = _rows()
    m = fit_bin_mapper(X)
    bins = m.transform(X)
    nb = [m.feature_num_bins(j) for j in range(X.shape[1])]
    entries = m.bin_entries(SparseColumn.from_dense(X))
    spec = efb.plan_bundles(entries, nb, m.missing_bin, sample_cnt=2000)
    assert spec == efb.find_bundles(bins, nb, m.missing_bin,
                                    sample_cnt=2000)
    assert not spec.is_trivial
    table, conflict_rows, _ = efb.write_bundles(entries, spec,
                                                m.missing_bin)
    np.testing.assert_array_equal(
        table, efb.bundle_matrix(bins, spec, m.missing_bin))
    whole = efb.find_bundles(bins, nb, m.missing_bin)
    assert efb.write_bundles(entries, whole, m.missing_bin)[1:][0] == 0
    assert conflict_rows >= 0


def _sparse_fit_table(n=40_000, seed=1):
    """Two Zipf blocks of 300 whose rare members a 2000-row sample cannot
    tell apart: planned from it, members of one block join the other's
    bundles and collide in the whole table."""
    rng = np.random.default_rng(seed)

    def zipf(k):
        w = np.arange(1, k + 1) ** -1.05
        return np.minimum(np.searchsorted(np.cumsum(w) / w.sum(),
                                          rng.random(n)), k - 1)

    a, b = zipf(300), zipf(300)
    idx = np.stack([np.zeros(n, int), 1 + a, 301 + b, np.full(n, 601)], 1)
    val = np.stack([rng.normal(size=n), np.ones(n), np.ones(n),
                    rng.normal(size=n)], 1).astype(np.float32)
    S = SparseColumn(np.arange(n + 1) * 4, idx.reshape(-1).astype(np.int32),
                     val.reshape(-1), (n, 602))
    y = (val[:, 0] + (a % 3 == 0) - (b % 5 == 0) > 0.3).astype(np.float64)
    return S, y


def test_a_collision_outside_the_sample_is_moved_out(monkeypatch):
    monkeypatch.setattr(efb, "PLAN_SAMPLE_ROWS", 2000)
    S, y = _sparse_fit_table()
    m = fit_bin_mapper(S)
    entries = m.bin_entries(S)
    nb = [m.feature_num_bins(j) for j in range(S.shape[1])]
    spec = efb.plan_bundles(entries, nb, m.missing_bin)
    assert efb.write_bundles(entries, spec, m.missing_bin)[1] > 0
    bundled = efb.bundle_for_training(entries, m)
    assert bundled.moved > 0 and bundled.conflict_rows == 0
    np.testing.assert_array_equal(
        efb.decode_rows(bundled.table, bundled.maps(), m.missing_bin),
        entries.toarray())
    # with a budget the plan stands and the loss is counted
    loose = efb.bundle_for_training(entries, m, max_conflict_rate=0.01)
    assert loose.moved == 0 and loose.conflict_rows > 0
    lost = (efb.decode_rows(loose.table, loose.maps(), m.missing_bin)
            != entries.toarray()).any(axis=1).sum()
    assert lost == loose.conflict_rows
    # and the fit says so: efb_conflict_rows is 0 at rate 0
    before = {s["id"] for s in get_profiler().spans()}
    model = LightGBMClassifier(numIterations=2, numLeaves=7, verbosity=0,
                               enableBundle=True).fit(
        DataTable({"features": S, "label": y}))
    new = [s for s in get_profiler().spans() if s["id"] not in before]
    fit, = [s for s in new if s["name"] == "train.fit"]
    assert fit["attrs"]["efb_conflict_rows"] == 0
    assert fit["attrs"]["efb_features"] == 602
    assert 4 <= fit["attrs"]["efb_bundles"] < 60     # (its own seed's plan)
    assert fit["attrs"]["efb_table_bytes"] \
        == fit["attrs"]["efb_bundles"] * S.shape[0]
    assert fit["attrs"]["hist_cache_bytes"] == 7 * 602 * 256 * 12
    names = [s["name"] for s in new]
    assert names.count("bin.bundle_plan") == names.count(
        "bin.bundle_build") == 1
    # bundling is binning's: no span of the fit holds any
    inside = {s["id"] for s in new if s["parent"] == fit["id"]}
    assert not [s for s in new if s["name"].startswith("bin.")
                and s["parent"] in inside | {fit["id"]}]
    assert model.getModel().trees


def test_an_unbundled_fit_has_no_efb_attrs():
    X, y = _rows(1500, nan=False)
    before = {s["id"] for s in get_profiler().spans()}
    LightGBMClassifier(numIterations=1, numLeaves=5, verbosity=0).fit(
        DataTable({"features": SparseColumn.from_dense(X), "label": y}))
    fit, = [s for s in get_profiler().spans()
            if s["id"] not in before and s["name"] == "train.fit"]
    assert not [k for k in fit["attrs"] if k.startswith("efb_")]
    assert fit["attrs"]["hist_cache_bytes"] == 5 * X.shape[1] * 256 * 12


def test_bundled_fit_on_a_sparse_column_agrees_with_the_plain_reference():
    """Through ``LightGBMClassifier.fit``: counts, bins and leaves as the
    reference works them out from the raw CSR rows, which knows nothing
    of bundles; thresholds and export on ORIGINAL features; the same
    forest as the dense column's unbundled fit."""
    from benchmark.reference import gbdt, gbdt_sparse
    X, y = _rows(8000, seed=4, nan=False)
    S = SparseColumn.from_dense(X)
    kw = dict(numIterations=3, numLeaves=15, minSumHessianInLeaf=5.0,
              minDataInLeaf=0, verbosity=0)
    est = LightGBMClassifier(enableBundle=True, **kw)
    model = est.fit(DataTable({"features": S, "label": y}))
    text = model.getNativeModel()
    m = fit_bin_mapper(S, max_bin=est.getMaxBin(), seed=est.getSeed())
    entries = m.bin_entries(S)
    assert efb.bundle_for_training(entries, m) is not None
    got = gbdt_sparse.check_fit(
        text, S, y, entries.bins, entries.implicit_bin,
        {"learning_rate": 0.1, "min_sum_hessian": 5.0, "min_data": 0,
         "max_bin": 255,
         "binning": {"sample_rows": 200000, "seed": est.getSeed(),
                     "min_data_in_bin": 3}},
        seed=1, expect_trees=3, sample_nodes=8, sample_features=12)
    assert got["tree_count_gap"] == got["count_mismatch"] \
        == got["bin_mismatch"] == 0
    assert got["leaf_value_gap"] < 1e-3 and got["split_gap_mean"] < 1e-6
    trees = gbdt.parse_model(text)
    used = {int(j) for t in trees for j in t["split_feature"]}
    assert max(used) < X.shape[1] and any(j >= 5 for j in used)
    assert f"max_feature_idx={X.shape[1] - 1}" in text
    plain = LightGBMClassifier(**kw).fit(
        DataTable({"features": X, "label": y}))
    np.testing.assert_allclose(
        np.asarray(model.getModel().predict_margin(X)),
        np.asarray(plain.getModel().predict_margin(X)), rtol=1e-4,
        atol=1e-5)


def test_bundled_reference_profile_is_the_host_paths():
    """The profile counted on the device from the bundled table the fit
    uploaded, expanded to features by the map ``_efb_expand`` gathers by,
    is the profile the host counts column by column from the dense
    bins: the same JSON."""
    X, y = _rows(5000, seed=2)
    est = LightGBMClassifier(numIterations=2, numLeaves=7, verbosity=0,
                             enableBundle=True)
    m = fit_bin_mapper(X, max_bin=est.getMaxBin(), seed=est.getSeed())
    dense = m.transform_packed(X)
    bundled = efb.bundle_for_training(
        m.bin_entries(SparseColumn.from_dense(X)), m)
    from mmlspark_tpu.gbdt.objectives import get_objective
    before = {s["id"] for s in get_profiler().spans()}
    booster = engine.train(bundled, est._prepare_labels(y), None, m,
                           get_objective("binary"), est._train_params())
    span, = [s for s in get_profiler().spans() if s["id"] not in before
             and s["name"] == "train.reference_profile"]
    assert span["attrs"]["counts"] == "device"
    assert span["attrs"]["rows"] == 5000

    def doc(profile):
        d = json.loads(profile.to_json())
        for stamp in ("created", "fit_span"):
            d["meta"].pop(stamp)
        return d

    device = doc(booster.reference_profile)
    engine._capture_reference_profile(booster, dense, m, None)
    assert doc(booster.reference_profile) == device
    # and with no table left on the device, the bundled table's own
    # columns are counted on the host: the same again
    engine._capture_reference_profile(booster, bundled, m, None)
    assert doc(booster.reference_profile) == device


def test_the_gate_is_decided_where_the_plan_is_made():
    X, y = _rows(1200, nan=False)
    m = fit_bin_mapper(X)
    assert efb.bundling_applies(m, True)
    assert not efb.bundling_applies(m, False)
    assert not efb.bundling_applies(m, True, ranker=True)
    cat = fit_bin_mapper(X, categorical_features=[4])
    assert not efb.bundling_applies(cat, True)
    wide = fit_bin_mapper(X, max_bin=300)
    assert not efb.bundling_applies(wide, True)
    bundled = efb.bundle_for_training(m.transform_packed(X), m)
    assert bundled is not None and bundled.shape == X.shape
    assert bundled[:100].shape == (100, X.shape[1])
    # a table of dense columns plans no bundle, and is never converted
    assert efb.bundle_for_training(
        m.transform_packed(X)[:, :3], fit_bin_mapper(X[:, :3])) is None
    # engine.train bundles nothing: enable_bundle and dense bins
    est = LightGBMClassifier(numIterations=1, numLeaves=5, verbosity=0,
                             enableBundle=True)
    from mmlspark_tpu.gbdt.objectives import get_objective
    before = {s["id"] for s in get_profiler().spans()}
    engine.train(m.transform_packed(X), y, None, m,
                 get_objective("binary"), est._train_params())
    fit, = [s for s in get_profiler().spans()
            if s["id"] not in before and s["name"] == "train.fit"]
    assert "efb_bundles" not in fit["attrs"]
    # ... and refuses a bundled table where bundles do not apply
    from mmlspark_tpu.gbdt.ranking import make_lambdarank_grad_fn
    qid = np.repeat(np.arange(120), 10)
    grad = make_lambdarank_grad_fn((y * 3).astype(int), qid)
    with pytest.raises(ValueError, match="bundled table"):
        engine.train(bundled, y, None, m, get_objective("lambdarank"),
                     est._train_params(), grad_fn_override=grad)
