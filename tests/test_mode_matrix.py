"""THE mode-matrix completeness pin (SURVEY.md §2.1, §3.1): the
reference's single engine supports every boostingType with every
objective under every deployment shape.  This table-driven test runs
every combination at tiny shapes and asserts it either TRAINS or raises
the one documented gate — any silent regression of a matrix cell fails
here by name."""

import numpy as np
import pytest

from mmlspark_tpu.core.mesh import build_mesh
from mmlspark_tpu.gbdt import (LightGBMClassifier, LightGBMRanker,
                               LightGBMRegressor, fit_bin_mapper)
from mmlspark_tpu.gbdt.engine import TrainParams, train
from mmlspark_tpu.gbdt.objectives import get_objective

BOOSTING = ["gbdt", "goss", "dart", "rf"]

#: round 5: the last gate (dart x ranking x sharded) closed — every
#: boosting x objective x deployment cell trains
GATED = set()


def _tables():
    rng = np.random.default_rng(3)
    n, f = 320, 5
    X = rng.normal(size=(n, f))
    yb = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    ym = rng.integers(0, 3, n).astype(float)
    ym[X[:, 0] > 0.3] = 2.0          # learnable-ish
    q = np.repeat(np.arange(n // 8), 8)
    yr = np.clip(np.digitize(X[:, 0], [-0.3, 0.4]), 0, 2).astype(float)
    return X, {"binary": yb, "multiclass": ym, "lambdarank": yr}, q


X_ALL, Y_ALL, Q_ALL = _tables()


def _estimator(objective, boosting):
    kw = dict(numIterations=2, numLeaves=7, minDataInLeaf=5, maxBin=31,
              verbosity=0)
    if boosting == "rf":
        kw.update(baggingFraction=0.6, baggingFreq=1)
    if objective == "lambdarank":
        return LightGBMRanker(boostingType=boosting, groupCol="query",
                              **kw)
    return LightGBMClassifier(boostingType=boosting, **kw)


@pytest.mark.parametrize("objective", ["binary", "multiclass",
                                       "lambdarank"])
@pytest.mark.parametrize("boosting", BOOSTING)
@pytest.mark.parametrize("deploy", ["serial", "mesh", "sharded"])
def test_matrix_cell(objective, boosting, deploy):
    y = Y_ALL[objective]
    t = {"features": X_ALL, "label": y}
    if objective == "lambdarank":
        t["query"] = Q_ALL
    expect_gate = (objective, deploy, boosting) in GATED

    if deploy == "sharded":
        mapper = fit_bin_mapper(X_ALL, max_bin=31)
        splits = np.array_split(np.arange(len(y)), 8)
        params = TrainParams(num_iterations=2, num_leaves=7,
                             min_data_in_leaf=5, max_bin=31,
                             boosting=boosting, verbosity=0,
                             **({"bagging_fraction": 0.6,
                                 "bagging_freq": 1}
                                if boosting == "rf" else {}))
        if objective == "lambdarank":
            # shards must hold WHOLE queries (group-contiguous
            # ingestion); 40 queries of 8 rows -> 5 queries per shard
            splits = [np.nonzero(np.isin(Q_ALL, np.arange(d, 40, 8)))[0]
                      for d in range(8)]
            rinfo = {"query_ids": [Q_ALL[i] for i in splits],
                     "sigma": 1.0, "truncation_level": 30}
            obj = get_objective("lambdarank")
        else:
            rinfo = None
            obj = (get_objective("multiclass", num_class=3)
                   if objective == "multiclass"
                   else get_objective("binary"))
        run = lambda: train(  # noqa: E731
            [mapper.transform_packed(X_ALL[i]) for i in splits],
            [y[i] for i in splits], None, mapper, obj,
            params, mesh=build_mesh(data=8, feature=1),
            ranking_info=rinfo)
    else:
        est = _estimator(objective, boosting)
        if deploy == "mesh":
            est = est.setMesh(build_mesh(data=8, feature=1))
        run = lambda: est.fit(t)  # noqa: E731

    if expect_gate:
        with pytest.raises(NotImplementedError):
            run()
        return
    model = run()
    trees = (model.trees if deploy == "sharded"
             else model.getModel().trees)
    expected = 2 * (3 if objective == "multiclass" else 1)
    assert len(trees) == expected


def _nan_table():
    """A binary table whose NaN carries signal: features 0, 1, 3 and 5
    hold NaN at their own rates, 2 and 4 none."""
    rng = np.random.default_rng(5)
    n, f = 640, 6
    X = rng.normal(size=(n, f))
    X[rng.random((n, f)) < np.array([0.5, 0.3, 0.0, 0.6, 0.0, 0.2])] = np.nan
    y = ((np.nan_to_num(X[:, 0], nan=1.5) - 1.2 * np.isnan(X[:, 3])
          + 0.6 * np.nan_to_num(X[:, 1], nan=-1.0)
          + 0.3 * rng.normal(size=n)) > 0.1).astype(float)
    return {"features": X, "label": y}


def _nan_bundle_table():
    """Four columns set in turn, one a row, each NaN in a third of its
    rows, and two dense ones: the four share one bundle column (their
    missing slots with them) when bundling is on."""
    rng = np.random.default_rng(9)
    n = 800
    grp = rng.integers(0, 4, n)
    X = np.zeros((n, 6))
    for j in range(4):
        on = grp == j
        X[on, j] = rng.normal(size=on.sum()) + 1.0
        X[on & (rng.random(n) < 0.35), j] = np.nan
    X[:, 4:] = rng.normal(size=(n, 2))
    y = ((np.nan_to_num(X[:, 0], nan=-2.0) - 1.5 * np.isnan(X[:, 1])
          + X[:, 4] + 0.3 * rng.normal(size=n)) > 0.3).astype(float)
    return {"features": X, "label": y}


NAN_TABLE = _nan_table()
NAN_BUNDLE_TABLE = _nan_bundle_table()

#: every numeric split path: the estimator's settings and, for a mesh
#: path, the (data, feature) mesh.  The data mesh, the feature-parallel
#: learner and voting with every feature voted are exact, so they grow
#: the serial trees
NAN_PATHS = {
    "serial": ({}, None),
    "data": ({"parallelism": "data"}, (8, 1)),
    "feature": ({"parallelism": "feature"}, (1, 8)),
    "voting": ({"parallelism": "voting", "topK": 6}, (8, 1)),
    "quantized": ({"quantizedGrad": "16"}, None),
    "goss": ({"boostingType": "goss", "topRate": 0.3, "otherRate": 0.2},
             None),
    "dart": ({"boostingType": "dart"}, None),
    "bundled": ({"enableBundle": True}, None),
}
NAN_EXACT = {"data", "feature", "voting", "bundled"}


def _nan_table_of(path):
    return NAN_BUNDLE_TABLE if path == "bundled" else NAN_TABLE


def _nan_fit(path, table):
    kw, mesh = NAN_PATHS[path]
    est = LightGBMClassifier(numIterations=3, numLeaves=7, minDataInLeaf=5,
                             maxBin=31, verbosity=0, **kw)
    if mesh is not None:
        est = est.setMesh(build_mesh(data=mesh[0], feature=mesh[1]))
    return est.fit(table).getModel()


@pytest.mark.parametrize("path", sorted(NAN_PATHS))
def test_nan_table_split_path(path):
    """Every numeric split path scores both default directions on a NaN
    table (no path here refuses one; a bundled table keeps each member's
    missing slot in its bundle column): some split sends NaN left
    (``decision_type`` 10), the exported routing counts the rows each
    leaf got (a subset under goss's sampling), and the exact paths grow
    the serial path's trees."""
    from mmlspark_tpu.gbdt import engine
    table = _nan_table_of(path)
    booster = _nan_fit(path, table)
    if path == "bundled":                 # the four share a bundle
        assert int(engine.last_fit_info["efb_bundles"]) < 6
    X = np.asarray(table["features"], np.float32)
    dts = np.concatenate([t.decision_type for t in booster.trees])
    assert set(dts) <= {2, 8, 10} and 10 in set(dts)
    leaves = np.asarray(booster.predict_leaf_index(X))
    for k, t in enumerate(booster.trees):
        walked = np.bincount(leaves[:, k], minlength=t.num_leaves)
        if path == "goss":
            assert (walked >= t.leaf_count).all()
        else:
            np.testing.assert_array_equal(walked, t.leaf_count)
    if path in NAN_EXACT:
        serial = _nan_fit("serial", table)
        for a, b in zip(serial.trees, booster.trees):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)
            np.testing.assert_array_equal(a.decision_type, b.decision_type)
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=2e-3, atol=1e-5)
