"""GBDT engine: grower invariants, end-to-end quality, persistence."""

import json
import os

import numpy as np
import pandas as pd
import pytest

from mmlspark_tpu.gbdt import (LightGBMClassifier, LightGBMClassificationModel,
                               LightGBMRegressor, LightGBMRegressionModel,
                               Booster, fit_bin_mapper)
from mmlspark_tpu.gbdt.binning import BinMapper


def _as_table(d):
    return {"features": d["features"], "label": d["label"]}


class TestBinning:
    def test_exact_bins_for_few_distinct(self):
        X = np.array([[0.0], [1.0], [1.0], [2.0], [3.0]])
        m = fit_bin_mapper(X, max_bin=255, min_data_in_bin=1)
        b = m.transform(X)
        # 4 distinct values -> 4 distinct bins, order-preserving
        assert len(np.unique(b)) == 4
        assert (np.diff(b[:, 0][np.argsort(X[:, 0], kind="stable")]) >= 0).all()

    def test_nan_goes_to_missing_bin(self):
        X = np.array([[0.0], [np.nan], [2.0]])
        m = fit_bin_mapper(X, max_bin=255, min_data_in_bin=1)
        b = m.transform(X)
        assert b[1, 0] == m.missing_bin

    def test_quantile_binning_large(self, rng):
        X = rng.normal(size=(10000, 1))
        m = fit_bin_mapper(X, max_bin=63)
        b = m.transform(X)
        assert b.max() < m.num_total_bins
        # roughly equal mass per bin
        counts = np.bincount(b[:, 0], minlength=64)
        used = counts[counts > 0]
        assert used.min() > 10000 / 63 * 0.3

    def test_threshold_value_monotone(self, rng):
        X = rng.normal(size=(1000, 1))
        m = fit_bin_mapper(X, max_bin=15)
        ts = [m.bin_threshold_value(0, i) for i in range(14)]
        assert ts == sorted(ts)

    @staticmethod
    def _adversarial_matrix(rng, n=4000):
        """Columns chosen to stress every fastbin code path: constant,
        few-distinct, point-mass spike, heavy tail, denormal span, NaN,
        ties, one huge outlier (grid degeneracy / non-finite scale)."""
        X = rng.normal(size=(n, 9)).astype(np.float32)
        X[:, 0] = 3.0
        X[:, 1] = rng.integers(0, 5, n)
        X[:, 2] = np.where(rng.random(n) < 0.9, 1.25,
                           rng.normal(size=n)).astype(np.float32)
        X[:, 3] = np.exp(rng.normal(size=n) * 3)
        X[:, 4] = rng.normal(size=n).astype(np.float32) * 1e-40
        X[: n // 50, 5] = np.nan
        X[:, 6] = np.round(rng.normal(size=n), 1)
        X[0, 7] = 1e30
        return X

    def test_transform_packed_parity_f32_f64(self, rng):
        """The native fastbin kernel must reproduce the float64 numpy
        searchsorted semantics BIT-EXACTLY for f32 and f64 inputs
        (binning.py documents the round-down bound-adjustment proof this
        test pins)."""
        import os
        from mmlspark_tpu import native
        if os.environ.get("MMLSPARK_TPU_NO_NATIVE"):
            pytest.skip("MMLSPARK_TPU_NO_NATIVE=1 forces the fallback; "
                        "parity vs itself proves nothing")
        assert native.bin_columns_available(), \
            "native fastbin kernel failed to build — the parity test " \
            "would silently compare the fallback against itself"
        X = self._adversarial_matrix(rng)
        m = fit_bin_mapper(X, max_bin=255)
        ref = m.transform(X).astype(np.uint8)
        out = m.transform_packed(X)
        assert out.dtype == np.uint8
        assert (out == ref).all()
        X64 = X.astype(np.float64)
        assert (m.transform_packed(X64) == m.transform(X64)
                .astype(np.uint8)).all()

    def test_transform_packed_parity_categorical(self, rng):
        X = self._adversarial_matrix(rng)
        X[:, 8] = rng.integers(0, 40, X.shape[0])
        m = fit_bin_mapper(X, max_bin=255, categorical_features=[8])
        assert (m.transform_packed(X)
                == m.transform(X).astype(np.uint8)).all()

    def test_transform_packed_parity_wide_bins(self, rng):
        """maxBin > 255 routes through the torch batched fallback; parity
        must hold there too (reviewer-found gap: int32 bins silently hit
        the slow per-column loop after the native kernel landed)."""
        X = rng.normal(size=(3000, 4)).astype(np.float32)
        m = fit_bin_mapper(X, max_bin=511)
        out = m.transform_packed(X)
        assert out.dtype == np.int32
        assert (out == m.transform(X)).all()

    def test_quantile_bounds_match_np_quantile(self, rng):
        """_find_bounds' sorted-array lerp reproduces np.quantile
        (method='linear') bit-exactly — including the f32-diff/f64-lerp
        dtype mix numpy uses internally."""
        from mmlspark_tpu.gbdt.binning import _find_bounds
        qs = np.linspace(0, 1, 256)[1:-1]
        for scale in (1.0, 1e3, 1e-3):
            for dt in (np.float32, np.float64):
                col = (rng.normal(size=9000) * scale).astype(dt)
                got = _find_bounds(col, 255, 3)
                want = np.unique(np.quantile(col, qs, method="linear"))
                assert np.array_equal(got, want.astype(np.float64)), dt


class TestClassifier:
    def test_binary_auc_beats_sklearn_stump(self, binary_table):
        from sklearn.metrics import roc_auc_score
        clf = LightGBMClassifier(numIterations=50, numLeaves=15,
                                 learningRate=0.2, minDataInLeaf=5)
        model = clf.fit(_as_table(binary_table))
        out = model.transform(_as_table(binary_table))
        auc = roc_auc_score(binary_table["label"], out["probability"][:, 1])
        assert auc > 0.93, f"train AUC too low: {auc}"

    def test_binary_close_to_sklearn_histgbt(self, binary_table):
        """Holdout AUC within 0.02 of sklearn's histogram GBDT."""
        from sklearn.ensemble import HistGradientBoostingClassifier
        from sklearn.metrics import roc_auc_score
        from sklearn.model_selection import train_test_split
        X, y = binary_table["features"], binary_table["label"]
        Xtr, Xte, ytr, yte = train_test_split(X, y, random_state=0)

        sk = HistGradientBoostingClassifier(
            max_iter=60, learning_rate=0.2, max_leaf_nodes=31,
            min_samples_leaf=20, early_stopping=False).fit(Xtr, ytr)
        sk_auc = roc_auc_score(yte, sk.predict_proba(Xte)[:, 1])

        model = LightGBMClassifier(
            numIterations=60, learningRate=0.2, numLeaves=31,
            minDataInLeaf=20).fit({"features": Xtr, "label": ytr})
        out = model.transform({"features": Xte, "label": yte})
        our_auc = roc_auc_score(yte, out["probability"][:, 1])
        assert our_auc > sk_auc - 0.02, (our_auc, sk_auc)

    def test_output_columns_and_shapes(self, binary_table):
        model = LightGBMClassifier(numIterations=5).fit(
            _as_table(binary_table))
        df = pd.DataFrame({
            "features": list(binary_table["features"][:10]),
            "label": binary_table["label"][:10]})
        out = model.transform(df)
        assert isinstance(out, pd.DataFrame)
        assert set(["rawPrediction", "probability", "prediction"]) <= set(
            out.columns)
        prob = np.stack(out["probability"].to_numpy())
        assert prob.shape == (10, 2)
        np.testing.assert_allclose(prob.sum(axis=1), 1.0, rtol=1e-5)
        pred = out["prediction"].to_numpy()
        assert set(np.unique(pred)) <= {0.0, 1.0}

    def test_multiclass_auto_promotion(self, rng):
        from sklearn.datasets import make_classification
        X, y = make_classification(n_samples=1500, n_features=10,
                                   n_informative=8, n_classes=3,
                                   random_state=1)
        model = LightGBMClassifier(numIterations=30, numLeaves=15,
                                   minDataInLeaf=5).fit(
            {"features": X, "label": y.astype(float)})
        out = model.transform({"features": X, "label": y})
        acc = np.mean(out["prediction"] == y)
        assert out["probability"].shape == (1500, 3)
        assert acc > 0.8, acc

    def test_sample_weights_respected(self, rng):
        # duplicate-class data where weights flip the majority
        X = np.concatenate([np.zeros((100, 2)), np.zeros((50, 2))])
        y = np.concatenate([np.zeros(100), np.ones(50)])
        w = np.concatenate([np.ones(100), np.full(50, 10.0)])
        model = LightGBMClassifier(
            numIterations=5, minDataInLeaf=1, weightCol="w").fit(
            {"features": X, "label": y, "w": w})
        out = model.transform({"features": X[:1], "label": y[:1]})
        # weighted positive mass dominates -> p1 > 0.5 despite fewer rows
        assert out["probability"][0, 1] > 0.5

    def test_early_stopping(self, binary_table):
        X, y = binary_table["features"], binary_table["label"]
        val = np.zeros(len(y), bool)
        val[::4] = True
        model = LightGBMClassifier(
            numIterations=200, learningRate=0.5, numLeaves=31,
            earlyStoppingRound=5, validationIndicatorCol="isVal").fit(
            {"features": X, "label": y, "isVal": val})
        assert len(model.getModel().trees) < 200


class TestRegressor:
    def test_r2_reasonable(self, regression_table):
        from sklearn.metrics import r2_score
        model = LightGBMRegressor(numIterations=80, learningRate=0.1,
                                  numLeaves=31, minDataInLeaf=5).fit(
            _as_table(regression_table))
        out = model.transform(_as_table(regression_table))
        r2 = r2_score(regression_table["label"], out["prediction"])
        assert r2 > 0.8, r2

    def test_l1_objective_runs(self, regression_table):
        model = LightGBMRegressor(objective="regression_l1",
                                  numIterations=10).fit(
            _as_table(regression_table))
        out = model.transform(_as_table(regression_table))
        assert np.isfinite(out["prediction"]).all()

    def test_constant_labels_yield_constant_prediction(self):
        X = np.random.default_rng(0).normal(size=(100, 3))
        y = np.full(100, 7.0)
        model = LightGBMRegressor(numIterations=10).fit(
            {"features": X, "label": y})
        out = model.transform({"features": X, "label": y})
        np.testing.assert_allclose(out["prediction"], 7.0, atol=1e-5)


class TestPersistence:
    def test_native_model_roundtrip(self, binary_table, tmp_path):
        model = LightGBMClassifier(numIterations=10).fit(
            _as_table(binary_table))
        p = str(tmp_path / "model.txt")
        model.saveNativeModel(p)
        loaded = LightGBMClassificationModel.loadNativeModel(p)
        loaded.setFeaturesCol("features")
        a = model.transform(_as_table(binary_table))["probability"]
        b = loaded.transform(_as_table(binary_table))["probability"]
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_native_model_text_structure(self, binary_table):
        model = LightGBMClassifier(numIterations=3).fit(
            _as_table(binary_table))
        txt = model.getNativeModel()
        for key in ["tree\n", "version=v3", "num_class=1", "objective=binary",
                    "Tree=0", "split_feature=", "threshold=", "leaf_value=",
                    "end of trees", "tree_sizes="]:
            assert key in txt, f"missing {key!r}"
        # tree_sizes must match actual block byte lengths
        sizes = [int(s) for s in
                 txt.split("tree_sizes=")[1].splitlines()[0].split()]
        assert len(sizes) == 3

    def test_stage_persistence_roundtrip(self, binary_table, tmp_path):
        model = LightGBMClassifier(numIterations=5).fit(
            _as_table(binary_table))
        model.save(str(tmp_path / "m"))
        loaded = LightGBMClassificationModel.load(str(tmp_path / "m"))
        a = model.transform(_as_table(binary_table))["prediction"]
        b = loaded.transform(_as_table(binary_table))["prediction"]
        np.testing.assert_array_equal(a, b)

    def test_estimator_persistence(self, tmp_path):
        est = LightGBMClassifier(numIterations=7, numLeaves=5,
                                 learningRate=0.3)
        est.save(str(tmp_path / "est"))
        est2 = LightGBMClassifier.load(str(tmp_path / "est"))
        assert est2.getNumIterations() == 7
        assert est2.getNumLeaves() == 5


class TestReviewRegressions:
    def test_is_unbalance_without_boost_from_average(self):
        """prepare() must resolve class weights even when init is skipped."""
        from mmlspark_tpu.gbdt.objectives import BinaryObjective
        import jax.numpy as jnp
        y = np.array([1.0] * 90 + [0.0] * 10)
        w = np.ones(100)
        obj = BinaryObjective(is_unbalance=True)
        obj.prepare(y, w)
        # negatives are rarer -> negative class up-weighted
        g, h = obj.grad_hess(jnp.zeros(100), jnp.asarray(y), jnp.asarray(w))
        g = np.asarray(g)
        assert abs(g[99]) > abs(g[0]) * 5  # neg grad ~9x pos grad

    def test_threshold_isolating_missing_bin_exports_inf(self):
        from mmlspark_tpu.gbdt.binning import fit_bin_mapper
        X = np.array([[0.0], [1.0], [2.0], [np.nan]])
        m = fit_bin_mapper(X, max_bin=255, min_data_in_bin=1)
        assert m.bin_threshold_value(0, 250) == np.inf

    def test_bagging_seed_independent_of_seed(self, binary_table):
        t = {"features": binary_table["features"][:500],
             "label": binary_table["label"][:500]}
        kw = dict(numIterations=5, baggingFraction=0.5, baggingFreq=1)
        m1 = LightGBMClassifier(seed=1, baggingSeed=9, **kw).fit(t)
        m2 = LightGBMClassifier(seed=1, baggingSeed=10, **kw).fit(t)
        a = m1.getModel().save_native_model_string()
        b = m2.getModel().save_native_model_string()
        assert a != b  # different bagging seeds -> different forests


class TestGoss:
    def test_goss_trains_and_matches_gbdt_quality(self, binary_table):
        from sklearn.metrics import roc_auc_score
        kw = dict(numIterations=30, numLeaves=15, verbosity=0)
        plain = LightGBMClassifier(**kw).fit(binary_table)
        goss = LightGBMClassifier(boostingType="goss", topRate=0.3,
                                  otherRate=0.2, **kw).fit(binary_table)
        y = binary_table["label"]
        auc_p = roc_auc_score(y, np.asarray(
            plain.transform(binary_table)["probability"])[:, 1])
        auc_g = roc_auc_score(y, np.asarray(
            goss.transform(binary_table)["probability"])[:, 1])
        assert auc_g > auc_p - 0.02  # sampled fit stays close in quality
        assert "boosting: goss" in goss.getModel().save_native_model_string()

    def test_goss_deterministic_given_seed(self, binary_table):
        kw = dict(numIterations=5, boostingType="goss", baggingSeed=7,
                  verbosity=0)
        a = LightGBMClassifier(**kw).fit(binary_table)
        b = LightGBMClassifier(**kw).fit(binary_table)
        assert a.getModel().save_native_model_string() == \
            b.getModel().save_native_model_string()

    def test_goss_regressor(self, regression_table):
        m = LightGBMRegressor(objective="regression", boostingType="goss",
                              numIterations=10, verbosity=0).fit(
            regression_table)
        out = m.transform(regression_table)
        resid = np.asarray(out["prediction"]) - regression_table["label"]
        base = regression_table["label"] - regression_table["label"].mean()
        assert np.mean(resid ** 2) < 0.5 * np.mean(base ** 2)

    def test_goss_rejects_bagging_and_bad_rates(self, binary_table):
        import pytest
        with pytest.raises(ValueError, match="bagging in GOSS"):
            LightGBMClassifier(boostingType="goss", baggingFraction=0.5,
                               baggingFreq=1, numIterations=2).fit(
                binary_table)
        with pytest.raises(ValueError, match="otherRate"):
            LightGBMClassifier(boostingType="goss", otherRate=0.0,
                               numIterations=2).fit(binary_table)


class TestValScoreScale:
    def test_val_margins_match_model_margins(self, binary_table):
        """Early-stopping val scores must equal true model margins (the
        shrunk trees carry the learning rate already — regression test for
        the double-lr bug)."""
        from mmlspark_tpu.gbdt import engine as eng
        n = len(binary_table["label"])
        vmask = np.zeros(n, bool)
        vmask[: n // 4] = True
        t = dict(binary_table)
        t["valid"] = vmask.astype(np.float64)
        captured = {}
        orig = eng._boost_scan

        def spy(*args, **kw):
            out = orig(*args, **kw)
            # final val_scores carry — np.array (COPY), not np.asarray:
            # on CPU the latter can be a zero-copy view of an XLA buffer
            # that _boost_scan's donation/free recycles after fit(),
            # leaving the view reading reallocated garbage
            captured["val"] = np.array(out[2])
            return out
        eng._boost_scan = spy
        try:
            # parallelism="serial" pins the in-process _boost_scan path
            # (the default would auto-resolve an 8-device mesh here)
            m = LightGBMClassifier(
                numIterations=3, validationIndicatorCol="valid",
                earlyStoppingRound=100, parallelism="serial",
                verbosity=0).fit(t)
        finally:
            eng._boost_scan = orig
        margins = np.asarray(m.getModel().predict_margin(
            np.asarray(binary_table["features"])[vmask]))
        assert np.allclose(captured["val"], margins, atol=1e-4)


class TestProfiling:
    def test_profile_trace_dir_writes_trace(self, binary_table, tmp_path):
        """profileTraceDir captures a jax.profiler trace of fit and
        core.profiling.summarize_trace can aggregate it offline (SURVEY
        §5.1 subsystem; VERDICT r2 A1 flagged zero in-package profiler
        usage)."""
        from mmlspark_tpu.core import profiling
        out = str(tmp_path / "trace")
        m = LightGBMClassifier(numIterations=2, numLeaves=7, verbosity=0,
                               profileTraceDir=out).fit(binary_table)
        assert m is not None
        files = [p for _, _, fs in __import__("os").walk(out) for p in fs]
        assert files, "no trace files written"
        rows = profiling.summarize_trace(out)
        assert isinstance(rows, list)


class TestRound4Objectives:
    """gamma / tweedie / cross_entropy / multiclassova (LightGBM
    objective parity, round 4)."""

    def test_gamma_and_tweedie_learn_positive_targets(self):
        from mmlspark_tpu.gbdt import LightGBMRegressor
        rng = np.random.default_rng(0)
        X = rng.normal(size=(1500, 6))
        mu = np.exp(0.5 * X[:, 0] - 0.3 * X[:, 1])
        y = rng.gamma(shape=2.0, scale=mu / 2.0)
        t = {"features": X, "label": y}
        for obj in ("gamma", "tweedie"):
            m = LightGBMRegressor(objective=obj, numIterations=30,
                                  numLeaves=15, minDataInLeaf=5,
                                  verbosity=0).fit(t)
            pred = np.asarray(m.transform(t)["prediction"])
            assert (pred > 0).all()          # log link
            corr = np.corrcoef(pred, mu)[0, 1]
            assert corr > 0.7, (obj, corr)

    def test_tweedie_variance_power_param_changes_fit(self):
        from mmlspark_tpu.gbdt import LightGBMRegressor
        rng = np.random.default_rng(1)
        X = rng.normal(size=(800, 5))
        y = np.exp(X[:, 0]) * rng.gamma(2.0, 0.5, 800)
        t = {"features": X, "label": y}
        a = LightGBMRegressor(objective="tweedie", tweedieVariancePower=1.1,
                              numIterations=5, verbosity=0).fit(t)
        b = LightGBMRegressor(objective="tweedie", tweedieVariancePower=1.9,
                              numIterations=5, verbosity=0).fit(t)
        assert (a.getModel().save_native_model_string()
                != b.getModel().save_native_model_string())

    def test_cross_entropy_accepts_probability_labels(self):
        from mmlspark_tpu.gbdt import LightGBMClassifier
        rng = np.random.default_rng(2)
        X = rng.normal(size=(1200, 6))
        p = 1.0 / (1.0 + np.exp(-(X[:, 0] + 0.5 * X[:, 1])))
        t = {"features": X, "label": p}         # SOFT labels in [0, 1]
        m = LightGBMClassifier(objective="cross_entropy",
                               numIterations=20, numLeaves=15,
                               minDataInLeaf=5, verbosity=0).fit(t)
        pred = np.asarray(m.transform(t)["probability"])[:, 1]
        assert np.corrcoef(pred, p)[0, 1] > 0.9

    def test_multiclassova_learns_and_normalizes(self):
        from mmlspark_tpu.gbdt import LightGBMClassifier
        from sklearn.datasets import make_classification
        X, y = make_classification(n_samples=900, n_features=8,
                                   n_informative=6, n_classes=3,
                                   random_state=5)
        t = {"features": X, "label": y.astype(float)}
        m = LightGBMClassifier(objective="multiclassova",
                               numIterations=12, numLeaves=7,
                               minDataInLeaf=5, verbosity=0).fit(t)
        assert len(m.getModel().trees) == 36
        probs = np.asarray(m.transform(t)["probability"])
        np.testing.assert_allclose(probs.sum(1), 1.0, rtol=1e-5)
        acc = (np.asarray(m.transform(t)["prediction"]) == t["label"]
               ).mean()
        # OVA converges slower than softmax at equal iterations
        assert acc > 0.75

    def test_new_objectives_roundtrip_native_format(self):
        """multiclassova and tweedie models survive the text format with
        their links: loaded boosters reproduce predictions exactly."""
        from sklearn.datasets import make_classification

        from mmlspark_tpu.gbdt import LightGBMClassifier, LightGBMRegressor
        from mmlspark_tpu.gbdt.booster import Booster
        X, y = make_classification(n_samples=400, n_features=6,
                                   n_informative=4, n_classes=3,
                                   random_state=0)
        t = {"features": X, "label": y.astype(float)}
        m = LightGBMClassifier(objective="multiclassova", numIterations=3,
                               numLeaves=5, verbosity=0).fit(t)
        b2 = Booster.load_native_model_string(
            m.getModel().save_native_model_string())
        assert b2.num_class == 3
        np.testing.assert_allclose(np.asarray(m.getModel().predict(X)),
                                   np.asarray(b2.predict(X)), rtol=1e-5)
        yr = np.abs(X[:, 0]) + 0.1
        r = LightGBMRegressor(objective="tweedie", numIterations=3,
                              verbosity=0).fit(
            {"features": X, "label": yr})
        b3 = Booster.load_native_model_string(
            r.getModel().save_native_model_string())
        p3 = np.asarray(b3.predict(X))
        assert (p3 > 0).all()              # log link survives the file
        np.testing.assert_allclose(np.asarray(r.getModel().predict(X)),
                                   p3, rtol=1e-5)


class TestPassThroughArgs:
    """passThroughArgs reach the engine like the reference's reach native
    LightGBM: keys naming TrainParams fields apply (string-coerced), the
    rest are recorded into the model file verbatim."""

    def test_pass_through_applies_and_records(self):
        from mmlspark_tpu.gbdt import LightGBMClassifier
        rng = np.random.default_rng(0)
        X = rng.normal(size=(400, 5)).astype(np.float32)
        y = (X[:, 0] > 0).astype(float)
        t = {"features": X, "label": y}
        m = LightGBMClassifier(
            numIterations=3, numLeaves=31, verbosity=0,
            passThroughArgs="num_leaves=5 custom_tag=abc").fit(t)
        s = m.getModel().save_native_model_string()
        # num_leaves=5 overrode the typed 31: no tree has >5 leaves
        for tr in m.getModel().trees:
            assert tr.num_leaves <= 5
        assert "[custom_tag: abc]" in s


# ------------------------------------------- the histogram cache's slots

_GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
_GROWERS = {
    "serial": {},
    "masked": {"compact_rows": False},
    "quantized": {"quantized_bits": 8, "quantized_max_code": 127},
}


def _grow_fixed(kind, **cfg_kw):
    """One tree at a fixed seed through ``grow_tree`` itself (no
    estimator in between), by the named grower."""
    import jax.numpy as jnp
    from mmlspark_tpu.gbdt.grower import (GrowerConfig, grow_tree,
                                          make_feat_info)
    rng = np.random.default_rng(26)
    n, f, B = 2500, 9, 64
    bins = rng.integers(0, B, size=(n, f)).astype(np.uint8)
    y = ((bins[:, 0] > 30) * 1.0 + (bins[:, 3] > 11) * (bins[:, 5] < 40)
         + rng.normal(scale=0.2, size=n)).astype(np.float32)
    gh = np.stack([y - y.mean(), np.ones(n, np.float32),
                   np.ones(n, np.float32)], axis=1).astype(np.float32)
    kw = dict(num_leaves=24, num_bins=B, min_data_in_leaf=5,
              hist_method="dot16")
    kw.update(_GROWERS[kind])
    kw.update(cfg_kw)
    tree, row_leaf = grow_tree(jnp.asarray(bins), jnp.asarray(gh),
                               make_feat_info(f), GrowerConfig(**kw))
    return ({k: np.asarray(v) for k, v in tree._asdict().items()},
            np.asarray(row_leaf))


class TestHistCacheSlots:
    """The split loop writes the new leaf's cache row without reading it
    first (that read kept the whole cache's old buffer alive and cost two
    whole-cache copies a split on the TPU: PERF.md Findings, PR 26).
    What that rests on: slot ``i + 1`` is first written at step ``i``,
    and a step that does not split leaves nothing behind that a later
    step reads.  The compiled program itself is guarded where a TPU
    compiler is (tests/test_mosaic_aot.py)."""

    @pytest.mark.parametrize("kind", sorted(_GROWERS))
    def test_growth_that_stops_early_equals_the_short_loop(self, kind):
        """Most of the 254 steps are inactive here; the tree must be the
        one a loop of exactly the reached length grows."""
        long_t, long_rl = _grow_fixed(kind, num_leaves=255,
                                      min_data_in_leaf=300)
        nl = int(long_t["num_leaves"])
        assert 3 <= nl <= 8, nl
        short_t, short_rl = _grow_fixed(kind, num_leaves=nl,
                                        min_data_in_leaf=300)
        assert int(short_t["num_leaves"]) == nl
        np.testing.assert_array_equal(long_rl, short_rl)
        for k, v in short_t.items():
            if v.ndim == 0:
                continue
            np.testing.assert_array_equal(long_t[k][:v.shape[0]], v, k)
            # the steps that did not split wrote nothing
            assert not long_t[k][v.shape[0]:].any(), k

    @pytest.mark.parametrize("kind", sorted(_GROWERS))
    def test_tree_equals_the_parent_commits_record(self, kind):
        """tests/golden/grower_<kind>.json was taken from the commit
        before the ungated write (e9b88e5): structure exact, values to
        1e-6."""
        with open(os.path.join(_GOLDEN_DIR, f"grower_{kind}.json")) as fh:
            want = json.load(fh)
        got, _ = _grow_fixed(kind)
        assert int(got["num_leaves"]) == want["num_leaves"]
        for k in ("node_feat", "node_bin", "node_left", "node_right",
                  "leaf_count", "node_count"):
            np.testing.assert_array_equal(got[k], np.asarray(want[k]), k)
        for k in ("leaf_value", "node_value", "leaf_weight"):
            np.testing.assert_allclose(got[k], np.asarray(want[k]),
                                       rtol=0, atol=1e-6, err_msg=k)
