"""Distributed GBDT: shard_map/psum training must match single-device."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from mmlspark_tpu.core.mesh import DATA_AXIS, FEATURE_AXIS, build_mesh
from mmlspark_tpu.gbdt import LightGBMClassifier, LightGBMRegressor


@pytest.fixture(scope="module")
def small_binary(rng=np.random.default_rng(5)):
    from sklearn.datasets import make_classification
    X, y = make_classification(n_samples=803, n_features=11,  # odd on purpose
                               n_informative=7, random_state=5)
    return {"features": X, "label": y.astype(float)}


def _serial_mesh():
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                (DATA_AXIS, FEATURE_AXIS))


def _forest_string(model):
    return model.getModel().save_native_model_string()


class TestDistributedParity:
    def test_data_parallel_identical_to_serial(self, small_binary):
        kw = dict(numIterations=8, numLeaves=7, minDataInLeaf=5)
        serial = LightGBMClassifier(**kw).setMesh(_serial_mesh()).fit(
            small_binary)
        dp = LightGBMClassifier(**kw).setMesh(build_mesh(data=8, feature=1)) \
            .fit(small_binary)
        # psum changes float summation order; trees must still be
        # structurally identical and leaf values equal to ~1e-4
        st, dt = serial.getModel().trees, dp.getModel().trees
        assert len(st) == len(dt)
        for a, b in zip(st, dt):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)
            np.testing.assert_array_equal(a.left_child, b.left_child)
            np.testing.assert_allclose(a.threshold, b.threshold, rtol=1e-6)
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=2e-3, atol=1e-5)

    def test_feature_parallel_identical_to_serial(self, small_binary):
        kw = dict(numIterations=6, numLeaves=7, minDataInLeaf=5)
        serial = LightGBMClassifier(**kw).setMesh(_serial_mesh()).fit(
            small_binary)
        fp = LightGBMClassifier(**kw, parallelism="feature").setMesh(
            build_mesh(data=1, feature=8)).fit(small_binary)
        st, ft = serial.getModel().trees, fp.getModel().trees
        assert len(st) == len(ft)
        for a, b in zip(st, ft):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=2e-3, atol=1e-5)

    def test_2d_mesh_trains(self, small_binary):
        model = LightGBMClassifier(numIterations=4, numLeaves=7,
                                   minDataInLeaf=5).setMesh(
            build_mesh(data=4, feature=2)).fit(small_binary)
        out = model.transform(small_binary)
        from sklearn.metrics import roc_auc_score
        auc = roc_auc_score(small_binary["label"], out["probability"][:, 1])
        assert auc > 0.85

    def test_distributed_regressor(self, regression_table):
        from sklearn.metrics import r2_score
        model = LightGBMRegressor(numIterations=20, numLeaves=15,
                                  minDataInLeaf=5).setMesh(
            build_mesh(data=8)).fit(
            {"features": regression_table["features"],
             "label": regression_table["label"]})
        out = model.transform(regression_table)
        assert r2_score(regression_table["label"], out["prediction"]) > 0.6

    def test_default_fit_uses_all_devices(self, small_binary):
        # no explicit mesh: with 8 virtual devices the data-parallel path
        # must engage and still produce a working model
        assert jax.device_count() == 8
        model = LightGBMClassifier(numIterations=4, numLeaves=7).fit(
            small_binary)
        out = model.transform(small_binary)
        assert np.isfinite(out["probability"]).all()


class TestDistributedGuards:
    def test_mesh_plus_validation_trains(self, small_binary):
        # mesh + validation/early stopping is supported since round 3
        # (VERDICT r2 next #3); only callbacks still require no mesh
        import numpy as np
        d = dict(small_binary)
        d["isVal"] = np.arange(len(d["label"])) % 4 == 0
        est = LightGBMClassifier(numIterations=3, earlyStoppingRound=2,
                                 validationIndicatorCol="isVal",
                                 verbosity=0).setMesh(build_mesh(data=8))
        model = est.fit(d)
        assert len(model.getModel().trees) >= 1

    def test_bad_parallelism_raises(self):
        from mmlspark_tpu.gbdt.distributed import resolve_mesh
        with pytest.raises(ValueError):
            resolve_mesh("data_parallel")

    def test_data_feature_2d_mesh(self):
        from mmlspark_tpu.gbdt.distributed import resolve_mesh
        m = resolve_mesh("data+feature")
        assert m.shape == {"data": 4, "feature": 2}

    def test_multiclass_distributed_matches_serial(self):
        import numpy as np
        from sklearn.datasets import make_classification
        X, y = make_classification(n_samples=600, n_features=8,
                                   n_informative=6, n_classes=3,
                                   random_state=2)
        d = {"features": X, "label": y.astype(float)}
        kw = dict(numIterations=3, numLeaves=5, minDataInLeaf=5)
        serial = LightGBMClassifier(**kw).setMesh(_serial_mesh()).fit(d)
        dist = LightGBMClassifier(**kw).setMesh(build_mesh(data=8)).fit(d)
        st, dt = serial.getModel().trees, dist.getModel().trees
        assert len(st) == len(dt) == 9  # 3 iters x 3 classes
        for a, b in zip(st, dt):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=2e-3, atol=1e-5)

    def test_init_score_col_used(self, small_binary):
        import numpy as np
        d = dict(small_binary)
        base = LightGBMClassifier(numIterations=3, numLeaves=5).fit(d)
        d["is"] = np.full(len(d["label"]), 2.0)  # strong positive prior
        warm = LightGBMClassifier(numIterations=3, numLeaves=5,
                                  initScoreCol="is").fit(d)
        a = base.getModel().save_native_model_string()
        b = warm.getModel().save_native_model_string()
        assert a != b  # init scores change the fit


class TestDistributedValidation:
    """Early stopping / validation under a mesh (VERDICT r2 next #3):
    the mesh-sharded validation path must reproduce the serial path's
    stopping decision and final model."""

    @pytest.fixture(scope="class")
    def val_table(self):
        from sklearn.datasets import make_classification
        X, y = make_classification(n_samples=901, n_features=10,
                                   n_informative=6, random_state=11)
        t = {"features": X, "label": y.astype(float)}
        vmask = np.zeros(len(y), bool)
        vmask[::4] = True
        t["valid"] = vmask.astype(np.float64)
        return t

    def test_early_stopping_parity_with_serial(self, val_table):
        kw = dict(numIterations=40, numLeaves=7, minDataInLeaf=5,
                  validationIndicatorCol="valid", earlyStoppingRound=3,
                  verbosity=0)
        serial = LightGBMClassifier(**kw).setMesh(_serial_mesh()).fit(
            val_table)
        dp = LightGBMClassifier(**kw).setMesh(
            build_mesh(data=8, feature=1)).fit(val_table)
        st, dt = serial.getModel().trees, dp.getModel().trees
        # identical stopping iteration and identical tree structure
        assert len(st) == len(dt)
        for a, b in zip(st, dt):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=2e-3, atol=1e-5)

    def test_early_stopping_triggers_under_mesh(self, val_table):
        full = LightGBMClassifier(
            numIterations=60, numLeaves=7, minDataInLeaf=5,
            verbosity=0).setMesh(build_mesh(data=4, feature=2)).fit(
            val_table)
        stopped = LightGBMClassifier(
            numIterations=60, numLeaves=7, minDataInLeaf=5,
            validationIndicatorCol="valid", earlyStoppingRound=2,
            verbosity=0).setMesh(build_mesh(data=4, feature=2)).fit(
            val_table)
        assert len(stopped.getModel().trees) < len(full.getModel().trees)

    def test_2d_mesh_validation_parity(self, val_table):
        kw = dict(numIterations=20, numLeaves=7, minDataInLeaf=5,
                  validationIndicatorCol="valid", earlyStoppingRound=4,
                  verbosity=0)
        serial = LightGBMClassifier(**kw).setMesh(_serial_mesh()).fit(
            val_table)
        d2 = LightGBMClassifier(**kw).setMesh(
            build_mesh(data=4, feature=2)).fit(val_table)
        assert len(serial.getModel().trees) == len(d2.getModel().trees)
        X = np.asarray(val_table["features"])
        np.testing.assert_allclose(
            np.asarray(serial.getModel().predict_margin(X)),
            np.asarray(d2.getModel().predict_margin(X)),
            rtol=5e-3, atol=1e-4)


class TestDistributedRanking:
    """Mesh-sharded lambdarank (VERDICT r2 next #3): whole queries packed
    per data shard, pairwise gradients shard-local, psum histograms."""

    @pytest.fixture(scope="class")
    def rank_table(self):
        rng = np.random.default_rng(17)
        n_q, rows_q = 60, 15
        rows = []
        for q in range(n_q):
            m = rng.integers(5, rows_q + 1)
            X = rng.normal(size=(m, 8))
            rel = np.clip((X[:, 0] * 1.2 + X[:, 1]
                           + rng.normal(size=m) * 0.3) * 1.2 + 1.5,
                          0, 4).astype(int)
            rows.append((X, rel, np.full(m, q)))
        X = np.concatenate([r[0] for r in rows])
        y = np.concatenate([r[1] for r in rows]).astype(np.float64)
        q = np.concatenate([r[2] for r in rows]).astype(np.int64)
        return {"features": X, "label": y, "query": q}

    def test_mesh_ranker_parity_with_serial(self, rank_table):
        from mmlspark_tpu.gbdt import LightGBMRanker
        kw = dict(numIterations=8, numLeaves=7, minDataInLeaf=3,
                  verbosity=0)
        serial = LightGBMRanker(**kw).fit(rank_table)
        dist = LightGBMRanker(**kw).setMesh(
            build_mesh(data=8, feature=1)).fit(rank_table)
        st, dt = serial.getModel().trees, dist.getModel().trees
        assert len(st) == len(dt)
        # query packing changes float summation order inside histograms;
        # tree structure must match, leaf values to float tolerance
        for a, b in zip(st, dt):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=5e-3, atol=1e-4)

    def test_mesh_ranker_learns(self, rank_table):
        from mmlspark_tpu.gbdt import LightGBMRanker
        from mmlspark_tpu.gbdt.ranking import ndcg_at_k
        m = LightGBMRanker(numIterations=20, numLeaves=15, minDataInLeaf=3,
                           verbosity=0).setMesh(
            build_mesh(data=4, feature=2)).fit(rank_table)
        out = m.transform(rank_table)
        ndcg = ndcg_at_k(np.asarray(out["prediction"]),
                         np.asarray(rank_table["label"]),
                         np.asarray(rank_table["query"]), k=10)
        assert ndcg > 0.75

    def test_mesh_ranker_early_stopping(self, rank_table):
        from mmlspark_tpu.gbdt import LightGBMRanker
        t = dict(rank_table)
        q = np.asarray(t["query"])
        vmask = (q % 5 == 0)          # whole queries go to validation
        t["valid"] = vmask.astype(np.float64)
        m = LightGBMRanker(numIterations=40, numLeaves=7, minDataInLeaf=3,
                           validationIndicatorCol="valid",
                           earlyStoppingRound=3, verbosity=0).setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        assert 1 <= len(m.getModel().trees) <= 40


class TestVotingParallel:
    """True PV-Tree voting parallelism (VERDICT r2 next #4): per-shard
    top-k feature votes, allgathered; full histograms psum-reduced ONLY
    for the 2k voted candidates."""

    @pytest.fixture(scope="class")
    def wide_table(self):
        from sklearn.datasets import make_classification
        X, y = make_classification(n_samples=1200, n_features=24,
                                   n_informative=6, n_redundant=2,
                                   random_state=3, class_sep=1.5)
        return {"features": X, "label": y.astype(float)}

    def test_voting_full_k_identical_to_data_parallel(self, wide_table):
        """top_k >= f votes every feature, so voting must reproduce the
        data-parallel learner exactly."""
        kw = dict(numIterations=6, numLeaves=7, minDataInLeaf=5,
                  verbosity=0)
        dp = LightGBMClassifier(**kw, parallelism="data").setMesh(
            build_mesh(data=8, feature=1)).fit(wide_table)
        vt = LightGBMClassifier(**kw, parallelism="voting", topK=24
                                ).setMesh(build_mesh(data=8, feature=1)
                                          ).fit(wide_table)
        st, vtr = dp.getModel().trees, vt.getModel().trees
        assert len(st) == len(vtr)
        for a, b in zip(st, vtr):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=2e-3, atol=1e-5)

    def test_voting_small_k_matches_on_separable_data(self, wide_table):
        """With clear top features, k=4 voting finds the same splits as
        exact data-parallel (the PV-Tree accuracy claim)."""
        kw = dict(numIterations=6, numLeaves=7, minDataInLeaf=5,
                  verbosity=0)
        dp = LightGBMClassifier(**kw, parallelism="data").setMesh(
            build_mesh(data=8, feature=1)).fit(wide_table)
        vt = LightGBMClassifier(**kw, parallelism="voting", topK=4
                                ).setMesh(build_mesh(data=8, feature=1)
                                          ).fit(wide_table)
        for a, b in zip(dp.getModel().trees, vt.getModel().trees):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)

    def test_voting_wide_table_smoke(self):
        """Tier-1 wide-table smoke (ISSUE 16): a 2000-feature voting fit
        on the select-ring path trains, predicts, and journals a voted
        payload that undercuts the dense reduce by the PV-Tree margin."""
        rng = np.random.default_rng(16)
        X = rng.normal(size=(512, 2000))
        y = (X[:, 0] + 0.5 * X[:, 7] - X[:, 11] > 0).astype(float)
        t = {"features": X, "label": y}
        m = LightGBMClassifier(numIterations=2, numLeaves=7,
                               minDataInLeaf=5, maxBin=15,
                               parallelism="voting", topK=16,
                               collective="ring", verbosity=0).setMesh(
            build_mesh(data=2, feature=1,
                       devices=jax.devices()[:2])).fit(t)
        assert len(m.getModel().trees) == 2
        p = np.asarray(m.transform(t)["probability"])
        assert p.shape[0] == 512 and np.all((p >= 0) & (p <= 1))
        from mmlspark_tpu.gbdt.engine import last_fit_info
        assert last_fit_info["collective"] == "ring"
        assert last_fit_info["collective_downgrade"] == "none"
        # voted payload per tree must undercut the dense (f,B,3) reduce
        assert float(last_fit_info["collective_payload_vs_dense"]) < 0.15
        # one batched collective per grow step: count <= num_leaves
        assert int(last_fit_info["collective_count_per_tree"]) <= 7
        # ... and the profiler counter pair accumulated per boost chunk
        from mmlspark_tpu.gbdt.engine import train_stats
        assert train_stats.counter("collective_count") > 0
        assert train_stats.counter("collective_payload_bytes") > 0
        from mmlspark_tpu.core.telemetry import get_registry
        text = get_registry().render_prometheus()
        assert 'event="collective_count",ns="train"' in text
        assert 'event="collective_payload_bytes",ns="train"' in text

    def test_voting_reduces_allreduce_bytes(self):
        """Compile the voting boost step and assert the histogram
        all-reduce moves (2k, B, 3) — not (f, B, 3) — per split: the
        PV-Tree communication claim, checked against the HLO."""
        import jax.numpy as jnp
        from mmlspark_tpu.core.mesh import build_mesh as bm
        from mmlspark_tpu.gbdt.distributed import make_boost_scan
        from mmlspark_tpu.gbdt.grower import GrowerConfig
        from mmlspark_tpu.gbdt.objectives import BinaryObjective

        f, B, k, n, C = 64, 64, 4, 1024, 2
        mesh = bm(data=8, feature=1)
        obj = BinaryObjective()
        obj.prepare(np.zeros(8), np.ones(8))
        cfg_v = GrowerConfig(num_leaves=7, num_bins=B, min_data_in_leaf=2,
                             voting_k=k, hist_method="segment")
        step = make_boost_scan(mesh, obj, cfg_v, 0.1, bag_sharded=False)
        args = (jax.ShapeDtypeStruct((n, f), jnp.uint8),
                jax.ShapeDtypeStruct((n,), jnp.float32),
                jax.ShapeDtypeStruct((n,), jnp.float32),
                jax.ShapeDtypeStruct((n,), jnp.float32),
                jax.ShapeDtypeStruct((n,), jnp.float32),
                jax.ShapeDtypeStruct((C, 1), jnp.float32),
                jax.ShapeDtypeStruct((C, f, 3), jnp.float32),
                jax.ShapeDtypeStruct((8, f), jnp.uint8),
                jax.ShapeDtypeStruct((8,), jnp.float32))
        hlo = step.lower(*args).compile().as_text()
        import re
        reduced = re.findall(r"all-reduce[^\n]*f32\[(\d+),%?(\d+),3\]", hlo)
        shapes = {(int(a), int(b)) for a, b in reduced}
        assert (2 * k, B) in shapes, shapes
        assert (f, B) not in shapes, "full-histogram all-reduce present"


class TestDistributedBoostingModes:
    """GOSS and rf under a mesh (round-2 gap: engine raised for both)."""

    @pytest.fixture(scope="class")
    def mode_table(self):
        from sklearn.datasets import make_classification
        X, y = make_classification(n_samples=1600, n_features=10,
                                   n_informative=6, random_state=21)
        return {"features": X, "label": y.astype(float)}

    def test_mesh_goss_learns(self, mode_table):
        from sklearn.metrics import roc_auc_score
        m = LightGBMClassifier(boostingType="goss", numIterations=20,
                               numLeaves=15, minDataInLeaf=5,
                               verbosity=0).setMesh(
            build_mesh(data=8, feature=1)).fit(mode_table)
        out = m.transform(mode_table)
        auc = roc_auc_score(mode_table["label"],
                            np.asarray(out["probability"])[:, 1])
        assert auc > 0.9

    def test_mesh_goss_deterministic(self, mode_table):
        kw = dict(boostingType="goss", numIterations=6, numLeaves=7,
                  minDataInLeaf=5, verbosity=0)
        a = LightGBMClassifier(**kw).setMesh(
            build_mesh(data=8, feature=1)).fit(mode_table)
        b = LightGBMClassifier(**kw).setMesh(
            build_mesh(data=8, feature=1)).fit(mode_table)
        assert (a.getModel().save_native_model_string()
                == b.getModel().save_native_model_string())

    def test_mesh_rf_matches_serial_rf(self, mode_table):
        kw = dict(boostingType="rf", numIterations=6, numLeaves=15,
                  minDataInLeaf=5, baggingFraction=0.6, baggingFreq=1,
                  verbosity=0)
        serial = LightGBMClassifier(**kw).setMesh(_serial_mesh()).fit(
            mode_table)
        dist = LightGBMClassifier(**kw).setMesh(
            build_mesh(data=8, feature=1)).fit(mode_table)
        st, dt = serial.getModel().trees, dist.getModel().trees
        assert len(st) == len(dt)
        assert all(abs(t.shrinkage - 1 / 6) < 1e-12 for t in dt)
        for a, b in zip(st, dt):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=2e-3, atol=1e-5)


class TestMeshModeMatrix:
    """Round-4 matrix completion (VERDICT r3 next #3): dart under mesh,
    callbacks under mesh, goss/rf multiclass, voting x categorical — the
    reference's single engine supports every boosting mode under every
    deployment shape (SURVEY.md §2.1, §3.1)."""

    @pytest.fixture(scope="class")
    def mode_table(self):
        from sklearn.datasets import make_classification
        X, y = make_classification(n_samples=1200, n_features=10,
                                   n_informative=6, random_state=31)
        return {"features": X, "label": y.astype(float)}

    @pytest.fixture(scope="class")
    def multi_table(self):
        from sklearn.datasets import make_classification
        X, y = make_classification(n_samples=900, n_features=8,
                                   n_informative=6, n_classes=3,
                                   random_state=32)
        return {"features": X, "label": y.astype(float)}

    def test_mesh_dart_matches_serial_dart(self, mode_table):
        """Same dropSeed => identical dropout schedule and identical
        ensemble structure, serial vs 8-shard mesh (dropout bookkeeping is
        host-side in both; only the fit rides the mesh)."""
        kw = dict(boostingType="dart", numIterations=8, numLeaves=7,
                  minDataInLeaf=5, dropRate=0.5, verbosity=0)
        serial = LightGBMClassifier(**kw).fit(mode_table)
        dist = LightGBMClassifier(**kw).setMesh(
            build_mesh(data=8, feature=1)).fit(mode_table)
        st, dt = serial.getModel().trees, dist.getModel().trees
        assert len(st) == len(dt)
        for a, b in zip(st, dt):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)
            assert abs(a.shrinkage - b.shrinkage) < 1e-12
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=2e-3, atol=1e-5)

    def test_mesh_dart_learns(self, mode_table):
        from sklearn.metrics import roc_auc_score
        m = LightGBMClassifier(boostingType="dart", numIterations=15,
                               numLeaves=15, minDataInLeaf=5,
                               dropRate=0.3, verbosity=0).setMesh(
            build_mesh(data=8, feature=1)).fit(mode_table)
        out = m.transform(mode_table)
        auc = roc_auc_score(mode_table["label"],
                            np.asarray(out["probability"])[:, 1])
        assert auc > 0.9

    def test_mesh_dart_trains_on_2d_mesh(self, mode_table):
        # the data-only restriction fell: the dropped-tree score update
        # walks feature-sharded rows via per-level psum (see
        # tests/test_dart_rf.py::TestFeatureMeshDartGoss for parity)
        m = LightGBMClassifier(boostingType="dart", numIterations=2,
                               numLeaves=5, verbosity=0).setMesh(
            build_mesh(data=4, feature=2)).fit(mode_table)
        assert len(m.getModel().trees) == 2

    def test_mesh_callbacks_replayed_per_iteration(self, mode_table):
        """Callbacks fire once per global iteration with the flat list of
        trees so far — the serial engine contract, now under a mesh."""
        from mmlspark_tpu.gbdt.binning import fit_bin_mapper
        from mmlspark_tpu.gbdt.engine import TrainParams, train
        from mmlspark_tpu.gbdt.objectives import BinaryObjective

        calls = []

        def cb(it, trees):
            calls.append((it, len(trees)))

        X = np.asarray(mode_table["features"])
        y = np.asarray(mode_table["label"])
        mapper = fit_bin_mapper(X, max_bin=63, seed=0)
        train(mapper.transform_packed(X), y, None, mapper,
              BinaryObjective(),
              TrainParams(num_iterations=10, num_leaves=7,
                          min_data_in_leaf=5, verbosity=0),
              mesh=build_mesh(data=8, feature=1), callbacks=[cb])
        assert [c[0] for c in calls] == list(range(10))
        assert [c[1] for c in calls] == list(range(1, 11))

    def test_mesh_goss_multiclass_learns(self, multi_table):
        m = LightGBMClassifier(boostingType="goss", numIterations=12,
                               numLeaves=7, minDataInLeaf=5,
                               verbosity=0).setMesh(
            build_mesh(data=8, feature=1)).fit(multi_table)
        out = m.transform(multi_table)
        acc = (np.asarray(out["prediction"])
               == multi_table["label"]).mean()
        assert len(m.getModel().trees) == 36  # 12 iters x 3 classes
        # GOSS trains on the (topRate+otherRate) influence sample, so it
        # trails plain gbdt at small iteration counts; 0.78 on 3 classes
        # still proves per-class trees are learning from the shared sample
        assert acc > 0.78

    def test_serial_goss_multiclass_learns(self, multi_table):
        m = LightGBMClassifier(boostingType="goss", numIterations=12,
                               numLeaves=7, minDataInLeaf=5,
                               verbosity=0).fit(multi_table)
        out = m.transform(multi_table)
        acc = (np.asarray(out["prediction"])
               == multi_table["label"]).mean()
        assert acc > 0.78

    def test_mesh_rf_multiclass_matches_serial(self, multi_table):
        kw = dict(boostingType="rf", numIterations=4, numLeaves=7,
                  minDataInLeaf=5, baggingFraction=0.6, baggingFreq=1,
                  verbosity=0)
        serial = LightGBMClassifier(**kw).setMesh(_serial_mesh()).fit(
            multi_table)
        dist = LightGBMClassifier(**kw).setMesh(
            build_mesh(data=8, feature=1)).fit(multi_table)
        st, dt = serial.getModel().trees, dist.getModel().trees
        assert len(st) == len(dt) == 12  # 4 iters x 3 classes
        assert all(abs(t.shrinkage - 1 / 4) < 1e-12 for t in dt)
        for a, b in zip(st, dt):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=2e-3, atol=1e-5)


class TestVotingCategorical:
    """Voting parallelism with categorical features (VERDICT r3 next #3):
    categoricals vote with their local Fisher-grouping gain and get the
    exact sorted-subset search over the psum-reduced candidates."""

    @pytest.fixture(scope="class")
    def cat_table(self, ):
        rng = np.random.default_rng(7)
        n = 1600
        c = rng.integers(0, 12, n)
        x1 = rng.normal(size=n)
        x2 = rng.normal(size=n)
        # class depends on categorical membership + one numeric margin
        logit = 2.0 * np.isin(c, [1, 4, 7, 9]) - 1.0 + 0.8 * x1
        y = (logit + rng.normal(scale=0.6, size=n) > 0).astype(float)
        X = np.column_stack([c.astype(float), x1, x2,
                             rng.normal(size=(n, 5))])
        return {"features": X, "label": y}

    def test_voting_categorical_full_k_matches_data_parallel(self,
                                                             cat_table):
        kw = dict(numIterations=6, numLeaves=7, minDataInLeaf=5,
                  categoricalSlotIndexes=[0], verbosity=0)
        dp = LightGBMClassifier(**kw, parallelism="data").setMesh(
            build_mesh(data=8, feature=1)).fit(cat_table)
        vt = LightGBMClassifier(**kw, parallelism="voting", topK=8
                                ).setMesh(build_mesh(data=8, feature=1)
                                          ).fit(cat_table)
        st, vtr = dp.getModel().trees, vt.getModel().trees
        assert len(st) == len(vtr)
        for a, b in zip(st, vtr):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=2e-3, atol=1e-5)

    def test_voting_categorical_uses_cat_split_and_learns(self, cat_table):
        from sklearn.metrics import roc_auc_score
        m = LightGBMClassifier(numIterations=10, numLeaves=7,
                               minDataInLeaf=5, parallelism="voting",
                               topK=3, categoricalSlotIndexes=[0],
                               verbosity=0).setMesh(
            build_mesh(data=8, feature=1)).fit(cat_table)
        trees = m.getModel().trees
        assert any((np.asarray(t.decision_type) & 1).any() for t in trees
                   ), "expected at least one categorical split"
        out = m.transform(cat_table)
        auc = roc_auc_score(cat_table["label"],
                            np.asarray(out["probability"])[:, 1])
        assert auc > 0.9


class TestVotingApproximation:
    """Voting's FAILURE mode (VERDICT r3 weak #5): when topK is genuinely
    too small for the number of equally-informative features, PV-Tree may
    miss the exact best split — the degradation must be graceful (bounded
    AUC loss vs exact data-parallel), which is the PV-Tree paper's claim
    and what a user who under-sizes topK will actually experience."""

    def test_voting_tiny_k_degrades_gracefully(self):
        from sklearn.datasets import make_classification
        from sklearn.metrics import roc_auc_score
        # many features of comparable informativeness: local votes across
        # shards genuinely disagree, so k=2 of 32 CAN miss the global best
        X, y = make_classification(n_samples=2000, n_features=32,
                                   n_informative=20, n_redundant=0,
                                   class_sep=0.8, random_state=17)
        t = {"features": X, "label": y.astype(float)}
        kw = dict(numIterations=12, numLeaves=15, minDataInLeaf=5,
                  verbosity=0)
        dp = LightGBMClassifier(**kw, parallelism="data").setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        vt = LightGBMClassifier(**kw, parallelism="voting", topK=2).setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        auc_dp = roc_auc_score(y, np.asarray(
            dp.transform(t)["probability"])[:, 1])
        auc_vt = roc_auc_score(y, np.asarray(
            vt.transform(t)["probability"])[:, 1])
        # the approximation differs from exact...
        assert (dp.getModel().save_native_model_string()
                != vt.getModel().save_native_model_string())
        # ...but degrades gracefully: bounded AUC loss, still a model
        assert auc_vt > auc_dp - 0.05
        assert auc_vt > 0.85


class TestMeshRankingGoss:
    """GOSS under mesh lambdarank (distributed LightGBM supports
    boosting=goss with a ranking objective): gradients stay full per
    query, only tree growth samples per shard."""

    def _rank_table(self):
        rng = np.random.default_rng(5)
        n_q, group, f = 100, 12, 8
        n = n_q * group
        X = rng.normal(size=(n, f))
        w = rng.normal(size=f)
        util = X @ w + rng.normal(size=n) * 0.5
        q = np.repeat(np.arange(n_q), group)
        labels = np.zeros(n)
        for qq in range(n_q):
            m = q == qq
            labels[m] = np.clip(np.digitize(
                util[m], np.quantile(util[m], [0.5, 0.75, 0.9])), 0, 3)
        return {"features": X, "label": labels, "query": q}

    def test_mesh_goss_ranker_learns(self):
        from mmlspark_tpu.gbdt import LightGBMRanker, ndcg_at_k
        t = self._rank_table()
        m = LightGBMRanker(boostingType="goss", numIterations=20,
                           numLeaves=15, minDataInLeaf=5,
                           groupCol="query", verbosity=0).setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        out = m.transform(t)
        ndcg = float(np.mean(ndcg_at_k(np.asarray(out["prediction"]),
                                       t["label"], t["query"], 5)))
        assert ndcg > 0.75

    def test_mesh_goss_ranker_deterministic(self):
        from mmlspark_tpu.gbdt import LightGBMRanker
        t = self._rank_table()
        kw = dict(boostingType="goss", numIterations=5, numLeaves=7,
                  minDataInLeaf=5, groupCol="query", verbosity=0)
        a = LightGBMRanker(**kw).setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        b = LightGBMRanker(**kw).setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        assert (a.getModel().save_native_model_string()
                == b.getModel().save_native_model_string())


class TestVotingMulticlass:
    """Voting parallelism x multiclass: per-class trees each run the
    PV-Tree two-phase vote over the shared data-sharded histograms."""

    def test_voting_full_k_matches_data_parallel_multiclass(self):
        from sklearn.datasets import make_classification
        X, y = make_classification(n_samples=900, n_features=10,
                                   n_informative=6, n_classes=3,
                                   random_state=12)
        t = {"features": X, "label": y.astype(float)}
        kw = dict(numIterations=4, numLeaves=7, minDataInLeaf=5,
                  verbosity=0)
        dp = LightGBMClassifier(**kw, parallelism="data").setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        vt = LightGBMClassifier(**kw, parallelism="voting", topK=10
                                ).setMesh(build_mesh(data=8, feature=1)
                                          ).fit(t)
        st, vtr = dp.getModel().trees, vt.getModel().trees
        assert len(st) == len(vtr) == 12
        for a, b in zip(st, vtr):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=2e-3, atol=1e-5)


class TestMeshRankingBaggingRf:
    """Bagging and rf under mesh lambdarank (round-4 matrix completion):
    the bagging stream draws over ORIGINAL row order and scatters through
    the query-pack permutation, so a mesh run reproduces the serial
    ranker's stream semantics; rf fits unshrunk trees at constant init
    scores with per-export averaging."""

    def _rank_table(self):
        rng = np.random.default_rng(9)
        n_q, group, f = 90, 10, 8
        n = n_q * group
        X = rng.normal(size=(n, f))
        w = rng.normal(size=f)
        util = X @ w + rng.normal(size=n) * 0.5
        q = np.repeat(np.arange(n_q), group)
        labels = np.zeros(n)
        for qq in range(n_q):
            m = q == qq
            labels[m] = np.clip(np.digitize(
                util[m], np.quantile(util[m], [0.5, 0.8])), 0, 2)
        return {"features": X, "label": labels, "query": q}

    def test_mesh_bagged_ranker_learns_and_is_deterministic(self):
        from mmlspark_tpu.gbdt import LightGBMRanker, ndcg_at_k
        t = self._rank_table()
        kw = dict(numIterations=15, numLeaves=15, minDataInLeaf=5,
                  baggingFraction=0.7, baggingFreq=2, groupCol="query",
                  verbosity=0)
        a = LightGBMRanker(**kw).setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        b = LightGBMRanker(**kw).setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        assert (a.getModel().save_native_model_string()
                == b.getModel().save_native_model_string())
        out = a.transform(t)
        ndcg = float(np.mean(ndcg_at_k(np.asarray(out["prediction"]),
                                       t["label"], t["query"], 5)))
        assert ndcg > 0.75

    def test_mesh_bagged_ranker_matches_serial_structure(self):
        """After the count-channel fix, a bagged mesh ranker sees the
        same per-leaf sample counts as the serial loop: same baggingSeed
        => same split structure."""
        from mmlspark_tpu.gbdt import LightGBMRanker
        t = self._rank_table()
        kw = dict(numIterations=5, numLeaves=7, minDataInLeaf=5,
                  baggingFraction=0.6, baggingFreq=1, groupCol="query",
                  verbosity=0)
        serial = LightGBMRanker(**kw).fit(t)
        dist = LightGBMRanker(**kw).setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        st, dt = serial.getModel().trees, dist.getModel().trees
        assert len(st) == len(dt)
        for a, b in zip(st, dt):
            np.testing.assert_array_equal(a.split_feature, b.split_feature)
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=2e-3, atol=1e-5)

    def test_mesh_rf_ranker_trains(self):
        from mmlspark_tpu.gbdt import LightGBMRanker, ndcg_at_k
        t = self._rank_table()
        m = LightGBMRanker(boostingType="rf", numIterations=8,
                           numLeaves=15, minDataInLeaf=5,
                           baggingFraction=0.6, baggingFreq=1,
                           groupCol="query", verbosity=0).setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        trees = m.getModel().trees
        assert len(trees) == 8
        assert all(abs(t_.shrinkage - 1 / 8) < 1e-12 for t_ in trees)
        out = m.transform(t)
        ndcg = float(np.mean(ndcg_at_k(np.asarray(out["prediction"]),
                                       t["label"], t["query"], 5)))
        assert ndcg > 0.6


class Test2DMeshModes:
    """data+feature 2-D mesh with multiclass + validation: both
    collectives (histogram psum over data, split allgather over feature)
    compose under the softmax K-tree scan."""

    def test_2d_mesh_multiclass_with_validation(self):
        from sklearn.datasets import make_classification
        X, y = make_classification(n_samples=800, n_features=8,
                                   n_informative=6, n_classes=3,
                                   random_state=15)
        t = {"features": X, "label": y.astype(float)}
        t["isVal"] = (np.arange(len(y)) % 5 == 0).astype(np.float64)
        m = LightGBMClassifier(numIterations=6, numLeaves=7,
                               minDataInLeaf=5, earlyStoppingRound=3,
                               validationIndicatorCol="isVal",
                               verbosity=0).setMesh(
            build_mesh(data=4, feature=2)).fit(t)
        assert len(m.getModel().trees) % 3 == 0
        acc = (np.asarray(m.transform(t)["prediction"])
               == t["label"]).mean()
        assert acc > 0.75


class TestMeshRankingDart:
    """dart x mesh lambdarank — the last matrix cell: shard-local lambda
    gradients at the dropped-out scores, shared host dropout loop."""

    def _rank_table(self):
        rng = np.random.default_rng(21)
        n_q, group = 80, 10
        n = n_q * group
        X = rng.normal(size=(n, 7))
        util = X @ rng.normal(size=7) + rng.normal(size=n) * 0.5
        q = np.repeat(np.arange(n_q), group)
        labels = np.zeros(n)
        for qq in range(n_q):
            m = q == qq
            labels[m] = np.clip(np.digitize(
                util[m], np.quantile(util[m], [0.5, 0.8])), 0, 2)
        return {"features": X, "label": labels, "query": q}

    def test_mesh_dart_ranker_matches_serial(self):
        from mmlspark_tpu.gbdt import LightGBMRanker
        t = self._rank_table()
        kw = dict(boostingType="dart", numIterations=6, numLeaves=7,
                  minDataInLeaf=5, dropRate=0.5, groupCol="query",
                  verbosity=0)
        serial = LightGBMRanker(**kw).fit(t)
        dist = LightGBMRanker(**kw).setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        st, dt = serial.getModel().trees, dist.getModel().trees
        assert len(st) == len(dt) == 6
        for a, b in zip(st, dt):
            assert abs(a.shrinkage - b.shrinkage) < 1e-12

    def test_mesh_dart_ranker_learns(self):
        from mmlspark_tpu.gbdt import LightGBMRanker, ndcg_at_k
        t = self._rank_table()
        m = LightGBMRanker(boostingType="dart", numIterations=15,
                           numLeaves=15, minDataInLeaf=5, dropRate=0.2,
                           groupCol="query", verbosity=0).setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        out = m.transform(t)
        ndcg = float(np.mean(ndcg_at_k(np.asarray(out["prediction"]),
                                       t["label"], t["query"], 5)))
        assert ndcg > 0.75


class TestMeshStepCache:
    """The mesh's built chunk programs outlive their fit
    (``distributed._build_step``): an equal (builder, mesh, objective,
    config, flags) finds the SAME jit object, so the second fit neither
    traces nor compiles; anything in the key that differs is a miss;
    bundles, baked into the program, bypass the table."""

    @pytest.fixture(scope="class")
    def tables(self):
        from sklearn.datasets import make_classification
        X, y = make_classification(n_samples=1200, n_features=10,
                                   n_informative=6, random_state=41)
        X3, y3 = make_classification(n_samples=900, n_features=8,
                                     n_informative=6, n_classes=3,
                                     random_state=42)
        rank = TestMeshRankingDart()._rank_table()
        return {"binary": {"features": X, "label": y.astype(float)},
                "multi": {"features": X3, "label": y3.astype(float)},
                "rank": rank}

    @staticmethod
    def _fit(est, table, mesh=None):
        """One mesh fit and what it left behind: the forest's text, the
        ``step_cache`` attr of each ``train.build_step``, the attrs of
        each ``train.launch``, and the seconds traced and the programs
        compiled or loaded over the WHOLE fit."""
        from mmlspark_tpu.core.profiler import get_profiler
        prof = get_profiler()
        before = {s["id"] for s in prof.spans()}
        traced0 = prof.jax_seconds("jaxpr_trace")
        seq0 = prof.compile_seq()
        model = est.setMesh(mesh or build_mesh(data=8, feature=1)) \
            .fit(table)
        new = [s for s in prof.spans() if s["id"] not in before]
        return {
            "forest": _forest_string(model),
            "step_cache": [s["attrs"]["step_cache"] for s in new
                           if s["name"] == "train.build_step"],
            "launches": [s["attrs"] for s in new
                         if s["name"] == "train.launch"],
            "traced_s": prof.jax_seconds("jaxpr_trace") - traced0,
            "compiles": prof.compile_seq() - seq0}

    BASE = dict(numIterations=4, numLeaves=7, minDataInLeaf=5, verbosity=0)
    BUILDERS = {
        # builder the fit reaches: (estimator, its table, parameters,
        # whether the fit dispatches through train.launch)
        "boost": ("classifier", "binary", {}, True),
        "goss": ("classifier", "binary", {"boostingType": "goss"}, True),
        "multiclass": ("classifier", "multi", {}, True),
        "rf": ("classifier", "binary",
               {"boostingType": "rf", "baggingFraction": 0.6,
                "baggingFreq": 1}, True),
        "dart": ("classifier", "binary",
                 {"boostingType": "dart", "dropRate": 0.5}, False),
        "ranking": ("ranker", "rank", {"groupCol": "query"}, False),
        "ranking_dart": ("ranker", "rank",
                         {"groupCol": "query", "boostingType": "dart",
                          "dropRate": 0.5}, False),
    }

    def _estimator(self, kind, **kw):
        from mmlspark_tpu.gbdt import LightGBMRanker
        cls = LightGBMRanker if kind == "ranker" else LightGBMClassifier
        return cls(**{**self.BASE, **kw})

    @pytest.mark.parametrize("builder", sorted(BUILDERS))
    def test_second_equal_fit_hits_and_neither_traces_nor_compiles(
            self, tables, builder):
        from mmlspark_tpu.gbdt import distributed, engine
        kind, table, kw, launches = self.BUILDERS[builder]
        # a learning rate no other test of this process uses: the first
        # fit is a miss whatever ran before
        kw = dict(kw, learningRate=0.0625 + 0.001 * sorted(
            self.BUILDERS).index(builder))
        stats0 = dict(engine.train_stats.snapshot()["counters"])
        first = self._fit(self._estimator(kind, **kw), tables[table])
        second = self._fit(self._estimator(kind, **kw), tables[table])
        assert first["step_cache"] == ["miss"]
        assert first["traced_s"] > 0 and first["compiles"] > 0
        assert second["step_cache"] == ["hit"]
        assert bool(second["launches"]) == launches
        for attrs in second["launches"]:
            assert attrs["jaxpr_trace_s"] == 0
            assert attrs["compile_misses"] == 0
        # the independent witness, over the whole fit (the dart and
        # ranking trainers dispatch outside train.launch)
        assert second["traced_s"] == 0 and second["compiles"] == 0
        assert second["forest"] == first["forest"]
        stats = engine.train_stats.snapshot()["counters"]
        assert stats["mesh_step_builds"] - stats0["mesh_step_builds"] == 1
        assert stats["mesh_step_hits"] - stats0["mesh_step_hits"] == 1
        assert len(distributed._step_table) <= distributed._STEP_TABLE_MAX

    @pytest.mark.parametrize("what", ["learning_rate", "num_leaves",
                                      "objective_state", "mesh_shape"])
    def test_a_different_key_is_a_miss(self, tables, what):
        t = tables["binary"]
        kw, other_kw = {"learningRate": 0.0525, "isUnbalance": True}, {}
        other_t, other_mesh = t, None
        if what == "learning_rate":
            other_kw = {"learningRate": 0.0535}
        elif what == "num_leaves":
            other_kw = {"numLeaves": 6}
        elif what == "objective_state":
            # prepare() resolves the class weight from the labels: the
            # same estimator on labels of another balance is another
            # program (the weight is a constant of the trace)
            y = t["label"].copy()
            y[np.flatnonzero(y > 0)[::3]] = 0.0
            other_t = {"features": t["features"], "label": y}
        else:
            other_mesh = build_mesh(data=4, feature=2)
        base = self._fit(self._estimator("classifier", **kw), t)
        assert base["step_cache"] in (["miss"], ["hit"])
        other = self._fit(self._estimator("classifier",
                                          **{**kw, **other_kw}),
                          other_t, mesh=other_mesh)
        assert other["step_cache"] == ["miss"]
        assert other["forest"] != base["forest"] or what == "mesh_shape"
        # and the first program is still there, with the first forest
        again = self._fit(self._estimator("classifier", **kw), t)
        assert again["step_cache"] == ["hit"]
        assert again["forest"] == base["forest"]

    def test_a_remade_mesh_and_a_copied_objective_hit(self, tables):
        """What the key holds compares by value: ``resolve_mesh`` makes
        the mesh again every fit, the estimator its objective."""
        import copy
        from mmlspark_tpu.gbdt import distributed
        from mmlspark_tpu.gbdt.grower import GrowerConfig
        from mmlspark_tpu.gbdt.objectives import BinaryObjective
        obj = BinaryObjective(is_unbalance=True)
        obj.prepare(np.array([0., 0., 1.]), np.ones(3))
        cfg = GrowerConfig(num_leaves=5)
        a = distributed.make_boost_scan(
            distributed.resolve_mesh("data"), obj, cfg, 0.03125, False)
        b = distributed.make_boost_scan(
            distributed.resolve_mesh("data"), copy.copy(obj),
            GrowerConfig(num_leaves=5), 0.03125, bag_sharded=False)
        assert a is b
        # the table's entry owns its objective: preparing the caller's on
        # other labels reaches neither the key nor the program's closure
        obj.prepare(np.array([0., 1., 1.]), np.ones(3))
        c = distributed.make_boost_scan(
            distributed.resolve_mesh("data"), obj, cfg, 0.03125, False)
        assert c is not a
        again = BinaryObjective(is_unbalance=True)
        again.prepare(np.array([0., 0., 1.]), np.ones(3))
        assert distributed.make_boost_scan(
            distributed.resolve_mesh("data"), again, cfg, 0.03125,
            False) is a

    def test_a_fit_with_bundles_bypasses_the_table(self):
        """The bundle maps are constants of the program: a fit that
        bundles builds its own step and leaves none behind, so no fit
        ever runs a program built around another table's maps."""
        from mmlspark_tpu.gbdt import distributed
        from tests.test_efb import _sparse_table

        def table(seed, group_size):
            # the same shape, bundled differently
            X, y = _sparse_table(np.random.default_rng(seed), n=1600,
                                 groups=24 // group_size,
                                 group_size=group_size)
            return {"features": X, "label": y}

        kw = dict(enableBundle=True, learningRate=0.0575)
        keys0 = list(distributed._step_table)
        fits = [self._fit(self._estimator("classifier", **kw), t)
                for t in (table(3, 8), table(4, 6), table(3, 8))]
        assert [f["step_cache"] for f in fits] == [["bypass"]] * 3
        assert list(distributed._step_table) == keys0
        assert fits[2]["forest"] == fits[0]["forest"] != fits[1]["forest"]

    def test_an_objective_that_does_not_hash_bypasses_the_table(self):
        """A mesh fit never hashed its objective before the table did: one
        whose state holds an array still gets its step, built afresh."""
        from mmlspark_tpu.core.profiler import get_profiler
        from mmlspark_tpu.gbdt import distributed
        from mmlspark_tpu.gbdt.grower import GrowerConfig
        from mmlspark_tpu.gbdt.objectives import BinaryObjective
        obj = BinaryObjective()
        obj.class_weight = np.ones(2)
        keys0 = list(distributed._step_table)
        step = distributed.make_boost_scan(
            build_mesh(data=8, feature=1), obj, GrowerConfig(num_leaves=5),
            0.1, False)
        assert callable(step)
        assert get_profiler().spans()[-1]["attrs"] == {
            "step_cache": "bypass"}
        assert list(distributed._step_table) == keys0

    def test_the_table_never_exceeds_its_bound(self):
        from mmlspark_tpu.gbdt import distributed
        from mmlspark_tpu.gbdt.grower import GrowerConfig
        from mmlspark_tpu.gbdt.objectives import BinaryObjective
        mesh, obj = build_mesh(data=8, feature=1), BinaryObjective()
        cfg = GrowerConfig(num_leaves=5)
        bound = distributed._STEP_TABLE_MAX
        first = distributed.make_boost_scan(mesh, obj, cfg, 0.5, False)
        for i in range(bound + 3):
            # the first entry is asked for again and again: the least
            # recently USED goes, not the oldest
            assert distributed.make_boost_scan(
                mesh, obj, cfg, 0.5, False) is first
            distributed.make_dart_step(mesh, obj, cfg, 0.5 + i / 1024)
            assert len(distributed._step_table) <= bound
        assert len(distributed._step_table) == bound
        # a dropped entry is simply built again
        assert ("make_dart_step", mesh, obj, cfg, 0.5, 1) \
            not in distributed._step_table
        assert callable(distributed.make_dart_step(mesh, obj, cfg, 0.5))

    def test_two_threads_fitting_at_once_both_return_the_forest(
            self, tables):
        """``TuneHyperparameters`` may fit from threads: both ask for
        one key at once and get one program, never a half-built one."""
        import sys
        import threading
        from mmlspark_tpu.gbdt import distributed
        kw = dict(learningRate=0.0585)
        n_threads = 4
        out, errors = [None] * n_threads, []
        gate = threading.Barrier(n_threads)

        def work(i):
            try:
                gate.wait(timeout=60)
                out[i] = self._fit(self._estimator("classifier", **kw),
                                   tables["binary"])["forest"]
            except Exception as e:  # noqa: BLE001 - reported below
                errors.append(e)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=300)
        finally:
            sys.setswitchinterval(switch)
        assert not any(th.is_alive() for th in threads)
        assert not errors, errors
        alone = self._fit(self._estimator("classifier", **kw),
                          tables["binary"])
        assert alone["step_cache"] == ["hit"]
        assert out == [alone["forest"]] * n_threads
        keys = [k for k in distributed._step_table
                if k[0] == "make_boost_scan" and k[4] == 0.0585]
        assert len(keys) == 1
