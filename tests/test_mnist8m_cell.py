"""The multiclass cell ``mnist8m_fit``: the K class trees of an iteration
grown by ONE compiled grower body in a loop, against the plain reference
``benchmark/reference/gbdt_multi.py``; ``correct`` false for each planted
fault and for the float8 control (the tests of
benchmark/tests/test_correct_multi.py, counted here); LightGBM's softmax
start score; thresholds that read back exactly; the pixel generator; the
cell's three per-layer readers; a program without the class loop refused
at once.
"""

import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.tests.test_correct_multi import (  # noqa: F401
    broken_train, test_multiclass_altered_leaf_is_not_correct,
    test_multiclass_altered_split_is_not_correct,
    test_multiclass_fault_is_not_correct,
    test_multiclass_float8_control_is_not_correct,
    test_multiclass_state_left_unchanged_is_not_correct,
    test_sound_multiclass_run_is_correct)
from benchmark import run as harness
from benchmark.lib import data_pixels
from benchmark.reference import gbdt_multi

BINNING = {"sample_rows": 200000, "seed": 42, "min_data_in_bin": 3}


def pixel_rows(seed, classes, rows=6000, features=64):
    X, y = data_pixels.pixel_strokes(seed, rows, features)
    return X, (y.astype(np.int64) % classes)


# ------------------------------------------- the system against the reference


@pytest.mark.parametrize("K", [4, 10])
def test_multiclass_fit_agrees_with_the_reference(K):
    """Through ``LightGBMClassifier.fit``: every class tree's node counts
    exact (the raw rows walked down the exported thresholds), its leaves
    the Newton steps of the reference's softmax gradients within the
    cell's limit, its splits the best the reference finds, the bins the
    configuration's rule gives."""
    from mmlspark_tpu.gbdt import LightGBMClassifier
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    X, y = pixel_rows(11 + K, K)
    model = LightGBMClassifier(
        objective="multiclass", numIterations=2, numLeaves=15,
        minSumHessianInLeaf=5.0, minDataInLeaf=0, verbosity=0).fit(
        {"features": X, "label": y.astype(float)})
    booster = model.getModel()
    assert booster.num_class == K and len(booster.trees) == 2 * K
    bins = fit_bin_mapper(X, max_bin=255, seed=0).transform_packed(X)
    got = gbdt_multi.check_fit(
        booster.save_native_model_string(), X, y, bins,
        {"learning_rate": 0.1, "min_sum_hessian": 5.0, "min_data": 0,
         "max_bin": 255, "binning": dict(BINNING, seed=0)},
        seed=3, expect_trees=2 * K, sample_nodes=254, sample_features=32)
    assert got["num_class"] == K
    assert got["tree_count_gap"] == 0
    assert got["count_mismatch"] == 0
    assert got["bin_mismatch"] == 0
    # the cell's limits: float32 sums on the CPU read far under them
    limits = harness.load_limits("mnist8m_fit")
    assert got["leaf_value_gap"] < limits["leaf_value_gap"], got
    assert got["split_gap_mean"] < limits["split_gap_mean"], got


# --------------------------------------------------- one grower body for any K


def _fit_attrs(K, seed=5, learning_rate=0.1):
    from mmlspark_tpu.core.profiler import get_profiler
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    from mmlspark_tpu.gbdt.engine import TrainParams
    from mmlspark_tpu.gbdt.objectives import get_objective
    X, y = pixel_rows(seed, K, rows=1500, features=16)
    mapper = fit_bin_mapper(X, max_bin=63)
    params = TrainParams(num_iterations=2, num_leaves=7,
                         learning_rate=learning_rate,
                         min_sum_hessian_in_leaf=1.0, min_data_in_leaf=0,
                         verbosity=0)
    booster = engine.train(mapper.transform_packed(X), y, None, mapper,
                           get_objective("multiclass", num_class=K), params)
    root = [s for s in get_profiler().spans() if s["name"] == "train.fit"][-1]
    return booster, root["attrs"]


@pytest.mark.parametrize("K", [3, 10])
def test_class_loop_holds_one_grower_body(K):
    """``train.fit`` says how many grower bodies its chunk program holds:
    one, whatever K, traced now or (a second fit, a cache hit) recorded
    when the program was built."""
    booster, attrs = _fit_attrs(K)
    assert len(booster.trees) == 2 * K
    assert attrs["num_class"] == K
    assert attrs["trees_per_iteration"] == K
    assert attrs["grow_bodies"] == 1
    assert _fit_attrs(K, seed=6)[1]["grow_bodies"] == 1


def _multi_stablehlo(K, n=512, f=6, C=2):
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.grower import GrowerConfig
    from mmlspark_tpu.gbdt.objectives import MulticlassObjective
    sds = jax.ShapeDtypeStruct
    return engine._boost_scan_multi.lower(
        sds((n, f), jnp.uint8), sds((K, n), jnp.float32),
        sds((n,), jnp.int32), sds((n,), jnp.float32),
        sds((C, 1), jnp.float32), sds((C, f, 3), jnp.float32),
        sds((1, f), jnp.uint8), sds((1, K), jnp.float32),
        obj=MulticlassObjective(K),
        cfg=GrowerConfig(num_leaves=7, num_bins=16, min_data_in_leaf=0,
                         min_sum_hessian_in_leaf=1.0),
        lr=0.1, K=K, has_val=False).as_text()


def test_class_loop_program_does_not_grow_with_k():
    """The chunk program at K = 10 is within 1.2x of K = 3's (the class
    loop unrolled in Python made it grow as K)."""
    small, large = _multi_stablehlo(3), _multi_stablehlo(10)
    assert len(large) < 1.2 * len(small), (len(small), len(large))


#: sha256 of ``engine._boost_scan``'s StableHLO at 512 x 6, 7 leaves, 16
#: bins, two trees (binary log loss): the one-tree-an-iteration program of
#: every binary, ranking, categorical and bundled cell, which the class
#: loop leaves as it was.  A change that means to alter that program
#: updates this digest.
K1_SCAN_STABLEHLO = ("e4344173f28c9a4bb8c78dccb8e56b439ad0d65f"
                     "7356b8596076490aea0620ad")


def test_one_class_scan_program_is_unchanged():
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.grower import GrowerConfig
    from mmlspark_tpu.gbdt.objectives import BinaryObjective
    n, f, C = 512, 6, 2
    obj = BinaryObjective()
    obj.prepare(np.zeros(8), np.ones(8))
    sds = jax.ShapeDtypeStruct
    text = engine._boost_scan.lower(
        sds((n, f), jnp.uint8), sds((n,), jnp.float32),
        sds((n,), jnp.float32), sds((n,), jnp.float32),
        sds((C, 1), jnp.float32), sds((C, f, 3), jnp.float32),
        sds((1, f), jnp.uint8), sds((1,), jnp.float32), obj=obj,
        cfg=GrowerConfig(num_leaves=7, num_bins=16, min_data_in_leaf=0,
                         min_sum_hessian_in_leaf=1.0),
        lr=0.1, has_val=False).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == K1_SCAN_STABLEHLO


def test_class_major_gradients_are_the_softmax_transposed():
    from mmlspark_tpu.gbdt.objectives import MulticlassObjective
    obj = MulticlassObjective(5)
    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.normal(size=(40, 5)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 5, 40), jnp.int32)
    w = jnp.asarray(rng.random(40), jnp.float32)
    g, h = obj.grad_hess(s, y, w)
    gk, hk = obj.grad_hess_class_major(s.T, y, w)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(g).T, atol=1e-7)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(h).T, atol=1e-7)


# ------------------------------------------------------ the start score


def test_softmax_start_score_is_the_log_of_each_class_share():
    """LightGBM's ``boost_from_average`` for softmax: class k starts at
    log(share of class k), baked into class k's first tree."""
    from mmlspark_tpu.gbdt.objectives import MulticlassObjective
    y = np.array([0] * 6 + [1] * 3 + [2] * 1)
    start = MulticlassObjective(3).init_score(y, np.ones(10))
    np.testing.assert_allclose(start, np.log([0.6, 0.3, 0.1]))
    w = np.array([1.0] * 6 + [2.0] * 3 + [4.0])     # weighted shares
    np.testing.assert_allclose(MulticlassObjective(3).init_score(y, w),
                               np.log([0.375, 0.375, 0.25]))
    # at a learning rate of 1e-6 a class's first tree is its start score
    booster, _ = _fit_attrs(4, seed=9, learning_rate=1e-6)
    _, y4 = pixel_rows(9, 4, rows=1500, features=16)
    start = np.log(np.bincount(y4, minlength=4) / len(y4))
    for k in range(4):
        np.testing.assert_allclose(booster.trees[k].leaf_value, start[k],
                                   atol=1e-4)
        # the second iteration's trees carry no start score
        assert np.abs(booster.trees[4 + k].leaf_value).max() < 1e-4


# --------------------------------------------- thresholds that read back


def test_thresholds_read_back_as_the_bounds_they_are():
    """A bound under 0.1 written to 17 places lost digits: a pixel level
    equal to it (k/255 in float32) walked the other way in the text."""
    from mmlspark_tpu.gbdt.booster import _arr_line, _parse_arr
    bounds = np.array([np.float32(1 / 255), np.float32(4 / 255), 0.5,
                       -np.float32(3e-3), 123.25, 0.0], np.float64)
    line = _arr_line("threshold", bounds)
    back = _parse_arr(line.split("=", 1)[1].strip(), np.float64)
    np.testing.assert_array_equal(back, bounds)
    # a value that 17 places already holds is written as before
    assert _arr_line("t", np.array([0.5, 123.25])) == "t=0.5 123.25\n"


# ------------------------------------------------------------ the generator


def test_pixel_rows_have_the_configuration_s_shape():
    X, y = data_pixels.pixel_strokes(2147483659, 40000, 784)
    assert X.dtype == np.float32 and X.shape == (40000, 784)
    inked = X > 0
    assert 0.17 < inked.mean() < 0.21                 # MNIST's 19%
    grid = np.zeros((28, 28), bool)
    grid[:2] = grid[-2:] = True
    grid[:, :2] = grid[:, -2:] = True
    assert inked[:, grid.ravel()].mean() < 1e-3       # the outer ring
    assert inked.sum(axis=0).min() > 0                # no constant column
    levels = X[inked] * 255
    np.testing.assert_allclose(levels, np.round(levels), atol=1e-4)
    assert 0.30 < (X[inked] == 1.0).mean() < 0.37     # saturated cores
    share = np.bincount(y.astype(int), minlength=10) / len(y)
    mnist = np.asarray(data_pixels.CLASS_COUNTS) / 60000
    np.testing.assert_allclose(share, mnist, atol=0.01)
    again, _ = data_pixels.pixel_strokes(2147483659, 40000, 784, threads=3)
    np.testing.assert_array_equal(X, again)           # the seed alone
    other, _ = data_pixels.pixel_strokes(7, 40000, 784)
    assert not np.array_equal(X, other)


# -------------------------------------------------------- the cell's readers


def test_the_cell_s_readers():
    from benchmark.metrics import (class_grow_bodies,
                                   multiclass_fit_tree_mfu,
                                   warmup_compile_s)
    from benchmark.tests.test_span_readers import Profiler, fit
    def run_of(first, second):
        spans = (fit(1, 0.0, attrs={"grow_bodies": first})
                 + fit(100, 20.0, attrs={"grow_bodies": second}))
        return types.SimpleNamespace(
            state={"profiler": Profiler(spans)},
            work={"trees": 40, "fits": 2},
            counters={"warmup_compile_s": 61.5})

    assert class_grow_bodies.read(run_of(1, 1)) == 1
    assert warmup_compile_s.read(run_of(1, 1)) == 61.5
    assert class_grow_bodies.read(run_of(10, 1)) is None   # they disagree
    assert warmup_compile_s.read(types.SimpleNamespace(counters={})) is None

    booster, _ = _fit_attrs(3, seed=4)
    run = types.SimpleNamespace(
        state={"multi_model_text": booster.save_native_model_string(),
               "features": 16, "num_bins": 64},
        work={"trees": 6, "window_s": 2.0}, chips=1,
        peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    share = multiclass_fit_tree_mfu.read(run)
    assert 0 < share < 100
    assert multiclass_fit_tree_mfu.read(types.SimpleNamespace(
        state={}, work={"trees": 6})) is None


def test_a_program_without_the_class_loop_is_refused_at_once(monkeypatch):
    """The parent's program unrolls the class loop; its driver exits 4
    before making any row."""
    from benchmark.drivers import fit_repeat_multi
    from mmlspark_tpu.gbdt import grower
    monkeypatch.delattr(grower, "GROW_TRACES")
    made = []
    monkeypatch.setitem(data_pixels.GENERATORS, "pixel_strokes",
                        lambda *a: made.append(a))
    ctx = types.SimpleNamespace(config={"data": "pixel_strokes"},
                                rehearse=True)
    with pytest.raises(SystemExit) as e:
        fit_repeat_multi.setup(ctx)
    assert e.value.code == fit_repeat_multi.CANNOT_RUN_EXIT
    assert made == []
