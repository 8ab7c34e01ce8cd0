"""``correct`` on the sparse, bundled cell: true for the program, false
for the float8 control and for each of the six planted faults, at the
configuration's rehearsal size on whatever backend jax has
(benchmark/tests/test_correct.py says how the harness is driven).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_correct_sparse.py -q -p no:cacheprovider
"""

import importlib
import os

import numpy as np
import pytest

from benchmark import run as harness
from benchmark.checks import readings, readings_sparse
from benchmark.tests.test_correct import broken_train, failing  # noqa: F401

CELL = "allstate_fit"


def run_cell(seed):
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "0.2",
            "--trace", "0", "--rehearse"]
    code, result = harness.execute(harness.parse(argv), have_chip=True)
    assert code == harness.REHEARSAL_EXIT
    return result


def test_sound_run_is_correct():
    result = run_cell(2147483659)
    assert result["correct"] is True
    assert all(c["value"] is not None for c in result["compared"].values())


def test_state_left_unchanged_is_not_correct(broken_train):
    def wrap(real):
        def train(bins, labels, *a, **kw):
            return readings.stale_state(real(bins, labels, *a, **kw), labels)
        return train
    broken_train(wrap)
    assert "leaf_value_gap" in failing(run_cell(77))


def test_half_batch_is_not_correct(broken_train):
    def wrap(real):
        def train(bins, labels, weights, *a, **kw):
            n = len(labels) // 2
            return real(bins[:n], labels[:n], weights, *a, **kw)
        return train
    broken_train(wrap)
    assert "count_mismatch" in failing(run_cell(78))


def test_altered_leaf_is_not_correct(broken_train):
    broken_train(lambda real: lambda *a, **kw: readings.altered_leaf(
        real(*a, **kw)))
    assert "leaf_value_gap" in failing(run_cell(79))


def test_altered_split_is_not_correct(broken_train):
    def wrap(real):
        def train(bins, labels, weights, mapper, *a, **kw):
            return readings_sparse.altered_split(
                real(bins, labels, weights, mapper, *a, **kw), mapper)
        return train
    broken_train(wrap)
    assert "count_mismatch" in failing(run_cell(80))


@pytest.fixture(scope="module")
def fitted():
    """One set-up of the cell at the rehearsal size, for the faults that
    are made again through the window's own call."""
    _, cell, config, traffic = harness.load_cell(
        os.path.join(harness.ROOT, "BENCHMARK.json"), CELL)
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    ctx = harness.Context(cell, config, traffic, 81, True, False)
    return driver, ctx, driver.setup(ctx), harness.load_limits(CELL)


def _compared(fitted, booster, precision="float64"):
    driver, ctx, state, limits = fitted
    st = dict(state, booster=booster)
    driver.release(ctx, st)
    compared, ok = harness.compare(
        driver.check(ctx, st, precision=precision), limits)
    return ok, {k for k, c in compared.items()
                if not c["value"] <= c["limit"]}


def test_bundle_conflict_is_not_correct(fitted):
    """Two columns that are set together in many rows, forced into one
    bundle: the second loses its value there.  Where the trees still
    split on it the counts say so; where the lost rows cost it its gain,
    the split the trees took instead lies below the reference's best."""
    state = fitted[2]
    assert _compared(fitted, state["booster"])[0]
    table, what = readings_sparse.conflicting_table(state)
    assert what["conflict_rows"] > 0
    ok, failed = _compared(fitted, readings_sparse.fit_on(state, table))
    assert not ok and failed & {"count_mismatch", "leaf_value_gap",
                                "split_gap_mean"}


def test_default_dropped_is_not_correct(fitted):
    """The expansion without the default bins made up from the leaf's
    totals: every bundled column reads as if no row held its default."""
    state = fitted[2]
    ok, failed = _compared(fitted, readings_sparse.fit_with_defaults_dropped(
        state["fit"]))
    assert not ok and failed & {"leaf_value_gap", "split_gap_mean",
                                "count_mismatch"}


#: a size at which a test can hold the control: the rehearsal size grows
#: too few nodes for a mean over them to say anything
CONTROL_SIZE = {"rows": 400000, "block_cap": 400,
                "params": {"numLeaves": 255, "minSumHessianInLeaf": 2.0}}


def test_float8_control_is_not_correct():
    """The reference's own learner with float8_e4m3 gradients, the
    nearest precision below the configuration's bfloat16: its leaves and
    the splits it puts first lie outside the limits; the sound fit does
    not."""
    _, cell, config, traffic = harness.load_cell(
        os.path.join(harness.ROOT, "BENCHMARK.json"), CELL)
    config["rehearsal"] = CONTROL_SIZE
    limits = harness.load_limits(CELL)
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    for seed in (5, 6):
        ctx = harness.Context(cell, config, traffic, seed, True, False)
        state = driver.setup(ctx)
        driver.release(ctx, state)
        sound, ok = harness.compare(driver.check(ctx, state), limits)
        assert ok, sound
        control = driver.check(ctx, state, precision="fp8")
        assert not harness.compare(control, limits)[1]
        assert control["leaf_value_gap"] > limits["leaf_value_gap"]
        assert np.isfinite(control["split_gap_mean"])


def test_native_loop_and_numpy_agree():
    from benchmark.lib import data_onehot
    from benchmark.reference import gbdt, gbdt_sparse
    X, _ = data_onehot.onehot_rows(3, 4000, [30, 50, 6, 4, 3, 7, 9, 5, 4, 3,
                                             2, 6, 8, 5, 4, 3, 11])
    rng = np.random.default_rng(0)
    # columns of 2, 3, 17 and 200 bins: four widths of the layout
    cap = rng.choice([2, 3, 17, 200], X.shape[1])
    bins = rng.integers(0, cap[X.indices]).astype(np.uint8)
    zero_bin = rng.integers(0, cap)
    g, h = rng.normal(size=4000), rng.random(4000)
    rows = np.sort(rng.choice(4000, 900, replace=False))
    layout = gbdt_sparse.Layout(X, bins, zero_bin)
    for r in (None, rows):
        a = gbdt_sparse.node_histogram(X, bins, layout, r, g, h)
        b = gbdt_sparse.node_histogram(X, bins, layout, r, g, h,
                                       native=False)
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-12)
        m = 4000 if r is None else 900
        # every column holds every row of the node once, in its own
        # bins; the groups' blocks are the whole of the histogram
        seen = 0
        for cols, block in layout.blocks(a):
            np.testing.assert_allclose(block[..., 2].sum(axis=1), m)
            seen += cols.size
            for k in (0, cols.size - 1):
                np.testing.assert_array_equal(
                    layout.column(a, cols[k]), block[k])
        assert seen == X.shape[1]
        # against the plain dense sums of the same rows
        sel = np.arange(4000) if r is None else r
        want = np.zeros((X.shape[1], 256, 3))
        for i in sel:
            e = slice(X.indptr[i], X.indptr[i + 1])
            held = np.zeros(X.shape[1], bool)
            held[X.indices[e]] = True
            for col, bn in zip(np.r_[X.indices[e], np.flatnonzero(~held)],
                               np.r_[bins[e], zero_bin[~held]]):
                want[col, bn] += (g[i], h[i], 1.0)
        for j in (0, 5, X.shape[1] - 1):
            got = layout.column(a, j)
            np.testing.assert_allclose(got, want[j, :got.shape[0]],
                                       rtol=1e-9, atol=1e-9)
            assert not want[j, got.shape[0]:].any()
        dense_best = gbdt.best_split(want, 1e-3, 1)
        mine = gbdt_sparse.best_split(a, layout, 1e-3, 1)
        assert mine[1:] == dense_best[1:]
        np.testing.assert_allclose(mine[0], dense_best[0], rtol=1e-9)
        # no admissible split: the first cell, as the dense search says
        assert gbdt_sparse.best_split(a, layout, 1e9, 1) \
            == gbdt.best_split(want, 1e9, 1) == (-np.inf, 0, 0)
