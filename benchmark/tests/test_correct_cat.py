"""``test_correct.py`` for the cell with categorical columns: ``correct``
is true for a sound run and false for each planted fault and control,
this mechanism's own among them (a category moved across a split; a
learner to which a categorical column is its codes in order).

Each test drives the harness's own run (benchmark/run.py ``execute``) at
the configuration's rehearsal size on whatever backend jax has.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import importlib
import os

import numpy as np
import pytest

from benchmark import run as harness
from benchmark.checks import readings, readings_cat

CELL = "criteo_fit"


def run_cell(seed):
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "0.2",
            "--trace", "0", "--rehearse"]
    code, result = harness.execute(harness.parse(argv), have_chip=True)
    assert code == harness.REHEARSAL_EXIT
    return result


@pytest.fixture
def broken_train(monkeypatch):
    """Replace ``engine.train`` by ``wrap(real_train)``."""
    from mmlspark_tpu.gbdt import engine
    real = engine.train

    def install(wrap):
        monkeypatch.setattr(engine, "train", wrap(real))
    return install


def failing(result):
    assert result["correct"] is False
    return {k for k, c in result["compared"].items()
            if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("seed", [2147483659, 3, 2270000004])
def test_sound_categorical_run_is_correct(seed):
    """Counts, bins and bitset routing exact, leaf values and split gaps
    under the cell's limits, on mixed numeric and categorical columns."""
    result = run_cell(seed)
    assert result["correct"] is True, result["compared"]
    compared = result["compared"]
    assert compared["count_mismatch"]["value"] == 0
    assert compared["bin_mismatch"]["value"] == 0
    assert compared["tree_count_gap"]["value"] == 0


def test_altered_bitset_is_not_correct(broken_train):
    def wrap(real):
        def train(bins, labels, weights, mapper, *a, **kw):
            return readings_cat.altered_bitset(
                real(bins, labels, weights, mapper, *a, **kw), mapper)
        return train
    broken_train(wrap)
    assert "count_mismatch" in failing(run_cell(90))


def test_altered_numeric_split_is_not_correct(broken_train):
    def wrap(real):
        def train(bins, labels, weights, mapper, *a, **kw):
            return readings_cat.altered_split(
                real(bins, labels, weights, mapper, *a, **kw), mapper)
        return train
    broken_train(wrap)
    assert "count_mismatch" in failing(run_cell(91))


def test_state_left_unchanged_is_not_correct(broken_train):
    def wrap(real):
        def train(bins, labels, *a, **kw):
            return readings.stale_state(real(bins, labels, *a, **kw), labels)
        return train
    broken_train(wrap)
    assert "leaf_value_gap" in failing(run_cell(92))


def test_half_batch_is_not_correct(broken_train):
    def wrap(real):
        def train(bins, labels, weights, *a, **kw):
            n = len(labels) // 2
            return real(bins[:n], labels[:n], weights, *a, **kw)
        return train
    broken_train(wrap)
    assert "count_mismatch" in failing(run_cell(93))


def test_altered_leaf_is_not_correct(broken_train):
    broken_train(lambda real: lambda *a, **kw: readings.altered_leaf(
        real(*a, **kw)))
    assert "leaf_value_gap" in failing(run_cell(94))


#: a size at which a test can hold the controls: the rehearsal size grows
#: too few nodes for a mean over them to say anything
CONTROL_SIZE = {"rows": 500000, "cardinality_cap": 20000,
                "params": {"numLeaves": 255, "minSumHessianInLeaf": 20.0}}


@pytest.fixture(scope="module")
def control_readings():
    """Sound, float8 and cat_as_numeric readings of one fit a seed."""
    _, cell, config, traffic = harness.load_cell(
        os.path.join(harness.ROOT, "BENCHMARK.json"), CELL)
    config["rehearsal"] = CONTROL_SIZE
    limits = harness.load_limits(CELL)
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    out = []
    for seed in (5, 6):
        ctx = harness.Context(cell, config, traffic, seed, True, False)
        state = driver.setup(ctx)
        driver.release(ctx, state)
        out.append({p: driver.check(ctx, state, precision=p)
                    for p in ("float64", "fp8", "cat_as_numeric")})
    return limits, out


def test_float8_control_is_not_correct(control_readings):
    limits, seeds = control_readings
    for got in seeds:
        assert harness.compare(got["float64"], limits)[1], got["float64"]
        assert not harness.compare(got["fp8"], limits)[1]
        assert got["fp8"]["split_gap_mean"] > limits["split_gap_mean"]
        assert np.isfinite(got["fp8"]["split_gap_mean"])


def test_cat_as_numeric_is_not_correct(control_readings):
    """A learner without the categorical mechanism lies at least ten times
    further below the best split than the program does, and over the
    limit: leaving the mechanism out cannot pass."""
    limits, seeds = control_readings
    for got in seeds:
        gap = got["cat_as_numeric"]["split_gap_mean"]
        assert not harness.compare(got["cat_as_numeric"], limits)[1]
        assert gap > limits["split_gap_mean"]
        assert gap >= 10 * got["float64"]["split_gap_mean"]
        assert got["float64"]["cat_split_share"] >= 0.25
