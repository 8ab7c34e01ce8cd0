"""``test_correct.py`` for the learning-to-rank cell: ``correct`` is true
for a sound run and false for each planted fault and control, this
mechanism's own among them (query boundaries moved by a row; pairs not
truncated; deltas not normalised; a learner whose gradients are squared
error on the labels).

Each test drives the harness's own run (benchmark/run.py ``execute``) at
the configuration's rehearsal size on whatever backend jax has.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import importlib
import os

import numpy as np
import pytest

from benchmark import run as harness
from benchmark.checks import readings, readings_rank
from benchmark.tests.test_correct import broken_train, failing  # noqa: F401

CELL = "istella_fit"


def run_cell(seed):
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "0.2",
            "--trace", "0", "--rehearse"]
    code, result = harness.execute(harness.parse(argv), have_chip=True)
    assert code == harness.REHEARSAL_EXIT
    return result


@pytest.mark.parametrize("seed", [2147483659, 3, 2310000004])
def test_sound_ranking_run_is_correct(seed):
    """Counts and bins exact, leaf values and split gaps under the cell's
    limits, with lambdarank's gradients on ragged queries."""
    result = run_cell(seed)
    assert result["correct"] is True, result["compared"]
    compared = result["compared"]
    assert compared["count_mismatch"]["value"] == 0
    assert compared["bin_mismatch"]["value"] == 0
    assert compared["tree_count_gap"]["value"] == 0


@pytest.mark.parametrize("fault", ["query_shift", "no_truncation",
                                   "unnormalised"])
def test_wrong_ranking_gradient_is_not_correct(fault, broken_train):
    """A fit whose lambdas were computed over the wrong queries, over
    every pair, or without the ideal DCG has leaves the reference's
    gradients do not give."""
    broken_train(readings_rank.TRAIN_FAULTS[fault])
    assert "leaf_value_gap" in failing(run_cell(95))


def test_state_left_unchanged_is_not_correct(broken_train):
    broken_train(lambda real: lambda *a, **kw: readings_rank.stale_state(
        real(*a, **kw)))
    assert "leaf_value_gap" in failing(run_cell(92))


def test_half_batch_is_not_correct(broken_train):
    broken_train(readings_rank.half_batch)
    assert "count_mismatch" in failing(run_cell(93))


def test_altered_leaf_is_not_correct(broken_train):
    broken_train(lambda real: lambda *a, **kw: readings.altered_leaf(
        real(*a, **kw)))
    assert "leaf_value_gap" in failing(run_cell(94))


def test_altered_split_is_not_correct(broken_train):
    def wrap(real):
        def train(bins, labels, weights, mapper, *a, **kw):
            return readings.altered_split(
                real(bins, labels, weights, mapper, *a, **kw), mapper)
        return train
    broken_train(wrap)
    assert "count_mismatch" in failing(run_cell(91))


#: a size at which a test can hold the controls: the rehearsal size grows
#: too few nodes for a mean over them to say anything
CONTROL_SIZE = {"rows": 300000, "queries": 1500, "features": 40,
                "params": {"numLeaves": 127, "minSumHessianInLeaf": 2.0}}


@pytest.fixture(scope="module")
def control_readings():
    """Sound, float8 and pointwise readings of one fit a seed."""
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
                    for p in ("float64", "fp8", "pointwise")})
    return limits, out


def test_float8_control_is_not_correct(control_readings):
    limits, seeds = control_readings
    for got in seeds:
        assert harness.compare(got["float64"], limits)[1], got["float64"]
        assert not harness.compare(got["fp8"], limits)[1]
        assert got["fp8"]["split_gap_mean"] > limits["split_gap_mean"]
        assert np.isfinite(got["fp8"]["split_gap_mean"])


def test_pointwise_control_is_not_correct(control_readings):
    """A learner without the ranking mechanism lies at least ten times
    further below the best split than the program does, and over the
    limit: leaving the mechanism out cannot pass."""
    limits, seeds = control_readings
    for got in seeds:
        gap = got["pointwise"]["split_gap_mean"]
        assert not harness.compare(got["pointwise"], limits)[1]
        assert gap > limits["split_gap_mean"]
        assert gap >= 10 * got["float64"]["split_gap_mean"]
