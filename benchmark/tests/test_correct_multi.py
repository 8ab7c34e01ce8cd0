"""``correct`` for the multiclass cell ``mnist8m_fit``: true for the sound
program, false for the float8 control and for each planted fault
(``benchmark/checks/readings_multi.py``), at the configuration's
rehearsal size (the control at a size where a mean over the compared
nodes says something).

Each test drives the harness's own run (benchmark/run.py ``execute``) on
whatever backend jax has, skipping only the look for a chip, with the
program broken underneath.

    JAX_PLATFORMS=cpu python -m pytest \
        benchmark/tests/test_correct_multi.py -q -p no:cacheprovider
"""

import importlib
import os

import numpy as np
import pytest

from benchmark import run as harness
from benchmark.checks import readings, readings_multi

CELL = "mnist8m_fit"


def run_cell(seed):
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "0.2",
            "--trace", "0", "--rehearse"]
    code, result = harness.execute(harness.parse(argv), have_chip=True)
    assert code == harness.REHEARSAL_EXIT
    return result


def failing(result):
    assert result["correct"] is False
    return {k for k, c in result["compared"].items()
            if not c["value"] <= c["limit"]}


@pytest.fixture
def broken_train(monkeypatch):
    """Replace ``engine.train`` by ``wrap(real_train)``."""
    from mmlspark_tpu.gbdt import engine
    real = engine.train

    def install(wrap):
        monkeypatch.setattr(engine, "train", wrap(real))
    return install


def test_sound_multiclass_run_is_correct():
    result = run_cell(2147483659)
    assert result["correct"] is True
    assert all(c["value"] is not None for c in result["compared"].values())


def test_multiclass_state_left_unchanged_is_not_correct(broken_train):
    def wrap(real):
        def train(bins, labels, *a, **kw):
            return readings_multi.stale_state(real(bins, labels, *a, **kw),
                                              labels)
        return train
    broken_train(wrap)
    assert "leaf_value_gap" in failing(run_cell(77))


def test_multiclass_altered_leaf_is_not_correct(broken_train):
    broken_train(lambda real: lambda *a, **kw: readings.altered_leaf(
        real(*a, **kw)))
    assert "leaf_value_gap" in failing(run_cell(79))


def test_multiclass_altered_split_is_not_correct(broken_train):
    def wrap(real):
        def train(bins, labels, weights, mapper, *a, **kw):
            return readings.altered_split(
                real(bins, labels, weights, mapper, *a, **kw), mapper)
        return train
    broken_train(wrap)
    assert "count_mismatch" in failing(run_cell(80))


@pytest.mark.parametrize("fault,fails_by", [
    ("half_batch", "count_mismatch"),
    ("class_swap", "leaf_value_gap"),
    ("sequential_grad", "leaf_value_gap"),
])
def test_multiclass_fault_is_not_correct(fault, fails_by, broken_train):
    broken_train(readings_multi.broken_fits()[fault])
    assert fails_by in failing(run_cell(78))


#: a size at which a test can hold the control: the rehearsal size grows
#: too few nodes for a mean over them to say anything
CONTROL_SIZE = {"rows": 30000, "features": 64,
                "params": {"numLeaves": 127, "minSumHessianInLeaf": 5.0}}


def test_multiclass_float8_control_is_not_correct():
    """The reference's split search with float8_e4m3 gradients and
    hessians: the splits it puts first lie, on average over the compared
    nodes, further below the best than the limit allows, on three seeds;
    the sound fit does not."""
    _, cell, config, traffic = harness.load_cell(
        os.path.join(harness.ROOT, "BENCHMARK.json"), CELL)
    config["rehearsal"] = CONTROL_SIZE
    limits = harness.load_limits(CELL)
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    gaps = []
    for seed in (5, 6, 7):
        ctx = harness.Context(cell, config, traffic, seed, True, False)
        state = driver.setup(ctx)
        driver.release(ctx, state)
        sound, ok = harness.compare(driver.check(ctx, state), limits)
        assert ok, sound
        control = driver.check(ctx, state, precision="fp8")
        assert not harness.compare(control, limits)[1]
        gaps.append(control["split_gap_mean"])
    assert min(gaps) > limits["split_gap_mean"], gaps
    assert np.isfinite(gaps).all()
