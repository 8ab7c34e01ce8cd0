"""``correct`` for ``bosch_missing_fit``, a table mostly missing by
station: true for the sound program, false for the float8 control and for
each planted fault (``benchmark/checks/readings_missing.py``), at the
configuration's rehearsal size (the control at a size where a mean over
the compared nodes says something).  And a program that scans one
default direction only is refused at once.

Each test drives the harness's own run (benchmark/run.py ``execute``) on
whatever backend jax has, skipping only the look for a chip, with the
program broken underneath.

    JAX_PLATFORMS=cpu python -m pytest \
        benchmark/tests/test_correct_missing.py -q -p no:cacheprovider
"""

import dataclasses
import importlib
import os

import numpy as np
import pytest

from benchmark import run as harness
from benchmark.checks import readings, readings_missing

CELL = "bosch_missing_fit"


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


def test_sound_missing_run_is_correct():
    result = run_cell(2147483661)
    assert result["correct"] is True
    assert all(c["value"] is not None for c in result["compared"].values())


def test_missing_state_left_unchanged_is_not_correct(broken_train):
    def wrap(real):
        def train(bins, labels, *a, **kw):
            return readings.stale_state(real(bins, labels, *a, **kw),
                                        labels)
        return train
    broken_train(wrap)
    assert "leaf_value_gap" in failing(run_cell(81))


def test_missing_altered_leaf_is_not_correct(broken_train):
    broken_train(lambda real: lambda *a, **kw: readings.altered_leaf(
        real(*a, **kw)))
    assert "leaf_value_gap" in failing(run_cell(82))


def test_missing_altered_split_is_not_correct(broken_train):
    def wrap(real):
        def train(bins, labels, weights, mapper, *a, **kw):
            return readings.altered_split(
                real(bins, labels, weights, mapper, *a, **kw), mapper)
        return train
    broken_train(wrap)
    assert "count_mismatch" in failing(run_cell(83))


def test_default_left_dropped_from_the_export_is_not_correct(broken_train):
    """Bit 1 cleared: the raw rows walk NaN right where the partition
    sent it left, and the counts no longer match."""
    broken_train(lambda real: lambda *a, **kw: readings_missing
                 .dleft_dropped(real(*a, **kw)))
    assert "count_mismatch" in failing(run_cell(84))


@pytest.mark.parametrize("fault,fails_by", [
    ("half_batch", "count_mismatch"),
    ("missing_right", "split_gap_mean"),
])
def test_missing_fault_is_not_correct(fault, fails_by, broken_train):
    """``missing_right``: the program forced to the one-direction scan
    (every NaN right) grows trees whose splits lie below the two-direction
    best; its counts still match its own export."""
    broken_train(readings_missing.broken_fits()[fault])
    assert fails_by in failing(run_cell(85))


def test_one_direction_program_is_refused_at_once(monkeypatch):
    """A program whose ``GrowerConfig`` has no ``has_missing`` (the
    parent of the two-direction scan) exits 4 before making rows."""
    from benchmark.drivers import fit_repeat_missing
    real_fields = dataclasses.fields
    monkeypatch.setattr(
        fit_repeat_missing.dataclasses, "fields",
        lambda c: tuple(f for f in real_fields(c) if f.name != "has_missing"))
    with pytest.raises(SystemExit) as e:
        run_cell(86)
    assert e.value.code == fit_repeat_missing.CANNOT_RUN_EXIT


#: a size at which a test can hold the control: the rehearsal size grows
#: too few nodes for a mean over them to say anything.  The histogram is
#: the chip's build (dot16, XLA's form on the CPU): the CPU's native and
#: segment builds sum float32 one row at a time, and a missing bin that
#: holds most of the rows drifts there (PERF.md section 7)
CONTROL_SIZE = {"rows": 200000, "features": 64,
                "params": {"numLeaves": 255, "minSumHessianInLeaf": 20.0,
                           "histogramMethod": "dot16"}}


def test_missing_float8_control_is_not_correct():
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
