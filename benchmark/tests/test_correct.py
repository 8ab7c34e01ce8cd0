"""``correct`` has to come out false when the timed path is broken, and
the control (the reference's split search in the nearest precision below
the configuration's) has to fail the comparison.

Each test drives the harness's own run (benchmark/run.py ``execute``) at
the configuration's rehearsal size on whatever backend jax has, skipping
only the look for a chip, with ``engine.train`` broken underneath: a step
that returns its state unchanged, half of the batch left out, the
exchange between chips left out (four devices), an answer altered where
it is produced.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os

import numpy as np
import pytest

from benchmark import run as harness
from benchmark.checks import readings

DP4 = os.path.join(harness.HERE, "rehearsal",
                   "epsilon_fit_dp4.BENCHMARK.json")


def run_cell(workload, seed, bench_json=None):
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", "0.2",
            "--trace", "0", "--rehearse"]
    if bench_json:
        argv += ["--bench-json", bench_json]
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


@pytest.mark.parametrize("workload", ["epsilon_fit", "bosch_fit"])
def test_sound_run_is_correct(workload):
    result = run_cell(workload, 2147483659)
    assert result["correct"] is True
    assert all(c["value"] is not None for c in result["compared"].values())


def failing(result):
    assert result["correct"] is False
    return {k for k, c in result["compared"].items()
            if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("workload", ["epsilon_fit", "bosch_fit"])
def test_state_left_unchanged_is_not_correct(workload, broken_train):
    def wrap(real):
        def train(bins, labels, *a, **kw):
            return readings.stale_state(real(bins, labels, *a, **kw), labels)
        return train
    broken_train(wrap)
    assert "leaf_value_gap" in failing(run_cell(workload, 77))


@pytest.mark.parametrize("workload", ["epsilon_fit", "bosch_fit"])
def test_half_batch_is_not_correct(workload, broken_train):
    def wrap(real):
        def train(bins, labels, weights, *a, **kw):
            n = len(labels) // 2
            return real(bins[:n], labels[:n], weights, *a, **kw)
        return train
    broken_train(wrap)
    assert "count_mismatch" in failing(run_cell(workload, 78))


@pytest.mark.parametrize("workload", ["epsilon_fit", "bosch_fit"])
def test_altered_leaf_is_not_correct(workload, broken_train):
    broken_train(lambda real: lambda *a, **kw: readings.altered_leaf(
        real(*a, **kw)))
    assert "leaf_value_gap" in failing(run_cell(workload, 79))


@pytest.mark.parametrize("workload", ["epsilon_fit", "bosch_fit"])
def test_altered_split_is_not_correct(workload, broken_train):
    def wrap(real):
        def train(bins, labels, weights, mapper, *a, **kw):
            return readings.altered_split(
                real(bins, labels, weights, mapper, *a, **kw), mapper)
        return train
    broken_train(wrap)
    assert "count_mismatch" in failing(run_cell(workload, 80))


def test_exchange_left_out_is_not_correct(broken_train):
    import jax
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices: XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4")

    def wrap(real):
        def train(bins, labels, weights, *a, **kw):
            n = len(labels) // 4            # the first shard's rows alone
            return real(bins[:n], labels[:n], weights, *a, **kw)
        return train
    assert run_cell("epsilon_fit_dp4", 81, DP4)["correct"] is True
    broken_train(wrap)
    assert "count_mismatch" in failing(run_cell("epsilon_fit_dp4", 81, DP4))


#: sizes at which a test can hold the control: the rehearsal sizes grow too
#: few nodes for a mean over them to say anything
CONTROL_SIZES = {
    "epsilon_fit": {"rows": 100000, "features": 64,
                    "params": {"numLeaves": 255, "minSumHessianInLeaf": 10.0}},
    "bosch_fit": {"rows": 40000, "features": 96,
                  "params": {"numLeaves": 255, "minSumHessianInLeaf": 5.0}},
}


@pytest.mark.parametrize("workload", sorted(CONTROL_SIZES))
def test_control_is_not_correct(workload):
    """The reference's own split search with float8_e4m3 gradients, the
    nearest precision below the configuration's bfloat16: the splits it
    puts first lie, on average over the compared nodes, further below the
    best than the limit allows, on three seeds; the sound fit does not."""
    import importlib

    _, cell, config, traffic = harness.load_cell(
        os.path.join(harness.ROOT, "BENCHMARK.json"), workload)
    config["rehearsal"] = CONTROL_SIZES[workload]
    limits = harness.load_limits(workload)
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


def test_native_loop_and_numpy_agree():
    from benchmark.reference import gbdt
    rng = np.random.default_rng(0)
    bins = rng.integers(0, 255, size=(5000, 70), dtype=np.uint8)
    g, h = rng.normal(size=5000), rng.random(5000)
    rows = np.sort(rng.choice(5000, 1200, replace=False))
    for r in (None, rows):
        a = gbdt.node_histogram(bins, r, g, h)
        b = gbdt.node_histogram(bins, r, g, h, native=False)
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-12)
        assert a[..., 2].sum() == (5000 if r is None else 1200) * 70
