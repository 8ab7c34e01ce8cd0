"""Program start-up and device selection (core/backend.py, chip_smoke.py):
where the compile cache goes, when Pallas interprets, that a CPU rehearsal
never reads as a chip pass, and that spawned workers stay off the chip."""

import os
import subprocess
import sys

import jax
import numpy as np

from mmlspark_tpu.core import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, env=None, timeout=600):
    args = ([sys.executable, "-c", code_or_args]
            if isinstance(code_or_args, str) else code_or_args)
    return subprocess.run(args, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


class TestCompileCache:
    def test_env_var_is_honoured_and_config_untouched(self, monkeypatch,
                                                      tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert backend.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_the_fixed_in_checkout_path(self):
        """Unset, a fresh process lands on <checkout>/.jax_compile_cache:
        no pid, time or temp name, the same from every entry point."""
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        out = _run("import jax\n"
                   "from mmlspark_tpu.core.backend import "
                   "configure_compile_cache as c\n"
                   "print(c()); print(jax.config.jax_compilation_cache_dir)",
                   env=env)
        assert out.returncode == 0, out.stderr
        want = os.path.join(REPO, ".jax_compile_cache")
        assert out.stdout.split() == [want, want]


class TestPallasInterpret:
    def test_decided_from_default_backend_alone(self, monkeypatch):
        assert backend.pallas_interpret() is True      # the CPU suite
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert backend.pallas_interpret() is False
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        assert backend.pallas_interpret() is True


class TestChipSmoke:
    def test_no_accelerator_fails_without_a_result(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = _run([sys.executable, "chip_smoke.py"], env=env)
        assert out.returncode not in (0, 3)
        assert "platform: cpu" in out.stdout
        assert '"ok"' not in out.stdout

    def test_cpu_rehearsal_runs_and_is_not_a_chip_pass(self):
        """Every phase at tiny size on a 4-device CPU mesh (Pallas
        interpreted, ring kernels included): names its platform, prints
        no result line, exits with the rehearsal code."""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        out = _run([sys.executable, "chip_smoke.py", "--rehearse"], env=env)
        assert out.returncode == 3, out.stdout[-3000:] + out.stderr[-3000:]
        assert "platform: cpu  device_kind: cpu  count: 4" in out.stdout
        for phase in ("train", "score", "serve", "mesh", "kernels"):
            assert f"[{phase}]" in out.stdout, phase
        last = out.stdout.strip().splitlines()[-1]
        assert "REHEARSAL" in last and "NOT a chip pass" in last
        assert '"ok"' not in out.stdout

    def test_a_failing_phase_cannot_exit_zero(self):
        code = ("import sys, chip_smoke as cs\n"
                "def boom(*a): raise cs.SmokeFailure('injected')\n"
                "cs.phase_host = boom\n"
                "sys.exit(cs.main(['--rehearse']))")
        out = _run(code, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 1
        assert "SmokeFailure: injected" in out.stderr
        assert '"ok"' not in out.stdout


class TestWorkersStayOffTheChip:
    """A chip belongs to one process.  The parent's environment says
    JAX_PLATFORMS=tpu; there is no TPU in this sandbox, so a child that
    reaches for one fails at its first jax call."""

    def test_spawned_fleet_worker_scores_on_cpu(self, monkeypatch):
        from mmlspark_tpu.gbdt import LightGBMRegressor
        from mmlspark_tpu.io.fleet import PredictorFleet
        rng = np.random.default_rng(2)
        X = rng.normal(size=(300, 5)).astype(np.float32)
        y = (X[:, 0] - X[:, 1]).astype(np.float64)
        booster = LightGBMRegressor(
            numIterations=4, numLeaves=7, minDataInLeaf=5,
            parallelism="serial", verbosity=0).fit(
            {"features": X, "label": y}).getModel()
        # jax's "jit" backend makes the worker initialise a backend
        # whatever the native scorer's availability
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        fleet = PredictorFleet(booster, num_shards=1, spawn=True,
                               backend="jit", join_timeout=120.0).start()
        try:
            got = np.asarray(fleet(X[:16]))
        finally:
            fleet.stop()
        np.testing.assert_allclose(
            got, np.asarray(booster.predict_margin(X[:16])), rtol=1e-6,
            atol=1e-6)

    def test_multiprocess_http_worker_entry_pins_cpu(self):
        """The spawn target fences the worker before its body runs."""
        code = ("import jax\n"
                "import mmlspark_tpu.io.serving as s\n"
                "s._mp_worker_main = lambda *a: print(\n"
                "    jax.config.jax_platforms, jax.default_backend())\n"
                "s._mp_worker_proc()")
        out = _run(code, env=dict(os.environ, JAX_PLATFORMS="tpu"))
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.split() == ["cpu", "cpu"]

    def test_spawn_targets_are_the_fenced_entries(self):
        from mmlspark_tpu.io.serving import (MultiprocessHTTPServer,
                                             _mp_worker_proc)
        srv = MultiprocessHTTPServer(num_workers=1, spawn_workers=False)
        try:
            assert srv._make_proc(0)._target is _mp_worker_proc
        finally:
            srv.stop()
