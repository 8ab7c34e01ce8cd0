"""Test bootstrap: force an 8-device virtual CPU platform.

The reference tests distributed behavior on ``local[*]`` with multiple
partitions (SURVEY.md §4); the TPU-native analog is a host-platform mesh of
8 virtual CPU devices, so every shard_map/psum path is exercised without TPU
hardware.  Must run before jax initializes its backends, hence conftest.
"""

import gc
import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# The crash flight recorder (core/telemetry.record_flight) defaults to
# artifacts/ in the CWD; tests that exercise crash paths (chaos smoke,
# injected fit failures) must not litter the repo's committed artifacts
# directory, so point the default at a throwaway tmp dir.  Tests that
# assert ON the recorder override this explicitly.
if "MMLSPARK_TPU_FLIGHTREC_DIR" not in os.environ:
    import tempfile

    os.environ["MMLSPARK_TPU_FLIGHTREC_DIR"] = tempfile.mkdtemp(
        prefix="flightrec_tests_")

# Persistent XLA compilation cache: the suite is compile-bound on CPU
# (every distinct fit shape jits a boost scan), and several tests spawn
# fresh worker processes that would otherwise recompile identical
# programs from scratch.  The on-disk cache dedupes compiles across
# those subprocesses AND across consecutive runs.  The env vars are set
# so worker SUBPROCESSES spawned by tests inherit the same cache (they
# import jax fresh and read these at init).
from mmlspark_tpu.core.backend import configure_compile_cache  # noqa: E402

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      configure_compile_cache())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
jax.config.update(
    "jax_persistent_cache_min_compile_time_secs",
    float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Fast signal first: end-to-end benchmark, notebook and
    2-process-gang executions are the slowest items in the suite
    (minutes each) and assert product quality, not unit correctness —
    run them LAST so a wall-clock-capped tier-1 pass spends its budget
    on the wide unit surface before the handful of long tails.  Stable
    partition: the relative order inside each group is unchanged."""
    slow_files = ("test_benchmarks.py", "test_notebooks.py",
                  "test_multicontroller.py")
    fast = [it for it in items
            if os.path.basename(it.fspath.strpath) not in slow_files]
    slow = [it for it in items
            if os.path.basename(it.fspath.strpath) in slow_files]
    items[:] = fast + slow


@pytest.fixture(autouse=True)
def _bounded_executable_mappings():
    """Every live XLA CPU executable holds about a dozen memory mappings,
    and the jit caches keep every program the suite ever compiled.  One
    pytest process reaches the kernel's ``vm.max_map_count`` (65530)
    around test_histogram.py, and the next compile or cache write dies
    with SIGSEGV/SIGABRT, taking the rest of the suite with it.  Past
    40 000 mappings, drop the in-memory executables; the on-disk compile
    cache makes reloading the ones still needed cheap."""
    yield
    try:
        with open("/proc/self/maps") as fh:
            mappings = sum(1 for _ in fh)
    except OSError:
        return
    if mappings > 40_000:
        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def mesh2():
    """2-device DATA-ONLY mesh over the forced host platform — the ring
    collective's layout (ops/pallas_collectives.py needs exactly one
    named axis for the interpret-mode DMA discharge), and the mesh the
    ISSUE-10 bit-parity contract is pinned on (at D=2 a ring's pairwise
    adds commute with psum's, so forests must match BITWISE)."""
    from jax.sharding import Mesh
    from mmlspark_tpu.core.mesh import DATA_AXIS
    return Mesh(np.asarray(jax.devices()[:2]), (DATA_AXIS,))


@pytest.fixture(scope="session")
def mesh2_2axis():
    """2-device standard (data, feature) mesh — what the engine receives
    BEFORE collective resolution rebuilds it data-only."""
    from mmlspark_tpu.core.mesh import build_mesh
    return build_mesh(data=2, feature=1, devices=jax.devices()[:2])


@pytest.fixture
def mosaic_interpreted(monkeypatch):
    """``method="dot16"`` takes the Mosaic kernel as on the TPU, run by
    the Pallas interpreter; returns the calls the kernel received."""
    import mmlspark_tpu.ops.histogram as H
    import mmlspark_tpu.ops.pallas_histogram as PH
    calls = []
    real = PH.histogram_dot16
    monkeypatch.setattr(PH, "histogram_dot16", lambda *a, **k: (
        calls.append(a[0].shape), real(*a, **k))[1])
    monkeypatch.setattr(H, "_dot16_on_chip",
                        lambda num_bins, quantized: (not quantized
                                                     and num_bins <= 256))
    monkeypatch.setattr(H, "pallas_interpret", lambda: True)
    return calls


@pytest.fixture(scope="session")
def binary_table(rng):
    """Small adult-income-shaped binary classification table."""
    from sklearn.datasets import make_classification
    X, y = make_classification(
        n_samples=2000, n_features=20, n_informative=10, n_redundant=4,
        random_state=7, class_sep=0.8)
    return {"features": X, "label": y.astype(np.float64)}


@pytest.fixture(scope="session")
def regression_table(rng):
    from sklearn.datasets import make_regression
    X, y = make_regression(
        n_samples=2000, n_features=15, n_informative=10, noise=10.0,
        random_state=11)
    return {"features": X, "label": y.astype(np.float64)}


def start_echo_server(post_hook=None, include_headers=False,
                      strip_query=False):
    """Shared loopback JSON echo service for HTTP-stage tests.

    POST → ``{"echo": payload}`` (plus the request headers when
    ``include_headers``), unless ``post_hook(path, payload, headers)``
    returns a ``(status, obj)`` override; GET → ``{"path": ...}``
    (query-stripped when ``strip_query``, for deterministic re-runs).
    Returns ``(base_url, shutdown)``.
    """
    import json as _json
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def _send(self, code, obj):
            body = _json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            try:
                payload = _json.loads(self.rfile.read(n)) if n else None
            except (ValueError, UnicodeDecodeError):
                payload = "<binary>"
            if post_hook is not None:
                hooked = post_hook(self.path, payload, self.headers)
                if hooked is not None:
                    self._send(*hooked)
                    return
            obj = {"echo": payload}
            if include_headers:
                obj["headers"] = dict(self.headers)
            self._send(200, obj)

        def do_GET(self):
            path = self.path.split("?")[0] if strip_query else self.path
            self._send(200, {"path": path})

    server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()

    def shutdown():
        server.shutdown()
        server.server_close()

    return f"http://127.0.0.1:{server.server_address[1]}", shutdown
