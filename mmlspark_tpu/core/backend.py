"""Which device this process runs on, decided in one place.

Three start-up decisions every entry point (``chip_smoke.py``,
``bench.py``, the ``tools/`` scripts, ``tests/conftest.py``, the spawned
CPU workers) shares:

* whether Pallas kernels run through Mosaic or the interpreter
  (:func:`pallas_interpret`);
* where the persistent XLA compile cache lives
  (:func:`configure_compile_cache`);
* that a worker process which is a CPU scorer by design never reaches for
  an accelerator its parent holds (:func:`pin_cpu_backend`).
"""

from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_compile_cache`` — fixed: a cache directory named
#: after a pid, a time or a temp dir is never found again.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def pallas_interpret() -> bool:
    """True when Pallas kernels must run in interpret mode: every backend
    but a real TPU.  On TPU the kernels go through Mosaic and a compile
    failure surfaces to the caller — nothing downgrades it."""
    return jax.default_backend() != "tpu"


def configure_compile_cache() -> str:
    """Place the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it, so nothing is
    touched in code.  Unset: the fixed in-checkout directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return _DEFAULT_CACHE_DIR


def pin_cpu_backend() -> None:
    """Hold THIS process to the CPU backend before anything initialises
    one.  For spawned workers whose parent may own the chip (a chip
    belongs to one process): the inherited ``JAX_PLATFORMS=tpu`` would
    make the child's first jax call fail or hang.  The env var is assigned
    for grandchildren; the live config is updated because jax has read the
    inherited value at import."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
