"""Debug / sanitizer mode — the SURVEY §5.2 subsystem.

The reference has no sanitizers (JVM memory safety plus prebuilt native
libs; SWIG handle misuse surfaces as CI segfaults).  The TPU-native
equivalent is ``jax.experimental.checkify`` compiled INTO the training
program:

* ``user_checks`` — ``checkify.debug_check`` invariants placed in the
  engine: finite gradients/hessians after the objective, and bin indices
  inside the histogram range (XLA clamps/drops OOB indices *silently* —
  the memory-corruption analog a sanitizer exists to make loud).
  The engine traces them only in debug mode
  (``GrowerConfig.debug_checks``, a static argument of the boost
  programs): a ``debug_check`` does nothing unless the program is
  checkified, but it still lowers to a dead computation that carries
  checkify's process-wide error number as a constant, so the HLO, and
  with it the persistent compile cache's key, would differ on every
  trace of the same program.

Blanket ``nan_checks`` is deliberately NOT enabled: split finding masks
empty-bin gain arithmetic with ``-inf``/``where``, so transient NaNs
before the mask are expected and would false-positive.  Automatic
``index_checks`` is also off: checkify's scatter rewrite crashes on the
vmapped ``segment_sum`` histogram (jax bug — "tuple index out of range"
inside the scatter error rule), so the OOB class is covered by the
explicit bins-range invariant instead.

Enable with ``MMLSPARK_TPU_DEBUG=1`` or :func:`debug_mode`.  Serial
training paths only (checkify does not discharge through ``shard_map``);
distributed fits ignore the flag.
"""

from __future__ import annotations

import functools
import os
from typing import Callable

_STATE = {"enabled": None}


def debug_enabled() -> bool:
    if _STATE["enabled"] is None:
        _STATE["enabled"] = os.environ.get(
            "MMLSPARK_TPU_DEBUG", "") not in ("", "0")
    return bool(_STATE["enabled"])


def debug_mode(on: bool) -> None:
    """Programmatic override of the MMLSPARK_TPU_DEBUG env switch."""
    _STATE["enabled"] = bool(on)


def checked(fn: Callable) -> Callable:
    """Wrap a jitted callable with checkify when debug mode is on.

    Raises ``jax.experimental.checkify.JaxRuntimeError`` (via
    ``err.throw()``) on the first failed check; returns ``fn`` untouched
    when debug mode is off, so call sites can wrap unconditionally.
    """
    if not debug_enabled():
        return fn
    from jax.experimental import checkify

    checked_fn = checkify.checkify(fn, errors=checkify.user_checks)

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        err, out = checked_fn(*args, **kwargs)
        err.throw()
        return out

    return wrapped


def check_finite(name: str, *arrays) -> None:
    """``debug_check`` that every array is finite (no-op outside
    checkify)."""
    import jax.numpy as jnp
    from jax.experimental import checkify
    for a in arrays:
        checkify.debug_check(
            jnp.all(jnp.isfinite(a)), "non-finite values in " + name)


def check_bins_in_range(bins, num_bins: int) -> None:
    """``debug_check`` that bin indices fit the histogram range — XLA
    would silently clamp/drop OOB indices and train on garbage.  Both
    ends: the int32 bin dtype (>256 total bins) can hold negative
    indices, which scatter ops drop just as silently."""
    import jax.numpy as jnp
    from jax.experimental import checkify
    b = bins.astype(jnp.int32)
    checkify.debug_check(
        (jnp.max(b) < num_bins) & (jnp.min(b) >= 0),
        "bin index out of range (negative or >= num_bins): corrupt "
        "binned matrix")
