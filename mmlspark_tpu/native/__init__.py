"""Native runtime extensions (C++), with build-on-demand and fallback.

The reference backs its IO layer and compute hot loops with JVM/Hadoop
native streams and LightGBM C++; here the equivalents are small C++
extensions compiled on first use with the in-image toolchain:

* ``fastio.cc``  — directory scan / bulk parallel file read / murmur3.
* ``fastbin.cc`` — the BinMapper quantization inner loop
  (``bin_columns``), the single-core-hostile part of dataset prep.
* ``fasthist_ffi.cc`` — XLA FFI custom-call gradient-histogram kernel
  for the CPU backend's GBDT hot loop (``hist_ffi_handler``), compiled
  against jaxlib's bundled ``xla/ffi/api`` headers.

Public surface:

* ``available() -> bool`` — whether the IO extension loaded (or could be
  built); all callers must keep a pure-Python fallback.
* ``read_file(path) -> bytes``
* ``read_files(paths, n_threads=8) -> list[bytes]`` — thread-pool bulk
  read with the GIL released.
* ``scan_dir(root, pattern, recursive) -> [(path, size, mtime)]``
* ``bin_columns_available() -> bool`` / ``bin_columns(...)`` — native
  binning kernel (callers fall back to numpy searchsorted).

Set ``MMLSPARK_TPU_NO_NATIVE=1`` to force the Python fallbacks.
"""

from __future__ import annotations

import logging
import os
import subprocess
import sys
import sysconfig
from typing import List, Optional, Tuple

log = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_mods = {}


def _so_path(stem: str) -> str:
    tag = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return os.path.join(_HERE, f"{stem}{tag}")


def _compile(src: str, out: str, include: str, *extra: str) -> bool:
    """Compile one .cc into a shared object with the first C++ compiler
    that works.  A compiler that runs and fails has its stderr logged:
    the callers' numpy/XLA fallbacks are correct but slower, and a
    silent fallback hides a broken toolchain."""
    for cxx in ("g++", "c++", "clang++"):
        try:
            proc = subprocess.run(
                [cxx, "-O2", "-std=c++17", "-shared", "-fPIC",
                 f"-I{include}", src, "-o", out, *extra],
                capture_output=True, text=True, timeout=180)
        except (OSError, subprocess.TimeoutExpired) as e:
            log.debug("native build: %s unusable (%s)", cxx, e)
            continue
        if proc.returncode == 0:
            return True
        log.warning("native build of %s with %s failed (rc=%d):\n%s",
                    os.path.basename(src), cxx, proc.returncode,
                    proc.stderr[-4000:])
    return False


def _build(src_name: str, stem: str) -> bool:
    """Compile one .cc into a CPython extension in the package directory."""
    return _compile(os.path.join(_HERE, src_name), _so_path(stem),
                    sysconfig.get_paths()["include"], "-pthread")


def _fresh(out_path: str, src_path: str) -> bool:
    """A built artifact is fresh when it exists and is no older than its
    source (a missing source can't invalidate it)."""
    return (os.path.exists(out_path)
            and (not os.path.exists(src_path)
                 or os.path.getmtime(out_path) >= os.path.getmtime(src_path)))


def _load(stem: str = "_fastio", src_name: str = "fastio.cc"):
    if stem in _mods:
        return _mods[stem]
    _mods[stem] = None
    if os.environ.get("MMLSPARK_TPU_NO_NATIVE"):
        return None
    so = _so_path(stem)
    src = os.path.join(_HERE, src_name)
    # stale .so + failed rebuild (no compiler / read-only dir): still load
    # the old binary rather than silently losing the native path
    if not _fresh(so, src) and not _build(src_name, stem) \
            and not os.path.exists(so):
        return None
    try:
        sys.path.insert(0, _HERE)
        _mods[stem] = __import__(stem)
    except ImportError:
        _mods[stem] = None
    finally:
        if _HERE in sys.path:
            sys.path.remove(_HERE)
    return _mods[stem]


def available() -> bool:
    return _load() is not None


def bin_columns_available() -> bool:
    return _load("_fastbin", "fastbin.cc") is not None


def predict_forest_available() -> bool:
    return _load("_fastforest", "fastforest.cc") is not None


def predict_forest(X, feat, thr, left, right, leaf, single, is_cat, dleft,
                   cat_bnd, cat_words, num_class, has_cat, out,
                   n_threads: int = 0) -> None:
    """Native early-exit forest margin accumulation into ``out`` (n, K)
    float32; see fastforest.cc for the exactness contract vs the jitted
    walk.  Raises RuntimeError when the extension is unavailable
    (callers gate on :func:`predict_forest_available`)."""
    mod = _load("_fastforest", "fastforest.cc")
    if mod is None:
        raise RuntimeError("mmlspark_tpu.native._fastforest unavailable; "
                           "use the jitted _predict_forest path")
    mod.predict_forest(X, feat, thr, left, right, leaf, single, is_cat,
                       dleft, cat_bnd, cat_words, int(num_class),
                       int(bool(has_cat)), int(n_threads), out)


_FFI_LIB = None


def _build_ffi(src_name: str, stem: str) -> bool:
    """Compile an XLA FFI shared lib against jaxlib's bundled headers."""
    from jax import ffi as _jffi
    # ".bin", not ".so": a bare .so in the package dir would be picked up
    # as a CPython extension module by pkgutil walkers (it isn't one)
    return _compile(os.path.join(_HERE, src_name),
                    os.path.join(_HERE, f"{stem}.bin"),
                    _jffi.include_dir())


def _ffi_lib():
    global _FFI_LIB
    if _FFI_LIB is None:
        _FFI_LIB = False
        if not os.environ.get("MMLSPARK_TPU_NO_NATIVE"):
            path = os.path.join(_HERE, "fasthist_ffi.bin")
            src = os.path.join(_HERE, "fasthist_ffi.cc")
            if _fresh(path, src) or _build_ffi("fasthist_ffi.cc",
                                               "fasthist_ffi"):
                import ctypes
                try:
                    _FFI_LIB = ctypes.cdll.LoadLibrary(path)
                except OSError:
                    _FFI_LIB = False
    return _FFI_LIB


def hist_ffi_handler():
    """ctypes function pointer for the XLA FFI histogram custom call
    (fasthist_ffi.cc), or None when the lib can't build/load.  Callers
    wrap it with ``jax.ffi.pycapsule`` and register under platform
    "cpu"."""
    lib = _ffi_lib()
    return getattr(lib, "MmlsparkFastHist", None) if lib else None


def seg_hist_ffi_handler():
    """Dynamic-offset segment histogram FFI handler (leaf hot path)."""
    lib = _ffi_lib()
    return getattr(lib, "MmlsparkFastSegHist", None) if lib else None


def partition_ffi_handler():
    """In-place DataPartition::Split FFI handler."""
    lib = _ffi_lib()
    return getattr(lib, "MmlsparkFastPartition", None) if lib else None


def split_ffi_handler():
    """Numeric best-split scan FFI handler (serial-path FindBestThreshold)."""
    lib = _ffi_lib()
    return getattr(lib, "MmlsparkFastSplit", None) if lib else None


def qhist_ffi_handler():
    """Quantized-gradient histogram FFI handler (ISSUE 17): int16 grid
    codes in, int32 accumulation out, with a packed-int64 single-add
    fast mode under the headroom bound (ops/histogram.packed_accum_ok)."""
    lib = _ffi_lib()
    return getattr(lib, "MmlsparkFastQHist", None) if lib else None


def seg_qhist_ffi_handler():
    """Quantized dynamic-offset segment histogram FFI handler."""
    lib = _ffi_lib()
    return getattr(lib, "MmlsparkFastSegQHist", None) if lib else None


def bin_columns(X, bext, nb, base, lo, scale, use_table, missing_bin,
                out) -> None:
    """Native BinMapper transform; see fastbin.cc for the argument
    contract.  Raises RuntimeError when the extension is unavailable
    (callers gate on :func:`bin_columns_available`)."""
    mod = _load("_fastbin", "fastbin.cc")
    if mod is None:
        raise RuntimeError("mmlspark_tpu.native._fastbin unavailable; use "
                           "the numpy searchsorted path")
    mod.bin_columns(X, bext, nb, base, lo, scale, use_table, missing_bin,
                    out)


def read_file(path: str) -> bytes:
    mod = _load()
    if mod is not None:
        return mod.read_file(path)
    with open(path, "rb") as f:
        return f.read()


def read_files(paths: List[str], n_threads: int = 8) -> List[bytes]:
    mod = _load()
    if mod is not None:
        return mod.read_files(list(paths), n_threads)
    return [read_file(p) for p in paths]


def murmur3_batch(terms: List[str], seed: int = 42) -> List[int]:
    """Spark-compatible Murmur3_x86_32 of each term's UTF-8 bytes, as
    signed int32 (C++ path only; callers gate on :func:`available` and
    fall back to featurize.hashing's pure-python murmur3_32)."""
    mod = _load()
    if mod is None:
        raise RuntimeError(
            "mmlspark_tpu.native extension unavailable; use the "
            "pure-python hasher (featurize.hashing.murmur3_32)")
    return mod.murmur3_batch(list(terms), seed)


def scan_dir(root: str, pattern: Optional[str] = None,
             recursive: bool = True) -> List[Tuple[str, int, float]]:
    mod = _load()
    if mod is not None:
        return mod.scan_dir(root, pattern, recursive)
    import fnmatch
    out: List[Tuple[str, int, float]] = []

    def walk(d: str):
        names = sorted(os.listdir(d))
        subdirs = []
        for name in names:
            full = os.path.join(d, name)
            if os.path.isdir(full):
                if not os.path.islink(full):   # no symlink-dir recursion
                    subdirs.append(full)
            elif os.path.isfile(full) and (
                    pattern is None or fnmatch.fnmatch(name, pattern)):
                st = os.stat(full)
                out.append((full, int(st.st_size), float(st.st_mtime)))
        if recursive:
            for sd in subdirs:
                walk(sd)

    walk(root)
    return out
