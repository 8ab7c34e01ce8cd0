"""Gradient-histogram construction — the GBDT hot loop.

This is the TPU-native replacement for the per-feature histogram build inside
the reference's native engine (``LGBM_BoosterUpdateOneIter`` → ConstructHistograms;
SURVEY.md §3.1 hot loop).  The reference scatters grad/hess into per-feature
bin buffers with CPU/CUDA code; scatter-add with data-dependent indices is the
one primitive TPUs dislike.  Four formulations, one per condition the code
can observe, and ``auto`` picks among them from the backend alone
(:func:`_auto_method`): ``native`` on the CPU, ``dot16`` on the TPU,
``segment`` anywhere else.

``native``
    The C++ accumulator behind an XLA FFI custom call (native/fasthist.cc):
    the CPU backend's build, and the fused gather+histogram of a leaf's
    segment (:func:`native_segment_hist`).  Not available on accelerators.

``segment``
    ``jax.ops.segment_sum`` per feature (vmapped).  Lowers to XLA scatter;
    correct everywhere: the XLA reference the other builds are held to,
    and ``auto`` where neither of the others applies.

``dot16``
    Nibble-decomposed one-hot matmul.  A bin index in [0, 256) is split into
    hi/lo 4-bit halves; the histogram becomes two chained contractions
    ``loᵀ @ (hi ⊗ gh)`` that run on the MXU with 16× less transient memory
    than a naive 256-wide one-hot.  FLOPs are identical to the naive one-hot
    (n·B per channel) but the working set stays in VMEM-sized chunks.
    On the TPU, for float gradients and at most 256 bins, the one-hot
    operands are made where the contraction consumes them
    (``pallas_histogram.histogram_dot16``): XLA's own program writes them
    to HBM, 840 bytes a cell whose bin is one byte (PERF.md, PR 28).
    :func:`_hist_dot16` stays as the definition of the result, and as the
    build of every other case.

``onehot``
    Naive one-hot einsum, row/feature chunked.  A reference for tests;
    ``auto`` never picks it.

All accept already *masked* gradient triples ``gh = (grad, hess, count)``
(rows outside the active leaf carry zeros), which is how leaf-conditional
histograms stay static-shaped under jit — and how the same code path serves
the distributed data-parallel learner: shards build local histograms and
``psum`` them over the mesh (SURVEY.md §5.8's socket-allreduce replacement).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import ffi as _jffi

from ..core.backend import pallas_interpret

#: channels in the gradient triple
GH_CHANNELS = 3  # grad, hess, count


_NATIVE_OK: Optional[bool] = None


def _native_available() -> bool:
    """Whether the XLA FFI custom calls are registered (CPU backend)."""
    global _NATIVE_OK
    if _NATIVE_OK is None:
        _NATIVE_OK = False
        try:
            from .. import native
            handlers = {
                "mmlspark_fasthist": native.hist_ffi_handler(),
                "mmlspark_fastseghist": native.seg_hist_ffi_handler(),
                "mmlspark_fastpartition": native.partition_ffi_handler(),
                "mmlspark_fastsplit": native.split_ffi_handler(),
                "mmlspark_fastqhist": native.qhist_ffi_handler(),
                "mmlspark_fastsegqhist": native.seg_qhist_ffi_handler(),
            }
            if all(h is not None for h in handlers.values()):
                for name, h in handlers.items():
                    _jffi.register_ffi_target(
                        name, _jffi.pycapsule(h), platform="cpu")
                _NATIVE_OK = True
        except Exception:  # noqa: BLE001 - no toolchain
            _NATIVE_OK = False
    return _NATIVE_OK


def _native_applies(num_bins) -> bool:
    return (num_bins <= 256 and jax.default_backend() == "cpu"
            and _native_available())


def packed_accum_ok(n_rows: int, max_code: int) -> bool:
    """Whether the packed-int64 single-add native accumulation is exact
    for ``n_rows`` quantized rows on a ``max_code`` grid: the 16-bit
    count field needs every cell's row count < 2^16 and the two biased
    24-bit g/h fields need ``n * 2*max_code < 2^24`` (each row adds at
    most ``2*max_code`` to a biased field).  Beyond the bound the C++
    kernel runs its unpacked int32x3 mode instead."""
    return (max_code > 0 and n_rows < (1 << 16)
            and n_rows * 2 * max_code < (1 << 24))


def native_segment_hist(bins, gh, row_order, off, cnt, num_bins,
                        max_code: int = 0):
    """Fused gather+histogram of the DataPartition segment
    ``row_order[off:off+cnt]`` via the FFI kernel, or None when the
    native CPU path doesn't apply (callers fall back to the bucket-ladder
    gather + :func:`compute_histogram`).  C++ loops exactly ``cnt`` rows
    — no power-of-two padding, no gathered sub-matrix materialization
    (PERF.md round-3 headroom: the bucket gather cost matched the
    histogram's)."""
    if not _native_applies(num_bins):
        return None
    f = bins.shape[1]
    if jnp.issubdtype(gh.dtype, jnp.integer):
        # quantized-gradient mode (ISSUE 17): int16 grid codes in,
        # exact int32 accumulation out; packed single-add fast mode
        # when the headroom bound holds for the WHOLE matrix (cnt is
        # dynamic, so the static gate uses n — conservative).
        packed = packed_accum_ok(bins.shape[0], max_code)
        meta = jnp.stack([off, cnt, jnp.asarray(int(packed), jnp.int32),
                          jnp.asarray(max_code, jnp.int32)]).astype(
                              jnp.int32)
        return _jffi.ffi_call(
            "mmlspark_fastsegqhist",
            jax.ShapeDtypeStruct((f, num_bins, GH_CHANNELS), jnp.int32),
        )(bins.astype(jnp.uint8), gh.astype(jnp.int16),
          row_order.astype(jnp.int32), meta)
    meta = jnp.stack([off, cnt]).astype(jnp.int32)
    return _jffi.ffi_call(
        "mmlspark_fastseghist",
        jax.ShapeDtypeStruct((f, num_bins, GH_CHANNELS), jnp.float32),
    )(bins.astype(jnp.uint8), gh.astype(jnp.float32),
      row_order.astype(jnp.int32), meta)


def native_partition(row_order, col, off, cnt, thr, use_cat, cat_bits,
                     num_bins, left_bin=None):
    """LightGBM ``DataPartition::Split`` as one in-place stable C++ pass
    (input_output_aliases donates ``row_order``), or None when the native
    CPU path doesn't apply.  Returns ``(row_order', cnt_left, cnt_right)``
    like the ``lax.switch`` bucket-ladder version it replaces — without
    the ladder's padding work or branch dispatch.  ``left_bin``: the bin a
    numeric split sends left besides ``<= thr``
    (``TreeArrays.node_dleft``); with it the pass also counts, and
    returns fourth, a numeric split's rows in the missing bin."""
    if not _native_applies(num_bins):
        return None
    m = row_order.shape[0]
    meta = [off, cnt, thr, use_cat.astype(jnp.int32)]
    if left_bin is not None:
        meta += [left_bin, jnp.asarray(num_bins - 1, jnp.int32)]
    meta = jnp.stack(meta).astype(jnp.int32)
    ro, counts = _jffi.ffi_call(
        "mmlspark_fastpartition",
        (jax.ShapeDtypeStruct((m,), jnp.int32),
         jax.ShapeDtypeStruct((2 if left_bin is None else 3,),
                              jnp.int32)),
        input_output_aliases={0: 0},
    )(row_order.astype(jnp.int32), col.astype(jnp.uint8), meta,
      cat_bits.astype(jnp.uint32))
    if left_bin is None:
        return ro, counts[0], counts[1]
    return ro, counts[0], counts[1], counts[2]


def native_find_split(hist, parent_g, parent_h, parent_c, feature_mask,
                      depth_ok, min_data_in_leaf, min_sum_hessian,
                      lambda_l1, lambda_l2, gain_floor, num_bins):
    """Numeric FindBestThreshold as one C++ pass (serial CPU path), or
    None when the native path doesn't apply.  Returns ``(gain, feat,
    bin)``; the caller supplies the is_cat/cat_bits zeros.

    The C++ scan picks the winning (feature, bin) with the same validity
    rules and first-occurrence flat order as grower.find_best_split, but
    its sequential f32 prefix sums round differently from XLA's cumsum,
    so the WINNER is what it contributes — the recorded gain is then
    recomputed here by the XLA float path on the winning feature row.
    That keeps best_gain (the best-first leaf priority) and the exported
    split_gain on XLA's float trajectory; the forests can differ from
    the pure-XLA path only when two candidates tie within prefix-sum
    rounding (fuzz-pinned winner-identical in tests/test_histogram.py)."""
    if not _native_applies(num_bins):
        return None
    parent = jnp.stack([parent_g, parent_h, parent_c]).astype(jnp.float32)
    conf = jnp.stack([
        jnp.float32(min_data_in_leaf), jnp.float32(min_sum_hessian),
        jnp.float32(lambda_l1), jnp.float32(lambda_l2),
        jnp.float32(gain_floor),
        jnp.asarray(depth_ok, jnp.float32)])
    gain_n, fb = _jffi.ffi_call(
        "mmlspark_fastsplit",
        (jax.ShapeDtypeStruct((1,), jnp.float32),
         jax.ShapeDtypeStruct((2,), jnp.int32)),
    )(hist.astype(jnp.float32), parent,
      feature_mask.astype(jnp.float32), conf)
    feat, b = fb[0], fb[1]
    l1 = jnp.float32(lambda_l1)
    l2 = jnp.float32(lambda_l2)

    def lg(g, h):
        t = jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, jnp.float32(0))
        return jnp.square(t) / (h + l2)

    row = jax.lax.dynamic_index_in_dim(hist.astype(jnp.float32), feat,
                                       axis=0, keepdims=False)   # (B, 3)
    cum = jnp.cumsum(row, axis=0)
    cell = jax.lax.dynamic_index_in_dim(cum, b, axis=0,
                                        keepdims=False)          # (3,)
    gl, hl = cell[0], cell[1]
    pg = jnp.float32(parent_g)
    ph = jnp.float32(parent_h)
    gain_x = lg(gl, hl) + lg(pg - gl, ph - hl) - lg(pg, ph)
    # The XLA-trajectory gain must ALSO clear the floor: when the C++
    # prefix-sum rounding clears it but gain_x lands at/below it, the
    # pure-XLA path would reject this split — return -inf, not a finite
    # sub-floor gain (ADVICE r4).
    gain = jnp.where(jnp.isfinite(gain_n[0])
                     & (gain_x > jnp.float32(gain_floor)),
                     gain_x, jnp.float32(-jnp.inf))
    return gain, feat, b


#: what ``method`` may name; anything else is a ``ValueError``
METHODS = ("auto", "native", "segment", "dot16", "onehot")


def check_method(method: str) -> None:
    """Refuse a name that is not one of :data:`METHODS` (input from
    outside: ``histogramMethod``, ``passThroughArgs``)."""
    if method not in METHODS:
        raise ValueError(f"Unknown histogram method {method!r}; valid: "
                         + ", ".join(METHODS))


def _auto_method() -> str:
    """The formulation ``auto`` stands for, from the backend alone.

    CPU backend: the native C++ accumulator (fasthist.cc) when the
    extension builds — it beats every XLA scatter/matmul formulation at
    all sizes on one core (~1 ns vs ~6 ns per row-feature; PERF.md) —
    and ``segment`` without it.  TPU: ``dot16``, at every row count and
    width (the sweep on the chip found no crossover: PERF.md Findings,
    PR 28).  Any other backend: ``segment``."""
    backend = jax.default_backend()
    if backend == "cpu" and _native_available():
        return "native"
    return "dot16" if backend == "tpu" else "segment"


def _dot16_on_chip(num_bins: int, quantized: bool) -> bool:
    """Whether a ``dot16`` call site compiles the Mosaic build: on the
    TPU, float gradients (this stack refuses the int32 kernel: ``Bad
    lhs/rhs type vector<128x128xi32>``), nibbles that cover the bins.
    Rows and features do not enter: at every rung and root of the
    benchmark's cells the kernel takes 0.07–0.15 ns a cell where XLA's
    formulation takes 0.30–1.33 (the sweep on the chip: PERF.md
    Findings, PR 28)."""
    return (jax.default_backend() == "tpu" and not quantized
            and num_bins <= 256)


def histogram_build(method: str, num_bins: int, quantized: bool) -> str:
    """The implementation :func:`compute_histogram` compiles for a call
    site: the resolved method, and for ``dot16`` which of its two builds
    (``dot16/mosaic``, ``dot16/xla``)."""
    check_method(method)
    if method == "auto":
        method = _auto_method()
    if method == "native" and (num_bins > 256 or not _native_available()):
        return "segment"
    if method == "dot16":
        return ("dot16/mosaic" if _dot16_on_chip(num_bins, quantized)
                else "dot16/xla")
    return method


def compute_histogram(bins: jnp.ndarray, gh: jnp.ndarray, num_bins: int,
                      method: str = "auto",
                      row_chunk: int = 8192,
                      max_code: int = 0) -> jnp.ndarray:
    """Per-feature gradient histograms.

    Args:
      bins: ``(n, f)`` integer bin indices in ``[0, num_bins)``.
      gh: ``(n, 3)`` float (grad, hess, count); rows not in the active leaf
        must already be zeroed.  An INTEGER dtype selects quantized mode
        (ISSUE 17): ``gh`` holds int16 grid codes and every formulation
        accumulates exactly in int32 — the result is ``(f, B, 3)`` int32
        (dequantize at split evaluation, grower-side).
      num_bins: static bin count B.
      method: one of :data:`METHODS`; anything else raises
        ``ValueError`` before any device work.
      max_code: quantized mode only — the grid's |code| bound, which
        gates the native packed-int64 single-add fast path
        (:func:`packed_accum_ok`).

    Returns:
      ``(f, num_bins, 3)`` float32 histogram (int32 in quantized mode).
    """
    quantized = jnp.issubdtype(gh.dtype, jnp.integer)
    acc_dtype = jnp.int32 if quantized else jnp.float32
    build = histogram_build(method, num_bins, quantized)
    if build == "native":
        if quantized:
            return _hist_native_q(bins, gh, num_bins, max_code)
        return _hist_native(bins, gh, num_bins)
    if build == "segment":
        return _hist_segment(bins, gh, num_bins, acc_dtype)
    if build == "dot16/mosaic":
        from .pallas_histogram import histogram_dot16
        # the TPU's XLA keeps a uint8 table rows-minor: no copy here
        return histogram_dot16(bins.T, gh, num_bins,
                               interpret=pallas_interpret())
    if build == "dot16/xla":
        return _hist_dot16(bins, gh, num_bins, row_chunk, acc_dtype)
    return _hist_onehot(bins, gh, num_bins, row_chunk, acc_dtype)


#: rows per call of :func:`bin_counts`: every build sums float32, which
#: counts exactly below 2^24 rows a bin
COUNT_CHUNK = 1 << 23


def bin_counts(bins: jnp.ndarray, num_bins: int) -> jnp.ndarray:
    """Rows per (feature, bin) of an ``(n, f)`` binned table, exact:
    ``(f, num_bins)`` int32.  The histogram the backend builds anyway
    (:func:`compute_histogram`, ``auto``) with unit weights, over row
    chunks small enough that its float32 sums are whole numbers, added
    as int32: a bin may hold more than 2^24 rows of the table (a
    three-valued column of a 3 x 10^7-row click log does).  One chunk is
    sliced out of the table at a time, and the last one reaches back
    over rows the one before has counted, which weigh nought in it: one
    shape, so one build.  Call it under ``jit``."""
    n, f = bins.shape
    chunk = min(COUNT_CHUNK, n)

    def body(i, acc):
        start = jnp.minimum(i * chunk, n - chunk)
        rows = jax.lax.dynamic_slice(bins, (start, 0), (chunk, f))
        fresh = start + jnp.arange(chunk) >= i * chunk
        weights = jnp.broadcast_to(fresh[:, None].astype(jnp.float32),
                                   (chunk, GH_CHANNELS))
        hist = compute_histogram(rows, weights, num_bins)
        return acc + hist[:, :, GH_CHANNELS - 1].astype(jnp.int32)

    return jax.lax.fori_loop(0, -(-n // chunk), body,
                             jnp.zeros((f, num_bins), jnp.int32))


def _hist_native(bins, gh, num_bins):
    """CPU-backend native accumulation via an XLA FFI custom call
    (native/fasthist_ffi.cc): the C++ loop runs synchronously INSIDE the
    compiled program — no Python in the loop (a pure_callback variant
    deadlocked the single-core CPU runtime), no extra materialization, so
    this IS the fused gather+histogram path, LightGBM-style.  Never
    selected on accelerator backends (_auto_method gates on cpu)."""
    f = bins.shape[1]
    return _jffi.ffi_call(
        "mmlspark_fasthist",
        jax.ShapeDtypeStruct((f, num_bins, GH_CHANNELS), jnp.float32),
    )(bins.astype(jnp.uint8), gh.astype(jnp.float32))


def _hist_native_q(bins, gh, num_bins, max_code):
    """Quantized-gradient native accumulation (ISSUE 17): int16 grid
    codes in, exact int32 histogram out.  When :func:`packed_accum_ok`
    holds, the C++ kernel folds the (g, h, count) triple into ONE biased
    packed int64 per row and does a single 64-bit add per row-feature —
    a third of the adds and two thirds of the cell traffic of the f32
    kernel — then unpacks to (f, B, 3) int32 at the end."""
    f = bins.shape[1]
    packed = packed_accum_ok(bins.shape[0], max_code)
    meta = jnp.stack([jnp.asarray(int(packed), jnp.int32),
                      jnp.asarray(max_code, jnp.int32)]).astype(jnp.int32)
    return _jffi.ffi_call(
        "mmlspark_fastqhist",
        jax.ShapeDtypeStruct((f, num_bins, GH_CHANNELS), jnp.int32),
    )(bins.astype(jnp.uint8), gh.astype(jnp.int16), meta)


def _hist_segment(bins, gh, num_bins, acc_dtype=jnp.float32):
    gh = gh.astype(acc_dtype)

    def per_feature(col):
        return jax.ops.segment_sum(gh, col.astype(jnp.int32),
                                   num_segments=num_bins)

    # vmap over features: (f, n) -> (f, B, 3)
    return jax.vmap(per_feature)(bins.T)


def _hist_onehot(bins, gh, num_bins, row_chunk, acc_dtype=jnp.float32):
    n, f = bins.shape
    gh = gh.astype(acc_dtype)
    chunk = min(row_chunk, n)
    pad = (-n) % chunk
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        gh = jnp.pad(gh, ((0, pad), (0, 0)))
    bins_c = bins.reshape(-1, chunk, f)
    gh_c = gh.reshape(-1, chunk, GH_CHANNELS)

    def step(acc, args):
        b, g = args
        b = b.astype(jnp.int32)   # bins may arrive uint8; cast per chunk
        onehot = (b[:, :, None] == jnp.arange(num_bins)[None, None, :])
        acc = acc + jnp.einsum("nfb,nc->fbc", onehot.astype(acc_dtype), g)
        return acc, None

    init = jnp.zeros((f, num_bins, GH_CHANNELS), acc_dtype)
    out, _ = jax.lax.scan(step, init, (bins_c, gh_c))
    return out


def _hist_dot16(bins, gh, num_bins, row_chunk, acc_dtype=jnp.float32):
    """Nibble-decomposed histogram: B = hi*16 + lo, two MXU contractions.
    With ``acc_dtype=int32`` (quantized mode) both one-hots and the
    contraction run in integers — the MXU nibble fold accumulates the
    int one-hot matmul in int32, bit-exactly."""
    n, f = bins.shape
    n_hi = (num_bins + 15) // 16
    gh = gh.astype(acc_dtype)
    chunk = min(row_chunk, n)
    lo_iota = jnp.arange(16)
    hi_iota = jnp.arange(n_hi)

    def step(acc, b, g):                 # (c, f) int, (c, 3) f32
        b = b.astype(jnp.int32)          # bins may arrive uint8
        lo = b % 16                      # (c, f)
        hi = b // 16
        lo_oh = (lo[:, :, None] == lo_iota).astype(acc_dtype)     # (c, f, 16)
        hi_oh = (hi[:, :, None] == hi_iota).astype(acc_dtype)     # (c, f, Hh)
        # rhs[n, f, hi, ch] = hi_oh * gh  -> contract n with lo_oh
        # two-step: t = einsum('cfh,cx->cfhx') is big; fuse instead:
        # out[f, l, h, x] = sum_c lo_oh[c,f,l] * hi_oh[c,f,h] * g[c,x]
        # Do it as batched matmul per feature: (16, c) @ (c, Hh*3)
        rhs = hi_oh[:, :, :, None] * g[:, None, None, :]          # (c, f, Hh, 3)
        rhs = rhs.reshape(b.shape[0], f, n_hi * GH_CHANNELS)
        out = jnp.einsum("cfl,cfr->flr", lo_oh, rhs,
                         preferred_element_type=acc_dtype)        # (f, 16, Hh*3)
        out = out.reshape(f, 16, n_hi, GH_CHANNELS)
        out = jnp.transpose(out, (0, 2, 1, 3)).reshape(
            f, n_hi * 16, GH_CHANNELS)
        return acc + out[:, :num_bins]

    return _sum_over_row_chunks(
        step, bins, gh, chunk,
        jnp.zeros((f, num_bins, GH_CHANNELS), acc_dtype))


def _sum_over_row_chunks(step, bins, gh, chunk, init):
    """``acc = step(acc, bins[rows], gh[rows])`` over chunks of ``chunk``
    rows.  Whole chunks are sliced out of the table inside the loop and
    the tail (fewer rows than a chunk) is padded alone and added last:
    the sums, and their order, of scanning a padded, reshaped copy of the
    table.  That scan cost the v5e's compiler 20 s and 1 GB of host
    memory per million rows whenever n was not a power of two (15
    minutes and over 30 GB at 3e7 rows: PERF.md Findings, PR 27)."""
    n, f = bins.shape

    def body(i, acc):
        b = jax.lax.dynamic_slice(bins, (i * chunk, 0), (chunk, f))
        g = jax.lax.dynamic_slice(gh, (i * chunk, 0),
                                  (chunk, gh.shape[1]))
        return step(acc, b, g)

    out = jax.lax.fori_loop(0, n // chunk, body, init)
    head = (n // chunk) * chunk
    if head < n:
        pad = ((0, chunk - (n - head)), (0, 0))
        out = step(out, jnp.pad(bins[head:], pad), jnp.pad(gh[head:], pad))
    return out
