"""Pallas TPU kernel for gradient-histogram construction.

The GBDT hot loop builds per-feature (B, 3) gradient histograms — a scatter
by bin index, the one primitive TPUs lack.  Matmul reformulations pay a
structural tax: a per-feature one-hot contraction has only ``B·3`` output
elements, so the MXU runs at ``B·3 / 128²`` ≈ 4.7 % utilization no matter
how the nibbles are split (that is what XLA's dot16 path achieves).

This kernel buys utilization back by **folding 8 features into one
128-wide matmul pair**.  With ``B = 256 = 16·16`` split into lo/hi nibbles
and combined keys

  klo = f·16 + (bin % 16)   ∈ [0, 128)
  khi = f·16 + (bin // 16)  ∈ [0, 128)

the contraction ``outᶜ = onehot(klo)ᵀ @ (onehot(khi) · ghᶜ)`` is a clean
(128, C) × (C, 128) MXU matmul per gradient channel whose **diagonal**
16×16 blocks are exactly the 8 features' histograms (off-diagonal blocks
are cross-feature garbage that costs 8× FLOPs but runs at ~100 % MXU
utilization — a net win over the 4.7 % structural bound, biggest in bf16).
Everything stays in VMEM; the kernel emits the full (3, 128, 128) product
per feature-block and XLA extracts the diagonal afterwards (in-kernel
lane slicing and reshapes are Mosaic-hostile).

``accum="bfloat16"`` runs the matmul operands in bf16 with f32
accumulation (preferred_element_type): the one-hot side is exact, only
grad/hess operand values round.

This replaces the per-feature scatter-add inside the reference's native
engine (``LGBM_BoosterUpdateOneIter`` → ConstructHistograms; SURVEY.md §3.1
hot loop).  On CPU the kernel runs in interpret mode (tests only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LO = 16          # low-nibble width
FB = 8           # features folded per matmul: FB * LO = 128 lanes
BMAX = LO * LO   # 256 bins supported; larger falls back to dot16
GH = 3           # gradient channels: grad, hess, count

#: Scoped-VMEM ceiling handed to Mosaic.  One grid step holds about ten
#: lane-padded ``(c, 128)`` 32-bit tiles (the double-buffered ``(c, 3)``
#: gh block, two one-hot scratches, the transposed bins and the matmul
#: operands): 19.4 MB at the ``c = 4096`` chunk ``compute_histogram``
#: uses, which Mosaic's 16 MB default refuses for n >= 262144 rows
#: (measured by compiling for v5e, PERF.md "Bring-up").  v5e has 128 MiB.
_VMEM_LIMIT_BYTES = 32 << 20


def _accum_dtypes(accum: str):
    """(matmul operand dtype, accumulator/output dtype) per accum mode.

    ``"int32"`` is the quantized-gradient mode (ISSUE 17): ``gh`` holds
    integer grid codes, both one-hot operands and the dot accumulate in
    int32, and the kernel output is EXACT int32 — order-invariant across
    chunk schedules and reduction topologies."""
    if accum == "int32":
        return jnp.int32, jnp.int32
    if accum == "bfloat16":
        return jnp.bfloat16, jnp.float32
    return jnp.float32, jnp.float32


def _hist_kernel(binsT_ref, gh_ref, out_ref, lo_scr, hi_scr, *, accum_dtype):
    """One (feature_block, row_chunk) grid step; accumulates into out_ref."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc_t = out_ref.dtype                 # f32, or int32 when quantized
    bT = binsT_ref[...].T                 # (C, FB) int32
    g = gh_ref[...].astype(acc_t)         # (C, 3)
    c = bT.shape[0]

    # Combined one-hots built 16 lanes at a time (per folded feature) into
    # VMEM scratch — n·(16+16) compares per row-feature instead of n·128.
    iota16 = jax.lax.broadcasted_iota(jnp.int32, (c, LO), 1)
    for f in range(FB):
        col = bT[:, f][:, None]
        lo_scr[:, f * LO:(f + 1) * LO] = (col % LO == iota16).astype(
            accum_dtype)
        hi_scr[:, f * LO:(f + 1) * LO] = (col // LO == iota16).astype(
            acc_t)

    lo_oh = lo_scr[...]
    hi_oh = hi_scr[...]
    for ch in range(3):
        rhs = (hi_oh * g[:, ch][:, None]).astype(accum_dtype)
        out_ref[0, ch] += jax.lax.dot_general(
            lo_oh, rhs, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=acc_t)                 # (128, 128)


def _fused_kernel(binsT_ref, idx_ref, gh_ref, out_ref, lo_scr, hi_scr, *,
                  accum_dtype):
    """One (feature_block, idx_chunk) grid step of the FUSED
    gather+histogram: the full (FB, n) binsT block is VMEM-resident
    across the idx-chunk axis, so the per-segment row gather happens
    in-register instead of materializing a (size, f) sub-matrix in HBM
    (PERF.md headroom: the bucket-gather costs as much as the dot16
    histogram itself, ~26 ns/row)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc_t = out_ref.dtype                       # f32, or int32 (quantized)
    idx = idx_ref[...]                          # (C,) i32, pre-clamped
    g = gh_ref[...].astype(acc_t)               # (C, 3), pre-masked
    c = idx.shape[0]

    iota16 = jax.lax.broadcasted_iota(jnp.int32, (c, LO), 1)
    for f in range(FB):
        col = jnp.take(binsT_ref[f, :], idx, axis=0).astype(
            jnp.int32)[:, None]                 # VMEM gather
        lo_scr[:, f * LO:(f + 1) * LO] = (col % LO == iota16).astype(
            accum_dtype)
        hi_scr[:, f * LO:(f + 1) * LO] = (col // LO == iota16).astype(
            acc_t)

    lo_oh = lo_scr[...]
    hi_oh = hi_scr[...]
    for ch in range(3):
        rhs = (hi_oh * g[:, ch][:, None]).astype(accum_dtype)
        out_ref[0, ch] += jax.lax.dot_general(
            lo_oh, rhs, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=acc_t)


#: Rows (lanes) of one grid step of the dot16 kernel, and the scoped VMEM
#: it may use.  From the sweep on the chip (tools/sweep_histogram.py
#: --dot16; PERF.md Findings, PR 28): 0.068 ns a cell at 8192 rows a step
#: and one fold of 8 features, 0.075 at 2048 rows and four folds; a step
#: then holds some 20 MB of ``(128, 8192)`` operands, over Mosaic's 16 MB
#: default.  v5e has 128 MiB.
DOT16_CHUNK = 8192
_DOT16_VMEM_LIMIT_BYTES = 64 << 20


def _dot16_kernel(binsT_ref, ghT_ref, out_ref, acc_ref, *, n_rows):
    """One (fold of 8 features, row chunk) grid step of the dot16 build:
    the bins arrive as the uint8 they are, rows along the lanes, and both
    one-hot operands are made here, in VMEM, where the MXU reads them.

    The fold's bins are spread over 128 sublanes (16 per feature) and
    compared with the sublane's nibble: ``hi[f*16+h, r] = (bin[f, r] >> 4
    == h)`` is the left operand (exact 0/1 in bf16) and ``where(bin[f, r]
    & 15 == l, g[r], 0)`` rounded once to bf16 the right one, so
    ``acc[f*16+h, f'*16+l] += hi @ rhs.T`` in float32 holds the 8
    features' ``(16, 16)`` histograms on its diagonal.  The last chunk
    moves the diagonal blocks to the first 16 lanes and writes
    ``out[fold, ch, f*16+h, l]``: the histogram's own bytes, nothing
    wider."""
    j = pl.program_id(1)
    c = binsT_ref.shape[1]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # rows past the table's end (the last chunk's tail) read whatever the
    # block holds; their gradients are nought, so they add nothing.  So
    # do features past the table's last (a fold of fewer than 8): they
    # fill diagonal blocks of their own, which the caller drops
    lane = jax.lax.broadcasted_iota(jnp.int32, (GH, c), 1)
    g = jnp.where(lane < n_rows - j * c, ghT_ref[...], 0.0)     # (3, c)
    x = binsT_ref[...].astype(jnp.int32)                        # (8, c)
    xrep = jnp.concatenate(
        [jnp.broadcast_to(x[f:f + 1, :], (LO, c)) for f in range(FB)],
        axis=0)                                                 # (128, c)
    nib = jax.lax.broadcasted_iota(jnp.int32, (FB * LO, c), 0) % LO
    hi = jnp.where((xrep >> 4) == nib, 1.0, 0.0).astype(jnp.bfloat16)
    lo = (xrep & (LO - 1)) == nib
    for ch in range(GH):
        rhs = jnp.where(lo, g[ch:ch + 1, :], 0.0).astype(jnp.bfloat16)
        acc_ref[ch] += jax.lax.dot_general(
            hi, rhs, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # (128, 128)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        row_f = jax.lax.broadcasted_iota(
            jnp.int32, (FB * LO, FB * LO), 0) // LO
        for ch in range(GH):
            a = acc_ref[ch]
            d = a                         # feature 0's block is in place
            for f in range(1, FB):
                d = jnp.where(
                    row_f == f,
                    pltpu.roll(a, shift=FB * LO - f * LO, axis=1), d)
            out_ref[0, ch] = d[:, :LO]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "chunk", "interpret"))
def histogram_dot16(binsT: jnp.ndarray, gh: jnp.ndarray, num_bins: int,
                    chunk: int = DOT16_CHUNK,
                    interpret: bool = False) -> jnp.ndarray:
    """The dot16 build with its one-hot product kept on the chip.

    Args:
      binsT: ``(f, n)`` uint8 bins, TRANSPOSED: rows along the minor
        axis, which is how the TPU's XLA lays a ``(n, f)`` uint8 table
        out anyway (its ``.T`` is a bitcast there, not a copy).
      gh: ``(n, 3)`` float32 (grad, hess, count), pre-masked.
      num_bins: at most 256.

    Returns ``(f, num_bins, 3)`` float32: ``bin = 16*hi + lo``, grad and
    hess rounded once to bfloat16 as MXU operands, products and sums in
    float32, counts exact: what ``ops/histogram._hist_dot16`` computes (on
    the chip bit for bit, at its chunk of 8192 rows: PERF.md Findings,
    PR 28).  No array wider than ``binsT`` itself goes through HBM.
    """
    if num_bins > BMAX:
        raise ValueError(f"dot16 kernel supports ≤{BMAX} bins, "
                         f"got {num_bins}")
    f, n = binsT.shape
    c = min(chunk, 128 * ((n + 127) // 128))
    folds = (f + FB - 1) // FB
    chunks = (n + c - 1) // c
    out = pl.pallas_call(
        functools.partial(_dot16_kernel, n_rows=n),
        grid=(folds, chunks),
        in_specs=[
            pl.BlockSpec((FB, c), lambda i, j: (i, j)),
            pl.BlockSpec((GH, c), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, GH, FB * LO, LO),
                               lambda i, j: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((folds, GH, FB * LO, LO),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((GH, FB * LO, FB * LO), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_DOT16_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * GH * chunks * c * folds * 128 * 128,
            bytes_accessed=(folds * FB * n + folds * n * 4 * GH
                            + folds * GH * 128 * LO * 4),
            transcendentals=0),
    )(binsT, gh.astype(jnp.float32).T)
    # out[fold, ch, f*16+hi, lo] -> hist[fold*8+f, hi*16+lo, ch]
    hist = out.reshape(folds, GH, FB, BMAX).transpose(0, 2, 3, 1)
    return hist.reshape(folds * FB, BMAX, GH)[:f, :num_bins]


#: VMEM budget gate for the fused kernel: the (FB, n) uint8 binsT block
#: must stay resident (plus ~1 MB of one-hot scratch and the (3,128,128)
#: accumulator), so n is capped under VMEM/FB bytes with headroom —
#: 1.5M rows = 12 MB block on a ~16 MB-VMEM core.
FUSED_MAX_ROWS = 1_500_000


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "size", "row_chunk",
                                    "accum", "interpret"))
def histogram_pallas_fused(binsT, gh_sub, idx, num_bins: int, size: int,
                           row_chunk: int = 1024, accum: str = "float32",
                           interpret: bool = False) -> jnp.ndarray:
    """Segment histogram with the row gather fused into the kernel.

    Args:
      binsT: ``(f, n)`` uint8/int32 TRANSPOSED binned matrix (the boost
        scan already keeps ``binsT`` hoisted per fit).
      gh_sub: ``(size, 3)`` float32 — the segment's gradient rows,
        gathered by the caller (12 B/row, cheap) and ZERO for padding.
      idx: ``(size,)`` int32 — the segment row ids (``row_order`` slice),
        clamped into ``[0, n)``; padded entries may repeat a valid row
        (their gh is zero).
      size: static bucket size (the grower's power-of-two ladder).

    Returns ``(f, num_bins, 3)`` float32, bit-comparable to gathering
    then calling :func:`histogram_pallas`.
    """
    if num_bins > BMAX:
        raise ValueError(f"pallas fused histogram supports ≤{BMAX} bins, "
                         f"got {num_bins}")
    f, n = binsT.shape
    if n > FUSED_MAX_ROWS:
        raise ValueError(
            f"fused kernel needs the (8, n) binsT block VMEM-resident; "
            f"n={n} exceeds {FUSED_MAX_ROWS}")
    accum_dtype, out_dtype = _accum_dtypes(accum)

    c = min(row_chunk, size)
    f_pad = (-f) % FB
    if f_pad:
        # direct callers only — the grower pre-pads binsT once per tree
        # so this whole-matrix copy never runs in the split loop
        binsT = jnp.pad(binsT, ((0, f_pad), (0, 0)))
    fp = f + f_pad
    nfb = fp // FB
    s_pad = (-size) % c
    if s_pad:
        idx = jnp.pad(idx, (0, s_pad))
        gh_sub = jnp.pad(gh_sub, ((0, s_pad), (0, 0)))

    grid = (nfb, (size + s_pad) // c)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, accum_dtype=accum_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((FB, n), lambda i, j: (i, 0)),   # VMEM-resident
            pl.BlockSpec((c,), lambda i, j: (j,)),
            pl.BlockSpec((c, 3), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 3, FB * LO, FB * LO),
                               lambda i, j: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nfb, 3, FB * LO, FB * LO),
                                       out_dtype),
        scratch_shapes=[
            pltpu.VMEM((c, FB * LO), accum_dtype),
            pltpu.VMEM((c, FB * LO), out_dtype),
        ],
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * 3 * (size + s_pad) * nfb * 128 * 128,
            bytes_accessed=fp * n + (size + s_pad) * 16,
            transcendentals=0),
    )(binsT.astype(jnp.int32) if interpret else binsT,
      idx.astype(jnp.int32), gh_sub.astype(out_dtype))
    out = out.reshape(nfb, 3, FB, LO, FB, LO)
    diag = out[:, :, jnp.arange(FB), :, jnp.arange(FB), :]
    hist = diag.transpose(1, 0, 4, 3, 2).reshape(fp, BMAX, 3)
    return hist[:f, :num_bins, :]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "row_chunk", "accum",
                                    "interpret"))
def histogram_pallas(bins: jnp.ndarray, gh: jnp.ndarray, num_bins: int,
                     row_chunk: int = 1024, accum: str = "float32",
                     interpret: bool = False) -> jnp.ndarray:
    """Per-feature gradient histograms via a VMEM-resident Pallas kernel.

    Args:
      bins: ``(n, f)`` int32 bin indices in ``[0, num_bins)``;
        num_bins ≤ 256.
      gh: ``(n, 3)`` float32 (grad, hess, count), pre-masked.
      accum: "float32" | "bfloat16" — MXU operand precision (accumulation
        is f32 via preferred_element_type) — or "int32" for the
        quantized-gradient mode: ``gh`` holds integer grid codes and the
        whole contraction runs (and returns) exact int32.

    Returns:
      ``(f, num_bins, 3)`` float32 (int32 when ``accum="int32"``).
    """
    if num_bins > BMAX:
        raise ValueError(f"pallas histogram supports ≤{BMAX} bins, "
                         f"got {num_bins}")
    n, f = bins.shape
    accum_dtype, out_dtype = _accum_dtypes(accum)

    c = min(row_chunk, max(128 * ((n + 127) // 128), 128))
    n_pad = (-n) % c
    f_pad = (-f) % FB
    # padded rows point at bin 0 with zero gh weight → no contribution
    binsT = jnp.pad(bins.T, ((0, f_pad), (0, n_pad)))
    gh = jnp.pad(gh.astype(out_dtype), ((0, n_pad), (0, 0)))
    fp, np_ = binsT.shape
    nfb = fp // FB

    grid = (nfb, np_ // c)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, accum_dtype=accum_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((FB, c), lambda i, j: (i, j)),
            pl.BlockSpec((c, 3), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 3, FB * LO, FB * LO),
                               lambda i, j: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nfb, 3, FB * LO, FB * LO),
                                       out_dtype),
        scratch_shapes=[
            pltpu.VMEM((c, FB * LO), accum_dtype),
            pltpu.VMEM((c, FB * LO), out_dtype),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * 3 * np_ * nfb * 128 * 128,
            bytes_accessed=np_ * fp * 4 + np_ * 12 + nfb * 3 * 128 * 128 * 4,
            transcendentals=0),
    )(binsT.astype(jnp.int32), gh)
    # extract diagonal blocks: out[i, ch, f·16+lo, f·16+hi] → hist
    out = out.reshape(nfb, 3, FB, LO, FB, LO)
    diag = out[:, :, jnp.arange(FB), :, jnp.arange(FB), :]  # (FB, nfb, 3, LO, LO)
    # (FB, nfb, 3, lo, hi) → (nfb, FB, hi, lo, 3) → (f, B, 3)
    hist = diag.transpose(1, 0, 4, 3, 2).reshape(fp, BMAX, 3)
    return hist[:f, :num_bins, :]
