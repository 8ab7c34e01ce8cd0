"""Pallas TPU kernel for gradient-histogram construction.

The GBDT hot loop builds per-feature (B, 3) gradient histograms — a scatter
by bin index, the one primitive TPUs lack.  Matmul reformulations pay a
structural tax: a per-feature one-hot contraction has only ``B·3`` output
elements, so the MXU runs at ``B·3 / 128²`` ≈ 4.7 % utilization no matter
how the nibbles are split, and XLA's own program of it writes the one-hot
operands to HBM (``ops/histogram._hist_dot16``: 840 bytes a cell whose bin
is one byte).

:func:`histogram_dot16` buys both back by **folding 8 features into one
128-wide matmul** and making the operands where the MXU reads them.  With
``B = 256 = 16·16`` split into hi/lo nibbles and combined keys

  khi = f·16 + (bin // 16)  ∈ [0, 128)
  klo = f·16 + (bin % 16)   ∈ [0, 128)

the contraction ``accᶜ = onehot(khi) @ (onehot(klo) · ghᶜ)ᵀ`` is a clean
(128, C) × (C, 128) MXU matmul per gradient channel whose **diagonal**
16×16 blocks are exactly the 8 features' histograms (off-diagonal blocks
are cross-feature garbage that costs 8× FLOPs but runs at ~100 % MXU
utilization).  Operands are bf16 (the one-hot side exact, grad/hess
rounded once), accumulation is f32; everything stays in VMEM and the
kernel writes the diagonal blocks only.

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
BMAX = LO * LO   # 256 bins supported; larger keeps XLA's formulation
GH = 3           # gradient channels: grad, hess, count

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
