// XLA FFI custom-call gradient-histogram kernel (CPU backend).
//
// The first cut of the native CPU histogram used jax.pure_callback, which
// deadlocks on this box: the single-core XLA CPU runtime's worker waits
// on the Python callback while the callback waits for the runtime (seen
// as a stuck second fit in bench.py --force-cpu).  An XLA FFI custom
// call runs synchronously INSIDE the compiled program on the executing
// thread — no Python, no cross-thread handshake — and is the idiomatic
// native-kernel seam jax provides for exactly this.
//
// Same accumulation loop as LightGBM's ConstructHistograms
// (src/io/dense_bin.hpp; expected path, UNVERIFIED — SURVEY.md §3.1):
// one row pass, three fused adds per row-feature into an L2-resident
// (f, B, 3) float32 accumulator.  Masked rows (g == h == c == 0) skip.
//
// Built header-only against jaxlib's bundled xla/ffi/api headers; loaded
// with ctypes and registered via jax.ffi.pycapsule (no pybind11 in this
// image).

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <limits>
#include <vector>

#include "xla/ffi/api/ffi.h"

namespace ffi = xla::ffi;

static ffi::Error HistImpl(ffi::Buffer<ffi::U8> bins,
                           ffi::Buffer<ffi::F32> gh,
                           ffi::ResultBuffer<ffi::F32> out) {
  auto bd = bins.dimensions();
  if (bd.size() != 2 || gh.dimensions().size() != 2 ||
      out->dimensions().size() != 3) {
    return ffi::Error::InvalidArgument(
        "fasthist: need bins (n,f) u8, gh (n,3) f32, out (f,B,3) f32");
  }
  const int64_t n = bd[0];
  const int64_t f = bd[1];
  const int64_t B = out->dimensions()[1];
  const uint8_t* b = bins.typed_data();
  const float* g = gh.typed_data();
  float* o = out->typed_data();
  std::fill(o, o + f * B * 3, 0.f);
  for (int64_t i = 0; i < n; ++i) {
    const float gi = g[3 * i];
    const float hi = g[3 * i + 1];
    const float ci = g[3 * i + 2];
    if (gi == 0.f && hi == 0.f && ci == 0.f) continue;  // masked row
    const uint8_t* br = b + i * f;
    for (int64_t j = 0; j < f; ++j) {
      int64_t bin = br[j];
      if (bin >= B) bin = B - 1;  // safety clamp; mapper guarantees < B
      float* cell = o + (j * B + bin) * 3;
      cell[0] += gi;
      cell[1] += hi;
      cell[2] += ci;
    }
  }
  return ffi::Error::Success();
}

XLA_FFI_DEFINE_HANDLER_SYMBOL(
    MmlsparkFastHist, HistImpl,
    ffi::Ffi::Bind()
        .Arg<ffi::Buffer<ffi::U8>>()
        .Arg<ffi::Buffer<ffi::F32>>()
        .Ret<ffi::Buffer<ffi::F32>>());

// Segment histogram with a DYNAMIC offset/count straight off the
// DataPartition row permutation: no power-of-two bucket ladder, no
// lax.switch, no padding work — C++ loops exactly `cnt` rows.
// (bins (n,f) u8, gh (n,3) f32, row_order (m,) i32, meta (2,) i32
// [off, cnt]) -> out (f,B,3) f32.
static ffi::Error SegHistImpl(ffi::Buffer<ffi::U8> bins,
                              ffi::Buffer<ffi::F32> gh,
                              ffi::Buffer<ffi::S32> row_order,
                              ffi::Buffer<ffi::S32> meta,
                              ffi::ResultBuffer<ffi::F32> out) {
  const int64_t n = bins.dimensions()[0];
  const int64_t f = bins.dimensions()[1];
  const int64_t m = row_order.dimensions()[0];
  const int64_t B = out->dimensions()[1];
  const uint8_t* b = bins.typed_data();
  const float* g = gh.typed_data();
  const int32_t* ro = row_order.typed_data();
  int64_t off = meta.typed_data()[0];
  int64_t cnt = meta.typed_data()[1];
  if (off < 0) off = 0;
  if (off + cnt > m) cnt = m - off;
  float* o = out->typed_data();
  std::fill(o, o + f * B * 3, 0.f);
  // the permutation makes every row access random: prefetch a few rows
  // ahead so the DRAM fetch overlaps the current row's accumulate
  // (LightGBM's indexed ConstructHistograms does the same)
  constexpr int64_t kPrefetch = 8;
  for (int64_t i = 0; i < cnt; ++i) {
    if (i + kPrefetch < cnt) {
      const int64_t pr = ro[off + i + kPrefetch];
      if (pr >= 0 && pr < n) {
        __builtin_prefetch(b + pr * f);
        __builtin_prefetch(b + pr * f + f - 1);  // row tail (2nd line if any)
        __builtin_prefetch(g + 3 * pr);
      }
    }
    int64_t row = ro[off + i];
    if (row < 0 || row >= n) continue;  // pad sentinel
    const float gi = g[3 * row];
    const float hi = g[3 * row + 1];
    const float ci = g[3 * row + 2];
    if (gi == 0.f && hi == 0.f && ci == 0.f) continue;  // bagged out
    const uint8_t* br = b + row * f;
    for (int64_t j = 0; j < f; ++j) {
      int64_t bin = br[j];
      if (bin >= B) bin = B - 1;
      float* cell = o + (j * B + bin) * 3;
      cell[0] += gi;
      cell[1] += hi;
      cell[2] += ci;
    }
  }
  return ffi::Error::Success();
}

XLA_FFI_DEFINE_HANDLER_SYMBOL(
    MmlsparkFastSegHist, SegHistImpl,
    ffi::Ffi::Bind()
        .Arg<ffi::Buffer<ffi::U8>>()
        .Arg<ffi::Buffer<ffi::F32>>()
        .Arg<ffi::Buffer<ffi::S32>>()
        .Arg<ffi::Buffer<ffi::S32>>()
        .Ret<ffi::Buffer<ffi::F32>>());

// DataPartition::Split as one stable in-place pass (LightGBM
// src/io/data_partition.hpp analog; expected path, UNVERIFIED).  The
// leaf's contiguous row_order segment [off, off+cnt) is partitioned
// into left|right by the split column; input_output_aliases makes the
// row_order update zero-copy.  ``meta`` (4,) i32 = [off, cnt, thr,
// use_cat]; ``counts`` out (2,) i32 = [cnt_left, cnt_right].  A table
// with missing values passes ``meta`` (6,) = [..., left_bin,
// missing_bin]: a numeric split sends left_bin (the missing bin where NaN
// goes left, -1 where it goes right) left with the bins <= thr, and
// ``counts`` (3,) adds the numeric split's rows in missing_bin.
static ffi::Error PartitionImpl(ffi::Buffer<ffi::S32> row_order,
                                ffi::Buffer<ffi::U8> col,
                                ffi::Buffer<ffi::S32> meta,
                                ffi::Buffer<ffi::U32> cat_bits,
                                ffi::ResultBuffer<ffi::S32> row_order_out,
                                ffi::ResultBuffer<ffi::S32> counts) {
  const int64_t m = row_order.dimensions()[0];
  const int64_t n = col.dimensions()[0];
  const int32_t* ro_in = row_order.typed_data();
  int32_t* ro = row_order_out->typed_data();
  if (ro != ro_in) std::copy(ro_in, ro_in + m, ro);  // alias miss: copy
  const uint8_t* c = col.typed_data();
  const int32_t* mt = meta.typed_data();
  int64_t off = mt[0];
  int64_t cnt = mt[1];
  const int32_t thr = mt[2];
  const bool use_cat = mt[3] != 0;
  const bool missing = meta.dimensions()[0] >= 6;
  const int64_t left_bin = missing ? mt[4] : -1;
  const int64_t missing_bin = missing && !use_cat ? mt[5] : -1;
  const uint32_t* bits = cat_bits.typed_data();
  if (off < 0) off = 0;
  if (off + cnt > m) cnt = m - off;
  const int64_t max_bin = cat_bits.dimensions()[0] * 32;  // bitset span
  std::vector<int32_t> right;
  right.reserve(static_cast<size_t>(cnt));
  int64_t w = off;
  int64_t missing_rows = 0;
  constexpr int64_t kPrefetch = 16;
  for (int64_t i = 0; i < cnt; ++i) {
    if (i + kPrefetch < cnt) {
      const int32_t pr = ro[off + i + kPrefetch];
      if (pr >= 0 && pr < n) __builtin_prefetch(c + pr);
    }
    const int32_t row = ro[off + i];
    int64_t bin = (row >= 0 && row < n) ? c[row] : 0;
    if (bin >= max_bin) bin = max_bin - 1;  // clamp, like the hist kernels
    const bool left = use_cat ? ((bits[bin >> 5] >> (bin & 31)) & 1u) != 0
                              : (bin <= thr || bin == left_bin);
    missing_rows += bin == missing_bin;
    if (left) {
      ro[w++] = row;
    } else {
      right.push_back(row);
    }
  }
  std::copy(right.begin(), right.end(), ro + w);
  counts->typed_data()[0] = static_cast<int32_t>(w - off);
  counts->typed_data()[1] = static_cast<int32_t>(right.size());
  if (missing) counts->typed_data()[2] = static_cast<int32_t>(missing_rows);
  return ffi::Error::Success();
}

XLA_FFI_DEFINE_HANDLER_SYMBOL(
    MmlsparkFastPartition, PartitionImpl,
    ffi::Ffi::Bind()
        .Arg<ffi::Buffer<ffi::S32>>()
        .Arg<ffi::Buffer<ffi::U8>>()
        .Arg<ffi::Buffer<ffi::S32>>()
        .Arg<ffi::Buffer<ffi::U32>>()
        .Ret<ffi::Buffer<ffi::S32>>()
        .Ret<ffi::Buffer<ffi::S32>>());

// Numeric best-split scan over a (f, B, 3) histogram — the serial-path
// FindBestThreshold (LightGBM src/treelearner/feature_histogram.hpp
// analog; expected path, UNVERIFIED).  Same validity rules and
// first-occurrence (feature-major, bin-minor) argmax order as
// grower.find_best_split's numeric branch: left = bins <= b, last bin
// excluded, min_data_in_leaf / min_sum_hessian gates, gain =
// leaf_gain(l) + leaf_gain(r) - leaf_gain(parent) in the l1-threshold
// form.  The sequential f32 prefix sums here round differently from
// XLA's cumsum, so this kernel's contribution is the WINNING (feature,
// bin) — the Python wrapper recomputes the recorded gain on XLA's
// float trajectory (ops/histogram.py native_find_split).
// parent (3,) f32 = [g, h, c]; conf (6,) f32 = [min_data_in_leaf,
// min_sum_hessian, lambda_l1, lambda_l2, gain_floor, depth_ok];
// outs: gain (1,) f32, fb (2,) i32 = [feature, bin].
static inline float LeafGainL1(float g, float h, float l1, float l2) {
  float t = std::fabs(g) - l1;
  if (t < 0.f) t = 0.f;
  t = std::copysign(t, g);
  if (g == 0.f) t = 0.f;  // jnp.sign(0) == 0
  return (t * t) / (h + l2);
}

static ffi::Error SplitImpl(ffi::Buffer<ffi::F32> hist,
                            ffi::Buffer<ffi::F32> parent,
                            ffi::Buffer<ffi::F32> fmask,
                            ffi::Buffer<ffi::F32> conf,
                            ffi::ResultBuffer<ffi::F32> gain_out,
                            ffi::ResultBuffer<ffi::S32> fb_out) {
  const auto hd = hist.dimensions();
  if (hd.size() != 3 || hd[2] != 3) {
    return ffi::Error::InvalidArgument("fastsplit: hist must be (f,B,3)");
  }
  const int64_t f = hd[0];
  const int64_t B = hd[1];
  if (parent.element_count() < 3 || conf.element_count() < 6 ||
      fmask.element_count() < f) {
    return ffi::Error::InvalidArgument(
        "fastsplit: need parent (3,), conf (6,), fmask (f,)");
  }
  const float* h = hist.typed_data();
  const float pg = parent.typed_data()[0];
  const float ph = parent.typed_data()[1];
  const float pc = parent.typed_data()[2];
  const float* fm = fmask.typed_data();
  const float* cf = conf.typed_data();
  const float min_cnt = cf[0];
  const float min_hess = cf[1];
  const float l1 = cf[2];
  const float l2 = cf[3];
  const float gain_floor = cf[4];
  const bool depth_ok = cf[5] != 0.f;
  const float parent_gain = LeafGainL1(pg, ph, l1, l2);
  float best = -std::numeric_limits<float>::infinity();
  int32_t bf = 0, bb = 0;
  if (depth_ok) {
    for (int64_t j = 0; j < f; ++j) {
      if (!(fm[j] > 0.f)) continue;
      const float* hj = h + j * B * 3;
      float gl = 0.f, hl = 0.f, cl = 0.f;
      for (int64_t b = 0; b + 1 < B; ++b) {  // last bin excluded
        gl += hj[3 * b];
        hl += hj[3 * b + 1];
        cl += hj[3 * b + 2];
        const float gr = pg - gl;
        const float hr = ph - hl;
        const float cr = pc - cl;
        if (cl >= min_cnt && cr >= min_cnt && hl >= min_hess &&
            hr >= min_hess) {
          const float gain = LeafGainL1(gl, hl, l1, l2) +
                             LeafGainL1(gr, hr, l1, l2) - parent_gain;
          if (gain > best) {  // strict: first occurrence wins, like argmax
            best = gain;
            bf = static_cast<int32_t>(j);
            bb = static_cast<int32_t>(b);
          }
        }
      }
    }
  }
  gain_out->typed_data()[0] =
      best > gain_floor ? best
                        : -std::numeric_limits<float>::infinity();
  fb_out->typed_data()[0] = bf;
  fb_out->typed_data()[1] = bb;
  return ffi::Error::Success();
}

XLA_FFI_DEFINE_HANDLER_SYMBOL(
    MmlsparkFastSplit, SplitImpl,
    ffi::Ffi::Bind()
        .Arg<ffi::Buffer<ffi::F32>>()
        .Arg<ffi::Buffer<ffi::F32>>()
        .Arg<ffi::Buffer<ffi::F32>>()
        .Arg<ffi::Buffer<ffi::F32>>()
        .Ret<ffi::Buffer<ffi::F32>>()
        .Ret<ffi::Buffer<ffi::S32>>());

// ---------------------------------------------------------------------------
// Quantized-gradient histograms (ISSUE 17).  gh holds int16 GRID CODES
// (per-round stochastic rounding, grower-side); accumulation is exact
// int32.  Two modes, selected by meta's `packed` flag (the JAX wrapper
// sets it from the static headroom bound ops/histogram.packed_accum_ok):
//
//   packed — the (g, h, count) triple is folded into ONE biased uint64
//     per row: [g + mc : 24 bits][h + mc : 24 bits][count : 16 bits],
//     and the inner loop does a SINGLE 64-bit add per row-feature into
//     an (f, B) uint64 scratch — a third of the adds and 8 bytes of
//     cell traffic instead of 12.  The bias keeps all fields
//     non-negative so field-carries cannot happen while
//     n * 2*max_code < 2^24 and n < 2^16.  Exactness contract per row:
//     count == 1 and |code| <= mc (the training invariant — the count
//     channel is the 0/1 bag mask and the quantizer clips).  Rows that
//     violate it (and count==0 rows) accumulate DIRECTLY into the int32
//     output instead, so the result is exact for any input; the final
//     unpack ADDS the scratch into the output.
//
//   unpacked — three int32 adds per row-feature, no scratch; used when
//     the packed bound fails.
namespace {

struct QAccum {
  int64_t f, B, mc;
  bool packed;
  int32_t* o;                  // (f, B, 3) int32, pre-zeroed
  std::vector<uint64_t> acc;   // (f, B) packed scratch (packed mode)

  void Init(int64_t f_, int64_t B_, int64_t mc_, bool packed_,
            int32_t* o_) {
    f = f_;
    B = B_;
    mc = mc_;
    packed = packed_;
    o = o_;
    std::fill(o, o + f * B * 3, 0);
    if (packed) acc.assign(static_cast<size_t>(f * B), 0ull);
  }

  inline void Row(const uint8_t* br, int32_t gi, int32_t hi, int32_t ci) {
    if (packed && ci == 1 && gi >= -mc && gi <= mc && hi >= -mc &&
        hi <= mc) {
      const uint64_t pv =
          (static_cast<uint64_t>(static_cast<uint32_t>(gi + mc)) << 40) |
          (static_cast<uint64_t>(static_cast<uint32_t>(hi + mc)) << 16) |
          1ull;
      uint64_t* a = acc.data();
      for (int64_t j = 0; j < f; ++j) {
        int64_t bin = br[j];
        if (bin >= B) bin = B - 1;
        a[j * B + bin] += pv;
      }
      return;
    }
    if (gi == 0 && hi == 0 && ci == 0) return;  // masked row
    for (int64_t j = 0; j < f; ++j) {
      int64_t bin = br[j];
      if (bin >= B) bin = B - 1;
      int32_t* cell = o + (j * B + bin) * 3;
      cell[0] += gi;
      cell[1] += hi;
      cell[2] += ci;
    }
  }

  void Finish() {
    if (!packed) return;
    const uint64_t* a = acc.data();
    for (int64_t c = 0; c < f * B; ++c) {
      const uint64_t v = a[c];
      if (v == 0) continue;
      const int64_t k = static_cast<int64_t>(v & 0xFFFFull);
      const int64_t hs =
          static_cast<int64_t>((v >> 16) & 0xFFFFFFull) - k * mc;
      const int64_t gs = static_cast<int64_t>(v >> 40) - k * mc;
      int32_t* cell = o + c * 3;
      cell[0] += static_cast<int32_t>(gs);
      cell[1] += static_cast<int32_t>(hs);
      cell[2] += static_cast<int32_t>(k);
    }
  }
};

}  // namespace

// (bins (n,f) u8, gh (n,3) s16, meta (2,) s32 [packed, max_code])
//   -> out (f,B,3) s32.
static ffi::Error QHistImpl(ffi::Buffer<ffi::U8> bins,
                            ffi::Buffer<ffi::S16> gh,
                            ffi::Buffer<ffi::S32> meta,
                            ffi::ResultBuffer<ffi::S32> out) {
  auto bd = bins.dimensions();
  if (bd.size() != 2 || gh.dimensions().size() != 2 ||
      out->dimensions().size() != 3 || meta.element_count() < 2) {
    return ffi::Error::InvalidArgument(
        "fastqhist: need bins (n,f) u8, gh (n,3) s16, meta (2,) s32, "
        "out (f,B,3) s32");
  }
  const int64_t n = bd[0];
  const int64_t f = bd[1];
  const int64_t B = out->dimensions()[1];
  const uint8_t* b = bins.typed_data();
  const int16_t* g = gh.typed_data();
  const bool packed = meta.typed_data()[0] != 0;
  const int64_t mc = meta.typed_data()[1];
  QAccum q;
  q.Init(f, B, mc, packed, out->typed_data());
  for (int64_t i = 0; i < n; ++i) {
    q.Row(b + i * f, g[3 * i], g[3 * i + 1], g[3 * i + 2]);
  }
  q.Finish();
  return ffi::Error::Success();
}

XLA_FFI_DEFINE_HANDLER_SYMBOL(
    MmlsparkFastQHist, QHistImpl,
    ffi::Ffi::Bind()
        .Arg<ffi::Buffer<ffi::U8>>()
        .Arg<ffi::Buffer<ffi::S16>>()
        .Arg<ffi::Buffer<ffi::S32>>()
        .Ret<ffi::Buffer<ffi::S32>>());

// Quantized segment histogram off the DataPartition permutation.
// (bins (n,f) u8, gh (n,3) s16, row_order (m,) s32, meta (4,) s32
// [off, cnt, packed, max_code]) -> out (f,B,3) s32.
static ffi::Error SegQHistImpl(ffi::Buffer<ffi::U8> bins,
                               ffi::Buffer<ffi::S16> gh,
                               ffi::Buffer<ffi::S32> row_order,
                               ffi::Buffer<ffi::S32> meta,
                               ffi::ResultBuffer<ffi::S32> out) {
  if (meta.element_count() < 4) {
    return ffi::Error::InvalidArgument(
        "fastsegqhist: meta must be (4,) s32 [off, cnt, packed, mc]");
  }
  const int64_t n = bins.dimensions()[0];
  const int64_t f = bins.dimensions()[1];
  const int64_t m = row_order.dimensions()[0];
  const int64_t B = out->dimensions()[1];
  const uint8_t* b = bins.typed_data();
  const int16_t* g = gh.typed_data();
  const int32_t* ro = row_order.typed_data();
  int64_t off = meta.typed_data()[0];
  int64_t cnt = meta.typed_data()[1];
  const bool packed = meta.typed_data()[2] != 0;
  const int64_t mc = meta.typed_data()[3];
  if (off < 0) off = 0;
  if (off + cnt > m) cnt = m - off;
  QAccum q;
  q.Init(f, B, mc, packed, out->typed_data());
  constexpr int64_t kPrefetch = 8;
  for (int64_t i = 0; i < cnt; ++i) {
    if (i + kPrefetch < cnt) {
      const int64_t pr = ro[off + i + kPrefetch];
      if (pr >= 0 && pr < n) {
        __builtin_prefetch(b + pr * f);
        __builtin_prefetch(b + pr * f + f - 1);
        __builtin_prefetch(g + 3 * pr);
      }
    }
    const int64_t row = ro[off + i];
    if (row < 0 || row >= n) continue;  // pad sentinel
    q.Row(b + row * f, g[3 * row], g[3 * row + 1], g[3 * row + 2]);
  }
  q.Finish();
  return ffi::Error::Success();
}

XLA_FFI_DEFINE_HANDLER_SYMBOL(
    MmlsparkFastSegQHist, SegQHistImpl,
    ffi::Ffi::Bind()
        .Arg<ffi::Buffer<ffi::U8>>()
        .Arg<ffi::Buffer<ffi::S16>>()
        .Arg<ffi::Buffer<ffi::S32>>()
        .Arg<ffi::Buffer<ffi::S32>>()
        .Ret<ffi::Buffer<ffi::S32>>());
