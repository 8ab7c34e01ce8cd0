"""Leaf-wise histogram tree grower, fully jit-compatible.

TPU-native replacement for LightGBM's ``SerialTreeLearner``/
``DataParallelTreeLearner`` (driven by the reference through
``LGBM_BoosterUpdateOneIter``; SURVEY.md §3.1 hot loop).  Design notes:

* **Static shapes.**  A tree has a fixed budget of ``num_leaves`` leaves and
  ``num_leaves - 1`` internal nodes; growth is a ``fori_loop`` of
  ``num_leaves - 1`` split steps with inactive steps masked out via
  ``lax.cond`` — XLA's answer to LightGBM's dynamic leaf queue.
* **Leaf membership as a vector.**  Instead of partitioned row indices, a
  ``row_leaf`` (n,) assignment vector selects the split leaf's rows by mask;
  leaf-conditional histograms are built from *masked* gradient triples so
  every step has identical shape and cost.
* **Histogram subtraction.**  Each split builds one child histogram and
  derives the sibling by subtraction, exactly like LightGBM.
* **Leaf numbering parity.**  Splitting leaf ``l`` at step ``i`` creates
  internal node ``i``; the left child keeps leaf id ``l`` and the right
  child becomes leaf ``i + 1`` — the same numbering LightGBM uses, so model
  export is a direct array dump.
* **Distributed.**  Pass ``axis_name`` when running under ``shard_map`` with
  rows sharded across the mesh: local histograms are ``psum``-reduced — the
  ICI-collective replacement for LightGBM's socket ``Network::Allreduce``
  (SURVEY.md §5.8).  Feature-axis sharding is layered on in
  :mod:`mmlspark_tpu.gbdt.distributed`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.histogram import compute_histogram

EPS_GAIN = 1e-10


class EFBArrays(NamedTuple):
    """Device-side EFB expansion maps (see gbdt/efb.py): bins holds G
    bundle columns; histograms and split columns reconstruct per ORIGINAL
    feature through these static-shaped arrays."""
    gather_idx: jnp.ndarray   # (f, B) i32 flat (bundle*B + bundle_bin)
    valid: jnp.ndarray        # (f, B) bool bins feature j actually uses
    bundle_of: jnp.ndarray    # (f,) i32
    off_of: jnp.ndarray       # (f,) i32
    nb_of: jnp.ndarray        # (f,) i32
    default_of: jnp.ndarray   # (f,) i32


def _efb_expand(hist_b, efb):
    """(G, B, 3) bundle histogram -> exact (f, B, 3) per-feature histogram.

    Member slices come from a static flat gather; each feature's default
    bin - whose rows the bundle encodes implicitly as "not this member" -
    is reconstituted as leaf_total minus the explicit bins.  Bundle 0's
    bins partition every row, so its sum IS the leaf total.
    """
    f = efb.gather_idx.shape[0]
    with jax.named_scope("efb_expand"):
        flat = hist_b.reshape(-1, hist_b.shape[-1])          # (G*B, 3)
        hist = jnp.take(flat, efb.gather_idx.reshape(-1), axis=0)
        hist = hist.reshape(f, hist_b.shape[1], hist_b.shape[2])
        hist = hist * efb.valid[:, :, None]
        tot = jnp.sum(hist_b[0], axis=0)                  # (3,) leaf total
        deficit = tot[None, :] - jnp.sum(hist, axis=1)    # (f, 3)
        return hist.at[jnp.arange(f), efb.default_of].add(deficit)


def efb_feature_column(binsT, feat, efb, num_bins):
    """Reconstruct original feature ``feat``'s bin column from its bundle
    column: in-range values shift back by the member offset (the last
    member slot is the NaN bin), everything else is the default bin."""
    g = efb.bundle_of[feat]
    bcol = jnp.take(binsT, g, axis=0).astype(jnp.int32)
    off = efb.off_of[feat]
    nb = efb.nb_of[feat]
    raw = bcol - off
    inr = (raw >= 0) & (raw <= nb)
    return jnp.where(inr, jnp.where(raw == nb, num_bins - 1, raw),
                     efb.default_of[feat])


@dataclass(frozen=True)
class GrowerConfig:
    """Static hyper-parameters (hashable → usable as a jit static arg)."""
    num_leaves: int = 31
    max_depth: int = -1
    num_bins: int = 256
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    hist_method: str = "auto"
    #: histogram only the smaller child's rows, gathered into a power-of-two
    #: bucket picked by ``lax.switch`` or, over ``SEGMENT_CHUNK_ROWS`` rows,
    #: chunk by chunk (LightGBM's DataPartition + smaller-child trick,
    #: re-shaped for static-shape jit); the sibling comes from subtraction.
    #: ~L full-data scans per tree become ~2-3 full-data equivalents.
    #: Disable to force full masked scans.
    compact_rows: bool = True
    #: smallest compaction bucket (rows); buckets double up to 2^ceil(lg n)
    min_bucket: int = 2048
    #: PV-Tree voting parallelism (Meng et al. 2016; LightGBM
    #: tree_learner=voting, top_k): > 0 with ``axis_name`` set keeps leaf
    #: histograms SHARD-LOCAL; each shard votes its top-k features by
    #: local gain, votes are allgathered, and only the 2k winning
    #: features' histograms are psum-reduced — comm per split drops from
    #: O(f*B) to O(k*B + votes).
    voting_k: int = 0
    axis_name: Optional[str] = None          # data-parallel psum axis
    feature_axis_name: Optional[str] = None  # feature-parallel axis
    #: cross-shard histogram reduction: "psum" (XLA all-reduce) or
    #: "ring" (Pallas on-chip ring reduce-scatter/all-gather,
    #: ops/pallas_collectives.py).  Resolved by the engine at config
    #: build (engine._resolve_collective_cfg); "ring" requires a
    #: data-only 1-axis mesh and uses psum where the VMEM gates refuse.
    collective: str = "psum"
    #: static size of the data mesh axis (the ring kernels need it at
    #: trace time; 1 = serial).  Set by distributed._sharded_cfg.
    data_axis_size: int = 1
    #: categorical split finding (LightGBM Fisher-grouping analog); static
    #: so the no-categorical compile pays zero cost for the extra machinery
    use_categorical: bool = False
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    #: quantized-gradient training (ISSUE 17; Shi et al. NeurIPS 2022,
    #: LightGBM ``use_quantized_grad``): discretize each round's (g, h)
    #: to a symmetric integer grid with seeded stochastic rounding and
    #: accumulate EXACT int32 histograms — the sibling subtraction
    #: becomes bit-exact in integers and the cross-shard reduces carry
    #: low-bit slabs.  0 = off; 8/16 = grid bits.  Resolved by the
    #: engine (_resolve_quantized): ``quantized_max_code`` is the
    #: clamped max |code| (grid half-width, possibly narrowed so the
    #: accumulated slab fits the wire dtype) and ``quantized_wire`` the
    #: psum slab dtype ("none" serial, else "int8"/"int16"/"int32").
    quantized_bits: int = 0
    quantized_seed: int = 0
    quantized_max_code: int = 0
    quantized_wire: str = "none"
    #: trace the sanitizer's invariants (core/debug.py) into the program;
    #: the engine sets it from debug mode at config build.  Static, and
    #: off means nothing of them is traced: checkify numbers each check
    #: from a process-wide counter, and that number, baked into the HLO
    #: as a constant, gave every re-trace of one program (a mesh step
    #: built anew) another persistent-cache key, so the cache
    #: missed and the fit compiled for real (PERF.md Findings, PR 25)
    debug_checks: bool = False
    #: a numeric feature of the table holds NaN (the engine reads the bin
    #: mapper's ``has_missing``; no user knob): the split scan scores both
    #: default directions of the missing bin, as LightGBM scans a feature
    #: of ``missing_type`` NaN, and each split records where it sends it
    #: (``TreeArrays.node_dleft``).  Static, and off traces nothing of the
    #: second direction: a table with no missing values compiles the
    #: one-direction program, in which the missing bin always goes right
    has_missing: bool = False

    @property
    def cat_words(self) -> int:
        """u32 words per per-node bin bitset."""
        return max(1, (self.num_bins + 31) // 32)


#: a tree's row counts are int32 and exact up to this many rows a shard
#: (they were float32 sums, exact to 2^24, before PR 27); a driver that
#: holds exported counts to the raw rows reads it before a large fit
EXACT_COUNT_ROWS = 2 ** 31 - 1


class TreeArrays(NamedTuple):
    """One grown tree.  Children encoding matches LightGBM: a child value
    ``c >= 0`` is an internal node index, ``c < 0`` is leaf ``~c``."""
    node_feat: jnp.ndarray    # (L-1,) i32
    node_bin: jnp.ndarray     # (L-1,) i32 threshold bin (<= goes left)
    node_left: jnp.ndarray    # (L-1,) i32
    node_right: jnp.ndarray   # (L-1,) i32
    node_gain: jnp.ndarray    # (L-1,) f32
    node_value: jnp.ndarray   # (L-1,) f32 internal output (shrinkage applied)
    node_weight: jnp.ndarray  # (L-1,) f32 sum of hessians
    node_count: jnp.ndarray   # (L-1,) i32 row count, exact
    node_is_cat: jnp.ndarray  # (L-1,) i32 1 = categorical split
    node_cat_bits: jnp.ndarray  # (L-1, W) u32 bin-bitset: bit set -> left
    leaf_value: jnp.ndarray   # (L,) f32 (shrinkage applied)
    leaf_weight: jnp.ndarray  # (L,) f32
    leaf_count: jnp.ndarray   # (L,) i32
    num_leaves: jnp.ndarray   # () i32 actual leaves grown
    #: grown with ``GrowerConfig.has_missing`` only, None otherwise.
    #: ``node_dleft``: the bin that goes LEFT besides ``<= node_bin``: the
    #: missing bin where the split sends NaN left (LightGBM's
    #: default_left), -1 where it sends it right.  ``node_missing``: the
    #: node's rows in the split feature's missing bin, which the default
    #: direction routed (0 on a categorical split)
    node_dleft: Optional[jnp.ndarray] = None    # (L-1,) i32
    node_missing: Optional[jnp.ndarray] = None  # (L-1,) i32


class _GrowState(NamedTuple):
    row_leaf: jnp.ndarray     # (n,) i32 (masked path; (1,) dummy otherwise)
    #: partition-mode row tracking (LightGBM DataPartition analog): a row
    #: permutation with each leaf's rows contiguous, plus per-leaf segment
    #: offsets/lengths.  (1,)/(L,) dummies on the masked path.
    row_order: jnp.ndarray    # (n + n_pow,) i32; entries >= n are sentinels
    leaf_start: jnp.ndarray   # (L,) i32
    leaf_cnt: jnp.ndarray     # (L,) i32
    leaf_hist: jnp.ndarray    # (L, f, B, 3)
    leaf_g: jnp.ndarray       # (L,)
    leaf_h: jnp.ndarray       # (L,)
    leaf_c: jnp.ndarray       # (L,) i32
    leaf_depth: jnp.ndarray   # (L,) i32
    leaf_parent: jnp.ndarray  # (L,) i32 (-1 for root)
    leaf_is_right: jnp.ndarray  # (L,) bool
    best_gain: jnp.ndarray    # (L,) f32 (-inf when leaf can't split)
    best_feat: jnp.ndarray    # (L,) i32
    best_bin: jnp.ndarray     # (L,) i32
    best_is_cat: jnp.ndarray  # (L,) i32
    best_cat_bits: jnp.ndarray  # (L, W) u32
    tree: TreeArrays
    best_dleft: Optional[jnp.ndarray] = None  # (L,) i32, has_missing only


def _threshold_l1(g, l1):
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)


def _leaf_gain(g, h, cfg: GrowerConfig):
    t = _threshold_l1(g, cfg.lambda_l1)
    return jnp.square(t) / (h + cfg.lambda_l2)


def _leaf_output(g, h, cfg: GrowerConfig):
    t = _threshold_l1(g, cfg.lambda_l1)
    return -t / (h + cfg.lambda_l2)


def _leaf_gain_l2(g, h, l1, l2):
    t = jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)
    return jnp.square(t) / (h + l2)


def _pack_bin_mask(mask: jnp.ndarray, cfg: GrowerConfig) -> jnp.ndarray:
    """(B,) bool bin subset -> (W,) u32 bitset (bit set = bin goes left)."""
    B = mask.shape[0]
    pos = jnp.arange(B)
    vals = jnp.where(mask, jnp.uint32(1) << (pos % 32).astype(jnp.uint32),
                     jnp.uint32(0))
    return jax.ops.segment_sum(vals, pos // 32,
                               num_segments=cfg.cat_words)


def bin_in_bitset(bits: jnp.ndarray, col: jnp.ndarray) -> jnp.ndarray:
    """Membership of bin indices ``col`` in a (W,) u32 bitset → bool."""
    word = bits[col >> 5]
    return ((word >> (col & 31).astype(jnp.uint32)) & 1).astype(bool)


def _cat_split_gains(hist, parent_g, parent_h, parent_c, cat_allowed,
                     feat_nbins, cfg: GrowerConfig):
    """Per-feature categorical split gains: the (f, B) gain matrix plus the
    sorted-bin order and onehot flags needed to reconstruct the winning
    left-subset bitset.  Shared by the exact finder and the voting
    learner's local-vote scoring (which needs per-FEATURE maxima, not the
    global argmax)."""
    B = hist.shape[1]
    g_b, h_b, c_b = hist[..., 0], hist[..., 1], hist[..., 2]
    # The trailing missing bin (NaN + overflow categories) may never join a
    # left subset: it must route RIGHT both in binned training and in raw
    # prediction, where rare/unseen values fail the bitset test.  (LightGBM
    # likewise sends unseen categories right.)
    not_missing = (jnp.arange(B) != B - 1)[None, :]
    nonzero = (c_b > 0) & not_missing
    l2c = cfg.lambda_l2 + cfg.cat_l2
    parent_gain = _leaf_gain_l2(parent_g, parent_h, cfg.lambda_l1, l2c)
    md, mh = cfg.min_data_in_leaf, cfg.min_sum_hessian_in_leaf

    # sorted-prefix scan: order bins by g/(h + cat_smooth), ascending;
    # a prefix of the sorted order is the candidate left subset
    ratio = jnp.where(nonzero, g_b / (h_b + cfg.cat_smooth), jnp.inf)
    order = jnp.argsort(ratio, axis=1)                       # (f, B)
    hist_s = jnp.take_along_axis(hist, order[:, :, None], axis=1)
    cums = jnp.cumsum(hist_s, axis=1)
    gls, hls, cls = cums[..., 0], cums[..., 1], cums[..., 2]
    grs, hrs, crs = parent_g - gls, parent_h - hls, parent_c - cls
    nz_cnt = jnp.sum(nonzero, axis=1).astype(jnp.float32)    # (f,)
    used_left = (jnp.arange(B) + 1).astype(jnp.float32)[None, :]
    used_right = nz_cnt[:, None] - used_left
    valid_s = ((cls >= md) & (crs >= md) & (hls >= mh) & (hrs >= mh)
               & (used_right >= 1)
               & (jnp.minimum(used_left, used_right)
                  <= cfg.max_cat_threshold))
    gains_s = (_leaf_gain_l2(gls, hls, cfg.lambda_l1, l2c)
               + _leaf_gain_l2(grs, hrs, cfg.lambda_l1, l2c) - parent_gain)
    gains_s = jnp.where(valid_s, gains_s, -jnp.inf)

    # one-vs-rest scan for small-cardinality features (missing bin is
    # excluded via `nonzero`)
    gr1, hr1, cr1 = parent_g - g_b, parent_h - h_b, parent_c - c_b
    valid_1 = (nonzero & (c_b >= md) & (cr1 >= md) & (h_b >= mh)
               & (hr1 >= mh) & (nz_cnt[:, None] >= 2))
    gains_1 = (_leaf_gain_l2(g_b, h_b, cfg.lambda_l1, l2c)
               + _leaf_gain_l2(gr1, hr1, cfg.lambda_l1, l2c) - parent_gain)
    gains_1 = jnp.where(valid_1, gains_1, -jnp.inf)

    use_onehot = (feat_nbins <= cfg.max_cat_to_onehot)       # (f,)
    gains_cat = jnp.where(use_onehot[:, None], gains_1, gains_s)
    gains_cat = jnp.where(cat_allowed[:, None], gains_cat, -jnp.inf)
    return gains_cat, order, use_onehot


@jax.named_scope("cat_scan")
def _find_best_cat_split(hist, parent_g, parent_h, parent_c, cat_allowed,
                         feat_nbins, cfg: GrowerConfig):
    """Best categorical split: per-feature gradient-ratio-sorted subset scan
    (LightGBM's Fisher-grouping sorted-histogram search) plus a one-vs-rest
    scan for low-cardinality features (max_cat_to_onehot)."""
    B = hist.shape[1]
    gains_cat, order, use_onehot = _cat_split_gains(
        hist, parent_g, parent_h, parent_c, cat_allowed, feat_nbins, cfg)
    flat = gains_cat.reshape(-1)
    idx = jnp.argmax(flat)
    gain = flat[idx]
    feat = (idx // B).astype(jnp.int32)
    k = (idx % B).astype(jnp.int32)

    onehot_win = use_onehot[feat]
    mask_onehot = jnp.arange(B) == k
    prefix = jnp.arange(B) <= k                  # positions in sorted order
    mask_sorted = jnp.zeros(B, bool).at[order[feat]].set(prefix)
    mask_bins = jnp.where(onehot_win, mask_onehot, mask_sorted)
    return gain, feat, k, _pack_bin_mask(mask_bins, cfg)


def _numeric_gains(hist, parent_g, parent_h, parent_c, cfg: GrowerConfig):
    """Numeric split gains of every (feature, direction, bin) of a
    ``(f, B, 3)`` histogram, and where each split is admissible:
    ``(gains, valid)``, ``(f, 2, B)`` each with ``cfg.has_missing``, else
    the one direction's ``(f, B)``.  The caller masks the gains.

    Direction 0 sends the missing bin (the trailing bin ``B - 1``: NaN)
    right: left = bins <= b, LightGBM's FindBestThreshold.  With
    ``cfg.has_missing`` direction 1 sends it left: left = bins <= b plus
    the missing bin, from the same cumulative sums, admissible only where
    the node holds missing rows of the feature and the right side keeps a
    row (LightGBM's two scans of a feature of ``missing_type`` NaN).
    Validity by min_data_in_leaf / min_sum_hessian; the missing bin is
    never a threshold."""
    B = hist.shape[1]
    md, mh = cfg.min_data_in_leaf, cfg.min_sum_hessian_in_leaf

    def scan(cum):
        gl, hl, cl = cum[..., 0], cum[..., 1], cum[..., 2]
        gr, hr, cr = parent_g - gl, parent_h - hl, parent_c - cl
        valid = ((cl >= md) & (cr >= md) & (hl >= mh) & (hr >= mh)
                 & (jnp.arange(B) < B - 1)[None, :])
        parent_gain = _leaf_gain(parent_g, parent_h, cfg)
        gains = (_leaf_gain(gl, hl, cfg) + _leaf_gain(gr, hr, cfg)
                 - parent_gain)
        return gains, valid, cr

    cum = jnp.cumsum(hist, axis=1)           # (f, B, 3)
    gains, valid, _ = scan(cum)
    if not cfg.has_missing:
        return gains, valid
    with jax.named_scope("missing_left"):
        nan = hist[:, B - 1:, :]             # (f, 1, 3)
        gains_l, valid_l, cr = scan(cum + nan)
        valid_l = valid_l & (nan[..., 2] > 0) & (cr >= 1)
    return (jnp.stack([gains, gains_l], axis=1),
            jnp.stack([valid, valid_l], axis=1))


def _best_numeric(gains, cand=None):
    """``(gain, feature, bin, dleft)`` of the first best entry of
    :func:`_numeric_gains`' masked array in (feature, direction, bin)
    order: a tie keeps the lower feature (LightGBM's SplitInfo order),
    then missing right (the one-direction scan's answer, so a direction
    the node's rows cannot tell apart stays right), then the lower bin.
    ``cand``: the feature ids of the array's rows (voting's candidates).
    ``dleft`` is None for the one direction's ``(f, B)``."""
    B = gains.shape[-1]
    flat = gains.reshape(-1)
    idx = jnp.argmax(flat)
    gain = flat[idx]
    if gains.ndim == 2:
        row = (idx // B).astype(jnp.int32)
        dleft = None
    else:
        row = (idx // (2 * B)).astype(jnp.int32)
        dleft = ((idx // B) % 2).astype(jnp.int32)
    feat = row if cand is None else cand[row]
    return gain, feat, (idx % B).astype(jnp.int32), dleft


def find_best_split(hist: jnp.ndarray, parent_g, parent_h, parent_c,
                    feat_info: jnp.ndarray, depth_ok,
                    cfg: GrowerConfig) -> Tuple[jnp.ndarray, ...]:
    """Best split over a (f, B, 3) histogram.

    ``feat_info``: (f, 3) float32 — [:, 0] feature mask, [:, 1] categorical
    flag, [:, 2] per-feature value-bin count.  Returns ``(gain, feature,
    bin, is_cat, cat_bits, dleft)`` where ``cat_bits`` is the (W,) u32
    left-subset bin bitset (zeros for numeric splits) and ``dleft`` 1
    where the missing bin goes left (None without ``cfg.has_missing``).

    Numeric path: :func:`_numeric_gains` and :func:`_best_numeric`.
    Categorical path: :func:`_find_best_cat_split`.
    """
    feature_mask = feat_info[:, 0]
    is_cat_f = feat_info[:, 1] > 0
    gains, valid = _numeric_gains(hist, parent_g, parent_h, parent_c, cfg)
    num_allowed = (feature_mask > 0) & (~is_cat_f if cfg.use_categorical
                                        else True)
    num_allowed = (num_allowed[:, None] if gains.ndim == 2
                   else num_allowed[:, None, None])
    gains = jnp.where(valid & num_allowed & depth_ok, gains, -jnp.inf)
    best_gain, feat, b, dleft = _best_numeric(gains)
    is_cat = jnp.asarray(0, jnp.int32)
    cat_bits = jnp.zeros(cfg.cat_words, jnp.uint32)
    if cfg.use_categorical:
        cat_allowed = is_cat_f & (feature_mask > 0) & depth_ok
        cat_gain, cat_feat, _, cat_bits_w = _find_best_cat_split(
            hist, parent_g, parent_h, parent_c, cat_allowed,
            feat_info[:, 2], cfg)
        cat_wins = cat_gain > best_gain
        best_gain = jnp.maximum(best_gain, cat_gain)
        feat = jnp.where(cat_wins, cat_feat, feat)
        b = jnp.where(cat_wins, 0, b)
        is_cat = cat_wins.astype(jnp.int32)
        cat_bits = jnp.where(cat_wins, cat_bits_w, cat_bits)
        if dleft is not None:
            dleft = jnp.where(cat_wins, 0, dleft)
    if cfg.feature_axis_name is not None:
        # feature-parallel learner: each shard scanned its feature slice;
        # allgather candidate splits and pick the global winner
        # (LightGBM tree_learner=feature analog, SURVEY.md §2.3).
        ax = cfg.feature_axis_name
        gains_all = jax.lax.all_gather(best_gain, ax)       # (S,)
        feats_all = jax.lax.all_gather(feat, ax)
        bins_all = jax.lax.all_gather(b, ax)
        cats_all = jax.lax.all_gather(is_cat, ax)
        bits_all = jax.lax.all_gather(cat_bits, ax)         # (S, W)
        shard = jnp.argmax(gains_all)
        n_local = jnp.asarray(hist.shape[0], jnp.int32)
        best_gain = gains_all[shard]
        feat = feats_all[shard] + shard.astype(jnp.int32) * n_local
        b = bins_all[shard]
        is_cat = cats_all[shard]
        cat_bits = bits_all[shard]
        if dleft is not None:
            dleft = jax.lax.all_gather(dleft, ax)[shard]
    gain_ok = best_gain > jnp.maximum(cfg.min_gain_to_split, EPS_GAIN)
    return (jnp.where(gain_ok, best_gain, -jnp.inf), feat, b, is_cat,
            cat_bits, dleft)


def _is_voting(cfg: GrowerConfig) -> bool:
    return cfg.axis_name is not None and cfg.voting_k > 0


def _is_quantized(cfg: GrowerConfig) -> bool:
    return cfg.quantized_bits > 0 and cfg.quantized_max_code > 0


def _quantize_gh(gh, cfg: GrowerConfig):
    """Discretize the round's ``(n, 3)`` float gh triple to integer grid
    codes with seeded stochastic rounding (ISSUE 17 tentpole).

    The grid scale comes from the round's GLOBAL max-abs (``pmax`` under
    a data mesh, so every shard quantizes on the identical grid and the
    reduced integer histograms are exact sums of exact codes).  SR —
    ``floor(x) + (u < frac(x))`` — keeps the code expectation unbiased;
    the PRNG key folds the g-scale's bit pattern into
    ``cfg.quantized_seed``, so the same seed + data is bit-reproducible
    while every boost round draws fresh noise.  The count channel is the
    0/1 bag mask and casts exactly.  Returns ``(codes (n, 3) int32,
    scale (3,) f32)`` with ``codes * scale`` the dequantization."""
    mc = cfg.quantized_max_code
    gmax = jnp.max(jnp.abs(gh[:, 0]))
    hmax = jnp.max(jnp.abs(gh[:, 1]))
    if cfg.axis_name is not None and cfg.data_axis_size > 1:
        gmax = jax.lax.pmax(gmax, cfg.axis_name)
        hmax = jax.lax.pmax(hmax, cfg.axis_name)
    gs = jnp.maximum(gmax, jnp.float32(1e-30)) / mc
    hs = jnp.maximum(hmax, jnp.float32(1e-30)) / mc
    key = jax.random.fold_in(
        jax.random.PRNGKey(cfg.quantized_seed),
        jax.lax.bitcast_convert_type(gmax.astype(jnp.float32), jnp.int32))
    u = jax.random.uniform(key, (gh.shape[0], 2))
    x = gh[:, :2] / jnp.stack([gs, hs])[None, :]
    lo = jnp.floor(x)
    code = lo + (u < (x - lo)).astype(jnp.float32)
    code = jnp.clip(code, -mc, mc).astype(jnp.int32)
    codes = jnp.concatenate(
        [code, gh[:, 2:3].astype(jnp.int32)], axis=1)
    scale = jnp.stack([gs, hs, jnp.float32(1.0)])
    return codes, scale


def _wire_cast_psum(h, cfg: GrowerConfig):
    """psum an integer histogram slab at the resolved wire width: the
    engine's headroom analysis (_resolve_quantized) guarantees the
    GLOBAL accumulated magnitude fits the narrow dtype, so the slab
    rides the all-reduce at 1 or 2 bytes/element instead of 4 and the
    sum is still exact."""
    if (cfg.quantized_wire in ("int8", "int16")
            and jnp.issubdtype(h.dtype, jnp.integer)):
        wt = jnp.int8 if cfg.quantized_wire == "int8" else jnp.int16
        return jax.lax.psum(h.astype(wt), cfg.axis_name).astype(h.dtype)
    return jax.lax.psum(h, cfg.axis_name)


@jax.named_scope("reduce")
def _reduce_hist(h, cfg: GrowerConfig):
    """Cross-shard reduction of a local histogram: ``lax.psum`` or the
    on-chip Pallas ring (ops/pallas_collectives.py) per
    ``cfg.collective``.  The ring entry falls back to psum only when
    its VMEM gate refuses the state.  Integer (quantized)
    slabs ride the psum at the resolved wire width; the ring's f32 lanes
    round-trip integer sums exactly below 2^24, which the engine's
    resolve gate guarantees before leaving ring enabled."""
    if cfg.collective == "ring" and cfg.data_axis_size > 1:
        from ..ops.pallas_collectives import ring_allreduce_or_psum
        return ring_allreduce_or_psum(h, cfg.axis_name,
                                      cfg.data_axis_size)
    return _wire_cast_psum(h, cfg)


def _hist(bins, gh, cfg: GrowerConfig, efb: Optional[EFBArrays] = None):
    h = compute_histogram(bins, gh, cfg.num_bins, method=cfg.hist_method,
                          max_code=cfg.quantized_max_code)
    if efb is not None:
        # bins holds G bundle columns; expand to per-feature histograms
        # BEFORE any psum — expansion is linear (static gather + a
        # leaf-total subtraction), so shard-local expansion followed by
        # the reduction equals expanding the reduced histogram
        h = _efb_expand(h, efb)
    if cfg.axis_name is not None and not _is_voting(cfg):
        # voting mode keeps histograms shard-local; only the voted
        # candidate slices are ever reduced (find_best_split_voting)
        h = _reduce_hist(h, cfg)
    return h


def _take_cand(hist, cand):
    """Gather candidate columns: ``(f,B,3)[cand (k2,)]`` → ``(k2,B,3)``,
    or batched ``(m,f,B,3)`` with ``cand (m,k2)`` → ``(m,k2,B,3)``."""
    if cand.ndim == 1:
        return jnp.take(hist, cand, axis=0)
    return jnp.take_along_axis(hist, cand[:, :, None, None], axis=1)


@jax.named_scope("reduce")
def _reduce_select(hist_local, cand, cfg: GrowerConfig):
    """Reduce ONLY the voted candidate columns across the data mesh: the
    voted-column ring (ops/pallas_collectives.ring_allreduce_select)
    when the collective resolved to ring, gather + ``lax.psum``
    otherwise (the slab must pass the ring's VMEM gate)."""
    if cfg.collective == "ring" and cfg.data_axis_size > 1:
        from ..ops.pallas_collectives import ring_allreduce_select_or_psum
        return ring_allreduce_select_or_psum(hist_local, cand,
                                             cfg.axis_name,
                                             cfg.data_axis_size)
    return _wire_cast_psum(_take_cand(hist_local, cand), cfg)


def _voting_masks(feat_info, depth_ok, cfg: GrowerConfig):
    """Per-feature numeric mask and (when categorical) cat-allowed mask
    shared by every phase of the voting protocol."""
    feature_mask = feat_info[:, 0]
    is_cat_f = feat_info[:, 1] > 0
    num_mask = ((feature_mask > 0) & (~is_cat_f if cfg.use_categorical
                                      else True))
    cat_allowed = (is_cat_f & (feature_mask > 0) & depth_ok
                   if cfg.use_categorical else None)
    return num_mask, cat_allowed


def _voting_feature_gains(hist, pg, ph, pc, mask_cols, depth_ok,
                          cfg: GrowerConfig):
    """Per-(feature, direction, bin) numeric split gains over ``hist``
    against the given parent totals (:func:`_numeric_gains`), masked by
    the ``(f, 1)`` ``mask_cols`` — the scan both the vote and decide
    phases run."""
    gains, valid = _numeric_gains(hist, pg, ph, pc, cfg)
    if gains.ndim == 3:
        mask_cols = mask_cols[..., None]
    return jnp.where(valid & mask_cols & depth_ok, gains, -jnp.inf)


def _voting_votes(hist_local, feat_info, depth_ok, num_mask, cat_allowed,
                  cfg: GrowerConfig):
    """Shard-local vote: the ids of the top-k features by local best
    gain against the shard's LOCAL leaf totals."""
    f = hist_local.shape[0]
    s_loc = jnp.sum(hist_local[0], axis=0)
    gains_loc = _voting_feature_gains(hist_local, s_loc[0], s_loc[1],
                                      s_loc[2], num_mask[:, None],
                                      depth_ok, cfg)
    score_f = jnp.max(gains_loc, axis=tuple(range(1, gains_loc.ndim)))
    if cfg.use_categorical:
        with jax.named_scope("cat_scan"):
            gains_cat_loc, _, _ = _cat_split_gains(
                hist_local, s_loc[0], s_loc[1], s_loc[2], cat_allowed,
                feat_info[:, 2], cfg)
        score_f = jnp.maximum(score_f, jnp.max(gains_cat_loc, axis=1))
    _, votes = jax.lax.top_k(score_f, min(cfg.voting_k, f))
    return votes


def _voting_candidates(votes_flat, f: int, cfg: GrowerConfig):
    """Global candidate set from the allgathered votes: top-2k features
    by vote count (feature id tie-break keeps every shard's selection
    identical and deterministic)."""
    counts = jnp.zeros(f, jnp.int32).at[votes_flat].add(1)
    k = min(cfg.voting_k, f)
    k2 = min(2 * k, f)
    key = counts * f + (f - 1 - jnp.arange(f, dtype=jnp.int32))
    _, cand = jax.lax.top_k(key, k2)                             # (k2,)
    return cand


def _voting_decide(hist_cand, cand, pg, ph, pc, feat_info, depth_ok,
                   num_mask, cat_allowed, cfg: GrowerConfig):
    """Exact decision over the globally reduced candidate histograms."""
    gains_cand = _voting_feature_gains(hist_cand, pg, ph, pc,
                                       num_mask[cand][:, None],
                                       depth_ok, cfg)
    best_gain, feat, b, dleft = _best_numeric(gains_cand, cand)
    is_cat = jnp.asarray(0, jnp.int32)
    cat_bits = jnp.zeros(cfg.cat_words, jnp.uint32)
    if cfg.use_categorical:
        cat_gain, cat_feat_loc, _, cat_bits_w = _find_best_cat_split(
            hist_cand, pg, ph, pc, cat_allowed[cand],
            feat_info[cand, 2], cfg)
        cat_wins = cat_gain > best_gain
        best_gain = jnp.maximum(best_gain, cat_gain)
        feat = jnp.where(cat_wins, cand[cat_feat_loc], feat)
        b = jnp.where(cat_wins, 0, b)
        is_cat = cat_wins.astype(jnp.int32)
        cat_bits = jnp.where(cat_wins, cat_bits_w, cat_bits)
        if dleft is not None:
            dleft = jnp.where(cat_wins, 0, dleft)
    gain_ok = best_gain > jnp.maximum(cfg.min_gain_to_split, EPS_GAIN)
    return (jnp.where(gain_ok, best_gain, -jnp.inf), feat, b, is_cat,
            cat_bits, dleft)


def find_best_split_voting(hist_local, parent_g, parent_h, parent_c,
                           feat_info, depth_ok, cfg: GrowerConfig,
                           deq=None):
    """PV-Tree split finding (Meng et al. 2016; LightGBM
    tree_learner=voting): each data shard scores every feature on its
    LOCAL histogram against its LOCAL totals, votes its top-k features,
    votes are allgathered, and only the globally top-2k voted features'
    histograms are reduced — via the voted-column ring or psum per
    ``cfg.collective`` (:func:`_reduce_select`) — for the exact global
    decision.

    Categorical features vote with their local Fisher-grouping gain
    (:func:`_cat_split_gains`) and, when voted into the candidate set, get
    the exact sorted-subset search over the reduced candidate
    histograms — same two-phase shape as the numeric path.
    Returns the same tuple as :func:`find_best_split`.

    ``deq`` (quantized-gradient mode): the votes and the decision run on
    DEQUANTIZED f32 histograms, but the candidate slab crosses the wire
    RAW — the low-bit integer codes ride :func:`_reduce_select` and only
    the reduced slab is dequantized.
    """
    f = hist_local.shape[0]
    num_mask, cat_allowed = _voting_masks(feat_info, depth_ok, cfg)
    # 1. local votes  2. global candidates  3. exact decision over the
    # reduced (k2, B, 3) candidate slab
    votes = _voting_votes(deq(hist_local) if deq else hist_local,
                          feat_info, depth_ok, num_mask, cat_allowed, cfg)
    votes_all = jax.lax.all_gather(votes, cfg.axis_name)        # (S, k)
    cand = _voting_candidates(votes_all.reshape(-1), f, cfg)
    hist_cand = _reduce_select(hist_local, cand, cfg)           # (k2, B, 3)
    if deq is not None:
        hist_cand = deq(hist_cand)
    return _voting_decide(hist_cand, cand, parent_g, parent_h, parent_c,
                          feat_info, depth_ok, num_mask, cat_allowed, cfg)


@jax.named_scope("split_scan")
def find_best_split_voting_pair(hist_l, hist_r, tot_l, tot_r, feat_info,
                                depth_ok, cfg: GrowerConfig, deq=None):
    """Batched-frontier voting for the two children of one grow step:
    both children's votes ride ONE allgather and both candidate slabs
    ONE ``(2, k2, B, 3)`` reduction, so the collective count per grow
    step is 1 candidate reduce instead of 2 — O(depth)-shaped instead of
    O(leaves)-shaped when ``num_leaves ≤ max_depth + 1``.  The stacked
    reduce is element-wise, so results are BIT-IDENTICAL to two
    independent :func:`find_best_split_voting` calls.  ``deq`` as in
    :func:`find_best_split_voting` — the stacked slab crosses the wire
    as raw integer codes and is dequantized after the reduction."""
    f = hist_l.shape[0]
    num_mask, cat_allowed = _voting_masks(feat_info, depth_ok, cfg)
    hl_v = deq(hist_l) if deq else hist_l
    hr_v = deq(hist_r) if deq else hist_r
    votes = jnp.stack([
        _voting_votes(hl_v, feat_info, depth_ok, num_mask, cat_allowed,
                      cfg),
        _voting_votes(hr_v, feat_info, depth_ok, num_mask, cat_allowed,
                      cfg)])
    votes_all = jax.lax.all_gather(votes, cfg.axis_name)     # (S, 2, k)
    cand_l = _voting_candidates(votes_all[:, 0].reshape(-1), f, cfg)
    cand_r = _voting_candidates(votes_all[:, 1].reshape(-1), f, cfg)
    slab = _reduce_select(jnp.stack([hist_l, hist_r]),
                          jnp.stack([cand_l, cand_r]), cfg)  # (2,k2,B,3)
    if deq is not None:
        slab = deq(slab)
    res_l = _voting_decide(slab[0], cand_l, *tot_l, feat_info, depth_ok,
                           num_mask, cat_allowed, cfg)
    res_r = _voting_decide(slab[1], cand_r, *tot_r, feat_info, depth_ok,
                           num_mask, cat_allowed, cfg)
    return res_l, res_r


def _bucket_sizes(n: int, cfg: GrowerConfig):
    """Power-of-two compaction bucket ladder covering [min_bucket, 2^⌈lg n⌉]."""
    n_pow = 1 << (n - 1).bit_length() if n > 1 else 1
    s = min(cfg.min_bucket, n_pow)
    sizes = [s]
    while s < n_pow:
        s *= 2
        sizes.append(s)
    return sizes


#: rows of the top rung of the ladder ``_segment_hist`` gathers rows on,
#: and of one chunk of its walk over a segment that no rung holds.
#: Chosen once from a sweep on the v5e over 2^14 .. 2^18 at the three
#: largest cells' shapes (PERF.md Findings, PR 34); not a parameter: the
#: code adapts by a node's row count.
SEGMENT_CHUNK_ROWS = 1 << 16


def _build_sizes(n: int, cfg: GrowerConfig):
    """The rungs of ``_bucket_sizes`` up to ``SEGMENT_CHUNK_ROWS``: the
    sizes at which ``_segment_hist`` gathers a segment's rows whole.  A
    longer segment is walked in chunks of the top rung's size, never
    gathered at the next power of two.  (``_partition_switch`` keeps the
    whole ladder: a node's split column gathered 2^16 elements at a time
    costs 18.5 ns an element on the v5e where the whole rung's gather
    costs 11.2, more than the rung's padding; PERF.md Findings, PR 34.)"""
    sizes = _bucket_sizes(n, cfg)
    return [s for s in sizes
            if s <= max(SEGMENT_CHUNK_ROWS, sizes[0])]


def _walked_rows(cnt, sizes):
    """Rows the ladder ``sizes`` covers for segments of ``cnt`` rows
    (numpy, on the host): the smallest rung that holds the segment, or
    whole chunks of the top rung."""
    cnt = np.asarray(cnt, np.int64)
    rungs = np.asarray(sizes, np.int64)
    rung = rungs[np.minimum(np.searchsorted(rungs, cnt), len(rungs) - 1)]
    top = rungs[-1]
    return np.where(cnt > top, -(-cnt // top) * top, rung)


def segment_walk_stats(parents, smaller, n_rows: int,
                       cfg: GrowerConfig) -> dict:
    """What a fit's splits asked of the ladders over ``n_rows`` rows, from
    node counts on the host: each split partitions its parent's rows on
    a rung of ``_bucket_sizes`` and histograms its smaller child's on a
    rung of ``_build_sizes`` or chunk by chunk.  ``seg_rows``: the rows
    those segments hold; ``seg_rows_walked``: the rows the rungs and
    chunks covered for them; ``seg_chunked_nodes``: children that took
    the chunk loop."""
    parents = np.asarray(parents, np.int64)
    smaller = np.asarray(smaller, np.int64)
    build = _build_sizes(n_rows, cfg)
    walked = (_walked_rows(parents, _bucket_sizes(n_rows, cfg)).sum()
              + _walked_rows(smaller, build).sum())
    return {"seg_rows": int(parents.sum() + smaller.sum()),
            "seg_rows_walked": int(walked),
            "seg_chunked_nodes": int((smaller > build[-1]).sum())}


def missing_split_stats(trees) -> dict:
    """What a fit's splits did with missing values, from host
    ``TreeArrays`` grown with ``GrowerConfig.has_missing`` (others have
    none and count nothing): ``missing_splits``, the splits whose node
    held rows in the split feature's missing bin; ``missing_left_splits``,
    those of them that sent the rows left; ``missing_rows_routed``, the
    rows those splits routed by their default direction."""
    splits = left = rows = 0
    for t in trees:
        if t.node_missing is None:
            continue
        m = max(int(t.num_leaves) - 1, 0)
        held = np.asarray(t.node_missing)[:m]
        went_left = np.asarray(t.node_dleft)[:m] >= 0
        splits += int((held > 0).sum())
        left += int(((held > 0) & went_left).sum())
        rows += int(held.astype(np.int64).sum())
    return {"missing_splits": splits, "missing_left_splits": left,
            "missing_rows_routed": rows}


@jax.named_scope("partition")
def _partition_switch(row_order, col, off, cnt, thr, use_cat, cat_bits,
                      n, sizes, cfg: GrowerConfig, left_bin=None):
    """Partition the split leaf's contiguous ``row_order`` segment into
    left|right in place — LightGBM's ``DataPartition::Split`` re-shaped for
    static-shape jit.  The segment (dynamic offset, dynamic length ``cnt``)
    is sliced at the smallest power-of-two bucket that fits, partitioned
    with an in-bucket stable cumsum+scatter, and written back, so the cost
    is O(leaf size), not O(n).  ``lax.switch`` picks the bucket; only the
    chosen branch executes, and no collectives live inside branches (shards
    may pick different buckets under a data mesh).

    A numeric split sends bins ``<= thr`` left, and ``left_bin``
    (``TreeArrays.node_dleft``: the missing bin where NaN goes left, -1
    where it goes right; None without ``cfg.has_missing``) with them.

    Returns ``(row_order', cnt_left, cnt_right)`` (counts of ALL leaf
    rows per side, bagged-out rows included — the partition tracks
    membership, histograms track contribution) and, given ``left_bin``,
    ``cnt_missing``: a numeric split's rows in the missing bin.  On the
    CPU backend the whole partition is one in-place native pass
    (ops/histogram.py native_partition).
    """
    if cfg.hist_method in ("auto", "native"):
        from ..ops.histogram import native_partition
        res = native_partition(row_order, col, off, cnt, thr, use_cat,
                               cat_bits, cfg.num_bins, left_bin=left_bin)
        if res is not None:
            return res

    def make(size):
        def fn(_):
            seg = jax.lax.dynamic_slice(row_order, (off,), (size,))
            iota = jnp.arange(size, dtype=jnp.int32)
            valid = iota < cnt
            rows = jnp.minimum(seg, n - 1)
            cseg = jnp.take(col, rows).astype(jnp.int32)
            num_left = cseg <= thr
            cnt_m = ()
            if left_bin is not None:
                num_left = num_left | (cseg == left_bin)
                is_m = valid & (cseg == cfg.num_bins - 1)
                if cfg.use_categorical:
                    is_m = is_m & ~use_cat
                cnt_m = (jnp.sum(is_m, dtype=jnp.int32),)
            if cfg.use_categorical:
                gl = jnp.where(use_cat, bin_in_bitset(cat_bits, cseg),
                               num_left)
            else:
                gl = num_left
            go_l = valid & gl
            go_r = valid & ~gl
            cnt_r = jnp.sum(go_r, dtype=jnp.int32)
            cnt_l = cnt - cnt_r
            pos_l = jnp.cumsum(go_l.astype(jnp.int32)) - 1
            pos_r = cnt_l + jnp.cumsum(go_r.astype(jnp.int32)) - 1
            # each leaf row gets a unique slot in [0, cnt); the bucket tail
            # (other leaves / sentinels) keeps its original values
            tgt = jnp.where(go_l, pos_l, jnp.where(go_r, pos_r, size))
            new_seg = seg.at[tgt].set(seg, mode="drop")
            out = jax.lax.dynamic_update_slice(row_order, new_seg, (off,))
            return (out, cnt_l, cnt_r) + cnt_m
        return fn

    branch = jnp.searchsorted(jnp.asarray(sizes, jnp.int32), cnt,
                              side="left")
    return jax.lax.switch(branch, [make(s) for s in sizes], 0)


def _segment_hist(bins, gh, row_order, off, cnt, n, sizes,
                  cfg: GrowerConfig):
    """Histogram the contiguous ``row_order[off:off+cnt]`` segment via the
    smallest power-of-two bucket gather, or, where no rung holds it, by
    summing the histograms of its chunks of the top rung's size.  Local
    (no psum) — the caller reduces over the data axis, keeping
    collectives out of switch branches.  On the CPU backend the gather
    fuses into the native FFI kernel (no (size, f) materialization)."""
    from ..ops.histogram import native_segment_hist
    if cfg.hist_method in ("auto", "native"):
        fused = native_segment_hist(bins, gh, row_order, off, cnt,
                                    cfg.num_bins,
                                    max_code=cfg.quantized_max_code)
        if fused is not None:
            return fused

    def build(size, base):
        """The histogram of ``size`` slots from ``base`` into the
        segment, those beyond its end masked."""
        seg = jax.lax.dynamic_slice(row_order, (off + base,), (size,))
        valid = jnp.arange(size, dtype=jnp.int32) < cnt - base
        rows = jnp.minimum(seg, n - 1)
        with jax.named_scope("row_gather"):
            # rows are clamped above: "clip" says so, and spares
            # the bucket the fill mode's select, a whole pass
            # over it that also kept XLA from handing the
            # histogram kernel its rows-minor layout straight
            # from the gather (PERF.md Findings, PR 28)
            b_sub = jnp.take(bins, rows, axis=0, mode="clip")
            gh_sub = jnp.take(gh, rows, axis=0, mode="clip") * \
                valid.astype(gh.dtype)[:, None]
        with jax.named_scope("segment_hist"):
            return compute_histogram(b_sub, gh_sub, cfg.num_bins,
                                     method=cfg.hist_method,
                                     max_code=cfg.quantized_max_code)

    def chunked(_):
        C = sizes[-1]
        out = jax.eval_shape(lambda: build(C, 0))
        return jax.lax.fori_loop(
            0, (cnt + C - 1) // C,
            lambda k, acc: acc + build(C, k * C),
            jnp.zeros(out.shape, out.dtype))

    branches = [lambda _, s=s: build(s, 0) for s in sizes]
    if n > sizes[-1]:
        branches.append(chunked)
    # ``len(sizes)``, the chunk loop, where no rung holds ``cnt`` rows
    branch = jnp.searchsorted(jnp.asarray(sizes, jnp.int32), cnt,
                              side="left")
    return jax.lax.switch(branch, branches, 0)


def _leaf_of_position(leaf_start, leaf_cnt, n):
    """(n,) leaf id per row_order position, from the leaves' contiguous
    segments: a mark at each non-empty leaf's start, a running count of
    the marks (the position's rank among the segments), and the leaf of
    that rank by start.  (An ``associative_scan`` forward fill gave the
    same ids and cost the v5e's compiler more than 40 minutes and 36 GB
    at 3e7 rows: PERF.md Findings, PR 27.)"""
    idx = jnp.where(leaf_cnt > 0, leaf_start, n)   # empty leaves dropped
    by_start = jnp.argsort(idx).astype(jnp.int32)
    marks = jnp.zeros(n, jnp.int32).at[idx].set(1, mode="drop")
    return by_start[jnp.cumsum(marks) - 1]


def _totals_from_hist(hist):
    """Leaf totals via any one feature's bins (they partition the rows).
    The count is summed as int32: a float32 sum stops being exact at 2^24
    rows, and a table of 3e7 rows exported counts that were off by one or
    two at its eleven largest nodes (PERF.md Findings, PR 27).  Each bin's
    own count is a float32 below 2^24, hence exact."""
    s = jnp.sum(hist[0], axis=0)             # (3,)
    return s[0], s[1], jnp.sum(hist[0, :, 2].astype(jnp.int32))


def _global_totals(g, h, c, cfg: GrowerConfig):
    """Leaf totals are global quantities; under voting the histograms stay
    local, so the (3,) totals are psum-reduced explicitly."""
    if _is_voting(cfg):
        # one float32 triple on the wire, as before: under voting the
        # count is exact below 2^24 rows a node
        tot = jax.lax.psum(jnp.stack([g, h, c.astype(jnp.float32)]),
                           cfg.axis_name)
        return tot[0], tot[1], jnp.round(tot[2]).astype(jnp.int32)
    return g, h, c


@jax.named_scope("split_scan")
def _find_split(hist, pg, ph, pc, fi, depth_ok, cfg: GrowerConfig,
                deq=None):
    """Best split over ``hist``.  ``deq`` (quantized mode): ``hist`` is
    raw int32 codes; voting forwards it so the candidate slab crosses
    the wire low-bit, every other path dequantizes up front — the gain
    math is unchanged f32 by construction."""
    pc = pc.astype(jnp.float32)      # the leaf's exact int32 row count
    if _is_voting(cfg):
        return find_best_split_voting(hist, pg, ph, pc, fi, depth_ok, cfg,
                                      deq=deq)
    if deq is not None:
        hist = deq(hist)
    if (cfg.hist_method in ("auto", "native") and not cfg.use_categorical
            and not cfg.has_missing
            and cfg.axis_name is None and cfg.feature_axis_name is None
            and (cfg.min_sum_hessian_in_leaf > 0 or cfg.lambda_l2 > 0)):
        # serial CPU path: the whole FindBestThreshold scan as one FFI
        # call; the C++ pass picks the winner, the gain is recomputed on
        # XLA's float trajectory (see native_find_split).  Mesh/voting/
        # categorical and missing values keep XLA; so does the degenerate
        # min_sum_hessian=lambda_l2=0 config, whose empty-side gains go
        # NaN and argmax semantics would differ.
        from ..ops.histogram import native_find_split
        res = native_find_split(
            hist, pg, ph, pc, fi[:, 0], depth_ok,
            cfg.min_data_in_leaf, cfg.min_sum_hessian_in_leaf,
            cfg.lambda_l1, cfg.lambda_l2,
            max(cfg.min_gain_to_split, EPS_GAIN), cfg.num_bins)
        if res is not None:
            gain, feat, b = res
            return (gain, feat, b, jnp.asarray(0, jnp.int32),
                    jnp.zeros(cfg.cat_words, jnp.uint32), None)
    return find_best_split(hist, pg, ph, pc, fi, depth_ok, cfg)


def hist_build_schedule(cfg: GrowerConfig, n_rows: int) -> dict:
    """Which build of the histogram a tree's call sites compile: the root
    (``n_rows`` rows a shard) and, where rows are compacted, each rung of
    the bucket ladder and, where the rows pass its top rung, the chunk
    loop.  ``build`` names the implementation
    (``ops.histogram.histogram_build``: one for all sites, since no build
    is chosen by the row count), ``fused`` counts the sites whose one-hot
    product stays on the chip, ``sites`` all of them."""
    from ..ops.histogram import histogram_build
    sites = 1
    if cfg.compact_rows:
        sizes = _build_sizes(n_rows, cfg)
        sites += len(sizes) + (n_rows > sizes[-1])
    build = histogram_build(cfg.hist_method, cfg.num_bins,
                            _is_quantized(cfg))
    return {"build": build,
            "fused": sites if build == "dot16/mosaic" else 0,
            "sites": sites}


def collective_schedule(cfg: GrowerConfig, f: int, *,
                        n_rows_local: int = 0,
                        feature_shards: int = 1) -> dict:
    """Static per-TREE accounting of the grower's cross-shard
    collectives — computed host-side from shapes so the engine can
    journal ``collective_count``/``collective_payload_bytes`` per boost
    chunk without touching the trace (ISSUE 16 tentpole d).

    ``count`` counts the payload-bearing launches: histogram reductions
    under a data axis (the voting path batches both children of a grow
    step into one, so count = num_leaves = root + L-1 steps), and
    split-column broadcasts under a feature axis.  ``payload_bytes``
    sums the logical bytes each shard hands to EVERY training
    collective, tiny aux ones included (vote allgathers, leaf totals,
    partition counts, the feature-parallel gain/feat/bin tuple).
    ``dense_payload_bytes`` is what the same tree pays on the dense
    data-parallel reduce path — L reduces of the full (f, B, 3) f32
    state — the denominator of the bench artifact's payload ratio.
    Serial fits return zero count/payload.

    Histogram-slab terms are priced at the RESOLVED wire itemsize
    (ISSUE 17 satellite — the old hardcoded ``* 4`` over-billed
    quantized slabs): ``cfg.quantized_wire`` int8/int16 slabs cost 1/2
    bytes per element on the psum wire, while the ring transport always
    moves f32 lanes (``_ring_flat`` casts), so ring fits price 4
    regardless.  ``dense_payload_bytes`` stays f32-priced — it is the
    un-quantized denominator.  Quantized fits journal the per-tree grid
    scale ``pmax`` pair separately (``quantized_scale_bytes``): two
    scalar latency-bound launches, not slab payload.
    """
    B, L, W = cfg.num_bins, cfg.num_leaves, cfg.cat_words
    dense = L * f * B * 3 * 4
    if cfg.collective == "ring":
        itemsize = 4               # ring lanes are f32 (see _ring_flat)
    else:
        itemsize = {"int8": 1, "int16": 2}.get(cfg.quantized_wire, 4)
    count, payload, scale_bytes = 0, 0, 0
    if cfg.axis_name is not None and cfg.data_axis_size > 1:
        if _is_voting(cfg):
            k = min(cfg.voting_k, f)
            k2 = min(2 * k, f)
            slab = k2 * B * 3 * itemsize
            count += L
            payload += slab + (L - 1) * 2 * slab   # root + batched pairs
            payload += 4 * (k + (L - 1) * 2 * k)   # vote allgathers (i32)
            payload += L * 3 * 4                   # leaf-totals psums
        else:
            count += L                             # root + L-1 children
            payload += L * f * B * 3 * itemsize
        if _is_quantized(cfg):
            scale_bytes = 2 * 4                    # grid-scale pmax pair
        if cfg.compact_rows:
            # partition-count pairs ride the wire width too (they go
            # through _wire_cast_psum even on ring fits): counts are
            # bounded by n, which any resolved narrow wire admits
            cnt_item = {"int8": 1, "int16": 2}.get(cfg.quantized_wire, 4)
            # with missing values the node's missing rows ride along
            payload += (L - 1) * (3 if cfg.has_missing else 2) * cnt_item
    if cfg.feature_axis_name is not None and feature_shards > 1:
        count += L - 1                             # split-column psums
        payload += (L - 1) * n_rows_local * 4
        payload += (2 * L - 1) * (16 + W * 4)      # split-tuple allgathers
    return {"count": count, "payload_bytes": payload,
            "dense_payload_bytes": dense,
            "quantized_scale_bytes": scale_bytes}


@functools.partial(jax.jit, static_argnames=("cfg",))
def grow_tree(bins: jnp.ndarray, gh: jnp.ndarray,
              feat_info: jnp.ndarray,
              cfg: GrowerConfig,
              efb: Optional[EFBArrays] = None,
              binsT: Optional[jnp.ndarray] = None
              ) -> Tuple[TreeArrays, jnp.ndarray]:
    """Grow one tree.  ``gh``: (n, 3) masked (grad, hess, count);
    ``feat_info``: (f, 3) [mask, is_cat, n_value_bins] (see
    :func:`make_feat_info`); ``efb``: optional bundle maps — then
    ``bins`` holds bundle columns (gbdt/efb.py); ``binsT``: optional
    precomputed ``bins.T`` (fit-invariant — pass it when calling in a
    loop)."""
    return _grow_tree_impl(bins, gh, feat_info, cfg, efb, binsT=binsT)


def make_feat_info(f: int, feature_mask=None, is_cat=None, nbins=None):
    """Assemble the (f, 3) feature-info array the grower consumes."""
    import numpy as np
    out = np.zeros((f, 3), np.float32)
    out[:, 0] = 1.0 if feature_mask is None else feature_mask
    if is_cat is not None:
        out[:, 1] = is_cat
    if nbins is not None:
        out[:, 2] = nbins
    return out


#: grower bodies traced in this process: ``_grow_tree_impl`` runs once
#: for each copy of the grower a program being traced holds (the engine
#: reads it around a chunk's dispatch: ``train.fit``'s ``grow_bodies``)
GROW_TRACES = [0]


def _grow_tree_impl(bins, gh, feat_info, cfg: GrowerConfig, efb=None,
                    binsT=None):
    GROW_TRACES[0] += 1
    # debug-mode invariants: every training path funnels through here,
    # so corrupt bins / non-finite gradients are caught regardless of
    # entry point.  Traced only when the config asks (GrowerConfig.
    # debug_checks): unchecked they do nothing, but each would still
    # leave its process-wide serial number in the HLO
    if cfg.debug_checks:
        from ..core import debug as _debug
        _debug.check_bins_in_range(bins, cfg.num_bins)
        _debug.check_finite("gradients/hessians", gh)
    # quantized-gradient mode (ISSUE 17): discretize this tree's gh to
    # integer grid codes ONCE; every histogram below accumulates exact
    # int32, the sibling subtraction is bit-exact in integers, and the
    # split evaluation dequantizes through ``deq`` so the gain math is
    # unchanged f32.
    qscale = None
    deq = None
    if _is_quantized(cfg):
        gh, qscale = _quantize_gh(gh, cfg)
        deq = lambda h: h.astype(jnp.float32) * qscale  # noqa: E731

    def tot_deq(g, h, c):
        if qscale is None:
            return g, h, c
        return (g.astype(jnp.float32) * qscale[0],
                h.astype(jnp.float32) * qscale[1], c)

    n = bins.shape[0]
    # under EFB bins holds G bundle columns; histograms, feat_info and
    # tree state stay per ORIGINAL feature
    f = efb.gather_idx.shape[0] if efb is not None else bins.shape[1]
    L = cfg.num_leaves
    W = cfg.cat_words
    sizes = _bucket_sizes(n, cfg)
    build_sizes = _build_sizes(n, cfg)
    neg_inf = jnp.float32(-jnp.inf)
    # Transposed copy for split-column reads: a column of row-major (n, f)
    # is a stride-f gather (slow on TPU); a row of (f, n) is one contiguous
    # dynamic-slice.  It is loop-invariant across the whole FIT, not just
    # this tree — XLA does NOT hoist it out of scanned boost loops (a
    # 48 ms/tree transpose at bench scale on CPU), so the scan builders
    # precompute it once and pass it in; the default covers direct calls.
    if binsT is None:
        binsT = bins.T
    with jax.named_scope("root_hist"):
        hist0 = _hist(bins, gh, cfg, efb)
    g0, h0, c0 = _global_totals(*tot_deq(*_totals_from_hist(hist0)), cfg)
    depth0_ok = (cfg.max_depth <= 0) | (0 < cfg.max_depth)
    bg0, bf0, bb0, bc0, bits0, bd0 = _find_split(
        hist0, g0, h0, c0, feat_info, jnp.asarray(depth0_ok), cfg,
        deq=deq)
    missing = cfg.has_missing

    tree = TreeArrays(
        node_feat=jnp.zeros(L - 1, jnp.int32),
        node_bin=jnp.zeros(L - 1, jnp.int32),
        node_left=jnp.zeros(L - 1, jnp.int32),
        node_right=jnp.zeros(L - 1, jnp.int32),
        node_gain=jnp.zeros(L - 1, jnp.float32),
        node_value=jnp.zeros(L - 1, jnp.float32),
        node_weight=jnp.zeros(L - 1, jnp.float32),
        node_count=jnp.zeros(L - 1, jnp.int32),
        node_is_cat=jnp.zeros(L - 1, jnp.int32),
        node_cat_bits=jnp.zeros((L - 1, W), jnp.uint32),
        leaf_value=jnp.zeros(L, jnp.float32).at[0].set(
            _leaf_output(g0, h0, cfg)),
        leaf_weight=jnp.zeros(L, jnp.float32).at[0].set(h0),
        leaf_count=jnp.zeros(L, jnp.int32).at[0].set(c0),
        num_leaves=jnp.asarray(1, jnp.int32),
        node_dleft=jnp.full(L - 1, -1, jnp.int32) if missing else None,
        node_missing=jnp.zeros(L - 1, jnp.int32) if missing else None,
    )
    if cfg.compact_rows:
        n_pow = sizes[-1]
        row_leaf0 = jnp.zeros(1, jnp.int32)
        row_order0 = jnp.concatenate([
            jnp.arange(n, dtype=jnp.int32),
            jnp.full(n_pow, n, jnp.int32)])
        leaf_start0 = jnp.zeros(L, jnp.int32)
        leaf_cnt0 = jnp.zeros(L, jnp.int32).at[0].set(n)
    else:
        row_leaf0 = jnp.zeros(n, jnp.int32)
        row_order0 = jnp.zeros(1, jnp.int32)
        leaf_start0 = jnp.zeros(L, jnp.int32)
        leaf_cnt0 = jnp.zeros(L, jnp.int32)
    state = _GrowState(
        row_leaf=row_leaf0,
        row_order=row_order0,
        leaf_start=leaf_start0,
        leaf_cnt=leaf_cnt0,
        leaf_hist=jnp.zeros((L, f, cfg.num_bins, 3), hist0.dtype
                            ).at[0].set(hist0),
        leaf_g=jnp.zeros(L, jnp.float32).at[0].set(g0),
        leaf_h=jnp.zeros(L, jnp.float32).at[0].set(h0),
        leaf_c=jnp.zeros(L, jnp.int32).at[0].set(c0),
        leaf_depth=jnp.zeros(L, jnp.int32),
        leaf_parent=jnp.full(L, -1, jnp.int32),
        leaf_is_right=jnp.zeros(L, bool),
        best_gain=jnp.full(L, neg_inf).at[0].set(bg0),
        best_feat=jnp.zeros(L, jnp.int32).at[0].set(bf0),
        best_bin=jnp.zeros(L, jnp.int32).at[0].set(bb0),
        best_is_cat=jnp.zeros(L, jnp.int32).at[0].set(bc0),
        best_cat_bits=jnp.zeros((L, W), jnp.uint32).at[0].set(bits0),
        tree=tree,
        best_dleft=(jnp.zeros(L, jnp.int32).at[0].set(bd0) if missing
                    else None),
    )

    def split_step(i, state: _GrowState) -> _GrowState:
        l = jnp.argmax(state.best_gain).astype(jnp.int32)
        gain = state.best_gain[l]
        do_split = gain > neg_inf

        # The step body runs UNCONDITIONALLY with its effects gated by
        # ``do_split`` (see the merge at the end) instead of under
        # ``lax.cond``, whose join copies the untouched carry buffers.
        # Inactive steps neutralize themselves: the partition/histogram
        # run with cnt forced to 0 (identity permutation, empty segment),
        # and every state write merges through ``ds``.  That alone does
        # not make the (L, f, B, 3) leaf_hist update in place: see
        # ``cache_update`` below for what the TPU's compiler needs.
        def do(state: _GrowState, ds) -> _GrowState:
            feat = state.best_feat[l]
            thr = state.best_bin[l]
            new_id = (i + 1).astype(jnp.int32)
            # the bin that goes left besides ``<= thr``: the missing bin
            # where the split sends NaN left (TreeArrays.node_dleft)
            left_bin = (jnp.where(state.best_dleft[l] > 0,
                                  cfg.num_bins - 1, -1).astype(jnp.int32)
                        if missing else None)
            if cfg.feature_axis_name is not None:
                # feat is a GLOBAL index but bins holds this shard's feature
                # slice: the owning shard contributes the split column, the
                # psum broadcasts it (LightGBM feature-parallel's bitmap
                # broadcast, as an ICI collective).
                f_local = bins.shape[1]
                shard = jax.lax.axis_index(cfg.feature_axis_name)
                owner = feat // f_local
                lidx = feat - owner * f_local
                col_local = jnp.where(
                    owner == shard,
                    jnp.take(binsT, jnp.minimum(lidx, f_local - 1), axis=0)
                    .astype(jnp.int32),
                    0)
                col = jax.lax.psum(col_local, cfg.feature_axis_name)
            elif efb is not None:
                with jax.named_scope("partition"), \
                        jax.named_scope("efb_column"):
                    col = efb_feature_column(binsT, feat, efb,
                                             cfg.num_bins)
            else:
                col = jnp.take(binsT, feat, axis=0)

            if cfg.compact_rows:
                # LightGBM DataPartition: split the leaf's contiguous
                # row_order segment in place (O(leaf size)), then histogram
                # only the SMALLER child's segment (globally smaller under
                # a data mesh, so every shard histograms the same side and
                # the psum-reduced partials compose); sibling by
                # subtraction.
                off = state.leaf_start[l]
                cnt = jnp.where(ds, state.leaf_cnt[l], 0)
                use_cat = state.best_is_cat[l] > 0
                row_order, cnt_l_p, cnt_r_p, *cnt_m = _partition_switch(
                    state.row_order, col, off, cnt, thr, use_cat,
                    state.best_cat_bits[l], n, sizes, cfg,
                    left_bin=left_bin)
                if cfg.axis_name is not None:
                    # counts are bounded by n, which the quantized wire
                    # policy keeps within the wire dtype — ride it too,
                    # with the node's missing rows where there are any
                    with jax.named_scope("reduce"):
                        tot = _wire_cast_psum(
                            jnp.stack([cnt_l_p, cnt_r_p] + cnt_m), cfg)
                    use_right = tot[1] <= tot[0]
                    cnt_m = list(tot[2:])
                else:
                    use_right = cnt_r_p <= cnt_l_p
                cnt_m = cnt_m[0] if missing else None
                child_off = jnp.where(use_right, off + cnt_l_p, off)
                child_cnt = jnp.where(use_right, cnt_r_p, cnt_l_p)
                hist_small = _segment_hist(
                    bins, gh, row_order, child_off, child_cnt, n,
                    build_sizes, cfg)
                if efb is not None:
                    # expansion is linear, so it commutes with the
                    # reduction below
                    with jax.named_scope("segment_hist"):
                        hist_small = _efb_expand(hist_small, efb)
                if cfg.axis_name is not None and not _is_voting(cfg):
                    # voting keeps per-leaf histograms local; only voted
                    # candidate slices are reduced inside _find_split
                    hist_small = _reduce_hist(hist_small, cfg)
                with jax.named_scope("cache_update"):
                    parent_hist = state.leaf_hist[l]
                    hist_r = jnp.where(use_right, hist_small,
                                       parent_hist - hist_small)
                    hist_l = parent_hist - hist_r
                row_leaf = state.row_leaf
                leaf_start = state.leaf_start.at[new_id].set(off + cnt_l_p)
                leaf_cnt = state.leaf_cnt.at[l].set(cnt_l_p) \
                                         .at[new_id].set(cnt_r_p)
            else:
                in_leaf = (state.row_leaf == l) & ds
                num_left = col <= thr
                if missing:
                    num_left = num_left | (col == left_bin)
                    is_m = in_leaf & (col == cfg.num_bins - 1)
                    if cfg.use_categorical:
                        is_m = is_m & (state.best_is_cat[l] == 0)
                    cnt_m = jnp.sum(is_m, dtype=jnp.int32)
                    if cfg.axis_name is not None:
                        cnt_m = _wire_cast_psum(cnt_m, cfg)
                if cfg.use_categorical:
                    go_left_val = jnp.where(
                        state.best_is_cat[l] > 0,
                        bin_in_bitset(state.best_cat_bits[l],
                                      col.astype(jnp.int32)),
                        num_left)
                    go_right = in_leaf & ~go_left_val
                elif missing:
                    go_right = in_leaf & ~num_left
                else:
                    go_right = in_leaf & (col > thr)
                row_leaf = jnp.where(go_right, new_id, state.row_leaf)
                hist_r = _hist(bins, gh * go_right[:, None], cfg, efb)
                hist_l = state.leaf_hist[l] - hist_r
                row_order = state.row_order
                leaf_start = state.leaf_start
                leaf_cnt = state.leaf_cnt
            g_r, h_r, c_r = _global_totals(
                *tot_deq(*_totals_from_hist(hist_r)), cfg)
            g_l = state.leaf_g[l] - g_r
            h_l = state.leaf_h[l] - h_r
            c_l = state.leaf_c[l] - c_r

            child_depth = state.leaf_depth[l] + 1
            depth_ok = jnp.asarray(
                (cfg.max_depth <= 0), bool) | (child_depth < cfg.max_depth)
            if _is_voting(cfg):
                # batched frontier (ISSUE 16): both children's votes
                # ride one allgather and both candidate slabs one
                # stacked reduction — 1 collective per grow step
                ((bg_l, bf_l, bb_l, bc_l, bits_l, bd_l),
                 (bg_r, bf_r, bb_r, bc_r, bits_r, bd_r)) = \
                    find_best_split_voting_pair(
                        hist_l, hist_r,
                        (g_l, h_l, c_l.astype(jnp.float32)),
                        (g_r, h_r, c_r.astype(jnp.float32)),
                        feat_info, depth_ok, cfg, deq=deq)
            else:
                bg_l, bf_l, bb_l, bc_l, bits_l, bd_l = _find_split(
                    hist_l, g_l, h_l, c_l, feat_info, depth_ok, cfg,
                    deq=deq)
                bg_r, bf_r, bb_r, bc_r, bits_r, bd_r = _find_split(
                    hist_r, g_r, h_r, c_r, feat_info, depth_ok, cfg,
                    deq=deq)

            t = state.tree
            # link the new internal node into its parent
            p = state.leaf_parent[l]
            has_parent = p >= 0
            p_safe = jnp.maximum(p, 0)
            was_right = state.leaf_is_right[l]
            node_left = t.node_left.at[p_safe].set(
                jnp.where(has_parent & ~was_right, i, t.node_left[p_safe]))
            node_right = t.node_right.at[p_safe].set(
                jnp.where(has_parent & was_right, i, t.node_right[p_safe]))
            tree = t._replace(
                node_feat=t.node_feat.at[i].set(feat),
                node_bin=t.node_bin.at[i].set(thr),
                node_is_cat=t.node_is_cat.at[i].set(state.best_is_cat[l]),
                node_cat_bits=t.node_cat_bits.at[i].set(
                    state.best_cat_bits[l]),
                node_left=node_left.at[i].set(-(l + 1)),
                node_right=node_right.at[i].set(-(new_id + 1)),
                node_gain=t.node_gain.at[i].set(gain),
                node_value=t.node_value.at[i].set(
                    _leaf_output(state.leaf_g[l], state.leaf_h[l], cfg)),
                node_weight=t.node_weight.at[i].set(state.leaf_h[l]),
                node_count=t.node_count.at[i].set(state.leaf_c[l]),
                leaf_value=t.leaf_value
                    .at[l].set(_leaf_output(g_l, h_l, cfg))
                    .at[new_id].set(_leaf_output(g_r, h_r, cfg)),
                leaf_weight=t.leaf_weight.at[l].set(h_l).at[new_id].set(h_r),
                leaf_count=t.leaf_count.at[l].set(c_l).at[new_id].set(c_r),
                num_leaves=t.num_leaves + 1,
            )
            if missing:
                tree = tree._replace(
                    node_dleft=t.node_dleft.at[i].set(left_bin),
                    node_missing=t.node_missing.at[i].set(cnt_m))
            with jax.named_scope("cache_update"):
                # XLA updates a loop's carry in place only if no read of
                # the OLD buffer can come after the first write to it.
                # Row ``l`` is gated on its own old value, an operand of
                # that first write.  Row ``new_id`` is gated on zeros, not
                # on the old ``leaf_hist[new_id]``: nothing orders that
                # read before the first write, so the v5e's compiler kept
                # the old buffer alive and copied the whole (L, f, B, 3)
                # cache before the first update and back after the second
                # (2 x 1.57 GB a split at Epsilon's shape; PERF.md
                # Findings, PR 26).  Invariant that makes zeros the same
                # value: slot ``new_id = i + 1`` is first written at step
                # ``i`` (earlier steps wrote slots ``0..i``), so it still
                # holds the zeros it was created with.  The CPU's compiler
                # copies the cache either way; tests/test_mosaic_aot.py
                # reads the v5e's compiled program instead.
                leaf_hist = state.leaf_hist \
                    .at[l].set(jnp.where(ds, hist_l, state.leaf_hist[l])) \
                    .at[new_id].set(jnp.where(ds, hist_r,
                                              jnp.zeros_like(hist_r)))
            return _GrowState(
                row_leaf=row_leaf,
                row_order=row_order,
                leaf_start=leaf_start,
                leaf_cnt=leaf_cnt,
                leaf_hist=leaf_hist,
                leaf_g=state.leaf_g.at[l].set(g_l).at[new_id].set(g_r),
                leaf_h=state.leaf_h.at[l].set(h_l).at[new_id].set(h_r),
                leaf_c=state.leaf_c.at[l].set(c_l).at[new_id].set(c_r),
                leaf_depth=state.leaf_depth.at[l].set(child_depth)
                                           .at[new_id].set(child_depth),
                leaf_parent=state.leaf_parent.at[l].set(i)
                                             .at[new_id].set(i),
                leaf_is_right=state.leaf_is_right.at[l].set(False)
                                                 .at[new_id].set(True),
                best_gain=state.best_gain.at[l].set(bg_l)
                                         .at[new_id].set(bg_r),
                best_feat=state.best_feat.at[l].set(bf_l)
                                         .at[new_id].set(bf_r),
                best_bin=state.best_bin.at[l].set(bb_l)
                                       .at[new_id].set(bb_r),
                best_is_cat=state.best_is_cat.at[l].set(bc_l)
                                             .at[new_id].set(bc_r),
                best_cat_bits=state.best_cat_bits.at[l].set(bits_l)
                                                 .at[new_id].set(bits_r),
                tree=tree,
                best_dleft=(state.best_dleft.at[l].set(bd_l)
                            .at[new_id].set(bd_r) if missing else None),
            )

        new_state = do(state, do_split)
        big = ("row_leaf", "row_order", "leaf_hist")
        merged = {}
        for name in _GrowState._fields:
            nv, ov = getattr(new_state, name), getattr(state, name)
            if name in big:   # self-neutralizing or slice-gated above
                merged[name] = nv
            else:             # L-sized (or smaller) — cheap full where
                merged[name] = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(do_split, a, b), nv, ov)
        return _GrowState(**merged)

    state = jax.lax.fori_loop(0, L - 1, split_step, state)
    if cfg.compact_rows:
        # reconstruct the per-row leaf assignment once per tree: position →
        # leaf from the segment table, then scatter through the permutation
        leaf_of_p = _leaf_of_position(state.leaf_start, state.leaf_cnt, n)
        row_leaf = jnp.zeros(n, jnp.int32).at[state.row_order[:n]].set(
            leaf_of_p)
        return state.tree, row_leaf
    return state.tree, state.row_leaf


def apply_shrinkage(tree: TreeArrays, learning_rate: float) -> TreeArrays:
    return tree._replace(
        leaf_value=tree.leaf_value * learning_rate,
        node_value=tree.node_value * learning_rate)


def _tree_walk(tree: TreeArrays, n: int, max_steps: int, get_val):
    """Shared depth-bounded tree walk: ``get_val(safe_node)`` supplies
    each row's current split-column bin (local gather, or a psum-
    assembled feature-sharded gather); everything else — threshold,
    default-direction and categorical-bitset compares, next-node
    selection, the early-exit while_loop, leaf extraction — lives HERE
    once, so the local and feature-sharded walks cannot drift apart
    (their parity is test-pinned).

    The ``while_loop`` stops as soon as every row reached a leaf, so the
    walk costs O(actual tree depth) iterations — typically ~log2(L) —
    with ``max_steps`` (= num_leaves, the worst-case chain) only as the
    safety fuel.  (VERDICT r2 weak #7: the fixed O(L) walk hurt at
    numLeaves=255-class configs.)"""

    def step(node):
        is_leaf = node < 0
        safe = jnp.maximum(node, 0)
        val = get_val(safe)
        thr = tree.node_bin[safe]
        go_left = val <= thr
        if tree.node_dleft is not None:
            # the missing bin goes left where the split sends NaN left
            go_left = go_left | (val == tree.node_dleft[safe])
        # categorical nodes: left iff the row's bin is in the subset bitset
        words = jnp.take_along_axis(tree.node_cat_bits[safe],
                                    (val >> 5)[:, None], axis=1)[:, 0]
        left_cat = ((words >> (val & 31).astype(jnp.uint32)) & 1
                    ).astype(bool)
        go_left = jnp.where(tree.node_is_cat[safe] > 0, left_cat, go_left)
        nxt = jnp.where(go_left, tree.node_left[safe],
                        tree.node_right[safe])
        return jnp.where(is_leaf, node, nxt)

    def cond(state):
        node, fuel = state
        return (fuel > 0) & jnp.any(node >= 0)

    def body(state):
        node, fuel = state
        return step(node), fuel - 1

    start = jnp.where(tree.num_leaves > 1,
                      jnp.zeros(n, jnp.int32), jnp.full(n, -1, jnp.int32))
    node, _ = jax.lax.while_loop(
        cond, body, (start, jnp.asarray(max_steps, jnp.int32)))
    leaf = -(node + 1)
    return tree.leaf_value[leaf]


@functools.partial(jax.jit, static_argnames=("max_steps",))
def predict_tree_binned(tree: TreeArrays, bins: jnp.ndarray,
                        max_steps: int) -> jnp.ndarray:
    """Score binned rows through one tree (validation sets, dart/goss
    score updates); all features local.  See :func:`_tree_walk`."""

    def get_val(safe):
        feat = tree.node_feat[safe]
        return jnp.take_along_axis(bins, feat[:, None], axis=1)[:, 0]

    return _tree_walk(tree, bins.shape[0], max_steps, get_val)


@functools.partial(jax.jit, static_argnames=("max_steps", "num_bins"))
def predict_tree_binned_efb(tree: TreeArrays, bins_b: jnp.ndarray,
                            max_steps: int, efb: EFBArrays,
                            num_bins: int) -> jnp.ndarray:
    """:func:`predict_tree_binned` over an EFB-BUNDLED matrix: node ids
    are ORIGINAL features, so each walk level decodes the row's bundle
    column back to the feature's bin (the per-row form of
    :func:`efb_feature_column`) before the compare — the piece that let
    goss/dart score on the bundled training matrix."""

    def get_val(safe):
        feat = tree.node_feat[safe]
        bcol = jnp.take_along_axis(
            bins_b, efb.bundle_of[feat][:, None],
            axis=1)[:, 0].astype(jnp.int32)
        off = efb.off_of[feat]
        nb = efb.nb_of[feat]
        raw = bcol - off
        inr = (raw >= 0) & (raw <= nb)
        return jnp.where(inr, jnp.where(raw == nb, num_bins - 1, raw),
                         efb.default_of[feat])

    return _tree_walk(tree, bins_b.shape[0], max_steps, get_val)


def predict_tree_binned_any(tree: TreeArrays, bins: jnp.ndarray,
                            max_steps: int, efb=None,
                            num_bins: int = 256) -> jnp.ndarray:
    """One call site for 'walk this matrix': plain per-feature bins when
    ``efb`` is None, EFB bundle decode otherwise.  Callers must pass the
    efb that matches THE MATRIX BEING WALKED — training matrices are
    bundled under EFB, validation matrices never are."""
    if efb is None:
        return predict_tree_binned(tree, bins, max_steps)
    return predict_tree_binned_efb(tree, bins, max_steps, efb, num_bins)


def predict_tree_binned_fshard(tree: TreeArrays, bins_local: jnp.ndarray,
                               max_steps: int,
                               axis_name: str) -> jnp.ndarray:
    """:func:`predict_tree_binned` with FEATURES sharded over
    ``axis_name`` (every shard holds all rows of its feature slice).

    Per walk step, the shard owning each row's current split column
    contributes that row's bin and one ``psum`` assembles the compare
    vector — the scoring-side analog of the grower's feature-parallel
    split-column broadcast (grower.py split_step).  The loop trip count
    is identical on every shard of the feature axis (they walk the same
    rows through the same replicated tree), so the in-loop collective is
    SPMD-safe; cost is one (n,) psum per tree level.
    """
    n, f_local = bins_local.shape
    shard = jax.lax.axis_index(axis_name)

    def get_val(safe):
        feat = tree.node_feat[safe]                 # GLOBAL feature ids
        owner = feat // f_local
        lidx = jnp.minimum(feat - owner * f_local, f_local - 1)
        val_local = jnp.where(
            owner == shard,
            jnp.take_along_axis(bins_local, lidx[:, None],
                                axis=1)[:, 0].astype(jnp.int32),
            0)
        return jax.lax.psum(val_local, axis_name)

    return _tree_walk(tree, n, max_steps, get_val)
