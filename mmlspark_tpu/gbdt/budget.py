"""Fit-time HBM budget for GBDT training (BASELINE config 5 scale guard).

The reference streams rows through LightGBM's C++ histogram pools and can
page; an XLA program cannot — every array in the jitted boost step must
fit HBM simultaneously, so a Criteo-class configuration (numLeaves=255,
maxBin=255, tens of millions of rows) must be budgeted BEFORE the first
compile, not discovered as a device OOM after minutes of tracing.
(Reference expected paths: LightGBM histogram pool sizing in
src/treelearner/serial_tree_learner.cpp, UNVERIFIED; SURVEY.md §7.)

The model below counts the resident arrays of one device's shard for the
dominant training path (the DataPartition grower inside the chunked
scan), plus the transients of the bucket-ladder compaction (a child's
rows on the build ladder's top rung, ``grower.SEGMENT_CHUNK_ROWS`` rows
at most, and the partition's slices of a node at the next power of two)
and what the MXU histogram build holds beside them: XLA's formulation
its one-hot temporaries, the Mosaic build (PR 28) only the bucket's
transposed copy.  Against the peaks measured on a v5e (PR 34) it reads
0.98x at 400 000 x 2000, 0.98x at 1 183 747 x 968, 0.98x at 7 325 625 x
220 with a ranker's layout, 1.42x at 30 000 000 x 39, where XLA keeps no
second copy of a narrow table, and 1.01x at 13 184 290 rows bundled into
90 columns with the cache 4228
features wide (PERF.md; tests/test_budget.py holds the cells).  It deliberately over-counts
slightly (gradients and their gh-stack both appear) — a guard that errs
a few percent high beats an OOM at iteration 40.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import numpy as np

#: rows the dot16 histogram build takes at a time (ops/histogram.py)
HIST_CHUNK_ROWS = 8192
#: a chunk of a ranker's pair pass: ``ranking``'s default
#: ``query_chunk_pairs`` cells a tensor, and the (queries, G, G) tensors
#: XLA keeps at once (pair mask, the two deltas, score difference,
#: sigmoid, lambda, hessian, and the reductions' operands)
RANK_CHUNK_PAIRS = 4_000_000
RANK_PAIR_TENSORS = 12


def estimate_fit_bytes(n_local: int, num_features: int, num_bins: int,
                       num_leaves: int, num_class: int = 1,
                       chunk: int = 64, bin_itemsize: int = 1,
                       bagging: bool = False, n_val_local: int = 0,
                       min_bucket: int = 2048,
                       hist_on_chip: bool = False,
                       rank_layout_bytes: int = 0,
                       num_bundles: Optional[int] = None) -> Dict[str, int]:
    """Per-device resident-bytes breakdown for one training fit.

    ``n_local``: this device's row count (global rows / data-mesh size).
    ``hist_on_chip``: the fit's histogram call sites all compile the
    build that keeps its one-hots in VMEM (``grower.
    hist_build_schedule``).  ``rank_layout_bytes``: a ranker's query
    layout as uploaded (``ranking.QueryLayout``; 0 for any other fit).
    ``num_bundles``: the columns of a table bundled at binning time
    (``gbdt/efb.py``): the table, its copies and the bucket are that
    wide, the per-leaf cache stays ``num_features`` wide, and each
    histogram is expanded to features beside its bundle-space build.
    Returns a dict of named costs plus ``"total"``.
    """
    n, B, L, K, C = (n_local, num_bins, num_leaves, num_class, chunk)
    # ``f``: the table's columns; the cache's are ``num_features``
    f = num_features if num_bundles is None else num_bundles
    costs: Dict[str, int] = {}
    costs["bins"] = n * f * bin_itemsize
    # the (f, n) transposed copy the scans keep for split-column reads
    costs["bins_transposed"] = n * f * bin_itemsize
    # scores + labels + weights + real/bag mask + row_order
    costs["row_vectors"] = n * 4 * (K + 4)
    # grad/hess (n, K) each + the (n, 3) gh stack the grower consumes
    costs["gradients"] = n * 4 * (2 * K + 3)
    # per-leaf histogram state: (L, f, B, 3) f32
    costs["leaf_hist"] = L * num_features * B * 3 * 4
    if num_bundles is not None:
        # the plan's gather map and mask, and the expansion's three
        # (features x bins, 3) arrays (gathered, masked, transposed),
        # whose 3 channels the TPU lays out padded to a lane tile of 128
        # (0.55 GB each at 4228 features: PERF.md Findings, PR 33)
        costs["bundle_expand"] = num_features * B * (5 + 3 * 128 * 4)
    # largest bucket of rows ``_segment_hist`` gathers, its ladder's top
    # rung (a longer segment is walked in chunks of it): one (rung, f)
    # bins gather plus its (rung, 3) gh gather
    from .grower import GrowerConfig, _bucket_sizes, _build_sizes
    ladder = GrowerConfig(min_bucket=min_bucket)
    bucket = _build_sizes(n, ladder)[-1]
    costs["bucket_transient"] = bucket * (f * bin_itemsize + 12)
    # ``_partition_switch`` slices a node at the next power of two: its
    # row ids, their split-column values, two running counts and the
    # slots they scatter to, 4 bytes each
    costs["partition_transient"] = _bucket_sizes(n, ladder)[-1] * 4 * 5
    # the MXU histogram build's temporaries for one chunk of rows: per
    # feature 16 x 3 products in f32 and again as bf16 operands, and the
    # 16-wide one-hot in bf16 (the TPU runtime reserves them with the
    # program: 5.2 of the 9.18 GB measured at 400 000 x 2000, PERF.md)
    costs["hist_build"] = min(n, HIST_CHUNK_ROWS) * f * 16 * (3 * 4 + 3 * 2
                                                             + 2)
    if hist_on_chip:
        # the kernel holds nothing of that: what stays is the bucket a
        # second time, transposed on its way in (it reads rows-minor)
        costs["hist_build"] = bucket * f * bin_itemsize
    # stacked per-chunk trees (C*K trees x ~14 L-sized f32/i32 fields)
    costs["chunk_trees"] = C * K * L * 14 * 4
    if bagging:
        costs["bag_masks"] = C * n * 4
    if n_val_local:
        costs["validation"] = n_val_local * (f * bin_itemsize
                                             + 4 * K * (C + 1))
    if rank_layout_bytes:
        # the layout, as much again for what the pair pass keeps a slot
        # (gathered scores, each slot's gradient and hessian, their
        # concatenations), and a chunk's (queries, G, G) pair tensors
        costs["rank_layout"] = (2 * rank_layout_bytes
                                + RANK_PAIR_TENSORS * 4 * RANK_CHUNK_PAIRS)
    costs["total"] = sum(costs.values())
    return costs


def device_capacity_bytes() -> Optional[int]:
    """This device's usable memory, or None when unknown.

    ``MMLSPARK_TPU_HBM_BYTES`` overrides (also how tests pin a tiny
    budget); TPU backends report ``bytes_limit`` via ``memory_stats``;
    CPU reports nothing and the guard stays advisory.
    """
    env = os.environ.get("MMLSPARK_TPU_HBM_BYTES")
    if env:
        return int(float(env))
    try:
        import jax
        stats = jax.devices()[0].memory_stats()
        if stats and "bytes_limit" in stats:
            return int(stats["bytes_limit"])
    except Exception:  # noqa: BLE001 - backend without memory_stats
        pass
    return None


def check_fit_budget(n_local: int, num_features: int, num_bins: int,
                     num_leaves: int, num_class: int = 1, chunk: int = 64,
                     bin_itemsize: int = 1, bagging: bool = False,
                     n_val_local: int = 0, data_shards: int = 1,
                     verbosity: int = 1,
                     hist_on_chip: bool = False,
                     rank_layout_bytes: int = 0,
                     num_bundles: Optional[int] = None) -> Dict[str, int]:
    """Estimate, log, and fail FAST when the fit cannot fit.

    Raises ``MemoryError`` with the breakdown and concrete remediations
    (more data shards, smaller maxBin/numLeaves) instead of letting XLA
    OOM after a long compile.  Returns the breakdown.
    """
    costs = estimate_fit_bytes(
        n_local, num_features, num_bins, num_leaves, num_class, chunk,
        bin_itemsize, bagging, n_val_local, hist_on_chip=hist_on_chip,
        rank_layout_bytes=rank_layout_bytes, num_bundles=num_bundles)
    cap = device_capacity_bytes()
    if verbosity > 0:
        import logging
        logging.getLogger("mmlspark_tpu.gbdt").info(
            "fit memory budget: %.2f GB/device estimated%s",
            costs["total"] / 1e9,
            "" if cap is None else f" of {cap / 1e9:.2f} GB available")
    if cap is not None and costs["total"] > cap:
        detail = ", ".join(f"{k}={v / 1e9:.2f}GB"
                           for k, v in costs.items() if k != "total")
        need_shards = int(np.ceil(costs["total"] / cap * data_shards))
        raise MemoryError(
            f"GBDT fit needs ~{costs['total'] / 1e9:.2f} GB per device "
            f"({detail}) but only {cap / 1e9:.2f} GB is available. "
            f"Remedies: shard rows over a larger data mesh (>= "
            f"{need_shards} shards at this scale), lower maxBin "
            f"(uint8 bins at <=255), lower numLeaves, or reduce "
            f"baggingFreq chunking. Set MMLSPARK_TPU_HBM_BYTES to "
            f"override the detected capacity.")
    return costs
