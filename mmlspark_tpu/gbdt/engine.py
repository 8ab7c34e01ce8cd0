"""Boosting loop — the executor-side training orchestration.

TPU-native analog of the reference's executor training loop
(``TrainUtils.trainLightGBM`` → ``LGBM_BoosterUpdateOneIter`` iterations;
SURVEY.md §3.1).  One jitted ``boost_step`` fuses grad/hess computation, tree
growth, and score update on device; the Python loop over iterations handles
bagging/feature-fraction re-sampling, validation metrics, and early stopping —
mirroring LightGBM's iteration loop on the host side of the JNI boundary,
minus the JNI.
"""

from __future__ import annotations

import functools
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import debug as _debug
from ..core import telemetry as _tm
from ..core.profiler import get_profiler, install_jax_hooks
from ..core.profiling import StageStats
from ..ops.histogram import bin_counts
from .binning import BinMapper, fit_bin_mapper
from .booster import Booster, HostTree, host_tree_from_arrays
from .grower import (EFBArrays, GrowerConfig, TreeArrays, apply_shrinkage,
                     collective_schedule, hist_build_schedule,
                     predict_tree_binned, predict_tree_binned_any,
                     predict_tree_binned_efb, segment_walk_stats,
                     _grow_tree_impl)
from .objectives import Objective, MulticlassObjective


def _resolve_collective_cfg(params: "TrainParams", mesh, *,
                            ranking: bool = False):
    """Resolve ``params.collective`` → ``("psum"|"ring", mesh, reason)``.

    "auto" stays on psum: the one timing on the four-chip host has the
    ring slower at every payload (PERF.md Findings, PR 29).  "ring"
    requires a multi-shard
    layout whose data axis is the only populated one, on a path whose
    scans support the data-only mesh (gbdt/goss/rf/multiclass, data- or
    voting-parallel — not ranking, dart or a feature-sharded mesh).
    Those structural refusals keep psum with a ``log.info``, and the
    REASON is returned so ``_record_fit_resolution`` lands it in
    ``last_fit_info`` and the /metrics info gauge ("none" when the
    request was honored or nothing beyond psum was asked for).  A ring
    kernel the TPU compiler refuses is NOT such a refusal: it raises out
    of the fit.  On success the mesh is rebuilt SINGLE-AXIS
    (``distributed.data_only_mesh``): the Pallas ring kernels — and
    their interpret-mode discharge, which rejects multi-axis
    environments — ring over exactly one named axis.  Voting fits ride
    the same data-only mesh (their mesh layout is the data layout; the
    voted-column ring reduces only the candidate slab)."""
    if params.collective in ("auto", "psum", ""):
        return "psum", mesh, "none"
    if mesh is None:
        if params.collective == "ring":
            log.info("collective='ring' needs a multi-shard mesh; this "
                     "serial fit keeps psum (single_data_shard)")
            return "psum", mesh, "single_data_shard"
        return "psum", mesh, "none"
    if params.collective != "ring":
        raise ValueError(f"Unknown collective {params.collective!r}; "
                         "valid: auto, psum, ring")
    from ..core.mesh import DATA_AXIS
    from .distributed import _feat_n, data_only_mesh
    d = int(mesh.shape[DATA_AXIS])
    reason = ("single_data_shard" if d <= 1
              else "feature_axis" if _feat_n(mesh) > 1
              else "ranking" if ranking
              else "dart" if params.boosting == "dart"
              else None)
    if reason is not None:
        log.info("collective='ring' needs a multi-shard data-parallel "
                 "or voting gbdt/goss/rf fit; this fit keeps psum "
                 "(%s)", reason)
        return "psum", mesh, reason
    return "ring", data_only_mesh(mesh), "none"


def _resolve_quantized(params: "TrainParams", n: int, mesh,
                       collective: str, *, ranking: bool = False):
    """Resolve ``params.quantized_grad`` → ``(bits, max_code, wire,
    collective, downgrade)`` (ISSUE 17).

    ``max_code`` is the per-round grid half-width: ``2^(bits-1)-1``
    clamped so ``n * max_code`` (the largest magnitude any int32
    histogram cell can reach — every row in one bin) keeps int32
    headroom.  ``wire`` is the dtype the psum slab crosses the
    interconnect in: the narrowest int that the SAME ``n * max_code``
    bound fits — int8/int16 when it already fits, else the grid is
    CLAMPED to make int16 fit when at least 3 code levels survive
    (payload beats resolution for histogram work; LightGBM's quantized
    training uses 2-5 bit grids), else int32.  Serial fits have no
    wire.  Paths the quantized grower doesn't support (dart's host
    rescale loop, lambdarank) and a ring whose f32 lane can't carry
    the codes exactly (``n * max_code >= 2^24``) degrade — quantization
    off or ring→psum respectively — with reason
    ``quantized_unsupported`` for ``last_fit_info`` and /metrics."""
    if params.quantized_grad == "off":
        return 0, 0, "none", collective, "none"
    if ranking or params.boosting == "dart":
        log.info("quantizedGrad=%s needs a gbdt/goss/rf fit (dart's "
                 "host loop and lambdarank keep f32 gradients); "
                 "quantization is off for this fit "
                 "(quantized_unsupported)", params.quantized_grad)
        return 0, 0, "none", collective, "quantized_unsupported"
    bits = int(params.quantized_grad)
    mc = min((1 << (bits - 1)) - 1, (2**31 - 1) // max(n, 1))
    from ..core.mesh import DATA_AXIS
    d = int(mesh.shape[DATA_AXIS]) if mesh is not None else 1
    if d <= 1:
        return bits, mc, "none", collective, "none"
    if n * mc <= 127:
        wire = "int8"
    elif n * mc <= 32767:
        wire = "int16"
    elif 32767 // max(n, 1) >= 3:
        mc = 32767 // n
        wire = "int16"
    else:
        wire = "int32"
    downgrade = "none"
    if collective == "ring" and n * mc >= (1 << 24):
        log.info("collective='ring' carries histograms in f32 lanes; "
                 "quantized codes up to n*max_code=%d cannot ride it "
                 "exactly — this fit keeps psum (quantized_unsupported)",
                 n * mc)
        collective, downgrade = "psum", "quantized_unsupported"
    return bits, mc, wire, collective, downgrade


#: What the LAST fit in this process actually ran (resolved histogram
#: kernel + collective + backend) — bench.py records it for provenance,
#: and the /metrics exposition below surfaces it as an info gauge.
last_fit_info: Dict[str, str] = {}


def _record_fit_resolution(cfg, collective: str,
                           downgrade: str = "none",
                           sched: Optional[dict] = None,
                           quantized_downgrade: str = "none",
                           hist_sched: Optional[dict] = None) -> None:
    last_fit_info.clear()
    last_fit_info.update(histogram_method=cfg.hist_method,
                         collective=collective,
                         collective_downgrade=downgrade,
                         backend=jax.default_backend(),
                         quantized_bits=str(cfg.quantized_bits),
                         quantized_max_code=str(cfg.quantized_max_code),
                         quantized_wire=cfg.quantized_wire,
                         quantized_downgrade=quantized_downgrade)
    if sched is not None:
        # static per-tree collective accounting (grower.
        # collective_schedule) — bench.py folds these into the artifact
        # detail, and the info gauge exposes them as labels
        dense = max(1, sched["dense_payload_bytes"])
        last_fit_info.update(
            collective_count_per_tree=str(sched["count"]),
            collective_payload_bytes_per_tree=str(sched["payload_bytes"]),
            collective_payload_vs_dense=(
                f"{sched['payload_bytes'] / dense:.6f}"))
        if sched.get("quantized_scale_bytes"):
            last_fit_info.update(quantized_scale_bytes_per_tree=str(
                sched["quantized_scale_bytes"]))
    if hist_sched is not None:
        # which build of the histogram the fit's programs compile, and
        # at how many of a tree's call sites (root + bucket rungs) its
        # one-hot product stays on the chip (grower.hist_build_schedule)
        last_fit_info.update(
            hist_build=hist_sched["build"],
            hist_build_rungs=f"{hist_sched['fused']}/{hist_sched['sites']}")


def _hist_sched_for(cfg, mesh, n: int) -> dict:
    """The histogram builds of this fit's call sites, at the rows one
    shard holds (the bucket ladder is built over those)."""
    if mesh is not None:
        from ..core.mesh import DATA_AXIS
        n = -(-n // max(1, int(mesh.shape[DATA_AXIS])))
    return hist_build_schedule(cfg, n)


def _collective_sched_for(cfg, mesh, n: int, f: int) -> dict:
    """Per-tree collective accounting for this fit: the grower schedule
    evaluated on the MESH-sharded cfg (axis names attach inside the
    scan builders, so the engine-level cfg alone would always read
    serial — zero count/payload)."""
    if mesh is None:
        return collective_schedule(cfg, f)
    from ..core.mesh import DATA_AXIS
    from .distributed import _feat_n, _sharded_cfg
    dn = int(mesh.shape[DATA_AXIS])
    return collective_schedule(
        _sharded_cfg(mesh, cfg), f,
        n_rows_local=-(-n // max(1, dn)),
        feature_shards=_feat_n(mesh))

log = logging.getLogger("mmlspark_tpu.gbdt")


@dataclass
class TrainParams:
    """Engine-level hyper-parameters (host-side; see LightGBMParams analog)."""
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    max_depth: int = -1
    max_bin: int = 255
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    feature_fraction: float = 1.0
    early_stopping_round: int = 0
    boost_from_average: bool = True
    seed: int = 42
    bagging_seed: int = 3
    #: "gbdt", "goss" (gradient-based one-side sampling), "dart"
    #: (dropout-boosting, Rashmi & Gilad-Bachrach 2015), or "rf"
    #: (random forest: bagged unshrunk trees, averaged)
    boosting: str = "gbdt"
    top_rate: float = 0.2
    other_rate: float = 0.1
    #: dart knobs (LightGBM names/defaults)
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    drop_seed: int = 4
    #: mesh-axis layout ("serial"/"data"/"feature"/"data+feature"/"voting")
    parallelism: str = "data"
    #: PV-Tree voting: features voted per shard (LightGBM top_k)
    top_k: int = 20
    histogram_method: str = "auto"
    #: cross-shard histogram reduction on mesh fits: "auto" (psum until
    #: an on-chip A/B flips it), "psum", or "ring" — the Pallas on-chip
    #: ring reduce-scatter/all-gather (ops/pallas_collectives.py;
    #: docs/collectives.md).  Ring fits run on a data-only 1-axis mesh
    #: and degrade to psum wherever the kernel gates refuse.
    collective: str = "auto"
    #: quantized-gradient training (ISSUE 17; Shi et al. 2022, LightGBM
    #: use_quantized_grad): "off" keeps f32 gradients; "16"/"8"
    #: discretize (g, h) each boost round onto a seeded
    #: stochastically-rounded int grid, accumulate histograms in int32,
    #: and cross shards in the narrowest wire dtype the row count
    #: admits (``_resolve_quantized``).  Split gains dequantize back to
    #: f32, so the math of the gain formula is unchanged.
    quantized_grad: str = "off"
    verbosity: int = 1
    #: categorical split knobs (LightGBM names)
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    #: chunk-level failure recovery (SURVEY.md §5.3): > 0 snapshots the
    #: boosting state to host RAM at every chunk boundary and, when a
    #: chunk's device execution fails (preempted or lost chip),
    #: re-uploads the inputs and replays THAT chunk up to this many times
    #: — the TPU-shaped analog of the reference's executor gang-restart.
    fault_tolerant_retries: int = 0
    #: Exclusive Feature Bundling (Ke et al. 2017; LightGBM
    #: enable_bundle): merge mutually-exclusive sparse features into
    #: bundle columns so histogram work scales with bundles, not
    #: features.  Serial gbdt/rf/multiclass paths only; trees and the
    #: exported model always reference original features.
    enable_bundle: bool = False
    max_conflict_rate: float = 0.0
    #: cross-process mid-fit checkpointing (SURVEY.md §5.3 elasticity):
    #: non-empty = a directory where the chunked scan loops persist
    #: (trees, scores, RNG streams, early-stopping state) at every chunk
    #: boundary; a killed fit re-run with the SAME inputs and params
    #: resumes from the last completed chunk bit-identically.  The
    #: snapshot is fingerprinted against (shape, params, topology) and
    #: ignored with a warning on mismatch; it is deleted on successful
    #: completion.  Live for the serial AND mesh gbdt/goss/rf/multiclass
    #: scan paths, including multicontroller sharded ingestion (each
    #: process persists its own score shards into the shared directory;
    #: see docs/fault-tolerance.md); inert (with a warning) for
    #: dart/ranking host loops.
    checkpoint_dir: str = ""
    #: chunk-boundary cadence when checkpointing: the scan chunk is
    #: bounded to this many iterations so at most this much work is
    #: lost to a process death.  Smaller = finer recovery granularity,
    #: more host syncs.  Chunking never changes the forest (the scan
    #: body is per-iteration), so this knob is excluded from the resume
    #: fingerprint.
    checkpoint_chunk: int = 32
    #: raw passthrough params recorded into the model file (parity with the
    #: reference's passThroughArgs).  Keys that NAME a TrainParams field
    #: are applied onto it (string-coerced) in ``__post_init__`` — like
    #: the reference, where passThroughArgs reach the native learner —
    #: while typed setters keep precedence semantics LightGBM-style
    #: (last writer wins: pass_through applies after the constructor).
    pass_through: Dict[str, str] = field(default_factory=dict)

    def __post_init__(self):
        for k, v in self.pass_through.items():
            if k == "pass_through" or not hasattr(self, k):
                continue
            cur = getattr(self, k)
            s = str(v).strip()
            try:
                if isinstance(cur, bool):
                    low = s.lower()
                    if low in ("1", "true", "yes", "on"):
                        val = True
                    elif low in ("0", "false", "no", "off"):
                        val = False
                    else:
                        raise ValueError(f"not a boolean: {s!r}")
                elif isinstance(cur, int):
                    val = int(s)
                elif isinstance(cur, float):
                    val = float(s)
                elif isinstance(cur, str):
                    val = s
                else:
                    continue
            except ValueError as e:
                raise ValueError(
                    f"passThroughArgs {k}={v!r} cannot be coerced to "
                    f"{type(cur).__name__}: {e}") from None
            setattr(self, k, val)
        qg = str(self.quantized_grad).strip().lower()
        self.quantized_grad = {"": "off", "0": "off", "false": "off",
                               "none": "off"}.get(qg, qg)
        if self.quantized_grad not in ("off", "8", "16"):
            raise ValueError(
                f"quantizedGrad={self.quantized_grad!r} is not supported; "
                "valid: off, 16, 8")
        # a removed or mistyped name fails here, not inside a trace
        from ..ops.histogram import check_method
        check_method(self.histogram_method)


@functools.partial(jax.jit, static_argnames=("obj", "cfg", "lr"),
                   donate_argnums=(1,))
def _boost_step(bins, scores, labels, weights, bag_mask, feat_info,
                obj: Objective, cfg: GrowerConfig, lr: float):
    """One boosting iteration for a single tree (single-class)."""
    with jax.named_scope("gradients"):
        g, h = obj.grad_hess(scores, labels, weights)
    gh = jnp.stack([g * bag_mask, h * bag_mask, bag_mask], axis=1)
    tree, row_leaf = _grow_tree_impl(bins, gh, feat_info, cfg)
    with jax.named_scope("score_update"):
        scores = scores + lr * tree.leaf_value[row_leaf]
    tree = apply_shrinkage(tree, lr)
    return tree, scores


def _draw_feature_fraction(rng, fi_base: np.ndarray, f: int,
                           feature_fraction: float) -> np.ndarray:
    """One per-iteration featureFraction mask draw.  Every training path
    (serial, mesh, mesh-ranking) consumes the SAME rng stream through this
    helper, preserving the serial draw-order reproducibility contract."""
    k_keep = max(1, int(np.ceil(f * feature_fraction)))
    sel = rng.choice(f, size=k_keep, replace=False)
    fi_it = fi_base.copy()
    fi_it[:, 0] = 0.0
    fi_it[sel, 0] = 1.0
    return fi_it


def _dummy_val(K: int):
    return jnp.zeros((0,) if K == 1 else (0, K), jnp.float32)


# -- cross-process mid-fit checkpointing (TrainParams.checkpoint_dir) -------

_CKPT_FILE = "boost_checkpoint.npz"       # meta + loop state, atomic
#: one per tree chunk, write-once.  The index field is wide enough that a
#: Criteo-class fit (T up to 10^6 with chunk=1) never collides with the
#: clear glob, which is DERIVED from this template (``_ckpt_glob``), not
#: hand-maintained alongside it.
_CKPT_CHUNK = "boost_chunk_{:06d}.npz"
#: per-process mesh state, stamped with the boundary iteration so the
#: state write (first) and the meta write (last, process 0) are never
#: torn against each other: the meta's ``it`` names exactly the state
#: generation that was durable before it.  The prefix is a separate
#: constant so the per-process GC glob (prefix + ``*``) stays correct
#: if the iteration field is ever widened.
_CKPT_MESH_PREFIX = "mesh_state_p{:03d}_it"
_CKPT_MESH_STATE = _CKPT_MESH_PREFIX + "{:06d}.npz"

#: Process-wide training recovery observability (the training-side
#: analog of ``ScoringEngine.stats()``): cumulative counters over every
#: fit in this process, seeded to explicit zeros so "no recovery event
#: happened" is observable rather than a missing key.  Tests and the
#: chaos drill snapshot before/after a fit and assert deltas.
train_stats = StageStats()
for _k in ("chunks_replayed", "ckpt_saved", "ckpt_resumed",
           "ckpt_discarded", "boost_chunks", "ref_profiles",
           "ref_profiles_device", "mesh_step_hits", "mesh_step_builds",
           "collective_count", "collective_payload_bytes"):
    train_stats.incr(_k, 0)
del _k
# federate under the process registry: a serving process that also
# trains (or a training controller with a debug HTTP server) exposes
# these on /metrics next to the scoring stats (ISSUE 5)
_tm.get_registry().register("train", train_stats)
# compile-event attribution (ISSUE 12): jax is imported by this module,
# so the profiler's jax.monitoring listener can install here — every
# backend compile from now on lands in the compile ledger
install_jax_hooks()


def _fit_resolution_exposition() -> str:
    """Prometheus info gauge naming the RESOLVED histogram kernel and
    collective the last fit in this process ran — so /metrics answers
    "which kernel is training actually using" without log spelunking."""
    if not last_fit_info:
        return ""
    labels = ",".join(f'{k}="{v}"' for k, v in sorted(
        last_fit_info.items()))
    name = "mmlspark_tpu_train_histogram_method_info"
    return (f"# HELP {name} Resolved histogram kernel/collective of the "
            "last fit\n"
            f"# TYPE {name} gauge\n"
            f"{name}{{{labels}}} 1\n")


_tm.get_registry().register_exposition("train_histogram_method",
                                       _fit_resolution_exposition)


def _quantized_exposition() -> str:
    """Prometheus info gauge naming the quantized-gradient resolution of
    the last fit (ISSUE 17): grid bits, max code after headroom clamps,
    the wire dtype psum slabs cross shards in, and whether a downgrade
    fired — so /metrics answers "is training actually running low-bit,
    and how low" without log spelunking."""
    if not last_fit_info:
        return ""
    keys = ("quantized_bits", "quantized_max_code", "quantized_wire",
            "quantized_downgrade")
    labels = ",".join(
        f'{k[len("quantized_"):]}="{last_fit_info[k]}"'
        for k in keys if k in last_fit_info)
    if not labels:
        return ""
    name = "mmlspark_tpu_train_quantized_info"
    return (f"# HELP {name} Quantized-gradient resolution of the last "
            "fit\n"
            f"# TYPE {name} gauge\n"
            f"{name}{{{labels}}} 1\n")


_tm.get_registry().register_exposition("train_quantized",
                                       _quantized_exposition)


def _ckpt_event(name: str, **fields) -> None:
    """Journal a checkpoint lifecycle event, stamped with the current
    fit span so ``tools/trace_report.py`` can place it on the fit's
    timeline."""
    _tm.get_journal().emit(name, fit=_tm.current_fit_span(), **fields)


def _dispatch_chunk(run, scores, val_scores, it: int, trees: int,
                    t_chunk: float, uploaded=None, **ids):
    """One bracketed chunk dispatch, for the serial and the mesh loop
    alike: ``run(scores, val_scores)`` under ``train.launch`` (tracing,
    compile or cache look-up, enqueue; its attrs say how much of it was
    which, from the ``jax.monitoring`` sums the profiler keeps), then
    ``jax.block_until_ready`` on the chunk's trees under
    ``train.device_wait``.  The sync is for honest chunk timing; the
    host needs these results before the next chunk (or the final fetch)
    anyway, so it moves a wait, it does not add one.

    ``uploaded``: a fit's FIRST chunk hands over ``(operands, bytes)``,
    the arrays ``train.upload`` (and ``train.rank_pack``) sent that the
    program does not donate, and the bytes those spans said went up.
    ``jnp.asarray`` returns before a transfer ends, so the program just
    enqueued waits for its table; ``train.upload_wait`` blocks on the
    operands first, and what ``train.device_wait`` then holds is device
    work and its drain.  The device sees nothing new: the host waits in
    two steps where it waited in one, and with the profiler off in one.

    Also feeds the ``train.boost_chunk`` dispatch ledger (the
    ``compile_seq`` delta classifying the dispatch as cache hit or miss)
    and journals the ``profile_span`` that ``tools/trace_report.py`` lays
    on the fit's timeline (ISSUE 12): host glue since ``t_chunk``
    against the two waits."""
    p = get_profiler()
    seq0 = p.compile_seq()
    traced0 = p.jax_seconds("jaxpr_trace")
    compiled0 = p.jax_seconds("backend_compile")
    with p.region("train.launch") as sp:
        out = run(scores, val_scores)
        misses = p.compile_seq() - seq0
        sp.update(
            compile_misses=misses,
            jaxpr_trace_s=round(p.jax_seconds("jaxpr_trace") - traced0, 6),
            backend_compile_s=round(
                p.jax_seconds("backend_compile") - compiled0, 6))
    t_host = time.perf_counter()
    if uploaded is not None and p.enabled:
        operands, nbytes = uploaded
        with p.region("train.upload_wait", bytes=int(nbytes)):
            jax.block_until_ready(operands)
    with p.region("train.device_wait", it=int(it), trees=int(trees)):
        jax.block_until_ready(out[0])
    t_done = time.perf_counter()
    if p.enabled:
        p.count_dispatch("train.boost_chunk", misses)
    p.span("train.boost_chunk", t_done - t_chunk, journal=True,
           it=int(it), host_ms=round((t_host - t_chunk) * 1e3, 3),
           device_ms=round((t_done - t_host) * 1e3, 3), **ids)
    return out


#: cap on rows fetched to the host per chunk boundary for the telemetry
#: train-loss gauge; larger fits are sampled with a stride (a gauge
#: needs a stable estimate, not the exact sum)
_MONITOR_LOSS_MAX_ROWS = 65536


def _monitor_chunk(it0: int, it1: int, dt_s: float, n_rows: int, K: int,
                   hist_method: str, objective=None, scores=None,
                   labels=None, weights=None,
                   collective: str = "none",
                   coll_sched: Optional[dict] = None) -> None:
    """Per-boost-chunk live training telemetry: ms/tree, rows/s,
    last-iteration and (when the objective can compute it cheaply)
    train-loss gauges on ``train_stats``, plus one ``boost_chunk``
    journal event — the numbers ``tools/chaos_training.py`` and the
    serving bench read from telemetry instead of ad-hoc prints.

    ``scores`` may be a device array; it is only fetched when the
    objective implements ``train_loss`` and the array is fully
    addressable (a multi-controller mesh shard is not — loss is skipped
    there rather than gathering the gang's scores).  The fetch is
    bounded: beyond ``_MONITOR_LOSS_MAX_ROWS`` rows the loss is
    computed on a strided sample, sliced ON DEVICE first, so a
    Criteo-scale fit pays a bounded D2H per boundary for the gauge, not
    an O(n) transfer the training loop never needed before.

    ``coll_sched``: the fit's per-tree collective accounting
    (grower.collective_schedule) — scaled by the chunk's tree count into
    the ``collective_count``/``collective_payload_bytes`` counters and
    journaled on the ``boost_chunk`` event, so the payload a wide-data
    voting fit saves is machine-checkable on /metrics (ISSUE 16).

    The whole of it is the fit's ``train.monitor`` span: telemetry's own
    cost inside the fit, ``loss_rows`` being the rows the loss gauge
    fetched."""
    with get_profiler().region("train.monitor", loss_rows=0) as sp:
        iters = max(1, it1 - it0)
        trees = iters * max(1, K)
        ms_per_tree = dt_s * 1e3 / trees
        rows_per_s = n_rows * iters / dt_s if dt_s > 0 else 0.0
        train_stats.set_gauge("ms_per_tree", round(ms_per_tree, 3))
        train_stats.set_gauge("train_rows_per_s", round(rows_per_s, 1))
        train_stats.set_gauge("last_iteration", float(it1))
        train_stats.incr("boost_chunks")
        coll_count = coll_bytes = None
        if coll_sched is not None:
            coll_count = coll_sched["count"] * trees
            coll_bytes = coll_sched["payload_bytes"] * trees
            train_stats.incr("collective_count", coll_count)
            train_stats.incr("collective_payload_bytes", coll_bytes)
        loss = None
        if (objective is not None and scores is not None
                and labels is not None
                and getattr(scores, "is_fully_addressable", True)):
            try:
                labels_np = np.asarray(labels)
                stride = max(1, len(labels_np) // _MONITOR_LOSS_MAX_ROWS)
                if stride > 1:
                    scores = scores[::stride]    # device-side slice: the
                    labels_np = labels_np[::stride]   # D2H stays bounded
                    weights = (None if weights is None
                               else np.asarray(weights)[::stride])
                sp["loss_rows"] = int(len(labels_np))
                loss = objective.train_loss(np.asarray(scores), labels_np,
                                            weights)
            except Exception:  # noqa: BLE001 - telemetry must never kill
                loss = None    # the fit it observes
        if loss is not None:
            train_stats.set_gauge("train_loss", round(float(loss), 6))
        ev = {"fit": _tm.current_fit_span(), "it_start": int(it0),
              "it_end": int(it1), "ms_per_tree": round(ms_per_tree, 3),
              "rows_per_s": round(rows_per_s, 1),
              "hist_method": hist_method, "collective": collective}
        if coll_count is not None:
            ev["collective_count"] = int(coll_count)
            ev["collective_payload_bytes"] = int(coll_bytes)
        if loss is not None:
            ev["train_loss"] = round(float(loss), 6)
        _tm.get_journal().emit("boost_chunk", **ev)


def _ckpt_glob(template: str) -> str:
    """Glob pattern for a checkpoint filename template, derived from the
    template's own format fields (every ``{...}`` becomes ``*``) so a
    template change can never silently orphan files."""
    import re
    return re.sub(r"\{[^{}]*\}", "*", template)


def _fsync_dir(path: str) -> None:
    """fsync a DIRECTORY so a just-renamed file survives power loss (the
    rename itself lives in the directory's metadata; fsyncing the file
    alone is not enough).  Best-effort: some platforms refuse directory
    fds, and a checkpoint must never kill the fit it protects."""
    import os
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _ckpt_fingerprint(n, f, K, params, labels, bins, weights,
                      init_scores) -> str:
    """Identity of a fit for resume safety: shapes, every param that
    shapes the boosting trajectory (checkpoint_dir itself excluded so
    moving the directory doesn't orphan the snapshot), AND a digest of
    the data — full labels, weights, init scores (the continued-training
    margins: a re-run with a different initModelPath must NOT resume the
    old trajectory) plus a strided sample of the binned matrix — so a
    same-shape fit on DIFFERENT inputs starts fresh instead of silently
    blending two fits."""
    import hashlib
    # checkpoint_dir/checkpoint_chunk shape WHERE and HOW OFTEN snapshots
    # land, never the boosting trajectory — excluded so moving the
    # directory or retuning the boundary cadence doesn't orphan a resume
    d = {k: v for k, v in params.__dict__.items()
         if k not in ("checkpoint_dir", "checkpoint_chunk")}
    h = hashlib.sha256(
        f"{n}|{f}|{K}|{sorted(d.items())!r}".encode("utf-8"))
    h.update(np.ascontiguousarray(np.asarray(labels)).tobytes())
    h.update(b"w" if weights is None else
             np.ascontiguousarray(np.asarray(weights)).tobytes())
    h.update(b"i" if init_scores is None else
             np.ascontiguousarray(np.asarray(init_scores)).tobytes())
    bins_np = np.asarray(bins)
    h.update(np.ascontiguousarray(
        bins_np[:: max(1, len(bins_np) // 4096)]).tobytes())
    return h.hexdigest()


def _ckpt_save(ckpt_dir, fp, it, trees_chunks, scores, val_scores,
               cur_bag, rng, bag_rng, best_metric, best_iter) -> None:
    """Persist the chunk-boundary state.

    Tree chunks are immutable once grown, so each is written to its own
    file exactly ONCE (O(1) device→host transfer and disk I/O per
    boundary, not O(chunks)); the small meta/state file — host copies of
    the device score vectors (float32 round-trips exactly), the two host
    RNG streams (bit-generator state as JSON), the carried bag mask and
    the early-stopping bests — is replaced atomically (tmp + fsync +
    rename) last, so a torn save leaves the PREVIOUS boundary loadable.
    A resumed fit replays the remaining chunks on bit-identical inputs."""
    import os
    os.makedirs(ckpt_dir, exist_ok=True)
    _ckpt_write_chunks(ckpt_dir, trees_chunks)
    _ckpt_write_meta(
        ckpt_dir, fp, it, len(trees_chunks), rng, bag_rng, best_metric,
        best_iter,
        arrays={"scores": np.asarray(scores),
                "val_scores": np.asarray(val_scores),
                "cur_bag": np.asarray(cur_bag)},
        extra_meta={"n_trees": _ckpt_tree_count(trees_chunks),
                    "fit_span": _tm.current_fit_span()})
    train_stats.incr("ckpt_saved")
    _ckpt_event("ckpt_saved", it=int(it), n_chunks=len(trees_chunks))


def _ckpt_tree_count(trees_chunks) -> int:
    """Total trees across the chunk list — endorsed by the meta so a
    load can detect a STALE over-meta chunk file.  The write-once skip
    in :func:`_ckpt_write_chunks` is only sound while the chunk CADENCE
    is unchanged: ``checkpoint_chunk`` is deliberately outside the
    fingerprint (retuning it must not orphan a resume), so a crash
    between a chunk write and its meta replace, followed by a resume
    with a different cadence, can leave file ``n`` holding a different
    iteration count than the new meta implies — identical VALUES are
    guaranteed by bit-identical replay, counts are not.  Validating
    the endorsed total at load turns that silent wrong-forest into a
    discard-and-start-fresh."""
    # shape alone: no D2H transfer for device-resident mesh chunks
    return int(sum(ch[0].shape[0] for ch in trees_chunks))


def _ckpt_read_chunks(ckpt_dir, n_chunks, n_trees=None):
    """Load the write-once tree chunk files, closing each npz (a
    lingering NpzFile holds its zip member open; resumed gangs would
    otherwise accumulate one fd per chunk per process).  When the
    meta's endorsed ``n_trees`` is given, a total-count mismatch —
    a stale over-meta chunk from a different ``checkpoint_chunk``
    cadence (see :func:`_ckpt_tree_count`) — raises, which the load
    paths turn into discard-and-start-fresh."""
    import os
    chunks = []
    for i in range(n_chunks):
        with np.load(os.path.join(ckpt_dir, _CKPT_CHUNK.format(i))) as cz:
            chunks.append(TreeArrays(*[cz[name]
                                       for name in TreeArrays._fields]))
    if n_trees is not None and _ckpt_tree_count(chunks) != n_trees:
        raise ValueError(
            f"tree chunk files hold {_ckpt_tree_count(chunks)} trees "
            f"but the checkpoint meta endorses {n_trees} (stale chunk "
            f"from a different checkpoint_chunk cadence)")
    return chunks


def _ckpt_write_chunks(ckpt_dir, trees_chunks) -> None:
    """Write-once tree chunk files (fsync'd, atomic rename each)."""
    import os
    for i, ch in enumerate(trees_chunks):
        cpath = os.path.join(ckpt_dir, _CKPT_CHUNK.format(i))
        if os.path.exists(cpath):
            continue
        tmp = cpath + ".tmp"
        with open(tmp, "wb") as fh:
            np.savez(fh, **{name: np.asarray(arr) for name, arr
                            in zip(TreeArrays._fields, ch)})
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, cpath)


def _ckpt_write_meta(ckpt_dir, fp, it, n_chunks, rng, bag_rng,
                     best_metric, best_iter, arrays, extra_meta=None
                     ) -> None:
    """The small meta/state file, replaced atomically LAST so a torn
    save leaves the previous boundary loadable; the containing
    directory is fsync'd after the rename so the rename itself survives
    power loss (the file fsync alone only makes the INODE durable, not
    the directory entry pointing at it)."""
    import json as _json
    import os
    meta = {
        "fingerprint": fp, "it": int(it),
        "n_chunks": int(n_chunks),
        "rng_state": rng.bit_generator.state,
        "bag_rng_state": bag_rng.bit_generator.state,
        "best_metric": float(best_metric), "best_iter": int(best_iter),
    }
    if extra_meta:
        meta.update(extra_meta)
    tmp = os.path.join(ckpt_dir, _CKPT_FILE + ".tmp")
    with open(tmp, "wb") as fh:
        np.savez(fh,
                 __meta__=np.frombuffer(
                     _json.dumps(meta).encode("utf-8"), np.uint8),
                 **arrays)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, os.path.join(ckpt_dir, _CKPT_FILE))
    _fsync_dir(ckpt_dir)


def _ckpt_load(ckpt_dir, fp):
    """Load and validate a snapshot; None when absent/torn/mismatched —
    a bad snapshot must degrade to a fresh fit, never kill the re-run."""
    import json as _json
    import os
    path = os.path.join(ckpt_dir, _CKPT_FILE)
    if not os.path.exists(path):
        return None
    try:
        with np.load(path) as z:
            meta = _json.loads(bytes(z["__meta__"]).decode("utf-8"))
            if meta["fingerprint"] != fp:
                log.warning("checkpoint at %s belongs to a different "
                            "fit (data or params changed); starting "
                            "fresh", path)
                train_stats.incr("ckpt_discarded")
                _ckpt_event("ckpt_discarded",
                            reason="fingerprint_mismatch")
                return None
            arrays = {k: z[k] for k in ("scores", "val_scores",
                                        "cur_bag")}
        return {
            "it": meta["it"],
            "trees_chunks": _ckpt_read_chunks(ckpt_dir,
                                              meta["n_chunks"],
                                              meta.get("n_trees")),
            "scores": arrays["scores"],
            "val_scores": arrays["val_scores"],
            "cur_bag": arrays["cur_bag"],
            "rng_state": meta["rng_state"],
            "bag_rng_state": meta["bag_rng_state"],
            "best_metric": meta["best_metric"],
            "best_iter": meta["best_iter"],
        }
    except Exception as e:  # noqa: BLE001 - torn/partial snapshot
        # degrade-to-fresh-fit is the right behavior, but the REASON
        # must be diagnosable — silent checkpoint loss looks identical
        # to "no checkpoint existed" in the logs otherwise
        log.warning("checkpoint at %s is unreadable (%s: %s); "
                    "starting fresh", path, type(e).__name__, e)
        train_stats.incr("ckpt_discarded")
        _ckpt_event("ckpt_discarded", reason=type(e).__name__)
        return None


def _ckpt_clear(ckpt_dir) -> None:
    import glob
    import os
    # ".tmp" partials too: a crash mid-atomic-write leaves one behind,
    # and the resumed fit may never rewrite that index
    paths = [os.path.join(ckpt_dir, _CKPT_FILE),
             os.path.join(ckpt_dir, _CKPT_FILE + ".tmp")]
    for tpl in (_CKPT_CHUNK, _CKPT_MESH_STATE):
        for pat in (_ckpt_glob(tpl), _ckpt_glob(tpl) + ".tmp"):
            paths += glob.glob(os.path.join(ckpt_dir, pat))
    for p in paths:
        try:
            os.remove(p)
        except OSError:
            pass


def _ckpt_fingerprint_mesh(n, f, K, params, labels, bins, w,
                           init_scores, mesh, shard_data=None) -> str:
    """Mesh-fit resume fingerprint: the serial digest plus the mesh
    topology (shape and process count), so a resume under a different
    shard layout starts fresh instead of scattering shards wrongly.

    Under sharded ingestion the digest covers only GLOBAL metadata
    (params, concatenated labels/weights/init-scores, per-shard sizes)
    — inputs every controller shares — so the shared fingerprint is
    identical on every process with no coordination round.  Feature
    VALUES are covered per process by :func:`_local_bins_digest`
    (stored in each state file, validated locally, and made unanimous
    by the gang gate in ``_train_distributed``)."""
    import hashlib
    from ..core.mesh import DATA_AXIS
    if shard_data is not None:
        sizes = list(shard_data["sizes"])
        y_cat = np.concatenate(
            [np.asarray(y) for y in shard_data["label_shards"]])
        w_cat = np.concatenate(
            [np.asarray(ws) for ws in shard_data["weight_shards"]])
        iss = shard_data.get("init_score_shards")
        is_cat = (None if iss is None or any(s is None for s in iss)
                  else np.concatenate([np.asarray(s) for s in iss]))
        base = _ckpt_fingerprint(n, f, K, params, y_cat,
                                 np.zeros((0, f), np.uint8), w_cat,
                                 is_cat)
        base = hashlib.sha256(
            (base + "|sizes=" + ",".join(map(str, sizes))
             ).encode("utf-8")).hexdigest()
    else:
        base = _ckpt_fingerprint(n, f, K, params, labels, bins, w,
                                 init_scores)
    from .distributed import _feat_n
    topo = (f"|mesh={int(mesh.shape[DATA_AXIS])}x"
            f"{_feat_n(mesh)}"
            f"|procs={jax.process_count()}")
    return hashlib.sha256((base + topo).encode("utf-8")).hexdigest()


def _local_bins_digest(shard_data) -> str:
    """Digest of the per-process inputs THIS process contributes under
    sharded ingestion: its feature shards AND its init-score shards.
    The shared mesh fingerprint can only cover metadata every
    controller holds (labels, weights, sizes) — init scores are
    excluded there too, because under multicontroller ingestion every
    process holds ``None`` in its peers' slots.  Without this digest a
    re-run on re-extracted feature values, or a continuation re-run
    with a different ``initModelPath``'s margins, would silently
    resume and blend two fits — the exact failure
    ``_ckpt_fingerprint`` hashes ``bins`` and ``init_scores`` to
    prevent on the serial path.  Non-sharded mesh fits return ""
    (their bins and init scores are already in the shared
    fingerprint)."""
    import hashlib
    if shard_data is None:
        return ""
    h = hashlib.sha256()
    for b in shard_data["bins_shards"]:
        if b is not None:
            h.update(np.ascontiguousarray(np.asarray(b)).tobytes())
    iss = shard_data.get("init_score_shards")
    if iss is not None:
        for i, s in enumerate(iss):
            if s is not None:
                # slot index tagged so present/absent layout changes
                # can never alias
                h.update(f"|is{i}|".encode("utf-8"))
                h.update(np.ascontiguousarray(
                    np.asarray(s, np.float32)).tobytes())
    return h.hexdigest()


def _ckpt_shard_bounds(index, shape):
    """Normalize an addressable-shard index (tuple of slices) to
    JSON-able ``[[start, stop], ...]`` bounds."""
    return [list(s.indices(dim)[:2]) for s, dim in zip(index, shape)]


def _ckpt_save_mesh(ckpt_dir, fp, it, trees_chunks, scores, val_scores,
                    cur_bag, rng, bag_rng, best_metric, best_iter,
                    local_digest="") -> None:
    """Mesh/multicontroller chunk-boundary snapshot.

    Write order gives crash consistency without any cross-process
    commit protocol:

    1. every process writes its OWN it-stamped state file — the
       addressable shards of the (sharded, possibly non-fully-
       addressable) score vectors plus the host-side bag mask —
       atomically (tmp + fsync + rename);
    2. processes barrier (``sync_global_devices``) so the meta can
       never name a boundary some peer hasn't persisted;
    3. process 0 replaces the meta file (fingerprint, it, RNG streams,
       early-stopping bests) and fsyncs the directory;
    4. each process garbage-collects its own OLDER state generations.

    A crash anywhere leaves the meta pointing at a complete, durable
    state generation: before step 3 the previous generation's files are
    still on disk (step 4 hasn't run), after step 3 the new generation
    is fully written.  Tree chunks are write-once and shared (trees are
    replicated across the mesh), so process 0 alone persists them.
    """
    import glob
    import os
    pid = jax.process_index()
    nproc = jax.process_count()
    os.makedirs(ckpt_dir, exist_ok=True)
    if pid == 0:
        _ckpt_write_chunks(ckpt_dir, trees_chunks)
    arrays = {"cur_bag": np.asarray(cur_bag)}
    shards_meta = []
    seen = set()
    for name, arr in (("scores", scores), ("val_scores", val_scores)):
        for sh in arr.addressable_shards:
            bounds = _ckpt_shard_bounds(sh.index, arr.shape)
            key = (name, str(bounds))
            if key in seen:      # replicas (e.g. along the feature axis)
                continue
            seen.add(key)
            arrays[f"shard_{len(shards_meta)}"] = np.asarray(sh.data)
            shards_meta.append({"name": name, "bounds": bounds})
    import json as _json
    pmeta = {"fingerprint": fp, "it": int(it), "pid": pid,
             "local_digest": local_digest, "shards": shards_meta}
    spath = os.path.join(ckpt_dir, _CKPT_MESH_STATE.format(pid, int(it)))
    tmp = spath + ".tmp"
    with open(tmp, "wb") as fh:
        np.savez(fh,
                 __meta__=np.frombuffer(
                     _json.dumps(pmeta).encode("utf-8"), np.uint8),
                 **arrays)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, spath)
    _fsync_dir(ckpt_dir)
    if nproc > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(f"ckpt_save_{it}")
    if pid == 0:
        _ckpt_write_meta(ckpt_dir, fp, it, len(trees_chunks), rng,
                         bag_rng, best_metric, best_iter, arrays={},
                         extra_meta={"nproc": nproc, "mesh": True,
                                     "n_trees": _ckpt_tree_count(
                                         trees_chunks),
                                     "fit_span":
                                         _tm.current_fit_span()})
    if nproc > 1:
        # second barrier: no peer may GC its PREVIOUS generation until
        # the meta naming the new one is durable — otherwise a gang
        # crash in the window between a peer's GC and process 0's meta
        # replace leaves the meta pointing at a generation whose state
        # files are already gone (full restart instead of bounded loss)
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(f"ckpt_meta_{it}")
    # GC this process's older state generations (the meta naming `it`
    # is durable for every process once written, and peers never read
    # another process's shard data, only its __meta__ for validation)
    own_glob = _CKPT_MESH_PREFIX.format(pid) + "*"
    for p in glob.glob(os.path.join(ckpt_dir, own_glob)):
        if p != spath:
            try:
                os.remove(p)
            except OSError:
                pass
    train_stats.incr("ckpt_saved")
    _ckpt_event("ckpt_saved", it=int(it), n_chunks=len(trees_chunks),
                pid=pid, mesh=True)


def _ckpt_load_mesh(ckpt_dir, fp, scores_like, val_scores_like,
                    local_digest=""):
    """Validate and load a mesh snapshot; None when absent/torn/
    mismatched (degrade to a fresh fit, exactly like the serial path).

    The shared parts of the verdict — meta present, fingerprint match,
    one state file per process stamped with the meta's ``it`` and
    fingerprint — are a pure function of the SHARED checkpoint
    directory, so every controller reaches them identically with no
    coordination round.  The ``local_digest`` check (this process's own
    feature data) can legitimately diverge across processes; the caller
    makes the final verdict unanimous with a gang allgather.  Each
    process materializes only its own state file's arrays; peers' files
    are opened for their ``__meta__`` validation alone.
    """
    import json as _json
    import os
    path = os.path.join(ckpt_dir, _CKPT_FILE)
    if not os.path.exists(path):
        return None
    pid = jax.process_index()
    try:
        with np.load(path) as z:
            meta = _json.loads(bytes(z["__meta__"]).decode("utf-8"))
        if meta["fingerprint"] != fp:
            log.warning("mesh checkpoint at %s belongs to a different "
                        "fit (data, params or topology changed); "
                        "starting fresh", path)
            train_stats.incr("ckpt_discarded")
            _ckpt_event("ckpt_discarded",
                        reason="fingerprint_mismatch", mesh=True)
            return None
        it = meta["it"]
        nproc = meta.get("nproc", 1)
        own_meta, own_arrays = None, None
        for p in range(nproc):
            spath = os.path.join(ckpt_dir,
                                 _CKPT_MESH_STATE.format(p, it))
            # materialize-and-close: peers' files are opened for their
            # __meta__ alone, and a lingering NpzFile leaks one fd per
            # peer per resume
            with np.load(spath) as sz:
                pmeta = _json.loads(
                    bytes(sz["__meta__"]).decode("utf-8"))
                if pmeta["fingerprint"] != fp or pmeta["it"] != it:
                    raise ValueError(
                        f"state file for process {p} does not match "
                        f"the checkpoint meta (boundary {it})")
                if p == pid:
                    own_meta = pmeta
                    own_arrays = {k: sz[k] for k in sz.files
                                  if k != "__meta__"}
        if own_meta.get("local_digest", "") != local_digest:
            # cheap string check FIRST: rejecting here must not pay the
            # full-forest chunk read below
            log.warning("mesh checkpoint state for process %d was "
                        "written against different local feature data; "
                        "starting fresh", pid)
            train_stats.incr("ckpt_discarded")
            _ckpt_event("ckpt_discarded", reason="local_digest",
                        mesh=True)
            return None
        chunks = _ckpt_read_chunks(ckpt_dir, meta["n_chunks"],
                                   meta.get("n_trees"))
        lookup = {}
        for i, sm in enumerate(own_meta["shards"]):
            lookup[(sm["name"], str(sm["bounds"]))] = \
                own_arrays[f"shard_{i}"]

        def restore(name, like):
            def cb(index):
                bounds = _ckpt_shard_bounds(index, like.shape)
                return lookup[(name, str(bounds))]
            return jax.make_array_from_callback(
                like.shape, like.sharding, cb)

        return {
            "it": it, "trees_chunks": chunks,
            "scores": restore("scores", scores_like),
            "val_scores": restore("val_scores", val_scores_like),
            "cur_bag": np.asarray(own_arrays["cur_bag"]),
            "rng_state": meta["rng_state"],
            "bag_rng_state": meta["bag_rng_state"],
            "best_metric": meta["best_metric"],
            "best_iter": meta["best_iter"],
        }
    except Exception as e:  # noqa: BLE001 - torn/partial snapshot
        log.warning("mesh checkpoint at %s is unusable (%s: %s); "
                    "starting fresh", path, type(e).__name__, e)
        train_stats.incr("ckpt_discarded")
        _ckpt_event("ckpt_discarded", reason=type(e).__name__,
                    mesh=True)
        return None


@functools.partial(jax.jit,
                   static_argnames=("obj", "cfg", "lr", "has_val", "rf"),
                   donate_argnums=(1, 7))
def _boost_scan(bins, scores, labels, weights, bag_masks, fi_stack,
                val_bins, val_scores, obj: Objective, cfg: GrowerConfig,
                lr: float, has_val: bool, rf: bool = False, efb=None):
    """A chunk of boosting iterations inside ONE compiled program.

    ``bag_masks``: (C, n) bagging masks, or (C, 1) broadcast when bagging
    is off; ``fi_stack``: (C, f, 3) per-iteration feature info.  Returns
    (stacked shrunk trees, scores, val_scores, per-iter val scores).

    One launch per chunk instead of per iteration: every dispatch and
    every host sync between iterations is a gap on the device, and the
    scan lets XLA pipeline tree t's tail with tree t+1's head.  This
    is the TPU-shaped analog of the reference keeping the whole iteration
    loop behind one JNI call (SURVEY.md §3.1).
    """
    binsT = bins.T   # fit-invariant; hoisted out of the scan (PERF.md r4)

    def body(carry, xs):
        scores, val_scores = carry
        bag, fi = xs
        bag = jnp.broadcast_to(bag, scores.shape)
        with jax.named_scope("gradients"):
            g, h = obj.grad_hess(scores, labels, weights)
        gh = jnp.stack([g * bag, h * bag, bag], axis=1)
        tree, row_leaf = _grow_tree_impl(bins, gh, fi, cfg, efb,
                                         binsT=binsT)
        if not rf:
            # rf (random forest): every tree fits the gradient at the
            # CONSTANT init scores, unshrunk; averaging happens at export
            with jax.named_scope("score_update"):
                scores = scores + lr * tree.leaf_value[row_leaf]
            tree = apply_shrinkage(tree, lr)
        if has_val:
            val_scores = val_scores + predict_tree_binned(
                tree, val_bins, cfg.num_leaves)
            out_val = val_scores
        else:
            out_val = _dummy_val(1)
        return (scores, val_scores), (tree, out_val)

    (scores, val_scores), (trees, val_hist) = jax.lax.scan(
        body, (scores, val_scores), (bag_masks, fi_stack))
    return trees, scores, val_scores, val_hist


def _dart_draw_drops(dart_rng, n_trees: int, params) -> np.ndarray:
    """Per-iteration dart dropout draw — ONE shared RNG-stream consumer so
    the serial and mesh dart loops make bit-identical dropout decisions
    for the same dropSeed (the serial↔mesh parity contract)."""
    if n_trees and dart_rng.random() >= params.skip_drop:
        sel = np.nonzero(dart_rng.random(n_trees) < params.drop_rate)[0]
        # maxDrop <= 0 means "no limit" (LightGBM max_drop docs)
        if params.max_drop > 0 and len(sel) > params.max_drop:
            sel = dart_rng.choice(sel, size=params.max_drop,
                                  replace=False)
        return sel
    return np.zeros(0, np.int64)


def _dart_host_loop(T, K, dart_rng, params, scores, bag_draw, fi_draw,
                    grow_unit, unit_margin, callbacks, val_hook=None,
                    units_out=None):
    """THE dart dropout bookkeeping — serial and mesh run this one loop
    (the serial↔mesh same-dropSeed parity contract holds by
    construction).  Per iteration: draw drops, subtract the dropped
    units' scaled margins, grow at the dropped-out scores via
    ``grow_unit(s_minus, bag, fi) -> (unit, b_new)``, apply the 1/(k+1)
    normalization, rescale the dropped units.  ``unit_margin(unit)``
    scores a unit on the TRAINING rows; ``val_hook(it, unit, sel,
    scales, norm)`` (optional) sees the PRE-update scales, matching the
    validation-margin algebra.  Returns (units, flat trees_list
    iteration-major class-minor, per-iteration scales, scores)."""
    units: List[TreeArrays] = units_out if units_out is not None else []
    trees_list: List[TreeArrays] = []
    scales: List[float] = []
    for it in range(T):
        bag = bag_draw(it)
        fi = fi_draw(it)
        sel = _dart_draw_drops(dart_rng, len(units), params)
        k = len(sel)
        if k:
            P = scales[sel[0]] * unit_margin(units[sel[0]])
            for i in sel[1:]:
                P = P + scales[i] * unit_margin(units[i])
            s_minus = scores - P
        else:
            s_minus = scores
        unit, b_new = grow_unit(s_minus, bag, fi)
        norm = 1.0 / (k + 1)
        scores = s_minus + norm * b_new
        if k:
            scores = scores + (k * norm) * P
        if val_hook is not None:
            val_hook(it, unit, sel, scales, norm)
        if k:
            for i in sel:
                scales[i] *= k * norm
        units.append(unit)
        scales.append(norm)
        if K == 1:
            trees_list.append(unit)
        else:
            trees_list.extend(
                jax.tree_util.tree_map(lambda a, kk=kk: a[kk], unit)
                for kk in range(K))
        if callbacks:
            for cb in callbacks:
                cb(it, trees_list)
    return units, trees_list, scales, scores


@functools.partial(jax.jit, static_argnames=("obj", "cfg", "lr", "K"))
def _dart_step(bins, binsT, s_minus, labels, weights, bag, fi,
               obj: Objective, cfg: GrowerConfig, lr: float, K: int = 1,
               efb=None):
    """One dart iteration body: fit tree(s) to the gradient at the
    dropped-out score vector; returns the lr-shrunk tree(s) and the base
    contribution (the host applies the 1/(k+1) dart normalization).
    ``binsT`` is the fit-invariant transpose, computed once by the caller.

    ``K > 1`` (multiclass): LightGBM's dart drops whole ITERATIONS — the
    K class trees of an iteration share one weight — so the step grows K
    trees at the shared dropped-out scores and returns them stacked
    (K, ...) with a (n, K) contribution."""
    with jax.named_scope("gradients"):
        g, h = obj.grad_hess(s_minus, labels, weights)
    if K == 1:
        gh = jnp.stack([g * bag, h * bag, bag], axis=1)
        tree, row_leaf = _grow_tree_impl(bins, gh, fi, cfg, efb,
                                         binsT=binsT)
        tree = apply_shrinkage(tree, lr)
        return tree, tree.leaf_value[row_leaf]
    trees_k, bnews = [], []
    for k in range(K):
        gh = jnp.stack([g[:, k] * bag, h[:, k] * bag, bag], axis=1)
        tree, row_leaf = _grow_tree_impl(bins, gh, fi, cfg, efb,
                                         binsT=binsT)
        tree = apply_shrinkage(tree, lr)
        trees_k.append(tree)
        bnews.append(tree.leaf_value[row_leaf])
    trees = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees_k)
    return trees, jnp.stack(bnews, axis=1)


@functools.partial(jax.jit, static_argnames=("L", "num_bins"))
def _dart_iter_margin(trees_st, bins, L: int, efb=None,
                      num_bins: int = 256):
    """(n, K) margins of one dart iteration's K stacked trees (``efb``:
    bins hold bundle columns; the walk decodes per level)."""
    if efb is None:
        return jax.vmap(
            lambda t: predict_tree_binned(t, bins, L))(trees_st).T
    return jax.vmap(
        lambda t: predict_tree_binned_efb(t, bins, L, efb, num_bins)
    )(trees_st).T


@functools.partial(jax.jit,
                   static_argnames=("obj", "cfg", "lr", "k1", "k2", "amp",
                                    "has_val", "K"),
                   donate_argnums=(1, 7))
def _boost_scan_goss(bins, scores, labels, weights, keys, fi_stack,
                     val_bins, val_scores, obj: Objective, cfg: GrowerConfig,
                     lr: float, k1: int, k2: int, amp: float, has_val: bool,
                     K: int = 1, efb=None):
    """GOSS chunk: each iteration grows its tree on the top-|g·h| rows plus
    an amplified random sample of the rest (Ke et al. 2017; LightGBM
    boosting=goss).  Histogram work shrinks to ``(topRate + otherRate)·n``
    rows via a gather; scores still update for every row via a full binned
    traversal of the new tree.

    ``K > 1`` (multiclass): rows rank by the class-summed influence
    Σ_k |g_k·h_k| and ONE sample feeds all K per-class trees, matching
    LightGBM's multiclass GOSS (one sampling pass per iteration)."""
    # pre-gather checks: GOSS hands _grow_tree_impl only the influence
    # SAMPLE, but predict_tree_binned walks the FULL matrix every
    # iteration, and the argsort pushes NaN rows to the sample's tail —
    # so both invariants must look at the unsampled inputs here
    if cfg.debug_checks:
        _debug.check_bins_in_range(bins, cfg.num_bins)

    def train_pred(tree):
        # scores update walks the TRAINING matrix; under EFB it holds
        # bundle columns, so the walk decodes per level (validation
        # matrices are never bundled and keep the plain walk)
        return predict_tree_binned_any(tree, bins, cfg.num_leaves,
                                       efb, cfg.num_bins)

    def body(carry, xs):
        scores, val_scores = carry
        key, fi = xs
        with jax.named_scope("gradients"):
            g, h = obj.grad_hess(scores, labels, weights)
        if cfg.debug_checks:
            _debug.check_finite("gradients/hessians", g, h)
        n = g.shape[0]
        infl = (jnp.abs(g * h) if K == 1
                else jnp.sum(jnp.abs(g * h), axis=1))
        rank = jnp.argsort(-infl)                    # descending influence
        top_idx = rank[:k1]
        rest = rank[k1:]
        rk = jax.random.uniform(key, (n - k1,))
        other_idx = jnp.take(rest, jnp.argsort(rk)[:k2])
        idx = jnp.concatenate([top_idx, other_idx])
        amp_vec = jnp.concatenate([
            jnp.ones(k1, jnp.float32), jnp.full(k2, amp, jnp.float32)])
        bins_g = jnp.take(bins, idx, axis=0)
        if K == 1:
            gh = jnp.stack([jnp.take(g, idx) * amp_vec,
                            jnp.take(h, idx) * amp_vec,
                            jnp.ones(k1 + k2, jnp.float32)], axis=1)
            tree, _ = _grow_tree_impl(bins_g, gh, fi, cfg, efb)
            with jax.named_scope("score_update"):
                scores = scores + lr * train_pred(tree)
            trees = apply_shrinkage(tree, lr)
            if has_val:
                val_scores = val_scores + predict_tree_binned(
                    trees, val_bins, cfg.num_leaves)
        else:
            trees_k = []
            for k in range(K):
                gh = jnp.stack([jnp.take(g[:, k], idx) * amp_vec,
                                jnp.take(h[:, k], idx) * amp_vec,
                                jnp.ones(k1 + k2, jnp.float32)], axis=1)
                tree, _ = _grow_tree_impl(bins_g, gh, fi, cfg, efb)
                with jax.named_scope("score_update"):
                    scores = scores.at[:, k].add(lr * train_pred(tree))
                tree = apply_shrinkage(tree, lr)
                if has_val:
                    val_scores = val_scores.at[:, k].add(
                        predict_tree_binned(tree, val_bins,
                                            cfg.num_leaves))
                trees_k.append(tree)
            trees = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *trees_k)
        out_val = val_scores if has_val else _dummy_val(K)
        return (scores, val_scores), (trees, out_val)

    (scores, val_scores), (trees, val_hist) = jax.lax.scan(
        body, (scores, val_scores), (keys, fi_stack))
    if K > 1:
        trees = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), trees)
    return trees, scores, val_scores, val_hist


@functools.partial(jax.jit,
                   static_argnames=("obj", "cfg", "lr", "K", "has_val",
                                    "rf"),
                   donate_argnums=(1, 7))
def _boost_scan_multi(bins, scores, labels, weights, bag_masks, fi_stack,
                      val_bins, val_scores, obj: Objective,
                      cfg: GrowerConfig, lr: float, K: int, has_val: bool,
                      efb=None, rf: bool = False):
    """Multiclass chunk: grad/hess computed ONCE per iteration for all K
    trees (LightGBM softmax semantics), then K grow steps consume the fixed
    gradients.  Emits trees flattened to (C*K, ...), iteration-major,
    class-minor — the order the model file expects.

    ``rf``: random-forest mode — every tree fits the gradient at the
    CONSTANT init scores, unshrunk (per-class averaging at export)."""
    binsT = bins.T   # fit-invariant; hoisted out of the scan (PERF.md r4)

    def body(carry, xs):
        scores, val_scores = carry
        bag, fi = xs
        bag = jnp.broadcast_to(bag, (scores.shape[0],))
        with jax.named_scope("gradients"):
            g, h = obj.grad_hess(scores, labels, weights)
        trees_k = []
        for k in range(K):
            gh = jnp.stack([g[:, k] * bag, h[:, k] * bag, bag], axis=1)
            tree, row_leaf = _grow_tree_impl(bins, gh, fi, cfg, efb,
                                             binsT=binsT)
            if not rf:
                with jax.named_scope("score_update"):
                    scores = scores.at[:, k].add(
                        lr * tree.leaf_value[row_leaf])
                tree = apply_shrinkage(tree, lr)
            if has_val:
                val_scores = val_scores.at[:, k].add(predict_tree_binned(
                    tree, val_bins, cfg.num_leaves))
            trees_k.append(tree)
        trees = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees_k)
        out_val = val_scores if has_val else _dummy_val(K)
        return (scores, val_scores), (trees, out_val)

    (scores, val_scores), (trees, val_hist) = jax.lax.scan(
        body, (scores, val_scores), (bag_masks, fi_stack))
    trees = jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), trees)
    return trees, scores, val_scores, val_hist


@jax.jit
def _pack_trees_stacked(stacked: TreeArrays) -> jnp.ndarray:
    """Flatten stacked (T, ...) TreeArrays into one (T, P) f32 buffer.

    A small device→host transfer costs its round trip whatever its
    size, so the whole forest crosses in ONE transfer instead of 12 per
    tree.  int fields fit f32 exactly (node/feature/bin ids ≪ 2^24);
    row counts are int32 and pass 2^24 on a large table, so they cross
    like the bitset words, as two 16-bit halves.
    Packing happens *inside* jit so trees produced under shard_map (multi-
    device, replicated) are legal inputs — XLA inserts the resharding.
    """
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    T = stacked.node_cat_bits.shape[0]
    bits = stacked.node_cat_bits.reshape(T, -1)
    # u32 words don't fit f32 exactly; ship two u16 halves (both exact)
    bits_lo = f32(bits & jnp.uint32(0xFFFF))
    bits_hi = f32(bits >> jnp.uint32(16))
    node_count = stacked.node_count.astype(jnp.int32)
    leaf_count = stacked.leaf_count.astype(jnp.int32)
    return jnp.concatenate([
        f32(stacked.num_leaves)[:, None],
        f32(stacked.node_feat), f32(stacked.node_bin),
        f32(stacked.node_left), f32(stacked.node_right),
        stacked.node_gain, stacked.node_value,
        stacked.node_weight, f32(node_count & 0xFFFF),
        f32(stacked.node_is_cat),
        stacked.leaf_value, stacked.leaf_weight, f32(leaf_count & 0xFFFF),
        bits_lo, bits_hi, f32(node_count >> 16), f32(leaf_count >> 16),
    ], axis=1)


def _fetch_host_trees(chunks: List[TreeArrays], num_leaves: int
                      ) -> Tuple[List[TreeArrays], np.ndarray]:
    """Batched device→host transfers → per-tree host ``TreeArrays`` + leaf
    counts (``host_tree_from_arrays`` makes HostTrees of them, in
    ``train.finalize``).

    ``chunks``: stacked (C_i, ...) TreeArrays pytrees as produced by the
    scan steps — one packed transfer per chunk (typically one per fit)."""
    with get_profiler().region("train.fetch_trees", bytes=0) as sp:
        if not chunks:
            return [], np.zeros(0, np.int64)
        # the device's pack and the transfer; the unpacking below is
        # this span's self time
        with get_profiler().region("train.fetch_wait"):
            packed = np.concatenate(
                [np.asarray(_pack_trees_stacked(c)) for c in chunks])
        sp["bytes"] = int(packed.nbytes)
        L, m = num_leaves, num_leaves - 1
        W = chunks[0].node_cat_bits.shape[-1]
        offs = np.cumsum([1] + [m] * 9 + [L] * 3 + [m * W] * 2 + [m, L])
        cols = [packed[:, a:b] for a, b in zip([0] + list(offs), offs)]
        nls = cols[0][:, 0].astype(np.int64)
        out = []
        for i in range(packed.shape[0]):
            bits = (cols[13][i].astype(np.uint32)
                    | (cols[14][i].astype(np.uint32) << np.uint32(16)))
            tree = TreeArrays(
                node_feat=cols[1][i].astype(np.int32),
                node_bin=cols[2][i].astype(np.int32),
                node_left=cols[3][i].astype(np.int32),
                node_right=cols[4][i].astype(np.int32),
                node_gain=cols[5][i], node_value=cols[6][i],
                node_weight=cols[7][i],
                node_count=(cols[8][i].astype(np.int64)
                            + (cols[15][i].astype(np.int64) << 16)),
                node_is_cat=cols[9][i].astype(np.int32),
                node_cat_bits=bits.reshape(m, W),
                leaf_value=cols[10][i], leaf_weight=cols[11][i],
                leaf_count=(cols[12][i].astype(np.int64)
                            + (cols[16][i].astype(np.int64) << 16)),
                num_leaves=nls[i])
            out.append(tree)
        return out, nls


def _truncate_no_growth(host_trees: List[HostTree], nls: np.ndarray, K: int,
                        stop_iter: int, verbosity: int
                        ) -> Tuple[List[HostTree], int]:
    """Reproduce LightGBM's stop-at-first-stump-iteration semantics post hoc
    (the loop no longer syncs per iteration to learn leaf counts live)."""
    grew = (nls.reshape(-1, K) > 1).any(axis=1)
    if grew.all():
        return host_trees, stop_iter
    first = int(np.argmax(~grew))
    if verbosity > 0:
        log.info("No further splits with positive gain; stopping at "
                 "iteration %d", first)
    return host_trees[:(first + 1) * K], min(stop_iter, first)



def _efb_dev_from_host(efb_host):
    """Upload the six EFB map arrays (dtypes pinned so a replay re-upload
    never retraces)."""
    return EFBArrays(
        gather_idx=jnp.asarray(efb_host[0], jnp.int32),
        valid=jnp.asarray(efb_host[1]),
        bundle_of=jnp.asarray(efb_host[2]),
        off_of=jnp.asarray(efb_host[3]),
        nb_of=jnp.asarray(efb_host[4]),
        default_of=jnp.asarray(efb_host[5]))


#: set to "0" to skip fit-time reference-profile capture (ISSUE 15) —
#: e.g. a bench run that fits thousands of throwaway models
REF_PROFILE_ENV = "MMLSPARK_TPU_REF_PROFILE"

#: rows fed to the margin sketch's representative-predict pass; the
#: per-feature sketches always count the FULL binned matrix (bincount
#: is cheap), only the margin baseline subsamples
_REF_PROFILE_MARGIN_ROWS = 32768


def _bin_representatives(mapper: BinMapper) -> List[np.ndarray]:
    """Per-feature lookup ``fine bin index -> representative raw
    value``.  Tree thresholds are bin upper bounds, so every raw value
    in fine bin ``b`` falls on the same side of every split as the
    bound ``ub[b]`` — predicting on the representatives routes to
    EXACTLY the leaves the true raw rows would (missing bin → NaN,
    which the forest walk routes via default direction; categorical
    bins → their raw category value)."""
    reps: List[np.ndarray] = []
    for j in range(mapper.num_features):
        rep = np.full(mapper.num_total_bins, np.nan, np.float64)
        if mapper.is_categorical(j):
            vals = mapper.cat_values[j]
            rep[:len(vals)] = vals.astype(np.float64)
        else:
            ub = mapper.upper_bounds[j]
            if len(ub):
                rep[:len(ub)] = ub
                rep[len(ub)] = ub[-1] + max(1.0, abs(float(ub[-1])))
            else:
                rep[0] = 0.0
        reps.append(rep)
    return reps


def _bin_space_forest(booster: Booster, mapper: BinMapper) -> Booster:
    """``booster`` with every categorical split's bitset over BIN indices
    (one word per 32 bins) in place of raw category values, for walking
    rows whose categorical columns hold their bin.  The same routing as the
    raw-value forest on the bins' own values, but a forest on a column of
    ten million values holds tens of millions of raw-value words, which
    the margin pass below would upload and leave on the device, as much
    as the seed's trees make it (PERF.md Findings, PR 27).  A forest
    without categorical splits is returned as it is."""
    import copy
    import dataclasses
    if not any(t.num_cat for t in booster.trees):
        return booster
    W = (mapper.num_total_bins + 31) // 32
    trees = []
    for t in booster.trees:
        if not t.num_cat:
            trees.append(t)
            continue
        words = np.zeros(t.num_cat * W, np.uint32)
        for i in np.flatnonzero(t.decision_type & 1):
            k = int(t.threshold[i])
            raw = t.cat_threshold[t.cat_boundaries[k]:t.cat_boundaries[k + 1]]
            cats = np.asarray(mapper.cat_values[int(t.split_feature[i])],
                              np.int64)
            at = np.minimum(cats >> 5, len(raw) - 1)
            left = np.flatnonzero(
                ((cats >> 5) < len(raw))
                & ((raw[at] >> (cats & 31).astype(np.uint32)) & 1 > 0))
            np.bitwise_or.at(words, k * W + (left >> 5),
                             np.uint32(1) << (left & 31).astype(np.uint32))
        trees.append(dataclasses.replace(
            t, cat_threshold=words,
            cat_boundaries=np.arange(t.num_cat + 1, dtype=np.int32) * W))
    out = copy.copy(booster)
    out.trees = trees
    out.invalidate_cache()
    return out


@functools.partial(jax.jit, static_argnames=("num_bins", "mesh"))
def _table_bin_counts(bins_d, num_bins: int, mesh=None):
    """Rows per (feature, fine bin) of the binned table a fit left on
    the device, ``(f, num_bins)`` int32 and exact
    (:func:`mmlspark_tpu.ops.histogram.bin_counts`): the reference
    profile's counts without a pass over the host's table.  On a mesh
    (``prepare_arrays``' layout) every chip counts its own rows and one
    ``psum`` of the table adds them; pad rows and pad features are in the
    counts, for the caller to take out."""
    if mesh is None:
        return bin_counts(bins_d, num_bins)
    from jax.sharding import PartitionSpec as P
    from ..core.mesh import DATA_AXIS
    from .distributed import _f_ax
    return jax.shard_map(
        lambda b: jax.lax.psum(bin_counts(b, num_bins), DATA_AXIS),
        mesh=mesh, in_specs=P(DATA_AXIS, _f_ax(mesh)),
        out_specs=P(_f_ax(mesh), None), check_vma=False)(bins_d)


def _representative_table(mapper: BinMapper) -> np.ndarray:
    """``(f, num_total_bins)`` float32 of :func:`_bin_representatives`,
    a categorical column's row holding the bin's own index for
    :func:`_bin_space_forest` (its trailing bin, every other value, stays
    NaN and goes right)."""
    table = np.stack(_bin_representatives(mapper))
    if mapper.has_categorical:
        cat = np.flatnonzero(mapper.categorical)
        table[cat] = np.where(np.isnan(table[cat]), np.nan,
                              np.arange(table.shape[1], dtype=np.float64))
    return table.astype(np.float32)


@functools.partial(jax.jit, static_argnames=("missing_bin",))
def _decode_bundled_rows(sample_b, efb, missing_bin: int):
    """Bundled rows ``(r, G)`` to their ``(r, f)`` bins: the decode of
    ``grower.efb_feature_column`` for every feature at once."""
    raw = (jnp.take(sample_b, efb.bundle_of, axis=1).astype(jnp.int32)
           - efb.off_of[None, :])
    inr = (raw >= 0) & (raw <= efb.nb_of[None, :])
    return jnp.where(inr, jnp.where(raw == efb.nb_of[None, :], missing_bin,
                                    raw),
                     efb.default_of[None, :]).astype(sample_b.dtype)


@jax.jit
def _representative_rows(sample, table):
    """``out[r, j] = table[j, sample[r, j]]``: binned rows to their
    float32 representatives where the forest walk reads them.  A compare
    and select against each bin, summed (one term is not nought; a NaN
    is picked, never multiplied): XLA fuses it, and worked feature-major,
    as the TPU lays both tables out, it needs no temporary."""
    bin_ids = jnp.arange(table.shape[1], dtype=sample.dtype)
    hit = sample.T[:, :, None] == bin_ids
    return jnp.sum(jnp.where(hit, table[:, None, :], 0.0), axis=-1).T


def _capture_reference_profile(booster: Booster, bins, mapper,
                               feature_names,
                               device_table: Optional[dict] = None) -> None:
    """Attach the fit-time data-quality baseline (ISSUE 15): per-feature
    sketches over the full binned training matrix plus a
    prediction-margin sketch from a bin-representative predict pass.
    Advisory — a capture failure logs and leaves
    ``booster.reference_profile`` None (drift monitoring off), it never
    fails the fit.  Charged to every fit: the ``train.reference_profile``
    span, ``rows`` being the rows sketched and ``counts`` where they were
    counted: ``device`` when the fit left ``device_table`` (see
    :func:`_train_impl`), whose table is counted there and released,
    ``host`` when ``bins`` is counted column by column.  A bundled fit
    (``bins`` a ``BundledTable``) counts its ``(n, G)`` table, expands
    the counts to features by the map ``grower._efb_expand`` gathers by,
    and decodes the sampled rows as ``grower.efb_feature_column`` does:
    the same profile as the unbundled table's.

    Its children say where the capture's time goes.
    ``train.refprofile_counts`` (``where`` = the parent's ``counts``): the
    count pass from its dispatch to the counts on the host, the wait for
    the device included, opened twice on the device path because the
    sampled rows are taken in between, while the device counts; on the
    host path the column passes.  ``train.refprofile_sample``: the
    sampled rows' take from the host table, then their upload, their
    decode where bundled, and the look-up of their representatives.
    ``train.refprofile_margins``: the bin-space forest's margins of those
    rows, to the host.  ``train.refprofile_rollup`` (``features``):
    ``build_reference_profile``, counts to sketches."""
    if os.environ.get(REF_PROFILE_ENV, "1") == "0" or mapper is None:
        return
    with get_profiler().region("train.reference_profile",
                               rows=0, counts="host") as sp:
        try:
            from ..core.sketch import build_reference_profile
            from .efb import BundledTable, decode_rows, expand_counts
            bundled = bins if isinstance(bins, BundledTable) else None
            if bundled is None:
                if isinstance(bins, (list, tuple)):
                    bins = np.concatenate([np.asarray(b) for b in bins],
                                          axis=0)
                bins = np.asarray(bins)
            if len(bins.shape) != 2 or bins.shape[1] != mapper.num_features:
                return
            n, f = bins.shape
            sp["rows"] = int(n)
            # the host rows as the fit uploaded them: (n, G) when bundled
            rows_h = bins if bundled is None else bundled.table
            prof = get_profiler()
            counts_d = None
            if device_table:
                sp["counts"] = "device"
                with prof.region("train.refprofile_counts", where="device"):
                    counts_d = _table_bin_counts(
                        device_table.pop("bins"), mapper.num_total_bins,
                        device_table.get("mesh"))
            # the sampled rows are taken while the device counts
            with prof.region("train.refprofile_sample"):
                sample = rows_h
                if n > _REF_PROFILE_MARGIN_ROWS:
                    idx = np.random.default_rng(0).choice(
                        n, size=_REF_PROFILE_MARGIN_ROWS, replace=False)
                    idx.sort()
                    sample = rows_h[idx]
            fine_counts = None
            if counts_d is not None or bundled is not None:
                with prof.region("train.refprofile_counts",
                                 where=sp["counts"]):
                    if counts_d is not None:
                        # the wait is the capture's; with it the fit's
                        # table leaves the device, before the sampled
                        # rows arrive
                        fine_counts = np.asarray(counts_d)[
                            :rows_h.shape[1]].astype(np.int64)
                        fine_counts[:, 0] -= device_table["pad_rows"]
                    else:
                        fine_counts = np.stack([
                            np.bincount(rows_h[:, g],
                                        minlength=mapper.num_total_bins)
                            for g in range(rows_h.shape[1])])
                    if bundled is not None:
                        fine_counts = expand_counts(fine_counts,
                                                    bundled.maps(), n)
            with prof.region("train.refprofile_sample"):
                table = _representative_table(mapper)
                if jax.default_backend() == "cpu":
                    # predict_margin walks numpy rows natively there
                    if bundled is not None:
                        sample = decode_rows(sample, bundled.maps(),
                                             mapper.missing_bin)
                    Xr = table[np.arange(f), sample]
                else:
                    sample_d = jnp.asarray(sample, mapper.bin_dtype)
                    if bundled is not None:
                        sample_d = _decode_bundled_rows(
                            sample_d, (device_table or {}).get("efb")
                            or _efb_dev_from_host(bundled.maps()),
                            mapper.missing_bin)
                    Xr = _representative_rows(sample_d, jnp.asarray(table))
            with prof.region("train.refprofile_margins"):
                margins = np.asarray(
                    _bin_space_forest(booster, mapper).predict_margin(Xr))
            # ``train.refprofile_rollup`` and, where nothing was counted
            # yet, the host's column passes (``train.refprofile_counts``)
            booster.reference_profile = build_reference_profile(
                bins, mapper, margins, feature_names=feature_names,
                meta={"trees": len(booster.trees),
                      "num_class": booster.num_class,
                      "fit_span": _tm.current_fit_span()},
                fine_counts=fine_counts)
            train_stats.incr("ref_profiles")
            if counts_d is not None:
                train_stats.incr("ref_profiles_device")
        except Exception:  # noqa: BLE001 - the profile is advisory
            log.exception("reference-profile capture failed; drift "
                          "monitoring will be unavailable for this model")


def train(*args, **kwargs) -> Booster:
    """Train a forest — the public entrypoint (see :func:`_train_impl`
    for the full parameter contract).

    Wraps the fit in a telemetry *fit span* (ISSUE 5): a span id is
    minted per fit and published process-globally
    (:func:`mmlspark_tpu.core.telemetry.current_fit_span`) so the
    checkpoint writer stamps it into snapshot meta and the elastic
    heartbeat stamps it into lease files; ``fit_begin`` / ``fit_end``
    (or ``fit_failed``) journal events bracket every ``boost_chunk`` /
    ``ckpt_*`` event emitted in between, which is what
    ``tools/trace_report.py`` reconstructs into a fit timeline.  A
    nested call (the sharded trainer's small-fit serial fallback) joins
    the enclosing span instead of minting its own."""
    nested = _tm.current_fit_span() is not None
    if nested:
        return _train_impl(*args, **kwargs)

    def _arg(i: int, name: str):
        return args[i] if len(args) > i else kwargs.get(name)

    span = _tm.new_trace_id()
    _tm.set_current_fit_span(span)
    t0 = time.perf_counter()
    _tm.get_journal().emit("fit_begin", fit=span)
    try:
        # the root of the fit's spans (docs/observability.md): every
        # phase below is its child, and what no child covers is its
        # self time
        with get_profiler().region("train.fit") as sp:
            device_table: dict = {}
            try:
                booster = _train_impl(*args, device_table=device_table,
                                      **kwargs)
            except BaseException as e:
                _tm.get_journal().emit("fit_failed", fit=span,
                                       error=type(e).__name__)
                if not isinstance(e, KeyboardInterrupt):
                    # self-contained post-mortem: journal tail
                    # (boost_chunk / ckpt_* history), metrics and thread
                    # stacks at the moment the fit died — the flight
                    # record IS the crash report
                    _tm.record_flight("fit_failed",
                                      {"fit": span, "error": repr(e)})
                raise
            bins, mesh = _arg(0, "bins"), _arg(13, "mesh")
            _capture_reference_profile(booster, bins, _arg(3, "mapper"),
                                       _arg(6, "feature_names"),
                                       device_table)
            with get_profiler().region("train.fit_attrs"):
                attrs = _fit_attrs(booster, bins, mesh, _arg(3, "mapper"))
            sp.update(attrs)
            _tm.get_journal().emit(
                "fit_end", fit=span,
                dur_s=round(time.perf_counter() - t0, 3),
                trees=len(booster.trees))
    finally:
        _tm.set_current_fit_span(None)
    return booster


def _segment_walk_attrs(trees, n_rows: int, min_bucket: int) -> dict:
    """``seg_rows``, ``seg_rows_walked`` and ``seg_chunked_nodes`` of a
    one-device fit (``grower.segment_walk_stats``), from the host trees'
    node counts: every split partitioned its node's rows and
    histogrammed its smaller child's (counts are of the rows in the bag,
    so under bagging or goss they understate the partition's)."""
    parents, smaller = [], []
    for t in trees:
        left, right = (
            np.where(child >= 0, t.internal_count[np.maximum(child, 0)],
                     t.leaf_count[np.maximum(~child, 0)])
            for child in (t.left_child, t.right_child))
        parents.append(t.internal_count)
        smaller.append(np.minimum(left, right))
    return segment_walk_stats(
        np.concatenate(parents) if parents else [],
        np.concatenate(smaller) if smaller else [], n_rows,
        GrowerConfig(min_bucket=min_bucket))


def _fit_attrs(booster: Booster, bins, mesh, mapper) -> dict:
    """What the ``train.fit`` span says of its fit: the trees returned,
    the table's shape, the devices it ran on, and the collectives the
    grower's schedule counts for those trees (``last_fit_info``, per
    tree, times the trees), and which build of the histogram its
    programs compiled at how many of a tree's call sites.  A fit on a
    table with categorical columns also says how many they are, how many
    of its trees' internal nodes are categorical splits, and the u32
    words of their raw-value bitsets; a numeric fit carries none of the
    three.  A fit whose gradient was a ranker's query layout
    (``ranking.LambdarankGrad``) says how many queries and size classes
    it held and, times the trees, the pairs of the queries' exact sizes
    and the pair slots its programs computed.  ``hist_cache_bytes`` is a
    device's per-leaf histogram cache, in feature space whatever the
    table; a fit on a table bundled at binning time (``gbdt/efb.py``)
    also says its features, bundle columns, the bundled table's bytes and
    the rows that lost a value to a conflict (0 at ``maxConflictRate``
    0); any other fit has none of the four.  A one-device fit that
    compacts rows says what its splits asked of the bucket ladders
    (``_segment_walk_attrs``); a mesh fit has none of the three."""
    shards = bins if isinstance(bins, (list, tuple)) else [bins]
    shapes = [np.shape(b) for b in shards if b is not None]
    trees = len(booster.trees)

    def per_tree(key: str) -> int:
        return int(last_fit_info.get(key, 0)) * trees

    attrs = {
        "trees": trees,
        "rows": int(sum(sh[0] for sh in shapes)),
        "features": int(shapes[0][1]) if shapes else 0,
        "devices": int(mesh.devices.size) if mesh is not None else 1,
        "collective_count": per_tree("collective_count_per_tree"),
        "collective_bytes": per_tree("collective_payload_bytes_per_tree"),
        "hist_build": last_fit_info.get("hist_build", ""),
        "hist_build_rungs": last_fit_info.get("hist_build_rungs", ""),
        "hist_cache_bytes": int(last_fit_info.get("hist_cache_bytes", 0)),
    }
    attrs.update({k: int(v) for k, v in last_fit_info.items()
                  if k.startswith("efb_")})
    if "rank_queries" in last_fit_info:
        attrs.update(
            rank_queries=int(last_fit_info["rank_queries"]),
            rank_size_classes=int(last_fit_info["rank_size_classes"]),
            rank_pairs_useful=per_tree("rank_pairs_useful_per_tree"),
            rank_pairs_computed=per_tree("rank_pairs_computed_per_tree"))
    if "seg_min_bucket" in last_fit_info:
        attrs.update(_segment_walk_attrs(
            booster.trees, attrs["rows"],
            int(last_fit_info["seg_min_bucket"])))
    if mapper is not None and mapper.has_categorical:
        attrs.update(
            cat_features=int(mapper.categorical.sum()),
            cat_splits=int(sum(t.num_cat for t in booster.trees)),
            cat_bitset_words=int(sum(len(t.cat_threshold)
                                     for t in booster.trees)))
    return attrs


def train_incremental(bins: np.ndarray, labels: np.ndarray,
                      mapper: BinMapper, *, init_booster: Booster,
                      objective: Objective, params: TrainParams,
                      weights: Optional[np.ndarray] = None,
                      feature_names: Optional[List[str]] = None,
                      callbacks: Optional[List[Callable]] = None
                      ) -> Booster:
    """Continued training straight from pre-binned rows — the
    fit-from-ingest entry (ISSUE 18).

    The streaming ingest retains rows ALREADY binned to the active
    model's ladder, so the raw values are gone; but tree thresholds are
    bin upper bounds, so every raw value in a bin routes through the
    active forest exactly like the bin's representative value
    (:func:`_bin_representatives`) — the init margins computed here are
    bit-identical to what ``base.py`` would compute from the raw rows.
    The new trees boost from those margins and the returned booster is
    ``init_booster.extended(new)``, the same merged-forest contract as
    the estimator's ``initModelPath`` path.

    ``params.checkpoint_dir`` composes: the fingerprint covers
    ``init_scores``, so a fit SIGKILLed mid-boost resumes bit-identical
    from the last durable chunk (the chaos drill's kill point).
    """
    if params.boosting not in ("gbdt", "goss"):
        raise ValueError(
            "incremental training requires boosting gbdt or goss: "
            f"got {params.boosting!r}")
    if init_booster.num_class != objective.num_model_per_iteration:
        raise ValueError(
            f"init model has num_class={init_booster.num_class}, this "
            f"fit trains {objective.num_model_per_iteration}")
    if init_booster.max_feature_idx != mapper.num_features - 1:
        raise ValueError(
            f"init model was trained on "
            f"{init_booster.max_feature_idx + 1} features, the binned "
            f"matrix has {mapper.num_features}")
    bins = np.ascontiguousarray(bins)
    if bins.ndim != 2 or bins.shape[1] != mapper.num_features:
        raise ValueError(
            f"bins shape {bins.shape} does not match the mapper's "
            f"{mapper.num_features} features")
    reps = _bin_representatives(mapper)
    Xr = np.empty(bins.shape, np.float64)
    for j, rep in enumerate(reps):
        Xr[:, j] = rep[bins[:, j].astype(np.int64)]
    margins = np.asarray(init_booster.predict_margin(Xr), np.float64)
    booster = train(bins, labels, weights, mapper, objective, params,
                    feature_names, init_scores=margins,
                    callbacks=callbacks)
    merged = init_booster.extended(booster)
    # the publishable profile must describe the MERGED forest's margins
    # (the canary's drift monitor compares live margins against it)
    _capture_reference_profile(merged, bins, mapper, feature_names)
    return merged


def _train_impl(bins: np.ndarray, labels: np.ndarray,
                weights: Optional[np.ndarray],
          mapper: BinMapper, objective: Objective, params: TrainParams,
          feature_names: Optional[List[str]] = None,
          val_bins: Optional[np.ndarray] = None,
          val_labels: Optional[np.ndarray] = None,
          val_weights: Optional[np.ndarray] = None,
          val_metric: Optional[Callable] = None,
          grad_fn_override=None,
          callbacks: Optional[List[Callable]] = None,
          mesh=None,
          init_scores: Optional[np.ndarray] = None,
          val_init_scores: Optional[np.ndarray] = None,
          ranking_info: Optional[Dict] = None,
          shard_rows: Optional[List[int]] = None,
          device_table: Optional[dict] = None) -> Booster:
    """Train a forest.  ``bins``: (n, f) int32 pre-binned features.

    ``val_init_scores``: per-row margin offsets for the validation set —
    the continued-training (init_model) companion of ``init_scores``, so
    early stopping evaluates the merged model's trajectory.

    ``grad_fn_override``: a ranker's gradient, ``ranking.LambdarankGrad``
    (``ranking.make_lambdarank_grad_fn``): its query layout is uploaded
    (``train.rank_pack``) and handed to the ordinary programs (scan, goss,
    dart) as their ``labels``, with lambdarank as their objective.  A
    bare closure is refused: the compiled programs cannot take one.

    ``callbacks``: each called as ``cb(it, trees_dev)`` with the list of
    on-device ``TreeArrays`` grown so far (fixed-size, shrinkage applied);
    host conversion happens once after the loop, so callbacks that need
    host trees must convert explicitly (and pay the device sync).

    ``mesh``: a ``(data, feature)`` Mesh for distributed training; rows and
    features are padded to the mesh shape and the boost step runs under
    ``shard_map`` with psum histogram allreduce (SURVEY.md §5.8 swap).

    ``bins`` may also be a LIST of per-shard binned matrices (with
    ``labels``/``weights`` lists to match) for multi-host ingestion: each
    data shard's rows go straight to its mesh slice with no global
    materialization (SURVEY.md §7 hard part 4; requires ``mesh``;
    supports validation/early stopping, per-machine bagging, callbacks,
    init scores, goss, rf, dart and lambdarank — for ranking each
    query's rows must live on one shard).

    ``bins`` may be a ``BundledTable`` (``gbdt/efb.py``: the table
    bundled at binning time, with its plan): its ``(n, G)`` table is
    what goes to the device, histograms expand to original features
    through the plan's maps, and nothing is bundled here.

    ``device_table``: out, for :func:`train`'s reference profile.  A fit
    that uploaded one host table (here, and ``_train_distributed``
    through ``prepare_arrays``) leaves the device array under ``bins``,
    its ``pad_rows`` (bin 0 of every column), a bundled table's maps
    under ``efb`` (one device) and, on a mesh, the ``mesh``; any other
    fit leaves it empty.
    """
    if isinstance(bins, (list, tuple)):
        return _train_distributed_sharded(
            bins, labels, weights, mapper, objective, params, mesh,
            feature_names, val_bins=val_bins, val_labels=val_labels,
            val_weights=val_weights, val_metric=val_metric,
            callbacks=callbacks,
            grad_fn_override=grad_fn_override, init_scores=init_scores,
            ranking_info=ranking_info, shard_rows=shard_rows)
    from .ranking import LambdarankGrad
    if grad_fn_override is not None \
            and not isinstance(grad_fn_override, LambdarankGrad):
        raise TypeError(
            "grad_fn_override takes a ranker's gradient "
            "(ranking.make_lambdarank_grad_fn), whose query layout the "
            "compiled programs take as an argument; got "
            f"{type(grad_fn_override).__name__}")
    from .efb import BundledTable, bundling_applies
    bundled = bins if isinstance(bins, BundledTable) else None
    n, f = bins.shape
    # everything before the hand-over to ``train.upload`` /
    # ``prepare_arrays``: weights, the objective's preparation and
    # initial score (passes over the labels), the histogram schedule,
    # the argument checks and the budget guard
    with get_profiler().region("train.prepare", rows=int(n)):
        K = objective.num_model_per_iteration
        rng = np.random.default_rng(params.seed)
        bag_rng = np.random.default_rng(params.bagging_seed)

        w = np.ones(n) if weights is None \
            else np.asarray(weights, np.float64)
        # the objective's passes over the labels (class sums, the base
        # rate): row-length float64 work, the largest part of this span
        # on a long table (PERF.md Findings, PR 35)
        with get_profiler().region("train.label_stats"):
            objective.prepare(np.asarray(labels), w)
            # Per-row init scores (initScoreCol) replace
            # boost_from_average, as in LightGBM; a training-time offset
            # not baked into the model.
            init = objective.init_score(np.asarray(labels), w) \
                if params.boost_from_average and init_scores is None \
                else 0.0

        use_voting = params.parallelism == "voting"
        collective, mesh, coll_downgrade = _resolve_collective_cfg(
            params, mesh, ranking=ranking_info is not None)
        qbits, qmc, qwire, collective, qdown = _resolve_quantized(
            params, n, mesh, collective, ranking=ranking_info is not None)
        cfg = GrowerConfig(
            num_leaves=params.num_leaves, max_depth=params.max_depth,
            num_bins=mapper.num_total_bins, lambda_l1=params.lambda_l1,
            lambda_l2=params.lambda_l2,
            min_data_in_leaf=params.min_data_in_leaf,
            min_sum_hessian_in_leaf=params.min_sum_hessian_in_leaf,
            min_gain_to_split=params.min_gain_to_split,
            hist_method=params.histogram_method,
            collective=collective,
            voting_k=params.top_k if use_voting else 0,
            use_categorical=mapper.has_categorical,
            cat_smooth=params.cat_smooth, cat_l2=params.cat_l2,
            max_cat_threshold=params.max_cat_threshold,
            max_cat_to_onehot=params.max_cat_to_onehot,
            quantized_bits=qbits, quantized_seed=params.seed,
            quantized_max_code=qmc, quantized_wire=qwire,
            debug_checks=_debug.debug_enabled())
        coll_sched = _collective_sched_for(cfg, mesh, n, f)
        hist_sched = _hist_sched_for(cfg, mesh, n)
        _record_fit_resolution(cfg, collective, coll_downgrade, coll_sched,
                               quantized_downgrade=qdown,
                               hist_sched=hist_sched)
        from ..core.mesh import FEATURE_AXIS
        _fs = int(dict(mesh.shape).get(FEATURE_AXIS, 1)) if mesh is not None \
            else 1
        last_fit_info.update(hist_cache_bytes=str(
            cfg.num_leaves * -(-f // _fs) * cfg.num_bins * 3 * 4))
        if mesh is None and cfg.compact_rows:
            # one device: the host knows every segment's rows from the
            # returned trees' node counts (``_segment_walk_attrs``); a
            # mesh's shards each hold their own share of a node
            last_fit_info.update(seg_min_bucket=str(cfg.min_bucket))
        if bundled is not None:
            last_fit_info.update(
                efb_features=str(f), efb_bundles=str(bundled.table.shape[1]),
                efb_table_bytes=str(bundled.table.nbytes),
                efb_conflict_rows=str(bundled.conflict_rows))

        if params.boosting not in ("gbdt", "goss", "dart", "rf"):
            raise NotImplementedError(
                f"boostingType={params.boosting!r} is not supported; "
                "use 'gbdt', 'goss', 'dart' or 'rf'")
        use_goss = params.boosting == "goss"
        use_dart = params.boosting == "dart"
        use_rf = params.boosting == "rf"
        if use_rf:
            if not (params.bagging_freq > 0 and
                    0.0 < params.bagging_fraction < 1.0):
                raise ValueError(
                    "boostingType='rf' requires bagging: set "
                    "baggingFraction in (0,1) and baggingFreq > 0 "
                    "(as in LightGBM)")

        if use_dart:
            if params.early_stopping_round > 0:
                raise NotImplementedError(
                    "boostingType='dart' does not support early stopping "
                    "(dropped-tree rescaling is not invertible by "
                    "truncation); unset earlyStoppingRound")
        if use_goss:
            if params.bagging_freq > 0 and params.bagging_fraction < 1.0:
                raise ValueError("Cannot use bagging in GOSS "
                                 "(as in LightGBM); unset baggingFraction/"
                                 "baggingFreq or use boostingType='gbdt'")
            if not (0.0 < params.top_rate < 1.0 and
                    0.0 < params.other_rate < 1.0) or \
                    params.top_rate + params.other_rate >= 1.0:
                raise ValueError("GOSS needs 0 < topRate < 1, "
                                 "0 < otherRate < 1 and topRate + otherRate "
                                 f"< 1, got {params.top_rate}/"
                                 f"{params.other_rate}")
            k1 = max(1, int(np.ceil(n * params.top_rate)))
            k2 = max(1, int(np.ceil(n * params.other_rate)))
            if k1 + k2 >= n:
                use_goss = False   # rounding on tiny n: nothing to shrink
                if params.verbosity > 0:
                    log.info("GOSS sample covers every row (n=%d); training "
                             "falls back to plain gbdt", n)
            else:
                goss_amp = (1.0 - params.top_rate) / params.other_rate
                goss_keys = jax.random.split(
                    jax.random.PRNGKey(params.bagging_seed),
                    params.num_iterations)

        use_mesh = mesh is not None and int(np.prod(
            [mesh.shape[a] for a in mesh.axis_names])) > 1
        if bundled is not None and not bundling_applies(
                mapper, True, ranker=grad_fn_override is not None
                or ranking_info is not None, mesh=mesh if use_mesh else None,
                voting=use_voting, goss=use_goss, dart=use_dart):
            raise ValueError(
                "this fit cannot take a bundled table (efb.bundling_applies "
                "says where bundles apply): hand it the unbundled bins")
        # scale guard (BASELINE config 5): estimate per-device HBM before the
        # first compile and fail fast with remediation if the fit can't fit
        from .budget import check_fit_budget
        _dn = (int(mesh.shape["data"]) if use_mesh else 1)
        _bagging = params.bagging_freq > 0 and params.bagging_fraction < 1.0
        # model the chunk the loop will ACTUALLY use: with nothing forcing a
        # host sync the whole fit is ONE scan stacking T*K trees on device
        _chunk = params.num_iterations
        if _bagging:
            _chunk = min(_chunk, 64)
        if val_bins is not None:
            _chunk = min(_chunk, 64)
        if callbacks:
            _chunk = min(_chunk, 8)
        if params.fault_tolerant_retries > 0:
            _chunk = min(_chunk, 32)
        if params.checkpoint_dir:
            _chunk = min(_chunk, max(1, params.checkpoint_chunk))
        check_fit_budget(
            n_local=-(-n // _dn), num_features=f,
            num_bundles=(bundled.table.shape[1] if bundled is not None
                         else None),
            num_bins=mapper.num_total_bins, num_leaves=params.num_leaves,
            num_class=K, chunk=_chunk,
            bin_itemsize=np.dtype(mapper.bin_dtype).itemsize,
            bagging=_bagging,
            n_val_local=(-(-val_bins.shape[0] // _dn)
                         if val_bins is not None else 0),
            data_shards=_dn, verbosity=params.verbosity,
            hist_on_chip=hist_sched["fused"] == hist_sched["sites"],
            rank_layout_bytes=(grad_fn_override.nbytes
                               if grad_fn_override is not None else 0))
    if use_mesh:
        if ranking_info is not None:
            if init_scores is not None:
                raise NotImplementedError(
                    "per-row init scores (initScoreCol, or the margins "
                    "of an initModelPath continuation) are not "
                    "supported with a MESH ranking objective — the "
                    "packed-query scan boots from zero like LightGBM's "
                    "lambdarank; continue a ranker serially, or train "
                    "fresh under the mesh")
            if callbacks:
                raise NotImplementedError(
                    "per-iteration callbacks are not supported with "
                    "mesh lambdarank (the ranking scan keeps trees on "
                    "device between chunks); drop the callbacks or "
                    "train without a mesh")
            return _train_distributed_ranking(
                bins, labels, w, mapper, objective, params, cfg, mesh,
                feature_names, init, rng, ranking_info,
                val_bins=val_bins, val_labels=val_labels,
                val_weights=val_weights, val_metric=val_metric)
        if grad_fn_override is not None:
            raise NotImplementedError(
                "custom gradient overrides are not supported with a "
                "mesh (only lambdarank, which provides ranking_info)")
        if use_dart:
            return _train_distributed_dart(
                bins, labels, w, mapper, objective, params, cfg, mesh,
                feature_names, init, rng, bag_rng, init_scores,
                val_bins=val_bins, val_labels=val_labels,
                val_weights=val_weights, val_metric=val_metric,
                callbacks=callbacks)
        return _train_distributed(
            bins, labels, w, mapper, objective, params, cfg, mesh,
            feature_names, init, rng, bag_rng, init_scores,
            val_bins=val_bins, val_labels=val_labels,
            val_weights=val_weights, val_metric=val_metric,
            callbacks=callbacks, val_init_scores=val_init_scores,
            device_table=device_table)

    # Exclusive Feature Bundling: a table bundled at binning time
    # (gbdt/efb.py) goes up as it is, with its plan's maps.  goss/dart
    # score the bundled TRAINING matrix through the EFB-aware walk
    # (predict_tree_binned_efb decodes each level's bundle column back
    # to the node's original feature).
    efb_dev = None
    bins_host_final = bins
    if bundled is not None:
        efb_host = bundled.maps()
        efb_dev = _efb_dev_from_host(efb_host)
        bins_host_final = bundled.table
    with get_profiler().region("train.upload") as sp:
        bins_d = jnp.asarray(bins_host_final, mapper.bin_dtype)
        labels_d = jnp.asarray(labels,
                               jnp.int32 if K > 1 else jnp.float32)
        weights_d = jnp.asarray(w, jnp.float32)
        scores0 = np.full((n, K) if K > 1 else (n,), init, np.float32)
        if init_scores is not None:
            iscores = np.asarray(init_scores, np.float32)
            scores0 = scores0 + (iscores if scores0.ndim == iscores.ndim
                                 else iscores[:, None])
        scores = jnp.asarray(scores0)
        upload_bytes = sp["bytes"] = int(
            bins_d.nbytes + labels_d.nbytes + weights_d.nbytes
            + scores.nbytes)

    # A ranker's gradient rides the ordinary programs: its query layout
    # goes up as their ``labels``, its row multipliers as their
    # ``weights``, and lambdarank is their objective.
    rank = grad_fn_override
    step_obj, step_labels, step_weights = objective, labels_d, weights_d
    if rank is not None:
        with get_profiler().region("train.rank_pack") as sp:
            step_obj = rank.objective
            step_labels, step_weights = rank.upload()
            sp["bytes"] = rank.nbytes
        upload_bytes += rank.nbytes
        lay = rank.layout
        last_fit_info.update(
            rank_queries=str(lay.queries),
            rank_size_classes=str(len(lay.classes)),
            rank_pairs_useful_per_tree=str(lay.pairs_useful),
            rank_pairs_computed_per_tree=str(lay.pairs_computed))

    has_val = val_bins is not None and val_metric is not None
    if has_val:
        val_bins_d = jnp.asarray(val_bins, mapper.bin_dtype)
        vs0 = np.full(
            (val_bins.shape[0], K) if K > 1 else (val_bins.shape[0],),
            init, np.float32)
        if val_init_scores is not None:
            vsc = np.asarray(val_init_scores, np.float32)
            vs0 = vs0 + (vsc if vs0.ndim == vsc.ndim else vsc[:, None])
        val_scores = jnp.asarray(vs0)
        val_labels_np = np.asarray(val_labels)
    else:
        val_bins_d = jnp.zeros((1, f), mapper.bin_dtype)
        val_scores = jnp.zeros((1, K) if K > 1 else (1,), jnp.float32)
    best_metric, best_iter = np.inf, -1

    fi_base = _feat_info_from_mapper(mapper, f)
    T = params.num_iterations
    esr = params.early_stopping_round
    use_bag = params.bagging_freq > 0 and params.bagging_fraction < 1.0
    use_ff = params.feature_fraction < 1.0
    cur_bag = np.ones(n, np.float32)

    def iter_fi(_gi):
        """Per-iteration feature-fraction mask (serial draw order)."""
        if not use_ff:
            return fi_base
        return _draw_feature_fraction(rng, fi_base, f,
                                      params.feature_fraction)

    # Chunking: iterations run on-device in lax.scan chunks; the host only
    # syncs between chunks, where early stopping and callbacks live.  With
    # no per-iteration host decision the whole fit is ONE launch.
    if has_val:
        # bounded regardless of esr: the scan stacks (chunk, n_val[, K])
        # per-iteration val scores, which must not grow with T or esr
        # (best_iter persists across chunks, so stopping stays correct)
        chunk = min(T, max(min(esr, 64), 8) if esr > 0 else 64)
    elif callbacks:
        chunk = min(T, 8)
    else:
        chunk = T
    if use_bag:
        # bag_masks are (chunk, n): bound the chunk so per-fit device
        # memory stays O(n), not O(T*n)
        chunk = min(chunk, 64)
    if params.fault_tolerant_retries > 0:
        # bounded chunks = bounded replay work after a device failure;
        # host copies of the training inputs make full re-upload possible
        # when a failure kills every device buffer
        chunk = min(chunk, 32)
        ft_host = {
            "bins": np.asarray(bins_host_final),
            "labels": np.asarray(labels),
            "w": np.asarray(w),
            "val_bins": np.asarray(val_bins_d),
        }
    ckpt = params.checkpoint_dir
    if ckpt and (use_dart or grad_fn_override is not None):
        log.warning("checkpoint_dir is inert for the dart host loop "
                    "(per-iteration host bookkeeping; no chunk "
                    "boundaries to snapshot) and for a ranker (its query "
                    "layout is not in the resume fingerprint)")
        ckpt = ""
    if ckpt:
        # bounded chunks = bounded lost work after a process death
        chunk = min(chunk, max(1, params.checkpoint_chunk))
        ckpt_fp = _ckpt_fingerprint(n, f, K, params, labels,
                                    bins_host_final, w, init_scores)

    trees_chunks: List[TreeArrays] = []
    stop_iter = T

    if use_dart:
        # Dart (Rashmi & Gilad-Bachrach 2015; LightGBM boosting=dart):
        # each iteration drops a random subset of the ensemble, fits the
        # new tree against the dropped-out scores, then renormalizes —
        # the new tree joins at weight 1/(k+1) and the k dropped trees
        # shrink by k/(k+1), preserving the ensemble total.  Per-tree
        # weights are tracked on host and baked into the exported trees.
        dart_rng = np.random.default_rng(params.drop_seed)
        run_dart = _debug.checked(functools.partial(
            _dart_step, obj=step_obj, cfg=cfg, lr=params.learning_rate,
            K=K, efb=efb_dev))
        binsT_d = jnp.transpose(bins_d)   # fit-invariant, once per fit
        L_steps = params.num_leaves

        def unit_margin(unit, b, efb=None):
            """One dart unit's contribution: a tree (K=1) or the stacked
            K class trees of one iteration (dart drops whole iterations,
            as LightGBM does).  ``efb`` must match THE MATRIX ``b``: the
            training matrix is bundled under EFB, the validation matrix
            never is — callers pass efb_dev only with bins_d."""
            if K == 1:
                return predict_tree_binned_any(unit, b, L_steps, efb,
                                               cfg.num_bins)
            return _dart_iter_margin(unit, b, L_steps, efb=efb,
                                     num_bins=cfg.num_bins)

        bag_state = {"cur": np.ones(n, np.float32)}

        def bag_draw(it):
            if use_bag and it % params.bagging_freq == 0:
                bag_state["cur"] = (
                    bag_rng.random(n) < params.bagging_fraction
                ).astype(np.float32)
            return jnp.asarray(bag_state["cur"])

        def fi_draw(it):
            return jnp.asarray(iter_fi(it))

        def grow_unit(s_minus, bag_mask, fi):
            return run_dart(bins_d, binsT_d, s_minus, step_labels,
                            step_weights, bag_mask, fi)

        val_state = {"scores": val_scores if has_val else None,
                     "best": (np.inf, -1)}

        def val_hook(it, unit, sel, scales_pre, norm):
            if not has_val:
                return
            vs = val_state["scores"]
            if len(sel):
                P_val = scales_pre[sel[0]] * unit_margin(
                    units_ref[sel[0]], val_bins_d)
                for i in sel[1:]:
                    P_val = P_val + scales_pre[i] * unit_margin(
                        units_ref[i], val_bins_d)
                vs = vs - norm * P_val
            vs = vs + norm * unit_margin(unit, val_bins_d)
            val_state["scores"] = vs
            metric = float(val_metric(np.asarray(vs), val_labels_np,
                                      val_weights))
            best, bi = val_state["best"]
            if metric < best - 1e-12:
                val_state["best"] = (metric, it)

        # the hook needs the unit list the loop is building
        units_ref: List[TreeArrays] = []
        units, trees_list, scales, scores = _dart_host_loop(
            T, K, dart_rng, params, scores, bag_draw, fi_draw, grow_unit,
            lambda u: unit_margin(u, bins_d, efb_dev), callbacks,
            val_hook=val_hook if has_val else None, units_out=units_ref)
        if trees_list:
            trees_chunks = [jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *trees_list)]
    else:
        # debug/sanitizer mode (SURVEY.md §5.2): checkified variants raise
        # on OOB indexing or non-finite gradients instead of training
        # silently on garbage; identity wrappers when debug mode is off.
        # Static args bind via partial so checkify only sees array args.
        with get_profiler().region("train.build_step"):
            run_scan = _debug.checked(functools.partial(
                _boost_scan, obj=step_obj, cfg=cfg, lr=params.learning_rate,
                has_val=has_val, rf=use_rf, efb=efb_dev))
            if use_goss:
                run_goss = _debug.checked(functools.partial(
                    _boost_scan_goss, obj=step_obj, cfg=cfg,
                    lr=params.learning_rate, k1=k1, k2=k2, amp=goss_amp,
                    has_val=has_val, K=K, efb=efb_dev))
            if K > 1:
                run_multi = _debug.checked(functools.partial(
                    _boost_scan_multi, obj=objective, cfg=cfg,
                    lr=params.learning_rate, K=K, has_val=has_val,
                    efb=efb_dev, rf=use_rf))
        cb_list: List[TreeArrays] = []
        it = 0
        # what the first chunk's program waits for (``train.upload_wait``):
        # the operands it does not donate, and the bytes that went up
        uploaded = ((bins_d, step_labels, step_weights), upload_bytes)
        if ckpt:
            snap = _ckpt_load(ckpt, ckpt_fp)
            if snap is None:
                # purge any stale snapshot files: the write-once chunk
                # files of an abandoned fit must not be skipped-over by
                # this run's saves and then stitched into ITS meta
                _ckpt_clear(ckpt)
            else:
                train_stats.incr("ckpt_resumed")
                _ckpt_event("ckpt_resumed", it=int(snap["it"]))
                it = snap["it"]
                trees_chunks = list(snap["trees_chunks"])
                scores = jnp.asarray(snap["scores"])
                val_scores = jnp.asarray(snap["val_scores"])
                cur_bag = np.asarray(snap["cur_bag"], np.float32)
                rng.bit_generator.state = snap["rng_state"]
                bag_rng.bit_generator.state = snap["bag_rng_state"]
                best_metric = snap["best_metric"]
                best_iter = snap["best_iter"]
                if callbacks:
                    log.warning("resuming from checkpoint at iteration "
                                "%d: callbacks replay only for the "
                                "remaining iterations", it)
                elif params.verbosity > 0:
                    log.info("resuming from checkpoint at iteration %d",
                             it)
        while it < T:
            C = min(chunk, T - it)
            if use_bag:
                rows = []
                for j in range(C):
                    if (it + j) % params.bagging_freq == 0:
                        cur_bag = (bag_rng.random(n) <
                                   params.bagging_fraction
                                   ).astype(np.float32)
                    rows.append(cur_bag)
                bag_masks = jnp.asarray(np.stack(rows))
            else:
                bag_masks = jnp.ones((C, 1), jnp.float32)
            if use_ff:
                fi_stack = jnp.asarray(
                    np.stack([iter_fi(it + j) for j in range(C)]))
            else:
                fi_stack = jnp.asarray(np.broadcast_to(
                    fi_base, (C,) + fi_base.shape))
            def run_chunk(scores, val_scores):
                if use_goss:
                    return run_goss(
                        bins_d, scores, step_labels, step_weights,
                        goss_keys[it:it + C], fi_stack, val_bins_d,
                        val_scores)
                if K > 1:
                    return run_multi(
                        bins_d, scores, step_labels, step_weights,
                        bag_masks, fi_stack, val_bins_d, val_scores)
                return run_scan(
                    bins_d, scores, step_labels, step_weights, bag_masks,
                    fi_stack, val_bins_d, val_scores)

            t_chunk = time.perf_counter()
            ftr = params.fault_tolerant_retries
            if ftr > 0:
                # chunk-boundary snapshots + replay (SURVEY.md §5.3): a
                # device failure may take EVERY device buffer with
                # it, so a replay re-uploads all chunk inputs from host
                # copies (ft_host snapshot taken before the loop, plus
                # this chunk's already-drawn masks) — the replayed chunk
                # is bit-identical to the one that failed.
                snap = (np.asarray(scores), np.asarray(val_scores))
                bagm_host = np.asarray(bag_masks)
                fi_host = np.asarray(fi_stack)
                for attempt in range(ftr + 1):
                    try:
                        trees_st, scores, val_scores, val_hist = run_chunk(
                            jnp.asarray(snap[0]), jnp.asarray(snap[1]))
                        # materialize: a failure discovered later must not
                        # invalidate this chunk's results
                        jax.block_until_ready(trees_st)
                        break
                    except Exception as e:  # noqa: BLE001 - device loss
                        from jax.experimental import checkify as _ck
                        if isinstance(e, _ck.JaxRuntimeError):
                            raise  # deterministic sanitizer error: a
                            # replay would fail identically
                        if attempt >= ftr:
                            raise
                        if efb_dev is not None:
                            # the EFB maps are device buffers too — dead
                            # after a device loss; re-upload and rebind
                            # the chunk runners that captured them
                            efb_dev = _efb_dev_from_host(efb_host)
                            run_scan = _debug.checked(functools.partial(
                                _boost_scan, obj=step_obj, cfg=cfg,
                                lr=params.learning_rate, has_val=has_val,
                                rf=use_rf, efb=efb_dev))
                            if K > 1:
                                run_multi = _debug.checked(
                                    functools.partial(
                                        _boost_scan_multi, obj=objective,
                                        cfg=cfg, lr=params.learning_rate,
                                        K=K, has_val=has_val,
                                        efb=efb_dev, rf=use_rf))
                        train_stats.incr("chunks_replayed")
                        _ckpt_event("chunk_replayed", it=int(it),
                                    attempt=attempt + 1)
                        log.warning(
                            "chunk at iteration %d failed (attempt %d/%d);"
                            " re-uploading state and replaying",
                            it, attempt + 1, ftr)
                        bins_d = jnp.asarray(ft_host["bins"],
                                             mapper.bin_dtype)
                        if rank is not None:
                            step_labels, step_weights = rank.upload()
                        else:
                            step_labels = jnp.asarray(
                                ft_host["labels"],
                                jnp.int32 if K > 1 else jnp.float32)
                            step_weights = jnp.asarray(ft_host["w"],
                                                       jnp.float32)
                        val_bins_d = jnp.asarray(ft_host["val_bins"],
                                                 mapper.bin_dtype)
                        bag_masks = jnp.asarray(bagm_host)
                        fi_stack = jnp.asarray(fi_host)
                        if use_goss:
                            goss_keys = jax.random.split(
                                jax.random.PRNGKey(params.bagging_seed),
                                params.num_iterations)
            else:
                trees_st, scores, val_scores, val_hist = _dispatch_chunk(
                    run_chunk, scores, val_scores, it, C * K, t_chunk,
                    uploaded=uploaded)
                uploaded = None
            trees_chunks.append(trees_st)
            _monitor_chunk(it, it + C, time.perf_counter() - t_chunk,
                           n, K, cfg.hist_method, objective, scores,
                           labels, w, coll_sched=coll_sched)
            stop = False
            if has_val:
                vh = np.asarray(val_hist)        # (C, n_val[, K])
                for j in range(C):
                    margins = (_rf_margins(init, vh[j], it + j)
                               if use_rf else vh[j])
                    metric = float(val_metric(margins, val_labels_np,
                                              val_weights))
                    gi = it + j
                    if metric < best_metric - 1e-12:
                        best_metric, best_iter = metric, gi
                    elif esr > 0 and gi - best_iter >= esr:
                        if params.verbosity > 0:
                            log.info("Early stopping at iteration %d "
                                     "(best %d, metric %.6f)", gi,
                                     best_iter, best_metric)
                        stop_iter = best_iter + 1
                        stop = True
                        break
            if callbacks:
                upto = stop_iter if stop else it + C
                for j in range(upto - it):
                    for k in range(K):
                        cb_list.append(jax.tree_util.tree_map(
                            lambda a, j=j, k=k: a[j * K + k], trees_st))
                    for cb in callbacks:
                        cb(it + j, cb_list)
            if stop:
                break
            it += C
            if ckpt and it < T:
                # it == T would snapshot state the very next statement
                # clears; a crash in that window just replays the final
                # chunk from the previous boundary
                _ckpt_save(ckpt, ckpt_fp, it, trees_chunks, scores,
                           val_scores, cur_bag, rng, bag_rng,
                           best_metric, best_iter)
        if ckpt:
            _ckpt_clear(ckpt)

    if device_table is not None:
        device_table.update(bins=bins_d, pad_rows=0, efb=efb_dev)
    return _export_booster(trees_chunks, K, stop_iter, init, params,
                           objective, mapper, feature_names, f,
                           dart_scales=scales if use_dart else None,
                           rf=use_rf)


def _train_distributed_sharded(bins_shards, label_shards, weight_shards,
                               mapper, objective, params, mesh,
                               feature_names, val_bins=None, val_labels=None,
                               val_weights=None, val_metric=None,
                               callbacks=None, grad_fn_override=None,
                               init_scores=None, ranking_info=None,
                               shard_rows=None) -> Booster:
    """Multi-host mesh training from per-shard inputs: each data shard's
    rows feed its own mesh slice via ``make_array_from_callback`` — the
    full binned matrix never exists on one host (SURVEY.md §7 hard part
    4; the reference's per-executor Dataset construction).

    Supports the full chunked mesh loop via ``_train_distributed``'s
    ``shard_data`` path: validation/early stopping (the validation set is
    assumed host-small and arrives monolithic), per-machine bagging,
    callbacks (non-ranking), per-shard init scores (non-ranking), goss,
    rf, dart (any mesh layout) and lambdarank (each query pinned to the
    shard holding its rows — ranking.shard_queries_from_shards),
    including dart×ranking (the dart host loop runs on the packed
    per-shard layout; bag masks scatter through the query-pack
    permutation).  Still gated: callbacks/init-scores×ranking and
    custom gradient overrides.
    ``init_scores`` may be a per-shard LIST or one array in
    shard-concatenation order; ``ranking_info['query_ids']`` may be a
    per-shard list or one array in shard-concatenation order."""
    if mesh is None:
        raise ValueError("sharded input requires a mesh (setMesh or "
                         "multi-device default)")
    if grad_fn_override is not None:
        raise NotImplementedError(
            "custom gradient overrides are not supported with sharded "
            "ingestion (the override closes over monolithic rows); "
            "rankers pass structured ranking_info instead")
    if any(b is None for b in bins_shards):
        # multi-controller: each controller passes None for slots other
        # hosts own; shard_rows (tiny global metadata) sizes them, and
        # the 1-D label/weight lists must be COMPLETE on every
        # controller (global objective statistics need them; they are
        # metadata-sized next to bins)
        if shard_rows is None:
            raise ValueError(
                "multi-controller sharded training (None bins slots) "
                "requires shard_rows — the global per-shard row counts")
        if any(y is None for y in label_shards):
            raise ValueError(
                "label_shards must be complete on every controller "
                "(labels are 1-D metadata; allgather them, e.g. "
                "jax.experimental.multihost_utils.process_allgather)")
    K = objective.num_model_per_iteration
    rng = np.random.default_rng(params.seed)
    bag_rng = np.random.default_rng(params.bagging_seed)
    if weight_shards is None:
        weight_shards = [None if y is None else
                         np.ones(len(y), np.float64)
                         for y in label_shards]
    sizes = (list(shard_rows) if shard_rows is not None
             else [b.shape[0] for b in bins_shards])
    if any(w is None for w in weight_shards):
        raise ValueError(
            "weight_shards must be complete on every controller (1-D "
            "metadata, like labels)")
    # objective statistics need the global label/weight vectors — 1-D and
    # tiny relative to bins, which is what must never be concatenated
    y_global = np.concatenate([np.asarray(y) for y in label_shards])
    w_global = np.concatenate([np.asarray(w) for w in weight_shards])
    objective.prepare(y_global, w_global)
    if init_scores is not None:
        if isinstance(init_scores, (list, tuple)):
            init_score_shards = list(init_scores)
        else:
            offs = np.cumsum([0] + sizes)
            init_score_shards = [
                np.asarray(init_scores)[offs[d]:offs[d + 1]]
                for d in range(len(sizes))]
    else:
        init_score_shards = None
    init = objective.init_score(y_global, w_global) \
        if params.boost_from_average and init_scores is None else 0.0

    collective, mesh, coll_downgrade = _resolve_collective_cfg(
        params, mesh, ranking=ranking_info is not None)
    qbits, qmc, qwire, collective, qdown = _resolve_quantized(
        params, sum(sizes), mesh, collective,
        ranking=ranking_info is not None)
    cfg = GrowerConfig(
        num_leaves=params.num_leaves, max_depth=params.max_depth,
        num_bins=mapper.num_total_bins, lambda_l1=params.lambda_l1,
        lambda_l2=params.lambda_l2, min_data_in_leaf=params.min_data_in_leaf,
        min_sum_hessian_in_leaf=params.min_sum_hessian_in_leaf,
        min_gain_to_split=params.min_gain_to_split,
        hist_method=params.histogram_method,
        collective=collective,
        voting_k=params.top_k if params.parallelism == "voting" else 0,
        use_categorical=mapper.has_categorical,
        cat_smooth=params.cat_smooth, cat_l2=params.cat_l2,
        max_cat_threshold=params.max_cat_threshold,
        max_cat_to_onehot=params.max_cat_to_onehot,
        quantized_bits=qbits, quantized_seed=params.seed,
        quantized_max_code=qmc, quantized_wire=qwire,
        debug_checks=_debug.debug_enabled())

    from .budget import check_fit_budget
    f_sh = next(b.shape[1] for b in bins_shards if b is not None)
    hist_sched = hist_build_schedule(cfg, max(sizes))
    _record_fit_resolution(
        cfg, collective, coll_downgrade,
        _collective_sched_for(cfg, mesh, sum(sizes), f_sh),
        quantized_downgrade=qdown, hist_sched=hist_sched)
    _bagging = params.bagging_freq > 0 and params.bagging_fraction < 1.0
    _chunk = params.num_iterations
    if _bagging:
        _chunk = min(_chunk, 64)
    if val_bins is not None:
        _chunk = min(_chunk, 64)
    if callbacks:
        _chunk = min(_chunk, 8)
    if params.fault_tolerant_retries > 0:
        _chunk = min(_chunk, 32)
    if params.checkpoint_dir:
        _chunk = min(_chunk, max(1, params.checkpoint_chunk))
    check_fit_budget(
        n_local=max(sizes), num_features=f_sh,
        num_bins=mapper.num_total_bins, num_leaves=params.num_leaves,
        num_class=K, chunk=_chunk,
        bin_itemsize=np.dtype(mapper.bin_dtype).itemsize,
        bagging=_bagging,
        n_val_local=(-(-val_bins.shape[0] // int(mesh.shape["data"]))
                     if val_bins is not None else 0),
        data_shards=int(mesh.shape["data"]), verbosity=params.verbosity,
        hist_on_chip=hist_sched["fused"] == hist_sched["sites"])
    shard_data = {"bins_shards": list(bins_shards),
                  "label_shards": list(label_shards),
                  "weight_shards": list(weight_shards),
                  "sizes": sizes,
                  "shard_rows": shard_rows,
                  "init_score_shards": init_score_shards}
    if ranking_info is not None:
        if init_score_shards is not None:
            raise NotImplementedError(
                "per-row init scores (initScoreCol, or the margins of "
                "an initModelPath continuation) are not supported with "
                "a mesh ranking objective (the packed-query scan boots "
                "from zero, as LightGBM's lambdarank does)")
        if callbacks:
            raise NotImplementedError(
                "per-iteration callbacks are not supported with mesh "
                "lambdarank (the ranking scan keeps trees on device "
                "between chunks)")
        qids = ranking_info["query_ids"]
        if isinstance(qids, (list, tuple)):
            if any(q is None for q in qids):
                raise ValueError(
                    "qid shards must be complete on every controller "
                    "(1-D metadata, like labels)")
            qid_shards = [np.asarray(q) for q in qids]
        else:
            offs = np.cumsum([0] + sizes)
            qid_shards = [np.asarray(qids)[offs[d]:offs[d + 1]]
                          for d in range(len(sizes))]
        shard_data["qid_shards"] = qid_shards
        return _train_distributed_ranking(
            None, None, None, mapper, objective, params, cfg, mesh,
            feature_names, init, rng, ranking_info,
            val_bins=val_bins, val_labels=val_labels,
            val_weights=val_weights, val_metric=val_metric,
            shard_data=shard_data)
    if params.boosting == "dart":
        return _train_distributed_dart(
            None, None, None, mapper, objective, params, cfg, mesh,
            feature_names, init, rng, bag_rng, None,
            val_bins=val_bins, val_labels=val_labels,
            val_weights=val_weights, val_metric=val_metric,
            callbacks=callbacks, shard_data=shard_data)
    return _train_distributed(
        None, None, None, mapper, objective, params, cfg, mesh,
        feature_names, init, rng, bag_rng,
        val_bins=val_bins, val_labels=val_labels,
        val_weights=val_weights, val_metric=val_metric,
        callbacks=callbacks, shard_data=shard_data)


def _train_distributed_ranking(bins, labels, w, mapper, objective, params,
                               cfg, mesh, feature_names, init, rng,
                               ranking_info, val_bins=None, val_labels=None,
                               val_weights=None, val_metric=None,
                               shard_data=None) -> Booster:
    """Mesh-sharded lambdarank: whole queries are packed per data shard
    (ranking.shard_queries), pairwise gradients stay shard-local, tree
    growth is data-parallel psum — the distributed MSLR configuration
    (SURVEY.md §3.1; BASELINE config 5).

    With ``shard_data`` (sharded ingestion), each query is pinned to the
    shard whose host holds its rows (ranking.shard_queries_from_shards)
    and the packed matrix assembles per slot via
    ``make_array_from_callback`` — no global materialization."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..core.mesh import DATA_AXIS, FEATURE_AXIS, pad_to_multiple
    from .distributed import make_ranking_scan
    from .ranking import shard_queries

    if shard_data is None:
        n, f = bins.shape
    else:
        n = int(sum(shard_data["sizes"]))
        f = next(b.shape[1] for b in shard_data["bins_shards"]
                 if b is not None)
    T = params.num_iterations
    esr = params.early_stopping_round
    use_ff = params.feature_fraction < 1.0
    use_bag = params.bagging_freq > 0 and params.bagging_fraction < 1.0
    use_rf_rk = params.boosting == "rf"
    bag_rng = np.random.default_rng(params.bagging_seed)
    dn = int(mesh.shape[DATA_AXIS])
    fn_shards = int(mesh.shape[FEATURE_AXIS])
    has_val = val_bins is not None and val_metric is not None
    if params.checkpoint_dir:
        log.warning("checkpoint_dir is inert for mesh lambdarank (the "
                    "packed-query scan state is not checkpointed); "
                    "restart a killed ranking fit from initModelPath")

    if shard_data is None:
        perm, real, (qidx, qmask, gains, labq, invmax) = shard_queries(
            np.asarray(labels), ranking_info["query_ids"], dn,
            ranking_info["truncation_level"])
        w_src = np.asarray(w, np.float32)
    else:
        from .ranking import shard_queries_from_shards
        if len(shard_data["bins_shards"]) != dn:
            raise ValueError(
                f"need one shard slot per data-mesh slice: got "
                f"{len(shard_data['bins_shards'])} slots for data={dn}")
        perm, real, (qidx, qmask, gains, labq, invmax), sh_offs = \
            shard_queries_from_shards(
                shard_data["label_shards"], shard_data["qid_shards"],
                ranking_info["truncation_level"])
        w_src = np.concatenate([np.asarray(ws, np.float32)
                                for ws in shard_data["weight_shards"]])
    npk = len(perm)                     # packed rows (D * S)
    valid = perm >= 0
    fp = pad_to_multiple(f, fn_shards) - f
    f_padded = f + fp
    wmul = np.zeros(npk, np.float32)
    wmul[valid] = w_src[perm[valid]]

    shard = lambda a, spec: jax.device_put(  # noqa: E731
        jnp.asarray(a), NamedSharding(mesh, spec))
    if shard_data is None:
        bins_np = np.asarray(bins, mapper.bin_dtype)
        bins_packed = np.zeros((npk, f_padded), mapper.bin_dtype)
        bins_packed[valid, :f] = bins_np[perm[valid]]
        bins_d = shard(bins_packed, P(DATA_AXIS, FEATURE_AXIS))
    else:
        # slot d's packed rows come from ITS host's local binned matrix
        # through the global perm shifted by the shard offset — the full
        # packed matrix never exists on one host (the same discipline as
        # prepare_arrays_from_shards; the callback never touches
        # non-local None slots)
        S_pk = npk // dn
        b_shards = shard_data["bins_shards"]

        def bins_cb(index):
            r0, r1, _ = index[0].indices(npk)
            c0, c1, _ = index[1].indices(f_padded)
            d = r0 // S_pk
            out = np.zeros((r1 - r0, c1 - c0), mapper.bin_dtype)
            p = perm[r0:r1]
            v = p >= 0
            src = b_shards[d]
            ce = min(c1, src.shape[1])
            if ce > c0:
                out[v, :ce - c0] = src[p[v] - sh_offs[d], c0:ce]
            return out

        bins_d = jax.make_array_from_callback(
            (npk, f_padded),
            NamedSharding(mesh, P(DATA_AXIS, FEATURE_AXIS)), bins_cb)
    scores = shard(np.full(npk, init, np.float32), P(DATA_AXIS))
    real_d = shard(real, P(DATA_AXIS))
    wmul_d = shard(wmul, P(DATA_AXIS))
    qidx_d = shard(qidx, P(DATA_AXIS, None, None))
    qmask_d = shard(qmask, P(DATA_AXIS, None, None))
    gains_d = shard(gains, P(DATA_AXIS, None, None))
    labq_d = shard(labq, P(DATA_AXIS, None, None))
    invmax_d = shard(invmax, P(DATA_AXIS, None))

    if has_val:
        nv = val_bins.shape[0]
        vrp = pad_to_multiple(nv, dn) - nv
        vb = np.asarray(val_bins, mapper.bin_dtype)
        if vrp:
            vb = np.concatenate([vb, np.zeros((vrp, f), vb.dtype)], axis=0)
        val_bins_d = shard(vb, P(DATA_AXIS, None))
        val_scores = shard(np.full(nv + vrp, init, np.float32),
                           P(DATA_AXIS))
        val_labels_np = np.asarray(val_labels)
    else:
        val_bins_d = shard(np.zeros((dn, f), mapper.bin_dtype),
                           P(DATA_AXIS, None))
        val_scores = shard(np.zeros(dn, np.float32), P(DATA_AXIS))

    fi_base = np.zeros((f_padded, 3), np.float32)
    fi_base[:f] = _feat_info_from_mapper(mapper, f)

    if params.boosting == "dart":
        from .distributed import (make_ranking_dart_step,
                                  make_tree_predict)
        if fn_shards > 1:
            raise NotImplementedError(
                "boostingType='dart' requires a data-only mesh; use "
                "parallelism='data' / feature=1")
        step_d = make_ranking_dart_step(
            mesh, cfg, params.learning_rate, ranking_info["sigma"],
            ranking_info["truncation_level"])
        pred_d = make_tree_predict(mesh, params.num_leaves)
        binsT_d = jnp.transpose(bins_d)
        dart_rng = np.random.default_rng(params.drop_seed)
        bag_sh = NamedSharding(mesh, P(DATA_AXIS))

        def _upload(mask_n):
            row = np.zeros(npk, np.float32)
            row[valid] = mask_n[perm[valid]]
            return jax.device_put(jnp.asarray(row), bag_sh)

        bag_state = {"dev": _upload(np.ones(n, np.float32))}

        def bag_draw(it):
            # upload only on redraw iterations (use_bag/bag_rng are the
            # function-level stream, shared with the chunked path)
            if use_bag and it % params.bagging_freq == 0:
                bag_state["dev"] = _upload(
                    (bag_rng.random(n) < params.bagging_fraction
                     ).astype(np.float32))
            return bag_state["dev"]

        def fi_draw(_it):
            if use_ff:
                return jnp.asarray(_draw_feature_fraction(
                    rng, fi_base, f, params.feature_fraction))
            return jnp.asarray(fi_base)

        def grow_unit(s_minus, bag, fi):
            return step_d(bins_d, binsT_d, s_minus, real_d, wmul_d,
                          qidx_d, qmask_d, gains_d, labq_d, invmax_d,
                          bag, fi)

        units, trees_list, scales, scores = _dart_host_loop(
            T, 1, dart_rng, params, scores, bag_draw, fi_draw,
            grow_unit, lambda u: pred_d(u, bins_d), None)
        chunks_d = []
        if trees_list:
            chunks_d = [jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *trees_list)]
        return _export_booster(chunks_d, 1, T, init, params, objective,
                               mapper, feature_names, f,
                               dart_scales=scales)

    goss_rk = None
    if params.boosting == "goss":
        # per-shard GOSS over the packed rows (gradients stay full — the
        # pairwise lambdas need whole queries; only tree growth samples)
        if fn_shards > 1:
            raise NotImplementedError(
                "boostingType='goss' requires a data-only mesh; use "
                "parallelism='data' / feature=1")
        s_local = npk // dn
        k1 = max(1, int(np.ceil(s_local * params.top_rate)))
        k2 = max(1, int(np.ceil(s_local * params.other_rate)))
        if k1 + k2 < s_local:
            goss_rk = (k1, k2,
                       (1.0 - params.top_rate) / params.other_rate)
        elif params.verbosity > 0:
            log.info("GOSS sample covers every local row; mesh ranking "
                     "falls back to plain gbdt")
    step = make_ranking_scan(mesh, cfg, params.learning_rate,
                             ranking_info["sigma"],
                             ranking_info["truncation_level"], has_val,
                             goss=goss_rk, bag_sharded=use_bag,
                             rf=use_rf_rk)
    goss_keys_r = jax.random.split(
        jax.random.PRNGKey(params.bagging_seed), T)

    chunk = T
    if use_bag:
        chunk = min(chunk, 64)
    if has_val:
        chunk = min(chunk, max(min(esr, 64), 8) if esr > 0 else 64)
    chunks: List[TreeArrays] = []
    best_metric, best_iter = np.inf, -1
    stop_iter = T
    it = 0
    cur_bag = np.ones(n, np.float32)
    while it < T:
        C = min(chunk, T - it)
        if use_ff:
            fi_stack = jnp.asarray(np.stack([
                _draw_feature_fraction(rng, fi_base, f,
                                       params.feature_fraction)
                for _ in range(C)]))
        else:
            fi_stack = jnp.asarray(np.broadcast_to(fi_base,
                                                   (C,) + fi_base.shape))
        if use_bag:
            rows = []
            for j in range(C):
                if (it + j) % params.bagging_freq == 0:
                    # same stream as a serial run with this baggingSeed,
                    # drawn over ORIGINAL row order then scattered
                    # through the query-pack permutation
                    cur_bag = (bag_rng.random(n) < params.bagging_fraction
                               ).astype(np.float32)
                row = np.zeros(npk, np.float32)
                row[valid] = cur_bag[perm[valid]]
                rows.append(row)
            bags = jax.device_put(
                jnp.asarray(np.stack(rows)),
                NamedSharding(mesh, P(None, DATA_AXIS)))
        else:
            bags = jnp.ones((C, 1), jnp.float32)
        trees_st, scores, val_scores, val_hist = step(
            bins_d, scores, real_d, wmul_d, qidx_d, qmask_d, gains_d,
            labq_d, invmax_d, goss_keys_r[it:it + C], bags, fi_stack,
            val_bins_d, val_scores)
        chunks.append(trees_st)
        stop = False
        if has_val:
            vh = np.asarray(val_hist)[:, :nv]
            for j in range(C):
                margins = (_rf_margins(init, vh[j], it + j)
                           if use_rf_rk else vh[j])
                metric = float(val_metric(margins, val_labels_np,
                                          val_weights))
                gi = it + j
                if metric < best_metric - 1e-12:
                    best_metric, best_iter = metric, gi
                elif esr > 0 and gi - best_iter >= esr:
                    if params.verbosity > 0:
                        log.info("Early stopping at iteration %d "
                                 "(best %d, metric %.6f)", gi, best_iter,
                                 best_metric)
                    stop_iter = best_iter + 1
                    stop = True
                    break
        if stop:
            break
        it += C

    return _export_booster(chunks, 1, stop_iter, init, params, objective,
                           mapper, feature_names, f, rf=use_rf_rk)


def _rf_margins(init, vh_row, tree_idx: int):
    """rf ensemble margins at iteration ``tree_idx``: trees are unshrunk
    raw fits, so the margin is init + running AVERAGE of the tree outputs
    (val_scores start at init, which must not be divided down)."""
    return init + (vh_row - init) / (tree_idx + 1)


def _rf_average_trees(trees, K: int) -> None:
    """Bake the 1/T random-forest averaging weight into the exported
    trees (the model output is the average of the raw trees)."""
    if not trees:
        return
    avg = 1.0 / (len(trees) // K)
    for t in trees:
        t.leaf_value = t.leaf_value * avg
        t.internal_value = t.internal_value * avg
        t.shrinkage = avg


def _feat_info_from_mapper(mapper: BinMapper, f: int) -> np.ndarray:
    """(f, 3) [mask, is_cat, n_value_bins] from the fitted BinMapper."""
    fi = np.zeros((f, 3), np.float32)
    fi[:, 0] = 1.0
    if mapper.has_categorical:
        fi[:, 1] = mapper.categorical.astype(np.float32)
        fi[:, 2] = [mapper.feature_num_bins(j) for j in range(f)]
    return fi


def _finalize_booster(trees, K, init, params, objective, mapper,
                      feature_names, f, stop_iter) -> Booster:
    if trees and params.boost_from_average and init != 0.0:
        # Bake the init score into the first tree per class so the exported
        # model is self-contained, as LightGBM does for boost_from_average.
        for k in range(K):
            t = trees[k]
            t.leaf_value = t.leaf_value + init
            t.internal_value = t.internal_value + init

    # pass_through keys that NAME TrainParams fields were applied by
    # __post_init__ and are already reflected in the typed values above
    # (num_iterations especially records the early-stopped count, which a
    # raw spread would clobber); only engine-unknown keys record verbatim
    extra = {k: v for k, v in params.pass_through.items()
             if not hasattr(params, k)}
    engine_params = {
        "boosting": params.boosting,
        "objective": objective.model_str,
        "num_iterations": str(stop_iter),
        "learning_rate": f"{params.learning_rate:g}",
        "num_leaves": str(params.num_leaves),
        "max_depth": str(params.max_depth),
        "max_bin": str(params.max_bin),
        **extra,
    }
    return Booster(
        trees, num_class=K, objective_str=objective.model_str,
        init_score=0.0, feature_names=feature_names,
        feature_infos=mapper.feature_infos(),
        max_feature_idx=f - 1, params=engine_params)


def _export_booster(chunks, K, stop_iter, init, params, objective, mapper,
                    feature_names, f, dart_scales=None,
                    rf: bool = False) -> Booster:
    """The tail every trainer shares: the device trees to the host
    (``train.fetch_trees``), then ``train.finalize``: cut to
    ``stop_iter`` iterations and to the last iteration that grew
    (``train.host_trees``: the HostTrees of those that stay), bake in
    the dart weights (``dart_scales``: one per ITERATION, shared by its
    K class trees) or the forest average (``rf``), and build the Booster
    (``train.booster``)."""
    trees, nls = _fetch_host_trees(chunks, params.num_leaves)
    with get_profiler().region("train.finalize"):
        trees, nls = trees[:stop_iter * K], nls[:stop_iter * K]
        trees, stop_iter = _truncate_no_growth(trees, nls, K, stop_iter,
                                               params.verbosity)
        # real-valued thresholds and, for categorical splits, bitsets over
        # raw values (``train.cat_bitsets``), for the trees that stay
        with get_profiler().region("train.host_trees", trees=len(trees)):
            trees = [host_tree_from_arrays(t, mapper, mapper.missing_bin)
                     for t in trees]
        if dart_scales is not None:
            for t, s in zip(trees, np.repeat(dart_scales, K)):
                t.leaf_value = t.leaf_value * s
                t.internal_value = t.internal_value * s
                t.shrinkage = s
        elif rf:
            _rf_average_trees(trees, K)
        with get_profiler().region("train.booster"):
            return _finalize_booster(trees, K, init, params, objective,
                                     mapper, feature_names, f, stop_iter)


def _train_distributed_dart(bins, labels, w, mapper, objective, params,
                            cfg, mesh, feature_names, init, rng, bag_rng,
                            init_scores, val_bins=None, val_labels=None,
                            val_weights=None, val_metric=None,
                            callbacks=None, shard_data=None) -> Booster:
    """Dart boosting over the mesh (any layout: the feature-sharded
    score update walks trees via per-level psum).

    Dropout bookkeeping (which trees drop, per-tree scales) is host-side
    RNG over scalars — identical to the serial dart path, so a mesh run
    with the same dropSeed reproduces the serial ensemble structure.  Only
    the array work rides the mesh: the grow step (histogram psums inside)
    via :func:`make_dart_step` and the dropped-tree subtraction via
    :func:`make_tree_predict` on replicated trees over data-sharded rows.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..core.mesh import DATA_AXIS
    from .distributed import (make_dart_step, make_tree_predict,
                              prepare_arrays, prepare_arrays_from_shards)

    K = objective.num_model_per_iteration
    T = params.num_iterations
    use_bag = params.bagging_freq > 0 and params.bagging_fraction < 1.0
    use_ff = params.feature_fraction < 1.0
    if params.fault_tolerant_retries > 0:
        log.warning("faultTolerantRetries is inert for boostingType='dart'"
                    " (per-iteration host loop; no chunk snapshots)")
    if params.checkpoint_dir:
        log.warning("checkpoint_dir is inert for mesh dart (per-iteration"
                    " host loop; no chunk boundaries to snapshot)")

    if shard_data is not None:
        sizes = list(shard_data["sizes"])
        S_sh = max(sizes)
        n = sum(sizes)
        f = next(b.shape[1] for b in shard_data["bins_shards"]
                 if b is not None)
        real_pos = np.concatenate(
            [d * S_sh + np.arange(sz) for d, sz in enumerate(sizes)])
        n_padded = len(sizes) * S_sh
        bins_d, labels_d, w_d, real, scores, rp, fp =             prepare_arrays_from_shards(
                shard_data["bins_shards"], shard_data["label_shards"],
                shard_data["weight_shards"], mesh, K, init,
                mapper.bin_dtype,
                shard_rows=shard_data.get("shard_rows"),
                init_score_shards=shard_data.get("init_score_shards"))
    else:
        n, f = bins.shape
        bins_np = np.asarray(bins, mapper.bin_dtype)
        bins_d, labels_d, w_d, real, scores, rp, fp = prepare_arrays(
            bins_np, np.asarray(labels), np.asarray(w, np.float32), mesh,
            K, init, init_scores)
        real_pos = np.arange(n)
        n_padded = n + rp
    fi_base = np.zeros((f + fp, 3), np.float32)
    fi_base[:f] = _feat_info_from_mapper(mapper, f)
    L = params.num_leaves

    step = make_dart_step(mesh, objective, cfg, params.learning_rate,
                          num_class=K)
    pred = make_tree_predict(mesh, L, num_class=K)
    binsT_d = jnp.transpose(bins_d)   # fit-invariant, once per fit

    # dart rejects early stopping upstream (the dropped-tree rescaling is
    # not invertible by truncation), so a validation set has nothing to
    # decide here — val args are accepted for signature parity and ignored,
    # exactly like the serial dart path's inert metric would be.
    dart_rng = np.random.default_rng(params.drop_seed)
    bag_sh = NamedSharding(mesh, P(DATA_AXIS))

    def upload_bag(mask_n):
        # scatter the n-row mask into the padded global layout (pad rows
        # stay 0; under sharded ingestion real rows sit per-shard slice)
        padded = np.zeros(n_padded, np.float32)
        padded[real_pos] = mask_n
        return jax.device_put(jnp.asarray(padded), bag_sh)

    bag_state = {"dev": upload_bag(np.ones(n, np.float32))}

    def bag_draw(it):
        if use_bag and it % params.bagging_freq == 0:
            bag_state["dev"] = upload_bag(
                (bag_rng.random(n) < params.bagging_fraction
                 ).astype(np.float32))
        return bag_state["dev"]

    def fi_draw(_it):
        if use_ff:
            return jnp.asarray(_draw_feature_fraction(
                rng, fi_base, f, params.feature_fraction))
        return jnp.asarray(fi_base)

    def grow_unit(s_minus, bagm, fi):
        return step(bins_d, binsT_d, s_minus, labels_d, w_d, bagm, fi)

    units, trees_list, scales, scores = _dart_host_loop(
        T, K, dart_rng, params, scores, bag_draw, fi_draw, grow_unit,
        lambda u: pred(u, bins_d), callbacks)

    trees_chunks = []
    if trees_list:
        trees_chunks = [jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *trees_list)]
    return _export_booster(trees_chunks, K, T, init, params, objective,
                           mapper, feature_names, f, dart_scales=scales)


def _train_distributed(bins, labels, w, mapper, objective, params, cfg, mesh,
                       feature_names, init, rng, bag_rng,
                       init_scores=None, val_bins=None, val_labels=None,
                       val_weights=None, val_metric=None,
                       callbacks=None, shard_data=None,
                       val_init_scores=None,
                       device_table: Optional[dict] = None) -> Booster:
    """Distributed boosting: the whole iteration loop is ONE shard_mapped
    ``lax.scan`` launch (no per-iteration host round-trips); with a
    validation set the loop chunks and the host replays per-iteration
    metrics for early stopping, exactly like the serial path.

    ``shard_data``: multi-host ingestion (SURVEY.md §7 hard part 4) — a
    dict of per-shard inputs (``bins_shards``/``label_shards``/
    ``weight_shards``/``sizes``/``init_score_shards``) that feed the mesh
    through ``prepare_arrays_from_shards`` so the global binned matrix is
    never materialized; ``bins`` is then ignored.  Bagging masks scatter
    to each shard's padded slice (per-machine bagging, as distributed
    LightGBM), and the fault-tolerance replay re-runs the same per-shard
    upload."""
    from .distributed import (make_boost_scan, make_goss_scan,
                              make_multiclass_scan, prepare_arrays,
                              prepare_arrays_from_shards)

    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..core.mesh import DATA_AXIS, FEATURE_AXIS, pad_to_multiple

    if shard_data is not None:
        sizes = list(shard_data["sizes"])
        S_sh = max(sizes)
        n = sum(sizes)
        f = next(b.shape[1] for b in shard_data["bins_shards"]
                 if b is not None)
        # positions of real rows inside the (D*S,) padded global layout
        real_pos = np.concatenate(
            [d * S_sh + np.arange(s) for d, s in enumerate(sizes)])
        n_padded = len(sizes) * S_sh
    else:
        n, f = bins.shape
    from .efb import BundledTable
    bundled = None
    if isinstance(bins, BundledTable):      # bundled at binning time
        bundled, bins = bins, bins.table
    K = objective.num_model_per_iteration
    T = params.num_iterations
    esr = params.early_stopping_round
    use_bag = params.bagging_freq > 0 and params.bagging_fraction < 1.0
    use_ff = params.feature_fraction < 1.0
    use_goss_m = params.boosting == "goss"
    use_rf_m = params.boosting == "rf"
    has_val = val_bins is not None and val_metric is not None
    if use_goss_m:
        dn_pre = int(mesh.shape[DATA_AXIS])
        if shard_data is not None:
            # k1/k2 are SPMD trace constants shared by every shard; size
            # them from the MEAN real shard rows.  Pad rows carry zero
            # gradients, so an undersized shard degrades gracefully
            # toward training on all its rows (the tiny-shard fallback),
            # never toward corrupt contributions — but warn when the
            # layout is badly skewed.
            s_local = max(1, int(np.ceil(n / len(sizes))))
            if max(sizes) > 2 * min(sizes) and params.verbosity >= 0:
                log.warning(
                    "GOSS with sharded ingestion: shard sizes %s are "
                    "imbalanced; per-shard sample fractions will differ "
                    "(small shards train closer to full)", sizes)
        else:
            s_local = pad_to_multiple(n, dn_pre) // dn_pre
        k1 = max(1, int(np.ceil(s_local * params.top_rate)))
        k2 = max(1, int(np.ceil(s_local * params.other_rate)))
        if k1 + k2 >= s_local:
            use_goss_m = False   # tiny shards: nothing to shrink
            if params.verbosity > 0:
                log.info("GOSS sample covers every local row; mesh "
                         "training falls back to plain gbdt")
        else:
            goss_amp_m = (1.0 - params.top_rate) / params.other_rate
            goss_keys_m = jax.random.split(
                jax.random.PRNGKey(params.bagging_seed),
                params.num_iterations)
    # Mesh checkpointing (checkpoint_dir is LIVE here, serial-style):
    # the fingerprint is computed from the inputs as given (a bundled
    # table as bundled) plus the mesh topology, so a
    # resume under a different (process count, shard layout) starts
    # fresh instead of scattering shards wrongly.
    ckpt = params.checkpoint_dir
    ckpt_fp = None
    ckpt_local = ""
    if ckpt:
        ckpt_fp = _ckpt_fingerprint_mesh(n, f, K, params, labels, bins,
                                         w, init_scores, mesh,
                                         shard_data)
        ckpt_local = _local_bins_digest(shard_data)

    # EFB under a data mesh: the one plan made at binning time (columns
    # are global), per-shard bundled rows, shard-local expansion before
    # the psum (``efb.bundling_applies``, checked by ``_train_impl``,
    # keeps goss, voting and feature shards out).
    efb_dev_m, efb_host_m = None, None
    # per-tree collective accounting for the chunk monitor: evaluated on
    # the sharded cfg (axis names attach inside the scan builders)
    coll_sched_m = _collective_sched_for(cfg, mesh, n, f)
    if bundled is not None:
        efb_host_m = bundled.maps()
        efb_dev_m = _efb_dev_from_host(efb_host_m)

    def build_step(efb_arg):
        """(Re)build the shard_mapped chunk program — the fault-tolerance
        replay needs fresh EFB closure constants after a device loss."""
        if use_goss_m:
            return make_goss_scan(
                mesh, objective, cfg, params.learning_rate, k1, k2,
                goss_amp_m, has_val, num_class=K)
        if K > 1:
            return make_multiclass_scan(
                mesh, objective, cfg, params.learning_rate, K, use_bag,
                has_val, efb=efb_arg, rf=use_rf_m)
        return make_boost_scan(
            mesh, objective, cfg, params.learning_rate, use_bag, has_val,
            rf=use_rf_m, efb=efb_arg)

    step = build_step(efb_dev_m)
    if shard_data is not None:
        def prep_arrays():
            return prepare_arrays_from_shards(
                shard_data["bins_shards"], shard_data["label_shards"],
                shard_data["weight_shards"], mesh, K, init,
                mapper.bin_dtype,
                shard_rows=shard_data.get("shard_rows"),
                init_score_shards=shard_data.get("init_score_shards"))
    else:
        bins_np = np.asarray(bins, mapper.bin_dtype)
        labels_np = np.asarray(labels)
        w_np = np.asarray(w, np.float32)

        def prep_arrays():
            return prepare_arrays(bins_np, labels_np, w_np, mesh, K, init,
                                  init_scores)
    bins_d, labels_d, w_d, real, scores, rp, fp = prep_arrays()
    # what the first chunk's program waits for (``train.upload_wait``):
    # the sharded operands it does not donate, and ``train.upload``'s bytes
    uploaded = ((bins_d, labels_d, w_d, real),
                sum(a.nbytes for a in (bins_d, labels_d, w_d, real, scores)))
    if shard_data is None:
        real_pos = np.arange(n)
        n_padded = n + rp
    f_padded = f + fp

    # feat_info stays per ORIGINAL feature under EFB (histograms expand
    # back to f features before split finding); fp then pads bundle
    # columns, not features
    fi_base = np.zeros((f if efb_dev_m is not None else f_padded, 3),
                       np.float32)
    fi_base[:f] = _feat_info_from_mapper(mapper, f)

    dn = int(mesh.shape[DATA_AXIS])
    if has_val:
        nv = val_bins.shape[0]
        vrp = pad_to_multiple(nv, dn) - nv
        vb = np.asarray(val_bins, mapper.bin_dtype)
        if vrp:
            vb = np.concatenate(
                [vb, np.zeros((vrp, f), vb.dtype)], axis=0)
        # all features per shard (trees are replicated; each data shard
        # scores its own validation slice)
        val_bins_d = jax.device_put(
            jnp.asarray(vb), NamedSharding(mesh, P(DATA_AXIS, None)))
        vshape = (nv + vrp, K) if K > 1 else (nv + vrp,)
        vspec = P(DATA_AXIS, None) if K > 1 else P(DATA_AXIS)
        vs0 = np.full(vshape, init, np.float32)
        if val_init_scores is not None:
            vsc = np.asarray(val_init_scores, np.float32)
            vsc = vsc if vs0.ndim == vsc.ndim else vsc[:, None]
            vs0[:nv] = vs0[:nv] + vsc
        val_scores = jax.device_put(
            jnp.asarray(vs0), NamedSharding(mesh, vspec))
        val_labels_np = np.asarray(val_labels)
    else:
        val_bins_d = jax.device_put(
            jnp.zeros((dn, f_padded), mapper.bin_dtype),
            NamedSharding(mesh, P(DATA_AXIS, None)))
        val_scores = jax.device_put(
            jnp.zeros((dn, K) if K > 1 else (dn,), jnp.float32),
            NamedSharding(mesh, P(DATA_AXIS, None) if K > 1
                          else P(DATA_AXIS)))

    def iter_fi_dist(_gi):
        if not use_ff:
            return fi_base
        return _draw_feature_fraction(rng, fi_base, f,
                                      params.feature_fraction)

    # Chunk when bagging materializes per-iteration (chunk, n) masks or a
    # validation set stacks per-iteration (chunk, n_val) margins;
    # otherwise the whole fit is one launch with a constant (T, 1) mask
    # (pad rows ride the (n,) `real` mask inside the step).
    chunk = T
    if use_bag:
        chunk = min(chunk, 64)
    if has_val:
        chunk = min(chunk, max(min(esr, 64), 8) if esr > 0 else 64)
    if callbacks:
        # callbacks are a per-iteration host contract: bound the chunk so
        # the host syncs often enough to replay them in order
        chunk = min(chunk, 8)
    ftr = params.fault_tolerant_retries
    if ftr > 0:
        # the mesh gang-restart analog (SURVEY.md §5.3): bounded chunks
        # bound the replay; the replay re-runs prep_arrays(), which closes
        # over the host inputs (monolithic arrays or per-host shards), so
        # a failure that kills every device buffer in the gang re-uploads
        # from the same source — no second host copy.
        chunk = min(chunk, 32)
        ft_vb = vb if has_val else None   # already padded
    if ckpt:
        # bounded chunks = bounded lost work after a controller death
        chunk = min(chunk, max(1, params.checkpoint_chunk))
    cur = np.ones(n, np.float32)
    chunks: List[TreeArrays] = []
    cb_list: List[TreeArrays] = []
    best_metric, best_iter = np.inf, -1
    stop_iter = T
    it = 0
    if ckpt:
        snap = _ckpt_load_mesh(ckpt, ckpt_fp, scores, val_scores,
                               local_digest=ckpt_local)
        if jax.process_count() > 1:
            # the verdict must be UNANIMOUS: the local_digest check (and
            # a torn own-state read) can diverge per process, and a gang
            # where one controller resumes while another starts fresh
            # computes garbage collectives
            from jax.experimental import multihost_utils
            peers_ok = multihost_utils.process_allgather(
                np.asarray([snap is not None], np.int32))
            if snap is not None and not bool(peers_ok.all()):
                log.warning("a peer controller rejected the mesh "
                            "checkpoint; starting fresh gang-wide")
                train_stats.incr("ckpt_discarded")
                _ckpt_event("ckpt_discarded", reason="peer_rejected",
                            mesh=True)
                snap = None
        if snap is None:
            # purge stale generations: write-once chunk files of an
            # abandoned fit must not be skipped-over by this run's
            # saves and then stitched into ITS meta (the verdict is
            # gang-unanimous — see above — so only process 0 deletes,
            # and the barrier keeps peers from racing their first save
            # against the purge)
            if jax.process_index() == 0:
                _ckpt_clear(ckpt)
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils
                multihost_utils.sync_global_devices("ckpt_stale_clear")
        else:
            train_stats.incr("ckpt_resumed")
            _ckpt_event("ckpt_resumed", it=int(snap["it"]), mesh=True)
            it = snap["it"]
            chunks = list(snap["trees_chunks"])
            scores = snap["scores"]
            val_scores = snap["val_scores"]
            cur = np.asarray(snap["cur_bag"], np.float32)
            rng.bit_generator.state = snap["rng_state"]
            bag_rng.bit_generator.state = snap["bag_rng_state"]
            best_metric = snap["best_metric"]
            best_iter = snap["best_iter"]
            if callbacks:
                log.warning("resuming mesh fit from checkpoint at "
                            "iteration %d: callbacks replay only for "
                            "the remaining iterations", it)
            elif params.verbosity > 0:
                log.info("resuming mesh fit from checkpoint at "
                         "iteration %d", it)
    while it < T:
        C = min(chunk, T - it)
        if use_bag:
            rows = []
            for j in range(C):
                if (it + j) % params.bagging_freq == 0:
                    # draw exactly n randoms so the stream matches a
                    # serial run with the same baggingSeed, then scatter
                    # into the padded layout (pad rows stay 0; under
                    # sharded ingestion real rows sit per-shard slice)
                    cur = (bag_rng.random(n) < params.bagging_fraction
                           ).astype(np.float32)
                row = np.zeros(n_padded, np.float32)
                row[real_pos] = cur
                rows.append(row)
            bags_host = np.stack(rows)
            bags = jax.device_put(jnp.asarray(bags_host),
                                  NamedSharding(mesh, P(None, DATA_AXIS)))
        else:
            bags_host = np.ones((C, 1), np.float32)
            bags = jnp.ones((C, 1), jnp.float32)
        if use_ff:
            fi_host = np.stack([iter_fi_dist(it + j) for j in range(C)])
        else:
            fi_host = np.broadcast_to(fi_base, (C,) + fi_base.shape)
        fi_stack = jnp.asarray(fi_host)
        def run_step(scores_in, val_scores_in):
            if use_goss_m:
                return step(
                    bins_d, scores_in, labels_d, w_d, real,
                    goss_keys_m[it:it + C], fi_stack, val_bins_d,
                    val_scores_in)
            return step(
                bins_d, scores_in, labels_d, w_d, real, bags, fi_stack,
                val_bins_d, val_scores_in)

        t_chunk = time.perf_counter()
        if ftr > 0:
            # one D2H snapshot per chunk buys replay; the happy path
            # reuses the LIVE device buffers (donation is safe — the
            # snapshot covers the replay)
            snap = (np.asarray(scores), np.asarray(val_scores))
            for attempt in range(ftr + 1):
                try:
                    if attempt == 0:
                        s_in, v_in = scores, val_scores
                    else:
                        s_in = jax.device_put(jnp.asarray(snap[0]),
                                              scores.sharding)
                        v_in = jax.device_put(jnp.asarray(snap[1]),
                                              val_scores.sharding)
                    trees_st, scores, val_scores, val_hist = run_step(
                        s_in, v_in)
                    jax.block_until_ready(trees_st)
                    break
                except Exception as e:  # noqa: BLE001 - device loss
                    from jax.experimental import checkify as _ck
                    if isinstance(e, _ck.JaxRuntimeError):
                        raise   # deterministic data bug: replay would
                        # fail identically
                    if attempt >= ftr:
                        raise
                    train_stats.incr("chunks_replayed")
                    _ckpt_event("chunk_replayed", it=int(it),
                                attempt=attempt + 1, mesh=True)
                    log.warning(
                        "mesh chunk at iteration %d failed (attempt "
                        "%d/%d); re-uploading the gang's inputs and "
                        "replaying", it, attempt + 1, ftr)
                    bins_d, labels_d, w_d, real, scores, _, _ = \
                        prep_arrays()
                    if use_goss_m:
                        # the PRNG key stack is a device buffer too
                        goss_keys_m = jax.random.split(
                            jax.random.PRNGKey(params.bagging_seed),
                            params.num_iterations)
                    if efb_dev_m is not None:
                        # the EFB maps are closure constants of the
                        # compiled step — dead with the gang; re-upload
                        # and rebuild the program around them
                        efb_dev_m = _efb_dev_from_host(efb_host_m)
                        step = build_step(efb_dev_m)
                    if has_val:
                        val_bins_d = jax.device_put(
                            jnp.asarray(ft_vb),
                            NamedSharding(mesh, P(DATA_AXIS, None)))
                        val_scores = jax.device_put(
                            jnp.asarray(snap[1]),
                            NamedSharding(mesh, vspec))
                    else:
                        val_bins_d = jax.device_put(
                            jnp.zeros((dn, f_padded), mapper.bin_dtype),
                            NamedSharding(mesh, P(DATA_AXIS, None)))
                        val_scores = jax.device_put(
                            jnp.asarray(snap[1]),
                            NamedSharding(mesh, P(DATA_AXIS, None)
                                          if K > 1 else P(DATA_AXIS)))
                    if use_bag:
                        bags = jax.device_put(
                            jnp.asarray(bags_host),
                            NamedSharding(mesh, P(None, DATA_AXIS)))
                    else:
                        bags = jnp.asarray(bags_host)
                    fi_stack = jnp.asarray(fi_host)
        else:
            trees_st, scores, val_scores, val_hist = _dispatch_chunk(
                run_step, scores, val_scores, it, C * K, t_chunk,
                uploaded=uploaded, mesh=True)
            uploaded = None
        chunks.append(trees_st)
        # objective=None: the gang's score vector is sharded (not fully
        # addressable on any one controller), so train loss is skipped
        # rather than gathered
        _monitor_chunk(it, it + C, time.perf_counter() - t_chunk, n, K,
                       cfg.hist_method, collective=cfg.collective,
                       coll_sched=coll_sched_m)
        stop = False
        if has_val:
            vh = np.asarray(val_hist)[:, :nv]    # drop val pad rows
            for j in range(C):
                margins = (_rf_margins(init, vh[j], it + j)
                           if use_rf_m else vh[j])
                metric = float(val_metric(margins, val_labels_np,
                                          val_weights))
                gi = it + j
                if metric < best_metric - 1e-12:
                    best_metric, best_iter = metric, gi
                elif esr > 0 and gi - best_iter >= esr:
                    if params.verbosity > 0:
                        log.info("Early stopping at iteration %d "
                                 "(best %d, metric %.6f)", gi, best_iter,
                                 best_metric)
                    stop_iter = best_iter + 1
                    stop = True
                    break
        if callbacks:
            # per-iteration host replay, same contract as the serial path:
            # cb(global_iter, flat list of per-iteration/per-class trees)
            upto = stop_iter if stop else it + C
            for j in range(upto - it):
                for kk in range(K):
                    cb_list.append(jax.tree_util.tree_map(
                        lambda a, j=j, kk=kk: a[j * K + kk], trees_st))
                for cb in callbacks:
                    cb(it + j, cb_list)
        if stop:
            break
        it += C
        if ckpt and it < T:
            # skip the final boundary: it == T would pay the D2H shard
            # copies and two gang barriers for a snapshot the clear
            # below deletes immediately
            _ckpt_save_mesh(ckpt, ckpt_fp, it, chunks, scores,
                            val_scores, cur, rng, bag_rng, best_metric,
                            best_iter, local_digest=ckpt_local)
    if ckpt:
        if jax.process_count() > 1:
            # every controller must be past its last possible read of
            # the snapshot before anyone deletes it
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("ckpt_clear")
        if jax.process_index() == 0:
            _ckpt_clear(ckpt)

    if device_table is not None \
            and shard_data is None and bins_d.is_fully_addressable:
        device_table.update(bins=bins_d, pad_rows=rp, mesh=mesh)
    return _export_booster(chunks, K, stop_iter, init, params, objective,
                           mapper, feature_names, f, rf=use_rf_m)
