"""Distributed GBDT training over a device mesh.

This module replaces the reference's entire distributed-training machinery
(SURVEY.md §3.1, §5.8): driver-socket rendezvous → ``jax.distributed`` /
mesh construction; LightGBM's TCP ``Network::Allreduce`` of per-feature
histograms (Bruck allgather + recursive-halving reduce-scatter) →
``jax.lax.psum`` over the ``data`` mesh axis, compiler-scheduled onto ICI.

Parallelism mapping (reference ``parallelism`` param → mesh axes):

* ``data``    — rows sharded over the ``data`` axis; per-shard histograms
  psum-reduced; split finding replicated (LightGBM data-parallel learner).
* ``feature`` — features sharded over the ``feature`` axis; each shard scans
  its feature slice for candidate splits, the winner is all-gathered and the
  owning shard broadcasts the split column (LightGBM feature-parallel
  learner).  This is the GBDT analog of sequence parallelism: the wide axis
  is sharded (SURVEY.md §5.7).
* ``data+feature`` — 2-D mesh composing both.
* ``voting``  — data-sharded layout with PV-Tree split finding (Meng et
  al. 2016; LightGBM tree_learner=voting): histograms stay shard-local,
  each shard votes its top-k features, and only the ~2k winning features'
  histogram slices are psum-reduced (grower.find_best_split_voting).

The whole boost step (grad/hess → grow tree → score update) runs inside one
``shard_map`` under ``jit``, so a single compiled program per iteration does
compute + collectives with no host round-trips.

A built step outlives its fit (:func:`_build_step`): the ``make_*``
builders keep their ``jit(shard_map(...))`` in one module-level table keyed
on the builder's name and its arguments as given — ``mesh``, ``obj``,
``cfg``, ``lr`` and every flag and scalar, all hashable by value — so the
next fit with an equal key gets the SAME jit object and its first call is
a hit in JAX's own dispatch cache: no trace, no lowering, no executable
load, as on one chip, where ``engine._boost_scan`` is a module-level jit.
``efb`` stays outside the key: the bundle maps are device arrays baked
into the program as constants, true of one fit's table only, so a builder
called with bundles builds afresh every time and is never kept.
"""

from __future__ import annotations

import collections
import copy
import functools
import inspect
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.mesh import DATA_AXIS, FEATURE_AXIS
from ..core.profiler import get_profiler
from .grower import (GrowerConfig, TreeArrays, _grow_tree_impl,
                     apply_shrinkage, predict_tree_binned,
                     predict_tree_binned_fshard)
from .objectives import Objective


VALID_PARALLELISM = ("serial", "data", "feature", "data+feature", "voting")

#: built steps kept across fits, least recently used out.  An entry is a
#: jit object and keeps the executables it has loaded on the chips; a
#: dropped one is built (and traced) again by the next fit that asks.
_STEP_TABLE_MAX = 8
_step_table: "collections.OrderedDict[tuple, object]" = \
    collections.OrderedDict()
_step_table_lock = threading.Lock()


def _build_step(builder, span: bool = True):
    """``builder``, memoised on its arguments, under the fit's
    ``train.build_step`` span (docs/observability.md).

    The key is the builder's name and every argument as given (defaults
    filled in): each is hashable by value and equal from fit to fit —
    a ``Mesh`` over the same devices, ``Objective.__hash__`` over type
    and state (``prepare``'s included), the frozen ``GrowerConfig``,
    ``lr``, the flags.  An equal key returns the SAME ``jax.jit``
    object, so the program is traced and compiled by the first fit's
    ``train.launch`` alone.  ``efb`` is outside the key because it is
    not a description of the program but a part of it: device arrays
    that the trace bakes in as constants.  A call with bundles (or with
    an objective whose state does not hash) is a ``bypass``: built
    afresh, never stored, so no fit runs a program built around another
    fit's maps, and the fault-tolerance replay still gets a new program
    around its re-uploaded maps.  A step is built around a copy of its
    objective, and the key holds that copy: a later ``prepare`` on the
    caller's object reaches neither.  Built under the lock (building
    traces nothing), so two threads asking for one key get one object.

    The span's attr ``step_cache`` says ``hit``, ``miss`` or ``bypass``
    and ``engine.train_stats`` counts ``mesh_step_hits`` /
    ``mesh_step_builds``; ``span=False`` memoises in silence
    (``make_tree_predict``, a helper of the dart fits' one step)."""
    sig = inspect.signature(builder)

    def lookup(*args, **kwargs):
        bound = sig.bind(*args, **kwargs)
        bound.apply_defaults()
        given = {k: copy.copy(v) if isinstance(v, Objective) else v
                 for k, v in bound.arguments.items()}
        key = (builder.__name__, *given.values())
        bypass = given.get("efb") is not None
        if not bypass:
            try:
                hash(key)
            except TypeError:
                bypass = True
        if bypass:
            return builder(**given), "bypass"
        with _step_table_lock:
            step = _step_table.get(key)
            if step is not None:
                _step_table.move_to_end(key)
                return step, "hit"
            step = _step_table[key] = builder(**given)
            if len(_step_table) > _STEP_TABLE_MAX:
                _step_table.popitem(last=False)
            return step, "miss"

    @functools.wraps(builder)
    def build(*args, **kwargs):
        if not span:
            return lookup(*args, **kwargs)[0]
        from .engine import train_stats
        with get_profiler().region("train.build_step") as sp:
            step, sp["step_cache"] = lookup(*args, **kwargs)
        train_stats.incr("mesh_step_hits" if sp["step_cache"] == "hit"
                         else "mesh_step_builds")
        return step
    return build


def _upload(prepare):
    """``prepare`` under the fit's ``train.upload`` span, handed to it as
    ``_span``, with the bytes of the global arrays it put on the mesh."""
    @functools.wraps(prepare)
    def wrapped(*args, **kwargs):
        with get_profiler().region("train.upload") as sp:
            out = prepare(*args, _span=sp, **kwargs)
            sp["bytes"] = int(sum(a.nbytes for a in out[:5]))
        return out
    return wrapped


def resolve_mesh(parallelism: str, mesh: Optional[Mesh] = None) -> Mesh:
    """Build the mesh shape implied by the ``parallelism`` param."""
    if mesh is not None:
        return mesh
    if parallelism not in VALID_PARALLELISM:
        raise ValueError(f"Unknown parallelism {parallelism!r}; "
                         f"valid: {VALID_PARALLELISM}")
    devs = jax.devices()
    n = len(devs)
    if parallelism == "feature" and n > 1:
        arr = np.asarray(devs).reshape(1, n)
    elif parallelism == "serial":
        arr = np.asarray(devs[:1]).reshape(1, 1)
    elif parallelism == "data+feature" and n > 1 and n % 2 == 0:
        arr = np.asarray(devs).reshape(n // 2, 2)
    else:  # data / voting (same mesh layout; voting differs in the grower)
        arr = np.asarray(devs).reshape(n, 1)
    return Mesh(arr, (DATA_AXIS, FEATURE_AXIS))


def data_only_mesh(mesh: Mesh) -> Mesh:
    """The same devices on a SINGLE-named-axis ``(data,)`` mesh.

    The Pallas ring collectives (ops/pallas_collectives.py) require
    exactly one named mesh axis — both for Mosaic's LOGICAL device-id
    lowering along the ring and for the interpret-mode DMA discharge,
    which rejects multi-axis environments.  Only meaningful for layouts
    whose feature axis is size 1 — pure data-parallel AND voting-
    parallel fits (voting shares the data layout; its voted-column ring
    reduces only the candidate slab) — and raises otherwise.  Every
    scan builder in this module sizes its PartitionSpecs via
    :func:`_f_ax`, so the rebuilt mesh flows through them unchanged."""
    if _feat_n(mesh) != 1:
        raise ValueError(
            "ring collectives need a pure data-parallel layout; "
            f"mesh has a feature axis of size {_feat_n(mesh)}")
    devs = np.asarray(mesh.devices).reshape(-1)
    return Mesh(devs, (DATA_AXIS,))


def _feat_n(mesh: Mesh) -> int:
    """Feature-axis size, 1 when the mesh is data-only (ring layout)."""
    return int(dict(mesh.shape).get(FEATURE_AXIS, 1))


def _f_ax(mesh: Mesh):
    """FEATURE_AXIS when the mesh carries one, else None — so the same
    PartitionSpecs build against both 2-axis and data-only meshes."""
    return FEATURE_AXIS if FEATURE_AXIS in dict(mesh.shape) else None


def _sharded_cfg(mesh: Mesh, cfg: GrowerConfig) -> GrowerConfig:
    data_n = int(mesh.shape[DATA_AXIS])
    feat_n = _feat_n(mesh)
    return GrowerConfig(**{
        **cfg.__dict__,
        "axis_name": DATA_AXIS if data_n > 1 else None,
        "feature_axis_name": FEATURE_AXIS if feat_n > 1 else None,
        "data_axis_size": data_n,
    })


@_build_step
def make_goss_scan(mesh: Mesh, obj: Objective, cfg: GrowerConfig, lr: float,
                   k1: int, k2: int, amp: float, has_val: bool = False,
                   num_class: int = 1):
    """Mesh GOSS: every data shard samples its own top-|g·h| rows plus an
    amplified random remainder (per-machine sampling, exactly like
    distributed LightGBM's boosting=goss), then the sampled sub-shards
    train one tree data-parallel with psum histograms.  ``k1``/``k2`` are
    PER-SHARD row counts; the per-iteration PRNG key is folded with the
    shard index so shards draw independent remainders.

    ``num_class > 1``: rows rank by the class-summed influence
    Σ_k |g_k·h_k| and one per-shard sample feeds all K class trees."""
    cfg = _sharded_cfg(mesh, cfg)
    K = num_class

    def tree_pred(tree, b):
        # train-side score update: with a feature axis each shard holds a
        # column slice, so the walk assembles compare vectors by psum;
        # validation bins stay full-feature per shard (host-small) and
        # keep the local walk
        if cfg.feature_axis_name is not None:
            return predict_tree_binned_fshard(tree, b, cfg.num_leaves,
                                              cfg.feature_axis_name)
        return predict_tree_binned(tree, b, cfg.num_leaves)

    def steps(bins, scores, labels, weights, real, keys, fis,
              val_bins, val_scores):
        def body(carry, xs):
            scores, val_scores = carry
            key, fi = xs
            if cfg.axis_name is not None:
                key = jax.random.fold_in(
                    key, jax.lax.axis_index(cfg.axis_name))
            with jax.named_scope("gradients"):
                g, h = obj.grad_hess(scores, labels, weights)
            g = g * (real if K == 1 else real[:, None])
            h = h * (real if K == 1 else real[:, None])
            n_local = g.shape[0]
            infl = (jnp.abs(g * h) if K == 1
                    else jnp.sum(jnp.abs(g * h), axis=1))
            rank = jnp.argsort(-infl)                # pads (0) sort last
            top_idx = rank[:k1]
            rest = rank[k1:]
            rk = jax.random.uniform(key, (n_local - k1,))
            other_idx = jnp.take(rest, jnp.argsort(rk)[:k2])
            idx = jnp.concatenate([top_idx, other_idx])
            amp_vec = jnp.concatenate([
                jnp.ones(k1, jnp.float32), jnp.full(k2, amp, jnp.float32)])
            valid = jnp.take(real, idx)
            bins_g = jnp.take(bins, idx, axis=0)
            if K == 1:
                gh = jnp.stack([jnp.take(g, idx) * amp_vec,
                                jnp.take(h, idx) * amp_vec,
                                valid], axis=1)
                tree, _ = _grow_tree_impl(bins_g, gh, fi, cfg)
                with jax.named_scope("score_update"):
                    scores = scores + lr * tree_pred(tree, bins)
                trees = apply_shrinkage(tree, lr)
                if has_val:
                    val_scores = val_scores + predict_tree_binned(
                        trees, val_bins, cfg.num_leaves)
            else:
                trees_k = []
                for k in range(K):
                    gh = jnp.stack([jnp.take(g[:, k], idx) * amp_vec,
                                    jnp.take(h[:, k], idx) * amp_vec,
                                    valid], axis=1)
                    tree, _ = _grow_tree_impl(bins_g, gh, fi, cfg)
                    with jax.named_scope("score_update"):
                        scores = scores.at[:, k].add(
                            lr * tree_pred(tree, bins))
                    tree = apply_shrinkage(tree, lr)
                    if has_val:
                        val_scores = val_scores.at[:, k].add(
                            predict_tree_binned(tree, val_bins,
                                                cfg.num_leaves))
                    trees_k.append(tree)
                trees = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *trees_k)
            if has_val:
                out_v = val_scores
            else:
                out_v = jnp.zeros((0,) if K == 1 else (0, K), jnp.float32)
            return (scores, val_scores), (trees, out_v)

        (scores, val_scores), (trees, val_hist) = jax.lax.scan(
            body, (scores, val_scores), (keys, fis))
        if K > 1:
            trees = jax.tree_util.tree_map(
                lambda a: a.reshape((-1,) + a.shape[2:]), trees)
        return trees, scores, val_scores, val_hist

    sc_spec = P(DATA_AXIS) if K == 1 else P(DATA_AXIS, None)
    if has_val:
        val_hist_spec = (P(None, DATA_AXIS) if K == 1
                         else P(None, DATA_AXIS, None))
    else:
        val_hist_spec = P(None, None) if K == 1 else P(None, None, None)
    fa = _f_ax(mesh)
    mapped = jax.shard_map(
        steps, mesh=mesh,
        in_specs=(P(DATA_AXIS, fa), sc_spec, P(DATA_AXIS),
                  P(DATA_AXIS), P(DATA_AXIS), P(None, None),
                  P(None, fa, None),
                  P(DATA_AXIS, None), sc_spec),
        out_specs=(P(), sc_spec, sc_spec, val_hist_spec),
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(1, 8))


@_build_step
def make_boost_scan(mesh: Mesh, obj: Objective, cfg: GrowerConfig, lr: float,
                    bag_sharded: bool, has_val: bool = False,
                    rf: bool = False, efb=None):
    """Chunked distributed boosting: a ``lax.scan`` over iterations INSIDE
    the shard_map, so a whole chunk of trees trains in one launch with all
    histogram psums compiler-scheduled onto ICI (the reference's per-
    iteration socket allreduce, amortized to one program).

    ``rf``: random-forest mode — every tree fits the gradient at the
    CONSTANT init scores, unshrunk (averaging happens at export), with
    the per-iteration bagging masks providing the forest's resampling.

    ``real``: (n,) row-validity mask sharded over ``data`` (zeros on pad
    rows), folded into every iteration's mask.  ``bags``: (C, n) bagging
    masks sharded over ``data`` when ``bag_sharded``, else a constant
    (C, 1) broadcast — so a padded no-bagging fit costs one (n,) mask, not
    a (C, n) stack of identical copies.

    ``has_val``: validation rows ride the mesh too — ``val_bins`` is
    sharded over ``data`` with ALL features per shard (trees are
    replicated, so each shard scores its own validation slice), and the
    per-iteration validation margins come back as a (C, n_val) array for
    host-side metric replay / early stopping (the reference's executor-
    side eval, SURVEY.md §3.1).

    Returns (stacked replicated trees, sharded scores, sharded val_scores,
    per-iteration val history).
    """
    cfg = _sharded_cfg(mesh, cfg)

    def steps(bins, scores, labels, weights, real, bags, fis,
              val_bins, val_scores):
        binsT = bins.T   # fit-invariant; hoisted out of the scan

        def body(carry, xs):
            scores, val_scores = carry
            bag, fi = xs
            bag = jnp.broadcast_to(bag, scores.shape) * real
            with jax.named_scope("gradients"):
                g, h = obj.grad_hess(scores, labels, weights)
            gh = jnp.stack([g * bag, h * bag, bag], axis=1)
            # efb rides the closure: the (f, B)-sized maps replicate as
            # baked constants; per-feature expansion happens SHARD-LOCAL
            # before the psum (expansion is linear, so it commutes)
            tree, row_leaf = _grow_tree_impl(bins, gh, fi, cfg, efb,
                                             binsT=binsT)
            if not rf:
                with jax.named_scope("score_update"):
                    scores = scores + lr * tree.leaf_value[row_leaf]
                tree = apply_shrinkage(tree, lr)
            if has_val:
                val_scores = val_scores + predict_tree_binned(
                    tree, val_bins, cfg.num_leaves)
                out_v = val_scores
            else:
                out_v = jnp.zeros((0,), jnp.float32)
            return (scores, val_scores), (tree, out_v)

        (scores, val_scores), (trees, val_hist) = jax.lax.scan(
            body, (scores, val_scores), (bags, fis))
        return trees, scores, val_scores, val_hist

    bag_spec = P(None, DATA_AXIS) if bag_sharded else P(None, None)
    val_hist_spec = P(None, DATA_AXIS) if has_val else P(None, None)
    fa = _f_ax(mesh)
    mapped = jax.shard_map(
        steps, mesh=mesh,
        in_specs=(P(DATA_AXIS, fa), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS), P(DATA_AXIS), bag_spec,
                  P(None, fa, None),
                  P(DATA_AXIS, None), P(DATA_AXIS)),
        out_specs=(P(), P(DATA_AXIS), P(DATA_AXIS), val_hist_spec),
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(1, 8))


@_build_step
def make_multiclass_scan(mesh: Mesh, obj: Objective, cfg: GrowerConfig,
                         lr: float, num_class: int, bag_sharded: bool,
                         has_val: bool = False, efb=None,
                         rf: bool = False):
    """Multiclass distributed chunk: grad/hess once per iteration for all K
    trees (LightGBM softmax semantics), K grow steps per scan iteration.
    Trees come back stacked (C*K, ...), iteration-major.

    ``rf``: random-forest mode — trees fit the gradient at the CONSTANT
    init scores, unshrunk (per-class averaging at export)."""
    cfg = _sharded_cfg(mesh, cfg)
    K = num_class

    def steps(bins, scores, labels, weights, real, bags, fis,
              val_bins, val_scores):
        binsT = bins.T   # fit-invariant; hoisted out of the scan

        def body(carry, xs):
            scores, val_scores = carry
            bag, fi = xs
            bag = jnp.broadcast_to(bag, (scores.shape[0],)) * real
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
                    val_scores = val_scores.at[:, k].add(
                        predict_tree_binned(tree, val_bins,
                                            cfg.num_leaves))
                trees_k.append(tree)
            trees = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *trees_k)
            out_v = val_scores if has_val else jnp.zeros((0, K), jnp.float32)
            return (scores, val_scores), (trees, out_v)

        (scores, val_scores), (trees, val_hist) = jax.lax.scan(
            body, (scores, val_scores), (bags, fis))
        trees = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), trees)
        return trees, scores, val_scores, val_hist

    bag_spec = P(None, DATA_AXIS) if bag_sharded else P(None, None)
    val_hist_spec = P(None, DATA_AXIS, None) if has_val else P(None, None)
    fa = _f_ax(mesh)
    mapped = jax.shard_map(
        steps, mesh=mesh,
        in_specs=(P(DATA_AXIS, fa), P(DATA_AXIS, None),
                  P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), bag_spec,
                  P(None, fa, None),
                  P(DATA_AXIS, None), P(DATA_AXIS, None)),
        out_specs=(P(), P(DATA_AXIS, None), P(DATA_AXIS, None),
                   val_hist_spec),
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(1, 8))


@_build_step
def make_ranking_dart_step(mesh: Mesh, cfg: GrowerConfig, lr: float,
                           sigma: float, trunc: int):
    """One dart iteration for MESH LAMBDARANK: pairwise ΔNDCG gradients
    computed shard-local at the dropped-out scores (queries are packed
    per shard, so no collective touches the lambda tensors), tree grown
    data-parallel with psum histograms.  Host-side dropout bookkeeping is
    the shared ``_dart_host_loop``.  Data-only mesh (dropped-unit scoring
    reads whole feature rows)."""
    from .ranking import lambda_grad_sorted

    cfg = _sharded_cfg(mesh, cfg)

    def step(bins, binsT, s_minus, real, wmul, qidx, qmask, gains, labq,
             invmax, bag, fi):
        nl = s_minus.shape[0]
        g, h = lambda_grad_sorted(s_minus, qidx, qmask, gains, labq,
                                  invmax, sigma, trunc, nl)
        h = jnp.maximum(h, 1e-9)
        wb = wmul * bag
        gh = jnp.stack([g * wb, h * wb, real * bag], axis=1)
        tree, row_leaf = _grow_tree_impl(bins, gh, fi, cfg, binsT=binsT)
        tree = apply_shrinkage(tree, lr)
        return tree, tree.leaf_value[row_leaf]

    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(None, DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS, None, None),
                  P(DATA_AXIS, None, None), P(DATA_AXIS, None, None),
                  P(DATA_AXIS, None, None), P(DATA_AXIS, None),
                  P(DATA_AXIS), P(None, None)),
        out_specs=(P(), P(DATA_AXIS)),
        check_vma=False)
    return jax.jit(mapped)


@_build_step
def make_dart_step(mesh: Mesh, obj: Objective, cfg: GrowerConfig,
                   lr: float, num_class: int = 1):
    """One dart iteration over the mesh: fit a tree to the gradient at
    the dropped-out score vector ``s_minus`` (histogram psums over the
    ``data`` axis — and, on a 2-D mesh, feature-parallel split search —
    inside the grower), returning the replicated lr-shrunk tree and its
    data-sharded base contribution.  The host applies the 1/(k+1) dart
    normalization and tracks per-tree scales, exactly like the serial
    path — dropout bookkeeping is tiny host metadata, only the fit and
    the scoring ride the mesh."""
    cfg = _sharded_cfg(mesh, cfg)
    fshard = _feat_n(mesh) > 1
    K = num_class

    def step(bins, binsT, s_minus, labels, weights, bag, fi):
        with jax.named_scope("gradients"):
            g, h = obj.grad_hess(s_minus, labels, weights)
        if K == 1:
            gh = jnp.stack([g * bag, h * bag, bag], axis=1)
            tree, row_leaf = _grow_tree_impl(bins, gh, fi, cfg,
                                             binsT=binsT)
            tree = apply_shrinkage(tree, lr)
            return tree, tree.leaf_value[row_leaf]
        trees_k, bnews = [], []
        for k in range(K):
            gh = jnp.stack([g[:, k] * bag, h[:, k] * bag, bag], axis=1)
            tree, row_leaf = _grow_tree_impl(bins, gh, fi, cfg,
                                             binsT=binsT)
            tree = apply_shrinkage(tree, lr)
            trees_k.append(tree)
            bnews.append(tree.leaf_value[row_leaf])
        trees = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                       *trees_k)
        return trees, jnp.stack(bnews, axis=1)

    sc_spec = P(DATA_AXIS) if K == 1 else P(DATA_AXIS, None)
    bins_spec = (P(DATA_AXIS, FEATURE_AXIS) if fshard
                 else P(DATA_AXIS, None))
    binsT_spec = (P(FEATURE_AXIS, DATA_AXIS) if fshard
                  else P(None, DATA_AXIS))
    fi_spec = P(FEATURE_AXIS, None) if fshard else P(None, None)
    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(bins_spec, binsT_spec, sc_spec,
                  P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                  fi_spec),
        out_specs=(P(), sc_spec),
        check_vma=False)
    return jax.jit(mapped)


@functools.partial(_build_step, span=False)
def make_tree_predict(mesh: Mesh, num_leaves: int, num_class: int = 1):
    """Replicated-tree scoring of mesh-sharded binned rows — dart's
    dropped-tree subtraction and validation scoring.  Data-only mesh:
    each shard walks its rows with all features local.  With a feature
    axis, the walk assembles each level's compare vector by psum
    (grower.predict_tree_binned_fshard — the scoring analog of the
    feature-parallel split-column broadcast).  ``num_class > 1`` scores
    one dart iteration's K stacked trees to (n, K)."""
    fshard = _feat_n(mesh) > 1
    if fshard:
        def walk(tree, bins):
            return predict_tree_binned_fshard(tree, bins, num_leaves,
                                              FEATURE_AXIS)
        bins_spec = P(DATA_AXIS, FEATURE_AXIS)
    else:
        def walk(tree, bins):
            return predict_tree_binned(tree, bins, num_leaves)
        bins_spec = P(DATA_AXIS, None)

    if num_class == 1:
        def pred(tree, bins):
            return walk(tree, bins)
        out_spec = P(DATA_AXIS)
    else:
        def pred(trees_st, bins):
            return jax.vmap(lambda t: walk(t, bins))(trees_st).T
        out_spec = P(DATA_AXIS, None)

    mapped = jax.shard_map(
        pred, mesh=mesh,
        in_specs=(P(), bins_spec),
        out_specs=out_spec,
        check_vma=False)
    return jax.jit(mapped)


@_build_step
def make_ranking_scan(mesh: Mesh, cfg: GrowerConfig, lr: float,
                      sigma: float, trunc: int, has_val: bool = False,
                      goss=None, bag_sharded: bool = False,
                      rf: bool = False):
    """Mesh-sharded lambdarank boosting (SURVEY.md §3.1 distributed
    lambdarank, BASELINE config MSLR): rows arrive query-packed per data
    shard (see :func:`mmlspark_tpu.gbdt.ranking.shard_queries`), so the
    pairwise ΔNDCG gradients are shard-LOCAL — no collective touches the
    (c, G, G) lambda tensors; only the histogram psum crosses ICI, exactly
    like the classifier path.

    ``qidx/qmask/gains/labq`` are (D*n_chunks, chunk, G) and ``invmax``
    (D*n_chunks, chunk), sharded over ``data`` on the leading axis;
    ``real`` masks pad rows.  Validation margins ride the mesh as in
    :func:`make_boost_scan`.

    ``goss``: optional ``(k1, k2, amp)`` — per-shard GOSS on top of the
    full lambdarank gradients: pairwise ΔNDCG gradients are computed on
    EVERY row (they need whole queries), then the tree grows on the
    top-|g·h| sample plus an amplified random remainder, exactly like
    distributed LightGBM's boosting=goss with a ranking objective.
    ``keys`` feeds the per-iteration PRNG (ignored otherwise).

    ``bags``: (C, n) bagging masks scattered through the query-pack
    permutation (constant (C, 1) when bagging is off); gradients and
    hessians are masked, membership (``real``) is not.  ``rf``: trees
    fit the gradients at the CONSTANT init scores, unshrunk (averaging
    at export) — random-forest mode with the ranking objective.
    """
    from .ranking import lambda_grad_sorted

    cfg = _sharded_cfg(mesh, cfg)

    def steps(bins, scores, real, wmul, qidx, qmask, gains, labq, invmax,
              keys, bags, fis, val_bins, val_scores):
        nl = scores.shape[0]
        binsT = bins.T   # fit-invariant; hoisted out of the scan

        def body(carry, xs):
            scores, val_scores = carry
            key, bag, fi = xs
            g, h = lambda_grad_sorted(scores, qidx, qmask, gains, labq,
                                      invmax, sigma, trunc, nl)
            h = jnp.maximum(h, 1e-9)
            # wmul = row weight * validity (LightGBM ranker weightCol
            # semantics); the count channel carries plain validity
            wb = wmul * jnp.broadcast_to(bag, (nl,))
            # count channel = validity * bag, matching the serial ranking
            # loop: with bagging the tree trains on the SAMPLE, so
            # min_data_in_leaf counts sampled rows (LightGBM semantics)
            cb = real * jnp.broadcast_to(bag, (nl,))
            if goss is None:
                gh = jnp.stack([g * wb, h * wb, cb], axis=1)
                tree, row_leaf = _grow_tree_impl(bins, gh, fi, cfg,
                                                 binsT=binsT)
                if not rf:
                    with jax.named_scope("score_update"):
                        scores = scores + lr * tree.leaf_value[row_leaf]
            else:
                k1, k2, amp = goss
                if cfg.axis_name is not None:
                    key = jax.random.fold_in(
                        key, jax.lax.axis_index(cfg.axis_name))
                gm = g * wb
                hm = h * wb                       # pads carry wmul 0
                rank = jnp.argsort(-jnp.abs(gm * hm))
                top_idx = rank[:k1]
                rk = jax.random.uniform(key, (nl - k1,))
                other_idx = jnp.take(rank[k1:], jnp.argsort(rk)[:k2])
                idx = jnp.concatenate([top_idx, other_idx])
                amp_vec = jnp.concatenate([
                    jnp.ones(k1, jnp.float32),
                    jnp.full(k2, amp, jnp.float32)])
                gh = jnp.stack([jnp.take(gm, idx) * amp_vec,
                                jnp.take(hm, idx) * amp_vec,
                                jnp.take(real, idx)], axis=1)
                tree, _ = _grow_tree_impl(jnp.take(bins, idx, axis=0),
                                          gh, fi, cfg)
                scores = scores + lr * predict_tree_binned(
                    tree, bins, cfg.num_leaves)
            if not rf:
                tree = apply_shrinkage(tree, lr)
            if has_val:
                val_scores = val_scores + predict_tree_binned(
                    tree, val_bins, cfg.num_leaves)
                out_v = val_scores
            else:
                out_v = jnp.zeros((0,), jnp.float32)
            return (scores, val_scores), (tree, out_v)

        (scores, val_scores), (trees, val_hist) = jax.lax.scan(
            body, (scores, val_scores), (keys, bags, fis))
        return trees, scores, val_scores, val_hist

    val_hist_spec = P(None, DATA_AXIS) if has_val else P(None, None)
    bag_spec = P(None, DATA_AXIS) if bag_sharded else P(None, None)
    fa = _f_ax(mesh)
    mapped = jax.shard_map(
        steps, mesh=mesh,
        in_specs=(P(DATA_AXIS, fa), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS), P(DATA_AXIS, None, None),
                  P(DATA_AXIS, None, None), P(DATA_AXIS, None, None),
                  P(DATA_AXIS, None, None), P(DATA_AXIS, None),
                  P(None, None), bag_spec,
                  P(None, fa, None),
                  P(DATA_AXIS, None), P(DATA_AXIS)),
        out_specs=(P(), P(DATA_AXIS), P(DATA_AXIS), val_hist_spec),
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(1, 13))


@_upload
def prepare_arrays_from_shards(bins_shards, label_shards, weight_shards,
                               mesh: Mesh, num_class: int, init: float,
                               bin_dtype, shard_rows=None,
                               init_score_shards=None, _piece_spy=None,
                               _span=None):
    """Multi-host ingestion (SURVEY.md §7 hard part 4): assemble the global
    sharded training arrays from PER-SHARD inputs without materializing the
    full matrix on any single host.

    ``bins_shards[d]`` is data-shard d's binned rows (n_d, f) — in a real
    multi-host deployment the per-host Arrow reader output.  Shards are
    padded to the max shard length with zero-weight rows; every device
    piece is produced by ``jax.make_array_from_callback``, which asks only
    for the ADDRESSABLE devices' (S, f_shard) blocks, so peak host memory
    is this host's shards, not D of them.  On a multi-controller
    deployment pass ``None`` in the non-local slots of the three shard
    lists plus ``shard_rows`` (the global per-shard row counts, small
    metadata every host knows); the callback never touches non-local
    slots.  Returns the same tuple as :func:`prepare_arrays`
    (rp = total pad rows across shards).
    """
    D = int(mesh.shape[DATA_AXIS])
    if len(bins_shards) != D:
        raise ValueError(
            f"need exactly one shard slot per data-mesh slice: got "
            f"{len(bins_shards)} slots for data={D}")
    local = [d for d in range(D) if bins_shards[d] is not None]
    if not local:
        raise ValueError("no local shards (every slot is None)")
    f = bins_shards[local[0]].shape[1]
    for d in local:
        if bins_shards[d].shape[1] != f:
            raise ValueError(
                f"shard {d} has {bins_shards[d].shape[1]} features, "
                f"shard {local[0]} has {f}: all shards must agree")
        nl = len(label_shards[d])
        nw = len(weight_shards[d]) if weight_shards[d] is not None else nl
        if not (bins_shards[d].shape[0] == nl == nw):
            raise ValueError(
                f"shard {d}: bins rows {bins_shards[d].shape[0]}, labels "
                f"{nl}, weights {nw} must all match")
    if shard_rows is not None:
        sizes = list(shard_rows)
        for d in local:
            if sizes[d] != bins_shards[d].shape[0]:
                raise ValueError(
                    f"shard_rows[{d}]={sizes[d]} does not match the local "
                    f"shard's {bins_shards[d].shape[0]} rows")
    elif len(local) == D:
        sizes = [b.shape[0] for b in bins_shards]
    else:
        raise ValueError("shard_rows is required when some shard slots "
                         "are None (multi-controller)")
    return _put_shards(mesh, sizes, f, bins_shards, label_shards,
                       weight_shards, init_score_shards, num_class, init,
                       bin_dtype, _span, _piece_spy)


@_upload
def prepare_arrays(bins: np.ndarray, labels: np.ndarray, weights: np.ndarray,
                   mesh: Mesh, num_class: int, init: float,
                   init_scores: Optional[np.ndarray] = None,
                   _piece_spy=None, _span=None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                              jnp.ndarray, jnp.ndarray, int, int]:
    """Pad rows/features to multiples of the mesh axes and put each
    device's block straight from host memory on its device.

    The table is cut into ``data``-axis shards of ceil(n / D) rows (the
    last ones shorter or empty) and handed, as views, to the same
    assembly as :func:`prepare_arrays_from_shards`: the padded layout is
    the rows in order, pad rows at the end carrying zero weight (excluded
    from histograms via the bag mask), pad features constant bin 0 (never
    produce a valid split).  No array is ever whole on one device, and
    only a block that holds padding is copied on the host.
    """
    n, f = bins.shape
    D = int(mesh.shape[DATA_AXIS])
    S = -(-n // D)
    edges = [min(d * S, n) for d in range(D + 1)]

    def cut(a):
        return None if a is None else \
            [a[edges[d]:edges[d + 1]] for d in range(D)]

    return _put_shards(mesh, [b - a for a, b in zip(edges, edges[1:])], f,
                       cut(bins), cut(labels), cut(weights),
                       cut(init_scores), num_class, init, bins.dtype, _span,
                       _piece_spy)


def _put_shards(mesh: Mesh, sizes, f: int, bins_shards, label_shards,
                weight_shards, init_score_shards, num_class: int, init,
                bin_dtype, span=None, piece_spy=None):
    """The five global arrays of a mesh fit from per-shard host rows.

    Shard d's rows lead ``data`` block d and zero-weight pad rows fill it
    to the longest shard's S; the feature axis is padded with bin 0 to a
    multiple of its size.  ``jax.make_array_from_callback`` asks for each
    addressable device's block, and each block is its own transfer from
    host memory to that device.  A block that needs no padding is the
    shard's own view (or a cast of it where the dtype differs); only a
    block that holds pad rows or pad features is built on the host.
    ``span`` (the ``train.upload`` attrs) gets ``pieces``, the per-device
    transfers, and ``host_copy_bytes``, the bytes of those built blocks.
    """
    from ..core.mesh import pad_to_multiple
    from .engine import train_stats
    S = max(sizes)
    n_global = len(sizes) * S
    f_padded = pad_to_multiple(f, _feat_n(mesh))
    copied = [0]

    def make(spec, dtype, fill, source, width=None):
        shape = (n_global,) if width is None else (n_global, width)
        sharding = NamedSharding(mesh, spec)
        want = sharding.shard_shape(shape)

        def piece(index):
            d = index[0].indices(n_global)[0] // S
            block = np.asarray(source(d), dtype)[
                (slice(None),) + tuple(index[1:])]
            if block.shape != want:
                out = np.full(want, fill, dtype)
                out[tuple(slice(k) for k in block.shape)] = block
                copied[0] += out.nbytes
                block = out
            if piece_spy is not None:
                piece_spy(block.shape)
            return block

        return jax.make_array_from_callback(shape, sharding, piece)

    def score_shard(d):
        # ``init``: a float, or one start score per class; per-shard init
        # scores (initScoreCol) offset the real rows, pad rows keep the
        # plain init (their weight is zero anyway)
        base = np.full((S,) if num_class == 1 else (S, num_class), init,
                       np.float32)
        if init_score_shards is not None \
                and init_score_shards[d] is not None:
            extra = np.asarray(init_score_shards[d], np.float32)
            base[:sizes[d]] += (extra if extra.ndim == base.ndim
                                else extra[:, None])
        return base

    lab_dtype = np.int32 if num_class > 1 else np.float32
    out = (make(P(DATA_AXIS, _f_ax(mesh)), bin_dtype, 0,
                lambda d: bins_shards[d], width=f_padded),
           make(P(DATA_AXIS), lab_dtype, 0, lambda d: label_shards[d]),
           make(P(DATA_AXIS), np.float32, 0.0, lambda d: weight_shards[d]),
           make(P(DATA_AXIS), np.float32, 0.0,
                lambda d: np.arange(S) < sizes[d]),
           make(P(DATA_AXIS, None), np.float32, init, score_shard,
                width=num_class) if num_class > 1 else
           make(P(DATA_AXIS), np.float32, init, score_shard))
    if span is not None:
        span["pieces"] = sum(len(a.addressable_shards) for a in out)
        span["host_copy_bytes"] = copied[0]
    train_stats.incr("mesh_uploads_direct")
    return out + (n_global - sum(sizes), f_padded - f)
