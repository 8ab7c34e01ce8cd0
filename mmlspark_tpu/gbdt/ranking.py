"""LightGBMRanker: lambdarank objective and estimator.

TPU-native re-implementation of the reference's ranker
(lightgbm/LightGBMRanker.scala, expected path, UNVERIFIED; SURVEY.md §2.1)
whose native engine computes pairwise ΔNDCG-weighted gradients per query.

Static-shape design (SURVEY.md §7 hard part 6): queries are grouped on the
host into SIZE CLASSES, powers of two from 8 up to the longest query
(:func:`pack_queries_by_size`); a class of length ``G`` is one
``(queries, G)`` block of row indices, cut into chunks of queries, and the
gradient program scans over a class's chunks computing the full
``(chunk, G, G)`` pairwise lambda tensor per chunk.  A query of more than
four documents is padded to under twice its length, so its pairs computed
are under four times its pairs; a shorter one costs the 64 slots of the
smallest class.  The trainer on one device takes the layout as the
``labels`` of :class:`LambdarankObjective` on the ordinary boost scan
(``engine._boost_scan``): the query tensors are arguments of a program
built once, not constants of a closure.  The mesh trainer
(``distributed.make_ranking_scan``) keeps the older layout, every query
padded to the longest one on its shard (:func:`shard_queries`); both call
the same pair mathematics (:func:`_pair_lambdas`).  Semantics follow
lambdarank:

* gains ``2^label - 1``, discounts ``1/log2(2 + rank)`` with ranks from the
  *current* scores, ΔNDCG normalized by the query's ideal DCG;
* ``lambda = -sigma * p_ij * ΔNDCG``, ``hess = sigma^2 p (1-p) ΔNDCG``;
* pairs participate when either member ranks above the truncation level
  (LightGBM's lambdarank_truncation_level).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.params import Param, TypeConverters
from ..core.schema import DataTable, features_matrix
from .base import LightGBMBase, LightGBMModelBase
from .booster import Booster
from .objectives import Objective

#: the shortest size class: a query of up to 8 documents is padded to 8
MIN_SIZE_CLASS = 8
#: a chunk's pair tensors are laid out with the query length minor; the
#: TPU pads that dimension to its 128 lanes, so a chunk is sized by it
_LANES = 128


def _query_runs(query_ids: np.ndarray):
    """``(order, starts, counts)``: the stable sort of the rows by query
    and each query's run in it."""
    order = np.argsort(query_ids, kind="stable")
    _, starts, counts = np.unique(query_ids[order], return_index=True,
                                  return_counts=True)
    return order, starts, counts


def _padded_runs(starts: np.ndarray, counts: np.ndarray, G: int):
    """``(idx, real)``, both (queries, G): positions ``starts + 0..G-1``
    of each run (0 where padded) and which of them are the run's own."""
    pos = np.arange(G)
    real = pos[None, :] < counts[:, None]
    return np.where(real, starts[:, None] + pos[None, :], 0), real


def pack_queries(query_ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                                 np.ndarray]:
    """Group rows by query, every query padded to the longest.

    Returns (order, qidx, qmask): ``order`` sorts rows by query (stable);
    ``qidx`` is (Q, G) of positions into the *sorted* row order (0 padded);
    ``qmask`` marks real entries.
    """
    order, starts, counts = _query_runs(np.asarray(query_ids))
    qidx, real = _padded_runs(starts, counts, int(counts.max()))
    return (order.astype(np.int32), qidx.astype(np.int32),
            real.astype(np.float32))


class QueryLayout(NamedTuple):
    """The rows of a table grouped by query into size classes, on the
    host.  ``classes`` holds, per class, ``(rows, gains, labq, invmax)``:
    ``rows`` (chunks, c, G) int32 indexes the table's rows in their
    ORIGINAL order (``n`` where padded), ``gains`` and ``labq`` the
    slots' ``2^label - 1`` and labels (0 and -1 where padded), ``invmax``
    (chunks, c) each query's inverse ideal DCG.  ``slot`` (n,) is where
    each original row lies in the concatenation of the classes' flattened
    blocks.  ``pairs_useful`` is the sum over queries of their squared
    sizes, ``pairs_computed`` the slots of the classes' pair tensors."""
    n: int
    queries: int
    slot: np.ndarray
    classes: tuple
    pairs_useful: int
    pairs_computed: int


def pack_queries_by_size(labels: np.ndarray, query_ids: np.ndarray,
                         truncation_level: int, max_label: int = 31,
                         query_chunk_pairs: int = 4_000_000
                         ) -> QueryLayout:
    """Group rows by query and queries by size class (powers of two from
    :data:`MIN_SIZE_CLASS`): array operations per class, no loop over
    queries."""
    order, starts, counts = _query_runs(np.asarray(query_ids))
    n = len(order)
    labels_sorted = np.asarray(labels, np.float32)[order]
    # the next power of two: frexp's exponent of size - 1 is its bit length
    size_class = np.maximum(
        MIN_SIZE_CLASS, 1 << np.frexp(counts - 1.0)[1].astype(np.int64))
    slot = np.empty(n, np.int32)
    classes, base, computed = [], 0, 0
    for G in np.unique(size_class).tolist():
        sel = np.flatnonzero(size_class == G)
        sidx, real = _padded_runs(starts[sel], counts[sel], G)
        gains_q, lab_q, invmax = query_tensors(
            labels_sorted, sidx, real.astype(np.float32), truncation_level,
            max_label)
        rows = np.where(real, order[sidx], n).astype(np.int32)
        # chunks of equal length, so that padding adds under one query a
        # chunk; a chunk's pair tensors hold c * G * max(G, lanes) cells
        c_max = max(1, query_chunk_pairs // (G * max(G, _LANES)))
        chunks = -(-len(sel) // c_max)
        c = -(-len(sel) // chunks)
        flat = base + np.arange(len(sel) * G).reshape(len(sel), G)
        slot[rows[real]] = flat[real]
        pad = chunks * c - len(sel)

        def blocks(a, fill):
            a = np.concatenate([a, np.full((pad,) + a.shape[1:], fill,
                                           a.dtype)])
            return a.reshape((chunks, c) + a.shape[1:])

        classes.append((blocks(rows, n), blocks(gains_q, 0.0),
                        blocks(lab_q, -1.0), blocks(invmax, 0.0)))
        base += chunks * c * G
        computed += chunks * c * G * G
    return QueryLayout(n, len(starts), slot, tuple(classes),
                       int(np.sum(counts.astype(np.int64) ** 2)), computed)


def _dcg_discount(rank):
    return 1.0 / jnp.log2(2.0 + rank)


def query_tensors(labels_sorted: np.ndarray, qidx: np.ndarray,
                  qmask: np.ndarray, truncation_level: int,
                  max_label: int = 31):
    """Host-side static per-query tensors: gains, padded labels, and the
    inverse ideal DCG (shared by the serial and mesh-sharded lambdarank
    paths)."""
    Q, G = qidx.shape
    gains_row = (2.0 ** np.minimum(labels_sorted, max_label) - 1.0)
    lab_q = labels_sorted[qidx] * qmask - (1.0 - qmask)   # pad -> -1
    gains_q = gains_row[qidx] * qmask
    ideal = -np.sort(-gains_q, axis=1)
    k = min(truncation_level, G)
    disc = 1.0 / np.log2(2.0 + np.arange(G))
    max_dcg = (ideal[:, :k] * disc[:k]).sum(axis=1)
    inv_max_dcg = np.where(max_dcg > 0,
                           1.0 / np.maximum(max_dcg, 1e-12), 0.0)
    return (gains_q.astype(np.float32), lab_q.astype(np.float32),
            inv_max_dcg.astype(np.float32))


def _pair_lambdas(s, qm, gains, labs, invmax, sig: float, tr: int):
    """``(g_q, h_q)``, both (c, G): each slot's lambdarank gradient and
    hessian from the full (c, G, G) pair tensors of a chunk of queries.
    ``s`` holds the slots' current scores (-1e9 where padded), ``qm`` 1.0
    at real slots."""
    # ranks within query from current scores (descending; ties keep the
    # order of the slots: argsort is stable)
    rank_order = jnp.argsort(-s, axis=1)
    ranks = jnp.argsort(rank_order, axis=1).astype(jnp.float32)
    disc = _dcg_discount(ranks)                # (c, G)
    # pairwise tensors (c, G, G): i vs j
    better = (labs[:, :, None] > labs[:, None, :])
    in_trunc = (ranks[:, :, None] < tr) | (ranks[:, None, :] < tr)
    pair_mask = (better & in_trunc).astype(jnp.float32) * \
        qm[:, :, None] * qm[:, None, :]
    dgain = jnp.abs(gains[:, :, None] - gains[:, None, :])
    ddisc = jnp.abs(disc[:, :, None] - disc[:, None, :])
    delta = dgain * ddisc * invmax[:, None, None]
    sdiff = s[:, :, None] - s[:, None, :]
    p = jax.nn.sigmoid(-sig * sdiff)           # P(j beats i)
    lam = -sig * p * delta * pair_mask         # grad for i (winner)
    hes = sig * sig * p * (1.0 - p) * delta * pair_mask
    g_q = jnp.sum(lam, axis=2) - jnp.sum(lam, axis=1)
    h_q = jnp.sum(hes, axis=2) + jnp.sum(hes, axis=1)
    return g_q, h_q


def lambda_grad_sorted(s_sorted, qidx_c, qmask_c, gains_c, labq_c, invmax_c,
                       sigma: float, trunc: int, n: int):
    """(n,) lambdarank grad/hess for scores already sorted by query, every
    query padded to the longest (the mesh trainer's layout).

    Query tensors arrive pre-chunked ``(n_chunks, c, G)``; a ``lax.scan``
    over chunks bounds the transient (c, G, G) pairwise tensors.  Pure
    function of jax arrays — usable inside shard_map (each shard passes
    its LOCAL query structures and local sorted scores)."""
    sig, tr = float(sigma), int(trunc)

    def chunk_step(carry, args):
        g_acc, h_acc = carry
        qi, qm, gains, labs, invmax = args         # (c, G, ...)
        s = s_sorted[qi] * qm - 1e9 * (1.0 - qm)   # pad to -inf-ish
        g_q, h_q = _pair_lambdas(s, qm, gains, labs, invmax, sig, tr)
        # scatter back into sorted row order (pad slots -> dropped)
        flat_qi = jnp.where(qm > 0, qi.astype(jnp.int32), n).reshape(-1)
        g_acc = g_acc.at[flat_qi].add((g_q * qm).reshape(-1), mode="drop")
        h_acc = h_acc.at[flat_qi].add((h_q * qm).reshape(-1), mode="drop")
        return (g_acc, h_acc), None

    init = (jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.float32))
    (g_s, h_s), _ = jax.lax.scan(
        chunk_step, init, (qidx_c, qmask_c, gains_c, labq_c, invmax_c))
    return g_s, h_s


def lambda_grad_classes(scores, slot, classes, sigma: float, trunc: int):
    """(n,) lambdarank grad/hess in the rows' ORIGINAL order from a
    :class:`QueryLayout`'s arrays: one scan over chunks per size class,
    each slot's sums kept where the slot is, and one gather by ``slot``
    back to the rows (no scatter: a row lies in exactly one slot)."""
    sig, tr = float(sigma), int(trunc)
    n = scores.shape[0]

    def chunk_step(_, args):
        rows, gains, labs, invmax = args           # (c, G, ...)
        qm = (rows < n).astype(jnp.float32)
        s = scores.at[rows].get(mode="fill", fill_value=-1e9)
        g_q, h_q = _pair_lambdas(s, qm, gains, labs, invmax, sig, tr)
        return None, (g_q * qm, h_q * qm)

    g_parts, h_parts = [], []
    for blocks in classes:
        _, (g_c, h_c) = jax.lax.scan(chunk_step, None, tuple(blocks))
        g_parts.append(g_c.reshape(-1))
        h_parts.append(h_c.reshape(-1))
    return (jnp.concatenate(g_parts)[slot], jnp.concatenate(h_parts)[slot])


class LambdarankObjective(Objective):
    """lambdarank as an objective of the ordinary boost programs: its
    ``labels`` are a :class:`QueryLayout`'s device arrays ``(slot,
    classes)`` and its ``weights`` per-row multipliers of gradient and
    hessian (LightGBM's lambdarank weight semantics).  Static in those
    programs by ``sigma`` and the truncation level alone."""

    name = "lambdarank"
    model_str = "lambdarank"

    def __init__(self, sigma: float = 1.0, truncation_level: int = 30):
        self.sigma = float(sigma)
        self.truncation_level = int(truncation_level)

    def grad_hess(self, scores, labels, weights):
        slot, classes = labels
        with jax.named_scope("rank_grad"):
            g, h = lambda_grad_classes(scores, slot, classes, self.sigma,
                                       self.truncation_level)
        return g * weights, jnp.maximum(h * weights, 1e-9)


@functools.partial(jax.jit, static_argnames=("obj",))
def _lambdarank_program(scores, labels, weights, obj: LambdarankObjective):
    return obj.grad_hess(scores, labels, weights)


class LambdarankGrad:
    """A table's lambdarank gradient: the host's :class:`QueryLayout`,
    the objective and the row weights.  ``engine.train`` takes one as its
    ``grad_fn_override``, uploads the layout (:meth:`upload`, span
    ``train.rank_pack``) and hands it with :attr:`objective` to its
    ordinary programs.  Called as ``fn(scores) -> (grad, hess)`` (rows in
    their original order) it runs the same mathematics as one program of
    its own, built once a process for a set of shapes."""

    def __init__(self, layout: QueryLayout, objective: LambdarankObjective,
                 weights: Optional[np.ndarray] = None):
        self.layout = layout
        self.objective = objective
        self.weights = weights
        self._device = None

    @property
    def nbytes(self) -> int:
        """Bytes of the layout :meth:`upload` sends."""
        return int(self.layout.slot.nbytes + sum(
            a.nbytes for blocks in self.layout.classes for a in blocks))

    def upload(self):
        """``(labels, weights)`` for :meth:`LambdarankObjective.grad_hess`
        on the device."""
        lay = self.layout
        labels = (jnp.asarray(lay.slot),
                  tuple(tuple(jnp.asarray(a) for a in blocks)
                        for blocks in lay.classes))
        weights = (jnp.ones(lay.n, jnp.float32) if self.weights is None
                   else jnp.asarray(self.weights, jnp.float32))
        return labels, weights

    def __call__(self, scores):
        if self._device is None:
            self._device = self.upload()
        return _lambdarank_program(jnp.asarray(scores, jnp.float32),
                                   *self._device, obj=self.objective)


def make_lambdarank_grad_fn(labels: np.ndarray, query_ids: np.ndarray,
                            sigma: float = 1.0,
                            truncation_level: int = 30,
                            max_label: int = 31,
                            query_chunk_pairs: int = 4_000_000,
                            weights: Optional[np.ndarray] = None
                            ) -> LambdarankGrad:
    """The table's :class:`LambdarankGrad`: ``fn(scores) -> (grad, hess)``.

    ``scores`` is in original row order (n,); so are the returned grad/hess.
    ``weights`` are per-row multipliers applied to grad/hess (LightGBM
    lambdarank weight semantics).
    """
    layout = pack_queries_by_size(labels, query_ids, truncation_level,
                                  max_label, query_chunk_pairs)
    return LambdarankGrad(
        layout, LambdarankObjective(sigma, truncation_level), weights)


def shard_queries(labels: np.ndarray, query_ids: np.ndarray, n_shards: int,
                  truncation_level: int, max_label: int = 31,
                  query_chunk_pairs: int = 4_000_000, assign=None):
    """Partition whole queries across data shards (greedy row balancing).

    The mesh-sharded lambdarank layout (SURVEY.md §3.1 distributed
    lambdarank): rows are physically regrouped so every query lives on
    exactly ONE data shard; the pairwise gradient then needs no cross-
    shard communication, and tree growth stays plain data-parallel psum.

    Returns ``(perm, real, qt)``: ``perm`` (D*S,) maps packed slot → source
    row (-1 pad), ``real`` the 0/1 validity mask, and ``qt`` the per-shard
    chunked query tensors (qidx, qmask, gains, labq, invmax) with shapes
    (D*n_chunks, chunk, G)/(D*n_chunks, chunk) ready for a
    ``P('data', ...)`` sharding — each shard's qidx indexes its LOCAL
    packed rows.

    ``assign`` (optional) overrides the greedy balancer with a fixed
    query → shard map, one entry per unique query id in SORTED id order —
    the sharded-ingestion path pins each query to the shard whose host
    already holds its rows (see :func:`shard_queries_from_shards`).
    """
    order, starts, counts = _query_runs(np.asarray(query_ids))
    D = n_shards
    loads = np.zeros(D, np.int64)
    if assign is None:
        assign = np.empty(len(starts), np.int32)
        for i, c in enumerate(counts):   # greedy: least-loaded shard
            s = int(np.argmin(loads))
            assign[i] = s
            loads[s] += c
    else:
        assign = np.asarray(assign, np.int32)
        if len(assign) != len(starts):
            raise ValueError(
                f"assign has {len(assign)} entries for {len(starts)} "
                "unique queries")
        np.add.at(loads, assign, counts)
    S = int(loads.max())
    G = int(counts.max())
    qs_per_shard = np.bincount(assign, minlength=D)
    Qs = int(qs_per_shard.max()) if len(starts) else 1
    chunk = max(1, min(Qs, query_chunk_pairs // max(G * G, 1)))
    Qp = Qs + ((-Qs) % chunk)

    perm = np.full((D, S), -1, np.int64)
    qidx = np.zeros((D, Qp, G), np.int32)
    qmask = np.zeros((D, Qp, G), np.float32)
    gains = np.zeros((D, Qp, G), np.float32)
    labq = -np.ones((D, Qp, G), np.float32)
    invmax = np.zeros((D, Qp), np.float32)

    labels_sorted = np.asarray(labels, np.float32)[order]
    fill_rows = np.zeros(D, np.int64)
    fill_q = np.zeros(D, np.int64)
    for i, (st, c) in enumerate(zip(starts, counts)):
        d = assign[i]
        r0 = fill_rows[d]
        perm[d, r0:r0 + c] = order[st:st + c]
        qi = fill_q[d]
        qidx[d, qi, :c] = np.arange(r0, r0 + c)
        qmask[d, qi, :c] = 1.0
        g_q, l_q, im = query_tensors(
            labels_sorted[st:st + c],
            np.arange(c, dtype=np.int32)[None, :c],
            np.ones((1, c), np.float32), truncation_level, max_label)
        gains[d, qi, :c] = g_q[0]
        labq[d, qi, :c] = l_q[0]
        invmax[d, qi] = im[0]
        fill_rows[d] += c
        fill_q[d] += 1

    real = (perm >= 0).astype(np.float32).reshape(-1)
    qt = (qidx.reshape(D * (Qp // chunk), chunk, G),
          qmask.reshape(D * (Qp // chunk), chunk, G),
          gains.reshape(D * (Qp // chunk), chunk, G),
          labq.reshape(D * (Qp // chunk), chunk, G),
          invmax.reshape(D * (Qp // chunk), chunk))
    return perm.reshape(-1), real, qt


def shard_queries_from_shards(label_shards, qid_shards, truncation_level: int,
                              max_label: int = 31,
                              query_chunk_pairs: int = 4_000_000):
    """Query packing for SHARDED ingestion: each query stays on the shard
    whose host already holds its rows — no cross-host row movement, the
    multi-host MSLR contract (SURVEY.md §7 hard part 4: per-host readers
    deliver whole queries; the reference's distributed lambdarank likewise
    requires group-contiguous partitions).

    ``label_shards`` / ``qid_shards`` are the per-shard 1-D lists (complete
    on every controller — small metadata, like the plain sharded path's
    label lists).  A query whose id appears in two shards is an ingestion
    error and raises.

    Returns ``(perm, real, qt, offsets)``: the same global packing triple
    as :func:`shard_queries` (``perm`` in shard-concatenation row order)
    plus the per-shard row offsets, so callers can translate packed slots
    to LOCAL shard rows: ``local = perm[d*S + j] - offsets[d]``.
    """
    D = len(qid_shards)
    sizes = np.array([len(np.asarray(q)) for q in qid_shards], np.int64)
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    qids = np.concatenate([np.asarray(q) for q in qid_shards])
    labels = np.concatenate([np.asarray(l, np.float32)
                             for l in label_shards])
    if len(labels) != len(qids):
        raise ValueError(
            f"labels ({len(labels)}) and query ids ({len(qids)}) differ")
    shard_of_row = np.repeat(np.arange(D, dtype=np.int32), sizes)
    uq, inv = np.unique(qids, return_inverse=True)
    lo = np.full(len(uq), D, np.int32)
    hi = np.full(len(uq), -1, np.int32)
    np.minimum.at(lo, inv, shard_of_row)
    np.maximum.at(hi, inv, shard_of_row)
    spans = np.nonzero(lo != hi)[0]
    if len(spans):
        bad = uq[spans[0]]
        raise ValueError(
            f"query {bad!r} spans shards {lo[spans[0]]} and "
            f"{hi[spans[0]]}: sharded lambdarank requires every query's "
            "rows on ONE shard (group-contiguous ingestion)")
    perm, real, qt = shard_queries(
        labels, qids, D, truncation_level, max_label=max_label,
        query_chunk_pairs=query_chunk_pairs, assign=lo)
    return perm, real, qt, offsets


class LightGBMRanker(LightGBMBase):
    """lambdarank estimator; mirrors the reference's LightGBMRanker API.

    On one device the fit packs its queries by size class (powers of two
    from 8 documents up to the longest query: a query of more than four
    documents costs under four times its pairs) and trains through the
    ordinary boost programs with :class:`LambdarankObjective`; on a mesh
    each shard's queries are padded to its longest (module docstring).
    """

    _default_objective = "lambdarank"

    groupCol = Param("groupCol", "Column with the query/group id",
                     default="query", typeConverter=TypeConverters.toString)
    maxPosition = Param("maxPosition", "NDCG truncation level", default=30,
                        typeConverter=TypeConverters.toInt)
    sigma = Param("sigma", "Sigmoid scaling of pairwise logistic loss",
                  default=1.0, typeConverter=TypeConverters.toFloat)
    evalAt = Param("evalAt", "NDCG@k positions for evaluation",
                   default=[1, 3, 5, 10],
                   typeConverter=TypeConverters.toListInt)

    def __init__(self, **kwargs):
        kwargs.setdefault("objective", "lambdarank")
        super().__init__(**kwargs)

    def _grad_fn_override(self, table: DataTable, train_idx, y, w):
        q = np.asarray(table[self.getGroupCol()])[train_idx]
        return make_lambdarank_grad_fn(
            y, q, sigma=self.getSigma(),
            truncation_level=self.getMaxPosition(), weights=w)

    def _ranking_info(self, table: DataTable, train_idx):
        return {
            "query_ids": np.asarray(table[self.getGroupCol()])[train_idx],
            "sigma": self.getSigma(),
            "truncation_level": self.getMaxPosition(),
        }

    def _val_metric_fn(self, table: DataTable, val_mask):
        if val_mask is None or not val_mask.any():
            return None
        q_val = np.asarray(table[self.getGroupCol()])[val_mask]
        k = max(self.getEvalAt())

        def neg_ndcg(scores, labels, weights):
            return -ndcg_at_k(np.asarray(scores), np.asarray(labels),
                              q_val, k=k)
        return neg_ndcg

    def _make_model(self, booster: Booster) -> "LightGBMRankerModel":
        return LightGBMRankerModel(booster=booster)


class LightGBMRankerModel(LightGBMModelBase):

    def _transform(self, table: DataTable) -> DataTable:
        X = features_matrix(table, self.getFeaturesCol())
        pred = np.asarray(self._booster.predict_margin(X))
        out = self._with_shap(table, X)
        return out.withColumn(self.getPredictionCol(),
                              pred.astype(np.float64))


def ndcg_at_k(scores: np.ndarray, labels: np.ndarray, query_ids: np.ndarray,
              k: int = 10) -> float:
    """Mean NDCG@k across queries (evaluation helper, numpy): one sort of
    the rows by query and falling score, one by query and falling label.
    Queries of one document or one label value are left out."""
    scores, labels = np.asarray(scores), np.asarray(labels)
    _, qcode = np.unique(np.asarray(query_ids), return_inverse=True)
    n = len(qcode)
    if n == 0:
        return 0.0
    gains = 2.0 ** labels - 1
    by_score = np.lexsort((-scores, qcode))
    by_label = np.lexsort((-labels, qcode))
    counts = np.bincount(qcode)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(n) - np.repeat(starts, counts)
    disc = np.where(pos < k, 1.0 / np.log2(2 + pos), 0.0)
    dcg = np.add.reduceat(gains[by_score] * disc, starts)
    idcg = np.add.reduceat(gains[by_label] * disc, starts)
    lab_sorted = labels[by_label]
    mixed = lab_sorted[starts] != lab_sorted[starts + counts - 1]
    keep = (counts >= 2) & mixed & (idcg > 0)
    if not keep.any():
        return 0.0
    return float(np.sum(dcg[keep] / idcg[keep]) / keep.sum())
