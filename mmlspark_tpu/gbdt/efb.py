"""Exclusive Feature Bundling (EFB) — LightGBM's sparse-feature fusion.

Ke et al. 2017 §4 / LightGBM ``enable_bundle``: features that are (near-)
mutually exclusive — at most one of them non-default per row, the shape
one-hot blocks take — are merged into a single **bundle** column whose
value encodes *which* member is non-default and *its* bin.  Histogram
construction then touches ``G`` bundle columns instead of ``f`` feature
columns; per-feature histograms are recovered exactly by slicing the
bundle histogram and reconstituting each member's default bin from leaf
totals (reference path: LightGBM ``src/io/dataset.cc`` FastFeatureBundling
+ ``FeatureGroup``; expected, UNVERIFIED).  Trees still reference
ORIGINAL features — EFB is a storage/compute optimization, invisible to
split finding, model export, and prediction.

Encoding of a bundle with members ``j`` (widths ``w_j = nb_j + 1``, the
``+1`` slot carrying the member's NaN/missing bin) at offsets ``off_j``
(cumulative, starting at 1):

* all members default        → 0
* member j at value bin b    → off_j + b          (b != default_j)
* member j missing (NaN)     → off_j + nb_j

Rows violating exclusivity (allowed up to ``max_conflict_rate``) keep the
first non-default member — the same information loss LightGBM accepts.

Bundling belongs to BINNING, once (:func:`bundle_for_training`, called
by ``LightGBMBase._fit``): the plan is found from a row sample of the
table's entries (:class:`~mmlspark_tpu.gbdt.binning.SparseBins`: what a
sparse column's nonzeros bin to, or a dense table's cells other than
each column's commonest bin), the ``(n, G)`` table is written straight
from the entries, and ``engine.train`` is handed the
:class:`BundledTable` and bundles nothing.  At ``max_conflict_rate`` 0
the build checks EVERY row: members found to collide outside the
sample are moved out and bundled again among themselves from their
exact whole-table conflicts, so no row loses a value.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .binning import SparseBins, _row_blocks

#: rows of the plan's sample (LightGBM samples for bundling too)
PLAN_SAMPLE_ROWS = 50_000
#: pairs enumerated at a time when conflicts are counted
_PAIR_CHUNK = 1 << 19
#: a sample that never saw two members together says nothing of them
#: unless it would have, were they independent: expected meetings in the
#: sample of at least ``EVIDENCE`` (their absence is then chance at
#: exp(-16)).  Short of that a member joins a bundle blind, only while
#: the bundle's members are non-default in at most ``BLIND_COVER`` of
#: the rows: what the whole table then shows to collide is rows of rare
#: members, moved out cheaply (see :func:`_greedy`)
EVIDENCE = 16
BLIND_COVER = 1.0 / 8


@dataclass(frozen=True)
class BundleSpec:
    """Static bundling plan.  Per-feature arrays are tuples so the spec
    can ride a hashable ``GrowerConfig`` as a jit-static argument."""
    bundles: Tuple[Tuple[int, ...], ...]   # bundle -> member feature ids
    bundle_of: Tuple[int, ...]             # feature -> bundle id
    off_of: Tuple[int, ...]                # feature -> offset in bundle
    nb_of: Tuple[int, ...]                 # feature -> value-bin count
    default_of: Tuple[int, ...]            # feature -> default bin

    @property
    def num_bundles(self) -> int:
        return len(self.bundles)

    @property
    def num_features(self) -> int:
        return len(self.bundle_of)

    @property
    def is_trivial(self) -> bool:
        """True when no bundle holds more than one feature."""
        return all(len(b) <= 1 for b in self.bundles)


def _defaults(sb: SparseBins, num_bins: int, missing_bin: int) -> np.ndarray:
    """Each feature's default in ``sb``: its most frequent bin other
    than the missing one (the lowest on a tie; 0 where every row is
    missing), from the rows per (feature, bin)."""
    n, f = sb.shape
    counts = np.bincount(sb.indices.astype(np.int64) * num_bins + sb.bins,
                         minlength=f * num_bins).reshape(f, num_bins)
    counts[np.arange(f), sb.implicit_bin] += n - sb.column_entries()
    counts[:, missing_bin] = 0
    return counts.argmax(axis=1)


def _non_default(sb: SparseBins, default_of: np.ndarray,
                 columns: Optional[np.ndarray] = None) -> SparseBins:
    """``sb`` with every feature's default bin left implicit and only
    non-default cells as entries (of ``columns`` alone, a bool mask,
    where given).  A feature whose implicit bin is not its default has
    its implicit cells written out first."""
    n, f = sb.shape
    # (a column with an entry in every row has no implicit cell)
    off = np.flatnonzero((sb.implicit_bin != default_of)
                         & (sb.column_entries() < n))
    if columns is not None:
        off = off[columns[off]]
    if off.size:
        # rare (a column mostly missing, a sample unlike its table):
        # those columns dense, every cell an entry, then all by row
        block = np.empty((n, off.size), sb.bins.dtype)
        block[:] = sb.implicit_bin[off][None, :]
        slot = np.full(f, -1, np.int64)
        slot[off] = np.arange(off.size)
        rows = sb.row_ids()
        mine = slot[sb.indices] >= 0
        block[rows[mine], slot[sb.indices[mine]]] = sb.bins[mine]
        rows = np.concatenate([rows[~mine], np.repeat(np.arange(n),
                                                      off.size)])
        cols = np.concatenate([sb.indices[~mine],
                               np.tile(off, n).astype(sb.indices.dtype)])
        bins = np.concatenate([sb.bins[~mine], block.reshape(-1)])
        order = np.argsort(rows, kind="stable")
        rows, cols, bins = rows[order], cols[order], bins[order]
    else:
        rows, cols, bins = None, sb.indices, sb.bins

    default_b = np.asarray(default_of, bins.dtype)

    def keep_block(a, b):
        lo, hi = (sb.indptr[a], sb.indptr[b]) if rows is None else \
            np.searchsorted(rows, [a, b])
        c = cols[lo:hi]
        if columns is not None:
            # few columns asked for: pick their entries first
            at = np.flatnonzero(columns[c])
            c = c[at]
            k = at[bins[lo:hi][at] != default_b[c]]
        else:
            k = np.flatnonzero(bins[lo:hi] != default_b[c])
        r = (np.repeat(np.arange(a, b), np.diff(sb.indptr[a:b + 1]))[k]
             if rows is None else rows[lo:hi][k])
        return r, cols[lo:hi][k], bins[lo:hi][k]

    parts = _row_blocks(keep_block, n)
    r = np.concatenate([p[0] for p in parts]) if parts else np.zeros(0, int)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(r, minlength=n), out=indptr[1:])
    return SparseBins(indptr, np.concatenate([p[1] for p in parts]),
                      np.concatenate([p[2] for p in parts]),
                      np.asarray(default_of, sb.bins.dtype), (n, f))


def _pair_counts(nd: SparseBins) -> np.ndarray:
    """``(f, f)`` int64: rows in which both features are non-default
    (the diagonal: a feature's own non-default rows).  Every ordered
    pair of a row's entries, some rows at a time."""
    n, f = nd.shape
    conf = np.zeros(f * f, np.int64)
    per_row = np.diff(nd.indptr)
    pairs_to = np.cumsum(per_row * per_row)
    a = 0
    while a < n:
        b = int(np.searchsorted(pairs_to, (pairs_to[a - 1] if a else 0)
                                + _PAIR_CHUNK, side="right"))
        b = min(max(b, a + 1), n)
        lo, hi = nd.indptr[a], nd.indptr[b]
        k = per_row[a:b]
        cols = nd.indices[lo:hi].astype(np.int64)
        partners = np.repeat(k, k)                 # per entry
        left = np.repeat(np.arange(hi - lo), partners)
        first = np.repeat(nd.indptr[a:b] - lo, k)  # per entry: row's start
        right = (np.arange(left.size)
                 - np.repeat(np.cumsum(partners) - partners, partners)
                 + np.repeat(first, partners))
        conf += np.bincount(cols[left] * f + cols[right], minlength=f * f)
        a = b
    return conf.reshape(f, f)


def _greedy(order: Sequence[int], width_of: np.ndarray,
            conflicts: np.ndarray, budget: float,
            max_bundle_bins: int,
            sample_rows: Optional[int] = None) -> List[List[int]]:
    """GreedyBundle: each feature of ``order`` into the first bundle
    where the added pairwise conflicts stay within ``budget`` and the
    encoded width below ``max_bundle_bins``, else into a new one.
    ``conflicts`` is indexed by feature, its diagonal a feature's own
    non-default rows.

    ``sample_rows``: the conflicts were counted on a sample of so many
    rows, not on the table.  No conflict seen is then evidence only
    where independent columns would have met in the sample (their
    expected meetings reach ``EVIDENCE``); short of that a feature joins a bundle
    blind, which is allowed while the bundle covers at most
    ``BLIND_COVER`` of the rows: a year column that is non-default in
    most rows does not take in members the sample never saw."""
    f = conflicts.shape[0]
    density = np.diagonal(conflicts)
    bundles: List[List[int]] = []
    with_bundle = np.zeros((64, f), np.int64)   # conflicts with a bundle
    used = np.zeros(64, np.float64)
    widths = np.zeros(64, np.int64)
    cover = np.zeros(64, np.int64)              # members' non-default rows
    for j in order:
        G = len(bundles)
        add = with_bundle[:G, j]
        fits = ((used[:G] + add <= budget)
                & (widths[:G] + width_of[j] < max_bundle_bins))
        if sample_rows is not None:
            fits &= ((density[j] * cover[:G] >= EVIDENCE * sample_rows)
                     | (cover[:G] + density[j] <= BLIND_COVER * sample_rows))
        ok = np.flatnonzero(fits)
        if ok.size:
            g = int(ok[0])
            bundles[g].append(int(j))
            used[g] += add[g]
            widths[g] += width_of[j]
        else:
            g = G
            if g == len(used):
                with_bundle = np.concatenate(
                    [with_bundle, np.zeros_like(with_bundle)])
                used, widths, cover = (np.concatenate([a, np.zeros_like(a)])
                                       for a in (used, widths, cover))
            bundles.append([int(j)])
            widths[g] = 1 + width_of[j]       # slot 0 = all-default
        with_bundle[g] += conflicts[j]
        cover[g] += density[j]
    return bundles


def _spec_of(bundles: List[List[int]], nb_of: Sequence[int],
             default_of: Sequence[int], max_bundle_bins: int) -> BundleSpec:
    f = len(nb_of)
    bundle_of = np.zeros(f, np.int64)
    off_of = np.zeros(f, np.int64)
    eff_nb = np.asarray(nb_of, np.int64).copy()
    for g, members in enumerate(bundles):
        bundle_of[members] = g
        if len(members) == 1:
            # solo features keep IDENTITY encoding (offset 0, nb spanning
            # the whole bin range so the missing bin passes through) —
            # a dense 255-bin feature re-encoded with an offset would
            # overflow the uint8 bundle range
            eff_nb[members[0]] = max_bundle_bins - 1
            continue
        off = 1
        for j in members:
            off_of[j] = off
            off += nb_of[j] + 1
    return BundleSpec(
        bundles=tuple(tuple(int(j) for j in m) for m in bundles),
        bundle_of=tuple(int(x) for x in bundle_of),
        off_of=tuple(int(x) for x in off_of),
        nb_of=tuple(int(x) for x in eff_nb),
        default_of=tuple(int(x) for x in default_of))


def plan_bundles(sb: SparseBins, nb_of: Sequence[int], missing_bin: int,
                 max_conflict_rate: float = 0.0,
                 max_bundle_bins: int = 256,
                 sample_cnt: Optional[int] = None,
                 seed: int = 0) -> BundleSpec:
    """Greedy bundling plan from a row sample of the table's entries
    (GreedyBundle analog).

    ``nb_of[j]``: value bins actually used by feature j (excl. missing).
    Features are scanned by non-default density (densest first, LightGBM
    order); one goes into the first bundle where (a) the added pairwise
    conflicts stay within ``max_conflict_rate`` of the sample and (b) the
    bundle's total encoded width stays below ``max_bundle_bins``.
    """
    idx = _plan_rows(sb.shape[0], sample_cnt, seed)
    return _plan(sb if idx is None else sb.take_rows(idx), nb_of,
                 missing_bin, max_conflict_rate, max_bundle_bins)


def _plan_rows(n: int, sample_cnt: Optional[int], seed: int):
    """The rows a plan is found from: a seeded sample, in row order, or
    None for all of them."""
    sample_cnt = sample_cnt or PLAN_SAMPLE_ROWS
    if n <= sample_cnt:
        return None
    idx = np.random.default_rng(seed).choice(n, sample_cnt, replace=False)
    idx.sort()
    return idx


def _plan(sb: SparseBins, nb_of: Sequence[int], missing_bin: int,
          max_conflict_rate: float, max_bundle_bins: int) -> BundleSpec:
    """:func:`plan_bundles` on the rows given, all of them."""
    default_of = _defaults(sb, max_bundle_bins, missing_bin)
    conflicts = _pair_counts(_non_default(sb, default_of))
    order = np.argsort(-np.diagonal(conflicts), kind="stable")
    bundles = _greedy(order, np.asarray(nb_of, np.int64) + 1,  # + missing
                      conflicts, max_conflict_rate * sb.shape[0],
                      max_bundle_bins, sample_rows=sb.shape[0])
    return _spec_of(bundles, nb_of, default_of, max_bundle_bins)


def find_bundles(bins: np.ndarray, nb_of: List[int], missing_bin: int,
                 max_conflict_rate: float = 0.0,
                 max_bundle_bins: int = 256,
                 sample_cnt: Optional[int] = None,
                 seed: int = 0) -> BundleSpec:
    """:func:`plan_bundles` of a dense ``(n, f)`` binned matrix."""
    return plan_bundles(SparseBins.from_dense(bins), nb_of, missing_bin,
                        max_conflict_rate, max_bundle_bins, sample_cnt,
                        seed)


def write_bundles(sb: SparseBins, spec: BundleSpec, missing_bin: int):
    """The ``(n, G)`` uint8 bundled table, written from the entries, and
    what the check of EVERY row found: ``(table, conflict_rows,
    collisions)``.  First non-default member (the lowest feature) wins a
    cell two members claim; ``conflict_rows`` counts the rows that lost
    a value so, ``collisions`` is the ``(k, 2)`` array of (winner,
    loser) member pairs."""
    n, f = sb.shape
    G = spec.num_bundles
    B = 256
    default_of = np.asarray(spec.default_of, np.int64)
    solo = np.zeros(f, bool)
    solo[[m[0] for m in spec.bundles if len(m) == 1]] = True
    # a bundled member's implicit cells have to be its default cells
    if (~solo & (sb.implicit_bin != default_of)
            & (sb.column_entries() < n)).any():
        sb = _non_default(sb, np.where(solo, sb.implicit_bin, default_of))
    bundle_of = np.asarray(spec.bundle_of, np.int32)
    # what an entry (feature, bin) writes: a solo column its bin, a
    # member its slot, 0 for a member's default (nothing to write)
    enc_of = np.zeros((f, B), np.uint8)
    member_at = np.full((G, B), -1, np.int32)     # (bundle, slot) -> member
    for j in range(f):
        if solo[j]:
            enc_of[j] = np.arange(B)
            continue
        off, nb = spec.off_of[j], spec.nb_of[j]
        enc_of[j, :nb] = off + np.arange(nb)
        enc_of[j, missing_bin] = off + nb
        enc_of[j, default_of[j]] = 0
        member_at[bundle_of[j], off:off + nb + 1] = j
    enc_flat = enc_of.reshape(-1)
    met = np.zeros(f * f, bool)                   # winner * f + loser
    out = np.zeros((n, G), np.uint8)
    for m in spec.bundles:
        if len(m) == 1:
            out[:, bundle_of[m[0]]] = sb.implicit_bin[m[0]]
    block_rows = max(1, min(1 << 18, (2 ** 31 - 1) // max(G, 1)))

    def block(a, b):
        lo, hi = sb.indptr[a], sb.indptr[b]
        cols = sb.indices[lo:hi]
        enc = enc_flat[(cols.astype(np.int32) << 8) | sb.bins[lo:hi]]
        row = np.repeat(np.arange(b - a, dtype=np.int32),
                        np.diff(sb.indptr[a:b + 1]))
        cell = row * np.int32(G) + bundle_of[cols]
        live = solo[cols] | (enc != 0)
        if not live.all():
            live = np.flatnonzero(live)
            cell, cols, enc, row = cell[live], cols[live], enc[live], \
                row[live]
        flat = out[a:b].reshape(-1)
        # entries run by row and, within a row, by feature: written
        # backwards, a cell keeps its first
        flat[cell[::-1]] = enc[::-1]
        lost = np.flatnonzero(flat[cell] != enc)
        if not lost.size:
            return 0
        winner = member_at.reshape(-1)[
            (bundle_of[cols[lost]] << 8) | flat[cell[lost]]]
        met[winner.astype(np.int64) * f + cols[lost]] = True
        rows_hit = np.zeros(b - a, bool)
        rows_hit[row[lost]] = True
        return int(rows_hit.sum())

    conflict_rows = sum(_row_blocks(block, n, block_rows))
    pairs = np.flatnonzero(met)
    return out, int(conflict_rows), np.stack([pairs // f, pairs % f], 1)


def bundle_matrix(bins: np.ndarray, spec: BundleSpec,
                  missing_bin: int) -> np.ndarray:
    """(n, f) binned matrix → (n, G) bundled matrix (uint8).

    First non-default member wins on (rare, budgeted) conflict rows."""
    return write_bundles(SparseBins.from_dense(bins), spec, missing_bin)[0]


def _cover(pairs: np.ndarray) -> List[int]:
    """Members to move out so that no colliding pair stays together:
    the member with the most colliding partners first, until no pair is
    left (a greedy vertex cover)."""
    out: List[int] = []
    while len(pairs):
        who, partners = np.unique(pairs, return_counts=True)
        v = int(who[np.argmax(partners)])
        out.append(v)
        pairs = pairs[(pairs != v).all(axis=1)]
    return out


def _move_out(spec: BundleSpec, movers: List[int], sb: SparseBins,
              nb_of: Sequence[int], max_bundle_bins: int) -> BundleSpec:
    """``spec`` with ``movers`` taken out of their bundles and bundled
    among themselves from their EXACT conflicts over the whole table
    (budget 0), the new bundles after the old."""
    f = spec.num_features
    moving = np.zeros(f, bool)
    moving[movers] = True
    default_of = np.asarray(spec.default_of, np.int64)
    conflicts = _pair_counts(_non_default(sb, default_of, columns=moving))
    order = np.asarray(movers)[np.argsort(
        -np.diagonal(conflicts)[movers], kind="stable")]
    kept = [[j for j in m if not moving[j]] for m in spec.bundles]
    bundles = [m for m in kept if m] + _greedy(
        order, np.asarray(nb_of, np.int64) + 1, conflicts, 0.0,
        max_bundle_bins)
    return _spec_of(bundles, nb_of, spec.default_of, max_bundle_bins)


def build_bundled(sb: SparseBins, spec: BundleSpec, nb_of: Sequence[int],
                  missing_bin: int, max_conflict_rate: float = 0.0,
                  max_bundle_bins: int = 256):
    """``(table, spec, conflict_rows, moved)``: the bundled table of the
    WHOLE of ``sb``.  With a conflict budget the plan stands and
    ``conflict_rows`` says how many rows lost a value to it.  At
    ``max_conflict_rate`` 0 members that collide anywhere, seen by the
    plan's sample or not, are moved out (``moved`` of them) and the
    table written again, until no row loses a value."""
    moved = 0
    for _ in range(8):
        table, conflict_rows, pairs = write_bundles(sb, spec, missing_bin)
        if max_conflict_rate > 0 or not len(pairs):
            return table, spec, conflict_rows, moved
        movers = _cover(pairs)
        moved += len(movers)
        spec = _move_out(spec, movers, sb, nb_of, max_bundle_bins)
    raise RuntimeError("bundles still collide after 8 rounds of moving "
                       "members out")


@dataclass
class BundledTable:
    """What ``engine.train`` takes in place of ``(n, f)`` bins when the
    table was bundled at binning time: the ``(n, G)`` uint8 ``table``
    and its plan.  ``shape`` is the table's in ORIGINAL features, which
    trees, thresholds and export keep."""

    table: np.ndarray
    spec: BundleSpec
    num_bins: int
    missing_bin: int
    conflict_rows: int = 0
    moved: int = 0
    _maps: Optional[tuple] = field(default=None, repr=False)

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.table.shape[0], self.spec.num_features)

    def __len__(self) -> int:
        return self.table.shape[0]

    def __getitem__(self, rows) -> "BundledTable":
        return BundledTable(self.table[rows], self.spec, self.num_bins,
                            self.missing_bin, self.conflict_rows,
                            self.moved, self._maps)

    def maps(self) -> tuple:
        """:func:`expansion_arrays` of the plan, made once."""
        if self._maps is None:
            self._maps = expansion_arrays(self.spec, self.num_bins,
                                          self.missing_bin)
        return self._maps


def bundling_applies(mapper, enable_bundle: bool, ranker: bool = False,
                     mesh=None, voting: bool = False,
                     goss: bool = False, dart: bool = False) -> bool:
    """Whether a fit's table is bundled: the gate, decided where the
    plan is made.  Bundles hold at most 256 encoded bins (uint8) and
    numeric columns; a ranker's table is never bundled; on a mesh the
    features must not be sharded (a bundle would be split), and voting,
    goss and dart there address the table by original feature."""
    if not enable_bundle or mapper.has_categorical \
            or mapper.num_total_bins > 256 or ranker:
        return False
    if mesh is not None and mesh.devices.size > 1:
        from ..core.mesh import FEATURE_AXIS
        if int(dict(mesh.shape).get(FEATURE_AXIS, 1)) > 1 or voting \
                or goss or dart:
            return False
    return True


def bundle_for_training(binned, mapper, max_conflict_rate: float = 0.0,
                        seed: int = 0, verbosity: int = 0
                        ) -> Optional[BundledTable]:
    """Plan and build the bundled table of a fit, once, at binning time.
    ``binned``: the table's :class:`SparseBins` (a sparse column's,
    ``mapper.bin_entries``) or its dense ``(n, f)`` bins.  None where no
    bundle would hold two features."""
    from ..core.profiler import get_profiler
    dense = not isinstance(binned, SparseBins)
    n, f = binned.shape
    nb_of = [mapper.feature_num_bins(j) for j in range(f)]
    B = mapper.num_total_bins
    with get_profiler().region("bin.bundle_plan", rows=int(n)) as sp:
        # a dense table's sample alone is turned into entries: a table
        # of dense columns plans no bundle and is never converted whole
        idx = _plan_rows(n, None, seed)
        if dense:
            sample = SparseBins.from_dense(binned if idx is None
                                           else binned[idx])
        else:
            sample = binned if idx is None else binned.take_rows(idx)
        spec = _plan(sample, nb_of, mapper.missing_bin, max_conflict_rate,
                     B)
        sp.update(bundles=spec.num_bundles, nnz=int(sample.bins.size))
    if spec.is_trivial:
        return None
    sb = SparseBins.from_dense(binned) if dense else binned
    with get_profiler().region("bin.bundle_build", rows=int(n),
                               nnz=int(sb.bins.size)) as sp:
        table, spec, conflict_rows, moved = build_bundled(
            sb, spec, nb_of, mapper.missing_bin, max_conflict_rate, B)
        sp.update(bytes=int(table.nbytes), bundles=spec.num_bundles,
                  moved=moved, conflict_rows=conflict_rows)
    if verbosity > 0:
        import logging
        logging.getLogger("mmlspark_tpu.gbdt").info(
            "EFB: %d features -> %d bundle columns (%d members moved out "
            "of the sample's plan)", f, spec.num_bundles, moved)
    return BundledTable(table, spec, B, mapper.missing_bin, conflict_rows,
                        moved)


def expand_counts(counts_b: np.ndarray, maps: tuple, rows: int) -> np.ndarray:
    """Rows per (bundle, bundle bin) to rows per (feature, bin): the
    count channel of ``grower._efb_expand`` on the host, each member's
    default bin the rows its explicit bins leave."""
    gather_idx, valid, _, _, _, default_of = maps
    counts = np.asarray(counts_b, np.int64).reshape(-1)[gather_idx] * valid
    counts[np.arange(len(counts)), default_of] += rows - counts.sum(axis=1)
    return counts


def decode_rows(table_rows: np.ndarray, maps: tuple,
                missing_bin: int) -> np.ndarray:
    """Bundled rows ``(r, G)`` back to ``(r, f)`` bins
    (``grower.efb_feature_column`` for every feature, on the host)."""
    _, _, bundle_of, off_of, nb_of, default_of = maps
    raw = table_rows[:, bundle_of].astype(np.int64) - off_of[None, :]
    inr = (raw >= 0) & (raw <= nb_of[None, :])
    return np.where(inr, np.where(raw == nb_of[None, :], missing_bin, raw),
                    default_of[None, :])


def expansion_arrays(spec: BundleSpec, num_bins: int, missing_bin: int):
    """Static numpy index maps for in-jit histogram expansion and split-
    column reconstruction.

    Returns ``(gather_idx, valid, bundle_of, off_of, nb_of, default_of)``
    where ``gather_idx[j, b]`` flat-indexes (bundle, bundle_bin) for
    original feature j's bin b (missing bin included), and ``valid``
    masks bins feature j doesn't use."""
    f, B = spec.num_features, num_bins
    gather_idx = np.zeros((f, B), np.int64)
    valid = np.zeros((f, B), bool)
    solo = {g for g, m in enumerate(spec.bundles) if len(m) == 1}
    for j in range(spec.num_features):
        g, off, nb = spec.bundle_of[j], spec.off_of[j], spec.nb_of[j]
        if g in solo:
            # identity mapping: the bundle column IS the feature column,
            # so every bin (default and missing included) carries its own
            # mass and the deficit correction contributes exactly zero
            gather_idx[j] = g * B + np.arange(B)
            valid[j] = True
            continue
        for b in range(nb):
            gather_idx[j, b] = g * B + off + b
            valid[j, b] = True
        gather_idx[j, missing_bin] = g * B + off + nb
        valid[j, missing_bin] = True
        # the default bin's slot (off + default) never receives rows —
        # its mass is reconstituted from leaf totals by the caller
    return (gather_idx, valid,
            np.asarray(spec.bundle_of, np.int32),
            np.asarray(spec.off_of, np.int32),
            np.asarray(spec.nb_of, np.int32),
            np.asarray(spec.default_of, np.int32))
