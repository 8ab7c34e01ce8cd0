"""The reference profile's rollup, a group of features at a time, against
the per-feature loop it replaced (ISSUE 37).

The oracle below is that loop as ``core/sketch.build_reference_profile``
ran it up to PR 36, kept here only: the profile built from the same
counts has to be the same, byte for byte in ``to_json()`` (with
``meta.created`` set alike) and sketch for sketch through
``ref_feature``.
"""

import numpy as np
import pytest

from mmlspark_tpu.core.sketch import (MAX_PROFILE_EDGES, ReferenceProfile,
                                      StreamSketch, build_reference_profile)
from mmlspark_tpu.gbdt.binning import BinMapper, fit_bin_mapper


def _oracle_downsample(edges, max_edges):
    edges = np.asarray(edges, np.float64)
    if len(edges) <= max_edges:
        return edges
    idx = np.unique(np.linspace(0, len(edges) - 1, max_edges)
                    .round().astype(np.int64))
    return edges[idx]


def per_feature_profile(bins, mapper, margins=None, max_edges=31,
                        margin_buckets=32, fine_counts=None):
    """``build_reference_profile`` as it was: one feature at a time."""
    n, f = np.shape(bins)
    if fine_counts is None:
        table, nb = np.asarray(bins), mapper.num_total_bins
        fine_counts = np.zeros((f, nb), np.int64)
        for j in range(f):
            fine_counts[j] = np.bincount(
                np.ascontiguousarray(table[:, j]), minlength=nb)[:nb]
    fine_counts = np.asarray(fine_counts, np.int64)
    edges_list, sketches = [], []
    for j in range(f):
        ub = mapper.upper_bounds[j]
        if mapper.is_categorical(j) or len(ub) == 0:
            edges = np.empty(0, np.float64)
        else:
            edges = _oracle_downsample(ub, max_edges)
        lo, hi = ((float(edges[0]), float(edges[-1]))
                  if len(edges) else (None, None))
        sk = StreamSketch(edges, lo, hi)
        fine = fine_counts[j]
        sk.nan = int(fine[mapper.missing_bin])
        if mapper.is_categorical(j):
            finite = int(fine[:mapper.missing_bin].sum())
            sk.counts[0] = finite
            sk.count = finite
        else:
            value_bins = fine[:len(ub) + 1]
            if len(edges):
                idx = np.searchsorted(ub, edges, side="left")
                coarse_of_fine = np.searchsorted(
                    idx, np.arange(len(ub) + 1), side="left")
                sk.counts += np.bincount(
                    coarse_of_fine, weights=value_bins,
                    minlength=len(sk.counts)).astype(np.int64)
            else:
                sk.counts[0] = int(value_bins.sum())
            sk.count = int(value_bins.sum())
        edges_list.append(edges)
        sketches.append(sk.snapshot())
    if margins is not None and np.asarray(margins).size:
        mg = np.asarray(margins, np.float64).ravel()
        mg = mg[np.isfinite(mg)]
        qs = np.linspace(0.0, 1.0, margin_buckets + 1)[1:-1]
        medges = np.unique(np.quantile(mg, qs)) if mg.size \
            else np.empty(0, np.float64)
        msk = StreamSketch(medges)
        msk.update(mg)
    else:
        medges = np.empty(0, np.float64)
        msk = StreamSketch(medges)
    return ReferenceProfile(edges_list, sketches, medges, msk.snapshot(),
                            meta={"n_rows": int(n), "created": 0.0})


def _table(shape):
    """A stand-in for the binned table: with the counts given, the
    profile reads its shape alone."""
    return np.broadcast_to(np.uint8(0), shape)


def _mapper(ladders, nb=256, categorical=None):
    cat = None if categorical is None else np.asarray(categorical, bool)
    return BinMapper(
        upper_bounds=[np.asarray(u, np.float64) for u in ladders],
        has_missing=np.ones(len(ladders), bool), num_total_bins=nb,
        missing_bin=nb - 1, categorical=cat,
        cat_values=None if cat is None else
        [np.arange(len(u) + 1) if c else None
         for u, c in zip(ladders, cat)])


def _ladder(rng, length):
    return np.sort(rng.choice(np.arange(-5000, 5000) / 7.0, length,
                              replace=False))


def _counts(rng, mapper, empty_share=0.0):
    """Fine counts where only bins a feature can reach hold rows."""
    f, nb = mapper.num_features, mapper.num_total_bins
    fine = rng.integers(0, 10_000, (f, nb)).astype(np.int64)
    for j in range(f):
        top = (mapper.missing_bin if mapper.is_categorical(j)
               else len(mapper.upper_bounds[j]) + 1)
        fine[j, top:mapper.missing_bin] = 0
    fine[rng.random(fine.shape) < empty_share] = 0
    return fine


def _case(name, rng):
    """(mapper, fine_counts, margins) of one exactness case."""
    margins = rng.normal(size=4096)
    if name == "equal_2000":
        m = _mapper([_ladder(rng, 254) for _ in range(2000)])
    elif name == "mixed_lengths":
        m = _mapper([_ladder(rng, L) for L in
                     (0, 1, 5, 30, 31, 32, 33, 100, 254, 0, 31, 254)])
    elif name == "categorical_mix":
        # criteo's mapper: counts and hashed categories side by side
        ladders = [_ladder(rng, L) for L in (3, 40, 254, 12)] + \
            [np.arange(L, dtype=np.float64) for L in (2, 254, 90, 0)]
        m = _mapper(ladders, categorical=[False] * 4 + [True] * 4)
    elif name == "empty_bins":
        m = _mapper([_ladder(rng, L) for L in (254, 254, 31, 64, 7)])
        return m, _counts(rng, m, empty_share=0.7), margins
    elif name == "all_missing":
        m = _mapper([_ladder(rng, L) for L in (254, 10, 0)])
        fine = _counts(rng, m)
        fine[0] = 0
        fine[0, m.missing_bin] = 12_345
        return m, fine, margins
    elif name == "repeated_bound":
        # a ladder no binning cuts, as a mapper loaded from JSON may hold
        lad = [np.repeat(_ladder(rng, 127), 2),
               np.array([1.0, 2.0, 2.0, 2.0, 3.0]),
               np.array([4.0, 4.0]), _ladder(rng, 254)]
        m = _mapper(lad)
    elif name == "no_margins":
        m = _mapper([_ladder(rng, L) for L in (254, 31, 0)])
        return m, _counts(rng, m), None
    else:
        raise AssertionError(name)
    return m, _counts(rng, m), margins


def _assert_same(new, old):
    new.meta["created"] = old.meta["created"] = 0.0
    assert new.to_json() == old.to_json()
    for j in range(old.num_features):
        a, b = new.ref_feature(j), old.ref_feature(j)
        np.testing.assert_array_equal(a.edges, b.edges)
        assert (a.lo, a.hi) == (b.lo, b.hi)
        assert a.snapshot() == b.snapshot()
        np.testing.assert_array_equal(a.counts, b.counts)


CASES = ["equal_2000", "mixed_lengths", "categorical_mix", "empty_bins",
         "all_missing", "repeated_bound", "no_margins"]


@pytest.mark.parametrize("name", CASES)
def test_rollup_equals_per_feature_loop(name):
    rng = np.random.default_rng(CASES.index(name))
    mapper, fine, margins = _case(name, rng)
    shape = (int(fine[0].sum()), mapper.num_features)
    new = build_reference_profile(_table(shape), mapper, margins,
                                  fine_counts=fine)
    old = per_feature_profile(_table(shape), mapper, margins,
                              fine_counts=fine)
    _assert_same(new, old)
    # ints as Python ints, moments as floats: what JSON writes
    snap = new.feature_sketches[0]
    assert type(snap["n"]) is int and type(snap["nan"]) is int
    assert all(type(c) is int for c in snap["buckets"].values())
    assert snap["mean"] == 0.0 and type(snap["mean"]) is float


@pytest.mark.parametrize("max_edges", [0, 1, 2, MAX_PROFILE_EDGES, 64])
def test_rollup_at_other_edge_caps(max_edges):
    # past MAX_PROFILE_EDGES the bucket keys are made for the call
    rng = np.random.default_rng(100 + max_edges)
    mapper = _mapper([_ladder(rng, L) for L in (254, 100, 64, 3, 0)])
    fine = _counts(rng, mapper, empty_share=0.2)
    shape = (int(fine[0].sum()), mapper.num_features)
    _assert_same(
        build_reference_profile(_table(shape), mapper, None,
                                max_edges=max_edges, fine_counts=fine),
        per_feature_profile(_table(shape), mapper, None,
                            max_edges=max_edges, fine_counts=fine))


def test_rollup_of_a_fitted_mapper_counted_on_the_host():
    # a mapper binning cut, its table counted here (fine_counts absent)
    rng = np.random.default_rng(7)
    X = rng.normal(size=(3000, 6))
    X[:, 1] = rng.integers(0, 4, 3000)        # few distinct values
    X[:, 2] = rng.integers(0, 40, 3000)       # categorical
    X[rng.random(X.shape) < 0.05] = np.nan
    X[:, 5] = np.nan                          # all missing
    mapper = fit_bin_mapper(X, max_bin=63, categorical_features=[2])
    bins = mapper.transform(X)
    margins = rng.normal(size=3000)
    _assert_same(build_reference_profile(bins, mapper, margins),
                 per_feature_profile(bins, mapper, margins))


def test_profile_json_round_trip_keeps_the_rollup():
    rng = np.random.default_rng(3)
    mapper, fine, margins = _case("mixed_lengths", rng)
    shape = (int(fine[0].sum()), mapper.num_features)
    prof = build_reference_profile(_table(shape), mapper, margins,
                                   fine_counts=fine)
    back = ReferenceProfile.from_json(prof.to_json())
    assert back.to_json() == prof.to_json()
