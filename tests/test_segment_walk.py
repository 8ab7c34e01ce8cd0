"""A leaf's segment over ``grower.SEGMENT_CHUNK_ROWS`` rows is histogrammed
chunk by chunk (``grower._segment_hist``): the same histogram as a rung
that held it whole, after the same stable partition
(``grower._partition_switch``, on its power-of-two rungs).  XLA's path
runs on the CPU under ``hist_method`` ``segment``; the tests shrink the
chunk to 16 rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from mmlspark_tpu.core.mesh import DATA_AXIS
from mmlspark_tpu.gbdt import grower
from mmlspark_tpu.gbdt.grower import GrowerConfig
from mmlspark_tpu.ops.histogram import compute_histogram

C, N, F, B = 16, 100, 5, 16


@pytest.fixture
def chunk_rows(monkeypatch):
    """Sets the top rung.  The constant is read when a program is traced
    and is no part of a jitted function's cache key, so programs traced
    under another value must not be found again, here or by a later
    test."""
    def set_rows(rows):
        monkeypatch.setattr(grower, "SEGMENT_CHUNK_ROWS", rows)
        jax.clear_caches()
    yield set_rows
    jax.clear_caches()


def _table(seed=0):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B, (N, F)).astype(np.uint8)
    gh = rng.normal(size=(N, 3)).astype(np.float32)
    return rng, bins, gh


def _walk(cfg, bins, gh, col, thr, use_cat, bits):
    """Partition ``row_order[off : off + cnt]`` and histogram it."""
    def fn(order, off, cnt):
        out = grower._partition_switch(
            order, jnp.asarray(col), off, cnt, thr, jnp.asarray(use_cat),
            jnp.asarray(bits), N, grower._bucket_sizes(N, cfg), cfg)
        hist = grower._segment_hist(jnp.asarray(bins), jnp.asarray(gh),
                                    order, off, cnt, N,
                                    grower._build_sizes(N, cfg), cfg)
        return out, hist
    return fn


def _segment(rng, off, cnt):
    """A ``row_order`` whose segment ascends in row id, as every leaf's
    does, among other leaves' rows and the sentinels."""
    order = np.concatenate([rng.permutation(N),
                            np.full(128, N)]).astype(np.int32)
    order[off:off + cnt] = np.sort(order[off:off + cnt])
    return order


def _expected(order, off, cnt, goes_left):
    seg = order[off:off + cnt]
    left = goes_left[seg]
    want = order.copy()
    want[off:off + cnt] = np.concatenate([seg[left], seg[~left]])
    return want, int(left.sum()), int((~left).sum())


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int"])
@pytest.mark.parametrize("split", ["numeric", "bitset"])
@pytest.mark.parametrize("cnt", [0, 1, C - 1, C, C + 1, 3 * C, 3 * C + 7, N])
def test_chunk_walk_is_the_whole_segments_partition_and_histogram(
        chunk_rows, cnt, split, quantized):
    chunk_rows(C)
    cfg = GrowerConfig(num_bins=B, min_bucket=4, hist_method="segment",
                       use_categorical=(split == "bitset"))
    assert grower._build_sizes(N, cfg) == [4, 8, 16]
    assert grower._bucket_sizes(N, cfg) == [4, 8, 16, 32, 64, 128]
    rng, bins, gh = _table()
    if quantized:
        gh = rng.integers(-127, 128, (N, 3)).astype(np.int16)
    col, thr = bins[:, 2], 7
    members = np.asarray([1, 2, 3, 5, 8, 13])
    bits = np.zeros(cfg.cat_words, np.uint32)
    bits[0] = np.sum(1 << members)
    goes_left = np.isin(col, members) if split == "bitset" else col <= thr
    off = 0 if cnt == N else 11          # a neighbour's rows in the tail
    order = _segment(rng, off, cnt)
    (got, cnt_l, cnt_r), hist = jax.jit(_walk(
        cfg, bins, gh, col, thr, split == "bitset", bits))(
        order, off, cnt)
    want, want_l, want_r = _expected(order, off, cnt, goes_left)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert (int(cnt_l), int(cnt_r)) == (want_l, want_r)
    seg = order[off:off + cnt]
    whole = np.asarray(compute_histogram(
        jnp.asarray(bins[seg]), jnp.asarray(gh[seg]), B, method="segment"))
    if quantized:
        assert hist.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(hist), whole)
    else:
        np.testing.assert_allclose(np.asarray(hist), whole, rtol=1e-6,
                                   atol=1e-6)


def test_shards_of_a_mesh_walk_their_own_trip_counts(chunk_rows):
    """Four shards, one program: a node's rows on a shard are that
    shard's own, so one builds on a rung, one on the top rung and two
    through the loop, three and five trips; no collective is inside."""
    chunk_rows(C)
    cfg = GrowerConfig(num_bins=B, min_bucket=4, hist_method="segment",
                       axis_name=DATA_AXIS, data_axis_size=4)
    rng, bins, gh = _table(1)
    col, thr = bins[:, 0], 6
    cnts = np.asarray([5, C, 2 * C + 3, 5 * C - 1], np.int32)
    offs = np.asarray([3, 0, 9, 7], np.int32)
    orders = np.stack([_segment(rng, o, c) for o, c in zip(offs, cnts)])
    walk = _walk(cfg, bins, gh, col, thr, False,
                 np.zeros(cfg.cat_words, np.uint32))

    def shard(order, off, cnt):
        (out, cnt_l, cnt_r), hist = walk(order[0], off[0], cnt[0])
        return out[None], cnt_l[None], cnt_r[None], hist[None]

    mesh = Mesh(np.asarray(jax.devices()[:4]), (DATA_AXIS,))
    rows = P(DATA_AXIS)
    got, cnt_l, cnt_r, hist = jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(rows, rows, rows),
        out_specs=(rows, rows, rows, rows), check_vma=False))(
        orders, offs, cnts)
    for d in range(4):
        want, want_l, want_r = _expected(orders[d], offs[d], cnts[d],
                                         col <= thr)
        np.testing.assert_array_equal(np.asarray(got[d]), want)
        assert (int(cnt_l[d]), int(cnt_r[d])) == (want_l, want_r)
        seg = orders[d][offs[d]:offs[d] + cnts[d]]
        np.testing.assert_allclose(
            np.asarray(hist[d]),
            np.asarray(compute_histogram(jnp.asarray(bins[seg]),
                                         jnp.asarray(gh[seg]), B,
                                         method="segment")),
            rtol=1e-6, atol=1e-6)


def test_a_forest_grown_through_the_loop_is_the_one_grown_on_rungs(
        chunk_rows):
    """Three trees of 15 leaves on 20 000 rows with the chunk at
    ``min_bucket``'s 2048 rows (a root's smaller child takes up to 5
    chunks, and every child over a tenth of the table takes the loop)
    and at 32 768 (every segment fits a rung): the same features,
    thresholds and node counts."""
    from mmlspark_tpu.gbdt import LightGBMClassifier, engine
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    from mmlspark_tpu.gbdt.objectives import get_objective
    rng = np.random.default_rng(3)
    X = rng.normal(size=(20_000, 8)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.3 * rng.normal(size=20_000) > 0).astype(np.float64)
    est = LightGBMClassifier(numIterations=3, numLeaves=15, verbosity=0,
                             histogramMethod="segment")
    mapper = fit_bin_mapper(X, max_bin=est.getMaxBin(), seed=est.getSeed())
    bins = mapper.transform_packed(X)
    objective = get_objective(est.getObjective(), num_class=1,
                              **est._objective_kwargs())

    def fit(rows):
        chunk_rows(rows)
        booster = engine.train(bins, est._prepare_labels(y), None, mapper,
                               objective, est._train_params())
        return booster.trees, engine.last_fit_info.get("hist_build_rungs")

    looped, looped_rungs = fit(2048)
    whole, whole_rungs = fit(32_768)
    # the root, one rung and the loop; the root and rungs 2^11 .. 2^15
    assert (looped_rungs, whole_rungs) == ("0/3", "0/6")
    assert sum(int((t.internal_count > 4096).sum()) for t in looped) >= 6
    assert len(looped) == len(whole) == 3
    for a, b in zip(looped, whole):
        np.testing.assert_array_equal(a.split_feature, b.split_feature)
        np.testing.assert_array_equal(a.threshold, b.threshold)
        np.testing.assert_array_equal(a.internal_count, b.internal_count)
        np.testing.assert_array_equal(a.leaf_count, b.leaf_count)


@pytest.mark.parametrize("rows,rungs,loop", [
    (400_000, 6, True), (1_183_747, 6, True), (30_000_000, 6, True),
    (7_325_625, 6, True), (13_184_290, 6, True), (100_000, 6, True),
    (65_536, 6, False), (40_000, 6, False), (3000, 2, False),
    (1000, 1, False)])
def test_ladder_ends_at_the_chunk_rung(rows, rungs, loop):
    """The build's ladder at every cell's rows (15 rungs at 3 x 10^7
    before, 10 at 400 000): powers of two from ``min_bucket`` to the
    chunk, and the loop only where the rows pass it."""
    from mmlspark_tpu.gbdt.grower import hist_build_schedule
    cfg = GrowerConfig(num_bins=255, hist_method="segment")
    sizes = grower._build_sizes(rows, cfg)
    assert sizes == grower._bucket_sizes(rows, cfg)[:len(sizes)]
    assert len(sizes) == rungs
    assert sizes[-1] <= grower.SEGMENT_CHUNK_ROWS
    assert all(b == 2 * a for a, b in zip(sizes, sizes[1:]))
    assert (rows > sizes[-1]) == loop
    assert hist_build_schedule(cfg, rows)["sites"] == 1 + rungs + loop


def test_segment_walk_stats_counts_rungs_and_chunks(chunk_rows):
    """Two splits: 100 rows into 60 | 40, then 60 into 59 | 1: the
    parents are partitioned on the 128- and 64-row rungs, the smaller
    children histogrammed in 3 chunks of 16 and on the 4-row rung."""
    chunk_rows(C)
    got = grower.segment_walk_stats([100, 60], [40, 1], N,
                                    GrowerConfig(min_bucket=4))
    assert got == {"seg_rows": 201, "seg_rows_walked": 128 + 64 + 48 + 4,
                   "seg_chunked_nodes": 1}
