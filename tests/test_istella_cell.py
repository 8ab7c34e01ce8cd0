"""The learning-to-rank cell ``istella_fit`` (PR 31): the program's lambdas
against the plain reference ``benchmark/reference/gbdt_rank.py`` on ragged
queries, the size-class query layout against the padded-to-longest one,
the ranker on the ordinary boost scan (a second fit re-traces nothing; the
spans and attrs are there), ``correct`` false for each fault and control
(the tests of benchmark/tests/test_correct_rank.py, counted here), the
generator, and the four new per-layer readers on a hand-made span list.
"""

import importlib
import types

import numpy as np
import pytest

from benchmark.tests.test_correct_rank import (  # noqa: F401
    broken_train, control_readings, test_altered_leaf_is_not_correct,
    test_altered_split_is_not_correct, test_float8_control_is_not_correct,
    test_half_batch_is_not_correct, test_pointwise_control_is_not_correct,
    test_sound_ranking_run_is_correct,
    test_state_left_unchanged_is_not_correct,
    test_wrong_ranking_gradient_is_not_correct)
from benchmark.tests.test_span_readers import Profiler, fit
from benchmark.lib import data_ltr
from benchmark.reference import gbdt_rank

RULE = {"sigma": 1.0, "truncation_level": 30, "max_label": 31,
        "hessian_floor": 1e-9}


def ragged(seed, queries=40, longest=70, shuffle=True):
    """Labels 0..4, scores with ties, query sizes from 1 to over one size
    class (8, 16, 32, 64, 128), rows not stored query by query."""
    rng = np.random.default_rng(seed)
    sizes = np.concatenate([[1, 2, 8, 9, longest],
                            rng.integers(1, longest + 1, queries - 5)])
    q = np.repeat(np.arange(len(sizes)) * 3 + 7, sizes)
    n = len(q)
    y = (rng.random(n) < 0.35) * rng.integers(1, 5, n).astype(np.float64)
    # a few distinct values, as scores are after one tree: ties abound
    scores = rng.choice(np.linspace(-1.5, 1.5, 9), size=n).astype(np.float32)
    if shuffle:
        p = rng.permutation(n)
        q, y, scores = q[p], y[p], scores[p]
    return q, y, scores


# ------------------------------------- the program's lambdas, by the rule


@pytest.mark.parametrize("sigma,trunc", [(1.0, 30), (2.0, 5), (0.5, 1000)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lambdas_are_the_references(seed, sigma, trunc):
    """float32 tolerance: the program sums float32 pair terms (up to 70 a
    document here, each a product of five float32 factors, relative
    error about 1e-6 a term) where the reference sums float64; held to
    1e-4 of the query-wide scale, a hundred times over that, and far
    under any wrong rule (a factor of sigma, a missing pair)."""
    from mmlspark_tpu.gbdt.ranking import make_lambdarank_grad_fn
    q, y, scores = ragged(seed)
    rule = dict(RULE, sigma=sigma, truncation_level=trunc)
    g, h = make_lambdarank_grad_fn(y, q, sigma=sigma,
                                   truncation_level=trunc)(scores)
    want_g, want_h = gbdt_rank.lambdarank_grad_hess(
        scores.astype(np.float64), y, gbdt_rank.query_runs(q), rule)
    assert np.abs(want_g).max() > 0.05
    np.testing.assert_allclose(np.asarray(g), want_g, rtol=1e-4,
                               atol=1e-4 * np.abs(want_g).max())
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=1e-4,
                               atol=1e-4 * want_h.max())


def test_lambdas_at_score_zero_rank_by_position():
    """Every score tied, as before the first tree: ranks are positions in
    the query, on both sides."""
    from mmlspark_tpu.gbdt.ranking import make_lambdarank_grad_fn
    q, y, _ = ragged(3)
    zeros = np.zeros(len(q), np.float32)
    g, h = make_lambdarank_grad_fn(y, q)(zeros)
    want_g, want_h = gbdt_rank.lambdarank_grad_hess(
        zeros.astype(np.float64), y, gbdt_rank.query_runs(q), RULE)
    np.testing.assert_allclose(np.asarray(g), want_g, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=1e-4, atol=1e-6)


def test_reference_lambdas_by_hand():
    """Two documents, labels 1 and 0, scores equal: one pair, ranks 0 and
    1, p = 1/2, delta = (2^1 - 1) (1 - 1/log2(3)) / 1."""
    delta = 1.0 - 1.0 / np.log2(3.0)
    g, h = gbdt_rank.query_lambdas(np.zeros(2), np.array([1.0, 0.0]), RULE)
    assert g == pytest.approx([-0.5 * delta, 0.5 * delta])
    assert h == pytest.approx([0.25 * delta, 0.25 * delta])
    # ties keep their position: the irrelevant document first
    g, _ = gbdt_rank.query_lambdas(np.zeros(2), np.array([0.0, 1.0]), RULE)
    assert g == pytest.approx([0.5 * delta, -0.5 * delta])
    # beyond the truncation level no pair counts
    g, _ = gbdt_rank.query_lambdas(
        np.array([3.0, 2.0, 1.0, 0.0]), np.array([0.0, 0.0, 0.0, 1.0]),
        dict(RULE, truncation_level=1))
    assert g[1] == g[2] == 0.0 and g[0] > 0 > g[3]


# ------------------------------------------------ the query layout


@pytest.mark.parametrize("seed", [0, 4])
def test_size_classes_give_the_padded_layouts_gradients(seed):
    """The layout by size class against every query padded to the longest
    (the mesh trainer's layout through the same pair mathematics): equal
    to float32 rounding, the sums running over other paddings."""
    import jax.numpy as jnp
    from mmlspark_tpu.gbdt.ranking import (lambda_grad_sorted,
                                           make_lambdarank_grad_fn,
                                           pack_queries, query_tensors)
    q, y, scores = ragged(seed)
    grad = make_lambdarank_grad_fn(y, q, sigma=1.0, truncation_level=30)
    g, h = grad(scores)
    order, qidx, qmask = pack_queries(q)
    gains, labq, invmax = query_tensors(y[order].astype(np.float32), qidx,
                                        qmask, 30)
    g_s, h_s = lambda_grad_sorted(
        jnp.asarray(scores[order]), *(jnp.asarray(a[None]) for a in (
            qidx, qmask, gains, labq, invmax)), 1.0, 30, len(q))
    want_g = np.zeros(len(q), np.float32)
    want_h = np.zeros(len(q), np.float32)
    want_g[order] = np.asarray(g_s)
    want_h[order] = np.maximum(np.asarray(h_s), 1e-9)
    np.testing.assert_allclose(np.asarray(g), want_g, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=2e-5, atol=1e-6)


def test_layout_pads_pairs_under_four_times():
    from mmlspark_tpu.gbdt.ranking import (MIN_SIZE_CLASS,
                                           pack_queries_by_size)
    q, y, _ = ragged(5, queries=300, longest=300)
    lay = pack_queries_by_size(y, q, 30, query_chunk_pairs=50_000)
    counts = np.unique(q, return_counts=True)[1]
    assert lay.queries == len(counts) and lay.n == len(q)
    assert lay.pairs_useful == int(np.sum(counts.astype(np.int64) ** 2))
    assert lay.pairs_useful < lay.pairs_computed <= 4 * lay.pairs_useful
    lengths = [blocks[0].shape[2] for blocks in lay.classes]
    assert lengths == sorted(set(lengths)) and lengths[0] == MIN_SIZE_CLASS
    assert all(g & (g - 1) == 0 for g in lengths) and lengths[-1] == 512
    # rows padded under twice, chunks of a class of one length, every
    # row in exactly one slot, and the slot names that row
    slots = sum(b[0].size for b in lay.classes)
    flat = np.concatenate([b[0].reshape(-1) for b in lay.classes])
    assert slots <= 2 * lay.n + MIN_SIZE_CLASS * lay.queries
    assert np.array_equal(flat[lay.slot], np.arange(lay.n))
    assert np.count_nonzero(flat < lay.n) == lay.n
    assert any(b[0].shape[0] > 1 for b in lay.classes)     # chunked


def test_pack_queries_pads_to_the_longest_without_a_loop():
    from mmlspark_tpu.gbdt.ranking import pack_queries
    q = np.array([5, 2, 5, 9, 2, 5])
    order, qidx, qmask = pack_queries(q)
    assert list(order) == [1, 4, 0, 2, 5, 3]
    assert qidx.tolist() == [[0, 1, 0], [2, 3, 4], [5, 0, 0]]
    assert qmask.tolist() == [[1, 1, 0], [1, 1, 1], [1, 0, 0]]


def ndcg_by_loop(scores, labels, query_ids, k):
    """``ndcg_at_k`` as it was before PR 31: a mask a query."""
    out, cnt = 0.0, 0
    for q in np.unique(query_ids):
        m = query_ids == q
        s, lab = scores[m], labels[m]
        if len(lab) < 2 or lab.max() == lab.min():
            continue
        order = np.argsort(-s, kind="stable")
        gains = 2.0 ** lab - 1
        disc = 1.0 / np.log2(2 + np.arange(len(lab)))
        dcg = (gains[order][:k] * disc[:k]).sum()
        idcg = (np.sort(gains)[::-1][:k] * disc[:k]).sum()
        if idcg > 0:
            out += dcg / idcg
            cnt += 1
    return out / max(cnt, 1)


@pytest.mark.parametrize("k", [1, 5, 10, 1000])
def test_ndcg_at_k_is_the_loops(k):
    from mmlspark_tpu.gbdt.ranking import ndcg_at_k
    q, y, scores = ragged(6)
    want = ndcg_by_loop(scores, y, q, k)
    assert 0.1 < want < 1.0
    assert ndcg_at_k(scores, y, q, k=k) == pytest.approx(want, rel=1e-12)
    assert gbdt_rank.ndcg_at(
        scores.astype(np.float64), y, gbdt_rank.query_runs(q),
        k) == pytest.approx(want, rel=1e-12)
    assert ndcg_at_k(scores[:0], y[:0], q[:0], k=k) == 0.0


# ------------------------------------- the ranker on the ordinary scan


def small_rank_fit(grad=None, seed=11, **params):
    from mmlspark_tpu.core.profiler import get_profiler
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    from mmlspark_tpu.gbdt.engine import TrainParams
    from mmlspark_tpu.gbdt.objectives import get_objective
    from mmlspark_tpu.gbdt.ranking import make_lambdarank_grad_fn
    X, y, q = data_ltr.ltr_queries(seed, 6000, 12, 40)
    mapper = fit_bin_mapper(X, max_bin=255, seed=42)
    bins = mapper.transform_packed(X)
    grad = grad or make_lambdarank_grad_fn(y, q, truncation_level=30)
    info = {"query_ids": q, "sigma": 1.0, "truncation_level": 30}
    tp = TrainParams(**dict(dict(
        num_iterations=2, num_leaves=15, max_bin=255, min_data_in_leaf=0,
        min_sum_hessian_in_leaf=0.5, verbosity=0), **params))
    before = len(get_profiler().spans())
    booster = engine.train(bins, y, None, mapper,
                           get_objective("lambdarank"), tp,
                           grad_fn_override=grad, ranking_info=info)
    return booster, get_profiler().spans()[before:], grad


def test_second_ranking_fit_traces_and_compiles_nothing():
    """The query layout is an argument of a program built once: the same
    table again, with the layout packed again, finds every program."""
    from mmlspark_tpu.core.profiler import get_profiler
    first, _, grad = small_rank_fit()
    seq = get_profiler().compile_seq()
    again, spans, _ = small_rank_fit()
    assert get_profiler().compile_seq() == seq
    launch = [s for s in spans if s["name"] == "train.launch"]
    assert len(launch) == 1
    assert launch[0]["attrs"]["compile_misses"] == 0
    assert launch[0]["attrs"]["jaxpr_trace_s"] < 0.05
    assert launch[0]["attrs"]["backend_compile_s"] == 0
    assert first.save_native_model_string() == \
        again.save_native_model_string()
    # and the gradient called on its own is one program a set of shapes
    from mmlspark_tpu.gbdt.ranking import _lambdarank_program
    grad(np.zeros(grad.layout.n, np.float32))
    size = _lambdarank_program._cache_size()
    _, _, other = small_rank_fit()
    other(np.ones(grad.layout.n, np.float32))
    assert _lambdarank_program._cache_size() == size


def test_ranking_fit_has_the_normal_paths_spans_and_its_own_attrs():
    booster, spans, grad = small_rank_fit()
    names = [s["name"] for s in spans]
    root = next(s for s in spans if s["name"] == "train.fit")
    for name in ("train.upload", "train.rank_pack", "train.build_step",
                 "train.launch", "train.device_wait", "train.fetch_trees",
                 "train.finalize", "train.reference_profile"):
        assert names.count(name) == 1, name
        assert next(s for s in spans
                    if s["name"] == name)["parent"] == root["id"]
    pack = next(s for s in spans if s["name"] == "train.rank_pack")
    lay = grad.layout
    assert pack["attrs"]["bytes"] == lay.slot.nbytes + sum(
        a.nbytes for blocks in lay.classes for a in blocks)
    attrs = root["attrs"]
    assert attrs["trees"] == len(booster.trees) == 2
    assert attrs["rank_queries"] == 40
    assert attrs["rank_size_classes"] == len(lay.classes)
    assert attrs["rank_pairs_useful"] == 2 * lay.pairs_useful
    assert attrs["rank_pairs_computed"] == 2 * lay.pairs_computed
    assert all(t.num_leaves > 4 for t in booster.trees)


def test_binary_fit_carries_no_ranking_attr():
    from tests.test_criteo_cell import small_fit
    _, spans = small_fit(categorical=False)
    root = next(s for s in spans if s["name"] == "train.fit")
    assert not any(k.startswith("rank_") for k in root["attrs"])
    assert "train.rank_pack" not in [s["name"] for s in spans]


def test_ranker_estimator_takes_the_same_path():
    """``LightGBMRanker.fit`` and the driver's direct call are one path:
    the same forest."""
    from mmlspark_tpu.gbdt import LightGBMRanker
    X, y, q = data_ltr.ltr_queries(11, 6000, 12, 40)
    model = LightGBMRanker(numIterations=2, numLeaves=15, minDataInLeaf=0,
                           minSumHessianInLeaf=0.5, groupCol="query",
                           verbosity=0).fit(
        {"features": X, "label": y, "query": q})
    booster, _, _ = small_rank_fit()
    for a, b in zip(model.getModel().trees, booster.trees):
        assert np.array_equal(a.split_feature, b.split_feature)
        assert np.array_equal(a.leaf_value, b.leaf_value)


@pytest.mark.parametrize("mode", [
    {"boosting": "goss", "top_rate": 0.3, "other_rate": 0.2},
    {"boosting": "dart", "drop_rate": 0.5},
    {"bagging_fraction": 0.7, "bagging_freq": 1},
    {"boosting": "rf", "bagging_fraction": 0.7, "bagging_freq": 1}])
def test_every_boosting_mode_takes_the_layout_as_an_argument(mode):
    """goss, dart, bagging and rf run the ranker through their ordinary
    programs with lambdarank as the objective: a fit, and no closure."""
    booster, spans, _ = small_rank_fit(num_iterations=3, **mode)
    assert len(booster.trees) == 3
    assert "train.rank_pack" in [s["name"] for s in spans]
    assert all(np.isfinite(t.leaf_value).all() for t in booster.trees)


def test_a_closure_is_refused_before_anything_is_uploaded():
    """The host loop a closure needed went with the ranker's move onto the
    ordinary programs: ``grad_fn_override`` is a ``LambdarankGrad``."""
    with pytest.raises(TypeError, match="make_lambdarank_grad_fn"):
        small_rank_fit(grad=lambda s: (s, s))


# ------------------------------------------------------------- generator


def test_ltr_queries_depend_on_seed_and_shape_only():
    a = data_ltr.ltr_queries(7, 70000, 24, 300, threads=1)
    b = data_ltr.ltr_queries(7, 70000, 24, 300, threads=5)
    c = data_ltr.ltr_queries(8, 70000, 24, 300, threads=5)
    assert all(np.array_equal(x, z) for x, z in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    X, y, q = a
    assert X.dtype == np.float32 and X.shape == (70000, 24)
    assert q.dtype == np.int32 and np.all(np.diff(q) >= 0)
    sizes = np.bincount(q)
    assert len(sizes) == 300 and sizes.min() >= 1 and sizes.sum() == 70000
    assert sizes.max() > 3 * np.median(sizes)            # a heavy tail
    assert set(np.unique(y)) <= {0.0, 1.0, 2.0, 3.0, 4.0}
    assert 0.94 < np.mean(y == 0) < 0.98
    shares = [np.mean(y == k) for k in (1, 2, 3, 4)]
    assert shares == sorted(shares, reverse=True) and shares[3] > 0
    # a query with a high effect holds many relevant documents
    per_query = np.bincount(q, weights=y > 0) / sizes
    assert per_query.max() > 4 * per_query.mean()
    few = [j for j in range(24) if len(np.unique(X[:, j])) <= 255]
    assert few == [7, 15, 23]


def test_query_sizes_fit_their_bounds_and_sum():
    rng = np.random.default_rng(0)
    sizes = data_ltr.query_sizes(rng, 7_325_625, 23_219)
    assert sizes.sum() == 7_325_625 and sizes.min() >= 1
    assert sizes.max() <= data_ltr.MAX_QUERY
    assert 200 < np.median(sizes) < 300
    tight = data_ltr.query_sizes(rng, 50, 50)
    assert np.all(tight == 1)
    with pytest.raises(ValueError):
        data_ltr.query_sizes(rng, 10, 50)


def test_reference_binning_is_the_programs_with_few_valued_columns():
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    X, _, _ = data_ltr.ltr_queries(3, 30000, 24, 100)
    mapper = fit_bin_mapper(X, max_bin=255, seed=42)
    bins = mapper.transform_packed(X)
    binning = {"min_data_in_bin": 3, "sample_rows": 200000, "seed": 42}
    assert gbdt_rank.check_bins(X, bins, list(range(24)), binning,
                                255) == 0
    bins[5, 15] ^= 1
    assert gbdt_rank.check_bins(X, bins, [15], binning, 255) == 1


# ------------------------------------------------------ the new readers

TEXT = """tree
version=v3
Tree=0
num_leaves=3
num_cat=0
split_feature=1 0
split_gain=5 2
threshold=0.5 1.5
decision_type=2 2
left_child=1 -1
right_child=-2 -3
leaf_value=0.1 -0.2 0.3
leaf_weight=1 1 1
leaf_count=2 2 1
internal_value=0 0
internal_weight=3 2
internal_count=5 3
is_linear=0
shrinkage=1

end of trees
"""


def run_of(spans, state=None, fits=2, trees=4, window_s=23.0):
    return types.SimpleNamespace(
        state=dict(state or {}, profiler=Profiler(spans)),
        work={"fits": fits, "trees": trees, "window_s": window_s},
        peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, chips=1)


def read(name, run):
    return importlib.import_module("benchmark.metrics." + name).read(run)


def rank_fit(first_id, t0, scale=1.0, useful=1000, computed=2500):
    """A fit with a ``train.rank_pack`` of .2 s after its upload."""
    spans = fit(first_id, t0, scale,
                attrs={"rank_queries": 40, "rank_size_classes": 5,
                       "rank_pairs_useful": useful,
                       "rank_pairs_computed": computed})
    up = next(s for s in spans if s["name"] == "train.upload")
    spans.insert(0, {"id": first_id + 50, "name": "train.rank_pack",
                     "start": up["end"], "end": up["end"] + 0.2 * scale,
                     "parent": first_id, "fit": up["fit"],
                     "attrs": {"bytes": 64}})
    return spans


def test_rank_span_readers_on_a_hand_made_list():
    spans = (rank_fit(100, 0.0, scale=10.0, useful=1, computed=999999)
             + rank_fit(200, 200.0) + rank_fit(300, 300.0, useful=3000,
                                               computed=5500))
    run = run_of(spans)
    # 2 fits x .2 s over 4 trees; the warm-up's is left out
    assert read("rank_pack_ms_per_tree", run) == pytest.approx(100.0)
    assert read("rank_pairs_computed_per_tree", run) == pytest.approx(2000.0)
    assert read("rank_pair_pad_share", run) == pytest.approx(50.0)


def test_rank_readers_find_nothing_in_another_fit():
    run = run_of(fit(100, 0.0) + fit(200, 200.0) + fit(300, 300.0))
    for name in ("rank_pack_ms_per_tree", "rank_pairs_computed_per_tree",
                 "rank_pair_pad_share", "rank_fit_tree_mfu"):
        assert read(name, run) is None, name
    # and the accepted readers find nothing under the new driver's key
    state = {"rank_model_text": TEXT}
    assert read("fit_tree_mfu", run_of([], state)) is None
    assert read("cat_fit_tree_mfu", run_of([], state)) is None


def test_rank_fit_tree_mfu_counts_the_histogram_work():
    from benchmark.lib import work
    from benchmark.reference import gbdt
    run = run_of([], {"rank_model_text": TEXT, "features": 2,
                      "num_bins": 256}, trees=1, window_s=1e-3)
    (tree,) = gbdt.parse_model(TEXT)
    assert work.rows_histogrammed(tree) == 5 + 2 + 1
    ops, moved = work.histogram_work([tree], 2, 256)
    least = max(ops / 197e12, moved / 819e9)
    assert read("rank_fit_tree_mfu", run) == pytest.approx(
        100.0 * least / 1e-3)
