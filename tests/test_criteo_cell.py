"""The click-log cell ``criteo_fit`` (PR 27): the program against the plain
reference ``benchmark/reference/gbdt_cat.py`` on mixed numeric and
categorical columns, ``correct`` false for each fault and control (the
tests of benchmark/tests/test_correct_cat.py, counted here), the three new
per-layer readers on a hand-made span list, the new spans and attrs, and
what the cell's size forced into the program.
"""

import importlib
import types

import numpy as np
import pytest

from benchmark.tests.test_correct_cat import (  # noqa: F401
    broken_train, control_readings, test_altered_bitset_is_not_correct,
    test_altered_leaf_is_not_correct,
    test_altered_numeric_split_is_not_correct,
    test_cat_as_numeric_is_not_correct, test_float8_control_is_not_correct,
    test_half_batch_is_not_correct, test_sound_categorical_run_is_correct,
    test_state_left_unchanged_is_not_correct)
from benchmark.tests.test_span_readers import Profiler, fit
from benchmark.lib import data_clicks
from benchmark.reference import gbdt, gbdt_cat

CARDS = [40, 12, 5000, 700, 30, 24, 300, 60, 3, 900, 80, 4000, 50, 27, 90,
         2500, 10, 70, 45, 4, 3500, 18, 15, 800, 33, 600]


# ------------------------------------------------------------- generator


def test_click_log_depends_on_seed_and_shape_only():
    a = data_clicks.click_log(7, 70000, CARDS, threads=1)
    b = data_clicks.click_log(7, 70000, CARDS, threads=5)
    c = data_clicks.click_log(8, 70000, CARDS, threads=5)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    X, y = a
    assert X.dtype == np.float32 and X.shape == (70000, 39)
    assert abs(y.mean() - data_clicks.POSITIVE_SHARE) < 0.02
    assert not np.isnan(X).any() and (X >= 0).all()
    for j, card in enumerate(CARDS):
        col = X[:, 13 + j]
        assert np.array_equal(col, np.floor(col)) and col.max() < card
    # a label encoder's codes: the most frequent value is not the smallest
    top = [np.bincount(X[:, 13 + j].astype(int)).argmax()
           for j in (2, 11, 20)]
    assert any(t > 10 for t in top)


# ------------------------------------------------------- the reference


TEXT = """tree
version=v3
Tree=0
num_leaves=3
num_cat=1
split_feature=1 0
split_gain=5 2
threshold=0 1.5
decision_type=1 2
left_child=1 -1
right_child=-2 -3
leaf_value=0.1 -0.2 0.3
leaf_weight=1 1 1
leaf_count=2 2 1
internal_value=0 0
internal_weight=3 2
internal_count=5 3
cat_boundaries=0 2
cat_threshold=5 1
is_linear=0
shrinkage=1

end of trees
"""


def test_reference_parses_and_walks_raw_value_bitsets():
    (tree,) = gbdt_cat.parse_model(TEXT)
    assert tree["num_cat"] == 1 and list(tree["is_cat"]) == [True, False]
    # words 5, 1: values 0, 2 and 32 go left at the root
    assert list(gbdt_cat.node_categories(tree, 0)) == [0, 2, 32]
    X = np.array([[1.0, 0], [2.0, 2], [9.0, 32], [0.0, 1], [0.0, 33],
                  [0.0, 64], [0.0, 1e6]], np.float32)
    leaf, counts = gbdt_cat.walk(tree, X)
    # left of the root: x0 <= 1.5 -> leaf 0, else leaf 2; right: leaf 1
    assert list(leaf) == [0, 2, 2, 1, 1, 1, 1]
    assert list(counts) == [7, 3]


def test_reference_rejects_a_model_whose_num_cat_lies():
    with pytest.raises(ValueError):
        gbdt_cat.parse_model(TEXT.replace("decision_type=1 2",
                                          "decision_type=2 2"))


def brute_force_cat(hist_f, rule, msh):
    """Every subset the rule admits, by enumeration."""
    B = hist_f.shape[0]
    tot = hist_f.sum(axis=0)
    listed = [b for b in range(B - 1) if hist_f[b, 2] > 0]
    order = sorted(listed, key=lambda b: (
        hist_f[b, 0] / (hist_f[b, 1] + rule["cat_smooth"]), b))
    best = -np.inf
    for k in range(1, len(order)):
        if min(k, len(order) - k) > rule["max_cat_threshold"]:
            continue
        s = hist_f[order[:k]].sum(axis=0)
        r = tot - s
        if min(s[2], r[2]) < 1 or min(s[1], r[1]) < msh:
            continue
        best = max(best, s[0] ** 2 / (s[1] + rule["cat_l2"])
                   + r[0] ** 2 / (r[1] + rule["cat_l2"])
                   - tot[0] ** 2 / (tot[1] + rule["cat_l2"]))
    return best


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_categorical_search_is_the_stated_rule(seed):
    rng = np.random.default_rng(seed)
    rule = {"cat_smooth": 10.0, "cat_l2": 10.0, "max_cat_threshold": 4,
            "max_cat_to_onehot": 4}
    hist = np.zeros((16, 3))
    used = rng.choice(15, size=11, replace=False)
    hist[used, 2] = rng.integers(5, 50, size=11)
    hist[used, 1] = hist[used, 2] * rng.uniform(0.1, 0.25, size=11)
    hist[used, 0] = rng.normal(size=11) * hist[used, 1]
    hist[15] = (3.0, 40.0, 200.0)        # the trailing bin holds rows too
    got = gbdt_cat.best_cat_split(hist, 11, rule, 2.0, 0)
    assert got is not None and not got[1][15]
    assert got[0] == pytest.approx(brute_force_cat(hist, rule, 2.0))
    assert gbdt_cat.mask_gain(hist, got[1], 10.0) == pytest.approx(got[0])
    # one bin against the rest where the column has few values
    one = gbdt_cat.best_cat_split(hist, 4, rule, 2.0, 0)
    assert one[1].sum() == 1


def test_reference_binning_is_the_programs_for_both_kinds():
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    X, _ = data_clicks.click_log(3, 30000, CARDS)
    cat = list(range(13, 39))
    mapper = fit_bin_mapper(X, max_bin=255, seed=42,
                            categorical_features=cat)
    bins = mapper.transform_packed(X)
    binning = {"min_data_in_bin": 3, "sample_rows": 200000, "seed": 42}
    bad, values = gbdt_cat.check_bins(X, bins, list(range(39)), cat,
                                      binning, 255)
    assert bad == 0
    few = [j for j in range(13) if len(np.unique(X[:, j])) <= 255]
    many = [j for j in range(13) if len(np.unique(X[:, j])) > 255]
    assert few and many          # both numeric rules were exercised
    for f in (15, 24):
        assert np.array_equal(values[f], mapper.cat_values[f])
    bins[5, 20] ^= 1
    assert gbdt_cat.check_bins(X, bins, [20], cat, binning, 255)[0] == 1


# ------------------------------------------------------ the new readers


def run_of(spans, state=None, fits=2, trees=4, window_s=23.0):
    return types.SimpleNamespace(
        state=dict(state or {}, profiler=Profiler(spans)),
        work={"fits": fits, "trees": trees, "window_s": window_s},
        peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, chips=1)


def read(name, run):
    return importlib.import_module("benchmark.metrics." + name).read(run)


def cat_fit(first_id, t0, scale=1.0, words=1000):
    """A fit with two ``train.cat_bitsets`` of .1 s inside its finalize."""
    spans = fit(first_id, t0, scale,
                attrs={"cat_features": 26, "cat_splits": 300,
                       "cat_bitset_words": words})
    fin = next(s for s in spans if s["name"] == "train.finalize")
    for k in range(2):
        spans.insert(0, {"id": first_id + 50 + k, "name": "train.cat_bitsets",
                         "start": fin["start"] + 0.1 * k * scale,
                         "end": fin["start"] + 0.1 * (k + 1) * scale,
                         "parent": fin["id"], "fit": fin["fit"],
                         "attrs": {"words": words // 2}})
    return spans


def test_cat_span_readers_on_a_hand_made_list():
    spans = (cat_fit(100, 0.0, scale=10.0, words=999999)
             + cat_fit(200, 200.0, words=1000) + cat_fit(300, 300.0,
                                                         words=3000))
    run = run_of(spans)
    # 2 fits x 2 spans x .1 s over 4 trees; the warm-up's are left out
    assert read("cat_bitsets_ms_per_tree", run) == pytest.approx(100.0)
    assert read("cat_bitset_words_per_tree", run) == pytest.approx(1000.0)


def test_cat_span_readers_find_nothing_in_a_numeric_fit():
    run = run_of(fit(100, 0.0) + fit(200, 200.0) + fit(300, 300.0))
    assert read("cat_bitsets_ms_per_tree", run) is None
    assert read("cat_bitset_words_per_tree", run) is None
    assert read("cat_fit_tree_mfu", run) is None
    # and the accepted reader finds nothing under the new driver's key
    assert read("fit_tree_mfu", run_of([], {"cat_model_text": TEXT})) is None


def test_cat_fit_tree_mfu_counts_the_histogram_work():
    run = run_of([], {"cat_model_text": TEXT, "features": 2,
                      "num_bins": 256}, trees=1, window_s=1e-3)
    (tree,) = gbdt_cat.parse_model(TEXT)
    from benchmark.lib import work
    assert work.rows_histogrammed(tree) == 5 + 2 + 1
    ops, moved = work.histogram_work([tree], 2, 256)
    least = max(ops / 197e12, moved / 819e9)
    assert read("cat_fit_tree_mfu", run) == pytest.approx(
        100.0 * least / 1e-3)


# ------------------------------------------- spans, attrs and the program


def small_fit(categorical):
    from mmlspark_tpu.core.profiler import get_profiler
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    from mmlspark_tpu.gbdt.engine import TrainParams
    from mmlspark_tpu.gbdt.objectives import get_objective
    X, y = data_clicks.click_log(11, 6000, [min(c, 40) for c in CARDS])
    mapper = fit_bin_mapper(
        X, max_bin=255, seed=42,
        categorical_features=list(range(13, 39)) if categorical else None)
    bins = mapper.transform_packed(X)
    params = TrainParams(num_iterations=2, num_leaves=15, max_bin=255,
                         min_data_in_leaf=0, min_sum_hessian_in_leaf=5.0,
                         verbosity=0)
    before = len(get_profiler().spans())
    booster = engine.train(bins, y, None, mapper, get_objective("binary"),
                           params)
    return booster, get_profiler().spans()[before:]


def test_categorical_fit_says_its_splits_and_words():
    booster, spans = small_fit(categorical=True)
    root = next(s for s in spans if s["name"] == "train.fit")
    cat_nodes = sum(int((t.decision_type & 1).sum()) for t in booster.trees)
    words = sum(len(t.cat_threshold) for t in booster.trees)
    assert cat_nodes > 0
    assert root["attrs"]["cat_features"] == 26
    assert root["attrs"]["cat_splits"] == cat_nodes
    assert root["attrs"]["cat_bitset_words"] == words
    # one span a tree that holds a categorical split, inside the loop
    # over the trees that ``train.finalize`` runs
    built = [s for s in spans if s["name"] == "train.cat_bitsets"]
    finalize = next(s for s in spans if s["name"] == "train.finalize")
    host_trees = next(s for s in spans if s["name"] == "train.host_trees")
    assert host_trees["parent"] == finalize["id"]
    assert built and all(s["parent"] == host_trees["id"] for s in built)
    assert sum(s["attrs"]["words"] for s in built) == words


def test_numeric_fit_carries_none_of_it():
    _, spans = small_fit(categorical=False)
    root = next(s for s in spans if s["name"] == "train.fit")
    assert not {"cat_features", "cat_splits", "cat_bitset_words"} \
        & set(root["attrs"])
    assert not [s for s in spans if s["name"] == "train.cat_bitsets"]


def test_raw_value_bitsets_and_their_text():
    """The exported bitset holds exactly the raw values of the left bins,
    and survives the model text."""
    from mmlspark_tpu.gbdt.booster import Booster
    booster, _ = small_fit(categorical=True)
    text = booster.save_native_model_string()
    again = Booster.load_native_model_string(text)
    trees = gbdt_cat.parse_model(text)
    for host, tree in zip(booster.trees, trees):
        assert np.array_equal(host.cat_threshold, tree["cat_threshold"])
        assert np.array_equal(host.cat_boundaries, tree["cat_boundaries"])
    for host, loaded in zip(booster.trees, again.trees):
        assert np.array_equal(host.cat_threshold, loaded.cat_threshold)
        assert np.array_equal(host.decision_type, loaded.decision_type)
    head = text.split("end of trees")[0]
    assert again.save_native_model_string().split("end of trees")[0] == head


def test_cat_lookup_table_bins_like_the_search():
    from mmlspark_tpu.gbdt import binning
    X, _ = data_clicks.click_log(5, 20000, CARDS)
    X[19003, 20] = np.nan
    X[19004, 20] = 123456789.0
    mapper = binning.fit_bin_mapper(X[:15000], max_bin=255, seed=1,
                                    categorical_features=list(range(13, 39)))
    fast = mapper.transform(X)
    packed = mapper.transform_packed(X)
    assert np.array_equal(fast, packed)
    for j in (15, 20, 33):
        lut = mapper._cat_lut(j)
        assert lut is not None
        old = binning._CAT_LUT_MAX
        binning._CAT_LUT_MAX = 0
        try:
            assert mapper._cat_lut(j) is None
            searched = mapper._transform_cat(X[:, j], j)
        finally:
            binning._CAT_LUT_MAX = old
        assert np.array_equal(searched, fast[:, j])
    assert fast[19003, 20] == mapper.missing_bin == fast[19004, 20]


def forward_fill_leaf_of_position(leaf_start, leaf_cnt, n):
    out = np.zeros(n, np.int32)
    for leaf in np.argsort(leaf_start, kind="stable"):
        if leaf_cnt[leaf] > 0:
            out[leaf_start[leaf]:] = leaf
    return out


@pytest.mark.parametrize("seed", range(4))
def test_leaf_of_position_without_the_scan(seed):
    import jax.numpy as jnp
    from mmlspark_tpu.gbdt.grower import _leaf_of_position
    rng = np.random.default_rng(seed)
    L, n = 31, 500
    live = rng.permutation(L)[:rng.integers(1, L + 1)]
    cuts = np.sort(rng.choice(np.arange(1, n), size=len(live) - 1,
                              replace=False))
    starts = np.concatenate([[0], cuts])
    leaf_start = rng.integers(0, n, size=L).astype(np.int32)
    leaf_cnt = np.zeros(L, np.int32)
    leaf_start[live] = starts
    leaf_cnt[live] = np.diff(np.concatenate([starts, [n]]))
    got = np.asarray(_leaf_of_position(jnp.asarray(leaf_start),
                                       jnp.asarray(leaf_cnt), n))
    assert np.array_equal(got, forward_fill_leaf_of_position(
        leaf_start, leaf_cnt, n))


@pytest.mark.parametrize("n", [100, 8192, 8193, 20000])
def test_dot16_chunks_and_tail_sum_the_table(n):
    import jax.numpy as jnp
    from mmlspark_tpu.ops.histogram import _hist_dot16
    rng = np.random.default_rng(n)
    bins = rng.integers(0, 256, size=(n, 5), dtype=np.uint8)
    gh = np.stack([rng.normal(size=n), rng.random(n), np.ones(n)],
                  axis=1).astype(np.float32)
    got = np.asarray(_hist_dot16(jnp.asarray(bins), jnp.asarray(gh), 256,
                                 8192))
    want = gbdt.node_histogram(bins, None, gh[:, 0].astype(np.float64),
                               gh[:, 1].astype(np.float64), native=False)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)
    assert got[..., 2].sum() == n * 5


def test_row_counts_past_two_to_24_stay_exact():
    """A float32 sum of counts stops being exact at 2^24 rows: the totals
    are summed as int32 and cross to the host as two 16-bit halves."""
    import jax.numpy as jnp
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.grower import TreeArrays, _totals_from_hist
    hist = np.zeros((2, 4, 3), np.float32)
    hist[0, :, 2] = [16777215, 16777215, 3, 0]
    assert float(hist[0, :, 2].sum(dtype=np.float32)) != 33554433
    _, _, c = _totals_from_hist(jnp.asarray(hist))
    assert c.dtype == jnp.int32 and int(c) == 33554433
    L, W = 3, 8
    z = lambda n, dt=np.float32: jnp.zeros((1, n), dt)     # noqa: E731
    stacked = TreeArrays(
        node_feat=z(L - 1, np.int32), node_bin=z(L - 1, np.int32),
        node_left=z(L - 1, np.int32), node_right=z(L - 1, np.int32),
        node_gain=z(L - 1), node_value=z(L - 1), node_weight=z(L - 1),
        node_count=jnp.asarray([[33554433, 16777217]], jnp.int32),
        node_is_cat=z(L - 1, np.int32),
        node_cat_bits=jnp.zeros((1, L - 1, W), jnp.uint32),
        leaf_value=z(L), leaf_weight=z(L),
        leaf_count=jnp.asarray([[16777216, 16777215, 2]], jnp.int32),
        num_leaves=jnp.asarray([3], jnp.int32))
    (tree,), _ = engine._fetch_host_trees([stacked], L)
    assert list(tree.node_count) == [33554433, 16777217]
    assert list(tree.leaf_count) == [16777216, 16777215, 2]


def test_margin_pass_walks_bins_and_leaves_nothing_on_the_device():
    """The reference profile's margins come from a copy of the forest whose
    bitsets are over bins: the same margins as the raw-value forest gives,
    and the returned booster holds no stacked device arrays, whose size
    would follow the seed's bitset words."""
    from mmlspark_tpu.gbdt.engine import (_bin_representatives,
                                          _bin_space_forest)
    from mmlspark_tpu.gbdt.binning import fit_bin_mapper
    booster, _ = small_fit(categorical=True)
    assert booster._stacked is None and booster.reference_profile is not None
    X, _ = data_clicks.click_log(11, 6000, [min(c, 40) for c in CARDS])
    mapper = fit_bin_mapper(X, max_bin=255, seed=42,
                            categorical_features=list(range(13, 39)))
    bins = mapper.transform_packed(X)
    view = _bin_space_forest(booster, mapper)
    assert sum(len(t.cat_threshold) for t in view.trees) == 8 * sum(
        t.num_cat for t in booster.trees)
    Xbin = np.empty(bins.shape, np.float32)
    for j, rep in enumerate(_bin_representatives(mapper)):
        if mapper.is_categorical(j):
            rep = np.where(np.isnan(rep), np.nan, np.arange(len(rep)))
        Xbin[:, j] = rep[bins[:, j].astype(np.int64)]
    assert np.array_equal(np.asarray(view.predict_margin(Xbin)),
                          np.asarray(booster.predict_margin(X)))
    numeric, _ = small_fit(categorical=False)
    assert _bin_space_forest(numeric, mapper) is numeric
