"""Missing values as LightGBM treats them: NaN falls in the trailing
(missing) bin, and a split of a feature of missing type NaN sends it the
better of its two default directions.

Every number here is held to a plain NumPy reference written in this
file on seeded random tables with NaN: the split search over both
directions (and its tie rule), the partition (XLA's bucket ladder and
the CPU's native pass), the binned walks, the exported
``decision_type`` bits and their text round trip, the raw-row walks
(``_predict_forest`` and the native forest) against the binned scores,
and SHAP's local accuracy on NaN rows.  A table with no NaN still
compiles the one-direction program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.gbdt import booster as bm
from mmlspark_tpu.gbdt import grower
from mmlspark_tpu.gbdt.binning import fit_bin_mapper
from mmlspark_tpu.gbdt.engine import TrainParams, _scans_missing, train
from mmlspark_tpu.gbdt.grower import GrowerConfig
from mmlspark_tpu.gbdt.objectives import get_objective

B = 32                      # bins: 31 value bins and the missing bin
MISSING = B - 1


def _cfg(**kw):
    base = dict(num_leaves=15, num_bins=B, min_data_in_leaf=3,
                min_sum_hessian_in_leaf=1e-3, has_missing=True)
    base.update(kw)
    return GrowerConfig(**base)


# ------------------------------------------------------------ reference


def ref_best_split(hist, pg, ph, pc, md, mh):
    """``(gain, feature, bin, dleft)`` in float64: every feature, both
    directions, every threshold bin; a tie keeps the lower feature, then
    missing right, then the lower bin.  Missing left is scored only where
    the node holds missing rows and the right side keeps a row."""
    hist = np.asarray(hist, np.float64)
    f, nb, _ = hist.shape
    best = (-np.inf, -1, -1, -1)

    def gain(gl, hl, gr, hr):
        return gl * gl / hl + gr * gr / hr - pg * pg / ph

    for j in range(f):
        nan = hist[j, nb - 1]
        for d in (0, 1):
            if d == 1 and nan[2] <= 0:
                continue
            for b in range(nb - 1):
                left = hist[j, :b + 1].sum(axis=0) + (nan if d else 0)
                gl, hl, cl = left
                gr, hr, cr = pg - gl, ph - hl, pc - cl
                if d == 1 and cr < 1:
                    continue
                if cl < md or cr < md or hl < mh or hr < mh:
                    continue
                g = gain(gl, hl, gr, hr)
                if g > best[0]:
                    best = (g, j, b, d)
    return best


def _hist_of(bins, gh):
    """``(f, B, 3)`` sums of (g, h, 1) over the rows."""
    f = bins.shape[1]
    hist = np.zeros((f, B, 3), np.float64)
    for j in range(f):
        for c in range(3):
            hist[j, :, c] = np.bincount(bins[:, j], weights=gh[:, c],
                                        minlength=B)
    return hist


def _nan_table(seed, n=3000, f=6, rates=(0.5, 0.3, 0.0, 0.6, 0.0, 0.2)):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    X[rng.random((n, f)) < np.asarray(rates)[None, :f]] = np.nan
    y = ((np.nan_to_num(X[:, 0], nan=1.5) - 1.2 * np.isnan(X[:, 3])
          + 0.6 * np.nan_to_num(X[:, 1], nan=-1.0)
          + 0.4 * rng.standard_normal(n)) > 0.1).astype(np.float64)
    return X, y


def _split(hist, cfg):
    hist = jnp.asarray(hist, jnp.float32)
    tot = jnp.sum(hist[0], axis=0)
    fi = jnp.asarray(grower.make_feat_info(hist.shape[0]))
    out = jax.jit(lambda h: grower.find_best_split(
        h, tot[0], tot[1], tot[2], fi, jnp.asarray(True), cfg))(hist)
    gain, feat, b, _, _, dleft = out
    return float(gain), int(feat), int(b), int(dleft)


# ------------------------------------------------------------ split scan


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_split_matches_reference_on_random_nan_tables(seed):
    """NaN in some features and not in others: the best split and its
    default direction are the reference's."""
    X, y = _nan_table(seed, n=800)
    mapper = fit_bin_mapper(X, max_bin=B - 1)
    bins = mapper.transform(X).astype(np.int64)
    rng = np.random.default_rng(seed + 10)
    gh = np.stack([(rng.random(len(y)) - y) * 2.0,
                   rng.uniform(0.1, 0.3, len(y)), np.ones(len(y))], axis=1)
    # a node: the rows of a random half, as a split would leave them
    rows = np.flatnonzero(rng.random(len(y)) < 0.5)
    hist = _hist_of(bins[rows], gh[rows])
    tot = hist[0].sum(axis=0)
    want = ref_best_split(hist, *tot, 3, 1e-3)
    got = _split(hist, _cfg())
    assert got[1:] == want[1:]
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)


def _one_feature(value_bins, nan):
    """A one-feature histogram: ``value_bins`` {bin: (g, h, c)}, and the
    missing bin's (g, h, c)."""
    hist = np.zeros((1, B, 3))
    for b, v in value_bins.items():
        hist[0, b] = v
    hist[0, MISSING] = nan
    return hist


@pytest.mark.parametrize("nan_g,want_dleft", [(-8.0, 1), (8.0, 0)])
def test_missing_rows_join_the_side_they_fit(nan_g, want_dleft):
    """Low bins carry negative gradients, high bins positive: NaN rows
    with negative gradients go left, with positive ones right."""
    hist = _one_feature({b: (-2.0 if b < 4 else 2.0, 1.0, 4.0)
                         for b in range(8)}, (nan_g, 4.0, 4.0))
    tot = hist[0].sum(axis=0)
    want = ref_best_split(hist, *tot, 1, 1e-3)
    got = _split(hist, _cfg())
    assert (got[2], got[3]) == (3, want_dleft) == (want[2], want[3])


def test_exact_tie_keeps_missing_right():
    """Value bins a, c, a and NaN: missing right at bin 0 (a | c a NaN)
    and missing left at bin 1 (a c NaN | a) mirror each other, so their
    gains tie to the last bit (every sum is exact); the tie keeps missing
    right."""
    a, c, nan = (8.0, 2.0, 4.0), (0.0, 2.0, 4.0), (4.0, 4.0, 8.0)
    hist = _one_feature({0: a, 1: c, 2: a}, nan)
    tot = hist[0].sum(axis=0)
    assert ref_best_split(hist, *tot, 1, 1e-3) == (10.0, 0, 0, 0)
    assert _split(hist, _cfg()) == (10.0, 0, 0, 0)


def test_all_nan_feature_in_node_cannot_split():
    """Feature 0 is NaN in every row of the node: neither direction can
    split it, and the split goes to feature 1."""
    hist = np.zeros((2, B, 3))
    hist[0, MISSING] = (-6.0, 8.0, 16.0)
    hist[1, 2] = (-7.0, 4.0, 8.0)
    hist[1, 9] = (1.0, 4.0, 8.0)
    tot = hist[0].sum(axis=0)
    want = ref_best_split(hist, *tot, 1, 1e-3)
    got = _split(hist, _cfg())
    assert got[1:] == want[1:] == (1, 2, 0)


def test_one_value_bin_and_nan_only_isolates_the_missing_bin():
    """``num_bin <= 2`` (one value bin besides NaN): the one split there
    is, values left and NaN right, whatever the gradients."""
    for nan_g in (-5.0, 5.0):
        hist = _one_feature({0: (3.0, 4.0, 8.0)}, (nan_g, 4.0, 8.0))
        tot = hist[0].sum(axis=0)
        assert _split(hist, _cfg())[1:] == (0, 0, 0)
        assert ref_best_split(hist, *tot, 1, 1e-3)[1:] == (0, 0, 0)


# ------------------------------------------------------------- partition


def _ref_partition(order, col, off, cnt, thr, left_bin):
    seg = order[off:off + cnt]
    c = col[seg]
    left = (c <= thr) | (c == left_bin)
    out = order.copy()
    out[off:off + cnt] = np.concatenate([seg[left], seg[~left]])
    return out, int(left.sum()), int((~left).sum()), int((c == MISSING).sum())


@pytest.mark.parametrize("method", ["auto", "segment"])
@pytest.mark.parametrize("dleft", [0, 1])
def test_partition_routes_the_missing_bin(method, dleft):
    """``_partition_switch``: XLA's bucket ladder (``segment``) and the
    CPU's native pass (``auto``) send the missing bin by the split's
    default direction and count its rows."""
    rng = np.random.default_rng(7 + dleft)
    n = 5000
    col = rng.integers(0, B - 1, n).astype(np.uint8)
    col[rng.random(n) < 0.4] = MISSING
    order = np.concatenate([rng.permutation(n), np.full(8192, n)]
                           ).astype(np.int32)
    off, cnt, thr = 700, 3100, 12
    left_bin = MISSING if dleft else -1
    cfg = _cfg(hist_method=method)
    sizes = grower._bucket_sizes(n, cfg)
    fn = jax.jit(lambda o, c: grower._partition_switch(
        o, c, jnp.int32(off), jnp.int32(cnt), jnp.int32(thr),
        jnp.asarray(False), jnp.zeros(cfg.cat_words, jnp.uint32), n, sizes,
        cfg, left_bin=jnp.int32(left_bin)))
    got, cl, cr, cm = fn(jnp.asarray(order), jnp.asarray(col))
    want, wl, wr, wm = _ref_partition(order, col, off, cnt, thr, left_bin)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert (int(cl), int(cr), int(cm)) == (wl, wr, wm)


# -------------------------------------------- walks, export, predictions


@pytest.fixture(scope="module")
def fitted():
    """A forest fit on a NaN table, with its device trees kept."""
    X, y = _nan_table(11)
    mapper = fit_bin_mapper(X, max_bin=B - 1)
    bins = mapper.transform_packed(X)
    params = TrainParams(num_iterations=4, num_leaves=15,
                         min_data_in_leaf=5, max_bin=B - 1, verbosity=0)
    kept = []
    model = train(bins, y, None, mapper, get_objective("binary"), params,
                  callbacks=[lambda it, trees: kept.append(list(trees))])
    return X, y, mapper, bins, model, kept[-1]


def _ref_walk(tree, bins):
    """Leaf of every binned row: ``bin <= node_bin`` or the node's
    ``node_dleft`` bin goes left."""
    out = np.zeros(len(bins), np.int64)
    feat, thr = np.asarray(tree.node_feat), np.asarray(tree.node_bin)
    dl = np.asarray(tree.node_dleft)
    left, right = np.asarray(tree.node_left), np.asarray(tree.node_right)
    for r, row in enumerate(bins):
        node = 0 if int(tree.num_leaves) > 1 else -1
        while node >= 0:
            v = int(row[feat[node]])
            node = left[node] if (v <= thr[node] or v == dl[node]) \
                else right[node]
        out[r] = ~node
    return out


def test_binned_walk_routes_by_default_direction(fitted):
    X, y, mapper, bins, model, trees = fitted
    some_left = False
    for t in trees:
        assert t.node_dleft is not None and t.node_missing is not None
        m = int(t.num_leaves) - 1
        some_left |= bool((np.asarray(t.node_dleft)[:m] == MISSING).any())
        got = np.asarray(grower.predict_tree_binned(t, jnp.asarray(bins),
                                                    15))
        want = np.asarray(t.leaf_value)[_ref_walk(t, np.asarray(bins))]
        np.testing.assert_array_equal(got, want)
    assert some_left, "no split sent NaN left: the table plants none"


def test_exported_bits_and_text_round_trip(fitted):
    X, y, mapper, bins, model, trees = fitted
    for t, ht in zip(trees, model.trees):
        m = ht.decision_type.size
        dleft = np.asarray(t.node_dleft)[:m] >= 0
        nan_type = mapper.has_missing[ht.split_feature] | dleft
        np.testing.assert_array_equal(((ht.decision_type >> 1) & 1) > 0,
                                      dleft | ~nan_type)
        np.testing.assert_array_equal(
            (ht.decision_type >> 2) & 3, np.where(nan_type, 2, 0))
        # a feature without NaN keeps LightGBM's 2
        assert set(ht.decision_type[~nan_type]) <= {2}
    text = model.save_native_model_string()
    back = bm.Booster.load_native_model_string(text)
    trees_text = text.split("end of trees")[0]
    assert back.save_native_model_string().split("end of trees")[0] \
        == trees_text
    for a, b in zip(model.trees, back.trees):
        np.testing.assert_array_equal(a.decision_type, b.decision_type)
    np.testing.assert_array_equal(np.asarray(back.predict_margin(X)),
                                  np.asarray(model.predict_margin(X)))


def test_raw_rows_score_as_the_binned_walks(fitted):
    """Raw rows holding NaN through ``_predict_forest`` and the native
    forest give the margins the binned walks give the training rows."""
    X, y, mapper, bins, model, trees = fitted
    binned = sum(np.asarray(grower.predict_tree_binned(
        t, jnp.asarray(bins), 15), np.float64) for t in trees)
    # the exported first tree carries the boost-from-average bias
    binned = binned + (model.trees[0].leaf_value[0]
                       - float(np.asarray(trees[0].leaf_value)[0]))
    s = model._stack()
    jit = np.asarray(bm._predict_forest(
        jnp.asarray(X), s["feat"], s["thr"], s["left"], s["right"],
        s["leaf"], s["single"], s["is_cat"], s["dleft"], s["cat_bnd"],
        s["cat_words"], s["depth"], 1, s["has_cat"]))[:, 0]
    np.testing.assert_allclose(jit + model.init_score, binned, rtol=1e-5,
                               atol=1e-5)
    from mmlspark_tpu import native
    if native.predict_forest_available():
        np.testing.assert_array_equal(np.asarray(model.predict_margin(X)),
                                      jit + np.float32(model.init_score))
    assert np.isnan(X).any(axis=1).mean() > 0.5


def test_shap_sums_to_the_margin_on_nan_rows(fitted):
    X, y, mapper, bins, model, trees = fitted
    rows = X[np.isnan(X).any(axis=1)][:40]
    phi = model.predict_contrib(rows)
    np.testing.assert_allclose(phi.sum(axis=1),
                               np.asarray(model.predict_margin(rows)),
                               rtol=1e-4, atol=1e-4)


def test_counters_count_the_default_directions(fitted):
    X, y, mapper, bins, model, trees = fitted
    stats = grower.missing_split_stats(trees)
    held = np.concatenate([np.asarray(t.node_missing)[:int(t.num_leaves) - 1]
                           for t in trees])
    assert stats["missing_splits"] == int((held > 0).sum()) > 0
    assert 0 < stats["missing_left_splits"] < stats["missing_splits"]
    assert stats["missing_rows_routed"] == int(held.sum())
    # node_missing is the node's rows in the split feature's missing bin
    t = trees[0]
    assert int(np.asarray(t.node_missing)[0]) == int(
        (np.asarray(bins)[:, int(t.node_feat[0])] == MISSING).sum())


# ----------------------------------------- the one-direction program


def test_table_without_nan_compiles_the_one_direction_grower():
    """No NaN: ``has_missing`` is off, the config is the one-direction
    config, and nothing of the second direction is in the program."""
    X, _ = _nan_table(3, n=500, rates=(0.0,) * 6)
    mapper = fit_bin_mapper(X, max_bin=B - 1)
    assert not _scans_missing(mapper, TrainParams())
    X[::7, 2] = np.nan
    assert _scans_missing(fit_bin_mapper(X, max_bin=B - 1), TrainParams())
    assert not _scans_missing(fit_bin_mapper(X, max_bin=B - 1),
                              TrainParams(use_missing=False))
    with pytest.raises(NotImplementedError, match="zeroAsMissing"):
        _scans_missing(mapper, TrainParams(zero_as_missing=True))

    bins = jnp.zeros((256, 4), jnp.uint8)
    gh = jnp.ones((256, 3), jnp.float32)
    fi = jnp.asarray(grower.make_feat_info(4))

    def lowered(cfg):
        return grower.grow_tree.lower(bins, gh, fi, cfg).as_text(
            debug_info=True)

    plain = GrowerConfig(num_leaves=7, num_bins=B)
    assert plain == GrowerConfig(num_leaves=7, num_bins=B,
                                 has_missing=False)
    assert "missing_left" not in lowered(plain)
    assert "missing_left" in lowered(_cfg(num_leaves=7))
    tree, _ = grower.grow_tree(bins, gh, fi, plain)
    assert tree.node_dleft is None and tree.node_missing is None



@pytest.mark.parametrize("with_missing", [False, True])
def test_checkpointed_tree_chunks_keep_the_default_directions(
        tmp_path, fitted, with_missing):
    """The chunk files of a checkpoint hold ``node_dleft`` and
    ``node_missing`` where the trees have them, and no such member where
    they do not."""
    from mmlspark_tpu.gbdt import engine
    t = fitted[5][0]
    if not with_missing:
        t = t._replace(node_dleft=None, node_missing=None)
    chunk = jax.tree_util.tree_map(lambda a: np.asarray(a)[None], t)
    engine._ckpt_write_chunks(str(tmp_path), [chunk])
    back, = engine._ckpt_read_chunks(str(tmp_path), 1)
    for name in grower.TreeArrays._fields:
        a, b = getattr(chunk, name), getattr(back, name)
        if a is None:
            assert b is None, name
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)
