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


def _bf16(x):
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)


def _dot16_case(kind, n, f, B, seed):
    """(bins uint8, gh float32) of one case of the on-chip dot16 build."""
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B, size=(n, f)).astype(np.uint8)
    gh = np.concatenate([rng.normal(size=(n, 2)), np.ones((n, 1))],
                        axis=1).astype(np.float32)
    if kind == "masked":            # rows outside the leaf: zero triple
        gh *= (rng.random(n) < 0.4)[:, None].astype(np.float32)
    elif kind == "bucket":
        # a bucket as grower._segment_hist gathers it: the segment's
        # tail holds sentinels clamped to the table's last row, whose
        # gradients the valid mask zeroes
        table, cnt = bins, (2 * n) // 3
        seg = np.concatenate([rng.permutation(n)[:cnt],
                              np.full(n - cnt, n, np.int64)])
        rows = np.minimum(seg, n - 1)
        bins = table[rows]
        gh = gh[rows] * (np.arange(n) < cnt)[:, None].astype(np.float32)
    elif kind == "rounding":
        # 1 + 2^-9 is not a bfloat16: an f32-operand build and a
        # bf16-operand build differ in the third digit
        gh[:, 0] = np.float32(1.0 + 2.0 ** -9)
        gh[:, 1] = np.float32(0.3)
    return bins, gh


_DOT16_CASES = [
    # kind, rows, features, bins: F in {1, 7, 8, 39, 50}, rows off and on
    # the kernel's 128-row lane tile and 256-row test chunk
    ("plain", 256, 8, 256), ("plain", 300, 1, 255), ("plain", 777, 7, 16),
    ("plain", 1024, 39, 255), ("plain", 513, 50, 256),
    ("masked", 640, 39, 255), ("masked", 100, 8, 16),
    ("bucket", 512, 50, 255), ("bucket", 333, 7, 256),
    ("rounding", 768, 8, 255), ("rounding", 1000, 39, 256),
    ("rounding", 130, 1, 16),
]


class TestDot16OnChip:
    """The dot16 build whose one-hot product stays on the chip
    (pallas_histogram.histogram_dot16; interpret mode here, Mosaic on the
    TPU: tests/test_mosaic_aot.py compiles it): today's result, to the
    order of the float32 sums."""

    @pytest.fixture
    def on_tpu(self, monkeypatch):
        """compute_histogram as it decides on the TPU, its kernel in
        interpret mode."""
        import mmlspark_tpu.ops.histogram as H
        monkeypatch.setattr(H.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(H, "pallas_interpret", lambda: True)
        return H

    @pytest.mark.parametrize("kind,n,f,B", _DOT16_CASES)
    def test_fused_build_matches_segment(self, kind, n, f, B):
        from mmlspark_tpu.ops.histogram import _hist_dot16
        from mmlspark_tpu.ops.pallas_histogram import histogram_dot16
        bins, gh = _dot16_case(kind, n, f, B, seed=n + f)
        got = np.asarray(histogram_dot16(
            jnp.asarray(bins.T), jnp.asarray(gh), B, chunk=256,
            interpret=True), np.float64)
        assert got.shape == (f, B, 3)
        # counts: exact
        np.testing.assert_array_equal(
            got[..., 2], _ref_hist(bins, gh.astype(np.float64), B)[..., 2])
        # grad and hess: the float64 sums of the bf16-rounded operands,
        # to float32 summation; and within the bf16-operand bound of the
        # unrounded ones (half an ulp: at most 2^-8 of each cell's mass)
        want = _ref_hist(bins, _bf16(gh), B)
        mass = _ref_hist(bins, np.abs(gh).astype(np.float64), B)
        assert np.all(np.abs(got - want) <= 1e-6 * mass + 1e-6)
        exact = _ref_hist(bins, gh.astype(np.float64), B)
        assert np.all(np.abs(got - exact) <= 2.0 ** -8 * mass + 1e-6)
        if kind == "rounding":
            # the rounding is there: the unrounded sums are NOT met to
            # float32 summation, so the operands were bfloat16
            assert np.max(np.abs(got - exact)[..., 0]) > 1e-4 * n / B
        # today's formulation on the same bf16 operands (the CPU's XLA
        # multiplies f32 exactly): summation order apart
        xla = np.asarray(_hist_dot16(jnp.asarray(bins), jnp.asarray(
            _bf16(gh), jnp.float32), B, 256))
        np.testing.assert_allclose(got, xla, rtol=1e-5, atol=1e-5)
        seg = np.asarray(compute_histogram(
            bins, _bf16(gh).astype(np.float32), B, method="segment"))
        np.testing.assert_allclose(got, seg, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("chunk", [128, 512, 8192])
    def test_row_chunk_does_not_change_the_sums(self, chunk):
        """1000 rows in chunks that do not divide them, and in one chunk
        (the default: the table's rows rounded up to the lane tile)."""
        from mmlspark_tpu.ops.pallas_histogram import histogram_dot16
        bins, gh = _dot16_case("masked", 1000, 39, 255, seed=3)
        got = np.asarray(histogram_dot16(
            jnp.asarray(bins.T), jnp.asarray(gh), 255, chunk=chunk,
            interpret=True))
        want = _ref_hist(bins, _bf16(gh), 255)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_dot16_on_the_tpu_is_the_fused_build(self, on_tpu, monkeypatch):
        """``compute_histogram(method='dot16')`` and ``auto`` reach the
        kernel on the TPU, with the signature and the result they had."""
        import mmlspark_tpu.ops.pallas_histogram as PH
        calls = []
        real = PH.histogram_dot16
        monkeypatch.setattr(PH, "histogram_dot16", lambda *a, **k: (
            calls.append(a[0].shape), real(*a, **k))[1])
        monkeypatch.setattr(on_tpu, "_native_available", lambda: False)
        bins, gh = _dot16_case("plain", 300, 7, 255, seed=5)
        want = _ref_hist(bins, _bf16(gh), 255)
        for method in ("dot16", "auto"):
            got = np.asarray(compute_histogram(bins, gh, 255, method=method))
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        assert calls == [(7, 300), (7, 300)]
        assert on_tpu.histogram_build("auto", 300, 255, False) == \
            "dot16/mosaic"

    @pytest.mark.parametrize("why,B,dtype", [
        ("integer gradients", 255, np.int16),
        ("more bins than two nibbles hold", 300, np.float32)])
    def test_other_cases_keep_todays_path(self, on_tpu, monkeypatch, why, B,
                                          dtype):
        """Quantized gradients (the int32 kernel is refused by this
        stack) and EFB bundles over 256 bins: XLA's formulation, as
        before, and the kernel is not entered."""
        import mmlspark_tpu.ops.pallas_histogram as PH

        def refuse(*a, **k):
            raise AssertionError(f"kernel entered with {why}")
        monkeypatch.setattr(PH, "histogram_dot16", refuse)
        rng = np.random.default_rng(0)
        bins = rng.integers(0, B, size=(400, 5)).astype(np.int32)
        gh = rng.integers(-100, 100, size=(400, 3)).astype(dtype)
        got = np.asarray(compute_histogram(bins, gh, B, method="dot16"))
        assert got.dtype == (np.int32 if dtype == np.int16 else np.float32)
        np.testing.assert_allclose(
            got, _ref_hist(bins, gh.astype(np.float64), B), atol=1e-3)
        assert on_tpu.histogram_build(
            "dot16", 400, B, dtype == np.int16) == "dot16/xla"

    def test_cpu_keeps_xla_for_an_explicit_dot16(self):
        import mmlspark_tpu.ops.histogram as H
        assert H.histogram_build("dot16", 4096, 255, False) == "dot16/xla"
        assert H.histogram_build("auto", 4096, 255, False) in (
            "native", "segment")

    def test_kernel_refuses_more_than_256_bins(self):
        from mmlspark_tpu.ops.pallas_histogram import histogram_dot16
        with pytest.raises(ValueError, match="256"):
            histogram_dot16(jnp.zeros((8, 128), jnp.uint8),
                            jnp.zeros((128, 3)), 300, interpret=True)


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
