"""Fit-time HBM budget guard (VERDICT r3 next #8; BASELINE config 5
scale).  The estimate must track the real resident arrays and the guard
must fail FAST — before compile — with remediation, never a device OOM."""

import numpy as np
import pytest

from mmlspark_tpu.gbdt.budget import (check_fit_budget,
                                      device_capacity_bytes,
                                      estimate_fit_bytes)


class TestEstimate:
    def test_breakdown_scales_linearly_in_rows(self):
        a = estimate_fit_bytes(1_000_000, 39, 256, 255)
        b = estimate_fit_bytes(2_000_000, 39, 256, 255)
        assert b["bins"] == 2 * a["bins"]
        assert b["row_vectors"] == 2 * a["row_vectors"]
        assert b["leaf_hist"] == a["leaf_hist"]  # row-independent

    def test_criteo_class_config_fits_modern_hbm_when_sharded(self):
        """BASELINE config 5 (numLeaves=255, maxBin=255, ~45M rows):
        one chip is tight; 8-way data sharding must fit comfortably in
        16 GB/device."""
        one = estimate_fit_bytes(45_000_000, 39, 256, 255)["total"]
        sharded = estimate_fit_bytes(45_000_000 // 8, 39, 256, 255)["total"]
        assert sharded < 16e9 / 2
        assert one > sharded * 6   # sharding actually buys headroom

    @pytest.mark.parametrize("rows,features,on_chip,whole,measured,high", [
        # XLA's dot16 build, its one-hots in HBM: epsilon_fit, bosch_fit
        # (ledger, PR 26), criteo_fit (chip run, PR 27)
        (400_000, 2000, False, True, 9_179_813_376, 1.1),
        (1_183_747, 968, False, True, 7_751_050_240, 1.1),
        (30_000_000, 39, False, True, 5_009_822_208, 1.25),
        # the Mosaic build, the same cells (chip runs, PR 28)
        (400_000, 2000, True, True, 5_387_627_520, 1.1),
        (1_183_747, 968, True, True, 7_451_223_040, 1.1),
        # a narrow table: XLA keeps no second copy of it nor all of the
        # partition's slices at once, and the guard errs high
        (30_000_000, 39, True, True, 4_449_167_872, 1.7),
        # a child over 2^16 rows histogrammed in chunks (chip runs, PR 34)
        (400_000, 2000, True, False, 3_539_702_784, 1.1),
        (1_183_747, 968, True, False, 3_327_691_776, 1.1),
        (30_000_000, 39, True, False, 2_998_680_576, 1.5),
    ])
    def test_estimate_against_the_peaks_measured_on_a_v5e(
            self, rows, features, on_chip, whole, measured, high):
        """``peak_hbm_bytes`` of the three one-chip cells (PERF.md): the
        estimate stands within a tenth of each, for the histogram build
        the fit compiles.  Without the transposed bins and the build's
        temporaries it read 0.37, 0.52 and 0.82 of the first three.
        ``whole``: the peak is of a program that gathered a child's rows
        at the next power of two (before PR 34); the estimate prices the
        build ladder's top rung, so the rest of that bucket, which those
        programs held beside it, is added back."""
        est = estimate_fit_bytes(rows, features, 256, 255, chunk=2,
                                 hist_on_chip=on_chip)
        top = 1 << 16
        rest = ((1 << (rows - 1).bit_length()) - top) if whole else 0
        total = est["total"] + rest * (
            features * (2 if on_chip else 1) + 12)
        assert 0.9 < total / measured < high
        assert est["bins_transposed"] == est["bins"]
        assert est["hist_build"] == (top * features if on_chip else
                                     min(rows, 8192) * features * 320)
        assert est["bucket_transient"] == top * (features + 12)
        assert est["partition_transient"] == \
            (1 << (rows - 1).bit_length()) * 20

    def test_estimate_against_the_bundled_cells_measured_peak(self):
        """``allstate_fit`` (my chip run, PR 34; 10 867 609 600 with the
        2^24-row bucket of rows, PR 33): 8 143 027 712 bytes at
        13 184 290 rows bundled into 90 columns, the per-leaf cache 4228
        features wide.  Counted 4228 wide the table alone is 55.7 GB;
        counted 90 wide with a 90-wide cache the estimate reads 0.57 of
        the peak."""
        kw = dict(chunk=2, hist_on_chip=True)
        est = estimate_fit_bytes(13_184_290, 4228, 256, 255,
                                 num_bundles=90, **kw)
        assert 0.9 < est["total"] / 8_143_027_712 < 1.1
        assert est["leaf_hist"] == 255 * 4228 * 256 * 12
        assert est["bins"] == 13_184_290 * 90
        narrow = estimate_fit_bytes(13_184_290, 90, 256, 255, **kw)
        assert narrow["total"] / 8_143_027_712 < 0.6
        assert estimate_fit_bytes(13_184_290, 4228, 256, 255,
                                  **kw)["bins"] > 55e9

    def test_estimate_against_the_ranking_cells_measured_peak(self):
        """``istella_fit`` (my chip run, PR 34; 8 492 530 176 with the
        2^23-row bucket of rows, PR 31): 4 493 147 648 bytes at
        7 325 625 x 220 with a query layout of 156 560 112 bytes.  Without
        the layout and the pair pass's temporaries the estimate reads
        0.87 of it; with them 0.98."""
        kw = dict(chunk=2, hist_on_chip=True)
        bare = estimate_fit_bytes(7_325_625, 220, 255, 255, **kw)
        est = estimate_fit_bytes(7_325_625, 220, 255, 255,
                                 rank_layout_bytes=156_560_112, **kw)
        assert bare["total"] / 4_493_147_648 < 0.9
        assert 0.9 < est["total"] / 4_493_147_648 < 1.1
        assert est["rank_layout"] == est["total"] - bare["total"]
        assert "rank_layout" not in bare

    def test_estimate_against_the_multiclass_cells_measured_peak(self):
        """``mnist8m_fit`` on a v5e: 5 345 013 248 bytes at 2 400 000 x
        784, 10 classes, scores and gradients class-major in the class
        loop.  A class loop unrolled in Python, with its scores (n, 10),
        read 6 018 166 784 at the same shape."""
        est = estimate_fit_bytes(2_400_000, 784, 256, 255, num_class=10,
                                 chunk=2, hist_on_chip=True)
        assert 0.9 < est["total"] / 5_345_013_248 < 1.1
        one = estimate_fit_bytes(2_400_000, 784, 256, 255, chunk=2,
                                 hist_on_chip=True)
        assert est["gradients"] == 2_400_000 * 4 * (4 * 10 + 3)
        assert one["gradients"] == 2_400_000 * 4 * 5

    def test_ranking_fit_hands_its_layout_to_the_guard(self, monkeypatch):
        from mmlspark_tpu.gbdt import budget
        from tests.test_istella_cell import small_rank_fit
        seen = {}
        real = budget.check_fit_budget

        def spy(*a, **kw):
            seen.update(kw)
            return real(*a, **kw)
        monkeypatch.setattr(budget, "check_fit_budget", spy)
        _, _, grad = small_rank_fit()
        assert seen["rank_layout_bytes"] == grad.nbytes > 0

    def test_bagging_and_validation_terms_counted(self):
        base = estimate_fit_bytes(1 << 20, 20, 64, 31)
        bag = estimate_fit_bytes(1 << 20, 20, 64, 31, bagging=True)
        val = estimate_fit_bytes(1 << 20, 20, 64, 31, n_val_local=1 << 18)
        assert bag["total"] > base["total"]
        assert val["total"] > base["total"]


class TestGuard:
    def test_env_override_and_fail_fast(self, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_HBM_BYTES", "1e6")
        assert device_capacity_bytes() == 1_000_000
        with pytest.raises(MemoryError, match="shard rows over a larger"):
            check_fit_budget(10_000_000, 39, 256, 255, verbosity=0)

    def test_guard_passes_small_config(self, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_HBM_BYTES", "16e9")
        costs = check_fit_budget(100_000, 39, 256, 255, verbosity=0)
        assert costs["total"] < 16e9

    def test_engine_fit_fails_fast_on_tiny_budget(self, monkeypatch):
        from mmlspark_tpu.gbdt import LightGBMClassifier
        monkeypatch.setenv("MMLSPARK_TPU_HBM_BYTES", "1e5")
        X = np.random.default_rng(0).normal(size=(4000, 10))
        y = (X[:, 0] > 0).astype(float)
        with pytest.raises(MemoryError, match="per device"):
            LightGBMClassifier(numIterations=2, verbosity=0).fit(
                {"features": X, "label": y})

    def test_mesh_divides_local_rows(self, monkeypatch):
        """The per-device estimate must use the SHARD row count: a config
        that overflows serially passes when sharded 8 ways."""
        import jax
        from jax.sharding import Mesh

        from mmlspark_tpu.core.mesh import (DATA_AXIS, FEATURE_AXIS,
                                            build_mesh)
        from mmlspark_tpu.gbdt import LightGBMClassifier
        X = np.random.default_rng(0).normal(size=(8000, 10))
        y = (X[:, 0] > 0).astype(float)
        t = {"features": X, "label": y}
        est = estimate_fit_bytes(8000, 10, 64, 31,
                                 chunk=2, bin_itemsize=1)["total"]
        shard_est = estimate_fit_bytes(1000, 10, 64, 31,
                                       chunk=2, bin_itemsize=1)["total"]
        budget = (est + shard_est) // 2
        monkeypatch.setenv("MMLSPARK_TPU_HBM_BYTES", str(budget))
        serial_mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                           (DATA_AXIS, FEATURE_AXIS))
        with pytest.raises(MemoryError):
            LightGBMClassifier(numIterations=2, numLeaves=31, maxBin=63,
                               verbosity=0).setMesh(serial_mesh).fit(t)
        model = LightGBMClassifier(numIterations=2, numLeaves=31,
                                   maxBin=63, verbosity=0).setMesh(
            build_mesh(data=8, feature=1)).fit(t)
        assert len(model.getModel().trees) >= 1
