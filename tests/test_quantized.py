"""Quantized-gradient training (ISSUE 17): wire-policy resolution,
seeded-SR determinism, integer exactness (sibling subtraction, method
parity), low-bit collective pricing, vendored-data accuracy parity, and
the provenance surfaces (last_fit_info + /metrics)."""

import gzip
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core.mesh import DATA_AXIS, build_mesh
from mmlspark_tpu.gbdt import LightGBMClassifier, LightGBMRegressor
from mmlspark_tpu.gbdt import engine as eng
from mmlspark_tpu.gbdt import grower as G
from mmlspark_tpu.gbdt.engine import TrainParams, _resolve_quantized
from mmlspark_tpu.ops import histogram as H

DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmarks", "data")


def _mesh_of(d):
    """The only thing _resolve_quantized reads off the mesh is the data
    axis size."""
    return types.SimpleNamespace(shape={DATA_AXIS: d})


def _forest(model):
    return model.getModel().save_native_model_string()


# ------------------------------------------------------- wire policy


class TestWirePolicy:
    def test_off_is_identity(self):
        p = TrainParams(quantized_grad="off")
        assert _resolve_quantized(p, 10_000, _mesh_of(4), "ring") == \
            (0, 0, "none", "ring", "none")

    def test_serial_has_no_wire(self):
        p = TrainParams(quantized_grad="16")
        bits, mc, wire, coll, down = _resolve_quantized(
            p, 1000, _mesh_of(1), "psum")
        assert (bits, wire, down) == (16, "none", "none")
        assert mc == 32767          # full 16-bit grid, no clamp needed

    def test_int8_wire_when_accumulated_codes_fit(self):
        p = TrainParams(quantized_grad="8")
        bits, mc, wire, _, down = _resolve_quantized(
            p, 1, _mesh_of(2), "psum")
        assert (bits, mc, wire, down) == (8, 127, "int8", "none")

    def test_int16_clamp_narrows_the_grid(self):
        """n*32767 blows past int16, but >=3 code levels survive a
        clamp — the grid narrows so the slab rides a 2-byte wire."""
        p = TrainParams(quantized_grad="16")
        bits, mc, wire, _, down = _resolve_quantized(
            p, 3000, _mesh_of(2), "psum")
        assert (bits, mc, wire, down) == (16, 10, "int16", "none")
        assert 3000 * mc <= 32767

    def test_int32_wire_when_clamp_would_kill_resolution(self):
        """Past n=32767//3 a 2-byte wire would leave <3 code levels;
        resolution wins and the slab stays int32."""
        p = TrainParams(quantized_grad="16")
        bits, mc, wire, _, _ = _resolve_quantized(
            p, 20_000, _mesh_of(2), "psum")
        assert (mc, wire) == (32767, "int32")

    def test_int32_overflow_headroom_clamp(self):
        """The accumulator bound: n*max_code must fit int32 even when
        every row lands in one bin."""
        n = 1 << 26
        p = TrainParams(quantized_grad="16")
        _, mc, _, _, _ = _resolve_quantized(p, n, _mesh_of(2), "psum")
        assert mc == (2**31 - 1) // n == 31
        assert n * mc < 2**31

    def test_ring_downgrades_when_codes_overflow_f32_lanes(self):
        """The ring transport carries f32 lanes; integer sums are exact
        there only below 2^24 — above, the fit keeps psum and says so."""
        p = TrainParams(quantized_grad="16")
        _, mc, wire, coll, down = _resolve_quantized(
            p, 20_000, _mesh_of(2), "ring")
        assert 20_000 * mc >= (1 << 24)
        assert (coll, down) == ("psum", "quantized_unsupported")

    def test_ring_kept_when_codes_fit_f32_lanes(self):
        p = TrainParams(quantized_grad="16")
        _, mc, _, coll, down = _resolve_quantized(
            p, 3000, _mesh_of(2), "ring")
        assert 3000 * mc < (1 << 24)
        assert (coll, down) == ("ring", "none")

    def test_dart_and_ranking_downgrade_with_reason(self):
        p = TrainParams(quantized_grad="16", boosting="dart")
        assert _resolve_quantized(p, 1000, _mesh_of(2), "psum") == \
            (0, 0, "none", "psum", "quantized_unsupported")
        p = TrainParams(quantized_grad="16")
        assert _resolve_quantized(p, 1000, _mesh_of(2), "psum",
                                  ranking=True) == \
            (0, 0, "none", "psum", "quantized_unsupported")


class TestTrainParamsCoercion:
    @pytest.mark.parametrize("raw", ["off", "0", "", "false", "none",
                                     False, 0, None])
    def test_falsy_spellings_mean_off(self, raw):
        assert TrainParams(quantized_grad=raw).quantized_grad == "off"

    @pytest.mark.parametrize("raw,want", [(16, "16"), ("16", "16"),
                                          (8, "8"), (" 8 ", "8")])
    def test_bit_widths(self, raw, want):
        assert TrainParams(quantized_grad=raw).quantized_grad == want

    def test_unsupported_width_rejected(self):
        with pytest.raises(ValueError, match="quantizedGrad"):
            TrainParams(quantized_grad="12")


# ----------------------------------------------- integer exactness


class TestIntegerExactness:
    def _codes(self, n, f, mc=127, seed=3):
        rng = np.random.default_rng(seed)
        bins = jnp.asarray(rng.integers(0, 64, size=(n, f),
                                        dtype=np.uint8))
        gh = jnp.asarray(np.concatenate(
            [rng.integers(-mc, mc + 1, size=(n, 2)),
             np.ones((n, 1))], 1), jnp.int16)
        return bins, gh

    def test_sibling_subtraction_bit_exact(self):
        """ISSUE 17 acceptance: with integer histograms, parent minus
        left IS the right child — np.array_equal, not allclose."""
        bins, gh = self._codes(4096, 7)
        left = np.zeros(4096, bool)
        left[np.random.default_rng(0).permutation(4096)[:1500]] = True
        hp = np.asarray(H.compute_histogram(bins, gh, 64,
                                            method="segment",
                                            max_code=127))
        hl = np.asarray(H.compute_histogram(
            bins[left], gh[left], 64, method="segment", max_code=127))
        hr = np.asarray(H.compute_histogram(
            bins[~left], gh[~left], 64, method="segment", max_code=127))
        assert np.issubdtype(hp.dtype, np.integer)
        np.testing.assert_array_equal(hp - hl, hr)

    def test_integer_accumulation_parity_across_methods(self):
        """Every build method must produce the IDENTICAL int32 table —
        integer sums have one right answer, reduction order be damned."""
        bins, gh = self._codes(2048, 5)
        ref = np.asarray(H.compute_histogram(bins, gh, 64,
                                             method="segment",
                                             max_code=127))
        methods = ["dot16"]
        if H._native_available():
            methods.append("native")
        for m in methods:
            got = np.asarray(H.compute_histogram(bins, gh, 64, method=m,
                                                 max_code=127))
            np.testing.assert_array_equal(ref, got), m

    def test_packed_accum_gate(self):
        assert H.packed_accum_ok(32768, 127)        # the bench pin
        assert not H.packed_accum_ok(1 << 16, 127)  # row-index width
        assert not H.packed_accum_ok(1 << 15, 300)  # 2*n*mc >= 2^24
        assert not H.packed_accum_ok(1024, 0)       # f32 fit


# -------------------------------------------------- collective pricing


def _dp_cfg(**kw):
    base = dict(num_leaves=31, num_bins=256, axis_name="d",
                data_axis_size=2)
    base.update(kw)
    return G.GrowerConfig(**base)


class TestCollectivePricing:
    """ISSUE 17 satellite: collective_schedule prices slabs at the
    RESOLVED wire itemsize (the old hardcoded ``* 4`` over-billed
    quantized fits), and the priced dtype matches what the psum
    actually carries."""

    def test_int16_slab_is_half_the_f32_bill(self):
        f32 = G.collective_schedule(_dp_cfg(), 50)
        q = G.collective_schedule(
            _dp_cfg(quantized_bits=16, quantized_max_code=10,
                    quantized_wire="int16"), 50)
        assert q["payload_bytes"] * 2 == f32["payload_bytes"]
        assert q["count"] == f32["count"]
        # the grid-scale pmax pair is accounted separately — two scalar
        # latency-bound launches, never slab payload
        assert q["quantized_scale_bytes"] == 8
        assert f32["quantized_scale_bytes"] == 0
        assert q["dense_payload_bytes"] == f32["dense_payload_bytes"]

    def test_int8_slab_is_quarter(self):
        f32 = G.collective_schedule(_dp_cfg(), 50)
        q = G.collective_schedule(
            _dp_cfg(quantized_bits=8, quantized_max_code=127,
                    quantized_wire="int8"), 50)
        assert q["payload_bytes"] * 4 == f32["payload_bytes"]

    def test_ring_always_prices_f32_lanes(self):
        """The ring transport casts to f32 lanes regardless of the
        wire resolution — only the psum count-pair aux rides narrow."""
        q_ring = G.collective_schedule(
            _dp_cfg(collective="ring", quantized_bits=16,
                    quantized_max_code=10, quantized_wire="int16"), 50)
        f32_ring = G.collective_schedule(_dp_cfg(collective="ring"), 50)
        L = 31
        assert q_ring["payload_bytes"] == \
            f32_ring["payload_bytes"] - (L - 1) * 2 * 2

    def test_priced_dtype_is_what_the_psum_carries(self):
        """Pin priced-vs-measured: the schedule bills 2 bytes/elem for
        an int16 wire, and the traced reduction really does cross the
        collective as int16 (and as int32 when the wire stays wide)."""
        def jaxpr_of(wire):
            cfg = _dp_cfg(quantized_bits=16, quantized_max_code=10,
                          quantized_wire=wire)
            fn = jax.vmap(lambda h: G._wire_cast_psum(h, cfg),
                          axis_name="d")
            return str(jax.make_jaxpr(fn)(
                jnp.ones((2, 4, 8, 3), jnp.int32)))
        narrow = jaxpr_of("int16")
        assert "i16" in narrow and "psum" in narrow
        wide = jaxpr_of("int32")
        assert "i16" not in wide and "psum" in wide
        # float slabs (f32 fallback paths) must never be cast
        cfg = _dp_cfg(quantized_wire="int16")
        fl = str(jax.make_jaxpr(jax.vmap(
            lambda h: G._wire_cast_psum(h, cfg), axis_name="d"))(
                jnp.ones((2, 4, 8, 3), jnp.float32)))
        assert "i16" not in fl


# ------------------------------------------------ end-to-end training


@pytest.fixture(scope="module")
def binary_3k():
    from sklearn.datasets import make_classification
    X, y = make_classification(n_samples=3000, n_features=12,
                               n_informative=8, random_state=11)
    return {"features": X.astype(np.float32), "label": y.astype(float)}


class TestQuantizedTraining:
    KW = dict(numIterations=8, numLeaves=15, minDataInLeaf=5,
              verbosity=0, seed=42)

    def test_seeded_sr_is_deterministic(self, binary_3k):
        """Same config + seed → bit-identical forest: the SR noise is
        PRNG-keyed off (seed, round scale), not entropy."""
        a = LightGBMClassifier(**self.KW, quantizedGrad="16").fit(
            binary_3k)
        b = LightGBMClassifier(**self.KW, quantizedGrad="16").fit(
            binary_3k)
        assert _forest(a) == _forest(b)

    def test_serial_quantized_quality(self, binary_3k):
        from sklearn.metrics import roc_auc_score
        m = LightGBMClassifier(**self.KW, quantizedGrad="16").fit(
            binary_3k)
        X, y = binary_3k["features"], binary_3k["label"]
        auc = roc_auc_score(y, m.getModel().predict(X, raw_score=True))
        assert auc > 0.95
        assert eng.last_fit_info["quantized_bits"] == "16"
        assert eng.last_fit_info["quantized_wire"] == "none"  # serial

    def test_distributed_resolution_and_payload(self, binary_3k):
        """D=2 data-parallel q16 at n=3000: the wire policy clamps the
        grid to 10 and the journaled per-tree payload is half dense."""
        from sklearn.metrics import roc_auc_score
        m = LightGBMClassifier(**self.KW, quantizedGrad="16",
                               parallelism="data").setMesh(
            build_mesh(data=2, feature=1,
                       devices=jax.devices()[:2])).fit(binary_3k)
        info = dict(eng.last_fit_info)
        assert info["quantized_wire"] == "int16"
        assert info["quantized_max_code"] == "10"
        assert info["quantized_downgrade"] == "none"
        assert info["quantized_scale_bytes_per_tree"] == "8"
        assert float(info["collective_payload_vs_dense"]) <= 0.51
        X, y = binary_3k["features"], binary_3k["label"]
        auc = roc_auc_score(y, m.getModel().predict(X, raw_score=True))
        assert auc > 0.95

    def test_distributed_deterministic(self, binary_3k):
        mk = lambda: LightGBMClassifier(
            **self.KW, quantizedGrad="16", parallelism="data").setMesh(
            build_mesh(data=2, feature=1,
                       devices=jax.devices()[:2])).fit(binary_3k)
        assert _forest(mk()) == _forest(mk())

    def test_dart_downgrades_with_reason(self, binary_3k):
        m = LightGBMClassifier(**self.KW, quantizedGrad="16",
                               boostingType="dart").fit(binary_3k)
        assert eng.last_fit_info["quantized_bits"] == "0"
        assert eng.last_fit_info["quantized_downgrade"] == \
            "quantized_unsupported"
        assert m.getModel().trees

    def test_exposition_renders_family(self, binary_3k):
        LightGBMClassifier(**self.KW, quantizedGrad="16").fit(binary_3k)
        text = eng._quantized_exposition()
        assert "mmlspark_tpu_train_quantized_info" in text
        assert 'bits="16"' in text and 'wire="none"' in text
        from mmlspark_tpu.core import telemetry as tm
        assert "mmlspark_tpu_train_quantized_info" in \
            tm.get_registry().render_prometheus()

    def test_exposition_empty_before_any_fit(self):
        saved = dict(eng.last_fit_info)
        eng.last_fit_info.clear()
        try:
            assert eng._quantized_exposition() == ""
        finally:
            eng.last_fit_info.update(saved)


# -------------------------------------------- vendored-data parity


def _load_csv_gz(name):
    with gzip.open(os.path.join(DATA_DIR, name), "rt") as fh:
        fh.readline()
        rows = np.asarray([[float(v) for v in line.split(",")]
                           for line in fh])
    return rows


class TestVendoredParity:
    """ISSUE 17 acceptance: quantized-vs-f32 eval deltas ≤ 1e-3
    relative on the REAL vendored tables (the committed
    artifacts/bench_quant_r17.json pins the same configs)."""

    def test_diabetes_l2_parity(self):
        rows = _load_csv_gz("diabetes.csv.gz")
        X, y = rows[:, :-1].astype(np.float32), rows[:, -1]
        idx = np.random.default_rng(8).permutation(len(y))
        tr, te = idx[:310], idx[310:]
        kw = dict(numIterations=120, numLeaves=7, learningRate=0.05,
                  minDataInLeaf=10, verbosity=0, seed=42)
        rmse = {}
        for qg in ("off", "16"):
            m = LightGBMRegressor(**kw, quantizedGrad=qg).fit(
                {"features": X[tr], "label": y[tr]})
            pred = m.getModel().predict(X[te])
            rmse[qg] = float(np.sqrt(np.mean((pred - y[te]) ** 2)))
        delta = abs(rmse["16"] - rmse["off"]) / rmse["off"]
        assert delta <= 1e-3, rmse

    @pytest.mark.slow
    def test_breast_cancer_auc_parity(self):
        from sklearn.metrics import roc_auc_score
        rows = _load_csv_gz("breast_cancer.csv.gz")
        X, y = rows[:, :-1].astype(np.float32), rows[:, -1]
        idx = np.random.default_rng(7).permutation(len(y))
        tr, te = idx[:400], idx[400:]
        kw = dict(numIterations=150, numLeaves=15, learningRate=0.05,
                  minDataInLeaf=10, verbosity=0, seed=42)
        auc = {}
        for qg in ("off", "16"):
            m = LightGBMClassifier(**kw, quantizedGrad=qg).fit(
                {"features": X[tr], "label": y[tr]})
            auc[qg] = roc_auc_score(
                y[te], m.getModel().predict(X[te], raw_score=True))
        delta = abs(auc["16"] - auc["off"]) / auc["off"]
        assert delta <= 1e-3, auc
