"""On-chip fused histogram collectives (ISSUE 10).

Everything here runs the REAL Pallas kernels in interpret mode on the
forced multi-device host platform (tests/conftest.py): remote DMAs
discharge to all_gather exchanges, so the ring schedule's semantics —
chunk rotation, slot reuse, reduction order — are exercised without a
chip.  The bit-parity contract is pinned at D=2 (pairwise float adds
commute, so ring == psum bitwise); larger rings are ulp-rotated and
tested with allclose.  chip_smoke.py runs the same kernels through Mosaic
on the four-chip host; no ring-vs-psum timing exists yet (ROADMAP S6).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from mmlspark_tpu.core.mesh import DATA_AXIS


def _smap(fn, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _data_mesh(d):
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:d]), (DATA_AXIS,))


class TestRingAllreduce:
    @pytest.mark.parametrize("d", [2, 3, 8])
    def test_matches_psum(self, d, rng):
        """Ring vs psum on the (f, B, 3) histogram state: bit-identical
        at D=2, ulp-rotated at larger rings."""
        from mmlspark_tpu.ops.pallas_collectives import ring_allreduce
        mesh = _data_mesh(d)
        f, B = 11, 64
        x = jax.device_put(
            jnp.asarray(rng.normal(size=(d * f, B, 3)), jnp.float32),
            NamedSharding(mesh, P(DATA_AXIS, None, None)))
        spec = P(DATA_AXIS, None, None)
        got = np.asarray(_smap(
            lambda a: ring_allreduce(a, DATA_AXIS, d, interpret=True),
            mesh, spec, spec)(x))
        want = np.asarray(_smap(
            lambda a: jax.lax.psum(a, DATA_AXIS), mesh, spec, spec)(x))
        if d == 2:
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_ragged_sizes(self, rng, mesh2):
        """Flatten/pad/chunk round-trip: shapes that don't divide 128
        lanes or the device count still reduce exactly."""
        from mmlspark_tpu.ops.pallas_collectives import ring_allreduce
        for shape in ((3,), (7, 5), (1, 129), (13, 17, 3)):
            x = jax.device_put(
                jnp.asarray(rng.normal(size=(2,) + shape), jnp.float32),
                NamedSharding(mesh2, P(*((DATA_AXIS,)
                                         + (None,) * len(shape)))))
            spec = P(*((DATA_AXIS,) + (None,) * len(shape)))
            got = np.asarray(_smap(
                lambda a: ring_allreduce(a, DATA_AXIS, 2, interpret=True),
                mesh2, spec, spec)(x))
            want = np.asarray(_smap(
                lambda a: jax.lax.psum(a, DATA_AXIS),
                mesh2, spec, spec)(x))
            np.testing.assert_array_equal(got, want)

    def test_vmem_gate_raises_and_or_psum_falls_back(self, mesh2):
        from mmlspark_tpu.ops import pallas_collectives as pc
        big = jnp.zeros((2 * 1024, 1200), jnp.float32)  # > 4 MB / shard
        with pytest.raises(ValueError, match="VMEM-residency gate"):
            _smap(lambda a: pc.ring_allreduce(a, DATA_AXIS, 2,
                                              interpret=True),
                  mesh2, P(DATA_AXIS, None), P(DATA_AXIS, None))(
                jax.device_put(big, NamedSharding(
                    mesh2, P(DATA_AXIS, None))))
        # the trace-safe entry silently degrades to psum instead
        out = _smap(lambda a: pc.ring_allreduce_or_psum(a, DATA_AXIS, 2),
                    mesh2, P(DATA_AXIS, None), P(DATA_AXIS, None))(
            jax.device_put(big, NamedSharding(mesh2, P(DATA_AXIS, None))))
        assert np.all(np.asarray(out) == 0.0)


class TestRingAllreduceSelect:
    """The voted-column slab ring (ISSUE 16): gather `hist[cand]` then
    reduce ONLY the `(k2, B, 3)` slab on the same chunked schedule.
    Parity is pinned against gather-then-psum at the pow2 ladder the
    dense ring ships with."""

    @pytest.mark.parametrize("size", [2048, 4096, 8192, 16384])
    def test_bucket_ladder_bit_parity(self, size, rng, mesh2):
        from mmlspark_tpu.ops.pallas_collectives import (
            ring_allreduce_select)
        d, f, B = 2, 64, 64
        k2 = max(2, size // (B * 3 * 4))  # slab elems track the ladder
        hist = jax.device_put(
            jnp.asarray(rng.normal(size=(d * f, B, 3)), jnp.float32),
            NamedSharding(mesh2, P(DATA_AXIS, None, None)))
        cand = jnp.asarray(
            rng.choice(f, size=min(k2, f), replace=False), jnp.int32)
        spec = P(DATA_AXIS, None, None)
        out_spec = P(None, None, None)
        got = np.asarray(_smap(
            lambda h: ring_allreduce_select(h, cand, DATA_AXIS, d,
                                            interpret=True),
            mesh2, spec, out_spec)(hist))
        want = np.asarray(_smap(
            lambda h: jax.lax.psum(jnp.take(h, cand, axis=0), DATA_AXIS),
            mesh2, spec, out_spec)(hist))
        assert got.shape == (cand.shape[0], B, 3)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("d", [3, 8])
    def test_larger_rings_allclose(self, d, rng):
        from mmlspark_tpu.ops.pallas_collectives import (
            ring_allreduce_select)
        mesh = _data_mesh(d)
        f, B, k2 = 31, 16, 10
        hist = jax.device_put(
            jnp.asarray(rng.normal(size=(d * f, B, 3)), jnp.float32),
            NamedSharding(mesh, P(DATA_AXIS, None, None)))
        cand = jnp.asarray(rng.choice(f, size=k2, replace=False),
                           jnp.int32)
        spec = P(DATA_AXIS, None, None)
        out_spec = P(None, None, None)
        got = np.asarray(_smap(
            lambda h: ring_allreduce_select(h, cand, DATA_AXIS, d,
                                            interpret=True),
            mesh, spec, out_spec)(hist))
        want = np.asarray(_smap(
            lambda h: jax.lax.psum(jnp.take(h, cand, axis=0), DATA_AXIS),
            mesh, spec, out_spec)(hist))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_batched_pair_slab(self, rng, mesh2):
        """The batched-frontier layout: stacked (2, f, B, 3) hists with
        per-leaf candidate rows reduce as one collective, bit-identical
        to two separate gather-then-psum calls at D=2."""
        from mmlspark_tpu.ops.pallas_collectives import (
            ring_allreduce_select)
        d, f, B, k2 = 2, 23, 32, 8
        hist = jax.device_put(
            jnp.asarray(rng.normal(size=(d * 2, f, B, 3)), jnp.float32),
            NamedSharding(mesh2, P(DATA_AXIS, None, None, None)))
        cand = jnp.asarray(
            np.stack([rng.choice(f, size=k2, replace=False)
                      for _ in range(2)]), jnp.int32)
        spec = P(DATA_AXIS, None, None, None)
        out_spec = P(None, None, None, None)
        got = np.asarray(_smap(
            lambda h: ring_allreduce_select(h, cand, DATA_AXIS, d,
                                            interpret=True),
            mesh2, spec, out_spec)(hist))
        want = np.asarray(_smap(
            lambda h: jax.lax.psum(
                jnp.take_along_axis(h, cand[:, :, None, None], axis=1),
                DATA_AXIS),
            mesh2, spec, out_spec)(hist))
        assert got.shape == (2, k2, B, 3)
        np.testing.assert_array_equal(got, want)

    def test_vmem_gate_and_or_psum_fallback(self, mesh2):
        from mmlspark_tpu.ops import pallas_collectives as pc
        hist = jnp.zeros((2 * 2048, 256, 3), jnp.float32)
        cand = jnp.arange(1500, dtype=jnp.int32)  # slab > 4 MB
        with pytest.raises(ValueError, match="VMEM-residency gate"):
            _smap(lambda h: pc.ring_allreduce_select(
                      h, cand, DATA_AXIS, 2, interpret=True),
                  mesh2, P(DATA_AXIS, None, None), P(None, None, None))(
                jax.device_put(hist, NamedSharding(
                    mesh2, P(DATA_AXIS, None, None))))
        out = _smap(lambda h: pc.ring_allreduce_select_or_psum(
                        h, cand, DATA_AXIS, 2),
                    mesh2, P(DATA_AXIS, None, None),
                    P(None, None, None))(
            jax.device_put(hist, NamedSharding(
                mesh2, P(DATA_AXIS, None, None))))
        assert out.shape == (1500, 256, 3)
        assert np.all(np.asarray(out) == 0.0)

    def test_serial_is_plain_gather(self, rng):
        from mmlspark_tpu.ops.pallas_collectives import (
            ring_allreduce_select)
        hist = jnp.asarray(rng.normal(size=(9, 8, 3)), jnp.float32)
        cand = jnp.asarray([4, 1, 7], jnp.int32)
        out = ring_allreduce_select(hist, cand, DATA_AXIS, 1,
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(hist)[[4, 1, 7]])


class TestFusedSegmentHistRing:
    """The gather→hist→ring kernel vs the gather→hist→psum reference, at
    the partition grower's real pow2 bucket ladder."""

    @pytest.mark.parametrize("size", [2048, 4096, 8192, 16384])
    def test_bucket_ladder_bit_parity(self, size, rng, mesh2):
        from mmlspark_tpu.ops.pallas_collectives import (
            fused_ring_applicable, fused_segment_hist_ring)
        from mmlspark_tpu.ops.pallas_histogram import histogram_pallas_fused
        d, f, n_local, B = 2, 11, 1500, 64
        assert fused_ring_applicable(f, n_local, B, d)
        binsT = jax.device_put(
            jnp.asarray(rng.integers(0, B, size=(d * f, n_local)),
                        jnp.int32),
            NamedSharding(mesh2, P(DATA_AXIS, None)))
        gh = jax.device_put(
            jnp.asarray(rng.normal(size=(d * size, 3)), jnp.float32),
            NamedSharding(mesh2, P(DATA_AXIS, None)))
        idx = jax.device_put(
            jnp.asarray(rng.integers(0, n_local, size=(d * size,)),
                        jnp.int32),
            NamedSharding(mesh2, P(DATA_AXIS)))
        in_specs = (P(DATA_AXIS, None), P(DATA_AXIS, None), P(DATA_AXIS))
        out_spec = P(DATA_AXIS, None, None)
        got = np.asarray(_smap(
            lambda b, g, i: fused_segment_hist_ring(
                b, g, i, B, size, DATA_AXIS, d, interpret=True),
            mesh2, in_specs, out_spec)(binsT, gh, idx))
        want = np.asarray(_smap(
            lambda b, g, i: jax.lax.psum(
                histogram_pallas_fused(b, g, i, B, size, interpret=True),
                DATA_AXIS),
            mesh2, in_specs, out_spec)(binsT, gh, idx))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.slow
    def test_bucket_65536_bit_parity(self, rng, mesh2):
        """Top of the committed ladder — minutes-scale in interpret
        mode, so it rides the slow marker like the other long tails."""
        self.test_bucket_ladder_bit_parity(65536, rng, mesh2)

    def test_full_256_bins_and_odd_features(self, rng, mesh2):
        """B=256 (full nibble fold) with a feature count that needs both
        the 8-fold and the per-device chunk padding."""
        from mmlspark_tpu.ops.pallas_collectives import (
            fused_segment_hist_ring)
        from mmlspark_tpu.ops.pallas_histogram import histogram_pallas_fused
        d, f, n_local, B, size = 2, 13, 700, 256, 512
        binsT = jax.device_put(
            jnp.asarray(rng.integers(0, B, size=(d * f, n_local)),
                        jnp.int32),
            NamedSharding(mesh2, P(DATA_AXIS, None)))
        gh = jax.device_put(
            jnp.asarray(rng.normal(size=(d * size, 3)), jnp.float32),
            NamedSharding(mesh2, P(DATA_AXIS, None)))
        idx = jax.device_put(
            jnp.asarray(rng.integers(0, n_local, size=(d * size,)),
                        jnp.int32),
            NamedSharding(mesh2, P(DATA_AXIS)))
        in_specs = (P(DATA_AXIS, None), P(DATA_AXIS, None), P(DATA_AXIS))
        out_spec = P(DATA_AXIS, None, None)
        got = np.asarray(_smap(
            lambda b, g, i: fused_segment_hist_ring(
                b, g, i, B, size, DATA_AXIS, d, interpret=True),
            mesh2, in_specs, out_spec)(binsT, gh, idx))
        want = np.asarray(_smap(
            lambda b, g, i: jax.lax.psum(
                histogram_pallas_fused(b, g, i, B, size, interpret=True),
                DATA_AXIS),
            mesh2, in_specs, out_spec)(binsT, gh, idx))
        np.testing.assert_array_equal(got, want)

    def test_vmem_gate_refuses_oversized_binst(self):
        from mmlspark_tpu.ops.pallas_collectives import (
            FUSED_RING_MAX_BINST_BYTES, fused_ring_applicable)
        # boundary: exactly at the gate passes, one row past fails
        d, f = 2, 16          # fp = 16 (already 8*D aligned)
        n_ok = FUSED_RING_MAX_BINST_BYTES // f
        assert fused_ring_applicable(f, n_ok, 64, d)
        assert not fused_ring_applicable(f, n_ok + 1, 64, d)
        # > BMAX bins can never fuse
        assert not fused_ring_applicable(f, 1000, 512, d)
        # serial (single shard) has nothing to ring over
        assert not fused_ring_applicable(f, 1000, 64, 1)


class TestFusedMaxRowsBoundary:
    def test_histogram_pallas_fused_gate(self):
        """The n <= FUSED_MAX_ROWS VMEM gate: at the boundary the kernel
        runs; one row past raises (grower falls back to the bucket
        gather + plain kernel path)."""
        from mmlspark_tpu.ops.pallas_histogram import (
            FB, FUSED_MAX_ROWS, histogram_pallas_fused)
        binsT = jnp.zeros((FB, FUSED_MAX_ROWS), jnp.uint8)
        out = histogram_pallas_fused(
            binsT, jnp.zeros((8, 3), jnp.float32),
            jnp.zeros((8,), jnp.int32), num_bins=16, size=8,
            interpret=True)
        assert out.shape == (FB, 16, 3)
        with pytest.raises(ValueError, match="VMEM-resident"):
            histogram_pallas_fused(
                jnp.zeros((FB, FUSED_MAX_ROWS + 1), jnp.uint8),
                jnp.zeros((8, 3), jnp.float32),
                jnp.zeros((8,), jnp.int32), num_bins=16, size=8,
                interpret=True)


class TestForestIdentity:
    """End-to-end: collective='ring' forests are BIT-IDENTICAL to their
    psum references on the 2-device mesh — the dense ring behind dot16
    and the fully fused pallas_ring kernel both."""

    def _fit(self, method, collective, mesh, **kw):
        from mmlspark_tpu.gbdt import fit_bin_mapper
        from mmlspark_tpu.gbdt.engine import TrainParams, train
        from mmlspark_tpu.gbdt.objectives import get_objective
        rng = np.random.default_rng(7)
        X = rng.normal(size=(640, 9))
        y = (X[:, 0] - X[:, 2] + 0.3 * X[:, 4] > 0).astype(np.float64)
        mapper = fit_bin_mapper(X, max_bin=63)
        bins = mapper.transform_packed(X)
        return train(bins, y, None, mapper, get_objective("binary"),
                     TrainParams(num_iterations=3, num_leaves=7,
                                 min_data_in_leaf=5, max_bin=63,
                                 histogram_method=method,
                                 collective=collective, verbosity=0,
                                 **kw),
                     mesh=mesh)

    @staticmethod
    def _assert_forests_equal(a, b):
        assert len(a.trees) == len(b.trees)
        for s, t in zip(a.trees, b.trees):
            np.testing.assert_array_equal(s.split_feature,
                                          t.split_feature)
            np.testing.assert_array_equal(s.threshold, t.threshold)
            np.testing.assert_array_equal(np.asarray(s.leaf_value),
                                          np.asarray(t.leaf_value))

    def test_dense_ring_forest_identity(self, mesh2_2axis):
        a = self._fit("dot16", "psum", mesh2_2axis)
        b = self._fit("dot16", "ring", mesh2_2axis)
        self._assert_forests_equal(a, b)

    def test_fused_ring_forest_identity(self, mesh2_2axis):
        a = self._fit("pallas_fused", "psum", mesh2_2axis)
        b = self._fit("pallas_ring", "ring", mesh2_2axis)
        self._assert_forests_equal(a, b)

    def test_voting_ring_forest_identity(self, mesh2_2axis):
        """ISSUE 16: voting-over-ring forests are bit-identical to
        voting-over-psum at D=2 — the voted slab rides the select-ring
        and pairwise adds commute."""
        a = self._fit("dot16", "psum", mesh2_2axis,
                      parallelism="voting", top_k=4)
        b = self._fit("dot16", "ring", mesh2_2axis,
                      parallelism="voting", top_k=4)
        self._assert_forests_equal(a, b)

    def test_voting_ring_uses_select_ring(self, mesh2_2axis,
                                          monkeypatch):
        """Guard against the voting fit silently staying on psum: the
        select-ring entry must be traced during a voting ring fit."""
        from mmlspark_tpu.ops import pallas_collectives as pc
        calls = []
        real = pc.ring_allreduce_select_or_psum

        def spy(*a, **k):
            calls.append(1)
            return real(*a, **k)

        monkeypatch.setattr(pc, "ring_allreduce_select_or_psum", spy)
        # a distinct top_k keeps jit from replaying a cached trace
        self._fit("dot16", "ring", mesh2_2axis,
                  parallelism="voting", top_k=5)
        assert calls, ("parallelism='voting' + collective='ring' never "
                       "reached the select-ring")

    def test_ring_actually_rings(self, mesh2_2axis, monkeypatch):
        """Guard against a silent fall-through to psum making the parity
        tests vacuous: count ring_allreduce invocations during a ring
        fit."""
        from mmlspark_tpu.ops import pallas_collectives as pc
        calls = []
        real = pc.ring_allreduce

        def spy(*a, **k):
            calls.append(1)
            return real(*a, **k)

        monkeypatch.setattr(pc, "ring_allreduce", spy)
        self._fit("dot16", "ring", mesh2_2axis)
        assert calls, "collective='ring' never reached the ring kernel"

    def test_resolution_recorded(self, mesh2_2axis):
        from mmlspark_tpu.gbdt.engine import last_fit_info
        self._fit("pallas_ring", "ring", mesh2_2axis)
        assert last_fit_info["collective"] == "ring"
        assert last_fit_info["histogram_method"] == "pallas_ring"
        # ... and the /metrics exposition names the resolved kernel
        from mmlspark_tpu.core import telemetry as tm
        text = tm.get_registry().render_prometheus()
        assert "mmlspark_tpu_train_histogram_method_info" in text
        assert 'histogram_method="pallas_ring"' in text
        assert 'collective="ring"' in text


class TestResolutionAndFallback:
    @pytest.mark.parametrize("method,collective", [
        ("dot16", "ring"), ("pallas_ring", "ring"),
        ("pallas_fused", "psum"), ("pallas", "psum")])
    def test_refused_kernel_raises_not_downgrades(
            self, monkeypatch, mesh2_2axis, method, collective):
        """On TPU an explicitly requested kernel the compiler refuses
        raises the compiler's message out of the fit — it never becomes
        another method or psum.  The CPU backend plays the refusing
        compiler: told it is a TPU, the fit takes the non-interpret
        path, which the CPU lowering rejects."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(Exception, match="[Ii]nterpret"):
            # a distinct leaf budget keeps jit from replaying the
            # interpret-mode traces the parity tests cached
            TestForestIdentity()._fit(method, collective, mesh2_2axis,
                                      lambda_l2=0.125)

    def test_unknown_collective_is_loud(self, mesh2_2axis):
        from mmlspark_tpu.gbdt.engine import (TrainParams,
                                              _resolve_collective_cfg)
        with pytest.raises(ValueError, match="Unknown collective"):
            _resolve_collective_cfg(TrainParams(collective="tree"),
                                    mesh2_2axis)

    def test_ring_flow_control_race_free_d4(self):
        """The barrier + slot-credit protocol Mosaic runs, under the
        threaded TPU interpreter with its race detector: at D=4 the ring
        is not lockstep (the pre-credit kernel raced here), so every
        comm-slot reuse must be ordered by a credit, the semaphores must
        balance across back-to-back launches, and the sum must match
        psum to rotation-order rounding."""
        from jax._src.pallas.mosaic.interpret import (
            interpret_pallas_call as ipc)
        from jax.experimental.pallas import tpu as pltpu

        from mmlspark_tpu.ops.pallas_collectives import ring_allreduce
        d = 4
        mesh = _data_mesh(d)
        spec = P(DATA_AXIS, None, None)
        params = pltpu.InterpretParams(detect_races=True)
        ring = _smap(lambda a: ring_allreduce(a, DATA_AXIS, d,
                                              interpret=params),
                     mesh, spec, spec)
        psum = _smap(lambda a: jax.lax.psum(a, DATA_AXIS), mesh, spec,
                     spec)
        x = jnp.asarray(np.random.default_rng(5).normal(
            size=(d * 50, 256, 3)).astype(np.float32))
        for _ in range(2):
            got = np.asarray(ring(x))
        assert not ipc.races.races_found
        np.testing.assert_allclose(got, np.asarray(psum(x)), rtol=1e-5,
                                   atol=1e-5)

    def test_auto_collective_stays_psum(self, mesh2_2axis):
        from mmlspark_tpu.gbdt.engine import (TrainParams,
                                              _resolve_collective_cfg)
        c, m, why = _resolve_collective_cfg(
            TrainParams(collective="auto"), mesh2_2axis)
        assert c == "psum" and m is mesh2_2axis and why == "none"

    def test_ring_excluded_paths_keep_psum(self, mesh2_2axis):
        """dart / ranking / feature-sharded layouts keep psum (their
        scans bind the 2-axis mesh the ring cannot ride); each records
        the downgrade reason.  Voting fits are no longer pinned — the
        voted-column select-ring rides the same data-only mesh."""
        from mmlspark_tpu.core.mesh import DATA_AXIS, build_mesh
        from mmlspark_tpu.gbdt.engine import (TrainParams,
                                              _resolve_collective_cfg)
        c, m, why = _resolve_collective_cfg(
            TrainParams(collective="ring", boosting="dart"), mesh2_2axis)
        assert c == "psum" and m is mesh2_2axis and why == "dart"
        c, m, why = _resolve_collective_cfg(
            TrainParams(collective="ring"), mesh2_2axis, ranking=True)
        assert c == "psum" and why == "ranking"
        fmesh = build_mesh(data=1, feature=2, devices=jax.devices()[:2])
        c, m, why = _resolve_collective_cfg(
            TrainParams(collective="ring", parallelism="feature"), fmesh)
        assert c == "psum" and why in ("feature_axis", "single_data_shard")
        # voting pin lifted: resolves to ring on a data-only mesh
        c, m, why = _resolve_collective_cfg(
            TrainParams(collective="ring", parallelism="voting"),
            mesh2_2axis)
        assert c == "ring" and why == "none"
        assert tuple(m.axis_names) == (DATA_AXIS,)

    def test_ring_resolution_builds_data_only_mesh(self, mesh2_2axis):
        from mmlspark_tpu.core.mesh import DATA_AXIS, FEATURE_AXIS
        from mmlspark_tpu.gbdt.engine import (TrainParams,
                                              _resolve_collective_cfg)
        c, m, why = _resolve_collective_cfg(
            TrainParams(collective="ring"), mesh2_2axis)
        assert c == "ring" and why == "none"
        assert tuple(m.axis_names) == (DATA_AXIS,)
        assert FEATURE_AXIS not in dict(m.shape)

    def test_downgrade_reason_recorded_and_exposed(self, mesh2_2axis):
        """Satellite: a ring→psum downgrade is a log.info, but the
        reason lands in last_fit_info AND the /metrics exposition."""
        from mmlspark_tpu.gbdt import fit_bin_mapper
        from mmlspark_tpu.gbdt.engine import (TrainParams, last_fit_info,
                                              train)
        from mmlspark_tpu.gbdt.objectives import get_objective
        rng = np.random.default_rng(3)
        X = rng.normal(size=(256, 6))
        y = (X[:, 0] > 0).astype(np.float64)
        mapper = fit_bin_mapper(X, max_bin=31)
        bins = mapper.transform_packed(X)
        train(bins, y, None, mapper, get_objective("binary"),
              TrainParams(num_iterations=2, num_leaves=4,
                          min_data_in_leaf=5, max_bin=31,
                          boosting="dart", collective="ring",
                          verbosity=0),
              mesh=mesh2_2axis)
        assert last_fit_info["collective"] == "psum"
        assert last_fit_info["collective_downgrade"] == "dart"
        from mmlspark_tpu.core import telemetry as tm
        text = tm.get_registry().render_prometheus()
        assert 'collective_downgrade="dart"' in text
        # serial fits record the single-shard reason
        train(bins, y, None, mapper, get_objective("binary"),
              TrainParams(num_iterations=2, num_leaves=4,
                          min_data_in_leaf=5, max_bin=31,
                          collective="ring", verbosity=0))
        assert last_fit_info["collective_downgrade"] == \
            "single_data_shard"
