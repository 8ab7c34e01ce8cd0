"""On-chip fused histogram collectives (ISSUE 10).

Everything here runs the REAL Pallas kernels in interpret mode on the
forced multi-device host platform (tests/conftest.py): remote DMAs
discharge to all_gather exchanges, so the ring schedule's semantics —
chunk rotation, slot reuse, reduction order — are exercised without a
chip.  The bit-parity contract is pinned at D=2 (pairwise float adds
commute, so ring == psum bitwise); larger rings are ulp-rotated and
tested with allclose.  chip_smoke.py runs the same kernels through Mosaic
on the four-chip host; PERF.md (Findings, PR 29) has the one timing of
ring against psum there.
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


_LADDER = [2048, 4096, 8192, 16384]


class TestSegmentHistUnderShardMap:
    """What a mesh fit does at every split, with either build of dot16:
    each shard histograms its own segment through the bucket ladder
    (``grower._segment_hist``) and the partials are reduced
    (``_reduce_hist``).  Held to the ``segment`` histogram of the two
    shards' segments together: counts bit for bit, grad and hess to the
    bf16-operand bound (the kernel rounds them once; the CPU's XLA build
    does not round at all)."""

    def _check(self, build, size, f, B, rng, mesh2, request):
        from mmlspark_tpu.gbdt.grower import (GrowerConfig, _reduce_hist,
                                              _segment_hist)
        from mmlspark_tpu.ops.histogram import (compute_histogram,
                                                histogram_build)
        if build == "dot16/mosaic":
            request.getfixturevalue("mosaic_interpreted")
        d, n = 2, _LADDER[-1] + 600
        cfg = GrowerConfig(num_bins=B, hist_method="dot16",
                           axis_name=DATA_AXIS, data_axis_size=d)
        assert histogram_build("dot16", B, False) == build
        bins = rng.integers(0, B, size=(d, n, f)).astype(np.uint8)
        gh = np.concatenate([rng.normal(size=(d, n, 2)),
                             np.ones((d, n, 1))], axis=2).astype(np.float32)
        # a leaf's segment somewhere inside each shard's permutation, of
        # a length only this rung of the ladder holds; the tail that the
        # bucket reads past it belongs to other leaves or is sentinels
        order = np.stack([np.concatenate([rng.permutation(n),
                                          np.full(_LADDER[-1], n)])
                          for _ in range(d)]).astype(np.int32)
        off = np.asarray([37, 0], np.int32)
        cnt = np.asarray([size, size // 2 + 1], np.int32)

        def shard(b, g, ro, o, c):
            h = _segment_hist(b[0], g[0], ro[0], o[0], c[0], n, _LADDER,
                              cfg)
            return _reduce_hist(h, cfg)[None]

        rows = P(DATA_AXIS)
        got = np.asarray(_smap(shard, mesh2, (rows,) * 5, rows)(
            bins, gh, order, off, cnt))
        np.testing.assert_array_equal(got[0], got[1])     # reduced
        seg = [order[k, off[k]:off[k] + cnt[k]] for k in range(d)]
        seg_bins = np.concatenate([bins[k][seg[k]] for k in range(d)])
        seg_gh = np.concatenate([gh[k][seg[k]] for k in range(d)])
        want = np.asarray(compute_histogram(seg_bins, seg_gh, B,
                                            method="segment"), np.float64)
        mass = np.asarray(compute_histogram(seg_bins, np.abs(seg_gh), B,
                                            method="segment"), np.float64)
        assert got[0].shape == (f, B, 3)
        np.testing.assert_array_equal(got[0][..., 2], want[..., 2])
        assert np.all(np.abs(got[0] - want) <= 2.0 ** -8 * mass + 1e-4)

    @pytest.mark.parametrize("build", ["dot16/xla", "dot16/mosaic"])
    @pytest.mark.parametrize("size", _LADDER)
    def test_bucket_ladder_parity(self, build, size, rng, mesh2, request):
        self._check(build, size, 11, 64, rng, mesh2, request)

    @pytest.mark.parametrize("build", ["dot16/xla", "dot16/mosaic"])
    def test_full_256_bins_and_odd_features(self, build, rng, mesh2,
                                            request):
        """B = 256 (every nibble pair in use) with a feature count that
        leaves the kernel's last fold of 8 short."""
        self._check(build, 2048, 13, 256, rng, mesh2, request)


class TestForestIdentity:
    """End-to-end: collective='ring' forests are BIT-IDENTICAL to their
    psum references on the 2-device mesh."""

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
        # a distinct learning rate keeps the mesh's step table from
        # handing back the identity test's step, traced already
        self._fit("dot16", "ring", mesh2_2axis, learning_rate=0.11)
        assert calls, "collective='ring' never reached the ring kernel"

    def test_resolution_recorded(self, mesh2_2axis):
        from mmlspark_tpu.gbdt.engine import last_fit_info
        self._fit("dot16", "ring", mesh2_2axis)
        assert last_fit_info["collective"] == "ring"
        assert last_fit_info["histogram_method"] == "dot16"
        # ... and the /metrics exposition names the resolved kernel
        from mmlspark_tpu.core import telemetry as tm
        text = tm.get_registry().render_prometheus()
        assert "mmlspark_tpu_train_histogram_method_info" in text
        assert 'histogram_method="dot16"' in text
        assert 'collective="ring"' in text


class TestResolutionAndFallback:
    @pytest.mark.parametrize("method,collective", [("dot16", "ring")])
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
