"""Pallas kernels through Mosaic, and the grower through the TPU's XLA,
WITHOUT a chip.

libtpu is installed here, so jax can describe a v5e 2x2 topology and
ahead-of-time compile for it: lowering and Mosaic compilation run in full,
nothing executes.  This is where a kernel author learns for free that the
compiler refuses something, and where the compiled program's text says
what the chip will do that the CPU's compiler does not (a loop's carry
copied whole); numbers, numerics and hangs still need chip_smoke.py on
the chip.  Skipped where no TPU compiler is available.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from mmlspark_tpu.core.mesh import DATA_AXIS


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 - no libtpu / no TPU compiler
        pytest.skip(f"no TPU compiler to target: {type(e).__name__}: {e}")
    return topo.devices


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_dot16_kernel_compiles_at_the_flagship_root(v5e):
    """The root histogram of chip_smoke's flagship, 400 000 x 50 at 256
    bins: a grid step of 8192 rows holds some 20 MB of operands, over
    Mosaic's 16 MB default, so the kernel has to ask for its ceiling;
    the last fold holds 2 features of 8, the last chunk 6784 rows."""
    from mmlspark_tpu.ops.pallas_histogram import histogram_dot16
    one = SingleDeviceSharding(v5e[0])
    n = 400_000
    jax.jit(lambda b, g: histogram_dot16(b, g, 256, interpret=False)).lower(
        _sds((50, n), jnp.uint8, one), _sds((n, 3), jnp.float32, one)
    ).compile()


def test_ring_kernel_compiles_on_the_2x2(v5e):
    """collective_id, the neighbour barrier and the slot credits as Mosaic
    wants them, on the flagship payload over four devices."""
    from mmlspark_tpu.ops.pallas_collectives import ring_allreduce
    d = len(v5e)
    mesh = Mesh(np.asarray(v5e), (DATA_AXIS,))
    spec = P(DATA_AXIS, None, None)
    fn = jax.shard_map(
        lambda a: ring_allreduce(a, DATA_AXIS, d, interpret=False),
        mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
    jax.jit(fn).lower(_sds((d * 50, 256, 3), jnp.float32,
                           NamedSharding(mesh, spec))).compile()


# ------------------------------------- the split loop's carry, in place

_CACHE_N, _CACHE_F, _CACHE_L, _CACHE_B = 40_000, 200, 255, 256


def _compile_grower(v5e, case, n=_CACHE_N, f=_CACHE_F, num_bins=_CACHE_B):
    """The v5e's compiled program for one way of running the grower at
    40 000 x 200, 255 leaves, dot16 (11-16 s each)."""
    from mmlspark_tpu.gbdt.grower import (GrowerConfig, _grow_tree_impl,
                                          grow_tree)
    one = SingleDeviceSharding(v5e[0])
    base = dict(num_leaves=_CACHE_L, num_bins=num_bins, hist_method="dot16",
                min_data_in_leaf=0, min_sum_hessian_in_leaf=100.0)
    if case in ("serial", "masked", "categorical"):
        cfg = GrowerConfig(compact_rows=(case != "masked"),
                           use_categorical=(case == "categorical"), **base)
        return grow_tree.lower(
            _sds((n, f), jnp.uint8, one), _sds((n, 3), jnp.float32, one),
            _sds((f, 3), jnp.float32, one), cfg).compile()
    if case == "shard_map":
        cfg = GrowerConfig(axis_name=DATA_AXIS, data_axis_size=len(v5e),
                           **base)
        mesh = Mesh(np.asarray(v5e), (DATA_AXIS,))
        rows, rep = P(DATA_AXIS, None), P()
        fn = jax.shard_map(
            lambda b, g, fi: _grow_tree_impl(b, g, fi, cfg)[0],
            mesh=mesh, in_specs=(rows, rows, rep), out_specs=rep,
            check_vma=False)
        return jax.jit(fn).lower(
            _sds((n, f), jnp.uint8, NamedSharding(mesh, rows)),
            _sds((n, 3), jnp.float32, NamedSharding(mesh, rows)),
            _sds((f, 3), jnp.float32, NamedSharding(mesh, rep))).compile()
    assert case == "boost_scan"
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.objectives import BinaryObjective
    obj = BinaryObjective()
    obj.prepare(np.zeros(8), np.ones(8))
    trees = 2
    return engine._boost_scan.lower(
        _sds((n, f), jnp.uint8, one), _sds((n,), jnp.float32, one),
        _sds((n,), jnp.float32, one), _sds((n,), jnp.float32, one),
        _sds((trees, 1), jnp.float32, one),
        _sds((trees, f, 3), jnp.float32, one),
        _sds((1, f), jnp.uint8, one), _sds((1,), jnp.float32, one),
        obj=obj, cfg=GrowerConfig(**base), lr=0.1,
        has_val=False).compile()


@pytest.mark.parametrize("case", ["serial", "shard_map", "masked",
                                  "boost_scan", "categorical"])
def test_split_loop_updates_the_histogram_cache_in_place(v5e, case):
    """A split changes 2 of the cache's 255 rows; the v5e's compiler must
    not copy the other 253.  It did, twice a split, while a read of the
    OLD carry (``leaf_hist[new_id]``) could come after the first update
    of it: 2.46 s of an Epsilon-shaped tree's 10.6 (PERF.md Findings,
    PR 26).  The CPU's compiler copies the carry either way, so only this
    compile can tell."""
    from mmlspark_tpu.core.profiling import compiled_copies
    cache = (_CACHE_L, _CACHE_F, _CACHE_B, 3)
    copies = compiled_copies(_compile_grower(v5e, case))
    assert [c for c in copies if c[1] == cache] == []


# ------------------------------- a long segment, built chunk by chunk

_WALK_N, _WALK_F = 300_000, 39


def test_chunked_build_holds_no_bucket_of_rows(v5e, decides_as_on_the_tpu):
    """The grower at 300 000 x 39, up to 5 chunks of 2^16 rows a node: a
    child over the chunk is histogrammed chunk by chunk, so the program
    holds no rows of a 2^19-row bucket (the rung that held the root's
    child before PR 34: its gathered ``(2^19, 39)`` rows, their
    transpose, its ``(2^19, 3)`` gradients; the partition's 1-D slices
    of that rung stay), and a kernel call site at the root, on each of
    the six rungs and in the loop, where the ladder to 2^19 had ten.
    About a minute to compile."""
    from mmlspark_tpu.core.profiling import compiled_instructions
    from mmlspark_tpu.gbdt import grower
    n, f = _WALK_N, _WALK_F
    assert n > 4 * grower.SEGMENT_CHUNK_ROWS
    compiled = _compile_grower(v5e, "serial", n=n, f=f)
    bucket = 1 << (n - 1).bit_length()
    assert [(name, shape) for name, shape, _ in compiled_instructions(
        compiled, min_bytes=bucket)
        if len(shape) >= 2 and bucket in shape] == []
    assert compiled.as_text().count("tpu_custom_call") == 8


# --------------------------- the histogram build's one-hots, on the chip


@pytest.fixture
def decides_as_on_the_tpu(monkeypatch):
    """``compute_histogram`` picks a build by ``jax.default_backend()``,
    which is the CPU here while the compile targets the v5e: the test
    steers it, and the kernel then goes through Mosaic, not interpret
    mode."""
    import mmlspark_tpu.ops.histogram as H
    monkeypatch.setattr(H.jax, "default_backend", lambda: "tpu")


def _wider_than_the_bins(compiled, f):
    """Instructions that hold ``(rows, F, 16)`` elements or more for some
    2048 rows or more: the one-hots and their products, which XLA's
    formulation of dot16 writes to HBM (``f32[8192,2000,16,3]`` and its
    kin, 840 bytes a cell: PERF.md Findings, PR 28).  The histogram
    cache, ``(leaves, F, bins, 3)``, has 255 rows."""
    import math
    from mmlspark_tpu.core.profiling import compiled_instructions
    return [(name, shape) for name, shape, _ in compiled_instructions(
        compiled, min_bytes=2048 * f * 16)
        if len(shape) >= 3 and shape[0] >= 2048 and shape[1] == f
        and math.prod(shape[2:]) >= 16]


@pytest.mark.parametrize("n,f", [(32768, 2000), (32768, 968), (65536, 39)])
def test_dot16_build_keeps_its_one_hots_on_the_chip(v5e,
                                                    decides_as_on_the_tpu,
                                                    n, f):
    """``compute_histogram(method="dot16")`` at a bucket of each wide cell
    and of the click log: a Mosaic call on the uint8 bins, no array wider
    than they are, and temporaries under the table's own size (XLA's
    formulation: 4.80 GB at the first shape)."""
    from mmlspark_tpu.ops.histogram import compute_histogram
    one = SingleDeviceSharding(v5e[0])
    compiled = jax.jit(
        lambda b, g: compute_histogram(b, g, 255, method="dot16")).lower(
        _sds((n, f), jnp.uint8, one), _sds((n, 3), jnp.float32, one)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _wider_than_the_bins(compiled, f) == []
    assert compiled.memory_analysis().temp_size_in_bytes < n * f


def test_boost_scan_at_epsilons_shape_holds_no_one_hot(
        v5e, decides_as_on_the_tpu):
    """The whole fit's program at 400 000 x 2000, 255 leaves and bins: a
    kernel at the root, in each of the six bucket rungs and in the chunk
    loop, no ``(rows, F, 16)`` array anywhere, and 2.9 GB of temporaries
    (the cache, the row-major table and a 2^16-row chunk twice) where
    the ladder to 2^19 rows held 6.1 and XLA's formulation 9.9 (about a
    minute to compile)."""
    compiled = _compile_grower(v5e, "boost_scan", n=400_000, f=2000,
                               num_bins=255)
    assert compiled.as_text().count("tpu_custom_call") == 8
    assert _wider_than_the_bins(compiled, 2000) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 3.5e9


def test_bundled_boost_scan_keeps_one_cache_and_no_feature_wide_rows(
        v5e, decides_as_on_the_tpu):
    """The fit's program on a table bundled at binning time, at
    ``allstate_fit``'s 90 bundle columns and 4228 features (65 536 rows:
    what is asked scales with columns, not rows): the per-leaf cache
    stays in FEATURE space, 255 x 4228 x 256 x 3 floats (3.31 GB), and
    the program must hold it once (no whole copy, temporaries under two
    of it) and nothing of ``(rows, 4228)``: rows exist only 90 wide.
    About a minute to compile."""
    from mmlspark_tpu.core.profiling import (compiled_copies,
                                             compiled_instructions)
    from mmlspark_tpu.gbdt import engine
    from mmlspark_tpu.gbdt.grower import EFBArrays, GrowerConfig
    from mmlspark_tpu.gbdt.objectives import BinaryObjective
    n, G, f, B, L = 65_536, 90, 4228, 256, 255
    one = SingleDeviceSharding(v5e[0])
    obj = BinaryObjective()
    obj.prepare(np.zeros(8), np.ones(8))
    efb = EFBArrays(
        gather_idx=_sds((f, B), jnp.int32, one),
        valid=_sds((f, B), jnp.bool_, one),
        **{k: _sds((f,), jnp.int32, one)
           for k in ("bundle_of", "off_of", "nb_of", "default_of")})
    compiled = engine._boost_scan.lower(
        _sds((n, G), jnp.uint8, one), _sds((n,), jnp.float32, one),
        _sds((n,), jnp.float32, one), _sds((n,), jnp.float32, one),
        _sds((2, 1), jnp.float32, one), _sds((2, f, 3), jnp.float32, one),
        _sds((1, f), jnp.uint8, one), _sds((1,), jnp.float32, one),
        obj=obj, cfg=GrowerConfig(
            num_leaves=L, num_bins=B, hist_method="dot16",
            min_data_in_leaf=0, min_sum_hessian_in_leaf=100.0),
        lr=0.1, has_val=False, efb=efb).compile()
    cache = (L, f, B, 3)
    assert compiled.as_text().count("tpu_custom_call") >= 6
    assert [c for c in compiled_copies(compiled) if c[1] == cache] == []
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2 * L * f * B * 3 * 4
    wide = [(name, shape) for name, shape, _ in compiled_instructions(
        compiled, min_bytes=2048 * f)
        if len(shape) >= 2 and f in shape[1:]
        and max(d for d in shape if d != f) >= 2048 and shape != cache
        and shape[0] >= 2048]
    assert wide == []


# ------------------- the reference profile's passes over the fit's table


@pytest.mark.parametrize("n,f,chips", [(400_000, 2000, 1),
                                       (30_000_000, 39, 1),
                                       (400_000, 2000, 4)])
def test_count_pass_holds_nothing_wider_than_the_table(
        v5e, decides_as_on_the_tpu, n, f, chips):
    """``engine._table_bin_counts`` at the wide cell's shape, at the click
    log's (four row chunks: a bin may pass 2^24 rows) and row-sharded on
    the 2x2: the histogram kernel on the uint8 bins, no ``(rows, F,
    bins)`` array, temporaries under 0.5 GB (the scan's are 6.1), and on
    the mesh one all-reduce, of the counts."""
    from mmlspark_tpu.core.mesh import FEATURE_AXIS
    from mmlspark_tpu.gbdt.engine import _table_bin_counts
    mesh, sharding = None, SingleDeviceSharding(v5e[0])
    if chips > 1:
        mesh = Mesh(np.asarray(v5e).reshape(chips, 1),
                    (DATA_AXIS, FEATURE_AXIS))
        sharding = NamedSharding(mesh, P(DATA_AXIS, FEATURE_AXIS))
    compiled = _table_bin_counts.lower(
        _sds((n, f), jnp.uint8, sharding), 256, mesh).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _wider_than_the_bins(compiled, f) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    from mmlspark_tpu.core.profiling import compiled_instructions
    reduced = compiled_instructions(compiled, opcodes=("all-reduce",))
    assert [shape for _, shape, _ in reduced] == \
        ([(f, 256)] if chips > 1 else [])


def test_representative_rows_needs_no_temporary(v5e):
    """The sampled rows' lookup at the wide cell's shape: compare, select
    and sum fused, worked in the layout the TPU keeps both tables in, so
    nothing but the ``(32768, 2000)`` float32 result is made (row-major
    it took a transposed copy of the result, 268 MB)."""
    from mmlspark_tpu.gbdt.engine import _representative_rows
    one = SingleDeviceSharding(v5e[0])
    compiled = _representative_rows.lower(
        _sds((32768, 2000), jnp.uint8, one),
        _sds((2000, 256), jnp.float32, one)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
