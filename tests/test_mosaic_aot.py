"""Pallas kernels through Mosaic WITHOUT a chip.

libtpu is installed here, so jax can describe a v5e 2x2 topology and
ahead-of-time compile for it: lowering and Mosaic compilation run in full,
nothing executes.  This is where a kernel author learns for free that the
compiler refuses something; numbers, numerics and hangs still need
chip_smoke.py on the chip.  Skipped where no TPU compiler is available.
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


def test_hist_kernel_compiles_at_the_flagship_root(v5e):
    """The root histogram of the 400 000-row flagship: one grid step
    needs ~19 MB of scoped VMEM, over Mosaic's 16 MB default — the kernel
    must ask for its ceiling (it compiled only below 262 144 rows)."""
    from mmlspark_tpu.ops.pallas_histogram import histogram_pallas
    one = SingleDeviceSharding(v5e[0])
    n = 400_000
    jax.jit(lambda b, g: histogram_pallas(
        b, g, 256, row_chunk=4096, interpret=False)).lower(
        _sds((n, 50), jnp.int32, one), _sds((n, 3), jnp.float32, one)
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


def test_fused_gather_is_refused_with_the_recorded_message(v5e):
    """State (b) in PERF.md: Mosaic has no 1-D dynamic gather, so
    pallas_fused (and pallas_ring, the same gather) raise on TPU."""
    from mmlspark_tpu.ops.pallas_histogram import histogram_pallas_fused
    one = SingleDeviceSharding(v5e[0])
    with pytest.raises(NotImplementedError,
                       match="Only 2D gather is supported"):
        jax.jit(lambda b, g, i: histogram_pallas_fused(
            b, g, i, 256, 2048, interpret=False)).lower(
            _sds((56, 4096), jnp.uint8, one),
            _sds((2048, 3), jnp.float32, one),
            _sds((2048,), jnp.int32, one))
