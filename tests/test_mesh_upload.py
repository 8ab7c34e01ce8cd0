"""A host table reaches a mesh block by block.

``distributed.prepare_arrays`` hands each device its own rows straight
from host memory, in the layout the staged put gave (the rows in order,
pad rows at the end with zero weight, pad features bin 0), and copies on
the host only a block that holds padding.  The staged put it replaced
(the whole padded table and its row vectors on device 0, then resharded)
is kept here as the reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mmlspark_tpu.core.mesh import DATA_AXIS, FEATURE_AXIS, pad_to_multiple
from mmlspark_tpu.core.profiler import get_profiler
from mmlspark_tpu.gbdt import distributed, engine


def _mesh(data, feature):
    return Mesh(np.asarray(jax.devices()[:data * feature])
                .reshape(data, feature), (DATA_AXIS, FEATURE_AXIS))


def _staged(bins, labels, weights, mesh, num_class, init, init_scores):
    """The staged put: pad on the host, then every array whole on the
    default device and resharded over the mesh."""
    n, f = bins.shape
    rp = pad_to_multiple(n, int(mesh.shape[DATA_AXIS])) - n
    fp = pad_to_multiple(f, int(mesh.shape[FEATURE_AXIS])) - f
    bins = np.pad(bins, ((0, rp), (0, fp)))
    labels = np.concatenate([labels, np.zeros(rp, labels.dtype)])
    weights = np.concatenate([weights, np.zeros(rp, weights.dtype)])
    real = np.concatenate([np.ones(n, np.float32), np.zeros(rp, np.float32)])
    scores = np.full((n + rp,) if num_class == 1 else (n + rp, num_class),
                     init, np.float32)
    if init_scores is not None:
        extra = np.concatenate([np.asarray(init_scores, np.float32),
                                np.zeros((rp,) + init_scores.shape[1:],
                                         np.float32)])
        scores = scores + (extra if extra.ndim == scores.ndim
                           else extra[:, None])
    rows = NamedSharding(mesh, P(DATA_AXIS))
    out = (jax.device_put(jnp.asarray(bins),
                          NamedSharding(mesh, P(DATA_AXIS, FEATURE_AXIS))),
           jax.device_put(jnp.asarray(
               labels, jnp.int32 if num_class > 1 else jnp.float32), rows),
           jax.device_put(jnp.asarray(weights, jnp.float32), rows),
           jax.device_put(jnp.asarray(real), rows),
           jax.device_put(jnp.asarray(scores), NamedSharding(
               mesh, P(DATA_AXIS, None) if num_class > 1 else P(DATA_AXIS))))
    return out + (rp, fp)


def _padded_block_bytes(arrays, n, f):
    """Bytes of the per-device blocks of bins, labels and weights that
    reach past the real rows or features: what the host has to build."""
    total = 0
    for a, width in zip(arrays[:3], (f, None, None)):
        for dev, index in a.sharding.addressable_devices_indices_map(
                a.shape).items():
            r1 = index[0].indices(a.shape[0])[1]
            c1 = index[1].indices(a.shape[1])[1] if width else 0
            if r1 > n or (width and c1 > width):
                total += a.addressable_data(0).nbytes
    return total


#: (data, feature, rows, features, classes, init scores)
CASES = {
    "data4": (4, 1, 400, 6, 1, False),
    "data4_ragged": (4, 1, 403, 6, 1, True),
    "data8_ragged": (8, 1, 1001, 5, 1, False),
    "data8_fewer_rows_than_devices": (8, 1, 3, 4, 1, False),
    "data_feature_padded": (2, 2, 402, 7, 1, False),
    "data_feature_ragged": (4, 2, 1001, 9, 1, True),
    "feature_padded": (1, 4, 50, 7, 1, False),
    "multiclass": (4, 1, 400, 6, 3, False),
    "multiclass_ragged_init": (4, 1, 402, 6, 3, True),
}


@pytest.mark.parametrize("case", CASES)
def test_prepare_arrays_puts_the_staged_layout_block_by_block(
        case, monkeypatch):
    data, feature, n, f, K, with_init = CASES[case]
    mesh = _mesh(data, feature)
    rng = np.random.default_rng(n * 10 + f)
    bins = rng.integers(0, 256, (n, f), dtype=np.uint8)
    labels = rng.integers(0, max(K, 2), n).astype(np.float64)
    weights = rng.random(n).astype(np.float32)
    init = 0.25 if K == 1 else np.linspace(-0.5, 0.5, K).astype(np.float32)
    init_scores = (rng.normal(size=(n,) if K == 1 else (n, K))
                   if with_init else None)
    want = _staged(bins, labels, weights, mesh, K, init, init_scores)

    pieces = []
    prof = get_profiler()
    before = {s["id"] for s in prof.spans()}
    uploads0 = engine.train_stats.snapshot()["counters"][
        "mesh_uploads_direct"]
    # nothing goes through a whole array on the default device
    with monkeypatch.context() as m:
        m.setattr(jnp, "asarray", None)
        got = distributed.prepare_arrays(
            bins, labels, weights, mesh, K, init, init_scores,
            _piece_spy=pieces.append)

    assert got[5:] == want[5:]
    assert all(type(v) is int for v in got[5:])
    for a, b in zip(got[:5], want[:5]):
        assert (a.shape, a.dtype, a.sharding) == (b.shape, b.dtype,
                                                  b.sharding)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # no piece holds more than one device's block
    block_rows = got[0].shape[0] // data
    block_cols = got[0].shape[1] // feature
    assert len(pieces) >= 5
    for shape in pieces:
        assert shape[0] == block_rows, shape
        assert len(shape) == 1 or shape[1] in (block_cols, K), shape
    upload, = [s for s in prof.spans() if s["id"] not in before
               and s["name"] == "train.upload"]
    assert upload["attrs"]["pieces"] == 5 * data * feature
    assert upload["attrs"]["host_copy_bytes"] == \
        _padded_block_bytes(got, n, f)
    if n % data == 0 and f % feature == 0:
        assert upload["attrs"]["host_copy_bytes"] == 0
    else:
        assert upload["attrs"]["host_copy_bytes"] > 0
    assert upload["attrs"]["bytes"] == sum(a.nbytes for a in got[:5])
    assert engine.train_stats.snapshot()["counters"][
        "mesh_uploads_direct"] == uploads0 + 1


def test_an_unpadded_block_is_the_callers_rows():
    """Where a block needs no padding the transfer reads the caller's
    own rows: the piece handed over is a view, not a copy."""
    mesh = _mesh(4, 1)
    bins = np.arange(400 * 6, dtype=np.uint8).reshape(400, 6)
    labels = np.zeros(400, np.float32)
    weights = np.ones(400, np.float32)
    seen = []
    real_callback = jax.make_array_from_callback

    def spy(shape, sharding, callback, *a, **kw):
        def looked(index):
            block = callback(index)
            seen.append(block)
            return block
        return real_callback(shape, sharding, looked, *a, **kw)

    from unittest import mock
    with mock.patch.object(jax, "make_array_from_callback", spy):
        distributed.prepare_arrays(bins, labels, weights, mesh, 1, 0.0)
    shared = [b for b in seen if np.shares_memory(b, bins)
              or np.shares_memory(b, labels) or np.shares_memory(b, weights)]
    assert len(shared) == 12    # bins, labels, weights on each of 4 devices


def test_a_mesh_fit_from_a_host_table_uploads_directly():
    """``engine.train`` on a mesh puts the table through the direct path:
    its ``train.upload`` reads 5 pieces a device and no host copy where
    the rows split evenly, and the forest is the staged put's."""
    from mmlspark_tpu.gbdt import fit_bin_mapper
    from mmlspark_tpu.gbdt.objectives import get_objective
    rng = np.random.default_rng(3)
    X = rng.normal(size=(800, 5)).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.normal(size=800) > 0).astype(np.float64)
    mapper = fit_bin_mapper(X, max_bin=63)
    bins = mapper.transform_packed(X)
    params = engine.TrainParams(num_iterations=3, num_leaves=7,
                                min_data_in_leaf=5, max_bin=63, verbosity=0)
    prof = get_profiler()
    before = {s["id"] for s in prof.spans()}
    booster = engine.train(bins, y, None, mapper, get_objective("binary"),
                           params, mesh=_mesh(4, 1))
    upload, = [s for s in prof.spans() if s["id"] not in before
               and s["name"] == "train.upload"]
    assert upload["attrs"]["pieces"] == 20
    assert upload["attrs"]["host_copy_bytes"] == 0

    def staged_put(bins, labels, weights, mesh, num_class, init,
                   init_scores=None, _span=None):
        return _staged(bins, labels, weights, mesh, num_class, init,
                       init_scores)

    from unittest import mock
    with mock.patch.object(distributed, "prepare_arrays",
                           distributed._upload(staged_put)):
        reference = engine.train(bins, y, None, mapper,
                                 get_objective("binary"), params,
                                 mesh=_mesh(4, 1))
    assert booster.save_native_model_string() == \
        reference.save_native_model_string()
