"""Device-mesh bootstrap — the framework's distributed runtime.

This replaces the reference's entire control-plane rendezvous for distributed
training (SURVEY.md §3.1/§5.8): where the reference's driver opens a socket,
collects ``ip:port`` from every executor, broadcasts a machine list, and the
native engine builds a raw TCP mesh (``LightGBMUtils.getNetworkInitNodes`` /
``TrainUtils.networkInit`` / ``LGBM_NetworkInit``, expected paths, UNVERIFIED),
a TPU-native framework simply:

* calls ``jax.distributed.initialize`` once per host (DCN coordination
  service — the moral equivalent of the driver-socket handshake), and
* lays devices out in a ``jax.sharding.Mesh`` whose axes XLA maps onto
  ICI; collectives (``psum`` for histogram allreduce) are compiler-scheduled.

Mesh axes used throughout the framework:

* ``"data"``  — row/data parallelism (LightGBM ``tree_learner=data`` analog;
  also batch parallelism for inference transformers).
* ``"feature"`` — feature-axis sharding of histograms/split-finding
  (LightGBM ``tree_learner=feature`` analog; the GBDT counterpart of
  sequence/context parallelism — it shards the wide axis, SURVEY.md §5.7).
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"
FEATURE_AXIS = "feature"

_active_mesh: Optional[Mesh] = None


_CLUSTER_ENV_HINTS = (
    "JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
    "MEGASCALE_COORDINATOR_ADDRESS", "TPU_WORKER_ID", "CLOUD_TPU_TASK_ID",
)


def distributed_initialize(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host bootstrap (DCN).

    Replaces the reference's driver-socket rendezvous: the JAX coordination
    service plays the driver role, every host plays an executor.  With
    explicit args it forwards them; with no args it defers to JAX's cluster
    auto-detection whenever the environment looks multi-host, and no-ops on a
    plain single-process machine so local runs need no ceremony.
    """
    explicit = any(a is not None
                   for a in (coordinator_address, num_processes, process_id))
    if explicit:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id)
        return
    if any(os.environ.get(k) for k in _CLUSTER_ENV_HINTS):
        jax.distributed.initialize()


def build_mesh(data: Optional[int] = None, feature: int = 1,
               devices: Optional[Sequence] = None) -> Mesh:
    """Build a ``(data, feature)`` mesh over the available devices.

    ``data`` defaults to ``n_devices // feature``.  With a single device this
    yields a degenerate 1x1 mesh, so the same code path runs everywhere.
    """
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if data is None:
        if n % feature != 0:
            raise ValueError(f"{n} devices not divisible by feature={feature}")
        data = n // feature
    if data * feature != n:
        raise ValueError(
            f"Mesh {data}x{feature} does not cover {n} devices")
    arr = np.asarray(devs).reshape(data, feature)
    return Mesh(arr, (DATA_AXIS, FEATURE_AXIS))


def get_mesh() -> Mesh:
    """The active mesh (set via :func:`use_mesh`), else a fresh default."""
    if _active_mesh is not None:
        return _active_mesh
    return build_mesh()


@contextmanager
def use_mesh(mesh: Mesh):
    global _active_mesh
    prev = _active_mesh
    _active_mesh = mesh
    try:
        yield mesh
    finally:
        _active_mesh = prev


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Rows sharded along the data axis, everything else replicated."""
    return NamedSharding(mesh, PartitionSpec(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def num_workers(mesh: Optional[Mesh] = None) -> int:
    m = mesh or get_mesh()
    return int(m.shape[DATA_AXIS])


def pad_to_multiple(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


def shard_rows(x: np.ndarray, mesh: Mesh, pad_value=0) -> Tuple[np.ndarray, int]:
    """Pad the leading axis to a multiple of the data-axis size.

    Returns (padded array, original length).  The pad rows carry zero weight
    downstream, mirroring how the reference's ``ClusterUtil`` repartitioning
    gives each executor a (ragged) slice — TPU meshes need equal slices.
    """
    k = num_workers(mesh)
    n = x.shape[0]
    m = pad_to_multiple(max(n, k), k)
    if m == n:
        return x, n
    pad_shape = (m - n,) + x.shape[1:]
    pad = np.full(pad_shape, pad_value, dtype=x.dtype)
    return np.concatenate([x, pad], axis=0), n
