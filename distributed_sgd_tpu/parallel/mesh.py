"""Device-mesh helpers: worker axis, dataset sharding, padding.

The reference's cluster topology — N worker processes each owning a
contiguous sample shard (SplitStrategy.scala:13-14) — maps onto a 1-D
``jax.sharding.Mesh`` with a ``workers`` axis: worker i == mesh position i,
its shard == the i-th slice of the batch-dimension-sharded resident
dataset.  Collectives over this axis (psum in parallel/sync.py) replace the
reference's gRPC star topology (Master.scala:179-198).  Multi-host runs use
the same axis over a global mesh (parallel/multihost.py); inside a slice
the collectives ride ICI, across slices DCN.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_sgd_tpu.data.rcv1 import Dataset

WORKER_AXIS = "workers"


shard_map = jax.shard_map


def pcast_varying(x, axes: Tuple[str, ...]):
    """Cast a replicated value to varying over `axes`: shard_map's
    varying-mesh-axes typing requires it before per-device control flow."""
    return jax.lax.pcast(x, axes, to="varying")


def make_mesh(n_workers: Optional[int] = None, devices=None) -> Mesh:
    """A 1-D mesh of `n_workers` devices along the `workers` axis."""
    devices = list(devices if devices is not None else jax.devices())
    if n_workers is None:
        n_workers = len(devices)
    if n_workers > len(devices):
        raise ValueError(f"n_workers={n_workers} > available devices {len(devices)}")
    return Mesh(np.asarray(devices[:n_workers]), (WORKER_AXIS,))


def local_device_groups(devices, n_workers: int, host_devices: int):
    """Deterministic contiguous device groups for hierarchical in-process
    clusters (core/cluster.py DevCluster, benches/bench_hier.py, the
    MULTICHIP dryrun): worker i gets devices [i*D, (i+1)*D), each group
    backing one WorkerNode's in-host mesh (parallel/hier.py).  Raises
    when the available devices cannot host the topology."""
    devices = list(devices)
    need = n_workers * host_devices
    if len(devices) < need:
        raise ValueError(
            f"{n_workers} workers x {host_devices} devices need {need} "
            f"devices, found {len(devices)}")
    return [devices[i * host_devices:(i + 1) * host_devices]
            for i in range(n_workers)]


def pad_to_multiple(data: Dataset, k: int) -> Dataset:
    """Pad with inert rows (all-zero features, label 0) so len % k == 0.

    Label 0 doubles as the validity mask: real labels are +/-1 (or nonzero
    float targets), so evaluation masks on `labels != 0`.
    """
    n = len(data)
    rem = (-n) % k
    if rem == 0:
        return data
    pad_idx = np.zeros((rem, data.indices.shape[1]), dtype=data.indices.dtype)
    pad_val = np.zeros((rem, data.values.shape[1]), dtype=data.values.dtype)
    pad_y = np.zeros((rem,), dtype=data.labels.dtype)
    return Dataset(
        indices=np.concatenate([data.indices, pad_idx]),
        values=np.concatenate([data.values, pad_val]),
        labels=np.concatenate([data.labels, pad_y]),
        n_features=data.n_features,
    )


def shard_dataset(data: Dataset, mesh: Mesh) -> Tuple[jax.Array, jax.Array, jax.Array, int]:
    """Place the packed dataset on the mesh, batch dim sharded over workers.

    Returns (indices, values, labels) as device arrays plus the true
    (pre-padding) sample count.  Worker i's shard is the i-th contiguous
    chunk — the same assignment as the reference's vanilla split.
    """
    n_true = len(data)
    k = mesh.shape[WORKER_AXIS]
    data = pad_to_multiple(data, k)
    sharding = NamedSharding(mesh, P(WORKER_AXIS))
    idx = jax.device_put(data.indices, sharding)
    val = jax.device_put(data.values, sharding)
    y = jax.device_put(data.labels, sharding)
    return idx, val, y, n_true


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
