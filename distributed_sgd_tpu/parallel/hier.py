"""In-host mesh engine for hierarchical RPC workers (docs/HIERARCHY.md).

The reference scales by running one process per device with gRPC between
all of them (kube/dsgd.yaml's 4-worker StatefulSet): every device costs a
full master->worker weight broadcast, a gRPC reply, and a master-side
decode per round.  Real TPU training stacks run the other shape — one
process per HOST, many devices under it, with collectives inside the host
and RPC only between hosts.  This module is that inner layer for the gRPC
topology (core/worker.py): a `WorkerNode` configured with
``DSGD_HOST_DEVICES=D`` binds its resident data slice to a local D-device
mesh, and each Gradient / local-window dispatch shards the request's
batch over the local devices, reducing in-host with ONE jitted
``lax.psum`` — one RPC reply per host per round instead of D.

The reply contract is byte-for-byte the flat worker's (core/worker.py
``_grad_fn`` / ``_window_fn``): the per-sample backward SUM over the whole
request batch, regularized ONCE (a host is ONE reference worker,
Slave.scala:142-157 — the D devices are an implementation detail the
master never sees).  Per-device partial sums are unregularized and the
regularizer is applied to the psum'd total, so the gradient support mask
(models/linear.py ``regularize``: the dim-sparsity scalar lands only where
grad != 0) matches the flat path's.  Parity with the flat worker is up to
float summation order (asserted in tests/test_hierarchy.py).

Data placement: the host's data slice is REPLICATED over the local mesh
(every device must gather arbitrary rows of the slice — the master draws
uniformly from the host's partition).  Host-local shard loading
(data/host_shard.py) keeps the slice at corpus/n_hosts, so the total
footprint matches the flat topology's one-corpus-copy-per-device while no
host ever materializes the global corpus.

The cross-host plane is untouched: versioned delta broadcasts, top-k /
qint8 compression with error feedback, quorum barriers and hedging, and
the overlapped fan-in all operate on the host's single (summed) reply
exactly as they did on a single-device worker's.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.models.linear import LinearModel
from distributed_sgd_tpu.ops import mxu
from distributed_sgd_tpu.ops.sparse import SparseBatch
from distributed_sgd_tpu.parallel.mesh import WORKER_AXIS, make_mesh, shard_map

AXIS = WORKER_AXIS


class HostMeshEngine:
    """One RPC worker's local device mesh: batch-sharded gradient sums.

    Compiled programs are cached per padded capacity exactly like the flat
    worker's ``_grad_cache`` — each power-of-two batch bucket (rounded up
    to a multiple of the device count) compiles once.
    """

    def __init__(self, model: LinearModel, devices: List, data: Dataset):
        if len(devices) < 2:
            raise ValueError(
                f"a host mesh needs >= 2 devices, got {len(devices)} "
                f"(host_devices=1 is the flat single-device worker)")
        self.model = model
        self.mesh = make_mesh(len(devices), devices=devices)
        self.n_devices = len(devices)
        # the host's data slice, replicated over the local mesh: every
        # device gathers arbitrary rows of the slice (the master draws
        # uniformly from the host's partition), so the rows cannot be
        # sharded without routing each sample id to its owner first
        rep = NamedSharding(self.mesh, P())
        self.idx = jax.device_put(data.indices, rep)
        self.val = jax.device_put(data.values, rep)
        self.y = jax.device_put(data.labels, rep)
        self.n_rows = len(data)
        # blocked MXU kernels pay off on TPU, not CPU — same selection as
        # the flat worker's (core/worker.py), probed on the first device
        self._blocked = (not data.is_dense
                         and mxu.blocked_pays_off(devices[0]))
        self._cache: Dict[Tuple, callable] = {}

    # -- padding -----------------------------------------------------------

    def pad_capacity(self, n: int) -> int:
        """Power-of-two batch bucket, rounded up to a device multiple so
        the shard_map split is exact."""
        d = self.n_devices
        per_dev = 1 if n <= d else 1 << (-(-n // d) - 1).bit_length()
        return d * per_dev

    def pad_ids(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        cap = self.pad_capacity(len(ids))
        padded = np.zeros(cap, dtype=np.int32)
        padded[: len(ids)] = ids
        valid = np.zeros(cap, dtype=np.float32)
        valid[: len(ids)] = 1.0
        return padded, valid

    # -- per-device bodies -------------------------------------------------

    def _partial_grad(self, w, idx, val, y, ids, valid):
        """One device's UNregularized backward sum over its batch shard
        (zeroed rows for pads contribute zero in every model)."""
        rows_i = idx[ids]
        rows_v = val[ids] * valid[:, None]
        batch = SparseBatch(rows_i, rows_v)
        by = y[ids] * valid.astype(y.dtype)
        if self._blocked:
            w2 = mxu.to_blocked(w, self.model.n_features)
            return self.model.grad_blocked(w2, batch, by)
        return self.model.grad_sum(w, batch, by)

    def _reduced_grad(self, w, idx, val, y, ids, valid):
        """psum the partials, regularize ONCE on the host total — the
        support mask (grad != 0) is the full batch's, matching the flat
        worker's reply bit-for-bit up to float summation order."""
        g = self._partial_grad(w, idx, val, y, ids, valid)
        g = jax.lax.psum(g, AXIS)
        if self._blocked:
            w2 = mxu.to_blocked(w, self.model.n_features)
            return mxu.from_blocked(
                self.model.regularize_blocked(g, w2), self.model.n_features)
        return self.model.regularize(g, w)

    def _grad_fn(self, capacity: int):
        key = ("grad", capacity)
        if key not in self._cache:

            def fn(w, idx, val, y, ids, valid):
                return self._reduced_grad(w, idx, val, y, ids, valid)

            # donate the request-scoped weight buffer (same rationale as
            # the flat worker's _grad_fn, ROADMAP item 2)
            self._cache[key] = jax.jit(
                shard_map(
                    fn, mesh=self.mesh,
                    in_specs=(P(), P(), P(), P(), P(AXIS), P(AXIS)),
                    out_specs=P(),
                    check_vma=True,
                ),
                donate_argnums=(0,),
            )
        return self._cache[key]

    def _window_fn(self, steps: int, capacity: int):
        """K-step local-SGD window (core/worker.py _window_fn semantics):
        each step's batch sharded over the local devices, the full-batch
        gradient psum'd in-host, the plain update applied replicated.
        Returns the summed weight-space decrement w_start - w_end."""
        key = ("window", steps, capacity)
        if key not in self._cache:

            def fn(w, idx, val, y, ids, valid, lr):
                def body(w_t, inp):
                    ids_t, valid_t = inp
                    g = self._reduced_grad(w_t, idx, val, y, ids_t, valid_t)
                    return w_t - lr * g, None

                w_end, _ = jax.lax.scan(body, w, (ids, valid))
                return w - w_end

            self._cache[key] = jax.jit(
                shard_map(
                    fn, mesh=self.mesh,
                    in_specs=(P(), P(), P(), P(),
                              P(None, AXIS), P(None, AXIS), P()),
                    out_specs=P(),
                    check_vma=True,
                ),
                donate_argnums=(0,),
            )
        return self._cache[key]

    # -- host API ----------------------------------------------------------

    def grad(self, w: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Sync Gradient reply body: sum of backwards + regularize over the
        whole request batch, one in-host all-reduce."""
        padded, valid = self.pad_ids(ids)
        g = self._grad_fn(len(padded))(
            jnp.asarray(w), self.idx, self.val, self.y,
            jnp.asarray(padded), jnp.asarray(valid),
        )
        return np.asarray(g)

    def local_window(self, w: np.ndarray, ids: np.ndarray, steps: int,
                     batch_size: int, learning_rate: float) -> np.ndarray:
        """K local SGD steps over `ids` split into `batch_size` batches;
        per-step batch padded to a device multiple.  Mirrors the flat
        worker's compute_local_window shapes: (steps, padded batch)
        compiles once."""
        d = self.n_devices
        bs = -(-max(1, int(batch_size)) // d) * d  # device-multiple batch
        n = min(len(ids), steps * batch_size)
        padded = np.zeros((steps, bs), dtype=np.int32)
        valid = np.zeros((steps, bs), dtype=np.float32)
        for t in range(steps):
            row = np.asarray(
                ids[t * batch_size: min(n, (t + 1) * batch_size)],
                dtype=np.int32)
            padded[t, : len(row)] = row
            valid[t, : len(row)] = 1.0
        delta = self._window_fn(steps, bs)(
            jnp.asarray(w), self.idx, self.val, self.y,
            jnp.asarray(padded), jnp.asarray(valid),
            jnp.float32(learning_rate),
        )
        return np.asarray(delta)
