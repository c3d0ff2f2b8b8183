"""Hierarchical multi-host training (docs/HIERARCHY.md).

Covers the in-host mesh engine's parity with the flat worker kernels,
the end-to-end hierarchical RPC topology on the 8-virtual-device test
mesh, the host-granular weighted split, host-local id mapping, and the
knobs-off identity discipline.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sgd_tpu.core.cluster import DevCluster
from distributed_sgd_tpu.core.split import vanilla_split, weighted_split
from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import SparseSVM
from distributed_sgd_tpu.ops.sparse import SparseBatch
from distributed_sgd_tpu.parallel.hier import HostMeshEngine
from distributed_sgd_tpu.parallel.mesh import local_device_groups
from distributed_sgd_tpu.rpc import dsgd_pb2 as pb

DIM = 256
N = 200


@pytest.fixture(scope="module")
def data():
    return rcv1_like(N, n_features=DIM, nnz=6, seed=0, idf_values=True)


@pytest.fixture(scope="module")
def model():
    ds = np.full(DIM, 0.01, np.float32)
    return SparseSVM(lam=1e-4, n_features=DIM, dim_sparsity=jnp.asarray(ds))


def _flat_grad(model, data, w, ids):
    """The flat worker's _grad_fn body, verbatim (core/worker.py)."""
    cap = 1 << max(0, (len(ids) - 1).bit_length())
    p = np.zeros(cap, np.int32)
    p[: len(ids)] = ids
    v = np.zeros(cap, np.float32)
    v[: len(ids)] = 1.0
    idx, val, y = (jnp.asarray(data.indices), jnp.asarray(data.values),
                   jnp.asarray(data.labels))
    pj, vj = jnp.asarray(p), jnp.asarray(v)
    rows_i, rows_v = idx[pj], val[pj] * vj[:, None]
    by = y[pj] * vj.astype(y.dtype)
    return np.asarray(model.grad_regularized(
        jnp.asarray(w), SparseBatch(rows_i, rows_v), by))


# -- in-host mesh engine ------------------------------------------------------


@pytest.mark.parametrize("n_devices", [2, 3, 4])
def test_host_engine_gradient_matches_flat_worker(data, model, n_devices):
    """The hierarchical reply must be the flat worker's reply (sum over
    the whole batch + regularize ONCE) up to float summation order —
    including non-power-of-two device groups and odd batch sizes."""
    eng = HostMeshEngine(model, jax.devices()[:n_devices], data)
    rng = np.random.default_rng(1)
    w = rng.normal(size=DIM).astype(np.float32)
    for size in (1, 7, 37):
        ids = rng.choice(N, size=size, replace=False)
        g_flat = _flat_grad(model, data, w, ids)
        g_hier = eng.grad(w.copy(), ids)
        np.testing.assert_allclose(g_hier, g_flat, rtol=1e-5, atol=1e-6)
        if size > 1:  # one hinge sample can legitimately have zero grad
            assert np.any(g_hier != 0.0)


def test_host_engine_window_matches_flat_worker(data, model):
    """K-step local-SGD window parity: same summed decrement as the flat
    worker's lax.scan (short tail batch included)."""
    eng = HostMeshEngine(model, jax.devices()[:2], data)
    rng = np.random.default_rng(2)
    w = rng.normal(size=DIM).astype(np.float32)
    k, bs, lr = 3, 8, 0.3
    ids = rng.choice(N, size=k * bs - 5, replace=False)

    idx, val, y = (jnp.asarray(data.indices), jnp.asarray(data.values),
                   jnp.asarray(data.labels))
    steps = -(-len(ids) // bs)
    p = np.zeros(steps * bs, np.int32)
    p[: len(ids)] = ids
    v = np.zeros(steps * bs, np.float32)
    v[: len(ids)] = 1.0

    def body(w_t, inp):
        ids_t, valid_t = inp
        rows_i, rows_v = idx[ids_t], val[ids_t] * valid_t[:, None]
        by = y[ids_t] * valid_t.astype(y.dtype)
        g = model.grad_regularized(w_t, SparseBatch(rows_i, rows_v), by)
        return w_t - lr * g, None

    w0 = jnp.asarray(w)
    w_end, _ = jax.lax.scan(
        body, w0, (jnp.asarray(p.reshape(steps, bs)),
                   jnp.asarray(v.reshape(steps, bs))))
    want = np.asarray(w0 - w_end)
    got = eng.local_window(w.copy(), ids, steps, bs, lr)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_host_engine_rejects_single_device(data, model):
    with pytest.raises(ValueError, match=">= 2 devices"):
        HostMeshEngine(model, jax.devices()[:1], data)


def test_local_device_groups():
    devs = list(range(8))
    assert local_device_groups(devs, 4, 2) == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert local_device_groups(devs, 2, 4) == [[0, 1, 2, 3], [4, 5, 6, 7]]
    with pytest.raises(ValueError, match="need 16 devices"):
        local_device_groups(devs, 4, 4)


# -- host-granular weighted split ---------------------------------------------


def test_weighted_split_proportional_and_exact():
    parts = weighted_split(100, [2, 1, 1])
    assert [len(p) for p in parts] == [50, 25, 25]
    # contiguous, disjoint, covering
    assert np.array_equal(np.concatenate(parts), np.arange(100))
    # largest-remainder rounding sums exactly and deterministically
    parts = weighted_split(10, [3, 3, 1])
    assert sum(len(p) for p in parts) == 10
    # exact shares [30/7, 30/7, 10/7]: floors [4, 4, 1], the one
    # leftover row goes to the largest remainder (index 2, .43)
    assert [len(p) for p in parts] == [4, 4, 2]
    again = weighted_split(10, [3, 3, 1])
    assert all(np.array_equal(a, b) for a, b in zip(parts, again))
    with pytest.raises(ValueError):
        weighted_split(10, [])
    with pytest.raises(ValueError):
        weighted_split(10, [2, 0])


def test_master_split_weights_heterogeneous_hosts(data, model):
    """A master whose workers registered different device counts weights
    the DEFAULT split by them; equal shapes (or any custom split fn)
    delegate untouched."""
    from distributed_sgd_tpu.core.split import strided_split

    with DevCluster(model, data, data, n_workers=2) as c:
        m = c.master
        members = m._members()
        keys = [k for k, _ in members]
        # flat registration: no shapes recorded, vanilla delegation
        assert not m._worker_devices
        got = m._split_parts(vanilla_split, members)
        want = vanilla_split(N, 2)
        assert all(np.array_equal(a, b) for a, b in zip(got, want))
        # heterogeneous shapes: weighted
        m._worker_devices[keys[0]] = 3
        m._worker_devices[keys[1]] = 1
        got = m._split_parts(vanilla_split, members)
        assert [len(p) for p in got] == [150, 50]
        # equal shapes: proportional == even, delegate to vanilla exactly
        m._worker_devices[keys[1]] = 3
        got = m._split_parts(vanilla_split, members)
        assert all(np.array_equal(a, b) for a, b in zip(got, want))
        # custom split fns are never re-weighted
        m._worker_devices[keys[1]] = 1
        got = m._split_parts(strided_split, members)
        want_s = strided_split(N, 2)
        assert all(np.array_equal(a, b) for a, b in zip(got, want_s))


# -- knobs-off identity -------------------------------------------------------


def test_knobs_off_worker_is_flat_and_wire_is_unchanged(data, model):
    """Default host_devices=1: no in-host mesh, no data offset, and the
    registration Node serializes byte-identically to the pre-hierarchy
    wire (proto3 leaves the unset devices field off the wire)."""
    with DevCluster(model, data, data, n_workers=2) as c:
        assert all(w._hier is None for w in c.workers)
        assert all(w._data_offset is None for w in c.workers)
        assert all(w.host_devices == 1 for w in c.workers)
        assert not c.master._worker_devices
    n = pb.Node(host="h", port=4001)
    assert n.devices == 0
    assert b"devices" not in n.SerializeToString()
    # a two-field Node round-trips through an old-style parse unchanged
    assert len(n.SerializeToString()) == len(
        pb.Node(host="h", port=4001).SerializeToString())


# -- end-to-end hierarchical topology -----------------------------------------


def test_hierarchical_cluster_end_to_end(data, model):
    """2 hosts x 2 devices with host-local slices: the fit converges in
    parity with the flat topology at equal global batch (lr scaled by
    H/W, docs/HIERARCHY.md), predict spans the host-local slices, and the
    master knows the host shapes."""
    with DevCluster(model, data, data, n_workers=4) as c:
        flat = c.master.fit_sync(max_epochs=3, batch_size=10,
                                 learning_rate=0.5)
    with DevCluster(model, data, data, n_workers=2, host_devices=2,
                    host_local=True) as c:
        assert all(w._hier is not None for w in c.workers)
        assert all(w._data_offset is not None for w in c.workers)
        # workers hold ONLY their slice
        assert all(w._n == 100 for w in c.workers)
        assert dict(c.master._worker_devices.items()) == {
            k: 2 for k in c.master._worker_devices}
        hier = c.master.fit_sync(max_epochs=3, batch_size=20,
                                 learning_rate=0.25)
        w_h = np.asarray(hier.state.weights)
        preds = c.master.predict(w_h)
        assert preds.shape == (N,)
        # distributed eval over host-local slices agrees with the
        # master-local eval of the same weights
        acc_dist = float((preds == data.labels).mean())
        _, acc_local = c.master.local_loss(w_h)
        assert acc_dist == pytest.approx(acc_local, abs=1e-6)
    assert hier.losses[-1] <= max(1.02 * flat.losses[-1],
                                  flat.losses[-1] + 0.02)


def test_hierarchical_local_steps_window(data, model):
    """DSGD_LOCAL_STEPS rides the hierarchical host unchanged: a K=2
    window fit completes and converges finitely on a 2x2 cluster."""
    with DevCluster(model, data, data, n_workers=2, host_devices=2) as c:
        res = c.master.fit_sync(max_epochs=2, batch_size=10,
                                learning_rate=0.25, local_steps=2)
        assert np.isfinite(res.losses[-1])
        assert np.any(np.asarray(res.state.weights) != 0.0)


def test_host_local_worker_rejects_foreign_ids(data, model):
    """A host-local worker must refuse sample ids outside its slice —
    computing a gradient over wrong rows would silently corrupt the
    fit; the error surfaces as a classified RPC failure instead."""
    from distributed_sgd_tpu.core.worker import WorkerNode

    w = WorkerNode("127.0.0.1", 0, "127.0.0.1", 1,
                   data.slice(slice(100, 200)), model,
                   data_offset=100)
    try:
        ids = np.arange(100, 120)
        g = w.compute_gradient(np.zeros(DIM, np.float32), ids)
        assert np.any(g != 0.0)
        with pytest.raises(ValueError, match="outside this host's"):
            w.compute_gradient(np.zeros(DIM, np.float32), np.arange(90, 120))
        with pytest.raises(ValueError, match="outside this host's"):
            w.compute_gradient(np.zeros(DIM, np.float32),
                               np.asarray([205]))
    finally:
        w.stop()
