"""Dense fast path: plain-matmul kernels for dense-layout datasets.

VERDICT round-1 item 4: dense rows (BASELINE.md config 5) must not run
through the sparse gather/scatter kernels with materialized arange indices.
`Dataset.dense` carries values[N, D] only; engines route it to
`LinearModel.margins_dense` / `grad_dense` (one [B, D] matmul each).

Parity oracle: the SAME rows expressed in the sparse layout (indices =
arange(D)) through the existing, already-oracle-tested kernels.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.data.synthetic import dense_regression
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.ops.sparse import SparseBatch
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine


def _pair(n=32, d=16, seed=0, labels="cls"):
    """The same data in dense and sparse layouts."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    if labels == "cls":
        y = np.where(rng.random(n) < 0.5, 1, -1).astype(np.int32)
    else:
        y = rng.normal(size=n).astype(np.float32)
    dense = Dataset.dense(x, y)
    idx = np.broadcast_to(np.arange(d, dtype=np.int32), (n, d)).copy()
    sparse = Dataset(indices=idx, values=x.copy(), labels=y, n_features=d)
    return dense, sparse


def test_dense_layout_properties():
    dense, sparse = _pair()
    assert dense.is_dense and not sparse.is_dense
    assert len(dense) == len(sparse)
    assert dense.pad_width == dense.n_features
    assert dense.indices.shape == (32, 0)
    sl = dense.slice(slice(0, 8))
    assert sl.is_dense and len(sl) == 8


@pytest.mark.parametrize("model_name,labels", [
    ("hinge", "cls"), ("logistic", "cls"), ("least_squares", "reg"),
])
def test_dense_model_math_matches_sparse(model_name, labels):
    dense, sparse = _pair(labels=labels)
    reg = "l2"
    model = make_model(model_name, 1e-3, dense.n_features, regularizer=reg)
    w = jnp.asarray(np.random.default_rng(1).normal(size=dense.n_features),
                    jnp.float32)
    y = jnp.asarray(dense.labels)

    sb = SparseBatch(jnp.asarray(sparse.indices), jnp.asarray(sparse.values))
    m_sparse = model.margins(w, sb)
    m_dense = model.margins_dense(w, jnp.asarray(dense.values))
    np.testing.assert_allclose(np.asarray(m_dense), np.asarray(m_sparse),
                               rtol=1e-5, atol=1e-5)

    for reduce in ("sum", "mean"):
        g_sparse = model.grad_sum(w, sb, y) if reduce == "sum" else model.grad_mean(w, sb, y)
        g_dense = model.grad_dense(w, jnp.asarray(dense.values), y, reduce=reduce)
        np.testing.assert_allclose(np.asarray(g_dense), np.asarray(g_sparse),
                                   rtol=1e-4, atol=1e-5)

    # grad_regularized auto-routes dense batches regardless of `kernel`
    db = SparseBatch(jnp.asarray(dense.indices), jnp.asarray(dense.values))
    g_auto = model.grad_regularized(w, db, y, kernel="mxu")
    g_ref = model.regularize(model.grad_sum(w, sb, y), w)
    np.testing.assert_allclose(np.asarray(g_auto), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


def test_sync_engine_auto_selects_dense_kernel():
    dense, _ = _pair(n=64, d=16)
    eng = SyncEngine(make_model("hinge", 1e-3, 16, regularizer="l2"),
                     make_mesh(2), batch_size=4, learning_rate=0.1)
    bound = eng.bind(dense)
    assert bound.kernel == "dense"


def test_dense_kernel_layout_mismatch_raises():
    dense, sparse = _pair(n=64, d=16)
    model = make_model("hinge", 1e-3, 16, regularizer="l2")
    with pytest.raises(ValueError, match="dense"):
        SyncEngine(model, make_mesh(2), batch_size=4, learning_rate=0.1,
                   kernel="dense").bind(sparse)


@pytest.mark.parametrize("virtual_workers", [1, 3])
def test_sync_epoch_dense_matches_sparse(virtual_workers):
    dense, sparse = _pair(n=64, d=16, labels="reg")
    model = make_model("least_squares", 0.0, 16, regularizer="none")
    mesh = make_mesh(2)
    key = jax.random.PRNGKey(7)
    w0 = jnp.zeros(16, jnp.float32)

    def run(data, kernel):
        eng = SyncEngine(model, mesh, batch_size=4, learning_rate=0.05,
                         kernel=kernel, virtual_workers=virtual_workers)
        b = eng.bind(data)
        w = b.epoch(w0, key)
        return np.asarray(w), b.evaluate(w)

    w_dense, (loss_d, _) = run(dense, "mxu")  # bind auto-routes to 'dense'
    w_sparse, (loss_s, _) = run(sparse, "scalar")
    # identical sampling keys -> identical batches -> same trajectory up to
    # float summation order
    np.testing.assert_allclose(w_dense, w_sparse, rtol=1e-4, atol=1e-5)
    assert abs(loss_d - loss_s) < 1e-5


def test_sync_eval_and_predict_dense():
    dense, sparse = _pair(n=64, d=16)
    model = make_model("hinge", 1e-3, 16, regularizer="l2")
    mesh = make_mesh(2)
    w = jnp.asarray(np.random.default_rng(3).normal(size=16), jnp.float32)
    bd = SyncEngine(model, mesh, 4, 0.1).bind(dense)
    bs = SyncEngine(model, mesh, 4, 0.1, kernel="scalar").bind(sparse)
    loss_d, acc_d = bd.evaluate(w)
    loss_s, acc_s = bs.evaluate(w)
    assert abs(loss_d - loss_s) < 1e-5 and acc_d == acc_s
    np.testing.assert_allclose(bd.predict(w), bs.predict(w))


def test_dense_regression_uses_dense_layout():
    ds = dense_regression(16, n_features=8, seed=0)
    assert ds.is_dense
    assert ds.indices.shape == (16, 0)


def test_local_sgd_dense():
    from distributed_sgd_tpu.parallel.local_sgd import LocalSGDEngine

    dense, _ = _pair(n=64, d=16, labels="reg")
    model = make_model("least_squares", 0.0, 16, regularizer="none")
    eng = LocalSGDEngine(model, make_mesh(2), batch_size=4, learning_rate=0.05,
                         sync_period=4, check_every=32)
    res = eng.fit(dense.slice(slice(0, 48)), dense.slice(slice(48, 64)),
                  max_epochs=2)
    assert res.state.updates > 0
    assert np.isfinite(res.test_losses[-1])


def test_hogwild_dense():
    from distributed_sgd_tpu.parallel.hogwild import HogwildEngine

    dense, _ = _pair(n=64, d=16, labels="reg")
    model = make_model("least_squares", 0.0, 16, regularizer="none")
    eng = HogwildEngine(model, n_workers=2, batch_size=4, learning_rate=0.05,
                        check_every=16)
    res = eng.fit(dense.slice(slice(0, 48)), dense.slice(slice(48, 64)),
                  max_epochs=1)
    assert res.state.updates > 0


def test_forward_and_objective_route_dense():
    """model.forward/objective/accuracy on a dense batch must match the
    sparse layout — this is the RPC worker's Forward path (core/worker.py),
    which would silently see all-zero margins if margins() didn't route
    dense batches."""
    dense, sparse = _pair(n=32, d=16)
    model = make_model("hinge", 1e-3, 16, regularizer="l2")
    w = jnp.asarray(np.random.default_rng(2).normal(size=16), jnp.float32)
    y = jnp.asarray(dense.labels)
    db = SparseBatch(jnp.asarray(dense.indices), jnp.asarray(dense.values))
    sb = SparseBatch(jnp.asarray(sparse.indices), jnp.asarray(sparse.values))
    np.testing.assert_allclose(np.asarray(model.forward(w, db)),
                               np.asarray(model.forward(w, sb)))
    assert not np.all(np.asarray(model.forward(w, db)) == 0.0)
    np.testing.assert_allclose(float(model.objective(w, db, y)),
                               float(model.objective(w, sb, y)), rtol=1e-6)
    assert float(model.accuracy(w, db, y)) == float(model.accuracy(w, sb, y))


def test_zero_width_sparse_is_unambiguous():
    """All-empty-rows sparse data pads to width 1 (pack_csr), and a
    zero-width Dataset that does not span all features is rejected — so
    width 0 always means dense, everywhere."""
    from distributed_sgd_tpu.data.rcv1 import pack_csr

    row_ptr = np.array([0, 0, 0], dtype=np.int64)
    idx, val = pack_csr(row_ptr, np.empty(0, np.int32), np.empty(0, np.float32))
    assert idx.shape == (2, 1)  # width >= 1, not 0
    with pytest.raises(ValueError, match="dense layout"):
        Dataset(indices=np.empty((2, 0), np.int32),
                values=np.empty((2, 0), np.float32),
                labels=np.zeros(2, np.int32), n_features=5)


def test_dim_sparsity_dense_matches_sparse():
    from distributed_sgd_tpu.data.rcv1 import dim_sparsity

    dense, sparse = _pair(n=32, d=16)
    # introduce some exact zeros so counts differ per column
    dense.values[dense.values < -1.0] = 0.0
    sparse.values[sparse.values < -1.0] = 0.0
    np.testing.assert_allclose(dim_sparsity(dense), dim_sparsity(sparse))


def test_feature_sharded_trains_dense():
    """Dense-layout data trains feature-sharded (round 4; the engine used
    to reject it — full parity coverage lives in tests/test_feature_sharded
    .py::test_dense_layout_matches_dp_engine_trajectory)."""
    import jax as _jax
    from jax.sharding import Mesh

    from distributed_sgd_tpu.parallel.feature_sharded import FeatureShardedEngine

    dense, _ = _pair(n=64, d=16)
    model = make_model("hinge", 1e-3, 16, regularizer="l2")
    devs = np.array(_jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("workers", "features"))
    eng = FeatureShardedEngine(model, mesh, batch_size=4, learning_rate=0.1).bind(dense)
    w2 = eng.epoch(eng.init_weights(), _jax.random.PRNGKey(0))
    assert np.all(np.isfinite(eng.to_dense(w2)))
