"""Independent reference-semantics oracle.

Re-implements the reference's sync training math in pure python dicts —
boxed sparse maps, exactly the data structures and formulas of
SparseSVM.scala:14-31, Slave.scala:142-157 and Master.scala:179-198 —
with NO use of this package's ops/models, and checks the compiled engine
reproduces it step for step.  This is the strongest parity check in the
suite: every kernel (scalar take/scatter, one-hot MXU, row gather) must land
on the same numbers as the boxed-map algorithm.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import SparseSVM
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine

D, B, K, LR, LAM = 300, 6, 2, 0.25, 1e-3


def _sparse_rows(data):
    rows = []
    for i in range(len(data)):
        idx = np.asarray(data.indices[i])
        val = np.asarray(data.values[i])
        rows.append({int(k): float(v) for k, v in zip(idx, val) if v != 0.0})
    return rows


def oracle_worker_grad(w: dict, rows, ys, ids, ds: dict):
    """One worker's Gradient reply on boxed maps (Slave.scala:142-157)."""
    grad: dict = {}
    for i in ids:  # per-sample backward, summed (sum, not mean)
        x, y = rows[i], ys[i]
        dot = sum(v * w.get(k, 0.0) for k, v in x.items())  # Sparse dot
        if y * dot >= 0:  # backward = y*x unless y*(x.w) < 0 (SparseSVM:26-29)
            for k, v in x.items():
                grad[k] = grad.get(k, 0.0) + y * v
    grad = {k: v for k, v in grad.items() if v != 0.0}  # Sparse drops zeros
    # regularize: + lambda*2*(w . dimSparsity) at grad's stored keys
    scalar = LAM * 2.0 * sum(wv * ds.get(k, 0.0) for k, wv in w.items())
    return {k: v + scalar for k, v in grad.items()}


def oracle_step(w: dict, rows, ys, ids_per_worker, ds: dict):
    """Master batch step: mean of worker replies, update (Master:194-197)."""
    grads = [oracle_worker_grad(w, rows, ys, ids, ds) for ids in ids_per_worker]
    keys = set().union(*[g.keys() for g in grads]) if grads else set()
    mean = {k: sum(g.get(k, 0.0) for g in grads) / len(grads) for k in keys}
    out = dict(w)
    for k, v in mean.items():
        out[k] = out.get(k, 0.0) - LR * v
    return out


@pytest.mark.parametrize("kernel", ["scalar", "mxu", "gather"])
def test_engine_matches_boxed_map_oracle(kernel):
    data = rcv1_like(64, n_features=D, nnz=8, seed=3)
    rows = _sparse_rows(data)
    ys = [int(y) for y in np.asarray(data.labels)]
    rng = np.random.default_rng(9)
    ds_vec = np.abs(rng.normal(size=D)).astype(np.float32) * 0.01
    ds_map = {i: float(ds_vec[i]) for i in range(D)}

    model = SparseSVM(lam=LAM, n_features=D, dim_sparsity=jnp.asarray(ds_vec))
    mesh = make_mesh(1)
    eng = SyncEngine(model, mesh, batch_size=B, learning_rate=LR,
                     kernel=kernel, virtual_workers=K)
    bound = eng.bind(data)

    w_np = (rng.normal(size=D) * 0.1).astype(np.float32)
    key = jax.random.PRNGKey(21)
    got = np.asarray(bound.step(jnp.asarray(w_np), key))

    # replicate the engine's sampling stream (disjoint per-virtual-worker
    # sub-shards), then run the boxed-map oracle
    key2 = jax.random.fold_in(key, 0)  # axis_index 0 on the 1-device mesh
    sub = bound.shard_n // K
    ids = np.asarray(
        jax.random.randint(jax.random.fold_in(key2, 0), (K, B), 0, sub)
    ) + (np.arange(K) * sub)[:, None]
    w0 = {i: float(w_np[i]) for i in range(D) if w_np[i] != 0.0}
    w1 = oracle_step(w0, rows, ys, [list(ids[k]) for k in range(K)], ds_map)
    want = np.zeros(D, dtype=np.float64)
    for k, v in w1.items():
        want[k] = v

    np.testing.assert_allclose(got, want.astype(np.float32),
                               rtol=2e-4, atol=2e-6)


def test_oracle_objective_matches_model():
    """Objective formula cross-check: lambda*||w||^2 + mean hinge on the
    sign-quirk prediction (SparseSVM.scala:14-23), boxed-map style."""
    data = rcv1_like(32, n_features=D, nnz=8, seed=5)
    rows = _sparse_rows(data)
    ys = [int(y) for y in np.asarray(data.labels)]
    rng = np.random.default_rng(1)
    w_np = (rng.normal(size=D) * 0.2).astype(np.float32)
    w = {i: float(w_np[i]) for i in range(D)}

    losses = []
    for x, y in zip(rows, ys):
        dot = sum(v * w.get(k, 0.0) for k, v in x.items())
        pred = -np.sign(dot)  # signum(x.w) * -1
        losses.append(max(0.0, 1.0 - y * pred))
    want = LAM * sum(v * v for v in w.values()) + float(np.mean(losses))

    from distributed_sgd_tpu.ops.sparse import SparseBatch

    model = SparseSVM(lam=LAM, n_features=D,
                      dim_sparsity=jnp.asarray(np.zeros(D, np.float32)))
    batch = SparseBatch(jnp.asarray(data.indices), jnp.asarray(data.values))
    got = float(model.objective(jnp.asarray(w_np), batch, jnp.asarray(data.labels)))
    assert abs(got - want) < 1e-4
