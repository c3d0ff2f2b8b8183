"""The fused Pallas worker-gradient kernel (ops/pallas_sparse.py) must
match the model's blocked-XLA gradient path.  Runs under the Pallas
interpreter on the CPU test mesh."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sgd_tpu.models.linear import LeastSquares, LogisticRegression, SparseSVM
from distributed_sgd_tpu.ops import mxu, pallas_sparse
from distributed_sgd_tpu.ops.sparse import SparseBatch


def _batches(k=3, b=10, p=6, d=700, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, d, (k, b, p)).astype(np.int32)
    val = rng.normal(size=(k, b, p)).astype(np.float32)
    val[rng.random((k, b, p)) < 0.2] = 0.0
    y = rng.choice([-1, 1], (k, b)).astype(np.int32)
    return jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y), d


@pytest.mark.parametrize("cls", [SparseSVM, LogisticRegression, LeastSquares])
def test_fused_worker_grads_match_blocked_path(cls):
    idx, val, y, d = _batches(seed=3)
    if cls is SparseSVM:
        model = cls(lam=1e-3, n_features=d,
                    dim_sparsity=jnp.asarray(np.full(d, 0.01, np.float32)))
    else:
        model = cls(lam=1e-3, n_features=d, regularizer="l2")
    w = jnp.asarray(np.random.default_rng(1).normal(size=d) * 0.1, dtype=jnp.float32)
    w2 = mxu.to_blocked(w, d)

    def coeff_fn(margins, labels):
        return model.grad_coeff(margins, labels)

    got = pallas_sparse.worker_grads(w2, idx, val, y, coeff_fn, interpret=True)
    assert got.shape == (3, mxu.n_blocks(d), mxu.LANES)
    for k in range(3):
        want = model.grad_blocked(w2, SparseBatch(idx[k], val[k]), y[k])
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want), rtol=1e-4, atol=1e-5
        )


def test_pad_batch_inert_rows():
    idx, val, y, d = _batches(k=1, b=5, p=4, d=300, seed=7)  # 5 -> pads to 8
    model = SparseSVM(lam=0.0, n_features=d,
                      dim_sparsity=jnp.asarray(np.zeros(d, np.float32)))
    w2 = mxu.to_blocked(
        jnp.asarray(np.random.default_rng(2).normal(size=d), dtype=jnp.float32), d
    )
    got = pallas_sparse.worker_grads(
        w2, idx, val, y, model.grad_coeff, interpret=True
    )
    want = model.grad_blocked(w2, SparseBatch(idx[0], val[0]), y[0])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=1e-4, atol=1e-5)
