"""Blocked one-hot MXU kernels (ops/mxu.py) must match the scalar-path
kernels (ops/sparse.py) exactly up to float summation order — same math,
different hardware mapping."""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import SparseSVM
from distributed_sgd_tpu.ops import mxu
from distributed_sgd_tpu.ops.sparse import SparseBatch, matvec, scatter_add
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine


def _batch(b=12, p=7, d=500, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, d, (b, p)).astype(np.int32)
    val = rng.normal(size=(b, p)).astype(np.float32)
    val[rng.random((b, p)) < 0.25] = 0.0
    y = rng.choice([-1, 1], b).astype(np.int32)
    return SparseBatch(jnp.asarray(idx), jnp.asarray(val)), jnp.asarray(y), d


def _model(d, seed=1):
    rng = np.random.default_rng(seed)
    ds = np.abs(rng.normal(size=d)).astype(np.float32) * 0.01
    return SparseSVM(lam=1e-3, n_features=d, dim_sparsity=jnp.asarray(ds))


class TestBlockedOps:
    def test_layout_roundtrip(self):
        d = 500
        w = jnp.asarray(np.random.default_rng(0).normal(size=d), dtype=jnp.float32)
        w2 = mxu.to_blocked(w, d)
        assert w2.shape == (mxu.n_blocks(d), mxu.LANES)
        assert mxu.n_blocks(d) % 8 == 0
        np.testing.assert_array_equal(np.asarray(mxu.from_blocked(w2, d)), np.asarray(w))

    def test_matvec_matches_scalar(self):
        batch, _, d = _batch(seed=2)
        w = jnp.asarray(np.random.default_rng(3).normal(size=d), dtype=jnp.float32)
        got = mxu.matvec(batch, mxu.to_blocked(w, d))
        want = matvec(batch, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    def test_scatter_matches_scalar(self):
        batch, _, d = _batch(seed=4)
        coeff = jnp.asarray(np.random.default_rng(5).normal(size=batch.batch_size),
                            dtype=jnp.float32)
        g2 = mxu.scatter_add(batch, coeff, mxu.n_blocks(d))
        got = mxu.from_blocked(g2, d)
        want = scatter_add(batch, coeff, d)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
        # pad lanes beyond D must stay exactly zero
        tail = np.asarray(g2).reshape(-1)[d:]
        np.testing.assert_array_equal(tail, np.zeros_like(tail))

    def test_model_grad_blocked_matches(self):
        batch, y, d = _batch(seed=6)
        model = _model(d)
        w = jnp.asarray(np.random.default_rng(7).normal(size=d) * 0.1, dtype=jnp.float32)
        w2 = mxu.to_blocked(w, d)
        for reduce in ("sum", "mean"):
            got = mxu.from_blocked(model.grad_blocked(w2, batch, y, reduce=reduce), d)
            want = model.grad_sum(w, batch, y) if reduce == "sum" else model.grad_mean(w, batch, y)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)

    def test_regularize_blocked_matches(self):
        batch, y, d = _batch(seed=8)
        model = _model(d)
        w = jnp.asarray(np.random.default_rng(9).normal(size=d) * 0.1, dtype=jnp.float32)
        w2 = mxu.to_blocked(w, d)
        g2 = model.grad_blocked(w2, batch, y)
        got = mxu.from_blocked(model.regularize_blocked(g2, w2), d)
        want = model.regularize(mxu.from_blocked(g2, d), w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


class TestEngineKernelEquivalence:
    def test_step_and_epoch_match_scalar_kernel(self):
        d = 300
        data = rcv1_like(64, n_features=d, nnz=9, seed=0)
        model = _model(d, seed=1)
        mesh = make_mesh(4)
        w0 = jnp.asarray(np.random.default_rng(2).normal(size=d) * 0.05, dtype=jnp.float32)
        key = jax.random.PRNGKey(7)

        outs = {}
        for kernel in ("scalar", "mxu"):
            eng = SyncEngine(model, mesh, batch_size=4, learning_rate=0.3, kernel=kernel)
            bound = eng.bind(data)
            w_step = bound.step(w0, key)
            w_epoch = bound.epoch(w0, key)
            outs[kernel] = (np.asarray(w_step), np.asarray(w_epoch))
        np.testing.assert_allclose(outs["mxu"][0], outs["scalar"][0], rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(outs["mxu"][1], outs["scalar"][1], rtol=1e-3, atol=1e-5)


def test_grad_regularized_blocked_matches_scalar():
    batch, y, d = _batch(seed=11)
    model = _model(d)
    w = jnp.asarray(np.random.default_rng(12).normal(size=d) * 0.1, dtype=jnp.float32)
    for reduce in ("sum", "mean"):
        got = model.grad_regularized(w, batch, y, reduce=reduce, kernel="mxu")
        want = model.grad_regularized(w, batch, y, reduce=reduce, kernel="scalar")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
        )
