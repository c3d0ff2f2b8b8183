"""Edge-shape sweep for the blocked kernel layouts.

Lane-blocked layouts classically break at boundary shapes: feature dims
below one lane (D < 128), exactly on a block edge (D = 128k), one-past
(D = 128k + 1), single-sample and single-nnz batches.  Every (layout,
shape) pair must agree with the scalar-path kernels.
"""


import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sgd_tpu.models.linear import SparseSVM
from distributed_sgd_tpu.ops import flat_sparse, mxu, pallas_sparse
from distributed_sgd_tpu.ops.sparse import SparseBatch, matvec, scatter_add

DIMS = [1, 5, 127, 128, 129, 1024, 1025]
BATCHES = [(1, 1), (1, 4), (3, 1), (9, 5)]


def _mk(b, p, d, seed):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, d, (b, p)).astype(np.int32)
    val = rng.normal(size=(b, p)).astype(np.float32)
    if b * p > 2:
        val.reshape(-1)[rng.integers(0, b * p, 2)] = 0.0  # some pads
    y = rng.choice([-1, 1], b).astype(np.int32)
    return SparseBatch(jnp.asarray(idx), jnp.asarray(val)), jnp.asarray(y)


@pytest.mark.parametrize("d", DIMS)
@pytest.mark.parametrize("bp", BATCHES)
def test_mxu_kernels_all_shapes(d, bp):
    b, p = bp
    batch, _ = _mk(b, p, d, seed=d * 31 + b)
    w = jnp.asarray(np.random.default_rng(d).normal(size=d), dtype=jnp.float32)
    w2 = mxu.to_blocked(w, d)
    np.testing.assert_allclose(
        np.asarray(mxu.matvec(batch, w2)),
        np.asarray(matvec(batch, w)),
        rtol=1e-4, atol=1e-5,
    )
    coeff = jnp.asarray(np.random.default_rng(d + 1).normal(size=b), dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(mxu.from_blocked(mxu.scatter_add(batch, coeff, mxu.n_blocks(d)), d)),
        np.asarray(scatter_add(batch, coeff, d)),
        rtol=1e-4, atol=1e-5,
    )


# -- selectable scatter formulations (ops/mxu.py DSGD_SCATTER) -------------
#
# Every formulation must agree with the scalar-path scatter on the same
# boundary shapes as the one-hot layout, PLUS the scatter-specific traps:
# all-pad (empty) rows, duplicate feature ids within a row (the fancy-
# indexed += failure mode a segment reduction must not reproduce), pads
# scattering into feature 0 on top of a REAL feature-0 contribution, B=1
# and B=1024, and the bf16 accumulation bound.

FORM_TOL = {"onehot": dict(rtol=1e-4, atol=1e-5),
            "segment": dict(rtol=1e-4, atol=1e-5),
            "twostage": dict(rtol=1e-4, atol=1e-5),
            # bf16 partial sums carry ~3 decimal digits, and the error
            # scales with the ACCUMULATED magnitude (cancellation can make
            # a final value small while its partial sums were large) — so
            # the bound is rtol + an atol proportional to the largest
            # accumulated value (_tol below): the documented accumulation
            # bound, NOT float-order noise (ops/mxu.py)
            "bf16": dict(rtol=2e-2, atol=2e-3)}


def _tol(form, want):
    tol = dict(FORM_TOL[form])
    if form == "bf16":
        tol["atol"] = max(tol["atol"], 3e-3 * float(np.abs(want).max()))
    return tol


def _assert_scatter_matches(batch, coeff, d, form):
    with mxu.scatter_formulation(form):
        got = mxu.from_blocked(
            mxu.scatter_add(batch, coeff, mxu.n_blocks(d)), d)
    want = np.asarray(scatter_add(batch, coeff, d))
    np.testing.assert_allclose(
        np.asarray(got), want, err_msg=f"formulation {form}",
        **_tol(form, want))


@pytest.mark.parametrize("form", mxu.SCATTER_FORMULATIONS)
@pytest.mark.parametrize("d", DIMS)
@pytest.mark.parametrize("bp", BATCHES)
def test_scatter_formulations_all_shapes(form, d, bp):
    b, p = bp
    batch, _ = _mk(b, p, d, seed=d * 31 + b)
    coeff = jnp.asarray(np.random.default_rng(d + 1).normal(size=b),
                        dtype=jnp.float32)
    _assert_scatter_matches(batch, coeff, d, form)


@pytest.mark.parametrize("form", mxu.SCATTER_FORMULATIONS)
def test_scatter_formulations_empty_rows_and_duplicates(form):
    d, b, p = 300, 6, 8
    rng = np.random.default_rng(5)
    idx = rng.integers(0, d, (b, p)).astype(np.int32)
    val = rng.normal(size=(b, p)).astype(np.float32)
    val[1, :] = 0.0  # fully-empty (all-pad) row
    idx[2, :] = idx[2, 0]  # every entry duplicates ONE feature id
    idx[3, :4] = 7  # partial duplicates within a row
    batch = SparseBatch(jnp.asarray(idx), jnp.asarray(val))
    coeff = jnp.asarray(rng.normal(size=b), dtype=jnp.float32)
    _assert_scatter_matches(batch, coeff, d, form)


@pytest.mark.parametrize("form", mxu.SCATTER_FORMULATIONS)
def test_scatter_formulations_pad_into_real_feature_zero(form):
    # pads are (index 0, value 0); a REAL feature-0 contribution must come
    # through exactly while the pads add nothing to it
    d, b = 130, 3
    idx = np.array([[0, 5, 0, 0], [129, 0, 0, 0], [0, 0, 0, 0]], np.int32)
    val = np.array([[2.0, 1.0, 0.0, 0.0], [1.5, 3.0, 0.0, 0.0],
                    [0.0, 0.0, 0.0, 0.0]], np.float32)
    batch = SparseBatch(jnp.asarray(idx), jnp.asarray(val))
    coeff = jnp.asarray([1.0, -2.0, 5.0], dtype=jnp.float32)
    _assert_scatter_matches(batch, coeff, d, form)
    with mxu.scatter_formulation(form):
        got = np.asarray(mxu.from_blocked(
            mxu.scatter_add(batch, coeff, mxu.n_blocks(d)), d))
    # hand-computed: feature 0 gets 1*2.0 + (-2)*3.0 = -4 (pads add 0)
    np.testing.assert_allclose(got[0], -4.0, **FORM_TOL[form])
    np.testing.assert_allclose(got[129], -3.0, **FORM_TOL[form])


@pytest.mark.parametrize("form", mxu.SCATTER_FORMULATIONS)
@pytest.mark.parametrize("b", [1, 1024])
def test_scatter_formulations_batch_extremes(form, b):
    d, p = 512, 5
    batch, _ = _mk(b, p, d, seed=b)
    coeff = jnp.asarray(np.random.default_rng(b + 1).normal(size=b),
                        dtype=jnp.float32)
    _assert_scatter_matches(batch, coeff, d, form)


def test_bf16_accumulation_bound_is_real():
    """The bf16 bound is a loosened TOLERANCE, not a different result: on
    an adversarial batch (many near-cancelling contributions into one
    feature) the bf16 error must stay within FORM_TOL['bf16'] of the f32
    scatter while being measurably nonzero — i.e. the formulation really
    accumulates in bf16 (a silent f32 fallback would be bit-exact)."""
    d, b, p = 256, 64, 16
    rng = np.random.default_rng(11)
    idx = np.full((b, p), 3, np.int32)  # everything lands on feature 3
    val = rng.normal(size=(b, p)).astype(np.float32)
    batch = SparseBatch(jnp.asarray(idx), jnp.asarray(val))
    coeff = jnp.asarray(rng.normal(size=b), dtype=jnp.float32)
    want = np.asarray(scatter_add(batch, coeff, d))
    with mxu.scatter_formulation("bf16"):
        got = np.asarray(mxu.from_blocked(
            mxu.scatter_add(batch, coeff, mxu.n_blocks(d)), d))
    np.testing.assert_allclose(got, want, **_tol("bf16", want))
    assert np.any(got != want), \
        "bf16 scatter is bit-identical to f32 — it is not accumulating in bf16"


@pytest.mark.parametrize("d", [1, 127, 129, 1025])
@pytest.mark.parametrize("bp", BATCHES)
def test_pallas_kernel_all_shapes(d, bp):
    b, p = bp
    batch, y = _mk(b, p, d, seed=d * 17 + b)
    model = SparseSVM(lam=1e-3, n_features=d,
                      dim_sparsity=jnp.asarray(np.full(d, 0.01, np.float32)))
    w2 = mxu.to_blocked(
        jnp.asarray(np.random.default_rng(d).normal(size=d), dtype=jnp.float32), d
    )
    got = pallas_sparse.worker_grads(
        w2, batch.indices[None], batch.values[None], y[None],
        model.grad_coeff, interpret=True,
    )
    want = model.grad_blocked(w2, batch, y)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("d", [1, 128, 129])
def test_flat_sparse_all_shapes(d):
    batch, _ = _mk(4, 3, d, seed=d)
    flat = flat_sparse.from_padded(
        SparseBatch(np.asarray(batch.indices), np.asarray(batch.values))
    )
    w = jnp.asarray(np.random.default_rng(d).normal(size=d), dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(flat_sparse.matvec(flat, w)),
        np.asarray(matvec(batch, w)),
        rtol=1e-4, atol=1e-5,
    )
