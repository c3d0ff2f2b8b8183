"""Edge-shape sweep for the blocked kernel layouts.

Lane-blocked layouts classically break at boundary shapes: feature dims
below one lane (D < 128), exactly on a block edge (D = 128k), one-past
(D = 128k + 1), single-sample and single-nnz batches.  Every (layout,
shape) pair must agree with the scalar-path kernels, and every sparse
family (ops/kernels.py) must hand back the same worker reply through the
model's one dispatch.
"""


import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sgd_tpu.models.linear import SparseSVM
from distributed_sgd_tpu.ops import flat_sparse, gather, mxu
from distributed_sgd_tpu.ops.sparse import SparseBatch, matvec, scatter_add

DIMS = [1, 5, 127, 128, 129, 1024, 1025]
# (113, 75): 8,475 entries, deep enough for `mxu.scatter_shards` to cut the
# one-hot scatter in two (one pad entry)
BATCHES = [(1, 1), (1, 4), (3, 1), (9, 5), (113, 75)]


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


# -- the sparse families' worker reply (models/linear.py's one dispatch) ----
#
# What an engine runs: weights into the family's layout, margins, then one
# worker's reply (`grad`) or the K virtual workers' replies summed
# (`grad_workers`: 'mxu' and 'gather' get the K margins from ONE call on the
# merged batch, which `mxu.lane_minor_rows` may pad, and 'gather' under a
# linear regulariser scatters all K batches into one accumulator).  Held to
# the scalar-path kernels on the same rows, under both regularisers.

FAMILIES = ("mxu", "gather", "scalar")
TOL = dict(rtol=1e-4, atol=1e-5)


def _reference_reply(model, w, batch, y):
    coeff = model.grad_coeff(matvec(batch, w), y)
    return np.asarray(model.regularize(scatter_add(batch, coeff, model.n_features), w))


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("d", DIMS)
@pytest.mark.parametrize("bp", BATCHES)
def test_family_reply_all_shapes(family, k, d, bp):
    b, p = bp
    made = [_mk(b, p, d, seed=d * 31 + b + 7 * j) for j in range(k)]
    w = jnp.asarray(np.random.default_rng(d).normal(size=d), dtype=jnp.float32)
    ds = jnp.asarray(np.random.default_rng(d + 2).random(d) * 0.01, dtype=jnp.float32)
    for regularizer in ("dim_sparsity", "l2"):
        model = SparseSVM(lam=1e-3, n_features=d, dim_sparsity=ds,
                          regularizer=regularizer)
        wl = model.to_layout(w, family)
        for batch, _ in made:
            np.testing.assert_allclose(
                np.asarray(model.margins(wl, batch, kernel=family)),
                np.asarray(matvec(batch, w)), **TOL)
        if k == 1:
            (batch, y), = made
            got = model.grad(wl, batch, y, kernel=family)
        else:
            got = model.grad_workers(
                wl, jnp.stack([m[0].indices for m in made]),
                jnp.stack([m[0].values for m in made]),
                jnp.stack([m[1] for m in made]), kernel=family)
        want = sum(_reference_reply(model, w, batch, y) for batch, y in made)
        np.testing.assert_allclose(
            np.asarray(model.from_layout(got, family)), want,
            err_msg=f"{family}, {regularizer}", **TOL)


# -- each family's scatter on the scatter-specific traps --------------------
#
# All-pad (empty) rows, duplicate feature ids within a row (the fancy-
# indexed += failure mode), pads scattering into feature 0 on top of a REAL
# feature-0 contribution, B=1 and B=1024: against a float64 `np.add.at`.


def _family_scatter(family, batch, coeff, d):
    if family == "scalar":
        return np.asarray(scatter_add(batch, coeff, d))
    blocked = (mxu if family == "mxu" else gather).scatter_add(
        batch, coeff, mxu.n_blocks(d))
    return np.asarray(mxu.from_blocked(blocked, d))


def _assert_scatter_matches(batch, coeff, d, family):
    want = np.zeros(d, np.float64)
    np.add.at(want, np.asarray(batch.indices).reshape(-1),
              (np.asarray(batch.values, np.float64)
               * np.asarray(coeff, np.float64)[:, None]).reshape(-1))
    np.testing.assert_allclose(
        _family_scatter(family, batch, coeff, d), want,
        err_msg=f"family {family}", **TOL)


@pytest.mark.parametrize("family", FAMILIES)
def test_scatter_empty_rows_and_duplicates(family):
    d, b, p = 300, 6, 8
    rng = np.random.default_rng(5)
    idx = rng.integers(0, d, (b, p)).astype(np.int32)
    val = rng.normal(size=(b, p)).astype(np.float32)
    val[1, :] = 0.0  # fully-empty (all-pad) row
    idx[2, :] = idx[2, 0]  # every entry duplicates ONE feature id
    idx[3, :4] = 7  # partial duplicates within a row
    batch = SparseBatch(jnp.asarray(idx), jnp.asarray(val))
    coeff = jnp.asarray(rng.normal(size=b), dtype=jnp.float32)
    _assert_scatter_matches(batch, coeff, d, family)


@pytest.mark.parametrize("family", FAMILIES)
def test_scatter_pad_into_real_feature_zero(family):
    # pads are (index 0, value 0); a REAL feature-0 contribution must come
    # through exactly while the pads add nothing to it
    d, b = 130, 3
    idx = np.array([[0, 5, 0, 0], [129, 0, 0, 0], [0, 0, 0, 0]], np.int32)
    val = np.array([[2.0, 1.0, 0.0, 0.0], [1.5, 3.0, 0.0, 0.0],
                    [0.0, 0.0, 0.0, 0.0]], np.float32)
    batch = SparseBatch(jnp.asarray(idx), jnp.asarray(val))
    coeff = jnp.asarray([1.0, -2.0, 5.0], dtype=jnp.float32)
    _assert_scatter_matches(batch, coeff, d, family)
    got = _family_scatter(family, batch, coeff, d)
    # hand-computed: feature 0 gets 1*2.0 + (-2)*3.0 = -4 (pads add 0)
    np.testing.assert_allclose(got[0], -4.0, **TOL)
    np.testing.assert_allclose(got[129], -3.0, **TOL)


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("b", [1, 1024])
def test_scatter_batch_extremes(family, b):
    d, p = 512, 5
    batch, _ = _mk(b, p, d, seed=b)
    coeff = jnp.asarray(np.random.default_rng(b + 1).normal(size=b),
                        dtype=jnp.float32)
    _assert_scatter_matches(batch, coeff, d, family)


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
