"""The K virtual workers of a device share `w`: `LinearModel.grad_workers`
computes their margins in ONE call on the merged batch and keeps the replies
apart where the regulariser needs them apart (PERF.md section 6, PR 27).

What the chip compiles it to is held by tests/test_row_placement.py (the
`v5e` fixture); here: the sum of replies is what K `grad` calls give, the
margins bit for bit, and the binding counts its choice.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.ops import gather, kernels, mxu
from distributed_sgd_tpu.ops.sparse import SparseBatch
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine
from distributed_sgd_tpu.utils import metrics as metrics_mod

D, P, K = 47_236, 76, 4  # rcv1-hinge: R = 376 blocked rows
LAM = 1e-2  # large enough for the regulariser's term to show in float32


def _workers(batch, duplicates, seed=27):
    """[K, B, P] stacked batches over D features.  `duplicates`: ids
    repeated inside a row, inside a worker's batch and across workers, so
    that the replies' supports overlap."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, D, (K, batch, P)).astype(np.int32)
    if duplicates:
        idx[:, :, 1] = idx[:, :, 0]  # twice in one row
        idx[:, :, 2] = 4242          # in every row of every worker
        idx[1:, :, 3:9] = idx[0, :, 3:9]  # worker 0's ids again in the others
    val = rng.normal(size=(K, batch, P)).astype(np.float32)
    y = rng.choice([-1, 1], (K, batch)).astype(np.int32)
    w = (rng.normal(size=D) * 0.1).astype(np.float32)
    ds = rng.random(D).astype(np.float32)
    return jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y), jnp.asarray(w), ds


@pytest.mark.parametrize("duplicates", [False, True], ids=["distinct", "duplicates"])
@pytest.mark.parametrize("batch", [100, 200])
@pytest.mark.parametrize("kernel", ["mxu", "gather"])
def test_merged_margins_give_the_sum_of_the_workers_replies(kernel, batch, duplicates):
    idx, val, y, w, ds = _workers(batch, duplicates)
    model = make_model("hinge", LAM, D, dim_sparsity=ds)
    w2 = model.to_layout(w, kernel)

    # the margins of the merged batch ARE the workers' own: one non-zero
    # term a gathered product, the same sum over a row's P entries
    matvec = gather.matvec if kernel == "gather" else mxu.matvec
    merged = matvec(SparseBatch(idx.reshape(K * batch, P), val.reshape(K * batch, P)), w2)
    apart = jnp.stack([matvec(SparseBatch(idx[j], val[j]), w2) for j in range(K)])
    np.testing.assert_array_equal(np.asarray(merged).reshape(K, batch), np.asarray(apart))

    replies = [model.grad(w2, SparseBatch(idx[j], val[j]), y[j], kernel=kernel)
               for j in range(K)]
    want = np.sum([np.asarray(g, np.float64) for g in replies], axis=0)
    got = np.asarray(jax.jit(
        lambda *a: model.grad_workers(*a, kernel=kernel))(w2, idx, val, y))
    # the same K replies (the hinge's jump sees the same margins), summed
    # in another order
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    if duplicates:
        # 'dim_sparsity' adds its term once for EVERY reply whose support
        # holds a feature; one scatter of all K batches would add it once
        scatter = gather.scatter_add if kernel == "gather" else mxu.scatter_add
        raw = np.sum([np.asarray(scatter(SparseBatch(idx[j], val[j]), model.grad_coeff(
            apart[j], y[j]), w2.shape[0]), np.float64) for j in range(K)], axis=0)
        term = 2.0 * LAM * float(np.dot(np.asarray(w, np.float64), ds))
        r, lane = divmod(4242, mxu.LANES)
        np.testing.assert_allclose(got[r, lane] - raw[r, lane], K * term, rtol=1e-3)


def test_the_rule_reads_family_and_row_width_only():
    assert kernels.merges_margins("mxu", P) and kernels.merges_margins("gather", 39)
    assert not kernels.merges_margins("mxu", 0)        # the dense layout
    assert not kernels.merges_margins("scalar", P)     # vmapped as they were
    assert not kernels.merges_margins("dense", 0)


def _sparse(n=512):
    return rcv1_like(n, n_features=640, nnz=6, seed=3)


def _dense(n=512):
    rng = np.random.default_rng(3)
    return Dataset.dense(rng.normal(size=(n, 24)).astype(np.float32),
                         np.where(rng.random(n) < 0.5, 1, -1).astype(np.int32))


@pytest.mark.parametrize("kernel,workers,data,merged", [
    ("mxu", 4, _sparse, True),
    ("gather", 2, _sparse, True),
    ("mxu", 1, _sparse, False),     # one worker a device: nothing to merge
    ("scalar", 4, _sparse, False),
    ("dense", 4, _dense, False),
])
def test_a_binding_counts_merged_margins_once(kernel, workers, data, merged):
    rows = data()
    model = make_model("hinge", 1e-3, rows.n_features, regularizer="l2")
    counter = metrics_mod.counter("bind.margins.merged")
    before = counter.value
    bound = SyncEngine(model, make_mesh(2), 8, 0.1, kernel=kernel, eval_chunk=32,
                       virtual_workers=workers).bind(rows)
    assert (bound.plan.margins == "merged") == merged
    assert counter.value - before == int(merged)
    bound.epoch(jnp.zeros((rows.n_features,), jnp.float32), jax.random.PRNGKey(0))
    assert counter.value - before == int(merged)  # a binding, not a trace or a run


@pytest.mark.parametrize("workers,said", [(4, "margins=merged"), (1, "margins=per_worker")])
def test_the_train_split_record_says_how_the_margins_are_computed(workers, said, caplog):
    from distributed_sgd_tpu.core.trainer import SyncTrainer

    rows = _sparse()
    model = make_model("hinge", 1e-3, rows.n_features, regularizer="l2")
    with caplog.at_level(logging.INFO, logger="dsgd.trainer"):
        SyncTrainer(model, make_mesh(1), 8, 0.1, virtual_workers=workers).fit(
            rows, rows, max_epochs=1)
    record = next(r.getMessage() for r in caplog.records
                  if r.getMessage().startswith("train split:"))
    assert said in record and "kernel=mxu" in record
