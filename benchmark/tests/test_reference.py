"""`reference.py` against the program's `models/linear.py` at a tiny size
on the CPU, where both compute in float32: hinge and logistic, sparse and
dense rows, the sync (sum) and the async (mean) reply, and evaluation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.ops.sparse import SparseBatch

D, B, P = 300, 24, 7
LAM = 1e-3


def _rows(dense: bool, seed: int = 0):
    rng = np.random.default_rng(seed)
    y = rng.choice([-1, 1], size=B).astype(np.int32)
    if dense:
        return None, rng.normal(size=(B, D)).astype(np.float32), y
    idx = np.sort(rng.choice(D, size=(B, P)), axis=1).astype(np.int32)
    return idx, rng.normal(size=(B, P)).astype(np.float32), y


def _model(loss: str, reg: str):
    ds = np.random.default_rng(1).random(D).astype(np.float32) if reg == "dim_sparsity" else None
    return make_model(loss, LAM, D, dim_sparsity=ds, regularizer=reg), ds


CASES = [(loss, reg, dense) for loss in ("hinge", "logistic")
         for reg in ("dim_sparsity", "l2") for dense in (False, True)]


@pytest.mark.parametrize("loss,reg,dense", CASES)
@pytest.mark.parametrize("reduce", ["sum", "mean"])
def test_worker_grad_matches_the_program(loss, reg, dense, reduce):
    model, ds = _model(loss, reg)
    idx, val, y = _rows(dense)
    w = jnp.asarray(np.random.default_rng(2).normal(size=D).astype(np.float32))
    batch = SparseBatch(
        jnp.zeros((B, 0), jnp.int32) if dense else jnp.asarray(idx), jnp.asarray(val))
    want = model.grad_regularized(w, batch, jnp.asarray(y), reduce=reduce)
    got = reference.worker_grad(
        loss, reg, w, None if dense else jnp.asarray(idx), jnp.asarray(val),
        jnp.asarray(y), LAM, None if ds is None else jnp.asarray(ds), reduce)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("loss,reg,dense", CASES)
def test_evaluate_matches_the_program(loss, reg, dense):
    model, _ = _model(loss, reg)
    idx, val, y = _rows(dense, seed=3)
    w = jnp.asarray(np.random.default_rng(4).normal(size=D).astype(np.float32))
    batch = SparseBatch(
        jnp.zeros((B, 0), jnp.int32) if dense else jnp.asarray(idx), jnp.asarray(val))
    want_obj = float(model.objective(w, batch, jnp.asarray(y)))
    want_acc = float(model.accuracy(w, batch, jnp.asarray(y)))
    obj, acc = reference.evaluate(
        loss, w, None if dense else jnp.asarray(idx), jnp.asarray(val),
        jnp.asarray(y), LAM, block=8)
    assert obj == pytest.approx(want_obj, rel=1e-5)
    assert acc == pytest.approx(want_acc, abs=1e-7)


def test_evaluate_skips_padding_rows_and_walks_device_shards():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P_

    idx, val, y = _rows(False, seed=5)
    y = y.copy()
    y[-4:] = 0  # padding
    w = jnp.asarray(np.random.default_rng(6).normal(size=D).astype(np.float32))
    plain = reference.evaluate("hinge", w, jnp.asarray(idx), jnp.asarray(val),
                               jnp.asarray(y), LAM)
    live = reference.evaluate("hinge", w, jnp.asarray(idx[:-4]), jnp.asarray(val[:-4]),
                              jnp.asarray(y[:-4]), LAM)
    assert plain == pytest.approx(live)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("x",))
    put = lambda a: jax.device_put(a, NamedSharding(mesh, P_("x")))  # noqa: E731
    sharded = reference.evaluate("hinge", w, put(idx), put(val), put(y), LAM)
    assert sharded == pytest.approx(plain)


def test_sync_step_is_the_mean_over_all_workers():
    idx, val, y = _rows(False, seed=7)
    ds = jnp.asarray(np.random.default_rng(1).random(D).astype(np.float32))
    w = jnp.asarray(np.random.default_rng(8).normal(size=D).astype(np.float32))
    halves = [(jnp.asarray(idx[:12]), jnp.asarray(val[:12]), jnp.asarray(y[:12])),
              (jnp.asarray(idx[12:]), jnp.asarray(val[12:]), jnp.asarray(y[12:]))]
    g = [reference.worker_grad("hinge", "dim_sparsity", w, *h, LAM, ds, "sum") for h in halves]
    want = w - 0.5 * (g[0] + g[1]) / 2
    got = reference.sync_step("hinge", "dim_sparsity", w, halves, LAM, 0.5, ds)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)


def test_local_steps_accumulate_the_delta():
    idx, val, y = _rows(False, seed=9)
    w = jnp.asarray(np.random.default_rng(10).normal(size=D).astype(np.float32))
    args = (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y))
    one = reference.local_steps("logistic", "l2", w, *args, LAM, 0.1, 1)
    two = reference.local_steps("logistic", "l2", w, *args, LAM, 0.1, 2)
    second = reference.local_steps("logistic", "l2", w - one, *args, LAM, 0.1, 1)
    np.testing.assert_allclose(np.asarray(two), np.asarray(one + second), rtol=1e-6, atol=1e-8)


def test_kink_distance_is_relative_and_hinge_only():
    w = jnp.asarray([1.0, -1.0, 2.0])
    idx = jnp.asarray([[0, 1], [0, 2]])
    val = jnp.asarray([[1.0, 1.0], [1.0, 1.0]])
    y = jnp.asarray([1, -1])
    dist = reference.kink_distance("hinge", w, idx, val, y)
    np.testing.assert_allclose(np.asarray(dist), [0.0, 1.0])
    assert reference.kink_distance("logistic", w, idx, val, y) is None
