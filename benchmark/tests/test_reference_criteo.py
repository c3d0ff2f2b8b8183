"""`reference.py` at `criteo-logistic`'s shape: 1,000,000 features, 39
entries a row of value 1/sqrt(39), one id in most rows of a step beside
thousands of singletons.  The sync step against float64 numpy, the program's
'gather' family against it at the configuration's own tolerance, and the
reading the tolerance exists to refuse: the same step with its operands
rounded to bf16."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, reference
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.ops.sparse import SparseBatch

D, P, K, B = 1_000_000, 39, 4, 100


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(harness.ROOT, "benchmark/configs/criteo-logistic.json")) as f:
        return json.load(f)


def _step_rows(seed: int = 0):
    """K batches of B rows: field 0 has 3 values, field 1 has 10, the rest
    are spread over the whole feature space."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, D, (K, B, P)).astype(np.int32)
    idx[..., 0] = 777_000 + rng.integers(0, 3, (K, B))
    idx[..., 1] = 31 + rng.integers(0, 10, (K, B))
    val = np.full((K, B, P), 1.0 / np.sqrt(P), np.float32)
    y = rng.choice([-1, 1], (K, B), p=[0.74, 0.26]).astype(np.int32)
    w = (rng.normal(size=D) * 0.1).astype(np.float32)
    return idx, val, y, w


def _float64_step(idx, val, y, w, lam, lr):
    w64 = w.astype(np.float64)
    total = np.zeros(D)
    for k in range(K):
        m = (val[k].astype(np.float64) * w64[idx[k]]).sum(axis=1)
        c = -y[k] / (1.0 + np.exp(y[k] * m))
        g = np.zeros(D)
        np.add.at(g, idx[k].reshape(-1), (c[:, None] * val[k]).reshape(-1))
        total += g + 2.0 * lam * w64
    return w64 - lr * total / K


def _reference_step(idx, val, y, w, lam, lr, rounded=jnp.float32):
    def r(a):
        return jnp.asarray(a).astype(rounded).astype(jnp.float32)

    batches = [(jnp.asarray(idx[k]), r(val[k]), jnp.asarray(y[k])) for k in range(K)]
    return np.asarray(reference.sync_step("logistic", "l2", r(w), batches, lam, lr)), np.asarray(r(w))


def test_the_sync_step_at_this_shape_is_the_float64_one(config):
    idx, val, y, w = _step_rows()
    lam, lr = float(config["lam"]), float(config["learning_rate"])
    got, _ = _reference_step(idx, val, y, w, lam, lr)
    want = _float64_step(idx, val, y, w, lam, lr)
    # float32 rounds w' - w by eps * |w| / |update| (5e-6 at |w| = 0.1): inside the limit
    assert harness.rel_err(got - w, want - w) <= float(config["tolerance"]["step_rel"])
    hot = np.bincount(idx[..., 0].reshape(-1) - 777_000)
    assert hot.min() >= 100  # one id in a hundred and more of the step's 400 rows


def test_the_gather_family_passes_the_configurations_step_tolerance(config):
    idx, val, y, w = _step_rows(seed=1)
    lam, lr = float(config["lam"]), float(config["learning_rate"])
    model = make_model("logistic", lam, D, regularizer="l2")
    w2 = model.to_layout(jnp.asarray(w), "gather")
    g = model.grad_workers(w2, jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y),
                           kernel="gather")
    got = w - lr * np.asarray(model.from_layout(g, "gather")) / K
    want, _ = _reference_step(idx, val, y, w, lam, lr)
    assert harness.rel_err(got - w, want - w) <= float(config["tolerance"]["step_rel"])


@pytest.mark.parametrize("rounded", [jnp.bfloat16, jnp.float16])
def test_operands_rounded_one_precision_lower_fail_the_step_tolerance(config, rounded):
    idx, val, y, w = _step_rows(seed=2)
    lam, lr = float(config["lam"]), float(config["learning_rate"])
    exact, _ = _reference_step(idx, val, y, w, lam, lr)
    low, w_low = _reference_step(idx, val, y, w, lam, lr, rounded)
    err = harness.rel_err(low - w_low, exact - w)
    assert err > 5 * float(config["tolerance"]["step_rel"]), err


def test_the_merged_scatter_sums_what_k_replies_sum(config):
    idx, val, y, w = _step_rows(seed=3)
    lam = float(config["lam"])
    model = make_model("logistic", lam, D, regularizer="l2")
    w2 = model.to_layout(jnp.asarray(w), "gather")
    merged = model.grad_workers(w2, jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y),
                                kernel="gather")
    apart = sum(model.grad(w2, SparseBatch(jnp.asarray(idx[k]), jnp.asarray(val[k])),
                           jnp.asarray(y[k]), kernel="gather") for k in range(K))
    np.testing.assert_allclose(np.asarray(merged), np.asarray(apart), rtol=1e-5, atol=1e-7)
