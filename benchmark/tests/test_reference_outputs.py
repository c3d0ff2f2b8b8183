"""`reference_outputs`: at one output it is `reference.py`; its columns do
not couple; it imports nothing from the program."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, reference_outputs
from benchmark.harness import ROOT

D, N, P, C = 200, 96, 5, 7
LAM, LR = 1e-3, 0.1


def _problem(seed=0):
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, D, (N, P)), jnp.int32)
    val = jnp.asarray(rng.normal(size=(N, P)), jnp.float32)
    y = jnp.asarray(rng.choice([-1, 1], (N, C)), jnp.int8)
    w = jnp.asarray(rng.normal(size=(D, C)) * 0.3, jnp.float32)
    return idx, val, y, w


def test_it_imports_nothing_from_the_program_or_the_flat_reference():
    with open(os.path.join(ROOT, "benchmark", "reference_outputs.py")) as f:
        imports = [line for line in f if line.startswith(("import ", "from "))]
    assert sorted(imports) == ["from __future__ import annotations\n", "import jax\n",
                               "import jax.numpy as jnp\n", "import numpy as np\n"]


@pytest.mark.parametrize("loss", ["hinge", "logistic"])
@pytest.mark.parametrize("reg", ["l2", "none"])
def test_every_column_is_the_flat_reference_on_that_columns_labels(loss, reg):
    idx, val, y, w = _problem()
    batches = [(idx[r], val[r], y[r]) for r in np.arange(N).reshape(4, -1)]
    got = np.asarray(reference_outputs.sync_step(loss, reg, w, batches, LAM, LR))
    for c in range(C):
        flat = [(i, v, l[:, c]) for i, v, l in batches]
        np.testing.assert_array_equal(
            got[:, c], np.asarray(reference.sync_step(loss, reg, w[:, c], flat, LAM, LR)))
    losses, hits = [], []
    for c in range(C):
        objective, acc = reference.evaluate(loss, w[:, c], idx, val, y[:, c], LAM, block=32)
        losses.append(objective)
        hits.append(acc)
    objective, acc = reference_outputs.evaluate(loss, w, idx, val, y, LAM, block=32)
    np.testing.assert_allclose(objective, np.sum(losses), rtol=1e-6)  # the SUM over outputs
    np.testing.assert_allclose(acc, np.mean(hits), rtol=1e-6)  # over (row, output) pairs


def test_dense_rows_and_padding_and_the_kink():
    idx, val, y, w = _problem(1)
    x = jnp.zeros((N, D), jnp.float32).at[jnp.arange(N)[:, None], idx].add(val)
    np.testing.assert_allclose(np.asarray(reference_outputs.margins(w, None, x)),
                               np.asarray(reference_outputs.margins(w, idx, val)),
                               rtol=1e-5, atol=1e-6)
    # a label 0 is padding: no loss, no hit, no gradient
    y0 = y.at[:8].set(0)
    a = reference_outputs.evaluate("hinge", w, idx[8:], val[8:], y[8:], LAM, block=8)
    b = reference_outputs.evaluate("hinge", w, idx, val, y0, LAM, block=8)
    np.testing.assert_allclose(a, b, rtol=1e-6)
    g0 = reference_outputs.worker_grad("hinge", "none", w, idx, val, y0, LAM)
    g1 = reference_outputs.worker_grad("hinge", "none", w, idx[8:], val[8:], y[8:], LAM)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), rtol=1e-6, atol=1e-6)
    dist = np.asarray(reference_outputs.kink_distance("hinge", w, idx, val, y))
    assert dist.shape == (N, C) and (dist >= 0).all() and (dist <= 1 + 1e-6).all()
    assert reference_outputs.kink_distance("logistic", w, idx, val, y) is None
    with pytest.raises(ValueError, match="no reference for regularizer"):
        reference_outputs.regularize("dim_sparsity", w, w, LAM)
