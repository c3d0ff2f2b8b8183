"""`reference_lists` against a numpy float64 form of the same equations,
and against `reference_outputs` on the same labels as a dense array."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_lists, reference_outputs

D, C, B, P, LW = 120, 9, 16, 5, 3
LAM, LR = 1e-3, 0.05
LOSSES = ("squared_hinge", "hinge", "logistic", "least_squares")


def _case(seed=0, workers=3):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(D, C)) * 0.5
    batches = []
    for _ in range(workers):
        idx = rng.integers(0, D, size=(B, P)).astype(np.int32)
        val = rng.normal(size=(B, P)).astype(np.float32)
        lists = np.full((B, LW), -1, np.int32)
        for b in range(B):
            ids = np.sort(rng.choice(C, size=rng.integers(0, LW + 1), replace=False))
            lists[b, :len(ids)] = ids
        batches.append((idx, val, lists))
    return w.astype(np.float32), batches


def _dense(lists):
    y = -np.ones((len(lists), C))
    for b, row in enumerate(lists):
        y[b, row[row >= 0]] = 1.0
    return y


def _coeff64(loss, m, y):
    if loss == "squared_hinge":
        return -2.0 * y * np.maximum(0.0, 1.0 - y * m)
    if loss == "hinge":
        return np.where(y * m < 0, 0.0, y)
    if loss == "logistic":
        return -y / (1.0 + np.exp(y * m))
    return 2.0 * (m - y)


def _loss64(loss, m, y):
    if loss == "squared_hinge":
        return np.maximum(0.0, 1.0 - y * m) ** 2
    if loss == "hinge":
        return np.maximum(0.0, 1.0 + y * np.sign(m))
    if loss == "logistic":
        return np.logaddexp(0.0, -y * m)
    return (m - y) ** 2


def _step64(loss, reg, w, batches):
    w = w.astype(np.float64)
    total = np.zeros_like(w)
    for idx, val, lists in batches:
        m = np.einsum("bp,bpc->bc", val.astype(np.float64), w[idx])
        c = _coeff64(loss, m, _dense(lists))
        g = np.zeros_like(w)
        np.add.at(g, idx, val.astype(np.float64)[:, :, None] * c[:, None, :])
        total += g + (2.0 * LAM * w if reg == "l2" else 0.0)
    return w - LR * total / len(batches)


def test_expand_is_plus_one_at_the_listed_ids_and_zero_on_a_padding_row():
    lists = np.asarray([[0, 3, -1], [-1, -1, -1], [-2, -2, -2], [8, -1, -1]], np.int32)
    y = np.asarray(reference_lists.expand(lists, C))
    want = -np.ones((4, C))
    want[0, [0, 3]] = 1.0
    want[3, 8] = 1.0
    want[2] = 0.0
    np.testing.assert_array_equal(y, want)


@pytest.mark.parametrize("reg", ["l2", "none"])
@pytest.mark.parametrize("loss", LOSSES)
def test_a_sync_step_is_the_float64_form(loss, reg):
    w, batches = _case()
    got = np.asarray(reference_lists.sync_step(
        loss, reg, jnp.asarray(w), [tuple(jnp.asarray(a) for a in b) for b in batches], LAM, LR))
    want = _step64(loss, reg, w, batches)
    np.testing.assert_allclose(got - w, want - w, rtol=2e-5, atol=1e-7)


@pytest.mark.parametrize("loss", LOSSES)
def test_the_evaluation_is_the_float64_form(loss):
    w, batches = _case(seed=1, workers=4)
    idx, val, lists = (np.concatenate([b[k] for b in batches]) for k in range(3))
    lists[5] = -2  # a padding row: out of every sum
    got_loss, got_acc = reference_lists.evaluate(
        loss, w, jnp.asarray(idx), jnp.asarray(val), jnp.asarray(lists), LAM, block=16)
    keep = lists[:, 0] != -2
    m = np.einsum("bp,bpc->bc", val.astype(np.float64), w.astype(np.float64)[idx])[keep]
    y = _dense(lists[keep])
    want_loss = LAM * np.sum(w.astype(np.float64) ** 2) + _loss64(loss, m, y).sum(axis=1).mean()
    pred = {"hinge": -np.sign(m), "least_squares": m}.get(loss, np.where(m >= 0, 1.0, -1.0))
    assert got_loss == pytest.approx(want_loss, rel=2e-6)
    assert got_acc == pytest.approx((pred == y).mean(), abs=1e-9)


@pytest.mark.parametrize("loss", ["hinge", "logistic"])
def test_on_dense_labels_it_is_reference_outputs(loss):
    w, batches = _case(seed=2)
    as_jnp = [tuple(jnp.asarray(a) for a in b) for b in batches]
    dense = [(i, v, jnp.asarray(_dense(np.asarray(l)), jnp.int8)) for i, v, l in as_jnp]
    np.testing.assert_array_equal(
        np.asarray(reference_lists.sync_step(loss, "l2", jnp.asarray(w), as_jnp, LAM, LR)),
        np.asarray(reference_outputs.sync_step(loss, "l2", jnp.asarray(w), dense, LAM, LR)))


def test_a_range_of_labels_is_that_ranges_columns():
    """The share property: a step on the labels [3, 7) is columns 3..6 of
    the step on all nine."""
    w, batches = _case(seed=3)

    def held(lists, first, end):
        out = np.full_like(lists, -1)
        for b, row in enumerate(lists):
            ids = row[(row >= first) & (row < end)] - first
            out[b, :len(ids)] = ids
        return out

    whole = np.asarray(reference_lists.sync_step(
        "squared_hinge", "l2", jnp.asarray(w),
        [tuple(jnp.asarray(a) for a in b) for b in batches], LAM, LR))
    part = np.asarray(reference_lists.sync_step(
        "squared_hinge", "l2", jnp.asarray(w[:, 3:7]),
        [(jnp.asarray(i), jnp.asarray(v), jnp.asarray(held(l, 3, 7))) for i, v, l in batches],
        LAM, LR))
    np.testing.assert_allclose(part, whole[:, 3:7], rtol=1e-6, atol=1e-8)
