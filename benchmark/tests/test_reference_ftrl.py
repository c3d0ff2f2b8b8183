"""`reference_ftrl` against a numpy float64 transcription of McMahan et
al.'s Algorithm 1 (the closed form, one synchronous step, the objective),
and the count behind `ftrl_step_roofline` by hand at the cell's shape."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import algorithmic_ftrl, peaks, reference, reference_ftrl

D, B, P, WORKERS = 300, 16, 5, 4
ALPHA, BETA, L1, L2 = 0.2, 1.0, 0.03, 1e-3


def _closed64(z, n):
    w = -(z - np.sign(z) * L1) / ((BETA + np.sqrt(n)) / ALPHA + L2)
    return np.where(np.abs(z) <= L1, 0.0, w)


def _case(seed=0):
    rng = np.random.default_rng(seed)
    z = (rng.normal(size=D) * 0.1).astype(np.float32)
    n = rng.uniform(0, 5, D).astype(np.float32)
    n[:40] = 0.0  # never touched: z there is 0 too
    z[:40] = 0.0
    batches = []
    for _ in range(WORKERS):
        idx = rng.integers(40, D - 60, (B, P)).astype(np.int32)  # the last 60 in no row
        idx[:, 0] = 50  # one id in every row
        val = rng.normal(size=(B, P)).astype(np.float32) * 0.5
        y = rng.choice([-1, 1], B).astype(np.int32)
        batches.append((idx, val, y))
    return z, n, batches


def _step64(z, n, batches):
    z, n = z.astype(np.float64), n.astype(np.float64)
    w = _closed64(z, n)
    g = np.zeros(D)
    for idx, val, y in batches:
        val = val.astype(np.float64)
        m = (val * w[idx]).sum(axis=1)
        c = -y / (1.0 + np.exp(y * m))
        np.add.at(g, idx.reshape(-1), (c[:, None] * val).reshape(-1))
    g /= len(batches)
    sigma = (np.sqrt(n + g * g) - np.sqrt(n)) / ALPHA
    moved = g != 0
    return np.where(moved, z + g - sigma * w, z), np.where(moved, n + g * g, n), g


def test_the_closed_form_is_the_float64_one_and_zero_inside_l1():
    z, n, _ = _case()
    w = np.asarray(reference_ftrl.weights(z, n, ALPHA, BETA, L1, L2))
    want = _closed64(z.astype(np.float64), n.astype(np.float64))
    np.testing.assert_allclose(w, want, rtol=2e-6, atol=1e-9)
    inside = np.abs(z) <= np.float32(L1)
    assert inside.any() and (~inside).any()
    assert (w[inside] == 0).all() and (w[~inside] != 0).all()
    assert (np.sign(w[~inside]) == -np.sign(z[~inside])).all()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_one_step_is_the_float64_one(seed):
    z, n, batches = _case(seed)
    zr, nr, g = (np.asarray(a) for a in reference_ftrl.sync_step(
        "logistic", z, n, batches, ALPHA, BETA, L1, L2))
    z64, n64, g64 = _step64(z, n, batches)
    np.testing.assert_allclose(g, g64, rtol=1e-5, atol=1e-7)
    for got, want, was in ((zr, z64, z), (nr, n64, n)):
        moved = got - was
        assert np.linalg.norm(moved - (want - was)) <= 1e-5 * np.linalg.norm(want - was)
    # every coordinate no entry names keeps its state bit for bit
    untouched = g64 == 0
    assert untouched[:40].all() and untouched[-60:].all()
    assert np.array_equal(zr[untouched], z[untouched]) and np.array_equal(nr[untouched], n[untouched])


def test_the_objective_is_the_mean_loss_and_the_penalty():
    z, n, batches = _case()
    w = np.asarray(reference_ftrl.weights(z, n, ALPHA, BETA, L1, L2))
    idx, val, y = (jnp.asarray(np.concatenate([b[k] for b in batches])) for k in range(3))
    obj, acc, mean_loss, pen = reference_ftrl.evaluate("logistic", w, idx, val, y, L1, L2)
    plain, plain_acc = reference.evaluate("logistic", w, idx, val, y, 0.0)
    w64 = w.astype(np.float64)
    assert pen == pytest.approx(L1 * np.abs(w64).sum() + 0.5 * L2 * (w64 ** 2).sum(), rel=1e-12)
    assert (mean_loss, acc) == (plain, plain_acc) and obj == mean_loss + pen


def test_the_step_count_by_hand_at_the_cells_shape():
    # 4 workers x 100 rows of 11 entries: rows 400 x (88 + 4), 4,400 entries
    # x (16 margins + 24 update)
    assert algorithmic_ftrl.step_bytes(100, 4, 11) == 36_800 + 70_400 + 105_600 == 212_800
    assert algorithmic_ftrl.step_bytes(200, 4, 11) == 2 * 212_800
    seconds = algorithmic_ftrl.least_seconds(212_800, peaks.peaks_for("TPU v5 lite"))
    assert seconds == pytest.approx(212_800 / 819e9)  # 0.26 us: a step of 100 us reads 0.26 %
