"""`reference.py` at `kdd2012-logistic`'s shape: 54,686,452 features, 11
entries a row of value 1/sqrt(11), three 3-valued fields that put one id
into about 130 of a step's 400 rows beside thousands of singletons.  The
sync step against float64 numpy; the program's sparse step (the entries
scattered into the weights, `BoundSync._sparse_step`) against the reference
at the configuration's own tolerance; the reading the tolerance exists to
refuse (the same step with its operands rounded to bf16); and the count
behind `entry_step_roofline`."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import algorithmic_entries, algorithmic_sparse, harness, peaks, reference
from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine

D, P, K, B = 54_686_452, 11, 4, 100


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(harness.ROOT, "benchmark/configs/kdd2012-logistic.json")) as f:
        return json.load(f)


def _rows(n, seed=0):
    """Rows as the generator lays them out: the last three fields have 3
    values each, the rest are spread over the feature space."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, D - 9, (n, P)).astype(np.int32)
    for f in range(3):
        idx[:, P - 3 + f] = D - 9 + 3 * f + rng.integers(0, 3, n)
    val = np.full((n, P), 1.0 / np.sqrt(P), np.float32)
    y = rng.choice([-1, 1], n, p=[0.956, 0.044]).astype(np.int32)
    return idx, val, y


def _weights(idx, seed=1):
    """w = 0 but on the features the rows hold: what a fit's weights are at
    this shape (a chip's split touches a fraction of the 54.7 M)."""
    w = np.zeros(D, np.float32)
    at = np.unique(idx)
    w[at] = np.random.default_rng(seed).normal(size=len(at)) * 0.1
    w[D - 9:] -= 3.0  # the few-valued fields' weights are large
    return w


def _float64_step(batches, w, lam, lr):
    w64 = w.astype(np.float64)
    total = np.zeros(D)
    for idx, val, y in batches:
        m = (val.astype(np.float64) * w64[idx]).sum(axis=1)
        c = -y / (1.0 + np.exp(y * m))
        np.add.at(total, idx.reshape(-1), (c[:, None] * val).reshape(-1))
        total += 2.0 * lam * w64
    return w64 - lr * total / len(batches)


def _reference_step(batches, w, lam, lr, rounded=jnp.float32):
    def r(a):
        return jnp.asarray(a).astype(rounded).astype(jnp.float32)

    given = [(jnp.asarray(i), r(v), jnp.asarray(y)) for i, v, y in batches]
    return np.asarray(reference.sync_step("logistic", "l2", r(w), given, lam, lr)), np.asarray(r(w))


def _step_batches(seed):
    idx, val, y = _rows(K * B, seed)
    return [(idx[k * B:(k + 1) * B], val[k * B:(k + 1) * B], y[k * B:(k + 1) * B])
            for k in range(K)], _weights(idx, seed + 1)


def test_the_sync_step_at_this_shape_is_the_float64_one(config):
    batches, w = _step_batches(0)
    lam, lr = float(config["lam"]), float(config["learning_rate"])
    got, _ = _reference_step(batches, w, lam, lr)
    want = _float64_step(batches, w, lam, lr)
    assert harness.rel_err(got - w, want - w) <= float(config["tolerance"]["step_rel"])
    hot = np.bincount(np.concatenate([b[0][:, -1] for b in batches]) - (D - 3))
    assert hot.min() >= 100  # one id in a hundred and more of the step's 400 rows


def test_the_sparse_step_passes_the_configurations_step_tolerance(config):
    """Through the program's own engine, as the driver's step check runs it:
    4,096 probe rows, one `BoundSync.step`, the rows read off the sampler."""
    lam, lr = float(config["lam"]), float(config["learning_rate"])
    idx, val, y = _rows(4096, seed=2)
    w = _weights(idx, seed=3)
    model = make_model("logistic", lam, D, regularizer="l2")
    bound = SyncEngine(model, make_mesh(1), B, lr, virtual_workers=K).bind(
        Dataset(idx, val, y, D))
    assert bound.kernel == "gather" and bound.update_sparse
    key = jax.random.PRNGKey(5)
    drawn = np.asarray(jax.jit(lambda k: bound._sample_ids(k, jnp.int32(0)))(
        jax.random.fold_in(key, 0)))
    got = np.asarray(bound.step(jnp.asarray(w), key))
    want, _ = _reference_step([(idx[r], val[r], y[r]) for r in drawn], w, lam, lr)
    assert harness.rel_err(got - w, want - w) <= float(config["tolerance"]["step_rel"])


def test_operands_rounded_to_bf16_fail_the_step_tolerance(config):
    """The precision the chip's matmuls would bring: bf16 rounds 1/sqrt(11)
    by 2.4e-3.  (f16 reads 3.05e-4 .. 3.67e-4 on the chip, just over the
    limit: it is set for bf16.)"""
    batches, w = _step_batches(4)
    lam, lr = float(config["lam"]), float(config["learning_rate"])
    exact, _ = _reference_step(batches, w, lam, lr)
    low, w_low = _reference_step(batches, w, lam, lr, jnp.bfloat16)
    err = harness.rel_err(low - w_low, exact - w)
    assert err > 5 * float(config["tolerance"]["step_rel"]), err


def test_the_entries_count_has_no_term_in_the_feature_count():
    n = algorithmic_entries.step_bytes(batch=B, workers_on_device=K, nnz=P)
    entries = K * B * P
    assert n == K * B * (8 * P + 4) + 12 * entries + 16 * entries == 160_000
    # what `algorithmic_sparse.step_bytes` adds for "w read and written once"
    with_w = algorithmic_sparse.step_bytes(B, K, D, P)
    assert with_w - n == 8 * D
    row = peaks.peaks_for("TPU v5 lite")
    least = algorithmic_entries.least_seconds(n, row)
    assert least == pytest.approx(160_000 / 819e9)  # 0.195 us
    # that term alone is 0.53 ms of "least time": more than a sparse step takes
    assert algorithmic_sparse.least_seconds(with_w, row) > 5e-4
    assert 100 * least / 60e-6 < 1  # a 60 us step reads a third of a percent


class _Ctx:
    peaks = peaks.peaks_for("TPU v5 lite")


class _Run:
    ctx = _Ctx()
    engine = {"batch_size": B, "virtual_workers": K, "n_features": D,
              "row_width": P, "dense": False}
    trace = {"worst_device": "TPU:0",
             "devices": {"TPU:0": {"program": {"step": {"seconds": 65e-6, "steps": 10}}}}}
    trace_path = None


def test_the_new_readers_return_a_reading_or_nothing_and_never_raise():
    from benchmark.layer_metrics import entry_step_roofline, update_us_per_step

    share = entry_step_roofline.read(_Run())
    assert share == pytest.approx(100 * (160_000 / 819e9) / 65e-6)
    assert 0 < share < 100
    assert update_us_per_step.read(_Run()) is None  # no trace file: nothing to read

    class Dense(_Run):
        engine = dict(_Run.engine, dense=True)

    class NoTrace(_Run):
        trace = None

    class NoProgram(_Run):
        trace = {"worst_device": "TPU:0", "devices": {"TPU:0": {}}}

    for run in (Dense(), NoTrace(), NoProgram()):
        assert entry_step_roofline.read(run) is None
        assert update_us_per_step.read(run) is None
