"""The sync step that never builds a gradient (`BoundSync._sparse_step`,
the one rule `kernels.sparse_update`; PERF.md section 6, PR 30).

Its plain reference is the dense update itself: `benchmark/reference.py`'s
`sync_step` for one step, the same equations in numpy float64 over many
(the yardstick over many steps is float64 and not the dense float32
program, which drops the `l2` term on every coordinate a step does not
touch).  Small shapes on the CPU, rows with duplicate ids inside a step:
one id in every row, another in a third of them, a pad entry (id 0,
value 0)."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, reference
from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.ops import kernels
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine
from distributed_sgd_tpu.utils import metrics as metrics_mod

D, P, N = 6000, 11, 4096
LR = 0.1
EVERY_ROW, PAD = 5, 0
UNUSED = 500  # the last features are in no row


def _rows(seed=0, n=N):
    """`n` rows of P one-hot entries of value 1/sqrt(P): column 0 one of 3
    ids, column 1 the SAME id in every row, the last column a pad; the
    last `UNUSED` features are in no row."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(6, D - UNUSED, (n, P)).astype(np.int32)
    idx[:, 0] = rng.integers(1, 4, n)
    idx[:, 1] = EVERY_ROW
    idx[:, -1] = PAD
    val = np.full((n, P), 1.0 / np.sqrt(P), np.float32)
    val[:, -1] = 0.0
    y = rng.choice([-1, 1], n, p=[0.8, 0.2]).astype(np.int32)
    return Dataset(idx, val, y, D)


def _weights(seed=1, scale=0.1):
    return (np.random.default_rng(seed).normal(size=D) * scale).astype(np.float32)


@pytest.fixture
def everywhere(monkeypatch):
    """The rule without its floor on the feature count: the tests' shapes
    are small."""
    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)


def _bind(data, lam, devices=1, workers=4, batch=25, steps=None, reg="l2", lr=LR, **kw):
    model = make_model("logistic", lam, D, regularizer=reg)
    return SyncEngine(model, make_mesh(devices), batch, lr, kernel="gather",
                      virtual_workers=workers, **kw).bind(data, steps)


def _draws(bound, key, steps):
    """ids [steps, all workers, batch] into the whole split, device by
    device as the program's own sampler draws them."""
    per_device = []
    for d in range(bound.n_workers):
        kd = jax.random.fold_in(key, d)
        ids = jax.jit(jax.vmap(lambda s, kd=kd: bound._sample_ids(kd, s)))(jnp.arange(steps))
        per_device.append(np.asarray(ids) + d * bound.shard_n)
    return np.concatenate(per_device, axis=1)


def _float64_steps(data, draws, w, lam, lr, reg="l2"):
    """The reference equations in float64: every worker's reply is its
    regularised batch SUM, the update w - lr * mean over all workers."""
    w = w.astype(np.float64)
    idx, val, y = data.indices, data.values.astype(np.float64), data.labels
    n = draws.shape[1]
    for step in draws:
        rows = step.reshape(-1)
        m = (val[rows] * w[idx[rows]]).sum(axis=1)
        c = -y[rows] / (1.0 + np.exp(y[rows] * m))
        g = np.zeros(D)
        np.add.at(g, idx[rows].reshape(-1), (c[:, None] * val[rows]).reshape(-1))
        if reg == "l2":
            g += 2.0 * lam * n * w
        w = w - lr * g / n
    return w


# -- the rule ---------------------------------------------------------------------

@pytest.mark.parametrize("kernel,reg,optimizer,decay,features,said", [
    ("gather", "l2", "sgd", 3e-8, 54_686_452, True),     # kdd2012-logistic
    ("gather", "none", "sgd", 0.0, 54_686_452, True),
    ("gather", "l2", "sgd", 1.8e-8, 1_000_000, False),   # criteo-logistic: under the floor
    ("gather", "dim_sparsity", "sgd", 0.0, 54_686_452, False),
    ("gather", "l2", "optax", 3e-8, 54_686_452, False),   # an optax optimizer
    ("gather", "l2", "sgd", 1.0, 54_686_452, False),     # a step that would flip w's sign
    ("mxu", "l2", "sgd", 3e-8, 54_686_452, False),
    ("scalar", "l2", "sgd", 3e-8, 54_686_452, False),
    ("dense", "l2", "sgd", 3e-8, 54_686_452, False),
])
def test_one_rule_says_which_bindings_scatter_into_the_weights(
        kernel, reg, optimizer, decay, features, said):
    assert kernels.sparse_update(kernel, reg, optimizer, decay, features) is said


def test_the_floor_lies_between_the_two_cells_feature_counts():
    assert 1_000_000 < kernels.SPARSE_UPDATE_MIN_FEATURES <= 54_686_452


@pytest.mark.parametrize("kw,sparse", [
    ({}, True),
    ({"reg": "none"}, True),
    ({"optimizer": "momentum"}, False),
    ({"optimizer": "adam"}, False),
])
def test_a_binding_asks_the_rule_once_and_counts_it(everywhere, kw, sparse):
    counter = metrics_mod.counter("bind.update.sparse")
    before = counter.value
    bound = _bind(_rows(), 1e-4, **kw)
    assert (bound.plan.update == "sparse") is sparse
    assert counter.value - before == int(sparse)
    bound.step(jnp.zeros((D,), jnp.float32), jax.random.PRNGKey(0))
    assert counter.value - before == int(sparse)  # a binding, not a trace or a run


def test_under_the_floor_the_family_keeps_the_dense_step():
    assert _bind(_rows(), 1e-4).plan.update == "dense"


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The platform probe answers "a TPU" and Pallas runs the kernel in its
    TPU interpret mode: the program a chip runs, on the CPU."""
    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops import mxu

    monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None: True)
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.mark.parametrize("floor,said", [
    (0, "update=sparse scatter=words"), (None, "update=dense scatter=words")])
def test_the_train_split_record_says_the_update(floor, said, caplog, monkeypatch):
    from distributed_sgd_tpu.core.trainer import SyncTrainer

    if floor is not None:
        monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", floor)
    rows = _rows()
    model = make_model("logistic", 1e-4, D, regularizer="l2")
    with caplog.at_level(logging.INFO, logger="dsgd.trainer"):
        SyncTrainer(model, make_mesh(1), 25, LR, kernel="gather", virtual_workers=4).fit(
            rows, rows, max_epochs=1)
    record = next(r.getMessage() for r in caplog.records
                  if r.getMessage().startswith("train split:"))
    assert said in record and "kernel=gather" in record and "margins=merged" in record


@pytest.mark.parametrize("kw,rows", [({}, True), ({"optimizer": "momentum"}, False)])
def test_on_a_tpu_a_sparse_binding_writes_rows_and_counts_it(everywhere, as_on_a_tpu, kw, rows):
    counter = metrics_mod.counter("bind.scatter.rows")
    before = counter.value
    bound = _bind(_rows(n=256), 1e-4, **kw)
    assert (bound.plan.update, bound.plan.scatter) == (("sparse", "rows") if rows else ("dense", "words"))
    assert counter.value - before == int(rows)
    bound.step(jnp.zeros((D,), jnp.float32), jax.random.PRNGKey(0))
    assert counter.value - before == int(rows)  # a binding, not a trace or a run


def test_off_the_tpu_no_binding_counts_the_kernel(everywhere):
    counter = metrics_mod.counter("bind.scatter.rows")
    before = counter.value
    bound = _bind(_rows(n=256), 1e-4)
    assert (bound.plan.update, bound.plan.scatter) == ("sparse", "words")
    assert counter.value == before


def test_on_a_tpu_the_train_split_record_says_rows(everywhere, as_on_a_tpu, caplog):
    from distributed_sgd_tpu.core.trainer import SyncTrainer

    rows = _rows(n=256)
    model = make_model("logistic", 1e-4, D, regularizer="l2")
    with caplog.at_level(logging.INFO, logger="dsgd.trainer"):
        SyncTrainer(model, make_mesh(1), 25, LR, kernel="gather", virtual_workers=4).fit(
            rows, rows, max_epochs=1)
    record = next(r.getMessage() for r in caplog.records
                  if r.getMessage().startswith("train split:"))
    assert "update=sparse scatter=rows" in record


# -- the scatter alone: a step's entries summed by row, each row written once --------

SMALL_ROWS = 40  # 5,120 features


def _case(name):
    """(ids, updates) of 4,400 entries into `SMALL_ROWS` x 128 weights."""
    rng = np.random.default_rng(31)
    last = SMALL_ROWS * 128 - 1
    ids = rng.integers(0, last + 1, 4400).astype(np.int32)
    upd = (rng.normal(size=4400) * 1e-3).astype(np.float32)
    if name == "hot":  # one id in 200 of 400 rows of 11 entries
        ids[:2200:11] = 777
    elif name == "lanes":  # two lanes of one row, nothing else in it
        ids = np.where(ids // 128 == 9, ids + 128, ids)
        ids[:2] = 9 * 128 + 3, 9 * 128 + 100
    elif name == "pads":  # a tenth of the entries are the pad
        ids[::10], upd[::10] = 0, 0.0
    elif name == "last_row":  # the last weight row, its last word too
        ids[:300] = rng.integers(last - 127, last + 1, 300)
        ids[300] = last
    elif name == "one_row":  # every entry in ONE row: a run over all 35 chunks
        ids = (5 * 128 + rng.integers(0, 128, 4400)).astype(np.int32)
    elif name == "few":  # fewer rows than the ring has semaphores, T no whole chunk
        ids, upd = ids[:37] // 128 * 128, upd[:37]
    return ids, upd


def _float64_scatter(w2, ids, upd):
    want = w2.astype(np.float64).reshape(-1)
    np.add.at(want, ids, upd.astype(np.float64))
    return want.reshape(w2.shape)


CASES = ["hot", "lanes", "pads", "last_row", "one_row", "few"]


@pytest.mark.parametrize("case", CASES)
def test_scatter_into_is_the_float64_scatter_add(case):
    from distributed_sgd_tpu.ops import gather

    ids, upd = _case(case)
    w2 = np.random.default_rng(2).normal(size=(SMALL_ROWS, 128)).astype(np.float32) * 3.0
    got = np.asarray(jax.jit(gather.scatter_into)(jnp.asarray(w2), ids, upd))
    want = _float64_scatter(w2, ids, upd)
    # float32's rounding of the new weight and of a row's sum, no more
    np.testing.assert_allclose(got, want, rtol=2e-7, atol=2e-9)
    untouched = np.setdiff1d(np.arange(SMALL_ROWS), ids // 128)
    np.testing.assert_array_equal(got[untouched], w2[untouched])
    if case == "hot":  # summed before it meets the weight: one rounding, not 200
        words = np.asarray(jnp.asarray(w2).reshape(-1).at[ids].add(upd)).reshape(w2.shape)
        assert abs(got[6, 9] - want[6, 9]) <= abs(words[6, 9] - want[6, 9])
        assert abs(got[6, 9] - want[6, 9]) <= 0.5 * np.spacing(np.float32(abs(want[6, 9]))) * 1.01


@pytest.mark.parametrize("ring", [1, 7, 32])
@pytest.mark.parametrize("case", CASES)
def test_the_kernel_writes_what_xla_writes(case, ring):
    """`_write_rows` in Pallas' TPU interpret mode against the XLA write
    of the same rows, bit for bit: rings the heads do not fill, fill once
    and wrap many times (1,2xx heads; `few` has 1..37), T over whole chunks."""
    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops import gather

    ids, upd = _case(case)
    w2 = jnp.asarray(np.random.default_rng(2).normal(size=(SMALL_ROWS, 128)), jnp.float32)
    want = np.asarray(jax.jit(gather.scatter_into)(w2, ids, upd))
    rows, head, total = jax.jit(gather._sum_by_row)(ids, upd)
    padded = np.append(ids, [0] * (-len(ids) % gather.CHUNK))  # the pad entry: feature 0
    assert int(np.sum(np.asarray(head))) == len(np.unique(padded // 128))
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(jax.jit(lambda w2: gather._write_rows(
            w2, rows, head, w2[rows] + total, ring=ring))(w2))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("case", ["hot", "few"])
def test_a_step_longer_than_scalar_memory_holds_is_written_in_blocks(case, monkeypatch):
    """More entries than `DMA_BLOCK`: one call of the kernel a block of
    positions, the heads spread over the first blocks, the last blocks
    empty."""
    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops import gather

    ids, upd = _case(case)
    w2 = jnp.asarray(np.random.default_rng(2).normal(size=(SMALL_ROWS, 128)), jnp.float32)
    want = np.asarray(jax.jit(gather.scatter_into)(w2, ids, upd))
    monkeypatch.setattr(gather, "DMA_BLOCK", 16)
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(jax.jit(lambda w2: gather.scatter_into(w2, ids, upd, "rows"))(w2))
    np.testing.assert_array_equal(got, want)


def test_the_sum_by_row_puts_a_rows_whole_run_at_its_head():
    from distributed_sgd_tpu.ops import gather

    ids, upd = _case("hot")
    rows, head, total = (np.asarray(a) for a in jax.jit(gather._sum_by_row)(ids, upd))
    assert len(rows) % gather.CHUNK == 0 and np.all(np.diff(rows) >= 0)
    assert head[0] and np.array_equal(head[1:], rows[1:] != rows[:-1])
    dense = _float64_scatter(np.zeros((SMALL_ROWS, 128), np.float32), ids, upd)
    np.testing.assert_allclose(total[head], dense[rows[head]], rtol=1e-6, atol=1e-9)
    # runs longer than a chunk exist here: the second product is exercised
    assert np.bincount(rows).max() > gather.CHUNK


def test_on_a_tpu_the_epoch_is_the_one_xla_writes(everywhere, monkeypatch):
    """One formulation, one write that differs: the epoch program with the
    kernel (interpreted) ends on the weights the XLA write ends on, bit for
    bit."""
    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops import mxu

    data, w, key = _rows(n=512), jnp.asarray(_weights()), jax.random.PRNGKey(3)
    want = np.asarray(_bind(data, 1e-4, steps=3).epoch(w, key))
    monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None: True)
    bound = _bind(data, 1e-4, steps=3)
    assert bound.plan.scatter == "rows"
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(bound.epoch(w, key))
    np.testing.assert_array_equal(got, want)


def test_the_hot_id_is_no_farther_from_the_reference_than_the_dense_steps(
        everywhere, monkeypatch):
    """An id in every row of the step: its ~100 increments are summed before
    they meet its weight, as the dense step's accumulator sums them."""
    data, key = _rows(), jax.random.PRNGKey(7)
    w = _weights()
    w[EVERY_ROW] = -5.75  # a hot weight of the cell's size
    sparse = _bind(data, 1e-4)
    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 10 * D)
    dense = _bind(data, 1e-4)
    assert (sparse.plan.update, dense.plan.update) == ("sparse", "dense")
    batches = [(jnp.asarray(data.indices[r]), jnp.asarray(data.values[r]),
                jnp.asarray(data.labels[r])) for r in _draws(sparse, key, 1)[0]]
    want = _float64_steps(data, _draws(sparse, key, 1), w, 1e-4, LR)
    ref = np.asarray(reference.sync_step("logistic", "l2", jnp.asarray(w), batches, 1e-4, LR))
    got_sparse = np.asarray(sparse.step(jnp.asarray(w), key))
    got_dense = np.asarray(dense.step(jnp.asarray(w), key))
    ulp = float(np.spacing(np.float32(5.75)))
    assert abs(got_sparse[EVERY_ROW] - want[EVERY_ROW]) <= max(
        abs(got_dense[EVERY_ROW] - want[EVERY_ROW]), 0.5 * ulp * 1.01)
    assert harness.rel_err(got_sparse - w, ref - w) <= 1e-6


# -- (a) one step against the plain reference --------------------------------------

@pytest.mark.parametrize("devices,workers", [(1, 1), (1, 4), (4, 1), (4, 4)])
@pytest.mark.parametrize("reg,lam", [("l2", 1e-4), ("l2", 0.0), ("none", 1e-4)])
def test_one_step_is_the_references(everywhere, devices, workers, reg, lam):
    data, w = _rows(), _weights()
    bound = _bind(data, lam, devices, workers, reg=reg)
    assert bound.plan.update == "sparse"
    key = jax.random.PRNGKey(7)
    got = np.asarray(bound.step(jnp.asarray(w), key))
    batches = [(jnp.asarray(data.indices[rows]), jnp.asarray(data.values[rows]),
                jnp.asarray(data.labels[rows])) for rows in _draws(bound, key, 1)[0]]
    assert len(batches) == devices * workers
    want = np.asarray(reference.sync_step("logistic", reg, jnp.asarray(w), batches, lam, LR))
    assert harness.rel_err(got - w, want - w) <= 2e-6
    # the id in every row moved, the pad's feature only by its regulariser
    assert got[EVERY_ROW] != w[EVERY_ROW]
    np.testing.assert_allclose(got[PAD], w[PAD] * (1 - 2 * LR * lam * (reg == "l2")), rtol=1e-6)


def test_four_devices_scatter_what_one_device_with_four_workers_scatters(everywhere):
    """K devices exchange their entries and every one scatters all of
    them: the same equations as K virtual workers on one device (other
    draws: the key folds the device in)."""
    data, w, key = _rows(), jnp.asarray(_weights()), jax.random.PRNGKey(3)
    one = _bind(data, 1e-4, 1, 4, steps=8)
    four = _bind(data, 1e-4, 4, 1, steps=8)
    a, b = np.asarray(one.epoch(w, key)), np.asarray(four.epoch(w, key))
    for bound, got in ((one, a), (four, b)):
        want = _float64_steps(data, _draws(bound, key, 8), np.asarray(w), 1e-4, LR)
        assert harness.rel_err(got - np.asarray(w), want - np.asarray(w)) <= 2e-6


# -- (b) 2,000 consecutive steps against float64 ------------------------------------

def test_over_2000_steps_the_sparse_step_is_nearer_float64_than_the_dense_step(
        everywhere, monkeypatch):
    """lambda = 1/n: c = 2 lr lam is 4.9e-8 a step, under half of float32's
    epsilon.  The dense float32 step's `w - lr (g + 2 lam K w) / n` rounds
    that away on every coordinate a step does not touch (or, where the
    mantissa is near 2, takes a whole ulp: twice the term); the sparse step
    carries it in a scalar and applies it."""
    steps, lam = 2000, 1.0 / N
    data, w, key = _rows(seed=4), _weights(seed=5), jax.random.PRNGKey(11)
    sparse = _bind(data, lam, steps=steps)
    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 10 * D)
    dense = _bind(data, lam, steps=steps)
    assert (sparse.plan.update, dense.plan.update) == ("sparse", "dense")
    got_sparse = np.asarray(sparse.epoch(jnp.asarray(w), key))
    got_dense = np.asarray(dense.epoch(jnp.asarray(w), key))
    draws = _draws(sparse, key, steps)
    np.testing.assert_array_equal(draws, _draws(dense, key, steps))
    want = _float64_steps(data, draws, w, lam, LR)

    def far(got, at=slice(None)):
        return float(np.linalg.norm((got - want)[at]) / np.linalg.norm(want - w))

    untouched = np.setdiff1d(np.arange(D), np.unique(data.indices[draws.reshape(-1)]))
    assert len(untouched) >= UNUSED
    print(f"distance from float64 over {steps} steps, / |w' - w|: sparse {far(got_sparse):.3e} "
          f"dense {far(got_dense):.3e}; on {len(untouched)} untouched coordinates: sparse "
          f"{far(got_sparse, untouched):.3e} dense {far(got_dense, untouched):.3e}")
    assert far(got_sparse) <= far(got_dense)
    assert far(got_sparse) <= 5e-6
    # the regulariser on coordinates no step touched: (1 - c)^steps, 9.8e-5 off 1
    shrink = (1.0 - 2.0 * LR * lam) ** steps
    kept = w[untouched].astype(np.float64) * shrink
    np.testing.assert_allclose(want[untouched], kept, rtol=1e-12)
    np.testing.assert_allclose(got_sparse[untouched], kept, rtol=3e-7)
    # the dense float32 step is several times farther there
    assert far(got_dense, untouched) > 3 * far(got_sparse, untouched)


# -- (c) the fold, no decay, the pad, the id in every row ----------------------------

@pytest.mark.parametrize("reg,lam,steps,folds", [
    ("l2", 0.05, 700, 7),      # c = 0.01: s falls by e^-7 inside one program
    ("l2", 0.5, 300, 33),      # c = 0.1: a fold every 9 steps, s by e^-31
    ("l2", 1.0 / N, 300, 0),   # lambda = 1/n: one fold, where the weights leave
    ("l2", 0.0, 300, 0),
    ("none", 0.05, 300, 0),
])
def test_many_steps_with_folds_are_the_float64_equations(everywhere, reg, lam, steps, folds):
    data, w, key = _rows(seed=6), _weights(seed=7), jax.random.PRNGKey(13)
    bound = _bind(data, lam, steps=steps, reg=reg)
    assert bound.plan.update == "sparse"
    assert (steps - 1) // bound._fold_span() >= folds
    got = np.asarray(bound.epoch(jnp.asarray(w), key))
    want = _float64_steps(data, _draws(bound, key, steps), w, lam, LR, reg)
    assert np.isfinite(got).all()
    assert harness.rel_err(got - w, want - w) <= 5e-6
    assert harness.rel_err(got, want) <= 5e-6
    # the id in every row and the pad's feature, each to float32
    np.testing.assert_allclose(got[[EVERY_ROW, PAD]], want[[EVERY_ROW, PAD]], rtol=2e-5)


def test_the_scale_is_never_a_float32_product():
    bound = _bind(_rows(), 1.5e-7)
    c = bound.plan.decay
    assert c == pytest.approx(3e-8)
    # float32's 1 - c is 1 - 2^-24: it would take TWICE the term a step
    assert (1.0 - float(np.float32(1.0) - np.float32(c))) / c > 1.9
    s = np.asarray(jax.jit(bound._scale)(jnp.int32(16_220)))
    np.testing.assert_allclose(s, (1.0 - bound.plan.decay) ** 16_220, rtol=1.2e-7)
    assert s < 1.0
    w2 = jnp.full((8, 128), 3.0, jnp.float32)
    np.testing.assert_allclose(np.asarray(bound._rescale(w2, 16_220)),
                               3.0 * (1.0 - bound.plan.decay) ** 16_220, rtol=1.2e-7)


def test_epochs_in_one_program_fold_as_single_epochs_do(everywhere, monkeypatch):
    """`multi_epoch` runs the same sparse steps, folded once an epoch and
    more: against the dense step's program on the same draws (c = 0.01 a
    step is far above float32's rounding, so the dense step keeps it)."""
    data, w, key = _rows(seed=8), jnp.asarray(_weights(seed=9)), jax.random.PRNGKey(17)
    sparse = _bind(data, 0.05, steps=150)
    assert sparse.plan.update == "sparse" and sparse._fold_span() < 150
    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 10 * D)
    dense = _bind(data, 0.05, steps=150)
    got, want = (np.asarray(b.multi_epoch(w, key, 2)) for b in (sparse, dense))
    assert harness.rel_err(got, want) <= 5e-6


def test_the_entries_are_the_gradient_scattered(everywhere):
    """`reply_entries` into a zeroed accumulator is `grad_blocked`'s sum."""
    from distributed_sgd_tpu.ops import gather
    from distributed_sgd_tpu.ops.sparse import SparseBatch

    data, w = _rows(seed=10, n=64), jnp.asarray(_weights(seed=11))
    model = make_model("logistic", 1e-4, D, regularizer="l2")
    w2 = model.to_layout(w, "gather")
    batch, y = SparseBatch(jnp.asarray(data.indices), jnp.asarray(data.values)), jnp.asarray(data.labels)
    ids, add = model.reply_entries(w2, batch, y)
    got = gather.scatter_into(jnp.zeros_like(w2), ids, add)
    want = model.grad_blocked(w2, batch, y, kernel="gather")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-9)
    # a scale on the stored weights is a scale on the margins
    _, halved = model.reply_entries(2.0 * w2, batch, y, scale=jnp.float32(0.5))
    np.testing.assert_allclose(np.asarray(halved), np.asarray(add), rtol=1e-6, atol=1e-9)


# -- the scopes the benchmark reads the step by --------------------------------------

def _scopes(lowered):
    import re

    return set(re.findall(r"dsgd\.[a-z_]+", lowered.compile().as_text()))


@pytest.mark.parametrize("devices,workers", [(1, 4), (4, 1)])
def test_the_sparse_epoch_program_carries_its_scopes(everywhere, devices, workers, monkeypatch):
    """`scatter_us_per_step` and `gather_scatter_roofline` read the scatter
    into the carry under `dsgd.scatter` as they read the dense step's;
    `update_us_per_step` reads `dsgd.update` (the scale) and `dsgd.rescale`
    (the fold); no gradient, so no `dsgd.regularize`."""
    w, key = jnp.zeros((D,), jnp.float32), jax.random.PRNGKey(0)

    def epoch(bound):
        return bound._epoch.lower(w, bound._opt_state, bound.data.indices,
                                  bound.data.values, bound.data.labels, key)

    step = {"dsgd.draw", "dsgd.margins", "dsgd.coeff", "dsgd.scatter", "dsgd.update",
            "dsgd.allreduce", "dsgd.layout"}
    sparse = epoch(_bind(_rows(), 1e-4, devices, workers))
    assert _scopes(sparse) == step | {"dsgd.rescale"}
    assert "jit__epoch_shard" in sparse.as_text().split("\n")[0]
    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 10 * D)
    assert _scopes(epoch(_bind(_rows(), 1e-4, devices, workers))) == step | {"dsgd.regularize"}
