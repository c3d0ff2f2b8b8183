"""The epoch boundary of `SyncTrainer.fit` as one device queue and one host
wait (parallel/sync.py `dispatch_evaluation` / `read`, core/trainer.py).

The contracts under test:

- the numbers: every epoch's `loss`, `acc`, `test_loss`, `test_acc` (and
  under FTRL `penalty`, `nonzero`) are the formula of the loop that pulled
  each sum with `float()` and ran the regulariser eagerly, on the same `w`:
  the program's sums bit for bit, the regulariser (now summed inside the
  evaluation program) to 1e-6 relative, the nonzero count exactly;
- the key stream: the key folded inside the epoch program is the eager
  `fold_in(PRNGKey(seed), epoch)`, bit for bit in the weights;
- the queue: a fit's epochs make no implicit device-to-host transfer, and
  pull once an epoch (`jax.device_get`);
- the warm-up list (DSGD_COMPILE_CACHE) lowers exactly the programs a fit
  dispatches, and the evaluation program's output is what `read` takes apart.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sgd_tpu.core.trainer import SyncTrainer
from distributed_sgd_tpu.data.rcv1 import Dataset, dim_sparsity, train_test_split
from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.ops import ftrl
from distributed_sgd_tpu.parallel import sync
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.utils.metrics import Metrics

SEED = 11
# (kernel, outputs, optimizer): the three kernel families, an output axis,
# and an FTRL binding
CASES = [("mxu", 1, None), ("gather", 1, None), ("dense", 1, None), ("gather", 3, None),
         ("gather", 1, "ftrl")]
IDS = ["mxu", "gather", "dense", "gather-3-outputs", "ftrl"]


def _problem(kernel, n_outputs, optimizer, devices=2):
    """(trainer, train, test) of a small fit of that kind."""
    if kernel == "dense":
        sparse = rcv1_like(512, n_features=64, nnz=8, noise=0.0, seed=3)
        x = np.zeros((512, 64), np.float32)
        np.add.at(x, (np.arange(512)[:, None], sparse.indices), sparse.values)
        data = Dataset.dense(x, sparse.labels)
        model = make_model("logistic", 1e-3, 64, regularizer="l2")
    elif n_outputs > 1:
        data = rcv1_like(512, n_features=1000, nnz=8, noise=0.0, seed=3, n_outputs=n_outputs)
        model = make_model("hinge", 1e-3, 1000, regularizer="l2", n_outputs=n_outputs)
    elif optimizer == "ftrl":
        data = rcv1_like(512, n_features=1000, nnz=8, noise=0.0, seed=3)
        model = make_model("logistic", 1e-3, 1000, regularizer="l2")
        optimizer = ftrl.Ftrl(l1=0.01)
    else:
        data = rcv1_like(512, n_features=1000, nnz=8, noise=0.0, seed=3)
        model = make_model("hinge", 1e-3, 1000, dim_sparsity=dim_sparsity(data))
    train, test = train_test_split(data)
    trainer = SyncTrainer(model, make_mesh(devices), batch_size=16, learning_rate=0.5,
                          metrics=Metrics(), seed=SEED, kernel=kernel, virtual_workers=2,
                          optimizer=optimizer)
    return trainer, train, test


def _spied_fit(monkeypatch, trainer, train, test, epochs):
    """The fit's result, its two bindings (train, test), and the weights
    each epoch program returned."""
    bound, weights = [], []
    bind, epoch = sync.SyncEngine.bind, sync.BoundSync.epoch

    def spy_bind(self, *a, **k):
        bound.append(bind(self, *a, **k))
        return bound[-1]

    def spy_epoch(self, *a, **k):
        weights.append(epoch(self, *a, **k))
        return weights[-1]

    monkeypatch.setattr(sync.SyncEngine, "bind", spy_bind)
    monkeypatch.setattr(sync.BoundSync, "epoch", spy_epoch)
    result = trainer.fit(train, test, max_epochs=epochs)
    monkeypatch.undo()
    return result, bound, weights


def _parents_formula(b, w):
    """(loss sum, hit sum, penalty, nonzero, rows) as the loop before one
    queue had them: the program's sums pulled with `float()`, the
    regulariser and the nonzero count eager."""
    sums = b._eval(w, b.data.indices, b.data.values, b.data.labels, *b._planned)
    loss_sum, hit_sum = float(sums[0]), float(sums[1])
    if b.plan.optimizer == "ftrl":
        p = b.ftrl
        penalty = (p.l1 * float(jnp.sum(jnp.abs(w)))
                   + 0.5 * p.l2 * float(jnp.sum(jnp.asarray(w, jnp.float32) ** 2)))
        nonzero = int(jnp.count_nonzero(w))
    else:
        penalty = b.model.lam * float(jnp.sum(jnp.asarray(w, jnp.float32) ** 2))
        nonzero = None
    n = b.data.n_true
    return loss_sum, hit_sum, penalty, nonzero, n


@pytest.mark.parametrize("kernel,n_outputs,optimizer", CASES, ids=IDS)
def test_every_epochs_numbers_are_the_formula_of_before_on_the_same_weights(
        monkeypatch, kernel, n_outputs, optimizer):
    trainer, train, test = _problem(kernel, n_outputs, optimizer)
    result, (b_train, b_test), weights = _spied_fit(monkeypatch, trainer, train, test, 2)
    assert result.epochs_run == 2 and len(weights) == 2
    series = {b_train: (result.losses, result.accuracies),
              b_test: (result.test_losses, result.test_accuracies)}
    for e, w in enumerate(weights):
        for b, (losses, accuracies) in series.items():
            loss_sum, hit_sum, penalty, nonzero, n = _parents_formula(b, w)
            read = b.read(np.asarray(b.dispatch_evaluation(w)))
            # the sums bit for bit: the accuracy is the hit sum's, the
            # objective the loss sum's beside the in-program regulariser
            assert accuracies[e] == hit_sum / (n * b.model.n_outputs) == read.accuracy
            assert losses[e] == read.penalty + loss_sum / n == read.objective
            assert read.penalty == pytest.approx(penalty, rel=1e-6)
            assert losses[e] == pytest.approx(penalty + loss_sum / n, rel=1e-6)
            assert read.nonzero == nonzero
            assert 0.0 < hit_sum and loss_sum > 0.0  # a problem, not zeros
        if optimizer == "ftrl":
            _l, _h, penalty, nonzero, _n = _parents_formula(b_train, w)
            assert result.penalty[e] == pytest.approx(penalty, rel=1e-6)
            # what the benchmark subtracts from the objective to check its
            # mean loss: the program's penalty to the bit
            assert result.penalty[e] == ftrl.penalty(w, b_train.ftrl)
            assert result.nonzero[e] == nonzero == int((np.asarray(w) != 0).sum())
            assert 0 < nonzero < w.size  # L1 zeroed some weights, not all
        else:
            assert result.penalty == [] and result.nonzero == []


def test_the_nonzero_count_comes_back_exactly_past_float32s_integers():
    """The count rides as its quotient and remainder by NONZERO_SPLIT: each
    exact in float32 where the count itself (past 2**24) would not be."""
    trainer, train, _test = _problem("gather", 1, "ftrl", devices=1)
    b = trainer.engine.bind(train)
    d = (1 << 24) + 2  # d - 1 nonzero weights: odd, past float32's integers
    w = jnp.ones((d,), jnp.float32).at[7].set(0.0)
    sums = np.asarray(jax.jit(b._weight_sums)(w))
    assert sums[2:].tolist() == [(d - 1) // sync.NONZERO_SPLIT, (d - 1) % sync.NONZERO_SPLIT]
    pulled = np.concatenate([np.float32([1.0, 2.0]), sums])
    assert b.read(pulled).nonzero == d - 1
    assert float(np.float32(d - 1)) != d - 1  # what one float32 would have said


@pytest.mark.parametrize("kernel,optimizer", [("mxu", None), ("gather", "ftrl")])
def test_the_key_folded_in_the_program_is_the_eager_fold_bit_for_bit(
        monkeypatch, kernel, optimizer):
    trainer, train, test = _problem(kernel, 1, optimizer)
    result, _bound, weights = _spied_fit(monkeypatch, trainer, train, test, 2)
    again, _train, _test = _problem(kernel, 1, optimizer)
    b = again.engine.bind(train)
    w = jnp.zeros(b.model.weight_shape, jnp.float32)
    base = jax.random.PRNGKey(SEED)
    for e in range(2):
        w = b.epoch(w, jax.random.fold_in(base, e))
        np.testing.assert_array_equal(np.asarray(w), np.asarray(weights[e]))
    np.testing.assert_array_equal(np.asarray(result.weights), np.asarray(w))


@pytest.mark.parametrize("kernel,n_outputs,optimizer", [CASES[0], CASES[4]], ids=["mxu", "ftrl"])
def test_a_fit_epoch_pulls_once_and_nothing_implicitly(monkeypatch, kernel, n_outputs, optimizer):
    """Every read of a device array's value from the first epoch on (where
    a `float()`, an `np.asarray` or an eager regulariser read back would
    read it) is inside the one explicit pull an epoch, `jax.device_get` of
    both splits' evaluation outputs, after both programs were enqueued.
    (Counted at `ArrayImpl._value`, which every such read goes through: the
    CPU backend's transfers pass `jax.transfer_guard` unchecked.)"""
    from jax._src import array as jax_array

    trainer, train, test = _problem(kernel, n_outputs, optimizer)
    calls, outputs, pulling = [], [], [False]
    get, dispatch, epoch = jax.device_get, sync.BoundSync.dispatch_evaluation, sync.BoundSync.epoch
    value = jax_array.ArrayImpl._value

    def spy_value(self):
        if calls:  # from the first epoch program on
            calls.append(("read", pulling[0] and any(self is o for o in outputs)))
        return value.fget(self)

    def spy_get(x):
        calls.append(("pull", len(x)))
        pulling[0] = True
        try:
            return get(x)
        finally:
            pulling[0] = False

    def spy_dispatch(self, w):
        calls.append(("dispatch", None))
        outputs.append(dispatch(self, w))
        return outputs[-1]

    def spy_epoch(self, *a, **k):
        calls.append(("epoch", None))
        return epoch(self, *a, **k)

    monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(spy_value))
    monkeypatch.setattr(jax, "device_get", spy_get)
    monkeypatch.setattr(sync.BoundSync, "dispatch_evaluation", spy_dispatch)
    monkeypatch.setattr(sync.BoundSync, "epoch", spy_epoch)
    result = trainer.fit(train, test, max_epochs=3)
    assert result.epochs_run == 3
    assert calls == [("epoch", None), ("dispatch", None), ("dispatch", None), ("pull", 2),
                     ("read", True), ("read", True)] * 3
    # the spy sees an implicit pull where there is one
    calls[:] = [("armed", None)]
    float(jnp.sum(result.weights))
    assert calls[1:] == [("read", False)]


@pytest.mark.parametrize("kernel,n_outputs,optimizer", [CASES[0], CASES[3], CASES[4]],
                         ids=["mxu", "gather-3-outputs", "ftrl"])
def test_the_warmup_lowers_exactly_the_programs_a_fit_dispatches(
        monkeypatch, kernel, n_outputs, optimizer):
    """Every program a fit dispatches lowers to the text of one of its
    binding's `fit_signatures` (the warm-up's list), and each of those is
    dispatched: the warm-up compiles no program nobody runs."""
    trainer, train, test = _problem(kernel, n_outputs, optimizer)
    signatures, dispatched = {}, []
    bind, epoch, dispatch = (sync.SyncEngine.bind, sync.BoundSync.epoch,
                             sync.BoundSync.dispatch_evaluation)

    def spy_bind(self, *a, **k):
        b = bind(self, *a, **k)
        signatures[b] = {p.lower(*args).as_text(): name for name, p, args in b.fit_signatures()}
        assert [name for name, _t in b.warmup_thunks()] == list(signatures[b].values())
        return b

    def spy_epoch(self, w, key, at=None):
        text = self._epoch.lower(w, self._state(), self.data.indices, self.data.values,
                                 self.data.labels, (key, at)).as_text()
        dispatched.append((self, text))
        return epoch(self, w, key, at)

    def spy_dispatch(self, w):
        dispatched.append((self, self._eval.lower(
            w, self.data.indices, self.data.values, self.data.labels, *self._planned).as_text()))
        return dispatch(self, w)

    monkeypatch.setattr(sync.SyncEngine, "bind", spy_bind)
    monkeypatch.setattr(sync.BoundSync, "epoch", spy_epoch)
    monkeypatch.setattr(sync.BoundSync, "dispatch_evaluation", spy_dispatch)
    trainer.fit(train, test, max_epochs=3)
    b_train, b_test = signatures
    used = {b: {signatures[b].get(text) for bb, text in dispatched if bb is b}
            for b in signatures}
    assert used[b_train] == {"epoch", "epoch.again", "eval"}
    assert used[b_test] == {"eval"}  # the test split is evaluated, never trained


@pytest.mark.parametrize("kernel,n_outputs,optimizer", [CASES[0], CASES[2], CASES[4]],
                         ids=["mxu", "dense", "ftrl"])
def test_the_lowered_evaluation_returns_what_read_takes_apart(kernel, n_outputs, optimizer):
    trainer, train, _test = _problem(kernel, n_outputs, optimizer)
    b = trainer.engine.bind(train)
    (_name, program, args), = [s for s in b.fit_signatures() if s[0] == "eval"]
    lowered = program.lower(*args)
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(7), b.model.weight_shape, jnp.float32)
    pulled = jax.device_get(b.dispatch_evaluation(w))
    want = (6,) if optimizer == "ftrl" else (3,)  # loss, hits, ||w||^2 (, ||w||_1, nonzero)
    assert lowered.out_info.shape == pulled.shape == want
    assert lowered.out_info.dtype == pulled.dtype == np.float32
    assert b.evaluate(w) == b.read(pulled)[:2]
