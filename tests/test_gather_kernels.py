"""The 'gather' kernel family (ops/gather.py) and the one rule that picks a
family from the shape (ops/kernels.py).

The family must compute what `benchmark/reference.py` states (take +
segment_sum, float32) and what kernel='scalar' computes, up to float
summation order: margins, the regularised gradient, one `BoundSync.step`
and `evaluate`, also where one id sits in every row of the batch and where
every id is distinct.  The rule: RCV1's shape -> 'mxu', the hashed
1,000,000-feature shape -> 'gather', dense rows -> 'dense', off the TPU
what each engine ran before the rule (the sync engines the one-hot
matmuls, Hogwild and the rpc worker the scalar path); and every engine
asks it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.models.linear import LinearModel, make_model
from distributed_sgd_tpu.ops import gather, kernels, mxu
from distributed_sgd_tpu.ops.sparse import SparseBatch
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine

D, P = 5000, 7
LAM, LR = 1e-3, 0.1


def _ids(kind: str, b: int, rng) -> np.ndarray:
    if kind == "distinct":  # no id twice in the whole batch
        return rng.permutation(D)[: b * P].reshape(b, P).astype(np.int32)
    idx = rng.integers(0, D, (b, P)).astype(np.int32)
    if kind == "hot":  # one id in EVERY row, another in every second one
        idx[:, 0] = 4321
        idx[::2, 1] = 17
    return idx


def _batch(kind: str, b: int = 24, seed: int = 0):
    rng = np.random.default_rng(seed)
    idx = _ids(kind, b, rng)
    val = rng.normal(size=(b, P)).astype(np.float32)
    y = rng.choice([-1, 1], b).astype(np.int32)
    w = rng.normal(size=D).astype(np.float32)
    return idx, val, y, w


def _model(reg: str = "l2") -> LinearModel:
    return make_model("logistic", LAM, D, regularizer=reg)


KINDS = ("random", "hot", "distinct")


@pytest.mark.parametrize("kind", KINDS)
def test_margins_match_reference_and_scalar(kind):
    idx, val, _, w = _batch(kind)
    batch = SparseBatch(jnp.asarray(idx), jnp.asarray(val))
    model = _model()
    got = model.margins(model.to_layout(jnp.asarray(w), "gather"), batch, kernel="gather")
    np.testing.assert_allclose(got, reference.margins(jnp.asarray(w), idx, val), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got, model.margins(jnp.asarray(w), batch), rtol=1e-6, atol=1e-6)


def test_gathered_picks_the_word_at_every_lane_and_block_edge():
    w = jnp.arange(D, dtype=jnp.float32)
    w2 = mxu.to_blocked(w, D)
    ids = jnp.asarray([[0, 127, 128, 255], [D - 1, 4096, 129, 0]], jnp.int32)
    np.testing.assert_array_equal(gather.gathered(w2, ids), np.asarray(ids, np.float32))


@pytest.mark.parametrize("reduce", ["sum", "mean"])
@pytest.mark.parametrize("kind", KINDS)
def test_regularised_gradient_matches_reference_and_scalar(kind, reduce):
    idx, val, y, w = _batch(kind, seed=3)
    batch = SparseBatch(jnp.asarray(idx), jnp.asarray(val))
    model = _model()
    got = model.grad_regularized(jnp.asarray(w), batch, jnp.asarray(y), reduce=reduce,
                                 kernel="gather")
    want = reference.worker_grad("logistic", "l2", jnp.asarray(w), idx, val, y, LAM,
                                 reduce=reduce)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    scalar = model.grad_regularized(jnp.asarray(w), batch, jnp.asarray(y), reduce=reduce)
    np.testing.assert_allclose(got, scalar, rtol=1e-5, atol=1e-6)


def test_scatter_accumulates_every_duplicate_and_keeps_pad_lanes_zero():
    b = 40
    idx = np.full((b, P), 4321, np.int32)  # one id, b * P times
    val = np.ones((b, P), np.float32)
    coeff = np.arange(b, dtype=np.float32)
    g2 = gather.scatter_add(SparseBatch(jnp.asarray(idx), jnp.asarray(val)),
                            jnp.asarray(coeff), mxu.n_blocks(D))
    flat = np.asarray(g2).reshape(-1)
    assert flat[4321] == P * coeff.sum()
    assert np.count_nonzero(flat) == 1 and not flat[D:].any()


def test_dim_sparsity_regulariser_runs_on_the_gather_family_too():
    idx, val, y, w = _batch("hot", seed=5)
    ds = np.abs(np.random.default_rng(6).normal(size=D)).astype(np.float32) * 0.01
    model = make_model("hinge", LAM, D, dim_sparsity=jnp.asarray(ds))
    batch = SparseBatch(jnp.asarray(idx), jnp.asarray(val))
    got = model.grad_regularized(jnp.asarray(w), batch, jnp.asarray(y), kernel="gather")
    want = model.grad_regularized(jnp.asarray(w), batch, jnp.asarray(y), kernel="mxu")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def _dataset(kind: str, n: int = 256, seed: int = 7) -> Dataset:
    rng = np.random.default_rng(seed)
    idx = _ids("hot" if kind == "hot" else "random", n, rng)
    val = np.full((n, P), 1.0 / np.sqrt(P), np.float32)
    y = rng.choice([-1, 1], n).astype(np.int32)
    return Dataset(idx, val, y, D)


@pytest.mark.parametrize("virtual_workers", [1, 4])
@pytest.mark.parametrize("kind", ["random", "hot"])
def test_one_sync_step_matches_reference_and_scalar(kind, virtual_workers):
    data = _dataset(kind)
    w0 = np.random.default_rng(8).normal(size=D).astype(np.float32) * 0.1
    key = jax.random.PRNGKey(9)
    new = {}
    for kernel in ("gather", "scalar"):
        bound = SyncEngine(_model(), make_mesh(1), 16, LR, kernel=kernel,
                           virtual_workers=virtual_workers).bind(data)
        assert bound.kernel == kernel
        new[kernel] = np.asarray(bound.step(jnp.asarray(w0), key))
    rows = np.asarray(jax.jit(lambda k: bound._sample_ids(k, jnp.int32(0)))(
        jax.random.fold_in(key, 0)))
    batches = [(data.indices[r], data.values[r], data.labels[r]) for r in rows]
    want = np.asarray(reference.sync_step("logistic", "l2", jnp.asarray(w0), batches, LAM, LR))
    for kernel, got in new.items():
        err = np.linalg.norm((got - w0) - (want - w0)) / np.linalg.norm(want - w0)
        assert err < 1e-5, (kernel, err)


def test_evaluate_and_predict_match_reference_and_scalar():
    data = _dataset("hot", n=512)
    w = np.random.default_rng(10).normal(size=D).astype(np.float32)
    got = {}
    for kernel in ("gather", "scalar"):
        bound = SyncEngine(_model(), make_mesh(2), 16, LR, kernel=kernel).bind(data)
        got[kernel] = (bound.evaluate(jnp.asarray(w)), bound.predict(jnp.asarray(w)))
    ref_loss, ref_acc = reference.evaluate(
        "logistic", jnp.asarray(w), jnp.asarray(data.indices), jnp.asarray(data.values),
        jnp.asarray(data.labels), LAM)
    for kernel, ((loss, acc), preds) in got.items():
        assert abs(loss - ref_loss) < 1e-5 and abs(acc - ref_acc) < 1e-6, kernel
        np.testing.assert_array_equal(preds, got["scalar"][1])


def test_a_fit_on_the_gather_family_follows_the_scalar_fit():
    from distributed_sgd_tpu.core.trainer import SyncTrainer

    train, test = _dataset("hot", n=512, seed=11), _dataset("hot", n=128, seed=12)
    losses = {}
    for kernel in ("gather", "scalar"):
        fit = SyncTrainer(_model(), make_mesh(1), 16, LR, kernel=kernel,
                          virtual_workers=4, seed=3).fit(train, test, max_epochs=3)
        losses[kernel] = fit.test_losses
    np.testing.assert_allclose(losses["gather"], losses["scalar"], rtol=1e-5)


# -- the rule -----------------------------------------------------------------

@pytest.mark.parametrize("n_features,width,platform,off_tpu,want", [
    (47_236, 76, "tpu", "scalar", "mxu"),        # rcv1-hinge stays on the one-hot matmuls
    (1_000_000, 39, "tpu", "scalar", "gather"),  # criteo-logistic
    (kernels.GATHER_MIN_FEATURES - 1, 39, "tpu", "scalar", "mxu"),
    (kernels.GATHER_MIN_FEATURES, 39, "tpu", "scalar", "gather"),
    (47_236, 76, "tpu", "mxu", "mxu"),           # on the TPU the asker's family says nothing
    (1_000_000, 39, "tpu", "mxu", "gather"),
    (2_000, 0, "tpu", "scalar", "dense"),        # epsilon-logistic: no index array
    (1_000_000, 0, "cpu", "mxu", "dense"),
    # off the TPU each engine keeps what it ran before the rule:
    (47_236, 76, "cpu", "scalar", "scalar"),     # Hogwild, the rpc worker
    (1_000_000, 39, "cpu", "scalar", "scalar"),
    (1_000_000, 39, "gpu", "scalar", "scalar"),
    (47_236, 76, "cpu", "mxu", "mxu"),           # the sync engines
    (kernels.GATHER_MIN_FEATURES - 1, 39, "cpu", "mxu", "mxu"),
    # ... but for the one-hot's growth with D, which no platform escapes
    (1_000_000, 39, "cpu", "mxu", "gather"),
])
def test_the_rule_maps_shape_and_platform_to_a_family(n_features, width, platform, off_tpu,
                                                      want):
    assert kernels.choose_kernel(n_features, width, platform, off_tpu) == want
    if off_tpu == "scalar":  # the default
        assert kernels.choose_kernel(n_features, width, platform) == want


def test_an_explicit_kernel_overrides_the_rule_and_dense_rows_stay_dense():
    assert kernels.resolve("mxu", 1_000_000, 39) == "mxu"
    assert kernels.resolve("gather", 100, 5) == "gather"
    assert kernels.resolve("scalar", 100, 0) == "dense"
    assert kernels.resolve(kernels.AUTO, 100, 5) == kernels.resolve(None, 100, 5) == "scalar"
    assert kernels.resolve(kernels.AUTO, 100, 5, off_tpu="mxu") == "mxu"


def test_the_platform_probe_is_blocked_pays_off(monkeypatch):
    monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None: True)
    assert kernels.resolve(None, 47_236, 76) == "mxu"
    assert kernels.resolve(None, 1_000_000, 39) == "gather"
    monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None: False)
    assert kernels.resolve(None, 1_000_000, 39) == "scalar"
    assert kernels.resolve(None, 1_000_000, 39, off_tpu="mxu") == "gather"


def test_off_the_tpu_the_sync_engines_default_to_the_one_hot_matmuls():
    """What they ran on every platform before the rule: the CPU tests of
    epoch, step, evaluation, optimizer state and checkpoints that construct
    an engine without `kernel=` keep driving ops/mxu.py."""
    from distributed_sgd_tpu.core.trainer import SyncTrainer
    from distributed_sgd_tpu.parallel.local_sgd import LocalSGDEngine
    from distributed_sgd_tpu.utils import metrics

    data = _dataset("random")
    for bound in (SyncEngine(_model(), make_mesh(1), 16, LR).bind(data),
                  SyncTrainer(_model(), make_mesh(1), 16, LR).engine.bind(data)):
        assert bound.kernel == "mxu"
        assert bound._to_kernel_layout(jnp.zeros(D, jnp.float32)).shape == (
            mxu.n_blocks(D), 128)
    local = LocalSGDEngine(_model(), make_mesh(2), 8, LR, sync_period=2, check_every=8)
    assert local.kernel == kernels.AUTO
    before = {k: metrics.counter(f"bind.kernel.{k}").value for k in kernels.KERNELS}
    local.fit(data, data, max_epochs=1)
    after = {k: metrics.counter(f"bind.kernel.{k}").value for k in kernels.KERNELS}
    assert after == {**before, "mxu": before["mxu"] + 2}  # the train and the test binding


@pytest.fixture
def asked(monkeypatch):
    """Every (n_features, row width, platform) the rule was asked for, with
    a TPU's answers given on the CPU."""
    calls = []
    rule = kernels.choose_kernel

    def spy(n_features, row_width, platform, off_tpu="scalar", n_outputs=1):
        calls.append((n_features, row_width, platform))
        return rule(n_features, row_width, "tpu", off_tpu, n_outputs)

    monkeypatch.setattr(kernels, "choose_kernel", spy)
    return calls


def _wide_problem(n=64, d=kernels.GATHER_MIN_FEATURES + 5):
    rng = np.random.default_rng(13)
    idx = rng.integers(0, d, (n, P)).astype(np.int32)
    data = Dataset(idx, np.full((n, P), 0.3, np.float32),
                   rng.choice([-1, 1], n).astype(np.int32), d)
    return data, make_model("logistic", LAM, d, regularizer="l2")


def test_sync_bind_asks_the_rule_and_counts_what_it_chose(asked):
    from distributed_sgd_tpu.utils import metrics

    data, model = _wide_problem()
    before = metrics.counter("bind.kernel.gather").value
    bound = SyncEngine(model, make_mesh(1), 8, LR).bind(data)
    assert bound.kernel == "gather" and asked == [(data.n_features, P, "cpu")]
    assert metrics.counter("bind.kernel.gather").value == before + 1
    small = Dataset(data.indices % 500, data.values, data.labels, 500)
    assert SyncEngine(make_model("logistic", LAM, 500, regularizer="l2"),
                      make_mesh(1), 8, LR).bind(small).kernel == "mxu"


def test_the_train_split_record_names_the_kernel(asked, caplog):
    import logging

    from distributed_sgd_tpu.core.trainer import SyncTrainer

    data, model = _wide_problem()
    with caplog.at_level(logging.INFO, logger="dsgd.trainer"):
        SyncTrainer(model, make_mesh(1), 8, LR).fit(data, data, max_epochs=1)
    record = next(r.getMessage() for r in caplog.records
                  if r.getMessage().startswith("train split:"))
    assert "kernel=gather" in record


def test_local_sgd_asks_the_rule(asked):
    from distributed_sgd_tpu.parallel.local_sgd import LocalSGDEngine

    data, model = _wide_problem()
    fit = LocalSGDEngine(model, make_mesh(2), 8, LR, sync_period=2,
                         check_every=8).fit(data, data, max_epochs=1)
    assert (data.n_features, P, "cpu") in asked
    assert np.all(np.isfinite(fit.weights))


def test_hogwild_worker_asks_the_rule(asked):
    from distributed_sgd_tpu.parallel.hogwild import _Worker
    from distributed_sgd_tpu.utils.metrics import Metrics

    data, model = _wide_problem()
    worker = _Worker(0, model, data, jax.devices()[0], 8, LR, 0, Metrics(),
                     steps_per_dispatch=2)
    assert worker.kernel == "gather" and worker._blocked
    assert asked == [(data.n_features, P, "cpu")]
    delta, _ = worker._step(jnp.zeros((data.n_features,), jnp.float32), None,
                            worker._idx, worker._val, worker._y, jax.random.PRNGKey(0))
    assert delta.shape == (data.n_features,) and np.any(np.asarray(delta) != 0)


def test_rpc_worker_asks_the_rule(asked):
    from distributed_sgd_tpu.core.worker import WorkerNode, _kernel_of
    from distributed_sgd_tpu.utils import metrics

    class Host:  # the minimum surface _kernel_of / WorkerNode._grad_fn need
        _grad_cache = {}

    data, model = _wide_problem()
    host = Host()
    host.model = model
    before = metrics.counter("bind.kernel.gather").value
    fn = WorkerNode._grad_fn(host, 8)
    g = fn(jnp.zeros((data.n_features,), jnp.float32), jnp.asarray(data.indices),
           jnp.asarray(data.values), jnp.asarray(data.labels),
           jnp.arange(8, dtype=jnp.int32), jnp.ones(8, jnp.float32))
    assert np.any(np.asarray(g) != 0)
    # asked through `resolve`, once a node: counted, and kept on the node
    assert host.kernel == _kernel_of(host) == "gather"
    WorkerNode._window_fn(host, 2, 4)
    assert asked == [(data.n_features, 1, "cpu")]
    assert metrics.counter("bind.kernel.gather").value == before + 1


def test_a_worker_node_asks_with_its_rows_width_and_device(asked):
    from distributed_sgd_tpu.core.worker import WorkerNode

    data, model = _wide_problem()
    node = WorkerNode("127.0.0.1", 0, "127.0.0.1", 1, data, model)
    assert node.kernel == "gather" and asked == [(data.n_features, P, "cpu")]


# -- the model's surface --------------------------------------------------------

def test_an_unknown_regulariser_is_refused():
    with pytest.raises(ValueError, match="regularizer"):
        LinearModel(1e-3, 10, regularizer="l1")
    with pytest.raises(ValueError, match="regularizer"):
        make_model("logistic", 1e-3, 10, regularizer="L2")
    assert make_model("logistic", 1e-3, 10, regularizer="none").regularizer == "none"


@pytest.mark.parametrize("name,want", [(None, "dim_sparsity"), ("l2", "l2"), ("none", "none")])
def test_config_regularizer_reaches_make_model(name, want, monkeypatch):
    from distributed_sgd_tpu import main
    from distributed_sgd_tpu.config import Config

    monkeypatch.setenv("DSGD_SYNTHETIC", "200")
    _, _, model = main.build(Config(regularizer=name, data_path="/nonexistent"))
    assert model.regularizer == want


def test_config_knows_the_kernel_values_and_refuses_others(monkeypatch):
    from distributed_sgd_tpu.config import Config

    assert Config().kernel == kernels.AUTO
    for name in ("auto", "mxu", "scalar", "gather"):
        assert Config(kernel=name).kernel == name
    with pytest.raises(ValueError, match="kernel"):
        Config(kernel="dense")
    with pytest.raises(ValueError, match="regularizer"):
        Config(regularizer="l1")
    monkeypatch.setenv("DSGD_REGULARIZER", "l2")
    assert Config.from_env().regularizer == "l2"
