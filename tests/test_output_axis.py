"""A model with an OUTPUT AXIS (`LinearModel(n_outputs=C)`: `W[D, C]`, one
row of C labels a sample) through the mesh sync engine (PERF.md section 6,
PR 32).

Nothing couples the columns, so two references hold the program: the plain
equations of `benchmark/reference_outputs.py` (one step, the evaluation), and
the binary program itself (column c of a C-output fit is the C = 1 fit on
column c's labels under the same draws).  Small sizes, the CPU; seeded
random `W` and not zeros: hinge at `W = 0` exercises one branch.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, reference_outputs
from distributed_sgd_tpu.core.trainer import SyncTrainer
from distributed_sgd_tpu.data.rcv1 import Dataset, load_rcv1, read_labels, read_topics
from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import make_model, require_flat_weights
from distributed_sgd_tpu.ops import gather, kernels
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine
from distributed_sgd_tpu.utils import metrics as metrics_mod

D, C, N, P = 300, 5, 512, 6
LAM, LR, BATCH = 1e-3, 0.1, 8


def _rows(dense: bool = False, n_outputs: int = C, seed: int = 3) -> Dataset:
    data = rcv1_like(N, n_features=D, nnz=P, seed=seed, n_outputs=n_outputs)
    if not dense:
        return data
    x = np.zeros((N, D), np.float32)
    np.add.at(x, (np.arange(N)[:, None], data.indices), data.values)
    return Dataset.dense(x, data.labels)


def _weights(n_outputs: int = C, seed: int = 5):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(D, n_outputs)) * 0.1,
                       jnp.float32)


def _bind(data, reg="l2", devices=1, workers=4, kernel=kernels.AUTO, loss="hinge",
          n_outputs=C):
    model = make_model(loss, LAM, D, regularizer=reg, n_outputs=n_outputs)
    return SyncEngine(model, make_mesh(devices), BATCH, LR, eval_chunk=64,
                      kernel=kernel, virtual_workers=workers).bind(data)


def _reference_step(bound, data, w, key, reg, loss="hinge"):
    """`reference_outputs.sync_step` on the batches the program's own
    sampler draws for `key`."""
    draw = jax.jit(lambda k: bound._sample_ids(k, jnp.int32(0)))
    batches = []
    for d in range(bound.n_workers):
        drawn = np.asarray(draw(jax.random.fold_in(key, d))) + d * bound.shard_n
        for rows in drawn:
            batches.append((None if data.is_dense else jnp.asarray(data.indices[rows]),
                            jnp.asarray(data.values[rows]), jnp.asarray(data.labels[rows])))
    return np.asarray(reference_outputs.sync_step(loss, reg, w, batches, LAM, LR))


# -- (i) one step against the plain reference, every family that takes outputs ----

FORMS = {  # kernel, the sparse update forced on / off (None: the family has none), dense rows
    "gather_dense_update": ("gather", False, False),
    "gather_sparse_update": ("gather", True, False),
    "scalar": ("scalar", None, False),
    "dense": ("dense", None, True),
}


@pytest.mark.parametrize("reg", ["l2", "none"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_one_step_equals_the_reference(form, reg, monkeypatch):
    kernel, sparse, dense = FORMS[form]
    if sparse is not None:
        monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0 if sparse else 10**9)
    data = _rows(dense)
    bound = _bind(data, reg, kernel=kernels.AUTO if dense else kernel)
    assert bound.kernel == kernel and bound.update_sparse == bool(sparse)
    w, key = _weights(), jax.random.PRNGKey(7)
    want = _reference_step(bound, data, w, key, reg)
    got = np.asarray(bound.step(w, key))
    assert got.shape == (D, C)
    np.testing.assert_allclose(got - np.asarray(w), want - np.asarray(w), rtol=2e-5, atol=2e-7)


def test_a_logistic_step_equals_the_reference():
    data = _rows()
    bound = _bind(data, loss="logistic")
    w, key = _weights(), jax.random.PRNGKey(9)
    want = _reference_step(bound, data, w, key, "l2", "logistic")
    np.testing.assert_allclose(np.asarray(bound.step(w, key)), want, rtol=1e-5, atol=1e-7)


# -- (ii) the columns do not couple: a C-output fit is C binary fits ----------------

@pytest.mark.parametrize("loss", ["hinge", "logistic"])
def test_a_fit_with_outputs_is_a_binary_fit_a_column(loss):
    data = _rows()
    train, test = data.slice(slice(0, 384)), data.slice(slice(384, None))

    def fit(train, test, n_outputs):
        model = make_model(loss, LAM, D, regularizer="l2", n_outputs=n_outputs)
        trainer = SyncTrainer(model, make_mesh(1), BATCH, LR, seed=11, kernel="gather",
                              virtual_workers=4, metrics=metrics_mod.Metrics())
        return trainer.fit(train, test, max_epochs=3)

    whole = fit(train, test, C)
    assert np.shape(whole.weights) == (D, C) and whole.epochs_run == 3
    columns, losses = [], []
    for c in range(C):
        one = fit(Dataset(train.indices, train.values, train.labels[:, c].astype(np.int32), D),
                  Dataset(test.indices, test.values, test.labels[:, c].astype(np.int32), D), 1)
        assert np.shape(one.weights) == (D,)
        columns.append(np.asarray(one.weights))
        losses.append(one.test_losses)
    np.testing.assert_allclose(np.asarray(whole.weights), np.stack(columns, axis=1),
                               rtol=1e-5, atol=1e-6)
    # the objective sums the columns' losses and each column's share of lam ||W||^2
    np.testing.assert_allclose(whole.test_losses, np.sum(losses, axis=0), rtol=1e-5)


# -- (iii) the plain reference at C = 1 is the flat reference -------------------------

@pytest.mark.parametrize("loss", ["hinge", "logistic"])
@pytest.mark.parametrize("reg", ["l2", "none"])
def test_reference_outputs_at_one_output_is_the_flat_reference(loss, reg):
    data = _rows(n_outputs=1)
    w = _weights(1)
    rows = np.arange(64).reshape(4, 16)
    flat = [(jnp.asarray(data.indices[r]), jnp.asarray(data.values[r]),
             jnp.asarray(data.labels[r])) for r in rows]
    wide = [(i, v, y[:, None]) for i, v, y in flat]
    np.testing.assert_array_equal(
        np.asarray(reference_outputs.sync_step(loss, reg, w, wide, LAM, LR))[:, 0],
        np.asarray(reference.sync_step(loss, reg, w[:, 0], flat, LAM, LR)))
    idx, val, y = (jnp.asarray(a) for a in (data.indices, data.values, data.labels))
    got = reference_outputs.evaluate(loss, w, idx, val, y[:, None], LAM, block=64)
    want = reference.evaluate(loss, w[:, 0], idx, val, y, LAM, block=64)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    if loss == "hinge":
        np.testing.assert_array_equal(
            np.asarray(reference_outputs.kink_distance(loss, w, idx, val, y[:, None]))[:, 0],
            np.asarray(reference.kink_distance(loss, w[:, 0], idx, val, y)))


# -- (iv) four virtual devices -----------------------------------------------------------

@pytest.mark.parametrize("sparse", [False, True])
@pytest.mark.parametrize("workers", [1, 2])
def test_four_devices_equal_the_reference(sparse, workers, monkeypatch):
    """`psum` of `[D', L]` (dense update) or the exchange of the entries'
    factors and the samples' coefficient rows (sparse update) inside the
    `shard_map`."""
    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0 if sparse else 10**9)
    data = _rows()
    bound = _bind(data, devices=4, workers=workers)
    assert (bound.kernel, bound.update_sparse, bound.n_workers) == ("gather", sparse, 4)
    w, key = _weights(), jax.random.PRNGKey(13)
    want = _reference_step(bound, data, w, key, "l2")
    got = np.asarray(bound.step(w, key))
    np.testing.assert_allclose(got - np.asarray(w), want - np.asarray(w), rtol=2e-5, atol=2e-7)
    one = _bind(data, devices=1, workers=4 * workers)
    np.testing.assert_allclose(bound.evaluate(w), one.evaluate(w), rtol=1e-6)


# -- (v) the evaluation -------------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["gather", "scalar"])
def test_evaluation_and_predictions_equal_the_reference(kernel):
    data = _rows().slice(slice(0, 500))  # 12 pad rows: label 0 counts nowhere
    bound = _bind(data, kernel=kernel)
    w = _weights()
    idx, val, y = (jnp.asarray(a) for a in (data.indices, data.values, data.labels))
    want = reference_outputs.evaluate("hinge", w, idx, val, y, LAM, block=100)
    np.testing.assert_allclose(bound.evaluate(w), want, rtol=1e-6)
    preds = bound.predict(w)
    assert preds.shape == (500, C)
    np.testing.assert_array_equal(
        preds, np.asarray(reference_outputs.predict("hinge", reference_outputs.margins(w, idx, val))))
    model = bound.model  # the model's own host-side objective and accuracy
    batch = gather.SparseBatch(idx, val)
    np.testing.assert_allclose(
        (float(model.objective(w, batch, y)), float(model.accuracy(w, batch, y))), want, rtol=1e-6)


def test_resident_labels_are_lane_padded_rows_with_zero_pads():
    bound = _bind(_rows())
    stored = np.asarray(bound.data.labels)
    assert stored.shape == (N, 128) and stored.dtype == np.int8
    np.testing.assert_array_equal(stored[:, :C], _rows().labels)
    assert not stored[:, C:].any()
    # the weights' pad rows and lanes stay zero through a step and an epoch
    w2 = bound.model.to_layout(bound.epoch(_weights(), jax.random.PRNGKey(1)), "gather")
    assert w2.shape == (304, 128) and not np.asarray(w2)[D:].any() and not np.asarray(w2)[:, C:].any()


# -- (vi) a checkpoint with [D, C] ----------------------------------------------------------

def test_a_checkpoint_holds_and_restores_the_output_axis(tmp_path):
    from distributed_sgd_tpu.checkpoint import Checkpointer

    data = _rows()
    train, test = data.slice(slice(0, 384)), data.slice(slice(384, None))

    def trainer(ckpt):
        model = make_model("hinge", LAM, D, regularizer="l2", n_outputs=C)
        return SyncTrainer(model, make_mesh(1), BATCH, LR, seed=17, virtual_workers=4,
                           metrics=metrics_mod.Metrics(), checkpointer=ckpt)

    straight = trainer(None).fit(train, test, max_epochs=3)
    ckpt = Checkpointer(str(tmp_path / "ckpt"))
    trainer(ckpt).fit(train, test, max_epochs=2)
    step, state = ckpt.restore_latest()
    assert step == 2 and np.shape(state["weights"]) == (D, C)
    resumed = trainer(ckpt).fit(train, test, max_epochs=3)
    ckpt.close()
    assert resumed.epochs_run == 3
    np.testing.assert_array_equal(np.asarray(resumed.weights), np.asarray(straight.weights))


# -- (vii) what carries one flat vector refuses, with one message ---------------------------

ONE_MESSAGE = (r"carries one flat weight vector w\[n_features\]; a model with n_outputs=5 "
               r"fits through SyncTrainer\.fit \(the mesh sync engine\) only")


def _refusers():
    from distributed_sgd_tpu.core.master import MasterNode
    from distributed_sgd_tpu.core.worker import WorkerNode
    from distributed_sgd_tpu.parallel.feature_sharded import FeatureShardedEngine, make_mesh_2d
    from distributed_sgd_tpu.parallel.hogwild import HogwildEngine
    from distributed_sgd_tpu.parallel.local_sgd import LocalSGDEngine

    data = _rows()
    return {
        "hogwild": lambda m: HogwildEngine(m, 2, BATCH, LR),
        "local_sgd": lambda m: LocalSGDEngine(m, make_mesh(2), BATCH, LR),
        "feature_sharded": lambda m: FeatureShardedEngine(m, make_mesh_2d(2, 2), BATCH, LR),
        "rpc_master": lambda m: MasterNode("127.0.0.1", 0, data, data, m, 1),
        "rpc_worker": lambda m: WorkerNode("127.0.0.1", 0, "127.0.0.1", 1, data, m),
        "serving": lambda m: require_flat_weights(
            np.zeros(m.weight_shape, np.float32), "the serving model store"),
    }


@pytest.mark.parametrize("engine", ["hogwild", "local_sgd", "feature_sharded", "rpc_master",
                                    "rpc_worker", "serving"])
def test_engines_of_one_flat_vector_refuse_the_output_axis(engine):
    model = make_model("hinge", LAM, D, regularizer="l2", n_outputs=C)
    with pytest.raises(ValueError, match=ONE_MESSAGE):
        _refusers()[engine](model)


def test_the_serving_store_keeps_its_snapshot_when_a_checkpoint_has_outputs(tmp_path):
    from distributed_sgd_tpu.checkpoint import Checkpointer
    from distributed_sgd_tpu.serving.model_store import ModelStore

    ckpt = Checkpointer(str(tmp_path))
    ckpt.save(1, np.zeros((D, C), np.float32))
    ckpt.close()
    store = ModelStore(str(tmp_path), poll_s=3600)
    assert not store.poll_once(force=True)  # refused and logged
    assert store.get() is None  # nothing served


def test_families_and_regularisers_without_a_form_refuse_at_construction():
    with pytest.raises(ValueError, match="no form with n_outputs=5"):
        make_model("hinge", LAM, D, dim_sparsity=np.ones(D, np.float32), n_outputs=C)
    with pytest.raises(ValueError, match="carries no output axis"):
        _bind(_rows(), kernel="mxu")
    with pytest.raises(ValueError, match=r"labels are \[N\] or \[N, C\]"):
        Dataset(np.zeros((2, 1), np.int32), np.zeros((2, 1), np.float32),
                np.zeros((2, 1, 1), np.int8), D)


# -- the one rule ------------------------------------------------------------------------------

@pytest.mark.parametrize("platform,off_tpu,n_outputs,want", [
    ("tpu", "scalar", 1, "mxu"), ("tpu", "scalar", 103, "gather"),
    ("cpu", "mxu", 1, "mxu"), ("cpu", "mxu", 2, "gather"),
    ("cpu", "scalar", 103, "scalar"),
])
def test_the_kernel_rule_takes_the_output_count(platform, off_tpu, n_outputs, want):
    assert kernels.choose_kernel(47_236, 76, platform, off_tpu, n_outputs) == want
    assert kernels.choose_kernel(47_236, 0, platform, off_tpu, n_outputs) == "dense"


def test_the_update_rule_counts_the_words_of_the_weights():
    rule = lambda d, c: kernels.sparse_update("gather", "l2", True, 1e-7, d, c)  # noqa: E731
    assert rule(47_236, 103) and not rule(47_236, 1) and not rule(47_236, 84)
    assert rule(4_000_000, 1) and not rule(3_999_999, 1)
    assert not kernels.sparse_update("scalar", "l2", True, 1e-7, 47_236, 103)
    assert not kernels.sparse_update("gather", "l2", False, 1e-7, 47_236, 103)


def test_an_optimizer_reads_a_gradient_with_the_output_axis():
    """optax state lives in the kernel's layout [D', L]; the step is the
    dense one whatever the rule's floor."""
    data = _rows()
    model = make_model("hinge", LAM, D, regularizer="l2", n_outputs=C)
    bound = SyncEngine(model, make_mesh(1), BATCH, LR, eval_chunk=64, virtual_workers=4,
                       optimizer="momentum").bind(data)
    assert not bound.update_sparse
    assert [np.shape(x) for x in bound.opt_state_leaves()] == [(304, 128)]
    w = bound.step(_weights(), jax.random.PRNGKey(3))
    assert w.shape == (D, C) and np.isfinite(np.asarray(w)).all()


# -- (viii) the scopes and the counters ----------------------------------------------------------

def _scopes(lowered):
    return set(re.findall(r"dsgd\.[a-z_]+", lowered.compile().as_text()))


@pytest.mark.parametrize("sparse", [False, True])
def test_the_compiled_programs_carry_their_scopes(sparse, monkeypatch):
    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0 if sparse else 10**9)
    bound = _bind(_rows())
    d, w, key = bound.data, _weights(), jax.random.PRNGKey(0)
    step = {"dsgd.draw", "dsgd.margins", "dsgd.coeff", "dsgd.scatter", "dsgd.update",
            "dsgd.allreduce", "dsgd.layout"}
    got = _scopes(bound._epoch.lower(w, bound._opt_state, d.indices, d.values, d.labels, key))
    assert got == step | ({"dsgd.rescale"} if sparse else {"dsgd.regularize"})
    assert {"dsgd.eval", "dsgd.margins", "dsgd.allreduce"} <= _scopes(
        bound._eval.lower(w, d.indices, d.values, d.labels))


def test_a_binding_with_outputs_is_counted_and_logged(caplog):
    import logging

    def count():
        return metrics_mod.global_metrics().counter("bind.outputs.multi").value

    before = count()
    _bind(_rows(n_outputs=1), n_outputs=1)
    assert count() == before
    data = _rows()
    model = make_model("hinge", LAM, D, regularizer="l2", n_outputs=C)
    trainer = SyncTrainer(model, make_mesh(1), BATCH, LR, virtual_workers=4,
                          metrics=metrics_mod.Metrics())
    with caplog.at_level(logging.INFO, logger="dsgd.trainer"):
        trainer.fit(data.slice(slice(0, 384)), data.slice(slice(384, None)), max_epochs=1)
    assert count() == before + 2  # the train and the test binding
    record = next(r for r in caplog.records if r.getMessage().startswith("train split:"))
    assert "kernel=gather" in record.getMessage() and "outputs=5" in record.getMessage()


# -- the qrels file: one parser, two views ---------------------------------------------------------

QRELS = """CCAT 1 1
C15 1 1
ECAT 2 1
CCAT 2 1
GCAT 3 1
CCAT 4 1
M14 4 1
MCAT 4 1
"""


def test_one_parser_keeps_every_topic_and_the_binary_view_keeps_its_quirk(tmp_path):
    path = tmp_path / "rcv1-v2.topics.qrels"
    path.write_text(QRELS)
    topics = read_topics(str(path))
    assert topics == {1: ["CCAT", "C15"], 2: ["ECAT", "CCAT"], 3: ["GCAT"],
                      4: ["CCAT", "M14", "MCAT"]}
    # last line wins (Dataset.scala:36-45): documents 1 and 4 are in CCAT and read -1
    assert read_labels(str(path)) == {1: -1, 2: 1, 3: -1, 4: -1}
    assert list(read_labels(str(path))) == [1, 2, 3, 4]


def test_load_rcv1_keeps_every_topic_when_asked(tmp_path):
    (tmp_path / "rcv1-v2.topics.qrels").write_text(QRELS)
    (tmp_path / "lyrl2004_vectors_train.dat").write_text(
        "3  5:0.5 9:0.25\n1  2:1.0\n4  7:0.5\n2  1:0.125 5:0.5\n")
    binary = load_rcv1(str(tmp_path), n_features=10)
    np.testing.assert_array_equal(binary.labels, [-1, -1, -1, 1])
    assert binary.labels.dtype == np.int32
    every = load_rcv1(str(tmp_path), n_features=10, labels="topics")
    np.testing.assert_array_equal(every.indices, binary.indices)
    assert every.labels.dtype == np.int8  # columns: C15 CCAT ECAT GCAT M14 MCAT
    np.testing.assert_array_equal(every.labels, [
        [-1, -1, -1, 1, -1, -1], [1, 1, -1, -1, -1, -1],
        [-1, 1, -1, -1, 1, 1], [-1, 1, 1, -1, -1, -1]])
    with pytest.raises(ValueError, match="'ccat' or 'topics'"):
        load_rcv1(str(tmp_path), labels="all")


def test_main_builds_the_model_its_labels_ask_for(monkeypatch):
    from distributed_sgd_tpu import main as program
    from distributed_sgd_tpu.config import Config

    monkeypatch.setenv("DSGD_SYNTHETIC", "400")
    train, test, model = program.build(Config(labels="topics"))
    assert train.labels.shape == (320, program.SYNTHETIC_TOPICS)
    assert (model.n_outputs, model.regularizer) == (program.SYNTHETIC_TOPICS, "l2")
    assert model.weight_shape == (train.n_features, program.SYNTHETIC_TOPICS)
    _train, _test, binary = program.build(Config())
    assert (binary.n_outputs, binary.regularizer) == (1, "dim_sparsity")
    with pytest.raises(ValueError, match="labels"):
        Config(labels="every")
