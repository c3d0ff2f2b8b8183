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
    assert bound.kernel == kernel and bound.plan.update == ("sparse" if sparse else "dense")
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
    assert (bound.kernel, bound.plan.update == "sparse", bound.n_workers) == ("gather", sparse, 4)
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
    rule = lambda d, c: kernels.sparse_update("gather", "l2", "sgd", 1e-7, d, c)  # noqa: E731
    assert rule(47_236, 103) and not rule(47_236, 1) and not rule(47_236, 84)
    assert rule(4_000_000, 1) and not rule(3_999_999, 1)
    assert not kernels.sparse_update("scalar", "l2", "sgd", 1e-7, 47_236, 103)
    assert not kernels.sparse_update("gather", "l2", "optax", 1e-7, 47_236, 103)


def test_an_optimizer_reads_a_gradient_with_the_output_axis():
    """optax state lives in the kernel's layout [D', L]; the step is the
    dense one whatever the rule's floor."""
    data = _rows()
    model = make_model("hinge", LAM, D, regularizer="l2", n_outputs=C)
    bound = SyncEngine(model, make_mesh(1), BATCH, LR, eval_chunk=64, virtual_workers=4,
                       optimizer="momentum").bind(data)
    assert bound.plan.update == "dense"
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


# -- (vi) the merge pass: a step's sorted entries added to W2 in ONE pass (PR 35) ------------------
#
# `gather._merge_rows` in Pallas' TPU interpret mode against the float64
# scatter-add and against the path it replaces (`scatter_rows_into` with
# XLA's write).  Small blocks, so that a few hundred weight rows hold every
# kind of block edge: (rows of W2, lanes, entries, block, piece of a block,
# pieces a product at most).

MERGE_SHAPES = {
    "default_constants": (3_000, 128, 3_000, gather.merge_block(128), gather.MERGE_SUB,
                          gather.MERGE_WIDE),
    "small_blocks": (328, 128, 3_000, 64, 16, 2),    # 328 = 5 x 64 + 8: a short last block
    "one_piece_a_block": (328, 128, 3_000, 32, 32, 1),
    "one_product_a_block": (328, 128, 3_000, 64, 16, 4),
    "one_block": (40, 128, 700, 64, 16, 4),          # D' under one block
    "two_lane_groups": (200, 256, 1_500, 64, 16, 4),  # C over 128
}
MERGE_CASES = ["law", "hot_run", "edges", "empty_blocks", "pads", "last_rows"]


def _merge_case(name, n_rows, n_entries, block):
    """ids of `n_entries` entries into `n_rows` weight rows."""
    rng = np.random.default_rng(35)
    # the generator's law: P(r) ~ ln(1 + 1/r), half the entries in the first rows
    ids = np.minimum(np.exp(rng.uniform(0, np.log(n_rows + 1), n_entries)).astype(np.int64) - 1,
                     n_rows - 1)
    if name == "hot_run":  # one id's run over many chunks and a whole block's worth of entries
        ids[: max(5 * gather.CHUNK, 2 * block)] = min(block + 3, n_rows - 1)
    elif name == "edges":  # the last row of every block and the first of the next, nothing between
        edges = np.arange(block, n_rows, block)
        ids = rng.choice(np.concatenate([edges - 1, edges, [0, n_rows - 1]]), n_entries)
    elif name == "empty_blocks":  # every other block has no entry; a chunk spans the gap
        ids = ids[(ids // block) % 2 == 0][: n_entries - 37]
    elif name == "pads":  # no whole chunk, and a tenth of the entries the pad entry itself
        ids = ids[: n_entries - 50]
        ids[::10] = 0
    elif name == "last_rows":  # the short last block, its last row too
        ids[:300] = rng.integers(max((n_rows - 1) // block * block - 4, 0), n_rows, 300)
        ids[300] = n_rows - 1
    return ids.astype(np.int32)


def _merge_inputs(shape, case):
    n_rows, lanes, n_entries, block, sub, wide = MERGE_SHAPES[shape]
    rng = np.random.default_rng(36)
    ids = _merge_case(case, n_rows, n_entries, block)
    values = rng.normal(size=len(ids)).astype(np.float32)
    if case == "pads":
        values[::10] = 0.0
    samples = 24
    src = rng.integers(0, samples, len(ids)).astype(np.int32)
    coeff = (rng.normal(size=(samples, lanes)) * 1e-2).astype(np.float32)
    w2 = (rng.normal(size=(n_rows, lanes)) * 3.0).astype(np.float32)
    if case == "hot_run":  # a weight whose ulp is the size of an entry's twentieth
        w2[min(block + 3, n_rows - 1)] = 4096.0
    return w2, ids, values, src, coeff, (block, sub, wide)


@pytest.mark.parametrize("case", MERGE_CASES)
@pytest.mark.parametrize("shape", sorted(MERGE_SHAPES))
def test_the_merge_pass_is_the_float64_scatter_add(shape, case):
    from jax.experimental.pallas import tpu as pltpu

    w2, ids, values, src, coeff, cut = _merge_inputs(shape, case)
    block = cut[0]
    rows = values[:, None].astype(np.float64) * coeff[src].astype(np.float64)
    want = w2.astype(np.float64)
    np.add.at(want, ids, rows)
    size = np.abs(w2).astype(np.float64)  # what float32 may round: every term's size
    np.add.at(size, ids, np.abs(rows))

    def merged(w2):
        at, entry = gather._entry_rows(jnp.asarray(ids), jnp.asarray(values),
                                       jnp.asarray(src), jnp.asarray(coeff))
        assert at.shape[0] % gather.CHUNK == 0
        return gather._merge_rows(w2, at, entry, *cut)

    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(jax.jit(merged)(jnp.asarray(w2)))
    # float32 sums of float32 rows: a product rounded to bfloat16 would be
    # off by 4e-3 of its size
    assert np.all(np.abs(got - want) <= 1e-6 * size)
    untouched = np.setdiff1d(np.arange(len(w2)), ids)
    np.testing.assert_array_equal(got[untouched], w2[untouched])
    # and the path it replaces, which sums the same rows in another order
    xla = np.asarray(jax.jit(gather.scatter_rows_into)(
        jnp.asarray(w2), jnp.asarray(ids), jnp.asarray(values), jnp.asarray(src),
        jnp.asarray(coeff)))
    assert np.all(np.abs(got - xla) <= 2e-6 * size)
    if case == "hot_run":  # summed before it meets its weight: ONE rounding at the weight's ulp
        hot = min(block + 3, len(w2) - 1)
        assert np.sum(ids == hot) > 4 * gather.CHUNK and np.all(w2[hot] == 4096.0)
        one_by_one = np.float32(4096.0) + np.zeros_like(w2[hot])
        for row in rows[ids == hot].astype(np.float32):
            one_by_one += row
        assert np.all(np.abs(got[hot] - want[hot])
                      <= 0.5 * np.spacing(np.float32(4096.0)) + 1e-6 * (size[hot] - 4096.0))
        assert np.abs(got[hot] - want[hot]).max() < 0.2 * np.abs(one_by_one - want[hot]).max()


def test_merged_through_scatter_rows_into_is_the_same_call():
    from jax.experimental.pallas import tpu as pltpu

    w2, ids, values, src, coeff, cut = _merge_inputs("default_constants", "law")
    args = tuple(jnp.asarray(a) for a in (w2, ids, values, src, coeff))
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(jax.jit(lambda *a: gather.scatter_rows_into(*a, "merge"))(*args))
        at, entry = gather._entry_rows(*args[1:])
        want = np.asarray(gather._merge_rows(args[0], at, entry, *cut))
    np.testing.assert_array_equal(got, want)


def test_the_three_pieces_sum_back_to_the_float32_bit_for_bit():
    rng = np.random.default_rng(37)
    x = np.concatenate([
        rng.normal(size=4096) * np.exp(rng.uniform(-60, 60, 4096)),
        [0.0, -0.0, 1.0, -1.0, 1 + 2**-23, 1 - 2**-24, 2.0**-103, 3e38, -3e38,
         np.float32(1 / 3), np.float32(np.pi)]]).astype(np.float32)
    hi, mid, lo = (np.asarray(p.astype(jnp.float32)) for p in jax.jit(gather.split3)(x))
    np.testing.assert_array_equal((hi + mid) + lo, x)
    assert all(p.dtype == jnp.bfloat16 for p in gather.split3(jnp.asarray(x)))
    # each piece takes its eight bits: the second is under an ulp of the first
    assert np.all(np.abs(mid) <= np.abs(x) * 2.0**-8)
    assert np.all(np.abs(lo) <= np.abs(x) * 2.0**-16)
    # under 2**-103 the later pieces' bits lie under float32's smallest
    # normal, which is flushed to zero: what is lost is under 2**-126
    tiny = (rng.normal(size=64) * 2.0**-120).astype(np.float32)
    hi, mid, lo = (np.asarray(p.astype(jnp.float32)) for p in gather.split3(jnp.asarray(tiny)))
    assert np.all(np.abs((hi + mid) + lo - tiny) <= 2.0**-126)


@pytest.fixture
def merging(monkeypatch):
    """The platform probe answers "a TPU", the sparse update has no floor
    and Pallas runs the kernels in its TPU interpret mode."""
    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops import mxu

    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)
    monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None: True)
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.mark.parametrize("reg", ["l2", "none"])
def test_an_epoch_with_the_merge_pass_is_the_epoch_xla_writes(reg, monkeypatch):
    """Three steps through `BoundSync` with the rule's answer forced on (the
    kernel interpreted) against the same steps written by XLA's scatter:
    one formulation up to the order a row's entries are summed in."""
    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops import mxu

    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)
    data, w, key = _rows(), _weights(), jax.random.PRNGKey(11)
    model = make_model("hinge", LAM, D, regularizer=reg, n_outputs=C)

    def epoch():
        bound = SyncEngine(model, make_mesh(1), BATCH, LR, eval_chunk=64,
                           virtual_workers=4).bind(data, 3)
        return bound, np.asarray(bound.epoch(w, key))

    bound, want = epoch()
    assert (bound.plan.update, bound.plan.scatter) == ("sparse", "words")
    monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None: True)
    with pltpu.force_tpu_interpret_mode():
        bound, got = epoch()
    assert bound.plan.scatter == "merge"
    moved = np.abs(want - np.asarray(w)).max()
    assert moved > 1e-3
    np.testing.assert_allclose(got - np.asarray(w), want - np.asarray(w), rtol=2e-5, atol=2e-7)


# -- the rule ----------------------------------------------------------------------------------

@pytest.mark.parametrize("features,outputs,entries,said", [
    (47_236, 103, 30_400, True),            # rcv1-topics-hinge: 1.55 rows an entry
    (47_236, 103, 4 * 30_400, True),        # its step over four chips' workers
    (47_236, 300, 30_400, True),            # three lane groups
    (30_400 * kernels.MERGE_MAX_ROWS_PER_ENTRY, 103, 30_400, True),
    (30_400 * kernels.MERGE_MAX_ROWS_PER_ENTRY + 1, 103, 30_400, False),  # past the crossing
    (54_686_452, 103, 4_400, False),        # kdd2012's feature count with outputs
    (47_236, 512, 30_400, True),
    (47_236, 513, 30_400, False),           # five lane groups: rows no block of the pass holds
    (203_882, 1_000, 28_800, False),        # amazoncat13k-dismec: 7.1 rows an entry, eight groups
])
def test_the_merge_rule_answers_from_shapes_alone(features, outputs, entries, said):
    assert kernels.merges_scatter(features, outputs, entries) is said


def _count(name):
    return metrics_mod.counter(name).value


def test_on_a_tpu_a_binding_with_outputs_merges_and_counts_it(merging, monkeypatch, caplog):
    import logging

    asked = []
    rule = kernels.merges_scatter
    monkeypatch.setattr(kernels, "merges_scatter", lambda *a: asked.append(a) or rule(*a))
    def counts():
        return tuple(_count(f"bind.scatter.{ending}") for ending in ("merge", "runs", "rows"))

    merge, runs, rows = counts()
    bound = _bind(_rows())
    assert (bound.plan.update, bound.plan.scatter) == ("sparse", "merge")
    assert asked == [(D, C, 4 * BATCH * P)]  # once a binding, the shapes alone
    assert counts() == (merge + 1, runs, rows)
    bound.step(_weights(), jax.random.PRNGKey(0))
    assert len(asked) == 1 and counts() == (merge + 1, runs, rows)  # no trace, no run
    # past the crossing the same binding walks its sorted entries' runs
    monkeypatch.setattr(kernels, "MERGE_MAX_ROWS_PER_ENTRY", 1)
    bound = _bind(_rows())
    assert bound.plan.scatter == "runs" and counts() == (merge + 1, runs + 1, rows)
    # one output: never asked, whatever its shapes would say, and a DMA a row of words
    monkeypatch.setattr(kernels, "MERGE_MAX_ROWS_PER_ENTRY", 10**6)
    asked.clear()
    flat = _bind(_rows(n_outputs=1), n_outputs=1, kernel="gather")
    assert (flat.plan.update, flat.plan.scatter) == ("sparse", "rows") and not asked
    assert counts() == (merge + 1, runs + 1, rows + 1)
    # and the train-split record says which
    model = make_model("hinge", LAM, D, regularizer="l2", n_outputs=C)
    data = _rows()
    with caplog.at_level(logging.INFO, logger="dsgd.trainer"):
        SyncTrainer(model, make_mesh(1), BATCH, LR, virtual_workers=4).fit(
            data.slice(slice(0, 384)), data.slice(slice(384, None)), max_epochs=1)
    record = next(r.getMessage() for r in caplog.records
                  if r.getMessage().startswith("train split:"))
    assert "update=sparse scatter=merge outputs=5" in record


def test_off_the_tpu_no_binding_merges(monkeypatch):
    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)
    monkeypatch.setattr(kernels, "merges_scatter", lambda *a: pytest.fail("asked off the TPU"))
    merge = _count("bind.scatter.merge")
    bound = _bind(_rows())
    assert (bound.plan.update, bound.plan.scatter) == ("sparse", "words")
    assert _count("bind.scatter.merge") == merge


def test_the_merged_epoch_program_carries_the_scatters_scope(merging):
    bound = _bind(_rows())
    assert bound.plan.scatter == "merge"
    d, w, key = bound.data, _weights(), jax.random.PRNGKey(0)
    lowered = bound._epoch.lower(w, bound._opt_state, d.indices, d.values, d.labels, key)
    assert _scopes(lowered) == {
        "dsgd.draw", "dsgd.margins", "dsgd.coeff", "dsgd.scatter", "dsgd.update",
        "dsgd.allreduce", "dsgd.layout", "dsgd.rescale"}
    assert "dsgd.scatter/scatter_merge" in lowered.as_text(debug_info=True)
