"""Per-coordinate FTRL-Proximal in the mesh sync engine (ops/ftrl.py,
`BoundSync._sparse_step` / `_ftrl_step`): one step against a float64 NumPy
transcription of McMahan et al.'s Algorithm 1, the sparse step against the
dense one, the coordinates a step does not touch, the L1 threshold, the
plan, the scope, the row-function ending on a TPU, the refusals of every
engine without (z, n), and `main.py` end to end.  Small shapes on the CPU:
rows with one id in every row, one of three ids in every row, a pad entry,
and features no row holds."""

import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.ops import ftrl, gather, kernels, mxu
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine
from distributed_sgd_tpu.utils import metrics as metrics_mod

D, P, N = 6000, 11, 4096
ALPHA, L1, LAM = 0.5, 0.01, 1e-3
BETA = 1.0  # McMahan et al. section 3.1, the program's ftrl.BETA
EVERY_ROW, PAD = 5, 0
UNUSED = 500  # the last features are in no row
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rows(seed=0, n=N):
    rng = np.random.default_rng(seed)
    idx = rng.integers(6, D - UNUSED, (n, P)).astype(np.int32)
    idx[:, 0] = rng.integers(1, 4, n)
    idx[:, 1] = EVERY_ROW
    idx[:, -1] = PAD
    val = np.full((n, P), 1.0 / np.sqrt(P), np.float32)
    val[:, -1] = 0.0
    y = rng.choice([-1, 1], n, p=[0.8, 0.2]).astype(np.int32)
    return Dataset(idx, val, y, D)


@pytest.fixture
def sparse(monkeypatch):
    """The sparse step without its floor on the feature count."""
    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)


def _bind(data, devices=1, workers=4, batch=25, steps=None, l1=L1, kernel="gather"):
    model = make_model("logistic", LAM, D, regularizer="l2")
    return SyncEngine(model, make_mesh(devices), batch, ALPHA, kernel=kernel,
                      virtual_workers=workers,
                      optimizer=ftrl.Ftrl(l1=l1)).bind(data, steps)


def _draws(bound, key):
    """ids [all workers, batch] of a step at index 0, device by device as
    the program's own sampler draws them."""
    per_device = []
    for d in range(bound.n_workers):
        ids = jax.jit(lambda k: bound._sample_ids(k, jnp.int32(0)))(jax.random.fold_in(key, d))
        per_device.append(np.asarray(ids) + d * bound.shard_n)
    return np.concatenate(per_device, axis=0)


def _closed_form(z, n, l1=L1):
    w = -(z - np.sign(z) * l1) / ((BETA + np.sqrt(n)) / ALPHA + LAM)
    return np.where(np.abs(z) <= l1, 0.0, w)


def _float64_step(data, draws, z, n, l1=L1):
    """Algorithm 1 in float64: g the mean over all workers of their
    batch sums of the logistic gradient at the closed-form w; every
    coordinate with g != 0 takes the update."""
    z, n = z.astype(np.float64), n.astype(np.float64)
    w = _closed_form(z, n, l1)
    idx, val, y = data.indices, data.values.astype(np.float64), data.labels
    g = np.zeros(D)
    for rows in draws:
        m = (val[rows] * w[idx[rows]]).sum(axis=1)
        c = -y[rows] / (1.0 + np.exp(y[rows] * m))
        np.add.at(g, idx[rows].reshape(-1), (c[:, None] * val[rows]).reshape(-1))
    g /= len(draws)
    sigma = (np.sqrt(n + g * g) - np.sqrt(n)) / ALPHA
    moved = g != 0
    return np.where(moved, z + g - sigma * w, z), np.where(moved, n + g * g, n), g


def _pack(z, n):
    """The state `[R2, 128]` of flat (z[D], n[D]): z in lanes 0-63, n in 64-127."""
    rows = ftrl.zeros(D).shape[0]
    half = lambda x: jnp.pad(jnp.asarray(x, jnp.float32), (0, rows * ftrl.HALF - D)).reshape(  # noqa: E731
        rows, ftrl.HALF)
    return jnp.concatenate([half(z), half(n)], axis=1)


def _state(bound):
    z, n = ftrl.coordinates(bound.opt_state_leaves()[0], D)
    return np.asarray(z), np.asarray(n)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


# -- one step against the equations ------------------------------------------------

@pytest.mark.parametrize("form", ["sparse", "dense"])
@pytest.mark.parametrize("devices,workers", [(1, 1), (1, 4), (4, 1), (4, 4)])
def test_one_step_follows_the_float64_equations(form, devices, workers, monkeypatch):
    if form == "sparse":
        monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)
    data = _rows()
    bound = _bind(data, devices=devices, workers=workers, steps=30)
    assert bound.plan.update == form and bound.plan.optimizer == "ftrl"
    bound.epoch(jnp.zeros(D), jax.random.PRNGKey(3))  # a state of 30 steps
    z0, n0 = _state(bound)
    key = jax.random.PRNGKey(11)
    w = np.asarray(bound.step(jnp.zeros(D), key))
    z1, n1 = _state(bound)
    z_ref, n_ref, g = _float64_step(data, _draws(bound, key), z0, n0)
    assert _rel(z1 - z0, z_ref - z0) < 1e-5
    assert _rel(n1 - n0, n_ref - n0) < 1e-5
    # w handed out is the closed form of the new state, L1's zeros exact
    clear = np.abs(np.abs(z_ref) - L1) > 1e-6
    np.testing.assert_allclose(w[clear], _closed_form(z_ref, n_ref)[clear],
                               rtol=1e-5, atol=1e-7)
    assert np.array_equal(w[clear] == 0, _closed_form(z_ref, n_ref)[clear] == 0)
    assert (g != 0).sum() > 100 and (w == 0).sum() > 0


# -- the sparse step against the dense one --------------------------------------

def test_the_sparse_step_follows_the_dense_step_over_2000_steps(monkeypatch):
    data = _rows()
    dense = _bind(data, steps=2000)
    monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)
    sparse = _bind(data, steps=2000)
    assert (dense.plan.update, sparse.plan.update) == ("dense", "sparse")
    key = jax.random.PRNGKey(5)
    w_dense = np.asarray(dense.epoch(jnp.zeros(D), key))
    w_sparse = np.asarray(sparse.epoch(jnp.zeros(D), key))
    (zd, nd), (zs, ns) = _state(dense), _state(sparse)
    assert _rel(zs, zd) < 1e-5 and _rel(ns, nd) < 1e-5
    assert _rel(w_sparse, w_dense) < 1e-5
    assert np.array_equal(w_sparse == 0, w_dense == 0)
    assert (nd[D - UNUSED:] == 0).all() and (zd[D - UNUSED:] == 0).all()


@pytest.mark.parametrize("form", ["sparse", "dense"])
def test_a_coordinate_no_entry_touches_keeps_z_and_n_bit_for_bit(form, monkeypatch):
    if form == "sparse":
        monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)
    data = _rows()
    bound = _bind(data)
    rng = np.random.default_rng(7)
    z0 = rng.normal(size=D).astype(np.float32) * 0.1
    n0 = rng.uniform(0.0, 4.0, D).astype(np.float32)
    bound.load_opt_state_leaves([_pack(z0, n0)])
    key = jax.random.PRNGKey(2)
    bound.step(jnp.zeros(D), key)
    z1, n1 = _state(bound)
    touched = np.zeros(D, bool)
    touched[data.indices[_draws(bound, key).reshape(-1)].reshape(-1)] = True
    assert touched.sum() < D // 2
    assert np.array_equal(z1[~touched].view(np.int32), z0[~touched].view(np.int32))
    assert np.array_equal(n1[~touched].view(np.int32), n0[~touched].view(np.int32))
    assert (n1[touched & (n1 != n0)] > n0[touched & (n1 != n0)]).all()


# -- the L1 threshold --------------------------------------------------------------

def test_the_l1_threshold_gives_exact_zeros_on_one_side_only():
    p = ftrl.Params(ALPHA, L1, LAM)
    z = jnp.asarray([-0.5, -L1 * 1.001, -L1, -L1 * 0.5, 0.0, L1 * 0.5, L1, L1 * 1.001, 0.5])
    n = jnp.full(z.shape, 2.0)
    w = np.asarray(ftrl.weights(z, n, p))
    assert (w[2:7] == 0).all()
    assert (w[[0, 1]] > 0).all() and (w[[7, 8]] < 0).all()  # w takes the sign of -z
    np.testing.assert_allclose(w, _closed_form(np.asarray(z, np.float64), 2.0),
                               rtol=1e-6, atol=1e-9)


def test_a_fit_with_l1_has_exact_zeros_among_the_touched_coordinates(sparse):
    bound = _bind(_rows(), steps=200, l1=0.02)
    w = np.asarray(bound.epoch(jnp.zeros(D), jax.random.PRNGKey(1)))
    z, n = _state(bound)
    touched = n > 0
    zeros = touched & (w == 0)
    assert 0 < zeros.sum() < touched.sum()
    assert (np.abs(z[zeros]) <= 0.02).all() and (np.abs(z[touched & (w != 0)]) > 0.02).all()


# -- the plan, the scope, the row ending ---------------------------------------------

@pytest.mark.parametrize("platform,scatter", [("tpu", "rows"), ("cpu", "words")])
def test_the_plan_takes_the_sparse_step_and_counts_ftrl(platform, scatter, monkeypatch):
    monkeypatch.setattr(mxu, "blocked_pays_off", lambda device=None: platform == "tpu")
    model = make_model("logistic", 1.5e-7, 54_686_452, regularizer="l2")
    counter = metrics_mod.counter("bind.optimizer.ftrl")
    before = counter.value
    plan = kernels.plan(model, learning_rate=0.1, optimizer="ftrl", row_width=11,
                        virtual_workers=4, batch_size=100, n_workers=1, eval_chunk=4096)
    assert (plan.update, plan.scatter, plan.optimizer, plan.decay) == (
        "sparse", scatter, "ftrl", 0.0)
    assert "update=sparse" in plan.record() and plan.record().endswith("optimizer=ftrl")
    assert counter.value - before == 1
    assert kernels.sparse_update("gather", "l2", "ftrl", 0.0, 54_686_452)
    assert not kernels.sparse_update("gather", "dim_sparsity", "ftrl", 0.0, 54_686_452)


def test_a_binding_refuses_a_plan_made_for_another_update(monkeypatch):
    """The plan decides which update runs: a binding under FTRL handed a
    plan for the plain update refuses it rather than run one and record
    the other."""
    import dataclasses

    made = kernels.plan
    monkeypatch.setattr(kernels, "plan", lambda *a, **k: dataclasses.replace(
        made(*a, **k), optimizer="sgd"))
    with pytest.raises(ValueError, match="plan is for optimizer='sgd'"):
        _bind(_rows(n=256))


@pytest.mark.parametrize("form", ["sparse", "dense"])
def test_the_epoch_program_carries_the_ftrl_scope(form, monkeypatch):
    if form == "sparse":
        monkeypatch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)
    bound = _bind(_rows(n=256))
    d = bound.data
    lowered = bound._epoch.lower(jnp.zeros(D), bound.opt_state_leaves()[0], d.indices,
                                 d.values, d.labels, jax.random.PRNGKey(0))
    text = lowered.as_text(debug_info=True)
    assert ftrl.SCOPE in text and "dsgd.margins" in text


def test_the_row_function_ending_writes_what_xla_writes():
    """The kernel of ours (`_write_rows`, interpreted) and XLA's write end a
    step of FTRL on the same bits, a hot id's entries summed first."""
    from jax.experimental.pallas import tpu as pltpu

    rng = np.random.default_rng(4)
    p = ftrl.Params(ALPHA, L1, LAM)
    state = _pack(rng.normal(size=D).astype(np.float32) * 0.05,
                  rng.uniform(0, 3, D).astype(np.float32))
    ids = jnp.asarray(np.concatenate([rng.integers(0, D - UNUSED, 3000),
                                      np.full(400, EVERY_ROW)]).astype(np.int32))
    updates = jnp.asarray(rng.normal(size=ids.shape[0]).astype(np.float32) * 0.01)

    def ending(how):
        return jax.jit(lambda s, i, u: gather.scatter_into(
            s, i, u, how, row=lambda old, total: ftrl.rows(old, total, p),
            per_row=ftrl.HALF))(state, ids, updates)

    with pltpu.force_tpu_interpret_mode():
        by_kernel = np.asarray(ending("rows"))
    by_xla = np.asarray(ending("words"))
    assert np.array_equal(by_kernel.view(np.int32), by_xla.view(np.int32))
    assert not np.array_equal(by_xla, np.asarray(state))


# -- what holds no (z, n) refuses it ---------------------------------------------------

def _refusals():
    from distributed_sgd_tpu import checkpoint
    from distributed_sgd_tpu.core.trainer import SyncTrainer
    from distributed_sgd_tpu.core.worker import WorkerNode
    from distributed_sgd_tpu.parallel.hogwild import HogwildEngine
    from distributed_sgd_tpu.parallel.local_sgd import LocalSGDEngine

    model = make_model("logistic", LAM, D, regularizer="l2")
    return {
        "hogwild": lambda: HogwildEngine(model, n_workers=2, batch_size=8, learning_rate=0.1,
                                         optimizer="ftrl"),
        "local_sgd": lambda: LocalSGDEngine(model, make_mesh(1), batch_size=8,
                                            learning_rate=0.1, optimizer=ftrl.Ftrl()),
        "rpc_worker": lambda: WorkerNode("127.0.0.1", 0, "127.0.0.1", 1, _rows(n=64), model)
        .start_async(np.zeros(D, np.float32), np.arange(64), 8, 0.1, optimizer="ftrl"),
        "checkpoint": lambda: checkpoint.sync_fit_extra([0.5], "ftrl", []),
        "fit_state": lambda: checkpoint.save_fit_state(
            "/nonexistent/fit_state.npz", weights=np.zeros(D), epoch=0, batch=0, rng_state={},
            test_losses_nf=[], opt_kind="ftrl", opt_leaves=[]),
        "sync_trainer_checkpointer": lambda: SyncTrainer(
            model, make_mesh(1), 8, 0.1, checkpointer=object(), optimizer="ftrl"),
        "output_axis": lambda: SyncEngine(
            make_model("logistic", LAM, D, regularizer="l2", n_outputs=3), make_mesh(1), 8, 0.1,
            optimizer="ftrl").bind(Dataset(_rows(n=64).indices, _rows(n=64).values,
                                           np.ones((64, 3), np.int32), D)),
    }


@pytest.mark.parametrize("who", sorted(_refusals()))
def test_every_engine_without_the_state_refuses_ftrl(who):
    with pytest.raises(ValueError, match="FTRL"):
        _refusals()[who]()


# -- the trainer and the entry point ---------------------------------------------------

def test_the_trainer_says_the_optimizer_and_the_nonzero_weights(sparse, caplog):
    from distributed_sgd_tpu.core.trainer import SyncTrainer

    rows = _rows()
    model = make_model("logistic", LAM, D, regularizer="l2")
    trainer = SyncTrainer(model, make_mesh(1), 25, ALPHA, kernel="gather", virtual_workers=4,
                          optimizer=ftrl.Ftrl(l1=L1))
    with caplog.at_level(logging.INFO, logger="dsgd.trainer"):
        res = trainer.fit(rows, rows, max_epochs=2)
    said = [r.getMessage() for r in caplog.records]
    record = next(m for m in said if m.startswith("train split:"))
    assert "update=sparse" in record and "optimizer=ftrl" in record
    assert len(res.nonzero) == 2 and res.nonzero[-1] == int((np.asarray(res.weights) != 0).sum())
    assert any(f"nonzero={res.nonzero[-1]} " in m for m in said if m.startswith("epoch 1:"))
    w = np.asarray(res.weights)
    loss = res.losses[-1]
    penalty = L1 * np.abs(w).sum() + 0.5 * LAM * (w.astype(np.float64) ** 2).sum()
    assert loss > penalty > 0 and np.isfinite(loss)
    assert len(res.penalty) == 2 and res.penalty[-1] == pytest.approx(penalty, rel=1e-5)
    with pytest.raises(ValueError, match="starts from its state"):
        trainer.fit(rows, rows, max_epochs=1, initial_weights=np.ones(D, np.float32))


def test_main_fits_a_small_ftrl_problem_end_to_end(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
               DSGD_SYNTHETIC="300", DSGD_MAX_EPOCHS="2", DSGD_NODE_COUNT="2",
               DSGD_BATCH_SIZE="16", DSGD_MODEL="logistic", DSGD_OPTIMIZER="ftrl",
               DSGD_FTRL_L1="0.001", DSGD_LEARNING_RATE="0.1")
    proc = subprocess.run([sys.executable, "-m", "distributed_sgd_tpu.main"], cwd=str(tmp_path),
                          env=env, timeout=240, capture_output=True, text=True)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-3000:]
    assert "fit done" in out and "optimizer=ftrl" in out and "nonzero=" in out
