"""The checks of `kdd2012-ftrl-sync-1chip` (benchmark/drivers/sync_ftrl.py)
refuse what they are there to refuse.  One small fit on the CPU (the
configuration's generator, fields cut a thousandfold, rehearsal rows, the
cell's `gather` family and sparse step without its floor), then the
driver's step and evaluation checks on it as the program computes them,
and again with a fault planted in the program's step or evaluation: the state left unchanged, half of the
workers' batches dropped, the values or the state rounded to bfloat16.
Every planted fault must read `ok` False, through the tolerances the
configuration states; the quality check on both bands at the budget."""

import dataclasses
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness
from benchmark.drivers import sync_ftrl
from benchmark.harness import ROOT

CELL = "kdd2012-ftrl-sync-1chip"
SEED = 4300000911


def _bf16(x):
    return jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)


@pytest.fixture(scope="module")
def fitted():
    """(trainer, problem, configuration, state, w, test bound) of a
    two-epoch fit of the cell's FTRL on a cut of its rows."""
    from distributed_sgd_tpu.ops import kernels

    patch = pytest.MonkeyPatch()
    patch.setattr(kernels, "SPARSE_UPDATE_MIN_FEATURES", 0)
    bench = harness.load_benchmark(ROOT)
    cell = harness.load_cell(bench, CELL, ROOT)
    data = dict(cell.config["data"])
    fields = {k: max(3, v // 1000) for k, v in data["field_cardinalities"].items()}
    data.update(field_cardinalities=fields, n_features=sum(fields.values()))
    cell = dataclasses.replace(cell, config=dict(cell.config, data=data))
    ctx = harness.Context(cell=cell, seed=SEED, seconds=1.0, trace=False, rehearse=True,
                          t_process=time.perf_counter(), devices=jax.devices()[:1],
                          device={}, peaks=None, trace_dir="")
    problem, model = harness.build_problem(ctx)
    cfg, optimizer = sync_ftrl.program_config_ftrl(ctx)
    # the cell's family: at 54 k features the rule would pick the one-hot kernels
    trainer = sync_ftrl.trainer_for(ctx, model, dataclasses.replace(cfg, kernel="gather"),
                                    optimizer)
    bounds = []
    bind = trainer.engine.bind
    trainer.engine.bind = lambda d, *a: bounds.append(bind(d, *a)) or bounds[-1]
    result = trainer.fit(problem.train, problem.test, max_epochs=2)
    trainer.engine.bind = bind
    assert bounds[0].plan.update == "sparse" and bounds[0].plan.optimizer == "ftrl"
    yield SimpleNamespace(trainer=trainer, problem=problem, cfg=cell.config,
                          state=bounds[0].opt_state_leaves()[0], w=result.weights,
                          params=bounds[0].ftrl, result=result)
    patch.undo()


def _plant(monkeypatch, fault):
    """Put `fault` into the program's sync step, as the probe's binding
    will trace it."""
    from distributed_sgd_tpu.ops import ftrl
    from distributed_sgd_tpu.parallel import sync

    if fault == "state_left_unchanged":
        monkeypatch.setattr(sync.BoundSync, "step", lambda self, w, key: w)
        return
    if fault == "bf16_state":
        matvec, rows = ftrl.matvec, ftrl.rows
        monkeypatch.setattr(ftrl, "matvec", lambda batch, state, p: matvec(batch, _bf16(state), p))
        monkeypatch.setattr(ftrl, "rows", lambda old, total, p: rows(_bf16(old), total, p))
        return
    draw = sync.BoundSync.draw_rows

    def drawn(self, idx, val, y, ids):
        bi, bv, by = draw(self, idx, val, y, ids)
        if fault == "bf16_values":
            return bi, _bf16(bv), by
        assert fault == "half_the_workers" and bv.ndim == 3  # [workers, batch, entries]
        kept = jnp.arange(bv.shape[0]) < bv.shape[0] // 2
        return bi, bv * kept[:, None, None], by

    monkeypatch.setattr(sync.BoundSync, "draw_rows", drawn)


def test_the_step_check_takes_the_programs_own_step(fitted):
    ok, said = sync_ftrl._step_check(fitted.trainer, fitted.problem, fitted.cfg,
                                     fitted.state, fitted.w, SEED)
    assert ok, said
    assert said["coordinates_moved"] > 1000 and said["moved_off_the_reference"] == 0


@pytest.mark.parametrize("fault", ["state_left_unchanged", "half_the_workers", "bf16_values",
                                   "bf16_state"])
def test_the_step_check_refuses_a_planted_fault(fitted, monkeypatch, fault):
    _plant(monkeypatch, fault)
    ok, said = sync_ftrl._step_check(fitted.trainer, fitted.problem, fitted.cfg,
                                     fitted.state, fitted.w, SEED)
    assert not ok, said
    if fault in ("half_the_workers", "bf16_values"):  # refused by the limits themselves
        assert said["moved_off_the_reference"] == 0
        assert any(said[f"{k}_rel_err"] > said["tol"][k] for k in ("z", "n", "w"))


@pytest.mark.parametrize("fault", [None, "bf16_w", "bf16_values"])
def test_the_evaluation_check_refuses_an_evaluation_in_bfloat16(fitted, fault):
    test, w = fitted.problem.test, fitted.w
    evaluated, weights = test, w
    if fault == "bf16_values":
        evaluated = type(test)(test.indices, _bf16(test.values), test.labels, test.n_features)
    if fault == "bf16_w":
        weights = _bf16(w)
    objective, acc = fitted.trainer.evaluate(weights, evaluated)
    ok, said = sync_ftrl._evaluation_check(fitted.cfg, fitted.params, w, test, objective, acc)
    assert ok == (fault is None), said


def test_the_state_check_holds_the_fits_state(fitted):
    ok, said = sync_ftrl._state_check(fitted.cfg, fitted.state, fitted.w)
    assert ok and said["finite_state"] and 0 < said["nonzero"] < said["touched"], said


def test_the_quality_check_holds_both_bands_at_the_budget():
    quality = {"budget_epochs": 3, "loss_band": [754.3, 973.9], "mean_loss_band": [0.15, 0.16]}

    def fit(objectives, penalties):
        return SimpleNamespace(test_losses=objectives, penalty=penalties)

    assert sync_ftrl._quality_check(quality, fit([280.0, 560.0, 850.155, 1e3],
                                                 [279.8, 559.8, 850.0, 1e3]))[0]
    for objectives, penalties in (
            ([280.0, 560.0, 1050.155], [279.8, 559.8, 1050.0]),  # L1 not applied
            ([280.0, 560.0, 650.155], [279.8, 559.8, 650.0]),  # half of the steps
            ([280.0, 560.0, 850.17], [279.8, 559.8, 850.0]),  # the loss out of its band
            ([280.0, 560.0], [279.8, 559.8])):  # the budget not reached
        ok, said = sync_ftrl._quality_check(quality, fit(objectives, penalties))
        assert not ok, said
    said = sync_ftrl._quality_check(quality, fit([280.0, 560.0, 850.155], [279.8, 559.8, 850.0]))[1]
    assert said["budget_mean_loss"] == pytest.approx(0.155) and said["mean_loss_band"] == [0.15, 0.16]
