"""Driver of `SyncTrainer.fit` under per-coordinate FTRL-Proximal
(`optimizer='ftrl'`, McMahan et al., KDD 2013, Algorithm 1): `sync_mesh`'s
fit, hook, window and `train_samples_per_s` as they are, the checks against
`benchmark/reference_ftrl.py`.

What differs from `sync_mesh.run`, and why it is a file: (1) before a row
is made it asks the program whether it takes `optimizer='ftrl'` and raises
if not, so a program without FTRL fails in seconds; (2) the trainer is
built with the configuration's `ftrl` block (alpha is `learning_rate`, the
L2 strength `lam`); (3) the checks compare the optimizer STATE (z, n) of a
step and the closed-form weights, the objective's two parts, and L1's exact
zeros, against `reference_ftrl`.
"""

from __future__ import annotations

import dataclasses
import time

from benchmark import checks, reference_ftrl
from benchmark.drivers.sync_mesh import (
    EPOCH_PROGRAM,
    EPOCH_RECORD,
    PROBE_ROWS_PER_DEVICE,
    _EpochHook,
    samples_per_second,
)
from benchmark.harness import LogTap, Run, TraceSession, build_problem, problem_facts, program_config, seeded_rows


def program_config_ftrl(ctx):
    """`harness.program_config` with the configuration's FTRL block, checked
    by the program's own Config; raises where the program has no FTRL."""
    spec = ctx.cell.config["ftrl"]
    try:
        from distributed_sgd_tpu import main as program
        from distributed_sgd_tpu.ops import ftrl  # noqa: F401

        cfg = dataclasses.replace(program_config(ctx), optimizer="ftrl", l1=float(spec["l1"]))
        optimizer = program.optimizer_of(cfg)
    except (ImportError, AttributeError, TypeError, ValueError) as e:
        raise RuntimeError(f"the program does not take optimizer='ftrl': {e}") from e
    return cfg, optimizer


def trainer_for(ctx, model, cfg, optimizer):
    """The fit's SyncTrainer, built as `main.scenario_mesh` builds it."""
    from distributed_sgd_tpu import main as program
    from distributed_sgd_tpu.core.trainer import SyncTrainer
    from distributed_sgd_tpu.parallel.mesh import make_mesh

    n_dev, virtual = program.select_topology(
        cfg.node_count, len(ctx.devices), cfg.use_async, cfg.virtual_workers,
        cfg.exact_topology)
    traffic = ctx.cell.traffic
    extra = {"sampling": traffic["sampling"]} if "sampling" in traffic else {}
    return SyncTrainer(
        model, make_mesh(n_dev, devices=ctx.devices), batch_size=cfg.batch_size,
        learning_rate=cfg.learning_rate, seed=cfg.seed, kernel=cfg.kernel,
        virtual_workers=virtual, checkpointer=None, checkpoint_every=cfg.checkpoint_every,
        optimizer=optimizer, momentum=cfg.momentum, profile_dir=None, **extra)


def hyper(cfg_file: dict):
    """(alpha, beta, l1, l2) as the configuration states them."""
    spec = cfg_file["ftrl"]
    l2 = float(cfg_file["lam"]) if cfg_file["regularizer"] == "l2" else 0.0
    return float(cfg_file["learning_rate"]), float(spec["beta"]), float(spec["l1"]), l2


def _near_threshold(z, l1: float, guard: float):
    """Coordinates whose |z| lies within `guard` of l1, where a rounding can
    put the closed form on either side of its threshold."""
    import numpy as np

    return np.abs(np.abs(z) - l1) <= guard


def _rel(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def _step_check(trainer, problem, cfg_file: dict, state, w, seed: int):
    """One synchronous step of the fit's own engine (its kernel, workers and
    batch) bound to a probe of seeded resident rows, from the fit's final
    (z, n), against `reference_ftrl.sync_step` from the same state: the
    step's change of z, of n and of the closed-form w, each relative to
    the reference's; coordinates within `threshold_guard` of l1 are left
    out of the w comparison only.  Which rows the step drew is read from
    the program's own sampler."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_sgd_tpu.ops import ftrl

    tol = cfg_file["tolerance"]
    alpha, beta, l1, l2 = hyper(cfg_file)
    train = problem.train
    d = train.n_features
    n_probe = PROBE_ROWS_PER_DEVICE * trainer.engine.mesh.size
    idx, val, y = seeded_rows(train, n_probe, seed)
    probe = type(train)(idx, val, y, d)
    bound = trainer.engine.bind(probe)
    bound.load_opt_state_leaves([state])
    key = jax.random.PRNGKey(seed)
    draw = jax.jit(lambda k: bound._sample_ids(k, jnp.int32(0)))
    batches = []
    for dev in range(bound.n_workers):
        drawn = np.asarray(draw(jax.random.fold_in(key, dev))) + dev * bound.shard_n
        for rows in drawn:  # one row of ids per (virtual) worker
            batches.append((jnp.asarray(probe.indices[rows]), jnp.asarray(probe.values[rows]),
                            jnp.asarray(probe.labels[rows])))
    w1 = np.asarray(bound.step(w, key))
    z0, n0 = (np.asarray(a) for a in ftrl.coordinates(state, d))
    z1, n1 = (np.asarray(a) for a in ftrl.coordinates(bound.opt_state_leaves()[0], d))
    zr, nr, g = reference_ftrl.sync_step(
        cfg_file["model"],
        jnp.asarray(z0), jnp.asarray(n0), batches, alpha, beta, l1, l2)
    wr0 = np.asarray(reference_ftrl.weights(z0, n0, alpha, beta, l1, l2))
    wr1 = np.asarray(reference_ftrl.weights(zr, nr, alpha, beta, l1, l2))
    zr, nr, g = np.asarray(zr), np.asarray(nr), np.asarray(g)
    w0 = np.asarray(w)
    moved_ref = g != 0
    moved_sys = (z1 != z0) | (n1 != n0)
    at = moved_ref | moved_sys  # every other coordinate is equal in both, bit for bit
    guarded = _near_threshold(zr, l1, float(tol["threshold_guard"])) | _near_threshold(
        z1, l1, float(tol["threshold_guard"]))
    keep = at & ~guarded
    errs = {"z": _rel(z1[at] - z0[at], zr[at] - z0[at]),
            "n": _rel(n1[at] - n0[at], nr[at] - n0[at]),
            "w": _rel(w1[keep] - w0[keep], wr1[keep] - wr0[keep])}
    limits = {k: float(tol[f"step_{k}_rel"]) for k in errs}
    ok = all(errs[k] <= limits[k] for k in errs) and not (moved_sys & ~moved_ref).any()
    return ok, {
        "z_rel_err": errs["z"], "n_rel_err": errs["n"], "w_rel_err": errs["w"],
        "tol": limits, "coordinates_moved": int(moved_ref.sum()),
        "moved_off_the_reference": int((moved_sys & ~moved_ref).sum()),
        "left_out_near_l1": int((at & guarded).sum()),
        "workers": len(batches), "rows": int(sum(b[1].shape[0] for b in batches)),
        "probe_rows": n_probe}


def _evaluation_check(cfg_file: dict, params, w, test, reported_loss: float,
                      reported_acc: float):
    """The objective the fit reported for `w` on the test split, by its two
    parts (the mean loss and the penalty the program computed, whose sum it
    is), and its accuracy, against the reference's over the whole split."""
    from distributed_sgd_tpu.ops import ftrl

    tol = cfg_file["tolerance"]
    _alpha, _beta, l1, l2 = hyper(cfg_file)
    ref_obj, ref_acc, ref_loss, ref_pen = reference_ftrl.evaluate(
        cfg_file["model"], w, test.indices, test.values, test.labels, l1, l2)
    pen = ftrl.penalty(w, params)
    d_loss = abs((reported_loss - pen) - ref_loss)
    d_pen = abs(pen - ref_pen) / max(ref_pen, 1e-300)
    d_acc = abs(reported_acc - ref_acc)
    ok = (d_loss <= float(tol["eval_loss_abs"]) and d_pen <= float(tol["eval_penalty_rel"])
          and d_acc <= float(tol["eval_acc_abs"]))
    return ok, {"reported_objective": reported_loss, "reference_objective": ref_obj,
                "reported_penalty": pen, "reference_penalty": ref_pen,
                "reference_mean_loss": ref_loss, "reported_acc": reported_acc,
                "reference_acc": ref_acc, "loss_abs_err": d_loss, "penalty_rel_err": d_pen,
                "acc_abs_err": d_acc, "loss_tol": tol["eval_loss_abs"],
                "penalty_tol": tol["eval_penalty_rel"], "acc_tol": tol["eval_acc_abs"]}


def _state_check(cfg_file: dict, state, w):
    """Finite state, n >= 0, and the weights' nonzero count against the
    reference's closed form of the same state, to within the coordinates
    near l1."""
    import numpy as np

    from distributed_sgd_tpu.ops import ftrl

    alpha, beta, l1, l2 = hyper(cfg_file)
    d = np.shape(w)[0]
    z, n = (np.asarray(a) for a in ftrl.coordinates(state, d))
    ref_nonzero = int(np.count_nonzero(np.asarray(
        reference_ftrl.weights(z, n, alpha, beta, l1, l2))))
    nonzero = int(np.count_nonzero(np.asarray(w)))
    near = int(_near_threshold(z, l1, float(cfg_file["tolerance"]["threshold_guard"])).sum())
    touched = int((n > 0).sum())
    finite = bool(np.isfinite(z).all() and np.isfinite(n).all())
    ok = finite and bool((n >= 0).all()) and abs(nonzero - ref_nonzero) <= near
    return ok, {"finite_state": finite, "n_min": float(n.min()), "nonzero": nonzero,
                "reference_nonzero": ref_nonzero, "near_l1": near, "touched": touched,
                "zero_share_of_touched": (touched - nonzero) / max(touched, 1)}


def _quality_check(quality_file: dict, result):
    """Both bands at the budget: the test objective's (`loss_band`), which
    l1 ||w||_1 dominates, and the mean test loss's (`mean_loss_band`), the
    objective less the penalty the fit recorded for the same epoch."""
    budget = int(quality_file["budget_epochs"])
    reached = len(result.test_losses) >= budget and len(result.penalty) >= budget
    objective = result.test_losses[budget - 1] if reached else None
    mean_loss = objective - result.penalty[budget - 1] if reached else None
    ok_objective, said = checks.quality(quality_file, objective)
    ok_loss, said_loss = checks.quality({"loss_band": quality_file["mean_loss_band"]}, mean_loss)
    return ok_objective and ok_loss, dict(said, budget_mean_loss=said_loss["budget_loss"],
                                          mean_loss_band=said_loss["loss_band"])


def run(ctx) -> Run:
    import numpy as np

    from distributed_sgd_tpu import compile_cache

    # the program must take FTRL before any row is made: a program without it
    # fails here, in seconds
    cfg, optimizer = program_config_ftrl(ctx)
    traffic, cfg_file = ctx.cell.traffic, ctx.cell.config
    tap = LogTap()
    problem, model = build_problem(ctx)
    trainer = trainer_for(ctx, model, cfg, optimizer)

    # keep the engines the fit binds: they say what an epoch is (steps,
    # batch, workers, kernel) and hold the final state
    bounds = []
    bind = trainer.engine.bind

    def tapped_bind(data, *a, **k):
        bound = bind(data, *a, **k)
        bounds.append(bound)
        return bound

    trainer.engine.bind = tapped_bind

    trace = TraceSession(ctx.trace_dir) if ctx.trace else None
    hook = _EpochHook(
        traffic["warm_epochs"], ctx.seconds, trace,
        epoch_seconds=lambda: float(tap.last("dsgd.trainer", EPOCH_RECORD)[3][-1]),
        compile_count=lambda: sum(compile_cache.counts()))
    t_fit = time.perf_counter()
    try:
        result = trainer.fit(problem.train, problem.test, max_epochs=10**9, criterion=hook)
    finally:
        hook.cancel()
        tap.close()
    t_end = time.perf_counter()

    warm = hook.warm
    n = len(hook.entries)
    if n <= warm:
        raise RuntimeError(f"the fit ended after {n} epochs, inside its {warm} warm epochs")
    bound_record = tap.first("dsgd.trainer", "train split:")
    ctx.setup["bind_s"] = (bound_record[0] if bound_record else t_fit) - t_fit
    ctx.setup["warm_s"] = hook.entries[warm - 1] - t_fit - ctx.setup["bind_s"]
    bound_train = bounds[0]
    workers = bound_train.n_workers * bound_train.virtual_workers
    samples_per_epoch = bound_train.steps_per_epoch * bound_train.batch_size * workers
    periods = [{"epoch": j, "start": hook.exits[j - 1], "end": hook.entries[j],
                "work_s": result.epoch_seconds[j]} for j in range(warm, n)]

    w = result.weights
    state = bound_train.opt_state_leaves()[0]
    ok_step, step = _step_check(trainer, problem, cfg_file, state, w, ctx.seed)
    ok_eval, evaluation = _evaluation_check(
        cfg_file, bound_train.ftrl, w, problem.test, result.test_losses[-1],
        result.test_accuracies[-1])
    ok_state, state_said = _state_check(cfg_file, state, w)
    ok_quality, quality = _quality_check(ctx.cell.quality, result)
    bad_epochs = sum(1 for j in range(warm, n) if not checks.all_finite(
        (result.losses[j], result.test_losses[j])))
    finite = bad_epochs == 0 and bool(np.all(np.isfinite(np.asarray(w))))
    # guarantees: the step averages over every worker the traffic names,
    # untouched coordinates keep (z, n), w is the closed form of the state
    guarantees = workers == cfg.node_count and ok_step and ok_state
    the_checks = {
        "step_vs_reference": step, "evaluation_vs_reference": evaluation,
        "quality_at_budget": quality,
        "guarantees": {"workers": workers, "node_count": cfg.node_count,
                       "mean_over_all_workers": ok_step, "state": state_said},
        "finite": finite}
    return Run(
        ctx=ctx,
        correct=bool(ok_step and ok_eval and ok_quality and guarantees and finite),
        checks=the_checks,
        attempted=len(periods), failed=bad_epochs,
        end_to_end={"train_samples_per_s": samples_per_second(periods, samples_per_epoch)},
        window_start=hook.entries[warm - 1],
        window_seconds=hook.entries[-1] - hook.exits[warm - 1],
        compiles=tuple(hook.compiles),
        periods=periods,
        engine={"kernel": bound_train.kernel, "devices": bound_train.n_workers,
                "virtual_workers": bound_train.virtual_workers,
                "batch_size": bound_train.batch_size,
                "steps_per_epoch": bound_train.steps_per_epoch,
                "samples_per_epoch": samples_per_epoch,
                "optimizer": bound_train.plan.optimizer,
                "update": bound_train.plan.update, "scatter": bound_train.plan.scatter,
                **problem_facts(problem)},
        fit={"epochs_run": result.epochs_run, "losses": list(result.losses),
             "test_losses": list(result.test_losses),
             "test_accuracies": list(result.test_accuracies),
             "nonzero": list(result.nonzero), "penalty": list(result.penalty),
             "epoch_seconds": list(result.epoch_seconds),
             "period_seconds": [p["end"] - p["start"] for p in periods],
             "fit_seconds": t_end - t_fit, "trace_attempts": hook.attempts},
        trace_path=trace.path() if hook.kept else None,
        trace_opens_in=EPOCH_PROGRAM,
    )
