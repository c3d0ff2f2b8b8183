"""Driver of `SyncTrainer.fit` for a one-vs-rest fit whose labels come as ID
LISTS (`W[D, C]`, a row's positive ids among the C outputs the node holds,
`Dataset.n_labels`): `sync_mesh`'s fit, hook, window and
`train_samples_per_s` as they are, the checks against
`benchmark/reference_lists.py`.

What differs from `sync_outputs.run`, and why it is a file: (1) the model is
made with the configuration's `n_outputs` and the generator's rows carry
lists, which the problem is checked for; (2) the step check's probe is bound
with its lists (no pair is taken out: the configuration's loss has a
continuous derivative, `kink_guard` 0); (3) the step and the evaluation
compare with `reference_lists`, which expands the lists itself.  A sample is
a row with all the labels the node holds.
"""

from __future__ import annotations

import importlib
import time

from benchmark import checks, reference_lists
from benchmark.drivers.sync_mesh import (
    EPOCH_PROGRAM,
    EPOCH_RECORD,
    PROBE_ROWS_PER_DEVICE,
    _EpochHook,
    samples_per_second,
)
from benchmark.harness import LogTap, Run, TraceSession, problem_facts, program_config, rel_err, seeded_rows


def build_problem(ctx):
    """`harness.build_problem` with the configuration's output count and
    the generator's label lists."""
    import jax

    from distributed_sgd_tpu.models.linear import make_model

    cfg = ctx.cell.config
    gen = importlib.import_module(f"benchmark.gen.{cfg['generator']}")
    t0 = time.perf_counter()
    problem = gen.generate(cfg["data"], ctx.seed, ctx.devices, ctx.rehearse)
    jax.block_until_ready((problem.train.values, problem.test.labels))
    ctx.mark("rows_s", t0)
    outputs, width = int(cfg["n_outputs"]), int(cfg["data"]["label_list_width"])
    train = problem.train
    if train.n_labels != outputs or train.labels.shape[1:] != (width,):
        raise ValueError(f"the generator's labels {train.labels.shape} among "
                         f"{train.n_labels} are not lists of {width} among {outputs}")
    model = make_model(cfg["model"], float(cfg["lam"]), problem.n_features,
                       regularizer=cfg["regularizer"], n_outputs=outputs)
    return problem, model


def _step_check(trainer, problem, model_cfg: dict, w, lr: float, seed: int):
    """One synchronous step of the program against the reference, through
    the fit's own engine (same kernel, workers and batch) bound to a probe
    of seeded resident rows with their lists, at the fit's final weights.
    Which rows the step drew is read from the program's own sampler."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tol = model_cfg["tolerance"]
    loss, reg, lam = model_cfg["model"], model_cfg["regularizer"], float(model_cfg["lam"])
    train = problem.train
    n_probe = PROBE_ROWS_PER_DEVICE * trainer.engine.mesh.size
    w0 = np.asarray(w, np.float32)  # the fit's final weights, [D, C]
    w_ref = jnp.asarray(w0)  # a plain single-device copy for the reference

    idx, val, lists = seeded_rows(train, n_probe, seed)
    probe = type(train)(idx, val, lists, train.n_features, n_labels=train.n_labels)
    bound = trainer.engine.bind(probe)
    key = jax.random.PRNGKey(seed)
    draw = jax.jit(lambda k: bound._sample_ids(k, jnp.int32(0)))
    batches = []
    for d in range(bound.n_workers):
        drawn = np.asarray(draw(jax.random.fold_in(key, d))) + d * bound.shard_n
        for rows in drawn:  # one row of ids per (virtual) worker
            batches.append((jnp.asarray(probe.indices[rows]), jnp.asarray(probe.values[rows]),
                            jnp.asarray(probe.labels[rows])))
    w_sys = np.asarray(bound.step(jnp.asarray(w0), key))
    w_new = np.asarray(reference_lists.sync_step(loss, reg, w_ref, batches, lam, lr))
    err = rel_err(w_sys - w0, w_new - w0)
    return err <= float(tol["step_rel"]), {
        "update_rel_err": err, "tol": tol["step_rel"], "workers": len(batches),
        "rows": int(sum(b[1].shape[0] for b in batches)), "probe_rows": n_probe,
        "outputs": int(w0.shape[1]),
        "positives_in_the_step": int(sum(int((np.asarray(b[2]) >= 0).sum()) for b in batches)),
        "update_norm": float(np.linalg.norm(w_new - w0))}


def _evaluation_check(config: dict, w, test, reported_loss: float, reported_acc: float):
    """`checks.evaluation` against the reference that reads lists."""
    tol = config["tolerance"]
    ref_loss, ref_acc = reference_lists.evaluate(
        config["model"], w, test.indices, test.values, test.labels, float(config["lam"]))
    d_loss, d_acc = abs(reported_loss - ref_loss), abs(reported_acc - ref_acc)
    ok = d_loss <= float(tol["eval_loss_abs"]) and d_acc <= float(tol["eval_acc_abs"])
    return ok, {"reported_loss": reported_loss, "reference_loss": ref_loss,
                "reported_acc": reported_acc, "reference_acc": ref_acc,
                "loss_abs_err": d_loss, "acc_abs_err": d_acc,
                "loss_tol": tol["eval_loss_abs"], "acc_tol": tol["eval_acc_abs"]}


def run(ctx) -> Run:
    import numpy as np

    from distributed_sgd_tpu import compile_cache
    from distributed_sgd_tpu import main as program
    from distributed_sgd_tpu.core.trainer import SyncTrainer
    from distributed_sgd_tpu.parallel.mesh import make_mesh

    traffic, model_cfg = ctx.cell.traffic, ctx.cell.config
    tap = LogTap()
    problem, model = build_problem(ctx)
    cfg = program_config(ctx)

    # topology by the program's own rule, engine built as scenario_mesh builds it
    n_dev, virtual = program.select_topology(
        cfg.node_count, len(ctx.devices), cfg.use_async,
        cfg.virtual_workers, cfg.exact_topology)
    mesh = make_mesh(n_dev, devices=ctx.devices)
    extra = {"sampling": traffic["sampling"]} if "sampling" in traffic else {}
    trainer = SyncTrainer(
        model, mesh, batch_size=cfg.batch_size,
        learning_rate=cfg.learning_rate, seed=cfg.seed,
        kernel=cfg.kernel, virtual_workers=virtual,
        checkpointer=None, checkpoint_every=cfg.checkpoint_every,
        optimizer=cfg.optimizer, momentum=cfg.momentum,
        profile_dir=None, **extra)

    # keep the engines the fit binds: they say what an epoch is (steps,
    # batch, workers, kernel) and run the step check on the resident rows
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
        result = trainer.fit(problem.train, problem.test, max_epochs=10**9,
                             criterion=hook)
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
    lr = float(model_cfg["learning_rate"])
    ok_step, step = _step_check(trainer, problem, model_cfg, w, lr, ctx.seed)
    ok_eval, evaluation = _evaluation_check(
        model_cfg, w, problem.test, result.test_losses[-1], result.test_accuracies[-1])
    budget = int(ctx.cell.quality["budget_epochs"])
    ok_quality, quality = checks.quality(
        ctx.cell.quality,
        result.test_losses[budget - 1] if len(result.test_losses) >= budget else None)
    bad_epochs = sum(1 for j in range(warm, n) if not checks.all_finite(
        (result.losses[j], result.test_losses[j])))
    finite = bad_epochs == 0 and bool(np.all(np.isfinite(np.asarray(w))))
    # guarantees: the step averages over every worker the traffic names, and
    # the fit carried one column of weights an output
    outputs = int(model_cfg["n_outputs"])
    shaped = tuple(np.shape(w)) == (problem.n_features, outputs)
    guarantees = workers == cfg.node_count and ok_step and shaped
    the_checks = {
        "step_vs_reference": step, "evaluation_vs_reference": evaluation,
        "quality_at_budget": quality,
        "guarantees": {"workers": workers, "node_count": cfg.node_count,
                       "mean_over_all_workers": ok_step,
                       "weights": list(np.shape(w)), "outputs": outputs},
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
                "n_outputs": outputs,
                # bytes a (row, output) pair of the labels as they are stored
                "label_bytes": problem.train.labels.dtype.itemsize
                * problem.train.labels.shape[1] / outputs,
                "labels": bound_train.labels_as,
                "update": "sparse" if bound_train.update_sparse else "dense",
                **problem_facts(problem)},
        fit={"epochs_run": result.epochs_run, "losses": list(result.losses),
             "test_losses": list(result.test_losses),
             "test_accuracies": list(result.test_accuracies),
             "epoch_seconds": list(result.epoch_seconds),
             "period_seconds": [p["end"] - p["start"] for p in periods],
             "fit_seconds": t_end - t_fit, "trace_attempts": hook.attempts},
        trace_path=trace.path() if hook.kept else None,
        trace_opens_in=EPOCH_PROGRAM,
    )
