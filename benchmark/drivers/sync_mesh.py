"""Driver of `SyncTrainer.fit` over a device mesh: the path `main.py`
takes for a synchronous fit (`main.scenario_mesh`).

One fit per process, because a fit binds its rows itself.  The window lives
inside that fit: `fit` calls its `criterion` at the end of every epoch
(after the epoch's train and test evaluation), so a criterion that
timestamps each call and answers True once the deadline has passed bounds
the fit by time and marks every epoch boundary without touching the
program.  The first `warm_epochs` epochs are set-up: they compile the
epoch program (twice, PERF.md section 5) and both evaluation programs.
"""

from __future__ import annotations

import statistics
import threading
import time
from typing import Optional

from benchmark import checks, reference
from benchmark.harness import LogTap, Run, TraceSession, build_problem, problem_facts, program_config, rel_err, seeded_rows


PROBE_ROWS_PER_DEVICE = 4096


EPOCH_PROGRAM = "_epoch_shard"  # the compiled epoch's name on the trace's `XLA Modules` line
EPOCH_RECORD = "epoch %d:"      # dsgd.trainer's record of an epoch; its last argument is epoch_s


class _EpochHook:
    """The criterion handed to `fit`: called once per epoch, at its end.

    A traced run records the last steps of one epoch program, the
    evaluation and the loop that follow it, up to the boundary (a whole
    epoch of the flagship is 650 k device events, of the four-chip cell ten
    million).  The program's own log record says how long the epoch that
    just ended ran (`epoch_s`, from the hook's return to the epoch program's
    end), and epochs repeat to a part in a thousand, so a timer asks for the
    profiler `LEAD_S` seconds before the next epoch program is due to end.

    At the next boundary the same record says when that program really
    ended.  The trace is kept if the profiler was recording `MIN_STEPS_S`
    before that (the window then opens inside the epoch program, which the
    reducer checks again on the trace itself) and has run `MIN_S`; where
    periods are shorter than that it runs on over whole periods.  A
    profiler that came up too late holds evaluation only: where another
    period would cost more than `MAX_S` of trace, that trace is dropped and
    the next one asked for with twice the lead, `ATTEMPTS` times in all."""

    LEAD_S = 0.25  # the profiler takes 0.05 s to come up on one chip, 0.13 s on four
    MIN_STEPS_S = 0.01
    MIN_S = 0.2
    MAX_S = 1.0
    ATTEMPTS = 3

    def __init__(self, warm: int, seconds: float, trace, epoch_seconds, compile_count):
        self.warm, self.seconds = int(warm), float(seconds)
        self.trace, self.lead = trace, self.LEAD_S
        self.epoch_seconds = epoch_seconds  # -> epoch_s of the epoch that just ended
        self.compile_count = compile_count
        self.entries, self.exits = [], []  # perf_counter at each call's entry and exit
        self.compiles = [None, None]
        self.attempts, self.kept = [], False  # per attempt: what the hook saw
        self._annotation = None
        self._timer = None

    def _annotate(self, name: str, **stats) -> None:
        import jax

        self._annotation = jax.profiler.TraceAnnotation(name, **stats)
        self._annotation.__enter__()

    def _close_annotation(self) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None

    def cancel(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer.join()
        if self.trace is not None and self.trace.running:
            self.trace.stop()

    def _traced_boundary(self, now: float, n: int, epoch_s: float) -> Optional[float]:
        """Keep, continue or drop the running trace; the delay after which
        to ask for the next one, if any."""
        trace = self.trace
        program_end = self.exits[-1] + epoch_s
        steps_s = program_end - trace.started_at  # of this epoch program, in the trace
        seen = {"epoch": n, "lead": self.lead, "start_took_s": trace.started_at - trace.requested_at,
                "steps_s": steps_s, "traced_s": now - trace.started_at}
        if steps_s >= self.MIN_STEPS_S and now - trace.started_at >= self.MIN_S:
            self._annotate("bench.boundary", epoch=n)
            self._close_annotation()
            trace.stop()
            self.kept = True
            self.attempts.append(dict(seen, kept=True))
        elif steps_s < self.MIN_STEPS_S and now - self.exits[-1] > self.MAX_S:
            trace.stop()
            self.attempts.append(dict(seen, kept=False))
            self.lead *= 2.0
            if len(self.attempts) < self.ATTEMPTS:
                return max(0.0, epoch_s - self.lead)
        return None

    @property
    def trace_done(self) -> bool:
        return self.trace is None or self.kept or len(self.attempts) >= self.ATTEMPTS

    def __call__(self, losses_newest_first) -> bool:
        now = time.perf_counter()
        self.entries.append(now)
        n = len(self.entries)
        self._close_annotation()
        stop, arm = False, None
        if n == self.warm:
            self.compiles[0] = self.compile_count()
        elif n > self.warm:
            if not self.trace_done:
                epoch_s = self.epoch_seconds()
                if self.trace.running:
                    arm = self._traced_boundary(now, n, epoch_s)
                elif self._timer is None:
                    arm = max(0.0, epoch_s - self.lead)
            past = now - self.exits[self.warm - 1]
            # a profiler that never came up must not hold the fit for ever
            if past >= self.seconds and (self.trace_done or past >= 3 * self.seconds + 60):
                stop = True
                self.compiles[1] = self.compile_count()
        if not stop and self.trace is not None and self.trace.running:
            # one host span per epoch period on the profiler's clock
            self._annotate("bench.epoch", epoch=n)
        self.exits.append(time.perf_counter())
        if arm is not None:
            self._timer = threading.Timer(arm, self.trace.start)
            self._timer.daemon = True
            self._timer.start()
        return stop


def samples_per_second(periods, samples_per_epoch: int) -> float:
    """Samples of one epoch over the MEDIAN epoch period of the window
    (boundary to boundary: the epoch program, both evaluations, the loop).

    The median and not the window's total: where periods are short
    (`epsilon`: 57 ms, a quarter of it host work) a run holds some hundreds
    of them, and what spreads two runs of the same code is a few periods
    that a stall on a shared host stretched by 100 ms and more (PERF.md
    section 6, third round).  The median over the whole window leaves those out; what
    slows every epoch moves it as it moves the total."""
    return samples_per_epoch / statistics.median(p["end"] - p["start"] for p in periods)


def _step_check(trainer, problem, model_cfg: dict, w, lr: float, seed: int):
    """One synchronous step of the program against the reference: every
    worker's reply is the regularised SUM over its batch, the update is
    w - lr * mean over ALL workers.  The step runs through the fit's own
    engine (same kernel, workers and batch) bound to a probe of seeded
    resident rows; rows within `kink_guard` of a jump of `backward` are
    left out of the probe (a rounding there flips a whole row and says
    nothing of precision).  Which rows the step drew is read from the
    program's own sampler."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tol = model_cfg["tolerance"]
    loss, reg, lam = model_cfg["model"], model_cfg["regularizer"], float(model_cfg["lam"])
    guard = float(tol.get("kink_guard", 0.0))
    train = problem.train
    dense = train.indices.shape[1] == 0
    n_dev = trainer.engine.mesh.size
    n_probe = PROBE_ROWS_PER_DEVICE * n_dev
    w0 = np.asarray(w, np.float32)  # the fit's final weights
    w_ref = jnp.asarray(w0)  # a plain single-device copy for the reference
    ds = None if problem.dim_sparsity is None else jnp.asarray(problem.dim_sparsity)

    idx, val, y = seeded_rows(train, 2 * n_probe, seed)
    dist = reference.kink_distance(
        loss, w_ref, None if dense else jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y))
    keep = np.arange(len(y)) if dist is None or not guard else np.flatnonzero(
        np.asarray(dist) >= guard)
    if len(keep) < n_probe:
        return False, {"error": f"only {len(keep)} of {len(y)} rows clear of the kink"}
    keep = keep[:n_probe]
    probe = type(train)(idx[keep], val[keep], y[keep], train.n_features)
    bound = trainer.engine.bind(probe)
    key = jax.random.PRNGKey(seed)
    draw = jax.jit(lambda k: bound._sample_ids(k, jnp.int32(0)))
    batches = []
    for d in range(bound.n_workers):
        drawn = np.asarray(draw(jax.random.fold_in(key, d))) + d * bound.shard_n
        for rows in drawn:  # one row of ids per (virtual) worker
            batches.append((None if dense else jnp.asarray(probe.indices[rows]),
                            jnp.asarray(probe.values[rows]),
                            jnp.asarray(probe.labels[rows])))
    w_sys = np.asarray(bound.step(jnp.asarray(w0), key))
    w_new = np.asarray(reference.sync_step(loss, reg, w_ref, batches, lam, lr, ds))
    err = rel_err(w_sys - w0, w_new - w0)
    return err <= float(tol["step_rel"]), {
        "update_rel_err": err, "tol": tol["step_rel"], "workers": len(batches),
        "rows": int(sum(b[1].shape[0] for b in batches)),
        "probe_rows": n_probe, "kept_of": [int(len(keep)), int(len(y))]}


def run(ctx) -> Run:
    import jax
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
    bound_train, bound_test = bounds[0], bounds[1]
    workers = bound_train.n_workers * bound_train.virtual_workers
    samples_per_epoch = bound_train.steps_per_epoch * bound_train.batch_size * workers
    periods = [{"epoch": j, "start": hook.exits[j - 1], "end": hook.entries[j],
                "work_s": result.epoch_seconds[j]} for j in range(warm, n)]

    w = result.weights
    lr = float(model_cfg["learning_rate"])
    ok_step, step = _step_check(trainer, problem, model_cfg, w, lr, ctx.seed)
    ok_eval, evaluation = checks.evaluation(
        model_cfg, w, problem.test, result.test_losses[-1], result.test_accuracies[-1])
    budget = int(ctx.cell.quality["budget_epochs"])
    ok_quality, quality = checks.quality(
        ctx.cell.quality,
        result.test_losses[budget - 1] if len(result.test_losses) >= budget else None)
    bad_epochs = sum(1 for j in range(warm, n) if not checks.all_finite(
        (result.losses[j], result.test_losses[j])))
    finite = bad_epochs == 0 and bool(np.all(np.isfinite(np.asarray(w))))
    # guarantee: the step averages over every worker the traffic names
    guarantees = workers == cfg.node_count and ok_step
    the_checks = {
        "step_vs_reference": step, "evaluation_vs_reference": evaluation,
        "quality_at_budget": quality,
        "guarantees": {"workers": workers, "node_count": cfg.node_count,
                       "mean_over_all_workers": ok_step},
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
