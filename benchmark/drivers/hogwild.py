"""Driver of `HogwildEngine.fit`: the path `main.py` takes for an
asynchronous fit in gossip mode (`main.scenario_mesh`, `use_async`).

The fit paces itself on the host: worker threads dispatch compiled local
steps and gossip deltas through host memory, the coordinator evaluates the
smoothed test loss at most every `backoff_s` seconds and hands the history
to its `criterion`.  The benchmark's criterion answers True once the window
is over; a benchmark thread owns the window: it waits for warm-up (the
first loss check done, `warm_steps_per_worker` local steps per worker
counted), then samples the `slave.async.*` counters at the window's start,
once a second, and at its end.
"""

from __future__ import annotations

import threading
import time

from benchmark import checks, reference
from benchmark.harness import LogTap, Run, TraceSession, build_problem, problem_facts, program_config, rel_err, seeded_rows

COUNTERS = ("slave.async.batch", "slave.async.grad.update", "slave.async.grad.dropped")
TRACE_S = 1.0  # a traced run stops its profiler at the first one-second tick past this: ~2 s, ~500 dispatches


class _Window(threading.Thread):
    """Waits for warm-up, then holds the window open for `seconds`."""

    def __init__(self, metrics, checks_done, warm_steps: int, seconds: float,
                 trace, compile_count):
        super().__init__(name="bench-window", daemon=True)
        self.metrics, self.checks_done = metrics, checks_done
        self.warm_steps, self.seconds = warm_steps, float(seconds)
        self.trace = trace
        self.compile_count = compile_count
        self.samples = []  # (perf_counter, {counter: value})
        self.compiles = [None, None]
        self.over = threading.Event()
        self.abandon = threading.Event()  # the fit ended first
        self.start_time = None

    def _read(self):
        # the time first: a reading is never older than its timestamp
        now = time.perf_counter()
        self.samples.append(
            (now, {c: self.metrics.counter(c).value for c in COUNTERS}))
        return now

    def run(self) -> None:
        import jax

        batch = self.metrics.counter("slave.async.batch")
        while not (self.checks_done() >= 1 and batch.value >= self.warm_steps):
            if self.abandon.wait(0.005):
                return
        self.compiles[0] = self.compile_count()
        self.start_time = self._read()
        if self.trace is not None:
            self.trace.start()
        annotation = None
        tick = 0
        while True:
            if self.trace is not None and self.trace.running:
                if annotation is not None:
                    annotation.__exit__(None, None, None)
                    annotation = None
                if time.perf_counter() - self.trace.started_at >= TRACE_S:
                    self.trace.stop()
                else:
                    annotation = jax.profiler.TraceAnnotation("bench.second", tick=tick)
                    annotation.__enter__()
            tick += 1
            due = self.start_time + min(tick, self.seconds)
            if self.abandon.wait(max(0.0, due - time.perf_counter())):
                break
            now = self._read()
            if now - self.start_time >= self.seconds:
                break
        if annotation is not None:
            annotation.__exit__(None, None, None)
        if self.trace is not None and self.trace.running:
            self.trace.stop()
        self.compiles[1] = self.compile_count()
        self.over.set()


def _kernel_check(engine, model, problem, model_cfg: dict, w, seed: int):
    """The worker's compiled k-step program against the reference, one
    seeded resident row at a time: on a one-row shard every draw is that
    row, so the summed delta of the k local steps is known without
    knowing the program's sampler."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_sgd_tpu.parallel.hogwild import _Worker

    tol = model_cfg["tolerance"]
    loss, reg, lam = model_cfg["model"], model_cfg["regularizer"], float(model_cfg["lam"])
    guard = float(tol.get("kink_guard", 0.0))
    n_rows = int(tol.get("async_rows", 32))
    train = problem.train
    ds = None if problem.dim_sparsity is None else jnp.asarray(problem.dim_sparsity)
    idx, val, y = seeded_rows(train, 4 * n_rows, seed)
    w0 = jnp.asarray(np.asarray(w, np.float32))
    worker = _Worker(
        0, model, type(train)(idx[:1], val[:1], y[:1], train.n_features),
        engine.devices[0], engine.batch_size, engine.learning_rate, seed,
        engine.metrics, steps_per_dispatch=engine.steps_per_dispatch,
        optimizer=engine.optimizer, momentum=engine.momentum)
    if worker._opt is not None:
        return False, {"error": "the kernel check covers plain SGD only"}
    key = jax.random.PRNGKey(seed)
    worst, used = 0.0, 0
    for r in range(idx.shape[0]):
        bi, bv, by = (jnp.asarray(a[r:r + 1]) for a in (idx, val, y))
        dist = reference.kink_distance(loss, w0, bi, bv, by)
        if guard and dist is not None and float(jnp.min(dist)) < guard:
            continue
        delta, _ = worker._step(w0, None, bi, bv, by, key)
        bs = engine.batch_size
        ref = reference.local_steps(
            loss, reg, w0, jnp.tile(bi, (bs, 1)), jnp.tile(bv, (bs, 1)),
            jnp.tile(by, bs), lam, engine.learning_rate, worker.k, ds)
        worst = max(worst, rel_err(delta, ref))
        used += 1
        if used == n_rows:
            break
    ok = used == n_rows and worst <= float(tol["step_rel"])
    return ok, {"delta_rel_err_max": worst, "tol": tol["step_rel"], "rows": used,
                "steps_per_dispatch": worker.k, "blocked": bool(worker._blocked)}


def run(ctx) -> Run:
    import numpy as np

    from distributed_sgd_tpu import compile_cache
    from distributed_sgd_tpu.parallel.hogwild import HogwildEngine
    from distributed_sgd_tpu.utils.metrics import Metrics

    traffic, model_cfg = ctx.cell.traffic, ctx.cell.config
    tap = LogTap()
    problem, model = build_problem(ctx)
    cfg = program_config(ctx)
    metrics = Metrics()
    # the engine as scenario_mesh builds it; the only addition is the
    # Metrics object the counters are read from
    engine = HogwildEngine(
        model, n_workers=cfg.node_count, batch_size=cfg.batch_size,
        learning_rate=cfg.learning_rate, check_every=cfg.check_every,
        leaky_loss=cfg.leaky_loss, seed=cfg.seed, checkpointer=None,
        steps_per_dispatch=cfg.steps_per_dispatch,
        optimizer=cfg.optimizer, momentum=cfg.momentum,
        compress=cfg.compress, compress_k=cfg.compress_k,
        compress_ef=cfg.compress_ef, gossip_topology=cfg.gossip_topology,
        devices=ctx.devices, metrics=metrics)

    check_times = []
    trace = TraceSession(ctx.trace_dir) if ctx.trace else None
    window = _Window(
        metrics, lambda: len(check_times),
        int(traffic["warm_steps_per_worker"]) * cfg.node_count, ctx.seconds,
        trace, lambda: sum(compile_cache.counts()))

    def criterion(smoothed_newest_first) -> bool:
        check_times.append(time.perf_counter())
        return window.over.is_set()

    window.start()
    t_fit = time.perf_counter()
    try:
        result = engine.fit(problem.train, problem.test, max_epochs=10**6,
                            criterion=criterion)
    finally:
        window.abandon.set()
        window.join()
    t_end = time.perf_counter()
    tap.close()
    if not window.over.is_set() or len(window.samples) < 2:
        raise RuntimeError("the fit ended before the window did")

    started = tap.first("dsgd.hogwild", "hogwild kernel=")
    ctx.setup["bind_s"] = (started[0] if started else t_fit) - t_fit
    ctx.setup["warm_s"] = window.start_time - t_fit - ctx.setup["bind_s"]
    (t0, c0), (t1, c1) = window.samples[0], window.samples[-1]
    seconds = t1 - t0
    steps = c1["slave.async.batch"] - c0["slave.async.batch"]
    k = engine.steps_per_dispatch
    destinations = cfg.node_count  # n - 1 peers and the coordinator
    dropped = c1["slave.async.grad.dropped"] - c0["slave.async.grad.dropped"]

    # every loss check the program logged: (updates, smoothed loss, smoothed accuracy)
    logged = [r[3] for r in tap.all("dsgd.hogwild", "loss computed at")]
    budget = int(ctx.cell.quality["budget_updates"])
    at_budget = next((float(a[1]) for a in logged if a[0] >= budget), None)
    ok_quality, quality = checks.quality(ctx.cell.quality, at_budget)

    w = result.weights
    ok_kernel, kernel = _kernel_check(engine, model, problem, model_cfg, w, ctx.seed)
    # fit returns the BEST weights; the loss it reports for them is the
    # smoothed one, s_t = c*raw_t + (1-c)*s_(t-1), so the raw evaluation of
    # those weights is recovered from the series before it is compared
    hist = list(result.test_losses)
    accs = list(result.test_accuracies)
    best = int(np.argmin(hist))
    c = engine.leaky_loss
    raw = [(s[best] - (1 - c) * (s[best - 1] if best else s[best])) / c
           for s in (hist, accs)]
    ok_eval, evaluation = checks.evaluation(model_cfg, w, problem.test, raw[0], raw[1])
    evaluation["best_check"] = best

    # guarantees: every dispatch reached the coordinator; every delta sent
    # to a peer was applied, dropped-and-counted, or is still in an inbox
    end = {n: metrics.counter(n).value for n in COUNTERS}
    pushed = (end["slave.async.batch"] // k) * (cfg.node_count - 1)
    unaccounted = pushed - end["slave.async.grad.update"] - end["slave.async.grad.dropped"]
    guarantees = {
        "coordinator_updates": int(result.state.updates),
        "local_steps": end["slave.async.batch"], "steps_per_dispatch": k,
        "pushed_to_peers": pushed, "applied": end["slave.async.grad.update"],
        "dropped": end["slave.async.grad.dropped"], "left_in_inboxes": unaccounted}
    ok_guarantees = (int(result.state.updates) == end["slave.async.batch"]
                     and 0 <= unaccounted <= cfg.node_count * 1024)
    finite = checks.all_finite(hist) and bool(np.all(np.isfinite(np.asarray(w))))
    return Run(
        ctx=ctx,
        correct=bool(ok_kernel and ok_eval and ok_quality and ok_guarantees and finite),
        checks={"kernel_vs_reference": kernel, "evaluation_vs_reference": evaluation,
                "quality_at_budget": quality, "guarantees": guarantees, "finite": finite},
        attempted=(steps // k) * destinations, failed=dropped,
        end_to_end={"async_samples_per_s": steps * cfg.batch_size / seconds},
        window_start=window.start_time, window_seconds=seconds,
        compiles=tuple(window.compiles),
        counters={n: {"start": c0[n], "end": c1[n],
                      "samples": [(t, s[n]) for t, s in window.samples]}
                  for n in COUNTERS},
        engine={"workers": cfg.node_count, "batch_size": cfg.batch_size,
                "steps_per_dispatch": k, "check_every": engine.check_every,
                **problem_facts(problem)},
        fit={"updates": int(result.state.updates), "test_losses": hist,
             "test_accuracies": accs, "loss_checks": [list(a) for a in logged],
             "budget_loss": at_budget, "fit_seconds": t_end - t_fit},
        trace_path=trace.path() if trace is not None else None,
    )
