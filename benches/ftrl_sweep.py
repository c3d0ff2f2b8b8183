"""The readings `kdd2012-ftrl` (benchmark/configs/kdd2012-ftrl.json) and its
quality band (benchmark/quality/kdd2012-ftrl-sync-1chip.json) were set
from, on the chip, at the cell's full size.

A sweep, not the benchmark: no number of it is a ledger number.  Every fit
is `SyncTrainer.fit` as `benchmark/drivers/sync_ftrl.py` builds it, for
`budget_epochs` (3) epochs from (z, n) = 0, on the cell's own rows:

- `layout`: us a step of the sparse FTRL step with the state as ONE
  `[D/64, 128]` array (z in lanes 0-63, n in 64-127; `ops/ftrl.py`) and as
  two `[D/128, 128]` arrays, 2,000 steps of 400 uniform rows;
- `alpha`: the test objective after each epoch at l1 = 0, alpha 0.03 .. 3;
- `l1`: the quartiles of |z| over the touched coordinates at the chosen
  alpha, and a fit at each: the share of them exactly zero, the mean loss;
- `band`: one fit a seed (`--seeds`, default 4300000101-4300000107): the
  test objective and the mean test loss after each epoch, what the two
  quality bands are set from;
- `faults`: on the first seed, what the bands read for two faults of the
  fit: L1 not applied by the update (a fit at l1 = 0, its objective read
  with the cell's l1), and half of every epoch's steps dropped;
- `controls`: at the last band fit's final state, the driver's step,
  evaluation and state checks in float32, and the reference's step and
  evaluation at the same state with the state, the weights or the values
  rounded to bfloat16 and float16: what the tolerances were set from.

    python benches/ftrl_sweep.py [--rehearse] [--only band,faults,...]
                                 [--seeds 4300000101-4300000114]

Prints one `<label>: {json}` line a reading.  Refuses a CPU unless
`--rehearse` (rows cut to the configuration's rehearsal block, full width).
"""

from __future__ import annotations

import functools
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "kdd2012-ftrl-sync-1chip"
SECTIONS = ("layout", "alpha", "l1", "band", "faults", "controls")
T0 = time.perf_counter()


def say(label, obj):
    print(f"{label}: " + json.dumps(dict(obj, at_s=round(time.perf_counter() - T0, 1)),
                                    default=float), flush=True)


def seeds_of(argv):
    if "--seeds" not in argv:
        return list(range(4300000101, 4300000108))
    lo, _, hi = argv[argv.index("--seeds") + 1].partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv) -> int:
    import numpy as np

    from benchmark import harness, reference_ftrl
    from benchmark.drivers import sync_ftrl

    rehearse = "--rehearse" in argv
    only = argv[argv.index("--only") + 1].split(",") if "--only" in argv else SECTIONS
    bench = harness.load_benchmark(ROOT)
    cell = harness.load_cell(bench, CELL, ROOT)
    devices, device, peaks = harness.check_devices(1, rehearse)
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.ops import ftrl, gather, mxu
    from distributed_sgd_tpu.ops.sparse import SparseBatch

    say("device", device)
    budget = int(cell.quality["budget_epochs"])
    l1_cell = float(cell.config["ftrl"]["l1"])
    lam = float(cell.config["lam"])

    def ctx_for(seed):
        return harness.Context(cell=cell, seed=seed, seconds=1.0, trace=False,
                               rehearse=rehearse, t_process=time.perf_counter(),
                               devices=devices, device=device, peaks=peaks,
                               trace_dir=os.devnull)

    def fit(ctx, problem, model, alpha, l1, half_steps=False, keep=False):
        """One fit of `budget` epochs; `half_steps` binds the train split
        with half of its steps an epoch."""
        cell.config["learning_rate"] = alpha
        cell.config["ftrl"]["l1"] = l1
        cfg, opt = sync_ftrl.program_config_ftrl(ctx)
        trainer = sync_ftrl.trainer_for(ctx, model, cfg, opt)
        bounds = []
        bind = trainer.engine.bind

        def tapped(data, steps=None):
            if half_steps and not bounds:
                workers = trainer.engine.mesh.size * trainer.engine.virtual_workers
                steps = max(1, math.ceil(math.ceil(len(data) / workers) / cfg.batch_size) // 2)
            bounds.append(bind(data, steps))
            return bounds[-1]

        trainer.engine.bind = tapped
        t = time.perf_counter()
        res = trainer.fit(problem.train, problem.test, max_epochs=budget)
        secs = time.perf_counter() - t
        w = res.weights
        z, n = (np.asarray(a) for a in ftrl.coordinates(bounds[0].opt_state_leaves()[0],
                                                        model.n_features))
        touched = n > 0
        az = np.abs(z[touched])
        said = {"alpha": alpha, "l1": l1, "seed": ctx.seed, "half_steps": half_steps,
                "steps_per_epoch": bounds[0].steps_per_epoch,
                "objective": res.test_losses,
                "mean_loss": [o - p for o, p in zip(res.test_losses, res.penalty)],
                "penalty": res.penalty, "objective_at_cell_l1": (
                    res.test_losses[-1] - res.penalty[-1]
                    + reference_ftrl.penalty(w, l1_cell, lam)),
                "train_objective": res.losses, "acc": res.test_accuracies,
                "nonzero": res.nonzero, "touched": int(touched.sum()),
                "zero_share": 1.0 - res.nonzero[-1] / max(int(touched.sum()), 1),
                "absz_q": {q: float(np.quantile(az, q)) for q in (0.1, 0.25, 0.5, 0.75, 0.9)},
                "epoch_s": res.epoch_seconds, "fit_s": secs,
                "record": bounds[0].plan.record()}
        if keep:
            return said, (trainer, bounds, res)
        del trainer, bounds, res, w
        gc.collect()
        return said, None

    alpha, l1 = float(cell.config["learning_rate"]), l1_cell
    seeds = seeds_of(argv)
    ctx = ctx_for(seeds[0] if "band" in only else 4300000011)
    problem, model = harness.build_problem(ctx)
    say("rows", {"seed": ctx.seed, "rows_s": ctx.setup.get("rows_s")})

    if "layout" in only:
        d = model.n_features
        p = ftrl.Params(0.1, 0.01, lam)
        ending = "rows" if mxu.blocked_pays_off(devices[0]) else "words"
        steps = 40 if rehearse else 2000
        tr = problem.train
        big_r = mxu.n_blocks(d)

        def draw(idx_all, val_all, y_all, s):
            rows = jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(3), s), (400,), 0,
                                      idx_all.shape[0])
            return idx_all[rows], val_all[rows], y_all[rows]

        def one(state, s, idx_all, val_all, y_all):
            bi, bv, by = draw(idx_all, val_all, y_all, s)
            at, add = model.reply_entries(state, SparseBatch(bi, bv), by, factor=0.25,
                                          matvec=functools.partial(ftrl.matvec, p=p))
            return gather.scatter_into(state, at, add, ending,
                                       row=functools.partial(ftrl.rows, p=p), per_row=ftrl.HALF)

        def two(zn, s, idx_all, val_all, y_all):
            z2, n2 = zn
            bi, bv, by = draw(idx_all, val_all, y_all, s)
            flat = bi.reshape(-1)
            zr, nr = z2[flat // 128], n2[flat // 128]
            lane = jax.lax.broadcasted_iota(jnp.int32, zr.shape, 1)
            at = (flat % 128)[:, None]
            zw = jnp.sum(jnp.where(lane == at, zr, 0.0), axis=-1)
            nw = jnp.sum(jnp.where(lane == at, nr, 0.0), axis=-1)
            m = jnp.sum(bv * ftrl.weights(zw, nw, p).reshape(bi.shape), axis=-1)
            add = (bv * (model.grad_coeff(m, by) * 0.25)[:, None]).reshape(-1)
            rows, head, total = gather._sum_by_row(flat, add)
            e = jnp.arange(rows.shape[0])
            fetch = jnp.where(head, rows, e % big_r)
            zn_new, nn_new = ftrl.update(z2[fetch], n2[fetch], total, p)
            if ending == "rows":
                return (gather._write_rows(z2, rows, head, zn_new),
                        gather._write_rows(n2, rows, head, nn_new))
            to = jnp.where(head, rows, big_r + e)
            return (z2.at[to].set(zn_new, mode="drop", unique_indices=True),
                    n2.at[to].set(nn_new, mode="drop", unique_indices=True))

        layout = {}
        for name, f, init in (("one_array", one, ftrl.zeros(d)),
                              ("two_arrays", two, (jnp.zeros((big_r, 128)),
                                                   jnp.zeros((big_r, 128))))):
            run = jax.jit(lambda st, ia, va, ya, f=f: jax.lax.scan(
                lambda c, s: (f(c, s, ia, va, ya), ()), st, jnp.arange(steps))[0])
            args = (tr.indices, tr.values, tr.labels)
            out = run(init, *args)
            jax.block_until_ready(out)
            times = []
            for _ in range(3):
                t = time.perf_counter()
                out = run(out, *args)
                jax.block_until_ready(out)
                times.append(1e6 * (time.perf_counter() - t) / steps)
            layout[name] = times
            del out, run
            gc.collect()
        say("layout_us_per_step", layout)

    if "alpha" in only or "l1" in only:
        alphas = (0.03, 0.1, 0.3, 1.0, 3.0) if "alpha" in only else (alpha,)
        sweep = {}
        for a in alphas:
            sweep[a], _ = fit(ctx, problem, model, a, 0.0)
            say("alpha", sweep[a])
        if "alpha" in only:  # the largest alpha still on the steep part at the budget
            best = min(s["objective"][-1] for s in sweep.values())
            steep = [a for a in alphas if sweep[a]["objective"][-1] <= best + 1e-3
                     and sweep[a]["objective"][-2] - sweep[a]["objective"][-1] >= 1e-4]
            alpha = max(steep) if steep else min(alphas, key=lambda a: sweep[a]["objective"][-1])
            say("alpha_chosen", {"alpha": alpha, "steep": steep, "best": best})
        if "l1" in only:
            q = sweep[alpha]["absz_q"]
            for quartile in (0.25, 0.5, 0.75):
                said, _ = fit(ctx, problem, model, alpha, float(f"{q[quartile]:.2g}"))
                say("l1", said)

    kept = None
    for seed in seeds if "band" in only or "controls" in only else ():
        if seed != ctx.seed:
            del problem
            gc.collect()
            ctx = ctx_for(seed)
            problem, model = harness.build_problem(ctx)
        keep = "controls" in only and seed == seeds[-1]
        said, kept = fit(ctx, problem, model, alpha, l1, keep=keep)
        say("band_seed", said)
        if "faults" in only and seed == seeds[0]:
            for fault in ({"l1": 0.0}, {"half_steps": True}):
                said, _ = fit(ctx, problem, model, alpha, **dict({"l1": l1}, **fault))
                say("fault", said)
    if "faults" in only and "band" not in only:
        for fault in ({"l1": 0.0}, {"half_steps": True}):
            said, _ = fit(ctx, problem, model, alpha, **dict({"l1": l1}, **fault))
            say("fault", said)

    if kept is not None:
        trainer, bounds, res = kept
        cfg_file = dict(cell.config, learning_rate=alpha,
                        ftrl=dict(cell.config["ftrl"], l1=l1))
        state = bounds[0].opt_state_leaves()[0]
        w = res.weights
        a_, b_, l1_, l2_ = sync_ftrl.hyper(cfg_file)
        for probe_seed in (4300000201, 4300000202, 4300000203):
            _, step = sync_ftrl._step_check(trainer, problem, cfg_file, state, w, probe_seed)
            say("step_f32", step)
        _, ev = sync_ftrl._evaluation_check(cfg_file, bounds[0].ftrl, w, problem.test,
                                            res.test_losses[-1], res.test_accuracies[-1])
        say("eval_f32", ev)
        say("state", sync_ftrl._state_check(cfg_file, state, w)[1])
        # one precision lower: the reference's step and evaluation with the
        # state / weights and the values rounded
        d = model.n_features
        z0, n0 = ftrl.coordinates(state, d)
        idx, val, y = harness.seeded_rows(problem.train, 400, 4300000301)
        batches = [(jnp.asarray(idx[k::4]), jnp.asarray(val[k::4]), jnp.asarray(y[k::4]))
                   for k in range(4)]
        loss = cfg_file["model"]
        zf, nf, gf = reference_ftrl.sync_step(loss, z0, n0, batches, a_, b_, l1_, l2_)
        wf0 = np.asarray(reference_ftrl.weights(z0, n0, a_, b_, l1_, l2_))
        wf1 = np.asarray(reference_ftrl.weights(zf, nf, a_, b_, l1_, l2_))
        z0h, n0h = np.asarray(z0), np.asarray(n0)
        at = np.asarray(gf) != 0
        for low in (jnp.bfloat16, jnp.float16):
            rnd = lambda x, low=low: jnp.asarray(x).astype(low).astype(jnp.float32)  # noqa: E731
            for what in ("state", "values", "both"):
                zz, nn = (rnd(z0), rnd(n0)) if what != "values" else (z0, n0)
                bb = [(i, rnd(v) if what != "state" else v, yy) for i, v, yy in batches]
                zl, nl, _ = reference_ftrl.sync_step(loss, zz, nn, bb, a_, b_, l1_, l2_)
                wl0 = np.asarray(reference_ftrl.weights(zz, nn, a_, b_, l1_, l2_))
                wl1 = np.asarray(reference_ftrl.weights(zl, nl, a_, b_, l1_, l2_))
                zl, nl, zzh, nnh = (np.asarray(x) for x in (zl, nl, zz, nn))
                say("step_low", {
                    "dtype": str(np.dtype(low)), "rounded": what,
                    "z_rel_err": sync_ftrl._rel(zl[at] - zzh[at], np.asarray(zf)[at] - z0h[at]),
                    "n_rel_err": sync_ftrl._rel(nl[at] - nnh[at], np.asarray(nf)[at] - n0h[at]),
                    "w_rel_err": sync_ftrl._rel((wl1 - wl0)[at], (wf1 - wf0)[at])})
            for what in ("w", "values", "both"):
                ww = rnd(w) if what != "values" else w
                tv = problem.test.values if what == "w" else rnd(problem.test.values)
                _, acc, ml, pn = reference_ftrl.evaluate(loss, ww, problem.test.indices, tv,
                                                         problem.test.labels, l1_, l2_)
                say("eval_low", {"dtype": str(np.dtype(low)), "rounded": what,
                                 "loss_abs_err": abs(ml - ev["reference_mean_loss"]),
                                 "penalty_rel_err": abs(pn - ev["reference_penalty"])
                                 / ev["reference_penalty"],
                                 "acc_abs_err": abs(acc - ev["reference_acc"])})
    say("done", {"memory_peak_bytes": harness.memory_peak_bytes(devices)})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
