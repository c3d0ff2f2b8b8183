"""Kernel gate: interleaved fused A/B of the scatter formulations.

ROADMAP item 2's acceptance harness, CI-shaped: the four selectable
scatter formulations (ops/mxu.py DSGD_SCATTER — 'onehot' shipped,
'segment' / 'twostage' / 'bf16' the round-6 sweep) run the SAME fused
training epoch (sampling + gather + hinge + scatter + regularize +
update, one compiled scan per epoch dispatch) interleaved on the same
device, slope-timed exactly like the headline bench
(epoch_s = (t[3 epochs] - t[1 epoch]) / 2, best of reps).  The full-scale
research harness stays `benches/scatter_wide.py --fused-ab`; THIS bench is
the regression gate — it must finish in CI time on whatever device runs
it, so it uses the flagship per-step SHAPE (B=100 x 3 workers x 76 nnz x
47,236 features — the tile geometry that decides the formulation race) on
a small corpus.

Modes (the `--comms`/`--rpc`/... gate pattern):

- full  (``python bench.py --kernels``): flagship step shape, all four
  formulations, plus the B=1024 unconstrained point for 'onehot' and for
  the measured winner when it differs;
- smoke (``--kernels --smoke``): tiny shapes, plus hard asserts — every
  formulation's one-epoch weights agree with 'onehot' ('segment' /
  'twostage' to float-order tolerance, 'bf16' to its documented
  accumulation bound) and the default engine IS 'onehot' byte-for-byte
  (the knobs-off guarantee).

Prints ONE JSON line on stdout; results are gated round-over-round
through benches/regress.py under the metric ``kernels_fused_ab_{mode}``
(per-formulation ``*_epoch_s`` = timing class, lower is better;
``*_info`` ratios recorded ungated) and appended to benches/history.json
on a clean run — kernel regressions now gate like --comms/--rpc/--chaos/
--trace-overhead/--telemetry/--elastic.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

FULL = dict(n=2400, n_features=47_236, nnz=76, batch=100, reps=4, passes=2)
SMOKE = dict(n=600, n_features=4096, nnz=16, batch=50, reps=2, passes=2)
K = 3  # virtual workers: the reference nodeCount topology
B_UNCONSTRAINED = 1024
FORMULATIONS = ("onehot", "segment", "twostage", "bf16")
# parity bars for the smoke asserts: float-order tolerance for the exact
# formulations, the documented bf16 accumulation bound for 'bf16'
EXACT_TOL = dict(rtol=1e-4, atol=1e-5)
BF16_TOL = dict(rtol=5e-2, atol=5e-3)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def timed_best(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _flagship(cfg):
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.rcv1 import Dataset
    from distributed_sgd_tpu.models.linear import SparseSVM

    n, d, nnz = cfg["n"], cfg["n_features"], cfg["nnz"]
    rng = np.random.default_rng(0)
    idx = np.sort(rng.integers(0, d, (n, nnz)).astype(np.int32), axis=1)
    val = np.abs(rng.normal(size=(n, nnz))).astype(np.float32)
    val /= np.maximum(np.linalg.norm(val, axis=1, keepdims=True), 1e-12)
    y = rng.choice(np.array([-1, 1], np.int32), n)
    counts = np.bincount(idx.ravel(), minlength=d)
    ds = np.zeros(d, np.float32)
    nz = counts > 0
    ds[nz] = 1.0 / (counts[nz] + 1.0)
    model = SparseSVM(lam=1e-5, n_features=d, dim_sparsity=jnp.asarray(ds))
    data = Dataset(indices=idx, values=val, labels=y, n_features=d)
    return model, data


def _bound(model, data, batch, formulation, steps_per_epoch=None):
    from distributed_sgd_tpu.parallel.mesh import make_mesh
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    eng = SyncEngine(model, make_mesh(1), batch_size=batch, learning_rate=0.5,
                     virtual_workers=K, scatter=formulation)
    return eng.bind(data, steps_per_epoch=steps_per_epoch)


def _epoch_slope(bound, d, reps):
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)

    def run(n_ep):
        return np.asarray(bound.multi_epoch(jnp.zeros(d, jnp.float32), key, n_ep))

    run(1)
    run(3)  # compile both programs outside the timed region
    t1 = timed_best(lambda: run(1), reps)
    t3 = timed_best(lambda: run(3), reps)
    return max((t3 - t1) / 2.0, 1e-9)


def _one_epoch_weights(bound, d):
    import jax
    import jax.numpy as jnp

    return np.asarray(bound.epoch(jnp.zeros(d, jnp.float32), jax.random.PRNGKey(7)))


def run_bench(smoke: bool = False) -> dict:
    import jax

    cfg = SMOKE if smoke else FULL
    label = "smoke" if smoke else "full"
    d = cfg["n_features"]
    log(f"kernels[{label}]: device={jax.devices()[0]} shape: n={cfg['n']} "
        f"D={d} nnz={cfg['nnz']} B={cfg['batch']} x K={K}")
    model, data = _flagship(cfg)

    # interleaved passes over the formulations cancel shared-device drift
    # (the scatter_wide.py --fused-ab protocol)
    times = {f: [] for f in FORMULATIONS}
    for rep in range(cfg["passes"]):
        for form in FORMULATIONS:
            bound = _bound(model, data, cfg["batch"], form)
            e = _epoch_slope(bound, d, cfg["reps"])
            times[form].append(e)
            log(f"  {form} ({rep + 1}): epoch {e:.4f}s "
                f"({e / bound.steps_per_epoch * 1e6:.0f} us/step)")
    best = {f: min(ts) for f, ts in times.items()}
    winner = min(best, key=best.get)
    result = {
        "metric": f"kernels_fused_ab_{label}",
        "device": jax.devices()[0].platform,
        "winner": winner,
        "winner_speedup_vs_onehot_info": round(
            best["onehot"] / best[winner], 3),
    }
    for form in FORMULATIONS:
        result[f"{form}_epoch_s"] = round(best[form], 4)

    if smoke:
        # hard asserts: (1) the DEFAULT engine (no override) runs 'onehot'
        # byte-for-byte — the knobs-off guarantee; (2) every formulation's
        # one-epoch weights agree with 'onehot' within its bound
        from distributed_sgd_tpu.ops import mxu

        assert mxu.active_scatter_formulation() == "onehot", \
            "process default formulation drifted off 'onehot'"
        w_ref = _one_epoch_weights(_bound(model, data, cfg["batch"], "onehot"), d)
        w_default = _one_epoch_weights(_bound(model, data, cfg["batch"], None), d)
        assert np.array_equal(w_ref, w_default), \
            "default engine != explicit onehot (knobs-off drift)"
        for form, tol in (("segment", EXACT_TOL), ("twostage", EXACT_TOL),
                          ("bf16", BF16_TOL)):
            w = _one_epoch_weights(_bound(model, data, cfg["batch"], form), d)
            assert np.all(np.isfinite(w)), f"{form}: non-finite weights"
            np.testing.assert_allclose(
                w, w_ref, err_msg=f"{form} parity vs onehot", **tol)
        log("smoke asserts passed: knobs-off byte-identical + parity "
            "for segment/twostage/bf16")
    else:
        # the unconstrained B=1024 operating point: 'onehot' always, the
        # winner too when it differs — the BASELINE.md 0.091 s point must
        # not regress while the parity-point race is re-run
        steps = 4
        b_eff = min(B_UNCONSTRAINED, max(1, cfg["n"] // (2 * K)))
        e = _epoch_slope(
            _bound(model, data, b_eff, "onehot", steps_per_epoch=steps), d,
            cfg["reps"])
        result["b1024_onehot_epoch_s"] = round(e, 4)
        log(f"  b1024(onehot, B={b_eff}, {steps} steps): epoch {e:.4f}s")
        if winner != "onehot":
            e = _epoch_slope(
                _bound(model, data, b_eff, winner, steps_per_epoch=steps), d,
                cfg["reps"])
            result[f"b1024_{winner}_epoch_s"] = round(e, 4)
            log(f"  b1024({winner}): epoch {e:.4f}s")

    log(f"winner: {winner} ({result['winner_speedup_vs_onehot_info']}x "
        f"vs onehot)")
    return result


def main(smoke: bool = False) -> None:
    result = run_bench(smoke=smoke)
    # round-over-round gate (benches/regress.py): same policy as bench.py —
    # a clean run is appended to history, a regressed run is not
    try:
        from benches import regress

        regressions, lines = regress.check(result, regress.load_history())
        result["regressed"] = regressions
        log(f"regression gate vs stored history, tolerance "
            f"{regress.DEFAULT_TOLERANCE:.0%}:")
        for ln in lines:
            log(ln)
        if regressions:
            log(f"FAIL: regressed metrics: {', '.join(regressions)} "
                f"(run NOT recorded)")
        else:
            regress.record(result)
            log("PASS: run appended to benches/history.json")
    except Exception as e:  # noqa: BLE001 - gating must not break the bench
        log(f"regression gate skipped: {e}")
        result["regressed"] = None
        result["gate_error"] = str(e)
    print(json.dumps(result))
    if result["regressed"]:
        raise SystemExit(1)


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main(smoke="--smoke" in sys.argv)
