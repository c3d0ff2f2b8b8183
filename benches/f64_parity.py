"""Full-scale f64 numerics-parity study (VERDICT item 2; BASELINE.md
"f64 numerics-parity bound").

The reference computes its objective on spire.Number (exact rational
math, SparseSVM.scala:14-31); the shipped engine evaluates in f32 on
device.  This study bounds what that costs: run the flagship 10-epoch
sync trajectory (the BENCH parity configuration — 804,414 x 47,236
synthetic RCV1, B=100, 3 virtual workers, seed 0, SyncTrainer's
per-epoch `fold_in(key, epoch)` key discipline) on the SHIPPED f32 path,
and at every epoch boundary evaluate the SAME weights twice:

- ``f32``: the engine's own jitted evaluate (the number every BENCH
  round reports);
- ``f64``: the reference objective re-computed under
  ``jax.enable_x64`` — float64 margins, float64 loss
  accumulation, float64 regularizer — on the identical weights/data.

The per-epoch |f32 - f64| divergence table is committed to BASELINE.md
and the measured bound is pinned by tests/test_f64_parity.py (smoke
shape in tier-1; the full-scale bound recorded in BASELINE.md).  Note
the hinge objective's sample losses take values in {0, 1, 2} exactly
(the loss reads sign(margin), SparseSVM.scala:14-16), so the divergence
isolates exactly two effects: f32 mean-accumulation over N samples and
the f32 regularizer sum — plus any margin whose f32 sign differs from
its f64 sign.

Run: ``python benches/f64_parity.py [--smoke]``.  Prints ONE JSON line
on stdout (per-epoch table included), diagnostics to stderr; gated
round-over-round through benches/regress.py (`value` = max divergence,
lower-is-better — deterministic given the seed, so any growth is a real
numerics change).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# runnable as `python benches/f64_parity.py` from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# full mode: the EXACT flagship parity configuration (bench.py constants)
FULL = dict(n=804_414, n_features=47_236, nnz=76, batch=100, workers=3,
            epochs=10, lr=0.5, lam=1e-5, seed=0)
# smoke: the same trajectory shape scaled to tier-1 wall budget; the
# pinned-bound test runs THIS (tests/test_f64_parity.py)
SMOKE = dict(n=8_000, n_features=8_192, nnz=16, batch=50, workers=3,
             epochs=10, lr=0.5, lam=1e-5, seed=0)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def gen_data(cfg: dict):
    """bench.py gen_data generalized to the smoke shape (same recipe:
    sorted indices, row-normalized |N(0,1)| values, median-margin
    labels)."""
    rng = np.random.default_rng(cfg["seed"])
    idx = rng.integers(0, cfg["n_features"], size=(cfg["n"], cfg["nnz"]),
                       dtype=np.int64).astype(np.int32)
    idx.sort(axis=1)
    val = np.abs(rng.normal(size=(cfg["n"], cfg["nnz"]))).astype(np.float32)
    val /= np.maximum(np.linalg.norm(val, axis=1, keepdims=True), 1e-12)
    w_true = rng.normal(size=cfg["n_features"]).astype(np.float32)
    margins = np.einsum("np,np->n", val, w_true[idx])
    y = np.where(margins > np.median(margins), 1, -1).astype(np.int32)
    return idx, val, y


def bind_engine(cfg: dict, idx, val, y):
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.rcv1 import Dataset
    from distributed_sgd_tpu.models.linear import SparseSVM
    from distributed_sgd_tpu.parallel.mesh import make_mesh
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    counts = np.bincount(idx.ravel(), minlength=cfg["n_features"])
    ds = np.zeros(cfg["n_features"], dtype=np.float32)
    nz = counts > 0
    ds[nz] = 1.0 / (counts[nz] + 1.0)
    model = SparseSVM(lam=cfg["lam"], n_features=cfg["n_features"],
                      dim_sparsity=jnp.asarray(ds))
    engine = SyncEngine(model, make_mesh(1), batch_size=cfg["batch"],
                        learning_rate=cfg["lr"],
                        virtual_workers=cfg["workers"])
    return engine.bind(Dataset(indices=idx, values=val, labels=y,
                               n_features=cfg["n_features"]))


def objective_x64(w, idx, val, y, lam: float) -> float:
    """The reference objective (SparseSVM.scala:14-23) evaluated in
    float64 under jax_enable_x64 on the given (f32-trajectory) weights:
    margins, sign-predictions, hinge losses, mean, and the L2
    regularizer all accumulate in f64."""
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True):
        w64 = jnp.asarray(np.asarray(w, dtype=np.float64))
        v64 = jnp.asarray(np.asarray(val, dtype=np.float64))
        margins = jnp.einsum("np,np->n", v64,
                             w64[jnp.asarray(idx, dtype=np.int64)])
        preds = jnp.sign(margins) * -1.0
        y64 = jnp.asarray(np.asarray(y, dtype=np.float64))
        losses = jnp.maximum(0.0, 1.0 - y64 * preds)
        obj = lam * jnp.sum(w64 * w64) + jnp.mean(losses)
        return float(obj)


def run_trajectory(cfg: dict):
    """The shipped f32 10-epoch trajectory with both evaluations at every
    epoch boundary; returns the per-epoch table."""
    import jax
    import jax.numpy as jnp

    idx, val, y = gen_data(cfg)
    bound = bind_engine(cfg, idx, val, y)
    w = jnp.zeros((cfg["n_features"],), dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    table = []
    for epoch in range(cfg["epochs"]):
        t0 = time.perf_counter()
        # SyncTrainer's key discipline: one fold per absolute epoch
        w = bound.epoch(w, jax.random.fold_in(key, epoch))
        np.asarray(w)  # force the dispatch before timing/eval
        epoch_s = time.perf_counter() - t0
        f32_obj, f32_acc = bound.evaluate(w)
        f64_obj = objective_x64(w, idx, val, y, cfg["lam"])
        div = abs(f32_obj - f64_obj)
        table.append(dict(epoch=epoch, f32_objective=f32_obj,
                          f64_objective=f64_obj, divergence=div,
                          acc=f32_acc, epoch_s=round(epoch_s, 3)))
        log(f"epoch {epoch}: f32={f32_obj:.9f} f64={f64_obj:.9f} "
            f"|div|={div:.3e} acc={f32_acc:.4f} ({epoch_s:.1f}s)")
    return table


def run_bench(smoke: bool = False) -> dict:
    cfg = SMOKE if smoke else FULL
    label = "smoke" if smoke else "full"
    log(f"f64 numerics-parity study ({label}): n={cfg['n']} "
        f"dim={cfg['n_features']} nnz={cfg['nnz']} batch={cfg['batch']} "
        f"workers={cfg['workers']} epochs={cfg['epochs']} seed={cfg['seed']}")
    table = run_trajectory(cfg)
    max_div = max(r["divergence"] for r in table)
    rel = max(r["divergence"] / max(abs(r["f64_objective"]), 1e-12)
              for r in table)
    log(f"max |f32 - f64| objective divergence over {cfg['epochs']} epochs: "
        f"{max_div:.3e} (relative {rel:.3e})")
    return {
        "metric": f"f64_parity_{label}",
        # deterministic given the seed: growth = a real numerics change
        "value": max_div,
        "unit": "|f32-f64| objective",
        "max_divergence": max_div,
        "max_relative_divergence": rel,
        "final_f32_objective_info": table[-1]["f32_objective"],
        "final_f64_objective_info": table[-1]["f64_objective"],
        "final_acc_info": table[-1]["acc"],
        "table": table,
        **{k: v for k, v in cfg.items()},
    }


def main(smoke: bool = False) -> None:
    result = run_bench(smoke=smoke)
    try:
        from benches import regress

        regressions, lines = regress.check(result, regress.load_history())
        result["regressed"] = regressions
        log("regression gate vs stored history:")
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


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main(smoke="--smoke" in sys.argv)
