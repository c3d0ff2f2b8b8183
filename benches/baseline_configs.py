"""The five BASELINE.md benchmark configs, runnable on one chip.

Emits one JSON line per config.  Sync workers are emulated with
virtual_workers (reference topology semantics on a single chip — see
parallel/sync.py); async gossip runs the faithful host-driven Hogwild
engine.  `--scale` shrinks sample counts for smoke runs (default 1.0 =
full-size; the driver's bench.py covers config 1 at full size with
slope-fit timing, this harness surveys the breadth).

Usage: python benches/baseline_configs.py [--scale 0.1] [--configs 1,2,3,4,5]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def _force_sync_dispatch():
    import jax
    import jax.numpy as jnp

    np.asarray(jnp.zeros(4))
    return jax


def rcv1_scale(n, seed=0):
    from distributed_sgd_tpu.data.synthetic import rcv1_like

    # ltc/IDF value weighting — the realistic model of RCV1-v2 term
    # weighting; the reference's lr=0.5 is only smooth with it
    # (benches/zipf_oscillation.py, BASELINE.md round 4)
    return rcv1_like(n, n_features=47236, nnz=76, seed=seed, idf_values=True)


def _sync_run(data, model_name, workers, batch, lr, lam, reg, epochs=2):
    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.rcv1 import dim_sparsity, train_test_split
    from distributed_sgd_tpu.models.linear import make_model
    from distributed_sgd_tpu.parallel.mesh import make_mesh
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    train, test = train_test_split(data)
    ds = jnp.asarray(dim_sparsity(train)) if reg == "dim_sparsity" else None
    model = make_model(model_name, lam, data.n_features, dim_sparsity=ds, regularizer=reg)
    eng = SyncEngine(model, make_mesh(1), batch_size=batch, learning_rate=lr,
                     virtual_workers=workers)
    bound = eng.bind(train)
    bound_test = eng.bind(test)
    w = jnp.zeros(data.n_features, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    # slope-fit like bench.py: (t[3 epochs] - t[1 epoch]) / 2 in single
    # dispatches, removing per-dispatch transport overhead
    times = {}
    for n_ep in (1, 3):
        np.asarray(bound.multi_epoch(w, key, n_ep))  # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(bound.multi_epoch(w, key, n_ep))
            best = min(best, time.perf_counter() - t0)
        times[n_ep] = best
    epoch_s = (times[3] - times[1]) / 2.0
    if epoch_s <= 0:  # jitter swamped a tiny run; report the upper bound
        epoch_s = times[3] / 3.0
    w = bound.multi_epoch(w, key, max(epochs, 1))
    loss, acc = bound_test.evaluate(w)
    return epoch_s, float(loss), float(acc), bound.steps_per_epoch


def config1(scale):
    """sync SGD, 2 workers, RCV1 hinge (application.conf defaults)."""
    n = int(804_414 * scale)
    e, loss, acc, spe = _sync_run(rcv1_scale(n), "hinge", 2, 100, 0.5, 1e-5,
                                  "dim_sparsity")
    return {"config": 1, "desc": "sync 2-worker RCV1 hinge", "n": n,
            "epoch_s": round(e, 4), "steps_per_epoch": spe,
            "test_loss": round(loss, 4), "test_acc": round(acc, 4)}


def config2(scale):
    """async Hogwild gossip, 4 workers, RCV1 hinge."""
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.rcv1 import dim_sparsity, train_test_split
    from distributed_sgd_tpu.models.linear import make_model
    from distributed_sgd_tpu.parallel.hogwild import HogwildEngine

    # amortized dispatch: k=32 local steps per compiled program, gossip the
    # summed delta every k (staleness period 32 steps — see hogwild.py);
    # budget = n updates per epoch, capped to keep the run minutes-bounded
    k = 32
    n = max(2000, min(40_000, int(804_414 * scale * 0.1)))
    data = rcv1_scale(n)
    train, test = train_test_split(data)
    model = make_model("hinge", 1e-5, data.n_features,
                       dim_sparsity=jnp.asarray(dim_sparsity(train)))
    eng = HogwildEngine(model, n_workers=4, batch_size=100, learning_rate=0.5,
                        check_every=100, steps_per_dispatch=k)
    t0 = time.perf_counter()
    res = eng.fit(train, test, max_epochs=1)
    wall = time.perf_counter() - t0
    ups = res.state.updates
    return {"config": 2, "desc": "async hogwild 4-worker RCV1 hinge", "n": n,
            "wall_s": round(wall, 2), "updates": ups,
            "steps_per_dispatch": k,
            "updates_per_s": round(ups / wall, 1),
            "test_loss": round(res.test_losses[-1], 4) if res.test_losses else None}


def config3(scale):
    """sync logistic regression on RCV1 (capability superset)."""
    n = int(804_414 * scale)
    e, loss, acc, spe = _sync_run(rcv1_scale(n), "logistic", 3, 100, 0.5, 1e-5, "l2")
    return {"config": 3, "desc": "sync 3-worker RCV1 logistic", "n": n,
            "epoch_s": round(e, 4), "steps_per_epoch": spe,
            "test_loss": round(loss, 4), "test_acc": round(acc, 4)}


def config4(scale):
    """async local-SGD (compiled), 8 emulated workers, batch 256, L2 hinge."""
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.rcv1 import train_test_split
    from distributed_sgd_tpu.models.linear import make_model
    from distributed_sgd_tpu.parallel.local_sgd import LocalSGDEngine
    from distributed_sgd_tpu.parallel.mesh import make_mesh

    # compiled rounds, but loss checks pace the host loop: cap like config 2
    n = max(4000, min(24_000, int(804_414 * scale * 0.25)))
    data = rcv1_scale(n)
    train, test = train_test_split(data)
    model = make_model("hinge", 1e-5, data.n_features, regularizer="l2")
    eng = LocalSGDEngine(model, make_mesh(1), batch_size=256, learning_rate=0.5,
                         sync_period=16, check_every=10_000)
    t0 = time.perf_counter()
    res = eng.fit(train, test, max_epochs=1)
    wall = time.perf_counter() - t0
    return {"config": 4, "desc": "async local-SGD b256 L2 hinge", "n": n,
            "wall_s": round(wall, 2), "updates": res.state.updates,
            "updates_per_s": round(res.state.updates / wall, 1),
            "test_loss": round(res.test_losses[-1], 4) if res.test_losses else None}


def config5(scale):
    """sync dense least-squares, synthetic 1M x 1024 (dense layout: plain
    matmul kernels, no index array)."""
    from distributed_sgd_tpu.data.rcv1 import Dataset

    n, d = int(1_000_000 * scale), 1024
    rng = np.random.default_rng(0)
    val = rng.normal(size=(n, d)).astype(np.float32) / np.sqrt(d)
    w_true = rng.normal(size=d).astype(np.float32)
    y = (val @ w_true + 0.01 * rng.normal(size=n)).astype(np.float32)
    data = Dataset.dense(val, y)
    e, loss, _, spe = _sync_run(data, "least_squares", 1, 256, 0.05, 0.0, "none")
    return {"config": 5, "desc": "sync dense 1024-d least squares", "n": n,
            "epoch_s": round(e, 4), "steps_per_epoch": spe,
            "test_mse": round(loss, 5)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--configs", type=str, default="1,2,3,4,5")
    args = ap.parse_args()
    _force_sync_dispatch()
    fns = {1: config1, 2: config2, 3: config3, 4: config4, 5: config5}
    for c in [int(x) for x in args.configs.split(",")]:
        log(f"running config {c} (scale {args.scale})...")
        t0 = time.perf_counter()
        out = fns[c](args.scale)
        log(f"config {c} done in {time.perf_counter()-t0:.1f}s")
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main()
