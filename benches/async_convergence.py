"""Async modes as TRAINERS: full-budget convergence vs sync (VERDICT r3 #1).

The reference's async mode is a training mode that converges on RCV1
(README.md:3,35 — MasterAsync.scala:96-162 exists to detect that
convergence), not just an update-rate demo.  This harness runs ALL THREE
async drivers — HogwildEngine, LocalSGDEngine, and the gRPC fit_async
cluster (real loopback RPC, the reference's own topology) — to their FULL
update budget (maxSteps = n_samples * max_epochs, MasterAsync.scala:83 —
no early stop) and reports the final smoothed test loss next to a sync
run on the SAME data and model, so "async works as a trainer" is a
measured claim for every driver.

Data: `rcv1_like(idf_values=True)` — Zipf feature popularity with ltc/IDF
value attenuation, the realistic model of RCV1-v2's term weighting — at
RCV1 feature scale, with the reference's own lr=0.5: the
Zipf-oscillation study (benches/zipf_oscillation.py) measured this
combination smooth, so the async-vs-sync comparison runs at the
reference's actual operating point.

Prints one JSON document; BASELINE.md records the table.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

N_ROWS = 24_000
N_FEATURES = 47_236
NNZ = 76
BATCH = 100
N_WORKERS = 4  # kube/config-async.yaml nodeCount
MAX_EPOCHS = 10  # budget multiplier (application.conf maxEpochs)
LR = 0.5  # the reference default; measured-smooth on ltc data
LAM = 1e-5
LEAKY = 0.9  # application.conf leakyLoss


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.rcv1 import dim_sparsity, train_test_split
    from distributed_sgd_tpu.data.synthetic import rcv1_like
    from distributed_sgd_tpu.models.linear import SparseSVM
    from distributed_sgd_tpu.parallel.hogwild import HogwildEngine
    from distributed_sgd_tpu.parallel.local_sgd import LocalSGDEngine
    from distributed_sgd_tpu.parallel.mesh import make_mesh
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    t0 = time.perf_counter()
    data = rcv1_like(N_ROWS, n_features=N_FEATURES, nnz=NNZ, seed=0,
                     idf_values=True)
    train, test = train_test_split(data)
    n = len(train)
    budget = n * MAX_EPOCHS
    log(f"data: {n} train rows, budget {budget} updates "
        f"({time.perf_counter()-t0:.1f}s to generate)")
    model = SparseSVM(lam=LAM, n_features=N_FEATURES,
                      dim_sparsity=jnp.asarray(dim_sparsity(train)))

    out: dict = {
        "study": "async_convergence", "n_train": n, "budget": budget,
        "lr": LR, "batch": BATCH, "workers": N_WORKERS,
        "max_epochs": MAX_EPOCHS,
    }

    # -- sync anchor (same data, same model, same lr) ----------------------
    t0 = time.perf_counter()
    eng = SyncEngine(model, make_mesh(1), batch_size=BATCH, learning_rate=LR,
                     virtual_workers=N_WORKERS)
    btr, bte = eng.bind(train), eng.bind(test)
    w = jnp.zeros(N_FEATURES, jnp.float32)
    key = jax.random.PRNGKey(0)
    sync_losses = []
    for e in range(MAX_EPOCHS):
        w = btr.epoch(w, jax.random.fold_in(key, e))
        loss, acc = bte.evaluate(w)
        sync_losses.append(round(float(loss), 4))
    out["sync"] = {
        "test_losses": sync_losses, "final": sync_losses[-1],
        "final_acc": round(float(acc), 4),
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    log(f"sync: {sync_losses} ({out['sync']['wall_s']}s)")

    # -- Hogwild to the full budget (no criterion -> maxSteps stops it) ----
    t0 = time.perf_counter()
    hog = HogwildEngine(model, n_workers=N_WORKERS, batch_size=BATCH,
                        learning_rate=LR, check_every=max(1000, budget // 40),
                        leaky_loss=LEAKY, backoff_s=0.2, steps_per_dispatch=32)
    res = hog.fit(train, test, max_epochs=MAX_EPOCHS)
    wall = time.perf_counter() - t0
    out["hogwild"] = {
        "updates": int(res.state.updates),
        "updates_per_s": round(res.state.updates / wall, 1),
        "smoothed_losses": [round(x, 4) for x in res.test_losses],
        "final_smoothed": round(res.test_losses[-1], 4),
        "best_smoothed": round(float(res.state.loss), 4),
        "final_acc": round(res.test_accuracies[-1], 4),
        "wall_s": round(wall, 1),
    }
    log(f"hogwild: {res.state.updates} updates in {wall:.0f}s, "
        f"final smoothed {res.test_losses[-1]:.4f} best {res.state.loss:.4f}")

    # -- local SGD to the full budget --------------------------------------
    t0 = time.perf_counter()
    lsgd = LocalSGDEngine(model, make_mesh(1), batch_size=BATCH,
                          learning_rate=LR, sync_period=128,
                          leaky_loss=LEAKY, check_every=max(1000, budget // 40))
    res2 = lsgd.fit(train, test, max_epochs=MAX_EPOCHS)
    wall = time.perf_counter() - t0
    out["local_sgd"] = {
        "updates": int(res2.state.updates),
        "updates_per_s": round(res2.state.updates / wall, 1),
        "smoothed_losses": [round(x, 4) for x in res2.test_losses],
        "final_smoothed": round(res2.test_losses[-1], 4),
        "best_smoothed": round(float(res2.state.loss), 4),
        "final_acc": round(res2.test_accuracies[-1], 4),
        "wall_s": round(wall, 1),
    }
    log(f"local_sgd: {res2.state.updates} updates in {wall:.0f}s, "
        f"final smoothed {res2.test_losses[-1]:.4f} best {res2.state.loss:.4f}")

    # -- gRPC async driver (fit_async) to the full budget (VERDICT r4 #7) --
    # the third async driver: real loopback gRPC cluster, StartAsync
    # fan-out, workers gossiping summed deltas over the wire
    # (steps_per_dispatch=32, like the Hogwild row), the master counting
    # local steps to the SAME lifetime budget (MasterAsync.scala:83)
    from distributed_sgd_tpu.core.cluster import DevCluster

    t0 = time.perf_counter()
    with DevCluster(model, train, test, n_workers=N_WORKERS,
                    steps_per_dispatch=32) as c:
        res3 = c.master.fit_async(
            max_epochs=MAX_EPOCHS, batch_size=BATCH, learning_rate=LR,
            check_every=max(1000, budget // 40), leaky_loss=LEAKY,
            backoff_s=0.2,
        )
    wall = time.perf_counter() - t0
    out["grpc_async"] = {
        "updates": int(res3.state.updates),
        "updates_per_s": round(res3.state.updates / wall, 1),
        "smoothed_losses": [round(x, 4) for x in res3.test_losses],
        "final_smoothed": round(res3.test_losses[-1], 4),
        "best_smoothed": round(float(res3.state.loss), 4),
        "final_acc": round(res3.test_accuracies[-1], 4),
        "wall_s": round(wall, 1),
    }
    log(f"grpc_async: {res3.state.updates} updates in {wall:.0f}s, "
        f"final smoothed {res3.test_losses[-1]:.4f} best {res3.state.loss:.4f}")

    # -- sparse gossip topologies (--topologies; docs/ELASTICITY.md) -------
    # ring and random:2 Hogwild rows on the same data/budget, with the
    # convergence-parity verdict vs the all-to-all row above — the
    # full-budget twin of `python bench.py --elastic`'s asserted gate
    if "--topologies" in sys.argv:
        base = out["hogwild"]["best_smoothed"]
        bound = max(1.02 * base, base + 0.02)  # docs/COMPRESSION.md gate
        out["topology_parity_bound"] = round(bound, 4)
        for topo in ("ring", "random:2"):
            t0 = time.perf_counter()
            eng_t = HogwildEngine(
                model, n_workers=N_WORKERS, batch_size=BATCH,
                learning_rate=LR, check_every=max(1000, budget // 40),
                leaky_loss=LEAKY, backoff_s=0.2, steps_per_dispatch=32,
                gossip_topology=topo)
            res_t = eng_t.fit(train, test, max_epochs=MAX_EPOCHS)
            wall = time.perf_counter() - t0
            best = round(float(res_t.state.loss), 4)
            out[f"hogwild_{topo.replace(':', '_')}"] = {
                "updates": int(res_t.state.updates),
                "updates_per_s": round(res_t.state.updates / wall, 1),
                "best_smoothed": best,
                "parity_ok": int(best <= bound),
                "wall_s": round(wall, 1),
            }
            log(f"hogwild[{topo}]: best smoothed {best:.4f} vs bound "
                f"{bound:.4f} ({'OK' if best <= bound else 'FAIL'})")

    sync_final = out["sync"]["final"]
    out["gap_hogwild"] = round(out["hogwild"]["best_smoothed"] - sync_final, 4)
    out["gap_local_sgd"] = round(out["local_sgd"]["best_smoothed"] - sync_final, 4)
    out["gap_grpc_async"] = round(out["grpc_async"]["best_smoothed"] - sync_final, 4)
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main()
