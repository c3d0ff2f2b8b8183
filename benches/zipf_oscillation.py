"""Zipf-generator lr=0.5 oscillation study (VERDICT r3 item 3).

Question: why does the flagship sync config (batch 100, 3 workers,
sum-then-mean worker replies, lr=0.5 — application.conf:15-28 defaults)
oscillate on `data/synthetic.rcv1_like` (Zipf feature popularity) when the
reference's defaults presumably converged on real RCV1?

Hypothesis under test: real RCV1-v2 vectors are ltc-weighted (log-TF x
IDF, cosine-normalized — LYRL2004), so Zipf-HEAD features carry tiny
values (idf ~ log(N/df) -> 0 as df -> N).  The bare Zipf generator gives
head features the same magnitude distribution as tail features; a head
coordinate then accumulates O(batch) same-sign contributions inside each
worker's SUMMED reply (Slave.scala:153), the master mean over workers
does not shrink it (Master.scala:194), and at lr=0.5 the per-step head
coordinate move overshoots the separator scale -> oscillation.  The
sum-then-mean scaling is reference-exact in both generators, so if the
IDF-weighted generator is smooth at lr=0.5, the mechanism is data realism
(head-value attenuation), not a parity bug.

Protocol (one v5e chip, flagship model dim_sparsity reg):
  - for each generator in {zipf, zipf+idf, uniform(bench.py)}:
      - one diagnostic step at lr=0.5 from w=0: report the max per-coord
        |delta_w| and which popularity rank it lands on;
      - full-scenario trajectories at lr in {0.5, 0.1, 0.02}: per-epoch
        test loss for 8 epochs (batch 100, 3 virtual workers).
Prints a JSON document; BASELINE.md records the conclusion.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

N_FEATURES = 47_236
NNZ = 76
BATCH = 100
N_WORKERS = 3
LAM = 1e-5
EPOCHS = 8
LRS = (0.5, 0.1, 0.02)
N_ROWS = 160_000  # big enough for stable trajectories, fast to generate


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def uniform_like(n: int, seed: int = 0):
    """bench.py's ACTUAL generator (imported, not copied — the study's
    uniform arm must be the round-2 full-scenario artifact's data model),
    wrapped into a Dataset."""
    import bench

    from distributed_sgd_tpu.data.rcv1 import Dataset

    idx, val, y = bench.gen_data(n, seed=seed)
    return Dataset(indices=idx, values=val, labels=y, n_features=N_FEATURES)


def make_data(kind: str):
    from distributed_sgd_tpu.data.synthetic import rcv1_like

    if kind == "uniform":
        return uniform_like(N_ROWS)
    return rcv1_like(N_ROWS, n_features=N_FEATURES, nnz=NNZ, seed=0,
                     idf_values=(kind == "zipf_idf"))


def study(kind: str) -> dict:
    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.rcv1 import dim_sparsity, train_test_split
    from distributed_sgd_tpu.models.linear import SparseSVM
    from distributed_sgd_tpu.parallel.mesh import make_mesh
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    t0 = time.perf_counter()
    data = make_data(kind)
    train, test = train_test_split(data)
    log(f"[{kind}] generated {N_ROWS} rows in {time.perf_counter()-t0:.1f}s")
    model = SparseSVM(lam=LAM, n_features=N_FEATURES,
                      dim_sparsity=jnp.asarray(dim_sparsity(train)))
    mesh = make_mesh(1)

    out: dict = {"kind": kind}

    # -- diagnostic step: where does the first lr=0.5 update land? --------
    eng = SyncEngine(model, mesh, batch_size=BATCH, learning_rate=0.5,
                     virtual_workers=N_WORKERS)
    bound = eng.bind(train)
    w0 = jnp.zeros(N_FEATURES, jnp.float32)
    w1 = np.asarray(bound.step(w0, jax.random.PRNGKey(7)))
    delta = np.abs(w1)  # w0 = 0
    top = int(np.argmax(delta))
    # popularity rank: for the Zipf generators feature id == rank
    out["first_step"] = {
        "max_abs_delta_w": float(delta.max()),
        "argmax_feature_id": top,
        "mean_abs_delta_w_nonzero": float(delta[delta > 0].mean()),
        "n_coords_moved_past_1": int((delta > 1.0).sum()),
    }
    log(f"[{kind}] first step at lr=0.5: max|dw|={delta.max():.3f} at feature "
        f"{top}; {int((delta > 1.0).sum())} coords moved past 1.0")

    # -- trajectories ------------------------------------------------------
    out["trajectories"] = {}
    for lr in LRS:
        eng = SyncEngine(model, mesh, batch_size=BATCH, learning_rate=lr,
                         virtual_workers=N_WORKERS)
        btr = eng.bind(train)
        bte = eng.bind(test)
        w = jnp.zeros(N_FEATURES, jnp.float32)
        key = jax.random.PRNGKey(0)
        losses = []
        for e in range(EPOCHS):
            w = btr.epoch(w, jax.random.fold_in(key, e))
            loss, acc = bte.evaluate(w)
            losses.append(round(float(loss), 4))
        # oscillation metric: how often does the test loss move UP epoch
        # over epoch, and by how much in total?
        ups = sum(max(0.0, losses[i + 1] - losses[i]) for i in range(len(losses) - 1))
        out["trajectories"][str(lr)] = {
            "test_losses": losses,
            "final": losses[-1],
            "total_upward_movement": round(ups, 4),
        }
        log(f"[{kind}] lr={lr}: {losses} (upward movement {ups:.3f})")
    return out


def main() -> None:
    results = [study(kind) for kind in ("zipf", "zipf_idf", "uniform")]
    print(json.dumps({"study": "zipf_oscillation", "n_rows": N_ROWS,
                      "epochs": EPOCHS, "results": results}, indent=2))


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main()
