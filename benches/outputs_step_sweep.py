"""What a sync step over weights WITH AN OUTPUT AXIS costs, by formulation
(PERF.md section 6, PR 32): what `ops/kernels.py`'s rule for `n_outputs`
was set from.

A micro-benchmark, not the benchmark: no number of it is a ledger number.
On the chip, at `rcv1-topics-hinge`'s shape (D = 47,236, 76 entries a row,
C = 103 outputs on 128 lanes, 4 virtual workers x batch 100 = 30,400
entries a step, the generator's own 1/r draw of feature ids):

- `step`: `BoundSync.epoch` itself over 409,600 of the configuration's own
  rows, us a step, the update as `kernels.sparse_update` names it (the
  entries scattered into the carried weights) and the other form forced
  (XLA's scatter-add of rows into a zeroed [D', 128] gradient, then the
  dense passes): the reading beside `SPARSE_UPDATE_MIN_FEATURES`;
- `forms`: ONE call of each side of the step by formulation, us a call:
  (b) rows of weights gathered (`gather.matvec_rows`) and scattered
  (`gather.scatter_rows_into` with the DMA write; XLA's row scatter-add
  into zeros plus one pass over `W`), and (c) the batch made dense
  (`X[400, D]` built by XLA's scatter) and two real matmuls with the dense
  pass, in float32 (HIGHEST) and in one bf16 pass.

    python benches/outputs_step_sweep.py [--rehearse] [--only step,forms]

Prints one JSON document (a line a row on stderr as it goes).  Refuses a
CPU unless `--rehearse` (tiny shapes, no timing worth reading).
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WORKERS, BATCH = 4, 100


def main(argv) -> int:
    rehearse = "--rehearse" in argv
    only = argv[argv.index("--only") + 1].split(",") if "--only" in argv else ("step", "forms")
    import jax
    import jax.numpy as jnp

    from benchmark.gen import rcv1_topics_like
    from distributed_sgd_tpu.models.linear import make_model
    from distributed_sgd_tpu.ops import gather, kernels
    from distributed_sgd_tpu.parallel import mesh as mesh_mod
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    device = jax.devices()[0]
    if device.platform != "tpu" and not rehearse:
        print(f"outputs_step_sweep: needs a TPU, found {device.platform}", file=sys.stderr)
        return 2
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                           "rcv1-topics-hinge.json")) as f:
        config = json.load(f)
    spec = dict(config["data"], rows_per_chip=409_600, block_rows=40_960)
    train = rcv1_topics_like.generate(spec, 32, [device], rehearse).train
    n_features, n_outputs = train.n_features, int(config["n_outputs"])
    lam, lr = float(config["lam"]), float(config["learning_rate"])
    out = {"device": device.device_kind, "rows": len(train), "n_features": n_features,
           "n_outputs": n_outputs}

    @contextlib.contextmanager
    def forced(module, name, answer):
        rule = getattr(module, name)
        setattr(module, name, lambda *_: answer)
        try:
            yield
        finally:
            setattr(module, name, rule)

    if "step" in only:
        steps, reps = (2, 1) if rehearse else (1000, 2)
        out["step"] = {"steps": steps}
        for name, sparse in (("sparse", True), ("dense", False)):
            model = make_model(config["model"], lam, n_features, regularizer="l2",
                               n_outputs=n_outputs)
            w, key = jnp.zeros(model.weight_shape, jnp.float32), jax.random.PRNGKey(0)
            with forced(kernels, "sparse_update", sparse):
                bound = SyncEngine(model, mesh_mod.make_mesh(1), BATCH, lr,
                                   virtual_workers=WORKERS).bind(train, steps)
                jax.block_until_ready(bound.epoch(w, key))
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(bound.epoch(w, key))
                best = min(best, time.perf_counter() - t0)
            out["step"][name] = {"us": best / steps * 1e6, "kernel": bound.kernel,
                                 "sparse": bound.update_sparse, "dma": bound.scatter_rows}
            print(json.dumps({name: out["step"][name]}), file=sys.stderr, flush=True)

    if "forms" in only:
        calls, reps = (2, 1) if rehearse else (100, 2)
        rows = WORKERS * BATCH
        idx0 = jnp.asarray(train.indices[:rows])
        val = jnp.asarray(train.values[:rows])
        y = jnp.pad(jnp.asarray(train.labels[:rows]), ((0, 0), (0, 128 - n_outputs)))
        model = make_model(config["model"], lam, n_features, regularizer="l2",
                           n_outputs=n_outputs)
        w2 = model.to_layout(jnp.asarray(np.random.default_rng(32).normal(
            size=model.weight_shape) * 0.1, jnp.float32), "gather")
        dma = device.platform == "tpu"
        exact, sample = jax.lax.Precision.HIGHEST, jnp.arange(rows)[:, None]

        def batch_of(i):  # other ids every call: nothing hoisted, one structure
            return gather.SparseBatch((idx0 + i) % n_features, val)

        def coeff_of(m):
            return model.grad_coeff(m, y) * (-lr / WORKERS)

        def sparse_rows(w2, i):
            b = batch_of(i)
            at, v, src, coeff = model.reply_rows(w2, b, y, None, -lr / WORKERS)
            return gather.scatter_rows_into(w2, at, v, src, coeff, dma=dma)

        def dense_rows(w2, i):
            b = batch_of(i)
            g = gather.scatter_add_rows(b, coeff_of(gather.matvec_rows(b, w2)), w2.shape[0])
            return w2 + g

        def dense_batch(precision):
            def form(w2, i):
                b = batch_of(i)
                x = jnp.zeros((rows, w2.shape[0]), jnp.float32).at[sample, b.indices].add(b.values)
                m = jnp.dot(x, w2, precision=precision)
                return w2 + jnp.dot(x.T, coeff_of(m), precision=precision)
            return form

        forms = {
            "b_margins_rows": lambda w2, i: w2.at[0].add(
                jnp.sum(gather.matvec_rows(batch_of(i), w2), axis=0)),
            "b_step_rows_into_carry": sparse_rows,
            "b_step_dense_accumulator": dense_rows,
            "c_densify": lambda w2, i: w2.at[0, 0].add(jnp.sum(
                jnp.zeros((rows, w2.shape[0]), jnp.float32).at[sample, batch_of(i).indices].add(
                    val)[:, 0])),
            "c_step_float32": dense_batch(exact),
            "c_step_one_bf16_pass": dense_batch(None),
        }
        out["forms"] = {"calls": calls}
        for name, form in forms.items():
            run = jax.jit(lambda w2, form=form: jax.lax.fori_loop(
                0, calls, lambda i, w2: form(w2, i), w2))
            jax.block_until_ready(run(w2))
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(run(w2))
                best = min(best, time.perf_counter() - t0)
            out["forms"][name] = best / calls * 1e6
            print(json.dumps({name: out["forms"][name]}), file=sys.stderr, flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
