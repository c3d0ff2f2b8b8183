"""What a sync step of the `gather` family costs by feature count, update
form and row layout (PERF.md section 6, PR 30).

A micro-benchmark, not the benchmark: no number of it is a ledger number.
It times `BoundSync.epoch` itself (one device, 4 virtual workers, batch 100,
rows of 11 one-hot entries: `kdd2012-logistic`'s step) on the chip:

- `layout`: the configuration's own train rows (`benchmark/gen/
  kdd2012_like.py`, 6,488,064 rows, D = 54,686,452), the step that
  `kernels.sparse_update` names, with the rows stored as two rows-minor
  arrays (132 B a row) and as one 128-lane row a row (`mesh.put_packed`,
  512 B): what `mesh.packed_width` answers for narrow rows from;
- `crossing`: 262,144 uniform rows over D features, D from 1e6 to 5.5e7,
  the step as the rule names it and the other form forced: where a step
  that passes over all of `w` (zero-fill, regulariser, update: 16 B a
  feature) starts to cost more than one that scatters into the carried
  weights, i.e. what `kernels.SPARSE_UPDATE_MIN_FEATURES` is set from;
- `variants` (PR 31; not in the default run): ONE call of the scatter of a
  step's 4,400 entries (the configuration's own draw of 400 rows: nine hot
  ids in one weight row, 1/r inside the large fields) into carried weights
  of D = 1e6 and of the cell's D, us a call, by form: XLA's word
  scatter-add (PR 30's step), XLA's row scatter-add, `gather.scatter_into`
  with XLA's row write and with the DMA kernel's, and the latter's pieces
  alone (the sort, the sum by row, the row fetch, the kernel's write-back
  by the depth of its semaphore ring).

    python benches/sparse_update_sweep.py [--rehearse] [--only layout,crossing,variants]

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

NNZ, WORKERS, BATCH = 11, 4, 100


def main(argv) -> int:
    rehearse = "--rehearse" in argv
    only = argv[argv.index("--only") + 1].split(",") if "--only" in argv else (
        "layout", "crossing")
    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.rcv1 import Dataset
    from distributed_sgd_tpu.models.linear import make_model
    from distributed_sgd_tpu.ops import kernels
    from distributed_sgd_tpu.parallel import mesh as mesh_mod, sync as sync_mod
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    device = jax.devices()[0]
    if device.platform != "tpu" and not rehearse:
        print(f"sparse_update_sweep: needs a TPU, found {device.platform}", file=sys.stderr)
        return 2
    steps, reps = (2, 1) if rehearse else (2000, 3)

    @contextlib.contextmanager
    def forced(module, name, answer):
        rule = getattr(module, name)
        setattr(module, name, lambda *_: answer)
        try:
            yield
        finally:
            setattr(module, name, rule)

    def step_us(data, lam, sparse=None, lanes="rule"):
        """us a step of `BoundSync.epoch`: the best of `reps` epochs of
        `steps` steps; `sparse` / `lanes` force the two rules' answers."""
        model = make_model("logistic", lam, data.n_features, regularizer="l2")
        w, key = jnp.zeros((data.n_features,), jnp.float32), jax.random.PRNGKey(0)
        with contextlib.ExitStack() as stack:
            if sparse is not None:
                stack.enter_context(forced(kernels, "sparse_update", sparse))
            if lanes != "rule":
                stack.enter_context(forced(sync_mod, "packed_width", lanes))
            bound = SyncEngine(model, mesh_mod.make_mesh(1), BATCH, 0.1, kernel="gather",
                               virtual_workers=WORKERS).bind(data, steps)
            jax.block_until_ready(bound.epoch(w, key))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(bound.epoch(w, key))
            best = min(best, time.perf_counter() - t0)
        return {"us": best / steps * 1e6, "sparse": bound.update_sparse,
                "packed": bound.data.packed}

    out = {"device": device.device_kind, "steps": steps}
    if "layout" in only:
        from benchmark.gen import kdd2012_like

        with open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                               "kdd2012-logistic.json")) as f:
            config = json.load(f)
        train = kdd2012_like.generate(config["data"], 30, [device], rehearse).train
        out["layout"] = {"rows": len(train), "n_features": train.n_features}
        for name, lanes in (("two_arrays", None), ("packed", 128)):
            out["layout"][name] = step_us(train, float(config["lam"]), lanes=lanes)
            print(json.dumps({name: out["layout"][name]}), file=sys.stderr, flush=True)
    if "crossing" in only:
        rng = np.random.default_rng(30)
        n = 256 if rehearse else 262_144
        grid = (3_000, 40_000) if rehearse else (
            1_000_000, 2_000_000, 4_000_000, 8_000_000, 16_000_000, 32_000_000, 54_686_452)
        out["crossing"] = []
        for features in grid:
            idx = rng.integers(0, features, (n, NNZ)).astype(np.int32)
            idx[:, 0] = rng.integers(0, 3, n)  # one id in a third of a step's rows
            data = Dataset(idx, np.full((n, NNZ), NNZ ** -0.5, np.float32),
                           rng.choice([-1, 1], n).astype(np.int32), features)
            row = {"n_features": features,
                   "sparse": step_us(data, 1.5e-7, sparse=True)["us"],
                   "dense": step_us(data, 1.5e-7, sparse=False)["us"]}
            out["crossing"].append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)
    if "variants" in only:
        out["variants"] = variants(rehearse)
    print(json.dumps(out))
    return 0


def variants(rehearse: bool) -> list:
    """us a call of every form of the scatter, a row a feature count."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops import gather, mxu

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                           "kdd2012-logistic.json")) as f:
        card = np.asarray(list(json.load(f)["data"]["field_cardinalities"].values()))
    calls, reps = (2, 1) if rehearse else (200, 3)
    lanes = gather.LANES

    def timed(form, w2):
        """`form(w2, acc, i) -> (w2, acc)`, `calls` times in one program."""
        def body(i, carry):
            return form(*carry, i)

        run = jax.jit(lambda w2: jax.lax.fori_loop(0, calls, body, (w2, jnp.float32(0))),
                      donate_argnums=0)
        w2, _ = jax.block_until_ready(run(w2))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            w2, _ = jax.block_until_ready(run(w2))
            best = min(best, time.perf_counter() - t0)
        return best / calls * 1e6, w2

    rows_out = []
    for features in (60_000,) if rehearse else (1_000_000, int(card.sum())):
        # the fields scaled to `features`, the small ones as they are
        scaled = np.maximum(3, card * features // card.sum())
        scaled[0] += features - scaled.sum()
        rng = np.random.default_rng(31)
        rank = np.floor(np.exp(rng.random((WORKERS * BATCH, NNZ)) * np.log(scaled + 1.0)))
        at = (np.cumsum(scaled) - scaled) + np.clip(rank, 1, scaled).astype(np.int64) - 1
        ids0 = jnp.asarray(at.reshape(-1), jnp.int32)
        upd = jnp.asarray(rng.normal(size=ids0.shape[0]) * 1e-3, jnp.float32)
        n_rows = mxu.n_blocks(features)
        w2 = jnp.zeros((n_rows, lanes), jnp.float32)

        def ids_of(i):  # whole rows further every call: nothing hoisted, one structure
            return (ids0 + lanes * (i % 8)) % (n_rows * lanes)

        def whole(scatter):
            return lambda w2, acc, i: (scatter(w2, ids_of(i), upd), acc)

        def piece(read):
            return lambda w2, acc, i: (w2, acc + read(w2, ids_of(i)))

        def entry_rows(ids):
            lane = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], lanes), 1)
            return jnp.where(lane == (ids % lanes)[:, None], upd[:, None], 0.0)

        forms = {
            "words_add": whole(lambda w2, ids, upd: w2.reshape(-1).at[ids].add(upd).reshape(
                w2.shape)),
            "rows_add": whole(lambda w2, ids, upd: w2.at[ids // lanes].add(entry_rows(ids))),
            "scatter_into_xla": whole(gather.scatter_into),
            "scatter_into_dma": whole(lambda *a: gather.scatter_into(*a, "rows")),
            "sort": piece(lambda w2, ids: jnp.sum(jax.lax.sort(
                (ids, upd), num_keys=1, is_stable=False)[1])),
            "sum_by_row": piece(lambda w2, ids: jnp.sum(gather._sum_by_row(ids, upd)[2])),
            "gather_rows": piece(lambda w2, ids: jnp.sum(w2[ids // lanes])),
        }
        rows, head, total = jax.jit(gather._sum_by_row)(ids0, upd)
        for ring in (1, 4, 16, 32, 64, 256):
            forms[f"write_rows_ring{ring}"] = lambda w2, acc, i, ring=ring: (
                gather._write_rows(w2, (rows + i % 8) % n_rows, head, total, ring=ring), acc)
        row = {"n_features": features, "entries": int(ids0.shape[0]),
               "rows_written": int(jnp.sum(head))}
        on_chip = jax.devices()[0].platform == "tpu"
        with contextlib.nullcontext() if on_chip else pltpu.force_tpu_interpret_mode():
            for name, form in forms.items():
                row[name], w2 = timed(form, w2)
                print(json.dumps({"n_features": features, name: row[name]}),
                      file=sys.stderr, flush=True)
        rows_out.append(row)
    return rows_out


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
