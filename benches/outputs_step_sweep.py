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
  (`gather.scatter_rows_into` with the DMA write and with the merge pass;
  XLA's row scatter-add into zeros plus one pass over `W`), and (c) the
  batch made dense (`X[400, D]` built by XLA's scatter) and two real matmuls
  with the dense pass, in float32 (HIGHEST) and in one bf16 pass;
- `merge` (PR 35): ONE call of `gather.scatter_rows_into`'s two endings on
  the same sorted entry rows, us a call with the sort and the entry rows
  inside: the DMA a touched row (`_run_sums`, `_add_rows`, `_write_rows`)
  against the merge pass (`_merge_rows`) by its block, piece and product
  width, then both by the rows of `W2` from 47,240 up: the readings beside
  `gather.MERGE_*` and `kernels.MERGE_MAX_ROWS_PER_ENTRY`.  Since PR 37
  the other ending is the walk of the sorted factors (`_sum_runs_into`):
  `runs` in the table by rows, beside what it replaced (`a_dma_a_row`);
- `runs` (PR 37; not in the default run): ONE call of that walk against the
  path it replaced (the entry rows, `_run_sums`, `_add_rows` with the DMA
  write) on 28,800 entries (`amazoncat13k-dismec`'s step: ids under the
  generator's law over 203,882 features, 400 samples) into tiles
  `[203,888, 8, 128]`, us a call with the sort inside; then the walk by its
  constants, and by entries and by heads (distinct ids) varied apart: what
  says whether the scalar core's time goes to the entries or to the DMAs.
- `margins` (PR 40; not in the default run): ONE call of
  `gather.matvec_rows` on tiles `[203,888, L / 128, 128]` at 256, 512 and
  1,024 lanes, 72 entries a row under the generator's law: XLA's gather of
  a tile an entry and its weighted sum against the margin kernel
  (`gather._margin_tiles`, its sort inside) by its piece S, on the
  evaluation's chunk of 4,096 samples and on a step's 400, and at 1,024
  lanes by its constants; beside each piece size the share of its entries
  that are distinct tiles (what the kernel fetches).  What
  `kernels.MARGIN_*` and `gather.MARGIN_*` were set from (`--lanes` picks
  the widths); also the kernel on a plan of the pieces made
  once (`gather.plan_pieces`, what the evaluation's chunks read) and what
  making that plan costs a call.

    python benches/outputs_step_sweep.py [--rehearse] [--only step,forms,merge,runs,margins]
                                         [--lanes 256,512,1024]

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
    only = (argv[argv.index("--only") + 1].split(",") if "--only" in argv
            else ("step", "forms", "merge"))
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
    n_outputs = int(config["n_outputs"])
    lam, lr = float(config["lam"]), float(config["learning_rate"])
    out = {"device": device.device_kind, "n_outputs": n_outputs}
    if "step" in only or "forms" in only:  # the configuration's own rows
        spec = dict(config["data"], rows_per_chip=409_600, block_rows=40_960)
        train = rcv1_topics_like.generate(spec, 32, [device], rehearse).train
        n_features = train.n_features
        out.update(rows=len(train), n_features=n_features)

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
                                 "update": bound.plan.update, "scatter": bound.plan.scatter}
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
        walk = "runs" if device.platform == "tpu" else "words"
        exact, sample = jax.lax.Precision.HIGHEST, jnp.arange(rows)[:, None]

        def batch_of(i):  # other ids every call: nothing hoisted, one structure
            return gather.SparseBatch((idx0 + i) % n_features, val)

        def coeff_of(m):
            return model.grad_coeff(m, y) * (-lr / WORKERS)

        def sparse_rows(w2, i, ending=walk):
            b = batch_of(i)
            at, v, src, coeff = model.reply_rows(w2, b, y, None, -lr / WORKERS)
            return gather.scatter_rows_into(w2, at, v, src, coeff, ending)

        def dense_rows(w2, i):
            b = batch_of(i)
            g = gather.scatter_add_rows(b, coeff_of(model.margins(w2, b, kernel="gather")),
                                        w2.shape)
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
                jnp.sum(model.margins(w2, batch_of(i), kernel="gather"), axis=0)),
            "b_step_rows_into_carry": sparse_rows,
            "b_step_rows_merged_into_carry": lambda w2, i: sparse_rows(
                w2, i, "merge" if walk == "runs" else walk),
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
    if "merge" in only:
        # the scatter alone, a step's 30,400 entries (ids under the
        # generator's law at every D') into W2 [D', 128]: today's path (a
        # DMA a touched row) against the merge pass by its constants, then
        # both by D' up to where the DMA path wins again
        from jax.experimental.pallas import tpu as pltpu

        calls, reps = (2, 1) if rehearse else (100, 2)
        entries, samples = (600, 8) if rehearse else (WORKERS * BATCH * 76, WORKERS * BATCH)
        rng = np.random.default_rng(35)
        val = jnp.asarray(rng.normal(size=entries) * 0.1, jnp.float32)
        src = jnp.asarray(rng.integers(0, samples, entries), jnp.int32)
        coeff = jnp.asarray(rng.normal(size=(samples, 128)) * 0.01, jnp.float32)
        dma = device.platform == "tpu"
        interpreted = contextlib.nullcontext if dma else pltpu.force_tpu_interpret_mode

        def ids_of(rows):  # P(id) ~ ln(1 + 1/r) over the features of `rows` weight rows
            d = rows - 4  # `to_rows` pads them to whole sublanes
            return d, jnp.asarray(np.minimum(np.exp(rng.uniform(
                0.0, np.log(d + 1.0), entries)).astype(np.int64) - 1, d - 1), jnp.int32)

        def timed(rows, after_entry_rows):
            d, ids0 = ids_of(rows)

            def call(i, w2):  # other ids every call, one law
                ids, entry = gather._entry_rows((ids0 + i) % d, val, src, coeff)
                return after_entry_rows(w2, ids, entry)

            return clocked(jax.jit(lambda w2: jax.lax.fori_loop(0, calls, call, w2)),
                           jnp.zeros((rows, 128), jnp.float32))

        def clocked(run, w2):
            with interpreted():
                return best_us(run, w2, calls, reps)

        def today(w2, ids, entry):
            return a_dma_a_row(w2, ids, entry, dma)

        def timed_runs(rows):  # the walk, from the factors: no entry rows
            d, ids0 = ids_of(rows)

            def call(i, w2):
                return gather.scatter_rows_into(w2, (ids0 + i) % d, val, src, coeff,
                                                "runs" if dma else "words")

            return clocked(jax.jit(lambda w2: jax.lax.fori_loop(0, calls, call, w2)),
                           jnp.zeros((rows, 128), jnp.float32))

        def merged(block, sub, wide):
            return lambda w2, ids, entry: gather._merge_rows(w2, ids, entry, block, sub, wide)

        rows = 1_000 if rehearse else 47_240
        out["merge"] = {"calls": calls, "entries": entries, "by_constants": {}, "by_rows": {}}
        # one call of each on the same weights: the pass against today's path
        ids, entry = gather._entry_rows(ids_of(rows)[1], val, src, coeff)
        w2 = jnp.asarray(rng.normal(size=(rows, 128)), jnp.float32)
        with interpreted():
            apart = jnp.abs(jax.jit(today)(w2, ids, entry)
                            - jax.jit(gather._merge_rows)(w2, ids, entry))
        out["merge"]["max_abs_apart"] = float(jnp.max(apart))
        out["merge"]["rows_apart"] = int(jnp.sum(jnp.max(apart, axis=1) > 0))
        print(json.dumps({k: out["merge"][k] for k in ("max_abs_apart", "rows_apart")}),
              file=sys.stderr, flush=True)
        table = out["merge"]["by_constants"]
        table["sort_and_entry_rows_alone"] = timed(rows, lambda w2, ids, entry: w2.at[0].add(
            jnp.sum(entry, axis=0) + jnp.sum(ids)))
        table["today"] = timed(rows, today)
        for block, sub, wide in ((256, 128, 1),) if rehearse else (
                (512, 128, 1), (2048, 128, 1), (2048, 128, 2), (2048, 128, 4), (2048, 128, 8),
                (2048, 64, 4), (2048, 256, 2), (4096, 128, 4)):
            table[f"merge_block{block}_sub{sub}_wide{wide}"] = timed(
                rows, merged(block, sub, wide))
        print(json.dumps(table), file=sys.stderr, flush=True)
        for factor in (1, 2) if rehearse else (1, 2, 3, 4, 8):
            out["merge"]["by_rows"][rows * factor] = row = {
                "a_dma_a_row": timed(rows * factor, today),
                "runs": timed_runs(rows * factor),
                "merge": timed(rows * factor, gather._merge_rows)}
            print(json.dumps({rows * factor: row}), file=sys.stderr, flush=True)
    if "runs" in only:
        out["runs"] = runs_table(jax, jnp, gather, device.platform == "tpu", rehearse)
    if "margins" in only:
        lanes = ([int(x) for x in argv[argv.index("--lanes") + 1].split(",")]
                 if "--lanes" in argv else (256, 512, 1024))
        out["margins"] = margins_table(jax, jnp, gather, kernels, device.platform == "tpu",
                                       rehearse, lanes)
    print(json.dumps(out))
    return 0


def best_us(run, w, calls, reps):
    """us a call of `run`, `calls` calls a run: the best of `reps` runs after
    one that compiles, each on the weights the last one gave back."""
    import jax

    best = float("inf")
    w = jax.block_until_ready(run(w))
    for _ in range(reps):
        t0 = time.perf_counter()
        w = jax.block_until_ready(run(w))
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e6


def a_dma_a_row(w2, ids, entry, dma):
    """What PR 37's walk replaced as `scatter_rows_into`'s DMA ending: the
    sorted entry rows' runs summed on the MXU, every touched row fetched,
    added to and written back by `_write_rows`."""
    import jax.numpy as jnp

    from distributed_sgd_tpu.ops import gather

    head = jnp.concatenate([jnp.ones((1,), bool), ids[1:] != ids[:-1]])
    return gather._add_rows(w2, ids, head, gather._run_sums(ids, entry),
                            "rows" if dma else "words")


def runs_table(jax, jnp, gather, on_tpu, rehearse):
    """The `runs` section: `gather._sum_runs_into` at `amazoncat13k-dismec`'s
    shape against the path it replaced, by its constants, by entries and by
    heads."""
    from jax.experimental.pallas import tpu as pltpu

    calls, reps = (2, 1) if rehearse else (50, 2)
    rows, d, lanes, samples, entries = ((512, 500, 256, 8, 1024) if rehearse
                                        else (203_888, 203_882, 1_024, 400, 28_800))
    rng = np.random.default_rng(37)
    coeff = jnp.asarray(rng.normal(size=(samples, lanes)) * 0.01, jnp.float32)
    interpreted = contextlib.nullcontext if on_tpu else pltpu.force_tpu_interpret_mode

    def factors(n, heads=None):
        """`n` entries: ids under the generator's law over the features, or
        uniform over `heads` distinct ones spread over them."""
        if heads is None:
            ids = np.minimum(np.exp(rng.uniform(0.0, np.log(d + 1.0), n)).astype(np.int64) - 1,
                             d - 1)
        else:
            ids = (np.arange(heads) * (d // heads))[rng.integers(0, heads, n)]
        return (jnp.asarray(ids, jnp.int32), jnp.asarray(rng.normal(size=n) * 0.1, jnp.float32),
                jnp.asarray(rng.integers(0, samples, n), jnp.int32))

    def clocked(ending, ids0, val, src):
        def call(i, w):  # other ids every call, one law
            return ending(w, (ids0 + i) % d, val, src)

        run = jax.jit(lambda w: jax.lax.fori_loop(0, calls, call, w), donate_argnums=0)
        with interpreted():  # 835 MB of weights: each run takes the last one's, donated
            return best_us(run, jnp.zeros((rows, lanes // 128, 128), jnp.float32), calls, reps)

    def replaced(w, ids, val, src):
        with jax.named_scope("dsgd.scatter"):
            return a_dma_a_row(w, *gather._entry_rows(ids, val, src, coeff), on_tpu)

    def walk(block=gather.RUN_BLOCK, unroll=gather.RUN_UNROLL):
        def ending(w, ids, val, src):
            return gather._sum_runs_into(
                w, *gather._sorted_entries(ids, val, src, block), coeff, block, unroll)
        return ending

    def sort_alone(w, ids, val, src):
        ids, val, src = gather._sorted_entries(ids, val, src, gather.RUN_BLOCK)
        return w.at[0, 0, 0].add(jnp.sum(ids) + jnp.sum(val) + jnp.sum(src))

    law = factors(entries)
    out = {"calls": calls, "entries": entries, "rows": rows, "lanes": lanes,
           "heads_under_the_law": int(np.unique(np.asarray(law[0])).size)}
    # one call of each on the same weights
    w = gather.to_tiles(jnp.asarray(rng.normal(size=(rows, lanes)), jnp.float32))
    with interpreted():
        apart = jnp.abs(jax.jit(replaced)(w, *law) - jax.jit(walk())(w, *law))
    out["max_abs_apart"] = float(jnp.max(apart))
    del w, apart
    out["sort_alone"] = clocked(sort_alone, *law)
    out["replaced"] = clocked(replaced, *law)
    out["walk"] = clocked(walk(), *law)
    print(json.dumps(out), file=sys.stderr, flush=True)
    out["by_constants"] = table = {}
    for block, unroll in ((128, 4),) if rehearse else (
            (256, 16), (1024, 16), (512, 8), (512, 32)):
        table[f"block{block}_unroll{unroll}"] = clocked(walk(block, unroll), *law)
        print(json.dumps(table), file=sys.stderr, flush=True)
    out["by_entries_and_heads"] = table = {}
    for n, heads in ((512, 16), (1024, 16)) if rehearse else (
            (28_800, 2_880), (28_800, 11_520), (28_800, 23_040),
            (14_400, 2_880), (14_400, 11_520), (57_600, 11_520)):
        table[f"entries{n}_heads{heads}"] = clocked(walk(), *factors(n, heads))
        print(json.dumps(table), file=sys.stderr, flush=True)
    return out


def margins_table(jax, jnp, gather, kernels, on_tpu, rehearse, lanes_of=(256, 512, 1024)):
    """The `margins` section: one call of `gather.matvec_rows` on tiles,
    XLA's gather against the margin kernel by piece, by lanes and by call
    (the evaluation's chunk, a step), and at 1,024 lanes by its constants."""
    from jax.experimental.pallas import tpu as pltpu

    from distributed_sgd_tpu.ops.sparse import SparseBatch

    calls, reps = (2, 1) if rehearse else (20, 2)
    d, width = (500, 6) if rehearse else (203_882, 72)
    rows = d + -d % gather.SUBLANES
    samples_of = {"eval_chunk": 64, "step": 40} if rehearse else {"eval_chunk": 4096, "step": 400}
    rng = np.random.default_rng(40)
    interpreted = contextlib.nullcontext if on_tpu else pltpu.force_tpu_interpret_mode
    out = {"calls": calls, "features": d, "entries_a_row": width}
    for lanes in (256, 1024) if rehearse else lanes_of:
        out[lanes] = by_lanes = {}
        for name, samples in samples_of.items():
            ids0 = np.minimum(np.exp(rng.uniform(0.0, np.log(d + 1.0), (samples, width))).astype(
                np.int64) - 1, d - 1)
            vals = jnp.asarray(rng.normal(size=(samples, width)), jnp.float32)
            ids = jnp.asarray(ids0, jnp.int32)

            def clocked(margins):
                def call(i, w):  # other ids every call, one law
                    m = margins(w, SparseBatch((ids + i) % d, vals))
                    return w.at[0].add(jnp.sum(m, axis=0).reshape(w.shape[1:]) * 1e-30)

                run = jax.jit(lambda w: jax.lax.fori_loop(0, calls, call, w), donate_argnums=0)
                with interpreted():  # each run takes the weights the last one gave back
                    return best_us(run, jnp.zeros((rows, lanes // 128, 128), jnp.float32),
                                   calls, reps)

            def kernel(piece, **constants):
                return lambda w, b: gather._margin_tiles(
                    w, *gather._sorted_pieces(b, piece, rows), piece, width, **constants)

            # the rule's piece at 1,024 lanes (the worst case of a 4 KB tile
            # an entry in VMEM), and half of it
            rule = kernels.margin_tiles(samples, width, 1024)
            xla = ("gather", kernels.margin_rows(samples, width, lanes))
            row = {"samples": samples, "xla_gather": clocked(
                lambda w, b: gather.matvec_rows(b, w, *xla))}
            w = gather.to_tiles(jnp.asarray(rng.normal(size=(rows, lanes)), jnp.float32))
            batch = SparseBatch(ids, vals)
            with interpreted():  # one call of each on the same weights
                apart = jnp.abs(jax.jit(lambda w: gather.matvec_rows(batch, w, *xla))(w)
                                - jax.jit(kernel(rule))(w, batch).reshape(samples, lanes))
            row[f"max_abs_apart_S{rule}"] = float(jnp.max(apart))
            del w, apart
            for piece in (rule // 2, rule):
                flat = ids0.reshape(-1, piece * width)
                row[f"distinct_share_S{piece}"] = float(np.mean(
                    [np.unique(p).size for p in flat]) / flat.shape[1])
                row[f"S{piece}"] = clocked(kernel(piece))
            if lanes == 1024:  # the kernel's constants, and the sort alone
                for constants in ({"unroll": 8}, {"unroll": 72}):
                    (key, value), = constants.items()
                    row[f"S{rule}_{key}{value}"] = clocked(kernel(rule, **constants))
                row[f"sort_alone_S{rule}"] = clocked(lambda w, b: w[:1] + sum(
                    jnp.sum(a[:, :1]) for a in gather._sorted_pieces(b, rule, rows)))
                # the kernel on a plan made once (`gather.plan_pieces`: rows
                # fixed, as the evaluation's are), and what making it costs
                plan = jax.jit(lambda i: gather.plan_pieces(i, rule, rows))(ids)
                row[f"S{rule}_planned"] = clocked(lambda w, b: gather.matvec_rows(
                    SparseBatch(ids, b.values), w, "planned", rule, plan=plan))
                row[f"plan_alone_S{rule}"] = clocked(lambda w, b: w[:1] + sum(
                    jnp.sum(a[..., :1]) for a in gather.plan_pieces(b.indices, rule, rows)))
                w = gather.to_tiles(jnp.asarray(rng.normal(size=(rows, lanes)), jnp.float32))
                with interpreted():  # the planned kernel is the walking one, bit for bit
                    walked = jax.jit(kernel(rule))(w, batch).reshape(samples, lanes)
                    planned = jax.jit(lambda w: gather.matvec_rows(
                        batch, w, "planned", rule, plan=plan))(w)
                row[f"planned_equal_S{rule}"] = bool(jnp.array_equal(walked, planned))
                del w, walked, planned
            by_lanes[name] = row
            print(json.dumps({lanes: {name: row}}), file=sys.stderr, flush=True)
    return out

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
