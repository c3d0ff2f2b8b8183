"""What ONE call of the one-hot kernels costs, by shape (PERF.md section 6, PR 27).

A micro-benchmark, not the benchmark: no number of it is a ledger number.
It times, on the chip, at RCV1's width (D = 47,236, R = 376, 76 entries a
row):

- `gather`: `OneHotBatch.margins` on T = rows x 76 stored entries, `flat`
  (`[T, R] x [R, 128]`, the evaluation's and the merged step's form),
  `vmapped` over K workers (`[K, T/K, R]`, what `grad_workers` compiled to
  until PR 27), through `mxu.matvec` where its rule pads the batch, and
  with the one-hot operand written entries-minor: the fixed cost of a
  call and the cost of an entry, by the layout the compiler builds;
- `scatter`: the K workers' `OneHotBatch.scatter_add` summed, vmapped over
  the workers (today's step), as K plain calls one after the other, and as
  one flat call on all entries (what a linear regulariser would allow);
- `scatter_shards`: the scatter's contraction over T entries cut into S
  shards (`mxu.scatter_shards`, forced to each S here), one call and
  batched over four workers whose replies stay apart as in the step, over
  T from 3,800 to 77,824 at R = 376, and at the two cells' depths at
  R = 1,568 (D = 200,000, where the one-hot and the `gather` family are
  said to cross); `rule` is the S the rule itself picks (PERF.md section
  6, PR 29);
- `step`: the program the cells run, `BoundSync.epoch` of `rcv1-hinge`'s
  model on resident rows (draw, merged margins, the workers' scatters,
  regulariser, update), us a step by batch with S forced: how the compiler
  tiles the scatter's contraction depends on what surrounds it, so the
  call alone does not say what the step pays.

Timing: the slope of a chained `lax.scan` between two trip counts: each
iteration's carry depends on the call's output, and the indices are arguments, not constants, so the
one-hot operands are built in the fusion as in the step.

    python benches/onehot_call_sweep.py [--rehearse] [--only gather,scatter,scatter_shards,step]

Prints one JSON document.  Refuses a CPU unless `--rehearse` (tiny shapes,
no timing worth reading).
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_FEATURES = 47_236
NNZ = 76


def main(argv) -> int:
    rehearse = "--rehearse" in argv
    only = argv[argv.index("--only") + 1].split(",") if "--only" in argv else (
        "gather", "scatter", "scatter_shards", "step")
    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.ops import mxu
    from distributed_sgd_tpu.ops.sparse import SparseBatch

    device = jax.devices()[0]
    if device.platform != "tpu" and not rehearse:
        print(f"onehot_call_sweep: needs a TPU, found {device.platform}", file=sys.stderr)
        return 2
    r = mxu.n_blocks(N_FEATURES)
    lo, hi, reps = (2, 4, 1) if rehearse else (200, 1000, 5)
    rng = np.random.default_rng(27)

    def rows(k, b):
        idx = np.sort(rng.integers(0, N_FEATURES, (k, b, NNZ)).astype(np.int32), axis=-1)
        val = np.abs(rng.normal(size=(k, b, NNZ))).astype(np.float32)
        return jnp.asarray(idx), jnp.asarray(val)

    def slope(body, *args):
        """us an iteration of `body(carry, *args) -> carry` over w2-shaped carries."""
        def looped(n):
            f = jax.jit(lambda c, *a: jax.lax.scan(
                lambda cc, _: (body(cc, *a), None), c, None, length=n)[0])
            c0 = jnp.full((r, mxu.LANES), 0.01, jnp.float32)
            jax.block_until_ready(f(c0, *args))
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(f(c0, *args))
                best = min(best, time.perf_counter() - t0)
            return best
        return (looped(hi) - looped(lo)) / (hi - lo) * 1e6

    def live(w2, idx):
        """`idx`, made to depend on the carry (by a shift that is always 0),
        so that no pass hoists the one-hot operands out of the loop: the
        step draws new rows every iteration."""
        return idx + (w2[0, 1] > 1e30).astype(jnp.int32)

    def merged(w2, idx, val):
        k, b, p = idx.shape
        return SparseBatch(live(w2, idx).reshape(k * b, p), val.reshape(k * b, p))

    def gather_flat(w2, idx, val):
        m = mxu.OneHotBatch(merged(w2, idx, val), r).margins(w2)
        return w2 + 1e-30 * jnp.sum(m)

    def gather_matvec(w2, idx, val):
        m = mxu.matvec(merged(w2, idx, val), w2)
        return w2 + 1e-30 * jnp.sum(m)

    def gather_vmapped(w2, idx, val):
        idx = live(w2, idx)
        m = jax.vmap(lambda i, v: mxu.OneHotBatch(SparseBatch(i, v), r).margins(w2))(idx, val)
        return w2 + 1e-30 * jnp.sum(m)

    def gather_entries_minor(w2, idx, val):
        """The same gather with the one-hot operand written [R, T], the
        entries along the lanes whatever T is: what a follow-up could make
        of `OneHotBatch.gathered_products`."""
        flat = live(w2, idx).reshape(-1)
        ohr = jax.lax.broadcasted_iota(jnp.int32, (r, flat.shape[0]), 0) == (flat // mxu.LANES)[None]
        m1 = jax.lax.dot_general(w2, ohr.astype(jnp.float32), (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [128, T]
        lane = jax.lax.broadcasted_iota(jnp.int32, m1.shape, 0) == (flat % mxu.LANES)[None]
        m = (jnp.sum(jnp.where(lane, m1, 0.0), axis=0) * val.reshape(-1)).reshape(-1, NNZ).sum(-1)
        return w2 + 1e-30 * jnp.sum(m)

    def coeffs(w2, k, b):
        return jnp.ones((k, b), jnp.float32) + w2[0, 0]

    def scatter_vmapped(w2, idx, val):
        k, b, _ = idx.shape
        idx = live(w2, idx)
        g = jax.vmap(lambda i, v, c: mxu.scatter_add(SparseBatch(i, v), c, r))(
            idx, val, coeffs(w2, k, b))
        return w2 + 1e-30 * jnp.sum(g, axis=0)

    def scatter_calls(w2, idx, val):
        k, b, _ = idx.shape
        idx = live(w2, idx)
        c = coeffs(w2, k, b)
        g = sum(mxu.scatter_add(SparseBatch(idx[j], val[j]), c[j], r) for j in range(k))
        return w2 + 1e-30 * g

    def scatter_flat(w2, idx, val):
        k, b, p = idx.shape
        idx = live(w2, idx)
        g = mxu.scatter_add(SparseBatch(idx.reshape(k * b, p), val.reshape(k * b, p)),
                            coeffs(w2, k, b).reshape(-1), r)
        return w2 + 1e-30 * g

    out = {"device": {"platform": device.platform, "kind": device.device_kind},
           "n_features": N_FEATURES, "rows_R": r, "nnz": NNZ, "scan": [lo, hi],
           "gather_us": [], "scatter_us": []}
    # (workers, rows a worker): the step's shapes at K = 1 / 4 and batch
    # 100 / 200, the evaluation's 512-row call, and a sweep over T
    step_shapes = [(1, 100), (4, 100), (1, 416), (1, 448), (1, 512), (4, 200)]
    sweep = [(1, n) for n in (8, 32, 64, 128, 200, 256, 300, 1024, 2048, 4096)]
    if rehearse:
        step_shapes, sweep = [(2, 4)], [(1, 8)]
    for k, b in (step_shapes + sweep if "gather" in only else []):
        idx, val = rows(k, b)
        row = {"workers": k, "rows": b, "entries": k * b * NNZ,
               "matvec_rows": mxu.lane_minor_rows(k * b, NNZ),
               "flat": slope(gather_flat, idx, val),
               "entries_minor": slope(gather_entries_minor, idx, val)}
        if row["matvec_rows"] != k * b:
            row["matvec"] = slope(gather_matvec, idx, val)
        if k > 1:
            row["vmapped"] = slope(gather_vmapped, idx, val)
        out["gather_us"].append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    flat_shapes = [(2, 4)] if rehearse else [(4, 100), (4, 200), (1, 100), (1, 200), (1, 400)]
    for k, b in (flat_shapes if "scatter" in only else []):
        idx, val = rows(k, b)
        row = {"workers": k, "rows": b, "entries": k * b * NNZ,
               "flat": slope(scatter_flat, idx, val)}
        if k > 1:
            row["vmapped"] = slope(scatter_vmapped, idx, val)
            row["calls"] = slope(scatter_calls, idx, val)
        out["scatter_us"].append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)

    def once_compiled(n_rows, features, k, t, call):
        """us a call of `call(w2, idx, val)` on K workers' T entries each, the
        trip count an argument of the compiled loop: one compile a shape."""
        width = NNZ if t % NNZ == 0 else mxu.LANES
        idx = jnp.asarray(np.sort(rng.integers(
            0, features, (k, t // width, width)).astype(np.int32), axis=-1))
        val = jnp.asarray(np.abs(rng.normal(size=idx.shape)).astype(np.float32))
        f = jax.jit(lambda n, c, i, v: jax.lax.fori_loop(0, n, lambda _, cc: call(cc, i, v), c))
        c0 = jnp.full((n_rows, mxu.LANES), 0.01, jnp.float32)
        jax.block_until_ready(f(lo, c0, idx, val))

        def best(n):
            seconds = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(f(n, c0, idx, val))
                seconds.append(time.perf_counter() - t0)
            return min(seconds)
        return (best(hi) - best(lo)) / (hi - lo) * 1e6

    def scatter_apart(w2, idx, val):
        """The K workers' scatters, replies kept apart as in the step (which
        regularises each by its own support before the sum)."""
        k, b, _ = idx.shape
        g = jax.vmap(lambda i, v, c: mxu.scatter_add(SparseBatch(i, v), c, w2.shape[0]))(
            live(w2, idx), val, coeffs(w2, k, b))
        return w2 + 1e-30 * jnp.sum(jnp.abs(g), axis=0)

    @contextlib.contextmanager
    def rule_says(shards):
        """`mxu.scatter_shards` made to answer `shards` while a program is traced."""
        rule, mxu.scatter_shards = mxu.scatter_shards, lambda *_: shards
        try:
            yield
        finally:
            mxu.scatter_shards = rule

    out["scatter_shards_us"] = []
    # ISSUE 29's grid, and the two depths either side of the compiler's one-window
    # limit at R = 376 (8,960 in one window, 8,968 tiled: read off a described v5e)
    depths = (3_800, 5_700, 7_600, 7_680, 8_960, 8_968, 9_500, 9_728, 11_400, 13_300,
              15_200, 15_360, 22_800, 30_400, 38_000, 77_824)
    wide = mxu.n_blocks(200_000)  # R = 1,568
    grid = [(r, N_FEATURES, t) for t in depths] + [(wide, 200_000, t) for t in (7_600, 15_200)]
    if rehearse:
        grid = [(r, N_FEATURES, 2 * NNZ), (wide, 200_000, mxu.LANES)]
    for n_rows, features, t in (grid if "scatter_shards" in only else []):
        row = {"rows_R": n_rows, "entries": t, "rule": mxu.scatter_shards(t, n_rows)}
        for k in (1, 4):
            row[f"workers_{k}"] = {}
            for s in sorted({1, 2, 3, 4, 8, row["rule"], -(-t // 7_600)}):
                with rule_says(s):  # `once_compiled` traces at its first call
                    row[f"workers_{k}"][str(s)] = once_compiled(
                        n_rows, features, k, t, scatter_apart)
        if n_rows == wide:  # the other half of the pair the kernel rule weighs
            row["merged_margins_4"] = once_compiled(n_rows, features, 4, t, gather_matvec)
        out["scatter_shards_us"].append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)

    def resident(features):
        """(rows, `rcv1-hinge`'s model) over `features` features for `step_us`."""
        from distributed_sgd_tpu.data.rcv1 import Dataset
        from distributed_sgd_tpu.models.linear import make_model

        n = 64 if rehearse else 32_768
        data = Dataset(
            np.sort(rng.integers(0, features, (n, NNZ)).astype(np.int32), axis=-1),
            np.abs(rng.normal(size=(n, NNZ))).astype(np.float32),
            rng.choice([-1, 1], n).astype(np.int32), features)
        return data, make_model("hinge", 1e-5, features,
                                dim_sparsity=np.ones((features,), np.float32))

    def step_us(data, model, workers, batch, shards, steps=2 if rehearse else 2000):
        """us a step of `BoundSync.epoch` (one device, `workers` virtual
        workers of `batch` rows of NNZ entries) with the rule made to say
        `shards`: the best of `reps` epochs of `steps` steps."""
        from distributed_sgd_tpu.parallel.mesh import make_mesh
        from distributed_sgd_tpu.parallel.sync import SyncEngine

        features = model.n_features
        w, key = jnp.zeros((features,), jnp.float32), jax.random.PRNGKey(0)
        with rule_says(shards):
            bound = SyncEngine(model, make_mesh(1), batch, 0.5, kernel="mxu",
                               virtual_workers=workers, eval_chunk=64).bind(data, steps)
            jax.block_until_ready(bound.epoch(w, key))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(bound.epoch(w, key))
            best = min(best, time.perf_counter() - t0)
        return best / steps * 1e6

    out["step_us"] = []
    near = (50, 75, 100, 101, 105, 110, 115, 117, 118)  # up to 8,968 entries
    far = (125, 128, 150, 175, 200, 250, 300, 400, 500, 1024)
    steps_grid = [(N_FEATURES, 4, b, (1, 2)) for b in near] + [
        (N_FEATURES, 4, b, (1, 2, 3, 4, 8)) for b in far] + [
        (N_FEATURES, 1, b, (1, 2, 4)) for b in (100, 200, 400)] + [
        (200_000, 4, b, (1, 2, 3, 4, 8)) for b in (100, 200)]
    if rehearse:
        steps_grid = [(N_FEATURES, 2, 4, (1, 2)), (2_000, 1, 3, (1, 3))]
    made = {}  # features -> (rows, model)
    for features, k, b, forced_s in (steps_grid if "step" in only else []):
        t = b * NNZ
        if features not in made:
            made[features] = resident(features)
        row = {"rows_R": mxu.n_blocks(features), "workers": k, "batch": b, "entries": t,
               "rule": mxu.scatter_shards(t, mxu.n_blocks(features))}
        # beside the rule's S, the S of the two depths weighed for its constant
        row["step_us"] = {str(s): step_us(*made[features], k, b, s) for s in sorted(
            set(forced_s) | {row["rule"], -(-t // 7_600), -(-t // 8_960)})}
        out["step_us"].append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
