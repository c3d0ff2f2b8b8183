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
  one flat call on all entries (what a linear regulariser would allow).

Timing: the slope of a chained `lax.scan` between two trip counts: each
iteration's carry depends on the call's output, and the indices are arguments, not constants, so the
one-hot operands are built in the fusion as in the step.

    python benches/onehot_call_sweep.py [--rehearse]

Prints one JSON document.  Refuses a CPU unless `--rehearse` (tiny shapes,
no timing worth reading).
"""

from __future__ import annotations

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
    for k, b in step_shapes + sweep:
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
    for k, b in ([(2, 4)] if rehearse else [(4, 100), (4, 200), (1, 100), (1, 200), (1, 400)]):
        idx, val = rows(k, b)
        row = {"workers": k, "rows": b, "entries": k * b * NNZ,
               "flat": slope(scatter_flat, idx, val)}
        if k > 1:
            row["vmapped"] = slope(scatter_vmapped, idx, val)
            row["calls"] = slope(scatter_calls, idx, val)
        out["scatter_us"].append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
