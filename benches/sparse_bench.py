"""Kernel microbenchmarks — the reference SparseBench equivalent.

The reference benches its two sparse representations (`Sparse` map vs
`SparseArrayVector` CSR) on addition / elementwise product / dot /
scalar multiplication / normSquared over 100 real RCV1 rows
(src/test/scala/epfl/distributed/math/SparseBench.scala:22-68).  This
benches the same five ops over RCV1-shaped rows in three implementations:

- `xla`: this framework's padded-sparse batch kernels (jit'd, on the
  default JAX platform — TPU when available);
- `xla_flat`: the flat CSR-style layout (ops/flat_sparse.py), the
  SparseArrayVector counterpart in the rep-vs-rep comparison;
- `scipy`: scipy.sparse CSR on CPU (a strong conventional baseline);
- `boxed`: per-row python dict arithmetic, the reference's cost model
  (boxed per-entry ops, fresh map per operation).

Usage: python benches/sparse_bench.py [n_rows] [--gate]

`--gate` additionally emits one flat JSON line and runs it through the
round-over-round regression harness (benches/regress.py) against the
kernel history — the reference wraps exactly this bench in ScalaMeter's
RegressionReporter (SparseBench.scala:9-15).  Only the framework's own
kernel timings (`xla_*`/`xla_flat_*`, `*_s` keys) gate; the scipy/boxed
comparison baselines are recorded as ungated `*_baseline` keys.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_rows(n_rows: int, n_features: int = 47236, nnz: int = 76, seed: int = 0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n_features, size=(n_rows, nnz), dtype=np.int64).astype(np.int32)
    idx.sort(axis=1)
    val = rng.random((n_rows, nnz)).astype(np.float32)
    return idx, val


def timeit(fn, reps: int = 5) -> float:
    fn()  # warmup / compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def bench_xla(idx, val, w):
    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.ops.sparse import SparseBatch, matvec, scatter_add

    d = len(w)
    batch = SparseBatch(jnp.asarray(idx), jnp.asarray(val))
    wj = jnp.asarray(w)
    coeff = jnp.ones(idx.shape[0], dtype=jnp.float32)

    dot = jax.jit(lambda b, w: matvec(b, w))
    add = jax.jit(lambda b, c: scatter_add(b, c, d))  # keyset-union sum of rows
    scal = jax.jit(lambda b: SparseBatch(b.indices, b.values * 2.0))
    prod = jax.jit(lambda b, w: b.values * jnp.take(w, b.indices))  # x * w elementwise
    norm2 = jax.jit(lambda b: jnp.sum(b.values**2, axis=-1))

    block = jax.block_until_ready
    return {
        "dot": timeit(lambda: block(dot(batch, wj))),
        "add(sum rows)": timeit(lambda: block(add(batch, coeff))),
        "scalar*": timeit(lambda: block(scal(batch))),
        "elementwise*": timeit(lambda: block(prod(batch, wj))),
        "normSquared": timeit(lambda: block(norm2(batch))),
    }


def bench_xla_flat(idx, val, w):
    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.ops import flat_sparse
    from distributed_sgd_tpu.ops.sparse import SparseBatch

    d = len(w)
    # pass HOST arrays: from_padded is host-side
    flat = flat_sparse.from_padded(SparseBatch(idx, val))
    wj = jnp.asarray(w)
    coeff = jnp.ones(idx.shape[0], dtype=jnp.float32)

    dot = jax.jit(lambda b, w: flat_sparse.matvec(b, w))
    add = jax.jit(lambda b, c: flat_sparse.scatter_add(b, c, d))
    scal = jax.jit(lambda b: b._replace(values=b.values * 2.0))
    prod = jax.jit(lambda b, w: b.values * jnp.take(w, b.indices))
    norm2 = jax.jit(
        lambda b: jax.ops.segment_sum(b.values**2, b.rows, num_segments=b.n_rows)
    )

    block = jax.block_until_ready
    return {
        "dot": timeit(lambda: block(dot(flat, wj))),
        "add(sum rows)": timeit(lambda: block(add(flat, coeff))),
        "scalar*": timeit(lambda: block(scal(flat))),
        "elementwise*": timeit(lambda: block(prod(flat, wj))),
        "normSquared": timeit(lambda: block(norm2(flat))),
    }


def bench_scipy(idx, val, w):
    from scipy import sparse

    n, p = idx.shape
    d = len(w)
    indptr = np.arange(0, n * p + 1, p)
    m = sparse.csr_matrix((val.ravel(), idx.ravel(), indptr), shape=(n, d))
    return {
        "dot": timeit(lambda: m @ w),
        "add(sum rows)": timeit(lambda: np.asarray(m.sum(axis=0))),
        "scalar*": timeit(lambda: m * 2.0),
        "elementwise*": timeit(lambda: m.multiply(w)),
        "normSquared": timeit(lambda: np.asarray(m.multiply(m).sum(axis=1))),
    }


def bench_boxed(idx, val, w):
    rows = [dict(zip(i.tolist(), v.tolist())) for i, v in zip(idx, val)]

    def dot():
        return [sum(v * w[k] for k, v in r.items()) for r in rows]

    def add():
        acc: dict = {}
        for r in rows:  # keyset-union fold, fresh map per merge (Vec.scala:133-137)
            acc = {k: acc.get(k, 0.0) + r.get(k, 0.0) for k in acc.keys() | r.keys()}
        return acc

    def scal():
        return [{k: v * 2.0 for k, v in r.items()} for r in rows]

    def prod():
        return [{k: v * w[k] for k, v in r.items()} for r in rows]

    def norm2():
        return [sum(v * v for v in r.values()) for r in rows]

    return {
        "dot": timeit(dot, reps=3),
        "add(sum rows)": timeit(add, reps=3),
        "scalar*": timeit(scal, reps=3),
        "elementwise*": timeit(prod, reps=3),
        "normSquared": timeit(norm2, reps=3),
    }


def main() -> None:
    # first non-flag argument is n_rows (SparseBench.scala:22 default 100)
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    n_rows = int(args[0]) if args else 100
    idx, val = make_rows(n_rows)
    w = np.random.default_rng(1).random(47236).astype(np.float32)

    results = {
        "xla": bench_xla(idx, val, w),
        "xla_flat": bench_xla_flat(idx, val, w),
        "scipy": bench_scipy(idx, val, w),
        "boxed": bench_boxed(idx, val, w),
    }
    ops = list(results["xla"])
    print(f"{n_rows} rows x 76 nnz, 47,236 features (median seconds)")
    print(f"{'op':>14} " + " ".join(f"{k:>12}" for k in results))
    for op in ops:
        print(f"{op:>14} " + " ".join(f"{results[k][op]:12.6f}" for k in results))

    if "--gate" in sys.argv:
        import json
        import re

        from benches import regress

        def slug(op):
            return re.sub(r"[^a-z0-9]+", "_", op.lower()).strip("_")

        run = {"metric": "sparse_kernels", "n_rows": n_rows}
        for impl, per_op in results.items():
            for op, secs in per_op.items():
                # framework kernels gate (lower-is-better _s suffix);
                # scipy/boxed are host-side comparison baselines: recorded
                # under an ungated suffix (see regress.direction)
                suffix = "_s" if impl.startswith("xla") else "_baseline"
                run[f"{impl}_{slug(op)}{suffix}"] = round(secs, 6)
        print(json.dumps(run))
        # tolerance 1.0 (2x): these are tens-of-microsecond timings whose
        # July records swung ~2x run to run; the gate exists to
        # catch structural regressions (an accidental de-jit or a fallback
        # to the scalar path is 10x+), not dispatch jitter.  History is
        # per-size (timings scale with n_rows), and — unlike the epoch
        # gate, which logs every run — a FAILING kernel run is NOT
        # recorded: appending regressed values would let repeated failing
        # runs drag the median up until the regression "passes"
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            f"kernel_history_n{n_rows}.json")
        history = regress.load_history(path)
        regressions, lines = regress.check(run, history, tolerance=1.0)
        print(f"kernel gate (n_rows={n_rows}) vs {len(history)} stored "
              f"run(s), tolerance 100%:", file=sys.stderr)
        for ln in lines:
            print(ln, file=sys.stderr)
        if regressions:
            print(f"FAIL: regressed kernels: {', '.join(regressions)} "
                  f"(run NOT recorded)", file=sys.stderr)
            raise SystemExit(1)
        regress.record(run, path)
        print(f"PASS; run appended to {path}", file=sys.stderr)
        raise SystemExit(0)


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main()
