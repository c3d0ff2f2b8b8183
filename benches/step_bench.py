"""Sync-step kernel-backend microbenchmark: scalar vs mxu.

Times one full sync DP step (sample + per-worker gradient sum + regularize
+ mean + update) at RCV1 shapes for each kernel backend of
parallel/sync.py, slope-fit over two scan lengths inside single compiled
programs (removes dispatch/RTT — see BASELINE.md methodology).

Usage: python benches/step_bench.py [n_samples] [--workers K]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

D, P, B = 47_236, 76, 100


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("n_samples", nargs="?", type=int, default=100_000)
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--kernels", type=str, default="scalar,mxu")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.rcv1 import Dataset
    from distributed_sgd_tpu.models.linear import SparseSVM
    from distributed_sgd_tpu.parallel.mesh import make_mesh
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    rng = np.random.default_rng(0)
    n = args.n_samples
    idx = rng.integers(0, D, (n, P)).astype(np.int32)
    val = rng.random((n, P)).astype(np.float32)
    y = rng.choice([-1, 1], n).astype(np.int32)
    ds = np.abs(rng.normal(size=D)).astype(np.float32) * 0.001
    model = SparseSVM(lam=1e-5, n_features=D, dim_sparsity=jnp.asarray(ds))
    data = Dataset(indices=idx, values=val, labels=y, n_features=D)
    mesh = make_mesh(1)
    w0 = jnp.zeros(D, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)

    print(f"{n} samples, {args.workers} workers x batch {B} "
          f"({args.workers * B * P} entries/step); best-of-3, slope-fit")
    for kernel in args.kernels.split(","):
        eng = SyncEngine(model, mesh, batch_size=B, learning_rate=0.5,
                         kernel=kernel, virtual_workers=args.workers)
        s1, s2 = 200, 1000
        ts = {}
        for S in (s1, s2):
            bound = eng.bind(data, steps_per_epoch=S)
            np.asarray(bound.epoch(w0, key))  # compile + warm
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(bound.epoch(w0, key))
                best = min(best, time.perf_counter() - t0)
            ts[S] = best
        us = (ts[s2] - ts[s1]) / (s2 - s1) * 1e6
        print(f"  kernel={kernel:>7}: {us:8.2f} us/step")


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main()
