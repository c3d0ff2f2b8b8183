"""Pallas-vs-XLA regime sweep (VERDICT round-1 item 8).

Times one sync DP step (sample + fused per-worker gradient + regularize +
mean + update) for the 'mxu' (XLA one-hot matmuls) and 'pallas' (fused
single-launch VMEM kernel, ops/pallas_sparse.py) backends across feature
dims D, batch sizes B, and virtual-worker counts K, slope-fit over two
scan lengths inside single compiled programs.

The question this answers: is there a shape regime where the hand-fused
kernel beats XLA's fusion of the same one-hot formulation?  The result
feeds the kernel-selection guidance in BASELINE.md / sync.py.

Usage: python benches/pallas_sweep.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

P = 76  # RCV1-like nnz per row


def time_step(model_D, B, K, kernel, n=20_000, s1=200, s2=2000):
    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.rcv1 import Dataset
    from distributed_sgd_tpu.models.linear import SparseSVM
    from distributed_sgd_tpu.parallel.mesh import make_mesh
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    rng = np.random.default_rng(0)
    idx = rng.integers(0, model_D, (n, P)).astype(np.int32)
    val = rng.random((n, P)).astype(np.float32)
    y = rng.choice([-1, 1], n).astype(np.int32)
    model = SparseSVM(lam=1e-5, n_features=model_D,
                      dim_sparsity=jnp.asarray(np.full(model_D, 1e-3, np.float32)))
    data = Dataset(indices=idx, values=val, labels=y, n_features=model_D)
    eng = SyncEngine(model, make_mesh(1), batch_size=B, learning_rate=0.5,
                     kernel=kernel, virtual_workers=K)
    w0 = jnp.zeros(model_D, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
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
    return (ts[s2] - ts[s1]) / (s2 - s1) * 1e6  # us/step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--ds", type=str, default="4096,47236")
    ap.add_argument("--bs", type=str, default="100,1024")
    ap.add_argument("--ks", type=str, default="1,3")
    args = ap.parse_args()

    import jax.numpy as jnp

    np.asarray(jnp.zeros(4))  # force backend init before timing

    Ds = [int(x) for x in args.ds.split(",")]
    Bs = [int(x) for x in args.bs.split(",")]
    Ks = [int(x) for x in args.ks.split(",")]
    if args.quick:
        Ds, Bs, Ks = Ds[:1], Bs[:1], Ks[:1]
    for D in Ds:
        for B in Bs:
            for K in Ks:
                row = {"D": D, "B": B, "K": K, "P": P}
                for kernel in ("mxu", "pallas"):
                    t0 = time.perf_counter()
                    try:
                        us = round(time_step(D, B, K, kernel), 1)
                    except Exception as e:  # e.g. pallas VMEM OOM at large B*K
                        msg = str(e).lower()
                        oom = any(s in msg for s in ("memory", "vmem", "resource_exhausted"))
                        us = "OOM" if oom else f"error: {type(e).__name__}"
                    row[kernel + "_us"] = us
                    row[kernel + "_wall_s"] = round(time.perf_counter() - t0, 1)
                if isinstance(row["pallas_us"], float) and isinstance(row["mxu_us"], float):
                    row["pallas_vs_mxu"] = round(row["pallas_us"] / row["mxu_us"], 2)
                print(json.dumps(row), flush=True)


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main()
