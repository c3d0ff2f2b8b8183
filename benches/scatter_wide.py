"""Multi-shard (wide-output) scatter: measuring the named ~3.5x lever.

The round-3 roofline (benches/roofline.py, BASELINE.md) ends on an
estimate: the scatter matmul `ohr.T [R, T] @ contrib [T, 128]` produces a
single [376, 128] output — 3 MXU output tiles fed by a T-deep contraction
— so the systolic array is output-tile-starved, and the named fix is "a
scatter with a wider output footprint (e.g. multi-shard weight blocks)".
This bench MEASURES that fix (VERDICT r3 item 2):

- `baseline`: the shipped one-hot scatter (ops/mxu.py scatter_add);
- `batched(S)`: split the contraction into S shards and run them as one
  batched dot_general [S, R, T/S] x [S, T/S, 128] -> [S, R, 128], then
  sum over S — S x the output tiles in flight, identical FLOPs + a cheap
  [S, R, 128] reduction;
- `unrolled(S)`: the same S shard matmuls as S independent dots summed in
  a tree — lets XLA schedule them as parallel computations rather than a
  batch loop.

Timing: chained-scan slope (the roofline's method — each iteration's
carry depends on the scatter output so nothing folds away; per-iter time
from the slope between two trip counts), at the reference step's shapes:
B in {300, 1024} samples x P=76 entries, R=376 blocked rows.

Modes (BASELINE.md round-4 "wide-output scatter" section sources all
three; raw JSON under benches/results/):
  (default)     full variant sweep at B in {300, 1024}
  --crossover   baseline vs batched-S=4 across B in {100..1024} — places
                the T ~ 32k crossover
  --fused-ab    interleaved same-chip A/B of the FULL flagship epoch with
                the scatter formulation swapped (single-dot, batched-S=4,
                and a shared [S, sub, R] one-hot feeding gather AND
                scatter) — the experiment that decides what ships

Prints one JSON document; BASELINE.md records the conclusion.
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
SHARDS = (2, 4, 8, 16)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def timed_best(fn, reps: int = 3) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _slope_tools():
    import jax

    def looped(body, carry0, iters):
        f = jax.jit(lambda c: jax.lax.scan(
            lambda cc, _: (body(cc), None), c, None, length=iters)[0])
        jax.block_until_ready(f(carry0))
        return timed_best(lambda: jax.block_until_ready(f(carry0)))

    def per_iter(body, carry0, lo=256, hi=4096):
        return max(looped(body, carry0, hi) - looped(body, carry0, lo),
                   1e-12) / (hi - lo)

    return per_iter


def crossover() -> None:
    """baseline vs batched-S=4 across batch sizes: places the crossover."""
    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.ops import mxu
    from distributed_sgd_tpu.ops.sparse import SparseBatch

    log(f"device: {jax.devices()[0]}")
    r = mxu.n_blocks(N_FEATURES)
    per_iter = _slope_tools()
    out: dict = {"study": "scatter_crossover", "r_blocks": r, "results": {}}
    for b in (100, 200, 300, 400, 512, 700, 1024):
        t_flat = b * NNZ
        rng = np.random.default_rng(0)
        idx = np.sort(rng.integers(0, N_FEATURES, (b, NNZ)).astype(np.int32), axis=1)
        val = np.abs(rng.normal(size=(b, NNZ))).astype(np.float32)
        bidx, bval = jnp.asarray(idx), jnp.asarray(val)
        flops = 2.0 * t_flat * r * 128
        batch = SparseBatch(bidx, bval)

        def build(c):
            oh = mxu.OneHotBatch(batch, r)
            cv = (oh.values.reshape(b, NNZ) * c[:b, 0:1]).reshape(-1)
            return oh.ohr, oh.ohc * cv[:, None]

        def baseline(c):
            ohr, contrib = build(c)
            g = jax.lax.dot(ohr.T, contrib, preferred_element_type=jnp.float32)
            return c + 1e-30 * g[0, 0]

        s, sub = 4, t_flat // 4

        def batched(c):
            ohr, contrib = build(c)
            g = jax.lax.dot_general(
                ohr.reshape(s, sub, r), contrib.reshape(s, sub, 128),
                (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)
            return c + 1e-30 * jnp.sum(g, axis=0)[0, 0]

        tb = per_iter(baseline, bval)
        ts = per_iter(batched, bval)
        out["results"][f"B{b}"] = {
            "t_flat": t_flat,
            "baseline": {"us": round(tb * 1e6, 1),
                         "tflops": round(flops / tb / 1e12, 1)},
            "batched_s4": {"us": round(ts * 1e6, 1),
                           "tflops": round(flops / ts / 1e12, 1)},
            "speedup": round(tb / ts, 2),
        }
        log(f"B={b}: baseline {tb*1e6:.1f}us ({flops/tb/1e12:.1f} TF/s) "
            f"batched4 {ts*1e6:.1f}us ({flops/ts/1e12:.1f} TF/s) "
            f"= {tb/ts:.2f}x")
    print(json.dumps(out, indent=2))


def fused_ab() -> None:
    """Interleaved same-chip A/B of the full flagship epoch per scatter
    formulation — the experiment that decides what ships in ops/mxu.py."""
    import jax
    import jax.numpy as jnp

    import distributed_sgd_tpu.models.linear as lin
    from distributed_sgd_tpu.data.rcv1 import Dataset
    from distributed_sgd_tpu.models.linear import SparseSVM
    from distributed_sgd_tpu.ops import mxu
    from distributed_sgd_tpu.parallel.mesh import make_mesh
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    n, b, k, s = 804_414, 100, 3, 4
    log(f"device: {jax.devices()[0]}")

    class BatchedScatter(mxu.OneHotBatch):
        """Only the scatter side sharded (gather untouched)."""

        def scatter_add(self, coeff):
            cv = (self.values.reshape(self.batch_size, self.pad_width)
                  * coeff.astype(jnp.float32)[:, None]).reshape(-1)
            contrib = (self.ohc.astype(jnp.float32) * cv[:, None]).astype(
                self.ohr.dtype)
            t, r = self.ohr.shape
            if t % s or t > 32_768:
                return jax.lax.dot(self.ohr.T, contrib,
                                   preferred_element_type=jnp.float32)
            g = jax.lax.dot_general(
                self.ohr.reshape(s, t // s, r), contrib.reshape(s, t // s, 128),
                (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)
            return jnp.sum(g, axis=0)

    class SharedWide(mxu.OneHotBatch):
        """One [S, sub, R] one-hot layout feeding gather AND scatter."""

        def __init__(self, batch, n_rows, dtype=jnp.float32):
            flat_idx = batch.indices.reshape(-1)
            t = flat_idx.shape[0]
            self.values = batch.values.astype(jnp.float32).reshape(-1)
            self._t = t
            self._shard = s if t % s == 0 and t <= 32_768 else 1
            sub = t // self._shard
            self.ohr3 = jax.nn.one_hot(
                flat_idx.reshape(self._shard, sub) // 128, n_rows, dtype=dtype)
            self.ohc = jax.nn.one_hot(flat_idx % 128, 128, dtype=dtype)
            self.batch_size = batch.batch_size
            self.pad_width = batch.pad_width

        def gathered_products(self, w2):
            m1 = jax.lax.dot_general(
                self.ohr3, w2.astype(self.ohr3.dtype), (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).reshape(self._t, 128)
            return jnp.sum(m1 * self.ohc.astype(jnp.float32), axis=-1) * self.values

        def scatter_add(self, coeff):
            cv = (self.values.reshape(self.batch_size, self.pad_width)
                  * coeff.astype(jnp.float32)[:, None]).reshape(-1)
            contrib = (self.ohc.astype(jnp.float32) * cv[:, None]).astype(
                self.ohr3.dtype)
            sub = self._t // self._shard
            g = jax.lax.dot_general(
                self.ohr3, contrib.reshape(self._shard, sub, 128),
                (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)
            return jnp.sum(g, axis=0)

    rng = np.random.default_rng(0)
    idx = np.sort(rng.integers(0, N_FEATURES, (n, NNZ)).astype(np.int32), axis=1)
    val = np.abs(rng.normal(size=(n, NNZ))).astype(np.float32)
    val /= np.maximum(np.linalg.norm(val, axis=1, keepdims=True), 1e-12)
    y = rng.choice(np.array([-1, 1], np.int32), n)
    ds = np.zeros(N_FEATURES, np.float32)
    counts = np.bincount(idx.ravel(), minlength=N_FEATURES)
    nz = counts > 0
    ds[nz] = 1.0 / (counts[nz] + 1.0)
    model = SparseSVM(lam=1e-5, n_features=N_FEATURES, dim_sparsity=jnp.asarray(ds))
    data = Dataset(indices=idx, values=val, labels=y, n_features=N_FEATURES)

    def epoch_s(label, cls, formulation=None):
        # two override mechanisms, one harness: the round-4 wide-output
        # layouts are OneHotBatch subclasses (monkeypatched in), the
        # round-6 formulations are registry backends (ops/mxu.py
        # DSGD_SCATTER) scoped around engine build + trace
        orig = mxu.OneHotBatch
        if cls is not None:
            mxu.OneHotBatch = cls
            lin.mxu.OneHotBatch = cls
        try:
            eng = SyncEngine(model, make_mesh(1), batch_size=b,
                             learning_rate=0.5, virtual_workers=k,
                             scatter=formulation)
            bound = eng.bind(data)
            key = jax.random.PRNGKey(0)

            def run(n_ep):
                return np.asarray(bound.multi_epoch(
                    jnp.zeros(N_FEATURES, jnp.float32), key, n_ep))

            run(1)
            run(3)
            t1 = timed_best(lambda: run(1), reps=5)
            t3 = timed_best(lambda: run(3), reps=5)
            e = (t3 - t1) / 2
            log(f"{label}: epoch {e:.4f}s, step "
                f"{e/bound.steps_per_epoch*1e6:.1f}us")
            return e
        finally:
            mxu.OneHotBatch = orig
            lin.mxu.OneHotBatch = orig

    # round-4 wide-output layouts + the round-6 selectable formulations
    # (ops/mxu.py; 'single_dot' IS 'onehot') in one interleaved A/B
    variants = {"single_dot": (mxu.OneHotBatch, None),
                "batched_s4": (BatchedScatter, None),
                "shared_wide": (SharedWide, None),
                "segment": (None, "segment"),
                "twostage": (None, "twostage"),
                "bf16": (None, "bf16")}
    # interleave two passes over all variants to cancel shared-chip drift
    times: dict = {name: [] for name in variants}
    for rep in range(2):
        for name, (cls, form) in variants.items():
            times[name].append(epoch_s(f"{name} ({rep + 1})", cls, form))
    base = min(times["single_dot"])
    out = {
        "study": "scatter_fused_ab", "interleaved_reps": 2,
        "device": jax.devices()[0].platform,
        "results": {
            name: {"epoch_s_best": round(min(ts), 4),
                   "epoch_s_all": [round(t, 4) for t in ts],
                   "vs_single_dot": round(base / min(ts), 3)}
            for name, ts in times.items()
        },
    }
    print(json.dumps(out, indent=2))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.ops import mxu
    from distributed_sgd_tpu.ops.sparse import SparseBatch

    log(f"device: {jax.devices()[0]}")
    r = mxu.n_blocks(N_FEATURES)
    out: dict = {"study": "scatter_wide", "r_blocks": r, "results": {}}

    def looped(body, carry0, iters):
        f = jax.jit(lambda c: jax.lax.scan(
            lambda cc, _: (body(cc), None), c, None, length=iters)[0])
        jax.block_until_ready(f(carry0))
        return timed_best(lambda: jax.block_until_ready(f(carry0)))

    def per_iter(body, carry0, lo=64, hi=1024):
        t_lo = looped(body, carry0, lo)
        t_hi = looped(body, carry0, hi)
        return max(t_hi - t_lo, 0.0) / (hi - lo)

    for b in (300, 1024):
        t_flat = b * NNZ
        rng = np.random.default_rng(0)
        idx = np.sort(rng.integers(0, N_FEATURES, (b, NNZ)).astype(np.int32), axis=1)
        val = np.abs(rng.normal(size=(b, NNZ))).astype(np.float32)
        bidx, bval = jnp.asarray(idx), jnp.asarray(val)
        flops = 2.0 * t_flat * r * 128  # contraction count, shared by all

        batch = SparseBatch(bidx, bval)

        # carry flows through coeff so each scan iteration re-runs the
        # scatter; 1e-30 keeps the numeric coupling without changing values
        def mk_carry():
            return bval

        def baseline(c):
            g = mxu.scatter_add(batch, c[:b, 0], r)
            return c + 1e-30 * g[0, 0]

        res_b: dict = {}
        t = per_iter(baseline, mk_carry())
        res_b["baseline"] = {"us": round(t * 1e6, 1),
                             "tflops": round(flops / t / 1e12, 1)}
        log(f"B={b}: baseline {t*1e6:.1f} us = {flops/t/1e12:.1f} TF/s")

        # shared one-hot build (identical to OneHotBatch), then the S-shard
        # scatter variants on the same operands
        def build(c):
            oh = mxu.OneHotBatch(SparseBatch(bidx, bval), r)
            cv = (oh.values.reshape(b, NNZ) * c[:b, 0:1]).reshape(-1)
            contrib = oh.ohc * cv[:, None]  # [T, 128]
            return oh.ohr, contrib

        for s in SHARDS:
            if t_flat % s:
                continue
            sub = t_flat // s

            def batched(c, s=s, sub=sub):
                ohr, contrib = build(c)
                a = ohr.reshape(s, sub, r)
                bm = contrib.reshape(s, sub, 128)
                g = jax.lax.dot_general(
                    a, bm, (((1,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)  # [S, R, 128]
                return c + 1e-30 * jnp.sum(g, axis=0)[0, 0]

            def unrolled(c, s=s, sub=sub):
                ohr, contrib = build(c)
                parts = [
                    jax.lax.dot(ohr[i * sub:(i + 1) * sub].T,
                                contrib[i * sub:(i + 1) * sub],
                                preferred_element_type=jnp.float32)
                    for i in range(s)
                ]
                while len(parts) > 1:  # tree sum
                    parts = [a + bb for a, bb in zip(parts[::2], parts[1::2])] + (
                        [parts[-1]] if len(parts) % 2 else [])
                return c + 1e-30 * parts[0][0, 0]

            for name, body in (("batched", batched), ("unrolled", unrolled)):
                t = per_iter(body, mk_carry())
                res_b[f"{name}_s{s}"] = {"us": round(t * 1e6, 1),
                                         "tflops": round(flops / t / 1e12, 1)}
                log(f"B={b}: {name} S={s}: {t*1e6:.1f} us = "
                    f"{flops/t/1e12:.1f} TF/s")

        out["results"][f"B{b}"] = res_b

    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    if "--crossover" in sys.argv:
        crossover()
    elif "--fused-ab" in sys.argv:
        fused_ab()
    else:
        main()
