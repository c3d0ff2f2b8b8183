"""Roofline accounting for the flagship RCV1 sync step (BASELINE.md).

Answers VERDICT r2 item 6: is the measured ~72 us step at a hardware
roofline, and if not, which lever is next?  Method:

1. steady-state epoch wall-clock on the real chip (slope fit, identical
   to bench.py's methodology);
2. XLA's own cost model for the compiled epoch program
   (`compiled.cost_analysis()`: flops + bytes accessed) — no hand-derived
   constants on the numerator;
3. achieved FLOP/s and HBM bytes/s divided by the published peaks of
   the chip it ran on (benches/device_peaks.py, keyed by device_kind; a
   device without a row there is an error);
4. a per-piece timing breakdown of the step at the same shapes: one-hot
   gather matmul (margins), one-hot scatter matmul (gradient), weight
   update, and the whole fused step;
5. optional jax.profiler trace (--trace DIR) for offline inspection.

Prints one JSON line on stdout; the analysis prose lives in BASELINE.md.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_SAMPLES = 804_414
N_FEATURES = 47_236
NNZ = 76
BATCH = 100
N_WORKERS = 3
LR = 0.5
LAM = 1e-5


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def timed_best(fn, reps: int = 5) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main() -> None:
    import jax
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.rcv1 import Dataset
    from distributed_sgd_tpu.models.linear import SparseSVM
    from distributed_sgd_tpu.ops import mxu
    from distributed_sgd_tpu.ops.sparse import SparseBatch
    from distributed_sgd_tpu.parallel.mesh import make_mesh
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    trace_dir = None
    if "--trace" in sys.argv:
        trace_dir = sys.argv[sys.argv.index("--trace") + 1]

    from benches.device_peaks import peaks_for

    dev = jax.devices()[0]
    peaks = peaks_for(dev)  # before any work: an unlisted chip is an error
    log(f"device: {dev} ({dev.device_kind})")
    rng = np.random.default_rng(0)
    idx = rng.integers(0, N_FEATURES, size=(N_SAMPLES, NNZ)).astype(np.int32)
    idx.sort(axis=1)
    val = np.abs(rng.normal(size=(N_SAMPLES, NNZ))).astype(np.float32)
    val /= np.maximum(np.linalg.norm(val, axis=1, keepdims=True), 1e-12)
    y = rng.choice(np.array([-1, 1], np.int32), N_SAMPLES)

    ds = np.zeros(N_FEATURES, dtype=np.float32)
    counts = np.bincount(idx.ravel(), minlength=N_FEATURES)
    nz = counts > 0
    ds[nz] = 1.0 / (counts[nz] + 1.0)
    model = SparseSVM(lam=LAM, n_features=N_FEATURES, dim_sparsity=jnp.asarray(ds))

    engine = SyncEngine(model, make_mesh(1), batch_size=BATCH, learning_rate=LR,
                        virtual_workers=N_WORKERS)
    bound = engine.bind(Dataset(indices=idx, values=val, labels=y,
                                n_features=N_FEATURES))
    steps = bound.steps_per_epoch
    w0 = jnp.zeros((N_FEATURES,), dtype=jnp.float32)
    key = jax.random.PRNGKey(0)

    # -- 1. steady-state epoch time (slope fit over 1 vs 3 epochs) ---------
    _ = np.asarray(bound.multi_epoch(w0, key, 1))  # compile + warm
    _ = np.asarray(bound.multi_epoch(w0, key, 3))
    t1 = timed_best(lambda: np.asarray(bound.multi_epoch(w0, key, 1)))
    t3 = timed_best(lambda: np.asarray(bound.multi_epoch(w0, key, 3)))
    epoch_s = (t3 - t1) / 2.0
    step_s = epoch_s / steps
    log(f"epoch {epoch_s:.4f}s over {steps} steps -> {step_s*1e6:.1f} us/step")

    # -- 2. XLA cost model for the compiled epoch --------------------------
    # cost_analysis counts a lax.scan BODY once, not x trip-count, so the
    # reported flops ARE the per-step flops; validate against the analytic
    # one-hot count (2 matmuls of [T,R]x[R,128] per worker, T = B*P) and
    # scale by steps_per_epoch for the epoch totals.
    compiled = bound._epoch.lower(
        w0, bound._opt_state, bound.data.indices, bound.data.values,
        bound.data.labels, key,
    ).compile()
    cost = compiled.cost_analysis() or {}
    flops_step_xla = float(cost.get("flops", 0.0))
    r_blocks = mxu.n_blocks(N_FEATURES)
    flops_step_analytic = 2 * 2 * N_WORKERS * BATCH * NNZ * r_blocks * 128
    log(f"per-step flops: XLA cost model {flops_step_xla/1e9:.2f} GF, "
        f"analytic one-hot {flops_step_analytic/1e9:.2f} GF")

    # per-step HBM bytes, analytic (the XLA 'bytes accessed' figure counts
    # the resident dataset once for the whole scan): batch rows in, blocked
    # weights read for gather + update, gradient write, weights write
    w2_bytes = r_blocks * 128 * 4
    batch_bytes = N_WORKERS * BATCH * NNZ * (4 + 4)
    bytes_step = batch_bytes + 2 * w2_bytes + 2 * w2_bytes

    achieved_flops = flops_step_xla / step_s if step_s > 0 else 0.0
    achieved_bps = bytes_step / step_s if step_s > 0 else 0.0
    mxu_util = achieved_flops / peaks["bf16_flops"]
    hbm_util = achieved_bps / peaks["hbm_bps"]
    log(f"achieved: {achieved_flops/1e12:.1f} TFLOP/s "
        f"({100*mxu_util:.1f}% of bf16 MXU peak), "
        f"~{achieved_bps/1e9:.1f} GB/s ({100*hbm_util:.1f}% of HBM peak)")

    # -- 3. per-piece timing at identical shapes ---------------------------
    # A single call of a microsecond piece times the dispatch, not the
    # piece; each piece runs as a CHAINED lax.scan (the carry depends on
    # the piece's output so nothing folds away) and per-iter time comes
    # from the slope between two trip counts.
    kb = N_WORKERS * BATCH
    bidx = jnp.asarray(idx[:kb])
    bval = jnp.asarray(val[:kb])
    by = jnp.asarray(y[:kb], jnp.float32)
    w2 = mxu.to_blocked(w0, N_FEATURES)
    r = w2.shape[0]
    g2c = np.asarray(
        jax.jit(lambda i_, v_, c_: mxu.scatter_add(SparseBatch(i_, v_), c_, r))(
            bidx, bval, by))

    def looped(body, carry0, iters):
        f = jax.jit(
            lambda c: jax.lax.scan(lambda cc, _: (body(cc), None), c,
                                   None, length=iters)[0],
            static_argnums=(),
        )
        jax.block_until_ready(f(carry0))  # compile
        return timed_best(lambda: jax.block_until_ready(f(carry0)), reps=3)

    def per_iter(body, carry0, lo=64, hi=1024):
        t_lo = looped(body, carry0, lo)
        t_hi = looped(body, carry0, hi)
        return max(t_hi - t_lo, 0.0) / (hi - lo)

    batch = SparseBatch(bidx, bval)
    t_margins = per_iter(
        lambda c: c + 1e-30 * jnp.sum(mxu.matvec(batch, c)), w2)
    t_scatter = per_iter(
        lambda c: c + 1e-30 * mxu.scatter_add(batch, c[:kb, 0], r)[0, 0], bval)
    t_update = per_iter(lambda c: c - LR * jnp.asarray(g2c), w2)
    log(f"pieces (chained-scan slope): gather-matmul {t_margins*1e6:.1f} us, "
        f"scatter-matmul {t_scatter*1e6:.1f} us, update {t_update*1e6:.1f} us; "
        f"sum {1e6*(t_margins+t_scatter+t_update):.1f} us vs in-epoch step "
        f"{step_s*1e6:.1f} us (difference = hinge/regularize fusing + "
        f"sampling + scan overhead)")

    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        np.asarray(bound.multi_epoch(w0, key, 1))
        jax.profiler.stop_trace()
        log(f"profiler trace -> {trace_dir}")

    print(json.dumps({
        "metric": "rcv1_step_mxu_utilization",
        "value": round(100 * mxu_util, 1),
        "unit": "%_of_bf16_peak",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "epoch_seconds": round(epoch_s, 4),
        "step_us": round(step_s * 1e6, 1),
        "steps_per_epoch": steps,
        "flops_step_xla_gf": round(flops_step_xla / 1e9, 2),
        "flops_step_analytic_gf": round(flops_step_analytic / 1e9, 2),
        "bytes_step_analytic_kb": round(bytes_step / 1e3, 1),
        "achieved_tflops": round(achieved_flops / 1e12, 2),
        "achieved_gbps": round(achieved_bps / 1e9, 2),
        "hbm_util_pct": round(100 * hbm_util, 1),
        "piece_us": {
            "gather_matmul": round(t_margins * 1e6, 1),
            "scatter_matmul": round(t_scatter * 1e6, 1),
            "update": round(t_update * 1e6, 1),
        },
        "peak_bf16_tflops": peaks["bf16_flops"] / 1e12,
        "peak_hbm_gbps": peaks["hbm_bps"] / 1e9,
    }))


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main()
