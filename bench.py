"""Benchmark harness: RCV1-scale sync epoch wall-clock on TPU.

`python bench.py` (no flag) measures the device: it exits non-zero when
jax finds no TPU, and its JSON line names the device it ran on
(`platform`, `device_kind`, `device_count`).  The flagged sub-benches are
CPU-loopback gates for counts/bytes/identity, not device measurements.

North-star metric (BASELINE.md): RCV1 sync-SGD epoch wall-clock at the
reference's application.conf defaults — batch 100, lr 0.5, lambda 1e-5,
hinge SVM, nodeCount=3 workers (application.conf:15-28), 47,236 features,
804,414 samples.  The real corpus is not downloadable in this environment,
so the run uses synthetic data with RCV1's exact shape statistics (n, d,
~76 nnz/row, unit-norm rows).

Generator choice (deliberate): this harness KEEPS the uniform-popularity
generator so the headline series (epoch seconds, final_loss 0.16/acc 0.94)
stays comparable across rounds in the driver's BENCH_r records and the
regression history.  Epoch wall-clock is shape-determined and identical
across generators; convergence REALISM lives elsewhere — the full-scenario
and five-config artifacts run on the ltc/IDF generator
(`rcv1_like(idf_values=True)`, benches/full_scenario.py +
benches/baseline_configs.py; see BASELINE.md's Zipf-oscillation study for
why value weighting is what separates the generators).

The TPU side runs the same topology the reference runs: 3 workers, each
computing a per-batch 100-sample gradient sum + regularize, mean-reduced
every step (SyncEngine virtual_workers=3 on one chip; on a pod the same
code spreads workers over the mesh).  Timing: each timed region is ONE
dispatch of a compiled multi-epoch program and ends in `np.asarray` of the
returned weights — a device->host copy, which waits for the device — so
the region covers launch + n epochs on the chip + one 189 KB pull.  It is
taken best-of-5 at 1 and at 3 epochs, and epoch_s =
(t[3 epochs] - t[1 epoch]) / 2: the per-dispatch constant (launch + pull)
cancels in the difference.  Compile + first run is logged separately and
never timed.

vs_baseline (the HEADLINE) is fully measured — no modeled constants: it
is the wall-clock of the reference's boxed-map sync algorithm run end to
end on this host (benches/boxed_baseline.py: same dict-of-float data
structures and formulas as the reference's spire.Number maps, single
process, zero serialization, workers sequential — every simplification
favors the floor), extrapolated from a measured steady-state window of
the full-scale epoch, divided by the TPU epoch.  A workers-parallel
variant (the whole floor divided by nodeCount, more than fair — the
master reduce is serial in the reference) is reported alongside.

The JVM model of round 1 is kept as SECONDARY diagnostics, clearly
labeled as modeled: worker compute and master reduce timed in python and
divided by JVM_SPEEDUP=10, plus an exact wire byte count charged at
1 GB/s.  Because the wire term dominates that model and rests on an
assumed throughput, the JSON reports a sensitivity range (wire charged at
1 and 10 GB/s) and a compute+reduce-only ratio with the wire term
dropped entirely.

Items the real reference also pays that every view EXCLUDES (each would
only raise the baseline): per-epoch full-dataset master eval
(Master.scala:201-209), gRPC framing/HTTP2, STM/executor overhead, GC.

Prints exactly ONE JSON line on stdout; diagnostics go to stderr.
"""

from __future__ import annotations

import json
import math
import sys
import time

import numpy as np

N_SAMPLES = 804_414  # DatasetTests.scala:18
N_FEATURES = 47_236  # Dataset.scala:16
NNZ = 76
BATCH = 100  # application.conf:15
N_WORKERS = 3  # application.conf nodeCount (dev defaults)
LR = 0.5
LAM = 1e-5
JVM_SPEEDUP = 10.0  # conservative python->JVM factor for the baseline proxy
WIRE_GBPS = 1.0  # generous JVM proto map<int32,double> codec throughput
BYTES_PER_ENTRY = 13  # proto map entry: tag+varint key + tag+fixed64 value

STEPS_PER_EPOCH = math.ceil(math.ceil(N_SAMPLES / N_WORKERS) / BATCH)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def gen_data(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, N_FEATURES, size=(n, NNZ), dtype=np.int64).astype(np.int32)
    idx.sort(axis=1)
    val = np.abs(rng.normal(size=(n, NNZ))).astype(np.float32)
    val /= np.maximum(np.linalg.norm(val, axis=1, keepdims=True), 1e-12)
    w_true = rng.normal(size=N_FEATURES).astype(np.float32)
    margins = np.einsum("np,np->n", val, w_true[idx])
    y = np.where(margins > np.median(margins), 1, -1).astype(np.int32)
    return idx, val, y


def _bind_flagship(idx, val, y, batch_size: int):
    """Flagship model + 3-worker sync engine bound to the full dataset —
    the ONE binding both operating points (B=100 parity, B=1024
    unconstrained) measure, so their methodology cannot diverge."""
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.rcv1 import Dataset
    from distributed_sgd_tpu.models.linear import SparseSVM
    from distributed_sgd_tpu.parallel.mesh import make_mesh
    from distributed_sgd_tpu.parallel.sync import SyncEngine

    counts = np.bincount(idx.ravel(), minlength=N_FEATURES)
    ds = np.zeros(N_FEATURES, dtype=np.float32)
    nz = counts > 0
    ds[nz] = 1.0 / (counts[nz] + 1.0)
    model = SparseSVM(lam=LAM, n_features=N_FEATURES, dim_sparsity=jnp.asarray(ds))
    mesh = make_mesh(1)  # one real chip; same code scales over the mesh
    engine = SyncEngine(
        model, mesh, batch_size=batch_size, learning_rate=LR,
        virtual_workers=N_WORKERS,
    )
    return engine.bind(
        Dataset(indices=idx, values=val, labels=y, n_features=N_FEATURES))


def _slope_epoch_seconds(bound, label: str = "") -> tuple:
    """Slope-fit epoch wall-clock: best-of-5 single-dispatch multi-epoch
    runs at 1 and 3 epochs, epoch_s = (t3 - t1) / 2 — the per-dispatch
    constant cancels — plus a 3-epoch convergence sanity eval outside the
    timed region."""
    import jax
    import jax.numpy as jnp

    w0 = jnp.zeros((N_FEATURES,), dtype=jnp.float32)
    key = jax.random.PRNGKey(0)

    times = {}
    for n_ep in (1, 3):
        t0 = time.perf_counter()
        np.asarray(bound.multi_epoch(w0, key, n_ep))  # compile + warm (pull)
        log(f"{label}compile+first run ({n_ep} epochs): "
            f"{time.perf_counter() - t0:.1f}s")
        best = float("inf")
        for _rep in range(5):
            t0 = time.perf_counter()
            np.asarray(bound.multi_epoch(w0, key, n_ep))
            best = min(best, time.perf_counter() - t0)
        times[n_ep] = best
        log(f"{label}best timed run ({n_ep} epochs): {best:.3f}s")
    epoch_s = (times[3] - times[1]) / 2.0

    w = bound.multi_epoch(w0, key, 3)
    loss, acc = bound.evaluate(w)
    log(f"{label}epoch={epoch_s:.4f}s; after 3 epochs: "
        f"loss={loss:.4f} acc={acc:.4f}")
    return epoch_s, float(loss), float(acc)


def tpu_epoch_seconds(idx, val, y) -> tuple:
    """Slope-fit sync epoch wall-clock on the TPU (3-worker topology)."""
    bound = _bind_flagship(idx, val, y, BATCH)
    log(f"steps per epoch: {bound.steps_per_epoch} "
        f"(= ceil(ceil({len(y)}/{N_WORKERS})/{BATCH}))")
    return _slope_epoch_seconds(bound)


B_UNCONSTRAINED = 1024  # best measured throughput config (BASELINE.md sweep)


def tpu_b1024_throughput(idx, val, y) -> dict:
    """Unconstrained operating point (VERDICT r4 item 5): the SAME epoch
    (same data, model, 3-worker topology, reference lr=0.5) at the
    framework's best per-dispatch batch, B=1024 — the 2.4x throughput
    lever the sweep table quantified (BASELINE.md: B=100->1024 at K=3 runs
    10.24x the work per step in 4.3x the time).  Batch size is a
    CONVERGENCE hyperparameter pinned at 100 by reference parity, so this
    is a documented superset config, benched end to end with the SAME
    binding + slope-fit helpers as the headline: epoch seconds and
    achieved TFLOP/s with the FLOP numerator from XLA's own cost model
    (compiled.cost_analysis(), which counts the lax.scan body once =
    per-step flops; no hand constants).
    """
    import jax
    import jax.numpy as jnp

    bound = _bind_flagship(idx, val, y, B_UNCONSTRAINED)
    steps = bound.steps_per_epoch
    epoch_s, loss, acc = _slope_epoch_seconds(bound, label="b1024 ")

    w0 = jnp.zeros((N_FEATURES,), dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    compiled = bound._epoch.lower(
        w0, bound._opt_state, bound.data.indices, bound.data.values,
        bound.data.labels, key,
    ).compile()
    flops_step = float((compiled.cost_analysis() or {}).get("flops", 0.0))
    tflops_per_s = flops_step * steps / epoch_s / 1e12 if epoch_s > 0 else 0.0
    log(f"b1024: {flops_step * steps / 1e12:.2f} TF/epoch over {steps} steps "
        f"-> {tflops_per_s:.1f} TF/s")
    return {"epoch_s": epoch_s, "steps": steps, "tflops_per_s": tflops_per_s,
            "loss3": loss, "acc3": acc}


def _expected_w_nnz(batches_done: int) -> float:
    """E[nnz(w)] after t batches: union of uniformly drawn feature ids
    (each batch touches N_WORKERS*BATCH*NNZ draws)."""
    draws = batches_done * N_WORKERS * BATCH * NNZ
    return N_FEATURES * (1.0 - math.exp(-draws / N_FEATURES))


def boxed_floor_epoch_seconds(idx, val, y, window_batches: int = 40) -> dict:
    """MEASURED boxed-map floor (benches/boxed_baseline.py) on a
    steady-state window of the full-scale epoch, extrapolated linearly.

    The window starts from w=0 and densifies within ~5 batches (each batch
    draws N_WORKERS*BATCH*NNZ ~ 23k of 47k features), so the early cheap
    batches make the extrapolation favor the floor."""
    from benches.boxed_baseline import boxed_epoch, rows_from_packed

    # the per-batch cost is sample-count-independent (fixed batch size),
    # so measure on a slice large enough to sample from
    n_slice = min(len(y), 60_000)
    rows = rows_from_packed(idx[:n_slice], val[:n_slice])
    ys = [int(v) for v in y[:n_slice]]
    counts = np.bincount(idx.ravel(), minlength=N_FEATURES)
    ds = {int(i): 1.0 / (c + 1.0) for i, c in enumerate(counts) if c > 0}

    _w, stats = boxed_epoch(
        rows, ys, N_WORKERS, BATCH, lr=LR, lam=LAM, ds=ds,
        max_batches=window_batches,
    )
    # extrapolate the measured window rate to the FULL epoch's step count
    per_batch = stats["wall_s"] / stats["batches_done"]
    epoch_s = per_batch * STEPS_PER_EPOCH
    log(
        f"boxed floor: {stats['wall_s']:.2f}s / {stats['batches_done']} batches "
        f"({per_batch*1e3:.1f} ms/batch) -> {epoch_s:.1f}s/epoch measured floor "
        f"({epoch_s / N_WORKERS:.1f}s if all worker compute were perfectly parallel)"
    )
    return {"total": epoch_s, "per_batch": per_batch,
            "workers_parallel_bound": epoch_s / N_WORKERS}


def baseline_epoch_seconds(idx, val, y, sample: int = 400) -> dict:
    """Model of one reference epoch (see module docstring)."""
    n = len(y)
    rows = [dict(zip(idx[i].tolist(), val[i].tolist())) for i in range(sample)]

    # 1. worker compute: per-sample boxed backward (Slave.scala:147-152)
    w: dict = {}
    t0 = time.perf_counter()
    for i in range(sample):
        x = rows[i]
        margin = 0.0
        for k_, v in x.items():  # sparse dot (Sparse.scala:15-46)
            margin += v * w.get(k_, 0.0)
        activity = y[i] * margin
        if activity >= 0:  # backward = y*x (SparseSVM.scala:26-29)
            yi = float(y[i])
            for k_, v in x.items():
                w[k_] = w.get(k_, 0.0) - LR * yi * v
    per_sample_py = (time.perf_counter() - t0) / sample
    compute_s = per_sample_py * n / JVM_SPEEDUP / N_WORKERS  # workers in parallel

    # 2. master reduce: mean of N_WORKERS sparse grads + update, per batch
    grad_nnz = int(N_FEATURES * (1.0 - math.exp(-BATCH * NNZ / N_FEATURES)))
    rng = np.random.default_rng(1)
    worker_grads = [
        dict(zip(rng.integers(0, N_FEATURES, grad_nnz).tolist(),
                 rng.random(grad_nnz).tolist()))
        for _ in range(N_WORKERS)
    ]
    t0 = time.perf_counter()
    acc: dict = {}
    for g in worker_grads:  # Vec.mean = fold of keyset-union merges
        acc = {k2: acc.get(k2, 0.0) + g.get(k2, 0.0) for k2 in acc.keys() | g.keys()}
    acc = {k2: v / N_WORKERS for k2, v in acc.items()}
    reduce_per_batch_py = time.perf_counter() - t0
    reduce_s = reduce_per_batch_py * STEPS_PER_EPOCH / JVM_SPEEDUP

    # 3. wire codecs: exact byte count at a generous throughput
    wire_bytes = 0.0
    for t in range(STEPS_PER_EPOCH):
        w_nnz = _expected_w_nnz(t)
        w_bytes = w_nnz * BYTES_PER_ENTRY
        g_bytes = grad_nnz * BYTES_PER_ENTRY
        # master encodes w per worker + each worker decodes it;
        # each worker encodes its reply + master decodes it
        wire_bytes += N_WORKERS * (2 * w_bytes + 2 * g_bytes)
    wire_s = wire_bytes / (WIRE_GBPS * 1e9)

    total = compute_s + reduce_s + wire_s
    log(
        f"baseline model: compute {compute_s:.2f}s (py {per_sample_py*1e6:.1f}us/sample / "
        f"{JVM_SPEEDUP:.0f} / {N_WORKERS} workers) + master-reduce {reduce_s:.2f}s "
        f"(py {reduce_per_batch_py*1e3:.2f}ms/batch / {JVM_SPEEDUP:.0f}) + "
        f"wire {wire_s:.2f}s ({wire_bytes/1e9:.2f} GB @ {WIRE_GBPS:.0f} GB/s) "
        f"= {total:.2f}s/epoch"
    )
    return {
        "total": total,
        "compute": compute_s,
        "reduce": reduce_s,
        "wire": wire_s,
    }


def _require_tpu() -> dict:
    """The device facts every result line carries; exits non-zero off the
    chip — a CPU timing must never appear under a device metric's name."""
    import jax

    devs = jax.devices()
    facts = {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
             "device_count": len(devs)}
    if facts["platform"] != "tpu":
        sys.exit(f"bench.py: the headline bench measures a TPU; jax found "
                 f"{json.dumps(facts)} — refusing to time it")
    return facts


def main() -> None:
    # one cache rule for every entry point (compile_cache.py): the
    # JAX_COMPILATION_CACHE_DIR environment variable, else
    # <checkout>/.jax_cache — placed before the first jit
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    if "--comms" in sys.argv:
        # wire-codec microbench (gradient compression PR): bytes +
        # encode/decode wall time per codec at dim=47,236 — its own stdout
        # JSON line, leaving the headline epoch bench contract untouched
        from benches import bench_comms

        bench_comms.main()
        return
    if "--rpc" in sys.argv:
        # pipelined sync-engine wire bench (docs/SYNC_PIPELINE.md):
        # broadcast bytes + rounds per epoch on a 2-worker loopback RPC
        # cluster, default vs DSGD_DELTA_BROADCAST=1 + DSGD_LOCAL_STEPS=4.
        # --smoke is the CI-sized fast mode: tiny corpus, asserts the
        # delta transport reconstructs the dense path's weights exactly
        from benches import bench_rpc_sync

        bench_rpc_sync.main(smoke="--smoke" in sys.argv)
        return
    if "--telemetry" in sys.argv:
        # cluster-telemetry gate (docs/OBSERVABILITY.md): the rpc sync
        # workload telemetry-off vs fully on (per-node registries, worker
        # health gauges, health monitor, endpoint polled at Prometheus
        # cadence); hard-asserts <5% overhead AND that the endpoint served
        # the per-worker series.  --smoke is the CI-sized mode.
        from benches import bench_telemetry

        bench_telemetry.main(smoke="--smoke" in sys.argv)
        return
    if "--trace-overhead" in sys.argv:
        # tracing-overhead gate (docs/OBSERVABILITY.md): the rpc sync
        # workload with the tracer unconfigured vs fully on (sample=1.0);
        # hard-asserts <5% overhead.  --smoke is the CI-sized mode.
        from benches import bench_trace

        bench_trace.main(smoke="--smoke" in sys.argv)
        return
    if "--elastic" in sys.argv:
        # elastic gate (docs/ELASTICITY.md): batch-drain apply throughput
        # (per-message vs inbox-drain on a real loopback master) + sparse
        # gossip topology convergence parity (all vs ring vs random:2,
        # in-process AND through the RPC plane with every elastic knob on).
        # --smoke is the CI-sized asserting mode.
        from benches import bench_elastic

        bench_elastic.main(smoke="--smoke" in sys.argv)
        return
    if "--hier" in sys.argv:
        # hierarchical multi-host gate (docs/HIERARCHY.md): knobs-off
        # identity, hierarchical-vs-flat loss parity at equal global
        # batch, and >= 2x per-round throughput over 1-device-per-worker
        # at equal device count on the 8-virtual-device harness.
        # --smoke is the CI-sized asserting mode.
        from benches import bench_hier

        bench_hier.main(smoke="--smoke" in sys.argv)
        return
    if "--spinup" in sys.argv:
        # elastic spin-up gate (ISSUE 13): subprocess cold/warm A/B of a
        # joining worker's time-to-first-contribution with the persistent
        # compile cache + AOT warmup (>= 2x warm-vs-cold hard assert),
        # spy-asserted O(delta) resplit re-loads through the row store,
        # and the knobs-off byte-identity / zero-cache-files proof.
        # --smoke is the CI-sized mode.
        from benches import bench_spinup

        bench_spinup.main(smoke="--smoke" in sys.argv)
        return
    if "--serve" in sys.argv:
        # serving-fleet SLO gate (docs/SERVING.md "serving fleet"): the
        # closed loop — DevCluster trains while a 3-replica fleet serves,
        # checkpoints stream in as weight deltas through the router's
        # canary gate — hard-asserting zero dropped requests and the p99
        # SLO under one replica kill + one canary rollback, plus the
        # delta-vs-full-reload wire savings.  --smoke is the CI-sized mode.
        from benches import bench_serve

        bench_serve.main(smoke="--smoke" in sys.argv)
        # serving-plane HA gate (docs/SERVING.md "HA"): two LIVE routers
        # peer-synced over SyncServeState front one replica fleet while a
        # 4x load ramp runs through a failover client and the DECIDER
        # router is killed mid-ramp — hard-asserting zero dropped
        # requests, the p99 SLO, no promoted-version split brain beyond
        # one sync interval, lease failover, post-failover promotion and
        # exactly one post-failover canary rollback.
        from benches import bench_serve_ha

        bench_serve_ha.main(smoke="--smoke" in sys.argv)
        return
    if "--scale" in sys.argv:
        # master-plane scaling gate (docs/SCALING.md): rounds/s vs worker
        # count N in {4..64} at fixed global batch, serialized knobs-off
        # master vs the O(N) plane (DSGD_STREAM + DSGD_FANIN_LANES +
        # DSGD_STAGE_POOL) — hard-asserts >= 1.5x at N=32 with weight
        # drift exactly 0.0 at every N.  --smoke is the CI-sized mode.
        from benches import bench_scale

        bench_scale.main(smoke="--smoke" in sys.argv)
        return
    if "--soak" in sys.argv:
        # sustained autoscale chaos soak (ROADMAP item 4): >= 24 workers
        # for minutes under seeded drop/delay/partition weather while a
        # join/leave schedule churns membership — gates zero live-worker
        # evictions, O(delta)-bounded reload rows, and convergence parity.
        # --smoke is the CI-sized mode.
        from benches import bench_soak

        bench_soak.main(smoke="--smoke" in sys.argv)
        return
    if "--flywheel" in sys.argv:
        # continual-learning flywheel gate (docs/CONTINUAL.md): train +
        # serve + live-probe-sourced drift detection + hands-free retrain
        # -> canary -> promote, with a distribution shift injected
        # mid-pump — hard-asserts recovery within the round budget, zero
        # dropped Predicts, zero operator actions, and a bounded process
        # leak slope.  --smoke is the CI-sized mode (runs the training
        # plane under a named chaos scenario besides).
        from benches import bench_flywheel

        bench_flywheel.main(smoke="--smoke" in sys.argv)
        return
    if "--chaos" in sys.argv:
        # chaos gate (docs/FAULT_TOLERANCE.md): sync training under the
        # canonical seeded fault plan, quorum on vs off — asserts
        # completion, zero live-worker evictions, convergence parity, and
        # >= 3x fewer soft-deadline-stalled rounds with DSGD_QUORUM=N-1.
        # --smoke is the deterministic CI-sized mode.
        from benches import bench_chaos

        bench_chaos.main(smoke="--smoke" in sys.argv)
        return
    device = _require_tpu()
    log(f"device: {json.dumps(device)}")
    log("generating RCV1-scale synthetic data...")
    t0 = time.perf_counter()
    idx, val, y = gen_data(N_SAMPLES)
    log(f"generated in {time.perf_counter()-t0:.1f}s")

    floor = boxed_floor_epoch_seconds(idx, val, y)
    model = baseline_epoch_seconds(idx, val, y)
    epoch_s, loss, acc = tpu_epoch_seconds(idx, val, y)
    b1024 = tpu_b1024_throughput(idx, val, y)

    # JVM-model views (all labeled as modeled): wire-speed sensitivity
    # range + a ratio with the modeled wire term dropped entirely
    model_wire10 = model["compute"] + model["reduce"] + model["wire"] / 10.0
    model_no_wire = model["compute"] + model["reduce"]

    result = {
        "metric": "rcv1_sync_epoch_seconds",
        "value": round(epoch_s, 4),
        "unit": "s",
        # headline: fully measured (boxed-map floor, this host) / measured TPU
        "vs_baseline": round(floor["total"] / epoch_s, 2),
        "baseline_kind": "measured_boxed_floor",
        "vs_boxed_floor_workers_parallel": round(
            floor["workers_parallel_bound"] / epoch_s, 2),
        "boxed_floor_epoch_seconds": round(floor["total"], 2),
        # secondary, MODELED views (JVM factor 10 + assumed wire speed)
        "vs_jvm_model_wire_1gbps": round(model["total"] / epoch_s, 2),
        "vs_jvm_model_wire_10gbps": round(model_wire10 / epoch_s, 2),
        "vs_jvm_model_compute_reduce_only": round(model_no_wire / epoch_s, 2),
        "jvm_model_breakdown_s": {k2: round(v, 2) for k2, v in model.items()},
        "final_loss": round(float(loss), 4),
        "final_acc": round(float(acc), 4),
        # unconstrained operating point (B=1024 superset config, same lr):
        # _seconds/_per_s suffixes gate these against their own history
        "b1024_epoch_seconds": round(b1024["epoch_s"], 4),
        "b1024_tflops_per_s": round(b1024["tflops_per_s"], 2),
        "b1024_vs_b100_epoch_speedup": round(epoch_s / b1024["epoch_s"], 2)
        if b1024["epoch_s"] > 0 else 0.0,
        "b1024_loss3_info": round(b1024["loss3"], 4),
        "n_samples": N_SAMPLES,
        "n_features": N_FEATURES,
        "batch_size": BATCH,
        "n_workers": N_WORKERS,
        "steps_per_epoch": STEPS_PER_EPOCH,
        **device,
    }
    # round-over-round regression gate (benches/regress.py, the ScalaMeter
    # RegressionReporter equivalent): compare against stored history BEFORE
    # printing, so the stdout JSON line itself carries the verdict in a
    # "regressed" field the driver's BENCH_r record preserves.  A clean run
    # is appended to history; a REGRESSED run is NOT (recording it would
    # drag the rolling median toward the regression — same policy as the
    # kernel gate in sparse_bench.py).  Per-metric detail goes to stderr;
    # the stdout contract stays ONE JSON line.  A gate that cannot run
    # (unreadable history, a bug in regress.py) fails the bench loudly
    # instead of passing as "nothing regressed".
    from benches import regress

    regressions, lines = regress.check(result, regress.load_history())
    result["regressed"] = regressions
    log(f"regression gate vs stored history, tolerance "
        f"{regress.DEFAULT_TOLERANCE:.0%}:")
    for ln in lines:
        log(ln)
    if regressions:
        log(f"FAIL: regressed metrics: {', '.join(regressions)} "
            f"(run NOT recorded)")
    else:
        regress.record(result)
        log("PASS: run appended to benches/history.json")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
