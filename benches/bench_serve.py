"""Serving-fleet SLO gate (docs/SERVING.md "serving fleet"; ROADMAP item 3).

The closed loop the fleet exists for, run end to end in one process:

- a 2-worker loopback DevCluster TRAINS (fit_sync, epoch-cadence
  checkpoints) while a 3-replica ServingFleet SERVES behind the router;
- every checkpoint streams into the fleet as a versioned weight update
  through the CheckpointDistributor -> router ``PushWeights`` path
  (sparse deltas after first contact — the wire-savings half of the
  gate), each version riding the router's canary gate;
- a sustained Predict load runs against the router while (1) one replica
  is KILLED mid-run (the health loop + breakers must drain it with zero
  dropped requests) and (2) one poisoned version is pushed (the canary
  probe must catch it and roll the canary back).

Hard asserts (both modes):

- **zero dropped requests**: every load-generator Predict is answered;
- **p99 <= SLO** over the whole timed window — kill and rollback
  included, which is the point;
- **exactly one rollback** and **at least one drained replica**;
- **delta distribution measurably cheaper on the wire** than N full-file
  reloads: router fan-out bytes vs the full-tensor-per-replica baseline
  (``serve.push.bytes`` / ``serve.push.bytes_full_equiv`` — the
  ``comms.*`` accounting pattern), ratio >= MIN_WIRE_SAVINGS.

Latency rows gate round-over-round through benches/regress.py under the
``*_p50_s`` / ``*_p99_s`` latency class (50% band); the wire row gates as
``*_bytes`` (10%).  Run: ``python bench.py --serve [--smoke]``.  Prints
exactly ONE JSON line on stdout; diagnostics go to stderr.
"""

from __future__ import annotations

import json
import sys
import tempfile
import threading
import time

import numpy as np

# corpus shape: FEW rows against a LARGE feature dimension, so one epoch
# of SGD touches well under the 50% delta break-even and checkpoint
# distribution genuinely rides the sparse form (640 rows x 8 nnz touch
# <= 5,120 of 16,384 coordinates)
FULL = dict(n=2560, n_features=47_236, nnz=16, batch=16, epochs=6, lr=0.5)
SMOKE = dict(n=640, n_features=16_384, nnz=8, batch=16, epochs=4, lr=0.5)
N_WORKERS = 2
N_REPLICAS = 3
N_CLIENTS = 4
PROBE_ROWS = 16
# ceil(0.34 * 3) = 2 canary replicas — and the router draws canaries from
# the ELIGIBLE set, so the mid-run replica kill cannot leave the canary
# gate pointing at a corpse (an unevaluable probe would defer promotion)
CANARY_FRACTION = 0.34
HEDGE_MS = 100.0
HEALTH_S = 0.25
# p99 bound over the whole timed window (kill + rollback included) on a
# GIL-shared CPU host that is TRAINING at the same time — generous vs the
# idle-fleet tail, hard vs a routing/batching break (an un-drained dead
# replica alone pushes p99 past the request deadline)
SLO_P99_S = dict(smoke=1.0, full=1.5)
MIN_WIRE_SAVINGS = 1.3  # full-reload-equivalent bytes / actual wire bytes


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _build(cfg: dict):
    # the canonical rpc workload builder (corpus shape, model, split):
    # imported, not copied, so the serve loop trains the same workload
    # the --rpc/--telemetry benches measure
    from benches.bench_rpc_sync import _build as build_rpc_workload

    return build_rpc_workload(cfg)


def run_bench(smoke: bool = False) -> dict:
    from distributed_sgd_tpu.core.cluster import DevCluster
    from distributed_sgd_tpu.rpc import dsgd_pb2 as pb
    from distributed_sgd_tpu.rpc.service import ServeStub, new_channel
    from distributed_sgd_tpu.serving.fleet import ServingFleet
    from distributed_sgd_tpu.serving.push import CheckpointDistributor, WeightPusher
    from distributed_sgd_tpu.serving.router import probe_from_dataset
    from distributed_sgd_tpu.utils import metrics as mm
    from distributed_sgd_tpu.utils.metrics import Metrics

    cfg = SMOKE if smoke else FULL
    label = "smoke" if smoke else "full"
    slo = SLO_P99_S[label]
    log(f"serve-fleet bench ({label}): n={cfg['n']} dim={cfg['n_features']} "
        f"nnz={cfg['nnz']} epochs={cfg['epochs']} workers={N_WORKERS} "
        f"replicas={N_REPLICAS} clients={N_CLIENTS} slo_p99={slo}s")
    train, test, make = _build(cfg)
    probe = probe_from_dataset(test, n=PROBE_ROWS)
    ckpt_dir = tempfile.mkdtemp(prefix="dsgd-serve-bench-")

    router_metrics = Metrics()
    push_metrics = Metrics()
    fleet = ServingFleet(
        ckpt_dir, n_replicas=N_REPLICAS, ckpt_poll_s=60.0,  # push-driven
        canary_fraction=CANARY_FRACTION, probe=probe,
        hedge_ms=HEDGE_MS, health_s=HEALTH_S, request_timeout_s=10.0,
        metrics=router_metrics,
    ).start()

    # -- the trainer half of the closed loop --------------------------------
    from distributed_sgd_tpu.checkpoint import Checkpointer

    cluster = DevCluster(make(), train, test, n_workers=N_WORKERS, seed=0)
    fit_done = threading.Event()

    def fit():
        try:
            ckpt = Checkpointer(ckpt_dir)
            cluster.master.fit_sync(
                max_epochs=cfg["epochs"], batch_size=cfg["batch"],
                learning_rate=cfg["lr"], checkpointer=ckpt,
                checkpoint_every=1)
            ckpt.close()
        finally:
            fit_done.set()

    fit_thread = threading.Thread(target=fit, name="bench-fit")
    fit_thread.start()
    distributor = CheckpointDistributor(
        ckpt_dir, [("127.0.0.1", fleet.router_port)], poll_s=0.25,
        metrics=push_metrics).start()

    channel = new_channel("127.0.0.1", fleet.router_port)
    stub = ServeStub(channel)
    deadline = time.time() + 120
    while time.time() < deadline:
        try:
            if stub.ServeHealth(pb.Empty(), timeout=2).ok:
                break
        except Exception:  # noqa: BLE001 - fleet still warming
            pass
        time.sleep(0.1)
    else:
        raise AssertionError("fleet never became ready (no version promoted)")
    log("fleet ready: first version promoted; warming jit buckets")

    rng = np.random.default_rng(11)

    def one_request(r, client_stub):
        nnz = int(r.integers(1, 6))
        idx = r.choice(cfg["n_features"], size=nnz, replace=False).astype(np.int32)
        val = r.normal(size=nnz).astype(np.float32)
        t0 = time.perf_counter()
        client_stub.Predict(pb.PredictRequest(indices=idx, values=val),
                            timeout=10)
        return time.perf_counter() - t0

    for _ in range(24):  # warmup: compile every replica's probe/pad buckets
        one_request(rng, stub)

    # -- sustained load, with one kill and one rollback mid-window ----------
    latencies: list = []
    dropped: list = []
    stop = threading.Event()

    def client(k):
        r = np.random.default_rng(100 + k)
        ch = new_channel("127.0.0.1", fleet.router_port)
        s = ServeStub(ch)
        while not stop.is_set():
            try:
                latencies.append(one_request(r, s))
            except Exception as e:  # noqa: BLE001 - the zero-drop assert
                dropped.append(repr(e))
        ch.close()

    clients = [threading.Thread(target=client, args=(k,), name=f"load-{k}")
               for k in range(N_CLIENTS)]
    t_load = time.perf_counter()
    for t in clients:
        t.start()

    time.sleep(1.0)
    fleet.kill_replica(0)
    log("replica 0 killed mid-load")
    deadline = time.time() + 30
    while (time.time() < deadline
           and router_metrics.counter(mm.ROUTER_DRAINED).value == 0):
        time.sleep(0.05)

    # one poisoned version straight at the router's canary gate (version
    # far above the trainer's epoch numbering so the streams never
    # collide).  The poison is deterministically WRONG on the probe set —
    # an anti-fit whose margins carry each probe row's own label sign, so
    # hinge predicts the opposite label on every row (loss -> 2.0) and
    # the rollback assert cannot depend on random-weights luck.
    poison = WeightPusher([("127.0.0.1", fleet.router_port)],
                          metrics=Metrics())
    bad_w = np.zeros(cfg["n_features"], np.float32)
    for p_idx, p_val, p_y in probe:
        bad_w[p_idx] += 100.0 * p_y * p_val
    acked = poison.push(100_000, bad_w)
    poison.close()
    log(f"poison push acked={acked} (0 = NACKed at the canary gate)")

    fit_done.wait(timeout=600)
    distributor.stop()  # final sweep ships the terminal checkpoint
    time.sleep(0.5)  # tail of load against the final promoted version
    stop.set()
    for t in clients:
        t.join()
    load_wall = time.perf_counter() - t_load

    lat = np.asarray(latencies)
    p50 = float(np.percentile(lat, 50))
    p99 = float(np.percentile(lat, 99))
    qps = len(lat) / load_wall
    wire = router_metrics.counter(mm.SERVE_PUSH_BYTES).value
    full_equiv = router_metrics.counter(mm.SERVE_PUSH_FULL_EQUIV).value
    savings = full_equiv / wire if wire else float("inf")
    rollbacks = router_metrics.counter(mm.ROUTER_CANARY_ROLLBACK).value
    promoted = router_metrics.counter(mm.ROUTER_CANARY_PROMOTED).value
    drained = router_metrics.counter(mm.ROUTER_DRAINED).value
    retries = router_metrics.counter(mm.ROUTER_RETRIES).value
    hedges = router_metrics.counter(mm.ROUTER_HEDGES).value

    log(f"{len(lat)} requests in {load_wall:.1f}s ({qps:.0f}/s): "
        f"p50 {p50 * 1e3:.1f}ms p99 {p99 * 1e3:.1f}ms (SLO {slo}s); "
        f"dropped={len(dropped)} retries={retries} hedges={hedges} "
        f"drained={drained}")
    log(f"distribution: {promoted} promoted / {rollbacks} rolled back; "
        f"router fan-out {wire} B vs {full_equiv} B full-reload equiv "
        f"= {savings:.2f}x savings (bar {MIN_WIRE_SAVINGS}x); trainer->"
        f"router {push_metrics.counter(mm.SERVE_PUSH_BYTES).value} B")

    cluster.stop()
    fleet.stop()
    channel.close()

    # -- the gate ------------------------------------------------------------
    assert not dropped, (
        f"{len(dropped)} dropped requests under kill+rollback: {dropped[:3]}")
    assert p99 <= slo, (
        f"p99 {p99:.3f}s over the {slo}s SLO under one replica kill + one "
        f"canary rollback")
    assert rollbacks == 1, (
        f"expected exactly the one poisoned version rolled back, got "
        f"{rollbacks}")
    assert promoted >= 2, (
        f"the trainer's checkpoint stream promoted only {promoted} "
        f"version(s) — the closed loop did not close")
    assert drained >= 1, "the killed replica was never drained"
    assert savings >= MIN_WIRE_SAVINGS, (
        f"delta distribution saved only {savings:.2f}x vs N full reloads "
        f"(bar {MIN_WIRE_SAVINGS}x)")

    return {
        "metric": f"serve_fleet_{label}",
        "unit": "s",
        "predict_p50_s": round(p50, 5),
        "predict_p99_s": round(p99, 5),
        "push_wire_bytes": int(wire),
        "push_full_equiv_bytes_info": int(full_equiv),
        "push_savings_ratio_info": round(savings, 2),
        "qps_info": round(qps, 1),
        "requests_info": len(lat),
        "dropped_info": len(dropped),
        "promoted_info": int(promoted),
        "rollbacks_info": int(rollbacks),
        "drained_info": int(drained),
        "hedges_info": int(hedges),
        "slo_p99_s_info": slo,
        "n_replicas": N_REPLICAS,
        "n_workers": N_WORKERS,
        **{k: v for k, v in cfg.items()},
    }


def main(smoke: bool = False) -> None:
    result = run_bench(smoke=smoke)
    # round-over-round recording (benches/regress.py): same policy as
    # bench.py — a clean run is appended to history
    try:
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
    except Exception as e:  # noqa: BLE001 - gating must not break the bench
        log(f"regression gate skipped: {e}")
        result["regressed"] = None
        result["gate_error"] = str(e)
    print(json.dumps(result))


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main(smoke="--smoke" in sys.argv)
