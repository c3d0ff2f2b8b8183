"""Serving-plane HA scenario gate (docs/SERVING.md "HA"; serving/ha.py).

The dual-LIVE-router protocol run end to end against real load:

- a 2-worker loopback DevCluster TRAINS while TWO ServingRouters — both
  LIVE, peer-synced over ``SyncServeState``, one holding the decider
  lease — front the same 2-replica fleet; the CheckpointDistributor
  streams every checkpoint to BOTH routers (the non-decider defers and
  mirrors the verdict within one sync interval);
- a Predict load ramps 4x (1 -> 4 client threads) through a
  ``FailoverServeClient``, while a split-brain probe samples BOTH
  routers' promoted version every 50ms and measures every disagreement
  window;
- mid-ramp the DECIDER router is KILLED: clients fail over, the survivor
  assumes the lease, the distributor re-targets, and subsequent
  checkpoints must promote on the survivor;
- after the failover one poisoned version is pushed at the survivor (the
  canary gate must roll it back), and a ``ReplicaAutoscaler`` rides the
  survivor's load signal through the ramp (its actions are recorded, not
  hard-asserted — scaling timing is host weather).

Hard asserts (both modes):

- **zero dropped requests** through the ramp AND the decider kill;
- **p99 <= SLO** over the whole timed window, kill included;
- **no split brain**: the longest promoted-version disagreement window
  between the two LIVE routers stays within one sync interval;
- **the survivor decides**: >= 1 lease failover, >= 1 version promoted
  AFTER the kill, and exactly one post-failover rollback.

Latency rows ride the ``serve_ha`` regression class (benches/regress.py):
reported round-over-round but never gated — the SLO assert above is the
latency gate, and timing noise must not block recording the
DETERMINISTIC drop/split-brain/failover counters this series exists for.
Run: ``python bench.py --serve [--smoke]`` (after the fleet scenario), or
``python benches/bench_serve_ha.py [--smoke]``.  Prints exactly ONE JSON
line on stdout; diagnostics go to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

# runnable directly (python benches/bench_serve_ha.py) as well as via -m
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FULL = dict(n=2560, n_features=47_236, nnz=16, batch=16, epochs=6, lr=0.5)
SMOKE = dict(n=640, n_features=16_384, nnz=8, batch=16, epochs=4, lr=0.5)
N_WORKERS = 2
N_REPLICAS = 2
N_CLIENTS = 4  # the ramp's ceiling: 1 -> 4 is the 4x load ramp
PROBE_ROWS = 16
CANARY_FRACTION = 0.5  # ceil(0.5 * 2) = 1 canary replica
HEALTH_S = 0.25
SYNC_S = 0.25      # HA sync interval — the split-brain bound under test
LEASE_TTL_S = 1.0  # 4x sync: three missed exchanges age the decider out
SLO_P99_S = dict(smoke=1.0, full=1.5)
# the autoscaler rides the ramp with a LOW breach bar so a spin-up
# genuinely exercises the warm add_replica path under load; its verdicts
# are host weather, so they record as *_info instead of hard-asserting
SCALE_SLO_MS = 15.0
SCALE_MAX = 4
GOOD_VERSION = 50_000    # benign post-failover respin: must PROMOTE
POISON_VERSION = 100_000  # poisoned post-failover push: must ROLL BACK


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_bench(smoke: bool = False) -> dict:
    from distributed_sgd_tpu.checkpoint import Checkpointer
    from distributed_sgd_tpu.core.cluster import DevCluster
    from distributed_sgd_tpu.rpc import dsgd_pb2 as pb
    from distributed_sgd_tpu.rpc.service import ServeStub, new_channel
    from distributed_sgd_tpu.serving.ha import (
        FailoverServeClient,
        HACoordinator,
        ReplicaAutoscaler,
        router_load_ms,
    )
    from distributed_sgd_tpu.serving.push import CheckpointDistributor, WeightPusher
    from distributed_sgd_tpu.serving.router import ServingRouter, probe_from_dataset
    from distributed_sgd_tpu.serving.server import ServingServer
    from distributed_sgd_tpu.utils import metrics as mm
    from distributed_sgd_tpu.utils.metrics import Metrics

    from benches.bench_rpc_sync import _build as build_rpc_workload

    cfg = SMOKE if smoke else FULL
    label = "smoke" if smoke else "full"
    slo = SLO_P99_S[label]
    log(f"serve-HA bench ({label}): n={cfg['n']} dim={cfg['n_features']} "
        f"epochs={cfg['epochs']} replicas={N_REPLICAS} routers=2 "
        f"ramp=1->{N_CLIENTS} sync={SYNC_S}s ttl={LEASE_TTL_S}s "
        f"slo_p99={slo}s")
    train, test, make = build_rpc_workload(cfg)
    probe = probe_from_dataset(test, n=PROBE_ROWS)
    ckpt_dir = tempfile.mkdtemp(prefix="dsgd-serve-ha-bench-")

    # -- the shared replica fleet + two LIVE routers -------------------------
    replicas = [
        ServingServer(ckpt_dir, port=0, host="127.0.0.1", ckpt_poll_s=60.0,
                      metrics=Metrics()).start()
        for _ in range(N_REPLICAS)
    ]
    endpoints = [("127.0.0.1", r.bound_port) for r in replicas]

    def mk_router(metrics):
        return ServingRouter(
            endpoints, port=0, host="127.0.0.1",
            canary_fraction=CANARY_FRACTION, probe=probe,
            health_s=HEALTH_S, request_timeout_s=10.0, metrics=metrics,
        ).start()

    m_a, m_b = Metrics(), Metrics()
    router_a, router_b = mk_router(m_a), mk_router(m_b)
    coord_a = HACoordinator([f"127.0.0.1:{router_b.bound_port}"],
                            sync_s=SYNC_S, lease_ttl_s=LEASE_TTL_S)
    coord_b = HACoordinator([f"127.0.0.1:{router_a.bound_port}"],
                            sync_s=SYNC_S, lease_ttl_s=LEASE_TTL_S)
    router_a.attach_ha(coord_a)
    router_b.attach_ha(coord_b)
    coord_a.start()
    coord_b.start()
    # the peer lease is rank-deterministic (lowest endpoint decides): name
    # the decider now so the kill below aims at the right router
    if coord_a.is_decider():
        decider, survivor = router_a, router_b
        survivor_metrics, survivor_coord = m_b, coord_b
    else:
        decider, survivor = router_b, router_a
        survivor_metrics, survivor_coord = m_a, coord_a
    assert coord_a.is_decider() != coord_b.is_decider(), \
        "exactly one router must hold the decider lease at boot"
    log(f"routers live: decider :{decider.bound_port}, "
        f"mirror :{survivor.bound_port}")

    # autoscale rides the SURVIVOR's load signal (it outlives the kill);
    # a spin-up joins the new replica to BOTH live routers
    scale_lock = threading.Lock()

    def scale_up():
        with scale_lock:
            r = ServingServer(ckpt_dir, port=0, host="127.0.0.1",
                              ckpt_poll_s=60.0, metrics=Metrics()).start()
            replicas.append(r)
            for router in (router_a, router_b):
                try:
                    router.add_replica("127.0.0.1", r.bound_port)
                except Exception:  # noqa: BLE001 - the killed router
                    pass

    def scale_down():
        with scale_lock:
            if len(replicas) <= N_REPLICAS:
                return
            r = replicas.pop()
            for router in (router_a, router_b):
                try:
                    router.remove_replica(f"127.0.0.1:{r.bound_port}")
                except Exception:  # noqa: BLE001 - the killed router
                    pass
            r.stop()

    autoscaler = ReplicaAutoscaler(
        signal_ms=lambda: router_load_ms(survivor),
        scale_up=scale_up, scale_down=scale_down,
        count=lambda: len(replicas), slo_ms=SCALE_SLO_MS,
        min_replicas=N_REPLICAS, max_replicas=SCALE_MAX,
        interval_s=0.25, cooldown_s=3.0, metrics=survivor_metrics,
    ).start()

    # -- the trainer half: checkpoints stream to BOTH routers ----------------
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
    push_metrics = Metrics()
    distributor = CheckpointDistributor(
        ckpt_dir,
        [("127.0.0.1", router_a.bound_port),
         ("127.0.0.1", router_b.bound_port)],
        poll_s=0.25, metrics=push_metrics).start()

    client = FailoverServeClient(
        [("127.0.0.1", decider.bound_port),
         ("127.0.0.1", survivor.bound_port)], timeout_s=10.0)
    deadline = time.time() + 120
    while time.time() < deadline:
        try:
            if client.health().ok and decider.promoted_version is not None:
                break
        except Exception:  # noqa: BLE001 - fleet still warming
            pass
        time.sleep(0.1)
    else:
        raise AssertionError("HA fleet never became ready (no promotion)")
    log("fleet ready: first version promoted on the decider")

    rng = np.random.default_rng(11)

    def one_request(r, c):
        nnz = int(r.integers(1, 6))
        idx = r.choice(cfg["n_features"], size=nnz,
                       replace=False).astype(np.int32)
        val = r.normal(size=nnz).astype(np.float32)
        t0 = time.perf_counter()
        c.predict(idx, val)
        return time.perf_counter() - t0

    for _ in range(24):  # warmup: compile the replicas' pad buckets
        one_request(rng, client)

    # -- split-brain probe: both routers' promoted version @ 20 Hz -----------
    split_windows: list = []
    probe_stop = threading.Event()

    def split_probe():
        chans = {r: new_channel("127.0.0.1", r.bound_port)
                 for r in (router_a, router_b)}
        stubs = {r: ServeStub(ch) for r, ch in chans.items()}
        open_at = None
        while not probe_stop.is_set():
            steps = []
            for r in (router_a, router_b):
                try:
                    steps.append(stubs[r].ServeHealth(
                        pb.Empty(), timeout=1.0).model_step)
                except Exception:  # noqa: BLE001 - the killed router
                    pass
            now = time.perf_counter()
            # disagreement exists only while BOTH routers answer: a dead
            # router is a failover, not a split brain
            if len(steps) == 2 and steps[0] != steps[1]:
                if open_at is None:
                    open_at = now
            elif open_at is not None:
                split_windows.append(now - open_at)
                open_at = None
            time.sleep(0.05)
        if open_at is not None:
            split_windows.append(time.perf_counter() - open_at)
        for ch in chans.values():
            ch.close()

    probe_thread = threading.Thread(target=split_probe, name="split-probe")
    probe_thread.start()

    # -- the 4x load ramp, with the decider killed mid-ramp ------------------
    latencies: list = []
    dropped: list = []
    stop = threading.Event()

    def load(k):
        r = np.random.default_rng(100 + k)
        c = FailoverServeClient(
            [("127.0.0.1", decider.bound_port),
             ("127.0.0.1", survivor.bound_port)], timeout_s=10.0)
        while not stop.is_set():
            try:
                latencies.append(one_request(r, c))
            except Exception as e:  # noqa: BLE001 - the zero-drop assert
                dropped.append(repr(e))
        client_failovers.append(c.failovers)
        c.close()

    client_failovers: list = []
    threads = [threading.Thread(target=load, args=(k,), name=f"load-{k}")
               for k in range(N_CLIENTS)]
    t_load = time.perf_counter()
    threads[0].start()
    time.sleep(0.75)
    threads[1].start()          # 2x
    time.sleep(0.75)
    for t in threads[2:]:       # 4x
        t.start()

    time.sleep(0.5)
    promoted_at_kill = survivor.promoted_version or 0
    log(f"killing the DECIDER router :{decider.bound_port} mid-ramp "
        f"(survivor mirrors v{promoted_at_kill})")
    decider.stop()
    t_kill = time.time()

    # survivor must assume the lease within ~one TTL (before re-targeting:
    # retarget waits out any in-flight push retry to the dead router, which
    # would pollute this measurement)
    deadline = time.time() + 30
    while time.time() < deadline and not survivor_coord.is_decider():
        time.sleep(0.05)
    failover_wait = time.time() - t_kill
    assert survivor_coord.is_decider(), \
        "survivor never assumed the decider lease"
    log(f"survivor assumed the decider lease after {failover_wait:.2f}s")
    # the distributor re-targets its push stream to the surviving router
    distributor.retarget([("127.0.0.1", survivor.bound_port)])

    fit_done.wait(timeout=600)
    distributor.stop()  # final sweep ships the terminal checkpoint
    # post-failover PROMOTE at the survivor: a benign respin of the weights
    # it mirrored must clear its canary gate now that it decides alone —
    # driven explicitly so smoke-sized training (which may already have
    # finished, or whose terminal checkpoint may legitimately regress the
    # probe) cannot make the verdict timing-dependent
    deadline = time.time() + 10
    while time.time() < deadline and survivor._w_promoted is None:
        time.sleep(0.05)
    assert survivor._w_promoted is not None, \
        "survivor never pinned the promoted weights it mirrored"
    good_w = survivor._w_promoted.copy()
    good_w[0] *= 1.001
    pusher = WeightPusher([("127.0.0.1", survivor.bound_port)],
                          metrics=Metrics())
    acked_good = pusher.push(GOOD_VERSION, good_w)
    rollbacks_before_poison = survivor_metrics.counter(
        mm.ROUTER_CANARY_ROLLBACK).value
    # then poison straight at the SURVIVOR's canary gate: margins carry
    # each probe row's own label sign -> loss ~2.0, deterministic rollback
    bad_w = np.zeros(cfg["n_features"], np.float32)
    for p_idx, p_val, p_y in probe:
        bad_w[p_idx] += 100.0 * p_y * p_val
    acked_bad = pusher.push(POISON_VERSION, bad_w)
    pusher.close()
    log(f"post-failover pushes at the survivor: benign v{GOOD_VERSION} "
        f"acked={acked_good}, poison v{POISON_VERSION} acked={acked_bad} "
        f"(0 = NACKed)")

    time.sleep(0.5)  # tail of load against the survivor's final version
    stop.set()
    for t in threads:
        t.join()
    load_wall = time.perf_counter() - t_load
    probe_stop.set()
    probe_thread.join()
    autoscaler.stop()

    lat = np.asarray(latencies)
    p50 = float(np.percentile(lat, 50))
    p99 = float(np.percentile(lat, 99))
    qps = len(lat) / load_wall
    split_max = max(split_windows) if split_windows else 0.0
    failovers = survivor_metrics.counter(mm.ROUTER_HA_FAILOVERS).value
    syncs = survivor_metrics.counter(mm.ROUTER_HA_SYNCS).value
    applied = survivor_metrics.counter(mm.ROUTER_HA_APPLIED).value
    rollbacks = (survivor_metrics.counter(mm.ROUTER_CANARY_ROLLBACK).value
                 - rollbacks_before_poison)
    scale_ups = survivor_metrics.counter(mm.ROUTER_SCALE_UP).value
    promoted_after = (survivor.promoted_version or 0)
    n_final = len(replicas)

    log(f"{len(lat)} requests in {load_wall:.1f}s ({qps:.0f}/s): "
        f"p50 {p50 * 1e3:.1f}ms p99 {p99 * 1e3:.1f}ms (SLO {slo}s); "
        f"dropped={len(dropped)} client_failovers={sum(client_failovers)}")
    log(f"HA: syncs={syncs} applied={applied} failovers={failovers} "
        f"split_max={split_max * 1e3:.0f}ms (bound {SYNC_S * 1e3:.0f}ms); "
        f"promoted v{promoted_at_kill} at kill -> v{promoted_after} final; "
        f"rollbacks={rollbacks}; autoscale ups={scale_ups} "
        f"fleet {N_REPLICAS}->{n_final}")

    cluster.stop()
    client.close()
    survivor.stop()
    for r in replicas:
        try:
            r.stop()
        except Exception:  # noqa: BLE001 - drained replicas stop twice
            pass

    # -- the gate ------------------------------------------------------------
    assert not dropped, (
        f"{len(dropped)} dropped requests through the ramp + decider "
        f"kill: {dropped[:3]}")
    assert p99 <= slo, (
        f"p99 {p99:.3f}s over the {slo}s SLO through a 4x ramp + decider "
        f"kill")
    assert split_max <= SYNC_S, (
        f"split brain: routers disagreed on the promoted version for "
        f"{split_max:.3f}s (> one {SYNC_S}s sync interval)")
    assert failovers >= 1, "the survivor never assumed the decider lease"
    assert promoted_after == GOOD_VERSION, (
        f"post-failover benign push did not end up promoted on the "
        f"survivor (v{promoted_at_kill} at kill -> v{promoted_after} "
        f"final, wanted v{GOOD_VERSION}): the survivor is not deciding, "
        f"or the poison rollback re-pinned the wrong version")
    assert rollbacks == 1, (
        f"expected exactly the one post-failover poison rolled back, got "
        f"{rollbacks}")
    assert N_REPLICAS <= n_final <= SCALE_MAX, (
        f"autoscaler left the fleet at {n_final} replicas, outside "
        f"[{N_REPLICAS}, {SCALE_MAX}]")

    return {
        "metric": f"serve_ha_{label}",
        "unit": "s",
        "predict_p50_s": round(p50, 5),
        "predict_p99_s": round(p99, 5),
        "dropped_info": len(dropped),
        "split_brain_max_s_info": round(split_max, 4),
        "sync_interval_s_info": SYNC_S,
        "failovers_info": int(failovers),
        "client_failovers_info": int(sum(client_failovers)),
        "failover_wait_s_info": round(failover_wait, 3),
        "promoted_at_kill_info": int(promoted_at_kill),
        "promoted_final_info": int(promoted_after),
        "rollbacks_info": int(rollbacks),
        "syncs_info": int(syncs),
        "applied_info": int(applied),
        "scale_ups_info": int(scale_ups),
        "replicas_final_info": n_final,
        "qps_info": round(qps, 1),
        "requests_info": len(lat),
        "slo_p99_s_info": slo,
        "n_replicas": N_REPLICAS,
        "n_workers": N_WORKERS,
        **{k: v for k, v in cfg.items()},
    }


def main(smoke: bool = False) -> None:
    result = run_bench(smoke=smoke)
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
