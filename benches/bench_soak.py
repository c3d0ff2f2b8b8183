"""Sustained autoscale chaos soak at O(N) workers (ROADMAP item 4,
docs/SCALING.md "Soak methodology").

The residue item 4 carried since PR 6: every churn proof so far stopped at
3-4 workers and ONE leave/join cycle.  This bench drives a production-ish
cluster — >= 24 loopback workers in full mode, minutes of wall clock —
through a seeded chaos plan (drop + delay + dup weather, timed partitions)
WHILE a join/leave schedule churns membership, with the whole O(N) master
plane on (DSGD_STREAM + DSGD_FANIN_LANES + DSGD_STAGE_POOL), quorum
barriers riding the weather, and host-local workers re-sharding their
resident slices incrementally (DSGD_HOST_OVERPROVISION, the PR 11
O(delta) machinery) at every resplit.

Hard gates (smoke and full):

- the fit COMPLETES every epoch and every scheduled churn event executed
  mid-fit (a soak whose churn missed the fit proved nothing);
- ZERO live-worker evictions (`master.evictions` delta == 0): graceful
  leaves are scale-downs, stragglers are slow not dead, and the heartbeat
  budget is sized past the longest partition window;
- reload bytes bounded by the O(delta) contract: total re-read rows stay
  under the split-arithmetic delta bound (simulated per transition from
  the same `overprovisioned_slice` the workers use, x1.5 slack for the
  resident-budget trim) AND strictly under one full-corpus reload per
  transition — churn must never degenerate to re-materializing the corpus;
- convergence parity: the soak's final loss stays inside the
  COMPRESSION.md gate (<= max(1.02 * base, base + 0.02)) of a clear-
  weather, churn-free, knobs-off baseline at the same shape.

Eviction-budget sizing (the knob table in docs/SCALING.md): the longest
partition black-holes one worker's heartbeat probes for its whole window,
so `heartbeat_s * heartbeat_max_misses` MUST exceed the longest partition
(+ one probe period of slack) or the soak's own weather evicts a live
worker.  Quorum is N-2 with hedging ON: a hedge ships a straggler's
sample ids to a donor whose host-local resident slice does not cover
them, and the donor serves it from a bounded TRANSIENT scratch read
through its RowReader (core/worker.py compute_gradient_hedged) — its
resident window never slides for someone else's rows, so the O(delta)
reload accounting this soak gates stays clean (the old hedge=False ban
existed because hedges used to route through ensure_rows; see
docs/HIERARCHY.md and docs/AGGREGATION.md).

Long-horizon mode (ISSUE 20, telemetry/resources.py): the soak is also
the leak proof.  A real ``ResourceProbe`` thread samples the process
across the whole chaos run while a ``LeakSentinel`` with CALIBRATED
absolute slope bars (rss bytes/s, fds/s, threads/s — the bench_flywheel
PR 16 calibration) watches the series; ``run_bench`` hard-asserts the
sentinel never tripped, ``main`` (the ``bench.py --soak`` path) that the
final Theil–Sen slopes sit under the bars; the bench then measures probe
overhead on the canonical ``--rpc`` workload (interleaved base/probe-on,
per-config minimum, the bench_telemetry pattern) against a <5% bar.
``soak_rss_slope`` / ``soak_fd_slope``
rows land in benches/history.json so the trend across rounds is
watchable even while each run's absolute bar passes.

Run: ``python bench.py --soak [--smoke]``.  One JSON line on stdout;
diagnostics to stderr; rows append to benches/history.json under the
``soak_*`` series (loss fields carry their own in-run parity gate — the
regress 2% loss band exempts chaos/soak series, whose losses depend on
which replies beat a wall-clock deadline).
"""

from __future__ import annotations

import json
import sys
import threading
import time

import numpy as np

LANES = 4
POOL = 4
PARITY_REL = 1.02
PARITY_ABS = 0.02
DELTA_SLACK = 1.5

# -- long-horizon leak gate (ISSUE 20) ----------------------------------------
# Absolute slope bars fed to the LeakSentinel and re-asserted on the
# final Theil–Sen fit.  RSS bars reuse the PR 16 bench_flywheel
# calibration (smoke windows are shorter, so allocator warmup reads
# steeper): 8 MB/s smoke / 4 MB/s full.  fds/threads churn with the
# join/leave schedule by design — the bars bound a monotone LEAK, not
# the sawtooth (Theil–Sen's pairwise median flattens the sawtooth).
MAX_RSS_SLOPE = dict(smoke=8e6, full=4e6)   # bytes/s
MAX_FD_SLOPE = 2.0                          # fds/s
MAX_THREAD_SLOPE = 2.0                      # threads/s
PROBE_S = dict(smoke=0.25, full=0.5)        # soak sampling cadence
MIN_HORIZON_S = dict(smoke=5.0, full=10.0)  # sentinel horizon guard
# probe-overhead gate on the canonical --rpc workload (the
# bench_telemetry shapes + pattern): interleave base/probe-on, keep the
# per-config MINIMUM, hard-assert < 5%.  The overhead probe ticks FAST
# (0.1 s) so the bar is measured at 100x the production default cadence.
OVERHEAD_SMOKE = dict(n=640, n_features=4096, nnz=8, batch=16, epochs=2,
                      lr=0.5)
OVERHEAD_FULL = dict(n=2560, n_features=16384, nnz=32, batch=16, epochs=4,
                     lr=0.5)
OVERHEAD_REPS = dict(smoke=1, full=2)
OVERHEAD_PROBE_S = 0.1
MAX_PROBE_OVERHEAD = 0.05

# weather comes from the NAMED scenario library (chaos/__init__.py
# SCENARIOS; DSGD_CHAOS=scenario:NAME) so this bench, a bug report, and
# a CI job mean the same seeded faults when they say "asym-partition"
SMOKE = dict(
    workers=6, n=960, n_features=1024, nnz=8, batch=4, epochs=7, lr=0.5,
    overprovision=0.2,
    chaos="scenario:asym-partition",  # w1/w2 1.5s partitions + noise
    quorum_slack=2, soft_s=0.3, grad_timeout_s=1.0,
    heartbeat_s=0.5, heartbeat_max_misses=8,  # 8 * ~0.5s >> 1.5s partition
    # (t_seconds, action): tail worker leaves gracefully, then a fresh
    # host-local worker joins the freed slot mid-fit
    churn=((5.0, "leave"), (11.0, "join")),
)
FULL = dict(
    workers=24, n=4800, n_features=2048, nnz=8, batch=4, epochs=24, lr=0.5,
    overprovision=0.2,
    chaos="scenario:thundering-rejoin",  # w1+w2+w3 vanish together 2s@3s
    quorum_slack=2, soft_s=0.4, grad_timeout_s=1.5,
    heartbeat_s=1.0, heartbeat_max_misses=10,  # ~10s+ budget > 2s partition
    churn=((20.0, "leave"), (40.0, "join"), (65.0, "leave"), (85.0, "join"),
           (110.0, "leave"), (130.0, "join")),
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _build(cfg: dict):
    from distributed_sgd_tpu.data.rcv1 import dim_sparsity, train_test_split
    from distributed_sgd_tpu.data.synthetic import rcv1_like

    data = rcv1_like(cfg["n"], n_features=cfg["n_features"], nnz=cfg["nnz"],
                     seed=11, idf_values=True)
    train, test = train_test_split(data)
    ds = dim_sparsity(train)

    def make():
        from distributed_sgd_tpu.models.linear import make_model

        return make_model("hinge", 1e-5, train.n_features, dim_sparsity=ds)

    return train, test, make


def _prewarm(cluster, train, batch: int) -> None:
    zeros = np.zeros(train.n_features, dtype=np.float32)
    warm_ids = np.arange(batch, dtype=np.int64)
    for w in cluster.workers:
        # host-local workers refuse foreign ids: warm each on ids inside
        # its own resident slice (offset-mapped), sized like a window
        lo = getattr(w, "_data_offset", None)
        ids = warm_ids + (lo if isinstance(lo, int) else 0)
        try:
            w.compute_gradient(zeros, np.asarray(ids, np.int64))
        except Exception:  # noqa: BLE001 - warmup is best effort
            pass
    cluster.master.local_loss(zeros)


def _expected_delta_bound(f: float, counts, train_rows: int):
    """Split-arithmetic upper bound on the rows the PR 11 O(delta)
    machinery may re-read across the churn `counts` sequence (the SAME
    `overprovisioned_slice` the workers resolve their targets from).

    Tail churn keeps every survivor's position, so transition c -> c' re-
    targets position i from slice(i, c) to slice(i, c'): the uncovered
    delta is the new load range minus its overlap with the previous
    target (the resident set covers at least the previous target up to
    budget trims — the x1.5 slack in the caller absorbs those).  A joiner
    starts empty and loads its whole target."""
    from distributed_sgd_tpu.data.host_shard import overprovisioned_slice

    resident = {}
    for i in range(counts[0]):
        lo, hi, _s, _e = overprovisioned_slice(train_rows, i, counts[0],
                                               overprovision=f)
        resident[i] = (lo, hi)
    total = 0
    for prev_c, new_c in zip(counts, counts[1:]):
        for i in range(new_c):
            lo, hi, _s, _e = overprovisioned_slice(train_rows, i, new_c,
                                                   overprovision=f)
            old = resident.get(i)
            if old is None:
                total += hi - lo  # joiner: full target
            else:
                overlap = max(0, min(hi, old[1]) - max(lo, old[0]))
                total += (hi - lo) - overlap
            resident[i] = (lo, hi)
        for i in list(resident):
            if i >= new_c:
                resident.pop(i)
    return total


def _run_soak(train, test, make, cfg: dict, label: str) -> dict:
    from distributed_sgd_tpu.core.cluster import DevCluster
    from distributed_sgd_tpu.telemetry import resources, slope
    from distributed_sgd_tpu.utils import metrics as mm

    g = mm.global_metrics()
    n0 = cfg["workers"]
    quorum = max(1, n0 - cfg["quorum_slack"])
    counts = [n0]
    executed = []
    stop = threading.Event()

    # long-horizon watch: a REAL probe thread (the production path, not a
    # test-driven tick loop) sampling across the whole soak, the sentinel
    # on absolute calibrated bars
    sentinel = slope.LeakSentinel(
        metrics=g, min_horizon_s=MIN_HORIZON_S[label],
        thresholds={"rss": MAX_RSS_SLOPE[label], "fds": MAX_FD_SLOPE,
                    "threads": MAX_THREAD_SLOPE})
    probe = resources.ResourceProbe(
        metrics=g, interval_s=PROBE_S[label], sentinel=sentinel).start()

    with DevCluster(make(), train, test, n_workers=n0, seed=0,
                    heartbeat_s=cfg["heartbeat_s"],
                    heartbeat_max_misses=cfg["heartbeat_max_misses"],
                    chaos=cfg["chaos"], host_local=True,
                    host_overprovision=cfg["overprovision"]) as c:
        _prewarm(c, train, cfg["batch"])
        gated_counters = {
            "evictions": mm.MASTER_EVICTIONS,
            "reload_rows": mm.DATA_RELOAD_ROWS,
            "reloads": mm.DATA_RELOADS,
            "resplits": mm.SYNC_RESPLITS,
            "stage_hits": mm.STAGE_HITS,
        }
        before = {k: g.counter(name).value
                  for k, name in gated_counters.items()}

        def _churner():
            t0 = time.monotonic()
            for t_at, action in cfg["churn"]:
                while not stop.is_set() and time.monotonic() - t0 < t_at:
                    time.sleep(0.1)
                if stop.is_set():
                    return
                try:
                    if action == "leave":
                        w = c.leave_worker(len(c.workers) - 1)
                        counts.append(counts[-1] - 1)
                        log(f"  churn @{t_at:5.1f}s: worker :{w.port} left "
                            f"({counts[-1]} members)")
                    else:
                        w = c.add_worker(host_local=True)
                        counts.append(counts[-1] + 1)
                        log(f"  churn @{t_at:5.1f}s: worker :{w.port} "
                            f"joined ({counts[-1]} members)")
                    executed.append((t_at, action))
                except Exception as e:  # noqa: BLE001 - surface via assert
                    log(f"  churn @{t_at:5.1f}s: {action} FAILED: {e}")
                    return

        churner = threading.Thread(target=_churner, daemon=True,
                                   name="soak-churn")
        t0 = time.perf_counter()
        churner.start()
        try:
            res = c.master.fit_sync(
                max_epochs=cfg["epochs"], batch_size=cfg["batch"],
                learning_rate=cfg["lr"],
                grad_timeout_s=cfg["grad_timeout_s"], grad_retries=6,
                quorum=quorum, straggler_soft_s=cfg["soft_s"], hedge=True,
                stream=True, fanin_lanes=LANES, stage_pool=POOL,
            )
        finally:
            stop.set()
            churner.join(timeout=10.0)
        wall = time.perf_counter() - t0
        after_members = len(c.master._workers)
        d = {k: g.counter(name).value - before[k]
             for k, name in gated_counters.items()}
    probe.stop()
    return {
        "res": res, "wall": wall, "counters": d, "counts": counts,
        "executed": executed, "survivors": after_members,
        "final_loss": float(res.losses[-1]),
        "weights": np.asarray(res.state.weights),
        "sentinel": sentinel, "probe_ticks": probe.ticks,
        "rss_slope": sentinel.slope("rss"),
        "fd_slope": sentinel.slope("fds"),
    }


def _probe_overhead(label: str) -> dict:
    """Probe-overhead gate on the canonical --rpc workload: interleaved
    base/probe-on fits, per-config MINIMUM (loopback gRPC on a shared
    host is noisy upward, never downward), hard < 5% assert — the
    bench_telemetry pattern, with the probe ticking at 0.1 s (100x the
    production default cadence)."""
    from benches.bench_rpc_sync import _build as build_rpc_workload
    from distributed_sgd_tpu.core.cluster import DevCluster
    from distributed_sgd_tpu.telemetry import resources

    cfg = OVERHEAD_SMOKE if label == "smoke" else OVERHEAD_FULL
    reps = OVERHEAD_REPS[label]
    train, test, make = build_rpc_workload(cfg)

    def fit(probe_on: bool) -> float:
        with DevCluster(make(), train, test, n_workers=2, seed=0) as c:
            probe = (resources.ResourceProbe(
                interval_s=OVERHEAD_PROBE_S).start() if probe_on else None)
            try:
                t0 = time.perf_counter()
                c.master.fit_sync(max_epochs=cfg["epochs"],
                                  batch_size=cfg["batch"],
                                  learning_rate=cfg["lr"])
                return time.perf_counter() - t0
            finally:
                if probe is not None:
                    probe.stop()

    base = probed = float("inf")
    ticks = 0
    for rep in range(reps):
        w = fit(False)
        base = min(base, w)
        log(f"  overhead rep {rep}: base  {w:.2f}s")
        w = fit(True)
        probed = min(probed, w)
        log(f"  overhead rep {rep}: probe {w:.2f}s")
    overhead = probed / base - 1.0
    log(f"probe overhead: {overhead:+.1%} (base {base:.2f}s, probed "
        f"{probed:.2f}s at {OVERHEAD_PROBE_S}s cadence; bar: "
        f"< {MAX_PROBE_OVERHEAD:.0%})")
    assert overhead <= MAX_PROBE_OVERHEAD, (
        f"resource probe costs {overhead:+.1%} on the rpc sync workload — "
        f"over the {MAX_PROBE_OVERHEAD:.0%} bar (base {base:.2f}s, probed "
        f"{probed:.2f}s)")
    return {
        "probe_overhead_frac_info": round(overhead, 4),
        "probe_base_wall_s_info": round(base, 3),
        "probe_on_wall_s_info": round(probed, 3),
    }


def run_bench(smoke: bool = False) -> dict:
    cfg = SMOKE if smoke else FULL
    label = "smoke" if smoke else "full"
    quorum = max(1, cfg["workers"] - cfg["quorum_slack"])
    log(f"soak bench ({label}): {cfg['workers']} workers, n={cfg['n']} "
        f"dim={cfg['n_features']} batch={cfg['batch']}/worker "
        f"epochs={cfg['epochs']} quorum={quorum} plan={cfg['chaos']!r} "
        f"churn={len(cfg['churn'])} events, overprovision="
        f"{cfg['overprovision']}")
    train, test, make = _build(cfg)

    # clear-weather, churn-free, knobs-off baseline at the same shape: the
    # convergence-parity anchor (drift-0 of the knobs themselves is the
    # scale bench's gate; weather + churn move loss through quorum timing)
    from distributed_sgd_tpu.core.cluster import DevCluster

    t0 = time.perf_counter()
    with DevCluster(make(), train, test, n_workers=cfg["workers"],
                    seed=0) as c:
        _prewarm(c, train, cfg["batch"])
        base = c.master.fit_sync(
            max_epochs=cfg["epochs"], batch_size=cfg["batch"],
            learning_rate=cfg["lr"], grad_timeout_s=30.0)
    base_wall = time.perf_counter() - t0
    base_loss = float(base.losses[-1])
    log(f"baseline: loss={base_loss:.6f} ({base_wall:.1f}s clear weather)")

    soak = _run_soak(train, test, make, cfg, label)
    d = soak["counters"]
    transitions = len(soak["counts"]) - 1
    bound = _expected_delta_bound(
        cfg["overprovision"], soak["counts"],
        train_rows=len(train)) if transitions else 0
    bound_slacked = int(DELTA_SLACK * bound) + cfg["workers"]
    full_equiv = transitions * len(train)
    parity_bound = max(PARITY_REL * base_loss, base_loss + PARITY_ABS)

    completed = soak["res"].epochs_run == cfg["epochs"]
    churn_ok = len(soak["executed"]) == len(cfg["churn"])
    zero_evictions = d["evictions"] == 0
    parity_ok = soak["final_loss"] <= parity_bound
    delta_ok = (transitions > 0 and d["reload_rows"] <= bound_slacked
                and d["reload_rows"] < full_equiv)
    log(f"soak: {soak['wall']:.1f}s wall, epochs "
        f"{soak['res'].epochs_run}/{cfg['epochs']}, churn "
        f"{len(soak['executed'])}/{len(cfg['churn'])} events, "
        f"members {soak['survivors']}/{cfg['workers']}, evictions "
        f"{d['evictions']}, resplits {d['resplits']}, reloads "
        f"{d['reloads']} ({d['reload_rows']} rows vs delta bound "
        f"{bound_slacked}, full-reload equiv {full_equiv}), loss "
        f"{soak['final_loss']:.6f} vs bound {parity_bound:.6f}, "
        f"stage hits {d['stage_hits']}")
    assert completed, "the soak fit did not run every epoch"
    assert churn_ok, (
        f"only {len(soak['executed'])}/{len(cfg['churn'])} churn events "
        f"landed inside the fit — lengthen the fit or tighten the schedule")
    assert zero_evictions, (
        f"{d['evictions']} live-worker eviction(s) under the soak — "
        f"graceful churn and weathered stragglers must never evict")
    assert delta_ok, (
        f"reload rows {d['reload_rows']} broke the O(delta) contract "
        f"(bound {bound_slacked}, full-reload equiv {full_equiv})")
    assert parity_ok, (
        f"soak final loss {soak['final_loss']:.6f} exceeds the parity "
        f"bound {parity_bound:.6f}")
    assert d["stage_hits"] > 0, "the soak never dispatched a staged draw"

    # -- long-horizon leak gate (ISSUE 20) --------------------------------
    sentinel = soak["sentinel"]
    rss_slope, fd_slope = soak["rss_slope"], soak["fd_slope"]
    log(f"leak watch: {soak['probe_ticks']} probe ticks, rss slope "
        f"{rss_slope:g} B/s (bar {MAX_RSS_SLOPE[label]:g}), fd slope "
        f"{fd_slope:g}/s (bar {MAX_FD_SLOPE:g}), tripped="
        f"{sorted(sentinel.tripped_series) or 'none'}")
    assert not sentinel.tripped(), (
        f"the leak sentinel tripped during the soak: "
        f"{sorted(sentinel.tripped_series)} — read the flight-*-leak.json "
        f"dump")
    assert rss_slope == rss_slope and fd_slope == fd_slope, (
        f"the probe never accumulated a judgeable window "
        f"({soak['probe_ticks']} ticks) — the leak gate measured nothing")

    overhead = _probe_overhead(label)

    return {
        **overhead,
        "metric": f"soak_{label}",
        # headline, gated lower-is-better: soak wall seconds (the weather
        # and churn schedule are seeded/fixed, so this is reproducible)
        "value": round(soak["wall"], 2),
        "unit": "s",
        "workers": cfg["workers"],
        "epochs": cfg["epochs"],
        "quorum": quorum,
        "churn_events": len(soak["executed"]),
        "transitions": transitions,
        "completed": int(completed),
        "zero_evictions": int(zero_evictions),
        "evictions": d["evictions"],
        "resplits": d["resplits"],
        "reloads": d["reloads"],
        "reload_rows": d["reload_rows"],
        "reload_delta_bound": bound_slacked,
        "reload_full_equiv": full_equiv,
        "delta_ok": int(delta_ok),
        "final_loss": round(soak["final_loss"], 6),
        "baseline_loss_info": round(base_loss, 6),
        "loss_parity_ok": int(parity_ok),
        "stage_hits": d["stage_hits"],
        "baseline_wall_s_info": round(base_wall, 2),
        "survivors": soak["survivors"],
        # leak-watch context on the headline row (the dedicated
        # soak_rss_slope/soak_fd_slope series below carry the gated trend)
        "probe_ticks": soak["probe_ticks"],
        "rss_slope_info": round(rss_slope, 2),
        "fd_slope_info": round(fd_slope, 4),
    }


def main(smoke: bool = False) -> None:
    result = run_bench(smoke=smoke)
    label = "smoke" if smoke else "full"
    # The slope bars on the final Theil–Sen fit, held here and not in
    # `run_bench`: bytes a second of a 17 s smoke run is a reading of the
    # box's allocator warm-up, which tier-1 only records.
    rss_slope, fd_slope = result["rss_slope_info"], result["fd_slope_info"]
    assert rss_slope <= MAX_RSS_SLOPE[label], (
        f"rss slope {rss_slope:g} B/s over the {MAX_RSS_SLOPE[label]:g} "
        f"B/s bar across the chaos soak")
    assert fd_slope <= MAX_FD_SLOPE, (
        f"fd slope {fd_slope:g}/s over the {MAX_FD_SLOPE:g}/s bar across "
        f"the chaos soak")
    # dedicated slope series (ISSUE 20): thin rows whose `*_slope` fields
    # regress.py gates lower-is-better at the 100% slope band (skipping
    # non-positive values) — the cross-round leak trend, beside the
    # per-run absolute bars asserted above
    slope_rows = [
        {"metric": f"soak_rss_slope_{label}", "unit": "bytes_per_s",
         "rss_slope": result["rss_slope_info"],
         "bar_info": MAX_RSS_SLOPE[label]},
        {"metric": f"soak_fd_slope_{label}", "unit": "fds_per_s",
         "fd_slope": result["fd_slope_info"], "bar_info": MAX_FD_SLOPE},
    ]
    try:
        from benches import regress

        history = regress.load_history()
        regressions, lines = regress.check(result, history)
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
        for row in slope_rows:
            row_reg, row_lines = regress.check(row, history)
            for ln in row_lines:
                log(ln)
            if row_reg:
                result["regressed"] = result["regressed"] + row_reg
                log(f"FAIL: {row['metric']} regressed (row NOT recorded)")
            else:
                regress.record(row)
    except Exception as e:  # noqa: BLE001 - gating must not break the bench
        log(f"regression gate skipped: {e}")
        result["regressed"] = None
        result["gate_error"] = str(e)
    print(json.dumps(result))


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main(smoke="--smoke" in sys.argv)
