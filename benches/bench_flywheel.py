"""Continual-learning flywheel gate (docs/CONTINUAL.md; ROADMAP item 1).

The closed loop the autopilot exists for, run end to end in one process
with ZERO operator actions after start():

- a 2-worker loopback DevCluster trains the initial model on the first
  ``window`` rows of a seeded :class:`DriftingStream`, checkpointing
  every epoch;
- a 2-replica ServingFleet serves those checkpoints behind its router
  while the bench pumps the REST of the stream through Predict — the
  router reservoir-samples that live traffic into its own canary probe
  set (labels joining late through the stream oracle);
- the stream's step schedule flips the concept mid-pump; the autopilot
  controller sees the probe-loss series spike, trips the drift
  detector, warm-start retrains on the newest window, and the new
  version flows through CheckpointDistributor -> canary -> promote.

The smoke mode additionally runs the TRAINING plane under a named chaos
scenario (``scenario:flaky-rack;scope=named`` — the scope confines the
weather to the DevCluster's named master/worker edges): transport
weather on the gradient plane must not confuse the drift detector,
whose signal lives on the serving plane (the false-positive half of
tests/test_autopilot.py, proven here end to end).

Hard asserts (both modes):

- **no trip before the shift**: the drift counter stays 0 while the
  pump is still serving pre-shift rows;
- **>= 1 autopilot retrain and >= 1 promotion**, observed only through
  the router's own canary counters;
- **zero dropped Predict requests** across the whole pump — detection,
  retrain, and promotion included;
- **recovery within the round budget**: after the promotion, a
  trailing-3 mean of the probe-loss series returns to within
  RECOVERY_BAND of the pre-shift baseline within ROUND_BUDGET
  probe refreshes of the shift reaching the serving edge;
- **bounded leak slope**: least-squares RSS growth over the pump stays
  under MAX_RSS_SLOPE_MB_S and the net open-fd growth under
  MAX_FD_GROWTH (the hours-horizon guard, ROADMAP 3b) — a breach dumps
  the flight ring before failing.

``shift_recovery_rounds`` gates round-over-round through
benches/regress.py under the ``*_recovery_rounds`` class (lower is
better, 50% band); the pump latency quantiles gate under the
``*_p50_s``/``*_p99_s`` latency class.  Run: ``python bench.py
--flywheel [--smoke]``.  Prints exactly ONE JSON line on stdout;
diagnostics go to stderr.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

# workload shape: DENSE rows against a SMALL feature dimension, the
# opposite of the serve bench — the probe measures OUT-OF-SAMPLE loss on
# fresh traffic, so the model must generalize from window_rows examples
# (256 features x 16 nnz: fresh-row hinge ~0.4-0.6 pre-shift vs ~1.3
# across a step shift — the contrast the detector trips on; at the rcv1
# shape the generalization gap alone reads as drift)
SMOKE = dict(n_features=256, nnz=16, window=512, shift_at=1024,
             horizon=3072, epochs=4, batch=16, lr=0.5,
             probe_capacity=32, label_delay=4,
             chaos="scenario:flaky-rack;scope=named")
FULL = dict(n_features=512, nnz=32, window=1024, shift_at=2048,
            horizon=6144, epochs=4, batch=16, lr=0.5,
            probe_capacity=48, label_delay=8,
            chaos=None)
N_WORKERS = 2
N_REPLICAS = 2
SEED = 7
CHUNK = 64  # pump granularity; ~2 probe refreshes land per chunk
# pace floor per served row: the pre-shift serving stretch must span the
# detector's warmup refreshes in WALL-CLOCK terms, whatever the predict
# path's latency — an unpaced pump on a warm jit cache can outrun the
# refresh cadence and anchor the baseline on post-shift traffic
PACE_S = 0.004
# detector: 2x the pre-shift baseline for 2 consecutive refreshes after
# 4 warmup refreshes; the 0.25 floor keeps 1/capacity probe quantization
# noise from ever clearing the ratio bar at small losses
DETECTOR = dict(ratio=2.0, patience=2, warmup=4, abs_floor=0.25)
RECOVERY_BAND = 1.35  # recovered = trailing-3 mean <= band * baseline
# refreshes from shift to recovery: sized for the residual-retrain path
# (a first retrain on a shift-straddling window only half-recovers; the
# controller's settling rule earns a second on purer traffic).  The
# smoke budget carries extra headroom because its retrains run under
# flaky-rack weather — every chaos-dropped Gradient stalls its full
# grad_timeout_s while probe refreshes keep ticking
ROUND_BUDGET = dict(smoke=90, full=80)
SETTLE_S = 120.0
MAX_RSS_SLOPE_MB_S = dict(smoke=8.0, full=4.0)
MAX_FD_GROWTH = 64


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_bench(smoke: bool = False) -> dict:
    from distributed_sgd_tpu.autopilot import (
        DriftDetector,
        DriftingStream,
        Flywheel,
    )
    from distributed_sgd_tpu.trace import flight
    from distributed_sgd_tpu.utils import metrics as mm
    from distributed_sgd_tpu.utils.metrics import Metrics, sample_process_gauges

    cfg = SMOKE if smoke else FULL
    label = "smoke" if smoke else "full"
    budget = ROUND_BUDGET[label]
    log(f"flywheel bench ({label}): dim={cfg['n_features']} nnz={cfg['nnz']} "
        f"window={cfg['window']} shift@{cfg['shift_at']} "
        f"horizon={cfg['horizon']} workers={N_WORKERS} "
        f"replicas={N_REPLICAS} chaos={cfg['chaos']!r} "
        f"recovery<={budget} refreshes")

    stream = DriftingStream(
        n_features=cfg["n_features"], nnz=cfg["nnz"], seed=SEED,
        schedule="step", shift_at=cfg["shift_at"])
    metrics = Metrics()
    fly = Flywheel(
        stream, horizon_rows=cfg["horizon"], window_rows=cfg["window"],
        n_workers=N_WORKERS, n_replicas=N_REPLICAS,
        max_epochs=cfg["epochs"], batch_size=cfg["batch"],
        learning_rate=cfg["lr"], probe_capacity=cfg["probe_capacity"],
        label_delay=cfg["label_delay"], source_refresh_s=0.25,
        canary_fraction=0.5, health_s=0.1,
        detector=DriftDetector(**DETECTOR),
        poll_s=0.1, cooldown_s=0.5, canary_timeout_s=60.0,
        max_retrains=3, seed=SEED, metrics=metrics,
        grad_timeout_s=1.5, grad_retries=5,
        chaos=cfg["chaos"])

    t0 = time.perf_counter()
    fly.start()
    log(f"flywheel up in {time.perf_counter() - t0:.1f}s "
        f"(initial fit + fleet + first promotion)")

    # -- the pump: the whole post-window stream, sampled per chunk ----------
    latencies: list = []
    dropped: list = []
    samples: list = []  # (t, stream_time, refreshes, tripped, promoted)
    rss_fd: list = []   # (t, rss_bytes, open_fds)
    t_pump = time.perf_counter()
    while not fly.exhausted:
        lat, drops = fly.pump(CHUNK, pace_s=PACE_S)
        latencies.extend(lat)
        dropped.extend(drops)
        now = time.perf_counter() - t_pump
        samples.append((
            now, fly.stream_time, len(fly.fleet.router.probe_losses()),
            metrics.counter(mm.AUTOPILOT_DRIFT_TRIPPED).value,
            metrics.counter(mm.AUTOPILOT_PROMOTED).value))
        rss_fd.append((now, *sample_process_gauges(metrics)))
    pump_wall = time.perf_counter() - t_pump
    log(f"pumped {fly.served} rows in {pump_wall:.1f}s "
        f"({fly.served / pump_wall:.0f}/s), dropped={len(dropped)}")

    # refresh index at which the shift reached the serving edge, and at
    # which the first autopilot promotion landed (both sampled at chunk
    # granularity — a couple of refreshes of slack, inside the budget)
    shift_idx = next(r for (_, st, r, _, _) in samples
                     if st >= cfg["shift_at"])
    baseline = float(np.mean(
        fly.fleet.router.probe_losses()[1:shift_idx])) if shift_idx > 1 else 0.0
    bar = RECOVERY_BAND * baseline
    warm = DETECTOR["warmup"]

    # settle: the stream is exhausted but a (residual) retrain may still
    # be in flight — wait until the probe series is back under the bar
    # with at least one promotion, or give up at the deadline and let
    # the asserts report what the curve actually did
    deadline = time.time() + SETTLE_S
    while time.time() < deadline:
        losses = fly.fleet.router.probe_losses()
        if (len(losses) >= 3
                and metrics.counter(mm.AUTOPILOT_PROMOTED).value >= 1
                and fly.controller.state == "SERVING"
                and float(np.mean(losses[-3:])) <= bar):
            break
        time.sleep(0.2)
    losses = fly.fleet.router.probe_losses()
    retrains = fly.controller.retrains
    promoted = int(metrics.counter(mm.AUTOPILOT_PROMOTED).value)
    rolled_back = int(metrics.counter(mm.AUTOPILOT_ROLLED_BACK).value)
    state = fly.controller.state
    fly.stop()

    # -- the recovery curve --------------------------------------------------
    promo_idx = next((r for (_, _, r, _, p) in samples if p >= 1),
                     len(losses))
    shifted = float(max(losses[shift_idx:], default=0.0))
    log("probe series: "
        + " ".join(f"{x:.2f}" for x in losses)
        + f" | shift@{shift_idx} promo@{promo_idx}")
    recovery_idx = None
    for i in range(max(shift_idx, promo_idx, 2), len(losses)):
        if float(np.mean(losses[i - 2:i + 1])) <= bar:
            recovery_idx = i
            break
    recovery_rounds = (recovery_idx - shift_idx
                       if recovery_idx is not None else -1)
    recovered = (float(np.mean(losses[recovery_idx - 2:recovery_idx + 1]))
                 if recovery_idx is not None else float("nan"))
    log(f"{len(losses)} refreshes; baseline={baseline:.3f} "
        f"(refreshes 1..{shift_idx}), peak-after-shift={shifted:.3f}, "
        f"recovery bar={bar:.3f} -> recovered={recovered:.3f} at refresh "
        f"{recovery_idx} = {recovery_rounds} rounds after shift "
        f"(budget {budget})")
    log(f"autopilot: retrains={retrains} promoted={promoted} "
        f"rolled_back={rolled_back} state={state}")

    # -- leak slope ----------------------------------------------------------
    ts = np.asarray([t for t, _, _ in rss_fd])
    rss = np.asarray([r for _, r, _ in rss_fd])
    fds = np.asarray([f for _, _, f in rss_fd])
    rss_slope = float(np.polyfit(ts, rss, 1)[0]) if len(ts) > 2 else 0.0
    fd_growth = int(fds[-1] - fds[0]) if len(fds) else 0
    slope_bar = MAX_RSS_SLOPE_MB_S[label] * 1e6
    log(f"leak slope: rss {rss_slope / 1e6:+.2f} MB/s over {ts[-1]:.0f}s "
        f"(bar {slope_bar / 1e6:.0f} MB/s), fds {fds[0]:.0f} -> "
        f"{fds[-1]:.0f} (bar +{MAX_FD_GROWTH})")
    if rss_slope > slope_bar or fd_growth > MAX_FD_GROWTH:
        flight.record("flywheel.leak_slope", rss_mb_s=rss_slope / 1e6,
                      fd_growth=fd_growth)
        flight.dump("flywheel")
        raise AssertionError(
            f"leak slope breach: rss {rss_slope / 1e6:+.2f} MB/s "
            f"(bar {slope_bar / 1e6:.0f}), fds {fd_growth:+d} "
            f"(bar +{MAX_FD_GROWTH}) — flight ring dumped")

    # -- the gate ------------------------------------------------------------
    pre_shift_trips = [trip for (_, st, _, trip, _) in samples
                      if st < cfg["shift_at"]]
    assert not pre_shift_trips or pre_shift_trips[-1] == 0, (
        f"drift tripped while the pump was still serving pre-shift rows "
        f"(false positive; trips={pre_shift_trips[-1]})")
    assert not dropped, (
        f"{len(dropped)} dropped Predict requests across the flywheel "
        f"cycle: {dropped[:3]}")
    assert retrains >= 1, "the autopilot never retrained"
    assert promoted >= 1, (
        f"no autopilot retrain was promoted ({retrains} retrains, "
        f"{rolled_back} rolled back)")
    assert shifted > RECOVERY_BAND * baseline, (
        f"the planted shift never moved the probe loss "
        f"(peak {shifted:.3f} vs baseline {baseline:.3f}) — nothing to "
        f"recover from, the bench measured nothing")
    assert recovery_idx is not None, (
        f"probe loss never recovered to {bar:.3f} "
        f"(= {RECOVERY_BAND} x baseline {baseline:.3f}) after the shift")
    assert recovery_rounds <= budget, (
        f"recovery took {recovery_rounds} refreshes (budget {budget})")

    lat = np.asarray(latencies)
    p50 = float(np.percentile(lat, 50))
    p99 = float(np.percentile(lat, 99))
    return {
        "metric": f"flywheel_{label}",
        "unit": "rounds",
        "shift_recovery_rounds": int(recovery_rounds),
        "predict_p50_s": round(p50, 5),
        "predict_p99_s": round(p99, 5),
        "baseline_loss_info": round(baseline, 4),
        "shifted_peak_loss_info": round(shifted, 4),
        "recovered_loss_info": round(recovered, 4),
        "refreshes_info": len(losses),
        "served_info": int(fly.served),
        "dropped_info": len(dropped),
        "retrains_info": int(retrains),
        "promoted_info": promoted,
        "rolled_back_info": rolled_back,
        "rss_slope_mb_s_info": round(rss_slope / 1e6, 3),
        "fd_growth_info": fd_growth,
        "detector_warmup_info": warm,
        "round_budget_info": budget,
        "chaos": cfg["chaos"],
        "n_features": cfg["n_features"],
        "window": cfg["window"],
        "horizon": cfg["horizon"],
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
