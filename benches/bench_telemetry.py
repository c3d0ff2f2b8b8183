"""Telemetry-overhead gate (docs/OBSERVABILITY.md, ISSUE 7).

The cluster telemetry plane's contract mirrors the tracer's: default-off
costs nothing (no Metrics RPC is ever issued), and FULLY ON — per-
dispatch worker health gauges, the master-side health monitor on every
round, plus a Prometheus-style poller hammering the cluster endpoint
(each pull triggers a throttled Metrics-RPC scrape fan-out) — costs
< 5% on the same 2-worker loopback RPC sync workload as ``bench.py
--rpc``:

- ``base``      — telemetry off: the knobs-off engine, shared global
  registry, no scrape, no endpoint;
- ``telemetry`` — DSGD_TELEMETRY semantics fully on (per-node
  registries, worker gauges, HealthMonitor(action='warn') observing
  every round and epoch, cluster endpoint polled every 200 ms).

Runs interleave base/telemetry and keep the per-config MINIMUM (loopback
gRPC on a shared host is noisy upward, never downward), then HARD-assert
``telemetry <= (1 + MAX_OVERHEAD) * base`` and that the polled endpoint
actually served per-worker health series (an overhead number for a plane
that silently exported nothing would gate the wrong thing).  Results go
through benches/regress.py like every bench — wall times emitted as
``*_info`` fields (ungated: loopback wall clock on a shared host would
false-alarm at any tolerance worth having).

Run: ``python bench.py --telemetry [--smoke]``.  Prints exactly ONE JSON
line on stdout; diagnostics go to stderr.
"""

from __future__ import annotations

import json
import sys
import threading
import time
import urllib.request

FULL = dict(n=2560, n_features=16384, nnz=32, batch=16, epochs=4, lr=0.5)
SMOKE = dict(n=640, n_features=4096, nnz=8, batch=16, epochs=2, lr=0.5)
N_WORKERS = 2
REPS = 2
POLL_S = 0.2  # Prometheus-ish pull cadence against the cluster endpoint
MAX_OVERHEAD = 0.05  # the ISSUE bar: scrape + health cost < 5%


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _build(cfg: dict):
    # the CANONICAL --rpc workload builder (corpus shape, model, split):
    # imported, not copied, so this bench cannot drift from the workload
    # it claims to measure
    from benches.bench_rpc_sync import _build as build_rpc_workload

    return build_rpc_workload(cfg)


def _run_fit(train, test, make_model_fn, cfg: dict, telemetry: bool):
    """One fit_sync on a fresh 2-worker loopback cluster; returns
    (fit wall seconds, exposition body or None).  The telemetry run polls
    the cluster endpoint concurrently — the pull itself is what triggers
    the Metrics-RPC scrape fan-out, so the measured wall clock includes
    the whole plane."""
    from distributed_sgd_tpu.core.cluster import DevCluster
    from distributed_sgd_tpu.telemetry.health import HealthMonitor

    with DevCluster(make_model_fn(), train, test, n_workers=N_WORKERS,
                    seed=0, telemetry_port=0 if telemetry else None) as c:
        body = None
        stop = threading.Event()
        poller = None
        health = None
        if telemetry:
            port = c.master.telemetry_exporter.port
            url = f"http://127.0.0.1:{port}/metrics"

            def poll():
                while not stop.wait(POLL_S):
                    try:
                        urllib.request.urlopen(url, timeout=5).read()
                    except Exception:  # noqa: BLE001 - keep polling
                        pass

            poller = threading.Thread(target=poll, daemon=True,
                                      name="telemetry-poll")
            poller.start()
            health = HealthMonitor(metrics=c.master.metrics, action="warn")
        t0 = time.perf_counter()
        c.master.fit_sync(max_epochs=cfg["epochs"], batch_size=cfg["batch"],
                          learning_rate=cfg["lr"], health=health)
        wall = time.perf_counter() - t0
        if telemetry:
            stop.set()
            poller.join(timeout=2.0)
            body = urllib.request.urlopen(url, timeout=5).read().decode()
        return wall, body


def run_bench(smoke: bool = False) -> dict:
    from distributed_sgd_tpu.utils import metrics as mm

    cfg = SMOKE if smoke else FULL
    label = "smoke" if smoke else "full"
    log(f"telemetry-overhead bench ({label}): n={cfg['n']} "
        f"dim={cfg['n_features']} nnz={cfg['nnz']} batch={cfg['batch']} "
        f"epochs={cfg['epochs']} workers={N_WORKERS} reps={REPS} "
        f"poll={POLL_S}s")
    train, test, make = _build(cfg)

    base_wall = float("inf")
    tel_wall = float("inf")
    body = ""
    for rep in range(REPS):
        w, _ = _run_fit(train, test, make, cfg, telemetry=False)
        base_wall = min(base_wall, w)
        log(f"rep {rep}: base      {w:.2f}s")
        w, b = _run_fit(train, test, make, cfg, telemetry=True)
        tel_wall = min(tel_wall, w)
        body = b or body
        log(f"rep {rep}: telemetry {w:.2f}s "
            f"({len((b or '').splitlines())} exposition lines)")

    overhead = tel_wall / base_wall - 1.0
    log(f"overhead: {overhead:+.1%} (base {base_wall:.2f}s, telemetry "
        f"{tel_wall:.2f}s; bar: < {MAX_OVERHEAD:.0%})")
    assert overhead <= MAX_OVERHEAD, (
        f"full telemetry (scrape + health) costs {overhead:+.1%} on the rpc "
        f"sync workload — over the {MAX_OVERHEAD:.0%} bar (base "
        f"{base_wall:.2f}s, telemetry {tel_wall:.2f}s)")
    # the plane must have EXPORTED, not just cost nothing: per-worker
    # health gauges and the cluster-summed counter family
    grad_gauge = mm.HEALTH_GRAD_NORM.replace(".", "_")
    rounds_total = mm.SYNC_ROUNDS.replace(".", "_") + "_total"
    assert f'{grad_gauge}{{role="worker"' in body, (
        "cluster endpoint served no per-worker gradient-norm gauge")
    assert f'{rounds_total}{{role="cluster"}}' in body, (
        "cluster endpoint served no cluster-summed rounds counter")

    return {
        "metric": f"telemetry_overhead_{label}",
        "unit": "fraction",
        # wall times on a shared host are emitted ungated (*_info): the
        # <5% bar above is the hard gate, history is the trail
        "overhead_frac_info": round(overhead, 4),
        "base_wall_s_info": round(base_wall, 3),
        "telemetry_wall_s_info": round(tel_wall, 3),
        "exposition_lines_info": len(body.splitlines()),
        "overhead_bar_info": MAX_OVERHEAD,
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
