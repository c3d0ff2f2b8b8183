"""Chaos gate: sync training under a canonical fault plan, quorum on/off
(docs/FAULT_TOLERANCE.md).

The acceptance bar of the chaos-hardening PR, measured on a 3-worker
loopback RPC cluster (real gRPC, core/cluster.py dev topology) under the
canonical plan — 5% drop, 20–200 ms delay, 1% duplication, one timed
partition of w1 — with a fixed seed so every run injects the same faults:

- the DSGD_QUORUM=N-1 run COMPLETES with ZERO evictions of live workers
  (stragglers are slow, not dead);
- its final loss stays within the compression PR's convergence-parity
  gate of the clear-weather baseline (<= max(1.02 * base, base + 0.02),
  docs/COMPRESSION.md);
- it stalls >= 3x fewer rounds past the soft deadline than the same
  plan with the quorum off (`master.sync.barrier.stalled` counts
  soft-deadline overruns that got no quorum relief);
- and the knobs are pure observation when off: the quorum-off baseline
  with stall accounting enabled lands on bit-identical weights to the
  plain knobs-off run (asserted in --smoke).

Five runs, one fresh cluster each, counters diffed from the global
registry: ``baseline`` (no chaos, knobs off), ``baseline_observed`` (no
chaos, soft-deadline accounting only), ``chaos_full_barrier`` (chaos on,
quorum off, generous retries so drops don't evict), ``chaos_quorum``
(chaos on, quorum=N-1, hedging on), and ``chaos_stream`` (the quorum run
again over the persistent FitStream transport, DSGD_STREAM — proving
quorum/hedging/eviction semantics survive on streams: stream writes eat
the same seeded weather, per-frame drops expire like unary deadlines,
chaos stream teardowns fall back to unary and re-open, hedges stay
unary, and the run must complete with zero live-worker evictions inside
the same loss-parity gate).

Run: ``python bench.py --chaos [--smoke]``.  Prints exactly ONE JSON
line on stdout; diagnostics to stderr; gated round-over-round through
benches/regress.py (``value`` = chaos+quorum wall seconds, ``*_loss``
lower-is-better).  The full-size soak is the `slow`-marked
tests/test_chaos.py::test_chaos_smoke_bench's big sibling.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

N_WORKERS = 3
# smoke: CI-sized — small corpus, short partition, fast deadlines.  full:
# the canonical ISSUE plan verbatim (10 s partition at t=30 s needs a run
# that long).  Both seeded, so the injected fault sequence replays.
SMOKE = dict(
    # 3 epochs: a 2-epoch fit is still so far from convergence that ONE
    # round with an entirely-uncovered slice moves the final loss past
    # the 2% parity bound; by epoch 3 the degraded rounds wash out
    n=640, n_features=2048, nnz=8, batch=16, epochs=3, lr=0.5,
    # the partition window sits where the (short) smoke fit actually
    # runs, and the drop rate is scaled up so the seeded weather lands
    # enough faults on a 22-round fit for the 3x contrast to be sharp
    chaos="seed=7;drop=0.08;delay=5ms~20ms;dup=0.01;partition=w1:2s@500ms",
    soft_s=0.35, grad_timeout_s=1.0,
)
FULL = dict(
    n=5120, n_features=47_236, nnz=76, batch=16, epochs=4, lr=0.5,
    chaos="seed=7;drop=0.05;delay=20ms~200ms;dup=0.01;partition=w1:10s@30s",
    # 2 s hard deadline: every full-barrier drop stalls a window for 2 s
    # (that cost IS the quorum-off headline), bounding the run at minutes
    soft_s=0.5, grad_timeout_s=2.0,
)
PARITY_REL = 1.02
PARITY_ABS = 0.02
STALL_IMPROVEMENT_X = 3.0

_COUNTERS = (
    "master.sync.rounds",
    "master.sync.barrier.stalled",
    "master.sync.quorum.degraded",
    "master.sync.quorum.hedges",
    "master.sync.quorum.hedge_wins",
    "master.sync.quorum.late",
    "chaos.injected.drop",
    "chaos.injected.delay",
    "chaos.injected.dup",
    "chaos.injected.partition",
    "chaos.injected.stream_teardown",
    "master.sync.stream.sends",
    "master.sync.stream.expired",
    "master.sync.stream.broken",
    "master.sync.stream.fallback",
    "master.sync.stream.late",
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _snapshot():
    from distributed_sgd_tpu.utils import metrics as mm

    g = mm.global_metrics()
    return {name: g.counter(name).value for name in _COUNTERS}


def _build(cfg: dict):
    from distributed_sgd_tpu.data.rcv1 import dim_sparsity, train_test_split
    from distributed_sgd_tpu.data.synthetic import rcv1_like

    data = rcv1_like(cfg["n"], n_features=cfg["n_features"], nnz=cfg["nnz"],
                     seed=7, idf_values=True)
    train, test = train_test_split(data)
    ds = dim_sparsity(train)

    def make():
        from distributed_sgd_tpu.models.linear import make_model

        return make_model("hinge", 1e-5, train.n_features, dim_sparsity=ds)

    return train, test, make


def _run(train, test, make_model_fn, cfg: dict, *, chaos=None, quorum=None,
         soft_s=None, grad_retries=1, stream=False, label="") -> dict:
    from distributed_sgd_tpu.core.cluster import DevCluster

    before = _snapshot()
    t0 = time.perf_counter()
    with DevCluster(make_model_fn(), train, test, n_workers=N_WORKERS,
                    seed=0, chaos=chaos) as c:
        # prewarm every worker's jitted gradient kernel (direct call, no
        # RPC): the first window must measure the WEATHER, not XLA compile
        # latency racing the gradient deadline
        zeros = np.zeros(train.n_features, dtype=np.float32)
        warm_ids = np.arange(min(cfg["batch"], len(train)), dtype=np.int64)
        for w in c.workers:
            w.compute_gradient(zeros, warm_ids)
        res = c.master.fit_sync(
            max_epochs=cfg["epochs"], batch_size=cfg["batch"],
            learning_rate=cfg["lr"], grad_timeout_s=cfg["grad_timeout_s"],
            grad_retries=grad_retries, quorum=quorum,
            straggler_soft_s=soft_s, stream=stream,
        )
        survivors = len(c.master._workers)
    wall_s = time.perf_counter() - t0
    after = _snapshot()
    d = {name: after[name] - before[name] for name in _COUNTERS}
    out = {
        "counters": d,
        "wall_s": wall_s,
        "rounds": d["master.sync.rounds"],
        "stalled": d["master.sync.barrier.stalled"],
        "final_loss": float(res.losses[-1]),
        "weights": np.asarray(res.state.weights),
        "survivors": survivors,
        "epochs_run": res.epochs_run,
    }
    log(f"{label:18s}: rounds={out['rounds']} stalled={out['stalled']} "
        f"degraded={d['master.sync.quorum.degraded']} "
        f"hedges={d['master.sync.quorum.hedges']} "
        f"(wins {d['master.sync.quorum.hedge_wins']}) "
        f"survivors={survivors}/{N_WORKERS} "
        f"loss={out['final_loss']:.6f} ({wall_s:.1f}s)")
    return out


def run_bench(smoke: bool = False) -> dict:
    cfg = SMOKE if smoke else FULL
    label = "smoke" if smoke else "full"
    log(f"chaos bench ({label}): n={cfg['n']} dim={cfg['n_features']} "
        f"workers={N_WORKERS} epochs={cfg['epochs']} plan={cfg['chaos']!r} "
        f"soft={cfg['soft_s']}s quorum={N_WORKERS - 1}")
    train, test, make = _build(cfg)

    base = _run(train, test, make, cfg, label="baseline")
    base_obs = _run(train, test, make, cfg, soft_s=cfg["soft_s"],
                    label="baseline_observed")
    drift = float(np.max(np.abs(base_obs["weights"] - base["weights"])))
    log(f"knobs-off invariance: max|w_observed - w_plain| = {drift:.2e}")
    if smoke:
        assert drift == 0.0, (
            f"soft-deadline stall accounting perturbed the fit (drift "
            f"{drift}) — it must be pure observation")

    # quorum off under chaos: every drop/partition stalls the full barrier
    # to the hard deadline and retries the window; retries are generous so
    # transient drops don't evict (the comparison is straggler handling,
    # not eviction policy)
    chaos_off = _run(train, test, make, cfg, chaos=cfg["chaos"],
                     soft_s=cfg["soft_s"], grad_retries=8,
                     label="chaos_full_barrier")
    chaos_q = _run(train, test, make, cfg, chaos=cfg["chaos"],
                   quorum=N_WORKERS - 1, soft_s=cfg["soft_s"],
                   label="chaos_quorum")
    # the same weathered quorum fit over the persistent streams
    # (DSGD_STREAM): quorum, hedging (always unary), per-frame drops, and
    # chaos-injected stream teardowns with unary fallback + re-open all
    # compose — semantics survive the transport swap
    chaos_s = _run(train, test, make, cfg, chaos=cfg["chaos"],
                   quorum=N_WORKERS - 1, soft_s=cfg["soft_s"], stream=True,
                   label="chaos_stream")
    ds = chaos_s["counters"]
    log(f"stream transport under chaos: sends="
        f"{ds['master.sync.stream.sends']} "
        f"expired={ds['master.sync.stream.expired']} "
        f"teardowns={ds['chaos.injected.stream_teardown']} "
        f"broken={ds['master.sync.stream.broken']} "
        f"fallbacks={ds['master.sync.stream.fallback']} "
        f"late={ds['master.sync.stream.late']}")

    parity_bound = max(PARITY_REL * base["final_loss"],
                       base["final_loss"] + PARITY_ABS)
    parity_ok = chaos_q["final_loss"] <= parity_bound
    no_evictions = chaos_q["survivors"] == N_WORKERS
    completed = chaos_q["epochs_run"] == cfg["epochs"]
    stream_parity_ok = chaos_s["final_loss"] <= parity_bound
    stream_completed = (chaos_s["epochs_run"] == cfg["epochs"]
                        and chaos_s["survivors"] == N_WORKERS)
    stall_x = chaos_off["stalled"] / max(1, chaos_q["stalled"])
    stall_ok = (chaos_off["stalled"] >= STALL_IMPROVEMENT_X
                * max(1, chaos_q["stalled"]))
    inflation = chaos_q["wall_s"] / max(1e-9, base["wall_s"])
    log(f"gates: completed={completed} evictions={'0' if no_evictions else 'SOME'} "
        f"loss {chaos_q['final_loss']:.6f} vs bound {parity_bound:.6f} "
        f"({'OK' if parity_ok else 'FAIL'}); stalled {chaos_off['stalled']} "
        f"(full barrier) vs {chaos_q['stalled']} (quorum) = {stall_x:.1f}x "
        f"({'OK' if stall_ok else 'FAIL'}, bar >= {STALL_IMPROVEMENT_X}x); "
        f"epoch-time inflation {inflation:.2f}x under chaos")
    if smoke:
        assert completed, "chaos+quorum fit did not run every epoch"
        assert no_evictions, (
            f"live workers were evicted under quorum "
            f"({chaos_q['survivors']}/{N_WORKERS} left) — a straggler is "
            f"slow, not dead")
        assert parity_ok, (
            f"chaos+quorum final loss {chaos_q['final_loss']:.6f} exceeds "
            f"the parity bound {parity_bound:.6f}")
        assert stall_ok, (
            f"quorum stalls {chaos_q['stalled']} not >= {STALL_IMPROVEMENT_X}x "
            f"fewer than full-barrier stalls {chaos_off['stalled']}")
        assert stream_completed, (
            f"chaos+quorum+stream fit lost workers or epochs "
            f"({chaos_s['survivors']}/{N_WORKERS} left, "
            f"{chaos_s['epochs_run']}/{cfg['epochs']} epochs) — "
            f"quorum/eviction semantics must survive the stream transport")
        assert stream_parity_ok, (
            f"chaos+quorum+stream final loss {chaos_s['final_loss']:.6f} "
            f"exceeds the parity bound {parity_bound:.6f}")
        assert ds["master.sync.stream.sends"] > 0, (
            "the stream row never actually streamed")

    return {
        "metric": f"chaos_sync_{label}",
        # headline, gated lower-is-better: wall seconds of the chaos+quorum
        # run (the fault plan is seeded, so this is reproducible weather)
        "value": round(chaos_q["wall_s"], 2),
        "unit": "s",
        "final_loss": round(chaos_q["final_loss"], 6),
        "baseline_loss_info": round(base["final_loss"], 6),
        "chaos_full_barrier_loss_info": round(chaos_off["final_loss"], 6),
        "loss_parity_ok": int(parity_ok),
        "completed": int(completed),
        "zero_evictions": int(no_evictions),
        "stalled_full_barrier": chaos_off["stalled"],
        "stalled_quorum": chaos_q["stalled"],
        "stall_improvement_x": round(stall_x, 2),
        "degraded_rounds": chaos_q["counters"]["master.sync.quorum.degraded"],
        "hedges": chaos_q["counters"]["master.sync.quorum.hedges"],
        "hedge_wins": chaos_q["counters"]["master.sync.quorum.hedge_wins"],
        "late_discards": chaos_q["counters"]["master.sync.quorum.late"],
        "injected_drops": chaos_q["counters"]["chaos.injected.drop"],
        "injected_partition_drops":
            chaos_q["counters"]["chaos.injected.partition"],
        "epoch_inflation_x_info": round(inflation, 2),
        "stream_final_loss_info": round(chaos_s["final_loss"], 6),
        "stream_completed": int(stream_completed),
        "stream_parity_ok": int(stream_parity_ok),
        "stream_sends": ds["master.sync.stream.sends"],
        "stream_frame_expiries": ds["master.sync.stream.expired"],
        "stream_teardowns": ds["chaos.injected.stream_teardown"],
        "stream_fallbacks": ds["master.sync.stream.fallback"],
        "stream_late_drops": ds["master.sync.stream.late"],
        "knobs_off_drift": drift,
        "baseline_wall_s_info": round(base["wall_s"], 2),
        "rounds_quorum": chaos_q["rounds"],
        "n_workers": N_WORKERS,
        "quorum": N_WORKERS - 1,
        **{k: v for k, v in cfg.items() if not isinstance(v, str)},
    }


def main(smoke: bool = False) -> None:
    result = run_bench(smoke=smoke)
    # round-over-round gate (benches/regress.py): same policy as bench.py —
    # a clean run is appended to history, a regressed run is not
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
