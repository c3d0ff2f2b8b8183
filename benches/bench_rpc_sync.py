"""RPC sync-path wire bench: broadcast bytes + rounds per epoch, with and
without the pipelined sync engine (docs/SYNC_PIPELINE.md).

The acceptance bar of the pipelined-sync PR: on a 2-worker RPC cluster
(real loopback gRPC, the same topology as core/cluster.py dev mode) with
DSGD_DELTA_BROADCAST=1 + DSGD_LOCAL_STEPS=4, master->worker broadcast
bytes per epoch drop >= 5x and sync rounds per epoch drop >= 4x vs the
default path, with final loss within 2% of the default (the convergence-
parity gate style of docs/COMPRESSION.md).

Three runs, one fresh cluster each, counters diffed from the global
registry (utils/metrics.py master.sync.*):

- ``default``   — knobs off: the seed's per-window dense broadcast;
- ``delta_k1``  — DSGD_DELTA_BROADCAST only: transport is exact
                  (WeightDelta ships absolute values), so the final
                  weights must EQUAL the default run's bit-for-bit —
                  asserted in --smoke (to 1e-6, observed 0);
- ``pipelined`` — delta broadcast + K=4 local steps: the headline.

Streaming transport rows (DSGD_STREAM, docs/SYNC_PIPELINE.md "Streaming
transport"): interleaved stream-vs-unary fits at the RPC-BOUND shape —
small batch, where the per-round floor is per-call unary overhead
(HTTP/2 stream setup/teardown, metadata, future allocation), not the
math.  Best-of-reps rounds/s each way, HARD-gated at >= 1.25x for the
persistent-stream transport with weight drift 0.0 (identical math: same
messages, same send-ordered decode — smoke additionally asserts the
final losses agree to 1e-6 and that a knobs-off run never touches a
stream instrument).  The ``*_rounds_per_s`` fields gate higher-is-better
through benches/regress.py's throughput class.

Run: ``python bench.py --rpc`` (or ``--rpc --smoke`` for the CI-sized
corpus).  Prints exactly ONE JSON line on stdout; diagnostics go to
stderr.  Results are gated round-over-round through benches/regress.py
(``*_bytes`` gates lower-is-better), so a future PR that silently
regresses broadcast bytes fails the gate.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

# full mode: RCV1's feature dimension and row density at a corpus size a
# CPU run finishes in minutes.  n is a multiple of 160 so each worker's
# partition (0.8 * n / 2) divides evenly by batch*K and the rounds ratio
# is exactly K (a ragged tail would pay one extra short round both ways)
FULL = dict(n=5120, n_features=47_236, nnz=76, batch=16, epochs=8, lr=0.5)
SMOKE = dict(n=640, n_features=4096, nnz=8, batch=16, epochs=1, lr=0.5)
K = 4
N_WORKERS = 2
# the RPC-bound shape for the streaming-transport rows: batch and dim so
# small that the per-round floor is unary per-call overhead — the 2 KB
# broadcast and the B=2 kernel are both far below the per-call cost, so
# the rows measure the TRANSPORT.  128 rounds/epoch on a 256-row
# partition.
STREAM_SHAPE = dict(n=640, n_features=512, nnz=8, batch=2, lr=0.5)
STREAM_EPOCHS = dict(smoke=2, full=4)
STREAM_REPS = dict(smoke=2, full=3)
STREAM_SPEEDUP_X = 1.25  # hard gate: stream rounds/s over unary rounds/s
# convergence-parity bar, the exact gate style of the compression PR
# (tests/test_compress.py::_assert_within_2pct / docs/COMPRESSION.md):
# final train loss within 2% relative of the default path, with a 0.02
# absolute floor — near a zero hinge loss the relative bound is
# ill-defined, and 0.02 is 2% of the loss at w = 0
PARITY_REL = 1.02
PARITY_ABS = 0.02

_COUNTERS = (
    "master.sync.rounds",
    "master.sync.bcast.bytes",
    "master.sync.bcast.full",
    "master.sync.bcast.delta",
    "master.sync.bcast.cached",
    "master.sync.bcast.stale",
    "master.sync.grad.bytes",
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
    from distributed_sgd_tpu.models.linear import make_model

    data = rcv1_like(cfg["n"], n_features=cfg["n_features"], nnz=cfg["nnz"],
                     seed=7, idf_values=True)
    train, test = train_test_split(data)
    ds = dim_sparsity(train)
    make = lambda: make_model("hinge", 1e-5, train.n_features, dim_sparsity=ds)
    return train, test, make


def _run(train, test, make_model_fn, cfg: dict, *, delta: bool, k: int) -> dict:
    """One fit_sync on a fresh 2-worker loopback cluster; returns the
    counter deltas, per-epoch rates, wall time, and final state."""
    from distributed_sgd_tpu.core.cluster import DevCluster

    before = _snapshot()
    t0 = time.perf_counter()
    with DevCluster(make_model_fn(), train, test, n_workers=N_WORKERS,
                    seed=0) as c:
        res = c.master.fit_sync(
            max_epochs=cfg["epochs"], batch_size=cfg["batch"],
            learning_rate=cfg["lr"], local_steps=k, delta_broadcast=delta,
        )
    wall_s = time.perf_counter() - t0
    after = _snapshot()
    d = {name: after[name] - before[name] for name in _COUNTERS}
    epochs = max(1, res.epochs_run)
    return {
        "counters": d,
        "rounds_per_epoch": d["master.sync.rounds"] / epochs,
        "bcast_bytes_per_epoch": d["master.sync.bcast.bytes"] / epochs,
        "grad_bytes_per_epoch": d["master.sync.grad.bytes"] / epochs,
        "final_loss": float(res.losses[-1]),
        "final_test_loss": float(res.test_losses[-1]),
        "weights": np.asarray(res.state.weights),
        "wall_s": wall_s,
    }


def _stream_run(train, test, make_model_fn, cfg: dict, epochs: int, *,
                stream: bool):
    """One small-batch fit on a fresh 2-worker cluster with kernels
    prewarmed (the round floor under test is the TRANSPORT, not XLA
    compile); returns (rounds/s, final weights, final loss, stream
    counters delta)."""
    import numpy as np

    from distributed_sgd_tpu.core.cluster import DevCluster
    from distributed_sgd_tpu.utils import metrics as mm

    g = mm.global_metrics()
    names = ("master.sync.rounds", "master.sync.stream.sends",
             "master.sync.stream.opened", "master.sync.stream.broken",
             "master.sync.stream.fallback")
    before = {n: g.counter(n).value for n in names}
    with DevCluster(make_model_fn(), train, test, n_workers=N_WORKERS,
                    seed=0) as c:
        zeros = np.zeros(train.n_features, dtype=np.float32)
        warm = np.arange(cfg["batch"], dtype=np.int64)
        for w in c.workers:
            w.compute_gradient(zeros, warm)
        # the master's per-epoch eval jit compiles on first use — warm it
        # OUTSIDE the timed window so a 2-epoch run isn't half compile
        c.master.local_loss(zeros)
        c.master.local_loss(zeros, test=True)
        t0 = time.perf_counter()
        res = c.master.fit_sync(
            max_epochs=epochs, batch_size=cfg["batch"],
            learning_rate=cfg["lr"], stream=stream)
        wall = time.perf_counter() - t0
    d = {n: g.counter(n).value - before[n] for n in names}
    return (d["master.sync.rounds"] / wall, np.asarray(res.state.weights),
            float(res.losses[-1]), d)


def stream_rows(smoke: bool) -> dict:
    """Interleaved stream-vs-unary rounds/s at the RPC-bound shape; hard
    asserts (both modes): >= STREAM_SPEEDUP_X throughput and weight drift
    exactly 0.0 (smoke additionally asserts losses to 1e-6 and zero
    stream-instrument movement on the knobs-off runs)."""
    label = "smoke" if smoke else "full"
    cfg = STREAM_SHAPE
    epochs = STREAM_EPOCHS[label]
    reps = STREAM_REPS[label]
    log(f"stream transport rows ({label}): n={cfg['n']} "
        f"dim={cfg['n_features']} batch={cfg['batch']} epochs={epochs} "
        f"reps={reps} workers={N_WORKERS} (RPC-bound shape)")
    train, test, make = _build(dict(cfg, epochs=epochs))
    best_u = best_s = 0.0
    w_u = w_s = None
    loss_u = loss_s = None
    unary_counters = {}
    stream_counters = {}
    for rep in range(reps):  # interleaved: noise hits both transports
        ru, w_u, loss_u, du = _stream_run(train, test, make, cfg, epochs,
                                          stream=False)
        rs, w_s, loss_s, ds = _stream_run(train, test, make, cfg, epochs,
                                          stream=True)
        for k_, v in du.items():
            unary_counters[k_] = unary_counters.get(k_, 0) + v
        stream_counters = ds
        best_u, best_s = max(best_u, ru), max(best_s, rs)
        log(f"  rep {rep}: unary {ru:.0f} rounds/s, stream {rs:.0f} rounds/s")
    import numpy as np

    drift = float(np.max(np.abs(w_u - w_s)))
    speedup = best_s / max(1e-9, best_u)
    log(f"stream transport: unary {best_u:.0f} vs stream {best_s:.0f} "
        f"rounds/s = {speedup:.2f}x (bar >= {STREAM_SPEEDUP_X}x); "
        f"weight drift {drift}; loss {loss_u:.6f} vs {loss_s:.6f}; "
        f"sends={stream_counters['master.sync.stream.sends']} "
        f"broken={stream_counters['master.sync.stream.broken']} "
        f"fallback={stream_counters['master.sync.stream.fallback']}")
    assert drift == 0.0, (
        f"stream transport drifted the weights by {drift} — the framed "
        f"messages are the unary messages and decode is send-ordered, so "
        f"the math must be bit-identical")
    assert speedup >= STREAM_SPEEDUP_X, (
        f"stream transport {speedup:.2f}x not >= {STREAM_SPEEDUP_X}x over "
        f"unary at the RPC-bound shape ({best_s:.0f} vs {best_u:.0f} "
        f"rounds/s)")
    if smoke:
        assert abs(loss_s - loss_u) <= 1e-6, (
            f"stream loss {loss_s} != unary loss {loss_u} at 1e-6")
        # knobs-off identity, the counter half (the wire-byte half lives
        # in tests/test_stream.py): unary fits never touch a stream
        for name in ("master.sync.stream.sends",
                     "master.sync.stream.opened"):
            assert unary_counters[name] == 0, (
                f"knobs-off run moved {name} (= {unary_counters[name]})")
        assert stream_counters["master.sync.stream.sends"] > 0
    return {
        "unary_rounds_per_s": round(best_u, 1),
        "stream_rounds_per_s": round(best_s, 1),
        "stream_speedup_x": round(speedup, 2),
        "stream_loss_drift": drift,
        "stream_final_loss_info": round(loss_s, 6),
        "stream_sends": stream_counters["master.sync.stream.sends"],
        "stream_broken": stream_counters["master.sync.stream.broken"],
        "stream_fallbacks": stream_counters["master.sync.stream.fallback"],
        "stream_batch": cfg["batch"],
        "stream_epochs": epochs,
    }


def run_bench(smoke: bool = False) -> dict:
    cfg = SMOKE if smoke else FULL
    label = "smoke" if smoke else "full"
    log(f"rpc sync bench ({label}): n={cfg['n']} dim={cfg['n_features']} "
        f"nnz={cfg['nnz']} batch={cfg['batch']} epochs={cfg['epochs']} "
        f"workers={N_WORKERS} K={K}")
    train, test, make = _build(cfg)

    dense = _run(train, test, make, cfg, delta=False, k=1)
    log(f"default : rounds/epoch={dense['rounds_per_epoch']:.0f} "
        f"bcast={dense['bcast_bytes_per_epoch']/1e3:.1f} KB/epoch "
        f"test_loss={dense['final_test_loss']:.6f} ({dense['wall_s']:.1f}s)")

    delta_k1 = _run(train, test, make, cfg, delta=True, k=1)
    drift = float(np.max(np.abs(delta_k1["weights"] - dense["weights"])))
    log(f"delta_k1: bcast={delta_k1['bcast_bytes_per_epoch']/1e3:.1f} KB/epoch "
        f"max|w - w_dense|={drift:.2e} (transport must be exact)")
    if smoke:
        # CI gate: the versioned sparse transport reconstructs the dense
        # path's weights exactly (absolute-value deltas; observed drift 0)
        assert drift <= 1e-6, (
            f"delta-broadcast weights drifted {drift} from the dense path "
            f"at K=1 — the versioned transport must be exact")
        per_round = delta_k1["counters"]["master.sync.bcast.bytes"] / max(
            1, delta_k1["counters"]["master.sync.rounds"])
        log(f"smoke: delta-path broadcast bytes/round = {per_round:.0f} "
            f"(dense path: "
            f"{dense['counters']['master.sync.bcast.bytes'] / max(1, dense['counters']['master.sync.rounds']):.0f})")

    piped = _run(train, test, make, cfg, delta=True, k=K)
    log(f"pipelined (K={K}): rounds/epoch={piped['rounds_per_epoch']:.0f} "
        f"bcast={piped['bcast_bytes_per_epoch']/1e3:.1f} KB/epoch "
        f"test_loss={piped['final_test_loss']:.6f} ({piped['wall_s']:.1f}s)")

    bcast_reduction = (dense["bcast_bytes_per_epoch"]
                       / max(1.0, piped["bcast_bytes_per_epoch"]))
    rounds_reduction = (dense["rounds_per_epoch"]
                        / max(1.0, piped["rounds_per_epoch"]))
    parity_bound = max(PARITY_REL * dense["final_loss"],
                       dense["final_loss"] + PARITY_ABS)
    parity_ok = piped["final_loss"] <= parity_bound
    if smoke:
        # CI gate: K-step windows must not break convergence
        assert parity_ok, (
            f"pipelined final loss {piped['final_loss']:.6f} exceeds the "
            f"parity bound {parity_bound:.6f} (default "
            f"{dense['final_loss']:.6f})")
    stream = stream_rows(smoke)

    sends = piped["counters"]
    hits = (sends["master.sync.bcast.delta"]
            + sends["master.sync.bcast.cached"])
    total_sends = hits + sends["master.sync.bcast.full"]
    log(f"reductions: bcast bytes {bcast_reduction:.1f}x, rounds "
        f"{rounds_reduction:.1f}x; delta-hit-rate {hits}/{total_sends}; "
        f"loss parity {'OK' if parity_ok else 'FAIL'} "
        f"({piped['final_loss']:.6f} vs bound {parity_bound:.6f}; "
        f"bar: >=5x bytes, >=4x rounds, loss <= max(1.02*base, base+0.02))")

    return {
        "metric": f"rpc_sync_pipeline_{label}",
        # headline, gated: the pipelined path's broadcast bytes must never
        # silently regress (direction: *_bytes gates lower-is-better)
        "value": round(piped["bcast_bytes_per_epoch"], 1),
        "unit": "bytes/epoch",
        "pipelined_bcast_bytes": round(piped["bcast_bytes_per_epoch"], 1),
        "pipelined_grad_bytes": round(piped["grad_bytes_per_epoch"], 1),
        "default_bcast_bytes": round(dense["bcast_bytes_per_epoch"], 1),
        "delta_k1_bcast_bytes": round(delta_k1["bcast_bytes_per_epoch"], 1),
        "bcast_reduction_x": round(bcast_reduction, 2),
        "rounds_reduction_x": round(rounds_reduction, 2),
        "rounds_per_epoch_default": dense["rounds_per_epoch"],
        "rounds_per_epoch_pipelined": piped["rounds_per_epoch"],
        "delta_hit_sends": hits,
        "full_sends": sends["master.sync.bcast.full"],
        "delta_k1_max_drift": drift,
        "final_loss": round(piped["final_loss"], 6),
        "default_final_loss_info": round(dense["final_loss"], 6),
        "test_loss_info": round(piped["final_test_loss"], 6),
        "default_test_loss_info": round(dense["final_test_loss"], 6),
        "loss_parity_ok": int(parity_ok),
        "loss_parity_bound_info": round(parity_bound, 6),
        "local_steps": K,
        "n_workers": N_WORKERS,
        **stream,
        **{k_: v for k_, v in cfg.items()},
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
