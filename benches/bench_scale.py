"""Master-plane scaling gate: rounds/s vs worker count, serialized vs O(N)
(docs/SCALING.md).

The reference master fans out one request per worker per round and pays a
serial per-worker cost at EVERY master-side stage — sample draw, request
build, send, reply decode — so rounds/s degrades linearly as N grows even
when the per-worker compute shrinks to keep the global batch fixed.  PR 12
removed the per-call RPC floor (DSGD_STREAM); this bench gates the rest of
the O(N) master plane (ISSUE 15): sharded fan-in decode lanes
(DSGD_FANIN_LANES) + pooled dispatch staging (DSGD_STAGE_POOL) on top of
the streams, against the fully serialized knobs-off master.

Sweep: N in {4, 16, 32, 64} in-process loopback workers (real gRPC, one
DevCluster per N) at a FIXED GLOBAL BATCH — per-worker batch = global/N,
so rounds/epoch is constant across N and a throughput change isolates the
master's per-round cost, not the workload.  Per N, `reps` interleaved
(serialized, scaled) fit pairs on the same warm cluster; rows record
best-of-reps, the gate ratio is the best PAIRED per-rep ratio (each
pair runs back to back, so the ratio cancels the slow load drift a
shared box adds across the sweep — a regressed plane fails every pair;
if the gate N still lands under the bar it is re-measured ONCE on a
fresh cluster and must clear the same bar on its own).

Gates (hard asserts, smoke and full):

- scaled rounds/s >= 1.5x serialized rounds/s at N=32 (a wall-clock
  ratio: held by `main`, the `bench.py --scale` path, with the tree's
  >= 2x bar; `run_bench` returns both as readings);
- weight drift exactly 0.0 between the two configs at EVERY swept N (the
  lanes keep one send-ordered f32 accumulation chain; the stager replays
  the serial sample stream; streams are bit-identical since PR 12);
- knobs-off staging counters stay zero (the serialized fits must never
  touch the stage plane).

Reported through benches/regress.py: `*_rounds_per_s` rows gate UP per N,
`*_scale_eff` rows (rounds/s at N normalized to the smallest swept N,
higher is better — how flat the master's per-round cost stays) gate UP
through the new scale_eff metric class.

Aggregation-tree rows (ISSUE 17, docs/AGGREGATION.md): on top of the
scaled master, `DSGD_AGG_TREE=fanout:8` elects sub-aggregator reduce
nodes so the master fans in F subtree sums instead of N payloads.  Per
tree-swept N the bench reports `n{N}_tree_rounds_per_s` (+ `_scale_eff`)
against the SAME scaled master, asserts two tree fits land on
byte-identical weights (the canonical-order reduce chain leaves no
nondeterminism — "drift 0.0"), and asserts tree-vs-scaled LOSS parity
(the subtree sums reassociate f32 addition, so weights match to
tolerance, not bit-exactly).  The >= 2x tree gate at N=64 is enforced
only on multi-core hosts: the tree's win is moving fan-in decode work
OFF the master onto concurrently-running workers, and a single-core
box has nowhere to move it (every worker shares the master's CPU), so
there the rows are recorded as history and the gate logs itself
skipped instead of manufacturing a number.

Chaos row: one tree fit with an elected aggregator HARD-KILLED mid-fit
— its children degrade to direct-to-master replies for the affected
rounds (flat fallback), the master evicts the corpse and rebuilds the
plan on the same hook as the resplit, zero LIVE workers are evicted,
and the fit completes every epoch.

Shard-sweep rows (ISSUE 18, docs/MASTER_SHARDING.md): on the flat
knobs-off master, `DSGD_MASTER_SHARDS=M` range-partitions the weight
vector across M shard lanes so each lane broadcasts and fans in only
its dim/M slice.  Per (M, N) in {1,2,4} x the shard sweep the bench
asserts sharded-vs-flat weights BIT-identical (range-disjoint SGD
commutes — drift 0.0, not allclose) and records
`m{M}_n{N}_proc_bytes`, the max-over-lanes broadcast+fan-in wire bytes
one shard process carries (gated DOWN through the bytes class), plus
`m{M}_n{N}_bytes_reduction` vs the flat single-process total (gated UP
through the bytes_reduction class).  The hard gate is >= 1.5x
bytes-per-process reduction at M=4/N=32 — a BYTES gate, not wall-clock:
on a one-box loopback wire the win is capacity (what one master process
must push/decode per round), which is exactly what bytes measure and
scheduler noise cannot fake.  The shard chaos row HARD-KILLS one shard
lane mid-fit: exactly one flat single-master fallback round absorbs the
loss, the plan rebuilds at M-1 on the advance hook, ZERO live workers
are evicted, the fit completes every epoch, and the final weights still
match the flat run bit for bit.  The shard rows are recorded as their
OWN history series (`scale_shard_{smoke,full}`, split_shard_series):
they are deterministic bytes, and welding them to the wall-clock series
would let a slow box day block recording them.

Run: ``python bench.py --scale [--smoke]``.  One JSON line on stdout;
diagnostics on stderr.  The chaos-weather endurance sibling is
``python bench.py --soak`` (benches/bench_soak.py).
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
import re
import sys
import time

import numpy as np

LANES = 4
POOL = 4
SPEEDUP_GATE_N = 32
SPEEDUP_GATE_X = 1.5
# aggregation-tree plane (ISSUE 17): fanout 8 keeps the master's payload
# fan-in at <= 8 subtree sums whatever N; the 2x bar vs the scaled
# master applies at N=64 (multi-core hosts only — see module docstring)
TREE_FANOUT = 8
TREE_GATE_N = 64
TREE_GATE_X = 2.0
# tree-vs-scaled loss parity band (f32 reassociation of subtree sums):
# same shape as bench_chaos/bench_soak's in-run parity bound
PARITY_REL = 1.02
PARITY_ABS = 0.02
# feature-sharded master plane (ISSUE 18): shard counts swept per N, and
# the >= 1.5x bytes-per-process reduction bar at M=4/N=32 (bytes, not
# wall-clock — see module docstring)
SHARD_M = (1, 2, 4)
SHARD_GATE_M = 4
SHARD_GATE_N = 32
SHARD_GATE_X = 1.5

SMOKE = dict(
    n=1280, n_features=512, nnz=8, global_batch=128, epochs=5, lr=0.5,
    sweep=(4, 32), tree=(32,), reps=4,
    chaos_n=12, chaos_epochs=3,
    shard_n=(8, 32), shard_epochs=2,
)
FULL = dict(
    n=1280, n_features=512, nnz=8, global_batch=128, epochs=8, lr=0.5,
    sweep=(4, 16, 32, 64), tree=(16, 32, 64, 128), reps=3,
    chaos_n=12, chaos_epochs=4,
    shard_n=(8, 32), shard_epochs=4,
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _build(cfg: dict):
    from distributed_sgd_tpu.data.rcv1 import dim_sparsity, train_test_split
    from distributed_sgd_tpu.data.synthetic import rcv1_like

    data = rcv1_like(cfg["n"], n_features=cfg["n_features"], nnz=cfg["nnz"],
                     seed=15, idf_values=True)
    train, test = train_test_split(data)
    ds = dim_sparsity(train)

    def make():
        from distributed_sgd_tpu.models.linear import make_model

        return make_model("hinge", 1e-5, train.n_features, dim_sparsity=ds)

    return train, test, make


def _fit(cluster, cfg: dict, batch: int, scaled: bool, tree: bool = False):
    """One timed fit; returns (rounds_per_s, weights, loss, stage_hits,
    rounds, wall).  `tree` rides the scaled knobs + DSGD_AGG_TREE."""
    from distributed_sgd_tpu.utils import metrics as mm

    g = mm.global_metrics()
    r0 = g.counter(mm.SYNC_ROUNDS).value
    h0 = g.counter(mm.STAGE_HITS).value
    t0 = time.perf_counter()
    res = cluster.master.fit_sync(
        max_epochs=cfg["epochs"], batch_size=batch,
        learning_rate=cfg["lr"], grad_timeout_s=30.0,
        stream=scaled, fanin_lanes=LANES if scaled else 0,
        stage_pool=POOL if scaled else 0,
        agg_tree=f"fanout:{TREE_FANOUT}" if tree else "",
    )
    wall = time.perf_counter() - t0
    rounds = g.counter(mm.SYNC_ROUNDS).value - r0
    hits = g.counter(mm.STAGE_HITS).value - h0
    return (rounds / wall, np.asarray(res.state.weights),
            float(res.losses[-1]), hits, rounds, wall)


# per-N config matrix: which fits run at each sweep point.  "tree" is
# scaled + DSGD_AGG_TREE; "serial" is the fully knobs-off master
_CONFIGS = (("serial", False, False), ("scaled", True, False),
            ("tree", True, True))


def _sweep_point(train, test, make, cfg: dict, n_workers: int,
                 configs=("serial", "scaled")) -> dict:
    """One N: fresh cluster, prewarm, `reps` interleaved config tuples."""
    from distributed_sgd_tpu.core.cluster import DevCluster

    batch = cfg["global_batch"] // n_workers
    assert batch >= 1, "sweep exceeds the global batch"
    # one shared CPU device for every worker: this bench isolates the
    # MASTER plane's per-round cost, and the tier-1 harness's 8-virtual-
    # device mesh (tests/conftest.py XLA flag) would otherwise spread the
    # workers over 8 device contexts whose extra executor threads eat the
    # very idle gaps the stage pool overlaps into — the standalone and
    # under-pytest measurements must agree
    import jax

    device = [jax.devices()[0]]
    t_up = time.perf_counter()
    with DevCluster(make(), train, test, n_workers=n_workers, seed=0,
                    devices=device) as c:
        up_s = time.perf_counter() - t_up
        # prewarm every worker's jitted gradient at its batch bucket and
        # the master's eval binding: the timed fits must measure the
        # master plane, not XLA compile latency
        zeros = np.zeros(train.n_features, dtype=np.float32)
        warm_ids = np.arange(batch, dtype=np.int64)
        for w in c.workers:
            w.compute_gradient(zeros, warm_ids)
        c.master.local_loss(zeros)
        best = {name: 0.0 for name in configs}
        rep_rps = {name: [] for name in configs}
        weights, losses = {}, {}
        hits = 0
        # the serialized-vs-scaled pairs run FIRST and alone, exactly as
        # before the tree rows existed: the 1.5x lanes gate is a paired
        # measurement, and interleaving tree fits into it perturbs the
        # very serial/scaled contrast it gates.  Tree reps follow on the
        # same warm cluster against the already-measured scaled best.
        for phase in (("serial", "scaled"), ("tree",)):
            for rep in range(cfg["reps"]):
                for name, scaled, tree in _CONFIGS:
                    if name not in configs or name not in phase:
                        continue
                    rps, w_fit, loss, h, rounds, wall = _fit(
                        c, cfg, batch, scaled, tree)
                    best[name] = max(best[name], rps)
                    rep_rps[name].append(rps)
                    losses.setdefault(name, loss)
                    if name == "tree" and "tree" in weights:
                        # two tree fits over the same membership run the
                        # same plan and the same canonical-order reduce
                        # chains: byte-identical or the tree is
                        # nondeterministic
                        assert np.array_equal(weights["tree"], w_fit), (
                            f"tree fit drifted across reps at "
                            f"N={n_workers} — the canonical-order reduce "
                            f"must be bit-exact")
                    weights.setdefault(name, w_fit)
                    if scaled:
                        hits += h
                    else:
                        assert h == 0, (
                            "a knobs-off fit touched the stage plane "
                            f"({h} stage hits at N={n_workers})")
                    log(f"  N={n_workers:3d} {name:6s} rep {rep}: "
                        f"{rps:7.1f} rounds/s ({rounds} rounds / "
                        f"{wall:.2f}s)")
    drift = 0.0
    if "serial" in weights and "scaled" in weights:
        drift = float(np.max(np.abs(weights["scaled"] - weights["serial"])))
        assert drift == 0.0, (
            f"scaled weights drifted from the serialized master at "
            f"N={n_workers} (max |dw| = {drift:g}) — the O(N) plane must "
            f"be bit-exact")
    tree_rps = tree_speedup = 0.0
    if "tree" in weights:
        # subtree sums reassociate the f32 mean, so the tree run parities
        # the scaled run on LOSS, not on weight bits
        bound = max(PARITY_REL * losses["scaled"],
                    losses["scaled"] + PARITY_ABS)
        assert losses["tree"] <= bound, (
            f"tree loss {losses['tree']:.4f} outside the parity band "
            f"{bound:.4f} at N={n_workers} (scaled {losses['scaled']:.4f})")
        tree_rps = best["tree"]
        tree_speedup = tree_rps / best["scaled"] if best["scaled"] else 0.0
    assert hits > 0, (
        f"the scaled fits at N={n_workers} never dispatched a pre-staged "
        f"draw — the stage plane is not engaged")
    # the gate's speedup is the best PAIRED per-rep ratio, not
    # best-of/best-of: each serial/scaled pair ran back to back on the
    # same warm cluster, so the ratio within a pair cancels the slow
    # load drift a shared box adds across the sweep (composing the max
    # scaled rep with the max serial rep from different time windows
    # punishes the plane for the box getting faster mid-measurement).
    # A regressed plane fails EVERY pair; rows still record best-of rps.
    speedup = 0.0
    if rep_rps.get("serial"):
        speedup = max(s / f for s, f in
                      zip(rep_rps["scaled"], rep_rps["serial"]))
    log(f"  N={n_workers:3d}: " + " vs ".join(
        f"{name} {best[name]:.1f}" for name in configs)
        + f" rounds/s (drift {drift}, cluster up in {up_s:.1f}s)")
    return {"n": n_workers, "serial_rps": best.get("serial", 0.0),
            "scaled_rps": best.get("scaled", 0.0), "speedup": speedup,
            "tree_rps": tree_rps, "tree_speedup": tree_speedup,
            "drift": drift, "configs": configs}


def _chaos_row(train, test, make, cfg: dict) -> dict:
    """Kill an elected aggregator mid-tree-fit: its children degrade to
    direct-to-master replies (flat fallback) for the affected rounds,
    the master evicts the corpse and REBUILDS the plan on the resplit
    hook, no live worker is evicted, and the fit completes."""
    import threading

    from distributed_sgd_tpu.aggtree import build_plan
    from distributed_sgd_tpu.core.cluster import DevCluster
    from distributed_sgd_tpu.utils import metrics as mm
    import jax

    n = cfg["chaos_n"]
    batch = max(1, cfg["global_batch"] // n)
    g = mm.global_metrics()
    # gate on the CHILD-side fallback counter: the dead parent fails its
    # own reply in the same window, so the master retries and discards
    # the replies that carried agg_flat — master.tree.flat_fallback only
    # counts flat payloads that reach a COMPLETED round (quorum rounds),
    # which a kill-then-evict round never is
    flat0 = g.counter(mm.AGG_FLAT).value
    rebuilds0 = g.counter(mm.TREE_REBUILDS).value
    with DevCluster(make(), train, test, n_workers=n, seed=0,
                    devices=[jax.devices()[0]]) as c:
        keys = [k for k, _ in c.master._members()]
        plan = build_plan(keys, TREE_FANOUT, seed=c.master.seed)
        victim_key = plan.aggregators()[0]
        victim = next(w for w in c.workers
                      if (w.host, w.port) == victim_key)
        r0 = g.counter(mm.SYNC_ROUNDS).value
        box = {}

        def run():
            try:
                box["res"] = c.master.fit_sync(
                    max_epochs=cfg["chaos_epochs"], batch_size=batch,
                    learning_rate=cfg["lr"], grad_timeout_s=5.0,
                    stream=True, fanin_lanes=LANES, stage_pool=POOL,
                    agg_tree=f"fanout:{TREE_FANOUT}")
            except Exception as e:  # noqa: BLE001 - surfaced below
                box["exc"] = e

        t = threading.Thread(target=run, daemon=True)
        t.start()
        t_end = time.monotonic() + 60
        while (g.counter(mm.SYNC_ROUNDS).value < r0 + 2
               and time.monotonic() < t_end and t.is_alive()):
            time.sleep(0.05)
        # hard kill: server torn down, no unregister — a crash, not a leave
        victim._stopped.set()
        victim.server.stop(grace=0)
        log(f"  chaos: killed aggregator {victim_key[0]}:{victim_key[1]} "
            f"mid-fit (N={n}, fanout={TREE_FANOUT})")
        t.join(timeout=300)
        assert not t.is_alive(), "chaos tree fit hung after aggregator kill"
        assert "exc" not in box, f"chaos tree fit raised: {box['exc']}"
        res = box["res"]
        assert res.epochs_run == cfg["chaos_epochs"]
        # the corpse was evicted; every LIVE worker kept its membership
        assert victim_key not in c.master._workers
        live_lost = [
            (w.host, w.port) for w in c.workers
            if w is not victim and (w.host, w.port) not in c.master._workers]
        assert not live_lost, f"live workers evicted under chaos: {live_lost}"
    flats = g.counter(mm.AGG_FLAT).value - flat0
    rebuilds = g.counter(mm.TREE_REBUILDS).value - rebuilds0
    # the intentional eviction dumps the flight ring at cwd by design —
    # don't leave this run's dump behind as repo litter (gitignored, but
    # tests/test_aggtree.py guards the tree stays clean)
    for litter in glob.glob(f"flight-*-{os.getpid()}-eviction.json"):
        with contextlib.suppress(OSError):
            os.remove(litter)
    assert flats > 0, (
        "no child ever degraded to the flat fallback — the kill missed "
        "the tree")
    assert rebuilds >= 1, "the aggregator eviction never rebuilt the plan"
    log(f"  chaos: {flats} flat-fallback replies, {rebuilds} rebuild(s), "
        f"0 live evictions, {res.epochs_run} epochs")
    return {"chaos_flat_fallbacks": int(flats),
            "chaos_rebuilds": int(rebuilds),
            "chaos_live_evictions": 0,
            "chaos_final_loss_info": round(float(res.losses[-1]), 5)}


def _shard_point(train, test, make, cfg: dict, n_workers: int) -> dict:
    """One shard-sweep N: flat baseline then M in SHARD_M on the same
    warm cluster — bit-identity asserted, per-process wire bytes
    recorded (max over lanes vs the flat single-process total)."""
    from distributed_sgd_tpu.core.cluster import DevCluster
    from distributed_sgd_tpu.utils import metrics as mm
    import jax

    batch = max(1, cfg["global_batch"] // n_workers)
    g = mm.global_metrics()
    rows = {}
    with DevCluster(make(), train, test, n_workers=n_workers, seed=0,
                    devices=[jax.devices()[0]]) as c:
        zeros = np.zeros(train.n_features, dtype=np.float32)
        warm_ids = np.arange(batch, dtype=np.int64)
        for w in c.workers:
            w.compute_gradient(zeros, warm_ids)
        c.master.local_loss(zeros)
        b0 = g.counter(mm.SYNC_BCAST_BYTES).value
        r0 = g.counter(mm.SYNC_GRAD_BYTES).value
        flat = c.master.fit_sync(
            max_epochs=cfg["shard_epochs"], batch_size=batch,
            learning_rate=cfg["lr"], grad_timeout_s=30.0)
        # the flat master is ONE process: its per-process wire cost is
        # the whole broadcast + fan-in ledger
        flat_bytes = (g.counter(mm.SYNC_BCAST_BYTES).value - b0
                      + g.counter(mm.SYNC_GRAD_BYTES).value - r0)
        w_flat = np.asarray(flat.state.weights)
        rows[f"n{n_workers}_flat_proc_bytes"] = int(flat_bytes)
        log(f"  N={n_workers:3d} flat : {flat_bytes:9d} bytes/process")
        for m in SHARD_M:
            res = c.master.fit_sync(
                max_epochs=cfg["shard_epochs"], batch_size=batch,
                learning_rate=cfg["lr"], grad_timeout_s=30.0,
                master_shards=m)
            assert np.array_equal(np.asarray(res.state.weights), w_flat), (
                f"M={m} sharded weights drifted from the flat master at "
                f"N={n_workers} — range-disjoint SGD must be bit-exact")
            ledger = c.master._last_shard_bytes
            assert ledger and len(ledger) == min(m, train.n_features), (
                f"shard ledger missing at M={m}, N={n_workers}")
            per_proc = max(b + gr for _, b, gr in ledger)
            reduction = flat_bytes / per_proc
            rows[f"m{m}_n{n_workers}_proc_bytes"] = int(per_proc)
            rows[f"m{m}_n{n_workers}_bytes_reduction"] = round(reduction, 3)
            log(f"  N={n_workers:3d} M={m}  : {per_proc:9d} bytes/process "
                f"({reduction:.2f}x reduction, drift 0.0)")
    return rows


def _shard_chaos_row(train, test, make, cfg: dict) -> dict:
    """Kill one shard lane mid-fit: the next window runs ONE flat
    single-master fallback round, the plan rebuilds at M-1 on the
    advance hook, zero workers are evicted, the fit completes every
    epoch, and the weights still match the flat run bit for bit."""
    import threading

    from distributed_sgd_tpu.core.cluster import DevCluster
    from distributed_sgd_tpu.utils import metrics as mm
    import jax

    n = cfg["chaos_n"]
    m = SHARD_GATE_M
    batch = max(1, cfg["global_batch"] // n)
    g = mm.global_metrics()
    fb0 = g.counter(mm.SHARD_FALLBACK_ROUNDS).value
    rb0 = g.counter(mm.SHARD_REBUILDS).value
    with DevCluster(make(), train, test, n_workers=n, seed=0,
                    devices=[jax.devices()[0]]) as c:
        zeros = np.zeros(train.n_features, dtype=np.float32)
        for w in c.workers:
            w.compute_gradient(zeros, np.arange(batch, dtype=np.int64))
        flat = c.master.fit_sync(
            max_epochs=cfg["chaos_epochs"], batch_size=batch,
            learning_rate=cfg["lr"], grad_timeout_s=30.0)
        box = {}

        def run():
            try:
                box["res"] = c.master.fit_sync(
                    max_epochs=cfg["chaos_epochs"], batch_size=batch,
                    learning_rate=cfg["lr"], grad_timeout_s=30.0,
                    master_shards=m)
            except Exception as e:  # noqa: BLE001 - surfaced below
                box["exc"] = e

        r0 = g.counter(mm.SYNC_ROUNDS).value
        t = threading.Thread(target=run, daemon=True)
        t.start()
        t_end = time.monotonic() + 60
        while (g.counter(mm.SYNC_ROUNDS).value < r0 + 2
               and time.monotonic() < t_end and t.is_alive()):
            time.sleep(0.02)
        assert t.is_alive(), "sharded chaos fit finished before the kill"
        c.master.kill_shard(1)
        log(f"  shard chaos: killed shard lane 1 mid-fit (M={m}, N={n})")
        t.join(timeout=300)
        assert not t.is_alive(), "sharded fit hung after shard kill"
        assert "exc" not in box, f"sharded chaos fit raised: {box['exc']}"
        res = box["res"]
        assert res.epochs_run == cfg["chaos_epochs"]
        # zero evictions: a master-shard death is a MASTER-side failure
        # and must never cost a worker its membership
        lost = [(w.host, w.port) for w in c.workers
                if (w.host, w.port) not in c.master._workers]
        assert not lost, f"live workers evicted under shard chaos: {lost}"
        assert np.array_equal(np.asarray(res.state.weights),
                              np.asarray(flat.state.weights)), (
            "shard-kill chaos run drifted from the flat master")
    fallbacks = g.counter(mm.SHARD_FALLBACK_ROUNDS).value - fb0
    rebuilds = g.counter(mm.SHARD_REBUILDS).value - rb0
    # the kill dumps the flight ring at cwd by design — same litter
    # discipline as the tree chaos row above
    for litter in glob.glob(f"flight-*-{os.getpid()}-shard-kill.json"):
        with contextlib.suppress(OSError):
            os.remove(litter)
    assert fallbacks == 1, (
        f"a shard kill must cost EXACTLY one flat fallback round, "
        f"got {fallbacks}")
    assert rebuilds == 1, (
        f"the kill must rebuild the shard plan exactly once, got {rebuilds}")
    log(f"  shard chaos: {fallbacks} flat fallback round, {rebuilds} "
        f"rebuild, 0 evictions, {res.epochs_run} epochs, drift 0.0")
    return {"shard_chaos_fallback_rounds": int(fallbacks),
            "shard_chaos_rebuilds": int(rebuilds),
            "shard_chaos_live_evictions": 0,
            "shard_chaos_final_loss_info": round(float(res.losses[-1]), 5)}


def run_bench(smoke: bool = False) -> dict:
    cfg = SMOKE if smoke else FULL
    label = "smoke" if smoke else "full"
    tree_ns = set(cfg["tree"])
    all_ns = sorted(set(cfg["sweep"]) | tree_ns)
    log(f"scale bench ({label}): n={cfg['n']} dim={cfg['n_features']} "
        f"global_batch={cfg['global_batch']} epochs={cfg['epochs']} "
        f"sweep={tuple(all_ns)} tree={cfg['tree']} lanes={LANES} "
        f"pool={POOL} fanout={TREE_FANOUT} shards={SHARD_M} "
        f"x N={cfg['shard_n']}")
    train, test, make = _build(cfg)
    points = []
    for n in all_ns:
        configs = []
        if n in cfg["sweep"]:
            configs += ["serial", "scaled"]
        if n in tree_ns:
            # tree-only points (e.g. N=128) still need the scaled
            # baseline on the same cluster for an honest speedup row
            configs += ["scaled", "tree"]
        configs = tuple(dict.fromkeys(configs))
        points.append(_sweep_point(train, test, make, cfg, n, configs))
    by_n = {p["n"]: p for p in points}
    base_n = min(cfg["sweep"])
    gate_n = SPEEDUP_GATE_N if SPEEDUP_GATE_N in by_n else max(cfg["sweep"])
    gate = by_n[gate_n]
    if gate["speedup"] < SPEEDUP_GATE_X:
        # best-of-reps ratios sit within scheduler noise of the bar on a
        # loaded 1-core box (observed 1.48-1.63x across identical code).
        # ONE re-measure on a fresh cluster — the fresh point is the one
        # `main` holds to the bar, so a real regression still fails twice
        log(f"gate: {gate['speedup']:.2f}x at N={gate_n} below the "
            f"{SPEEDUP_GATE_X}x bar — re-measuring once on a fresh cluster")
        gate = _sweep_point(train, test, make, cfg, gate_n,
                            ("serial", "scaled"))
        gate["tree_rps"] = by_n[gate_n]["tree_rps"]
        gate["tree_speedup"] = by_n[gate_n]["tree_speedup"]
        gate["configs"] = by_n[gate_n]["configs"]
        by_n[gate_n] = gate
        points = [gate if p["n"] == gate_n else p for p in points]
    log(f"gate: {gate['speedup']:.2f}x at N={gate_n} "
        f"(bar >= {SPEEDUP_GATE_X}x), drift 0.0 at every N")
    # the tree's reading: against the scaled master at N=64 (or the
    # largest tree point the sweep has)
    tree_gate_n = (TREE_GATE_N if TREE_GATE_N in tree_ns
                   else max(tree_ns))
    tgate = by_n[tree_gate_n]
    log(f"tree gate: {tgate['tree_speedup']:.2f}x vs scaled at "
        f"N={tree_gate_n} (bar >= {TREE_GATE_X}x on multi-core)")
    chaos = _chaos_row(train, test, make, cfg)
    # feature-sharded master plane: bytes-per-process sweep + chaos row
    shard_rows = {}
    for n in cfg["shard_n"]:
        shard_rows.update(_shard_point(train, test, make, cfg, n))
    shard_gate = shard_rows[
        f"m{SHARD_GATE_M}_n{SHARD_GATE_N}_bytes_reduction"]
    log(f"shard gate: {shard_gate:.2f}x bytes-per-process reduction at "
        f"M={SHARD_GATE_M}/N={SHARD_GATE_N} (bar >= {SHARD_GATE_X}x, "
        f"drift 0.0 at every M x N)")
    assert shard_gate >= SHARD_GATE_X, (
        f"sharded master {shard_gate:.2f}x bytes-per-process reduction at "
        f"M={SHARD_GATE_M}/N={SHARD_GATE_N} — below the >= {SHARD_GATE_X}x "
        f"bar over the flat master")
    shard_chaos = _shard_chaos_row(train, test, make, cfg)

    result = {
        "metric": f"scale_{label}",
        # headline, gated lower-is-better: seconds per round of the scaled
        # master at the gate point (1 / rounds_per_s keeps the `value`
        # convention meaningful)
        "value": round(1.0 / gate["scaled_rps"], 5),
        "unit": "s/round",
        "speedup_gate_n": gate_n,
        "speedup_gate_info": round(gate["speedup"], 3),
        "tree_gate_n": tree_gate_n,
        "tree_gate_info": round(tgate["tree_speedup"], 3),
        "tree_fanout": TREE_FANOUT,
        "global_batch": cfg["global_batch"],
        "lanes": LANES,
        "pool": POOL,
        "shard_gate_m": SHARD_GATE_M,
        "shard_gate_n": SHARD_GATE_N,
        "shard_bytes_reduction": round(shard_gate, 3),
    }
    result.update(chaos)
    result.update(shard_rows)
    result.update(shard_chaos)
    tree_base = min(tree_ns)
    for p in points:
        n = p["n"]
        if "serial" in p["configs"]:
            result[f"n{n}_serial_rounds_per_s"] = round(p["serial_rps"], 1)
            result[f"n{n}_speedup_info"] = round(p["speedup"], 3)
        result[f"n{n}_scaled_rounds_per_s"] = round(p["scaled_rps"], 1)
        if n in cfg["sweep"]:
            # scaling efficiency: how flat the scaled master's rounds/s
            # stays as N grows (1.0 = perfectly flat); gated UP via the
            # regress scale_eff class — a collapse means a stage went
            # serial-in-N
            result[f"n{n}_scale_eff"] = round(
                p["scaled_rps"] / by_n[base_n]["scaled_rps"], 4)
        result[f"n{n}_drift"] = p["drift"]
        if "tree" in p["configs"]:
            result[f"n{n}_tree_rounds_per_s"] = round(p["tree_rps"], 1)
            result[f"n{n}_tree_speedup_info"] = round(p["tree_speedup"], 3)
            result[f"n{n}_tree_scale_eff"] = round(
                p["tree_rps"] / by_n[tree_base]["tree_rps"], 4)
    return result


# shard-sweep row names: the m{M}_n{N}_* matrix, the flat per-process
# baselines they divide by, and the shard_* gate/chaos summaries
_SHARD_ROW = re.compile(r"^(m\d+_n\d+_|n\d+_flat_proc_bytes$|shard_)")


def split_shard_series(result: dict) -> tuple:
    """Partition run_bench's combined rows into (timing series, shard series).

    The shard rows are shape-determined bytes (10% regress class) while
    the rest of the sweep is wall-clock on a shared box (35% class, and
    still noisy at that).  Recorded as ONE series, a slow box day blocks
    recording the deterministic capacity rows — so the shard sweep gets
    its own `"metric"` series (`scale_shard_{smoke,full}`), gated and
    appended independently, per regress.py's series-independence rule
    ("one series' value never pollutes another's median").  The stdout
    contract is untouched: main() still prints the combined dict.
    """
    shard = {k: v for k, v in result.items() if _SHARD_ROW.match(k)}
    timing = {k: v for k, v in result.items() if k not in shard}
    if shard:
        shard = {
            "metric": result["metric"].replace("scale_", "scale_shard_"),
            # headline, gated lower-is-better: wire bytes the worst shard
            # process carries at the gate point (deterministic)
            "value": shard[f"m{SHARD_GATE_M}_n{SHARD_GATE_N}_proc_bytes"],
            "unit": "bytes",
            **shard,
        }
    return timing, shard


def main(smoke: bool = False) -> None:
    result = run_bench(smoke=smoke)
    # The wall-clock bars, held here and not in `run_bench`: a ratio of CPU
    # times is a reading of the box it ran on, which tier-1 (six xdist
    # workers sharing the cores) only records.
    speedup, gate_n = result["speedup_gate_info"], result["speedup_gate_n"]
    assert speedup >= SPEEDUP_GATE_X, (
        f"scaled master {speedup:.2f}x at N={gate_n} — below the "
        f">= {SPEEDUP_GATE_X}x bar over the serialized master")
    # tree gate, multi-core hosts only: the tree moves fan-in work OFF the
    # master onto concurrently-running reduce nodes, and with one core
    # there is nowhere to move it — there the rows are recorded (history
    # catches a collapse) and the bar is logged as skipped, not faked
    if (os.cpu_count() or 1) > 1:
        tree, tree_n = result["tree_gate_info"], result["tree_gate_n"]
        assert tree >= TREE_GATE_X, (
            f"aggregation tree {tree:.2f}x at N={tree_n} — below the "
            f">= {TREE_GATE_X}x bar over the scaled master")
    else:
        log("tree gate SKIPPED: single-core host (workers and master "
            "share one CPU, so off-master reduce cannot speed the round)")
    try:
        from benches import regress

        history = regress.load_history()
        timing, shard = split_shard_series(result)
        regressions = []
        for series in (timing, shard):
            if not series:
                continue
            regs, lines = regress.check(series, history)
            regressions += regs
            log(f"regression gate [{series['metric']}] vs stored history, "
                f"tolerance {regress.DEFAULT_TOLERANCE:.0%}:")
            for ln in lines:
                log(ln)
            if regs:
                log(f"FAIL [{series['metric']}]: regressed metrics: "
                    f"{', '.join(regs)} (series NOT recorded)")
            else:
                regress.record(series)
                log(f"PASS [{series['metric']}]: series appended to "
                    f"benches/history.json")
        result["regressed"] = regressions
    except Exception as e:  # noqa: BLE001 - gating must not break the bench
        log(f"regression gate skipped: {e}")
        result["regressed"] = None
        result["gate_error"] = str(e)
    print(json.dumps(result))


if __name__ == "__main__":
    from distributed_sgd_tpu import compile_cache

    compile_cache.place()
    main(smoke="--smoke" in sys.argv)
