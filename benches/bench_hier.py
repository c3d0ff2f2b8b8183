"""Hierarchical multi-host gate (docs/HIERARCHY.md, ROADMAP item 1).

Three claims, measured on the 8-virtual-CPU-device harness (the same
device emulation the MULTICHIP dryruns and tier-1 tests run):

1. **Knobs-off identity** — DSGD_HOST_DEVICES=1 (default) builds no
   in-host mesh, registers with the pre-hierarchy Node wire (no
   `devices` field serialized), and leaves the master's split exactly
   `vanilla_split`.  Hard-asserted every run.
2. **Convergence parity at equal global batch** — a hierarchical fit
   (H hosts x D devices, per-host batch B*W/H) reaches the flat RPC
   topology's (W single-device workers, batch B) final loss within the
   compression PR's parity gate (<= max(1.02 * flat, flat + 0.02),
   docs/COMPRESSION.md).  Reference semantics average per-WORKER
   gradient sums, so consolidating W workers into H hosts at equal
   global batch scales the per-round update by W/H — the hierarchical
   run uses lr * H/W to keep the update identical in expectation
   (docs/HIERARCHY.md "choosing lr").
3. **>= 2x per-round throughput at equal device count** — the gated
   configuration is 2 hosts x 4 devices vs 8 workers x 1 (the dryrun's
   hierarchical topology) at equal global batch: half the weight
   broadcasts, half the gRPC replies, half the fan-in decodes per
   round, one in-host psum replacing four gRPC repliers per host.
   4 hosts x 2 devices is measured and reported alongside (ungated:
   with only 2 gRPC calls saved per round, the shared per-round floor —
   master apply, draw, dispatch — caps its loopback ratio below the
   2x bar that the 2x4 shape clears; on a real network, where the
   per-worker RPC cost dominates that floor, both shapes gain more).

Per-round time is the master's `master.sync.batch.duration` histogram
over whole fits (best-of-reps minimum — loopback on a shared host is
noisy upward, never downward), so per-epoch eval and cluster setup are
excluded from the round metric while staying inside the honest fits.

Wall times are emitted as ``*_info`` fields (ungated in
benches/regress.py — loopback wall clock on a shared host would
false-alarm at any tolerance worth having); the hard asserts above are
the real gate, and the deterministic ``hier_loss`` gates against
history at the 2% loss-class band.

Run: ``python bench.py --hier [--smoke]``.  Prints exactly ONE JSON
line on stdout; diagnostics go to stderr.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

# equal global batch everywhere: flat W=8 workers at B, hier H hosts at
# B*W/H, lr scaled by H/W (see module docstring / docs/HIERARCHY.md)
N_DEVICES = 8
GLOBAL_BATCH = 200
# smoke keeps the FULL corpus shape: the RPC-plane share (where the
# hierarchical win lives) is set by dim and rounds-per-fit, and shrinking
# either turns real signal into boundary noise — smoke trims reps/epochs
FULL = dict(n=8000, n_features=47_236, nnz=76, epochs=3, reps=4, lr=0.5)
SMOKE = dict(n=8000, n_features=47_236, nnz=76, epochs=2, reps=3, lr=0.5)
MIN_SPEEDUP = 2.0  # the ISSUE bar, gated on the 2-host x 4-device shape
PARITY_REL = 1.02  # docs/COMPRESSION.md convergence-parity gate
PARITY_ABS = 0.02


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _ensure_devices(n: int) -> None:
    """An n-device virtual CPU mesh (this bench never runs on the chip):
    the env knobs are read at backend creation, so set them first; if the
    process already initialized a smaller backend, rebuild it through the
    config API."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < n:
        from jax.extend import backend as _jex_backend

        _jex_backend.clear_backends()
        jax.config.update("jax_num_cpu_devices", n)
    assert len(jax.devices()) >= n, (
        f"need {n} devices, found {len(jax.devices())} — run under "
        f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")


def _build(cfg: dict):
    import jax.numpy as jnp

    from distributed_sgd_tpu.data.synthetic import rcv1_like
    from distributed_sgd_tpu.models.linear import SparseSVM

    full = rcv1_like(cfg["n"], n_features=cfg["n_features"], nnz=cfg["nnz"],
                     seed=0, idf_values=True)
    test = full.slice(slice(0, max(200, cfg["n"] // 10)))
    ds = np.full(cfg["n_features"], 0.01, np.float32)

    def make_model():
        return SparseSVM(lam=1e-5, n_features=cfg["n_features"],
                         dim_sparsity=jnp.asarray(ds))

    return full, test, make_model


class _TimedCluster:
    """One topology under measurement: a live DevCluster whose fits are
    interleaved with the other topologies' (a shared-host slow phase
    hits every config instead of biasing one).  Per-round time reads the
    master.sync.batch.duration histogram, so per-epoch eval and cluster
    setup stay out of the round metric; the reported number is the
    MINIMUM over reps (loopback on a shared host is noisy upward, never
    downward)."""

    def __init__(self, train, test, make_model, n_workers, host_devices,
                 batch, lr, host_local=False):
        from distributed_sgd_tpu.core.cluster import DevCluster

        self.cluster = DevCluster(
            make_model(), train, test, n_workers=n_workers, seed=0,
            host_devices=host_devices,
            host_local=host_local and host_devices > 1)
        self.batch, self.lr = batch, lr
        self.loss = None
        self.best_round_s = float("inf")

    def warm(self, epochs: int) -> None:
        """Compile fit; its final loss is the parity sample."""
        res = self.cluster.master.fit_sync(
            max_epochs=epochs, batch_size=self.batch, learning_rate=self.lr)
        self.loss = res.losses[-1]

    def rep(self, epochs: int) -> float:
        h = self.cluster.master.metrics.histogram(
            "master.sync.batch.duration")
        c0, s0 = h.count, h.sum
        self.cluster.master.fit_sync(
            max_epochs=epochs, batch_size=self.batch, learning_rate=self.lr)
        r = (h.sum - s0) / (h.count - c0)
        self.best_round_s = min(self.best_round_s, r)
        return r

    def close(self) -> None:
        self.cluster.stop()


def _assert_knobs_off(train, test, make_model):
    """DSGD_HOST_DEVICES=1 (default) must be the pre-hierarchy engine:
    no in-host mesh, no Node.devices on the wire, vanilla split."""
    from distributed_sgd_tpu.core.cluster import DevCluster
    from distributed_sgd_tpu.core.split import vanilla_split
    from distributed_sgd_tpu.rpc import dsgd_pb2 as pb

    with DevCluster(make_model(), train, test, n_workers=2, seed=0) as c:
        assert all(w._hier is None for w in c.workers), (
            "a default worker built an in-host mesh")
        assert all(w._data_offset is None for w in c.workers)
        assert not c.master._worker_devices, (
            f"flat workers registered host shapes: "
            f"{c.master._worker_devices}")
        members = c.master._members()
        got = c.master._split_parts(vanilla_split, members)
        want = vanilla_split(len(train), len(members))
        assert all(np.array_equal(a, b) for a, b in zip(got, want)), (
            "knobs-off split diverged from vanilla_split")
    # flat registration wire: byte-identical to the pre-hierarchy Node
    flat = pb.Node(host="w", port=4001)
    assert b"devices" not in flat.SerializeToString() and \
        flat.SerializeToString() == pb.Node(
            host="w", port=4001).SerializeToString()
    assert flat.devices == 0
    log("knobs-off identity: OK (no mesh, no Node.devices, vanilla split)")


def run_bench(smoke: bool = False) -> dict:
    _ensure_devices(N_DEVICES)
    cfg = SMOKE if smoke else FULL
    label = "smoke" if smoke else "full"
    log(f"hierarchical gate ({label}): n={cfg['n']} dim={cfg['n_features']} "
        f"nnz={cfg['nnz']} global_batch={GLOBAL_BATCH} epochs={cfg['epochs']} "
        f"reps={cfg['reps']} on {N_DEVICES} virtual devices")
    train, test, make_model = _build(cfg)

    _assert_knobs_off(train, test, make_model)

    lr = cfg["lr"]
    epochs = cfg["epochs"]
    t0 = time.perf_counter()
    # equal global batch everywhere; hierarchical lr scaled by H/W (see
    # module docstring).  flat = the 1-device-per-worker baseline; 2x4 =
    # the gated hierarchical shape; 4x2 reported alongside.
    configs = [
        ("flat 8x1", N_DEVICES, 1, GLOBAL_BATCH // N_DEVICES, lr),
        ("hier 2x4", 2, N_DEVICES // 2, GLOBAL_BATCH // 2,
         lr * 2 / N_DEVICES),
        ("hier 4x2", 4, N_DEVICES // 4, GLOBAL_BATCH // 4,
         lr * 4 / N_DEVICES),
    ]
    clusters = {}
    try:
        for name, nw, hd, b, clr in configs:
            clusters[name] = _TimedCluster(train, test, make_model, nw, hd,
                                           b, clr, host_local=hd > 1)
            clusters[name].warm(epochs)
            log(f"{name}: warmed (parity loss {clusters[name].loss:.6f}, "
                f"t+{time.perf_counter() - t0:.0f}s)")
        for rep in range(cfg["reps"]):
            for name in clusters:
                r = clusters[name].rep(epochs)
                log(f"rep {rep}: {name} {r * 1e3:.2f} ms/round")
        flat_s = clusters["flat 8x1"].best_round_s
        flat_loss = clusters["flat 8x1"].loss
        h2_s = clusters["hier 2x4"].best_round_s
        h2_loss = clusters["hier 2x4"].loss
        h4_s = clusters["hier 4x2"].best_round_s
        h4_loss = clusters["hier 4x2"].loss
    finally:
        for tc in clusters.values():
            tc.close()

    speedup = flat_s / h2_s
    speedup4 = flat_s / h4_s
    parity_bound = max(PARITY_REL * flat_loss, flat_loss + PARITY_ABS)
    log(f"per-round speedup: 2x4 {speedup:.2f}x (bar >= {MIN_SPEEDUP}x), "
        f"4x2 {speedup4:.2f}x (info); parity: hier {h2_loss:.6f} / "
        f"{h4_loss:.6f} vs bound {parity_bound:.6f}")
    assert h2_loss <= parity_bound and h4_loss <= parity_bound, (
        f"hierarchical fit lost convergence parity: {h2_loss:.6f} / "
        f"{h4_loss:.6f} vs bound {parity_bound:.6f} (flat {flat_loss:.6f})")
    assert speedup >= MIN_SPEEDUP, (
        f"hierarchical 2x{N_DEVICES // 2} per-round speedup {speedup:.2f}x "
        f"under the {MIN_SPEEDUP}x bar (flat {flat_s * 1e3:.2f} ms/round, "
        f"hier {h2_s * 1e3:.2f} ms/round)")

    return {
        "metric": f"hier_rpc_{label}",
        "unit": "x",
        # the headline ratio (plain name: recorded, not direction-gated —
        # the hard assert above is the gate) + deterministic loss series
        "speedup_per_round": round(speedup, 3),
        "speedup_per_round_4x2": round(speedup4, 3),
        "hier_loss": round(h2_loss, 6),
        "hier_4x2_loss_info": round(h4_loss, 6),
        "flat_loss_info": round(flat_loss, 6),
        # loopback wall clock: recorded ungated (*_info)
        "flat_round_ms_info": round(flat_s * 1e3, 3),
        "hier_round_ms_info": round(h2_s * 1e3, 3),
        "hier_4x2_round_ms_info": round(h4_s * 1e3, 3),
        "speedup_bar_info": MIN_SPEEDUP,
        "global_batch": GLOBAL_BATCH,
        "n_devices": N_DEVICES,
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
