"""O(N) master plane CI wiring (ISSUE 15, docs/SCALING.md): the scale and
soak smoke gates run inside the tier-1 wall budget, and the wheel-based
liveness plane keeps its per-worker latency promise.

The full-size siblings (`python bench.py --scale` / `--soak`) sweep to 64
workers and soak 24 for minutes; these smokes keep the same hard asserts
(>= 1.5x at the gate N with drift 0.0; zero evictions + O(delta) reloads
+ loss parity under churned weather) at CI shapes.
"""

import threading
import time

from distributed_sgd_tpu.core.cluster import DevCluster
from distributed_sgd_tpu.data.rcv1 import dim_sparsity, train_test_split
from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import make_model


def test_scale_smoke_bench_end_to_end():
    """`bench.py --scale --smoke`'s deterministic gates: weight drift
    exactly 0.0 at every swept N and the knobs-off stage plane untouched
    (hard-asserted inside run_bench).  The >= 1.5x rounds/s bar over the
    serialized master is a CPU wall-clock ratio: `bench.py --scale` holds
    it by hand, here it is a reading."""
    from benches.bench_scale import run_bench

    r = run_bench(smoke=True)  # raises on any gate failure
    for key in list(r):
        if key.endswith("_drift"):
            assert r[key] == 0.0
        if key.endswith("_scale_eff"):
            assert r[key] > 0.0


def test_soak_smoke_bench_end_to_end():
    """`bench.py --soak --smoke` is the CI autoscale-soak gate: chaos
    weather + a leave/join churn cycle over host-local workers with the
    whole O(N) plane on — zero live-worker evictions, O(delta)-bounded
    reload rows, convergence parity (all hard-asserted inside
    run_bench)."""
    from benches.bench_soak import run_bench

    r = run_bench(smoke=True)  # raises on any gate failure
    assert r["zero_evictions"] == 1
    assert r["completed"] == 1
    assert r["delta_ok"] == 1
    assert r["loss_parity_ok"] == 1
    assert r["churn_events"] == 2


def test_wedged_peer_does_not_stretch_a_dead_peers_eviction():
    """The O(1)-latency liveness promise (docs/SCALING.md): one WEDGED
    worker (Ping served, but only after a long stall) must not delay a
    DEAD worker's eviction — per-worker wheel entries probe and settle
    independently, where the old sweep awaited every probe before any
    next cycle."""
    train, test = train_test_split(
        rcv1_like(160, n_features=64, nnz=8, seed=9, idf_values=True))
    ds = dim_sparsity(train)
    model = make_model("hinge", 1e-5, train.n_features, dim_sparsity=ds)
    with DevCluster(model, train, test, n_workers=3,
                    heartbeat_s=0.2, heartbeat_max_misses=3) as c:
        # worker 1 is WEDGED: the master's probes against it hang until
        # far past the test horizon (its stub is proxied below — a
        # deterministic stand-in for a SIGSTOPped peer).  Worker 2 is
        # DEAD: its server hard-stops, so probes fail instantly.  The
        # dead one must evict on its own miss budget regardless.
        wedged = c.workers[1]
        m = c.master
        dead = c.workers[2]
        dead_key = (dead.host, dead.port)
        wedged_key = (wedged.host, wedged.port)
        real_stub = m._workers[wedged_key]

        class _SlowPing:
            """Stub proxy whose Ping.future resolves only after 5 s —
            a peer slower than the whole test horizon."""

            def __init__(self, stub):
                self._stub = stub

            def __getattr__(self, name):
                return getattr(self._stub, name)

            @property
            def Ping(self):  # noqa: N802 - stub surface
                outer = self

                class _Method:
                    def future(self, req, timeout=None):
                        fut = _NeverFut()
                        return fut

                    def __call__(self, req, timeout=None):
                        return outer._stub.Ping(req, timeout=timeout)

                return _Method()

        class _NeverFut:
            """A probe future that never settles before its deadline —
            the master's per-probe timeout is what must bound it."""

            def __init__(self):
                self._cbs = []
                self._timer = threading.Timer(5.0, self._fire)
                self._timer.daemon = True
                self._timer.start()

            def _fire(self):
                for cb in self._cbs:
                    cb(self)

            def add_done_callback(self, cb):
                self._cbs.append(cb)

            def result(self):
                raise RuntimeError("still pending")

            def done(self):
                return False

        with m._members_lock:
            m._workers[wedged_key] = _SlowPing(real_stub)
        # hard-kill worker 2's server so its probes fail instantly
        dead.server.stop(grace=0)
        dead._master_channel.close()
        t0 = time.monotonic()
        deadline = t0 + 20.0
        while time.monotonic() < deadline:
            with m._members_lock:
                if dead_key not in m._workers:
                    break
            time.sleep(0.05)
        took = time.monotonic() - t0
        with m._members_lock:
            assert dead_key not in m._workers, (
                "dead worker never evicted while a slow peer was probed")
            # the wedged-but-alive peer is NOT evicted by slowness alone
            # within this horizon: each stalled probe costs one timeout,
            # and three must accumulate
            assert wedged_key in m._workers or took > 0.6
            m._workers[wedged_key] = real_stub
        # the dead peer's eviction landed within its own miss budget
        # (3 misses x ~0.2 s cadence + slack), NOT the wedged peer's
        # stall horizon
        assert took < 10.0, (
            f"eviction took {took:.1f}s — the wedged peer stretched the "
            f"liveness cycle")
        c.workers.remove(dead)
