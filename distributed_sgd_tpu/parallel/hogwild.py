"""Asynchronous Hogwild SGD with full-mesh delta gossip.

TPU-native re-design of the reference's async mode (core/Slave.scala:79-111
+ core/MasterAsync.scala:32-177).  TPU SPMD is synchronous, so Hogwild's
unsynchronized races cannot live *inside* one compiled program; instead the
asynchrony lives on the host, exactly where the reference keeps it (gRPC
threads), while each worker's compute step is a compiled device function:

- worker i owns a weights replica on its own device and a resident shard
  of the training data (vanilla contiguous assignment, as sent in
  StartAsyncRequest, MasterAsync.scala:52-55);
- its hot loop runs `steps_per_dispatch` (k) local SGD steps in ONE
  compiled program — each step draws a uniform batch from the shard and
  computes ``delta = lr * regularize(mean of backwards)`` ON DEVICE
  (Slave.scala:93-99 — note MEAN here vs the sync mode's SUM) against the
  locally-updated weights — then gossips the SUMMED delta to every peer
  and the master, fire-and-forget (Slave.scala:103-105).  k=1 is the
  reference's per-step gossip; larger k amortizes host dispatch (the
  bottleneck on slow transports) at the cost of gossip staleness bounded
  by k local steps;
- all weight mutations are *delta subtractions* — commutative — so a
  stale-snapshot step composes with concurrent incoming deltas exactly
  like the reference's STM `transform(_ - delta)` (Slave.scala:101,180);
- gossiped deltas cross devices through host memory (the analogue of the
  reference's proto serialization); inboxes are bounded and drop-oldest
  under overload — the reference's fire-and-forget gRPC likewise gives no
  delivery guarantee — with drops counted in metrics;
- the master counts updates, ends at ``maxSteps = n_samples * max_epochs``
  (MasterAsync.scala:83,164-177), and a loss-checker loop evaluates the
  smoothed test loss every `check_every` updates with 2.5 s backoff,
  tracks best weights, and early-stops on the smoothed history
  (MasterAsync.scala:96-162); fit returns the BEST weights, not the last
  (MasterAsync.scala:87-94).

For a fully-compiled on-mesh alternative with the same convergence family
(local SGD + periodic averaging) see parallel/local_sgd.py.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_sgd_tpu.core.early_stopping import Criterion
from distributed_sgd_tpu.core.loss_check import LossChecker, async_fit_result
from distributed_sgd_tpu.core.split import vanilla_split
from distributed_sgd_tpu.core.trainer import FitResult
from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.models.linear import LinearModel, require_single_output
from distributed_sgd_tpu.ops import ftrl
from distributed_sgd_tpu.ops.sparse import SparseBatch
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine
from distributed_sgd_tpu.utils import measure
from distributed_sgd_tpu.utils import metrics as metrics_mod

log = logging.getLogger("dsgd.hogwild")


class _Worker:
    """One async worker: device-resident shard + weights replica + inbox."""

    def __init__(
        self,
        wid: int,
        model: LinearModel,
        shard: Dataset,
        device,
        batch_size: int,
        learning_rate: float,
        seed: int,
        metrics: metrics_mod.Metrics,
        max_inbox: int = 1024,
        steps_per_dispatch: int = 1,
        optimizer=None,
        momentum: float = 0.9,
        compressor=None,
        gossip_topology: str = "all",
    ):
        self.wid = wid
        self.device = device
        self.metrics = metrics
        # sparse gossip topology (parallel/topology.py): which peers this
        # worker's dispatch gossips to.  "all" keeps the reference's full
        # fan-out; ring/random:k select deterministically per (dispatch,
        # wid) — the in-process twin of the RPC workers' selection, so the
        # convergence-parity gate (benches/bench_elastic.py) measures the
        # same edge schedule the wire plane would run.
        from distributed_sgd_tpu.parallel.topology import parse_topology

        self._topo_mode, self._topo_k = parse_topology(gossip_topology)
        self._topo_seed = seed
        self._dispatch_no = 0
        # wire-path gradient compression (compress/): this worker's OWN
        # instance — residuals are per (worker, destination), never shared
        self._compressor = compressor
        self.k = max(1, int(steps_per_dispatch))
        self.inbox: "queue.Queue[np.ndarray]" = queue.Queue(maxsize=max_inbox)
        self._lock = threading.Lock()
        self._running = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._key = jax.random.PRNGKey(seed + 1000 * (wid + 1))
        self._t = 0

        self._idx = jax.device_put(shard.indices, device)
        self._val = jax.device_put(shard.values, device)
        self._y = jax.device_put(shard.labels, device)
        shard_n = len(shard)
        bs = batch_size

        from distributed_sgd_tpu.ops import kernels

        dense = shard.is_dense
        # the one rule on shape and this worker's device (ops/kernels.py)
        kernel = self.kernel = kernels.resolve(
            None, model.n_features, shard.indices.shape[1], device)

        k = self.k

        from distributed_sgd_tpu.parallel.sync import resolve_optimizer

        opt = self._opt = resolve_optimizer(optimizer, learning_rate, momentum)
        self._model = model
        self._opt_state = None  # carried across dispatches (set in start_async)

        def kstep(w, opt_state, idx, val, y, key):
            # k local SGD steps in ONE compiled dispatch (lax.scan), each on
            # the locally-updated weights; returns the SUMMED delta for
            # gossip.  Deltas commute (every mutation is a subtraction,
            # Slave.scala:101,180), so peers merging the sum see exactly the
            # k individual merges; what changes vs k=1 is only *when* they
            # see them — a bounded staleness period of k local steps, the
            # dispatch-amortization knob for slow transports.  On the MXU
            # path weights (and optimizer state) stay in the blocked layout
            # ACROSS the scan — one to/from conversion per dispatch, not per
            # step (the pattern of local_sgd.round_shard).  With a stateful
            # optimizer the state is LOCAL to this worker and persists
            # across dispatches (opt_state threads through the carry); the
            # gossiped quantity stays a weight-space delta, so merges remain
            # the commutative subtractions the algorithm needs.
            w = model.to_layout(w, kernel)

            def body(carry, kk):
                w_t, opt_s, acc = carry
                with jax.named_scope("dsgd.draw"):  # as BoundSync._one_step names it
                    ids = jax.random.randint(kk, (bs,), 0, shard_n)
                    bi = jnp.zeros((bs, 0), jnp.int32) if dense else idx[ids]
                    bv, by = val[ids], y[ids]
                # MEAN (Slave.scala:93-98) + regularize (Slave.scala:99)
                g = model.grad(w_t, SparseBatch(bi, bv), by,
                               kernel=kernel, reduce="mean")
                from distributed_sgd_tpu.parallel.sync import local_update

                w_t, opt_s, delta = local_update(opt, learning_rate, g, w_t, opt_s)
                return (w_t, opt_s, acc + delta), None

            keys = jax.random.split(key, k)
            (_, opt_state, acc), _ = jax.lax.scan(
                body, (w, opt_state, jnp.zeros_like(w)), keys)
            return model.from_layout(acc, kernel), opt_state

        self._step = jax.jit(kstep)
        self._apply = jax.jit(lambda w, d: w - d)
        self.w: Optional[jax.Array] = None
        self._peers: List["_Worker"] = []
        self._master: Optional["HogwildEngine"] = None

    # -- wiring ------------------------------------------------------------
    def connect(self, peers: List["_Worker"], master: "HogwildEngine") -> None:
        self._peers = [p for p in peers if p.wid != self.wid]
        self._master = master

    # -- RPC-equivalent surface (Slave service, proto.proto:37-49) ---------
    def push_delta(self, delta: np.ndarray) -> None:
        """Peer updateGrad (Slave.scala:177-185): fire-and-forget inbox."""
        try:
            self.inbox.put_nowait(delta)
        except queue.Full:
            try:  # drop-oldest under overload; counted, not silent
                self.inbox.get_nowait()
                self.inbox.put_nowait(delta)
            except queue.Empty:
                pass
            self.metrics.counter("slave.async.grad.dropped").increment()

    @property
    def _blocked(self) -> bool:
        """Whether `kernel` keeps w lane-blocked: the name the benchmark's
        Hogwild driver reads (benchmark/drivers/hogwild.py)."""
        from distributed_sgd_tpu.ops import kernels

        return self.kernel in kernels.BLOCKED

    def start_async(self, w0: np.ndarray) -> None:
        """StartAsync RPC (Slave.scala:159-175)."""
        self.w = jax.device_put(jnp.asarray(w0, dtype=jnp.float32), self.device)
        if self._opt is not None:
            # same layout derivation as kstep: the state must mirror the
            # scan carry's structure exactly
            self._opt_state = self._opt.init(
                self._model.to_layout(self.w, self.kernel))
        self._running.set()
        self._thread = threading.Thread(target=self._loop, name=f"hogwild-{self.wid}", daemon=True)
        self._thread.start()

    def stop_async(self) -> None:
        """StopAsync RPC (Slave.scala:187-195)."""
        self._running.clear()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()

    # -- hot loop (Slave.asyncTask, Slave.scala:79-111) --------------------
    def _drain_inbox(self) -> None:
        # deltas commute (w <- w - d, Slave.scala:177-185), so the queued
        # batch sums on host and applies in ONE device dispatch
        acc = None
        n = 0
        while True:
            try:
                d = self.inbox.get_nowait()
            except queue.Empty:
                break
            acc = d if acc is None else acc + d
            n += 1
        if acc is not None:
            with self._lock:
                self.w = self._apply(self.w, jnp.asarray(acc))
            self.metrics.counter("slave.async.grad.update").increment(n)

    def _gossip_peers(self) -> List["_Worker"]:
        """This dispatch's destinations under the configured topology; the
        'all' path returns the connected list untouched (byte-identical
        default)."""
        if self._topo_mode == "all" or not self._peers:
            return self._peers
        from distributed_sgd_tpu.parallel.topology import select_gossip_peers

        by_wid = {p.wid: p for p in self._peers}
        sel, _ = select_gossip_peers(
            self._topo_mode, self._topo_k, list(by_wid), self.wid,
            self._dispatch_no, seed=self._topo_seed)
        return [by_wid[w] for w in sel]

    def _loop(self) -> None:
        node = f"w{self.wid}"
        while self._running.is_set():
            # one span per iteration (its histogram's max is what an
            # operator reads after a stall; its count equals the dispatches)
            # and one per phase, all under the iteration's dispatch number
            with measure.span("slave.async.iteration", metrics=self.metrics,
                              node=node, worker=self.wid,
                              dispatch=self._dispatch_no):
                self._iteration()

    def _iteration(self) -> None:
        span = measure.span
        # phases of the iteration's span: no histogram, no trace of their own
        phase = {"histogram": False, "root": False, "worker": self.wid,
                 "dispatch": self._dispatch_no}
        with span("slave.async.drain", **phase):
            self._drain_inbox()
        with span("slave.async.step", **phase):
            self._key, k = jax.random.split(self._key)
            snapshot = self.w  # stale-read is the algorithm (Hogwild)
            delta, self._opt_state = self._step(
                snapshot, self._opt_state, self._idx, self._val, self._y, k)
        with span("slave.async.apply", **phase):
            with self._lock:
                self.w = self._apply(self.w, delta)
        self.metrics.counter("slave.async.batch").increment(self.k)
        with span("slave.async.pull", **phase):  # the wait for the device
            delta_np = np.asarray(delta)  # host hop = the wire serialization
        self._dispatch_no += 1
        with span("slave.async.push", **phase):
            self._push(delta_np)
        self._t += self.k

    def _push(self, delta_np: np.ndarray) -> None:
        peers = self._gossip_peers()
        if self._compressor is None:
            for peer in peers:
                peer.push_delta(delta_np)
            if self._master is not None:
                self._master._update_grad(delta_np, n_steps=self.k)
        else:
            # the in-process engine models the wire faithfully: each
            # destination receives the DECODED lossy delta its own
            # encode would have produced (per-dest EF residuals), and
            # the real proto message is built so comms.* accounting
            # measures actual serialized bytes.  Local weights above
            # already absorbed the full delta; what a destination
            # doesn't get now, its residual ships later — merges stay
            # the commutative subtractions Hogwild needs.
            from distributed_sgd_tpu.rpc import codec as _codec  # cached after first loop

            for peer in peers:
                msg = self._compressor.compress(
                    delta_np, dest=("peer", peer.wid))
                peer.push_delta(_codec.decode_grad(msg))
            if self._master is not None:
                msg = self._compressor.compress(delta_np, dest="master")
                self._master._update_grad(
                    _codec.decode_grad(msg), n_steps=self.k)


class HogwildEngine:
    """Coordinator: spawns workers, counts updates, checks smoothed loss."""

    def __init__(
        self,
        model: LinearModel,
        n_workers: int,
        batch_size: int,
        learning_rate: float,
        check_every: int = 100,
        leaky_loss: float = 0.9,
        backoff_s: float = 2.5,
        devices=None,
        seed: int = 0,
        metrics: Optional[metrics_mod.Metrics] = None,
        steps_per_dispatch: int = 1,
        checkpointer=None,
        optimizer=None,
        momentum: float = 0.9,
        compress: str = "none",
        compress_k: float = 0.01,
        compress_ef: bool = True,
        gossip_topology: str = "all",
    ):
        require_single_output(model, 'HogwildEngine')
        ftrl.refuse(optimizer, 'HogwildEngine')
        """steps_per_dispatch=k amortizes host dispatch: each worker runs k
        local SGD steps in one compiled program and gossips the summed
        delta every k steps.  k=1 is the reference's per-step gossip
        (Slave.scala:103-105); larger k trades gossip freshness (staleness
        bounded by k local steps) for k× fewer host hops — the difference
        that matters when host dispatch, not the device, paces the loop.

        `optimizer` (None/'sgd' | 'momentum' | 'adam' | optax transform)
        shapes each worker's LOCAL steps; state never travels — the wire
        still carries weight-space deltas, so peer merges stay commutative.

        `compress`/`compress_k`/`compress_ef` (DSGD_COMPRESS*) put the
        delta gossip through the compress/ wire codecs: each worker gets
        its own compressor with per-destination error-feedback residuals,
        and every destination receives the decoded lossy delta its encode
        produced — the in-process analogue of the RPC topology's
        compressed UpdateGrad stream (docs/COMPRESSION.md).

        `gossip_topology` (DSGD_GOSSIP_TOPOLOGY, docs/ELASTICITY.md):
        all (default, the reference's full fan-out) | ring | random:k —
        sparse peer selection per dispatch, deterministic per (dispatch,
        wid); the coordinator always receives every delta regardless."""
        if not (0.0 <= leaky_loss <= 1.0):
            raise ValueError("leaking coefficient must be between 0 and 1")
        if steps_per_dispatch < 1:
            raise ValueError("steps_per_dispatch must be >= 1")
        from distributed_sgd_tpu.parallel.topology import parse_topology

        parse_topology(gossip_topology)  # fail typos at construction
        self.gossip_topology = gossip_topology
        self.model = model
        self.n_workers = n_workers
        self.batch_size = batch_size
        self.learning_rate = learning_rate
        self.check_every = check_every
        self.leaky_loss = leaky_loss
        self.backoff_s = backoff_s
        self.steps_per_dispatch = int(steps_per_dispatch)
        self.checkpointer = checkpointer  # persists best weights (LossChecker)
        self.optimizer = optimizer
        self.momentum = momentum
        self.compress = compress
        self.compress_k = compress_k
        self.compress_ef = compress_ef
        self.seed = seed
        self.metrics = metrics or metrics_mod.global_metrics()
        devs = list(devices if devices is not None else jax.devices())
        # round-robin device assignment; >1 worker may share a chip
        self.devices = [devs[i % len(devs)] for i in range(n_workers)]

        self._lock = threading.Lock()
        self._updates = 0
        self._w_master: Optional[jax.Array] = None
        self._apply = jax.jit(lambda w, d: w - d)
        self._stop = threading.Event()
        self._max_steps = 0
        self._workers: List[_Worker] = []  # live during fit (watchdog + tests)

    # master updateGrad RPC (MasterAsync.scala:164-177); one gossip message
    # carries n_steps local steps, and maxSteps counts local steps
    def _update_grad(self, delta: np.ndarray, n_steps: int = 1) -> None:
        with self._lock:
            self._w_master = self._apply(self._w_master, jnp.asarray(delta))
            self._updates += n_steps
            updates = self._updates
        if updates % 1000 < max(1, n_steps):  # crossing check: strides of k
            log.info("%d updates received", updates)
        if updates >= self._max_steps:
            self._stop.set()

    def fit(
        self,
        train: Dataset,
        test: Dataset,
        max_epochs: int,
        criterion: Optional[Criterion] = None,
        initial_weights: Optional[np.ndarray] = None,
        stall_timeout_s: float = 60.0,
        max_restarts: int = 2,
        startup_grace_s: Optional[float] = None,
    ) -> FitResult:
        """`stall_timeout_s` arms the watchdog: when no update arrives for
        that long, dead worker threads (a crashed `_loop`) get their
        StartAsync re-issued with the CURRENT weights — up to `max_restarts`
        times each — so the lifetime budget completes on the survivors; a
        stall with nobody restartable and nobody alive raises RuntimeError
        instead of spinning forever (the reference's MasterAsync would spin:
        it counts updates blindly, MasterAsync.scala:164-177).  Before the
        FIRST update the window is `startup_grace_s` (default
        max(stall_timeout_s, 180)): the first dispatch legitimately
        produces nothing while XLA compiles the k-step program, and a
        misfired restart would recompile and make the stall worse."""
        n = len(train)
        w0 = (
            np.zeros(self.model.n_features, dtype=np.float32)
            if initial_weights is None
            else np.asarray(initial_weights, dtype=np.float32)
        )
        # the checker restores any prior snapshot, including the lifetime
        # update count: maxSteps is a LIFETIME budget (MasterAsync.scala:83),
        # so a resumed fit seeds its counter and spends only the remainder
        checker = LossChecker(self.leaky_loss, criterion, checkpointer=self.checkpointer)
        t_start = time.time()
        self._w_master = jnp.asarray(w0)
        self._updates = checker.restored_updates
        self._max_steps = n * max_epochs  # MasterAsync.scala:83
        self._stop.clear()
        if self._updates >= self._max_steps:
            log.info(
                "resumed past the %d-step budget (%d updates done): nothing to run",
                self._max_steps, self._updates)
            return async_fit_result(
                checker, w0, t_start, self._updates, self.batch_size, n)

        # contiguous shard assignment, as the reference's vanilla split
        splits = vanilla_split(n, self.n_workers)
        from distributed_sgd_tpu.compress import make_compressor

        workers = [
            _Worker(
                i,
                self.model,
                train.slice(splits[i]),
                self.devices[i],
                self.batch_size,
                self.learning_rate,
                self.seed,
                self.metrics,
                steps_per_dispatch=self.steps_per_dispatch,
                optimizer=self.optimizer,
                momentum=self.momentum,
                compressor=make_compressor(
                    self.compress, k=self.compress_k,
                    error_feedback=self.compress_ef, seed=self.seed + i,
                    metrics=self.metrics),
                gossip_topology=self.gossip_topology,
            )
            for i in range(self.n_workers)
        ]
        for w in workers:
            w.connect(workers, self)
        self._workers = workers
        log.info("hogwild kernel=%s, workers on %s",
                 "/".join(sorted({"blocked-onehot" if w.kernel == "mxu" else w.kernel
                                  for w in workers})),
                 " ".join(str(w.device) for w in workers))

        # master-local test eval (the loss checker's localLoss equivalent)
        eval_bound = SyncEngine(self.model, make_mesh(1), self.batch_size, 0.0).bind(test)

        for w in workers:
            w.start_async(w0)

        last_step = self._updates - self.check_every  # first check runs immediately
        if startup_grace_s is None:
            startup_grace_s = max(stall_timeout_s, 180.0)
        restarts = {w.wid: 0 for w in workers}
        start_updates = self._updates
        last_progress = self._updates
        last_progress_t = time.monotonic()
        interventions = 0
        try:
            while not self._stop.is_set():
                with self._lock:
                    updates = self._updates
                    w_now = self._w_master
                window = (startup_grace_s if updates == start_updates
                          else stall_timeout_s)
                if updates > last_progress:
                    last_progress, last_progress_t = updates, time.monotonic()
                    interventions = 0
                elif time.monotonic() - last_progress_t > window:
                    interventions += 1
                    dead = [w for w in workers
                            if w._thread is None or not w._thread.is_alive()]
                    alive = [w for w in workers if w not in dead]
                    restartable = [w for w in dead
                                   if restarts[w.wid] < max_restarts]
                    if not alive and not restartable:
                        raise RuntimeError(
                            f"hogwild fit stalled: no live workers and no "
                            f"restarts left (budget {updates}/{self._max_steps})")
                    if restartable:
                        for w in restartable:
                            restarts[w.wid] += 1
                            log.warning(
                                "watchdog: worker %d dead; re-issuing "
                                "StartAsync with current weights (restart "
                                "%d/%d)", w.wid, restarts[w.wid], max_restarts)
                            w.start_async(np.asarray(w_now))
                        interventions = 0  # a restart earns a fresh window
                    elif interventions > 3:
                        # nothing restartable and still no progress: without
                        # this cap a mix of restart-exhausted dead workers
                        # and live-but-stalled ones would intervene forever,
                        # the exact spin this watchdog exists to prevent
                        raise RuntimeError(
                            f"hogwild fit stalled after {interventions - 1} "
                            f"quiet windows ({len(alive)} live worker(s), "
                            f"{len(dead)} dead, budget "
                            f"{updates}/{self._max_steps})")
                    last_progress_t = time.monotonic()
                if updates - last_step < self.check_every:
                    self._stop.wait(self.backoff_s)
                    continue
                # the loss check shares the chip with the workers
                with measure.span("master.async.check", metrics=self.metrics,
                                  node="master", updates=updates):
                    raw_loss, raw_acc = eval_bound.evaluate(w_now)
                    stop = checker.check(raw_loss, raw_acc, w_now, step=updates)
                # counter with the reference's toLong truncation quirk
                # (MasterAsync.scala:126) + a real-valued histogram for
                # dashboards (int() flatlines any loss < 1)
                self.metrics.counter("master.async.loss").increment(int(checker.smoothed[0]))
                self.metrics.histogram("master.async.loss.value").record(checker.smoothed[0])
                log.info(
                    "loss computed at %d updates: test_loss=%.6f test_acc=%.4f",
                    updates, checker.smoothed[0], checker.smoothed_accs[0],
                )
                last_step = updates
                if stop:
                    log.info("converged to target: stopping computation")
                    self._stop.set()
        finally:
            for w in workers:
                w.stop_async()
            for w in workers:
                w.join()
            # release the device-resident shards/replicas: an engine held
            # alive after fit must not pin n_workers dataset copies
            self._workers = []

        # return BEST weights (MasterAsync.scala:87-94)
        return async_fit_result(
            checker, w0, t_start, self._updates, self.batch_size, n)
