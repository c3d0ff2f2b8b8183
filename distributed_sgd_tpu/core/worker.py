"""Worker node: gRPC server + device-resident data + compiled kernels.

TPU-native re-design of the reference's Slave (core/Slave.scala): the
process boundary, registration retry, peer bookkeeping, and the async
gossip loop survive as host-side control plane, while every computation a
slave performs — per-sample forward (Slave.scala:129-140), batch gradient
sum + regularize (Slave.scala:142-157), and the Hogwild local step
(Slave.scala:79-111) — runs as a jitted XLA program on this worker's
device over a device-resident copy of the training data.

Variable-length RPC sample lists are padded to power-of-two buckets with
zeroed feature values (a zero row contributes zero gradient in every
model), so each bucket size compiles exactly once.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, NamedTuple, Optional, Tuple

import grpc
import jax
import jax.numpy as jnp
import numpy as np

from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.models.linear import LinearModel, require_single_output
from distributed_sgd_tpu.ops import ftrl
from distributed_sgd_tpu.ops.sparse import SparseBatch
from distributed_sgd_tpu.rpc import codec, dsgd_pb2 as pb
from distributed_sgd_tpu.rpc.service import (
    GossipSender,
    MasterStub,
    RpcPolicy,
    WorkerStub,
    add_worker_servicer,
    new_channel,
    new_server,
)
from distributed_sgd_tpu import trace as trace_mod
from distributed_sgd_tpu.trace import flight
from distributed_sgd_tpu.utils import measure
from distributed_sgd_tpu.utils import metrics as metrics_mod
from distributed_sgd_tpu.utils.log import node_logger

# registration timing now lives in RpcPolicy (rpc/service.py): the policy
# defaults keep the reference's 5 s call deadline (Slave.scala:48) and 2 s
# initial retry delay (Slave.scala:56), growing with jittered exponential
# backoff to a ~30 s cap instead of a fixed 2 s sleep forever


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


class _Resident(NamedTuple):
    """One consistent snapshot of the worker's resident data slice.

    All five fields swap together (a single attribute assignment, atomic
    under the GIL) when an elastic reload re-shards the slice
    (``ensure_rows``), so a dispatch that grabbed the snapshot before the
    swap computes entirely on the OLD slice with the OLD offset — never a
    mix.  ``host`` keeps the host-side arrays only when a RowReader makes
    incremental reloads possible (the overlap rows a reload reuses)."""

    offset: Optional[int]  # global row id of local row 0 (None = full corpus)
    n: int  # resident rows
    idx: object  # device-resident indices / values / labels
    val: object
    y: object
    host: Optional[Dataset]  # host copy for reload overlap reuse (reader set)


def _kernel_of(node, row_width: int = 1) -> str:
    """The kernel family of a worker's compiled bodies: the one rule on the
    model's feature count, the resident rows' width and the pinned device
    (ops/kernels.py `resolve`), asked once a node and kept on it.  `node` is
    a WorkerNode or anything with `.model`, as the bare hosts of the tests
    are: those have no device (the process default backend answers) and
    sparse rows (a dense batch routes itself, models/linear.py)."""
    kernel = getattr(node, "kernel", None)
    if kernel is None:
        from distributed_sgd_tpu.ops import kernels

        kernel = node.kernel = kernels.resolve(
            None, node.model.n_features, row_width, getattr(node, "device", None))
    return kernel


class WorkerNode:
    def __init__(
        self,
        host: str,
        port: int,
        master_host: str,
        master_port: int,
        data: Dataset,
        model: LinearModel,
        device=None,
        seed: int = 0,
        metrics: Optional[metrics_mod.Metrics] = None,
        steps_per_dispatch: int = 1,
        max_inflight_gossip: int = 64,
        compress: str = "none",
        compress_k: float = 0.01,
        compress_ef: bool = True,
        rpc_policy: Optional[RpcPolicy] = None,
        profile_dir: Optional[str] = None,
        profile_steps: int = 16,
        gossip_topology: str = "all",
        master_watch_s: Optional[float] = None,
        master_watch_misses: int = 3,
        telemetry: bool = False,
        host_devices: int = 1,
        devices=None,
        data_offset: Optional[int] = None,
        row_reader=None,
        total_rows: Optional[int] = None,
        host_overprovision: float = 0.0,
    ):
        require_single_output(model, 'the rpc worker')
        self.host, self.port = host, port
        self.log = node_logger(host, port, master=False)
        self.metrics = metrics or metrics_mod.global_metrics()
        # unified retry/backoff/breaker policy for every outgoing RPC
        # (registration backoff, gossip breaker suppression)
        self.rpc_policy = rpc_policy or RpcPolicy(
            seed=seed + port, metrics=self.metrics)
        self.model = model
        self.device = device if device is not None else jax.devices()[0]
        self.seed = seed
        # wire-path gradient compression (compress/, docs/COMPRESSION.md):
        # None for the default codec, keeping every send below byte-identical
        # to the uncompressed tree.  Residuals are per destination inside the
        # compressor, so sync replies ("sync:master") and each gossip peer
        # accumulate independently.
        from distributed_sgd_tpu.compress import make_compressor

        self._compressor = make_compressor(
            compress, k=compress_k, error_feedback=compress_ef,
            seed=seed + port, metrics=self.metrics)
        # sync-reply EF retry guard: (window key, residual snapshot) of
        # the last Gradient request, plus the fit-session token last seen —
        # see encode_sync_grad.  The key is the broadcast step_version
        # under the versioned wire (retries repeat it even when the wire
        # form changes), the raw weight bytes under the pre-pipeline wire.
        # The lock exists for the quorum barrier (DSGD_QUORUM): a straggler
        # can still be encoding window v when the master's request for v+1
        # (possibly carrying an ef_rollback_version) arrives on another
        # servicer thread — without quorum exactly one Gradient is ever in
        # flight per worker and the lock is uncontended
        self._sync_guard_lock = threading.Lock()
        self._sync_ef_guard: Tuple[Optional[object], Optional[np.ndarray]] = (
            None, None)
        self._sync_fit_token = 0
        # versioned weight-replica cache for the pipelined sync path
        # (docs/SYNC_PIPELINE.md): the last applied weight vector keyed by
        # (fit_token, step_version), so the master can broadcast sparse
        # WeightDeltas (or nothing at all on retry windows) instead of the
        # full dense tensor — see resolve_request_weights
        self._replica_lock = threading.Lock()
        self._replica: Optional[Tuple[int, int, np.ndarray]] = None
        # k local SGD steps per compiled dispatch; the summed delta is
        # gossiped every k steps (deltas commute — same amortization as
        # parallel/hogwild.py, GradUpdate.n_steps carries k on the wire).
        # k=1 is the reference's per-step gossip (Slave.scala:103-105)
        self.steps_per_dispatch = max(1, int(steps_per_dispatch))
        # sparse gossip topology (DSGD_GOSSIP_TOPOLOGY, parallel/topology.py,
        # docs/ELASTICITY.md): which peers receive each dispatch's delta.
        # "all" (default) keeps the reference's full fan-out byte-identical;
        # ring/random:k select deterministically per (dispatch, worker) with
        # breaker-aware reselection around suppressed edges.  The master
        # ALWAYS receives the delta (budget counting) regardless of mode.
        from distributed_sgd_tpu.parallel.topology import parse_topology

        self._topo_mode, self._topo_k = parse_topology(gossip_topology)
        self._dispatch_no = 0
        # master-membership watch (docs/ELASTICITY.md): when set, a
        # registered worker probes Master.Ping with its own identity every
        # `master_watch_s`; after `master_watch_misses` consecutive misses
        # — or ONE NOT_FOUND from a reachable master that does not know us
        # (fast restart / missed eviction) — it clears _registered and
        # re-enters the jittered registration loop, the storm-safe path a
        # RESTARTED master's workers take back into membership.  None
        # (default) keeps the one-shot registration of the reference.
        self._master_watch_s = master_watch_s
        self._master_watch_misses = max(1, int(master_watch_misses))
        # cluster telemetry plane (telemetry/, DSGD_TELEMETRY,
        # docs/OBSERVABILITY.md): when on, each gradient dispatch publishes
        # the training-health gauges (gradient norm, dispatch staleness,
        # EF residual norm) that the master's Metrics-RPC scrape
        # re-exports per worker.  Off (default) the dispatch path runs no
        # extra host work at all; the Metrics RPC itself is always served
        # (pull-only — it costs nothing until somebody scrapes).
        self.telemetry = bool(telemetry)
        self._last_dispatch_t: Optional[float] = None

        # hierarchical in-host mesh (docs/HIERARCHY.md, DSGD_HOST_DEVICES):
        # host_devices > 1 binds the data slice to a local D-device mesh —
        # each Gradient / local-window dispatch shards the request batch
        # over the local devices and reduces with ONE in-host psum, so the
        # cross-host plane sees one reply per HOST instead of per device.
        # host_devices=1 (default) keeps the flat single-device worker
        # byte-identical to the pre-hierarchy engine.
        self._hier = None
        self.host_devices = max(1, int(host_devices))
        # incremental host-local re-sharding (data/host_shard.py,
        # docs/HIERARCHY.md "Elastic composition"): with a RowReader the
        # worker can RELOAD its resident slice when an elastic resplit
        # assigns rows outside it — reading only the uncovered delta —
        # instead of refusing the foreign ids.  `host_overprovision`
        # widens each reload by a neighbor-range margin so small boundary
        # shifts cost zero reloads.  The reader's domain is the TRAIN
        # corpus, so its row count must be explicit.
        self._row_reader = row_reader
        self._overprovision = max(0.0, float(host_overprovision))
        self._total_rows = total_rows
        self._reload_lock = threading.Lock()
        # resident-extent budget for reloads (see ensure_rows): seeded by
        # the constructed slice (nominal + over-provision), re-anchored by
        # each full-assignment reload (start_async).  Bounds both memory
        # and the per-reload device_put under drifting resplits.
        self._resident_budget = len(data)
        if row_reader is not None:
            if total_rows is None:
                raise ValueError(
                    "row_reader needs total_rows: the reload path must "
                    "know the reader's corpus extent to clip slices")
            if data_offset is None:
                raise ValueError(
                    "row_reader without data_offset: a full-corpus worker "
                    "has nothing to reload")
            if host_devices > 1:
                raise ValueError(
                    "row_reader is incompatible with host_devices > 1: "
                    "the in-host mesh replicates its slice at build time "
                    "(elastic reload would need a mesh rebind)")
        if self.host_devices > 1:
            from distributed_sgd_tpu.parallel.hier import HostMeshEngine

            devs = list(devices) if devices is not None else jax.local_devices()
            if len(devs) < self.host_devices:
                raise ValueError(
                    f"host_devices={self.host_devices} but only "
                    f"{len(devs)} local device(s) are available")
            self._hier = HostMeshEngine(model, devs[: self.host_devices], data)
            self.device = devs[0]
            # forward/async reuse the engine's mesh-replicated arrays
            # (ops on replicated arrays compute fine; the sync Gradient
            # plane is where the in-host reduction pays)
            res_idx, res_val, res_y = (
                self._hier.idx, self._hier.val, self._hier.y)
        else:
            # device-resident copy of the worker's data (the reference
            # slave also holds the full data and receives sample indices,
            # Main.scala:138)
            res_idx = jax.device_put(data.indices, self.device)
            res_val = jax.device_put(data.values, self.device)
            res_y = jax.device_put(data.labels, self.device)
        # host-local data slice (data/host_shard.py): `data` holds only
        # global rows [data_offset, data_offset + len(data)) and incoming
        # sample ids are mapped before any gather.  None (default) = the
        # full corpus is resident and ids pass through untouched.  The
        # whole resident state lives in ONE snapshot tuple so an elastic
        # reload swaps it atomically (see _Resident).
        self._resident = _Resident(
            data_offset, len(data), res_idx, res_val, res_y,
            data if row_reader is not None else None)
        kernel = _kernel_of(self, data.indices.shape[1])
        self.log.info(
            "worker kernel=%s on %s (%d in-host device(s))",
            "blocked-onehot" if kernel == "mxu" else kernel,
            self.device, self.host_devices)

        self._peers: Dict[Tuple[str, int], WorkerStub] = {}
        # bounded fire-and-forget gossip per peer (and to the master):
        # drop-oldest over max_inflight_gossip in-flight UpdateGrads, drops
        # counted under slave.async.grad.dropped (parity with the
        # in-process engine's bounded inbox, parallel/hogwild.py)
        self._gossip: Dict[Tuple[str, int], GossipSender] = {}
        self._max_inflight_gossip = int(max_inflight_gossip)
        self._peers_lock = threading.Lock()
        # server first: port 0 resolves to the bound port HERE, so the
        # outgoing channels below carry the worker's real endpoint as their
        # chaos edge origin
        self.server = new_server(port, host="0.0.0.0")
        self.port = self.port or self.server.bound_port
        self._master_channel = new_channel(master_host, master_port,
                                           origin=(host, self.port))
        self._master = MasterStub(self._master_channel)
        self._master_gossip = GossipSender(
            self._master.UpdateGrad, self.metrics, self._max_inflight_gossip,
            breaker=self.rpc_policy.breaker((master_host, master_port)),
            deadline_s=self.rpc_policy.deadline_s)

        # async (Hogwild) state — Slave.scala:23-34
        self._w_lock = threading.Lock()
        self._w: Optional[jax.Array] = None
        self._running_async = threading.Event()
        self._async_thread: Optional[threading.Thread] = None
        self._assignment: Optional[jax.Array] = None
        self._async_bs = 0
        self._async_lr = 0.0

        self._apply = jax.jit(lambda w, d: w - d)
        self._grad_cache: Dict[int, callable] = {}  # keyed by padded capacity

        # aggregation-tree reduce role (aggtree/reduce.py, DSGD_AGG_TREE):
        # constructed lazily by the FIRST agg-annotated request so a
        # knobs-off worker registers no aggtree instrument and allocates
        # nothing (tests/test_aggtree.py identity gate)
        self._agg = None
        self._shard_asm = None
        self._agg_lock = threading.Lock()

        # DSGD_PROFILE_DIR on the RPC worker role: a jax.profiler capture
        # of the FIRST `profile_steps` device dispatches (Gradient bodies
        # or async-loop steps) — this is where the distributed wall-clock
        # actually goes, which the trainer-only wiring never saw
        # (docs/OBSERVABILITY.md).  Thread-safe inside ProfileWindow:
        # dispatches arrive on gRPC servicer threads and the async loop
        # concurrently.
        self._profile = measure.ProfileWindow(profile_dir, profile_steps,
                                              logger=self.log)

        add_worker_servicer(self.server, _WorkerServicer(self),
                            node=self.node_label)
        self._registered = threading.Event()
        self._stopped = threading.Event()

    @property
    def node_label(self) -> str:
        """Stable identity for trace spans and flight events."""
        return f"{self.host}:{self.port}"

    def _ensure_reducer(self):
        """Lazily construct the aggregation-tree reduce role
        (aggtree/reduce.py) on the first agg-annotated request or child
        push — a knobs-off worker never calls this, so it registers no
        aggtree instrument (tests/test_aggtree.py identity gate)."""
        if self._agg is None:
            with self._agg_lock:
                if self._agg is None:
                    from distributed_sgd_tpu.aggtree.reduce import Reducer

                    self._agg = Reducer(self)
        return self._agg

    def _ensure_shard_assembler(self):
        """Lazily construct the shard rendezvous (shardedps/assemble.py)
        on the first shard-tagged Gradient request — the same default-off
        discipline as the reducer above: a knobs-off worker never calls
        this and registers no shard instrument (tests/test_shardedps.py
        identity gate)."""
        if self._shard_asm is None:
            with self._agg_lock:
                if self._shard_asm is None:
                    from distributed_sgd_tpu.shardedps.assemble import (
                        ShardAssembler,
                    )

                    self._shard_asm = ShardAssembler(metrics=self.metrics,
                                                     log=self.log)
        return self._shard_asm

    # resident-slice views (read-only; the canonical state is the atomic
    # _Resident snapshot — dispatch paths grab the snapshot ONCE and use
    # its fields, these properties serve telemetry/tests)
    @property
    def _idx(self):
        return self._resident.idx

    @property
    def _val(self):
        return self._resident.val

    @property
    def _y(self):
        return self._resident.y

    @property
    def _n(self) -> int:
        return self._resident.n

    @property
    def _data_offset(self) -> Optional[int]:
        return self._resident.offset

    # -- lifecycle (Slave.scala:40-77) -------------------------------------

    def start(self, wait_registered: bool = True) -> "WorkerNode":
        self.server.start()
        self.log.info("worker started on %s:%d", self.host, self.port)
        t = threading.Thread(target=self._register_loop, daemon=True, name="register")
        t.start()
        if wait_registered:
            self._registered.wait()
        return self

    def _register_loop(self) -> None:
        node = pb.Node(host=self.host, port=self.port)
        if self.host_devices > 1:
            # host shape rides the registration (docs/HIERARCHY.md): the
            # master weights its host-granular split by devices so a
            # bigger host gets a proportionally bigger partition.  Flat
            # workers leave the field unset — wire byte-identical to the
            # pre-hierarchy Node
            node.devices = self.host_devices
        while not self._stopped.is_set():
            attempt = 0
            while not self._stopped.is_set() and not self._registered.is_set():
                try:
                    self._master.RegisterSlave(
                        node, timeout=self.rpc_policy.deadline_s)
                    self._registered.set()
                    self.log.info("registered with master")
                except grpc.RpcError as e:
                    # jittered exponential backoff (policy default: 2 s first
                    # delay, the reference's fixed retry period,
                    # Slave.scala:56).  The jitter is what makes a whole
                    # fleet re-registering after a master restart storm-safe:
                    # N workers' retries spread over the backoff window
                    # instead of synchronizing (docs/ELASTICITY.md)
                    delay = self.rpc_policy.backoff_s(attempt)
                    attempt += 1
                    self.log.info("registration failed (%s); retry %d in %.1fs",
                                  e.code(), attempt, delay)
                    self._stopped.wait(delay)
            if self._master_watch_s is None or self._stopped.is_set():
                return
            # registered + watch enabled: probe the master WITH OUR OWN
            # identity.  Two distinct loss signals re-enter the
            # registration loop above: sustained unreachability (slow
            # restart / partition, counted in misses) and NOT_FOUND — a
            # reachable master that does not know us (a FAST restart
            # rebinds the port before misses can accumulate, and an
            # eviction we missed looks identical), which re-registers
            # immediately
            misses = 0
            while not self._stopped.wait(self._master_watch_s):
                try:
                    self._master.Ping(node,
                                      timeout=self.rpc_policy.deadline_s)
                    misses = 0
                except grpc.RpcError as e:
                    if e.code() == grpc.StatusCode.NOT_FOUND:
                        self.log.warning(
                            "master no longer knows us (restart or "
                            "eviction); re-registering")
                        flight.record("master.forgot", worker=self.node_label)
                        self._registered.clear()
                        break
                    misses += 1
                    if misses >= self._master_watch_misses:
                        self.log.warning(
                            "master unreachable for %d probes (%s); "
                            "re-registering", misses, e.code())
                        flight.record("master.lost", worker=self.node_label,
                                      misses=misses)
                        self._registered.clear()
                        break
            if self._registered.is_set():
                return  # stopped while the watch was healthy

    def stop(self) -> None:
        self._stopped.set()
        self._running_async.clear()
        if self._async_thread is not None:
            self._async_thread.join()
        self._profile.close()
        if self._registered.is_set():
            try:
                self._master.UnregisterSlave(
                    pb.Node(host=self.host, port=self.port), timeout=2.0
                )
            except grpc.RpcError:
                pass
        with self._peers_lock:
            senders = list(self._gossip.values())
        for sender in senders:
            sender.close()
        self._master_gossip.close()
        self.server.stop(grace=1.0)
        self._master_channel.close()
        self.log.info("worker stopped")

    def await_termination(self) -> None:
        self.server.wait_for_termination()

    # -- peer management ---------------------------------------------------

    def add_peer(self, host: str, port: int) -> None:
        key = (host, port)
        if key == (self.host, self.port):
            return
        with self._peers_lock:
            if key not in self._peers:
                stub = WorkerStub(new_channel(host, port,
                                              origin=(self.host, self.port)))
                self._peers[key] = stub
                # breaker-aware gossip: a partitioned peer costs one probe
                # per cooldown, not max_inflight in-flight cancels.  A
                # (re)introduction is evidence of liveness, so a breaker
                # tripped by the peer's previous incarnation re-closes
                breaker = self.rpc_policy.breaker(key)
                breaker.record_ok()
                self._gossip[key] = GossipSender(
                    stub.UpdateGrad, self.metrics, self._max_inflight_gossip,
                    breaker=breaker, deadline_s=self.rpc_policy.deadline_s)
                self.log.info("peer added: %s:%d", host, port)

    def remove_peer(self, host: str, port: int) -> None:
        with self._peers_lock:
            self._peers.pop((host, port), None)
            sender = self._gossip.pop((host, port), None)
            if self._compressor is not None:
                # a rejoining peer starts from a zero residual (the same
                # state as any destination joining mid-stream), and departed
                # peers must not pin dim-sized residual arrays forever.  An
                # async-loop compress in flight for this dest may re-create
                # the entry after this drop; the loop's post-fan-out sweep
                # (under this same lock) re-drops any dest that lost
                # membership mid-fan-out
                self._compressor.residual_drop(("peer", (host, port)))
        if sender is not None:
            sender.close()

    # -- compiled kernels --------------------------------------------------

    def _grad_fn(self, capacity: int):
        """Sync Gradient RPC body (sum + regularize), jitted per capacity.

        On a TPU-pinned worker the body runs on the lane-blocked kernels
        the shape rule picks (ops/kernels.py: one-hot matmuls or the true
        gather, the same families as the mesh engines); on CPU workers
        the scalar gather/scatter is faster, so it stays.  The async step
        compiles its own mean-reduced variant (_async_loop).
        """
        model = self.model
        kernel = _kernel_of(self)
        if capacity not in self._grad_cache:

            def fn(w, idx, val, y, ids, valid):
                rows_i = idx[ids]
                rows_v = val[ids] * valid[:, None]  # zero rows for pads
                batch = SparseBatch(rows_i, rows_v)
                by = y[ids] * valid.astype(y.dtype)
                return model.grad_regularized(w, batch, by, kernel=kernel)

            # donate the request's weight buffer (ROADMAP item 2): the
            # wrapper creates it from the wire/replica numpy array per
            # dispatch and nobody reads it afterwards, so XLA can write
            # the [D] gradient straight into its HBM instead of
            # allocating a fresh dim-sized output every window
            self._grad_cache[capacity] = jax.jit(fn, donate_argnums=(0,))
        return self._grad_cache[capacity]

    def _pad_ids(self, ids: np.ndarray) -> Tuple[jax.Array, jax.Array]:
        cap = _next_pow2(len(ids))
        padded = np.zeros(cap, dtype=np.int32)
        padded[: len(ids)] = ids
        valid = np.zeros(cap, dtype=np.float32)
        valid[: len(ids)] = 1.0
        return jnp.asarray(padded), jnp.asarray(valid)

    def warmup_thunks(self, batch_size: int, local_steps: int = 1):
        """Flagship compile thunks for the AOT warmup pass
        (compile_cache.py, DSGD_COMPILE_CACHE): the sync Gradient kernel
        at this worker's configured capacity bucket, the K-step local
        window when the pipelined engine is on, and their hierarchical
        (in-host psum) twins on a multi-device host.  Each thunk runs the
        REAL jitted callable once on inert inputs (zero weights, all-pad
        batches — zero rows contribute zero gradient in every model), so
        both the in-process dispatch cache and the persistent disk cache
        are populated before the first master request arrives."""
        d = self.model.n_features
        bs = max(1, int(batch_size))
        k = max(1, int(local_steps))
        if self._resident.n == 0:
            # an empty joining slice has no rows to gather from; kernels
            # compile lazily after the first reload assigns real rows
            return []
        if self._hier is not None:
            hier = self._hier
            thunks = [(f"hier.grad[b{bs}]", lambda: hier.grad(
                np.zeros(d, np.float32), np.zeros(bs, np.int64)))]
            if k > 1:
                thunks.append((f"hier.window[k{k},b{bs}]", lambda: (
                    hier.local_window(np.zeros(d, np.float32),
                                      np.zeros(k * bs, np.int64),
                                      k, bs, 0.0))))
            return thunks
        cap = _next_pow2(bs)

        def grad():
            res = self._resident
            np.asarray(self._grad_fn(cap)(
                jnp.zeros(d, jnp.float32), res.idx, res.val, res.y,
                jnp.zeros(cap, jnp.int32), jnp.zeros(cap, jnp.float32)))

        thunks = [(f"grad[cap{cap}]", grad)]
        if k > 1:

            def window():
                res = self._resident
                np.asarray(self._window_fn(k, bs)(
                    jnp.zeros(d, jnp.float32), res.idx, res.val, res.y,
                    jnp.zeros((k, bs), jnp.int32),
                    jnp.zeros((k, bs), jnp.float32), jnp.float32(0.0)))

            thunks.append((f"window[k{k},b{bs}]", window))
        return thunks

    def _local_ids(self, ids: np.ndarray) -> Tuple[np.ndarray, "_Resident"]:
        """Map global sample ids into this worker's resident rows; returns
        (local ids, the resident snapshot they are valid against) — the
        caller must compute on THAT snapshot's arrays, not re-read the
        attributes (an elastic reload may swap them mid-dispatch).

        With the full corpus resident (data_offset=None, the default) ids
        pass through untouched — zero cost on the flat path.  A host-local
        slice (data/host_shard.py) maps id -> id - offset; ids outside the
        slice trigger an incremental RELOAD through the worker's RowReader
        when one is configured (ensure_rows — the elastic resplit path,
        O(delta) rows read), and are REFUSED otherwise: silently wrapping
        them would compute a gradient over the wrong samples, and the
        failed RPC surfaces at the master as a classified worker failure
        (retry/evict), which is the honest signal that the split and the
        resident slices disagree.  The refusal also covers the reload swap
        window: a request racing the swap either maps cleanly against one
        snapshot or fails loudly and is retried."""
        res = self._resident
        if res.offset is None:
            return ids, res
        local = np.asarray(ids, dtype=np.int64) - res.offset
        if len(local) and (local.min() < 0 or local.max() >= res.n):
            if self._row_reader is not None:
                gmin = int(np.min(ids))
                gmax = int(np.max(ids)) + 1
                res = self.ensure_rows(gmin, gmax)
                local = np.asarray(ids, dtype=np.int64) - res.offset
                if not len(local) or (local.min() >= 0
                                      and local.max() < res.n):
                    return local, res
            raise ValueError(
                f"sample ids outside this host's resident slice "
                f"[{res.offset}, {res.offset + res.n}): "
                f"the master's split is not host-granular for this worker")
        return local, res

    def ensure_rows(self, lo: int, hi: int) -> "_Resident":
        """Grow/shift the resident slice to cover global rows [lo, hi)
        through the RowReader, reading ONLY the uncovered delta
        (data/host_shard.reload_slice) widened by the over-provision
        margin (DSGD_HOST_OVERPROVISION); returns the current snapshot.

        A range the slice already covers returns immediately (the
        membership-stable fast path costs one tuple read + two compares).
        An overlapping reload UNIONs with the resident range — repeated
        window-level triggers after one resplit each read only their gap,
        never re-read rows the previous trigger fetched — but the union
        is BOUNDED by the resident budget (the constructed slice extent,
        re-anchored by full-assignment reloads): when it would exceed the
        budget, rows on the side FARTHEST from the requested range are
        dropped, so drifting resplits slide a fixed-size window across
        the corpus instead of growing the resident set monotonically
        toward it (disk reads stay O(delta); host/device memory and the
        per-reload device_put stay O(budget)).  A disjoint jump drops
        the old rows entirely.  Swaps the _Resident snapshot atomically;
        in-flight dispatches keep computing on the snapshot they
        grabbed."""
        from distributed_sgd_tpu.data import host_shard

        with self._reload_lock:
            res = self._resident
            if (res.offset is None or self._row_reader is None
                    or (lo >= res.offset and hi <= res.offset + res.n)):
                return res
            total = self._total_rows
            margin = host_shard.overprovision_margin(
                hi - lo, self._overprovision)
            req_lo = max(0, lo - margin)
            req_hi = min(total, max(hi, lo + 1) + margin)
            want_lo, want_hi = req_lo, req_hi
            if want_lo < res.offset + res.n and res.offset < want_hi:
                # overlap: union so earlier rows stay warm
                want_lo = min(want_lo, res.offset)
                want_hi = max(want_hi, res.offset + res.n)
            budget = max(self._resident_budget, req_hi - req_lo)
            excess = (want_hi - want_lo) - budget
            if excess > 0:
                # trim old slack outside the requested range, biggest
                # side first — the kept window always covers [req_lo,
                # req_hi) and tracks the direction the split moved
                slack_lo = req_lo - want_lo
                slack_hi = want_hi - req_hi
                if slack_lo >= slack_hi:
                    cut = min(slack_lo, excess)
                    want_lo += cut
                    want_hi -= min(slack_hi, excess - cut)
                else:
                    cut = min(slack_hi, excess)
                    want_hi -= cut
                    want_lo += min(slack_lo, excess - cut)
            host = res.host
            new_data, rows_read = host_shard.reload_slice(
                host, res.offset, self._row_reader, total,
                host.n_features, host.pad_width if not host.is_dense else 0,
                want_lo, want_hi, labels_dtype=host.labels.dtype)
            new_res = _Resident(
                want_lo, len(new_data),
                jax.device_put(new_data.indices, self.device),
                jax.device_put(new_data.values, self.device),
                jax.device_put(new_data.labels, self.device),
                new_data)
            self._resident = new_res
            self.metrics.counter(metrics_mod.DATA_RELOADS).increment()
            self.metrics.counter(
                metrics_mod.DATA_RELOAD_ROWS).increment(rows_read)
            flight.record("data.reload", worker=self.node_label,
                          start=want_lo, end=want_hi, rows_read=rows_read)
            self.log.info(
                "resident slice re-sharded: [%d, %d) -> [%d, %d), "
                "%d row(s) read (delta only)", res.offset,
                res.offset + res.n, want_lo, want_hi, rows_read)
            return new_res

    def compute_gradient(self, w: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Sync Gradient RPC body: sum of backwards + regularize
        (Slave.scala:142-157).  On a hierarchical host the batch shards
        over the local mesh and reduces with one in-host psum
        (parallel/hier.py) — same reply, one RPC per host."""
        self._profile.tick()
        ids, res = self._local_ids(ids)
        if self._hier is not None:
            g = self._hier.grad(np.asarray(w, dtype=np.float32), ids)
            self.metrics.counter("slave.sync.backward").increment()
            return g
        pids, valid = self._pad_ids(ids)
        g = self._grad_fn(len(pids))(
            jnp.asarray(w), res.idx, res.val, res.y, pids, valid
        )
        self.metrics.counter("slave.sync.backward").increment()
        return np.asarray(g)

    def compute_gradient_hedged(self, w: np.ndarray,
                                ids: np.ndarray) -> np.ndarray:
        """Hedge-request compute (GradientRequest.hedge): same math as
        compute_gradient, but a FOREIGN slice — ids outside a host-local
        donor's resident window — is read through the donor's RowReader
        into a transient scratch batch instead of sliding the resident
        window via ensure_rows.  The donor's resident bounds, reload
        counters, and over-provision budget belong to ITS OWN slice; a
        backup duplicate of someone else's rows must not thrash them
        (docs/HIERARCHY.md — the caveat that used to ban hedge=True in
        bench_soak).  Ids inside the resident slice take the normal path
        unchanged, so a full-corpus worker never pays anything here."""
        res = self._resident
        if (res.offset is not None and self._row_reader is not None
                and len(ids)):
            local = np.asarray(ids, dtype=np.int64) - res.offset
            if local.min() < 0 or local.max() >= res.n:
                return self._scratch_gradient(w, ids, res)
        return self.compute_gradient(w, ids)

    def _scratch_gradient(self, w: np.ndarray, ids: np.ndarray,
                          res: "_Resident") -> np.ndarray:
        """Bounded scratch read + one gradient over it: materializes ONLY
        [min(ids), max(ids)+1) through the RowReader — the same clipped
        window ensure_rows would have requested, WITHOUT the
        over-provision margin, the resident-budget union, the _Resident
        swap, or the reload counters/flight record — computes on the
        transient arrays, and drops them."""
        from distributed_sgd_tpu.data import host_shard

        self._profile.tick()
        gmin = int(np.min(ids))
        gmax = int(np.max(ids)) + 1
        host = res.host
        scratch = host_shard.load_host_shard(
            self._row_reader, self._total_rows, host.n_features,
            host.pad_width if not host.is_dense else 0, gmin, gmax,
            labels_dtype=host.labels.dtype)
        self.metrics.counter(metrics_mod.HEDGE_SCRATCH).increment()
        pids, valid = self._pad_ids(np.asarray(ids, dtype=np.int64) - gmin)
        g = self._grad_fn(len(pids))(
            jnp.asarray(w), jnp.asarray(scratch.indices),
            jnp.asarray(scratch.values), jnp.asarray(scratch.labels),
            pids, valid)
        self.metrics.counter("slave.sync.backward").increment()
        return np.asarray(g)

    # -- pipelined sync engine (docs/SYNC_PIPELINE.md) ---------------------

    def resolve_request_weights(self, request):
        """Versioned weight resolution for the sync Gradient path.

        Returns (weights, stale).  A full broadcast (`weights` set)
        installs the replica at `step_version`; a WeightDelta assigns the
        master's ABSOLUTE new values at `delta.indices` on top of the
        cached replica when `base_version` matches; a header-only request
        (neither arm set, version tracking on) reuses the replica as-is.
        Any mismatch — empty cache after a (re)start, wrong base version,
        wrong fit session — returns stale=True WITHOUT computing anything:
        the master falls back to a full broadcast on the retry window.

        A request whose target version the replica already holds returns
        the cache directly regardless of arm, so a delta re-sent after a
        lost reply is never applied twice (the absolute-value encoding
        would make re-application harmless anyway; the version check makes
        it structural).  Pre-pipeline masters always send full weights
        with step_version=0, which lands in the install arm every window —
        identical behavior to the unversioned wire.
        """
        tok = request.fit_token
        version = request.step_version
        with self._replica_lock:
            if self._replica is not None and self._replica[0] != tok:
                self._replica = None  # new fit session: drop the old replica
            if request.HasField("weights"):
                w = codec.decode_tensor(request.weights)
                self._replica = (tok, version, w)
                return w, False
            if self._replica is None:
                return None, True
            _, cached_ver, cached = self._replica
            if cached_ver == version:
                return cached, False  # retry / already-applied: idempotent
            if request.HasField("delta") and cached_ver == request.delta.base_version:
                w = codec.apply_weight_delta(cached, request.delta)
                self._replica = (tok, version, w)
                return w, False
            return None, True

    def _window_fn(self, steps: int, capacity: int):
        """K-step local-SGD window (GradientRequest.local_steps), jitted per
        (steps, per-step capacity): a lax.scan of the same sum-reduced
        regularized gradient as _grad_fn, each step applying the
        reference's plain update w -= lr * g locally.  Returns the summed
        weight-space decrement w_start - w_end — at K=1 this is exactly
        lr * compute_gradient(w, ids), so the master recovers the same
        pseudo-gradient the one-batch window would have produced."""
        model = self.model
        kernel = _kernel_of(self)
        key = ("window", steps, capacity)
        if key not in self._grad_cache:

            def fn(w, idx, val, y, ids, valid, lr):
                def body(w_t, inp):
                    ids_t, valid_t = inp
                    rows_i = idx[ids_t]
                    rows_v = val[ids_t] * valid_t[:, None]  # zero rows for pads
                    batch = SparseBatch(rows_i, rows_v)
                    by = y[ids_t] * valid_t.astype(y.dtype)
                    g = model.grad_regularized(w_t, batch, by, kernel=kernel)
                    return w_t - lr * g, None

                w_end, _ = jax.lax.scan(body, w, (ids, valid))
                return w - w_end

            # w is request-scoped here too (see _grad_fn): donating it
            # lets the K-step scan run in place and the summed decrement
            # reuse the buffer — no per-window HBM copy
            self._grad_cache[key] = jax.jit(fn, donate_argnums=(0,))
        return self._grad_cache[key]

    def compute_local_window(self, w: np.ndarray, ids: np.ndarray, k: int,
                             batch_size: int, learning_rate: float) -> np.ndarray:
        """Run up to `k` local SGD steps over `ids` split into
        `batch_size`-sized batches; returns the summed decrement delta.
        The final (or only) batch may be short — epoch tails send fewer
        than k*batch_size ids — and is masked out via zeroed rows, so each
        (steps, batch_size) shape compiles exactly once."""
        self._profile.tick()
        ids, res = self._local_ids(ids)
        bs = max(1, int(batch_size))
        n = len(ids)
        # step count derives from the ids actually sent, capped at k so an
        # oversized sample list cannot run more local steps than the wire
        # contract (GradientRequest.local_steps) allows
        steps = max(1, min(-(-n // bs), max(1, int(k))))
        if self._hier is not None:
            delta = self._hier.local_window(
                np.asarray(w, dtype=np.float32), ids, steps, bs,
                float(learning_rate))
            self.metrics.counter("slave.sync.backward").increment(steps)
            return delta
        n = min(n, steps * bs)  # excess ids beyond the k-step budget dropped
        padded = np.zeros(steps * bs, dtype=np.int32)
        padded[:n] = np.asarray(ids[:n], dtype=np.int32)
        valid = np.zeros(steps * bs, dtype=np.float32)
        valid[:n] = 1.0
        delta = self._window_fn(steps, bs)(
            jnp.asarray(w), res.idx, res.val, res.y,
            jnp.asarray(padded.reshape(steps, bs)),
            jnp.asarray(valid.reshape(steps, bs)),
            jnp.float32(learning_rate),
        )
        self.metrics.counter("slave.sync.backward").increment(steps)
        return np.asarray(delta)

    def encode_sync_grad(self, g: np.ndarray, window_key,
                         fit_token: int = 0):
        """Compressed Gradient reply with at-most-once residual drain.

        `compress` removes the shipped top-k mass from the EF residual at
        encode time, but the sync master DISCARDS every ok reply in a batch
        window when a sibling worker fails and retries the whole window
        (core/master.py fit_sync) — without compensation each retry would
        permanently lose this worker's largest-magnitude coordinates.  A
        retry is recognizable here by `window_key` — the broadcast
        step_version when the master versions its broadcasts (versions
        start at 1 and only advance after a fully-successful window, and
        a retry repeats the version even when the wire FORM changed, e.g.
        a full broadcast downgrading to header-only once this worker
        acknowledged it), the raw weight bytes otherwise (byte-identical
        weights = retry, the pre-pipeline rule).  On a repeated key the
        pre-drain residual is restored before re-encoding.  (Identical
        weights across *different* windows would need an exactly-zero
        update — in which case the restored and current residuals
        coincide and the rollback is a no-op.)

        `fit_token` scopes the residual to ONE fit: the master stamps each
        fit_sync's requests with a fresh token, and a token change drops
        the residual + guard here, so one fit's unsent mass (a gradient of
        the abandoned trajectory) never leaks into the next fit's first
        windows.  0 = an older master without session tracking: behave as
        before (residual carried, bounded by one window's unsent mass).
        """
        with self._sync_guard_lock:
            if fit_token and fit_token != self._sync_fit_token:
                self._sync_fit_token = fit_token
                self._compressor.residual_drop("sync:master")
                self._sync_ef_guard = (None, None)
            prev_key, prev_res = self._sync_ef_guard
            if prev_key is not None and prev_key == window_key:
                self._compressor.residual_restore("sync:master", prev_res)
            else:
                self._sync_ef_guard = (
                    window_key,
                    self._compressor.residual_snapshot("sync:master"),
                )
            return self._compressor.compress(g, dest="sync:master")

    def record_health(self, g: np.ndarray) -> None:
        """Per-dispatch training-health gauges (telemetry/health.py,
        DSGD_TELEMETRY): this node's gradient norm, the gap since its
        previous dispatch (update staleness as the worker sees it), and
        the error-feedback residual norm when compression is on.  Called
        only with ``self.telemetry`` set, so the knobs-off dispatch path
        pays nothing."""
        now = time.monotonic()
        prev, self._last_dispatch_t = self._last_dispatch_t, now
        m = self.metrics
        m.gauge(metrics_mod.HEALTH_GRAD_NORM).set(float(np.linalg.norm(g)))
        if prev is not None:
            m.gauge(metrics_mod.HEALTH_STALENESS).set(now - prev)
        if self._compressor is not None:
            # the residual destination depends on the engine: sync replies
            # drain "sync:master", the async gossip loop drains "master" —
            # report whichever this worker is actually accumulating
            res = self._compressor.residual_snapshot("sync:master")
            if res is None:
                res = self._compressor.residual_snapshot("master")
            if res is not None:
                m.gauge(metrics_mod.HEALTH_EF_RESIDUAL_NORM).set(
                    float(np.linalg.norm(res)))

    def rollback_sync_ef(self, version: int) -> None:
        """Quorum contribution mask (GradientRequest.ef_rollback_version):
        the master discarded this worker's reply for broadcast `version`
        (the quorum barrier proceeded without it), so the residual drain
        of that window must be rolled back — the round contributed
        nothing, and its unsent top-k mass must neither be lost (drain)
        nor ride a later message twice (the master never applied the
        shipped part, so restoring the PRE-drain snapshot is exact).

        Exact-match only: if the guard's window key is not `version` the
        worker never encoded that window (the request itself was lost
        before compute) and there is nothing to roll back — the
        instruction is idempotent and safe to repeat."""
        if self._compressor is None:
            return
        with self._sync_guard_lock:
            prev_key, prev_res = self._sync_ef_guard
            if prev_key is not None and prev_key == version:
                self._compressor.residual_restore("sync:master", prev_res)
                self._sync_ef_guard = (None, None)
                self.metrics.counter("slave.sync.ef.rollback").increment()
                trace_mod.event(trace_mod.EVENT_EF_ROLLBACK, version=version)
                flight.record("ef.rollback", worker=self.node_label,
                              version=version)

    def compute_forward(self, w: np.ndarray, ids: np.ndarray):
        """Forward RPC body (Slave.scala:129-140) -> (predictions, margins).

        Margins ride along so the master can compute margin-based losses
        (logistic) exactly — see ForwardReply in dsgd.proto."""
        ids, res = self._local_ids(ids)
        pids, _ = self._pad_ids(ids)
        wj = jnp.asarray(w)
        batch = SparseBatch(res.idx[pids], res.val[pids])
        margins = self.model.margins(wj, batch)
        preds = self.model.predict(margins)
        self.metrics.counter("slave.sync.forward").increment()
        return np.asarray(preds)[: len(ids)], np.asarray(margins)[: len(ids)]

    # -- async engine (Slave.scala:79-111,159-195) -------------------------

    def start_async(self, w0: np.ndarray, assignment: np.ndarray, batch_size: int,
                    learning_rate: float, optimizer: str = "",
                    momentum: float = 0.9) -> None:
        ftrl.refuse(optimizer, "the rpc worker")
        # a re-issued StartAsync (master watchdog reassignment after a peer
        # death, master.py _async_watchdog) REPLACES any running loop: stop
        # and join it first so two loops never race on the shared state
        if self._async_thread is not None and self._async_thread.is_alive():
            self.log.info("StartAsync re-issued: replacing the running async loop")
            self._running_async.clear()
            self._async_thread.join()
        if self._hier is not None:
            # the in-host reduction is a sync-plane lever; the async loop
            # runs on the mesh-replicated arrays (correct, but every local
            # device computes the same step — no speedup)
            self.log.warning(
                "host_devices=%d: the async loop runs replicated on the "
                "local mesh (the in-host psum accelerates the sync "
                "Gradient plane)", self.host_devices)
        res = self._resident
        if res.offset is not None:
            if self._row_reader is not None and len(assignment):
                # elastic resplit landing outside the resident slice:
                # re-shard incrementally (O(delta) rows through the
                # reader) BEFORE mapping, instead of refusing the fit.
                # The assignment is the FULL new slice, so it re-anchors
                # the resident budget (span + both margins) — later
                # window-level reloads trim to this size
                a_lo = int(np.min(assignment))
                a_hi = int(np.max(assignment)) + 1
                from distributed_sgd_tpu.data.host_shard import (
                    overprovision_margin,
                )

                self._resident_budget = (a_hi - a_lo) + 2 * \
                    overprovision_margin(a_hi - a_lo, self._overprovision)
                res = self.ensure_rows(a_lo, a_hi)
            assignment = np.asarray(assignment, dtype=np.int64) - res.offset
            if len(assignment) and (assignment.min() < 0
                                    or assignment.max() >= res.n):
                raise ValueError(
                    "StartAsync assignment outside this host's resident "
                    "slice (host-local loading needs a host-granular split)")
        if self._compressor is not None:
            # error-feedback residuals belong to the trajectory that
            # accumulated them: a StartAsync begins (or replaces) a session
            # from fresh weights, and shipping the abandoned trajectory's
            # unsent mass into it would inject stale gradients — same for
            # the sync-reply residual of any fit that ran before this one
            self._compressor.reset()
            self._sync_ef_guard = (None, None)
        with self._w_lock:
            self._w = jax.device_put(jnp.asarray(w0, dtype=jnp.float32), self.device)
        self._assignment = jax.device_put(
            jnp.asarray(assignment, dtype=jnp.int32), self.device
        )
        self._async_bs = int(batch_size)
        self._async_lr = float(learning_rate)
        # optimizer for the LOCAL steps (StartAsyncRequest.optimizer;
        # ""/sgd = the reference's plain update, Slave.scala:99-101) —
        # resolved HERE so an unknown name fails the StartAsync RPC
        # instead of killing the daemon loop thread
        from distributed_sgd_tpu.parallel.sync import resolve_optimizer

        # momentum passes through verbatim — an explicit 0.0 is honored
        # (the master always sets both proto fields; when optimizer is
        # absent/sgd the value is unused anyway)
        self._async_opt = resolve_optimizer(
            optimizer or None, float(learning_rate), float(momentum))
        self._running_async.set()
        self._async_thread = threading.Thread(
            target=self._async_loop, daemon=True, name=f"async-{self.port}"
        )
        self._async_thread.start()
        self.log.info("async started: %d samples, bs=%d lr=%g optimizer=%s",
                      len(assignment), batch_size, learning_rate, optimizer or "sgd")

    def stop_async(self) -> None:
        self._running_async.clear()

    def apply_delta(self, delta: np.ndarray) -> None:
        """Peer/master UpdateGrad: w <- w - delta (Slave.scala:177-185)."""
        with self._w_lock:
            if self._w is not None:
                self._w = self._apply(self._w, jnp.asarray(delta))
        self.metrics.counter("slave.async.grad.update").increment()

    def _async_loop(self) -> None:
        # the loop thread is a daemon: an uncaught exception here would
        # kill Hogwild training SILENTLY (the master's stall watchdog only
        # notices minutes later) — leave post-mortem evidence first
        try:
            self._async_loop_impl()
        except Exception as e:  # noqa: BLE001 - record, dump, then surface
            flight.record("async.loop.crash", worker=self.node_label,
                          error=repr(e))
            flight.dump("exception")
            self.log.exception("async loop crashed")
            raise

    def _async_loop_impl(self) -> None:
        bs, lr = self._async_bs, self._async_lr
        n_assigned = int(self._assignment.shape[0])
        model = self.model
        ksteps = self.steps_per_dispatch
        # one resident snapshot for the whole loop: the assignment was
        # mapped against it in start_async, and a replacement StartAsync
        # (the only path that re-shards mid-async) replaces this loop too
        res = self._resident

        kernel = _kernel_of(self)
        opt = self._async_opt

        def kstep(w, opt_state, assignment, idx, val, y, key):
            # k local SGD steps in ONE compiled dispatch; returns the
            # SUMMED delta for gossip (commutative merge — peers applying
            # the sum see exactly the k individual w <- w - delta merges,
            # just k steps later; staleness bounded by k).  Optimizer
            # state is LOCAL and threads through the carry across
            # dispatches; the wire still carries weight-space deltas
            def body(carry, kk):
                w_t, opt_s, acc = carry
                ids = assignment[jax.random.randint(kk, (bs,), 0, n_assigned)]
                batch = SparseBatch(idx[ids], val[ids])
                # MEAN reduce (Slave.scala:93-98) + regularize (Slave:99)
                g = model.grad_regularized(
                    w_t, batch, y[ids], reduce="mean", kernel=kernel
                )
                from distributed_sgd_tpu.parallel.sync import local_update

                w_t, opt_s, delta = local_update(opt, lr, g, w_t, opt_s)
                return (w_t, opt_s, acc + delta), None

            keys = jax.random.split(key, ksteps)
            (_, opt_state, acc), _ = jax.lax.scan(
                body, (w, opt_state, jnp.zeros_like(w)), keys)
            return acc, opt_state

        # donate the local optimizer state (threaded carry, rebound every
        # dispatch; the weight SNAPSHOT must not be donated — a concurrent
        # UpdateGrad may still read the same buffer through self._w)
        kstep = jax.jit(kstep, donate_argnums=(1,))
        key = jax.random.PRNGKey(self.seed + self.port)
        opt_state = opt.init(self._w) if opt is not None else None
        while self._running_async.is_set():
            key, k = jax.random.split(key)
            self._profile.tick()
            snapshot = self._w  # stale read is the algorithm
            delta, opt_state = kstep(
                snapshot, opt_state, self._assignment, res.idx, res.val,
                res.y, k)
            with self._w_lock:
                self._w = self._apply(self._w, delta)
            self.metrics.counter("slave.async.batch").increment(ksteps)
            delta_np = np.asarray(delta)
            if self.telemetry:
                # async dispatches publish the same health gauges as sync
                # Gradient bodies: the delta IS this node's update signal
                self.record_health(delta_np)
            # gossip fan-out span (trace/, one local trace per dispatch,
            # head-sampled): encode + hand-off per destination — the sends
            # themselves are fire-and-forget futures
            with measure.span("slave.async.gossip", metrics=self.metrics,
                              node=self.node_label, k=ksteps):
                self._gossip_dispatch(delta_np, ksteps)

    def _select_gossip(self):
        """This dispatch's peer destinations under the configured topology
        (parallel/topology.py).  'all' returns the live sender map in
        insertion order — the exact pre-topology iteration, so the default
        wire is byte- and order-identical; ring/random:k select
        deterministically per (dispatch, worker) and re-route edges whose
        breaker is refusing sends (counted + traced)."""
        with self._peers_lock:
            senders = dict(self._gossip)
        if self._topo_mode == "all":
            return list(senders.items())
        from distributed_sgd_tpu.parallel import topology as topo

        def _suppressed(key):
            s = senders.get(key)
            return (s is not None and s.breaker is not None
                    and s.breaker.suppressed())

        keys, reselects = topo.select_gossip_peers(
            self._topo_mode, self._topo_k, list(senders),
            (self.host, self.port), self._dispatch_no, seed=self.seed,
            suppressed=_suppressed)
        if reselects:
            self.metrics.counter(
                metrics_mod.TOPOLOGY_RESELECT).increment(reselects)
            trace_mod.event(trace_mod.EVENT_TOPOLOGY_RESELECT,
                            node=self.node_label, edges=reselects)
            flight.record("topology.reselect", worker=self.node_label,
                          edges=reselects)
        return [(k, senders[k]) for k in keys]

    def _gossip_dispatch(self, delta_np: np.ndarray, ksteps: int) -> None:
        """One dispatch's delta fan-out to the topology-selected peers + the
        master (the master ALWAYS receives: it counts the budget)."""
        self._dispatch_no += 1
        if self._compressor is None:
            msg = codec.encode_grad(delta_np)
            msg.n_steps = ksteps
            for _key, sender in self._select_gossip():
                sender.send(msg)  # fire-and-forget (Slave.scala:103-105),
            self._master_gossip.send(msg)  # bounded in-flight, drop-oldest
            return
        # per-destination encode: each peer (and the master) has its
        # own error-feedback residual, so the k coordinates shipped
        # can differ by destination.  Every message stays a plain
        # weight-space delta, so the receiving merges keep the
        # summed-delta commutativity contract above — EF only defers
        # WHEN a coordinate's mass arrives, bounded by the residual.
        # Note on transport drops: like the uncompressed wire, a
        # gossip message the bounded sender cancels is simply lost
        # (fire-and-forget permits it) — EF retransmits only what
        # SELECTION dropped, never what the transport dropped; the
        # loss stays bounded by one message per cancel, exactly as
        # in the uncompressed mode (docs/COMPRESSION.md).
        # Compress OUTSIDE _peers_lock (the first call jit-compiles
        # the selection — holding the lock through that would stall
        # Register/UnregisterSlave servicers); the post-loop sweep
        # below closes the race where a concurrent remove_peer's
        # residual_drop interleaves with an in-flight compress and
        # the dropped entry gets silently re-created.
        senders_c = self._select_gossip()
        for peer_key, sender in senders_c:
            msg = self._compressor.compress(
                delta_np, dest=("peer", peer_key))
            msg.n_steps = ksteps
            sender.send(msg)
        msg = self._compressor.compress(delta_np, dest="master")
        msg.n_steps = ksteps
        self._master_gossip.send(msg)
        with self._peers_lock:
            for peer_key, _ in senders_c:
                if peer_key not in self._gossip:
                    self._compressor.residual_drop(("peer", peer_key))


class _WorkerServicer:
    """gRPC method bodies (SlaveImpl, Slave.scala:113-196)."""

    def __init__(self, w: WorkerNode):
        self.w = w

    def RegisterSlave(self, request, context):  # noqa: N802
        self.w.add_peer(request.host, request.port)
        return pb.Ack()

    def UnregisterSlave(self, request, context):  # noqa: N802
        self.w.remove_peer(request.host, request.port)
        return pb.Ack()

    def Ping(self, request, context):  # noqa: N802
        return pb.Ack()

    def Forward(self, request, context):  # noqa: N802
        w = codec.decode_tensor(request.weights)
        ids = np.fromiter(request.samples, dtype=np.int64)
        preds, margins = self.w.compute_forward(w, ids)
        if request.want_margins:
            return pb.ForwardReply(predictions=preds, margins=margins)
        return pb.ForwardReply(predictions=preds)

    def Gradient(self, request, context):  # noqa: N802
        return self._gradient_update(request)

    def _gradient_update(self, request):
        """One sync-window Gradient body, shared verbatim by the unary
        Gradient RPC and the FitStream servicer loop below — streaming
        changes the transport, never the math (the stream-vs-unary
        bit-identity the rpc bench gates on falls out of this sharing)."""
        # quorum contribution mask: the master marks the window whose
        # reply it discarded so the EF residual drain rolls back first
        if request.ef_rollback_version:
            self.w.rollback_sync_ef(request.ef_rollback_version)
        if request.shard_count:
            # feature-sharded master plane (DSGD_MASTER_SHARDS,
            # docs/MASTER_SHARDING.md): this request is one lane's leg of
            # an M-way round — rendezvous the slices, compute once, reply
            # the range slice.  Flat requests never set shard_count, so
            # the knobs-off path pays one falsy proto-field read.
            return self._sharded_update(request)
        w, stale = self.w.resolve_request_weights(request)
        if stale:
            # replica/version mismatch: no gradient to give — the master
            # falls back to a full broadcast on the retry window
            self.w.metrics.counter("slave.sync.stale").increment()
            return pb.GradUpdate(stale_version=True)
        ids = np.fromiter(request.samples, dtype=np.int64)
        k = request.local_steps
        # compute vs encode/EF attribution (docs/OBSERVABILITY.md): under
        # an active trace these become children of the Gradient server
        # span (root=False: on an unsampled round they stay no-op rather
        # than fabricating orphan traces); always they feed the span.*
        # histograms
        with measure.span("slave.grad.compute", metrics=self.w.metrics,
                          root=False,
                          samples=len(ids), local_steps=int(k or 1)):
            if k > 1:
                g = self.w.compute_local_window(
                    w, ids, k, request.batch_size, request.learning_rate)
            elif request.hedge:
                # foreign-slice hedges read through a bounded scratch so
                # the donor's resident window never slides for someone
                # else's rows (see compute_gradient_hedged)
                g = self.w.compute_gradient_hedged(w, ids)
            else:
                g = self.w.compute_gradient(w, ids)
        if request.hedge:
            # straggler hedge (another worker's data slice): reply
            # uncompressed and leave this worker's OWN sync EF residual
            # untouched — the residual for that slice belongs to the
            # straggler, and draining ours here would double-count mass
            # against the master's average.  The health gauges are
            # likewise NOT recorded: the gradient norm belongs to the
            # straggler's slice, and overwriting this node's per-worker
            # series with it would pollute the dashboards exactly when
            # the cluster is under straggler stress
            self.w.metrics.counter("slave.sync.hedge").increment()
            msg = codec.encode_grad(g)
            if k > 1:
                msg.n_steps = k
            return msg
        if self.w.telemetry:
            self.w.record_health(g)
        if request.agg_parent or request.agg_children:
            # aggregation tree (DSGD_AGG_TREE, docs/AGGREGATION.md): this
            # node is an elected reduce node and/or an interior child —
            # collect, reduce, and route the subtree sum instead of the
            # plain reply.  Flat requests never reach this branch, so the
            # knobs-off dispatch path pays one falsy proto-field read.
            return self._agg_gradient(request, g, k)
        return self._encode_reply(request, g, k)

    def _sharded_update(self, request):
        """One lane's leg of a sharded round (shardedps/assemble.py):
        resolve this shard's weight slice, rendezvous with the sibling
        legs, compute the full gradient ONCE per round, and reply only
        the ``[shard_lo, shard_hi)`` slice — through the SAME encode/tree
        tail as a flat reply, so per-shard trees and the wire codec need
        no sharded special case."""
        asm = self.w._ensure_shard_assembler()
        g = asm.gradient(request, self.w.compute_gradient)
        if g is None:
            # a slice failed to resolve (or the rendezvous timed out):
            # every leg of the round replies stale and the master's retry
            # re-sends full slices on every lane
            self.w.metrics.counter("slave.sync.stale").increment()
            return pb.GradUpdate(stale_version=True,
                                 shard_index=request.shard_index)
        if self.w.telemetry and request.shard_index == 0:
            # health gauges once per round, not once per lane — the
            # gradient is the round's single full-dimension fan-in
            self.w.record_health(g)
        g_slice = np.ascontiguousarray(
            g[request.shard_lo:request.shard_hi])
        k = request.local_steps
        if request.agg_parent or request.agg_children:
            msg = self._agg_gradient(request, g_slice, k)
        else:
            msg = self._encode_reply(request, g_slice, k)
        msg.shard_index = request.shard_index
        return msg

    def _encode_reply(self, request, g, k):
        """The sync-reply encode tail, shared by the flat path and the
        tree path (a subtree sum rides the SAME per-edge codec /
        compression / EF machinery as a flat reply — for an aggregator
        the error-feedback residual simply accumulates against its
        subtree sum instead of its own gradient)."""
        # sync fan-in reply: compressed when configured (EF residual keyed
        # to the one sync destination — this worker answers one master),
        # with the retry-rollback + fit-session guards of encode_sync_grad
        with measure.span("slave.grad.encode", metrics=self.w.metrics,
                          root=False):
            if self.w._compressor is not None:
                # retry-window key: the step_version when the master versions
                # its broadcasts (a retry repeats the version even if the wire
                # form changed, e.g. full -> header-only after a mid-window
                # fallback), the weight bytes otherwise (pre-pipeline wire:
                # byte-identical weights = retry)
                window_key = request.step_version or request.weights.data
                msg = self.w.encode_sync_grad(g, window_key, request.fit_token)
            else:
                msg = codec.encode_grad(g)
        if k > 1:
            msg.n_steps = k  # wire accounting: steps amortized per round
        return msg

    def _agg_gradient(self, request, g, k):
        """Tree-annotated Gradient body (docs/AGGREGATION.md): reduce the
        stamped children into this node's own gradient in CANONICAL
        (stamped) order, then either push the subtree sum to the stamped
        parent over AggregateGrad (reply = armless agg_forwarded ack) or
        reply it to the master directly (root child — and the flat
        fallback when the push fails, tagged agg_flat).  Either way the
        encode tail below runs EXACTLY once per round, so the per-edge
        error-feedback residual drains at most once per round too."""
        from distributed_sgd_tpu.aggtree import reduce as agg_reduce

        red = self.w._ensure_reducer()
        contributors = [self.w.node_label]
        partial = False
        if request.agg_children:
            children = list(request.agg_children)
            with measure.span("slave.agg.reduce", metrics=self.w.metrics,
                              root=False, children=len(children)):
                got = red.collect(request.fit_token, request.agg_round,
                                  children,
                                  agg_reduce.wait_budget_s(request))
                # canonical order: the stamped child tuple, misses skipped
                # (f32 addition is order-sensitive — two runs over the same
                # plan and reply set must chain identically)
                updates = [got[c] for c in children if c in got]
                g = red.reduce(np.asarray(g, dtype=np.float32), updates)
            for c in children:
                u = got.get(c)
                if u is None:
                    partial = True
                else:
                    contributors.extend(u.agg_contributors or [c])
        msg = self._encode_reply(request, g, k)
        msg.agg_contributors.extend(contributors)
        if partial:
            msg.agg_partial = True
            self.w.metrics.counter(metrics_mod.AGG_PARTIAL).increment()
        if request.agg_parent:
            if red.push_up(request.agg_parent, request.fit_token,
                           request.agg_round, msg):
                # the subtree sum is riding the tree — the master's
                # barrier still gets one reply per dispatched worker,
                # this armless ack (decodes as zero, see codec.parse_grad)
                return pb.GradUpdate(agg_forwarded=True)
            # dead/unreachable parent: this whole subtree degrades to a
            # direct-to-master send for THIS round (the tree loses
            # performance, never the round).  Counted HERE, not at the
            # master: a dead parent usually fails its own reply in the
            # same window, so the master retries and discards the very
            # replies that carried the fallback flag — the child is the
            # only node that reliably witnesses the degradation.
            self.w.metrics.counter(metrics_mod.AGG_FLAT).increment()
            msg.agg_flat = True
            flight.record("agg.flat_fallback", worker=self.w.node_label,
                          parent=request.agg_parent,
                          round=int(request.agg_round))
        return msg

    def AggregateGrad(self, request, context):  # noqa: N802
        """Tree child push intake (DSGD_AGG_TREE): buffer the child's
        encoded subtree sum for the in-flight (or imminent) Gradient
        body above — see aggtree/reduce.py for the buffer contract."""
        self.w._ensure_reducer().offer(request.fit_token, request.round,
                                       request.origin, request.update)
        return pb.Ack()

    def FitStream(self, request_iterator, context):  # noqa: N802
        """Streaming sync fan-out (DSGD_STREAM, docs/SYNC_PIPELINE.md):
        one persistent bidi stream per master carrying framed
        GradientRequests for the lifetime of a fit; each frame runs the
        EXACT unary Gradient body and answers on the stream under the
        request's seq.  Teardown — the master closing, a transport reset,
        or an exception out of the body (e.g. the foreign-id refusal) —
        ends the generator, which the master's stream client treats like
        a failed unary call: in-flight windows replay over unary, the
        re-register path is untouched, and an elastic resplit simply
        re-opens the stream (rpc/stream.py)."""
        m = self.w.metrics
        m.counter(metrics_mod.SLAVE_STREAM_OPENED).increment()
        self.w.log.info("FitStream opened by %s", context.peer())
        try:
            for frame in request_iterator:
                if frame.WhichOneof("payload") != "request":
                    continue  # future-proofing: unknown arms are skipped
                m.counter(metrics_mod.SLAVE_STREAM_FRAMES).increment()
                update = self._gradient_update(frame.request)
                yield pb.Frame(seq=frame.seq, fit_token=frame.fit_token,
                               update=update)
        except grpc.RpcError:
            # the CLIENT tore the stream down (master closed at fit end,
            # cancelled, or the connection reset) — there is nobody left
            # to answer; end quietly, this is the normal lifecycle
            self.w.log.info("FitStream closed by peer")
        except Exception as e:  # noqa: BLE001 - surface, then tear down
            # a per-frame failure has no error arm on the stream: tearing
            # the stream down IS the classified failure (the master falls
            # back to unary, where the same request fails loudly per-call)
            self.w.log.warning("FitStream servicer loop failed: %r", e)
            flight.record("stream.servicer.error", worker=self.w.node_label,
                          error=repr(e))
            raise
        finally:
            m.counter(metrics_mod.SLAVE_STREAM_CLOSED).increment()

    def StartAsync(self, request, context):  # noqa: N802
        self.w.start_async(
            codec.decode_tensor(request.weights),
            np.fromiter(request.samples, dtype=np.int64),
            request.batch_size,
            request.learning_rate,
            optimizer=request.optimizer,
            momentum=request.momentum,
        )
        return pb.Ack()

    def StopAsync(self, request, context):  # noqa: N802
        self.w.stop_async()
        return pb.Ack()

    def UpdateGrad(self, request, context):  # noqa: N802
        self.w.apply_delta(codec.decode_grad(request))
        return pb.Ack()

    def Metrics(self, request, context):  # noqa: N802
        # cluster telemetry scrape (telemetry/aggregate.py): pull-only —
        # serving the snapshot costs nothing until a master scrapes, so
        # the method needs no knob
        from distributed_sgd_tpu.telemetry.aggregate import snapshot_metrics

        return snapshot_metrics(self.w.metrics, role="worker",
                                node=self.w.node_label)
