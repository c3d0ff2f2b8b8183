"""Typed configuration with ``DSGD_*`` environment overrides.

Mirrors the reference's 17-field pureconfig case class
(utils/Config.scala:3-21) and its per-key env override scheme
(src/main/resources/application.conf:1-52).  Role selection follows the
reference (Main.scala:122-159): if ``master_host``/``master_port`` are unset
the process runs an in-process dev cluster; if they equal the node's own
host/port the process is the master; otherwise it is a worker.

Capability supersets over the reference (documented, opt-in):
``model`` (hinge | logistic | least_squares), ``checkpoint_dir`` (orbax),
``async_mode`` (gossip | local_sgd), ``sync_period`` for on-mesh
local-SGD, ``feature_shards`` for dp x tp tensor parallelism over a 2-D
mesh (parallel/feature_sharded.py).
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Optional


def _env(name: str, default, cast):
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    if cast is bool:
        return raw.strip().lower() in ("1", "true", "yes", "on")
    return cast(raw)


def _env_gate(name: str) -> bool:
    """A boolean env gate that refuses anything but a boolean word: the
    variable used to name a directory, and silently reading a path as
    "off" would drop the warmup an operator asked for."""
    raw = (os.environ.get(name) or "").strip().lower()
    if raw in ("", "0", "false", "no", "off"):
        return False
    if raw in ("1", "true", "yes", "on"):
        return True
    raise ValueError(
        f"{name}={os.environ[name]!r}: the variable is an on/off gate for "
        f"the AOT warmup pass and no longer names a directory — set "
        f"JAX_COMPILATION_CACHE_DIR=<dir> to place the cache and "
        f"{name}=1 to warm it")


@dataclass
class Config:
    # -- reference-parity fields (utils/Config.scala:3-21) ------------------
    host: str = "127.0.0.1"
    port: int = 4000
    master_host: Optional[str] = None
    master_port: Optional[int] = None
    batch_size: int = 100
    learning_rate: float = 0.5
    lam: float = 1e-5  # `lambda` in the reference; keyword in Python
    node_count: int = 3
    full: bool = False
    use_async: bool = False  # `async` in the reference; keyword in Python
    record: bool = False
    data_path: str = "data"
    max_epochs: int = 10
    check_every: int = 100
    leaky_loss: float = 0.9
    conv_delta: float = 0.01
    patience: int = 5

    # -- TPU-native extensions ---------------------------------------------
    model: str = "hinge"  # hinge | svm | logistic | least_squares
    seed: int = 0
    engine: str = "mesh"  # mesh (XLA collectives) | rpc (gRPC parity topology)
    async_mode: str = "gossip"  # gossip | local_sgd
    sync_period: int = 16  # local-SGD averaging period (steps)
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 1  # sync-trainer epoch cadence
    heartbeat_s: Optional[float] = None  # master worker-failure detection period
    # consecutive heartbeat misses before eviction (was hardcoded 3 in
    # core/master.py:_heartbeat_loop; docs/FAULT_TOLERANCE.md)
    heartbeat_max_misses: int = 3
    # -- chaos-hardened sync training (docs/FAULT_TOLERANCE.md) ------------
    # quorum: rpc sync fits proceed once `quorum` of N gradient replies are
    # in hand and the straggler soft deadline fired, hedging the missing
    # workers' data slices to fast responders (Chen et al. 2016's backup-
    # replica shape).  None (default) keeps the full barrier — wire and
    # call graph byte-identical to the quorum-less engine.
    quorum: Optional[int] = None
    # soft deadline (seconds) before a quorum round degrades / a stall is
    # counted; None = p95-adaptive from the per-worker reply-latency EWMA
    straggler_soft_s: Optional[float] = None
    # deterministic fault-injection plan applied to every RPC edge of this
    # process (chaos/), e.g.
    # "seed=7;drop=0.05;delay=20ms~200ms;dup=0.01;partition=w2:10s@30s";
    # None/empty = no injection (and no wrapping at all)
    chaos: Optional[str] = None
    # -- distributed tracing + flight recorder (docs/OBSERVABILITY.md) -----
    # trace: per-round span timelines across master/worker/serving with
    # Chrome/Perfetto export (trace/).  Default off; the off path is a
    # provably zero-cost no-op (no span objects are ever allocated) and
    # the wire stays byte-identical either way (context rides gRPC
    # metadata, never the proto).
    trace: bool = False
    # per-process trace files land here (also the flight-recorder dump
    # dir); None with trace=1 defaults to ./dsgd-traces
    trace_dir: Optional[str] = None
    # per-trace_id head sampling in [0, 1]: a sampled round is traced end
    # to end on every node; 1.0 = trace everything
    trace_sample: float = 1.0
    # flight recorder ring capacity (events kept per process for the
    # post-mortem dumps: SIGUSR2, eviction, below-quorum, loop crash);
    # 0 disables recording entirely
    flight_recorder: int = 512
    # -- elastic membership + crash-safe training state (docs/ELASTICITY.md)
    # gossip topology for the async delta plane: all (reference full
    # fan-out, byte-identical default) | ring | random:k — deterministic
    # sparse peer selection per (dispatch, worker) with breaker-aware
    # reselection; the master always receives every delta
    gossip_topology: str = "all"
    # elastic async membership: resplit + re-issue assignments on ANY
    # membership change (join or leave) mid-StartAsync; off keeps the
    # merge-into-survivors eviction path and mid-fit joins idle
    elastic: bool = False
    # batch-drain master inbox: buffer async UpdateGrads and apply one
    # summed update per drain instead of one jitted apply per message
    async_drain: bool = False
    # crash-safe fit-state cadence: snapshot the FULL sync-fit loop state
    # (weights/opt/RNG/epoch/window cursor/fit-token lineage) atomically
    # every N successful windows into checkpoint_dir; 0 disables.  A
    # restarted master resumes bit-exactly from the last snapshot.
    fit_ckpt_every: int = 0
    # -- cluster telemetry plane + training-health monitor (telemetry/) ----
    # telemetry: the master scrapes every registered worker's instrument
    # registry over the Metrics RPC (heartbeat-piggybacked + on-demand)
    # and re-exports the merged series — counters summed, histogram
    # buckets summed exactly, gauges last-write per worker label — on ONE
    # cluster-level /metrics endpoint; workers additionally publish the
    # training-health gauges (gradient norm, dispatch staleness, EF
    # residual norm).  Off (default): no Metrics RPC is ever issued and
    # the wire/call graph stay byte-identical (rpc engine only; the mesh
    # engines are one process — their existing exporter IS cluster-level).
    telemetry: bool = False
    # cluster /metrics bind port on the master (0 = OS-assigned)
    telemetry_port: int = 9091
    # loss-trend watchdog on rpc sync fits (telemetry/health.py): None
    # (default) = no health observation at all; warn = log + flight dump
    # on trip; snapshot = additionally write a resumable fit-state
    # snapshot (needs DSGD_CHECKPOINT_DIR); halt = snapshot, then stop
    # the fit — a dying run leaves evidence and a checkpoint instead of
    # a flat loss curve.
    health_action: Optional[str] = None
    # -- long-horizon resource plane (telemetry/resources.py, ISSUE 20) ----
    # resource-probe cadence in seconds: a dependency-free daemon thread
    # samples /proc/self/{statm,fd,status}, gc stats, and the internal
    # pressure gauges (drain inbox, trace buffer, flight ring, admission
    # queue, compile cache) into proc.* gauges, and feeds the leak-slope
    # sentinel (Theil–Sen over each series; a trip routes through
    # health_action).  0 (default): no probe thread, no proc.* gauges, no
    # blackbox files — knobs-off byte-identical.
    resource_probe_s: float = 0.0
    # crash-surviving blackbox ring dir (telemetry/blackbox.py): each probe
    # tick appends a JSONL snapshot (resources + counters + round cursor)
    # to bounded, atomically-rotated segments; read post-mortem with
    # `python -m distributed_sgd_tpu.telemetry.blackbox`.  Requires
    # resource_probe_s > 0 (the probe is the only writer).
    blackbox_dir: Optional[str] = None
    metrics_port: Optional[int] = None  # Prometheus-style text exporter
    # InfluxDB write endpoint for the push reporter (reference parity:
    # Kamon InfluxDBReporter, application.conf:54-78), e.g.
    # http://influxdb:8086/write?db=dsgd — active when record=true
    influx_url: Optional[str] = None
    profile_dir: Optional[str] = None  # jax.profiler trace output
    pad_width: Optional[int] = None  # sparse-batch nnz padding (None = auto)
    # sparse kernel family of every engine: 'auto' (default) asks the one
    # rule on feature count, row width and platform (ops/kernels.py: one-hot
    # matmuls up to ~2e5 features, the true gather beyond; off the TPU
    # Hogwild and the rpc worker stay scalar); a family's name pins it
    kernel: str = "auto"  # auto | mxu | scalar | gather
    # the model's regulariser (models/linear.py): None = 'dim_sparsity'
    # where the data brings its sidecar (reference parity), else 'l2'
    regularizer: Optional[str] = None  # dim_sparsity | l2 | none
    # which labels a fit takes from the qrels file: 'ccat', the reference's
    # one bit a document (Dataset.scala:36-45), or 'topics', every topic
    # code at once as a model with one output a code (W[D, C]; the mesh
    # sync engine only, under 'l2' unless `regularizer` says 'none').
    # 'lists': `data_path`/train.txt in the Extreme Classification
    # Repository's text format (data/multilabel.py), every label of the
    # file an output, a row's labels kept as the ids of its positives
    labels: str = "ccat"  # ccat | topics | lists
    virtual_workers: int = 1  # reference workers emulated per mesh device
    exact_topology: bool = False  # insist on exactly node_count workers
    # sgd (reference) | momentum | adam (sync engine) | ftrl: per-coordinate
    # FTRL-Proximal (ops/ftrl.py; the mesh sync engine only), learning_rate
    # its alpha and lam its L2 strength
    optimizer: str = "sgd"
    momentum: float = 0.9  # used by optimizer='momentum'
    l1: float = 0.0  # optimizer='ftrl': the L1 strength (exact zeros)
    steps_per_dispatch: int = 1  # async: k local steps per gossip dispatch
    # gradient compression on the wire paths (compress/, docs/COMPRESSION.md):
    # sync Gradient replies + async delta gossip.  'none' keeps the wire
    # byte-identical to the uncompressed tree; 'topk' ships the compress_k
    # largest-magnitude coordinates with error feedback; 'qint8' ships
    # stochastically-rounded int8 with per-chunk scales.  In-mesh engines
    # (XLA collectives, no wire) ignore these with a warning.
    compress: str = "none"  # none | topk | qint8
    compress_k: float = 0.01  # topk size: fraction of dim if < 1, count if >= 1
    compress_ef: bool = True  # error-feedback residual accumulation
    # pipelined sync RPC engine (docs/SYNC_PIPELINE.md; engine=rpc sync fits
    # only — the mesh engines have no wire, async has no barrier).  Both
    # default off: the default wire stays byte-identical to the seed.
    # local_steps=K runs K device-side SGD steps per round on each worker
    # (K x fewer barriers/broadcasts per epoch, local-SGD semantics);
    # delta_broadcast replaces the per-window full dense weight broadcast
    # with versioned sparse deltas over worker-side replica caches, with
    # automatic full-broadcast fallback on any mismatch.
    local_steps: int = 1  # sync rpc: K local SGD steps per round
    delta_broadcast: bool = False  # sync rpc: versioned sparse weight broadcasts
    # streaming RPC fan-out (docs/SYNC_PIPELINE.md "Streaming transport"):
    # sync Gradient requests/replies ride ONE persistent bidirectional
    # FitStream per (master, worker) pair instead of one unary call per
    # worker per round, with the encode-ahead thread pre-staging each
    # worker's next request frame.  Bit-identical math (the rpc bench
    # gates drift 0.0); a broken stream falls back to unary per worker
    # (breaker-fed), and older worker binaries answering UNIMPLEMENTED
    # stay unary (mixed fleets keep working).  Off (default): no Frame is
    # ever constructed and the wire stays byte-identical to the seed.
    stream: bool = False  # sync rpc: persistent per-worker gradient streams
    # O(N) master plane (docs/SCALING.md; engine=rpc sync fits only).
    # Both default off: the default fan-in decode and dispatch call graphs
    # stay byte-identical to the serialized master.
    # fanin_lanes=K shards the fan-in DECODE into K lanes — each reply's
    # wire->ndarray parse runs in its own gRPC arrival callback instead of
    # queueing on one decoder lock, while the float accumulation stays one
    # send-ordered chain (weights byte-identical to K=0, asserted).
    fanin_lanes: int = 0
    # stage_pool=P stages round t+1's dispatch during round t's barrier on
    # a P-thread pool: every worker's sample draw (determinism-safe) and
    # request build (weight arm attached) leave the dispatch critical
    # path, for stream and unary fits alike.
    stage_pool: int = 0
    # aggregation tree (aggtree/, docs/AGGREGATION.md): "fanout:F" elects
    # sub-aggregator reduce nodes so the master's fan-in terminates
    # O(F) subtree sums instead of O(N) replies.  "" (default): flat
    # fan-in — no plan built, no reducer constructed, wire byte-identical.
    agg_tree: str = ""
    # feature-sharded master plane (shardedps/, docs/MASTER_SHARDING.md;
    # engine=rpc sync fits only): M >= 1 range-partitions the weight
    # vector across M master shard lanes — per-shard broadcast and
    # fan-in, global step bit-identical to the flat plane.  Composes with
    # delta_broadcast and agg_tree (one shard-colored tree per lane);
    # incompatible with stream / quorum / local_steps>1 / fanin_lanes /
    # stage_pool / compress (validated below).  0 (default): no shard
    # plan built, no shard instrument registered, wire byte-identical.
    master_shards: int = 0
    # tensor parallelism: shard the blocked weight rows over F feature
    # shards (parallel/feature_sharded.py; dev-mode sync scenario only —
    # needs workers x F devices).  1 = the 1-D DP engines (default)
    feature_shards: int = 1
    # -- elastic spin-up fast path (compile_cache.py, data/row_store.py;
    # docs/HIERARCHY.md "Elastic composition") --------------------------
    # AOT warmup: pre-compile each role's flagship shapes on a background
    # thread at bind/build time, so a joining worker / restarted master /
    # fresh serve replica never JITs under traffic.  A gate only — the
    # persistent cache itself is always on under main.py and its
    # directory is placed from outside (JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache; compile_cache.place).
    compile_cache: bool = False
    # neighbor-range over-provisioning for host-local slices: each
    # worker loads ceil(f * slice) extra rows on both sides, so an
    # elastic resplit within the margin costs ZERO reload and a bigger
    # shift re-loads only the uncovered delta through its RowReader.
    # 0 (default) keeps exact-slice loading byte-identical.
    host_overprovision: float = 0.0
    # mmap row store (data/row_store.py): path to a packed binary corpus
    # built once from the parser (build_from_corpus).  A worker role with
    # a store maps it instead of parsing, and with host_index loads ONLY
    # its slice — the real-corpus no-egress host-local loading path.
    row_store: Optional[str] = None
    # this worker's position in the master's node_count-way contiguous
    # split (worker role + row_store): load rows host_slice(train_rows,
    # host_index, node_count) through the store's reader.  None = the
    # full train split is resident (ids pass through untouched).
    host_index: Optional[int] = None
    # hierarchical multi-host training (docs/HIERARCHY.md, engine=rpc):
    # each RPC worker becomes a D-device host — Gradient/local-window
    # batches shard over a local mesh and reduce with one in-host psum,
    # so the cross-host plane (delta broadcasts, compression, quorum)
    # runs per HOST instead of per device.  1 (default) = the flat
    # single-device worker, byte-identical wire and weights; 0 = auto
    # (jax.local_device_count(), resolved at role start-up).
    host_devices: int = 1

    # -- serving roles (serving/; docs/SERVING.md) -------------------------
    # DSGD_ROLE overrides the master_host/master_port-derived role below;
    # 'serve' (a replica / single node) and 'route' (the fleet router) are
    # the roles with no derivation rule (neither has a place in the
    # training topology), the other three make an implicit deployment
    # explicit.  None = derive (reference behavior).
    role_override: Optional[str] = None
    serve_port: int = 4100  # gRPC dsgd.Serving bind port (replica OR router)
    serve_max_batch: int = 64  # micro-batch flush size cap
    serve_max_delay_ms: float = 5.0  # coalescing window from oldest queued row
    serve_queue_depth: int = 256  # admission bound -> RESOURCE_EXHAUSTED
    serve_ckpt_poll_s: float = 2.0  # checkpoint hot-reload poll period
    # -- serving fleet (serving/router.py + serving/push.py) ---------------
    # All default-off: with every knob below unset, role=serve builds the
    # single-node server byte-identical to the pre-fleet subsystem
    # (asserted by tests/test_router.py).
    # role=serve only: N in-process replicas behind an in-process router
    # on serve_port (the one-machine fleet; kube runs real pods instead).
    # 0 = the single-node server.
    serve_replicas: int = 0
    # role=route: the replica endpoints to balance over, 'host:port,...'
    # (kube/serve.yaml lists the StatefulSet pod DNS names here)
    serve_targets: Optional[str] = None
    # master/dev roles: fleet endpoints (typically the ROUTER) the
    # trainer's checkpoint distributor streams weight deltas to
    # (serving/push.py CheckpointDistributor); needs DSGD_CHECKPOINT_DIR
    serve_push: Optional[str] = None
    # canary fraction of the fleet a pushed version lands on first; the
    # router promotes it fleet-wide only when the probe-set loss does not
    # regress vs the promoted baseline (0 = no canary gate)
    serve_canary: float = 0.0
    # held-out probe set for the canary gate: an .npz with padded 2-D
    # indices/values + 1-D labels (serving/router.py load_probe)
    serve_probe: Optional[str] = None
    # hedge deadline: a routed Predict slower than this races a duplicate
    # on the next-best replica, first success wins (0 = no hedging)
    serve_hedge_ms: float = 0.0
    serve_health_s: float = 1.0  # router ServeHealth poll period
    # promoted-state persistence (serving/router.py): a JSON sidecar the
    # router rewrites on every promote/rollback, so a RESTARTED router
    # re-pins the already-promoted serving version (and keeps its probe
    # baseline + rejected set) instead of re-canarying it.  None
    # (default): router state is in-memory only, byte-identical behavior.
    serve_state: Optional[str] = None
    # canary probe-set refresh cadence (seconds; docs/SERVING.md): with
    # f > 0 the router re-reads DSGD_SERVE_PROBE every f seconds (mtime-
    # gated) and rotates the fresh held-out rows in, re-anchoring the
    # canary baseline on the PROMOTED version's loss over the new rows —
    # a long-running fleet's gate tracks live traffic instead of
    # fossilizing on the rows it started with.  0 (default): the probe
    # set and baseline are fixed at fleet start, byte-identical behavior.
    serve_probe_refresh_s: float = 0.0
    # serving-plane HA (serving/ha.py; docs/SERVING.md "HA"): peer LIVE
    # router endpoints this router syncs promoted state with, as
    # 'peers:<host:port,...>[;self=<host:port>][;sync=<dur>][;ttl=<dur>]
    # [;lease=<path>]'.  One router holds the decider lease for promote/
    # rollback verdicts; the others mirror every transition over the
    # SyncServeState RPC within one sync interval and assume the lease if
    # it lapses.  None (default): single-router plane, no sync RPC ever
    # issued, byte-identical serving wire.
    serve_ha: Optional[str] = None
    # load-adaptive replica autoscale SLO in milliseconds (serving/ha.py
    # ReplicaAutoscaler; fleet mode, role=serve + serve_replicas > 0):
    # when the router's worst eligible-replica load signal (EWMA latency
    # x in-flight) sits over this for consecutive ticks, a replica spins
    # up through the warm boot path; sustained idle drains one.  0
    # (default): fixed fleet size.
    serve_slo_ms: float = 0.0
    # autoscale fleet-size ceiling (floor is the boot size)
    serve_scale_max: int = 8
    # dead time after every autoscale action: hysteresis against flapping
    serve_scale_cooldown_s: float = 5.0

    # -- continual-learning autopilot (autopilot/; docs/CONTINUAL.md) -------
    # All default-off: with DSGD_AUTOPILOT unset no autopilot thread
    # starts, no reservoir attaches to the router, no new instrument
    # registers, and serving wire + training weights stay byte-identical
    # (asserted by tests/test_flywheel.py).
    # master-of-switch: arm the flywheel.  dev role runs the full loop
    # (probe sourcing + drift detection + warm-start retrain through the
    # canary gate); route role attaches probe sourcing + the refresh
    # cadence to the router (the drift SIGNAL, readable over /metrics);
    # master role makes the retrain entry available.  serve/worker roles
    # have no flywheel half and reject the knob at construction.
    autopilot: bool = False
    autopilot_poll_s: float = 1.0  # controller probe-loss poll period
    autopilot_cooldown_s: float = 5.0  # post-verdict settle before re-arming
    # drift rule (controller.DriftDetector, the HealthMonitor shape):
    # EWMA(probe loss) > max(ratio * baseline, baseline + floor) for
    # `patience` consecutive refreshes after `warmup` — the floor keeps
    # the bounded-probe sampling noise (a capacity-row mean quantizes
    # loss in 1/capacity steps) from ever clearing the ratio bar when
    # the baseline lands near zero
    autopilot_drift_ratio: float = 1.5
    autopilot_drift_patience: int = 2
    autopilot_drift_warmup: int = 4
    autopilot_drift_floor: float = 0.1
    # retrain window: the newest N stream rows the warm-start fit trains
    # on (autopilot/stream.window_split — "the current distribution")
    autopilot_window: int = 4096
    autopilot_max_retrains: int = 0  # 0 = unbounded; N caps the flywheel
    autopilot_canary_timeout_s: float = 120.0  # verdict wait before giving up
    # residual settling: after a promotion re-anchors the detector, keep
    # retraining while EWMA(probe loss) stays above band * the pre-trip
    # healthy baseline — a retrain window that straddled the shift only
    # half-recovers, and the rebase would otherwise normalize the
    # plateau.  Must exceed 1; 0 disables (one retrain per trip).
    autopilot_recovery_band: float = 1.35
    # live probe sourcing (autopilot/probe_source.py): reservoir capacity,
    # the label-delay model (ground truth arrives `label_delay` requests
    # late), and the cadence at which the sampled rows rotate in as the
    # canary probe set (each rotation re-probes the promoted version —
    # the drift signal's sample rate)
    autopilot_probe_capacity: int = 64
    autopilot_label_delay: int = 0
    autopilot_source_refresh_s: float = 2.0

    _CHOICES = {
        "model": ("hinge", "svm", "logistic", "squared_hinge", "least_squares"),
        "engine": ("mesh", "rpc"),
        "async_mode": ("gossip", "local_sgd"),
        # 'dense' is auto-selected from the data layout, never configured
        "kernel": ("auto", "mxu", "scalar", "gather"),
        "regularizer": (None, "dim_sparsity", "l2", "none"),
        "labels": ("ccat", "topics", "lists"),
        "optimizer": ("sgd", "momentum", "adam", "ftrl"),
        "compress": ("none", "topk", "qint8"),
    }

    def __post_init__(self):
        for name, choices in self._CHOICES.items():
            v = getattr(self, name)
            if v not in choices:
                raise ValueError(
                    f"config field {name}={v!r} must be one of {choices}"
                )
        if self.virtual_workers < 1:
            raise ValueError("virtual_workers must be >= 1")
        if self.heartbeat_max_misses < 1:
            raise ValueError("heartbeat_max_misses must be >= 1")
        if self.quorum is not None and self.quorum < 1:
            raise ValueError("quorum must be >= 1 (or unset for a full barrier)")
        if self.straggler_soft_s is not None and self.straggler_soft_s <= 0:
            raise ValueError("straggler_soft_s must be > 0 (or unset for adaptive)")
        if self.chaos:
            # fail typos at construction, not mid-fit: the plan grammar is
            # owned by chaos.parse_plan
            from distributed_sgd_tpu.chaos import parse_plan

            parse_plan(self.chaos)
        if self.agg_tree:
            # same discipline: the tree grammar is owned by aggtree.plan
            from distributed_sgd_tpu.aggtree import parse_agg_tree

            parse_agg_tree(self.agg_tree)
        # shard-count grammar owned by shardedps.plan; the composition
        # matrix (docs/MASTER_SHARDING.md) is enforced at construction so
        # an incompatible pair fails here, not windows into a fit
        from distributed_sgd_tpu.shardedps import parse_master_shards

        if parse_master_shards(self.master_shards):
            for bad, knob in ((self.stream, "DSGD_STREAM"),
                              (self.quorum is not None, "DSGD_QUORUM"),
                              (self.local_steps > 1, "DSGD_LOCAL_STEPS"),
                              (self.fanin_lanes > 0, "DSGD_FANIN_LANES"),
                              (self.stage_pool > 0, "DSGD_STAGE_POOL"),
                              (self.compress != "none", "DSGD_COMPRESS")):
                if bad:
                    raise ValueError(
                        f"DSGD_MASTER_SHARDS does not compose with {knob} "
                        f"(docs/MASTER_SHARDING.md composition table)")
        # fail topology typos at construction; grammar owned by
        # parallel/topology.parse_topology
        from distributed_sgd_tpu.parallel.topology import parse_topology

        parse_topology(self.gossip_topology)
        if self.fit_ckpt_every < 0:
            raise ValueError("fit_ckpt_every must be >= 0 (0 disables)")
        if self.fit_ckpt_every > 0 and not self.checkpoint_dir:
            raise ValueError(
                "DSGD_FIT_CKPT_EVERY needs DSGD_CHECKPOINT_DIR: the crash "
                "snapshot lives under the checkpoint directory")
        if not 0.0 <= self.trace_sample <= 1.0:
            raise ValueError("trace_sample must be a probability in [0, 1]")
        if self.telemetry_port < 0:
            raise ValueError("telemetry_port must be >= 0 (0 = OS-assigned)")
        if self.health_action not in (None, "warn", "snapshot", "halt"):
            raise ValueError(
                f"DSGD_HEALTH_ACTION={self.health_action!r} must be one of "
                f"warn | snapshot | halt (unset = no health monitor)")
        if (self.health_action in ("snapshot", "halt")
                and not self.checkpoint_dir):
            raise ValueError(
                f"DSGD_HEALTH_ACTION={self.health_action} needs "
                f"DSGD_CHECKPOINT_DIR: the resumable trip snapshot lives "
                f"under the checkpoint directory")
        if self.flight_recorder < 0:
            raise ValueError("flight_recorder must be >= 0 (0 disables)")
        if self.resource_probe_s < 0:
            raise ValueError(
                "DSGD_RESOURCE_PROBE_S must be >= 0 (0 = no resource probe)")
        if self.blackbox_dir and self.resource_probe_s <= 0:
            raise ValueError(
                "DSGD_BLACKBOX_DIR needs DSGD_RESOURCE_PROBE_S > 0: the "
                "resource probe is the blackbox's only writer")
        if self.checkpoint_every < 1:
            raise ValueError("checkpoint_every must be >= 1")
        if self.steps_per_dispatch < 1:
            raise ValueError("steps_per_dispatch must be >= 1")
        if self.local_steps < 1:
            raise ValueError("local_steps must be >= 1")
        if self.fanin_lanes < 0:
            raise ValueError(
                "DSGD_FANIN_LANES must be >= 0 (0 = single-lock fan-in "
                "decode; K shards the decode into K lanes)")
        if self.stage_pool < 0:
            raise ValueError(
                "DSGD_STAGE_POOL must be >= 0 (0 = draws and request "
                "builds on the dispatch path; P stages them on a P-thread "
                "pool during the previous barrier)")
        if self.compress_k <= 0:
            raise ValueError("compress_k must be > 0 (fraction of dim or count)")
        if self.feature_shards < 1:
            raise ValueError("feature_shards must be >= 1")
        if self.host_devices < 0:
            raise ValueError(
                "host_devices must be >= 0 (0 = auto from "
                "jax.local_device_count(); 1 = flat single-device worker)")
        # -- elastic spin-up fast path --------------------------------------
        if not 0.0 <= self.host_overprovision <= 1.0:
            raise ValueError(
                "DSGD_HOST_OVERPROVISION must be a fraction in [0, 1] "
                "(0 = exact slices; f loads ceil(f * slice) neighbor rows "
                "on each side)")
        if self.host_index is not None:
            if not self.row_store:
                raise ValueError(
                    "DSGD_HOST_INDEX needs DSGD_ROW_STORE: a host-local "
                    "slice is loaded through the store's row reader (the "
                    "full-parse path always materializes the corpus)")
            if not 0 <= self.host_index < self.node_count:
                raise ValueError(
                    f"DSGD_HOST_INDEX={self.host_index} outside "
                    f"[0, node_count={self.node_count})")
        if self.host_index is not None and self.host_devices not in (0, 1):
            raise ValueError(
                "DSGD_HOST_INDEX with DSGD_HOST_DEVICES > 1 is not "
                "supported yet: the in-host mesh binds its slice at build "
                "time (no incremental reload)")
        if self.feature_shards > 1 and self.use_async:
            raise ValueError(
                "feature_shards is a sync (2-D mesh) engine; it cannot be "
                "combined with use_async"
            )
        if self.feature_shards > 1 and self.engine == "rpc":
            raise ValueError(
                "feature_shards needs the mesh engine (2-D shard_map); the "
                "rpc topology has no feature axis"
            )
        if self.feature_shards > 1 and self.optimizer != "sgd":
            raise ValueError(
                "the feature-sharded engine runs the reference's plain SGD "
                "update; optimizer must be 'sgd' when feature_shards > 1"
            )
        if self.optimizer == "ftrl" and (self.use_async or self.engine != "mesh"):
            raise ValueError(
                "optimizer='ftrl' carries its state (z, n) in the mesh sync engine "
                "only: engine='mesh' without use_async")
        if self.l1 < 0:
            raise ValueError(f"l1 must be >= 0, got {self.l1}")
        if self.exact_topology and self.virtual_workers != 1:
            raise ValueError(
                "exact_topology and an explicit virtual_workers are mutually "
                "exclusive: virtual_workers pins the per-device emulation "
                "directly, so the exact-topology solver would be ignored"
            )
        if self.role_override not in (None, "dev", "master", "worker",
                                      "serve", "route"):
            raise ValueError(
                f"DSGD_ROLE={self.role_override!r} must be one of "
                f"dev | master | worker | serve | route (unset = derive from "
                f"master_host/master_port)"
            )
        if self.role_override == "serve" and not self.checkpoint_dir:
            raise ValueError(
                "role=serve needs checkpoint_dir (DSGD_CHECKPOINT_DIR): "
                "serving loads and hot-reloads the trainer's checkpoints"
            )
        if self.serve_max_batch < 1:
            raise ValueError("serve_max_batch must be >= 1")
        if self.serve_max_delay_ms < 0:
            raise ValueError("serve_max_delay_ms must be >= 0")
        if self.serve_queue_depth < 1:
            raise ValueError("serve_queue_depth must be >= 1")
        if self.serve_ckpt_poll_s <= 0:
            raise ValueError("serve_ckpt_poll_s must be > 0")
        # -- serving fleet (docs/SERVING.md "serving fleet") ----------------
        if self.serve_replicas < 0:
            raise ValueError("serve_replicas must be >= 0 (0 = single node)")
        if self.role_override == "route" and not self.serve_targets:
            raise ValueError(
                "role=route needs DSGD_SERVE_TARGETS: the router balances "
                "over an explicit replica endpoint list (host:port,...)")
        for spec in (self.serve_targets, self.serve_push):
            if spec:
                # fail endpoint-list typos at construction, not mid-route;
                # grammar owned by serving.push.parse_targets
                from distributed_sgd_tpu.serving.push import parse_targets

                parse_targets(spec)
        if self.serve_push and not self.checkpoint_dir:
            raise ValueError(
                "DSGD_SERVE_PUSH needs DSGD_CHECKPOINT_DIR: the checkpoint "
                "distributor watches the trainer's checkpoint directory")
        if not 0.0 <= self.serve_canary <= 1.0:
            raise ValueError("serve_canary must be a fraction in [0, 1]")
        if (self.serve_canary > 0 and not self.serve_probe
                and self.role_override in ("route", "serve")):
            # an armed canary with nothing to evaluate would silently
            # promote every version ungated — the operator believes a
            # gate exists; fail at construction like every other
            # cross-field dependency (fleet APIs pass probe rows
            # directly, so only the env-driven roles need the pairing)
            raise ValueError(
                "DSGD_SERVE_CANARY > 0 needs DSGD_SERVE_PROBE: the canary "
                "gate evaluates pushed versions against a held-out probe "
                "set (docs/SERVING.md)")
        if self.serve_hedge_ms < 0:
            raise ValueError("serve_hedge_ms must be >= 0 (0 = no hedging)")
        if self.serve_health_s <= 0:
            raise ValueError("serve_health_s must be > 0")
        if self.serve_probe_refresh_s < 0:
            raise ValueError(
                "DSGD_SERVE_PROBE_REFRESH_S must be >= 0 (0 = fixed probe "
                "set)")
        if (self.serve_probe_refresh_s > 0 and not self.serve_probe
                and self.role_override in ("route", "serve")):
            raise ValueError(
                "DSGD_SERVE_PROBE_REFRESH_S > 0 needs DSGD_SERVE_PROBE: "
                "the refresh re-reads the probe file on its cadence "
                "(docs/SERVING.md)")
        # -- serving-plane HA + autoscale (docs/SERVING.md "HA") ------------
        if self.serve_ha:
            if self.role_override != "route":
                raise ValueError(
                    "DSGD_SERVE_HA is a router knob (DSGD_ROLE=route): "
                    "peer promoted-state sync runs between LIVE routers")
            # fail spec typos at construction, not on the first sync;
            # grammar owned by serving.ha.parse_ha_spec
            from distributed_sgd_tpu.serving.ha import parse_ha_spec

            parse_ha_spec(self.serve_ha)
        if self.serve_slo_ms < 0:
            raise ValueError(
                "DSGD_SERVE_SLO_MS must be >= 0 (0 = autoscale off)")
        if (self.serve_slo_ms > 0 and self.role_override == "serve"
                and self.serve_replicas < 1):
            raise ValueError(
                "DSGD_SERVE_SLO_MS needs the fleet mode "
                "(DSGD_SERVE_REPLICAS >= 1): the autoscaler grows and "
                "shrinks an in-process replica fleet")
        if self.serve_scale_max < 1:
            raise ValueError("DSGD_SERVE_SCALE_MAX must be >= 1")
        if (self.serve_slo_ms > 0
                and self.serve_scale_max < max(1, self.serve_replicas)):
            raise ValueError(
                "DSGD_SERVE_SCALE_MAX must be >= the boot fleet size "
                "(DSGD_SERVE_REPLICAS): the boot size is the scale floor")
        if self.serve_scale_cooldown_s < 0:
            raise ValueError("DSGD_SERVE_SCALE_COOLDOWN_S must be >= 0")
        # -- continual-learning autopilot (docs/CONTINUAL.md) ---------------
        if self.autopilot_poll_s <= 0:
            raise ValueError("DSGD_AUTOPILOT_POLL_S must be > 0")
        if self.autopilot_cooldown_s < 0:
            raise ValueError("DSGD_AUTOPILOT_COOLDOWN_S must be >= 0")
        if self.autopilot_drift_ratio <= 1.0:
            raise ValueError(
                "DSGD_AUTOPILOT_DRIFT_RATIO must be > 1 (the drift rule "
                "compares EWMA probe loss against ratio x baseline)")
        if self.autopilot_drift_patience < 1:
            raise ValueError("DSGD_AUTOPILOT_DRIFT_PATIENCE must be >= 1")
        if self.autopilot_drift_warmup < 0:
            raise ValueError("DSGD_AUTOPILOT_DRIFT_WARMUP must be >= 0")
        if self.autopilot_drift_floor < 0:
            raise ValueError("DSGD_AUTOPILOT_DRIFT_FLOOR must be >= 0")
        if self.autopilot_window < 1:
            raise ValueError("DSGD_AUTOPILOT_WINDOW must be >= 1 rows")
        if self.autopilot_max_retrains < 0:
            raise ValueError(
                "DSGD_AUTOPILOT_MAX_RETRAINS must be >= 0 (0 = unbounded)")
        if self.autopilot_canary_timeout_s <= 0:
            raise ValueError("DSGD_AUTOPILOT_CANARY_TIMEOUT_S must be > 0")
        if self.autopilot_recovery_band and self.autopilot_recovery_band <= 1:
            raise ValueError(
                "DSGD_AUTOPILOT_RECOVERY_BAND must be > 1 (0 disables "
                "residual settling)")
        if self.autopilot_probe_capacity < 1:
            raise ValueError("DSGD_AUTOPILOT_PROBE_CAPACITY must be >= 1")
        if self.autopilot_label_delay < 0:
            raise ValueError("DSGD_AUTOPILOT_LABEL_DELAY must be >= 0")
        if self.autopilot_source_refresh_s <= 0:
            raise ValueError("DSGD_AUTOPILOT_SOURCE_REFRESH_S must be > 0")
        if self.autopilot and self.role_override in ("serve", "worker"):
            raise ValueError(
                f"DSGD_AUTOPILOT has no {self.role_override} half: the "
                f"flywheel lives in the dev/route/master roles "
                f"(docs/CONTINUAL.md)")
        if self.autopilot and self.serve_probe_refresh_s > 0:
            raise ValueError(
                "DSGD_AUTOPILOT and DSGD_SERVE_PROBE_REFRESH_S are "
                "mutually exclusive: the traffic reservoir REPLACES the "
                "operator-rotated probe file (docs/CONTINUAL.md)")

    @property
    def role(self) -> str:
        """'dev' | 'master' | 'worker' per Main.scala:122-159, or any of
        those plus 'serve' / 'route' when DSGD_ROLE overrides the
        derivation."""
        if self.role_override is not None:
            return self.role_override
        if self.master_host is None or self.master_port is None:
            return "dev"
        if (self.master_host, self.master_port) == (self.host, self.port):
            return "master"
        return "worker"

    @classmethod
    def from_env(cls, **overrides) -> "Config":
        """Build from DSGD_* env vars (application.conf:1-52 names)."""
        cfg = cls(
            host=_env("DSGD_NODE_HOST", cls.host, str),
            port=_env("DSGD_NODE_PORT", cls.port, int),
            master_host=_env("DSGD_MASTER_HOST", None, str),
            master_port=_env("DSGD_MASTER_PORT", None, int),
            batch_size=_env("DSGD_BATCH_SIZE", cls.batch_size, int),
            learning_rate=_env("DSGD_LEARNING_RATE", cls.learning_rate, float),
            lam=_env("DSGD_LAMBDA", cls.lam, float),
            node_count=_env("DSGD_NODE_COUNT", cls.node_count, int),
            full=_env("DSGD_FULL", cls.full, bool),
            use_async=_env("DSGD_ASYNC", cls.use_async, bool),
            record=_env("DSGD_RECORD", cls.record, bool),
            data_path=_env("DSGD_DATA_PATH", cls.data_path, str),
            max_epochs=_env("DSGD_MAX_EPOCHS", cls.max_epochs, int),
            check_every=_env("DSGD_CHECK_EVERY", cls.check_every, int),
            leaky_loss=_env("DSGD_LEAKY_LOSS", cls.leaky_loss, float),
            conv_delta=_env("DSGD_CONV_DELTA", cls.conv_delta, float),
            patience=_env("DSGD_PATIENCE", cls.patience, int),
            model=_env("DSGD_MODEL", cls.model, str),
            seed=_env("DSGD_SEED", cls.seed, int),
            engine=_env("DSGD_ENGINE", cls.engine, str),
            async_mode=_env("DSGD_ASYNC_MODE", cls.async_mode, str),
            sync_period=_env("DSGD_SYNC_PERIOD", cls.sync_period, int),
            checkpoint_dir=_env("DSGD_CHECKPOINT_DIR", None, str),
            checkpoint_every=_env("DSGD_CHECKPOINT_EVERY", cls.checkpoint_every, int),
            heartbeat_s=_env("DSGD_HEARTBEAT_S", None, float),
            heartbeat_max_misses=_env("DSGD_HEARTBEAT_MAX_MISSES",
                                      cls.heartbeat_max_misses, int),
            quorum=_env("DSGD_QUORUM", None, int),
            straggler_soft_s=_env("DSGD_STRAGGLER_SOFT_S", None, float),
            chaos=_env("DSGD_CHAOS", None, str),
            trace=_env("DSGD_TRACE", cls.trace, bool),
            trace_dir=_env("DSGD_TRACE_DIR", None, str),
            trace_sample=_env("DSGD_TRACE_SAMPLE", cls.trace_sample, float),
            flight_recorder=_env("DSGD_FLIGHT_RECORDER",
                                 cls.flight_recorder, int),
            gossip_topology=_env("DSGD_GOSSIP_TOPOLOGY",
                                 cls.gossip_topology, str),
            elastic=_env("DSGD_ELASTIC", cls.elastic, bool),
            async_drain=_env("DSGD_ASYNC_DRAIN", cls.async_drain, bool),
            fit_ckpt_every=_env("DSGD_FIT_CKPT_EVERY", cls.fit_ckpt_every, int),
            telemetry=_env("DSGD_TELEMETRY", cls.telemetry, bool),
            telemetry_port=_env("DSGD_TELEMETRY_PORT", cls.telemetry_port, int),
            health_action=_env("DSGD_HEALTH_ACTION", None, str),
            resource_probe_s=_env("DSGD_RESOURCE_PROBE_S",
                                  cls.resource_probe_s, float),
            blackbox_dir=_env("DSGD_BLACKBOX_DIR", None, str),
            metrics_port=_env("DSGD_METRICS_PORT", None, int),
            influx_url=_env("DSGD_INFLUX_URL", None, str),
            profile_dir=_env("DSGD_PROFILE_DIR", None, str),
            pad_width=_env("DSGD_PAD_WIDTH", None, int),
            kernel=_env("DSGD_KERNEL", cls.kernel, str),
            regularizer=_env("DSGD_REGULARIZER", None, str),
            labels=_env("DSGD_LABELS", cls.labels, str),
            virtual_workers=_env("DSGD_VIRTUAL_WORKERS", cls.virtual_workers, int),
            exact_topology=_env("DSGD_EXACT_TOPOLOGY", cls.exact_topology, bool),
            optimizer=_env("DSGD_OPTIMIZER", cls.optimizer, str),
            momentum=_env("DSGD_MOMENTUM", cls.momentum, float),
            l1=_env("DSGD_FTRL_L1", cls.l1, float),
            steps_per_dispatch=_env("DSGD_STEPS_PER_DISPATCH", cls.steps_per_dispatch, int),
            compress=_env("DSGD_COMPRESS", cls.compress, str),
            compress_k=_env("DSGD_COMPRESS_K", cls.compress_k, float),
            compress_ef=_env("DSGD_COMPRESS_EF", cls.compress_ef, bool),
            local_steps=_env("DSGD_LOCAL_STEPS", cls.local_steps, int),
            delta_broadcast=_env("DSGD_DELTA_BROADCAST", cls.delta_broadcast, bool),
            stream=_env("DSGD_STREAM", cls.stream, bool),
            fanin_lanes=_env("DSGD_FANIN_LANES", cls.fanin_lanes, int),
            stage_pool=_env("DSGD_STAGE_POOL", cls.stage_pool, int),
            agg_tree=_env("DSGD_AGG_TREE", cls.agg_tree, str),
            master_shards=_env("DSGD_MASTER_SHARDS", cls.master_shards, int),
            feature_shards=_env("DSGD_FEATURE_SHARDS", cls.feature_shards, int),
            host_devices=_env("DSGD_HOST_DEVICES", cls.host_devices, int),
            compile_cache=_env_gate("DSGD_COMPILE_CACHE"),
            host_overprovision=_env("DSGD_HOST_OVERPROVISION",
                                    cls.host_overprovision, float),
            row_store=_env("DSGD_ROW_STORE", None, str),
            host_index=_env("DSGD_HOST_INDEX", None, int),
            role_override=_env("DSGD_ROLE", None, str),
            serve_port=_env("DSGD_SERVE_PORT", cls.serve_port, int),
            serve_max_batch=_env("DSGD_SERVE_MAX_BATCH", cls.serve_max_batch, int),
            serve_max_delay_ms=_env("DSGD_SERVE_MAX_DELAY_MS", cls.serve_max_delay_ms, float),
            serve_queue_depth=_env("DSGD_SERVE_QUEUE_DEPTH", cls.serve_queue_depth, int),
            serve_ckpt_poll_s=_env("DSGD_SERVE_CKPT_POLL_S", cls.serve_ckpt_poll_s, float),
            serve_replicas=_env("DSGD_SERVE_REPLICAS", cls.serve_replicas, int),
            serve_targets=_env("DSGD_SERVE_TARGETS", None, str),
            serve_push=_env("DSGD_SERVE_PUSH", None, str),
            serve_canary=_env("DSGD_SERVE_CANARY", cls.serve_canary, float),
            serve_probe=_env("DSGD_SERVE_PROBE", None, str),
            serve_hedge_ms=_env("DSGD_SERVE_HEDGE_MS", cls.serve_hedge_ms, float),
            serve_health_s=_env("DSGD_SERVE_HEALTH_S", cls.serve_health_s, float),
            serve_state=_env("DSGD_SERVE_STATE", None, str),
            serve_probe_refresh_s=_env("DSGD_SERVE_PROBE_REFRESH_S",
                                       cls.serve_probe_refresh_s, float),
            serve_ha=_env("DSGD_SERVE_HA", None, str),
            serve_slo_ms=_env("DSGD_SERVE_SLO_MS", cls.serve_slo_ms, float),
            serve_scale_max=_env("DSGD_SERVE_SCALE_MAX",
                                 cls.serve_scale_max, int),
            serve_scale_cooldown_s=_env("DSGD_SERVE_SCALE_COOLDOWN_S",
                                        cls.serve_scale_cooldown_s, float),
            autopilot=_env("DSGD_AUTOPILOT", cls.autopilot, bool),
            autopilot_poll_s=_env("DSGD_AUTOPILOT_POLL_S",
                                  cls.autopilot_poll_s, float),
            autopilot_cooldown_s=_env("DSGD_AUTOPILOT_COOLDOWN_S",
                                      cls.autopilot_cooldown_s, float),
            autopilot_drift_ratio=_env("DSGD_AUTOPILOT_DRIFT_RATIO",
                                       cls.autopilot_drift_ratio, float),
            autopilot_drift_patience=_env("DSGD_AUTOPILOT_DRIFT_PATIENCE",
                                          cls.autopilot_drift_patience, int),
            autopilot_drift_warmup=_env("DSGD_AUTOPILOT_DRIFT_WARMUP",
                                        cls.autopilot_drift_warmup, int),
            autopilot_drift_floor=_env("DSGD_AUTOPILOT_DRIFT_FLOOR",
                                       cls.autopilot_drift_floor, float),
            autopilot_window=_env("DSGD_AUTOPILOT_WINDOW",
                                  cls.autopilot_window, int),
            autopilot_max_retrains=_env("DSGD_AUTOPILOT_MAX_RETRAINS",
                                        cls.autopilot_max_retrains, int),
            autopilot_recovery_band=_env("DSGD_AUTOPILOT_RECOVERY_BAND",
                                         cls.autopilot_recovery_band, float),
            autopilot_canary_timeout_s=_env(
                "DSGD_AUTOPILOT_CANARY_TIMEOUT_S",
                cls.autopilot_canary_timeout_s, float),
            autopilot_probe_capacity=_env("DSGD_AUTOPILOT_PROBE_CAPACITY",
                                          cls.autopilot_probe_capacity, int),
            autopilot_label_delay=_env("DSGD_AUTOPILOT_LABEL_DELAY",
                                       cls.autopilot_label_delay, int),
            autopilot_source_refresh_s=_env(
                "DSGD_AUTOPILOT_SOURCE_REFRESH_S",
                cls.autopilot_source_refresh_s, float),
        )
        return dataclasses.replace(cfg, **overrides)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "Config":
        return cls(**json.loads(s))
