"""Application entry point: config-driven role selection and scenario.

Mirror of the reference Main (Main.scala:18-159): no CLI flags — behavior
is driven entirely by DSGD_* env config.  Role selection
(Main.scala:122-159):

- master_host/master_port unset        -> dev mode (in-process cluster)
- (master_host, master_port) == self   -> master process
- otherwise                            -> worker process
- DSGD_ROLE overrides the derivation; DSGD_ROLE=serve (the only role with
  no derivation rule) runs the online-inference front end over the
  trainer's checkpoints (serving/, docs/SERVING.md)

Dev mode picks the execution engine via DSGD_ENGINE:

- ``mesh`` (default): the TPU-native fast path — in-mesh collectives
  (parallel/sync.py or parallel/local_sgd.py / parallel/hogwild.py for
  async) with no RPC data plane;
- ``rpc``: reference-parity topology — an in-process gRPC cluster
  (core/cluster.py), master fanning batches out to worker processes'
  servicers exactly like the reference dev mode (Main.scala:143-158).

The scenario (Main.scala:70-120): initial eval at w0 = 0, fit (sync or
async per config), final weights + local test loss/acc logged.

Run: ``python -m distributed_sgd_tpu.main``
"""

from __future__ import annotations

import logging
import os
import socket
import sys

import jax
import numpy as np

from distributed_sgd_tpu.config import Config
from distributed_sgd_tpu.core.early_stopping import no_improvement
from distributed_sgd_tpu.data.multilabel import read_multilabel, to_lists
from distributed_sgd_tpu.data.rcv1 import Dataset, dim_sparsity, load_rcv1, train_test_split
from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.ops.ftrl import Ftrl
from distributed_sgd_tpu.utils import measure
from distributed_sgd_tpu.utils import metrics as metrics_mod
from distributed_sgd_tpu.utils.log import setup as setup_logging

log = logging.getLogger("dsgd.main")


SYNTHETIC_TOPICS = 8  # outputs of the synthetic stand-in for labels='topics'


def load_data(cfg: Config) -> Dataset:
    """RCV1 from cfg.data_path, or synthetic via DSGD_SYNTHETIC=<n> when the
    corpus is absent (no-egress environments)."""
    synthetic = os.environ.get("DSGD_SYNTHETIC")
    lists = cfg.labels == "lists"
    train_file = os.path.join(
        cfg.data_path, "train.txt" if lists else "lyrl2004_vectors_train.dat")
    if synthetic or not os.path.exists(train_file):
        n = int(synthetic or 100_000)
        log.info("%s not found or DSGD_SYNTHETIC set: generating %d synthetic rows",
                 "train.txt" if lists else "RCV1", n)
        # ltc/IDF value weighting, like real RCV1-v2 term weighting — the
        # shipped default lr=0.5 only descends smoothly with it
        # (benches/zipf_oscillation.py, BASELINE.md round 4)
        data = rcv1_like(n, seed=cfg.seed, idf_values=True,
                         n_outputs=1 if cfg.labels == "ccat" else SYNTHETIC_TOPICS)
        if lists:  # the same labels, a row's positives as ids
            return Dataset(data.indices, data.values, to_lists(data.labels)[0],
                           data.n_features, n_labels=SYNTHETIC_TOPICS)
        return data
    if lists:
        return read_multilabel(train_file, pad_width=cfg.pad_width)
    return load_rcv1(cfg.data_path, full=cfg.full, pad_width=cfg.pad_width,
                     labels=cfg.labels)


def build(cfg: Config):
    data = measure.duration_log("data loaded", lambda: load_data(cfg), log)
    train, test = train_test_split(data)
    if data.labels.ndim == 2:
        # every topic at once: one output a label column (or a label the
        # rows' lists index); 'dim_sparsity' masks by one gradient's support
        # and has no form with outputs
        model = make_model(cfg.model, cfg.lam, train.n_features,
                           regularizer=cfg.regularizer or "l2",
                           n_outputs=data.n_labels or data.labels.shape[1])
        return train, test, model
    if cfg.optimizer == "ftrl":  # FTRL takes lam as its L2 strength
        return train, test, make_model(cfg.model, cfg.lam, train.n_features,
                                       regularizer=cfg.regularizer or "l2")
    ds = measure.duration_log("dim sparsity", lambda: dim_sparsity(train), log)
    model = make_model(cfg.model, cfg.lam, train.n_features, dim_sparsity=ds,
                       regularizer=cfg.regularizer)
    return train, test, model


def optimizer_of(cfg: Config):
    """What the mesh engines take as `optimizer=`: the name, or for 'ftrl'
    FTRL-Proximal's own hyperparameters (ops/ftrl.py)."""
    if cfg.optimizer == "ftrl":
        return Ftrl(l1=cfg.l1)
    return cfg.optimizer


def _make_checkpointer(cfg: Config):
    """cfg.checkpoint_dir -> Checkpointer (or None): the sync trainer saves
    at cfg.checkpoint_every epoch cadence and resumes from the latest
    snapshot; async engines persist each new best-weights snapshot via
    their LossChecker and resume from the latest best."""
    if not cfg.checkpoint_dir:
        return None
    from distributed_sgd_tpu.checkpoint import Checkpointer

    return Checkpointer(cfg.checkpoint_dir)


def _restore_weights(ckpt):
    """Latest checkpointed weights (async resume / autopilot warm
    start), or None."""
    if ckpt is None:
        return None
    restored = ckpt.restore_latest()
    if restored is None:
        return None
    step, state = restored
    log.info("warm start from checkpoint at step %d", step)
    return np.asarray(state["weights"])


def select_topology(
    node_count: int, n_devices: int, use_async: bool,
    virtual_workers: int = 1, exact_topology: bool = False,
):
    """(mesh devices, virtual workers per device) for the sync path.

    Cover the full reference worker count even on fewer chips — remaining
    workers are emulated per device (parallel/sync.py virtual_workers).
    Default: use ALL available devices with ceil-division virtual workers —
    the total may exceed node_count by < n_devices, but no device sits
    idle.  exact_topology=True (DSGD_EXACT_TOPOLOGY) instead insists on
    exactly node_count workers via the largest divisor <= n_devices (which
    can idle most of the mesh — e.g. node_count=7 on 6 chips runs 1 chip).
    Async engines ignore virtual_workers, so they always get every device.
    """
    n_max = min(node_count, n_devices)
    virtual = virtual_workers
    if not use_async and virtual == 1 and node_count > n_max:
        if exact_topology:
            n = max(d for d in range(1, n_max + 1) if node_count % d == 0)
            virtual = node_count // n
            if n < n_max:
                log.warning(
                    "exact_topology: shrank the mesh to %d device(s) (the "
                    "largest divisor of node_count=%d that is <= %d; %d "
                    "device(s) idle) to run exactly %d workers",
                    n, node_count, n_max, n_max - n, node_count,
                )
        else:
            n = n_max
            virtual = -(-node_count // n)  # ceil
            if n * virtual != node_count:
                log.warning(
                    "node_count=%d rounded up to %d workers (%d devices x %d "
                    "virtual) to keep every device busy; set "
                    "DSGD_EXACT_TOPOLOGY=1 for exactly node_count workers",
                    node_count, n * virtual, n, virtual,
                )
    else:
        n = n_max
    return n, virtual


def scenario_mesh(cfg: Config, train: Dataset, test: Dataset, model) -> None:
    """Dev-mode fast path: in-mesh engines, no RPC data plane."""
    from distributed_sgd_tpu.parallel.mesh import make_mesh

    n, virtual = select_topology(
        cfg.node_count, len(jax.devices()), cfg.use_async,
        cfg.virtual_workers, cfg.exact_topology,
    )
    mesh = make_mesh(n)
    criterion = no_improvement(patience=cfg.patience, min_delta=cfg.conv_delta)
    if cfg.compress != "none" and not (cfg.use_async and cfg.async_mode == "gossip"):
        # the sync / local-SGD / feature-sharded mesh engines exchange
        # gradients through XLA collectives — there is no wire to compress
        # (docs/COMPRESSION.md "when NOT to compress"); only the gossip
        # engine and the rpc topology honor DSGD_COMPRESS
        log.warning(
            "DSGD_COMPRESS=%s ignored: in-mesh engines have no wire path "
            "(use engine=rpc or async_mode=gossip)", cfg.compress)
    if (cfg.local_steps > 1 or cfg.delta_broadcast or cfg.stream
            or cfg.fanin_lanes or cfg.stage_pool or cfg.agg_tree
            or cfg.master_shards):
        # the pipelined sync levers shape RPC wire traffic; the mesh
        # engines exchange gradients through XLA collectives
        log.warning(
            "DSGD_LOCAL_STEPS/DSGD_DELTA_BROADCAST/DSGD_STREAM/"
            "DSGD_FANIN_LANES/DSGD_STAGE_POOL/DSGD_AGG_TREE/"
            "DSGD_MASTER_SHARDS ignored: the pipelined sync engine is "
            "the rpc topology's (use engine=rpc; the mesh local-SGD "
            "equivalent is async_mode=local_sgd / sync_period)")
    if cfg.quorum is not None or cfg.chaos:
        # quorum barriers gate RPC fan-ins and chaos wraps RPC stubs; an
        # in-mesh XLA collective has neither
        log.warning(
            "DSGD_QUORUM/DSGD_CHAOS ignored: the quorum barrier and the "
            "fault-injection layer live on the rpc topology's wire "
            "(use engine=rpc)")
    if cfg.elastic or cfg.async_drain or cfg.fit_ckpt_every:
        # elastic membership, the batch-drain inbox, and the crash-safe
        # fit-state snapshot all live on the rpc control plane
        log.warning(
            "DSGD_ELASTIC/DSGD_ASYNC_DRAIN/DSGD_FIT_CKPT_EVERY ignored: "
            "the elastic + crash-recovery subsystem is the rpc topology's "
            "(use engine=rpc; docs/ELASTICITY.md)")
    if (cfg.gossip_topology != "all"
            and not (cfg.use_async and cfg.async_mode == "gossip")):
        # only the gossip plane has peer fan-out to sparsify
        log.warning(
            "DSGD_GOSSIP_TOPOLOGY=%s ignored: only the gossip engines "
            "(async_mode=gossip or engine=rpc async) have a peer fan-out",
            cfg.gossip_topology)
    if cfg.telemetry or cfg.health_action:
        # the telemetry plane scrapes over the Metrics RPC and the health
        # monitor rides fit_sync's fan-in; a one-process mesh engine has
        # neither (its existing /metrics exporter IS the cluster view)
        log.warning(
            "DSGD_TELEMETRY/DSGD_HEALTH_ACTION ignored: the cluster "
            "telemetry plane is the rpc topology's (use engine=rpc; "
            "docs/OBSERVABILITY.md)")
    if cfg.host_devices != 1:
        # the mesh engines ARE an all-device mesh already; the in-host
        # psum layer under an RPC plane is the rpc topology's
        log.warning(
            "DSGD_HOST_DEVICES ignored: the mesh engine already spans "
            "every device — the hierarchical in-host layer is the rpc "
            "topology's (use engine=rpc; docs/HIERARCHY.md)")
    log.info(
        "engine=mesh devices=%d virtual_workers=%d kernel=%s model=%s async=%s",
        n, virtual, cfg.kernel, cfg.model, cfg.use_async,
    )

    ckpt = _make_checkpointer(cfg)
    if cfg.feature_shards > 1:
        # dp x tp: config.__post_init__ already rejected async/rpc combos
        from distributed_sgd_tpu.parallel.feature_sharded import (
            FeatureShardedEngine,
            make_mesh_2d,
        )

        n_devs = len(jax.devices())
        n_w = max(1, n_devs // cfg.feature_shards)
        log.info("engine=mesh 2-D dp=%d x tp=%d (feature_shards)",
                 n_w, cfg.feature_shards)
        eng = FeatureShardedEngine(
            model, make_mesh_2d(n_w, cfg.feature_shards),
            batch_size=cfg.batch_size, learning_rate=cfg.learning_rate,
        )
        res = eng.fit(train, test, cfg.max_epochs, criterion,
                      checkpointer=ckpt, checkpoint_every=cfg.checkpoint_every,
                      seed=cfg.seed)
        _finish(cfg, res, saved=ckpt is not None)
        return
    if cfg.use_async and cfg.async_mode == "gossip":
        from distributed_sgd_tpu.parallel.hogwild import HogwildEngine

        eng = HogwildEngine(
            model, n_workers=cfg.node_count, batch_size=cfg.batch_size,
            learning_rate=cfg.learning_rate, check_every=cfg.check_every,
            leaky_loss=cfg.leaky_loss, seed=cfg.seed, checkpointer=ckpt,
            steps_per_dispatch=cfg.steps_per_dispatch,
            optimizer=optimizer_of(cfg), momentum=cfg.momentum,
            compress=cfg.compress, compress_k=cfg.compress_k,
            compress_ef=cfg.compress_ef,
            gossip_topology=cfg.gossip_topology,
        )
        res = eng.fit(train, test, cfg.max_epochs, criterion,
                      initial_weights=_restore_weights(ckpt))
    elif cfg.use_async:
        from distributed_sgd_tpu.parallel.local_sgd import LocalSGDEngine

        eng = LocalSGDEngine(
            model, mesh, batch_size=cfg.batch_size,
            learning_rate=cfg.learning_rate, sync_period=cfg.sync_period,
            check_every=cfg.check_every, leaky_loss=cfg.leaky_loss, seed=cfg.seed,
            kernel=cfg.kernel, checkpointer=ckpt,
            optimizer=optimizer_of(cfg), momentum=cfg.momentum,
        )
        res = eng.fit(train, test, cfg.max_epochs, criterion,
                      initial_weights=_restore_weights(ckpt))
    else:
        from distributed_sgd_tpu.core.trainer import SyncTrainer

        trainer = SyncTrainer(
            model, mesh, batch_size=cfg.batch_size,
            learning_rate=cfg.learning_rate, seed=cfg.seed,
            kernel=cfg.kernel, virtual_workers=virtual,
            checkpointer=ckpt, checkpoint_every=cfg.checkpoint_every,
            optimizer=optimizer_of(cfg), momentum=cfg.momentum,
            profile_dir=cfg.profile_dir,
        )
        res = trainer.fit(train, test, cfg.max_epochs, criterion)

    _finish(cfg, res, saved=ckpt is not None)


def _fit_state_args(cfg: Config) -> dict:
    """DSGD_FIT_CKPT_EVERY -> fit_sync crash-snapshot kwargs (empty when
    disabled; config validation already required checkpoint_dir).  ANY
    health action also gets the path (with fit_ckpt_every=0 it is the
    path alone, so no cadence snapshots run): 'snapshot'/'halt' write the
    trip snapshot there, and every action — 'warn' included — RESTORES
    one a previous halted run left, so restarting after a halt resumes
    regardless of which action the restart runs with."""
    if not (cfg.fit_ckpt_every or cfg.health_action) or not cfg.checkpoint_dir:
        return {}
    from distributed_sgd_tpu.checkpoint import fit_state_path

    return {"fit_state_path": fit_state_path(cfg.checkpoint_dir),
            "fit_state_every": cfg.fit_ckpt_every}


def _resolve_host_devices(cfg: Config, dev_workers: int = 0) -> int:
    """DSGD_HOST_DEVICES -> the worker's in-host mesh width
    (docs/HIERARCHY.md): 0 = auto — every local device on a standalone
    worker role, the per-worker share of the local mesh in dev mode
    (`dev_workers` in-process workers divide what one process can see);
    1 = the flat single-device worker, D = exactly D devices."""
    if cfg.host_devices == 0:
        d = jax.local_device_count()
        if dev_workers:
            d = max(1, d // dev_workers)
        log.info("DSGD_HOST_DEVICES=0: auto-sized the in-host mesh to "
                 "%d device(s)", d)
        return d
    return cfg.host_devices


def _health_monitor(cfg: Config, metrics=None):
    """DSGD_HEALTH_ACTION -> telemetry.HealthMonitor (None when unset)."""
    if not cfg.health_action:
        return None
    from distributed_sgd_tpu.telemetry.health import HealthMonitor

    log.info("training-health monitor on: action=%s", cfg.health_action)
    monitor = HealthMonitor(metrics=metrics, action=cfg.health_action)
    # the leak-slope sentinel (resource probe, ISSUE 20) routes its trips
    # through the same DSGD_HEALTH_ACTION machinery as a loss divergence
    from distributed_sgd_tpu.telemetry import resources

    probe = resources.active()
    if probe is not None and probe.sentinel is not None:
        probe.sentinel.attach_health(monitor)
    return monitor


def scenario_rpc(cfg: Config, train: Dataset, test: Dataset, model) -> None:
    """Dev-mode reference-parity path: in-process gRPC cluster."""
    from distributed_sgd_tpu.core.cluster import DevCluster

    criterion = no_improvement(patience=cfg.patience, min_delta=cfg.conv_delta)
    host_devices = _resolve_host_devices(cfg, dev_workers=cfg.node_count)
    with DevCluster(model, train, test, n_workers=cfg.node_count, seed=cfg.seed,
                    heartbeat_s=cfg.heartbeat_s,
                    heartbeat_max_misses=cfg.heartbeat_max_misses,
                    steps_per_dispatch=cfg.steps_per_dispatch,
                    compress=cfg.compress, compress_k=cfg.compress_k,
                    compress_ef=cfg.compress_ef, chaos=cfg.chaos,
                    gossip_topology=cfg.gossip_topology,
                    telemetry_port=cfg.telemetry_port if cfg.telemetry
                    else None,
                    host_devices=host_devices,
                    host_overprovision=cfg.host_overprovision) as c:
        if cfg.compile_cache:
            # dev-mode spin-up fast path: every in-process worker warms
            # its flagship shapes in the background before the fit's
            # first fan-out reaches it
            from distributed_sgd_tpu import compile_cache

            for i, w in enumerate(c.workers):
                compile_cache.warmup_async(
                    f"worker[w{i}]",
                    w.warmup_thunks(cfg.batch_size, cfg.local_steps))
        w0 = np.zeros(model.n_features, dtype=np.float32)
        loss0, acc0 = c.master.local_loss(w0, test=False)
        log.info("initial loss=%.6f acc=%.4f", loss0, acc0)
        ckpt = _make_checkpointer(cfg)
        if cfg.use_async:
            res = c.master.fit_async(
                cfg.max_epochs, cfg.batch_size, cfg.learning_rate, criterion,
                check_every=cfg.check_every, leaky_loss=cfg.leaky_loss,
                initial_weights=_restore_weights(ckpt), checkpointer=ckpt,
                optimizer=cfg.optimizer, momentum=cfg.momentum,
                elastic=cfg.elastic, batch_drain=cfg.async_drain,
            )
        else:
            res = c.master.fit_sync(
                cfg.max_epochs, cfg.batch_size, cfg.learning_rate, criterion,
                checkpointer=ckpt, checkpoint_every=cfg.checkpoint_every,
                optimizer=cfg.optimizer, momentum=cfg.momentum,
                local_steps=cfg.local_steps,
                delta_broadcast=cfg.delta_broadcast,
                stream=cfg.stream,
                fanin_lanes=cfg.fanin_lanes, stage_pool=cfg.stage_pool,
                agg_tree=cfg.agg_tree,
                master_shards=cfg.master_shards,
                quorum=cfg.quorum, straggler_soft_s=cfg.straggler_soft_s,
                health=_health_monitor(cfg, metrics=c.master.metrics),
                **_fit_state_args(cfg),
            )
        _finish(cfg, res, evaluator=lambda w: c.master.local_loss(w, test=True),
                saved=ckpt is not None)


def _finish(cfg: Config, res, evaluator=None, saved: bool = False) -> None:
    w = res.state.weights
    log.info("fit done: %d epochs, final loss=%.6f, %d updates",
             res.epochs_run, res.state.loss, res.state.updates)
    if evaluator is None:
        log.info("test losses: %s", ", ".join(f"{x:.6f}" for x in res.test_losses))
    else:
        tl, ta = evaluator(np.asarray(w))
        log.info("final test loss=%.6f acc=%.4f", tl, ta)
    # safety net: every scenario path now wires its checkpointer into the
    # fit itself (mesh + RPC, sync + async), so this exit-time snapshot only
    # runs for future paths added without in-fit wiring
    if cfg.checkpoint_dir and not saved:
        from distributed_sgd_tpu.checkpoint import Checkpointer

        Checkpointer(cfg.checkpoint_dir).save(res.epochs_run, w)


def _package_version(name: str) -> str:
    from importlib import metadata

    try:
        return metadata.version(name)
    except metadata.PackageNotFoundError:
        return "absent"


def _log_devices() -> None:
    """Name the device this process computes on, once, at start-up."""
    devs = jax.devices()
    log.info("device: platform=%s kind=%s count=%d jax=%s jaxlib=%s libtpu=%s",
             devs[0].platform, devs[0].device_kind, len(devs),
             jax.__version__, _package_version("jaxlib"),
             _package_version("libtpu"))


def _log_run_summary() -> None:
    """Exit-time facts a run leaves behind: what the persistent compile
    cache did for this process and how much device memory it peaked at
    (memory_stats() is None on backends that do not report it)."""
    from distributed_sgd_tpu import compile_cache

    log.info("compile cache: dir=%s hits=%d misses=%d",
             compile_cache.cache_dir(), *compile_cache.counts())
    peaks = [(d.id, (d.memory_stats() or {}).get("peak_bytes_in_use"))
             for d in jax.local_devices()]
    log.info("device memory peak: %s", " ".join(
        f"{i}:{p if p is not None else 'unreported'}" for i, p in peaks))


def main() -> None:
    setup_logging()
    cfg = Config.from_env()
    log.info("host: %s (%s)", socket.gethostname(), sys.platform)
    log.info("config: %s", cfg.to_json())
    np.random.seed(cfg.seed)  # Main.scala:32 Random.setSeed(0)

    # place jax's persistent compilation cache BEFORE the first jit of the
    # process (compile_cache.py: JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache), so every XLA compile below — warmup thunks
    # and live traffic alike — reads/writes it.  DSGD_COMPILE_CACHE arms
    # the AOT warmup pass on top.
    from distributed_sgd_tpu import compile_cache

    compile_cache.place(warmup=cfg.compile_cache)
    # the router computes on no device unless its canary gate fires, and
    # must not claim the chip a co-located replica process needs
    on_device = cfg.role != "route"
    if on_device:
        _log_devices()

    # observability plumbing (docs/OBSERVABILITY.md), BEFORE any channel or
    # server exists so every RPC edge is covered:
    # - DSGD_TRACE: per-round span timelines, Chrome/Perfetto export
    # - DSGD_FLIGHT_RECORDER: always-on post-mortem ring (SIGUSR2 dumps)
    from distributed_sgd_tpu import trace as trace_mod
    from distributed_sgd_tpu.trace import flight

    role = cfg.role
    trace_dir = cfg.trace_dir or ("dsgd-traces" if cfg.trace else None)
    if cfg.trace:
        trace_mod.configure(enabled=True, dir=trace_dir,
                            sample=cfg.trace_sample,
                            service=f"{role}-{cfg.port}")
        log.info("tracing on: sample=%g dir=%s (merge with "
                 "`python -m distributed_sgd_tpu.trace.merge %s`)",
                 cfg.trace_sample, trace_dir, trace_dir)
    flight.configure(capacity=cfg.flight_recorder,
                     service=f"{role}-{cfg.port}", dir=trace_dir or ".")
    flight.install_signal_handler()

    # long-horizon resource plane (telemetry/resources.py, ISSUE 20):
    # DSGD_RESOURCE_PROBE_S > 0 starts the per-process probe thread —
    # /proc + pressure gauges every tick, the leak-slope sentinel riding
    # the series (trip action = DSGD_HEALTH_ACTION, default warn), and
    # (DSGD_BLACKBOX_DIR) the crash-surviving blackbox ring.  Unset: no
    # thread, no gauges, no files — byte-identical (asserted by test).
    probe = None
    if cfg.resource_probe_s > 0:
        from distributed_sgd_tpu.telemetry import blackbox as blackbox_mod
        from distributed_sgd_tpu.telemetry import resources, slope

        sentinel = slope.LeakSentinel(metrics=metrics_mod.global_metrics())
        box = (blackbox_mod.Blackbox(cfg.blackbox_dir,
                                     service=f"{role}-{cfg.port}")
               if cfg.blackbox_dir else None)
        probe = resources.configure(cfg.resource_probe_s,
                                    metrics=metrics_mod.global_metrics(),
                                    sentinel=sentinel, blackbox=box)
        log.info("resource probe on: every %gs (blackbox=%s)",
                 cfg.resource_probe_s, cfg.blackbox_dir or "off")

    # record=true enables metric SHIPPING (the reference's Kamon reporter
    # flag, Main.scala:40-43); the transports are orthogonal and may both
    # run: DSGD_METRICS_PORT serves Prometheus pull, DSGD_INFLUX_URL pushes
    # line protocol every second (reference parity, application.conf:54-78)
    exporter = None
    pusher = None
    if cfg.record:
        if cfg.metrics_port is not None:
            from distributed_sgd_tpu.utils.metrics import PrometheusExporter

            exporter = PrometheusExporter(
                metrics_mod.global_metrics(), cfg.metrics_port).start()
            log.info("metrics exporter on :%d", exporter.port)
        if cfg.influx_url:
            from distributed_sgd_tpu.utils.metrics import InfluxPusher

            pusher = InfluxPusher(metrics_mod.global_metrics(), cfg.influx_url).start()
            log.info("influx pusher -> %s", cfg.influx_url)
        if exporter is None and pusher is None:
            log.warning(
                "DSGD_RECORD=1 but neither DSGD_METRICS_PORT nor "
                "DSGD_INFLUX_URL is set: metrics are collected but not shipped")

    try:
        _run_role(cfg, role)
    except Exception:
        # an uncaught exception in any engine loop that surfaces here
        # leaves flight-recorder evidence before the process dies
        flight.dump("exception")
        raise
    finally:
        # stop + final flush on EVERY exit path: a crashed run's tail
        # metrics (incl. metrics.push.errors) are the ones that matter —
        # same for the trace buffer
        trace_mod.flush()
        if on_device:
            _log_run_summary()
        if probe is not None:
            probe.stop()
        if exporter is not None:
            exporter.stop()
        if pusher is not None:
            pusher.stop()


def _install_chaos(cfg: Config) -> None:
    """DSGD_CHAOS on a standalone master/worker process: install the plan
    before any channel exists so every outgoing stub is wrapped (chaos/).
    Partition specs reference endpoints as host:port in multi-process
    deployments; dev mode's DevCluster also names them w0..wN/master."""
    if not cfg.chaos:
        return
    from distributed_sgd_tpu import chaos

    chaos.install(cfg.chaos, metrics=metrics_mod.global_metrics())
    log.warning("chaos plan active on this node: %s", cfg.chaos)


def _load_probe(cfg: Config):
    """DSGD_SERVE_PROBE -> canary probe rows (None when unset)."""
    if not cfg.serve_probe:
        return None
    from distributed_sgd_tpu.serving.router import load_probe

    probe = load_probe(cfg.serve_probe)
    log.info("canary probe set: %d rows from %s", len(probe), cfg.serve_probe)
    return probe


def _autopilot_probe_source(cfg: Config):
    """DSGD_AUTOPILOT on the route role -> (ProbeReservoir, refresh_s):
    live probe sourcing (autopilot/probe_source.py) replaces the
    operator-rotated probe file.  The env-driven role joins ground truth
    through the seeded DriftingStream oracle — the documented assumption
    (docs/CONTINUAL.md) that the traffic IS the synthetic stream, which
    is exactly what the dev role and the flywheel bench send; a
    production integrator supplies its own labeler (feedback-log join)
    programmatically."""
    if not cfg.autopilot:
        return None, 0.0
    from distributed_sgd_tpu.autopilot import DriftingStream, ProbeReservoir

    stream = DriftingStream(seed=cfg.seed)
    reservoir = ProbeReservoir(
        stream.oracle_labeler(), capacity=cfg.autopilot_probe_capacity,
        seed=cfg.seed, label_delay=cfg.autopilot_label_delay,
        recency=2 * cfg.autopilot_probe_capacity,
        min_fill=max(1, cfg.autopilot_probe_capacity // 2))
    log.info(
        "autopilot probe sourcing: reservoir capacity=%d label_delay=%d "
        "refresh=%gs", reservoir.capacity, reservoir.label_delay,
        cfg.autopilot_source_refresh_s)
    return reservoir, cfg.autopilot_source_refresh_s


def _autopilot_stream_build(cfg: Config):
    """DSGD_AUTOPILOT on the master role -> the stream plane
    (autopilot/stream.py): the resident corpus is the newest
    DSGD_AUTOPILOT_WINDOW rows of the seeded drifting stream and the
    eval set is pinned to the window's trailing edge, so the existing
    early-stopping machinery judges convergence against the CURRENT
    distribution.  A master relaunch warm-starts automatically from the
    epoch-cadence checkpoint (fit_sync's restore path); grant it a
    raised DSGD_MAX_EPOCHS budget and the relaunch IS one flywheel
    retrain round (the dev role and benches/bench_flywheel.py run the
    full closed loop hands-free in one process)."""
    from distributed_sgd_tpu.autopilot import DriftingStream

    stream = DriftingStream(seed=cfg.seed)
    train = measure.duration_log(
        "stream window materialized",
        lambda: stream.rows(0, cfg.autopilot_window), log)
    test = stream.eval_set(max(256, cfg.autopilot_window // 8),
                           at=cfg.autopilot_window)
    ds = dim_sparsity(train)
    model = make_model(cfg.model, cfg.lam, train.n_features,
                       dim_sparsity=ds, regularizer=cfg.regularizer)
    return train, test, model


def _run_dev_flywheel(cfg: Config) -> None:
    """DSGD_ROLE=dev + DSGD_AUTOPILOT: the full closed loop in one
    process (autopilot/flywheel.py).  A DevCluster trains on the stream
    window, a ServingFleet serves the checkpoints, the router sources
    its probe set from its own traffic, and the controller drives drift
    -> retrain -> canary -> promote hands-free.  Pumps one complete
    shift through the fleet (the stream's schedule decides when), waits
    for the controller to settle, logs the summary, and exits."""
    from distributed_sgd_tpu.autopilot import (
        DriftDetector,
        DriftingStream,
        Flywheel,
    )

    stream = DriftingStream(seed=cfg.seed)
    horizon = stream.shift_at + 2 * cfg.autopilot_window
    detector = DriftDetector(
        ratio=cfg.autopilot_drift_ratio,
        patience=cfg.autopilot_drift_patience,
        warmup=cfg.autopilot_drift_warmup,
        abs_floor=cfg.autopilot_drift_floor)
    fly = Flywheel(
        stream, horizon_rows=horizon, window_rows=cfg.autopilot_window,
        model=cfg.model, lam=cfg.lam, n_workers=2,
        n_replicas=max(2, cfg.serve_replicas),
        max_epochs=cfg.max_epochs, batch_size=cfg.batch_size,
        learning_rate=cfg.learning_rate, patience=cfg.patience,
        conv_delta=cfg.conv_delta,
        probe_capacity=cfg.autopilot_probe_capacity,
        label_delay=cfg.autopilot_label_delay,
        source_refresh_s=cfg.autopilot_source_refresh_s,
        canary_fraction=cfg.serve_canary or 0.5,
        detector=detector, poll_s=cfg.autopilot_poll_s,
        cooldown_s=cfg.autopilot_cooldown_s,
        canary_timeout_s=cfg.autopilot_canary_timeout_s,
        max_retrains=cfg.autopilot_max_retrains,
        recovery_band=cfg.autopilot_recovery_band,
        seed=cfg.seed, ckpt_dir=cfg.checkpoint_dir or None,
        telemetry_port=cfg.telemetry_port if cfg.telemetry else None,
    )
    log.info("dev flywheel: horizon=%d rows (%s shift at %d), window=%d",
             horizon, stream.schedule, stream.shift_at,
             cfg.autopilot_window)
    fly.start()
    try:
        summary = fly.run()
    finally:
        fly.stop()
    log.info(
        "flywheel done: served=%d dropped=%d retrains=%d promoted=%d "
        "rolled_back=%d state=%s", summary["served"], summary["dropped"],
        summary["retrains"], summary["promoted"], summary["rolled_back"],
        summary["state"])


def _serve_distributor(cfg: Config):
    """DSGD_SERVE_PUSH on a training role -> started CheckpointDistributor
    (None when unset): every checkpoint the fit writes streams to the
    fleet as a versioned weight delta (docs/SERVING.md "serving fleet");
    config validation already required checkpoint_dir."""
    if not cfg.serve_push:
        return None
    from distributed_sgd_tpu.serving.push import CheckpointDistributor, parse_targets

    targets = parse_targets(cfg.serve_push)
    log.info("checkpoint distributor on: %s -> %s",
             cfg.checkpoint_dir, cfg.serve_push)
    return CheckpointDistributor(
        cfg.checkpoint_dir, targets,
        metrics=metrics_mod.global_metrics()).start()


def _build_worker_row_store(cfg: Config):
    """DSGD_ROW_STORE on the worker role: map the packed corpus
    (data/row_store.py) instead of parsing it, and with DSGD_HOST_INDEX
    load ONLY this worker's host slice (+ the DSGD_HOST_OVERPROVISION
    neighbor margin) through the store's RowReader — the no-egress
    real-corpus host-local spin-up path (docs/HIERARCHY.md "Elastic
    composition").  Returns (data, model, worker kwargs).

    A missing store next to an existing corpus is built once (the one
    parse every later spin-up amortizes); the train split's dim-sparsity
    vector rides the store's sidecar so no worker re-scans the corpus to
    build its model."""
    from distributed_sgd_tpu.data import host_shard
    from distributed_sgd_tpu.data.row_store import (
        RowStore,
        build_from_corpus,
        meta_path,
    )

    if not os.path.exists(meta_path(cfg.row_store)):
        log.info("row store %s missing: building from %s (one-time parse)",
                 cfg.row_store, cfg.data_path)
        measure.duration_log(
            "row store built",
            lambda: build_from_corpus(cfg.data_path, cfg.row_store,
                                      full=cfg.full,
                                      pad_width=cfg.pad_width), log)
    store = RowStore(cfg.row_store)
    ds = store.dim_sparsity()
    if ds is None:
        log.warning("row store has no dim-sparsity sidecar: the model "
                    "falls back to the plain l2 regularizer")
    model = make_model(cfg.model, cfg.lam, store.n_features,
                       dim_sparsity=ds, regularizer=cfg.regularizer)
    n_train = store.train_rows
    if cfg.host_index is None:
        # full train split resident, straight off the mmap — no parse,
        # no reader needed (ids pass through untouched)
        data = store.read_rows(0, n_train)
        log.info("row store mapped: %d train rows resident (full split)",
                 n_train)
        return data, model, {}
    lo, hi, start, end = host_shard.overprovisioned_slice(
        n_train, cfg.host_index, cfg.node_count,
        overprovision=cfg.host_overprovision)
    data = host_shard.load_host_shard(
        store.reader, n_train, store.n_features, store.pad_width,
        lo, hi, labels_dtype=store.labels_dtype)
    log.info(
        "host-local slice %d/%d loaded through the row store: rows "
        "[%d, %d) resident (nominal [%d, %d) + overprovision %g)",
        cfg.host_index, cfg.node_count, lo, hi, start, end,
        cfg.host_overprovision)
    return data, model, dict(
        data_offset=lo, row_reader=store.reader, total_rows=n_train,
        host_overprovision=cfg.host_overprovision)


def _warmup_worker(cfg: Config, worker) -> None:
    """DSGD_COMPILE_CACHE on the worker role: kick the background AOT
    pass over the worker's flagship shapes while registration runs."""
    if not cfg.compile_cache:
        return
    from distributed_sgd_tpu import compile_cache

    compile_cache.warmup_async(
        f"worker[:{cfg.port}]",
        worker.warmup_thunks(cfg.batch_size, cfg.local_steps))


def _run_role(cfg: Config, role: str) -> None:
    if role == "route":
        # Serving-fleet router (serving/router.py; DSGD_ROLE=route): fans
        # Predict traffic over DSGD_SERVE_TARGETS with health-aware
        # power-of-two-choices balancing, and gates pushed checkpoint
        # versions through the canary fraction (docs/SERVING.md).
        from distributed_sgd_tpu.serving.push import parse_targets
        from distributed_sgd_tpu.serving.router import ServingRouter

        # DSGD_AUTOPILOT: live probe sourcing — the router reservoir-
        # samples its own Predict traffic into the canary probe set
        # (autopilot/probe_source.py, docs/CONTINUAL.md)
        probe_source, source_refresh_s = _autopilot_probe_source(cfg)
        router = ServingRouter(
            parse_targets(cfg.serve_targets), port=cfg.serve_port,
            model=cfg.model, lam=cfg.lam,
            canary_fraction=cfg.serve_canary, probe=_load_probe(cfg),
            hedge_ms=cfg.serve_hedge_ms, health_s=cfg.serve_health_s,
            telemetry_port=cfg.telemetry_port if cfg.telemetry else None,
            metrics=metrics_mod.global_metrics(), seed=cfg.seed,
            # DSGD_SERVE_STATE: a restarted router re-pins the promoted
            # version instead of re-canarying it (docs/SERVING.md)
            state_path=cfg.serve_state,
            # DSGD_SERVE_PROBE_REFRESH_S: rotate fresh held-out probe rows
            # in from the probe file on a cadence (ROADMAP 3c)
            probe_path=cfg.serve_probe,
            probe_refresh_s=cfg.serve_probe_refresh_s,
            probe_source=probe_source,
            probe_source_refresh_s=source_refresh_s,
        ).start()
        if cfg.serve_ha:
            # DSGD_SERVE_HA: dual LIVE routers — attach the lease-based
            # coordinator and start the promoted-state peer-sync loop
            # (serving/ha.py, docs/SERVING.md "HA")
            from distributed_sgd_tpu.serving.ha import HACoordinator

            router.attach_ha(HACoordinator.from_spec(
                cfg.serve_ha, metrics=metrics_mod.global_metrics()))
            router._ha.start()
        log.info("routing on :%d over %s (canary=%g, hedge=%gms)",
                 router.bound_port, cfg.serve_targets, cfg.serve_canary,
                 cfg.serve_hedge_ms)
        try:
            router.await_termination()
        finally:
            router.stop()
        return
    if role == "serve" and cfg.serve_replicas > 0:
        # One-machine fleet (serving/fleet.py): DSGD_SERVE_REPLICAS
        # in-process replicas behind an in-process router on serve_port —
        # the kube deployment runs the same two roles as real pods.
        from distributed_sgd_tpu.serving.fleet import ServingFleet

        fleet = ServingFleet(
            cfg.checkpoint_dir, cfg.serve_replicas, model=cfg.model,
            lam=cfg.lam, router_port=cfg.serve_port,
            max_batch=cfg.serve_max_batch,
            max_delay_ms=cfg.serve_max_delay_ms,
            queue_depth=cfg.serve_queue_depth,
            ckpt_poll_s=cfg.serve_ckpt_poll_s,
            canary_fraction=cfg.serve_canary, probe=_load_probe(cfg),
            hedge_ms=cfg.serve_hedge_ms, health_s=cfg.serve_health_s,
            telemetry_port=cfg.telemetry_port if cfg.telemetry else None,
            metrics=metrics_mod.global_metrics(), seed=cfg.seed,
            state_path=cfg.serve_state,
            probe_path=cfg.serve_probe,
            probe_refresh_s=cfg.serve_probe_refresh_s,
        ).start()
        autoscaler = None
        if cfg.serve_slo_ms > 0:
            # DSGD_SERVE_SLO_MS: load-adaptive replica autoscale — the
            # router's EWMA-latency x in-flight signal against the p99
            # SLO, warm spin-up / drain with hysteresis + cooldown
            # (serving/ha.py ReplicaAutoscaler, docs/SERVING.md)
            from distributed_sgd_tpu.serving.ha import (
                ReplicaAutoscaler,
                router_load_ms,
            )

            autoscaler = ReplicaAutoscaler(
                signal_ms=lambda: router_load_ms(fleet.router),
                scale_up=fleet.add_replica, scale_down=fleet.drain_replica,
                count=lambda: len(fleet.replicas), slo_ms=cfg.serve_slo_ms,
                min_replicas=cfg.serve_replicas,
                max_replicas=cfg.serve_scale_max,
                cooldown_s=cfg.serve_scale_cooldown_s,
                metrics=metrics_mod.global_metrics()).start()
        log.info("serving fleet: router :%d over %d in-process replicas",
                 fleet.router_port, cfg.serve_replicas)
        try:
            fleet.await_termination()
        finally:
            if autoscaler is not None:
                autoscaler.stop()
            fleet.stop()
        return
    if role == "serve":
        # Online inference front end (serving/; DSGD_ROLE=serve): no
        # training data, no cluster membership — it loads weights from
        # cfg.checkpoint_dir and hot-reloads as the trainer saves new ones.
        from distributed_sgd_tpu.serving.server import ServingServer

        server = ServingServer.from_config(
            cfg, metrics=metrics_mod.global_metrics()).start()
        log.info(
            "serving model=%s on :%d (ckpt=%s, max_batch=%d, "
            "max_delay_ms=%g, queue_depth=%d)",
            cfg.model, server.bound_port, cfg.checkpoint_dir,
            cfg.serve_max_batch, cfg.serve_max_delay_ms,
            cfg.serve_queue_depth,
        )
        try:
            server.await_termination()
        finally:
            server.stop()
        return
    if role == "dev":
        if cfg.autopilot:
            # the full train/serve flywheel in one process — drift ->
            # retrain -> canary -> promote hands-free (docs/CONTINUAL.md)
            _run_dev_flywheel(cfg)
            return
        train, test, model = build(cfg)
        distributor = _serve_distributor(cfg)
        try:
            if cfg.engine == "rpc":
                scenario_rpc(cfg, train, test, model)
            else:
                scenario_mesh(cfg, train, test, model)
        finally:
            if distributor is not None:
                distributor.stop()
    elif role == "master":
        from distributed_sgd_tpu.core.master import MasterNode

        _install_chaos(cfg)
        if cfg.autopilot:
            # stream plane: corpus = the newest stream window, eval
            # pinned to its trailing edge (docs/CONTINUAL.md)
            train, test, model = _autopilot_stream_build(cfg)
        else:
            train, test, model = build(cfg)
        master = MasterNode(
            cfg.host, cfg.port, train, test, model,
            expected_workers=cfg.node_count, seed=cfg.seed,
        ).start(heartbeat_s=cfg.heartbeat_s,
                heartbeat_max_misses=cfg.heartbeat_max_misses)
        if cfg.telemetry:
            # cluster telemetry plane (telemetry/): scrape aggregator +
            # the ONE cluster-level /metrics endpoint
            master.enable_telemetry(cfg.telemetry_port)
        criterion = no_improvement(patience=cfg.patience, min_delta=cfg.conv_delta)
        if cfg.autopilot:
            from distributed_sgd_tpu.autopilot import continual_criterion

            # continual eval: convergence judged on the last few evals
            # only — a warm-started retrain must not be stopped by a
            # best earned on a distribution that no longer exists
            criterion = continual_criterion(
                criterion, horizon=2 * cfg.patience + 1)
        master.await_ready()
        ckpt = _make_checkpointer(cfg)
        distributor = _serve_distributor(cfg)
        try:
            if cfg.use_async:
                res = master.fit_async(
                    cfg.max_epochs, cfg.batch_size, cfg.learning_rate, criterion,
                    check_every=cfg.check_every, leaky_loss=cfg.leaky_loss,
                    initial_weights=_restore_weights(ckpt), checkpointer=ckpt,
                    optimizer=cfg.optimizer, momentum=cfg.momentum,
                    elastic=cfg.elastic, batch_drain=cfg.async_drain,
                )
            else:
                res = master.fit_sync(
                    cfg.max_epochs, cfg.batch_size, cfg.learning_rate, criterion,
                    checkpointer=ckpt, checkpoint_every=cfg.checkpoint_every,
                    optimizer=cfg.optimizer, momentum=cfg.momentum,
                    local_steps=cfg.local_steps,
                    delta_broadcast=cfg.delta_broadcast,
                    stream=cfg.stream,
                    fanin_lanes=cfg.fanin_lanes, stage_pool=cfg.stage_pool,
                    agg_tree=cfg.agg_tree,
                    master_shards=cfg.master_shards,
                    quorum=cfg.quorum, straggler_soft_s=cfg.straggler_soft_s,
                    health=_health_monitor(cfg, metrics=master.metrics),
                    **_fit_state_args(cfg),
                )
            _finish(cfg, res,
                    evaluator=lambda w: master.local_loss(w, test=True),
                    saved=ckpt is not None)
        finally:
            if distributor is not None:
                # stop() runs one final sweep, so the terminal checkpoint
                # the fit wrote still reaches the fleet — on EVERY exit
                # path, like the dev branch
                distributor.stop()
        master.stop()
    else:  # worker
        from distributed_sgd_tpu.core.worker import WorkerNode

        _install_chaos(cfg)
        host_devices = _resolve_host_devices(cfg)
        extra = {}
        if cfg.row_store:
            # mmap row store + optional host-local slice (spin-up fast
            # path): no parse, and with DSGD_HOST_INDEX no full-corpus
            # materialization either
            if cfg.host_index is not None and host_devices > 1:
                raise ValueError(
                    "DSGD_HOST_INDEX with a multi-device in-host mesh is "
                    "not supported (the mesh binds its slice at build "
                    "time); set DSGD_HOST_DEVICES=1")
            train, model, extra = _build_worker_row_store(cfg)
        else:
            train, _, model = build(cfg)
        worker = WorkerNode(
            cfg.host, cfg.port, cfg.master_host, cfg.master_port, train, model,
            seed=cfg.seed, steps_per_dispatch=cfg.steps_per_dispatch,
            compress=cfg.compress, compress_k=cfg.compress_k,
            compress_ef=cfg.compress_ef,
            # DSGD_PROFILE_DIR on the worker role: device trace of the
            # first dispatches — where distributed time actually goes
            profile_dir=cfg.profile_dir,
            gossip_topology=cfg.gossip_topology,
            # elastic deployments survive a master restart: the watch
            # probes Master.Ping and re-enters the jittered registration
            # loop on sustained loss (docs/ELASTICITY.md)
            master_watch_s=(cfg.heartbeat_s or 5.0) if cfg.elastic else None,
            # cluster telemetry: publish the per-dispatch health gauges
            # the master's Metrics-RPC scrape re-exports per worker
            telemetry=cfg.telemetry,
            # hierarchical in-host mesh (docs/HIERARCHY.md): this worker
            # becomes a D-device host — batches shard over the local
            # devices, gradients reduce with one in-host psum, and the
            # master's split turns host-granular via Node.devices
            host_devices=host_devices,
            # host-local row-store slice (data_offset/row_reader/...)
            **extra,
        )
        # AOT warmup races registration, not traffic: the flagship shapes
        # compile (or disk-cache-hit) while the master is still
        # introducing this worker to the membership
        _warmup_worker(cfg, worker)
        worker.start()
        worker.await_termination()


if __name__ == "__main__":
    main()
