"""On-mesh local SGD with periodic averaging — the compiled async mode.

The reference's Hogwild gossip (Slave.scala:79-111) is host-asynchronous by
nature; parallel/hogwild.py reproduces it faithfully.  This module is the
TPU-idiomatic alternative in the same convergence family (local update
steps on stale replicas + delta exchange): every device runs ``sync_period``
independent SGD steps on its own weights replica — the compiled analogue of
Hogwild's stale local loop — then replicas average over the ICI mesh with
one ``pmean`` (the all-to-all gossip collapsed into a collective).  The
entire round is one compiled program; no host participation, no
serialization, no queues.  Offered behind ``Config.async_mode='local_sgd'``
(SURVEY.md §7 step 6's "alternative to offer behind config").

The host loop around rounds reuses the reference's async loss-checker
semantics: leaky-smoothed test loss, best-weights tracking, early stop on
the smoothed history, total update budget n_samples * max_epochs
(MasterAsync.scala:83,96-162).
"""

from __future__ import annotations

import logging
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distributed_sgd_tpu.core.early_stopping import Criterion
from distributed_sgd_tpu.core.loss_check import LossChecker, async_fit_result
from distributed_sgd_tpu.core.trainer import FitResult
from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.models.linear import LinearModel, require_single_output
from distributed_sgd_tpu.ops import ftrl
from distributed_sgd_tpu.ops import kernels
from distributed_sgd_tpu.ops.sparse import SparseBatch
from distributed_sgd_tpu.parallel.mesh import WORKER_AXIS as AXIS, pcast_varying, shard_map
from distributed_sgd_tpu.parallel.sync import SyncEngine
from distributed_sgd_tpu.utils import metrics as metrics_mod

log = logging.getLogger("dsgd.local_sgd")


class LocalSGDEngine:
    def __init__(
        self,
        model: LinearModel,
        mesh,
        batch_size: int,
        learning_rate: float,
        sync_period: int = 16,
        check_every: int = 100,
        leaky_loss: float = 0.9,
        seed: int = 0,
        metrics: Optional[metrics_mod.Metrics] = None,
        kernel: str = kernels.AUTO,
        checkpointer=None,
        optimizer=None,
        momentum: float = 0.9,
    ):
        require_single_output(model, 'LocalSGDEngine')
        ftrl.refuse(optimizer, 'LocalSGDEngine')
        if not (0.0 <= leaky_loss <= 1.0):
            raise ValueError("leaking coefficient must be between 0 and 1")
        if kernel not in (kernels.AUTO, "mxu", "scalar", "gather"):
            raise ValueError(
                f"kernel must be {kernels.AUTO!r} (the shape rule, "
                f"ops/kernels.py), 'mxu', 'scalar' or 'gather', got {kernel!r}")
        self.kernel = kernel
        # optimizer for the replicas' local steps; state rides the scan
        # carry within a round and, like the weights, is pmean-averaged at
        # each sync point (float leaves; the standard local-SGD/FedAvg-
        # with-momentum treatment), so replicas re-diverge from a common
        # optimizer state each round
        self.optimizer = optimizer
        self.momentum = momentum
        self.model = model
        self.mesh = mesh
        self.batch_size = int(batch_size)
        self.learning_rate = float(learning_rate)
        self.sync_period = int(sync_period)
        self.check_every = check_every
        self.leaky_loss = leaky_loss
        self.seed = seed
        self.metrics = metrics or metrics_mod.global_metrics()
        self.checkpointer = checkpointer  # persists best weights (LossChecker)
        self.n_workers = mesh.shape[AXIS]

    def fit(
        self,
        train: Dataset,
        test: Dataset,
        max_epochs: int,
        criterion: Optional[Criterion] = None,
        initial_weights: Optional[np.ndarray] = None,
    ) -> FitResult:
        engine = SyncEngine(self.model, self.mesh, self.batch_size,
                            self.learning_rate, kernel=self.kernel)
        bound = engine.bind(train)  # reuse dataset sharding + eval/compile plumbing
        eval_bound = engine.bind(test)
        data = bound.data
        shard_n = bound.shard_n
        bs, lr, h = self.batch_size, self.learning_rate, self.sync_period
        model = self.model

        # what the bind chose: the one rule on shape and platform
        # (ops/kernels.py) unless a kernel was named; dense rows run 'dense'
        kernel = bound.kernel

        from distributed_sgd_tpu.parallel.sync import resolve_optimizer

        opt = resolve_optimizer(self.optimizer, self.learning_rate, self.momentum)

        def round_shard(w, opt_state, idx, val, y, key):
            key = jax.random.fold_in(key, jax.lax.axis_index(AXIS))
            w = model.to_layout(w, kernel)

            def body(carry, t):
                wl, opt_s = carry
                ids = jax.random.randint(jax.random.fold_in(key, t), (bs,), 0, shard_n)
                bi, bv, by = bound.draw_rows(idx, val, y, ids)
                g = model.grad(wl, SparseBatch(bi, bv), by,
                               kernel=kernel, reduce="mean")
                from distributed_sgd_tpu.parallel.sync import local_update

                wl, opt_s, _delta = local_update(opt, lr, g, wl, opt_s)
                return (wl, opt_s), ()

            # replicas diverge over the round, then average: weights and
            # float optimizer leaves via pmean (the gossip, collapsed);
            # integer leaves (e.g. adam's count) advance identically on
            # every replica, so pmax just re-asserts their invariance
            w_var = pcast_varying(w, (AXIS,))
            opt_var = jax.tree.map(
                lambda x: pcast_varying(x, (AXIS,)), opt_state)
            (wl, opt_state), _ = jax.lax.scan(body, (w_var, opt_var), jnp.arange(h))
            wl = jax.lax.pmean(wl, AXIS)
            opt_state = jax.tree.map(
                lambda x: jax.lax.pmean(x, AXIS)
                if jnp.issubdtype(x.dtype, jnp.floating) else jax.lax.pmax(x, AXIS),
                opt_state,
            )
            return model.from_layout(wl, kernel), opt_state

        round_fn = jax.jit(
            shard_map(
                round_shard,
                mesh=self.mesh,
                in_specs=(P(), P(), P(AXIS), P(AXIS), P(AXIS), P()),
                out_specs=(P(), P()),
            )
        )

        n = len(train)
        max_steps = n * max_epochs  # MasterAsync.scala:83
        w = (
            jnp.zeros(self.model.n_features, dtype=jnp.float32)
            if initial_weights is None
            else jnp.asarray(initial_weights, dtype=jnp.float32)
        )
        # optimizer state lives in the kernel's layout (like the weights
        # inside a round); initialized once, averaged at every sync point
        opt_state = (
            opt.init(model.to_layout(w, kernel))
            if opt is not None else None
        )
        key = jax.random.PRNGKey(self.seed)
        checker = LossChecker(self.leaky_loss, criterion, checkpointer=self.checkpointer)
        # maxSteps is a LIFETIME budget (MasterAsync.scala:83): a resumed
        # fit seeds the step counter from the snapshot and runs only the
        # remainder
        steps_done = checker.restored_updates
        last_check = steps_done - self.check_every
        t_start = time.time()

        while steps_done < max_steps:
            key, rk = jax.random.split(key)
            t0 = time.perf_counter()
            w, opt_state = round_fn(
                w, opt_state, data.indices, data.values, data.labels, rk)
            jax.block_until_ready(w)
            self.metrics.histogram("slave.async.round.seconds").record(
                time.perf_counter() - t0
            )
            steps_done += self.n_workers * h
            if steps_done - last_check < self.check_every:
                continue
            raw_loss, raw_acc = eval_bound.evaluate(w)
            stop = checker.check(raw_loss, raw_acc, w, step=steps_done)
            log.info(
                "loss computed at %d updates: test_loss=%.6f test_acc=%.4f",
                steps_done, checker.smoothed[0], checker.smoothed_accs[0],
            )
            last_check = steps_done
            if stop:
                log.info("converged to target: stopping computation")
                break

        return async_fit_result(
            checker, np.asarray(w), t_start, steps_done, self.batch_size, n)
