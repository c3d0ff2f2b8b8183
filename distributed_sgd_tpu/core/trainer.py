"""Host-side training drivers: epoch loop, evaluation, early stopping.

The TPU-native counterpart of the reference master's fit orchestration
(core/Master.scala:120-218): run one compiled epoch (parallel/sync.py),
evaluate train+test objective/accuracy on device, feed the *test* loss
history (newest first) to the stopping criterion — exactly the reference's
loop structure (early stop on test losses, Master.scala:166; epoch-end
eval of all four series, Master.scala:201-211) with the per-batch gRPC
fan-out replaced by `lax.scan` + `psum`.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_sgd_tpu.checkpoint import (
    restore_sync_fit,
    save_sync_fit,
    save_sync_fit_final,
)
from distributed_sgd_tpu.core.early_stopping import Criterion
from distributed_sgd_tpu.core.grad_state import GradState
from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.models.linear import LinearModel
from distributed_sgd_tpu.ops import ftrl, kernels
from distributed_sgd_tpu.parallel.sync import BoundSync, SyncEngine
from distributed_sgd_tpu.utils import measure
from distributed_sgd_tpu.utils import metrics as metrics_mod

log = logging.getLogger("dsgd.trainer")


@dataclass
class FitResult:
    state: GradState
    losses: List[float] = field(default_factory=list)  # chronological
    accuracies: List[float] = field(default_factory=list)
    test_losses: List[float] = field(default_factory=list)
    test_accuracies: List[float] = field(default_factory=list)
    epochs_run: int = 0
    epoch_seconds: List[float] = field(default_factory=list)
    # under FTRL, after each epoch: the weights that are not exactly 0, and
    # l1 ||w||_1 + (l2/2) ||w||^2, the part of each objective that is not
    # the mean loss
    nonzero: List[int] = field(default_factory=list)
    penalty: List[float] = field(default_factory=list)

    @property
    def weights(self):
        return self.state.weights


def record_epoch(result: FitResult, test_newest_first: List[float], epoch: int,
                 loss: float, acc: float, test_loss: float, test_acc: float,
                 epoch_s: float) -> None:
    """Epoch-end bookkeeping shared by every sync fit loop (mesh trainer,
    RPC fit_sync, feature-sharded fit): the four series + wall clock,
    epochs_run, and the NEWEST-FIRST test-loss history the stopping
    criterion consumes (the reference reads newest first,
    EarlyStopping.scala:18-46)."""
    result.losses.append(loss)
    result.accuracies.append(acc)
    result.test_losses.append(test_loss)
    result.test_accuracies.append(test_acc)
    result.epoch_seconds.append(epoch_s)
    result.epochs_run = epoch + 1
    test_newest_first.insert(0, test_loss)


class SyncTrainer:
    """Bulk-synchronous data-parallel trainer over a device mesh."""

    def __init__(
        self,
        model: LinearModel,
        mesh,
        batch_size: int,
        learning_rate: float,
        sampling: str = "fresh",
        metrics: Optional[metrics_mod.Metrics] = None,
        seed: int = 0,
        profile_dir: Optional[str] = None,
        checkpointer=None,
        checkpoint_every: int = 1,
        kernel: str = kernels.AUTO,
        virtual_workers: int = 1,
        optimizer=None,
        momentum: float = 0.9,
    ):
        self.engine = SyncEngine(
            model, mesh, batch_size, learning_rate, sampling=sampling,
            kernel=kernel, virtual_workers=virtual_workers,
            optimizer=optimizer, momentum=momentum,
        )
        from distributed_sgd_tpu.checkpoint import opt_kind_tag

        if checkpointer is not None:
            ftrl.refuse(optimizer, "checkpoint.FitState")
        self._opt_kind = opt_kind_tag(optimizer)
        self.model = model
        self.metrics = metrics or metrics_mod.global_metrics()
        self.seed = seed
        self.profile_dir = profile_dir  # jax.profiler trace of one steady period
        self.checkpointer = checkpointer  # checkpoint.Checkpointer or None
        self.checkpoint_every = checkpoint_every

    def fit(
        self,
        train: Dataset,
        test: Dataset,
        max_epochs: int,
        criterion: Optional[Criterion] = None,
        initial_weights: Optional[jax.Array] = None,
    ) -> FitResult:
        bound_train = self.engine.bind(train)
        bound_test = self.engine.bind(test)
        placed = bound_train.placement()
        stored = placed[0][2]  # one layout for every device's rows
        log.info("train split: %d rows %s stored major_to_minor=%s, per device %s",
                 len(train), bound_train.record(), stored, " ".join(
                     f"[id={d} rows={r} bytes_in_use={b}]"
                     for d, r, _stored, b in placed))
        if bound_train.plan.optimizer == "ftrl" and initial_weights is not None:
            raise ValueError("an FTRL fit starts from its state (z, n) = 0, not from weights")
        w = (  # [D], or [D, C] for a model with an output axis
            jnp.zeros(self.model.weight_shape, dtype=jnp.float32)
            if initial_weights is None
            else jnp.asarray(initial_weights, dtype=jnp.float32)
        )
        base_key = jax.random.PRNGKey(self.seed)
        result = FitResult(state=GradState(weights=w))
        test_losses_newest_first: List[float] = []

        start_epoch = 0
        restored = restore_sync_fit(
            self.checkpointer, self._opt_kind, bound_train.opt_state_leaves())
        if restored is not None:
            # early-stopping continuity: the criterion sees the full
            # newest-first test-loss history; optimizer continuity:
            # momentum/adam buffers resume where they left off (a zeroed
            # adam state on converged weights would bias-correct into a
            # large first step).  Kind/shape mismatches raise (shared
            # contract, checkpoint.decode_sync_fit_state)
            start_epoch, w_np, test_losses_newest_first, opt_leaves = restored
            w = jnp.asarray(w_np)
            if opt_leaves:
                bound_train.load_opt_state_leaves(opt_leaves)
            log.info("resumed from checkpoint at epoch %d", start_epoch)

        if start_epoch >= max_epochs:
            # a resumed run that is already done must not report epochs_run=0
            # with a NaN loss (ADVICE r2): evaluate the restored weights
            loss, acc = bound_train.evaluate(w)
            log.info(
                "checkpoint already at epoch %d >= max_epochs %d: nothing to "
                "run (loss=%.6f acc=%.4f)", start_epoch, max_epochs, loss, acc)
            result.epochs_run = start_epoch
            result.state = GradState(weights=w, loss=loss).finish()
            return result

        # DSGD_PROFILE_DIR: one whole period (epoch program, both
        # evaluations, the loop and its spans) from the first boundary at
        # which the epoch AND the evaluation programs have run twice (the
        # epoch program compiles a second time in epoch start+1, PERF.md);
        # a shorter fit traces its last epoch
        profile_epoch = min(start_epoch + 2, max_epochs - 1)
        profiling = profiled = False
        span = measure.span
        for epoch in range(start_epoch, max_epochs):
            if profiling:
                self._stop_profile()
                profiling = False
            elif self.profile_dir is not None and epoch == profile_epoch:
                jax.profiler.start_trace(self.profile_dir)
                profiling = profiled = True
            t0 = time.perf_counter()
            # every span here has three sinks (utils/measure.py): the
            # histogram exporters, a DSGD_TRACE span, and the profiler's
            # clock, where the benchmark lays them against the device's gaps
            with span("trainer.epoch", metrics=self.metrics,
                      node="trainer", epoch=epoch):
                # keyed by absolute epoch index: a resumed run continues the
                # same batch-sampling stream instead of replaying epochs
                # 0..N-1's keys; folded inside the epoch program
                w = bound_train.epoch(w, base_key, at=epoch)
            # The boundary is one queue and one wait: both evaluation
            # programs are enqueued behind the epoch program before the host
            # waits for anything, and their outputs come back in one pull,
            # so the device has work queued while the host waits.  Both
            # splits' phases stay (BoundSync.evaluate's): the train split's
            # dispatch and wait hold both splits', each split's pull and reg
            # take its own numbers apart.
            with span("trainer.evaluate", metrics=self.metrics,
                      node="trainer", epoch=epoch, split="train"):
                with span("trainer.evaluate.dispatch", histogram=False, root=False):
                    outs = (bound_train.dispatch_evaluation(w),
                            bound_test.dispatch_evaluation(w))
                with span("trainer.evaluate.wait", histogram=False, root=False):
                    jax.block_until_ready(w)
                    epoch_s = time.perf_counter() - t0  # the epoch program's own time
                    pulled = jax.device_get(outs)
                train_eval = bound_train.read(pulled[0])
            with span("trainer.evaluate", metrics=self.metrics,
                      node="trainer", epoch=epoch, split="test"):
                with span("trainer.evaluate.dispatch", histogram=False, root=False):
                    pass  # enqueued with the train split's
                with span("trainer.evaluate.wait", histogram=False, root=False):
                    pass  # pulled with the train split's
                test_eval = bound_test.read(pulled[1])
            loss, acc = train_eval.objective, train_eval.accuracy
            test_loss, test_acc = test_eval.objective, test_eval.accuracy
            with span("trainer.bookkeeping", metrics=self.metrics,
                      node="trainer", epoch=epoch):
                record_epoch(result, test_losses_newest_first, epoch,
                             loss, acc, test_loss, test_acc, epoch_s)
                self.metrics.histogram("master.sync.loss").record(loss)
                self.metrics.histogram("master.sync.acc").record(100 * acc)
                self.metrics.histogram("master.sync.epoch.seconds").record(epoch_s)
                nonzero = ""
                if train_eval.nonzero is not None:  # FTRL: L1's exact zeros beside the objective
                    result.nonzero.append(train_eval.nonzero)
                    result.penalty.append(train_eval.penalty)
                    nonzero = f" nonzero={train_eval.nonzero}"
                log.info(
                    "epoch %d: loss=%.6f acc=%.4f test_loss=%.6f test_acc=%.4f%s (%.2fs)",
                    epoch, loss, acc, test_loss, test_acc, nonzero, epoch_s,
                )

            if self.checkpointer is not None and (epoch + 1) % self.checkpoint_every == 0:
                save_sync_fit(self.checkpointer, epoch + 1, w,
                              test_losses_newest_first, self._opt_kind,
                              bound_train.opt_state_leaves())

            if criterion is not None:
                # a caller's criterion is the caller's time (the benchmark's
                # hook runs here), told apart from the program's own
                with span("trainer.criterion", metrics=self.metrics,
                          node="trainer", epoch=epoch):
                    stop = criterion(test_losses_newest_first)
                if stop:
                    log.info("Converged to target: stopping computation")
                    break
        else:
            if max_epochs > 0:
                log.info("Reached max number of epochs: stopping computation")
        if profiling:
            self._stop_profile()
        save_sync_fit_final(
            self.checkpointer, result.epochs_run, start_epoch,
            self.checkpoint_every, w, test_losses_newest_first,
            self._opt_kind, bound_train.opt_state_leaves())
        if self.profile_dir is not None and not profiled:
            log.warning(
                "no profiler trace captured: the fit stopped before epoch %d",
                profile_epoch,
            )

        result.state = GradState(
            weights=w, loss=result.losses[-1] if result.losses else float("nan")
        ).finish()
        return result

    def _stop_profile(self) -> None:
        jax.profiler.stop_trace()
        log.info("profiler trace written to %s", self.profile_dir)

    def predict(self, weights: jax.Array, data: Dataset):
        """Predictions over a split (Master.predict, Master.scala:61-75)."""
        bound = self.engine.bind(data)
        return bound.predict(weights)

    def evaluate(self, weights: jax.Array, data: Dataset):
        """(objective, accuracy) — Master.distributedLoss/Accuracy.  Binds
        `data` afresh: where the evaluation's margins are planned
        (`kernels.Fetch` 'planned') every call makes the binding's margin
        plan again; a caller that evaluates one split often binds it once
        (`engine.bind`) and calls the binding's `evaluate`."""
        return self.engine.bind(data).evaluate(weights)
