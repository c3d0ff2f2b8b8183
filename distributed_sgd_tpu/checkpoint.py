"""Checkpoint / resume via orbax.

A strict capability superset of the reference, which persists nothing —
its only recovery mechanism is the async master's in-memory best-weights
tracking (MasterAsync.scala:66-69,130-139; SURVEY.md §5.4).  Wiring
(`Config.checkpoint_dir`, built in main.py):

- SyncTrainer saves weights (plus optimizer state and the newest-first
  test-loss history) every `checkpoint_every` epochs and resumes from the
  latest snapshot, continuing the same batch-sampling stream, momentum
  buffers, and early-stopping window;
- the async drivers (Hogwild gossip, local-SGD, gRPC MasterNode.fit_async)
  hand their Checkpointer to LossChecker, which persists the best-so-far
  weights + full smoothing history on every improvement and every
  `save_every`-th plateau check — so the reference's "return best"
  behavior survives a process kill — and main.py feeds the latest snapshot
  back as `initial_weights` on restart.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from distributed_sgd_tpu.ops import ftrl

log = logging.getLogger("dsgd.checkpoint")


def _orbax():
    """`orbax.checkpoint`, imported when the first Checkpointer is built:
    the import drags in google-cloud logging, whose start-up scan of every
    installed distribution cost ~4 s here and ~35 s per process on the
    chip machine (chip run, PR 21) — paid by every role, checkpointing or
    not, while it sat at module level."""
    try:
        import orbax.checkpoint as ocp
    except ImportError as e:
        raise RuntimeError("orbax is not available") from e
    return ocp


class Checkpointer:
    """Epoch-cadence training-state checkpointing."""

    def __init__(self, directory: str, keep: int = 3):
        ocp = self._ocp = _orbax()
        self.directory = os.path.abspath(directory)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(max_to_keep=keep, create=True),
        )

    def save(self, step: int, weights, extra: Optional[Dict[str, Any]] = None) -> bool:
        from distributed_sgd_tpu.utils.measure import span

        with span("ckpt.save", step=step):
            state = {"weights": np.asarray(weights)}
            if extra:
                state.update({k: np.asarray(v) for k, v in extra.items()})
            saved = self._mgr.save(
                step, args=self._ocp.args.StandardSave(state))
            self._mgr.wait_until_finished()
        if saved:
            log.info("checkpoint saved at step %d -> %s", step, self.directory)
        else:  # orbax declines e.g. writes to an already-existing step
            log.warning("checkpoint at step %d NOT saved (step exists?)", step)
        return bool(saved)

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def reload(self) -> None:
        """Re-read the step list from disk.

        orbax's CheckpointManager caches the directory listing at
        construction and after its own saves; a process that only READS a
        directory another process writes (the serving hot-reload poll,
        serving/model_store.py) must drop that cache to observe new steps.
        """
        self._mgr.reload()

    def poll_newer(self, than: Optional[int]) -> Optional[Tuple[int, Dict[str, Any]]]:
        """One reader-side poll step: drop the cached directory listing,
        and restore the latest snapshot iff its step is newer than `than`
        (None = anything counts as newer).  Returns None when nothing
        newer exists — or when the newest snapshot was deleted between
        listing and restore (restore_latest re-lists).  The shared dance
        of every directory WATCHER: the serving hot-reload poll
        (serving/model_store.py) and the fleet checkpoint distributor
        (serving/push.py CheckpointDistributor)."""
        self.reload()
        step = self.latest_step()
        if step is None or (than is not None and step <= than):
            return None
        return self.restore_latest()

    def restore_latest(self) -> Optional[Tuple[int, Dict[str, Any]]]:
        from distributed_sgd_tpu.utils.measure import span

        step = self._mgr.latest_step()
        if step is None:
            return None
        # explicit StandardRestore args: arg-less restore() only works on a
        # manager that already SAVED this process (saving registers the item
        # handler as a side effect) — a restore-only process (resume at
        # startup, the serving hot-reload poll) needs the args spelled out
        with span("ckpt.restore", step=step):
            state = self._mgr.restore(
                step, args=self._ocp.args.StandardRestore())
        state["weights"] = jnp.asarray(state["weights"])
        return step, state

    def close(self) -> None:
        self._mgr.close()


# -- shared sync-fit snapshot contract ------------------------------------
#
# Both sync engines (mesh SyncTrainer, core/trainer.py; RPC
# MasterNode.fit_sync, core/master.py) persist the same state keys —
# weights, newest-first test-loss history, optimizer kind tag, flat
# optimizer-state leaves — so their checkpoints are interchangeable.  The
# contract lives here, once.


def opt_kind_tag(optimizer) -> str:
    """Checkpoint tag for structural resume validation: string-configured
    optimizers validate by name; arbitrary optax transformations all tag
    'custom' (their identity is not recoverable from a string)."""
    if isinstance(optimizer, str):
        return optimizer
    return "sgd" if optimizer is None else "custom"


def sync_fit_extra(
    test_losses_newest_first, opt_kind: str, opt_leaves
) -> Dict[str, Any]:
    """Build the `extra` dict saved alongside the weights."""
    ftrl.refuse(opt_kind, "checkpoint.FitState")
    extra: Dict[str, Any] = {}
    if test_losses_newest_first:
        extra["test_losses_nf"] = np.asarray(test_losses_newest_first, np.float32)
    extra["opt_kind"] = np.frombuffer(opt_kind.encode(), dtype=np.uint8)
    for i, leaf in enumerate(opt_leaves):
        extra[f"opt_{i}"] = np.asarray(leaf)
    return extra


def decode_sync_fit_state(state: Dict[str, Any], opt_kind: str, expected_leaves):
    """Decode + validate a sync-fit snapshot against the configured optimizer.

    Returns (test_losses_newest_first, opt_leaves).  Refuses a snapshot
    written under a different optimizer kind, leaf count, or leaf shape
    (e.g. a kernel-layout change) rather than silently resuming with
    zeroed or misassembled optimizer state.
    """
    test_nf = (
        [float(x) for x in np.asarray(state["test_losses_nf"])]
        if "test_losses_nf" in state else []
    )
    saved_kind = (
        bytes(np.asarray(state["opt_kind"], np.uint8)).decode()
        if "opt_kind" in state else "sgd"
    )
    if saved_kind != opt_kind:
        raise ValueError(
            f"checkpoint was written with optimizer {saved_kind!r} but this "
            f"run is configured with {opt_kind!r}; resume with the original "
            f"optimizer or point at a fresh checkpoint_dir"
        )
    opt_leaves = []
    while f"opt_{len(opt_leaves)}" in state:
        opt_leaves.append(state[f"opt_{len(opt_leaves)}"])
    expected = list(expected_leaves)
    shapes_ok = len(opt_leaves) == len(expected) and all(
        np.shape(g) == np.shape(e) for g, e in zip(opt_leaves, expected)
    )
    if not shapes_ok:
        raise ValueError(
            f"checkpointed optimizer-state leaves "
            f"{[np.shape(x) for x in opt_leaves]} do not match the configured "
            f"optimizer/kernel layout {[np.shape(x) for x in expected]}; "
            f"resume with the original optimizer and kernel, or use a fresh "
            f"checkpoint_dir"
        )
    return test_nf, opt_leaves


# -- the sync-fit snapshot PROTOCOL, single-sourced --------------------------
# Three fit loops speak it (mesh SyncTrainer, RPC fit_sync, the 2-D
# FeatureShardedEngine), and their checkpoints interchange BECAUSE they all
# go through these helpers: weights + newest-first test-loss history (the
# early-stopping window) + optimizer kind/leaves, saved every
# `checkpoint_every` epochs plus once at any off-cadence end.


def restore_sync_fit(checkpointer, opt_kind: str, expected_leaves):
    """Restore the latest sync-fit snapshot, validated against the
    configured optimizer.  Returns (start_epoch, weights_np,
    test_losses_newest_first, opt_leaves), or None when there is no
    checkpointer or no snapshot."""
    if checkpointer is None:
        return None
    restored = checkpointer.restore_latest()
    if restored is None:
        return None
    start_epoch, state = restored
    test_nf, opt_leaves = decode_sync_fit_state(state, opt_kind, expected_leaves)
    return start_epoch, np.asarray(state["weights"]), test_nf, opt_leaves


def save_sync_fit(checkpointer, epoch: int, weights, test_losses_newest_first,
                  opt_kind: str = "sgd", opt_leaves=()) -> None:
    checkpointer.save(epoch, weights, extra=sync_fit_extra(
        test_losses_newest_first, opt_kind, list(opt_leaves)))


# -- crash-safe FULL fit state (docs/ELASTICITY.md; DSGD_FIT_CKPT_EVERY) -----
#
# The epoch-cadence snapshots above capture weights + optimizer state at
# epoch boundaries; a master killed MID-epoch replays the whole epoch on
# restart.  The fit-state snapshot captures everything the fit_sync loop
# needs to resume BIT-EXACTLY from the last completed window: weights,
# optimizer leaves, the epoch + window cursor, the np.random.Generator
# bit-generator state (so the resumed run replays the identical sample
# draws), the early-stopping history, the broadcast version, and the
# fit_token lineage (every token that has driven this fit — a restarted
# master issues a NEW token from its per-incarnation nonce, so long-lived
# workers reset stale per-fit state, and the lineage records the chain).
# Written ATOMICALLY (tmp + os.replace): a crash mid-write leaves the
# previous snapshot intact, never a torn file.

FIT_STATE_FILE = "fit_state.npz"


def fit_state_path(directory: str) -> str:
    """Canonical fit-state snapshot location under a checkpoint dir."""
    return os.path.join(directory, FIT_STATE_FILE)


@dataclasses.dataclass
class FitState:
    """Decoded crash-recovery snapshot of one fit_sync loop."""

    epoch: int
    batch: int                    # window cursor within `epoch`
    weights: np.ndarray
    rng_state: Dict[str, Any]     # np.random.Generator.bit_generator.state
    test_losses_nf: List[float]   # newest-first early-stopping history
    opt_leaves: List[np.ndarray]
    bcast_version: int
    fit_tokens: List[int]         # lineage: tokens that have driven this fit
    # terminal marker: the CONVERGENCE CRITERION ended this fit at
    # epoch < max_epochs — a restart must take the nothing-to-run path
    # even though the epoch cursor says budget remains (resuming a
    # converged fit would train PAST convergence).  Budget exhaustion is
    # deliberately NOT marked: the epoch cursor already carries it, and
    # an unmarked terminal snapshot lets a re-run with a raised
    # max_epochs resume training
    finished: bool = False


def save_fit_state(
    path: str,
    *,
    weights,
    epoch: int,
    batch: int,
    rng_state: Dict[str, Any],
    test_losses_nf,
    opt_kind: str,
    opt_leaves,
    bcast_version: int = 0,
    fit_tokens=(),
    finished: bool = False,
) -> None:
    """Atomic full-fit-state snapshot (see the section comment above)."""
    from distributed_sgd_tpu.utils.measure import span

    ftrl.refuse(opt_kind, "checkpoint.FitState")
    with span("ckpt.save", step=int(epoch), batch=int(batch)):
        state: Dict[str, Any] = {
            "weights": np.asarray(weights, np.float32),
            "epoch": np.int64(epoch),
            "batch": np.int64(batch),
            "rng_state": np.frombuffer(
                json.dumps(rng_state).encode(), dtype=np.uint8),
            "opt_kind": np.frombuffer(opt_kind.encode(), dtype=np.uint8),
            "bcast_version": np.int64(bcast_version),
            "fit_tokens": np.asarray(list(fit_tokens), dtype=np.int64),
            "finished": np.int64(1 if finished else 0),
        }
        if test_losses_nf:
            state["test_losses_nf"] = np.asarray(test_losses_nf, np.float32)
        for i, leaf in enumerate(opt_leaves):
            state[f"opt_{i}"] = np.asarray(leaf)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **state)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)  # atomic on POSIX: old snapshot or new, never torn


def restore_fit_state(path: Optional[str], opt_kind: str,
                      expected_leaves) -> Optional[FitState]:
    """Load + validate a fit-state snapshot; None when absent.  Optimizer
    kind/leaf validation reuses decode_sync_fit_state, so a snapshot from
    a differently-configured fit refuses loudly instead of resuming with
    misassembled state."""
    if not path or not os.path.exists(path):
        return None
    from distributed_sgd_tpu.utils.measure import span

    with span("ckpt.restore", step=-1):
        with np.load(path) as z:
            state = {k: z[k] for k in z.files}
    test_nf, opt_leaves = decode_sync_fit_state(state, opt_kind, expected_leaves)
    return FitState(
        epoch=int(state["epoch"]),
        batch=int(state["batch"]),
        weights=np.asarray(state["weights"], np.float32),
        rng_state=json.loads(bytes(np.asarray(state["rng_state"],
                                              np.uint8)).decode()),
        test_losses_nf=test_nf,
        opt_leaves=opt_leaves,
        bcast_version=int(state.get("bcast_version", 0)),
        fit_tokens=[int(t) for t in state.get("fit_tokens", [])],
        finished=bool(int(state.get("finished", 0))),
    )


def save_sync_fit_final(checkpointer, epochs_run: int, start_epoch: int,
                        checkpoint_every: int, weights,
                        test_losses_newest_first, opt_kind: str = "sgd",
                        opt_leaves=()) -> None:
    """Off-cadence end (early stop, or max_epochs not a multiple of
    `checkpoint_every`): persist the final state so no run with a
    checkpointer ends unsaved.

    `weights` may be a zero-arg callable, resolved only when the save
    actually happens — so a caller whose weight materialization is
    expensive (the feature-sharded engine's device->host gather) pays
    nothing on the no-save path."""
    if (
        checkpointer is not None
        and epochs_run > start_epoch
        and epochs_run % checkpoint_every != 0
    ):
        if callable(weights):
            weights = weights()
        save_sync_fit(checkpointer, epochs_run, weights,
                      test_losses_newest_first, opt_kind, opt_leaves)
