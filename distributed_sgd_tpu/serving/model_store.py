"""Checkpoint-backed model store with atomic hot-swap and push-apply.

Serves the model the trainer just saved, with no server restart.  Two
update paths feed the published snapshot:

- **file poll** (the PR-1 path, always on by default): a background poll
  re-reads the checkpoint directory (`Checkpointer.poll_newer`) every
  `poll_s` seconds and, when a newer step appears, restores its weights
  and swaps the published snapshot in one reference assignment;
- **push** (`apply_push`, the serving-fleet path — docs/SERVING.md): the
  trainer's master streams versioned weight updates over the `PushWeights`
  RPC — a full tensor, or a sparse absolute-value `WeightDelta` applied IN
  PLACE on top of the current snapshot (rpc/codec.py `apply_weight_delta`,
  the same codec the sync broadcast plane uses).  The first applied push
  switches the store to push mode: the periodic file poll stops swapping
  (the push stream is authoritative — after a canary rollback the file
  may hold exactly the version that was rolled back), but a push whose
  delta base does not match the current snapshot (version gap: restarted
  replica, missed push) NACKs and falls back to one forced full-file
  reload, so a replica can always catch up from the shared directory.

Readers (`get()`) always see a complete (step, weights) pair — a flush
that started on step N finishes on step N even if N+1 lands mid-batch,
and the NEXT flush picks up N+1.

All checkpoint formats in this repo interchange through the same snapshot
contract (checkpoint.py): every snapshot carries a dense `weights` vector,
which is the only key serving needs — optimizer state and early-stop
history are ignored.

A restore that fails (e.g. the poll raced a half-committed write before
orbax finalized it) keeps the previous snapshot and counts
`serve.model.reload.errors`; successful swaps count `serve.model.reload`,
applied pushes count `serve.model.push.full` / `serve.model.push.delta`,
and every swap (either path) publishes the `serve.model.version` gauge so
the cluster /metrics endpoint shows which version each replica serves.
"""

from __future__ import annotations

import logging
import threading
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from distributed_sgd_tpu.models.linear import require_flat_weights
from distributed_sgd_tpu.utils import metrics as metrics_mod

log = logging.getLogger("dsgd.serving")


class ModelStore:
    def __init__(self, checkpoint_dir: str, poll_s: float = 2.0, metrics=None):
        from distributed_sgd_tpu.checkpoint import Checkpointer

        if poll_s <= 0:
            raise ValueError("poll_s must be > 0")
        self._ckpt = Checkpointer(checkpoint_dir)
        self.poll_s = float(poll_s)
        self._metrics = metrics
        # the published snapshot; swapped by ONE reference assignment, so
        # readers never lock.  _swap_lock serializes WRITERS only (the poll
        # thread vs concurrent PushWeights servicer calls — a delta apply
        # is a read-modify-write and must not race another swap).
        self._current: Optional[Tuple[int, jnp.ndarray]] = None
        self._swap_lock = threading.Lock()
        # set by the first applied push: the push stream is authoritative
        # and the periodic file poll stops swapping (see module docstring)
        self._push_mode = False
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="serve-ckpt-poll")
        self.poll_once()  # serve immediately if a snapshot already exists

    # -- readers -------------------------------------------------------------

    def get(self) -> Optional[Tuple[int, jnp.ndarray]]:
        """(step, weights) of the newest loaded snapshot, or None before the
        first checkpoint lands."""
        return self._current

    @property
    def step(self) -> Optional[int]:
        cur = self._current
        return cur[0] if cur is not None else None

    @property
    def push_mode(self) -> bool:
        """True once a push has been applied (file poll no longer swaps)."""
        return self._push_mode

    # -- the swap ------------------------------------------------------------

    def _publish(self, step: int, weights, reason: str) -> None:
        """One reference assignment + version gauge; callers hold _swap_lock."""
        self._current = (int(step), weights)
        if self._metrics is not None:
            self._metrics.gauge(metrics_mod.SERVE_MODEL_VERSION).set(step)
        log.info("serving model swapped to step %d (%d features, %s) on %s",
                 step, weights.shape[0], reason,
                 ",".join(sorted(str(d) for d in weights.devices())))

    # -- the file poll -------------------------------------------------------

    def poll_once(self, force: bool = False) -> bool:
        """Check for a newer checkpoint file; swap it in.  True iff swapped.

        `force` (the version-gap fallback of `apply_push`) bypasses push
        mode AND the newer-step comparison: the file's latest snapshot
        wins outright, whatever version the push stream left behind."""
        cur = self._current
        if self._push_mode and not force:
            return False
        try:
            restored = self._ckpt.poll_newer(
                None if force else (cur[0] if cur is not None else None))
            if restored is None:
                return False
            step, state = restored
            weights = jnp.asarray(state["weights"], dtype=jnp.float32)
            require_flat_weights(weights, "the serving model store")
        except Exception as e:  # noqa: BLE001 - keep serving the old snapshot
            log.warning("checkpoint reload failed (serving stays on step %s): %s",
                        cur[0] if cur else None, e)
            if self._metrics is not None:
                self._metrics.counter("serve.model.reload.errors").increment()
            return False
        with self._swap_lock:
            # re-check under the writer lock: the (multi-second) orbax
            # restore above ran unlocked, and a push may have landed
            # meanwhile — the push stream is authoritative, so an
            # unforced file poll must never clobber it
            now = self._current
            if not force and (self._push_mode
                              or (now is not None and step <= now[0])):
                return False
            self._publish(step, weights, reason="file reload")
        if self._metrics is not None:
            self._metrics.counter("serve.model.reload").increment()
        return True

    def _loop(self) -> None:
        while not self._stop.wait(self.poll_s):
            self.poll_once()

    # -- push-apply (PushWeights; docs/SERVING.md "serving fleet") -----------

    def apply_push(self, request) -> Tuple[bool, int]:
        """Apply one PushWeightsRequest; returns (ok, serving_step).

        Full form: the pushed tensor replaces the snapshot at the pushed
        version unconditionally — the pusher is authoritative, which is
        what lets a canary ROLLBACK re-install an older version (a
        monotone guard would wedge the rollback).  Delta form: applied in
        place iff the current snapshot IS the delta's base version;
        anything else — empty store, missed push, restarted replica — is
        a version gap: NACK (the pusher resends full) plus one forced
        full-file reload so a shared checkpoint directory also heals it.
        """
        from distributed_sgd_tpu.rpc import codec

        version = int(request.version)
        with self._swap_lock:
            if request.HasField("weights"):
                w = jnp.asarray(codec.decode_tensor(request.weights),
                                dtype=jnp.float32)
                self._push_mode = True
                self._publish(version, w, reason="push full")
                if self._metrics is not None:
                    self._metrics.counter(
                        metrics_mod.SERVE_MODEL_PUSH_FULL).increment()
                return True, version
            cur = self._current
            if (request.HasField("delta") and cur is not None
                    and cur[0] == request.delta.base_version):
                w = jnp.asarray(
                    codec.apply_weight_delta(np.asarray(cur[1]), request.delta))
                self._push_mode = True
                self._publish(version, w, reason="push delta")
                if self._metrics is not None:
                    self._metrics.counter(
                        metrics_mod.SERVE_MODEL_PUSH_DELTA).increment()
                return True, version
        # version gap (or a request with neither arm): count it, then fall
        # back to a full-file reload OUTSIDE the swap lock (orbax I/O must
        # not block concurrent pushes); whatever the directory holds is
        # better than a replica pinned on a stale snapshot
        if self._metrics is not None:
            self._metrics.counter(metrics_mod.SERVE_MODEL_PUSH_GAP).increment()
        log.warning(
            "push version gap: delta base %s vs serving step %s — NACK + "
            "full-file reload fallback",
            request.delta.base_version if request.HasField("delta") else None,
            self.step)
        self.poll_once(force=True)
        return False, self.step or 0

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "ModelStore":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=self.poll_s + 1.0)
        self._ckpt.close()
