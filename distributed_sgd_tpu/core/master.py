"""Master node: cluster membership, readiness barrier, distributed fits.

TPU-native re-design of the reference master (core/Master.scala,
core/MasterSync.scala, core/MasterAsync.scala).  The control plane is
preserved structurally — registration with full-mesh peer introduction
(Master.scala:222-243), readiness barrier gating all work
(Master.scala:34-59), unregister broadcast (Master.scala:245-253), the
sync per-batch fan-out/barrier/mean loop (Master.scala:120-218), the async
StartAsync fan-out + update counting + loss checker (MasterAsync.scala) —
while all local evaluation runs compiled on the master's device and worker
gradient computation runs compiled on theirs.

This RPC mode exists for reference-parity cluster topology and cross-host
deployments WITHOUT a shared jax mesh; when all devices live in one
process/slice, parallel/sync.py's in-mesh engine is the fast path (no
weight serialization at all).
"""

from __future__ import annotations

import collections
import logging
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional, Tuple

import grpc
import jax
import jax.numpy as jnp
import numpy as np

from distributed_sgd_tpu.checkpoint import (
    restore_fit_state,
    restore_sync_fit,
    save_fit_state,
    save_sync_fit,
    save_sync_fit_final,
)
from distributed_sgd_tpu.core.early_stopping import Criterion
from distributed_sgd_tpu.core.grad_state import GradState
from distributed_sgd_tpu.core.loss_check import LossChecker, async_fit_result
from distributed_sgd_tpu.core.split import vanilla_split, weighted_split
from distributed_sgd_tpu.core.trainer import FitResult, record_epoch
from distributed_sgd_tpu.data.rcv1 import Dataset
from distributed_sgd_tpu.models.linear import LinearModel, require_single_output
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine
from distributed_sgd_tpu.rpc import codec, dsgd_pb2 as pb
from distributed_sgd_tpu.rpc.service import (
    RpcPolicy,
    WorkerStub,
    add_master_servicer,
    new_channel,
    new_server,
)
from distributed_sgd_tpu import trace as trace_mod
from distributed_sgd_tpu.telemetry import resources
from distributed_sgd_tpu.trace import flight
from distributed_sgd_tpu.utils import metrics as metrics_mod
from distributed_sgd_tpu.utils.log import node_logger

SplitFn = Callable[[int, int], List[np.ndarray]]


class _FailureTracker:
    """Consecutive-failure counter with an eviction threshold.

    Shared policy for every fan-out that classifies worker failures
    (heartbeat probes, Gradient barriers, Forward eval): a success resets
    the worker's count; `record_failure` returns True once the worker has
    failed `threshold` consecutive times and should be declared dead.
    """

    def __init__(self, threshold: int):
        self.threshold = max(1, int(threshold))
        self._counts: Dict[Tuple[str, int], int] = {}

    def record_ok(self, key: Tuple[str, int]) -> None:
        self._counts.pop(key, None)

    def record_failure(self, key: Tuple[str, int]) -> Tuple[int, bool]:
        n = self._counts.get(key, 0) + 1
        if n >= self.threshold:
            self._counts.pop(key, None)
            return n, True
        self._counts[key] = n
        return n, False


def _await_futures(futs, bytes_counter=None):
    """Barrier with failure classification over [(key, future-or-None)].

    Returns (ok, failed): ok = [(key, reply)] in input order, failed =
    [(key, status-or-error)].  A None future stands for a channel that
    closed under us at call time.  `bytes_counter` (optional) accounts
    every reply that ARRIVED, right here in result handling — so a window
    later discarded because a sibling failed (fit_sync's retry) still
    counts its received bytes, which the old post-barrier sum missed."""
    ok, failed = [], []
    for key, fut in futs:
        try:
            if fut is None:
                raise ValueError("channel closed")
            reply = fut.result()
            if bytes_counter is not None:
                bytes_counter.increment(reply.ByteSize())
            ok.append((key, reply))
        except (grpc.RpcError, ValueError) as e:
            failed.append((key, e.code() if isinstance(e, grpc.RpcError) else e))
    return ok, failed


class _ArrivalDecoder:
    """Send-ordered decode-on-arrival for the sync fan-in (ROADMAP item 2),
    optionally SHARDED into K decoder lanes (DSGD_FANIN_LANES,
    docs/SCALING.md).

    The full-barrier fan-in used to decode every Gradient reply AFTER the
    barrier closed — N dim-sized scatter-decodes serialized on the
    critical path while N-1 of them could have run during the wait.  This
    moves each reply's decode into the reply's own arrival callback,
    constrained to SEND ORDER (the decode cursor only advances over the
    contiguous settled prefix), so float accumulation order — and
    therefore the resulting weights — stays bit-identical to the
    post-barrier loop.  With in-order arrivals every decode but the
    slowest reply's overlaps the wait; out-of-order arrivals decode as
    soon as their prefix completes.

    ``lanes=K >= 1`` shards the DECODE: workers map to lanes by a fixed
    send-index assignment (``i % K``), each lane guards its own slot map
    with its own lock, and — the point — the expensive half of the decode
    (`codec.parse_grad`: repeated-field -> ndarray materialization, qint8
    dequantization) runs in the arrival callback BEFORE any lock is
    taken, so K callbacks parse concurrently instead of queueing on one
    decoder lock.  Only the cheap float ACCUMULATION (`codec.add_parsed`)
    is serialized, under the accumulator lock, walking the contiguous
    settled prefix in send order.  Keeping the accumulation a single
    send-ordered f32 chain is what makes the lanes BIT-EXACT against the
    single-accumulator path: a per-lane partial-sum + K-way reduce would
    regroup the float additions ((r0+r1)+(r2+r3) instead of
    ((r0+r1)+r2)+r3) and drift in the last ulp — asserted impossible by
    tests/test_fanin_lanes.py, which pins lanes-on weights byte-identical
    to lanes-off across sync, quorum, retry, and compressed rounds.

    ``defer=True`` (the quorum barrier's mode) parses arrivals into a
    side table but never accumulates: the contributor set (hedge wins,
    late originals) is only known at round close, when the caller replays
    it in canonical order through ``add_into`` — pre-parsed replies cost
    O(dim) adds only, unparsed ones (hedge replies arrive on unary
    futures nobody watches) parse on the spot.

    Lock discipline: parse outside every lock; lane locks guard only
    their slot maps (set-once per index, so a callback racing `finish()`
    can never decode a reply twice); the accumulator lock serializes the
    cursor walk and is never held while a lane lock is awaited in the
    other direction.  A failed or stale reply marks the window dirty and
    freezes the cursor — the caller retries the window and the
    accumulator is re-zeroed on the next attempt, so partially-decoded
    state never leaks into an applied update.  ``lanes=0`` (default)
    keeps the pre-shard single-lock path byte-for-byte."""

    def __init__(self, acc: np.ndarray, lanes: int = 0, defer: bool = False):
        self.acc = acc
        self.lanes = max(0, int(lanes))
        self.defer = bool(defer)
        self._lock = threading.Lock()
        self._results: Dict[int, object] = {}
        self._cursor = 0
        self.dirty = False
        self.decoded = 0
        self.parsed = 0
        if self.lanes:
            k = self.lanes
            self._lane_locks = [threading.Lock() for _ in range(k)]
            # per-lane slot maps: index -> (reply | None, parsed | None)
            self._lane_slots: List[Dict[int, tuple]] = [dict() for _ in range(k)]
            # defer mode's side table: id(reply) -> (reply, parsed); the
            # reply reference keeps the id stable until the round closes
            self._parsed_by_reply: Dict[int, tuple] = {}

    # -- shared entry points ------------------------------------------------

    def watch(self, i: int, fut) -> None:
        if not self.lanes:
            if fut is None:
                with self._lock:
                    self._results.setdefault(i, None)
                    self._advance()
                return
            fut.add_done_callback(lambda f, i=i: self._on_done(i, f))
            return
        if fut is None:
            self._settle_lane(i, None)
            return
        fut.add_done_callback(lambda f, i=i: self._on_done_lane(i, f))

    def finish(self, futs) -> bool:
        """Drain any settled tail the callbacks have not reached yet (the
        barrier already awaited every future, but gRPC's callback threads
        may lag the main thread's own `result()`); returns clean?"""
        if not self.lanes:
            with self._lock:
                for i, (_key, fut) in enumerate(futs):
                    if i not in self._results:
                        try:
                            self._results[i] = (fut.result()
                                                if fut is not None else None)
                        except Exception:  # noqa: BLE001
                            self._results[i] = None
                self._advance()
                return not self.dirty
        for i, (_key, fut) in enumerate(futs):
            lane = self._lane_locks[i % self.lanes]
            with lane:
                seen = i in self._lane_slots[i % self.lanes]
            if not seen:
                try:
                    reply = fut.result() if fut is not None else None
                except Exception:  # noqa: BLE001
                    reply = None
                self._settle_lane(i, reply)
        self._advance_lanes()
        return not self.dirty

    # -- legacy single-lock path (lanes=0) ----------------------------------

    def _on_done(self, i: int, fut) -> None:
        try:
            reply = fut.result()
        except Exception:  # noqa: BLE001 - classification is the barrier's job
            reply = None
        with self._lock:
            self._results.setdefault(i, reply)
            self._advance()

    def _advance(self) -> None:
        while not self.dirty and self._cursor in self._results:
            r = self._results[self._cursor]
            if r is None or r.stale_version:
                # the window will retry: stop decoding (the work would be
                # discarded) and let the caller's classification decide
                self.dirty = True
                return
            codec.decode_grad_into(r, self.acc)
            self.decoded += 1
            self._cursor += 1

    # -- sharded lanes (lanes=K) --------------------------------------------

    def _on_done_lane(self, i: int, fut) -> None:
        try:
            reply = fut.result()
        except Exception:  # noqa: BLE001 - classification is the barrier's job
            reply = None
        self._settle_lane(i, reply)

    def _settle_lane(self, i: int, reply) -> None:
        # parse BEFORE any lock: this is the concurrency the lanes buy
        parsed = None
        if reply is not None and not reply.stale_version:
            parsed = codec.parse_grad(reply)
        lane = i % self.lanes
        with self._lane_locks[lane]:
            slots = self._lane_slots[lane]
            if i in slots:  # set-once: a lagging callback must not re-enter
                return
            slots[i] = (reply, parsed)
        if parsed is not None:
            with self._lock:  # exact count; defer's side table reads here too
                self.parsed += 1
                if self.defer:
                    self._parsed_by_reply[id(reply)] = (reply, parsed)
        if not self.defer:
            self._advance_lanes()

    def _advance_lanes(self) -> None:
        if self.defer:
            return
        with self._lock:  # the accumulator lock: one ordered f32 chain
            while not self.dirty:
                lane = self._cursor % self.lanes
                with self._lane_locks[lane]:
                    item = self._lane_slots[lane].get(self._cursor)
                if item is None:
                    return
                reply, parsed = item
                if reply is None or reply.stale_version:
                    self.dirty = True
                    return
                codec.add_parsed(parsed, self.acc)
                self.decoded += 1
                self._cursor += 1

    def add_into(self, reply, out: np.ndarray) -> None:
        """Defer mode's round-close accumulate: reuse the arrival
        callback's parse when one landed for this reply object, parse on
        the spot otherwise (hedge replies, late settles) — the float adds
        are `decode_grad_into`'s exactly, in the caller's order."""
        item = None
        if self.lanes and self.defer:
            with self._lock:
                item = self._parsed_by_reply.get(id(reply))
        if item is not None and item[0] is reply:
            codec.add_parsed(item[1], out)
        else:
            codec.decode_grad_into(reply, out)


class _LatencyEwma:
    """Per-worker Gradient reply-latency EWMA (mean + mean absolute
    deviation) feeding the quorum barrier's adaptive soft deadline
    (docs/FAULT_TOLERANCE.md).

    `soft_deadline_s(keys, quorum)` answers "how long should the `quorum`
    fastest workers need?": per worker a p95 proxy (mean + 3 * deviation),
    then the quorum-th SMALLEST of those, with slack.  Taking a low order
    statistic (not the max) is the point — a straggler's own tail must
    not stretch the deadline that is supposed to cut it off.  Returns
    None until at least `quorum` workers have history (the first windows
    include compile latency and must run as full barriers)."""

    SLACK = 1.5
    FLOOR_S = 0.05

    def __init__(self, alpha: float = 0.2):
        self.alpha = float(alpha)
        self._mean: Dict[Tuple[str, int], float] = {}
        self._dev: Dict[Tuple[str, int], float] = {}
        self._lock = threading.Lock()

    def record(self, key: Tuple[str, int], seconds: float) -> None:
        with self._lock:
            m = self._mean.get(key)
            if m is None:
                self._mean[key] = seconds
                self._dev[key] = 0.0
                return
            err = seconds - m
            self._mean[key] = m + self.alpha * err
            self._dev[key] = ((1 - self.alpha) * self._dev[key]
                              + self.alpha * abs(err))

    def p95_s(self, key: Tuple[str, int]) -> Optional[float]:
        with self._lock:
            m = self._mean.get(key)
            if m is None:
                return None
            return m + 3.0 * self._dev[key]

    def soft_deadline_s(self, keys, quorum: int) -> Optional[float]:
        ests = sorted(e for e in (self.p95_s(k) for k in keys) if e is not None)
        if len(ests) < max(1, quorum):
            return None
        return max(self.FLOOR_S, self.SLACK * ests[max(1, quorum) - 1])


def _reply_weight(reply) -> int:
    """Quorum mass of one barrier reply (docs/AGGREGATION.md §Quorum).

    Under DSGD_AGG_TREE the master's fan-in mixes three reply shapes: a
    subtree sum carries its exact contributor set (weight = |set|), an
    armless forwarded ack carries NOTHING — its gradient went up-tree —
    so it must not satisfy the quorum count blindly (weight 0), and a
    flat reply (a hedge, or a worker outside the plan) carries exactly
    one worker's gradient (weight 1).  Flat fits and Forward replies
    have neither field and weigh 1, so knobs-off counting is unchanged.
    """
    if getattr(reply, "agg_contributors", None):
        return len(reply.agg_contributors)
    if getattr(reply, "agg_forwarded", False):
        return 0
    return 1


def _await_quorum(futs, quorum: int, soft_deadline: float,
                  bytes_counter=None, latency: Optional[_LatencyEwma] = None):
    """Quorum barrier over [(key, future-or-None)] (docs/FAULT_TOLERANCE.md).

    Waits until every future settles, or until `soft_deadline` (absolute
    time.monotonic) passes with at least `quorum` worth of successful
    reply WEIGHT in hand — weight per _reply_weight, so a subtree sum
    counts its whole contributor set and a forwarded ack counts nothing
    (plain replies weigh 1, keeping the knobs-off count unchanged).
    Returns (ok, failed, pending): ok/failed as _await_futures,
    pending = [(key, future)] still in flight — the caller decides
    whether to hedge their slices, keep waiting, or discard them (late
    settles are idempotent: nobody reads an abandoned future).  Reply
    bytes and per-worker latencies are accounted as replies ARRIVE, so
    discarded stragglers still feed the EWMA that adapts the deadline."""
    cv = threading.Condition()

    def _notify(_):
        with cv:
            cv.notify()

    t_sent = time.monotonic()
    ok, failed, pending = [], [], []
    ok_weight = 0
    for key, fut in futs:
        if fut is None:
            failed.append((key, ValueError("channel closed")))
        else:
            pending.append((key, fut))
            fut.add_done_callback(_notify)
    while pending:
        still = []
        for key, fut in pending:
            if not fut.done():
                still.append((key, fut))
                continue
            try:
                reply = fut.result()
                if bytes_counter is not None:
                    bytes_counter.increment(reply.ByteSize())
                if latency is not None:
                    latency.record(key, time.monotonic() - t_sent)
                ok.append((key, reply))
                ok_weight += _reply_weight(reply)
            except grpc.RpcError as e:
                failed.append((key, e.code()))
        pending = still
        if not pending:
            break
        now = time.monotonic()
        remaining = soft_deadline - now
        if remaining <= 0 and ok_weight >= quorum:
            break
        with cv:
            # past the soft deadline but below quorum: keep waiting (the
            # per-call gRPC deadline is the hard bound), waking on settles
            cv.wait(timeout=0.25 if remaining <= 0
                    else max(0.005, min(0.25, remaining)))
    return ok, failed, pending


def _draw_ids(rng: np.random.Generator, part: np.ndarray, start: int,
              size: int) -> np.ndarray:
    """Uniform without-replacement draw of up to `size` sample ids from one
    worker's partition, clipped by the epoch cursor exactly like the
    reference's slice of a fresh permutation.

    The reference (Master.scala:184) re-permutes the ENTIRE partition
    every batch window and slices [start : start+size] — a fresh
    permutation per window makes that slice nothing more than a uniform
    without-replacement draw of min(size, len(part)-start) ids, at
    O(|part|) host work per window.  Generator.choice(replace=False) draws
    the same distribution at O(size): ~16 us vs ~6 ms on a 200k-sample
    partition.  The stream stays keyed by (seed, epoch) in the caller, so
    checkpoint resume replays identical draws."""
    take = min(int(size), max(0, len(part) - start))
    if take <= 0:
        return np.empty(0, dtype=np.int64)
    return np.asarray(part)[rng.choice(len(part), size=take, replace=False)]


class _DispatchStager:
    """Pooled round-(t+1) dispatch staging (DSGD_STAGE_POOL,
    docs/SCALING.md).

    The serialized master draws every worker's sample ids ON the dispatch
    critical path, one worker after another, each round.  With staging
    on, round t+1's draws run on the stage pool DURING round t's barrier
    (the main thread is blocked in gRPC with the GIL released, so the
    staging thread genuinely overlaps) — dispatch then starts from a
    ready ids-by-worker map.

    Determinism is the whole contract.  The sample stream is one
    epoch-keyed np.random.Generator consumed in (round, worker) order;
    a resumed fit replays it from a snapshotted bit-generator state.  So:

    - the pre-draw consumes the SAME values, in the SAME order, the
      serial path's next round would have consumed (one staging task
      draws all workers sequentially — never one task per worker);
    - the pre-draw snapshots the generator state first, and ANY
      discard — a retry re-dispatching the same cursor, a resplit
      changing membership/partitions, an epoch ending — RESTORES it, so
      the serial path's draw at that point reads the exact values it
      would have read had staging never run;
    - `rng_state()` exposes the state a SERIAL run would hold right now
      (the pre-draw base while a stage is pending), which is what the
      crash-safe fit-state snapshot must persist — persisting the
      post-pre-draw state would make a resumed fit skip a round's draws.

    The same pool is handed to `_BroadcastState` so per-worker request
    builds (weight-arm attach + frame construction) fan out across it at
    encode time; `hits`/`discards` feed master.sync.stage.* counters."""

    def __init__(self, pool_size: int):
        from concurrent.futures import ThreadPoolExecutor

        self.pool = ThreadPoolExecutor(
            max_workers=max(1, int(pool_size)), thread_name_prefix="stage-pool")
        self._fut = None
        self._base_state = None
        self._tag: Optional[Tuple[int, int]] = None
        self._keys: List[Tuple[str, int]] = []
        self.hits = 0
        self.discards = 0

    def stage(self, rng, keys, parts, epoch: int, cursor: int,
              span: int) -> None:
        """Arm one pre-draw for (epoch, cursor); call only with no stage
        pending (take/discard every round)."""
        assert self._fut is None, "a staged draw is already pending"
        self._base_state = rng.bit_generator.state
        self._tag = (int(epoch), int(cursor))
        self._keys = list(keys)
        parts = list(parts)

        def _draw_all():
            # sequential, in fan-out order: the exact consumption pattern
            # of the serial dispatch loop
            return [_draw_ids(rng, part, cursor, span) for part in parts]

        self._fut = self.pool.submit(_draw_all)

    def take(self, rng, keys, epoch: int, cursor: int):
        """The staged ids-by-worker map when the staging assumptions still
        hold (same epoch, same window cursor, same membership); None
        otherwise — the generator state is restored and the caller draws
        serially, reading the values a never-staged run would read."""
        if self._fut is None:
            return None
        draws = self._fut.result()  # join: surfaces staging exceptions
        self._fut = None
        if self._tag != (int(epoch), int(cursor)) or list(keys) != self._keys:
            rng.bit_generator.state = self._base_state
            self._base_state = None
            self.discards += 1
            return None
        self._base_state = None
        self.hits += 1
        return dict(zip(self._keys, draws))

    def discard(self, rng) -> None:
        """Membership moved under the stage (resplit): drop the pre-drawn
        ids and restore the generator."""
        if self._fut is None:
            return
        self._fut.result()
        self._fut = None
        rng.bit_generator.state = self._base_state
        self._base_state = None
        self.discards += 1

    def rng_state(self, rng):
        """The bit-generator state a SERIAL run would hold right now — the
        pre-draw base while a stage is pending, the live state otherwise.
        Crash-safe fit-state snapshots persist THIS, never the raw state."""
        return (self._base_state if self._fut is not None
                else rng.bit_generator.state)

    def close(self) -> None:
        self.pool.shutdown(wait=False)


class _BroadcastState:
    """Versioned master->worker weight broadcast for fit_sync
    (docs/SYNC_PIPELINE.md).

    Tracks the master's weight version, each worker's last-acknowledged
    replica version, and encodes — at most once per version — the wire
    forms a window can need: the full tensor, the sparse WeightDelta vs
    the previous version (absolute new values at the changed coordinates),
    or nothing at all (header-only, when the worker's replica is already
    current — retry windows re-serialize zero bytes).  With
    `delta_broadcast` off it degrades to the pre-pipeline wire — every
    request carries the full dense tensor and no version fields, byte-
    identical to the seed — while still re-encoding only when the weights
    actually changed.

    The sparse form is used only while it is cheaper than the tensor
    (8 bytes/changed coordinate vs 4 bytes/element dense: break-even at
    50% density); denser updates fall back to a full broadcast, as do a
    (re)joined worker, a worker more than one version behind, and any
    stale_version reply.
    """

    SPARSE_BREAK_EVEN = 0.5  # changed fraction above which dense is smaller

    def __init__(self, delta_broadcast: bool, metrics, versioned: bool = False,
                 encode_ahead: bool = True, stage_pool=None):
        self.delta_broadcast = delta_broadcast
        self.metrics = metrics
        # pooled dispatch (DSGD_STAGE_POOL, docs/SCALING.md): when a stage
        # pool executor is handed in, _build_staged fans the per-worker
        # request builds (weight-arm attach included) across it instead of
        # building N requests serially on the one encoder thread — and
        # staging is armed for UNARY fits too (raw GradientRequests
        # instead of stream Frames), so the serialized per-worker build
        # leaves the dispatch critical path on both transports
        self._stage_exec = stage_pool
        # encode-ahead (ROADMAP item 2): `advance()` hands the new
        # version's wire forms (full tensor bytes + the np.nonzero sparse
        # delta) to a single background encoder thread, overlapping the
        # encode with the window's host-side bookkeeping (fit-state
        # snapshot, membership check, sample draws) and — under quorum —
        # with straggler replies still in flight.  `populate` joins the
        # pending encode before reading, so the wire forms are
        # byte-identical to the synchronous path; with encode_ahead off
        # (or before the first advance) encoding stays lazy in populate.
        self.encode_ahead = bool(encode_ahead)
        self._enc_pool = None
        self._enc_future = None
        # `versioned` without delta_broadcast (the quorum barrier's mode):
        # every request still carries the full dense tensor, but stamped
        # with step_version — the workers' EF guard and the quorum
        # contribution mask (GradientRequest.ef_rollback_version) both key
        # on the version, so quorum + compression is correct on the
        # otherwise-unpipelined wire too
        self.versioned = bool(delta_broadcast or versioned)
        # versions start at 1: step_version=0 on the wire means "no version
        # tracking" (a pre-pipeline master), and the workers' EF retry
        # guard keys on the version alone whenever one is present — a
        # retried window may switch wire form (full -> header-only) while
        # keeping its version, so the version must never be ambiguous
        self.version = 1 if self.versioned else 0
        self._worker_ver: Dict[Tuple[str, int], int] = {}
        self._w_prev: Optional[np.ndarray] = None
        # the version's wire forms (full tensor / sparse delta), each
        # encoded lazily at most once — the shared versioned weight-send
        # plan (rpc/codec.py WeightSendPlan), the SAME path the serving
        # fleet's checkpoint push and the shard lanes ride
        self._send_plan: Optional[codec.WeightSendPlan] = None
        # pre-staged round dispatch (DSGD_STREAM, docs/SYNC_PIPELINE.md
        # "Streaming transport"): with staging armed (stage_for), the
        # encoder thread ALSO builds each worker's next request frame —
        # weight arm attached, version stamped — so when the window
        # barrier closes, dispatch is one sample draw + one stream write
        # per worker with zero weight re-serialization on the critical
        # path.  Entries carry the assumptions they were built under
        # (version, the worker's acknowledged version) and are discarded
        # when reality moved (stale fallback, resplit, retry window).
        self._stage_keys: list = []
        self._stage_ctx: Optional[Tuple[int, int, int, float]] = None
        self._stage_frames = True
        self._stage_lock = threading.Lock()
        self._staged: Dict[Tuple[str, int], tuple] = {}

    def stage_for(self, keys, fit_token: int, local_steps: int,
                  batch_size: int, learning_rate: float,
                  frames: bool = True) -> None:
        """Arm (or re-arm after a membership change) request staging for
        `keys`; takes effect from the next advance().  `frames=True`
        stages stream `pb.Frame`s (the DSGD_STREAM dispatch path);
        `frames=False` stages raw `pb.GradientRequest`s for the unary
        plane (DSGD_STAGE_POOL) — with neither knob on, nothing ever
        calls this and populate()'s call graph stays untouched."""
        self._stage_keys = list(keys)
        self._stage_ctx = (int(fit_token), int(local_steps),
                           int(batch_size), float(learning_rate))
        self._stage_frames = bool(frames)
        with self._stage_lock:
            self._staged = {}

    def advance(self, w_new: np.ndarray, w_old: np.ndarray) -> None:
        """Weights moved: bump the version, invalidate encoded forms, and
        (encode_ahead) start encoding the new version off-thread."""
        self.version += 1
        self._w_prev = w_old
        self._send_plan = None
        with self._stage_lock:
            self._staged = {}
        if not self.encode_ahead:
            return
        if self._enc_pool is None:
            import weakref
            from concurrent.futures import ThreadPoolExecutor

            self._enc_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="bcast-encode")
            # the broadcast state is fit-scoped: release the encoder
            # thread when the fit drops it (every exit path, exceptions
            # included) without threading a close() through fit_sync
            weakref.finalize(self, self._enc_pool.shutdown, wait=False)
        self._enc_future = self._enc_pool.submit(self._preencode, w_new)

    def _preencode(self, w: np.ndarray) -> None:
        """Encoder-thread body: build the forms `populate` will need —
        the resolved plan lands in the lazy slot, `_join_encode` gives
        the happens-before edge — then stage per-worker request frames
        when staging is armed (the slot is set by then, so _attach_arm
        never joins from the encoder thread itself)."""
        plan = self._new_plan(w)
        plan.full()
        if self.delta_broadcast:
            plan.delta()  # "use the full form" is itself a computed result
        self._send_plan = plan
        if self._stage_keys and self._stage_ctx is not None:
            self._build_staged(w)

    def _build_staged(self, w: np.ndarray) -> None:
        """Encoder-thread tail: one ready-to-send Frame (stream) or
        GradientRequest (unary, stage-pool fits) per staged worker for the
        NEXT window, fanned across the stage pool when one was handed in
        (per-worker weight-arm attach is the O(N x dim) serial wall this
        removes).  Wire accounting stays at dispatch time
        (take_staged_frame / take_staged_request), so counters equal the
        populate() path's."""
        token, k, bs, lr = self._stage_ctx
        version = self.version
        frames = self._stage_frames

        def _build(key):
            if frames:
                frame = pb.Frame()
                req = frame.request
                msg = frame
            else:
                req = pb.GradientRequest()
                msg = req
            req.fit_token = token
            if k > 1:
                req.local_steps = k
                req.batch_size = bs
                req.learning_rate = lr
            assumed = self._worker_ver.get(key)
            form, nbytes = self._attach_arm(req, key, w)
            return key, (msg, form, nbytes, assumed, version)

        keys = list(self._stage_keys)
        if self._stage_exec is not None and len(keys) > 1:
            staged = dict(self._stage_exec.map(_build, keys))
        else:
            staged = dict(_build(key) for key in keys)
        with self._stage_lock:
            self._staged = staged

    def _take_staged(self, key, frames: bool):
        """The pre-staged message for `key` if its staging assumptions
        still hold (same broadcast version, same acknowledged worker
        version, same transport); None otherwise — the caller builds and
        populates a fresh one.  Joins the encoder first, exactly like
        populate()'s lazy reads, and accounts the send here so metrics
        match the unstaged path."""
        self._join_encode()
        with self._stage_lock:
            if self._stage_frames != frames:
                return None
            item = self._staged.pop(key, None)
        if item is None:
            return None
        msg, form, nbytes, assumed, version = item
        if version != self.version or self._worker_ver.get(key) != assumed:
            return None  # stale fallback / resplit moved under the stage
        metrics_mod.record_broadcast(self.metrics, form, nbytes)
        return msg

    def take_staged_frame(self, key):
        """Stream dispatch's staged `pb.Frame`, or None (build fresh)."""
        return self._take_staged(key, frames=True)

    def take_staged_request(self, key):
        """Unary dispatch's staged `pb.GradientRequest`, or None."""
        return self._take_staged(key, frames=False)

    def _join_encode(self) -> None:
        f = self._enc_future
        if f is not None:
            f.result()  # surfaces encoder exceptions on the fit thread
            self._enc_future = None

    def note_ok(self, key) -> None:
        self._worker_ver[key] = self.version

    def note_stale(self, key) -> None:
        self._worker_ver.pop(key, None)

    def forget_missing(self, keys) -> None:
        """Membership changed: drop version claims for departed workers so
        a same-endpoint rejoin starts from a full broadcast."""
        live = set(keys)
        for k in [k for k in self._worker_ver if k not in live]:
            self._worker_ver.pop(k, None)

    def populate(self, req, key, w: np.ndarray) -> None:
        """Attach the cheapest valid weight arm for worker `key` to `req`
        and account it (utils/metrics.py master.sync.bcast.*)."""
        form, nbytes = self._attach_arm(req, key, w)
        metrics_mod.record_broadcast(self.metrics, form, nbytes)

    def _attach_arm(self, req, key, w: np.ndarray):
        """Choose + attach the weight arm for `key`; returns the
        (form, bytes) pair the caller accounts.  Shared by populate()
        (dispatch thread, joins the encoder through the lazy slot reads)
        and _build_staged (encoder thread, slots already set)."""
        if not self.delta_broadcast:
            full = self._plan_for(w).full()
            req.weights.CopyFrom(full)
            if self.versioned:
                req.step_version = self.version
            return "full", full.ByteSize()
        req.step_version = self.version
        plan = self._plan_for(w)
        arm = plan.choose_arm(self._worker_ver.get(key), self.version)
        if arm == "cached":
            return "cached", 0
        if arm == "delta":
            delta = plan.delta()
            req.delta.CopyFrom(delta)
            return "delta", delta.ByteSize()
        full = plan.full()
        req.weights.CopyFrom(full)
        return "full", full.ByteSize()

    def _new_plan(self, w: np.ndarray) -> "codec.WeightSendPlan":
        """This version's shared weight-send plan (rpc/codec.py): the
        delta-vs-full choice and both lazy encodes live in the ONE
        helper the checkpoint pusher and the shard lanes also walk.
        Without delta_broadcast the sparse form is disabled outright
        (w_prev=None), so the plan degrades to a lazy encode_tensor."""
        return codec.plan_weight_send(
            w, self._w_prev if self.delta_broadcast else None,
            base_version=self.version - 1,
            break_even=self.SPARSE_BREAK_EVEN)

    def _plan_for(self, w: np.ndarray) -> "codec.WeightSendPlan":
        # slot first, join only on a miss: a set slot IS the encoder's
        # finished result (assigned last, forms already resolved), and
        # checking first lets the encoder thread itself resolve forms
        # while staging frames without deadlocking on its own future
        if self._send_plan is None:
            self._join_encode()
        if self._send_plan is None:
            self._send_plan = self._new_plan(w)
        return self._send_plan


class MasterNode:
    def __init__(
        self,
        host: str,
        port: int,
        train: Dataset,
        test: Dataset,
        model: LinearModel,
        expected_workers: int,
        seed: int = 0,
        metrics: Optional[metrics_mod.Metrics] = None,
        rpc_policy: Optional[RpcPolicy] = None,
    ):
        require_single_output(model, 'the rpc master')
        self.host, self.port = host, port
        self.log = node_logger(host, port, master=True)
        self.metrics = metrics or metrics_mod.global_metrics()
        # unified control-plane RPC policy (deadline / backoff / breaker)
        # replacing the scattered hardcoded timeout=5.0 calls
        self.rpc_policy = rpc_policy or RpcPolicy(seed=seed,
                                                  metrics=self.metrics)
        # per-worker reply latency EWMAs: feed the quorum barriers'
        # adaptive soft deadlines (fit_sync / predict quorum params).
        # Gradient and Forward latencies differ by an order of magnitude,
        # so each fan-out keeps its own tracker
        self._latency = _LatencyEwma()
        self._fwd_latency = _LatencyEwma()
        self.model = model
        self.train = train
        self.test = test
        self.expected_workers = expected_workers
        self.seed = seed
        # O(N) master plane defaults (docs/SCALING.md): fit_sync resolves
        # its fanin_lanes / stage_pool parameters against these when the
        # caller passes None (main.py passes the DSGD_* config values
        # explicitly; tests and embedders may pin the attributes instead)
        self.fanin_lanes = 0
        self.stage_pool = 0
        # aggregation-tree plane default (DSGD_AGG_TREE, docs/AGGREGATION.md):
        # "" = flat fan-in; "fanout:F" elects sub-aggregator reduce nodes
        self.agg_tree = ""
        # feature-sharded master plane default (DSGD_MASTER_SHARDS,
        # docs/MASTER_SHARDING.md): 0 = the flat single-master wire;
        # M >= 1 range-partitions the weight vector across M shard lanes
        self.master_shards = 0
        # the in-flight fit's shard coordinator (set/cleared by fit_sync);
        # kill_shard() routes the bench chaos hook through it
        self._shard_coord = None
        # last sharded fit's per-lane wire ledger, [(index, bcast_bytes,
        # grad_bytes)] — the bench's bytes-per-process gate reads it after
        # the fit returns (the coordinator itself is fit-scoped)
        self._last_shard_bytes = None

        self._workers: Dict[Tuple[str, int], WorkerStub] = {}
        self._channels: Dict[Tuple[str, int], grpc.Channel] = {}
        self._order: List[Tuple[str, int]] = []  # registration order
        # persistent per-worker gradient streams (DSGD_STREAM,
        # docs/SYNC_PIPELINE.md "Streaming transport"): opened lazily by
        # the first streamed dispatch of a fit, closed at fit end /
        # unregister / stop.  Empty forever when no fit runs with
        # stream=True — the knobs-off call graph never touches FitStream
        # (asserted by tests/test_stream.py).
        self._streams: Dict[Tuple[str, int], object] = {}
        self._streams_lock = threading.Lock()
        # peers whose binary answered UNIMPLEMENTED to FitStream: skew is
        # per PROCESS, not per fit — the set outlives the fit-scoped
        # clients above (harvested in _close_streams) so a later fit never
        # re-probes a known-old binary.  Cleared per peer on unregister: a
        # worker restarting on the same endpoint may run a NEW binary.
        self._stream_unsupported: set = set()
        # host shapes (docs/HIERARCHY.md): local device count each worker
        # reported at registration (Node.devices; 0/absent = flat single-
        # device worker).  Feeds the host-granular weighted split below.
        self._worker_devices: Dict[Tuple[str, int], int] = {}
        self._members_lock = threading.Lock()
        self.cluster_ready = threading.Event()  # Master.scala:34-35

        # master-local eval (Master.localLoss/localAccuracy) on this device
        engine = SyncEngine(model, make_mesh(1), batch_size=1, learning_rate=0.0)
        self._eval_train = engine.bind(train)
        self._eval_test = engine.bind(test)

        # async state (AsyncMasterGrpcImpl)
        self._async_lock = threading.Lock()
        self._w_async: Optional[jax.Array] = None
        self._updates = 0
        self._max_steps = 0
        self._async_running = threading.Event()
        # inverse of _async_running for interruptible sleeps: CLEAR while a
        # fit runs (so wait(backoff) really sleeps), SET on budget/stop (so
        # the check loop wakes immediately instead of a full backoff later)
        self._async_done = threading.Event()
        self._apply = jax.jit(lambda w, d: w - d)
        # batch-drain inbox (docs/ELASTICITY.md; ROADMAP item 4): with
        # fit_async(batch_drain=True) incoming UpdateGrads buffer here and
        # a drain thread applies ONE summed update per drain — deltas
        # commute (parallel/hogwild.py _drain_inbox), so the per-message
        # jitted apply under _async_lock stops being the scaling wall.
        # Off (default) the servicer applies per message, byte-identical
        # to the pre-drain engine.
        self._inbox: List[Tuple[np.ndarray, int]] = []
        self._inbox_cv = threading.Condition()
        self._drain_on = False
        # long-horizon resource plane (telemetry/resources.py, ISSUE 20):
        # publish the drain-inbox depth as a pressure source — a slowly
        # filling inbox is the classic async-plane death.  The weakref
        # closure returns None once this master is collected, which
        # self-unregisters the source; registration is a dict insert, so
        # knobs-off runs (no probe thread) never call it.
        inbox_ref = weakref.ref(self)
        self._inbox_pressure_token = resources.register_pressure(
            metrics_mod.PROC_PRESSURE_DRAIN_INBOX,
            lambda: (len(m._inbox) if (m := inbox_ref()) is not None
                     else None))
        # endpoints that RE-registered while already members (a worker
        # process restarted on the same host:port before any eviction —
        # the new process idles with no assignment, heartbeats succeed,
        # and membership is unchanged, so neither the elastic resplit nor
        # the stall watchdog would ever re-issue its slice); the async
        # fit loop kicks these with their current assignment each tick
        self._rereg_pending: set = set()

        # cluster telemetry plane (telemetry/, DSGD_TELEMETRY,
        # docs/OBSERVABILITY.md): enable_telemetry() installs the scrape
        # aggregator (+ optional cluster /metrics endpoint); None (default)
        # means no Metrics RPC is ever issued — knobs-off call graph and
        # wire stay byte-identical
        self.telemetry = None
        self.telemetry_exporter = None

        self.server = new_server(port, host="0.0.0.0")
        self.port = self.port or self.server.bound_port
        add_master_servicer(self.server, _MasterServicer(self), node="master")

        # heartbeat failure detection (superset; SURVEY.md §5.3: the
        # reference has none and a dead worker hangs the sync barrier)
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        # fit-session counter: each fit_sync stamps its GradientRequests
        # with a fresh token so long-lived workers reset their sync-reply
        # EF residuals between fits (GradientRequest.fit_token).  The base
        # is a per-incarnation nonce: a RESTARTED master must not reuse a
        # token its long-lived workers already saw, or the worker would
        # skip the reset and leak the dead master's residual into the new
        # fit (48-bit nonce + 15-bit sequence stays inside int64)
        import random as _random

        self._fit_token_base = _random.getrandbits(48) << 15
        self._fit_seq = 0

    # -- lifecycle ---------------------------------------------------------

    def start(self, heartbeat_s: Optional[float] = None,
              heartbeat_max_misses: int = 3) -> "MasterNode":
        """`heartbeat_max_misses` (DSGD_HEARTBEAT_MAX_MISSES) is the
        consecutive-miss eviction threshold — 3 keeps the historical
        hardcoded default."""
        self.server.start()
        self.log.info("master started on %s:%d, expecting %d workers",
                      self.host, self.port, self.expected_workers)
        if heartbeat_s:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop,
                args=(heartbeat_s, max(1, int(heartbeat_max_misses))),
                daemon=True, name="heartbeat",
            )
            self._hb_thread.start()
        return self

    def enable_telemetry(self, port: Optional[int] = None,
                         scrape_min_age_s: float = 0.5):
        """Install the cluster telemetry plane (telemetry/aggregate.py):
        the master scrapes every registered worker's instrument registry
        over the Metrics RPC — piggybacked on the heartbeat cadence when
        the heartbeat runs, and refreshed on demand (throttled by
        `scrape_min_age_s`) whenever the cluster endpoint is pulled — and
        re-exports the merged series on one `/metrics` endpoint bound to
        `port` (0 = OS-assigned; None = aggregator only, no endpoint).
        Returns the ClusterTelemetry so embedders can render directly."""
        from distributed_sgd_tpu.telemetry.aggregate import (
            ClusterExporter,
            ClusterTelemetry,
        )

        self.telemetry = ClusterTelemetry(self.metrics, node="master",
                                          role="master")
        if port is not None:
            self.telemetry_exporter = ClusterExporter(
                self.telemetry.prometheus_text, port,
                refresh=lambda: self.scrape_telemetry(
                    min_age_s=scrape_min_age_s),
            ).start()
            self.log.info("cluster telemetry endpoint on :%d",
                          self.telemetry_exporter.port)
        return self.telemetry

    def scrape_telemetry(self, min_age_s: float = 0.0) -> int:
        """One (throttled) Metrics-RPC scrape over the current members;
        returns snapshots merged.  Safe from any thread; never raises."""
        if self.telemetry is None:
            return 0
        return self.telemetry.scrape(self._members(), self.rpc_policy,
                                     min_age_s=min_age_s)

    # bounded liveness-probe pool (docs/SCALING.md): at most this many
    # Ping futures in flight at once — at O(N) workers a thundering-herd
    # sweep would hold N channels' worth of pending probes while the
    # per-probe deadline bounds each one anyway.  Probes past the cap
    # defer one wheel quantum; liveness latency stays per-worker.
    HB_PROBE_POOL = 16

    def _heartbeat_loop(self, interval_s: float, max_failures: int = 3) -> None:
        """O(1)-latency liveness (docs/SCALING.md): per-worker probes on a
        shared deadline wheel instead of the old all-members sweep.

        The sweep awaited EVERY probe before the next cycle — one wedged
        peer stretched every worker's liveness cadence by the probe
        deadline, so eviction latency grew with the slowest member.  Here
        each worker owns a wheel entry: its probe fires at its own due
        time, settles on its own deadline, and re-arms itself `interval_s`
        after completion — a slow peer delays only itself.  Initial due
        times stagger across one interval so N probes never land as one
        herd.  Eviction decisions (the PR 6 semantics: `max_failures`
        consecutive misses, success resets, unregister_worker(evicted=
        True)) run on THIS thread — gRPC callbacks only enqueue
        completions — and the telemetry piggyback keeps its cadence on a
        sidecar thread so a slow scrape never delays a probe."""
        from distributed_sgd_tpu.rpc.stream import Wheel

        tracker = _FailureTracker(max_failures)
        # probe deadline: the interval, capped by the policy deadline so a
        # long interval doesn't grant a wedged peer a long blocking probe
        probe_timeout = min(interval_s, self.rpc_policy.deadline_s)
        # telemetry piggyback (docs/OBSERVABILITY.md): the scrape rides
        # the liveness cadence — concurrent futures bounded by the probe
        # deadline, breaker-consulting, failures degrade to counters — on
        # its own sidecar thread, so a degraded scrape can delay the VIEW
        # but never the eviction probes.  Armed lazily each tick because
        # enable_telemetry() typically runs AFTER start().
        scrape_armed = False

        def _scrape_loop():
            while not self._hb_stop.wait(interval_s):
                # leak-slope gauges first (docs/OBSERVABILITY.md): the
                # sidecar is the process's hours-horizon cadence, so RSS /
                # open-fd samples land in the same exposition the scrape
                # refreshes — what the flywheel bench's slope assert reads
                metrics_mod.sample_process_gauges(self.metrics)
                self.telemetry.scrape(self._members(), self.rpc_policy,
                                      deadline_s=probe_timeout)

        wheel = Wheel(name="heartbeat-wheel")
        due_ready: "collections.deque" = collections.deque()  # keys due now
        completions: "collections.deque" = collections.deque()  # (key, ok)
        wake = threading.Event()
        scheduled: set = set()   # keys with a wheel entry or probe in flight
        in_flight: set = set()
        deferred: List[Tuple[str, int]] = []  # due past the probe-pool cap

        def _fire(key):
            due_ready.append(key)
            wake.set()

        def _probe(key, stub):
            in_flight.add(key)
            try:
                fut = stub.Ping.future(pb.Empty(), timeout=probe_timeout)
            except ValueError:  # channel closed under us (unregister/stop)
                completions.append((key, False))
                wake.set()
                return

            def _done(f, key=key):
                try:
                    f.result()
                    completions.append((key, True))
                except Exception:  # noqa: BLE001 - any failure is a miss
                    completions.append((key, False))
                wake.set()

            fut.add_done_callback(_done)

        while not self._hb_stop.is_set():
            if self.telemetry is not None and not scrape_armed:
                scrape_armed = True
                threading.Thread(target=_scrape_loop, daemon=True,
                                 name="telemetry-scrape").start()
            now = time.monotonic()
            members = self._members()
            stub_by_key = dict(members)
            # new members join the wheel with phases staggered across one
            # interval; departed members' entries die on fire (no stub)
            fresh = [k for k, _ in members if k not in scheduled]
            for i, key in enumerate(fresh):
                scheduled.add(key)
                wheel.watch(now + interval_s * (i + 1) / (len(fresh) + 1),
                            lambda key=key: _fire(key))
            # completions first: decide liveness on THIS thread
            while completions:
                key, ok = completions.popleft()
                in_flight.discard(key)
                with self._members_lock:
                    still_member = key in self._workers
                if not still_member:
                    scheduled.discard(key)
                    tracker.record_ok(key)  # drop any stale miss count
                    continue
                if ok:
                    tracker.record_ok(key)
                else:
                    n, evict = tracker.record_failure(key)
                    self.log.warning("heartbeat miss %d/%d for %s:%d",
                                     n, max_failures, *key)
                    if evict:
                        self.log.warning("worker %s:%d declared dead", *key)
                        self.unregister_worker(*key, evicted=True)
                        scheduled.discard(key)
                        continue
                wheel.watch(time.monotonic() + interval_s,
                            lambda key=key: _fire(key))
            # fire due probes, bounded by the probe pool
            pending = deferred + [due_ready.popleft()
                                  for _ in range(len(due_ready))]
            deferred = []
            for key in pending:
                stub = stub_by_key.get(key)
                if stub is None or key not in scheduled:
                    scheduled.discard(key)
                    # drop any stale miss count: a re-registration on the
                    # same host:port must not inherit the departed
                    # incarnation's consecutive-miss tally
                    tracker.record_ok(key)
                    continue
                if len(in_flight) >= self.HB_PROBE_POOL:
                    deferred.append(key)  # next wake re-offers it
                    continue
                _probe(key, stub)
            wake.wait(timeout=min(interval_s, 0.5) if deferred
                      else interval_s)
            wake.clear()

    def stop(self) -> None:
        self._hb_stop.set()
        self._async_running.clear()
        self._async_done.set()
        self._close_streams()
        resources.unregister_pressure(
            metrics_mod.PROC_PRESSURE_DRAIN_INBOX, self._inbox_pressure_token)
        if self.telemetry_exporter is not None:
            self.telemetry_exporter.stop()
        self.server.stop(grace=1.0)
        for ch in self._channels.values():
            ch.close()
        self.log.info("master stopped")

    def await_ready(self, timeout: Optional[float] = None) -> bool:
        return self.cluster_ready.wait(timeout)

    # -- membership (Master.scala:222-253) ---------------------------------

    def register_worker(self, host: str, port: int, devices: int = 0) -> None:
        """Join-cap semantics: at most `expected_workers` members at any
        instant (the reference `require`s the same cap, Master.scala:224),
        but the cap is on CURRENT membership, not lifetime joins — an
        eviction (heartbeat, Gradient/Forward failure, graceful leave)
        frees a slot, so a restarted worker re-registers and a running
        fit_sync absorbs it at its next batch via the live-membership
        re-split (elastic grow-back up to the configured cluster size;
        tests/test_fault_tolerance.py::test_worker_rejoins_mid_fit)."""
        key = (host, port)
        rereg_stub = None
        with self._members_lock:
            # host shape (docs/HIERARCHY.md): recorded for members and
            # re-registrations alike (a restarted process may change its
            # device count); 0/absent = flat
            if devices > 0:
                self._worker_devices[key] = int(devices)
            else:
                self._worker_devices.pop(key, None)
            if key in self._workers:
                # already a member: either a redundant registration retry
                # (first attempt landed but its reply was lost) or a worker
                # process RESTARTED on the same endpoint — during an async
                # fit both are safe to answer with a fresh StartAsync kick
                # (the worker side replaces a running loop idempotently),
                # and the restarted-process case REQUIRES it: the idle new
                # process passes heartbeats, so nothing else would ever
                # re-issue its slice
                if self._async_running.is_set():
                    self._rereg_pending.add(key)
                rereg_stub = self._workers[key]
                rereg_others = [k for k in self._workers if k != key]
            elif len(self._workers) >= self.expected_workers:
                raise ValueError("cluster already at expected node count")
            else:
                others = list(self._workers.keys())
                ch = new_channel(host, port, origin=(self.host, self.port))
                stub = WorkerStub(ch)
                self._workers[key] = stub
                self._channels[key] = ch
                self._order.append(key)
                count = len(self._workers)
        if rereg_stub is not None:
            # re-introduce the peer set to the (possibly fresh) process: a
            # restarted worker starts with an EMPTY peer map, and without
            # this its gossip out-edges would stay gone for the rest of the
            # fit (it would send deltas only to the master).  add_peer is
            # idempotent on the worker side, so a redundant registration
            # retry from a live worker is a no-op fan-out.
            for oh, op in rereg_others:
                try:
                    self.rpc_policy.call_with_retry(
                        rereg_stub.RegisterSlave, pb.Node(host=oh, port=op),
                        peer=key, retries=1)
                except grpc.RpcError as e:
                    self.log.warning(
                        "peer re-introduction failed for %s:%d (%s)",
                        oh, op, e.code())
            return
        self.log.info("worker registered: %s:%d (%d/%d)",
                      host, port, count, self.expected_workers)
        # full-mesh introduction, both directions (Master.scala:229-233)
        new_node = pb.Node(host=host, port=port)
        for oh, op in others:
            try:
                # full policy (deadline + one jittered retry + breaker): a
                # transient blip must not silently cost the mesh an edge
                self.rpc_policy.call_with_retry(
                    self._workers[(oh, op)].RegisterSlave, new_node,
                    peer=(oh, op), retries=1)
                self.rpc_policy.call_with_retry(
                    stub.RegisterSlave, pb.Node(host=oh, port=op),
                    peer=key, retries=1)
            except grpc.RpcError as e:
                self.log.warning("peer introduction failed for %s:%d (%s)", oh, op, e.code())
        if count >= self.expected_workers:
            self.cluster_ready.set()  # Master.scala:235-241

    def unregister_worker(self, host: str, port: int,
                          evicted: bool = False) -> None:
        """`evicted=True` marks an involuntary removal (heartbeat miss,
        Gradient/Forward failure threshold, async watchdog) — those dump
        the flight recorder so a dead worker leaves post-mortem evidence
        even with tracing off; a graceful leave does not."""
        key = (host, port)
        if evicted:
            self.metrics.counter(metrics_mod.MASTER_EVICTIONS).increment()
            flight.record("worker.evicted", worker=f"{host}:{port}")
            flight.dump("eviction")
        # the departed worker's gradient stream dies with its membership
        # (its channel closes below; a half-open stream would otherwise
        # pin pending futures until their frame deadlines), and its skew
        # marker clears — a same-endpoint rejoin may be a newer binary
        with self._streams_lock:
            stream = self._streams.pop(key, None)
            self._stream_unsupported.discard(key)
        if stream is not None:
            stream.close()
        if self.telemetry is not None:
            # a departed worker's series leave the cluster exposition with
            # its membership (its final snapshot would otherwise pin stale
            # gauges forever)
            self.telemetry.drop(key)
        with self._members_lock:
            self._workers.pop(key, None)
            ch = self._channels.pop(key, None)
            self._worker_devices.pop(key, None)
            if key in self._order:
                self._order.remove(key)
            remaining = list(self._workers.values())
        if ch is not None:
            ch.close()
        node = pb.Node(host=host, port=port)
        for stub in remaining:  # broadcast (Master.scala:245-253)
            try:
                stub.UnregisterSlave(node, timeout=self.rpc_policy.deadline_s)
            except (grpc.RpcError, ValueError):
                # ValueError: the recipient's own channel closed under us —
                # at O(N) churn two departures can overlap, and the second
                # leaver's broadcast must not blow up the servicer thread
                pass
        self.log.info("worker unregistered: %s:%d", host, port)

    def _members(self) -> List[Tuple[Tuple[str, int], WorkerStub]]:
        with self._members_lock:
            return [(k, self._workers[k]) for k in self._order]

    def _split_parts(self, split: SplitFn, members) -> List[np.ndarray]:
        """Host-granular sample assignment (docs/HIERARCHY.md).

        When every member is a flat single-device worker — or the host
        shapes are all EQUAL, where proportional and even splits coincide
        — this delegates to `split` untouched, so the knobs-off call
        graph and partitions stay byte-identical to the pre-hierarchy
        engine.  Heterogeneous host shapes weight the contiguous split by
        each host's device count (core/split.py weighted_split) so every
        device across the cluster owns the same expected row count.
        Custom split strategies keep their own semantics: weighting only
        ever replaces the default `vanilla_split`."""
        with self._members_lock:
            devs = [max(1, self._worker_devices.get(k, 1))
                    for k, _ in members]
        if (split is not vanilla_split or not devs
                or len(set(devs)) == 1):
            return split(len(self.train), len(members))
        self.log.info(
            "host-granular split: weighting partitions by device count %s",
            devs)
        return weighted_split(len(self.train), devs)

    def _stubs(self) -> List[WorkerStub]:
        return [stub for _, stub in self._members()]

    # -- streaming fan-out (DSGD_STREAM; docs/SYNC_PIPELINE.md) ------------

    def _grad_stream(self, key, stub):
        """The live FitStream client for `key`, (re)opened lazily.

        The hot path is one lock-free dict read + three flag reads; the
        slow path returns None — sending goes unary — when the peer is
        marked unsupported (an older binary answered UNIMPLEMENTED: skew
        does not heal mid-process, so the marker survives the fit-scoped
        client in `_stream_unsupported` until the peer re-registers),
        when its breaker is suppressing (every stream teardown fed it one
        failure, so a flapping peer degrades to unary until the breaker's
        half-open probe heals it), or when the channel is gone
        (unregistered under us)."""
        s = self._streams.get(key)
        if s is not None and s.usable:
            return s
        from distributed_sgd_tpu.rpc.stream import FitStreamClient

        with self._streams_lock:
            if key in self._stream_unsupported:
                return None
            s = self._streams.get(key)
            if s is not None:
                if s.usable:
                    return s
                if s.unsupported:
                    self._stream_unsupported.add(key)
                    return None
                self._streams.pop(key, None)  # broken: replace below
            if self.rpc_policy.breaker(key).suppressed():
                return None
            with self._members_lock:
                if key not in self._workers:
                    return None
            breaker = self.rpc_policy.breaker(key)
            try:
                s = FitStreamClient(
                    stub.FitStream, peer=f"{key[0]}:{key[1]}",
                    metrics=self.metrics, log=self.log,
                    on_break=breaker.record_failure)
            except Exception:  # noqa: BLE001 - channel closed under us
                return None  # this window goes unary; the barrier classifies
            self._streams[key] = s
            return s

    def _close_streams(self) -> None:
        with self._streams_lock:
            streams, self._streams = dict(self._streams), {}
            # skew outlives the fit-scoped clients: a later fit must not
            # re-probe a peer whose binary already answered UNIMPLEMENTED
            for k, s in streams.items():
                if s.unsupported:
                    self._stream_unsupported.add(k)
        for s in streams.values():
            s.close()

    def _dispatch_gradient(self, key, stub, frame, req, timeout_s: float,
                           use_stream: bool):
        """One window's Gradient send for one worker: a frame write down
        the persistent stream (wrapped so a stream teardown transparently
        replays the request over unary with the remaining deadline), or
        the classic unary future.  Returns a future-alike or None (the
        channel closed under us — the barrier classifies it)."""
        if use_stream and frame is not None:
            s = self._grad_stream(key, stub)
            if s is not None:
                fut = s.send(frame, timeout_s,
                             unary_call=stub.Gradient, request=req)
                if fut is not None:
                    return fut
        try:
            return stub.Gradient.future(req, timeout=timeout_s)
        except ValueError:  # channel closed under us
            return None

    # -- distributed eval (Master.scala:61-98) -----------------------------

    def predict(
        self,
        weights: np.ndarray,
        split: SplitFn = vanilla_split,
        timeout_s: float = 60.0,
        retries: int = 1,
        return_margins: bool = False,
        quorum: Optional[int] = None,
        straggler_soft_s: Optional[float] = None,
    ):
        """Fan ForwardRequests out to every worker; gather predictions
        (and, with `return_margins`, the raw x.w margins — exact input for
        margin-based losses like logistic).

        Same fault policy as fit_sync: per-call deadlines, `retries`
        consecutive failures evict the worker, and the fan-out is retried
        across the survivors with a fresh split.  Raises RuntimeError if
        every worker is lost.

        With `quorum` set the barrier grows straggler hedging
        (docs/FAULT_TOLERANCE.md): once Q replies are in hand and the soft
        deadline (`straggler_soft_s`, or adaptive from the Forward
        latency EWMA) fires, each missing worker's sample slice is
        re-issued to the fastest responders.  Unlike fit_sync's quorum,
        evaluation NEVER drops a slice — predictions for every sample are
        required — so quorum here only bounds how long a straggler can
        hold the fan-out hostage before its slice is recomputed elsewhere;
        an uncoverable slice falls back to the classic retry/evict loop.
        """
        self._require_ready()
        wmsg = codec.encode_tensor(weights)
        tracker = _FailureTracker(retries + 1)
        while True:
            members = self._members()
            if not members:
                raise RuntimeError("all workers lost during predict")
            parts = self._split_parts(split, members)
            part_by_key = {key: ids for (key, _), ids in zip(members, parts)}
            # one trace per eval fan-out attempt (trace/): Forward calls
            # and their hedges become child spans, same as fit_sync windows
            with trace_mod.root_span(trace_mod.SPAN_EVAL_FORWARD,
                                     node="master", workers=len(members)):
                futs = []
                for (key, stub), ids in zip(members, parts):
                    try:
                        fut = stub.Forward.future(
                            pb.ForwardRequest(
                                samples=ids.astype(np.int32), weights=wmsg,
                                want_margins=return_margins,
                            ),
                            timeout=timeout_s,
                        )
                    except ValueError:
                        fut = None
                    futs.append((key, fut))
                if quorum is None:
                    ok, failed = _await_futures(futs)
                else:
                    ok, failed = self._forward_quorum(
                        futs, members, part_by_key, quorum, straggler_soft_s,
                        timeout_s, wmsg, return_margins)
            if not failed:
                out = np.zeros(len(self.train), dtype=np.float32)
                margins = np.zeros(len(self.train), dtype=np.float32)
                for key, reply in ok:
                    ids = part_by_key[key]
                    out[ids] = np.fromiter(reply.predictions, dtype=np.float32)
                    if return_margins:
                        if len(reply.margins) != len(ids):
                            # version-skew tolerance: an older worker that
                            # predates the margins field replies without it
                            margins = None
                        elif margins is not None:
                            margins[ids] = np.fromiter(reply.margins, dtype=np.float32)
                return (out, margins) if return_margins else out
            for key, _ in ok:
                tracker.record_ok(key)
            for key, code in failed:
                n, evict = tracker.record_failure(key)
                if evict:
                    self.log.warning("worker %s:%d failed Forward %d times (%s); "
                                     "declaring dead", key[0], key[1], n, code)
                    self.unregister_worker(*key, evicted=True)
                else:
                    self.log.warning("worker %s:%d failed Forward (%s); retry %d/%d",
                                     key[0], key[1], code, n, retries)

    def _forward_quorum(self, futs, members, part_by_key, quorum,
                        straggler_soft_s, timeout_s, wmsg, want_margins):
        """Quorum-gated Forward barrier with straggler hedging (see
        predict).  Returns (ok, failed) with every entry keyed by the
        SLICE's worker key — a winning hedge reply is recorded under the
        straggler's key, so the caller's slice-addressed assembly and the
        failure tracker both stay oblivious to who actually computed it."""
        quorum_n = min(quorum, len(members))
        soft_s = straggler_soft_s
        if soft_s is None:
            soft_s = self._fwd_latency.soft_deadline_s(
                part_by_key.keys(), quorum_n)
        soft_s = min(soft_s, timeout_s) if soft_s else timeout_s
        ok, failed, pending = _await_quorum(
            futs, quorum_n, time.monotonic() + soft_s,
            latency=self._fwd_latency)
        uncovered = [k for k, _ in pending] + [k for k, _ in failed]
        if uncovered and len(ok) >= quorum_n:
            stub_by_key = dict(members)
            donors = sorted(
                (k for k, _ in ok),
                key=lambda k: self._fwd_latency.p95_s(k) or float("inf"))
            hedges = []
            for i, skey in enumerate(uncovered):
                donor = donors[i % len(donors)]
                try:
                    hfut = stub_by_key[donor].Forward.future(
                        pb.ForwardRequest(
                            samples=part_by_key[skey].astype(np.int32),
                            weights=wmsg, want_margins=want_margins),
                        timeout=min(timeout_s, 2.0 * soft_s))
                except ValueError:
                    continue
                hedges.append((skey, hfut))
                self.metrics.counter(metrics_mod.QUORUM_HEDGES).increment()
                trace_mod.event(trace_mod.EVENT_QUORUM_HEDGE,
                                straggler=f"{skey[0]}:{skey[1]}",
                                donor=f"{donor[0]}:{donor[1]}")
                self.log.info("hedging Forward slice of straggler %s:%d "
                              "on %s:%d", *skey, *donor)
            h_ok, _h_failed = _await_futures(hedges)
            still = []
            for key, fut in pending:  # late originals are preferred
                if not fut.done():
                    still.append((key, fut))
                    continue
                try:
                    ok.append((key, fut.result()))
                except grpc.RpcError as e:
                    failed.append((key, e.code()))
            pending = still
            covered = {k for k, _ in ok}
            for skey, reply in h_ok:
                if skey not in covered:
                    ok.append((skey, reply))
                    covered.add(skey)
                    self.metrics.counter(
                        metrics_mod.QUORUM_HEDGE_WINS).increment()
        elif pending:
            # below quorum: wait the hard deadline out, classic barrier
            ok2, failed2, _ = _await_quorum(
                pending, len(pending) + 1,
                time.monotonic() + timeout_s + 5.0,
                latency=self._fwd_latency)
            ok.extend(ok2)
            failed.extend(failed2)
            pending = []
        covered = {k for k, _ in ok}
        # an uncoverable slice (straggler past soft + hedge deadlines, or
        # its hedge failed too) joins the classic retry/evict path
        failed = [(k, c) for k, c in failed if k not in covered]
        for key, fut in pending:
            if key not in covered:
                failed.append((key, grpc.StatusCode.DEADLINE_EXCEEDED))
        return ok, failed

    def distributed_loss(self, weights: np.ndarray) -> float:
        """Objective from the Forward fan-out (Master.scala:77-98).

        Computes per-sample losses from the workers' MARGINS (requested via
        ForwardRequest.want_margins) — exact for every model:
        prediction-based losses (the reference's hinge) are unchanged
        because losses_from_margins defaults to sample_loss(predict(m)),
        and margin-based losses (logistic) no longer need the mesh path.
        If an older worker replies without margins (version skew), falls
        back to the reference's prediction-based reconstruction — still
        exact for hinge; raises for margin-only losses.
        """
        preds, margins = self.predict(weights, return_margins=True)
        y = self.train.labels
        reg = self.model.lam * float(np.dot(weights, weights))
        if margins is not None:
            sample = np.asarray(
                self.model.losses_from_margins(jnp.asarray(margins), jnp.asarray(y))
            )
        else:
            self.log.warning(
                "a worker replied without margins (older binary?); "
                "reconstructing loss from predictions (Master.scala:77-98)"
            )
            sample = np.asarray(
                self.model.sample_loss(jnp.asarray(preds), jnp.asarray(y))
            )
        return reg + float(sample.mean())

    def distributed_accuracy(self, weights: np.ndarray) -> float:
        preds = self.predict(weights)
        return float((preds == self.train.labels).mean())

    def local_loss(self, weights, test: bool = False) -> Tuple[float, float]:
        bound = self._eval_test if test else self._eval_train
        return bound.evaluate(jnp.asarray(weights, dtype=jnp.float32))

    # -- aggregation tree (aggtree/, docs/AGGREGATION.md) --------------------

    def _build_tree_plan(self, keys, fanout: int):
        """Deterministic reduce tree over the current member list
        (aggtree/plan.py — pure, so every rebuild at the same membership
        lands on the byte-identical plan).  Called only with
        DSGD_AGG_TREE set; registers the tree gauges, which is why the
        knobs-off path must never reach here (tests/test_aggtree.py)."""
        from distributed_sgd_tpu.aggtree import build_plan

        plan = build_plan(keys, fanout, seed=self.seed)
        self.metrics.gauge(metrics_mod.TREE_DEPTH).set(plan.depth)
        self.metrics.gauge(metrics_mod.TREE_EDGES).set(plan.n_edges)
        flight.record("tree.plan", members=len(keys), fanout=int(fanout),
                      depth=plan.depth, edges=plan.n_edges,
                      aggregators=len(plan.aggregators()),
                      digest=plan.digest()[:12])
        self.log.info("aggregation tree: %r", plan)
        return plan

    def kill_shard(self, index: int) -> None:
        """Chaos hook (benches/bench_scale.py --scale chaos row): declare
        master shard `index` of the in-flight sharded fit dead.  The next
        window degrades to ONE flat single-master round, then the shard
        plan rebuilds over the survivors — live workers are never evicted
        for a master-side death (docs/MASTER_SHARDING.md failure
        matrix).  Raises when no sharded fit is in flight."""
        coord = self._shard_coord
        if coord is None:
            raise RuntimeError(
                "kill_shard: no sharded fit in flight "
                "(DSGD_MASTER_SHARDS, docs/MASTER_SHARDING.md)")
        coord.kill(int(index))

    @staticmethod
    def _annotate_tree(req, key, plan, agg_round: int,
                       grad_timeout_s: float) -> None:
        """Stamp one worker's GradientRequest with its tree role.  A
        worker that is a root child with no children gets NO stamp at
        all — its request (and reply) is byte-identical to the flat
        wire, which is also why a trivial plan annotates nothing."""
        parent = plan.parent.get(key)
        kids = plan.children.get(key, ())
        if parent is None and not kids:
            return
        if parent is not None:
            req.agg_parent = f"{parent[0]}:{parent[1]}"
        req.agg_round = int(agg_round)
        if kids:
            del req.agg_children[:]
            req.agg_children.extend(f"{c[0]}:{c[1]}" for c in kids)
            # child-wait budget scaled by subtree height: the deepest
            # nodes time out first, so partial sums cascade bottom-up
            # inside ~60% of the master's round deadline instead of
            # every level burning the full budget serially
            slice_s = 0.6 * float(grad_timeout_s) / max(1, plan.depth)
            req.agg_wait_ms = max(1, int(
                1000.0 * plan.height.get(key, 1) * slice_s))

    # -- sync fit (Master.scala:120-218) -----------------------------------

    def fit_sync(
        self,
        max_epochs: int,
        batch_size: int,
        learning_rate: float,
        criterion: Optional[Criterion] = None,
        split: SplitFn = vanilla_split,
        initial_weights: Optional[np.ndarray] = None,
        grad_timeout_s: float = 30.0,
        on_worker_death: str = "resplit",
        grad_retries: int = 1,
        checkpointer=None,
        checkpoint_every: int = 1,
        optimizer=None,
        momentum: float = 0.9,
        local_steps: int = 1,
        delta_broadcast: bool = False,
        quorum: Optional[int] = None,
        straggler_soft_s: Optional[float] = None,
        hedge: bool = True,
        fit_state_path: Optional[str] = None,
        fit_state_every: int = 0,
        health=None,
        stream: bool = False,
        fanin_lanes: Optional[int] = None,
        stage_pool: Optional[int] = None,
        agg_tree: Optional[str] = None,
        master_shards: Optional[int] = None,
    ) -> FitResult:
        """Fault-tolerant sync fit, with an optional pipelined wire path.

        The reference's barrier (`Future.sequence`, Master.scala:190) hangs
        forever if a worker dies mid-fit.  Here every Gradient call carries a
        deadline (`grad_timeout_s`), membership is re-read every batch, and a
        worker whose call fails `grad_retries + 1` consecutive times (grace
        for transient blips / first-call compile latency; a success resets
        the count) is declared dead.  What happens then is the caller's
        choice: `on_worker_death="resplit"` (default) unregisters it and
        retries the batch across the survivors with a fresh re-split;
        `on_worker_death="fail"` raises WITHOUT touching membership, so the
        caller can investigate the intact cluster.

        Checkpointing mirrors the mesh SyncTrainer (core/trainer.py):
        `checkpointer` saves weights + the newest-first test-loss history
        (+ optimizer kind/leaves) every `checkpoint_every` epochs and the
        fit resumes from the latest snapshot — same state keys, so the two
        sync engines' checkpoints are interchangeable for plain SGD.

        `optimizer` accepts the same surface as the mesh engine (None/'sgd'
        = the reference's plain update, Master.scala:197; 'momentum'/'adam'/
        an optax transformation): workers still return raw gradient sums
        (Slave.scala:153) and the transformation is applied master-side
        where the reference applies its update.

        Pipelined sync levers (docs/SYNC_PIPELINE.md), each default-off so
        the default wire stays byte-identical to the unpipelined path:

        - `delta_broadcast=True` (DSGD_DELTA_BROADCAST): versioned sparse
          weight broadcasts — workers cache the last applied weight vector
          and the master ships only the changed coordinates (or nothing on
          retry windows), falling back to a full tensor on worker (re)join,
          version mismatch (GradUpdate.stale_version), resplit, or a
          denser-than-break-even update.
        - `local_steps=K > 1` (DSGD_LOCAL_STEPS): each worker runs K
          device-side SGD steps per round over K batches drawn from its
          partition and returns the summed weight-space decrement; the
          master averages the decrements and applies the result as a
          pseudo-gradient (mean_delta / learning_rate) through the same
          optimizer surface — K x fewer barriers and broadcasts per epoch,
          local-SGD semantics (Stich, 2018) between them.
        - `stream=True` (DSGD_STREAM, "Streaming transport"): every
          window's GradientRequest rides ONE persistent bidirectional
          FitStream per worker instead of a fresh unary call, with the
          encode-ahead thread pre-staging each worker's next request
          frame — dispatch becomes one sample draw + one stream write per
          worker, amortizing per-call HTTP/2 setup/teardown, metadata,
          and future allocation over the whole fit.  The math is
          bit-identical to the unary plane (same messages, same
          send-ordered decode; the rpc bench gates drift 0.0), a broken
          stream transparently replays its window over unary and feeds
          the same per-peer breaker, UNIMPLEMENTED peers (older binaries)
          stay unary permanently, and hedges are ALWAYS unary — they
          target a different worker than the stream's owner, and every
          quorum fire re-proves the interop path.  Off (default): no
          Frame is ever constructed, call graph byte-identical.
        - `fanin_lanes=K` (DSGD_FANIN_LANES, docs/SCALING.md): shard the
          fan-in DECODE into K lanes — each reply's wire->ndarray parse
          runs in its own arrival callback without queueing on one
          decoder lock, while the float accumulation stays ONE
          send-ordered f32 chain, so the weights are byte-identical to
          the unsharded path (asserted by tests/test_fanin_lanes.py).
          Quorum rounds parse on arrival too and accumulate at round
          close, once the contributor set is known.  The lane count is
          pinned for the fit: changing the master's `fanin_lanes`
          attribute mid-fit (when this parameter was None) raises.
          None (default): resolve from `self.fanin_lanes` (0 = the
          pre-shard single-lock decoder, byte-identical).
        - `stage_pool=P` (DSGD_STAGE_POOL, docs/SCALING.md): stage round
          t+1's dispatch during round t's barrier on a P-thread pool —
          every worker's sample draw (determinism-safe: one staging task
          consumes the epoch generator in serial order and any
          retry/resplit restores its state, see _DispatchStager) and
          every worker's request build (weight arm attached by the
          encode-ahead thread, fanned across the pool), for stream AND
          unary fits — dispatch becomes a take + samples-append + send
          per worker.  Staged sends account the same master.sync.bcast.*
          counters populate() would.  None (default): resolve from
          `self.stage_pool` (0 = draws and builds on the dispatch path,
          byte-identical).

        - `master_shards=M` (DSGD_MASTER_SHARDS, docs/MASTER_SHARDING.md):
          range-partition the weight vector across M master shard lanes —
          each lane broadcasts only its contiguous feature slice (through
          the same delta/codec path), workers rendezvous the M slices,
          compute ONCE, and reply per-slice, and each lane applies its
          slice independently; range-disjoint hinge-loss SGD commutes, so
          the step is bit-identical to the flat plane while broadcast AND
          fan-in bytes per master process scale down ~1/M.  Composes with
          delta_broadcast (per-lane versions) and agg_tree (one
          shard-colored tree per lane); refuses stream / quorum /
          local_steps>1 / fanin_lanes / stage_pool.  A killed shard
          (kill_shard) costs ONE flat fallback round, then the plan
          rebuilds over the survivors.  0/None (default): no coordinator,
          no shard instrument, wire byte-identical.

        Quorum barrier (DSGD_QUORUM, docs/FAULT_TOLERANCE.md; Chen et al.
        2016's N+b backup-replica shape): with `quorum=Q` the window
        barrier returns once all replies land OR once a soft deadline
        (`straggler_soft_s`, or p95-adaptive from each worker's reply
        latency EWMA when unset) fires with >= Q worth of CONTRIBUTOR
        weight in hand — under DSGD_AGG_TREE a subtree sum counts its
        whole contributor set and a forwarded ack counts zero
        (_reply_weight), so acks from leaves whose gradients sit inside
        a straggling aggregator never satisfy the count blindly; flat
        replies weigh one, keeping the tree-off count unchanged.
        The master then hedges each missing worker's data slice to the
        fastest responders (`hedge=True`), prefers a straggler's own reply
        if it lands during the hedge window, averages over the actual
        contributors (unbiased 1/|ok| scaling), discards late replies
        idempotently via the (fit_token, step_version) window keys, and
        tells each non-contributing worker to roll back its error-feedback
        residual drain (GradientRequest.ef_rollback_version).  Below
        quorum the window degrades to today's full barrier + retry, and a
        quorum-satisfied round never counts toward eviction — a straggler
        is slow, not dead (run the heartbeat for liveness).  Default
        `quorum=None` keeps the barrier, wire, and call graph identical
        to the pre-quorum engine.

        Crash-safe fit state (`fit_state_path` + `fit_state_every=R`,
        DSGD_FIT_CKPT_EVERY, docs/ELASTICITY.md): every R successful
        windows the FULL loop state — weights, optimizer leaves, epoch +
        window cursor, sample-draw RNG state, early-stopping history,
        broadcast version, fit_token lineage — is written atomically to
        `fit_state_path`.  A restarted master (kill -9 mid-fit) that
        finds the snapshot waits for worker re-registration (the
        workers' jittered-backoff loop is storm-safe), issues a NEW
        fit_token from its fresh incarnation nonce (long-lived workers
        reset stale per-fit state; the old token joins the lineage),
        restores the cursor + RNG, and replays from the last completed
        snapshot — bit-identical to an uninterrupted run at the same
        step count (tests/test_elastic.py).  `fit_state_every=0`
        (default) disables snapshots; snapshotting is pure observation
        (enabled-but-uninterrupted runs land on bit-identical weights).

        Training-health monitor (`health`, a telemetry.HealthMonitor;
        DSGD_HEALTH_ACTION, docs/OBSERVABILITY.md): per-round gradient-
        norm/staleness gauges plus a loss-trend watchdog.  A non-finite
        fan-in gradient trips BEFORE the poisoned update is applied; an
        EWMA loss divergence trips at the epoch eval.  On trip the
        monitor dumps the flight recorder, and per its action the fit
        additionally writes a resumable fit-state snapshot to
        `fit_state_path` ('snapshot') and/or stops ('halt') — a dying
        fit leaves evidence and a checkpoint instead of a flat loss
        curve.  None (default) runs no health observation at all.
        """
        if on_worker_death not in ("resplit", "fail"):
            raise ValueError(f"on_worker_death must be resplit|fail, got {on_worker_death!r}")
        if quorum is not None and int(quorum) < 1:
            raise ValueError(f"quorum must be >= 1, got {quorum}")
        quorum = int(quorum) if quorum is not None else None
        if straggler_soft_s is not None and straggler_soft_s <= 0:
            raise ValueError(
                f"straggler_soft_s must be > 0, got {straggler_soft_s}")
        local_steps = max(1, int(local_steps))
        # O(N) master plane (docs/SCALING.md): both knobs resolve against
        # the node attributes when the parameters are None, and the lane
        # count is PINNED for the fit — per-window decoders must all shard
        # identically or a retry window's re-zeroed accumulator would walk
        # a different cursor layout than the attempt it replaces
        lanes = (self.fanin_lanes if fanin_lanes is None
                 else int(fanin_lanes))
        lanes = max(0, int(lanes))
        pool_n = (self.stage_pool if stage_pool is None else int(stage_pool))
        stager = _DispatchStager(pool_n) if pool_n and pool_n > 0 else None
        # aggregation-tree plane (DSGD_AGG_TREE, docs/AGGREGATION.md): the
        # fanout resolves against the node attribute like the knobs above;
        # 0/"" = flat fan-in — no plan is ever built, no tree instrument
        # registered, the wire byte-identical (tests/test_aggtree.py)
        tree_spec = (self.agg_tree if agg_tree is None else agg_tree) or ""
        tree_fanout = 0
        if tree_spec:
            from distributed_sgd_tpu.aggtree import parse_agg_tree

            tree_fanout = parse_agg_tree(tree_spec)
        tree_plan = None
        # feature-sharded master plane (DSGD_MASTER_SHARDS,
        # docs/MASTER_SHARDING.md): 0/None = the flat single-master wire —
        # no coordinator, no shard instrument, byte-identical
        # (tests/test_shardedps.py).  M >= 1 range-partitions every
        # round's broadcast AND fan-in across M shard lanes; the
        # restrictions below mirror Config.__post_init__ for embedders
        # that call fit_sync directly.
        from distributed_sgd_tpu.shardedps import parse_master_shards

        n_shards = parse_master_shards(
            self.master_shards if master_shards is None else master_shards)
        if n_shards:
            for bad, knob in ((stream, "DSGD_STREAM"),
                              (quorum is not None, "DSGD_QUORUM"),
                              (local_steps > 1, "DSGD_LOCAL_STEPS"),
                              (lanes > 0, "DSGD_FANIN_LANES"),
                              (stager is not None, "DSGD_STAGE_POOL")):
                if bad:
                    raise ValueError(
                        f"DSGD_MASTER_SHARDS does not compose with {knob} "
                        f"(docs/MASTER_SHARDING.md composition table)")
        self._require_ready()
        members = self._members()
        keys = [k for k, _ in members]
        if tree_fanout and not n_shards:
            tree_plan = self._build_tree_plan(keys, tree_fanout)
        shard_coord = None
        if n_shards:
            from distributed_sgd_tpu.shardedps.coordinator import (
                ShardedCoordinator,
            )

            # with DSGD_AGG_TREE the coordinator builds ONE shard-colored
            # tree per lane instead of the flat plan above
            shard_coord = ShardedCoordinator(
                self, n_shards, self.model.n_features, keys,
                delta_broadcast, tree_fanout, grad_timeout_s)
            self._shard_coord = shard_coord
        parts = self._split_parts(split, members)
        max_samples = max(len(p) for p in parts)
        w = (
            np.zeros(self.model.n_features, dtype=np.float32)
            if initial_weights is None
            else np.asarray(initial_weights, dtype=np.float32)
        )
        result = FitResult(state=GradState(weights=w))
        test_newest_first: List[float] = []
        tracker = _FailureTracker(grad_retries + 1)
        self._fit_seq += 1
        fit_token = self._fit_token_base + self._fit_seq
        # quorum forces version stamping even on the plain full-tensor
        # wire: the EF rollback mask keys on step_version
        bcast = _BroadcastState(delta_broadcast, self.metrics,
                                versioned=quorum is not None,
                                stage_pool=stager.pool if stager else None)
        use_stream = bool(stream)
        if use_stream or stager is not None:
            # pre-staged round dispatch: from the first advance() on, the
            # encoder thread (fanned across the stage pool when one is
            # armed) builds each worker's next request — stream Frames or
            # unary GradientRequests — while the current window's replies
            # are still in flight
            bcast.stage_for(keys, fit_token, local_steps, batch_size,
                            learning_rate, frames=use_stream)
        # allocation-free fan-in: one dim-sized accumulator reused by every
        # window instead of a (workers x dim) dense stack per barrier
        grad_acc = np.zeros(self.model.n_features, dtype=np.float32)
        grad_bytes = self.metrics.counter(metrics_mod.SYNC_GRAD_BYTES)
        rounds = self.metrics.counter(metrics_mod.SYNC_ROUNDS)
        window_span = batch_size * local_steps
        # quorum bookkeeping (all inert when quorum is None):
        # ef_rollback[worker] = broadcast version whose reply the quorum
        # barrier discarded — the NEXT request to that worker carries it so
        # the worker rolls back its EF residual drain for the skipped round
        ef_rollback: Dict[Tuple[str, int], int] = {}
        # per-ATTEMPT tree round (DSGD_AGG_TREE): bumped on every fan-out,
        # retries included, so a stale child push from an abandoned attempt
        # keys a round its parent will never collect — it ages out of the
        # aggregator's bounded buffer instead of double-counting
        agg_round_seq = 0
        stalled = self.metrics.counter(metrics_mod.SYNC_STALLED)
        # training-health monitor (telemetry/health.py): inert when None
        if (health is not None and health.action != "warn"
                and not fit_state_path):
            self.log.warning(
                "health action %r has no fit-state path (set "
                "DSGD_CHECKPOINT_DIR): a trip will leave flight evidence "
                "but no resumable snapshot", health.action)
        halted = False

        from distributed_sgd_tpu.checkpoint import opt_kind_tag
        from distributed_sgd_tpu.parallel.sync import resolve_optimizer

        opt = resolve_optimizer(optimizer, learning_rate, momentum)
        opt_kind = opt_kind_tag(optimizer)
        opt_state = opt.init(jnp.asarray(w)) if opt is not None else None
        if opt is not None:
            import optax

            @jax.jit
            def _opt_step(w_, opt_state_, g_):
                updates, opt_state_ = opt.update(g_, opt_state_, w_)
                return optax.apply_updates(w_, updates), opt_state_

        start_epoch = 0
        expected = jax.tree_util.tree_leaves(opt_state) if opt is not None else []
        restored = restore_sync_fit(checkpointer, opt_kind, expected)
        if restored is not None:
            start_epoch, w_np, test_newest_first, opt_leaves = restored
            w = np.asarray(w_np, dtype=np.float32)
            if opt is not None and opt_leaves:
                opt_state = jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(opt_state),
                    [jnp.asarray(x) for x in opt_leaves],
                )
            self.log.info("resumed sync fit from checkpoint at epoch %d", start_epoch)

        # crash-safe fit state (docs/ELASTICITY.md): a window-cadence
        # snapshot outranks the epoch-cadence one — it is strictly newer
        # state (mid-epoch cursor + RNG) written by the same fit
        resume_batch = 0
        resume_rng_state = None
        fit_tokens = [fit_token]
        fit_state_every = max(0, int(fit_state_every))
        fs = (restore_fit_state(fit_state_path, opt_kind, expected)
              if fit_state_path else None)
        if fs is not None and fs.epoch < start_epoch:
            # the epoch-cadence checkpoint is strictly newer — possible
            # when fit_state_every exceeds the windows in an epoch:
            # resuming from the older window snapshot would re-train
            # completed, already-checkpointed epochs
            self.log.info(
                "fit-state snapshot at epoch %d is older than the epoch "
                "checkpoint at %d: ignoring it", fs.epoch, start_epoch)
            fs = None
        if fs is not None:
            start_epoch = fs.epoch
            resume_batch = fs.batch
            resume_rng_state = fs.rng_state
            w = np.asarray(fs.weights, dtype=np.float32)
            test_newest_first = list(fs.test_losses_nf)
            if opt is not None and fs.opt_leaves:
                opt_state = jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(opt_state),
                    [jnp.asarray(x) for x in fs.opt_leaves],
                )
            if bcast.versioned and fs.bcast_version > 0:
                # continue the version stream: workers key EF retry guards
                # on step_version, and a restarted master must never reuse
                # a version its long-lived workers already acknowledged
                bcast.version = int(fs.bcast_version)
            fit_tokens = fs.fit_tokens + [fit_token]
            self.log.info(
                "resumed crash-safe fit state at epoch %d window cursor %d "
                "(fit lineage: %d token(s))",
                start_epoch, resume_batch, len(fit_tokens))

        if start_epoch >= max_epochs or (fs is not None and fs.finished):
            # nothing to run: the budget is exhausted, OR the snapshot is
            # the TERMINAL one of a fit that already finished (possibly
            # early via the convergence criterion at epoch < max_epochs —
            # resuming such a fit would train PAST convergence and mutate
            # a finished run's weights)
            loss, acc = self.local_loss(w)
            self.log.info(
                "fit state already %s at epoch %d (max_epochs %d): nothing "
                "to run (loss=%.6f acc=%.4f)",
                "finished" if (fs is not None and fs.finished) else "complete",
                start_epoch, max_epochs, loss, acc)
            result.epochs_run = start_epoch
            result.state = GradState(weights=w, loss=loss).finish()
            return result

        def _health_snapshot(epoch_, batch_, rng_state_, w_):
            """Resumable fit-state snapshot at the exact loop state a
            health trip interrupted (actions 'snapshot'/'halt'); no-op
            without a fit_state_path (warned above)."""
            if not fit_state_path:
                return
            save_fit_state(
                fit_state_path, weights=w_, epoch=epoch_, batch=batch_,
                rng_state=rng_state_, test_losses_nf=test_newest_first,
                opt_kind=opt_kind,
                opt_leaves=jax.tree_util.tree_leaves(opt_state)
                if opt_state is not None else [],
                bcast_version=bcast.version, fit_tokens=fit_tokens)
            self.log.warning(
                "health watchdog wrote a resumable fit-state snapshot to "
                "%s", fit_state_path)

        rounds_since_save = 0
        stopped_early = False
        # streams are fit-scoped: whatever path exits the epoch loop
        # (completion, convergence, health halt, all-workers-lost,
        # any exception), the persistent per-worker gradient streams
        # close with the fit
        try:
            for epoch in range(start_epoch, max_epochs):
                t0 = time.perf_counter()
                batch = 0
                # keyed by absolute epoch: a resumed run draws the same per-epoch
                # sample stream a fresh run would (mirrors SyncTrainer's
                # fold_in(base_key, epoch))
                rng = np.random.default_rng((self.seed, epoch))
                if resume_rng_state is not None:
                    # crash-safe resume lands MID-epoch: restore the generator
                    # to its snapshotted state and continue from the window
                    # cursor — the remaining windows draw the identical sample
                    # stream the uninterrupted run would have drawn
                    rng.bit_generator.state = resume_rng_state
                    batch = resume_batch
                    resume_rng_state = None
                while batch < max_samples:
                    # lane pin: the sharded decoder's cursor layout must be
                    # identical across every attempt of a window — an
                    # attribute flip mid-fit is refused, not absorbed
                    live_lanes = (self.fanin_lanes if fanin_lanes is None
                                  else fanin_lanes)
                    if max(0, int(live_lanes)) != lanes:
                        raise RuntimeError(
                            f"fan-in lane count changed mid-fit "
                            f"({lanes} -> {live_lanes}): the lane layout is "
                            f"pinned at fit start (docs/SCALING.md)")
                    # live membership: heartbeat-driven unregister_worker (or a
                    # graceful leave) reaches the loop here, not at fit start
                    current = self._members()
                    if [k for k, _ in current] != keys:
                        if not current:
                            raise RuntimeError("all workers lost mid-fit")
                        if stager is not None:
                            # pre-drawn samples were drawn for the OLD
                            # partitions: drop them and rewind the
                            # generator so the fresh serial draw below
                            # reads what a never-staged run would
                            stager.discard(rng)
                        members, keys = current, [k for k, _ in current]
                        parts = self._split_parts(split, members)
                        max_samples = max(len(p) for p in parts)
                        if tree_fanout and shard_coord is None:
                            # the reduce tree is a pure function of the
                            # member list: rebuild it on the SAME hook the
                            # resplit fires, so plan and split always
                            # describe the same membership snapshot
                            tree_plan = self._build_tree_plan(
                                keys, tree_fanout)
                            self.metrics.counter(
                                metrics_mod.TREE_REBUILDS).increment()
                            flight.record("tree.rebuild",
                                          members=len(keys),
                                          depth=tree_plan.depth)
                        if shard_coord is not None:
                            # the shard plan keys on (dim, M), not the
                            # member list — but the per-lane trees and
                            # per-lane version claims do: rebuild them on
                            # the SAME membership hook as the resplit
                            shard_coord.on_membership(keys)
                        bcast.forget_missing(keys)  # rejoins start from full
                        if use_stream or stager is not None:
                            # re-arm staging for the new membership; departed
                            # workers' streams were closed by unregister, and
                            # a (re)joined worker's stream re-opens lazily on
                            # its first dispatch below
                            bcast.stage_for(keys, fit_token, local_steps,
                                            batch_size, learning_rate,
                                            frames=use_stream)
                        # host-local workers absorb the new partition bounds
                        # themselves: ids outside a resident slice trigger the
                        # worker-side incremental reload (O(delta) rows through
                        # its RowReader) or the classified foreign-id refusal
                        self.metrics.counter(metrics_mod.SYNC_RESPLITS).increment()
                        flight.record("sync.resplit", members=len(members))
                        self.log.warning("membership changed; re-split across %d workers",
                                         len(members))
                        if batch >= max_samples:
                            break
                    t_batch = time.perf_counter()
                    # one trace per fan-out window (trace/; NOOP when tracing
                    # is off or this round is not head-sampled): worker
                    # Gradient calls — hedges and retries included — become
                    # client/server child spans of this root via the stub and
                    # servicer hooks in rpc/service.py, and quorum/chaos
                    # events attach inside it (docs/OBSERVABILITY.md)
                    wspan = trace_mod.root_span(
                        trace_mod.SPAN_SYNC_WINDOW, node="master", epoch=epoch,
                        batch=int(batch), version=bcast.version)
                    with wspan:
                        futs = []
                        agg_round_seq += 1  # fresh tree round per attempt
                        ids_by_key: Dict[Tuple[str, int], np.ndarray] = {}
                        rb_sent: Dict[Tuple[str, int], int] = {}
                        # overlapped fan-in (full barrier only): zero the
                        # accumulator BEFORE the fan-out so each reply's
                        # scatter-decode runs in its arrival callback,
                        # send-ordered — only the slowest reply's decode stays
                        # on the critical path.  The quorum barrier keeps its
                        # post-barrier decode: its contributor set (hedge wins,
                        # late originals) is only known once the round closes.
                        decoder = None
                        if shard_coord is not None:
                            # per-lane slice replies decode in
                            # ShardedCoordinator.accumulate — an arrival
                            # decoder would scatter slice-local coordinates
                            # into the full accumulator at the wrong offsets
                            pass
                        elif quorum is None:
                            grad_acc.fill(0.0)
                            decoder = _ArrivalDecoder(grad_acc, lanes=lanes)
                        elif lanes:
                            # quorum + lanes: parse-on-arrival only — the
                            # contributor set (hedge wins, late originals)
                            # is resolved at round close, where add_into
                            # replays it in canonical order
                            decoder = _ArrivalDecoder(grad_acc, lanes=lanes,
                                                      defer=True)
                        # pooled dispatch: round t's barrier already drew
                        # these ids on the stage pool; a retry/resplit that
                        # falsified the staging assumptions restored the
                        # generator, and the serial draw below reads the
                        # exact values a never-staged run would
                        staged_ids = (stager.take(rng, keys, epoch, batch)
                                      if stager is not None else None)
                        if shard_coord is not None:
                            # sharded fan-out: the serial sample draw below
                            # is the flat loop's exactly (the bit-identity
                            # contract keys on identical draws); the
                            # per-lane request build, byte accounting, and
                            # shard-colored tree stamps are the
                            # coordinator's (shardedps/coordinator.py)
                            for (key, stub), part in zip(members, parts):
                                ids_by_key[key] = _draw_ids(
                                    rng, part, batch, window_span)
                            agg_round_seq = shard_coord.dispatch(
                                members, ids_by_key, w, fit_token,
                                grad_timeout_s, agg_round_seq)
                        else:
                            for (key, stub), part in zip(members, parts):
                                ids = (staged_ids[key]
                                       if staged_ids is not None
                                       else _draw_ids(rng, part, batch,
                                                      window_span))
                                ids_by_key[key] = ids
                                frame = None
                                req = None
                                if use_stream:
                                    # pre-staged dispatch: the encoder
                                    # thread already built this worker's
                                    # frame (weight arm attached) during
                                    # the previous barrier — dispatch adds
                                    # the sample draw and writes
                                    frame = bcast.take_staged_frame(key)
                                    if frame is not None:
                                        req = frame.request
                                elif stager is not None:
                                    req = bcast.take_staged_request(key)
                                if req is not None:
                                    req.samples.extend(ids.astype(np.int32))
                                else:
                                    if use_stream:
                                        frame = pb.Frame()
                                        req = frame.request
                                        req.samples.extend(
                                            ids.astype(np.int32))
                                        req.fit_token = fit_token
                                    else:
                                        req = pb.GradientRequest(
                                            samples=ids.astype(np.int32),
                                            fit_token=fit_token)
                                    if local_steps > 1:
                                        req.local_steps = local_steps
                                        req.batch_size = batch_size
                                        req.learning_rate = learning_rate
                                    bcast.populate(req, key, w)
                                if (tree_plan is not None
                                        and not tree_plan.trivial):
                                    # stamp this worker's tree role
                                    # (parent / children / wait budget)
                                    # from the plan — staged requests and
                                    # stream frames are mutated in place,
                                    # so the annotation rides every
                                    # transport; a trivial plan (N <= F)
                                    # stamps nothing, the wire stays flat
                                    self._annotate_tree(req, key, tree_plan,
                                                        agg_round_seq,
                                                        grad_timeout_s)
                                rb = ef_rollback.pop(key, None)
                                if rb is not None:
                                    req.ef_rollback_version = rb
                                    # re-armed if this request fails
                                    rb_sent[key] = rb
                                fut = self._dispatch_gradient(
                                    key, stub, frame, req, grad_timeout_s,
                                    use_stream)
                                futs.append((key, fut))
                                if decoder is not None:
                                    decoder.watch(len(futs) - 1, fut)
                        if (stager is not None
                                and batch + window_span < max_samples):
                            # overlap window: round t+1's draws run on the
                            # stage pool while this round's replies are in
                            # flight (epoch-final rounds stage nothing —
                            # the next epoch re-keys the generator)
                            stager.stage(rng, keys, parts, epoch,
                                         batch + window_span, window_span)
                        if shard_coord is not None:
                            # M x N barrier with per-worker collapse: any
                            # stale/failed leg degrades the worker exactly
                            # once (shardedps/coordinator.py collect)
                            replies = None
                            good, stale, failed = shard_coord.collect(
                                grad_bytes)
                            satisfied = False
                            if (straggler_soft_s is not None
                                    and time.perf_counter() - t_batch
                                    > straggler_soft_s):
                                stalled.increment()
                        elif quorum is None:
                            # barrier, with deadlines; receive-side wire accounting
                            # happens per arriving reply inside _await_futures (send-
                            # side comms.* counters live in the workers' compressors),
                            # so discarded/retried windows are accounted too
                            ok, failed = _await_futures(futs, bytes_counter=grad_bytes)
                            decoder.finish(futs)
                            good, stale = [], []
                            for key, reply in ok:
                                (stale if reply.stale_version else good).append((key, reply))
                            replies = [r for _, r in good]
                            satisfied = False
                            # pure observation when a soft deadline is configured
                            # without quorum: how often would the quorum barrier
                            # have had to intervene?  (bench_chaos.py's baseline)
                            if (straggler_soft_s is not None
                                    and time.perf_counter() - t_batch > straggler_soft_s):
                                stalled.increment()
                        else:
                            replies, good, stale, failed, satisfied = (
                                self._quorum_barrier(
                                    futs, members, ids_by_key, quorum,
                                    straggler_soft_s, grad_timeout_s, fit_token,
                                    local_steps, batch_size, learning_rate, bcast,
                                    w, hedge, ef_rollback, grad_bytes, rb_sent))
                            if not satisfied:
                                # below-quorum degradation: the barrier fell back
                                # to the classic full barrier — dump the flight
                                # ring so the window leaves evidence even when
                                # the fit later recovers (docs/OBSERVABILITY.md)
                                flight.record(
                                    "quorum.below", epoch=epoch, batch=int(batch),
                                    version=bcast.version,
                                    got=sum(_reply_weight(r)
                                            for _, r in good),
                                    quorum=min(quorum, len(members)))
                                # throttled: a minutes-long partition degrades
                                # EVERY window — keep evidence fresh without
                                # blocking the barrier loop on disk each round
                                flight.dump("below_quorum", min_interval_s=10.0)
                        rounds.increment()
                        for key, _ in good:
                            tracker.record_ok(key)
                            bcast.note_ok(key)
                        for key, _ in stale:
                            # a stale reply is still a LIVE worker: reset its
                            # failure count (the pre-quorum code treated every ok
                            # reply as liveness evidence)
                            tracker.record_ok(key)
                            # replica mismatch (restart, missed window): full
                            # broadcast on the retry — the correctness fallback
                            bcast.note_stale(key)
                            self.metrics.counter(metrics_mod.SYNC_STALE).increment()
                            trace_mod.event(trace_mod.EVENT_BCAST_STALE,
                                            worker=f"{key[0]}:{key[1]}")
                            self.log.warning(
                                "worker %s:%d replica stale at v%d; falling back to "
                                "full broadcast", key[0], key[1], bcast.version)
                        if not satisfied:
                            if failed:
                                for key, code in failed:
                                    n, evict = tracker.record_failure(key)
                                    if not evict:
                                        self.log.warning(
                                            "worker %s:%d failed Gradient (%s); retry %d/%d",
                                            key[0], key[1], code, n, grad_retries)
                                        continue
                                    if on_worker_death == "fail":
                                        # abort WITHOUT mutating membership: the caller
                                        # chose to investigate, not to continue degraded
                                        raise RuntimeError(
                                            f"worker {key[0]}:{key[1]} died mid-fit "
                                            f"({n} consecutive Gradient failures: {code})")
                                    self.log.warning(
                                        "worker %s:%d failed Gradient %d times (%s); declaring dead",
                                        key[0], key[1], n, code)
                                    self.unregister_worker(*key, evicted=True)
                            if failed or stale:
                                wspan.set(retry=True)
                                continue  # retry this window (survivors or re-split)
                        # allocation-free fan-in: scatter/add every reply into the
                        # preallocated accumulator, then scale once — replaces the
                        # per-window [decode_grad(r) for r in ok] dense stack +
                        # np.mean (Vec.mean, Master.scala:194).  The full barrier
                        # already decoded per arrival (send-ordered, so the sums
                        # are bit-identical — see _ArrivalDecoder); the quorum
                        # path decodes here, once the contributor set is known:
                        # under a satisfied quorum `replies` holds the actual
                        # contributors (own + hedge replies) and the mean over
                        # |contributors| is the unbiased 1/|ok| scaling of Chen
                        # et al. 2016's backup-worker rule.
                        if shard_coord is not None:
                            # range-disjoint slice fan-in: each lane
                            # decodes its replies into its OWN view of the
                            # accumulator and applies its own divisor —
                            # per coordinate, the flat barrier's exact
                            # float chain (docs/MASTER_SHARDING.md)
                            shard_coord.accumulate(grad_acc)
                        elif decoder is not None and decoder.defer:
                            # quorum + lanes: the contributor set is known
                            # only now — accumulate it in canonical order,
                            # reusing each reply's arrival-callback parse
                            # (hedge replies parse here; the float adds
                            # are decode_grad_into's exactly)
                            grad_acc.fill(0.0)
                            for reply in replies:
                                decoder.add_into(reply, grad_acc)
                        elif decoder is None or decoder.decoded != len(replies):
                            grad_acc.fill(0.0)
                            for reply in replies:
                                codec.decode_grad_into(reply, grad_acc)
                        if shard_coord is not None:
                            pass  # per-lane divisors applied above
                        elif tree_plan is not None and not tree_plan.trivial:
                            # tree fan-in: each reply is either a subtree
                            # sum tagged with its exact contributor set, a
                            # flat-fallback payload (dead parent), or an
                            # armless agg_forwarded ack (decodes as zero,
                            # contributes nothing) — the mean divides by
                            # the TOTAL contributors, so a partial round
                            # (missed child push) still averages honestly
                            n_contrib = 0
                            for r in replies:
                                if r.agg_contributors:
                                    n_contrib += len(r.agg_contributors)
                                elif not r.agg_forwarded:
                                    # flat reply inside a tree round (e.g.
                                    # a quorum hedge, or a worker absent
                                    # from the plan): one contributor
                                    n_contrib += 1
                                if r.agg_partial:
                                    self.metrics.counter(
                                        metrics_mod.TREE_PARTIAL).increment()
                                if r.agg_flat:
                                    self.metrics.counter(
                                        metrics_mod.TREE_FLAT_FALLBACK
                                    ).increment()
                            grad_acc /= max(1, n_contrib)
                        else:
                            grad_acc /= len(replies)  # true divide, bit-matching np.mean
                        if health is not None:
                            # NaN/Inf sentinel: a non-finite fan-in NEVER
                            # reaches the weights, whatever the action — the
                            # snapshot carries the last GOOD state, cursor
                            # pointing at this window
                            if health.observe_round(
                                    float(np.linalg.norm(grad_acc)),
                                    staleness_s=time.perf_counter() - t_batch):
                                wspan.set(health_tripped=True)
                                if health.action in ("snapshot", "halt"):
                                    # the stager may have pre-drawn the next
                                    # round: persist the SERIAL state, or a
                                    # resume would skip a round's draws
                                    _health_snapshot(
                                        epoch, batch,
                                        stager.rng_state(rng)
                                        if stager is not None
                                        else rng.bit_generator.state, w)
                                if health.action == "halt":
                                    halted = True
                                    break
                                # warn/snapshot: drop the poisoned round and
                                # continue on the last finite weights (the
                                # verdict is NOT latched — every later
                                # non-finite round is dropped too)
                                self.log.error(
                                    "dropping non-finite fan-in at epoch %d "
                                    "window %d (health action %s)",
                                    epoch, int(batch), health.action)
                                batch += window_span
                                continue
                        w_old = w
                        if local_steps > 1:
                            # replies are summed weight-space decrements; apply the
                            # mean as a pseudo-gradient through the same optimizer
                            # surface (error-feedback discipline of local SGD)
                            if opt is None:
                                w = w - grad_acc
                            else:
                                w_j, opt_state = _opt_step(
                                    jnp.asarray(w), opt_state,
                                    jnp.asarray(grad_acc) / learning_rate)
                                w = np.asarray(w_j)
                        elif opt is None:
                            w = w - learning_rate * grad_acc  # Master.scala:197
                        else:
                            w_j, opt_state = _opt_step(
                                jnp.asarray(w), opt_state, jnp.asarray(grad_acc))
                            w = np.asarray(w_j)
                        if shard_coord is not None:
                            # per-lane versions advance over slices; a
                            # just-absorbed shard kill rebuilds the plan
                            # here, before the next window dispatches
                            shard_coord.advance(w, w_old)
                        else:
                            bcast.advance(w, w_old)
                        self.metrics.histogram("master.sync.batch.duration").record(
                            time.perf_counter() - t_batch)
                        batch += window_span
                        rounds_since_save += 1
                        if (fit_state_path and fit_state_every
                                and rounds_since_save >= fit_state_every):
                            # window-cadence crash snapshot: the cursor points
                            # PAST the just-applied window, and the RNG state is
                            # exactly what the next window will draw from — the
                            # stager's serial-equivalent view when a pre-draw
                            # is pending, so a resumed fit replays identically
                            save_fit_state(
                                fit_state_path, weights=w, epoch=epoch,
                                batch=batch,
                                rng_state=stager.rng_state(rng)
                                if stager is not None
                                else rng.bit_generator.state,
                                test_losses_nf=test_newest_first,
                                opt_kind=opt_kind,
                                opt_leaves=jax.tree_util.tree_leaves(opt_state)
                                if opt_state is not None else [],
                                bcast_version=bcast.version,
                                fit_tokens=fit_tokens)
                            rounds_since_save = 0
                if halted:
                    self.log.error(
                        "fit halted by the training-health watchdog (%s) at "
                        "epoch %d window %d", health.trip_reason, epoch,
                        int(batch))
                    break
                epoch_s = time.perf_counter() - t0

                loss, acc = self.local_loss(w)
                test_loss, test_acc = self.local_loss(w, test=True)
                record_epoch(result, test_newest_first, epoch,
                             loss, acc, test_loss, test_acc, epoch_s)
                self.metrics.histogram("master.sync.loss").record(loss)
                self.metrics.histogram("master.sync.acc").record(100 * acc)
                self.metrics.histogram("master.sync.epoch.seconds").record(epoch_s)
                self.log.info(
                    "epoch %d: loss=%.6f acc=%.4f test_loss=%.6f test_acc=%.4f (%.2fs)",
                    epoch, loss, acc, test_loss, test_acc, epoch_s,
                )
                if health is not None and health.observe_loss(loss):
                    # loss-trend watchdog (EWMA divergence / non-finite loss):
                    # the monitor already dumped the flight ring; snapshot at
                    # the epoch boundary (next epoch's cursor, fresh per-epoch
                    # stream — the same shape as the terminal snapshot below)
                    if health.action in ("snapshot", "halt"):
                        _health_snapshot(
                            epoch + 1, 0,
                            np.random.default_rng(
                                (self.seed, epoch + 1)).bit_generator.state, w)
                    if health.action == "halt":
                        self.log.error(
                            "fit halted by the training-health watchdog (%s) "
                            "after epoch %d", health.trip_reason, epoch)
                        halted = True
                        break
                if checkpointer is not None and (epoch + 1) % checkpoint_every == 0:
                    save_sync_fit(
                        checkpointer, epoch + 1, w, test_newest_first, opt_kind,
                        jax.tree_util.tree_leaves(opt_state)
                        if opt_state is not None else [])
                if criterion is not None and criterion(test_newest_first):
                    self.log.info("Converged to target: stopping computation")
                    stopped_early = True
                    break
        finally:
            # the shard coordinator is fit-scoped: kill_shard must never
            # reach a coordinator whose fit already returned.  Its wire
            # ledger outlives it for the bench's bytes-per-process gate.
            if self._shard_coord is not None:
                self._last_shard_bytes = self._shard_coord.bytes_by_lane()
            self._shard_coord = None
            if use_stream:
                self._close_streams()
            if stager is not None:
                # a pending pre-draw dies with the fit (the epoch generator
                # it would restore into is gone too); hit/discard tallies
                # land once per fit
                stager.close()
                self.metrics.counter(
                    metrics_mod.STAGE_HITS).increment(stager.hits)
                self.metrics.counter(
                    metrics_mod.STAGE_DISCARDS).increment(stager.discards)

        save_sync_fit_final(
            checkpointer, result.epochs_run, start_epoch, checkpoint_every,
            w, test_newest_first, opt_kind,
            jax.tree_util.tree_leaves(opt_state) if opt_state is not None else [])
        if fit_state_path and (fit_state_every or health is not None) \
                and not halted:
            # terminal snapshot (skipped on a health halt: the watchdog's
            # own snapshot carries the exact interrupted cursor, which a
            # coarser end-of-fit write would roll back).  A health-enabled
            # run writes it even with fit_state_every=0, so a COMPLETED
            # resume overwrites the stale trip snapshot instead of leaving
            # it to be re-restored by every later run.  finished marks a
            # CONVERGED fit (criterion
            # break at epochs_run < max_epochs) so a restart takes the
            # nothing-to-run path instead of training past convergence —
            # the epoch cursor alone cannot say this.  Budget exhaustion
            # is NOT marked: there the cursor carries the same fact
            # (start_epoch >= max_epochs), and leaving it unmarked lets a
            # re-run with a RAISED max_epochs resume training, matching
            # the epoch-checkpoint workflow next door
            save_fit_state(
                fit_state_path, weights=w, epoch=result.epochs_run, batch=0,
                rng_state=np.random.default_rng(
                    (self.seed, result.epochs_run)).bit_generator.state,
                test_losses_nf=test_newest_first, opt_kind=opt_kind,
                opt_leaves=jax.tree_util.tree_leaves(opt_state)
                if opt_state is not None else [],
                bcast_version=bcast.version, fit_tokens=fit_tokens,
                finished=stopped_early)

        result.state = GradState(
            weights=w, loss=result.losses[-1] if result.losses else float("nan")
        ).finish()
        return result

    def _quorum_barrier(self, futs, members, ids_by_key, quorum,
                        straggler_soft_s, grad_timeout_s, fit_token,
                        local_steps, batch_size, learning_rate, bcast, w,
                        hedge, ef_rollback, grad_bytes, rb_sent):
        """One window's quorum barrier + straggler hedging
        (docs/FAULT_TOLERANCE.md).

        Returns (replies, good, stale, failed, satisfied):

        - satisfied=True — the round closes NOW with `replies` (>= quorum
          worth of CONTRIBUTOR weight — _reply_weight: a subtree sum
          counts its whole contributor set, a forwarded ack counts zero,
          a flat or hedge reply counts one — so under DSGD_AGG_TREE the
          quorum measures gradients actually in hand, not acks).  `good`
          lists the workers whose OWN reply was
          used (liveness + broadcast-version bookkeeping); stragglers'
          discarded windows are marked in `ef_rollback` and their late
          replies are counted (idempotently dropped — nobody reads an
          abandoned future).  No failure is recorded for a missing
          straggler: slow is not dead (heartbeat owns liveness).
        - satisfied=False — quorum could not be met at the soft deadline:
          everything was awaited to the hard (per-call) deadline and the
          caller runs the classic full-barrier failure/stale/retry path
          over (good, stale, failed) unchanged.
        """
        quorum_n = min(quorum, len(members))
        soft_s = straggler_soft_s
        if soft_s is None:
            # p95-adaptive from the per-worker reply-latency EWMA; until
            # it warms (>= quorum workers with history) the window runs as
            # a full barrier, which is what seeds the EWMA
            soft_s = self._latency.soft_deadline_s(ids_by_key.keys(), quorum_n)
        soft_s = min(soft_s, grad_timeout_s) if soft_s else grad_timeout_s
        t0 = time.monotonic()
        ok, failed, pending = _await_quorum(
            futs, quorum_n, t0 + soft_s,
            bytes_counter=grad_bytes, latency=self._latency)
        # a stalled round is one the quorum could NOT relieve: the barrier
        # physically overran the soft deadline because fewer than Q usable
        # replies were in hand when it fired (a quorum-relieved round exits
        # within a poll quantum of the deadline).  bench_chaos.py's >= 3x
        # headline counts exactly these.
        if time.monotonic() - t0 > soft_s + max(0.05, 0.25 * soft_s):
            self.metrics.counter(metrics_mod.SYNC_STALLED).increment()
            trace_mod.event(trace_mod.EVENT_BARRIER_STALLED,
                            soft_s=round(soft_s, 4), got=len(ok))
            flight.record("barrier.stalled", soft_s=round(soft_s, 4),
                          got=len(ok), quorum=quorum_n)
        good, stale = [], []
        for key, reply in ok:
            (stale if reply.stale_version else good).append((key, reply))

        uncovered = ([k for k, _ in pending] + [k for k, _ in failed]
                     + [k for k, _ in stale])
        hedge_futs = []
        # quorum is counted in CONTRIBUTOR weight, not reply count: under
        # DSGD_AGG_TREE a subtree sum covers its whole contributor set
        # while a forwarded ack covers nobody (_reply_weight) — Q acks
        # from leaves whose gradients are still stuck inside a straggling
        # aggregator must not close the round
        good_weight = sum(_reply_weight(r) for _, r in good)
        if uncovered and good_weight >= quorum_n and hedge and good:
            # hedge each missing slice on the fastest responders: a
            # duplicate Gradient over the straggler's drawn ids, weights
            # populated for the donor (header-only under delta broadcast —
            # the donor just acknowledged this version)
            donors = sorted(
                (k for k, _ in good),
                key=lambda k: self._latency.p95_s(k) or float("inf"))
            stub_by_key = dict(members)
            hedge_deadline = min(grad_timeout_s, 2.0 * soft_s)
            for i, skey in enumerate(uncovered):
                donor = donors[i % len(donors)]
                hreq = pb.GradientRequest(
                    samples=ids_by_key[skey].astype(np.int32),
                    fit_token=fit_token, hedge=True)
                if local_steps > 1:
                    hreq.local_steps = local_steps
                    hreq.batch_size = batch_size
                    hreq.learning_rate = learning_rate
                bcast.note_ok(donor)  # its own reply proved this version
                bcast.populate(hreq, donor, w)
                try:
                    hfut = stub_by_key[donor].Gradient.future(
                        hreq, timeout=hedge_deadline)
                except ValueError:
                    continue
                hedge_futs.append((skey, hfut))
                self.metrics.counter(metrics_mod.QUORUM_HEDGES).increment()
                trace_mod.event(trace_mod.EVENT_QUORUM_HEDGE,
                                straggler=f"{skey[0]}:{skey[1]}",
                                donor=f"{donor[0]}:{donor[1]}")
                flight.record("quorum.hedge",
                              straggler=f"{skey[0]}:{skey[1]}",
                              donor=f"{donor[0]}:{donor[1]}")
                self.log.info(
                    "hedging slice of straggler %s:%d on %s:%d", *skey, *donor)
            h_ok, _h_failed = _await_futures(hedge_futs,
                                             bytes_counter=grad_bytes)
        else:
            h_ok = []

        # harvest originals that landed while the hedges ran — a
        # straggler's OWN reply is always preferred over its hedge (its
        # EF drain was real, and preferring it keeps the residual exact)
        still_pending = []
        for key, fut in pending:
            if not fut.done():
                still_pending.append((key, fut))
                continue
            try:
                reply = fut.result()
                grad_bytes.increment(reply.ByteSize())
                self._latency.record(key, soft_s)  # at least the soft window
                (stale if reply.stale_version else good).append((key, reply))
            except grpc.RpcError as e:
                failed.append((key, e.code()))

        own = {k for k, _ in good}
        # a slice covered by BOTH its own late original and its hedge
        # contributes exactly once — the original wins, the hedge is waste
        hedge_wins = [
            (skey, r) for skey, r in h_ok
            if skey not in own and not r.stale_version]
        # canonical slice order: float accumulation is order-sensitive, so
        # contributions are summed in fan-out order regardless of arrival
        # order — a quorum round with every reply in hand is bit-identical
        # to the plain barrier
        order = {key: i for i, key in enumerate(ids_by_key)}
        good.sort(key=lambda kr: order[kr[0]])
        replies = [r for _, r in
                   sorted(good + hedge_wins, key=lambda kr: order[kr[0]])]
        # satisfaction in contributor weight (see _reply_weight): the
        # harvested late originals above may have lifted the weight past
        # Q even if the soft-deadline snapshot was short, and vice versa
        # a pile of forwarded acks never lifts it at all
        reply_weight = sum(_reply_weight(r) for r in replies)
        if reply_weight >= quorum_n:
            if len(good) < len(ids_by_key):
                self.metrics.counter(metrics_mod.QUORUM_DEGRADED).increment()
                missing = [f"{k[0]}:{k[1]}" for k in ids_by_key
                           if k not in own]
                trace_mod.event(trace_mod.EVENT_QUORUM_DEGRADED,
                                contributors=reply_weight, missing=missing)
                flight.record("quorum.degraded", contributors=reply_weight,
                              missing=missing)
            for skey, _ in hedge_wins:
                self.metrics.counter(metrics_mod.QUORUM_HEDGE_WINS).increment()
                trace_mod.event(trace_mod.EVENT_QUORUM_HEDGE_WIN,
                                straggler=f"{skey[0]}:{skey[1]}")
            # contribution mask: every fanned-out worker whose own reply
            # was NOT used rolls its EF drain back on the next request
            # (exact-match on the broadcast version, so a worker that
            # never received this window simply ignores it).  A request
            # that failed outright may never have been processed — a
            # rollback marker it carried is still owed, so re-arm the OLD
            # marker for those (exact-match keeps either choice safe; this
            # picks the one a never-delivered request leaves true).
            late_counter = self.metrics.counter(metrics_mod.QUORUM_LATE)
            failed_keys = {k for k, _ in failed}
            for key in ids_by_key:
                if key not in own:
                    if key in failed_keys and key in rb_sent:
                        ef_rollback[key] = rb_sent[key]
                    else:
                        ef_rollback[key] = bcast.version
            # the late settle runs on a gRPC callback thread after this
            # window's span closed: capture the window context NOW so the
            # discard still lands in the round's timeline
            w_ctx = trace_mod.current()
            for key, fut in still_pending:
                def _count_late(f, _c=late_counter, _k=key):
                    if not f.cancelled():
                        _c.increment()
                        trace_mod.event_in(
                            w_ctx, trace_mod.EVENT_QUORUM_LATE,
                            node="master", worker=f"{_k[0]}:{_k[1]}")
                        flight.record("quorum.late", worker=f"{_k[0]}:{_k[1]}")
                fut.add_done_callback(_count_late)
            # stragglers are NOT failures: no tracker/eviction pressure
            # from a quorum-satisfied round
            return replies, good, stale, [], True

        # below quorum: classic full barrier — await the hard deadline,
        # then hand the classic failure/stale/retry path the full picture
        # (the stall, if any, was already counted by the overrun check)
        if still_pending:
            ok2, failed2, _ = _await_quorum(
                still_pending, len(still_pending) + 1,
                time.monotonic() + grad_timeout_s + 5.0,
                bytes_counter=grad_bytes, latency=self._latency)
            for key, reply in ok2:
                (stale if reply.stale_version else good).append((key, reply))
            failed.extend(failed2)
        # hedge replies are dropped below quorum: the classic retry path
        # averages over the member fan-out only (and hedges were only sent
        # if quorum had been met when the soft deadline fired).  Fan-out
        # order again, for bit-identity with the plain barrier.  Rollback
        # markers whose carrying request yielded no usable reply are
        # re-armed for the retry (a worker that DID process the request
        # consumed its marker, making the repeat an exact-match no-op).
        order = {key: i for i, key in enumerate(ids_by_key)}
        good.sort(key=lambda kr: order[kr[0]])
        own = {k for k, _ in good}
        for key, rb in rb_sent.items():
            if key not in own:
                ef_rollback.setdefault(key, rb)
        return [r for _, r in good], good, stale, failed, False

    def fit_async(
        self,
        max_epochs: int,
        batch_size: int,
        learning_rate: float,
        criterion: Optional[Criterion] = None,
        check_every: int = 100,
        leaky_loss: float = 0.9,
        backoff_s: float = 2.5,
        split: SplitFn = vanilla_split,
        initial_weights: Optional[np.ndarray] = None,
        checkpointer=None,
        optimizer: Optional[str] = None,
        momentum: float = 0.9,
        stall_checks: int = 4,
        max_stall_interventions: int = 3,
        stall_window_s: Optional[float] = None,
        startup_grace_s: Optional[float] = None,
        elastic: bool = False,
        batch_drain: bool = False,
    ) -> FitResult:
        """Async fit with a stall watchdog (superset; the reference counts
        updates blindly, MasterAsync.scala:164-177, and a dead worker means
        the budget never completes — the master spins forever re-evaluating
        frozen weights).  When no update arrives for the stall window, the
        watchdog probes every assigned worker: the dead are evicted
        (joining any heartbeat eviction that already happened) and their
        sample assignments re-issued to survivors via StartAsync with the
        current weights, so the lifetime budget completes on the
        survivors; with no survivors — or after `max_stall_interventions`
        interventions without any progress — the fit aborts cleanly with
        RuntimeError instead of spinning (the bar fit_sync already set,
        on_worker_death).

        Window sizing: `stall_window_s` defaults to
        max(stall_checks x backoff_s, 60) — a short backoff must not arm a
        sub-compile-time watchdog, because a worker's FIRST dispatch
        legitimately produces nothing while XLA compiles its k-step
        program (and a misfired kick replaces the loop and recompiles,
        making the stall worse).  Before the first update ever arrives the
        window is `startup_grace_s` (default max(stall_window, 180)) for
        the same reason.  Tests pass explicit small values.

        Elastic membership (`elastic=True`, DSGD_ELASTIC,
        docs/ELASTICITY.md): on ANY membership change — a worker evicted,
        a worker gracefully leaving, or a NEW worker registering mid-fit —
        the loop re-splits the sample assignment deterministically across
        the CURRENT members (the same core/split.py strategy the sync
        resplit path uses) and re-issues StartAsync (with the current
        weights) only to workers whose slice changed; the gossip plane
        absorbs the change through the existing full-mesh introduction /
        unregister broadcast, so a join or leave never stops the world.
        Off (default) the loop keeps the pre-elastic behavior: evicted
        workers' slices MERGE into survivors and mid-fit joins idle until
        the next fit.

        Batch drain (`batch_drain=True`, DSGD_ASYNC_DRAIN): buffer
        incoming UpdateGrads in an inbox and apply one summed update per
        drain (deltas commute; mirrors parallel/hogwild.py _drain_inbox),
        replacing the per-message jitted apply that serializes on
        _async_lock at high worker counts.  Off (default) keeps the
        per-message apply byte-identical."""
        if optimizer is not None and not isinstance(optimizer, str):
            raise ValueError(
                "the RPC topology ships the optimizer by NAME in "
                "StartAsyncRequest; pass 'sgd'/'momentum'/'adam' (an optax "
                "transform object cannot cross the wire)"
            )
        from distributed_sgd_tpu.parallel.sync import resolve_optimizer

        # dry-run the resolution so an unknown name fails HERE, before any
        # worker is started (a mid-fan-out failure would leave early
        # workers gossiping and _async_running permanently set)
        resolve_optimizer(optimizer, learning_rate, momentum)
        self._require_ready()
        if self._async_running.is_set():
            raise RuntimeError("a computation is already running")  # MasterAsync.scala:42
        members = self._members()
        parts = self._split_parts(split, members)
        # per-worker sample assignment, kept for watchdog reassignment
        assignments = {key: part for (key, _), part in zip(members, parts)}
        w0 = (
            np.zeros(self.model.n_features, dtype=np.float32)
            if initial_weights is None
            else np.asarray(initial_weights, dtype=np.float32)
        )
        # the checker restores any prior snapshot, including the lifetime
        # update count: maxSteps is a LIFETIME budget (MasterAsync.scala:83
        # counts updates across the whole computation), so a resumed fit
        # starts its counter at the restored count and spends only the
        # remainder
        checker = LossChecker(leaky_loss, criterion, checkpointer=checkpointer)
        t_start = time.time()
        with self._async_lock:
            self._w_async = jnp.asarray(w0)
            self._updates = checker.restored_updates
            self._max_steps = len(self.train) * max_epochs  # MasterAsync.scala:83
        if self._updates >= self._max_steps:
            self.log.info(
                "resumed past the %d-step budget (%d updates done): nothing to run",
                self._max_steps, self._updates)
            return async_fit_result(
                checker, w0, t_start, self._updates, batch_size, len(self.train))
        self._async_done.clear()
        self._async_running.set()

        last_step = self._updates - check_every  # first check runs immediately
        if stall_window_s is None:
            stall_window_s = max(max(1, stall_checks) * backoff_s, 60.0)
        if startup_grace_s is None:
            startup_grace_s = max(stall_window_s, 180.0)
        start_updates = self._updates
        last_progress = self._updates
        last_progress_t = time.monotonic()
        interventions = 0
        # every endpoint that EVER held an assignment gets the end-of-fit
        # StopAsync broadcast, even if evicted mid-fit: a falsely-evicted
        # but alive worker must not keep training (and gossiping into the
        # master) after the fit returns
        ever_assigned = set(assignments)
        with self._members_lock:
            self._rereg_pending.clear()  # stale kicks from a prior fit
        drain_thread = None
        if batch_drain:
            with self._inbox_cv:
                self._inbox.clear()  # never apply a prior fit's stragglers
                self._drain_on = True
            drain_thread = threading.Thread(
                target=self._drain_loop, daemon=True, name="async-drain")
            drain_thread.start()
        try:
            # fan-out INSIDE the try: a worker dying mid-fan-out must still
            # reach the finally (_end_async_endpoints), or _async_running
            # stays set forever and the started workers gossip with no stop
            for key, part in assignments.items():  # MasterAsync.scala:52-55
                self._start_async_worker(key, part, w0, batch_size,
                                         learning_rate, optimizer, momentum)
            self.log.info("waiting for slaves updates")
            while self._async_running.is_set():
                with self._async_lock:
                    updates = self._updates
                    w_now = self._w_async
                window = (startup_grace_s if updates == start_updates
                          else stall_window_s)
                # membership reaches the async fit HERE each tick: an
                # assigned worker that lost membership gets its samples
                # re-issued immediately (no full-stall wait), and under
                # `elastic` a JOIN triggers the same deterministic resplit
                with self._members_lock:
                    member_order = list(self._order)
                if elastic:
                    if set(member_order) != set(assignments):
                        self._elastic_resplit(
                            assignments, member_order, np.asarray(w_now),
                            batch_size, learning_rate, optimizer, momentum,
                            split, ever_assigned)
                else:
                    member_keys = set(member_order)
                    evicted = [k for k in assignments if k not in member_keys]
                    if evicted:
                        self.log.warning(
                            "async fit: %d assigned worker(s) no longer members; "
                            "reassigning", len(evicted))
                        self._reassign_async(assignments, evicted,
                                             np.asarray(w_now),
                                             batch_size, learning_rate,
                                             optimizer, momentum)
                # same-endpoint restarts: a worker that RE-registered while
                # still a member left no membership delta for the blocks
                # above to see — re-kick its current slice (idempotent on a
                # live worker; see register_worker)
                with self._members_lock:
                    rejoined = [k for k in self._rereg_pending
                                if k in assignments]
                    self._rereg_pending.clear()
                for key in rejoined:
                    self.log.warning(
                        "async fit: %s:%d re-registered while assigned; "
                        "re-issuing its StartAsync", key[0], key[1])
                    self._try_start_async_worker(
                        key, assignments[key], np.asarray(w_now),
                        batch_size, learning_rate, optimizer, momentum)
                if updates > last_progress:
                    last_progress, last_progress_t = updates, time.monotonic()
                    interventions = 0
                elif time.monotonic() - last_progress_t > window:
                    interventions += 1
                    if interventions > max_stall_interventions:
                        raise RuntimeError(
                            f"async fit stalled: no update progress after "
                            f"{interventions - 1} watchdog interventions "
                            f"(budget {updates}/{self._max_steps})")
                    self._async_watchdog(
                        assignments, np.asarray(w_now), batch_size,
                        learning_rate, optimizer, momentum)
                    last_progress_t = time.monotonic()
                if updates - last_step < check_every:
                    self._async_done.wait(backoff_s)
                    continue
                raw_loss, raw_acc = self.local_loss(w_now, test=True)
                stop = checker.check(raw_loss, raw_acc, w_now, step=updates)
                # counter keeps the reference's toLong truncation quirk
                # (MasterAsync.scala:126); the histogram carries the real value
                self.metrics.counter("master.async.loss").increment(int(checker.smoothed[0]))
                self.metrics.histogram("master.async.loss.value").record(checker.smoothed[0])
                self.log.info(
                    "loss computed at %d updates: test_loss=%.6f test_acc=%.4f",
                    updates, checker.smoothed[0], checker.smoothed_accs[0],
                )
                last_step = updates
                if stop:
                    self.log.info("converged to target: stopping computation")
                    break
        finally:
            self._end_async_endpoints(ever_assigned)
            if drain_thread is not None:
                # stop the drain AFTER StopAsync: in-flight gossip drains
                # into the weights instead of stranding in the inbox
                with self._inbox_cv:
                    self._drain_on = False
                    self._inbox_cv.notify()
                drain_thread.join(timeout=10.0)
        # BEST weights, not last (MasterAsync.scala:87-94)
        return async_fit_result(
            checker, w0, t_start, self._updates, batch_size, len(self.train))

    def _end_async_endpoints(self, endpoints) -> None:
        """StopAsync broadcast to every endpoint that ever held an
        assignment — members through their live stubs, evicted endpoints
        through a short-lived channel (best effort; a truly dead process
        just refuses the connection)."""
        self._async_running.clear()
        self._async_done.set()
        deadline = self.rpc_policy.deadline_s
        for key in endpoints:
            with self._members_lock:
                stub = self._workers.get(key)
            try:
                if stub is not None:
                    stub.StopAsync(pb.Empty(), timeout=deadline)
                else:
                    ch = new_channel(*key, origin=(self.host, self.port))
                    try:
                        WorkerStub(ch).StopAsync(pb.Empty(), timeout=deadline)
                    finally:
                        ch.close()
            except (grpc.RpcError, ValueError):
                pass

    def _start_async_worker(self, key, part, w, batch_size, learning_rate,
                            optimizer, momentum) -> None:
        with self._members_lock:
            stub = self._workers.get(key)
        if stub is None:
            raise RuntimeError(f"worker {key[0]}:{key[1]} vanished before StartAsync")
        # generous deadline: a RE-issued StartAsync first joins the
        # worker's running loop thread (worker.py start_async), which can
        # legitimately block for a full in-flight dispatch — a deadline
        # shorter than that would falsely evict a live survivor while the
        # handler goes on to start the new loop anyway (orphan training)
        stub.StartAsync(
            pb.StartAsyncRequest(
                weights=codec.encode_tensor(np.asarray(w)),
                samples=np.asarray(part).astype(np.int32),
                batch_size=batch_size,
                learning_rate=learning_rate,
                optimizer=optimizer or "",
                momentum=momentum,
            ),
            timeout=60.0,
        )

    def _async_watchdog(self, assignments, w_now, batch_size, learning_rate,
                        optimizer, momentum) -> None:
        """No update progress for the stall window: probe every assigned
        worker, evict the unresponsive, and re-issue their assignments.

        Dead workers fall in two classes: already evicted by the heartbeat
        loop (no longer members) and newly unresponsive to a Ping (evicted
        here).  Each dead worker's samples are merged into a survivor's
        assignment and re-issued via StartAsync with the CURRENT weights —
        the worker side replaces its running loop on a repeated StartAsync
        (worker.py start_async), so kicking a live-but-idle worker is safe
        too.  Raises RuntimeError when nobody is left to carry the budget.
        """
        with self._members_lock:
            member_keys = set(self._workers)
        dead = [k for k in assignments if k not in member_keys]
        for key in assignments:
            if key in dead:
                continue
            with self._members_lock:
                stub = self._workers.get(key)
            try:
                if stub is None:
                    raise ValueError("channel closed")
                stub.Ping(pb.Empty(), timeout=self.rpc_policy.deadline_s)
            except (grpc.RpcError, ValueError) as e:
                code = e.code() if isinstance(e, grpc.RpcError) else e
                self.log.warning(
                    "async watchdog: worker %s:%d unresponsive (%s); "
                    "declaring dead", key[0], key[1], code)
                self.unregister_worker(*key, evicted=True)
                dead.append(key)
        if not dead:
            survivors = list(assignments)
            if not survivors:
                raise RuntimeError("async fit: all workers lost mid-fit")
            # every worker answers pings yet nobody gossips: their async
            # loops are gone (e.g. a restarted process re-registered) —
            # re-issue every assignment with the current weights
            self.log.warning(
                "async watchdog: stalled with %d live workers; re-issuing "
                "all StartAsync assignments", len(survivors))
            for key in survivors:
                self._try_start_async_worker(key, assignments[key], w_now,
                                             batch_size, learning_rate,
                                             optimizer, momentum)
            return
        self._reassign_async(assignments, dead, w_now, batch_size,
                             learning_rate, optimizer, momentum)

    def _elastic_resplit(self, assignments, member_order, w_now, batch_size,
                         learning_rate, optimizer, momentum, split,
                         ever_assigned) -> None:
        """Elastic membership change (docs/ELASTICITY.md): re-split the
        corpus deterministically across the CURRENT members — the same
        core/split.py strategy the sync resplit path uses, over the same
        registration order, so any master looking at the same membership
        derives the same slices — and re-issue StartAsync (current
        weights) ONLY to workers whose slice changed.  Workers that kept
        their slice keep training untouched: a join or leave never stops
        the world.  Departed workers simply drop out of the assignment
        map; their peers swept the gossip state when the unregister
        broadcast landed (worker.remove_peer drops the EF residual, the
        RPC-sender window is closed)."""
        if not member_order:
            raise RuntimeError("async fit: all workers lost mid-fit")
        parts = self._split_parts(
            split, [(k, None) for k in member_order])
        new_assign = {key: part for key, part in zip(member_order, parts)}
        changed = [key for key in member_order
                   if key not in assignments
                   or not np.array_equal(assignments[key], new_assign[key])]
        joined = [key for key in member_order if key not in assignments]
        departed = [key for key in assignments if key not in new_assign]
        assignments.clear()
        assignments.update(new_assign)
        ever_assigned.update(member_order)
        self.metrics.counter(metrics_mod.ASYNC_RESPLITS).increment()
        flight.record("async.resplit", members=len(member_order),
                      joined=len(joined), departed=len(departed),
                      reissued=len(changed))
        self.log.warning(
            "elastic resplit across %d member(s): %d joined, %d departed, "
            "%d assignment(s) re-issued", len(member_order), len(joined),
            len(departed), len(changed))
        for key in changed:
            self._try_start_async_worker(key, assignments[key], w_now,
                                         batch_size, learning_rate,
                                         optimizer, momentum)

    def _reassign_async(self, assignments, dead, w_now, batch_size,
                        learning_rate, optimizer, momentum) -> None:
        """Merge each dead worker's samples into a survivor's assignment and
        re-issue StartAsync there with the current weights (the worker side
        replaces its running loop on a repeated StartAsync).  Raises
        RuntimeError when no survivor is left to carry the budget."""
        survivors = [k for k in assignments if k not in dead]
        if not survivors:
            raise RuntimeError("async fit: all workers lost mid-fit")
        targets = []
        for i, key in enumerate(dead):
            target = survivors[i % len(survivors)]
            part = assignments.pop(key)
            assignments[target] = np.concatenate([assignments[target], part])
            if target not in targets:
                targets.append(target)
            self.log.warning(
                "async fit: re-issuing %d samples of dead worker "
                "%s:%d to %s:%d", len(part), key[0], key[1], *target)
        for target in targets:
            self._try_start_async_worker(target, assignments[target], w_now,
                                         batch_size, learning_rate, optimizer,
                                         momentum)

    def _try_start_async_worker(self, key, part, w, batch_size, learning_rate,
                                optimizer, momentum) -> None:
        """Re-issue wrapper: a target that dies in the window between the
        probe and the StartAsync is evicted instead of aborting the fit —
        the loop's membership check reassigns its samples next tick."""
        try:
            self._start_async_worker(key, part, w, batch_size, learning_rate,
                                     optimizer, momentum)
        except (grpc.RpcError, RuntimeError) as e:
            code = e.code() if isinstance(e, grpc.RpcError) else e
            self.log.warning(
                "async fit: StartAsync re-issue to %s:%d failed (%s); "
                "evicting — samples reassign next tick", key[0], key[1], code)
            self.unregister_worker(*key, evicted=True)

    # -- batch-drain inbox (docs/ELASTICITY.md) ----------------------------

    # inbox bound, mirroring hogwild's max_inbox=1024: each entry holds a
    # DENSE dim-sized float32 delta, so an unbounded list would grow the
    # master's RSS without limit whenever sustained arrival outruns the
    # single drain thread (exactly the high-worker-count regime the drain
    # targets)
    ASYNC_INBOX_CAP = 1024

    def _inbox_put(self, delta: np.ndarray, n_steps: int) -> bool:
        """Buffer a delta iff the drain thread is accepting AND the inbox
        has room.  The check happens under the inbox lock — an
        unsynchronized `_drain_on` read followed by a put could land AFTER
        the drain thread observed shutdown and exited, stranding the delta
        in the inbox where the NEXT batch-drain fit would apply it to
        fresh weights.  Returns False when declined (caller applies
        per-message: on overflow that keeps every delta counted AND
        throttles arrival through the jitted apply under `_async_lock` —
        bounded work, so the gRPC server pool never starves the way a
        blocking put would)."""
        with self._inbox_cv:
            if not self._drain_on or len(self._inbox) >= self.ASYNC_INBOX_CAP:
                if self._drain_on:
                    self.metrics.counter(
                        metrics_mod.ASYNC_DRAIN_FALLBACK).increment()
                return False
            self._inbox.append((delta, n_steps))
            # health gauge (telemetry/health.py): inbox depth is the
            # arrival-vs-drain pressure signal the alert rules watch; a
            # GIL-atomic float set under the lock we already hold
            self.metrics.gauge(
                metrics_mod.HEALTH_DRAIN_BACKLOG).set(len(self._inbox))
            self._inbox_cv.notify()
            return True

    def _drain_loop(self) -> None:
        """Batch-drain thread: sum every buffered delta on the host and
        apply ONE jitted update per drain (deltas commute — the receiving
        merge sees exactly the per-message subtractions, summed; mirrors
        parallel/hogwild.py _drain_inbox).  Exits once the fit clears
        `_drain_on` AND the inbox is empty, so no delta is stranded."""
        drains = self.metrics.counter(metrics_mod.ASYNC_DRAINS)
        sizes = self.metrics.histogram(metrics_mod.ASYNC_DRAIN_SIZE)
        while True:
            with self._inbox_cv:
                while not self._inbox and self._drain_on:
                    self._inbox_cv.wait(timeout=0.25)
                batch, self._inbox = self._inbox, []
                self.metrics.gauge(metrics_mod.HEALTH_DRAIN_BACKLOG).set(0)
                if not batch and not self._drain_on:
                    return
            if not batch:
                continue
            acc = np.array(batch[0][0], dtype=np.float32, copy=True)
            total = int(batch[0][1])
            for delta, n in batch[1:]:
                acc += delta
                total += int(n)
            self._update_grad(acc, n_steps=total)
            drains.increment()
            sizes.record(len(batch))

    # master UpdateGrad RPC (MasterAsync.scala:164-177); one gossip message
    # may carry n_steps summed local steps (dispatch amortization) and
    # maxSteps counts local steps
    def _update_grad(self, delta: np.ndarray, n_steps: int = 1) -> None:
        with self._async_lock:
            if self._w_async is None:
                return
            self._w_async = self._apply(self._w_async, jnp.asarray(delta))
            stride = max(1, int(n_steps))
            self._updates += stride
            updates = self._updates
        if updates % 1000 < stride:  # crossing check: strides of k
            self.log.info("%d updates received", updates)
        if updates >= self._max_steps and self._async_running.is_set():
            self.log.info("max number of steps reached: stopping computation")
            self._async_running.clear()
            self._async_done.set()  # wake the check loop immediately

    def _require_ready(self) -> None:
        if not self.cluster_ready.is_set():  # withClusterReady barrier
            self.log.info("waiting for %d workers to join", self.expected_workers)
            self.cluster_ready.wait()


class _MasterServicer:
    """gRPC method bodies (AbstractMasterGrpc, Master.scala:220-253)."""

    def __init__(self, m: MasterNode):
        self.m = m

    def RegisterSlave(self, request, context):  # noqa: N802
        try:
            # Node.devices (docs/HIERARCHY.md): 0/absent from flat workers
            # and pre-hierarchy binaries — the split stays unweighted
            self.m.register_worker(request.host, request.port,
                                   devices=request.devices)
        except ValueError as e:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        return pb.Ack()

    def UnregisterSlave(self, request, context):  # noqa: N802
        self.m.unregister_worker(request.host, request.port)
        return pb.Ack()

    def UpdateGrad(self, request, context):  # noqa: N802
        # receive-side wire accounting for the gossip stream (send-side
        # comms.* counters live in the workers' compressors)
        self.m.metrics.counter("master.async.grad.bytes").increment(
            request.ByteSize())
        delta = codec.decode_grad(request)
        n_steps = request.n_steps or 1
        # batch-drain mode: decode on the servicer thread (parallel),
        # buffer for the drain thread's single summed apply; _inbox_put
        # declines atomically when draining is off (or just shut down)
        if not self.m._inbox_put(delta, n_steps):
            self.m._update_grad(delta, n_steps=n_steps)
        return pb.Ack()

    def Ping(self, request, context):  # noqa: N802
        # membership probe for the workers' re-registration watch
        # (docs/ELASTICITY.md): a caller this master does not know gets
        # NOT_FOUND — the one signal that survives a FAST restart (the
        # rebound port answers probes before the watch can accumulate
        # unreachability misses) and an eviction the worker missed
        if request.host:
            key = (request.host, request.port)
            with self.m._members_lock:
                known = key in self.m._workers
            if not known:
                context.abort(grpc.StatusCode.NOT_FOUND,
                              f"{key[0]}:{key[1]} is not a member")
        return pb.Ack()
