"""Synchronous data-parallel SGD as compiled XLA collectives.

This is the TPU-native form of the reference's sync mode
(core/Master.scala:120-218 + core/Slave.scala:142-157).  The mapping:

| reference (gRPC star topology)                  | here (mesh collectives) |
|-------------------------------------------------|-------------------------|
| worker process i with sample shard i            | mesh device i, sharded resident dataset |
| master sends GradientRequest(w, batch idx)      | (weights replicated; no transfer) |
| worker: per-sample backward, SUM, regularize    | grad_sum + regularize per device |
| master: Vec.mean over worker replies            | lax.psum / n_workers     |
| w <- w - lr * grad                              | same, on every device    |
| per-batch barrier (Future.sequence)             | implicit in SPMD         |
| epoch = foldLeft over batch windows             | lax.scan over steps      |

The whole epoch is ONE compiled program: no host round-trips, no
serialization of the 47k-dim weight vector per batch per worker (the
reference ships it over gRPC every batch, Master.scala:184-189).

Kernel backends (`kernel=`): each binding's plan (ops/kernels.py `plan`)
asks the one rule on shape and platform unless a family is named.  'mxu' keeps
weights in the lane-blocked [R, 128] view across the epoch scan and runs
the sparse gather/scatter as one-hot MXU matmuls (ops/mxu.py — ~32 us vs
~310 us per 3-worker step at RCV1 shapes on v5e, benches/step_bench.py);
'gather' keeps the same view and runs them as a row gather and a
scatter-add whose cost does not grow with D (ops/gather.py: the rule's
choice from 200,000 features on; where ops/kernels.sparse_update says so
its step builds no gradient at all and scatters the workers' entries into
the carried weights, `_sparse_step` below); 'scalar' is the reference-shaped
take/scatter path (ops/sparse.py); 'dense' runs dense-layout datasets
(Dataset.dense — no index array) as plain [B, D] matmuls, auto-selected
at bind().  All backends produce identical updates up to float summation
order (tests/test_mxu_kernels.py, tests/test_gather_kernels.py,
tests/test_dense_path.py).

Batch sampling mirrors Master.scala:184 (`split.map(Random.shuffle(_))`
then slice): every step each worker draws a fresh uniform batch from its
shard.  `sampling='fresh'` reproduces this with per-step uniform draws
(with replacement — delta documented); `sampling='epoch'` has each
(virtual) worker walk a per-epoch permutation of its OWN disjoint
ceil-split sub-shard (classic epoch semantics, stronger convergence).
Both modes use the same vanilla-split sample ownership
(SplitStrategy.scala:13-14): switching sampling never changes which
samples a worker may touch.

Evaluation (objective + accuracy over a full split) also runs sharded and
chunked on device, replacing the reference's master-local full-dataset
per-epoch pass (Master.scala:201-209) — 4 of those per epoch are the
reference's #2 hot loop (SURVEY.md §3.5).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_sgd_tpu.data.rcv1 import LIST_NO_ROW, Dataset
from distributed_sgd_tpu.models.linear import LinearModel, expand_labels, require_single_output
from distributed_sgd_tpu.ops import ftrl, gather, kernels
from distributed_sgd_tpu.ops.sparse import SparseBatch
from distributed_sgd_tpu.parallel.mesh import (
    WORKER_AXIS,
    gather_replicated,
    label_slot,
    packed_width,
    pcast_varying,
    put_packed,
    put_rows,
    shard_map,
    unpack_rows,
)
from distributed_sgd_tpu.utils import measure

AXIS = WORKER_AXIS
# the evaluation program returns an FTRL fit's count of nonzero weights as
# its quotient and remainder by this: float32 holds each exactly below 2**24
NONZERO_SPLIT = 1 << 16


class Evaluation(NamedTuple):
    """One split's evaluation as the host reads it (`BoundSync.read`)."""

    objective: float  # penalty + mean sample loss
    accuracy: float
    # the objective's regulariser: lam ||w||^2, under FTRL
    # l1 ||w||_1 + (l2 / 2) ||w||^2
    penalty: float
    nonzero: Optional[int]  # under FTRL the weights not exactly 0, else None


class ShardedData(NamedTuple):
    indices: jax.Array  # int32[N_pad, P], sharded over workers
    values: jax.Array  # f32[N_pad, P], sharded over workers
    # [N_pad], or [N_pad, C] for a model with C outputs (bind() stores it
    # zero-padded to the lanes of the kernel's margins,
    # kernels.Plan.lanes); sharded over workers; 0 = padding mask.
    # Or id lists (`label_lists` below).
    # What the evaluation, `predict`'s callers and every step whose
    # `label_slot` is None read
    labels: jax.Array
    n_true: int  # real sample count (host-side)
    # the rows' true width: bind() may store indices / values zero-padded
    # to whole lanes (mesh.put_rows), `values` also one column wider for the
    # label; None: the arrays are as wide as the rows
    width: Optional[int] = None
    # narrow sparse rows bind() stored as ONE array (mesh.put_packed):
    # `indices` then holds int32[N_pad, 128] rows of `width` indices and
    # `width` values' bits, and `values` is a zero-width placeholder
    packed: bool = False
    # the word of a stored row that ALSO holds the row's label, as float32
    # (mesh.label_slot): a lane of the packed row (its bits), else a column
    # of `values` past `width`; the step reads it out of the rows it draws.
    # None: the step gathers `labels`
    label_slot: Optional[int] = None
    # `labels` is int32[N_pad, Lw]: every row's positive ids among the
    # model's outputs (Dataset.n_labels), stored as they come; the step and
    # the evaluation expand what they read (models/linear.expand_labels)
    label_lists: bool = False

    @property
    def is_dense(self) -> bool:
        """Dense layout (Dataset.dense): zero-width index array."""
        return self.indices.shape[1] == 0


class BoundSync:
    """Sync engine bound to one dataset's shapes: jitted epoch/eval/step."""

    def __init__(
        self,
        model: LinearModel,
        mesh: Mesh,
        data: ShardedData,
        batch_size: int,
        learning_rate: float,
        sampling: str = "fresh",
        steps_per_epoch: Optional[int] = None,
        eval_chunk: int = 4096,
        kernel: str = "mxu",
        virtual_workers: int = 1,
        optimizer=None,
        momentum: float = 0.9,
        donate: bool = False,
        plan: Optional[kernels.Plan] = None,  # None: made here, for `kernel`
    ):
        if sampling not in ("fresh", "epoch"):
            raise ValueError(f"sampling must be 'fresh' or 'epoch', got {sampling!r}")
        # buffer donation (ROADMAP item 2): donate=True marks the weights
        # and optimizer-state arguments of the TRAINING dispatches (step /
        # epoch / fused multi-epoch) as donated, so XLA reuses their HBM
        # for the outputs instead of allocating fresh buffers per call.
        # Bit-exact, but it consumes the caller's arrays: re-using a
        # donated input faults (tests/test_donation.py) — hence opt-in.
        # Eval/predict never donate (weights are read-only there).
        self._donate = (0, 1) if donate else ()
        self.model = model
        self.mesh = mesh
        self.data = data
        self.batch_size = int(batch_size)
        self.learning_rate = float(learning_rate)
        self.sampling = sampling
        self.n_workers = mesh.shape[AXIS]
        # Emulate K reference workers per mesh device: each step draws K
        # per-worker batches from K DISJOINT contiguous sub-shards (the
        # vanilla-split assignment, SplitStrategy.scala:13-14), computes
        # each worker's sum+regularize reply exactly (vmap), and means
        # them — reference topology semantics (Slave.scala:142-157 per
        # worker + Master.scala:194 mean) without needing K physical
        # chips.  Total worker count = mesh * K; the reference's
        # application.conf nodeCount=3 maps to K=3 on one chip.
        self.virtual_workers = int(virtual_workers)
        if self.virtual_workers < 1:
            raise ValueError("virtual_workers must be >= 1")
        self.shard_n = data.indices.shape[0] // self.n_workers
        self.eval_chunk = min(eval_chunk, self.shard_n)
        if self.shard_n % self.eval_chunk != 0:
            raise ValueError(
                f"shard size {self.shard_n} not a multiple of eval_chunk {self.eval_chunk}"
            )
        # optional optax optimizer (capability superset; the reference is
        # plain SGD, Master.scala:197).  None = reference update w - lr*g.
        # State lives in the kernel's weight layout and is threaded through
        # every compiled loop, replicated over the mesh like the weights.
        # FTRL-Proximal (ops/ftrl.py; `optimizer='ftrl'` or `ftrl.Ftrl`) is
        # no optax update: its state (z, n) rides in the optimizer state's
        # place, and every w the binding hands out is the closed form of it.
        # The plan says which update runs; `self.ftrl` holds FTRL's numbers.
        kind = optimizer_kind(optimizer)
        self.plan = plan or kernels.plan(
            model, learning_rate=self.learning_rate, optimizer=kind,
            row_width=0 if data.is_dense else data.width or data.indices.shape[1],
            virtual_workers=self.virtual_workers, batch_size=self.batch_size,
            n_workers=self.n_workers, eval_chunk=self.eval_chunk, lists=data.label_lists,
            riding=data.label_slot is not None, kernel=kernel, device=mesh.devices.flat[0])
        if self.plan.optimizer != kind:
            raise ValueError(f"the plan is for optimizer={self.plan.optimizer!r}, "
                             f"the binding's optimizer is {kind!r}")
        self.ftrl = (ftrl.params(optimizer, self.learning_rate, model)
                     if kind == "ftrl" else None)
        self.opt = (resolve_optimizer(optimizer, self.learning_rate, momentum)
                    if kind == "optax" else None)
        self.kernel = self.plan.kernel
        if (self.kernel == "dense") != data.is_dense:
            raise ValueError(
                f"kernel='dense' goes with dense-layout data (Dataset.dense) and "
                f"vice versa; got kernel={self.kernel!r}, dense data={data.is_dense}"
            )
        model.check_kernel(self.kernel)
        # rows stored wider than the dataset holds them (mesh.put_rows):
        # every read takes the true width back off (rows / chunk)
        padded = (not data.packed and data.width is not None
                  and data.width < data.values.shape[1])
        self._width = data.width if padded else None
        self._packed = data.width if data.packed else None
        if data.label_slot is not None:
            first = 2 * data.width if data.packed else data.width
            stored = (data.indices if data.packed else data.values).shape[1]
            if model.n_outputs > 1 or not first <= data.label_slot < stored:
                raise ValueError(
                    f"label_slot={data.label_slot} is no spare word of a stored row "
                    f"(words {first}..{stored - 1}, one output)")
            if data.label_lists:
                raise ValueError("a label list rides in no word of a stored row")
        # reference: maxSamples = max shard size; steps = ceil(max/bs)
        # (Master.scala:138,179) computed over true samples and the TOTAL
        # worker count (mesh devices x virtual workers per device)
        max_shard = math.ceil(data.n_true / (self.n_workers * self.virtual_workers))
        self.steps_per_epoch = steps_per_epoch or max(1, math.ceil(max_shard / self.batch_size))
        # FTRL's 2 x 4 D bytes are made where a binding first trains (`_state`):
        # the test split's binding and the checks' never do
        self._opt_state = None if kind == "ftrl" else self._init_opt_state()
        sspec = P() if kind == "ftrl" else jax.tree.map(lambda _: P(), self._opt_state)
        # the workers' replies under FTRL: the loss's gradient alone
        self._loss_model = model if kind != "ftrl" else type(model)(
            0.0, model.n_features, regularizer="none")

        # the evaluation's margin plan (`kernels.Fetch` 'planned'): every
        # chunk's pieces as the margin kernel reads them, made once, from rows
        # that never change; an argument of the evaluation programs beside them
        planned = self.plan.eval_fetch.how == "planned"
        self.margin_plan = self._plan_margins() if planned else None
        pspec = (P(AXIS),) if planned else ()

        dspec = (P(AXIS), P(AXIS), P(AXIS))
        self._epoch = jax.jit(
            shard_map(
                self._epoch_shard,
                mesh=mesh,
                in_specs=(P(), sspec) + dspec + (P(),),
                out_specs=(P(), sspec),
            ),
            donate_argnums=self._donate,
        )
        self._step = jax.jit(
            shard_map(
                self._step_shard,
                mesh=mesh,
                in_specs=(P(), sspec) + dspec + (P(),),
                out_specs=(P(), sspec),
            ),
            donate_argnums=self._donate,
        )
        self._sspec = sspec
        self._eval = jax.jit(
            shard_map(
                self._eval_shard,
                mesh=mesh,
                in_specs=(P(),) + dspec + pspec,
                out_specs=P(),
            )
        )
        self._predict = jax.jit(
            shard_map(
                self._predict_shard,
                mesh=mesh,
                in_specs=(P(),) + dspec[:2] + pspec,
                out_specs=P(AXIS),
            )
        )

    @property
    def _planned(self) -> tuple:
        """The evaluation programs' arguments past the rows: the margin plan,
        where the binding has one."""
        return () if self.margin_plan is None else (self.margin_plan,)

    @property
    def update_sparse(self) -> bool:
        """Whether the step builds no gradient (`_sparse_step`)."""
        return self.plan.update == "sparse"

    @property
    def labels_as(self) -> str:
        return self.plan.labels

    # -- per-device bodies (run under shard_map) ---------------------------

    def _subshards(self):
        """(sub, starts, sizes): the per-virtual-worker ceil-split of this
        device's shard — the vanilla-split assignment
        (SplitStrategy.scala:13-14: grouped(ceil(n/k))).  The SINGLE source
        of sample ownership: both sampling modes and the trainability check
        derive from it, so ownership can never diverge between modes."""
        k = self.virtual_workers
        sub = -(-self.shard_n // k)  # ceil
        starts = np.minimum(np.arange(k) * sub, self.shard_n - 1)
        sizes = np.maximum(self.shard_n - starts, 1)
        return sub, starts, sizes

    def _sample_ids(self, key: jax.Array, step: jax.Array) -> jax.Array:
        """[virtual_workers, batch_size] sample ids into this device's shard.

        Each virtual worker draws ONLY from its own disjoint contiguous
        ceil-split sub-shard (_subshards), so the K-virtual and K-device
        topologies partition data identically and every sample is
        reachable.  The short trailing sub-shard maps out-of-range draws in
        via modulo (bias/duplicates bounded by sub - size).
        """
        k, b = self.virtual_workers, self.batch_size
        sub, starts, sizes = self._subshards()
        wrap = jnp.asarray(np.minimum(sub, sizes))
        if self.sampling == "fresh":
            # fresh uniform draw per step, like the per-batch reshuffle in
            # Master.scala:184 (delta: with replacement within a batch)
            sel = jax.random.randint(jax.random.fold_in(key, step), (k, b), 0, sub)
        else:
            # 'epoch': each virtual worker walks a per-epoch permutation of
            # its own sub-shard (VERDICT r3 item 5: same ownership as
            # 'fresh', sampling without replacement within the epoch)
            perms = jax.vmap(jax.random.permutation, in_axes=(0, None))(
                jax.random.split(key, k), sub
            )  # [k, sub]
            start = jnp.minimum(step * b, sub - b)
            sel = jax.lax.dynamic_slice(perms, (jnp.zeros_like(start), start), (k, b))
        sel = sel % wrap.astype(sel.dtype)[:, None]
        return sel + jnp.asarray(starts, dtype=sel.dtype)[:, None]

    def _one_step(self, w, opt_state, idx, val, y, key, step):
        """One sync DP step on weights in the kernel's native layout:
        dense [D] for 'scalar'/'dense', lane-blocked [R, 128] for
        'mxu'/'gather'.  Returns (w', opt_state')."""
        g = self._gradient(w, idx, val, y, key, step, self.model)
        with jax.named_scope("dsgd.update"):
            # master mean over ALL workers (Master.scala:194)
            g = g / (self.n_workers * self.virtual_workers)
            if self.opt is None:  # reference update (Master.scala:197)
                return w - self.learning_rate * g, opt_state
            import optax

            updates, opt_state = self.opt.update(g, opt_state, w)
            return optax.apply_updates(w, updates), opt_state

    def _gradient(self, w, idx, val, y, key, step, model):
        """The SUM over all workers of their replies (`model`'s gradients
        of the step's draw at `w`), in the kernel's layout."""
        # The jax.named_scope names (dsgd.draw, dsgd.allreduce, dsgd.update
        # here; dsgd.onehot / margins / coeff / scatter / regularize where
        # the kernels are defined) are HLO metadata only: the benchmark's
        # per-piece device metrics find each piece of the step by them
        # (benchmark/program_spans.py, PERF.md section 3).
        one = self.virtual_workers == 1
        with jax.named_scope("dsgd.draw"):
            ids = self._sample_ids(key, step)  # [K, B]
            if one:
                ids = ids[0]
            bi, bv, by = self.draw_rows(idx, val, y, ids)  # the resident-row gathers
        by = self._labels(by)
        if one:  # one worker's Gradient reply (Slave.scala:142-157)
            g = model.grad(w, SparseBatch(bi, bv), by, kernel=self.kernel)
        else:  # the K virtual workers' replies, summed (mean-normalized by the caller)
            g = model.grad_workers(w, bi, bv, by, kernel=self.kernel)
        with jax.named_scope("dsgd.allreduce"):
            return jax.lax.psum(g, AXIS)

    def _ftrl_step(self, state, idx, val, y, key, step):
        """The dense step under FTRL (a binding `kernels.sparse_update` does
        not name): w from (z, n) over all of D, the workers' loss gradients
        as `_one_step` takes them (no regulariser term: the L2 strength is in
        the closed form), their mean over ALL workers, and the same
        per-coordinate update over all of D (ops/ftrl.py)."""
        w = self._to_kernel_layout(self._ftrl_weights(state))
        g = self._gradient(w, idx, val, y, key, step, self._loss_model)
        with jax.named_scope(ftrl.SCOPE):
            g = self._from_kernel_layout(g) / (self.n_workers * self.virtual_workers)
            return ftrl.apply(state, g, self.ftrl)

    def _ftrl_steps(self, state, idx, val, y, key):
        """`steps_per_epoch` steps of FTRL on its state."""
        if self.update_sparse:
            return self._sparse_steps(state, idx, val, y, key)

        def body(state, step):
            return self._ftrl_step(state, idx, val, y, key, step), ()

        state, _ = jax.lax.scan(body, state, jnp.arange(self.steps_per_epoch))
        return state

    def _ftrl_weights(self, state):
        """w[D] of FTRL's state: what every program of the binding hands out."""
        return ftrl.materialise(state, self.model.n_features, self.ftrl)

    # -- the sparse step (kernels.sparse_update) ------------------------------
    #
    # The update w' = w - lr (sum_k g_k + 2 lam K w) / n over all n workers is
    #
    #     w' = (1 - c) w - (lr / n) sum over the step's entries of
    #                                 coeff_b v_bp e[i_bp],      c = 2 lr lam
    #
    # and a step stores only ~K B P words of it: the loop carries v2 with
    # w2 = s v2, a step's margins are s x the gathered v2, its entries are
    # scattered INTO v2 with the factor -(lr / n) / s', s' = (1 - c) s, and
    # s is folded into v2 where the weights leave the loop.  No [R, 128]
    # array is produced inside the loop: the step's bytes have no term in D.
    #
    # s is never a float32 product: c is ~3e-8 at lambda = 1/n, under half of
    # float32's epsilon, so s (1 - c) would round to s (as the dense float32
    # step's w - lr 2 lam w rounds to w on every coordinate a step does not
    # touch).  The rate is constant, so after t steps s = exp(t log1p(-c)),
    # from the scan's own step counter: exact to float32, nothing carried.

    # The loop folds s into v2 before t |log1p(-c)| passes this: s stays in
    # (1/e, 1], so 1 / s never nears float32's range, and the exponent's
    # float32 rounding (half an ulp of 1 at most) keeps every entry's weight
    # s_T / s_t exact to ~1e-7.  At lambda = 1/n that is tens of millions of
    # steps: one fold a program, where the weights are handed out.
    _FOLD_LOG = 1.0

    def _fold_span(self) -> int:
        """Steps the sparse loop runs between two folds of s into v2."""
        if self.plan.decay == 0.0:
            return self.steps_per_epoch
        return max(1, int(self._FOLD_LOG / -math.log1p(-self.plan.decay)))

    def _scale(self, since):
        """s after `since` steps since the last fold (None: no decay)."""
        if self.plan.decay == 0.0:
            return None
        return jnp.exp(since.astype(jnp.float32) * jnp.float32(math.log1p(-self.plan.decay)))

    def _rescale(self, v2, since: int):
        """The fold: w2 = s v2 after `since` steps, one pass over the
        weights, as v2 + (s - 1) v2 with s - 1 from expm1 in float64 (a
        float32 s would be 1.0 and lose the term)."""
        if self.plan.decay == 0.0:
            return v2
        with jax.named_scope("dsgd.rescale"):
            return v2 + jnp.float32(math.expm1(since * math.log1p(-self.plan.decay))) * v2

    def _sparse_step(self, v2, idx, val, y, key, step, since):
        """One sync DP step on the carried `v2` (blocked weights = s v2,
        `since` steps after the last fold): draw, margins and coefficients
        as `_one_step` has them, the replies kept as entries, exchanged as
        entries over the mesh, scattered into `v2`."""
        n = self.n_workers * self.virtual_workers
        with jax.named_scope("dsgd.draw"):
            ids = self._sample_ids(key, step)  # [K, B]
            bi, bv, by = self.draw_rows(idx, val, y, ids)
        by = self._labels(by)
        width = bi.shape[-1]
        # the K virtual workers share the weights: one call on their merged
        # batches (kernels.merges_margins), one scatter of all their entries
        merged = SparseBatch(bi.reshape(-1, width), bv.reshape(-1, width))
        if self.plan.optimizer == "ftrl":
            # `v2` is FTRL's state: the margins read w off it, the entries are
            # g (the mean over ALL workers of their sums), and a touched row's
            # 64 coordinates take the update from their summed g
            at, add = self.model.reply_entries(
                v2, merged, by.reshape(-1), factor=1.0 / n,
                matvec=functools.partial(ftrl.matvec, p=self.ftrl))
            return self._scatter_entries(v2, at, add, row=functools.partial(
                ftrl.rows, p=self.ftrl), per_row=ftrl.HALF)
        with jax.named_scope("dsgd.update"):
            s, s_next = self._scale(since), self._scale(since + 1)
            # master mean over ALL workers (Master.scala:194) and the
            # reference update (Master.scala:197), on the entries
            factor = -self.learning_rate / n
            if s_next is not None:
                factor = factor / s_next
        if self.model.n_outputs > 1:
            return self._scatter_reply_rows(v2, merged, by, s, factor)
        at, add = self.model.reply_entries(v2, merged, by.reshape(-1), s, factor)
        return self._scatter_entries(v2, at, add)

    def _scatter_entries(self, v2, at, add, **ending):
        """Every device's entries (ids `at`, updates `add`) into `v2`."""
        # every device scatters every device's entries: the sum a psum of
        # the dense replies would give, in another order (one device: the
        # all-gather is the identity, and what types the entries replicated)
        with jax.named_scope("dsgd.allreduce"):
            # ids and the updates' bits side by side: one collective a step
            bits = jax.lax.bitcast_convert_type(add, jnp.int32)
            both = gather_replicated(jnp.stack([at, bits]), AXIS)  # [devices, 2, T]
            at = both[:, 0].reshape(-1)
            add = jax.lax.bitcast_convert_type(both[:, 1], jnp.float32).reshape(-1)
        return gather.scatter_into(v2, at, add, self.plan.scatter, **ending)

    def _scatter_reply_rows(self, v2, merged, by, s, factor):
        """The rest of `_sparse_step` with an output axis: an entry's
        update is a row of `v2`, exchanged as its factors (id, value, the
        sample it belongs to) beside every sample's coefficient row, a
        hundredth of the bytes of the rows themselves."""
        at, val, src, coeff = self.model.reply_rows(
            v2, merged, by.reshape((-1,) + by.shape[2:]), s, factor, self.plan.step_fetch)
        t, (samples, lanes) = at.shape[0], coeff.shape
        with jax.named_scope("dsgd.allreduce"):
            # all of it as bits in one vector: one collective a step
            bits = jnp.concatenate([
                at, jax.lax.bitcast_convert_type(val, jnp.int32), src,
                jax.lax.bitcast_convert_type(coeff, jnp.int32).reshape(-1)])
            every = gather_replicated(bits, AXIS)  # [devices, 3 T + samples x lanes]
            at = every[:, :t].reshape(-1)
            val = jax.lax.bitcast_convert_type(every[:, t:2 * t], jnp.float32).reshape(-1)
            # a device's samples follow the devices' before it
            src = (every[:, 2 * t:3 * t]
                   + samples * jnp.arange(every.shape[0], dtype=jnp.int32)[:, None]).reshape(-1)
            coeff = jax.lax.bitcast_convert_type(
                every[:, 3 * t:], jnp.float32).reshape(-1, lanes)
        return gather.scatter_rows_into(v2, at, val, src, coeff, self.plan.scatter)

    def _sparse_steps(self, v2, idx, val, y, key):
        """`steps_per_epoch` sparse steps on blocked weights, folded."""
        steps, span = self.steps_per_epoch, self._fold_span()

        def run(v2, first, count):
            def body(v2, j):
                return self._sparse_step(v2, idx, val, y, key, first + j, j), ()

            v2, _ = jax.lax.scan(body, v2, jnp.arange(count))
            return self._rescale(v2, count)

        if steps <= span:
            return run(v2, 0, steps)
        whole, rest = divmod(steps, span)
        v2, _ = jax.lax.scan(lambda v2, i: (run(v2, i * span, span), ()),
                             v2, jnp.arange(whole))
        return run(v2, whole * span, rest) if rest else v2

    def rows(self, resident, ids):
        """Rows `ids` of a resident [rows, width] array as the dataset holds
        them: without the lane padding bind() may have stored them with."""
        rows = resident[ids]  # whole stored rows: the compiler's fast gather
        return rows if self._width is None else rows[..., :self._width]

    def chunk(self, resident, start):
        """The evaluation's rows [start, start + eval_chunk), likewise."""
        rows = jax.lax.dynamic_slice_in_dim(resident, start, self.eval_chunk, 0)
        return rows if self._width is None else rows[:, :self._width]

    def draw_rows(self, idx, val, y, ids):
        """(indices, values, labels) of rows `ids`, a step's draw: one gather
        where bind() packed a row's indices and values into one stored row,
        else two, and a third of single words out of `y` only where the
        label does not ride in the row (ShardedData.label_slot; float32
        where it does).  The one place that knows where a label lies."""
        slot = self.data.label_slot
        if self._packed is not None:
            stored = idx[ids]
            by = y[ids] if slot is None else jax.lax.bitcast_convert_type(
                stored[..., slot], jnp.float32)
            return unpack_rows(stored, self._packed) + (by,)
        stored = val[ids]  # whole stored rows: the compiler's fast gather
        bv = stored if self._width is None else stored[..., :self._width]
        return self.rows(idx, ids), bv, y[ids] if slot is None else stored[..., slot]

    def _labels(self, y):
        """The labels the losses take, of labels as they are resident: id
        lists expanded to rows of +1 / -1 / 0 (under `dsgd.labels`), every
        other form as it is."""
        if self.plan.labels != "lists":
            return y
        # [B, C], or [B, L] beside lane-padded margins
        return expand_labels(y, self.model.n_outputs, self.plan.lanes or self.model.n_outputs)

    def chunk_rows(self, idx, val, start):
        """(indices, values) of the evaluation's chunk at `start`, likewise."""
        if self._packed is not None:
            return unpack_rows(self.chunk(idx, start), self._packed)
        return self.chunk(idx, start), self.chunk(val, start)

    def _to_kernel_layout(self, w):
        w = self.model.to_layout(w, self.kernel)
        return gather.to_tiles(w) if self.plan.tiles else w

    def _from_kernel_layout(self, w):
        return self.model.from_layout(gather.from_tiles(w) if self.plan.tiles else w,
                                      self.kernel)

    def _loop_labels(self, y):
        """The labels a scan over steps gathers from, where it gathers any
        (a label that rides in its row, ShardedData.label_slot, is never
        read from here).  Where the rows are read in place there is no copy
        of them at the program's entry for the compiler to hide a fetch
        behind, and it then fetches the WHOLE label array into fast memory
        again in every step (1.6 us of a 24.7 us step at 491,520 labels,
        PERF.md section 6, PR 25); a float32 copy made once before the loop
        it keeps there.  Every grad_coeff casts its labels to float32 first,
        so the step computes the same."""
        if self.plan.labels == "in_row" or self._width is None or y.ndim == 2:
            return y  # (rows of labels: one gather, no fetch)
        return y.astype(jnp.float32)

    def _epoch_shard(self, w, opt_state, idx, val, y, key):
        if isinstance(key, tuple):  # (base key, epoch): the fit's key, folded here (`epoch`)
            key = jax.random.fold_in(*key)
        key = jax.random.fold_in(key, jax.lax.axis_index(AXIS))
        if self.plan.optimizer == "ftrl":
            # `opt_state` is (z, n); `w` is taken as every binding's programs
            # take it and not read: the weights are the closed form of (z, n)
            state = self._ftrl_steps(opt_state, idx, val, self._loop_labels(y), key)
            return self._ftrl_weights(state), state
        w = self._to_kernel_layout(w)
        y = self._loop_labels(y)
        if self.update_sparse:
            return self._from_kernel_layout(self._sparse_steps(w, idx, val, y, key)), opt_state

        def body(carry, step):
            return self._one_step(*carry, idx, val, y, key, step), ()

        (w, opt_state), _ = jax.lax.scan(
            body, (w, opt_state), jnp.arange(self.steps_per_epoch)
        )
        return self._from_kernel_layout(w), opt_state

    def _step_shard(self, w, opt_state, idx, val, y, key):
        key = jax.random.fold_in(key, jax.lax.axis_index(AXIS))
        if self.plan.optimizer == "ftrl":  # `w` not read, as in `_epoch_shard`
            zero = jnp.int32(0)
            state = (self._sparse_step(opt_state, idx, val, y, key, zero, zero)
                     if self.update_sparse else
                     self._ftrl_step(opt_state, idx, val, y, key, zero))
            return self._ftrl_weights(state), state
        w = self._to_kernel_layout(w)
        if self.update_sparse:
            zero = jnp.int32(0)
            w = self._rescale(self._sparse_step(w, idx, val, y, key, zero, zero), 1)
            return self._from_kernel_layout(w), opt_state
        w, opt_state = self._one_step(w, opt_state, idx, val, y, key, jnp.int32(0))
        return self._from_kernel_layout(w), opt_state

    def _chunk_plan(self, plan, t):
        """Chunk `t`'s pieces of the margin plan (None: the binding has none)."""
        if plan is None:
            return None
        with jax.named_scope("dsgd.margins"):
            return jax.tree.map(lambda a: a[t], plan)

    def _eval_shard(self, w, idx, val, y, plan=None) -> jax.Array:
        # chunked scan so the working set stays small; pads (label 0) masked;
        # bind() padded each shard to a multiple of eval_chunk
        chunk = self.eval_chunk
        n_chunks = self.shard_n // chunk
        w_layout = self._to_kernel_layout(w)

        def body(acc, t):
            loss_acc, hit_acc = acc
            # the chunk's fetch, with whatever re-layout the compiler puts there
            with jax.named_scope("dsgd.eval_rows"):
                s = t * chunk
                ci, cv = self.chunk_rows(idx, val, s)
                cy = self._labels(jax.lax.dynamic_slice_in_dim(y, s, chunk, 0))
                mask = (cy != 0).astype(jnp.float32)
            # the same gather the step runs (models/linear.py `margins`)
            margins = self.model.margins(w_layout, SparseBatch(ci, cv), kernel=self.kernel,
                                         fetch=self.plan.eval_fetch,
                                         plan=self._chunk_plan(plan, t))
            with jax.named_scope("dsgd.eval_reduce"):
                losses = self.model.losses_from_margins(margins, cy)
                preds = self.model.predict(margins)
                hits = (preds == cy.astype(jnp.float32)).astype(jnp.float32)
                return (loss_acc + jnp.sum(losses * mask), hit_acc + jnp.sum(hits * mask)), ()

        # the pieces inside keep their own names (dsgd.eval_rows, dsgd.margins,
        # dsgd.eval_reduce): the benchmark's boundary metrics read them
        with jax.named_scope("dsgd.eval"):
            init = pcast_varying((jnp.float32(0), jnp.float32(0)), (AXIS,))
            (loss_sum, hit_sum), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
            sums = jnp.stack([loss_sum, hit_sum])
        with jax.named_scope("dsgd.allreduce"):
            sums = jax.lax.psum(sums, AXIS)
        # the objective's regulariser, of the replicated `w`: the same on
        # every device, so past the psum
        with jax.named_scope("dsgd.eval"), jax.named_scope("dsgd.eval_reduce"):
            return jnp.concatenate([sums, self._weight_sums(w)])

    def _weight_sums(self, w) -> jax.Array:
        """[||w||^2], under FTRL [||w||_1, ||w||^2, nonzero // NONZERO_SPLIT,
        nonzero % NONZERO_SPLIT]: `ftrl.weight_sums` fenced off from the rest
        of the program (so it gives `ftrl.penalty`'s bits), and the count in
        two parts that float32 holds exactly (`read`)."""
        w = jnp.asarray(w, jnp.float32)
        if self.plan.optimizer != "ftrl":
            return jnp.sum(w * w)[None]
        fence = jax.lax.optimization_barrier
        nonzero = jnp.count_nonzero(w)
        return jnp.concatenate([fence(ftrl.weight_sums(fence(w))), jnp.stack([
            (nonzero // NONZERO_SPLIT).astype(jnp.float32),
            (nonzero % NONZERO_SPLIT).astype(jnp.float32)])])

    def _predict_shard(self, w, idx, val, plan=None) -> jax.Array:
        chunk = self.eval_chunk
        n_chunks = self.shard_n // chunk
        w_layout = self._to_kernel_layout(w)

        def body(_, t):
            with jax.named_scope("dsgd.eval_rows"):
                ci, cv = self.chunk_rows(idx, val, t * chunk)
            margins = self.model.margins(w_layout, SparseBatch(ci, cv), kernel=self.kernel,
                                         fetch=self.plan.eval_fetch,
                                         plan=self._chunk_plan(plan, t))
            with jax.named_scope("dsgd.eval_reduce"):
                return (), self.model.predict(margins)

        with jax.named_scope("dsgd.eval"):
            _, preds = jax.lax.scan(body, (), jnp.arange(n_chunks))
            return preds.reshape((-1,) + preds.shape[2:])

    def _margin_plan_shard(self, idx, val) -> gather.PiecePlan:
        """This device's margin plan: `gather.plan_pieces` of every chunk of
        its shard, a leading axis the chunks, one chunk at a time."""
        chunk = self.eval_chunk
        d = self.model.n_features
        rows = d + -d % gather.SUBLANES  # of the weight tiles (`gather.to_rows`)

        def one(t):
            return gather.plan_pieces(self.chunk_rows(idx, val, t * chunk)[0],
                                      self.plan.eval_fetch.piece, rows)

        return jax.lax.map(one, jnp.arange(self.shard_n // chunk))

    def _plan_margins(self) -> gather.PiecePlan:
        """The binding's margin plan, made on its devices (under the span
        `bind.margin_plan`); of shapes alone where the rows are (a program
        lowered for a device this process does not hold)."""
        make = jax.jit(shard_map(self._margin_plan_shard, mesh=self.mesh,
                                 in_specs=(P(AXIS), P(AXIS)), out_specs=P(AXIS)))
        rows = (self.data.indices, self.data.values)
        if not all(isinstance(a, jax.Array) for a in rows):
            return jax.eval_shape(make, *rows)
        with measure.span("bind.margin_plan"):
            return jax.block_until_ready(make(*rows))

    def record(self) -> str:
        """The binding's fields of the `train split:` record: the plan's
        (`kernels.Plan.record`), and where the evaluation's margins are
        planned, the margin plan's bytes and the share of its entries that
        are distinct tiles (what the kernel fetches)."""
        if self.margin_plan is None:
            return self.plan.record()
        plan = self.margin_plan
        share = float(jnp.sum(plan.heads)) / (plan.heads.size * self.plan.eval_fetch.piece
                                              * (self.data.width or self.data.indices.shape[1]))
        return (f"{self.plan.record()} margin_plan_bytes="
                f"{sum(a.nbytes for a in plan)} margin_plan_distinct={share:.4f}")

    def _multi_epoch_shard(self, n_epochs, w, opt_state, idx, val, y, key):
        key = jax.random.fold_in(key, jax.lax.axis_index(AXIS))
        if self.plan.optimizer == "ftrl":  # `w` not read, as in `_epoch_shard`
            state, _ = jax.lax.scan(
                lambda s, e: (self._ftrl_steps(s, idx, val, self._loop_labels(y),
                                               jax.random.fold_in(key, e)), ()),
                opt_state, jnp.arange(n_epochs))
            return self._ftrl_weights(state), state
        w = self._to_kernel_layout(w)
        y = self._loop_labels(y)

        def epoch_body(c, e):
            ke = jax.random.fold_in(key, e)
            if self.update_sparse:
                return (self._sparse_steps(c[0], idx, val, y, ke), c[1]), ()

            def body(c2, step):
                return self._one_step(*c2, idx, val, y, ke, step), ()

            c, _ = jax.lax.scan(body, c, jnp.arange(self.steps_per_epoch))
            return c, ()

        (w, opt_state), _ = jax.lax.scan(epoch_body, (w, opt_state), jnp.arange(n_epochs))
        return self._from_kernel_layout(w), opt_state

    def _check_trainable(self) -> None:
        """Checked at train-call time, not bind time: an eval-only binding
        (e.g. the test split) never samples batches."""
        k = self.virtual_workers
        sub, _starts, _sizes = self._subshards()
        if self.sampling == "epoch" and self.batch_size > sub:
            raise ValueError(
                f"sampling='epoch' needs batch_size ({self.batch_size}) <= "
                f"per-virtual-worker sub-shard ({sub} = "
                f"ceil({self.shard_n}/{k})); lower the batch size or worker "
                f"count"
            )
        if k > 1 and (k - 1) * sub >= self.shard_n:
            # vanilla_split would hand the trailing worker(s) an EMPTY
            # group here (grouped(ceil) yields < k groups); rather than
            # silently double-weighting the last sample, refuse
            raise ValueError(
                f"virtual_workers={k} over a {self.shard_n}-sample shard "
                f"leaves trailing workers without a nonempty ceil-split "
                f"sub-shard (the reference's vanilla split would give them "
                f"empty groups); lower virtual_workers"
            )

    # -- host API ----------------------------------------------------------

    def warmup_thunks(self):
        """Flagship compile thunks for the AOT warmup pass
        (compile_cache.py, DSGD_COMPILE_CACHE): pre-lower + XLA-compile,
        at this binding's exact shapes and WITHOUT executing them, the
        programs `SyncTrainer.fit` dispatches, with the arguments it passes
        (`fit_signatures`).  ``lower(...)`` takes the real bound arrays
        (lowering reads shapes/shardings only; donation consumes nothing
        until execution) and ``.compile()`` populates the persistent cache,
        so the fit's first dispatch re-traces cheaply and reads the XLA
        executable from disk instead of re-running the backend compile."""
        return [(name, lambda p=program, a=args: p.lower(*a).compile())
                for name, program, args in self.fit_signatures()]

    def fit_signatures(self):
        """[(name, program, arguments)] of what a fit dispatches on this
        binding: the epoch program on the fit's first weights (as made, on
        no device in particular) with the key folded inside it, again on
        the weights and state it returns (replicated over the mesh: the
        epoch program compiles once for each), and the evaluation program
        on those weights.  Shapes stand where no array does yet."""
        w0 = jnp.zeros(self.model.weight_shape, jnp.float32)
        state = self._state()
        rows = (self.data.indices, self.data.values, self.data.labels)
        key = (jax.random.PRNGKey(0), 0)  # `epoch(w, key, at)`'s
        replicated = NamedSharding(self.mesh, P())
        out = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated)  # noqa: E731
        w = out(w0)
        return [("epoch", self._epoch, (w0, state) + rows + (key,)),
                ("epoch.again", self._epoch, (w, jax.tree.map(out, state)) + rows + (key,)),
                ("eval", self._eval, (w,) + rows + self._planned)]

    def _maybe_warmup(self) -> None:
        """Kick the background warmup at bind time when the entry point
        armed it (DSGD_COMPILE_CACHE); a no-op otherwise."""
        from distributed_sgd_tpu import compile_cache

        if compile_cache.warmup_enabled():
            compile_cache.warmup_async(
                f"mesh[{self.n_workers}x{self.kernel}]",
                self.warmup_thunks())

    def placement(self):
        """[(device id, resident rows, the values' major_to_minor, device
        bytes_in_use)] for the bound split — where and how the rows actually
        sit, as the runtime reports it: (0, 1) is row-major, what the step's
        gather reads (mesh.put_rows); the layout and the bytes are None on
        backends that do not report them."""
        rows = self.data.indices if self.data.packed else self.data.values
        layout = rows.format.layout
        stored = None if layout is None else layout.major_to_minor
        return [
            (s.device.id, s.data.shape[0], stored,
             (s.device.memory_stats() or {}).get("bytes_in_use"))
            for s in self.data.indices.addressable_shards
        ]

    def epoch(self, w: jax.Array, key: jax.Array, at: Optional[int] = None) -> jax.Array:
        """One epoch on `key`; given `at`, on `fold_in(key, at)`, folded inside
        the program (the fit's key of epoch `at`, with no program of its own)."""
        self._check_trainable()
        w, self._opt_state = self._epoch(
            w, self._state(), self.data.indices, self.data.values,
            self.data.labels, key if at is None else (key, at),
        )
        return w

    def multi_epoch(self, w: jax.Array, key: jax.Array, n_epochs: int) -> jax.Array:
        """Run `n_epochs` epochs in ONE device dispatch (per-epoch key fold).

        Exists so benchmarks can slope-fit true epoch time on transports
        with per-dispatch overhead; also useful to amortize dispatch in
        long headless runs."""
        if not hasattr(self, "_multi_cache"):
            self._multi_cache = {}
        self._check_trainable()
        if n_epochs not in self._multi_cache:
            import functools

            self._multi_cache[n_epochs] = jax.jit(
                shard_map(
                    functools.partial(self._multi_epoch_shard, n_epochs),
                    mesh=self.mesh,
                    in_specs=(P(), self._sspec) + (P(AXIS), P(AXIS), P(AXIS)) + (P(),),
                    out_specs=(P(), self._sspec),
                ),
                donate_argnums=self._donate,
            )
        w, self._opt_state = self._multi_cache[n_epochs](
            w, self._state(), self.data.indices, self.data.values,
            self.data.labels, key,
        )
        return w

    def step(self, w: jax.Array, key: jax.Array) -> jax.Array:
        self._check_trainable()
        w, self._opt_state = self._step(
            w, self._state(), self.data.indices, self.data.values,
            self.data.labels, key,
        )
        return w

    def _state(self):
        """The optimizer state, FTRL's made on first use."""
        if self._opt_state is None:
            self._opt_state = self._init_opt_state()
        return self._opt_state

    def _init_opt_state(self):
        if self.plan.optimizer == "ftrl":
            return ftrl.zeros(self.model.n_features)
        if self.opt is None:
            return ()
        return self.opt.init(
            self._to_kernel_layout(jnp.zeros(self.model.weight_shape, jnp.float32))
        )

    def reset_optimizer(self) -> None:
        """Zero the optimizer state (momentum buffers etc.)."""
        self._opt_state = self._init_opt_state()

    def opt_state_leaves(self):
        """Optimizer state as a flat list of arrays (checkpoint form)."""
        return jax.tree.leaves(self._state())

    def load_opt_state_leaves(self, leaves) -> None:
        """Restore optimizer state from `opt_state_leaves()` output."""
        treedef = jax.tree.structure(self._state())
        self._opt_state = jax.tree.unflatten(
            treedef, [jnp.asarray(x) for x in leaves]
        )

    def predict(self, w: jax.Array) -> np.ndarray:
        """Model predictions for every (true) sample in the bound split,
        the Master.predict fan-out equivalent (Master.scala:61-75)."""
        preds = self._predict(w, self.data.indices, self.data.values, *self._planned)
        preds = np.asarray(preds)[: self.data.n_true]
        # with an output axis [n, C]: the margins' pad lanes taken off
        return preds if preds.ndim == 1 else preds[:, : self.model.n_outputs]

    def dispatch_evaluation(self, w: jax.Array) -> jax.Array:
        """The evaluation program enqueued on `w`, not waited for: its output
        is the split's loss and hit sums and the sums over `w` its objective
        adds (`_eval_shard`), a few float32 that `read` takes apart once
        pulled."""
        return self._eval(w, self.data.indices, self.data.values, self.data.labels,
                          *self._planned)

    def read(self, pulled: np.ndarray) -> Evaluation:
        """The split's evaluation from what `dispatch_evaluation` returned,
        pulled to the host: no device work.  Phases of the caller's
        `trainer.evaluate` (see `evaluate`)."""
        with measure.span("trainer.evaluate.pull", histogram=False, root=False):
            loss_sum, hit_sum, *weight_sums = pulled.tolist()
        with measure.span("trainer.evaluate.reg", histogram=False, root=False):
            if self.plan.optimizer == "ftrl":
                abs_sum, squares, high, low = weight_sums
                penalty = ftrl.penalty_of(abs_sum, squares, self.ftrl)
                nonzero = int(high) * NONZERO_SPLIT + int(low)
            else:
                penalty, nonzero = self.model.lam * weight_sums[0], None
        n = self.data.n_true
        return Evaluation(penalty + loss_sum / n, hit_sum / (n * self.model.n_outputs),
                          penalty, nonzero)

    def evaluate(self, w: jax.Array) -> Tuple[float, float]:
        """(objective, accuracy) over the bound split.

        objective = lam*||w||^2 + mean sample loss (SparseSVM.scala:20-23),
        under FTRL l1 ||w||_1 + (l2 / 2) ||w||^2 + mean sample loss;
        accuracy = fraction(forward == y) (Master.scala:98-101).  With an
        output axis a sample's loss is the sum over its outputs and the
        accuracy is over (sample, output) pairs.
        """
        # phases of the caller's span (trainer.evaluate, master.async.check):
        # no histogram, and no span of their own outside one.  They tile the
        # call, so the device's idle time inside it is one phase's: the
        # program's dispatch, the one pull of its output (which waits for
        # it), and the host taking the numbers apart (`read`).  The fit
        # (`SyncTrainer.fit`) opens the same phases around the same calls.
        with measure.span("trainer.evaluate.dispatch", histogram=False, root=False):
            out = self.dispatch_evaluation(w)
        with measure.span("trainer.evaluate.wait", histogram=False, root=False):
            pulled = jax.device_get(out)
        return self.read(pulled)[:2]


def local_update(opt, learning_rate: float, g, w, opt_state):
    """One local optimizer step, shared by every async scan body
    (parallel/hogwild.py, parallel/local_sgd.py, core/worker.py).

    Returns (w', opt_state', delta) where delta is the weight-space
    DECREMENT (w' = w - delta): gossip protocols accumulate and ship delta
    so peer merges stay the commutative subtractions Hogwild needs
    (Slave.scala:101,180), regardless of the optimizer.
    """
    with jax.named_scope("dsgd.update"):
        if opt is None:
            delta = learning_rate * g  # the reference update (Slave.scala:99)
            return w - delta, opt_state, delta
        updates, opt_state = opt.update(g, opt_state, w)
        return w + updates, opt_state, -updates


def resolve_optimizer(optimizer, learning_rate: float, momentum: float = 0.9):
    """None/'sgd' -> None (the reference's plain update, Master.scala:197);
    'momentum'/'adam' -> the optax transformation at `learning_rate`; an
    optax GradientTransformation passes through untouched.  FTRL is none of
    these (BoundSync takes it before it asks here)."""
    ftrl.refuse(optimizer, "an engine whose update reads a whole gradient")
    if optimizer is None or optimizer == "sgd":
        return None
    if isinstance(optimizer, str):
        import optax

        if optimizer == "momentum":
            return optax.sgd(learning_rate, momentum=momentum)
        if optimizer == "adam":
            return optax.adam(learning_rate)
        raise ValueError(
            f"optimizer must be 'sgd', 'momentum', 'adam' or an optax "
            f"GradientTransformation, got {optimizer!r}"
        )
    return optimizer


def optimizer_kind(optimizer) -> str:
    """The update a sync binding runs, as `kernels.plan` takes it: 'ftrl'
    (ops/ftrl.py), 'sgd' (None or 'sgd': the reference's) or 'optax' (every
    other, which `resolve_optimizer` makes or refuses)."""
    if ftrl.of(optimizer) is not None:
        return "ftrl"
    return "sgd" if optimizer is None or optimizer == "sgd" else "optax"


class SyncEngine:
    """Factory: shards datasets onto the mesh and binds compiled loops."""

    def __init__(
        self,
        model: LinearModel,
        mesh: Mesh,
        batch_size: int,
        learning_rate: float,
        sampling: str = "fresh",
        eval_chunk: int = 4096,
        kernel: str = kernels.AUTO,
        virtual_workers: int = 1,
        optimizer=None,
        momentum: float = 0.9,
        donate: bool = False,
    ):
        # kernel: AUTO (the default) lets each binding's plan ask the shape
        # rule (ops/kernels.py `plan`); a family's name pins it
        self.model = model
        self.mesh = mesh
        self.batch_size = batch_size
        self.learning_rate = learning_rate
        self.sampling = sampling
        self.eval_chunk = eval_chunk
        self.kernel = kernel
        self.virtual_workers = virtual_workers
        self.optimizer = optimizer
        self.momentum = momentum
        self.donate = donate

    def bind(self, data: Dataset, steps_per_epoch: Optional[int] = None) -> BoundSync:
        n_workers = self.mesh.shape[AXIS]
        n_true = len(data)
        if n_true < n_workers:
            raise ValueError(f"dataset of {n_true} rows < {n_workers} workers")
        total, chunk = padded_layout(n_true, n_workers, self.eval_chunk)
        sharding = NamedSharding(self.mesh, P(AXIS))
        if jax.process_count() > 1 and self.mesh.size == jax.device_count():
            require_single_output(self.model, "the multi-host bind")
            # multi-host global mesh: every process passes the SAME full
            # dataset but pads/copies ONLY its own row range
            # (host_shard_bounds matches padded_layout's per-device
            # ownership) before contributing it to the global array — host
            # RAM and bind latency scale with the local shard, not the
            # corpus.  A loader that reads only its host's slice from disk
            # builds ShardedData directly instead (see
            # tests/test_multihost_2proc.py's host-local path).
            from distributed_sgd_tpu.data.host_shard import (
                dataset_reader,
                load_host_shard,
            )
            from distributed_sgd_tpu.parallel.multihost import host_shard_bounds

            start, end = host_shard_bounds(n_true, eval_chunk=self.eval_chunk)
            local = load_host_shard(
                dataset_reader(data), n_true, data.n_features,
                data.indices.shape[1], start, end,
                labels_dtype=data.labels.dtype)

            def put(arr, label=None):
                return jax.make_array_from_process_local_data(
                    sharding, arr, (total,) + arr.shape[1:]
                )
            # every process places its own rows and labels, as they come
            lanes = slot = None
        else:
            local = _pad_to_exact(data, total)
            platform = self.mesh.devices.flat[0].platform
            lanes = packed_width(local.indices.shape[1], platform)
            # the spare 32-bit word of a stored row the label rides in
            slot = label_slot(local.values.shape[1], lanes, self.model.n_outputs,
                              platform) if local.values.dtype == np.float32 else None

            def put(arr, label=None):
                return put_rows(arr, sharding, label=label)
        riding = None if slot is None else local.labels
        if lanes is not None:
            indices = put_packed(local.indices, local.values, lanes, sharding, label=riding)
            values = put(np.zeros((total, 0), np.float32))
        else:
            indices, values = put(local.indices), put(local.values, riding)
        lists = bool(data.n_labels)
        if lists and data.n_labels != self.model.n_outputs:
            raise ValueError(
                f"the rows list their labels among {data.n_labels} outputs, the model "
                f"has n_outputs={self.model.n_outputs}")
        # the kernel plan: its family's margins say how wide labels are stored
        plan = kernels.plan(
            self.model, learning_rate=self.learning_rate,
            optimizer=optimizer_kind(self.optimizer),
            row_width=data.indices.shape[1], virtual_workers=self.virtual_workers,
            batch_size=self.batch_size, n_workers=n_workers, eval_chunk=chunk, lists=lists,
            riding=slot is not None, kernel=self.kernel, device=self.mesh.devices.flat[0])
        sharded = ShardedData(
            indices=indices,
            values=values,
            # lists stay as narrow as they come: the readers expand them
            labels=(put_rows(local.labels, sharding, width=plan.lanes)
                    if plan.lanes and not lists else put(local.labels)),
            n_true=n_true,
            width=local.values.shape[1],
            packed=lanes is not None,
            label_slot=slot,
            label_lists=lists,
        )
        return self._bound(sharded, chunk, steps_per_epoch, plan)

    def bind_host_local(self, reader, n_samples: int, n_features: int,
                        pad_width: int,
                        steps_per_epoch: Optional[int] = None,
                        labels_dtype=None) -> BoundSync:
        """Multi-host bind WITHOUT the global corpus: each process hands in
        a row reader (data/host_shard.py RowReader) and loads ONLY its
        host_shard_bounds extent — real rows via one clipped read, padding
        rows as zeros — so no host ever materializes the full dataset
        (ROADMAP item 1 / VERDICT round 5; proven across 4 real processes
        in tests/test_multihost_4proc.py).  `pad_width=0` selects the
        dense layout (zero-width indices), mirroring Dataset.is_dense.

        `labels_dtype` must match the corpus on EVERY host (one dtype
        for the global array); None defaults to float32 for the dense
        layout (the regression path) and int32 otherwise — the loader
        raises on a lossy mismatch rather than truncating."""
        from distributed_sgd_tpu.parallel.multihost import host_local_sharded

        require_single_output(self.model, "the host-local multi-host bind")
        if labels_dtype is None:
            labels_dtype = np.float32 if pad_width == 0 else np.int32
        sharded, chunk = host_local_sharded(
            self.mesh, reader, n_samples, n_features, pad_width,
            eval_chunk=self.eval_chunk, labels_dtype=labels_dtype)
        return self._bound(sharded, chunk, steps_per_epoch)

    def _bound(self, sharded: ShardedData, chunk: int, steps_per_epoch: Optional[int],
               plan: Optional[kernels.Plan] = None) -> BoundSync:
        """`sharded` bound with this engine's settings (`plan`: the one
        `bind` made; None: the binding makes its own).  Spin-up fast path
        (compile_cache.py, DSGD_COMPILE_CACHE): the background AOT pass
        starts at bind time, so the fit's first epoch finds its XLA
        executable in the persistent cache."""
        bound = BoundSync(
            self.model, self.mesh, sharded, self.batch_size, self.learning_rate,
            sampling=self.sampling, steps_per_epoch=steps_per_epoch, eval_chunk=chunk,
            kernel=self.kernel, virtual_workers=self.virtual_workers,
            optimizer=self.optimizer, momentum=self.momentum, donate=self.donate, plan=plan)
        bound._maybe_warmup()
        return bound


def padded_layout(n_true: int, n_workers: int, eval_chunk: int = 4096) -> Tuple[int, int]:
    """(padded_total, chunk) for the engine's resident-dataset layout: each
    of the n_workers equal shards is padded to a multiple of the eval chunk
    so the chunked eval scan never reads out of range (pads carry label 0
    and are masked).  Multi-host loaders use this to reproduce per-device
    row ownership without materialising the global array (multihost.py)."""
    shard = math.ceil(n_true / n_workers)
    chunk = min(eval_chunk, shard)
    shard_padded = math.ceil(shard / chunk) * chunk
    return n_workers * shard_padded, chunk


def _pad_to_exact(data: Dataset, target: int) -> Dataset:
    rem = target - len(data)
    if rem < 0:
        raise ValueError("target smaller than dataset")
    if rem == 0:
        return data
    return Dataset(
        indices=np.concatenate(
            [data.indices, np.zeros((rem, data.indices.shape[1]), dtype=data.indices.dtype)]
        ),
        values=np.concatenate(
            [data.values, np.zeros((rem, data.values.shape[1]), dtype=data.values.dtype)]
        ),
        # the pad mask: label 0, or a list that says "no row"
        labels=np.concatenate(
            [data.labels, np.full((rem,) + data.labels.shape[1:],
                                  LIST_NO_ROW if data.n_labels else 0, data.labels.dtype)]),
        n_features=data.n_features,
        n_labels=data.n_labels,
    )
