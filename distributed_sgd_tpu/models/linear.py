"""Linear model family on sparse batches: hinge SVM, logistic, squared
hinge, least squares.

``SparseSVM`` reproduces the reference model exactly, sign quirks included
(core/ml/SparseSVM.scala:14-31):

- ``forward(w, x) = signum(x . w) * (-1)``            (SparseSVM.scala:14)
- ``loss(pred, y) = max(0, 1 - y * pred)``            (SparseSVM.scala:16)
- objective ``lambda * ||w||^2 + mean sample loss``   (SparseSVM.scala:20-23)
- subgradient ``backward(w,x,y) = 0 if y*(x.w) < 0 else y*x``
                                                      (SparseSVM.scala:26-29)
- ``regularize(g, w) = g + 1[g != 0] * (lambda*2*(w . dimSparsity))``
                                                      (SparseSVM.scala:31)

The `1[g != 0]` factor mirrors `Vec.valueLike`: the reference adds the
scalar only at the sparse gradient's stored keys (Vec.scala:60-75), and
Sparse construction drops |x| <= 1e-20 entries (Sparse.scala:104-114), so
"stored keys" == "nonzero after summation" — which `g != 0` reproduces.

Known reference quirk NOT reproduced: the reference's dimSparsity vector is
built on 0-based indices while data vectors keep the file's 1-based feature
ids (Main.scala:54-65 `buff(idx - 1)` vs Dataset.scala:24-33), so its
`w . dimSparsity` mixes shifted coordinates.  We index consistently
(0-based everywhere); the regularizer magnitude is unchanged to first
order.  Documented here so the parity delta is a known quantity.

All models share the structure: per-sample gradient = coeff(margin, y) * x,
so a whole-batch gradient is one `scatter_add` — the design that lets the
entire backward pass compile to gather + elementwise + segment-sum on TPU,
replacing the reference's per-sample boxed map loop (Slave.scala:147-152).

LogisticRegression and LeastSquares are documented capability supersets
(BASELINE.md configs 3 and 5; the reference ships hinge only), as is
SquaredHinge (LIBLINEAR's L2-loss SVC, what DiSMEC fits a label).

Labels come as `y[B]`, as `y[B, C]` with an output axis, or as ID LISTS
(`Dataset.n_labels`: a row's positive ids among the C outputs, int32
[B, Lw]), which `expand_labels` turns into the `[B, C]` rows of +1 / -1 / 0
that every loss here takes: the one place that knows the list format.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_sgd_tpu.data.rcv1 import LIST_NO_ROW
from distributed_sgd_tpu.ops import gather, kernels, mxu
from distributed_sgd_tpu.ops.sparse import SparseBatch, matvec, scatter_add

REGULARIZERS = ("dim_sparsity", "l2", "none")


def expand_labels(lists: jax.Array, n_outputs: int, width: int) -> jax.Array:
    """Label lists `int32[..., Lw]` (a row's positive ids among `n_outputs`
    outputs; negative ids are empty slots, LIST_NO_ROW in the first slot a
    padding row) as the labels the losses take, `f32[..., width]`: +1 at a
    listed id, -1 at every other output, 0 (the pad mask) on the lanes past
    `n_outputs` and on all of a padding row.  Lw compares a (row, lane)
    pair on the VPU; nothing is gathered or scattered."""
    with jax.named_scope("dsgd.labels"):
        lane = jax.lax.broadcasted_iota(
            jnp.int32, lists.shape[:-1] + (width,), lists.ndim - 1)
        listed = lane == lists[..., :1]
        for slot in range(1, lists.shape[-1]):
            listed = listed | (lane == lists[..., slot:slot + 1])
        counts = (lane < n_outputs) & (lists[..., :1] != LIST_NO_ROW)
        return jnp.where(counts, jnp.where(listed, 1.0, -1.0), 0.0).astype(jnp.float32)


class LinearModel:
    """Shared machinery: margins, batched gradients, regularization.

    Subclasses define `predict(margins)`, `sample_loss(preds, y)` and
    `grad_coeff(margins, y)` as pure jnp functions.  `regularizer` is one of
    'dim_sparsity' (reference parity), 'l2' (standard 2*lam*w), 'none'.

    `n_outputs` = C > 1 is C such models over the same rows at once (one
    label a row and output, `y[B, C]` in {-1, +1}): weights `W[D, C]`,
    margins `[B, C]`, the objective `lam ||W||_F^2 + mean over rows of the
    SUM over outputs of the loss`, accuracy over (row, output) pairs.
    Nothing couples the columns: column c of every result is the C = 1
    model on `y[:, c]`.  C = 1 keeps the flat `w[D]` and `y[B]`.
    """

    def __init__(
        self,
        lam: float,
        n_features: int,
        dim_sparsity: Optional[jax.Array] = None,
        regularizer: str = "dim_sparsity",
        n_outputs: int = 1,
    ):
        if regularizer not in REGULARIZERS:
            raise ValueError(
                f"unknown regularizer {regularizer!r}; choose from {REGULARIZERS}")
        if n_outputs < 1:
            raise ValueError(f"n_outputs must be >= 1, got {n_outputs}")
        if n_outputs > 1 and regularizer == "dim_sparsity":
            # its mask is a reply's own support, per (feature, output):
            # one accumulator a worker and output, which no engine builds
            raise ValueError(
                "the 'dim_sparsity' regulariser masks by one gradient's support and "
                f"has no form with n_outputs={n_outputs}; choose 'l2' or 'none'")
        self.lam = float(lam)
        self.n_features = int(n_features)
        self.n_outputs = int(n_outputs)
        self.regularizer = regularizer
        if regularizer == "dim_sparsity":
            if dim_sparsity is None:
                raise ValueError("dim_sparsity regularizer needs the dim_sparsity vector")
            self.dim_sparsity = jnp.asarray(dim_sparsity, dtype=jnp.float32)
        else:
            self.dim_sparsity = None

    @property
    def weight_shape(self) -> tuple:
        """The flat weights' shape: [D], or [D, C] with an output axis."""
        if self.n_outputs == 1:
            return (self.n_features,)
        return (self.n_features, self.n_outputs)

    # -- abstract ----------------------------------------------------------
    def predict(self, margins: jax.Array) -> jax.Array:
        raise NotImplementedError

    def sample_loss(self, preds: jax.Array, y: jax.Array) -> jax.Array:
        raise NotImplementedError

    def grad_coeff(self, margins: jax.Array, y: jax.Array) -> jax.Array:
        raise NotImplementedError

    # -- shared: the one dispatch on the kernel family (ops/kernels.py) -----
    #
    # `kernel` names the family and with it the layout `w` is in: flat [D]
    # for 'scalar' and 'dense', lane-blocked [R, 128] for 'mxu' and 'gather'
    # (`to_layout` / `from_layout`).  Engines carry the layout across their
    # compiled loops and call `margins` and `grad` in it; callers that hold
    # flat weights call `grad_regularized`.  Dense-layout batches take the
    # plain products whatever `kernel` says.  With an output axis the flat
    # layout is [D, C] and 'gather' keeps one row a FEATURE, the outputs on
    # its lanes (ops/gather.py `to_rows`: [D', L], margins and labels [B, L],
    # pad lanes zero); the one-hot family has no such form.

    def check_kernel(self, kernel: str) -> None:
        """Refuse the family that has no form with this model's outputs."""
        if self.n_outputs > 1 and kernel == "mxu":
            raise ValueError(
                "the one-hot family ('mxu') pays R x 128 x n_outputs MACs a stored "
                "entry and carries no output axis; 'gather', 'scalar' and 'dense' do")

    def to_layout(self, w: jax.Array, kernel: str) -> jax.Array:
        if self.n_outputs > 1:
            self.check_kernel(kernel)
            return gather.to_rows(w) if kernel == "gather" else w
        return mxu.to_blocked(w, self.n_features) if kernel in kernels.BLOCKED else w

    def from_layout(self, w: jax.Array, kernel: str) -> jax.Array:
        if self.n_outputs > 1:
            return (gather.from_rows(w, self.n_features, self.n_outputs)
                    if kernel == "gather" else w)
        return mxu.from_blocked(w, self.n_features) if kernel in kernels.BLOCKED else w

    def margins(self, w: jax.Array, batch: SparseBatch, kernel: str = "scalar",
                fetch: Optional[kernels.Fetch] = None, plan=None) -> jax.Array:
        """Per-sample dots x_b . w, `w` in `kernel`'s layout (`fetch`: how
        rows of outputs are read, `_rows_margins`; `plan`: the batch's
        `gather.PiecePlan` where `fetch` is 'planned')."""
        if batch.is_dense:
            return self.margins_dense(w, batch.values)
        if kernel == "gather" and self.n_outputs > 1:
            return self._rows_margins(batch, w, fetch, plan)
        if kernel == "gather":
            return gather.matvec(batch, w)
        if kernel in kernels.BLOCKED:
            return mxu.matvec_chunked(batch, w)
        with jax.named_scope("dsgd.margins"):
            return matvec(batch, w)

    def grad(self, w: jax.Array, batch: SparseBatch, y: jax.Array,
             kernel: str = "scalar", reduce: str = "sum") -> jax.Array:
        """One worker's regularised gradient (backward reduce + regularize,
        Slave.scala:142-157), `w` and the result in `kernel`'s layout.
        reduce='sum' is the sync reply (Slave.scala:147-153), 'mean' the
        async local step (Slave.scala:93-98)."""
        if batch.is_dense:
            return self.regularize(self.grad_dense(w, batch.values, y, reduce=reduce), w)
        if kernel in kernels.BLOCKED:
            g = self.grad_blocked(w, batch, y, reduce=reduce, kernel=kernel)
            return self.regularize_blocked(g, w)
        g = self.grad_sum(w, batch, y) if reduce == "sum" else self.grad_mean(w, batch, y)
        return self.regularize(g, w)

    def grad_workers(self, w: jax.Array, indices: jax.Array, values: jax.Array,
                     y: jax.Array, kernel: str = "scalar") -> jax.Array:
        """The SUM over K workers of their sync replies (`grad`, reduce
        'sum'), for batches stacked [K, B, P] / [K, B]: what a device that
        holds K virtual workers hands the all-reduce (Master.scala:194's
        mean divides it later).

        The K workers share `w`, so their MARGINS are one call on the merged
        batch [K*B, P] (`kernels.merges_margins`): batched over the workers
        by `vmap` the one-hot gather compiles to K thin matmuls one after
        the other, each too small for the layout in which the chip runs it
        at 0.65 ns an entry and not at 1.2-1.9 (`mxu.lane_minor_rows`;
        PERF.md section 6, PR 27).  A gathered product has one non-zero
        term, so the margins are the per-worker ones bit for bit.  The
        REPLIES stay apart where 'dim_sparsity' masks each by its worker's
        own support: coefficient, scatter and regulariser per worker, then
        the sum.  A family whose
        scatter walks its entries (`kernels.ONE_ACCUMULATOR`) under a
        regulariser that is linear in the gradient ('l2', 'none') merges
        the scatter too, which sums the same terms in another order: one
        scatter of all K batches into ONE accumulator, plus K times the
        regulariser's term — no [K, R, 128] of replies to zero, fill and
        reduce (16 MB a step at D = 1e6; PERF.md section 6, PR 26).

        This is the family's step under `kernels.SPARSE_UPDATE_MIN_FEATURES`
        and wherever a whole gradient is read ('dim_sparsity', an optax
        optimizer).  From that many features on, with the reference update,
        `BoundSync` builds NO accumulator: it takes the same entries from
        `reply_entries` and scatters them into the carried weights
        (`kernels.sparse_update`; PERF.md section 6, PR 30)."""
        k, b = y.shape[:2]  # y [K, B], or [K, B, C] with an output axis
        if kernels.merges_margins(kernel, indices.shape[-1]):
            merged = SparseBatch(indices.reshape(k * b, -1), values.reshape(k * b, -1))
            if kernel in kernels.ONE_ACCUMULATOR and self.regularizer != "dim_sparsity":
                g = self.grad_blocked(w, merged, y.reshape((k * b,) + y.shape[2:]),
                                      kernel=kernel)
                return self.regularize_blocked(g, w, workers=k)
            # one piece: matvec_chunked's sub-scan would bring the calls back
            matvec = gather.matvec if kernel == "gather" else mxu.matvec
            gk = jax.vmap(
                lambda bi, bv, by, m: self.regularize_blocked(self.grad_blocked(
                    w, SparseBatch(bi, bv), by, kernel=kernel, margins=m), w)
            )(indices, values, y, matvec(merged, w).reshape(k, b))
        else:
            gk = jax.vmap(
                lambda bi, bv, by: self.grad(w, SparseBatch(bi, bv), by, kernel=kernel)
            )(indices, values, y)
        with jax.named_scope("dsgd.allreduce"):
            return jnp.sum(gk, axis=0)  # summed here, mean-normalized by the caller

    def reply_entries(self, v2: jax.Array, batch: SparseBatch, y: jax.Array,
                      scale: Optional[jax.Array] = None, factor=1.0, matvec=gather.matvec):
        """The sync replies of the workers whose batches `batch` merges,
        without their regulariser term, as ENTRIES: (flat feature ids [T],
        `factor` x coefficient x value [T]).  Scattered into a zeroed
        accumulator they are `grad_blocked`'s sum; a binding that
        `kernels.sparse_update` names never builds that gradient and
        scatters them into the carried weights (`BoundSync._sparse_step`),
        after exchanging them as entries where it has more than one device.
        The blocked weights are `scale * v2` (None: `v2` itself): a gathered
        margin is linear in them, so the scalar goes on the margins.
        `matvec(batch, v2)`: the margins, where the weights are a function
        of the state `v2` holds (FTRL's closed form of (z, n),
        ops/ftrl.py `matvec`) and not `v2` itself."""
        margins = matvec(batch, v2)
        with jax.named_scope("dsgd.update"):
            if scale is not None:
                margins = scale * margins
        with jax.named_scope("dsgd.coeff"):
            coeff = self.grad_coeff(margins, y) * factor
        with jax.named_scope("dsgd.scatter"):
            cv = batch.values.astype(jnp.float32) * coeff.astype(jnp.float32)[:, None]
        return batch.indices.reshape(-1), cv.reshape(-1)

    def reply_rows(self, v2: jax.Array, batch: SparseBatch, y: jax.Array,
                   scale: Optional[jax.Array] = None, factor=1.0,
                   fetch: Optional[kernels.Fetch] = None):
        """`reply_entries` with an output axis (`v2 [D', L]`, `y [B, L]`):
        an entry's update is a whole row, `value x coeff[sample]`, and is
        handed on as its factors: (feature ids [T], values [T], the sample
        of every entry [T], `factor` x the samples' coefficient rows
        [B, L]), what `gather.scatter_rows_into` takes.  `fetch`: the
        margins as `margins` takes them."""
        margins = self._rows_margins(batch, v2, fetch)
        with jax.named_scope("dsgd.update"):
            if scale is not None:
                margins = scale * margins
        with jax.named_scope("dsgd.coeff"):
            coeff = self.grad_coeff(margins, y) * factor
        with jax.named_scope("dsgd.scatter"):
            src = jax.lax.broadcasted_iota(jnp.int32, batch.indices.shape, 0)
        return (batch.indices.reshape(-1), batch.values.astype(jnp.float32).reshape(-1),
                src.reshape(-1), coeff.astype(jnp.float32))

    def _rows_margins(self, batch: SparseBatch, w2: jax.Array, fetch=None,
                      plan=None) -> jax.Array:
        """`gather.matvec_rows` as a binding's plan says (`fetch`), else XLA's
        gather in the pieces `kernels.margin_rows` gives this call's shape."""
        lanes = int(np.prod(w2.shape[1:]))
        return gather.matvec_rows(batch, w2, *fetch or (
            "gather", kernels.margin_rows(*batch.indices.shape, lanes)), plan=plan)

    def sample_losses(self, w: jax.Array, batch: SparseBatch, y: jax.Array) -> jax.Array:
        """Per-sample losses (no regularization term), vectorized."""
        return self.losses_from_margins(self.margins(w, batch), y)

    def losses_from_margins(self, margins: jax.Array, y: jax.Array) -> jax.Array:
        """Per-sample losses given precomputed margins — lets eval paths
        compute margins with whichever kernel fits the weight layout."""
        return self.sample_loss(self.predict(margins), y)

    def forward(self, w: jax.Array, batch: SparseBatch) -> jax.Array:
        return self.predict(self.margins(w, batch))

    def objective(self, w: jax.Array, batch: SparseBatch, y: jax.Array) -> jax.Array:
        """lambda*||w||^2 + mean sample loss (SparseSVM.scala:20-23)."""
        preds = self.forward(w, batch)
        reg = self.lam * jnp.sum(w.astype(jnp.float32) ** 2)
        return reg + jnp.mean(_per_row(self.sample_loss(preds, y)))

    def accuracy(self, w: jax.Array, batch: SparseBatch, y: jax.Array) -> jax.Array:
        """fraction(forward == y) (Master.scala:98-101)."""
        return jnp.mean((self.forward(w, batch) == y.astype(jnp.float32)).astype(jnp.float32))

    def grad_sum(self, w: jax.Array, batch: SparseBatch, y: jax.Array) -> jax.Array:
        """Sum of per-sample backward over the batch (Slave.scala:147-153)."""
        if batch.is_dense:
            return self.grad_dense(w, batch.values, y, reduce="sum")
        margins = self.margins(w, batch)
        with jax.named_scope("dsgd.coeff"):
            coeff = self.grad_coeff(margins, y)
        with jax.named_scope("dsgd.scatter"):
            return scatter_add(batch, coeff, self.n_features)

    def grad_mean(self, w: jax.Array, batch: SparseBatch, y: jax.Array) -> jax.Array:
        """Mean of per-sample backward (async path, Slave.scala:93-98)."""
        g = self.grad_sum(w, batch, y)
        with jax.named_scope("dsgd.scatter"):
            return g / batch.batch_size

    # -- dense fast path ----------------------------------------------------
    #
    # When rows are fully dense (Dataset.dense layout: values[B, D], no
    # index array), gather/scatter degenerate to plain matmuls — the shape
    # the MXU was built for.  Same math as the sparse kernels on a row
    # whose indices are arange(D) (BASELINE.md config 5).

    def margins_dense(self, w: jax.Array, x: jax.Array) -> jax.Array:
        """Per-sample dots for dense rows: x[B, D] @ w[D].

        Precision HIGHEST keeps f32 products on TPU (default matmul
        precision would truncate operands to bf16), preserving the
        invariant that every kernel backend produces identical updates up
        to float summation order (sync.py docstring)."""
        with jax.named_scope("dsgd.margins"):
            return jnp.dot(
                x.astype(jnp.float32), w.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )

    def grad_dense(
        self, w: jax.Array, x: jax.Array, y: jax.Array, reduce: str = "sum"
    ) -> jax.Array:
        """Batched backward for dense rows: coeff[B] @ x[B, D] — one MXU
        matmul replacing gather + scatter (Slave.scala:147-153 semantics);
        with an output axis x^T[D, B] @ coeff[B, C]."""
        margins = self.margins_dense(w, x)
        with jax.named_scope("dsgd.coeff"):
            coeff = self.grad_coeff(margins, y)
            if reduce == "mean":
                coeff = coeff / x.shape[0]
        with jax.named_scope("dsgd.scatter"):
            if coeff.ndim == 2:
                return jnp.dot(
                    x.astype(jnp.float32).T, coeff.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                )
            return jnp.dot(
                coeff.astype(jnp.float32), x.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )

    def regularize(self, grad: jax.Array, w: jax.Array) -> jax.Array:
        """SparseSVM.scala:31 semantics (see module docstring)."""
        with jax.named_scope("dsgd.regularize"):
            if self.regularizer == "dim_sparsity":
                scalar = self.lam * 2.0 * jnp.dot(
                    w.astype(jnp.float32), self.dim_sparsity
                )
                return grad + jnp.where(grad != 0, scalar, 0.0)
            if self.regularizer == "l2":
                return grad + 2.0 * self.lam * w
            return grad

    # -- blocked (MXU one-hot) fast path -----------------------------------
    #
    # Same math on the [R, 128] lane-blocked weight view (ops/mxu.py):
    # the training engines keep weights blocked across their compiled scans
    # and convert at the jit boundary.  Semantics match the scalar path
    # bit-for-bit up to float summation order.

    @property
    def dim_sparsity_blocked(self) -> Optional[jax.Array]:
        if self.dim_sparsity is None:
            return None
        if not hasattr(self, "_ds_blocked_np"):
            # cache the HOST array; the jnp conversion must happen inside
            # each trace (caching a traced array would leak the tracer)
            self._ds_blocked_np = mxu.to_blocked_np(
                np.asarray(self.dim_sparsity), self.n_features
            )
        return jnp.asarray(self._ds_blocked_np)

    def margins_blocked(self, w2: jax.Array, batch: SparseBatch) -> jax.Array:
        return mxu.matvec(batch, w2)

    def grad_blocked(
        self, w2: jax.Array, batch: SparseBatch, y: jax.Array, reduce: str = "sum",
        kernel: str = "mxu", margins: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Batched backward on blocked weights: gather + coeff + scatter, as
        one-hot matmuls (ops/mxu.py) or, for kernel='gather', as a true
        gather and scatter (ops/gather.py).  `margins`: the batch's, where
        the caller has them already (`grad_workers` computes every worker's
        in one call); the gather side is then not run.

        reduce='sum' is the sync worker reply (Slave.scala:147-153);
        reduce='mean' is the async local step (Slave.scala:93-98).
        """
        if kernel == "gather" and self.n_outputs > 1:  # rows of outputs
            scatter = functools.partial(gather.scatter_add_rows, batch, shape=w2.shape)
            if margins is None:
                margins = self._rows_margins(batch, w2)
        elif kernel == "gather":
            scatter = functools.partial(gather.scatter_add, batch, n_rows=w2.shape[0])
            if margins is None:
                margins = gather.matvec(batch, w2)
        else:
            oh = mxu.OneHotBatch(batch, w2.shape[0])
            scatter = oh.scatter_add
            if margins is None:
                margins = oh.margins(w2)
        with jax.named_scope("dsgd.coeff"):
            coeff = self.grad_coeff(margins, y)
            if reduce == "mean":
                coeff = coeff / batch.batch_size
        return scatter(coeff)

    def grad_regularized(
        self,
        w: jax.Array,
        batch: SparseBatch,
        y: jax.Array,
        reduce: str = "sum",
        kernel: str = "scalar",
    ) -> jax.Array:
        """`grad` for callers that hold FLAT weights: flat [D] in, flat [D]
        out, through `kernel`'s layout in between.  Engines that carry a
        layout across a scan call `grad` directly instead."""
        if batch.is_dense:
            kernel = "dense"
        g = self.grad(self.to_layout(w, kernel), batch, y, kernel=kernel, reduce=reduce)
        return self.from_layout(g, kernel)

    def regularize_blocked(self, g2: jax.Array, w2: jax.Array,
                           workers: int = 1) -> jax.Array:
        """`regularize` on the blocked view; zero pad lanes stay zero
        because the scalar is only added where g2 != 0.  `workers` > 1:
        `g2` is the sum of that many workers' gradients and gets that many
        times the term (`grad_workers`; linear regularisers only)."""
        with jax.named_scope("dsgd.regularize"):
            if self.regularizer == "dim_sparsity":
                if workers != 1:
                    raise ValueError("dim_sparsity masks by each worker's own support")
                scalar = self.lam * 2.0 * jnp.sum(
                    w2.astype(jnp.float32) * self.dim_sparsity_blocked
                )
                return g2 + jnp.where(g2 != 0, scalar, 0.0)
            if self.regularizer == "l2":
                return g2 + 2.0 * self.lam * workers * w2
            return g2


class SparseSVM(LinearModel):
    """Reference-exact hinge model (see module docstring)."""

    def predict(self, margins: jax.Array) -> jax.Array:
        # signum(x.w) * -1  (SparseSVM.scala:14); preds in {-1, 0, +1}
        return jnp.sign(margins) * -1.0

    def sample_loss(self, preds: jax.Array, y: jax.Array) -> jax.Array:
        return jnp.maximum(0.0, 1.0 - y.astype(jnp.float32) * preds)

    def grad_coeff(self, margins: jax.Array, y: jax.Array) -> jax.Array:
        # backward = 0 if y*(x.w) < 0 else y*x  (SparseSVM.scala:26-29)
        yf = y.astype(jnp.float32)
        activity = yf * margins
        return jnp.where(activity < 0, 0.0, yf)


class _MarginLoss(LinearModel):
    """A loss of the margin y m itself, not of a prediction: the sign of
    the margin predicts, subclasses define `losses_from_margins`."""

    def predict(self, margins: jax.Array) -> jax.Array:
        return jnp.where(margins >= 0, 1.0, -1.0)

    def sample_loss(self, preds: jax.Array, y: jax.Array) -> jax.Array:
        del preds  # the loss is margin-based; see losses_from_margins
        raise NotImplementedError("use losses_from_margins()/objective()")

    def objective(self, w: jax.Array, batch: SparseBatch, y: jax.Array) -> jax.Array:
        reg = self.lam * jnp.sum(w.astype(jnp.float32) ** 2)
        return reg + jnp.mean(_per_row(self.sample_losses(w, batch, y)))


class LogisticRegression(_MarginLoss):
    """Binary logistic loss on +/-1 labels (superset; BASELINE.md config 3)."""

    def losses_from_margins(self, margins: jax.Array, y: jax.Array) -> jax.Array:
        yf = y.astype(jnp.float32)
        return jnp.logaddexp(0.0, -yf * margins)  # log(1 + exp(-y m)), stable

    def grad_coeff(self, margins: jax.Array, y: jax.Array) -> jax.Array:
        yf = y.astype(jnp.float32)
        return -yf * jax.nn.sigmoid(-yf * margins)


class SquaredHinge(_MarginLoss):
    """max(0, 1 - y m)^2 on +/-1 labels: the l2-regularised L2-loss SVM of
    LIBLINEAR's primal solver, which DiSMEC (Babbar & Schoelkopf, WSDM 2017)
    fits a label.  Its derivative is continuous at the kink (y m = 1), so a
    rounding there moves a coefficient by the rounding and flips nothing."""

    def losses_from_margins(self, margins: jax.Array, y: jax.Array) -> jax.Array:
        return jnp.maximum(0.0, 1.0 - y.astype(jnp.float32) * margins) ** 2

    def grad_coeff(self, margins: jax.Array, y: jax.Array) -> jax.Array:
        yf = y.astype(jnp.float32)  # 0 (a pad) gives 0
        return -2.0 * yf * jnp.maximum(0.0, 1.0 - yf * margins)


class LeastSquares(LinearModel):
    """Squared-error regression (superset; BASELINE.md config 5)."""

    def predict(self, margins: jax.Array) -> jax.Array:
        return margins

    def sample_loss(self, preds: jax.Array, y: jax.Array) -> jax.Array:
        return (preds - y.astype(jnp.float32)) ** 2

    def grad_coeff(self, margins: jax.Array, y: jax.Array) -> jax.Array:
        return 2.0 * (margins - y.astype(jnp.float32))

    def accuracy(self, w: jax.Array, batch: SparseBatch, y: jax.Array) -> jax.Array:
        # accuracy is meaningless for regression; report negative MSE
        preds = self.forward(w, batch)
        return -jnp.mean((preds - y.astype(jnp.float32)) ** 2)


def _per_row(losses: jax.Array) -> jax.Array:
    """A row's loss: with an output axis the SUM over its outputs."""
    return losses if losses.ndim == 1 else jnp.sum(losses, axis=-1)


def _single_output_only(who: str, n_outputs: int) -> ValueError:
    return ValueError(
        f"{who} carries one flat weight vector w[n_features]; a model with "
        f"n_outputs={n_outputs} fits through SyncTrainer.fit (the mesh sync "
        f"engine) only")


def require_single_output(model: "LinearModel", who: str) -> None:
    """The ONE refusal of every engine that carries one flat weight vector
    (Hogwild, local SGD, the rpc master and workers and with them their
    wire and its compression, feature sharding, the multi-host binds): a
    model with an output axis trains and evaluates through the mesh sync
    engine alone."""
    if getattr(model, "n_outputs", 1) != 1:
        raise _single_output_only(who, model.n_outputs)


def require_flat_weights(weights, who: str) -> None:
    """The same refusal where only the weights are seen (serving a
    checkpoint a fit with an output axis wrote)."""
    if np.ndim(weights) != 1:
        raise _single_output_only(who, np.shape(weights)[-1])


def make_model(
    name: str,
    lam: float,
    n_features: int,
    dim_sparsity: Optional[jax.Array] = None,
    regularizer: Optional[str] = None,
    n_outputs: int = 1,
) -> LinearModel:
    kinds = {
        "hinge": SparseSVM,
        "svm": SparseSVM,
        "logistic": LogisticRegression,
        "squared_hinge": SquaredHinge,
        "least_squares": LeastSquares,
    }
    if name not in kinds:
        raise ValueError(f"unknown model {name!r}; choose from {sorted(kinds)}")
    if regularizer is None:
        regularizer = "dim_sparsity" if dim_sparsity is not None else "l2"
    return kinds[name](lam, n_features, dim_sparsity=dim_sparsity, regularizer=regularizer,
                       n_outputs=n_outputs)
