"""Linear model family on sparse batches: hinge SVM, logistic, least squares.

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
(BASELINE.md configs 3 and 5; the reference ships hinge only).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_sgd_tpu.ops import mxu
from distributed_sgd_tpu.ops.sparse import SparseBatch, matvec, scatter_add


class LinearModel:
    """Shared machinery: margins, batched gradients, regularization.

    Subclasses define `predict(margins)`, `sample_loss(preds, y)` and
    `grad_coeff(margins, y)` as pure jnp functions.  `regularizer` is one of
    'dim_sparsity' (reference parity), 'l2' (standard 2*lam*w), 'none'.
    """

    def __init__(
        self,
        lam: float,
        n_features: int,
        dim_sparsity: Optional[jax.Array] = None,
        regularizer: str = "dim_sparsity",
    ):
        self.lam = float(lam)
        self.n_features = int(n_features)
        self.regularizer = regularizer
        if regularizer == "dim_sparsity":
            if dim_sparsity is None:
                raise ValueError("dim_sparsity regularizer needs the dim_sparsity vector")
            self.dim_sparsity = jnp.asarray(dim_sparsity, dtype=jnp.float32)
        else:
            self.dim_sparsity = None

    # -- abstract ----------------------------------------------------------
    def predict(self, margins: jax.Array) -> jax.Array:
        raise NotImplementedError

    def sample_loss(self, preds: jax.Array, y: jax.Array) -> jax.Array:
        raise NotImplementedError

    def grad_coeff(self, margins: jax.Array, y: jax.Array) -> jax.Array:
        raise NotImplementedError

    # -- shared ------------------------------------------------------------
    def margins(self, w: jax.Array, batch: SparseBatch) -> jax.Array:
        if batch.is_dense:
            return self.margins_dense(w, batch.values)
        with jax.named_scope("dsgd.margins"):
            return matvec(batch, w)

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
        return reg + jnp.mean(self.sample_loss(preds, y))

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
        matmul replacing gather + scatter (Slave.scala:147-153 semantics)."""
        margins = self.margins_dense(w, x)
        with jax.named_scope("dsgd.coeff"):
            coeff = self.grad_coeff(margins, y)
            if reduce == "mean":
                coeff = coeff / x.shape[0]
        with jax.named_scope("dsgd.scatter"):
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
        self, w2: jax.Array, batch: SparseBatch, y: jax.Array, reduce: str = "sum"
    ) -> jax.Array:
        """Batched backward on blocked weights: one fused gather + coeff +
        scatter with the one-hot operands built once (ops/mxu.py).

        reduce='sum' is the sync worker reply (Slave.scala:147-153);
        reduce='mean' is the async local step (Slave.scala:93-98).
        """
        oh = mxu.OneHotBatch(batch, w2.shape[0])
        margins = oh.margins(w2)
        with jax.named_scope("dsgd.coeff"):
            coeff = self.grad_coeff(margins, y)
            if reduce == "mean":
                coeff = coeff / batch.batch_size
        return oh.scatter_add(coeff)

    def grad_regularized(
        self,
        w: jax.Array,
        batch: SparseBatch,
        y: jax.Array,
        reduce: str = "sum",
        blocked: bool = False,
    ) -> jax.Array:
        """Dense-in/dense-out worker gradient (backward reduce + regularize,
        Slave.scala:142-157): one entry point for callers that hold dense
        weights, routed through the blocked MXU kernels when `blocked`.
        Engines that carry blocked weights across a scan call the blocked
        methods directly instead.  Dense-layout batches route to the
        plain-matmul fast path regardless of `blocked`."""
        if batch.is_dense:
            g = self.grad_dense(w, batch.values, y, reduce=reduce)
            return self.regularize(g, w)
        if blocked:
            w2 = mxu.to_blocked(w, self.n_features)
            g2 = self.grad_blocked(w2, batch, y, reduce=reduce)
            return mxu.from_blocked(self.regularize_blocked(g2, w2), self.n_features)
        g = self.grad_sum(w, batch, y) if reduce == "sum" else self.grad_mean(w, batch, y)
        return self.regularize(g, w)

    def regularize_blocked(self, g2: jax.Array, w2: jax.Array) -> jax.Array:
        """`regularize` on the blocked view; zero pad lanes stay zero
        because the scalar is only added where g2 != 0."""
        with jax.named_scope("dsgd.regularize"):
            if self.regularizer == "dim_sparsity":
                scalar = self.lam * 2.0 * jnp.sum(
                    w2.astype(jnp.float32) * self.dim_sparsity_blocked
                )
                return g2 + jnp.where(g2 != 0, scalar, 0.0)
            if self.regularizer == "l2":
                return g2 + 2.0 * self.lam * w2
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


class LogisticRegression(LinearModel):
    """Binary logistic loss on +/-1 labels (superset; BASELINE.md config 3)."""

    def predict(self, margins: jax.Array) -> jax.Array:
        return jnp.where(margins >= 0, 1.0, -1.0)

    def sample_loss(self, preds: jax.Array, y: jax.Array) -> jax.Array:
        del preds  # logistic loss is margin-based; see losses_from_margins
        raise NotImplementedError("use losses_from_margins()/objective()")

    def losses_from_margins(self, margins: jax.Array, y: jax.Array) -> jax.Array:
        yf = y.astype(jnp.float32)
        return jnp.logaddexp(0.0, -yf * margins)  # log(1 + exp(-y m)), stable

    def objective(self, w: jax.Array, batch: SparseBatch, y: jax.Array) -> jax.Array:
        reg = self.lam * jnp.sum(w.astype(jnp.float32) ** 2)
        return reg + jnp.mean(self.sample_losses(w, batch, y))

    def grad_coeff(self, margins: jax.Array, y: jax.Array) -> jax.Array:
        yf = y.astype(jnp.float32)
        return -yf * jax.nn.sigmoid(-yf * margins)


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


def make_model(
    name: str,
    lam: float,
    n_features: int,
    dim_sparsity: Optional[jax.Array] = None,
    regularizer: Optional[str] = None,
) -> LinearModel:
    kinds = {
        "hinge": SparseSVM,
        "svm": SparseSVM,
        "logistic": LogisticRegression,
        "least_squares": LeastSquares,
    }
    if name not in kinds:
        raise ValueError(f"unknown model {name!r}; choose from {sorted(kinds)}")
    if regularizer is None:
        regularizer = "dim_sparsity" if dim_sparsity is not None else "l2"
    return kinds[name](lam, n_features, dim_sparsity=dim_sparsity, regularizer=regularizer)
