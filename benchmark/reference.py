"""The plain reference: the models' equations in straightforward jax.numpy.

float32 under `jax.default_matmul_precision("highest")` (on a TPU a float32
product otherwise runs in bf16 passes), take / multiply / sum for the
margins, `segment_sum` for the scatter.  No one-hot operands, no blocked
weight view, no chunked scans, and nothing imported from the program: this
file is what `correct` compares the system against.

Equations (zifeo/distributed-sgd, core/ml/SparseSVM.scala:14-31, and the
standard logistic model for the dense configuration):

  margin            m = x . w
  hinge   predict   p = -sign(m)                      (the reference's sign quirk)
          loss      max(0, 1 - y*p)
          backward  0 if y*m < 0 else y*x
  logistic predict  +1 if m >= 0 else -1
          loss      log(1 + exp(-y*m))
          backward  -y * sigmoid(-y*m) * x
  objective         lam*||w||^2 + mean loss
  regularize        dim_sparsity: g + 1[g != 0] * 2*lam*(w . dim_sparsity)
                    l2:           g + 2*lam*w
  sync worker reply regularize(SUM of backward over the batch)
  async local step  regularize(MEAN of backward over the batch)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"


def margins(w, idx, val):
    """x . w per row; `idx` None or zero-width means dense rows."""
    with jax.default_matmul_precision(HIGHEST):
        w = w.astype(jnp.float32)
        val = val.astype(jnp.float32)
        if idx is None or idx.shape[-1] == 0:
            return jnp.sum(val * w[None, :], axis=-1)
        return jnp.sum(val * jnp.take(w, idx, axis=0), axis=-1)


def predict(loss: str, m):
    if loss == "hinge":
        return -jnp.sign(m)
    if loss == "logistic":
        return jnp.where(m >= 0, 1.0, -1.0)
    raise ValueError(f"no reference for loss {loss!r}")


def sample_losses(loss: str, m, y):
    yf = y.astype(jnp.float32)
    if loss == "hinge":
        return jnp.maximum(0.0, 1.0 - yf * predict(loss, m))
    if loss == "logistic":
        return jnp.logaddexp(0.0, -yf * m)
    raise ValueError(f"no reference for loss {loss!r}")


def backward_coeff(loss: str, m, y):
    yf = y.astype(jnp.float32)
    if loss == "hinge":
        return jnp.where(yf * m < 0, 0.0, yf)
    if loss == "logistic":
        return -yf * jax.nn.sigmoid(-yf * m)
    raise ValueError(f"no reference for loss {loss!r}")


def kink_distance(loss: str, w, idx, val, y):
    """How far each row is from a point where `backward` jumps (hinge:
    y*m == 0), relative to the size of the terms its margin sums:
    |m| / sum_p |x_p w_p|.  A rounding of the operands moves a margin by
    about that sum times the unit round-off, so rows below a guard of a few
    round-offs can flip a whole row of the gradient and are kept out of the
    gradient checks.  None for a smooth loss."""
    if loss != "hinge":
        return None
    w = w.astype(jnp.float32)
    wi = w[None, :] if idx is None or idx.shape[-1] == 0 else jnp.take(w, idx, axis=0)
    terms = val.astype(jnp.float32) * wi
    return jnp.abs(jnp.sum(terms, axis=-1)) / jnp.maximum(
        jnp.sum(jnp.abs(terms), axis=-1), 1e-30)


def regularize(kind: str, g, w, lam: float, dim_sparsity=None):
    with jax.default_matmul_precision(HIGHEST):
        if kind == "dim_sparsity":
            scalar = lam * 2.0 * jnp.sum(w.astype(jnp.float32) * dim_sparsity)
            return g + jnp.where(g != 0, scalar, 0.0)
        if kind == "l2":
            return g + 2.0 * lam * w
        if kind == "none":
            return g
    raise ValueError(f"no reference for regularizer {kind!r}")


def worker_grad(loss: str, reg: str, w, idx, val, y, lam: float,
                dim_sparsity=None, reduce: str = "sum"):
    """One worker's reply for one batch: regularize(reduce of backward)."""
    n_features = w.shape[0]
    with jax.default_matmul_precision(HIGHEST):
        c = backward_coeff(loss, margins(w, idx, val), y)
        if reduce == "mean":
            c = c / val.shape[0]
        elif reduce != "sum":
            raise ValueError(f"reduce must be 'sum' or 'mean', got {reduce!r}")
        contrib = c[:, None] * val.astype(jnp.float32)
        if idx is None or idx.shape[-1] == 0:
            g = jnp.sum(contrib, axis=0)
        else:
            g = jax.ops.segment_sum(
                contrib.reshape(-1), idx.reshape(-1), num_segments=n_features)
        return regularize(reg, g, w, lam, dim_sparsity)


def sync_step(loss: str, reg: str, w, batches, lam: float, lr: float,
              dim_sparsity=None):
    """w' after one synchronous step: every worker's reply (batch SUM,
    regularized) averaged over ALL workers, then w - lr * mean."""
    replies = [worker_grad(loss, reg, w, i, v, y, lam, dim_sparsity, "sum")
               for (i, v, y) in batches]
    g = sum(replies[1:], replies[0]) / len(replies)
    return w - lr * g


def local_steps(loss: str, reg: str, w, idx, val, y, lam: float, lr: float,
                k: int, dim_sparsity=None):
    """The summed delta of `k` sequential local steps that all draw the
    same batch (the single-row shard of the Hogwild kernel check): each
    step's delta = lr * regularize(MEAN backward) on the locally updated w."""
    acc = jnp.zeros_like(w)
    for _ in range(int(k)):
        delta = lr * worker_grad(loss, reg, w, idx, val, y, lam,
                                 dim_sparsity, "mean")
        w = w - delta
        acc = acc + delta
    return acc


def _block_rows(n: int, target: int) -> int:
    """The largest divisor of `n` that is at most `target`."""
    for b in range(min(n, target), 0, -1):
        if n % b == 0:
            return b
    return n


def _shards(idx, val, y):
    """(idx, val, y) per device: a sharded global array is walked one
    addressable shard at a time, a plain array is its own single shard."""
    dense = idx is None or idx.shape[-1] == 0
    pieces = getattr(val, "addressable_shards", None)
    if pieces is None or len(pieces) <= 1:
        return [(None if dense else idx, val, y)]
    order = sorted(range(len(pieces)), key=lambda i: pieces[i].index[0].start or 0)
    vals = [pieces[i].data for i in order]
    ys = [y.addressable_shards[i].data for i in order]
    if dense:
        return [(None, v, l) for v, l in zip(vals, ys)]
    idxs = [idx.addressable_shards[i].data for i in order]
    return list(zip(idxs, vals, ys))


def evaluate(loss: str, w, idx, val, y, lam: float, block: int = 32768):
    """(objective, accuracy) over a whole split, computed in row blocks,
    device shard by device shard.  Rows whose label is 0 are padding and
    do not count."""

    @jax.jit
    def shard_sums(w, bi, bv, by):
        b = _block_rows(bv.shape[0], block)
        nb = bv.shape[0] // b

        def one(args):
            ci, cv, cy = args
            m = margins(w, ci, cv)
            mask = (cy != 0).astype(jnp.float32)
            ls = sample_losses(loss, m, cy) * mask
            hit = (predict(loss, m) == cy.astype(jnp.float32)).astype(jnp.float32)
            return jnp.sum(ls), jnp.sum(hit * mask), jnp.sum(mask)

        ci = None if bi is None else bi.reshape(nb, b, bi.shape[-1])
        return jax.lax.map(
            one, (ci, bv.reshape(nb, b, bv.shape[-1]), by.reshape(nb, b)))

    loss_sum = hits = count = 0.0
    w = jnp.asarray(w, jnp.float32)
    for bi, bv, by in _shards(idx, val, y):
        dev = next(iter(bv.devices()))
        ls, hit, cnt = shard_sums(jax.device_put(w, dev), bi, bv, by)
        # per-block float32 sums, added up in float64 on the host
        loss_sum += float(np.asarray(ls, np.float64).sum())
        hits += float(np.asarray(hit, np.float64).sum())
        count += float(np.asarray(cnt, np.float64).sum())
    reg = lam * float(jnp.sum(w ** 2))
    return reg + loss_sum / count, hits / count
