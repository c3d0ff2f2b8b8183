"""The plain reference with an output axis: `benchmark/reference.py`'s
equations for C models over the same rows, in straightforward jax.numpy.

`W [D, C]`, a row's labels `y [C]` in {-1, +1} (0: padding).  For every
output c the binary model of `reference.py` on column c, unchanged:

  margin            m_c = x . W[:, c]
  hinge   predict   p_c = -sign(m_c)                  (the reference's sign quirk)
          loss      max(0, 1 - y_c*p_c)
          backward  0 if y_c*m_c < 0 else y_c*x
  logistic predict  +1 if m_c >= 0 else -1
          loss      log(1 + exp(-y_c*m_c))
          backward  -y_c * sigmoid(-y_c*m_c) * x
  objective         lam*||W||_F^2 + mean over rows of SUM_c loss_c
  accuracy          the share of (row, output) pairs with p_c == y_c
  regularize        l2:   G + 2*lam*W          none: G
  sync worker reply regularize(SUM over the batch of x (outer) backward coefficient)
  update            W - lr * mean over ALL workers of their replies

Nothing couples the columns.  float32 under
`jax.default_matmul_precision("highest")`, take / multiply / sum for the
margins, `segment_sum` for the scatter; nothing imported from the program
and nothing from `reference.py`, which is flat (`w[None, :]`, a sum over the
last axis) and stays as it is: at C = 1 this file equals it
(`benchmark/tests/test_reference_outputs.py`).

Departures from the source (zifeo/distributed-sgd trains ONE SparseSVM on
the CCAT bit of the qrels file): the output axis itself (the collection's
own protocol, Lewis et al. 2004: one SVM a topic category); `l2` in place
of the source's `dim_sparsity` term, whose mask is one gradient's support
and has no form a (feature, output) pair; the sum over outputs in the
objective (a mean would scale lam's meaning by C).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"


def margins(w, idx, val):
    """[B, C]: x . W[:, c] per row and output; `idx` None or zero-width
    means dense rows."""
    with jax.default_matmul_precision(HIGHEST):
        w = w.astype(jnp.float32)
        val = val.astype(jnp.float32)
        if idx is None or idx.shape[-1] == 0:
            return jnp.sum(val[:, :, None] * w[None, :, :], axis=1)
        return jnp.sum(val[:, :, None] * jnp.take(w, idx, axis=0), axis=1)


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
    """[B, C]: how far each (row, output) is from a point where `backward`
    jumps (hinge: y_c*m_c == 0), relative to the size of the terms its
    margin sums: |m_c| / sum_p |x_p W[p, c]|.  None for a smooth loss."""
    if loss != "hinge":
        return None
    w = w.astype(jnp.float32)
    wi = w[None, :, :] if idx is None or idx.shape[-1] == 0 else jnp.take(w, idx, axis=0)
    terms = val.astype(jnp.float32)[:, :, None] * wi
    return jnp.abs(jnp.sum(terms, axis=1)) / jnp.maximum(
        jnp.sum(jnp.abs(terms), axis=1), 1e-30)


def regularize(kind: str, g, w, lam: float):
    if kind == "l2":
        return g + 2.0 * lam * w
    if kind == "none":
        return g
    raise ValueError(f"no reference for regularizer {kind!r} with an output axis")


def worker_grad(loss: str, reg: str, w, idx, val, y, lam: float):
    """One worker's reply for one batch: regularize(SUM of backward).  A
    label 0 (padding) contributes nothing under either loss."""
    n_features = w.shape[0]
    with jax.default_matmul_precision(HIGHEST):
        c = backward_coeff(loss, margins(w, idx, val), y)  # [B, C]
        contrib = val.astype(jnp.float32)[:, :, None] * c[:, None, :]  # [B, P, C]
        if idx is None or idx.shape[-1] == 0:
            g = jnp.sum(contrib, axis=0)
        else:
            g = jax.ops.segment_sum(
                contrib.reshape(-1, c.shape[1]), idx.reshape(-1), num_segments=n_features)
        return regularize(reg, g, w, lam)


def sync_step(loss: str, reg: str, w, batches, lam: float, lr: float):
    """W' after one synchronous step: every worker's reply (batch SUM,
    regularized) averaged over ALL workers, then W - lr * mean."""
    replies = [worker_grad(loss, reg, w, i, v, y, lam) for (i, v, y) in batches]
    g = sum(replies[1:], replies[0]) / len(replies)
    return w - lr * g


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


def evaluate(loss: str, w, idx, val, y, lam: float, block: int = 2048):
    """(objective, accuracy) over a whole split, computed in row blocks (a
    block's gathered weights are block x P x C words: 64 MB at 2,048 rows of
    76 entries and 103 outputs), device shard by device shard.  Labels 0
    are padding and do not count."""

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
            one, (ci, bv.reshape(nb, b, bv.shape[-1]), by.reshape(nb, b, by.shape[-1])))

    loss_sum = hits = count = 0.0
    w = jnp.asarray(w, jnp.float32)
    n_outputs = w.shape[1]
    for bi, bv, by in _shards(idx, val, y):
        dev = next(iter(bv.devices()))
        ls, hit, cnt = shard_sums(jax.device_put(w, dev), bi, bv, by)
        # per-block float32 sums, added up in float64 on the host
        loss_sum += float(np.asarray(ls, np.float64).sum())
        hits += float(np.asarray(hit, np.float64).sum())
        count += float(np.asarray(cnt, np.float64).sum())
    reg = lam * float(jnp.sum(w ** 2))
    return reg + loss_sum * n_outputs / count, hits / count
