"""The plain reference for one-vs-rest fits whose labels come as ID LISTS:
C binary linear models over the same rows, a row's labels the ids of its
positives, in straightforward jax.numpy.

`W [D, C]`; a row's labels `lists [Lw]` int32: the ids in [0, C) of the
outputs that are +1 for this row, negative numbers in the unused slots (a
list whose FIRST slot is -2 is a padding row: none of its outputs counts).

  labels            y_c = +1 if c is in the row's list, else -1      [C]
  margin            m_c = x . W[:, c]
  squared_hinge     loss      max(0, 1 - y_c*m_c)^2
                    backward  -2 * y_c * max(0, 1 - y_c*m_c) * x
                    predict   +1 if m_c >= 0 else -1
  hinge / logistic  as `benchmark/reference_outputs.py` states them (the
                    reference's sign quirk in hinge's predict and backward)
  least_squares     loss (m_c - y_c)^2, backward 2 (m_c - y_c) x, predict m_c
  objective         lam*||W||_F^2 + mean over rows of SUM_c loss_c
  accuracy          the share of (row, output) pairs with predict == y_c
  regularize        l2:   G + 2*lam*W          none: G
  sync worker reply regularize(SUM over the batch of x (outer) backward coefficient)
  update            W - lr * mean over ALL workers of their replies

Nothing couples the columns: the fit of a RANGE of labels is that range's
columns of the fit of all labels (DiSMEC's Algorithm 1 rests on it).
float32 under `jax.default_matmul_precision("highest")`; take / multiply /
sum for the margins, `segment_sum` for the scatter, float64 sums of the
evaluation's blocks on the host.  Nothing is imported from the program or
from the other references.

Departure from the source (DiSMEC trains a label with LIBLINEAR's primal
trust-region solver): mini-batch SGD on the same objective, the program's
algorithm.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
NO_ROW = -2


def expand(lists, n_outputs: int):
    """[B, C] float32 in {-1, +1} (0: every output of a padding row)."""
    lists = jnp.asarray(lists)
    ids = jnp.arange(n_outputs, dtype=lists.dtype)
    listed = jnp.any(lists[:, :, None] == ids[None, None, :], axis=1)
    y = jnp.where(listed, 1.0, -1.0).astype(jnp.float32)
    return jnp.where(lists[:, :1] == NO_ROW, 0.0, y)


def margins(w, idx, val):
    """[B, C]: x . W[:, c] per row and output."""
    with jax.default_matmul_precision(HIGHEST):
        return jnp.sum(val.astype(jnp.float32)[:, :, None]
                       * jnp.take(w.astype(jnp.float32), idx, axis=0), axis=1)


def predict(loss: str, m):
    if loss == "hinge":
        return -jnp.sign(m)
    if loss in ("logistic", "squared_hinge"):
        return jnp.where(m >= 0, 1.0, -1.0)
    if loss == "least_squares":
        return m
    raise ValueError(f"no reference for loss {loss!r}")


def sample_losses(loss: str, m, y):
    if loss == "hinge":
        return jnp.maximum(0.0, 1.0 - y * predict(loss, m))
    if loss == "logistic":
        return jnp.logaddexp(0.0, -y * m)
    if loss == "squared_hinge":
        return jnp.maximum(0.0, 1.0 - y * m) ** 2
    if loss == "least_squares":
        return (m - y) ** 2
    raise ValueError(f"no reference for loss {loss!r}")


def backward_coeff(loss: str, m, y):
    if loss == "hinge":
        return jnp.where(y * m < 0, 0.0, y)
    if loss == "logistic":
        return -y * jax.nn.sigmoid(-y * m)
    if loss == "squared_hinge":
        return -2.0 * y * jnp.maximum(0.0, 1.0 - y * m)
    if loss == "least_squares":
        return 2.0 * (m - y)
    raise ValueError(f"no reference for loss {loss!r}")


def regularize(kind: str, g, w, lam: float):
    if kind == "l2":
        return g + 2.0 * lam * w
    if kind == "none":
        return g
    raise ValueError(f"no reference for regularizer {kind!r} with an output axis")


def worker_grad(loss: str, reg: str, w, idx, val, lists, lam: float):
    """One worker's reply for one batch: regularize(SUM of backward)."""
    n_features, n_outputs = w.shape
    with jax.default_matmul_precision(HIGHEST):
        c = backward_coeff(loss, margins(w, idx, val), expand(lists, n_outputs))  # [B, C]
        contrib = val.astype(jnp.float32)[:, :, None] * c[:, None, :]  # [B, P, C]
        g = jax.ops.segment_sum(
            contrib.reshape(-1, n_outputs), idx.reshape(-1), num_segments=n_features)
        return regularize(reg, g, w, lam)


def sync_step(loss: str, reg: str, w, batches, lam: float, lr: float):
    """W' after one synchronous step: every worker's reply (batch SUM,
    regularized) averaged over ALL workers, then W - lr * mean.  `batches`:
    one (idx, val, lists) a worker."""
    total = None
    for idx, val, lists in batches:  # one reply alive at a time: W is large
        reply = worker_grad(loss, reg, w, idx, val, lists, lam)
        total = reply if total is None else total + reply
    return w - lr * (total / len(batches))


def _block_rows(n: int, target: int) -> int:
    """The largest divisor of `n` that is at most `target`."""
    for b in range(min(n, target), 0, -1):
        if n % b == 0:
            return b
    return n


def _shards(idx, val, lists):
    """(idx, val, lists) per device: a sharded global array is walked one
    addressable shard at a time, a plain array is its own single shard."""
    pieces = getattr(val, "addressable_shards", None)
    if pieces is None or len(pieces) <= 1:
        return [(idx, val, lists)]
    order = sorted(range(len(pieces)), key=lambda i: pieces[i].index[0].start or 0)
    return [(idx.addressable_shards[i].data, pieces[i].data,
             lists.addressable_shards[i].data) for i in order]


def evaluate(loss: str, w, idx, val, lists, lam: float, block: int = 512):
    """(objective, accuracy) over a whole split, computed in row blocks (a
    block's gathered weights are block x P x C words: 147 MB at 512 rows of
    72 entries and 1,000 outputs), device shard by device shard; a padding
    row does not count."""
    w = jnp.asarray(w, jnp.float32)
    n_outputs = w.shape[1]

    @jax.jit
    def shard_sums(w, bi, bv, bl):
        b = _block_rows(bv.shape[0], block)
        nb = bv.shape[0] // b

        def one(args):
            ci, cv, cl = args
            m = margins(w, ci, cv)
            y = expand(cl, n_outputs)
            mask = (y != 0).astype(jnp.float32)
            ls = jnp.sum(sample_losses(loss, m, y) * mask, axis=1)  # a row: summed over C
            hit = (predict(loss, m) == y).astype(jnp.float32) * mask
            return jnp.sum(ls), jnp.sum(hit), jnp.sum(mask[:, 0])

        return jax.lax.map(one, (bi.reshape(nb, b, -1), bv.reshape(nb, b, -1),
                                 bl.reshape(nb, b, -1)))

    loss_sum = hits = rows = 0.0
    for bi, bv, bl in _shards(idx, val, lists):
        dev = next(iter(bv.devices())) if hasattr(bv, "devices") else None
        ls, hit, cnt = shard_sums(w if dev is None else jax.device_put(w, dev), bi, bv, bl)
        # per-block float32 sums, added up in float64 on the host
        loss_sum += float(np.asarray(ls, np.float64).sum())
        hits += float(np.asarray(hit, np.float64).sum())
        rows += float(np.asarray(cnt, np.float64).sum())
    reg = lam * float(jnp.sum(w ** 2))
    return reg + loss_sum / rows, hits / (rows * n_outputs)
