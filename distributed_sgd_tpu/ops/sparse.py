"""Padded-sparse batch representation and its two TPU kernels.

This layer replaces the reference's L0 math kernel — the boxed
``Map[Int, spire.math.Number]`` sparse vectors and their per-sample
elementwise ops (math/Vec.scala, math/Sparse.scala) — with a fixed-shape
representation XLA can compile:

    SparseBatch(indices: int32[B, P], values: f32[B, P])

Each row holds one sample's nonzero feature (index, value) pairs padded to
width P with (index=0, value=0).  Zero-valued pads are semantically inert in
both kernels below, so no explicit mask is carried.  Static shapes are what
make this TPU-native: XLA tiling needs fixed P, so the loader buckets rows
by nnz and pads to the bucket width (data/rcv1.py) instead of carrying
dynamic sparsity the way the reference's maps do.

Kernels:

- ``matvec(batch, w) -> f32[B]``: per-sample sparse dot products
  x_i . w as a gather + multiply + row reduction.  Replaces the reference's
  `Sparse.dot` hot loop (Vec.scala:58, Sparse.scala:15-46).
- ``scatter_add(batch, coeff, n_features) -> f32[D]``: sum_i coeff_i * x_i
  as one flat segment scatter-add.  Replaces `Vec.sum` over per-sample
  gradients (Vec.scala:133-137, Slave.scala:153).

Both are pure jittable functions; under `shard_map` they run per-shard with
collectives applied by the caller (parallel/sync.py).
"""

from __future__ import annotations

from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class SparseBatch(NamedTuple):
    """A batch of sparse rows, padded to a common nnz width.

    indices: int32[B, P] — 0-based feature ids (0 for padding)
    values:  f32[B, P]   — feature values (0.0 for padding)
    """

    indices: jax.Array
    values: jax.Array

    @property
    def batch_size(self) -> int:
        return self.values.shape[0]

    @property
    def pad_width(self) -> int:
        return self.indices.shape[1]

    @property
    def is_dense(self) -> bool:
        """Dense-layout batch (Dataset.dense): zero-width index array,
        values hold every feature.  The canonical discriminator — model
        methods route these rows to the plain-matmul kernels."""
        return self.indices.shape[1] == 0


def matvec(batch: SparseBatch, w: jax.Array) -> jax.Array:
    """Per-row sparse dot product: out[b] = sum_p values[b,p] * w[indices[b,p]].

    Pads contribute values 0 * w[0] = 0.  Accumulates in f32 regardless of
    the dtype of `values`/`w` (bf16-safe).  Weights with an output axis
    `w[D, C]` give `out[b, c]`, the same sum a column.
    """
    gathered = jnp.take(w, batch.indices, axis=0)
    values = batch.values.astype(jnp.float32)
    if w.ndim == 2:
        return jnp.sum(values[..., None] * gathered.astype(jnp.float32), axis=-2)
    prod = values * gathered.astype(jnp.float32)
    return jnp.sum(prod, axis=-1)


def scatter_add(batch: SparseBatch, coeff: jax.Array, n_features: int) -> jax.Array:
    """Weighted scatter of rows into a dense vector.

    out = sum_b coeff[b] * x_b, computed as one flat `.at[].add()` scatter
    (an XLA segment-sum; TPU-friendly).  Pads scatter 0.0 into feature 0.
    Coefficients with an output axis `coeff[B, C]` give `out[D, C]`.
    """
    flat_idx = batch.indices.reshape(-1)
    if coeff.ndim == 2:
        rows = batch.values.astype(jnp.float32)[..., None] * coeff.astype(jnp.float32)[:, None, :]
        return jnp.zeros((n_features, coeff.shape[1]), jnp.float32).at[flat_idx].add(
            rows.reshape(-1, coeff.shape[1]))
    flat_val = (batch.values.astype(jnp.float32) * coeff.astype(jnp.float32)[:, None]).reshape(-1)
    return jnp.zeros((n_features,), dtype=jnp.float32).at[flat_idx].add(flat_val)


def pad_rows(
    rows: Sequence[Tuple[np.ndarray, np.ndarray]],
    pad_width: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side: pack variable-nnz (indices, values) rows into [B, P] arrays.

    Rows longer than `pad_width` are truncated by largest-|value| (keeps the
    heaviest features); shorter rows are zero-padded.
    """
    b = len(rows)
    out_idx = np.zeros((b, pad_width), dtype=np.int32)
    out_val = np.zeros((b, pad_width), dtype=np.float32)
    for i, (idx, val) in enumerate(rows):
        n = len(idx)
        if n > pad_width:
            keep = np.argsort(-np.abs(val))[:pad_width]
            keep.sort()
            idx, val = idx[keep], val[keep]
            n = pad_width
        out_idx[i, :n] = idx
        out_val[i, :n] = val
    return out_idx, out_val


def take_batch(indices: np.ndarray, values: np.ndarray, sample_ids: np.ndarray) -> SparseBatch:
    """Select rows `sample_ids` from packed [N, P] host arrays as a SparseBatch."""
    return SparseBatch(
        indices=jnp.asarray(indices[sample_ids]),
        values=jnp.asarray(values[sample_ids]),
    )


def nnz_per_row(values: np.ndarray) -> np.ndarray:
    return (values != 0).sum(axis=1)
