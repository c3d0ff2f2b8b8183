"""Synthetic datasets for tests and benchmarks.

`rcv1_like` generates a packed sparse classification set with RCV1-shaped
statistics (cosine-normalized rows, ~76 nnz per row over 47,236 features by
default) from a planted linear separator with label noise — used wherever
the real RCV1 files are unavailable (no network egress) and by BASELINE.md
config 5's dense least-squares problem via `dense_regression`.
"""

from __future__ import annotations

import numpy as np

from distributed_sgd_tpu.data.rcv1 import Dataset


def rcv1_like(
    n_samples: int,
    n_features: int = 47236,
    nnz: int = 76,
    noise: float = 0.05,
    seed: int = 0,
    idf_values: bool = False,
    n_outputs: int = 1,
) -> Dataset:
    """Planted-separator sparse classification data, packed [N, P];
    `n_outputs` > 1: labels [N, C], one planted separator a column.

    `idf_values=True` weights each entry by its feature's inverse document
    frequency (log(N/df)) before the cosine normalization — the ltc
    (log-TF x IDF, cosine) scheme the REAL RCV1-v2 vectors use (LYRL2004).
    Without it, head (Zipf-popular) features carry the same magnitude
    distribution as tail ones, which real term weighting never allows —
    the difference that decides whether the reference's lr=0.5 converges
    smoothly (see BASELINE.md, Zipf-oscillation study).
    """
    rng = np.random.default_rng(seed)
    # Zipf-ish feature popularity like term frequencies
    pop = 1.0 / np.arange(1, n_features + 1, dtype=np.float64)
    pop /= pop.sum()
    idx = rng.choice(n_features, size=(n_samples, nnz), p=pop).astype(np.int32)
    idx.sort(axis=1)
    val = np.abs(rng.normal(size=(n_samples, nnz))).astype(np.float32)
    # real RCV1 rows (and the reference's Map-backed vectors) cannot hold
    # duplicate feature ids: zero out repeat draws, leaving inert pad slots
    dup = np.zeros_like(idx, dtype=bool)
    dup[:, 1:] = idx[:, 1:] == idx[:, :-1]
    if idf_values:
        # DOCUMENT frequency: each feature counts once per row (dedup via
        # the sorted-duplicate mask), so df <= n_samples and idf >= 0 —
        # collection frequency would exceed n_samples for mid-head Zipf
        # features and log(N/df) would go negative, zeroing terms real
        # ltc/IDF (LYRL2004) only down-weights
        df = np.bincount(idx[~dup], minlength=n_features)
        idf = np.log(n_samples / np.maximum(df, 1.0)).astype(np.float32)
        val *= idf[idx]
    val[dup] = 0.0
    val /= np.maximum(np.linalg.norm(val, axis=1, keepdims=True), 1e-12)  # cosine norm

    w_true = rng.normal(size=n_features).astype(np.float32)
    margins = np.einsum("np,np->n", val, w_true[idx])
    y = np.where(margins > np.median(margins), 1, -1).astype(np.int32)
    flip = rng.random(n_samples) < noise
    y[flip] = -y[flip]
    if n_outputs > 1:
        y = _topic_labels(rng, idx, val, y, n_outputs, noise)
    return Dataset(indices=idx, values=val, labels=y, n_features=n_features)


def _topic_labels(rng, idx, val, first, n_outputs: int, noise: float) -> np.ndarray:
    """int8 [N, C]: column 0 the binary labels `first`, every further
    column its own planted separator, positive above the margins' quantile
    of a prior that halves every third column (0.3, 0.24, 0.19 ...), then
    flipped with probability `noise` x the prior."""
    y = np.empty((len(first), n_outputs), np.int8)
    y[:, 0] = first
    for c in range(1, n_outputs):
        prior = 0.3 * 0.5 ** ((c - 1) / 3.0)
        margins = np.einsum("np,np->n", val, rng.normal(size=idx.max() + 1)[idx])
        col = np.where(margins > np.quantile(margins, 1.0 - prior), 1, -1)
        flip = rng.random(len(first)) < noise * prior
        y[:, c] = np.where(flip, -col, col)
    return y


def dense_regression(
    n_samples: int,
    n_features: int = 1024,
    noise: float = 0.01,
    seed: int = 0,
) -> Dataset:
    """Dense least-squares data in the dense layout (BASELINE.md config 5).

    Uses `Dataset.dense`: values[N, D] only, no index array — engines route
    it through the plain-matmul kernels (models/linear.py dense fast path)
    and the int32 indices that would double the footprint never exist.
    """
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n_samples, n_features)).astype(np.float32)
    w_true = rng.normal(size=n_features).astype(np.float32)
    y = x @ w_true + noise * rng.normal(size=n_samples).astype(np.float32)
    return Dataset.dense(x, y.astype(np.float32))
