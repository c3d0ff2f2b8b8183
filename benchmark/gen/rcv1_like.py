"""RCV1-shaped sparse rows with a planted separator, generated on device.

The statistics follow the program's `data/synthetic.rcv1_like(idf_values=
True)` (its verdict in PERF.md: sound): Zipf-ish feature popularity, |N(0,1)|
term weights, repeat draws within a row zeroed, ltc weighting (log-TF x
IDF, cosine-normalised rows; LYRL2004), labels from a planted linear
separator with label noise.  That generator runs in numpy on the host and
draws from tables (`rng.choice(p=pop)`, `w_true[idx]`); on a TPU a table
look-up per element is a serialized gather (7.6 s for one block of 55 M
draws, my chip run, PR 22), so this one states every table as a function
of the index and is elementwise throughout (13 ms for the same block):

  popularity   P(rank r) = ln(1 + 1/r) / ln(D + 1),  r = 1..D, drawn by
               inverting its CDF: r = floor(exp(u * ln(D + 1)))   (~ 1/r)
  idf          -ln(P(feature in a row)), P = 1 - (1 - P(r))^nnz: the
               document frequency the distribution implies, not a count
  separator    w_true[i] = a standard normal hashed from (seed, i)
  labels       +1 where the row's margin exceeds the block's mean margin,
               flipped with probability `label_noise`
  dim_sparsity 1 / (n_train * P(feature in a row) + 1), the program's
               `data/rcv1.dim_sparsity` with the expected count in place
               of the counted one
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.gen.rows import Problem, device_splits


def rank_prob(n_features: int) -> np.ndarray:
    """P(rank r), r = 1..D, as float64."""
    r = np.arange(1, n_features + 1, dtype=np.float64)
    return np.log1p(1.0 / r) / math.log(n_features + 1.0)


def doc_prob(n_features: int, nnz: int) -> np.ndarray:
    """P(feature i occurs in a row of `nnz` draws)."""
    return 1.0 - (1.0 - rank_prob(n_features)) ** nnz


def dim_sparsity(n_features: int, nnz: int, n_train: int) -> np.ndarray:
    return (1.0 / (n_train * doc_prob(n_features, nnz) + 1.0)).astype(np.float32)


def _mix(x):
    """murmur3's 32-bit finalizer."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def planted_weight(idx, seed):
    """A standard normal per feature id, a pure function of (seed, id)."""
    i = idx.astype(jnp.uint32)
    s = jnp.asarray(seed).astype(jnp.uint32)
    h1 = _mix(i * jnp.uint32(2) + jnp.uint32(1) + _mix(s))
    h2 = _mix(i * jnp.uint32(2) + _mix(s + jnp.uint32(0x9E3779B9)))
    u1 = (h1.astype(jnp.float32) + 1.0) * (1.0 / 4294967296.0)
    u2 = h2.astype(jnp.float32) * (1.0 / 4294967296.0)
    u1 = jnp.maximum(u1, 1e-12)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos((2.0 * math.pi) * u2)


def block(key, seed, n_rows: int, n_features: int, nnz: int, noise: float):
    """(indices int32[n, nnz], values f32[n, nnz], labels int32[n]) of one block."""
    k_idx, k_val, k_flip = jax.random.split(key, 3)
    log_d1 = math.log(n_features + 1.0)
    u = jax.random.uniform(k_idx, (n_rows, nnz), dtype=jnp.float32)
    rank = jnp.floor(jnp.exp(u * log_d1)).astype(jnp.int32)
    idx = jnp.sort(jnp.clip(rank, 1, n_features) - 1, axis=1)
    val = jnp.abs(jax.random.normal(k_val, (n_rows, nnz), dtype=jnp.float32))
    # ltc weighting from the distribution's own document frequency
    p = jnp.log1p(1.0 / (idx + 1).astype(jnp.float32)) / log_d1
    in_row = -jnp.expm1(nnz * jnp.log1p(-p))  # 1 - (1 - p)^nnz
    val = val * -jnp.log(in_row)
    # a real row holds a feature once: zero the repeat draws (inert pads)
    dup = jnp.concatenate(
        [jnp.zeros((n_rows, 1), bool), idx[:, 1:] == idx[:, :-1]], axis=1)
    val = jnp.where(dup, 0.0, val)
    val = val / jnp.maximum(
        jnp.sqrt(jnp.sum(val * val, axis=1, keepdims=True)), 1e-12)
    margin = jnp.sum(val * planted_weight(idx, seed), axis=1)
    y = jnp.where(margin > jnp.mean(margin), 1, -1).astype(jnp.int32)
    flip = jax.random.uniform(k_flip, (n_rows,)) < noise
    return idx, val, jnp.where(flip, -y, y)


def generate(spec: dict, seed: int, devices, rehearse: bool = False) -> Problem:
    from distributed_sgd_tpu.data.rcv1 import Dataset

    n_features, nnz = int(spec["n_features"]), int(spec["nnz"])
    noise = float(spec["label_noise"])

    def block_of(key, salt, block_rows):
        return lambda b: block(jax.random.fold_in(key, b), salt, block_rows,
                               n_features, nnz, noise)

    train, test, per_device = device_splits(spec, seed, devices, rehearse, block_of)
    train, test = Dataset(*train, n_features), Dataset(*test, n_features)
    return Problem(
        train=train, test=test, n_features=n_features,
        dim_sparsity=dim_sparsity(n_features, nnz, len(train)),
        rows_per_device=per_device)
