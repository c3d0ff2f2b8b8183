"""Epsilon-shaped dense rows with a planted separator, generated on device.

The PASCAL Large Scale Learning Challenge's "epsilon" set cannot be
fetched here; what the benchmark needs of it is its shape: 2,000 dense
float features, rows scaled to unit length, a binary label that a linear
model separates to roughly 0.90 accuracy.  Rows are unit-normalised
standard normals; the label is the sign of the margin against a planted
w_true ~ N(0, I), flipped with probability `label_noise`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.gen.rows import Problem, device_splits


def block(key, w_true, n_rows: int, noise: float):
    """(values f32[n, D], labels int32[n]) of one block."""
    k_x, k_flip = jax.random.split(key)
    x = jax.random.normal(k_x, (n_rows, w_true.shape[0]), dtype=jnp.float32)
    x = x / jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    margin = jnp.sum(x * w_true[None, :], axis=1)
    y = jnp.where(margin >= 0, 1, -1).astype(jnp.int32)
    flip = jax.random.uniform(k_flip, (n_rows,)) < noise
    return x, jnp.where(flip, -y, y)


def generate(spec: dict, seed: int, devices, rehearse: bool = False) -> Problem:
    from distributed_sgd_tpu.data.rcv1 import Dataset

    n_features = int(spec["n_features"])
    noise = float(spec["label_noise"])

    def block_of(key, _salt, block_rows):
        w_true = jax.random.normal(
            jax.random.fold_in(key, 0x7FFFFFFF), (n_features,), dtype=jnp.float32)
        return lambda b: block(jax.random.fold_in(key, b), w_true, block_rows, noise)

    train, test, per_device = device_splits(spec, seed, devices, rehearse, block_of)

    def dense(x, y):
        # the dense layout is a zero-width index array (Dataset.dense)
        return Dataset(np.empty((x.shape[0], 0), np.int32), x, y, n_features)

    return Problem(train=dense(*train), test=dense(*test), n_features=n_features,
                   dim_sparsity=None, rows_per_device=per_device)
