"""KDD-Cup-2012-shaped one-hot click rows with a planted separator,
generated on device.

LIBSVM's binary set `kdd2012` (KDD Cup 2012 track 2, click prediction on
soso.com's search ads: 149,639,105 rows x 54,686,452 features) cannot be
fetched here; what the benchmark needs of it is its shape: every row holds
exactly 11 stored entries, the one-hot id of each of its 11 categorical
fields, and is scaled to unit length, so every value is 1 / sqrt(11).
Nothing is hashed: a field owns a contiguous range of ids as wide as its
cardinality, and the ranges laid end to end are the 54,686,452 features.

  value        rank r in 1..C with P(r) ~ 1/r, by inverting the CDF:
               r = floor(exp(u * ln(C + 1)))   (gen/criteo_like.py's draw)
  feature id   offset of the field + r - 1
  separator    w_true[id] = a standard normal hashed from (seed, id)
               (gen/rcv1_like.planted_weight)
  labels       +1 where the row's margin x . w_true lies above the block's
               mean + z * its standard deviation, z set so that a share
               `positive_rate` of the rows is positive AFTER a share
               `label_noise` of all labels was flipped
               (gen/criteo_like.positive_threshold)

What no other configuration's rows have: a weight vector of 219 MB of which
a step's 400 rows touch at most 4,400 words, three 3-valued fields that put
ONE id into about 220 of those rows, and two 20-million-valued fields most
of whose ids no row of the chip's split ever holds.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.gen.criteo_like import positive_threshold
from benchmark.gen.rcv1_like import planted_weight
from benchmark.gen.rows import Problem, device_splits


def cardinalities(spec: dict) -> np.ndarray:
    """Every field's number of values, as the configuration lists them;
    together they are the feature count."""
    card = np.asarray(list(spec["field_cardinalities"].values()), dtype=np.int64)
    if len(card) != int(spec["nnz"]):
        raise ValueError(f"{len(card)} fields listed, nnz is {spec['nnz']}")
    if int(card.sum()) != int(spec["n_features"]):
        raise ValueError(f"the fields hold {int(card.sum())} values, "
                         f"n_features is {spec['n_features']}")
    return card


def block(key, seed, n_rows: int, log_card1, card, offset, z: float, noise: float):
    """(indices int32[n, fields], values f32[n, fields], labels int32[n])."""
    k_idx, k_flip = jax.random.split(key)
    fields = card.shape[0]
    u = jax.random.uniform(k_idx, (n_rows, fields), dtype=jnp.float32)
    rank = jnp.clip(jnp.floor(jnp.exp(u * log_card1[None, :])).astype(jnp.int32), 1, card[None, :])
    idx = offset[None, :] + rank - 1
    val = jnp.full((n_rows, fields), 1.0 / math.sqrt(fields), jnp.float32)
    margin = jnp.sum(val * planted_weight(idx, seed), axis=1)
    y = jnp.where(margin > jnp.mean(margin) + z * jnp.std(margin), 1, -1).astype(jnp.int32)
    flip = jax.random.uniform(k_flip, (n_rows,)) < noise
    return idx, val, jnp.where(flip, -y, y)


def generate(spec: dict, seed: int, devices, rehearse: bool = False) -> Problem:
    from distributed_sgd_tpu.data.rcv1 import Dataset

    n_features = int(spec["n_features"])
    noise = float(spec["label_noise"])
    card = cardinalities(spec)
    z = positive_threshold(float(spec["positive_rate"]), noise)
    log_card1 = jnp.asarray(np.log(card + 1.0), jnp.float32)
    card32 = jnp.asarray(card, jnp.int32)
    offset = jnp.asarray(np.cumsum(card) - card, jnp.int32)

    def block_of(key, salt, block_rows):
        return lambda b: block(jax.random.fold_in(key, b), salt, block_rows,
                               log_card1, card32, offset, z, noise)

    train, test, per_device = device_splits(spec, seed, devices, rehearse, block_of)
    return Problem(train=Dataset(*train, n_features), test=Dataset(*test, n_features),
                   n_features=n_features, dim_sparsity=None, rows_per_device=per_device)
