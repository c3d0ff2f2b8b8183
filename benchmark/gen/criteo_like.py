"""Criteo-shaped hashed sparse rows with a planted separator, generated on
device.

LIBSVM's binary set `criteo` (the Criteo Display Advertising Challenge,
features hashed to 1,000,000) cannot be fetched here; what the benchmark
needs of it is its shape: every row holds exactly 39 stored entries, one a
field (13 binned integer fields, 26 categorical fields), and is scaled to
unit length, so every value is 1 / sqrt(39).  A field's value is drawn
Zipf-like inside the field's own cardinality and hashed, with the field's
number, into [0, n_features):

  value        rank r in 1..C with P(r) ~ 1/r, by inverting the CDF:
               r = floor(exp(u * ln(C + 1)))  (as gen/rcv1_like.py's
               popularity; exponent 1, listed under the file's `assumed`)
  feature id   murmur3's finalizer of (r * 64 + field), modulo n_features:
               a function of field and value alone, as a hashing trick is
  separator    w_true[id] = a standard normal hashed from (seed, id)
               (gen/rcv1_like.planted_weight)
  labels       +1 where the row's margin x . w_true lies above the block's
               mean + z * its standard deviation, z set so that a share
               `positive_rate` of the rows is positive AFTER a share
               `label_noise` of all labels was flipped

What no other configuration's rows have: a field with 3, 4 or 10 values
puts the SAME feature id into a hundred or more of a step's 400 rows, and
the Zipf head of every large field does the same on a smaller scale, so the
scatter accumulates hundreds of duplicates of a few ids beside thousands
of singletons.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.gen.rcv1_like import _mix, planted_weight
from benchmark.gen.rows import Problem, device_splits


def cardinalities(spec: dict) -> np.ndarray:
    """Every field's number of values: the integer fields' bins, then the
    categorical fields' cardinalities, as the configuration lists them."""
    card = np.asarray(list(spec["integer_bins"]) + list(spec["categorical_cardinalities"]),
                      dtype=np.int64)
    if len(card) != int(spec["nnz"]):
        raise ValueError(f"{len(card)} fields listed, nnz is {spec['nnz']}")
    return card


def positive_threshold(positive_rate: float, noise: float) -> float:
    """z such that P(N(0,1) > z) = p, where p(1 - noise) + (1 - p) noise is
    the positive rate asked for."""
    p = (positive_rate - noise) / (1.0 - 2.0 * noise)
    if not 0.0 < p < 1.0:
        raise ValueError("positive_rate must lie between label_noise and 1 - label_noise")
    return NormalDist().inv_cdf(1.0 - p)


def feature_ids(rank, n_features: int):
    """Hashed ids of the values `rank` [n, fields] (1-based, one column a
    field)."""
    field = jnp.arange(rank.shape[1], dtype=jnp.uint32)[None, :]
    h = _mix(rank.astype(jnp.uint32) * jnp.uint32(64) + field)
    return (h % jnp.uint32(n_features)).astype(jnp.int32)


def block(key, seed, n_rows: int, n_features: int, log_card1, card, z: float, noise: float):
    """(indices int32[n, fields], values f32[n, fields], labels int32[n])."""
    k_idx, k_flip = jax.random.split(key)
    fields = card.shape[0]
    u = jax.random.uniform(k_idx, (n_rows, fields), dtype=jnp.float32)
    rank = jnp.clip(jnp.floor(jnp.exp(u * log_card1[None, :])).astype(jnp.int32), 1, card[None, :])
    idx = feature_ids(rank, n_features)
    val = jnp.full((n_rows, fields), 1.0 / math.sqrt(fields), jnp.float32)
    margin = jnp.sum(val * planted_weight(idx, seed), axis=1)
    y = jnp.where(margin > jnp.mean(margin) + z * jnp.std(margin), 1, -1).astype(jnp.int32)
    flip = jax.random.uniform(k_flip, (n_rows,)) < noise
    return idx, val, jnp.where(flip, -y, y)


def _refuse_without_the_gather_family() -> None:
    """A program whose `Config` does not know kernel='gather' (before PR 26)
    would run this shape on the one-hot matmuls: 160 s a run and more with a
    cold cache (my chip run, PR 26), and a step the float32 tolerance
    refuses.  Such a program is refused here, at once, with the exit code
    `run.py` gives a cell it cannot run."""
    import sys

    from distributed_sgd_tpu.config import Config

    try:
        Config(kernel="gather")
    except ValueError:
        print("benchmark/gen/criteo_like.py: the program beside the benchmark knows no "
              "kernel='gather' (its Config refuses the name): it cannot run "
              "criteo-logistic's shape", file=sys.stderr)
        raise SystemExit(2) from None


def generate(spec: dict, seed: int, devices, rehearse: bool = False) -> Problem:
    _refuse_without_the_gather_family()
    from distributed_sgd_tpu.data.rcv1 import Dataset

    n_features = int(spec["n_features"])
    noise = float(spec["label_noise"])
    card = cardinalities(spec)
    z = positive_threshold(float(spec["positive_rate"]), noise)
    log_card1 = jnp.asarray(np.log(card + 1.0), jnp.float32)
    card32 = jnp.asarray(card, jnp.int32)

    def block_of(key, salt, block_rows):
        return lambda b: block(jax.random.fold_in(key, b), salt, block_rows,
                               n_features, log_card1, card32, z, noise)

    train, test, per_device = device_splits(spec, seed, devices, rehearse, block_of)
    return Problem(train=Dataset(*train, n_features), test=Dataset(*test, n_features),
                   n_features=n_features, dim_sparsity=None, rows_per_device=per_device)
