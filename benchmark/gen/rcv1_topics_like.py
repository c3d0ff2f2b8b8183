"""`rcv1_like`'s rows with EVERY topic's label: one planted separator a
topic category, generated on device.

RCV1-v2's qrels file holds every topic code of every document (103 Topic
categories, 3.24 codes a document on average; Lewis et al., JMLR 5, 2004);
the flagship configuration keeps one bit of it (CCAT).  This generator
takes `rcv1_like.block`'s rows as they are (the same key derivation: a seed
gives the same indices and values in both) and replaces the one label by a
row of C:

  priors       the four top-level codes as the configuration lists them,
               the other C - 4 on a geometric law in rank, p_j = a r^(j-1),
               with a and r solved so that all priors sum to `mean_codes`
               and the rarest is `rarest_prior`.  (ISSUE 32 asked for a
               power law; none fits: 99 priors that sum to 2.065 with the
               last under 1e-5 need a first prior over 1.  The geometric law
               that fits opens at 0.203, where the collection's largest
               second-level code, C15, stands at 18.8 % FROM MEMORY.)
  separators   W_true[i, c] = a standard normal hashed from (seed, i, c)
               (`rcv1_like.planted_weight` on the id i * L + c, the seed
               salted so that column 0 is not the binary cell's separator)
  labels       +1 where the row's margin x . W_true[:, c], standardised over
               its sub-block of `LABEL_ROWS` rows, lies above z_c, the
               normal quantile of the topic's planted share; then flipped
               with probability `label_noise` x prior_c (a twentieth of a
               topic's positives are noise at 0.05, whatever its prior),
               the planted share set so that the flipped labels keep the prior
  hierarchy    NOT modelled: a child code does not imply its parents

The separators are a [D, L] table (6 M hashes) and a row's C margins are its
76 table rows summed with its values as weights: the row gather the chip
runs at 1.4-4.4 ns a row (PERF.md section 6, PR 26 / PR 30), 2,048 rows of a
block at a time so that the gathered rows stay at 80 MB.
"""

from __future__ import annotations

from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.gen import rcv1_like
from benchmark.gen.rows import Problem, device_splits

LANES = 128
LABEL_ROWS = 2048  # rows a sub-block: what a topic's threshold is standardised over
SEPARATOR_SALT = 0x70B1C5


def priors(spec: dict) -> np.ndarray:
    """P(label = +1) per output, float64 [C]: the top-level codes first,
    then the geometric tail in descending order."""
    top = np.asarray(list(spec["top_level_priors"].values()), np.float64)
    n_tail = int(spec["n_outputs"]) - len(top)
    total, rarest = float(spec["mean_codes"]) - top.sum(), float(spec["rarest_prior"])

    def tail_sum(r):  # a r^(n-1) = rarest
        a = rarest / r ** (n_tail - 1)
        return a * (1.0 - r ** n_tail) / (1.0 - r)

    lo, hi = 1e-6, 1.0 - 1e-9  # the sum falls as r grows towards 1
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if tail_sum(mid) > total else (lo, mid)
    r = 0.5 * (lo + hi)
    tail = rarest / r ** (n_tail - 1) * r ** np.arange(n_tail)
    if not 0.0 < tail[0] < 1.0:
        raise ValueError(f"no geometric tail fits: it would open at {tail[0]}")
    return np.concatenate([top, tail])


def flip_probability(prior: np.ndarray, noise: float) -> np.ndarray:
    return noise * prior


def thresholds(prior: np.ndarray, noise: float) -> np.ndarray:
    """z_c: the standard normal quantile above which a topic's PLANTED
    share lies, so that after the flips a share prior_c is positive."""
    flip = flip_probability(prior, noise)
    planted = (prior - flip) / (1.0 - 2.0 * flip)
    return np.asarray([NormalDist().inv_cdf(1.0 - p) for p in planted], np.float64)


def separators(seed, n_features: int, n_outputs: int):
    """W_true as f32 [D, L], the outputs on the lanes, pad lanes zero."""
    ids = (jnp.arange(n_features, dtype=jnp.uint32)[:, None] * jnp.uint32(LANES)
           + jnp.arange(LANES, dtype=jnp.uint32)[None, :])
    salted = jnp.asarray(seed).astype(jnp.uint32) ^ jnp.uint32(SEPARATOR_SALT)
    return jnp.where(jnp.arange(LANES)[None, :] < n_outputs,
                     rcv1_like.planted_weight(ids, salted), 0.0)


def topic_labels(key, idx, val, table, z, flip, n_outputs: int):
    """int8 [n, C] in {-1, +1}: the rows' labels under `table`'s separators,
    a sub-block of `LABEL_ROWS` rows at a time."""
    n = idx.shape[0]
    sub = min(LABEL_ROWS, n)
    if n % sub:
        raise ValueError(f"{n} rows are not whole sub-blocks of {sub}")

    def one(args):
        ci, cv, k = args
        rows = table[ci.reshape(-1)].reshape(ci.shape + (LANES,))
        m = jnp.sum(cv[..., None] * rows, axis=1)  # [sub, L]
        m = (m - jnp.mean(m, axis=0)) / jnp.maximum(jnp.std(m, axis=0), 1e-12)
        y = jnp.where(m > z[None, :], 1, -1)
        flipped = jax.random.uniform(k, m.shape) < flip[None, :]
        return jnp.where(flipped, -y, y)[:, :n_outputs].astype(jnp.int8)

    y = jax.lax.map(one, (idx.reshape(-1, sub, idx.shape[1]),
                          val.reshape(-1, sub, val.shape[1]),
                          jax.random.split(key, n // sub)))
    return y.reshape(n, n_outputs)


def _refuse_without_an_output_axis() -> None:
    """A program whose model has no `n_outputs` (before PR 32) cannot hold
    W[D, C]: refused here, at once, with the exit code `run.py` gives a cell
    it cannot run."""
    import inspect
    import sys

    from distributed_sgd_tpu.models.linear import make_model

    if "n_outputs" not in inspect.signature(make_model).parameters:
        print("benchmark/gen/rcv1_topics_like.py: the program beside the benchmark has "
              "no output axis (models/linear.make_model takes no n_outputs): it cannot "
              "run rcv1-topics-hinge", file=sys.stderr)
        raise SystemExit(2)


def generate(spec: dict, seed: int, devices, rehearse: bool = False) -> Problem:
    _refuse_without_an_output_axis()
    from distributed_sgd_tpu.data.rcv1 import Dataset

    n_features, nnz = int(spec["n_features"]), int(spec["nnz"])
    n_outputs, noise = int(spec["n_outputs"]), float(spec["label_noise"])
    prior = priors(spec)
    pad = (0, LANES - n_outputs)
    z = jnp.asarray(np.pad(thresholds(prior, noise), pad), jnp.float32)
    flip = jnp.asarray(np.pad(flip_probability(prior, noise), pad), jnp.float32)

    def block_of(key, salt, block_rows):
        table = separators(salt, n_features, n_outputs)

        def one(b):
            kb = jax.random.fold_in(key, b)  # rcv1_like.generate's key of block b
            idx, val, _ccat = rcv1_like.block(kb, salt, block_rows, n_features, nnz, 0.0)
            return idx, val, topic_labels(
                jax.random.fold_in(kb, SEPARATOR_SALT), idx, val, table, z, flip, n_outputs)

        return one

    train, test, per_device = device_splits(spec, seed, devices, rehearse, block_of)
    return Problem(train=Dataset(*train, n_features), test=Dataset(*test, n_features),
                   n_features=n_features, dim_sparsity=None, rows_per_device=per_device)
