"""AmazonCat-13K-shaped rows with ONE DiSMEC batch of their labels, as id
lists, generated on device.

The Extreme Classification Repository's AmazonCat-13K (McAuley & Leskovec,
RecSys 2013; Bhatia et al.) has 203,882 bag-of-words features, 13,330
labels and 5.04 labels a point; DiSMEC (Babbar & Schoelkopf, WSDM 2017,
Algorithm 1) cuts the labels into batches of 1,000 and gives a batch to a
node that holds every row.  This generator makes what ONE such node holds:

  rows         `rcv1_like.block`'s at D = 203,882 and P = 72 (the same key
               derivation: a seed gives the same indices and values there),
               so the 1/r popularity law `algorithmic_rows.expected_distinct`
               counts holds here as it stands
  priors       of ALL `n_labels_published` labels a power law in rank,
               p_r = head_prior * r^-beta, beta solved so that they sum to
               `mean_labels` (head 0.30 and the law itself are ASSUMED; the
               solved beta is 0.8957 and the rarest prior 6.06e-5)
  the batch    a stratified sample of that law: the ranks are cut into
               `n_outputs` strata of 13,330 / 1,000 consecutive ranks and a
               stratum gives the rank whose prior is nearest the stratum's
               mean, so the batch's priors sum to mean_labels x n_outputs /
               n_labels_published (0.378 a row) and most rows have none
  separators   W_true[i, c] = a standard normal hashed from (seed, i, c), a
               [D, L] table with the batch's outputs on L = 1,024 lanes (4 KB
               rows); label c is positive where the row's margin
               x . W_true[:, c], standardised over its sub-block of
               `LABEL_ROWS` rows, lies above the normal quantile of the
               label's planted share; then flipped with probability
               `label_noise` x prior_c (`rcv1_topics_like`'s relative noise,
               the planted share set so that the flips keep the prior)
  lists        a row's positives as `int32[label_list_width]` ascending ids
               inside the batch, -1 after them; a row with more positives
               keeps its lowest ids (counted, and printed by `generate`)
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.gen import rcv1_like
from benchmark.gen.rcv1_topics_like import flip_probability, thresholds
from benchmark.gen.rows import Problem, device_splits

LANES = 128
LABEL_ROWS = 2048  # rows a sub-block: what a label's threshold is standardised over
SEPARATOR_SALT = 0xA3C13
NO_LABEL = -1  # an unused slot of a row's list


def all_priors(spec: dict) -> np.ndarray:
    """P(label = +1) of every published label, float64 [n_labels_published],
    descending: head_prior * rank^-beta, beta solved for the sum."""
    n, head = int(spec["n_labels_published"]), float(spec["head_prior"])
    total = float(spec["mean_labels"])
    rank = np.arange(1, n + 1, dtype=np.float64)
    if not head < total < head * n:
        raise ValueError(f"no power law from {head} over {n} ranks sums to {total}")
    lo, hi = 0.0, 8.0  # the sum falls as beta grows
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if head * np.sum(rank ** -mid) > total else (lo, mid)
    return head * rank ** (-0.5 * (lo + hi))


def batch_ranks(spec: dict) -> np.ndarray:
    """The 0-based ranks of the labels this node holds, int64 [n_outputs]:
    one a stratum of consecutive ranks, the one nearest the stratum's mean
    prior."""
    p = all_priors(spec)
    edges = np.round(np.linspace(0, len(p), int(spec["n_outputs"]) + 1)).astype(np.int64)
    return np.asarray([lo + int(np.argmin(np.abs(p[lo:hi] - p[lo:hi].mean())))
                       for lo, hi in zip(edges[:-1], edges[1:])])


def priors(spec: dict) -> np.ndarray:
    """The priors of the batch's `n_outputs` labels, descending."""
    return all_priors(spec)[batch_ranks(spec)]


def output_lanes(n_outputs: int) -> int:
    return -(-n_outputs // LANES) * LANES


def separators(seed, n_features: int, n_outputs: int):
    """W_true as f32 [D, L], the outputs on the lanes, pad lanes zero."""
    lanes = output_lanes(n_outputs)
    ids = (jnp.arange(n_features, dtype=jnp.uint32)[:, None] * jnp.uint32(lanes)
           + jnp.arange(lanes, dtype=jnp.uint32)[None, :])
    salted = jnp.asarray(seed).astype(jnp.uint32) ^ jnp.uint32(SEPARATOR_SALT)
    return jnp.where(jnp.arange(lanes)[None, :] < n_outputs,
                     rcv1_like.planted_weight(ids, salted), 0.0)


def label_lists(key, idx, val, table, z, flip, n_outputs: int, width: int):
    """(int32 [n, width] lists, int32 [1] rows with more than `width`
    positives): the rows' labels under `table`'s separators, a sub-block of
    `LABEL_ROWS` rows at a time."""
    n, lanes = idx.shape[0], table.shape[1]
    sub = min(LABEL_ROWS, n)
    if n % sub:
        raise ValueError(f"{n} rows are not whole sub-blocks of {sub}")
    lane = jnp.arange(lanes, dtype=jnp.int32)[None, :]

    def one(args):
        ci, cv, k = args
        rows = table[ci.T.reshape(-1)].reshape(ci.shape[::-1] + (lanes,))  # [P, sub, L]
        m = jnp.sum(cv.T[..., None] * rows, axis=0)  # [sub, L]
        m = (m - jnp.mean(m, axis=0)) / jnp.maximum(jnp.std(m, axis=0), 1e-12)
        flipped = jax.random.uniform(k, m.shape) < flip[None, :]
        positive = ((m > z[None, :]) != flipped) & (lane < n_outputs)
        # the `width` lowest positive ids, one pass a slot
        slots, last = [], jnp.full((sub, 1), -1, jnp.int32)
        for _ in range(width):
            last = jnp.min(jnp.where(positive & (lane > last), lane, lanes),
                           axis=1, keepdims=True)
            slots.append(jnp.where(last < lanes, last, NO_LABEL))
        over = jnp.sum(jnp.sum(positive, axis=1) > width, dtype=jnp.int32)
        return jnp.concatenate(slots, axis=1), over

    lists, over = jax.lax.map(one, (idx.reshape(-1, sub, idx.shape[1]),
                                    val.reshape(-1, sub, val.shape[1]),
                                    jax.random.split(key, n // sub)))
    return lists.reshape(n, width), jnp.sum(over).reshape(1)


def _refuse_without_label_lists() -> None:
    """A program that knows no label lists or no squared hinge (before
    PR 36) cannot run this configuration: refused here, at once, with the
    exit code `run.py` gives a cell it cannot run."""
    import dataclasses
    import sys

    from distributed_sgd_tpu.data.rcv1 import Dataset
    from distributed_sgd_tpu.models.linear import make_model

    missing = []
    if "n_labels" not in {f.name for f in dataclasses.fields(Dataset)}:
        missing.append("no label lists (data/rcv1.Dataset has no n_labels)")
    try:
        make_model("squared_hinge", 0.0, 8, regularizer="l2")
    except ValueError:
        missing.append("no squared_hinge in models/linear.make_model")
    if missing:
        print("benchmark/gen/amazoncat_like.py: the program beside the benchmark has "
              f"{' and '.join(missing)}: it cannot run amazoncat13k-dismec", file=sys.stderr)
        raise SystemExit(2)


def generate(spec: dict, seed: int, devices, rehearse: bool = False) -> Problem:
    _refuse_without_label_lists()
    from distributed_sgd_tpu.data.rcv1 import Dataset

    n_features, nnz = int(spec["n_features"]), int(spec["nnz"])
    n_outputs, width = int(spec["n_outputs"]), int(spec["label_list_width"])
    noise = float(spec["label_noise"])
    prior = priors(spec)
    pad = (0, output_lanes(n_outputs) - n_outputs)
    z = jnp.asarray(np.pad(thresholds(prior, noise), pad), jnp.float32)
    flip = jnp.asarray(np.pad(flip_probability(prior, noise), pad), jnp.float32)

    def block_of(key, salt, block_rows):
        table = separators(salt, n_features, n_outputs)

        def one(b):
            kb = jax.random.fold_in(key, b)  # rcv1_like.generate's key of block b
            idx, val, _binary = rcv1_like.block(kb, salt, block_rows, n_features, nnz, 0.0)
            return (idx, val) + label_lists(
                jax.random.fold_in(kb, SEPARATOR_SALT), idx, val, table, z, flip,
                n_outputs, width)

        return one

    train, test, per_device = device_splits(spec, seed, devices, rehearse, block_of)
    over = int(np.asarray(train[3]).sum() + np.asarray(test[3]).sum())
    print("labels: " + json.dumps({
        "outputs": n_outputs, "list_width": width, "rows_cut_to_the_width": over,
        "expected_positives_a_row": float(prior.sum())}), flush=True)

    def split(arrays):
        return Dataset(*arrays[:3], n_features, n_labels=n_outputs)

    return Problem(train=split(train), test=split(test), n_features=n_features,
                   dim_sparsity=None, rows_per_device=per_device)
