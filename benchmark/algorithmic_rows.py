"""Flops and bytes a sparse SGD step *needs* when the weights carry an
output axis, from shapes and from the row generator's own law alone: the
count behind `row_step_roofline`.  Nothing is imported from the program,
and the work is the same whatever implements it.

One step on one device holding K (virtual) workers, each with a batch of B
rows of P stored entries and C labels a row, weights `W[D, C]`, under `l2`
(or no) regularisation with the plain update:

    W' = (1 - c) W  -  (lr / n) * sum over the step's entries of
                                   v_bp * e[i_bp] (outer) coeff_b

A stored entry reads its feature's C weights for the margins and adds a
C-wide update to them, so the unit the step touches is a weight ROW (C
words), and a row that several entries of the step name has to be read for
the margins, read for the update and written back only ONCE:

flops               2 K B P C for the margins + 2 K B P C for the updates
rows drawn          K B (8 P + 4 + label bytes): a row's indices and values,
                    the four bytes `algorithmic_entries` counts a row for
                    its draw, and its C labels as the program stores them
weight rows         12 C U: 4 C bytes a DISTINCT feature id read for the
                    margins, read and written for the update; U the
                    expected number of distinct ids among the step's K B P
                    draws under the generator's popularity law

No term in D: the first term of the update is one scalar for the whole
matrix, and a pass over `W` is a formulation's, not the algorithm's.  A
step that reads a row an entry (as a gather does) or passes over `W` moves
more than this and reads a lower share of the same count.
"""

from __future__ import annotations

import math

import numpy as np


def rank_prob(n_features: int) -> np.ndarray:
    """P(rank r), r = 1..D, of `benchmark/gen/rcv1_like.py`'s draw:
    ln(1 + 1/r) / ln(D + 1), as float64."""
    r = np.arange(1, n_features + 1, dtype=np.float64)
    return np.log1p(1.0 / r) / math.log(n_features + 1.0)


def expected_distinct(n_features: int, draws: int) -> float:
    """E[number of distinct feature ids among `draws` independent draws]
    = sum over ids of 1 - (1 - p_i)^draws."""
    p = rank_prob(n_features)
    return float(np.sum(-np.expm1(draws * np.log1p(-p))))


def step_flops(batch: int, workers_on_device: int, nnz: int, n_outputs: int) -> int:
    return 4 * int(workers_on_device) * int(batch) * int(nnz) * int(n_outputs)


def step_bytes(batch: int, workers_on_device: int, nnz: int, n_outputs: int,
               n_features: int, label_bytes: int = 1) -> float:
    """Bytes the whole step needs: the rows drawn with their labels, and
    every distinct weight row read twice and written once."""
    k, b, p, c = int(workers_on_device), int(batch), int(nnz), int(n_outputs)
    distinct = expected_distinct(int(n_features), k * b * p)
    return k * b * (8 * p + 4 + int(label_bytes) * c) + 12.0 * c * distinct


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of flops over the bf16 peak and bytes over
    HBM's peak."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bps"])
