"""Bytes a sparse SGD step *needs* when its regulariser is linear in `w`,
from shapes alone: the count behind `entry_step_roofline`.  Nothing is
imported from the program, and the work is the same whatever implements it.

One step on one device holding K (virtual) workers, each with a batch of B
rows of P stored entries, under `l2` (or no) regularisation with the plain
update `w' = w - lr * mean`:

    w' = (1 - c) w  -  (lr / n) * sum over the step's entries of
                                   coeff_b * v_bp * e[i_bp]

The first term is one scalar for the whole vector, so a step has to touch
only the words its entries name.  The count therefore has **no term in the
feature count D** (`algorithmic_sparse.step_bytes` adds 8 D for "w read and
written once": 93 % of its count at D = 1,000,000, and at D = 54,686,452
more than a step takes):

rows drawn          K*B*(8*P + 4): indices, values and the label of a row
gather (margins)    every stored entry reads its index (4), its value (4)
                    and one word of w (4)             -> 12 * K*B*P bytes
update (scatter)    the same reads, and the word of w is written back (4)
                                                      -> 16 * K*B*P bytes

A step that does pass over all of `w` (a dense gradient, a dense
regulariser, a dense update) moves more than this and reads a lower share
of the same count: one yardstick for both.  As in `algorithmic_sparse`,
these are random word accesses, bound by latency long before bandwidth:
the share says how far the step is from streaming its entries.
"""

from __future__ import annotations

from benchmark.algorithmic_sparse import least_seconds  # noqa: F401  (bytes over HBM's peak)


def step_bytes(batch: int, workers_on_device: int, nnz: int) -> int:
    """Bytes the whole step needs: the rows drawn and the entries' traffic
    of the gather and of the update's read-modify-write."""
    k, b, p = int(workers_on_device), int(batch), int(nnz)
    entries = k * b * p
    return k * b * (8 * p + 4) + 12 * entries + 16 * entries
