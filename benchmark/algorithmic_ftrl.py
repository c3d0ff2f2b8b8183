"""Bytes a step of per-coordinate FTRL-Proximal *needs* in the synchronous
engine, from shapes alone: the count behind `ftrl_step_roofline`.  Nothing
is imported from the program, and the work is the same whatever implements
it.

One step on one device holding K (virtual) workers, each with a batch of B
rows of P stored entries.  Every coordinate carries two words of state,
z and n; its weight is a closed form of them, and the update touches only
the coordinates the step's entries name (McMahan et al., KDD 2013,
Algorithm 1):

rows drawn          K*B*(8*P + 4): indices, values and the label of a row
margins             every stored entry reads its index (4), its value (4)
                    and its coordinate's z and n (8)   -> 16 * K*B*P bytes
update              the same index and value (8), and z and n read (8) and
                    written back (8)                   -> 24 * K*B*P bytes

No term in the feature count D: the closed form is computed where a word
is read, and a coordinate no entry names is neither read nor written.  A
step that passes over all of the state (a dense gradient, a dense update)
moves more than this and reads a lower share of the same count.  As in
`algorithmic_entries`, these are random word accesses, bound by latency
long before bandwidth: the share says how far the step is from streaming
its entries.
"""

from __future__ import annotations

from benchmark.algorithmic_sparse import least_seconds  # noqa: F401  (bytes over HBM's peak)


def step_bytes(batch: int, workers_on_device: int, nnz: int) -> int:
    """Bytes the whole step needs: the rows drawn, the margins' reads of
    the state and the update's read-modify-write of it."""
    k, b, p = int(workers_on_device), int(batch), int(nnz)
    entries = k * b * p
    return k * b * (8 * p + 4) + 16 * entries + 24 * entries
