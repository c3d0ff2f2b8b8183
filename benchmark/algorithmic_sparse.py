"""Bytes a sparse SGD step over a large feature space *needs*, from shapes
alone: the counts behind `gather_scatter_roofline` and
`sparse_step_roofline`.  Nothing is imported from the program, and the
work is the same whatever implements it (one-hot matmuls, a row gather, a
kernel that walks the entries).

One step on one device holding K (virtual) workers, each with a batch of B
rows of P stored entries, over a weight vector of D floats, under an `l2`
regulariser (no per-feature vector to read, unlike `algorithmic.step_work`'s
sparse branch, which counts the `dim_sparsity` read):

gather (margins)    every stored entry reads its index (4), its value (4)
                    and one word of w (4)        -> 12 * K*B*P bytes
scatter (reply)     the same reads, and the word of the gradient is
                    written back (4)             -> 16 * K*B*P bytes
rows drawn          K*B*(8*P + 4): indices, values and the label of a row
w                   read once and written once for `l2` and the update: 8*D

Both pieces are random word accesses: a chip that moved every word at its
HBM bandwidth would be bound by these bytes, and a real chip is bound by
the latency of a 4-byte access long before.  The shares read a few percent
at best, and say how far the kernels are from streaming.
"""

from __future__ import annotations


def gather_scatter_bytes(batch: int, workers_on_device: int, nnz: int) -> dict:
    """{'gather', 'scatter'} bytes the entries of one step move."""
    entries = int(workers_on_device) * int(batch) * int(nnz)
    return {"gather": 12 * entries, "scatter": 16 * entries}


def step_bytes(batch: int, workers_on_device: int, n_features: int, nnz: int) -> int:
    """Bytes the whole step needs: the rows drawn, the entries' traffic,
    w read and written once."""
    k, b, p = int(workers_on_device), int(batch), int(nnz)
    entries = gather_scatter_bytes(b, k, p)
    return k * b * (8 * p + 4) + entries["gather"] + entries["scatter"] + 8 * int(n_features)


def least_seconds(n_bytes: int, peaks: dict) -> float:
    """The least time a chip with these peaks could take to move `n_bytes`."""
    return n_bytes / peaks["hbm_bps"]
