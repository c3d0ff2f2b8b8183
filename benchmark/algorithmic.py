"""Operations and bytes one SGD step *needs*, from shapes alone.

A roofline share divides the least time the chip could take for the
algorithm's work by the time the device really spent.  The numerator must
therefore count what the algorithm requires, not what a formulation
spends: the one-hot matmuls of `ops/mxu.py` issue ~48k MACs per nonzero,
the algorithm needs two multiply-adds.  Everything here is arithmetic on
shapes; nothing is imported from the program.

One step on one device holding K (virtual) workers, each with a batch of B
rows, over a weight vector of D floats:

sparse rows (P stored entries per row)
  margins      K*B*P multiply-adds                     -> 2*K*B*P flops
  scatter      K*B*P multiplies (coeff*value) + adds   -> 2*K*B*P flops
  regularizer  per worker w.dim_sparsity (2*D) and the masked add (D)
  update       mean over workers folded into w - lr*g  -> 2*D flops
  bytes        the rows drawn: K*B*(P*(4+4) + 4); w read once and written
               once (8*D); the regularizer's vector read once (4*D)

dense rows (P == D, no index array)
  margins, gradient   2*K*B*D flops each
  regularizer (l2)    2*D per worker
  update              2*D
  bytes               K*B*(4*D + 4) rows; w read and written (8*D)

The per-worker gradients are an on-chip matter for the algorithm (they
need never reach HBM), so they carry no bytes here.
"""

from __future__ import annotations


def step_work(batch: int, workers_on_device: int, n_features: int,
              nnz: int, dense: bool) -> dict:
    """{'flops', 'bytes'} one step needs on one device."""
    k, b, d = int(workers_on_device), int(batch), int(n_features)
    if dense:
        flops = 4 * k * b * d + 2 * d * k + 2 * d
        bytes_ = k * b * (4 * d + 4) + 8 * d
    else:
        p = int(nnz)
        flops = 4 * k * b * p + 3 * d * k + 2 * d
        bytes_ = k * b * (8 * p + 4) + 12 * d
    return {"flops": flops, "bytes": bytes_}


def least_step_seconds(work: dict, peaks: dict) -> dict:
    """The least time a chip with these peaks could take for `work`, and
    which of the two bounds it ('flops' or 'bytes')."""
    t_flops = work["flops"] / peaks["bf16_flops"]
    t_bytes = work["bytes"] / peaks["hbm_bps"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return {"seconds": max(t_flops, t_bytes), "bound": bound,
            "flops_seconds": t_flops, "bytes_seconds": t_bytes}
