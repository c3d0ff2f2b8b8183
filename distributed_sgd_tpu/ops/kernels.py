"""Which kernel family runs a shape: the one rule every engine asks.

Four families compute the same margins and the same worker reply
(models/linear.py dispatches on the name):

- 'dense'   rows without an index array: plain [B, D] products;
- 'mxu'     one-hot matmuls over the blocked weights (ops/mxu.py):
            R*128 MACs a stored entry, so the cost grows with D;
- 'gather'  a true gather / scatter over the blocked weights, one
            128-lane row an entry (ops/gather.py): no term in D;
- 'scalar'  XLA's take / scatter-add (ops/sparse.py): what a CPU runs
            fastest, and the reference-shaped fallback everywhere.

`choose_kernel` maps (feature count, row width, platform) to one of them;
`SyncEngine.bind` (and through it `LocalSGDEngine`), Hogwild's `_Worker`
and `core/worker.py` all ask it through `resolve`, which reads the
platform off the device and counts the answer.  An explicit `kernel=`
still overrides: the rule answers only for `AUTO`.
"""

from __future__ import annotations

from typing import Optional

from distributed_sgd_tpu.utils import metrics

AUTO = "auto"
KERNELS = ("mxu", "scalar", "gather", "dense")
# the families that keep w in the lane-blocked [R, 128] view (ops/mxu.py)
BLOCKED = ("mxu", "gather")
# the families whose scatter walks its entries one after the other, so K
# workers' batches scattered into ONE accumulator cost what they cost apart
# and the K accumulators' zero-fill and reduce are saved
# (models/linear.py `grad_workers`)
ONE_ACCUMULATOR = ("gather",)

# From this many features on, 'gather' runs where 'mxu' would.  Measured
# on a v5e, each family forced at both shapes (PERF.md section 6, PR 26;
# margins + scatter, ns a stored entry, 4 workers a step):
#
#     D = 47,236 (R = 376)    batch 100: mxu  2.3, gather 10.2
#                             batch 200: mxu  4.2, gather 10.2
#     D = 1,000,000 (R = 7,816) batch 100: mxu 28.7, gather  9.6
#
# 'gather' reads the same at both feature counts and both batches; the
# one-hot's MACs grow with R, so on the line through its two batch-100
# points the families cross near D = 3.3e5.  The crossing itself was not
# measured.  At the constant itself (D = 200,000, R = 1,568; one call of
# each side by `benches/onehot_call_sweep.py`, PERF.md section 6, PR 29)
# the one-hot pair costs 5.2 ns an entry at batch 100 and 4.8-5.1 at batch
# 200 (the batch-200 figure at D = 47,236 above was the scatter's
# contraction tiled by the compiler, which `mxu.scatter_shards` has ended:
# a step's entries are no input of this rule), half of what 'gather' pays:
# the constant sits under the crossing, between the two measured feature
# counts, a factor of four to five from either.
GATHER_MIN_FEATURES = 200_000


def choose_kernel(n_features: int, row_width: int, platform: str,
                  off_tpu: str = "scalar") -> str:
    """The kernel family for rows of `row_width` stored entries (0: the
    dense layout) over `n_features` features on `platform`.  `off_tpu` is
    the family the asking engine runs off the TPU, where nothing was
    measured: the sync engines the one-hot matmuls (the CPU tests of the
    blocked layout, its optimizer state and checkpoints run through them),
    Hogwild and the rpc worker the scalar path, a CPU's fastest.  The
    threshold goes with the one-hot wherever it runs: its R*128 MACs an
    entry are no platform's to escape (the CPU rehearsal of the
    1,000,000-feature cell takes 88 s on 'mxu' and 12 s on 'gather')."""
    if row_width == 0:
        return "dense"
    family = "mxu" if platform == "tpu" else off_tpu
    if family == "mxu" and n_features >= GATHER_MIN_FEATURES:
        return "gather"
    return family


def merges_margins(kernel: str, row_width: int) -> bool:
    """Whether the virtual workers of a device get their margins from ONE
    call on their merged batches (`LinearModel.grad_workers`): they all read
    the same `w`, so for sparse rows (`row_width` stored entries; 0: the
    dense layout) through the blocked kernels of ours nothing keeps them
    apart, and K calls batched over the workers cost K calls.  'scalar' and
    'dense' keep XLA's own batching.  Static per binding: `BoundSync` counts
    it under `bind.margins.merged`."""
    return row_width != 0 and kernel in ("mxu", "gather")


def resolve(kernel: Optional[str], n_features: int, row_width: int,
            device=None, off_tpu: str = "scalar") -> str:
    """What an engine binds: an explicit `kernel` as given (dense rows can
    only run 'dense'), the rule's answer on the platform of `device` (None:
    the process default backend) for AUTO / None.  `mxu.blocked_pays_off`
    is the platform probe: one policy for "is this a TPU", which tests
    steer.  Counted once a binding under `bind.kernel.<name>`."""
    if kernel in (None, AUTO) or row_width == 0:
        from distributed_sgd_tpu.ops import mxu

        platform = "tpu" if mxu.blocked_pays_off(device) else "cpu"
        chosen = choose_kernel(n_features, row_width, platform, off_tpu)
    else:
        chosen = kernel
    metrics.counter(f"bind.kernel.{chosen}").increment()
    return chosen
