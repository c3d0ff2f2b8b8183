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

Weights with an output axis (`LinearModel.n_outputs` = C > 1: `W[D, C]`)
run 'dense', 'gather' (one weight row a FEATURE, the outputs on its lanes)
or 'scalar'; the one-hot family has no form with outputs.

`choose_kernel` maps (feature count, row width, platform, outputs) to one.
Hogwild's `_Worker` and `core/worker.py` ask it through `resolve`, which
reads the platform off the device and counts the answer.  An explicit
`kernel=` still overrides: the rule answers only for `AUTO`.

A sync binding (`SyncEngine.bind`, and through it `LocalSGDEngine`) asks
`plan` instead: one call that probes the platform once, asks every rule
below and counts the `bind.*` counters, and whose frozen `Plan` the engine,
the model and the kernels read.  The rules: `merges_margins` (the K virtual
workers' margins in one call), `ONE_ACCUMULATOR` (their entries scattered
into one gradient), `sparse_update` (no gradient at all: the entries
scattered into the carried weights, the regulariser a scalar on them, so
that a step's bytes have no term in the feature count), `merges_scatter`
(with an output axis, whether that scatter is one pass over the weights or
a walk of its entries that moves each touched row once), `margin_rows`
(with an output axis, how many samples one row gather of the margins takes)
and `margin_tiles` (with rows carried as tiles on a TPU, the piece of
samples whose distinct tiles the margin kernel fetches once each; where the
rows are the evaluation's, fixed for the binding, the kernel reads a plan of
them made at bind, `Fetch` 'planned').
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

from distributed_sgd_tpu.ops import gather, mxu
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
#
# With an output axis the one-hot family is out at any feature count: R x
# 128 x C MACs an entry, 4.96 M at `rcv1-topics-hinge`'s shape (R = 376,
# C = 103: 1.5 ms a side of a 30,400-entry step at the bf16 peak, for work
# that needs 6.3 MFLOP), so it was not built.  Of the two forms that were
# timed, one call of the whole step's two sides on a v5e
# (`benches/outputs_step_sweep.py`; PERF.md section 6, PR 32; us a call):
#
#     rows gathered and scattered ('gather')            408.0
#     the batch made dense, X[400, D], and two matmuls
#       in float32 (HIGHEST)                          1,207.8
#       in one bf16 pass                                853.9
#       (building X alone, XLA's scatter of 30,400 words: 903.3)
#
# so outputs go to 'gather' wherever the one-hot family would have run.
GATHER_MIN_FEATURES = 200_000


def choose_kernel(n_features: int, row_width: int, platform: str,
                  off_tpu: str = "scalar", n_outputs: int = 1) -> str:
    """The kernel family for rows of `row_width` stored entries (0: the
    dense layout) over `n_features` features and `n_outputs` outputs on
    `platform`.  `off_tpu` is
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
    if family == "mxu" and (n_features >= GATHER_MIN_FEATURES or n_outputs > 1):
        return "gather"
    return family


def merges_margins(kernel: str, row_width: int) -> bool:
    """Whether the virtual workers of a device get their margins from ONE
    call on their merged batches (`LinearModel.grad_workers`): they all read
    the same `w`, so for sparse rows (`row_width` stored entries; 0: the
    dense layout) through the blocked kernels of ours nothing keeps them
    apart, and K calls batched over the workers cost K calls.  'scalar' and
    'dense' keep XLA's own batching.  Static per binding: `plan` counts it
    under `bind.margins.merged`."""
    return row_width != 0 and kernel in ("mxu", "gather")


# From this many features on, a sync binding of a walking family takes the
# sparse step (`sparse_update`).  Measured on a v5e, `BoundSync.epoch` at 4
# workers x batch 100, rows of 11 one-hot entries, each form forced
# (`benches/sparse_update_sweep.py`; PERF.md section 6, PR 30; us a step):
#
#     D = 1,000,000    sparse  64.7   dense    65.1
#     D = 2,000,000            66.2            68.0
#     D = 4,000,000            69.0            74.2
#     D = 8,000,000           384.4           402.6
#     D = 16,000,000          384.7           613.6
#     D = 54,686,452          430.8         1,722.3
#
# The sparse step is never behind.  Up to 4e6 features `w` is served from
# on-chip memory and the dense passes (zero-fill, regulariser, update: 16 B
# a feature) cost 0.4 to 5 us of a 65-74 us step; from 8e6 on every word
# access of either form goes to HBM (+320 us a step) and the dense passes
# add 27.5 us a million features.  The table is PR 30's, when the sparse
# step scattered single words with XLA's scatter-add; since PR 31 it sums a
# step's entries by weight row and writes each touched row once
# (ops/gather.py `scatter_into`: 76 us of `kdd2012-sync-1chip`'s step where
# the word scatter took 384).  PR 30 set the constant for rounding: the
# word scatter added a hot id's ~200 increments to its weight one by one,
# each rounded at the weight's ulp, where the dense step sums them in a
# zeroed accumulator first (`criteo-logistic`'s step check, D = 1e6, limit
# 2e-5 on the update's relative error, read 4.4e-7..2.3e-6 dense and 2.4e-5
# sparse).  The row sums are the accumulator's order of operations, so a
# hot id's increments no longer separate the forms (what is left of the
# sparse step's error on `kdd2012-logistic`, 1.9e-5..2.8e-5 of the update,
# is the scale it carries beside the weights: 0 or 1 ulp on a hot weight);
# what does, under the constant, is speed: where `w` is on-chip the word
# scatter-add is the cheaper one (4,400 entries at D = 1e6: 48.3 us a call
# against the row scatter's 63.5), and `criteo-sync-1chip` with the sparse
# step forced trains 1,512,447 samples/s against 1,842,109 (its scatter
# 153.4 us a step against 107.5 + 1.8 of update; `update_rel_err` 1.7e-5,
# inside its limit of 2e-5 with little room; my chip runs, PR 31).  The
# constant stays where the dense passes start to cost more than a
# twentieth of the step; where the forms cross between 4e6 and 8e6
# features is ROADMAP D4's to measure.
#
# With an output axis the floor is on the WORDS of `W` the dense passes
# would touch, D x C.  Re-read at `rcv1-topics-hinge`'s shape (D = 47,236,
# C = 103: 4,865,308 words; a `W2` of 24.2 MB on 128 lanes), `BoundSync.epoch`
# over 409,600 of its rows, 4 workers x batch 100, each form forced
# (`benches/outputs_step_sweep.py`; PERF.md section 6, PR 32; us a step):
#
#     sparse (the entries' rows into the carry)  428.0
#     dense (XLA's scatter-add of 30,400 rows into zeros, the passes)  436.7
#
# The sparse step is ahead by 2 %, as the table above has it at 4e6 words
# (69.0 / 74.2): the constant holds for words as it held for features.
# Since PR 35 that sparse step ends in the merge pass (`merges_scatter`) and
# reads 288.3 against the dense form's 434.9 (the same sweep, PR 35).
SPARSE_UPDATE_MIN_FEATURES = 4_000_000


def sparse_update(kernel: str, regularizer: str, optimizer: str,
                  decay: float, n_features: int, n_outputs: int = 1) -> bool:
    """Whether a sync binding's step never materialises a gradient: the
    workers' replies stay (id, coefficient x value) entries up to the
    reduction and are scattered straight into the carried weights, and the
    regulariser's term is a scalar on them (`BoundSync._sparse_step`), so
    the step's device bytes have no term in the feature count.  That is the
    update `w' = (1 - decay) w - (lr / n) sum of the entries` and nothing
    else, so it holds for a family whose scatter walks entries
    (`ONE_ACCUMULATOR`), under a regulariser that is linear in `w` ('l2':
    `decay` = 2 lr lam a step; 'none': 0), with the reference update
    (`optimizer` 'sgd'; an optax optimizer, 'optax', reads a whole
    gradient) and a decay that leaves the sign of `w` (under 1), from
    SPARSE_UPDATE_MIN_FEATURES words of weights on (`n_features` x
    `n_outputs`).  'ftrl' (ops/ftrl.py) takes it too: its update is
    per-coordinate, the touched rows of its (z, n) state are what a step's
    entries name, and its L2 term lives in the closed form (`decay` 0).
    'dim_sparsity' masks each reply by its own support and keeps the dense
    step, as the one-hot, dense and scalar families do.  Static per binding:
    `plan` counts it under `bind.update.sparse`."""
    return (kernel in ONE_ACCUMULATOR and regularizer in ("l2", "none")
            and optimizer in ("sgd", "ftrl") and 0.0 <= decay < 1.0
            and n_features * n_outputs >= SPARSE_UPDATE_MIN_FEATURES)


# Up to this many weight rows a step's entry, an output-axis binding's
# sparse step ends in the merge pass (`merges_scatter`).  Timed on a v5e,
# one call of `gather.scatter_rows_into` with each ending on 30,400 entries
# (ids under the generator's law at every D'), `W2 [D', 128]`, us a call
# (`benches/outputs_step_sweep.py --only merge`; my chip runs, PR 37: the
# walk of the sorted factors, `gather._sum_runs_into`, the other ending since
# then; what it replaced, a DMA a row of entry rows summed on the MXU, beside
# it, as PR 35 timed it too):
#
#     D' =  47,240 (1.55 rows an entry)  the walk 370.7  a DMA a row 352.2  merged 216.6
#     D' =  94,480 (3.1)                          393.5              371.0         270.7
#     D' = 141,720 (4.7)                          405.3              404.9         320.7
#     D' = 188,960 (6.2)                          418.3              418.3         432.7
#     D' = 377,920 (12.4)                         603.4              572.6         729.6
#
# The walk has no term in D' but the ids the law spreads over more rows
# (and `W2` leaving on-chip memory); the pass moves every row of `W2`
# through VMEM and multiplies every 128-row piece a chunk's ids reach, ~2 us
# a thousand rows.  They cross just under 6.2 rows an entry, where PR 35 read
# the crossing against the DMA a row (on 512 B rows the walk is what that
# was, to 5 %: a row is one sublane and not a register there, and both are
# bound by the row DMAs); the constant sits under the crossing, between
# points where the pass is 21 and 3 % ahead.
# `rcv1-topics-hinge` asks at 1.55 (47,236 features, 4 x 100 x 76 entries);
# `amazoncat13k-dismec` at 7.1 and on 1,024 lanes (the walk, twice over);
# `kdd2012-logistic`'s words would read 12,400 and never ask (one output).
MERGE_MAX_ROWS_PER_ENTRY = 4
# Lanes of a weight row up to which the pass's blocks fit the VMEM a kernel
# may use (`gather.merge_block`: four buffers of at least 512 rows)
MERGE_MAX_LANES = 512


def merges_scatter(n_features: int, n_outputs: int, n_entries: int) -> bool:
    """Whether the sparse step of weights with an output axis adds a step's
    sorted entries to `W2` in ONE streaming pass over it
    (`gather._merge_rows`: every block of weight rows through VMEM once, its
    band of the entries placed by a 0 / 1 product on the MXU) instead of
    fetching, adding to and writing back every touched row by itself
    (`gather._sum_runs_into`, two DMAs a row): where `W2` is small beside what a
    step touches, `n_features` rows against `n_entries` entries of ALL the
    mesh's workers.  From shapes alone; a TPU's question (`plan` asks once
    a binding where a kernel of ours would run and counts the answer under
    `bind.scatter.merge`, or `bind.scatter.runs`)."""
    return (n_features <= MERGE_MAX_ROWS_PER_ENTRY * n_entries
            and gather.output_lanes(n_outputs) <= MERGE_MAX_LANES)


# Bytes of gathered weight rows ONE row gather of the output-axis margins
# holds at most (`margin_rows`).  `gather.matvec_rows` gathers a row an
# entry, entry-major, and sums a sample's rows: [P B, L] float32 written and
# read once.  The evaluation's chunk of 4,096 samples is 159 MB of them at
# `rcv1-topics-hinge`'s shape (76 entries, 128 lanes: one gather, 285 GB/s,
# PERF.md section 5) and would be 1.2 GB at 72 entries and 1,024 lanes; a
# step's 400 samples are 118 MB there.  The constant sits just above the
# largest gather the chip has run well, so neither of those moves, and a
# wider chunk is cut into pieces of at most that (PERF.md section 6, PR 36).
GATHERED_ROWS_MAX_BYTES = 160 * 2 ** 20


def margin_rows(samples: int, row_width: int, lanes: int) -> int:
    """Samples a row gather of `gather.matvec_rows` takes at once, for a
    batch of `samples` rows of `row_width` stored entries against weight
    rows of `lanes` lanes: `samples` halved until a piece's gathered rows
    fit GATHERED_ROWS_MAX_BYTES (or it is odd).  From shapes alone; the
    pieces run one after the other (`lax.map`)."""
    piece = int(samples)
    while piece % 2 == 0 and piece * row_width * lanes * 4 > GATHERED_ROWS_MAX_BYTES:
        piece //= 2
    return piece


# The margin kernel (`gather._margin_tiles`: each distinct weight tile of a
# piece of samples fetched once by DMA, a sample's tiles summed in a
# register) takes weight rows of at least MARGIN_TILES_MIN_LANES lanes, in
# pieces whose worst case (every entry its own tile: a cache of one tile an
# entry) fits MARGIN_VMEM_BYTES, and whose factors fit MARGIN_SMEM_BYTES of
# scalar memory (a v5e has 128 MiB and 1 MiB).  Timed on a v5e, one call of
# `gather.matvec_rows` with its sort inside, 72 entries a row under the
# generator's law over 203,882 features, tiles `[203,888, L / 128, 128]`,
# us a call (`benches/outputs_step_sweep.py --only margins`; my chip runs,
# PR 40; the kernel at 72 entries a turn of its walks):
#
#                   XLA's gather + sum   S = 64 / 128 / 256 (the evaluation's
#                                        4,096 samples)  S = 100 / 200 (a step's 400)
#     1,024 lanes        6,998 / 568        4,313 / 3,954 / 3,708      436 / 436
#       512 lanes        5,104 / 273                3,966 / 3,720      434 / 437
#       256 lanes        4,308 / 267                3,960 / 3,718      434 / 438
#
# (S = 64 on the two-word sort: its call read 4,313 against 3,804 at 256.)
# The kernel is bound by its scalar core, ~15 ns a distinct tile's DMA and
# ~5.5 ns an entry walked whatever the lanes, so it wins where XLA's gather
# pays for 4 KB a row, and at 512 lanes and below a step's 400 samples are
# faster through XLA; the largest piece that fits is the fastest (a piece's
# entries that are distinct tiles: 0.549 / 0.492 / 0.436 at S = 64 / 128 /
# 256).  Two caches, the next piece fetched while one is walked, did not pay
# in either order of the DMAs and the walk (4,087 and 4,430 against 4,040 at
# S = 128).
MARGIN_TILES_MIN_LANES = 1024
MARGIN_VMEM_BYTES = 96 * 2 ** 20
MARGIN_SMEM_BYTES = 512 * 2 ** 10


def margin_tiles(samples: int, row_width: int, lanes: int) -> int:
    """Samples a piece of the margin kernel takes, for a call of
    `gather.matvec_rows` on `samples` rows of `row_width` stored entries
    against weight tiles of `lanes` lanes: `samples` halved until the
    piece's worst case fits (0: it never does, or the rows are narrower
    than MARGIN_TILES_MIN_LANES, and XLA's gather takes the margins).  From
    shapes alone; a TPU's question (`plan` asks once a binding and counts
    the kernel under `bind.margins.tiles`)."""
    if lanes < MARGIN_TILES_MIN_LANES:
        return 0
    tile = -(-lanes // 1024) * 4096  # VMEM bytes: whole registers of 8 x 128 words
    piece = int(samples)
    # scalar words an entry: two pieces' ids and positions, values, slots, ids fetched
    while (tile * (piece * row_width + 2 * piece) > MARGIN_VMEM_BYTES
           or 4 * 7 * piece * row_width > MARGIN_SMEM_BYTES):
        if piece % 2:
            return 0
        piece //= 2
    return piece


class Fetch(NamedTuple):
    """How a call of the output-axis margins (`gather.matvec_rows`) reads
    weight rows: 'gather' (XLA's, `piece` samples a gather: `margin_rows`),
    'distinct' (the margin kernel, pieces of `piece`: `margin_tiles`) or
    'planned' (the margin kernel on rows fixed for the binding, the
    evaluation's chunks: it reads the plan of their pieces the binding made
    once, `gather.plan_pieces`, and sorts and walks no ids)."""
    how: str
    piece: int


def _family(kernel: Optional[str], n_features: int, row_width: int, on_tpu: bool,
            off_tpu: str, n_outputs: int) -> str:
    """`kernel` as named (dense rows run 'dense'), else the rule's answer."""
    if kernel in (None, AUTO) or row_width == 0:
        platform = "tpu" if on_tpu else "cpu"
        kernel = choose_kernel(n_features, row_width, platform, off_tpu, n_outputs)
    metrics.counter(f"bind.kernel.{kernel}").increment()
    return kernel


def resolve(kernel: Optional[str], n_features: int, row_width: int,
            device=None, off_tpu: str = "scalar", n_outputs: int = 1) -> str:
    """What Hogwild's `_Worker` and the rpc worker bind, on the platform of
    `device` (None: the default backend) as `mxu.blocked_pays_off` says."""
    on_tpu = mxu.blocked_pays_off(device)
    return _family(kernel, n_features, row_width, on_tpu, off_tpu, n_outputs)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Every kernel decision of one sync binding, made once by `plan`: what
    `BoundSync` compiles, what `LinearModel` and the kernels are handed and
    what the `train split:` record says.  `scatter`: the sparse step's
    ending, a kernel of ours on a TPU ('merge' / 'runs' with outputs, by
    `merges_scatter`; 'rows' without), else 'words' (XLA's); `tiles`: wide
    weight rows carried as tiles (`gather.to_tiles`); `lanes`: of a weight
    row of outputs (0: none), as the margins and stored labels have them;
    `step_fetch` / `eval_fetch`: a step's K x B samples a device (drawn
    anew every step), an evaluation chunk (rows fixed for the binding:
    'planned' where the kernel reads them); `decay`: what a step takes off every coordinate;
    `optimizer`: the update, 'sgd' (the reference's), 'ftrl' (the state
    (z, n) carried where the weights would be, ops/ftrl.py) or 'optax'."""

    kernel: str
    margins: str
    scatter_shards: int
    update: str
    scatter: str
    tiles: bool
    lanes: int
    step_fetch: Fetch
    eval_fetch: Fetch
    labels: str
    outputs: int
    decay: float
    optimizer: str

    def record(self) -> str:
        """The binding's fields of the `train split:` record."""
        return (f"kernel={self.kernel} margins={self.margins} "
                f"scatter_shards={self.scatter_shards} update={self.update} "
                f"scatter={self.scatter} outputs={self.outputs} labels={self.labels} "
                f"eval_rows={self.eval_fetch.piece} margin_fetch={self.eval_fetch.how} "
                f"optimizer={self.optimizer}")


OPTIMIZERS = ("sgd", "ftrl", "optax")


def plan(model, *, learning_rate: float, optimizer: str, row_width: int,
         virtual_workers: int, batch_size: int, n_workers: int, eval_chunk: int,
         lists: bool = False, riding: bool = False, kernel: Optional[str] = AUTO,
         device=None) -> Plan:
    """The plan of a sync binding of `model` (rows of `row_width` entries, 0:
    dense; labels as id `lists`, `riding` in a stored row, or gathered;
    `optimizer` one of OPTIMIZERS); `kernel` names a family or is AUTO.
    `device`'s platform is probed once (`mxu.blocked_pays_off`); every
    decision is counted here under `bind.*`."""
    if kernel not in (None, AUTO) + KERNELS:
        raise ValueError(
            f"kernel must be one of {KERNELS} (or {AUTO!r}: the rule on shape "
            f"and platform), got {kernel!r}")
    if optimizer not in OPTIMIZERS:
        raise ValueError(f"optimizer must be one of {OPTIMIZERS}, got {optimizer!r}")
    d, c = model.n_features, model.n_outputs
    # each of the n workers adds 2 lam w to its reply; the update is lr x their
    # mean (FTRL's L2 term lives in its closed form: no decay)
    decay = (2.0 * learning_rate * model.lam
             if model.regularizer == "l2" and optimizer != "ftrl" else 0.0)
    on_tpu = mxu.blocked_pays_off(device)
    kernel = _family(kernel, d, row_width, on_tpu, "mxu", c)
    sparse = sparse_update(kernel, model.regularizer, optimizer, decay, d, c)
    lanes = gather.output_lanes(c) if kernel == "gather" and c > 1 else 0
    ours = sparse and on_tpu  # a kernel of ours ends the sparse step
    merge = ours and c > 1 and merges_scatter(
        d, c, n_workers * virtual_workers * batch_size * row_width)
    tiles = lanes > gather.LANES and not merge

    def fetch(samples, distinct, fixed=False):
        piece = margin_tiles(samples, row_width, lanes) if distinct else 0
        return Fetch("planned" if fixed else "distinct", piece) if piece else Fetch(
            "gather", margin_rows(samples, row_width, lanes))

    # the evaluation's rows never change: their pieces are planned once
    eval_fetch = fetch(eval_chunk, ours and tiles, fixed=True)
    merged = virtual_workers > 1 and merges_margins(kernel, row_width)
    decided = Plan(
        kernel=kernel, margins="merged" if merged else "per_worker",
        scatter_shards=(mxu.scatter_shards(batch_size * row_width, mxu.n_blocks(d))
                        if kernel == "mxu" else 1),
        update="sparse" if sparse else "dense",
        scatter="merge" if merge else ("runs" if c > 1 else "rows") if ours else "words",
        tiles=tiles, lanes=lanes,
        # the step takes the margin kernel where the evaluation's chunk does
        step_fetch=fetch(virtual_workers * batch_size, eval_fetch.how == "planned"),
        eval_fetch=eval_fetch, labels="lists" if lists else "in_row" if riding else "gathered",
        outputs=c, decay=decay, optimizer=optimizer)
    for name, counted in (("outputs.multi", c > 1), ("margins.merged", merged),
                          ("optimizer.ftrl", optimizer == "ftrl"),
                          ("scatter.sharded", decided.scatter_shards > 1),
                          (f"labels.{decided.labels}", True), ("update.sparse", sparse),
                          (f"scatter.{decided.scatter}", ours),
                          ("margins.tiles", eval_fetch.how == "planned"),
                          ("margins.planned", eval_fetch.how == "planned")):
        if counted:
            metrics.counter(f"bind.{name}").increment()
    return decided
