"""Blocked one-hot MXU kernels: sparse gather/scatter as matmuls.

XLA lowers a random scatter/gather over a 47k-float vector to a serialized
per-element loop on TPU (~13 ns/element measured — the whole hot path of
the reference's sync mode, SURVEY.md §3.5, is bound by it).  The TPU-native
answer is to reshape the weight vector into a lane-blocked matrix

    w2 = w padded to R*128, viewed as [R, 128]   (R = ceil(D/128), 8-aligned)

and express both sparse kernels as one-hot matmuls that run on the MXU
(systolic array) instead of the scalar path:

- gather:  w[idx[t]] = (onehot(idx[t]//128) @ w2)[t, idx[t]%128]
           -> M1 = OHR @ w2 on the MXU, then a lane-select against
           OHC = onehot(idx%128) on the VPU;
- scatter: sum_t v[t]*e_{idx[t]} = OHR^T @ (OHC * v[:,None])  — one MXU
           matmul producing the blocked gradient [R, 128] directly.

An entry pays R*128 ~ 48k MACs either way and no serial step.  On a TPU
v5e at D = 47,236 (R = 376) the gather costs 0.73 ns a stored entry
(`rcv1-sync-1chip`, 22.26 us for 4 x 100 x 76 entries; ledger, PR 28) and
the scatter 0.91 ns at batch 100 (27.76 us) and 0.90 ns at batch 200
(54.75 us for 60,800; my chip runs, PR 29).  What an entry of the scatter
costs is decided by the DEPTH of one contraction: the compiler keeps a
contraction of up to ~8,700 entries in one window and tiles a deeper one
into windows of 128, each paying a window's fixed cost (the one 15,200-deep
dot of batch 200 read 178.87 us, 2.94 ns an entry), so `scatter_add` cuts
its contraction into shards no deeper than that by a rule on the shape
(`scatter_shards`; PERF.md section 6, PR 29).  XLA's own scatter-add over
the same weights costs 6.9-8.0 ns an entry, one after the other
(ops/gather.py), and a sort plus `segment_sum` in its place lost by 10 x on
the chip (PERF.md section 6, PR 28): this file holds one formulation of
each side.  The one-hot matrices are built in registers by XLA (iota
compare) and fuse into the consuming matmul, sharded or not.

These kernels replace the reference's per-sample map arithmetic
(Sparse.scala:15-46, Slave.scala:147-153) on the training hot path; the
scalar-path kernels in ops/sparse.py remain the reference-shaped fallback
(`kernel='scalar'`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from distributed_sgd_tpu.ops.sparse import SparseBatch

LANES = 128
_SUBLANE = 8


def blocked_pays_off(device=None) -> bool:
    """One shared policy for 'do the blocked kernels pay off on this
    device?': yes on TPU (where they beat scalar scatter ~10x), no on CPU
    (where the scalar gather/scatter wins).  It is the platform probe of
    the kernel rules (ops/kernels.py `plan`, `resolve`), which decide WHICH
    blocked family from the shape.  Pass the pinned device when there is
    one; falls back to the process default backend."""
    platform = getattr(device, "platform", None)
    if platform is None:
        platform = jax.default_backend()
    return platform == "tpu"


def n_blocks(n_features: int) -> int:
    """Rows R of the blocked weight view: ceil(D/128), rounded up to a
    multiple of 8 so [R, 128] is exactly sublane x lane tiled."""
    r = -(-int(n_features) // LANES)
    return -(-r // _SUBLANE) * _SUBLANE


def to_blocked(w: jax.Array, n_features: int) -> jax.Array:
    """[D] -> [R, 128] (zero-padded).  Cheap: pad + reshape."""
    r = n_blocks(n_features)
    with jax.named_scope("dsgd.layout"):
        return jnp.pad(w, (0, r * LANES - n_features)).reshape(r, LANES)


def from_blocked(w2: jax.Array, n_features: int) -> jax.Array:
    """[R, 128] -> [D]."""
    with jax.named_scope("dsgd.layout"):
        return w2.reshape(-1)[:n_features]


def to_blocked_np(w: np.ndarray, n_features: int) -> np.ndarray:
    r = n_blocks(n_features)
    return np.pad(w, (0, r * LANES - n_features)).reshape(r, LANES)


class OneHotBatch:
    """The per-batch one-hot operands of the gather and scatter sides of a
    step.  All members are traced arrays, written once here; what compiles
    is NOT one shared build: XLA fuses an iota-compare build into each
    consuming matmul, so a step holds one `iota_compare_fusion` for the
    gather and one for the scatter, and a caller that makes the two sides
    from two `OneHotBatch`es (`LinearModel.grad_workers`: the margins of K
    workers in one call, their scatters apart) pays no second build.
    Which way round the compiler lays the gather's operand out decides what
    an entry of the gather costs (`lane_minor_rows`), how deep one
    contraction runs what an entry of the scatter costs (`scatter_shards`)."""

    def __init__(self, batch: SparseBatch, n_rows: int, dtype=jnp.float32):
        with jax.named_scope("dsgd.onehot"):
            flat_idx = batch.indices.reshape(-1)
            self.values = batch.values.astype(jnp.float32).reshape(-1)  # [T]
            self.ohr = jax.nn.one_hot(flat_idx // LANES, n_rows, dtype=dtype)  # [T, R]
            self.ohc = jax.nn.one_hot(flat_idx % LANES, LANES, dtype=dtype)  # [T, L]
        self.flat_idx = flat_idx  # [T], for a scatter that builds its own operands
        self.batch_size = batch.batch_size
        self.pad_width = batch.pad_width

    def gathered_products(self, w2: jax.Array) -> jax.Array:
        """[T] of values[t] * w[idx[t]] — the gather, via MXU."""
        with jax.named_scope("dsgd.margins"):
            m1 = jax.lax.dot(
                self.ohr, w2.astype(self.ohr.dtype), preferred_element_type=jnp.float32
            )  # [T, L]
            return jnp.sum(m1 * self.ohc.astype(jnp.float32), axis=-1) * self.values

    def margins(self, w2: jax.Array) -> jax.Array:
        """Per-sample dots x_b . w  (ops.sparse.matvec equivalent)."""
        products = self.gathered_products(w2)
        with jax.named_scope("dsgd.margins"):
            return products.reshape(self.batch_size, self.pad_width).sum(-1)

    def scatter_add(self, coeff: jax.Array) -> jax.Array:
        """Blocked sum_b coeff[b] * x_b -> [R, 128] (scatter_add equivalent):
        onehot(rows)^T @ (onehot(lanes) * c*v), the contraction over the
        batch's T entries cut into S = `scatter_shards(T, R)` equal shards:
        the S partial products come from ONE dot batched over the shard
        axis, each accumulated in f32, and are summed in f32.  The same
        products (operands as before: one bf16 pass on the MXU), the same
        f32 accumulation, the terms in another order; S = 1 is the one plain
        dot.  Where S does not divide T the entry axis is padded with
        entries of value 0 on index 0: a pad adds 0 to block 0.

        By the ledger (v5e, R = 376; PR 28) it is the largest piece of the
        sparse step; what an entry costs is decided by whether its
        contraction fits one of the compiler's windows (`scatter_shards`):
        the same dot in two f32 shards of 7,600 entries read 54.75 us a step
        of `rcv1-sync-b200` where the one 15,200-deep dot read 178.89
        (PERF.md section 6, PR 28).  BASELINE.md round 4's "batched shards
        run the whole step 8-15 % slower" (July, T = 22,800 in four shards)
        does not hold on this compiler: the iota-compare build fuses into
        the sharded dot as into the plain one.
        """
        with jax.named_scope("dsgd.scatter"):
            cv = (
                self.values.reshape(self.batch_size, self.pad_width)
                * coeff.astype(jnp.float32)[:, None]
            ).reshape(-1)
            shards = scatter_shards(cv.shape[0], self.ohr.shape[1])
            if shards == 1:
                contrib = self.ohc.astype(jnp.float32) * cv[:, None]  # [T, L]
                return jax.lax.dot(
                    self.ohr.T, contrib.astype(self.ohr.dtype),
                    preferred_element_type=jnp.float32
                )
            # the one-hot operands again, from the padded ids: a pad of the
            # [T, R] operand itself would be written out and read back
            dtype, n_rows = self.ohr.dtype, self.ohr.shape[1]
            pad = (0, -cv.shape[0] % shards)
            idx = jnp.pad(self.flat_idx, pad)
            ohr = jax.nn.one_hot(idx // LANES, n_rows, dtype=dtype)
            contrib = jax.nn.one_hot(
                idx % LANES, LANES, dtype=jnp.float32) * jnp.pad(cv, pad)[:, None]
            partials = jax.lax.dot_general(  # [S, R, L]
                ohr.reshape(shards, -1, n_rows),
                contrib.astype(dtype).reshape(shards, -1, LANES),
                (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
            )
            return partials.sum(axis=0)


# The chip runs the scatter's dot as a convolution that walks the contraction
# (the entries) in WINDOWS, one pipeline iteration a window (read off the
# programs compiled for a described v5e: `window_config` of the fusion).  Up
# to a depth the whole contraction is ONE window, and a window costs ~0.3 us
# + 0.9 ns an entry; past it the compiler tiles the contraction, in the sync
# step down to windows of 128 entries, every one paying the 0.3 us: an
# entry of `rcv1-sync-b200`'s 15,200-deep scatter cost 2.94 ns against 0.91
# at 7,600 (ledger, PR 28).  Cut into shards no deeper than one window, the
# shard axis is what gets tiled and every contraction stays whole.  Measured
# on a v5e, `BoundSync.epoch` at R = 376, 4 workers, us a step by batch (T
# entries a worker), the one dot against the best S (PERF.md section 6,
# PR 29):
#
#     batch 100 (T  7,600)  63.5        S = 2:  73.0
#     batch 110 (T  8,360)  65.5        S = 2:  76.9    <- one window still
#     batch 115 (T  8,740) 140.4        S = 2:  94.9    <- tiled by 128
#     batch 200 (T 15,200) 235.5        S = 2: 111.9  (S = 4: 113.6, S = 8: 140.0)
#     batch 400 (T 30,400) 454.9        S = 4: 210.0  (S = 2: 228.8, S = 3: 288.0)
#     batch 1,024 (77,824) 1,125.3      S = 9: 529.4  (S = 11: 536.8, S = 8: 562.0)
#
# Compiled, the step keeps one window through T = 8,664 at K = 4 and 8,892
# at K = 1; the constant is the deepest contraction MEASURED in one window.
# The window holds a fixed count of one-hot elements, not of entries: one
# window through T = 4,736 at R = 752 and 2,176 at R = 1,568 (compiled),
# and at R = 1,568 batch 100 reads 204.0 as one dot and 168.2 in four shards
# of 1,900 (batch 200: 378.0 / 304.9 in eight).  So the depth shrinks with R
# beyond the R it was measured at.  tests/test_row_placement.py holds the
# windows on the described v5e; the day it fails the compiler has changed
# and these two constants can go.
SCATTER_WINDOW_ENTRIES = 8_360
SCATTER_WINDOW_ROWS = 376


def scatter_depth(n_rows: int) -> int:
    """The deepest contraction (entries) `OneHotBatch.scatter_add` runs as
    one dot over `n_rows` blocked rows: SCATTER_WINDOW_ENTRIES up to
    SCATTER_WINDOW_ROWS rows, fewer in proportion beyond."""
    return max(1, SCATTER_WINDOW_ENTRIES * min(n_rows, SCATTER_WINDOW_ROWS) // n_rows)


def scatter_shards(n_entries: int, n_rows: int) -> int:
    """The shards S `OneHotBatch.scatter_add` cuts a contraction over
    `n_entries` entries into: the fewest that are no deeper than
    `scatter_depth(n_rows)`.  A function of the shape alone, static per
    binding: `BoundSync` counts S > 1 under `bind.scatter.sharded`."""
    return max(1, -(-int(n_entries) // scatter_depth(n_rows)))


# A call of the gather costs 0.63-0.67 ns a stored entry where the compiler
# builds its one-hot operand with the ENTRIES along the lanes, and 1.2-1.9 ns
# where it builds it entries-major (v5e, R = 376; PERF.md section 6, PR 27).
# Compiled for a described v5e, it takes the first layout when the entries
# are whole 128-lane tiles and more than 32,768 of them (at R = 376; the
# evaluation's 512 x 76 = 38,912 always were).  So a one-piece `matvec` pads
# its batch with empty rows up to such a count, where that costs at most 1/8
# more entries: 4 workers x 100 rows x 76 run as 448 rows in 24.0 us, not
# 49.2 (and not the 44.0 of four calls batched over the workers).
# tests/test_row_placement.py holds the layout; the day it fails the
# compiler has changed and these two constants can go.
LANE_MINOR_MIN_ENTRIES = 32_768
MATVEC_MAX_PADDING = 1.125


def lane_minor_rows(n_rows: int, width: int) -> int:
    """The rows a one-piece `matvec` of `n_rows` rows of `width` entries
    runs on: the next count whose entries are whole lanes and more than
    LANE_MINOR_MIN_ENTRIES, or `n_rows` where that is beyond
    MATVEC_MAX_PADDING times the entries."""
    whole = LANES // math.gcd(width, LANES)  # rows whose entries fill whole lanes
    enough = LANE_MINOR_MIN_ENTRIES // width + 1
    rows = -(-max(n_rows, enough) // whole) * whole
    return rows if rows <= MATVEC_MAX_PADDING * n_rows else n_rows


def matvec(batch: SparseBatch, w2: jax.Array) -> jax.Array:
    """Blocked matvec (margins) in ONE call of the gather, for the
    evaluation's chunks and the merged batches of a device's virtual
    workers; empty rows pad the batch where `lane_minor_rows` says so (a
    pad is entry 0 with value 0: it adds 0 * w[0] to a margin cut off)."""
    n = batch.batch_size
    rows = lane_minor_rows(n, batch.pad_width)
    if rows != n:
        with jax.named_scope("dsgd.onehot"):
            pad = ((0, rows - n), (0, 0))
            batch = SparseBatch(jnp.pad(batch.indices, pad), jnp.pad(batch.values, pad))
    margins = OneHotBatch(batch, w2.shape[0]).margins(w2)
    return margins if rows == n else margins[:n]


MATVEC_SUB = 512  # samples per sub-scan step of matvec_chunked


def matvec_chunked(batch: SparseBatch, w2: jax.Array) -> jax.Array:
    """`matvec` for batches of any size: whole multiples of MATVEC_SUB
    samples run as a sub-scan over MATVEC_SUB at a time, which bounds the
    [T, R] one-hot working set while keeping the matmuls large (the
    evaluation's 4,096-sample chunks); smaller or ragged batches go to
    `matvec` in one piece."""
    sub = MATVEC_SUB
    n = batch.batch_size
    if n <= sub or n % sub != 0:
        return matvec(batch, w2)

    def body(_, t):
        # the evaluation's rows, fetched a second time: on the TPU each
        # sub-chunk of 76-wide rows is also re-laid-out here (two
        # `[512,76]` copies), which `eval_rows_ms` is there to read.  No
        # step of a cell comes through the sub-scan (merged virtual workers
        # and batches up to MATVEC_SUB take `matvec` in one piece)
        with jax.named_scope("dsgd.eval_rows"):
            ci = jax.lax.dynamic_slice_in_dim(batch.indices, t * sub, sub, 0)
            cv = jax.lax.dynamic_slice_in_dim(batch.values, t * sub, sub, 0)
        return (), matvec(SparseBatch(ci, cv), w2)

    _, m = jax.lax.scan(body, (), jnp.arange(n // sub))
    return m.reshape(-1)


def scatter_add(batch: SparseBatch, coeff: jax.Array, n_rows: int) -> jax.Array:
    """Standalone blocked scatter-add."""
    return OneHotBatch(batch, n_rows).scatter_add(coeff)
