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

Per element this costs R*128 ≈ 48k MACs — and still beats the scalar
scatter ~13x on measured throughput (~1 ns vs ~13 ns per element), because
the MXU runs at tens of TFLOP/s while the scalar path runs at ~75M
elements/s.  The one-hot matrices are built in-registers by XLA (iota
compare) and fuse into the surrounding step, so a full SGD step (gather +
hinge + scatter + update) measures ~27 us vs ~110 us for the scalar path
at RCV1 shapes (B=100, P=76).

These kernels replace the reference's per-sample map arithmetic
(Sparse.scala:15-46, Slave.scala:147-153) on the training hot path; the
scalar-path kernels in ops/sparse.py remain the reference-shaped fallback
(`kernel='scalar'`).
"""

from __future__ import annotations

import contextlib
import logging
import math
import threading
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_sgd_tpu.ops.sparse import SparseBatch

log = logging.getLogger("dsgd.mxu")

LANES = 128
_SUBLANE = 8

# -- selectable scatter formulations (DSGD_SCATTER; ROADMAP item 2) --------
#
# The scatter side of the fused step is the measured MXU bottleneck
# (BASELINE.md roofline: single [R, 128] output block, 3 MXU tiles fed by a
# T-deep contraction).  Round 4 measured and rejected the wide-output
# reshape; this round ships a third sweep as a SELECTABLE backend so the
# fused A/B harness (benches/scatter_wide.py --fused-ab) can rematch the
# formulations on real hardware and `auto` can promote a measured winner
# at runtime:
#
# - 'onehot'   (default): the shipped single deep-contraction one-hot
#   matmul — knobs-off training is byte-identical to every prior round.
# - 'segment'  sort-by-index + jax.ops.segment_sum into the blocked rows:
#   contributions sorted by flat feature id, one sorted segment-sum into
#   the [R*128] flat view.  No one-hot operands at all on the scatter
#   side (the gather still builds OHR/OHC; XLA drops the unused scatter
#   operand).
# - 'twostage' per-lane spread, then block add: stage 1 forms the [T, 128]
#   per-lane contribution rows on the VPU (OHC * values — the same
#   operand the one-hot matmul contracts), stage 2 segment-reduces the
#   rows by block id (sorted) instead of paying the T-deep matmul.
# - 'bf16'     the one-hot contraction with bf16 ACCUMULATION: the
#   contraction is split into two shards, each accumulated in bf16
#   (preferred_element_type=bfloat16 — half the accumulator traffic of
#   the f32-accumulate pass), with the final cross-shard add in f32.
#   Numerics: ~3 decimal digits per partial sum — parity holds to a
#   tolerance bound, not bit-exactness (tests/test_kernel_edge_shapes.py
#   pins the bound).
#
# All formulations compute sum_b coeff[b] * x_b on the blocked [R, 128]
# view; 'onehot'/'segment'/'twostage' agree up to float summation order,
# 'bf16' to the documented tolerance.  The active formulation is a
# process-wide knob (config.py DSGD_SCATTER -> main.py -> engines) read at
# TRACE time: set it before building engines/jitted fns (main.py does),
# or scope it with `scatter_formulation(...)` around engine construction
# the way the benches and tests do.

SCATTER_FORMULATIONS = ("onehot", "segment", "twostage", "bf16")

_scatter_lock = threading.Lock()
_active_scatter = "onehot"


def set_scatter_formulation(name: str) -> None:
    """Select the process-wide scatter formulation (trace-time dispatch).

    Call before building engines / jitted functions: already-compiled
    programs keep the formulation they were traced with."""
    if name not in SCATTER_FORMULATIONS:
        raise ValueError(
            f"scatter formulation {name!r} must be one of "
            f"{SCATTER_FORMULATIONS} (or 'auto' via "
            f"resolve_scatter_formulation)")
    global _active_scatter
    with _scatter_lock:
        _active_scatter = name


def active_scatter_formulation() -> str:
    return _active_scatter


@contextlib.contextmanager
def scatter_formulation(name: str):
    """Scoped formulation override (benches/tests): build + trace engines
    inside the block; restores the previous selection on exit."""
    prev = _active_scatter
    set_scatter_formulation(name)
    try:
        yield
    finally:
        set_scatter_formulation(prev)


# 'auto' measurements, keyed by (backend, batch, nnz, n_features) — one
# runtime rematch per process per shape
_AUTO_CACHE: Dict[Tuple, str] = {}


def resolve_scatter_formulation(
    name: str,
    batch_size: int = 100,
    nnz: int = 76,
    n_features: int = 47_236,
    reps: int = 2,
) -> str:
    """'auto' -> the formulation measured fastest ON THIS DEVICE at the
    given step shape (chained-scan slope over the fused gather+scatter
    body, the harness methodology); anything else passes through.

    The rematch runs once per process per shape (~seconds) and its pick is
    logged; the default config never calls this — DSGD_SCATTER defaults to
    'onehot', so knobs-off behavior stays byte-identical."""
    if name != "auto":
        if name not in SCATTER_FORMULATIONS:
            raise ValueError(
                f"DSGD_SCATTER={name!r} must be 'auto' or one of "
                f"{SCATTER_FORMULATIONS}")
        return name
    key = (jax.default_backend(), int(batch_size), int(nnz), int(n_features))
    if key in _AUTO_CACHE:
        return _AUTO_CACHE[key]
    import time as _time

    r = n_blocks(n_features)
    rng = np.random.default_rng(0)
    idx = jnp.asarray(np.sort(
        rng.integers(0, n_features, (batch_size, nnz)).astype(np.int32), axis=1))
    val = jnp.asarray(np.abs(rng.normal(size=(batch_size, nnz))).astype(np.float32))
    batch = SparseBatch(idx, val)

    def _slope(form: str) -> float:
        with scatter_formulation(form):
            def body(c):
                oh = OneHotBatch(batch, r)
                coeff = oh.margins(jnp.zeros((r, LANES), jnp.float32)) + c[:batch_size, 0]
                g = oh.scatter_add(coeff)
                return c + 1e-30 * g[0, 0]

            def looped(iters):
                f = jax.jit(lambda c: jax.lax.scan(
                    lambda cc, _: (body(cc), None), c, None, length=iters)[0])
                jax.block_until_ready(f(val))
                best = float("inf")
                for _ in range(reps):
                    t0 = _time.perf_counter()
                    jax.block_until_ready(f(val))
                    best = min(best, _time.perf_counter() - t0)
                return best

            lo, hi = 8, 24
            return max(looped(hi) - looped(lo), 1e-12) / (hi - lo)

    times = {form: _slope(form) for form in SCATTER_FORMULATIONS}
    winner = min(times, key=times.get)
    log.info(
        "DSGD_SCATTER=auto rematch on %s (B=%d, nnz=%d, D=%d): %s -> %s",
        key[0], batch_size, nnz, n_features,
        {f: f"{t * 1e6:.1f}us" for f, t in times.items()}, winner)
    _AUTO_CACHE[key] = winner
    # surface the rematch OUTCOME beyond the log line (ROADMAP item 2
    # follow-up): a process-global gauge (value indexes
    # SCATTER_FORMULATIONS, scraped by /metrics exporters), a trace event
    # (no-op unless a trace is active), and a flight record so a
    # post-mortem dump attributes which formulation the process ran.
    # fit_sync and WorkerNode additionally stamp their OWN registries at
    # fit/build time — the per-fit attribution the bench gates read.
    from distributed_sgd_tpu import trace as _trace_mod
    from distributed_sgd_tpu.trace import flight as _flight
    from distributed_sgd_tpu.utils import metrics as _metrics_mod

    _metrics_mod.global_metrics().gauge(
        _metrics_mod.SCATTER_FORMULATION).set(
            SCATTER_FORMULATIONS.index(winner))
    _trace_mod.event(_trace_mod.EVENT_SCATTER_SELECTED, formulation=winner,
                     backend=key[0])
    _flight.record("scatter.rematch", formulation=winner, backend=key[0],
                   batch=int(batch_size), nnz=int(nnz),
                   n_features=int(n_features))
    return winner


def blocked_pays_off(device=None) -> bool:
    """One shared policy for 'do the blocked kernels pay off on this
    device?': yes on TPU (where they beat scalar scatter ~10x), no on CPU
    (where the scalar gather/scatter wins).  It is the platform probe of
    the kernel rule (ops/kernels.py `resolve`), which decides WHICH
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
    an entry costs (`lane_minor_rows`)."""

    def __init__(self, batch: SparseBatch, n_rows: int, dtype=jnp.float32):
        with jax.named_scope("dsgd.onehot"):
            flat_idx = batch.indices.reshape(-1)
            self.flat_idx = flat_idx  # [T] flat feature ids (segment formulations)
            self.n_rows = n_rows
            self.values = batch.values.astype(jnp.float32).reshape(-1)  # [T]
            self.ohr = jax.nn.one_hot(flat_idx // LANES, n_rows, dtype=dtype)  # [T, R]
            self.ohc = jax.nn.one_hot(flat_idx % LANES, LANES, dtype=dtype)  # [T, L]
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
        """Blocked sum_b coeff[b] * x_b -> [R, 128] (scatter_add equivalent).

        Dispatches on the process-wide scatter formulation (module
        docstring; DSGD_SCATTER).  The default, 'onehot', stays the single
        deep-contraction dot ON MEASUREMENT (benches/scatter_wide.py +
        BASELINE.md rounds 4/6, raw JSON in benches/results/scatter_*.json):
        splitting the contraction into S=4 batched shards (a [4, R, 128]
        wide output footprint) runs the ISOLATED scatter 1.7-4.8x faster
        below the T ~ 32k crossover — but regresses the FUSED training
        step 8-15% in an interleaved same-chip A/B, because the sharded
        layouts break the iota-compare one-hot fusion the single dot
        shares with the gather.  Measured rejections, not estimates; the
        round-6 formulations stay selectable for the next hardware
        rematch (`--fused-ab`).
        """
        with jax.named_scope("dsgd.scatter"):  # every formulation, named once
            cv = (
                self.values.reshape(self.batch_size, self.pad_width)
                * coeff.astype(jnp.float32)[:, None]
            ).reshape(-1)
            form = _active_scatter
            if form == "segment":
                return _scatter_segment(self.flat_idx, cv, self.n_rows)
            if form == "twostage":
                return _scatter_twostage(
                    self.flat_idx, self.ohc.astype(jnp.float32), cv, self.n_rows)
            if form == "bf16":
                return _scatter_bf16(self.ohr, self.ohc, cv)
            contrib = self.ohc.astype(jnp.float32) * cv[:, None]  # [T, L]
            return jax.lax.dot(
                self.ohr.T, contrib.astype(self.ohr.dtype),
                preferred_element_type=jnp.float32
            )


def _scatter_segment(flat_idx: jax.Array, cv: jax.Array, n_rows: int) -> jax.Array:
    """'segment': sort-by-index + one sorted segment-sum into the flat
    [R*128] view.  Sorting first lets XLA lower the segment reduction over
    monotone ids instead of a random scatter; pads (index 0, value 0)
    contribute exactly 0 to feature 0 like every other formulation."""
    order = jnp.argsort(flat_idx)
    flat = jax.ops.segment_sum(
        cv[order], flat_idx[order],
        num_segments=n_rows * LANES, indices_are_sorted=True)
    return flat.reshape(n_rows, LANES)


def _scatter_twostage(flat_idx: jax.Array, ohc: jax.Array, cv: jax.Array,
                      n_rows: int) -> jax.Array:
    """'twostage': stage 1 spreads each contribution across its lane on
    the VPU (OHC * value — [T, 128] rows, the one-hot matmul's own right
    operand); stage 2 block-adds the rows by block id with a SORTED
    segment reduction, replacing the T-deep MXU contraction."""
    rows = flat_idx // LANES
    order = jnp.argsort(rows)
    contrib = ohc * cv[:, None]  # [T, L] stage 1
    return jax.ops.segment_sum(
        contrib[order], rows[order],
        num_segments=n_rows, indices_are_sorted=True)


def _scatter_bf16(ohr: jax.Array, ohc: jax.Array, cv: jax.Array) -> jax.Array:
    """'bf16': the one-hot contraction accumulated in bf16, f32 final add.

    The contraction is split into two halves, each accumulated in bf16
    (preferred_element_type=bfloat16 — half the accumulator traffic), and
    the halves are added in f32.  Parity holds to a tolerance bound, not
    bit-exactness (tests/test_kernel_edge_shapes.py)."""
    contrib = (ohc.astype(jnp.float32) * cv[:, None]).astype(jnp.bfloat16)
    ohr16 = ohr.astype(jnp.bfloat16)
    t, r = ohr.shape
    if t % 2:
        g = jax.lax.dot(ohr16.T, contrib,
                        preferred_element_type=jnp.bfloat16)
        return g.astype(jnp.float32)
    s, sub = 2, t // 2
    g = jax.lax.dot_general(
        ohr16.reshape(s, sub, r), contrib.reshape(s, sub, LANES),
        (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.bfloat16)
    return jnp.sum(g.astype(jnp.float32), axis=0)


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
        ci = jax.lax.dynamic_slice_in_dim(batch.indices, t * sub, sub, 0)
        cv = jax.lax.dynamic_slice_in_dim(batch.values, t * sub, sub, 0)
        return (), matvec(SparseBatch(ci, cv), w2)

    _, m = jax.lax.scan(body, (), jnp.arange(n // sub))
    return m.reshape(-1)


def scatter_add(batch: SparseBatch, coeff: jax.Array, n_rows: int) -> jax.Array:
    """Standalone blocked scatter-add."""
    return OneHotBatch(batch, n_rows).scatter_add(coeff)
