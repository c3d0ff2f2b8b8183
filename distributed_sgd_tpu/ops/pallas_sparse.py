"""Pallas TPU kernel: fused per-worker sparse-SVM-family gradient.

The XLA one-hot MXU path (ops/mxu.py) is already ~4x faster than scalar
scatter, but XLA materializes the [T, R] one-hot operand (~11 MB per step
at RCV1 shapes) through HBM for each of the two matmuls.  This kernel
fuses the whole worker gradient —

    margins  m_b = x_b . w                (gather via one-hot MXU matmul)
    coeff_b  = grad_coeff(m_b, y_b)       (hinge / logistic / lsq)
    grad     g = sum_b coeff_b * x_b      (scatter via one-hot MXU matmul)

— into one single-pass `pallas_call` per step: blocked weights [R, 128]
and the gradient accumulator live in VMEM for the whole kernel, and the
one-hot operands are built in VMEM once per tile and consumed by both
matmuls without ever touching HBM.  Grid dimension = virtual workers K, so
one launch produces every reference worker's Gradient reply
(Slave.scala:142-153) for the step.

Mosaic has no cross-lane reshapes, so the host passes entries FLAT —
idx/val [K, T, 1] with T = B*P — and all in-kernel per-sample plumbing is
done with matmuls against a sample-aggregation one-hot S[T_tile, 32]
(S[e, b] = 1 iff entry e belongs to sample b):

    per-sample margins   m = S^T @ gathered        (aggregate entries)
    per-entry coeff      c_e = S @ coeff           (broadcast back)

Each tile covers 32 whole samples (TT = 32*P entries), so margins complete
within the tile and coeff/scatter fuse into the same pass.

The coefficient rule is a static python function (margins, labels) ->
coeff traced into the kernel, so every LinearModel subclass
(models/linear.py) reuses the same kernel.  Labels are f32; padding rows
carry y=0, val=0 and are inert (val=0 zeroes the scatter side).

CPU/testing: pass interpret=True (tests/test_pallas_kernels.py) — the same
kernel runs under the Pallas interpreter on the CPU test mesh
(SURVEY.md §4 strategy).  On the chip Mosaic compiles it as written
(interpret=False): chip_smoke.py checks one flagship-shape step against
kernel='mxu' every run.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SAMPLE_TILE = 32  # samples per in-kernel tile; 32*P entries per matmul

def _worker_grad_kernel(idx_ref, val_ref, y_ref, w2_ref, g2_ref, g2_acc, *, coeff_fn, p):
    """One grid step = one worker's fused gradient (see module docstring)."""
    r = w2_ref.shape[0]
    t_total = idx_ref.shape[1]
    tt = SAMPLE_TILE * p
    n_tiles = t_total // tt

    g2_acc[:] = jnp.zeros_like(g2_acc)
    for t in range(n_tiles):
        sl = pl.ds(t * tt, tt)
        idxt = idx_ref[0, sl, :]  # [TT, 1] i32
        valt = val_ref[0, sl, :]  # [TT, 1] f32
        rows = idxt // LANES
        cols = idxt % LANES
        ohr = (
            jax.lax.broadcasted_iota(jnp.int32, (tt, r), 1) == rows
        ).astype(jnp.float32)  # [TT, R]
        ohc = (
            jax.lax.broadcasted_iota(jnp.int32, (tt, LANES), 1) == cols
        ).astype(jnp.float32)  # [TT, 128]
        # sample-of-entry aggregation one-hot
        ent = jax.lax.broadcasted_iota(jnp.int32, (tt, 1), 0)
        sid = ent // p  # [TT, 1] in [0, 32)
        s_agg = (
            jax.lax.broadcasted_iota(jnp.int32, (tt, SAMPLE_TILE), 1) == sid
        ).astype(jnp.float32)  # [TT, 32]

        # gather: margins of this tile's 32 samples
        m1 = jax.lax.dot_general(
            ohr, w2_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [TT, 128]
        gathered = jnp.sum(m1 * ohc, axis=-1, keepdims=True) * valt  # [TT, 1]
        m_tile = jax.lax.dot_general(
            s_agg, gathered, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [32, 1]

        # coefficient rule + broadcast back to entries
        y_tile = y_ref[0, pl.ds(t * SAMPLE_TILE, SAMPLE_TILE), :]  # [32, 1]
        coeff = coeff_fn(m_tile, y_tile)  # [32, 1]
        coeff_e = jax.lax.dot_general(
            s_agg, coeff, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [TT, 1]

        # scatter: accumulate this tile's gradient contribution
        contrib = ohc * (coeff_e * valt)  # [TT, 128]
        g2_acc[:] += jax.lax.dot_general(
            ohr, contrib, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [R, 128]
    g2_ref[0, :, :] = g2_acc[:]


def pad_batch(idx: jax.Array, val: jax.Array, y: jax.Array):
    """Pad the per-worker batch dim to a SAMPLE_TILE multiple with inert
    rows (idx 0, val 0, y 0)."""
    k, b, p = idx.shape
    bp = -(-b // SAMPLE_TILE) * SAMPLE_TILE
    if bp == b:
        return idx, val, y
    pad = ((0, 0), (0, bp - b), (0, 0))
    return (
        jnp.pad(idx, pad),
        jnp.pad(val, pad),
        jnp.pad(y, ((0, 0), (0, bp - b))),
    )


@functools.partial(jax.jit, static_argnames=("coeff_fn", "interpret"))
def worker_grads(
    w2: jax.Array,
    idx: jax.Array,
    val: jax.Array,
    y: jax.Array,
    coeff_fn: Callable[[jax.Array, jax.Array], jax.Array],
    interpret: bool = False,
) -> jax.Array:
    """Fused gradients for K workers: [K, R, 128] from idx/val/y [K, B, P].

    coeff_fn(margins, labels) -> per-sample gradient coefficient, applied
    on [32, 1] tiles inside the kernel (e.g. SparseSVM.grad_coeff).
    """
    idx, val, y = pad_batch(idx, val.astype(jnp.float32), y.astype(jnp.float32))
    k, bp, p = idx.shape
    r, lanes = w2.shape
    assert lanes == LANES
    t_total = bp * p
    # flatten on the host side: Mosaic supports no cross-lane reshapes
    idx_f = idx.reshape(k, t_total, 1)
    val_f = val.reshape(k, t_total, 1)
    y3 = y.reshape(k, bp, 1)
    kernel = functools.partial(_worker_grad_kernel, coeff_fn=coeff_fn, p=p)
    return pl.pallas_call(
        kernel,
        grid=(k,),
        in_specs=[
            pl.BlockSpec((1, t_total, 1), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, t_total, 1), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bp, 1), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((r, LANES), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, r, LANES), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
        # under shard_map the output inherits the inputs' varying mesh axes
        out_shape=jax.ShapeDtypeStruct(
            (k, r, LANES),
            jnp.float32,
            vma=frozenset(jax.typeof(idx_f).vma) | frozenset(jax.typeof(w2).vma),
        ),
        scratch_shapes=[
            pltpu.VMEM((r, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(idx_f, val_f, y3, w2)
