"""True gather / scatter over the blocked weights: a cost per stored entry
with no term in the feature count (10.2 ns an entry for the pair at
D = 47,236, 9.6 at D = 1,000,000: PERF.md section 6, PR 26).

The one-hot formulation (ops/mxu.py) pays R*128 MACs a stored entry, so its
step grows with D: 4.4x faster than this at RCV1's 47,236 features, 3x
slower at 1,000,000.  XLA's scalar gather
(ops/sparse.py: `take` of single words) is independent of D but serial,
12.6 ns a word on a v5e.  What the chip does fast is gather whole ROWS (the
step's own draw of resident rows is that operation), so both kernels here
work on the lane-blocked view `w2 [R, 128]` of ops/mxu.py:

    margins  rows = w2[i // 128]             one 512-byte row an entry,
             m_b  = sum_p v_bp * rows[b, p, i_bp % 128]   the lane picked
                                             by a compare on the VPU
    scatter  g[i] += c_b * v_bp              XLA's scatter-add over the
                                             flat view, in entry order:
                                             duplicates of an id accumulate

The scatter comes in two forms.  `scatter_add` fills a fresh [R, 128]
accumulator (a gradient: what 'dim_sparsity', an optimizer and the async
engines read).  `scatter_into` adds the entries to the weights it is
handed, in place where they are a loop's carry: the sync step of a binding
that `kernels.sparse_update` names (`BoundSync._sparse_step`) has no
accumulator at all, so no zero-fill, no pass over `w` for the regulariser
or the update, and its bytes have no term in D (PERF.md section 6, PR 30).

Measured on a v5e at D = 1,000,000, 15,600 entries a step, inside the
compiled epoch (my chip runs, PR 26): margins 2.7 ns an entry (2.5 ns over
the evaluation's 4,096-row chunks), scatter 6.9 ns with the four virtual
workers' entries in one accumulator; at D = 47,236, 30,400 and 60,800
entries a step (the family forced on the `rcv1` cells): margins 2.2 ns,
scatter 8.0 ns with the four workers' replies kept apart, as
'dim_sparsity' needs them.  Two Pallas kernels that keep `w2`
(4 MB) resident in VMEM and walk the entries from scalar memory were
written and timed against these in isolation and lost (gather 10.6 ns an
entry against 4.2-4.6, scatter 19 ns against 8-12): the walk is bound by
the scalar core's address arithmetic and, in the scatter, by the load that
has to wait for the previous entry's store.
Everything is float32: a gather rounds nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_sgd_tpu.ops.sparse import SparseBatch

LANES = 128


def gathered(w2: jax.Array, indices: jax.Array) -> jax.Array:
    """w[indices] from the blocked view, any shape of `indices`."""
    flat = indices.reshape(-1)
    rows = w2.astype(jnp.float32)[flat // LANES]  # [T, 128]: the row gather
    lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    picked = jnp.where(lane == (flat % LANES)[:, None], rows, 0.0)
    return jnp.sum(picked, axis=-1).reshape(indices.shape)


def matvec(batch: SparseBatch, w2: jax.Array) -> jax.Array:
    """Per-sample dots x_b . w on the blocked view (ops.sparse.matvec's
    equal; pads contribute 0 * w[0])."""
    with jax.named_scope("dsgd.margins"):
        products = batch.values.astype(jnp.float32) * gathered(w2, batch.indices)
        return jnp.sum(products, axis=-1)


def scatter_add(batch: SparseBatch, coeff: jax.Array, n_rows: int) -> jax.Array:
    """Blocked sum_b coeff[b] * x_b -> [R, 128] (ops.sparse.scatter_add's
    equal; duplicates of an id accumulate, pads add 0.0 to feature 0)."""
    with jax.named_scope("dsgd.scatter"):
        cv = batch.values.astype(jnp.float32) * coeff.astype(jnp.float32)[:, None]
        flat = jnp.zeros((n_rows * LANES,), jnp.float32).at[
            batch.indices.reshape(-1)].add(cv.reshape(-1))
        return flat.reshape(n_rows, LANES)


def scatter_into(w2: jax.Array, ids: jax.Array, updates: jax.Array) -> jax.Array:
    """`w2` with `updates[t]` added at flat feature `ids[t]`: the same
    scatter-add, into the weights themselves (duplicates of an id
    accumulate, a pad adds 0.0 to feature 0).  Inside a scan whose carry
    `w2` is, the compiler updates the carry in place."""
    with jax.named_scope("dsgd.scatter"):
        return w2.reshape(-1).at[ids].add(updates).reshape(w2.shape)
