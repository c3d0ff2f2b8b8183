"""Per-coordinate FTRL-Proximal (McMahan et al., "Ad Click Prediction: a View
from the Trenches", KDD 2013, Algorithm 1) in the mesh sync engine.

Every coordinate i carries two words of state, z_i and n_i, both 0 at the
start.  Its weight is a closed form of them,

    w_i = 0                                                if |z_i| <= l1
    w_i = -(z_i - sgn(z_i) l1) / ((beta + sqrt(n_i)) / alpha + l2)   otherwise

and a step whose gradient is g (the mean over ALL workers of each worker's
batch SUM, the vector the plain step would multiply by its learning rate)
updates every coordinate with g_i != 0 from the pre-step state:

    sigma_i = (sqrt(n_i + g_i^2) - sqrt(n_i)) / alpha
    z_i    += g_i - sigma_i w_i
    n_i    += g_i^2

Every other coordinate keeps z and n bit for bit.  alpha is the binding's
learning rate and l2 the model's `lam` (the 'l2' regulariser; 'none' is 0):
the L2 term lives in the closed form, so no reply carries `2 lam w` and the
sparse step carries no decay scale.  l1 is FTRL's own (`Ftrl`); beta is
BETA, the paper's "beta = 1 is usually good enough" (section 3.1).

The state is ONE array `[R2, 128]`, R2 = 2 x `mxu.n_blocks(D)`: coordinate i
in row i // 64, z_i in lane i % 64 and n_i in lane 64 + i % 64.  A row
access brings a coordinate's z and n together, so the sparse step's margins
stay one row gather and its ending one fetch and one DMA a touched row, as
the plain sparse step has them (ops/gather.py `scatter_into`, the sum by
row at 64 coordinates a row, `rows` the row function).  The z half read as
`[R, 128]` is the blocked view of z, `mxu.to_blocked`'s.

`weights` and `update` are the one per-coordinate function of both steps:
the sparse step applies them to the rows its entries touch, the dense step
(`BoundSync._ftrl_step`, under `kernels.SPARSE_UPDATE_MIN_FEATURES`) to all
of D.  Everything here runs under the scope `dsgd.ftrl`.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from distributed_sgd_tpu.ops import mxu

HALF = 64  # coordinates a state row holds: z in lanes [0, 64), n in [64, 128)
BETA = 1.0  # beta of the per-coordinate rate alpha / (beta + sqrt(n_i))
SCOPE = "dsgd.ftrl"


@dataclasses.dataclass(frozen=True)
class Ftrl:
    """`optimizer=Ftrl(l1)` (or `'ftrl'`: no L1): FTRL-Proximal's own
    hyperparameter, the L1 strength."""

    l1: float = 0.0


class Params(NamedTuple):
    """What a binding computes with: alpha (the learning rate) and the L1
    and L2 strengths."""

    alpha: float
    l1: float
    l2: float


def of(optimizer) -> Optional[Ftrl]:
    """`optimizer` as FTRL's hyperparameters, or None for any other."""
    if isinstance(optimizer, Ftrl):
        return optimizer
    if isinstance(optimizer, str) and optimizer == "ftrl":
        return Ftrl()
    return None


def refuse(optimizer, who: str) -> None:
    """The ONE refusal of everything that holds no (z, n): Hogwild, local
    SGD, the rpc workers and master, the checkpoint, and every engine that
    reads a whole gradient.  FTRL trains through SyncTrainer.fit alone."""
    if of(optimizer) is not None:
        raise ValueError(
            f"{who} carries no FTRL-Proximal state (z, n); optimizer='ftrl' trains "
            f"through SyncTrainer.fit (the mesh sync engine) only, without a checkpointer")


def params(optimizer, learning_rate: float, model) -> Optional[Params]:
    """The binding's Params, or None where `optimizer` is not FTRL.  FTRL
    takes one flat weight vector and a regulariser that is an L2 strength
    ('l2': lam; 'none': 0)."""
    spec = of(optimizer)
    if spec is None:
        return None
    if model.n_outputs != 1 or model.regularizer not in ("l2", "none"):
        raise ValueError(
            f"FTRL-Proximal takes one output and an 'l2' (or no) regulariser, got "
            f"n_outputs={model.n_outputs}, regularizer={model.regularizer!r}")
    if not (learning_rate > 0 and spec.l1 >= 0):
        raise ValueError(f"FTRL needs alpha > 0 and l1 >= 0; got "
                         f"alpha={learning_rate}, {spec}")
    l2 = model.lam if model.regularizer == "l2" else 0.0
    return Params(float(learning_rate), float(spec.l1), float(l2))


def weights(z, n, p: Params):
    """The closed form, elementwise."""
    with jax.named_scope(SCOPE):
        shrunk = z - jnp.sign(z) * p.l1
        w = -shrunk / ((BETA + jnp.sqrt(n)) / p.alpha + p.l2)
        return jnp.where(jnp.abs(z) <= p.l1, 0.0, w)


def update(z, n, g, p: Params):
    """(z', n') after a step whose gradient is `g`, elementwise; where
    g == 0 the state is returned as it is."""
    with jax.named_scope(SCOPE):
        w = weights(z, n, p)
        g2 = g * g
        sigma = (jnp.sqrt(n + g2) - jnp.sqrt(n)) / p.alpha
        moved = g != 0
        return jnp.where(moved, z + (g - sigma * w), z), jnp.where(moved, n + g2, n)


def rows(old, total, p: Params):
    """The sparse step's row function (`gather.scatter_into`'s `row`): state
    rows `[T, 128]` after a step whose summed gradient of each row's 64
    coordinates is in lanes [0, 64) of `total`."""
    with jax.named_scope(SCOPE):
        z, n = update(old[:, :HALF], old[:, HALF:], total[:, :HALF], p)
        return jnp.concatenate([z, n], axis=1)


def matvec(batch, state, p: Params):
    """Per-sample dots x_b . w on the state: ONE row gather brings every
    entry's z and n, the weights are the closed form of them."""
    with jax.named_scope("dsgd.margins"):
        flat = batch.indices.reshape(-1)
        got = state[flat // HALF]  # [T, 128]: the row gather
        lane = jax.lax.broadcasted_iota(jnp.int32, got.shape, 1)
        at = (flat % HALF)[:, None]
        z = jnp.sum(jnp.where(lane == at, got, 0.0), axis=-1)
        n = jnp.sum(jnp.where(lane == at + HALF, got, 0.0), axis=-1)
        w = weights(z, n, p).reshape(batch.indices.shape)
        return jnp.sum(batch.values.astype(jnp.float32) * w, axis=-1)


def zeros(n_features: int):
    """The state at the start of a fit."""
    return jnp.zeros((2 * mxu.n_blocks(n_features), 2 * HALF), jnp.float32)


def halves(state):
    """(z, n) as `[R2, 64]` each."""
    return state[:, :HALF], state[:, HALF:]


def coordinates(state, n_features: int):
    """(z[D], n[D]) of a state."""
    z, n = halves(state)
    return z.reshape(-1)[:n_features], n.reshape(-1)[:n_features]


def materialise(state, n_features: int, p: Params):
    """w[D]: the closed form over the whole state."""
    z, n = halves(state)
    return weights(z, n, p).reshape(-1)[:n_features]


def apply(state, g, p: Params):
    """The dense step's update: `update` over all of D, for the gradient
    g[D] of every coordinate."""
    z, n = halves(state)
    g = jnp.pad(g, (0, z.size - g.shape[0])).reshape(z.shape)
    z, n = update(z, n, g, p)
    return jnp.concatenate([z, n], axis=1)


def weight_sums(w) -> jax.Array:
    """float32 [||w||_1, ||w||^2], what `penalty` is made of.  The
    evaluation program sums them inside it (`BoundSync._weight_sums`), fenced
    off so that they compile as `penalty`'s own program does: a fit's
    objective less `penalty(w)` is then its mean loss to the bit."""
    w = jnp.asarray(w, jnp.float32)
    return jnp.stack([jnp.sum(jnp.abs(w)), jnp.sum(w * w)])


_weight_sums = jax.jit(weight_sums)


def penalty(w, p: Params) -> float:
    """l1 ||w||_1 + (l2 / 2) ||w||^2, the regulariser of the objective an
    FTRL fit reports beside its mean loss."""
    return penalty_of(*jax.device_get(_weight_sums(w)).tolist(), p)


def penalty_of(abs_sum: float, squares: float, p: Params) -> float:
    """`penalty` of the two `weight_sums`."""
    return p.l1 * abs_sum + 0.5 * p.l2 * squares
