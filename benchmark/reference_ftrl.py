"""The plain reference of per-coordinate FTRL-Proximal (McMahan et al.,
"Ad Click Prediction: a View from the Trenches", KDD 2013, Algorithm 1) in
the synchronous engine: float32, straightforward jax.numpy, dense over all
D coordinates, HIGHEST matmul precision, nothing imported from the program.

  weights   w_i = 0                                            if |z_i| <= l1
            w_i = -(z_i - sgn(z_i) l1) / ((beta + sqrt(n_i)) / alpha + l2)
  gradient  g = the mean over ALL workers of each worker's batch SUM of the
            loss's gradient at w (`reference.worker_grad`, no regulariser:
            the L2 strength lives in the closed form), segment-summed over D
  update    for every i with g_i != 0, from the pre-step state:
            sigma_i = (sqrt(n_i + g_i^2) - sqrt(n_i)) / alpha
            z_i += g_i - sigma_i w_i,   n_i += g_i^2
            every other coordinate keeps z and n as they are
  objective mean loss (`reference.evaluate` with lam 0) + l1 ||w||_1
            + (l2 / 2) ||w||^2, the penalty summed in float64 on the host
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference


def weights(z, n, alpha: float, beta: float, l1: float, l2: float):
    """The closed form, elementwise, float32."""
    z = jnp.asarray(z, jnp.float32)
    n = jnp.asarray(n, jnp.float32)
    w = -(z - jnp.sign(z) * l1) / ((beta + jnp.sqrt(n)) / alpha + l2)
    return jnp.where(jnp.abs(z) <= l1, 0.0, w)


def sync_step(loss: str, z, n, batches, alpha: float, beta: float, l1: float, l2: float):
    """(z', n', g) after one synchronous step over `batches` [(idx, val, y)
    of each worker], from the state (z, n) of every coordinate."""
    with jax.default_matmul_precision(reference.HIGHEST):
        z = jnp.asarray(z, jnp.float32)
        n = jnp.asarray(n, jnp.float32)
        w = weights(z, n, alpha, beta, l1, l2)
        g = None
        for idx, val, y in batches:
            reply = reference.worker_grad(loss, "none", w, idx, val, y, 0.0, reduce="sum")
            g = reply if g is None else g + reply
        g = g / len(batches)
        sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / alpha
        moved = g != 0
        return (jnp.where(moved, z + (g - sigma * w), z), jnp.where(moved, n + g * g, n), g)


def penalty(w, l1: float, l2: float) -> float:
    """l1 ||w||_1 + (l2 / 2) ||w||^2, in float64 on the host."""
    w = np.asarray(w, np.float64)
    return float(l1 * np.abs(w).sum() + 0.5 * l2 * np.dot(w, w))


def evaluate(loss: str, w, idx, val, y, l1: float, l2: float):
    """(objective, accuracy, mean loss, penalty) over a whole split."""
    mean_loss, acc = reference.evaluate(loss, w, idx, val, y, 0.0)
    pen = penalty(w, l1, l2)
    return mean_loss + pen, acc, mean_loss, pen
