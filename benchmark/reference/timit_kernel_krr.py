"""Plain reference of exact Gaussian-kernel ridge regression on TIMIT by
block Gauss-Seidel over the dual (Tu, Roelofs, Venkataraman, Recht,
arXiv:1602.05310; stephentu/keystone ``KernelRidgeRegression.scala``,
``KernelGenerator.scala § GaussianKernelGenerator``,
``KernelBlockLinearMapper.scala``), written from its equations:

    xs  = (x - mean) / std                          StandardScaler
    K(x, z) = exp(-gamma |x - z|^2)                 by the gemm expansion
    for each block b of `block_size` rows, in order, `epochs` times:
        alpha_b <- (K_bb + lam n I)^-1 (Y_b - F_b + K_bb alpha_b)
        F       <- F + K(:, b) (alpha_b_new - alpha_b)
    scores(x*) = K(x*, X) alpha

float32, plain ``jax.numpy``, one (n, block_size) column block of K on
the device at a time.  Imports nothing of ``keystone_tpu``.  The
products take the roles of ``weighted_bcd.roles``: ``solver`` is every
product that enters a block solve (the distance gemm of the fit,
``K_bb alpha_b``, ``K(:, b) delta``), ``other`` the two products of a
prediction; the reference proper runs both at ``highest``.

Departures from the paper, each on purpose: the regulariser is
``lam * n`` as the program under test scales it (the Scala code adds
``lambda`` unscaled; ``lam`` in the configuration is chosen for the
product); labels are +-1 indicators of the 147 phone states; the blocks
are swept in their order, not in a random permutation, and one sweep
(`epochs`) is the configuration's cut; the rows of a column block sit on
one chip, not sharded over a cluster's nodes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.weighted_bcd import dot, roles


@functools.partial(jax.jit, static_argnames=("gamma", "precision"))
def kernel(x, z, gamma: float, precision: str):
    """exp(-gamma |x - z|^2) for every row of x against every row of z."""
    xn = jnp.sum(x * x, axis=1, keepdims=True)
    zn = jnp.sum(z * z, axis=1)
    sq = jnp.maximum(xn - 2.0 * dot(x, z.T, precision) + zn, 0.0)
    return jnp.exp(-gamma * sq)


@functools.partial(jax.jit, static_argnames=("gamma", "precision"), donate_argnums=(4,))
def _block_step(xs, xb, yb, ab, f, lo, reg, gamma, precision):
    width = xb.shape[0]
    kcol = kernel(xs, xb, gamma, precision)  # K(:, b): (n, width)
    kbb = lax.dynamic_slice_in_dim(kcol, lo, width)
    fb = lax.dynamic_slice_in_dim(f, lo, width)
    target = yb - fb + dot(kbb, ab, precision)
    chol = jax.scipy.linalg.cho_factor(kbb + reg * jnp.eye(width, dtype=jnp.float32))
    ab_new = jax.scipy.linalg.cho_solve(chol, target)
    return ab_new, f + dot(kcol, ab_new - ab, precision)


@functools.partial(jax.jit, static_argnames=("gamma", "precision"))
def _score_block(out, hs, xb, ab, gamma, precision):
    return out + dot(kernel(hs, xb, gamma, precision), ab, precision)


def scale(train_x, held_x):
    x = jnp.asarray(train_x, jnp.float32)
    n = x.shape[0]
    mean = jnp.mean(x, axis=0)
    std = jnp.sqrt(jnp.sum((x - mean) ** 2, axis=0) / max(n - 1.0, 1.0))
    std = jnp.maximum(std, 1e-8)
    return (x - mean) / std, (jnp.asarray(held_x, jnp.float32) - mean) / std


def fit_and_score(cfg: dict, train_x, train_labels, held_x, *, epochs: int,
                  precision="highest") -> dict:
    """Fit on (train_x, train_labels); returns host arrays: the dual
    coefficients ``alpha`` (n, classes) and the held-out class scores
    ``scores`` (h, classes)."""
    solver, other = roles(precision)
    gamma, width = float(cfg["gamma"]), int(cfg["block_size"])
    xs, hs = scale(train_x, held_x)
    n = xs.shape[0]
    y = 2.0 * jax.nn.one_hot(jnp.asarray(train_labels), cfg["num_classes"],
                             dtype=jnp.float32) - 1.0
    reg = jnp.float32(cfg["lam"] * n)
    starts = range(0, n, width)
    alpha = [jnp.zeros_like(y[lo: lo + width]) for lo in starts]
    f = jnp.zeros_like(y)
    for _ in range(epochs):
        for b, lo in enumerate(starts):
            alpha[b], f = _block_step(
                xs, xs[lo: lo + width], y[lo: lo + width], alpha[b], f, jnp.int32(lo), reg,
                gamma=gamma, precision=solver,
            )
    scores = jnp.zeros((hs.shape[0], y.shape[1]), jnp.float32)
    for b, lo in enumerate(starts):
        scores = _score_block(scores, hs, xs[lo: lo + width], alpha[b], gamma=gamma,
                              precision=other)
    return {"alpha": jax.device_get(jnp.concatenate(alpha)), "scores": jax.device_get(scores)}
