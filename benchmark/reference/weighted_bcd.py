"""Plain class-weighted block coordinate descent least squares
(KeystoneML ``nodes/learning/BlockWeightedLeastSquares.scala``), written
from its equations in ``jax.numpy``: float32, every product at the stated
``precision`` (``highest`` for the reference; ``high`` or ``bfloat16``
for the control, see PERF.md), one block of columns on the device at a time.
It imports nothing of ``keystone_tpu`` and takes nothing that the program
has made.

    alpha_i = mix * n / (K * n_class(i)) + (1 - mix)
    minimise  sum_i alpha_i |x_i W + b - y_i|^2 + lam * n * |W|^2
    by Gauss-Seidel sweeps over blocks of columns, weighted means giving b.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def roles(precision) -> tuple:
    """(precision of the solver's Gramian and cross term, precision of every
    other product) from one name for all or a {"solver", "other"} pair."""
    if isinstance(precision, str):
        return precision, precision
    return precision["solver"], precision["other"]


def _through_fp8(x):
    """x as an fp8 product would see it: scaled per tensor so that its largest
    magnitude sits at e4m3's 448, rounded to float8 e4m3 (3 mantissa bits,
    small values flushed), and scaled back."""
    x = x.astype(jnp.float32)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def dot(a, b, precision: str):
    """One float32 product at ``precision``: ``highest`` (the reference),
    ``high`` (three bf16 passes), ``bfloat16`` (operands rounded to
    bfloat16, float32 accumulation: what the MXU's default does, written out
    so that it means the same on a CPU) or ``fp8`` (operands through float8 e4m3 with a
    per-tensor scale, float32 accumulation)."""
    if precision == "fp8":
        return jnp.matmul(_through_fp8(a), _through_fp8(b), precision="highest")
    if precision == "bfloat16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=precision)


def class_weights(y, mix: float):
    """Row weights from a +-1 indicator matrix (n, K)."""
    n, k = y.shape
    cls = jnp.argmax(y, axis=1)
    counts = jnp.maximum(jnp.sum((y > 0).astype(jnp.float32), axis=0), 1.0)
    return mix * n / (k * counts[cls]) + (1.0 - mix)


@functools.partial(jax.jit, static_argnames=("solver", "other"))
def _block_step(blk, yc, p, wb, alpha, reg, solver, other):
    wsum = jnp.sum(alpha)
    mean = dot(alpha, blk, other) / wsum
    xc = blk - mean
    sa = jnp.sqrt(alpha)[:, None]
    a = xc * sa
    target = (yc - p) * sa + dot(a, wb, other)
    gram = dot(a.T, a, solver) + reg * jnp.eye(a.shape[1], dtype=jnp.float32)
    cross = dot(a.T, target, solver)
    chol = jax.scipy.linalg.cho_factor(gram)
    wb_new = jax.scipy.linalg.cho_solve(chol, cross)
    return wb_new, p + dot(xc, wb_new - wb, other), mean


def fit(block_fn, num_blocks: int, y, *, epochs: int, lam: float, mix: float,
        precision="highest"):
    """``block_fn(b)`` gives block b of the feature matrix, (n, w) float32.
    Returns the per-block weights [(w, K)], the per-block weighted column
    means [(w,)] and the intercept (K,)."""
    solver, other = roles(precision)
    y = jnp.asarray(y, jnp.float32)
    n = y.shape[0]
    alpha = class_weights(y, mix)
    ym = dot(alpha, y, other) / jnp.sum(alpha)
    yc = y - ym
    p = jnp.zeros_like(yc)
    weights = [None] * num_blocks
    means = [None] * num_blocks
    reg = jnp.float32(lam * n)
    for _ in range(epochs):
        for b in range(num_blocks):
            blk = block_fn(b)
            wb = weights[b]
            if wb is None:
                wb = jnp.zeros((blk.shape[1], y.shape[1]), jnp.float32)
            weights[b], p, means[b] = _block_step(
                blk, yc, p, wb, alpha, reg, solver=solver, other=other
            )
    intercept = ym
    for wb, mean in zip(weights, means):
        intercept = intercept - dot(mean, wb, other)
    return weights, means, intercept


def predict(block_fn, weights, intercept, precision="highest"):
    """Scores of the rows that ``block_fn`` featurizes, block by block."""
    _, other = roles(precision)
    out = None
    for b, wb in enumerate(weights):
        part = dot(block_fn(b), wb, other)
        out = part if out is None else out + part
    return out + intercept
