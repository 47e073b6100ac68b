"""Plain reference of MnistRandomFFT (KeystoneML ``pipelines/images/mnist/
MnistRandomFFT.scala`` with ``nodes/stats/RandomSignNode.scala``,
``PaddedFFT.scala``, ``LinearRectifier.scala``, ``nodes/util/
VectorCombiner.scala``, ``nodes/learning/BlockLeastSquares.scala``), written
from its equations:

    x      = pixels / 255                                  784 numbers
    s_i    = 2 bernoulli(PRNGKey(seed + i), 0.5) - 1       branch i's signs
    f_i    = max(0, (x * s_i) C)                           512 numbers
    C[j,k] = cos(2 pi j k / 1024) / sqrt(1024)             j < 784, k < 512
    x_f    = [f_0, ..., f_199]                             102,400 numbers
    one Gauss-Seidel sweep over blocks of 2048 columns, with an intercept:
        W_b <- (X_b^T X_b + lam n I)^-1 X_b^T (Y - P + X_b W_b)

The program flips the signs of the uint8 pixels before it scales them, in
each branch: the flip is exact and rounding is symmetric in sign, so the
order changes no bit of ``x * s_i``.

``(x * s_i) C`` is the real part of the first 512 bins of the unitary
Fourier transform of ``x * s_i`` zero-padded to 1024 (the padding adds
nothing to a sum), written as a product with the cosine matrix: no FFT of
any library computes it.  ``j k`` is reduced modulo 1024 in integers before
the cosine, so that C is exact to float32.

float32, plain ``jax.numpy``, no kernel, cache or chunk rule.  A block of
2048 columns is four branches; it is made from the pixels when the sweep
reaches it, so the 102,400 features of all rows never exist at once.  The
products take the roles of ``weighted_bcd.roles``: ``solver`` is the
Gramian and the cross term of each block, ``other`` the DFT product, the
residual updates and the scoring; the reference proper runs both at
``highest``.  The solve is ``weighted_bcd.fit`` with mixture 0: every row
weighs 1, the weighted means are the means, and its block step is the
unweighted one.  Imports nothing of ``keystone_tpu``.

The signs are drawn as the program under test states them
(``ops/stats.py § RandomSignNode.init``), repeated here call for call: a
reference that drew other signs would be another model.  The regulariser
is ``lam * n``.  The images are synthetic.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import weighted_bcd
from benchmark.reference.weighted_bcd import dot, roles


def signs(seed: int, branch: int, pixels: int):
    bits = jax.random.bernoulli(jax.random.PRNGKey(seed + branch), 0.5, (pixels,))
    return bits.astype(jnp.float32) * 2.0 - 1.0


def cosine_matrix(pixels: int, padded: int):
    """(pixels, padded // 2): the real part of the unitary DFT's first half."""
    j = jnp.arange(pixels, dtype=jnp.int32)[:, None]
    k = jnp.arange(padded // 2, dtype=jnp.int32)[None, :]
    angle = (2.0 * math.pi / padded) * ((j * k) % padded).astype(jnp.float32)
    return jnp.cos(angle) / jnp.float32(math.sqrt(padded))


@functools.partial(jax.jit, static_argnames=("other",))
def _branches(x, sign_rows, c, *, other):
    """max(0, (x * s_i) C) for each row of ``sign_rows``, side by side."""
    return jnp.concatenate(
        [jnp.maximum(dot(x * s, c, other), 0.0) for s in sign_rows], axis=1)


def featurizer(cfg: dict, seed: int, other: str):
    """``features(pixels, lo, hi)``: branches [lo, hi) of uint8 rows."""
    c = cosine_matrix(cfg["num_pixels"], cfg["padded"])
    every = jnp.stack([signs(seed, i, cfg["num_pixels"]) for i in range(cfg["num_ffts"])])

    def features(pixels, lo: int, hi: int):
        x = jnp.asarray(pixels, jnp.float32) / 255.0
        return _branches(x, every[lo:hi], c, other=other)

    return features


def _labels(cfg: dict, train_labels):
    return 2.0 * jax.nn.one_hot(jnp.asarray(train_labels), cfg["num_classes"],
                                dtype=jnp.float32) - 1.0


def fit_and_score(cfg: dict, train_x, train_labels, held_x, *, seed: int, feature_rows: int,
                  precision="highest") -> dict:
    """Fit on (train_x, train_labels); returns host arrays: ``features`` of
    the first ``feature_rows`` held-out images, the first block's weights
    ``w0`` and the held-out class ``scores``."""
    solver, other = roles(precision)
    features = featurizer(cfg, seed, other)
    per_block = cfg["block_size"] // cfg["fft_features"]
    blocks = cfg["num_ffts"] // per_block
    train_x = jnp.asarray(train_x)
    held_x = jnp.asarray(held_x)
    weights, _, intercept = weighted_bcd.fit(
        lambda b: features(train_x, b * per_block, (b + 1) * per_block), blocks,
        _labels(cfg, train_labels), epochs=cfg["num_iter"], lam=cfg["lam"], mix=0.0,
        precision={"solver": solver, "other": other},
    )
    scores = weighted_bcd.predict(
        lambda b: features(held_x, b * per_block, (b + 1) * per_block), weights, intercept,
        precision={"solver": solver, "other": other},
    )
    return {
        "features": jax.device_get(features(held_x[:feature_rows], 0, cfg["num_ffts"])),
        "w0": jax.device_get(weights[0]),
        "scores": jax.device_get(scores),
    }


def first_block_weights(cfg: dict, x0, train_labels, *, precision="highest"):
    """The first block's weights of the same sweep from GIVEN features
    ``x0`` (n, block): with zero weights everywhere the first step's target
    is the centred labels, so W_0 = (X_0c^T X_0c + lam n I)^-1 X_0c^T Y_c
    (``weighted_bcd.fit`` over that one block)."""
    solver, other = roles(precision)
    x0 = jnp.asarray(x0, jnp.float32)
    weights, _, _ = weighted_bcd.fit(
        lambda b: x0, 1, _labels(cfg, train_labels), epochs=1, lam=cfg["lam"], mix=0.0,
        precision={"solver": solver, "other": other},
    )
    return jax.device_get(weights[0])
