"""Plain reference of KeystoneML's ``TimitPipeline``: StandardScaler ->
CosineRandomFeatures in blocks (gaussian W, uniform phase) -> class-weighted
block least squares -> raw class scores.  float32, products at the stated
``precision``; one 4096-column block of features on the device at a time.
Imports nothing of ``keystone_tpu``.  The random projection of block i is
drawn as the configuration states it: ``kw, kb = split(PRNGKey(seed + i))``,
``W = gamma * normal(kw, (block, dim))``, ``b = uniform(kb, (block,), 0, 2 pi)``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import weighted_bcd


def projection(cfg: dict, feature_seed: int, i: int):
    kw, kb = jax.random.split(jax.random.PRNGKey(feature_seed + i))
    block, dim = cfg["cosine_block_size"], cfg["input_dim"]
    w = cfg["gamma"] * jax.random.normal(kw, (block, dim), jnp.float32)
    b = jax.random.uniform(kb, (block,), jnp.float32, 0.0, 2 * math.pi)
    return w, b


@functools.partial(jax.jit, static_argnames=("precision",))
def _features(xs, w, b, precision):
    return jnp.cos(weighted_bcd.dot(xs, w.T, precision) + b)


def fit_and_score(cfg: dict, train_x, train_labels, held_x, *, feature_seed: int,
                  epochs: int, precision="highest"):
    """Fit on (train_x, train_labels) and return the held-out class scores
    (h, classes) as a host array."""
    x = jnp.asarray(train_x, jnp.float32)
    n = x.shape[0]
    mean = jnp.mean(x, axis=0)
    std = jnp.sqrt(jnp.sum((x - mean) ** 2, axis=0) / max(n - 1.0, 1.0))
    std = jnp.maximum(std, 1e-8)
    xs = (x - mean) / std
    hs = (jnp.asarray(held_x, jnp.float32) - mean) / std
    y = 2.0 * jax.nn.one_hot(jnp.asarray(train_labels), cfg["num_classes"],
                             dtype=jnp.float32) - 1.0
    num_blocks = cfg["num_cosine_blocks"]
    _, other = weighted_bcd.roles(precision)
    proj = [projection(cfg, feature_seed, i) for i in range(num_blocks)]

    weights, _, intercept = weighted_bcd.fit(
        lambda b: _features(xs, *proj[b], precision=other), num_blocks, y,
        epochs=epochs, lam=cfg["lam"], mix=cfg["mixture_weight"], precision=precision,
    )
    scores = weighted_bcd.predict(
        lambda b: _features(hs, *proj[b], precision=other), weights, intercept,
        precision=precision,
    )
    return jax.device_get(scores)
