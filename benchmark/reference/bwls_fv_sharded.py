"""Plain reference of the row-sharded BlockWeightedLeastSquares solve at
the ImageNetSiftLcsFV widths.  The feature matrix is the benchmark's own
(made on the devices from the seed, ``datagen.py``), so the reference is
handed the same shards; every product is plain ``jax.numpy`` at the stated
``precision`` and the compiler places the all-reduces.  Imports nothing of
``keystone_tpu``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import weighted_bcd


@functools.partial(jax.jit, static_argnames=("width",))
def _columns(x, start, width):
    return jax.lax.dynamic_slice_in_dim(x, start, width, axis=1)


def fit_and_predict(cfg: dict, x, y, held_x, *, epochs: int, precision="highest"):
    """Returns (first block's weights (block, K), held-out predictions
    (h, K)) as host arrays."""
    block = cfg["block_size"]
    num_blocks = x.shape[1] // block
    weights, _, intercept = weighted_bcd.fit(
        lambda b: _columns(x, b * block, block), num_blocks, y,
        epochs=epochs, lam=cfg["lam"], mix=cfg["mixture_weight"], precision=precision,
    )
    pred = weighted_bcd.predict(
        lambda b: _columns(held_x, b * block, block), weights, intercept,
        precision=precision,
    )
    return jax.device_get(weights[0]), jax.device_get(pred)
