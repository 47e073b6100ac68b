"""Plain reference of RandomPatchCifar (KeystoneML
``pipelines/images/cifar/RandomPatchCifar.scala`` with ``nodes/images/
Convolver.scala``, ``SymmetricRectifier.scala``, ``Pooler.scala``,
``nodes/stats/StandardScaler.scala``, ``nodes/learning/
BlockLeastSquares.scala``; after Coates & Ng, ICML 2011), written from its
equations in the published ORDER:

    P      = patches of the training images, 6 x 6 x 3 in (dy, dx, c) order
    norm(p) = (p - mean p) / sqrt(var p + 10)         variance over d - 1
    m, W   = column means of norm(P);  V diag(1 / sqrt(s + eps)) V^T of its
             covariance (divisor: the number of patches)
    F      = rows of norm(P);  F <- (F - m) W;  each row / (|row| + 1e-10)
    for every patch p of an image at step 1 (27 x 27 of them):
        z(p) = (norm(p) - m) W F^T                    extract, normalise,
        r(p) = [max(0, z - alpha), max(0, -z - alpha)]   subtract, whiten,
    pooled = sums of r over windows of 14 at stride 13 (2 x 2)   multiply
    x      = pooled, flattened (py, px, channel), 80,000 numbers
    xs     = (x - mean) / std                         std over n - 1
    one Gauss-Seidel sweep over blocks of 4096 columns, with an intercept:
        W_b <- (X_b^T X_b + lam n I)^-1 X_b^T (Y - P + X_b W_b)

float32, plain ``jax.numpy``, im2col in blocks of images so that the
27 x 27 x K activation of a block fits.  Imports nothing of
``keystone_tpu``.  The products take the roles of ``weighted_bcd.roles``:
``solver`` is every product that enters a solve or the filter bank (the
patch covariance, the whitening of the bank, the solver's Gramian and
cross term), ``other`` the two products of the convolution, the residual
updates and the scoring; the reference proper runs both at ``highest``.
The solve is ``weighted_bcd.fit`` with mixture 0: every row weighs 1, the
weighted means are the means, and its block step is the unweighted one.

Departures from upstream, each on purpose.  The draw of patches and of the
filters among them is the one the program under test states (``jax.random``
calls on ``PRNGKey(seed)``, ``pipelines/random_patch_cifar.py §
_learn_filters``), repeated here call for call, as ``timit_cosine_rf.py``
repeats its projections: a reference that drew other patches would be
another model.  Patches are drawn over all training images at once, not as
a sample of every image's windows.  The regulariser is ``lam * n``.  The
images are synthetic.  ``precision`` may carry ``"normalize_patches":
False`` — the featurizer WITHOUT the per-patch normalisation of the image
patches (the bank as it is), which is the control that shows the
comparison can tell.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import weighted_bcd
from benchmark.reference.weighted_bcd import dot, roles

#: images per im2col block: 16 x 729 x 10,000 float32 responses are 467 MB
IMAGES_A_BLOCK = 16


def normalize_rows(p, var_constant):
    centred = p - jnp.mean(p, axis=-1, keepdims=True)
    var = jnp.sum(centred * centred, axis=-1, keepdims=True) / (p.shape[-1] - 1.0)
    return centred / jnp.sqrt(var + var_constant)


@functools.partial(jax.jit, static_argnames=("patches", "filters", "size", "solver"))
def learn_filters(images, key, zca_eps, var_constant, *, patches, filters, size, solver):
    """(F (K, d) whitened, unit norm; W (d, d); m (d,)) from the stated draw."""
    n, h, w, c = images.shape
    k_img, k_y, k_x, k_f = jax.random.split(key, 4)
    which = jax.random.randint(k_img, (patches,), 0, n)
    ys = jax.random.randint(k_y, (patches,), 0, h - size + 1)
    xs = jax.random.randint(k_x, (patches,), 0, w - size + 1)
    rows = jax.random.choice(k_f, patches, (filters,), replace=False)
    dy, dx = jnp.meshgrid(jnp.arange(size), jnp.arange(size), indexing="ij")
    p = images[which[:, None, None], ys[:, None, None] + dy, xs[:, None, None] + dx]
    p = normalize_rows(p.reshape(patches, size * size * c).astype(jnp.float32), var_constant)
    m = jnp.mean(p, axis=0)
    pc = p - m
    cov = dot(pc.T, pc, solver) / patches
    s, v = jnp.linalg.eigh(cov)
    w_zca = dot(v / jnp.sqrt(jnp.maximum(s, 0.0) + zca_eps), v.T, "highest")
    f = dot(p[rows] - m, w_zca, solver)
    return f / (jnp.sqrt(jnp.sum(f * f, axis=1, keepdims=True)) + 1e-10), w_zca, m


@functools.partial(
    jax.jit, static_argnames=("size", "alpha", "pool_size", "pool_stride", "normalize", "other")
)
def _features_block(images, f, w_zca, m, var_constant, *, size, alpha, pool_size, pool_stride,
                    normalize, other):
    b, h, w, c = images.shape
    oh, ow = h - size + 1, w - size + 1
    x = images.astype(jnp.float32)
    p = jnp.stack(
        [x[:, dy: dy + oh, dx: dx + ow, :] for dy in range(size) for dx in range(size)], axis=3
    ).reshape(b * oh * ow, size * size * c)
    if normalize:
        p = normalize_rows(p, var_constant)
    z = dot(dot(p - m, w_zca, other), f.T, other).reshape(b, oh, ow, -1)
    r = jnp.concatenate([jnp.maximum(z - alpha, 0.0), jnp.maximum(-z - alpha, 0.0)], axis=-1)
    starts_y = range(0, oh - pool_size + 1, pool_stride)
    starts_x = range(0, ow - pool_size + 1, pool_stride)
    pooled = jnp.stack([
        jnp.stack([jnp.sum(r[:, y: y + pool_size, x0: x0 + pool_size], axis=(1, 2))
                   for x0 in starts_x], axis=1)
        for y in starts_y
    ], axis=1)
    return pooled.reshape(b, -1)


def features(cfg: dict, images, bank, other: str, normalize: bool = True):
    """(n, 80,000) pooled features of ``images`` under the bank (F, W, m),
    on the device, im2col in blocks of ``IMAGES_A_BLOCK`` images."""
    images = jnp.asarray(images)
    pad = -images.shape[0] % IMAGES_A_BLOCK
    blocks = jnp.pad(images, ((0, pad),) + ((0, 0),) * 3).reshape(
        (-1, IMAGES_A_BLOCK) + images.shape[1:])
    out = jax.lax.map(
        lambda blk: _features_block(
            blk, *bank, jnp.float32(cfg["var_constant"]), size=cfg["patch_size"],
            alpha=cfg["alpha"], pool_size=cfg["pool_size"], pool_stride=cfg["pool_stride"],
            normalize=normalize, other=other,
        ),
        blocks,
    )
    return out.reshape(-1, out.shape[-1])[: images.shape[0]]


@jax.jit
def _moments(x):
    mean = jnp.mean(x, axis=0)
    var = jnp.sum((x - mean) ** 2, axis=0) / jnp.maximum(x.shape[0] - 1.0, 1.0)
    return mean, jnp.maximum(jnp.sqrt(var), 1e-8)


def fit_and_score(cfg: dict, train_x, train_labels, held_x, *, seed: int, feature_rows: int,
                  precision="highest") -> dict:
    """Fit on (train_x, train_labels); returns host arrays: ``features`` of
    the first ``feature_rows`` held-out images, the first block's weights
    ``w0`` and the held-out class ``scores``."""
    solver, other = roles(precision)
    normalize = not isinstance(precision, dict) or precision.get("normalize_patches", True)
    bank = learn_filters(
        jnp.asarray(train_x), jax.random.PRNGKey(seed), jnp.float32(cfg["zca_eps"]),
        jnp.float32(cfg["var_constant"]), patches=cfg["whitener_size"],
        filters=cfg["num_filters"], size=cfg["patch_size"], solver=solver,
    )
    x = features(cfg, train_x, bank, other, normalize)
    mean, std = _moments(x)
    y = 2.0 * jax.nn.one_hot(jnp.asarray(train_labels), cfg["num_classes"],
                             dtype=jnp.float32) - 1.0
    width = cfg["block_size"]
    starts = range(0, x.shape[1], width)

    def scaled(rows):
        return lambda b: (rows[:, starts[b]: starts[b] + width]
                          - mean[starts[b]: starts[b] + width]) / std[starts[b]: starts[b] + width]

    weights, _, intercept = weighted_bcd.fit(
        scaled(x), len(starts), y, epochs=cfg["num_iter"], lam=cfg["lam"], mix=0.0,
        precision={"solver": solver, "other": other},
    )
    del x
    held = features(cfg, held_x, bank, other, normalize)
    scores = weighted_bcd.predict(scaled(held), weights, intercept,
                                  precision={"solver": solver, "other": other})
    return {
        "features": jax.device_get(held[:feature_rows]),
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
    y = 2.0 * jax.nn.one_hot(jnp.asarray(train_labels), cfg["num_classes"],
                             dtype=jnp.float32) - 1.0
    weights, _, _ = weighted_bcd.fit(
        lambda b: x0, 1, y, epochs=1, lam=cfg["lam"], mix=0.0,
        precision={"solver": solver, "other": other},
    )
    return jax.device_get(weights[0])
