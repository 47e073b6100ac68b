"""Plain reference of KeystoneML's ``ImageNetSiftLcsFV`` featurization and
scoring, written from the published descriptions in ``jax.numpy``: float32,
every product at the stated ``precision``, images in blocks of rows.  It
imports nothing of ``keystone_tpu`` and takes nothing that the program has
made; the vocabulary (PCA basis, diagonal GMM) is the benchmark's own, made
here from the seed (``make_vocabulary``), as the source pipeline may be given
one from files (``--siftPcaFile``, ``--siftGmmMeanFile`` ...).

  SIFT branch: channel mean -> gaussian blur (sigma^2 = (bin/6)^2 - 1/4) ->
    central-difference gradient -> 8 orientations, magnitude split linearly
    between the two nearest -> 4x4 spatial bins under a triangular window of
    support 2 bin - 1 on a dense grid (VLFeat's flat-window dsift) -> L2,
    clamp 0.2, L2 -> 128-d.
  LCS branch: per keypoint 4x4 subpatches, mean and standard deviation of
    each channel -> 96-d.
  both: (x - mean) C -> improved Fisher vector against a diagonal GMM
    (Perronnin & Sanchez) -> sign(x) sqrt|x| -> L2 row normalisation.
  concatenation -> x W + b.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import weighted_bcd
from benchmark.reference.weighted_bcd import dot

ORIENTATIONS = 8
GRID = 4


# ------------------------------------------------------------------ operators
def _centers(extent: int, step: int, half_patch: int) -> np.ndarray:
    return np.arange(half_patch, extent - half_patch, step)


def _triangular_operator(extent: int, step: int, bin_size: int) -> np.ndarray:
    """Row (center, bin): max(0, 1 - |pixel - (center + offset)| / bin) over the
    pixels of one axis; offsets are the bin centres (-1.5, -0.5, .5, 1.5) bin
    truncated toward zero.  Pixels outside the image add nothing."""
    centers = _centers(extent, step, 2 * bin_size)
    offsets = np.trunc((np.arange(GRID) - (GRID - 1) / 2.0) * bin_size)
    mids = (centers[:, None] + offsets[None, :]).reshape(-1, 1)
    pixels = np.arange(extent)[None, :]
    return np.maximum(0.0, 1.0 - np.abs(pixels - mids) / bin_size).astype(np.float32)


def _gaussian_operator(extent: int, sigma: float) -> np.ndarray:
    """Row i: the normalised gaussian of radius ceil(3 sigma) centred at pixel i,
    cut at the image's edge (zero padding, no renormalisation)."""
    radius = max(1, int(math.ceil(3.0 * sigma)))
    taps = np.exp(-0.5 * (np.arange(-radius, radius + 1, dtype=np.float32) / sigma) ** 2)
    taps = taps / taps.sum()
    op = np.zeros((extent, extent), np.float32)
    for i in range(extent):
        for t, j in enumerate(range(i - radius, i + radius + 1)):
            if 0 <= j < extent:
                op[i, j] = taps[t]
    return op


def _box_operator(extent: int, size: int) -> np.ndarray:
    op = np.zeros((extent - size + 1, extent), np.float32)
    for i in range(op.shape[0]):
        op[i, i:i + size] = 1.0
    return op


def _two_sided(left, maps, right, precision):
    """left (p, h), maps (n, h, w, c), right (q, w) -> (n, p, q, c)."""
    n, h, w, c = maps.shape
    rows = dot(left, maps.reshape(n, h, w * c), precision).reshape(n, -1, w, c)
    cols = dot(right, jnp.swapaxes(rows, 1, 2).reshape(n, w, -1), precision)
    return jnp.swapaxes(cols.reshape(n, right.shape[0], rows.shape[1], c), 1, 2)


# ---------------------------------------------------------------- descriptors
def sift_descriptors(images, cfg: dict, precision: str):
    """(n, H, W, 3) floats in [0, 1] -> (n, T, 128)."""
    step, bin_size = cfg["sift_step"], cfg["sift_bin_size"]
    gray = jnp.mean(images, axis=-1)
    n, h, w = gray.shape
    sigma2 = (bin_size / 6.0) ** 2 - 0.25
    if sigma2 > 0.04:
        sigma = math.sqrt(sigma2)
        gray = _two_sided(jnp.asarray(_gaussian_operator(h, sigma)), gray[..., None],
                          jnp.asarray(_gaussian_operator(w, sigma)), precision)[..., 0]
    dy = jnp.zeros_like(gray).at[:, 1:-1, :].set(0.5 * (gray[:, 2:, :] - gray[:, :-2, :]))
    dx = jnp.zeros_like(gray).at[:, :, 1:-1].set(0.5 * (gray[:, :, 2:] - gray[:, :, :-2]))
    magnitude = jnp.sqrt(dx * dx + dy * dy)
    theta = jnp.mod(jnp.arctan2(dy, dx), 2 * jnp.pi) * (ORIENTATIONS / (2 * jnp.pi))
    low = jnp.floor(theta)
    frac = theta - low
    low = jnp.mod(low.astype(jnp.int32), ORIENTATIONS)
    planes = magnitude[..., None] * (
        jax.nn.one_hot(low, ORIENTATIONS) * (1.0 - frac[..., None])
        + jax.nn.one_hot(jnp.mod(low + 1, ORIENTATIONS), ORIENTATIONS) * frac[..., None]
    )
    ay = _triangular_operator(h, step, bin_size)
    ax = _triangular_operator(w, step, bin_size)
    binned = _two_sided(jnp.asarray(ay), planes, jnp.asarray(ax), precision)
    ky, kx = ay.shape[0] // GRID, ax.shape[0] // GRID
    binned = binned.reshape(n, ky, GRID, kx, GRID, ORIENTATIONS)
    desc = jnp.transpose(binned, (0, 1, 3, 2, 4, 5)).reshape(n, ky * kx, -1)

    def l2(v):
        return v / jnp.maximum(jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True)), 1e-8)

    return l2(jnp.minimum(l2(desc), 0.2))


def lcs_descriptors(images, cfg: dict, precision: str):
    """(n, H, W, C) -> (n, T, 2 C 16): per 4x4 subpatch the channel means,
    then the channel standard deviations."""
    step, sub = cfg["lcs_step"], cfg["lcs_subpatch"]
    n, h, w, c = images.shape
    by, bx = jnp.asarray(_box_operator(h, sub)), jnp.asarray(_box_operator(w, sub))
    # box sums are exact products with 1: always at `highest`
    mean = _two_sided(by, images, bx, "highest") / (sub * sub)
    square = _two_sided(by, images * images, bx, "highest") / (sub * sub)
    stats = jnp.concatenate(
        [mean, jnp.sqrt(jnp.maximum(square - mean * mean, 0.0))], axis=-1
    )
    corners = (np.arange(GRID) - GRID // 2) * sub
    ys = (_centers(h, step, 2 * sub)[:, None] + corners[None, :]).reshape(-1)
    xs = (_centers(w, step, 2 * sub)[:, None] + corners[None, :]).reshape(-1)
    picked = stats[:, ys][:, :, xs]
    ky, kx = len(ys) // GRID, len(xs) // GRID
    picked = picked.reshape(n, ky, GRID, kx, GRID, 2 * c)
    return jnp.transpose(picked, (0, 1, 3, 2, 4, 5)).reshape(n, ky * kx, -1)


# -------------------------------------------------------------- Fisher vector
def fisher_vector(desc, vocab: dict, precision: str):
    """(n, T, D_in) descriptors -> (n, 2 K D): PCA, then the improved Fisher
    vector, signed square root and L2 normalisation."""
    n, t, _ = desc.shape
    z = dot(desc - vocab["pca_mean"], vocab["pca_components"], precision)
    w, mu, var = vocab["gmm_weights"], vocab["gmm_means"], vocab["gmm_variances"]
    flat = z.reshape(n * t, -1)
    inv = 1.0 / var
    quad = (dot(flat * flat, inv.T, precision) - 2.0 * dot(flat, (mu * inv).T, precision)
            + jnp.sum(mu * mu * inv, axis=1))
    log_p = (jnp.log(w) - 0.5 * (jnp.sum(jnp.log(var), axis=1)
                                 + z.shape[-1] * math.log(2 * math.pi)) - 0.5 * quad)
    gamma = jax.nn.softmax(log_p, axis=1).reshape(n, t, -1)
    s0 = jnp.sum(gamma, axis=1)
    gt = jnp.swapaxes(gamma, 1, 2)
    s1 = dot(gt, z, precision)
    s2 = dot(gt, z * z, precision)
    sigma = jnp.sqrt(var)
    phi1 = (s1 - s0[..., None] * mu) / sigma / (t * jnp.sqrt(w)[None, :, None])
    phi2 = ((s2 - 2.0 * mu * s1 + s0[..., None] * mu * mu) / var - s0[..., None]) / (
        t * jnp.sqrt(2.0 * w)[None, :, None])
    fv = jnp.concatenate([phi1.reshape(n, -1), phi2.reshape(n, -1)], axis=1)
    fv = jnp.sign(fv) * jnp.sqrt(jnp.abs(fv))
    return fv / jnp.maximum(jnp.sqrt(jnp.sum(fv * fv, axis=1, keepdims=True)), 1e-12)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _features_block(images_u8, vocab, cfg_key, precision):
    cfg = dict(cfg_key)
    images = images_u8.astype(jnp.float32) / 255.0
    return jnp.concatenate([
        fisher_vector(sift_descriptors(images, cfg, precision), vocab["sift"], precision),
        fisher_vector(lcs_descriptors(images, cfg, precision), vocab["lcs"], precision),
    ], axis=1)


def _cfg_key(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in ("sift_step", "sift_bin_size", "lcs_step", "lcs_subpatch"))


def features(images_u8, vocab: dict, cfg: dict, precision: str = "highest", rows: int = 256):
    """uint8 images (host) -> (n, 65,536 at the published widths) features on
    the device, ``rows`` images at a time."""
    parts = [
        _features_block(jnp.asarray(images_u8[i:i + rows]), vocab, _cfg_key(cfg), precision)
        for i in range(0, images_u8.shape[0], rows)
    ]
    return jnp.concatenate(parts, axis=0)


def scores(images_u8, vocab: dict, model: dict, cfg: dict, precision: str = "highest",
           rows: int = 256):
    """Class scores (n, classes) of a given linear model, as a host array."""
    out = []
    for i in range(0, images_u8.shape[0], rows):
        f = _features_block(jnp.asarray(images_u8[i:i + rows]), vocab, _cfg_key(cfg), precision)
        out.append(np.asarray(dot(f, model["weights"], precision) + model["intercept"]))
    return np.concatenate(out, axis=0)


@functools.partial(jax.jit, static_argnames=("width",))
def _columns(x, start, width):
    return jax.lax.dynamic_slice_in_dim(x, start, width, axis=1)


def fit_and_score(cfg: dict, train_u8, train_labels, held_u8, vocab: dict, *, epochs: int,
                  precision="highest"):
    """Featurize with the given vocabulary, solve the class-weighted block
    least squares over the features, and score the held-out images: host
    array (h, classes)."""
    _, other = weighted_bcd.roles(precision)
    x = features(train_u8, vocab, cfg, other)
    held = features(held_u8, vocab, cfg, other)
    y = 2.0 * jax.nn.one_hot(jnp.asarray(train_labels), cfg["num_classes"],
                             dtype=jnp.float32) - 1.0
    block = cfg["solver_block_size"]
    num_blocks = x.shape[1] // block
    weights, _, intercept = weighted_bcd.fit(
        lambda b: _columns(x, b * block, block), num_blocks, y, epochs=epochs,
        lam=cfg["lam"], mix=cfg["mixture_weight"], precision=precision,
    )
    out = weighted_bcd.predict(lambda b: _columns(held, b * block, block), weights,
                               intercept, precision=precision)
    return np.asarray(out)


# ------------------------------------------------------------------ vocabulary
def _fit_vocabulary(desc, cfg: dict, key):
    """PCA basis and diagonal GMM of one branch from sampled descriptors
    (m, D_in): eigenvectors of the covariance, then EM from means drawn
    among the projected descriptors.  Smooth and the benchmark's own: the
    program is handed the result, it does not fit one."""
    d, k = cfg["pca_dims"], cfg["gmm_k"]
    mean = jnp.mean(desc, axis=0)
    centered = desc - mean
    cov = dot(centered.T, centered, "highest") / desc.shape[0]
    _, vecs = jnp.linalg.eigh(cov)
    components = vecs[:, ::-1][:, :d]
    z = dot(centered, components, "highest")
    pick = jax.random.choice(key, z.shape[0], (k,), replace=False)
    mu, var = z[pick], jnp.tile(jnp.var(z, axis=0)[None, :], (k, 1))
    w = jnp.full((k,), 1.0 / k, jnp.float32)
    for _ in range(int(cfg.get("vocabulary_em_steps", 4))):
        inv = 1.0 / var
        quad = (dot(z * z, inv.T, "highest") - 2.0 * dot(z, (mu * inv).T, "highest")
                + jnp.sum(mu * mu * inv, axis=1))
        log_p = jnp.log(w) - 0.5 * jnp.sum(jnp.log(var), axis=1) - 0.5 * quad
        gamma = jax.nn.softmax(log_p, axis=1)
        nk = jnp.maximum(jnp.sum(gamma, axis=0), 1e-6)
        mu = dot(gamma.T, z, "highest") / nk[:, None]
        var = jnp.maximum(dot(gamma.T, z * z, "highest") / nk[:, None] - mu * mu, 1e-4)
        w = nk / jnp.sum(nk)
    return {"pca_mean": mean, "pca_components": components,
            "gmm_weights": w, "gmm_means": mu, "gmm_variances": var}


def make_vocabulary(images_u8, cfg: dict, seed: int) -> dict:
    """The two branches' vocabularies from ``images_u8`` (a few hundred
    images are plenty) and the seed: ``descriptor_samples_per_image``
    descriptors of each image, drawn from the seed."""
    images = jnp.asarray(images_u8).astype(jnp.float32) / 255.0
    key = jax.random.PRNGKey(seed)
    out = {}
    for name, fn in (("sift", sift_descriptors), ("lcs", lcs_descriptors)):
        key, k_pick, k_fit = jax.random.split(key, 3)
        desc = jax.jit(lambda im, fn=fn: fn(im, cfg, "highest"))(images)
        n, t, _ = desc.shape
        take = min(cfg["descriptor_samples_per_image"], t)
        idx = jax.vmap(lambda k: jax.random.choice(k, t, (take,), replace=False))(
            jax.random.split(k_pick, n))
        sampled = jnp.take_along_axis(desc, idx[..., None], axis=1).reshape(n * take, -1)
        out[name] = _fit_vocabulary(sampled, cfg, k_fit)
    return out
