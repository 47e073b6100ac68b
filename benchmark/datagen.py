"""The benchmark's own seeded data: the same seed gives the same inputs.

Everything is made in bulk (one vectorised host call, or one jitted call
on the devices straight into the shards), because every run of every later
check pays this as set-up.  ``--seed`` may be a little over 2**31, so it is
folded before it reaches a PRNG key.
"""

from __future__ import annotations

import numpy as np

_FOLD = 2**31 - 4096  # room for the small offsets added below


def fold(seed: int) -> int:
    return int(seed) % _FOLD


def timit_frames(n: int, dim: int, classes: int, seed: int, skew: float = 0.5):
    """MFCC-like frames around one prototype per phone state, as
    ``loaders/timit.py § synthetic`` makes them (prototype + 0.8 noise), with
    class frequencies ~ (1 + c)^-skew so that the class weighting of the
    solver has something to weigh.  Returns float32 (n, dim), int32 (n,)."""
    rng = np.random.default_rng([fold(seed), 17])
    prototypes = rng.standard_normal((classes, dim), dtype=np.float32)
    freq = (1.0 + np.arange(classes)) ** -skew
    labels = rng.choice(classes, size=n, p=freq / freq.sum()).astype(np.int32)
    labels[:classes] = np.arange(classes)  # every class is seen at least once
    x = prototypes[labels] + np.float32(0.8) * rng.standard_normal((n, dim), dtype=np.float32)
    return x, labels


def window(x, labels, n: int, index: int, views: int, step: int):
    """Fit ``index``'s own rows: ``n`` rows from offset ``(index % views) * step``
    of a buffer of ``n + (views - 1) * step`` seeded rows.  A view, no copy:
    every fit gets another array object with other content, so that nothing
    keyed on the data can answer for it, at no cost in set-up."""
    lo = (index % views) * step
    return x[lo: lo + n], labels[lo: lo + n]


def fv_like_rows(n: int, d: int, classes: int, seed: int, shardings=None, stream: int = 0):
    """Dense float32 rows at the Fisher-vector width with a class signal,
    and their +-1 indicator labels, made ON the devices in one jitted call
    (no host copy of the matrix ever exists).  Row i of class c is
    gaussian noise plus 0.25 cos(2 pi ((c (j + 1)) mod K) / K) in column j:
    rows of a DFT matrix, so the classes are told apart by the solve."""
    import jax
    import jax.numpy as jnp

    def gen(key):
        kx, kl = jax.random.split(key)
        lab = jax.random.randint(kl, (n,), 0, classes)
        col = jnp.arange(1, d + 1, dtype=jnp.int32)
        phase = (lab[:, None] * col[None, :]) % classes
        signal = 0.25 * jnp.cos((2.0 * jnp.pi / classes) * phase.astype(jnp.float32))
        x = jax.random.normal(kx, (n, d), jnp.float32) + signal
        return x, 2.0 * jax.nn.one_hot(lab, classes, dtype=jnp.float32) - 1.0

    # the key is an ARGUMENT: one program for every seed, so that every run
    # after a checkout's first finds it in the compile cache
    key = jax.random.fold_in(jax.random.PRNGKey(fold(seed)), stream)
    return jax.jit(gen, out_shardings=shardings)(key)


def texture_images(n: int, size: int, classes: int, seed: int, stream: int = 0,
                   rows: int = 4096):
    """Class-structured texture images as ``loaders/imagenet.py § _synth_image``
    draws them (an oriented grating whose angle, frequency and colour follow
    the class, a random phase, 0.05 gaussian noise), made on the device
    ``rows`` images to a jitted call and read back once per call: uint8
    (n, size, size, 3) and int32 labels (n,), on the host."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(key):
        k_lab, k_phase, k_noise = jax.random.split(key, 3)
        lab = jax.random.randint(k_lab, (rows,), 0, classes)
        angle = jnp.pi * lab / classes
        freq = 0.2 + 0.05 * (lab % 4)
        phase = jax.random.uniform(k_phase, (rows,), jnp.float32, 0.0, 2 * jnp.pi)
        yy, xx = jnp.meshgrid(jnp.arange(size, dtype=jnp.float32),
                              jnp.arange(size, dtype=jnp.float32), indexing="ij")
        arg = freq[:, None, None] * (jnp.cos(angle)[:, None, None] * xx
                                     + jnp.sin(angle)[:, None, None] * yy)
        grating = 0.5 + 0.5 * jnp.sin(arg + phase[:, None, None])
        color = 0.3 + 0.6 * ((lab[:, None] >> jnp.arange(3)[None, :]) & 1)
        img = grating[..., None] * color[:, None, None, :]
        img = img + 0.05 * jax.random.normal(k_noise, img.shape, jnp.float32)
        return jnp.rint(jnp.clip(img, 0.0, 1.0) * 255.0).astype(jnp.uint8), lab

    base = jax.random.fold_in(jax.random.PRNGKey(fold(seed)), 1000 + stream)
    images, labels = [], []
    for i in range(-(-n // rows)):
        img, lab = block(jax.random.fold_in(base, i))
        images.append(np.asarray(img))
        labels.append(np.asarray(lab))
    return np.concatenate(images)[:n], np.concatenate(labels)[:n].astype(np.int32)


def linear_model(d: int, classes: int, seed: int):
    """A linear scoring model made on the device from the seed, in the type
    it is served in: weights (d, classes) ~ N(0, 1/d) and an intercept."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        kw, kb = jax.random.split(key)
        return (jax.random.normal(kw, (d, classes), jnp.float32),
                0.1 * jax.random.normal(kb, (classes,), jnp.float32))

    return gen(jax.random.fold_in(jax.random.PRNGKey(fold(seed)), 2000))
