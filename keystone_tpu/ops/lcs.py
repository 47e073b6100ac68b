"""Local Color Statistics descriptors.

Reference: nodes/images/LCSExtractor.scala — the second branch of the
ImageNet FV pipeline: per keypoint on a dense grid, the patch around it is
divided into ``grid × grid`` subpatches and the descriptor concatenates
each subpatch's per-channel mean and standard deviation
(dim = 2 · C · grid²; 96 for RGB with the default 4×4 grid).

TPU form: every subpatch corner a descriptor reads lies on the lattice
of stride ``g = gcd(step, sub)``, so the box sums of x and x² are taken
there only, over the crop the corners read (at ImageNet's step 6 and
subpatch 6, the 21 × 21 tiles of the first 126 rows and columns), and
each of the 16 subpatch offsets reads the lattice map of means and
standard deviations through one static strided slice: no gather, one
jitted program for the whole batch.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from keystone_tpu.workflow.transformer import Transformer
from keystone_tpu.utils import precision

_GRID = 4


class LCSExtractor(Transformer):
    """Input: (n, H, W, C) images.  Output: ((n, K, 2·C·16), mask)."""

    fusable = False

    def __init__(self, step: int = 4, subpatch_size: int = 6):
        self.step = int(step)
        self.subpatch_size = int(subpatch_size)

    def params(self):
        return (self.step, self.subpatch_size)

    def apply_batch(self, xs, mask=None):
        xs = jnp.asarray(xs, jnp.float32)
        if xs.ndim == 3:
            xs = xs[..., None]
        out = _lcs(xs, self.step, self.subpatch_size, mxu=precision.apply_mode())
        return out, jnp.ones(out.shape[:2], jnp.float32)

    def apply_one(self, x):
        return self.apply_batch(x[None])[0][0]


def _lcs_grid(extent: int, step: int, sub: int) -> np.ndarray:
    margin = 2 * sub  # patch = 4x4 subpatches of size sub
    lo, hi = margin, extent - margin
    if hi <= lo:
        return np.zeros((0,), np.int32)
    return np.arange(lo, hi, step, dtype=np.int32)


def _box_matrix(extent: int, sub: int, stride: int) -> np.ndarray:
    """(⌊(extent−sub)/stride⌋+1, extent) banded ones operator ≡ the VALID
    1-D box sum along one axis at ``stride``: row j sums
    x[j·stride : j·stride+sub].  The matmul twin of the reduce form's
    box sums, same trick as ops/filters._blur_matrix."""
    out = np.zeros(((extent - sub) // stride + 1, extent), np.float32)
    for j in range(out.shape[0]):
        out[j, j * stride : j * stride + sub] = 1.0
    return out


def _box_sums(x, sub: int, g: int, jy: int, jx: int, mxu: str):
    """Sums of x over the sub × sub windows whose top-left corners are
    (i·g, j·g), i < jy, j < jx: (n, jy, jx, C).  Only the crop those
    windows read enters; at g = sub the windows tile it."""
    x = x[:, : (jy - 1) * g + sub, : (jx - 1) * g + sub, :]
    if mxu == "bf16_apply":
        # apply policy (utils/precision.py): the separable box sums as
        # banded-ones MXU einsums with bf16 inputs / f32 accumulation —
        # the same linear-map-as-matmul rework (and the same physical
        # form, filters.separable_apply) as the banded blur.  Inert
        # modes keep the reduce_window form below bit-identical.
        from keystone_tpu.ops.filters import separable_apply

        bh = jnp.asarray(_box_matrix(x.shape[1], sub, g))
        bw = jnp.asarray(_box_matrix(x.shape[2], sub, g))
        return separable_apply(bh, bw, x, mxu=mxu)
    return lax.reduce_window(x, 0.0, lax.add, (1, sub, sub, 1), (1, g, g, 1), "VALID")


@partial(jax.jit, static_argnames=("step", "sub", "mxu"))
@jax.named_scope("lcs")
def _lcs(xs, step, sub, mxu: str = "f32"):
    n, h, w, c = xs.shape
    ky = _lcs_grid(h, step, sub).shape[0]
    kx = _lcs_grid(w, step, sub).shape[0]
    if ky == 0 or kx == 0:
        return jnp.zeros((n, ky * kx, _GRID * _GRID * 2 * c), jnp.float32)
    # keypoint i's subpatch corners are 2·sub + i·step + (−2, −1, 0, 1)·sub
    # = i·step + a·sub, a < 4: all on the lattice of stride g, the
    # farthest at lattice index ((k−1)·step + 3·sub) / g
    g = math.gcd(step, sub)
    p, s = step // g, sub // g
    jy = (ky - 1) * p + (_GRID - 1) * s + 1
    jx = (kx - 1) * p + (_GRID - 1) * s + 1
    area = float(sub * sub)
    with jax.named_scope("lcs.box_sums"):
        s1 = _box_sums(xs, sub, g, jy, jx, mxu)
        s2 = _box_sums(xs * xs, sub, g, jy, jx, mxu)
    with jax.named_scope("lcs.assemble"):
        mean = s1 / area
        var = jnp.maximum(s2 / area - mean * mean, 0.0)
        std = jnp.sqrt(var)
        feat = jnp.concatenate([mean, std], axis=-1)  # (n, jy, jx, 2C)

        def subpatch(a, b):  # subpatch (a, b) of every keypoint: (n, ky, kx, 2C)
            y0, x0 = a * s, b * s
            end = (n, y0 + (ky - 1) * p + 1, x0 + (kx - 1) * p + 1, 2 * c)
            return lax.slice(feat, (0, y0, x0, 0), end, (1, p, p, 1))

        desc = jnp.stack(
            [subpatch(a, b) for a in range(_GRID) for b in range(_GRID)], axis=3
        )  # (n, ky, kx, 16, 2C)
        return desc.reshape(n, ky * kx, _GRID * _GRID * 2 * c)
