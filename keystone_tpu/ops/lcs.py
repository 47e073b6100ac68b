"""Local Color Statistics descriptors.

Reference: nodes/images/LCSExtractor.scala — the second branch of the
ImageNet FV pipeline: per keypoint on a dense grid, the patch around it is
divided into ``grid × grid`` subpatches and the descriptor concatenates
each subpatch's per-channel mean and standard deviation
(dim = 2 · C · grid²; 96 for RGB with the default 4×4 grid).

TPU form: subpatch means/E[x²] are box-filter convolutions
(reduce_window sums), gathered at the keypoint grid — one jitted program
for the whole batch.
"""

from __future__ import annotations

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


def _box_matrix(extent: int, sub: int) -> np.ndarray:
    """(extent−sub+1, extent) banded ones operator ≡ the VALID stride-1
    1-D box sum along one axis: row y sums x[y : y+sub].  The matmul
    twin of the reduce_window box filter, same trick as
    ops/filters._blur_matrix."""
    out = np.zeros((extent - sub + 1, extent), np.float32)
    for y in range(out.shape[0]):
        out[y, y : y + sub] = 1.0
    return out


@partial(jax.jit, static_argnames=("step", "sub", "mxu"))
@jax.named_scope("lcs")
def _lcs(xs, step, sub, mxu: str = "f32"):
    n, h, w, c = xs.shape
    area = float(sub * sub)
    dims = (1, sub, sub, 1)
    ones = (1, 1, 1, 1)
    # box sums of x and x² with stride 1, VALID: index (y, x) = sum of
    # the sub×sub box whose top-left corner is (y, x)
    if mxu == "bf16_apply":
        # apply policy (utils/precision.py): the separable box sums as
        # banded-ones MXU einsums with bf16 inputs / f32 accumulation —
        # the same linear-map-as-matmul rework (and the same physical
        # form, filters.separable_apply) as the banded blur.  Inert
        # modes keep the reduce_window form below bit-identical.
        from keystone_tpu.ops.filters import separable_apply

        bh = jnp.asarray(_box_matrix(h, sub))
        bw = jnp.asarray(_box_matrix(w, sub))
        s1 = separable_apply(bh, bw, xs, mxu=mxu)
        s2 = separable_apply(bh, bw, xs * xs, mxu=mxu)
    else:
        s1 = lax.reduce_window(xs, 0.0, lax.add, dims, ones, "VALID")
        s2 = lax.reduce_window(xs * xs, 0.0, lax.add, dims, ones, "VALID")
    mean = s1 / area
    var = jnp.maximum(s2 / area - mean * mean, 0.0)
    std = jnp.sqrt(var)
    feat = jnp.concatenate([mean, std], axis=-1)  # (n, h', w', 2C)

    ys = jnp.asarray(_lcs_grid(h, step, sub))
    xs_ = jnp.asarray(_lcs_grid(w, step, sub))
    # subpatch top-left corners relative to keypoint: (-2,-1,0,1)*sub
    offs = ((jnp.arange(_GRID) - _GRID // 2) * sub).astype(jnp.int32)
    yy = (ys[:, None] + offs[None, :]).reshape(-1)
    xx = (xs_[:, None] + offs[None, :]).reshape(-1)
    g = feat[:, yy, :, :][:, :, xx, :]  # (n, Ky*4, Kx*4, 2C)
    ky, kx = ys.shape[0], xs_.shape[0]
    g = g.reshape(n, ky, _GRID, kx, _GRID, 2 * c)
    return jnp.transpose(g, (0, 1, 3, 2, 4, 5)).reshape(
        n, ky * kx, _GRID * _GRID * 2 * c
    )
