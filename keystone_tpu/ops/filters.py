"""Shared small-filter helpers for the image feature extractors.

One Gaussian-kernel builder and one separable blur, used by dense SIFT
(per-scale pre-smoothing) and DAISY (orientation-map pooling) — keeping
truncation and padding semantics in one place.

The blur's default physical form is two banded-matrix MXU einsums (the
same linear-map-as-matmul rework `ops/sift._window_matrix` applied to
the SIFT windowing in r3): the r4 multi-scale roofline measured the
depthwise-conv form at ~0.1× of its HBM byte bound (~50 µs per conv,
8 convs per multi-scale batch — the conv emitter's fixed costs dominate
at these tiny kernels), where a (extent, extent) banded matmul is a few
µs of MXU work.  The conv form stays as the parity fallback.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np
from jax import lax

from keystone_tpu.utils import precision


def gaussian_kernel1d(sigma: float, truncate: float = 3.0) -> np.ndarray:
    """Normalized 1-D Gaussian, radius ⌈truncate·σ⌉ (≥1)."""
    r = max(1, int(np.ceil(truncate * sigma)))
    xs = np.arange(-r, r + 1, dtype=np.float32)
    k = np.exp(-0.5 * (xs / sigma) ** 2)
    return k / k.sum()


@functools.lru_cache(maxsize=64)
def _blur_matrix(extent: int, sigma: float, truncate: float = 3.0) -> np.ndarray:
    """(extent, extent) banded operator ≡ the SAME-zero-padded 1-D
    Gaussian conv along one axis: row i holds the kernel centered at i,
    TRUNCATED at the image edge without renormalization (zero padding's
    semantics — matches scipy ``mode="constant"``)."""
    k1 = gaussian_kernel1d(sigma, truncate)
    r = (k1.size - 1) // 2
    b = np.zeros((extent, extent), np.float32)
    for i in range(extent):
        lo, hi = i - r, i + r + 1
        klo = max(0, -lo)
        khi = k1.size - max(0, hi - extent)
        b[i, max(lo, 0) : min(hi, extent)] = k1[klo:khi]
    return b


#: image extent above which the banded-matmul blur falls back to the
#: conv form: the dense (extent, extent) operator makes the matmul pass
#: O(extent³) per axis vs the conv's O(k·extent²), and the measured win
#: (rounds 1–5, not re-measured) is at 128 px where the conv emitter's fixed costs
#: dominate.  512 px keeps the matmul pass within ~4 GF/axis/image —
#: still cheap MXU work — while callers on larger maps (e.g. DAISY on
#: full-resolution inputs) keep the byte-bound conv (ADVICE r4).
_MATMUL_BLUR_MAX_EXTENT = 512


def separable_apply(bh, bw, x, mxu: str = "f32"):
    """Apply a separable (rows-operator, cols-operator) pair to
    (n, h, w, c) maps as two MXU einsums: out = bh · x · bwᵀ per
    channel.  The single physical form shared by the banded-matrix blur
    below and the LCS box sums (ops/lcs.py); under the ``bf16_apply``
    policy both einsums cast their inputs to bf16 with f32 accumulation
    (utils/precision.apply_einsum), inert otherwise."""
    out = precision.apply_einsum("ph,nhwc->npwc", bh, x, mode=mxu)
    return precision.apply_einsum("qw,npwc->npqc", bw, out, mode=mxu)


def separable_gaussian_blur(x, sigma: float, strategy: str = "matmul", mxu: str = "f32"):
    """Separable Gaussian blur of (n, h, w, c) maps.

    SAME zero padding (matches scipy ``mode="constant"``); accumulation
    in f32 regardless of input dtype.  ``strategy="matmul"`` (default)
    runs the two 1-D passes as banded-matrix einsums on the MXU, falling
    back to conv above ``_MATMUL_BLUR_MAX_EXTENT``; ``"conv"`` keeps the
    depthwise-conv form (parity reference).  ``mxu`` is the resolved
    precision-policy mode: under ``bf16_apply`` the banded einsums cast
    their inputs to bf16 (utils/precision.apply_einsum), accumulation
    staying f32; the conv fallback stays true f32 in every mode."""
    if strategy == "matmul" and max(x.shape[1], x.shape[2]) > _MATMUL_BLUR_MAX_EXTENT:
        strategy = "conv"
    if strategy == "matmul":
        h, w = x.shape[1], x.shape[2]
        bh = jnp.asarray(_blur_matrix(h, float(sigma)))
        bw = jnp.asarray(_blur_matrix(w, float(sigma)))
        return separable_apply(bh, bw, x, mxu=mxu)
    c = x.shape[-1]
    k1 = jnp.asarray(gaussian_kernel1d(sigma))
    eye = jnp.eye(c)[None, None]
    out = lax.conv_general_dilated(
        x,
        k1.reshape(-1, 1, 1, 1) * eye,
        (1, 1),
        "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    )
    return lax.conv_general_dilated(
        out,
        k1.reshape(1, -1, 1, 1) * eye,
        (1, 1),
        "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    )
