"""Operations and bytes of RandomPatchCifar's featurizer, from its shapes
alone (2 x multiply-accumulates, as ``ops_count.py`` counts): the
ALGORITHM's work — convolve every patch with every filter, normalise,
rectify, pool — whatever implements it.
"""

from __future__ import annotations


def pooled(image: int, patch: int, pool_size: int, pool_stride: int) -> tuple:
    """(output positions a side, pooling windows a side)."""
    out = image - patch + 1
    return out, (out - pool_size) // pool_stride + 1


def features(image: int, patch: int, filters: int, pool_size: int, pool_stride: int) -> int:
    """Numbers an image comes out as: windows x 2 channels a filter."""
    _, windows = pooled(image, patch, pool_size, pool_stride)
    return windows * windows * 2 * filters


def conv_per_image(image: int, channels: int, patch: int, filters: int, pool_size: int,
                   pool_stride: int) -> dict:
    """One image through convolution, per-patch normalisation, symmetric
    rectifier and sum pooling: the gemm of P = out^2 patches of
    d = patch^2 x channels numbers against K filters (2 P d K); the box
    sums of x and x^2 that give each patch its mean and variance (2 d an
    entry of each: 4 P d); per response a scale and an offset (2), two
    thresholds of two operations (4), and one addition into each window
    that holds it (2 windows^2 size^2 K for both channels).  Least bytes:
    the image in as it arrives (uint8), its features out in float32; the
    filters are read once a pass over the images (``filter_bytes``, in
    float32), not once an image."""
    out, windows = pooled(image, patch, pool_size, pool_stride)
    positions, d = out * out, patch * patch * channels
    flops = (
        2.0 * positions * d * filters + 4.0 * positions * d + 6.0 * positions * filters
        + 2.0 * windows * windows * pool_size * pool_size * filters
    )
    nbytes = float(image * image * channels) + 4.0 * features(
        image, patch, filters, pool_size, pool_stride)
    return {"flops": flops, "bytes": nbytes, "filter_bytes": 4.0 * d * filters,
            "gemm_flops": 2.0 * positions * d * filters}


def filter_learning(patches: int, d: int, filters: int) -> float:
    """The filter bank: the patch covariance (2 P d^2), its eigenvectors
    (~ 9 d^3), the whitener (2 d^3) and the whitening of the bank (2 K d^2)."""
    return 2.0 * patches * d * d + 11.0 * d**3 + 2.0 * filters * d * d
