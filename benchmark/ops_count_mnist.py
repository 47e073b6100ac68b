"""Operations and bytes of MnistRandomFFT's featurizer, from its shapes
alone: the ALGORITHM's work — a real FFT of every branch's padded image —
whatever implements it, so that a later DFT-as-matmul kernel is read
against the same work as the FFT it replaces.
"""

from __future__ import annotations

import math


def features(padded: int, ffts: int) -> int:
    """Numbers an image comes out as: the first half of each transform."""
    return ffts * (padded // 2)


def fft_per_image(pixels: int, padded: int, ffts: int) -> dict:
    """One image through ``ffts`` branches of sign, transform and rectifier:
    a ``padded``-point real FFT's 2.5 N log2 N operations a branch (the sign
    flip and the rectifier are elementwise and left out); least bytes a
    branch, its ``pixels`` float32 in and its ``padded / 2`` float32 out."""
    flops = 2.5 * padded * math.log2(padded)
    nbytes = 4.0 * (pixels + padded // 2)
    return {"flops": ffts * flops, "bytes": ffts * nbytes}
