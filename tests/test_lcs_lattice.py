"""LCS on the keypoints' lattice (ops/lcs.py § _lcs).

The descriptors are defined by stride-1 box sums of x and x² read at
every keypoint's 16 subpatch corners; the program takes the sums only on
the lattice of stride gcd(step, sub) that those corners lie on and reads
each subpatch offset by a static strided slice.  Held here to the plain
definition: exactly in the reduce form (pixels k/16 make every sum exact
in float32, whatever the order of additions), within bf16's tolerance in
the ``bf16_apply`` form.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.ops.lcs import LCSExtractor, _lcs

_GRID = 4


def _reference(x, step, sub):
    """(n, ky·kx, 2·C·16): keypoints row-major, then subpatch y, subpatch
    x, then [mean C, std C] — from the stride-1 box-sum map."""
    n, h, w, c = x.shape
    hh, ww = h - sub + 1, w - sub + 1
    x64 = x.astype(np.float64)
    s1 = np.zeros((n, hh, ww, c))
    s2 = np.zeros((n, hh, ww, c))
    for dy in range(sub):
        for dx in range(sub):
            s1 += x64[:, dy : dy + hh, dx : dx + ww]
            s2 += x64[:, dy : dy + hh, dx : dx + ww] ** 2
    # E[x²] − mean² as XLA compiles it on the CPU: each division by the
    # constant area a product with its float32 reciprocal, and
    # s2 · inv − mean² one fused multiply-add (the product s2 · inv
    # exact, one rounding at the end)
    inv = np.float32(1.0 / (sub * sub))
    mean = s1.astype(np.float32) * inv
    var = s2 * np.float64(inv) - (mean * mean).astype(np.float64)
    var = np.maximum(var.astype(np.float32), np.float32(0))
    feat = np.concatenate([mean, np.sqrt(var)], axis=-1)
    ys = np.arange(2 * sub, h - 2 * sub, step)
    xs = np.arange(2 * sub, w - 2 * sub, step)
    offs = (np.arange(_GRID) - _GRID // 2) * sub
    desc = [
        np.concatenate([feat[:, y + a, x + b] for a in offs for b in offs], axis=-1)
        for y in ys
        for x in xs
    ]
    if not desc:
        return np.zeros((n, 0, 2 * c * _GRID * _GRID), np.float32)
    return np.stack(desc, axis=1)


def _pixels(n, h, w, c=3, seed=0):
    """k/16, k < 256: sums of 36 of these or of their squares are exact
    in float32."""
    k = np.random.default_rng(seed).integers(0, 256, (n, h, w, c))
    return (k / 16.0).astype(np.float32)


_CASES = [
    pytest.param(6, 6, 128, 128, id="imagenet-6-6"),
    pytest.param(4, 6, 64, 64, id="defaults-4-6"),
    pytest.param(5, 7, 64, 64, id="coprime-5-7"),
    pytest.param(6, 4, 40, 40, id="6-4"),
    pytest.param(8, 4, 48, 48, id="8-4"),
    pytest.param(6, 6, 96, 70, id="non-square"),
    pytest.param(6, 6, 20, 24, id="no-keypoints"),
]


@pytest.mark.parametrize("step,sub,h,w", _CASES)
def test_lattice_matches_the_stride_one_definition(step, sub, h, w):
    x = _pixels(2, h, w)
    ref = _reference(x, step, sub)
    got = np.asarray(_lcs(jnp.asarray(x), step, sub))
    assert got.shape == ref.shape
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("step,sub,h,w", _CASES)
def test_lattice_bf16_apply_within_bf16_tolerance(step, sub, h, w):
    x = _pixels(2, h, w, seed=1)
    ref = _reference(x, step, sub)
    got = np.asarray(_lcs(jnp.asarray(x), step, sub, mxu="bf16_apply"))
    assert got.shape == ref.shape
    # tests/test_bf16_apply.py § _tol: 2 % of the largest entry
    tol = float(2e-2 * np.abs(ref).max(initial=0.0) + 1e-6)
    np.testing.assert_allclose(got, ref, atol=tol)


def test_extractor_output_is_the_lattice_descriptors():
    x = _pixels(3, 128, 128)
    desc, mask = LCSExtractor(step=6, subpatch_size=6).apply_batch(jnp.asarray(x))
    assert desc.shape == (3, 18 * 18, 96) and mask.shape == (3, 18 * 18)
    np.testing.assert_array_equal(np.asarray(desc), _reference(x, 6, 6))


def test_imagenet_program_has_no_gather_and_sums_on_the_lattice():
    """At step 6, subpatch 6 on 128 × 128 the box sums output the 21 × 21
    lattice, and neither the lowered nor the compiled program gathers."""
    x = jnp.zeros((2, 128, 128, 3), jnp.float32)
    lowered = _lcs.lower(x, 6, 6)
    text = lowered.as_text()
    assert "gather" not in text
    assert "dynamic_update_slice" not in text
    sums = [ln for ln in text.splitlines() if "reduce_window" in ln]
    assert sums and all("tensor<2x21x21x3xf32>" in ln for ln in sums if "->" in ln)
    assert "123x123" not in text
    compiled = lowered.compile().as_text()
    assert "gather(" not in compiled
    assert "dynamic-update-slice(" not in compiled
    assert "lcs.box_sums" in compiled and "lcs.assemble" in compiled
