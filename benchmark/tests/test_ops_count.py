"""``ops_count.py`` against hand-worked small shapes."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import ops_count  # noqa: E402


def test_solver_flops_one_block():
    # n=10 rows, d=4 in one block of 4, k=3, one epoch:
    # Gramian 2*10*16 = 320, cross + target + residual 6*10*4*3 = 720,
    # Cholesky 64/3, triangular solves 2*16*3 = 96
    assert ops_count.solver_flops(10, 4, 3, 4, 1) == 320 + 720 + 64 / 3 + 96


def test_solver_flops_ragged_tail_and_epochs():
    # d=6 in blocks of 4: widths 4 and 2; two epochs double it
    one = (2 * 10 * 16 + 6 * 10 * 4 * 3 + 64 / 3 + 96) + (
        2 * 10 * 4 + 6 * 10 * 2 * 3 + 8 / 3 + 2 * 4 * 3
    )
    assert abs(ops_count.solver_flops(10, 6, 3, 4, 2) - 2 * one) < 1e-9


def test_solver_bytes():
    # block read 10*4, y + residual read + residual written 3*10*3,
    # Gramian 16, weights read and written 2*4*3; float32
    assert ops_count.solver_bytes(10, 4, 3, 4, 1) == 4 * (40 + 90 + 16 + 24)


def test_cosine_features():
    assert ops_count.cosine_features_flops(5, 3, 7) == 2 * 5 * 3 * 7
    assert ops_count.cosine_features_bytes(5, 3, 7) == 4 * (15 + 21 + 35)


def test_sift_lcs_fv_per_image_at_the_published_widths_by_hand():
    # 128x128 image, SIFT step 4 / bin 4: centers 8..119 -> 28 a side, P = 112,
    # T = 784; LCS step 6 / subpatch 6: centers 12..115 -> 18 a side, T = 324
    got = ops_count.sift_lcs_fv_per_image(128, 4, 4, 6, 64, 256, 1000, 784, 324)
    sift = 2 * 112 * 128 * 128 * 8 + 2 * 112 * 128 * 112 * 8 + 4 * 128**3
    fv_sift = 2 * 784 * 128 * 64 + 8 * 784 * 64 * 256
    fv_lcs = 2 * 324 * 96 * 64 + 8 * 324 * 64 * 256
    assert got["featurize_flops"] == sift + fv_sift + fv_lcs
    assert got["fv_kernel_flops"] == fv_sift + fv_lcs
    assert got["scoring_flops"] == 2 * 65536 * 1000
    assert got["image_bytes"] == 3 * 128 * 128
    one = ops_count.fv_kernel_per_image(784, 128, 64, 256)
    assert one["bytes"] == 2 * 784 * 128 + 4 * (128 * 64 + 2 * 256 * 64) + 4 * 2 * 256 * 64


def test_roofline_seconds_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert ops_count.roofline_seconds(400.0, 10.0, peaks, 2) == (2.0, "flops")
    assert ops_count.roofline_seconds(100.0, 100.0, peaks, 1) == (10.0, "bytes")
