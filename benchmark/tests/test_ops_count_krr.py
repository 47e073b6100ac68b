"""``ops_count_krr.py`` against a brute-force tally at small shapes: every
multiply-accumulate of the sweep's matrix products counted where a plain
loop would make it (2 operations each), the Cholesky factorisation and its
two triangular solves by their textbook counts; and the figures PERF.md
quotes for the cell."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import ops_count_krr  # noqa: E402


def tally(n, d, k, block, epochs):
    flops = nbytes = 0
    for _ in range(epochs):
        for lo in range(0, n, block):
            w = min(block, n - lo)
            for _row in range(n):  # the column block K(:, b)
                for _col in range(w):
                    flops += 2 * d  # x_i . z_j over d features
            for _row in range(n):  # F += K(:, b) delta
                for _cls in range(k):
                    flops += 2 * w
            for _row in range(w):  # K_bb alpha_b
                for _cls in range(k):
                    flops += 2 * w
            flops += w**3 / 3.0  # Cholesky
            flops += 2 * (w * w * k)  # forward and back substitution, w^2 each a column
            nbytes += 4 * (n * d + 3 * n * k + w * w)
    return flops, nbytes


@pytest.mark.parametrize("n,d,k,block,epochs", [(12, 3, 2, 4, 1), (10, 5, 3, 4, 2), (7, 2, 1, 7, 3)])
def test_counts_match_a_brute_force_tally(n, d, k, block, epochs):
    flops, nbytes = tally(n, d, k, block, epochs)
    assert ops_count_krr.krr_flops(n, d, k, block, epochs) == pytest.approx(flops, rel=1e-12)
    assert ops_count_krr.krr_bytes(n, d, k, block, epochs) == pytest.approx(nbytes, rel=1e-12)


def test_the_cells_own_figures():
    n, d, k, block = 196608, 440, 147, 4096
    flops = ops_count_krr.krr_flops(n, d, k, block, 1)
    # an epoch of full blocks: 2 n^2 (d + k) + (n / b)(b^3 / 3 + 4 b^2 k)
    assert flops == pytest.approx(2.0 * n * n * (d + k) + 48 * (block**3 / 3.0 + 4.0 * block**2 * k))
    assert 46.9e12 < flops < 47.0e12  # 46.95 TF a fit, 1.57 TF of it the block solves
    assert ops_count_krr.krr_bytes(n, d, k, block, 1) == pytest.approx(
        48 * 4.0 * (n * d + 3 * n * k + block**2))
    assert ops_count_krr.krr_flops(n, d, k, block, 3) == 3 * flops
