"""Operations and bytes of exact kernel ridge regression by block
Gauss-Seidel over the dual, from its shapes alone (2 x multiply-accumulates,
as ``ops_count.py`` counts): the ALGORITHM's work, whatever implements it.
The exp, the norms and the masks are elementwise and left out.
"""

from __future__ import annotations


def _widths(n: int, block: int) -> list:
    return [min(block, n - lo) for lo in range(0, n, block)]


def krr_flops(n: int, d: int, k: int, block: int, epochs: int) -> float:
    """Per epoch and block of w rows: the distance gemm of the column block
    K(:, b) (2 n w d), the update F += K(:, b) delta (2 n w k), K_bb alpha_b
    (2 w^2 k), the w^3/3 Cholesky and its two triangular solves (2 w^2 k).
    Over an epoch of full blocks: 2 n^2 d + 2 n^2 k + (n / w)(w^3/3 + 4 w^2 k)."""
    per_epoch = sum(
        2.0 * n * w * d + 2.0 * n * w * k + w**3 / 3.0 + 4.0 * w * w * k
        for w in _widths(n, block)
    )
    return float(epochs * per_epoch)


def krr_bytes(n: int, d: int, k: int, block: int, epochs: int) -> float:
    """Least float32 traffic of the same sweep: per block step the rows read
    once (n d), F read and written and Y's block beside alpha's (3 n k as an
    upper count of the (n, k) arrays touched), and K_bb written for the solve
    (w^2).  The (n, w) column block itself never has to reach HBM: a fused
    kernel could multiply each tile by delta where it is made."""
    per_epoch = sum(4.0 * (n * d + 3.0 * n * k + w * w) for w in _widths(n, block))
    return float(epochs * per_epoch)
