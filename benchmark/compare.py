"""The comparisons that decide ``correct``: each gives one number, which
the harness prints beside its limit.  ``got`` of another shape than ``ref``,
or not finite, reads inf."""

from __future__ import annotations

import numpy as np


def _gap(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return None, ref
    return got - ref, ref


def _centered_std(ref) -> float:
    """Spread of the reference's scores from row to row: each column (class)
    about its own mean, so that an offset shared by every row of a class does
    not pass for signal."""
    return float(np.std(ref - ref.mean(axis=0, keepdims=True)))


def rmse_over_std(got, ref) -> float:
    """Root mean square of (got - ref) over the standard deviation of ref."""
    gap, ref = _gap(got, ref)
    return float("inf") if gap is None else float(np.sqrt(np.mean(gap**2)) / np.std(ref))


def relative_error(got, ref) -> float:
    """Frobenius norm of (got - ref) over that of ref."""
    gap, ref = _gap(got, ref)
    return float("inf") if gap is None else float(np.linalg.norm(gap) / np.linalg.norm(ref))


def max_gap_over_std(got, ref) -> float:
    """The widest gap of one entry over the standard deviation of ref: what
    one altered answer moves."""
    gap, ref = _gap(got, ref)
    return float("inf") if gap is None else float(np.max(np.abs(gap)) / np.std(ref))


def rmse_over_centered_std(got, ref) -> float:
    gap, ref = _gap(got, ref)
    return float("inf") if gap is None else float(np.sqrt(np.mean(gap**2)) / _centered_std(ref))


def worst_row_rmse_over_centered_std(got, ref) -> float:
    """The row (one answer) that lies farthest from the reference's, by its
    root mean square gap over the reference's centred spread: what one
    altered answer moves, where single entries swing by their nature."""
    gap, ref = _gap(got, ref)
    if gap is None:
        return float("inf")
    return float(np.sqrt(np.max(np.mean(gap**2, axis=1))) / _centered_std(ref))


def median_row_rmse_over_centered_std(got, ref) -> float:
    """The median row's root mean square gap over the reference's centred
    spread: steady from seed to seed where a few rows lie far off."""
    gap, ref = _gap(got, ref)
    if gap is None:
        return float("inf")
    return float(np.sqrt(np.median(np.mean(gap**2, axis=1))) / _centered_std(ref))

