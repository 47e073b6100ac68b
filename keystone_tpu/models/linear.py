"""Exact least-squares solvers.

Reference: nodes/learning/LinearMapper.scala § LinearMapEstimator /
LinearMapper and nodes/learning/LocalLeastSquaresEstimator.scala.

The reference computes per-partition ``AᵀA`` / ``Aᵀb`` gemms, treeReduces
them to the driver, Cholesky-solves there, and broadcasts the model.  Here
the whole fit is ONE jitted program: the einsum contraction over the
row-sharded batch axis becomes an XLA all-reduce over ICI, and the solve
runs replicated on every device — no driver round-trip exists at all.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from keystone_tpu.models.common import (
    kahan_add,
    solve_spd,
    stage_stream_batch,
    xtx_xty,
)
from keystone_tpu.workflow.dataset import Dataset
from keystone_tpu.workflow.estimator import LabelEstimator
from keystone_tpu.workflow.transformer import Transformer
from keystone_tpu.utils.precision import sdot


class LinearMapper(Transformer):
    """Applies ``xW + b`` (nodes/learning/LinearMapper.scala § LinearMapper)."""

    traced_attrs = ("weights", "intercept")

    def __init__(self, weights: jnp.ndarray, intercept: Optional[jnp.ndarray] = None):
        self.weights = weights
        self.intercept = intercept

    def apply_one(self, x):
        out = x @ self.weights
        if self.intercept is not None:
            out = out + self.intercept
        return out

    def apply_batch(self, xs, mask=None):
        out = xs @ self.weights
        if self.intercept is not None:
            out = out + self.intercept
        return out

    def apply_dataset(self, ds):
        # sparse scoring (LBFGS.scala sparse path): score scipy rows by
        # gathering weight rows — never densify n×d at huge vocab
        from keystone_tpu.ops.sparse import is_scipy_sparse_rows, score_sparse_dataset

        if ds.is_host and is_scipy_sparse_rows(ds.items):
            return score_sparse_dataset(ds, self.weights, self.intercept)
        return super().apply_dataset(ds)


class LinearMapEstimator(LabelEstimator):
    """Exact ridge least squares via normal equations
    (nodes/learning/LinearMapper.scala § LinearMapEstimator).

    With ``fit_intercept`` the solve runs on (weighted-)centered data and
    recovers the intercept as ``ȳ − x̄·W``, matching the reference's
    mean-subtraction path.
    """

    def __init__(self, lam: float = 0.0, fit_intercept: bool = True):
        self.lam = float(lam)
        self.fit_intercept = fit_intercept

    def params(self):
        return (self.lam, self.fit_intercept)

    def choose_physical(self, sample, full_n=None):
        """Physical choice (workflow/NodeOptimizationRule), two axes like
        the reference's rule:

        - sparsity: on host datasets of scipy sparse rows, the dense
          normal equations would densify n×d AND form a d×d Gram —
          infeasible at text-scale vocabularies — so route to the
          sparse-gradient L-BFGS solver, which minimizes the SAME
          objective (1/(2n)‖XW−Y‖² + λ/2‖W‖² ⇒ (XᵀX+λnI)W = XᵀY).  An
          intercept survives the swap (unregularized constant column).
        - size: when the FULL problem is small (n·d below the measured
          crossover — rounds 1–5, not re-measured), pick
          :class:`LocalLeastSquaresEstimator`, the unsharded
          single-device solve with no collectives and no mesh padding
          (the reference's collect()+LAPACK path for small data)."""
        from keystone_tpu.ops.sparse import is_scipy_sparse_rows

        if sample is not None and sample.is_host and is_scipy_sparse_rows(
            sample.items
        ):
            from keystone_tpu.models.lbfgs import SparseLBFGSwithL2

            return SparseLBFGSwithL2(
                lam=self.lam,
                num_iterations=100,
                fit_intercept=self.fit_intercept,
            )
        if (
            sample is not None
            and not sample.is_host
            and full_n is not None
            and sample.array.ndim == 2
            and full_n * sample.array.shape[1] <= _LOCAL_SOLVE_MAX_ELEMENTS
        ):
            return LocalLeastSquaresEstimator(
                lam=self.lam, fit_intercept=self.fit_intercept
            )
        return self

    def fit_dataset(self, data: Dataset, labels: Optional[Dataset] = None):
        if labels is None:
            raise ValueError("LinearMapEstimator requires labels")
        # robustness, not just optimization: host CSR datasets must fit
        # even when NodeChoiceRule didn't run (custom optimizers,
        # best-effort sampling failures) — route like choose_physical
        from keystone_tpu.ops.sparse import is_scipy_sparse_rows

        if data.is_host and is_scipy_sparse_rows(data.items):
            return self.choose_physical(data).fit_dataset(data, labels)
        from keystone_tpu.workflow.dataset import StreamDataset

        if isinstance(data, StreamDataset):
            if data.is_host:
                raise TypeError(
                    "host-payload stream reached the exact solver with "
                    "non-CSR items; featurize to arrays (or CSR) first"
                )
            # out-of-core: labels are (n, k) and stay in memory; features
            # stream past the sufficient-statistic accumulators
            import numpy as np

            y = np.asarray(labels.numpy())

            def pairs():
                offset = 0
                for b in data.batches():
                    yield b, y[offset : offset + len(b)]
                    offset += len(b)

            return self.fit_stream(pairs)
        w, b = _fit_normal_equations(
            data.array,
            labels.array,
            jnp.float32(data.n),
            self.lam,
            self.fit_intercept,
        )
        return LinearMapper(w, b if self.fit_intercept else None)

    def fit_arrays(self, x, y=None) -> LinearMapper:
        x = jnp.asarray(x)
        w, b = _fit_normal_equations(
            x, jnp.asarray(y), jnp.float32(x.shape[0]), self.lam, self.fit_intercept
        )
        return LinearMapper(w, b if self.fit_intercept else None)

    def fit_stream(self, batches) -> LinearMapper:
        """Out-of-core exact least squares from a stream of host batches.

        ``batches``: a callable returning an iterator of ``(x, y)`` host
        arrays (re-invoked per pass), or a re-iterable (e.g. a list).
        The normal equations only need accumulated sufficient statistics,
        so HBM holds one batch plus the (d, d)/(d, k) accumulators — the
        dataset can be arbitrarily larger than device memory (the
        reference's analogue: features as spilled RDDs, SURVEY §2.9).

        Two passes when ``fit_intercept``: means first, then Gramians of
        EXPLICITLY centered batches — the one-pass shortcut
        ``XᵀX − n·x̄x̄ᵀ`` cancels catastrophically in f32 (see
        _fit_normal_equations).  Accumulators are Kahan-compensated, so
        rounding error stays O(ε) instead of growing with batch count.
        """
        get = batches if callable(batches) else lambda: iter(batches)
        if not self.fit_intercept:
            gram = None
            n = 0
            for bx, by in get():
                bx, by, bn, row_ok = stage_stream_batch(bx, by)
                n += bn
                gram = _acc_gram(gram, bx, by, None, None, row_ok)
            if n == 0:
                raise ValueError("empty batch stream")
            w = solve_spd(gram[0], gram[2], reg=self.lam * n)
            return LinearMapper(w, None)
        sums = None
        n = 0
        for bx, by in get():
            bx, by, bn, row_ok = stage_stream_batch(bx, by)
            n += bn
            sums = _acc_sums(sums, bx, by)
        if n == 0:
            raise ValueError("empty batch stream")
        xm, ym = sums[0] / n, sums[2] / n
        gram = None
        n2 = 0
        for bx, by in get():
            bx, by, bn, row_ok = stage_stream_batch(bx, by)
            n2 += bn
            gram = _acc_gram(gram, bx, by, xm, ym, row_ok)
        if n2 != n:
            raise ValueError(
                f"batch stream is not re-iterable: first pass saw {n} rows, "
                f"second pass {n2}. Pass a CALLABLE returning a fresh "
                "iterator (or a re-iterable like a list), not a one-shot "
                "generator."
            )
        w = solve_spd(gram[0], gram[2], reg=self.lam * n)
        return LinearMapper(w, ym - xm @ w)


@jax.jit
def _acc_sums(carry, x, y):
    """carry = (s1x, c1x, s1y, c1y) Kahan-compensated column sums."""
    bx, by = jnp.sum(x, axis=0), jnp.sum(y, axis=0)
    if carry is None:
        return bx, jnp.zeros_like(bx), by, jnp.zeros_like(by)
    s1x, c1x, s1y, c1y = carry
    s1x, c1x = kahan_add(s1x, c1x, bx)
    s1y, c1y = kahan_add(s1y, c1y, by)
    return s1x, c1x, s1y, c1y


@jax.jit
def _acc_gram(carry, x, y, xm, ym, row_ok):
    """carry = (sxx, cxx, sxy, cxy) Kahan-compensated Gramian sums."""
    if xm is not None:
        # center with the GLOBAL means; mask keeps shard-padding rows at 0
        x = (x - xm) * row_ok
        y = (y - ym) * row_ok
    gxx, gxy = xtx_xty(x, y)
    if carry is None:
        return gxx, jnp.zeros_like(gxx), gxy, jnp.zeros_like(gxy)
    sxx, cxx, sxy, cxy = carry
    sxx, cxx = kahan_add(sxx, cxx, gxx)
    sxy, cxy = kahan_add(sxy, cxy, gxy)
    return sxx, cxx, sxy, cxy


#: Alias matching common usage in reference pipelines.
LeastSquaresEstimator = LinearMapEstimator


#: n·d crossover below which the unsharded local solve beats the sharded
#: normal-equations path.  Measured on an 8-device mesh (rounds 1–5, not re-measured
#: "Local vs distributed solve"): local wins through n·d = 2²⁰
#: (4096×256: 49 ms vs 52 ms, and 2.7× at 256×64), the sharded path wins
#: from n·d = 2²³ up (2.2× at 16384×512); the boundary sits between.
_LOCAL_SOLVE_MAX_ELEMENTS = 1 << 21


class LocalLeastSquaresEstimator(LabelEstimator):
    """Single-device exact solve via QR/SVD lstsq — the physical
    alternative the optimizer picks for small data
    (nodes/learning/LocalLeastSquaresEstimator.scala).  No collectives:
    everything is gathered to one device, like the reference's
    ``collect()`` + LAPACK path."""

    fit_intercept = True  # class default for pre-option pickles

    def __init__(self, lam: float = 0.0, fit_intercept: bool = True):
        self.lam = float(lam)
        self.fit_intercept = bool(fit_intercept)

    def params(self):
        return (self.lam, self.fit_intercept)

    def fit_dataset(self, data: Dataset, labels: Optional[Dataset] = None) -> LinearMapper:
        if labels is None:
            raise ValueError("LocalLeastSquaresEstimator requires labels")
        x = jnp.asarray(data.numpy())
        y = jnp.asarray(labels.numpy())
        return self.fit_arrays(x, y)

    def fit_arrays(self, x, y=None) -> LinearMapper:
        x = jnp.asarray(x)
        y = jnp.asarray(y)
        if self.fit_intercept:
            xm = jnp.mean(x, axis=0)
            ym = jnp.mean(y, axis=0)
            xc, yc = x - xm, y - ym
        else:
            xc, yc = x, y
        if self.lam > 0.0:
            w = solve_spd(sdot(xc.T, xc), sdot(xc.T, yc), reg=self.lam * x.shape[0])
        else:
            w = jnp.linalg.lstsq(xc, yc)[0]
        if not self.fit_intercept:
            return LinearMapper(w, None)
        return LinearMapper(w, ym - xm @ w)


@partial(jax.jit, static_argnames=("fit_intercept",))
def _fit_normal_equations(x, y, n, lam, fit_intercept):
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    if fit_intercept:
        # Means over the true row count: padding rows are zero, so plain
        # sums divided by n are exact.
        xm = jnp.sum(x, axis=0) / n
        ym = jnp.sum(y, axis=0) / n
        # Center EXPLICITLY before the Gramian (pad rows masked back to 0).
        # The algebraic shortcut XᵀX − n·x̄x̄ᵀ cancels catastrophically in
        # f32 when feature magnitudes are large (e.g. 0–255 pixels).
        row_ok = (jnp.arange(x.shape[0]) < n).astype(jnp.float32)[:, None]
        xc = (x - xm) * row_ok
        yc = (y - ym) * row_ok
        xtx_c, xty_c = xtx_xty(xc, yc)
        w = solve_spd(xtx_c, xty_c, reg=lam * n)
        b = ym - xm @ w
        return w, b
    xtx, xty = xtx_xty(x, y)
    w = solve_spd(xtx, xty, reg=lam * n)
    return w, jnp.zeros((y.shape[1],), jnp.float32)
