"""Multinomial naive Bayes.

Reference: nodes/learning/NaiveBayes.scala § NaiveBayesEstimator — a port
of MLlib's multinomial NB used as the Newsgroups pipeline's alternative
head.  Log priors + smoothed log conditionals; the model transformer
outputs per-class log-posterior scores (argmax-compatible with
MaxClassifier).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from keystone_tpu.models.common import constrain
from keystone_tpu.parallel.mesh import DATA_AXIS
from keystone_tpu.workflow.dataset import Dataset
from keystone_tpu.workflow.estimator import LabelEstimator
from keystone_tpu.workflow.transformer import Transformer


class NaiveBayesModel(Transformer):
    traced_attrs = ("log_prior", "log_cond")

    def __init__(self, log_prior: jnp.ndarray, log_cond: jnp.ndarray):
        self.log_prior = log_prior  # (K,)
        self.log_cond = log_cond  # (K, d)

    def apply_batch(self, xs, mask=None):
        return xs @ self.log_cond.T + self.log_prior

    def apply_one(self, x):
        return x @ self.log_cond.T + self.log_prior

    def apply_dataset(self, ds):
        from keystone_tpu.ops.sparse import is_scipy_sparse_rows, score_sparse_dataset

        if ds.is_host and is_scipy_sparse_rows(ds.items):
            return score_sparse_dataset(ds, self.log_cond.T, self.log_prior)
        return super().apply_dataset(ds)


class NaiveBayesEstimator(LabelEstimator):
    """labels: int class ids (n,) or one-hot/±1 indicator matrix (n, K)."""

    def __init__(self, num_classes: int, lam: float = 1.0):
        self.num_classes = int(num_classes)
        self.lam = float(lam)  # additive smoothing

    def params(self):
        return (self.num_classes, self.lam)

    def fit_dataset(self, data: Dataset, labels: Optional[Dataset] = None):
        if labels is None:
            raise ValueError("NaiveBayesEstimator requires labels")
        # sparse counts: the sufficient statistic onehotᵀX is a
        # scatter-add over the COO entries — never densify n×d.  Rows
        # are nnz-BUCKETED so one dense document doesn't inflate the
        # whole corpus's padding (the count sum is row-permutation
        # invariant, so summing per-bucket contributions is exact).
        from keystone_tpu.ops.sparse import (
            BucketedSparseRows,
            bucketize_with_labels,
            is_scipy_sparse_rows,
        )

        if data.is_host and is_scipy_sparse_rows(data.items):
            from keystone_tpu.ops.sparse import host_onehot

            sp = BucketedSparseRows.from_scipy_rows(data.items)
            # host one-hot: labels get permuted in numpy next, so a
            # device one-hot would cross the host↔device link twice for nothing
            onehot = host_onehot(labels.numpy(), self.num_classes)
            bidx, bvals, boh, n, d, _row_ok = bucketize_with_labels(
                sp, onehot, n=data.n
            )
            lp, lc = _nb_fit_sparse(
                bidx, bvals, boh, jnp.float32(n), d, self.lam
            )
            return NaiveBayesModel(lp, lc)
        return self._fit(data.array, labels.array, data.n)

    def fit_arrays(self, x, y=None):
        x = jnp.asarray(x, jnp.float32)
        return self._fit(x, jnp.asarray(y), x.shape[0])

    def _fit(self, x, y, n):
        lp, lc = _nb_fit(x, _to_onehot(y, self.num_classes), jnp.float32(n), self.lam)
        return NaiveBayesModel(lp, lc)


def _to_onehot(y, k):
    y = jnp.asarray(y)
    if y.ndim == 1:
        return jax.nn.one_hot(y.astype(jnp.int32), k, dtype=jnp.float32)
    return (y > 0).astype(jnp.float32)


@partial(jax.jit, static_argnames=("d",))
def _nb_fit_sparse(bidx, bvals, bonehot, n, d, lam):
    """Sparse multinomial NB: feat_counts = (Xᵀ·onehot)ᵀ via scatter-add
    on bucketed COO entries (sparse_grad per bucket, summed — bucket
    values/labels are pre-zeroed on padding rows); identical math to
    _nb_fit."""
    from keystone_tpu.ops.sparse import sparse_grad

    class_counts = jnp.zeros((bonehot[0].shape[1],), jnp.float32)
    feat_counts = jnp.zeros((bonehot[0].shape[1], d), jnp.float32)
    for idx, vals, onehot in zip(bidx, bvals, bonehot):
        idx = constrain(idx, DATA_AXIS)
        vals = constrain(vals, DATA_AXIS)
        onehot = constrain(onehot, DATA_AXIS)
        class_counts = class_counts + jnp.sum(onehot, axis=0)
        feat_counts = feat_counts + sparse_grad(idx, vals, onehot, d).T
    class_counts = constrain(class_counts)
    feat_counts = constrain(feat_counts)
    return _nb_finish(class_counts, feat_counts, n, lam)


@jax.jit
def _nb_fit(x, onehot, n, lam):
    x = constrain(x.astype(jnp.float32), DATA_AXIS)
    row_ok = (jnp.arange(x.shape[0]) < n).astype(jnp.float32)
    onehot = onehot * row_ok[:, None]
    class_counts = constrain(jnp.sum(onehot, axis=0))  # (K,)
    feat_counts = constrain(onehot.T @ x)  # (K, d) — treeAggregate analogue
    return _nb_finish(class_counts, feat_counts, n, lam)


def _nb_finish(class_counts, feat_counts, n, lam):
    """Shared prior/smoothing/log-conditional tail of both fit paths."""
    log_prior = jnp.log(jnp.maximum(class_counts, 1e-10)) - jnp.log(n)
    smoothed = feat_counts + lam
    log_cond = jnp.log(smoothed) - jnp.log(
        jnp.sum(smoothed, axis=1, keepdims=True)
    )
    return log_prior, log_cond
