"""ZCA whitening.

Reference: nodes/images/ZCAWhitener.scala § ZCAWhitenerEstimator — SVD of
the centered patch matrix; whitening map W = V·(S²/n + εI)^(−1/2)·Vᵀ so
whitened patches stay in the original coordinate system (used on CIFAR
random patches before convolution, pipelines/images/cifar/RandomPatchCifar.scala).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from keystone_tpu.workflow.dataset import Dataset
from keystone_tpu.workflow.estimator import Estimator
from keystone_tpu.workflow.transformer import Transformer
from keystone_tpu.utils.precision import sdot


class ZCAWhitener(Transformer):
    traced_attrs = ("whitener", "mean")

    def __init__(self, whitener: jnp.ndarray, mean: jnp.ndarray):
        self.whitener = whitener  # (d, d)
        self.mean = mean  # (d,)

    def apply_batch(self, xs, mask=None):
        return (xs - self.mean) @ self.whitener

    def apply_one(self, x):
        return (x - self.mean) @ self.whitener


class ZCAWhitenerEstimator(Estimator):
    def __init__(self, eps: float = 1e-1):
        self.eps = float(eps)

    def params(self):
        return (self.eps,)

    def fit_dataset(self, data: Dataset) -> ZCAWhitener:
        w, m = _zca_fit(data.array, jnp.float32(data.n), self.eps)
        return ZCAWhitener(w, m)

    def fit_arrays(self, x) -> ZCAWhitener:
        x = jnp.asarray(x, jnp.float32)
        w, m = _zca_fit(x, jnp.float32(x.shape[0]), self.eps)
        return ZCAWhitener(w, m)


@jax.jit
def _zca_fit(x, n, eps):
    mean = jnp.sum(x, axis=0) / n
    row_ok = (jnp.arange(x.shape[0]) < n).astype(jnp.float32)[:, None]
    xc = (x - mean) * row_ok
    cov = sdot(xc.T, xc) / n
    evals, evecs = jnp.linalg.eigh(cov)
    inv_sqrt = 1.0 / jnp.sqrt(jnp.maximum(evals, 0.0) + eps)
    # the whitener enters every feature made with it: solver-grade, as the
    # covariance is (at the MXU's default its entries sat 2^-9 off)
    whitener = sdot(evecs * inv_sqrt, evecs.T)
    return whitener, mean
