"""PCA.

Reference: nodes/learning/PCA.scala § PCAEstimator (local: gather sample →
LAPACK gesvd), DistributedPCAEstimator (covariance via treeReduce + local
eig), PCATransformer.  Used to project SIFT descriptors 128→64 in the
ImageNet pipeline.

TPU form: the "local" variant SVDs on device; the "distributed" variant
forms the covariance as a sharded Gramian (all-reduce over ICI) and eigh's
it replicated — both are single jitted programs.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from keystone_tpu.models.common import constrain
from keystone_tpu.parallel.mesh import DATA_AXIS
from keystone_tpu.workflow.dataset import Dataset
from keystone_tpu.workflow.estimator import Estimator
from keystone_tpu.workflow.transformer import Transformer
from keystone_tpu.utils.precision import fcast, sdot


class PCATransformer(Transformer):
    """Projects onto the top-k principal directions: x ↦ (x − μ)·C."""

    # fitted arrays ride as traced jit arguments: both branch PCAs share
    # one compiled program per shape, and lowering never reads the
    # components back to the host (Transformer.traced_attrs)
    traced_attrs = ("components", "mean")

    def __init__(self, components: jnp.ndarray, mean: Optional[jnp.ndarray] = None):
        self.components = components  # (d, k)
        self.mean = mean

    def apply_batch(self, xs, mask=None):
        if self.mean is not None:
            xs = xs - self.mean
        xs_c, comp_c = fcast(xs, self.components)
        out = jnp.matmul(xs_c, comp_c, preferred_element_type=jnp.float32)
        return (out, mask) if mask is not None else out

    def apply_one(self, x):
        if self.mean is not None:
            x = x - self.mean
        return x @ self.components


class PCAEstimator(Estimator):
    """SVD-based PCA on gathered data (PCA.scala § PCAEstimator)."""

    def __init__(self, dims: int, center: bool = True):
        self.dims = int(dims)
        self.center = center

    def params(self):
        return (self.dims, self.center)

    def fit_dataset(self, data: Dataset) -> PCATransformer:
        x = data.array
        if data.mask is not None:
            # ragged descriptor sets: (n, max_k, d) -> valid rows only
            # (flatten + mask threshold live inside the jit: eager they
            # were 2 extra compiled programs per fit)
            comp, mean = _pca_masked(x, data.mask, self.dims, self.center)
            return PCATransformer(comp, mean if self.center else None)
        comp, mean = _pca_fit(x, float(data.n), self.dims, self.center)
        return PCATransformer(comp, mean if self.center else None)

    def fit_arrays(self, x) -> PCATransformer:
        x = jnp.asarray(x, jnp.float32)
        comp, mean = _pca_fit(x, float(x.shape[0]), self.dims, self.center)
        return PCATransformer(comp, mean if self.center else None)


class DistributedPCAEstimator(PCAEstimator):
    """Covariance via sharded Gramian + replicated eigh
    (PCA.scala § DistributedPCAEstimator).  Preferable when n ≫ d."""

    def fit_arrays(self, x) -> PCATransformer:
        x = jnp.asarray(x, jnp.float32)
        comp, mean = _pca_cov_fit(x, float(x.shape[0]), self.dims, self.center)
        return PCATransformer(comp, mean if self.center else None)

    def fit_dataset(self, data: Dataset) -> PCATransformer:
        x = data.array
        if data.mask is not None:
            return super().fit_dataset(data)
        comp, mean = _pca_cov_fit(x, float(data.n), self.dims, self.center)
        return PCATransformer(comp, mean if self.center else None)


@partial(jax.jit, static_argnames=("dims", "center"))
def _pca_fit(x, n, dims, center):
    mean = jnp.sum(x, axis=0) / n
    row_ok = (jnp.arange(x.shape[0]) < n).astype(jnp.float32)[:, None]
    xc = (x - mean) * row_ok if center else x
    _, _, vt = jnp.linalg.svd(xc, full_matrices=False)
    return vt[:dims].T, mean


@partial(jax.jit, static_argnames=("dims", "center"))
def _pca_cov_fit(x, n, dims, center):
    x = constrain(x, DATA_AXIS)
    mean = jnp.sum(x, axis=0) / n
    # center explicitly (pad rows re-masked to zero): the gram/n − x̄x̄ᵀ
    # shortcut cancels catastrophically in f32 at large feature magnitudes
    if center:
        row_ok = (jnp.arange(x.shape[0]) < n).astype(jnp.float32)[:, None]
        x = (x - mean) * row_ok
    cov = constrain(sdot(x.T, x)) / n  # treeReduce analogue
    evals, evecs = jnp.linalg.eigh(cov)
    comp = evecs[:, ::-1][:, :dims]  # descending eigenvalue order
    return comp, mean


@partial(jax.jit, static_argnames=("dims", "center"))
def _pca_masked(x, mask, dims, center):
    if x.ndim == 3:  # ragged (n, max_k, d) + (n, max_k) mask
        x = x.reshape(-1, x.shape[-1])
        mask = mask.reshape(-1)
    valid = mask > 0
    w = valid.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(w), 1.0)
    mean = (w @ x) / n
    xc = (x - mean) * w[:, None] if center else x * w[:, None]
    cov = sdot(xc.T, xc) / n
    evals, evecs = jnp.linalg.eigh(cov)
    return evecs[:, ::-1][:, :dims], mean
