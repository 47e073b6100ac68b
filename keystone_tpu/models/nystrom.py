"""Nyström kernel feature approximation — the kernel tier's scale-out.

Reference [fork]: the kernel block-coordinate line (arXiv:1602.05310)
pairs the exact blockwise KRR solver with approximation tiers for the
regime where even out-of-core exact sweeps are too expensive: Nyström
(Williams & Seeger) and random features (the repo already ships
``ops.CosineRandomFeatures`` for the latter).  Nyström samples *m*
landmark rows L from the training set and maps

    φ(x) = K(x, L) · (K_LL + εI)^{−1/2}        (m-dim features)

so that φ(x)·φ(z)ᵀ ≈ K(x, z) — the million-row kernel problem becomes a
d=m LINEAR problem that the existing ``BlockLeastSquaresEstimator``
(in-core, out-of-core, checkpointed — the whole PR-7 machinery) solves
as-is.  This is what opens the kernel-TIMIT / kernel-CIFAR scenario
family in ``pipelines/`` without an n×n anything.

Numerics: landmark sampling is seeded and content-independent (uniform
without replacement); the K_LL gram and the whitening solve are
SOLVER-GRADE f32 under every ``KEYSTONE_MATMUL`` mode (registered in
``analysis/precision.SOLVER_ENTRIES``); the *apply* gemms — K(x, L)
and the whitening projection — are scoring, riding the apply precision
policy like every other forward op.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.models.kernel_ridge import GaussianKernelGenerator
from keystone_tpu.workflow.dataset import Dataset
from keystone_tpu.workflow.estimator import Estimator
from keystone_tpu.workflow.transformer import Transformer


@jax.jit
def _nystrom_whiten(lmk, gamma, reg):
    """(K_LL + reg·m·I)^{−1/2} via a symmetric eigendecomposition —
    solver math: the gram gemm is solver-grade (sdot) and the
    eigenbasis projection accumulates f32.  ``reg`` scales with m the
    way the KRR solve's λn does, so the floor is shape-independent."""
    kern = GaussianKernelGenerator(gamma)  # solver_grade=True
    m = lmk.shape[0]
    kmm = kern(lmk, lmk)
    kmm = 0.5 * (kmm + kmm.T) + reg * m * jnp.eye(m, dtype=jnp.float32)
    evals, evecs = jnp.linalg.eigh(kmm)
    # clamp: K_LL is PSD up to rounding; a tiny negative eigenvalue must
    # not turn the whitening into NaNs
    inv_sqrt = evecs * jax.lax.rsqrt(jnp.maximum(evals, 1e-12))[None, :]
    return jnp.dot(
        inv_sqrt, evecs.T, preferred_element_type=jnp.float32
    )


class NystromFeatureMap(Transformer):
    """φ(x) = K(x, L)·W for fitted landmarks L and whitening W.

    Scoring, not solving: the K(x, L) gram rides the apply precision
    policy (the Pallas gram megakernel streams it bf16 on capable
    backends under ``bf16``/``bf16_apply``) and the whitening
    projection goes through ``precision.apply_dot``."""

    traced_attrs = ("landmarks", "whiten")

    def __init__(self, kernel_gen, landmarks, whiten):
        self.kernel_gen = kernel_gen
        self.landmarks = landmarks  # (m, d) f32
        self.whiten = whiten  # (m, m) f32

    def jit_static(self):
        return (float(self.kernel_gen.gamma),)

    def apply_batch(self, xs, mask=None):
        from keystone_tpu.ops import gram_pallas
        from keystone_tpu.utils import precision

        xs = xs.astype(jnp.float32)
        mode = precision.apply_mode()
        knm = gram_pallas.gram_block(
            xs,
            self.landmarks,
            float(self.kernel_gen.gamma),
            solver_grade=False,
            mxu=mode,
        )
        return precision.apply_dot(knm, self.whiten, mode=mode)

    def apply_one(self, x):
        return self.apply_batch(x[None])[0]


class NystromFeatures(Estimator):
    """Landmark sampling + whitening solve; the fitted transformer is a
    :class:`NystromFeatureMap` whose output feeds any linear solver
    (canonically ``BlockLeastSquaresEstimator``).

    ``num_landmarks`` rows are drawn uniformly without replacement with
    a seeded rng.  Fitting from a (non-host) ``StreamDataset`` never
    materializes the stream: the sampled global row indices are chosen
    up front (``data.n`` is known) and collected in ONE pass over the
    batches — the out-of-core landmark path the million-row recipes
    use.  K_nm itself is never formed at fit time; it streams at apply
    time batch by batch through the pipeline machinery."""

    def __init__(
        self,
        kernel_gen: GaussianKernelGenerator,
        num_landmarks: int = 1024,
        reg: float = 1e-6,
        seed: int = 0,
    ):
        self.kernel_gen = kernel_gen
        self.num_landmarks = int(num_landmarks)
        self.reg = float(reg)
        self.seed = int(seed)

    def params(self):
        return (
            self.kernel_gen.gamma,
            self.num_landmarks,
            self.reg,
            self.seed,
        )

    def fit_dataset(self, data: Dataset):
        from keystone_tpu.workflow.dataset import StreamDataset

        if isinstance(data, StreamDataset):
            if data.is_host:
                raise TypeError(
                    "host-payload stream reached NystromFeatures; "
                    "featurize to arrays before the fit"
                )
            return self._fit_landmarks(self._sample_stream(data))
        return self.fit_arrays(data.array[: data.n])

    def fit_arrays(self, x):
        x = np.asarray(x, np.float32)
        n = x.shape[0]
        m = min(self.num_landmarks, n)
        idx = np.sort(
            np.random.default_rng(self.seed).choice(n, size=m, replace=False)
        )
        return self._fit_landmarks(x[idx])

    def _sample_stream(self, data) -> np.ndarray:
        """One streaming pass collecting the pre-chosen landmark rows:
        indices are sampled against the KNOWN row count, so the draw is
        identical to the in-core path on the same seed."""
        n = data.n
        m = min(self.num_landmarks, n)
        idx = np.sort(
            np.random.default_rng(self.seed).choice(n, size=m, replace=False)
        )
        rows = []
        offset = 0
        take = 0  # cursor into the sorted index list
        for batch in data.batches():
            batch = np.asarray(batch, np.float32)
            hi = offset + batch.shape[0]
            while take < m and idx[take] < hi:
                rows.append(batch[idx[take] - offset])
                take += 1
            offset = hi
            if take >= m:
                break
        if take < m:
            raise ValueError(
                f"stream delivered {offset} rows; cannot sample "
                f"{m} landmarks from a declared n={n}"
            )
        return np.stack(rows)

    def _fit_landmarks(self, lmk: np.ndarray) -> NystromFeatureMap:
        lmk = jnp.asarray(lmk, jnp.float32)
        whiten = _nystrom_whiten(
            lmk,
            jnp.float32(self.kernel_gen.gamma),
            jnp.float32(self.reg),
        )
        return NystromFeatureMap(self.kernel_gen, lmk, whiten)
