"""Fisher-vector encoding.

Reference: nodes/images/external/FisherVector.scala +
GMMFisherVectorEstimator → JNI utils/external/EncEval.scala (C++ GMM EM +
FV encode; SURVEY.md §2.8 "must get first-class TPU-era equivalents").

FV of a descriptor set {x_t} against a diagonal GMM (w, μ, σ²)
(Perronnin–Sánchez improved Fisher vector):

    γ_tk   = posterior responsibility of component k for x_t
    Φ¹_k   = 1/(T·√w_k)    · Σ_t γ_tk (x_t − μ_k)/σ_k
    Φ²_k   = 1/(T·√(2w_k)) · Σ_t γ_tk ((x_t − μ_k)²/σ²_k − 1)

concatenated to a 2·K·D vector per image.  Power/L2 normalization are the
separate SignedHellingerMapper / NormalizeRows nodes, as in the reference
pipeline.  The encode is a batched einsum over (n, max_k, d) ragged
descriptor sets with masks — MXU-shaped, replacing the per-image C++ loop.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from keystone_tpu.models.gmm import GaussianMixtureModel, GaussianMixtureModelEstimator
from keystone_tpu.workflow.dataset import Dataset
from keystone_tpu.workflow.estimator import Estimator
from keystone_tpu.workflow.transformer import Transformer
from keystone_tpu.utils import precision


class FisherVector(Transformer):
    """Input: ragged ((n, max_k, d), mask) descriptor sets.
    Output: dense (n, 2·K·D) Fisher vectors.

    ``use_pallas`` — True routes through the fused VMEM-resident TPU
    kernel (ops/fisher_pallas.py); False forces the XLA einsum path; None
    (default) picks per call: the fused kernel on TPU when the
    responsibility tensor γ (T·K floats per image) is large enough to be
    HBM-bandwidth bound (re-measured r2 with the whole-image-tile
    kernel on v5 lite: 1.7× at T=784/K=64, 3× at T=784/K=256; parity
    at T ≤ 256 for any K), einsum otherwise.
    """

    fusable = False

    # per-image γ elements above which the fused kernel measurably wins
    _PALLAS_GAMMA_THRESHOLD = 32768

    # the fitted GMM (a registered pytree) rides as a traced argument:
    # both branch FV nodes share one compiled encode per shape, and the
    # vocabulary is never read back at lowering time
    traced_attrs = ("gmm",)

    def __init__(
        self, gmm: GaussianMixtureModel, use_pallas: Optional[bool] = None
    ):
        self.gmm = gmm
        self.use_pallas = use_pallas

    def jit_static(self):
        return (self.use_pallas,)

    def params(self):
        from keystone_tpu.utils.hashing import cached_fingerprint

        fp = cached_fingerprint(
            self, "_fp", self.gmm.weights, self.gmm.means, self.gmm.variances
        )
        return (fp, self.use_pallas)

    # the scope sits AROUND the kernel's own jit, not inside it: the compiler
    # names the kernel's operation for the innermost name before
    # `pallas_call`, and that name is how a device trace finds the kernel
    @jax.named_scope("fv")
    def apply_batch(self, xs, mask=None):
        if xs.ndim == 2:
            xs = xs[None]
            squeeze = True
        else:
            squeeze = False
        if mask is None:
            mask = jnp.ones(xs.shape[:2], jnp.float32)
        use_pallas = self.use_pallas
        if use_pallas is None:
            from keystone_tpu.ops.fisher_pallas import pallas_supported

            gamma_elems = xs.shape[1] * self.gmm.means.shape[0]
            use_pallas = (
                gamma_elems >= self._PALLAS_GAMMA_THRESHOLD
                and pallas_supported(xs)
            )
        if use_pallas:
            from keystone_tpu.ops.fisher_pallas import fisher_encode_pallas

            out = fisher_encode_pallas(
                xs,
                mask,
                self.gmm.weights,
                self.gmm.means,
                self.gmm.variances,
                mxu=precision.matmul_mode(),
            )
        else:
            out = _fisher_encode(
                xs,
                mask,
                self.gmm.weights,
                self.gmm.means,
                self.gmm.variances,
                mxu=precision.apply_mode(),
            )
        return out[0] if squeeze else out

    def apply_one(self, x):
        return self.apply_batch(x[None].reshape(1, *jnp.asarray(x).shape))[0]


class FusedPcaFisherVector(Transformer):
    """PCA projection + Fisher-vector encode as ONE kernel dispatch —
    the fused forward megakernel (ops/fisher_pallas.fused_forward_pallas).

    With ``sift_normalize=True`` it also absorbs SIFT's final
    L2→clamp→re-L2 tail, so a RAW-descriptor SIFT feed runs
    sift-normalize → PCA → FV in one program.  Built by the optimizer's
    ``PallasFvFusionRule`` from an adjacent single-consumer
    ``PCATransformer → FisherVector`` pair on Pallas-capable devices;
    off-TPU (or ``use_pallas=False``) it applies the IDENTICAL math as
    the per-stage XLA chain, so the transformer stays portable and
    parity-testable on CPU meshes.

    Not ``fusable``: like FisherVector it reduces a ragged (desc, mask)
    pair to a dense row — the generic chain fuser has no mask story.
    """

    fusable = False

    # fitted arrays ride as traced jit arguments (shared compiled
    # programs across refits; nothing read back at lowering time)
    traced_attrs = ("components", "mean", "gmm")

    def __init__(
        self,
        pca,
        gmm: GaussianMixtureModel,
        sift_normalize: bool = False,
        use_pallas: Optional[bool] = None,
    ):
        self.components = pca.components  # (d_in, d)
        self.mean = pca.mean  # (d_in,) or None
        self.gmm = gmm
        self.sift_normalize = bool(sift_normalize)
        self.use_pallas = use_pallas

    @property
    def label(self):
        tail = "SiftNorm > PCA > FV" if self.sift_normalize else "PCA > FV"
        return f"FusedFV[{tail}]"

    def jit_static(self):
        return (self.use_pallas, self.sift_normalize, self.mean is None)

    def params(self):
        from keystone_tpu.utils.hashing import cached_fingerprint

        arrays = [self.components]
        if self.mean is not None:
            arrays.append(self.mean)
        arrays += [self.gmm.weights, self.gmm.means, self.gmm.variances]
        fp = cached_fingerprint(self, "_fp", *arrays)
        return (fp, self.sift_normalize, self.use_pallas, self.mean is None)

    # the scope sits AROUND the kernel's own jit, not inside it: the compiler
    # names the kernel's operation for the innermost name before
    # `pallas_call`, and that name is how a device trace finds the kernel
    @jax.named_scope("fv")
    def apply_batch(self, xs, mask=None):
        if xs.ndim == 2:
            xs = xs[None]
            squeeze = True
        else:
            squeeze = False
        if mask is None:
            mask = jnp.ones(xs.shape[:2], jnp.float32)
        use_pallas = self.use_pallas
        if use_pallas is None:
            from keystone_tpu.ops.fisher_pallas import pallas_supported

            gamma_elems = xs.shape[1] * self.gmm.means.shape[0]
            use_pallas = (
                gamma_elems >= FisherVector._PALLAS_GAMMA_THRESHOLD
                and pallas_supported(xs)
            )
        if use_pallas:
            from keystone_tpu.ops.fisher_pallas import fused_forward_pallas

            out = fused_forward_pallas(
                xs,
                mask,
                self.components,
                self.mean,
                self.gmm.weights,
                self.gmm.means,
                self.gmm.variances,
                mxu=precision.matmul_mode(),
                normalize=self.sift_normalize,
            )
        else:
            # per-stage XLA fallback: bit-for-bit the unfused chain
            # (sift normalize → PCATransformer's matmul → _fisher_encode)
            z = xs
            if self.sift_normalize:
                from keystone_tpu.ops.sift import _sift_normalize

                z = _sift_normalize(z)
            if self.mean is not None:
                z = z - self.mean
            z_c, comp_c = precision.fcast(z, self.components)
            z = jnp.matmul(z_c, comp_c, preferred_element_type=jnp.float32)
            out = _fisher_encode(
                z,
                mask,
                self.gmm.weights,
                self.gmm.means,
                self.gmm.variances,
                mxu=precision.apply_mode(),
            )
        return out[0] if squeeze else out

    def apply_one(self, x):
        return self.apply_batch(jnp.asarray(x)[None])[0]


class GMMFisherVectorEstimator(Estimator):
    """Fits the GMM vocabulary on (sampled) descriptors and returns the
    FisherVector transformer (nodes/images/external/GMMFisherVectorEstimator)."""

    def __init__(self, k: int, max_iterations: int = 25, seed: int = 0):
        self.k = int(k)
        self.max_iterations = int(max_iterations)
        self.seed = int(seed)

    def params(self):
        return (self.k, self.max_iterations, self.seed)

    def fit_dataset(self, data: Dataset) -> FisherVector:
        gmm = GaussianMixtureModelEstimator(
            self.k, max_iterations=self.max_iterations, seed=self.seed
        ).fit_dataset(data)
        return FisherVector(gmm)

    def fit_arrays(self, x) -> FisherVector:
        gmm = GaussianMixtureModelEstimator(
            self.k, max_iterations=self.max_iterations, seed=self.seed
        ).fit_arrays(x)
        return FisherVector(gmm)


@partial(jax.jit, static_argnames=("mxu",))
def _fisher_encode(xs, mask, w, mu, var, mxu: str = "f32"):
    """xs: (n, T, d); mask: (n, T); w: (K,); mu, var: (K, d).

    NOT under the FEATURIZE bf16 policy: the sufficient-statistic einsums
    contract only over T and are OUTPUT-bound ((n, K, d) stays f32 either
    way), so bf16 input casts measured 0.64× in isolation at K=256,
    T=512 on v5 lite; the Pallas path gets its bf16 win at the HBM
    boundary instead (ops/fisher_pallas.py).  The opt-in APPLY policy
    (``mxu='bf16_apply'``, utils/precision.py) converts the posterior
    gemms and the s1/s2 einsums anyway — inside a fused forward program
    the casts also halve the γ/descriptor streams between contractions,
    and accumulation stays f32.  Inert modes trace the exact pre-policy
    graph (CPU meshes bit-identical).
    """
    sigma = jnp.sqrt(var)  # (K, d)
    # responsibilities, batched over images
    from keystone_tpu.models.gmm import _log_gaussians

    n, t, d = xs.shape
    flat = xs.reshape(n * t, d)
    if mxu == "bf16_apply":
        # the two (n·t, d)×(d, K) posterior gemms under the apply
        # policy; one copy of the math lives in gmm._log_gaussians, and
        # EM fitting (solver math) keeps the inert default dot.
        lg = _log_gaussians(
            flat, mu, var, jnp.log(w),
            dot=partial(precision.apply_dot, mode=mxu),
        )  # (n*t, K)
    else:
        lg = _log_gaussians(flat, mu, var, jnp.log(w))  # (n*t, K)
    lr = lg - jax.scipy.special.logsumexp(lg, axis=1, keepdims=True)
    gamma = (jnp.exp(lr).reshape(n, t, -1)) * mask[..., None]  # (n, T, K)

    counts = jnp.maximum(jnp.sum(mask, axis=1), 1.0)  # (n,) = T per image

    # standardized descriptors per component: (x − μ_k)/σ_k
    # Σ_t γ_tk x_t  and  Σ_t γ_tk x_t²  via einsum (MXU), then recombine
    s0 = jnp.einsum("ntk->nk", gamma)  # (n, K)
    s1 = precision.apply_einsum("ntk,ntd->nkd", gamma, xs, mode=mxu)
    s2 = precision.apply_einsum("ntk,ntd->nkd", gamma, xs * xs, mode=mxu)

    # Φ¹ = (s1 − s0·μ)/σ;  Φ² = (s2 − 2μ·s1 + s0·μ²)/σ² − s0
    phi1 = (s1 - s0[..., None] * mu) / sigma
    phi2 = (s2 - 2.0 * mu * s1 + s0[..., None] * (mu * mu)) / var - s0[..., None]

    tnorm = counts[:, None, None]
    phi1 = phi1 / (tnorm * jnp.sqrt(w)[None, :, None])
    phi2 = phi2 / (tnorm * jnp.sqrt(2.0 * w)[None, :, None])
    k, dd = mu.shape
    return jnp.concatenate(
        [phi1.reshape(n, k * dd), phi2.reshape(n, k * dd)], axis=1
    )
