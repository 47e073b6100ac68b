"""Diagonal-covariance Gaussian mixture model (EM).

Reference: nodes/learning/GaussianMixtureModel.scala §
GaussianMixtureModelEstimator — the Fisher-vector vocabulary model.  The
reference's production path is the native EncEval C++ EM
(utils/external/EncEval.scala via JNI, SURVEY.md §2.8); this is its
TPU-native replacement: EM as a jitted lax.scan whose E-step
responsibilities come from one log-density gemm and whose M-step
sufficient statistics contract over the row-sharded axis (the treeReduce).

Initialization: k-means++ centers, global variance — deterministic given
the seed, like the reference's seeded sampling.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from keystone_tpu.models.common import constrain
from keystone_tpu.models.kmeans import _kmeans_fit
from keystone_tpu.parallel.mesh import DATA_AXIS
from keystone_tpu.workflow.dataset import Dataset
from keystone_tpu.workflow.estimator import Estimator
from keystone_tpu.workflow.transformer import Transformer
from keystone_tpu.utils.precision import sdot

_LOG2PI = 1.8378770664093453


def _log_gaussians(x, means, variances, log_weights, dot=None):
    """(n, K) log w_k + log N(x; μ_k, diag σ²_k) via gemm expansion.

    ``dot`` overrides the two gemms — the Fisher-vector bf16 apply path
    passes utils/precision.apply_dot so the posterior contractions ride
    the policy; the default plain ``@`` keeps EM solver math (and every
    other caller) bit-identical to before."""
    if dot is None:
        dot = lambda a, b: a @ b  # noqa: E731 - the inert gemm, verbatim
    inv = 1.0 / variances  # (K, d)
    # ‖(x−μ)/σ‖² = Σ x²/σ² − 2 Σ xμ/σ² + Σ μ²/σ²
    quad = (
        dot(x * x, inv.T)
        - 2.0 * dot(x, (means * inv).T)
        + jnp.sum(means * means * inv, axis=1)
    )
    log_norm = -0.5 * (jnp.sum(jnp.log(variances), axis=1) + x.shape[1] * _LOG2PI)
    return log_weights + log_norm - 0.5 * quad


class GaussianMixtureModel(Transformer):
    """Posterior responsibilities transformer; carries (weights, means,
    variances) for Fisher-vector encoding."""

    traced_attrs = ("weights", "means", "variances")

    def __init__(self, weights, means, variances):
        self.weights = weights  # (K,)
        self.means = means  # (K, d)
        self.variances = variances  # (K, d)

    @property
    def k(self):
        return self.means.shape[0]

    def log_responsibilities(self, x):
        lg = _log_gaussians(x, self.means, self.variances, jnp.log(self.weights))
        return lg - jax.scipy.special.logsumexp(lg, axis=1, keepdims=True)

    def apply_batch(self, xs, mask=None):
        r = jnp.exp(self.log_responsibilities(xs))
        return (r, mask) if mask is not None else r

    def apply_one(self, x):
        return self.apply_batch(x[None])[0]


# Pytree registration lets a fitted GMM ride as a TRACED jit argument
# (FisherVector.traced_attrs carries the whole model object), so its
# arrays are never embedded as program constants — see
# Transformer.traced_attrs for the measured lowering/compile-cache cost
# of device-array closure constants.
jax.tree_util.register_pytree_node(
    GaussianMixtureModel,
    lambda g: ((g.weights, g.means, g.variances), None),
    lambda _, c: GaussianMixtureModel(*c),
)


class GaussianMixtureModelEstimator(Estimator):
    def __init__(
        self,
        k: int,
        max_iterations: int = 50,
        min_variance: float = 1e-6,
        seed: int = 0,
        kmeans_iters: int = 10,
    ):
        self.k = int(k)
        self.max_iterations = int(max_iterations)
        self.min_variance = float(min_variance)
        self.seed = int(seed)
        self.kmeans_iters = int(kmeans_iters)

    def params(self):
        return (
            self.k,
            self.max_iterations,
            self.min_variance,
            self.seed,
            self.kmeans_iters,
        )

    def fit_dataset(self, data: Dataset) -> GaussianMixtureModel:
        from keystone_tpu.obs import ledger

        obs = ledger.solver_obs()
        x = data.array
        if data.mask is not None:
            # ragged prep (flatten, mask, true count) lives INSIDE
            # _gmm_fit's jit — one program, not two
            w, m, v = _gmm_fit(
                x, None, data.mask, self.k, self.max_iterations,
                self.min_variance, self.seed, self.kmeans_iters, obs=obs,
            )
        else:
            # row mask + PRNG key are built INSIDE _gmm_fit (row_ok=None)
            # — eager, the iota/less/convert/threefry preamble was 4 tiny
            # compiled programs per fit (r5 call-site attribution)
            w, m, v = _gmm_fit(
                x, float(data.n), None, self.k, self.max_iterations,
                self.min_variance, self.seed, self.kmeans_iters, obs=obs,
            )
        return GaussianMixtureModel(w, m, v)

    def fit_arrays(self, x) -> GaussianMixtureModel:
        from keystone_tpu.obs import ledger

        x = jnp.asarray(x, jnp.float32)
        w, m, v = _gmm_fit(
            x, float(x.shape[0]), None, self.k, self.max_iterations,
            self.min_variance, self.seed, self.kmeans_iters,
            obs=ledger.solver_obs(),
        )
        return GaussianMixtureModel(w, m, v)


@partial(jax.jit, static_argnames=("iters", "obs"))
def _em_steps(x, n, row_ok, w0, mu0, var0, iters, min_var, obs=False):
    """``iters`` EM steps from a given initial GMM (the deterministic part
    of the fit; also the contract of the native C++ EM in
    ops/fisher_ffi.py § gmm_em_ffi, which parity-tests against this).

    ``obs`` (static): per-EM-iteration ``solver.epoch`` telemetry (mean
    log-likelihood — the logsumexp is already computed for the E-step,
    so the extra cost is one masked reduction) via
    ``jax.debug.callback``; the inert program carries no callbacks."""

    def em(carry, it):
        w, mu, var = carry
        lg = _log_gaussians(x, mu, var, jnp.log(w))
        lse = jax.scipy.special.logsumexp(lg, axis=1, keepdims=True)
        lr = lg - lse
        r = jnp.exp(lr) * row_ok[:, None]  # (n, K)
        nk = constrain(jnp.sum(r, axis=0))  # psum over 'data'
        nk = jnp.maximum(nk, 1e-10)
        mu_new = constrain(sdot(r.T, x)) / nk[:, None]
        ex2 = constrain(sdot(r.T, x * x)) / nk[:, None]
        var_new = jnp.maximum(ex2 - mu_new * mu_new, min_var)
        w_new = nk / n
        if obs:
            from keystone_tpu.obs import ledger

            loglik = constrain(jnp.sum(lse[:, 0] * row_ok)) / n
            jax.debug.callback(
                ledger.solver_callback("gmm", "epoch", "mean_log_likelihood"),
                it,
                loglik,
            )
        return (w_new, mu_new, var_new), None

    # xs only when observing — the inert program stays byte-identical
    # to the pre-obs one (see models/kmeans.py)
    if obs:
        (w, mu, var), _ = lax.scan(em, (w0, mu0, var0), jnp.arange(iters))
    else:
        (w, mu, var), _ = lax.scan(em, (w0, mu0, var0), None, length=iters)
    return w, mu, var


@partial(jax.jit, static_argnames=("k", "iters", "kmeans_iters", "obs"))
def _gmm_fit(x, n, row_ok, k, iters, min_var, seed, kmeans_iters, obs=False):
    # the eager preambles (ragged flatten/mask/count; dense iota/less;
    # PRNGKey) were ~7 extra compiled programs per fit, each its own
    # compile-cache lookup and dispatch — all live inside this one
    # program now
    if row_ok is not None and row_ok.ndim == 2:  # ragged (n,max_k) mask
        x = x.reshape(-1, x.shape[-1])
        valid = (row_ok.reshape(-1) > 0).astype(jnp.float32)
        x = x * valid[:, None]
        n = jnp.sum(valid)
        row_ok = valid
    elif row_ok is not None:  # 1-D row mask (n,): valid-row indicator
        # n may arrive as None (fit_dataset's mask branch) — derive it
        # from the mask, and zero masked rows so they can't leak into
        # the moment sums (the pre-r5 handling, regressed when the
        # ragged path was fused into this jit)
        row_ok = (row_ok.reshape(-1) > 0).astype(jnp.float32)
        x = x * row_ok[:, None]
        if n is None:
            n = jnp.sum(row_ok)
    elif row_ok is None:
        row_ok = (jnp.arange(x.shape[0]) < n).astype(jnp.float32)
    key = jax.random.PRNGKey(seed)
    x = constrain(x.astype(jnp.float32), DATA_AXIS)
    means0 = _kmeans_fit(x, row_ok, k, kmeans_iters, key, obs=obs)
    gmean = jnp.sum(x * row_ok[:, None], axis=0) / n
    gvar = jnp.sum((x - gmean) ** 2 * row_ok[:, None], axis=0) / n
    var0 = jnp.tile(jnp.maximum(gvar, min_var)[None, :], (k, 1))
    w0 = jnp.full((k,), 1.0 / k, jnp.float32)
    return _em_steps(x, n, row_ok, w0, means0, var0, iters, min_var, obs=obs)
