"""Class-weighted block coordinate descent least squares.

Reference: nodes/learning/BlockWeightedLeastSquares.scala — the solver
behind the TIMIT and ImageNet-FV pipelines.  It rebalances skewed class
distributions by giving each example a weight blending a balanced
per-class term with a uniform term, controlled by ``mixture_weight``:

    α_i = mixture_weight · n/(K·n_c(i)) + (1 − mixture_weight)

(α has mean 1: mixture_weight=0 is plain least squares; 1 weights every
class's total contribution equally).  The fit solves the weighted ridge
normal equations blockwise, Gauss–Seidel over feature blocks, with
weighted mean-centering providing the intercept.

TPU form mirrors block_ls.py: one jitted scan-over-epochs /
fori-over-blocks program; weighted Gramians contract over the row-sharded
axis (all-reduce over ICI); the class axis shards over 'model'.

A block's regularised Gramian XᵀDX + λn·I depends on the block, the
weights and λ, and on no sweep.  A fit of more than one sweep therefore
builds and factors each of them once, in a prologue loop of the same
program, and every sweep's block step solves against the kept Cholesky
factor — the same products, precision and order of summation as
factoring anew in every sweep, which is what a one-sweep fit still does
inline (:func:`factor_cache_blocks` decides, on shapes alone).  The
reference keeps each block's statistics from its first pass as well.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from keystone_tpu.models.block_ls import BlockLinearMapper, blockify
from keystone_tpu.models.common import (
    constrain,
    factor_spd,
    solve_factored,
    solve_spd,
)
from keystone_tpu.parallel.collectives import (
    gram_panels,
    sharded_gram,
    sharded_matmul,
)
from jax.sharding import PartitionSpec as P
from keystone_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, current_mesh
from keystone_tpu.workflow.dataset import Dataset
from keystone_tpu.workflow.estimator import LabelEstimator


@jax.jit
def class_weights(y: jnp.ndarray, n, mixture_weight: float):
    """Per-example weights from ±1 one-hot label matrix (n_rows, K).

    Class of row i = argmax of the one-hot; padding rows get weight 0.
    ONE jitted program: eager, this chain dispatched ~18 tiny programs
    per fit (argmax/one_hot/reduce/gather/...), each its own
    compile-cache lookup and dispatch.
    """
    n_rows, k = y.shape
    cls = jnp.argmax(y, axis=1)
    onehot = jax.nn.one_hot(cls, k, dtype=jnp.float32)
    counts = jnp.sum(onehot * (y.max(axis=1, keepdims=True) > 0), axis=0)
    counts = jnp.maximum(counts, 1.0)
    balanced = n / (k * counts[cls])
    alpha = mixture_weight * balanced + (1.0 - mixture_weight)
    row_ok = (jnp.arange(n_rows) < n).astype(jnp.float32)
    return alpha * row_ok


class BlockWeightedLeastSquaresEstimator(LabelEstimator):
    # class-level default for pre-spill_dtype pickles
    spill_dtype = "float32"

    def __init__(
        self,
        block_size: int = 4096,
        num_iter: int = 1,
        lam: float = 0.0,
        mixture_weight: float = 0.5,
        fit_intercept: bool = True,
        spill_dtype: str = "float32",
    ):
        self.block_size = int(block_size)
        self.num_iter = int(num_iter)
        self.lam = float(lam)
        self.mixture_weight = float(mixture_weight)
        self.fit_intercept = fit_intercept
        #: out-of-core spill precision: "bfloat16" halves disk + wire
        #: bytes per sweep (a bandwidth lever — utils/precision.py);
        #: solver math stays f32 either way
        self.spill_dtype = str(spill_dtype)

    def params(self):
        return (
            self.block_size,
            self.num_iter,
            self.lam,
            self.mixture_weight,
            self.fit_intercept,
            self.spill_dtype,
        )

    def fit_dataset(self, data: Dataset, labels: Optional[Dataset] = None):
        if labels is None:
            raise ValueError("BlockWeightedLeastSquaresEstimator requires labels")
        from keystone_tpu.workflow.dataset import StreamDataset

        if isinstance(data, StreamDataset):
            if data.is_host:
                raise TypeError(
                    "host-payload stream reached a block solver; "
                    "featurize to arrays (or CSR) before the fit"
                )
            return self.fit_stream_dataset(data, labels)
        return self._fit(data.array, labels.array, data.n)

    def fit_stream_dataset(
        self, data, labels, spill_dir=None, checkpoint_dir=None, prefetch=None
    ) -> BlockLinearMapper:
        """Out-of-core weighted fit: spill streamed features to a block
        store, then sweep blocks from disk (see block_ls._oc_bcd_fit).
        ``prefetch`` — block read-ahead depth (None →
        ``KEYSTONE_OC_PREFETCH``, else 2).  The spill directory is
        deleted after a successful fit."""
        import shutil

        from keystone_tpu.models.block_ls import _spill_dir
        from keystone_tpu.workflow.blockstore import FeatureBlockStore

        store = FeatureBlockStore.from_batches(
            _spill_dir(spill_dir),
            data.batches(),
            data.n,
            self.block_size,
            dtype=self.spill_dtype,
        )
        fitted = self.fit_store(
            store, labels, checkpoint_dir=checkpoint_dir, prefetch=prefetch
        )
        shutil.rmtree(store.directory, ignore_errors=True)
        return fitted

    def fit_store(
        self, store, labels, checkpoint_dir=None, prefetch=None
    ) -> BlockLinearMapper:
        """Weighted out-of-core fit.  Rides block_ls._oc_bcd_fit, so the
        async double-buffered device feed (blockstore.iter_device_blocks)
        and the donated per-block carry (_oc_block_step donates p and
        w_b; the staged block frees by refcount) apply to the weighted
        sweep too."""
        from keystone_tpu.models.block_ls import (
            _check_store_rows,
            _oc_bcd_fit,
            finish_block_model,
        )
        from keystone_tpu.workflow.dataset import as_dataset

        labels = as_dataset(labels)
        _check_store_rows(store, labels)
        y = labels.array.astype(jnp.float32)
        alpha = class_weights(y, jnp.float32(labels.n), self.mixture_weight)
        weights, xm, ym = _oc_bcd_fit(
            store,
            y,
            alpha,
            float(labels.n),
            self.lam,
            self.num_iter,
            self.fit_intercept,
            checkpoint_dir=checkpoint_dir,
            prefetch=prefetch,
        )
        return finish_block_model(
            weights, xm, ym, store.d, self.block_size, self.fit_intercept
        )

    def fit_arrays(self, x, y=None):
        x = jnp.asarray(x)
        return self._fit(x, jnp.asarray(y), x.shape[0])

    def _fit(self, x, y, n) -> BlockLinearMapper:
        from keystone_tpu.obs import ledger

        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        nf = jnp.float32(n)
        kept = factor_cache_blocks(
            x.shape[0], x.shape[1], self.block_size, self.num_iter
        )
        # the host's part of the solve (the dispatch); nothing here waits
        with ledger.span(
            "solver.fit", solver="bcd.weighted", n=int(n),
            blocks=-(-x.shape[1] // self.block_size),
            gram_panels=gram_panels(self.block_size),
            factor_cache=kept,
            factor_cache_bytes=kept * self.block_size**2 * 4,
        ):
            alpha = class_weights(y, nf, self.mixture_weight)
            weights, xm, ym = _weighted_bcd_fit(
                x, y, alpha, nf, self.lam, self.num_iter, self.block_size,
                self.fit_intercept, obs=ledger.solver_obs(),
            )
        from keystone_tpu.models.block_ls import finish_block_model

        return finish_block_model(
            weights, xm, ym, x.shape[1], self.block_size, self.fit_intercept
        )


def factor_cache_blocks(n_rows: int, d: int, block_size: int, num_iter: int) -> int:
    """How many blocks' Cholesky factors :func:`_weighted_bcd_fit` keeps
    across its sweeps: all ``ceil(d / block_size)`` or none.  Two shapes
    decide, both static.  One sweep has no second use for a factor.  And
    the ``(blocks, block_size, block_size)`` float32 factors, replicated,
    may cost a device no more than one more copy of its rows of the
    features (the solver holds two, ``x`` and ``xb``): the rows a device
    holds, array rows over the mesh's data axis, are at least
    ``block_size``.  At width 4096 sixteen factors are 1.07 GB."""
    rows_a_device = n_rows // current_mesh().shape[DATA_AXIS]
    if num_iter > 1 and rows_a_device >= block_size:
        return -(-d // block_size)
    return 0


@partial(
    jax.jit,
    static_argnames=("num_iter", "block_size", "fit_intercept", "obs"),
)
def _weighted_bcd_fit(
    x, y, alpha, n, lam, num_iter, block_size, fit_intercept, obs=False
):
    wsum = jnp.sum(alpha)
    if fit_intercept:
        xm = (alpha @ x) / wsum
        ym = (alpha @ y) / wsum
        row_ok = (alpha > 0).astype(jnp.float32)[:, None]
        xc = (x - xm) * row_ok
        yc = (y - ym) * row_ok
    else:
        xm = jnp.zeros((x.shape[1],), jnp.float32)
        ym = jnp.zeros((y.shape[1],), jnp.float32)
        xc, yc = x, y

    xb = blockify(xc, block_size)  # (nb, n_rows, bs)
    nb, n_rows, bs = xb.shape
    k = yc.shape[1]
    xb = constrain(xb, None, DATA_AXIS, None)
    yc = constrain(yc, DATA_AXIS, MODEL_AXIS)
    sa = jnp.sqrt(alpha)

    w0 = jnp.zeros((nb, bs, k), jnp.float32)
    p0 = jnp.zeros_like(yc)

    def scaled(b):
        return xb[b] * sa[:, None]  # √α-scaled block: AᵀA = XᵀDX

    def gram(a):
        with jax.named_scope("bcd.gram"):
            return sharded_gram(a)

    factors = None
    if factor_cache_blocks(n_rows, x.shape[1], block_size, num_iter):

        def factor_block(b):
            ata = gram(scaled(b))
            with jax.named_scope("bcd.solve"):
                return factor_spd(ata, reg=lam * n)

        # once per fit: no sweep changes a block's Gramian or its factor
        factors = constrain(lax.map(factor_block, jnp.arange(nb)))

    def block_step(b, carry):
        w, p = carry
        a = scaled(b)
        wb = w[b]
        # scopes are metadata only (what an operator reads in a device
        # trace): the HLO and the compile cache's key do not change
        with jax.named_scope("bcd.residual"):
            target = (yc - p) * sa[:, None] + a @ wb
        if factors is None:
            ata = gram(a)
        with jax.named_scope("bcd.cross"):
            atr = sharded_matmul(a, target, out_spec=P(None, MODEL_AXIS))
        with jax.named_scope("bcd.solve"):
            if factors is None:
                wb_new = solve_spd(ata, atr, reg=lam * n)
            else:
                wb_new = solve_factored(factors[b], atr)
        with jax.named_scope("bcd.residual"):
            p_new = constrain(p + xb[b] @ (wb_new - wb), DATA_AXIS, MODEL_AXIS)
        return w.at[b].set(wb_new), p_new

    def epoch(carry, e):
        carry = lax.fori_loop(0, nb, block_step, carry)
        if obs:
            # per-epoch convergence point for the run ledger (static
            # flag: the inert program carries no callback — see
            # block_ls._bcd_fit)
            from keystone_tpu.obs import ledger

            _, p = carry
            r = yc - p
            jax.debug.callback(
                ledger.solver_callback(
                    "bcd.weighted", "epoch", "objective"
                ),
                e,
                0.5 * jnp.vdot(r, r) / n,
            )
        return carry, None

    # xs only when observing — the inert program stays byte-identical
    # to the pre-obs one (see models/kmeans.py)
    if obs:
        (w, _), _ = lax.scan(epoch, (w0, p0), jnp.arange(num_iter))
    else:
        (w, _), _ = lax.scan(epoch, (w0, p0), None, length=num_iter)
    return w, xm, ym
