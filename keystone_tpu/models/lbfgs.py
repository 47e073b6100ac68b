"""Batch L-BFGS with L2 regularization.

Reference: nodes/learning/LBFGS.scala § DenseLBFGSwithL2 /
SparseLBFGSwithL2 with gradient classes (LeastSquaresDenseGradient,
LeastSquaresSparseGradient): per-iteration distributed gradients via
``treeAggregate`` of per-partition gemms, Breeze L-BFGS line search on the
driver.

TPU form: the gradient is a sharded einsum over the row-sharded batch
(all-reduce over ICI), and the *entire* L-BFGS loop — two-loop recursion,
backtracking Armijo line search, rolling (s, y) history — is one jitted
``lax.scan``.  There is no driver: every device runs the identical
replicated optimizer state.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from keystone_tpu.models.linear import LinearMapper
from keystone_tpu.parallel.mesh import DATA_AXIS
from keystone_tpu.models.common import constrain
from keystone_tpu.workflow.dataset import Dataset
from keystone_tpu.workflow.estimator import LabelEstimator
from keystone_tpu.utils.precision import sdot


def _lbfgs_machinery(
    vag_of_data: Callable,
    shape,
    m: int,
    tol: float,
    max_line_search: int,
    obs_label: Optional[str] = None,
):
    """``(init, step)`` over FLAT iterates for the L-BFGS loop.

    ``obs_label``: when set, every effective step emits a
    ``solver.epoch`` convergence point (objective + grad norm) to the
    active run ledger via ``jax.debug.callback``.  The label is resolved
    at TRACE time and threaded as a static jit argument by the callers,
    so with observability off the compiled program is exactly the
    pre-obs one (no callbacks, no host traffic).

    ``vag_of_data(data, x) -> (f, g)`` with ``x`` in its ORIGINAL shape;
    ``data`` is an arbitrary pytree threaded through explicitly (rather
    than closed over) so the resumable driver's jitted chunks take the
    feature arrays as arguments — a closure would embed them as XLA
    constants, doubling HBM for large fits.  ``step(data, carry)``
    returns ``(carry, f)`` (scan-compatible); ``init(data, x0_flat)``
    builds the carry ``(x, f, g, s_hist, y_hist, rho_hist, count,
    done)`` — exactly the state a mid-fit checkpoint must persist.
    """

    def value_and_grad(data, x):
        f, g = vag_of_data(data, x.reshape(shape))
        return f, jnp.asarray(g).reshape(-1)

    def dot(a, b):
        return jnp.vdot(a, b)

    def two_loop(g, s_hist, y_hist, rho_hist, count):
        """Standard two-loop recursion over the rolling history."""
        q = g
        alphas = jnp.zeros((m,), jnp.float32)

        def bwd(i, carry):
            q, alphas = carry
            idx = (count - 1 - i) % m
            valid = i < jnp.minimum(count, m)
            a = rho_hist[idx] * dot(s_hist[idx], q)
            a = jnp.where(valid, a, 0.0)
            q = q - a * y_hist[idx]
            return q, alphas.at[idx].set(a)

        q, alphas = lax.fori_loop(0, m, bwd, (q, alphas))
        # initial Hessian scaling γ = sᵀy / yᵀy of the newest pair
        newest = (count - 1) % m
        gamma = jnp.where(
            count > 0,
            dot(s_hist[newest], y_hist[newest])
            / jnp.maximum(dot(y_hist[newest], y_hist[newest]), 1e-20),
            1.0,
        )
        r = gamma * q

        def fwd(i, r):
            idx = (count - jnp.minimum(count, m) + i) % m
            valid = i < jnp.minimum(count, m)
            beta = rho_hist[idx] * dot(y_hist[idx], r)
            upd = (alphas[idx] - beta) * s_hist[idx]
            return r + jnp.where(valid, 1.0, 0.0) * upd

        return lax.fori_loop(0, m, fwd, r)

    def line_search(data, x, f, g, p):
        """Backtracking Armijo (c1=1e-4), halving from t=1."""
        gp = dot(g, p)
        c1 = 1e-4

        def cond(carry):
            t, it, f_new = carry
            return jnp.logical_and(it < max_line_search, f_new > f + c1 * t * gp)

        def body(carry):
            t, it, _ = carry
            t = t * 0.5
            f_new, _ = value_and_grad(data, x + t * p)
            return t, it + 1, f_new

        f1, _ = value_and_grad(data, x + p)
        t, _, _ = lax.while_loop(cond, body, (jnp.float32(1.0), 0, f1))
        return t

    def step(data, carry):
        x, f, g, s_hist, y_hist, rho_hist, count, done = carry

        def do_step(_):
            p = -two_loop(g, s_hist, y_hist, rho_hist, count)
            # fall back to steepest descent if p isn't a descent direction
            p = jnp.where(dot(p, g) < 0, p, -g)
            t = line_search(data, x, f, g, p)
            x_new = x + t * p
            f_new, g_new = value_and_grad(data, x_new)
            s = x_new - x
            yv = g_new - g
            sy = dot(s, yv)
            idx = count % m
            ok = sy > 1e-10  # curvature condition; skip update otherwise
            s_h = jnp.where(ok, s_hist.at[idx].set(s), s_hist)
            y_h = jnp.where(ok, y_hist.at[idx].set(yv), y_hist)
            r_h = jnp.where(ok, rho_hist.at[idx].set(1.0 / jnp.maximum(sy, 1e-20)), rho_hist)
            cnt = jnp.where(ok, count + 1, count)
            gnorm = jnp.sqrt(dot(g_new, g_new))
            if obs_label is not None:
                # fires only on EFFECTIVE steps (the cond's done branch
                # skips it), so the ledger series is the true trajectory
                from keystone_tpu.obs import ledger as _ledger

                jax.debug.callback(
                    _ledger.solver_callback(
                        obs_label, "objective", "grad_norm"
                    ),
                    f_new,
                    gnorm,
                )
            return x_new, f_new, g_new, s_h, y_h, r_h, cnt, gnorm < tol

        def skip(_):
            return x, f, g, s_hist, y_hist, rho_hist, count, done

        carry = lax.cond(done, skip, do_step, None)
        return carry, carry[1]

    def init(data, x0_flat):
        f0, g0 = value_and_grad(data, x0_flat)
        s_hist = jnp.zeros((m, x0_flat.size), jnp.float32)
        y_hist = jnp.zeros((m, x0_flat.size), jnp.float32)
        rho_hist = jnp.zeros((m,), jnp.float32)
        return (
            x0_flat,
            f0,
            g0,
            s_hist,
            y_hist,
            rho_hist,
            jnp.int32(0),
            jnp.array(False),
        )

    return init, step


def lbfgs_minimize(
    value_and_grad: Callable,
    x0: jnp.ndarray,
    max_iter: int = 50,
    history: int = 10,
    tol: float = 1e-7,
    max_line_search: int = 20,
    obs_label: Optional[str] = None,
):
    """Minimize a smooth function of one array with L-BFGS.

    ``value_and_grad(x) -> (f, g)`` must be jit-traceable.  Returns the
    final iterate.  The whole loop compiles to a single XLA program.

    The iterate and the (m, ·) history buffers are kept FLATTENED: a
    (m, d, k) history pads its k lane dim to the 128-wide TPU tile (1.7×
    extra HBM at k=147 — the difference between fitting and OOM at
    d=10⁶), while (m, d·k) pads only the tail of one axis.
    """
    shape = jnp.shape(x0)
    init, step = _lbfgs_machinery(
        lambda _, x: value_and_grad(x),
        shape,
        history,
        tol,
        max_line_search,
        obs_label=obs_label,
    )
    carry = init(None, jnp.asarray(x0).reshape(-1))
    (x, *_), _ = lax.scan(
        lambda c, _: step(None, c), carry, None, length=max_iter
    )
    return x.reshape(shape)


def lbfgs_minimize_resumable(
    vag_of_data: Callable,
    data,
    x0,
    max_iter: int,
    history: int,
    tol: float = 1e-7,
    max_line_search: int = 20,
    checkpoint_every: int = 10,
    save_cb=None,
    load_cb=None,
):
    """L-BFGS as a host loop of jitted ``checkpoint_every``-step chunks,
    persisting the FULL optimizer carry (iterate, gradient, s/y/ρ
    history, count) between chunks so an interrupted fit resumes exactly
    (round-3 review weak-3: the reference's text fits run hours; a mid-fit
    kill must not lose everything — nodes/learning/LBFGS.scala had
    Spark lineage underneath it).

    ``load_cb() -> (it_done, host_carry) | None`` and
    ``save_cb(it_done, host_carry)`` own durability (and, in
    multi-process runs, the broadcast of the resume decision — see
    ``_lbfgs_checkpoint_callbacks``).  The trajectory is IDENTICAL to
    :func:`lbfgs_minimize` (same step function; chunking only cuts the
    scan), so resumed == uninterrupted to float tolerance.
    """
    import numpy as np

    shape = jnp.shape(x0)
    init, step = _lbfgs_machinery(
        vag_of_data, shape, history, tol, max_line_search
    )

    # the scan carry is DONATED: chunk N's optimizer state (iterate,
    # gradient, 2·m weight-sized history buffers) lands in chunk N−1's
    # HBM instead of transiently doubling the (2m+2)·d·k footprint at
    # every chunk boundary — at text scale that doubling is GBs.  The
    # caller rebinds `carry` to the output immediately, and save_cb only
    # ever sees the NEW carry.
    @partial(jax.jit, static_argnames=("iters",), donate_argnums=(1,))
    def chunk(data, carry, iters):
        return lax.scan(
            lambda c, _: step(data, c), carry, None, length=iters
        )[0]

    start, carry = 0, None
    if load_cb is not None:
        loaded = load_cb()
        if loaded is not None:
            start, host_carry = loaded
            if start > max_iter:
                # a COMPLETED longer fit's checkpoint: resuming would
                # silently return more-iterated weights for a shorter
                # requested fit — refit from scratch instead (start ==
                # max_iter is fine: same fit re-requested, reuse it)
                start, host_carry = 0, None
            if host_carry is not None:
                carry = tuple(jnp.asarray(a) for a in host_carry)
    if carry is None:
        start = 0
        carry = jax.jit(init)(data, jnp.asarray(x0).reshape(-1))
    from keystone_tpu.obs import ledger, metrics

    observe = ledger.active() is not None
    it = start
    while it < max_iter:
        import time as _time

        t_chunk = _time.perf_counter()
        n_steps = min(checkpoint_every, max_iter - it)
        carry = chunk(data, carry, n_steps)
        it += n_steps
        save_seconds = None
        if save_cb is not None:
            # the DEVICE carry is handed over: at d·k·(2m+2) scale the
            # host copy is GBs, and non-writer processes must not pay it
            # (save_cb converts after its process-index check)
            ledger.device_wait(carry)
            t_save = _time.perf_counter()
            save_cb(it, carry)
            save_seconds = _time.perf_counter() - t_save
            metrics.observe("solver.checkpoint_save_seconds", save_seconds)
        if observe:
            # per-chunk convergence point from the (replicated) carry;
            # the per-iteration series inside the chunk rides the
            # machinery's own callback when obs_label was threaded
            f, gnorm = _carry_stats(carry[1], carry[2])
            ledger.solver_epoch(
                "lbfgs.chunk",
                it=int(it),
                objective=float(np.asarray(f)),  # lint: allow-host-sync
                grad_norm=float(np.asarray(gnorm)),  # lint: allow-host-sync
                chunk_seconds=_time.perf_counter() - t_chunk,
                checkpoint_save_seconds=save_seconds,
            )
    return carry[0].reshape(shape)


@jax.jit
def _carry_stats(f, g):
    """(objective, ‖g‖) of a resumable-driver carry — one tiny program,
    so the obs-enabled chunk loop never pulls the weight-sized gradient
    to host just to norm it."""
    return f, jnp.sqrt(jnp.vdot(g, g))


def _lbfgs_checkpoint_callbacks(
    checkpoint_dir: str, problem: str, tag: str, flat_size: int, m: int
):
    """(load_cb, save_cb) persisting the L-BFGS carry to
    ``<dir>/lbfgs_<tag>.npz`` through the hardened durable layer
    (utils/durable: atomic tmp+fsync+rename, BLAKE2b sidecar, rolling
    last-good fallback — a corrupt newest checkpoint resumes from the
    previous chunk instead of refitting from scratch), with
    content-fingerprint validation and — multi-process — process 0 alone
    reading and BROADCASTING the resume decision, because every process
    must enter the chunk loop at the same iteration or the collectives
    deadlock.  ``flat_size``/``m`` let every process build the carry
    template locally, so the broadcast pytree has uniform shapes with or
    without a checkpoint on disk."""
    import os

    import numpy as np

    from keystone_tpu.utils import durable

    os.makedirs(checkpoint_dir, exist_ok=True)
    path = os.path.join(checkpoint_dir, f"lbfgs_{tag}.npz")
    keys = ("x", "f", "g", "s_hist", "y_hist", "rho_hist", "count", "done")
    template = (
        np.zeros((flat_size,), np.float32),
        np.float32(0),
        np.zeros((flat_size,), np.float32),
        np.zeros((m, flat_size), np.float32),
        np.zeros((m, flat_size), np.float32),
        np.zeros((m,), np.float32),
        np.int32(0),
        np.bool_(False),
    )

    def _valid(z) -> bool:
        if str(z.get("problem")) != problem:
            return False  # a different fit's checkpoint: not corrupt, stale
        carry = tuple(np.asarray(z[k]) for k in keys)
        return all(a.shape == t.shape for a, t in zip(carry, template))

    def _read():
        loaded = durable.load_npz(path, validate=_valid)
        if loaded is None:
            return None  # no valid checkpoint at any depth: fit from scratch
        z, _ = loaded
        return int(z["it"]), tuple(np.asarray(z[k]) for k in keys)

    def load_cb():
        if jax.process_count() == 1:
            return _read()
        from jax.experimental import multihost_utils

        got = _read() if jax.process_index() == 0 else None
        it = int(
            multihost_utils.broadcast_one_to_all(
                np.int32(got[0] if got is not None else -1)
            )
        )
        if it < 0:
            return None
        carry = got[1] if got is not None else template
        carry = multihost_utils.broadcast_one_to_all(
            tuple(np.asarray(a, t.dtype) for a, t in zip(carry, template))
        )
        return it, tuple(carry)

    def save_cb(it, carry):
        # the carry is replicated across processes (deterministic same
        # math everywhere) — one writer suffices, and only it pays the
        # device→host copy
        if jax.process_index() != 0:
            return
        durable.save_npz(
            path,
            dict(
                {k: np.asarray(a) for k, a in zip(keys, carry)},
                it=np.int32(it),
                problem=problem,
            ),
            keep=2,
        )

    return load_cb, save_cb


class DenseLBFGSwithL2(LabelEstimator):
    """Least-squares loss + L2, minimized with L-BFGS
    (nodes/learning/LBFGS.scala § DenseLBFGSwithL2).

    loss(W) = 1/(2n)·‖XW − Y‖² + (λ/2)·‖W‖²
    """

    def __init__(
        self,
        lam: float = 0.0,
        num_iterations: int = 50,
        history: int = 10,
        fit_intercept: bool = False,
    ):
        self.lam = float(lam)
        self.num_iterations = int(num_iterations)
        self.history = int(history)
        self.fit_intercept = fit_intercept

    def params(self):
        return (self.lam, self.num_iterations, self.history, self.fit_intercept)

    def choose_physical(self, sample):
        """Dense vs sparse physical choice (the reference's
        NodeOptimizationRule picking LeastSquaresDenseGradient vs
        LeastSquaresSparseGradient from sampled data): host datasets of
        scipy sparse rows route to the sparse-gradient solver."""
        from keystone_tpu.ops.sparse import is_scipy_sparse_rows

        if (
            type(self) is DenseLBFGSwithL2
            and sample is not None
            and sample.is_host
            and is_scipy_sparse_rows(sample.items)
        ):
            return SparseLBFGSwithL2(
                lam=self.lam,
                num_iterations=self.num_iterations,
                history=self.history,
                # survives the swap: the sparse path models the intercept
                # as an unregularized constant column
                fit_intercept=self.fit_intercept,
            )
        return self

    def fit_dataset(self, data: Dataset, labels: Optional[Dataset] = None):
        if labels is None:
            raise ValueError("DenseLBFGSwithL2 requires labels")
        return self._fit(data.array, labels.array, data.n)

    def fit_arrays(self, x, y=None):
        x = jnp.asarray(x)
        return self._fit(x, jnp.asarray(y), x.shape[0])

    def _fit(self, x, y, n):
        from keystone_tpu.obs import ledger

        with ledger.span("solver.fit", solver="lbfgs", n=int(n), blocks=1):
            w, b = _lbfgs_least_squares(
                jnp.asarray(x, jnp.float32),
                jnp.asarray(y, jnp.float32),
                jnp.float32(n),
                self.lam,
                self.num_iterations,
                self.history,
                self.fit_intercept,
                obs=ledger.solver_obs(),
            )
        return LinearMapper(w, b if self.fit_intercept else None)

    def fit_checkpointed(
        self,
        data: Dataset,
        labels: Optional[Dataset] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 10,
    ):
        """Fit with mid-fit checkpoint/resume: the optimizer carry
        (iterate, gradient, s/y/ρ history, count) persists every
        ``checkpoint_every`` iterations, and an interrupted fit resumes
        from the last saved carry with the identical trajectory
        (round-3 review weak-3; the BCD solvers' ``fit_checkpointed``
        analogue for the L-BFGS family)."""
        if labels is None:
            raise ValueError("fit_checkpointed requires labels")
        if checkpoint_dir is None:
            return self.fit_dataset(data, labels)
        w, b = _lbfgs_dense_checkpointed(
            data.array,
            labels.array,
            data.n,
            self.lam,
            self.num_iterations,
            self.history,
            self.fit_intercept,
            checkpoint_dir,
            checkpoint_every,
        )
        return LinearMapper(w, b if self.fit_intercept else None)


class SparseLBFGSwithL2(DenseLBFGSwithL2):
    """Sparse-gradient variant (LBFGS.scala § SparseLBFGSwithL2 /
    LeastSquaresSparseGradient).

    Features stay in COO form, nnz-BUCKETED (ops/sparse.BucketedSparseRows
    — rows grouped by power-of-two nnz caps so one dense-ish document
    doesn't inflate every row's padding), never the dense n×d matrix:
    the forward pass gathers weight rows, the gradient scatter-adds into
    (d, k), both row-chunked so the live intermediate stays bounded at
    any (vocab, k).  At 100k+ vocabulary this is ~3 orders of magnitude
    less memory than densifying, which is exactly how the reference ran
    text at scale.

    ``fit_intercept=True`` augments each row with a constant feature
    (index d, value 1) whose weight is excluded from the L2 penalty —
    the sparse-safe intercept (centering would densify; the constant
    column does not).

    Accepts: a host Dataset of scipy sparse rows (what ``Sparsify``
    emits), a ``PaddedSparseRows``/``BucketedSparseRows`` directly via
    :meth:`fit_sparse`, or — fallback — any dense input, which routes to
    the dense solver so the optimizer's physical-choice rule can still
    select either class name.
    """

    # already the sparse physical form: restore the base hook (the same
    # function object Estimator defines) so NodeChoiceRule's
    # is-overridden guard skips the (expensive) sample execution
    # entirely for nodes that could never swap
    choose_physical = LabelEstimator.choose_physical

    def fit_dataset(self, data: Dataset, labels: Optional[Dataset] = None):
        from keystone_tpu.ops.sparse import (
            BucketedSparseRows,
            is_scipy_sparse_rows,
        )

        if labels is None:
            raise ValueError("SparseLBFGSwithL2 requires labels")
        if data.is_host and is_scipy_sparse_rows(data.items):
            sp = BucketedSparseRows.from_scipy_rows(data.items)
            return self.fit_sparse(sp, labels.array, n=data.n)
        return super().fit_dataset(data, labels)

    def _capped_history(self, d_aug: int, k: int) -> int:
        """HBM-capped history length m.  L-BFGS history is 2·m
        weight-sized buffers; at text-scale (d=10⁶, k=147 → 0.6 GB per
        buffer) a fixed m=10 alone exceeds HBM.  Cap m so the history
        fits in a fraction of the device, trading convergence rate for
        feasibility (still L-BFGS, just shorter memory)."""
        from keystone_tpu.workflow.profiling import device_hbm_budget

        per_pair = 2 * d_aug * k * 4
        # 0.2: the line search holds ~6 more weight-sized temporaries
        # (x, g, p, trial iterates, value_and_grad activations) beyond
        # the 2·m history buffers — measured at d=10⁶·k=147, 0.35 OOMed
        hist_fraction = 0.2
        history = min(
            self.history,
            max(2, int(device_hbm_budget(hist_fraction) // per_pair)),
        )
        if history < self.history:
            import logging

            logging.getLogger(__name__).info(
                "sparse L-BFGS: history %d -> %d (weight-sized pairs are "
                "%.2f GB each; keeping them under %d%% of HBM)",
                self.history,
                history,
                per_pair / 2**30,
                int(hist_fraction * 100),
            )
        return history

    def fit_sparse(
        self,
        sp,
        y,
        n: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 10,
    ):
        """Fit from a PaddedSparseRows or BucketedSparseRows matrix.
        With ``checkpoint_dir``, the fit persists the full optimizer
        carry every ``checkpoint_every`` iterations and resumes an
        interrupted run (round-3 review weak-3)."""
        from keystone_tpu.ops.sparse import bucketize_with_labels

        d = sp.num_features
        intercept = bool(self.fit_intercept)
        bidx, bvals, by, n, d_aug, _row_ok = bucketize_with_labels(
            sp, y, n=n, intercept=intercept
        )
        k = by[0].shape[1]
        history = self._capped_history(d_aug, k)
        if checkpoint_dir is None:
            from keystone_tpu.obs import ledger

            w = _lbfgs_sparse_least_squares(
                tuple(bidx),
                tuple(bvals),
                tuple(by),
                jnp.float32(n),
                d_aug,
                self.lam,
                self.num_iterations,
                history,
                intercept,
                obs=ledger.solver_obs(),
            )
        else:
            w = _lbfgs_sparse_checkpointed(
                tuple(bidx),
                tuple(bvals),
                tuple(by),
                n,
                d_aug,
                self.lam,
                self.num_iterations,
                history,
                intercept,
                checkpoint_dir,
                checkpoint_every,
            )
        if intercept:
            return LinearMapper(w[:d], w[d])
        return LinearMapper(w, None)

    def fit_checkpointed(
        self,
        data,
        labels: Optional[Dataset] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 10,
        n: Optional[int] = None,
    ):
        """Sparse fit with mid-fit checkpoint/resume.  ``data`` may be a
        host Dataset of scipy sparse rows (the Sparsify output), a
        Padded/BucketedSparseRows, or dense (routes to the dense
        checkpointed path).  The checkpoint holds the full optimizer
        carry — at 1M-vocab scale the one solver family where a mid-fit
        kill used to lose everything (round-3 review weak-3)."""
        from keystone_tpu.ops.sparse import (
            BucketedSparseRows,
            is_scipy_sparse_rows,
        )

        if labels is None:
            raise ValueError("fit_checkpointed requires labels")
        y = labels.array if isinstance(labels, Dataset) else labels
        if isinstance(data, Dataset):
            if data.is_host and is_scipy_sparse_rows(data.items):
                sp = BucketedSparseRows.from_scipy_rows(data.items)
                n = data.n
            else:
                return super().fit_checkpointed(
                    data, labels, checkpoint_dir, checkpoint_every
                )
        else:
            sp = data
        return self.fit_sparse(
            sp,
            y,
            n=n,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
        )


def _sparse_vag(data, w, *, d: int, intercept: bool):
    """The ONE sparse least-squares objective body, shared verbatim by
    the single-scan jitted solver and the checkpointed chunked driver —
    a fix applied to one path cannot silently miss the other.

    ``data = (bidx, bvals, by, n, lam)``: the model (d, k) is
    replicated; per-iteration work is a row-sharded gather-matvec
    forward and a scatter-add gradient per bucket, all-reduced over the
    mesh — the sparse analogue of the dense path's einsum + psum.
    Bucket padding rows carry value-0 entries and zero labels, so they
    contribute nothing.  With ``intercept``, the last weight row is the
    unregularized bias of the constant column (excluded from the L2
    penalty)."""
    from keystone_tpu.ops.sparse import sparse_grad, sparse_matmul

    bidx, bvals, by, n, lam = data
    bidx = tuple(constrain(i, DATA_AXIS) for i in bidx)
    bvals = tuple(constrain(v, DATA_AXIS) for v in bvals)
    by = tuple(constrain(y, DATA_AXIS) for y in by)
    if intercept:
        reg = jnp.ones((d, 1), jnp.float32).at[d - 1].set(0.0)
    else:
        reg = jnp.ones((d, 1), jnp.float32)
    wp = w * reg
    f = 0.5 * lam * jnp.vdot(wp, wp)
    g = lam * wp
    for idx, vals, y in zip(bidx, bvals, by):
        r = sparse_matmul(idx, vals, w) - y  # (rows_b, k), row-sharded
        f = f + 0.5 * jnp.vdot(r, r) / n
        g = g + constrain(sparse_grad(idx, vals, r, d)) / n
    return f, g


@partial(
    jax.jit,
    static_argnames=("d", "num_iterations", "history", "intercept", "obs"),
)
def _lbfgs_sparse_least_squares(
    bidx, bvals, by, n, d, lam, num_iterations, history, intercept=False,
    obs=False,
):
    """Single-XLA-program sparse L-BFGS (objective: :func:`_sparse_vag`)."""
    k = by[0].shape[1]
    data = (bidx, bvals, by, n, lam)
    w0 = jnp.zeros((d, k), jnp.float32)
    return lbfgs_minimize(
        lambda w: _sparse_vag(data, w, d=d, intercept=intercept),
        w0,
        max_iter=num_iterations,
        history=history,
        obs_label="lbfgs.sparse" if obs else None,
    )


def _lbfgs_sparse_checkpointed(
    bidx,
    bvals,
    by,
    n,
    d,
    lam,
    num_iterations,
    history,
    intercept,
    checkpoint_dir,
    checkpoint_every,
):
    """Sparse L-BFGS via the resumable chunked driver.  Same math as
    :func:`_lbfgs_sparse_least_squares` (the vag body is identical);
    only the scan is cut into checkpointable chunks."""
    import hashlib

    import numpy as np

    k = by[0].shape[1]
    fp = hashlib.sha256()
    fp.update(
        repr(
            (
                tuple(np.shape(i) for i in bidx),
                tuple(np.shape(yy) for yy in by),
                int(d),
                float(lam),
                float(n),
                bool(intercept),
                int(history),
                "sparse-v1",
            )
        ).encode()
    )
    # first rows of the first bucket pin the data identity.
    # gather_to_host, not np.asarray: bucket values/labels are
    # mesh-sharded and a row's shard may be non-addressable locally
    from keystone_tpu.parallel import multihost as _mh

    fp.update(_mh.gather_to_host(bidx[0][:1]).tobytes())
    fp.update(_mh.gather_to_host(bvals[0][:1]).tobytes())
    fp.update(_mh.gather_to_host(by[0][:1]).tobytes())
    load_cb, save_cb = _lbfgs_checkpoint_callbacks(
        checkpoint_dir, fp.hexdigest(), "sparse", d * k, history
    )
    return lbfgs_minimize_resumable(
        partial(_sparse_vag, d=d, intercept=intercept),
        (
            tuple(bidx),
            tuple(bvals),
            tuple(by),
            jnp.float32(n),
            jnp.float32(lam),
        ),
        jnp.zeros((d, k), jnp.float32),
        max_iter=num_iterations,
        history=history,
        checkpoint_every=checkpoint_every,
        save_cb=save_cb,
        load_cb=load_cb,
    )


@partial(jax.jit, static_argnames=("fit_intercept",))
def _lbfgs_center(x, y, n, fit_intercept):
    """The intercept centering of :func:`_lbfgs_least_squares`, split out
    so the checkpointed driver can run it once ahead of the chunks."""
    if fit_intercept:
        xm = jnp.sum(x, axis=0) / n
        ym = jnp.sum(y, axis=0) / n
        row_ok = (jnp.arange(x.shape[0]) < n).astype(jnp.float32)[:, None]
        return (x - xm) * row_ok, (y - ym) * row_ok, xm, ym
    return (
        x,
        y,
        jnp.zeros((x.shape[1],), jnp.float32),
        jnp.zeros((y.shape[1],), jnp.float32),
    )


def _lbfgs_dense_checkpointed(
    x,
    y,
    n,
    lam,
    num_iterations,
    history,
    fit_intercept,
    checkpoint_dir,
    checkpoint_every,
):
    """Dense L-BFGS via the resumable chunked driver (same math as
    :func:`_lbfgs_least_squares`)."""
    import hashlib

    from keystone_tpu.parallel import multihost as _mh

    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    xc, yc, xm, ym = _lbfgs_center(x, y, jnp.float32(n), bool(fit_intercept))
    d, k = x.shape[1], y.shape[1]
    fp = hashlib.sha256()
    fp.update(
        repr(
            (
                tuple(x.shape),
                tuple(y.shape),
                float(lam),
                int(n),
                bool(fit_intercept),
                int(history),
                "dense-v1",
            )
        ).encode()
    )
    # gather_to_host, not np.asarray: rows may be sharded across
    # processes and a row's shard non-addressable locally
    fp.update(_mh.gather_to_host(x[:1]).tobytes())
    fp.update(_mh.gather_to_host(y[:1]).tobytes())
    load_cb, save_cb = _lbfgs_checkpoint_callbacks(
        checkpoint_dir, fp.hexdigest(), "dense", d * k, history
    )
    w = lbfgs_minimize_resumable(
        _dense_vag,
        (xc, yc, jnp.float32(n), jnp.float32(lam)),
        jnp.zeros((d, k), jnp.float32),
        max_iter=num_iterations,
        history=history,
        checkpoint_every=checkpoint_every,
        save_cb=save_cb,
        load_cb=load_cb,
    )
    b = (
        ym - xm @ w
        if fit_intercept
        else jnp.zeros((y.shape[1],), jnp.float32)
    )
    return w, b


def _dense_vag(data, w):
    """The ONE dense least-squares objective body, shared by the
    single-scan jitted solver and the checkpointed chunked driver.
    ``data = (xc, yc, n, lam)`` with xc/yc pre-centered (pad rows
    zero)."""
    xc, yc, n, lam = data
    xc = constrain(xc, DATA_AXIS)
    yc = constrain(yc, DATA_AXIS)
    r = xc @ w - yc  # (n_rows, k), row-sharded; pad rows are zero
    f = 0.5 * jnp.vdot(r, r) / n + 0.5 * lam * jnp.vdot(w, w)
    g = constrain(sdot(xc.T, r)) / n + lam * w
    return f, g


@partial(
    jax.jit,
    static_argnames=("num_iterations", "history", "fit_intercept", "obs"),
)
def _lbfgs_least_squares(
    x, y, n, lam, num_iterations, history, fit_intercept, obs=False
):
    xc, yc, xm, ym = _lbfgs_center.__wrapped__(x, y, n, fit_intercept)
    data = (xc, yc, n, lam)
    w0 = jnp.zeros((x.shape[1], y.shape[1]), jnp.float32)
    w = lbfgs_minimize(
        lambda w_: _dense_vag(data, w_),
        w0,
        max_iter=num_iterations,
        history=history,
        obs_label="lbfgs.dense" if obs else None,
    )
    b = ym - xm @ w if fit_intercept else jnp.zeros((y.shape[1],), jnp.float32)
    return w, b
