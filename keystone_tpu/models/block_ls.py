"""Block coordinate descent ridge regression — the north-star solver.

Reference: nodes/learning/BlockLeastSquares.scala §
BlockLeastSquaresEstimator and BlockLinearMapper.scala: features are split
into fixed-size blocks (VectorSplitter); each epoch sweeps the blocks
Gauss–Seidel style — recompute the residual, form the block's normal
equations via per-partition gemm + treeReduce, solve on the driver with
Cholesky + λI, broadcast.  This is how d≈200k-dim Fisher-vector models
fit in memory.

TPU design: the entire multi-epoch sweep is ONE jitted
``lax.scan``-over-epochs of a ``lax.fori_loop``-over-blocks program.

  - X is laid out pre-blocked as (num_blocks, n, block_size), rows sharded
    over the mesh 'data' axis.  Block Gramians contract over rows → XLA
    all-reduce over ICI (the treeReduce).
  - The running prediction P = Σ_b X_b W_b (n, k) stays row-sharded; the
    class axis k is sharded over 'model', so the per-block multi-class
    solve is itself tensor-parallel (the reference's driver solve,
    eliminated).
  - Weights (num_blocks, block_size, k) are replicated over 'data'
    (broadcast analogue) and sharded over 'model' on k.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from keystone_tpu.models.common import constrain, solve_spd
from keystone_tpu.parallel.collectives import (
    gram_panels,
    sharded_gram,
    sharded_matmul,
)
from jax.sharding import PartitionSpec as P
from keystone_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from keystone_tpu.workflow.dataset import Dataset
from keystone_tpu.workflow.estimator import LabelEstimator
from keystone_tpu.workflow.transformer import Transformer


def blockify(x: jnp.ndarray, block_size: int):
    """(n, d) -> (num_blocks, n, block_size), zero-padding d if needed
    (the VectorSplitter analogue, nodes/util/VectorSplitter.scala)."""
    n, d = x.shape
    nb = -(-d // block_size)
    if nb * block_size != d:
        x = jnp.pad(x, ((0, 0), (0, nb * block_size - d)))
    return x.reshape(n, nb, block_size).transpose(1, 0, 2)


class BlockLinearMapper(Transformer):
    """Applies per-block weights and sums partial predictions
    (nodes/learning/BlockLinearMapper.scala).  ``weights`` is
    (num_blocks, block_size, k)."""

    traced_attrs = ("weights", "intercept", "feature_mean")

    def jit_static(self):
        return (self.block_size,)

    def __init__(
        self,
        weights: jnp.ndarray,
        block_size: int,
        intercept: Optional[jnp.ndarray] = None,
        feature_mean: Optional[jnp.ndarray] = None,
    ):
        self.weights = weights
        self.block_size = int(block_size)
        self.intercept = intercept
        self.feature_mean = feature_mean

    @property
    def flat_weights(self) -> jnp.ndarray:
        nb, bs, k = self.weights.shape
        return self.weights.reshape(nb * bs, k)

    def apply_batch(self, xs, mask=None):
        from keystone_tpu.utils import precision

        return _block_predict(
            xs,
            self.weights,
            self.intercept,
            self.feature_mean,
            mxu=precision.apply_mode(),
        )

    def apply_one(self, x):
        return self.apply_batch(x[None])[0]

    def apply_and_evaluate(self, xs, eval_fn):
        """Stream per-block partial prediction sums to an eval callback
        (BlockLinearMapper.applyAndEvaluate) — used to watch convergence
        per block without materializing all partials."""
        xb = blockify(jnp.asarray(xs), self.block_size)
        acc = jnp.zeros((xs.shape[0], self.weights.shape[-1]), jnp.float32)
        results = []
        for b in range(self.weights.shape[0]):
            acc = acc + xb[b] @ self.weights[b]
            out = acc
            if self.feature_mean is not None or self.intercept is not None:
                out = acc + _offset(self.weights, self.feature_mean, self.intercept)
            results.append(eval_fn(out))
        return results


def _offset(weights, feature_mean, intercept):
    off = 0.0
    if feature_mean is not None:
        nb, bs, k = weights.shape
        pad = nb * bs - feature_mean.shape[0]
        if pad > 0:  # mean given at true d; weights are block-padded
            feature_mean = jnp.pad(feature_mean, (0, pad))
        off = off - feature_mean @ weights.reshape(nb * bs, k)
    if intercept is not None:
        off = off + intercept
    return off


@partial(jax.jit, static_argnames=("mxu",))
def _block_predict(xs, weights, intercept, feature_mean, mxu: str = "f32"):
    # Blocks are contiguous column ranges (blockify), so summing per-block
    # partials equals ONE flat matmul against the concatenated weights.
    # The blocked einsum compiled to a scan of dynamic-sliced weight reads
    # (async slice-copies dominated the scoring stage in device traces);
    # the flat dot streams the weights once, straight into the MXU.
    # Scoring (not solving), so the flat dot is under the apply precision
    # policy: 'bf16_apply' halves the (d × k) weight stream — at the
    # headline shape that is 32768×1000 f32 read per batch — with f32
    # accumulation; inert modes keep the exact pre-policy dot.
    xs = xs.astype(jnp.float32)
    nb, bs, k = weights.shape
    d = xs.shape[-1]
    if nb * bs != d:
        xs = jnp.pad(xs, ((0, 0), (0, nb * bs - d)))
    from keystone_tpu.utils import precision

    out = precision.apply_dot(xs, weights.reshape(nb * bs, k), mode=mxu)
    out = out + _offset(weights, feature_mean, intercept)
    return out


class BlockLeastSquaresEstimator(LabelEstimator):
    """Gauss–Seidel block coordinate descent ridge
    (nodes/learning/BlockLeastSquares.scala § BlockLeastSquaresEstimator).

    Math per (epoch, block):  W_b ← (X_bᵀX_b + nλI)⁻¹ X_bᵀ(Y − P + X_bW_b)
    where P = Σ_b X_b W_b is the running prediction.
    """

    # class-level default for pre-spill_dtype pickles
    spill_dtype = "float32"

    def __init__(
        self,
        block_size: int = 4096,
        num_iter: int = 1,
        lam: float = 0.0,
        fit_intercept: bool = True,
        spill_dtype: str = "float32",
    ):
        self.block_size = int(block_size)
        self.num_iter = int(num_iter)
        self.lam = float(lam)
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
            self.fit_intercept,
            self.spill_dtype,
        )

    def fit_dataset(self, data: Dataset, labels: Optional[Dataset] = None):
        if labels is None:
            raise ValueError("BlockLeastSquaresEstimator requires labels")
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
        """Out-of-core fit: spill the streamed features to a block store
        once, then sweep blocks from disk (the default path when a
        StreamDataset reaches this estimator through the DAG).

        ``prefetch`` — block read-ahead depth for the sweep (None →
        ``KEYSTONE_OC_PREFETCH`` env, else 2; see :func:`_oc_prefetch`).

        The spill directory is deleted after a successful fit; on failure
        it is left behind for inspection (a later retry re-spills, and
        checkpoint fingerprints are content-based so resume still works)."""
        import shutil

        from keystone_tpu.obs import ledger
        from keystone_tpu.workflow.blockstore import FeatureBlockStore

        with ledger.span("solver.spill", solver="bcd", n=data.n):
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
        """Fit from an existing FeatureBlockStore (features never fully
        resident in HBM; see _oc_bcd_fit).  ``prefetch`` as in
        :meth:`fit_stream_dataset`.

        Multi-process: ``store`` holds this process's row slice,
        ``labels`` is the GLOBAL label Dataset (made via
        ``multihost.make_global_dataset``); n checks and weighting use
        the global row count."""
        from keystone_tpu.workflow.dataset import as_dataset

        labels = as_dataset(labels)
        _check_store_rows(store, labels)
        y = labels.array.astype(jnp.float32)
        alpha = (jnp.arange(y.shape[0]) < labels.n).astype(jnp.float32)
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

        x = x.astype(jnp.float32)
        y = y.astype(jnp.float32)
        # the solver program holds its input and ONE centred block of it
        # (no centred copy, no blocked copy: see _bcd_fit)
        with ledger.span(
            "solver.fit", solver="bcd", n=int(n),
            blocks=-(-x.shape[1] // self.block_size),
            gram_panels=gram_panels(self.block_size),
            d=int(x.shape[1]), block_size=self.block_size,
            held_bytes=4 * x.shape[0] * (x.shape[1] + self.block_size),
        ):
            weights, xm, ym = _bcd_fit(
                x, y, jnp.float32(n), self.lam, self.num_iter, self.block_size,
                self.fit_intercept, obs=ledger.solver_obs(),
            )
        return finish_block_model(
            weights, xm, ym, x.shape[1], self.block_size, self.fit_intercept
        )

    def fit_checkpointed(self, data, labels, checkpoint_dir: str, prefetch=None):
        """Fit with per-epoch state checkpointing and resume.

        The reference has no mid-solver checkpointing (models are only
        saveable after fit — SURVEY.md §5); this closes that gap: each
        epoch's (W, P) lands in ``checkpoint_dir/bcd_epoch.npz``, and an
        interrupted fit resumes from the last completed epoch.

        ``prefetch`` rides the signature for parity with
        :meth:`fit_store` / :meth:`fit_stream_dataset`: when a
        checkpointed fit is routed out-of-core (a StreamDataset source
        spilled to a block store) the depth reaches ``_oc_bcd_fit``; the
        in-memory path here stages no disk blocks, so it is unused.
        """
        from keystone_tpu.workflow.dataset import StreamDataset as _SD

        if isinstance(data, _SD):
            return self.fit_stream_dataset(
                data, labels, checkpoint_dir=checkpoint_dir, prefetch=prefetch
            )
        import os

        import numpy as np

        from keystone_tpu.workflow.dataset import Dataset, as_dataset

        data = as_dataset(data)
        labels = as_dataset(labels)
        x = data.array.astype(jnp.float32)
        y = labels.array.astype(jnp.float32)
        n = data.n
        nf = jnp.float32(n)
        if self.fit_intercept:
            xm = jnp.sum(x, axis=0) / nf
            ym = jnp.sum(y, axis=0) / nf
            row_ok = (jnp.arange(x.shape[0]) < n)[:, None].astype(jnp.float32)
            xc = (x - xm) * row_ok
            yc = (y - ym) * row_ok
        else:
            xm = ym = None
            xc, yc = x, y
        xb = blockify(xc, self.block_size)
        nb, _, bs = xb.shape
        k = yc.shape[1]

        os.makedirs(checkpoint_dir, exist_ok=True)
        path = os.path.join(checkpoint_dir, "bcd_epoch.npz")
        # fingerprint the problem: resuming a checkpoint from different
        # data/labels/λ would silently break the P = Σ X_b W_b invariant.
        # Hash probe ROWS of each process's addressable shard (order-
        # sensitive: permutation-invariant scalar moments would accept a
        # reshuffled dataset and resume a stale W/P pair) and allgather
        # the per-process digests so the fingerprint is identical on
        # every process.
        import hashlib

        from keystone_tpu.parallel.multihost import gather_to_host, global_from_host

        def _probe_digest(*arrays) -> int:
            h = hashlib.sha256()
            for a in arrays:
                shards = getattr(a, "addressable_shards", None)
                # one-off pre-fit fingerprint read, not sweep-path
                loc = np.asarray(shards[0].data) if shards else np.asarray(a)  # lint: allow-host-sync
                h.update(loc[0].tobytes())
                h.update(loc[-1].tobytes())
            return int.from_bytes(h.digest()[:8], "little")

        local_digest = np.asarray([_probe_digest(x, y)], np.uint64)
        digests = tuple(gather_to_host(local_digest).ravel().tolist())
        fp = hashlib.sha256()
        fp.update(
            repr(
                (
                    x.shape,
                    y.shape,
                    int(n),
                    self.lam,
                    self.block_size,
                    bool(self.fit_intercept),
                    digests,
                )
            ).encode()
        )
        problem = fp.hexdigest()

        from keystone_tpu.utils import durable

        def _read_checkpoint():
            """(resume_epoch+1, w_host, p_host) or (0, zeros, zeros).
            durable.load_npz scans newest→last-good: a corrupt newest
            epoch checkpoint resumes from the previous epoch, not from
            scratch."""
            w0 = np.zeros((nb, bs, k), np.float32)
            p0 = np.zeros(yc.shape, np.float32)
            loaded = durable.load_npz(
                path,
                validate=lambda z: str(z.get("problem")) == problem
                and z["w"].shape == w0.shape
                and z["p"].shape == p0.shape,
            )
            if loaded is None:
                return 0, w0, p0
            z, _ = loaded
            return int(z["epoch"]) + 1, z["w"], z["p"]

        if jax.process_count() > 1:
            # processes must enter the epoch loop at the SAME iteration
            # (every sweep runs collectives): process 0's checkpoint
            # decision is broadcast, never decided per-process — a silent
            # local read failure would desynchronize and deadlock
            from jax.experimental import multihost_utils

            if jax.process_index() == 0:
                start, w_h, p_h = _read_checkpoint()
            else:
                start = 0
                w_h = np.zeros((nb, bs, k), np.float32)
                p_h = np.zeros(yc.shape, np.float32)
            start, w_h, p_h = multihost_utils.broadcast_one_to_all(
                (np.int32(start), np.asarray(w_h), np.asarray(p_h))
            )
            start = int(start)
        else:
            start, w_h, p_h = _read_checkpoint()

        w = jnp.zeros((nb, bs, k), jnp.float32)
        p = jnp.zeros_like(yc)
        if start > 0:
            # restore with mesh-wide shardings (w replicated, p like the
            # labels) — the host copies exist on every process
            mesh = getattr(yc.sharding, "mesh", None)
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                w_sharding = NamedSharding(mesh, PartitionSpec())
            else:
                w_sharding = w.sharding
            w = global_from_host(w_h, w_sharding)
            p = global_from_host(p_h, yc.sharding)
        from keystone_tpu.obs import ledger, metrics

        observe = ledger.solver_obs()
        for e in range(start, self.num_iter):
            import time as _time

            # one sick host must abort ALL hosts at the epoch boundary
            # (SickHostError / DeadlineExceeded in bounded time) rather
            # than deadlock its peers inside the epoch's collectives.
            # Inert single-process / without KEYSTONE_HEALTH_TIMEOUT.
            from keystone_tpu.parallel.multihost import maybe_health_barrier

            maybe_health_barrier("bcd.checkpointed.epoch")
            t_epoch = _time.perf_counter()
            # donated carry: the old (w, p) buffers are consumed by the
            # epoch program and rebound to its outputs here
            w, p = _bcd_epoch(xb, yc, nf, self.lam, w, p)
            # required sync (the gathers below read w); metered as
            # device-busy either way
            ledger.device_wait(w)
            # the gathers are COLLECTIVES: every process must run them
            w_host = gather_to_host(w)
            p_host = gather_to_host(p)
            # … but only process 0 writes: rotation + sidecar are not
            # concurrent-writer-safe on a shared dir, and the resume
            # decision is read by process 0 alone anyway (broadcast).
            # durable.save_npz = atomic tmp+fsync+rename, BLAKE2b
            # sidecar, previous epoch rotated to <path>.1 — the
            # last-good fallback _read_checkpoint resumes from when the
            # newest save is later found corrupt
            t_save = _time.perf_counter()
            if jax.process_index() == 0:
                durable.save_npz(
                    path,
                    {
                        # host scalars: savez coerces — no device read
                        "epoch": e,
                        "w": w_host,
                        "p": p_host,
                        "problem": problem,
                    },
                    keep=2,
                )
            save_seconds = _time.perf_counter() - t_save
            metrics.observe("solver.checkpoint_save_seconds", save_seconds)
            if observe:
                ledger.solver_epoch(
                    "bcd.checkpointed",
                    epoch=e,
                    objective=float(np.asarray(_bcd_objective(yc, p, nf))),  # lint: allow-host-sync
                    epoch_seconds=_time.perf_counter() - t_epoch,
                    checkpoint_save_seconds=save_seconds,
                )
        return finish_block_model(
            w, xm, ym, x.shape[1], self.block_size, self.fit_intercept
        )


def finish_block_model(weights, xm, ym, d, block_size, fit_intercept):
    """Wrap fitted block weights into a BlockLinearMapper, computing the
    intercept from the (weighted) means when centering was used."""
    nb, bs, k = weights.shape
    if not fit_intercept:
        return BlockLinearMapper(weights, block_size)
    wflat = weights.reshape(nb * bs, k)[:d]
    intercept = ym - xm[:d] @ wflat
    pad = nb * bs - d
    return BlockLinearMapper(
        jnp.pad(wflat, ((0, pad), (0, 0))).reshape(nb, bs, k),
        block_size,
        intercept=intercept,
    )


# --------------------------------------------------------------------------
# Out-of-core block coordinate descent (features streamed from disk).
#
# The reference fits d≈200k-dim models by re-reading cached feature-block
# RDDs per (epoch, block) (nodes/learning/BlockLeastSquares.scala,
# SURVEY.md §3.2).  TPU analogue: blocks live in a FeatureBlockStore on
# host disk; HBM holds ONE (n × bs) staged block, the (n × k) residual P,
# labels, and the per-block weights — so the feature matrix can exceed
# device memory arbitrarily.  Disk reads prefetch on a worker thread and
# overlap the async-dispatched device step.
#
# One implementation serves both solvers: the unweighted case is the
# weighted case with α_i = 1 on valid rows (class_weights with
# mixture_weight=0), so `_oc_bcd_fit` is shared and the weighted math is
# exactly block_weighted_ls._weighted_bcd_fit's.
# --------------------------------------------------------------------------


@jax.jit
def _oc_wmean(alpha, a, wsum):
    return (alpha @ a) / wsum


@jax.jit
def _bcd_objective(yc, p, n):
    """Residual objective 0.5·‖Y−P‖²/n of a BCD carry — one tiny jitted
    reduction so obs-enabled host loops never pull the (n × k) residual
    to host just to norm it (sharded inputs reduce via collectives)."""
    r = yc - p
    return 0.5 * jnp.vdot(r, r) / n


@partial(jax.jit, donate_argnums=(5, 6))
def _oc_block_step(a_raw, xm_b, yc, sa, row_ok, p, wb, lam_n):
    """One out-of-core BCD block update (compiled once, reused for every
    (epoch, block) step — all blocks share one shape by construction).

    The carried state ``p``/``wb`` is DONATED (aliased onto the step's
    ``p_new``/``wb_new`` outputs): step N's residual and weights land in
    step N−1's HBM instead of allocating fresh — in the out-of-core
    regime HBM headroom is what bounds the block size, and without
    donation each step transiently holds two (n × k) residuals.  The
    staged block is NOT donated (no same-shape output to alias; its
    buffer frees by refcount when the loop drops it).  Callers must not
    touch a donated input after the call.

    The third output is a (1, 1) ``tick`` slice of the new weights:
    both real outputs are donated into LATER steps (p next step, wb next
    epoch), so neither can be waited on for flow control — the tick is
    never donated and gives the sweep a compute-completion handle to
    ``block_until_ready`` two steps behind, bounding how far the async
    dispatch queue (and the staged blocks its pending executions pin in
    HBM) can run ahead of the device."""
    a0 = (a_raw - xm_b) * row_ok[:, None]  # centered, padding re-zeroed
    a0 = constrain(a0, DATA_AXIS, None)
    a = a0 * sa[:, None]
    target = (yc - p) * sa[:, None] + a @ wb
    ata = sharded_gram(a)
    atr = sharded_matmul(a, target, out_spec=P(None, MODEL_AXIS))
    wb_new = solve_spd(ata, atr, reg=lam_n)
    p_new = constrain(p + a0 @ (wb_new - wb), DATA_AXIS, MODEL_AXIS)
    return wb_new, p_new, wb_new[:1, :1]


#: upper bound on the env-supplied read-ahead depth.  Each slot pins one
#: (n × block_size) host block, so an absurd depth (a stray
#: KEYSTONE_OC_PREFETCH=100000 in a job template) is an OOM sentence,
#: not a tuning choice — reject it up front.
_OC_PREFETCH_MAX = 64


def _oc_prefetch(explicit=None) -> int:
    """Resolved read-ahead depth for out-of-core block staging: the
    explicit caller value wins, else the ``KEYSTONE_OC_PREFETCH`` env
    override, else 2 (the measured default — one block transferring
    while one computes).  Deeper prefetch buys overlap on slow disks at
    the cost of pinned host memory: each slot holds an (n × block_size)
    f32/bf16 host block.

    The value is VALIDATED, not best-effort-coerced — on BOTH entry
    points (the same ``[1, _OC_PREFETCH_MAX]`` bound applies to the
    ``prefetch=`` fit argument and the env var): a non-integer or
    out-of-range depth raises ``ValueError`` naming its source — a
    silently-ignored typo ("KEYSTONE_OC_PREFETCH=eight") used to run
    the whole fit at the default depth while the operator believed the
    tuning was in effect."""
    import os

    if explicit is not None:
        return _check_prefetch_depth(int(explicit), "prefetch")
    raw = os.environ.get("KEYSTONE_OC_PREFETCH")
    if raw is None or raw == "":
        return 2
    try:
        depth = int(raw)
    except ValueError:
        raise ValueError(
            f"KEYSTONE_OC_PREFETCH={raw!r} is not an integer; expected a "
            f"block read-ahead depth in [1, {_OC_PREFETCH_MAX}]"
        ) from None
    return _check_prefetch_depth(depth, "KEYSTONE_OC_PREFETCH")


def _check_prefetch_depth(depth: int, source: str) -> int:
    if not 1 <= depth <= _OC_PREFETCH_MAX:
        raise ValueError(
            f"{source}={depth} is outside [1, {_OC_PREFETCH_MAX}]: each "
            "prefetch slot pins one (n × block_size) host block, so the "
            "depth must be a small positive integer"
        )
    return depth


def _check_store_rows(store, labels) -> None:
    """Single-process: store rows == label rows.  Multi-process: the
    per-process slices must jointly cover the global labels."""
    import jax

    procs = jax.process_count()
    if procs == 1:
        if labels.n != store.n:
            raise ValueError(f"labels n={labels.n} != store n={store.n}")
    elif store.n * procs < labels.n:
        raise ValueError(
            f"{procs} per-process stores of {store.n} rows cannot cover "
            f"{labels.n} global label rows"
        )


def _oc_bcd_fit(
    store,
    y,
    alpha,
    n,
    lam,
    num_iter,
    fit_intercept,
    checkpoint_dir=None,
    prefetch=None,
):
    """Stream feature blocks from ``store`` through BCD sweeps.

    ``y``: (n_rows, k) device labels, row-sharded; ``alpha``: (n_rows,)
    per-example weights with zeros on padding rows; ``prefetch``: block
    read-ahead depth (None → :func:`_oc_prefetch` resolution).  Returns
    ``(weights (nb, bs, k), xm (nb*bs,), ym (k,))``.

    Multi-process (pod) runs: ``store`` holds only THIS process's row
    slice on local disk (equal slices per host, the
    ``multihost.process_batch_slice`` convention) and blocks are staged
    as global row-sharded arrays via
    ``multihost.global_rows_from_local`` — no host ever materializes
    the full matrix, matching the reference's per-executor spilled
    feature partitions.

    With ``checkpoint_dir``, each completed epoch saves (epoch, W, P) and
    an interrupted fit resumes from the last epoch (fault-tolerance
    analogue of Spark lineage recompute, SURVEY.md §5).
    """
    import os

    import numpy as np


    from keystone_tpu.parallel import multihost as _mh

    nb, bs = store.num_blocks, store.block_size
    n_rows, k = y.shape
    prefetch = _oc_prefetch(prefetch)
    wsum = jnp.sum(alpha)
    sa = jnp.sqrt(alpha)
    row_ok = (alpha > 0).astype(jnp.float32)

    # Row-count validation, ONCE, against store metadata — every block
    # stages to the same padded shape by construction, so re-checking
    # inside the hot loop re-raised the identical comparison nb×num_iter
    # times per fit.  A 1-column probe resolves the mesh/process padding
    # without reading any feature block from disk.
    probe = _mh.global_rows_from_local(np.zeros((store.n, 1), np.float32))
    if probe.shape[0] != n_rows:
        raise ValueError(
            f"store rows pad to {probe.shape[0]} but labels have {n_rows}: "
            "store.n must equal the label Dataset's n (per-process "
            "row slice in multi-process runs)"
        )
    del probe

    def stage(blk):
        a = _mh.global_rows_from_local(blk)
        # bf16 stores cross the host→device wire at half width; solver
        # math stays f32 — cast on DEVICE, after the transfer
        if a.dtype != jnp.float32:
            a = a.astype(jnp.float32)
        return a

    import time as _time

    from keystone_tpu.obs import ledger, metrics

    def _ready(x):
        # compute backpressure: block until a step output from two
        # iterations back is READY (no device read, no host copy) so the
        # dispatch queue — and the staged blocks its pending executions
        # pin in HBM — never runs more than 2 steps ahead.  The staging
        # window only bounds in-flight TRANSFERS; transfers are not
        # ordered behind compute, so without this the Python loop races
        # the whole sweep into the queue.  The wait is device-busy time.
        ledger.device_wait(x)

    if fit_intercept:
        # double-buffered device feed: block b+1's host→device transfer
        # overlaps block b's weighted-mean reduction, and the bounded
        # staging window replaces the per-block real device read this
        # loop used to carry as backpressure
        xm_rows = []
        for _, a in store.iter_device_blocks(
            range(nb), prefetch=prefetch, stage=stage
        ):
            xm_rows.append(_oc_wmean(alpha, a, wsum))
            if len(xm_rows) > 2:
                _ready(xm_rows[-3])
        xm = jnp.stack(xm_rows)  # (nb, bs)
        ym = _oc_wmean(alpha, y, wsum)
    else:
        xm = jnp.zeros((nb, bs), jnp.float32)
        ym = jnp.zeros((k,), jnp.float32)
    yc = (y - ym) * row_ok[:, None]

    w = [jnp.zeros((bs, k), jnp.float32) for _ in range(nb)]
    p = jnp.zeros_like(yc)
    start = 0

    ckpt_path = problem = None
    if checkpoint_dir is not None:
        import hashlib

        os.makedirs(checkpoint_dir, exist_ok=True)
        ckpt_path = os.path.join(checkpoint_dir, "oc_bcd_epoch.npz")
        # Content-based problem fingerprint: resuming with different data,
        # labels, weights (mixture), λ, or intercept setting must restart,
        # while a re-spill of IDENTICAL data to a new temp dir must still
        # resume — so hash content proxies, never the directory path.
        # Per-process-sharded stores hold DIFFERENT rows, so the local
        # store probe is allgathered (like fit_checkpointed's digests) —
        # every process must compute the SAME fingerprint or a shared-dir
        # checkpoint could only ever match on one of them.
        local_probe = np.frombuffer(
            hashlib.sha256(
                np.asarray(store.read_block(0)[0]).tobytes()
            ).digest()[:8],
            np.uint64,
        )
        probes = tuple(_mh.gather_to_host(local_probe).ravel().tolist())
        fp = hashlib.sha256()
        fp.update(
            repr(
                (
                    store.n,
                    store.d,
                    bs,
                    (n_rows, k),
                    float(lam),
                    n,
                    bool(fit_intercept),
                    probes,
                )
            ).encode()
        )
        # gather_to_host, not np.asarray: y/alpha rows are sharded and
        # a row's shard may be non-addressable from this process
        fp.update(_mh.gather_to_host(y[:1]).tobytes())
        fp.update(_mh.gather_to_host(alpha[: min(n_rows, 64)]).tobytes())
        problem = fp.hexdigest()

        from keystone_tpu.utils import durable

        def _read_oc_checkpoint():
            # newest→last-good scan (utils/durable): a corrupt newest
            # epoch falls back to the previous one instead of a scratch fit
            loaded = durable.load_npz(
                ckpt_path,
                validate=lambda z: str(z.get("problem")) == problem
                and z["w"].shape == (nb, bs, k),
            )
            if loaded is None:
                return 0, None, None
            z, _ = loaded
            return int(z["epoch"]) + 1, np.asarray(z["w"]), np.asarray(z["p"])

        if jax.process_count() > 1:
            # every sweep runs collectives, so processes must enter the
            # loop at the SAME iteration: process 0's resume decision is
            # broadcast, never decided per-process — a silent local read
            # failure would desynchronize and deadlock
            from jax.experimental import multihost_utils

            if jax.process_index() == 0:
                start, w_h, p_h = _read_oc_checkpoint()
            else:
                start, w_h, p_h = 0, None, None
            if w_h is None:
                w_h = np.zeros((nb, bs, k), np.float32)
                p_h = np.zeros(yc.shape, np.float32)
                start = int(start)
            start, w_h, p_h = multihost_utils.broadcast_one_to_all(
                (np.int32(start), np.asarray(w_h), np.asarray(p_h))
            )
            start = int(start)
            if start > 0:
                w = [jnp.asarray(w_h[b]) for b in range(nb)]
                p = _mh.global_from_host(p_h[: yc.shape[0]], yc.sharding)
        else:
            start, w_h, p_h = _read_oc_checkpoint()
            if start > 0:
                w = [jnp.asarray(w_h[b]) for b in range(nb)]
                p = _mh.global_from_host(
                    p_h[: yc.shape[0]], yc.sharding
                )

    lam_n = jnp.float32(lam * n)
    order = [b for _ in range(start, num_iter) for b in range(nb)]
    epoch = start
    # Dataflow: iter_device_blocks dispatches block b+1's host→device
    # transfer while block b computes, waiting (block_until_ready, no
    # device READ) on the transfer of the block two behind before
    # yielding — so staged HOST buffers stay bounded.  The step donates
    # only the carried p and w[b] (epoch N's state reuses epoch N−1's
    # HBM; the staged block itself is NOT donated — it frees by
    # refcount).  Compute flow control is separate: a ready-wait on the
    # step's non-donated tick output from two steps back (see _ready),
    # replacing the real 4-byte device read the loop used to carry.
    from collections import deque

    observe = ledger.solver_obs()
    t_epoch = _time.perf_counter()
    pending: deque = deque()
    for i, (b, a) in enumerate(
        store.iter_device_blocks(order, prefetch=prefetch, stage=stage)
    ):
        w[b], p, tick = _oc_block_step(
            a, xm[b], yc, sa, row_ok, p, w[b], lam_n
        )
        pending.append(tick)
        if len(pending) > 2:
            _ready(pending.popleft())
        if (i + 1) % nb == 0:
            # epoch boundary: abort collectively if a peer host went
            # sick mid-sweep (see fit_checkpointed's barrier) — the
            # checkpoint gathers below are collectives every process
            # must enter, and a dead peer would park them forever
            _mh.maybe_health_barrier("oc_bcd.epoch")
            save_seconds = None
            if ckpt_path is not None:
                # required sync (the gathers below read p); metered as
                # device-busy either way
                ledger.device_wait(p)
                # collectives first (every process participates) …
                w_host = np.stack([_mh.gather_to_host(x) for x in w])
                p_host = _mh.gather_to_host(p)
                # … then ONE writer: rotation + sidecar are not
                # concurrent-writer-safe, and resume reads are process-0
                # + broadcast anyway.  durable.save_npz = atomic
                # tmp+fsync+rename + checksum sidecar + previous epoch
                # rotated to <path>.1 (the resume scan's last-good
                # fallback)
                t_save = _time.perf_counter()
                if jax.process_index() == 0:
                    durable.save_npz(
                        ckpt_path,
                        {
                            # host scalars: savez coerces — no device read
                            "epoch": epoch,
                            "w": w_host,
                            "p": p_host,
                            "problem": problem,
                        },
                        keep=2,
                    )
                save_seconds = _time.perf_counter() - t_save
                metrics.observe("solver.checkpoint_save_seconds", save_seconds)
            if observe:
                # per-epoch objective is a real device read — charge the
                # wait to the device-busy account (obs-gated: the inert
                # sweep carries no sync at all)
                t_dev = _time.perf_counter()
                obj = float(np.asarray(_bcd_objective(yc, p, n)))  # lint: allow-host-sync
                metrics.observe(
                    "device.busy_seconds", _time.perf_counter() - t_dev
                )
                ledger.solver_epoch(
                    "bcd.out_of_core",
                    epoch=epoch,
                    objective=obj,
                    epoch_seconds=_time.perf_counter() - t_epoch,
                    checkpoint_save_seconds=save_seconds,
                )
            t_epoch = _time.perf_counter()
            epoch += 1
    return jnp.stack(w), xm.reshape(-1), ym


def _spill_dir(hint=None):
    """A fresh directory for spilled feature blocks: the explicit hint,
    else the PipelineEnv state dir, else the system temp dir."""
    import os
    import tempfile

    from keystone_tpu.workflow.pipeline import PipelineEnv

    base = hint or PipelineEnv.state_dir
    if base is not None:
        os.makedirs(base, exist_ok=True)
    return tempfile.mkdtemp(prefix="kst_spill_", dir=base)


def _bcd_block_step(a, wb, y, p, reg):
    """One Gauss–Seidel block update from the block's (centred) columns
    ``a`` (n_rows, bs): the new block weights and the new running
    prediction."""
    # residual with this block's contribution restored
    target = y - p + a @ wb
    # per-partition gemm + treeReduce == sharded contraction + psum
    ata = sharded_gram(a)
    atr = sharded_matmul(a, target, out_spec=P(None, MODEL_AXIS))
    wb_new = solve_spd(ata, atr, reg=reg)
    return wb_new, constrain(p + a @ (wb_new - wb), DATA_AXIS, MODEL_AXIS)


def _bcd_epoch_body(xb, y, n, lam, carry):
    """One Gauss–Seidel sweep over all blocks of a blocked matrix."""

    def block_step(b, carry):
        w, p = carry
        wb_new, p_new = _bcd_block_step(xb[b], w[b], y, p, lam * n)
        return w.at[b].set(wb_new), p_new

    return lax.fori_loop(0, xb.shape[0], block_step, carry)


@partial(jax.jit, donate_argnums=(4, 5))
def _bcd_epoch(xb, y, n, lam, w, p):
    """Single checkpointable epoch (used by fit_checkpointed's host
    loop).  The carried ``(w, p)`` is DONATED: epoch N's state lands in
    epoch N−1's HBM instead of doubling the live weight+residual
    footprint across every epoch boundary.  The caller's old bindings
    are invalid after the call (they are rebound to the outputs, and the
    checkpoint gathers read the NEW state)."""
    xb = constrain(xb, None, DATA_AXIS, None)
    y = constrain(y, DATA_AXIS, MODEL_AXIS)
    return _bcd_epoch_body(xb, y, n, lam, (w, p))


@partial(jax.jit, static_argnames=("num_iter", "block_size", "fit_intercept", "obs"))
def _bcd_fit(x, y, n, lam, num_iter, block_size, fit_intercept, obs=False):
    """The hot loop (SURVEY.md §3.2) as one XLA program, from the feature
    matrix AS IT ARRIVES: x (n_rows, d) row-sharded, y (n_rows, k), n the
    true row count.  Returns ``(weights (nb, bs, k), xm (d,), ym (k,))``.

    The program holds x and one block of it.  A block step slices its
    ``block_size`` columns out of x, centres them on their column means
    and zeroes the padding rows — there is no centred copy and no
    ``blockify`` copy of the matrix (at 16,384 × 80,000 each is 5.2 GB).
    Where ``block_size`` does not divide d the last block is the LAST
    ``block_size`` columns of x with the columns an earlier block owns
    zeroed (a zero column has a zero weight: its row of the cross term
    is zero), and its weights are rolled to the front once, after the
    sweeps, to the place ``BlockLinearMapper`` reads them from.

    ``obs`` (static): emit a per-epoch ``solver.epoch`` convergence
    point (residual objective) to the active run ledger via
    ``jax.debug.callback``.  Same math either way — the flag only adds
    the host callback, and is resolved at trace time so the inert
    program carries no callbacks at all.
    """
    bs = block_size
    n_rows, d = x.shape
    k = y.shape[1]
    nb = -(-d // bs)
    if d < bs:  # one narrow block: its zero columns sit behind it as it is
        x = jnp.pad(x, ((0, 0), (0, bs - d)))
    width = x.shape[1]
    x = constrain(x, DATA_AXIS, None)
    y = constrain(y, DATA_AXIS, MODEL_AXIS)
    row_ok = (jnp.arange(n_rows) < n)[:, None].astype(jnp.float32)
    if fit_intercept:
        xm = jnp.sum(x, axis=0) / n
        ym = jnp.sum(y, axis=0) / n
        # pad rows would centre to −mean and corrupt the Gramians
        y = (y - ym) * row_ok
    else:
        xm = jnp.zeros((width,), jnp.float32)
        ym = jnp.zeros((k,), jnp.float32)

    def block(b):
        lo = jnp.minimum(b * bs, width - bs)
        a = lax.dynamic_slice_in_dim(x, lo, bs, axis=1)
        if fit_intercept:
            a = (a - lax.dynamic_slice_in_dim(xm, lo, bs)) * row_ok
        own = lo + jnp.arange(bs) >= b * bs
        return constrain(jnp.where(own, a, 0.0), DATA_AXIS, None)

    def block_step(b, carry):
        w, p = carry
        wb_new, p_new = _bcd_block_step(block(b), w[b], y, p, lam * n)
        return w.at[b].set(wb_new), p_new

    def epoch(carry, e):
        carry = lax.fori_loop(0, nb, block_step, carry)
        if obs:
            from keystone_tpu.obs import ledger

            _, p = carry
            r = y - p
            jax.debug.callback(
                ledger.solver_callback("bcd", "epoch", "objective"),
                e,
                0.5 * jnp.vdot(r, r) / n,
            )
        return carry, None

    w0 = jnp.zeros((nb, bs, k), jnp.float32)
    p0 = jnp.zeros_like(y)
    # xs only when observing — the inert program stays byte-identical
    # to the pre-obs one (see models/kmeans.py)
    if obs:
        (w, _), _ = lax.scan(epoch, (w0, p0), jnp.arange(num_iter))
    else:
        (w, _), _ = lax.scan(epoch, (w0, p0), None, length=num_iter)
    behind = nb * bs - width  # columns of the last block that an earlier one owns
    if behind:
        w = w.at[nb - 1].set(jnp.roll(w[nb - 1], -behind, axis=0))
    return w, xm[:d], ym
