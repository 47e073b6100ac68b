"""TPU-native sparse feature representation: padded COO rows.

Reference: nodes/learning/LBFGS.scala § LeastSquaresSparseGradient — the
reference keeps CSR feature rows on executors and computes least-squares
gradients without ever densifying the n×d matrix (SURVEY.md §2.2).

The TPU analogue is pad-and-mask, the same strategy the framework uses
for ragged descriptor sets: each row carries up to ``nnz_max``
(index, value) pairs, padding entries have value 0.0 (index 0), so they
contribute nothing to either the forward gather-matvec or the gradient
scatter-add — no separate mask array is needed.  Memory is n·nnz·8 bytes
instead of n·d·4: at a 100k+ vocabulary and ~10² nonzeros per document
this is ~3 orders of magnitude smaller, which is what lets the text
pipelines run at realistic vocab sizes without densifying.

Shapes are static (nnz_max fixed at construction), so everything jits.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax.numpy as jnp

from keystone_tpu.parallel import mesh as _mesh


def is_scipy_sparse_rows(items) -> bool:
    """True for a non-empty sequence of scipy sparse row vectors."""
    return len(items) > 0 and all(
        hasattr(r, "tocoo") and hasattr(r, "shape") for r in items[:2]
    )


class PaddedSparseRows:
    """(n, nnz_max) int32 indices + float32 values + feature count.

    ``indices``/``values`` live on device, row-sharded over the mesh
    'data' axis like any Dataset array; rows past ``n`` and entries past
    a row's true nnz are value-0 padding.
    """

    def __init__(self, indices, values, num_features: int, n: Optional[int] = None,
                 shard: bool = True):
        self.n = int(np.shape(indices)[0] if n is None else n)
        self.num_features = int(num_features)
        if shard:
            self.indices = _mesh.shard_batch(np.asarray(indices, np.int32))
            self.values = _mesh.shard_batch(np.asarray(values, np.float32))
        else:
            self.indices = jnp.asarray(indices, jnp.int32)
            self.values = jnp.asarray(values, jnp.float32)

    @property
    def nnz_max(self) -> int:
        return int(self.indices.shape[1])

    @property
    def shape(self):
        return (self.n, self.num_features)

    @property
    def nbytes(self) -> int:
        return int(self.indices.size * 4 + self.values.size * 4)

    @staticmethod
    def from_scipy_rows(
        rows: Sequence, num_features: Optional[int] = None
    ) -> "PaddedSparseRows":
        """Build from scipy sparse row vectors (what ``Sparsify`` emits)."""
        coos = [r.tocoo() for r in rows]
        d = int(num_features if num_features is not None else coos[0].shape[-1])
        widths = {int(c.shape[-1]) for c in coos}
        if widths - {d}:
            # JAX's gather clamps out-of-range indices, so a
            # featurizer/weights width mismatch would silently mis-score;
            # fail loudly like the dense path's shape error instead.
            raise ValueError(
                f"sparse rows have width(s) {sorted(widths)} but "
                f"num_features={d}"
            )
        nnz_max = max(1, max((c.nnz for c in coos), default=1))
        n = len(coos)
        idx = np.zeros((n, nnz_max), np.int32)
        val = np.zeros((n, nnz_max), np.float32)
        for i, c in enumerate(coos):
            idx[i, : c.nnz] = c.col
            val[i, : c.nnz] = c.data
        return PaddedSparseRows(idx, val, d, n=n)

    @staticmethod
    def from_dense(x, threshold: float = 0.0) -> "PaddedSparseRows":
        x = np.asarray(x)
        mask = np.abs(x) > threshold
        nnz_max = max(1, int(mask.sum(axis=1).max()))
        n, d = x.shape
        idx = np.zeros((n, nnz_max), np.int32)
        val = np.zeros((n, nnz_max), np.float32)
        for i in range(n):
            cols = np.nonzero(mask[i])[0]
            idx[i, : cols.size] = cols
            val[i, : cols.size] = x[i, cols]
        return PaddedSparseRows(idx, val, d, n=n)

    def toarray(self) -> np.ndarray:
        """Dense (n, d) host copy (tests / small data only)."""
        idx = np.asarray(self.indices)[: self.n]
        val = np.asarray(self.values)[: self.n]
        out = np.zeros((self.n, self.num_features), np.float32)
        for i in range(self.n):
            np.add.at(out[i], idx[i], val[i])
        return out

    def matmul(self, w, intercept=None, mode: Optional[str] = None):
        """Gather-based ``X @ w`` without densifying: (n_rows, k).

        ``mode=None`` resolves the apply precision policy (this is the
        SCORING path — LinearMapper / logistic inference); solver
        callers contract through :func:`sparse_matmul` directly, whose
        default stays inert f32."""
        from keystone_tpu.utils import precision

        if mode is None:
            mode = precision.apply_mode()
        out = sparse_matmul(self.indices, self.values, jnp.asarray(w), mode=mode)
        if intercept is not None:
            out = out + intercept
        return out


# Row-chunked kernels: the forward gather and the gradient scatter both
# flow through a (rows, nnz, k) contribution tensor; at TIMIT-like k=147
# and 10²–10³ nnz that is GBs if materialized whole (round-2 review item 4).
# Chunking the row axis through lax.scan bounds the live intermediate at
# _CHUNK_BUDGET bytes regardless of (rows, nnz, k); XLA hoists the
# loop-invariant pad/reshape of the COO arrays out of optimizer loops.
_CHUNK_BUDGET = 64 << 20  # ≈100 MB working-set sweet spot, minus headroom


def _auto_chunk(rows: int, nnz: int, k: int) -> int:
    per_row = max(1, nnz * max(k, 1)) * 4
    c = max(128, _CHUNK_BUDGET // per_row)
    return 1 << int(np.floor(np.log2(c)))  # pow2 keeps compiled shapes few


def _chunk_coo(indices, values, chunk: int):
    rows = indices.shape[0]
    nc = -(-rows // chunk)
    pad = nc * chunk - rows
    idx = jnp.pad(indices, ((0, pad), (0, 0))).reshape(nc, chunk, -1)
    val = jnp.pad(values, ((0, pad), (0, 0))).reshape(nc, chunk, -1)
    return idx, val


def sparse_matmul(indices, values, w, mode: str = "f32"):
    """(rows, nnz) COO × (d, k) → (rows, k): gather rows of w, weight, sum.

    Padding entries (value 0) contribute nothing regardless of index.
    Large inputs are row-chunked so the (chunk, nnz, k) gather stays
    within the working-set budget.

    ``mode`` is the apply precision policy (utils/precision.py): the
    default 'f32' is INERT — solver callers (logistic / L-BFGS
    gradients) rely on that; scoring paths (PaddedSparseRows.matmul)
    pass the resolved policy, under which the per-row contraction runs
    with bf16 values/gathered weights and f32 accumulation."""
    from jax import lax

    from keystone_tpu.utils import precision

    indices = jnp.asarray(indices)
    values = jnp.asarray(values)
    w = jnp.asarray(w)
    rows, nnz = indices.shape
    k = w.shape[-1]
    chunk = _auto_chunk(rows, nnz, k)
    if rows <= chunk:
        wg = w[indices]  # (rows, nnz, k)
        return precision.apply_einsum("rn,rnk->rk", values, wg, mode=mode)
    idx, val = _chunk_coo(indices, values, chunk)

    def step(_, iv):
        i, v = iv
        out = precision.apply_einsum("rn,rnk->rk", v, w[i], mode=mode)
        return None, out

    _, out = lax.scan(step, None, (idx, val))
    return out.reshape(-1, k)[:rows]


class BucketedSparseRows:
    """Rows grouped into nnz buckets, each padded only to ITS cap.

    The global-``nnz_max`` cliff (round-2 review item 4): one dense-ish row
    in :class:`PaddedSparseRows` inflates every row's padding to the
    global max.  Here rows are permuted so similar-nnz rows share a
    bucket with a power-of-two cap; total memory is ≤2× Σ nnz when every
    natural cap keeps its own bucket, and the ``max_buckets`` merge picks
    whichever adjacent-cap merge adds the least padding.  ``perm[i]`` is
    the ORIGINAL index of sorted row i; the
    label matrix must be permuted the same way before a bucketed fit,
    and bucket scores scatter back through ``perm`` (least-squares /
    logistic losses are row-permutation invariant, so training on the
    permuted order is exact, not approximate).
    """

    def __init__(self, buckets, perm, num_features: int, n: int):
        self.buckets = list(buckets)  # List[PaddedSparseRows]
        self.perm = np.asarray(perm, np.int64)
        self.num_features = int(num_features)
        self.n = int(n)

    @property
    def shape(self):
        return (self.n, self.num_features)

    @property
    def nbytes(self) -> int:
        return sum(b.nbytes for b in self.buckets)

    @staticmethod
    def from_scipy_rows(
        rows: Sequence,
        num_features: Optional[int] = None,
        max_buckets: int = 6,
    ) -> "BucketedSparseRows":
        coos = [r.tocoo() for r in rows]
        d = int(num_features if num_features is not None else coos[0].shape[-1])
        widths = {int(c.shape[-1]) for c in coos}
        if widths - {d}:
            raise ValueError(
                f"sparse rows have width(s) {sorted(widths)} but "
                f"num_features={d}"
            )
        n = len(coos)
        nnz = np.asarray([max(c.nnz, 1) for c in coos])
        caps = 1 << np.ceil(np.log2(nnz)).astype(np.int64)
        # merge caps until ≤ max_buckets distinct, always merging the
        # ADJACENT pair that adds the least total padding (merging the
        # smallest cap blindly into the next PRESENT cap could jump many
        # octaves and re-create the global-padding cliff for the bulk of
        # the rows)
        uniq = sorted(set(caps.tolist()))
        while len(uniq) > max_buckets:
            costs = [
                int((caps == uniq[i]).sum()) * (uniq[i + 1] - uniq[i])
                for i in range(len(uniq) - 1)
            ]
            i = int(np.argmin(costs))
            caps[caps == uniq[i]] = uniq[i + 1]
            uniq.pop(i)
        # stable argsort by cap groups rows bucket-by-bucket; perm[i] is
        # the original index of the i-th row in concatenated-bucket order
        perm = np.argsort(caps, kind="stable")
        buckets = []
        for cap in sorted(set(caps.tolist())):
            sel = perm[caps[perm] == cap]
            m = len(sel)
            idx = np.zeros((m, cap), np.int32)
            val = np.zeros((m, cap), np.float32)
            for i, ri in enumerate(sel):
                c = coos[ri]
                idx[i, : c.nnz] = c.col
                val[i, : c.nnz] = c.data
            buckets.append(PaddedSparseRows(idx, val, d, n=m))
        return BucketedSparseRows(buckets, perm, d, n)

    def matmul(self, w, intercept=None) -> np.ndarray:
        """``X @ w`` (+ intercept) with per-bucket gathers; returns a
        HOST (n, k) array in the ORIGINAL row order."""
        w = jnp.asarray(w)
        out = np.empty((self.n, int(w.shape[-1])), np.float32)
        start = 0
        for b in self.buckets:
            scores = np.asarray(b.matmul(w))[: b.n]
            out[self.perm[start : start + b.n]] = scores
            start += b.n
        if intercept is not None:
            out = out + np.asarray(intercept)
        return out


def host_onehot(y, k: int) -> np.ndarray:
    """(n,) int class ids or (n, K) indicator matrix → float32 one-hot,
    built ON HOST: the sparse fit paths permute labels in numpy anyway,
    so a device one-hot would cross the host↔device link twice for
    nothing (~0.6 GB at n=10⁶, K=147)."""
    y = np.asarray(y)
    if y.ndim == 1:
        out = np.zeros((y.shape[0], k), np.float32)
        out[np.arange(y.shape[0]), y.astype(np.int64)] = 1.0
        return out
    return (y > 0).astype(np.float32)


def bucketize_with_labels(sp, y, n: Optional[int] = None, intercept: bool = False):
    """Per-bucket (indices, values, labels, mask) tuples for bucketed
    solvers.

    ``sp``: PaddedSparseRows or BucketedSparseRows; ``y``: (≥n, k) host
    or device label/target matrix aligned with the ORIGINAL row order.
    Rows whose original index ≥ ``n`` are treated as padding (matrix
    built over a padded Dataset) — their values and labels are zeroed
    and they are excluded from the masks.  Values are also zeroed on
    bucket shard-padding rows; labels are permuted into bucket order and
    shard-padded per bucket; with ``intercept`` each row gains a
    constant feature at index ``sp.num_features`` (value 1 on valid rows
    only).  Returns ``(bidx, bvals, by, n, d_aug, brow_ok)`` where
    ``brow_ok`` holds per-bucket (rows_b,) float masks of VALID rows —
    traced solver inputs (never static: counts changing within a shard
    multiple must not trigger recompiles).
    """
    from keystone_tpu.parallel import mesh as _mesh_mod

    if isinstance(sp, PaddedSparseRows):
        sp = BucketedSparseRows([sp], np.arange(sp.n), sp.num_features, sp.n)
    n = sp.n if n is None else int(n)
    y = np.asarray(y, np.float32)
    if y.shape[0] < n:
        raise ValueError(
            f"labels have {y.shape[0]} rows but the sparse matrix has "
            f"{n} true rows"
        )
    # rows past n (padding of the source Dataset) get zero labels
    y_ext = np.zeros((sp.n, y.shape[1]), np.float32)
    y_ext[:n] = y[:n]
    d = sp.num_features
    bidx, bvals, by, brow_ok = [], [], [], []
    start = 0
    for b in sp.buckets:
        sel = sp.perm[start : start + b.n]
        start += b.n
        rows_b = int(b.indices.shape[0])  # mesh-padded row count
        row_ok = np.zeros((rows_b,), np.float32)
        row_ok[: b.n] = (sel < n).astype(np.float32)
        yb = np.zeros((rows_b, y.shape[1]), np.float32)
        yb[: b.n] = y_ext[sel]
        row_ok_dev = _mesh_mod.shard_batch(row_ok)
        idx, vals = b.indices, b.values * row_ok_dev[:, None]
        if intercept:
            idx = jnp.concatenate(
                [idx, jnp.full((rows_b, 1), d, jnp.int32)], axis=1
            )
            vals = jnp.concatenate([vals, row_ok_dev[:, None]], axis=1)
        bidx.append(idx)
        bvals.append(vals)
        by.append(_mesh_mod.shard_batch(yb))
        brow_ok.append(row_ok_dev)
    return (
        tuple(bidx),
        tuple(bvals),
        tuple(by),
        n,
        d + 1 if intercept else d,
        tuple(brow_ok),
    )


def score_sparse_dataset(ds, weights, intercept=None):
    """Score a host Dataset of scipy sparse rows against dense weights
    by gathering weight rows (shared by LinearMapper and the logistic
    model — n×d never densifies).  Rows are nnz-bucketed so one heavy
    row doesn't inflate the whole batch's padding."""
    sp = BucketedSparseRows.from_scipy_rows(
        ds.items, num_features=weights.shape[0]
    )
    return ds.with_array(jnp.asarray(sp.matmul(weights, intercept)))


def sparse_grad(indices, values, r, d):
    """``Xᵀ r`` by scatter-add: (d, k) from (rows, nnz) COO and (rows, k).

    Duplicate indices accumulate (jnp ``.at[].add``); padding entries add
    zero.  Large inputs are row-chunked: the (chunk, nnz, k) contribution
    tensor is the only live intermediate, accumulated into the (d, k)
    output across scan steps."""
    from jax import lax

    indices = jnp.asarray(indices)
    values = jnp.asarray(values)
    r = jnp.asarray(r)
    rows, nnz = indices.shape
    k = r.shape[1]
    chunk = _auto_chunk(rows, nnz, k)
    if rows <= chunk:
        contrib = values[..., None] * r[:, None, :]  # (rows, nnz, k)
        return (
            jnp.zeros((d, k), jnp.float32)
            .at[indices.reshape(-1)]
            .add(contrib.reshape(-1, k))
        )
    idx, val = _chunk_coo(indices, values, chunk)
    nc = idx.shape[0]
    pad = nc * chunk - rows
    r3 = jnp.pad(r, ((0, pad), (0, 0))).reshape(nc, chunk, k)

    def step(acc, ivr):
        i, v, rc = ivr
        contrib = v[..., None] * rc[:, None, :]  # (chunk, nnz, k)
        return acc.at[i.reshape(-1)].add(contrib.reshape(-1, k)), None

    acc, _ = lax.scan(step, jnp.zeros((d, k), jnp.float32), (idx, val, r3))
    return acc
