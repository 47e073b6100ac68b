"""Shared solver numerics."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from keystone_tpu.parallel import mesh as _mesh


def factor_spd(A: jnp.ndarray, reg: float = 0.0) -> jnp.ndarray:
    """The upper Cholesky factor of (A + reg·I) for symmetric
    positive-definite A: the half of :func:`solve_spd` that does not see
    the right-hand side, for a caller that solves against one matrix more
    than once (:func:`solve_factored`)."""
    d = A.shape[0]
    A = A + reg * jnp.eye(d, dtype=A.dtype)
    c, _ = jax.scipy.linalg.cho_factor(A)
    return c


def solve_factored(c: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """Solve against a factor :func:`factor_spd` returned."""
    return jax.scipy.linalg.cho_solve((c, False), B)


def solve_spd(A: jnp.ndarray, B: jnp.ndarray, reg: float = 0.0) -> jnp.ndarray:
    """Solve (A + reg·I) X = B for symmetric positive-definite A via
    Cholesky — the on-device replacement for every reference driver-side
    ``cholesky(... + λI) \\ ...`` (e.g. nodes/learning/BlockLeastSquares.scala)."""
    return solve_factored(factor_spd(A, reg), B)


def constrain(x, *spec):
    """Sharding-constrain ``x`` to PartitionSpec(*spec) on the current mesh."""
    mesh = _mesh.current_mesh()
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def xtx_xty(x: jnp.ndarray, y: jnp.ndarray):
    """Replicated (XᵀX, XᵀY) from row-sharded X, Y.

    The reference's per-partition gemm + treeReduce pair (SURVEY.md §3.2);
    zero padding rows contribute nothing, so padded Datasets are safe.
    """
    from keystone_tpu.parallel.collectives import sharded_gram, sharded_matmul

    return sharded_gram(x), sharded_matmul(x, y)


def kahan_add(s, c, inc):
    """One compensated-summation step: returns (new_sum, new_compensation).
    Used by the streaming (out-of-core) fits so accumulator rounding error
    stays O(ε) instead of growing with batch count.  XLA does not
    reassociate floats by default, so the compensation survives jit."""
    y = inc - c
    t = s + y
    return t, (t - s) - y


def stage_stream_batch(*host_arrays):
    """Host batch arrays → mesh-sharded device arrays, true row count, and
    a pad-row mask, with the row capacity bucketed to the next power of
    two.  Bucketing bounds jit recompiles for variable-size streams to
    O(log max_batch) shapes instead of one per distinct size; zero pad
    rows are masked by ``row_ok`` wherever sums would see them."""
    bn = int(np.shape(host_arrays[0])[0])
    cap = 1 << max(0, (bn - 1)).bit_length()  # next pow2 >= bn
    staged = []
    for a in host_arrays:
        a = np.asarray(a, np.float32)
        if cap != a.shape[0]:
            a = np.pad(a, [(0, cap - a.shape[0])] + [(0, 0)] * (a.ndim - 1))
        staged.append(_mesh.shard_batch(a))
    row_ok = (jnp.arange(staged[0].shape[0]) < bn).astype(jnp.float32)[:, None]
    return (*staged, bn, row_ok)
