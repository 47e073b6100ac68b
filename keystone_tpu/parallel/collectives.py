"""Collective helpers — the treeReduce/treeAggregate replacements.

The reference's only "collectives" are Spark ``treeReduce``/``treeAggregate``
(logarithmic aggregation of per-partition Gramians / gradients / moments to
the driver) and ``broadcast`` (SURVEY.md §2.9).  Here:

  - Inside ``shard_map``-decorated code, :func:`psum` is a literal
    all-reduce over ICI.
  - In jit-with-sharding code, :func:`sharded_gram` / :func:`sharded_matmul`
    express the per-partition-gemm + treeReduce pair as one einsum whose
    contraction over the row-sharded axis XLA lowers to a
    reduce-scatter/all-reduce — the idiomatic TPU form of call stack
    SURVEY.md §3.2.  A Gramian is symmetric: at a solver's block width
    :func:`sharded_gram` multiplies out only the upper block triangle in
    column panels (:func:`gram_panels`, a function of the width alone)
    and mirrors it; the precision (``solver_precision()``) and the
    all-reduce's place — on the products, before the local mirror — are
    those of the one dot.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from keystone_tpu.parallel import mesh as _mesh


def psum(x, axis_name: str = _mesh.DATA_AXIS):
    """All-reduce sum over a mesh axis (use inside shard_map/pmap)."""
    return lax.psum(x, axis_name)


def pmean(x, axis_name: str = _mesh.DATA_AXIS):
    return lax.pmean(x, axis_name)


def tree_psum(tree, axis_name: str = _mesh.DATA_AXIS):
    return jax.tree_util.tree_map(lambda x: lax.psum(x, axis_name), tree)


def sharded_matmul(a, b, out_spec: Optional[P] = None, mesh=None):
    """``a.T @ b`` with rows of a/b sharded over 'data'.

    This is the single communication pattern behind every reference solver
    (per-partition ``AᵀB`` gemm + treeReduce; e.g.
    nodes/learning/LinearMapper.scala § LinearMapEstimator): contraction
    over the sharded row axis; XLA inserts the all-reduce.  The result is
    constrained replicated (or ``out_spec``) — the broadcast analogue.

    Solver contractions request TRUE f32 MXU passes: XLA:TPU's *default*
    matmul precision truncates f32 inputs to bf16-grade passes (measured
    on v5 lite: default ≈ 2× the throughput of precision='float32'),
    which is fine for the featurize path but silently degrades normal
    equations — the reference computes these in f64 (netlib BLAS).  See
    utils/precision.py § solver_precision.
    """
    from keystone_tpu.utils.precision import solver_precision

    mesh = mesh or _mesh.current_mesh()
    out = jnp.matmul(
        a.T, b, precision=solver_precision(), preferred_element_type=jnp.float32
    )
    return lax.with_sharding_constraint(
        out, NamedSharding(mesh, out_spec if out_spec is not None else P())
    )


def gram_panels(width: int) -> int:
    """How many column panels :func:`sharded_gram` splits a Gramian of
    this static width into; 1 means the one dot.  The width is all the
    rule sees: at least 1024 and a multiple of ``128 · panels`` (whole
    MXU tiles in every panel) is panelled, sixteen panels where that
    divides and else eight; anything narrower or ragged — the ``d × d``
    normal equations of models/linear.py, toy widths — is not worth the
    assembly.  Measured on a v5e at width 4096 and 4096 / 8192 / 16384
    rows (PERF.md §6, PR 24): sixteen panels take 59 / 57 / 55 % of the
    one dot's time, eight 62 / 59 / 58 %, four 70 / 67 / 65 %."""
    if width >= 1024:
        for panels in (16, 8):
            if width % (128 * panels) == 0:
                return panels
    return 1


def sharded_gram(a, mesh=None):
    """``a.T @ a`` (Gramian) over row-sharded ``a``, replicated result.

    The product is symmetric, so only its upper block triangle is
    multiplied out.  The columns of ``a`` are split into
    :func:`gram_panels` panels of ``t`` columns; panel ``i`` contributes
    one strip ``a[:, i·t:(i+1)·t]ᵀ @ a[:, i·t:]`` — the diagonal tile and
    everything right of it — through :func:`sharded_matmul`, so every
    entry is the same ``solver_precision()`` contraction over the same
    sharded rows as in the one dot, and is all-reduced where the one
    dot's is (136 of 256 tiles' bytes at sixteen panels).  The tiles
    below the diagonal are the strips' transposes, copied locally: they
    are bit-equal to their mirror images, at 53 % of the one dot's flops
    (56 % at eight panels).  A width the rule leaves alone takes the one
    dot.
    """
    width = a.shape[-1]
    panels = gram_panels(width)
    if panels == 1:
        return sharded_matmul(a, a, mesh=mesh)
    t = width // panels
    out = jnp.zeros((width, width), jnp.float32)
    for i in range(panels):
        lo, hi = i * t, (i + 1) * t
        strip = sharded_matmul(a[:, lo:hi], a[:, lo:], mesh=mesh)
        out = out.at[lo:hi, lo:].set(strip)
        if hi < width:
            out = out.at[hi:, lo:hi].set(strip[:, t:].T)
    return lax.with_sharding_constraint(out, _mesh.replicated(mesh))
