"""Pallas TPU kernel for Gaussian gram blocks.

The kernel tier's hot contraction is the gram block K(X_i, Z_j) =
exp(−γ‖x−z‖²): the XLA chain (models/kernel_ridge.py §
GaussianKernelGenerator) lowers the ‖x−z‖² gemm expansion into a matmul
plus THREE full-size (tile_n × tile_m) HBM round trips — the squared
distance, its clamp, and the exp each materialize between fusions when
the block exceeds the fusion budget.  For out-of-core KRR that tensor
is produced nb² times per epoch, so the op is HBM-bandwidth bound on
exactly the sweep the solver spends its life in.

This kernel fuses the whole chain in VMEM per output tile:

    per (row tile i, col tile j):
      cross = x_i · z_jᵀ                      (one MXU matmul, f32 acc)
      sq    = max(‖x‖² − 2·cross + ‖z‖², 0)   (VPU, never leaves VMEM)
      out   = exp(−γ·sq)                      (VPU → one HBM write)

HBM traffic collapses to one read of each operand tile and one write of
the kernel block.  Under ``mxu='bf16'`` / ``'bf16_apply'`` the operand
tiles stream from HBM at half width (a bandwidth lever — the row norms
and all VMEM compute stay f32).  The SOLVER path always streams f32
(``mxu='f32'``): kernel values feed block Cholesky solves, and the
precision contract (analysis/precision.py) keeps solver math
solver-grade under every ``KEYSTONE_MATMUL`` mode — f32 tiles are
multiplied at ``Precision.HIGHEST`` (``_tile_precision``), as the XLA
chain's ``sdot`` is; at the MXU's default the f32 operands were rounded
to bf16 first and entries sat 1.0e-4..3.3e-4 off at d=2048 (my chip
runs, PR 21).

``gram_block`` is the dispatcher: Pallas on TPU backends
(``pallas_supported()``, ``KEYSTONE_GRAM_PALLAS=0`` escape hatch), and
a bit-identical XLA chain everywhere else — ``_gram_block_xla`` emits
exactly the ``GaussianKernelGenerator`` graph, pinned by test.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from keystone_tpu.ops.fisher_pallas import pallas_supported


def _precision():
    from keystone_tpu.utils import precision

    return precision


#: VMEM bytes budgeted per program, under Mosaic's 16 MiB scoped limit
#: on the v5e (the compiler refuses a kernel whose buffers exceed it).
_VMEM_BUDGET = 15 << 20
_MIN_TILE = 128


def _tile_vmem_bytes(tile: int, d: int) -> int:
    """What one grid step keeps in VMEM at square ``tile``-row tiles:
    the pipeline double-buffers BOTH (tile, d) operand tiles and the
    (tile, tile) f32 output, plus ~3 (tile, tile) f32 intermediates
    (cross, sq, exp).  Operands are counted at f32 width — a bf16
    stream halves the buffers but adds the f32 upcast copies, the same
    total.  ``d`` is lane-padded to 128 as Mosaic lays it out.

    Above the 128-row floor the ``HIGHEST`` multiply keeps split copies
    of both operand tiles, as much again as the buffers themselves: the
    v5e compiler's scoped-VMEM need grows by 32·tile bytes per feature
    at 256 and 512 rows and by 16·tile at 128 (read off its refusals:
    256 rows passes d=1792 and needs 16.77M at 2048; 512 passes 768 and
    needs 17.02M at 896; 128 passes 7552 and needs 16.12M at 8192)."""
    d_pad = -(-d // 128) * 128
    operand_copies = 2 * 2 if tile <= _MIN_TILE else 2 * 2 * 2
    return 4 * (operand_copies * tile * d_pad + 2 * tile * tile + 3 * tile * tile)


#: features per row up to which the untiled-d operand tiles fit VMEM at
#: the 128-row floor (compiled for the v5e at exactly this width in
#: tests/test_tpu_compile.py) — above it the dispatcher takes the XLA
#: chain rather than asking Mosaic for the impossible.
GRAM_MAX_D = (
    (_VMEM_BUDGET - _tile_vmem_bytes(_MIN_TILE, 0)) // (16 * _MIN_TILE) // 128 * 128
)


def _gram_tile(n: int, d: int) -> int:
    """Rows per operand tile under the VMEM budget.  Single-tile inputs
    round to a sublane multiple (8); tiled inputs use a 128-multiple so
    the lane-dim layouts stay native."""
    cap = 512
    while cap > _MIN_TILE and _tile_vmem_bytes(cap, d) > _VMEM_BUDGET:
        cap //= 2
    if n <= cap:
        return -(-n // 8) * 8
    return cap


def _tile_precision(ref):
    """f32 tiles are the solver stream: multiply them at true f32 (the
    MXU's default rounds f32 operands to bf16 first).  bf16 tiles are
    exact in one pass already."""
    return jax.lax.Precision.HIGHEST if ref.dtype == jnp.float32 else None


def _gram_kernel(x_ref, z_ref, out_ref, *, gamma: float):
    # operands may arrive bf16 (halved HBM read traffic — the kernel is
    # bandwidth bound); norms and all compute stay f32 in VMEM
    x = x_ref[:].astype(jnp.float32)  # (TN, d)
    z = z_ref[:].astype(jnp.float32)  # (TM, d)
    xn = jnp.sum(x * x, axis=1, keepdims=True)  # (TN, 1)
    zn = jnp.sum(z * z, axis=1)[None, :]  # (1, TM)
    # contract d without materializing zᵀ (dot_general, f32 accumulation)
    cross = jax.lax.dot_general(
        x, z, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_tile_precision(x_ref),
    )
    sq = jnp.maximum(xn - 2.0 * cross + zn, 0.0)
    out_ref[:] = jnp.exp(-gamma * sq)


@functools.partial(jax.jit, static_argnames=("gamma", "interpret", "mxu"))
def gram_block_pallas(
    x, z, gamma: float, interpret: bool = False, mxu: str = "f32"
):
    """K(x, z) = exp(−γ‖x−z‖²) as one fused Pallas kernel.

    ``x``: (n, d); ``z``: (m, d) → (n, m) f32.  ``gamma`` is static
    (one fit = one γ = one compile).  Matches ``_gram_block_xla`` /
    ``GaussianKernelGenerator`` to f32 rounding; padding tiles compute
    garbage that is sliced away before return."""
    n, d = x.shape
    m = z.shape[0]
    tn = _gram_tile(n, d)
    tm = _gram_tile(m, d)
    n_tiles = -(-n // tn)
    m_tiles = -(-m // tm)
    if n_tiles * tn != n:
        x = jnp.pad(x, ((0, n_tiles * tn - n), (0, 0)))
    if m_tiles * tm != m:
        z = jnp.pad(z, ((0, m_tiles * tm - m), (0, 0)))

    fdt = _precision().fdtype(mxu)
    out = pl.pallas_call(
        functools.partial(_gram_kernel, gamma=float(gamma)),
        grid=(n_tiles, m_tiles),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        in_specs=[
            pl.BlockSpec((tn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tm, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tn, tm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tn, m_tiles * tm), jnp.float32),
        interpret=interpret,
    )(x.astype(fdt), z.astype(fdt))
    return out[:n, :m]


def _gram_block_xla(x, z, gamma, solver_grade: bool = True):
    """The CPU/fallback chain — EXACTLY the ``GaussianKernelGenerator``
    graph, by construction: it IS the generator (imported lazily; the
    models module imports this one only inside functions, so there is
    no cycle).  Routing through the dispatcher off-TPU is bit-identical
    to calling the generator directly (pinned by test), and a future
    generator change cannot silently diverge the fallback."""
    from keystone_tpu.models.kernel_ridge import GaussianKernelGenerator

    return GaussianKernelGenerator(gamma, solver_grade=solver_grade)(x, z)


def gram_pallas_enabled(d: int = None) -> bool:
    """Should gram blocks route to the Pallas kernel?  True only on a
    TPU-capable target (``pallas_supported``), and only while the
    untiled feature dim fits the VMEM budget.

    The ``gram_pallas`` gate resolves through the planner precedence
    (``keystone_tpu.planner.registry``): ``KEYSTONE_GRAM_PALLAS=0`` is
    the documented env override; with the env unset, an installed
    ``PhysicalPlan`` that sampled the XLA chain as cheaper routes there;
    with neither, the historical default (Pallas wherever it runs)."""
    if os.environ.get("KEYSTONE_GRAM_PALLAS", "1") == "0":
        return False
    if os.environ.get("KEYSTONE_GRAM_PALLAS") is None:
        try:
            from keystone_tpu.planner import registry as _plans

            if _plans.planned_gate("gram_pallas") == "xla":
                return False
        except Exception:
            pass
    if d is not None and d > GRAM_MAX_D:
        return False
    return pallas_supported()


def gram_block(
    x,
    z,
    gamma,
    solver_grade: bool = True,
    mxu: str = "f32",
    use_pallas=None,
    interpret: bool = False,
):
    """One kernel column/tile block, routed to the fused Pallas kernel
    on capable backends and to the bit-identical XLA chain elsewhere.

    ``use_pallas=None`` resolves via :func:`gram_pallas_enabled`;
    callers inside jitted solver steps resolve it ONCE per fit and pass
    it static.  ``solver_grade`` keeps the XLA chain's contraction on
    ``sdot`` (true-f32 MXU passes) — the Pallas path multiplies f32
    tiles at true f32 as well, and its operand stream width follows
    ``mxu`` (kept ``'f32'`` by every solver caller)."""
    if use_pallas is None:
        use_pallas = gram_pallas_enabled(int(x.shape[-1]))
    if use_pallas:
        return gram_block_pallas(
            x, z, float(gamma), interpret=interpret, mxu=mxu
        )
    return _gram_block_xla(x, z, gamma, solver_grade=solver_grade)


# ------------------------------------------------- polynomial / linear tier
def _poly_gram_kernel(x_ref, z_ref, out_ref, *, alpha: float, c: float, degree: int):
    # same VMEM discipline as the Gaussian kernel: operands may stream
    # bf16, the contraction accumulates f32, and the affine + integer
    # power epilogue never leaves VMEM
    x = x_ref[:].astype(jnp.float32)  # (TN, d)
    z = z_ref[:].astype(jnp.float32)  # (TM, d)
    cross = jax.lax.dot_general(
        x, z, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_tile_precision(x_ref),
    )
    out_ref[:] = (alpha * cross + c) ** degree


@functools.partial(
    jax.jit, static_argnames=("alpha", "c", "degree", "interpret", "mxu")
)
def poly_block_pallas(
    x, z, alpha: float, c: float, degree: int, interpret: bool = False,
    mxu: str = "f32",
):
    """K(x, z) = (α·x·zᵀ + c)^degree as one fused Pallas kernel —
    the polynomial (and, at α=1, c=0, degree=1, linear) twin of
    :func:`gram_block_pallas`; identical tiling/VMEM budget, identical
    padding discipline (padding tiles compute garbage, sliced away)."""
    n, d = x.shape
    m = z.shape[0]
    tn = _gram_tile(n, d)
    tm = _gram_tile(m, d)
    n_tiles = -(-n // tn)
    m_tiles = -(-m // tm)
    if n_tiles * tn != n:
        x = jnp.pad(x, ((0, n_tiles * tn - n), (0, 0)))
    if m_tiles * tm != m:
        z = jnp.pad(z, ((0, m_tiles * tm - m), (0, 0)))
    fdt = _precision().fdtype(mxu)
    out = pl.pallas_call(
        functools.partial(
            _poly_gram_kernel,
            alpha=float(alpha),
            c=float(c),
            degree=int(degree),
        ),
        grid=(n_tiles, m_tiles),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        in_specs=[
            pl.BlockSpec((tn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tm, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tn, tm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tn, m_tiles * tm), jnp.float32),
        interpret=interpret,
    )(x.astype(fdt), z.astype(fdt))
    return out[:n, :m]


def _poly_block_xla(x, z, alpha, c, degree, solver_grade: bool = True):
    """The CPU/fallback chain — EXACTLY the ``PolynomialKernelGenerator``
    graph, by construction (the ``_gram_block_xla`` discipline: the
    fallback IS the generator, so it can never silently diverge)."""
    from keystone_tpu.models.kernel_ridge import PolynomialKernelGenerator

    return PolynomialKernelGenerator(
        degree=int(degree), alpha=alpha, c=c, solver_grade=solver_grade
    )(x, z)


def _linear_block_xla(x, z, solver_grade: bool = True):
    """Bit-identical fallback = the ``LinearKernelGenerator`` itself."""
    from keystone_tpu.models.kernel_ridge import LinearKernelGenerator

    return LinearKernelGenerator(solver_grade=solver_grade)(x, z)


def poly_gram_block(
    x,
    z,
    alpha: float = 1.0,
    c: float = 1.0,
    degree: int = 2,
    solver_grade: bool = True,
    mxu: str = "f32",
    use_pallas=None,
    interpret: bool = False,
):
    """Polynomial-kernel gram block through the same Pallas/XLA gating
    as :func:`gram_block` (``gram_pallas_enabled`` +
    ``KEYSTONE_GRAM_PALLAS=0`` escape hatch + ``GRAM_MAX_D`` bound)."""
    if use_pallas is None:
        use_pallas = gram_pallas_enabled(int(x.shape[-1]))
    if use_pallas:
        return poly_block_pallas(
            x, z, float(alpha), float(c), int(degree),
            interpret=interpret, mxu=mxu,
        )
    return _poly_block_xla(x, z, alpha, c, degree, solver_grade=solver_grade)


def linear_gram_block(
    x,
    z,
    solver_grade: bool = True,
    mxu: str = "f32",
    use_pallas=None,
    interpret: bool = False,
):
    """Linear-kernel gram block: rides the polynomial megakernel at
    (α=1, c=0, degree=1) on Pallas targets; the XLA fallback is the
    ``LinearKernelGenerator`` chain, bit-identical."""
    if use_pallas is None:
        use_pallas = gram_pallas_enabled(int(x.shape[-1]))
    if use_pallas:
        return poly_block_pallas(
            x, z, 1.0, 0.0, 1, interpret=interpret, mxu=mxu
        )
    return _linear_block_xla(x, z, solver_grade=solver_grade)


def gram_block_for(kernel_gen, x, z, mxu: str = "f32", use_pallas=None,
                   interpret: bool = False):
    """Route a kernel GENERATOR instance through the matching
    dispatcher — the single entry ``BlockKernelMatrix`` uses, so every
    first-class generator (Gaussian, polynomial, linear) shares the
    Pallas/XLA gating and duck-typed generators stay untouched.
    Returns None for generators with no dispatcher route (the caller
    falls back to calling the generator directly)."""
    from keystone_tpu.models.kernel_ridge import (
        GaussianKernelGenerator,
        LinearKernelGenerator,
        PolynomialKernelGenerator,
    )

    sg = getattr(kernel_gen, "solver_grade", True)
    if isinstance(kernel_gen, GaussianKernelGenerator):
        return gram_block(
            x, z, float(kernel_gen.gamma), solver_grade=sg, mxu=mxu,
            use_pallas=use_pallas, interpret=interpret,
        )
    if isinstance(kernel_gen, PolynomialKernelGenerator):
        return poly_gram_block(
            x, z, alpha=float(kernel_gen.alpha), c=float(kernel_gen.c),
            degree=int(kernel_gen.degree), solver_grade=sg, mxu=mxu,
            use_pallas=use_pallas, interpret=interpret,
        )
    if isinstance(kernel_gen, LinearKernelGenerator):
        return linear_gram_block(
            x, z, solver_grade=sg, mxu=mxu, use_pallas=use_pallas,
            interpret=interpret,
        )
    return None
