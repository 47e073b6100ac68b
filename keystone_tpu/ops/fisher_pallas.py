"""Pallas TPU kernel for Fisher-vector encoding.

The XLA path (ops/fisher.py § _fisher_encode) materializes the
responsibility tensor γ (n, T, K) in HBM between the softmax and the two
sufficient-statistic einsums.  For FV workloads γ is as large as the
descriptors themselves (T≈10³ descriptors × K≈256 components per image),
so the op is HBM-bandwidth bound — exactly the case the Pallas guide
calls for a fused kernel.

This kernel streams descriptor tiles through VMEM once per image:

    per (image i, tile t):
      logp  = log w + log N(x; μ, σ²)      (two MXU matmuls)
      γ     = softmax_K(logp) · mask       (VPU, never leaves VMEM)
      s0   += Σ_t γ;  s1 += γᵀx;  s2 += γᵀx²   (MXU, VMEM accumulators)
    on the last tile: Φ¹, Φ² from (s0, s1, s2) → out[i]

Accumulators live in VMEM scratch (K + 2·K·D floats ≪ 16 MB), so HBM
traffic is exactly one read of the descriptors and one write of the FV.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LOG2PI = 1.8378770664093453


def _precision():
    # deferred: keeps this kernel module importable without dragging the
    # policy module into jax.experimental import time
    from keystone_tpu.utils import precision

    return precision


# Max descriptors per VMEM tile when the GMM shape is unknown.  Measured
# on v5 lite (T=784, K=256, d=64): one whole-image tile runs the kernel
# at ~42 TF/s vs ~14 TF/s with 128-row tiles — per-program overhead
# (accumulator init/finalize, revolving windows) dominates small tiles,
# and M=T-sized matmuls feed the MXU far better.
TILE_T_MAX = 1024
#: VMEM bytes budgeted for the per-tile intermediates (γ/logp/e are
#: (tile, K) f32 — ~3 live copies — plus x and x² at (tile, d)); the
#: rest of the ~16 MB budget holds the (K, d) accumulators + constants.
_VMEM_TILE_BUDGET = 12 << 20


def _tile_t(t: int, k: int | None = None, d: int | None = None) -> int:
    """Fewest tiles covering t under the VMEM budget.

    With the GMM shape (k, d) known, the cap comes from the budget —
    measured r4 at the multi-scale config (T=2520, K=256): one 2520-row
    tile runs 620→524 µs/batch vs 3×896 tiles, because the fixed-cap
    tiling both paid per-tile overhead AND padded the whole descriptor
    tensor 2520→2688 (a 130 µs jnp.pad copy).  Single tile: any sublane
    multiple (8) works.  Multiple tiles: the mask block rides T as its
    LANE dim, so the tile must be a 128-multiple."""
    cap = TILE_T_MAX
    if k is not None and d is not None:
        rows = _VMEM_TILE_BUDGET // (4 * (3 * k + 2 * d))
        # floor of 8 (one sublane group), NOT some larger convenience
        # minimum: a floor above the budget would silently re-breach the
        # VMEM limit the cap exists to respect.  (Multi-tile tiles are
        # ≥128 regardless — the mask lane-dim constraint — so K large
        # enough that 128 rows overflow VMEM fails at Mosaic compile,
        # as it would have at any tile size.)
        cap = max(8, min(4096, rows // 8 * 8))
    tiles = -(-t // cap)
    while True:
        if tiles == 1:
            return -(-t // 8) * 8
        tile = -(-t // tiles // 128) * 128
        # the 128-up-rounding can push one tile count past the cap;
        # adding a tile shrinks it (terminates at tile=128)
        if tile <= max(cap, 128):
            return tile
        tiles += 1


def _fv_tile_body(x, m, logw_ref, mu_ref, inv_ref, lognorm_ref,
                  out_ref, s0_ref, s1_ref, s2_ref, cnt_ref):
    """Shared FV accumulation over one descriptor tile: posterior gemms
    → masked softmax → sufficient-statistic accumulators → Φ¹/Φ² on the
    last tile.  ``x`` (TILE_T, d) f32 in VMEM; ``m`` (TILE_T, 1) mask.
    Both the plain FV kernel and the fused sift-normalize→PCA→FV
    megakernel end here, so their math cannot drift apart."""
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        s0_ref[:] = jnp.zeros_like(s0_ref)
        s1_ref[:] = jnp.zeros_like(s1_ref)
        s2_ref[:] = jnp.zeros_like(s2_ref)
        cnt_ref[0] = 0.0

    mu_inv = mu_ref[:] * inv_ref[:]  # (K, d)

    # log N(x; μ_k, σ²_k) via the gemm expansion (all on the MXU)
    quad = (
        jnp.dot(x * x, inv_ref[:].T, preferred_element_type=jnp.float32)
        - 2.0 * jnp.dot(x, mu_inv.T, preferred_element_type=jnp.float32)
        + jnp.sum(mu_ref[:] * mu_inv, axis=1)[None, :]
    )
    logp = logw_ref[0][None, :] + lognorm_ref[0][None, :] - 0.5 * quad

    # row softmax over K — γ never leaves VMEM
    mx = jnp.max(logp, axis=1, keepdims=True)
    e = jnp.exp(logp - mx)
    gamma = (e / jnp.sum(e, axis=1, keepdims=True)) * m  # (TILE_T, K)

    s0_ref[0, :] += jnp.sum(gamma, axis=0)
    s1_ref[:] += jnp.dot(gamma.T, x, preferred_element_type=jnp.float32)
    s2_ref[:] += jnp.dot(gamma.T, x * x, preferred_element_type=jnp.float32)
    cnt_ref[0] += jnp.sum(m)

    @pl.when(t == nt - 1)
    def _finalize():
        k, d = s1_ref.shape
        s0 = s0_ref[0, :]  # (K,)
        s1 = s1_ref[:]
        s2 = s2_ref[:]
        mu = mu_ref[:]
        var = 1.0 / inv_ref[:]
        sigma = jnp.sqrt(var)
        w = jnp.exp(logw_ref[0])
        tn = jnp.maximum(cnt_ref[0], 1.0)
        phi1 = (s1 - s0[:, None] * mu) / sigma
        phi2 = (s2 - 2.0 * mu * s1 + s0[:, None] * (mu * mu)) / var - s0[:, None]
        phi1 = phi1 / (tn * jnp.sqrt(w)[:, None])
        phi2 = phi2 / (tn * jnp.sqrt(2.0 * w)[:, None])
        # keep 2-D: Mosaic can't shape-cast (K, d) -> (K*d); the caller
        # flattens (n, 2K, d) -> (n, 2KD) outside the kernel
        out_ref[0, :k, :] = phi1
        out_ref[0, k:, :] = phi2


def _fv_kernel(x_ref, mask_ref, logw_ref, mu_ref, inv_ref, lognorm_ref,
               out_ref, s0_ref, s1_ref, s2_ref, cnt_ref):
    # descriptors may arrive bf16 (halved HBM traffic — the kernel is
    # bandwidth bound); compute stays f32 in VMEM
    x = x_ref[0].astype(jnp.float32)  # (TILE_T, d)
    # mask arrives (1, 1, TILE_T) with T on the LANE dim: a (n, T, 1)
    # input would be lane-padded to 128 by TPU tiling — 128× the HBM
    # traffic for the same bits.  The (1,T)→(T,1) relayout is per-tile
    # VPU work on ~10³ elements, noise next to the saved DMA.
    m = mask_ref[0].T  # (TILE_T, 1)
    _fv_tile_body(x, m, logw_ref, mu_ref, inv_ref, lognorm_ref,
                  out_ref, s0_ref, s1_ref, s2_ref, cnt_ref)


def _fv_fused_kernel(x_ref, mask_ref, comp_ref, mean_ref, logw_ref, mu_ref,
                     inv_ref, lognorm_ref, out_ref, s0_ref, s1_ref, s2_ref,
                     cnt_ref, *, normalize: bool):
    """Fused forward tile: [SIFT normalize →] PCA project → FV
    accumulate, one VMEM pass per descriptor tile.

    The unfused chain writes the normalized (T, d_in) descriptors AND
    the projected (T, d) descriptors back to HBM between stages (and on
    the un-jitted serve path pays a program launch per stage); here raw
    descriptors stream from HBM exactly once and only the FV leaves.
    ``normalize`` is a Python-static flag (functools.partial at
    pallas_call time): True when the feed is RAW windowed SIFT output
    (the extractor's normalize tail absorbed in-kernel), False when the
    producer already normalized."""
    x = x_ref[0].astype(jnp.float32)  # (TILE_T, d_in) descriptor tile
    if normalize:
        # SIFT normalize: L2 → clamp 0.2 → re-L2 (VPU; same form and
        # epsilons as ops/sift._sift_normalize, the parity reference)
        nrm = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
        x = x / jnp.maximum(nrm, 1e-8)
        x = jnp.minimum(x, 0.2)
        nrm = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
        x = x / jnp.maximum(nrm, 1e-8)
    # PCA projection on the MXU: (TILE_T, d_in) × (d_in, d), f32
    # accumulation.  Tile padding rows project to (−μ)·C ≠ 0, but the
    # mask zeroes their γ so they contribute nothing downstream.
    z = jnp.dot(
        x - mean_ref[0][None, :], comp_ref[:],
        preferred_element_type=jnp.float32,
    )
    m = mask_ref[0].T  # (TILE_T, 1) — see _fv_kernel on the lane layout
    _fv_tile_body(z, m, logw_ref, mu_ref, inv_ref, lognorm_ref,
                  out_ref, s0_ref, s1_ref, s2_ref, cnt_ref)


@functools.partial(jax.jit, static_argnames=("interpret", "mxu"))
def fisher_encode_pallas(
    xs, mask, w, mu, var, interpret: bool = False, mxu: str = "f32"
):
    """xs: (n, T, d); mask: (n, T); GMM (w (K,), mu/var (K, d)) → (n, 2KD).

    Matches ops/fisher.py § _fisher_encode up to f32 rounding.  With
    ``mxu='bf16'`` (the featurize policy) or ``mxu='bf16_apply'`` (the
    apply policy — utils/precision.fdtype maps both to bf16) descriptors
    stream from HBM as bf16 (half the read traffic of the
    bandwidth-bound kernel); all VMEM compute stays f32.
    """
    n, t, d = xs.shape
    k = mu.shape[0]
    tile_t = _tile_t(t, k, d)
    tiles = -(-t // tile_t)
    if tiles * tile_t != t:
        pad = tiles * tile_t - t
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    inv = 1.0 / var
    logw = jnp.log(w).reshape(1, k)
    lognorm = (-0.5 * (jnp.sum(jnp.log(var), axis=1) + d * _LOG2PI)).reshape(1, k)

    grid = (n, tiles)
    out = pl.pallas_call(
        _fv_kernel,
        grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        in_specs=[
            pl.BlockSpec((1, tile_t, d), lambda i, t: (i, t, 0)),
            pl.BlockSpec((1, 1, tile_t), lambda i, t: (i, 0, t)),
            pl.BlockSpec((1, k), lambda i, t: (0, 0)),
            pl.BlockSpec((k, d), lambda i, t: (0, 0)),
            pl.BlockSpec((k, d), lambda i, t: (0, 0)),
            pl.BlockSpec((1, k), lambda i, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 2 * k, d), lambda i, t: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 2 * k, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, k), jnp.float32),
            pltpu.VMEM((k, d), jnp.float32),
            pltpu.VMEM((k, d), jnp.float32),
            pltpu.SMEM((1,), jnp.float32),
        ],
        interpret=interpret,
    )(
        xs.astype(_precision().fdtype(mxu)),
        mask.astype(jnp.float32)[:, None, :],
        logw.astype(jnp.float32),
        mu.astype(jnp.float32),
        inv.astype(jnp.float32),
        lognorm.astype(jnp.float32),
    )
    return out.reshape(n, 2 * k * d)


@functools.partial(
    jax.jit, static_argnames=("interpret", "mxu", "normalize")
)
def fused_forward_pallas(
    desc,
    mask,
    components,
    mean,
    w,
    mu,
    var,
    interpret: bool = False,
    mxu: str = "f32",
    normalize: bool = True,
):
    """[SIFT-normalize →] PCA-project → FV-encode as ONE Pallas kernel.

    ``desc``: (n, T, d_in) descriptors — RAW (pre-normalize) windowed
    SIFT output with ``normalize=True``, already-normalized descriptors
    with ``normalize=False``; ``mask``: (n, T); ``components``:
    (d_in, d) PCA projection; ``mean``: (d_in,) or None; GMM
    ``(w (K,), mu/var (K, d))`` → (n, 2·K·D).

    Matches the per-stage chain ``ops/sift._sift_normalize →
    models/pca.PCATransformer → ops/fisher._fisher_encode`` to f32
    rounding.  HBM traffic collapses from three round trips (normalized
    descriptors out+in, projected descriptors out+in, FV out) to one
    descriptor read and one FV write; on the un-jitted serve path the
    three program launches become one.  Under ``mxu='bf16'`` /
    ``'bf16_apply'`` the descriptor stream crosses HBM at half width;
    all VMEM compute stays f32."""
    n, t, d_in = desc.shape
    k, d = mu.shape
    # VMEM budget must hold BOTH descriptor widths per tile (raw d_in
    # and projected d) on top of the γ/logp copies
    tile_t = _tile_t(t, k, d_in + d)
    tiles = -(-t // tile_t)
    if tiles * tile_t != t:
        pad = tiles * tile_t - t
        desc = jnp.pad(desc, ((0, 0), (0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    inv = 1.0 / var
    logw = jnp.log(w).reshape(1, k)
    lognorm = (-0.5 * (jnp.sum(jnp.log(var), axis=1) + d * _LOG2PI)).reshape(1, k)
    mean_row = (
        jnp.zeros((1, d_in), jnp.float32)
        if mean is None
        else jnp.asarray(mean, jnp.float32).reshape(1, d_in)
    )

    grid = (n, tiles)
    out = pl.pallas_call(
        functools.partial(_fv_fused_kernel, normalize=bool(normalize)),
        grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        in_specs=[
            pl.BlockSpec((1, tile_t, d_in), lambda i, t: (i, t, 0)),
            pl.BlockSpec((1, 1, tile_t), lambda i, t: (i, 0, t)),
            pl.BlockSpec((d_in, d), lambda i, t: (0, 0)),
            pl.BlockSpec((1, d_in), lambda i, t: (0, 0)),
            pl.BlockSpec((1, k), lambda i, t: (0, 0)),
            pl.BlockSpec((k, d), lambda i, t: (0, 0)),
            pl.BlockSpec((k, d), lambda i, t: (0, 0)),
            pl.BlockSpec((1, k), lambda i, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 2 * k, d), lambda i, t: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 2 * k, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, k), jnp.float32),
            pltpu.VMEM((k, d), jnp.float32),
            pltpu.VMEM((k, d), jnp.float32),
            pltpu.SMEM((1,), jnp.float32),
        ],
        interpret=interpret,
    )(
        desc.astype(_precision().fdtype(mxu)),
        mask.astype(jnp.float32)[:, None, :],
        components.astype(jnp.float32),
        mean_row,
        logw.astype(jnp.float32),
        mu.astype(jnp.float32),
        inv.astype(jnp.float32),
        lognorm.astype(jnp.float32),
    )
    return out.reshape(n, 2 * k * d)


def pallas_supported(x=None) -> bool:
    """True when the computation targets a device that can run TPU pallas
    kernels.  The target is resolved in priority order: the active
    framework mesh (covers CPU-mesh dryruns on TPU hosts), the concrete
    input array's committed devices, then the default backend.  Nothing
    is caught: a backend that cannot be read raises, it does not answer
    False and silently route the FV stage to the XLA path."""
    from keystone_tpu.parallel.mesh import active_mesh

    m = active_mesh()
    if m is not None and m.devices.size:
        return m.devices.flat[0].platform == "tpu"
    # tracers and numpy inputs carry no device info
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return next(iter(x.devices())).platform == "tpu"
    return jax.default_backend() == "tpu"
