"""Convolution → symmetric rectifier → sum pooling as one program that
never writes the convolution's activation.

RandomPatchCifar's featurizer (nodes/images/Convolver.scala with
``normalizePatches``, SymmetricRectifier.scala, Pooler.scala) turns a
3,072-byte image into 27 × 27 × 10,000 responses — 29 MB in float32, 58 MB
rectified — and sums them down to 2 × 2 × 20,000 numbers.  Written stage
by stage the activation goes to HBM and back; here it lives only in VMEM,
one image and one block of filters at a time:

    rows   = im2col(x), each patch normalised: (p − mean p) / √(var p + c)
             (float32, before anything is rounded), one row per output
             position, ordered by POOLING GROUP (below), two columns of
             ones appended                                        [XLA]
    z      = rows · [filters; bias_hi; bias_lo]    one MXU pass, f32 acc
    pooled = Σ over a window's rows of max(z − α, m) and max(−z − α, m)

The bias (``Convolver.offset``: −mean·W·Fᵀ of the ZCA whitener) rides in
the product as two extra rows, its bf16 rounding error in the second, so
the epilogue is four vector operations an element: with h = α + m ≥ 0,

    max(z − α, m) = max(z, h) − α        max(−z − α, m) = −min(z, −h) − α

and the −α (times the rows of a window) comes off after the sum.

**Pooling groups.**  Windows may overlap (size 14, stride 13 on 27 rows
gives [0, 14) and [13, 27)), so the output positions are cut at every
window boundary into segments that lie wholly inside or outside each
window, and the rows of one (y segment, x segment) group are contiguous
and padded with zero rows to a multiple of 8: a group's sum is a sum of
whole (8, lanes) registers, and a window's sum is the sum of its groups.
A padding row has z = 0 exactly (its ones are zeros too), reads h in
``max(z, h)``, and comes off with the α's.

The kernel takes the rows of a tile of images and a block of filters a
grid step; ``conv_rectify_pool`` loops over tiles of images inside the
caller's program, so the temporaries are a tile's (``_TILE_IMAGES``),
whatever n is.  Off a TPU the same rows and the same epilogue run as
plain XLA (``_pooled_xla``), in float32.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: images per pass of the loop inside the program: what bounds the
#: program's temporaries (rows 0.2 MB and pooled output 0.32 MB an image
#: at the CIFAR widths)
_TILE_IMAGES = 1024
#: images and filters per grid step of the kernel, and the columns of one
#: product inside it (the (rows, columns) float32 product is what the
#: epilogue walks: 784 × 256 × 4 B = 0.8 MB)
_STEP_IMAGES = 8
_STEP_FILTERS = 1280
_DOT_COLUMNS = 256
#: float32 elements of z the XLA twin holds at once
_XLA_Z_ELEMENTS = 1 << 25


@dataclasses.dataclass(frozen=True)
class PoolGeometry:
    """How the (out_h, out_w) output positions are laid out as rows."""

    #: (y0, y1, x0, x1) of each group of positions, in row order
    groups: tuple
    #: [start, stop) of each group's rows (stop − start a multiple of 8)
    spans: tuple
    #: per pooling window (row-major over the pooled grid): its groups
    windows: tuple
    #: per window: rows that are padding, rows that are positions
    pad_rows: tuple
    real_rows: tuple
    #: all rows, a multiple of 16 (bf16 packs 16 rows a register)
    rows: int
    pooled_hw: tuple


def _segments(extent: int, stride: int, size: int) -> tuple:
    """(windows [lo, hi), segments [lo, hi) cut at every window boundary
    and lying inside at least one window)."""
    windows = [(stride * i, stride * i + size) for i in range((extent - size) // stride + 1)]
    cuts = sorted({c for w in windows for c in w})
    segs = [
        (a, b) for a, b in zip(cuts, cuts[1:])
        if any(lo <= a and b <= hi for lo, hi in windows)
    ]
    return windows, segs


@functools.lru_cache(maxsize=64)
def pool_geometry(out_h: int, out_w: int, stride: int, size: int) -> PoolGeometry:
    wy, sy = _segments(out_h, stride, size)
    wx, sx = _segments(out_w, stride, size)
    groups, spans, at = [], [], 0
    for y0, y1 in sy:
        for x0, x1 in sx:
            rows = -(-((y1 - y0) * (x1 - x0)) // 8) * 8
            groups.append((y0, y1, x0, x1))
            spans.append((at, at + rows))
            at += rows
    windows, pad_rows, real_rows = [], [], []
    for ylo, yhi in wy:
        for xlo, xhi in wx:
            members = tuple(
                g for g, (y0, y1, x0, x1) in enumerate(groups)
                if ylo <= y0 and y1 <= yhi and xlo <= x0 and x1 <= xhi
            )
            real = sum(
                (groups[g][1] - groups[g][0]) * (groups[g][3] - groups[g][2]) for g in members
            )
            windows.append(members)
            real_rows.append(real)
            pad_rows.append(sum(spans[g][1] - spans[g][0] for g in members) - real)
    return PoolGeometry(
        tuple(groups), tuple(spans), tuple(windows), tuple(pad_rows), tuple(real_rows),
        -(-at // 16) * 16, (len(wy), len(wx)),
    )


def _row_width(d: int) -> int:
    """Columns of a row: the d patch entries and two ones, to whole lanes."""
    return -(-(d + 2) // 128) * 128


def _patch_rows(xs, fh, fw, stride, normalize, var_constant, geom, dtype):
    """(t, H, W, C) images → (t, geom.rows, D) rows in ``dtype``: every
    output position's patch in ``conv_general_dilated_patches``' (c, dy,
    dx) order, normalised in float32 if asked, then two ones, then zeros
    up to D, a multiple of 128; grouped and padded as ``geom`` says."""
    # the extraction is a convolution with a one-hot kernel: exact at the
    # MXU's default for integer pixels (0..255 are bf16 numbers), and
    # asked for at full precision for anything else
    exact = None if jnp.issubdtype(xs.dtype, jnp.integer) else lax.Precision.HIGHEST
    with jax.named_scope("conv.stats"):
        patches = lax.conv_general_dilated_patches(
            xs.astype(jnp.float32), filter_shape=(fh, fw), window_strides=(stride, stride),
            padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=exact,
        )  # (t, oh, ow, c·fh·fw)
        d = patches.shape[-1]
        if normalize:
            from keystone_tpu.ops.images import normalize_rows

            patches = normalize_rows(patches, var_constant)
        width = _row_width(d)
        cols = jnp.concatenate(
            [patches, jnp.ones(patches.shape[:-1] + (2,), jnp.float32)], axis=-1
        ).astype(dtype)
        cols = jnp.pad(cols, ((0, 0),) * 3 + ((0, width - d - 2),))
        t = cols.shape[0]
        parts = []
        for (y0, y1, x0, x1), (lo, hi) in zip(geom.groups, geom.spans):
            g = cols[:, y0:y1, x0:x1].reshape(t, -1, width)
            parts.append(jnp.pad(g, ((0, 0), (0, hi - lo - g.shape[1]), (0, 0))))
        rows = jnp.concatenate(parts, axis=1)
        return jnp.pad(rows, ((0, 0), (0, geom.rows - rows.shape[1]), (0, 0)))


def _filter_columns(filters, offset, width: int, columns: int, dtype):
    """(K, fh, fw, c) filters and the (K,) additive offset → the product's
    right-hand side (width, columns) in ``dtype``: the filters in the
    rows' (c, dy, dx) order, the offset as ``dtype`` sees it, what that
    rounding lost, zeros."""
    k, fh, fw, c = filters.shape
    rhs = jnp.transpose(filters.astype(jnp.float32), (3, 1, 2, 0)).reshape(c * fh * fw, k)
    bias = jnp.zeros((k,), jnp.float32) if offset is None else offset.astype(jnp.float32)
    seen = bias.astype(dtype).astype(jnp.float32)
    rhs = jnp.concatenate([rhs, seen[None], (bias - seen)[None]], axis=0)
    return jnp.pad(rhs, ((0, width - rhs.shape[0]), (0, columns - k))).astype(dtype)


def _window_sums(z, geom: PoolGeometry, hi: float, alpha: float) -> list:
    """z (..., rows, columns) → per window (Σ max(z − α, m), Σ max(−z − α,
    m)), each (..., 1, columns), with hi = α + m ≥ 0."""
    with jax.named_scope("conv.pool"):
        q = jnp.maximum(z, hi)
        r = jnp.minimum(z, -hi)
        lead, cw = z.shape[:-2], z.shape[-1]

        def group_sums(v):
            return [
                jnp.sum(v[..., a:b, :].reshape(lead + (-1, 8, cw)), axis=-3)
                for a, b in geom.spans
            ]

        gq, gr = group_sums(q), group_sums(r)
        out = []
        for members, pad, real in zip(geom.windows, geom.pad_rows, geom.real_rows):
            off = hi * pad + alpha * real
            sq = functools.reduce(jnp.add, [gq[g] for g in members])
            sr = functools.reduce(jnp.add, [gr[g] for g in members])
            out.append((
                jnp.sum(sq, axis=-2, keepdims=True) - off,
                -jnp.sum(sr, axis=-2, keepdims=True) - off,
            ))
        return out


def _kernel(p_ref, g_ref, out_ref, *, geom, hi, alpha, dot_columns):
    step_images, _, step_filters = out_ref.shape

    def one_image(i, carry):
        rows = p_ref[i]
        for c in range(step_filters // dot_columns):
            cols = pl.ds(c * dot_columns, dot_columns)
            with jax.named_scope("conv.gemm"):
                z = jnp.dot(rows, g_ref[:, cols], preferred_element_type=jnp.float32)
            for w, (pos, neg) in enumerate(_window_sums(z, geom, hi, alpha)):
                out_ref[i, pl.ds(2 * w, 1), cols] = pos
                out_ref[i, pl.ds(2 * w + 1, 1), cols] = neg
        return carry

    lax.fori_loop(0, step_images, one_image, 0)


def step_filters_for(k: int) -> tuple:
    """(filters a grid step, columns a product) for k filters."""
    step = min(_STEP_FILTERS, -(-k // 128) * 128)
    return step, (_DOT_COLUMNS if step % _DOT_COLUMNS == 0 else 128)


def pooled_pallas(rows, rhs, k: int, geom: PoolGeometry, hi: float, alpha: float,
                  interpret: bool = False):
    """rows (t, R, D), rhs (D, columns) → (t, 2·windows, k) float32; t a
    multiple of ``_STEP_IMAGES``, columns a multiple of the step's filters.
    The last block of filters may hang over k: what it writes there is
    dropped."""
    t, r, d = rows.shape
    step, dot_columns = step_filters_for(k)
    out_rows = 2 * len(geom.windows)
    return pl.pallas_call(
        functools.partial(_kernel, geom=geom, hi=hi, alpha=alpha, dot_columns=dot_columns),
        grid=(t // _STEP_IMAGES, rhs.shape[1] // step),
        in_specs=[
            pl.BlockSpec((_STEP_IMAGES, r, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((d, step), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((_STEP_IMAGES, out_rows, step), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((t, out_rows, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 << 20,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * t * r * d * rhs.shape[1], transcendentals=0,
            bytes_accessed=rows.size * rows.dtype.itemsize + 4 * t * out_rows * k
            + (t // _STEP_IMAGES) * rhs.size * rhs.dtype.itemsize,
        ),
        name="conv_rectify_pool_pallas",
        interpret=interpret,
    )(rows, rhs)


def _pooled_xla(rows, rhs, k: int, geom: PoolGeometry, hi: float, alpha: float):
    """The kernel's arithmetic as plain XLA, a few images at a time."""
    def some(p):
        with jax.named_scope("conv.gemm"):
            z = jnp.einsum("trd,dk->trk", p, rhs, preferred_element_type=jnp.float32)
        sums = _window_sums(z, geom, hi, alpha)
        return jnp.concatenate([s for pair in sums for s in pair], axis=-2)[..., :k]

    at_once = max(1, _XLA_Z_ELEMENTS // (rows.shape[1] * rhs.shape[1]))
    if at_once >= rows.shape[0]:
        return some(rows)
    return lax.map(lambda p: some(p[None])[0], rows, batch_size=at_once)


def conv_rectify_pool(
    xs, filters, offset, *, stride: int, normalize: bool, var_constant: float,
    alpha: float, max_val: float, pool_stride: int, pool_size: int, dtype,
    use_pallas: bool, interpret: bool = False,
):
    """(n, H, W, C) images → (n, pooled_h · pooled_w · 2K) float32: the
    flattened ``Pooler(SymmetricRectifier(Convolver(x)))`` with channels
    [positive K, negative K] at each pooled position.  ``dtype`` is what
    the product streams (the featurize policy's: bf16 on a TPU)."""
    n, h, w, _ = xs.shape
    k, fh, fw, _ = filters.shape
    geom = pool_geometry((h - fh) // stride + 1, (w - fw) // stride + 1, pool_stride, pool_size)
    hi = float(alpha) + float(max_val)
    if hi < 0:
        raise ValueError("conv_rectify_pool needs alpha + max_val >= 0")
    step, _ = step_filters_for(k)
    rhs = _filter_columns(filters, offset, _row_width(fh * fw * filters.shape[3]),
                          -(-k // step) * step, dtype)
    tile = min(_TILE_IMAGES, -(-n // _STEP_IMAGES) * _STEP_IMAGES)
    tiles = -(-n // tile)
    if tiles * tile != n:
        xs = jnp.pad(xs, ((0, tiles * tile - n),) + ((0, 0),) * 3)

    def one_tile(x):
        rows = _patch_rows(x, fh, fw, stride, normalize, var_constant, geom, dtype)
        if use_pallas:
            pooled = pooled_pallas(rows, rhs, k, geom, hi, float(alpha), interpret)
        else:
            pooled = _pooled_xla(rows, rhs, k, geom, hi, float(alpha))
        return pooled.reshape(x.shape[0], -1)

    if tiles == 1:
        return one_tile(xs)[:n]
    features = 2 * len(geom.windows) * k

    def body(i, out):
        x = lax.dynamic_slice_in_dim(xs, i * tile, tile)
        return lax.dynamic_update_slice_in_dim(out, one_tile(x), i * tile, axis=0)

    out = lax.fori_loop(0, tiles, body, jnp.zeros((tiles * tile, features), jnp.float32))
    return out[:n]
