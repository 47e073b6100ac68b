"""Image feature ops (reference src/main/scala/nodes/images/).

Images are batched NHWC float arrays.  The reference's per-image
im2col + BLAS gemm loops (executor map tasks) become whole-batch XLA
convolutions that tile directly onto the MXU.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from keystone_tpu.workflow.transformer import Transformer
from keystone_tpu.utils import precision


class Convolver(Transformer):
    """Convolution of K learned/random filters over images
    (nodes/images/Convolver.scala — the CIFAR feature extractor).

    ``filters``: (num_filters, fh, fw, c).  The reference's optional patch
    whitening is folded into the filters/offset via
    :meth:`from_whitened_patches`: convolving ZCA-whitened patches with
    raw filters equals convolving raw patches with ``W_zca·filters`` plus
    a constant offset — one gemm instead of two.

    Two physical forms (the reference's NodeOptimizationRule chose conv
    strategies the same way — SURVEY.md §2.1):

    - ``"direct"`` — ``lax.conv_general_dilated``, XLA's native conv path;
    - ``"im2col"`` — explicit patch extraction + ONE (N·OH·OW, fh·fw·c) ×
      (fh·fw·c, K) gemm, the reference's own execution strategy and a
      better MXU mapping when the patch dim and filter count are both
      MXU-friendly (≥~128) while the conv is small;
    - ``"auto"`` (default) — resolved per shape by
      ``_IM2COL_MAX_PATCH_ELEMENTS`` (see there for what was measured),
      pinned to a concrete form by the optimizer's NodeChoiceRule when it
      samples.

    ``normalize_patches`` is upstream's ``normalizePatches``: every patch
    has its mean taken off and is divided by √(variance + ``var_constant``)
    (``Stats.normalizeRows``: the variance over d − 1) BEFORE it meets the
    filters.  That is not linear in the image, so no filter bank folds it
    in: a normalising Convolver extracts its patches (im2col, whatever
    ``strategy`` says) and normalises them in float32 first.  The
    whitener's fold (:meth:`from_whitened_patches`) is linear in the
    NORMALISED patch and stays.  Followed by a SymmetricRectifier and a
    sum Pooler it is fused into :class:`PooledConvolver` by the optimizer
    and its activation is never written.
    """

    strategy = "auto"  # class default for pre-strategy pickles
    normalize_patches = False  # … and for pre-normalisation ones
    var_constant = 10.0
    # fitted filters/offset ride as traced jit arguments (refits and
    # sibling instances share programs; no lowering read-back)
    traced_attrs = ("filters", "offset")

    def jit_static(self):
        return (self.stride, self.strategy, self.normalize_patches, self.var_constant)

    def __init__(
        self,
        filters: jnp.ndarray,
        stride: int = 1,
        offset=None,
        strategy: str = "auto",
        normalize_patches: bool = False,
        var_constant: float = 10.0,
    ):
        if strategy not in ("auto", "direct", "im2col"):
            raise ValueError(f"unknown Convolver strategy {strategy!r}")
        self.filters = jnp.asarray(filters, jnp.float32)
        self.stride = int(stride)
        self.offset = offset  # (num_filters,) additive term
        self.strategy = strategy
        self.normalize_patches = bool(normalize_patches)
        self.var_constant = float(var_constant)

    @classmethod
    def from_whitened_patches(
        cls, patches: jnp.ndarray, whitener, patch_shape, stride: int = 1,
        normalize_patches: bool = False, var_constant: float = 10.0,
    ) -> "Convolver":
        """Build from flat random patches + a fitted ZCAWhitener
        (RandomPatchCifar pattern): filters = (W_zca · Pᵀ) reshaped,
        offset = −mean·W_zca·Pᵀ; with ``normalize_patches`` the whitener
        is taken to have been fitted on normalised patches, and the
        convolver normalises each image patch before it applies them.
        The two products enter every feature: solver-grade."""
        fh, fw, c = patch_shape
        p = jnp.asarray(patches, jnp.float32)  # (K, fh*fw*c), whitened space
        w_eff = precision.sdot(whitener.whitener, p.T)  # (d, K)
        offset = -precision.sdot(whitener.mean, w_eff)  # (K,)
        filters = w_eff.T.reshape(-1, fh, fw, c)
        return cls(filters, stride=stride, offset=offset,
                   normalize_patches=normalize_patches, var_constant=var_constant)

    def params(self):
        from keystone_tpu.utils.hashing import cached_fingerprint

        if self.offset is None:
            fp = cached_fingerprint(self, "_fp", self.filters)
        else:
            fp = cached_fingerprint(self, "_fp", self.filters, self.offset)
        return (
            self.filters.shape,
            fp,
            self.stride,
            self.offset is None,
            self.strategy,
            self.normalize_patches,
            self.var_constant,
        )

    def choose_physical(self, sample):
        """Pin ``"auto"`` to the measured-best concrete strategy for the
        sampled image shape (NodeOptimizationRule conv choice)."""
        if self.strategy != "auto" or sample is None or sample.is_host:
            return self
        shape = tuple(sample.array.shape)
        if len(shape) == 3:
            shape = shape + (1,)
        if len(shape) != 4:
            return self
        picked = _pick_conv_strategy(
            shape[1], shape[2], self.filters.shape, self.stride
        )
        return Convolver(
            self.filters, stride=self.stride, offset=self.offset, strategy=picked,
            normalize_patches=self.normalize_patches, var_constant=self.var_constant,
        )

    def apply_batch(self, xs, mask=None):
        # The FEATURIZE bf16 policy skips the Convolver (XLA's default
        # precision already runs f32 convs as bf16-grade MXU passes;
        # explicit casts measured 0.94× at CIFAR shapes in isolation).
        # The opt-in APPLY policy ('bf16_apply') converts it anyway: in a
        # fused forward program the casts halve the inter-stage streams,
        # and accumulation stays f32 (utils/precision.apply_dot/acast).
        # apply_mode() is resolved at trace time; every jit wrapper that
        # traces this (per-instance, class-shared, fused-chain) keys its
        # cache on the resolved mode.
        if xs.ndim == 3:
            xs = xs[..., None]
        xs = xs.astype(jnp.float32)
        mxu = precision.apply_mode()
        strategy = "im2col" if self.normalize_patches else self.strategy
        if strategy == "auto":
            strategy = _pick_conv_strategy(
                xs.shape[1], xs.shape[2], self.filters.shape, self.stride
            )
        if strategy == "im2col":
            out = self._apply_im2col(xs, mxu)
        else:
            rhs = jnp.transpose(self.filters, (1, 2, 3, 0))  # HWIO
            if mxu == "bf16_apply":
                xs_c, rhs_c = precision.acast(xs, rhs, mode=mxu)
                out = lax.conv_general_dilated(
                    xs_c,
                    rhs_c,
                    window_strides=(self.stride, self.stride),
                    padding="VALID",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    preferred_element_type=jnp.float32,
                )
            else:
                out = lax.conv_general_dilated(
                    xs,
                    rhs,
                    window_strides=(self.stride, self.stride),
                    padding="VALID",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                )
        if self.offset is not None:
            out = out + self.offset
        return out

    def _apply_im2col(self, xs, mxu: str = "f32"):
        """Patch extraction + one gemm — the reference's own execution
        plan (Windower im2col → BLAS gemm, SURVEY.md §3.3), mapped to the
        MXU as a single (N·OH·OW, fh·fw·c) × (fh·fw·c, K) contraction."""
        k, fh, fw, c = self.filters.shape
        n, h, w, _ = xs.shape
        patches = lax.conv_general_dilated_patches(
            xs,
            filter_shape=(fh, fw),
            window_strides=(self.stride, self.stride),
            padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )  # (n, oh, ow, c*fh*fw) — channel-major patch layout
        oh, ow = patches.shape[1], patches.shape[2]
        if self.normalize_patches:
            patches = normalize_rows(patches, self.var_constant)
        # filters (k, fh, fw, c) -> (c, fh, fw, k) flattened to match the
        # patches' (c, fh, fw) minor order
        rhs = jnp.transpose(self.filters, (3, 1, 2, 0)).reshape(c * fh * fw, k)
        out = precision.apply_dot(
            patches.reshape(n * oh * ow, c * fh * fw), rhs, mode=mxu
        )
        return out.reshape(n, oh, ow, k)

    def apply_one(self, x):
        return self.apply_batch(x[None])[0]


def normalize_rows(patches, var_constant: float):
    """Upstream's ``Stats.normalizeRows`` over the last axis: the mean
    taken off, then divided by √(variance over d − 1, plus the constant)."""
    centred = patches - jnp.mean(patches, axis=-1, keepdims=True)
    var = jnp.sum(centred * centred, axis=-1, keepdims=True) / (patches.shape[-1] - 1.0)
    return centred * lax.rsqrt(var + var_constant)


#: The im2col patches tensor per image — (oh·ow) positions × (fh·fw·c)
#: patch dim — up to which ``"auto"`` takes patch-extract + gemm and above
#: which XLA's conv emitter.  It decides only for a Convolver that stands
#: alone and does not normalise its patches: a normalising one extracts
#: patches whatever this says, and one followed by a rectifier and a sum
#: pooler is fused into ``PooledConvolver``, which has no such choice
#: (RandomPatchCifar at 32 × 32 × 3 with 6 × 6 patches is 78,732 elements
#: and never asks).  Read once on the v5e (my chip run, PR 32; 6 × 6 × 3
#: filters, µs an image, direct / im2col): at 256 filters 0.24 / 0.25 at
#: 13,068 elements, 0.40 / 0.55 at 24,300, 0.64 / 0.89 at 38,988, 0.99 /
#: 1.29 at 57,132, 2.75 / 3.15 at 62,208, 1.23 / 1.73 at 78,732; at 1024
#: filters 2.46 / 2.44 at 38,988 and 4.84 / 5.25 at 78,732.  So the older
#: rounds' crossover is not borne out at these widths: below the constant
#: im2col is level at best and up to 1.4× slower.  The value stands until
#: a `perf_opt` issue reads it over more filter shapes (PERF.md §7).
_IM2COL_MAX_PATCH_ELEMENTS = 58_000


def _pick_conv_strategy(h: int, w: int, filter_shape, stride: int) -> str:
    k, fh, fw, c = filter_shape
    oh = max(0, (h - fh) // stride + 1)
    ow = max(0, (w - fw) // stride + 1)
    if oh * ow * fh * fw * c <= _IM2COL_MAX_PATCH_ELEMENTS:
        return "im2col"
    return "direct"


class Pooler(Transformer):
    """Spatial pooling over a grid with a pluggable pixel function
    (nodes/images/Pooler.scala): out[g] = Σ_{p∈cell g} pixel_fn(x[p])."""

    def __init__(
        self,
        stride: int,
        pool_size: int,
        pixel_fn: Optional[Callable] = None,
        pool_mode: str = "sum",
    ):
        self.stride = int(stride)
        self.pool_size = int(pool_size)
        self.pixel_fn = pixel_fn
        self.pool_mode = pool_mode

    def params(self):
        # a pixel function has no identity to compare: such a pooler is
        # never merged and keeps its own program (TermFrequency's rule)
        if self.pixel_fn is not None:
            return None
        return (self.stride, self.pool_size, self.pool_mode)

    def apply_batch(self, xs, mask=None):
        x = xs.astype(jnp.float32)
        if self.pixel_fn is not None:
            x = self.pixel_fn(x)
        dims = (1, self.pool_size, self.pool_size, 1)
        strides = (1, self.stride, self.stride, 1)
        if self.pool_mode == "sum":
            return lax.reduce_window(x, 0.0, lax.add, dims, strides, "VALID")
        if self.pool_mode == "max":
            return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, "VALID")
        raise ValueError(f"unknown pool mode {self.pool_mode}")

    def apply_one(self, x):
        return self.apply_batch(x[None])[0]


class SymmetricRectifier(Transformer):
    """Channel-doubling rectifier [max(0, x−α), max(0, −x−α)]
    (nodes/images/SymmetricRectifier.scala)."""

    def __init__(self, max_val: float = 0.0, alpha: float = 0.0):
        self.max_val = float(max_val)
        self.alpha = float(alpha)

    def params(self):
        return (self.max_val, self.alpha)

    def apply_batch(self, xs, mask=None):
        pos = jnp.maximum(xs - self.alpha, self.max_val)
        neg = jnp.maximum(-xs - self.alpha, self.max_val)
        return jnp.concatenate([pos, neg], axis=-1)

    def apply_one(self, x):
        return self.apply_batch(x[None])[0]


class PooledConvolver(Transformer):
    """``Convolver`` → ``SymmetricRectifier`` → sum ``Pooler`` (→
    ``ImageVectorizer``) as ONE node whose program never writes the
    convolution's activation (ops/conv_pool_pallas.py): what the optimizer
    makes of that chain (``workflow/optimizer.py § ConvPoolFusionRule``).
    At RandomPatchCifar's widths the activation is 29 MB an image and the
    pooled output 0.32 MB.

    ``owns_tiling``: the program loops over tiles of images itself and
    writes each tile's pooled rows into its one output, so the node is
    applied whole (no chunk of rows is offered to it, and no chunk
    outputs are held beside their concatenation).
    """

    traced_attrs = ("filters", "offset")
    owns_tiling = True

    def __init__(self, conv: Convolver, rectifier: "SymmetricRectifier",
                 pooler: Pooler, vectorize: bool):
        self.filters = conv.filters
        self.offset = conv.offset
        self.stride = conv.stride
        self.normalize_patches = conv.normalize_patches
        self.var_constant = conv.var_constant
        self.alpha = rectifier.alpha
        self.max_val = rectifier.max_val
        self.pool_stride = pooler.stride
        self.pool_size = pooler.pool_size
        self.vectorize = bool(vectorize)
        # the convolver's identity (a pinned recipe, or a digest it has
        # already paid for) is this node's too
        cached = getattr(conv, "_fp", None)
        if cached is not None:
            self._fp = cached

    @staticmethod
    def fuses(conv, rectifier, pooler) -> bool:
        """Whether the chain is the fused program's: a sum pooler of plain
        pixels over a rectifier whose two thresholds are not negative."""
        return (
            pooler.pool_mode == "sum" and pooler.pixel_fn is None
            and rectifier.alpha + rectifier.max_val >= 0
        )

    def jit_static(self):
        return (
            self.stride, self.normalize_patches, self.var_constant, self.alpha,
            self.max_val, self.pool_stride, self.pool_size, self.vectorize,
        )

    def params(self):
        from keystone_tpu.utils.hashing import cached_fingerprint

        arrays = (self.filters,) if self.offset is None else (self.filters, self.offset)
        return (self.filters.shape, cached_fingerprint(self, "_fp", *arrays),
                self.offset is None) + self.jit_static()

    def apply_batch(self, xs, mask=None):
        from keystone_tpu.ops.conv_pool_pallas import conv_rectify_pool, pool_geometry
        from keystone_tpu.ops.fisher_pallas import pallas_supported

        if xs.ndim == 3:
            xs = xs[..., None]
        flat = conv_rectify_pool(
            xs, self.filters, self.offset, stride=self.stride,
            normalize=self.normalize_patches, var_constant=self.var_constant,
            alpha=self.alpha, max_val=self.max_val, pool_stride=self.pool_stride,
            pool_size=self.pool_size, dtype=precision.fdtype(),
            use_pallas=pallas_supported(),
        )
        if self.vectorize:
            return flat
        _, fh, fw, _ = self.filters.shape
        ph, pw = pool_geometry(
            (xs.shape[1] - fh) // self.stride + 1, (xs.shape[2] - fw) // self.stride + 1,
            self.pool_stride, self.pool_size,
        ).pooled_hw
        return flat.reshape(xs.shape[0], ph, pw, -1)

    def apply_one(self, x):
        return self.apply_batch(x[None])[0]


class GrayScaler(Transformer):
    """NHWC → NHW luminance via channel mean (nodes/images/GrayScaler.scala)."""

    def params(self):
        return ()

    def apply_batch(self, xs, mask=None):
        if xs.ndim == 3 or xs.shape[-1] == 1:
            return xs.reshape(xs.shape[:3])
        return jnp.mean(xs, axis=-1)

    def apply_one(self, x):
        return self.apply_batch(x[None])[0]


class ImageVectorizer(Transformer):
    """Image → flat vector (nodes/images/ImageVectorizer.scala)."""

    def params(self):
        return ()

    def apply_batch(self, xs, mask=None):
        return xs.reshape(xs.shape[0], -1)

    def apply_one(self, x):
        return x.reshape(-1)


class PixelScaler(Transformer):
    """uint8 pixels → [0,1] floats (nodes/images/PixelScaler.scala).

    ``only_if_integer=True`` divides only integer inputs and passes
    floating inputs through as f32 — for pipelines whose loaders ship
    uint8 (cheap transfer) but that must also accept pre-normalized
    [0,1] float arrays without silently collapsing them to ~1/255 scale.
    (The default stays unconditional: e.g. MNIST CSV loads *floats* in
    [0,255] that genuinely need the division.)  The dtype check is
    static at trace time — no runtime branch under jit.
    """

    def __init__(self, scale: float = 255.0, only_if_integer: bool = False):
        self.scale = float(scale)
        self.only_if_integer = bool(only_if_integer)

    def params(self):
        return (self.scale, self.only_if_integer)

    def apply_batch(self, xs, mask=None):
        if self.only_if_integer and jnp.issubdtype(
            jnp.asarray(xs).dtype, jnp.floating
        ):
            return jnp.asarray(xs, jnp.float32)
        return xs.astype(jnp.float32) / self.scale

    def apply_one(self, x):
        return self.apply_batch(jnp.asarray(x)[None])[0]


class Windower(Transformer):
    """Sliding-window patch extraction (nodes/images/Windower.scala):
    (n, H, W, C) → (n, num_windows, wh·ww·C) flat patches."""

    def __init__(self, step: int, window_size: int):
        self.step = int(step)
        self.window_size = int(window_size)

    def params(self):
        return (self.step, self.window_size)

    def apply_batch(self, xs, mask=None):
        if xs.ndim == 3:
            xs = xs[..., None]
        n, h, w, c = xs.shape
        ws = self.window_size
        patches = lax.conv_general_dilated_patches(
            xs.astype(jnp.float32),
            filter_shape=(ws, ws),
            window_strides=(self.step, self.step),
            padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )  # (n, H', W', C*ws*ws) with feature index (c, dy, dx)
        hp, wp = patches.shape[1], patches.shape[2]
        # reorder feature dim (c, dy, dx) -> (dy, dx, c) to match
        # row-major patch flattening
        patches = patches.reshape(n, hp * wp, c, ws, ws)
        patches = jnp.transpose(patches, (0, 1, 3, 4, 2))
        return patches.reshape(n, hp * wp, ws * ws * c)

    def apply_one(self, x):
        return self.apply_batch(x[None])[0]


class RandomPatcher(Transformer):
    """Random patch extraction (nodes/images/RandomPatcher.scala):
    (n, H, W, C) → (n·num_patches, ph·pw·C) — train-time feature learning."""

    fusable = False

    def __init__(self, num_patches: int, patch_h: int, patch_w: int, seed: int = 0):
        self.num_patches = int(num_patches)
        self.patch_h = int(patch_h)
        self.patch_w = int(patch_w)
        self.seed = int(seed)

    def params(self):
        return (self.num_patches, self.patch_h, self.patch_w, self.seed)

    def apply_batch(self, xs, mask=None):
        if xs.ndim == 3:
            xs = xs[..., None]
        return _random_patches(
            xs.astype(jnp.float32),
            self.num_patches,
            self.patch_h,
            self.patch_w,
            jax.random.PRNGKey(self.seed),
        )

    def apply_dataset(self, ds):
        out = self.apply_batch(ds.array[: ds.n])
        from keystone_tpu.workflow.dataset import Dataset

        return Dataset(out)

    def apply_one(self, x):
        return self.apply_batch(x[None])[0]


@partial(jax.jit, static_argnames=("k", "ph", "pw"))
def _random_patches(xs, k, ph, pw, key):
    n, h, w, c = xs.shape
    ky, kx = jax.random.split(key)
    ys = jax.random.randint(ky, (n, k), 0, h - ph + 1)
    xoff = jax.random.randint(kx, (n, k), 0, w - pw + 1)

    def one(img, yy, xx):
        def slice_one(y0, x0):
            return lax.dynamic_slice(img, (y0, x0, 0), (ph, pw, c))

        return jax.vmap(slice_one)(yy, xx)

    patches = jax.vmap(one)(xs, ys, xoff)  # (n, k, ph, pw, c)
    return patches.reshape(n * k, ph * pw * c)


class CenterCornerPatcher(Transformer):
    """Center + 4 corner crops, optionally horizontally flipped
    (nodes/images/CenterCornerPatcher.scala) — the 10-view test-time
    augmentation for ImageNet.  Output: (n, num_views, ph, pw, C)."""

    def __init__(self, patch_h: int, patch_w: int, horizontal_flips: bool = False):
        self.patch_h = int(patch_h)
        self.patch_w = int(patch_w)
        self.horizontal_flips = horizontal_flips

    def params(self):
        return (self.patch_h, self.patch_w, self.horizontal_flips)

    def apply_batch(self, xs, mask=None):
        if xs.ndim == 3:
            xs = xs[..., None]
        n, h, w, c = xs.shape
        ph, pw = self.patch_h, self.patch_w
        starts = [
            (0, 0),
            (0, w - pw),
            (h - ph, 0),
            (h - ph, w - pw),
            ((h - ph) // 2, (w - pw) // 2),
        ]
        views = [xs[:, y : y + ph, x : x + pw, :] for (y, x) in starts]
        if self.horizontal_flips:
            views = views + [v[:, :, ::-1, :] for v in views]
        return jnp.stack(views, axis=1)

    def apply_one(self, x):
        return self.apply_batch(x[None])[0]
