"""Statistical feature ops (reference src/main/scala/nodes/stats/).

All device ops are natively batched (apply_batch on the sharded (n, d)
array) and fusable, so chains like RandomSign → PaddedFFT → Rectifier
compile into one XLA stage.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.models.common import constrain
from keystone_tpu.parallel.mesh import DATA_AXIS
from keystone_tpu.utils.hashing import cached_fingerprint, pin_recipe
from keystone_tpu.workflow.dataset import Dataset
from keystone_tpu.workflow.estimator import Estimator
from keystone_tpu.workflow.transformer import Transformer


class CosineRandomFeatures(Transformer):
    """Random Fourier features: cos(x·Wᵀ + b)
    (nodes/stats/CosineRandomFeatures.scala — TIMIT's featurizer).

    W rows ~ Gaussian(0, γ) for the RBF kernel or Cauchy(0, γ) for the
    Laplacian kernel; b ~ Uniform[0, 2π].

    Identity (``params()``): a node from :meth:`init` signs with the
    recipe of its draw — seed, gamma, distribution, shape, PRNG and
    versions — for as long as ``w`` and ``b`` are the arrays ``init``
    made, and reads none of their bytes; a node built from given arrays,
    or one whose ``w`` or ``b`` was reassigned, signs with a digest of
    their content (``utils/hashing.py``).  Equal signatures mean equal
    values.  The reverse is not promised: ``init(seed=3)`` and
    ``CosineRandomFeatures(w, b)`` holding the same bytes do not merge
    under CSE, which costs a recomputation and is never a wrong answer.
    """

    # TIMIT gathers many instances of this class with identical shapes —
    # traced parameters make them share ONE compiled program per shape
    # (Transformer.traced_attrs)
    traced_attrs = ("w", "b")

    def __init__(self, w: jnp.ndarray, b: jnp.ndarray):
        self.w = w  # (num_out, num_in)
        self.b = b  # (num_out,)

    @classmethod
    def init(
        cls,
        num_input_features: int,
        num_output_features: int,
        gamma: float = 1.0,
        seed: int = 0,
        distribution: str = "gaussian",
    ) -> "CosineRandomFeatures":
        kw, kb = jax.random.split(jax.random.PRNGKey(seed))
        shape = (num_output_features, num_input_features)
        if distribution == "gaussian":
            w = gamma * jax.random.normal(kw, shape, jnp.float32)
        elif distribution == "cauchy":
            w = gamma * jax.random.cauchy(kw, shape, jnp.float32)
        else:
            raise ValueError(f"unknown distribution {distribution!r}")
        b = jax.random.uniform(kb, (num_output_features,), jnp.float32, 0.0, 2 * np.pi)
        node = cls(w, b)
        # ``draw`` names the lines above: change it with them
        pin_recipe(
            node, "_fp", (w, b), draw="split(PRNGKey(seed)):w=gamma*dist,b=U[0,2pi)",
            seed=int(seed), gamma=float(gamma), distribution=distribution,
        )
        return node

    def params(self):
        return (self.w.shape, cached_fingerprint(self, "_fp", self.w, self.b))

    def apply_batch(self, xs, mask=None):
        # Deliberately NOT under the bf16 matmul policy: the phase xWᵀ is
        # unbounded, so bf16's ~0.4% relative rounding becomes an absolute
        # phase error that wraps through cos with O(1) feature error
        # (measured: 0.4 rad at |phase|≈100).  Random-feature quality
        # depends on phase fidelity; keep f32.
        return jnp.cos(xs @ self.w.T + self.b)

    def apply_one(self, x):
        return jnp.cos(self.w @ x + self.b)


class RandomSignNode(Transformer):
    """Elementwise Rademacher sign flip (nodes/stats/RandomSignNode.scala);
    paired with PaddedFFT for fastfood-style random features.

    Signs as ``CosineRandomFeatures`` does: by the recipe of the draw
    while ``signs`` is the array :meth:`init` made, by content otherwise."""

    traced_attrs = ("signs",)  # MNIST gathers N sign-flip branches

    def __init__(self, signs: jnp.ndarray):
        self.signs = signs

    @classmethod
    def init(cls, num_features: int, seed: int = 0) -> "RandomSignNode":
        bits = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.5, (num_features,))
        node = cls(bits.astype(jnp.float32) * 2.0 - 1.0)
        # ``draw`` names the lines above: change it with them
        pin_recipe(
            node, "_fp", (node.signs,), draw="2*bernoulli(PRNGKey(seed),0.5)-1",
            seed=int(seed),
        )
        return node

    def params(self):
        return (self.signs.shape[0], cached_fingerprint(self, "_fp", self.signs))

    def apply_batch(self, xs, mask=None):
        return xs * self.signs

    def apply_one(self, x):
        return x * self.signs


class PaddedFFT(Transformer):
    """Zero-pad to the next power of two and take a real FFT
    (nodes/stats/PaddedFFT.scala — MNIST's featurizer).

    Output = [Re(rfft), Im(rfft)] of the positive-frequency half (the
    reference emits the complex spectrum's components as a real vector;
    concatenation keeps full information with static shapes).  The FFT is
    unitary (norm="ortho") so feature magnitudes stay at the input's
    scale — important for the f32 normal-equation solvers downstream
    (the f64-everywhere reference didn't need this).
    """

    def params(self):
        return ()

    def apply_batch(self, xs, mask=None):
        d = xs.shape[-1]
        padded = 1 << (d - 1).bit_length()
        xs = jnp.pad(xs, [(0, 0)] * (xs.ndim - 1) + [(0, padded - d)])
        spec = jnp.fft.rfft(xs, axis=-1, norm="ortho")
        return jnp.concatenate([jnp.real(spec), jnp.imag(spec)], axis=-1)

    def apply_one(self, x):
        return self.apply_batch(x[None])[0]


class LinearRectifier(Transformer):
    """max(x − α, maxVal) (nodes/stats/LinearRectifier.scala)."""

    def __init__(self, max_val: float = 0.0, alpha: float = 0.0):
        self.max_val = float(max_val)
        self.alpha = float(alpha)

    def params(self):
        return (self.max_val, self.alpha)

    def apply_batch(self, xs, mask=None):
        return jnp.maximum(xs - self.alpha, self.max_val)

    def apply_one(self, x):
        return jnp.maximum(x - self.alpha, self.max_val)


class SignedHellingerMapper(Transformer):
    """sign(x)·√|x| (nodes/stats/SignedHellingerMapper.scala) — the
    power-normalization step after Fisher-vector encoding."""

    def params(self):
        return ()

    def apply_batch(self, xs, mask=None):
        out = jnp.sign(xs) * jnp.sqrt(jnp.abs(xs))
        return (out, mask) if mask is not None else out

    def apply_one(self, x):
        return jnp.sign(x) * jnp.sqrt(jnp.abs(x))


class NormalizeRows(Transformer):
    """L2 row normalization (nodes/stats/NormalizeRows.scala)."""

    def __init__(self, eps: float = 1e-12):
        self.eps = float(eps)

    def params(self):
        return (self.eps,)

    def apply_batch(self, xs, mask=None):
        norm = jnp.sqrt(jnp.sum(xs * xs, axis=-1, keepdims=True))
        out = xs / jnp.maximum(norm, self.eps)
        return (out, mask) if mask is not None else out

    def apply_one(self, x):
        return x / jnp.maximum(jnp.sqrt(jnp.sum(x * x)), self.eps)


class StandardScalerModel(Transformer):
    traced_attrs = ("mean", "std")

    def __init__(self, mean: jnp.ndarray, std: Optional[jnp.ndarray] = None):
        self.mean = mean
        self.std = std

    def apply_batch(self, xs, mask=None):
        out = xs - self.mean
        if self.std is not None:
            out = out / self.std
        return out

    def apply_one(self, x):
        return self.apply_batch(x[None])[0]


class StandardScaler(Estimator):
    """Column mean/std via sharded moment sums — the treeAggregate
    col-stats of nodes/stats/StandardScaler.scala."""

    def __init__(self, normalize_std: bool = True, eps: float = 1e-8):
        self.normalize_std = normalize_std
        self.eps = float(eps)

    def params(self):
        return (self.normalize_std, self.eps)

    def fit_dataset(self, data: Dataset) -> StandardScalerModel:
        from keystone_tpu.workflow.dataset import StreamDataset

        if isinstance(data, StreamDataset):
            return self.fit_stream(data.batches)
        return self._fit(data.array, data.n)

    def fit_arrays(self, x) -> StandardScalerModel:
        x = jnp.asarray(x, jnp.float32)
        return self._fit(x, x.shape[0])

    def _fit(self, x, n):
        mean, std = _moments(x, jnp.float32(n))
        if not self.normalize_std:
            return StandardScalerModel(mean, None)
        return StandardScalerModel(mean, jnp.maximum(std, self.eps))

    def fit_stream(self, batches) -> StandardScalerModel:
        """Out-of-core moments from a stream of (n_i, d) host batches
        (companion of LinearMapEstimator.fit_stream; same contract:
        a callable returning a fresh iterator, or a re-iterable).

        Two passes: means, then Σ(x − mean)² of EXPLICITLY centered
        batches — the one-pass ``Σx² − n·mean²`` shortcut cancels
        catastrophically in f32 for large-mean/small-spread columns
        (std collapses to eps and scaled features explode).  Sums are
        Kahan-compensated across batches."""
        from keystone_tpu.models.common import stage_stream_batch

        get = batches if callable(batches) else lambda: iter(batches)
        sums = None
        n = 0
        for b in get():
            x, bn, row_ok = stage_stream_batch(b)
            n += bn
            sums = _acc_col_sums(sums, x)
        if n == 0:
            raise ValueError("empty batch stream")
        mean = sums[0] / n
        sq = None
        n2 = 0
        for b in get():
            x, bn, row_ok = stage_stream_batch(b)
            n2 += bn
            sq = _acc_centered_sq(sq, x, mean, row_ok)
        if n2 != n:
            raise ValueError(
                f"batch stream is not re-iterable: first pass saw {n} rows, "
                f"second pass {n2}. Pass a CALLABLE returning a fresh "
                "iterator (or a re-iterable like a list)."
            )
        var = sq[0] / max(n - 1.0, 1.0)  # unbiased, like _moments
        if not self.normalize_std:
            return StandardScalerModel(mean, None)
        return StandardScalerModel(mean, jnp.maximum(jnp.sqrt(var), self.eps))


@jax.jit
def _acc_col_sums(carry, x):
    """carry = (s1, c1): Kahan-compensated Σx columns."""
    from keystone_tpu.models.common import kahan_add

    b1 = jnp.sum(x, axis=0)
    if carry is None:
        return b1, jnp.zeros_like(b1)
    s1, c1 = carry
    return kahan_add(s1, c1, b1)


@jax.jit
def _acc_centered_sq(carry, x, mean, row_ok):
    """carry = (s2, c2): Kahan-compensated Σ(x − mean)² columns; the mask
    keeps shard-padding rows (which would center to −mean) at zero."""
    from keystone_tpu.models.common import kahan_add

    xc = (x - mean) * row_ok
    b2 = jnp.sum(xc * xc, axis=0)
    if carry is None:
        return b2, jnp.zeros_like(b2)
    s2, c2 = carry
    return kahan_add(s2, c2, b2)


@jax.jit
def _moments(x, n):
    x = constrain(x.astype(jnp.float32), DATA_AXIS)
    s1 = constrain(jnp.sum(x, axis=0))
    mean = s1 / n
    # EXPLICIT centering before the square: the Σx² − n·mean² shortcut
    # cancels catastrophically in f32 for large-mean/small-spread columns
    # (hypothesis found 2% std error at mean≈30; worse cases collapse to
    # 0).  Padding rows are zero, so they must be masked after centering.
    row_ok = (jnp.arange(x.shape[0]) < n).astype(jnp.float32)[:, None]
    xc = (x - mean) * row_ok
    s2c = constrain(jnp.sum(xc * xc, axis=0))
    # unbiased, like Breeze's stddev (n-1 denominator)
    var = s2c / jnp.maximum(n - 1.0, 1.0)
    return mean, jnp.sqrt(var)


class Sampler(Transformer):
    """Row subsampling with a fixed seed (nodes/stats/Sampler.scala);
    used to cut datasets down for PCA/GMM fitting."""

    is_host = False
    fusable = False

    def __init__(self, size: int, seed: int = 0):
        self.size = int(size)
        self.seed = int(seed)

    def params(self):
        return (self.size, self.seed)

    def apply_dataset(self, ds: Dataset) -> Dataset:
        k = min(self.size, ds.n)
        idx = np.random.default_rng(self.seed).choice(ds.n, size=k, replace=False)
        return Dataset(np.asarray(ds.array)[np.sort(idx)])

    def apply_one(self, x):
        return x


class ColumnSampler(Transformer):
    """Sample ``num_samples`` descriptors per item from ragged descriptor
    sets (nodes/stats/ColumnSampler.scala — the reference samples columns
    of per-image descriptor matrices before PCA/GMM fitting).

    Input: Dataset with array (n, max_k, d) + mask (n, max_k).
    Output: flat dense Dataset (n·num_samples, d), sampling only valid
    descriptors (with replacement when an item has fewer than requested).
    """

    fusable = False

    def __init__(self, num_samples: int, seed: int = 0):
        self.num_samples = int(num_samples)
        self.seed = int(seed)

    def params(self):
        # "fold_in-v1" versions the per-item key derivation (fold_in of
        # the global index, batching-invariant); bumping it invalidates
        # saved-state/CSE matches from the pre-fold_in derivation, whose
        # output differs for the same (num_samples, seed)
        return (self.num_samples, self.seed, "fold_in-v1")

    def apply_dataset(self, ds: Dataset) -> Dataset:
        from keystone_tpu.workflow.dataset import StreamDataset

        if isinstance(ds, StreamDataset):
            if ds.is_host:
                raise TypeError(
                    "ColumnSampler stream path needs device descriptor "
                    "batches, but this StreamDataset carries host "
                    "objects. Featurize to arrays first."
                )
            # Out-of-core path: sample each descriptor batch as it
            # streams past and keep only the (small) samples.  Keys are
            # derived from the GLOBAL item index, so the sample is
            # identical to the in-memory path regardless of batching.
            import numpy as np

            outs = []
            offset = 0
            key = jax.random.PRNGKey(self.seed)
            for arr, mask in ds.device_batches():
                if arr.ndim != 3:
                    raise ValueError(
                        "ColumnSampler expects (n, max_k, d) descriptor sets"
                    )
                m = arr.shape[0]
                out = _sample_descriptors(
                    arr,
                    mask
                    if mask is not None
                    else jnp.ones(arr.shape[:2], jnp.float32),
                    self.num_samples,
                    key,
                    offset=offset,
                )
                outs.append(np.asarray(out.reshape(m * self.num_samples, -1)))
                offset += m
            if offset != ds.n:
                raise ValueError(
                    f"descriptor stream produced {offset} items, expected {ds.n}"
                )
            return Dataset(np.concatenate(outs, axis=0))
        arr = ds.array
        if arr.ndim != 3:
            raise ValueError("ColumnSampler expects (n, max_k, d) descriptor sets")
        n = ds.n
        from keystone_tpu.workflow.transformer import _apply_chunk_rows

        chunk = _apply_chunk_rows()
        if chunk and arr.shape[0] > chunk:
            # fixed-shape row chunks with GLOBAL-index keys (exactly the
            # stream path's offset sampling, so output is bit-identical
            # to the whole-array program) — keeps the compiled program's
            # shape independent of n (see Transformer._apply_dataset_chunked)
            from keystone_tpu.workflow.transformer import iter_row_chunks

            mask_full = (
                ds.mask
                if ds.mask is not None
                else jnp.ones(arr.shape[:2], jnp.float32)
            )
            key = jax.random.PRNGKey(self.seed)
            parts = [
                _sample_descriptors(a, m, self.num_samples, key, offset=i)
                for a, m, i in iter_row_chunks(arr, mask_full, chunk)
            ]
            out = jnp.concatenate(parts, axis=0)
            flat = out[:n].reshape(n * self.num_samples, arr.shape[-1])
        else:
            # sample + slice-to-true-rows + flatten as ONE program: the
            # eager slice/reshape at (n, max_k, d) scale compiled two
            # extra (0.1-1.4 s) programs per sampler per process
            # (rounds 1–5, not re-measured fit-floor split)
            flat = _sample_descriptors_flat(
                arr, ds.mask, self.num_samples, self.seed, n_true=n
            )
        return Dataset(flat)

    def apply_one(self, x):
        raise TypeError("ColumnSampler operates on datasets")


from functools import partial as _partial


@_partial(jax.jit, static_argnames=("k", "n_true"))
def _sample_descriptors_flat(arr, mask, k, seed, n_true):
    """In-memory sampler fast path: mask default, PRNG key derivation,
    sampling, true-row slice, and the flat reshape fused into one jit
    program (the eager PRNGKey alone was 2 compiled programs/fit)."""
    key = jax.random.PRNGKey(seed)
    if mask is None:
        mask = jnp.ones(arr.shape[:2], jnp.float32)
    out = _sample_descriptors(arr, mask, k, key)
    return out[:n_true].reshape(n_true * k, arr.shape[-1])


@_partial(jax.jit, static_argnames=("k",))
def _sample_descriptors(arr, mask, k, key, offset=0):
    n, max_k, d = arr.shape
    # Per-item keys fold in the GLOBAL item index (offset for stream
    # batches), so sampling is batching-invariant: the streaming and
    # in-memory paths draw identical descriptors for the same seed.
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(n, dtype=jnp.int32) + jnp.int32(offset)
    )

    def per_item(a, m, kk):
        logits = jnp.where(m > 0, 0.0, -jnp.inf)
        idx = jax.random.categorical(kk, logits, shape=(k,))
        return a[idx]

    return jax.vmap(per_item)(arr, mask, keys)
