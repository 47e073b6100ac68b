"""RandomPatchCifar (reference
pipelines/images/cifar/RandomPatchCifar.scala, after Coates & Ng, ICML
2011), at the widths of its documented run (``--numFilters 10000 --lambda
3000``):

sample ``whitener_size`` 6×6×3 patches from the training images →
normalise each (mean off, ÷ √(var + 10)) → fit a ZCA whitener (ε 0.1) on
them → ``num_filters`` of them, whitened and scaled to unit norm, are the
filter bank → Convolver with per-patch normalisation (27×27×K) →
SymmetricRectifier (α 0.25, ×2 channels) → sum Pooler (size 14, stride
13: 2×2) → 8·K features → StandardScaler → BlockLeastSquares (blocks of
4096, one sweep) → MaxClassifier.

As in the reference, the filter learning happens imperatively when the
pipeline is built (one program, ``_learn_filters``); the Convolver folds
the whitener into its filters (``Convolver.from_whitened_patches``) and
normalises each image patch itself.  The optimizer turns Convolver →
SymmetricRectifier → Pooler → ImageVectorizer into one node whose
program never writes the 29 MB-an-image activation
(``ops/images.py § PooledConvolver``).

Pixels are 0..255 as upstream's are (the variance constant 10 is in
those units); ``run`` scales the loader's [0, 1] floats back."""

from __future__ import annotations

import argparse
import dataclasses
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from keystone_tpu.evaluation import MulticlassClassifierEvaluator
from keystone_tpu.loaders.cifar import CifarLoader, NUM_CLASSES
from keystone_tpu.models import BlockLeastSquaresEstimator
from keystone_tpu.models.zca import ZCAWhitener, _zca_fit
from keystone_tpu.ops import (
    ClassLabelIndicators,
    Convolver,
    ImageVectorizer,
    MaxClassifier,
    Pooler,
    SymmetricRectifier,
)
from keystone_tpu.ops.images import normalize_rows
from keystone_tpu.ops.stats import StandardScaler
from keystone_tpu.utils import precision
from keystone_tpu.utils.hashing import pin_recipe
from keystone_tpu.workflow import Dataset, Pipeline


@dataclasses.dataclass
class Config:
    train_path: Optional[str] = None
    test_path: Optional[str] = None
    num_filters: int = 10000
    patch_size: int = 6
    #: patches the whitener is fitted on, drawn from all training images …
    whitener_size: int = 100000
    #: … or, where given, this many for every training image (the older
    #: spelling, kept for small runs)
    patches_per_image: Optional[int] = None
    pool_size: int = 14
    pool_stride: int = 13
    alpha: float = 0.25
    #: the solver adds lam · n to a block's Gramian (this repo's
    #: convention); upstream's --lambda 3000 at 50,000 images is 0.06
    lam: float = 0.06
    block_size: int = 4096
    num_iter: int = 1
    zca_eps: float = 0.1
    var_constant: float = 10.0
    seed: int = 0
    synthetic_n: int = 512
    model_path: Optional[str] = None


@partial(jax.jit, static_argnames=("patches", "filters", "size"))
def _learn_filters(images, n, key, zca_eps, var_constant, *, patches, filters, size):
    """The filter bank from the first ``n`` images: (filters (K, d) whitened
    and of unit norm, the whitener (d, d), the patch mean (d,)), with
    d = size·size·channels in (dy, dx, c) order.  The draw, stated so that
    a reference can repeat it: ``k_img, k_y, k_x, k_f = split(key, 4)``;
    image, row and column of each patch are ``randint`` draws from the
    first three, and the filters are the rows ``choice(k_f, patches,
    (filters,), replace=False)`` of the normalised patches."""
    _, h, w, c = images.shape
    k_img, k_y, k_x, k_f = jax.random.split(key, 4)
    which = jax.random.randint(k_img, (patches,), 0, n)
    ys = jax.random.randint(k_y, (patches,), 0, h - size + 1)
    xs = jax.random.randint(k_x, (patches,), 0, w - size + 1)
    cut = jax.vmap(lambda i, y, x: lax.dynamic_slice(images, (i, y, x, 0), (1, size, size, c)))
    p = cut(which, ys, xs).reshape(patches, size * size * c).astype(jnp.float32)
    p = normalize_rows(p, var_constant)
    whitener, mean = _zca_fit(p, jnp.float32(patches), zca_eps)
    rows = jax.random.choice(k_f, patches, (filters,), replace=False)
    f = precision.sdot(p[rows] - mean, whitener)
    f = f / (jnp.sqrt(jnp.sum(f * f, axis=1, keepdims=True)) + 1e-10)
    return f, whitener, mean


class RandomPatchCifar:
    name = "RandomPatchCifar"
    Config = Config

    @staticmethod
    def learn_convolver(config: Config, train_x: Dataset) -> Convolver:
        """Feature learning (imperative, as upstream): the patch draw, the
        normalisation, the ZCA fit and the filter bank."""
        from keystone_tpu.obs import ledger

        images = train_x.array
        if images.ndim == 3:
            images = images[..., None]
        patches = (
            config.whitener_size if config.patches_per_image is None
            else config.patches_per_image * train_x.n
        )
        filters = min(config.num_filters, patches)
        size = config.patch_size
        with ledger.span("featurize.filters", patches=patches, filters=filters):
            f, whitener, mean = _learn_filters(
                images, train_x.n, jax.random.PRNGKey(config.seed),
                jnp.float32(config.zca_eps), jnp.float32(config.var_constant),
                patches=patches, filters=filters, size=size,
            )
            conv = Convolver.from_whitened_patches(
                f, ZCAWhitener(whitener, mean), (size, size, images.shape[-1]),
                normalize_patches=True, var_constant=config.var_constant,
            )
        if train_x.name is not None:
            # a named dataset stands for its rows: the bank is a function
            # of them and of these numbers, and signs without being read
            pin_recipe(
                conv, "_fp", (conv.filters, conv.offset), formula="random-patch-zca",
                data=train_x.name, n=train_x.n, image=tuple(images.shape[1:]),
                patches=patches, size=size, seed=config.seed, zca_eps=config.zca_eps,
                var_constant=config.var_constant,
            )
        return conv

    @staticmethod
    def build_scorer(config: Config, train_x: Dataset, train_labels: Dataset) -> Pipeline:
        """The pipeline up to its raw class scores (what a fit computes and
        a benchmark compares)."""
        conv = RandomPatchCifar.learn_convolver(config, train_x)
        featurizer = (
            Pipeline.of(conv)
            .and_then(SymmetricRectifier(alpha=config.alpha))
            .and_then(Pooler(config.pool_stride, config.pool_size))
            .and_then(ImageVectorizer())
        )
        labels_pm1 = ClassLabelIndicators(NUM_CLASSES)(train_labels)
        scaled = featurizer.and_then(StandardScaler(), train_x)
        return scaled.and_then(
            BlockLeastSquaresEstimator(
                block_size=config.block_size,
                num_iter=config.num_iter,
                lam=config.lam,
            ),
            train_x,
            labels_pm1,
        )

    @staticmethod
    def build(config: Config, train_x: Dataset, train_labels: Dataset) -> Pipeline:
        return RandomPatchCifar.build_scorer(config, train_x, train_labels).and_then(
            MaxClassifier()
        )

    @staticmethod
    def fitted_stages(fitted: Pipeline) -> dict:
        """The fitted nodes of a pipeline of this entry by class name (the
        featurizer ``PooledConvolver``, the ``StandardScalerModel``, the
        ``BlockLinearMapper``), wherever stage fusion has put them."""
        found = {}
        for op in fitted.graph.operators.values():
            t = getattr(op, "transformer", None)
            for stage in getattr(t, "stages", [t]):
                found[type(stage).__name__] = stage
        return found

    @staticmethod
    def run(config: Config) -> dict:
        def pixels(data: Dataset) -> Dataset:
            # the loaders decode to [0, 1]; upstream's images are 0..255
            name = None if data.name is None else data.name + "-x255"
            return Dataset(data.array * 255.0, n=data.n, name=name)

        if config.train_path:
            test = CifarLoader.load(config.test_path or config.train_path)
        else:
            test = CifarLoader.synthetic(config.synthetic_n // 4, seed=2)

        def build():
            # train loads ONLY when a fit is needed (saved-model runs skip it)
            train = (
                CifarLoader.load(config.train_path)
                if config.train_path
                else CifarLoader.synthetic(config.synthetic_n, seed=1)
            )
            return RandomPatchCifar.build(config, pixels(train.data), train.labels)

        from keystone_tpu.workflow.pipeline import (
            FittedPipeline,
            fit_relevant_config,
        )

        t0 = time.time()
        fitted, loaded = FittedPipeline.fit_or_load(
            config.model_path, build, config=fit_relevant_config(config)
        )
        fit_time = time.time() - t0
        preds = fitted(pixels(test.data)).get()
        m = MulticlassClassifierEvaluator(NUM_CLASSES).evaluate(preds, test.labels)
        return {
            "pipeline": RandomPatchCifar.name,
            "fit_seconds": fit_time,
            "model_loaded": loaded,
            "test_error": m.total_error,
            "accuracy": m.accuracy,
        }


def main(argv=None):
    p = argparse.ArgumentParser(description=RandomPatchCifar.name)
    p.add_argument("--train-path")
    p.add_argument("--test-path")
    p.add_argument("--num-filters", type=int, default=Config.num_filters)
    p.add_argument("--lam", type=float, default=Config.lam)
    p.add_argument("--synthetic-n", type=int, default=512)
    p.add_argument("--model-path")
    a = p.parse_args(argv)
    cfg = Config(
        train_path=a.train_path,
        test_path=a.test_path,
        num_filters=a.num_filters,
        lam=a.lam,
        synthetic_n=a.synthetic_n,
        model_path=a.model_path,
    )
    print(RandomPatchCifar.run(cfg))


if __name__ == "__main__":
    main()
