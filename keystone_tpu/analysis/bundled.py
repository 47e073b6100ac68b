"""Bundled-pipeline builders for the pre-flight analyzer.

``cli.py check <PipelineName>`` (and the analyzer's false-positive gate
in tests/test_analysis.py) need every bundled pipeline *constructed* —
graph assembled, estimators unbound — without running a fit.  Each
builder here instantiates the app's own ``build()`` over tiny synthetic
loader data (the same path tests/test_pipelines.py exercises end to
end, scaled down: graph construction is cheap; only RandomPatchCifar's
imperative feature learning touches the device, on a few dozen rows).

Returns ``(pipeline, example)`` where ``example`` is the training-data
Dataset — the input spec the shapes pass seeds the open source with.
"""

from __future__ import annotations

from typing import Tuple


def _mnist():
    from keystone_tpu.loaders.mnist import MnistLoader
    from keystone_tpu.pipelines.mnist_random_fft import MnistRandomFFT

    cfg = MnistRandomFFT.Config(num_ffts=2, block_size=1024, synthetic_n=128)
    train = MnistLoader.synthetic(cfg.synthetic_n, seed=1)
    return MnistRandomFFT.build(cfg, train.data, train.labels), train.data


def _linear_pixels():
    from keystone_tpu.loaders.cifar import CifarLoader
    from keystone_tpu.pipelines.linear_pixels import LinearPixels

    cfg = LinearPixels.Config(synthetic_n=128)
    train = CifarLoader.synthetic(cfg.synthetic_n, seed=1)
    return LinearPixels.build(cfg, train.data, train.labels), train.data


def _random_patch_cifar():
    from keystone_tpu.loaders.cifar import CifarLoader
    from keystone_tpu.pipelines.random_patch_cifar import RandomPatchCifar

    cfg = RandomPatchCifar.Config(
        num_filters=32,
        patches_per_image=2,
        block_size=128,
        num_iter=1,
        synthetic_n=64,
    )
    train = CifarLoader.synthetic(cfg.synthetic_n, seed=1)
    return RandomPatchCifar.build(cfg, train.data, train.labels), train.data


def _newsgroups():
    from keystone_tpu.loaders.newsgroups import NewsgroupsDataLoader
    from keystone_tpu.pipelines.newsgroups import NewsgroupsPipeline

    cfg = NewsgroupsPipeline.Config(
        num_features=512, head="nb", num_classes=4, synthetic_n=120
    )
    train = NewsgroupsDataLoader.synthetic(
        cfg.synthetic_n, cfg.num_classes, seed=1
    )
    return NewsgroupsPipeline.build(cfg, train.data, train.labels), train.data


def _timit():
    from keystone_tpu.loaders.timit import TimitFeaturesDataLoader
    from keystone_tpu.pipelines.timit import TimitPipeline

    cfg = TimitPipeline.Config(
        num_cosine_features=256,
        cosine_block_size=128,
        num_epochs=1,
        num_classes=8,
        synthetic_n=256,
    )
    train = TimitFeaturesDataLoader.synthetic(
        cfg.synthetic_n, cfg.num_classes, seed=1
    )
    return TimitPipeline.build(cfg, train.data, train.labels), train.data


def _imagenet():
    from keystone_tpu.loaders.imagenet import ImageNetLoader
    from keystone_tpu.pipelines.imagenet_sift_lcs_fv import ImageNetSiftLcsFV

    cfg = ImageNetSiftLcsFV.Config(
        num_classes=4,
        gmm_k=4,
        gmm_iters=2,
        pca_dims=16,
        descriptor_samples_per_image=16,
        solver_block_size=256,
        synthetic_n=24,
        image_size=48,
        sift_step=8,
        lcs_step=8,
    )
    train = ImageNetLoader.synthetic(
        cfg.synthetic_n,
        cfg.num_classes,
        size=(cfg.image_size, cfg.image_size),
        seed=1,
    )
    return (
        ImageNetSiftLcsFV.build(cfg, train.data, train.labels),
        train.data,
    )


def _voc():
    from keystone_tpu.loaders.voc import VOCLoader
    from keystone_tpu.pipelines.voc_sift_fisher import VOCSIFTFisher

    cfg = VOCSIFTFisher.Config(
        gmm_k=4,
        gmm_iters=2,
        pca_dims=16,
        descriptor_samples_per_image=16,
        solver_block_size=256,
        synthetic_n=16,
        image_size=48,
        sift_step=8,
    )
    train = VOCLoader.synthetic(
        cfg.synthetic_n, size=(cfg.image_size, cfg.image_size), seed=1
    )
    return VOCSIFTFisher.build(cfg, train.data, train.labels), train.data


def _amazon():
    from keystone_tpu.loaders.amazon import AmazonReviewsDataLoader
    from keystone_tpu.pipelines.amazon_reviews import AmazonReviewsPipeline

    cfg = AmazonReviewsPipeline.Config(
        num_features=1024, ngrams=2, num_iters=4, synthetic_n=120
    )
    train = AmazonReviewsDataLoader.synthetic(cfg.synthetic_n, seed=1)
    return (
        AmazonReviewsPipeline.build(cfg, train.data, train.labels),
        train.data,
    )


def _kernel_timit():
    from keystone_tpu.loaders.timit import TimitFeaturesDataLoader
    from keystone_tpu.pipelines.kernel_timit import KernelTimitPipeline

    cfg = KernelTimitPipeline.Config(
        num_landmarks=64,
        solver_block_size=64,
        num_epochs=1,
        num_classes=8,
        synthetic_n=256,
    )
    train = TimitFeaturesDataLoader.synthetic(
        cfg.synthetic_n, cfg.num_classes, seed=1
    )
    return (
        KernelTimitPipeline.build(cfg, train.data, train.labels),
        train.data,
    )


def _kernel_cifar():
    from keystone_tpu.loaders.cifar import CifarLoader
    from keystone_tpu.pipelines.kernel_cifar import KernelCifarPipeline

    cfg = KernelCifarPipeline.Config(
        num_landmarks=48,
        solver_block_size=48,
        num_epochs=1,
        synthetic_n=96,
    )
    train = CifarLoader.synthetic(cfg.synthetic_n, seed=1)
    return (
        KernelCifarPipeline.build(cfg, train.data, train.labels),
        train.data,
    )


def _kernel_ridge_timit():
    from keystone_tpu.loaders.timit import TimitFeaturesDataLoader
    from keystone_tpu.pipelines.kernel_ridge_timit import KernelRidgeTimitPipeline

    cfg = KernelRidgeTimitPipeline.Config(
        block_size=64, num_classes=8, synthetic_n=256
    )
    train = TimitFeaturesDataLoader.synthetic(
        cfg.synthetic_n, cfg.num_classes, seed=1
    )
    return (
        KernelRidgeTimitPipeline.build(cfg, train.data, train.labels),
        train.data,
    )


_BUILDERS = {
    "MnistRandomFFT": _mnist,
    "LinearPixels": _linear_pixels,
    "RandomPatchCifar": _random_patch_cifar,
    "NewsgroupsPipeline": _newsgroups,
    "TimitPipeline": _timit,
    "ImageNetSiftLcsFV": _imagenet,
    "VOCSIFTFisher": _voc,
    "AmazonReviewsPipeline": _amazon,
    "KernelTimitPipeline": _kernel_timit,
    "KernelCifarPipeline": _kernel_cifar,
    "KernelRidgeTimitPipeline": _kernel_ridge_timit,
}

BUNDLED = tuple(_BUILDERS)


def build_bundled(name: str) -> Tuple[object, object]:
    """(pipeline, example Dataset) for one bundled app, assembled over
    tiny synthetic data — the ``cli.py check`` construction path."""
    try:
        builder = _BUILDERS[name]
    except KeyError:
        raise ValueError(
            f"unknown bundled pipeline {name!r}; known: {sorted(_BUILDERS)}"
        ) from None
    return builder()
