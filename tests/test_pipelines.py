"""End-to-end pipeline integration tests on synthetic data (SURVEY.md §4:
mini pipelines in local mode asserting accuracy above a threshold)."""

import os

import numpy as np
import pytest

from keystone_tpu.pipelines import (
    AmazonReviewsPipeline,
    ImageNetSiftLcsFV,
    KernelCifarPipeline,
    KernelRidgeTimitPipeline,
    KernelTimitPipeline,
    LinearPixels,
    MnistRandomFFT,
    NewsgroupsPipeline,
    RandomPatchCifar,
    TimitPipeline,
    VOCSIFTFisher,
)
from keystone_tpu.workflow import Dataset


def test_mnist_random_fft_e2e():
    cfg = MnistRandomFFT.Config(num_ffts=2, lam=1e-2, synthetic_n=512)
    result = MnistRandomFFT.run(cfg)
    assert result["accuracy"] > 0.8, result


def test_linear_pixels_e2e():
    cfg = LinearPixels.Config(lam=1e-3, synthetic_n=256)
    result = LinearPixels.run(cfg)
    assert result["accuracy"] > 0.8, result


def test_random_patch_cifar_e2e():
    cfg = RandomPatchCifar.Config(
        num_filters=64,
        patches_per_image=4,
        block_size=256,
        num_iter=2,
        synthetic_n=192,
    )
    result = RandomPatchCifar.run(cfg)
    assert result["accuracy"] > 0.6, result


def test_newsgroups_nb_e2e():
    cfg = NewsgroupsPipeline.Config(
        num_features=2000, head="nb", num_classes=4, synthetic_n=300
    )
    result = NewsgroupsPipeline.run(cfg)
    assert result["accuracy"] > 0.9, result


def test_newsgroups_ls_e2e():
    cfg = NewsgroupsPipeline.Config(
        num_features=2000, head="ls", num_classes=4, synthetic_n=300
    )
    result = NewsgroupsPipeline.run(cfg)
    assert result["accuracy"] > 0.9, result


def test_timit_e2e():
    cfg = TimitPipeline.Config(
        num_cosine_features=1024,
        cosine_block_size=512,
        num_epochs=2,
        num_classes=20,
        synthetic_n=1024,
        lam=1e-4,
        gamma=0.02,
    )
    result = TimitPipeline.run(cfg)
    assert result["accuracy"] > 0.5, result


def test_kernel_timit_e2e():
    """The Nyström kernel variant (ISSUE 13) learns the same synthetic
    TIMIT task the random-feature variant does, and its out-of-core
    stream path reproduces the in-core metrics exactly (landmark draw
    and solver route are stream-invariant)."""
    cfg = KernelTimitPipeline.Config(
        num_landmarks=96,
        solver_block_size=96,
        num_epochs=2,
        num_classes=8,
        synthetic_n=512,
    )
    result = KernelTimitPipeline.run(cfg)
    assert result["accuracy"] > 0.5, result
    streamed = KernelTimitPipeline.run(
        KernelTimitPipeline.Config(
            num_landmarks=96,
            solver_block_size=96,
            num_epochs=2,
            num_classes=8,
            synthetic_n=512,
            stream=True,
            stream_batch_size=128,
        )
    )
    assert streamed["accuracy"] == result["accuracy"], (streamed, result)


def test_kernel_cifar_e2e():
    cfg = KernelCifarPipeline.Config(
        num_landmarks=64,
        solver_block_size=64,
        num_epochs=2,
        synthetic_n=256,
    )
    result = KernelCifarPipeline.run(cfg)
    assert result["accuracy"] > 0.5, result


def test_imagenet_sift_lcs_fv_e2e(imagenet_toy_config):
    result = ImageNetSiftLcsFV.run(imagenet_toy_config)
    assert result["top5_error"] <= result["top1_error"] + 1e-9, result
    assert result["accuracy"] > 0.5, result


def _toy_images(cfg, n, seed):
    hw = cfg.image_size
    return np.random.default_rng(seed).integers(
        0, 256, (n, hw, hw, 3), dtype=np.uint8
    )


def _scores_finite_and_shaped(cfg, scorer):
    out = scorer(Dataset(_toy_images(cfg, 4, 0))).get().numpy()
    assert out.shape == (4, cfg.num_classes)
    assert np.isfinite(out).all()


def _scores_batch_invariant(cfg, scorer):
    # per-image results must not depend on batch packing (pure map
    # semantics, the reference's Transformer.apply(RDD) contract)
    imgs = _toy_images(cfg, 6, 1)
    full = scorer(Dataset(imgs)).get().numpy()
    half = scorer(Dataset(imgs[:3])).get().numpy()
    np.testing.assert_allclose(full[:3], half, rtol=2e-4, atol=2e-4)


def _multiscale_sift_through_fitted_pca_and_fv(cfg, scorer):
    # vl_phow's bins and smoothing in front of the fitted SIFT branch's
    # PCA and Fisher vector, all under one jit
    import jax

    from keystone_tpu.ops import GrayScaler, SIFTExtractor

    graph = scorer.graph
    child = {deps[0]: n for n, deps in graph.dependencies.items() if deps}
    at = next(
        n
        for n, op in graph.operators.items()
        if isinstance(getattr(op, "transformer", None), SIFTExtractor)
    )
    pca = graph.operators[child[at]].transformer
    fv = graph.operators[child[child[at]]].transformer
    gray = GrayScaler()
    sift = SIFTExtractor(
        step=cfg.sift_step, bin_sizes=(4, 6, 8, 10), smoothing_magnif=6.0
    )

    def forward(images):
        desc, mask = sift.apply_batch(gray.apply_batch(images))
        desc, mask = pca.apply_batch(desc, mask=mask)
        return fv.apply_batch(desc, mask=mask)

    imgs = _toy_images(cfg, 2, 2).astype(np.float32) / 255.0
    out = np.asarray(jax.jit(forward)(imgs))
    assert out.shape == (2, 2 * cfg.gmm_k * cfg.pca_dims)
    assert np.isfinite(out).all()


@pytest.mark.parametrize(
    "holds",
    [
        _scores_finite_and_shaped,
        _scores_batch_invariant,
        _multiscale_sift_through_fitted_pca_and_fv,
    ],
    ids=lambda f: f.__name__.lstrip("_"),
)
def test_imagenet_sift_lcs_fv_scorer(holds, imagenet_toy_config, imagenet_toy_scorer):
    """Properties of the north-star forward, held on the entry's own
    fitted scorer (they used to be held on a hand-built copy)."""
    holds(imagenet_toy_config, imagenet_toy_scorer)


def test_imagenet_augmented_view_eval():
    """The reference's 10-view test path: CenterCornerPatcher views,
    scores averaged per image id (AugmentedExamplesEvaluator) before
    classification (SURVEY §3.4)."""
    cfg = ImageNetSiftLcsFV.Config(
        num_classes=4,
        gmm_k=4,
        gmm_iters=4,
        pca_dims=16,
        descriptor_samples_per_image=32,
        solver_block_size=512,
        synthetic_n=40,  # → 10 test images: non-divisible on the 4-wide
        image_size=48,   # data axis, exercising the padded-rows crop
        sift_step=8,
        lcs_step=8,
        augmented_eval=True,
    )
    result = ImageNetSiftLcsFV.run(cfg)
    assert 0.0 <= result["top5_error"] <= result["top1_error"] + 1e-9, result
    assert result["accuracy"] > 0.5, result


def test_voc_sift_fisher_e2e():
    cfg = VOCSIFTFisher.Config(
        gmm_k=4,
        gmm_iters=4,
        pca_dims=16,
        descriptor_samples_per_image=32,
        solver_block_size=512,
        synthetic_n=36,
        image_size=48,
        sift_step=8,
    )
    result = VOCSIFTFisher.run(cfg)
    assert result["mean_ap"] > 0.2, result


def test_amazon_reviews_e2e():
    cfg = AmazonReviewsPipeline.Config(num_features=4096, synthetic_n=400)
    result = AmazonReviewsPipeline.run(cfg)
    assert result["accuracy"] > 0.9, result


def test_cli_list(capsys):
    from keystone_tpu.cli import main

    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "MnistRandomFFT" in out and "ImageNetSiftLcsFV" in out


@pytest.mark.parametrize(
    "app_cfg",
    [
        lambda mp: (MnistRandomFFT, MnistRandomFFT.Config(
            num_ffts=2, synthetic_n=256, model_path=mp)),
        lambda mp: (LinearPixels, LinearPixels.Config(
            synthetic_n=256, model_path=mp)),
        lambda mp: (TimitPipeline, TimitPipeline.Config(
            synthetic_n=256, num_classes=8, num_cosine_features=512,
            model_path=mp)),
        lambda mp: (AmazonReviewsPipeline, AmazonReviewsPipeline.Config(
            synthetic_n=200, model_path=mp)),
        lambda mp: (NewsgroupsPipeline, NewsgroupsPipeline.Config(
            synthetic_n=160, num_classes=3, model_path=mp)),
        lambda mp: (RandomPatchCifar, RandomPatchCifar.Config(
            synthetic_n=128, num_filters=32, block_size=256, model_path=mp)),
        lambda mp: (VOCSIFTFisher, VOCSIFTFisher.Config(
            synthetic_n=24, gmm_k=4, gmm_iters=3, pca_dims=8,
            descriptor_samples_per_image=16, solver_block_size=128,
            image_size=48, model_path=mp)),
        lambda mp: (KernelTimitPipeline, KernelTimitPipeline.Config(
            synthetic_n=256, num_classes=8, num_landmarks=64,
            solver_block_size=64, num_epochs=1, model_path=mp)),
        lambda mp: (KernelCifarPipeline, KernelCifarPipeline.Config(
            synthetic_n=96, num_landmarks=48, solver_block_size=48,
            num_epochs=1, model_path=mp)),
        lambda mp: (KernelRidgeTimitPipeline, KernelRidgeTimitPipeline.Config(
            synthetic_n=256, num_classes=8, block_size=64, model_path=mp)),
    ],
)
def test_model_path_roundtrip_across_apps(app_cfg, tmp_path):
    """Every converted app: fit+save, then load-not-refit with equal
    metrics (compared generically — apps report different metric keys)."""
    app, cfg = app_cfg(str(tmp_path / "model.pkl"))
    r1 = app.run(cfg)
    assert r1["model_loaded"] is False
    r2 = app.run(cfg)
    assert r2["model_loaded"] is True
    skip = ("fit_seconds", "model_loaded")
    assert {k: v for k, v in r2.items() if k not in skip} == {
        k: v for k, v in r1.items() if k not in skip
    }


def test_mnist_model_path_roundtrip(tmp_path):
    """--model-path: first run fits and saves; second run loads the
    fitted pipeline and only scores; a changed config refuses to reuse
    the stale model instead of silently reporting its metrics."""
    mp = str(tmp_path / "mnist-model.pkl")
    cfg = MnistRandomFFT.Config(num_ffts=2, synthetic_n=256, model_path=mp)
    r1 = MnistRandomFFT.run(cfg)
    assert os.path.exists(mp) and r1["model_loaded"] is False
    r2 = MnistRandomFFT.run(cfg)
    assert r2["model_loaded"] is True  # load, not refit
    assert r2["accuracy"] == r1["accuracy"]
    stale = MnistRandomFFT.Config(num_ffts=4, synthetic_n=256, model_path=mp)
    with pytest.raises(ValueError, match="different\n?.*config|different config"):
        MnistRandomFFT.run(stale)
