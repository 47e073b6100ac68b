"""What ties the ``imagenet-sift-lcs-fv`` configuration to the program: seeded
images, the vocabulary and the linear model made by the benchmark, the
program's pipeline built from its own nodes around them, and the operations
of one image.  Only this file and the driver import ``keystone_tpu``."""

from __future__ import annotations

import numpy as np

from benchmark import datagen, ops_count


def make_data(cfg: dict, cell: dict, seed: int, ref) -> dict:
    size, classes = cfg["image_size"], cfg["num_classes"]
    images, labels = datagen.texture_images(
        cell["images"] + cell["view_step"] * (cell["views"] - 1), size, classes, seed
    )
    seen, _ = datagen.texture_images(cfg["vocabulary_images"], size, classes, seed, stream=1)
    vocab = ref.make_vocabulary(seen, cfg, datagen.fold(seed))
    d = 2 * 2 * cfg["gmm_k"] * cfg["pca_dims"]
    weights, intercept = datagen.linear_model(d, classes, seed)
    return {"images": images, "labels": labels, "vocab": vocab,
            "model": {"weights": weights, "intercept": intercept}}


def _branch(base, vocab: dict):
    from keystone_tpu.models.gmm import GaussianMixtureModel
    from keystone_tpu.models.pca import PCATransformer
    from keystone_tpu.ops import NormalizeRows, SignedHellingerMapper
    from keystone_tpu.ops.fisher import FisherVector

    gmm = GaussianMixtureModel(
        vocab["gmm_weights"], vocab["gmm_means"], vocab["gmm_variances"]
    )
    return (
        base.and_then(PCATransformer(vocab["pca_components"], vocab["pca_mean"]))
        .and_then(FisherVector(gmm))
        .and_then(SignedHellingerMapper())
        .and_then(NormalizeRows())
    )


def featurizer(cfg: dict, vocab: dict):
    """The two branches of ``ImageNetSiftLcsFV.build_scorer`` with the
    vocabulary given in place of its estimators."""
    from keystone_tpu.ops import GrayScaler, LCSExtractor, PixelScaler, SIFTExtractor
    from keystone_tpu.workflow import Pipeline

    sift = (
        Pipeline.of(PixelScaler(only_if_integer=True))
        .and_then(GrayScaler())
        .and_then(SIFTExtractor(step=cfg["sift_step"], bin_sizes=(cfg["sift_bin_size"],)))
    )
    lcs = Pipeline.of(PixelScaler(only_if_integer=True)).and_then(
        LCSExtractor(step=cfg["lcs_step"], subpatch_size=cfg["lcs_subpatch"])
    )
    return Pipeline.gather([_branch(sift, vocab["sift"]), _branch(lcs, vocab["lcs"])])


def scorer(cfg: dict, data: dict):
    """The fitted scoring pipeline around the benchmark's vocabulary and
    linear model, through the program's own optimizer."""
    from keystone_tpu.models.block_ls import BlockLinearMapper

    block = cfg["solver_block_size"]
    w = data["model"]["weights"]
    mapper = BlockLinearMapper(
        w.reshape(w.shape[0] // block, block, w.shape[1]), block,
        intercept=data["model"]["intercept"],
    )
    return featurizer(cfg, data["vocab"]).and_then(mapper).fit().block_until_ready()


def score(fitted, images):
    """Host images in, host scores out."""
    from keystone_tpu.workflow import Dataset

    return fitted(Dataset(images)).get().numpy()


def reference_scores(ref, cfg: dict, data: dict, images, precision):
    other = precision if isinstance(precision, str) else precision["other"]
    return ref.scores(images, data["vocab"], data["model"], cfg, precision=other)


def ops(cfg: dict, cell: dict) -> dict:
    size = cfg["image_size"]
    t_sift = len(range(2 * cfg["sift_bin_size"], size - 2 * cfg["sift_bin_size"],
                       cfg["sift_step"])) ** 2
    t_lcs = len(range(2 * cfg["lcs_subpatch"], size - 2 * cfg["lcs_subpatch"],
                      cfg["lcs_step"])) ** 2
    return ops_count.sift_lcs_fv_per_image(
        size, cfg["sift_step"], cfg["sift_bin_size"], cfg["lcs_subpatch"], cfg["pca_dims"],
        cfg["gmm_k"], cfg["num_classes"], t_sift, t_lcs,
    )
