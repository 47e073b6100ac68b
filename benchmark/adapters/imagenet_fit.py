"""The fit cell of ``imagenet-sift-lcs-fv``: the pipeline of
``ImageNetSiftLcsFV.build_scorer`` with the vocabulary given in place of its
PCA and GMM estimators (the source pipeline may be given one from files), so
that a fit is upload -> SIFT/LCS -> Fisher vectors -> the class-weighted
block solve, and a plain reference can follow it: the program's own
vocabulary fit draws k-means++ centres that rounding flips."""

from __future__ import annotations

from benchmark import datagen, ops_count
from benchmark.adapters import imagenet


def make_data(cfg: dict, cell: dict, seed: int, ref) -> dict:
    size, classes = cfg["image_size"], cfg["num_classes"]
    n, h = cell["n"] + (cell["views"] - 1) * cell["view_step"], cell["held_out"]
    images, labels = datagen.texture_images(n + h, size, classes, seed)
    seen, _ = datagen.texture_images(cfg["vocabulary_images"], size, classes, seed, stream=1)
    return {
        "x": images[:n], "labels": labels[:n], "held_x": images[n:],
        "vocab": ref.make_vocabulary(seen, cfg, datagen.fold(seed)),
    }


def fit_inputs(data: dict, cell: dict, index: int):
    return datagen.window(data["x"], data["labels"], cell["n"], index, cell["views"],
                          cell["view_step"])


def build(cfg: dict, cell: dict, seed: int, train_x, train_labels, data: dict):
    from keystone_tpu.models import BlockWeightedLeastSquaresEstimator
    from keystone_tpu.ops import ClassLabelIndicators

    return imagenet.featurizer(cfg, data["vocab"]).and_then(
        BlockWeightedLeastSquaresEstimator(
            block_size=cfg["solver_block_size"], num_iter=cell["num_epochs"],
            lam=cfg["lam"], mixture_weight=cfg["mixture_weight"],
        ),
        train_x,
        ClassLabelIndicators(cfg["num_classes"])(train_labels),
    )


def held_out_answers(fitted, held_x):
    return imagenet.score(fitted, held_x)


def reference_scores(ref, cfg: dict, cell: dict, data: dict, seed: int, precision, index: int):
    x, labels = fit_inputs(data, cell, index)
    return ref.fit_and_score(
        cfg, x, labels, data["held_x"], data["vocab"],
        epochs=cell["num_epochs"], precision=precision,
    )


def ops(cfg: dict, cell: dict) -> dict:
    per_image = imagenet.ops(cfg, cell)
    n, k = cell["n"], cfg["num_classes"]
    d = 2 * 2 * cfg["gmm_k"] * cfg["pca_dims"]
    args = (n, d, k, cfg["solver_block_size"], cell["num_epochs"])
    return {
        "solver_flops": ops_count.solver_flops(*args),
        "solver_bytes": ops_count.solver_bytes(*args),
        "featurize_flops": n * per_image["featurize_flops"],
        "featurize_bytes": n * per_image["image_bytes"],
    }
