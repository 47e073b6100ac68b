"""What ties the ``mnist-random-fft`` configuration to the program: the
data it is fed, the program's own entry (``MnistRandomFFT.build_scorer``),
how what it fitted scores held-out images, the parts of the fitted model
that are compared beside the scores, and the operations of one fit.  Only
this file and the driver import ``keystone_tpu``."""

from __future__ import annotations

import numpy as np

from benchmark import datagen, ops_count, ops_count_mnist


def entry():
    from keystone_tpu.pipelines.mnist_random_fft import MnistRandomFFT

    MnistRandomFFT.build_scorer  # a program without the entry fails here
    return MnistRandomFFT


def grey_images(n: int, size: int, classes: int, seed: int):
    """``datagen.texture_images`` at ``size``, each pixel the rounded mean of
    its three channels: uint8 (n, size * size) rows and int32 labels."""
    images, labels = datagen.texture_images(n, size, classes, seed)
    grey = np.rint(images.mean(axis=-1, dtype=np.float32)).astype(np.uint8)
    return grey.reshape(n, size * size), labels


def make_data(cfg: dict, cell: dict, seed: int, ref=None) -> dict:
    entry()  # … before any data is made
    n, h = cell["n"] + (cell["views"] - 1) * cell["view_step"], cell["held_out"]
    x, labels = grey_images(n + h, cfg["image_size"], cfg["num_classes"], seed)
    return {"x": x[:n], "labels": labels[:n], "held_x": x[n:]}


def fit_inputs(data: dict, cell: dict, index: int):
    return datagen.window(data["x"], data["labels"], cell["n"], index, cell["views"],
                          cell["view_step"])


def config(cfg: dict, cell: dict, seed: int):
    return entry().Config(num_ffts=cfg["num_ffts"], block_size=cfg["block_size"],
                          num_iter=cfg["num_iter"], lam=cfg["lam"], seed=datagen.fold(seed))


def build(cfg: dict, cell: dict, seed: int, train_x, train_labels, data=None):
    """The unfitted pipeline of one fit: the entry's own ``build_scorer``,
    its 200 sign draws and all."""
    return entry().build_scorer(config(cfg, cell, seed), train_x, train_labels)


def held_out_answers(fitted, held_x):
    """Raw class scores of held-out images through the fitted pipeline's own
    public call."""
    from keystone_tpu.workflow import Dataset

    return fitted(Dataset(held_x)).get().numpy()


def _featurizer(branches):
    """The gather of the fitted branches, each its own fitted stages."""
    from keystone_tpu.workflow import Pipeline

    chains = []
    for stages in branches:
        chain = Pipeline.of(stages[0])
        for stage in stages[1:]:
            chain = chain.and_then(stage)
        chains.append(chain)
    return Pipeline.gather(chains)


def fitted_parts(fitted, held_x, train_x, cell: dict, cfg: dict) -> dict:
    """What the fit left in its model, as host arrays: the fitted
    featurizer's 102,400 features of the first ``feature_rows`` held-out
    images (its own fitted branches, gathered); the first block's weights
    ``w0`` (one sweep: only that block's Gramian, cross term and factor
    have entered them); and ``x0``, the first block of the fit's own
    training features as its own fitted branches make them (the first
    ``block_size / 512`` branches), which is what the solver was handed."""
    from keystone_tpu.workflow import Dataset

    stages = entry().fitted_stages(fitted)
    branches = stages["branches"]
    rows = held_x[: cell["feature_rows"]]
    features = _featurizer(branches)(Dataset(rows)).get().numpy()
    first = _featurizer(branches[: cfg["block_size"] // cfg["fft_features"]])
    x0 = np.asarray(first(Dataset(train_x)).get().numpy())
    return {"features": features, "x0": x0,
            "w0": np.asarray(stages["BlockLinearMapper"].weights[0])}


def reference_answers(ref, cfg: dict, cell: dict, data: dict, seed: int, precision,
                      index: int, x0) -> dict:
    """The plain reference's fit of the same rows; and, from the program's
    own first block of training features ``x0``, the weights a plain solve
    of that block gives (``w0_given_x0``): the solver's precision alone,
    whatever the featurizer's transform rounds."""
    x, labels = fit_inputs(data, cell, index)
    out = ref.fit_and_score(cfg, x, labels, data["held_x"], seed=datagen.fold(seed),
                            feature_rows=cell["feature_rows"], precision=precision)
    out["w0_given_x0"] = ref.first_block_weights(cfg, x0, labels, precision=precision)
    return out


def ops(cfg: dict, cell: dict) -> dict:
    n = cell["n"]
    fft = ops_count_mnist.fft_per_image(cfg["num_pixels"], cfg["padded"], cfg["num_ffts"])
    d = ops_count_mnist.features(cfg["padded"], cfg["num_ffts"])
    blocks = -(-d // cfg["block_size"])
    args = (n, blocks * cfg["block_size"], cfg["num_classes"], cfg["block_size"], cfg["num_iter"])
    return {
        "solver_flops": ops_count.solver_flops(*args),
        "solver_bytes": ops_count.solver_bytes(*args),
        "featurize_flops": n * fft["flops"],
        "featurize_bytes": n * fft["bytes"],
        "fft_flops": n * fft["flops"],
        "fft_bytes": n * fft["bytes"],
    }
