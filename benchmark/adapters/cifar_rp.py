"""What ties the ``cifar-random-patch`` configuration to the program: the
data it is fed, the program's own entry (``RandomPatchCifar.build_scorer``),
how what it fitted scores held-out images, the parts of the fitted model
that are compared beside the scores, and the operations of one fit.  Only
this file and the driver import ``keystone_tpu``."""

from __future__ import annotations

from benchmark import datagen, ops_count, ops_count_cifar


def entry():
    from keystone_tpu.pipelines.random_patch_cifar import RandomPatchCifar

    RandomPatchCifar.build_scorer  # a program without the entry fails here
    return RandomPatchCifar


def make_data(cfg: dict, cell: dict, seed: int, ref=None) -> dict:
    entry()  # … before any data is made
    n, h = cell["n"] + (cell["views"] - 1) * cell["view_step"], cell["held_out"]
    images, labels = datagen.texture_images(n + h, cfg["image_size"], cfg["num_classes"], seed)
    return {"x": images[:n], "labels": labels[:n], "held_x": images[n:]}


def fit_inputs(data: dict, cell: dict, index: int):
    return datagen.window(data["x"], data["labels"], cell["n"], index, cell["views"],
                          cell["view_step"])


def config(cfg: dict, cell: dict, seed: int):
    return entry().Config(
        num_filters=cfg["num_filters"], patch_size=cfg["patch_size"],
        whitener_size=cfg["whitener_size"], pool_size=cfg["pool_size"],
        pool_stride=cfg["pool_stride"], alpha=cfg["alpha"], lam=cfg["lam"],
        block_size=cfg["block_size"], num_iter=cfg["num_iter"], zca_eps=cfg["zca_eps"],
        var_constant=cfg["var_constant"], seed=datagen.fold(seed),
    )


def build(cfg: dict, cell: dict, seed: int, train_x, train_labels, data=None):
    """The unfitted pipeline of one fit: the entry's own ``build_scorer``,
    filter learning and all."""
    return entry().build_scorer(config(cfg, cell, seed), train_x, train_labels)


def held_out_answers(fitted, held_x):
    """Raw class scores of held-out images through the fitted pipeline's own
    public call."""
    from keystone_tpu.workflow import Dataset

    return fitted(Dataset(held_x)).get().numpy()


def fitted_parts(fitted, held_x, train_x, cell: dict, cfg: dict) -> dict:
    """What the fit left in its model, as host arrays: the fitted
    featurizer's pooled features of the first ``feature_rows`` held-out
    images (its own node applied to them); the first block's weights ``w0``
    (one sweep: only that block's Gramian, cross term and factor have
    entered them); and ``x0``, the first block of the fit's own training
    features as its own nodes make them (featurizer, then the fitted
    scaler's mean and deviation), which is what the solver was handed."""
    import numpy as np

    from keystone_tpu.workflow import Dataset

    stages = entry().fitted_stages(fitted)
    featurizer, scaler = stages["PooledConvolver"], stages["StandardScalerModel"]
    width = cfg["block_size"]
    rows = held_x[: cell["feature_rows"]]
    features = featurizer.apply_dataset(Dataset(rows)).numpy()
    x0 = np.asarray(featurizer.apply_dataset(Dataset(train_x)).array[: len(train_x), :width])
    x0 = (x0 - np.asarray(scaler.mean)[:width]) / np.asarray(scaler.std)[:width]
    return {"features": features.reshape(len(rows), -1), "x0": x0,
            "w0": np.asarray(stages["BlockLinearMapper"].weights[0])}


def reference_answers(ref, cfg: dict, cell: dict, data: dict, seed: int, precision,
                      index: int, x0) -> dict:
    """The plain reference's fit of the same rows; and, from the program's
    own first block of training features ``x0``, the weights a plain solve
    of that block gives (``w0_given_x0``): the solver's precision alone,
    whatever the featurizers' streams round."""
    x, labels = fit_inputs(data, cell, index)
    out = ref.fit_and_score(cfg, x, labels, data["held_x"], seed=datagen.fold(seed),
                            feature_rows=cell["feature_rows"], precision=precision)
    out["w0_given_x0"] = ref.first_block_weights(cfg, x0, labels, precision=precision)
    return out


def ops(cfg: dict, cell: dict) -> dict:
    n = cell["n"]
    per_image = ops_count_cifar.conv_per_image(
        cfg["image_size"], cfg["image_channels"], cfg["patch_size"], cfg["num_filters"],
        cfg["pool_size"], cfg["pool_stride"],
    )
    d = ops_count_cifar.features(cfg["image_size"], cfg["patch_size"], cfg["num_filters"],
                                 cfg["pool_size"], cfg["pool_stride"])
    blocks = -(-d // cfg["block_size"])
    args = (n, blocks * cfg["block_size"], cfg["num_classes"], cfg["block_size"], cfg["num_iter"])
    learn = ops_count_cifar.filter_learning(
        cfg["whitener_size"], cfg["patch_size"] ** 2 * cfg["image_channels"], cfg["num_filters"])
    return {
        "solver_flops": ops_count.solver_flops(*args),
        "solver_bytes": ops_count.solver_bytes(*args),
        "featurize_flops": n * per_image["flops"] + learn,
        "featurize_bytes": n * per_image["bytes"] + per_image["filter_bytes"],
        "conv_flops": n * per_image["flops"],
        "conv_bytes": n * per_image["bytes"] + per_image["filter_bytes"],
    }
