"""What ties the ``timit-cosine-rf`` configuration to the program: the data
it is fed, the program's pipeline, how what it fitted scores held-out
rows, and the operations of one fit.  Only this file and the
driver import ``keystone_tpu``."""

from __future__ import annotations

from benchmark import datagen, ops_count


def feature_seed(seed: int) -> int:
    return datagen.fold(seed)


def make_data(cfg: dict, cell: dict, seed: int, ref=None) -> dict:
    n, h = cell["n"] + (cell["views"] - 1) * cell["view_step"], cell["held_out"]
    x, labels = datagen.timit_frames(n + h, cfg["input_dim"], cfg["num_classes"], seed)
    return {"x": x[:n], "labels": labels[:n], "held_x": x[n:], "held_labels": labels[n:]}


def fit_inputs(data: dict, cell: dict, index: int):
    return datagen.window(data["x"], data["labels"], cell["n"], index, cell["views"],
                          cell["view_step"])


def config(cfg: dict, cell: dict, seed: int):
    from keystone_tpu.pipelines.timit import Config

    return Config(
        num_cosine_features=cfg["num_cosine_blocks"] * cfg["cosine_block_size"],
        cosine_block_size=cfg["cosine_block_size"],
        gamma=cfg["gamma"],
        num_epochs=cell.get("num_epochs", cfg["num_epochs"]),
        lam=cfg["lam"],
        mixture_weight=cfg["mixture_weight"],
        solver_block_size=cfg["solver_block_size"],
        num_classes=cfg["num_classes"],
        seed=feature_seed(seed),
    )


def build(cfg: dict, cell: dict, seed: int, train_x, train_labels, data=None):
    """The unfitted pipeline of one fit: ``TimitPipeline.build``'s graph up
    to its raw class scores, composed here from the program's public nodes.
    The entry itself ends in a ``MaxClassifier`` and gives no public way to
    the scores under it; that head is an argmax which a fit never runs, so
    the fit is the same work (``tests/test_correct.py`` holds the two graphs
    to the same predictions)."""
    from keystone_tpu.models import BlockWeightedLeastSquaresEstimator
    from keystone_tpu.ops import ClassLabelIndicators, CosineRandomFeatures
    from keystone_tpu.ops.stats import StandardScaler
    from keystone_tpu.workflow import Pipeline

    conf = config(cfg, cell, seed)
    (dim,) = train_x.item_shape
    branches = [
        Pipeline.of(CosineRandomFeatures.init(dim, conf.cosine_block_size, gamma=conf.gamma,
                                              seed=conf.seed + i))
        for i in range(conf.num_cosine_features // conf.cosine_block_size)
    ]
    featurizer = Pipeline.of(StandardScaler().with_data(train_x)).then_pipeline(
        Pipeline.gather(branches)
    )
    return featurizer.and_then(
        BlockWeightedLeastSquaresEstimator(
            block_size=conf.solver_block_size, num_iter=conf.num_epochs, lam=conf.lam,
            mixture_weight=conf.mixture_weight,
        ),
        train_x,
        ClassLabelIndicators(conf.num_classes)(train_labels),
    )


def build_entry(cfg: dict, cell: dict, seed: int, train_x, train_labels):
    """``TimitPipeline.build`` itself, argmax and all (the test's witness)."""
    from keystone_tpu.pipelines.timit import TimitPipeline

    return TimitPipeline.build(config(cfg, cell, seed), train_x, train_labels)


def held_out_answers(fitted, held_x):
    """Raw class scores of held-out rows through the fitted pipeline's own
    public call."""
    from keystone_tpu.workflow import Dataset

    return fitted(Dataset(held_x)).get().numpy()


def reference_scores(ref, cfg: dict, cell: dict, data: dict, seed: int, precision, index: int):
    x, labels = fit_inputs(data, cell, index)
    return ref.fit_and_score(
        cfg, x, labels, data["held_x"], feature_seed=feature_seed(seed),
        epochs=cell.get("num_epochs", cfg["num_epochs"]), precision=precision,
    )


def ops(cfg: dict, cell: dict) -> dict:
    n, k = cell["n"], cfg["num_classes"]
    d = cfg["num_cosine_blocks"] * cfg["cosine_block_size"]
    epochs = cell.get("num_epochs", cfg["num_epochs"])
    block = cfg["solver_block_size"]
    return {
        "solver_flops": ops_count.solver_flops(n, d, k, block, epochs),
        "solver_bytes": ops_count.solver_bytes(n, d, k, block, epochs),
        "featurize_flops": ops_count.cosine_features_flops(n, cfg["input_dim"], d),
        "featurize_bytes": ops_count.cosine_features_bytes(n, cfg["input_dim"], d),
    }
