"""KernelTimitPipeline — the Nyström BASELINE of the TIMIT experiment
in arXiv:1602.05310 (which compares random features and Nyström
features against the exact kernel): MFCC frames → StandardScaler →
NystromFeatures (seeded landmark sampling + whitening solve; K_nm
streams at apply time) → BlockLeastSquares (147 classes) →
MaxClassifier.  It does NOT run the paper's headline method: the exact
kernel ridge regression by block Gauss–Seidel over the dual is
``pipelines/kernel_ridge_timit.py`` (``KernelRidgeTimitPipeline``).

Where ``pipelines/timit.py`` approximates the Gaussian kernel with
random cosine features, this variant uses the data-dependent Nyström
map — same primal solver, same labels plumbing, an approximate kernel
feature space.  ``--stream`` keeps the MFCC frames out of core end to end:
landmarks are collected in one streaming pass and the solver spills to
a FeatureBlockStore."""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

from keystone_tpu.evaluation import MulticlassClassifierEvaluator
from keystone_tpu.loaders.timit import TimitFeaturesDataLoader, NUM_CLASSES
from keystone_tpu.models import BlockLeastSquaresEstimator, NystromFeatures
from keystone_tpu.models.kernel_ridge import GaussianKernelGenerator
from keystone_tpu.ops import ClassLabelIndicators, MaxClassifier
from keystone_tpu.ops.stats import StandardScaler
from keystone_tpu.workflow import Dataset, Pipeline


@dataclasses.dataclass
class Config:
    features_path: Optional[str] = None
    labels_path: Optional[str] = None
    test_features_path: Optional[str] = None
    test_labels_path: Optional[str] = None
    num_landmarks: int = 2048
    gamma: float = 0.015
    nystrom_reg: float = 1e-7
    num_epochs: int = 3
    lam: float = 1e-5
    solver_block_size: int = 1024
    num_classes: int = NUM_CLASSES
    seed: int = 0
    synthetic_n: int = 4096
    model_path: Optional[str] = None
    # out-of-core: stream MFCC frames from disk; landmarks sample in
    # one pass and the Nyström features spill to a FeatureBlockStore
    stream: bool = False
    stream_batch_size: int = 8192


class KernelTimitPipeline:
    name = "KernelTimitPipeline"
    Config = Config

    @staticmethod
    def build(config: Config, train_x: Dataset, train_labels: Dataset) -> Pipeline:
        kern = GaussianKernelGenerator(config.gamma)
        labels_pm1 = ClassLabelIndicators(config.num_classes)(train_labels)
        return (
            Pipeline.of(StandardScaler().with_data(train_x))
            .and_then(
                NystromFeatures(
                    kern,
                    num_landmarks=config.num_landmarks,
                    reg=config.nystrom_reg,
                    seed=config.seed,
                ),
                train_x,
            )
            .and_then(
                BlockLeastSquaresEstimator(
                    block_size=config.solver_block_size,
                    num_iter=config.num_epochs,
                    lam=config.lam,
                ),
                train_x,
                labels_pm1,
            )
            .and_then(MaxClassifier())
        )

    @staticmethod
    def run(config: Config) -> dict:
        _train_cache = []

        def _train():
            if not _train_cache:
                if config.features_path:
                    loader = (
                        TimitFeaturesDataLoader.stream
                        if config.stream
                        else TimitFeaturesDataLoader.load
                    )
                    kw = (
                        {"batch_size": config.stream_batch_size}
                        if config.stream
                        else {}
                    )
                    _train_cache.append(
                        loader(config.features_path, config.labels_path, **kw)
                    )
                else:
                    synth = TimitFeaturesDataLoader.synthetic(
                        config.synthetic_n, config.num_classes, seed=1
                    )
                    if config.stream:
                        from keystone_tpu.loaders.stream import stream_labeled

                        synth = stream_labeled(
                            synth, config.stream_batch_size
                        )
                    _train_cache.append(synth)
            return _train_cache[0]

        if config.features_path:
            test = (
                TimitFeaturesDataLoader.load(
                    config.test_features_path, config.test_labels_path
                )
                if config.test_features_path
                else _train()
            )
        else:
            test = TimitFeaturesDataLoader.synthetic(
                config.synthetic_n // 4, config.num_classes, seed=2
            )

        def build():
            train = _train()
            return KernelTimitPipeline.build(config, train.data, train.labels)

        from keystone_tpu.workflow.pipeline import (
            FittedPipeline,
            fit_relevant_config,
        )

        t0 = time.time()
        fitted, loaded = FittedPipeline.fit_or_load(
            config.model_path, build, config=fit_relevant_config(config)
        )
        fit_time = time.time() - t0
        preds = fitted(test.data).get()
        m = MulticlassClassifierEvaluator(config.num_classes).evaluate(
            preds, test.labels
        )
        return {
            "pipeline": KernelTimitPipeline.name,
            "fit_seconds": fit_time,
            "model_loaded": loaded,
            "test_error": m.total_error,
            "accuracy": m.accuracy,
            "macro_f1": m.macro_f1,
        }


def main(argv=None):
    p = argparse.ArgumentParser(description=KernelTimitPipeline.name)
    p.add_argument("--features-path")
    p.add_argument("--labels-path")
    p.add_argument("--num-landmarks", type=int, default=2048)
    p.add_argument("--gamma", type=float, default=0.015)
    p.add_argument("--num-epochs", type=int, default=3)
    p.add_argument("--lam", type=float, default=1e-5)
    p.add_argument("--num-classes", type=int, default=NUM_CLASSES)
    p.add_argument("--synthetic-n", type=int, default=4096)
    p.add_argument("--model-path")
    p.add_argument(
        "--stream",
        "--out-of-core",
        action="store_true",
        dest="stream",
        help="stream MFCC frames from disk; landmarks sample in one "
        "pass and Nyström features spill to a disk block store",
    )
    p.add_argument("--stream-batch-size", type=int, default=8192)
    a = p.parse_args(argv)
    cfg = Config(
        features_path=a.features_path,
        labels_path=a.labels_path,
        num_landmarks=a.num_landmarks,
        gamma=a.gamma,
        num_epochs=a.num_epochs,
        lam=a.lam,
        num_classes=a.num_classes,
        synthetic_n=a.synthetic_n,
        model_path=a.model_path,
        stream=a.stream,
        stream_batch_size=a.stream_batch_size,
    )
    print(KernelTimitPipeline.run(cfg))


if __name__ == "__main__":
    main()
