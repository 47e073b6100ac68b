"""KernelRidgeTimitPipeline — exact Gaussian-kernel ridge regression on
TIMIT, the headline method of Tu, Roelofs, Venkataraman, Recht, *Large
Scale Kernel Learning using Block Coordinate Descent* (arXiv:1602.05310):
MFCC frames → StandardScaler → KernelRidgeRegression (block Gauss–Seidel
over the dual, 147 phone states as ±1 indicators) → MaxClassifier.

Where ``pipelines/kernel_timit.py`` approximates the kernel with Nyström
features and ``pipelines/timit.py`` with random cosine features, this is
the exact solve they are compared against: the fitted model is the
scaled training rows and their dual coefficients α, and a prediction is
``K(x, X_train)·α``.  ``build_scorer`` ends at those raw class scores,
``build`` adds the argmax."""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

from keystone_tpu.evaluation import MulticlassClassifierEvaluator
from keystone_tpu.loaders.timit import DIM, NUM_CLASSES, TimitFeaturesDataLoader
from keystone_tpu.models.kernel_ridge import (
    GaussianKernelGenerator,
    KernelBlockLinearMapper,
    KernelRidgeRegressionEstimator,
)
from keystone_tpu.ops import ClassLabelIndicators, MaxClassifier
from keystone_tpu.ops.stats import StandardScaler
from keystone_tpu.workflow import Dataset, Pipeline


@dataclasses.dataclass
class Config:
    features_path: Optional[str] = None
    labels_path: Optional[str] = None
    test_features_path: Optional[str] = None
    test_labels_path: Optional[str] = None
    # after the scaler ‖x−z‖² is of the order of 2·dim, so 1/dim keeps
    # the kernel away from the identity (0.015, the Nyström variant's
    # default, gives K ≈ I on standardized 440-d frames)
    gamma: float = 1.0 / DIM
    lam: float = 1e-4
    block_size: int = 4096
    num_epochs: int = 1
    num_classes: int = NUM_CLASSES
    synthetic_n: int = 4096
    model_path: Optional[str] = None


class KernelRidgeTimitPipeline:
    name = "KernelRidgeTimitPipeline"
    Config = Config

    @staticmethod
    def build_scorer(
        config: Config, train_x: Dataset, train_labels: Dataset
    ) -> Pipeline:
        """Pipeline ending at the raw class scores K(x, X_train)·α."""
        labels_pm1 = ClassLabelIndicators(config.num_classes)(train_labels)
        return Pipeline.of(StandardScaler().with_data(train_x)).and_then(
            KernelRidgeRegressionEstimator(
                GaussianKernelGenerator(config.gamma),
                lam=config.lam,
                block_size=config.block_size,
                num_epochs=config.num_epochs,
            ),
            train_x,
            labels_pm1,
        )

    @staticmethod
    def build(config: Config, train_x: Dataset, train_labels: Dataset) -> Pipeline:
        return KernelRidgeTimitPipeline.build_scorer(
            config, train_x, train_labels
        ).and_then(MaxClassifier())

    @staticmethod
    def fitted_model(fitted: Pipeline) -> KernelBlockLinearMapper:
        """The kernel model inside a fitted pipeline of this entry: the
        scaled training rows (``train_x``) and the dual coefficients
        (``alpha``), wherever stage fusion has put the mapper."""
        for op in fitted.graph.operators.values():
            t = getattr(op, "transformer", None)
            for stage in getattr(t, "stages", [t]):
                if isinstance(stage, KernelBlockLinearMapper):
                    return stage
        raise ValueError("no fitted KernelBlockLinearMapper in this pipeline")

    @staticmethod
    def run(config: Config) -> dict:
        def _train():
            if config.features_path:
                return TimitFeaturesDataLoader.load(
                    config.features_path, config.labels_path
                )
            return TimitFeaturesDataLoader.synthetic(
                config.synthetic_n, config.num_classes, seed=1
            )

        if config.test_features_path:
            test = TimitFeaturesDataLoader.load(
                config.test_features_path, config.test_labels_path
            )
        elif config.features_path:
            test = _train()
        else:
            test = TimitFeaturesDataLoader.synthetic(
                config.synthetic_n // 4, config.num_classes, seed=2
            )

        def build():
            # train loads ONLY when a fit is needed
            train = _train()
            return KernelRidgeTimitPipeline.build(config, train.data, train.labels)

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
            "pipeline": KernelRidgeTimitPipeline.name,
            "fit_seconds": fit_time,
            "model_loaded": loaded,
            "test_error": m.total_error,
            "accuracy": m.accuracy,
            "macro_f1": m.macro_f1,
        }


def main(argv=None):
    p = argparse.ArgumentParser(description=KernelRidgeTimitPipeline.name)
    p.add_argument("--features-path")
    p.add_argument("--labels-path")
    p.add_argument("--test-features-path")
    p.add_argument("--test-labels-path")
    p.add_argument("--gamma", type=float, default=Config.gamma)
    p.add_argument("--lam", type=float, default=Config.lam)
    p.add_argument("--block-size", type=int, default=Config.block_size)
    p.add_argument("--num-epochs", type=int, default=Config.num_epochs)
    p.add_argument("--num-classes", type=int, default=NUM_CLASSES)
    p.add_argument("--synthetic-n", type=int, default=Config.synthetic_n)
    p.add_argument("--model-path")
    a = p.parse_args(argv)
    print(KernelRidgeTimitPipeline.run(Config(**vars(a))))


if __name__ == "__main__":
    main()
