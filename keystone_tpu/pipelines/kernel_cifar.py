"""KernelCifarPipeline — the Nyström BASELINE of arXiv:1602.05310 on
CIFAR: raw pixels → ImageVectorizer → StandardScaler → NystromFeatures
→ BlockLeastSquares → MaxClassifier.

The kernel counterpart of ``pipelines/linear_pixels.py``: same input
plumbing, but the linear solve runs in the m-dimensional Nyström
feature space of a Gaussian kernel over scaled pixels.  The paper's
headline method, exact kernel ridge regression swept block by block
over the dual, is not here: its entry is
``pipelines/kernel_ridge_timit.py`` (``KernelRidgeTimitPipeline``).  ``--stream``
keeps CIFAR records out of core."""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

from keystone_tpu.evaluation import MulticlassClassifierEvaluator
from keystone_tpu.loaders.cifar import CifarLoader, NUM_CLASSES
from keystone_tpu.models import BlockLeastSquaresEstimator, NystromFeatures
from keystone_tpu.models.kernel_ridge import GaussianKernelGenerator
from keystone_tpu.ops import ClassLabelIndicators, ImageVectorizer, MaxClassifier
from keystone_tpu.ops.stats import StandardScaler
from keystone_tpu.workflow import Dataset, Pipeline


@dataclasses.dataclass
class Config:
    train_path: Optional[str] = None
    test_path: Optional[str] = None
    num_landmarks: int = 2048
    gamma: float = 2e-4
    nystrom_reg: float = 1e-7
    num_epochs: int = 3
    lam: float = 1e-5
    solver_block_size: int = 1024
    seed: int = 0
    synthetic_n: int = 1024
    model_path: Optional[str] = None
    # out-of-core: re-read CIFAR records from disk per pass
    stream: bool = False
    stream_batch_size: int = 1024


class KernelCifarPipeline:
    name = "KernelCifarPipeline"
    Config = Config

    @staticmethod
    def build(config: Config, train_x: Dataset, train_labels: Dataset) -> Pipeline:
        kern = GaussianKernelGenerator(config.gamma)
        labels_pm1 = ClassLabelIndicators(NUM_CLASSES)(train_labels)
        vec = Pipeline.of(ImageVectorizer())
        scaled = vec.and_then(
            StandardScaler().with_data(vec(train_x))
        )
        return (
            scaled.and_then(
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
        from keystone_tpu.loaders.stream import require_stream_test_path

        require_stream_test_path(config)
        if config.train_path:
            test = CifarLoader.load(config.test_path or config.train_path)
        else:
            test = CifarLoader.synthetic(config.synthetic_n // 4, seed=2)

        def build():
            from keystone_tpu.loaders.stream import resolve_train_source

            train = resolve_train_source(
                config,
                load=CifarLoader.load,
                stream=CifarLoader.stream,
                synthetic=lambda: CifarLoader.synthetic(
                    config.synthetic_n, seed=1
                ),
            )
            return KernelCifarPipeline.build(config, train.data, train.labels)

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
        m = MulticlassClassifierEvaluator(NUM_CLASSES).evaluate(
            preds, test.labels
        )
        return {
            "pipeline": KernelCifarPipeline.name,
            "fit_seconds": fit_time,
            "model_loaded": loaded,
            "test_error": m.total_error,
            "accuracy": m.accuracy,
        }


def main(argv=None):
    p = argparse.ArgumentParser(description=KernelCifarPipeline.name)
    p.add_argument("--train-path")
    p.add_argument("--test-path")
    p.add_argument("--num-landmarks", type=int, default=2048)
    p.add_argument("--gamma", type=float, default=2e-4)
    p.add_argument("--num-epochs", type=int, default=3)
    p.add_argument("--lam", type=float, default=1e-5)
    p.add_argument("--synthetic-n", type=int, default=1024)
    p.add_argument("--model-path")
    from keystone_tpu.loaders.stream import add_stream_args

    add_stream_args(p, default_batch_size=1024, noun="CIFAR records")
    a = p.parse_args(argv)
    print(KernelCifarPipeline.run(Config(
        train_path=a.train_path,
        test_path=a.test_path,
        num_landmarks=a.num_landmarks,
        gamma=a.gamma,
        num_epochs=a.num_epochs,
        lam=a.lam,
        synthetic_n=a.synthetic_n,
        model_path=a.model_path,
        stream=a.stream,
        stream_batch_size=a.stream_batch_size,
    )))


if __name__ == "__main__":
    main()
