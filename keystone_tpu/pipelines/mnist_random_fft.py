"""MnistRandomFFT (reference
pipelines/images/mnist/MnistRandomFFT.scala), at the defaults of its
``MnistRandomFFTConfig`` (``numFFTs 200``, ``blockSize 2048``, one sweep):

pixels → ``num_ffts`` branches of {RandomSignNode → PixelScaler ([0, 1])
→ PaddedFFT (the real parts of the first 512 bins of the transform padded
to 1024) → LinearRectifier(0)} → gathered, 512 · ``num_ffts`` features (102,400) →
BlockLeastSquares (blocks of ``block_size``, ``num_iter`` sweeps) →
MaxClassifier.

Upstream solves in f64 with lambda 0; here the solver is float32 and adds
``lam · n`` to a block's Gramian (this repo's convention), with the
smallest ``lam`` at which a block's factor stays within a few times the
float32 floor (``benchmark/configs/mnist-random-fft.json``, ``assumed``).
The pixel scaling and the transform's unitary scale each multiply every
feature by one constant, which is a rescaled lambda.  The scaling comes
after the sign flip, inside each branch: a flip of integer pixels is exact
and rounding is symmetric in sign, so the bits are those of scaling first,
and each branch reads the uint8 pixels: a quarter of the float32 bytes,
which keeps 12,288 rows within one apply chunk (``_chunk_rows_for``).

Each branch enters the graph as the one node stage fusion would make of
its chain (``FusedTransformer``): the same program, and the build and
every pass of the optimizer walk 200 branch nodes rather than four a
branch, while the chip waits for them in every fit."""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

from keystone_tpu.evaluation import MulticlassClassifierEvaluator
from keystone_tpu.loaders.mnist import MnistLoader, NUM_CLASSES
from keystone_tpu.models import BlockLeastSquaresEstimator
from keystone_tpu.ops import (
    ClassLabelIndicators,
    LinearRectifier,
    MaxClassifier,
    PaddedFFT,
    PixelScaler,
    RandomSignNode,
)
from keystone_tpu.workflow import Dataset, FusedTransformer, Pipeline
from keystone_tpu.workflow import graph as G


@dataclasses.dataclass
class Config:
    train_path: Optional[str] = None
    test_path: Optional[str] = None
    num_ffts: int = 200
    block_size: int = 2048
    num_iter: int = 1
    #: the solver adds lam · n to a block's Gramian; upstream's lambda is 0
    lam: float = 1e-3
    seed: int = 0
    synthetic_n: int = 2048
    # persist/reuse the fitted pipeline (the reference's serializable
    # PipelineModel flow): fit once, save; later runs load and only score
    model_path: Optional[str] = None
    # out-of-core: re-parse the training CSV per sweep; the block solver
    # spills the streamed features to disk once and sweeps them from there
    stream: bool = False
    stream_batch_size: int = 4096


class MnistRandomFFT:
    name = "MnistRandomFFT"
    Config = Config

    @staticmethod
    def build_scorer(config: Config, train_x: Dataset, train_labels: Dataset) -> Pipeline:
        """The pipeline up to its raw class scores (what a fit computes and
        a benchmark compares)."""
        (dim,) = train_x.item_shape  # stream-safe (peeks one batch)
        branches = [
            FusedTransformer([RandomSignNode.init(dim, seed=config.seed + i), PixelScaler(),
                              PaddedFFT(), LinearRectifier(0.0)])
            for i in range(config.num_ffts)
        ]
        featurizer = Pipeline.gather(branches)
        labels_pm1 = ClassLabelIndicators(NUM_CLASSES)(train_labels)
        return featurizer.and_then(
            BlockLeastSquaresEstimator(
                block_size=config.block_size, num_iter=config.num_iter, lam=config.lam
            ),
            train_x,
            labels_pm1,
        )

    @staticmethod
    def build(config: Config, train_x: Dataset, train_labels: Dataset) -> Pipeline:
        return MnistRandomFFT.build_scorer(config, train_x, train_labels).and_then(
            MaxClassifier()
        )

    @staticmethod
    def fitted_stages(fitted: Pipeline) -> dict:
        """The fitted nodes of a pipeline of this entry, wherever stage
        fusion has put them: ``branches``, for each gathered branch in the
        order of its features, its stages from the pixels on
        (``RandomSignNode``, ``PixelScaler``, ``PaddedFFT``,
        ``LinearRectifier``), and the ``BlockLinearMapper``."""
        graph = fitted.graph
        ops = graph.operators
        gather = next(n for n, op in ops.items() if isinstance(op, G.GatherOperator))
        branches = []
        for node in graph.dependencies[gather]:
            stages = []
            while isinstance(ops.get(node), G.TransformerOperator):
                t = ops[node].transformer
                stages[:0] = getattr(t, "stages", [t])
                (node,) = graph.dependencies[node]
            branches.append(stages)
        (mapper,) = [
            op.transformer for node, op in ops.items()
            if gather in graph.dependencies[node] and hasattr(op, "transformer")
        ]
        return {"branches": branches, "BlockLinearMapper": mapper}

    @staticmethod
    def run(config: Config) -> dict:
        from keystone_tpu.loaders.stream import require_stream_test_path

        require_stream_test_path(config)
        if config.train_path:
            test = MnistLoader.load(config.test_path or config.train_path)
        else:
            test = MnistLoader.synthetic(config.synthetic_n // 4, seed=2)

        def build():
            # training data loads ONLY when a fit is actually needed —
            # scoring runs with a saved model skip it entirely
            from keystone_tpu.loaders.stream import resolve_train_source

            train = resolve_train_source(
                config,
                load=MnistLoader.load,
                stream=MnistLoader.stream,
                synthetic=lambda: MnistLoader.synthetic(
                    config.synthetic_n, seed=1
                ),
            )
            return MnistRandomFFT.build(config, train.data, train.labels)

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
        metrics = MulticlassClassifierEvaluator(NUM_CLASSES).evaluate(
            preds, test.labels
        )
        return {
            "pipeline": MnistRandomFFT.name,
            "fit_seconds": fit_time,
            "model_loaded": loaded,
            "test_error": metrics.total_error,
            "accuracy": metrics.accuracy,
        }


def main(argv=None):
    p = argparse.ArgumentParser(description=MnistRandomFFT.name)
    p.add_argument("--train-path")
    p.add_argument("--test-path")
    p.add_argument("--num-ffts", type=int, default=Config.num_ffts)
    p.add_argument("--block-size", type=int, default=Config.block_size)
    p.add_argument("--lam", type=float, default=Config.lam)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--synthetic-n", type=int, default=2048)
    p.add_argument("--model-path")
    from keystone_tpu.loaders.stream import add_stream_args

    add_stream_args(p, default_batch_size=4096, noun="the training CSV")
    a = p.parse_args(argv)
    cfg = Config(
        train_path=a.train_path,
        test_path=a.test_path,
        num_ffts=a.num_ffts,
        block_size=a.block_size,
        lam=a.lam,
        seed=a.seed,
        synthetic_n=a.synthetic_n,
        model_path=a.model_path,
        stream=a.stream,
        stream_batch_size=a.stream_batch_size,
    )
    print(MnistRandomFFT.run(cfg))


if __name__ == "__main__":
    main()
