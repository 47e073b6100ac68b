"""Parity guard: every component named in SURVEY.md §2's inventory must
resolve to a public symbol (reference class names included, via aliases
where our canonical name differs — e.g. BlockWeightedLeastSquares for
nodes/learning/BlockWeightedLeastSquares.scala)."""

import importlib

import pytest

INVENTORY = {
    "keystone_tpu.workflow": [
        "Transformer", "Estimator", "LabelEstimator", "Pipeline", "Dataset",
        "transformer", "Cacher", "PipelineEnv",
    ],
    "keystone_tpu.workflow.graph": [
        "Graph", "NodeId", "SourceId", "SinkId", "TransformerOperator",
        "EstimatorOperator", "DatasetOperator", "DatumOperator",
        "DelegatingOperator", "GatherOperator",
    ],
    "keystone_tpu.workflow.executor": ["GraphExecutor"],
    "keystone_tpu.workflow.optimizer": [
        "Optimizer", "Rule", "RuleBatch", "Once", "FixedPoint",
        "EquivalentNodeMergeRule", "AutoMaterializeRule", "NodeChoiceRule",
        "StageFusionRule", "FusedTransformer",
    ],
    "keystone_tpu.workflow.profiling": ["ProfilingAutoCacheRule"],
    "keystone_tpu.workflow.state": [
        "SavedStateLoadRule", "ExtractSaveablePrefixes", "save_pipeline_state",
    ],
    "keystone_tpu.models": [
        "LinearMapEstimator", "LinearMapper", "BlockLinearMapper",
        "BlockLeastSquaresEstimator", "BlockWeightedLeastSquaresEstimator",
        "BlockWeightedLeastSquares", "DenseLBFGSwithL2", "SparseLBFGSwithL2",
        "LocalLeastSquaresEstimator", "KernelRidgeRegressionEstimator",
        "KernelRidgeRegression", "KernelBlockLinearMapper",
        "OutOfCoreKernelBlockLinearMapper", "NystromFeatures",
        "NystromFeatureMap",
        "GaussianKernelGenerator", "PCAEstimator", "DistributedPCAEstimator",
        "PCATransformer", "ZCAWhitenerEstimator", "GaussianMixtureModel",
        "GaussianMixtureModelEstimator", "KMeansPlusPlusEstimator",
        "KMeansModel", "NaiveBayesEstimator", "LogisticRegressionEstimator",
    ],
    "keystone_tpu.models.kernel_matrix": ["BlockKernelMatrix"],
    "keystone_tpu.ops": [
        "Convolver", "Windower", "RandomPatcher", "CenterCornerPatcher",
        "Pooler", "SymmetricRectifier", "GrayScaler", "ImageVectorizer",
        "PixelScaler", "DaisyExtractor", "LCSExtractor", "SIFTExtractor",
        "FisherVector", "GMMFisherVectorEstimator", "CosineRandomFeatures",
        "PaddedFFT", "RandomSignNode", "LinearRectifier", "StandardScaler",
        "Sampler", "ColumnSampler", "SignedHellingerMapper", "NormalizeRows",
        "TermFrequency", "CommonSparseFeatures", "Tokenizer", "LowerCase",
        "Trimmer", "NGramsFeaturizer", "NGramsCounts", "StupidBackoffLM",
        "ClassLabelIndicators", "MaxClassifier", "TopKClassifier",
        "VectorSplitter", "VectorCombiner", "Densify", "Sparsify",
    ],
    "keystone_tpu.ops.nlp": ["NGramIndexer"],
    "keystone_tpu.loaders": [
        "ImageNetLoader", "CifarLoader", "CsvDataLoader",
        "TimitFeaturesDataLoader", "NewsgroupsDataLoader",
        "AmazonReviewsDataLoader", "VOCLoader", "LabeledData", "MnistLoader",
    ],
    "keystone_tpu.evaluation": [
        "MulticlassClassifierEvaluator", "BinaryClassifierEvaluator",
        "MeanAveragePrecisionEvaluator", "AugmentedExamplesEvaluator",
    ],
    "keystone_tpu.utils": ["Image", "ImageMetadata"],
    "keystone_tpu.utils.matrix": [
        "rows_to_matrix", "matrix_to_rows", "matrix_to_row_array",
    ],
    "keystone_tpu.utils.stats": ["about_eq"],
    "keystone_tpu.pipelines": [],
}

PIPELINES = [
    "mnist_random_fft", "linear_pixels", "random_patch_cifar", "newsgroups",
    "timit", "imagenet_sift_lcs_fv", "voc_sift_fisher", "amazon_reviews",
    "kernel_timit", "kernel_cifar", "kernel_ridge_timit",
]


@pytest.mark.parametrize("module", sorted(INVENTORY))
def test_inventory_symbols_resolve(module):
    m = importlib.import_module(module)
    missing = [s for s in INVENTORY[module] if not hasattr(m, s)]
    assert not missing, f"{module} missing {missing}"


@pytest.mark.parametrize("name", PIPELINES)
def test_pipeline_modules_have_mains(name):
    m = importlib.import_module(f"keystone_tpu.pipelines.{name}")
    assert callable(getattr(m, "main"))
