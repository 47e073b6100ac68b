"""Feature ops (reference src/main/scala/nodes/{stats,images,nlp,misc,util}/)."""

from keystone_tpu.ops.stats import (  # noqa: F401
    ColumnSampler,
    CosineRandomFeatures,
    LinearRectifier,
    NormalizeRows,
    PaddedFFT,
    RandomSignNode,
    Sampler,
    SignedHellingerMapper,
    StandardScaler,
    StandardScalerModel,
)
from keystone_tpu.ops.sparse import (  # noqa: F401
    BucketedSparseRows,
    PaddedSparseRows,
)
from keystone_tpu.ops.util import (  # noqa: F401
    ClassLabelIndicators,
    Densify,
    FloatToDouble,
    MaxClassifier,
    Sparsify,
    TopKClassifier,
    VectorCombiner,
    VectorSplitter,
)
from keystone_tpu.ops.images import (  # noqa: F401
    CenterCornerPatcher,
    Convolver,
    PooledConvolver,
    GrayScaler,
    ImageVectorizer,
    PixelScaler,
    Pooler,
    RandomPatcher,
    SymmetricRectifier,
    Windower,
)
from keystone_tpu.ops.sift import SIFTExtractor  # noqa: F401
from keystone_tpu.ops.lcs import LCSExtractor  # noqa: F401
from keystone_tpu.ops.daisy import DaisyExtractor  # noqa: F401
from keystone_tpu.ops.fisher import (  # noqa: F401
    FisherVector,
    FusedPcaFisherVector,
    GMMFisherVectorEstimator,
)
from keystone_tpu.ops.nlp import (  # noqa: F401
    CommonSparseFeatures,
    HashingTF,
    LowerCase,
    NGramsCounts,
    NGramsFeaturizer,
    StupidBackoffLM,
    TermFrequency,
    log_tf,
    Tokenizer,
    Trimmer,
)
