"""Example applications (reference src/main/scala/pipelines/).

Each app mirrors the reference's shape: a flag-parsed config dataclass, a
``build(...)`` assembling the pipeline from nodes, and a ``run(config)``
returning metrics.  Run via ``python -m keystone_tpu.cli <AppName> [flags]``
(the bin/run-pipeline.sh analogue) or ``python -m keystone_tpu.pipelines.<module>``.
"""

from keystone_tpu.pipelines.mnist_random_fft import MnistRandomFFT  # noqa: F401
from keystone_tpu.pipelines.linear_pixels import LinearPixels  # noqa: F401
from keystone_tpu.pipelines.random_patch_cifar import RandomPatchCifar  # noqa: F401
from keystone_tpu.pipelines.newsgroups import NewsgroupsPipeline  # noqa: F401
from keystone_tpu.pipelines.timit import TimitPipeline  # noqa: F401
from keystone_tpu.pipelines.imagenet_sift_lcs_fv import ImageNetSiftLcsFV  # noqa: F401
from keystone_tpu.pipelines.voc_sift_fisher import VOCSIFTFisher  # noqa: F401
from keystone_tpu.pipelines.amazon_reviews import AmazonReviewsPipeline  # noqa: F401
from keystone_tpu.pipelines.kernel_timit import KernelTimitPipeline  # noqa: F401
from keystone_tpu.pipelines.kernel_cifar import KernelCifarPipeline  # noqa: F401
from keystone_tpu.pipelines.kernel_ridge_timit import KernelRidgeTimitPipeline  # noqa: F401

ALL_PIPELINES = {
    "MnistRandomFFT": MnistRandomFFT,
    "LinearPixels": LinearPixels,
    "RandomPatchCifar": RandomPatchCifar,
    "NewsgroupsPipeline": NewsgroupsPipeline,
    "TimitPipeline": TimitPipeline,
    "ImageNetSiftLcsFV": ImageNetSiftLcsFV,
    "VOCSIFTFisher": VOCSIFTFisher,
    "AmazonReviewsPipeline": AmazonReviewsPipeline,
    "KernelTimitPipeline": KernelTimitPipeline,
    "KernelCifarPipeline": KernelCifarPipeline,
    "KernelRidgeTimitPipeline": KernelRidgeTimitPipeline,
}
