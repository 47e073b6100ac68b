"""Test fixture: a virtual 8-device CPU mesh.

The reference tests all distributed code paths on a LocalSparkContext
("local[N]" threads in one JVM; SURVEY.md §4).  The analogue here is
XLA's virtual CPU devices: 8 host devices exercise the same
sharding/collective code paths as an 8-chip TPU slice without hardware.
Must be set before jax initializes.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def mesh():
    """Process-global 4x2 (data x model) mesh over the 8 virtual devices."""
    from keystone_tpu.parallel import default_mesh, set_mesh

    m = default_mesh(model_parallelism=2)
    set_mesh(m)
    yield m
    set_mesh(None)


@pytest.fixture
def rng_key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def imagenet_toy_config():
    """The toy width of the north-star entry that the suite fits."""
    from keystone_tpu.pipelines import ImageNetSiftLcsFV

    return ImageNetSiftLcsFV.Config(
        num_classes=4,
        gmm_k=4,
        gmm_iters=4,
        pca_dims=16,
        descriptor_samples_per_image=32,
        solver_block_size=512,
        synthetic_n=48,
        image_size=48,
        sift_step=8,
        lcs_step=8,
    )


@pytest.fixture(scope="module")
def imagenet_toy_scorer(imagenet_toy_config):
    """``ImageNetSiftLcsFV.build_scorer`` fitted at the toy width: the
    program's own forward, for the tests that hold its properties."""
    from keystone_tpu.loaders.imagenet import ImageNetLoader
    from keystone_tpu.pipelines import ImageNetSiftLcsFV

    cfg = imagenet_toy_config
    train = ImageNetLoader.synthetic(
        cfg.synthetic_n,
        cfg.num_classes,
        size=(cfg.image_size, cfg.image_size),
        seed=1,
    )
    return ImageNetSiftLcsFV.build_scorer(cfg, train.data, train.labels).fit()
