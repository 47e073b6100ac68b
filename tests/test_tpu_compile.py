"""The main path's Pallas kernels, compiled for a described TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described and not attached (``v5e:2x2``): it refuses what the chip
would refuse — a kernel over the scoped-VMEM limit, a mis-tiled slice —
which interpret-mode parity tests cannot see.  Nothing runs; a compile
that passes is not a chip run.

The topology is described INSIDE a module-scoped fixture (never at
import: only one process may hold libtpu, and every xdist worker
imports every test file), in this test's own process, with the
persistent compilation cache off around the compiles (an entry written
for a described chip cannot be read back without one).  Keep these
tests in this ONE file: a second file could land on another worker,
where the fixture would skip every test in silence.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from keystone_tpu.ops.gram_pallas import GRAM_MAX_D

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

BATCH, T, T_MULTISCALE, D_SIFT, D_PCA, K = 128, 784, 2520, 128, 64, 256
MS_BATCH = 64
GRAM_BLOCK = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _gmm(s):
    return _f32(s, K), _f32(s, K, D_PCA), _f32(s, K, D_PCA)


@pytest.mark.parametrize("mxu", ["f32", "bf16"])
@pytest.mark.parametrize("batch,t", [(BATCH, T), (MS_BATCH, T_MULTISCALE)])
def test_fisher_encode_compiles_for_v5e(one_chip, no_persistent_cache, batch, t, mxu):
    from keystone_tpu.ops.fisher_pallas import fisher_encode_pallas

    s = one_chip
    compiled = fisher_encode_pallas.lower(
        _f32(s, batch, t, D_PCA), _f32(s, batch, t), *_gmm(s), mxu=mxu
    ).compile()
    assert compiled.memory_analysis().output_size_in_bytes == batch * 2 * K * D_PCA * 4


@pytest.mark.parametrize("normalize", [False, True])
@pytest.mark.parametrize("batch,t", [(BATCH, T), (MS_BATCH, T_MULTISCALE)])
def test_fused_forward_compiles_for_v5e(
    one_chip, no_persistent_cache, batch, t, normalize
):
    from keystone_tpu.ops.fisher_pallas import fused_forward_pallas

    s = one_chip
    compiled = fused_forward_pallas.lower(
        _f32(s, batch, t, D_SIFT), _f32(s, batch, t), _f32(s, D_SIFT, D_PCA),
        _f32(s, D_SIFT), *_gmm(s), mxu="bf16", normalize=normalize,
    ).compile()
    assert compiled.memory_analysis().output_size_in_bytes == batch * 2 * K * D_PCA * 4


@pytest.mark.parametrize("kernel", ["gaussian", "polynomial"])
@pytest.mark.parametrize("d", [440, 640, 1664, 2048, 4096, GRAM_MAX_D])
def test_gram_blocks_compile_for_v5e(one_chip, no_persistent_cache, d, kernel):
    """Block 4096 at TIMIT width, at the widths the compiler refused before
    the tile rule counted the pipeline's double buffers (2048, 4096), and
    at ``GRAM_MAX_D`` itself — the bound must compile at the 128-row floor.
    The solver stream (f32 tiles) multiplies at ``Precision.HIGHEST``, which
    keeps more in VMEM above the floor: 640 and 1664 are the widest d the
    tile rule still gives 512 and 256 rows."""
    from keystone_tpu.ops import gram_pallas

    assert gram_pallas._gram_tile(GRAM_BLOCK, 640) == 512
    assert gram_pallas._gram_tile(GRAM_BLOCK, 1664) == 256
    assert gram_pallas._gram_tile(GRAM_BLOCK, 2048) == 128
    x = _f32(one_chip, GRAM_BLOCK, d)
    if kernel == "gaussian":
        lowered = gram_pallas.gram_block_pallas.lower(x, x, gamma=0.01)
    else:
        lowered = gram_pallas.poly_block_pallas.lower(x, x, alpha=1.0, c=1.0, degree=3)
    lowered.compile()
    tile = gram_pallas._gram_tile(GRAM_BLOCK, d)
    assert tile == 128 or (
        gram_pallas._tile_vmem_bytes(tile, d) <= gram_pallas._VMEM_BUDGET
    )


def test_compiled_fv_program_holds_the_custom_call(one_chip, no_persistent_cache):
    from keystone_tpu.ops.fisher_pallas import fisher_encode_pallas

    s = one_chip
    text = (
        fisher_encode_pallas.lower(
            _f32(s, 8, T, D_PCA), _f32(s, 8, T), *_gmm(s), mxu="bf16"
        )
        .compile()
        .as_text()
    )
    assert "tpu_custom_call" in text


def test_fv_scope_leaves_the_kernels_operation_name(one_chip, no_persistent_cache):
    """The FV node's ``jax.named_scope("fv")`` is in the operation's metadata
    and the kernel keeps the name a device trace finds it by
    (``benchmark/layers/fv_kernel_roofline.py``): a scope INSIDE the kernel's
    jit would rename the operation to ``fv.N``."""
    import re

    import numpy as np

    from keystone_tpu.models.gmm import GaussianMixtureModel
    from keystone_tpu.ops import fisher

    gmm = GaussianMixtureModel(
        np.full(K, 1.0 / K, np.float32), np.zeros((K, D_PCA), np.float32),
        np.ones((K, D_PCA), np.float32),
    )
    node = fisher.FisherVector(gmm, use_pallas=True)
    text = (
        jax.jit(lambda a: node.apply_batch(a))
        .lower(_f32(one_chip, 8, T, D_PCA))
        .compile()
        .as_text()
    )
    (line,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert line.strip().startswith("%fisher_encode_pallas")
    assert "/fv/" in re.search(r'op_name="([^"]*)"', line).group(1)
