"""The main path's Pallas kernels and the solver's panelled Gramian,
compiled for a described TPU v5e.

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
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from keystone_tpu.ops.gram_pallas import GRAM_MAX_D
from keystone_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

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


def _v5e_mesh(topo, chips):
    return Mesh(np.array(topo.devices[:chips]).reshape(chips, 1), (DATA_AXIS, MODEL_AXIS))


def _rows_over(mesh, *shape):
    return _f32(NamedSharding(mesh, P(DATA_AXIS, None)), *shape)


@pytest.mark.parametrize("n", [4096, 8192, 16384])
def test_panelled_gram_compiles_for_v5e(topo, no_persistent_cache, n):
    """The solver's Gramian at block width 4096 and the three cells' row
    counts (a chip): the upper block triangle in sixteen panels is under six
    tenths of the one dot's flops by the TPU compiler's own count, and
    needs no buffer beside its 64 MB result."""
    from keystone_tpu.parallel.collectives import (
        gram_panels,
        sharded_gram,
        sharded_matmul,
    )

    mesh = _v5e_mesh(topo, 1)
    a = _rows_over(mesh, n, GRAM_BLOCK)
    assert gram_panels(GRAM_BLOCK) == 16
    panelled = jax.jit(lambda x: sharded_gram(x, mesh=mesh)).lower(a).compile()
    one_dot = jax.jit(lambda x: sharded_matmul(x, x, mesh=mesh)).lower(a).compile()
    assert panelled.cost_analysis()["flops"] <= 0.6 * one_dot.cost_analysis()["flops"]
    memory = panelled.memory_analysis()
    assert memory.output_size_in_bytes == GRAM_BLOCK * GRAM_BLOCK * 4
    assert memory.temp_size_in_bytes == 0


def test_panelled_gram_all_reduces_its_strips_across_four_chips(topo, no_persistent_cache):
    """Rows over the 2x2 host: the strips' partial sums are all-reduced (136
    of 256 tiles), never a whole ``f32[4096,4096]``, and the mirror is local."""
    import re

    from keystone_tpu.parallel.collectives import sharded_gram

    mesh = _v5e_mesh(topo, 4)
    text = (
        jax.jit(lambda x: sharded_gram(x, mesh=mesh))
        .lower(_rows_over(mesh, 4 * 4096, GRAM_BLOCK))
        .compile()
        .as_text()
    )
    reduced = [
        [int(d) for d in shape.split(",")]
        for ln in text.splitlines()
        for call in (" all-reduce(", " all-reduce-start(")
        if call in ln
        for shape in re.findall(r"f32\[(\d+,\d+)\]", ln.split(call)[0])
    ]
    assert reduced and [GRAM_BLOCK, GRAM_BLOCK] not in reduced
    tile = GRAM_BLOCK // 16
    assert sum(r * c for r, c in reduced) == 136 * tile * tile


def test_weighted_bcd_program_keeps_its_name_and_panels(topo, no_persistent_cache):
    """``bcd_roofline`` finds the solver's device time by the program name
    ``weighted_bcd_fit``; the panelled Gramian stays inside that program
    (sixteen ``highest`` products where there was one) under ``bcd.gram``."""
    from keystone_tpu.models import block_weighted_ls as bw
    from keystone_tpu.parallel import use_mesh

    mesh = _v5e_mesh(topo, 1)
    n, blocks, k = 1024, 2, 16
    with use_mesh(mesh):
        lowered = bw._weighted_bcd_fit.lower(
            _rows_over(mesh, n, blocks * GRAM_BLOCK), _rows_over(mesh, n, k),
            _f32(NamedSharding(mesh, P()), n),
            jnp.float32(n), 1e-4, num_iter=1, block_size=GRAM_BLOCK, fit_intercept=True,
        )
    lines = lowered.as_text(debug_info=True).splitlines()
    assert any(ln.startswith("module @") and "weighted_bcd_fit" in ln for ln in lines)
    assert "weighted_bcd_fit" in lowered.compile().as_text().splitlines()[0]
    gram_locs = [ln.split(" = ")[0] for ln in lines if ln.startswith("#loc") and "bcd.gram/dot_general" in ln]
    gram_dots = [
        ln for ln in lines
        if "stablehlo.dot_general" in ln and any(ln.endswith(f"loc({loc})") for loc in gram_locs)
    ]
    assert len(gram_dots) == 16 and all("[HIGHEST, HIGHEST]" in ln for ln in gram_dots)


@pytest.mark.parametrize("route", ["pallas", "xla"])
def test_krr_sweep_keeps_its_name_and_its_gram_route(topo, no_persistent_cache, route):
    """``krr_roofline`` and ``krr_device_ms_per_block`` find the in-core
    kernel sweep's device time by the program name ``krr_fit``; the column
    block comes from the route the ``gram_pallas`` gate resolved (one
    ``tpu_custom_call`` in the loop body, or none), at TIMIT's width."""
    from keystone_tpu.models import kernel_ridge as kr
    from keystone_tpu.parallel import use_mesh

    mesh = _v5e_mesh(topo, 1)
    n, block, d, k = 2048, 512, 440, 147
    with use_mesh(mesh):
        compiled = kr._krr_fit.lower(
            _rows_over(mesh, n, d), _rows_over(mesh, n, k), _f32(NamedSharding(mesh, P())),
            1.0 / d, 2e-6, block, 1, use_pallas=(route == "pallas"),
        ).compile()
    text = compiled.as_text()
    assert "krr_fit" in text.splitlines()[0]
    assert text.count('custom_call_target="tpu_custom_call"') == (route == "pallas")
    # one column block, not three: the masks never touch the (n, block) array
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 4 * n * block


@pytest.mark.parametrize("images", [1024, 4096])
def test_cifar_featurizer_never_holds_its_activation(one_chip, no_persistent_cache, images):
    """RandomPatchCifar's convolution → rectifier → pooling at its published
    10,000 filters, as ``PooledConvolver`` traces it: the kernel is in the
    program, the output is the 80,000 pooled numbers an image, and the
    temporaries are one tile's (under 2 GB whatever n is), where the
    activation alone would be 29 MB an image."""
    from keystone_tpu.ops import conv_pool_pallas as cp

    s = one_chip
    k = 10000
    args = (jax.ShapeDtypeStruct((images, 32, 32, 3), jnp.uint8, sharding=s),
            _f32(s, k, 6, 6, 3), _f32(s, k))
    compiled = jax.jit(lambda x, f, o: cp.conv_rectify_pool(
        x, f, o, stride=1, normalize=True, var_constant=10.0, alpha=0.25, max_val=0.0,
        pool_stride=13, pool_size=14, dtype=jnp.bfloat16, use_pallas=True,
    )).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == images * 80000 * 4
    assert mem.temp_size_in_bytes < 2 << 30 < images * 27 * 27 * k * 4
    assert "tpu_custom_call" in compiled.as_text()


def test_bcd_program_keeps_its_name_and_holds_one_block(topo, no_persistent_cache):
    """The unweighted solver at the CIFAR cell's block width (fewer rows and blocks): the
    program is ``jit__bcd_fit`` (``ls_roofline`` finds it by that name) and
    its temporaries are a block's, not a copy of its input."""
    from keystone_tpu.models import block_ls
    from keystone_tpu.parallel import use_mesh

    mesh = _v5e_mesh(topo, 1)
    n, d, bs = 4096, 20000, 4096  # five blocks, the last one 3616 wide
    with use_mesh(mesh):
        lowered = block_ls._bcd_fit.lower(
            _rows_over(mesh, n, d), _rows_over(mesh, n, 10), _f32(NamedSharding(mesh, P())),
            0.06, 1, bs, True,
        )
        assert "module @jit__bcd_fit" in lowered.as_text()
        mem = lowered.compile().memory_analysis()
    assert mem.argument_size_in_bytes >= 4 * n * d
    assert mem.temp_size_in_bytes < 4 * n * d // 2
