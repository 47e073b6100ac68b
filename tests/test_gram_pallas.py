"""Pallas gram-block megakernel tests (interpret mode on CPU — the TPU
lowering is exercised by bench/verify runs on hardware)."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.models.kernel_ridge import GaussianKernelGenerator
from keystone_tpu.ops import gram_pallas
from keystone_tpu.ops.gram_pallas import (
    _gram_block_xla,
    _gram_tile,
    gram_block,
    gram_block_pallas,
)


def _setup(n=37, m=21, d=12, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    z = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    return x, z


def test_gram_pallas_matches_generator_f32():
    x, z = _setup()
    ref = np.asarray(GaussianKernelGenerator(0.3)(x, z))
    got = np.asarray(gram_block_pallas(x, z, 0.3, interpret=True))
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_gram_pallas_multi_tile(monkeypatch):
    """tiles > 1 on both grid axes exercises the 128-multiple tiling
    and the output-slice unpadding (padding tiles compute exp(0)=1
    garbage that must never leak into the returned block)."""
    monkeypatch.setattr(gram_pallas, "_VMEM_BUDGET", 1 << 17)
    x, z = _setup(n=300, m=260, d=16)
    tile = _gram_tile(300, 16)
    assert tile % 128 == 0 and -(-300 // tile) >= 2
    ref = np.asarray(GaussianKernelGenerator(0.2)(x, z))
    got = np.asarray(gram_block_pallas(x, z, 0.2, interpret=True))
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_gram_pallas_bf16_stream_tolerance():
    """bf16 operand streaming (the bandwidth lever): compute stays f32
    in VMEM, so the error is bounded by the input rounding alone."""
    x, z = _setup(d=16)
    ref = np.asarray(GaussianKernelGenerator(0.3)(x, z))
    got = np.asarray(gram_block_pallas(x, z, 0.3, interpret=True, mxu="bf16"))
    np.testing.assert_allclose(got, ref, atol=0.06)
    assert not np.array_equal(got, ref)  # the stream really narrowed


def test_xla_fallback_bit_identical_to_generator():
    """The dispatcher's CPU path must emit EXACTLY the generator's
    graph — solver-grade and scoring variants both."""
    x, z = _setup()
    for solver_grade in (True, False):
        ref = np.asarray(
            GaussianKernelGenerator(0.4, solver_grade=solver_grade)(x, z)
        )
        got = np.asarray(_gram_block_xla(x, z, 0.4, solver_grade=solver_grade))
        np.testing.assert_array_equal(got, ref)
    # the public dispatcher on a CPU backend routes to that chain
    ref = np.asarray(GaussianKernelGenerator(0.4)(x, z))
    np.testing.assert_array_equal(np.asarray(gram_block(x, z, 0.4)), ref)


def test_dispatcher_routing(monkeypatch):
    """gram_block routes to Pallas exactly when the backend is capable,
    the escape hatch is open, and d fits the VMEM budget."""
    calls = []

    def fake_pallas(x, z, gamma, interpret=False, mxu="f32"):
        calls.append(mxu)
        return _gram_block_xla(x, z, gamma)

    monkeypatch.setattr(gram_pallas, "gram_block_pallas", fake_pallas)
    monkeypatch.setattr(gram_pallas, "pallas_supported", lambda x=None: True)
    x, z = _setup()

    gram_block(x, z, 0.3)
    assert calls == ["f32"]

    # env escape hatch wins over a capable backend
    monkeypatch.setenv("KEYSTONE_GRAM_PALLAS", "0")
    calls.clear()
    gram_block(x, z, 0.3)
    assert calls == []
    monkeypatch.delenv("KEYSTONE_GRAM_PALLAS")

    # an over-budget feature dim falls back to the XLA chain
    assert not gram_pallas.gram_pallas_enabled(gram_pallas.GRAM_MAX_D + 1)
    assert gram_pallas.gram_pallas_enabled(64)

    # explicit False always wins
    calls.clear()
    gram_block(x, z, 0.3, use_pallas=False)
    assert calls == []


def test_oc_sweep_routes_through_pallas(monkeypatch):
    """The out-of-core KRR sweep consumes the megakernel when enabled:
    use_pallas=True dispatches every gram through gram_block_pallas
    (interpret-shimmed here) and the fit matches the XLA-chain sweep."""
    import tempfile

    from keystone_tpu.models.kernel_ridge import (
        KernelRidgeRegressionEstimator,
        _oc_krr_fit,
    )
    from keystone_tpu.workflow.blockstore import RowBlockStore

    rng = np.random.default_rng(3)
    n, d, k = 96, 8, 2
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(n, k)).astype(np.float32)
    store = RowBlockStore.from_array(tempfile.mkdtemp(), x, 32)

    ref = _oc_krr_fit(store, jnp.asarray(y), float(n), 0.1, 1e-3, 2,
                      use_pallas=False)

    calls = []
    orig = gram_pallas.gram_block_pallas

    def interp(xa, za, gamma, interpret=False, mxu="f32"):
        calls.append(mxu)
        return orig(xa, za, gamma, interpret=True, mxu=mxu)

    monkeypatch.setattr(gram_pallas, "gram_block_pallas", interp)
    got = _oc_krr_fit(store, jnp.asarray(y), float(n), 0.1, 1e-3, 2,
                      use_pallas=True)
    assert calls and set(calls) == {"f32"}  # solver path streams f32
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_block_kernel_matrix_routes_through_pallas(monkeypatch):
    """BlockKernelMatrix's gram compute rides the megakernel for
    Gaussian generators on capable backends; duck-typed generators keep
    their own math."""
    from keystone_tpu.models.kernel_matrix import BlockKernelMatrix

    calls = []
    orig = gram_pallas.gram_block_pallas

    def interp(xa, za, gamma, interpret=False, mxu="f32"):
        calls.append(mxu)
        return orig(xa, za, gamma, interpret=True, mxu=mxu)

    monkeypatch.setattr(gram_pallas, "gram_block_pallas", interp)
    monkeypatch.setattr(gram_pallas, "pallas_supported", lambda x=None: True)

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    kern = GaussianKernelGenerator(0.2)
    km = BlockKernelMatrix(kern, x, block_size=16)
    col = np.asarray(km.column_block(1))
    assert calls == ["f32"]  # solver_grade generator → f32 stream
    np.testing.assert_allclose(
        col, np.asarray(kern(x, x[16:32])), atol=1e-5
    )

    class OtherKernel:
        gamma = 0.2

        def __call__(self, a, b):
            return jnp.ones((a.shape[0], b.shape[0]), jnp.float32)

    calls.clear()
    km2 = BlockKernelMatrix(OtherKernel(), x, block_size=16)
    out = np.asarray(km2.column_block(0))
    assert calls == [] and (out == 1.0).all()


# --------------------------------------------- polynomial / linear kernels
def test_poly_pallas_matches_generator_f32():
    from keystone_tpu.models.kernel_ridge import PolynomialKernelGenerator
    from keystone_tpu.ops.gram_pallas import poly_block_pallas

    x, z = _setup(d=10)
    gen = PolynomialKernelGenerator(degree=3, alpha=0.5, c=1.25)
    ref = np.asarray(gen(x, z))
    got = np.asarray(
        poly_block_pallas(x, z, 0.5, 1.25, 3, interpret=True)
    )
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_poly_and_linear_xla_fallback_bit_identical():
    """The dispatcher's CPU path IS the generator for the new kernels
    too — solver-grade and scoring variants both."""
    from keystone_tpu.models.kernel_ridge import (
        LinearKernelGenerator,
        PolynomialKernelGenerator,
    )
    from keystone_tpu.ops.gram_pallas import (
        linear_gram_block,
        poly_gram_block,
    )

    x, z = _setup()
    for solver_grade in (True, False):
        pg = PolynomialKernelGenerator(
            degree=2, alpha=0.7, c=0.3, solver_grade=solver_grade
        )
        np.testing.assert_array_equal(
            np.asarray(
                poly_gram_block(
                    x, z, alpha=0.7, c=0.3, degree=2,
                    solver_grade=solver_grade, use_pallas=False,
                )
            ),
            np.asarray(pg(x, z)),
        )
        lg = LinearKernelGenerator(solver_grade=solver_grade)
        np.testing.assert_array_equal(
            np.asarray(
                linear_gram_block(
                    x, z, solver_grade=solver_grade, use_pallas=False
                )
            ),
            np.asarray(lg(x, z)),
        )


def test_linear_rides_poly_megakernel_identity():
    """linear = poly at (α=1, c=0, degree=1): the interpret-mode kernel
    matches the generator to f32 rounding."""
    from keystone_tpu.models.kernel_ridge import LinearKernelGenerator
    from keystone_tpu.ops.gram_pallas import poly_block_pallas

    x, z = _setup(d=8)
    ref = np.asarray(LinearKernelGenerator()(x, z))
    got = np.asarray(poly_block_pallas(x, z, 1.0, 0.0, 1, interpret=True))
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_gram_block_for_routes_every_first_class_generator(monkeypatch):
    """The generator-dispatch entry covers Gaussian, polynomial, and
    linear under one gating; unknown generators return None (caller
    falls back to the generator itself)."""
    from keystone_tpu.models.kernel_ridge import (
        LinearKernelGenerator,
        PolynomialKernelGenerator,
    )

    x, z = _setup(d=8)
    # off-pallas: bit-identical to each generator
    for gen in (
        GaussianKernelGenerator(0.2),
        PolynomialKernelGenerator(degree=2, alpha=0.9, c=0.1),
        LinearKernelGenerator(),
    ):
        got = gram_pallas.gram_block_for(gen, x, z, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(gen(x, z)))

    class Duck:
        def __call__(self, a, b):
            return jnp.zeros((a.shape[0], b.shape[0]), jnp.float32)

    assert gram_pallas.gram_block_for(Duck(), x, z) is None


def test_block_kernel_matrix_routes_poly_and_linear(monkeypatch):
    """BlockKernelMatrix rides the poly megakernel for the new
    generators on capable backends (same gating as Gaussian)."""
    from keystone_tpu.models.kernel_matrix import BlockKernelMatrix
    from keystone_tpu.models.kernel_ridge import (
        LinearKernelGenerator,
        PolynomialKernelGenerator,
    )

    calls = []
    orig = gram_pallas.poly_block_pallas

    def interp(xa, za, alpha, c, degree, interpret=False, mxu="f32"):
        calls.append((alpha, c, degree, mxu))
        return orig(xa, za, alpha, c, degree, interpret=True, mxu=mxu)

    monkeypatch.setattr(gram_pallas, "poly_block_pallas", interp)
    monkeypatch.setattr(gram_pallas, "pallas_supported", lambda x=None: True)

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(48, 8)).astype(np.float32))
    pg = PolynomialKernelGenerator(degree=2, alpha=0.5, c=1.0)
    km = BlockKernelMatrix(pg, x, block_size=16)
    col = np.asarray(km.column_block(0))
    assert calls == [(0.5, 1.0, 2, "f32")]
    np.testing.assert_allclose(col, np.asarray(pg(x, x[:16])), rtol=1e-5, atol=1e-5)

    calls.clear()
    km2 = BlockKernelMatrix(LinearKernelGenerator(), x, block_size=16)
    np.testing.assert_allclose(
        np.asarray(km2.column_block(1)),
        np.asarray(LinearKernelGenerator()(x, x[16:32])),
        rtol=1e-5,
        atol=1e-5,
    )
    assert calls == [(1.0, 0.0, 1, "f32")]


@pytest.mark.parametrize("kernel", ["gaussian", "polynomial"])
@pytest.mark.parametrize("mxu,highest", [("f32", True), ("bf16", False)])
def test_solver_stream_multiplies_at_true_f32(kernel, mxu, highest):
    """f32 tiles are the solver stream: the in-kernel contraction must ask
    for ``Precision.HIGHEST`` (the MXU's default rounds f32 operands to
    bf16 — 1.0e-4..3.3e-4 off the XLA chain at d=2048 on the chip, PR 21).
    bf16 tiles are exact in one pass and must not pay for six."""
    import jax

    x, z = _setup()
    if kernel == "gaussian":
        fn = lambda a, b: gram_block_pallas(a, b, 0.3, interpret=True, mxu=mxu)  # noqa: E731
    else:
        fn = lambda a, b: gram_pallas.poly_block_pallas(  # noqa: E731
            a, b, 1.0, 1.0, 2, interpret=True, mxu=mxu
        )
    assert ("Precision.HIGHEST" in str(jax.make_jaxpr(fn)(x, z))) is highest


def test_tile_rule_counts_the_true_f32_multiply():
    """Above the 128-row floor the HIGHEST multiply keeps split copies of
    both operand tiles (twice the operand bytes, read off the v5e
    compiler's refusals); at the floor it does not, so ``GRAM_MAX_D`` is
    still the widest d the floor compiles."""
    t = gram_pallas._tile_vmem_bytes
    assert t(256, 2048) - t(256, 1024) == 32 * 256 * 1024
    assert t(128, 2048) - t(128, 1024) == 16 * 128 * 1024
    assert _gram_tile(4096, 440) == 512  # TIMIT width keeps the widest tile
    assert _gram_tile(4096, 2048) == 128
    assert t(128, gram_pallas.GRAM_MAX_D) <= gram_pallas._VMEM_BUDGET
    assert t(128, gram_pallas.GRAM_MAX_D + 128) > gram_pallas._VMEM_BUDGET
