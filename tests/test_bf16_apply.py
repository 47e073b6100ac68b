"""The opt-in bf16 APPLY policy (utils/precision.py § bf16_apply).

Contract under test, per converted contraction:

  1. INERT off-chip: with ``set_matmul("bf16_apply")`` on a CPU mesh the
     policy resolves to f32 and every op is BIT-identical to the f32
     mode — the tier-1 gate that keeps test meshes honest.
  2. PARITY when active: with the on-TPU gate force-lifted
     (``precision.force_bf16_apply``) each converted op matches its f32
     output within a tolerance set by bf16's 8-bit mantissa (~0.4%
     relative per input; f32 accumulation keeps reduction error from
     growing with contraction length).
  3. Solver math never inherits the cast: fits are bit-identical with
     the policy on, active or not.
  4. End-to-end: a pipeline trained in f32 and applied in both modes
     keeps its top-1 accuracy.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.utils import precision


@pytest.fixture(autouse=True)
def _restore_policy():
    before = precision._MODE  # preserve an env-pinned KEYSTONE_MATMUL
    yield
    precision.set_matmul(before)


def _tol(ref, frac=2e-2):
    return float(frac * np.abs(np.asarray(ref)).max() + 1e-6)


def _f32_vs_inert_vs_forced(apply_fn):
    """Run ``apply_fn`` under the three policy states; returns arrays."""
    with precision.matmul("f32"):
        ref = np.asarray(apply_fn())
    with precision.matmul("bf16_apply"):
        inert = np.asarray(apply_fn())  # CPU: the gate keeps this f32
    with precision.matmul("bf16_apply"), precision.force_bf16_apply():
        active = np.asarray(apply_fn())
    return ref, inert, active


# ------------------------------------------------------------- resolution


def test_mode_resolution_gates_on_tpu():
    """bf16_apply is a legal mode that resolves INERT off-chip; the
    force override (the parity suite's lever) lifts the gate."""
    with precision.matmul("bf16_apply"):
        assert precision.matmul_mode() == "f32"  # CPU mesh: inert
        assert precision.apply_mode() == "f32"
        assert precision.adtype() == jnp.float32
        with precision.force_bf16_apply():
            assert precision.matmul_mode() == "bf16_apply"
            assert precision.apply_mode() == "bf16_apply"
            assert precision.adtype() == jnp.bfloat16
            # the apply policy is a superset of the featurize policy
            assert precision.fdtype() == jnp.bfloat16
    # featurize-only modes never activate the apply helpers
    with precision.matmul("bf16"):
        assert precision.apply_mode() == "f32"
        assert precision.adtype() == jnp.float32


def test_helpers_inert_path_is_plain_f32():
    rng = np.random.default_rng(0)
    a = rng.normal(size=(8, 16)).astype(np.float32)
    b = rng.normal(size=(16, 4)).astype(np.float32)
    with precision.matmul("f32"):
        got = np.asarray(precision.apply_dot(a, b))
        ein = np.asarray(precision.apply_einsum("ij,jk->ik", a, b))
    want = np.asarray(
        jnp.dot(a, b, preferred_element_type=jnp.float32)
    )
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(ein, want)


def test_helpers_active_cast_to_bf16_with_f32_accumulation():
    rng = np.random.default_rng(1)
    a = rng.normal(size=(32, 64)).astype(np.float32)
    b = rng.normal(size=(64, 8)).astype(np.float32)
    with precision.matmul("bf16_apply"), precision.force_bf16_apply():
        got = precision.apply_dot(a, b)
    assert got.dtype == jnp.float32  # result stays f32
    ref = a @ b
    assert not np.array_equal(np.asarray(got), ref)  # inputs were rounded
    np.testing.assert_allclose(np.asarray(got), ref, atol=_tol(ref))


# ------------------------------------------------- per-op parity + inertness


def test_sift_bf16_apply():
    from keystone_tpu.ops import SIFTExtractor

    imgs = np.random.default_rng(2).uniform(0, 1, (2, 48, 48)).astype(np.float32)
    sift = SIFTExtractor(step=6, bin_sizes=(4, 6))  # engages the blur too
    ref, inert, active = _f32_vs_inert_vs_forced(
        lambda: sift.apply_batch(imgs)[0]
    )
    np.testing.assert_array_equal(inert, ref)
    np.testing.assert_allclose(active, ref, atol=2e-2)


def test_blur_einsums_bf16_apply():
    from keystone_tpu.ops.filters import separable_gaussian_blur

    x = np.random.default_rng(3).uniform(0, 1, (2, 32, 32, 3)).astype(np.float32)
    ref = np.asarray(separable_gaussian_blur(jnp.asarray(x), 1.2, mxu="f32"))
    act = np.asarray(
        separable_gaussian_blur(jnp.asarray(x), 1.2, mxu="bf16_apply")
    )
    np.testing.assert_allclose(act, ref, atol=_tol(ref))
    # featurize-only bf16 stays out of the blur (inert helper mode)
    feat = np.asarray(separable_gaussian_blur(jnp.asarray(x), 1.2, mxu="bf16"))
    np.testing.assert_array_equal(feat, ref)


@pytest.mark.parametrize("strategy", ["direct", "im2col"])
def test_convolver_bf16_apply(strategy):
    from keystone_tpu.ops import Convolver

    rng = np.random.default_rng(4)
    imgs = rng.uniform(0, 1, (2, 16, 16, 3)).astype(np.float32)
    filt = rng.normal(size=(8, 5, 5, 3)).astype(np.float32)
    conv = Convolver(jnp.asarray(filt), strategy=strategy)
    ref, inert, active = _f32_vs_inert_vs_forced(
        lambda: conv.apply_batch(jnp.asarray(imgs))
    )
    np.testing.assert_array_equal(inert, ref)
    np.testing.assert_allclose(active, ref, atol=_tol(ref))
    assert not np.array_equal(active, ref)  # the cast really engaged


def test_fisher_einsum_bf16_apply():
    from keystone_tpu.models.gmm import GaussianMixtureModel
    from keystone_tpu.ops.fisher import FisherVector

    rng = np.random.default_rng(5)
    k, d, t, n = 8, 16, 64, 4
    gmm = GaussianMixtureModel(
        jnp.full((k,), 1.0 / k),
        jnp.asarray(rng.normal(size=(k, d)), jnp.float32),
        jnp.ones((k, d), jnp.float32),
    )
    xs = jnp.asarray(rng.normal(size=(n, t, d)), jnp.float32)
    fv = FisherVector(gmm, use_pallas=False)
    ref, inert, active = _f32_vs_inert_vs_forced(lambda: fv.apply_batch(xs))
    np.testing.assert_array_equal(inert, ref)
    # posterior gemms + s1/s2 einsums under the policy: γ is a softmax
    # (bounded [0,1]) and Φ is normalized, so 4% of scale bounds it
    np.testing.assert_allclose(active, ref, atol=_tol(ref, 4e-2))


def test_fisher_pallas_accepts_bf16_apply_mode():
    """The Pallas kernel treats bf16_apply like bf16 for its descriptor
    stream (interpret mode; skipped where this jax lacks the kernel —
    the same pre-existing gap as tests/test_pallas.py)."""
    from keystone_tpu.ops.fisher_pallas import fisher_encode_pallas

    rng = np.random.default_rng(6)
    k, d, t, n = 8, 16, 128, 2
    xs = jnp.asarray(rng.normal(size=(n, t, d)), jnp.float32)
    mask = jnp.ones((n, t), jnp.float32)
    w = jnp.full((k,), 1.0 / k)
    mu = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
    var = jnp.ones((k, d), jnp.float32)
    try:
        ref = np.asarray(
            fisher_encode_pallas(xs, mask, w, mu, var, interpret=True, mxu="f32")
        )
    except Exception as e:  # pragma: no cover - environment-dependent
        pytest.skip(f"pallas interpret unavailable here: {e!r}")
    got = np.asarray(
        fisher_encode_pallas(
            xs, mask, w, mu, var, interpret=True, mxu="bf16_apply"
        )
    )
    np.testing.assert_allclose(got, ref, atol=_tol(ref))


def test_lcs_bf16_apply():
    from keystone_tpu.ops.lcs import LCSExtractor

    imgs = (
        np.random.default_rng(7).uniform(0, 1, (2, 40, 40, 3)).astype(np.float32)
    )
    lcs = LCSExtractor(step=5, subpatch_size=4)
    ref, inert, active = _f32_vs_inert_vs_forced(
        lambda: lcs.apply_batch(imgs)[0]
    )
    np.testing.assert_array_equal(inert, ref)
    np.testing.assert_allclose(active, ref, atol=_tol(ref))


def test_sparse_scoring_bf16_apply():
    from keystone_tpu.ops.sparse import PaddedSparseRows, sparse_matmul

    rng = np.random.default_rng(8)
    dense = (rng.random((12, 30)) * (rng.random((12, 30)) > 0.7)).astype(
        np.float32
    )
    sp = PaddedSparseRows.from_dense(dense)
    w = rng.normal(size=(30, 5)).astype(np.float32)
    ref, inert, active = _f32_vs_inert_vs_forced(lambda: sp.matmul(w))
    np.testing.assert_array_equal(inert, ref)
    np.testing.assert_allclose(active, ref, atol=_tol(ref, 4e-2))
    # the bare kernel's default is INERT regardless of policy — the
    # solver gradient paths (logistic / L-BFGS) rely on it
    with precision.matmul("bf16_apply"), precision.force_bf16_apply():
        bare = np.asarray(sparse_matmul(sp.indices, sp.values, jnp.asarray(w)))
    np.testing.assert_array_equal(bare, ref)


def test_block_predict_bf16_apply():
    from keystone_tpu.models import BlockLeastSquaresEstimator

    rng = np.random.default_rng(9)
    x = rng.normal(size=(128, 40)).astype(np.float32)
    w = rng.normal(size=(40, 4)).astype(np.float32)
    lbl = (x @ w).argmax(1)
    y = -np.ones((128, 4), np.float32)
    y[np.arange(128), lbl] = 1.0
    est = BlockLeastSquaresEstimator(block_size=16, num_iter=3, lam=1e-3)
    model = est.fit_arrays(x, y)
    ref, inert, active = _f32_vs_inert_vs_forced(
        lambda: model.apply_batch(jnp.asarray(x))
    )
    np.testing.assert_array_equal(inert, ref)
    np.testing.assert_allclose(active, ref, atol=_tol(ref, 4e-2))
    # scoring precision must not flip predictions on a separated problem
    assert (active.argmax(1) == ref.argmax(1)).all()


def test_entry_scorer_inert_on_cpu(imagenet_toy_config, imagenet_toy_scorer):
    """Tier-1 gate: the north-star forward (SIFT and LCS → PCA → FV →
    normalize → block scoring, the public entry's fitted scorer) is
    bit-identical on a CPU mesh with the policy set — bf16_apply may not
    perturb any off-chip result."""
    from keystone_tpu.workflow import Dataset

    hw = imagenet_toy_config.image_size
    imgs = np.random.default_rng(10).integers(
        0, 256, (2, hw, hw, 3), dtype=np.uint8
    )
    with precision.matmul("f32"):
        ref = imagenet_toy_scorer(Dataset(imgs)).get().numpy()
    with precision.matmul("bf16_apply"):
        got = imagenet_toy_scorer(Dataset(imgs)).get().numpy()
    np.testing.assert_array_equal(got, ref)


# ------------------------------------------------------------ solver guard


def test_solver_fit_bit_identical_under_active_policy():
    """Gramians / normal equations / Cholesky never inherit the apply
    cast: fitted weights are bit-identical with bf16_apply ACTIVE."""
    from keystone_tpu.models import BlockWeightedLeastSquaresEstimator

    rng = np.random.default_rng(11)
    x = rng.normal(size=(96, 24)).astype(np.float32)
    lbl = rng.integers(0, 3, size=96)
    y = -np.ones((96, 3), np.float32)
    y[np.arange(96), lbl] = 1.0
    est = BlockWeightedLeastSquaresEstimator(block_size=8, num_iter=2, lam=1e-2)
    with precision.matmul("f32"):
        w32 = np.asarray(est.fit_arrays(x, y).flat_weights)
    with precision.matmul("bf16_apply"), precision.force_bf16_apply():
        w16 = np.asarray(est.fit_arrays(x, y).flat_weights)
    np.testing.assert_array_equal(w16, w32)


# ------------------------------------------------------------- end to end


def test_end_to_end_accuracy_gate_bf16_apply():
    """Train f32, apply in f32 vs active bf16_apply: top-1 must hold on
    the planted-pattern problem (the ISSUE's accuracy gate, CPU-sized)."""
    from keystone_tpu.models import BlockLeastSquaresEstimator
    from keystone_tpu.ops import Convolver, Pooler, SymmetricRectifier
    from keystone_tpu.workflow import Dataset, Pipeline, transformer

    rng = np.random.default_rng(12)
    n, hw, c, k = 96, 12, 3, 3
    imgs = rng.uniform(0, 1, (n, hw, hw, c)).astype(np.float32)
    lbl = rng.integers(0, k, size=n)
    for i in range(n):  # class-dependent planted pattern
        imgs[i, :4, :4, lbl[i] % c] += 1.5
    y = -np.ones((n, k), np.float32)
    y[np.arange(n), lbl] = 1.0
    filt = rng.normal(size=(8, 4, 4, c)).astype(np.float32)

    pipe = (
        Pipeline.of(Convolver(jnp.asarray(filt)))
        .and_then(SymmetricRectifier())
        .and_then(Pooler(3, 3))
        .and_then(transformer(lambda v: v.reshape(-1), name="Flatten"))
        .and_then(
            BlockLeastSquaresEstimator(block_size=32, num_iter=3, lam=1e-3),
            Dataset(imgs),
            Dataset(y),
        )
    )
    with precision.matmul("f32"):
        fitted = pipe.fit()
        acc_f32 = (
            fitted(Dataset(imgs)).get().numpy().argmax(1) == lbl
        ).mean()
    with precision.matmul("bf16_apply"), precision.force_bf16_apply():
        acc_bf16 = (
            fitted(Dataset(imgs)).get().numpy().argmax(1) == lbl
        ).mean()
    assert acc_f32 == 1.0
    assert acc_bf16 >= acc_f32 - 0.02, (acc_f32, acc_bf16)
