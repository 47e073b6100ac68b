"""Solver equivalence tests.

The reference's key correctness pattern (SURVEY.md §4): the distributed
block solver must match an exact local solve on the same synthetic data
(BlockLinearMapperSuite.scala, BlockWeightedLeastSquaresSuite.scala,
LBFGSSuite.scala, KernelModelSuite.scala).  Here "distributed" means
sharded over the virtual 8-device CPU mesh from conftest.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.models import (
    BlockLeastSquaresEstimator,
    BlockWeightedLeastSquaresEstimator,
    DenseLBFGSwithL2,
    DistributedPCAEstimator,
    GaussianKernelGenerator,
    GaussianMixtureModelEstimator,
    KernelRidgeRegressionEstimator,
    KMeansPlusPlusEstimator,
    LinearMapEstimator,
    LocalLeastSquaresEstimator,
    LogisticRegressionEstimator,
    NaiveBayesEstimator,
    PCAEstimator,
    ZCAWhitenerEstimator,
)
from keystone_tpu.workflow import Dataset


def _ridge_exact(x, y, lam_n, center=True):
    """Closed-form (centered) ridge in float64."""
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    if center:
        xm, ym = x.mean(0), y.mean(0)
        xc, yc = x - xm, y - ym
    else:
        xm = ym = None
        xc, yc = x, y
    w = np.linalg.solve(xc.T @ xc + lam_n * np.eye(x.shape[1]), xc.T @ yc)
    b = ym - xm @ w if center else np.zeros(y.shape[1])
    return w, b


@pytest.fixture
def regression_data():
    rng = np.random.default_rng(42)
    n, d, k = 96, 12, 3
    x = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=(d, k)).astype(np.float32)
    y = x @ w_true + 0.01 * rng.normal(size=(n, k)).astype(np.float32)
    return x, y


def test_linear_map_matches_exact(regression_data):
    x, y = regression_data
    lam = 0.1
    model = LinearMapEstimator(lam=lam).fit_dataset(Dataset(x), Dataset(y))
    w_ref, b_ref = _ridge_exact(x, y, lam * x.shape[0])
    np.testing.assert_allclose(np.asarray(model.weights), w_ref, atol=2e-3)
    np.testing.assert_allclose(np.asarray(model.intercept), b_ref, atol=2e-3)


def test_linear_map_with_padding_matches_unpadded(regression_data):
    """91 rows pad to 96 on the 4-wide data axis; result must be identical."""
    x, y = regression_data
    m1 = LinearMapEstimator(lam=0.1).fit_dataset(Dataset(x[:91]), Dataset(y[:91]))
    m2 = LinearMapEstimator(lam=0.1).fit_arrays(x[:91], y[:91])
    np.testing.assert_allclose(
        np.asarray(m1.weights), np.asarray(m2.weights), atol=1e-4
    )


def test_local_least_squares(regression_data):
    x, y = regression_data
    model = LocalLeastSquaresEstimator(lam=0.05).fit_dataset(Dataset(x), Dataset(y))
    w_ref, b_ref = _ridge_exact(x, y, 0.05 * x.shape[0])
    np.testing.assert_allclose(np.asarray(model.weights), w_ref, atol=2e-3)


def test_block_ls_converges_to_exact(regression_data):
    x, y = regression_data
    lam = 0.1
    est = BlockLeastSquaresEstimator(block_size=5, num_iter=40, lam=lam)
    model = est.fit_dataset(Dataset(x), Dataset(y))
    w_ref, b_ref = _ridge_exact(x, y, lam * x.shape[0])
    np.testing.assert_allclose(np.asarray(model.flat_weights)[: x.shape[1]], w_ref, atol=5e-3)
    np.testing.assert_allclose(np.asarray(model.intercept), b_ref, atol=5e-3)
    # predictions too
    pred = np.asarray(model.apply_batch(jnp.asarray(x)))
    np.testing.assert_allclose(pred, x @ w_ref + b_ref, atol=1e-2)


def test_block_ls_single_block_equals_linear_map(regression_data):
    x, y = regression_data
    lam = 0.2
    bm = BlockLeastSquaresEstimator(block_size=12, num_iter=1, lam=lam).fit_arrays(x, y)
    lm = LinearMapEstimator(lam=lam).fit_arrays(x, y)
    np.testing.assert_allclose(
        np.asarray(bm.flat_weights)[:12], np.asarray(lm.weights), atol=1e-3
    )


def test_block_weighted_ls_matches_direct_weighted_solve():
    rng = np.random.default_rng(7)
    n, d, k = 64, 8, 3
    x = rng.normal(size=(n, d)).astype(np.float32)
    labels = rng.integers(0, k, size=n)
    labels[: n // 2] = 0  # skew classes
    y = -np.ones((n, k), np.float32)
    y[np.arange(n), labels] = 1.0
    lam, mw = 0.05, 0.5

    est = BlockWeightedLeastSquaresEstimator(
        block_size=8, num_iter=30, lam=lam, mixture_weight=mw
    )
    model = est.fit_arrays(x, y)

    # direct float64 weighted solve with the same weights
    counts = np.bincount(labels, minlength=k)
    alpha = mw * n / (k * counts[labels]) + (1 - mw)
    wsum = alpha.sum()
    xm = (alpha @ x) / wsum
    ym = (alpha @ y) / wsum
    xc, yc = x - xm, y - ym
    D = np.diag(alpha)
    w_ref = np.linalg.solve(
        xc.T @ D @ xc + lam * n * np.eye(d), xc.T @ D @ yc
    )
    b_ref = ym - xm @ w_ref
    np.testing.assert_allclose(np.asarray(model.flat_weights)[:d], w_ref, atol=5e-3)
    np.testing.assert_allclose(np.asarray(model.intercept), b_ref, atol=5e-3)


def test_block_weighted_mw_zero_equals_unweighted(regression_data):
    x, y = regression_data
    yy = (y == y.max(axis=1, keepdims=True)).astype(np.float32) * 2 - 1
    a = BlockWeightedLeastSquaresEstimator(
        block_size=6, num_iter=25, lam=0.1, mixture_weight=0.0
    ).fit_arrays(x, yy)
    b = BlockLeastSquaresEstimator(block_size=6, num_iter=25, lam=0.1).fit_arrays(x, yy)
    np.testing.assert_allclose(
        np.asarray(a.flat_weights), np.asarray(b.flat_weights), atol=2e-3
    )


def test_lbfgs_matches_closed_form(regression_data):
    x, y = regression_data
    lam = 0.1
    model = DenseLBFGSwithL2(lam=lam, num_iterations=80).fit_dataset(
        Dataset(x), Dataset(y)
    )
    n = x.shape[0]
    w_ref = np.linalg.solve(
        x.T @ x / n + lam * np.eye(x.shape[1]), x.T @ y / n
    )
    np.testing.assert_allclose(np.asarray(model.weights), w_ref, atol=5e-3)


def test_pca_projects_to_principal_subspace():
    rng = np.random.default_rng(3)
    # anisotropic data: top-2 dirs dominate
    base = rng.normal(size=(200, 6)).astype(np.float32)
    base[:, 2:] *= 0.05
    rot, _ = np.linalg.qr(rng.normal(size=(6, 6)))
    x = (base @ rot.T).astype(np.float32)
    for est in (PCAEstimator(2), DistributedPCAEstimator(2)):
        model = est.fit_dataset(Dataset(x))
        c = np.asarray(model.components)  # (6, 2)
        # projector onto learned subspace must match float64 PCA projector
        xm = x - x.mean(0)
        _, _, vt = np.linalg.svd(xm.astype(np.float64), full_matrices=False)
        p_ref = vt[:2].T @ vt[:2]
        p_got = c @ c.T
        np.testing.assert_allclose(p_got, p_ref, atol=1e-2)


def test_zca_whitens_covariance():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(400, 5)).astype(np.float32)
    x = x @ np.diag([3.0, 2.0, 1.0, 0.5, 0.25]).astype(np.float32)
    model = ZCAWhitenerEstimator(eps=1e-5).fit_dataset(Dataset(x))
    w = np.asarray(model.apply_batch(jnp.asarray(x)))
    cov = np.cov(w.T)
    np.testing.assert_allclose(cov, np.eye(5), atol=0.15)


def test_kmeans_recovers_separated_clusters():
    rng = np.random.default_rng(5)
    centers = np.array([[5, 5], [-5, 5], [0, -5]], np.float32)
    x = np.concatenate(
        [c + 0.2 * rng.normal(size=(50, 2)).astype(np.float32) for c in centers]
    )
    model = KMeansPlusPlusEstimator(3, max_iterations=20, seed=1).fit_dataset(
        Dataset(x)
    )
    got = np.sort(np.asarray(model.centers), axis=0)
    np.testing.assert_allclose(got, np.sort(centers, axis=0), atol=0.3)
    onehot = np.asarray(model.apply_batch(jnp.asarray(x)))
    assert onehot.shape == (150, 3)
    assert np.allclose(onehot.sum(axis=1), 1.0)


def test_gmm_recovers_components():
    rng = np.random.default_rng(6)
    x = np.concatenate(
        [
            np.array([4.0, 0.0], np.float32) + 0.5 * rng.normal(size=(150, 2)),
            np.array([-4.0, 0.0], np.float32) + 0.5 * rng.normal(size=(150, 2)),
        ]
    ).astype(np.float32)
    gmm = GaussianMixtureModelEstimator(k=2, max_iterations=30, seed=2).fit_dataset(
        Dataset(x)
    )
    means = np.sort(np.asarray(gmm.means)[:, 0])
    np.testing.assert_allclose(means, [-4.0, 4.0], atol=0.3)
    np.testing.assert_allclose(np.asarray(gmm.weights), [0.5, 0.5], atol=0.1)
    r = np.asarray(gmm.apply_batch(jnp.asarray(x)))
    assert np.allclose(r.sum(axis=1), 1.0, atol=1e-4)


def test_naive_bayes_counts():
    x = np.array(
        [[3, 0, 1], [2, 0, 0], [0, 4, 1], [0, 3, 2]], np.float32
    )
    y = np.array([0, 0, 1, 1])
    model = NaiveBayesEstimator(num_classes=2, lam=1.0).fit_arrays(x, y)
    lp = np.asarray(model.log_prior)
    np.testing.assert_allclose(np.exp(lp), [0.5, 0.5], atol=1e-5)
    lc = np.asarray(model.log_cond)
    # class 0: feature counts [5,0,1]+1 → [6,1,2]/9
    np.testing.assert_allclose(np.exp(lc[0]), [6 / 9, 1 / 9, 2 / 9], atol=1e-5)
    scores = np.asarray(model.apply_batch(jnp.asarray(x)))
    assert (scores.argmax(axis=1) == y).all()


def test_logistic_regression_separable():
    rng = np.random.default_rng(8)
    n = 100
    x = rng.normal(size=(n, 2)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.int32)
    model = LogisticRegressionEstimator(num_classes=2, lam=1e-3, num_iters=60).fit_arrays(
        x, y
    )
    pred = np.asarray(model.apply_batch(jnp.asarray(x))).argmax(axis=1)
    assert (pred == y).mean() > 0.97


def test_krr_matches_direct_dual_solve():
    rng = np.random.default_rng(9)
    n, d, k = 48, 4, 2
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(n, k)).astype(np.float32)
    gamma, lam = 0.5, 1e-2
    kern = GaussianKernelGenerator(gamma)
    est = KernelRidgeRegressionEstimator(kern, lam=lam, block_size=16, num_epochs=25)
    model = est.fit_arrays(x, y)

    K = np.asarray(kern(jnp.asarray(x), jnp.asarray(x)), np.float64)
    alpha_ref = np.linalg.solve(K + lam * n * np.eye(n), y)
    np.testing.assert_allclose(np.asarray(model.alpha)[:n], alpha_ref, atol=5e-3)

    xt = rng.normal(size=(10, d)).astype(np.float32)
    pred = np.asarray(model.apply_batch(jnp.asarray(xt)))
    Kt = np.asarray(kern(jnp.asarray(xt), jnp.asarray(x)), np.float64)
    np.testing.assert_allclose(pred, Kt @ alpha_ref, atol=1e-2)


def test_krr_cached_blocks_matches_recompute():
    """cache_kernel_blocks=True (BlockKernelMatrix LRU sweep, the
    reference's cached-RDD strategy) must produce the same dual
    coefficients as the inlined recompute sweep — including with padding
    (n not a block multiple)."""
    rng = np.random.default_rng(11)
    n, d, k = 53, 5, 2
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(n, k)).astype(np.float32)
    kern = GaussianKernelGenerator(0.4)
    kwargs = dict(lam=1e-2, block_size=16, num_epochs=8)
    plain = KernelRidgeRegressionEstimator(kern, **kwargs).fit_arrays(x, y)
    cached = KernelRidgeRegressionEstimator(
        kern, cache_kernel_blocks=True, **kwargs
    ).fit_arrays(x, y)
    np.testing.assert_allclose(
        np.asarray(cached.alpha)[:n], np.asarray(plain.alpha)[:n], atol=2e-4
    )


def test_solvers_in_pipeline_with_sharded_padding():
    """End-to-end through the DSL with a non-divisible row count."""
    rng = np.random.default_rng(10)
    x = rng.normal(size=(61, 6)).astype(np.float32)
    w = rng.normal(size=(6, 2)).astype(np.float32)
    y = x @ w
    from keystone_tpu.workflow import Identity, Pipeline

    pipe = Pipeline.of(Identity()).and_then(
        LinearMapEstimator(lam=1e-4), Dataset(x), Dataset(y)
    )
    pred = pipe(Dataset(x)).get().numpy()
    np.testing.assert_allclose(pred, y, atol=2e-2)


def test_linear_map_fit_stream_matches_in_memory(regression_data):
    """Out-of-core normal equations: streaming odd-sized host batches
    (forcing shard padding per batch) must reproduce the in-memory fit."""
    x, y = regression_data
    lam = 0.1
    full = LinearMapEstimator(lam=lam).fit_arrays(x, y)

    def batches():
        for i in range(0, x.shape[0], 37):  # 37 ∤ 4: every batch pads
            yield x[i : i + 37], y[i : i + 37]

    streamed = LinearMapEstimator(lam=lam).fit_stream(batches)
    np.testing.assert_allclose(
        np.asarray(streamed.weights), np.asarray(full.weights), atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(streamed.intercept), np.asarray(full.intercept), atol=2e-4
    )
    # no-intercept variant, re-iterable list source
    full0 = LinearMapEstimator(lam=lam, fit_intercept=False).fit_arrays(x, y)
    lst = [(x[:50], y[:50]), (x[50:], y[50:])]
    s0 = LinearMapEstimator(lam=lam, fit_intercept=False).fit_stream(lst)
    np.testing.assert_allclose(
        np.asarray(s0.weights), np.asarray(full0.weights), atol=2e-4
    )


def test_linear_map_fit_stream_rejects_one_shot_generator(regression_data):
    x, y = regression_data
    gen = ((x[i : i + 32], y[i : i + 32]) for i in range(0, x.shape[0], 32))
    with pytest.raises(ValueError, match="not re-iterable"):
        LinearMapEstimator(lam=0.1).fit_stream(gen)


def test_standard_scaler_fit_stream_matches_in_memory():
    from keystone_tpu.ops import StandardScaler

    rng = np.random.default_rng(5)
    x = (100.0 + 3.0 * rng.normal(size=(301, 7))).astype(np.float32)
    full = StandardScaler().fit_arrays(x)
    streamed = StandardScaler().fit_stream(
        [x[i : i + 53] for i in range(0, 301, 53)]  # odd sizes force padding
    )
    np.testing.assert_allclose(
        np.asarray(streamed.mean), np.asarray(full.mean), rtol=1e-5
    )
    # the streaming path centers explicitly (more accurate than the
    # in-memory Σx²−n·mean² shortcut), so they agree only to f32 level
    np.testing.assert_allclose(
        np.asarray(streamed.std), np.asarray(full.std), rtol=5e-4
    )


def test_standard_scaler_fit_stream_survives_large_mean_small_spread():
    """The two-pass centered variance must not cancel: mean ~1e3 with
    std ~0.01 collapses to 0 under the one-pass f32 shortcut."""
    from keystone_tpu.ops import StandardScaler

    rng = np.random.default_rng(6)
    x64 = 1000.0 + 0.01 * rng.standard_normal((512, 5))
    x = x64.astype(np.float32)
    streamed = StandardScaler().fit_stream(
        lambda: (x[i : i + 128] for i in range(0, 512, 128))
    )
    ref_std = x64.std(axis=0, ddof=1)
    np.testing.assert_allclose(np.asarray(streamed.std), ref_std, rtol=0.05)


def test_out_of_core_featurize_then_fit_stream():
    """Full out-of-core training path: stream raw batches through a
    FITTED featurizer, feed featurized batches to the streaming solver,
    and match the in-memory fit of the same featurized data."""
    from keystone_tpu.ops import LinearRectifier, RandomSignNode

    from keystone_tpu.workflow import Pipeline

    rng = np.random.default_rng(9)
    n, d, k = 192, 16, 3
    x = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=(d, k)).astype(np.float32)
    y = (x @ w_true).astype(np.float32)
    featurizer = Pipeline.of(RandomSignNode.init(d, seed=1)).and_then(
        LinearRectifier(0.0)
    )

    def feat_batches():
        for i in range(0, n, 50):  # odd size: padding + pow2 bucketing
            bx, by = x[i : i + 50], y[i : i + 50]
            yield featurizer(Dataset(bx)).get().numpy(), by

    streamed = LinearMapEstimator(lam=1e-3).fit_stream(feat_batches)
    full_feats = featurizer(Dataset(x)).get().numpy()
    full = LinearMapEstimator(lam=1e-3).fit_arrays(full_feats, y)
    np.testing.assert_allclose(
        np.asarray(streamed.weights), np.asarray(full.weights), atol=2e-4
    )


def test_krr_cached_disk_tier_matches_recompute(monkeypatch, tmp_path):
    """K beyond the HBM budget: the cached mode goes TIERED (partial HBM
    LRU + disk-persisted column blocks) instead of silently assuming K
    fits HBM (round-2 review weak-7).  Parity with the recompute fit, and
    epochs >= 2 must reread from cache/disk, not regenerate gemms."""
    from keystone_tpu.models.kernel_ridge import (
        GaussianKernelGenerator,
        KernelRidgeRegressionEstimator,
    )

    rng = np.random.default_rng(0)
    n, d, k = 256, 16, 2
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(n, k)).astype(np.float32)
    kern = GaussianKernelGenerator(gamma=0.05)

    ref = KernelRidgeRegressionEstimator(
        kern, lam=1e-2, block_size=64, num_epochs=2
    ).fit_arrays(x, y)

    # force the disk tier: pretend HBM fits ~one column block
    import keystone_tpu.workflow.profiling as prof

    monkeypatch.setattr(
        prof, "device_hbm_budget", lambda frac=0.5: 256 * 64 * 4 + 1
    )

    # count kernel gemms: epoch 2 must REREAD (HBM/disk), not regenerate
    calls = []
    orig_call = type(kern).__call__

    def counting_call(self, a, b):
        calls.append(np.shape(a)[0])
        return orig_call(self, a, b)

    monkeypatch.setattr(type(kern), "__call__", counting_call)
    cached = KernelRidgeRegressionEstimator(
        kern,
        lam=1e-2,
        block_size=64,
        num_epochs=2,
        cache_kernel_blocks=True,
        kernel_cache_dir=str(tmp_path / "kcache"),
    ).fit_arrays(x, y)
    # exactly 4 full-column gemms (n rows each) across BOTH epochs —
    # later sweeps reload from the HBM LRU or disk
    assert [c for c in calls if c == n] == [n] * 4, calls
    # 4 column blocks + the fingerprint meta persisted on disk
    import os

    files = sorted(os.listdir(tmp_path / "kcache"))
    assert sum(f.endswith(".npy") for f in files) == 4, files
    # the durable spill path publishes a BLAKE2b sidecar per column —
    # read-time verification is what catches a torn spill block
    assert sum(f.endswith(".npy.b2") for f in files) == 4, files
    assert "kcache_meta.json" in files
    np.testing.assert_allclose(
        np.asarray(cached.alpha), np.asarray(ref.alpha), atol=2e-4
    )

    # a DIFFERENT problem reusing the same cache dir must invalidate it,
    # never serve the previous fit's kernel columns
    x2 = rng.normal(size=(n, d)).astype(np.float32)
    ref2 = KernelRidgeRegressionEstimator(
        kern, lam=1e-2, block_size=64, num_epochs=2
    ).fit_arrays(x2, y)
    cached2 = KernelRidgeRegressionEstimator(
        kern,
        lam=1e-2,
        block_size=64,
        num_epochs=2,
        cache_kernel_blocks=True,
        kernel_cache_dir=str(tmp_path / "kcache"),
    ).fit_arrays(x2, y)
    np.testing.assert_allclose(
        np.asarray(cached2.alpha), np.asarray(ref2.alpha), atol=2e-4
    )


def test_kernel_spill_dir_refuses_foreign_files(tmp_path):
    """A stale cache dir is cleared file-by-file (only kcol_*.npy +
    kcache_meta.json); a dir holding ANYTHING else is refused, never
    rmtree'd (ADVICE r3 medium: data-loss hazard on a reused user
    directory)."""
    import os

    from keystone_tpu.models.kernel_matrix import BlockKernelMatrix
    from keystone_tpu.models.kernel_ridge import GaussianKernelGenerator

    rng = np.random.default_rng(1)
    x = rng.normal(size=(32, 4)).astype(np.float32)
    kern = GaussianKernelGenerator(gamma=0.1)

    d = tmp_path / "user_dir"
    d.mkdir()
    (d / "precious.txt").write_text("do not delete")
    with pytest.raises(ValueError, match="does not own"):
        BlockKernelMatrix(kern, x, block_size=16, spill_dir=str(d))
    assert (d / "precious.txt").read_text() == "do not delete"

    # a dir holding ONLY cache-owned files from a stale fit is cleared
    # per-file and reused — including the durable path's derivatives: a
    # BLAKE2b sidecar and an atomic-write tmp abandoned by a crashed
    # writer (neither may render a reusable cache dir "foreign")
    d2 = tmp_path / "stale"
    d2.mkdir()
    (d2 / "kcol_00000.npy").write_bytes(b"stale")
    (d2 / "kcol_00000.npy.b2").write_bytes(b"stale-sidecar")
    (d2 / "kcol_00001.npy.tmp.1234.5678").write_bytes(b"crashed-writer")
    (d2 / "kcache_meta.json").write_text("{}")
    BlockKernelMatrix(kern, x, block_size=16, spill_dir=str(d2))
    assert not (d2 / "kcol_00000.npy").exists()
    assert not (d2 / "kcol_00000.npy.b2").exists()
    assert not (d2 / "kcol_00001.npy.tmp.1234.5678").exists()
    assert (d2 / "kcache_meta.json").exists()

    # the fingerprint keys the FULL kernel identity: same gamma attr on
    # a different generator type must invalidate, not pass validation
    class OtherKernel:
        gamma = 0.1

        def __call__(self, a, b):  # pragma: no cover - never sampled
            return np.zeros((a.shape[0], b.shape[0]), np.float32)

    BlockKernelMatrix(OtherKernel(), x, block_size=16, spill_dir=str(d2))
    import json

    # the fingerprint must be STABLE across instances (no id-based
    # default repr leaking in) — a fresh instance of the same plain
    # class must validate, not clear, the dir
    meta1 = json.load(open(d2 / "kcache_meta.json"))
    BlockKernelMatrix(OtherKernel(), x, block_size=16, spill_dir=str(d2))
    assert json.load(open(d2 / "kcache_meta.json")) == meta1

    # OS dotfile artifacts (.nfsXXXX, .DS_Store) are tolerated, not
    # treated as foreign user data
    (d2 / ".nfs0000deadbeef").write_bytes(b"")
    BlockKernelMatrix(kern, x, block_size=16, spill_dir=str(d2))
    assert (d2 / ".nfs0000deadbeef").exists()

    # and re-instantiating with the original generator re-fingerprints
    # (round-trip sanity: validation is on content, not mtime)
    assert meta1["fingerprint"] != json.load(
        open(d2 / "kcache_meta.json")
    )["fingerprint"]


# ------------------------------------------- the weighted solver's kept factors
# A fit of more than one sweep factors each block's regularised Gramian
# once, before the first sweep (models/block_weighted_ls.py §
# factor_cache_blocks); these hold it to a Gauss–Seidel that factors anew
# in every sweep, and to the program's shape.  The suite's mesh has four
# devices on the data axis, so a device holds a quarter of the rows.
_FC_BLOCK = 16
_FC_SHAPES = {  # rows, features: blocks of _FC_BLOCK
    "rows_ge_block": (64, 64),
    "ragged_last_block": (64, 56),
    "rows_lt_block": (32, 64),
}


def _fc_data(rows, d, k=3, seed=11):
    from keystone_tpu.models import block_weighted_ls as bw

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, d)).astype(np.float32))
    cls = rng.integers(0, k, size=rows)
    cls[: rows // 2] = 0  # skewed classes, so the weights differ
    y = jnp.asarray(2.0 * np.eye(k, dtype=np.float32)[cls] - 1.0)
    nf = jnp.float32(rows)
    return x, y, bw.class_weights(y, nf, 0.5), nf


def _gauss_seidel_factoring_every_sweep(x, y, alpha, n, lam, sweeps, bs, intercept):
    """The same sweep in plain ``jax.numpy``: every block's Gramian and its
    Cholesky factor rebuilt in every sweep."""
    hi = jax.lax.Precision.HIGHEST
    if intercept:
        wsum = jnp.sum(alpha)
        ok = (alpha > 0).astype(jnp.float32)[:, None]
        x = (x - (alpha @ x) / wsum) * ok
        y = (y - (alpha @ y) / wsum) * ok
    nb = -(-x.shape[1] // bs)
    x = jnp.pad(x, ((0, 0), (0, nb * bs - x.shape[1])))
    sa = jnp.sqrt(alpha)[:, None]
    w = [jnp.zeros((bs, y.shape[1]), jnp.float32) for _ in range(nb)]
    p = jnp.zeros_like(y)
    for _ in range(sweeps):
        for b in range(nb):
            xb = x[:, b * bs:(b + 1) * bs]
            a = xb * sa
            target = (y - p) * sa + a @ w[b]
            ata = jnp.matmul(a.T, a, precision=hi) + lam * n * jnp.eye(bs)
            factor = jax.scipy.linalg.cho_factor(ata)
            new = jax.scipy.linalg.cho_solve(factor, jnp.matmul(a.T, target, precision=hi))
            p = p + xb @ (new - w[b])
            w[b] = new
    return np.asarray(jnp.stack(w))


@pytest.mark.parametrize("num_iter", [1, 2, 3])
@pytest.mark.parametrize("intercept", [True, False], ids=["intercept", "no_intercept"])
@pytest.mark.parametrize("shape", list(_FC_SHAPES))
def test_weighted_bcd_keeping_factors_fits_what_refactoring_fits(
    shape, intercept, num_iter, monkeypatch
):
    from keystone_tpu.models import block_weighted_ls as bw

    rows, d = _FC_SHAPES[shape]
    blocks = -(-d // _FC_BLOCK)
    kept = bw.factor_cache_blocks(rows, d, _FC_BLOCK, num_iter)
    assert kept == (blocks if num_iter > 1 and shape != "rows_lt_block" else 0)
    x, y, alpha, nf = _fc_data(rows, d)
    fit = lambda: np.asarray(  # noqa: E731
        bw._weighted_bcd_fit(x, y, alpha, nf, 0.1, num_iter, _FC_BLOCK, intercept)[0]
    )
    got = fit()
    want = _gauss_seidel_factoring_every_sweep(
        x, y, alpha, nf, 0.1, num_iter, _FC_BLOCK, intercept
    )
    assert got.shape == want.shape == (blocks, _FC_BLOCK, 3)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-5
    if kept:
        # the solver's own program with the rule answering "none": the same
        # operations on the same device layout, so the same bits
        monkeypatch.setattr(bw, "factor_cache_blocks", lambda *a: 0)
        bw._weighted_bcd_fit.clear_cache()
        try:
            refactored = fit()
        finally:
            bw._weighted_bcd_fit.clear_cache()
        assert np.array_equal(got, refactored)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _fc_jaxpr(shape, num_iter):
    from keystone_tpu.models import block_weighted_ls as bw

    x, y, alpha, nf = _fc_data(*_FC_SHAPES[shape])
    (call,) = jax.make_jaxpr(
        lambda *a: bw._weighted_bcd_fit(*a, 0.1, num_iter, _FC_BLOCK, True)
    )(x, y, alpha, nf).eqns
    return call.params["jaxpr"].jaxpr  # the jitted program's own body


def _loops(jaxpr):
    """The program's outermost loops (a ``fori_loop`` of static bounds
    traces to a ``scan`` as well), by length."""
    return {e.params["length"]: e.params["jaxpr"].jaxpr for e in jaxpr.eqns
            if e.primitive.name == "scan"}


def _shapes_out(eqns):
    return {tuple(v.aval.shape) for e in eqns for v in e.outvars}


def test_no_sweep_of_a_three_sweep_fit_builds_or_factors_a_gramian():
    program = _fc_jaxpr("rows_ge_block", 3)
    eqns = list(_eqns(program))
    blocks = 64 // _FC_BLOCK
    loops = _loops(program)
    assert set(loops) == {3, blocks}  # the sweeps; the prologue over the blocks
    sweep = list(_eqns(loops[3]))
    assert "cholesky" not in {e.primitive.name for e in sweep}
    assert "triangular_solve" in {e.primitive.name for e in sweep}
    dots = [e for e in sweep if e.primitive.name == "dot_general"]
    assert dots and (_FC_BLOCK, _FC_BLOCK) not in _shapes_out(dots)
    # all of that is in the prologue, once a block
    prologue = list(_eqns(loops[blocks]))
    assert [e.primitive.name for e in eqns].count("cholesky") == 1
    assert [e.primitive.name for e in prologue].count("cholesky") == 1
    assert (_FC_BLOCK, _FC_BLOCK) in _shapes_out(
        e for e in prologue if e.primitive.name == "dot_general"
    )
    assert (blocks, _FC_BLOCK, _FC_BLOCK) in _shapes_out(eqns)


@pytest.mark.parametrize(
    "shape,num_iter", [("rows_ge_block", 1), ("rows_lt_block", 3)],
    ids=["one_sweep", "rows_lt_block"],
)
def test_a_fit_that_keeps_no_factor_holds_no_array_of_them(shape, num_iter):
    program = _fc_jaxpr(shape, num_iter)
    assert (64 // _FC_BLOCK, _FC_BLOCK, _FC_BLOCK) not in _shapes_out(_eqns(program))
    # Gramian and factor are where they were: in the block step of the sweeps
    loops = _loops(program)
    assert set(loops) == {num_iter}
    names = [e.primitive.name for e in _eqns(loops[num_iter])]
    assert names.count("cholesky") == 1


def test_solve_spd_is_its_two_halves_and_traces_as_before():
    from keystone_tpu.models.common import factor_spd, solve_factored, solve_spd

    def before(A, B, reg):
        A = A + reg * jnp.eye(A.shape[0], dtype=A.dtype)
        c, lower = jax.scipy.linalg.cho_factor(A)
        return jax.scipy.linalg.cho_solve((c, lower), B)

    rng = np.random.default_rng(5)
    m = rng.normal(size=(24, 8)).astype(np.float32)
    A, B = jnp.asarray(m.T @ m), jnp.asarray(rng.normal(size=(8, 3)).astype(np.float32))
    halves = lambda A, B, reg: solve_factored(factor_spd(A, reg), B)  # noqa: E731
    texts = {
        str(jax.make_jaxpr(f)(A, B, 0.5)) for f in (before, halves, lambda *a: solve_spd(*a))
    }
    assert len(texts) == 1
    assert np.array_equal(solve_spd(A, B, 0.5), before(A, B, 0.5))
