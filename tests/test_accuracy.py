"""Accuracy tests that can FAIL (round-1 review item 5).

Round-1's end-to-end tests asserted acc==1.0 on separable synthetic data,
which cannot catch subtle solver bugs (a wrong λ scaling or a dropped
class weight still hits 1.0).  This module adds:

  (a) a NON-separable problem with a computable Bayes rate — the fitted
      pipeline's accuracy must land in a band around the Bayes optimum
      (too low = broken solver, too high = leakage/bug in the harness);
  (b) cross-checks of the solvers/decompositions against
      scipy/scikit-learn closed-form results on fixed seeds, at
      tolerances tight enough that a λ-convention or class-weight
      formula change fails the test;
  (c) a real-format golden dataset: deterministic textured JPEGs packed
      into a real tar, decoded through ImageNetLoader, validated against
      an independent PIL decode, and fitted end to end.

The sklearn cross-checks pin the λ conventions documented in the model
docstrings: LinearMapEstimator solves (XᵀX + λnI)w = Xᵀy →
sklearn.Ridge(alpha=λ·n); LogisticRegressionEstimator minimizes
mean-CE + ½λ‖w‖² → sklearn C = 1/(λ·n).
"""

import io
import tarfile

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.workflow import Dataset, Pipeline


# ------------------------------------------------------------------ (a) Bayes


def test_linear_pipeline_hits_bayes_band():
    """Two overlapping Gaussians, ‖μ₁−μ₀‖ = 2, identity covariance: the
    Bayes rate is Φ(1) ≈ 0.841 and LDA (≈ ridge on ±1 targets) is Bayes
    optimal.  Held-out accuracy of the FULL PIPELINE (DSL fit → predict)
    must land in a band around the Bayes rate — a solver bug drops it
    below; train-set leakage or a harness bug pushes it above."""
    from scipy.stats import norm

    from keystone_tpu.models import LinearMapEstimator
    from keystone_tpu.ops import ClassLabelIndicators, LinearRectifier, MaxClassifier

    rng = np.random.default_rng(7)
    d, n_train, n_test = 8, 4096, 4096
    mu = np.zeros(d)
    mu[0] = 1.0  # means ±e0 → class-mean distance 2 → Bayes acc Φ(1)

    def draw(n):
        lab = rng.integers(0, 2, size=n)
        x = rng.normal(size=(n, d)) + (2 * lab[:, None] - 1) * mu[None, :]
        return x.astype(np.float32), lab.astype(np.int32)

    xtr, ytr = draw(n_train)
    xte, yte = draw(n_test)
    bayes = float(norm.cdf(1.0))

    labels_pm1 = ClassLabelIndicators(2)(Dataset(ytr))
    pipe = Pipeline.of(LinearRectifier(-1e9)).and_then(
        LinearMapEstimator(lam=1e-4), Dataset(xtr), labels_pm1
    ).and_then(MaxClassifier())
    fitted = pipe.fit()
    pred = fitted(Dataset(xte)).get().numpy()
    acc = float((pred[: yte.shape[0]].ravel() == yte).mean())
    assert bayes - 0.04 <= acc <= bayes + 0.04, (acc, bayes)


def _indicators(labels, k):
    y = -np.ones((labels.shape[0], k), np.float32)
    y[np.arange(labels.shape[0]), labels] = 1.0
    return y


def test_linear_estimator_hits_bayes_band():
    from scipy.stats import norm

    from keystone_tpu.models import LinearMapEstimator

    rng = np.random.default_rng(7)
    d, n_train, n_test = 8, 4096, 4096
    mu = np.zeros(d)
    mu[0] = 1.0

    def draw(n):
        lab = rng.integers(0, 2, size=n)
        x = rng.normal(size=(n, d)) + (2 * lab[:, None] - 1) * mu[None, :]
        return x.astype(np.float32), lab.astype(np.int32)

    xtr, ytr = draw(n_train)
    xte, yte = draw(n_test)
    bayes = float(norm.cdf(1.0))  # ≈ 0.8413

    model = LinearMapEstimator(lam=1e-4).fit_arrays(xtr, _indicators(ytr, 2))
    pred = np.argmax(np.asarray(model.apply_batch(jnp.asarray(xte))), axis=1)
    acc = float((pred == yte).mean())
    assert bayes - 0.04 <= acc <= bayes + 0.04, (acc, bayes)


def test_weighted_solver_rebalances_skewed_classes():
    """9:1 imbalanced overlapping classes: mixture_weight=1 (fully
    balanced) must lift minority-class recall well above the unweighted
    solver's.  Fails if class_weights stops weighting."""
    from keystone_tpu.models import (
        BlockLeastSquaresEstimator,
        BlockWeightedLeastSquaresEstimator,
    )

    rng = np.random.default_rng(3)
    d = 8
    n_maj, n_min = 3600, 400
    x = np.concatenate(
        [
            rng.normal(size=(n_maj, d)) - 0.75,
            rng.normal(size=(n_min, d)) + 0.75,
        ]
    ).astype(np.float32)
    lab = np.concatenate([np.zeros(n_maj, np.int32), np.ones(n_min, np.int32)])
    perm = rng.permutation(lab.shape[0])
    x, lab = x[perm], lab[perm]
    y = _indicators(lab, 2)

    xte = np.concatenate(
        [rng.normal(size=(1000, d)) - 0.75, rng.normal(size=(1000, d)) + 0.75]
    ).astype(np.float32)
    yte = np.concatenate([np.zeros(1000, np.int32), np.ones(1000, np.int32)])

    def minority_recall(model):
        pred = np.argmax(np.asarray(model.apply_batch(jnp.asarray(xte))), axis=1)
        return float((pred[yte == 1] == 1).mean())

    plain = BlockLeastSquaresEstimator(
        block_size=8, num_iter=4, lam=1e-3
    ).fit_arrays(x, y)
    balanced = BlockWeightedLeastSquaresEstimator(
        block_size=8, num_iter=4, lam=1e-3, mixture_weight=1.0
    ).fit_arrays(x, y)
    r_plain, r_bal = minority_recall(plain), minority_recall(balanced)
    assert r_bal > r_plain + 0.05, (r_plain, r_bal)


# --------------------------------------------------- (b) sklearn cross-checks


def test_ridge_lambda_convention_matches_sklearn():
    """LinearMapEstimator(λ) must equal sklearn Ridge(alpha=λ·n) exactly
    (same normal equations).  A changed λ scaling fails this at once."""
    from sklearn.linear_model import Ridge

    from keystone_tpu.models import LinearMapEstimator

    rng = np.random.default_rng(0)
    n, d, k = 512, 24, 3
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(n, k)).astype(np.float32)
    lam = 0.37

    model = LinearMapEstimator(lam=lam).fit_arrays(x, y)
    sk = Ridge(alpha=lam * n, fit_intercept=True).fit(x, y)
    np.testing.assert_allclose(
        np.asarray(model.weights), sk.coef_.T, rtol=2e-3, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(model.intercept), sk.intercept_, rtol=2e-3, atol=2e-4
    )


def test_weighted_ls_matches_f64_weighted_normal_equations():
    """BlockWeightedLeastSquares (converged BCD) must equal the direct
    f64 weighted ridge solve with the documented α formula.  Fails if
    the class-weight formula or its centering changes."""
    from keystone_tpu.models import BlockWeightedLeastSquaresEstimator
    from keystone_tpu.models.block_weighted_ls import class_weights

    rng = np.random.default_rng(1)
    n, d, k = 600, 16, 3
    x = rng.normal(size=(n, d)).astype(np.float32)
    lab = rng.choice(k, size=n, p=[0.6, 0.3, 0.1])
    y = _indicators(lab, k)
    lam, mw = 1e-2, 0.5

    est = BlockWeightedLeastSquaresEstimator(
        block_size=8, num_iter=30, lam=lam, mixture_weight=mw
    )
    model = est.fit_arrays(x, y)

    # independent f64 reference with the documented formula
    alpha = np.asarray(class_weights(jnp.asarray(y), np.float32(n), mw), np.float64)
    xd, yd = x.astype(np.float64), y.astype(np.float64)
    xm = alpha @ xd / alpha.sum()
    ym = alpha @ yd / alpha.sum()
    xc, yc = xd - xm, yd - ym
    w_ref = np.linalg.solve(
        xc.T @ (alpha[:, None] * xc) + lam * n * np.eye(d),
        xc.T @ (alpha[:, None] * yc),
    )
    got = np.asarray(model.flat_weights)[:d]
    np.testing.assert_allclose(got, w_ref, rtol=5e-3, atol=5e-4)
    # and the intercept folds the weighted means: b = ym − xm·W
    np.testing.assert_allclose(
        np.asarray(model.apply_batch(jnp.asarray(xm[None].astype(np.float32))))[0],
        ym,
        atol=5e-3,
    )


def test_logreg_matches_sklearn():
    """mean-CE + ½λ‖w‖² ⇒ sklearn C = 1/(λ·n), fit_intercept=False."""
    from sklearn.linear_model import LogisticRegression

    from keystone_tpu.models import LogisticRegressionEstimator

    rng = np.random.default_rng(2)
    n, d, k = 800, 10, 3
    w_true = rng.normal(size=(d, k))
    x = rng.normal(size=(n, d)).astype(np.float32)
    lab = np.array([rng.choice(k, p=p) for p in
                    np.exp(x @ w_true) / np.exp(x @ w_true).sum(1, keepdims=True)],
                   np.int32)
    lam = 1e-2

    model = LogisticRegressionEstimator(k, lam=lam, num_iters=300).fit_arrays(x, lab)
    sk = LogisticRegression(
        C=1.0 / (lam * n), fit_intercept=False, tol=1e-8, max_iter=2000
    ).fit(x, lab)
    # softmax weights are identifiable up to a per-row constant shift;
    # compare after centering columns per feature
    got = np.asarray(model.weights)
    want = sk.coef_.T
    got = got - got.mean(axis=1, keepdims=True)
    want = want - want.mean(axis=1, keepdims=True)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-3)


def test_pca_matches_sklearn_subspace():
    from sklearn.decomposition import PCA as SKPCA

    from keystone_tpu.models import PCAEstimator

    rng = np.random.default_rng(4)
    n, d, q = 400, 20, 5
    x = (rng.normal(size=(n, q)) @ rng.normal(size=(q, d)) * 3.0
         + rng.normal(size=(n, d)) * 0.1).astype(np.float32)

    ours = PCAEstimator(q).fit_arrays(x)
    p_ours = np.asarray(ours.components)  # (d, q)
    p_sk = SKPCA(n_components=q).fit(x).components_.T  # (d, q)
    # subspaces equal ⇔ projection operators equal (basis sign/rotation-free)
    np.testing.assert_allclose(
        p_ours @ p_ours.T, p_sk @ p_sk.T, atol=1e-3
    )


def test_kmeans_matches_sklearn_centers():
    from sklearn.cluster import KMeans as SKKMeans

    from keystone_tpu.models import KMeansPlusPlusEstimator

    rng = np.random.default_rng(5)
    k, d = 4, 6
    centers = rng.normal(size=(k, d)) * 6.0
    x = np.concatenate(
        [c + rng.normal(size=(200, d)) * 0.3 for c in centers]
    ).astype(np.float32)

    ours = KMeansPlusPlusEstimator(k, max_iterations=20, seed=0).fit_arrays(x)
    sk = SKKMeans(n_clusters=k, n_init=10, random_state=0).fit(x)
    got = np.asarray(ours.centers)
    want = sk.cluster_centers_
    # match up to permutation: greedy nearest pairing must be tight
    dist = np.linalg.norm(got[:, None, :] - want[None, :, :], axis=-1)
    order = dist.argmin(axis=1)
    assert sorted(order.tolist()) == list(range(k)), "centers not a permutation"
    assert float(dist[np.arange(k), order].max()) < 0.15


def test_gmm_matches_sklearn_means_and_loglik():
    from sklearn.mixture import GaussianMixture

    from keystone_tpu.models import GaussianMixtureModelEstimator

    rng = np.random.default_rng(6)
    k, d = 3, 4
    centers = np.array([[-4.0] * d, [0.0] * d, [4.0] * d])
    x = np.concatenate(
        [c + rng.normal(size=(300, d)) * (0.5 + i * 0.25)
         for i, c in enumerate(centers)]
    ).astype(np.float32)

    ours = GaussianMixtureModelEstimator(k, max_iterations=60, seed=0).fit_arrays(x)
    sk = GaussianMixture(
        n_components=k, covariance_type="diag", n_init=5, random_state=0
    ).fit(x)
    got = np.asarray(ours.means)
    want = sk.means_
    dist = np.linalg.norm(got[:, None, :] - want[None, :, :], axis=-1)
    order = dist.argmin(axis=1)
    assert sorted(order.tolist()) == list(range(k))
    assert float(dist[np.arange(k), order].max()) < 0.25
    # average log-likelihood within 1% of sklearn's (f64 numpy, model params)
    from scipy.special import logsumexp

    w = np.asarray(ours.weights, np.float64)
    m = np.asarray(ours.means, np.float64)
    v = np.asarray(ours.variances, np.float64)
    xd = x.astype(np.float64)
    lg = (
        np.log(w)[None, :]
        - 0.5 * np.sum(np.log(2 * np.pi * v), axis=1)[None, :]
        - 0.5 * np.sum(
            (xd[:, None, :] - m[None, :, :]) ** 2 / v[None, :, :], axis=2
        )
    )
    ll_ours = float(np.mean(logsumexp(lg, axis=1)))
    ll_sk = float(sk.score(x))
    assert abs(ll_ours - ll_sk) < 0.01 * abs(ll_sk), (ll_ours, ll_sk)


# ------------------------------------------------ (c) real-format golden data


def _textured_jpeg(rng, kind: str, hw: int = 64) -> bytes:
    """Textured JPEG: a patchwork of oriented gratings whose orientation
    MIX depends on the class (kind 'h': mostly horizontal tiles, 'v':
    mostly vertical).  Fisher vectors discriminate via per-component
    descriptor OCCUPANCY, so the classes must differ in descriptor
    *distribution* — a single pure tone per image makes every descriptor
    identical and FV encodes only noise residuals (anticorrelated across
    a class, which defeats any classifier)."""
    from PIL import Image as PILImage

    tile = 16
    p_h = 0.92 if kind == "h" else 0.08
    img = np.zeros((hw, hw))
    y, x = np.mgrid[0:tile, 0:tile]
    grat_h = 127 + 90 * np.sin(y * 0.9 + 0.5)
    grat_v = 127 + 90 * np.sin(x * 0.9 + 0.5)
    for ty in range(0, hw, tile):
        for tx in range(0, hw, tile):
            img[ty:ty + tile, tx:tx + tile] = (
                grat_h if rng.uniform() < p_h else grat_v
            )
    img = (img + rng.normal(scale=5.0, size=(hw, hw))).clip(0, 255)
    arr = np.stack([img] * 3, axis=-1).astype(np.uint8)
    buf = io.BytesIO()
    PILImage.fromarray(arr).save(buf, format="JPEG", quality=95)
    return buf.getvalue()


def test_imagenet_golden_tar_pixels_and_fit(tmp_path):
    """Real tar of real JPEGs: (1) loader pixels must match an
    independent PIL decode; (2) the SIFT→PCA→FV→weighted-LS pipeline
    must separate the two texture classes on held-out images."""
    from PIL import Image as PILImage

    from keystone_tpu.loaders import ImageNetLoader
    from keystone_tpu.models import BlockWeightedLeastSquaresEstimator
    from keystone_tpu.models.gmm import GaussianMixtureModelEstimator
    from keystone_tpu.models.pca import PCAEstimator
    from keystone_tpu.ops import GrayScaler, NormalizeRows, SIFTExtractor, SignedHellingerMapper
    from keystone_tpu.ops.fisher import FisherVector

    rng = np.random.default_rng(0)
    per_class, hw = 10, 64
    blobs = {}
    for synset, kind in (("horiz", "h"), ("vert", "v")):
        with tarfile.open(tmp_path / f"{synset}.tar", "w") as tf:
            for i in range(per_class):
                blob = _textured_jpeg(rng, kind, hw)
                blobs[f"{synset}_{i}"] = blob
                info = tarfile.TarInfo(f"{synset}_{i}.JPEG")
                info.size = len(blob)
                tf.addfile(info, io.BytesIO(blob))

    ld = ImageNetLoader.load(str(tmp_path), size=(hw, hw))
    assert ld.data.n == 2 * per_class
    labels = np.asarray(ld.labels.numpy())
    assert (labels == 0).sum() == per_class and (labels == 1).sum() == per_class

    # (1) pixel parity with an independent PIL decode (identical codec
    # bytes, so tolerance only covers decoder rounding)
    imgs = np.asarray(ld.data.numpy())
    ref0 = np.asarray(
        PILImage.open(io.BytesIO(blobs["horiz_0"])).convert("RGB"), np.float32
    )
    scale = imgs.max()
    want = ref0 / (255.0 if scale <= 1.001 else 1.0)
    err = np.abs(imgs[0].astype(np.float32) - want).mean()
    assert err < 2.0 * (1.0 if scale > 1.001 else 1 / 255.0), err

    # (2) end-to-end fit on 8/class, eval on held-out 2/class
    x = imgs.astype(np.float32)
    if x.max() > 1.001:
        x = x / 255.0
    tr = np.concatenate([np.arange(0, 8), np.arange(per_class, per_class + 8)])
    te = np.array([8, 9, per_class + 8, per_class + 9])

    gray = GrayScaler()
    sift = SIFTExtractor(step=6, bin_sizes=(4,))
    g = gray.apply_batch(jnp.asarray(x))
    desc, mask = sift.apply_batch(g)
    flat = np.asarray(desc).reshape(-1, desc.shape[-1])
    mflat = np.asarray(mask).reshape(-1) > 0
    pca = PCAEstimator(16).fit_arrays(flat[mflat][:4000])
    d2, m2 = pca.apply_batch(desc, mask=mask)
    gmm = GaussianMixtureModelEstimator(8, max_iterations=30, seed=0).fit_arrays(
        np.asarray(d2).reshape(-1, 16)[np.asarray(m2).reshape(-1) > 0][:4000]
    )
    fv = FisherVector(gmm)
    feats = fv.apply_batch(d2, mask=m2)
    feats = NormalizeRows().apply_batch(SignedHellingerMapper().apply_batch(feats))
    feats = np.asarray(feats)

    model = BlockWeightedLeastSquaresEstimator(
        block_size=64, num_iter=3, lam=1e-2, mixture_weight=0.5
    ).fit_arrays(feats[tr], _indicators(labels[tr], 2))
    pred = np.argmax(np.asarray(model.apply_batch(jnp.asarray(feats[te]))), axis=1)
    assert (pred == labels[te]).mean() == 1.0, (pred, labels[te])


# ---------------------------------------------------------------------------
# App-level accuracy bands (round-2 review item 6): skewed non-separable
# synthetic through the APP entry points — sensitive enough that
# perturbing mixture_weight or λ in the app config fails the band.
# ---------------------------------------------------------------------------


def _skewed_gaussian_problem(tmp_path, K=6, D=40, n=6144):
    """Heavily skewed Gaussian prototypes with overlap; returns the
    on-disk paths the Timit app loads plus ORACLE metrics computed from
    the true generative model (nearest-prototype rules)."""
    priors = np.array([0.80] + [0.04] * (K - 1))
    protos = np.zeros((K, D), np.float32)
    for c in range(K):
        protos[c, c] = 1.5
    sigma = 1.0

    def draw(n_, seed):
        r = np.random.default_rng(seed)
        lab = r.choice(K, size=n_, p=priors)
        x = protos[lab] + sigma * r.normal(size=(n_, D)).astype(np.float32)
        return x.astype(np.float32), lab.astype(np.int64)

    xtr, ytr = draw(n, 1)
    xte, yte = draw(n, 2)
    paths = {}
    for name, arr in [
        ("ftr", xtr), ("ltr", ytr), ("fte", xte), ("lte", yte)
    ]:
        p = str(tmp_path / f"{name}.npy")
        np.save(p, arr)
        paths[name] = p

    def macro_f1(pred, y):
        f1 = []
        for c in range(K):
            tp = ((pred == c) & (y == c)).sum()
            fp = ((pred == c) & (y != c)).sum()
            fn = ((pred != c) & (y == c)).sum()
            p_ = tp / max(tp + fp, 1)
            r_ = tp / max(tp + fn, 1)
            f1.append(2 * p_ * r_ / max(p_ + r_, 1e-9))
        return float(np.mean(f1))

    d2 = ((xte[:, None, :] - protos[None]) ** 2).sum(-1)
    balanced = np.argmin(d2, axis=1)  # the balanced-cost Bayes rule
    oracle = {
        "balanced_macro_f1": macro_f1(balanced, yte),
        "balanced_acc": float((balanced == yte).mean()),
    }
    return paths, oracle, K


def _timit_cfg(paths, K, **kw):
    from keystone_tpu.pipelines.timit import Config

    base = dict(
        features_path=paths["ftr"],
        labels_path=paths["ltr"],
        test_features_path=paths["fte"],
        test_labels_path=paths["lte"],
        num_cosine_features=512,
        cosine_block_size=256,
        num_classes=K,
        num_epochs=3,
        lam=1e-3,
        mixture_weight=0.9,
    )
    base.update(kw)
    return Config(**base)


def test_timit_app_macro_band_and_config_sensitivity(tmp_path):
    """TimitPipeline through run(): with a high mixture_weight the
    macro-F1 must land in a band around the BALANCED Bayes oracle
    (calibrated: app 0.428 vs oracle 0.433 on this problem) — and the
    band must catch config wiring bugs: mixture_weight dropped to 0
    lands ≈0.30, λ=10 lands ≈0.15, both far outside."""
    from keystone_tpu.pipelines.timit import TimitPipeline

    paths, oracle, K = _skewed_gaussian_problem(tmp_path)
    lo = oracle["balanced_macro_f1"] - 0.06
    hi = oracle["balanced_macro_f1"] + 0.04

    out = TimitPipeline.run(_timit_cfg(paths, K))
    assert lo <= out["macro_f1"] <= hi, (out["macro_f1"], lo, hi)
    # accuracy sanity: between the balanced rule's and the skew ceiling
    assert oracle["balanced_acc"] - 0.05 <= out["accuracy"] <= 0.90

    # the band is SENSITIVE: each perturbed config falls out of band
    broken_mw = TimitPipeline.run(_timit_cfg(paths, K, mixture_weight=0.0))
    assert broken_mw["macro_f1"] < lo, broken_mw["macro_f1"]
    broken_lam = TimitPipeline.run(_timit_cfg(paths, K, lam=10.0))
    assert broken_lam["macro_f1"] < lo, broken_lam["macro_f1"]


def _write_newsgroups_fixture(root, num_classes=3, docs_per_class=120, seed=0):
    """Directory-tree fixture with OVERLAPPING topic vocabularies: each
    doc draws 70% of its topic terms from its own class and 30% from the
    others, plus shared filler — non-separable on purpose."""
    import os

    rng = np.random.default_rng(seed)
    shared = [f"word{i}" for i in range(60)]
    topics = [[f"topic{c}term{i}" for i in range(25)] for c in range(num_classes)]
    for c in range(num_classes):
        gdir = os.path.join(root, f"group{c}")
        os.makedirs(gdir, exist_ok=True)
        for j in range(docs_per_class):
            words = []
            for _ in range(int(rng.integers(12, 28))):
                if rng.random() < 0.7:
                    words.append(str(rng.choice(topics[c])))
                else:
                    other = int(rng.choice([o for o in range(num_classes) if o != c]))
                    words.append(str(rng.choice(topics[other])))
            words += [str(w) for w in rng.choice(shared, size=int(rng.integers(10, 25)))]
            rng.shuffle(words)
            with open(os.path.join(gdir, f"doc{j:04d}.txt"), "w") as f:
                f.write(" ".join(words))
    return root


def test_newsgroups_app_sparse_route_matches_sklearn(tmp_path):
    """NewsgroupsPipeline (ls head, real CSR route: num_features ≥ 16384
    engages sparse_output + the sparse-gradient solver) must match
    sklearn Ridge solving the IDENTICAL objective on the IDENTICAL
    features — same featurizer chain, same λ convention (alpha = λ·n),
    no intercept — within solver-convergence slack."""
    import scipy.sparse as sp_
    from sklearn.linear_model import Ridge

    from keystone_tpu.loaders.newsgroups import NewsgroupsDataLoader
    from keystone_tpu.ops.nlp import (
        CommonSparseFeatures,
        LowerCase,
        NGramsFeaturizer,
        TermFrequency,
        Tokenizer,
        Trimmer,
        log_tf,
    )
    from keystone_tpu.pipelines.newsgroups import Config, NewsgroupsPipeline

    root = _write_newsgroups_fixture(str(tmp_path / "ng"))
    lam = 1e-2
    out = NewsgroupsPipeline.run(
        Config(data_path=root, head="ls", ls_lam=lam, num_features=16384)
    )
    acc_app = out["accuracy"]

    # identical features, outside the app: same loader, same split,
    # same chain, same vocab-fit-on-train
    data = NewsgroupsDataLoader.load(root)
    train, test = data.split(0.8, seed=0)

    def featurize_docs(docs, csf_model):
        rows = []
        for doc in docs:
            d = doc
            for t in (Trimmer(), LowerCase(), Tokenizer(),
                      NGramsFeaturizer((1, 2)), TermFrequency(log_tf)):
                d = t.apply_one(d)
            rows.append(csf_model.apply_one(d))
        return sp_.vstack(rows).tocsr()

    term_dicts = []
    for doc in train.data.items:
        d = doc
        for t in (Trimmer(), LowerCase(), Tokenizer(),
                  NGramsFeaturizer((1, 2)), TermFrequency(log_tf)):
            d = t.apply_one(d)
        term_dicts.append(d)
    csf = CommonSparseFeatures(16384, sparse_output=True).fit_arrays(term_dicts)
    xtr = featurize_docs(train.data.items, csf)
    xte = featurize_docs(test.data.items, csf)
    ytr = train.labels.numpy()
    yte = test.labels.numpy()
    k = int(ytr.max()) + 1
    y_pm1 = -np.ones((len(ytr), k), np.float32)
    y_pm1[np.arange(len(ytr)), ytr] = 1.0
    # our objective 1/(2n)‖XW−Y‖² + λ/2‖W‖² == sklearn Ridge with
    # alpha = λ·n (and no intercept, like the sparse route)
    skl = Ridge(alpha=lam * xtr.shape[0], fit_intercept=False)
    skl.fit(xtr, y_pm1)
    acc_skl = float((np.argmax(xte @ skl.coef_.T, axis=1) == yte).mean())

    assert abs(acc_app - acc_skl) <= 0.03, (acc_app, acc_skl)
    # non-separable fixture: neither should be perfect, both well above chance
    assert 0.5 < acc_skl < 0.999, acc_skl


def _write_voc_fixture(root, n=60, size=(48, 48), seed=0, noise=0.15):
    """VOC-format disk fixture (JPEGs + XML): per-class oriented-grating
    blobs with ``noise`` label dropout — mAP has an IRREDUCIBLE ceiling
    (~0.89 measured: perfect presence knowledge vs the noisy labels)."""
    import os

    from PIL import Image as PILImage

    from keystone_tpu.loaders.voc import NUM_CLASSES, VOC_CLASSES

    rng = np.random.default_rng(seed)
    img_dir, ann_dir = os.path.join(root, "img"), os.path.join(root, "ann")
    os.makedirs(img_dir, exist_ok=True)
    os.makedirs(ann_dir, exist_ok=True)
    h, w = size
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    angles = [0.0, np.pi / 3, 2 * np.pi / 3]
    true = np.zeros((n, NUM_CLASSES), np.float32)
    noisy = np.zeros((n, NUM_CLASSES), np.float32)
    for i in range(n):
        present = rng.random(3) < 0.45
        if not present.any():
            present[rng.integers(0, 3)] = True
        img = np.full((h, w, 3), 110.0) + rng.normal(0, 6, (h, w, 3))
        for c in np.nonzero(present)[0]:
            x0 = rng.integers(0, w // 2)
            y0 = rng.integers(0, h // 2)
            a = angles[c]
            grat = 110 + 90 * np.sin(
                0.9 * (np.cos(a) * xx + np.sin(a) * yy)
                + rng.uniform(0, 2 * np.pi)
            )
            img[y0 : y0 + h // 2, x0 : x0 + w // 2] = grat[
                y0 : y0 + h // 2, x0 : x0 + w // 2, None
            ]
            true[i, c] = 1.0
            if rng.random() > noise:
                noisy[i, c] = 1.0
        if not noisy[i].any():
            noisy[i, int(np.nonzero(present)[0][0])] = 1.0
        pil = PILImage.fromarray(np.clip(img, 0, 255).astype(np.uint8))
        pil.save(os.path.join(img_dir, f"im{i:04d}.jpg"), quality=95)
        objs = "".join(
            f"<object><name>{VOC_CLASSES[c]}</name></object>"
            for c in np.nonzero(noisy[i])[0]
        )
        with open(os.path.join(ann_dir, f"im{i:04d}.xml"), "w") as f:
            f.write(f"<annotation>{objs}</annotation>")
    return img_dir, ann_dir


def test_voc_app_map_band_on_noisy_fixture(tmp_path):
    """VOCSIFTFisher through run() on a NON-separable disk fixture: the
    label-dropout noise caps mAP at ~0.89 (measured ceiling: perfect
    presence knowledge scored against the noisy labels), so a band
    [0.80, 0.93] catches both broken featurization/solver wiring (below)
    and evaluation leaks toward 1.0 (above)."""
    from keystone_tpu.pipelines.voc_sift_fisher import Config, VOCSIFTFisher

    img_dir, ann_dir = _write_voc_fixture(str(tmp_path / "voc"))
    out = VOCSIFTFisher.run(
        Config(
            images_dir=img_dir,
            annotations_dir=ann_dir,
            image_size=48,
            gmm_k=8,
            pca_dims=16,
            descriptor_samples_per_image=16,
            solver_block_size=256,
            num_epochs=2,
            lam=1e-4,
        )
    )
    assert 0.80 <= out["mean_ap"] <= 0.93, out["mean_ap"]
