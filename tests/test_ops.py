"""Feature-op tests — hand-computable small inputs and golden values,
mirroring ConvolverSuite / PoolerSuite / PaddedFFTSuite etc. (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops import (
    CenterCornerPatcher,
    ClassLabelIndicators,
    CommonSparseFeatures,
    Convolver,
    CosineRandomFeatures,
    DaisyExtractor,
    FisherVector,
    GMMFisherVectorEstimator,
    GrayScaler,
    HashingTF,
    LCSExtractor,
    LinearRectifier,
    LowerCase,
    MaxClassifier,
    NGramsFeaturizer,
    NormalizeRows,
    PaddedFFT,
    Pooler,
    RandomPatcher,
    RandomSignNode,
    SIFTExtractor,
    SignedHellingerMapper,
    StandardScaler,
    StupidBackoffLM,
    TermFrequency,
    Tokenizer,
    TopKClassifier,
    VectorCombiner,
    VectorSplitter,
    Windower,
)
from keystone_tpu.ops.sift import sift_output_count
from keystone_tpu.workflow import Dataset


def test_cosine_random_features():
    t = CosineRandomFeatures.init(8, 16, gamma=0.5, seed=1)
    x = np.random.default_rng(0).normal(size=(5, 8)).astype(np.float32)
    out = np.asarray(t.apply_batch(jnp.asarray(x)))
    assert out.shape == (5, 16)
    expect = np.cos(x @ np.asarray(t.w).T + np.asarray(t.b))
    np.testing.assert_allclose(out, expect, atol=1e-5)
    assert (out >= -1).all() and (out <= 1).all()


def test_random_sign_and_padded_fft():
    rs = RandomSignNode.init(10, seed=3)
    signs = np.asarray(rs.signs)
    assert set(np.unique(signs)) <= {-1.0, 1.0}
    x = np.random.default_rng(1).normal(size=(4, 10)).astype(np.float32)
    flipped = np.asarray(rs.apply_batch(jnp.asarray(x)))
    np.testing.assert_allclose(flipped, x * signs, atol=1e-6)

    fft = PaddedFFT()
    out = np.asarray(fft.apply_batch(jnp.asarray(x)))
    spec = np.fft.rfft(np.pad(x, ((0, 0), (0, 6))), axis=-1, norm="ortho")  # pad 10->16
    expect = spec.real[:, :8]  # upstream's width: the first half's real parts
    np.testing.assert_allclose(out, expect, atol=1e-3)


def test_linear_rectifier_and_hellinger_and_normalize():
    x = jnp.asarray([[-2.0, 0.5, 4.0]])
    assert np.allclose(
        np.asarray(LinearRectifier(0.0, 1.0).apply_batch(x)), [[0.0, 0.0, 3.0]]
    )
    sh = np.asarray(SignedHellingerMapper().apply_batch(x))
    np.testing.assert_allclose(sh, [[-np.sqrt(2), np.sqrt(0.5), 2.0]], atol=1e-6)
    nr = np.asarray(NormalizeRows().apply_batch(x))
    np.testing.assert_allclose(np.linalg.norm(nr, axis=1), [1.0], atol=1e-6)


def test_standard_scaler_matches_numpy():
    rng = np.random.default_rng(2)
    x = rng.normal(3.0, 2.0, size=(37, 5)).astype(np.float32)  # 37: padding case
    model = StandardScaler().fit_dataset(Dataset(x))
    np.testing.assert_allclose(np.asarray(model.mean), x.mean(0), atol=1e-4)
    np.testing.assert_allclose(np.asarray(model.std), x.std(0, ddof=1), atol=1e-4)
    out = np.asarray(model.apply_batch(jnp.asarray(x)))
    np.testing.assert_allclose(out.mean(0), 0.0, atol=1e-4)
    np.testing.assert_allclose(out.std(0, ddof=1), 1.0, atol=1e-3)


def test_convolver_matches_manual():
    rng = np.random.default_rng(3)
    img = rng.normal(size=(1, 5, 5, 2)).astype(np.float32)
    filt = rng.normal(size=(3, 2, 2, 2)).astype(np.float32)  # 3 filters 2x2x2
    out = np.asarray(Convolver(filt).apply_batch(jnp.asarray(img)))
    assert out.shape == (1, 4, 4, 3)
    # manual correlation at (1,2)
    patch = img[0, 1:3, 2:4, :]
    expect = np.array([(patch * filt[f]).sum() for f in range(3)])
    np.testing.assert_allclose(out[0, 1, 2], expect, atol=1e-4)


def test_pooler_sum():
    img = jnp.asarray(np.arange(16, dtype=np.float32).reshape(1, 4, 4, 1))
    out = np.asarray(Pooler(2, 2).apply_batch(img))
    assert out.shape == (1, 2, 2, 1)
    np.testing.assert_allclose(out[0, :, :, 0], [[10.0, 18.0], [42.0, 50.0]])


def test_symmetric_rectifier_doubles_channels():
    from keystone_tpu.ops import SymmetricRectifier

    img = jnp.asarray(np.array([[[[1.0], [-2.0]]]], np.float32))
    out = np.asarray(SymmetricRectifier(alpha=0.5).apply_batch(img))
    assert out.shape == (1, 1, 2, 2)
    np.testing.assert_allclose(out[0, 0], [[0.5, 0.0], [0.0, 1.5]])


def test_windower_matches_manual_slices():
    rng = np.random.default_rng(4)
    img = rng.normal(size=(1, 4, 4, 1)).astype(np.float32)
    out = np.asarray(Windower(2, 2).apply_batch(jnp.asarray(img)))
    assert out.shape == (1, 4, 4)  # 2x2 windows of 2*2*1
    np.testing.assert_allclose(out[0, 0], img[0, 0:2, 0:2, 0].reshape(-1), atol=1e-6)
    np.testing.assert_allclose(out[0, 3], img[0, 2:4, 2:4, 0].reshape(-1), atol=1e-6)


def test_random_patcher_and_center_corner():
    rng = np.random.default_rng(5)
    imgs = rng.normal(size=(2, 8, 8, 3)).astype(np.float32)
    out = np.asarray(RandomPatcher(4, 3, 3, seed=0).apply_batch(jnp.asarray(imgs)))
    assert out.shape == (8, 27)

    views = np.asarray(
        CenterCornerPatcher(4, 4, horizontal_flips=True).apply_batch(jnp.asarray(imgs))
    )
    assert views.shape == (2, 10, 4, 4, 3)
    np.testing.assert_allclose(views[0, 0], imgs[0, :4, :4, :])  # top-left
    np.testing.assert_allclose(views[0, 5], imgs[0, :4, :4, :][:, ::-1, :])


def test_grayscaler():
    imgs = np.random.default_rng(6).normal(size=(2, 3, 3, 3)).astype(np.float32)
    out = np.asarray(GrayScaler().apply_batch(jnp.asarray(imgs)))
    np.testing.assert_allclose(out, imgs.mean(-1), atol=1e-6)


def test_classifier_heads():
    scores = jnp.asarray([[0.1, 0.9, 0.3], [0.8, 0.2, 0.5]])
    assert np.asarray(MaxClassifier().apply_batch(scores)).tolist() == [1, 0]
    topk = np.asarray(TopKClassifier(2).apply_batch(scores))
    assert topk.tolist() == [[1, 2], [0, 2]]
    ind = np.asarray(ClassLabelIndicators(3).apply_batch(jnp.asarray([0, 2])))
    np.testing.assert_allclose(ind, [[1, -1, -1], [-1, -1, 1]])


def test_vector_splitter_combiner_roundtrip():
    x = jnp.asarray(np.arange(20, dtype=np.float32).reshape(2, 10))
    blocks = VectorSplitter(4).apply_batch(x)
    assert blocks.shape == (2, 3, 4)  # padded to 12
    back = VectorCombiner().apply_batch(blocks)
    np.testing.assert_allclose(np.asarray(back)[:, :10], np.asarray(x))


def test_sift_shapes_and_properties():
    rng = np.random.default_rng(7)
    imgs = rng.normal(size=(2, 32, 32)).astype(np.float32)
    # smoothing off: these pin the unsmoothed descriptor core
    ext = SIFTExtractor(step=4, bin_sizes=(4,), smoothing_magnif=0)
    desc, mask = ext.apply_batch(jnp.asarray(imgs))
    k = sift_output_count(32, 32, 4, (4,))
    assert desc.shape == (2, k, 128)
    assert mask.shape == (2, k)
    d = np.asarray(desc)
    norms = np.linalg.norm(d, axis=-1)
    assert (norms <= 1.01).all()
    assert norms.max() > 0.5  # normalized descriptors on noisy input

    # uniform image → zero gradients → zero descriptors
    flat = np.ones((1, 32, 32), np.float32)
    d0, _ = ext.apply_batch(jnp.asarray(flat))
    assert np.abs(np.asarray(d0)).max() < 1e-6

    # vertical edge: energy concentrates in horizontal-gradient bins
    edge = np.zeros((1, 32, 32), np.float32)
    edge[:, :, 16:] = 1.0
    de, _ = ext.apply_batch(jnp.asarray(edge))
    assert np.abs(np.asarray(de)).max() > 0.1


def test_lcs_constant_image():
    img = np.full((1, 40, 40, 3), 0.7, np.float32)
    desc, mask = LCSExtractor(step=6, subpatch_size=4).apply_batch(jnp.asarray(img))
    d = np.asarray(desc)
    assert d.shape[-1] == 2 * 3 * 16
    means = d.reshape(d.shape[0], d.shape[1], 16, 6)[..., :3]
    stds = d.reshape(d.shape[0], d.shape[1], 16, 6)[..., 3:]
    np.testing.assert_allclose(means, 0.7, atol=1e-5)
    # f32 cancellation in E[x²]−mean² bounds the achievable zero to ~√eps
    np.testing.assert_allclose(stds, 0.0, atol=1e-3)


def test_daisy_shapes():
    rng = np.random.default_rng(8)
    imgs = rng.normal(size=(1, 64, 64)).astype(np.float32)
    ext = DaisyExtractor(step=8, radius=8, rings=2, ring_points=4, orientations=4)
    desc, mask = ext.apply_batch(jnp.asarray(imgs))
    assert desc.shape[-1] == (1 + 2 * 4) * 4
    assert desc.shape[0] == 1 and desc.shape[1] > 0
    # histograms are L2-normalized per block
    d = np.asarray(desc).reshape(1, desc.shape[1], -1, 4)
    norms = np.linalg.norm(d, axis=-1)
    assert (norms <= 1.01).all()


def test_fisher_vector_matches_numpy_reference():
    rng = np.random.default_rng(9)
    k, d, t = 3, 4, 20
    w = np.array([0.5, 0.3, 0.2], np.float32)
    mu = rng.normal(size=(k, d)).astype(np.float32)
    var = (0.5 + rng.random((k, d))).astype(np.float32)
    from keystone_tpu.models.gmm import GaussianMixtureModel

    gmm = GaussianMixtureModel(jnp.asarray(w), jnp.asarray(mu), jnp.asarray(var))
    x = rng.normal(size=(1, t, d)).astype(np.float32)
    fv = np.asarray(FisherVector(gmm).apply_batch(jnp.asarray(x))[0])

    # float64 reference
    sigma = np.sqrt(var)
    logp = np.zeros((t, k))
    for j in range(k):
        logp[:, j] = (
            np.log(w[j])
            - 0.5 * np.sum(np.log(2 * np.pi * var[j]))
            - 0.5 * np.sum(((x[0] - mu[j]) / sigma[j]) ** 2, axis=1)
        )
    gamma = np.exp(logp - logp.max(1, keepdims=True))
    gamma /= gamma.sum(1, keepdims=True)
    phi1 = np.zeros((k, d))
    phi2 = np.zeros((k, d))
    for j in range(k):
        z = (x[0] - mu[j]) / sigma[j]
        phi1[j] = (gamma[:, j : j + 1] * z).sum(0) / (t * np.sqrt(w[j]))
        phi2[j] = (gamma[:, j : j + 1] * (z * z - 1)).sum(0) / (t * np.sqrt(2 * w[j]))
    expect = np.concatenate([phi1.ravel(), phi2.ravel()])
    np.testing.assert_allclose(fv, expect, atol=2e-4)


def test_fisher_vector_respects_mask():
    rng = np.random.default_rng(10)
    from keystone_tpu.models.gmm import GaussianMixtureModel

    k, d = 2, 3
    gmm = GaussianMixtureModel(
        jnp.asarray([0.6, 0.4]),
        jnp.asarray(rng.normal(size=(k, d)), dtype=jnp.float32),
        jnp.ones((k, d), jnp.float32),
    )
    x = rng.normal(size=(1, 10, d)).astype(np.float32)
    mask = np.zeros((1, 10), np.float32)
    mask[:, :6] = 1.0
    fv_masked = np.asarray(
        FisherVector(gmm).apply_batch(jnp.asarray(x), mask=jnp.asarray(mask))
    )
    fv_trunc = np.asarray(FisherVector(gmm).apply_batch(jnp.asarray(x[:, :6])))
    np.testing.assert_allclose(fv_masked, fv_trunc, atol=1e-5)


def test_gmm_fisher_vector_estimator_pipeline():
    rng = np.random.default_rng(11)
    descs = rng.normal(size=(200, 4)).astype(np.float32)
    fv_t = GMMFisherVectorEstimator(k=2, max_iterations=5).fit_arrays(descs)
    out = fv_t.apply_batch(jnp.asarray(rng.normal(size=(3, 17, 4)).astype(np.float32)))
    assert np.asarray(out).shape == (3, 2 * 2 * 4)


def test_nlp_chain():
    docs = ["The cat sat, the cat ran!", "A dog sat."]
    tok = Tokenizer()
    low = LowerCase()
    toks = [tok.apply_one(low.apply_one(d)) for d in docs]
    assert toks[0] == ["the", "cat", "sat", "the", "cat", "ran"]
    ng = NGramsFeaturizer((1, 2))
    grams = ng.apply_one(toks[0])
    assert ("the", "cat") in grams and ("cat",) in grams
    tf = TermFrequency()
    counts = tf.apply_one(grams)
    assert counts[("cat",)] == 2 and counts[("the", "cat")] == 2

    import math

    tf_log = TermFrequency(lambda v: math.log(v + 1))
    assert abs(tf_log.apply_one(grams)[("cat",)] - math.log(3)) < 1e-9


def test_common_sparse_features():
    docs = [
        {("a",): 2.0, ("b",): 1.0},
        {("a",): 1.0, ("c",): 1.0},
        {("a",): 3.0, ("b",): 2.0},
    ]
    model = CommonSparseFeatures(2).fit_arrays(docs)
    assert ("a",) in model.vocab  # highest doc frequency
    rows = model.apply_dataset(Dataset(docs)).numpy()
    assert rows.shape == (3, 2)
    a_col = model.vocab[("a",)]
    np.testing.assert_allclose(rows[:, a_col], [2.0, 1.0, 3.0])


def test_hashing_tf_deterministic():
    h = HashingTF(32)
    r1 = h.apply_one({("x", "y"): 2.0, ("z",): 1.0})
    r2 = h.apply_one({("x", "y"): 2.0, ("z",): 1.0})
    np.testing.assert_allclose(r1, r2)
    assert r1.sum() == 3.0


def test_stupid_backoff():
    counts = {
        ("the",): 10,
        ("cat",): 5,
        ("sat",): 3,
        ("the", "cat"): 4,
        ("cat", "sat"): 2,
    }
    lm = StupidBackoffLM(counts)
    # seen bigram: count(bigram)/context-count("the"->4)
    assert abs(lm.score(("the", "cat")) - 4 / 4) < 1e-9
    # unseen bigram backs off: 0.4 * P(dog) = 0.4 * 0
    assert lm.score(("the", "dog")) == 0.0
    # unseen context backs off to unigram
    assert abs(lm.score(("sat", "cat")) - 0.4 * (5 / 18)) < 1e-9


def test_ragged_flow_sift_to_fv():
    """SIFT → (ragged) → FV through the Dataset/Transformer mask plumbing."""
    rng = np.random.default_rng(12)
    imgs = rng.normal(size=(2, 24, 24)).astype(np.float32)
    ds = Dataset(imgs)
    sift_ds = SIFTExtractor(step=6, bin_sizes=(3,)).apply_dataset(ds)
    assert sift_ds.mask is not None
    fv_est = GMMFisherVectorEstimator(k=2, max_iterations=3)
    from keystone_tpu.ops import ColumnSampler

    sampled = ColumnSampler(8, seed=0).apply_dataset(sift_ds)
    fv_t = fv_est.fit_dataset(sampled)
    fv_ds = fv_t.apply_dataset(sift_ds)
    assert fv_ds.numpy().shape == (2, 2 * 2 * 128)


def test_sift_matches_independent_numpy_reference():
    # independent slow implementation of the same dense-SIFT spec
    # (loops + np.convolve vs the jitted conv program) — the golden-value
    # pattern the reference uses for its image ops (SURVEY §4)
    from keystone_tpu.ops.sift import (
        SIFTExtractor,
        _keypoint_grid,
        _triangular_kernel,
    )

    rng = np.random.default_rng(0)
    h = w = 32
    step, bin_size, o, grid = 4, 4, 8, 4
    img = rng.uniform(0, 1, (h, w)).astype(np.float32)

    # gradients (central differences, zero at borders)
    dy = np.zeros((h, w), np.float32)
    dx = np.zeros((h, w), np.float32)
    dy[1:-1, :] = (img[2:, :] - img[:-2, :]) * 0.5
    dx[:, 1:-1] = (img[:, 2:] - img[:, :-2]) * 0.5
    mag = np.sqrt(dx * dx + dy * dy)
    ang = np.arctan2(dy, dx) % (2 * np.pi)

    # soft orientation binning
    theta = ang * (o / (2 * np.pi))
    lo = np.floor(theta).astype(int) % o
    hi = (lo + 1) % o
    frac = theta - np.floor(theta)
    omap = np.zeros((h, w, o), np.float32)
    for yy in range(h):
        for xx in range(w):
            omap[yy, xx, lo[yy, xx]] += mag[yy, xx] * (1 - frac[yy, xx])
            omap[yy, xx, hi[yy, xx]] += mag[yy, xx] * frac[yy, xx]

    # separable triangular window, SAME padding
    k1 = _triangular_kernel(bin_size)
    sm = np.zeros_like(omap)
    for c in range(o):
        tmp = np.zeros((h, w), np.float32)
        for xx in range(w):
            tmp[:, xx] = np.convolve(omap[:, xx, c], k1, mode="same")
        for yy in range(h):
            sm[yy, :, c] = np.convolve(tmp[yy, :], k1, mode="same")

    ys = _keypoint_grid(h, step, bin_size)
    xs_ = _keypoint_grid(w, step, bin_size)
    offs = ((np.arange(grid) - (grid - 1) / 2.0) * bin_size).astype(int)
    descs = []
    for cy in ys:
        for cx in xs_:
            # canonical (y_bin, x_bin, orientation) feature order — the
            # r5 descriptor-layout contract (ops/sift._DESCRIPTOR_ORDER)
            d = np.stack(
                [sm[cy + oy, cx + ox] for oy in offs for ox in offs]
            ).reshape(-1)
            n1 = max(np.linalg.norm(d), 1e-8)
            d = np.minimum(d / n1, 0.2)
            d = d / max(np.linalg.norm(d), 1e-8)
            descs.append(d)
    ref = np.stack(descs)

    out, mask = SIFTExtractor(
        step=step, bin_sizes=(bin_size,), smoothing_magnif=0
    ).apply_batch(img[None])
    np.testing.assert_allclose(np.asarray(out[0]), ref, atol=2e-5, rtol=2e-4)


def test_pixel_scaler_only_if_integer():
    import jax.numpy as jnp

    from keystone_tpu.ops import PixelScaler

    u8 = np.full((2, 4, 4, 3), 128, np.uint8)
    f01 = np.full((2, 4, 4, 3), 0.5, np.float32)
    guard = PixelScaler(only_if_integer=True)
    np.testing.assert_allclose(np.asarray(guard.apply_batch(u8)), 128 / 255.0)
    # pre-normalized floats pass through unscaled (no silent /255 collapse)
    np.testing.assert_allclose(np.asarray(guard.apply_batch(f01)), 0.5)
    # the default stays unconditional: float [0,255] CSV pixels divide
    np.testing.assert_allclose(
        np.asarray(PixelScaler().apply_batch(f01 * 255.0)), 0.5
    )
    assert guard.params() != PixelScaler().params()  # distinct CSE identity


def test_sift_scale_too_large_for_image_yields_zero_keypoints():
    """A bin size whose support exceeds the image contributes an empty
    descriptor set (VLFeat drops such scales), not a crash."""
    from keystone_tpu.ops import SIFTExtractor
    from keystone_tpu.ops.sift import sift_output_count

    imgs = np.random.default_rng(0).uniform(0, 1, (2, 32, 32)).astype(np.float32)
    d, m = SIFTExtractor(step=4, bin_sizes=(8,)).apply_batch(jnp.asarray(imgs))
    assert d.shape == (2, 0, 128) and m.shape == (2, 0)
    # multi-scale: the feasible scale still contributes
    d2, _ = SIFTExtractor(step=4, bin_sizes=(4, 8)).apply_batch(jnp.asarray(imgs))
    assert d2.shape[1] == sift_output_count(32, 32, 4, (4, 8))
    assert d2.shape[1] == sift_output_count(32, 32, 4, (4,))


def test_sift_per_scale_gaussian_smoothing():
    """VLFeat applies per-scale Gaussian smoothing before gradients
    (σ = √((bin/magnif)² − 0.25), magnif=6 default).  Pin: the σ
    schedule, that smoothing is ON by default and changes descriptors,
    and that it equals blur-then-unsmoothed-extract (self-consistency)."""
    from scipy.ndimage import gaussian_filter

    from keystone_tpu.ops import SIFTExtractor

    ext = SIFTExtractor(step=5, bin_sizes=(4, 8))
    assert ext._sigma(4) == pytest.approx(np.sqrt((4 / 6) ** 2 - 0.25), abs=1e-6)
    assert ext._sigma(8) == pytest.approx(np.sqrt((8 / 6) ** 2 - 0.25), abs=1e-6)
    assert SIFTExtractor(step=5, smoothing_magnif=0)._sigma(4) == 0.0

    rng = np.random.default_rng(5)
    imgs = rng.uniform(0, 1, (2, 40, 40)).astype(np.float32)
    smoothed, _ = SIFTExtractor(step=5, bin_sizes=(8,)).apply_batch(
        jnp.asarray(imgs)
    )
    plain, _ = SIFTExtractor(
        step=5, bin_sizes=(8,), smoothing_magnif=0
    ).apply_batch(jnp.asarray(imgs))
    assert np.abs(np.asarray(smoothed) - np.asarray(plain)).max() > 1e-3

    # self-consistency: smoothing inside == scipy blur outside + no smoothing
    sigma = ext._sigma(8)
    blurred = np.stack(
        [
            gaussian_filter(im, sigma, mode="constant", truncate=3.0)
            for im in imgs
        ]
    ).astype(np.float32)  # mode="constant" = the conv's SAME zero padding
    via_scipy, _ = SIFTExtractor(
        step=5, bin_sizes=(8,), smoothing_magnif=0
    ).apply_batch(jnp.asarray(blurred))
    np.testing.assert_allclose(
        np.asarray(smoothed), np.asarray(via_scipy), atol=2e-3
    )


def test_blur_matmul_matches_conv_and_scipy():
    """The banded-matrix blur (r4 default) must equal the depthwise-conv
    form and scipy's mode='constant' Gaussian — same truncation, same
    zero-padding edge semantics, both axes."""
    from scipy.ndimage import gaussian_filter

    from keystone_tpu.ops.filters import separable_gaussian_blur

    rng = np.random.default_rng(7)
    x = rng.uniform(0, 1, (2, 33, 47, 3)).astype(np.float32)
    for sigma in (0.45, 1.3):
        mm = np.asarray(separable_gaussian_blur(jnp.asarray(x), sigma))
        cv = np.asarray(
            separable_gaussian_blur(jnp.asarray(x), sigma, strategy="conv")
        )
        np.testing.assert_allclose(mm, cv, atol=2e-5)
        sp = np.stack(
            [
                np.stack(
                    [
                        gaussian_filter(
                            x[i, :, :, c], sigma, mode="constant", truncate=3.0
                        )
                        for c in range(3)
                    ],
                    axis=-1,
                )
                for i in range(2)
            ]
        )
        np.testing.assert_allclose(mm, sp, atol=2e-3)


def test_sift_multiscale_concatenates_per_scale_descriptors():
    """Multiple bin sizes (the reference's multi-scale dense SIFT): output
    is the per-scale descriptor sets concatenated along the keypoint axis."""
    from keystone_tpu.ops import SIFTExtractor
    from keystone_tpu.ops.sift import sift_output_count

    rng = np.random.default_rng(3)
    imgs = rng.normal(size=(2, 40, 40)).astype(np.float32)
    multi, m_mask = SIFTExtractor(step=5, bin_sizes=(3, 5)).apply_batch(
        jnp.asarray(imgs)
    )
    k = sift_output_count(40, 40, 5, (3, 5))
    assert multi.shape == (2, k, 128) and m_mask.shape == (2, k)
    s3, _ = SIFTExtractor(step=5, bin_sizes=(3,)).apply_batch(jnp.asarray(imgs))
    s5, _ = SIFTExtractor(step=5, bin_sizes=(5,)).apply_batch(jnp.asarray(imgs))
    np.testing.assert_allclose(
        np.asarray(multi),
        np.concatenate([np.asarray(s3), np.asarray(s5)], axis=1),
        atol=1e-6,
    )


def test_hashing_tf_stable_across_process_hash_seeds():
    """Python's hash(str) is salted per process; HashingTF must not be,
    or saved models score garbage in any other process (--model-path)."""
    import os
    import subprocess
    import sys

    from keystone_tpu.ops import HashingTF

    tf = HashingTF(64)
    here = np.asarray(tf.apply_one({"alpha": 1.0, ("bi", "gram"): 2.0}))
    code = (
        "import numpy as np\n"
        "from keystone_tpu.ops import HashingTF\n"
        "row = HashingTF(64).apply_one({'alpha': 1.0, ('bi', 'gram'): 2.0})\n"
        "print(','.join(str(int(i)) for i in np.nonzero(np.asarray(row))[0]))\n"
    )
    env = dict(
        os.environ,
        PYTHONHASHSEED="12345",  # force a DIFFERENT salt
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        + os.pathsep
        + os.environ.get("PYTHONPATH", ""),
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr[-1000:]
    other = [int(i) for i in out.stdout.strip().split(",")]
    assert sorted(np.nonzero(here)[0].tolist()) == sorted(other)


def test_sift_matmul_windowing_matches_conv():
    """The MXU-matmul windowing path (r3 default) must reproduce the
    depthwise-conv path to fp tolerance across shapes/scales/smoothing."""
    from keystone_tpu.ops.sift import _dsift

    rng = np.random.default_rng(0)
    for hw, step, b, sigma in [(64, 4, 4, 0.0), (48, 6, 4, 0.55), (33, 5, 3, 0.0)]:
        imgs = jnp.asarray(rng.uniform(size=(2, hw, hw)).astype(np.float32))
        conv = np.asarray(_dsift(imgs, step, b, sigma=sigma, windowing="conv"))
        mm = np.asarray(_dsift(imgs, step, b, sigma=sigma, windowing="matmul"))
        assert conv.shape == mm.shape
        np.testing.assert_allclose(mm, conv, atol=1e-6)


def test_sift_old_pickle_defaults_to_conv_windowing():
    from keystone_tpu.ops.sift import SIFTExtractor

    s = SIFTExtractor.__new__(SIFTExtractor)
    assert s.windowing == "conv"  # pre-windowing pickles keep their path
    assert SIFTExtractor().windowing == "matmul"
