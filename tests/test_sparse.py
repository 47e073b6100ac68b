"""Sparse-gradient text path (round-1 review item 7).

The reference's LBFGS.scala § LeastSquaresSparseGradient computes
least-squares gradients from CSR without densifying n×d; the TPU
analogue is padded-COO gather/scatter (ops/sparse.py).  These tests pin:
solver parity with the dense solver on the same data, the huge-vocab
memory win, and the end-to-end Sparsify → SparseLBFGS → sparse-scoring
pipeline flow.
"""

import numpy as np

import jax.numpy as jnp

from keystone_tpu.workflow import Dataset, Pipeline


def _sparse_problem(rng, n, d, k, nnz):
    """Random sparse rows + targets from a sparse ground-truth model."""
    idx = np.stack([rng.choice(d, size=nnz, replace=False) for _ in range(n)])
    val = rng.normal(size=(n, nnz)).astype(np.float32)
    w_true = rng.normal(size=(d, k)).astype(np.float32) * 0.3
    dense = np.zeros((n, d), np.float32)
    for i in range(n):
        dense[i, idx[i]] = val[i]
    y = (dense @ w_true + 0.05 * rng.normal(size=(n, k))).astype(np.float32)
    return idx.astype(np.int32), val, dense, y


def test_padded_sparse_rows_roundtrip_and_matmul():
    from keystone_tpu.ops.sparse import PaddedSparseRows

    rng = np.random.default_rng(0)
    idx, val, dense, _ = _sparse_problem(rng, 32, 200, 3, 7)
    sp = PaddedSparseRows(idx, val, 200)
    np.testing.assert_allclose(sp.toarray(), dense, atol=1e-6)
    w = rng.normal(size=(200, 5)).astype(np.float32)
    got = np.asarray(sp.matmul(jnp.asarray(w)))[: sp.n]
    np.testing.assert_allclose(got, dense @ w, rtol=1e-4, atol=1e-4)


def test_sparse_lbfgs_matches_dense_lbfgs():
    """Same data, same loss: the sparse-gradient solver must land on the
    dense solver's optimum (overlapping vocab = every feature here)."""
    from keystone_tpu.models import DenseLBFGSwithL2, SparseLBFGSwithL2
    from keystone_tpu.ops.sparse import PaddedSparseRows

    rng = np.random.default_rng(1)
    idx, val, dense, y = _sparse_problem(rng, 256, 400, 4, 12)
    lam = 1e-2

    dense_model = DenseLBFGSwithL2(lam=lam, num_iterations=80).fit_arrays(dense, y)
    sp = PaddedSparseRows(idx, val, 400)
    sparse_model = SparseLBFGSwithL2(lam=lam, num_iterations=80).fit_sparse(
        sp, jnp.asarray(y)
    )
    wd = np.asarray(dense_model.weights)
    ws = np.asarray(sparse_model.weights)
    scale = np.abs(wd).max() + 1e-9
    assert np.abs(ws - wd).max() / scale < 2e-2, np.abs(ws - wd).max() / scale


def test_sparse_fit_at_huge_vocab_without_densifying():
    """d = 200k: the dense matrix would be ~400 MB; the padded-COO form
    is ~3 orders smaller and the fit still runs and predicts."""
    from keystone_tpu.models import SparseLBFGSwithL2
    from keystone_tpu.ops.sparse import PaddedSparseRows

    rng = np.random.default_rng(2)
    n, d, k, nnz = 512, 200_000, 4, 24
    idx = np.stack([rng.choice(d, size=nnz, replace=False) for _ in range(n)])
    val = np.abs(rng.normal(size=(n, nnz))).astype(np.float32)
    lab = rng.integers(0, k, size=n)
    # class-dependent signal: shift indices into a class-specific band
    idx = (idx // k) * k + lab[:, None]
    y = -np.ones((n, k), np.float32)
    y[np.arange(n), lab] = 1.0

    sp = PaddedSparseRows(idx.astype(np.int32), val, d)
    dense_bytes = n * d * 4
    assert sp.nbytes * 100 < dense_bytes, (sp.nbytes, dense_bytes)

    model = SparseLBFGSwithL2(lam=1e-3, num_iterations=30).fit_sparse(
        sp, jnp.asarray(y)
    )
    w = np.asarray(model.weights)
    assert np.isfinite(w).all()
    pred = np.argmax(np.asarray(sp.matmul(model.weights)), axis=1)[:n]
    assert (pred == lab).mean() > 0.9


def test_node_choice_swaps_dense_solvers_to_sparse():
    """The optimizer's physical choice (NodeOptimizationRule analogue):
    on host CSR samples, exact LS and dense LBFGS route to the
    sparse-gradient solver; dense samples keep the original."""
    import scipy.sparse as sp

    from keystone_tpu.models import (
        DenseLBFGSwithL2,
        LinearMapEstimator,
        SparseLBFGSwithL2,
    )

    rows = [sp.csr_matrix(np.eye(1, 50, k=i, dtype=np.float32)) for i in range(4)]
    sparse_sample = Dataset(rows)
    dense_sample = Dataset(np.ones((4, 50), np.float32))

    chosen = LinearMapEstimator(lam=0.3).choose_physical(sparse_sample)
    assert isinstance(chosen, SparseLBFGSwithL2) and chosen.lam == 0.3
    assert LinearMapEstimator(lam=0.3).choose_physical(dense_sample).__class__ \
        is LinearMapEstimator

    d = DenseLBFGSwithL2(lam=0.1, fit_intercept=False)
    assert isinstance(d.choose_physical(sparse_sample), SparseLBFGSwithL2)
    assert d.choose_physical(dense_sample) is d
    # intercept now survives the swap (constant-column intercept)
    di = DenseLBFGSwithL2(lam=0.1, fit_intercept=True)
    chosen_i = di.choose_physical(sparse_sample)
    assert isinstance(chosen_i, SparseLBFGSwithL2) and chosen_i.fit_intercept
    # already-sparse stays put
    s = SparseLBFGSwithL2(lam=0.1)
    assert s.choose_physical(sparse_sample) is s


def test_linear_map_fit_dataset_routes_sparse_without_optimizer():
    """LinearMapEstimator.fit_dataset on a host CSR dataset must fit via
    the sparse solver even when no optimizer rule rewired it."""
    import scipy.sparse as sp

    from keystone_tpu.models import LinearMapEstimator

    rng = np.random.default_rng(5)
    n, d, k = 64, 80, 2
    dense = (rng.uniform(size=(n, d)) < 0.1) * rng.normal(size=(n, d))
    dense = dense.astype(np.float32)
    lab = (dense.sum(axis=1) > 0).astype(np.int32)
    y = -np.ones((n, k), np.float32)
    y[np.arange(n), lab] = 1.0
    rows = [sp.csr_matrix(dense[i : i + 1]) for i in range(n)]
    model = LinearMapEstimator(lam=1e-3).fit_dataset(Dataset(rows), Dataset(y))
    pred = np.argmax(np.asarray(model.apply_batch(jnp.asarray(dense))), axis=1)
    assert (pred == lab).mean() > 0.9


def test_common_sparse_features_sparse_output_pipeline():
    """CommonSparseFeatures(sparse_output=True) keeps CSR rows through
    the DAG; the default optimizer's node choice then fits the LS head
    with the sparse solver, end to end — a pipeline whose dense route
    would materialize n×d."""
    from keystone_tpu.models import LinearMapEstimator
    from keystone_tpu.ops import MaxClassifier
    from keystone_tpu.ops.nlp import CommonSparseFeatures

    rng = np.random.default_rng(4)
    vocab = [f"w{i}" for i in range(64)]
    n, k = 96, 3
    lab = rng.integers(0, k, size=n).astype(np.int32)
    docs = []
    for i in range(n):
        terms = {f"c{lab[i]}": 3.0}  # class-indicative token
        for w in rng.choice(vocab, size=5, replace=False):
            terms[w] = 1.0
        docs.append(terms)
    y = -np.ones((n, k), np.float32)
    y[np.arange(n), lab] = 1.0

    pipe = Pipeline.of(
        # identity host stage so the estimator sees the featurized docs
        CommonSparseFeatures(67, sparse_output=True)
        .fit_arrays(docs)
    ).and_then(
        LinearMapEstimator(lam=1e-3), Dataset(docs), Dataset(y)
    ).and_then(MaxClassifier())
    fitted = pipe.fit()
    pred = fitted(Dataset(docs)).get().numpy().ravel()[:n]
    assert (pred == lab).mean() > 0.95


def test_sparse_naive_bayes_matches_dense():
    """NB on CSR rows (scatter-add counts) must equal the dense fit, and
    its model must score sparse datasets."""
    import scipy.sparse as sp

    from keystone_tpu.models import NaiveBayesEstimator

    rng = np.random.default_rng(7)
    n, d, k = 128, 200, 4
    dense = (rng.uniform(size=(n, d)) < 0.1) * rng.integers(1, 5, size=(n, d))
    dense = dense.astype(np.float32)
    lab = rng.integers(0, k, size=n).astype(np.int32)
    rows = [sp.csr_matrix(dense[i : i + 1]) for i in range(n)]

    dm = NaiveBayesEstimator(k, lam=1.0).fit_arrays(dense, lab)
    sm = NaiveBayesEstimator(k, lam=1.0).fit_dataset(Dataset(rows), Dataset(lab))
    np.testing.assert_allclose(
        np.asarray(sm.log_cond), np.asarray(dm.log_cond), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(sm.log_prior), np.asarray(dm.log_prior), rtol=1e-6
    )
    scored = sm.apply_dataset(Dataset(rows)).numpy()
    want = np.asarray(dm.apply_batch(jnp.asarray(dense)))
    np.testing.assert_allclose(scored, want, rtol=1e-4, atol=1e-4)


def test_sparse_logreg_matches_dense_and_runs_amazon():
    """Sparse logistic regression (gather/scatter gradients) matches the
    dense fit on identical data, and the Amazon app runs end-to-end with
    CSR hashed features."""
    import scipy.sparse as sp

    from keystone_tpu.models import LogisticRegressionEstimator

    rng = np.random.default_rng(6)
    n, d, k = 256, 300, 3
    dense = ((rng.uniform(size=(n, d)) < 0.08) * rng.normal(size=(n, d))).astype(
        np.float32
    )
    w_true = rng.normal(size=(d, k)).astype(np.float32)
    lab = np.argmax(dense @ w_true, axis=1).astype(np.int32)

    est = LogisticRegressionEstimator(k, lam=1e-3, num_iters=120)
    dm = est.fit_arrays(dense, lab)
    rows = [sp.csr_matrix(dense[i : i + 1]) for i in range(n)]
    sm = est.fit_dataset(Dataset(rows), Dataset(lab))
    wd, ws = np.asarray(dm.weights), np.asarray(sm.weights)
    scale = np.abs(wd).max() + 1e-9
    assert np.abs(ws - wd).max() / scale < 3e-2, np.abs(ws - wd).max() / scale

    # sparse scoring path through the model
    scored = sm.apply_dataset(Dataset(rows)).numpy()
    np.testing.assert_allclose(
        scored, dense @ ws, rtol=1e-4, atol=1e-4
    )

    from keystone_tpu.pipelines.amazon_reviews import AmazonReviewsPipeline, Config

    out = AmazonReviewsPipeline.run(Config(num_features=20000, synthetic_n=300))
    assert out["accuracy"] > 0.9, out


def test_sparsify_to_sparse_lbfgs_pipeline_and_scoring():
    """End-to-end DSL flow: dense rows → Sparsify (host CSR items) →
    SparseLBFGSwithL2 (sparse gradient fit) → sparse gather scoring →
    MaxClassifier, without densifying inside the solver."""
    from keystone_tpu.models import SparseLBFGSwithL2
    from keystone_tpu.ops import MaxClassifier, Sparsify

    rng = np.random.default_rng(3)
    n, d, k = 128, 300, 3
    w_true = rng.normal(size=(d, k)).astype(np.float32)
    dense = (rng.uniform(size=(n, d)) < 0.05).astype(np.float32) * rng.normal(
        size=(n, d)
    ).astype(np.float32)
    lab = np.argmax(dense @ w_true, axis=1).astype(np.int32)
    y = -np.ones((n, k), np.float32)
    y[np.arange(n), lab] = 1.0

    pipe = Pipeline.of(Sparsify()).and_then(
        SparseLBFGSwithL2(lam=1e-4, num_iterations=60),
        Dataset(dense),
        Dataset(y),
    ).and_then(MaxClassifier())
    fitted = pipe.fit()
    pred = fitted(Dataset(dense)).get().numpy().ravel()[:n]
    assert (pred == lab).mean() > 0.95


# ------------------------------------------------- bucketing + chunking


def _random_csr_rows(rng, n, d, nnz_per_row):
    import scipy.sparse as sp

    rows = []
    for i in range(n):
        nz = int(nnz_per_row[i])
        cols = rng.choice(d, size=max(nz, 1), replace=False)
        vals = rng.normal(size=max(nz, 1)).astype(np.float32)
        rows.append(
            sp.csr_matrix((vals, ([0] * len(cols), cols)), shape=(1, d))
        )
    return rows


def test_bucketed_kills_global_padding_cliff():
    """One dense row must NOT inflate every row's padding (round-2 review):
    bucketed memory stays near Σnnz while global padding blows up n×max."""
    from keystone_tpu.ops.sparse import BucketedSparseRows, PaddedSparseRows

    rng = np.random.default_rng(0)
    n, d = 256, 5000
    nnz = np.full(n, 8)
    nnz[0] = 4000  # the one dense-ish document
    rows = _random_csr_rows(rng, n, d, nnz)
    padded = PaddedSparseRows.from_scipy_rows(rows)
    bucketed = BucketedSparseRows.from_scipy_rows(rows)
    assert padded.nnz_max >= 4000
    # padded: every row pays 4000 entries; bucketed: ~8-entry buckets + one
    assert bucketed.nbytes < padded.nbytes / 20
    # and the math agrees with the dense product
    dense = np.concatenate([r.toarray() for r in rows]).astype(np.float32)
    w = rng.normal(size=(d, 3)).astype(np.float32)
    np.testing.assert_allclose(
        bucketed.matmul(w), dense @ w, atol=2e-3
    )


def test_bucketed_matmul_restores_row_order():
    from keystone_tpu.ops.sparse import BucketedSparseRows

    rng = np.random.default_rng(1)
    n, d = 40, 100
    nnz = rng.integers(1, 60, size=n)  # spans several pow2 buckets
    rows = _random_csr_rows(rng, n, d, nnz)
    sp_m = BucketedSparseRows.from_scipy_rows(rows)
    assert len(sp_m.buckets) > 1
    dense = np.concatenate([r.toarray() for r in rows]).astype(np.float32)
    w = rng.normal(size=(d, 4)).astype(np.float32)
    np.testing.assert_allclose(sp_m.matmul(w), dense @ w, atol=2e-3)


def test_bucketed_max_buckets_cap():
    from keystone_tpu.ops.sparse import BucketedSparseRows

    rng = np.random.default_rng(2)
    n, d = 128, 4096
    nnz = 2 ** rng.integers(0, 11, size=n)  # 11 natural pow2 caps
    rows = _random_csr_rows(rng, n, d, nnz)
    sp_m = BucketedSparseRows.from_scipy_rows(rows, max_buckets=4)
    assert len(sp_m.buckets) <= 4


def test_chunked_ops_match_unchunked(monkeypatch):
    """sparse_matmul / sparse_grad with a tiny chunk budget must agree
    with the single-shot path bit-for-bit-ish."""
    import keystone_tpu.ops.sparse as sparse_mod

    rng = np.random.default_rng(3)
    rows, nnz, d, k = 300, 13, 70, 5
    idx = rng.integers(0, d, size=(rows, nnz)).astype(np.int32)
    vals = rng.normal(size=(rows, nnz)).astype(np.float32)
    w = rng.normal(size=(d, k)).astype(np.float32)
    r = rng.normal(size=(rows, k)).astype(np.float32)
    big_mm = np.asarray(sparse_mod.sparse_matmul(idx, vals, w))
    big_g = np.asarray(sparse_mod.sparse_grad(idx, vals, r, d))
    monkeypatch.setattr(sparse_mod, "_auto_chunk", lambda *a: 64)
    small_mm = np.asarray(sparse_mod.sparse_matmul(idx, vals, w))
    small_g = np.asarray(sparse_mod.sparse_grad(idx, vals, r, d))
    np.testing.assert_allclose(small_mm, big_mm, atol=1e-5)
    np.testing.assert_allclose(small_g, big_g, atol=1e-4)


def test_sparse_lbfgs_heavy_tailed_nnz_property():
    """Property test (round-2 review item 4): a heavy-tailed nnz corpus fits
    through the bucketed path and matches the dense solver."""
    from keystone_tpu.models import DenseLBFGSwithL2, SparseLBFGSwithL2

    rng = np.random.default_rng(4)
    n, d, k = 192, 400, 3
    # log-normal-ish tail: most rows tiny, a few near-dense
    nnz = np.minimum((rng.pareto(1.0, size=n) * 5 + 1).astype(int), d - 1)
    rows = _random_csr_rows(rng, n, d, nnz)
    dense = np.concatenate([r.toarray() for r in rows]).astype(np.float32)
    w_true = rng.normal(size=(d, k)).astype(np.float32)
    lab = np.argmax(dense @ w_true, axis=1)
    y = -np.ones((n, k), np.float32)
    y[np.arange(n), lab] = 1.0

    sparse_model = SparseLBFGSwithL2(lam=1e-3, num_iterations=150).fit_dataset(
        Dataset(rows), Dataset(y)
    )
    dense_model = DenseLBFGSwithL2(
        lam=1e-3, num_iterations=150, fit_intercept=False
    ).fit_arrays(dense, y)
    # both near the shared optimum; heavy-tailed nnz makes the problem
    # ill-conditioned, so allow loose convergence slack
    np.testing.assert_allclose(
        np.asarray(sparse_model.weights),
        np.asarray(dense_model.weights),
        atol=1e-2,
    )


def test_sparse_lbfgs_intercept_matches_dense():
    """The constant-column intercept must reproduce the dense solver's
    centered intercept (same objective, different parameterization)."""
    from keystone_tpu.models import DenseLBFGSwithL2, SparseLBFGSwithL2

    rng = np.random.default_rng(5)
    n, d, k = 160, 90, 3
    dense = ((rng.uniform(size=(n, d)) < 0.2) * rng.normal(size=(n, d))).astype(
        np.float32
    )
    w_true = rng.normal(size=(d, k)).astype(np.float32)
    shift = np.array([1.0, -2.0, 0.5], np.float32)
    scores = dense @ w_true + shift
    lab = np.argmax(scores, axis=1)
    y = -np.ones((n, k), np.float32)
    y[np.arange(n), lab] = 1.0

    import scipy.sparse as sp_

    rows = [sp_.csr_matrix(dense[i : i + 1]) for i in range(n)]
    m_sp = SparseLBFGSwithL2(
        lam=1e-3, num_iterations=150, fit_intercept=True
    ).fit_dataset(Dataset(rows), Dataset(y))
    m_d = DenseLBFGSwithL2(
        lam=1e-3, num_iterations=150, fit_intercept=True
    ).fit_arrays(dense, y)
    assert m_sp.intercept is not None
    np.testing.assert_allclose(
        np.asarray(m_sp.weights), np.asarray(m_d.weights), atol=2e-2
    )
    np.testing.assert_allclose(
        np.asarray(m_sp.intercept), np.asarray(m_d.intercept), atol=2e-2
    )


# ------------------------------------- node-choice breadth (round-2 review 3)


def test_node_choice_local_vs_distributed_ls():
    """Size-based physical choice: a small full problem swaps the sharded
    normal-equations estimator for the local single-device solve; a large
    one keeps the distributed path."""
    from keystone_tpu.models import (
        LinearMapEstimator,
        LocalLeastSquaresEstimator,
    )

    rng = np.random.default_rng(0)
    small = Dataset(rng.normal(size=(64, 16)).astype(np.float32))
    est = LinearMapEstimator(lam=1e-2)
    chosen = est.choose_physical(small, full_n=64)
    assert isinstance(chosen, LocalLeastSquaresEstimator)
    assert chosen.lam == est.lam and chosen.fit_intercept == est.fit_intercept
    # big full_n (sample is still small) keeps the distributed solve
    assert est.choose_physical(small, full_n=1_000_000) is est
    # no size information -> no swap
    assert est.choose_physical(small) is est


def test_node_choice_fires_through_optimizer_pipeline(caplog):
    """Both r3 choices fire from SAMPLED stats inside the default
    optimizer: local-LS swap on a small pipeline, Convolver strategy
    pinned from the sampled image shape."""
    import logging

    import jax.numpy as jnp

    from keystone_tpu.models import LinearMapEstimator
    from keystone_tpu.ops import MaxClassifier
    from keystone_tpu.ops.images import Convolver, _pick_conv_strategy
    from keystone_tpu.workflow import transformer as transformer_fn

    rng = np.random.default_rng(1)
    n, hw, kf = 48, 16, 8
    imgs = rng.normal(size=(n, hw, hw, 3)).astype(np.float32)
    filters = rng.normal(size=(kf, 3, 3, 3)).astype(np.float32)
    lab = rng.integers(0, 3, size=n)
    y = -np.ones((n, 3), np.float32)
    y[np.arange(n), lab] = 1.0

    conv = Convolver(filters)  # strategy="auto"
    assert conv.strategy == "auto"
    pool = transformer_fn(lambda v: v.mean(axis=(1, 2)))
    pipe = (
        Pipeline.of(conv)
        .and_then(pool)
        .and_then(LinearMapEstimator(lam=1e-3), Dataset(imgs), Dataset(y))
        .and_then(MaxClassifier())
    )
    with caplog.at_level(logging.INFO, "keystone_tpu.workflow.optimizer"):
        fitted = pipe.fit()
    choices = [r.message for r in caplog.records if "node choice" in r.message]
    assert any("LocalLeastSquaresEstimator" in m for m in choices), choices
    assert any("Convolver" in m for m in choices), choices
    pred = fitted(Dataset(imgs)).get().numpy().ravel()[:n]
    assert np.isfinite(pred).all()
    # the pinning itself (auto -> measured concrete strategy):
    sample = Dataset(imgs)
    pinned = conv.choose_physical(sample)
    assert pinned is not conv
    assert pinned.strategy == _pick_conv_strategy(hw, hw, filters.shape, 1)
    assert pinned.strategy in ("direct", "im2col")
    # a pinned convolver does not re-pin
    assert pinned.choose_physical(sample) is pinned


def test_nb_and_logistic_bucketed_heavy_tailed_match_dense():
    """NB and logistic now route through the bucketed representation:
    a corpus with one near-dense document must fit cheaply and match
    the dense fits (counts and CE loss are row-permutation invariant)."""
    from keystone_tpu.models import LogisticRegressionEstimator, NaiveBayesEstimator

    rng = np.random.default_rng(11)
    n, d, k = 96, 500, 3
    nnz = np.full(n, 6)
    nnz[0] = 400  # the dense-ish document
    rows = _random_csr_rows(rng, n, d, nnz)
    # make values positive (NB counts)
    for r in rows:
        r.data = np.abs(r.data) + 0.5
    dense = np.concatenate([r.toarray() for r in rows]).astype(np.float32)
    lab = rng.integers(0, k, size=n).astype(np.int32)

    nb_sp = NaiveBayesEstimator(k, lam=1.0).fit_dataset(
        Dataset(rows), Dataset(lab)
    )
    nb_d = NaiveBayesEstimator(k, lam=1.0).fit_arrays(dense, lab)
    np.testing.assert_allclose(
        np.asarray(nb_sp.log_cond), np.asarray(nb_d.log_cond), atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(nb_sp.log_prior), np.asarray(nb_d.log_prior), atol=1e-5
    )

    lr_sp = LogisticRegressionEstimator(k, lam=1e-2, num_iters=40).fit_dataset(
        Dataset(rows), Dataset(lab)
    )
    lr_d = LogisticRegressionEstimator(k, lam=1e-2, num_iters=40).fit_arrays(
        dense, lab
    )
    np.testing.assert_allclose(
        np.asarray(lr_sp.weights), np.asarray(lr_d.weights), atol=5e-3
    )


def test_bucketize_handles_padded_dataset_rows():
    """A host Dataset may carry padding rows beyond its true n; rows past
    n must be excluded from masks/labels, not crash or train (review
    finding: the old padded paths masked these, the bucketed path must
    too)."""
    import scipy.sparse as sp_

    from keystone_tpu.models import NaiveBayesEstimator
    from keystone_tpu.ops.sparse import BucketedSparseRows, bucketize_with_labels

    rng = np.random.default_rng(0)
    rows = _random_csr_rows(rng, 12, 30, np.full(12, 4))
    for r in rows:
        r.data = np.abs(r.data) + 0.5
    n_true = 9  # last 3 rows are Dataset padding
    lab = rng.integers(0, 3, size=n_true).astype(np.int32)

    sp_m = BucketedSparseRows.from_scipy_rows(rows)
    y = np.zeros((n_true, 3), np.float32)
    y[np.arange(n_true), lab] = 1.0
    bidx, bvals, by, n, d, brow_ok = bucketize_with_labels(sp_m, y, n=n_true)
    assert n == n_true
    assert sum(float(np.asarray(m).sum()) for m in brow_ok) == n_true

    # end to end: NB over the padded host Dataset matches the dense fit
    # restricted to the true rows
    ds = Dataset(rows)
    ds.n = n_true
    nb_sp = NaiveBayesEstimator(3, lam=1.0).fit_dataset(ds, Dataset(lab))
    dense = np.concatenate([r.toarray() for r in rows[:n_true]]).astype(np.float32)
    nb_d = NaiveBayesEstimator(3, lam=1.0).fit_arrays(dense, lab)
    np.testing.assert_allclose(
        np.asarray(nb_sp.log_cond), np.asarray(nb_d.log_cond), atol=1e-5
    )
