"""L-BFGS mid-fit checkpoint/resume (round-3 review weak-3).

Both BCD solvers checkpoint per epoch; the L-BFGS family previously had
no mid-fit checkpoint at all — the one solver family where a kill lost
everything.  These tests pin: (1) the chunked resumable driver matches
the single-scan jitted fit, (2) an interrupted fit RESUMES from the
carry (not from scratch) and lands on the uninterrupted result, (3) a
different problem's checkpoint is rejected by fingerprint, (4) the
sparse path at vocab scale round-trips through the checkpoint.
"""

import os

import numpy as np
import pytest

import keystone_tpu.models.lbfgs as lb
from keystone_tpu.models.lbfgs import DenseLBFGSwithL2, SparseLBFGSwithL2
from keystone_tpu.workflow import Dataset


def _dense_problem(n=96, d=12, k=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d, k)).astype(np.float32)
    y = (x @ w + 0.01 * rng.normal(size=(n, k))).astype(np.float32)
    return x, y


def test_dense_checkpointed_matches_plain_fit(tmp_path, mesh):
    x, y = _dense_problem()
    est = DenseLBFGSwithL2(lam=1e-3, num_iterations=25, history=5)
    plain = est.fit_dataset(Dataset(x), Dataset(y))
    ckpt = est.fit_checkpointed(
        Dataset(x), Dataset(y), checkpoint_dir=str(tmp_path), checkpoint_every=7
    )
    np.testing.assert_allclose(
        np.asarray(ckpt.weights), np.asarray(plain.weights), atol=2e-4
    )
    assert os.path.exists(tmp_path / "lbfgs_dense.npz")


def test_dense_interrupted_resumes_and_matches(tmp_path, mesh):
    """Kill the fit mid-chunk; the rerun must RESUME (load_cb hit at
    it>0, fewer chunks executed) and land on the uninterrupted model."""
    x, y = _dense_problem()
    est = DenseLBFGSwithL2(lam=1e-3, num_iterations=24, history=5)
    control = est.fit_checkpointed(
        Dataset(x), Dataset(y),
        checkpoint_dir=str(tmp_path / "control"), checkpoint_every=6,
    )

    # crash injection: die after 2 completed chunks (12 iterations)
    orig = lb.lbfgs_minimize_resumable
    state = {"chunks": 0}

    def crashing(vag, data, x0, **kw):
        real_save = kw.get("save_cb")

        def counting_save(it, carry):
            real_save(it, carry)
            state["chunks"] += 1
            if state["chunks"] == 2:
                raise RuntimeError("injected mid-fit kill")

        kw["save_cb"] = counting_save
        return orig(vag, data, x0, **kw)

    lb.lbfgs_minimize_resumable = crashing
    try:
        with pytest.raises(RuntimeError, match="injected"):
            est.fit_checkpointed(
                Dataset(x), Dataset(y),
                checkpoint_dir=str(tmp_path / "crash"), checkpoint_every=6,
            )
    finally:
        lb.lbfgs_minimize_resumable = orig

    # the carry survived at iteration 12
    with np.load(tmp_path / "crash" / "lbfgs_dense.npz") as z:
        assert int(z["it"]) == 12
        assert int(z["count"]) > 0  # real s/y history, not a fresh carry

    # resume: instrument the chunk loop via save_cb call count — a
    # resumed 24-iteration fit with every=6 from it=12 saves exactly
    # twice (18, 24); from scratch it would save 4 times
    saves = []
    orig2 = lb._lbfgs_checkpoint_callbacks

    def counting_callbacks(*a, **kw):
        load_cb, save_cb = orig2(*a, **kw)

        def save(it, carry):
            saves.append(it)
            save_cb(it, carry)

        return load_cb, save

    lb._lbfgs_checkpoint_callbacks = counting_callbacks
    try:
        resumed = est.fit_checkpointed(
            Dataset(x), Dataset(y),
            checkpoint_dir=str(tmp_path / "crash"), checkpoint_every=6,
        )
    finally:
        lb._lbfgs_checkpoint_callbacks = orig2
    assert saves == [18, 24], saves
    np.testing.assert_allclose(
        np.asarray(resumed.weights), np.asarray(control.weights), atol=1e-5
    )


def test_checkpoint_rejected_for_different_problem(tmp_path, mesh):
    """A checkpoint from different data/λ must not be resumed."""
    x, y = _dense_problem(seed=0)
    est = DenseLBFGSwithL2(lam=1e-3, num_iterations=10, history=4)
    est.fit_checkpointed(
        Dataset(x), Dataset(y), checkpoint_dir=str(tmp_path), checkpoint_every=5
    )
    x2, y2 = _dense_problem(seed=7)
    plain = est.fit_dataset(Dataset(x2), Dataset(y2))
    ckpt = est.fit_checkpointed(
        Dataset(x2), Dataset(y2),
        checkpoint_dir=str(tmp_path), checkpoint_every=5,
    )
    np.testing.assert_allclose(
        np.asarray(ckpt.weights), np.asarray(plain.weights), atol=2e-4
    )
    # λ change likewise restarts (fingerprint covers the objective)
    est2 = DenseLBFGSwithL2(lam=1e-1, num_iterations=10, history=4)
    plain2 = est2.fit_dataset(Dataset(x2), Dataset(y2))
    ckpt2 = est2.fit_checkpointed(
        Dataset(x2), Dataset(y2),
        checkpoint_dir=str(tmp_path), checkpoint_every=5,
    )
    np.testing.assert_allclose(
        np.asarray(ckpt2.weights), np.asarray(plain2.weights), atol=2e-4
    )


def test_completed_checkpoint_not_reused_for_shorter_fit(tmp_path, mesh):
    """A completed 16-iteration fit leaves its carry on disk; a later
    8-iteration request on the same problem must refit from scratch,
    never silently return the more-iterated weights."""
    x, y = _dense_problem()
    long = DenseLBFGSwithL2(lam=1e-3, num_iterations=16, history=4)
    long_model = long.fit_checkpointed(
        Dataset(x), Dataset(y), checkpoint_dir=str(tmp_path), checkpoint_every=4
    )
    short = DenseLBFGSwithL2(lam=1e-3, num_iterations=8, history=4)
    fresh = short.fit_dataset(Dataset(x), Dataset(y))
    got = short.fit_checkpointed(
        Dataset(x), Dataset(y), checkpoint_dir=str(tmp_path), checkpoint_every=4
    )
    np.testing.assert_allclose(
        np.asarray(got.weights), np.asarray(fresh.weights), atol=2e-4
    )
    # and the 8-iter weights genuinely differ from the 16-iter ones
    assert np.abs(
        np.asarray(got.weights) - np.asarray(long_model.weights)
    ).max() > 1e-6


def test_sparse_checkpointed_vocab_scale_resumes(tmp_path, mesh):
    """Sparse path at vocab scale (d=50k here; the pattern is the 1M
    fit): interrupted fit resumes from the saved carry and matches the
    uninterrupted checkpointed fit exactly, and the plain jitted fit to
    solver tolerance."""
    import scipy.sparse as sp

    rng = np.random.default_rng(1)
    n, d, k, nnz = 192, 50_000, 3, 8
    rows = []
    for _ in range(n):
        idx = rng.choice(d, size=nnz, replace=False)
        rows.append(
            sp.csr_matrix(
                (rng.normal(size=nnz).astype(np.float32), (np.zeros(nnz), idx)),
                shape=(1, d),
            )
        )
    y = rng.normal(size=(n, k)).astype(np.float32)

    est = SparseLBFGSwithL2(lam=1e-2, num_iterations=12, history=4)
    plain = est.fit_dataset(
        Dataset(rows), Dataset(y)
    )
    control = est.fit_checkpointed(
        Dataset(rows), Dataset(y),
        checkpoint_dir=str(tmp_path / "control"), checkpoint_every=4,
    )
    np.testing.assert_allclose(
        np.asarray(control.weights), np.asarray(plain.weights), atol=5e-4
    )

    # interrupt after the first save, then resume
    orig = lb._lbfgs_checkpoint_callbacks

    def crashing_callbacks(*a, **kw):
        load_cb, save_cb = orig(*a, **kw)

        def save(it, carry):
            save_cb(it, carry)
            if it == 4:
                raise RuntimeError("injected mid-fit kill")

        return load_cb, save

    lb._lbfgs_checkpoint_callbacks = crashing_callbacks
    try:
        with pytest.raises(RuntimeError, match="injected"):
            est.fit_checkpointed(
                Dataset(rows), Dataset(y),
                checkpoint_dir=str(tmp_path / "crash"), checkpoint_every=4,
            )
    finally:
        lb._lbfgs_checkpoint_callbacks = orig
    with np.load(tmp_path / "crash" / "lbfgs_sparse.npz") as z:
        assert int(z["it"]) == 4

    resumed = est.fit_checkpointed(
        Dataset(rows), Dataset(y),
        checkpoint_dir=str(tmp_path / "crash"), checkpoint_every=4,
    )
    np.testing.assert_allclose(
        np.asarray(resumed.weights), np.asarray(control.weights), atol=1e-5
    )
