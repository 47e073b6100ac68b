"""KernelRidgeTimitPipeline — exact Gaussian-kernel ridge regression on
TIMIT-shaped frames by block Gauss–Seidel (arXiv:1602.05310) — held on the
CPU at toy size to the benchmark's plain reference
(``benchmark/reference/timit_kernel_krr.py``, which imports nothing of the
program): held-out scores and dual coefficients of the entry, ``build``
against ``build_scorer``, the in-core sweep through the ``gram_pallas``
dispatcher against the generator called directly, the three sweeps (in
core, cached, out of core) against each other, and the ``solver.fit``
span's attributes."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import datagen  # noqa: E402
from benchmark.reference import timit_kernel_krr as reference  # noqa: E402
from keystone_tpu.models import kernel_ridge as kr  # noqa: E402
from keystone_tpu.models.common import solve_spd  # noqa: E402
from keystone_tpu.obs import ledger  # noqa: E402
from keystone_tpu.pipelines import ALL_PIPELINES  # noqa: E402
from keystone_tpu.pipelines.kernel_ridge_timit import KernelRidgeTimitPipeline  # noqa: E402
from keystone_tpu.workflow import Dataset  # noqa: E402
from keystone_tpu.workflow.blockstore import RowBlockStore  # noqa: E402

DIM, CLASSES, BLOCK = 24, 5, 64
CFG = {"input_dim": DIM, "num_classes": CLASSES, "block_size": BLOCK,
       "gamma": 1.0 / DIM, "lam": 1e-3}


def _frames(n, held=48, seed=7):
    x, labels = datagen.timit_frames(n + held, DIM, CLASSES, seed)
    return x[:n], labels[:n], x[n:]


def _config(epochs=1):
    return KernelRidgeTimitPipeline.Config(
        gamma=CFG["gamma"], lam=CFG["lam"], block_size=BLOCK, num_epochs=epochs,
        num_classes=CLASSES,
    )


def _relative(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("n,epochs", [(256, 1), (200, 2)])  # whole blocks; a ragged last block
def test_entry_matches_the_plain_reference(n, epochs):
    x, labels, held = _frames(n)
    fitted = KernelRidgeTimitPipeline.build_scorer(
        _config(epochs), Dataset(x, name=f"krr-{n}"), Dataset(labels, name=f"krr-{n}-labels")
    ).fit()
    scores = fitted(Dataset(held)).get().numpy()
    model = KernelRidgeTimitPipeline.fitted_model(fitted)
    want = reference.fit_and_score(CFG, x, labels, held, epochs=epochs)
    assert model.train_n == n and model.train_x.shape[0] % BLOCK == 0
    alpha = np.asarray(model.alpha)
    assert _relative(alpha[:n], want["alpha"]) < 1e-4
    assert not alpha[n:].any()  # padding rows carry no coefficient
    assert scores.shape == (48, CLASSES) and np.std(want["scores"]) > 0.1
    assert _relative(scores, want["scores"]) < 1e-4


def test_build_is_build_scorer_with_an_argmax():
    assert ALL_PIPELINES["KernelRidgeTimitPipeline"] is KernelRidgeTimitPipeline
    x, labels, held = _frames(192)
    data = Dataset(x, name="krr-b"), Dataset(labels, name="krr-b-labels")
    scores = KernelRidgeTimitPipeline.build_scorer(_config(), *data).fit()(Dataset(held)).get()
    classes = KernelRidgeTimitPipeline.build(_config(), *data).fit()(Dataset(held)).get()
    assert np.array_equal(np.argmax(scores.numpy(), axis=1), classes.numpy())
    assert len(set(classes.numpy().tolist())) > 1


def _generator_sweep(x, y, n, gamma, lam, bs, num_epochs):
    """The in-core sweep as it was before it went through the dispatcher:
    the generator called directly, the column block masked as a whole, the
    products at the default precision (the same on a CPU)."""
    n_rows = x.shape[0]
    row_ok = (jnp.arange(n_rows) < n).astype(jnp.float32)
    y = y * row_ok[:, None]
    kern = kr.GaussianKernelGenerator(gamma)

    def block_step(b, carry):
        alpha, f = carry
        xb = lax.dynamic_slice_in_dim(x, b * bs, bs)
        ok_b = lax.dynamic_slice_in_dim(row_ok, b * bs, bs)
        kcol = kern(x, xb) * row_ok[:, None] * ok_b[None, :]
        kbb = lax.dynamic_slice_in_dim(kcol, b * bs, bs) + jnp.diag(1.0 - ok_b)
        ab = lax.dynamic_slice_in_dim(alpha, b * bs, bs)
        yb = lax.dynamic_slice_in_dim(y, b * bs, bs)
        fb = lax.dynamic_slice_in_dim(f, b * bs, bs)
        ab_new = solve_spd(kbb, yb - fb + kbb @ ab, reg=lam * n) * ok_b[:, None]
        return (lax.dynamic_update_slice_in_dim(alpha, ab_new, b * bs, axis=0),
                f + kcol @ (ab_new - ab))

    def epoch(carry, _):
        return lax.fori_loop(0, n_rows // bs, block_step, carry), None

    (alpha, _), _ = lax.scan(epoch, (jnp.zeros_like(y), jnp.zeros_like(y)), None,
                             length=num_epochs)
    return alpha


def test_in_core_sweep_through_the_dispatcher_is_the_generators_bit_for_bit():
    """Off a TPU the dispatcher's route IS the generator, and taking the
    masks off the (n, block) column block (they meet zero rows of Δα, and
    the row mask goes on the (n, k) product) changes no bit."""
    x, labels, _ = _frames(200)  # 56 padding rows in the last block
    x = jnp.pad(jnp.asarray(x), ((0, 56), (0, 0)))
    y = jnp.pad(2.0 * jax.nn.one_hot(labels, CLASSES) - 1.0, ((0, 56), (0, 0)))
    want = jax.jit(_generator_sweep, static_argnums=(3, 5, 6))(
        x, y, jnp.float32(200), 0.05, 1e-3, BLOCK, 2
    )
    got = kr._krr_fit(x, y, jnp.float32(200), 0.05, 1e-3, BLOCK, 2, use_pallas=False)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.abs(np.asarray(got)[:200]).max() > 0 and not np.asarray(got)[200:].any()


@pytest.fixture(scope="module")
def toy_problem():
    x, labels, _ = _frames(200)
    # the estimator alone has no scaler and the reference scales its rows
    # itself: rows already standardised come back the same, to rounding
    xs = np.asarray(reference.scale(x, x[:1])[0])
    y = 2.0 * np.eye(CLASSES, dtype=np.float32)[labels] - 1.0
    want = reference.fit_and_score(CFG, xs, labels, xs[:4], epochs=2)["alpha"]
    return xs, y, want


@pytest.mark.parametrize("sweep", ["in_core", "cached", "out_of_core"])
def test_the_three_sweeps_agree(sweep, toy_problem, tmp_path):
    xs, y, want = toy_problem
    est = kr.KernelRidgeRegressionEstimator(
        kr.GaussianKernelGenerator(CFG["gamma"]), lam=CFG["lam"], block_size=BLOCK,
        num_epochs=2, cache_kernel_blocks=(sweep == "cached"),
    )
    mark = max((r.span_id for r in ledger.recent_spans()), default=0)
    if sweep == "out_of_core":
        store = RowBlockStore.from_batches(str(tmp_path / "rows"), [xs], xs.shape[0], BLOCK)
        model = est.fit_store(store, y)
    else:
        model = est.fit_arrays(xs, y)
    assert _relative(np.asarray(model.alpha)[:200], want) < 2e-4
    spans = [r for r in ledger.recent_spans() if r.span_id > mark and r.name == "solver.fit"]
    if sweep == "out_of_core":
        return  # the streamed sweep reports through solver.spill and its epochs
    (span,) = spans
    assert span.attrs["solver"] == {"in_core": "krr", "cached": "krr.cached"}[sweep]
    assert (span.attrs["n"], span.attrs["blocks"], span.attrs["block_size"],
            span.attrs["epochs"], span.attrs["gram"]) == (200, 4, BLOCK, 2, "xla")
    if sweep == "cached":  # the second epoch rereads every tile of the first
        assert span.attrs["cache_hits"] > 0


def test_obs_report_prints_the_kernel_sweeps_span_attributes(tmp_path):
    from tools.obs_report import render, summarize

    xs = np.random.default_rng(3).normal(size=(96, 8)).astype(np.float32)
    y = 2.0 * np.eye(3, dtype=np.float32)[np.arange(96) % 3] - 1.0
    run = ledger.start_run(str(tmp_path))
    try:
        kr.KernelRidgeRegressionEstimator(
            kr.GaussianKernelGenerator(0.1), lam=1e-3, block_size=32, num_epochs=2,
            cache_kernel_blocks=True,
        ).fit_arrays(xs, y)
    finally:
        ledger.stop_run()
    text = render(summarize(run.path))
    line = next(ln for ln in text.splitlines() if "krr.cached" in ln)
    for part in ("block_size=32", "epochs=2", "gram=xla", "cache_hits="):
        assert part in line, line
