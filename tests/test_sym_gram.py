"""``sharded_gram``: the upper block triangle of ``aᵀa``, mirrored.

The solver's Gramian is symmetric, so ``parallel/collectives.py §
sharded_gram`` multiplies out only the strips on and right of the
diagonal (sixteen column panels at a width that is a multiple of 2048,
eight at another multiple of 1024) and copies the rest.  These tests hold the panelled form to the one dot
it replaced: same entries to f32 rounding, exactly symmetric, replicated
under the mesh, 53 % of the flops, the same fitted weights — and the
``solver.fit`` span says how many panels a fit ran with, and how many
blocks' Cholesky factors the weighted solver kept across its sweeps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from keystone_tpu.models import block_ls
from keystone_tpu.models import block_weighted_ls as bw
from keystone_tpu.obs import ledger
from keystone_tpu.parallel import local_mesh, shard_batch, use_mesh
from keystone_tpu.parallel.collectives import (
    gram_panels,
    sharded_gram,
    sharded_matmul,
)

ROWS = 64


def _one_dot(a):
    return jnp.matmul(a.T, a, precision=lax.Precision.HIGHEST)


def _rows(width, seed=0):
    return np.random.default_rng(seed).normal(size=(ROWS, width)).astype(np.float32)


@pytest.mark.parametrize(
    "width,panels",
    [(96, 1), (1000, 1), (1024, 8), (1152, 1), (2048, 16), (3072, 8), (4096, 16), (8192, 16)],
)
def test_panel_count_is_a_function_of_the_width_alone(width, panels):
    assert gram_panels(width) == panels


@pytest.mark.parametrize("sharded", [False, True], ids=["one_device", "mesh_rows"])
@pytest.mark.parametrize("width", [96, 1024, 2048, 4096])
def test_gram_equals_the_one_dot_and_is_symmetric(mesh, width, sharded):
    x = _rows(width, seed=width)
    want = np.asarray(_one_dot(jnp.asarray(x)))
    with use_mesh(mesh if sharded else local_mesh()) as m:
        a = shard_batch(x, m) if sharded else jnp.asarray(x)
        if sharded:
            assert not a.sharding.is_fully_replicated  # rows over 'data'
        g = jax.jit(sharded_gram)(a)
    assert g.shape == (width, width) and g.dtype == jnp.float32
    assert g.sharding.is_fully_replicated
    got = np.asarray(g)
    assert np.array_equal(got, got.T)  # mirrored entries are copies
    # the same f32 contraction, summed in another order at most
    scale = np.sqrt(np.outer(np.diag(want), np.diag(want)))
    assert np.max(np.abs(got - want) / scale) < 2e-6


def _flops(fn, width, rows=512):
    a = jax.ShapeDtypeStruct((rows, width), jnp.float32)
    cost = jax.jit(fn).lower(a).compile().cost_analysis()
    return (cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"]


def test_panelled_form_costs_under_six_tenths_of_the_one_dot():
    with use_mesh(local_mesh()):
        panelled = _flops(sharded_gram, 4096)
        one = _flops(lambda a: sharded_matmul(a, a), 4096)
        assert 0.5 * one < panelled <= 0.6 * one
        # a width the rule leaves alone is the one dot, flop for flop
        assert _flops(sharded_gram, 96) == _flops(lambda a: sharded_matmul(a, a), 96)


def _toy_fit(width, blocks, n=256, k=5, seed=3):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, width * blocks)).astype(np.float32))
    cls = rng.integers(0, k, size=n)
    y = jnp.asarray(2.0 * np.eye(k, dtype=np.float32)[cls] - 1.0)
    nf = jnp.float32(n)
    alpha = bw.class_weights(y, nf, 0.25)
    w, xm, ym = bw._weighted_bcd_fit(x, y, alpha, nf, 1.0, 2, width, True)
    return np.asarray(w)


def test_weighted_bcd_fit_gives_the_one_dot_fits_weights(monkeypatch):
    width, blocks = 1024, 2
    assert gram_panels(width) > 1
    with use_mesh(local_mesh()):
        panelled = _toy_fit(width, blocks)
        # the same fit with the Gramian held to the one dot (the solver
        # reads its module attribute at trace time, as the benchmark's
        # planted faults rely on)
        monkeypatch.setattr(
            bw, "sharded_gram", lambda a, mesh=None: sharded_matmul(a, a, mesh=mesh)
        )
        bw._weighted_bcd_fit.clear_cache()
        try:
            one_dot = _toy_fit(width, blocks)
        finally:
            bw._weighted_bcd_fit.clear_cache()
    assert panelled.shape == (blocks, width, 5)
    err = np.linalg.norm(panelled - one_dot) / np.linalg.norm(one_dot)
    assert 0 <= err < 1e-5  # f32 rounding (lam keeps the toy system well conditioned)


@pytest.mark.parametrize(
    "estimator,solver",
    [
        (bw.BlockWeightedLeastSquaresEstimator, "bcd.weighted"),
        (block_ls.BlockLeastSquaresEstimator, "bcd"),
    ],
)
@pytest.mark.parametrize("block_size,panels", [(1024, 8), (96, 1)])
def test_solver_fit_span_carries_gram_panels(estimator, solver, block_size, panels):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 2 * block_size)).astype(np.float32)
    y = 2.0 * np.eye(4, dtype=np.float32)[rng.integers(0, 4, size=32)] - 1.0
    mark = max((r.span_id for r in ledger.recent_spans()), default=0)
    estimator(block_size=block_size, num_iter=1, lam=1e-2).fit_arrays(x, y)
    fits = [
        r for r in ledger.recent_spans()
        if r.span_id > mark and r.name == "solver.fit"
    ]
    assert len(fits) == 1
    assert fits[0].attrs["solver"] == solver
    assert fits[0].attrs["blocks"] == 2
    assert fits[0].attrs["gram_panels"] == panels == gram_panels(block_size)
    # one sweep keeps no factor; only the weighted solver has the cache
    kept = {k: v for k, v in fits[0].attrs.items() if k.startswith("factor_cache")}
    assert kept == (
        {"factor_cache": 0, "factor_cache_bytes": 0} if solver == "bcd.weighted" else {}
    )


@pytest.mark.parametrize(
    "rows,num_iter,kept",
    [(384, 2, 2), (384, 1, 0), (32, 2, 0)],
    ids=["two_sweeps", "one_sweep", "rows_lt_block"],
)
def test_solver_fit_span_says_how_many_factors_the_fit_kept(rows, num_iter, kept):
    # four devices on the suite's data axis: 96 rows a device, or 8
    block_size = 96
    rng = np.random.default_rng(2)
    x = rng.normal(size=(rows, 2 * block_size)).astype(np.float32)
    y = 2.0 * np.eye(4, dtype=np.float32)[rng.integers(0, 4, size=rows)] - 1.0
    mark = max((r.span_id for r in ledger.recent_spans()), default=0)
    bw.BlockWeightedLeastSquaresEstimator(
        block_size=block_size, num_iter=num_iter, lam=1e-2
    ).fit_arrays(x, y)
    (fit,) = [
        r for r in ledger.recent_spans()
        if r.span_id > mark and r.name == "solver.fit"
    ]
    assert fit.attrs["factor_cache"] == kept
    assert fit.attrs["factor_cache_bytes"] == kept * block_size * block_size * 4


def test_obs_report_shows_the_solvers_gram_panels(tmp_path):
    from tools.obs_report import render, summarize

    rng = np.random.default_rng(1)
    x = rng.normal(size=(32, 2048)).astype(np.float32)
    y = 2.0 * np.eye(4, dtype=np.float32)[rng.integers(0, 4, size=32)] - 1.0
    led = ledger.start_run(str(tmp_path))
    try:
        bw.BlockWeightedLeastSquaresEstimator(
            block_size=1024, num_iter=1, lam=1e-2
        ).fit_arrays(x, y)
        path = led.path
    finally:
        ledger.stop_run()
    summary = summarize(path)
    assert summary["solvers"]["bcd.weighted"]["count"] == 1
    assert summary["solvers"]["bcd.weighted"]["gram_panels"] == 8
    assert summary["solvers"]["bcd.weighted"]["factor_cache"] == 0
    text = render(summary)
    assert "gram_panels=8" in text
    assert "factor_cache=0  factor_cache_bytes=0" in text
