"""MnistRandomFFT at toy widths, held to the benchmark's plain reference
(``benchmark/reference/mnist_random_fft.py``, which imports nothing of the
program and writes the transform as a product with the cosine matrix):
the padded FFT at upstream's width, the sign draw in one program, the entry
end to end through the block solver, its fitted parts, and the executor's
gather."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import compare, harness  # noqa: E402
from benchmark.reference import mnist_random_fft as reference  # noqa: E402
from keystone_tpu.obs import ledger  # noqa: E402
from keystone_tpu.ops import LinearRectifier, PaddedFFT, RandomSignNode  # noqa: E402
from keystone_tpu.ops import stats  # noqa: E402
from keystone_tpu.pipelines.mnist_random_fft import Config, MnistRandomFFT  # noqa: E402
from keystone_tpu.workflow import Dataset, Pipeline  # noqa: E402
from keystone_tpu.workflow import graph as G  # noqa: E402
from keystone_tpu.workflow.optimizer import FusedTransformer, StageFusionRule  # noqa: E402

CFG = {"num_pixels": 784, "padded": 1024, "fft_features": 512, "num_ffts": 3,
       "block_size": 512, "num_iter": 1, "num_classes": 10, "lam": 1e-3}


@pytest.mark.parametrize("pixels", [10, 100, 784])
def test_padded_fft_is_the_cosine_product(pixels):
    padded = 1 << (pixels - 1).bit_length()
    x = np.random.default_rng(pixels).normal(size=(6, pixels)).astype(np.float32)
    got = np.asarray(PaddedFFT().apply_batch(jnp.asarray(x)))
    want = x.astype(np.float64) @ np.asarray(reference.cosine_matrix(pixels, padded), np.float64)
    assert got.shape == (6, padded // 2)  # upstream's width: 512 for MNIST's 784
    # a float32 FFT of sums of up to 784 terms against the float64 product
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())
    np.testing.assert_allclose(np.asarray(PaddedFFT().apply_one(jnp.asarray(x[0]))), got[0],
                               atol=1e-6)


@pytest.mark.parametrize("seed", [0, 7, 2**31 - 1, -3, -(2**31)])
def test_the_signs_are_the_pinned_recipe_bit_for_bit(seed):
    """The draw the recipe names, ``2*bernoulli(PRNGKey(seed),0.5)-1``,
    taken eagerly line by line."""
    node = RandomSignNode.init(784, seed=seed)
    bits = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.5, (784,))
    want = bits.astype(jnp.float32) * 2.0 - 1.0
    assert np.array_equal(np.asarray(node.signs), np.asarray(want))
    assert node.signs.dtype == jnp.float32
    assert "draw='2*bernoulli(PRNGKey(seed),0.5)-1'" in node.params()[1]


@pytest.mark.parametrize("sign", [1.0, -1.0])
def test_flipping_the_pixels_before_scaling_them_changes_no_bit(sign):
    """The entry scales inside each branch, after the sign flip, so that
    every branch reads the uint8 pixels; the reference scales first."""
    from keystone_tpu.ops import PixelScaler

    pixels = jnp.arange(256, dtype=jnp.uint8)[None, :]
    signs = jnp.full((256,), sign, jnp.float32)
    flipped_first = PixelScaler().apply_batch(RandomSignNode(signs).apply_batch(pixels))
    scaled_first = RandomSignNode(signs).apply_batch(PixelScaler().apply_batch(pixels))
    assert flipped_first.dtype == jnp.float32
    assert np.array_equal(np.asarray(flipped_first).view(np.uint32),
                          np.asarray(scaled_first).view(np.uint32))


def test_every_branch_reads_the_uint8_pixels_in_one_apply(monkeypatch):
    """No node stands between the pixels and the branches: each fused
    branch is one program over the whole uint8 input (12,288 rows of 784
    are 9.6 MB, within one apply chunk; as float32 they would be six)."""
    x, labels = seeded(64, 5)
    pipe = MnistRandomFFT.build_scorer(Config(num_ffts=3, block_size=512, seed=1),
                                       Dataset(x), Dataset(labels))
    graph = pipe.graph  # as built: each branch is already its one fused node
    gather = next(n for n, op in graph.operators.items() if isinstance(op, G.GatherOperator))
    for node in graph.dependencies[gather]:
        op = graph.operators[node]
        assert [type(s).__name__ for s in op.transformer.stages] == [
            "RandomSignNode", "PixelScaler", "PaddedFFT", "LinearRectifier"]
        assert not isinstance(graph.operators.get(graph.dependencies[node][0]),
                              G.TransformerOperator)
    import importlib

    tr = importlib.import_module("keystone_tpu.workflow.transformer")
    monkeypatch.delenv("KEYSTONE_APPLY_CHUNK", raising=False)

    # as on one chip (the tests' eight virtual devices chunk nothing)
    monkeypatch.setattr(tr, "_apply_chunk_rows", lambda: tr._APPLY_CHUNK_DEFAULT)
    assert tr._chunk_rows_for(np.zeros((12288, 784), np.uint8)) == 0
    assert tr._chunk_rows_for(np.zeros((12288, 784), np.float32)) == 2048


@pytest.mark.parametrize("seed", [2**31, 2**31 + 5, -(2**31) - 1])
def test_a_seed_wider_than_32_bits_is_refused(seed):
    with pytest.raises(ValueError, match="32 bits"):
        RandomSignNode.init(784, seed=seed)


def test_the_sign_draw_is_one_program_for_every_seed():
    stats._draw_signs(0, 97)  # compiled once for this width …
    before = stats._draw_signs._cache_size()
    for seed in range(1, 9):
        RandomSignNode.init(97, seed=seed)
    assert stats._draw_signs._cache_size() == before  # … and never again


def seeded(n, seed):
    """Digit-sized grey images as the cell makes them, and labels."""
    adapter = harness.load_module("adapters", "mnist_fft")
    return adapter.grey_images(n, 28, 10, seed)


def test_the_entry_is_the_plain_reference():
    """``build_scorer(...).fit()`` with 3 FFTs in blocks of 512 (three
    blocks), against the reference on the same rows: the fitted
    featurizer's features, the first block's weights and the held-out
    scores, through the cell's own comparisons."""
    x, labels = seeded(640, 5)
    train, held = x[:512], x[512:]
    fitted = MnistRandomFFT.build_scorer(
        Config(num_ffts=3, block_size=512, lam=CFG["lam"], seed=11),
        Dataset(train, name="parity"), Dataset(labels[:512], name="parity-labels")).fit()
    adapter = harness.load_module("adapters", "mnist_fft")
    cell = {"feature_rows": 32}
    got = adapter.fitted_parts(fitted, held, train, cell, CFG)
    scores = adapter.held_out_answers(fitted, held)
    want = reference.fit_and_score(CFG, train, labels[:512], held, seed=11, feature_rows=32)
    assert got["features"].shape == (32, 3 * 512) and got["x0"].shape == (512, 512)
    # each bound ~10x the CPU's reading (4.8e-7, 1.9e-5, 1.7e-5, 3.5e-6):
    # a float32 FFT against the float32 `highest` cosine product is rounding
    # alone, and a 512-row solve with lam n = 0.5 amplifies it about 40-fold
    assert compare.rmse_over_std(got["features"], want["features"]) < 5e-6
    assert compare.relative_error(got["w0"], want["w0"]) < 2e-4
    assert compare.relative_error(
        got["w0"], reference.first_block_weights(CFG, got["x0"], labels[:512])) < 2e-4
    assert compare.rmse_over_std(scores, want["scores"]) < 5e-5
    # the argmax head is the entry's build: the same classes
    classes = MnistRandomFFT.build(
        Config(num_ffts=3, block_size=512, lam=CFG["lam"], seed=11),
        Dataset(train, name="parity"), Dataset(labels[:512], name="parity-labels")
    ).fit()(Dataset(held)).get().numpy()
    assert np.array_equal(classes, np.argmax(scores, axis=1))


def test_fitted_stages_gives_each_branch_in_feature_order():
    x, labels = seeded(96, 2)
    fitted = MnistRandomFFT.build_scorer(
        Config(num_ffts=4, block_size=1024, seed=3), Dataset(x), Dataset(labels)).fit()
    stages = MnistRandomFFT.fitted_stages(fitted)
    assert len(stages["branches"]) == 4
    for i, branch in enumerate(stages["branches"]):
        names = [type(s).__name__ for s in branch if type(s).__name__ != "Cacher"]
        assert names == ["RandomSignNode", "PixelScaler", "PaddedFFT", "LinearRectifier"]
        assert np.array_equal(np.asarray(branch[0].signs),
                              np.asarray(reference.signs(3, i, 784)))
    assert np.asarray(stages["BlockLinearMapper"].weights[0]).shape == (1024, 10)


def test_the_gather_is_one_named_program_under_one_span():
    branches = [RandomSignNode.init(16, seed=i) | PaddedFFT() | LinearRectifier(0.0)
                for i in range(20)]
    with ledger.span("test.mark") as mark:
        pass
    out = Pipeline.gather(branches)(Dataset(np.ones((8, 16), np.float32))).get()
    assert out.numpy().shape == (8, 20 * 8)
    spans = [r for r in ledger.recent_spans()
             if r.span_id > mark.span_id and r.name == "executor.gather"]
    assert len(spans) == 1
    assert spans[0].attrs == {"branches": 20, "rows": 8, "bytes": 8 * 160 * 4}


def test_stage_fusion_fuses_every_chain_in_one_pass():
    """Each branch's chain becomes one node at the place of its last stage;
    the shared first stage, read by every branch, stays alone."""
    pipe = Pipeline.of(LinearRectifier(0.0, alpha=0.5)).then_pipeline(Pipeline.gather(
        [RandomSignNode.init(16, seed=i) | PaddedFFT() | LinearRectifier(0.0)
         for i in range(24)]))
    graph = StageFusionRule().apply(pipe.graph)
    fused = [op.transformer for op in graph.operators.values()
             if isinstance(getattr(op, "transformer", None), FusedTransformer)]
    assert len(fused) == 24
    assert all([type(s).__name__ for s in t.stages]
               == ["RandomSignNode", "PaddedFFT", "LinearRectifier"] for t in fused)
    (gather,) = [n for n, op in graph.operators.items() if isinstance(op, G.GatherOperator)]
    assert all(isinstance(graph.operators[d].transformer, FusedTransformer)
               for d in graph.dependencies[gather])
    assert len(graph.operators) == 24 + 2
    assert StageFusionRule().apply(graph).operators == graph.operators  # nothing left
