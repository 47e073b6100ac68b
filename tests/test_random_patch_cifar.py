"""RandomPatchCifar at toy widths, held to the benchmark's plain reference
(``benchmark/reference/cifar_random_patch.py``, which imports nothing of the
program and follows the published order: extract patches → normalise →
subtract the mean → whiten → multiply → rectify → pool): the fused
featurizer, the entry end to end, the unweighted block solver without its
two copies, the fusion rule and the chunk rule."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import cifar_random_patch as reference  # noqa: E402
from keystone_tpu.models import BlockLeastSquaresEstimator, block_ls  # noqa: E402
from keystone_tpu.obs import ledger  # noqa: E402
from keystone_tpu.ops import (  # noqa: E402
    Convolver,
    ImageVectorizer,
    PooledConvolver,
    Pooler,
    SymmetricRectifier,
)
from keystone_tpu.ops import conv_pool_pallas as cp  # noqa: E402
from keystone_tpu.pipelines.random_patch_cifar import RandomPatchCifar  # noqa: E402
from keystone_tpu.utils import precision  # noqa: E402
from keystone_tpu.workflow import Dataset, Pipeline  # noqa: E402

tr = importlib.import_module("keystone_tpu.workflow.transformer")  # not the decorator

CFG = {"patch_size": 6, "alpha": 0.25, "pool_size": 6, "pool_stride": 5, "var_constant": 10.0}
SIZE = 16  # 11 x 11 responses, windows [0, 6) and [5, 11): they overlap, as 14 / 13 on 27 do


def images(n, size=SIZE, seed=0, lo=0, hi=256):
    return np.random.default_rng(seed).integers(lo, hi, (n, size, size, 3)).astype(np.uint8)


def bank(k, seed=1):
    """A filter bank (F, W, m) as the reference takes it: unit-norm rows, a
    symmetric whitener, a patch mean."""
    rng = np.random.default_rng(seed)
    d = 108
    f = rng.normal(size=(k, d)).astype(np.float32)
    f /= np.linalg.norm(f, axis=1, keepdims=True)
    a = rng.normal(size=(d, d)).astype(np.float32) * 0.05
    w = (a @ a.T + np.eye(d, dtype=np.float32)).astype(np.float32)
    m = (rng.normal(size=(d,)) * 0.05).astype(np.float32)
    return jnp.asarray(f), jnp.asarray(w), jnp.asarray(m)


def pooled_node(b, vectorize=True, pool=None, alpha=CFG["alpha"]):
    from keystone_tpu.models.zca import ZCAWhitener

    f, w, m = b
    conv = Convolver.from_whitened_patches(
        f, ZCAWhitener(w, m), (6, 6, 3), normalize_patches=True,
        var_constant=CFG["var_constant"])
    pool = pool or Pooler(CFG["pool_stride"], CFG["pool_size"])
    return conv, SymmetricRectifier(alpha=alpha), pool, PooledConvolver(
        conv, SymmetricRectifier(alpha=alpha), pool, vectorize)


def close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.sqrt(np.mean((got - want) ** 2)) <= tol * np.std(want), (
        np.sqrt(np.mean((got - want) ** 2)) / np.std(want))
    assert np.max(np.abs(got - want)) <= 20 * tol * np.std(want)


# ------------------------------------------------------------- the featurizer
@pytest.mark.parametrize("filters", [64, 130, 256])  # one step; over one; two products a step
@pytest.mark.parametrize("n,tile", [(5, 1024), (8, 1024), (20, 8), (16, 8)])
def test_fused_featurizer_is_the_references_published_order(filters, n, tile, monkeypatch):
    monkeypatch.setattr(cp, "_TILE_IMAGES", tile)  # 20 over 8: three tiles, the last padded
    b = bank(filters)
    x = images(n)
    got = pooled_node(b)[3].apply_batch(jnp.asarray(x))
    want = reference.features(CFG, x, b, "highest")
    assert got.shape == (n, 2 * 2 * 2 * filters)
    close(got, want, 2e-6)


@pytest.mark.parametrize("filters,n", [(64, 8), (130, 3)])
def test_the_kernel_itself_in_interpret_mode(filters, n):
    f, w, m = b = bank(filters)
    conv = pooled_node(b)[0]
    x = images(n)
    got = cp.conv_rectify_pool(
        jnp.asarray(x), conv.filters, conv.offset, stride=1, normalize=True,
        var_constant=CFG["var_constant"], alpha=CFG["alpha"], max_val=0.0,
        pool_stride=CFG["pool_stride"], pool_size=CFG["pool_size"], dtype=jnp.float32,
        use_pallas=True, interpret=True)
    close(got, reference.features(CFG, x, b, "highest"), 2e-6)


@pytest.mark.parametrize("out,stride,size,rows", [
    (27, 13, 14, 784),   # CIFAR: 4 x 176 + 4 x 16 + 8 = 776, to a multiple of 16
    (27, 13, 13, 704),   # no overlap: the last row and column belong to no window
    (11, 5, 6, 176),
    (3, 1, 2, 80),
])
def test_pool_geometry_covers_every_window_once(out, stride, size, rows):
    geom = cp.pool_geometry(out, out, stride, size)
    assert geom.rows == rows and rows % 16 == 0
    at = 0
    for lo, hi in geom.spans:
        assert lo == at and (hi - lo) % 8 == 0
        at = hi
    per_side = (out - size) // stride + 1
    assert geom.pooled_hw == (per_side, per_side) and len(geom.windows) == per_side**2
    assert all(r == size * size for r in geom.real_rows)
    for members in geom.windows:  # a window's groups tile it: no position twice
        cells = [(y, x) for g in members for y in range(geom.groups[g][0], geom.groups[g][1])
                 for x in range(geom.groups[g][2], geom.groups[g][3])]
        assert len(cells) == len(set(cells)) == size * size


def test_normalisation_holds_where_subtracting_afterwards_fails():
    """Bright, flat images (float pixels 200 ± 1): every patch's mean is 200
    and its spread ~1.  The program normalises the patch in float32 BEFORE
    it rounds anything, so bf16 streams cost it a bf16 rounding; the form
    conv(x, G) − mean·colsum(G) rounds pixels of size 200 to bf16's spacing
    of 1 first, subtracts two numbers of size 200·|G|, and is lost."""
    b = f, w, m = bank(64)
    x = (200.0 + np.random.default_rng(3).normal(size=(6, SIZE, SIZE, 3))).astype(np.float32)
    want = np.asarray(reference.features(CFG, x, b, "highest"))
    with precision.matmul("bf16"):
        got = np.asarray(pooled_node(b)[3].apply_batch(jnp.asarray(x)))
    ours = np.sqrt(np.mean((got - want) ** 2)) / np.std(want)
    assert ours < 2e-2, ours
    # the subtract-afterwards form at the same streams
    g = jnp.matmul(w, f.T, precision="highest")  # (d, K)
    xs = jnp.asarray(x, jnp.float32)
    p = jnp.stack([xs[:, dy:dy + 11, dx:dx + 11, :] for dy in range(6) for dx in range(6)],
                  axis=3).reshape(-1, 108)
    mu = p.mean(axis=1, keepdims=True)
    sd = jnp.sqrt(jnp.sum((p - mu) ** 2, axis=1, keepdims=True) / 107.0 + CFG["var_constant"])
    raw = jnp.matmul(p.astype(jnp.bfloat16), g.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    z = (raw - mu * jnp.sum(g.astype(jnp.bfloat16).astype(jnp.float32), axis=0)) / sd - m @ g
    z_true = jnp.matmul(reference.normalize_rows(p, CFG["var_constant"]) - m, g,
                        precision="highest")
    theirs = float(jnp.sqrt(jnp.mean((z - z_true) ** 2)) / jnp.std(z_true))
    assert theirs > 10 * ours, (theirs, ours)


def test_fused_node_equals_the_chain_stage_by_stage():
    conv, rect, pool, fused = pooled_node(bank(64), vectorize=False)
    x = jnp.asarray(images(7))
    chain = pool.apply_batch(rect.apply_batch(conv.apply_batch(x)))
    close(fused.apply_batch(x), chain, 2e-6)
    assert fused.apply_batch(x).shape == (7, 2, 2, 128)


def test_a_normalising_convolver_is_not_the_plain_one():
    f, w, m = b = bank(32)
    plain = Convolver(pooled_node(b)[0].filters, offset=pooled_node(b)[0].offset)
    x = jnp.asarray(images(3))
    a, c = plain.apply_batch(x), pooled_node(b)[0].apply_batch(x)
    assert a.shape == c.shape and float(jnp.std(a) / jnp.std(c)) > 10
    assert plain.jit_static() != pooled_node(b)[0].jit_static()
    assert plain.params() != pooled_node(b)[0].params()


# ---------------------------------------------------------- the optimizer rule
def optimized_labels(pipe):
    from keystone_tpu.workflow.pipeline import PipelineEnv

    g = PipelineEnv.get_optimizer().execute(pipe.graph)
    return sorted(op.label() for op in g.operators.values())


def chain(conv, rect, pool, vec=True):
    p = Pipeline.of(conv).and_then(rect).and_then(pool)
    return p.and_then(ImageVectorizer()) if vec else p


def test_the_optimizer_fuses_the_chain_into_one_node():
    conv, rect, pool, _ = pooled_node(bank(32))
    labels = optimized_labels(chain(conv, rect, pool)(Dataset(images(4))))
    assert "PooledConvolver" in labels and "Convolver" not in labels
    assert not {"SymmetricRectifier", "Pooler", "ImageVectorizer"} & set(labels)
    labels = optimized_labels(chain(conv, rect, pool, vec=False)(Dataset(images(4))))
    assert "PooledConvolver" in labels and "Pooler" not in labels


@pytest.mark.parametrize("case", ["max_pool", "pixel_fn", "negative_threshold", "shared_conv"])
def test_the_rule_leaves_what_is_not_its_chain(case):
    conv, rect, pool, _ = pooled_node(bank(32))
    if case == "max_pool":
        pool = Pooler(5, 6, pool_mode="max")
    if case == "pixel_fn":
        pool = Pooler(5, 6, pixel_fn=jnp.abs)
    if case == "negative_threshold":
        rect = SymmetricRectifier(alpha=-1.0)
    data = Dataset(images(4))
    if case == "shared_conv":  # the convolver's output has a second consumer
        head = Pipeline.of(conv)
        pipe = Pipeline.gather([head.and_then(rect).and_then(pool).and_then(ImageVectorizer()),
                                head.and_then(ImageVectorizer())])(data)
    else:
        pipe = chain(conv, rect, pool)(data)
    assert not any("PooledConvolver" in label for label in optimized_labels(pipe))


# -------------------------------------------------------------- the chunk rule
class _Rows:
    def __init__(self, n, row_bytes):
        self.shape, self.nbytes = (n, row_bytes // 4), n * row_bytes


@pytest.mark.parametrize("n,row_bytes,want", [
    (4096, 3 * 128 * 128, 2048),        # the ImageNet cells' images
    (4096, 784 * 128 * 4, 2048),        # their SIFT descriptors, 1.6 GB
    (8192, 440 * 4, 0),                 # timit-rf.fit: 14 MB fit in one chunk, so they are one
    (196608, 440 * 4, 16384),           # timit-krr.fit: long and narrow
    (16384, 3072, 2048),                # CIFAR images
    (16384, 80000 * 4, 0),              # their 5.2 GB of features: no chunk
    (4096, 4, 0),                       # int32 labels: the ImageNet cells'
    (16384, 4, 0),                      # ... CIFAR's
    (196608, 4, 0),                     # ... timit-krr.fit's, 0.8 MB
    (8192, 4096, 0),                    # exactly one chunk's bytes: whole
    (8193, 4096, 2048),                 # a row over them: as before
    (1, (32 << 20) + 1, 2048),          # a byte over them: as before
    (65536, 512, 0),                    # long and narrow, and still one chunk's bytes
    (65537, 512, 8192),                 # a row over: the grown chunk, as before
])
def test_the_chunk_rule_offers_no_chunk_whose_output_cannot_exist(n, row_bytes, want, monkeypatch):
    monkeypatch.setattr(tr, "_apply_chunk_rows", lambda: tr._APPLY_CHUNK_DEFAULT)
    assert tr._chunk_rows_for(_Rows(n, row_bytes)) == want


def test_a_node_that_owns_its_tiling_is_applied_whole(monkeypatch):
    monkeypatch.setattr(tr, "_apply_chunk_rows", lambda: 4)
    fused = pooled_node(bank(8))[3]
    calls = []
    real = PooledConvolver.apply_batch
    monkeypatch.setattr(PooledConvolver, "apply_batch",
                        lambda self, xs, mask=None: calls.append(xs.shape[0]) or real(self, xs))
    x = images(16, size=12)
    out = fused.apply_dataset(Dataset(x, shard=False)).numpy()
    assert calls == [16] and out.shape[0] == 16  # one trace of all rows: no chunks of 4
    assert PooledConvolver.owns_tiling and not Convolver.owns_tiling


# ------------------------------------------------------------------ the solver
def plain_bcd(x, y, bs, lam, iters, intercept):
    """Gauss–Seidel over zero-padded blocks, in float64."""
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    n, d = x.shape
    xm, ym = (x.mean(0), y.mean(0)) if intercept else (np.zeros(d), np.zeros(y.shape[1]))
    nb = -(-d // bs)
    xc = np.pad(x - xm, ((0, 0), (0, nb * bs - d)))
    w, p = np.zeros((nb, bs, y.shape[1])), np.zeros_like(y)
    for _ in range(iters):
        for b in range(nb):
            a = xc[:, b * bs:(b + 1) * bs]
            t = (y - ym) - p + a @ w[b]
            new = np.linalg.solve(a.T @ a + lam * n * np.eye(bs), a.T @ t)
            p, w[b] = p + a @ (new - w[b]), new
    return w, xm, ym


@pytest.mark.parametrize("d,bs", [(100, 256), (512, 256), (1000, 256), (700, 128)])
@pytest.mark.parametrize("iters,intercept", [(1, True), (2, True), (1, False)])
def test_the_block_solver_from_the_matrix_as_it_arrives(d, bs, iters, intercept):
    rng = np.random.default_rng(d + iters)
    n = 320
    x = (rng.normal(size=(n, d)) + 3.0).astype(np.float32)
    y = rng.normal(size=(n, 5)).astype(np.float32)
    est = BlockLeastSquaresEstimator(block_size=bs, num_iter=iters, lam=1e-2,
                                     fit_intercept=intercept)
    model = est.fit_dataset(Dataset(x), Dataset(y))
    w, xm, ym = plain_bcd(x, y, bs, 1e-2, iters, intercept)
    assert model.weights.shape == w.shape
    np.testing.assert_allclose(np.asarray(model.weights), w, atol=1e-3 * np.abs(w).max())
    want = (np.pad(x - xm, ((0, 0), (0, w.shape[0] * bs - d))) @ w.reshape(-1, 5)) + ym
    np.testing.assert_allclose(model(Dataset(x)).numpy(), want, atol=2e-3)


def test_the_solver_program_holds_its_input_and_one_block():
    """From ``memory_analysis()`` of the compiled program: the temporaries
    are a block's worth, not a centred copy and a blocked copy of x."""
    n, d, bs = 1024, 4000, 256
    args = (jax.ShapeDtypeStruct((n, d), jnp.float32), jax.ShapeDtypeStruct((n, 10), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32))
    mem = block_ls._bcd_fit.lower(*args, 1e-2, 1, bs, True).compile().memory_analysis()
    if mem is None:
        pytest.skip("this backend gives no memory analysis")
    # a device's numbers (the test mesh shards the rows): the arguments are
    # its rows of x, and input + temporaries stay under two copies of them
    # (a block is 1/16 of x here)
    assert mem.argument_size_in_bytes >= 4 * n * d // jax.device_count()
    assert mem.temp_size_in_bytes < 0.5 * mem.argument_size_in_bytes, mem


def test_solver_span_says_what_it_holds():
    mark = max((r.span_id for r in ledger.recent_spans()), default=0)
    x = np.random.default_rng(0).normal(size=(64, 300)).astype(np.float32)
    y = np.random.default_rng(1).normal(size=(64, 3)).astype(np.float32)
    BlockLeastSquaresEstimator(block_size=128, lam=1e-2).fit_dataset(Dataset(x), Dataset(y))
    (span,) = [r for r in ledger.recent_spans() if r.span_id > mark and r.name == "solver.fit"]
    rows = Dataset(x).array.shape[0]  # padded to the mesh
    assert span.attrs["solver"] == "bcd" and span.attrs["d"] == 300
    assert span.attrs["block_size"] == 128 and span.attrs["blocks"] == 3
    assert span.attrs["held_bytes"] == 4 * rows * (300 + 128)


# ------------------------------------------------------------------- the entry
TOY = dict(num_filters=64, whitener_size=3000, pool_size=6, pool_stride=5, block_size=256,
           lam=0.06, seed=5)
REF_CFG = {**CFG, "zca_eps": 0.1, "whitener_size": 3000, "num_filters": 64, "num_classes": 10,
           "block_size": 256, "num_iter": 1, "lam": 0.06}


@pytest.fixture(scope="module")
def toy_fit():
    from benchmark import datagen

    x, labels = datagen.texture_images(384 + 64, SIZE, 10, seed=11, rows=448)
    train = Dataset(x[:384], name="toy-cifar"), Dataset(labels[:384], name="toy-cifar-labels")
    mark = max((r.span_id for r in ledger.recent_spans()), default=0)
    cfg = RandomPatchCifar.Config(**TOY)
    fitted = RandomPatchCifar.build_scorer(cfg, *train).fit()
    spans = [r for r in ledger.recent_spans() if r.span_id > mark]
    want = reference.fit_and_score(REF_CFG, x[:384], labels[:384], x[384:], seed=5,
                                   feature_rows=32)
    return cfg, train, x[384:], fitted, want, spans


def test_entry_against_the_reference_end_to_end(toy_fit):
    _, _, held, fitted, want, _ = toy_fit
    stages = RandomPatchCifar.fitted_stages(fitted)
    features = stages["PooledConvolver"].apply_dataset(Dataset(held[:32])).numpy()
    close(features, want["features"], 1e-5)
    w0 = np.asarray(stages["BlockLinearMapper"].weights[0])
    assert np.linalg.norm(w0 - want["w0"]) <= 1e-3 * np.linalg.norm(want["w0"])
    close(fitted(Dataset(held)).get().numpy(), want["scores"], 1e-4)


def test_build_and_build_scorer_give_the_same_predictions(toy_fit):
    cfg, train, held, fitted, _, _ = toy_fit
    scores = fitted(Dataset(held)).get().numpy()
    labels = RandomPatchCifar.build(cfg, *train).fit()(Dataset(held)).get().numpy()
    np.testing.assert_array_equal(labels, scores.argmax(axis=1))


def test_entry_spans_and_signatures(toy_fit):
    spans = toy_fit[-1]
    (learn,) = [r for r in spans if r.name == "featurize.filters"]
    assert learn.attrs["patches"] == 3000 and learn.attrs["filters"] == 64
    staged = [r.attrs["node"] for r in spans if r.name == "executor.stage"]
    assert "PooledConvolver" in staged and "Convolver" not in staged
    # a named dataset's filter bank signs by its recipe: nothing is read back
    optimize = [r for r in spans if r.name == "pipeline.optimize" and "sig_by_recipe" in r.attrs]
    assert optimize and all(r.attrs["sig_bytes_hashed"] == 0 for r in optimize)
    assert any(r.attrs["sig_by_recipe"] >= 1 for r in optimize)


def test_an_unnamed_dataset_signs_its_bank_by_content():
    from keystone_tpu.utils import hashing

    x = images(40, size=SIZE)
    conv = RandomPatchCifar.learn_convolver(RandomPatchCifar.Config(**TOY), Dataset(x))
    with hashing.tally_signatures() as tally:
        conv.params()
    assert tally.bytes_hashed >= conv.filters.nbytes and tally.by_recipe == 0
    named = RandomPatchCifar.learn_convolver(RandomPatchCifar.Config(**TOY),
                                             Dataset(x, name="forty"))
    with hashing.tally_signatures() as tally:
        named.params()
    assert tally.bytes_hashed == 0 and tally.by_recipe == 1
    np.testing.assert_array_equal(np.asarray(conv.filters), np.asarray(named.filters))


def test_published_defaults():
    cfg = RandomPatchCifar.Config()
    assert (cfg.num_filters, cfg.patch_size, cfg.whitener_size, cfg.pool_size, cfg.pool_stride,
            cfg.alpha, cfg.zca_eps, cfg.block_size, cfg.num_iter) == (
        10000, 6, 100000, 14, 13, 0.25, 0.1, 4096, 1)


def test_the_second_fit_mints_and_prices_nothing():
    """The sampling rule prices the shared featurizer from SHAPES, its
    filters as arguments: closed over, every fit's new filters were a new
    program (a 2 s compile of every fit on the chip, PR 32).  A second fit
    on other images, so other filters, opens no mint span and asks for no
    compilation of a priced program."""
    from benchmark import compile_log
    from keystone_tpu.workflow import profiling

    cfg = RandomPatchCifar.Config(**{**TOY, "num_filters": 48})
    log = compile_log.CompileLog().install()

    def fit(seed):
        x = images(96, seed=seed)
        labels = np.random.default_rng(seed).integers(0, 10, 96).astype(np.int32)
        mark = max((r.span_id for r in ledger.recent_spans()), default=0)
        before = log.snapshot()
        RandomPatchCifar.build_scorer(cfg, Dataset(x, name=f"mint-{seed}"),
                                      Dataset(labels, name=f"mint-{seed}-labels")).fit()
        mints = [r.attrs["node"] for r in ledger.recent_spans()
                 if r.span_id > mark and r.name == "transformer.jit_mint"]
        return mints, compile_log.delta(log.snapshot(), before)["requests"]

    first, _ = fit(1)
    assert "PooledConvolver" in first
    priced = len(profiling._PRICED)
    second, requests = fit(2)
    assert second == [] and requests == 0 and len(profiling._PRICED) == priced
