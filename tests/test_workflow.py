"""Workflow core tests.

Mirrors the reference's workflow/PipelineSuite.scala, OptimizerSuite.scala,
GraphSuite.scala pattern: toy graphs, side-effect counters in fake nodes to
assert CSE merges and memoized execution counts (SURVEY.md §4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.workflow import (
    Dataset,
    Estimator,
    FusedTransformer,
    LabelEstimator,
    Pipeline,
    Transformer,
    default_optimizer,
    transformer,
)


class CountingDouble(Transformer):
    """x * 2 with an invocation counter; CSE-mergeable."""

    calls = 0

    def params(self):
        return ("double",)

    def apply_one(self, x):
        return x * 2.0

    def apply_batch(self, xs, mask=None):
        CountingDouble.calls += 1
        return xs * 2.0


class AddConst(Transformer):
    def __init__(self, c):
        self.c = float(c)

    def params(self):
        return (self.c,)

    def apply_one(self, x):
        return x + self.c

    def apply_batch(self, xs, mask=None):
        return xs + self.c


class MeanShift(Estimator):
    """Fits the mean; transformer subtracts it."""

    fit_calls = 0

    def params(self):
        return ("meanshift",)

    def fit_arrays(self, x):
        MeanShift.fit_calls += 1
        mu = jnp.mean(x, axis=0)
        return AddConst(0.0) if mu.ndim == 0 else _Sub(mu)


class _Sub(Transformer):
    def __init__(self, mu):
        self.mu = mu

    def apply_batch(self, xs, mask=None):
        return xs - self.mu


class ScaleToLabelMean(LabelEstimator):
    def fit_arrays(self, x, y=None):
        s = jnp.mean(y) / jnp.maximum(jnp.mean(x), 1e-9)
        return AddConst(0.0) if s.ndim != 0 else _Scale(s)


class _Scale(Transformer):
    def __init__(self, s):
        self.s = s

    def apply_batch(self, xs, mask=None):
        return xs * self.s


def test_transformer_eager_apply():
    t = AddConst(1.0)
    ds = Dataset(np.zeros((5, 3), np.float32))
    out = t(ds)
    assert np.allclose(out.numpy(), 1.0)
    assert out.n == 5
    assert float(t(jnp.array(2.0))) == 3.0


def test_lambda_transformer():
    t = transformer(lambda x: x * 3.0, name="Triple")
    ds = Dataset(np.ones((4, 2), np.float32))
    assert np.allclose(t(ds).numpy(), 3.0)
    assert t.label == "Triple"


def test_pipeline_chain_and_apply():
    p = AddConst(1.0) | AddConst(2.0)
    ds = Dataset(np.zeros((6, 2), np.float32))
    out = p(ds).get()
    assert np.allclose(out.numpy(), 3.0)


def test_padding_preserved_through_pipeline():
    # 5 rows on a 4-wide data axis: padded to 8, but numpy() returns 5.
    ds = Dataset(np.arange(10, dtype=np.float32).reshape(5, 2))
    out = (AddConst(1.0) | AddConst(1.0))(ds).get()
    assert out.numpy().shape == (5, 2)


def test_estimator_with_data_and_fit():
    data = np.random.default_rng(0).normal(2.0, 1.0, (32, 4)).astype(np.float32)
    pipe = AddConst(0.0) | MeanShift().with_data(Dataset(data))
    out = pipe(Dataset(data)).get().numpy()
    assert abs(out.mean()) < 1e-5


def test_label_estimator():
    x = np.ones((16, 3), np.float32)
    y = np.full((16, 3), 5.0, np.float32)
    pipe = Pipeline.of(AddConst(0.0)).and_then(
        ScaleToLabelMean(), Dataset(x), Dataset(y)
    )
    out = pipe(Dataset(x)).get().numpy()
    assert np.allclose(out, 5.0, atol=1e-5)


def test_fit_resolves_estimators_and_is_reusable():
    MeanShift.fit_calls = 0
    data = np.random.default_rng(1).normal(3.0, 1.0, (32, 4)).astype(np.float32)
    pipe = AddConst(1.0).and_then(MeanShift(), Dataset(data))
    fitted = pipe.fit()
    out1 = fitted(Dataset(data)).get().numpy()
    out2 = fitted(Dataset(data + 1.0)).get().numpy()
    assert MeanShift.fit_calls == 1
    assert abs(out1.mean()) < 1e-4
    assert abs(out2.mean() - 1.0) < 1e-4


def test_gather_concatenates_features():
    branches = [Pipeline.of(AddConst(float(i))) for i in range(3)]
    p = Pipeline.gather(branches)
    ds = Dataset(np.zeros((4, 2), np.float32))
    out = p(ds).get().numpy()
    assert out.shape == (4, 6)
    assert np.allclose(out[:, 0:2], 0.0)
    assert np.allclose(out[:, 4:6], 2.0)


def test_cse_merges_identical_branches():
    """Two gather branches share an identical CountingDouble prefix; after
    CSE it must execute exactly once (EquivalentNodeMergeRule semantics).

    The merge + single-execution property is asserted on the CSE rule
    directly (the default path's materialization pass ALSO samples the
    graph during optimization — the reference's AutoCacheRule ran the
    same kind of sampling jobs — which would obscure the count)."""
    from keystone_tpu.workflow import GraphExecutor
    from keystone_tpu.workflow.optimizer import EquivalentNodeMergeRule

    CountingDouble.calls = 0
    b1 = CountingDouble() | AddConst(1.0)
    b2 = CountingDouble() | AddConst(2.0)
    p = Pipeline.gather([b1, b2])
    ds = Dataset(np.ones((4, 2), np.float32))
    g = EquivalentNodeMergeRule().apply(p(ds).graph)
    out_expr = GraphExecutor(g).execute(g.sinks[0])
    out = np.asarray(out_expr.dataset.array)
    assert out.shape == (4, 4)
    assert np.allclose(out[:, :2], 3.0)
    assert np.allclose(out[:, 2:], 4.0)
    assert CountingDouble.calls == 1

    # and the full default path still produces the same result
    out2 = p(Dataset(np.ones((4, 2), np.float32))).get().numpy()
    assert np.allclose(out2, out)


def test_fusion_rule_fuses_linear_chains():
    from keystone_tpu.workflow import Graph, StageFusionRule, TransformerOperator

    g = Graph()
    g, src = g.add_source()
    g, n1 = g.add_node(TransformerOperator(AddConst(1.0)), (src,))
    g, n2 = g.add_node(TransformerOperator(AddConst(2.0)), (n1,))
    g, n3 = g.add_node(TransformerOperator(AddConst(3.0)), (n2,))
    g, sink = g.add_sink(n3)
    fused = StageFusionRule().apply(g)
    ops = [op for op in fused.operators.values()]
    assert len(ops) == 1
    assert isinstance(ops[0].transformer, FusedTransformer)
    assert len(ops[0].transformer.stages) == 3


def test_fusion_preserves_no_memoize_flag():
    """Fusing INTO an over-HBM-budget node (no_memoize — recompute per
    consumer) must carry the flag to the fused replacement, or the
    executor pins the very output the cache rule decided the device
    cannot afford."""
    from keystone_tpu.workflow import Graph, StageFusionRule, TransformerOperator

    g = Graph()
    g, src = g.add_source()
    g, n1 = g.add_node(TransformerOperator(AddConst(1.0)), (src,))
    flagged = TransformerOperator(AddConst(2.0))
    flagged.no_memoize = True
    g, n2 = g.add_node(flagged, (n1,))
    g, sink = g.add_sink(n2)
    fused = StageFusionRule().apply(g)
    ops = [op for op in fused.operators.values()]
    assert len(ops) == 1
    assert isinstance(ops[0].transformer, FusedTransformer)
    assert getattr(ops[0], "no_memoize", False) is True


def test_fused_transformer_matches_unfused():
    chain = [AddConst(1.0), CountingDouble(), AddConst(-0.5)]
    fused = FusedTransformer(chain)
    x = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    expect = (x + 1.0) * 2.0 - 0.5
    assert np.allclose(np.asarray(fused.apply_batch(x)), np.asarray(expect))


def test_fit_re_fuses_chains_through_substituted_estimators():
    """fit() must re-run stage fusion AFTER estimator substitution: the
    fitted model's apply node was a DelegatingOperator (unfusable) during
    optimization, so the scoring path would otherwise dispatch one jit
    program per post-model stage (each costing a per-process trace +
    cache load — the fit-overhead split of rounds 1–5, not re-measured)."""
    data = np.random.default_rng(3).normal(1.0, 1.0, (16, 4)).astype(np.float32)
    fitted = (
        AddConst(0.5)
        .and_then(MeanShift(), Dataset(data))
        .and_then(AddConst(1.0))
        .and_then(AddConst(2.0))
    ).fit()
    from keystone_tpu.workflow import TransformerOperator

    fused = [
        op.transformer
        for op in fitted.graph.operators.values()
        if isinstance(op, TransformerOperator)
        and isinstance(op.transformer, FusedTransformer)
    ]
    # the fitted MeanShift + trailing AddConsts collapse into one stage
    assert any(len(f.stages) >= 3 for f in fused)
    out = fitted(Dataset(data)).get().numpy()
    expect = (data + 0.5) - (data + 0.5).mean(axis=0) + 3.0
    assert np.allclose(out, expect, atol=1e-5)


def test_chunked_apply_matches_unchunked(monkeypatch):
    """Row-chunked device applies (shape-stable programs — fit setup
    cost stops scaling with n) must be bit-identical to the whole-batch
    program: plain ops, ragged tails padded to the canonical chunk, and
    ragged (values, mask) producers."""
    monkeypatch.setenv("KEYSTONE_APPLY_CHUNK", "64")
    rng = np.random.default_rng(11)
    x = rng.normal(size=(205, 6)).astype(np.float32)  # 3 full + ragged tail
    op = AddConst(1.5)
    chunked = op.apply_dataset(Dataset(x))
    monkeypatch.setenv("KEYSTONE_APPLY_CHUNK", "0")
    whole = op.apply_dataset(Dataset(x))
    np.testing.assert_array_equal(
        np.asarray(chunked.array), np.asarray(whole.array)
    )
    assert chunked.n == whole.n


def test_chunked_apply_ragged_producer_and_sampler(monkeypatch):
    """SIFT (a (values, mask) producer) and ColumnSampler (global-index
    keys) through the chunked path == unchunked, including the sampler's
    offset-keyed chunks."""
    from keystone_tpu.ops import ColumnSampler, SIFTExtractor

    rng = np.random.default_rng(4)
    imgs = rng.uniform(0, 1, (70, 40, 40)).astype(np.float32)
    sift = SIFTExtractor(step=6, bin_sizes=(4,))
    sampler = ColumnSampler(8, seed=3)

    monkeypatch.setenv("KEYSTONE_APPLY_CHUNK", "32")
    d1 = sift.apply_dataset(Dataset(imgs))
    s1 = sampler.apply_dataset(d1)
    monkeypatch.setenv("KEYSTONE_APPLY_CHUNK", "0")
    d0 = sift.apply_dataset(Dataset(imgs))
    s0 = sampler.apply_dataset(d0)
    np.testing.assert_allclose(
        np.asarray(d1.array), np.asarray(d0.array), atol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(d1.mask), np.asarray(d0.mask)
    )
    np.testing.assert_allclose(
        np.asarray(s1.array), np.asarray(s0.array), atol=1e-6
    )


@pytest.mark.parametrize(
    "n, row_floats, want",
    [
        (64, 6, 4),  # up to _APPLY_MAX_CHUNKS chunks: the canonical chunk
        (205, 6, 16),  # narrow rows: doubled until 16 chunks or fewer remain
        (205, 32, 8),  # ... and no further than the chunk's bytes allow
        (205, 64, 4),  # rows already heavy at the canonical chunk never grow
        (40, 6, 0),  # the whole input is within one chunk's bytes: one apply
    ],
)
def test_default_chunk_grows_for_long_narrow_datasets(monkeypatch, n, row_floats, want):
    """The default chunk doubles for a long dataset of narrow rows (the
    host loop of 2048-row applies idled the chip for a tenth of a
    196,608-row fit), bounded by the chunk's bytes; an input that is
    itself within those bytes is never cut (``iter_row_chunks`` not
    called); the applies stay bit-identical to the whole batch, and a
    forced chunk is taken as is."""
    import importlib

    tr = importlib.import_module("keystone_tpu.workflow.transformer")
    monkeypatch.delenv("KEYSTONE_APPLY_CHUNK", raising=False)
    monkeypatch.setattr(tr, "_apply_chunk_rows", lambda: 4)
    monkeypatch.setattr(tr, "_APPLY_CHUNK_BYTES", 4 * 4 * 64)  # four rows of 64 floats
    x = np.random.default_rng(n).normal(size=(n, row_floats)).astype(np.float32)
    ds = Dataset(x)
    assert tr._chunk_rows_for(ds.array) == want
    seen = []
    real = tr.iter_row_chunks
    monkeypatch.setattr(
        tr, "iter_row_chunks", lambda a, m, c: seen.append(c) or real(a, m, c)
    )
    out = AddConst(1.5).apply_dataset(ds)
    assert seen == ([want] if 0 < want < n else [])
    np.testing.assert_array_equal(np.asarray(out.array)[:n], x + np.float32(1.5))
    monkeypatch.setenv("KEYSTONE_APPLY_CHUNK", "4")
    assert tr._chunk_rows_for(ds.array) == 4


def test_host_transformer_path():
    up = transformer(lambda s: s.upper(), name="Upper", host=True)
    ds = Dataset(["ab", "cd"])
    out = up(ds)
    assert out.items == ["AB", "CD"]


def test_save_load_fitted(tmp_path):
    data = np.random.default_rng(2).normal(1.0, 1.0, (16, 4)).astype(np.float32)
    fitted = AddConst(0.5).and_then(MeanShift(), Dataset(data)).fit()
    path = str(tmp_path / "pipe.pkl")
    fitted.save(path)
    from keystone_tpu.workflow import FittedPipeline

    loaded = FittedPipeline.load(path)
    a = fitted(Dataset(data)).get().numpy()
    b = loaded(Dataset(data)).get().numpy()
    assert np.allclose(a, b)


def test_fitted_read_back_reads_every_fitted_array():
    """read_back() must return one finite scalar per fitted device array
    (the bench fit leg's run-end sync — a REAL device→host transfer,
    robust to fusion wrapping because it walks nested state generically)."""
    data = np.random.default_rng(5).normal(2.0, 1.0, (16, 4)).astype(np.float32)
    fitted = AddConst(0.5).and_then(MeanShift(), Dataset(data)).fit()
    scalars = fitted.read_back()
    assert scalars.size >= 1  # at least the fitted mean
    assert np.all(np.isfinite(scalars))


def test_pipeline_datum_apply():
    p = AddConst(1.0) | AddConst(1.0)
    out = p.apply_datum(jnp.array([1.0, 2.0])).get()
    assert np.allclose(np.asarray(out), [3.0, 4.0])


def test_save_load_fitted_after_apply(tmp_path):
    """Applying a fitted pipeline populates the per-transformer jit cache;
    save/load must still work (the cache is weak+module-level, never
    pickled) and the loaded pipeline must predict identically."""
    from keystone_tpu.models import LinearMapEstimator
    from keystone_tpu.ops import ClassLabelIndicators, LinearRectifier
    from keystone_tpu.workflow.pipeline import FittedPipeline

    rng = np.random.default_rng(0)
    x = Dataset(rng.normal(size=(64, 8)).astype(np.float32))
    y = ClassLabelIndicators(3)(
        Dataset(rng.integers(0, 3, size=(64,)).astype(np.int32))
    )
    fitted = (
        Pipeline.of(LinearRectifier(0.0)).and_then(LinearMapEstimator(lam=0.1), x, y)
    ).fit()
    before = fitted(x).get().numpy()  # populates _JIT_APPLY_CACHE
    path = str(tmp_path / "fp.pkl")
    fitted.save(path)
    loaded = FittedPipeline.load(path)
    np.testing.assert_allclose(loaded(x).get().numpy(), before, atol=1e-6)


# ------------------------------------------------- traced-parameter applies
def test_traced_params_share_one_program_across_instances():
    """Two PCATransformers (different fitted values, same shapes) must
    share ONE compiled program: parameters ride as traced arguments
    (Transformer.traced_attrs), so lowering never embeds fitted device
    arrays as constants — the per-array host read-back and the
    refit-recompiles-everything cache-key hazard (rounds 1–5, not
    re-measured)."""
    import importlib

    from keystone_tpu.models.pca import PCATransformer
    from keystone_tpu.workflow.dataset import Dataset

    # the workflow package re-exports the `transformer` DECORATOR under
    # the module's name, so attribute-style imports get the function
    T = importlib.import_module("keystone_tpu.workflow.transformer")

    # hermetic: earlier tests may have populated PCA entries for other
    # input signatures (bf16 mode, masked applies)
    for k in [k for k in T._SHARED_APPLY_CACHE if k[0][1] is PCATransformer]:
        del T._SHARED_APPLY_CACHE[k]

    rng = np.random.default_rng(0)
    xs = rng.normal(size=(8, 12)).astype(np.float32)
    c1 = rng.normal(size=(12, 3)).astype(np.float32)
    c2 = rng.normal(size=(12, 3)).astype(np.float32)
    m1 = rng.normal(size=(12,)).astype(np.float32)
    p1 = PCATransformer(jnp.asarray(c1), jnp.asarray(m1))
    p2 = PCATransformer(jnp.asarray(c2), None)

    y1 = np.asarray(p1.apply_dataset(Dataset(xs, shard=False)).array)
    y2 = np.asarray(p2.apply_dataset(Dataset(xs, shard=False)).array)
    np.testing.assert_allclose(y1, (xs - m1) @ c1, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y2, xs @ c2, rtol=1e-5, atol=1e-5)

    # one shared wrapper per parameter STRUCTURE (mean present vs absent
    # key separately so a bad instance poisons only its own signature);
    # instances with equal structure share one wrapper and one program
    keys = [k for k in T._SHARED_APPLY_CACHE if k[0][1] is PCATransformer]
    assert len(keys) == 2
    key2 = [k for k in keys if k[2] == T.traced_param_sig(p2)]
    assert len(key2) == 1
    fn = T._SHARED_APPLY_CACHE[key2[0]]
    # a third instance with the SAME structure as p2 must hit the cache,
    # not grow it
    sizes = fn._cache_size()
    p3 = PCATransformer(jnp.asarray(c1), None)
    y3 = np.asarray(p3.apply_dataset(Dataset(xs, shard=False)).array)
    np.testing.assert_allclose(y3, xs @ c1, rtol=1e-5, atol=1e-5)
    assert fn._cache_size() == sizes
    # the process-lifetime template must not pin fitted arrays (review:
    # fingerprint caches ride shallow copies)
    tpl = T.stripped_template(p1)
    assert tpl.components is None and tpl.mean is None
    assert "_fp" not in vars(tpl)


def test_traced_params_refit_uses_new_values():
    """The shared program must read the INSTANCE's current parameters —
    a stale closure constant would silently score with the old fit."""
    from keystone_tpu.models.linear import LinearMapper
    from keystone_tpu.workflow.dataset import Dataset

    xs = np.eye(4, dtype=np.float32)
    w1 = np.full((4, 2), 2.0, np.float32)
    w2 = np.full((4, 2), 5.0, np.float32)
    out1 = np.asarray(LinearMapper(jnp.asarray(w1)).apply_dataset(
        Dataset(xs, shard=False)).array)
    out2 = np.asarray(LinearMapper(jnp.asarray(w2)).apply_dataset(
        Dataset(xs, shard=False)).array)
    np.testing.assert_allclose(out1, xs @ w1)
    np.testing.assert_allclose(out2, xs @ w2)


# ------------------------------------- parameter-free nodes: one program a kind
def _transformer_module():
    import importlib

    # the workflow package re-exports the `transformer` DECORATOR under
    # the module's name, so attribute-style imports get the function
    return importlib.import_module("keystone_tpu.workflow.transformer")


def _shared_entries(cls):
    T = _transformer_module()
    return {k: f for k, f in T._SHARED_APPLY_CACHE.items() if k[0][1] is cls}


def _images(dtype, shape=(2, 32, 32, 3)):
    x = np.random.default_rng(7).integers(0, 256, size=shape)
    return jnp.asarray(x.astype(dtype))


def _parameter_free_cases():
    from keystone_tpu.ops import (
        ClassLabelIndicators,
        GrayScaler,
        LCSExtractor,
        PixelScaler,
        SIFTExtractor,
    )

    return [
        pytest.param(
            lambda: PixelScaler(only_if_integer=True), lambda: PixelScaler(),
            lambda: _images(np.uint8), id="PixelScaler",
        ),
        pytest.param(GrayScaler, None, lambda: _images(np.float32), id="GrayScaler"),
        pytest.param(
            lambda: SIFTExtractor(step=4, bin_sizes=(4,)),
            lambda: SIFTExtractor(step=8, bin_sizes=(4,)),
            lambda: _images(np.float32, (2, 32, 32)), id="SIFTExtractor",
        ),
        pytest.param(
            lambda: LCSExtractor(step=4, subpatch_size=4),
            lambda: LCSExtractor(step=4, subpatch_size=3),
            lambda: _images(np.float32), id="LCSExtractor",
        ),
        pytest.param(
            lambda: ClassLabelIndicators(5), lambda: ClassLabelIndicators(6),
            lambda: jnp.arange(7, dtype=jnp.int32) % 5, id="ClassLabelIndicators",
        ),
    ]


@pytest.mark.parametrize("make, make_other, make_input", _parameter_free_cases())
def test_equal_parameter_free_nodes_share_one_program(make, make_other, make_input):
    """A node that holds no array takes its jitted apply from the
    process-wide cache by (class, params()): a second object with equal
    params() — the next fit's, the next build's — calls the first's
    wrapper and traces nothing; another params() gets its own; and the
    shared program computes what the node's own program did."""
    T = _transformer_module()
    first, second, xs = make(), make(), make_input()
    cls = type(first)
    for k in _shared_entries(cls):  # hermetic: other tests' entries of the class
        del T._SHARED_APPLY_CACHE[k]
    out = first._apply_batch_jitted(xs, None)
    ((key, fn),) = _shared_entries(cls).items()
    assert key[0] == T.share_key(first) == T.share_key(second)
    assert first not in T._JIT_APPLY_CACHE
    traced = fn._cache_size()
    again = second._apply_batch_jitted(xs, None)
    assert list(_shared_entries(cls)) == [key] and fn._cache_size() == traced
    assert second not in T._JIT_APPLY_CACHE
    # bit for bit what a per-object wrapper computed; the eager apply_batch
    # within a rounding (XLA compiles PixelScaler's division to a multiply)
    own = jax.jit(lambda a: first.apply_batch(a, mask=None))(xs)
    eager = first.apply_batch(xs, mask=None)
    for got in (out, again):
        for g, o, e in zip(*map(jax.tree_util.tree_leaves, (got, own, eager))):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(o))
            np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=1e-6, atol=0)
    if make_other is not None:
        other = make_other()
        assert T.share_key(other) != key[0]
        other._apply_batch_jitted(xs, None)
        assert len(_shared_entries(cls)) == 2 and fn._cache_size() == traced


class _Shift(Transformer):
    """Holds an array it does not declare in traced_attrs."""

    def __init__(self, mu, tag="shift"):
        self.mu = mu
        self.tag = tag

    def params(self):
        return (self.tag,)

    def apply_batch(self, xs, mask=None):
        return xs - self.mu


@pytest.mark.parametrize(
    "make",
    [
        pytest.param(lambda: transformer(lambda v: v * 2.0), id="params-None"),
        pytest.param(lambda: _Shift(jnp.arange(6, dtype=jnp.float32)), id="undeclared-array"),
        pytest.param(lambda: _Shift(np.arange(6, dtype=np.float32)), id="undeclared-host-array"),
    ],
)
def test_nodes_without_a_safe_identity_keep_a_program_per_object(make):
    """params() None promises no identity, and an undeclared array would
    be pinned by a process-lifetime template: both stay in the weak
    per-object cache, and the array dies with its node."""
    import gc
    import weakref

    T = _transformer_module()
    xs = jnp.ones((4, 6), jnp.float32)
    shared_before = dict(T._SHARED_APPLY_CACHE)
    a, b = make(), make()
    ya, yb = a._apply_batch_jitted(xs, None), b._apply_batch_jitted(xs, None)
    np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))
    assert T._SHARED_APPLY_CACHE == shared_before
    assert T._JIT_APPLY_CACHE[a] is not T._JIT_APPLY_CACHE[b]
    held = getattr(a, "mu", None)
    dies = weakref.ref(a if held is None or isinstance(held, np.ndarray) else held)
    del a, held, ya
    gc.collect()
    assert dies() is None


def test_matmul_mode_flip_retraces_a_shared_node():
    from keystone_tpu.ops import LCSExtractor
    from keystone_tpu.utils import precision

    T = _transformer_module()
    for k in _shared_entries(LCSExtractor):
        del T._SHARED_APPLY_CACHE[k]
    xs = _images(np.float32)
    with precision.matmul("f32"):
        LCSExtractor(step=4, subpatch_size=4)._apply_batch_jitted(xs, None)
    with precision.matmul("bf16"):
        LCSExtractor(step=4, subpatch_size=4)._apply_batch_jitted(xs, None)
    modes = sorted(k[1][0] for k in _shared_entries(LCSExtractor))
    assert modes == ["bf16", "f32"]


def test_under_specified_params_do_not_share_a_program():
    """params() that leave out an attribute apply_batch reads: the key
    also holds the instance's plain attributes, so each value computes
    its own answer (tests/test_multitenant.py holds the same end to end)."""

    class Leaky(Transformer):
        def __init__(self, scale):
            self.scale = float(scale)

        def params(self):
            return ("leaky",)

        def apply_batch(self, xs, mask=None):
            return xs * self.scale

    xs = jnp.ones((3, 2), jnp.float32)
    assert float(Leaky(2.0)._apply_batch_jitted(xs, None)[0, 0]) == 2.0
    assert float(Leaky(3.0)._apply_batch_jitted(xs, None)[0, 0]) == 3.0
    assert len(_shared_entries(Leaky)) == 2


def test_shared_apply_cache_is_bounded(monkeypatch):
    from keystone_tpu.ops import ClassLabelIndicators

    T = _transformer_module()
    monkeypatch.setattr(T, "_SHARED_APPLY_CACHE", {})
    monkeypatch.setattr(T, "_SHARED_APPLY_MAX", 3)
    xs = jnp.zeros((4,), jnp.int32)
    for k in range(2, 8):
        ClassLabelIndicators(k)._apply_batch_jitted(xs, None)
    assert [key[0][2] for key in T._SHARED_APPLY_CACHE] == [(5,), (6,), (7,)]
    # an evicted live node just mints again
    assert ClassLabelIndicators(2)._apply_batch_jitted(xs, None).shape == (4, 2)
    assert len(T._SHARED_APPLY_CACHE) == 3


def test_degrading_node_shares_its_plain_twins_program():
    from keystone_tpu.ops import LinearRectifier

    T = _transformer_module()
    plain = LinearRectifier(0.25)
    degrading = LinearRectifier(0.25).with_fallback(_Shift(jnp.ones(6)))
    degrading.optional = True
    assert T.share_key(degrading) == T.share_key(plain)
    assert T.stripped_template(degrading).fallback is None
    assert degrading.fallback is not None


def _price_cases():
    from keystone_tpu.ops import GrayScaler, PixelScaler

    return [
        pytest.param(lambda: PixelScaler(only_if_integer=True), np.uint8, True, id="PixelScaler"),
        pytest.param(GrayScaler, np.float32, True, id="GrayScaler"),
        pytest.param(lambda: transformer(lambda v: v * 2.0), np.float32, False, id="params-None"),
        pytest.param(lambda: _Shift(jnp.arange(3, dtype=jnp.float32)), np.float32, False,
                     id="undeclared-array"),
    ]


@pytest.mark.parametrize("make, dtype, kept", _price_cases())
def test_a_nodes_price_is_kept_by_kind_shapes_and_matmul_mode(make, dtype, kept, monkeypatch):
    """The sampling rule's one pricing function (``profiling._priced_by_shape``)
    compiles a node's program at the full batch shape to read XLA's cost
    counters.  The number depends on the node's kind, its input and
    parameter shapes and the matmul mode, so it is kept by those: a new
    object of the kind — the next fit's — is priced from the memo and
    nothing is compiled.  A node that promises no identity (params() None,
    or an array it did not declare) is priced every time, as its apply is
    minted per object."""
    from keystone_tpu.utils import precision
    from keystone_tpu.workflow import profiling

    compiled = []
    orig = profiling.hlo_stage_cost
    monkeypatch.setattr(profiling, "hlo_stage_cost",
                        lambda *a: compiled.append(1) or orig(*a))
    monkeypatch.setattr(profiling, "_PRICED", {})
    at = lambda n: jax.ShapeDtypeStruct((n, 8, 8, 3), dtype)  # noqa: E731
    first, hit = profiling._priced_by_shape(make(), at(64), None)
    assert first is not None and first > 0 and hit is False and len(compiled) == 1
    again, hit = profiling._priced_by_shape(make(), at(64), None)  # a NEW object
    assert again == first and hit is kept and len(compiled) == (1 if kept else 2)
    assert len(profiling._PRICED) == (1 if kept else 0)
    if not kept:
        return
    _, hit = profiling._priced_by_shape(make(), at(128), None)  # another shape
    assert hit is False and len(compiled) == 2
    with precision.matmul("bf16" if precision.matmul_mode() != "bf16" else "f32"):
        _, hit = profiling._priced_by_shape(make(), at(64), None)  # another mode
        assert hit is False and len(compiled) == 3
    assert profiling._priced_by_shape(make(), at(64), None) == (first, True)
    assert len(profiling._PRICED) == 3 and len(compiled) == 3


def test_the_price_memo_is_bounded(monkeypatch):
    from keystone_tpu.ops import ClassLabelIndicators
    from keystone_tpu.workflow import profiling

    monkeypatch.setattr(profiling, "_PRICED", {})
    monkeypatch.setattr(profiling, "_PRICED_MAX", 3)
    labels = jax.ShapeDtypeStruct((16,), jnp.int32)
    for k in range(2, 8):
        profiling._priced_by_shape(ClassLabelIndicators(k), labels, None)
    assert [key[0][2] for key in profiling._PRICED] == [(5,), (6,), (7,)]
    # an evicted kind is just priced again
    assert profiling._priced_by_shape(ClassLabelIndicators(2), labels, None)[1] is False
    assert len(profiling._PRICED) == 3


def test_shared_sift_program_is_the_per_object_wrappers_text():
    """The lowered module of the shared apply is, text for text, what
    the per-object ``(xs, mask)`` wrapper lowered to, so the persistent
    compile cache a process warmed before this change still answers.
    The digest is the parent commit's (d6c4605, jax 0.9.0), taken there
    from ``_JIT_APPLY_CACHE``'s wrapper at this shape."""
    import hashlib

    from keystone_tpu.ops import SIFTExtractor

    T = _transformer_module()
    node = SIFTExtractor(step=4, bin_sizes=(4,))
    xs = jnp.zeros((2, 32, 32), jnp.float32)
    node._apply_batch_jitted(xs, None)
    fn = next(
        f for k, f in _shared_entries(SIFTExtractor).items() if k[0] == T.share_key(node)
    )
    text = fn.lower({}, xs, None).as_text()
    plain = T.jit_named(lambda a, m: node.apply_batch(a, mask=m), [node])
    assert text == plain.lower(xs, None).as_text()
    assert text.split("module @", 1)[1].split(" ", 1)[0] == "jit_apply_SIFTExtractor"
    if jax.__version__ == "0.9.0":
        assert hashlib.sha256(text.encode()).hexdigest() == (
            "b1789e97c86da4020c4b731f42ac19791d5e996a1393eaa47c49616802f5f203"
        )


def test_fused_chain_shares_program_across_instances():
    """Two FusedTransformer instances with identical stage identities
    (class+params) share one compiled chain (optimizer._FUSED_SHARED_CACHE)."""
    from keystone_tpu.ops.stats import NormalizeRows, SignedHellingerMapper
    from keystone_tpu.workflow import optimizer as O

    O._FUSED_SHARED_CACHE.clear()  # hermetic: identify OUR chain's entry
    f1 = O.FusedTransformer([SignedHellingerMapper(), NormalizeRows()])
    f2 = O.FusedTransformer([SignedHellingerMapper(), NormalizeRows()])
    xs = jnp.asarray(np.random.default_rng(1).normal(size=(4, 6)), jnp.float32)
    y1 = f1.apply_batch(xs)
    target = [
        k
        for k, v in O._FUSED_SHARED_CACHE.items()
        if callable(v) and k[0][0][1] is SignedHellingerMapper
    ]
    assert target, "fused chain did not take the shared path"
    fn = O._FUSED_SHARED_CACHE[target[0]]
    size = fn._cache_size()
    y2 = f2.apply_batch(xs)
    assert fn._cache_size() == size  # second instance reused the program
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-6)
    # and the executor path must ride the same shared program — the
    # per-instance outer jit would otherwise inline it with the stage
    # parameters embedded as constants (self_jitted bypass)
    from keystone_tpu.workflow.dataset import Dataset

    y3 = f2.apply_dataset(Dataset(np.asarray(xs), shard=False)).array
    assert fn._cache_size() == size
    np.testing.assert_allclose(np.asarray(y3), np.asarray(y1), rtol=1e-6)


def test_fused_chain_with_traced_stage_params():
    """A fused chain containing a traced_attrs stage (PCA) passes the
    stage's arrays as arguments and still matches the eager compose."""
    from keystone_tpu.models.pca import PCATransformer
    from keystone_tpu.ops.stats import NormalizeRows
    from keystone_tpu.workflow import optimizer as O

    rng = np.random.default_rng(2)
    xs = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    comp = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
    pca = PCATransformer(comp, None)
    fused = O.FusedTransformer([pca, NormalizeRows()])
    got = np.asarray(fused.apply_batch(xs))
    want = np.asarray(NormalizeRows().apply_batch(pca.apply_batch(xs)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
