"""Auxiliary subsystem tests: profiling auto-cache, saved-state reload,
DOT viz, solver checkpointing, multihost helpers, debug, interop."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.workflow import Dataset, Pipeline, Transformer


class Expensive(Transformer):
    """Side-effect execution counter (the reference's fake-node pattern).

    Counts via ``jax.debug.callback`` so every EXECUTION of the compiled
    program bumps the counter — node-level execution runs through a
    jitted wrapper now, where a bare Python increment would fire once at
    trace time regardless of how many times the program runs.  Read the
    count through :func:`expensive_calls` (callbacks land async)."""

    calls = 0

    def __init__(self, tag: str):
        self.tag = tag

    def params(self):
        return (self.tag,)

    @staticmethod
    def _bump():
        Expensive.calls += 1

    def apply_batch(self, xs, mask=None):
        import jax

        jax.debug.callback(Expensive._bump)
        return xs * 2.0


def expensive_calls() -> int:
    """Expensive.calls after flushing pending host callbacks."""
    import jax

    jax.effects_barrier()
    return Expensive.calls


class AddC(Transformer):
    def __init__(self, c):
        self.c = float(c)

    def params(self):
        return (self.c,)

    def apply_batch(self, xs, mask=None):
        return xs + self.c


def test_profiling_collects_node_costs():
    from keystone_tpu.workflow.profiling import profile_graph

    p = Pipeline.gather(
        [Expensive("x") | AddC(1.0), Expensive("x") | AddC(2.0)]
    )
    lazy = p(Dataset(np.ones((64, 8), np.float32)))
    profiles = profile_graph(lazy.graph, sample_size=16)
    assert len(profiles) >= 2
    assert all(pr.output_bytes > 0 for pr in profiles.values())
    assert all(pr.scale >= 1.0 for pr in profiles.values())


def test_profiling_autocache_rule_within_budget():
    from keystone_tpu.workflow.optimizer import EquivalentNodeMergeRule
    from keystone_tpu.workflow.profiling import ProfilingAutoCacheRule
    from keystone_tpu.workflow.transformer import Cacher
    from keystone_tpu.workflow import TransformerOperator

    p = Pipeline.gather([Expensive("x") | AddC(1.0), Expensive("x") | AddC(2.0)])
    lazy = p(Dataset(np.ones((64, 8), np.float32)))
    g = EquivalentNodeMergeRule().apply(lazy.graph)
    g2 = ProfilingAutoCacheRule(budget_bytes=1 << 30, sample_size=16).apply(g)
    cachers = [
        op
        for op in g2.operators.values()
        if isinstance(op, TransformerOperator) and isinstance(op.transformer, Cacher)
    ]
    assert len(cachers) == 1  # the shared Expensive output got pinned


def test_profile_graph_targets_restricts_profiled_nodes():
    """targets= limits profiling to the given nodes (ancestors still
    execute, memoized, to produce their inputs) — the cache rule passes
    the shared set here so the sampling pass doesn't price (or run)
    subgraphs the placement decision never reads."""
    from keystone_tpu.workflow.profiling import profile_graph

    p = Pipeline.gather([Expensive("x") | AddC(1.0), Expensive("x") | AddC(2.0)])
    lazy = p(Dataset(np.ones((64, 8), np.float32)))
    all_profiles = profile_graph(lazy.graph, sample_size=16)
    target = next(iter(all_profiles))
    only = profile_graph(lazy.graph, sample_size=16, targets=frozenset([target]))
    assert set(only) == {target}
    assert only[target].output_bytes == all_profiles[target].output_bytes


def test_profiling_autocache_skips_sampling_without_shared_nodes():
    """A linear pipeline has nothing to place — the rule must return the
    graph untouched WITHOUT running the sampled profiling pass (it was
    ~60% of north-star fit wall-clock before r4's shared-only restriction)."""
    import keystone_tpu.workflow.profiling as prof_mod
    from keystone_tpu.workflow.profiling import ProfilingAutoCacheRule

    calls = {"n": 0}
    orig = prof_mod.profile_graph

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    p = Expensive("lin") | AddC(1.0) | AddC(2.0)
    lazy = p(Dataset(np.ones((32, 4), np.float32)))
    prof_mod.profile_graph = counting
    try:
        g2 = ProfilingAutoCacheRule(budget_bytes=1 << 30, sample_size=16).apply(
            lazy.graph
        )
    finally:
        prof_mod.profile_graph = orig
    assert calls["n"] == 0
    assert g2.operators.keys() == lazy.graph.operators.keys()


def test_profiling_autocache_over_budget_sets_no_memoize():
    from keystone_tpu.workflow.optimizer import EquivalentNodeMergeRule
    from keystone_tpu.workflow.profiling import ProfilingAutoCacheRule
    from keystone_tpu.workflow import GraphExecutor, TransformerOperator

    Expensive.calls = 0
    p = Pipeline.gather([Expensive("x") | AddC(1.0), Expensive("x") | AddC(2.0)])
    lazy = p(Dataset(np.ones((64, 8), np.float32)))
    g = EquivalentNodeMergeRule().apply(lazy.graph)
    g2 = ProfilingAutoCacheRule(budget_bytes=1, sample_size=16).apply(g)
    flagged = [
        op
        for op in g2.operators.values()
        if getattr(op, "no_memoize", False)
    ]
    assert len(flagged) == 1
    # executing recomputes the shared node once per consumer
    Expensive.calls = 0
    ex = GraphExecutor(g2)
    ex.execute(g2.sinks[0])
    assert expensive_calls() == 2


def test_saved_state_roundtrip(tmp_path):
    from keystone_tpu.workflow.optimizer import Optimizer, Once, RuleBatch
    from keystone_tpu.workflow.state import SavedStateLoadRule, save_pipeline_state

    state_dir = str(tmp_path / "state")
    data = Dataset(np.ones((16, 4), np.float32), name="train-data")
    p = Pipeline.of(AddC(1.0)) | AddC(2.0)
    lazy = p(data)
    saved = save_pipeline_state(lazy, state_dir)
    assert saved >= 1

    # a fresh identical pipeline over the SAME named dataset reloads
    Expensive.calls = 0
    data2 = Dataset(np.ones((16, 4), np.float32), name="train-data")
    lazy2 = (Pipeline.of(AddC(1.0)) | AddC(2.0))(data2)
    g = SavedStateLoadRule(state_dir).apply(lazy2.graph)
    from keystone_tpu.workflow import DatasetOperator, GraphExecutor

    ds_ops = [op for op in g.operators.values() if isinstance(op, DatasetOperator)]
    assert len(ds_ops) >= 1
    out = GraphExecutor(g).execute(g.sinks[0])
    np.testing.assert_allclose(out.dataset.numpy(), 4.0)


def test_to_dot():
    from keystone_tpu.workflow.viz import to_dot

    p = AddC(1.0) | AddC(2.0)
    dot = to_dot(p.graph)
    assert dot.startswith("digraph") and "AddC" in dot and "->" in dot


def test_block_ls_fit_checkpointed_resumes(tmp_path):
    from keystone_tpu.models import BlockLeastSquaresEstimator

    rng = np.random.default_rng(0)
    x = rng.normal(size=(48, 6)).astype(np.float32)
    y = rng.normal(size=(48, 2)).astype(np.float32)
    est = BlockLeastSquaresEstimator(block_size=3, num_iter=6, lam=0.1)
    ckpt = str(tmp_path / "ck")
    m1 = est.fit_checkpointed(Dataset(x), Dataset(y), ckpt)
    # resume from final state: must produce identical weights without work
    m2 = est.fit_checkpointed(Dataset(x), Dataset(y), ckpt)
    np.testing.assert_allclose(
        np.asarray(m1.flat_weights), np.asarray(m2.flat_weights), atol=1e-6
    )
    # and equals the un-checkpointed fit
    m3 = est.fit_arrays(x, y)
    np.testing.assert_allclose(
        np.asarray(m1.flat_weights), np.asarray(m3.flat_weights), atol=1e-4
    )
    # partial checkpoint resumes to the same answer as a full run
    import numpy as _np

    with _np.load(os.path.join(ckpt, "bcd_epoch.npz")) as z:
        assert int(z["epoch"]) == 5


def test_multihost_helpers_single_process(mesh):
    from keystone_tpu.parallel import multihost

    m = multihost.hybrid_mesh(model_parallelism=2)
    assert m.shape["data"] * m.shape["model"] == 8
    sl = multihost.process_batch_slice(100)
    assert sl == slice(0, 100)
    d = multihost.make_global_dataset(np.ones((8, 2), np.float32))
    assert d.numpy().shape == (8, 2)


def test_debug_helpers():
    from keystone_tpu.utils.debug import assert_all_finite, checked

    assert_all_finite(np.ones(3))
    with pytest.raises(FloatingPointError):
        assert_all_finite(np.array([1.0, np.nan]))

    def f(x):
        return jnp.log(x)

    import jax

    with pytest.raises(Exception):
        checked(f)(jnp.asarray(-1.0))


def test_interop():
    import torch

    from keystone_tpu.utils.interop import to_jax, to_numpy, to_torch

    t = torch.ones(3, 2)
    j = to_jax(t)
    assert j.shape == (3, 2)
    back = to_torch(j)
    assert back.shape == (3, 2)
    import scipy.sparse as sp

    s = sp.csr_matrix(np.eye(3, dtype=np.float32))
    assert to_jax(s).shape == (3, 3)
    assert to_numpy(t).shape == (3, 2)


def test_ngram_indexer():
    from keystone_tpu.ops.nlp import NGramIndexer

    idx = NGramIndexer()
    k1 = idx.pack(("the", "cat"))
    k2 = idx.pack(("the", "dog"))
    assert k1 != k2
    assert idx.pack(("the", "cat")) == k1  # deterministic
    assert idx.unpack(k1, 2) == ("the", "cat")


def test_image_utils():
    from keystone_tpu.utils.image import crop, flip_horizontal, pixel_stats

    imgs = jnp.asarray(np.arange(2 * 4 * 4 * 3, dtype=np.float32).reshape(2, 4, 4, 3))
    c = crop(imgs, 1, 1, 2, 2)
    assert c.shape == (2, 2, 2, 3)
    f = flip_horizontal(imgs)
    np.testing.assert_allclose(np.asarray(f[:, :, 0]), np.asarray(imgs[:, :, -1]))
    mean, std = pixel_stats(imgs)
    assert mean.shape == (3,)


def test_block_kernel_matrix():
    from keystone_tpu.models import GaussianKernelGenerator
    from keystone_tpu.models.kernel_matrix import BlockKernelMatrix

    rng = np.random.default_rng(0)
    x = rng.normal(size=(40, 5)).astype(np.float32)
    kern = GaussianKernelGenerator(0.3)
    bk = BlockKernelMatrix(kern, x, block_size=16)
    full = np.asarray(kern(jnp.asarray(x), jnp.asarray(x)))
    np.testing.assert_allclose(np.asarray(bk.block(0, 1)), full[:16, 16:32], atol=1e-6)
    np.testing.assert_allclose(np.asarray(bk.column_block(2)), full[:, 32:], atol=1e-6)
    v = rng.normal(size=(40, 2)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(bk.matvec(jnp.asarray(v))), full @ v, atol=1e-4)
    _ = bk.block(0, 1)  # cached path


def test_pipeline_env_state_dir_roundtrip(tmp_path):
    from keystone_tpu.workflow import Pipeline, PipelineEnv
    from keystone_tpu.workflow.state import save_pipeline_state

    state = str(tmp_path / "env-state")
    data = Dataset(np.full((8, 3), 2.0, np.float32), name="env-train")
    pipe = Expensive("env") | AddC(1.0)
    save_pipeline_state(pipe(data), state)
    try:
        PipelineEnv.state_dir = state
        Expensive.calls = 0  # reload must NOT recompute the prefix
        out = (Expensive("env") | AddC(1.0))(
            Dataset(np.full((8, 3), 2.0, np.float32), name="env-train")
        ).get()
        np.testing.assert_allclose(out.numpy(), 5.0)
        assert expensive_calls() == 0
    finally:
        PipelineEnv.state_dir = None


def test_pipeline_env_user_optimizer_not_overwritten(tmp_path):
    from keystone_tpu.workflow import Optimizer, PipelineEnv

    custom = Optimizer([])
    try:
        PipelineEnv.set_optimizer(custom)
        PipelineEnv.state_dir = str(tmp_path)
        assert PipelineEnv.get_optimizer() is custom
    finally:
        PipelineEnv.set_optimizer(None)
        PipelineEnv.state_dir = None


def test_pipeline_env_direct_assignment_honored(tmp_path):
    # assigning the public attribute (without set_optimizer) must survive
    # a later state_dir change — the state-dir wiring only replaces
    # optimizers it built itself
    from keystone_tpu.workflow import Optimizer, PipelineEnv

    custom = Optimizer([])
    try:
        PipelineEnv.optimizer = custom
        PipelineEnv.state_dir = str(tmp_path)
        assert PipelineEnv.get_optimizer() is custom
    finally:
        PipelineEnv.set_optimizer(None)
        PipelineEnv.state_dir = None


def test_cached_fingerprint_invalidates_on_reassignment():
    # a transformer whose weights are swapped must change identity, or
    # CSE/saved-state rules would alias nodes with different weights
    import jax.numpy as jnp

    from keystone_tpu.ops import Convolver

    f1 = jnp.ones((2, 3, 3, 1), jnp.float32)
    f2 = jnp.zeros((2, 3, 3, 1), jnp.float32)
    conv = Convolver(f1)
    fp1 = conv.params()
    conv.filters = f2
    fp2 = conv.params()
    assert fp1 != fp2
    # and same content produces the same fingerprint across instances
    assert Convolver(f1).params() == Convolver(jnp.ones((2, 3, 3, 1))).params()


def test_pipeline_env_inplace_extension_honored(tmp_path):
    # extending the auto-built default in place is a user customization;
    # a later state_dir change must not silently rebuild over it
    from keystone_tpu.workflow import PipelineEnv
    from keystone_tpu.workflow.optimizer import Once, RuleBatch

    try:
        PipelineEnv.set_optimizer(None)
        opt = PipelineEnv.get_optimizer()
        opt.batches.append(RuleBatch("custom", Once(), []))
        PipelineEnv.state_dir = str(tmp_path)
        assert PipelineEnv.get_optimizer() is opt
    finally:
        PipelineEnv.set_optimizer(None)
        PipelineEnv.state_dir = None


def test_hlo_stage_cost_counts_matmul_flops():
    import jax

    from keystone_tpu.workflow.profiling import hlo_stage_cost

    a = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 64), jnp.float32)
    cost = hlo_stage_cost(lambda x, y: x @ y, a, b)
    assert cost is not None
    # 2*m*n*k flops, allow XLA accounting slack
    assert cost["flops"] >= 256 * 128 * 64
    assert cost["seconds_est"] > 0


def test_profile_graph_static_cost_ranks_heavier_node_higher():
    from keystone_tpu.workflow import transformer
    from keystone_tpu.workflow.profiling import profile_graph

    big = transformer(lambda x: (x @ jnp.ones((64, 512))) @ jnp.ones((512, 8)))
    small = transformer(lambda x: x[:8] * 2.0)  # per-example, vmapped
    p = Pipeline.gather([Pipeline.of(big), Pipeline.of(small)])
    lazy = p(Dataset(np.ones((2048, 64), np.float32)))
    profiles = profile_graph(lazy.graph, sample_size=16, static_cost=True)
    static = {
        n: pr for n, pr in profiles.items() if pr.hlo_seconds is not None
    }
    assert len(static) >= 2
    times = sorted(pr.hlo_seconds for pr in static.values())
    assert times[-1] > times[0]  # the matmul chain prices above the slice


@pytest.mark.parametrize(
    "case", ["env_dir_left_alone", "unset_goes_to_checkout", "off_switch"]
)
def test_compilation_cache_enable_and_disable(tmp_path, monkeypatch, case):
    """ONE way to place the cache: ``JAX_COMPILATION_CACHE_DIR``.  Set, it
    is left alone (no code path sets another directory); unset, the cache
    goes to the fixed directory in the checkout — never ``~`` or a
    temporary name; ``KEYSTONE_COMPILE_CACHE=off`` only switches it off."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from keystone_tpu.utils import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.delenv("KEYSTONE_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        if case == "env_dir_left_alone":
            # jax reads the variable into its config at import; mimic that
            d = str(tmp_path / "from-env")
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
            jax.config.update("jax_compilation_cache_dir", d)
            assert compile_cache.enable_compilation_cache() == d
            assert jax.config.jax_compilation_cache_dir == d
            assert not os.path.exists(d)  # jax makes it on first write, not us
        elif case == "unset_goes_to_checkout":
            jax.config.update("jax_compilation_cache_dir", None)
            got = compile_cache.enable_compilation_cache()
            assert got == compile_cache.CACHE_DIR == os.path.join(repo, ".jax_cache")
            assert os.path.isdir(got)
            assert jax.config.jax_compilation_cache_dir == got
            # the same path every time: it is part of the cache key
            assert compile_cache.enable_compilation_cache() == got
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        else:
            jax.config.update("jax_compilation_cache_dir", None)
            monkeypatch.setenv("KEYSTONE_COMPILE_CACHE", "off")
            assert compile_cache.enable_compilation_cache() is None
            assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
        cc.reset_cache()


def test_default_optimizer_uses_profiled_materialization():
    """round-1 review item 8: the HBM-budgeted profiling cache rule is the
    DEFAULT materialization pass, with the budget read from the device."""
    from keystone_tpu.workflow.optimizer import (
        ProfiledMaterializeRule,
        default_optimizer,
    )
    from keystone_tpu.workflow.profiling import device_hbm_budget

    import keystone_tpu.workflow.profiling as prof_mod

    opt = default_optimizer()
    rules = [r for b in opt.batches for r in b.rules]
    assert any(isinstance(r, ProfiledMaterializeRule) for r in rules)
    assert device_hbm_budget() > 0

    # on a shared-prefix graph the default pass must place a Cacher VIA
    # THE PROFILED PATH — the structural fallback also places one, so
    # record that the profiling rule actually ran and did not fall back
    from keystone_tpu.workflow import Cacher, TransformerOperator

    ran = []
    orig = prof_mod.ProfilingAutoCacheRule.apply

    def counting_apply(self, graph):
        out = orig(self, graph)
        ran.append(True)
        return out

    prof_mod.ProfilingAutoCacheRule.apply = counting_apply
    try:
        b1 = Pipeline.of(AddC(1.0)) | AddC(2.0)
        b2 = Pipeline.of(AddC(1.0)) | AddC(3.0)
        p = Pipeline.gather([b1, b2])
        lazy = p(Dataset(np.ones((16, 4), np.float32)))
        g = opt.execute(lazy.graph)
    finally:
        prof_mod.ProfilingAutoCacheRule.apply = orig
    assert ran, "profiled materialization fell back to the structural rule"
    assert any(
        isinstance(op, TransformerOperator) and isinstance(op.transformer, Cacher)
        for op in g.operators.values()
    )


def _two_branch_fit():
    """Fitted: Expensive -> {AddC(1), AddC(2)} -> gather -> combine -> block
    least squares.  CSE merges the two Expensive nodes and the fit's
    optimizer places a Cacher behind the merged one; the fitted graph
    keeps it."""
    from keystone_tpu.models import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import VectorCombiner

    rng = np.random.default_rng(0)
    x = rng.normal(size=(96, 8)).astype(np.float32)
    y = np.where(rng.random((96, 3)) < 0.3, 1.0, -1.0).astype(np.float32)
    feat = Pipeline.gather(
        [Expensive("two") | AddC(1.0), Expensive("two") | AddC(2.0)]
    ) | VectorCombiner()
    fitted = feat.and_then(
        BlockLeastSquaresEstimator(block_size=8, num_iter=1, lam=1e-3), Dataset(x), Dataset(y)
    ).fit()
    return fitted, rng.normal(size=(32, 8)).astype(np.float32)


def _labels(g, flag=None):
    """Labels of g's transformer nodes (those whose operator has ``flag``)."""
    from keystone_tpu.workflow import TransformerOperator

    return sorted(
        op.label() for op in g.operators.values()
        if isinstance(op, TransformerOperator) and (flag is None or getattr(op, flag, False))
    )


def _feeds(g, label):
    """Labels of the nodes that read a ``label`` node's output."""
    return sorted(
        g.operators[d].label()
        for n, op in g.operators.items() if op.label() == label
        for d in g.dependents(n) if d in g.operators
    )


@pytest.mark.parametrize(
    "case", ["one_cacher", "second_call_is_quiet", "scores_of_the_structural_rule",
             "new_fan_out", "over_budget"]
)
def test_a_placed_cacher_is_a_decision_already_made(case, monkeypatch):
    """A fitted pipeline's graph keeps the Cacher its fit placed, and every
    scoring call optimizes that graph again.  The node with two consumers is
    then the Cacher itself: a barrier, not a candidate (``optimizer.
    needs_barrier``, the one test of both materialization rules).  So the
    call's pass finds nothing to place and leaves before it slices the
    input, runs a sample, waits for it and compiles a price — while fan-out
    that is NEW in the graph it sees is still profiled and placed, and the
    over-budget ranking and demotion are what they were."""
    import keystone_tpu.workflow.profiling as prof_mod
    from benchmark import compile_log
    from keystone_tpu.obs import ledger
    from keystone_tpu.workflow import PipelineEnv
    from keystone_tpu.workflow.optimizer import AutoMaterializeRule, default_optimizer

    sampled = []
    orig = prof_mod.profile_graph
    monkeypatch.setattr(
        prof_mod, "profile_graph", lambda *a, **k: sampled.append(1) or orig(*a, **k)
    )
    fitted, held = _two_branch_fit()
    assert sampled == [1] and _feeds(fitted.graph, "Expensive") == ["Cacher"]

    def scoring_graph():
        return PipelineEnv.get_optimizer().execute(fitted(Dataset(held)).graph)

    if case == "one_cacher":
        g = scoring_graph()
        assert _labels(g).count("Cacher") == 1 and len(g.operators) == len(fitted.graph.operators) + 1
        assert _feeds(g, "Cacher") == ["AddC", "AddC"] and sampled == [1]
    elif case == "second_call_is_quiet":
        fitted(Dataset(held)).get().numpy()
        log = compile_log.CompileLog().install()
        mark = max(r.span_id for r in ledger.recent_spans())
        before = log.snapshot()
        fitted(Dataset(held)).get().numpy()
        asked = compile_log.delta(log.snapshot(), before)
        recs = [r for r in ledger.recent_spans() if r.span_id > mark]
        assert (asked["requests"], asked["backend_compiles"]) == (0, 0)
        assert not [r for r in recs if r.name == "transformer.jit_mint"]
        (optimize,) = [r for r in recs if r.name == "pipeline.optimize"]
        rules = [r for r in recs if r.parent_id == optimize.span_id]
        assert {r.name for r in rules} == {"optimizer.rule"}
        # no rule ran a stage, sliced or moved data: nothing was launched in it
        assert not [r for r in recs if r.parent_id in {x.span_id for x in rules}]
        assert sampled == [1]  # the fit's; neither call sliced and ran a sample
    elif case == "scores_of_the_structural_rule":
        profiled = fitted(Dataset(held)).get().numpy()
        structural = default_optimizer()
        for batch in structural.batches:
            if batch.name == "materialize":
                batch.rules = [AutoMaterializeRule()]
        try:
            PipelineEnv.set_optimizer(structural)
            assert _labels(scoring_graph()).count("Cacher") == 1
            np.testing.assert_array_equal(fitted(Dataset(held)).get().numpy(), profiled)
        finally:
            PipelineEnv.set_optimizer(None)
    elif case == "new_fan_out":
        # a lazy input whose own node feeds the fitted pipeline AND a
        # second consumer: fan-out the fit never saw
        lazy = Pipeline.of(AddC(5.0))(Dataset(held))
        both = Pipeline.gather([fitted, Pipeline.of(AddC(7.0))])(lazy)
        g = PipelineEnv.get_optimizer().execute(both.graph)
        assert sampled == [1, 1]  # the fit's, and this graph's new shared node
        # the fit's Cacher as it was, and one behind the new shared node
        assert _feeds(g, "Cacher") == ["AddC", "AddC", "AddC", "Expensive"]
        np.testing.assert_array_equal(
            both.get().numpy()[:, :3], fitted(Dataset(held + 5.0)).get().numpy()
        )
    else:
        # two shared nodes, room for one: the matmul chain saves more
        # compute per byte pinned than the add, so it gets the Cacher and
        # the add is demoted to recompute per consumer
        class Heavy(Transformer):
            def params(self):
                return ("heavy",)

            def apply_batch(self, xs, mask=None):
                return (xs @ jnp.ones((8, 512))) @ jnp.ones((512, 8))

        n = 4096
        lazy = Pipeline.gather([
            Heavy() | AddC(1.0), Heavy() | AddC(2.0),
            AddC(9.0) | AddC(3.0), AddC(9.0) | AddC(4.0),
        ])(Dataset(np.ones((n, 8), np.float32)))
        monkeypatch.setenv("KEYSTONE_HBM_BUDGET_BYTES", str(2 * (n * 8 * 4) + 2))
        g = default_optimizer().execute(lazy.graph)
        assert _feeds(g, "Heavy") == ["Cacher"] and _labels(g).count("Cacher") == 1
        assert _labels(g, flag="no_memoize") == ["AddC"]
        assert prof_mod.last_footprint == {
            "shared_bytes": 2 * n * 8 * 4, "budget_bytes": n * 8 * 4 + 1,
        }


def test_saved_state_orbax_mesh_mismatch_restores_replicated(tmp_path):
    """A prefix saved (mesh-padded) on one mesh must still restore under a
    mesh whose 'data' axis doesn't divide the saved leading dim — via the
    host-restore + re-shard fallback, not silent recompute (ADVICE r1)."""
    import jax

    from keystone_tpu.parallel import default_mesh, use_mesh
    from keystone_tpu.workflow.state import (
        load_dataset_orbax,
        save_dataset_orbax,
    )

    path = str(tmp_path / "mismatch.orbax")
    # n=6 padded for the session mesh (data=4) -> 8 rows
    ds = Dataset(np.arange(6 * 3, dtype=np.float32).reshape(6, 3), n=6)
    save_dataset_orbax(ds, path)
    saved_rows = ds.array.shape[0]

    # ragged dataset: the mask must be re-padded in lockstep with the array
    ragged_path = str(tmp_path / "mismatch-ragged.orbax")
    base = Dataset(np.ones((6, 5, 2), np.float32), n=6)  # padded to 8 rows
    rag = base.with_array(
        base.array, mask=jnp.ones((base.array.shape[0], 5), bool)
    )
    save_dataset_orbax(rag, ragged_path)

    three = default_mesh(jax.devices("cpu")[:3], model_parallelism=1)
    assert saved_rows % 3 != 0  # the mismatch this test is about
    with use_mesh(three):
        restored = load_dataset_orbax(path)
        assert restored.n == 6
        np.testing.assert_allclose(
            restored.numpy(), np.arange(6 * 3, dtype=np.float32).reshape(6, 3)
        )
        # re-sharded for the CURRENT mesh: leading dim divisible by 3
        assert restored.array.shape[0] % 3 == 0

        rrag = load_dataset_orbax(ragged_path)
        assert rrag.mask is not None
        assert rrag.mask.shape[0] == rrag.array.shape[0]  # aligned padding
        assert rrag.array.shape[0] % 3 == 0


def test_saved_state_orbax_backend_roundtrip(tmp_path):
    """Tensorstore-backed stage checkpoints (SURVEY §5): save with
    backend="orbax", reload via the same SavedStateLoadRule."""
    from keystone_tpu.workflow import DatasetOperator, GraphExecutor
    from keystone_tpu.workflow.state import SavedStateLoadRule, save_pipeline_state

    state_dir = str(tmp_path / "orbax-state")
    data = Dataset(np.full((16, 4), 3.0, np.float32), name="orbax-train")
    lazy = (Pipeline.of(AddC(1.0)) | AddC(2.0))(data)
    saved = save_pipeline_state(lazy, state_dir, backend="orbax")
    assert saved >= 1
    assert any(f.endswith(".orbax") for f in os.listdir(state_dir))

    lazy2 = (Pipeline.of(AddC(1.0)) | AddC(2.0))(
        Dataset(np.full((16, 4), 3.0, np.float32), name="orbax-train")
    )
    g = SavedStateLoadRule(state_dir).apply(lazy2.graph)
    assert any(isinstance(op, DatasetOperator) for op in g.operators.values())
    out = GraphExecutor(g).execute(g.sinks[0])
    np.testing.assert_allclose(out.dataset.numpy(), 6.0)

    with pytest.raises(ValueError, match="unknown state backend"):
        save_pipeline_state(lazy, state_dir, backend="bogus")

    # newest save wins: re-saving with npz must remove the orbax sibling
    save_pipeline_state(lazy, state_dir, backend="npz")
    assert not any(f.endswith(".orbax") for f in os.listdir(state_dir))
    assert any(f.endswith(".npz") for f in os.listdir(state_dir))
    g = SavedStateLoadRule(state_dir).apply(lazy2.graph)
    out = GraphExecutor(g).execute(g.sinks[0])
    np.testing.assert_allclose(out.dataset.numpy(), 6.0)


def test_old_pickle_missing_new_attrs_still_applies():
    """Pipelines pickled before smoothing_magnif / sparse_output existed
    must unpickle to the behavior they were fitted with (ADVICE r2)."""
    import numpy as np

    from keystone_tpu.ops.nlp import CommonSparseFeaturesModel, HashingTF
    from keystone_tpu.ops.sift import SIFTExtractor

    # Simulate an old pickle: bypass __init__, drop the new attributes.
    sift = SIFTExtractor.__new__(SIFTExtractor)
    sift.step = 8
    sift.bin_sizes = (4,)
    assert sift.smoothing_magnif == 0.0  # class-level default
    img = np.random.default_rng(0).uniform(size=(1, 32, 32)).astype(np.float32)
    d, m = sift.apply_batch(img)
    assert np.all(np.isfinite(np.asarray(d)))

    csf = CommonSparseFeaturesModel.__new__(CommonSparseFeaturesModel)
    csf.vocab = {"a": 0, "b": 1}
    csf.num_features = 2
    assert csf.sparse_output is False
    row = csf.apply_one({"a": 2.0})
    assert isinstance(row, np.ndarray) and row[0] == 2.0

    tf = HashingTF.__new__(HashingTF)
    tf.num_features = 16
    assert tf.sparse_output is False
    assert isinstance(tf.apply_one({"x": 1.0}), np.ndarray)


def test_from_scipy_rows_width_mismatch_raises():
    import scipy.sparse as sp

    from keystone_tpu.ops.sparse import PaddedSparseRows

    rows = [sp.csr_matrix(([1.0], ([0], [3])), shape=(1, 10))]
    with pytest.raises(ValueError, match="width"):
        PaddedSparseRows.from_scipy_rows(rows, num_features=7)
    # Matching width still fine.
    PaddedSparseRows.from_scipy_rows(rows, num_features=10)
