"""Whole-pipeline optimizer.

Reference: workflow/Optimizer.scala — a Catalyst-style rule executor
(batches with Once/FixedPoint strategies) over the pipeline Graph, with
three rule families (SURVEY.md §2.1):

  - EquivalentNodeMergeRule: CSE — merge structurally identical subgraphs
    so e.g. two branches sharing SIFT compute it once.
  - AutoCacheRule: decide which shared outputs to materialize.
  - NodeOptimizationRule: per-node physical operator choice from sampled
    data statistics.

The TPU twist (SURVEY.md §7): XLA already does CSE/fusion *within* a
compiled stage; this optimizer works *across* stages — it decides
materialization points, and it fuses maximal linear chains of device
transformers into single jit-compiled stages (StageFusionRule), so a
featurization chain costs one XLA program, not one dispatch per node.
"""

from __future__ import annotations

import logging
from typing import Callable, List, Optional, Sequence

import jax

from keystone_tpu.workflow import graph as G
from keystone_tpu.workflow.estimator import Estimator
from keystone_tpu.workflow.transformer import (
    Cacher,
    Transformer,
    jit_named,
    mint_span,
    share_key,
)

logger = logging.getLogger(__name__)


class Rule:
    name: str = "rule"

    def apply(self, graph: G.Graph) -> G.Graph:
        raise NotImplementedError


class Once:
    def __init__(self):
        self.max_iterations = 1


class FixedPoint:
    def __init__(self, max_iterations: int = 20):
        self.max_iterations = max_iterations


class RuleBatch:
    def __init__(self, name: str, strategy, rules: Sequence[Rule]):
        self.name = name
        self.strategy = strategy
        self.rules = list(rules)


class Optimizer:
    """Executes rule batches until their strategy is exhausted or the graph
    stops changing (workflow/Optimizer.scala § RuleExecutor.execute)."""

    def __init__(self, batches: Sequence[RuleBatch]):
        self.batches = list(batches)

    def execute(self, graph: G.Graph) -> G.Graph:
        from keystone_tpu.obs import ledger

        for batch in self.batches:
            for _ in range(batch.strategy.max_iterations):
                before = _graph_fingerprint(graph)
                for rule in batch.rules:
                    with ledger.span("optimizer.rule", rule=rule.name, batch=batch.name):
                        graph = rule.apply(graph)
                if _graph_fingerprint(graph) == before:
                    break
        return graph


def _graph_fingerprint(g: G.Graph):
    return (
        tuple(sorted((n.id, id(op)) for n, op in g.operators.items())),
        tuple(sorted((n.id, tuple(d.id for d in ds)) for n, ds in g.dependencies.items())),
    )


# --------------------------------------------------------------------- CSE
class EquivalentNodeMergeRule(Rule):
    """Merge nodes whose operator + entire input prefix are structurally
    equal (workflow/EquivalentNodeMergeRule.scala).  This is what makes
    ``Pipeline.gather`` branches sharing a SIFT prefix compute it once."""

    name = "EquivalentNodeMerge"

    def apply(self, graph: G.Graph) -> G.Graph:
        memo: dict = {}
        groups: dict = {}
        for n in graph.topological_nodes():
            sig = graph.prefix_signature(n, memo)
            if sig is not None and sig[0] != "unique":
                groups.setdefault(sig, []).append(n)
        for sig, nodes in groups.items():
            if len(nodes) < 2:
                continue
            keep = min(nodes)
            for other in nodes:
                if other == keep:
                    continue
                graph = graph.replace_dependency(other, keep)
                graph = graph.remove_node(other)
        return graph


# ----------------------------------------------------------- materialization
def _is_cacher(op) -> bool:
    return isinstance(op, G.TransformerOperator) and isinstance(op.transformer, Cacher)


def needs_barrier(graph: G.Graph, n: G.NodeId) -> bool:
    """Whether ``n``'s output has more than one consumer and no
    materialization barrier yet.  A placed ``Cacher`` IS the barrier: it
    is never a candidate itself, however many nodes read it, and a node
    that one already reads has been decided.  The one test of both
    materialization rules — the structural one below and the profiled one
    (``workflow/profiling.py``) — so an optimized graph that is optimized
    again (every call of a fitted pipeline) has nothing left to place."""
    if _is_cacher(graph.operators.get(n)):
        return False
    consumers = [d for d in graph.dependents(n) if not isinstance(d, G.SinkId)]
    return len(consumers) > 1 and not any(
        _is_cacher(graph.operators.get(d)) for d in consumers
    )


def place_barrier(graph: G.Graph, n: G.NodeId) -> G.Graph:
    """A ``Cacher`` behind ``n``, read by every node that read ``n``;
    the graph as it is where ``n`` needs none."""
    if not needs_barrier(graph, n):
        return graph
    consumers = [d for d in graph.dependents(n) if isinstance(d, G.NodeId)]
    graph, cache_node = graph.add_node(G.TransformerOperator(Cacher()), (n,))
    for d in consumers:
        graph = graph.set_dependencies(
            d, tuple(cache_node if x == n else x for x in graph.dependencies[d])
        )
    return graph


class AutoMaterializeRule(Rule):
    """Insert Cacher nodes after outputs consumed by >1 dependent.

    The reference's AutoCacheRule profiles nodes on sampled partitions and
    greedily places ``.cache()`` calls under a cluster-memory budget
    (workflow/AutoCacheRule.scala).  Here the executor already memoizes
    per-node results, so "cache or recompute" is decided structurally:
    shared outputs get an explicit materialization barrier, which also
    pins them as stage boundaries for the fusion rule below.  The
    cost-model driven HBM-vs-recompute variant is the default
    (``ProfiledMaterializeRule``); this one is its fallback.
    """

    name = "AutoMaterialize"

    def apply(self, graph: G.Graph) -> G.Graph:
        for n in list(graph.topological_nodes()):
            if isinstance(graph.operators.get(n), G.TransformerOperator):
                graph = place_barrier(graph, n)
        return graph


# ------------------------------------------------------------- node choice
class NodeChoiceRule(Rule):
    """Physical operator selection (workflow/NodeOptimizationRule).

    For estimators that override ``choose_physical``, executes the
    estimator's input subgraph on a small sample (the analogue of the
    reference's optimizer-time sampling Spark jobs) and lets the estimator
    pick its best physical implementation — e.g. a local exact solve for
    small data vs the distributed block solver, or dense vs sparse LBFGS.
    """

    name = "NodeChoice"

    def __init__(self, sample_size: int = 256):
        self.sample_size = sample_size

    def apply(self, graph: G.Graph) -> G.Graph:
        from keystone_tpu.workflow.dataset import Dataset
        from keystone_tpu.workflow.executor import DatasetExpr, GraphExecutor
        from keystone_tpu.workflow.transformer import Transformer

        # full dataset size: lets size-based choices (local vs
        # distributed solve) see past the truncated sample
        full_n = max(
            (
                op.dataset.n if isinstance(op.dataset, Dataset) else len(op.dataset)
                for op in graph.operators.values()
                if isinstance(op, G.DatasetOperator)
            ),
            default=None,
        )
        for n in list(graph.topological_nodes()):
            op = graph.operators.get(n)
            if isinstance(op, G.EstimatorOperator):
                node = op.estimator
                overridden = (
                    type(node).choose_physical is not Estimator.choose_physical
                )
                rewrap = G.EstimatorOperator
            elif isinstance(op, G.TransformerOperator):
                node = op.transformer
                overridden = (
                    type(node).choose_physical is not Transformer.choose_physical
                )
                rewrap = G.TransformerOperator
            else:
                continue
            if not overridden:
                continue
            sample = None
            try:
                ex = _SampleExecutor(graph, self.sample_size)
                expr = ex.execute(graph.dependencies[n][0])
                if isinstance(expr, DatasetExpr):
                    sample = expr.dataset
            except Exception as e:  # sampling is best-effort, like upstream
                logger.debug("node-choice sampling failed for %s: %s", node.label, e)
            import inspect

            if "full_n" in inspect.signature(node.choose_physical).parameters:
                chosen = node.choose_physical(sample, full_n=full_n)
            else:
                chosen = node.choose_physical(sample)
            if chosen is not node:
                logger.info("node choice: %s -> %s", node.label, chosen.label)
                graph = graph.set_operator(n, rewrap(chosen))
        return graph


class _SampleExecutor:
    """Executes a subgraph with dataset literals truncated to k rows."""

    def __init__(self, graph: G.Graph, k: int):
        from keystone_tpu.workflow.executor import GraphExecutor

        self._inner = GraphExecutor(_truncate_datasets(graph, k))

    def execute(self, target):
        return self._inner.execute(target)


def _truncate_datasets(graph: G.Graph, k: int) -> G.Graph:
    from keystone_tpu.workflow.dataset import Dataset, StreamDataset, as_dataset

    for n, op in list(graph.operators.items()):
        if isinstance(op, G.DatasetOperator):
            ds = as_dataset(op.dataset)
            if isinstance(ds, StreamDataset):
                # sample the first batch(es) — materializing the whole
                # stream to truncate it would defeat out-of-core (the
                # reference's AutoCacheRule samples partitions the same
                # way); the sampled rows stand in for the stream in the
                # truncated PROFILING graph only
                import numpy as np

                if ds.is_host:
                    items, got2 = [], 0
                    for batch, _m in ds.device_batches():
                        items.extend(batch)
                        got2 += len(batch)
                        if got2 >= k:
                            break
                    if items:
                        graph = graph.set_operator(
                            n, G.DatasetOperator(Dataset(items[:k]))
                        )
                    continue
                parts, masks, got = [], [], 0
                for arr, mask in ds.device_batches():
                    parts.append(np.asarray(arr))
                    if mask is not None:
                        masks.append(np.asarray(mask))
                    got += arr.shape[0]
                    if got >= k:
                        break
                if not parts:
                    continue
                sample = np.concatenate(parts, axis=0)[:k]
                m = min(k, ds.n)
                # ragged streams: keep the per-batch masks, or sampled
                # nodes would treat padded descriptor rows as real data
                smask = (
                    np.concatenate(masks, axis=0)[:k] if masks else None
                )
                sliced = Dataset(sample, n=m, mask=smask, shard=False)
                graph = graph.set_operator(n, G.DatasetOperator(sliced))
            elif not ds.is_host and ds.n > k:
                sliced = Dataset(ds.array[:k], n=min(k, ds.n), shard=False)
                graph = graph.set_operator(n, G.DatasetOperator(sliced))
            elif ds.is_host and ds.n > k:
                graph = graph.set_operator(
                    n, G.DatasetOperator(Dataset(ds.items[:k]))
                )
    return graph


# ------------------------------------------------------------- stage fusion

#: class-shared jitted fused chains, keyed by (per-stage share keys,
#: matmul mode) — see transformer.share_key
_FUSED_SHARED_CACHE: dict = {}


class FusedTransformer(Transformer):
    """A maximal linear chain of device transformers compiled as ONE jit
    stage.  This is the TPU replacement for the reference's per-node
    ``rdd.map`` chain: stage boundaries = jit boundaries (SURVEY.md §7)."""

    # apply_batch manages its own program caches below; the generic
    # per-instance jit wrapper must not add an outer jit, or the shared
    # chain's traced stage parameters become outer-program constants
    self_jitted = True

    def __init__(self, stages: Sequence[Transformer]):
        self.stages = list(stages)
        self._jitted = {}  # matmul mode -> jitted fn; never pickled

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_jitted"] = {}
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        if not isinstance(self._jitted, dict):  # pre-dict pickles stored None
            self._jitted = {}

    @property
    def label(self):
        return "Fused[" + " > ".join(s.label for s in self.stages) + "]"

    def params(self):
        ps = tuple(s.params() for s in self.stages)
        return None if any(p is None for p in ps) else ps

    def apply_one(self, x):
        for s in self.stages:
            x = s.apply_one(x)
        return x

    def apply_batch(self, xs, mask=None):
        # Keyed by the resolved matmul mode (utils/precision.py
        # invariant): a policy flip must retrace, not reuse a
        # stale-precision executable.  'bf16_apply' is its own key — the
        # fused chain is where the apply policy pays most (every stage's
        # bf16 casts shrink the in-program streams XLA fuses across), so
        # the whole chain recompiles under the new policy as one program.
        from keystone_tpu.utils import precision

        mode = precision.matmul_mode()
        skeys = tuple(share_key(s) for s in self.stages)
        if all(k is not None for k in skeys):
            # the input signature scopes the untraceable memo (one odd
            # dtype/rank must not pin every later call of the chain to
            # the per-instance path — same discipline as
            # Transformer._apply_batch_jitted)
            from keystone_tpu.workflow.transformer import traced_param_sig

            ckey = (
                skeys,
                mode,
                str(getattr(xs, "dtype", "")),
                getattr(xs, "ndim", None),
                tuple(traced_param_sig(s) for s in self.stages),
            )
            try:
                return self._apply_shared(ckey, xs)
            except (TypeError, jax.errors.JAXTypeError):
                _FUSED_SHARED_CACHE[ckey] = None
        fn = self._jitted.get(mode)
        minted = fn is None
        if minted:
            stages = list(self.stages)

            def run(arr):
                for s in stages:
                    arr = s.apply_batch(arr)
                return arr

            fn = self._jitted[mode] = jit_named(run, stages)
        with mint_span(minted, self.stages, shared=False):
            return fn(xs)

    def _apply_shared(self, ckey, xs):
        """Cross-instance shared jitted chain: stage parameters ride as
        traced arguments (Transformer.traced_attrs), so e.g. the two
        branch tails Fused[SignedHellinger > NormalizeRows] compile ONCE
        and refits never invalidate the persistent compile cache."""
        sentinel = object()
        entry = _FUSED_SHARED_CACHE.get(ckey, sentinel)
        if entry is None:  # memoized untraceable for this chain+signature
            raise TypeError("fused chain memoized untraceable")  # caller falls back
        minted = entry is sentinel
        if minted:
            # Bound the cache: chains whose stage params() embed per-fit
            # fingerprints mint a fresh key every refit, and each entry's
            # templates pin that fit's non-traced arrays.  FIFO-evict —
            # an evicted-but-live chain just rebuilds its entry.
            while len(_FUSED_SHARED_CACHE) >= 128:
                _FUSED_SHARED_CACHE.pop(next(iter(_FUSED_SHARED_CACHE)))
            from keystone_tpu.workflow.transformer import rebound, stripped_template

            templates = [stripped_template(s) for s in self.stages]

            def run(plist, arr):
                for t, p in zip(templates, plist):
                    arr = rebound(t, p).apply_batch(arr)
                return arr

            entry = _FUSED_SHARED_CACHE[ckey] = jit_named(run, self.stages)
        plist = [
            {name: getattr(s, name) for name in type(s).traced_attrs}
            for s in self.stages
        ]
        with mint_span(minted, self.stages, shared=True):
            return entry(plist, xs)


class StageFusionRule(Rule):
    """Fuse consecutive single-consumer device TransformerOperators."""

    name = "StageFusion"

    def apply(self, graph: G.Graph) -> G.Graph:
        changed = True
        while changed:
            changed = False
            for n in graph.topological_nodes():
                op = graph.operators.get(n)
                if not _fusable(op):
                    continue
                deps_on_n = graph.dependents(n)
                if len(deps_on_n) != 1 or isinstance(deps_on_n[0], G.SinkId):
                    continue
                m = deps_on_n[0]
                mop = graph.operators.get(m)
                if not _fusable(mop) or graph.dependencies[m] != (n,):
                    continue
                stages = _stages(op) + _stages(mop)
                fused_op = G.TransformerOperator(FusedTransformer(stages))
                # the fused node's OUTPUT is m's output: if the cache rule
                # flagged m over-HBM-budget (no_memoize → recompute per
                # consumer), the fused replacement must carry the flag or
                # the executor pins the very output the device can't
                # afford.  (n's flag needs no propagation: fusing a
                # single-consumer n eliminates its output entirely.)
                if getattr(mop, "no_memoize", False):
                    fused_op.no_memoize = True
                graph = graph.set_operator(m, fused_op)
                graph = graph.set_dependencies(m, graph.dependencies[n])
                graph = graph.remove_node(n)
                changed = True
                break
        return graph


def _fusable(op) -> bool:
    return (
        isinstance(op, G.TransformerOperator)
        and not op.transformer.is_host
        and getattr(op.transformer, "fusable", True)
        and not isinstance(op.transformer, Cacher)
        # degradation-declaring stages (optional / with_fallback —
        # workflow/executor.py) must stay standalone nodes: fusing one
        # into a chain would make the executor fail the WHOLE chain
        # where the user asked for that one stage to degrade
        and not getattr(op.transformer, "optional", False)
        and getattr(op.transformer, "fallback", None) is None
    )


def _stages(op) -> list:
    t = op.transformer
    return list(t.stages) if isinstance(t, FusedTransformer) else [t]


class ConvPoolFusionRule(Rule):
    """Collapse ``Convolver → SymmetricRectifier → sum Pooler`` (and the
    ``ImageVectorizer`` behind it, if there is one) into ONE
    ``PooledConvolver`` node, whose program keeps the convolution's
    activation in fast memory a tile at a time (ops/conv_pool_pallas.py).

    At RandomPatchCifar's widths that activation is 29 MB an image: no
    stage-by-stage execution of the chain can exist there, the sampled
    ones of the node-choice and materialisation rules included, so this
    runs right after CSE and before either.  Every link must be a plain
    single-consumer node (the contract of ``_fusable``)."""

    name = "ConvPoolFusion"

    def apply(self, graph: G.Graph) -> G.Graph:
        from keystone_tpu.ops.images import (
            Convolver,
            ImageVectorizer,
            Pooler,
            PooledConvolver,
            SymmetricRectifier,
        )

        def only_dependent(n, kind):
            """The one node that consumes n, if it is a fusable ``kind``
            fed by n alone."""
            deps_on_n = graph.dependents(n)
            if len(deps_on_n) != 1 or isinstance(deps_on_n[0], G.SinkId):
                return None
            m = deps_on_n[0]
            mop = graph.operators.get(m)
            if (
                _fusable(mop)
                and type(mop.transformer) is kind
                and graph.dependencies[m] == (n,)
            ):
                return m
            return None

        for n in list(graph.topological_nodes()):
            op = graph.operators.get(n)
            if not _fusable(op) or type(op.transformer) is not Convolver:
                continue
            r = only_dependent(n, SymmetricRectifier)
            p = r and only_dependent(r, Pooler)
            if not p:
                continue
            conv, rect, pool = (graph.operators[x].transformer for x in (n, r, p))
            if not PooledConvolver.fuses(conv, rect, pool):
                continue
            v = only_dependent(p, ImageVectorizer)
            last = v or p
            fused_op = G.TransformerOperator(PooledConvolver(conv, rect, pool, bool(v)))
            if getattr(graph.operators[last], "no_memoize", False):
                fused_op.no_memoize = True
            graph = graph.set_operator(last, fused_op)
            graph = graph.set_dependencies(last, graph.dependencies[n])
            for gone in [x for x in (n, r, p) if x != last]:
                graph = graph.remove_node(gone)
        return graph


class PallasFvFusionRule(Rule):
    """Collapse the FV hot path's per-stage dispatch chain into the
    fused Pallas forward megakernel.

    An adjacent single-consumer ``PCATransformer → FisherVector`` pair
    becomes ONE ``FusedPcaFisherVector`` node
    (ops/fisher_pallas.fused_forward_pallas): descriptors stream from
    HBM once instead of round-tripping between the stages, and the
    per-stage program launches become one.  When the upstream
    ``SIFTExtractor`` feeds the PCA exclusively, its L2→clamp→re-L2
    normalize tail is absorbed into the kernel too (the extractor is
    swapped for a raw-descriptor copy), making the fused node a true
    sift-normalize → PCA-project → FV-encode forward.

    Fires only when the computation targets a Pallas-capable device
    (``pallas_supported()``); CPU meshes and dryruns keep the pre-rule
    graph, so compile-count and byte-identity pins are untouched.
    The ``fused_fv`` gate resolves through the planner precedence:
    ``KEYSTONE_FUSED_FV=0`` (the documented env override) disables the
    rule outright, else an installed ``PhysicalPlan`` that sampled the
    chain as cheaper ('xla' winner) disables it; with neither, the rule
    fires wherever Pallas runs — the historical static default."""

    name = "PallasFvFusion"

    def apply(self, graph: G.Graph) -> G.Graph:
        import os

        if os.environ.get("KEYSTONE_FUSED_FV", "1") == "0":
            return graph
        if os.environ.get("KEYSTONE_FUSED_FV") is None:
            # env unset: consult the installed plan (env stays the
            # stronger override; no plan leaves the legacy path intact)
            try:
                from keystone_tpu.planner import registry as _plans

                if _plans.planned_gate("fused_fv") == "xla":
                    return graph
            except Exception:
                pass
        from keystone_tpu.ops.fisher_pallas import pallas_supported

        if not pallas_supported():
            return graph
        import copy

        from keystone_tpu.models.pca import PCATransformer
        from keystone_tpu.ops.fisher import FisherVector, FusedPcaFisherVector
        from keystone_tpu.ops.sift import SIFTExtractor

        def _plain(op) -> bool:
            # degradation-declaring stages must stay standalone nodes
            # (same contract as _fusable): the executor degrades THEM,
            # not a fused stranger
            return (
                isinstance(op, G.TransformerOperator)
                and not getattr(op.transformer, "optional", False)
                and getattr(op.transformer, "fallback", None) is None
            )

        changed = True
        while changed:
            changed = False
            for n in graph.topological_nodes():
                op = graph.operators.get(n)
                if not _plain(op) or not isinstance(
                    op.transformer, PCATransformer
                ):
                    continue
                deps_on_n = graph.dependents(n)
                if len(deps_on_n) != 1 or isinstance(deps_on_n[0], G.SinkId):
                    continue
                m = deps_on_n[0]
                mop = graph.operators.get(m)
                if (
                    not _plain(mop)
                    or not isinstance(mop.transformer, FisherVector)
                    or graph.dependencies[m] != (n,)
                ):
                    continue
                fv = mop.transformer
                if fv.use_pallas is False:
                    continue  # an explicit opt-out covers the fused form too
                # absorb the SIFT normalize tail when the extractor's
                # output feeds ONLY this PCA (a shared extractor must
                # keep emitting normalized descriptors for its other
                # consumers — vocabulary samplers in the fit graph)
                sift_normalize = False
                pca_deps = graph.dependencies[n]
                if len(pca_deps) == 1:
                    s = pca_deps[0]
                    sop = graph.operators.get(s)
                    if (
                        _plain(sop)
                        and isinstance(sop.transformer, SIFTExtractor)
                        and sop.transformer.normalize
                        and tuple(graph.dependents(s)) == (n,)
                    ):
                        raw_sift = copy.copy(sop.transformer)
                        raw_sift.normalize = False
                        graph = graph.set_operator(
                            s, G.TransformerOperator(raw_sift)
                        )
                        sift_normalize = True
                fused_op = G.TransformerOperator(
                    FusedPcaFisherVector(
                        op.transformer,
                        fv.gmm,
                        sift_normalize=sift_normalize,
                        use_pallas=fv.use_pallas,
                    )
                )
                # the fused node's output is m's output — carry the
                # cache rule's over-budget flag (see StageFusionRule)
                if getattr(mop, "no_memoize", False):
                    fused_op.no_memoize = True
                graph = graph.set_operator(m, fused_op)
                graph = graph.set_dependencies(m, graph.dependencies[n])
                graph = graph.remove_node(n)
                changed = True
                break
        return graph


# ------------------------------------------------------------------ default
class ProfiledMaterializeRule(Rule):
    """Default materialization pass (r2): the HBM-budgeted
    ProfilingAutoCacheRule with the budget read from the actual device,
    falling back to the structural AutoMaterializeRule when profiling is
    unavailable (no device stats, unexecutable sample, host-only graph).

    This is the promotion round-1 review item 8 asked for: the reference's
    AutoCacheRule (sampled profiling + memory-budget greedy placement,
    workflow/AutoCacheRule.scala) is now the DEFAULT path, not a
    hand-wired option."""

    name = "ProfiledMaterialize"

    def __init__(self, sample_size: int = 64):
        self.sample_size = int(sample_size)

    def apply(self, graph: G.Graph) -> G.Graph:
        try:
            from keystone_tpu.workflow.profiling import (
                ProfilingAutoCacheRule,
                device_hbm_budget,
            )

            return ProfilingAutoCacheRule(
                budget_bytes=device_hbm_budget(),
                sample_size=self.sample_size,
                static_cost=True,
            ).apply(graph)
        except Exception as e:
            import logging

            logging.getLogger(__name__).warning(
                "profiled materialization failed (%s); using structural rule", e
            )
            return AutoMaterializeRule().apply(graph)


def default_optimizer(
    sample_size: int = 256, materialize_sample_size: int = 64
) -> Optimizer:
    """``sample_size`` governs node-choice sampling;
    ``materialize_sample_size`` the profiled materialization pass (kept
    smaller by default — it executes the whole prefix graph per node)."""
    return Optimizer(
        [
            RuleBatch("cse", FixedPoint(5), [EquivalentNodeMergeRule()]),
            # before any rule that samples: the chain it replaces cannot
            # be executed stage by stage at the widths it exists for
            RuleBatch("conv-pool", Once(), [ConvPoolFusionRule()]),
            RuleBatch("node-choice", Once(), [NodeChoiceRule(sample_size)]),
            RuleBatch(
                "materialize",
                Once(),
                [ProfiledMaterializeRule(materialize_sample_size)],
            ),
            # Pallas FV fusion first: it targets the (non-fusable)
            # PCA→FV pair specifically, before the generic chain fuser
            # sweeps the remaining linear runs
            RuleBatch(
                "fusion", Once(), [PallasFvFusionRule(), StageFusionRule()]
            ),
        ]
    )
