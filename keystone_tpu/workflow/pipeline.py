"""Pipeline DSL: chain/gather composition, lazy results, fit.

Reference: workflow/Pipeline.scala § Pipeline[A,B], PipelineDataset,
PipelineDatum — pipelines are DAGs with one open source and one sink;
``andThen`` chains, ``Pipeline.gather`` merges branches, applying a
pipeline to data yields a *lazy* result wrapper, and ``fit()`` resolves
every estimator into its fitted transformer (the reference's
PipelineModel), triggering optimization + execution.

Typical usage (cf. pipelines/images/mnist/MnistRandomFFT.scala):

    featurizer = Pipeline.gather([
        RandomSignNode.init(d, seed) | PaddedFFT() | LinearRectifier(0.0)
        for seed in seeds
    ])
    predictor = (featurizer
                 .and_then(BlockLeastSquaresEstimator(2048, 1, lam),
                           train_x, train_labels)
                 .and_then(MaxClassifier()))
    test_pred = predictor(test_x).get()
"""

from __future__ import annotations

import itertools
import os
import pickle
from typing import List, Optional, Sequence, Union

from keystone_tpu.workflow import graph as G
from keystone_tpu.workflow.dataset import Dataset, StreamDataset, as_dataset
from keystone_tpu.workflow.estimator import Estimator, LabelEstimator
from keystone_tpu.workflow.executor import (
    DatasetExpr,
    DatumExpr,
    GraphExecutor,
    TransformerExpr,
)
from keystone_tpu.workflow.transformer import Chainable, Transformer


class PipelineEnv:
    """Process-global pipeline environment (workflow/PipelineEnv.scala):
    the optimizer instance and the state directory for saved pipelines.

    Setting ``state_dir`` prepends a SavedStateLoadRule batch to the
    default optimizer, so previously-materialized prefixes reload
    automatically (the reference's saved-state flow)."""

    optimizer = None  # lazily constructed default
    state_dir: Optional[str] = None
    #: stage-retry budget for every executor the framework creates
    #: (GraphExecutor node_retries — SURVEY §5 task-retry analogue).
    #: None = read KEYSTONE_STAGE_RETRIES at use time (lazy: a malformed
    #: env value must not crash module import, and post-import env
    #: changes should take effect); set an int here to override.
    node_retries: Optional[int] = None

    @classmethod
    def stage_retries(cls) -> int:
        if cls.node_retries is not None:
            return max(0, int(cls.node_retries))
        raw = os.environ.get("KEYSTONE_STAGE_RETRIES", "0")
        try:
            return max(0, int(raw))
        except ValueError:
            import logging

            logging.getLogger(__name__).warning(
                "KEYSTONE_STAGE_RETRIES=%r is not an integer; using 0", raw
            )
            return 0
    _built_for_state_dir: Optional[str] = None
    _auto_built = None  # the instance get_optimizer constructed itself
    _auto_built_sig = ()  # identity of its rule batches at build time

    @classmethod
    def set_optimizer(cls, optimizer) -> None:
        """Install a custom optimizer; it is never overwritten by the
        state_dir wiring (compose SavedStateLoadRule yourself if needed)."""
        cls.optimizer = optimizer
        cls._auto_built = None
        cls._auto_built_sig = ()

    @classmethod
    def get_optimizer(cls):
        # anything not built by this method — via set_optimizer, direct
        # assignment to the public attribute, or in-place extension of
        # the auto-built default's rule batches — is user-owned: honor it
        if cls.optimizer is not None and (
            cls.optimizer is not cls._auto_built
            or len(cls.optimizer.batches) != len(cls._auto_built_sig)
            or any(
                b is not s
                for b, s in zip(cls.optimizer.batches, cls._auto_built_sig)
            )
        ):
            return cls.optimizer
        if cls.optimizer is None or cls._built_for_state_dir != cls.state_dir:
            from keystone_tpu.workflow.optimizer import (
                Once,
                RuleBatch,
                default_optimizer,
            )

            opt = default_optimizer()
            if cls.state_dir:
                from keystone_tpu.workflow.state import SavedStateLoadRule

                opt.batches.insert(
                    0,
                    RuleBatch(
                        "saved-state", Once(), [SavedStateLoadRule(cls.state_dir)]
                    ),
                )
            cls.optimizer = opt
            cls._auto_built = opt
            cls._auto_built_sig = tuple(opt.batches)
            cls._built_for_state_dir = cls.state_dir
        return cls.optimizer


def _validate_requested(validate) -> bool:
    """The ONE pre-flight gate shared by ``fit`` and ``freeze``:
    explicit flag wins, ``None`` reads ``KEYSTONE_VALIDATE``.  Kept
    module-local (not ``analysis.validation_enabled``) so the off path
    costs one env lookup and never imports the analysis package — the
    inert-path guarantee the solver byte-identity pins ride on."""
    if validate is not None:
        return bool(validate)
    return os.environ.get("KEYSTONE_VALIDATE", "0") == "1"


class Pipeline(Chainable):
    """A DAG with one open source and one sink."""

    def __init__(self, graph: G.Graph, source: G.SourceId, sink: G.SinkId):
        self.graph = graph
        self.source = source
        self.sink = sink

    # ------------------------------------------------------- constructors
    @staticmethod
    def of(x) -> "Pipeline":
        if isinstance(x, Pipeline):
            return x
        if isinstance(x, Transformer):
            return Pipeline.from_transformer(x)
        raise TypeError(f"cannot lift {x!r} into a Pipeline")

    @staticmethod
    def from_transformer(t: Transformer) -> "Pipeline":
        g = G.Graph()
        g, src = g.add_source()
        g, node = g.add_node(G.TransformerOperator(t), (src,))
        g, sink = g.add_sink(node)
        return Pipeline(g, src, sink)

    @staticmethod
    def from_estimator(est: Estimator, data, labels=None) -> "Pipeline":
        """``est.withData(data[, labels])``: a pipeline whose transform is
        the transformer obtained by fitting ``est`` on ``data``."""
        g = G.Graph()
        g, data_dep = _splice_input(g, data)
        deps = [data_dep]
        if labels is not None:
            g, labels_dep = _splice_input(g, labels)
            deps.append(labels_dep)
        elif isinstance(est, LabelEstimator):
            raise ValueError(f"{est.label} requires labels")
        g, est_node = g.add_node(G.EstimatorOperator(est), tuple(deps))
        g, src = g.add_source()
        g, apply_node = g.add_node(G.DelegatingOperator(), (est_node, src))
        g, sink = g.add_sink(apply_node)
        return Pipeline(g, src, sink)

    @staticmethod
    def gather(branches: Sequence[Union["Pipeline", Transformer]]) -> "Pipeline":
        """Merge N branches over a shared input; output = concatenated
        features (workflow/Pipeline.scala § gather).  The CSE rule merges
        any common branch prefixes so shared featurization runs once."""
        branches = [Pipeline.of(b) for b in branches]
        if not branches:
            raise ValueError("gather of zero branches")
        # one pass over every branch's graph, each id renumbered once (a
        # union and an edge rewrite per branch made 200 branches quadratic)
        ids = itertools.count()
        src = G.SourceId(next(ids))
        sources, ops, deps, sinks, outs = [src], {}, {}, {}, []
        for b in branches:
            mapping = {b.source: src}

            def remap(i, mapping=mapping):
                if i not in mapping:
                    mapping[i] = type(i)(next(ids))
                return mapping[i]

            sources += [remap(s) for s in b.graph.sources if s != b.source]
            for n, op in b.graph.operators.items():
                ops[remap(n)] = op
            for n, ds in b.graph.dependencies.items():
                deps[remap(n)] = tuple(remap(d) for d in ds)
            for k, d in b.graph.sink_dependencies.items():
                if k == b.sink:
                    outs.append(remap(d))
                else:
                    sinks[remap(k)] = remap(d)
        gather_node, sink = G.NodeId(next(ids)), G.SinkId(next(ids))
        ops[gather_node], deps[gather_node] = G.GatherOperator(), tuple(outs)
        sinks[sink] = gather_node
        return Pipeline(G.Graph(tuple(sources), ops, deps, sinks), src, sink)

    # ------------------------------------------------------- composition
    def then_pipeline(self, other: "Pipeline") -> "Pipeline":
        g, mapping = self.graph.union(other.graph)
        g = g.connect(self.sink, mapping[other.source])
        return Pipeline(g, self.source, mapping[other.sink])

    def and_then(self, nxt, data=None, labels=None) -> "Pipeline":
        """Chain a transformer/pipeline, or an estimator fit on this
        pipeline's output over ``data`` (workflow/Pipeline.scala § andThen)."""
        if isinstance(nxt, Estimator):
            if data is None:
                raise ValueError(f"and_then({nxt.label}) requires training data")
            featurized = self(data)  # lazy: shares this pipeline's prefix
            est_pipe = Pipeline.from_estimator(nxt, featurized, labels)
            return self.then_pipeline(est_pipe)
        return self.then_pipeline(Pipeline.of(nxt))

    # -------------------------------------------------------- application
    def __call__(self, data):
        if isinstance(data, PipelineDataset):
            g, mapping = data.graph.union(self.graph)
            out_dep = g.sink_dependencies[data.sink]
            g = g.remove_sink(data.sink)
            new_src = mapping[self.source]
            g = g.replace_dependency(new_src, out_dep)
            g = g.remove_source(new_src)
            return PipelineDataset(g, mapping[self.sink])
        if isinstance(data, (Dataset,)) or _is_batchlike(data):
            ds = as_dataset(data)
            g, _ = self.graph.replace_source_with_node(
                self.source, G.DatasetOperator(ds)
            )
            return PipelineDataset(g, self.sink)
        g, _ = self.graph.replace_source_with_node(self.source, G.DatumOperator(data))
        return PipelineDatum(g, self.sink)

    def apply(self, data):
        return self(data)

    def apply_datum(self, x) -> "PipelineDatum":
        """Apply to one datum (arrays are otherwise treated as batches)."""
        g, _ = self.graph.replace_source_with_node(self.source, G.DatumOperator(x))
        return PipelineDatum(g, self.sink)

    # --------------------------------------------------------------- fit
    def fit(self, deadline=None, validate=None) -> "FittedPipeline":
        """Optimize, execute every estimator fit, and return a pure
        transformer pipeline (the reference's ``Pipeline.fit():
        PipelineModel``).  Fits are memoized via the executor, so shared
        prefixes run once.

        ``validate``: run the pre-flight static analyzer
        (``keystone_tpu.analysis``) before any device work — abstract
        shape/dtype propagation over the bound estimator subgraphs,
        fault-plan/breaker/deadline configuration lint, and the
        CSE/cache-signature audit.  Error findings raise
        ``PipelineValidationError`` (the fit never starts); warnings
        log.  Default ``None`` reads ``KEYSTONE_VALIDATE`` (\"1\" = on);
        off, the cost is one env lookup and ``keystone_tpu.analysis``
        is never imported — the solver byte-identity pins ride on this
        inert path.

        ``deadline``: a wall-clock budget for the whole fit — seconds or
        a ``utils.guard.Deadline``.  The executor apportions it over the
        stages (see ``GraphExecutor``): a stage that overruns its share
        raises ``DeadlineExceeded`` inside the stage-retry scope, so
        hung stages are retried, degraded (``optional`` /
        ``with_fallback`` nodes), or fail the fit in bounded time
        instead of stalling it forever.  Default None: no watchdog, no
        threads — the pre-deadline behavior exactly.

        Observability: the whole fit runs inside a ``pipeline.fit`` span
        (``obs/ledger.py``: in memory and in any profiler session, always)
        with ``pipeline.optimize``, per-stage ``executor.stage`` and
        ``solver.fit`` spans under it.  With ``KEYSTONE_OBS_DIR`` set (or
        a ledger attached via ``obs.ledger.start_run``) the spans, solver
        convergence events, and I/O counters also land in the run's JSONL
        ledger, and a metrics snapshot is flushed at fit end so
        ``tools/obs_report.py`` can summarize a run even if the process
        later dies."""
        if _validate_requested(validate):
            from keystone_tpu.analysis import validate_fit

            validate_fit(self, deadline=deadline)
        from keystone_tpu.obs import ledger as _ledger

        with _ledger.span("pipeline.fit"):
            fitted_pipe = self._fit_inner(deadline=deadline)
        led = _ledger.active()
        if led is not None:
            try:
                import jax

                jax.effects_barrier()  # flush in-flight solver callbacks
            except Exception:
                pass
            led.metrics_snapshot()
        return fitted_pipe

    def _fit_inner(self, deadline=None) -> "FittedPipeline":
        g = _optimize(self.graph, PipelineEnv.get_optimizer().execute, _auto_out_of_core)
        # ONE executor (and one resolved Deadline) for every estimator
        # in the walk: memoized prefixes and the fit budget are shared
        ex = GraphExecutor(g, deadline=deadline)
        fitted: dict = {}
        for n in g.topological_nodes():
            if isinstance(g.operators[n], G.EstimatorOperator):
                expr = ex.execute(n)
                assert isinstance(expr, TransformerExpr)
                fitted[n] = expr.transformer
        for n, t in fitted.items():
            for dep in g.dependents(n):
                if isinstance(dep, G.NodeId) and isinstance(
                    g.operators[dep], G.DelegatingOperator
                ):
                    rest = tuple(d for d in g.dependencies[dep] if d != n)
                    g = g.set_operator(dep, G.TransformerOperator(t))
                    g = g.set_dependencies(dep, rest)
            g = g.remove_node(n)
        g = _prune_unreachable(g, self.sink, keep_sources=(self.source,))
        # Re-fuse: estimator substitution just turned DelegatingOperators
        # (unfusable while the transformer was unknown) into plain device
        # transformers, leaving linear chains the pre-fit fusion pass
        # could not touch.  One more pass means the SCORING path runs as
        # few jit programs as possible — each extra program costs a
        # per-process trace + compile-cache load, the dominant cost of a
        # cold scoring run (the fit-overhead split of rounds 1–5, not re-measured).
        from keystone_tpu.workflow.optimizer import StageFusionRule

        g = _optimize(g, StageFusionRule().apply)
        return FittedPipeline(g, self.source, self.sink)

    def freeze(self, validate=None, example=None, plan=None) -> "FrozenApplier":
        """Freeze this pipeline for repeated online application: run the
        whole-pipeline optimizer ONCE now, and return a
        :class:`FrozenApplier` that binds each incoming batch to the
        pre-optimized graph — the serving entry point
        (``keystone_tpu.serve`` builds its micro-batching service on
        this).  Requires an estimator-free pipeline (``fit()`` first).

        ``validate`` runs the pre-flight analyzer in apply mode before
        the serve path primes any bucket program: a statically-broken
        pipeline (mis-shaped stage given ``example``, signature
        collision, bad fault plan) is rejected with
        ``PipelineValidationError`` instead of failing request-by-
        request.  ``example`` (a per-item shape tuple, batch array, or
        Dataset) seeds shape propagation from the open source.  Default
        ``None`` reads ``KEYSTONE_VALIDATE``; off, the path is inert.

        ``plan`` opts into cost-based physical planning
        (``keystone_tpu.planner``): ``True`` samples candidate
        implementations on ``example`` batches and builds a
        :class:`~keystone_tpu.planner.plan.PhysicalPlan` here (installed
        before the optimizer runs, shipped in the applier and its
        artifacts); a ``PhysicalPlan`` instance installs as-is.  Default
        ``None``: no plan — the legacy path, byte-identical."""
        return FrozenApplier(self, validate=validate, example=example, plan=plan)

    def to_dot(
        self, name: str = "pipeline", timings=None, retries=None, findings=None
    ) -> str:
        """Graphviz DOT of this pipeline's DAG (Pipeline.toDOT analogue).
        ``timings``/``retries`` overlay measured per-node seconds and
        retry counts (see ``workflow/viz.py`` — ``ledger_overlay`` folds
        them out of a run ledger); ``findings`` overlays analyzer
        findings (red = error, yellow = warning — ``cli.py check
        --dot``)."""
        from keystone_tpu.workflow.viz import to_dot

        return to_dot(
            self.graph, name, timings=timings, retries=retries,
            findings=findings,
        )

    def __repr__(self):
        return f"Pipeline({self.graph!r})"


class FittedPipeline(Pipeline):
    """An estimator-free pipeline; picklable for save/load
    (the analogue of the reference's serialized PipelineModel +
    workflow/SavedStateLoadRule.scala)."""

    def fit(self, deadline=None, validate=None) -> "FittedPipeline":
        return self

    def _walk_fitted(self, visit=None) -> None:
        """Apply block_on_arrays over every fitted transformer's state —
        the ONE place that knows where fitted state lives (both sync
        paths ride it, so they cannot diverge)."""
        from keystone_tpu.workflow.executor import block_on_arrays

        seen: set = set()
        for op in self.graph.operators.values():
            t = getattr(op, "transformer", None)
            if t is not None:
                block_on_arrays(t, seen, visit=visit)

    def block_until_ready(self) -> "FittedPipeline":
        """Wait for every fitted transformer's device arrays to finish
        computing.  ``fit()`` dispatches solves asynchronously (XLA async
        execution); honest fit-time measurement and safe hand-off to
        other processes require this barrier."""
        self._walk_fitted()
        return self

    def read_back(self):
        """Device→host read of ONE element of every fitted device array;
        returns them as a flat float64 numpy vector.

        It is the cheap finiteness probe of a fit, not a sync:
        ``block_until_ready`` waits for the device on today's runtime
        (re-tested on the chip by chip_smoke.py's sync probe on every
        run — 0.3047 s blocked vs 0.3049 s synced by a host read, my
        chip run, PR 21).  A timed fit ends with this instead of a
        probe score, which would charge the one-row scoring programs'
        traces to fit time.

        Non-numeric leaves that expose ``block_until_ready`` but cannot
        join the batched read are only blocked on, not read (none exist
        in-repo)."""
        import jax.numpy as jnp
        import numpy as np

        leaves = []
        self._walk_fitted(visit=leaves.append)
        heads = []
        for a in leaves:
            try:  # one element per array, gathered ON DEVICE
                h = jnp.ravel(a)[:1]
                if jnp.issubdtype(h.dtype, jnp.floating):
                    # clamp IN THE NATIVE dtype so a finite wide value
                    # stays finite through the f32 transfer; true
                    # non-finites become nan (the caller's finiteness
                    # check must fire on those, and only those)
                    lim = float(jnp.finfo(jnp.float32).max)
                    h = jnp.where(
                        jnp.isfinite(h), jnp.clip(h, -lim, lim), jnp.nan
                    )
                heads.append(h.astype(jnp.float32))
            except TypeError:
                # non-numeric leaf exposing block_until_ready: it cannot
                # join the batched read, but it must still be forced
                a.block_until_ready()
        if not heads:
            # no numeric fitted state — there is nothing a read could
            # force, and returning empty would let a caller treat an
            # unsynced timing as synced
            raise RuntimeError(
                "read_back: fitted pipeline holds no readable device arrays"
            )
        # ONE device→host transfer for the lot: each read rides a
        # host↔device round trip, and a fitted pipeline holds dozens of
        # arrays — per-array np.asarray would pay dozens of RTTs
        return np.asarray(jnp.concatenate(heads), np.float64)

    def save(self, path: str) -> None:
        with open(path, "wb") as f:
            pickle.dump(self, f)

    @staticmethod
    def _load_raw(path: str):
        """Unpickle ``path`` → (fitted, saved_config_or_None); accepts the
        bare-pipeline and the fit_or_load {config, pipeline} formats."""
        with open(path, "rb") as f:
            obj = pickle.load(f)
        saved_cfg = None
        if isinstance(obj, dict) and "pipeline" in obj:
            saved_cfg, obj = obj.get("config"), obj["pipeline"]
        if not isinstance(obj, FittedPipeline):
            raise TypeError(f"{path} does not contain a FittedPipeline")
        return obj, saved_cfg

    @staticmethod
    def load(path: str) -> "FittedPipeline":
        return FittedPipeline._load_raw(path)[0]

    @staticmethod
    def fit_or_load(path, build_fn, config=None):
        """Load the fitted pipeline saved at ``path``, or build+fit+save.

        ``build_fn`` is called ONLY when fitting is needed — training-data
        loading belongs inside it, so scoring runs with a saved model skip
        it entirely.  ``config`` (any ==-comparable value, e.g. the app's
        Config dataclass) is persisted alongside the pipeline; loading
        with a config that doesn't match what the model was fitted with
        raises instead of silently reporting stale results.

        Returns ``(fitted, loaded)`` — ``loaded`` is True when the model
        came from disk.
        """
        import os

        if path and os.path.exists(path):
            obj, saved_cfg = FittedPipeline._load_raw(path)
            if config is not None and saved_cfg is None:
                # Legacy bare-pickle save() format: no config was persisted,
                # so the staleness check cannot run — exactly the mismatch
                # it exists to catch. Warn instead of silently accepting.
                import logging

                logging.getLogger(__name__).warning(
                    "saved model at %s has no persisted config (legacy "
                    "save() format); cannot verify it matches the current "
                    "config — re-fit (delete the file) to enable the "
                    "staleness check",
                    path,
                )
            if config is not None and saved_cfg is not None and saved_cfg != config:
                raise ValueError(
                    f"saved model at {path} was fitted with a different "
                    f"config ({saved_cfg!r}); refusing to score with "
                    "mismatched parameters — delete the file or pass a "
                    "matching config"
                )
            return obj, True
        fitted = build_fn().fit().block_until_ready()
        if path:
            with open(path, "wb") as f:
                pickle.dump({"config": config, "pipeline": fitted}, f)
        return fitted, False


class FrozenApplier:
    """A fitted pipeline optimized once and applied many times — the
    online-serving apply path (``keystone_tpu.serve``).

    ``Pipeline(...)``/``PipelineDataset.get()`` re-run the whole-pipeline
    optimizer on every application, which is the right trade for one
    big offline batch and the wrong one for a stream of small requests:
    the optimizer walk is pure host-side overhead once the graph is
    fitted and frozen.  Freezing runs the optimizer ONCE over the
    unbound graph; each call then binds the batch to the pre-optimized
    graph (persistent graphs make the bind a cheap copy) and runs a
    fresh :class:`GraphExecutor` walk over it.

    Compiled-program reuse: the per-transformer jitted apply caches
    (``workflow/transformer.py``) key on the SAME transformer instances
    on every call, so as long as callers keep the input shape set finite
    — the serve batcher's padding-bucket discipline
    (:func:`~keystone_tpu.workflow.transformer.iter_row_chunks` pads
    every flush up to a fixed bucket size) — every request after the
    first per bucket runs entirely from cache-hot programs.

    ``deadline`` per call plumbs into the executor exactly like
    ``Pipeline.fit(deadline=…)``: stages run under apportioned
    watchdogs, and ``optional``/``with_fallback`` nodes degrade instead
    of failing the batch — graceful degradation applies on the serve
    path too.

    **AOT artifacts** — :meth:`export_artifacts` lowers the whole
    frozen apply at each padding-bucket shape to a serialized
    ``jax.export`` program (the fitted weights ride along as program
    constants), and :meth:`install_artifacts` registers the
    deserialized programs so calls at exactly those shapes skip the
    optimizer-bind + per-stage trace/lower entirely — the cold-start,
    hot-swap, and supervisor-heal paths stop paying compile time.
    With nothing installed the cost is one empty-dict check per call
    (the pre-artifact path, byte-identical)."""

    def __init__(self, pipeline: "Pipeline", validate=None, example=None,
                 plan=None):
        for op in pipeline.graph.operators.values():
            if isinstance(op, G.EstimatorOperator):
                raise TypeError(
                    f"cannot freeze a pipeline with unfitted estimator "
                    f"{op.label()!r}; call fit() first"
                )
        if _validate_requested(validate):
            from keystone_tpu.analysis import validate_freeze

            validate_freeze(pipeline, example=example)
        #: the cost-based PhysicalPlan (keystone_tpu.planner), or None.
        #: Built/installed BEFORE the optimizer executes so planning
        #: rules (fused-FV) consult it; plain data, so it pickles with
        #: the applier (replica clones) and rides export_artifacts.
        self.plan = None
        if plan is not None and plan is not False:
            from keystone_tpu import planner

            if plan is True:
                self.plan = planner.build_plan(pipeline, example=example)
            else:
                self.plan = plan
            planner.install_plan(self.plan, source="freeze")
        self.graph = _optimize(pipeline.graph)
        self.source = pipeline.source
        self.sink = pipeline.sink
        #: the PRE-optimizer pipeline: the artifact signature hashes
        #: this (the pickled deploy payload) — the optimized graph is
        #: process-local (profiling-driven rules place by timings)
        self._frozen_from = pipeline
        #: installed AOT bucket programs: (shape, dtype str) -> callable.
        #: Unpicklable jitted callables — stripped by __getstate__.
        self._bucket_programs: dict = {}
        self._artifact_meta: dict = {}
        #: True when any stage declares optional/with_fallback: such
        #: pipelines keep the executor walk for deadline-carrying calls
        #: (a monolithic AOT program cannot degrade mid-run)
        self._degradable = any(
            getattr(getattr(op, "transformer", None), "optional", False)
            or getattr(getattr(op, "transformer", None), "fallback", None)
            is not None
            for op in self.graph.operators.values()
        )

    def __getstate__(self):
        state = dict(self.__dict__)
        # jitted callables are unpicklable; a cloned applier re-installs
        # from the bundle (ReplicaPool keeps it) or recompiles
        state["_bucket_programs"] = {}
        state["_artifact_meta"] = {}
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        # appliers pickled by older code lack the artifact fields
        self.__dict__.setdefault("_bucket_programs", {})
        self.__dict__.setdefault("_artifact_meta", {})
        self.__dict__.setdefault("_frozen_from", None)
        self.__dict__.setdefault("_degradable", True)
        self.__dict__.setdefault("plan", None)

    def __call__(self, data, deadline=None) -> Dataset:
        """Apply the frozen graph to one batch (a Dataset or batch-like
        array); returns the result Dataset.  ``deadline``: wall-clock
        budget for this batch, apportioned per stage by the executor.

        When an AOT bucket program is installed for the batch's exact
        shape/dtype (see :meth:`install_artifacts`), it runs instead of
        the executor walk — same math, one pre-lowered program.  A
        deadline-carrying call keeps the deadline contract: on a
        pipeline that declares degradation it takes the walk (per-stage
        watchdogs and substitutes need stage boundaries); otherwise the
        program runs under one whole-batch ``guard.run_with_deadline``
        watchdog, so an overrun still raises the typed
        ``DeadlineExceeded`` the walk would have.  A bucket program
        that fails at run time falls back to the walk for good and is
        counted (``serve.artifact_fallbacks``)."""
        ds = as_dataset(data)
        if (
            self._bucket_programs
            and not isinstance(ds, StreamDataset)
            and not ds.is_host
            and ds.mask is None
        ):
            # StreamDatasets are excluded BEFORE touching .array: an
            # out-of-core stream's .array materializes every batch, and
            # the walk streams them — shape-keyed programs can never
            # match a stream anyway
            if deadline is None or not self._degradable:
                key = (tuple(ds.array.shape), str(ds.array.dtype))
                fn = self._bucket_programs.get(key)
                if fn is not None:
                    from keystone_tpu.utils import guard

                    try:
                        if deadline is None:
                            out = fn(ds.array)
                        else:
                            # the walk apportions the budget per stage;
                            # a monolith gets it whole — an overrun is
                            # the same typed OSError either way
                            out = guard.run_with_deadline(
                                lambda: fn(ds.array),
                                guard.as_deadline(deadline),
                                site="serve.artifact",
                            )
                        return Dataset(out, n=ds.n, shard=False)
                    except guard.DeadlineExceeded:
                        # a genuine timeout, not a broken program: the
                        # caller's deadline contract fires; keep the
                        # program for the next flush
                        raise
                    except Exception as e:
                        # one failed program must not fail serving (or
                        # re-pay a doomed call per flush): drop it and
                        # walk — the compile tier takes over
                        self._bucket_programs.pop(key, None)
                        from keystone_tpu.obs import metrics

                        metrics.inc("serve.artifact_fallbacks")
                        import logging

                        logging.getLogger(__name__).warning(
                            "AOT bucket program %s failed (%s: %s); "
                            "falling back to the executor walk",
                            key,
                            type(e).__name__,
                            e,
                        )
        g, _ = self.graph.replace_source_with_node(
            self.source, G.DatasetOperator(ds)
        )
        ex = GraphExecutor(g, deadline=deadline)
        expr = ex.execute(g.sink_dependencies[self.sink])
        if not isinstance(expr, DatasetExpr):
            raise TypeError(
                f"frozen apply produced {type(expr).__name__}, expected dataset"
            )
        return expr.dataset

    # ------------------------------------------------------ AOT artifacts
    ARTIFACT_FORMAT = 1

    def fingerprint(self) -> str:
        """The pipeline signature hash artifacts are keyed by
        (``utils.hashing.pipeline_fingerprint`` of the pre-optimizer
        pipeline — structure + every fitted weight's bytes)."""
        if self._frozen_from is None:
            raise RuntimeError(
                "this FrozenApplier was pickled by an older version and "
                "lost its source pipeline; re-freeze to use artifacts"
            )
        from keystone_tpu.utils.hashing import pipeline_fingerprint

        return pipeline_fingerprint(self._frozen_from)

    def _bucket_callable(self):
        """The whole frozen apply as ONE traceable function of the
        padded batch — what gets lowered per bucket.  Host stages,
        data-dependent Python, and anything else untraceable raise at
        trace time; callers treat that as \"this pipeline has no
        artifact tier\" and ride the compile ladder."""
        graph, source, sink = self.graph, self.source, self.sink

        def run(x):
            ds = Dataset(x, n=x.shape[0], shard=False)
            g, _ = graph.replace_source_with_node(
                source, G.DatasetOperator(ds)
            )
            ex = GraphExecutor(g)
            expr = ex.execute(g.sink_dependencies[sink])
            if not isinstance(expr, DatasetExpr):
                raise TypeError(
                    f"frozen apply produced {type(expr).__name__}, "
                    "expected dataset"
                )
            return expr.dataset.array

        return run

    @staticmethod
    def _bucket_entry_key(rows: int) -> str:
        return f"b{int(rows):05d}"

    def export_artifacts(
        self, example=None, buckets=(8, 16, 32), item_shape=None, dtype=None
    ) -> dict:
        """Lower the frozen apply at every padding-bucket shape and
        serialize the programs with ``jax.export``; returns the artifact
        bundle ``{"manifest": {...}, "blobs": {entry: bytes}}`` the
        registry stores next to ``model.pkl``.

        Keyed by bucket shape/dtype, jax version, backend platform, and
        the pipeline's signature hash (:meth:`fingerprint`) — any skew
        at install time falls through to the compile ladder instead of
        replaying a stale program.  Fitted weights are embedded as
        program constants, so blobs scale with model size (they live
        next to the model blob, which carries the same bytes).

        ``example``: one datum (array) the per-item shape/dtype are read
        from; or pass ``item_shape``/``dtype`` explicitly."""
        import jax
        from jax import export as jexport

        import numpy as np

        if example is not None:
            ex = np.asarray(example)
            item_shape = tuple(ex.shape)
            dtype = ex.dtype
        if item_shape is None:
            raise ValueError(
                "export_artifacts needs the per-item shape: pass "
                "example=<one datum> or item_shape="
            )
        dtype = np.dtype(dtype if dtype is not None else np.float32)
        buckets = sorted({int(b) for b in buckets})
        if not buckets or min(buckets) < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets}")
        blobs: dict = {}
        entries: dict = {}
        platforms: set = set()
        fn = self._bucket_callable()
        for b in buckets:
            shape = (b,) + tuple(item_shape)
            exported = jexport.export(jax.jit(fn))(
                jax.ShapeDtypeStruct(shape, dtype)
            )
            platforms.update(exported.platforms)
            key = self._bucket_entry_key(b)
            blobs[key] = bytes(exported.serialize())
            entries[key] = {"rows": b, "file": f"{key}.hlo"}
        # the artifact ladder's remaining cold rung: a deserialized AOT
        # module still pays one BACKEND compile on first call.  With a
        # persistent compile cache active, run that compile NOW — on
        # the REHYDRATED program, so the cache key matches exactly what
        # a deploying host's install+first-call mints — and ship the
        # minted cache entries in the bundle.  seed_compile_cache()
        # installs them on the deploy host, whose first deploy then
        # skips even the backend compile.  Best-effort: no active
        # cache, no shipped entries.
        from keystone_tpu.utils.compile_cache import (
            collect_new_entries,
            snapshot_cache_entries,
        )

        before = snapshot_cache_entries()
        if before is not None:
            for b in buckets:
                key = self._bucket_entry_key(b)
                shape = (b,) + tuple(item_shape)
                try:
                    rehydrated = jexport.deserialize(bytearray(blobs[key]))
                    jax.jit(rehydrated.call).lower(
                        jax.ShapeDtypeStruct(shape, dtype)
                    ).compile()
                except Exception as e:
                    import logging

                    logging.getLogger(__name__).warning(
                        "cache pre-seed compile for bucket %d failed "
                        "(%s: %s); that rung ships without entries",
                        b,
                        type(e).__name__,
                        e,
                    )
            for i, (name, data) in enumerate(
                sorted(collect_new_entries(before).items())
            ):
                ckey = f"cache{i:03d}"
                blobs[ckey] = data
                entries[ckey] = {
                    "kind": "compile_cache",
                    "file": f"{ckey}.bin",
                    "name": name,
                }
        manifest = {
            "format": FrozenApplier.ARTIFACT_FORMAT,
            "jax_version": jax.__version__,
            "platforms": sorted(platforms),
            "signature": self.fingerprint(),
            "item_shape": list(item_shape),
            "dtype": str(dtype),
            "buckets": buckets,
            "entries": entries,
        }
        if getattr(self, "plan", None) is not None:
            # the PhysicalPlan ships INSIDE the manifest: it rides the
            # registry's blob-before-pointer publish (MANIFEST.json is
            # written last) and re-installs on every artifact install —
            # clone, worker spawn, swap, heal
            manifest["plan"] = self.plan.to_dict()
        return {"manifest": manifest, "blobs": blobs}

    def install_artifacts(
        self,
        bundle,
        device=None,
        signature=None,
        strict: bool = False,
        program_cache: Optional[dict] = None,
    ) -> int:
        """Deserialize an artifact bundle and register its bucket
        programs; returns how many were installed.

        The fallback ladder's first rung: ANY mismatch — format drift,
        jax version skew, wrong backend, signature drift, a corrupt
        blob — skips the offending artifact (counted as
        ``serve.artifact_fallbacks``) and leaves the compile tiers to
        serve, instead of failing the deploy.  ``strict=True`` raises
        instead (forensics).  ``device``: pin the programs' compilation
        to one device (the replica-fleet placement discipline);
        ``signature``: the expected pipeline hash, precomputed by the
        caller (default: :meth:`fingerprint`, which reads every fitted
        weight once).  ``program_cache``: a caller-owned dict keyed by
        (bundle signature, entry, device) of already-deserialized
        programs — the ReplicaPool shares one across replica builds and
        supervisor heals, so a replacement replica re-installs in
        microseconds instead of re-deserializing (compile time must
        not become recovery time); the programs are immutable pure
        functions, safe to share across worker generations."""
        import logging

        import jax
        from jax import export as jexport

        from keystone_tpu.obs import metrics

        log = logging.getLogger(__name__)

        def reject(why: str) -> int:
            if strict:
                raise ArtifactMismatch(why)
            metrics.inc("serve.artifact_fallbacks")
            log.warning("AOT artifacts rejected (%s); will compile", why)
            return 0

        manifest = (bundle or {}).get("manifest") or {}
        blobs = (bundle or {}).get("blobs") or {}
        if manifest.get("format") != FrozenApplier.ARTIFACT_FORMAT:
            return reject(f"unknown artifact format {manifest.get('format')!r}")
        if manifest.get("jax_version") != jax.__version__:
            return reject(
                f"jax version skew (artifact {manifest.get('jax_version')}, "
                f"running {jax.__version__})"
            )
        backend = jax.default_backend()
        if backend not in (manifest.get("platforms") or ()):
            return reject(
                f"backend skew (artifact {manifest.get('platforms')}, "
                f"running {backend!r})"
            )
        want = signature if signature is not None else self.fingerprint()
        if manifest.get("signature") != want:
            return reject(
                "pipeline signature drift (artifact "
                f"{manifest.get('signature')!r}, pipeline {want!r})"
            )
        plan_dict = manifest.get("plan")
        if plan_dict is not None:
            # past the reject ladder the bundle IS this pipeline's: its
            # plan is re-installed verbatim so a cloned replica / spawned
            # worker / swapped or healed fleet serves the planned
            # physical configuration, not whatever the env says here
            try:
                from keystone_tpu import planner

                self.plan = planner.PhysicalPlan.from_dict(plan_dict)
                planner.install_plan(self.plan, source="artifacts")
            except Exception as e:
                if strict:
                    raise ArtifactMismatch(f"plan failed to install: {e}")
                log.warning("shipped plan failed to install (%s)", e)
        item_shape = tuple(int(d) for d in manifest.get("item_shape") or ())
        dtype = str(manifest.get("dtype") or "float32")
        installed = 0
        for key, ent in (manifest.get("entries") or {}).items():
            if ent.get("kind") == "compile_cache" or "rows" not in ent:
                # shipped persistent-compile-cache entries ride the
                # bundle but are installed by seed_compile_cache(), not
                # registered as bucket programs
                continue
            cache_key = (manifest.get("signature"), key, device)
            call = (
                program_cache.get(cache_key)
                if program_cache is not None
                else None
            )
            if call is None:
                blob = blobs.get(key)
                if blob is None:
                    continue  # load-time skip already counted by the reader
                try:
                    exported = jexport.deserialize(bytearray(blob))
                    call = jax.jit(exported.call)
                except Exception as e:
                    if strict:
                        raise ArtifactMismatch(
                            f"artifact {key} failed to deserialize: {e}"
                        )
                    metrics.inc("serve.artifact_fallbacks")
                    log.warning(
                        "AOT artifact %s failed to deserialize (%s: %s); "
                        "that bucket will compile",
                        key,
                        type(e).__name__,
                        e,
                    )
                    continue
                if device is not None:
                    call = _pinned_to_device(call, device)
                if program_cache is not None:
                    program_cache[cache_key] = call
            shape = (int(ent["rows"]),) + item_shape
            self._bucket_programs[(shape, dtype)] = call
            self._artifact_meta[(shape, dtype)] = {
                "rows": int(ent["rows"]),
                "jax_version": manifest["jax_version"],
            }
            installed += 1
        return installed

    def has_bucket_program(self, shape, dtype) -> bool:
        import numpy as np

        return (tuple(shape), str(np.dtype(dtype))) in self._bucket_programs

    def installed_buckets(self) -> int:
        """How many AOT bucket programs this applier currently holds."""
        return len(self._bucket_programs)


class ArtifactMismatch(RuntimeError):
    """An AOT artifact bundle does not match this process/pipeline
    (format, jax version, backend, or pipeline signature) — raised only
    under ``install_artifacts(strict=True)``; the serving path counts
    the mismatch and falls through to the compile ladder instead."""


def _pinned_to_device(fn, device):
    """Wrap an AOT program so its (first-call) compilation and constants
    land on ``device`` — the replica fleet's one-replica-one-device
    placement discipline; without this every replica's artifact program
    would compute on the default device."""
    import jax

    def call(x):
        with jax.default_device(device):
            return fn(x)

    return call


class PreflightOOMError(RuntimeError):
    """``fit()`` refused to start: the predicted resident footprint
    exceeds the device's HBM limit and auto-spill is disabled
    (``KEYSTONE_AUTO_SPILL=0``).  The message carries the predicted
    bytes and the ``--stream`` pointer."""


def _auto_out_of_core(g):
    """No ``fit()`` may OOM the chip (round-4 review item 2; the reference's
    AutoCacheRule owns memory decisions so the user doesn't —
    workflow/AutoCacheRule.scala).

    The profiled materialization pass already priced every shared output
    against the HBM budget; this pre-flight compares its estimate (plus
    the in-memory source bytes) against the device limit.  The estimate
    is a STRUCTURAL UNDER-count — unshared memoized outputs, the
    gathered solver features, solver state, and in-program transients
    (e.g. the FV γ tensor) ride on top of it.  Measured calibration
    (r5, this chip): the n=16384 north-star fit OOMs 16 GB HBM at a
    predicted 9.1 GB (≥1.8× under), while n=8192 (predicted 4.5 GB)
    completes in-memory — hence the 0.45 default fraction, which
    separates those two cases on a 16 GB device.  Over budget, the
    large device-array sources are
    converted to StreamDatasets over the same rows — downstream
    featurization then streams batch-by-batch and the solvers spill
    features to a FeatureBlockStore, the standard out-of-core path the
    ``--stream`` apps exercise (tests/test_stream_e2e.py asserts
    stream == in-memory bit-parity).  ``KEYSTONE_AUTO_SPILL=0`` refuses
    instead with the predicted footprint (PreflightOOMError)."""
    import logging

    import numpy as np

    from keystone_tpu.workflow import profiling
    from keystone_tpu.workflow.dataset import StreamDataset

    sources = []
    for n, op in g.operators.items():
        if isinstance(op, G.DatasetOperator):
            ds = as_dataset(op.dataset)
            if (
                not isinstance(ds, StreamDataset)
                and not ds.is_host
                and ds.mask is None
            ):
                sources.append((n, ds, ds.array.nbytes))
    source_bytes = sum(b for _, _, b in sources)
    shared_bytes = int(profiling.last_footprint.get("shared_bytes", 0))
    # consume-once: the estimate belongs to THIS fit's materialize pass;
    # a later fit whose pass takes the structural fallback must not
    # inherit it (profiling.py clears at pass start too)
    profiling.last_footprint.clear()
    predicted = source_bytes + shared_bytes
    frac = float(os.environ.get("KEYSTONE_OOC_FRACTION", "0.45"))
    limit = profiling.device_hbm_budget(fraction=frac)
    if predicted <= limit or not sources:
        return g
    if os.environ.get("KEYSTONE_AUTO_SPILL", "1") == "0":
        raise PreflightOOMError(
            f"fit() pre-flight: predicted resident footprint ~"
            f"{predicted / 1e9:.2f} GB (sources {source_bytes / 1e9:.2f} GB "
            f"+ shared featurized outputs {shared_bytes / 1e9:.2f} GB) "
            f"exceeds {frac:.0%} of device HBM ({limit / 1e9:.2f} GB). "
            "Load the training data as a stream (app flag --stream / "
            "--out-of-core, or build with a StreamDataset) so features "
            "spill to the disk block store, or re-enable auto-spill "
            "(unset KEYSTONE_AUTO_SPILL)."
        )
    # 512-row spill batches: the auto-spill stream pays one dispatch per
    # batch per stage per sweep — 64-row batches made the n=16384 spill
    # fit dispatch-bound (rounds 1–5, not re-measured: >35 min); 512
    # cuts the dispatch count 8×
    # while the largest per-batch transient (512×361×128 f32 SIFT
    # descriptors ≈ 94 MB) stays far under any HBM pressure
    batch = int(os.environ.get("KEYSTONE_SPILL_BATCH", "512"))
    biggest = max(b for _, _, b in sources)
    for n, ds, b in sources:
        # spill the batch-carrying sources; parameter-sized datasets
        # (labels, constants) stay resident — streaming them buys no
        # HBM and some estimators require in-memory labels
        if b < max(1 << 20, biggest // 8):
            continue
        arr = np.asarray(ds.array[: ds.n])  # one device→host read

        def batches(_arr=arr):
            for i in range(0, _arr.shape[0], batch):
                yield _arr[i : i + batch]

        stream = StreamDataset(batches, n=ds.n, name=ds.name)
        g = g.set_operator(n, G.DatasetOperator(stream))
        logging.getLogger(__name__).warning(
            "fit() pre-flight: predicted footprint %.2f GB exceeds %.2f GB "
            "HBM budget; source %s (%.2f GB) converted to a stream — "
            "features will spill to the disk block store "
            "(KEYSTONE_AUTO_SPILL=0 to refuse instead)",
            predicted / 1e9,
            limit / 1e9,
            ds.name or "dataset",
            b / 1e9,
        )
    return g


def fit_relevant_config(config, exclude=()):
    """App Config dataclass → dict of FIT-relevant fields for
    ``fit_or_load``'s staleness check.

    Eval-only knobs must not invalidate a saved model — fitting once and
    scoring new test sets later is the feature's purpose — so fields that
    only affect evaluation inputs are dropped: the model path itself,
    test-set paths, and view-patch size.  Anything that changes the
    FITTED ARTIFACT (featurizer params, solver params, train paths,
    ImageNet's augmented_eval — which persists a scorer instead of a
    classifier) stays.  ``exclude`` adds app-specific eval-only fields.
    """
    import dataclasses

    d = dataclasses.asdict(config)
    eval_only = {
        "model_path",
        "test_path",
        "test_features_path",
        "test_labels_path",
        "view_patch",
        # execution strategy, not model identity: streaming the same
        # data fits the same model (to fp tolerance), so a saved model
        # stays valid across in-memory/out-of-core runs
        "stream",
        "stream_batch_size",
    } | set(exclude)
    for k in eval_only:
        d.pop(k, None)
    return d


class PipelineDataset:
    """Lazy result of applying a pipeline to a dataset
    (workflow/Pipeline.scala § PipelineDataset).  ``get()`` triggers
    optimize + execute; the result is cached."""

    def __init__(self, graph: G.Graph, sink: G.SinkId):
        self.graph = graph
        self.sink = sink
        self._result: Optional[Dataset] = None

    def get(self, deadline=None) -> Dataset:
        """Trigger optimize + execute (cached).  ``deadline``: wall-clock
        budget for the apply, apportioned per stage by the executor —
        the scoring-path twin of ``Pipeline.fit(deadline=…)``."""
        if self._result is None:
            expr = _apply(self.graph, self.sink, deadline)
            if not isinstance(expr, DatasetExpr):
                raise TypeError(f"sink produced {type(expr).__name__}, expected dataset")
            self._result = expr.dataset
        return self._result

    def numpy(self):
        return self.get().numpy()


class PipelineDatum:
    """Lazy single-datum result (workflow/Pipeline.scala § PipelineDatum)."""

    def __init__(self, graph: G.Graph, sink: G.SinkId):
        self.graph = graph
        self.sink = sink
        self._result = None
        self._done = False

    def get(self, deadline=None):
        if not self._done:
            expr = _apply(self.graph, self.sink, deadline)
            if not isinstance(expr, DatumExpr):
                raise TypeError(f"sink produced {type(expr).__name__}, expected datum")
            self._result = expr.value
            self._done = True
        return self._result


# ----------------------------------------------------------------- helpers
def _optimize(graph: G.Graph, *passes) -> G.Graph:
    """``graph`` through ``passes`` (default: the optimizer's rule batches)
    inside one ``pipeline.optimize`` span, whose ``nodes`` reads the
    graph's size before at its start and after at its end.  At its end it
    also says what the nodes' signatures cost inside it
    (``utils/hashing.py``): ``sig_bytes_hashed``, the bytes of weights
    copied to the host to be digested, and ``sig_by_recipe``, the nodes
    that signed with the recipe of a seeded draw and copied nothing."""
    from keystone_tpu.obs import ledger
    from keystone_tpu.utils.hashing import tally_signatures

    with ledger.span("pipeline.optimize", nodes=len(graph.operators)) as sp:
        with tally_signatures() as sigs:
            for run in passes or (PipelineEnv.get_optimizer().execute,):
                graph = run(graph)
        sp.set(
            nodes=len(graph.operators),
            sig_bytes_hashed=sigs.bytes_hashed,
            sig_by_recipe=sigs.by_recipe,
        )
    return graph


def _apply(graph: G.Graph, sink: G.SinkId, deadline):
    """Optimize + execute of one lazy result (a scoring call optimizes its
    graph again every time), inside one ``pipeline.apply`` span."""
    from keystone_tpu.obs import ledger

    with ledger.span("pipeline.apply"):
        g = _optimize(graph)
        ex = GraphExecutor(g, deadline=deadline)
        return ex.execute(g.sink_dependencies.get(sink, sink))


def _splice_input(g: G.Graph, data):
    """Attach ``data`` (literal dataset or lazy PipelineDataset graph) to
    ``g``; returns (graph, dependency id of the data's value)."""
    if isinstance(data, PipelineDataset):
        g2, mapping = g.union(data.graph)
        dep = g2.sink_dependencies[mapping[data.sink]]
        g2 = g2.remove_sink(mapping[data.sink])
        return g2, dep
    ds = as_dataset(data)
    g2, node = g.add_node(G.DatasetOperator(ds), ())
    return g2, node


def _prune_unreachable(
    g: G.Graph, sink: G.SinkId, keep_sources: Sequence[G.SourceId]
) -> G.Graph:
    keep = set(keep_sources)
    keep.add(g.sink_dependencies[sink])
    keep.update(g.ancestors(g.sink_dependencies[sink]))
    return G.Graph(
        tuple(s for s in g.sources if s in keep),
        {n: op for n, op in g.operators.items() if n in keep},
        {n: ds for n, ds in g.dependencies.items() if n in keep},
        {sink: g.sink_dependencies[sink]},
    )


def _is_batchlike(x) -> bool:
    import numpy as np

    return isinstance(x, (list, tuple)) or (hasattr(x, "ndim") and x.ndim >= 1)
