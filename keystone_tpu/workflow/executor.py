"""Demand-driven, memoizing DAG executor.

Reference: workflow/GraphExecutor.scala § GraphExecutor — a topological
demand-driven walk that memoizes per-node results ("Expressions"); fit
nodes execute once and their fitted transformers are reused by all
dependents.

Results here are:
  - DatasetExpr: a sharded device-array Dataset (or host list)
  - DatumExpr: a single value
  - TransformerExpr: a fitted Transformer (output of estimator nodes)
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from keystone_tpu.workflow import graph as G
from keystone_tpu.workflow.dataset import Dataset, as_dataset
from keystone_tpu.workflow.estimator import Estimator, LabelEstimator
from keystone_tpu.workflow.transformer import Transformer

logger = logging.getLogger(__name__)

#: per-process monotonic discriminators for signatureless nodes'
#: breaker keys (see GraphExecutor._stage_breaker): stamped on the
#: transformer/operator object so the key is stable for the object's
#: lifetime and never recycled the way id() addresses are
_BREAKER_TOKENS = itertools.count()


@dataclasses.dataclass
class DatumExpr:
    value: Any


@dataclasses.dataclass
class DatasetExpr:
    dataset: Dataset


@dataclasses.dataclass
class TransformerExpr:
    transformer: Transformer


class GraphExecutor:
    def __init__(
        self,
        graph: G.Graph,
        profile: bool = False,
        node_retries: Optional[int] = None,
        deadline=None,
        stage_pool=None,
        pool_token=None,
        pool_sigs=None,
    ):
        """``node_retries``: re-run a failed stage up to this many times
        before propagating (SURVEY §5 "failure detection/elastic
        recovery" — the coarse analogue of Spark task retry: stages are
        pure functions of memoized inputs, so re-running one is always
        safe).  Default (None) resolves PipelineEnv.node_retries /
        KEYSTONE_STAGE_RETRIES, so EVERY executor the framework creates
        honors the knob without per-site plumbing.  Deterministic
        failures still propagate after the budget; process-level
        recovery is workflow/recovery.py.

        ``deadline``: a wall-clock budget (seconds, or a
        ``utils.guard.Deadline``) for THIS executor's whole walk —
        ``Pipeline.fit(deadline=…)`` and the lazy ``get(deadline=…)``
        results plumb through here.  Each stage attempt runs under a
        watchdog whose budget is the overall remaining time apportioned
        over the not-yet-executed nodes, further capped by the
        ``KEYSTONE_STAGE_DEADLINE`` per-stage env knob; an overrun
        raises ``DeadlineExceeded`` (an ``OSError``) INSIDE the retry
        scope, so a hung stage is retried — and, for nodes declaring
        ``optional=True`` / ``with_fallback``, degraded — like any
        transient fault.  With neither a deadline nor
        ``KEYSTONE_BREAKER_THRESHOLD`` configured the per-stage cost is
        one ``None`` check (no watchdog thread, no breaker lookup).

        ``stage_pool``/``pool_token``/``pool_sigs``: the cross-pipeline
        shared-stage tier (ISSUE 14 — the cache-ownership inversion).
        Per-run memoization stays in ``self.results`` exactly as
        before, but nodes listed in ``pool_sigs`` (``{NodeId:
        normalized prefix signature}``, planned by ``workflow/cross.py``)
        additionally read through and publish into the process-wide
        :class:`~keystone_tpu.workflow.stage_pool.SharedStagePool`
        under ``(signature, pool_token)`` — so co-served tenant walks
        over the same flush compute each shared prefix ONCE, and a pool
        hit prunes the whole prefix sub-walk.  All three default to
        None/empty: the pre-pool walk is byte-identical (pinned)."""
        from keystone_tpu.utils import guard

        self.graph = graph
        self.results: Dict[G.GraphId, Any] = {}
        self.profile = profile
        if node_retries is None:
            from keystone_tpu.workflow.pipeline import PipelineEnv

            node_retries = PipelineEnv.stage_retries()
        self.node_retries = max(0, int(node_retries))
        self.timings: Dict[G.NodeId, float] = {}
        self.deadline = guard.as_deadline(deadline)
        self._stage_seconds = guard.stage_deadline_seconds()
        self._breaker_threshold = guard.stage_breaker_threshold()
        #: the shared-stage tier is active only when ALL THREE are
        #: given: a pool without a token could leak results across
        #: different request batches
        self._pool = stage_pool if pool_token is not None else None
        self._pool_token = pool_token
        self._pool_sigs: Dict[G.NodeId, tuple] = dict(pool_sigs or {})

    def execute(self, target: G.GraphId):
        if isinstance(target, G.SinkId):
            target = self.graph.sink_dependencies[target]
        return self._eval(target)

    def _eval(self, target: G.GraphId):
        if target in self.results:
            return self.results[target]
        if isinstance(target, G.SourceId):
            raise RuntimeError(
                f"unbound source {target}: apply the pipeline to data before executing"
            )
        op = self.graph.operators[target]
        # shared-stage pool read-through BEFORE the dep walk: a hit on
        # the sharing frontier prunes the whole prefix sub-walk (that
        # pruning IS the multi-tenant win — the first co-served tenant
        # computed it this flush).  Key = (content-addressed prefix
        # signature, flush token): results can never leak across
        # different request batches.
        pool_sig = (
            self._pool_sigs.get(target) if self._pool is not None else None
        )
        if pool_sig is not None:
            hit, pooled = self._pool.get((pool_sig, self._pool_token))
            if hit:
                self.results[target] = pooled
                return pooled
        deps = [self._eval(d) for d in self.graph.dependencies[target]]
        from keystone_tpu.obs import ledger, metrics
        from keystone_tpu.utils import guard

        brk = self._stage_breaker(op, target)
        delays = None
        failed_seconds = 0.0
        degraded = False
        attempts_made = 0
        with ledger.span(
            "executor.stage", node=op.label(), node_id=target.id
        ) as sp:
            if brk is not None and not brk.allow():
                # the node's breaker is open: don't spend an attempt (or
                # deadline budget) on a stage presumed broken — degrade
                # immediately, or refuse with CircuitOpenError
                t0 = time.perf_counter()
                result = self._degrade(op, deps, reason="breaker_open")
                degraded = True
            else:
                for attempt in range(self.node_retries + 1):
                    attempts_made = attempt + 1
                    # t0 restarts per attempt: profile timings charge each
                    # node ONLY its successful attempt — failed attempts and
                    # the retry backoff sleeps used to skew
                    # ProfilingAutoCacheRule placement (a flaky node looked
                    # expensive exactly when it should not have)
                    t0 = time.perf_counter()
                    try:
                        # the fault site sits INSIDE the retry scope — and
                        # inside the watchdog, so an injected hang is
                        # converted to DeadlineExceeded (an OSError) and
                        # retried/degraded exactly like a raised fault,
                        # which is what the chaos tests assert
                        from keystone_tpu.faults import fault_point

                        def _run():
                            fault_point("executor.stage", node=op.label())
                            return self._execute_op(op, deps)

                        result = guard.run_with_deadline(
                            _run,
                            self._attempt_deadline(),
                            site="executor.stage",
                            node=op.label(),
                        )
                        if brk is not None:
                            brk.record_success()
                        break
                    except Exception as e:
                        failed_seconds += time.perf_counter() - t0
                        # a blown EXECUTOR-wide budget ends the stage's
                        # retry loop immediately: every further attempt
                        # would be born expired, and the backoff sleeps
                        # alone could overshoot the promised wall-clock
                        # bound by node_retries × max_delay per node.
                        # Likewise a breaker THIS failure just opened:
                        # retrying against it repeats exactly the cost
                        # the breaker exists to stop paying (state(),
                        # not allow(), so no half-open probe is consumed)
                        budget_blown = (
                            self.deadline is not None and self.deadline.expired()
                        )
                        if brk is not None and not budget_blown:
                            # born-expired attempts after the run budget
                            # blew are artifacts of the OVERALL deadline,
                            # not evidence about this node — charging
                            # them would open healthy nodes' breakers
                            # (which persist across fits in-process)
                            brk.record_failure()
                        breaker_opened = (
                            brk is not None and brk.state() == guard.OPEN
                        )
                        if (
                            attempt >= self.node_retries
                            or budget_blown
                            or breaker_opened
                        ):
                            if _degradable(op) is not None:
                                # budget spent on a node that declared a
                                # substitute: degrade instead of failing
                                # the whole run.  t0 restarts so profile
                                # timings charge the node only the
                                # SUBSTITUTE's cost — the failed attempt
                                # (possibly a full deadline wait) is
                                # retry-budget cost, not compute profile
                                t0 = time.perf_counter()
                                result = self._degrade(
                                    op, deps, reason="budget_exhausted", error=e
                                )
                                degraded = True
                                break
                            if failed_seconds:
                                metrics.inc(
                                    "executor.failed_attempt_seconds", failed_seconds
                                )
                            raise
                        metrics.inc("executor.stage_retries")
                        ledger.event(
                            "executor.retry",
                            node=op.label(),
                            attempt=attempt + 1,
                            error=f"{type(e).__name__}: {e}"[:200],
                        )
                        logger.warning(
                            "stage %s failed (%s); retry %d/%d",
                            op.label(),
                            e,
                            attempt + 1,
                            self.node_retries,
                        )
                        # brief backoff (+jitter) before the re-run: transient
                        # causes (preemption, flaky interconnect) need a beat to
                        # clear, and decorrelating parallel executors helps
                        if delays is None:
                            from keystone_tpu.utils.durable import backoff_delays

                            delays = iter(
                                backoff_delays(
                                    self.node_retries, base_delay=0.05, max_delay=1.0
                                )
                            )
                        time.sleep(next(delays, 1.0))
            if failed_seconds:
                # failed-attempt time is real cost, but it belongs to the
                # RETRY budget, not the node's compute profile
                metrics.inc("executor.failed_attempt_seconds", failed_seconds)
            # attempts = stage-body executions actually started (0
            # when the breaker refused the stage outright)
            sp.set(attempts=attempts_made, retries=max(0, attempts_made - 1))
            if degraded:
                sp.set(degraded=True)
            if failed_seconds:
                sp.set(failed_attempt_seconds=failed_seconds)
            if self.profile:
                _sync_expr(result)
                self.timings[target] = time.perf_counter() - t0
        if pool_sig is not None and not degraded:
            # publish for the flush's co-served tenants.  NEVER publish
            # a degraded result: a substitute's output is this run's
            # compromise, not the stage's value — sharing it would
            # silently degrade every other tenant too.
            self._pool.put((pool_sig, self._pool_token), result)
        if not getattr(op, "no_memoize", False):
            # no_memoize nodes (over the HBM budget — workflow/profiling.py)
            # recompute per consumer instead of pinning their output
            self.results[target] = result
        return result

    def _attempt_deadline(self):
        """Per-attempt watchdog budget, or None (the inert path: no
        thread is spawned).  With an executor-wide deadline, the
        remaining time is apportioned evenly over not-yet-executed
        nodes — recomputed each stage, so early finishers donate their
        slack — and never outlives the overall deadline; the
        KEYSTONE_STAGE_DEADLINE env knob caps each attempt on top."""
        from keystone_tpu.utils import guard

        if self.deadline is None:
            if self._stage_seconds is None:
                return None
            return guard.Deadline.after(self._stage_seconds)
        remaining_nodes = max(1, len(self.graph.operators) - len(self.results))
        share = self.deadline.remaining() / remaining_nodes
        if self._stage_seconds is not None:
            share = min(share, self._stage_seconds)
        return self.deadline.child(share)

    def _stage_breaker(self, op, target):
        """The node's circuit breaker, or None when breakers are off
        (no KEYSTONE_BREAKER_THRESHOLD — the default, costing one
        attribute check per stage).

        Key choice: label alone collides (every DelegatingOperator is
        labelled 'apply'; same-class transformers share a class name),
        and one flaky node must never open the breaker of a healthy
        twin.  The key therefore adds the transformer's stable
        ``signature()`` when it has one — parameter-identical nodes
        share breaker state across executors/fits in this process,
        which is the registry's point — and falls back to the
        transformer/operator OBJECT identity for signatureless nodes
        (graph node ids restart per graph, so they would collide across
        independently-built pipelines; object identity persists across
        executors over the same graph, which is the case that matters)."""
        if self._breaker_threshold is None:
            return None
        from keystone_tpu.utils import guard

        t = getattr(op, "transformer", None)
        sig = None
        if t is not None:
            try:
                sig = t.signature()
            except Exception:
                sig = None
        if sig is not None:
            disc = f"{hash(sig) & 0xFFFFFFFF:08x}"
        else:
            # monotonic token stamped on the object, NOT id(): the
            # registry outlives the graph, and CPython readily recycles
            # a freed object's address — an id key could hand a healthy
            # new node a dead node's OPEN breaker
            obj = t if t is not None else op
            disc = getattr(obj, "_breaker_token", None)
            if disc is None:
                disc = f"t{next(_BREAKER_TOKENS)}"
                try:
                    obj._breaker_token = disc
                except AttributeError:
                    # unwritable object (slots/frozen): fall back to a
                    # fresh token per executor construction — state
                    # persists within this executor's walk only
                    pass
        return guard.breaker(
            f"executor.stage:{op.label()}:{disc}",
            threshold=self._breaker_threshold,
        )

    def _degrade(self, op, deps, reason: str, error=None):
        """Apply the node's degradation substitute (declared fallback,
        or Identity for ``optional`` nodes) instead of the node itself,
        emitting the ``degraded`` ledger event + counter.  A
        non-degradable node refused by its breaker raises
        ``CircuitOpenError`` — the run fails loudly, never silently
        skips a mandatory stage."""
        from keystone_tpu.obs import ledger, metrics
        from keystone_tpu.utils import guard

        sub = _degradable(op)
        if sub is None:
            raise guard.CircuitOpenError(
                f"stage {op.label()!r}: circuit breaker is open and the "
                "node declares no fallback/optional degradation"
            )
        metrics.inc("executor.degraded", node=op.label())
        ledger.event(
            "degraded",
            node=op.label(),
            substitute=sub.label,
            reason=reason,
            error=None
            if error is None
            else f"{type(error).__name__}: {error}"[:200],
        )
        logger.warning(
            "stage %s degraded to %s (%s)", op.label(), sub.label, reason
        )
        return _apply_transformer(sub, deps)

    def _execute_op(self, op: G.Operator, deps):
        if isinstance(op, G.DatasetOperator):
            return DatasetExpr(as_dataset(op.dataset))
        if isinstance(op, G.DatumOperator):
            return DatumExpr(op.datum)
        if isinstance(op, G.TransformerOperator):
            return _apply_transformer(op.transformer, deps)
        if isinstance(op, G.EstimatorOperator):
            return _fit_estimator(op.estimator, deps)
        if isinstance(op, G.DelegatingOperator):
            t = deps[0]
            if not isinstance(t, TransformerExpr):
                raise TypeError("DelegatingOperator expects a fitted transformer dep 0")
            return _apply_transformer(t.transformer, deps[1:])
        if isinstance(op, G.GatherOperator):
            return _gather(deps)
        raise TypeError(f"unknown operator {op!r}")


def _degradable(op):
    """The substitute transformer a failed node degrades to: its
    declared ``fallback``, :class:`Identity` for ``optional`` nodes,
    else None (the node is mandatory — failure propagates)."""
    t = getattr(op, "transformer", None)
    if t is None:
        return None
    fb = getattr(t, "fallback", None)
    if fb is not None:
        return fb
    if getattr(t, "optional", False):
        from keystone_tpu.workflow.transformer import Identity

        return Identity()
    return None


def block_on_arrays(obj, _seen=None, _depth=0, visit=None) -> None:
    """Block until every device array reachable from ``obj`` is computed.

    Transformers are plain objects, not pytrees, and solvers nest state
    (e.g. a model holding a scaler holding mean/std arrays) — a flat
    ``jax.tree.leaves(vars(t))`` walk stops at the nested object and
    misses its arrays, silently under-blocking.  This walks attributes,
    containers, and dataclass-like objects recursively (cycle-safe).

    ``visit``: optional callback applied to each device array INSTEAD of
    blocking — FittedPipeline.read_back uses it to gather one element
    of every array for its finiteness probe."""
    if _depth > 8:
        return
    if _seen is None:
        _seen = set()
    if id(obj) in _seen:
        return
    _seen.add(id(obj))
    if hasattr(obj, "block_until_ready"):
        if visit is not None:
            visit(obj)
        else:
            obj.block_until_ready()
        return
    if isinstance(obj, dict):
        children = list(obj.values())
    elif isinstance(obj, (list, tuple, set, frozenset)):
        children = list(obj)
    elif hasattr(obj, "__dict__") and not isinstance(obj, type):
        children = list(vars(obj).values())
    else:
        return
    for c in children:
        if c is not None and not isinstance(c, (str, bytes, int, float, bool)):
            block_on_arrays(c, _seen, _depth + 1, visit=visit)


def _sync_expr(result) -> None:
    """Block until a node's result is actually computed, so profile-mode
    timings charge each node its own device time.  Fit nodes return a
    Transformer (not a pytree) — block on every array it holds (including
    nested model state), else the async solve would be misattributed to
    the next dataset-producing node.  Every branch waits inside a
    ``device.wait`` span."""
    from keystone_tpu.obs import ledger

    if isinstance(result, DatasetExpr):
        result.dataset.cache()  # its own ``device.wait`` span
    elif isinstance(result, DatumExpr):
        with ledger.waiting():
            block_on_arrays(result.value)
    elif isinstance(result, TransformerExpr):
        with ledger.waiting():
            block_on_arrays(result.transformer)


def _apply_transformer(t: Transformer, deps):
    if len(deps) != 1:
        raise ValueError(f"{t.label}: transformers are unary, got {len(deps)} deps")
    d = deps[0]
    if isinstance(d, DatasetExpr):
        return DatasetExpr(t.apply_dataset(d.dataset))
    if isinstance(d, DatumExpr):
        return DatumExpr(t.apply_one(d.value))
    raise TypeError(f"{t.label}: cannot apply to {d!r}")


@jax.jit
def _gather_concat(arrays):
    """A gather's concatenation as one program, named ``jit__gather_concat``
    in a device trace.  Eager, ``jnp.concatenate`` cuts more than 16 arrays
    into a tree of concatenations that writes the whole width twice: at
    200 branches of 12,288 x 512 a third 5 GB beside the branches and the
    result."""
    return jnp.concatenate(arrays, axis=-1)


def _gather(deps):
    from keystone_tpu.obs import ledger
    from keystone_tpu.workflow.dataset import StreamDataset

    if all(isinstance(d, DatasetExpr) for d in deps):
        if any(isinstance(d.dataset, StreamDataset) for d in deps):
            if not all(isinstance(d.dataset, StreamDataset) for d in deps):
                raise TypeError(
                    "Gather mixes streaming and materialized branches; "
                    "the branches of one source are either all streams or none"
                )
            return DatasetExpr(StreamDataset.zip_concat([d.dataset for d in deps]))
        base = deps[0].dataset
        arrs = [d.dataset.array for d in deps]
        with ledger.span("executor.gather", branches=len(arrs), rows=int(arrs[0].shape[0]),
                         bytes=sum(int(a.nbytes) for a in arrs)):
            return DatasetExpr(base.with_array(_gather_concat(arrs)))
    if all(isinstance(d, DatumExpr) for d in deps):
        return DatumExpr(jnp.concatenate([jnp.asarray(d.value) for d in deps], axis=-1))
    raise TypeError("Gather expects homogeneous dataset or datum deps")


def _fit_estimator(est: Estimator, deps):
    data = deps[0]
    if not isinstance(data, DatasetExpr):
        raise TypeError(f"{est.label}.fit expects a dataset dependency")
    if isinstance(est, LabelEstimator):
        if len(deps) < 2 or not isinstance(deps[1], DatasetExpr):
            raise TypeError(f"{est.label}.fit expects (data, labels) dataset deps")
        fitted = est.fit_dataset(data.dataset, deps[1].dataset)
    else:
        fitted = est.fit_dataset(data.dataset)
    return TransformerExpr(fitted)
