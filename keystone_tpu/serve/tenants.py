"""Multi-tenant serving: N pipelines, one fleet, shared prefixes once.

A production deployment serves many heads over the same featurization
(one SIFT/FV/Nyström front end feeding per-customer classifiers).
Served as N independent :class:`~keystone_tpu.serve.service.PipelineService`
instances, every tenant's flush recomputes the shared prefix; this
module co-serves them behind ONE batcher + replica fleet and computes
each shared prefix once per combined flush:

- :class:`MultiTenantApplier` — the frozen-apply unit the
  :class:`~keystone_tpu.serve.fleet.ReplicaPool` replicates: one
  :class:`~keystone_tpu.workflow.pipeline.FrozenApplier` per tenant
  plus the cross-pipeline :class:`~keystone_tpu.workflow.cross.SharingPlan`
  (shared-prefix signatures, collision-gated).  Applying a flush walks
  each tenant's graph over the SAME bound batch under one flush token;
  the walks read marked stages through the process-wide
  :class:`~keystone_tpu.workflow.stage_pool.SharedStagePool`, so the
  first tenant computes the shared prefix and every co-tenant's walk
  prunes at the pool hit.
- :class:`MultiTenantService` — per-tenant admission queues with
  per-tenant quotas and default deadlines, deficit-round-robin flush
  scheduling (fair share of every combined flush under unequal offered
  load), per-tenant circuit breakers (a tenant whose requests keep
  failing is refused at ITS admission, nobody else's), per-tenant
  metrics/latency windows/SLO burn rate in ``/statusz``, and
  tenant-contained flush failures: a tenant-targeted ``serve.batch``
  fault (``ctx.tenant=``) fails that tenant's riders only — co-flushed
  tenants deliver.

Fairness/batching: the batcher drains the per-tenant queues with
classic deficit round robin (quantum = ``max_batch / active tenants``
rows per round), then orders the flush tenant-contiguously so each
tenant's rows form one segment of the combined padded batch.  Each
tenant's HEAD runs over the full padded batch (heads are cheap; the
shared prefix is the cost) and its rows are sliced out at delivery.

Single-tenant degeneration is pinned: with one tenant the sharing plan
is empty, the executor takes the identical pre-pool walk, and
predictions are byte-identical to a plain ``PipelineService`` over the
same pipeline (tests/test_multitenant.py).
"""

from __future__ import annotations

import itertools
import logging
import threading
import time
from collections import deque
from concurrent.futures import InvalidStateError
from typing import Dict, Optional

import numpy as np

from keystone_tpu.faults import fault_point
from keystone_tpu.obs import ledger, metrics
from keystone_tpu.serve.service import Overloaded, PipelineService
from keystone_tpu.utils import guard
from keystone_tpu.workflow import graph as G
from keystone_tpu.workflow.cross import plan_sharing
from keystone_tpu.workflow.stage_pool import (
    SharedStagePool,
    default_pool,
    pool_by_token,
)

logger = logging.getLogger(__name__)

metrics.register_buckets(
    "serve.tenant_latency_seconds", metrics.LATENCY_MS_BUCKETS
)
metrics.register_buckets(
    "serve.tenant_failed_wait_seconds", metrics.LATENCY_MS_BUCKETS
)

#: process-wide flush-token mint — tokens must never repeat while any
#: pool entry lives, and never collide across co-resident services
_TOKENS = itertools.count(1)

#: per-service registration namespace mint: two co-resident services
#: (blue/green, bench A/B arms) may share tenant NAMES — registrations
#: on the shared default pool must not clobber each other
_OWNERS = itertools.count(1)


class UnknownTenant(TypeError):
    """The request names a tenant this service does not serve — the
    CLIENT's fault (a ``TypeError`` like the shape-contract violation:
    HTTP 400, no SLO burn)."""


def _freeze(pipeline):
    from keystone_tpu.workflow.pipeline import FrozenApplier

    return (
        pipeline
        if isinstance(pipeline, FrozenApplier)
        else FrozenApplier(pipeline)
    )


class MultiTenantApplier:
    """N frozen appliers + the cross-pipeline sharing plan, applied as
    one unit per combined flush.  This is what the
    :class:`~keystone_tpu.serve.fleet.ReplicaPool` clones per replica —
    the plan is plain data and pickles along; a clone's walks share the
    same pool entries because the keys are content-addressed, not
    instance-addressed."""

    #: duck-typed frozen-applier marker (serve/fleet._as_applier)
    serve_applier = True

    def __init__(self, models: Dict[str, object], pool=None, share: bool = True):
        if not models:
            raise ValueError("serve_multi needs at least one tenant model")
        self.appliers = {str(k): _freeze(p) for k, p in models.items()}
        self.share = bool(share)
        if share:
            self.plan = plan_sharing(
                {t: a.graph for t, a in self.appliers.items()}
            )
        else:
            from keystone_tpu.workflow.cross import SharingPlan

            self.plan = SharingPlan(
                {t: {} for t in self.appliers}, frozenset(), {}, 0
            )
        #: a private pool (tests / budget isolation).  The pool object
        #: holds a lock (unpicklable), so pickling keeps only its
        #: TOKEN — replica clones in this process re-resolve the SAME
        #: pool (stage_pool.pool_by_token), preserving the configured
        #: budget/registrations; a cross-process unpickle falls back to
        #: the process default (keys stay content+token addressed)
        self._pool = pool
        self._pool_ref = None if pool is None else pool.token
        if self.plan.shared:
            ledger.event(
                "serve.pool_plan",
                tenants=len(self.appliers),
                shared_stages=len(self.plan.shared),
                refused=self.plan.refused,
            )

    def pool(self) -> SharedStagePool:
        if self._pool is not None:
            return self._pool
        if self._pool_ref is not None:
            resolved = pool_by_token(self._pool_ref)
            if resolved is not None:
                self._pool = resolved
                return resolved
        return default_pool()

    def graphs(self):
        """Per-tenant graphs (serve/fleet device placement walks them)."""
        return [a.graph for a in self.appliers.values()]

    def __getstate__(self):
        state = dict(self.__dict__)
        state["_pool"] = None  # holds a lock; clones re-resolve via token
        return state

    # -------------------------------------------------------------- apply
    def __call__(self, ds, deadline=None, tenants=None, errors_out=None):
        """Walk every requested tenant's graph over ``ds`` under ONE
        flush token; returns ``{tenant: result Dataset}`` (each over the
        FULL batch — the service slices per-tenant rows out).

        ``errors_out``: a dict marks this a LIVE flush — each tenant's
        walk fires the ``serve.batch`` fault site with ``ctx.tenant``
        and a per-tenant failure is stored there instead of propagating
        (blast-radius containment: one tenant's poison/overload must
        not shed another's traffic).  ``None`` (priming, offline use)
        propagates the first failure after the pool flush is released."""
        pool = self.pool()
        names = list(self.appliers) if tenants is None else list(tenants)
        unknown = [t for t in names if t not in self.appliers]
        if unknown:
            raise UnknownTenant(f"unknown tenant(s) {unknown!r}")
        token = next(_TOKENS)
        pool.begin_flush(token, self.plan.sigs_for(names))
        outs: Dict[str, object] = {}
        first_error = None
        try:
            for t in names:
                try:
                    if tenants is not None:
                        # live flushes only (priming passes tenants=None):
                        # the tenant-scoped serve.batch fire is what lets
                        # a chaos plan target ONE tenant's flush work
                        fault_point("serve.batch", tenant=t)
                    outs[t] = self._walk(t, ds, deadline, pool, token)
                except BaseException as e:
                    if errors_out is None:
                        raise
                    errors_out[t] = e
                    metrics.inc("serve.tenant_batch_errors", tenant=t)
        finally:
            pool.end_flush(token)
        return outs

    def _walk(self, tenant: str, ds, deadline, pool, token):
        from keystone_tpu.workflow.executor import DatasetExpr, GraphExecutor

        a = self.appliers[tenant]
        g, _ = a.graph.replace_source_with_node(
            a.source, G.DatasetOperator(ds)
        )
        ex = GraphExecutor(
            g,
            deadline=deadline,
            stage_pool=pool,
            pool_token=token,
            pool_sigs=self.plan.node_sigs.get(tenant),
        )
        expr = ex.execute(g.sink_dependencies[a.sink])
        if not isinstance(expr, DatasetExpr):
            raise TypeError(
                f"tenant {tenant!r} apply produced "
                f"{type(expr).__name__}, expected dataset"
            )
        return expr.dataset


class MultiTenantService(PipelineService):
    """A :class:`PipelineService` serving N tenants through one batcher
    and one replica fleet, with the shared stage pool computing common
    featurization prefixes once per combined flush.  Construct via
    :func:`serve_multi`."""

    def __init__(
        self,
        models: Dict[str, object],
        *,
        share: bool = True,
        pool: Optional[SharedStagePool] = None,
        tenant_queue_bound: Optional[Dict[str, int]] = None,
        tenant_deadline_ms: Optional[Dict[str, float]] = None,
        tenant_breaker_threshold: Optional[int] = None,
        dedup: bool = False,
        **kw,
    ):
        if kw.get("workers") or kw.get("hosts") is not None:
            raise NotImplementedError(
                "multi-tenant serving runs in-process (the shared stage "
                "pool and per-tenant containment need the executor walk); "
                "workers= (process fleet) and hosts= (cross-host fleet) "
                "apply to single-tenant services"
            )
        applier = MultiTenantApplier(models, pool=pool, share=share)
        self.tenants = tuple(applier.appliers)
        self._mt_applier = applier
        # per-tenant state must exist BEFORE super().__init__: the base
        # constructor primes (broadcast apply) and starts the batcher
        # thread, which immediately calls the overridden _next_batch
        self._tq: Dict[str, deque] = {t: deque() for t in self.tenants}
        self._deficit: Dict[str, float] = {t: 0.0 for t in self.tenants}
        self._rr = 0
        self._tlat = {
            t: metrics.WindowedHistogram(
                "serve.tenant_latency_seconds", tenant=t
            )
            for t in self.tenants
        }
        self._tfail = {
            t: metrics.WindowedHistogram(
                "serve.tenant_failed_wait_seconds", tenant=t
            )
            for t in self.tenants
        }
        #: cross-request in-flight dedup (opt-in): identical concurrent
        #: payloads for the SAME tenant are computed once — the
        #: follower's future resolves from the leader's result,
        #: bit-identical.  Keyed per tenant: two tenants' identical
        #: payloads run different models and must never share.
        self._dedup = bool(dedup)
        self._dedup_lock = threading.Lock()
        self._dedup_inflight: Dict[tuple, object] = {}
        self._tenant_bounds = dict(tenant_queue_bound or {})
        self._tenant_deadline_s = {
            t: float(ms) / 1000.0
            for t, ms in (tenant_deadline_ms or {}).items()
        }
        #: per-tenant quota/deadline breakers (the guard layer): None
        #: threshold = off (the default, zero per-request cost)
        self._tenant_breakers = (
            {
                t: guard.CircuitBreaker(
                    f"serve.tenant.{t}",
                    threshold=int(tenant_breaker_threshold),
                )
                for t in self.tenants
            }
            if tenant_breaker_threshold
            else {}
        )
        super().__init__(applier, **kw)
        stage_pool = applier.pool()
        #: registrations are namespaced per SERVICE instance: a
        #: co-resident service closing its own tenant "a" must not
        #: unregister another service's live "a" on the shared pool
        self._pool_owner = f"{self.name}#{next(_OWNERS)}"
        for t in self.tenants:
            stage_pool.register_tenant(
                f"{self._pool_owner}:{t}",
                set(applier.plan.node_sigs.get(t, {}).values()),
            )
        # ProfilingAutoCacheRule-style placement at pool granularity:
        # priming observed every shared stage's output bytes, so the
        # pin set can be chosen under the budget now
        if applier.plan.shared and self._item_shape is not None:
            stage_pool.auto_pin()

    # --------------------------------------------------------- tenant hooks
    def _resolve_tenant(self, tenant):
        if tenant is None:
            if len(self.tenants) == 1:
                return self.tenants[0]
            raise UnknownTenant(
                f"service {self.name!r} serves tenants "
                f"{list(self.tenants)}; submit(tenant=...) is required"
            )
        tenant = str(tenant)
        if tenant not in self._tq:
            raise UnknownTenant(
                f"unknown tenant {tenant!r}; serving {list(self.tenants)}"
            )
        brk = self._tenant_breakers.get(tenant)
        if brk is not None and not brk.allow():
            raise guard.CircuitOpenError(
                f"tenant {tenant!r} breaker is open (repeated failures); "
                "admission refused for this tenant only"
            )
        return tenant

    def _default_deadline_for(self, tenant):
        return self._tenant_deadline_s.get(tenant, self.default_deadline_s)

    def _tenant_bound(self, tenant: str) -> int:
        """Per-tenant quota: explicit, else an equal share of the global
        bound — one tenant's burst can never occupy another's slots."""
        explicit = self._tenant_bounds.get(tenant)
        if explicit is not None:
            return int(explicit)
        return max(1, self.queue_bound // max(1, len(self.tenants)))

    def _check_bound_locked(self, n_new, tenant):
        q = self._tq[tenant]
        bound = self._tenant_bound(tenant)
        if len(q) + n_new > bound:
            metrics.inc("serve.rejected", n_new)
            raise Overloaded(
                f"tenant {tenant!r} queue at its quota ({bound}); "
                "retry later"
            )
        if self._queue_depth_locked() + n_new > self.queue_bound:
            metrics.inc("serve.rejected", n_new)
            raise Overloaded(
                f"service {self.name!r} queue at bound "
                f"({self.queue_bound}); retry later"
            )

    def _push_locked(self, reqs, tenant):
        q = self._tq[tenant]
        q.extend(reqs)
        depth = self._queue_depth_locked()
        metrics.set_gauge("serve.queue_depth", depth)
        metrics.set_gauge("serve.tenant_queue_depth", len(q), tenant=tenant)
        return depth

    def _queue_depth_locked(self) -> int:
        return sum(len(q) for q in self._tq.values())

    @property
    def queue_depth(self) -> int:
        return self._queue_depth_locked()

    def _fail_queued_locked(self, make_exc) -> None:
        for t, q in self._tq.items():
            while q:
                self._fail(q.popleft(), make_exc())
            metrics.set_gauge("serve.tenant_queue_depth", 0, tenant=t)
        metrics.set_gauge("serve.queue_depth", 0)

    def _account_admission(self, tenant, outcome, n):
        if tenant is None or tenant not in self._tq:
            return
        if outcome == "submitted":
            metrics.inc("serve.tenant_submitted", n, tenant=tenant)
        elif outcome == "rejected":
            metrics.inc("serve.tenant_rejected", n, tenant=tenant)
            for _ in range(n):
                self._tfail[tenant].observe(0.0)
        elif outcome in ("poison", "error"):
            metrics.inc("serve.tenant_errors", n, tenant=tenant)

    def _account_tenant(self, req, outcome, seconds):
        t = req.tenant
        if t is None or t not in self._tq:
            return
        brk = self._tenant_breakers.get(t)
        if outcome in ("completed", "degraded"):
            metrics.inc("serve.tenant_completed", tenant=t)
            self._tlat[t].observe(seconds)
            if brk is not None:
                brk.record_success()
            return
        if outcome == "shed":
            metrics.inc("serve.tenant_shed", tenant=t)
            self._tfail[t].observe(seconds)
            # a shed is the SERVICE's capacity decision, breaker-neutral
            return
        metrics.inc("serve.tenant_errors", tenant=t)
        self._tfail[t].observe(seconds)
        if brk is not None:
            brk.record_failure()

    # --------------------------------------------------------------- dedup
    def _dedup_keys(self, arrs):
        """Per-datum content digests (outside the admission lock —
        hashing payloads is the expensive part)."""
        if not self._dedup:
            return None
        from keystone_tpu.serve.service import _content_key

        return [_content_key(a) for a in arrs]

    def _dedup_match(self, tenant, keys) -> dict:
        """Map datum index → in-flight leader (an earlier unresolved
        request with identical content) or — for a duplicate WITHIN
        this call — the leading datum's index (resolved to its request
        by :meth:`_dedup_register` once the requests exist).  Holds the
        admission lock; the map lock nests inside."""
        followers: dict = {}
        local: dict = {}
        with self._dedup_lock:
            for i, k in enumerate(keys):
                mk = (tenant, k)
                if mk in local:
                    followers[i] = local[mk]  # datum index of the leader
                    continue
                cand = self._dedup_inflight.get(mk)
                if cand is not None and not cand.future.done():
                    followers[i] = cand
                else:
                    local[mk] = i  # this datum leads for mk
        return followers

    def _dedup_register(self, tenant, keys, reqs, followers) -> None:
        # resolve within-call followers (datum-index placeholders) to
        # their leader request objects now that requests exist
        for i, leader in list(followers.items()):
            if isinstance(leader, int):
                followers[i] = reqs[leader]
        with self._dedup_lock:
            for i, req in enumerate(reqs):
                if i in followers:
                    continue
                mk = (tenant, keys[i])
                self._dedup_inflight[mk] = req
                req.future.add_done_callback(self._dedup_cleanup(mk, req))

    def _dedup_cleanup(self, mk, req):
        def cb(_fut):
            with self._dedup_lock:
                if self._dedup_inflight.get(mk) is req:
                    del self._dedup_inflight[mk]

        return cb

    def _dedup_attach(self, followers: dict, reqs: list) -> None:
        """Fan the leader's outcome out to each follower (outside the
        admission lock).  Success delivers a COPY of the leader's
        result row — bit-identical, and a caller mutating its response
        can never corrupt a co-rider's.  Failure propagates the
        leader's typed error through the standard failure terminal."""
        metrics.inc("serve.dedup_hits", len(followers))
        rec = self.recorder
        for i, leader in followers.items():
            req = reqs[i]
            if rec is not None and req.request_id is not None:
                rec.annotate(
                    req.request_id,
                    "serve.dedup",
                    leader=leader.request_id,
                )

            def deliver(lf, req=req, leader=leader):
                try:
                    exc = lf.exception()
                except BaseException as e:  # a cancelled leader
                    exc = e
                if exc is not None:
                    self._fail(req, exc, leader=leader.request_id)
                    return
                waited = time.monotonic() - req.t_submit
                metrics.inc("serve.completed")
                self._lat_win.observe(waited)
                self._account_tenant(req, "completed", waited)
                if req.request_id is not None:
                    if rec is not None:
                        rec.finish(
                            req.request_id,
                            "completed",
                            only_live=True,
                            leader=leader.request_id,
                        )
                    if ledger.active() is not None:
                        ledger.event(
                            "serve.request",
                            request_id=req.request_id,
                            outcome="completed",
                            leader=leader.request_id,
                            seconds=round(waited, 6),
                        )
                try:
                    req.future.set_result(np.copy(lf.result()))
                except InvalidStateError:
                    pass  # the follower was cancelled meanwhile

            leader.future.add_done_callback(deliver)

    # ------------------------------------------------------------ batching
    def _next_batch(self):
        """Deficit-round-robin flush former: every active tenant earns
        ``max_batch / active`` row credits per round and spends them
        FIFO from its own queue, so a combined flush carries a fair
        share of each tenant's backlog no matter how unequal the
        offered loads are.  Riders are then ordered tenant-contiguously
        (stable within a tenant) so the flush's rows form one segment
        per tenant."""
        from keystone_tpu.serve.service import _Flush

        with self._cond:
            while self._queue_depth_locked() == 0:
                if self._closing:
                    return None
                self._cond.wait()
            oldest = min(q[0].t_submit for q in self._tq.values() if q)
            flush_at = oldest + self.max_wait_s
            while (
                self._queue_depth_locked() < self.max_batch
                and not self._closing
            ):
                timeout = flush_at - time.monotonic()
                if timeout <= 0:
                    break
                self._cond.wait(timeout)
            batch = self._drr_pop_locked()
            metrics.set_gauge("serve.queue_depth", self._queue_depth_locked())
            for t in self.tenants:
                metrics.set_gauge(
                    "serve.tenant_queue_depth", len(self._tq[t]), tenant=t
                )
            return _Flush(batch, f"b{next(self._batch_seq)}")

    def _drr_pop_locked(self) -> list:
        active = [t for t in self.tenants if self._tq[t]]
        for t in self.tenants:
            if t not in self._deficit or not self._tq[t]:
                self._deficit[t] = 0.0
        if not active:
            return []
        quantum = max(1.0, self.max_batch / len(active))
        # rotate the starting tenant per flush so sub-quantum rounding
        # never systematically favors tenant order
        self._rr += 1
        start = self._rr % len(active)
        order = active[start:] + active[:start]
        batch: list = []
        while len(batch) < self.max_batch and any(
            self._tq[t] for t in order
        ):
            for t in order:
                if len(batch) >= self.max_batch:
                    # a full flush earns nobody further credit this
                    # round — banked quantum would let one tenant
                    # monopolize the NEXT flush wholesale
                    break
                q = self._tq[t]
                if not q:
                    self._deficit[t] = 0.0
                    continue
                self._deficit[t] += quantum
                while (
                    q
                    and self._deficit[t] >= 1.0
                    and len(batch) < self.max_batch
                ):
                    batch.append(q.popleft())
                    self._deficit[t] -= 1.0
        for t in order:
            # carry at most one quantum of unspent credit across
            # flushes (the DRR discipline): enough to smooth
            # sub-quantum rounding, never enough to capture a whole
            # future flush
            self._deficit[t] = min(self._deficit[t], quantum)
        idx = {t: i for i, t in enumerate(order)}
        batch.sort(key=lambda r: idx.get(r.tenant, len(idx)))
        return batch

    # --------------------------------------------------------------- apply
    def _apply_reqs(self, reqs, replica, deadline):
        """Segment-aware combined apply: one padded batch, one flush
        token, each tenant's walk reading the shared prefix through the
        pool.  Per-tenant failures are CONTAINED: the failing tenant's
        riders fail (bisected when the error is content-shaped — poison
        isolation works per tenant), co-flushed tenants deliver.  Only
        when EVERY tenant failed does the flush take the base error
        path (replica breaker charge, whole-flush accounting)."""
        segs = []
        for i, r in enumerate(reqs):
            if not segs or segs[-1][0] != r.tenant:
                segs.append([r.tenant, i, i + 1])
            else:
                segs[-1][2] = i + 1
        names = list(dict.fromkeys(s[0] for s in segs))
        if len(names) == 1:
            # single-tenant group (bisection sub-runs land here): let
            # failures PROPAGATE so the caller's bisection/containment
            # machinery sees them
            outs = self._apply_rows(
                np.stack([r.x for r in reqs]),
                deadline=deadline,
                replica=replica,
                tenants=names,
            )
            return outs[names[0]]
        errors: dict = {}
        outs = self._apply_rows(
            np.stack([r.x for r in reqs]),
            deadline=deadline,
            replica=replica,
            tenants=names,
            errors_out=errors,
        )
        if errors and len(errors) == len(names):
            raise next(iter(errors.values()))
        out_rows: list = [None] * len(reqs)
        for t, s, e in segs:
            if t in errors:
                exc = errors[t]
                group = reqs[s:e]
                from keystone_tpu.serve.service import _poison_suspect

                if self._bisect and _poison_suspect(exc):
                    # content-shaped failure: isolate the poison rider
                    # WITHIN this tenant's segment — innocents complete
                    self._bisect_flush(
                        group, replica, "tenant-bisect", deadline, exc
                    )
                else:
                    for r in group:
                        self._fail(r, exc, replica=replica.index)
                continue
            rows = outs[t]
            for i in range(s, e):
                out_rows[i] = rows[i]
        return out_rows

    # -------------------------------------------------------------- status
    def status(self) -> dict:
        out = super().status()
        reg = metrics.REGISTRY
        tenants = {}
        for t in self.tenants:
            lat = self._tlat[t].summary()
            n_ok = lat["count"]
            n_fail = self._tfail[t].summary()["count"]
            entry = {
                "queue_depth": len(self._tq[t]),
                "quota": self._tenant_bound(t),
                "latency_ms": self._ms(lat),
                "counters": {
                    "submitted": reg.counter_value(
                        "serve.tenant_submitted", tenant=t
                    ),
                    "completed": reg.counter_value(
                        "serve.tenant_completed", tenant=t
                    ),
                    "shed": reg.counter_value("serve.tenant_shed", tenant=t),
                    "rejected": reg.counter_value(
                        "serve.tenant_rejected", tenant=t
                    ),
                    "errors": reg.counter_value(
                        "serve.tenant_errors", tenant=t
                    ),
                },
            }
            brk = self._tenant_breakers.get(t)
            if brk is not None:
                entry["breaker"] = brk.state()
            if self._slo_s is not None:
                n = n_ok + n_fail
                bad = (
                    0.0
                    if n == 0
                    else (
                        self._tlat[t].fraction_above(self._slo_s) * n_ok
                        + n_fail
                    )
                    / n
                )
                budget = 1.0 - self._slo_target
                entry["slo"] = {
                    "bad_fraction": round(bad, 6),
                    "burn_rate": (
                        None if budget <= 0.0 else round(bad / budget, 3)
                    ),
                }
            tenants[t] = entry
        out["tenants"] = tenants
        plan = self._mt_applier.plan
        out["stage_pool"] = {
            **self._mt_applier.pool().stats(),
            "shared_stages": len(plan.shared),
            "collision_refusals": plan.refused,
            "sharing": self._mt_applier.share,
        }
        return out

    # ------------------------------------------------------------ shutdown
    def close(self, drain: bool = True, timeout: float = 30.0) -> None:
        super().close(drain=drain, timeout=timeout)
        pool = self._mt_applier.pool()
        for t in self.tenants:
            pool.unregister_tenant(f"{self._pool_owner}:{t}")


def serve_multi(
    models: Dict[str, object],
    *,
    share: bool = True,
    pool: Optional[SharedStagePool] = None,
    tenant_queue_bound: Optional[Dict[str, int]] = None,
    tenant_deadline_ms: Optional[Dict[str, float]] = None,
    tenant_breaker_threshold: Optional[int] = None,
    dedup: bool = False,
    **kw,
) -> MultiTenantService:
    """Stand up a multi-tenant :class:`MultiTenantService`.

    ``models``: ``{tenant name: fitted pipeline (or FrozenApplier)}``.
    ``share=False`` disables the cross-pipeline stage pool (the arm a
    shared-vs-unshared comparison measures against).  ``pool``:
    a private :class:`SharedStagePool` (default: the process-wide one).
    ``tenant_queue_bound``/``tenant_deadline_ms``: per-tenant quota and
    default deadline overrides (quota default: an equal share of
    ``queue_bound``).  ``tenant_breaker_threshold``: consecutive
    failures before a tenant's OWN admission breaker opens (None =
    off).  Remaining keywords are :func:`keystone_tpu.serve.serve`'s
    (``max_batch``, ``deadline_ms``, ``replicas``, ``example``, ...).

    ``dedup=True`` enables cross-request in-flight dedup: identical
    concurrent payloads for the same tenant are computed ONCE — later
    arrivals ride the in-flight leader's computation, consume no queue
    slot, and resolve bit-identically from its result (counted as
    ``serve.dedup_hits``).  Off by default: coupled outcomes (a shed
    leader sheds its followers) are a semantic opt-in.

    Requests are routed with ``svc.submit(x, tenant="name")`` / HTTP
    ``POST /predict`` with ``"tenant"`` in the body."""
    return MultiTenantService(
        models,
        share=share,
        pool=pool,
        tenant_queue_bound=tenant_queue_bound,
        tenant_deadline_ms=tenant_deadline_ms,
        tenant_breaker_threshold=tenant_breaker_threshold,
        dedup=dedup,
        **kw,
    )
