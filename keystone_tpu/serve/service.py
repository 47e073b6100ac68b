"""Online inference service: dynamic micro-batching over a frozen
pipeline, with admission control and deadline-aware shedding.

KeystoneML pipelines are trained once and then applied to a stream of
requests; the reference served that stream through Velox/Spark batch
jobs, and Clipper-style systems (Crankshaw et al., NSDI 2017) showed the
serving win is a thin layer over the frozen model: micro-batch requests
to saturate the accelerator, bound the queue so tail latency stays
bounded, and shed work that cannot meet its deadline.  This module is
that layer for ``keystone_tpu``:

- **Frozen apply** — :class:`~keystone_tpu.workflow.FrozenApplier` runs
  the whole-pipeline optimizer once at service construction; each flush
  binds one padded batch to the pre-optimized graph.
- **Padding buckets** — every flush is padded UP to a fixed bucket size
  (``iter_row_chunks``, the same pad discipline as chunked offline
  applies), so the set of compiled program shapes is finite and
  cache-hot: a single-datum request rides the smallest bucket's batch
  program instead of tracing a per-datum one.
- **Dynamic micro-batching** — a background worker drains the bounded
  FIFO queue, flushing when ``max_batch`` requests are waiting or the
  oldest has waited ``max_wait_ms``, whichever first.
- **Admission control** — ``submit`` past ``queue_bound`` raises
  :class:`Overloaded` (backpressure to the caller); requests whose
  :class:`~keystone_tpu.utils.guard.Deadline` would expire before the
  batch completes (EWMA-predicted) are shed with
  :class:`~keystone_tpu.utils.guard.DeadlineExceeded` instead of
  wasting device time on an answer nobody is waiting for.
- **Degradation** — when every rider carries a deadline, the batch's
  LOOSEST one plumbs into the
  :class:`~keystone_tpu.workflow.GraphExecutor`, so ``optional`` /
  ``with_fallback`` stages degrade on the serve path exactly as they do
  in fits (loosest, not tightest: one near-expiry straggler must never
  deadline-fail a flush its co-riders could comfortably complete).

Observability (``keystone_tpu.obs``): ``serve.queue_depth`` gauge,
``serve.batch_rows``/``serve.batch_seconds``/``serve.latency_seconds``
histograms, ``serve.submitted``/``completed``/``shed``/``rejected``/
``batch_errors``/``deadline_miss`` counters, and one ``serve.batch``
ledger span per flush.  Fault injection (``keystone_tpu.faults``):
sites ``serve.enqueue`` (admission path) and ``serve.batch`` (worker
flush) — chaos plans exercise overload and hang scenarios.

Usage::

    svc = serve(fitted, max_batch=32, max_wait_ms=5, queue_bound=256,
                deadline_ms=100, example=x0)
    fut = svc.submit(x)            # concurrent.futures.Future
    y = fut.result()
    svc.close()                    # drains in-flight requests

**Replica fleet (PR 8)** — the batcher no longer applies flushes
inline: it forms batches and hands them to a
:class:`~keystone_tpu.serve.fleet.ReplicaPool` router, which dispatches
each flush to the least-loaded of N per-device replicas (falling past
replicas whose breaker is open).  ``replicas=1`` with no explicit
devices is the PR-5 single-device behavior bit-for-bit — the pool wraps
the given applier directly.  :meth:`PipelineService.swap` performs a
blue/green model hot-swap: stage a new generation of replicas, prime
their padding-bucket programs while the old generation keeps serving,
then commit at the flush boundary — queued requests never drop.  The
versioned model store feeding swaps is
``keystone_tpu/serve/registry.py``.

**Request-scoped tracing (ISSUE 9)** — every request carries a
``request_id`` (honored from the caller / ``X-Request-Id``, else
generated) from ingress through enqueue → batch flush → replica apply
to its terminal outcome (``completed`` / ``shed`` / ``rejected`` /
``degraded`` / ``error``).  The trace lands in an always-on in-memory
:class:`~keystone_tpu.obs.recorder.FlightRecorder` (bounded, tail-based
retention — shed/error/slow traces pinned) that is independent of the
JSONL ledger, so a shed request is explainable live via
``GET /requestz/<id>`` even with the ledger off.  When a ledger IS
active, ``serve.batch`` spans additionally record their rider request
ids as span links and each terminal outcome emits a ``serve.request``
event, so ``tools/trace_report.py`` reconstructs the same chains from
either source.  Span parenting survives the batcher and replica worker
threads via the PR-4 ``ledger.capture_context``/``restore_context``
machinery (captured at service construction, restored in every worker).
``serve(recorder=False)`` disables all of it — the PR-5 single-batcher
path and solver HLO are byte-identical with the recorder off (pinned).

``GET /statusz`` reads rolling-window latency percentiles from
:class:`~keystone_tpu.obs.metrics.WindowedHistogram` wrappers (ring of
per-interval histograms merged on read, ms-resolution buckets) that
also feed the cumulative ``/metrics`` series, plus an SLO error-budget
burn rate against a configurable latency objective (``slo_ms``,
defaulting to the service deadline).

**Self-healing (ISSUE 10)** — three mechanisms close the loop between
detection and recovery without an operator: (1) the
:class:`~keystone_tpu.serve.fleet.ReplicaSupervisor` restarts dead or
wedged replica workers in place (re-clone from the pool's source,
re-prime, rejoin the router) and quarantines a slot that keeps dying;
(2) a flush failing with a request-attributable error is **bisected**
— recursively halved over the same padding buckets — until the poison
request is isolated: it alone fails (typed :class:`PoisonRequest`,
HTTP 422, recorder-pinned trace), innocent riders complete, and a
content-keyed quarantine cache refuses the same payload at admission
thereafter; (3) **hedged dispatch** (opt-in ``hedge_ms``) re-enqueues
a batch still stuck in a straggling replica's queue onto a second
replica — first claim wins, the loser is cancelled without device work
and charged breaker-neutral.  When the WHOLE fleet is down (every
replica quarantined/dead/breaker-open) the service fails fast instead
of force-routing: submits raise
:class:`~keystone_tpu.serve.fleet.FleetUnavailable` (503 + derived
``Retry-After`` at HTTP, non-200 ``/healthz``) until the supervisor's
first successful restart — or a breaker's half-open probe — re-admits
traffic.

**Multi-tenant serving (ISSUE 14)** — ``serve/tenants.py`` subclasses
this service to co-serve N pipelines behind one batcher + fleet:
per-tenant admission queues/quotas/deadlines/breakers, deficit-round-
robin combined flushes, and the cross-pipeline shared stage pool
(``workflow/stage_pool.py``) computing shared featurization prefixes
once per flush.  The tenant hooks below (``_resolve_tenant``,
``_check_bound_locked``, ``_push_locked``, ``_account_tenant``, ...)
are inert on this base class — the single-tenant path is unchanged.

**Process fleet + autoscaling (ISSUE 15)** — ``workers=N`` promotes
replica COMPUTE into worker processes (``serve/procfleet.py`` over the
``serve/wire.py`` shared-memory protocol) behind this same control
plane, so a multi-core host's throughput is bounded by cores, not the
GIL; a worker death mid-flush raises :class:`WorkerCrashed`, the flush
is un-claimed and requeued, and the supervisor's replacement serves it
— zero lost futures.  ``autoscale={...}`` starts a
:class:`~keystone_tpu.serve.autoscale.Autoscaler` control thread that
resizes the fleet (``scale_to``) and retunes the dispatch window from
windowed occupancy, queue depth, SLO burn, and the shared-pool hit
rate.  ``workers=0`` (default) is the threaded path, byte-for-byte.

The HTTP front end is ``keystone_tpu/serve/http.py``; the CLI entry is
``python -m keystone_tpu.cli serve``; the load generator is
``tools/workloads.py``.
"""

from __future__ import annotations

import hashlib
import heapq
import itertools
import logging
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future, InvalidStateError
from typing import List, Optional, Sequence, Tuple

import numpy as np

from keystone_tpu.faults import fault_point
from keystone_tpu.obs import ledger, metrics
from keystone_tpu.obs.recorder import FlightRecorder, new_request_id
from keystone_tpu.serve.fleet import (
    FleetUnavailable,
    ReplicaPool,
    ReplicaSupervisor,
)
from keystone_tpu.serve.procfleet import WorkerCrashed
from keystone_tpu.utils import guard

logger = logging.getLogger(__name__)

# millisecond-resolution histogram bounds for the serve-path latencies:
# the registry defaults alias every sub-millisecond flush into one
# bucket, which makes windowed p99 estimates (and Prometheus
# histogram_quantile) useless at serving timescales.  Registered at
# import, before any service records a sample.
metrics.register_buckets("serve.latency_seconds", metrics.LATENCY_MS_BUCKETS)
metrics.register_buckets("serve.batch_seconds", metrics.LATENCY_MS_BUCKETS)
metrics.register_buckets("serve.failed_wait_seconds", metrics.LATENCY_MS_BUCKETS)

#: EWMA smoothing for the per-batch latency predictor the shed decision
#: uses: new = (1-ALPHA)*old + ALPHA*sample.  0.3 tracks load shifts
#: within a few batches without letting one outlier batch (a compile, a
#: GC pause) shed everything behind it.
_EWMA_ALPHA = 0.3


class Overloaded(RuntimeError):
    """Admission control refused the request: the queue is at its bound.
    Backpressure is the caller's signal to retry later or route away —
    deliberately NOT an ``OSError``, so generic transient-I/O retry
    loops don't hammer an already-overloaded service."""


class ServiceClosed(RuntimeError):
    """The service is shut down (or shutting down) and accepts no new
    requests."""


class PoisonRequest(ValueError):
    """THIS request's content makes the model fail — isolated by batch
    bisection (the request alone reproduces the error), or matched
    against the quarantine cache of previously-isolated content.  A
    ``ValueError`` on purpose: it is the CLIENT's fault (the HTTP layer
    answers 422, and it does not burn the server's SLO error budget),
    and retrying it unchanged will fail again."""


#: bound on the content-keyed poison quarantine cache (LRU eviction)
_POISON_CACHE_CAP = 512

#: quarantine entries expire after this long: _poison_suspect is a
#: type-level heuristic, and a transient third-party RuntimeError
#: (e.g. an XLA RESOURCE_EXHAUSTED during the singleton re-run) could
#: misclassify an innocent payload — a TTL bounds that blast radius to
#: minutes (a real poison resubmitted later just re-bisects, one extra
#: isolation per TTL window)
_POISON_TTL_S = 600.0

#: hedge delay = max(configured floor, this multiple of the EWMA batch
#: time) — the cheap stand-in for a tail quantile: for exponential-ish
#: flush times 3× the mean sits near p95, so hedges fire on genuine
#: stragglers, not on every flush
_HEDGE_EWMA_MULT = 3.0


def _content_key(arr: np.ndarray) -> bytes:
    """The quarantine-cache key: a BLAKE2b digest of the request's
    dtype + shape + bytes.  Content-keyed, not id-keyed: the same bad
    payload resubmitted (or replayed by a retrying client) short-
    circuits at admission without touching a device."""
    h = hashlib.blake2b(digest_size=16)
    h.update(str(arr.dtype).encode())
    h.update(str(arr.shape).encode())
    h.update(arr.tobytes())
    return h.digest()


def _poison_suspect(exc: BaseException) -> bool:
    """Is this apply failure plausibly caused by a request's CONTENT
    (worth bisecting), as opposed to infrastructure?  The repo-wide
    convention makes this a type test: every infrastructure failure
    rides ``OSError`` (``FaultInjected``, ``DeadlineExceeded``, real
    I/O), breaker refusals are ``CircuitOpenError``, and resource
    exhaustion is ``MemoryError`` — everything else (the ``ValueError``
    /``FloatingPointError``/XLA-check family) is content-shaped."""
    return not isinstance(
        exc, (OSError, MemoryError, guard.CircuitOpenError)
    )


#: distinguishes "caller said nothing" from an explicit None for knobs
#: where None is itself a meaningful setting (hedge_ms=None = hedging
#: OFF must stay OFF even when a plan carries a hedge)
_UNSET = object()


def _planned_knob(name: str):
    """The installed PhysicalPlan's value for a serving knob, or None —
    the third tier of the precedence ladder (explicit arg > env > plan >
    static default).  Guarded import: with no planner in play this is a
    cheap no-op and the legacy path stays byte-identical."""
    try:
        from keystone_tpu.planner import registry as _plans

        return _plans.planned_knob(name)
    except Exception:
        return None


def _plan_status_safe():
    """The installed plan's ``/statusz`` section, or None (guarded the
    same way as :func:`_planned_knob`)."""
    try:
        from keystone_tpu.planner import registry as _plans

        return _plans.plan_status()
    except Exception:
        return None


def default_buckets(max_batch: int, min_bucket: int = 8) -> Tuple[int, ...]:
    """Power-of-two padding buckets up to (and including) ``max_batch``.
    The smallest bucket bounds single-datum padding waste; the largest
    equals ``max_batch`` so a full flush pads nothing."""
    max_batch = max(1, int(max_batch))
    b = min(int(min_bucket), max_batch)
    out = []
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(sorted(set(out)))


class _Request:
    __slots__ = (
        "x",
        "deadline",
        "future",
        "t_submit",
        "request_id",
        "tenant",
        "block",
        "row",
        "gen",
    )

    def __init__(
        self,
        x,
        deadline: Optional[guard.Deadline],
        request_id: Optional[str] = None,
        tenant: Optional[str] = None,
    ):
        self.x = x
        self.deadline = deadline
        self.future: Future = Future()
        self.t_submit = time.monotonic()
        #: trace identity; None when tracing is off for this request —
        #: every trace hook takes the None id as its inert no-op
        self.request_id = request_id
        #: multi-tenant routing label (serve/tenants.py); None on the
        #: single-tenant service — every tenant hook is inert then
        self.tenant = tenant
        #: slab-direct admission (serve/ingress.py): when set, ``x`` is
        #: row ``row`` of admission block ``block`` (a zero-copy view of
        #: a shared-memory slab).  A flush formed of exactly one block's
        #: rows in order skips the stack+pad copies (_apply_reqs) and —
        #: on a process fleet — ships the slab by REFERENCE to the
        #: worker.  None on every other submit path.
        self.block = None
        self.row = 0
        #: rollout generation tag (serve/rollout.py): "canary" when the
        #: flush carrying this request was routed to a staged canary
        #: generation, "live" when a canary window explicitly kept it on
        #: the serving generation; None outside any canary window —
        #: every rollout hook treats None as "live"
        self.gen: Optional[str] = None


def _block_of(reqs) -> Optional[object]:
    """The admission block a flush is a complete in-order image of, or
    None.  The preformed-flush fast path requires EXACTLY the block's
    rows 0..count-1 in order: a shed/cancelled rider, a flush mixing two
    submits, or a block spanning flushes all fall back to the stack+pad
    copy path (which remains correct for views)."""
    blk = getattr(reqs[0], "block", None)
    if blk is None or not getattr(blk, "admission_block", False):
        return None
    if len(reqs) != blk.count:
        return None
    for i, r in enumerate(reqs):
        if r.block is not blk or r.row != i:
            return None
    return blk


class _Flush:
    """One formed micro-batch in flight through the router.

    The claim state machine is what makes hedging and worker-crash
    requeues safe: a flush may sit in TWO replica queues (hedged) or be
    re-run after a crash requeue, but ``claim()`` admits exactly ONE
    runner — every other popper sees the claim spent and skips without
    device work (the hedge loser's "cancellation").  ``abort()`` stops a
    never-claimed flush from running at all (a wedged worker's in-hand
    batch whose riders the supervisor already failed)."""

    QUEUED, RUNNING, DONE, ABORTED = "queued", "running", "done", "aborted"

    __slots__ = ("riders", "bid", "primary", "hedged", "_state", "_lock")

    def __init__(self, riders: list, bid: str):
        self.riders = riders
        self.bid = bid
        #: index of the replica the router first dispatched to (set by
        #: ReplicaPool.dispatch under the router lock)
        self.primary: Optional[int] = None
        self.hedged = False
        self._state = _Flush.QUEUED
        self._lock = threading.Lock()

    @property
    def state(self) -> str:
        return self._state

    def unflushed(self) -> bool:
        """Still waiting in a queue — the hedge monitor's fire test."""
        return self._state == _Flush.QUEUED

    def claim(self) -> bool:
        """First caller wins the right to run this flush."""
        with self._lock:
            if self._state != _Flush.QUEUED:
                return False
            self._state = _Flush.RUNNING
            return True

    def done(self) -> None:
        with self._lock:
            if self._state == _Flush.RUNNING:
                self._state = _Flush.DONE

    def abort(self) -> bool:
        """Spend the claim without running (supervisor abandonment).
        True when the flush had never been claimed — its riders can be
        failed knowing no result will ever race the failure."""
        with self._lock:
            if self._state == _Flush.QUEUED:
                self._state = _Flush.ABORTED
                return True
            return False

    def unclaim(self) -> bool:
        """Return a RUNNING flush to QUEUED — the process-death path
        ONLY: the claiming runner's worker died before any result was
        produced or delivered, so a front-requeue plus a fresh claim on
        the supervisor's replacement re-runs it safely (already-resolved
        riders are skipped by the delivery paths).  True when the claim
        was actually returned."""
        with self._lock:
            if self._state == _Flush.RUNNING:
                self._state = _Flush.QUEUED
                return True
            return False


class _HedgeMonitor:
    """A single timer thread watching dispatched-but-unflushed flushes:
    when one is still queued after its hedge delay, re-enqueue it on a
    second replica (``ReplicaPool.hedge_dispatch``).  First popper wins
    the claim; the loser skips without device work and is charged
    breaker-NEUTRAL.  One heap, one thread, regardless of QPS."""

    def __init__(self, service: "PipelineService"):
        self._svc = service
        self._heap: list = []
        self._seq = itertools.count()
        self._cond = threading.Condition()
        self._stopping = False
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name=f"{service.name}-hedge"
        )
        self._thread.start()

    def schedule(self, flush: _Flush, delay_s: float) -> None:
        with self._cond:
            heapq.heappush(
                self._heap,
                (time.monotonic() + max(0.0, delay_s), next(self._seq), flush),
            )
            self._cond.notify()

    def stop(self, timeout: float = 5.0) -> None:
        with self._cond:
            self._stopping = True
            self._cond.notify()
        self._thread.join(timeout)

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._stopping:
                    if not self._heap:
                        self._cond.wait()
                    else:
                        wait = self._heap[0][0] - time.monotonic()
                        if wait <= 0.0:
                            break
                        self._cond.wait(wait)
                if self._stopping:
                    return
                _, _, flush = heapq.heappop(self._heap)
            try:
                self._svc._hedge_fire(flush)
            except Exception:  # a failed hedge must never kill the timer
                logger.exception("hedge dispatch failed")


class PipelineService:
    """A frozen fitted pipeline behind a micro-batching request queue.

    Construct via :func:`serve`.  ``submit``/``submit_many`` return
    ``concurrent.futures.Future`` objects resolved by the background
    batcher thread; ``close`` drains in-flight work.  Thread-safe: any
    number of client threads may submit concurrently (the HTTP front
    end's handler threads do)."""

    def __init__(
        self,
        pipeline,
        max_batch: int = 32,
        max_wait_ms: Optional[float] = None,
        queue_bound: int = 128,
        buckets: Optional[Sequence[int]] = None,
        deadline_ms: Optional[float] = None,
        example=None,
        degrade: bool = True,
        name: str = "serve",
        replicas: int = 1,
        devices: Optional[Sequence] = None,
        version: str = "v0",
        recorder=True,
        slo_ms: Optional[float] = None,
        slo_target: float = 0.99,
        slo_window_s: Optional[float] = None,
        supervise: bool = True,
        heartbeat_s: float = 30.0,
        supervise_interval_s: float = 0.5,
        restart_limit: int = 3,
        restart_window_s: float = 60.0,
        hedge_ms=_UNSET,
        bisect: bool = True,
        artifacts: Optional[dict] = None,
        workers: int = 0,
        worker_opts: Optional[dict] = None,
        autoscale: Optional[dict] = None,
        hosts=None,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if queue_bound < 1:
            raise ValueError(f"queue_bound must be >= 1, got {queue_bound}")
        workers = int(workers or 0)
        if workers < 0:
            raise ValueError(f"workers must be >= 0, got {workers}")
        if workers > 0 and replicas != 1:
            raise ValueError(
                "workers= (process fleet) and replicas= (thread fleet) "
                "are exclusive; pass exactly one"
            )
        if workers > 0 and devices is not None:
            raise ValueError(
                "workers= owns device placement in the worker processes; "
                "devices= applies to the thread fleet only"
            )
        if workers > 0:
            from keystone_tpu.serve.procfleet import refuse_chip_children
            from keystone_tpu.utils.hostmap import HostMap, parse_hosts

            # workers that would start on THIS host need this host's chip
            if hosts is None or any(
                e.local
                for e in (
                    hosts.entries if isinstance(hosts, HostMap) else parse_hosts(hosts)
                )
            ):
                refuse_chip_children(f"workers={workers} on this host")
        if hosts is not None and workers < 1:
            raise ValueError(
                "hosts= selects the cross-host TCP fleet and needs "
                "workers>=1 to size it; local workers=N without hosts "
                "stays on the shared-memory transport"
            )
        # the persistent-compile-cache tier of the prime fallback ladder
        # (artifact → cache → compile): auto-enabled for library callers
        # too, not just the CLI entry points.  KEYSTONE_COMPILE_CACHE=0
        # disables; the directory is JAX_COMPILATION_CACHE_DIR's, or
        # the fixed one in the checkout (utils/compile_cache.py).
        from keystone_tpu.utils.compile_cache import (
            enable_compilation_cache,
            seed_compile_cache,
        )

        enable_compilation_cache()
        if artifacts:
            # the bundle may ship persistent-compile-cache entries
            # (export's pre-seeded rung): install them BEFORE any
            # replica primes, so the first deploy on a fresh host skips
            # the backend compile of the deserialized modules too
            seed_compile_cache(artifacts)
        # cost-based PhysicalPlan (keystone_tpu.planner): the artifact
        # manifest or the frozen applier may ship one.  Installed BEFORE
        # any serving knob resolves, so buckets / max_wait / dispatch
        # window / hedge read the planned values through the one
        # precedence ladder (explicit arg > env > plan > static default)
        self._plan = None
        try:
            from keystone_tpu import planner as _planner

            plan_dict = ((artifacts or {}).get("manifest") or {}).get("plan")
            if plan_dict is not None:
                self._plan = _planner.PhysicalPlan.from_dict(plan_dict)
            else:
                self._plan = getattr(pipeline, "plan", None)
            if self._plan is not None:
                _planner.install_plan(self._plan, source="serve")
        except Exception:
            self._plan = None
        # the bucket/shape contract is resolved BEFORE the pool builds:
        # process workers prime their padding buckets at spawn, so the
        # worker_opts must carry the final bucket set and item shape
        self.max_batch = int(max_batch)
        planned_buckets = None if buckets else _planned_knob("buckets")
        self.buckets = (
            tuple(sorted({int(b) for b in buckets}))
            if buckets
            else (
                tuple(sorted({int(b) for b in planned_buckets}))
                if planned_buckets
                else default_buckets(self.max_batch)
            )
        )
        if self.buckets[-1] < self.max_batch:
            # a flush larger than every bucket would have nowhere to pad
            self.buckets = self.buckets + (self.max_batch,)
        #: admission-time shape/dtype contract, learned from ``example``
        #: (or the first request): a mismatched request fails ITS submit,
        #: never the whole batch it would have ridden in
        self._item_shape: Optional[tuple] = None
        self._dtype = None
        if example is not None:
            ex = np.asarray(example)
            self._item_shape = tuple(ex.shape)
            self._dtype = ex.dtype
        #: process fleet (workers > 0): replicas are worker PROCESSES
        #: behind the same router — multi-core compute stops measuring
        #: the GIL.  workers == 0 is the PR-14 threaded path, untouched.
        self.workers = workers
        if workers > 0:
            # hosts= promotes the fleet onto the TCP transport
            # (serve/net.py): workers register over a socket and beat a
            # heartbeat lease instead of sharing memory.  Without hosts
            # the shared-memory process path is byte-for-byte untouched.
            pool_backend = "net" if hosts is not None else "process"
            replicas = workers
            pool_worker_opts = dict(worker_opts or {})
            if hosts is not None:
                pool_worker_opts.setdefault("hosts", hosts)
            pool_worker_opts.setdefault("buckets", list(self.buckets))
            pool_worker_opts.setdefault("item_shape", self._item_shape)
            pool_worker_opts.setdefault(
                "dtype",
                None if self._dtype is None else np.dtype(self._dtype).str,
            )
        else:
            pool_backend = "thread"
            pool_worker_opts = None
        #: fleet telemetry (workers > 0): the one sink every worker
        #: handle ships spans/metric-deltas into.  Built BEFORE the pool
        #: (handles attach at construction); its recorder reference is
        #: wired after the recorder itself exists below.  Thread fleets
        #: have no wire to account for — no sink.
        self._telemetry = None
        self._trace_ctx_cap = 0
        if workers > 0:
            from keystone_tpu.serve.telemetry import (
                MAX_TRACE_REQUEST_IDS,
                FleetTelemetry,
            )

            self._telemetry = FleetTelemetry()
            self._trace_ctx_cap = MAX_TRACE_REQUEST_IDS
        self._pool = ReplicaPool(
            pipeline,
            replicas=replicas,
            devices=devices,
            version=version,
            name=name,
            heartbeat_s=heartbeat_s,
            artifacts=artifacts,
            backend=pool_backend,
            worker_opts=pool_worker_opts,
            telemetry=self._telemetry,
        )
        # planned dispatch window: the pool's starting point (the
        # autoscaler / PlanTuner may retune it live from here)
        planned_window = _planned_knob("dispatch_window")
        if planned_window is not None and int(planned_window) != self._pool.window:
            self._pool.set_window(int(planned_window))
        #: the flight recorder: True (default) = a fresh bounded
        #: recorder, False/None = tracing fully off (request ids stay
        #: None, no trace hook runs — the PR-5 path, pinned), or a
        #: caller-provided FlightRecorder instance
        if recorder is True:
            self.recorder: Optional[FlightRecorder] = FlightRecorder()
        elif recorder:
            self.recorder = recorder
        else:
            self.recorder = None
        if self._telemetry is not None:
            # shipped worker spans stitch into /requestz via the
            # recorder; with the recorder off the sink still aggregates
            # fleet METRICS (trace contexts are never sent at all)
            self._telemetry.recorder = self.recorder
        #: thread-local trace context: set by _run_batch around a
        #: dispatch (recorder on + remote fleet only), read by
        #: _apply_rows' remote branch — threaded out-of-band because
        #: _apply_reqs is an override point (serve/tenants.py)
        self._trace_tls = threading.local()
        #: rolling-window latency/batch instruments backing /statusz
        #: percentiles; every observe also feeds the cumulative
        #: registry series of the same name (/metrics)
        #: ``slo_window_s`` resizes the SLO observation window (burn
        #: rate, /statusz percentiles, the rollout judge) — short
        #: windows make a canary/bake verdict reflect NOW, long ones
        #: smooth bursts.  Only the request-outcome windows resize:
        #: ``serve.batch_seconds`` keeps the default window because
        #: occupancy() divides by window_seconds × replicas and the
        #: autoscaler's thresholds are tuned against that default.
        slo_window = (
            max(1.0, float(slo_window_s)) if slo_window_s else 60.0
        )
        self._lat_win = metrics.WindowedHistogram(
            "serve.latency_seconds", window_seconds=slo_window
        )
        self._batch_win = metrics.WindowedHistogram("serve.batch_seconds")
        #: time failed requests (shed/rejected/errored) spent waiting
        #: before their terminal — and, for the SLO burn rate, the
        #: windowed COUNT of failures: a shed flood must drain the
        #: error budget, not hide from a completed-only latency window
        self._fail_win = metrics.WindowedHistogram(
            "serve.failed_wait_seconds", window_seconds=slo_window
        )
        #: SLO latency objective (seconds): explicit slo_ms, else the
        #: service deadline, else no SLO section in /statusz
        self._slo_s = (
            float(slo_ms) / 1000.0
            if slo_ms
            else (float(deadline_ms) / 1000.0 if deadline_ms else None)
        )
        self._slo_target = min(1.0, max(0.0, float(slo_target)))
        self._batch_seq = itertools.count(1)
        self._trace_dump_seq = itertools.count(1)
        #: span-parenting context captured where the service was built:
        #: restored in the batcher and every replica worker, so ledger
        #: spans emitted there nest under the constructor's open span
        self._obs_ctx = ledger.capture_context()
        # flush wait: explicit arg > plan > the historical 5 ms default
        if max_wait_ms is None:
            max_wait_ms = _planned_knob("max_wait_ms")
        if max_wait_ms is None:
            max_wait_ms = 5.0
        self.max_wait_s = max(0.0, float(max_wait_ms)) / 1000.0
        self.queue_bound = int(queue_bound)
        self.default_deadline_s = (
            None if not deadline_ms else float(deadline_ms) / 1000.0
        )
        self._degrade = bool(degrade)
        self.name = name
        self._q: deque = deque()
        self._cond = threading.Condition()
        self._closing = False
        self._closed = False
        self._ewma_batch_s = 0.0
        #: EWMA writes now race across replica workers; keep them atomic
        self._ewma_lock = threading.Lock()
        #: serializes concurrent swap() calls (watcher + admin endpoint)
        self._swap_lock = threading.Lock()
        self._swap_seq = 0
        #: batch-failure bisection (poison-request isolation) on the
        #: flush error path; the quarantine cache short-circuits repeat
        #: offenders at admission (content-keyed, LRU-bounded)
        self._bisect = bool(bisect)
        self._poison_cache: "OrderedDict[bytes, float]" = OrderedDict()
        self._poison_lock = threading.Lock()
        #: guarded-rollout hooks (serve/rollout.py).  ``_rollout``: the
        #: live CanaryController while a canary window is open — the
        #: batcher offers it every formed flush (take) and the request
        #: terminals report outcomes to it (observe); None outside a
        #: window, making every hook a single attribute read on the
        #: pinned path.  ``_rollout_guard``: the post-commit bake watch.
        #: ``_version_history``: prior version ids, newest last — what
        #: POST /rollback walks.  ``_rollout_history``: recent episode
        #: verdicts for /rolloutz.
        self._rollout = None
        self._rollout_guard = None
        self._rollout_state: Optional[dict] = None
        self._rollout_history: deque = deque(maxlen=16)
        self._version_history: list = []
        if example is not None:
            self.prime()
        self._pool.start(
            self._run_flush,
            obs_context=self._obs_ctx,
            on_stranded=self._handle_stranded_flush,
        )
        self._worker = threading.Thread(
            target=self._loop, daemon=True, name=f"{name}-batcher"
        )
        self._worker.start()
        #: hedged dispatch: re-enqueue a still-unflushed batch on a
        #: second replica after max(hedge_ms, 3×EWMA).  None (default)
        #: = off — no monitor thread, the PR-9 dispatch path unchanged.
        #: Needs a second replica to hedge onto.
        #: hedge_ms=0 is a MEANINGFUL floor (delay = pure 3×EWMA);
        #: only None disables hedging.  _UNSET (nothing passed) lets an
        #: installed plan's hedge_ms apply; an EXPLICIT None keeps
        #: hedging off regardless of the plan
        if hedge_ms is _UNSET:
            hedge_ms = _planned_knob("hedge_ms")
        self._hedge_floor_s = (
            None if hedge_ms is None else max(0.0, float(hedge_ms)) / 1000.0
        )
        self._hedge = (
            _HedgeMonitor(self)
            if self._hedge_floor_s is not None and self._pool.size > 1
            else None
        )
        #: the self-healing supervisor: detects dead/wedged replica
        #: workers, restarts them in place, quarantines repeat offenders
        self.supervisor = (
            ReplicaSupervisor(
                self,
                interval=supervise_interval_s,
                restart_limit=restart_limit,
                restart_window=restart_window_s,
            ).start()
            if supervise
            else None
        )
        #: SLO-driven autoscaling (default OFF): ``autoscale=`` is a
        #: config dict for :class:`~keystone_tpu.serve.autoscale.
        #: Autoscaler` (min_workers/max_workers/interval_s/...), whose
        #: control thread adds workers under queue/SLO pressure,
        #: retires idle ones, and retunes the dispatch window live
        self.autoscaler = None
        if autoscale:
            from keystone_tpu.serve.autoscale import Autoscaler

            try:
                self.autoscaler = Autoscaler(self, **dict(autoscale)).start()
            except BaseException:
                # a bad autoscale config must not leak the already-built
                # fleet (live worker PROCESSES for the process backend,
                # plus the batcher/supervisor threads) with no handle
                self.close(drain=False, timeout=10.0)
                raise
        metrics.set_gauge("serve.workers", float(self._pool.size))

    # ------------------------------------------------------------ priming
    def prime(self, replicas=None, have_artifacts: Optional[bool] = None) -> None:
        """Make the apply program at every bucket shape on every replica
        ready NOW, so no request ever pays a trace+compile against its
        deadline.  Requires the item shape (an ``example`` at
        construction, or a first request already served).
        ``replicas``: prime just these (the swap path primes a staged
        generation; default: the pool's live replicas).

        Each bucket rides the prime fallback ladder and is metered as
        ``serve.prime_seconds{source=artifact|cache|compile}``:
        **artifact** — an installed AOT bucket program (pre-lowered at
        publish; the first call only runs the backend compile of its
        serialized module); **cache** — a fresh trace whose executable
        the persistent XLA compilation cache may serve; **compile** —
        a fully cold trace+compile.  When a bundle was configured but a
        bucket has no installed program, that bucket counts as a
        ``serve.artifact_misses``.  ``have_artifacts``: whether the
        GENERATION being primed was given a bundle — the swap path
        passes the staged bundle's presence, because the pool's own
        flag still describes the LIVE generation mid-swap and would
        mislabel the staged primes; default None reads the pool (the
        construction and heal paths, where they agree)."""
        if self._item_shape is None:
            raise ValueError(
                "prime() needs the request item shape; construct the "
                "service with example=<one datum> (or serve a request first)"
            )
        from keystone_tpu.utils.compile_cache import cache_active

        have_bundle = (
            self._pool.has_artifacts
            if have_artifacts is None
            else bool(have_artifacts)
        )
        cache_tier = cache_active()
        t_all = time.monotonic()
        sources: dict = {}
        n_replicas = 0
        for replica in self._pool.replicas if replicas is None else replicas:
            n_replicas += 1
            for bucket in self.buckets:
                zeros = np.zeros((bucket,) + self._item_shape, self._dtype)
                t0 = time.monotonic()
                box: list = []
                self._apply_rows(
                    zeros,
                    deadline=None,
                    replica=replica,
                    prime=True,
                    source_box=box,
                )
                dt = time.monotonic() - t0
                if box and box[0] == "artifact":
                    source = "artifact"
                else:
                    if have_bundle:
                        metrics.inc("serve.artifact_misses")
                    source = "cache" if cache_tier else "compile"
                metrics.observe("serve.prime_seconds", dt, source=source)
                sources[source] = sources.get(source, 0) + 1
                if source == "artifact" and getattr(
                    replica.applier, "_degradable", False
                ):
                    # degradation-declaring pipelines route deadline-
                    # carrying live flushes to the executor WALK — warm
                    # it too, or the first such request pays the
                    # trace+compile in-band that priming exists to
                    # prevent (a far-future deadline selects the walk
                    # without ever firing a watchdog).  Timed and
                    # labeled as its OWN cache/compile-tier prime:
                    # charged to the artifact label, the per-source
                    # ladder timings would show the artifact tier as
                    # slow as the compile tier on degradable pipelines.
                    t1 = time.monotonic()
                    self._apply_rows(
                        zeros,
                        deadline=guard.Deadline.after(86400.0),
                        replica=replica,
                        prime=True,
                    )
                    walk_src = "cache" if cache_tier else "compile"
                    metrics.observe(
                        "serve.prime_seconds",
                        time.monotonic() - t1,
                        source=walk_src,
                    )
                    sources[walk_src] = sources.get(walk_src, 0) + 1
        took = time.monotonic() - t_all
        dominant = max(sources, key=sources.get) if sources else "compile"
        ledger.event(
            "serve.prime",
            seconds=round(took, 6),
            replicas=n_replicas,
            source=dominant,
            n=sum(sources.values()),
        )
        rec = self.recorder
        if rec is not None:
            # a prime is a control-plane moment (cold start, swap
            # staging, supervisor heal): visible in /tracez between the
            # request traces it delayed
            rec.ops(
                "serve.prime",
                seconds=round(took, 6),
                replicas=n_replicas,
                source=dominant,
                n=sum(sources.values()),
            )

    def prime_replacement(self, replica) -> None:
        """Prime one not-yet-routed replica's bucket programs — the
        supervisor's restart path (``prime()`` for a single replica,
        tolerating a service that has not yet learned its item shape)."""
        if self._item_shape is not None:
            self.prime(replicas=[replica])

    def fail_flush(self, flush, exc: BaseException) -> None:
        """Fail every still-unresolved rider of a flush (the supervisor's
        abandonment path, and the batcher's fleet-unavailable path)."""
        for req in flush.riders:
            self._fail(req, exc, batch=flush.bid)

    def _handle_stranded_flush(
        self, flush, why: str = "replica died"
    ) -> None:
        """THE stranded-work re-dispatch policy — one copy, shared by
        the crash-handler race path, scale-down leftovers, and the
        supervisor's heal/quarantine redistribution: a copy that is no
        longer QUEUED is skipped (its claimed winner owns delivery);
        otherwise re-dispatch onto a survivor, window ignored — extra
        queueing on a living replica beats failing admitted work; only
        with NO routable survivor do the riders fail typed, aborted
        FIRST so a pending hedge timer can never resurrect a flush
        whose riders were already answered."""
        if not getattr(flush, "unflushed", lambda: False)():
            return  # claimed/done/aborted elsewhere: not ours to place
        target = self._pool.hedge_dispatch(
            flush, exclude_index=None, respect_window=False
        )
        if target is None:
            getattr(flush, "abort", lambda: False)()
            self.fail_flush(
                flush,
                FleetUnavailable(
                    f"{why} and no routable survivor could absorb "
                    "its queue"
                ),
            )

    # ------------------------------------------------------------ hedging
    def _hedge_delay_s(self) -> float:
        """The re-dispatch delay: the configured floor, lifted to a
        ~p95-ish EWMA multiple once real batch samples exist."""
        return max(self._hedge_floor_s or 0.0, _HEDGE_EWMA_MULT * self._ewma_batch_s)

    def _hedge_fire(self, flush: _Flush) -> None:
        """Timer callback: the flush is still sitting in its primary
        replica's queue past the hedge delay — enqueue it on a second
        replica.  Whichever replica pops it first claims it; the other
        skips without device work."""
        if not flush.unflushed() or flush.hedged:
            return
        flush.hedged = True  # at most one hedge per flush
        rep = self._pool.hedge_dispatch(flush, exclude_index=flush.primary)
        if rep is None:
            return  # no second replica free: the hedge is skipped
        metrics.inc("serve.hedges")
        rec = self.recorder
        if rec is not None:
            rec.ops(
                "serve.hedge",
                batch=flush.bid,
                from_replica=flush.primary,
                to_replica=rep.index,
            )

    # ------------------------------------------------------------- submit
    def submit(
        self,
        x,
        deadline=None,
        request_id: Optional[str] = None,
        tenant: Optional[str] = None,
    ) -> Future:
        """Enqueue one datum; returns a Future resolving to its result
        row (numpy).  ``deadline``: seconds or a ``guard.Deadline``
        (default: the service's ``deadline_ms``).  ``request_id``: the
        trace identity (default: generated when the flight recorder is
        on — resolve the outcome later via ``/requestz/<id>``).
        ``tenant``: multi-tenant routing label — refused (TypeError) on
        a single-tenant service; see ``serve/tenants.py``.  Raises
        :class:`Overloaded` when the queue is at bound and
        :class:`ServiceClosed` after shutdown began."""
        return self._submit_all(
            [x],
            deadline,
            None if request_id is None else [request_id],
            tenant=tenant,
        )[0]

    def submit_many(self, xs, deadline=None, request_ids=None, tenant=None) -> list:
        """Enqueue a sequence of datums; returns their Futures in order.
        One shared deadline resolution (all requests of the call carry
        the same absolute expiry) and ATOMIC admission: either every
        datum is enqueued or none is — a partial enqueue would leave
        orphaned requests executing for a caller that saw the error.
        ``request_ids``: per-datum trace identities (default: generated
        when the flight recorder is on).  ``tenant``: multi-tenant
        routing label (single-tenant services refuse it)."""
        return self._submit_all(list(xs), deadline, request_ids, tenant=tenant)

    # ------------------------------------------------------ tenant hooks
    # The multi-tenant service (serve/tenants.py) overrides these; on
    # the base single-tenant service every one is inert (or refuses),
    # so the PR-5..13 admission path is unchanged.
    def _resolve_tenant(self, tenant: Optional[str]) -> Optional[str]:
        if tenant is not None:
            raise TypeError(
                f"service {self.name!r} is single-tenant; tenant="
                f"{tenant!r} refused (serve_multi builds tenant routing)"
            )
        return None

    def _default_deadline_for(self, tenant: Optional[str]):
        return self.default_deadline_s

    def _check_bound_locked(self, n_new: int, tenant: Optional[str]) -> None:
        """Admission bound check; must hold ``self._cond``."""
        if len(self._q) + n_new > self.queue_bound:
            metrics.inc("serve.rejected", n_new)
            raise Overloaded(
                f"service {self.name!r} queue at bound "
                f"({self.queue_bound}); retry later"
            )

    def _push_locked(self, reqs: list, tenant: Optional[str]) -> int:
        """Enqueue admitted requests; must hold ``self._cond``.
        Returns the post-push queue depth (the enqueue annotation).
        The gauge is set under the lock: written outside it, a stale
        pre-flush depth could overwrite the batcher's newer value."""
        self._q.extend(reqs)
        depth = len(self._q)
        metrics.set_gauge("serve.queue_depth", depth)
        return depth

    def _account_admission(
        self, tenant: Optional[str], outcome: str, n: int
    ) -> None:
        """Per-tenant admission-terminal accounting hook (inert here)."""

    def _account_tenant(self, req, outcome: str, seconds: float) -> None:
        """Per-tenant request-terminal accounting hook (inert here)."""

    def _fail_queued_locked(self, make_exc) -> None:
        """Fail every queued request; must hold ``self._cond``.  The
        multi-tenant service overrides this to drain its per-tenant
        queues."""
        while self._q:
            self._fail(self._q.popleft(), make_exc())
        metrics.set_gauge("serve.queue_depth", 0)

    def _queue_depth_locked(self) -> int:
        return len(self._q)

    # ------------------------------------------------------- dedup hooks
    # In-flight request dedup (serve/tenants.py enables it): identical
    # concurrent payloads are computed once and fanned out.  Every hook
    # is inert on the base service — zero cost on the single-tenant
    # path.
    def _dedup_keys(self, arrs) -> Optional[list]:
        """Content keys for this submit (None = dedup off)."""
        return None

    def _dedup_match(self, tenant, keys) -> dict:
        """``{datum index: leader _Request}`` for already-in-flight
        identical payloads; must hold ``self._cond``."""
        return {}

    def _dedup_register(self, tenant, keys, reqs, followers) -> None:
        """Register the call's leaders in the in-flight map; must hold
        ``self._cond``."""

    def _dedup_attach(self, followers: dict, reqs: list) -> None:
        """Wire follower futures to their leaders (outside the lock)."""

    def _resolve_request_ids(self, n: int, request_ids) -> List[Optional[str]]:
        if request_ids is not None:
            rids = [None if r is None else str(r) for r in request_ids]
            if len(rids) != n:
                raise ValueError(
                    f"got {len(rids)} request_ids for {n} datums"
                )
            return rids
        if self.recorder is not None:
            return [new_request_id() for _ in range(n)]
        return [None] * n

    def submit_batch(
        self,
        block,
        deadline=None,
        request_ids=None,
        tenant: Optional[str] = None,
    ) -> list:
        """Admit a whole admission block (``serve/wire.py``
        ``SlabBlock`` — or any duck-typed ``admission_block`` carrier
        exposing ``count`` / ``rows()``) under ONE queue-lock round;
        returns one Future per row, in order.  Each request's payload is
        a zero-copy VIEW of the block, so when the block forms a flush
        by itself the router skips the stack+pad copies and a process
        worker attaches the same shared-memory slab by name.

        The caller keeps ownership of the block's lifetime: hold it
        (e.g. ``block.retain(n)`` + ``release_one`` done-callbacks)
        until every returned future resolves — the router may read the
        slab up to that point (hedges, crash requeues, bisection).
        Raises exactly what :meth:`submit_many` raises; on ANY raise no
        row was admitted (atomic, same as every submit path)."""
        if not getattr(block, "admission_block", False):
            raise TypeError(
                f"submit_batch wants an admission block (wire.SlabBlock); "
                f"got {type(block).__name__} — use submit_many for plain "
                "sequences"
            )
        return self._submit_all(
            list(block.rows()),
            deadline,
            request_ids,
            tenant=tenant,
            block=block,
        )

    def bucket_for(self, k: int) -> int:
        """The padding bucket a ``k``-row flush pads to (public so the
        ingress can pre-pad admission blocks to the exact flush shape)."""
        return self._bucket_for(int(k))

    def _submit_all(
        self, xs, deadline, request_ids=None, tenant=None, block=None
    ) -> list:
        if not xs:
            return []
        rids = self._resolve_request_ids(len(xs), request_ids)
        rec = self.recorder
        try:
            if self._closing:
                raise ServiceClosed(f"service {self.name!r} is closed")
            tenant = self._resolve_tenant(tenant)
            dl = guard.as_deadline(
                deadline
                if deadline is not None
                else self._default_deadline_for(tenant)
            )
            # ctx.tenant rides the fault site so chaos plans can target
            # ONE tenant's admission path (blast-radius isolation)
            tctx = {} if tenant is None else {"tenant": tenant}
            for _ in xs:
                fault_point("serve.enqueue", **tctx)
            arrs = [np.asarray(x) for x in xs]
            # content keys for in-flight dedup (None unless the service
            # enables dedup) — hashed OUTSIDE the lock, and SHARED with
            # the poison check below: both key on the same digest, and
            # hashing payloads is the expensive part of this path
            dd_keys = self._dedup_keys(arrs)
            # the poison quarantine cache: content previously isolated
            # by bisection is refused BEFORE it reaches a device (and
            # before it can fail a co-batched flush again).  Zero cost
            # until something has actually been quarantined.
            if self._poison_cache:
                keys = (
                    dd_keys
                    if dd_keys is not None
                    else [_content_key(a) for a in arrs]
                )
                now = time.monotonic()
                with self._poison_lock:
                    hit = False
                    for k in keys:
                        t = self._poison_cache.get(k)
                        if t is None:
                            continue
                        if now - t > _POISON_TTL_S:
                            del self._poison_cache[k]  # expired: amnesty
                        else:
                            hit = True
                            break
                if hit:
                    metrics.inc("serve.poison_blocked", len(arrs))
                    raise PoisonRequest(
                        "request content matches a previously-isolated "
                        "poison payload; refused at admission"
                    )
            # fleet-unavailable fail-fast: every replica quarantined/
            # dead/breaker-open answers 503 at once instead of queueing
            # work the router will refuse.  One attribute read while the
            # fleet is healthy.
            if not self._pool.available():
                metrics.inc("serve.unavailable", len(arrs))
                raise FleetUnavailable(
                    f"service {self.name!r}: no replica can serve",
                    retry_after_seconds=self._pool.retry_after_unavailable(),
                )
            followers: dict = {}
            with self._cond:
                if self._closing:
                    raise ServiceClosed(f"service {self.name!r} is closed")
                # the shape/dtype contract is learned and checked UNDER the
                # lock: concurrent first requests must agree on one item
                # shape, and a mismatched request must fail ITS OWN submit
                # (before anything is enqueued), never the batch it would
                # have ridden in.  Staged, committed only after admission:
                # a rejected (or internally-inconsistent) call must not fix
                # the contract for requests that were never served
                item_shape, dtype = self._item_shape, self._dtype
                for arr in arrs:
                    if item_shape is None:
                        item_shape, dtype = tuple(arr.shape), arr.dtype
                    elif tuple(arr.shape) != item_shape:
                        raise TypeError(
                            f"request shape {tuple(arr.shape)} != service item "
                            f"shape {item_shape}"
                        )
                if dd_keys is not None:
                    followers = self._dedup_match(tenant, dd_keys)
                # followers ride their leader's computation: they occupy
                # no queue slot, which is exactly the capacity win
                self._check_bound_locked(len(arrs) - len(followers), tenant)
                self._item_shape, self._dtype = item_shape, dtype
                reqs = []
                for i, (a, rid) in enumerate(zip(arrs, rids)):
                    xa = a if a.dtype == dtype else a.astype(dtype)
                    r = _Request(xa, dl, rid, tenant=tenant)
                    # slab-direct admission: tag the request with its
                    # block row ONLY when no conversion copied the view
                    # (a dtype-mismatched block silently rides the copy
                    # path — correct, just not zero-copy)
                    if block is not None and xa is a:
                        r.block, r.row = block, i
                    reqs.append(r)
                if dd_keys is not None:
                    self._dedup_register(tenant, dd_keys, reqs, followers)
                # push, then annotate — both UNDER the queue lock: the
                # batcher pops under this same lock, so once we
                # release, the flush path's finish() cannot run ahead
                # of the enqueue event (annotated after the lock, a
                # preempted submitter could lose the event — or
                # resurrect an evicted id as a phantom trace)
                push_reqs = (
                    reqs
                    if not followers
                    else [r for i, r in enumerate(reqs) if i not in followers]
                )
                depth = self._push_locked(push_reqs, tenant)
                if rec is not None:
                    # followers are never enqueued: their trace gets the
                    # serve.dedup annotation instead (a phantom enqueue
                    # event would misreport queue behavior for exactly
                    # the requests dedup diverts)
                    enqueued_rids = (
                        rids
                        if not followers
                        else [r.request_id for r in push_reqs]
                    )
                    for rid in enqueued_rids:
                        rec.annotate(
                            rid, "serve.enqueue", queue_depth=depth, **tctx
                        )
                self._cond.notify_all()
            if followers:
                self._dedup_attach(followers, reqs)
        except BaseException as e:
            # terminal outcome at admission: the trace (if any) must not
            # dangle open — a rejected request is as explainable as a
            # shed one.  Finished OUTSIDE the queue lock.
            if isinstance(e, PoisonRequest):
                outcome = "poison"
            elif isinstance(
                e,
                (
                    Overloaded,
                    ServiceClosed,
                    FleetUnavailable,
                    # a tenant breaker's refusal is backpressure (the
                    # HTTP layer answers 429 + Retry-After), not an
                    # error: charged to rejected counters/traces
                    guard.CircuitOpenError,
                ),
            ):
                outcome = "rejected"
            else:
                outcome = "error"
            # rejected/errored admissions burn the SLO error budget too
            # (waited ~0: admission answers immediately) — EXCEPT client
            # faults (shape mismatch, malformed payloads: the 400
            # family): a misbehaving client must not be able to page an
            # operator by draining the server's error budget
            if not isinstance(e, (TypeError, ValueError)):
                for _ in xs:
                    self._fail_win.observe(0.0)
            self._account_admission(tenant, outcome, len(xs))
            err = f"{type(e).__name__}: {e}"
            for rid in rids:
                if rid is not None:
                    if rec is not None:
                        rec.finish(rid, outcome, error=err)
                    ledger.event(
                        "serve.request",
                        request_id=rid,
                        outcome=outcome,
                        error=err,
                    )
            raise
        metrics.inc("serve.submitted", len(reqs))
        self._account_admission(tenant, "submitted", len(reqs))
        return [r.future for r in reqs]

    @property
    def queue_depth(self) -> int:
        return len(self._q)

    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def version(self) -> str:
        """The model version the live replica generation serves."""
        return self._pool.version

    @property
    def replicas(self) -> int:
        return self._pool.size

    def replica_statuses(self) -> list:
        """Per-replica status dicts (index, device, model version,
        breaker state, outstanding flushes, dead/quarantined/restart
        supervision state) — the fleet view ``/healthz`` and
        ``/replicas`` expose so a load balancer can see a half-sick
        fleet, not just process liveness."""
        return self._pool.statuses()

    @property
    def available(self) -> bool:
        """False when NO replica can serve (all quarantined, dead, or
        breaker-open): submits raise :class:`FleetUnavailable`,
        ``/predict`` answers 503, and ``/healthz`` turns non-200 until
        a supervisor restart or half-open probe re-admits traffic.
        Runs the FULL scan (this backs low-rate health surfaces);
        the per-submit admission check stays one attribute read."""
        return not self._closed and self._pool.available_now()

    def unavailable_retry_after(self) -> float:
        """The ``Retry-After`` an unavailable 503 should carry: the
        soonest breaker half-open probe among routable replicas."""
        return self._pool.retry_after_unavailable()

    def retry_after_hint(self) -> float:
        """Estimated seconds until the queue drains — what a 429 should
        send as ``Retry-After`` instead of a constant.  Derived from the
        shedding path's EWMA flush-completion estimate: a full queue is
        ``ceil(depth / max_batch)`` flushes, spread across the fleet's
        replicas.  Falls back to 1 s before the first sample."""
        ewma = self._ewma_batch_s
        if ewma <= 0.0:
            return 1.0
        with self._cond:
            depth = self._queue_depth_locked()
        flushes = -(-max(1, depth) // self.max_batch)  # ceil division
        return ewma * flushes / max(1, self._pool.size)

    # ------------------------------------------------------------- scaling
    def occupancy(self) -> float:
        """Windowed fleet busy fraction: total batch-apply seconds over
        the last window divided by (window × replicas).  ~1.0 means
        every replica computed wall-to-wall; the autoscaler's primary
        utilization signal, and a ``/statusz`` field."""
        s = self._batch_win.summary()
        denom = s["window_seconds"] * max(1, self._pool.size)
        occ = min(1.0, (s["sum"] or 0.0) / denom) if denom > 0 else 0.0
        metrics.set_gauge("serve.occupancy", occ)
        return occ

    def slo_burn(self) -> Optional[dict]:
        """The windowed SLO burn detail (None when no objective is
        configured): ``burn_rate`` plus the ``window_requests`` /
        ``window_failed`` sample counts behind it, so a consumer — the
        rollout judge (serve/rollout.py), the bake guard, ``/statusz``
        — can refuse to read a near-empty window as a verdict instead
        of treating noise as signal.  ``bad`` counts completed-but-
        over-objective requests PLUS every failed terminal in the
        window (a shed flood is the worst latency violation there is
        and must drain the budget); ``burn_rate`` is None when the
        target leaves zero error budget."""
        if self._slo_s is None:
            return None
        lat = self._lat_win.summary()
        n_ok = lat["count"]
        n_fail = self._fail_win.summary()["count"]
        n = n_ok + n_fail
        bad = (
            0.0
            if n == 0
            else (self._lat_win.fraction_above(self._slo_s) * n_ok + n_fail)
            / n
        )
        budget = 1.0 - self._slo_target
        return {
            "objective_ms": round(1000.0 * self._slo_s, 3),
            "target": self._slo_target,
            "window_seconds": self._lat_win.window_seconds,
            "window_requests": n,
            "window_failed": n_fail,
            "bad_fraction": bad,
            "burn_rate": None if budget <= 0.0 else bad / budget,
        }

    def slo_burn_rate(self) -> Optional[float]:
        """The windowed SLO error-budget burn rate (None when no
        objective is configured, or the target leaves no budget) — the
        same number ``/statusz`` embeds, exposed directly for the
        autoscaler; :meth:`slo_burn` carries the sample counts."""
        detail = self.slo_burn()
        return None if detail is None else detail["burn_rate"]

    @property
    def host_capacity(self) -> Optional[int]:
        """Total worker slots across the cross-host fleet's host map
        (None off the net backend, or when any host is unbounded) — the
        autoscaler clamps its grow target here so a scale-up can never
        ask for workers no host has room to run."""
        return getattr(self._pool, "host_capacity", None)

    @property
    def listen_address(self) -> Optional[str]:
        """``host:port`` remote workers connect to (net backend only) —
        what ``keystone worker --connect`` takes on another box."""
        return getattr(self._pool, "listen_address", None)

    def scale_to(self, n: int, timeout: float = 60.0) -> int:
        """Resize the fleet to ``n`` replicas (grow: spawn → prime →
        admit; shrink: graceful retire-and-drain, leftovers
        re-dispatched).  Serialized under the swap lock so a concurrent
        blue/green swap never races a resize.  Returns the resulting
        size."""
        n = max(1, int(n))
        with self._swap_lock:
            if self._closing:
                raise ServiceClosed(f"service {self.name!r} is closed")
            while self._pool.size < n:
                t0 = time.monotonic()
                fresh = self._pool.add_replica(primer=self.prime_replacement)
                metrics.inc("serve.scale_ups")
                self._scale_event(
                    "up", fresh.index, time.monotonic() - t0
                )
            while self._pool.size > n:
                t0 = time.monotonic()
                left = self._pool.remove_replica(timeout=timeout)
                if left is None:
                    break  # at the floor
                metrics.inc("serve.scale_downs")
                for flush in left:
                    if getattr(flush, "unflushed", lambda: False)():
                        self._handle_stranded_flush(
                            flush, why="replica retired during scale-down"
                        )
                    else:
                        # a CLAIMED flush the victim never delivered (a
                        # wedged worker that outlived the drain
                        # timeout): fail its riders typed — late
                        # delivery into resolved futures is tolerated,
                        # exactly the supervisor's abandonment contract
                        getattr(flush, "abort", lambda: False)()
                        self.fail_flush(
                            flush,
                            FleetUnavailable(
                                "replica retired during scale-down with "
                                "a flush still in hand"
                            ),
                        )
                self._scale_event("down", None, time.monotonic() - t0)
        metrics.set_gauge("serve.workers", float(self._pool.size))
        return self._pool.size

    def _scale_event(self, action: str, replica, seconds: float) -> None:
        ledger.event(
            "serve.scale",
            action=action,
            replica=replica,
            workers=self._pool.size,
            seconds=round(seconds, 6),
        )
        rec = self.recorder
        if rec is not None:
            rec.ops(
                "serve.scale",
                action=action,
                replica=replica,
                workers=self._pool.size,
                seconds=round(seconds, 6),
            )
        logger.info(
            "scaled %s %r to %d replica(s) in %.2fs",
            action,
            self.name,
            self._pool.size,
            seconds,
        )

    def set_dispatch_window(self, n: int) -> int:
        """Retune the router's dispatch window live (autoscaler lever)."""
        return self._pool.set_window(n)

    def retune_buckets(self, buckets) -> Tuple[int, ...]:
        """Retune the padding-bucket ladder live (the PlanTuner lever).

        An atomic tuple swap: in-flight flushes already carry their
        bucket, queued requests pick from the new ladder at flush time,
        and an unprimed new bucket rides the existing prime fallback
        ladder on first use — padding changes, results never do, so no
        future is lost.  Thread fleets only: process workers bake their
        bucket set into spawned programs at startup."""
        if self.workers > 0:
            raise ValueError(
                "retune_buckets applies to thread fleets; process workers "
                "prime their bucket ladder at spawn"
            )
        from keystone_tpu.planner import registry as _plans

        ok, coerced, why = _plans.validate_knob("buckets", buckets)
        if not ok:
            raise ValueError(f"bad bucket retune: {why}")
        if coerced[-1] < self.max_batch:
            coerced = coerced + (self.max_batch,)
        self.buckets = coerced
        return self.buckets

    # ------------------------------------------------------------- statusz
    @classmethod
    def _ingress_ms(cls, reg, name: str) -> Optional[dict]:
        """One cumulative ingress histogram as a ms summary, or None
        when the front end never observed it (HTTP-only traffic has no
        binary parse samples)."""
        summary = reg.histogram_summary(name)
        return None if summary is None else cls._ms(summary)

    @staticmethod
    def _ms(window_summary: dict) -> dict:
        """A windowed summary in milliseconds (rounded for the wire)."""
        out = {"count": window_summary["count"]}
        for key in ("p50", "p95", "p99", "min", "max"):
            v = window_summary.get(key)
            out[key] = None if v is None else round(1000.0 * v, 3)
        return out

    def status(self) -> dict:
        """The live ops view ``GET /statusz`` serves: rolling-window
        latency/batch percentiles (from the windowed histograms — the
        last ``window_seconds``, not process lifetime), per-replica
        occupancy/breaker statuses, whole-process outcome counters, the
        flight-recorder stats, and — when a latency objective is
        configured — the SLO error-budget burn rate: the windowed
        fraction of requests over the objective divided by the allowed
        fraction (``1 - slo_target``); burn > 1 means the error budget
        is draining faster than it accrues."""
        lat = self._lat_win.summary()
        bat = self._batch_win.summary()
        reg = metrics.REGISTRY
        replica_stats = self.replica_statuses()
        rec = self.recorder
        out = {
            "name": self.name,
            "status": "closed" if self._closed else "ok",
            "version": self.version,
            "backend": self._pool.backend,
            "workers": self._pool.size,
            "dispatch_window": self._pool.window,
            "occupancy": round(self.occupancy(), 4),
            "queue_depth": self.queue_depth,
            "queue_bound": self.queue_bound,
            "max_batch": self.max_batch,
            "window_seconds": self._lat_win.window_seconds,
            "latency_ms": self._ms(lat),
            "batch_ms": self._ms(bat),
            "available": self.available,
            "counters": {
                name.split(".", 1)[1]: reg.counter_total(name)
                for name in (
                    "serve.submitted",
                    "serve.completed",
                    "serve.shed",
                    "serve.rejected",
                    "serve.deadline_miss",
                    "serve.batch_errors",
                    "serve.replica_restarts",
                    "serve.bisections",
                    "serve.poison",
                    "serve.poison_blocked",
                    "serve.hedges",
                    "serve.hedge_wins",
                    "serve.unavailable",
                    "serve.artifact_hits",
                    "serve.artifact_misses",
                    "serve.artifact_fallbacks",
                    "serve.worker_crashes",
                    "serve.scale_ups",
                    "serve.scale_downs",
                    "serve.dedup_hits",
                )
            },
            # the AOT tier at a glance: was a bundle configured, how
            # many bucket programs each live replica holds, and the
            # prime ladder's per-source timing totals
            "artifacts": {
                "configured": self._pool.has_artifacts,
                "installed_buckets": sum(
                    r.get("artifact_buckets", 0) for r in replica_stats
                ),
                "prime_seconds": {
                    src: reg.histogram_value(
                        "serve.prime_seconds", source=src
                    )
                    for src in ("artifact", "cache", "compile")
                },
            },
            "replicas": replica_stats,
            "supervisor": (
                None if self.supervisor is None else self.supervisor.status()
            ),
            "autoscaler": (
                None if self.autoscaler is None else self.autoscaler.status()
            ),
            "plan": _plan_status_safe(),
            "recorder": None if rec is None else rec.stats(),
        }
        # front-end ingress health (present once any front end has
        # served a connection — pure registry reads, so a library-only
        # service with no listener shows an all-zero block harmlessly
        # only if something registered the histograms; gate on traffic)
        ingress_conns = reg.counter_total(
            "ingress.bin_conns"
        ) + reg.counter_total("ingress.http_conns")
        if ingress_conns or reg.counter_total("ingress.accepts"):
            out["ingress"] = {
                "accepts": reg.counter_total("ingress.accepts"),
                "bin_conns": reg.counter_total("ingress.bin_conns"),
                "http_conns": reg.counter_total("ingress.http_conns"),
                "frames": reg.counter_total("ingress.frames"),
                "batch_rows": reg.counter_total("ingress.batch_rows"),
                "bytes_copied": reg.counter_total("ingress.bytes_copied"),
                "frame_errors": {
                    labels.get("kind", "?"): value
                    for labels, value in reg.counter_series(
                        "ingress.frame_errors"
                    )
                },
                "parse_ms": self._ingress_ms(reg, "ingress.parse_seconds"),
                "admit_ms": self._ingress_ms(reg, "ingress.admit_seconds"),
            }
        if self._telemetry is not None:
            # the fleet block: per-worker apply/wire percentiles and
            # clock-sync health, built from the spans/metric deltas
            # workers shipped over their existing reply/beat frames
            out["fleet"] = self._telemetry.fleet_status()
        if self._slo_s is not None:
            # slo_burn() carries the window sample counts next to the
            # rate — the same refuse-to-decide-on-noise detail the
            # rollout judge reads
            detail = self.slo_burn()
            bad = detail["bad_fraction"]
            out["slo"] = {
                "objective_ms": detail["objective_ms"],
                "target": detail["target"],
                "window_seconds": detail["window_seconds"],
                "window_requests": detail["window_requests"],
                "window_failed": detail["window_failed"],
                "bad_fraction": round(bad, 6),
                "compliance": round(1.0 - bad, 6),
                "burn_rate": (
                    None
                    if detail["burn_rate"] is None
                    else round(detail["burn_rate"], 3)
                ),
            }
        return out

    def rollout_status(self) -> dict:
        """The ``GET /rolloutz`` block: the live rollout phase (canary
        window or bake watch) when one is active, the recent episode
        verdicts, and the swap history ``POST /rollback`` would walk."""
        active = self._rollout_state
        guard_ = self._rollout_guard
        if guard_ is not None:
            active = guard_.status()
        rollout = self._rollout
        if rollout is not None and isinstance(active, dict):
            active = dict(active)
            active["canary"] = rollout.snapshot()
        return {
            "version": self.version,
            "active": active,
            "history": list(self._rollout_history),
            "prior_versions": list(self._version_history),
            "slo": self.slo_burn(),
        }

    def dump_trace(self, dir_path: str) -> Optional[str]:
        """Write the flight recorder's full state (the ``/tracez?full=1``
        payload) durably into ``dir_path`` and return the file path —
        the artifact ``tools/trace_report.py`` reads offline (its
        recorder-dump mode; the ``.json`` suffix is load()'s mode
        switch).  Returns None when tracing is off.  Published via
        ``utils.durable.atomic_write`` so a crash mid-dump never leaves
        a truncated file for the post-incident read."""
        import json
        import os

        rec = self.recorder
        if rec is None:
            return None
        os.makedirs(dir_path, exist_ok=True)
        seq = next(self._trace_dump_seq)
        path = os.path.join(
            dir_path,
            f"trace-{self.name}-{int(time.time())}-{seq}.json",  # lint: allow-wall-clock
        )
        payload = rec.dump()

        def _write(tmp: str) -> None:
            with open(tmp, "w") as f:
                json.dump(payload, f)

        from keystone_tpu.utils import durable

        durable.atomic_write(path, _write)
        return path

    # --------------------------------------------------------------- swap
    def swap(
        self,
        pipeline,
        version: Optional[str] = None,
        prime: bool = True,
        artifacts: Optional[dict] = None,
    ) -> dict:
        """Blue/green model hot-swap: stage a full replica generation
        for ``pipeline``, prime its padding-bucket programs while the
        OLD generation keeps serving, then atomically commit at the
        flush boundary.  Queued requests never drop — flushes already
        routed to an old replica resolve from the version that admitted
        them; everything dispatched after the commit runs on the new
        one.  Returns ``{"version", "pause_seconds", "prime_seconds",
        "replicas"}`` (``pause_seconds`` is the router-lock-held window:
        the only time no flush can be dispatched).

        Concurrent swaps serialize; a failed stage/prime leaves the old
        generation serving untouched (the ``serve.swap`` fault site
        injects exactly that).

        ``artifacts``: the new version's AOT artifact bundle (registry
        ``load_artifacts``): staged replicas install the pre-lowered
        bucket programs so the stage→prime window stops paying
        trace+lower time, and the bundle becomes the pool's for
        supervisor heals after the commit.  A damaged/skewed bundle
        degrades that swap to recompilation — it never fails it."""
        if self._closing:
            raise ServiceClosed(f"service {self.name!r} is closed")
        with self._swap_lock:
            # re-check under the lock: close() sets _closing and then
            # waits on this lock, so a swap that was queued behind
            # another swap (or raced close()'s first check) must not
            # stage a fresh generation into a shutting-down service
            if self._closing:
                raise ServiceClosed(f"service {self.name!r} is closed")
            self._swap_seq += 1
            version = version or f"swap{self._swap_seq}"
            prev_version = self.version
            with ledger.span("serve.swap", version=version):
                fault_point("serve.swap", version=version)
                t0 = time.monotonic()
                if artifacts:
                    # shipped compile-cache entries install before the
                    # staged generation primes (same rung as cold start)
                    from keystone_tpu.utils.compile_cache import (
                        seed_compile_cache,
                    )

                    seed_compile_cache(artifacts)
                staged = self._pool.stage(pipeline, version, artifacts=artifacts)
                try:
                    if prime and self._item_shape is not None:
                        self.prime(
                            replicas=staged,
                            have_artifacts=artifacts is not None,
                        )
                except BaseException:
                    # failed prime = failed swap: retire the staged
                    # workers instead of leaking them; the old
                    # generation never stopped serving
                    for r in staged:
                        r.retire()
                    raise
                prime_s = time.monotonic() - t0
                pause_s = self._pool.commit(staged, version)
                # the incoming version's PhysicalPlan replaces the old
                # one AT the commit (the plan ships with the model):
                # from the bundle manifest, or the pickled applier
                try:
                    from keystone_tpu import planner as _planner

                    plan_dict = (
                        (artifacts or {}).get("manifest") or {}
                    ).get("plan")
                    new_plan = (
                        _planner.PhysicalPlan.from_dict(plan_dict)
                        if plan_dict is not None
                        else getattr(pipeline, "plan", None)
                    )
                    if new_plan is not None:
                        self._plan = new_plan
                        _planner.install_plan(new_plan, source="swap")
                except Exception:
                    logger.warning(
                        "swap %s: shipped plan failed to install", version
                    )
            # swap-history bookkeeping for POST /rollback: the version
            # this commit displaced, newest last (internal — the pinned
            # swap return/ops surface is unchanged)
            self._version_history.append(prev_version)
            metrics.inc("serve.swaps")
            metrics.observe("serve.swap_pause_seconds", pause_s)
            metrics.observe("serve.swap_prime_seconds", prime_s)
            rec = self.recorder
            if rec is not None:
                # the swap is a control-plane span in the recorder, so
                # /tracez shows it BETWEEN the request traces it
                # interleaves with (riders routed to the retiring
                # generation before it, new-generation traffic after)
                rec.ops(
                    "serve.swap",
                    version=version,
                    pause_seconds=round(pause_s, 6),
                    prime_seconds=round(prime_s, 6),
                    replicas=len(staged),
                )
            logger.info(
                "hot-swapped %r to version %s (%d replicas, prime %.2fs, "
                "pause %.2fms)",
                self.name,
                version,
                len(staged),
                prime_s,
                1000.0 * pause_s,
            )
            return {
                "version": version,
                "pause_seconds": pause_s,
                "prime_seconds": prime_s,
                "replicas": len(staged),
            }

    # ----------------------------------------------------------- shutdown
    def close(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop accepting requests and shut the batcher down.  With
        ``drain=True`` (default) every already-queued request is flushed
        and resolved before the worker exits; with ``drain=False``
        queued requests fail with :class:`ServiceClosed`."""
        with self._cond:
            self._closing = True
            if not drain:
                self._fail_queued_locked(
                    lambda: ServiceClosed("service closed before execution")
                )
            self._cond.notify_all()
        # stop the healers first: a supervisor restarting (or a hedge
        # monitor re-enqueueing into) a pool that close() is tearing
        # down would race the retirement below — and the autoscaler
        # before both, so no resize races the drain
        if self.autoscaler is not None:
            self.autoscaler.stop()
        if self.supervisor is not None:
            self.supervisor.stop()
        if self._hedge is not None:
            self._hedge.stop()
        # the bake guard is a healer too: a revert swap racing the
        # teardown below would stage a generation into a closing pool
        # (its loop also exits on _closing; this bounds the join)
        guard_ = self._rollout_guard
        if guard_ is not None:
            guard_.stop()
        # wait out an in-flight swap: with _closing set no NEW swap can
        # start, and an in-flight one either commits into the still-live
        # pool (its generation is then retired below) or fails on its
        # own.  Without this, a swap mid-prime would commit fresh worker
        # threads into a pool close() already tore down, leaking them.
        # Bounded: a wedged prime must not wedge close() — the pool's
        # _draining flag makes a late commit() refuse the install.
        if self._swap_lock.acquire(timeout=timeout):
            self._swap_lock.release()
        else:
            logger.warning(
                "service %r closing with a swap still in flight after "
                "%.1fs; a late commit will be refused",
                self.name,
                timeout,
            )
        # release a batcher blocked at the pool's dispatch window BEFORE
        # joining it: on a wedged fleet the batcher would otherwise burn
        # this whole join timeout, and its in-hand batch would be
        # dropped on the floor (in neither the service queue nor any
        # replica queue) with its futures never resolved.  Drained, it
        # dispatches the batch into a replica queue where the pool
        # close below hands it back as abandoned.
        self._pool.begin_drain()
        self._worker.join(timeout)
        if self._worker.is_alive():
            logger.warning(
                "service %r batcher did not exit within %.1fs", self.name, timeout
            )
            # the batcher is wedged (e.g. a hung apply with no deadline
            # configured): it will never drain the queue, so fail the
            # still-queued futures rather than leave their callers
            # blocked forever
            with self._cond:
                self._fail_queued_locked(
                    lambda: ServiceClosed(
                        "service closed with the batcher wedged; "
                        "request never executed"
                    )
                )
        # retire the replica workers: each drains its already-routed
        # flushes first, so drained == every admitted future resolved.
        # A wedged replica worker hands back its abandoned flushes
        # (already-delivered hedge-loser copies fail no one: _fail
        # skips resolved futures).
        for flush in self._pool.close(timeout=timeout):
            flush.abort()
            for req in flush.riders:
                self._fail(
                    req,
                    ServiceClosed(
                        "service closed with its replica wedged; "
                        "request never executed"
                    ),
                )
        self._closed = True

    def __enter__(self) -> "PipelineService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------- worker
    def _loop(self) -> None:
        """The batcher: form flushes, route each onto a replica.  The
        dispatch is an enqueue — while replica 0 computes a flush, the
        batcher is already forming (and routing) the next one, which is
        what lets N replicas serve N flushes concurrently."""
        ledger.restore_context(self._obs_ctx)
        while True:
            flush = self._next_batch()
            if flush is None:
                return
            # the canary split (serve/rollout.py): while a guarded
            # rollout's judge window is open, the controller claims a
            # deterministic seeded-hash fraction of flushes for the
            # staged generation; everything else (and everything when
            # no window is open — one attribute read) routes normally.
            # A claimed flush is NOT hedged: hedging re-enqueues onto
            # the live generation, which would both pollute the canary
            # sample and mask a slow canary behind a fast live win.
            rollout = self._rollout
            if rollout is not None and rollout.take(flush):
                continue
            try:
                self._pool.dispatch(flush)
            except FleetUnavailable as e:
                # fail fast: no replica can take this flush — resolve
                # its riders NOW (503 at HTTP) instead of parking them
                # behind a pool the router refuses
                flush.abort()
                self.fail_flush(flush, e)
                continue
            hedge = self._hedge
            if hedge is not None:
                hedge.schedule(flush, self._hedge_delay_s())

    def _next_batch(self):
        """Block until a flush is due; pop and return it (None = shut
        down with an empty queue).  Flush condition: ``max_batch``
        requests waiting, the OLDEST has waited ``max_wait_s``, or the
        service is closing (drain)."""
        with self._cond:
            while not self._q:
                if self._closing:
                    return None
                # untimed: every producer path (submit, close) notifies
                # under this condition, so an idle service costs zero
                # wakeups
                self._cond.wait()
            flush_at = self._q[0].t_submit + self.max_wait_s
            while len(self._q) < self.max_batch and not self._closing:
                timeout = flush_at - time.monotonic()
                if timeout <= 0:
                    break
                self._cond.wait(timeout)
            k = min(len(self._q), self.max_batch)
            batch = [self._q.popleft() for _ in range(k)]
            metrics.set_gauge("serve.queue_depth", len(self._q))
            return _Flush(batch, f"b{next(self._batch_seq)}")

    def _fail(self, req, exc, **attrs) -> None:
        """Deliver an exception to a request, tolerating a caller that
        already cancelled its future — an InvalidStateError here would
        kill the batcher thread and brick the whole service.  Also the
        trace terminal for failure paths: the outcome is ``shed`` for a
        deadline shed, ``poison`` for an isolated poison request,
        ``error`` otherwise, finished only if the trace is still live
        (an already-finalized id is left alone).  The trace is finalized
        BEFORE the future is delivered, so a caller woken by
        ``.result()`` can immediately resolve its id via ``/requestz``
        without racing the finalization.  An already-resolved future
        (a hedge loser's copy, a supervisor-abandoned flush whose hung
        runner delivered after all) is skipped entirely — no double
        terminal, no phantom SLO burn."""
        if req.future.done():
            return
        waited = time.monotonic() - req.t_submit
        # client faults (shape mismatch, poison content — the 4xx
        # family) do not burn the server's SLO error budget
        if not isinstance(exc, (TypeError, ValueError)):
            self._fail_win.observe(waited)
        if isinstance(exc, guard.DeadlineExceeded):
            outcome = "shed"
        elif isinstance(exc, PoisonRequest):
            outcome = "poison"
        else:
            outcome = "error"
        self._account_tenant(req, outcome, waited)
        rollout = self._rollout
        if rollout is not None:
            rollout.observe(req, outcome, waited)
        rid = req.request_id
        if rid is not None:
            rec = self.recorder
            if rec is not None:
                rec.finish(
                    rid,
                    outcome,
                    only_live=True,
                    error=f"{type(exc).__name__}: {exc}",
                    **attrs,
                )
            if ledger.active() is not None:
                ledger.event(
                    "serve.request", request_id=rid, outcome=outcome, **attrs
                )
        try:
            req.future.set_exception(exc)
        except InvalidStateError:
            pass

    def _run_flush(self, replica, flush) -> None:
        """One routed flush, on ``replica``'s worker thread: claim it
        (exactly one runner per flush — the hedging/crash-requeue
        guarantee), then shed, pad, apply, resolve futures, account the
        outcome to the router and the replica's breaker.  An unclaimed
        pop is a hedge loser (or a supervisor-aborted flush): cancelled
        without device work, charged breaker-NEUTRAL."""
        if not flush.claim():
            if flush.state != _Flush.ABORTED:
                # the other replica won the hedge race — this copy is
                # the cancelled loser (no device work was wasted)
                metrics.inc("serve.hedge_cancelled")
                rec = self.recorder
                if rec is not None:
                    rec.ops(
                        "serve.hedge",
                        batch=flush.bid,
                        replica=replica.index,
                        outcome="cancelled",
                    )
            self._pool.complete(replica, ok=None)
            return
        if flush.hedged and replica.index != flush.primary:
            metrics.inc("serve.hedge_wins")
        ok: Optional[bool] = False
        try:
            ok = self._run_batch(flush, replica)
        except WorkerCrashed:
            # the replica's worker PROCESS died under this flush: no
            # result was produced or delivered, so return the claim and
            # re-raise — the replica worker loop's crash handler
            # front-requeues the flush and marks the slot dead, and the
            # supervisor's replacement re-claims and serves it.  Zero
            # lost futures, same contract as a thread crash.
            flush.unclaim()
            metrics.inc("serve.worker_crashes", replica=replica.index)
            raise
        except BaseException as e:
            # an escape past _run_batch's own containment (a delivery-
            # layer bug): the claim is SPENT, so a worker-crash requeue
            # could never run this flush again — fail the unresolved
            # riders here, while we still own them.  Escapes reaching
            # the worker loop are therefore all PRE-claim, where the
            # crash handler's front-requeue is always safe.
            logger.exception(
                "flush %s delivery escaped containment on replica %d",
                flush.bid,
                replica.index,
            )
            self.fail_flush(flush, e)
        finally:
            flush.done()
            self._pool.complete(replica, ok=ok)

    def _run_batch(self, flush, replica) -> Optional[bool]:
        """Returns False exactly when the replica's APPLY failed — the
        outcome that should charge its breaker toward open.  True means
        the apply succeeded (charges a success, closes a half-open
        probe).  Shed/cancelled-only batches return None — nothing ran
        on the device, so the breaker is not charged either way: a sick
        replica whose inflated EWMA sheds every rider must not keep
        "passing" its half-open probes with zero device work."""
        batch = flush.riders
        bid = flush.bid
        rec = self.recorder
        now = time.monotonic()
        if rec is not None:
            riders = [r.request_id for r in batch if r.request_id is not None]
            if riders:
                # the batch span records its rider ids as span links —
                # the flush is SHARED by its riders, so it is recorded
                # once and joined on read (/requestz, trace_report).
                # One "serve.batch" event per rider marks its arrival on
                # THIS replica's worker (batch id + replica + queue
                # wait); deeper flush facts live on the batch record —
                # per-rider event count is part of the overhead budget.
                rec.batch(bid, riders, replica=replica.index, rows=len(batch))
            for req in batch:
                rec.annotate(
                    req.request_id,
                    "serve.batch",
                    batch=bid,
                    replica=replica.index,
                    queue_wait_seconds=round(now - req.t_submit, 6),
                )
        # shed what cannot make it: a request whose deadline expires
        # before the batch's predicted completion would occupy a padded
        # row and return an answer its caller already abandoned
        predicted = self._ewma_batch_s
        live = []
        for req in batch:
            fut = req.future
            if fut.done():
                # resolved on a previous attempt (a worker-crash re-run:
                # shed/cancelled/failed riders keep their outcome)
                continue
            if fut.running():
                # already claimed by a previous attempt on a crashed
                # worker — still owed a result; no state transition to
                # make (and set_running_or_notify_cancel on a RUNNING
                # future logs CRITICAL + raises)
                running = True
            else:
                running = fut.set_running_or_notify_cancel()
            if not running:
                # the caller cancelled while the request was queued:
                # don't spend a padded row on it (and, marked RUNNING,
                # a surviving request can no longer be cancelled out
                # from under the set_result below)
                metrics.inc("serve.cancelled")
                if rec is not None:
                    rec.finish(
                        req.request_id,
                        "cancelled",
                        only_live=True,
                        batch=bid,
                        replica=replica.index,
                    )
                continue
            if req.deadline is not None and req.deadline.remaining() <= predicted:
                metrics.inc("serve.shed")
                self._fail(
                    req,
                    guard.DeadlineExceeded(
                        "serve.shed", time.monotonic() - req.t_submit
                    ),
                    batch=bid,
                    replica=replica.index,
                    predicted_seconds=round(predicted, 6),
                    waited_seconds=round(time.monotonic() - req.t_submit, 6),
                )
            else:
                live.append(req)
        if not live:
            # nothing executed, so no new latency sample — DECAY the
            # predictor instead of leaving it frozen: one outlier batch
            # (a cold compile on an unprimed service) would otherwise
            # pin the EWMA above every deadline and shed 100% of
            # traffic forever.  Decay-and-retry converges: predicted
            # drops geometrically until a batch runs and real samples
            # resume.
            with self._ewma_lock:
                self._ewma_batch_s *= 1.0 - _EWMA_ALPHA
            return None
        k = len(live)
        bucket = self._bucket_for(k)
        trace_ids = [r.request_id for r in live if r.request_id is not None]
        deg0 = (
            metrics.REGISTRY.counter_total("executor.degraded")
            if rec is not None
            else 0.0
        )
        t0 = time.monotonic()
        try:
            with ledger.span(
                "serve.batch",
                rows=k,
                bucket=bucket,
                replica=replica.index,
                batch=bid,
                request_ids=trace_ids,
            ):
                fault_point("serve.batch")
                batch_deadline = None
                if self._degrade:
                    # the LOOSEST rider's deadline (and only when every
                    # rider carries one): the executor budget exists to
                    # stop stages NOBODY is still waiting on and to
                    # trigger declared degradation under pressure —
                    # keyed to min() instead, one near-expiry straggler
                    # that escaped the shed predictor would
                    # DeadlineExceeded the whole flush and fail
                    # co-batched requests holding comfortable budgets
                    dls = [r.deadline for r in live if r.deadline is not None]
                    if dls and len(dls) == len(live):
                        batch_deadline = max(dls, key=lambda d: d.at)
                # trace context for the wire: set ONLY when the recorder
                # is on AND the fleet is remote — recorder-off keeps
                # every apply frame byte-identical (pinned), and the
                # thread fleet has no wire to annotate.  Thread-local
                # because _apply_reqs is an override point
                # (serve/tenants.py) whose signature must not grow.
                if rec is not None and self._telemetry is not None:
                    self._trace_tls.ctx = {
                        "batch": bid,
                        "request_ids": trace_ids[: self._trace_ctx_cap],
                    }
                try:
                    out = self._apply_reqs(live, replica, batch_deadline)
                finally:
                    self._trace_tls.ctx = None
        except WorkerCrashed:
            # process death is NOT a batch error: the flush will be
            # re-run whole on the slot's replacement (see _run_flush)
            raise
        except BaseException as e:  # one bad batch must not kill the worker
            metrics.inc("serve.batch_errors")
            logger.warning(
                "serve batch of %d failed on replica %d: %s: %s",
                k,
                replica.index,
                type(e).__name__,
                e,
            )
            if rec is not None:
                rec.batch_update(bid, error=f"{type(e).__name__}: {e}")
            if self._bisect and _poison_suspect(e):
                # a request-attributable failure: bisect the batch to
                # isolate the poison rider(s) — innocent co-batched
                # riders complete, the poison fails typed + quarantined
                return self._bisect_flush(live, replica, bid, batch_deadline, e)
            for req in live:
                self._fail(req, e, batch=bid, replica=replica.index)
            return False
        dt = time.monotonic() - t0
        with self._ewma_lock:
            self._ewma_batch_s = (
                dt
                if not self._ewma_batch_s
                else (1.0 - _EWMA_ALPHA) * self._ewma_batch_s + _EWMA_ALPHA * dt
            )
        metrics.inc("serve.batches")
        self._batch_win.observe(dt)
        metrics.observe("serve.batch_rows", k)
        degraded = False
        if rec is not None:
            # best-effort per-flush degradation detection: the executor
            # counts declared-stage degradations process-wide, so a
            # delta across THIS apply marks the flush (concurrent
            # flushes can cross-attribute — observability, not control)
            degraded = (
                metrics.REGISTRY.counter_total("executor.degraded") > deg0
            )
            rec.batch_update(
                bid,
                rows=k,
                bucket=bucket,
                seconds=round(dt, 6),
                degraded=degraded,
            )
        self._deliver_completed(
            live, out, replica, bid, dt, t0, degraded=degraded
        )
        return True

    def _deliver_completed(
        self, reqs, out, replica, bid, dt, t0, degraded=False
    ) -> None:
        """Resolve completed riders: latency/outcome accounting, trace
        terminals, then the result delivery — shared by the flush happy
        path and bisection's innocent-rider completions.  A rider whose
        future is already resolved (a supervisor-abandoned flush whose
        hung runner finished after all) is skipped: no double terminal,
        no double metrics, and the late ``set_result`` is swallowed."""
        rec = self.recorder
        outcome = "degraded" if degraded else "completed"
        done_t = time.monotonic()
        # one ledger-activation check per FLUSH, not per rider: the
        # inert-path cost of N module-frontend calls is real at serving
        # rates (part of the recorder overhead budget)
        led_on = ledger.active() is not None
        rollout = self._rollout
        for i, req in enumerate(reqs):
            if req.future.done():
                continue
            self._lat_win.observe(done_t - req.t_submit)
            late = req.deadline is not None and req.deadline.expired()
            if late:
                # completed, but late: the shed predictor under-estimated
                # (e.g. the first batch after a stall) — count it so the
                # bench's "completed beat their deadlines" claim is honest
                metrics.inc("serve.deadline_miss")
            metrics.inc("serve.completed")
            self._account_tenant(req, outcome, done_t - req.t_submit)
            if rollout is not None:
                rollout.observe(req, outcome, done_t - req.t_submit)
            if req.request_id is not None:
                if rec is not None:
                    rec.finish(
                        req.request_id,
                        outcome,
                        batch=bid,
                        replica=replica.index,
                        apply_seconds=round(dt, 6),
                        late=late,
                    )
                if led_on:
                    ledger.event(
                        "serve.request",
                        request_id=req.request_id,
                        outcome=outcome,
                        batch=bid,
                        replica=replica.index,
                        seconds=round(done_t - req.t_submit, 6),
                        queue_wait_seconds=round(t0 - req.t_submit, 6),
                    )
            try:
                req.future.set_result(out[i])
            except InvalidStateError:
                pass  # a racing cancel/abandonment got there first

    # ---------------------------------------------------------- bisection
    def _bisect_flush(
        self, live, replica, bid, batch_deadline, first_error
    ) -> Optional[bool]:
        """Isolate poison rider(s) in a failed flush by recursive
        halving, re-using the padding buckets: each failing group is
        split and both halves re-applied; a failing SINGLETON is the
        poison — it alone fails (typed :class:`PoisonRequest`, content
        quarantined), every innocent rider completes.  Depth is
        structurally bounded by ⌈log2(rows)⌉ halvings; at most two
        applies run per level.  Returns the flush's breaker charge:
        True when only poison failures occurred (the replica is
        healthy), False when infrastructure failed a re-run too."""
        metrics.inc("serve.bisections")
        deepest = 0
        applies = 0
        poisons = 0
        infra_failed = False
        t_bisect0 = time.monotonic()

        def fail_poison(req, cause):
            nonlocal poisons
            poisons += 1
            metrics.inc("serve.poison")
            key = _content_key(req.x)
            with self._poison_lock:
                self._poison_cache[key] = time.monotonic()
                self._poison_cache.move_to_end(key)
                while len(self._poison_cache) > _POISON_CACHE_CAP:
                    self._poison_cache.popitem(last=False)
            self._fail(
                req,
                PoisonRequest(
                    "request content fails the model "
                    f"({type(cause).__name__}: {cause}); isolated by "
                    "batch bisection and quarantined"
                ),
                batch=bid,
                replica=replica.index,
            )

        def run_group(reqs, depth):
            nonlocal deepest, applies, infra_failed
            deepest = max(deepest, depth)
            try:
                applies += 1
                t0 = time.monotonic()
                out = self._apply_reqs(reqs, replica, batch_deadline)
            except BaseException as ge:
                if isinstance(ge, WorkerCrashed):
                    # the worker process died mid-bisect: propagate so
                    # the whole flush re-runs on the replacement
                    # (already-resolved riders are skipped there)
                    raise
                if not _poison_suspect(ge):
                    # infrastructure failed the RE-RUN: this group's
                    # riders get the real error, and the replica is
                    # charged (it could not complete clean work)
                    infra_failed = True
                    for req in reqs:
                        self._fail(req, ge, batch=bid, replica=replica.index)
                    return
                if len(reqs) == 1:
                    fail_poison(reqs[0], ge)
                    return
                mid = (len(reqs) + 1) // 2
                run_group(reqs[:mid], depth + 1)
                run_group(reqs[mid:], depth + 1)
                return
            self._deliver_completed(
                reqs, out, replica, bid, time.monotonic() - t0, t0
            )

        if len(live) == 1:
            fail_poison(live[0], first_error)
        else:
            mid = (len(live) + 1) // 2
            run_group(live[:mid], 1)
            run_group(live[mid:], 1)
        took = time.monotonic() - t_bisect0
        if ledger.active() is not None:
            ledger.event(
                "serve.bisect",
                batch=bid,
                replica=replica.index,
                rows=len(live),
                depth=deepest,
                n=applies,
                seconds=round(took, 6),
            )
        rec = self.recorder
        if rec is not None:
            rec.batch_update(bid, depth=deepest, poisons=poisons)
            rec.ops(
                "serve.bisect",
                batch=bid,
                replica=replica.index,
                rows=len(live),
                depth=deepest,
                poisons=poisons,
                seconds=round(took, 6),
            )
        logger.warning(
            "bisected a poisoned flush of %d on replica %d: %d poison "
            "request(s) isolated in %d applies (depth %d, %.3fs)",
            len(live),
            replica.index,
            poisons,
            applies,
            deepest,
            took,
        )
        return False if infra_failed else True

    # -------------------------------------------------------------- apply
    def _apply_reqs(self, reqs, replica, deadline):
        """One flush's apply body: stack the riders' rows and run the
        frozen graph.  Returns something indexable per rider (ndarray
        rows here).  The multi-tenant service overrides this with the
        segment-aware shared-pool apply — both the flush happy path and
        bisection's re-runs route through it, so poison isolation works
        identically per tenant.

        Preformed-flush fast path: when the flush is a complete
        in-order image of ONE admission block (slab-direct ingress),
        the block's slab IS the padded batch — already bucket-shaped,
        pad rows zeroed at allocation — so the ``np.stack`` copy and
        the ``iter_row_chunks`` re-pad are both skipped, and a process
        worker can attach the slab by reference."""
        blk = _block_of(reqs)
        if blk is not None and blk.padded_rows == self._bucket_for(len(reqs)):
            metrics.inc("serve.preformed_flushes")
            return self._apply_rows(
                blk.array,
                deadline=deadline,
                replica=replica,
                pre_padded_n=len(reqs),
                slab_ref=blk.ref,
            )
        stacked = np.stack([req.x for req in reqs])
        metrics.inc("serve.bytes_copied", stacked.nbytes)
        return self._apply_rows(stacked, deadline=deadline, replica=replica)

    def _bucket_for(self, k: int) -> int:
        for b in self.buckets:
            if b >= k:
                return b
        return self.buckets[-1]

    def _apply_rows(
        self,
        stacked: np.ndarray,
        deadline=None,
        replica=None,
        prime: bool = False,
        source_box: Optional[list] = None,
        pre_padded_n: Optional[int] = None,
        slab_ref: Optional[dict] = None,
        **apply_kw,
    ) -> np.ndarray:
        """Pad ``(k, ...)`` rows up to the smallest bucket >= k (the
        ``iter_row_chunks`` pad discipline — zero pad rows, outputs
        sliced back to k), apply the frozen graph on ``replica``
        (default: the pool's first), return host rows.

        ``source_box``: when given, ``"artifact"`` is appended iff the
        batch the applier actually sees matches an installed AOT bucket
        program AND the program survived the call — the authoritative
        prime-source label.  Checked on the POST-construction dataset
        (a sharded deviceless path may pad the batch past the bucket
        shape, in which case the program does not serve), and
        RE-checked after the apply (a program failing at call time is
        dropped and the walk serves — labeling that bucket "artifact"
        would hide exactly the fallback the metric exists to show)."""
        from keystone_tpu.workflow.dataset import Dataset
        from keystone_tpu.workflow.transformer import iter_row_chunks

        if pre_padded_n is not None:
            # slab-direct flush (serve/ingress.py): ``stacked`` is the
            # admission block's array, ALREADY padded to the bucket with
            # zeroed pad rows — re-padding would be the exact copy the
            # zero-copy path exists to skip
            k = int(pre_padded_n)
            padded = stacked
        else:
            k = stacked.shape[0]
            bucket = self._bucket_for(k)
            padded, _mask, _start = next(
                iter(iter_row_chunks(stacked, None, bucket))
            )
        rep = replica if replica is not None else self._pool.replicas[0]
        if getattr(rep.applier, "remote_worker", False):
            # process fleet: the padded HOST batch goes straight to the
            # worker over the shared-memory wire — the router performs
            # no device transfer and holds the GIL only for the memcpy.
            # The n kwarg rides through Replica.apply to the remote
            # applier; prime is consumed BY Replica.apply (it skips the
            # serve.replica fault site for warm-ups — the worker's
            # apply is identical either way).
            if slab_ref is not None and getattr(
                rep.applier, "accepts_slab_ref", False
            ):
                # the ingress already landed the batch in a shared-
                # memory slab: ship the REFERENCE, the worker attaches
                # the same segment by name — the dispatch memcpy is
                # skipped too
                apply_kw = dict(apply_kw, slab_ref=slab_ref)
            trace_ctx = getattr(self._trace_tls, "ctx", None)
            if trace_ctx is not None:
                # recorder-on dispatch: the batch id + rider ids ride
                # the apply frame so the worker's shipped spans stitch
                # back to this flush's record.  None (recorder off,
                # prime calls) adds no key — the frame is byte-identical
                apply_kw = dict(apply_kw, trace=trace_ctx)
            out = rep.apply(
                padded, deadline=deadline, prime=prime, n=k, **apply_kw
            )
            if source_box is not None and rep.applier.has_bucket_program(
                tuple(padded.shape), padded.dtype
            ):
                source_box.append("artifact")
            return np.asarray(out.array)[:k]
        if rep.device is not None:
            # fleet path: commit the batch to THIS replica's device —
            # the default Dataset sharding spans every local device,
            # which XLA rejects against parameters pinned to one
            import jax

            ds = Dataset(jax.device_put(padded, rep.device), n=k, shard=False)
        else:
            ds = Dataset(padded, n=k)
        has = getattr(rep.applier, "has_bucket_program", None)
        prog_key = None
        if (
            source_box is not None
            and has is not None
            and not ds.is_host
            and ds.mask is None
            and has(tuple(ds.array.shape), ds.array.dtype)
        ):
            prog_key = (tuple(ds.array.shape), ds.array.dtype)
        out = rep.apply(ds, deadline=deadline, prime=prime, **apply_kw)
        if prog_key is not None and has(*prog_key):
            source_box.append("artifact")
        if isinstance(out, dict):
            # multi-tenant applier: one full-batch output per tenant
            # (heads differ in output width, so there is no single
            # stacked array to return)
            return {t: np.asarray(d.array)[:k] for t, d in out.items()}
        return np.asarray(out.array)[:k]


def serve(
    pipeline,
    *,
    max_batch: int = 32,
    max_wait_ms: Optional[float] = None,
    queue_bound: int = 128,
    buckets: Optional[Sequence[int]] = None,
    deadline_ms: Optional[float] = None,
    example=None,
    degrade: bool = True,
    name: str = "serve",
    replicas: int = 1,
    devices: Optional[Sequence] = None,
    version: str = "v0",
    recorder=True,
    slo_ms: Optional[float] = None,
    slo_target: float = 0.99,
    slo_window_s: Optional[float] = None,
    supervise: bool = True,
    heartbeat_s: float = 30.0,
    supervise_interval_s: float = 0.5,
    restart_limit: int = 3,
    restart_window_s: float = 60.0,
    hedge_ms=_UNSET,
    bisect: bool = True,
    artifacts: Optional[dict] = None,
    workers: int = 0,
    worker_opts: Optional[dict] = None,
    autoscale: Optional[dict] = None,
    hosts=None,
) -> PipelineService:
    """Freeze a fitted pipeline and stand up a :class:`PipelineService`.

    - ``max_batch`` / ``max_wait_ms`` — flush the micro-batch when either
      bound is hit (count, or oldest-request age).  ``max_wait_ms``,
      ``buckets``, ``hedge_ms``, and the dispatch window resolve through
      the physical-plan precedence (explicit arg > env > installed
      ``PhysicalPlan`` > static default — ``keystone_tpu.planner``);
      passing a value always wins, and with no plan the defaults are
      the historical ones (5 ms wait, power-of-two buckets, hedging
      off).
    - ``queue_bound`` — admission control: ``submit`` past this depth
      raises :class:`Overloaded`.
    - ``buckets`` — padding-bucket batch sizes (default: powers of two
      from 8 up to ``max_batch``); every flush pads to the smallest
      bucket that fits, so compiled program shapes are finite.
    - ``deadline_ms`` — default per-request deadline; requests predicted
      to miss it are shed instead of executed.
    - ``example`` — one datum, used to prime every bucket's compiled
      program at construction (strongly recommended: without it the
      first request per bucket pays the trace+compile).
    - ``degrade`` — plumb the batch's loosest request deadline into the
      executor so ``optional``/``with_fallback`` stages degrade on the
      serve path (loosest so a single tight straggler cannot fail its
      co-batched requests; applied only when every rider has one).
    - ``replicas`` / ``devices`` — size of the serving fleet: each
      replica is an independent clone of the fitted state placed on its
      own device (``devices=None`` cycles ``jax.local_devices()``).
      ``replicas=1`` with no devices is the single-device fast path —
      the given pipeline's applier serves directly, no clone.
    - ``version`` — the model version label the initial replica
      generation reports (``/healthz``, ``/replicas``); hot-swaps via
      :meth:`PipelineService.swap` move it.
    - ``recorder`` — the flight recorder (ON by default): every request
      gets a traced causal chain (ingress → enqueue → batch → replica →
      outcome) in a bounded in-memory ring, served live by
      ``GET /tracez`` / ``GET /requestz/<id>``.  ``False`` disables
      tracing entirely — the service mints no ids and runs no trace
      hook (the PR-5 path, byte-identical — pinned); the HTTP front
      end still echoes an id per response for client-side log
      correlation, it just resolves nowhere server-side.  Or pass a
      configured :class:`~keystone_tpu.obs.recorder.FlightRecorder`.
    - ``slo_ms`` / ``slo_target`` — the latency objective behind
      ``GET /statusz``'s error-budget burn rate (default objective:
      ``deadline_ms``; no deadline, no SLO section).  ``slo_window_s``
      resizes the burn observation window (default 60 s) — the knob a
      guarded rollout's judge/bake guard (``serve/rollout.py``) reads
      through, so short windows make rollback verdicts reflect the
      canary's now rather than the last minute.
    - ``supervise`` (default ON) — the self-healing
      :class:`~keystone_tpu.serve.fleet.ReplicaSupervisor`: dead/wedged
      replica workers are restarted in place (re-clone + re-place from
      the pool's source, buckets re-primed, router rejoined);
      ``restart_limit`` restarts within ``restart_window_s`` seconds
      quarantine the slot.  ``heartbeat_s`` is the wedge budget — a
      worker holding one flush longer than this is declared wedged, so
      size it above the slowest honest apply.
    - ``hedge_ms`` — hedged dispatch (default OFF): a batch still
      unflushed after max(``hedge_ms``, 3× the EWMA batch time) is
      re-enqueued on a second replica; whichever replica claims it
      first runs it, the loser is cancelled without device work and
      charged breaker-neutral.
    - ``bisect`` (default ON) — batch-failure bisection: a flush that
      fails with a request-attributable error is recursively halved to
      isolate the poison request, which alone fails (typed
      :class:`PoisonRequest`, HTTP 422) while innocent co-batched
      riders complete; the content-keyed quarantine cache then refuses
      repeat offenders at admission.
    - ``workers`` — the PROCESS fleet (default 0 = the threaded fleet,
      byte-for-byte the pre-process path): ``workers=N`` runs N
      one-replica worker processes behind the same router — each loads
      the deploy payload + AOT artifacts, primes, and serves applies
      over a shared-memory wire (``serve/wire.py``), so a multi-core
      host's throughput is bounded by cores, not the GIL.  Exclusive
      with ``replicas``/``devices``.  ``worker_opts`` tunes spawn
      (``ready_timeout``, ``max_slab_bytes``).
    - ``hosts`` — the CROSS-HOST fleet (needs ``workers>=1``): workers
      connect over TCP (``serve/net.py``) instead of sharing memory.
      A host map (``"hostA:4,hostB:4"``, or a list / ``HostMap``)
      tells the router where ``keystone worker --connect`` processes
      may be spawned; ``"local"`` spawns on this box.  Each remote
      worker beats a heartbeat lease — an expired lease is treated as
      death (flushes re-served on survivors), and the worker
      self-fences when its OWN lease lapses so a healed partition
      cannot double-serve.  ``worker_opts`` grows ``lease_s``,
      ``listen_host``/``listen_port``, ``spawn_grace_s``,
      ``max_frame_bytes``.  Without ``hosts``, ``workers=N`` stays on
      the shared-memory transport, byte-for-byte.
    - ``autoscale`` — SLO-driven autoscaling (default OFF): a config
      dict for :class:`~keystone_tpu.serve.autoscale.Autoscaler`
      (``min_workers``/``max_workers``/``interval_s``/thresholds).  A
      control thread watches windowed occupancy, queue depth, SLO
      error-budget burn, and the shared-pool hit rate; it grows the
      fleet (spawn → prime-from-artifacts → admit), retires idle
      replicas (drain → join), and retunes the dispatch window live.
    - ``artifacts`` — an AOT artifact bundle
      (``FrozenApplier.export_artifacts`` / registry
      ``load_artifacts``): every replica installs the pre-lowered
      bucket programs so construction-time priming loads instead of
      re-tracing — the cold-start path stops paying compile time.  Any
      mismatch (jax version skew, different backend, corrupt blob,
      signature drift) silently falls one rung down the ladder —
      artifact → persistent compile cache → fresh compile — counted as
      ``serve.artifact_fallbacks``, never failing the deploy.
    """
    return PipelineService(
        pipeline,
        max_batch=max_batch,
        max_wait_ms=max_wait_ms,
        queue_bound=queue_bound,
        buckets=buckets,
        deadline_ms=deadline_ms,
        example=example,
        degrade=degrade,
        name=name,
        replicas=replicas,
        devices=devices,
        version=version,
        recorder=recorder,
        slo_ms=slo_ms,
        slo_target=slo_target,
        slo_window_s=slo_window_s,
        supervise=supervise,
        heartbeat_s=heartbeat_s,
        supervise_interval_s=supervise_interval_s,
        restart_limit=restart_limit,
        restart_window_s=restart_window_s,
        hedge_ms=hedge_ms,
        bisect=bisect,
        artifacts=artifacts,
        workers=workers,
        worker_opts=worker_opts,
        autoscale=autoscale,
        hosts=hosts,
    )
