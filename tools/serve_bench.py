"""Open-loop load generator for the online serving subsystem.

Drives a :class:`keystone_tpu.serve.PipelineService` with a fixed
arrival schedule — requests are submitted at the target QPS whether or
not earlier ones completed (open loop: the honest way to measure a
service, since closed-loop generators self-throttle and hide queueing
collapse) — and reports latency percentiles, achieved throughput, mean
batch occupancy, and the shed/rejected breakdown.

Usage (CPU-safe; any laptop)::

    JAX_PLATFORMS=cpu python tools/serve_bench.py \
        --qps 2000 --duration 3 --max-batch 32 --max-wait-ms 2 \
        --deadline-ms 250 --queue-bound 128

    # burst mode: arrivals in groups of N at the same mean rate
    ... --burst 16

    # emulate a heavier model: stall every flush via the serve.batch
    # fault site (the chaos machinery doubles as a load shaper)
    ... --batch-delay-ms 10

    # serve a saved model instead of the synthetic default
    ... --model fitted.pkl --dim 512

    # replica fleet: one FrozenApplier clone per local device
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python tools/serve_bench.py --replicas 4 ...

    # blue/green hot-swap halfway through the offer window; the report
    # gains the swap pause + prime time and per-replica occupancy
    ... --replicas 4 --swap-mid-run

    # inject a straggler (replica 0 stalls every flush 40 ms) and hedge
    # around it: queued flushes escape onto a healthy replica
    ... --replicas 2 --straggler-ms 40 --hedge-ms 10

    # multi-tenant sharing A/B (ISSUE 14): N pipelines sharing a
    # featurization prefix, shared stage pool vs sharing disabled —
    # per-tenant QPS/p99, fairness ratio, pool counters, bit-identity
    python tools/serve_bench.py --tenants 3

The default workload is a small synthetic two-stage pipeline
(NormalizeRows → LinearMapper) so the tool measures the serving layer
itself; ``--model`` swaps in a real fitted pipeline whose input is a
``--dim``-vector.  Exit code 0; the report is one JSON object on
stdout.  ``bench.py --leg-serve`` embeds this report (overload config)
in the round artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import wait as futures_wait

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_pipeline(dim: int = 64, classes: int = 16, seed: int = 0):
    """The synthetic two-stage workload (NormalizeRows → LinearMapper)."""
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.models.linear import LinearMapper
    from keystone_tpu.ops.stats import NormalizeRows
    from keystone_tpu.workflow import Pipeline

    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(dim, classes)).astype(np.float32))
    return Pipeline.of(NormalizeRows()) | LinearMapper(w)


def _fft_gather_feat(dim: int, branches: int, seed: int = 0):
    """A ``branches``-way gather of RandomSignNode → PaddedFFT →
    LinearRectifier chains — the MnistRandomFFT shape; returns
    ``(featurizer pipeline, feature dim)``.  Each branch's rectifier
    carries a DISTINCT constant: identical-structure branches lower to
    identical HLO that the persistent compile cache dedupes across
    programs (hiding trace costs in A/Bs), which real heterogeneous
    pipelines don't enjoy.  Shared by the AOT-artifact workload and the
    multi-tenant one — one definition, so the benches cannot silently
    drift apart."""
    from keystone_tpu.ops.stats import (
        LinearRectifier,
        PaddedFFT,
        RandomSignNode,
    )
    from keystone_tpu.workflow import Pipeline

    feat = Pipeline.gather(
        [
            RandomSignNode.init(dim, seed * 1000 + i)
            | PaddedFFT()
            | LinearRectifier(0.0, alpha=0.001 * (i + 1))
            for i in range(int(branches))
        ]
    )
    padded = 1 << (dim - 1).bit_length()
    return feat, branches * (padded // 2 + 1) * 2


def build_aot_pipeline(
    dim: int = 64, classes: int = 16, seed: int = 0, branches: int = 8
):
    """The cold-start/restart A/B workload: an ``_fft_gather_feat``
    featurizer feeding a normalized linear head.  The gather is the
    point: a plain two-stage chain fuses into ONE tiny program whose
    Python trace costs nothing, so an A/B over it measures only XLA
    backend time (which both arms pay); a real pipeline is N fused
    branch programs, each traced+lowered per padding bucket per
    replica clone — exactly the repeated host-side work the AOT
    artifact (one whole-graph program per bucket) removes."""
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.models.linear import LinearMapper
    from keystone_tpu.ops.stats import NormalizeRows

    feat, feat_dim = _fft_gather_feat(dim, branches, seed)
    rng = np.random.default_rng(seed)
    w = jnp.asarray(
        rng.normal(size=(feat_dim, classes)).astype(np.float32)
    )
    return feat | NormalizeRows() | LinearMapper(w)


def build_service(
    dim: int = 64,
    classes: int = 16,
    max_batch: int = 32,
    max_wait_ms: float = 2.0,
    queue_bound: int = 128,
    deadline_ms: float | None = 250.0,
    model: str | None = None,
    seed: int = 0,
    replicas: int = 1,
    recorder: bool = True,
    **serve_kw,
):
    """A primed service over the synthetic two-stage pipeline (or a
    saved fitted model); returns ``(service, item_shape)``.
    ``recorder=False`` runs the PR-5 untraced path — the on/off pair is
    how the bench pins the flight recorder's overhead budget.  Extra
    keywords (``hedge_ms``, ``supervise``, ``heartbeat_s``, ...) pass
    through to :func:`keystone_tpu.serve.serve` — the hedging A/B and
    the chaos soak ride this."""
    import numpy as np

    from keystone_tpu.serve import serve

    if model:
        from keystone_tpu.workflow import FittedPipeline

        pipe = FittedPipeline.load(model)
    else:
        pipe = build_pipeline(dim=dim, classes=classes, seed=seed)
    item_shape = (int(dim),)
    svc = serve(
        pipe,
        max_batch=max_batch,
        max_wait_ms=max_wait_ms,
        queue_bound=queue_bound,
        deadline_ms=deadline_ms,
        example=np.zeros(item_shape, np.float32),
        name="serve_bench",
        replicas=replicas,
        recorder=recorder,
        **serve_kw,
    )
    return svc, item_shape


def _hist_delta(before: dict, after: dict, name: str) -> tuple:
    b = before.get(name) or {"count": 0, "sum": 0.0}
    a = after.get(name) or {"count": 0, "sum": 0.0}
    return a["count"] - b["count"], a["sum"] - b["sum"]


def run_bench(
    svc,
    item_shape,
    qps: float,
    duration: float,
    burst: int = 1,
    deadline_ms: float | None = None,
    batch_delay_ms: float = 0.0,
    swap_pipeline=None,
    straggler_ms: float = 0.0,
    straggler_replica: int = 0,
) -> dict:
    """Offer ``qps`` requests/sec for ``duration`` seconds (groups of
    ``burst`` arrivals at the same mean rate), wait for the tail to
    drain, and report.  Each burst group is admitted with ONE
    ``submit_many`` call (client-side batched submit, atomic
    all-or-none; the report's ``submit_mode`` field records it) —
    the client stops paying a lock round-trip per datum and a
    rejected group is counted as the unit it arrived as.  ``batch_delay_ms`` > 0 stalls every flush via a
    ``serve.batch:delay=…`` fault plan (emulating a heavier model, so a
    laptop can exercise overload deterministically).  ``swap_pipeline``:
    blue/green hot-swap this fitted pipeline in at the midpoint of the
    offer window; the report gains the swap info (pause, prime time) so
    the round artifact records what a live rollout costs under load.
    ``straggler_ms`` > 0 makes ONE replica (``straggler_replica``) stall
    every flush apply via a context-matched ``serve.replica`` plan —
    the deterministic straggler the hedging A/B measures against."""
    import contextlib

    import numpy as np

    from keystone_tpu import faults
    from keystone_tpu.obs import metrics
    from keystone_tpu.serve import FleetUnavailable, Overloaded
    from keystone_tpu.utils import guard

    burst = max(1, int(burst))
    deadline_s = None if not deadline_ms else float(deadline_ms) / 1000.0
    snap0 = metrics.snapshot()
    c0 = dict(snap0.get("counters") or {})

    lock = threading.Lock()
    latencies: list = []
    outcomes = {"completed": 0, "shed": 0, "rejected": 0, "errors": 0}

    def record(fut, t_submit):
        t_done = time.monotonic()
        exc = fut.exception()
        with lock:
            if exc is None:
                outcomes["completed"] += 1
                latencies.append(t_done - t_submit)
            elif isinstance(exc, guard.DeadlineExceeded):
                outcomes["shed"] += 1
            else:
                outcomes["errors"] += 1

    rng = np.random.default_rng(1)
    payload = rng.normal(size=(burst,) + tuple(item_shape)).astype(np.float32)
    n_arrivals = max(1, int(round(qps * duration)))
    interval = burst / qps
    futs = []

    clauses = []
    if batch_delay_ms > 0:
        clauses.append(f"serve.batch:delay={batch_delay_ms / 1000.0}")
    if straggler_ms > 0:
        # serve.worker, not serve.replica: the stall lands in the worker
        # loop BEFORE the flush is claimed, so the batch stays
        # "still-unflushed" for the whole stall — the exact failure mode
        # hedged dispatch exists to rescue (a claimed flush mid-apply is
        # beyond any hedge that avoids duplicate device work)
        clauses.append(
            f"serve.worker:ctx.replica={int(straggler_replica)}"
            f":delay={straggler_ms / 1000.0}"
        )
    plan = (
        faults.inject(";".join(clauses)) if clauses else contextlib.nullcontext()
    )
    swap_info: dict = {}
    swap_thread = None
    if swap_pipeline is not None:

        def _swap_midway():
            time.sleep(duration / 2.0)
            try:
                swap_info.update(svc.swap(swap_pipeline, version="bench-swap"))
            except Exception as e:  # report it; don't kill the offer loop
                swap_info["error"] = f"{type(e).__name__}: {e}"

        swap_thread = threading.Thread(target=_swap_midway, daemon=True)
    t_start = time.monotonic()
    if swap_thread is not None:
        swap_thread.start()
    with plan:
        next_t = t_start
        sent = 0
        while sent < n_arrivals:
            now = time.monotonic()
            if now < next_t:
                time.sleep(min(next_t - now, 0.002))
                continue
            # client-side batched submit: the whole burst group rides
            # ONE admission call (submit_many — atomic all-or-none)
            # instead of a per-datum submit loop, so the bench client
            # stops paying lock/condition round-trips per datum and an
            # overloaded group is rejected as the unit it arrived as
            group = payload[: min(burst, n_arrivals - sent)]
            t_submit = time.monotonic()
            try:
                batch_futs = svc.submit_many(group, deadline=deadline_s)
            except (Overloaded, FleetUnavailable):
                # both are typed refusals at admission (a 503 on the
                # wire).  An open breaker is where this workload can land
                # — it sits on the overload cliff, and a run of shed
                # flushes opens it: on the v5e one run in two died here
                # with FleetUnavailable out of the offer loop (PR 21)
                with lock:
                    outcomes["rejected"] += len(group)
            else:
                for fut in batch_futs:
                    fut.add_done_callback(
                        lambda f, t0=t_submit: record(f, t0)
                    )
                futs.extend(batch_futs)
            sent += len(group)
            next_t += interval
        # throughput denominator = the OFFER window: including the
        # post-offer tail-drain below would bias achieved_qps low by
        # queue_bound × batch-time per run, making round-over-round
        # movement track drain length instead of serving capacity
        offer_elapsed = time.monotonic() - t_start
        # drain the tail: everything admitted resolves (completed or
        # shed) — the report must account for every offered request
        futures_wait(futs, timeout=duration + 30.0)
    wall_elapsed = time.monotonic() - t_start
    if swap_thread is not None:
        swap_thread.join(timeout=duration + 60.0)
    replica_stats = svc.replica_statuses()

    snap1 = metrics.snapshot()
    c1 = dict(snap1.get("counters") or {})
    rows_n, rows_sum = _hist_delta(
        snap0.get("histograms") or {}, snap1.get("histograms") or {}, "serve.batch_rows"
    )
    lat_ms = sorted(x * 1000.0 for x in latencies)

    def pct(p):
        if not lat_ms:
            return None
        return round(float(np.percentile(lat_ms, p)), 2)

    completed = outcomes["completed"]
    report = {
        "offered_qps": qps,
        "duration_s": duration,
        "burst": burst,
        "submit_mode": "batched",
        "deadline_ms": deadline_ms,
        "batch_delay_ms": batch_delay_ms,
        "straggler_ms": straggler_ms,
        "hedges": int(
            c1.get("serve.hedges", 0.0) - c0.get("serve.hedges", 0.0)
        ),
        "hedge_wins": int(
            c1.get("serve.hedge_wins", 0.0) - c0.get("serve.hedge_wins", 0.0)
        ),
        "n_requests": n_arrivals,
        "completed": completed,
        "shed": outcomes["shed"],
        "rejected": outcomes["rejected"],
        "errors": outcomes["errors"],
        "achieved_qps": (
            round(completed / offer_elapsed, 1) if offer_elapsed > 0 else None
        ),
        # BOTH denominators, every leg: offered-window QPS (capacity —
        # the A/B comparand) AND drain-inclusive wall QPS.  One number
        # alone biases A/Bs: offered-window flatters a run that banked a
        # deep queue during the window and drained it after; wall-clock
        # punishes a run for its own queue bound.  Reporting the pair
        # (plus drain_s) makes the bias visible instead of implicit.
        "achieved_qps_wall": (
            round(completed / wall_elapsed, 1) if wall_elapsed > 0 else None
        ),
        "drain_s": round(wall_elapsed - offer_elapsed, 3),
        "p50_ms": pct(50),
        "p95_ms": pct(95),
        "p99_ms": pct(99),
        "max_ms": round(lat_ms[-1], 2) if lat_ms else None,
        "batches": rows_n,
        "mean_batch_occupancy": round(rows_sum / rows_n, 2) if rows_n else None,
        "shed_rate": round(
            (outcomes["shed"] + outcomes["rejected"]) / n_arrivals, 4
        ),
        "deadline_miss": int(
            c1.get("serve.deadline_miss", 0.0) - c0.get("serve.deadline_miss", 0.0)
        ),
        "replicas": len(replica_stats),
        "recorder": svc.recorder is not None,
        # flush share per replica: a healthy least-outstanding router
        # keeps these near-uniform; a skew marks a slow/broken replica.
        # Counter deltas, not replica statuses — statuses reset at a
        # swap (a fresh generation), counters span the whole run
        "replica_occupancy": _occupancy(replica_stats, c0, c1),
    }
    if swap_pipeline is not None:
        report["swap"] = {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in swap_info.items()
        }
    return report


def _occupancy(replica_stats: list, c0: dict, c1: dict) -> list:
    def flushes(i: int) -> int:
        key = f"serve.replica_flushes{{replica={i}}}"
        return int(c1.get(key, 0.0) - c0.get(key, 0.0))

    counts = {r["replica"]: flushes(r["replica"]) for r in replica_stats}
    total = sum(counts.values()) or 1
    return [
        {
            "replica": r["replica"],
            "version": r["version"],
            "flushes": counts[r["replica"]],
            "share": round(counts[r["replica"]] / total, 4),
            "errors": r["errors"],
            "breaker": r["breaker"],
        }
        for r in replica_stats
    ]


def build_tenant_models(
    tenants: int = 3,
    dim: int = 64,
    classes: int = 16,
    branches: int = 6,
    seed: int = 0,
):
    """N tenant pipelines SHARING a featurization prefix: every tenant
    gathers the SAME RandomSignNode → PaddedFFT → LinearRectifier
    branches (identical seeds/constants, so the prefix signatures are
    equal and the cross-pipeline planner shares them) feeding a
    per-tenant linear head (distinct weights — never shared, and with
    ``params() = None`` never collision-prone either)."""
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.models.linear import LinearMapper
    from keystone_tpu.ops.stats import NormalizeRows

    models = {}
    for t in range(int(tenants)):
        # the SAME seed for every tenant's featurizer: equal prefix
        # signatures are what the cross-pipeline planner shares
        feat, feat_dim = _fft_gather_feat(dim, branches, seed)
        rng = np.random.default_rng(seed + 100 + t)
        w = jnp.asarray(
            rng.normal(size=(feat_dim, classes)).astype(np.float32)
        )
        models[f"t{t}"] = feat | NormalizeRows() | LinearMapper(w)
    return models


def build_tenant_service(
    tenants: int = 3,
    share: bool = True,
    dim: int = 64,
    classes: int = 16,
    branches: int = 6,
    max_batch: int = 32,
    max_wait_ms: float = 2.0,
    queue_bound: int = 256,
    deadline_ms: float | None = 1000.0,
    seed: int = 0,
    **serve_kw,
):
    """A primed multi-tenant service over :func:`build_tenant_models`;
    returns ``(service, item_shape, tenant_names)``.  ``share=False``
    is the A/B control arm: identical DRR batching and combined
    flushes, shared stage pool OFF — every tenant's walk recomputes the
    prefix."""
    import numpy as np

    from keystone_tpu.serve import serve_multi

    models = build_tenant_models(
        tenants=tenants, dim=dim, classes=classes, branches=branches, seed=seed
    )
    item_shape = (int(dim),)
    svc = serve_multi(
        models,
        share=share,
        max_batch=max_batch,
        max_wait_ms=max_wait_ms,
        queue_bound=queue_bound,
        deadline_ms=deadline_ms,
        example=np.zeros(item_shape, np.float32),
        name="serve_bench_mt",
        **serve_kw,
    )
    return svc, item_shape, list(models)


def run_tenants_bench(
    svc,
    item_shape,
    names,
    qps: float,
    duration: float,
    deadline_ms: float | None = None,
    burst: int = 8,
) -> dict:
    """Open-loop offered load split EQUALLY across tenants: each tick
    submits one ``burst``-sized ``submit_many`` group for one tenant,
    rotating the tenant list, at the aggregate mean rate (bursting
    keeps the GENERATOR's per-request Python off the measurement — a
    per-datum submit loop caps out near 3k QPS on a small host and
    would measure itself, not the service).  Waits for the tail and
    reports aggregate + per-tenant achieved QPS / p50 / p99 / outcome
    counts, plus the fairness ratio (max per-tenant p99 over min —
    1.0 = perfectly even service under equal offered load)."""
    import numpy as np

    from keystone_tpu.serve import Overloaded

    deadline_s = None if not deadline_ms else float(deadline_ms) / 1000.0
    burst = max(1, int(burst))
    lock = threading.Lock()
    lat: dict = {t: [] for t in names}
    outcomes: dict = {
        t: {"completed": 0, "shed": 0, "rejected": 0, "errors": 0}
        for t in names
    }

    def record(fut, t_submit, tenant):
        from keystone_tpu.utils import guard

        t_done = time.monotonic()
        exc = fut.exception()
        with lock:
            o = outcomes[tenant]
            if exc is None:
                o["completed"] += 1
                lat[tenant].append(t_done - t_submit)
            elif isinstance(exc, guard.DeadlineExceeded):
                o["shed"] += 1
            else:
                o["errors"] += 1

    rng = np.random.default_rng(1)
    payload = rng.normal(size=(burst,) + tuple(item_shape)).astype(np.float32)
    n_arrivals = max(len(names) * burst, int(round(qps * duration)))
    interval = burst / qps
    futs = []
    t_start = time.monotonic()
    next_t = t_start
    sent = 0
    tick = 0
    while sent < n_arrivals:
        now = time.monotonic()
        if now < next_t:
            time.sleep(min(next_t - now, 0.002))
            continue
        tenant = names[tick % len(names)]
        tick += 1
        k = min(burst, n_arrivals - sent)
        t_submit = time.monotonic()
        try:
            group = svc.submit_many(
                payload[:k], deadline=deadline_s, tenant=tenant
            )
        except Overloaded:
            with lock:
                outcomes[tenant]["rejected"] += k
        else:
            for fut in group:
                fut.add_done_callback(
                    lambda f, t0=t_submit, tn=tenant: record(f, t0, tn)
                )
            futs.extend(group)
        sent += k
        next_t += interval
    offer_elapsed = time.monotonic() - t_start
    futures_wait(futs, timeout=duration + 30.0)
    wall_elapsed = time.monotonic() - t_start

    def pct(vals, p):
        if not vals:
            return None
        return round(float(np.percentile([v * 1000.0 for v in vals], p)), 2)

    per_tenant = {}
    for t in names:
        per_tenant[t] = {
            **outcomes[t],
            "achieved_qps": (
                round(outcomes[t]["completed"] / offer_elapsed, 1)
                if offer_elapsed > 0
                else None
            ),
            "p50_ms": pct(lat[t], 50),
            "p99_ms": pct(lat[t], 99),
        }
    completed = sum(o["completed"] for o in outcomes.values())
    p99s = [v["p99_ms"] for v in per_tenant.values() if v["p99_ms"]]
    pool = (
        svc.status().get("stage_pool", {}) if hasattr(svc, "status") else {}
    )
    return {
        "offered_qps": qps,
        "duration_s": duration,
        "tenants": len(names),
        "n_requests": n_arrivals,
        "aggregate_completed": completed,
        "aggregate_qps": (
            round(completed / offer_elapsed, 1) if offer_elapsed > 0 else None
        ),
        # the same offered-window vs drain-inclusive pair run_bench
        # reports — every leg carries both denominators
        "aggregate_qps_wall": (
            round(completed / wall_elapsed, 1) if wall_elapsed > 0 else None
        ),
        "drain_s": round(wall_elapsed - offer_elapsed, 3),
        # per-tenant p99 spread under EQUAL offered load: the fairness
        # claim is max/min ≤ 1.25 (acceptance criterion)
        "fairness_p99_ratio": (
            round(max(p99s) / min(p99s), 3) if p99s and min(p99s) > 0 else None
        ),
        "per_tenant": per_tenant,
        "pool": {
            k: pool.get(k)
            for k in (
                "hits",
                "misses",
                "evictions",
                "shared_stages",
                "collision_refusals",
                "sharing",
            )
        },
    }


def run_tenants_ab(
    qps: float = 12000.0,
    duration: float = 2.0,
    rounds: int = 3,
    tenants: int = 3,
    branches: int = 12,
    max_batch: int = 64,
    deadline_ms: float = 8000.0,
    dim: int = 512,
) -> dict:
    """The multi-tenant sharing A/B: the IDENTICAL workload against a
    shared-pool service and a sharing-disabled twin in one process,
    order-alternating rounds with a discarded warmup (the
    run_overhead_pair discipline).  Also pins bit-identity: one probe
    batch per tenant must predict EXACTLY the same bytes shared vs
    unshared — sharing is an execution strategy, never a numerics
    change.

    Defaults sit the workload where the claim lives: offered load well
    past capacity (achieved QPS then measures capacity), a wide/deep
    featurization prefix (the shared compute), and the flight recorder
    OFF in both arms — per-request tracing Python is identical in both
    and at thousands of QPS on a small host it floors the measurable
    ratio toward 1 (the recorder's own budget is pinned by its own
    leg)."""
    import statistics

    import numpy as np

    services = {}
    for mode, share in (("shared", True), ("unshared", False)):
        svc, item_shape, names = build_tenant_service(
            tenants=tenants,
            share=share,
            dim=dim,
            branches=branches,
            max_batch=max_batch,
            queue_bound=max(256, max_batch * 8),
            deadline_ms=deadline_ms,
            recorder=False,
        )
        services[mode] = (svc, item_shape, names)

    # bit-identity probe BEFORE the load rounds (quiet services)
    rng = np.random.default_rng(7)
    probe = rng.normal(size=(dim,)).astype(np.float32)
    identical = True
    for t in services["shared"][2]:
        a = services["shared"][0].submit(probe, tenant=t).result(30.0)
        b = services["unshared"][0].submit(probe, tenant=t).result(30.0)
        identical = identical and np.array_equal(a, b)

    samples: dict = {"shared": [], "unshared": []}
    try:
        for rnd in range(max(2, int(rounds)) + 1):
            order = (
                ("shared", "unshared")
                if rnd % 2 == 0
                else ("unshared", "shared")
            )
            for mode in order:
                svc, item_shape, names = services[mode]
                rep = run_tenants_bench(
                    svc,
                    item_shape,
                    names,
                    qps=qps,
                    duration=duration if rnd > 0 else 0.5,
                    deadline_ms=deadline_ms,
                )
                if rnd > 0:
                    samples[mode].append(rep)
    finally:
        for svc, _, _ in services.values():
            svc.close()

    def med(mode, key):
        vals = [r[key] for r in samples[mode] if r.get(key) is not None]
        return round(float(statistics.median(vals)), 3) if vals else None

    shared_qps = med("shared", "aggregate_qps")
    unshared_qps = med("unshared", "aggregate_qps")
    out = {
        "offered_qps": qps,
        "duration_s": duration,
        "rounds": len(samples["shared"]),
        "tenants": tenants,
        "aggregate_qps_shared": shared_qps,
        "aggregate_qps_unshared": unshared_qps,
        # the acceptance claim: shared sustains ≥ 1.5× unshared
        "speedup": (
            round(shared_qps / unshared_qps, 3)
            if shared_qps and unshared_qps
            else None
        ),
        "fairness_p99_ratio": med("shared", "fairness_p99_ratio"),
        "predictions_identical": bool(identical),
        "pool": samples["shared"][-1]["pool"] if samples["shared"] else {},
        "per_tenant_shared": (
            samples["shared"][-1]["per_tenant"] if samples["shared"] else {}
        ),
    }
    return out


def run_overhead_pair(
    qps: float = 300.0,
    duration: float = 2.0,
    rounds: int = 4,
    max_batch: int = 16,
    deadline_ms: float = 500.0,
    batch_delay_ms: float = 2.0,
    dim: int = 64,
) -> dict:
    """The flight-recorder overhead pin: the SAME workload against two
    services in ONE process — recorder on vs off — interleaved with
    alternating order across ``rounds`` and a discarded warmup round, so
    process cold-start, CPU-frequency, and scheduler noise cancel
    instead of masquerading as tracing overhead.  Runs at a steady
    operating point BELOW the collapse knee (offered < capacity):
    in overload, achieved QPS sits on the collapse cliff where tiny
    capacity shifts swing it wildly and no 5%-budget claim is
    measurable.  Reports per-mode medians and on/off ratios — the
    acceptance budget is ratios within 5% of 1.0."""
    import statistics

    services = {}
    for mode, rec in (("on", True), ("off", False)):
        svc, item_shape = build_service(
            dim=dim,
            max_batch=max_batch,
            queue_bound=128,
            deadline_ms=deadline_ms,
            recorder=rec,
        )
        services[mode] = (svc, item_shape)
    samples = {"on": [], "off": []}
    try:
        for rnd in range(max(2, int(rounds)) + 1):
            order = ("on", "off") if rnd % 2 == 0 else ("off", "on")
            for mode in order:
                svc, item_shape = services[mode]
                rep = run_bench(
                    svc,
                    item_shape,
                    qps=qps,
                    duration=duration if rnd > 0 else 0.5,
                    deadline_ms=deadline_ms,
                    batch_delay_ms=batch_delay_ms,
                )
                if rnd > 0:  # round 0 is the discarded warmup
                    samples[mode].append(rep)
    finally:
        for svc, _ in services.values():
            svc.close()

    def med(mode: str, key: str):
        vals = [r[key] for r in samples[mode] if r.get(key) is not None]
        return round(float(statistics.median(vals)), 2) if vals else None

    out = {
        "offered_qps": qps,
        "duration_s": duration,
        "rounds": len(samples["on"]),
        "batch_delay_ms": batch_delay_ms,
    }
    for mode in ("on", "off"):
        out[f"recorder_{mode}"] = {
            k: med(mode, k)
            for k in ("achieved_qps", "p50_ms", "p95_ms", "p99_ms")
        }
    ratios = {}
    for key, name in (
        ("achieved_qps", "achieved_qps_ratio"),
        ("p99_ms", "p99_ratio"),
    ):
        on, off = out["recorder_on"].get(key), out["recorder_off"].get(key)
        if on and off:
            ratios[name] = round(on / off, 3)
    out["overhead"] = ratios
    return out


def run_straggler_ab(
    qps: float = 300.0,
    duration: float = 2.0,
    rounds: int = 4,
    replicas: int = 2,
    max_batch: int = 16,
    deadline_ms: float = 2000.0,
    straggler_ms: float = 40.0,
    hedge_ms: float = 10.0,
    dim: int = 64,
) -> dict:
    """The hedging acceptance pin: the SAME workload with ONE injected
    straggler replica (every flush on replica 0 stalls ``straggler_ms``)
    against two fleets in one process — hedging ON vs OFF — order-
    alternated across ``rounds`` with a discarded warmup, exactly the
    ``run_overhead_pair`` discipline.  Hedging must cut p99 (queued
    flushes escape the straggler's queue onto a healthy replica) at
    ≤ 5% achieved-QPS cost — hedge losers are claim-skips, not
    duplicated device work.  Reports per-mode medians plus
    ``p99_ratio`` (hedged/unhedged, want < 1) and ``qps_cost``
    (1 − hedged/unhedged QPS, want ≤ 0.05)."""
    import statistics

    services = {}
    for mode, hedge in (("hedged", hedge_ms), ("unhedged", None)):
        svc, item_shape = build_service(
            dim=dim,
            max_batch=max_batch,
            queue_bound=256,
            deadline_ms=deadline_ms,
            replicas=replicas,
            hedge_ms=hedge,
            # the straggler is an INJECTED stall, not a wedge: keep the
            # supervisor from "healing" the leg out from under the A/B
            supervise=False,
        )
        services[mode] = (svc, item_shape)
    samples = {"hedged": [], "unhedged": []}
    try:
        for rnd in range(max(2, int(rounds)) + 1):
            order = (
                ("hedged", "unhedged") if rnd % 2 == 0 else ("unhedged", "hedged")
            )
            for mode in order:
                svc, item_shape = services[mode]
                rep = run_bench(
                    svc,
                    item_shape,
                    qps=qps,
                    duration=duration if rnd > 0 else 0.5,
                    deadline_ms=deadline_ms,
                    straggler_ms=straggler_ms,
                )
                if rnd > 0:  # round 0 is the discarded warmup
                    samples[mode].append(rep)
    finally:
        for svc, _ in services.values():
            svc.close()

    def med(mode: str, key: str):
        vals = [r[key] for r in samples[mode] if r.get(key) is not None]
        return round(float(statistics.median(vals)), 2) if vals else None

    out = {
        "offered_qps": qps,
        "duration_s": duration,
        "rounds": len(samples["hedged"]),
        "replicas": replicas,
        "straggler_ms": straggler_ms,
        "hedge_ms": hedge_ms,
    }
    for mode in ("hedged", "unhedged"):
        out[mode] = {
            k: med(mode, k)
            for k in ("achieved_qps", "p50_ms", "p95_ms", "p99_ms", "max_ms")
        }
    out["hedged"]["hedges"] = sum(r["hedges"] for r in samples["hedged"])
    out["hedged"]["hedge_wins"] = sum(
        r["hedge_wins"] for r in samples["hedged"]
    )
    hedging = {}
    on_p99, off_p99 = out["hedged"].get("p99_ms"), out["unhedged"].get("p99_ms")
    if on_p99 and off_p99:
        hedging["p99_ratio"] = round(on_p99 / off_p99, 3)
    on_q, off_q = (
        out["hedged"].get("achieved_qps"),
        out["unhedged"].get("achieved_qps"),
    )
    if on_q and off_q:
        hedging["qps_cost"] = round(1.0 - on_q / off_q, 4)
    out["hedging"] = hedging
    return out


# ----------------------------------------------------- AOT artifact A/Bs
def build_gil_pipeline(
    dim: int = 64,
    classes: int = 16,
    burn_rounds: int = 300,
    seed: int = 0,
):
    """The COMPUTE-BOUND (not stall-emulated) workload for the
    thread-vs-process A/B: a deterministic pure-Python featurizer
    (iterated CRC mixing per row — interpreter-loop work that HOLDS the
    GIL, like real tokenize/ngram featurization stages) feeding the
    normalize→linear head.  On a multi-core host, N worker THREADS
    serialize on the GIL through this stage while N worker PROCESSES
    compute in parallel — which is exactly the claim
    ``bench.py --leg-serve-procs`` measures.  Bit-deterministic: the
    burn factor is integer CRC math on the row's exact bytes, so
    thread and process fleets must produce identical output bytes."""
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.models.linear import LinearMapper
    from keystone_tpu.ops.stats import NormalizeRows
    from keystone_tpu.workflow import Pipeline

    from tools.gilburn import GilBurnFeature

    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(dim, classes)).astype(np.float32))
    return (
        Pipeline.of(GilBurnFeature(rounds=burn_rounds))
        | NormalizeRows()
        | LinearMapper(w)
    )


def build_gil_service(
    mode: str,
    workers: int = 2,
    dim: int = 64,
    burn_rounds: int = 300,
    max_batch: int = 16,
    queue_bound: int = 512,
    max_wait_ms: float = 2.0,
    seed: int = 0,
    **serve_kw,
):
    """A primed service over the GIL-bound pipeline: ``mode="thread"``
    → ``replicas=workers`` worker threads (the PR-8 fleet),
    ``mode="process"`` → ``workers=workers`` worker processes (PR-15).
    Recorder off in both arms (identical per-request Python, pinned by
    its own leg)."""
    import numpy as np

    from keystone_tpu.serve import serve

    pipe = build_gil_pipeline(dim=dim, burn_rounds=burn_rounds, seed=seed)
    fleet_kw = (
        dict(workers=int(workers))
        if mode == "process"
        else dict(replicas=int(workers))
    )
    svc = serve(
        pipe,
        max_batch=max_batch,
        max_wait_ms=max_wait_ms,
        queue_bound=queue_bound,
        deadline_ms=None,
        example=np.zeros((dim,), np.float32),
        name=f"procs_{mode}",
        recorder=False,
        **fleet_kw,
        **serve_kw,
    )
    return svc, (int(dim),)


def run_procs_ab(
    qps: float = 2500.0,
    duration: float = 2.5,
    rounds: int = 3,
    workers: int = 2,
    dim: int = 64,
    burn_rounds: int = 2000,
    max_batch: int = 16,
) -> dict:
    """Thread-vs-process fleet A/B on the compute-bound workload:
    IDENTICAL open-loop load against ``replicas=workers`` threads and
    ``workers=workers`` processes, order-alternating rounds with a
    discarded warmup (the run_overhead_pair discipline), plus a
    bit-identity probe (one fixed batch serially through both fleets
    must produce byte-identical predictions).

    HONEST SCALING BOUND: processes can beat threads only where cores
    exist — the report carries ``cores`` (the scheduler affinity mask)
    and ``achievable_speedup = min(workers, cores)``.  On a >= 2-core
    host the acceptance claim is speedup >= 1.8×; a 1-core host cannot
    express the claim (both arms share one core) and the leg instead
    requires the process fleet to be within 30% of the threaded one
    (the wire protocol's overhead bound) while still pinning
    bit-identity.  The PR-8 fleet leg's speedup was STALL-dominated by
    construction (an injected 40 ms flush delay that releases the GIL)
    — it measured router concurrency, not multi-core compute; THIS leg
    is the compute-bound claim."""
    import os as _os
    import statistics

    import numpy as np

    cores = len(_os.sched_getaffinity(0))
    services = {}
    samples: dict = {"thread": [], "process": []}
    try:
        # build + probe INSIDE the try: a spawn failure or a hung probe
        # must still close (and reap the worker processes of) whatever
        # was already built
        for mode in ("thread", "process"):
            services[mode] = build_gil_service(
                mode,
                workers=workers,
                dim=dim,
                burn_rounds=burn_rounds,
                max_batch=max_batch,
                # offered load sits ABOVE capacity so achieved QPS
                # measures capacity; a modest bound keeps the
                # post-offer tail short
                queue_bound=512,
            )

        # bit-identity probe on quiet services (serial submits)
        rng = np.random.default_rng(11)
        probe = rng.normal(size=(24, dim)).astype(np.float32)
        digests = {}
        for mode, (svc, _shape) in services.items():
            outs = [
                np.asarray(svc.submit(probe[i]).result(timeout=60.0))
                for i in range(probe.shape[0])
            ]
            digests[mode] = _prediction_sha(np.stack(outs))
        identical = digests["thread"] == digests["process"]

        for rnd in range(max(2, int(rounds)) + 1):
            order = (
                ("thread", "process")
                if rnd % 2 == 0
                else ("process", "thread")
            )
            for mode in order:
                svc, item_shape = services[mode]
                rep = run_bench(
                    svc,
                    item_shape,
                    qps=qps,
                    duration=duration if rnd > 0 else 0.5,
                    deadline_ms=None,
                )
                if rnd > 0:
                    samples[mode].append(rep)
    finally:
        for svc, _ in services.values():
            svc.close()

    def med(mode: str, key: str):
        vals = [r[key] for r in samples[mode] if r.get(key) is not None]
        return round(float(statistics.median(vals)), 2) if vals else None

    t_qps, p_qps = med("thread", "achieved_qps"), med("process", "achieved_qps")
    speedup = round(p_qps / t_qps, 3) if t_qps and p_qps else None
    achievable = min(int(workers), cores)
    ok = bool(identical) and speedup is not None and (
        speedup >= 1.8 if cores >= 2 else speedup >= 0.7
    )
    return {
        "offered_qps": qps,
        "duration_s": duration,
        "rounds": len(samples["thread"]),
        "workers": workers,
        "cores": cores,
        "burn_rounds": burn_rounds,
        "thread_qps": t_qps,
        "process_qps": p_qps,
        "thread_p99_ms": med("thread", "p99_ms"),
        "process_p99_ms": med("process", "p99_ms"),
        "speedup": speedup,
        "achievable_speedup": achievable,
        "cores_limited": cores < int(workers),
        "predictions_identical": bool(identical),
        "prediction_sha": digests,
        "ok": ok,
        "note": (
            "compute-bound (GIL-held featurizer) A/B: threads measure "
            "the GIL, processes measure cores.  The PR-8 fleet leg's "
            "~2.6x was stall-dominated by construction (injected "
            "GIL-releasing flush delay) and was never a multi-core "
            "hardware claim."
        ),
    }


# --------------------------------------------------------- ingress A/B
def _http_datum_worker(host, port, rows, stop_evt, lock, lats, counts):
    """One persistent-connection HTTP/JSON client: per-datum POSTs on a
    keep-alive HTTP/1.1 connection (the pre-ingress submit shape, minus
    the per-request TCP handshake the keep-alive satellite removed —
    measuring WITH keep-alive is the conservative comparison)."""
    import http.client

    def connect():
        return http.client.HTTPConnection(host, port, timeout=30.0)

    conn = connect()
    i = 0
    try:
        while not stop_evt.is_set():
            body = json.dumps({"instance": rows[i % len(rows)]}).encode()
            i += 1
            t0 = time.monotonic()
            try:
                conn.request(
                    "POST",
                    "/predict",
                    body,
                    {"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                resp.read()
                ok = resp.status == 200
            except Exception:
                ok = False
                try:
                    conn.close()
                except Exception:
                    pass
                conn = connect()
            dt = time.monotonic() - t0
            with lock:
                if ok:
                    counts["completed"] += 1
                    lats.append(dt)
                else:
                    counts["errors"] += 1
    finally:
        try:
            conn.close()
        except Exception:
            pass


def _binary_batch_worker(host, port, batch, stop_evt, lock, lats, counts):
    """One binary batch-protocol client: whole ``(b, dim)`` batches per
    CRC-framed message on a persistent connection (the zero-copy path)."""
    from keystone_tpu.serve.ingress import BinaryClient

    b = int(batch.shape[0])
    try:
        with BinaryClient(host, port) as c:
            while not stop_evt.is_set():
                t0 = time.monotonic()
                try:
                    c.predict(batch)
                    ok = True
                except Exception:
                    ok = False
                dt = time.monotonic() - t0
                with lock:
                    if ok:
                        counts["completed"] += b
                        lats.append(dt)
                    else:
                        counts["errors"] += b
    except Exception:
        with lock:
            counts["errors"] += b


def _saturate(worker, n_clients, args_common, duration) -> dict:
    """Closed-loop saturation leg: ``n_clients`` persistent-connection
    client threads hammer the front end for ``duration`` seconds; the
    per-datum rate over the measurement window IS the ceiling (a closed
    loop self-throttles at capacity — exactly the number a ceiling
    claim wants, unlike an open loop which would measure queueing)."""
    import numpy as np

    lock = threading.Lock()
    lats: list = []
    counts = {"completed": 0, "errors": 0}
    stop_evt = threading.Event()
    threads = [
        threading.Thread(
            target=worker,
            args=args_common + (stop_evt, lock, lats, counts),
            daemon=True,
        )
        for _ in range(int(n_clients))
    ]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(max(0.2, float(duration)))
    stop_evt.set()
    for t in threads:
        t.join(30.0)
    elapsed = time.monotonic() - t0
    lat_ms = sorted(x * 1000.0 for x in lats)

    def pct(p):
        if not lat_ms:
            return None
        return round(float(np.percentile(lat_ms, p)), 2)

    return {
        "clients": int(n_clients),
        "completed": counts["completed"],
        "errors": counts["errors"],
        "per_datum_qps": (
            round(counts["completed"] / elapsed, 1) if elapsed > 0 else None
        ),
        # closed loop: the offer window IS the wall window (no tail to
        # drain past stop), so the two denominators coincide — reported
        # under both names so every leg carries the pair
        "per_datum_qps_wall": (
            round(counts["completed"] / elapsed, 1) if elapsed > 0 else None
        ),
        "p50_ms": pct(50),
        "p99_ms": pct(99),
    }


def run_ingress_ab(
    duration: float = 2.0,
    rounds: int = 2,
    dim: int = 64,
    max_batch: int = 64,
    shards: int = 2,
    http_clients: int = 8,
    bin_clients: int = 4,
    bin_batch: int | None = None,
) -> dict:
    """The zero-copy ingress acceptance A/B: ONE service + compute
    fleet behind ONE :class:`~keystone_tpu.serve.ingress.AsyncIngress`
    port, saturated twice — per-datum HTTP/JSON on keep-alive threaded
    connections (the sniffed slow path, i.e. the old front end's submit
    shape) vs whole-batch binary frames on the event loop.  Order-
    alternating rounds with a discarded warmup (the run_overhead_pair
    discipline); per-datum QPS and p99 for both; the acceptance claim
    is binary >= 3x HTTP per-datum QPS with bit-identical predictions.

    Also reports the zero-copy counters: ``serve.preformed_flushes``
    (binary batches that skipped stack+pad) and the per-arm
    ``ingress.bytes_copied`` delta — the JSON arm charges every parsed
    payload byte, the binary arm charges none."""
    import statistics

    import numpy as np

    from keystone_tpu.obs import metrics
    from keystone_tpu.serve.ingress import BinaryClient, serve_ingress

    bin_batch = int(bin_batch or max_batch)
    svc, item_shape = build_service(
        dim=dim,
        max_batch=max_batch,
        max_wait_ms=2.0,
        queue_bound=4096,
        deadline_ms=None,
        recorder=False,
    )
    front = serve_ingress(svc, port=0, shards=shards)
    samples: dict = {"http": [], "binary": []}
    rng = np.random.default_rng(3)
    probe = rng.normal(size=(bin_batch,) + tuple(item_shape)).astype(
        np.float32
    )
    try:
        # bit-identity pin on the quiet service: the SAME batch through
        # both submit paths must predict the same bytes.  (float32 JSON
        # round-trips exactly: every float32 is representable in the
        # JSON text and comes back bit-equal through float64.)
        with BinaryClient("127.0.0.1", front.port) as c:
            bin_out = c.predict(probe)
        import urllib.request

        req = urllib.request.Request(
            f"http://127.0.0.1:{front.port}/predict",
            data=json.dumps({"instances": probe.tolist()}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            http_out = np.asarray(
                json.loads(resp.read())["predictions"], dtype=np.float32
            )
        identical = bool(np.array_equal(bin_out, http_out))

        rows_json = [r.tolist() for r in probe]
        bytes_copied: dict = {}
        pre0 = metrics.REGISTRY.counter_value("serve.preformed_flushes")
        for rnd in range(max(1, int(rounds)) + 1):
            order = (
                ("http", "binary") if rnd % 2 == 0 else ("binary", "http")
            )
            for mode in order:
                b0 = metrics.REGISTRY.counter_value("ingress.bytes_copied")
                if mode == "http":
                    rep = _saturate(
                        _http_datum_worker,
                        http_clients,
                        ("127.0.0.1", front.port, rows_json),
                        duration if rnd > 0 else 0.5,
                    )
                else:
                    rep = _saturate(
                        _binary_batch_worker,
                        bin_clients,
                        ("127.0.0.1", front.port, probe),
                        duration if rnd > 0 else 0.5,
                    )
                rep["bytes_copied"] = int(
                    metrics.REGISTRY.counter_value("ingress.bytes_copied")
                    - b0
                )
                if rnd > 0:
                    samples[mode].append(rep)
                    bytes_copied[mode] = (
                        bytes_copied.get(mode, 0) + rep["bytes_copied"]
                    )
        preformed = int(
            metrics.REGISTRY.counter_value("serve.preformed_flushes") - pre0
        )
    finally:
        front.stop()
        svc.close()

    def med(mode: str, key: str):
        vals = [r[key] for r in samples[mode] if r.get(key) is not None]
        return round(float(statistics.median(vals)), 2) if vals else None

    http_qps = med("http", "per_datum_qps")
    bin_qps = med("binary", "per_datum_qps")
    speedup = (
        round(bin_qps / http_qps, 3) if http_qps and bin_qps else None
    )
    return {
        "mode": "closed-loop saturation",
        "duration_s": duration,
        "rounds": len(samples["http"]),
        "dim": dim,
        "max_batch": max_batch,
        "shards": shards,
        "bin_batch": bin_batch,
        "http": {
            "clients": http_clients,
            "per_datum_qps": http_qps,
            "per_datum_qps_wall": med("http", "per_datum_qps_wall"),
            "p50_ms": med("http", "p50_ms"),
            "p99_ms": med("http", "p99_ms"),
            "errors": sum(r["errors"] for r in samples["http"]),
        },
        "binary": {
            "clients": bin_clients,
            "per_datum_qps": bin_qps,
            "per_datum_qps_wall": med("binary", "per_datum_qps_wall"),
            "frame_p50_ms": med("binary", "p50_ms"),
            "frame_p99_ms": med("binary", "p99_ms"),
            "errors": sum(r["errors"] for r in samples["binary"]),
        },
        "speedup": speedup,
        "predictions_identical": identical,
        "preformed_flushes": preformed,
        "bytes_copied": bytes_copied,
        # the acceptance claim: binary batch path sustains >= 3x the
        # threaded HTTP/JSON per-datum ceiling, predictions bit-equal
        "ok": bool(identical) and speedup is not None and speedup >= 3.0,
    }


def run_autoscale_scenario(
    qps: float = 2000.0,
    duration: float = 4.0,
    idle_timeout: float = 60.0,
    max_workers: int = 3,
    dim: int = 64,
    burn_rounds: int = 2000,
) -> dict:
    """The autoscale acceptance scenario: a 1-worker process fleet
    under sustained open-loop load must scale up (1 → N as queue/
    occupancy pressure mounts), then — offered load gone — scale back
    down to the floor, with EVERY submitted request resolving
    successfully (zero dropped, zero hung: the queue bound is sized
    above the offered burst so nothing is sheddable)."""
    import time as _time

    import numpy as np

    from keystone_tpu.serve import serve

    pipe = build_gil_pipeline(dim=dim, burn_rounds=burn_rounds)
    svc = serve(
        pipe,
        max_batch=16,
        max_wait_ms=2.0,
        queue_bound=100_000,
        deadline_ms=None,
        example=np.zeros((dim,), np.float32),
        name="procs_autoscale",
        recorder=False,
        workers=1,
        autoscale=dict(
            min_workers=1,
            max_workers=int(max_workers),
            interval_s=0.4,
            up_queue_frac=0.002,  # queue_bound is huge; react to depth
            up_cooldown_s=1.0,
            down_ticks=4,
            down_cooldown_s=3.0,
            # scale-down keyed to an empty queue + calm burn: the 60 s
            # occupancy window decays too slowly for a seconds-scale
            # scenario to gate on it
            down_occupancy=0.95,
        ),
    )
    peak = 1
    workers_track = []
    futs = []
    rng = np.random.default_rng(5)
    payload = rng.normal(size=(64, dim)).astype(np.float32)
    t0 = _time.monotonic()
    try:
        interval = 1.0 / qps
        next_t = t0
        i = 0
        while _time.monotonic() - t0 < duration:
            now = _time.monotonic()
            if now < next_t:
                _time.sleep(min(next_t - now, 0.002))
                continue
            futs.append(svc.submit(payload[i % payload.shape[0]]))
            i += 1
            next_t += interval
            if i % 50 == 0:
                n = svc.replicas
                workers_track.append(n)
                peak = max(peak, n)
        # drain: every admitted request must complete
        from concurrent.futures import TimeoutError as _FTimeout

        completed = 0
        errors = 0
        hung = 0
        for f in futs:
            try:
                f.result(timeout=180.0)
                completed += 1
            except _FTimeout:
                hung += 1
            except Exception:
                errors += 1
        peak = max(peak, svc.replicas)
        # idle: the fleet must come back down to the floor
        deadline = _time.monotonic() + idle_timeout
        final = svc.replicas
        while final > 1 and _time.monotonic() < deadline:
            _time.sleep(0.5)
            final = svc.replicas
        scaler = svc.autoscaler.status() if svc.autoscaler else {}
    finally:
        svc.close()
    return {
        "offered_qps": qps,
        "duration_s": duration,
        "submitted": len(futs),
        "completed": completed,
        "errors": errors,
        "hung": hung,
        "peak_workers": peak,
        "final_workers": final,
        "workers_track": workers_track[-20:],
        "scaled_up": peak > 1,
        "scaled_down": final == 1,
        "autoscaler": scaler,
        "ok": (
            errors == 0 and hung == 0 and peak > 1 and final == 1
        ),
    }


def publish_bench_registry(
    root: str,
    dim: int = 64,
    classes: int = 16,
    max_batch: int = 32,
    seed: int = 0,
    builder=None,
) -> str:
    """Publish an A/B workload into a fresh registry at ``root`` WITH
    its AOT artifact bundle; returns the version id.  Both arms of
    every A/B deploy from this — identical model bytes, the only
    difference being whether the deploy loads the artifacts.
    ``builder``: the pipeline factory (default :func:`build_pipeline`;
    the restart A/B uses :func:`build_aot_pipeline`)."""
    import numpy as np

    from keystone_tpu.serve import ModelRegistry
    from keystone_tpu.serve.service import default_buckets

    pipe = (builder or build_pipeline)(dim=dim, classes=classes, seed=seed)
    bundle = pipe.freeze().export_artifacts(
        example=np.zeros((dim,), np.float32),
        buckets=default_buckets(max_batch),
    )
    return ModelRegistry(root).publish(pipe, artifacts=bundle)


def run_cold_start(
    arm: str,
    registry_root: str,
    dim: int = 64,
    max_batch: int = 32,
) -> dict:
    """ONE cold-start-to-first-prediction sample, in THIS process (the
    A/B driver runs each sample in a fresh subprocess — in-process the
    second arm would ride the first's shared jit caches and measure
    nothing).  ``arm``: ``artifact`` loads the registry's AOT bundle,
    ``compile`` ignores it (the pre-artifact deploy path).  Reports the
    registry-load → service-ready (primed) → first-prediction
    timeline."""
    import time

    import numpy as np

    from keystone_tpu.obs import metrics
    from keystone_tpu.serve import ModelRegistry, serve

    reg = ModelRegistry(registry_root)
    c0 = dict(metrics.snapshot().get("counters") or {})
    t0 = time.perf_counter()
    fitted, version = reg.load()
    t_load = time.perf_counter() - t0
    arts = reg.load_artifacts(version) if arm == "artifact" else None
    svc = serve(
        fitted,
        max_batch=max_batch,
        deadline_ms=None,
        example=np.zeros((dim,), np.float32),
        name="coldstart",
        supervise=False,
        artifacts=arts,
    )
    t_ready = time.perf_counter() - t0
    x = np.random.default_rng(7).normal(size=(dim,)).astype(np.float32)
    y = np.asarray(svc.submit(x).result())
    t_first = time.perf_counter() - t0
    snap = metrics.snapshot()
    c1 = dict(snap.get("counters") or {})
    hists = snap.get("histograms") or {}
    prime = {
        src: (hists.get(f"serve.prime_seconds{{source={src}}}") or {}).get(
            "count", 0
        )
        for src in ("artifact", "cache", "compile")
    }
    svc.close()
    return {
        "arm": arm,
        "model_load_s": round(t_load, 4),
        "ready_s": round(t_ready, 4),
        "first_prediction_s": round(t_first, 4),
        "prime_sources": prime,
        "artifact_hits": int(
            c1.get("serve.artifact_hits", 0) - c0.get("serve.artifact_hits", 0)
        ),
        "artifact_fallbacks": int(
            c1.get("serve.artifact_fallbacks", 0)
            - c0.get("serve.artifact_fallbacks", 0)
        ),
        # FULL-output digest: predictions_match is a bit-for-bit claim,
        # so it must cover every byte, not an eyeball head
        "prediction_sha": _prediction_sha(y),
        "prediction_head": [round(float(v), 6) for v in y.ravel()[:4]],
    }


def _prediction_sha(y) -> str:
    # the repo's one full-array digest (shape+dtype+bytes): the parity
    # claim must not grow a second hashing implementation to drift from
    from keystone_tpu.utils.hashing import array_fingerprint

    return array_fingerprint(y)


def run_restart(
    arm: str,
    registry_root: str,
    dim: int = 64,
    max_batch: int = 32,
    replicas: int = 2,
    timeout_s: float = 60.0,
) -> dict:
    """ONE supervisor restart-to-rejoin sample: serve the registry's
    model on a 2-replica fleet, crash replica 0's worker via an
    injected ``serve.worker`` fault under light load, and report how
    long the supervisor's heal (re-clone + re-prime + adopt) took —
    the window during which the fleet runs a replica short.  With
    ``arm="artifact"`` the replacement primes from installed AOT
    programs; ``compile`` re-traces every bucket."""
    import time

    import numpy as np

    from keystone_tpu import faults
    from keystone_tpu.serve import ModelRegistry, serve

    reg = ModelRegistry(registry_root)
    fitted, version = reg.load()
    arts = reg.load_artifacts(version) if arm == "artifact" else None
    svc = serve(
        fitted,
        max_batch=max_batch,
        deadline_ms=None,
        example=np.zeros((dim,), np.float32),
        name="restart_bench",
        replicas=replicas,
        supervise=True,
        supervise_interval_s=0.05,
        heartbeat_s=30.0,
        artifacts=arts,
    )
    rng = np.random.default_rng(11)
    payload = rng.normal(size=(dim,)).astype(np.float32)
    try:
        # warm both replicas with real traffic first
        for _ in range(4):
            svc.submit(payload).result()
        with faults.inject("serve.worker:ctx.replica=0:raise:times=1"):
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                try:
                    svc.submit(payload).result(timeout=10.0)
                except Exception:
                    pass  # the crashed flush's riders fail typed; fine
                if svc.supervisor.restarts_total >= 1:
                    break
                time.sleep(0.01)
        last = svc.supervisor.last_restart
        # the healed fleet must answer cleanly
        y = np.asarray(svc.submit(payload).result(timeout=30.0))
    finally:
        svc.close()
    if not last:
        raise RuntimeError("supervisor never restarted the crashed replica")
    return {
        "arm": arm,
        "restart_to_rejoin_s": last["seconds"],
        "reason": last["reason"],
        "restarts": svc.supervisor.restarts_total,
        "prediction_sha": _prediction_sha(y),
        "prediction_head": [round(float(v), 6) for v in y.ravel()[:4]],
    }


def _artifact_arm_subprocess(
    flag: str, arm: str, root: str, dim: int, max_batch: int
):
    """Run one A/B arm in a pinned-env subprocess: fresh process (cold
    jit caches, cold shared-apply cache) and a FRESH empty persistent
    compile cache per invocation — both arms start equally cold, so
    the delta is the artifact tier, not leftover warmth.  The workload
    geometry (dim/max_batch) is forwarded explicitly: the arm must
    serve exactly what the driver published."""
    import shutil
    import subprocess
    import tempfile

    from keystone_tpu.serve.procfleet import refuse_chip_children

    # this driver published the registry version, so it has touched JAX
    refuse_chip_children("the per-arm A/B subprocesses")
    env = dict(os.environ)
    cache = tempfile.mkdtemp(prefix="keystone-ab-xla-")
    env["JAX_COMPILATION_CACHE_DIR"] = cache
    try:
        proc = subprocess.run(
            [
                sys.executable,
                os.path.abspath(__file__),
                flag,
                arm,
                "--registry",
                root,
                "--dim",
                str(int(dim)),
                "--max-batch",
                str(int(max_batch)),
            ],
            capture_output=True,
            text=True,
            timeout=600,
            env=env,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"{flag} {arm} arm failed: {proc.stderr[-400:]}"
            )
        return json.loads(proc.stdout.strip().splitlines()[-1])
    finally:
        shutil.rmtree(cache, ignore_errors=True)


def _ab_summary(samples: dict, key: str) -> dict:
    import statistics

    out = {}
    for arm in ("artifact", "compile"):
        vals = [s[key] for s in samples[arm] if s.get(key) is not None]
        out[arm] = round(float(statistics.median(vals)), 4) if vals else None
    if out.get("artifact") and out.get("compile"):
        out["speedup"] = round(out["compile"] / out["artifact"], 3)
    return out


def _run_artifact_ab(
    flag: str,
    summary_keys,
    dim: int,
    max_batch: int,
    rounds: int,
    registry_root,
) -> dict:
    """The shared A/B harness: publish ONE registry version
    (+artifacts, the heterogeneous-branch ``build_aot_pipeline``
    workload), run each arm ``rounds`` times in order-alternated fresh
    subprocesses, report per-arm medians + speedups, and pin the parity
    claim — every sample's FULL prediction digest must agree across
    arms.  Cleans up the registry it created."""
    import shutil
    import tempfile

    created = registry_root is None
    root = registry_root or tempfile.mkdtemp(prefix="keystone-artifact-ab-")
    try:
        publish_bench_registry(
            root, dim=dim, max_batch=max_batch, builder=build_aot_pipeline
        )
        samples = {"artifact": [], "compile": []}
        for rnd in range(max(1, int(rounds))):
            order = (
                ("artifact", "compile")
                if rnd % 2 == 0
                else ("compile", "artifact")
            )
            for arm in order:
                samples[arm].append(
                    _artifact_arm_subprocess(flag, arm, root, dim, max_batch)
                )
        out = {"rounds": rounds, "dim": dim, "max_batch": max_batch}
        for key in summary_keys:
            out[key] = _ab_summary(samples, key)
        shas = {
            s.get("prediction_sha")
            for arm_samples in samples.values()
            for s in arm_samples
        }
        out["predictions_match"] = len(shas) == 1
        out["samples"] = samples
        return out
    finally:
        if created:
            shutil.rmtree(root, ignore_errors=True)


def run_cold_start_ab(
    dim: int = 64, max_batch: int = 32, rounds: int = 2, registry_root=None
) -> dict:
    """The cold-start A/B: median registry-load → service-ready →
    first-prediction timeline per arm, plus the artifact speedup and
    the full-digest parity pin."""
    out = _run_artifact_ab(
        "--cold-start-arm",
        ("first_prediction_s", "ready_s"),
        dim,
        max_batch,
        rounds,
        registry_root,
    )
    out["prime_sources"] = {
        arm: out["samples"][arm][0]["prime_sources"] for arm in out["samples"]
    }
    return out


def run_restart_ab(
    dim: int = 64, max_batch: int = 32, rounds: int = 2, registry_root=None
) -> dict:
    """The supervisor heal A/B: same registry, same injected worker
    crash, restart-to-rejoin latency with artifact-primed replacements
    vs recompiled ones.  (The multi-branch workload matters: a heal
    re-builds every per-instance branch program — exactly the trace
    work this A/B exposes; a fused two-stage chain re-traces nearly
    nothing.)"""
    return _run_artifact_ab(
        "--restart-arm",
        ("restart_to_rejoin_s",),
        dim,
        max_batch,
        rounds,
        registry_root,
    )


def run_plan_ab(
    dim: int = 64,
    classes: int = 16,
    max_batch: int = 32,
    qps: float = 300.0,
    duration: float = 3.0,
    deadline_ms: float = 500.0,
    queue_bound: int = 256,
    seed: int = 0,
    drift_duration: float = 3.0,
    drift_qps: float = 250.0,
) -> dict:
    """Planned-vs-static A/B (ISSUE 20): one fitted pipeline served
    with the cost-based :class:`~keystone_tpu.planner.PhysicalPlan`
    installed (sampled winners + derived serving knobs) against the
    static defaults — on the raw forward leg and the open-loop serve
    leg — plus a live :class:`~keystone_tpu.planner.PlanTuner` retune
    under the zoo's ``drift`` scenario.  The acceptance gates:
    ``speedup`` >= 1.0 (the plan matches or beats the defaults; off-TPU
    both arms run identical physics, so ~1.0 is the honest expectation)
    and the drift sub-check either improves windowed p99 or reverts via
    the bake guard with ``lost_futures == 0``."""
    import numpy as np

    from keystone_tpu import planner
    from keystone_tpu.serve import serve
    from keystone_tpu.workflow.dataset import Dataset

    fitted = build_pipeline(dim=dim, classes=classes, seed=seed).fit()
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(max(256, 4 * max_batch), dim)).astype(np.float32)
    item_shape = (int(dim),)

    # ---- forward A/B: static defaults vs the sampled plan.  The two
    # appliers are timed in INTERLEAVED rounds (ambient CPU-clock drift
    # would otherwise dominate a back-to-back pair of µs-scale arms)
    # and each arm keeps its best round.
    planner.clear_plan()
    frozen_static = fitted.freeze()
    plan = planner.build_plan(
        fitted, example=X[: 2 * max_batch], max_batch=max_batch, seed=seed
    )
    frozen_planned = fitted.freeze(plan=plan)  # installs the plan

    rows = min(X.shape[0], 4 * max_batch)
    ds = Dataset(X[:rows], shard=False)
    best = {"static": None, "planned": None}
    arms = (("static", frozen_static), ("planned", frozen_planned))

    def _enter_arm(name):
        # mode gates (matmul) resolve at APPLY time through the
        # registry, so the static arm must run with the plan cleared
        if name == "planned":
            planner.install_plan(plan, source="serve")
        else:
            planner.clear_plan()

    for name, frozen in arms:  # warmup pays trace/compile
        _enter_arm(name)
        frozen(ds)
    for _ in range(15):
        for name, frozen in arms:
            _enter_arm(name)
            t0 = time.perf_counter()
            frozen(ds)
            dt = time.perf_counter() - t0
            if best[name] is None or dt < best[name]:
                best[name] = dt
    planner.install_plan(plan, source="serve")
    static_ips = float(rows) / best["static"] if best["static"] else 0.0
    planned_ips = float(rows) / best["planned"] if best["planned"] else 0.0
    forward = {
        "static_ips": round(static_ips, 1),
        "planned_ips": round(planned_ips, 1),
        "speedup": (
            round(planned_ips / static_ips, 2) if static_ips else None
        ),
    }

    # ---- serve A/B: identical open-loop load; the planned arm leaves
    # every knob unset so the plan tier resolves them, the static arm
    # clears the plan so the static defaults resolve
    def serve_arm(planned: bool) -> dict:
        if planned:
            planner.install_plan(plan, source="serve")
        else:
            planner.clear_plan()
        svc = serve(
            fitted,
            max_batch=max_batch,
            queue_bound=queue_bound,
            deadline_ms=deadline_ms,
            example=np.zeros(item_shape, np.float32),
            name="plan_ab",
        )
        try:
            return run_bench(
                svc,
                item_shape,
                qps=qps,
                duration=duration,
                deadline_ms=deadline_ms,
            )
        finally:
            svc.close()

    static_serve = serve_arm(False)
    planned_serve = serve_arm(True)
    serve_ab = {
        "static": {
            k: static_serve.get(k)
            for k in ("achieved_qps", "p50_ms", "p99_ms", "completed")
        },
        "planned": {
            k: planned_serve.get(k)
            for k in ("achieved_qps", "p50_ms", "p99_ms", "completed")
        },
        "speedup": (
            round(
                float(planned_serve["achieved_qps"])
                / float(static_serve["achieved_qps"]),
                2,
            )
            if static_serve.get("achieved_qps")
            and planned_serve.get("achieved_qps")
            else None
        ),
    }

    # ---- drift retune: a live PlanTuner against the zoo's drift
    # scenario — every retune is bake-guarded, so the sub-check is
    # "p99 improved OR the retune reverted", with zero lost futures
    from keystone_tpu.planner import PlanTuner
    from keystone_tpu.utils import guard
    from tools import workloads as zoo

    planner.install_plan(plan, source="serve")
    svc = serve(
        fitted,
        max_batch=max_batch,
        queue_bound=queue_bound,
        deadline_ms=deadline_ms,
        example=np.zeros(item_shape, np.float32),
        name="plan_drift",
    )
    tuner = PlanTuner(
        svc, plan=plan, interval_s=0.2, bake_s=0.6, cooldown_s=0.5
    )
    scenario = zoo.make_scenario(
        "drift", seed=seed, duration_s=drift_duration, qps=drift_qps,
        dim=dim,
    )
    lock = threading.Lock()
    lat: list = []
    counts = {"completed": 0, "lost": 0, "shed": 0, "rejected": 0}
    deadline_s = float(deadline_ms) / 1000.0

    def record(fut, t0):
        t1 = time.monotonic()
        exc = fut.exception()
        with lock:
            if exc is None:
                counts["completed"] += 1
                lat.append((t0, t1 - t0))
            elif isinstance(exc, guard.DeadlineExceeded):
                counts["shed"] += 1
            else:
                counts["lost"] += 1

    def _submit(event, rows):
        t0 = time.monotonic()
        try:
            fs = svc.submit_many(rows, deadline=deadline_s)
        except Exception:
            with lock:
                counts["rejected"] += int(rows.shape[0])
            return 0
        for f in fs:
            f.add_done_callback(lambda fut, t0=t0: record(fut, t0))
        return len(fs)

    tuner.start()
    t_start = time.monotonic()
    try:
        zoo.play(scenario, _submit, time_scale=1.0)
        time.sleep(max(0.5, 2 * tuner.bake_s))  # let a pending bake land
    finally:
        tuner.stop()
        svc.close()

    def _p99(samples):
        if not samples:
            return None
        vals = sorted(s for _, s in samples)
        return round(
            vals[min(len(vals) - 1, int(0.99 * len(vals)))] * 1000.0, 3
        )

    mid = t_start + (time.monotonic() - t_start) / 2.0
    first = [s for s in lat if s[0] < mid]
    second = [s for s in lat if s[0] >= mid]
    tstat = tuner.status()
    drift = {
        "outcomes": counts,
        "lost_futures": counts["lost"],
        "p99_ms_first_half": _p99(first),
        "p99_ms_second_half": _p99(second),
        "retunes": tstat.get("retunes"),
        "last_action": tstat.get("last_action"),
    }

    planner.clear_plan()
    return {
        "plan": {
            "fingerprint": plan.fingerprint(),
            "backend": plan.backend,
            "stages": {s.gate: s.winner for s in plan.stages},
            "knobs": plan.knobs,
        },
        "forward": forward,
        "serve": serve_ab,
        "drift_retune": drift,
        # the headline acceptance number: the planned configuration
        # matches or beats static on both legs (forward is the
        # low-noise leg; serve rides open-loop achieved QPS)
        "speedup": forward["speedup"],
        "serve_speedup": serve_ab["speedup"],
    }


def run_scenario(
    name: str,
    seed: int = 0,
    duration: float = 3.0,
    qps: float = 200.0,
    dim: int = 64,
    max_batch: int = 32,
    max_wait_ms: float = 2.0,
    queue_bound: int = 128,
    deadline_ms: float | None = 250.0,
    replicas: int = 1,
    time_scale: float = 1.0,
) -> dict:
    """Replay one seeded zoo scenario (``tools/workloads.py``) against
    a live service and report outcomes + latency percentiles.  The
    report carries the scenario's ``trace_digest`` so a regression
    found here replays bit-exactly (same name + seed = same traffic)."""
    import numpy as np

    from keystone_tpu.serve import Overloaded
    from keystone_tpu.utils import guard
    from tools import workloads as zoo

    scenario = zoo.make_scenario(
        name, seed=seed, duration_s=duration, qps=qps, dim=dim
    )
    svc, _item_shape = build_service(
        dim=dim,
        max_batch=max_batch,
        max_wait_ms=max_wait_ms,
        queue_bound=queue_bound,
        deadline_ms=deadline_ms,
        replicas=replicas,
    )
    deadline_s = None if not deadline_ms else float(deadline_ms) / 1000.0
    lock = threading.Lock()
    latencies: list = []
    outcomes = {"completed": 0, "shed": 0, "rejected": 0, "errors": 0}
    futs: list = []

    def record(fut, t_submit):
        t_done = time.monotonic()
        exc = fut.exception()
        with lock:
            if exc is None:
                outcomes["completed"] += 1
                latencies.append(t_done - t_submit)
            elif isinstance(exc, guard.DeadlineExceeded):
                outcomes["shed"] += 1
            else:
                outcomes["errors"] += 1

    def _submit(event, rows):
        t_submit = time.monotonic()
        try:
            fs = svc.submit_many(rows, deadline=deadline_s)
        except Overloaded:
            with lock:
                outcomes["rejected"] += rows.shape[0]
            return 0
        for f in fs:
            f.add_done_callback(lambda fut, t0=t_submit: record(fut, t0))
        with lock:
            futs.extend(fs)
        return len(fs)

    t0 = time.monotonic()
    try:
        zoo.play(scenario, _submit, time_scale=time_scale)
        for f in list(futs):
            try:
                f.result(timeout=30.0)
            except Exception:
                pass
    finally:
        svc.close()
    wall = time.monotonic() - t0
    lat = sorted(latencies)

    def _pct(p):
        if not lat:
            return None
        return round(lat[min(len(lat) - 1, int(p * len(lat)))] * 1000.0, 3)

    return {
        "scenario": scenario.summary(),
        "wall_seconds": round(wall, 3),
        "outcomes": outcomes,
        "submitted_rows": scenario.total_rows(),
        "qps_achieved": (
            round(outcomes["completed"] / wall, 1) if wall > 0 else None
        ),
        "p50_ms": _pct(0.50),
        "p99_ms": _pct(0.99),
    }


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # single-arm entries the A/B driver spawns (fresh process per
    # sample); also usable by hand for debugging one arm
    if argv and argv[0] in ("--cold-start-arm", "--restart-arm"):
        sub = argparse.ArgumentParser(prog=f"serve_bench {argv[0]}")
        sub.add_argument("arm", choices=("artifact", "compile"))
        sub.add_argument("--registry", required=True)
        sub.add_argument("--dim", type=int, default=64)
        sub.add_argument("--max-batch", type=int, default=32)
        a = sub.parse_args(argv[1:])
        fn = run_cold_start if argv[0] == "--cold-start-arm" else run_restart
        print(
            json.dumps(
                fn(a.arm, a.registry, dim=a.dim, max_batch=a.max_batch)
            )
        )
        return 0
    ap = argparse.ArgumentParser(
        description="open-loop load generator for keystone_tpu.serve"
    )
    ap.add_argument("--qps", type=float, default=500.0, help="offered load")
    ap.add_argument("--duration", type=float, default=3.0, help="seconds")
    ap.add_argument(
        "--burst", type=int, default=1, help="arrivals per group (same mean rate)"
    )
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--queue-bound", type=int, default=128)
    ap.add_argument("--deadline-ms", type=float, default=250.0)
    ap.add_argument(
        "--batch-delay-ms",
        type=float,
        default=0.0,
        help="stall every flush this long via the serve.batch fault site "
        "(emulates a heavier model; makes overload reproducible anywhere)",
    )
    ap.add_argument("--dim", type=int, default=64, help="request vector length")
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument(
        "--model", default=None, help="serve this saved FittedPipeline instead"
    )
    ap.add_argument(
        "--replicas",
        type=int,
        default=1,
        help="serving fleet size (one FrozenApplier clone per local "
        "device; pair with XLA_FLAGS=--xla_force_host_platform_device_count=N "
        "on CPU)",
    )
    ap.add_argument(
        "--swap-mid-run",
        action="store_true",
        help="blue/green hot-swap a freshly-built model in at the offer "
        "window's midpoint; the report gains the swap pause/prime times",
    )
    ap.add_argument(
        "--no-recorder",
        action="store_true",
        help="disable the flight recorder (request tracing); the "
        "on-vs-off pair pins the recorder overhead budget (p99/QPS "
        "within 5%%)",
    )
    ap.add_argument(
        "--straggler-ms",
        type=float,
        default=0.0,
        help="stall ONE replica's worker loop (--straggler-replica) "
        "this long per flush via a context-matched serve.worker plan "
        "(pre-claim, so the stalled batch stays hedgeable) — the "
        "deterministic straggler for hedging A/Bs",
    )
    ap.add_argument(
        "--straggler-replica",
        type=int,
        default=0,
        help="which replica index the straggler plan targets",
    )
    ap.add_argument(
        "--hedge-ms",
        type=float,
        default=None,
        help="enable hedged dispatch with this floor delay (needs "
        "--replicas >= 2); pair with --straggler-ms to see the p99 win",
    )
    ap.add_argument(
        "--cold-start",
        action="store_true",
        help="run the AOT-artifact A/Bs instead of the load generator: "
        "cold-start-to-first-prediction and supervisor "
        "restart-to-rejoin, each artifact-vs-compile in fresh "
        "subprocesses with fresh compile caches",
    )
    ap.add_argument(
        "--tenants",
        type=int,
        default=None,
        metavar="N",
        help="multi-tenant mode: co-serve N pipelines sharing a "
        "featurization prefix (serve/tenants.py) and run the "
        "shared-vs-unshared A/B — per-tenant QPS/p99, the fairness "
        "ratio, the pool hit/eviction counts, the aggregate-QPS "
        "speedup, and a bit-identity pin",
    )
    ap.add_argument(
        "--tenant-branches",
        type=int,
        default=6,
        help="gather width of the shared featurization prefix "
        "(heavier prefix = bigger sharing win)",
    )
    ap.add_argument(
        "--ab-rounds",
        type=int,
        default=2,
        help="samples per arm for --cold-start (order-alternated)",
    )
    ap.add_argument(
        "--workers",
        type=int,
        default=0,
        help="PROCESS fleet: serve with N worker processes instead of "
        "worker threads (0 = threaded).  With --procs-ab, the fleet "
        "size of BOTH arms of the thread-vs-process A/B",
    )
    ap.add_argument(
        "--procs-ab",
        action="store_true",
        help="run the thread-vs-process A/B on the compute-bound "
        "(GIL-held featurizer) workload instead of the load generator: "
        "achieved-QPS per arm, speedup vs the core-count-aware bound, "
        "and a bit-identity pin",
    )
    ap.add_argument(
        "--autoscale-scenario",
        action="store_true",
        help="run the autoscale acceptance scenario: a 1-worker "
        "process fleet scales 1->N under open-loop load and back down "
        "when idle, with zero dropped or hung requests",
    )
    ap.add_argument(
        "--burn-rounds",
        type=int,
        default=2000,
        help="CRC passes per row for the GIL-bound workload "
        "(--procs-ab / --autoscale-scenario)",
    )
    ap.add_argument(
        "--ingress-ab",
        action="store_true",
        help="run the zero-copy ingress A/B instead of the load "
        "generator: per-datum HTTP/JSON keep-alive clients vs binary "
        "batch frames against ONE AsyncIngress port (same service, "
        "same fleet) — per-datum QPS + p99 both arms, the >= 3x "
        "acceptance claim, and a bit-identity pin",
    )
    ap.add_argument(
        "--ingress-shards",
        type=int,
        default=2,
        help="AsyncIngress shard count for --ingress-ab (SO_REUSEPORT "
        "listener loops)",
    )
    ap.add_argument(
        "--http-clients",
        type=int,
        default=8,
        help="concurrent keep-alive HTTP clients in the --ingress-ab "
        "slow-path arm",
    )
    ap.add_argument(
        "--bin-clients",
        type=int,
        default=4,
        help="concurrent binary batch clients in the --ingress-ab "
        "fast-path arm",
    )
    ap.add_argument(
        "--scenario",
        default=None,
        metavar="NAME",
        help="replay a seeded adversarial zoo scenario "
        "(tools/workloads.py: bursty, diurnal, heavy_tailed, "
        "poison_flood, tenant_skewed, drift) instead of the open-loop "
        "generator; the report carries the replay digest",
    )
    ap.add_argument(
        "--scenario-seed",
        type=int,
        default=0,
        help="zoo scenario seed (same name + seed = same traffic)",
    )
    args = ap.parse_args(argv)

    if args.scenario:
        report = run_scenario(
            args.scenario,
            seed=args.scenario_seed,
            duration=args.duration,
            qps=args.qps,
            dim=args.dim,
            max_batch=args.max_batch,
            max_wait_ms=args.max_wait_ms,
            queue_bound=args.queue_bound,
            deadline_ms=args.deadline_ms,
            replicas=args.replicas,
        )
        print(json.dumps(report, indent=2))
        return 0

    if args.ingress_ab:
        report = run_ingress_ab(
            duration=args.duration,
            rounds=args.ab_rounds,
            dim=args.dim,
            max_batch=args.max_batch,
            shards=args.ingress_shards,
            http_clients=args.http_clients,
            bin_clients=args.bin_clients,
        )
        print(json.dumps(report, indent=2))
        return 0 if report.get("ok") else 1

    if args.procs_ab:
        report = run_procs_ab(
            qps=args.qps,
            duration=args.duration,
            rounds=args.ab_rounds,
            workers=args.workers or 2,
            dim=args.dim,
            burn_rounds=args.burn_rounds,
        )
        print(json.dumps(report, indent=2))
        return 0 if report.get("ok") else 1

    if args.autoscale_scenario:
        report = run_autoscale_scenario(
            qps=args.qps,
            duration=args.duration,
            burn_rounds=args.burn_rounds,
        )
        print(json.dumps(report, indent=2))
        return 0 if report.get("ok") else 1

    if args.cold_start:
        report = {
            "cold_start": run_cold_start_ab(
                dim=args.dim, max_batch=args.max_batch, rounds=args.ab_rounds
            ),
            "restart": run_restart_ab(
                dim=args.dim, max_batch=args.max_batch, rounds=args.ab_rounds
            ),
        }
        print(json.dumps(report, indent=2))
        return 0

    if args.tenants:
        report = run_tenants_ab(
            qps=args.qps,
            duration=args.duration,
            rounds=args.ab_rounds,
            tenants=args.tenants,
            branches=args.tenant_branches,
            max_batch=args.max_batch,
            deadline_ms=args.deadline_ms,
            dim=args.dim,
        )
        print(json.dumps(report, indent=2))
        return 0

    fleet_kw = (
        dict(workers=args.workers)
        if args.workers
        else dict(replicas=args.replicas)
    )
    svc, item_shape = build_service(
        dim=args.dim,
        classes=args.classes,
        max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms,
        queue_bound=args.queue_bound,
        deadline_ms=args.deadline_ms,
        model=args.model,
        recorder=not args.no_recorder,
        hedge_ms=args.hedge_ms,
        **fleet_kw,
    )
    swap_pipeline = None
    if args.swap_mid_run:
        if args.model:
            from keystone_tpu.workflow import FittedPipeline

            swap_pipeline = FittedPipeline.load(args.model)
        else:
            swap_pipeline = build_pipeline(
                dim=args.dim, classes=args.classes, seed=1
            )
    try:
        report = run_bench(
            svc,
            item_shape,
            qps=args.qps,
            duration=args.duration,
            burst=args.burst,
            deadline_ms=args.deadline_ms,
            batch_delay_ms=args.batch_delay_ms,
            swap_pipeline=swap_pipeline,
            straggler_ms=args.straggler_ms,
            straggler_replica=args.straggler_replica,
        )
    finally:
        svc.close()
    print(json.dumps(report, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
