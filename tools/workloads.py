"""Seeded adversarial workload zoo for the serving fleet.

Every scenario is a DETERMINISTIC function of ``(name, seed)``: the
arrival schedule, batch sizes, tenant choices, poison placement, and
payload row seeds all derive from one BLAKE2b-seeded PRNG, so a
workload that kills a canary (or slips past one) replays exactly —
``trace_digest()`` pins the whole schedule to a hash the tests assert
on.  The zoo doubles as the guarded-rollout drill corpus
(``tools/chaos.py --workload rollout``), and it is the tree's one load
generator: :func:`play` is the only arrival loop, :func:`offer` drives
it open-loop against a service and counts what came back, and the
``build_*`` functions make the small synthetic models and services the
failure drills (``tools/chaos.py``) and the service tests load.

Scenarios::

    constant      one single-row request every 1/qps seconds (the
                  open-loop offer :func:`offer` reports on)
    bursty        quiet baseline with seeded 10x arrival bursts
    diurnal       sinusoidal offered rate over the window
    heavy_tailed  Pareto-ish batch sizes: most tiny, a few huge
    poison_flood  clean warmup, then a window where a fraction of
                  rows carry the poison marker (``MARK`` in x[0])
    tenant_skewed zipf-ish tenant pick: one hot tenant dominates
    drift         payload distribution shifts steadily mid-window
                  (the slow-burn failure a post-commit bake catches)

Usage::

    JAX_PLATFORMS=cpu python tools/workloads.py --scenario poison_flood \
        --seed 7            # print the schedule summary + digest

``MarkerGate`` is the zoo's "bad model": a host stage that raises on
marker rows (the ``tests/test_selfheal.py`` PoisonGate idiom, importable
so registry-published pipelines unpickle).  A version carrying it fails
exactly the rows ``poison_flood`` floods — the canary-vs-guardrails
drill in ``tools/chaos.py`` publishes it as the staged version.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import random
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from keystone_tpu.workflow.transformer import Transformer  # noqa: E402

#: the poison marker (matches tests/test_selfheal.py): a row whose
#: first element is MARK trips MarkerGate
MARK = np.float32(123.0)

SCENARIOS = (
    "constant",
    "bursty",
    "diurnal",
    "heavy_tailed",
    "poison_flood",
    "tenant_skewed",
    "drift",
)


class MarkerGate(Transformer):
    """Host stage that raises when a row's first element is the poison
    marker — the zoo's deterministic bad model version.  Host-side
    (sequential) so the error raises cleanly on the flush thread,
    outside any XLA program; module-level so a registry-published
    pipeline carrying it unpickles by reference."""

    is_host = True
    parallel_host = False

    def params(self):
        return ()

    def apply_one(self, x):
        x = np.asarray(x)
        if x[0] == MARK:
            raise ValueError("poison marker row")
        return x


def build_zoo_pipeline(dim: int = 8, scale: float = 2.0, gate: bool = False):
    """The drill pipeline: NormalizeRows → LinearMapper(eye·scale), so
    a served row's output norm fingerprints WHICH version answered
    (norm == scale).  ``gate=True`` prepends :class:`MarkerGate` — the
    "bad" version that fails marker rows the good one passes."""
    import jax.numpy as jnp

    from keystone_tpu.models.linear import LinearMapper
    from keystone_tpu.ops.stats import NormalizeRows
    from keystone_tpu.workflow import Pipeline

    w = jnp.asarray(np.eye(dim, dtype=np.float32) * scale)
    if gate:
        return Pipeline.of(MarkerGate()) | NormalizeRows() | LinearMapper(w)
    return Pipeline.of(NormalizeRows()) | LinearMapper(w)


#: output width of the synthetic models' linear heads
_CLASSES = 16


def build_pipeline(dim: int = 64, seed: int = 0):
    """The synthetic two-stage workload (NormalizeRows → LinearMapper)."""
    import jax.numpy as jnp

    from keystone_tpu.models.linear import LinearMapper
    from keystone_tpu.ops.stats import NormalizeRows
    from keystone_tpu.workflow import Pipeline

    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(dim, _CLASSES)).astype(np.float32))
    return Pipeline.of(NormalizeRows()) | LinearMapper(w)


def _fft_gather_feat(dim: int, branches: int):
    """A ``branches``-way gather of RandomSignNode → PaddedFFT →
    LinearRectifier chains — the MnistRandomFFT shape; returns
    ``(featurizer pipeline, feature dim)``.  Each branch's rectifier
    carries a DISTINCT constant: identical-structure branches lower to
    identical HLO that the persistent compile cache dedupes across
    programs, which real heterogeneous pipelines don't enjoy."""
    from keystone_tpu.ops.stats import (
        LinearRectifier,
        PaddedFFT,
        RandomSignNode,
    )
    from keystone_tpu.workflow import Pipeline

    feat = Pipeline.gather(
        [
            RandomSignNode.init(dim, i)
            | PaddedFFT()
            | LinearRectifier(0.0, alpha=0.001 * (i + 1))
            for i in range(int(branches))
        ]
    )
    padded = 1 << (dim - 1).bit_length()
    return feat, branches * (padded // 2 + 1) * 2


def build_tenant_models(tenants: int = 3, dim: int = 64, branches: int = 6):
    """N tenant pipelines SHARING a featurization prefix: every tenant
    gathers the SAME RandomSignNode → PaddedFFT → LinearRectifier
    branches (identical seeds/constants, so the prefix signatures are
    equal and the cross-pipeline planner shares them) feeding a
    per-tenant linear head (distinct weights — never shared, and with
    ``params() = None`` never collision-prone either)."""
    import jax.numpy as jnp

    from keystone_tpu.models.linear import LinearMapper
    from keystone_tpu.ops.stats import NormalizeRows

    models = {}
    for t in range(int(tenants)):
        # the SAME seed for every tenant's featurizer: equal prefix
        # signatures are what the cross-pipeline planner shares
        feat, feat_dim = _fft_gather_feat(dim, branches)
        rng = np.random.default_rng(100 + t)
        w = jnp.asarray(
            rng.normal(size=(feat_dim, _CLASSES)).astype(np.float32)
        )
        models[f"t{t}"] = feat | NormalizeRows() | LinearMapper(w)
    return models


def build_service(
    dim: int = 64,
    max_batch: int = 32,
    max_wait_ms: float = 2.0,
    queue_bound: int = 128,
    deadline_ms: float | None = 250.0,
    **serve_kw,
):
    """A primed service over :func:`build_pipeline`; returns
    ``(service, item_shape)``.  Extra keywords (``replicas``,
    ``workers``, ``hosts``, ``hedge_ms``, ``heartbeat_s``, ...) pass
    through to :func:`keystone_tpu.serve.serve` — the chaos drills and
    the soak pick their fleet with them."""
    from keystone_tpu.serve import serve

    item_shape = (int(dim),)
    svc = serve(
        build_pipeline(dim=dim),
        max_batch=max_batch,
        max_wait_ms=max_wait_ms,
        queue_bound=queue_bound,
        deadline_ms=deadline_ms,
        example=np.zeros(item_shape, np.float32),
        name="workload",
        **serve_kw,
    )
    return svc, item_shape


class Scenario:
    """A fully materialized, replayable workload: an ordered list of
    arrival events, each ``{"t", "kind", "tenant", "rows", "row_seed",
    "shift"}`` — everything :func:`payload` needs to rebuild the exact
    bytes.  Construct via :func:`make_scenario`."""

    __slots__ = ("name", "seed", "duration_s", "dim", "tenants", "events")

    def __init__(self, name, seed, duration_s, dim, tenants, events):
        self.name = name
        self.seed = int(seed)
        self.duration_s = float(duration_s)
        self.dim = int(dim)
        self.tenants = int(tenants)
        self.events = events

    def trace(self) -> list:
        """The schedule as plain dicts (JSON-ready, digest input)."""
        return [dict(e) for e in self.events]

    def trace_digest(self) -> str:
        """BLAKE2b over the canonical-JSON schedule: two scenarios with
        the same digest submit byte-identical traffic."""
        blob = json.dumps(
            {
                "name": self.name,
                "seed": self.seed,
                "dim": self.dim,
                "events": self.trace(),
            },
            sort_keys=True,
            separators=(",", ":"),
        ).encode("utf-8")
        return hashlib.blake2b(blob, digest_size=16).hexdigest()

    def total_rows(self) -> int:
        return sum(e["rows"] for e in self.events)

    def poison_rows(self) -> int:
        return sum(e["rows"] for e in self.events if e["kind"] == "poison")

    def summary(self) -> dict:
        kinds: dict = {}
        for e in self.events:
            kinds[e["kind"]] = kinds.get(e["kind"], 0) + 1
        return {
            "name": self.name,
            "seed": self.seed,
            "duration_s": self.duration_s,
            "dim": self.dim,
            "events": len(self.events),
            "rows": self.total_rows(),
            "poison_rows": self.poison_rows(),
            "kinds": kinds,
            "digest": self.trace_digest(),
        }


def _zoo_rng(name: str, seed: int) -> random.Random:
    """One PRNG per (scenario, seed), derived through BLAKE2b so
    adjacent integer seeds don't produce correlated streams."""
    digest = hashlib.blake2b(
        f"{name}:{int(seed)}".encode("utf-8"), digest_size=8
    ).digest()
    return random.Random(int.from_bytes(digest, "big"))


def make_scenario(
    name: str,
    seed: int = 0,
    duration_s: float = 2.0,
    qps: float = 200.0,
    dim: int = 8,
    tenants: int = 4,
) -> Scenario:
    """Materialize one zoo scenario.  ``qps`` is the MEAN event rate;
    each scenario shapes arrivals/sizes/content its own way around it.
    Deterministic in ``(name, seed)`` for fixed knobs."""
    if name not in SCENARIOS:
        raise ValueError(f"unknown scenario {name!r}; one of {SCENARIOS}")
    rng = _zoo_rng(name, seed)
    duration_s = float(duration_s)
    n_events = max(1, int(round(qps * duration_s)))
    events = []

    def _event(t, kind="ok", tenant=0, rows=1, shift=0.0):
        events.append(
            {
                "t": round(float(t), 6),
                "kind": kind,
                "tenant": f"t{int(tenant)}",
                "rows": int(rows),
                "row_seed": rng.getrandbits(32),
                "shift": round(float(shift), 6),
            }
        )

    if name == "constant":
        # arrivals on a fixed grid whether or not earlier ones completed
        # (open loop: a closed-loop generator throttles itself and hides
        # queueing collapse)
        for i in range(n_events):
            _event(i / qps)
    elif name == "bursty":
        # quiet baseline + seeded bursts: ~1/8 of events arrive in
        # 10-event clumps at the same instant (queue-depth spikes the
        # admission/shedding layer must absorb)
        t = 0.0
        budget = n_events
        while budget > 0:
            t += rng.expovariate(qps)
            if t >= duration_s:
                t = duration_s * rng.random()
            if rng.random() < 0.125:
                clump = min(budget, 10)
                for _ in range(clump):
                    _event(t, rows=rng.choice((1, 1, 2)))
                budget -= clump
            else:
                _event(t, rows=rng.choice((1, 1, 2)))
                budget -= 1
        events.sort(key=lambda e: e["t"])
    elif name == "diurnal":
        # sinusoidal rate: thin-out by the instantaneous rate so the
        # peak-to-trough swing survives into the schedule
        for i in range(n_events * 2):
            t = duration_s * i / (n_events * 2)
            rate = 0.5 * (1.0 + math.sin(2.0 * math.pi * t / duration_s))
            if rng.random() < rate:
                _event(t, rows=1)
        if not events:
            _event(0.0, rows=1)
    elif name == "heavy_tailed":
        # Pareto-ish batch sizes: most events one row, the tail huge
        # (the oversized submit_many groups that stress max_batch
        # packing and padding buckets)
        t = 0.0
        for _ in range(n_events):
            t += rng.expovariate(qps)
            rows = min(64, max(1, int(rng.paretovariate(1.2))))
            _event(min(t, duration_s), rows=rows)
    elif name == "poison_flood":
        # clean warmup third, then a flood window where 40% of events
        # carry marker rows — against a gated version the canary
        # generation concentrates the failures
        t = 0.0
        for i in range(n_events):
            t += rng.expovariate(qps)
            t = min(t, duration_s)
            in_flood = i >= n_events // 3
            if in_flood and rng.random() < 0.4:
                _event(t, kind="poison", rows=rng.choice((1, 2)))
            else:
                _event(t, rows=rng.choice((1, 1, 2)))
    elif name == "tenant_skewed":
        # zipf-ish tenant pick: tenant 0 takes ~ half the traffic (the
        # fairness/starvation drill for the multi-tenant accountant)
        weights = [1.0 / (k + 1) for k in range(max(1, int(tenants)))]
        total = sum(weights)
        t = 0.0
        for _ in range(n_events):
            t += rng.expovariate(qps)
            r = rng.random() * total
            acc = 0.0
            pick = 0
            for k, w in enumerate(weights):
                acc += w
                if r <= acc:
                    pick = k
                    break
            _event(min(t, duration_s), tenant=pick, rows=1)
    elif name == "drift":
        # distribution drift: payload mean shifts linearly from 0 to 3
        # sigma across the window — the slow-burn regression a canary
        # window can miss and a post-commit bake must catch
        t = 0.0
        for _ in range(n_events):
            t += rng.expovariate(qps)
            t = min(t, duration_s)
            shift = 3.0 * (t / duration_s)
            _event(t, kind="drift" if shift > 0.5 else "ok", shift=shift)
    events.sort(key=lambda e: e["t"])
    return Scenario(name, seed, duration_s, dim, tenants, events)


def payload(event: dict, dim: int) -> np.ndarray:
    """Rebuild one event's exact rows from its recorded ``row_seed``:
    normal rows, plus the marker in x[0] for poison events and the
    recorded mean shift for drift events."""
    rows = int(event["rows"])
    x = (
        np.random.default_rng(int(event["row_seed"]))
        .normal(size=(rows, int(dim)))
        .astype(np.float32)
    )
    if event["kind"] == "poison":
        x[:, 0] = MARK
    shift = float(event.get("shift") or 0.0)
    if shift:
        x = (x + np.float32(shift)).astype(np.float32)
    return x


def play(scenario: Scenario, submit, time_scale: float = 1.0) -> list:
    """Drive ``submit(event, rows_array)`` along the scenario's
    schedule (``time_scale`` compresses it; 0 = as fast as possible)
    and return the per-event results.  ``submit`` exceptions are
    captured as results, not raised — an admission refusal is a
    scheduled outcome, not a replay failure."""
    out = []
    t0 = time.monotonic()
    for event in scenario.events:
        if time_scale > 0.0:
            due = t0 + event["t"] * time_scale
            now = time.monotonic()
            if due > now:
                time.sleep(due - now)
        try:
            out.append(submit(event, payload(event, scenario.dim)))
        except Exception as e:
            out.append(e)
    return out


def offer(
    svc,
    item_shape,
    qps: float,
    duration: float,
    deadline_ms: float | None = None,
    batch_delay_ms: float = 0.0,
) -> dict:
    """Offer ``qps`` single-row requests a second for ``duration``
    seconds (the ``constant`` scenario through :func:`play`), wait for
    the tail to drain, and report what became of every one: completed,
    shed at its deadline, rejected at admission, or failed; p50/p99 of
    the completed; mean batch occupancy.  ``batch_delay_ms`` > 0 stalls
    every flush via a ``serve.batch:delay=…`` fault plan (a heavier
    model, so a laptop can exercise overload deterministically)."""
    import contextlib
    import threading
    from concurrent.futures import wait as futures_wait

    from keystone_tpu import faults
    from keystone_tpu.obs import metrics
    from keystone_tpu.serve import FleetUnavailable, Overloaded
    from keystone_tpu.utils import guard

    deadline_s = None if not deadline_ms else float(deadline_ms) / 1000.0
    scenario = make_scenario(
        "constant", seed=1, duration_s=duration, qps=qps, dim=item_shape[0]
    )
    snap0 = metrics.snapshot()

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

    def submit(event, rows):
        t_submit = time.monotonic()
        futs = svc.submit_many(rows, deadline=deadline_s)
        for fut in futs:
            fut.add_done_callback(lambda f: record(f, t_submit))
        return futs

    plan = (
        faults.inject(f"serve.batch:delay={batch_delay_ms / 1000.0}")
        if batch_delay_ms > 0
        else contextlib.nullcontext()
    )
    with plan:
        t_start = time.monotonic()
        results = play(scenario, submit)
        # throughput denominator = the OFFER window: the tail drain
        # below would make achieved_qps track the queue's depth
        offer_elapsed = time.monotonic() - t_start
        futs = []
        for event, res in zip(scenario.events, results):
            if isinstance(res, (Overloaded, FleetUnavailable)):
                # both are typed refusals at admission (a 503 on the
                # wire).  An open breaker is where an overloaded service
                # can land: on the v5e one run in two ended here with
                # FleetUnavailable out of the offer loop (PR 21)
                outcomes["rejected"] += event["rows"]
            elif isinstance(res, Exception):
                outcomes["errors"] += event["rows"]
            else:
                futs.extend(res)
        # everything admitted resolves (completed or shed): the report
        # accounts for every offered request
        futures_wait(futs, timeout=duration + 30.0)

    snap1 = metrics.snapshot()

    def batch_rows(snap):
        hist = (snap.get("histograms") or {}).get("serve.batch_rows")
        return hist or {"count": 0, "sum": 0.0}

    def deadline_misses(snap):
        return (snap.get("counters") or {}).get("serve.deadline_miss", 0.0)

    batches = batch_rows(snap1)["count"] - batch_rows(snap0)["count"]
    rows_batched = batch_rows(snap1)["sum"] - batch_rows(snap0)["sum"]
    lat_ms = [x * 1000.0 for x in latencies]

    def pct(p):
        return round(float(np.percentile(lat_ms, p)), 2) if lat_ms else None

    n_requests = scenario.total_rows()
    return {
        "offered_qps": qps,
        "duration_s": duration,
        "deadline_ms": deadline_ms,
        "batch_delay_ms": batch_delay_ms,
        "n_requests": n_requests,
        **outcomes,
        "achieved_qps": round(outcomes["completed"] / offer_elapsed, 1),
        "p50_ms": pct(50),
        "p99_ms": pct(99),
        "batches": int(batches),
        "mean_batch_occupancy": (
            round(rows_batched / batches, 2) if batches else None
        ),
        "shed_rate": round(
            (outcomes["shed"] + outcomes["rejected"]) / n_requests, 4
        ),
        "deadline_miss": int(deadline_misses(snap1) - deadline_misses(snap0)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="materialize a seeded zoo scenario and print its "
        "schedule summary + replay digest"
    )
    ap.add_argument("--scenario", default=None, choices=SCENARIOS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--duration", type=float, default=2.0)
    ap.add_argument("--qps", type=float, default=200.0)
    ap.add_argument("--dim", type=int, default=8)
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument(
        "--trace",
        action="store_true",
        help="dump the full event schedule, not just the summary",
    )
    args = ap.parse_args(argv)
    names = [args.scenario] if args.scenario else list(SCENARIOS)
    for name in names:
        sc = make_scenario(
            name,
            seed=args.seed,
            duration_s=args.duration,
            qps=args.qps,
            dim=args.dim,
            tenants=args.tenants,
        )
        print(json.dumps(sc.trace() if args.trace else sc.summary(), indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
