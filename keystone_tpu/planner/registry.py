"""The physical-choice registry: every gate and knob, one precedence.

KeystoneML's optimizer (PAPER.md, ICDE 2017 §4) chooses *physical*
operator implementations per logical stage from sampled cost models.
Before this package, the TPU rebuild made those choices with scattered
environment gates — ``KEYSTONE_FUSED_FV``, ``KEYSTONE_GRAM_PALLAS``,
``KEYSTONE_MATMUL`` — each read at its own dispatch site with its own
default.  This module is the consolidation: one literal registry of
every gate (a named physical choice with enumerated candidates) and
every knob (a named numeric serving parameter with validated bounds),
plus the process-global installed :class:`~keystone_tpu.planner.plan.
PhysicalPlan` that dispatch sites consult.

Resolution precedence at EVERY dispatch site, documented once here:

    explicit argument  >  env override  >  installed plan  >  static default

Env vars are thereby demoted from the *mechanism* to a documented
*override*: with no plan installed and no env set, every site resolves
to its historical static default through the identical code path — the
no-plan behavior is byte-identical and pinned by regression tests.

``GATES``/``KNOBS``/``OPERATIONAL_ENV`` are **literal** dicts/sets so
``tools/lint.py``'s ``gate`` rule can parse them from the AST without
importing the package (the fault-site registry discipline): a new
``KEYSTONE_*`` env read controlling a physical choice must be
registered here or carry ``# lint: allow-gate``.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional

#: Physical-choice gates.  ``kind`` decides how the env override is
#: decoded: ``switch`` gates read "0" as the fallback candidate and any
#: other set value as the preferred candidate (the historical
#: ``KEYSTONE_X=0`` escape-hatch grammar); ``mode`` gates read the env
#: value as a candidate name directly.  The first candidate is the
#: static default (what the site did before the planner existed);
#: ``tpu_only`` lists candidates that only a Pallas-capable backend can
#: run (the cost model never samples them elsewhere, and the analysis
#: ``plan`` pass flags a shipped plan that picked one for this backend).
GATES = {
    "fused_fv": {
        "env": "KEYSTONE_FUSED_FV",
        "kind": "switch",
        "candidates": ("pallas", "xla"),
        "tpu_only": ("pallas",),
        "doc": "PCA->FisherVector forward: fused Pallas megakernel vs "
               "per-stage XLA chain (workflow/optimizer.PallasFvFusionRule)",
    },
    "gram_pallas": {
        "env": "KEYSTONE_GRAM_PALLAS",
        "kind": "switch",
        "candidates": ("pallas", "xla"),
        "tpu_only": ("pallas",),
        "doc": "kernel gram blocks: fused Pallas tile kernel vs the "
               "bit-identical XLA chain (ops/gram_pallas.gram_block)",
    },
    "matmul": {
        "env": "KEYSTONE_MATMUL",
        "kind": "mode",
        "candidates": ("auto", "bf16", "f32", "bf16_apply"),
        "tpu_only": ("bf16", "bf16_apply"),
        "doc": "featurize/apply matmul precision policy "
               "(utils/precision.matmul_mode); solver math (sdot) is "
               "correctness-critical and NEVER under the plan",
    },
}

#: Serving knobs a plan may carry, with the validated bounds the
#: analysis ``plan`` pass and the PlanTuner enforce.  ``env`` names the
#: historical override where one exists (still honored, above the plan).
KNOBS = {
    "buckets": {
        "env": None,
        "kind": "int_tuple",
        "min": 1,
        "max": 65536,
        "doc": "padding-bucket sizes (serve/service.default_buckets)",
    },
    "max_wait_ms": {
        "env": None,
        "kind": "float",
        "min": 0.0,
        "max": 1000.0,
        "doc": "micro-batch flush wait (PipelineService)",
    },
    "dispatch_window": {
        "env": None,
        "kind": "int",
        "min": 1,
        "max": 64,
        "doc": "per-replica outstanding-flush window (fleet.set_window)",
    },
    "hedge_ms": {
        "env": None,
        "kind": "float",
        "min": 0.0,
        "max": 60000.0,
        "doc": "straggler hedge delay (PipelineService hedge_ms)",
    },
    "pool_budget_bytes": {
        "env": "KEYSTONE_POOL_BUDGET_BYTES",
        "kind": "int",
        "min": 1 << 20,
        "max": 1 << 40,
        "doc": "shared stage pool HBM budget "
               "(workflow/profiling.pool_budget_bytes)",
    },
}

#: ``KEYSTONE_*`` env vars that do NOT select a physical implementation
#: or plan-managed knob — operational/debug/test configuration the
#: ``gate`` lint rule must not flag.  Registering a new operational env
#: here (or a new physical gate in GATES) is the rule's escape path;
#: a one-off read can carry ``# lint: allow-gate`` instead.
OPERATIONAL_ENV = {
    "KEYSTONE_APPLY_CHUNK",
    "KEYSTONE_AUTO_SPILL",
    "KEYSTONE_BF16_APPLY_FORCE",  # test-only parity override, not a choice
    "KEYSTONE_BREAKER_RESET",
    "KEYSTONE_BREAKER_THRESHOLD",
    "KEYSTONE_CACHE_PROFILE_ALL",
    "KEYSTONE_COMPILE_CACHE",
    "KEYSTONE_FAULTS",
    "KEYSTONE_HANG_SECONDS",
    "KEYSTONE_HBM_BUDGET_BYTES",  # fit-time cache budget, not a serve knob
    "KEYSTONE_HEALTH_TIMEOUT",
    "KEYSTONE_HOST_WORKERS",
    "KEYSTONE_INIT_RETRIES",
    "KEYSTONE_IO_RETRIES",
    "KEYSTONE_METRICS",
    "KEYSTONE_OBS_DIR",
    "KEYSTONE_OBS_KEEP_SEGMENTS",
    "KEYSTONE_OBS_MAX_BYTES",
    "KEYSTONE_OC_PREFETCH",
    "KEYSTONE_OOC_FRACTION",
    "KEYSTONE_SOLVER_PRECISION",  # correctness-critical: never planned
    "KEYSTONE_SPILL_BATCH",
    "KEYSTONE_STAGE_DEADLINE",
    "KEYSTONE_STAGE_RETRIES",
    "KEYSTONE_STATE_DIR",
    "KEYSTONE_STREAM_TIMEOUT",
    "KEYSTONE_VALIDATE",
    "KEYSTONE_VERIFY_BLOCKS",
}


# ------------------------------------------------------------- installed plan

_LOCK = threading.Lock()
_PLAN = None  # the installed PhysicalPlan (None = no plan: legacy path)
_PLAN_SOURCE: Optional[str] = None
#: build-time forcing stack: the cost model samples a candidate by
#: forcing it ABOVE env and plan (it must measure the candidate it asked
#: for, not whatever the operator would have resolved)
_FORCED: list = []


def install_plan(plan, source: str = "install") -> None:
    """Install ``plan`` as THE process plan (every dispatch site's
    third precedence tier).  Idempotent per plan fingerprint; emits an
    ops-ledger event so a swapped/healed replica's plan provenance is
    auditable."""
    global _PLAN, _PLAN_SOURCE
    with _LOCK:
        _PLAN = plan
        _PLAN_SOURCE = source
    try:
        from keystone_tpu.obs import ledger

        ledger.event(
            "plan.install",
            source=source,
            version=None if plan is None else plan.fingerprint(),
            stages=0 if plan is None else len(plan.stages),
        )
    except Exception:
        pass


def clear_plan() -> None:
    """Remove the installed plan (tests; the byte-identical legacy
    path)."""
    global _PLAN, _PLAN_SOURCE
    with _LOCK:
        _PLAN = None
        _PLAN_SOURCE = None


def current_plan():
    return _PLAN


def plan_status() -> Optional[dict]:
    """Compact ``/statusz`` section: None when no plan is installed."""
    plan = _PLAN
    if plan is None:
        return None
    return {
        "fingerprint": plan.fingerprint(),
        "source": _PLAN_SOURCE,
        "backend": plan.backend,
        "stages": len(plan.stages),
        "choices": {s.gate: s.winner for s in plan.stages},
        "knobs": dict(plan.knobs),
    }


@contextmanager
def forced(gate: str, candidate: str):
    """Force ``gate`` to ``candidate`` for the block — the cost model's
    sampling lever, resolving ABOVE every other tier."""
    if gate not in GATES:
        raise KeyError(f"unknown gate {gate!r}; registered: {sorted(GATES)}")
    if candidate not in GATES[gate]["candidates"]:
        raise ValueError(
            f"{candidate!r} is not a candidate of gate {gate!r}: "
            f"{GATES[gate]['candidates']}"
        )
    entry = (gate, candidate)
    with _LOCK:
        _FORCED.append(entry)
    try:
        yield
    finally:
        with _LOCK:
            _FORCED.remove(entry)


def forced_gate(name: str) -> Optional[str]:
    """Innermost forced candidate for ``name``, or None.  Lock-free on
    the hot path (dispatch sites call this per resolution): ``tuple()``
    snapshots the list atomically under the GIL."""
    for gate, cand in reversed(tuple(_FORCED)):
        if gate == name:
            return cand
    return None


def planned_gate(name: str) -> Optional[str]:
    """The candidate the installed plan picked for ``name`` — the
    *forced > plan* slice of the precedence ladder (the dispatch sites
    keep their explicit-arg and env tiers in their own code so the
    no-plan path stays byte-identical).  None when nothing applies."""
    cand = forced_gate(name)
    if cand is not None:
        return cand
    plan = _PLAN
    if plan is None:
        return None
    cand = plan.choice_for(name)
    if cand is not None and cand not in GATES[name]["candidates"]:
        return None  # a corrupt/foreign plan never forces a bad dispatch
    return cand


def planned_knob(name: str):
    """The installed plan's value for knob ``name``, clamped to the
    registry bounds; None when no plan carries it."""
    plan = _PLAN
    if plan is None:
        return None
    if name not in KNOBS:
        raise KeyError(f"unknown knob {name!r}; registered: {sorted(KNOBS)}")
    value = plan.knobs.get(name)
    if value is None:
        return None
    ok, coerced, _why = validate_knob(name, value)
    return coerced if ok else None


def validate_knob(name: str, value):
    """``(ok, coerced, why)`` — the ONE bounds check the plan builder,
    the analysis ``plan`` pass, and the PlanTuner all use."""
    spec = KNOBS.get(name)
    if spec is None:
        return False, None, f"unknown knob {name!r}"
    lo, hi = spec["min"], spec["max"]
    kind = spec["kind"]
    try:
        if kind == "int_tuple":
            vals = tuple(int(v) for v in value)
            if not vals:
                return False, None, "empty bucket set"
            if any(v < lo or v > hi for v in vals):
                return False, None, f"bucket outside [{lo}, {hi}]: {vals}"
            return True, tuple(sorted(set(vals))), ""
        v = int(value) if kind == "int" else float(value)
    except (TypeError, ValueError):
        return False, None, f"{name}={value!r} is not {kind}"
    if v < lo or v > hi:
        return False, None, f"{name}={v} outside [{lo}, {hi}]"
    return True, v, ""


def supported_candidates(gate: str, backend: Optional[str] = None):
    """The candidates of ``gate`` the current (or named) backend can
    actually run — what the cost model samples and what the analysis
    pass accepts in a shipped plan."""
    spec = GATES[gate]
    tpu_only = set(spec.get("tpu_only", ()))
    if not tpu_only:
        return tuple(spec["candidates"])
    if backend is None:
        backend = current_backend()
    if backend == "tpu":
        return tuple(spec["candidates"])
    return tuple(c for c in spec["candidates"] if c not in tpu_only)


def current_backend() -> str:
    """The default JAX backend platform ('tpu' / 'cpu' / ...).  A
    backend that cannot be read raises: answering 'cpu' there would
    plan a TPU host with the CPU's candidates."""
    import jax

    return jax.default_backend()


def gate_env_names():
    """Every env var registered as a gate or knob override (the lint
    rule's allow set, alongside OPERATIONAL_ENV)."""
    names = {g["env"] for g in GATES.values() if g.get("env")}
    names |= {k["env"] for k in KNOBS.values() if k.get("env")}
    return names
