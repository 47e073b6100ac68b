"""Matmul precision policy — what bf16 actually buys on this hardware.

The reference computes everything in f64 on CPU BLAS (netlib-java,
SURVEY.md §2.8).  On TPU the naive expectation is "bf16 inputs ≈ 4× MXU
throughput", but measurement on v5 lite (chained in-jit matmuls, real
device sync) shows XLA's DEFAULT precision already runs f32 matmuls as
bf16-grade MXU passes:

    f32 inputs, precision=default : 2.0× the throughput of true f32
    f32 inputs, precision=float32 : baseline (full-precision passes)
    bf16 inputs                   : ≈ default-f32 (no additional compute win)

Two consequences shape this module:

1. **bf16 is a BANDWIDTH/capacity lever, not a compute lever.**  Explicit
   bf16 pays off only where an op is HBM-bound on its inputs: the SIFT
   windowing convs (+17% measured) and the Pallas FV kernel's descriptor
   stream (+11%).  Output-bound contractions (FV sufficient-statistic
   einsums: 0.64×) and compute-bound convs (Convolver: 0.94×) get only
   cast overhead and are deliberately NOT under the policy, as is the
   phase-sensitive CosineRandomFeatures (unbounded error through cos).

2. **Solvers must opt OUT of XLA's default.**  Default precision quietly
   degrades Gramians/normal equations to bf16-grade passes on TPU — the
   one place the reference used f64.  :func:`sdot` /
   :func:`solver_precision` pin solver contractions to true-f32 passes
   (2× slower on those matmuls, correctness first; env-overridable).

Modes for the featurize policy:
  - ``auto`` (default): bf16 when the default backend is a TPU, f32
    otherwise (CPU test meshes keep full precision).
  - ``bf16`` / ``f32``: forced, e.g. for parity tests.
  - ``bf16_apply``: everything ``auto``/``bf16`` does, PLUS the
    opt-in APPLY policy — every hot forward contraction (FV
    posterior/sufficient-statistic einsums, Convolver, blur einsums,
    LCS box filters, block-linear scoring, sparse scoring) casts its
    inputs to bf16 on device through :func:`apply_dot` /
    :func:`apply_einsum`, always with f32 accumulation.  The measured
    per-op story above (bf16 loses on output-bound contractions) is
    about HBM traffic of the op in isolation; inside a fused forward
    program the casts also halve every *inter*-contraction stream, so
    the whole-pipeline win is a separate measurement — the benchmark's
    scoring cell run under each mode is the arbiter.  ``bf16_apply`` resolves to the
    INERT f32 policy off-TPU (CPU test meshes stay bit-identical; see
    :func:`matmul_mode`) unless ``force_bf16_apply`` /
    ``KEYSTONE_BF16_APPLY_FORCE=1`` overrides the gate for parity
    testing.  Solver math (``sdot`` / ``solver_precision`` users:
    Gramians, BCD epochs, L-BFGS, EM) is NOT under this policy in any
    mode.

Set via env ``KEYSTONE_MATMUL``, :func:`set_matmul`, or the
:func:`matmul` context manager.  Compiled functions key their caches on
the resolved mode (transformer jit wrappers include it in their cache
signature; module-level kernels take it as a static argument), so
flipping the policy retraces rather than silently reusing stale
executables.
"""

from __future__ import annotations

import os
from contextlib import contextmanager

import jax
import jax.numpy as jnp

_MODES = ("auto", "bf16", "f32", "bf16_apply")
_MODE = os.environ.get("KEYSTONE_MATMUL", "auto")
if _MODE not in _MODES:
    raise ValueError(f"KEYSTONE_MATMUL must be one of {_MODES}, got {_MODE!r}")
#: True once the mode was pinned by a stronger tier than the plan — the
#: KEYSTONE_MATMUL env override at import, or set_matmul()/matmul()
#: (explicit calls).  While False and 'auto', an installed PhysicalPlan
#: may refine the mode (the planner precedence: explicit > env > plan >
#: static default).
_MODE_EXPLICIT = "KEYSTONE_MATMUL" in os.environ

#: test/dev override: lets ``bf16_apply`` resolve ACTIVE on non-TPU
#: backends so the bf16 numerics are exercisable on CPU meshes (the
#: parity suite); never set in production.
_APPLY_FORCE = os.environ.get("KEYSTONE_BF16_APPLY_FORCE", "0") == "1"

_DEFAULT_IS_TPU: bool | None = None


def _on_tpu() -> bool:
    """Whether computation currently targets a TPU.

    Resolution order mirrors ops/fisher_pallas.py § pallas_supported: the
    active framework mesh first (so a CPU mesh on a TPU host — e.g. the
    multichip dryrun — keeps full precision and validates what it claims
    to), then the default backend (cached: it cannot change).  A backend
    that cannot be read raises — it is never taken for "not a TPU"."""
    global _DEFAULT_IS_TPU
    from keystone_tpu.parallel.mesh import active_mesh

    m = active_mesh()
    if m is not None and m.devices.size:
        return m.devices.flat[0].platform == "tpu"
    if _DEFAULT_IS_TPU is None:
        _DEFAULT_IS_TPU = jax.default_backend() == "tpu"
    return _DEFAULT_IS_TPU


def set_matmul(mode: str) -> None:
    global _MODE, _MODE_EXPLICIT
    if mode not in _MODES:
        raise ValueError(f"matmul mode must be one of {_MODES}, got {mode!r}")
    _MODE = mode
    _MODE_EXPLICIT = True


def _planned_matmul() -> str | None:
    """The installed PhysicalPlan's matmul winner, or None.  Guarded
    lazy import: with no planner in play this costs one cheap call and
    the legacy resolution is untouched."""
    try:
        from keystone_tpu.planner import registry as _plans

        return _plans.planned_gate("matmul")
    except Exception:
        return None


def matmul_mode() -> str:
    """The resolved mode: 'bf16', 'f32', or 'bf16_apply' (never 'auto').

    With nothing pinned (no ``KEYSTONE_MATMUL`` env, no ``set_matmul``),
    an installed ``PhysicalPlan``'s sampled winner applies first — the
    plan tier of the precedence ladder.  ``bf16_apply`` gates on REAL
    TPU hardware: off-chip it resolves to 'f32' — the inert policy — so
    CPU test meshes (and the multichip dryrun's CPU mesh on a TPU host)
    produce bit-identical outputs with the policy set or not.
    ``force_bf16_apply`` / ``KEYSTONE_BF16_APPLY_FORCE=1`` lifts the
    gate for parity testing."""
    mode = _MODE
    if not _MODE_EXPLICIT:
        planned = _planned_matmul()
        if planned in _MODES:
            mode = planned
    if mode == "auto":
        return "bf16" if _on_tpu() else "f32"
    if mode == "bf16_apply":
        return "bf16_apply" if (_on_tpu() or _APPLY_FORCE) else "f32"
    return mode


@contextmanager
def matmul(mode: str):
    global _MODE, _MODE_EXPLICIT
    prev, prev_explicit = _MODE, _MODE_EXPLICIT
    set_matmul(mode)
    try:
        yield
    finally:
        _MODE = prev
        # restore the explicitness too: a scoped matmul() inside an
        # otherwise-unpinned process must not permanently mask the plan
        _MODE_EXPLICIT = prev_explicit


@contextmanager
def force_bf16_apply():
    """Lift the on-TPU gate so ``bf16_apply`` resolves active on any
    backend — the parity suite's way of exercising the bf16 numerics on
    CPU meshes.  Production code never needs this."""
    global _APPLY_FORCE
    prev = _APPLY_FORCE
    _APPLY_FORCE = True
    try:
        yield
    finally:
        _APPLY_FORCE = prev


_SOLVER_PRECISIONS = ("default", "float32", "highest")
_SOLVER_PRECISION = os.environ.get("KEYSTONE_SOLVER_PRECISION", "float32")
if _SOLVER_PRECISION not in _SOLVER_PRECISIONS:
    raise ValueError(
        f"KEYSTONE_SOLVER_PRECISION must be one of {_SOLVER_PRECISIONS}, "
        f"got {_SOLVER_PRECISION!r}"
    )


def solver_precision():
    """lax.Precision for solver contractions (Gramians, normal equations,
    LBFGS gradients, covariances).

    Measured on TPU v5 lite: XLA's DEFAULT matmul precision runs f32
    inputs as bf16-grade MXU passes (~2× the throughput of true f32) —
    acceptable for forward features, but normal equations square the
    condition number and the reference solves them in f64, so solvers
    default to 'float32' (full-precision passes).  Override with
    ``KEYSTONE_SOLVER_PRECISION=default`` to trade accuracy for the 2×.
    """
    from jax import lax

    return {
        "default": lax.Precision.DEFAULT,
        "float32": lax.Precision.HIGHEST,
        "highest": lax.Precision.HIGHEST,
    }[_SOLVER_PRECISION]


def sdot(a, b):
    """Solver-grade matmul: true-f32 MXU passes, f32 accumulation.  Use
    for every contraction whose result enters a linear solve (Gramians,
    AᵀB right-hand sides, covariances, EM sufficient statistics,
    LBFGS gradients)."""
    import jax.numpy as jnp

    return jnp.matmul(
        a, b, precision=solver_precision(), preferred_element_type=jnp.float32
    )


def fdtype(mode: str | None = None):
    """The featurize-matmul input dtype for ``mode`` (default: current).
    ``bf16_apply`` is a superset of the featurize policy, so it maps to
    bf16 here too."""
    m = matmul_mode() if mode is None else mode
    return jnp.bfloat16 if m in ("bf16", "bf16_apply") else jnp.float32


def fcast(*xs, mode: str | None = None):
    """Cast featurize-matmul inputs to the policy dtype.  Pair every use
    with ``preferred_element_type=jnp.float32`` so accumulation (and the
    result) stays f32."""
    dt = fdtype(mode)
    out = tuple(jnp.asarray(x).astype(dt) for x in xs)
    return out if len(out) > 1 else out[0]


# ------------------------------------------------------------------------
# Apply-side policy: the opt-in bf16 fast path for the forward /
# featurization contractions that the featurize policy deliberately
# leaves alone.  Active ONLY when the resolved mode is "bf16_apply"
# (on-TPU-gated above); in every other mode the helpers are identity
# wrappers around jnp.dot / jnp.einsum with f32 accumulation, emitting
# the exact graph the call sites emitted before the policy existed.


def apply_mode(mode: str | None = None) -> str:
    """Collapse the resolved policy to what the APPLY path cares about:
    'bf16_apply' when the apply policy is active, else 'f32'.  Ops whose
    only policy-sensitive contractions go through apply_dot/apply_einsum
    use this as their static jit key so a featurize-only 'bf16' flip
    does not force a pointless retrace of an identical program."""
    m = matmul_mode() if mode is None else mode
    return m if m == "bf16_apply" else "f32"


def adtype(mode: str | None = None):
    """Apply-policy contraction input dtype: bf16 iff active."""
    m = matmul_mode() if mode is None else mode
    return jnp.bfloat16 if m == "bf16_apply" else jnp.float32


def acast(*xs, mode: str | None = None):
    """Cast apply-policy contraction inputs (identity when inert).  Pair
    with ``preferred_element_type=jnp.float32`` like :func:`fcast`."""
    dt = adtype(mode)
    out = tuple(jnp.asarray(x).astype(dt) for x in xs)
    return out if len(out) > 1 else out[0]


def apply_dot(a, b, mode: str | None = None):
    """Apply-policy matmul: bf16 inputs (when active) with f32
    accumulation and f32 output.  Inert modes produce the exact
    ``jnp.dot(a, b, preferred_element_type=f32)`` the converted call
    sites used before — CPU meshes stay bit-identical by construction."""
    a, b = acast(a, b, mode=mode)
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def apply_einsum(spec: str, *operands, mode: str | None = None):
    """Apply-policy einsum: bf16 operands (when active), f32
    accumulation/output.  See :func:`apply_dot`."""
    ops = acast(*operands, mode=mode)
    if len(operands) == 1:
        ops = (ops,)
    return jnp.einsum(spec, *ops, preferred_element_type=jnp.float32)
