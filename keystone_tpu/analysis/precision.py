"""Pass (b): precision-policy lint over solver jaxprs.

PR 2's guarantee — solver math stays bit-identical f32 under every
``KEYSTONE_MATMUL`` mode — is pinned by byte-identity tests on two
paths.  This pass generalizes the pin into a *checker*: it traces the
jaxpr of every registered solver entry point (``lbfgs`` dense+sparse,
``block_ls``, ``block_weighted_ls``, ``kernel_ridge``) under each
precision mode (``bf16_apply`` force-resolved so the sweep is honest on
CPU), walks every contraction equation — recursing through pjit / scan /
while / cond sub-jaxprs — and errors on:

- ``bf16-solver-input``: a ``dot_general``/conv operand is bfloat16 —
  the apply-side policy leaked into solver math;
- ``non-f32-accumulation``: a contraction's result (or declared
  ``preferred_element_type``) is not f32 — accumulation degraded.

The registry of entry points is data (:data:`SOLVER_ENTRIES`), so a new
solver family is one tuple away from coverage; :func:`check_fn` is the
reusable core (the seeded-defect tests point it at deliberately-bf16
functions).
"""

from __future__ import annotations

import contextlib
import logging
from typing import Callable, List, Optional, Sequence, Tuple

from keystone_tpu.analysis.findings import PASS_PRECISION, Finding

logger = logging.getLogger(__name__)

#: contraction primitives whose operands/accumulation the lint audits
_DOT_PRIMS = ("dot_general", "conv_general_dilated", "ragged_dot")

#: the modes every solver must stay f32 under (the full KEYSTONE_MATMUL
#: surface; "auto" resolves to one of these)
MODES = ("f32", "bf16", "bf16_apply")


def _jaxpr_types():
    """(ClosedJaxpr, Jaxpr) — the public ``jax.extend.core`` types."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    return ClosedJaxpr, Jaxpr


def _iter_eqns(jaxpr, closed_t, jaxpr_t):
    """Yield every equation in ``jaxpr`` and, recursively, in any
    sub-jaxpr carried by equation params (pjit, scan, while, cond)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _as_jaxprs(v, closed_t, jaxpr_t):
                yield from _iter_eqns(sub, closed_t, jaxpr_t)


def _as_jaxprs(v, closed_t, jaxpr_t):
    if isinstance(v, closed_t):
        yield v.jaxpr
    elif isinstance(v, jaxpr_t):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _as_jaxprs(x, closed_t, jaxpr_t)


def _var_dtype(v) -> Optional[str]:
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    return None if dt is None else str(dt)


def check_fn(
    fn: Callable, *avals, name: str = "solver", mode: Optional[str] = None
) -> List[Finding]:
    """Trace ``fn`` over ``avals`` (ShapeDtypeStructs) and audit every
    contraction equation.  ``mode`` labels the findings; the caller owns
    setting the precision policy before tracing."""
    import jax

    closed_t, jaxpr_t = _jaxpr_types()
    # a FRESH function object per call: jax caches traces by (fun,
    # avals), and the precision policy is read at trace time — reusing
    # a cached jaxpr across the mode sweep would audit mode 1's graph
    # three times and make the sweep vacuous
    closed = jax.make_jaxpr(lambda *a: fn(*a))(*avals)
    findings: List[Finding] = []
    tag = f"{name}" + (f" under mode={mode}" if mode else "")
    for eqn in _iter_eqns(closed.jaxpr, closed_t, jaxpr_t):
        if eqn.primitive.name not in _DOT_PRIMS:
            continue
        for v in eqn.invars:
            dt = _var_dtype(v)
            if dt == "bfloat16":
                findings.append(
                    Finding(
                        "error",
                        PASS_PRECISION,
                        "bf16-solver-input",
                        f"{tag}: {eqn.primitive.name} consumes a bfloat16 "
                        "operand — the apply-side precision policy leaked "
                        "into solver math (use utils.precision.sdot)",
                        label=name,
                    )
                )
                break
        pet = eqn.params.get("preferred_element_type")
        out_dt = _var_dtype(eqn.outvars[0]) if eqn.outvars else None
        bad_pet = pet is not None and "float32" not in str(pet) and "float64" not in str(pet)
        bad_out = out_dt is not None and out_dt not in ("float32", "float64")
        if bad_pet or bad_out:
            findings.append(
                Finding(
                    "error",
                    PASS_PRECISION,
                    "non-f32-accumulation",
                    f"{tag}: {eqn.primitive.name} accumulates in "
                    f"{pet if bad_pet else out_dt} — solver contractions "
                    "must accumulate (and emit) f32",
                    label=name,
                )
            )
    return findings


# --------------------------------------------------------------- registry


def _avals(*specs):
    """ShapeDtypeStructs from (shape, dtype) pairs."""
    import jax
    import numpy as np

    return tuple(jax.ShapeDtypeStruct(s, np.dtype(d)) for s, d in specs)


def _entry_lbfgs_dense():
    from keystone_tpu.models.lbfgs import _lbfgs_least_squares

    fn = lambda x, y, n, lam: _lbfgs_least_squares(  # noqa: E731
        x, y, n, lam, num_iterations=2, history=3, fit_intercept=True
    )
    return fn, _avals(((8, 4), "f4"), ((8, 2), "f4"), ((), "f4"), ((), "f4"))


def _entry_lbfgs_sparse():
    from keystone_tpu.models.lbfgs import _sparse_vag

    fn = lambda idx, vals, y, n, lam, w: _sparse_vag(  # noqa: E731
        ((idx,), (vals,), (y,), n, lam), w, d=5, intercept=False
    )
    return fn, _avals(
        ((8, 3), "i4"),
        ((8, 3), "f4"),
        ((8, 2), "f4"),
        ((), "f4"),
        ((), "f4"),
        ((5, 2), "f4"),
    )


def _entry_block_ls():
    from keystone_tpu.models.block_ls import _oc_block_step

    return _oc_block_step, _avals(
        ((8, 4), "f4"),
        ((4,), "f4"),
        ((8, 2), "f4"),
        ((8,), "f4"),
        ((8,), "f4"),
        ((8, 2), "f4"),
        ((4, 2), "f4"),
        ((), "f4"),
    )


def _entry_block_weighted_ls():
    from keystone_tpu.models.block_weighted_ls import _weighted_bcd_fit

    fn = lambda x, y, alpha, n, lam: _weighted_bcd_fit(  # noqa: E731
        x, y, alpha, n, lam, 1, 4, True
    )
    return fn, _avals(
        ((8, 4), "f4"), ((8, 2), "f4"), ((8,), "f4"), ((), "f4"), ((), "f4")
    )


def _entry_kernel_ridge():
    from keystone_tpu.models.kernel_ridge import _krr_fit

    fn = lambda x, y, n: _krr_fit(x, y, n, 0.5, 1e-3, 4, 2)  # noqa: E731
    return fn, _avals(((8, 4), "f4"), ((8, 2), "f4"), ((), "f4"))


def _entry_kernel_ridge_oc():
    """The out-of-core gram-block sweep: one diag (solve) step chained
    into one off-diag F update — the two jitted programs the streamed
    fit dispatches.  Traced with use_pallas=False: the lint runs on CPU
    and audits the XLA chain; the Pallas path accumulates f32 in VMEM
    by construction and carries no dot_general to audit."""
    from keystone_tpu.models.kernel_ridge import (
        _oc_krr_diag_step,
        _oc_krr_offdiag_step,
    )

    def fn(xb, fb, ab, yb, ok_b, lam_n, xi, fi):
        ab2, fb2, dab, _ = _oc_krr_diag_step(
            xb, fb, ab, yb, ok_b, lam_n, gamma=0.5, use_pallas=False
        )
        fi2, _ = _oc_krr_offdiag_step(
            fi, xi, xb, dab, ok_b, ok_b, gamma=0.5, use_pallas=False
        )
        return ab2, fb2, fi2

    return fn, _avals(
        ((8, 4), "f4"),
        ((8, 2), "f4"),
        ((8, 2), "f4"),
        ((8, 2), "f4"),
        ((8,), "f4"),
        ((), "f4"),
        ((8, 4), "f4"),
        ((8, 2), "f4"),
    )


def _entry_nystrom():
    from keystone_tpu.models.nystrom import _nystrom_whiten

    fn = lambda l, g, r: _nystrom_whiten(l, g, r)  # noqa: E731
    return fn, _avals(((8, 4), "f4"), ((), "f4"), ((), "f4"))


#: (name, builder) — builder returns (traceable fn, input avals).  Every
#: solver family the repo ships must appear here; the seeded-defect
#: tests assert the checker catches a planted bf16 leak via check_fn.
SOLVER_ENTRIES: Sequence[Tuple[str, Callable]] = (
    ("lbfgs.dense", _entry_lbfgs_dense),
    ("lbfgs.sparse", _entry_lbfgs_sparse),
    ("block_ls", _entry_block_ls),
    ("block_weighted_ls", _entry_block_weighted_ls),
    ("kernel_ridge", _entry_kernel_ridge),
    ("kernel_ridge.oc", _entry_kernel_ridge_oc),
    ("nystrom", _entry_nystrom),
)


def _mode_context(mode: str):
    from keystone_tpu.utils import precision

    if mode == "bf16_apply":
        ctx = contextlib.ExitStack()
        ctx.enter_context(precision.matmul("bf16_apply"))
        # force-resolve the policy ACTIVE off-TPU: the sweep must audit
        # the graph a real TPU would run, not the CPU-inert fallback
        ctx.enter_context(precision.force_bf16_apply())
        return ctx
    return precision.matmul(mode)


def run(modes: Sequence[str] = MODES) -> List[Finding]:
    """Audit every registered solver entry point under every mode."""
    findings: List[Finding] = []
    for name, build in SOLVER_ENTRIES:
        try:
            fn, avals = build()
        except Exception as e:
            findings.append(
                Finding(
                    "warning",
                    PASS_PRECISION,
                    "solver-entry-unavailable",
                    f"solver entry {name} could not be built for "
                    f"tracing: {type(e).__name__}: {e}",
                    label=name,
                )
            )
            continue
        for mode in modes:
            try:
                with _mode_context(mode):
                    findings.extend(
                        check_fn(fn, *avals, name=name, mode=mode)
                    )
            except Exception as e:
                findings.append(
                    Finding(
                        "warning",
                        PASS_PRECISION,
                        "solver-entry-untraceable",
                        f"solver entry {name} failed to trace under "
                        f"mode={mode}: {type(e).__name__}: {e}",
                        label=name,
                    )
                )
    return findings
