"""Pass (d): CSE / cache-signature audit.

``signature()`` is load-bearing identity across the stack: the CSE rule
merges equal-prefix nodes, the shared-apply program caches key on
``(class, params())``, saved-state reload and the executor's breaker
registry both derive keys from it.  A transformer whose ``params()``
under-specifies its behavior — two observably different instances with
equal signatures — therefore doesn't just miss an optimization: CSE
silently replaces one node with the other, and cached programs/breaker
state leak between them (the PR-4 breaker-key collision class, caught
here statically).

Findings:

- ``signature-collision`` (error): two distinct transformer/estimator
  instances in the graph report equal signatures but differ in
  observable state (a public scalar/tuple attribute, or an array
  attribute's shape/dtype/small-value content);
- ``unstable-signature`` (error): ``signature()`` raises, is
  unhashable, or returns different values on consecutive calls —
  every signature consumer assumes stable hashable identity;
- ``dataset-name-collision`` (error): two distinct bound datasets share
  a ``name`` (the cross-process CSE/saved-state identity) but disagree
  on payload length/kind.
"""

from __future__ import annotations

import logging
from typing import List

from keystone_tpu.analysis.findings import PASS_SIGNATURES, Finding
from keystone_tpu.workflow import graph as G
from keystone_tpu.workflow.transformer import PLAIN_TYPES, PLUMBING_ATTRS

logger = logging.getLogger(__name__)

#: value-compare arrays up to this many elements (device→host read is
#: bounded); larger arrays compare by shape/dtype only
_VALUE_COMPARE_MAX = 4096


def _state_conflict(a, b) -> str:
    """Name of the first observable state difference between two
    equal-signature instances, or '' when none is detectable."""
    import numpy as np

    va = {k: v for k, v in vars(a).items() if k not in PLUMBING_ATTRS}
    vb = {k: v for k, v in vars(b).items() if k not in PLUMBING_ATTRS}
    for k in sorted(set(va) | set(vb)):
        if k.startswith("__"):
            continue
        x, y = va.get(k, _MISSING), vb.get(k, _MISSING)
        if x is _MISSING or y is _MISSING:
            return k
        if isinstance(x, PLAIN_TYPES) or isinstance(y, PLAIN_TYPES):
            if type(x) is not type(y) or x != y:
                return k
            continue
        if isinstance(x, tuple) and isinstance(y, tuple):
            if x != y:
                return k
            continue
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            if not (hasattr(y, "shape") and hasattr(y, "dtype")):
                return k
            if tuple(x.shape) != tuple(y.shape) or str(x.dtype) != str(
                y.dtype
            ):
                return k
            try:
                if (
                    int(np.prod(x.shape)) <= _VALUE_COMPARE_MAX
                    and not np.array_equal(
                        np.asarray(x, np.float64), np.asarray(y, np.float64)
                    )
                ):
                    return k
            except (TypeError, ValueError):
                pass
            continue
        # opaque objects: type change is observable, content is not
        if type(x) is not type(y):
            return k
    return ""


class _Missing:
    pass


_MISSING = _Missing()


def _stable_signature(obj, n, label, findings: List[Finding]):
    """signature() if stable+hashable, else None (with a finding)."""
    try:
        s1 = obj.signature()
        s2 = obj.signature()
        if s1 is not None:
            hash(s1)
    except Exception as e:
        findings.append(
            Finding(
                "error",
                PASS_SIGNATURES,
                "unstable-signature",
                f"{label}.signature() raised or is unhashable "
                f"({type(e).__name__}: {e}); every CSE/cache/breaker "
                "consumer assumes stable hashable identity",
                node=n.id,
                label=label,
            )
        )
        return None
    if s1 != s2:
        findings.append(
            Finding(
                "error",
                PASS_SIGNATURES,
                "unstable-signature",
                f"{label}.signature() returns different values on "
                "consecutive calls; identity must be deterministic",
                node=n.id,
                label=label,
            )
        )
        return None
    return s1


def collision_signatures(graph: G.Graph) -> set:
    """The set of transformer/estimator ``signature()`` values that
    COLLIDE in ``graph``: ≥ 2 distinct instances report the signature
    while differing in observable state.

    This is the cross-pipeline sharing admission gate
    (``workflow/cross.py``): the planner unions every co-served tenant
    graph and refuses to mark any stage whose signature lands in this
    set — a collision means ``params()`` under-specifies behavior, so a
    shared-pool entry for one instance would silently answer for the
    other.  Unstable/raising signatures are treated as colliding too
    (identity that cannot be trusted cannot key a shared cache)."""
    colliding: set = set()
    by_sig: dict = {}
    for n in graph.topological_nodes():
        op = graph.operators[n]
        if isinstance(op, G.TransformerOperator):
            obj = op.transformer
        elif isinstance(op, G.EstimatorOperator):
            obj = op.estimator
        else:
            continue
        try:
            s1 = obj.signature()
            s2 = obj.signature()
            if s1 is not None:
                hash(s1)
        except Exception:
            # raising/unhashable identity: nothing to key a refusal by
            # — the planner's own (guarded) signature() call yields
            # None for such nodes, so they are never pooled anyway
            continue
        if s1 is None:
            continue  # params() is None: never pooled
        if s1 != s2:
            # unstable identity cannot be trusted to key a shared
            # cache: refuse BOTH observed values
            colliding.add(s1)
            try:
                colliding.add(s2)
            except TypeError:
                pass
            continue
        by_sig.setdefault(s1, []).append(obj)
    for sig, group in by_sig.items():
        if len(group) < 2:
            continue
        first = group[0]
        for other in group[1:]:
            if other is first:
                continue
            if _state_conflict(first, other):
                colliding.add(sig)
                break
    return colliding


def run(graph: G.Graph) -> List[Finding]:
    findings: List[Finding] = []
    by_sig: dict = {}
    datasets_by_name: dict = {}
    for n in graph.topological_nodes():
        op = graph.operators[n]
        if isinstance(op, G.TransformerOperator):
            obj = op.transformer
        elif isinstance(op, G.EstimatorOperator):
            obj = op.estimator
        elif isinstance(op, G.DatasetOperator):
            ds = op.dataset
            name = getattr(ds, "name", None)
            if name is not None:
                prev = datasets_by_name.get(name)
                if prev is not None and prev[1] is not ds:
                    pn, pds = prev
                    if (
                        getattr(pds, "n", None) != getattr(ds, "n", None)
                        or getattr(pds, "is_host", None)
                        != getattr(ds, "is_host", None)
                    ):
                        findings.append(
                            Finding(
                                "error",
                                PASS_SIGNATURES,
                                "dataset-name-collision",
                                f"datasets at n{pn.id} and n{n.id} share "
                                f"name {name!r} but differ in payload "
                                "(names are cross-process CSE/saved-state "
                                "identity)",
                                node=n.id,
                                label=op.label(),
                            )
                        )
                else:
                    datasets_by_name[name] = (n, ds)
            continue
        else:
            continue
        sig = _stable_signature(obj, n, op.label(), findings)
        if sig is None:
            continue
        by_sig.setdefault(sig, []).append((n, obj, op.label()))

    for sig, group in by_sig.items():
        if len(group) < 2:
            continue
        n0, obj0, label0 = group[0]
        for n1, obj1, label1 in group[1:]:
            if obj1 is obj0:
                continue  # literally the same instance: the intended case
            attr = _state_conflict(obj0, obj1)
            if attr:
                findings.append(
                    Finding(
                        "error",
                        PASS_SIGNATURES,
                        "signature-collision",
                        f"{label0} at n{n0.id} and n{n1.id} report equal "
                        f"signatures but differ in attribute {attr!r}: "
                        "CSE would merge them and shared program/breaker "
                        "caches would leak between them — include the "
                        "attribute in params()",
                        node=n1.id,
                        label=label1,
                    )
                )
    return findings
