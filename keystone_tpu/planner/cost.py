"""The sampling-based cost model: micro-profile candidates, fit curves,
pick winners.

This is the KeystoneML optimizer loop (PAPER.md §4) in miniature: at
``freeze()`` time, each stage with more than one physical candidate is
executed on a few **sampled batch sizes** (the ProfilingAutoCacheRule
sampling discipline — truncated inputs, wall-timed runs, best-of-reps),
a linear cost curve ``seconds ≈ a + b·n`` is fitted per candidate, and
the candidate cheapest at the serving batch size wins.  Winners plus
the derived serving knobs land in one :class:`~keystone_tpu.planner.
plan.PhysicalPlan`.

Determinism: sample indices come from ``np.random.default_rng(seed)``
and candidate enumeration order is the registry's — with an injected
``runner`` (tests) the whole plan is a pure function of its inputs.
The default runner wall-times real executions; each timed run passes
the ``plan.sample`` fault site (ctx ``gate=/candidate=/n=``), so a
fault-injected delay inflates exactly one candidate's samples — the
winner-flip test's lever, and the chaos story for the cost model
itself.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from keystone_tpu import faults
from keystone_tpu.planner import registry
from keystone_tpu.planner.plan import (
    CandidateCost,
    PhysicalPlan,
    StageChoice,
    stage_signature,
)

logger = logging.getLogger(__name__)

#: tie margin: a non-default candidate must beat the default by more
#: than this fraction to displace it (sampling noise must not flip a
#: pinned default on a coin toss)
TIE_MARGIN = 0.02


def fit_curve(samples: Sequence[Tuple[int, float]]) -> Tuple[float, float]:
    """Least-squares ``seconds ≈ a + b·n`` over ``[(n, seconds), ...]``;
    degenerate sample sets collapse to a flat curve through the mean."""
    if not samples:
        return (0.0, 0.0)
    ns = np.asarray([float(n) for n, _ in samples])
    ts = np.asarray([float(t) for _, t in samples])
    if len(samples) == 1 or float(np.ptp(ns)) == 0.0:
        return (float(ts.mean()), 0.0)
    b = float(np.cov(ns, ts, bias=True)[0, 1] / np.var(ns))
    a = float(ts.mean() - b * ns.mean())
    return (max(0.0, a), max(0.0, b))


def price(coeffs: Tuple[float, float], n: int) -> float:
    return float(coeffs[0] + coeffs[1] * float(n))


def _block(out) -> None:
    """Force async device work to finish inside the timed region."""
    try:
        import jax

        jax.block_until_ready(out)
    except Exception:
        pass


def wall_runner(fn: Callable[[], object], *, gate: str, candidate: str,
                n: int, reps: int = 2) -> float:
    """Best-of-``reps`` wall seconds for one candidate run at batch
    ``n``.  The first (untimed) call absorbs trace/compile; each timed
    rep passes the ``plan.sample`` fault site so chaos plans can stall
    one candidate's measurements specifically."""
    _block(fn())
    best: Optional[float] = None
    for _ in range(max(1, int(reps))):
        t0 = time.perf_counter()
        faults.fault_point(
            "plan.sample", gate=gate, candidate=candidate, n=int(n)
        )
        _block(fn())
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return float(best)


def _sample_batch(arr: np.ndarray, n: int, rng) -> np.ndarray:
    """``n`` rows drawn (with replacement) from ``arr`` — deterministic
    under the plan seed, any requested size from any sample."""
    rows = max(1, int(arr.shape[0]))
    idx = rng.integers(0, rows, size=int(n))
    return np.asarray(arr)[idx]


def _pick_winner(
    gate: str, candidates: Dict[str, CandidateCost]
) -> Tuple[str, str]:
    """(winner, why) — cheapest at full batch, with the static default
    keeping ties (TIE_MARGIN)."""
    order = [c for c in registry.GATES[gate]["candidates"] if c in candidates]
    runnable = [c for c in order if candidates[c].supported]
    if not runnable:
        return order[0], "no runnable candidate; static default retained"
    if len(runnable) == 1:
        return runnable[0], "single supported candidate on this backend"
    default = runnable[0]
    best = min(runnable, key=lambda c: candidates[c].full_seconds)
    d_cost = candidates[default].full_seconds
    b_cost = candidates[best].full_seconds
    if best != default and d_cost > 0 and (d_cost - b_cost) / d_cost <= TIE_MARGIN:
        return default, (
            f"{best} within {TIE_MARGIN:.0%} of default; default retained"
        )
    if best == default:
        return default, (
            f"default cheapest at n={'full'} "
            f"({b_cost * 1e3:.3f}ms)"
        )
    return best, (
        f"beats {default} at full batch "
        f"({b_cost * 1e3:.3f}ms vs {d_cost * 1e3:.3f}ms)"
    )


def _sample_gate(
    gate: str,
    label: str,
    signature: str,
    cand_fns: Dict[str, Optional[Callable[[np.ndarray], object]]],
    input_arr: np.ndarray,
    batch_sizes: Sequence[int],
    full_batch: int,
    rng,
    runner: Callable[..., float],
) -> StageChoice:
    """Time every candidate of one gate at every sampled batch size and
    choose.  A candidate mapped to None is recorded unsupported."""
    costs: Dict[str, CandidateCost] = {}
    for cand, fn in cand_fns.items():
        cc = CandidateCost(name=cand)
        if fn is None:
            cc.supported = False
            cc.note = "not runnable on this backend"
            cc.full_seconds = float("inf")
            costs[cand] = cc
            continue
        try:
            for n in batch_sizes:
                x = _sample_batch(input_arr, n, rng)
                with registry.forced(gate, cand):
                    secs = runner(
                        lambda x=x, fn=fn: fn(x),
                        gate=gate,
                        candidate=cand,
                        n=n,
                    )
                cc.samples.append([int(n), float(secs)])
            cc.coeffs = fit_curve(cc.samples)
            cc.full_seconds = price(cc.coeffs, full_batch)
        except Exception as e:  # sampling is best-effort, like profiling
            logger.debug("plan sampling failed for %s/%s: %s", gate, cand, e)
            cc.supported = False
            cc.note = f"sampling failed: {type(e).__name__}"
            cc.full_seconds = float("inf")
        costs[cand] = cc
    winner, why = _pick_winner(gate, costs)
    # JSON has no Infinity: unsupported candidates price as 0 with the
    # supported=False flag carrying the meaning
    for cc in costs.values():
        if not np.isfinite(cc.full_seconds):
            cc.full_seconds = 0.0
    return StageChoice(
        gate=gate,
        signature=signature,
        label=label,
        winner=winner,
        why=why,
        candidates=[costs[c] for c in registry.GATES[gate]["candidates"]
                    if c in costs],
    )


def _matmul_candidates(backend: str) -> Tuple[str, ...]:
    """Precision modes worth sampling: off-TPU every mode resolves to
    the inert f32 policy, so there is exactly one physical candidate —
    sampling 'f32' against 'auto' there would let timer noise ship a
    pinned mode that changes numerics on a later TPU deploy."""
    if backend == "tpu":
        return ("auto", "f32", "bf16_apply")
    return ("auto",)


def build_plan(
    pipeline,
    example=None,
    batch_sizes: Sequence[int] = (8, 32, 128),
    full_batch: int = 32,
    max_batch: int = 32,
    seed: int = 0,
    runner: Optional[Callable[..., float]] = None,
    candidates: Optional[Dict[str, Sequence[str]]] = None,
    source: str = "freeze",
) -> PhysicalPlan:
    """Build a :class:`PhysicalPlan` for a fitted ``pipeline``.

    ``example`` — a batch (or one datum) of representative input; the
    sampled batches are drawn from its rows.  Without it, stage
    sampling is skipped and every gate keeps its static default (the
    plan still pins serving knobs and backend).  ``runner`` — injected
    timing function (tests); default :func:`wall_runner`.
    ``candidates`` — per-gate candidate override (bench A/B and the
    winner-flip tests); default :func:`registry.supported_candidates`.
    """
    from keystone_tpu.workflow import graph as G

    backend = registry.current_backend()
    rng = np.random.default_rng(int(seed))
    run = runner or wall_runner
    batch_sizes = tuple(sorted({int(b) for b in batch_sizes}))
    stages: list = []
    forward_coeffs: Optional[Tuple[float, float]] = None

    ex_arr = None
    if example is not None:
        ex_arr = np.asarray(example)
        if ex_arr.ndim == 0:
            ex_arr = ex_arr[None]
        if ex_arr.shape[0] == 1 or ex_arr.ndim == 1:
            ex_arr = ex_arr.reshape(1, *ex_arr.shape[1:] or (1,))

    def cands_for(gate: str) -> Tuple[str, ...]:
        if candidates and gate in candidates:
            return tuple(candidates[gate])
        return registry.supported_candidates(gate, backend=backend)

    graph = pipeline.graph
    executor = None
    if ex_arr is not None:
        try:
            from keystone_tpu.workflow.dataset import Dataset
            from keystone_tpu.workflow.executor import GraphExecutor

            bound, _ = graph.replace_source_with_node(
                pipeline.source,
                G.DatasetOperator(
                    Dataset(ex_arr, n=int(ex_arr.shape[0]), shard=False)
                ),
            )
            executor = (bound, GraphExecutor(bound))
        except Exception as e:
            logger.debug("plan input binding failed: %s", e)
            executor = None

    def _input_rows(node) -> Optional[np.ndarray]:
        """The sampled input rows feeding ``node`` (its single dep's
        output), as a host array."""
        if executor is None:
            return None
        bound, ex = executor
        deps = bound.dependencies.get(node, ())
        if len(deps) != 1:
            return None
        try:
            from keystone_tpu.workflow.executor import DatasetExpr

            expr = ex.execute(deps[0])
            if not isinstance(expr, DatasetExpr) or expr.dataset.is_host:
                return None
            return np.asarray(expr.dataset.array)
        except Exception as e:
            logger.debug("plan input execution failed at %s: %s", node, e)
            return None

    # ---------------------------------------------------- per-stage gates
    if executor is not None:
        bound = executor[0]
        for node in bound.topological_nodes():
            op = bound.operators.get(node)
            t = getattr(op, "transformer", None)
            if t is None:
                continue
            tname = type(t).__name__
            if tname in ("FisherVector", "FusedPcaFisherVector"):
                choice = _plan_fused_fv(
                    bound, node, t, _input_rows, cands_for("fused_fv"),
                    batch_sizes, full_batch, rng, run,
                )
                if choice is not None:
                    stages.append(choice)
            elif tname in (
                "KernelBlockLinearMapper",
                "OutOfCoreKernelBlockLinearMapper",
            ):
                arr = _input_rows(node)
                if arr is None:
                    continue
                fns = {
                    c: (lambda x, t=t: t.apply_batch(x))
                    for c in cands_for("gram_pallas")
                }
                stages.append(
                    _sample_gate(
                        "gram_pallas", op.label(), stage_signature(t), fns,
                        arr, batch_sizes, full_batch, rng, run,
                    )
                )

    # -------------------------------------------- whole-pipeline matmul
    mm_cands = (
        tuple(candidates["matmul"])
        if candidates and "matmul" in candidates
        else _matmul_candidates(backend)
    )
    if executor is not None:
        from keystone_tpu.utils import precision

        bound, ex0 = executor
        sink_dep = bound.sink_dependencies.get(pipeline.sink)

        def forward(x: np.ndarray):
            from keystone_tpu.workflow.dataset import Dataset
            from keystone_tpu.workflow.executor import GraphExecutor

            g2, _ = graph.replace_source_with_node(
                pipeline.source,
                G.DatasetOperator(Dataset(x, n=int(x.shape[0]), shard=False)),
            )
            ex2 = GraphExecutor(g2)
            return ex2.execute(g2.sink_dependencies[pipeline.sink])

        if sink_dep is not None:
            costs: Dict[str, CandidateCost] = {}
            try:
                for cand in mm_cands:
                    cc = CandidateCost(name=cand)
                    for n in batch_sizes:
                        x = _sample_batch(ex_arr, n, rng)
                        with precision.matmul(cand):
                            secs = run(
                                lambda x=x: forward(x),
                                gate="matmul",
                                candidate=cand,
                                n=n,
                            )
                        cc.samples.append([int(n), float(secs)])
                    cc.coeffs = fit_curve(cc.samples)
                    cc.full_seconds = price(cc.coeffs, full_batch)
                    costs[cand] = cc
            except Exception as e:
                logger.debug("plan forward sampling failed: %s", e)
                costs = {}
            if costs:
                winner, why = _pick_winner("matmul", costs)
                psig = ""
                try:
                    from keystone_tpu.utils.hashing import pipeline_fingerprint

                    psig = pipeline_fingerprint(pipeline)
                except Exception:
                    pass
                stages.append(
                    StageChoice(
                        gate="matmul",
                        signature=f"pipeline:{psig[:12]}" if psig else
                        "pipeline",
                        label="<forward>",
                        winner=winner,
                        why=why,
                        candidates=[
                            costs[c]
                            for c in registry.GATES["matmul"]["candidates"]
                            if c in costs
                        ],
                    )
                )
                forward_coeffs = costs[winner].coeffs

    knobs = select_knobs(forward_coeffs, max_batch=max_batch)
    psig = ""
    try:
        from keystone_tpu.utils.hashing import pipeline_fingerprint

        psig = pipeline_fingerprint(pipeline)
    except Exception:
        pass
    return PhysicalPlan(
        backend=backend,
        seed=int(seed),
        batch_sizes=batch_sizes,
        full_batch=int(full_batch),
        stages=stages,
        knobs=knobs,
        source=source,
        pipeline_signature=psig,
    )


def _plan_fused_fv(
    graph, node, fv, input_rows, cands, batch_sizes, full_batch, rng, run
) -> Optional[StageChoice]:
    """The fused-FV gate compares REAL alternatives: the per-stage
    PCA→FV chain ('xla') against the one fused forward node the
    optimizer rule would install ('pallas') — both fed the PCA's input,
    exactly the substitution ``PallasFvFusionRule`` makes."""
    tname = type(fv).__name__
    if tname == "FusedPcaFisherVector":
        # already fused (a re-plan over an optimized graph): nothing to
        # compare — record the standing choice
        return StageChoice(
            gate="fused_fv",
            signature=stage_signature(fv),
            label="FusedPcaFisherVector",
            winner="pallas",
            why="graph already carries the fused node",
        )
    deps = graph.dependencies.get(node, ())
    pca = None
    pca_node = None
    if len(deps) == 1:
        op = graph.operators.get(deps[0])
        t = getattr(op, "transformer", None)
        if type(t).__name__ == "PCATransformer":
            pca, pca_node = t, deps[0]
    if pca is None:
        return None  # the rule only fuses a PCA→FV pair
    arr = input_rows(pca_node)
    if arr is None:
        return None
    fns: Dict[str, Optional[Callable]] = {}
    for c in cands:
        if c == "xla":
            fns[c] = lambda x, pca=pca, fv=fv: fv.apply_batch(
                pca.apply_batch(x)
            )
        elif c == "pallas":
            try:
                from keystone_tpu.ops.fisher import FusedPcaFisherVector

                fused = FusedPcaFisherVector(
                    pca, fv.gmm, sift_normalize=False,
                    use_pallas=fv.use_pallas,
                )
                fns[c] = lambda x, fused=fused: fused.apply_batch(x)
            except Exception as e:
                logger.debug("fused candidate unavailable: %s", e)
                fns[c] = None
        else:
            fns[c] = None
    return _sample_gate(
        "fused_fv",
        f"{type(pca).__name__}->{tname}",
        stage_signature(fv),
        fns,
        arr,
        batch_sizes,
        full_batch,
        rng,
        run,
    )


def select_knobs(
    forward_coeffs: Optional[Tuple[float, float]], max_batch: int = 32
) -> dict:
    """Serving knobs from the fitted forward curve.

    - **buckets**: the power-of-two ladder (the static default — the
      PlanTuner refines the set live from observed flush occupancy);
    - **max_wait_ms**: wait at most ~2 fixed-overheads ``a`` for riders
      (waiting longer than the amortizable launch cost buys nothing),
      clamped to [1, 20] ms around the static 5 ms default;
    - **dispatch_window**: the pool's static default of 2 (the curve
      carries no queueing information; the tuner owns this knob live);
    - **hedge_ms**: fire a hedge past ~5× the fitted full-batch time —
      late enough that healthy flushes never hedge;
    - **pool_budget_bytes**: the resolved device budget, PINNED so a
      deploy host with different headroom serves what was planned.
    """
    from keystone_tpu.serve.service import default_buckets
    from keystone_tpu.workflow.profiling import pool_budget_bytes

    knobs = {
        "buckets": [int(b) for b in default_buckets(int(max_batch))],
        "dispatch_window": 2,
        "pool_budget_bytes": int(pool_budget_bytes()),
    }
    if forward_coeffs is None:
        knobs["max_wait_ms"] = 5.0
        return knobs
    a, b = forward_coeffs
    knobs["max_wait_ms"] = round(min(20.0, max(1.0, 2000.0 * a)), 3)
    knobs["hedge_ms"] = round(
        min(60000.0, max(50.0, 5000.0 * (a + b * max_batch))), 3
    )
    return knobs
