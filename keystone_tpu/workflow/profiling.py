"""Profiling-driven materialization (the AutoCacheRule proper).

Reference: workflow/AutoCacheRule.scala — estimates per-node output size
and compute time by running nodes on sampled partitions, then greedily
places caches under a cluster-memory budget.

TPU version: the budget is HBM (≈16 GB/chip — far tighter than a Spark
cluster's aggregate RAM, SURVEY.md §7 hard part e), and the decision is
materialize-vs-recompute: shared node outputs that fit keep an explicit
materialization barrier (Cacher); shared outputs that don't fit are
flagged no-memoize so the executor recomputes them per consumer instead
of pinning them in HBM.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Dict, Optional

import numpy as np

from keystone_tpu.workflow import graph as G
from keystone_tpu.workflow.dataset import Dataset
from keystone_tpu.workflow.optimizer import (
    Rule,
    _truncate_datasets,
    needs_barrier,
    place_barrier,
)

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class NodeProfile:
    """Measured on a sample, extrapolated to the full dataset."""

    seconds: float
    output_bytes: int
    scale: float  # full_n / sample_n extrapolation factor
    hlo_seconds: Optional[float] = None  # full-scale roofline estimate
    #: where the static price came from: a program compiled for it (False),
    #: the memo of an earlier node of its kind and shapes (True), or no
    #: static price was asked for or possible (None)
    price_hit: Optional[bool] = None

    @property
    def full_bytes(self) -> int:
        return int(self.output_bytes * self.scale)

    @property
    def full_seconds(self) -> float:
        # the static estimate, when available, is already at full scale and
        # immune to wall-clock noise / sub-sample fixed overheads
        if self.hlo_seconds is not None:
            return self.hlo_seconds
        return self.seconds * self.scale


# roofline peaks (f32 flops/s, HBM bytes/s) used to turn compiled HLO
# counters into a time estimate, keyed by ``device_kind`` (CPU devices
# share the "cpu" row).  Only the *relative* ranking across nodes matters
# for cache placement, but the constants are real hardware numbers
# (Google Cloud documentation, "TPU v5e": 197 Tf/s bf16 → ~49 Tf/s f32;
# 819 GB/s HBM).
_ROOFLINE_PEAKS = {
    "TPU v5 lite": (4.9e13, 8.1e11),
    "cpu": (5e10, 3e10),
}


def roofline_peaks():
    """(f32 flops/s, HBM bytes/s) of the first device.  A device kind
    that is not in the table is an error, not the CPU's peaks: a TPU
    priced as a CPU ranks every stage wrong in silence."""
    import jax

    dev = jax.local_devices()[0]
    key = "cpu" if dev.platform == "cpu" else dev.device_kind
    if key not in _ROOFLINE_PEAKS:
        raise KeyError(
            f"no roofline peaks for device kind {key!r} (platform "
            f"{dev.platform!r}); add its row to _ROOFLINE_PEAKS"
        )
    return _ROOFLINE_PEAKS[key]


def hlo_stage_cost(fn, *avals) -> Optional[dict]:
    """Compile ``fn`` for the given ShapeDtypeStructs and read XLA's cost
    analysis (SURVEY.md §5: "per-stage cost model from compiled HLO cost
    analysis instead of sampling runs").  Returns {'flops', 'bytes',
    'seconds_est'} or None when analysis is unavailable.

    Nothing executes and no buffers are allocated — this prices a stage at
    *full* batch size without paying for a full-size run."""
    import jax

    peak_f, peak_b = roofline_peaks()
    try:
        compiled = jax.jit(fn).lower(*avals).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0) or 0.0)
        byts = float(ca.get("bytes accessed", 0.0) or 0.0)
        if flops <= 0.0 and byts <= 0.0:
            return None
        return {
            "flops": flops,
            "bytes": byts,
            "seconds_est": max(flops / peak_f, byts / peak_b),
        }
    except Exception as e:  # cost analysis is best-effort
        logger.debug("hlo cost analysis failed: %s", e)
        return None


def profile_graph(
    graph: G.Graph,
    sample_size: int = 64,
    static_cost: bool = False,
    targets=None,
) -> Dict[G.NodeId, NodeProfile]:
    """Run every reachable transformer node on truncated dataset literals,
    recording wall time and output size (the reference's sampling pass).

    With ``static_cost=True``, additionally price each device transformer
    at FULL batch size from its compiled HLO (hlo_stage_cost) — sampled
    runs still provide shapes and output sizes, but the seconds estimate
    comes from XLA's own cost counters instead of extrapolated wall time.

    ``targets`` restricts profiling to a node subset (their sampled
    ancestors still execute, memoized, to produce inputs).  The cache rule
    passes the SHARED nodes here: they are the only ones whose profiles
    the placement decision reads, and pricing only them avoids compiling
    every stage at full batch size and avoids sampled execution of
    subgraphs (e.g. the solver's) that no shared output depends on — 4
    shared of 23 profilable on the north-star fit when rounds 1–5 measured
    it, where the unrestricted pass was ~60% of total fit wall-clock; in
    the benchmark's ImageNet fit one node, `PixelScaler` (PR 33)."""
    from keystone_tpu.workflow.executor import DatasetExpr, GraphExecutor

    full_n = max(
        (
            op.dataset.n if isinstance(op.dataset, Dataset) else len(op.dataset)
            for op in graph.operators.values()
            if isinstance(op, G.DatasetOperator)
        ),
        default=1,
    )
    truncated = _truncate_datasets(graph, sample_size)
    ex = GraphExecutor(truncated, profile=True)
    profiles: Dict[G.NodeId, NodeProfile] = {}
    for n in truncated.topological_nodes():
        op = truncated.operators[n]
        if not isinstance(op, (G.TransformerOperator, G.GatherOperator)):
            continue
        if targets is not None and n not in targets:
            continue
        try:
            expr = ex.execute(n)
        except Exception as e:  # profiling is best-effort, like upstream
            logger.debug("profiling failed at %s: %s", op.label(), e)
            continue
        nbytes = 0
        sample_n = 1
        if isinstance(expr, DatasetExpr) and not expr.dataset.is_host:
            arr = expr.dataset.array
            nbytes = int(np.prod(arr.shape)) * arr.dtype.itemsize
            sample_n = max(expr.dataset.n, 1)
        hlo_seconds = price_hit = None
        if static_cost:
            hlo_seconds, price_hit = _static_node_seconds(truncated, ex, n, op, full_n)
        profiles[n] = NodeProfile(
            seconds=ex.timings.get(n, 0.0),
            output_bytes=nbytes,
            scale=max(full_n / sample_n, 1.0),
            hlo_seconds=hlo_seconds,
            price_hit=price_hit,
        )
    return profiles


def _static_node_seconds(graph: G.Graph, ex, n: G.NodeId, op, full_n: int):
    """(full-scale roofline estimate, whether the memo had it) for one
    transformer node, from the sampled input's shape with the batch axis
    widened to full_n; (None, None) for a node that cannot be priced."""
    import jax

    if not isinstance(op, G.TransformerOperator):
        return None, None
    from keystone_tpu.workflow.executor import DatasetExpr

    deps = graph.dependencies.get(n, ())
    if len(deps) != 1:
        return None, None
    d = ex.results.get(deps[0])
    if not isinstance(d, DatasetExpr) or d.dataset.is_host:
        return None, None
    ds = d.dataset
    arr_aval = jax.ShapeDtypeStruct((full_n,) + tuple(ds.array.shape[1:]), ds.array.dtype)
    mask_aval = None
    if ds.mask is not None:
        mask_aval = jax.ShapeDtypeStruct((full_n,) + tuple(ds.mask.shape[1:]), ds.mask.dtype)
    return _priced_by_shape(op.transformer, arr_aval, mask_aval)


#: roofline seconds of a node, by what the estimate can depend on: (the
#: node's program identity, input and parameter shapes, matmul mode).
#: Bounded FIFO, as the shared-apply cache is; holds numbers only.
_PRICED: dict = {}
_PRICED_MAX = 128


def _priced_by_shape(t, arr_aval, mask_aval):
    """The static price of a node, and whether the memo had it.  The
    program is lowered from SHAPES, as the node's own shared apply is —
    its fitted arrays (``Transformer.traced_attrs``; none for a
    parameter-free node) ride as traced arguments — and the estimate is
    kept for every later node of the same kind and shapes: a warm fit or
    call prices from the memo and compiles nothing.  Closed over instead,
    a freshly fitted array is read back to the host by the lowering and
    embedded in the program — 4.3 MB of convolution filters made a new
    2 s compile of every RandomPatchCifar fit (my chip run, PR 32) — for
    a number that cannot depend on its values.  A node that promises no
    identity (``program_share_key`` None) is priced every time."""
    import jax

    from keystone_tpu.obs import ledger
    from keystone_tpu.utils import precision
    from keystone_tpu.workflow.transformer import program_share_key, rebound

    shape_of = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    params = {
        name: jax.tree_util.tree_map(shape_of, getattr(t, name))
        for name in type(t).traced_attrs
    }
    kind = program_share_key(t)
    key = (kind, precision.matmul_mode(), str(arr_aval), str(mask_aval),
           str(jax.tree_util.tree_structure(params)), str(jax.tree_util.tree_leaves(params)))
    if kind is not None and key in _PRICED:
        return _PRICED[key], True
    with ledger.span("transformer.jit_mint", node=type(t).__name__, shared=False):
        cost = hlo_stage_cost(
            lambda p, a, m: rebound(t, p).apply_batch(a, mask=m),
            params, arr_aval, mask_aval,
        )
    seconds = cost["seconds_est"] if cost else None
    if kind is not None:
        while len(_PRICED) >= _PRICED_MAX:
            _PRICED.pop(next(iter(_PRICED)))
        _PRICED[key] = seconds
    return seconds, False


def device_hbm_budget(fraction: float = 0.5) -> int:
    """Cache budget from the REAL device's memory limit (bytes).

    Reads the backend's memory stats (HBM ``bytes_limit``); ``fraction``
    leaves headroom for solver state and XLA temporaries.  The CPU
    backend exposes no stats and budgets as a 16 GiB device would (CPU
    test meshes); a TPU that reports no limit raises instead of being
    guessed at.  ``KEYSTONE_HBM_BUDGET_BYTES`` overrides the device
    limit (before ``fraction``) — the auto-out-of-core tests use it to
    provoke the over-budget path on small data."""
    import os

    import jax

    env = os.environ.get("KEYSTONE_HBM_BUDGET_BYTES", "").strip()
    if env:
        try:
            return int(int(env) * fraction)
        except ValueError:
            logger.warning("KEYSTONE_HBM_BUDGET_BYTES=%r is not an int", env)
    # a LOCAL device: in a multi-process job jax.devices()[0] belongs to
    # process 0 and only its owner may ask it for memory stats
    dev = jax.local_devices()[0]
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if limit:
        return int(limit * fraction)
    if dev.platform == "tpu":
        raise RuntimeError(
            f"{dev} reports no memory limit (memory_stats={stats!r}); set "
            "KEYSTONE_HBM_BUDGET_BYTES rather than guess the device's size"
        )
    # the CPU backend reports no stats: budget as for a 16 GiB device
    return int((16 << 30) * fraction)


def pool_budget_bytes(fraction: float = 0.25) -> int:
    """The shared stage pool's default HBM budget
    (``workflow/stage_pool.py``): a quarter of the device limit by
    default — the pool holds transient per-flush featurized outputs
    NEXT TO every tenant's resident model weights and the serve
    batches, so it gets a deliberately smaller slice than the fit-time
    cache budget.  ``KEYSTONE_POOL_BUDGET_BYTES`` overrides outright
    (the eviction tests provoke pressure on small data with it); with
    the env unset, an installed ``PhysicalPlan``'s pinned
    ``pool_budget_bytes`` knob applies (the planner precedence — a
    deploy host with different headroom serves what was planned); with
    neither, the device-derived default."""
    import os

    env = os.environ.get("KEYSTONE_POOL_BUDGET_BYTES", "").strip()
    if env:
        try:
            return int(env)
        except ValueError:
            logger.warning("KEYSTONE_POOL_BUDGET_BYTES=%r is not an int", env)
    try:
        from keystone_tpu.planner import registry as _plans

        planned = _plans.planned_knob("pool_budget_bytes")
    except Exception:
        planned = None
    if planned is not None:
        return int(planned)
    return device_hbm_budget(fraction=fraction)


#: Footprint estimate of the LAST ProfilingAutoCacheRule pass, read by
#: Pipeline.fit's auto-out-of-core decision (workflow/pipeline.py §
#: _auto_out_of_core).  A module global rather than a graph annotation:
#: rule batches rebuild Graph instances, so an annotation would not
#: survive the fusion pass that runs after materialization.
last_footprint: dict = {}


class ProfilingAutoCacheRule(Rule):
    """Greedy cache placement under an HBM byte budget.

    ``static_cost=True`` prices nodes from compiled-HLO counters at full
    batch size (jitter-free) instead of extrapolated sampled wall time."""

    name = "ProfilingAutoCache"

    def __init__(
        self,
        budget_bytes: int = 8 << 30,
        sample_size: int = 64,
        static_cost: bool = False,
    ):
        self.budget_bytes = int(budget_bytes)
        self.sample_size = int(sample_size)
        self.static_cost = bool(static_cost)

    def apply(self, graph: G.Graph) -> G.Graph:
        from keystone_tpu.obs import ledger, metrics

        # a PREVIOUS fit's estimate must never leak into this fit's
        # auto-out-of-core decision (fallback/early-return paths would
        # otherwise leave it standing — review r5)
        last_footprint.clear()
        shared = [
            n
            for n in graph.topological_nodes()
            if isinstance(graph.operators.get(n), (G.TransformerOperator, G.GatherOperator))
            and needs_barrier(graph, n)
        ]
        if not shared:
            # nothing to place — a linear graph, or one whose fan-out
            # already stands behind a Cacher (a fitted pipeline's, in every
            # call): no slice of the input, no sampled run, no price
            ledger.annotate(
                "optimizer.rule", to_place=0, sampled=0, priced=0, price_hits=0,
                waited_seconds=0.0,
            )
            return graph
        import os

        # debug/A-B knob: profile every node like the pre-r4 rule did
        # (~60% of the north-star fit's wall-clock when rounds 1–5 measured
        # it; the shared-only pass is in `fit_optimize_s`, 0.032 s of a
        # 0.447 s ImageNet fit: my chip run, PR 33)
        profile_all = os.environ.get("KEYSTONE_CACHE_PROFILE_ALL", "") == "1"
        waited = ledger.waited_seconds()
        profiles = profile_graph(
            graph,
            self.sample_size,
            static_cost=self.static_cost,
            targets=None if profile_all else frozenset(shared),
        )
        ledger.annotate(
            "optimizer.rule",
            to_place=len(shared),
            sampled=1,
            priced=sum(p.price_hit is False for p in profiles.values()),
            price_hits=sum(p.price_hit is True for p in profiles.values()),
            # the sampled nodes' syncs: for the first, the wait for the upload
            waited_seconds=ledger.waited_seconds() - waited,
        )
        seconds = _comparable_seconds(profiles)
        # most compute saved per byte pinned, first
        shared.sort(
            key=lambda n: (
                -(seconds[n] / max(profiles[n].full_bytes, 1))
                if n in profiles
                else 0.0
            )
        )
        remaining = self.budget_bytes
        shared_bytes = 0
        pinned_bytes = 0
        demotions = 0
        for n in shared:
            prof = profiles.get(n)
            cost = prof.full_bytes if prof else 0
            shared_bytes += cost
            if cost <= remaining:
                remaining -= cost
                pinned_bytes += cost
                graph = place_barrier(graph, n)
            else:
                op = graph.operators[n]
                if isinstance(op, G.TransformerOperator):
                    demotions += 1
                    logger.info(
                        "over HBM budget: %s (%.1f MB) will recompute per consumer",
                        op.label(),
                        cost / 1e6,
                    )
                    # never mutate shared Operator instances (graphs share
                    # them persistent-structure style): flag a fresh copy
                    flagged = G.TransformerOperator(op.transformer)
                    flagged.no_memoize = True
                    graph = graph.set_operator(n, flagged)
        # record the pass's byte estimates for the auto-out-of-core
        # decision (fit-time pre-flight in workflow/pipeline.py)
        last_footprint.clear()
        last_footprint.update(
            {
                "shared_bytes": int(shared_bytes),
                "budget_bytes": int(self.budget_bytes),
            }
        )
        metrics.set_gauge("optimizer.pinned_bytes", float(pinned_bytes))
        if demotions:
            metrics.inc("optimizer.no_memoize_demotions", demotions)
        ledger.event(
            "optimizer.cache_placement",
            shared_nodes=len(shared),
            pinned_bytes=int(pinned_bytes),
            no_memoize_demotions=int(demotions),
            shared_bytes=int(shared_bytes),
            budget_bytes=int(self.budget_bytes),
        )
        return graph


def _comparable_seconds(profiles: Dict[G.NodeId, NodeProfile]) -> Dict[G.NodeId, float]:
    """Per-node cost in ONE unit.

    Roofline estimates (hlo_seconds) are idealized lower bounds, often far
    below wall time; ranking them directly against extrapolated wall times
    for nodes static pricing couldn't handle (gathers, host nodes) would
    systematically favor the wall-priced nodes.  Calibrate: median
    roofline/wall ratio over nodes that have both, applied to wall-only
    nodes, so every entry is in pseudo-roofline seconds."""
    ratios = [
        p.hlo_seconds / (p.seconds * p.scale)
        for p in profiles.values()
        if p.hlo_seconds is not None and p.seconds > 0
    ]
    calib = float(np.median(ratios)) if ratios else 1.0
    return {
        n: (
            p.hlo_seconds
            if p.hlo_seconds is not None
            else p.seconds * p.scale * calib
        )
        for n, p in profiles.items()
    }
