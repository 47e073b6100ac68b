"""Profiler tracing + per-stage timing.

The reference has no dedicated tracer: it relies on (1) the optimizer's
sampling-based node profiling (AutoCacheRule) and (2) Spark's event-log
UI timeline, with apps logging coarse stage timings via the Logging trait
(SURVEY.md §5 "Tracing/profiling").  The TPU-era equivalents here:

- ``trace(logdir)`` / ``start_trace``/``stop_trace``: wrap
  ``jax.profiler`` to capture a device trace viewable in
  TensorBoard/Perfetto — the Spark-UI-timeline replacement.  The
  program's own spans (``obs/ledger.py § span``: ``pipeline.fit``,
  ``executor.stage``, ...) are in its host plane by themselves.
- ``annotate(name)``: one more named region
  (``jax.profiler.TraceAnnotation``) inside the trace.
- ``stage_timings(result)``: coarse per-node wall timings of a lazy
  pipeline result (the Logging-trait stage-timings replacement), using
  the executor's profiling mode (device-synchronized per node).

The HLO-cost-model side of profiling (the AutoCacheRule analogue proper)
lives in ``workflow/profiling.py``.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Optional

import jax


def start_trace(logdir: str) -> None:
    """Begin capturing a jax.profiler device trace into ``logdir``."""
    jax.profiler.start_trace(logdir)


def stop_trace() -> None:
    jax.profiler.stop_trace()


@contextlib.contextmanager
def trace(logdir: str, annotation: Optional[str] = None):
    """Capture a device trace around a block::

        with tracing.trace("/tmp/keystone-trace"):
            pipeline.fit()

    View with TensorBoard (tensorboard-plugin-profile) or Perfetto.
    """
    with jax.profiler.trace(logdir):
        if annotation is None:
            yield
        else:
            with jax.profiler.TraceAnnotation(annotation):
                yield


def annotate(name: str):
    """Named region inside an active trace (stages show by name)."""
    return jax.profiler.TraceAnnotation(name)


def step_annotation(step: int, name: str = "step"):
    """Mark one solver/pipeline iteration (StepTraceAnnotation)."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


def stage_timings(result) -> Dict[str, float]:
    """Per-node wall seconds for a lazy pipeline result.

    Runs the pipeline optimizer first (same as ``result.get()``), then
    executes the optimized graph in the executor's profiling mode (each
    node's output is device-synchronized before the clock stops, so times
    are real compute, not dispatch) — so the nodes reported are the ones
    that actually run, including optimizer-fused/inserted stages.  Keys
    are ``"{node_id}:{label}"`` — the node id disambiguates repeated ops.
    """
    from keystone_tpu.workflow.executor import GraphExecutor
    from keystone_tpu.workflow.pipeline import PipelineEnv

    g = PipelineEnv.get_optimizer().execute(result.graph)
    ex = GraphExecutor(g, profile=True)
    ex.execute(result.sink)
    out: Dict[str, float] = {}
    for node, seconds in ex.timings.items():
        label = g.operators[node].label() if node in g.operators else str(node)
        out[f"{node.id}:{label}"] = seconds
    return out
