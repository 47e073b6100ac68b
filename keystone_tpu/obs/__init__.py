"""Unified structured observability: metrics registry + run ledger.

The reference leaned on Spark's event-log UI and a sampling profiler
(SURVEY.md §5); the TPU rebuild replaces both with two process-wide
primitives every subsystem reports through:

- :mod:`keystone_tpu.obs.metrics` — thread-safe counters, gauges, and
  histograms (``REGISTRY``), exported as JSON or Prometheus text.
  Always on (a bump is one lock + dict update); ``KEYSTONE_METRICS=0``
  disables recording entirely.
- :mod:`keystone_tpu.obs.ledger` — the one span primitive
  (``ledger.span``): always on, every span in a bounded in-memory ring
  (``recent_spans()``) and in any jax profiler session's host plane;
  and its opt-in export, a per-run JSONL span/event stream
  (Dapper-style) activated by ``KEYSTONE_OBS_DIR`` or
  ``ledger.start_run``, which also samples HBM/RSS watermarks at the
  end of root spans.  Long-lived runs rotate past
  ``KEYSTONE_OBS_MAX_BYTES`` into keep-N numbered segments.  The
  program's forced waits go through ``ledger.device_wait`` /
  ``ledger.waiting`` (a ``device.wait`` span each, also charged to the
  ``device.busy_seconds`` account); what runs on without the host — a
  host-to-device put — is handed to ``ledger.watch``, whose one daemon
  watcher thread closes a record (``dataset.transfer``) in the same
  three sinks when the array is ready, so that the calling thread never
  waits for an observation.
- :mod:`keystone_tpu.obs.recorder` — the serving path's flight
  recorder: a bounded in-memory ring of recent request traces with
  tail-based retention (shed/error/slow traces pinned), ON by default
  in ``serve()`` and independent of the ledger.  Read it live via
  ``GET /tracez`` / ``GET /requestz/<id>`` (``serve/http.py``) or
  render a dump with ``python tools/trace_report.py``.

Render a ledger with ``python tools/obs_report.py <run.jsonl>``.
"""

from keystone_tpu.obs import ledger, metrics  # noqa: F401
from keystone_tpu.obs.ledger import (  # noqa: F401
    RunLedger,
    event,
    span,
    start_run,
    stop_run,
)
from keystone_tpu.obs.metrics import (  # noqa: F401
    REGISTRY,
    MetricsRegistry,
    WindowedHistogram,
)
from keystone_tpu.obs.recorder import (  # noqa: F401
    FlightRecorder,
    new_request_id,
)
