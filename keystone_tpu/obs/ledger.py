"""Spans, and the run ledger that exports them: one span primitive for
the whole program, always on, with three sinks; and the one thread that
closes a span for what the calling thread must not wait for.

:class:`span` is the one way to time a region.  Every span

1. enters ``jax.profiler.TraceAnnotation(name)`` — a flag test while no
   profiler session runs; while one does (the benchmark's ``--trace 1``,
   ``utils/tracing.trace``, an operator's TensorBoard capture) the span is
   in the xplane's host plane on the clock of the device events, whoever
   started the session;
2. appends one closed-span :class:`SpanRecord` to a bounded process-wide
   ring (``recent_spans()``): ``(span_id, parent_id, root_id, name, t0_ns,
   dur_ns, attrs)`` from ``time.perf_counter_ns()``.  ``root_id`` is the id
   of the thread's outermost open span, so all spans of one fit or one
   scoring call share it.  No lock beyond the GIL, no JSON, no I/O, no
   syscall, no device call; attrs are scalars (or flat lists of scalars),
   never an array, so the ring pins no device memory;
3. only while a JSONL **run ledger** is active, writes ``span_start`` /
   ``span_end`` lines to it.

Observing never synchronises: nothing here makes the calling thread wait
for the device.  A wait that the program needs anyway is made through
:func:`device_wait` (or inside :func:`waiting`) and so has a name: a
``device.wait`` span, whose parent says whose wait it is.

What runs on without the host — a host-to-device put — gets its real
duration from :func:`watch`: the caller hands the device array over and goes
on; one daemon thread (the **watcher**, started by the first hand-off) waits
for it and closes a record that starts where the caller's span started and
ends when the array is ready.  Such a record reaches the same sinks as a
span: the ring, the JSONL ledger while one is active (a ``span_end`` line;
it has no ``span_start``, nothing ran on a thread of the program), and a
profiler session's host plane, on the watcher's own line (the
``TraceAnnotation`` is held around the watcher's wait, so it starts when the
watcher took the array up, not at the put).  It closes AFTER its parent and
its root: a reader selects it by ``parent_id`` / ``root_id`` / ``t0_ns``,
never by its place in the ring.  Its end is the watcher's wake-up: some
40 µs after the array is ready while the calling thread waits or sleeps, up
to the interpreter's switch interval (5 ms) after it while the calling
thread runs bytecode without releasing the GIL (CPU micro-measurement,
PR 34).  The watcher holds an array only until it is ready; at exit it is
told to stop and joined for a bounded time.

One **run** = one JSONL file ``run_<run_id>.jsonl`` under the ledger
directory.  Every line is one event::

    {"ts": <unix seconds>, "run_id": "...", "seq": <monotonic int>,
     "kind": "run_start"|"span_start"|"span_end"|"event"|"metrics",
     "name": "...", "span": <id>, "parent": <id|null>, "attrs": {...}}

``span_end`` lines additionally carry ``"seconds"`` (wall duration) and
the final attrs (spans may accumulate attrs while open — the executor
records attempt counts this way).  The schema is flat on purpose:
``tools/obs_report.py`` and ad-hoc ``jq`` both read it without a parser
library.

The JSONL export is opt-in:

- ``KEYSTONE_OBS_DIR=<dir>`` activates a process-wide ledger lazily (the
  first ``span``/``event`` call creates it, ``atexit`` closes it) — the
  zero-code route, mirroring ``KEYSTONE_FAULTS``.
- ``start_run(dir)`` / ``stop_run()`` scope a ledger explicitly
  (tools and tests use this; an explicit run wins over the env one).

With neither, no file is written and ``active()`` is None: the ring is
not "active".  While a ledger is active the end of every ROOT span (and
``close()``) samples the device HBM watermark (``memory_stats()``) plus
host max-RSS into the metrics registry (gauge ``hbm.bytes_in_use`` /
``host.max_rss_bytes``).

Solver telemetry rides :func:`solver_epoch` — host loops call it
directly; jitted solver scans reach it through ``jax.debug.callback``
(see ``models/lbfgs.py`` et al., gated by a static ``obs`` flag so the
compiled program is byte-identical when no ledger is active).
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import itertools
import json
import os
import queue
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

from keystone_tpu.obs import metrics

ENV_DIR = "KEYSTONE_OBS_DIR"
#: size cap (bytes) per ledger segment before rotation; unset = no cap.
#: A long-lived ``serve --watch`` process with KEYSTONE_OBS_DIR set
#: appends forever — without a cap it eventually fills the disk.
ENV_MAX_BYTES = "KEYSTONE_OBS_MAX_BYTES"
#: rotated segments kept per run (oldest pruned); default 8
ENV_KEEP_SEGMENTS = "KEYSTONE_OBS_KEEP_SEGMENTS"

DEFAULT_KEEP_SEGMENTS = 8

#: Registered span/event attribute-key vocabulary.  ``tools/lint.py``'s
#: ``attr`` rule parses this set from the AST (the fault-site rule's
#: discipline — no package import) and requires every literal keyword
#: at a ``ledger.span(...)``/``ledger.event(...)``/flight-recorder
#: emit site to be a snake_case member: a typo'd key otherwise vanishes
#: silently into the JSONL/ring stream and every downstream reader
#: (obs_report, trace_report, jq recipes) quietly reads nothing.  Add
#: a key here when introducing a genuinely new attribute; a one-off
#: escape is a trailing ``# lint: allow-attr``.
ATTR_VOCABULARY = {
    "action",
    "apply_seconds",
    "attempt",
    "attempts",
    "batch",
    "block_size",
    "blocks",
    "branches",
    "bucket",
    "budget_bytes",
    "budget_seconds",
    "bytes",
    "cache_hits",
    "canary_fraction",
    "checkpoint_save_seconds",
    "chunk_seconds",
    "chunks",
    "d",
    "degraded",
    "depth",
    "dtype",
    "epoch",
    "epoch_seconds",
    "error",
    "factor_cache",
    "factor_cache_bytes",
    "failed_attempt_seconds",
    "filters",
    "from_state",
    "from_replica",
    "from_version",
    "grad_norm",
    "gram_panels",
    "held_bytes",
    "host",
    "instances",
    "it",
    "key",
    "knob",
    "late",
    "leader",
    "n",
    "no_memoize_demotions",
    "node",
    "node_id",
    "nodes",
    "objective",
    "occupancy",
    "outcome",
    "patches",
    "path",
    "pause_seconds",
    "pid",
    "pinned_bytes",
    "poisons",
    "predicted_seconds",
    "price_hits",
    "priced",
    "prime_seconds",
    "queue_depth",
    "queue_wait_seconds",
    "reason",
    "replica",
    "replicas",
    "request_id",
    "request_ids",
    "restarts",
    "retries",
    "refused",
    "rows",
    "rule",
    "sampled",
    "seconds",
    "shape",
    "shared",
    "shared_bytes",
    "shared_nodes",
    "shared_stages",
    "sick",
    "sig_by_recipe",
    "sig_bytes_hashed",
    "site",
    "solver",
    "source",
    "stages",
    "stats",
    "substitute",
    "tag",
    "tenant",
    "tenants",
    "to_place",
    "to_state",
    "to_replica",
    "to_version",
    "verdict",
    "version",
    "waited_seconds",
    "wire",
    "worker",
    "worker_spans",
    "workers",
}

#: per-process run discriminator: time.time() alone has 1-second
#: resolution, and two runs started within the same second would
#: silently append into the same JSONL file
_RUN_COUNTER = itertools.count()


def _env_int(name: str) -> Optional[int]:
    """Non-negative int from the environment, or None (unset, empty,
    or non-numeric — warned-free: the ledger must never fail to open
    over a malformed knob)."""
    raw = os.environ.get(name)
    if not raw:
        return None
    try:
        v = int(raw)
    except ValueError:
        return None
    return v if v >= 0 else None


def _json_safe(v):
    """Best-effort JSON coercion: numpy scalars/arrays and exotic
    objects must never kill the instrumented path."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, dict):
        return {str(k): _json_safe(x) for k, x in v.items()}
    if isinstance(v, (list, tuple, set, frozenset)):
        return [_json_safe(x) for x in v]
    item = getattr(v, "item", None)  # numpy scalar / 0-d array
    if callable(item):
        try:
            return _json_safe(item())
        except Exception:
            pass
    tolist = getattr(v, "tolist", None)
    if callable(tolist):
        try:
            return _json_safe(tolist())
        except Exception:
            pass
    return str(v)


def _sample_memory() -> Dict[str, float]:
    """Device HBM in-use bytes (when the backend exposes memory_stats)
    plus host peak RSS.  Best-effort: CPU test meshes have no HBM stats
    and must not error."""
    out: Dict[str, float] = {}
    try:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        used = stats.get("bytes_in_use")
        if used is not None:
            out["hbm_bytes_in_use"] = float(used)
            metrics.gauge_max("hbm.bytes_in_use", float(used))
            peak = stats.get("peak_bytes_in_use")
            if peak is not None:
                metrics.gauge_max("hbm.peak_bytes_in_use", float(peak))
    except Exception:
        pass
    try:
        import resource

        rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        out["host_max_rss_bytes"] = float(rss_kb) * 1024.0
        metrics.gauge_max("host.max_rss_bytes", float(rss_kb) * 1024.0)
    except Exception:
        pass
    return out


#: closed spans the process keeps in memory (oldest dropped first)
RING_SIZE = 32768


class SpanRecord(NamedTuple):
    """One closed span, as the ring holds it."""

    span_id: int
    parent_id: Optional[int]
    root_id: int
    name: str
    t0_ns: int
    dur_ns: int
    attrs: Dict[str, Any]


_RING: collections.deque = collections.deque(maxlen=RING_SIZE)
_SPAN_IDS = itertools.count(1)  # next() is atomic under the GIL
_TLS = threading.local()  # .stack: this thread's open spans, outermost first
_SCALARS = (str, int, float, bool, type(None))


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def _check_attrs(attrs: Dict[str, Any]) -> None:
    """Span attrs are scalars, or flat lists of scalars (``request_ids``):
    the ring outlives the span, so an array here would pin its device
    buffer — and reading one would wait for the device."""
    for key, v in attrs.items():
        if isinstance(v, _SCALARS) or (
            isinstance(v, (list, tuple)) and all(isinstance(x, _SCALARS) for x in v)
        ):
            continue
        raise TypeError(
            f"span attribute {key!r} is a {type(v).__name__}; spans hold "
            "str, int, float, bool, None or flat lists of those (pass a "
            "shape or a byte count, never an array)"
        )


class span:
    """Timed nested region: ``with ledger.span("executor.stage", node=...)
    as sp`` — always on (see the module docstring for its three sinks).
    ``sp.set(**attrs)`` merges attrs reported at close."""

    __slots__ = (
        "name", "attrs", "span_id", "parent_id", "root_id", "t0_ns", "dur_ns", "_ann",
        "_led",
    )

    def __init__(self, name: str, **attrs):
        _check_attrs(attrs)
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> None:
        _check_attrs(attrs)
        self.attrs.update(attrs)

    def __enter__(self) -> "span":
        st = _stack()
        self.span_id = next(_SPAN_IDS)
        if st:
            self.parent_id, self.root_id = st[-1].span_id, st[0].span_id
        else:
            self.parent_id, self.root_id = None, self.span_id
        self._led = led = active()
        if led is not None:
            led._emit(
                "span_start", self.name, span=self.span_id, parent=self.parent_id,
                attrs=self.attrs,
            )
        st.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type=None, exc=None, tb=None) -> None:
        self.dur_ns = dur_ns = time.perf_counter_ns() - self.t0_ns
        self._ann.__exit__(exc_type, exc, tb)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        _RING.append(tuple.__new__(SpanRecord, (
            self.span_id, self.parent_id, self.root_id, self.name, self.t0_ns,
            dur_ns, self.attrs,
        )))
        led = self._led
        if led is not None:
            end_attrs = self.attrs
            if self.parent_id is None:  # a root span ends: one memory sample
                end_attrs = {**end_attrs, **_sample_memory()}
            led._emit(
                "span_end", self.name, span=self.span_id, parent=self.parent_id,
                attrs=end_attrs, seconds=dur_ns / 1e9,
            )


def recent_spans() -> List[SpanRecord]:
    """A copy of the ring: the process's last ``RING_SIZE`` closed spans,
    in the order they closed (a child before its parent)."""
    return list(_RING)


def self_seconds(records) -> Dict[int, float]:
    """``{span_id: seconds}`` — each span's duration less the part of it
    that its child spans (among ``records``) cover."""
    children: Dict[int, list] = {}
    for r in records:
        if r.parent_id is not None:
            children.setdefault(r.parent_id, []).append(r)
    out = {}
    for r in records:
        lo, hi = r.t0_ns, r.t0_ns + r.dur_ns
        covered, cursor = 0, lo
        for c in sorted(children.get(r.span_id, ()), key=lambda c: c.t0_ns):
            c_lo, c_hi = max(c.t0_ns, cursor), min(c.t0_ns + c.dur_ns, hi)
            if c_hi > c_lo:
                covered += c_hi - c_lo
                cursor = c_hi
        out[r.span_id] = (r.dur_ns - covered) / 1e9
    return out


class RunLedger:
    """Append-only JSONL event stream for one run.

    **Rotation** — a long-lived process (``serve --watch`` under
    ``KEYSTONE_OBS_DIR``) appends to one run forever, so the active file
    carries a size cap: past ``max_bytes`` it is renamed to a numbered
    segment (``run_<id>.jsonl.000001``, monotonically increasing) and a
    fresh active file continues the run; only the newest
    ``keep_segments`` segments are kept, oldest pruned.  ``self.path``
    always names the ACTIVE file — readers of a live run see the newest
    tail, and each rotation bumps the ``obs.ledger_rotations`` counter.
    Defaults come from ``KEYSTONE_OBS_MAX_BYTES`` (unset = unbounded,
    the historical behavior) and ``KEYSTONE_OBS_KEEP_SEGMENTS``."""

    def __init__(
        self,
        directory: str,
        run_id: Optional[str] = None,
        max_bytes: Optional[int] = None,
        keep_segments: Optional[int] = None,
    ):
        os.makedirs(directory, exist_ok=True)
        if run_id is None:
            run_id = (
                f"{int(time.time()):x}-{os.getpid()}-{next(_RUN_COUNTER)}"
            )
        self.run_id = run_id
        self.directory = directory
        self.path = os.path.join(directory, f"run_{run_id}.jsonl")
        if max_bytes is None:
            max_bytes = _env_int(ENV_MAX_BYTES)
        self.max_bytes = max_bytes if max_bytes and max_bytes > 0 else None
        if keep_segments is None:
            keep_segments = _env_int(ENV_KEEP_SEGMENTS) or DEFAULT_KEEP_SEGMENTS
        self.keep_segments = max(1, int(keep_segments))
        # resume rotation state from disk: reopening an EXISTING run id
        # (a restarted serve --watch process) must count the bytes
        # already in the active file and continue segment numbering
        # past the highest kept suffix — starting both at zero would
        # let the active file grow to existing+max_bytes and the first
        # rotation os.replace() over (destroy) a retained segment
        try:
            self._bytes = os.path.getsize(self.path)
        except OSError:
            self._bytes = 0
        self._segment = 0
        prefix = f"run_{run_id}.jsonl."
        try:
            for name in os.listdir(directory):
                if name.startswith(prefix) and name[len(prefix):].isdigit():
                    self._segment = max(self._segment, int(name[len(prefix):]))
        except OSError:
            pass
        self._lock = threading.RLock()
        self._seq = 0
        self._f = open(self.path, "a", encoding="utf-8")
        self._closed = False
        self._emit("run_start", "run", attrs={"pid": os.getpid()})

    # ------------------------------------------------------------ emit
    def _emit(
        self,
        kind: str,
        name: str,
        span: Optional[int] = None,
        parent: Optional[int] = None,
        attrs: Optional[Dict[str, Any]] = None,
        **extra,
    ) -> None:
        rec = {
            "ts": time.time(),
            "run_id": self.run_id,
            "kind": kind,
            "name": name,
        }
        if span is not None:
            rec["span"] = span
        if parent is not None:
            rec["parent"] = parent
        if attrs:
            rec["attrs"] = _json_safe(attrs)
        rec.update(extra)
        with self._lock:
            if self._closed:
                return
            self._seq += 1
            rec["seq"] = self._seq
            line = json.dumps(rec) + "\n"
            self._f.write(line)
            self._f.flush()
            self._bytes += len(line)
            if self.max_bytes is not None and self._bytes >= self.max_bytes:
                self._rotate_locked()

    def _rotate_locked(self) -> None:
        """Must hold self._lock.  Seal the active file as the next
        numbered segment, reopen a fresh active file, prune segments
        past ``keep_segments`` (oldest first)."""
        self._f.close()
        self._segment += 1
        try:
            os.replace(self.path, f"{self.path}.{self._segment:06d}")
        except OSError:
            # the active file vanished under us (operator cleanup): a
            # rotation failure must not kill the instrumented path
            pass
        self._f = open(self.path, "a", encoding="utf-8")
        self._bytes = 0
        prefix = os.path.basename(self.path) + "."
        segments = []
        try:
            for name in os.listdir(self.directory):
                if name.startswith(prefix) and name[len(prefix):].isdigit():
                    segments.append((int(name[len(prefix):]), name))
        except OSError:
            segments = []
        for _, name in sorted(segments)[: -self.keep_segments]:
            try:
                os.remove(os.path.join(self.directory, name))
            except OSError:
                pass
        metrics.inc("obs.ledger_rotations")

    def event(self, name: str, **attrs) -> None:
        st = _stack()
        self._emit(
            "event",
            name,
            parent=st[-1].span_id if st else None,
            attrs=attrs,
        )

    def metrics_snapshot(self) -> None:
        """Embed the current registry snapshot as one ``metrics`` line
        (the report's source for I/O totals and watermarks)."""
        self._emit("metrics", "metrics.snapshot", attrs=metrics.snapshot())

    def close(self, snapshot: bool = True) -> None:
        if self._closed:
            return
        drain_watches(WATCHER_EXIT_SECONDS)  # records still open belong to this run
        _sample_memory()
        if snapshot:
            self.metrics_snapshot()
        self._emit("run_end", "run")
        with self._lock:
            self._closed = True
            self._f.close()


# ----------------------------------------------------------- activation

_LOCK = threading.Lock()
_ACTIVE: Optional[RunLedger] = None  # start_run / attach
_ENV_LEDGER: Optional[RunLedger] = None  # lazily created from KEYSTONE_OBS_DIR


def active() -> Optional[RunLedger]:
    """The current JSONL ledger, or None (the default: spans stay in memory).  An explicit
    ``start_run``/``attach`` ledger wins; otherwise ``KEYSTONE_OBS_DIR``
    lazily creates one process-wide run."""
    if _ACTIVE is not None:
        return _ACTIVE
    directory = os.environ.get(ENV_DIR)
    if not directory:
        return None
    global _ENV_LEDGER
    with _LOCK:
        if _ENV_LEDGER is None or (
            _ENV_LEDGER._closed or _ENV_LEDGER.directory != directory
        ):
            _ENV_LEDGER = RunLedger(directory)
            atexit.register(_ENV_LEDGER.close)
    return _ENV_LEDGER


def start_run(directory: str, run_id: Optional[str] = None) -> RunLedger:
    """Explicitly open (and activate) a run ledger; pair with
    :func:`stop_run`."""
    global _ACTIVE
    led = RunLedger(directory, run_id=run_id)
    with _LOCK:
        _ACTIVE = led
    return led


def attach(ledger: Optional[RunLedger]) -> None:
    """Install an existing ledger as the active one (None detaches)."""
    global _ACTIVE
    with _LOCK:
        _ACTIVE = ledger


def stop_run(snapshot: bool = True) -> None:
    """Close and detach the explicitly-activated ledger."""
    global _ACTIVE
    with _LOCK:
        led, _ACTIVE = _ACTIVE, None
    if led is not None:
        led.close(snapshot=snapshot)


# ------------------------------------------------------------- frontends


def event(name: str, **attrs) -> None:
    """Record one event on the active ledger; no-op when inert."""
    led = active()
    if led is not None:
        led.event(name, **attrs)


def annotate(name: str, **attrs) -> None:
    """Merge ``attrs`` into this thread's innermost OPEN span called
    ``name`` (``sp.set`` for code that runs inside a span another module
    opened: an optimizer rule saying how its pass went on the
    ``optimizer.rule`` span around it).  No such span open: nothing."""
    for sp in reversed(_stack()):
        if sp.name == name:
            sp.set(**attrs)
            return


def capture_context():
    """Snapshot the calling thread's open-span stack (opaque token).
    The span stack is thread-local, so work handed to a worker thread —
    ``utils/guard.run_with_deadline`` watchdogs are the in-repo case —
    would otherwise record spans/events with no parent and a root of
    their own.  Capture on the calling thread, :func:`restore_context`
    inside the worker, and the worker's spans nest (and share the root)
    where the caller's would have."""
    return list(_stack())


def restore_context(token) -> None:
    """Install a :func:`capture_context` snapshot on the CURRENT thread
    (a copy — the originating thread's stack is never shared or
    mutated).  No-op for a None token."""
    if token is not None:
        _TLS.stack = list(token)


@contextlib.contextmanager
def waiting():
    """The program's one forced wait: wrap the call that stands still until
    the device has results the program needs NOW (a ``Cacher``'s or the
    sampling pass's sync, flow control of a dispatch queue, a checkpoint
    gather).  The wait is a ``device.wait`` span — its parent says whose it
    is: an ``executor.stage`` under ``pipeline.fit`` / ``pipeline.apply`` is
    a node's sync, one under ``optimizer.rule`` the sampling pass's, a
    ``solver.fit`` the solver's flow control — and its seconds go to the
    ``device.busy_seconds`` account (a host-side measure: seconds the host
    was BLOCKED on device results; with ``blockstore.stage_wait_seconds``
    it is ``tools/obs_report.py``'s ``dataflow`` summary) and to this
    thread's running total (:func:`waited_seconds`)."""
    sp = span("device.wait")
    try:
        with sp:
            yield
    finally:
        seconds = sp.dur_ns / 1e9
        metrics.observe("device.busy_seconds", seconds)
        _TLS.waited = getattr(_TLS, "waited", 0.0) + seconds


def device_wait(x):
    """Wait for ``x`` (any pytree of device values) inside :func:`waiting`,
    and return it."""
    import jax

    with waiting():
        jax.block_until_ready(x)
    return x


def waited_seconds() -> float:
    """Seconds the calling thread has stood in ``device.wait`` spans so far
    (a running total: take the difference around a region)."""
    return getattr(_TLS, "waited", 0.0)


# -------------------------------------------------------------- the watcher

_WATCHED: "queue.SimpleQueue" = queue.SimpleQueue()
_WATCHER: Optional[threading.Thread] = None
#: seconds the exit hook gives the watcher to close what is pending
WATCHER_EXIT_SECONDS = 5.0


def watch(name: str, array, parent: span, **attrs) -> None:
    """Close a record ``name`` when ``array`` is ready, without the caller
    waiting: it starts at ``parent``'s start (the put's), is ``parent``'s
    child and shares its root.  ``array`` is one device array (a sharded one
    is ready when every shard is); the watcher drops it as soon as it is
    ready, and a deleted or donated one ends its record with
    ``outcome="deleted"``."""
    _check_attrs(attrs)
    global _WATCHER
    if _WATCHER is None or not _WATCHER.is_alive():
        with _LOCK:
            if _WATCHER is None or not _WATCHER.is_alive():
                _WATCHER = threading.Thread(
                    target=_watch_loop, name="keystone-obs-watcher", daemon=True
                )
                _WATCHER.start()
    _WATCHED.put((name, array, parent.span_id, parent.root_id, parent.t0_ns, attrs))


def _watch_loop() -> None:
    while True:
        item = _WATCHED.get()
        if item is None:
            return
        if isinstance(item, threading.Event):  # drain_watches: all before it closed
            item.set()
            continue
        name, array, parent_id, root_id, t0_ns, attrs = item
        del item
        with TraceAnnotation(name):
            try:
                array.block_until_ready()
            except Exception:  # deleted or donated under the watcher, or the put failed
                attrs = {**attrs, "outcome": "deleted" if array.is_deleted() else "error"}
            end_ns = time.perf_counter_ns()
        del array
        span_id = next(_SPAN_IDS)
        _RING.append(SpanRecord(span_id, parent_id, root_id, name, t0_ns, end_ns - t0_ns, attrs))
        led = active()
        if led is not None:
            led._emit(
                "span_end", name, span=span_id, parent=parent_id, attrs=attrs,
                seconds=(end_ns - t0_ns) / 1e9,
            )


def drain_watches(timeout: Optional[float] = None) -> bool:
    """Wait until the watcher has closed every record handed to it before
    this call; False when ``timeout`` seconds did not suffice.  For readers
    that need a finished window (tests, a report at the end of a run): the
    program itself never calls it on a timed path."""
    if _WATCHER is None or not _WATCHER.is_alive():
        return True
    done = threading.Event()
    _WATCHED.put(done)
    return done.wait(timeout)


@atexit.register
def _stop_watcher() -> None:
    """Tell the watcher to stop and give it ``WATCHER_EXIT_SECONDS`` to
    close what is pending: a daemon thread still inside the runtime's wait
    when the interpreter finalises is the one way this could end a process
    badly."""
    watcher = _WATCHER
    if watcher is not None and watcher.is_alive():
        _WATCHED.put(None)
        watcher.join(WATCHER_EXIT_SECONDS)


def solver_obs() -> bool:
    """Should solvers trace per-epoch telemetry?  True only while a JSONL
    ledger is active (the in-memory ring is not "active").  Resolved at
    trace time and threaded as a STATIC jit argument, so the compiled
    program is exactly the pre-obs one when this is False."""
    return active() is not None


def solver_epoch(solver: str, **series) -> None:
    """One solver convergence point (epoch/objective/grad-norm/...).
    Host loops call this directly; jitted scans reach it via
    :func:`solver_callback`."""
    led = active()
    if led is not None:
        led.event("solver.epoch", solver=solver, **series)


def fold_stage_spans(ledger_path: str) -> Dict[str, dict]:
    """Aggregate a ledger's ``executor.stage`` span_end lines into
    ``{key: {seconds, count, retries, failed_attempt_seconds, chunks}}``
    (``chunks``: the applies the node's stages were made of, summed — equal
    to ``count`` where every apply was whole; 0 for stages that apply no
    transformer to a device array).

    The ONE reader of this part of the schema — ``tools/obs_report.py``
    and ``workflow/viz.ledger_overlay`` both fold through here, so a
    schema change cannot silently drift them apart.  Keys are
    ``"{node_id}:{label}"`` when the span recorded a node id (matching
    the ``utils/tracing.stage_timings`` convention — distinct nodes
    sharing a label stay distinct), else the bare label."""
    out: Dict[str, dict] = {}
    with open(ledger_path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                e = json.loads(line)
            except json.JSONDecodeError:
                continue  # a torn final line must not hide the run
            if e.get("kind") != "span_end" or e.get("name") != "executor.stage":
                continue
            attrs = e.get("attrs") or {}
            label = str(attrs.get("node", "?"))
            nid = attrs.get("node_id")
            key = f"{nid}:{label}" if nid is not None else label
            st = out.setdefault(
                key,
                {
                    "label": label,
                    "seconds": 0.0,
                    "count": 0,
                    "retries": 0,
                    "failed_attempt_seconds": 0.0,
                    "chunks": 0,
                },
            )
            st["seconds"] += float(e.get("seconds") or 0.0)
            st["count"] += 1
            st["retries"] += int(attrs.get("retries") or 0)
            st["failed_attempt_seconds"] += float(
                attrs.get("failed_attempt_seconds") or 0.0
            )
            st["chunks"] += int(attrs.get("chunks") or 0)
    return out


def solver_callback(solver: str, *names):
    """A ``jax.debug.callback``-shaped emitter: positional traced values
    are matched to ``names``.  Values arrive as numpy arrays; scalar
    coercion happens in the JSON layer."""

    def cb(*vals):
        led = active()
        if led is None:
            return
        led.event(
            "solver.epoch",
            solver=solver,
            **{n: v for n, v in zip(names, vals)},
        )

    return cb
