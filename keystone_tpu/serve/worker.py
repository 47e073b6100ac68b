"""One-replica serve loop: the worker-process side of the process fleet.

``worker_main`` is the spawn target of
:class:`~keystone_tpu.serve.procfleet.WorkerHandle`: it loads the
deploy payload (fitted pipeline + optional AOT artifact bundle) from
the path the router staged, builds the frozen applier, installs the
pre-lowered bucket programs, primes every padding bucket (the PR-11
ladder — artifact, persistent compile cache, fresh compile), beats a
shared-memory heartbeat, and then serves ``apply`` frames until the
router says ``bye`` (or the control pipe dies with the router).

The worker owns the accelerator runtime for its replica: the parent
router process never imports a device backend on the hot path, so N
workers compute on N cores/devices in true parallel — the whole point
of the promotion (ROADMAP 4: stop measuring the GIL).

Protocol (see ``serve/wire.py``; strict request/response, one in
flight):

- ``{"op": "apply", "ref": <slab ref>, "n": k, "deadline_s": t|null}``
  → ``{"op": "result", "ref": <slab ref>,
  "seconds": dt}`` — the input reference names a slab in the ROUTER's
  pool; the result reference names one in THIS worker's response pool
  (each side owns and unlinks its own slabs).
- apply failures answer ``{"op": "error", "kind", "etype", "emsg"}``
  where ``kind`` preserves the repo's error classification across the
  process boundary — ``deadline`` (a shed-typed
  ``guard.DeadlineExceeded``), ``oserror`` (infrastructure),
  ``memory``, or ``content`` (the bisectable family) — so poison
  isolation and breaker charging behave exactly as they do in-process.
- ``{"op": "ping"}`` → ``{"op": "pong", "pid": ...}``;
  ``{"op": "bye"}`` ends the loop.

Spawn discipline: workers are ALWAYS started via the ``spawn`` start
method (``procfleet`` enforces it) — a forked JAX runtime inherits
locked mutexes and wedges on first dispatch; ``tools/lint.py``'s
``proc-spawn`` rule keeps ``multiprocessing`` use fenced into these
modules.
"""

from __future__ import annotations

import logging
import pickle
import threading
import time

import contextlib

import numpy as np

from keystone_tpu.serve import wire
from keystone_tpu.serve.telemetry import WorkerTelemetry

logger = logging.getLogger(__name__)

#: how often the worker refreshes its shared heartbeat slot.
#: ``time.monotonic`` is CLOCK_MONOTONIC on Linux — one system-wide
#: clock, comparable across the router and its workers.
HEARTBEAT_INTERVAL_S = 0.25


def _classify(exc: BaseException) -> str:
    """The cross-process error classification (the ``_poison_suspect``
    contract from serve/service.py, serialized): infrastructure rides
    ``oserror``, capacity rides ``memory``, shed rides ``deadline``,
    and everything else is ``content`` — the bisectable family."""
    from keystone_tpu.utils import guard

    if isinstance(exc, wire.PayloadTooLarge):
        # an oversized RESULT (the request fit; the output overflowed
        # the slab cap): relayed as its own kind so the router raises
        # the same typed PayloadTooLarge a request-side overflow gets —
        # NOT a generic content error masquerading as model poison
        return "too_large"
    if isinstance(exc, guard.DeadlineExceeded):
        return "deadline"
    if isinstance(exc, guard.CircuitOpenError):
        return "circuit"
    if isinstance(exc, MemoryError):
        return "memory"
    if isinstance(exc, OSError):
        return "oserror"
    return "content"


def _load_payload(path: str) -> dict:
    with open(path, "rb") as f:
        return pickle.load(f)


def _build_applier(payload: dict):
    """Freeze the staged pipeline and install its artifact bundle (a
    failed install degrades to the compile ladder, mirroring
    ``ReplicaPool._install_artifacts`` — a worker must come up serving
    even off a damaged bundle)."""
    from keystone_tpu.serve.fleet import _as_applier
    from keystone_tpu.utils.hashing import pipeline_fingerprint
    from keystone_tpu.workflow.pipeline import FrozenApplier

    pipeline = payload["pipeline"]
    applier = _as_applier(pipeline)
    artifacts = payload.get("artifacts")
    installed = 0
    if artifacts:
        try:
            if isinstance(pipeline, FrozenApplier):
                sig = pipeline.fingerprint()
            else:
                sig = pipeline_fingerprint(pipeline)
            installed = applier.install_artifacts(
                artifacts, device=None, signature=sig, program_cache={}
            )
        except Exception as e:
            logger.warning(
                "worker artifact install failed (%s: %s); compiling",
                type(e).__name__,
                e,
            )
    plan = getattr(applier, "plan", None)
    if plan is not None:
        # artifact installs re-install the shipped plan themselves; an
        # artifact-less (or rejected-bundle) spawn still carries the plan
        # in the pickled applier — install it so this worker process
        # serves the planned physical configuration
        try:
            from keystone_tpu import planner

            if planner.current_plan() is None:
                planner.install_plan(plan, source="spawn")
        except Exception as e:
            logger.warning("worker plan install failed (%s)", e)
    return applier, installed


def _prime(applier, buckets, item_shape, dtype) -> int:
    """Warm every padding bucket's program — exactly the shapes the
    router will dispatch.  Degradation-declaring pipelines also warm
    the deadline-carrying executor walk (the same double-prime the
    in-process service does)."""
    from keystone_tpu.utils import guard
    from keystone_tpu.workflow.dataset import Dataset

    if not buckets or item_shape is None:
        return 0
    n = 0
    for b in buckets:
        zeros = np.zeros((int(b),) + tuple(item_shape), np.dtype(dtype))
        applier(Dataset(zeros, n=int(b)))
        n += 1
        if getattr(applier, "_degradable", False) and getattr(
            applier, "installed_buckets", lambda: 0
        )():
            applier(
                Dataset(zeros, n=int(b)),
                deadline=guard.Deadline.after(86400.0),
            )
            n += 1
    return n


def build_from_payload(payload: dict, spec: dict, tel=None):
    """The full cold-start ladder shared by BOTH worker transports (the
    pipe-spawned process worker and the TCP worker of ``serve/net.py``):
    freeze the pipeline, install AOT artifacts (degrading to the
    compile ladder on a damaged bundle), and prime every padding
    bucket.  Returns ``(applier, installed, primed)``.  ``tel``: a
    :class:`~keystone_tpu.serve.telemetry.WorkerTelemetry` that records
    ``worker.build`` / ``worker.prime`` spans for shipping on the ready
    frame — cold-start time becomes visible from the router's ops
    surface, not just worker logs."""
    span = tel.span if tel is not None else (
        lambda _name, **_a: contextlib.nullcontext()
    )
    with span("worker.build"):
        applier, installed = _build_applier(payload)
    with span("worker.prime"):
        primed = _prime(
            applier,
            spec.get("buckets"),
            spec.get("item_shape"),
            spec.get("dtype") or "float32",
        )
    return applier, installed, primed


#: public name for the cross-process error classification (the TCP worker
#: relays its apply failures through the same classifier)
classify_error = _classify


def _artifact_keys(applier) -> list:
    """The (shape, dtype) keys of installed AOT bucket programs — the
    ready frame ships them so the router's prime loop can label its
    ``serve.prime_seconds{source=}`` samples honestly for a remote
    replica."""
    progs = getattr(applier, "_bucket_programs", None) or {}
    out = []
    for key in progs:
        try:
            shape, dtype = key
            out.append([list(shape), np.dtype(dtype).str])
        except (TypeError, ValueError):
            continue
    return out


def worker_main(conn, spec: dict) -> None:
    """The worker process entry point (spawned by ``WorkerHandle``).

    ``conn``: the worker end of the control pipe.  ``spec``: plain-data
    worker configuration — ``name``/``index`` (labels), ``payload_path``
    (the staged deploy payload), ``buckets``/``item_shape``/``dtype``
    (the prime set; item_shape None skips priming), ``heartbeat`` (a
    shared ``multiprocessing.Value('d')`` this loop refreshes).
    """
    import os

    from keystone_tpu.utils import guard
    from keystone_tpu.workflow.dataset import Dataset

    hb = spec.get("heartbeat")
    stop_beating = threading.Event()

    def beat_loop():
        while not stop_beating.wait(HEARTBEAT_INTERVAL_S):
            if hb is not None:
                hb.value = time.monotonic()

    if hb is not None:
        hb.value = time.monotonic()
        threading.Thread(target=beat_loop, daemon=True, name="hb").start()

    # the response pool honors the SAME slab cap as the router's
    # request pool: a result wider than the default cap must not turn
    # into a bisectable "content" error when the operator raised the
    # cap for exactly that workload
    pool = wire.SlabPool(
        prefix=f"{spec.get('name', 'serve')}-w",
        max_slab_bytes=int(
            spec.get("max_slab_bytes") or wire.DEFAULT_MAX_SLAB_BYTES
        ),
    )
    attacher = wire.SlabAttacher()
    #: worker-side telemetry: load/prime/attach/apply spans plus
    #: metrics-registry deltas, shipped by piggybacking on the frames
    #: this loop already answers (ready, result, error) — bounded,
    #: dropped-not-queued, and invisible to an old router (optional
    #: body key)
    tel = WorkerTelemetry()
    t0 = time.monotonic()
    try:
        with tel.span("worker.load"):
            payload = _load_payload(spec["payload_path"])
        applier, installed, primed = build_from_payload(payload, spec, tel=tel)
    except BaseException as e:
        try:
            wire.send_frame(
                conn,
                {
                    "op": "fatal",
                    "etype": type(e).__name__,
                    "emsg": str(e)[:800],
                },
            )
        except (OSError, ValueError):
            pass
        pool.close()
        return
    wire.send_frame(
        conn,
        {
            "op": "ready",
            "pid": os.getpid(),
            "primed": primed,
            "artifact_buckets": installed,
            "artifact_keys": _artifact_keys(applier),
            "startup_seconds": round(time.monotonic() - t0, 3),
            "telemetry": tel.ship(t_rx=t0),
        },
    )

    held: list = []  # response slabs reusable once the NEXT frame lands
    try:
        while True:
            try:
                msg = wire.recv_frame(conn)
            except (EOFError, OSError):
                return  # the router died; nothing to serve for
            # the previous response has been fully read by the router
            # (strict request/response: it sent this frame after), so
            # its slab can rejoin the free list now
            while held:
                pool.release(held.pop())
            op = msg.get("op")
            if op == "bye":
                try:
                    wire.send_frame(conn, {"op": "bye_ack"})
                except (OSError, ValueError):
                    pass
                return
            if op == "ping":
                wire.send_frame(conn, {"op": "pong", "pid": os.getpid()})
                continue
            if op != "apply":
                wire.send_frame(
                    conn,
                    {
                        "op": "error",
                        "kind": "content",
                        "etype": "WireError",
                        "emsg": f"unknown op {op!r}",
                    },
                )
                continue
            t_apply = time.monotonic()
            try:
                with tel.span("worker.attach"):
                    arr = attacher.read(msg["ref"])
                n = int(msg.get("n", arr.shape[0]))
                deadline_s = msg.get("deadline_s")
                deadline = (
                    None
                    if deadline_s is None
                    else guard.Deadline.after(float(deadline_s))
                )
                with tel.span("worker.apply", n=n):
                    out = applier(Dataset(arr, n=n), deadline=deadline)
                result = np.asarray(out.array)
                slab, ref = wire.write_array(pool, result)
            except BaseException as e:
                wire.send_frame(
                    conn,
                    {
                        "op": "error",
                        "kind": _classify(e),
                        "etype": type(e).__name__,
                        "emsg": str(e)[:800],
                        "seconds": round(time.monotonic() - t_apply, 6),
                        "telemetry": tel.ship(t_rx=t_apply),
                    },
                )
                continue
            held.append(slab)
            wire.send_frame(
                conn,
                {
                    "op": "result",
                    "ref": ref,
                    "seconds": round(time.monotonic() - t_apply, 6),
                    "telemetry": tel.ship(t_rx=t_apply),
                },
            )
    finally:
        stop_beating.set()
        attacher.close()
        pool.close()
        try:
            conn.close()
        except OSError:
            pass
