"""Online serving subsystem (keystone_tpu/serve): micro-batcher state
machine, admission control, deadline shedding, chaos over serve.* sites,
compiled-program reuse, HTTP front end, and the byte-identity pins.

All tier-1 (seconds-scale, CPU): the service is host-side threading over
tiny device programs.
"""

import json
import subprocess
import sys
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu import faults
from keystone_tpu.models.linear import LinearMapper
from keystone_tpu.obs import metrics
from keystone_tpu.ops.stats import NormalizeRows
from keystone_tpu.serve import (
    Overloaded,
    PipelineService,
    ServiceClosed,
    default_buckets,
    serve,
)
from keystone_tpu.utils import guard
from keystone_tpu.workflow import Dataset, Pipeline

pytestmark = pytest.mark.serve

DIM = 6


def _pipeline(scale: float = 2.0) -> Pipeline:
    w = jnp.asarray(np.eye(DIM, dtype=np.float32) * scale)
    return Pipeline.of(NormalizeRows()) | LinearMapper(w)


def _service(**kw) -> PipelineService:
    kw.setdefault("max_batch", 8)
    kw.setdefault("max_wait_ms", 30.0)
    kw.setdefault("queue_bound", 64)
    kw.setdefault("example", np.zeros(DIM, np.float32))
    return serve(_pipeline(), **kw)


def _counter(name: str) -> float:
    return metrics.REGISTRY.counter_value(name)


# ------------------------------------------------------------- correctness


def test_serve_matches_offline_apply():
    """The padded-bucket serve path returns exactly what the offline
    batch apply returns (pad rows are sliced off, per-row semantics)."""
    x = np.random.default_rng(0).normal(size=(5, DIM)).astype(np.float32)
    pipe = _pipeline()
    ref = np.asarray(pipe(Dataset(x)).get().array)[:5]
    with _service() as svc:
        futs = svc.submit_many(x)
        got = np.stack([f.result(timeout=30) for f in futs])
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)


def test_freeze_rejects_unfitted_pipeline():
    from keystone_tpu.models.linear import LinearMapEstimator
    from keystone_tpu.workflow.pipeline import FrozenApplier

    x = np.random.default_rng(0).normal(size=(8, DIM)).astype(np.float32)
    y = np.eye(DIM, dtype=np.float32)[np.arange(8) % DIM]
    pipe = Pipeline.of(NormalizeRows()).and_then(
        LinearMapEstimator(lam=1e-3), x, y
    )
    with pytest.raises(TypeError, match="call fit"):
        FrozenApplier(pipe)
    # fitted, the same pipeline freezes and serves
    with serve(
        pipe.fit(), max_batch=4, max_wait_ms=5.0, example=x[0]
    ) as svc:
        out = svc.submit(x[0]).result(timeout=30)
    assert np.asarray(out).shape == (DIM,)


# --------------------------------------------------- batcher state machine


def test_flush_on_max_batch():
    """max_batch requests flush immediately — well before the (long)
    timer — and ride ONE batch."""
    before = _counter("serve.batches")
    with _service(max_batch=4, max_wait_ms=10_000.0) as svc:
        x = np.ones((4, DIM), np.float32)
        t0 = time.monotonic()
        futs = svc.submit_many(x)
        [f.result(timeout=30) for f in futs]
        elapsed = time.monotonic() - t0
    assert elapsed < 5.0  # nowhere near the 10 s timer
    assert _counter("serve.batches") == before + 1


def test_flush_on_timer():
    """A lone request flushes when the oldest-request timer expires,
    not when max_batch fills."""
    with _service(max_batch=8, max_wait_ms=50.0) as svc:
        fut = svc.submit(np.ones(DIM, np.float32))
        out = fut.result(timeout=30)
    assert np.asarray(out).shape == (DIM,)


def test_fifo_order_preserved():
    """Requests resolve with their OWN results in submission order —
    index-encoded payloads round-trip one-to-one (FIFO fairness)."""
    with _service(max_batch=4, max_wait_ms=5.0) as svc:
        xs = [np.full(DIM, float(i + 1), np.float32) for i in range(20)]
        futs = [svc.submit(x) for x in xs]
        outs = [np.asarray(f.result(timeout=30)) for f in futs]
    pipe = _pipeline()
    ref = np.asarray(pipe(Dataset(np.stack(xs))).get().array)[:20]
    for i, out in enumerate(outs):
        np.testing.assert_allclose(out, ref[i], rtol=1e-6, atol=1e-7)


def test_deadline_expired_request_is_shed():
    """A request whose deadline already passed is shed (typed
    DeadlineExceeded), while a live request in the same flush completes."""
    shed0 = _counter("serve.shed")
    with _service(max_batch=8, max_wait_ms=30.0) as svc:
        doomed = svc.submit(np.ones(DIM, np.float32), deadline=-0.01)
        live = svc.submit(np.ones(DIM, np.float32), deadline=30.0)
        with pytest.raises(guard.DeadlineExceeded):
            doomed.result(timeout=30)
        assert np.asarray(live.result(timeout=30)).shape == (DIM,)
    assert _counter("serve.shed") == shed0 + 1


def test_queue_bound_rejects_with_overloaded():
    """Admission control: submits past queue_bound raise Overloaded
    (and count) while queued requests still drain at shutdown."""
    rej0 = _counter("serve.rejected")
    svc = _service(max_batch=64, max_wait_ms=10_000.0, queue_bound=2)
    try:
        f1 = svc.submit(np.ones(DIM, np.float32))
        f2 = svc.submit(np.ones(DIM, np.float32))
        with pytest.raises(Overloaded):
            svc.submit(np.ones(DIM, np.float32))
        assert _counter("serve.rejected") == rej0 + 1
    finally:
        svc.close()  # drain flushes the two queued requests
    assert np.asarray(f1.result(timeout=5)).shape == (DIM,)
    assert np.asarray(f2.result(timeout=5)).shape == (DIM,)


def test_clean_shutdown_drains_in_flight():
    """close(drain=True) resolves every queued request before the
    worker exits; post-close submits raise ServiceClosed."""
    svc = _service(max_batch=4, max_wait_ms=10_000.0, queue_bound=64)
    futs = [svc.submit(np.ones(DIM, np.float32)) for _ in range(10)]
    svc.close()
    for f in futs:
        assert np.asarray(f.result(timeout=5)).shape == (DIM,)
    with pytest.raises(ServiceClosed):
        svc.submit(np.ones(DIM, np.float32))


def test_close_without_drain_fails_queued():
    svc = _service(max_batch=64, max_wait_ms=10_000.0)
    futs = [svc.submit(np.ones(DIM, np.float32)) for _ in range(3)]
    svc.close(drain=False)
    for f in futs:
        with pytest.raises(ServiceClosed):
            f.result(timeout=5)


def test_cancelled_future_does_not_kill_batcher():
    """A caller cancelling its queued future must not brick the worker:
    the cancelled request is skipped and later requests still serve."""
    with _service(max_batch=4, max_wait_ms=50.0) as svc:
        doomed = svc.submit(np.ones(DIM, np.float32))
        assert doomed.cancel()  # still queued: cancel succeeds
        later = svc.submit(np.ones(DIM, np.float32))
        assert np.asarray(later.result(timeout=30)).shape == (DIM,)
        again = svc.submit(np.ones(DIM, np.float32))
        assert np.asarray(again.result(timeout=30)).shape == (DIM,)


def test_rejected_first_call_does_not_fix_item_shape():
    """An oversize first submit_many is rejected whole — and must not
    lock in an item-shape contract no served request ever set."""
    with serve(
        _pipeline(), max_batch=4, max_wait_ms=5.0, queue_bound=2
    ) as svc:
        with pytest.raises(Overloaded):
            svc.submit_many(np.ones((3, DIM + 1), np.float32))
        assert svc.queue_depth == 0  # atomic: nothing orphaned
        # the real workload's shape is learned fresh
        out = svc.submit(np.ones(DIM, np.float32)).result(timeout=30)
        assert np.asarray(out).shape == (DIM,)


def test_shed_predictor_recovers_from_outlier_batch():
    """A poisoned EWMA (e.g. a cold compile measured into the first
    sample) must decay across fully-shed flushes instead of shedding
    100% of deadline traffic forever."""
    with _service(max_batch=8, max_wait_ms=2.0) as svc:
        svc._ewma_batch_s = 5.0  # simulate one 5 s outlier sample
        deadline = 1.0
        out = None
        for _ in range(30):  # decay: 5.0 * 0.7^n < 1.0 within ~5 flushes
            try:
                out = svc.submit(
                    np.ones(DIM, np.float32), deadline=deadline
                ).result(timeout=30)
                break
            except guard.DeadlineExceeded:
                continue
        assert out is not None, "predictor never recovered"
        assert svc._ewma_batch_s < 1.0


def test_http_frontend_stop_without_start_does_not_hang():
    from keystone_tpu.serve import HttpFrontend

    with _service(max_batch=4, max_wait_ms=5.0) as svc:
        front = HttpFrontend(svc, port=0)
        front.stop()  # never started: must close, not deadlock
        # and the context manager auto-starts
        with HttpFrontend(svc, port=0) as started:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{started.port}/healthz", timeout=10
            ) as resp:
                assert resp.status == 200


def test_shape_mismatch_rejected_at_submit():
    """A bad request fails ITS OWN submit — never the batch it would
    have ridden in."""
    with _service() as svc:
        good = svc.submit(np.ones(DIM, np.float32))
        with pytest.raises(TypeError, match="item shape"):
            svc.submit(np.ones(DIM + 1, np.float32))
        assert np.asarray(good.result(timeout=30)).shape == (DIM,)


def test_default_buckets():
    assert default_buckets(32) == (8, 16, 32)
    assert default_buckets(24) == (8, 16, 24)
    assert default_buckets(4) == (4,)
    assert default_buckets(1) == (1,)


# ----------------------------------------------------------------- chaos


@pytest.mark.chaos
def test_chaos_enqueue_fault_backpressures_caller():
    """An injected fault at serve.enqueue surfaces to the submitting
    caller (admission chaos); the next submit succeeds."""
    with _service(max_batch=2, max_wait_ms=5.0) as svc:
        with faults.inject("serve.enqueue:times=1:raise"):
            with pytest.raises(faults.FaultInjected):
                svc.submit(np.ones(DIM, np.float32))
            fut = svc.submit(np.ones(DIM, np.float32))
            assert np.asarray(fut.result(timeout=30)).shape == (DIM,)


@pytest.mark.chaos
def test_chaos_batch_fault_fails_batch_not_service():
    """An injected fault at serve.batch fails that flush's futures and
    ONLY them — the worker survives and serves the next flush."""
    err0 = _counter("serve.batch_errors")
    with _service(max_batch=2, max_wait_ms=5.0) as svc:
        with faults.inject("serve.batch:times=1:raise"):
            bad = svc.submit_many(np.ones((2, DIM), np.float32))
            for f in bad:
                with pytest.raises(faults.FaultInjected):
                    f.result(timeout=30)
            good = svc.submit(np.ones(DIM, np.float32))
            assert np.asarray(good.result(timeout=30)).shape == (DIM,)
    assert _counter("serve.batch_errors") == err0 + 1


@pytest.mark.chaos
@pytest.mark.hangs
def test_chaos_batch_stall_sheds_waiting_deadlines():
    """The hang scenario: a stalled flush (serve.batch:delay) makes the
    request queued behind it miss its deadline — it is shed, while the
    stalled request itself completes."""
    with _service(max_batch=1, max_wait_ms=2.0, queue_bound=8) as svc:
        with faults.inject("serve.batch:times=1:delay=0.4"):
            slow = svc.submit(np.ones(DIM, np.float32), deadline=10.0)
            time.sleep(0.05)  # the worker is now inside the stalled flush
            doomed = svc.submit(np.ones(DIM, np.float32), deadline=0.05)
            assert np.asarray(slow.result(timeout=30)).shape == (DIM,)
            with pytest.raises(guard.DeadlineExceeded):
                doomed.result(timeout=30)


def test_optional_stage_degrades_on_serve_path():
    """Executor degradation applies to served batches: a failing
    ``optional=True`` stage is replaced by Identity instead of failing
    the flush."""
    from keystone_tpu.workflow import Transformer

    class _Flaky(Transformer):
        optional = True

        def apply_one(self, x):
            raise RuntimeError("boom")

        def apply_batch(self, xs, mask=None):
            raise RuntimeError("boom")

    w = jnp.asarray(np.eye(DIM, dtype=np.float32) * 3.0)
    pipe = Pipeline.of(_Flaky()) | LinearMapper(w)
    deg0 = metrics.REGISTRY.counter_total("executor.degraded")
    x = np.random.default_rng(2).normal(size=(DIM,)).astype(np.float32)
    with serve(
        pipe, max_batch=4, max_wait_ms=5.0, example=np.zeros(DIM, np.float32)
    ) as svc:
        out = np.asarray(svc.submit(x).result(timeout=30))
    np.testing.assert_allclose(out, x * 3.0, rtol=1e-6)
    assert metrics.REGISTRY.counter_total("executor.degraded") > deg0


# -------------------------------------------------- compiled-program reuse


def _total_apply_programs() -> int:
    """Compiled apply-program count across every jit cache an apply can
    ride: the fused-chain shared cache, the traced-params shared cache,
    and the per-instance wrappers.

    Collect first: the per-instance cache is a WeakKeyDictionary over
    transformer objects, and earlier tests' dead pipelines linger as
    cyclic garbage until a generational GC pass — one landing BETWEEN
    two counts silently shrinks the second and fails an equality pin
    that no new compile violated.  Forcing collection before every
    count makes both sides see post-GC state; a genuinely new program
    still raises the count."""
    import gc
    import importlib

    gc.collect()
    T = importlib.import_module("keystone_tpu.workflow.transformer")
    O = importlib.import_module("keystone_tpu.workflow.optimizer")
    n = 0
    for v in O._FUSED_SHARED_CACHE.values():
        if callable(v):
            n += v._cache_size()
    for v in T._SHARED_APPLY_CACHE.values():
        if callable(v):
            n += v._cache_size()
    for entry in T._JIT_APPLY_CACHE.values():
        for f in entry.values():
            if callable(f):
                n += f._cache_size()
    return n


def test_single_datum_rides_bucket_program():
    """The compile-count pin (ISSUE 5 satellite): after priming, a
    single-datum request is padded to the smallest bucket and reuses
    its BATCH program — no per-datum program is ever traced."""
    with _service(buckets=(8,), max_batch=8, max_wait_ms=5.0) as svc:
        n0 = _total_apply_programs()
        assert n0 > 0  # priming compiled the bucket programs
        out = svc.submit(np.zeros(DIM, np.float32)).result(timeout=30)
        assert np.asarray(out).shape == (DIM,)
        assert _total_apply_programs() == n0


def test_priming_compiles_each_bucket_once():
    """Every bucket shape is primed at construction, so a first request
    at ANY admissible size pays zero traces."""
    with _service(buckets=(4, 8), max_batch=8, max_wait_ms=5.0) as svc:
        n0 = _total_apply_programs()
        for k in (1, 3, 4, 6, 8):  # both buckets, never a new shape
            futs = svc.submit_many(np.ones((k, DIM), np.float32))
            [f.result(timeout=30) for f in futs]
        assert _total_apply_programs() == n0


# ------------------------------------------------------- byte-identity pins


def test_solver_hlo_identical_with_service_running():
    """Running a service must not perturb traced solver programs: the
    serving layer lives entirely outside jit."""
    import jax

    from keystone_tpu.models.block_ls import _bcd_epoch_body

    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 16, 8)), jnp.float32
    )
    y = jnp.ones((16, 2), jnp.float32)
    w = jnp.zeros((2, 8, 2), jnp.float32)
    p = jnp.zeros((16, 2), jnp.float32)

    def step(xb, yb, wb, pb):
        return _bcd_epoch_body(xb, yb, jnp.float32(16.0), 1e-3, (wb, pb))

    plain = jax.jit(step).lower(x, y, w, p).as_text()
    with _service() as svc:
        svc.submit(np.ones(DIM, np.float32)).result(timeout=30)
        serving = jax.jit(step).lower(x, y, w, p).as_text()
    assert plain == serving


def test_library_import_path_excludes_serve():
    """With no service running, importing the library must not import
    (or pay for) the serving subsystem — the offline import path is
    exactly what it was before this subsystem existed."""
    out = subprocess.run(
        [
            sys.executable,
            "-c",
            "import keystone_tpu, sys; "
            "print('keystone_tpu.serve' in sys.modules)",
        ],
        capture_output=True,
        text=True,
        timeout=240,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-500:]
    assert out.stdout.strip().splitlines()[-1] == "False"


# ------------------------------------------------------------------- HTTP


def test_http_predict_healthz_metrics():
    from keystone_tpu.serve import serve_http

    x = np.random.default_rng(1).normal(size=(3, DIM)).astype(np.float32)
    pipe = _pipeline()
    ref = np.asarray(pipe(Dataset(x)).get().array)[:3]
    with _service(max_batch=4, max_wait_ms=5.0) as svc:
        with serve_http(svc, port=0) as front:
            base = f"http://127.0.0.1:{front.port}"
            req = urllib.request.Request(
                base + "/predict",
                data=json.dumps({"instances": x.tolist()}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 200
                preds = json.loads(resp.read())["predictions"]
            np.testing.assert_allclose(
                np.asarray(preds, np.float32), ref, rtol=1e-5, atol=1e-6
            )

            with urllib.request.urlopen(base + "/healthz", timeout=10) as resp:
                health = json.loads(resp.read())
            assert health["status"] == "ok"
            assert health["max_batch"] == 4

            with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
                text = resp.read().decode()
            assert "serve_completed_total" in text
            assert "serve_batch_rows_count" in text

            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(base + "/nope", timeout=10)
            assert err.value.code == 404


def test_http_bad_request_and_single_instance():
    from keystone_tpu.serve import serve_http

    with _service(max_batch=4, max_wait_ms=5.0) as svc:
        with serve_http(svc, port=0) as front:
            base = f"http://127.0.0.1:{front.port}"
            req = urllib.request.Request(
                base + "/predict", data=b"not json at all"
            )
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(req, timeout=10)
            assert err.value.code == 400

            req = urllib.request.Request(
                base + "/predict",
                data=json.dumps(
                    {"instance": [1.0] * DIM, "deadline_ms": 5000}
                ).encode(),
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                preds = json.loads(resp.read())["predictions"]
            assert len(preds) == 1 and len(preds[0]) == DIM


# --------------------------------------------------------------- overload


@pytest.mark.hangs
def test_overload_keeps_accepting_with_bounded_queue():
    """The acceptance scenario (seconds-scale): offered QPS > capacity
    (a serve.batch delay plan emulates a heavier model).  The service
    keeps completing work at occupancy > 1, sheds/rejects the excess
    (counted), and every completed request beats its deadline."""
    from tools import workloads

    svc, item_shape = workloads.build_service(
        dim=16,
        max_batch=8,
        max_wait_ms=2.0,
        queue_bound=32,
        deadline_ms=500.0,
    )
    try:
        rep = workloads.offer(
            svc,
            item_shape,
            qps=600.0,
            duration=1.5,
            deadline_ms=500.0,
            batch_delay_ms=15.0,
        )
    finally:
        svc.close()
    # offered 600 qps vs capacity ~ 8 rows / 15ms ≈ 530: overload
    assert rep["completed"] > 0
    assert rep["mean_batch_occupancy"] > 1.0
    assert rep["shed"] + rep["rejected"] > 0  # excess counted, not queued
    assert rep["errors"] == 0
    assert rep["deadline_miss"] == 0  # completed requests beat deadlines
    assert rep["p99_ms"] is not None and rep["p99_ms"] < 500.0


def test_offer_counts_an_unavailable_fleet_as_rejected(monkeypatch):
    """An open breaker answers ``FleetUnavailable`` at admission — a
    typed refusal like ``Overloaded``.  The offer must count it and go
    on: on the v5e an overloaded service ended the offer loop with it
    one run in two (PR 21), and a run that dies reports nothing."""
    from tools import workloads

    svc, item_shape = workloads.build_service(
        dim=16, max_batch=8, max_wait_ms=2.0, queue_bound=32, deadline_ms=500.0
    )
    try:
        monkeypatch.setattr(svc._pool, "available", lambda: False)
        rep = workloads.offer(
            svc, item_shape, qps=400.0, duration=0.25, deadline_ms=500.0
        )
    finally:
        svc.close()
    assert rep["n_requests"] == 100
    assert rep["rejected"] == 100 and rep["completed"] == 0 and rep["errors"] == 0
