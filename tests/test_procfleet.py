"""Process fleet (serve/wire.py, serve/worker.py, serve/procfleet.py):
the wire protocol's framing and slab discipline, and the promoted
worker-process replicas behind the PR-8 router — spawn/ready, remote
applies bit-identical to the threaded path, SIGKILL mid-flush healing
with zero lost futures, and live scale up/down.

Process-spawning tests share one module-scoped service (each spawn pays
a fresh interpreter + jax import); protocol tests are pure in-process.
"""

import os
import signal
import threading
import time

import numpy as np
import pytest

from keystone_tpu.serve import wire

pytestmark = pytest.mark.serve

DIM = 6


# ------------------------------------------------------------- framing
def test_frame_roundtrip():
    msg = {"op": "apply", "n": 3, "deadline_s": 0.25, "ref": {"slab": "x"}}
    assert wire.unpack_frame(wire.pack_frame(msg)) == msg


def test_frame_rejects_bad_magic_version_truncation():
    good = wire.pack_frame({"op": "ping"})
    with pytest.raises(wire.WireError):
        wire.unpack_frame(b"XXXX" + good[4:])
    with pytest.raises(wire.WireError):
        wire.unpack_frame(good[: len(wire.MAGIC)])  # truncated
    tampered = bytearray(good)
    tampered[len(wire.MAGIC)] = 99  # foreign protocol version
    with pytest.raises(wire.WireError):
        wire.unpack_frame(bytes(tampered))
    with pytest.raises(wire.WireError):
        wire.unpack_frame(wire.MAGIC + bytes([wire.VERSION]) + b"not json")
    with pytest.raises(wire.WireError):
        wire.pack_frame(["not", "a", "dict"])


def test_frame_rejects_unserializable_body():
    with pytest.raises(wire.WireError):
        wire.pack_frame({"arr": np.zeros(3)})  # arrays never ride frames


# ---------------------------------------------------------------- slabs
def test_slab_pool_reuses_across_buckets():
    pool = wire.SlabPool(prefix="t0")
    try:
        big = pool.acquire(1 << 20)  # 1 MiB class
        name = big.name
        pool.release(big)
        # a smaller payload REUSES the free larger slab instead of
        # creating a new one (slab classes mirror padding buckets)
        small = pool.acquire(1 << 12)
        assert small.name == name
        assert pool.stats()["created"] == 1
        assert pool.stats()["reused"] == 1
        pool.release(small)
    finally:
        pool.close()


def test_slab_pool_rejects_oversized_payload():
    pool = wire.SlabPool(prefix="t1", max_slab_bytes=1 << 16)
    try:
        with pytest.raises(wire.PayloadTooLarge):
            pool.acquire((1 << 16) + 1)
        # the refusal is a client-shaped ValueError: never bisected as
        # poison, never charged to infrastructure
        assert issubclass(wire.PayloadTooLarge, ValueError)
    finally:
        pool.close()


def test_write_array_attach_roundtrip():
    pool = wire.SlabPool(prefix="t2")
    attacher = wire.SlabAttacher()
    try:
        arr = np.arange(24, dtype=np.float32).reshape(4, 6) * 0.5
        slab, ref = wire.write_array(pool, arr)
        out = attacher.read(ref)
        np.testing.assert_array_equal(out, arr)
        assert out.dtype == arr.dtype
        # the copy owns its memory: slab reuse cannot corrupt it
        slab2, ref2 = wire.write_array(pool, np.zeros_like(arr))
        np.testing.assert_array_equal(out, arr)
        pool.release(slab)
        pool.release(slab2)
    finally:
        attacher.close()
        pool.close()


def test_attacher_rejects_overclaiming_ref():
    pool = wire.SlabPool(prefix="t3")
    attacher = wire.SlabAttacher()
    try:
        slab, ref = wire.write_array(pool, np.zeros(8, np.float32))
        bad = dict(ref, nbytes=slab.capacity + 1, shape=[slab.capacity + 1])
        with pytest.raises(wire.WireError):
            attacher.view(bad)
    finally:
        attacher.close()
        pool.close()


# ----------------------------------------------------- process fleet e2e
def _pipeline(scale: float = 2.0):
    import jax.numpy as jnp

    from keystone_tpu.models.linear import LinearMapper
    from keystone_tpu.ops.stats import NormalizeRows
    from keystone_tpu.workflow import Pipeline

    w = jnp.asarray(np.eye(DIM, dtype=np.float32) * scale)
    return Pipeline.of(NormalizeRows()) | LinearMapper(w)


@pytest.fixture(scope="module")
def proc_service():
    """One workers=2 process fleet shared by the e2e tests (each spawn
    pays a fresh interpreter + jax import; healing respawns keep the
    fixture valid across tests)."""
    from keystone_tpu.serve import serve

    svc = serve(
        _pipeline(),
        workers=2,
        max_batch=8,
        max_wait_ms=2.0,
        queue_bound=512,
        example=np.zeros(DIM, np.float32),
        name="procfleet_t",
        supervise_interval_s=0.1,
        heartbeat_s=10.0,
        restart_limit=1000,
    )
    yield svc
    svc.close()


def _rows(k: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).normal(size=(k, DIM)).astype(np.float32)


def _threaded_ref(x: np.ndarray) -> np.ndarray:
    from keystone_tpu.serve import serve

    ref = serve(
        _pipeline(),
        max_batch=8,
        max_wait_ms=2.0,
        example=np.zeros(DIM, np.float32),
        name="procfleet_ref",
        supervise=False,
    )
    try:
        return np.stack(
            [f.result(timeout=60) for f in [ref.submit(r) for r in x]]
        )
    finally:
        ref.close()


def test_process_fleet_serves_and_matches_threaded(proc_service):
    """The promotion is a transport change: predictions from the process
    fleet are BIT-identical to a threaded single-replica service laid
    out as a worker lays itself out (a spawned worker builds its own
    default mesh, every device on the data axis; a mesh the router
    ``set_mesh`` is not shipped to it).  Against the suite's 4x2 mesh,
    which splits each row's norm into two partial sums, they agree to
    one unit in the last place."""
    from keystone_tpu.parallel import default_mesh, use_mesh

    x = _rows(12, seed=3)
    got = np.stack(
        [f.result(timeout=60) for f in [proc_service.submit(r) for r in x]]
    )
    with use_mesh(default_mesh()):
        assert got.tobytes() == _threaded_ref(x).tobytes()
    np.testing.assert_array_max_ulp(got, _threaded_ref(x), maxulp=1)


def test_process_fleet_status_exposes_workers(proc_service):
    st = proc_service.status()
    assert st["backend"] == "process"
    assert st["workers"] == proc_service.replicas
    reps = st["replicas"]
    assert all(r["backend"] == "process" for r in reps)
    assert all(isinstance(r["pid"], int) for r in reps)
    alive = [r for r in reps if r["worker_alive"]]
    assert alive, "no live worker process in status"
    # the child-side heartbeat is beating
    ages = [
        r["worker_heartbeat_age_s"]
        for r in alive
        if r["worker_heartbeat_age_s"] is not None
    ]
    assert ages and min(ages) < 5.0


def test_worker_sigkill_mid_flight_loses_nothing(proc_service):
    """SIGKILL a live worker while requests are in flight: the claim
    machinery un-claims and requeues the killed worker's flush, the
    supervisor spawns a replacement, and EVERY submitted future
    resolves with a correct result — zero lost, zero hung."""
    from keystone_tpu.obs import metrics

    svc = proc_service
    restarts0 = metrics.REGISTRY.counter_total("serve.replica_restarts")
    x = _rows(200, seed=4)
    killed = []

    def killer():
        time.sleep(0.05)
        pids = [
            r["pid"] for r in svc.replica_statuses() if r.get("worker_alive")
        ]
        if pids:
            os.kill(pids[0], signal.SIGKILL)
            killed.append(pids[0])

    t = threading.Thread(target=killer)
    t.start()
    futs = []
    for i in range(x.shape[0]):
        try:
            futs.append(svc.submit(x[i]))
        except Exception:
            pass  # a fully-down instant refuses typed; acceptable
        time.sleep(0.001)
    t.join()
    done = 0
    for f in futs:
        r = f.result(timeout=120)  # TimeoutError here = a LOST future
        assert abs(float(np.linalg.norm(r)) - 2.0) < 1e-4
        done += 1
    assert killed, "the killer thread found no live worker to SIGKILL"
    assert done == len(futs)
    # wait out the heal so the fixture is whole for later tests
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if (
            metrics.REGISTRY.counter_total("serve.replica_restarts")
            > restarts0
            and sum(
                1
                for r in svc.replica_statuses()
                if r.get("worker_alive")
            )
            >= 2
        ):
            break
        time.sleep(0.1)
    assert (
        metrics.REGISTRY.counter_total("serve.replica_restarts") > restarts0
    ), "supervisor never restarted the killed worker"


def test_scale_up_and_down_live(proc_service):
    """scale_to grows the fleet (spawn → prime → admit) and shrinks it
    gracefully (drain → join) while traffic keeps completing."""
    svc = proc_service
    n0 = svc.replicas
    x = _rows(8, seed=5)
    svc.scale_to(n0 + 1)
    assert svc.replicas == n0 + 1
    outs = [
        f.result(timeout=60) for f in [svc.submit(r) for r in x]
    ]
    assert all(abs(float(np.linalg.norm(o)) - 2.0) < 1e-4 for o in outs)
    svc.scale_to(n0)
    assert svc.replicas == n0
    outs = [
        f.result(timeout=60) for f in [svc.submit(r) for r in x]
    ]
    assert all(abs(float(np.linalg.norm(o)) - 2.0) < 1e-4 for o in outs)


def test_multi_tenant_refuses_process_backend():
    from keystone_tpu.serve import serve_multi

    with pytest.raises(NotImplementedError):
        serve_multi({"a": _pipeline()}, workers=2)


def test_workers_and_replicas_are_exclusive():
    from keystone_tpu.serve import serve

    with pytest.raises(ValueError):
        serve(_pipeline(), workers=2, replicas=2)


@pytest.mark.parametrize("hosts", [None, "local:2"])
def test_local_workers_refused_when_router_holds_tpu(monkeypatch, hosts):
    """One process per chip: on a TPU the router (which has loaded the
    model's device arrays) must refuse to spawn local workers with a
    typed error at start — each worker would die in its spawn ("The TPU is
    already in use"), at every start and every heal."""
    import jax

    from keystone_tpu.serve import ChipOwnershipError, serve

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ChipOwnershipError, match="one process"):
        serve(_pipeline(), workers=2, hosts=hosts)


# ------------------------------------------- fleet telemetry (ISSUE 18)


def test_apply_frame_trace_key_and_slab_ref_pins(monkeypatch):
    """Frame-level byte pins: without trace context the apply control
    frame has EXACTLY the pre-tracing keys (recorder-off wire is
    unchanged), and the slab-ref fast path still ships the CALLER's
    reference — telemetry added zero copies to zero-copy dispatch."""
    from keystone_tpu.serve import procfleet as pf

    h = object.__new__(pf.WorkerHandle)
    h.name = "pin"
    h._lock = threading.Lock()
    h._closed = False
    h._conn = object()
    h._pool = None
    h.telemetry = None
    sent = []
    monkeypatch.setattr(pf.wire, "send_frame", lambda conn, m: sent.append(m))
    monkeypatch.setattr(pf.wire, "recv_frame", lambda conn: {"op": "pong"})
    ref = {"slab": "s0", "count": 2}
    h.apply(None, 2, slab_ref=ref)
    assert set(sent[0]) == {"op", "n", "deadline_s", "ref"}
    assert sent[0]["ref"] is ref
    ctx = {"batch": "b1", "request_ids": ["r1"]}
    h.apply(None, 2, slab_ref=ref, trace=ctx)
    assert sent[1]["trace"] == ctx
    assert set(sent[1]) == {"op", "n", "deadline_s", "ref", "trace"}


def test_process_fleet_stitches_cross_process_trace(proc_service):
    """E2E acceptance: a traced request served by a spawned worker
    process shows the TRUE cross-process chain on /requestz — the
    stitched batch record names the worker and host and carries the
    worker-side apply span aligned to the router clock (non-negative,
    inside the exchange window)."""
    rid = "proc-trace-e2e"
    x = _rows(4, seed=21)
    futs = [proc_service.submit(x[0], request_id=rid)]
    futs += [proc_service.submit(r) for r in x[1:]]
    for f in futs:
        f.result(timeout=60)
    rec = proc_service.recorder
    assert rec is not None
    tr = rec.request(rid)
    assert tr is not None and tr["batch_records"]
    stitched = [b for b in tr["batch_records"] if b.get("worker")]
    assert stitched, f"unstitched batch records: {tr['batch_records']}"
    b = stitched[0]
    assert b.get("host")
    assert "wire" in b
    names = [s["name"] for s in b.get("worker_spans", [])]
    assert "worker.apply" in names
    for s in b["worker_spans"]:
        assert s["seconds"] >= 0.0 and s["t_off"] >= 0.0
    # the ops surface sees the fleet: /statusz fleet block + labeled
    # series in the router registry
    st = proc_service.status()
    assert st.get("fleet", {}).get("workers")
    from keystone_tpu.obs import metrics

    series = metrics.REGISTRY.histogram_series("serve.fleet.apply_seconds")
    assert series
    assert all(lb.get("worker") and lb.get("host") for lb, _ in series)
