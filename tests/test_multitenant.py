"""Multi-tenant serving + the cross-pipeline shared stage pool (ISSUE 14).

Pins: pool eviction under budget pressure, per-entry refcounts across
tenants, the signature-collision admission gate (two same-signature
different-state stages are NEVER cross-shared), single-tenant-with-pool
byte identity vs the pre-pool path, shared-vs-unshared bit identity,
DRR fair-share flush forming, per-tenant quota/fault blast-radius
isolation, and the tenant surfaces (HTTP routing, /statusz)."""

import json
import urllib.request

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from keystone_tpu import faults
from keystone_tpu.models.linear import LinearMapper
from keystone_tpu.ops.stats import (
    LinearRectifier,
    NormalizeRows,
    PaddedFFT,
    RandomSignNode,
)
from keystone_tpu.serve import (
    Overloaded,
    PipelineService,
    UnknownTenant,
    serve,
    serve_multi,
)
from keystone_tpu.workflow import Pipeline
from keystone_tpu.workflow.cross import plan_sharing
from keystone_tpu.workflow.stage_pool import SharedStagePool
from keystone_tpu.workflow.transformer import Transformer

DIM = 16


def _head_weights(classes, seed):
    rng = np.random.default_rng(seed)
    padded = 1 << (DIM - 1).bit_length()
    feat_dim = (padded // 2) * 2
    return jnp.asarray(rng.normal(size=(feat_dim, classes)).astype(np.float32))


def _tenant_pipeline(seed, classes=4):
    """A pipeline with a DETERMINISTIC shared featurization prefix
    (same branch seeds for every tenant) and a per-tenant head."""
    feat = Pipeline.gather(
        [
            RandomSignNode.init(DIM, 1000 + i)
            | PaddedFFT()
            | LinearRectifier(0.0, alpha=0.01 * (i + 1))
            for i in range(2)
        ]
    )
    return feat | NormalizeRows() | LinearMapper(_head_weights(classes, seed))


def _mk(models, pool=None, **kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("max_wait_ms", 2.0)
    kw.setdefault("queue_bound", 64)
    kw.setdefault("example", np.zeros((DIM,), np.float32))
    return serve_multi(models, pool=pool, **kw)


# ------------------------------------------------------------------- pool
def test_pool_eviction_under_budget_pressure():
    pool = SharedStagePool(budget_bytes=100)
    tok = "t1"
    pool.begin_flush(tok, {"A": 2, "B": 2})
    assert pool.put(("A", tok), "va", nbytes=60)
    assert pool.put(("B", tok), "vb", nbytes=60)  # evicts A (LRU)
    hit, _ = pool.get(("A", tok))
    assert not hit, "evicted entry must miss (recompute, never wrong)"
    hit, v = pool.get(("B", tok))
    assert hit and v == "vb"
    st = pool.stats()
    assert st["evictions"] >= 1
    pool.end_flush(tok)
    assert pool.stats()["entries"] == 0


def test_pool_oversized_entry_never_resident():
    pool = SharedStagePool(budget_bytes=100)
    pool.begin_flush("t", {"A": 2})
    assert not pool.put(("A", "t"), "v", nbytes=1000)
    assert pool.stats()["resident_bytes"] == 0


def test_pool_refcount_frees_at_zero():
    """Per-entry refcounts across tenants: the entry is freed the
    moment its LAST declared consumer reads it — HBM returns early,
    not at flush end."""
    pool = SharedStagePool(budget_bytes=1 << 20)
    tok = 9
    pool.begin_flush(tok, {"S": 3})  # producer + 2 readers
    assert pool.put(("S", tok), "val", nbytes=10)
    assert pool.stats()["entries"] == 1
    hit, _ = pool.get(("S", tok))
    assert hit and pool.stats()["entries"] == 1  # one reader left
    hit, _ = pool.get(("S", tok))
    assert hit and pool.stats()["entries"] == 0  # last reader freed it
    hit, _ = pool.get(("S", tok))
    assert not hit


def test_pool_single_consumer_sig_not_stored():
    pool = SharedStagePool(budget_bytes=1 << 20)
    pool.begin_flush("t", {"S": 1})
    assert not pool.put(("S", "t"), "v", nbytes=10)
    assert pool.stats()["entries"] == 0


def test_pool_tokens_isolate_flushes():
    """Entries can never leak across flush tokens (different request
    batches)."""
    pool = SharedStagePool(budget_bytes=1 << 20)
    pool.begin_flush("t1", {"S": 2})
    pool.put(("S", "t1"), "flush1", nbytes=8)
    pool.begin_flush("t2", {"S": 2})
    hit, _ = pool.get(("S", "t2"))
    assert not hit
    pool.end_flush("t1")
    pool.end_flush("t2")


def test_pool_registered_tenant_entries_evict_last():
    pool = SharedStagePool(budget_bytes=100)
    pool.register_tenant("a", ["KEEP"])
    tok = "t"
    pool.begin_flush(tok, {"KEEP": 2, "DROP": 2})
    assert pool.put(("KEEP", tok), "k", nbytes=50)
    assert pool.put(("DROP", tok), "d", nbytes=50)
    # a third entry forces eviction: the unregistered sig goes first
    pool.begin_flush("t2", {"X": 2})
    assert pool.put(("X", "t2"), "x", nbytes=50)
    hit, _ = pool.get(("KEEP", tok))
    assert hit, "registered-tenant entry should outlive unregistered one"
    pool.unregister_tenant("a")
    assert pool.sig_refcount("KEEP") == 0


# ---------------------------------------------------------- sharing plan
def test_plan_sharing_detects_shared_prefix():
    a = _tenant_pipeline(1).freeze()
    b = _tenant_pipeline(2).freeze()
    plan = plan_sharing({"a": a.graph, "b": b.graph})
    assert plan.shared, "equal featurization prefixes must be planned shared"
    assert plan.refused == 0
    for sig in plan.shared:
        assert plan.consumers[sig] == 2
    # per-flush consumer counts restrict to the flush's tenants
    assert plan.sigs_for(["a", "b"])
    assert plan.sigs_for(["a"]) == {}


def test_plan_sharing_single_tenant_empty():
    a = _tenant_pipeline(1).freeze()
    plan = plan_sharing({"a": a.graph})
    assert not plan.shared and plan.node_sigs["a"] == {}


class _LeakyStage(Transformer):
    """Deliberately under-specified identity: params() omits ``scale``,
    so two observably different instances report EQUAL signatures —
    the exact bug class the collision gate exists to refuse."""

    def __init__(self, scale):
        self.scale = float(scale)

    def params(self):
        return ("leaky",)

    def apply_one(self, x):
        return x * self.scale

    def apply_batch(self, xs, mask=None):
        return xs * self.scale


def test_collision_gate_refuses_unsafe_share():
    a = Pipeline.of(_LeakyStage(2.0)).freeze()
    b = Pipeline.of(_LeakyStage(3.0)).freeze()
    plan = plan_sharing({"a": a.graph, "b": b.graph})
    assert plan.refused >= 1, "colliding signatures must be refused"
    assert not plan.shared
    # end to end: served co-tenant predictions stay tenant-correct
    svc = _mk(
        {"a": Pipeline.of(_LeakyStage(2.0)), "b": Pipeline.of(_LeakyStage(3.0))},
        pool=SharedStagePool(budget_bytes=1 << 20),
    )
    try:
        x = np.full((DIM,), 1.0, np.float32)
        ya = svc.submit(x, tenant="a").result(10)
        yb = svc.submit(x, tenant="b").result(10)
        np.testing.assert_array_equal(ya, x * 2.0)
        np.testing.assert_array_equal(yb, x * 3.0)
        assert svc.status()["stage_pool"]["collision_refusals"] >= 1
    finally:
        svc.close()


# ---------------------------------------------------------- byte identity
def test_single_tenant_with_pool_byte_identical_to_pre_pool():
    """The acceptance pin: single-tenant serving WITH the pool equals
    the pre-pool PipelineService path bit for bit."""
    pipe = _tenant_pipeline(7)
    pool = SharedStagePool(budget_bytes=1 << 24)
    multi = _mk({"only": pipe}, pool=pool)
    plain = serve(
        _tenant_pipeline(7),
        max_batch=8,
        max_wait_ms=2.0,
        queue_bound=64,
        example=np.zeros((DIM,), np.float32),
    )
    try:
        rng = np.random.default_rng(3)
        for _ in range(3):
            x = rng.normal(size=(DIM,)).astype(np.float32)
            ym = multi.submit(x).result(10)  # single tenant: no label needed
            yp = plain.submit(x).result(10)
            assert np.array_equal(ym, yp)
        st = multi.status()["stage_pool"]
        assert st["shared_stages"] == 0
        assert st["hits"] == 0 and st["misses"] == 0
    finally:
        multi.close()
        plain.close()


def test_shared_vs_unshared_bit_identical_and_pool_hits():
    models = lambda: {"a": _tenant_pipeline(1), "b": _tenant_pipeline(2)}  # noqa: E731
    pool = SharedStagePool(budget_bytes=1 << 24)
    shared = _mk(models(), pool=pool)
    unshared = _mk(models(), share=False)
    try:
        rng = np.random.default_rng(5)
        x = rng.normal(size=(DIM,)).astype(np.float32)
        for t in ("a", "b"):
            ys = shared.submit(x, tenant=t).result(10)
            yu = unshared.submit(x, tenant=t).result(10)
            assert np.array_equal(ys, yu), f"tenant {t} diverged shared-vs-unshared"
        # the prefix actually pooled: priming + the live flushes hit
        assert pool.stats()["hits"] >= 1
        assert unshared.status()["stage_pool"]["sharing"] is False
    finally:
        shared.close()
        unshared.close()


def test_shared_prefix_computed_once_per_combined_flush():
    """Submit one co-tenant pair in a single flush window; the second
    tenant's walk must HIT the pool (shared prefix computed once)."""
    pool = SharedStagePool(budget_bytes=1 << 24)
    svc = _mk(
        {"a": _tenant_pipeline(1), "b": _tenant_pipeline(2)},
        pool=pool,
        max_wait_ms=50.0,
    )
    try:
        h0 = pool.stats()["hits"]
        x = np.random.default_rng(0).normal(size=(DIM,)).astype(np.float32)
        fa = svc.submit(x, tenant="a")
        fb = svc.submit(x, tenant="b")
        fa.result(10)
        fb.result(10)
        assert pool.stats()["hits"] > h0
    finally:
        svc.close()


# ------------------------------------------------------------- scheduling
def test_drr_pop_forms_fair_mixed_flushes():
    svc = _mk({"a": _tenant_pipeline(1), "b": _tenant_pipeline(2)})
    try:
        from keystone_tpu.serve.service import _Request

        with svc._cond:
            for i in range(20):
                svc._tq["a"].append(_Request(np.zeros((DIM,)), None, tenant="a"))
            for i in range(20):
                svc._tq["b"].append(_Request(np.zeros((DIM,)), None, tenant="b"))
            batch = svc._drr_pop_locked()
        counts = {"a": 0, "b": 0}
        for r in batch:
            counts[r.tenant] += 1
        assert len(batch) == svc.max_batch
        assert counts["a"] == counts["b"] == svc.max_batch // 2
        # tenant-contiguous ordering (the segment contract)
        tenants_seq = [r.tenant for r in batch]
        assert tenants_seq == sorted(tenants_seq) or tenants_seq == sorted(
            tenants_seq, reverse=True
        )
        # repeated pops stay fair — no banked-credit monopoly
        with svc._cond:
            batch2 = svc._drr_pop_locked()
        c2 = {"a": 0, "b": 0}
        for r in batch2:
            c2[r.tenant] += 1
        assert abs(c2["a"] - c2["b"]) <= 1
        for b in (batch, batch2):
            for r in b:
                r.future.cancel()
    finally:
        svc.close(drain=False)


def test_tenant_quota_overload_is_isolated():
    svc = _mk(
        {"a": _tenant_pipeline(1), "b": _tenant_pipeline(2)},
        tenant_queue_bound={"a": 2, "b": 32},
        max_wait_ms=200.0,  # keep requests queued while we overfill
        max_batch=64,
    )
    try:
        x = np.zeros((DIM,), np.float32)
        futs = [svc.submit(x, tenant="a") for _ in range(2)]
        with pytest.raises(Overloaded):
            svc.submit(x, tenant="a")
        # tenant b is untouched by a's full quota
        fb = svc.submit(x, tenant="b")
        assert fb.result(10) is not None
        for f in futs:
            f.result(10)
    finally:
        svc.close()


def test_unknown_and_missing_tenant_rejected():
    svc = _mk({"a": _tenant_pipeline(1), "b": _tenant_pipeline(2)})
    try:
        x = np.zeros((DIM,), np.float32)
        with pytest.raises(UnknownTenant):
            svc.submit(x, tenant="nope")
        with pytest.raises(UnknownTenant):
            svc.submit(x)  # ambiguous with 2 tenants
    finally:
        svc.close()


def test_single_tenant_service_refuses_tenant_kwarg():
    plain = serve(
        _tenant_pipeline(1),
        max_batch=4,
        example=np.zeros((DIM,), np.float32),
    )
    try:
        with pytest.raises(TypeError):
            plain.submit(np.zeros((DIM,), np.float32), tenant="a")
    finally:
        plain.close()


# ---------------------------------------------------------- blast radius
def test_tenant_targeted_enqueue_fault_isolated():
    svc = _mk({"a": _tenant_pipeline(1), "b": _tenant_pipeline(2)})
    try:
        x = np.zeros((DIM,), np.float32)
        with faults.inject("serve.enqueue:ctx.tenant=a:raise"):
            with pytest.raises(faults.FaultInjected):
                svc.submit(x, tenant="a")
            yb = svc.submit(x, tenant="b").result(10)
            assert np.all(np.isfinite(yb))
    finally:
        svc.close()


def test_tenant_targeted_batch_fault_contained_to_tenant():
    """A serve.batch fault matched to ctx.tenant=a fails a's riders in
    the combined flush; b's riders in the SAME flush deliver."""
    svc = _mk(
        {"a": _tenant_pipeline(1), "b": _tenant_pipeline(2)},
        max_wait_ms=50.0,
    )
    try:
        x = np.random.default_rng(1).normal(size=(DIM,)).astype(np.float32)
        with faults.inject("serve.batch:ctx.tenant=a:raise:times=1"):
            fa = svc.submit(x, tenant="a")
            fb = svc.submit(x, tenant="b")
            yb = fb.result(15)
            assert np.all(np.isfinite(yb))
            with pytest.raises(Exception):
                fa.result(15)
    finally:
        svc.close()


def test_tenant_breaker_opens_for_failing_tenant_only():
    svc = _mk(
        {"a": _tenant_pipeline(1), "b": _tenant_pipeline(2)},
        tenant_breaker_threshold=2,
        max_wait_ms=5.0,
    )
    try:
        from keystone_tpu.utils import guard

        x = np.random.default_rng(1).normal(size=(DIM,)).astype(np.float32)
        with faults.inject("serve.batch:ctx.tenant=a:raise"):
            failures = 0
            for _ in range(6):
                try:
                    svc.submit(x, tenant="a").result(15)
                except guard.CircuitOpenError:
                    break
                except Exception:
                    failures += 1
            else:
                pytest.fail("tenant a's breaker never opened")
            assert failures >= 2
            # tenant b admits and serves throughout
            yb = svc.submit(x, tenant="b").result(15)
            assert np.all(np.isfinite(yb))
    finally:
        svc.close()


# --------------------------------------------------------------- surfaces
def test_statusz_tenants_and_pool_sections():
    svc = _mk({"a": _tenant_pipeline(1), "b": _tenant_pipeline(2)})
    try:
        x = np.zeros((DIM,), np.float32)
        svc.submit(x, tenant="a").result(10)
        st = svc.status()
        assert set(st["tenants"]) == {"a", "b"}
        ta = st["tenants"]["a"]
        assert ta["counters"]["submitted"] >= 1
        assert ta["counters"]["completed"] >= 1
        assert "latency_ms" in ta and "quota" in ta
        sp = st["stage_pool"]
        assert {"hits", "misses", "shared_stages", "collision_refusals"} <= set(sp)
    finally:
        svc.close()


def test_http_tenant_routing():
    from keystone_tpu.serve import serve_http

    svc = _mk({"a": _tenant_pipeline(1), "b": _tenant_pipeline(2, classes=6)})
    front = serve_http(svc, port=0)
    base = f"http://127.0.0.1:{front.port}"
    try:
        def post(body):
            req = urllib.request.Request(
                base + "/predict",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
            )
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        x = [0.5] * DIM
        code, body = post({"instance": x, "tenant": "b"})
        assert code == 200 and len(body["predictions"][0]) == 6
        code, body = post({"instance": x, "tenant": "nope"})
        assert code == 400
        code, body = post({"instance": x})  # ambiguous
        assert code == 400
        # /statusz carries the tenant + pool sections
        with urllib.request.urlopen(base + "/statusz", timeout=30) as r:
            st = json.loads(r.read())
        assert set(st["tenants"]) == {"a", "b"}
        assert "stage_pool" in st
    finally:
        front.stop()
        svc.close()


def test_replicated_multi_tenant_serving():
    """The applier clones per replica (graphs() placement path), the
    pool keys stay content+token addressed across clones, AND a
    PRIVATE pool survives the clone's pickle round-trip (re-resolved
    by token — a clone falling back to the default pool would leave
    the configured budget/stats blind to live traffic)."""
    pool = SharedStagePool(budget_bytes=1 << 24)
    svc = _mk(
        {"a": _tenant_pipeline(1), "b": _tenant_pipeline(2)},
        pool=pool,
        replicas=2,
    )
    try:
        x = np.random.default_rng(2).normal(size=(DIM,)).astype(np.float32)
        outs = [
            (
                svc.submit(x, tenant="a").result(15),
                svc.submit(x, tenant="b").result(15),
            )
            for _ in range(4)
        ]
        for ya, yb in outs[1:]:
            assert np.array_equal(ya, outs[0][0])
            assert np.array_equal(yb, outs[0][1])
        # the replica clones' flush walks hit THIS pool, not the
        # process default (the token re-resolution contract)
        assert pool.stats()["hits"] >= 1
    finally:
        svc.close()


def test_tenant_breaker_refusal_counts_as_rejected():
    """A tenant-breaker refusal is backpressure (HTTP 429): traced and
    counted as rejected, never as a tenant error."""
    from keystone_tpu.obs import metrics as _metrics
    from keystone_tpu.utils import guard

    svc = _mk(
        {"a": _tenant_pipeline(1), "b": _tenant_pipeline(2)},
        tenant_breaker_threshold=1,
    )
    try:
        x = np.random.default_rng(1).normal(size=(DIM,)).astype(np.float32)
        with faults.inject("serve.batch:ctx.tenant=a:raise"):
            with pytest.raises(Exception):
                svc.submit(x, tenant="a").result(15)  # opens the breaker
            errs0 = _metrics.REGISTRY.counter_value(
                "serve.tenant_errors", tenant="a"
            )
            rej0 = _metrics.REGISTRY.counter_value(
                "serve.tenant_rejected", tenant="a"
            )
            with pytest.raises(guard.CircuitOpenError):
                svc.submit(x, tenant="a")
        assert (
            _metrics.REGISTRY.counter_value(
                "serve.tenant_rejected", tenant="a"
            )
            == rej0 + 1
        )
        assert (
            _metrics.REGISTRY.counter_value("serve.tenant_errors", tenant="a")
            == errs0
        )
    finally:
        svc.close()


# ------------------------------------------------------------- dedup
def test_dedup_identical_inflight_payloads_computed_once():
    from keystone_tpu.obs import metrics as _metrics

    """With dedup=True, identical concurrent payloads for the SAME
    tenant ride one computation: followers occupy no queue slot, count
    as serve.dedup_hits, and resolve bit-identically."""
    svc = _mk(
        {"a": _tenant_pipeline(1)},
        dedup=True,
        max_wait_ms=25.0,  # hold the flush open so followers pile up
    )
    try:
        x = np.random.default_rng(2).normal(size=(DIM,)).astype(np.float32)
        h0 = _metrics.REGISTRY.counter_total("serve.dedup_hits")
        futs = [svc.submit(x, tenant="a") for _ in range(6)]
        outs = [np.asarray(f.result(30)) for f in futs]
        for o in outs[1:]:
            assert o.tobytes() == outs[0].tobytes()
        hits = _metrics.REGISTRY.counter_total("serve.dedup_hits") - h0
        assert hits >= 4, hits
        # followers get an OWNING copy: mutating one response cannot
        # corrupt a co-rider's
        outs[1][:] = 0
        assert outs[2].tobytes() == outs[0].tobytes()
    finally:
        svc.close()


def test_dedup_never_crosses_tenants():
    """The same payload for two tenants runs two different models —
    dedup keys are (tenant, content), so results differ and no
    cross-tenant hit is counted."""
    svc = _mk(
        {"a": _tenant_pipeline(1), "b": _tenant_pipeline(2)},
        dedup=True,
        max_wait_ms=25.0,
    )
    try:
        x = np.random.default_rng(3).normal(size=(DIM,)).astype(np.float32)
        fa = svc.submit(x, tenant="a")
        fb = svc.submit(x, tenant="b")
        ya, yb = np.asarray(fa.result(30)), np.asarray(fb.result(30))
        assert ya.tobytes() != yb.tobytes()
    finally:
        svc.close()


def test_dedup_off_by_default():
    from keystone_tpu.obs import metrics as _metrics

    svc = _mk({"a": _tenant_pipeline(1)}, max_wait_ms=10.0)
    try:
        x = np.random.default_rng(4).normal(size=(DIM,)).astype(np.float32)
        h0 = _metrics.REGISTRY.counter_total("serve.dedup_hits")
        futs = [svc.submit(x, tenant="a") for _ in range(4)]
        outs = [np.asarray(f.result(30)) for f in futs]
        for o in outs[1:]:
            assert o.tobytes() == outs[0].tobytes()  # same math regardless
        assert (
            _metrics.REGISTRY.counter_total("serve.dedup_hits") - h0 == 0
        )
    finally:
        svc.close()


def test_dedup_map_drains_after_resolution():
    """The in-flight map is bounded by construction: entries leave when
    their leader resolves, so a long-running service cannot leak."""
    import time

    svc = _mk({"a": _tenant_pipeline(1)}, dedup=True)
    try:
        xs = np.random.default_rng(5).normal(size=(8, DIM)).astype(np.float32)
        futs = [svc.submit(xs[i], tenant="a") for i in range(8)]
        for f in futs:
            f.result(30)
        deadline = time.monotonic() + 5.0
        while svc._dedup_inflight and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not svc._dedup_inflight
    finally:
        svc.close()
