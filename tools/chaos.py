"""Run a pipeline under a fault plan and report per-site outcomes.

The driver half of the chaos contract (keystone_tpu/faults.py injects,
utils/durable.py survives): execute a workload with a KEYSTONE_FAULTS-
grammar plan active and report, per site, how many calls passed through,
how many faults were injected, and whether the workload survived.

Usage (CPU-safe; any laptop)::

    JAX_PLATFORMS=cpu python tools/chaos.py \
        --plan "blockstore.read:every=3:raise;ckpt.save:after=1:times=1:corrupt" \
        --workload bcd --restarts 1

    # or drive your own entry point: any module:function() that runs a fit
    JAX_PLATFORMS=cpu python tools/chaos.py --plan "..." \
        --workload my_pkg.my_module:main

Built-in workloads (synthetic, seconds-scale): ``bcd`` (checkpointed
block coordinate descent), ``ooc`` (out-of-core streamed BCD — spills a
FeatureBlockStore, exercising blockstore.*), ``lbfgs`` (chunk-
checkpointed dense L-BFGS), ``stream`` (a resilient StreamDataset
sweep), ``kernel`` (checkpointed out-of-core kernel BCD — spills a
RowBlockStore and sweeps gram blocks, exercising blockstore.* +
kernel.sweep + ckpt.*), ``nethost`` (a live 2-worker CROSS-HOST TCP
fleet — ``serve/net.py`` — severed by a seeded network partition
mid-wave and required to heal with zero lost futures), ``rollout``
(a guarded canary rollout — ``serve/rollout.py`` — of a bad model
version under the seeded ``poison_flood`` zoo workload from
``tools/workloads.py``: the canary generation must concentrate the
failures, the judge must roll back and quarantine the version in the
registry, the watcher must refuse to redeploy it, and zero futures
may hang across the abandoned staged generation).

Network plans: the ``serve.net.connect``/``serve.net.send``/
``serve.net.recv`` sites take ``drop`` (the frame vanishes — silence,
not an error; ``partition`` is a grammar alias for it, so
``serve.net.send:ctx.link=NAME:partition`` reads as what it does),
``delay``, ``hang``, and ``corrupt`` (a flipped byte the far side's
CRC condemns).  Context-match on ``ctx.link=<worker>`` to sever one
worker's link; both directions (send + recv) make a full partition.

Latency plans (``delay=SECONDS`` / ``hang`` actions) are first-class:
pair them with ``--stage-deadline`` / ``--stream-timeout`` (and
``--stage-retries``) so the deadline/watchdog/breaker layer
(``utils/guard.py``) converts injected stalls into retried or degraded
operations, and the report's ``guard`` section shows deadline hits,
breaker opens, and degraded nodes alongside the per-site fault counts.

Exit code 0 = workload completed under the plan (all injected faults
survived); 1 = the workload failed — the report's ``error`` names the
escaping fault/exception; 2 = the workload completed but a site named
in the plan never injected (``not-exercised`` — a typo'd trigger or a
workload that never reaches the site must not read as a green chaos
run).

**Soak mode** (``--soak SECONDS [--seed N] [--soak-replicas R |
--soak-workers W]``) stands up a live replica fleet (supervisor +
hedging on; ``--soak-workers`` promotes it to a PROCESS fleet and adds
seeded mid-wave worker SIGKILLs to the menu) and loops
seeded randomized multi-site plans over the ``serve.*`` sites — worker
crashes, flush failures, injected delays — submitting a request wave
under each plan and requiring EVERY future to resolve (result or typed
error).  Exit 1 on any hung/lost request, or on a fleet that cannot
serve a clean wave once the soak ends.  The plan sequence is
deterministic in the seed, so a failing soak replays exactly.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from concurrent.futures import TimeoutError as _FutTimeout

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bcd(tmp, restarts):
    import numpy as np

    from keystone_tpu.models import BlockLeastSquaresEstimator
    from keystone_tpu.workflow import Dataset, fit_with_recovery

    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 48)).astype(np.float32)
    y = rng.normal(size=(256, 4)).astype(np.float32)
    ckpt = os.path.join(tmp, "bcd-ckpt")

    class CheckpointedBLS(BlockLeastSquaresEstimator):
        def fit_dataset(self, data, labels=None):
            return self.fit_checkpointed(data, labels, checkpoint_dir=ckpt)

    est = CheckpointedBLS(block_size=16, num_iter=4, lam=1e-3)
    fit_with_recovery(
        lambda: est.with_data(Dataset(x), Dataset(y)),
        state_dir=tmp,
        max_restarts=restarts,
    )


def _ooc(tmp, restarts):
    import numpy as np

    from keystone_tpu.loaders.stream import batched
    from keystone_tpu.models import BlockLeastSquaresEstimator
    from keystone_tpu.workflow import Dataset, StreamDataset, fit_with_recovery

    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 48)).astype(np.float32)
    y = rng.normal(size=(256, 4)).astype(np.float32)
    est = BlockLeastSquaresEstimator(block_size=16, num_iter=2, lam=1e-3)
    fit_with_recovery(
        lambda: est.with_data(
            StreamDataset(batched(x, 64), n=x.shape[0]), Dataset(y)
        ),
        max_restarts=restarts,
    )


def _kernel(tmp, restarts):
    """Out-of-core kernel BCD under fault: the row-block spill rides
    blockstore.read/write, each diag step fires kernel.sweep, and the
    per-epoch (α, F) checkpoint rides ckpt.save/load — so a plan over
    any of those proves the sweep resumes from the last completed epoch
    instead of restarting (or worse, trusting torn state)."""
    import numpy as np

    from keystone_tpu.loaders.stream import batched
    from keystone_tpu.models import KernelRidgeRegressionEstimator
    from keystone_tpu.models.kernel_ridge import GaussianKernelGenerator
    from keystone_tpu.workflow import Dataset, StreamDataset, fit_with_recovery

    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 16)).astype(np.float32)
    y = rng.normal(size=(128, 2)).astype(np.float32)
    ckpt = os.path.join(tmp, "krr-ckpt")

    class CheckpointedKRR(KernelRidgeRegressionEstimator):
        def fit_dataset(self, data, labels=None):
            return self.fit_stream_dataset(
                data,
                labels,
                spill_dir=os.path.join(tmp, "krr-store"),
                checkpoint_dir=ckpt,
            )

    est = CheckpointedKRR(
        GaussianKernelGenerator(0.05), lam=1e-3, block_size=32, num_epochs=3
    )
    fit_with_recovery(
        lambda: est.with_data(
            StreamDataset(batched(x, 64), n=x.shape[0]), Dataset(y)
        ),
        state_dir=tmp,
        max_restarts=restarts,
    )


def _lbfgs(tmp, restarts):
    import numpy as np

    from keystone_tpu.models.lbfgs import DenseLBFGSwithL2
    from keystone_tpu.workflow import Dataset, fit_with_recovery

    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 16)).astype(np.float32)
    y = rng.normal(size=(128, 2)).astype(np.float32)
    ckpt = os.path.join(tmp, "lbfgs-ckpt")

    class CheckpointedLBFGS(DenseLBFGSwithL2):
        def fit_dataset(self, data, labels=None):
            return self.fit_checkpointed(
                data, labels, checkpoint_dir=ckpt, checkpoint_every=3
            )

    est = CheckpointedLBFGS(lam=1e-3, num_iterations=9, history=4)
    fit_with_recovery(
        lambda: est.with_data(Dataset(x), Dataset(y)),
        max_restarts=restarts,
    )


def _stream(tmp, restarts):
    import numpy as np

    from keystone_tpu.loaders.stream import batched
    from keystone_tpu.workflow.dataset import StreamDataset

    from keystone_tpu.utils.guard import env_float

    rng = np.random.default_rng(0)
    x = rng.normal(size=(512, 8)).astype(np.float32)
    ds = StreamDataset(
        batched(x, 32),
        n=512,
        retries=3,
        # env_float: "0" means disabled, same as every other guard knob
        timeout=env_float("KEYSTONE_STREAM_TIMEOUT"),
    )
    total = sum(np.asarray(b).shape[0] for b in ds.batches())
    if total != 512:
        raise RuntimeError(f"stream delivered {total}/512 rows")


def _serve_artifacts(tmp, restarts):
    """The AOT artifact ladder under fault: publish a model WITH
    pre-lowered artifacts, then deploy → predict → hot-swap → heal a
    crashed worker, all while the plan batters ``serve.artifact_load``
    (corrupt the blobs, fail the reads, stall them).  The contract
    being proven: a damaged or missing artifact degrades that
    deploy/swap/heal to recompilation — it NEVER fails it, and
    predictions keep flowing."""
    import numpy as np

    from keystone_tpu.serve import ModelRegistry, serve
    from tools.workloads import build_pipeline

    dim = 16
    reg = ModelRegistry(os.path.join(tmp, "registry"))
    example = np.zeros((dim,), np.float32)
    for seed in (0, 1):
        pipe = build_pipeline(dim=dim, seed=seed)
        bundle = pipe.freeze().export_artifacts(example=example, buckets=(4, 8))
        reg.publish(pipe, artifacts=bundle)
    fitted, version = reg.load("v0001")
    arts = reg.load_artifacts(version)
    svc = serve(
        fitted,
        max_batch=8,
        buckets=(4, 8),
        example=example,
        name="chaos_artifacts",
        replicas=2,
        supervise=True,
        supervise_interval_s=0.05,
        artifacts=arts,
    )
    rng = np.random.default_rng(3)
    x = rng.normal(size=(dim,)).astype(np.float32)
    try:
        y0 = np.asarray(svc.submit(x).result(timeout=30.0))
        # hot-swap to v0002, loading its artifacts under the plan
        fitted2, v2 = reg.load("v0002")
        svc.swap(fitted2, version=v2, artifacts=reg.load_artifacts(v2))
        np.asarray(svc.submit(x).result(timeout=30.0))
        # heal: crash one worker, require the supervisor to rejoin it
        from keystone_tpu import faults as _faults

        with _faults.inject("serve.worker:ctx.replica=0:raise:times=1"):
            deadline = time.time() + 30.0
            while time.time() < deadline:
                try:
                    svc.submit(x).result(timeout=10.0)
                except Exception:
                    pass
                if svc.supervisor.restarts_total >= 1:
                    break
                time.sleep(0.01)
        if svc.supervisor.restarts_total < 1:
            raise RuntimeError("supervisor never healed the crashed worker")
        y1 = np.asarray(svc.submit(x).result(timeout=30.0))
        if not np.all(np.isfinite(y1)):
            raise RuntimeError("post-heal prediction is non-finite")
        del y0
    finally:
        svc.close()


def _tenants(tmp, restarts):
    """Multi-tenant blast-radius isolation: two tenants sharing a
    featurization prefix behind one fleet, a wave of traffic per tenant
    under the active plan.  Tenant-targeted plans
    (``serve.enqueue:ctx.tenant=a:raise`` /
    ``serve.batch:ctx.tenant=a:raise``) may fail tenant ``a``'s
    requests — every failure must be TYPED (no hung future), and
    tenant ``b``'s wave must complete 100% clean: one tenant's
    poison/overload can never shed another's traffic.  Raises (chaos
    exit 1) on any cross-tenant failure or unresolved future."""
    import numpy as np

    from keystone_tpu.serve import serve_multi
    from tools.workloads import build_tenant_models

    dim = 16
    models = build_tenant_models(tenants=2, dim=dim, branches=3)
    # chaos plans say ctx.tenant=a / ctx.tenant=b
    models = {"a": models.pop("t0"), "b": models.pop("t1")}
    svc = serve_multi(
        models,
        max_batch=8,
        max_wait_ms=2.0,
        queue_bound=64,
        example=np.zeros((dim,), np.float32),
        name="chaos_tenants",
    )
    rng = np.random.default_rng(5)
    xs = rng.normal(size=(24, dim)).astype(np.float32)
    try:
        futs = {"a": [], "b": []}
        for i in range(xs.shape[0]):
            for t in ("a", "b"):
                try:
                    futs[t].append(svc.submit(xs[i], tenant=t))
                except Exception:
                    # admission refusal IS a typed terminal (the
                    # targeted tenant's faults land here too)
                    futs[t].append(None)
        b_failures = 0
        for t, fs in futs.items():
            for f in fs:
                if f is None:
                    if t == "b":
                        b_failures += 1
                    continue
                try:
                    y = np.asarray(f.result(timeout=30.0))
                    if not np.all(np.isfinite(y)):
                        raise RuntimeError(f"tenant {t} non-finite result")
                except RuntimeError:
                    raise
                except Exception:
                    if t == "b":
                        b_failures += 1
        if b_failures:
            raise RuntimeError(
                f"cross-tenant blast radius: {b_failures} tenant-b "
                "request(s) failed under a tenant-a-targeted plan"
            )
    finally:
        svc.close()


def _kill_live_worker(svc, pick) -> bool:
    """SIGKILL one live worker process of a process-backed service —
    THE seeded kill action, shared by the ``procfleet`` workload and
    the soak loop (two drifting copies would silently test different
    behavior).  ``pick``: seeded index chooser, ``callable(n) -> int``.
    Returns whether a kill landed."""
    import signal as _signal

    pids = [
        r.get("pid")
        for r in svc.replica_statuses()
        if r.get("worker_alive") and r.get("pid")
    ]
    if not pids:
        return False
    try:
        os.kill(pids[int(pick(len(pids)))], _signal.SIGKILL)
        return True
    except OSError:
        return False


class _ChaosCheckFailed(RuntimeError):
    """A workload's OWN acceptance check failed (non-finite result,
    hung future, unhealthy exit wave) — distinct from RuntimeError-
    typed terminal failures the serve layer legitimately answers
    (FleetUnavailable, RemoteApplyError), which are acceptable
    outcomes, not chaos failures."""


def _procfleet(tmp, restarts):
    """The process fleet under seeded kill/hang chaos: a workers=2
    service takes waves of traffic while the workload SIGKILLs live
    worker processes between (and during) waves and the active plan
    batters the parent-side serve sites.  The contract being proven is
    PR-15's promotion invariant: a worker process death loses NOTHING
    — in-flight flushes requeue onto the supervisor's replacement,
    every submitted future resolves (result or typed failure; a hung
    future raises → chaos exit 1), and after the last kill a clean
    wave serves 100% with bit-finite results."""
    from concurrent.futures import TimeoutError as _FTimeout

    import numpy as np

    from tools.workloads import build_service

    dim = 8
    svc, item_shape = build_service(
        dim=dim,
        max_batch=8,
        max_wait_ms=2.0,
        queue_bound=256,
        deadline_ms=None,
        workers=2,
        supervise_interval_s=0.1,
        heartbeat_s=5.0,
        restart_limit=10_000,
    )
    rng = np.random.default_rng(7 + int(restarts))
    xs = rng.normal(size=(32,) + tuple(item_shape)).astype(np.float32)
    hung = 0
    try:
        for wave in range(4):
            futs = []
            for i in range(xs.shape[0]):
                try:
                    futs.append(svc.submit(xs[i]))
                except Exception:
                    futs.append(None)  # typed admission refusal
                if i == 10:
                    # mid-wave: kill a seeded-random live worker
                    _kill_live_worker(svc, lambda n: int(rng.integers(n)))
            for f in futs:
                if f is None:
                    continue
                try:
                    y = np.asarray(f.result(timeout=30.0))
                    if not np.all(np.isfinite(y)):
                        raise _ChaosCheckFailed(
                            "non-finite result after a kill"
                        )
                except _FTimeout:
                    hung += 1
                except _ChaosCheckFailed:
                    raise
                except Exception:
                    pass  # typed failure (FleetUnavailable, remote
                    # errors): an acceptable terminal
        if hung:
            raise _ChaosCheckFailed(
                f"{hung} future(s) hung across worker SIGKILLs — "
                "the process fleet lost admitted work"
            )
        # exit gate: with the kills over, a clean wave must serve 100%
        deadline = time.monotonic() + 30.0
        clean = 0
        while clean < xs.shape[0] and time.monotonic() < deadline:
            clean = 0
            waiters = []
            for i in range(xs.shape[0]):
                try:
                    waiters.append(svc.submit(xs[i]))
                except Exception:
                    pass
            for f in waiters:
                try:
                    f.result(timeout=30.0)
                    clean += 1
                except Exception:
                    pass
            if clean < xs.shape[0]:
                time.sleep(0.2)
        if clean < xs.shape[0]:
            raise _ChaosCheckFailed(
                f"fleet unhealthy after kills: clean wave served "
                f"{clean}/{xs.shape[0]}"
            )
    finally:
        svc.close()


def _nethost(tmp, restarts):
    """The cross-host TCP fleet under a seeded network partition: a
    workers=2 ``hosts=`` service (serve/net.py — every replica is a
    spawned ``keystone worker --connect`` process under a heartbeat
    lease) takes waves of traffic while the workload severs one
    worker's link mid-wave — a ``serve.net.send``/``serve.net.recv``
    ``drop`` plan held for ~3 lease windows, the ``partition`` alias
    of the plan grammar.  The contract being proven is the PR's
    partition invariant: the router declares the silent worker dead at
    lease expiry and re-serves its in-flight flush on the survivor
    (zero lost futures — a hung future raises → chaos exit 1), the
    fenced worker discards its stale result and rejoins with a fresh
    lease once the partition heals, and after the heal a clean wave
    serves 100% from a 2-live fleet."""
    import threading as _threading
    from concurrent.futures import TimeoutError as _FTimeout

    import numpy as np

    from keystone_tpu import faults as _faults
    from tools.workloads import build_service

    dim = 8
    svc, item_shape = build_service(
        dim=dim,
        max_batch=8,
        max_wait_ms=2.0,
        queue_bound=256,
        deadline_ms=None,
        workers=2,
        hosts=["local", "local"],
        supervise_interval_s=0.1,
        heartbeat_s=10.0,
        restart_limit=10_000,
        worker_opts={"lease_s": 1.0, "spawn_grace_s": 3.0},
    )
    rng = np.random.default_rng(11 + int(restarts))
    xs = rng.normal(size=(32,) + tuple(item_shape)).astype(np.float32)
    hung = 0
    severs: list = []
    try:
        links = sorted(
            r.get("link")
            for r in svc.replica_statuses()
            if r.get("link")
        )
        if len(links) < 2:
            raise _ChaosCheckFailed(
                f"net fleet came up with links {links!r}; expected 2"
            )

        def _sever(victim: str) -> None:
            # both directions of the victim's link drop on the router
            # side: its beats stop arriving (lease expiry → declared
            # dead) AND the router's frames stop reaching it (the
            # worker's own lease lapses → self-fence).  ~3 lease
            # windows is long past expiry on both sides.
            plan = (
                f"serve.net.send:ctx.link={victim}:drop;"
                f"serve.net.recv:ctx.link={victim}:drop"
            )
            with _faults.inject(plan):
                time.sleep(3.0)

        for wave in range(4):
            futs = []
            for i in range(xs.shape[0]):
                try:
                    futs.append(svc.submit(xs[i]))
                except Exception:
                    futs.append(None)  # typed admission refusal
                if wave == 1 and i == 10:
                    # mid-wave: sever a seeded-random worker's link
                    victim = links[int(rng.integers(len(links)))]
                    th = _threading.Thread(
                        target=_sever, args=(victim,), daemon=True
                    )
                    th.start()
                    severs.append(th)
            for f in futs:
                if f is None:
                    continue
                try:
                    y = np.asarray(f.result(timeout=60.0))
                    if not np.all(np.isfinite(y)):
                        raise _ChaosCheckFailed(
                            "non-finite result across a partition"
                        )
                except _FTimeout:
                    hung += 1
                except _ChaosCheckFailed:
                    raise
                except Exception:
                    pass  # typed failure: an acceptable terminal
        for th in severs:
            th.join(timeout=30.0)
        if hung:
            raise _ChaosCheckFailed(
                f"{hung} future(s) hung across the partition — "
                "the cross-host fleet lost admitted work"
            )
        # heal gate: the fenced worker must rejoin (fresh lease) —
        # 2 live workers before the clean wave is demanded
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            live = [
                r
                for r in svc.replica_statuses()
                if r.get("worker_alive")
            ]
            if len(live) >= 2:
                break
            time.sleep(0.2)
        else:
            raise _ChaosCheckFailed(
                "fleet never healed to 2 live workers after the "
                "partition lifted"
            )
        # exit gate: with the partition healed, a clean wave serves 100%
        deadline = time.monotonic() + 30.0
        clean = 0
        while clean < xs.shape[0] and time.monotonic() < deadline:
            clean = 0
            waiters = []
            for i in range(xs.shape[0]):
                try:
                    waiters.append(svc.submit(xs[i]))
                except Exception:
                    pass
            for f in waiters:
                try:
                    f.result(timeout=30.0)
                    clean += 1
                except Exception:
                    pass
            if clean < xs.shape[0]:
                time.sleep(0.2)
        if clean < xs.shape[0]:
            raise _ChaosCheckFailed(
                f"fleet unhealthy after the partition: clean wave "
                f"served {clean}/{xs.shape[0]}"
            )
    finally:
        svc.close()


def _rollout(tmp, restarts):
    """The guarded-rollout drill: a good version serves live while a
    BAD version (``tools/workloads.py`` MarkerGate — fails exactly the
    rows the seeded ``poison_flood`` scenario floods) is canaried at
    50% of traffic.  The contract being proven is PR-19's guard
    invariant: canary-hashed requests concentrate the failures on the
    staged generation while live traffic stays clean, the judge rolls
    back on the error-rate guardrail and QUARANTINES the version in
    the registry (checksummed ``BAD`` sidecar), the watcher refuses to
    redeploy the quarantined version even with ``CURRENT`` pointing at
    it, every future across the abandoned staged generation resolves
    (a hung future raises → chaos exit 1), and a clean final wave
    serves 100% from the untouched live generation."""
    import threading as _threading
    from concurrent.futures import TimeoutError as _FTimeout

    import numpy as np

    from keystone_tpu.obs import metrics as _metrics
    from keystone_tpu.serve import (
        ModelRegistry,
        RegistryWatcher,
        RolloutConfig,
        serve,
    )
    from keystone_tpu.serve.rollout import CanaryController
    from tools import workloads as zoo

    dim = 8
    reg = ModelRegistry(os.path.join(tmp, "registry"))
    good = zoo.build_zoo_pipeline(dim=dim, scale=2.0, gate=False)
    bad = zoo.build_zoo_pipeline(dim=dim, scale=3.0, gate=True)
    v1 = reg.publish(good)
    v2 = reg.publish(bad, set_current=False)
    fitted, ver = reg.load(v1)
    svc = serve(
        fitted,
        version=ver,
        max_batch=8,
        max_wait_ms=2.0,
        queue_bound=512,
        example=np.zeros((dim,), np.float32),
        name="chaos_rollout",
        replicas=2,
        slo_ms=250.0,
    )
    scenario = zoo.make_scenario(
        "poison_flood", seed=int(restarts), duration_s=2.0, qps=300.0, dim=dim
    )
    flood_at = scenario.duration_s / 3.0
    futs: list = []
    futs_lock = _threading.Lock()

    def _submit(event, rows):
        try:
            fs = svc.submit_many(rows)
        except Exception:
            return None  # typed admission refusal: a scheduled outcome
        with futs_lock:
            futs.extend(fs)
        return len(fs)

    pump = _threading.Thread(
        target=lambda: zoo.play(scenario, _submit, time_scale=1.0),
        daemon=True,
    )
    try:
        pump.start()
        # judge inside the flood window: the scenario's clean warmup
        # third would otherwise commit the bad version before the first
        # marker row arrives
        time.sleep(flood_at)
        cfg = RolloutConfig(
            canary=0.5,
            seed=int(restarts),
            min_samples=16,
            decide_s=20.0,
            max_error_rate=0.1,
            insufficient="rollback",
        )
        info = CanaryController(svc, cfg, registry=reg).run(
            reg.load(v2)[0], version=v2
        )
        if info["verdict"] != "rolled_back":
            raise _ChaosCheckFailed(
                f"canary let the bad version through: {info!r}"
            )
        if svc.version != v1:
            raise _ChaosCheckFailed(
                f"service serves {svc.version!r} after rollback, not {v1!r}"
            )
        if reg.quarantined(v2) is None:
            raise _ChaosCheckFailed(
                f"rollback did not quarantine {v2} in the registry"
            )
        # the watcher must refuse the quarantined version even when an
        # operator (or a crashed deploy) points CURRENT straight at it
        reg.set_current(v2)
        RegistryWatcher(svc, reg, poll_seconds=3600.0)._poll_once()
        if svc.version != v1:
            raise _ChaosCheckFailed(
                "watcher redeployed a quarantined version"
            )
        reg.set_current(v1)
        pump.join(timeout=30.0)
        if pump.is_alive():
            raise _ChaosCheckFailed("workload pump never finished")
        hung = 0
        with futs_lock:
            pending = list(futs)
        for f in pending:
            try:
                f.result(timeout=30.0)
            except _FTimeout:
                hung += 1
            except Exception:
                pass  # typed failure (poison, shed): acceptable
        if hung:
            raise _ChaosCheckFailed(
                f"{hung} future(s) hung across the abandoned canary "
                "generation — the rollout lost admitted work"
            )
        if _metrics.REGISTRY.counter_total("serve.rollout.rollbacks") < 1:
            raise _ChaosCheckFailed("serve.rollout.rollbacks never counted")
        hist = svc.rollout_status()["history"]
        if not hist or hist[-1]["verdict"] != "rolled_back":
            raise _ChaosCheckFailed(
                f"rollout history missing the rollback: {hist!r}"
            )
        # exit gate: a clean marker-free wave serves 100% from the
        # live generation (norm fingerprints the GOOD version's scale)
        xs = np.random.default_rng(13).normal(size=(16, dim)).astype(
            np.float32
        )
        for i in range(xs.shape[0]):
            y = np.asarray(svc.submit(xs[i]).result(timeout=30.0))
            norm = float(np.linalg.norm(y))
            if abs(norm - 2.0) > 1e-3:
                raise _ChaosCheckFailed(
                    f"post-rollback result norm {norm:.4f} fingerprints "
                    "the wrong version (want 2.0, the good scale)"
                )
    finally:
        svc.close()


WORKLOADS = {
    "bcd": _bcd,
    "ooc": _ooc,
    "kernel": _kernel,
    "lbfgs": _lbfgs,
    "stream": _stream,
    "serve_artifacts": _serve_artifacts,
    "tenants": _tenants,
    "procfleet": _procfleet,
    "nethost": _nethost,
    "rollout": _rollout,
}

#: workloads that activate their own fault plan mid-run (a seeded
#: partition, a timed sever, a canaried bad version under a poison
#: flood) — runnable with no --plan at all
SELF_INJECTING = frozenset({"nethost", "rollout"})


# --------------------------------------------------------------- soak
#: the serve-path sites a soak plan draws from, with the actions each
#: may carry (worker crashes exercise the supervisor; delays exercise
#: hedging/shedding; raises exercise failure containment + bisection
#: charging).  `hang` is deliberately absent: an un-deadlined hang is
#: an hour-long stall, which is a test of the clock, not the fleet.
_SOAK_MENU = (
    ("serve.enqueue", ("raise",)),
    ("serve.batch", ("raise", "delay")),
    ("serve.replica", ("raise", "delay")),
    ("serve.worker", ("raise", "delay")),
)


def _soak_plan(rng) -> str:
    """One randomized (but seed-deterministic) multi-site plan clause
    set in the KEYSTONE_FAULTS grammar."""
    n_sites = rng.randint(1, 3)
    picks = rng.sample(range(len(_SOAK_MENU)), n_sites)
    clauses = []
    for i in picks:
        site, actions = _SOAK_MENU[i]
        action = actions[rng.randrange(len(actions))]
        times = rng.randint(1, 3)
        after = rng.randint(0, 4)
        if action == "delay":
            delay = round(rng.uniform(0.005, 0.05), 4)
            clauses.append(f"{site}:delay={delay}:after={after}:times={times}")
        else:
            clauses.append(f"{site}:raise:after={after}:times={times}")
    return ";".join(clauses)


def run_soak(
    seconds: float,
    seed: int = 0,
    replicas: int = 2,
    wave: int = 48,
    result_timeout: float = 30.0,
    workers: int = 0,
) -> dict:
    """Loop seeded randomized multi-site fault plans against a LIVE
    serving fleet; every submitted future must resolve (a completed
    result or a typed failure) — a future that never resolves is a
    LOST/HUNG request, the one outcome the self-healing layer must
    never produce.  Returns the report dict; the CLI exits non-zero on
    any hung request (or a fleet that cannot serve a clean wave at the
    end)."""
    import random as _random

    import numpy as np

    from keystone_tpu import faults
    from keystone_tpu.utils import guard as _guard

    from tools import workloads

    rng = _random.Random(seed)
    fleet_kw = (
        # process fleet soak (PR 15): worker PROCESSES behind the same
        # router — the plan menu still fires at the parent-side sites,
        # and the soak loop additionally SIGKILLs live workers
        dict(workers=workers)
        if workers
        else dict(replicas=replicas)
    )
    svc, item_shape = workloads.build_service(
        dim=8,
        max_batch=8,
        max_wait_ms=2.0,
        queue_bound=256,
        deadline_ms=None,
        # soak services heal aggressively: short heartbeat, fast sweep,
        # a restart budget the whole soak cannot exhaust
        supervise_interval_s=0.1,
        heartbeat_s=5.0,
        restart_limit=10_000,
        hedge_ms=25.0,
        **fleet_kw,
    )
    payload = np.random.default_rng(seed).normal(
        size=(wave,) + tuple(item_shape)
    ).astype(np.float32)
    report = {
        "seconds": seconds,
        "seed": seed,
        "replicas": replicas,
        "workers": workers,
        "iterations": 0,
        "submitted": 0,
        "completed": 0,
        "failed_typed": 0,
        "rejected": 0,
        "hung": 0,
        "process_kills": 0,
        "plans": [],
    }

    def _maybe_kill_worker() -> None:
        """Process-fleet soak action: SIGKILL a seeded-random live
        worker child mid-wave (the failure mode threads can't even
        have) — the supervisor must heal and no future may hang."""
        if _kill_live_worker(svc, rng.randrange):
            report["process_kills"] += 1

    try:
        end = time.monotonic() + float(seconds)
        while time.monotonic() < end:
            plan = _soak_plan(rng)
            report["iterations"] += 1
            report["plans"].append(plan)
            # process fleets get killed roughly every other iteration
            kill_at = (
                rng.randrange(wave) if workers and rng.random() < 0.5 else None
            )
            futs = []
            with faults.inject(plan):
                for i in range(wave):
                    if i == kill_at:
                        _maybe_kill_worker()
                    try:
                        futs.append(svc.submit(payload[i]))
                    except Exception:
                        report["rejected"] += 1
                    report["submitted"] += 1
                # resolve INSIDE the plan window: mid-flight faults on
                # in-flight futures are the point of the soak
                for f in futs:
                    try:
                        f.result(timeout=result_timeout)
                        report["completed"] += 1
                    except _FutTimeout:
                        report["hung"] += 1
                    except Exception:
                        report["failed_typed"] += 1
        # the exit gate: after the last plan, a clean wave must serve —
        # a fleet that "survived" the soak but can no longer serve is a
        # failure (give healing a moment to finish)
        clean_ok = 0
        deadline = time.monotonic() + result_timeout
        while clean_ok < wave and time.monotonic() < deadline:
            clean_ok = 0
            futs = []
            for i in range(wave):
                try:
                    futs.append(svc.submit(payload[i]))
                except Exception:
                    pass  # still healing: retry the wave below
            for f in futs:
                try:
                    f.result(timeout=result_timeout)
                    clean_ok += 1
                except Exception:
                    pass
            if clean_ok < wave:
                _guard.interruptible_sleep(0.2)
        report["clean_wave_completed"] = clean_ok
        report["clean_wave_size"] = wave
        report["healthy_after_soak"] = clean_ok == wave
    finally:
        svc.close()
    report["ok"] = report["hung"] == 0 and report["healthy_after_soak"]
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="run a workload under a KEYSTONE_FAULTS plan and "
        "report per-site injected/survived counts"
    )
    ap.add_argument(
        "--plan",
        default=None,
        help="fault plan, KEYSTONE_FAULTS grammar "
        "(e.g. 'ckpt.save:after=1:corrupt;blockstore.read:p=0.1:seed=7'). "
        "Required unless --soak is given.",
    )
    ap.add_argument(
        "--soak",
        type=float,
        default=None,
        metavar="SECONDS",
        help="soak mode: loop seeded randomized multi-site plans "
        "(serve.* sites) against a live replica fleet for SECONDS; "
        "exits non-zero on any lost/hung future or a fleet that cannot "
        "serve a clean wave afterwards.  Ignores --plan/--workload.",
    )
    ap.add_argument(
        "--seed",
        type=int,
        default=0,
        help="soak plan-generator seed (deterministic plan sequence)",
    )
    ap.add_argument(
        "--soak-replicas",
        type=int,
        default=2,
        help="fleet size for the soak service (pair with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU)",
    )
    ap.add_argument(
        "--soak-workers",
        type=int,
        default=0,
        help="run the soak over a PROCESS fleet of this many worker "
        "processes (0 = the threaded fleet): the soak loop then also "
        "SIGKILLs live workers mid-wave — every future must still "
        "resolve",
    )
    ap.add_argument(
        "--workload",
        default="bcd",
        help=f"one of {sorted(WORKLOADS)} or module.path:function",
    )
    ap.add_argument(
        "--restarts",
        type=int,
        default=1,
        help="fit_with_recovery restart budget for the built-in workloads",
    )
    ap.add_argument(
        "--tmp", default=None, help="scratch dir (default: a fresh tempdir)"
    )
    ap.add_argument(
        "--ledger",
        default=None,
        metavar="DIR",
        help="write a run ledger (JSONL spans/events) under DIR: per-"
        "restart fault stats() land there instead of being lost to "
        "reset_stats() between restart attempts, and the report reads "
        "per-site counts from the unified metrics registry "
        "(render with tools/obs_report.py)",
    )
    ap.add_argument(
        "--stage-deadline",
        type=float,
        default=None,
        metavar="SECONDS",
        help="per-stage watchdog budget for the workload "
        "(KEYSTONE_STAGE_DEADLINE): a hang injected at executor.stage "
        "becomes a retried/degraded stage instead of a stalled run",
    )
    ap.add_argument(
        "--stage-retries",
        type=int,
        default=None,
        metavar="N",
        help="stage retry budget (KEYSTONE_STAGE_RETRIES) — the budget "
        "deadline overruns are retried from",
    )
    ap.add_argument(
        "--stream-timeout",
        type=float,
        default=None,
        metavar="SECONDS",
        help="per-batch fetch watchdog for the 'stream' workload "
        "(KEYSTONE_STREAM_TIMEOUT): a hung source counts against the "
        "retry/bad-batch quota instead of blocking the iterator",
    )
    args = ap.parse_args(argv)

    if args.soak is not None:
        report = run_soak(
            args.soak,
            seed=args.seed,
            replicas=args.soak_replicas,
            workers=args.soak_workers,
        )
        print(json.dumps(report, indent=2))
        return 0 if report["ok"] else 1
    if args.plan is None and args.workload not in SELF_INJECTING:
        ap.error(
            "--plan is required (unless --soak, or a self-injecting "
            f"workload: {sorted(SELF_INJECTING)})"
        )

    if args.stage_deadline is not None:
        os.environ["KEYSTONE_STAGE_DEADLINE"] = str(args.stage_deadline)
    if args.stage_retries is not None:
        os.environ["KEYSTONE_STAGE_RETRIES"] = str(args.stage_retries)
    if args.stream_timeout is not None:
        os.environ["KEYSTONE_STREAM_TIMEOUT"] = str(args.stream_timeout)

    import tempfile

    from keystone_tpu import faults
    from keystone_tpu.obs import ledger as obs_ledger
    from keystone_tpu.obs import metrics

    # fail fast on grammar errors; a self-injecting workload (nethost
    # activates its own seeded partition plan mid-wave) may run with
    # no outer plan at all
    plan = (
        faults.parse_plan(args.plan)
        if args.plan is not None
        else faults.FaultPlan([], source="(workload self-injected)")
    )
    tmp = args.tmp or tempfile.mkdtemp(prefix="kst_chaos_")

    if args.workload in WORKLOADS:
        run = lambda: WORKLOADS[args.workload](tmp, args.restarts)  # noqa: E731
    else:
        modname, _, fnname = args.workload.partition(":")
        fn = getattr(importlib.import_module(modname), fnname or "main")
        run = fn

    led = None
    if args.ledger:
        led = obs_ledger.start_run(args.ledger)
        led.event("chaos.start", plan=args.plan, workload=args.workload)

    faults.reset_stats()
    metrics.reset()  # the report window starts here, registry included
    error = None
    with faults.inject(plan):
        try:
            run()
        except BaseException as e:  # report, don't crash the reporter
            error = f"{type(e).__name__}: {e}"

    stats = faults.stats()
    # the unified registry accumulates across restarts (reset_stats only
    # clears the faults-module window): prefer it for per-site counts so
    # the report and the ledger agree
    snap = metrics.snapshot()
    reg_sites = {}
    for key, v in (snap.get("counters") or {}).items():
        for name in ("faults.calls", "faults.injected"):
            if key.startswith(name + "{site="):
                site = key[len(name) + 6 : -1]
                reg_sites.setdefault(site, {"calls": 0, "injected": 0})
                reg_sites[site][
                    "calls" if name.endswith("calls") else "injected"
                ] += int(v)
    if reg_sites:
        stats = {
            site: {
                "calls": c["calls"],
                "injected": c["injected"],
            }
            for site, c in reg_sites.items()
        }
    escaped_site = None
    if error is not None and "injected fault at" in error:
        for site in faults.SITES:
            if repr(site) in error:
                escaped_site = site

    # every site the plan NAMES must appear in the report, even with
    # zero calls — a typo'd trigger (after=100 on a 5-call site) or a
    # workload that never reaches the site otherwise vanishes entirely
    # and the run reads green
    planned = {s.site for s in plan.specs}
    for site in planned:
        stats.setdefault(site, {"calls": 0, "injected": 0})

    def survived(site, counts):
        # only claim survival when it is attributable: a clean run
        # survived everything it was actually GIVEN; a planned site
        # that never injected is "not-exercised", not survived; an
        # escaped FaultInjected pins one site; any other failure (e.g.
        # a downstream CorruptStateError from a corrupt action) leaves
        # per-site survival unknown -> null
        if counts["injected"] == 0:
            return None
        if error is None:
            return counts["injected"]
        if site == escaped_site:
            return counts["injected"] - 1
        return None

    def verdict(site, counts):
        if counts["injected"] == 0:
            return "not-exercised" if site in planned else "no-injections"
        if error is None:
            return "survived"
        if site == escaped_site:
            return "escaped"
        return "unknown"

    not_exercised = sorted(
        site
        for site in planned
        if stats.get(site, {}).get("injected", 0) == 0
    )

    def _labeled(name, label):
        """{label_value: total} for one counter family in the snapshot."""
        out = {}
        prefix = name + "{" + label + "="
        for key, v in (snap.get("counters") or {}).items():
            if key == name:
                out[""] = out.get("", 0) + int(v)
            elif key.startswith(prefix) and key.endswith("}"):
                out[key[len(prefix) : -1]] = int(v)
        return out

    def _gauges_labeled(snapshot, name, label):
        """{label_value: gauge} for one gauge family in a snapshot."""
        out = {}
        prefix = name + "{" + label + "="
        for key, v in (snapshot.get("gauges") or {}).items():
            if key.startswith(prefix) and key.endswith("}"):
                out[key[len(prefix) : -1]] = v
        return out

    report = {
        "plan": args.plan,
        "workload": args.workload,
        "completed": error is None,
        "error": error,
        "not_exercised": not_exercised,
        "sites": {
            site: {
                "calls": counts["calls"],
                "injected": counts["injected"],
                "survived": survived(site, counts),
                "verdict": verdict(site, counts),
            }
            for site, counts in sorted(stats.items())
        },
        # the deadline/watchdog/breaker layer's outcomes (utils/guard.py)
        # — how injected latency was absorbed, from the same registry
        # the per-site counts come from — plus the serve fleet's
        # self-healing outcomes (supervisor restarts, quarantines,
        # batch bisections) when the workload ran a service
        "guard": {
            "deadline_exceeded": _labeled("guard.deadline_exceeded", "site"),
            "breaker_opens": _labeled("breaker.opens", "key"),
            "degraded": _labeled("executor.degraded", "node"),
            "replica_restarts": _labeled("serve.replica_restarts", "replica"),
            "quarantined": _gauges_labeled(snap, "serve.quarantined", "replica"),
            "bisections": int(
                (snap.get("counters") or {}).get("serve.bisections", 0)
            ),
            "poison": int((snap.get("counters") or {}).get("serve.poison", 0)),
            "hedges": int((snap.get("counters") or {}).get("serve.hedges", 0)),
        },
    }
    if led is not None:
        led.event(
            "faults.stats",
            final=True,
            completed=error is None,
            error=error,
            stats=report["sites"],
        )
        report["ledger"] = led.path
        obs_ledger.stop_run()
    print(json.dumps(report, indent=2))
    if error is not None:
        return 1
    # completed, but a named site never fired: the plan did not test
    # what it claims to test — fail the run so CI catches the typo
    return 2 if not_exercised else 0


if __name__ == "__main__":
    sys.exit(main())
