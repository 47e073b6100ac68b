"""Observability tests: metrics registry + run ledger + wiring.

Tier-1 coverage the ISSUE pins:

- metrics counters fire on blockstore read/write and durable retries;
- span nesting + JSONL schema round-trip;
- disabled-mode silence (no env, no ledger ⇒ no file, no file events —
  spans stay in memory; ``KEYSTONE_METRICS=0`` ⇒ no recording);
- a chaos run's ledger carries fault injected stats;
- REGRESSION: executor profile timings exclude retry backoff sleeps and
  failed attempts (they skewed ProfilingAutoCacheRule placement);
- e2e: a pipeline fit under ``KEYSTONE_OBS_DIR`` yields a ledger whose
  obs_report summary has per-stage spans, a solver convergence series,
  I/O counters, and memory watermarks.
"""

import glob
import json
import os
import sys

import jax
import numpy as np
import pytest

from keystone_tpu import faults
from keystone_tpu.obs import ledger, metrics
from keystone_tpu.workflow import Dataset, Pipeline

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools")
)

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    """Every test starts with a fresh registry, no active ledger, and no
    obs env — and leaves the process the same way."""
    monkeypatch.delenv(ledger.ENV_DIR, raising=False)
    monkeypatch.delenv(metrics.ENV_DISABLE, raising=False)
    ledger.attach(None)
    metrics.reset()
    yield
    ledger.stop_run()
    ledger.attach(None)
    metrics.reset()


def _events(path):
    return [json.loads(line) for line in open(path)]


def _run_events(directory):
    paths = glob.glob(os.path.join(directory, "run_*.jsonl"))
    assert len(paths) == 1, paths
    return paths[0], _events(paths[0])


# ------------------------------------------------------------- registry


def test_metrics_counters_gauges_histograms():
    metrics.inc("a.count")
    metrics.inc("a.count", 2, site="s")
    metrics.observe("a.lat", 0.02)
    metrics.gauge_max("a.peak", 10)
    metrics.gauge_max("a.peak", 4)  # watermark: lower sample is ignored
    snap = metrics.snapshot()
    assert snap["counters"]["a.count"] == 1.0
    assert snap["counters"]["a.count{site=s}"] == 2.0
    assert snap["gauges"]["a.peak"] == 10.0
    assert snap["histograms"]["a.lat"]["count"] == 1
    assert metrics.REGISTRY.counter_total("a.count") == 3.0
    text = metrics.REGISTRY.to_prometheus_text()
    assert 'a_count_total{site="s"} 2' in text
    assert "a_lat_bucket" in text


def test_metrics_disabled_records_nothing(monkeypatch):
    monkeypatch.setenv(metrics.ENV_DISABLE, "0")
    metrics.inc("x")
    metrics.observe("y", 1.0)
    metrics.gauge_max("z", 1.0)
    snap = metrics.snapshot()
    assert not snap["counters"] and not snap["gauges"] and not snap["histograms"]


def test_blockstore_read_write_counters_fire(tmp_path):
    from keystone_tpu.workflow.blockstore import FeatureBlockStore

    x = np.random.default_rng(0).normal(size=(32, 12)).astype(np.float32)
    store = FeatureBlockStore.from_array(str(tmp_path / "store"), x, 8)
    assert metrics.REGISTRY.counter_value("blockstore.writes") == 1.0
    written = metrics.REGISTRY.counter_value("blockstore.write_bytes")
    assert written == 2 * 32 * 8 * 4  # two zero-padded 8-wide f32 blocks
    store.read_block(0)
    assert metrics.REGISTRY.counter_value("blockstore.reads") == 1.0
    assert metrics.REGISTRY.counter_value("blockstore.read_bytes") == 32 * 8 * 4


def test_durable_retry_and_corruption_counters(tmp_path):
    from keystone_tpu.utils import durable

    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise OSError("transient")
        return "ok"

    assert durable.with_retries(flaky, retries=3, sleep=lambda _: None) == "ok"
    assert metrics.REGISTRY.counter_value("durable.retries") == 2.0

    p = tmp_path / "state.bin"
    p.write_bytes(b"payload")
    durable.write_checksum(str(p))
    p.write_bytes(b"tampered")
    with pytest.raises(durable.CorruptStateError):
        durable.verify_checksum(str(p))
    assert metrics.REGISTRY.counter_value("durable.corruption") == 1.0


# --------------------------------------------------------------- ledger


def test_span_nesting_and_jsonl_schema_roundtrip(tmp_path):
    led = ledger.start_run(str(tmp_path))
    with ledger.span("outer", node="A") as sp:
        sp.set(attempts=2)
        with ledger.span("inner"):
            ledger.event("tick", k=1)
    ledger.stop_run()

    path, events = _run_events(str(tmp_path))
    kinds = [e["kind"] for e in events]
    assert kinds == [
        "run_start",
        "span_start",
        "span_start",
        "event",
        "span_end",
        "span_end",
        "metrics",
        "run_end",
    ]
    # every event carries the required schema fields
    for e in events:
        assert {"ts", "run_id", "seq", "kind", "name"} <= set(e)
        assert e["run_id"] == led.run_id
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)
    outer_start = events[1]
    inner_start = events[2]
    tick = events[3]
    inner_end, outer_end = events[4], events[5]
    # nesting: inner's parent is outer's span id; the event nests in inner
    assert inner_start["parent"] == outer_start["span"]
    assert tick["parent"] == inner_start["span"]
    assert inner_end["span"] == inner_start["span"]
    # span_end carries duration and the attrs accumulated while open
    assert outer_end["seconds"] >= 0
    assert outer_end["attrs"]["attempts"] == 2
    assert outer_end["attrs"]["node"] == "A"


def test_disabled_mode_emits_nothing(tmp_path, monkeypatch):
    assert ledger.active() is None
    with ledger.span("s") as sp:
        # the span itself is always on (in memory); only the FILE is silent
        sp.set(attempts=1)
        ledger.event("e")
    ledger.solver_epoch("bcd", epoch=0)
    assert glob.glob(str(tmp_path / "*.jsonl")) == []
    assert ledger.recent_spans()[-1].name == "s"
    assert not ledger.solver_obs()  # the ring is not "active"
    # env-var activation flows through the same frontends
    monkeypatch.setenv(ledger.ENV_DIR, str(tmp_path))
    with ledger.span("s2") as sp:
        assert sp is not None
    assert len(glob.glob(str(tmp_path / "run_*.jsonl"))) == 1


def test_env_dir_activates_pipeline_fit_ledger(tmp_path, monkeypatch):
    """e2e: KEYSTONE_OBS_DIR + a real Pipeline.fit() ⇒ a JSONL ledger
    with a pipeline.fit span, per-stage executor spans, a solver
    convergence series, and a metrics snapshot obs_report can fold."""
    from keystone_tpu.models import BlockLeastSquaresEstimator
    from keystone_tpu.ops import LinearRectifier

    monkeypatch.setenv(ledger.ENV_DIR, str(tmp_path))
    rng = np.random.default_rng(0)
    x = Dataset(rng.normal(size=(96, 24)).astype(np.float32))
    y = Dataset(rng.normal(size=(96, 3)).astype(np.float32))
    pipe = Pipeline.of(LinearRectifier(0.0)).and_then(
        BlockLeastSquaresEstimator(block_size=8, num_iter=3, lam=1e-3), x, y
    )
    pipe.fit().block_until_ready()
    jax.effects_barrier()
    # close the env ledger so the JSONL is flushed and later tests are
    # isolated (the autouse fixture detaches; this closes)
    led = ledger.active()
    led.close()

    path, events = _run_events(str(tmp_path))
    names = {e["name"] for e in events}
    assert "pipeline.fit" in names
    stage_spans = [
        e for e in events if e["kind"] == "span_end" and e["name"] == "executor.stage"
    ]
    assert stage_spans, "no executor stage spans in ledger"
    assert all("retries" in (e.get("attrs") or {}) for e in stage_spans)
    solver = [e for e in events if e["name"] == "solver.epoch"]
    assert len(solver) == 3  # one per BCD epoch
    epochs = [e["attrs"]["epoch"] for e in solver]
    assert epochs == [0, 1, 2]
    assert all("objective" in e["attrs"] for e in solver)

    from obs_report import render, summarize

    summary = summarize(path)
    assert summary["stage_top"], summary
    assert summary["convergence"]["bcd"], summary
    assert summary["memory"]["host_max_rss_bytes"] is not None
    text = render(summary)
    assert "top stages by time" in text and "solver convergence" in text


def test_out_of_core_fit_ledger_has_io_and_convergence(tmp_path):
    """Streamed (out-of-core) fit: the ledger's summary carries
    blockstore I/O totals, the spill span, and the per-epoch series."""
    from keystone_tpu.loaders.stream import batched
    from keystone_tpu.models import BlockLeastSquaresEstimator
    from keystone_tpu.workflow.dataset import StreamDataset

    rng = np.random.default_rng(1)
    x = rng.normal(size=(128, 24)).astype(np.float32)
    y = rng.normal(size=(128, 3)).astype(np.float32)
    led = ledger.start_run(str(tmp_path))
    est = BlockLeastSquaresEstimator(block_size=8, num_iter=2, lam=1e-3)
    est.fit_dataset(StreamDataset(batched(x, 32), n=128), Dataset(y))
    jax.effects_barrier()
    path = led.path
    ledger.stop_run()

    from obs_report import summarize

    summary = summarize(path)
    assert summary["io"]["blockstore_read_bytes"] > 0
    assert summary["io"]["blockstore_write_bytes"] > 0
    series = summary["convergence"]["bcd.out_of_core"]
    assert [pt["epoch"] for pt in series] == [0, 1]
    assert all(pt["epoch_seconds"] > 0 for pt in series)
    names = {e["name"] for e in _events(path)}
    assert "solver.spill" in names


def test_chaos_run_ledger_contains_fault_stats(tmp_path):
    """A recovered chaos fit leaves (a) injected-fault counters in the
    unified registry (mirrored from faults.py) and (b) per-restart
    faults.stats events in the ledger, emitted BEFORE stats are lost to
    any reset between attempts."""
    from keystone_tpu.models import BlockLeastSquaresEstimator
    from keystone_tpu.workflow import fit_with_recovery

    rng = np.random.default_rng(2)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    y = rng.normal(size=(64, 2)).astype(np.float32)
    est = BlockLeastSquaresEstimator(block_size=8, num_iter=2, lam=1e-3)

    led = ledger.start_run(str(tmp_path))
    faults.reset_stats()
    # times=1 fails the (retry-less) first fit attempt; the restart
    # runs with the budget exhausted and completes
    with faults.inject("executor.stage:times=1:raise"):
        fit_with_recovery(
            lambda: est.with_data(Dataset(x), Dataset(y)), max_restarts=1
        )
    led.metrics_snapshot()
    path = led.path
    ledger.stop_run()

    assert (
        metrics.REGISTRY.counter_value("faults.injected", site="executor.stage")
        == 1.0
    )
    events = _events(path)
    stats_events = [e for e in events if e["name"] == "faults.stats"]
    assert stats_events, "no per-restart faults.stats event in ledger"
    st = stats_events[0]["attrs"]["stats"]
    assert st["executor.stage"]["injected"] == 1

    from obs_report import summarize

    summary = summarize(path)
    assert summary["faults"]["executor.stage"]["injected"] == 1
    assert summary["fault_restarts"]


# ---------------------------------------------------- executor timing fix


def test_profile_timings_exclude_backoff_and_failed_attempts():
    """REGRESSION (ISSUE 3 satellite): profile-mode stage timings used to
    start the clock before the retry loop, charging failed attempts AND
    backoff sleeps (≥50 ms each) to the stage — skewing cache placement.
    With one injected stage fault + retry, the successful attempt of a
    trivial transform must time far under the backoff floor."""
    from keystone_tpu.ops import LinearRectifier
    from keystone_tpu.utils import tracing

    rng = np.random.default_rng(3)
    data = Dataset(rng.normal(size=(32, 8)).astype(np.float32))
    pipe = Pipeline.of(LinearRectifier(0.0))

    from keystone_tpu.workflow.pipeline import PipelineEnv

    # warm-up pass: pays the one-time trace/compile of the stage so the
    # faulted run below times pure (sub-ms) compute, not compilation
    warm = tracing.stage_timings(pipe(data))
    assert any("LinearRectifier" in k for k in warm)

    metrics.reset()
    PipelineEnv.node_retries = 2
    try:
        # stage calls run in topological order (Dataset first): after=1
        # pins the injection to the LinearRectifier stage itself
        with faults.inject("executor.stage:after=1:times=1:raise"):
            timings = tracing.stage_timings(pipe(data))
    finally:
        PipelineEnv.node_retries = None
    hit = [k for k in timings if "LinearRectifier" in k]
    assert hit, timings
    # backoff's first delay is >= 50 ms; a timing that included it (or
    # the failed attempt) cannot come in under 40 ms
    assert timings[hit[0]] < 0.04, (
        f"stage timing {timings[hit[0]]:.3f}s includes retry backoff"
    )
    assert metrics.REGISTRY.counter_value("executor.stage_retries") >= 1.0
    assert metrics.REGISTRY.counter_total("executor.failed_attempt_seconds") > 0


def test_stream_retry_and_bad_batch_metrics():
    from keystone_tpu.loaders.stream import resilient

    calls = {"n": 0}

    def source():
        calls["n"] += 1

        def gen():
            yield np.zeros((4, 2))
            if calls["n"] < 99:  # always fails: batch 1 gets dropped
                raise OSError("flaky batch")
            yield np.ones((4, 2))

        return gen()

    src = resilient(source, retries=1, max_bad_batches=1, sleep=lambda _: None)
    delivered = list(src())
    assert len(delivered) == 1
    assert metrics.REGISTRY.counter_value("stream.retries") == 1.0
    assert metrics.REGISTRY.counter_value("stream.bad_batches") == 1.0
    snap = metrics.snapshot()
    assert any(
        k.startswith("stream.batch_seconds") for k in snap["histograms"]
    )


def test_solver_obs_numerics_bit_identical(tmp_path):
    """The observed program must compute the same bits as the inert one
    (the static obs flag only adds callbacks)."""
    from keystone_tpu.models import BlockLeastSquaresEstimator
    from keystone_tpu.models.gmm import GaussianMixtureModelEstimator

    rng = np.random.default_rng(4)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    y = rng.normal(size=(64, 2)).astype(np.float32)
    bcd = BlockLeastSquaresEstimator(block_size=8, num_iter=2, lam=1e-3)
    gmm = GaussianMixtureModelEstimator(3, max_iterations=3)

    m0 = bcd.fit_dataset(Dataset(x), Dataset(y))
    g0 = gmm.fit_dataset(Dataset(x))
    ledger.start_run(str(tmp_path))
    m1 = bcd.fit_dataset(Dataset(x), Dataset(y))
    g1 = gmm.fit_dataset(Dataset(x))
    jax.effects_barrier()
    ledger.stop_run()
    np.testing.assert_array_equal(np.asarray(m0.weights), np.asarray(m1.weights))
    np.testing.assert_array_equal(np.asarray(g0.means), np.asarray(g1.means))


# ------------------------------------------- ledger rotation (ISSUE 9)


def test_ledger_rotation_bounds_disk(tmp_path):
    """A size-capped RunLedger rotates the active file into numbered
    segments and prunes past keep-N — a long-lived serve --watch process
    under KEYSTONE_OBS_DIR cannot fill the disk."""
    rot0 = metrics.REGISTRY.counter_value("obs.ledger_rotations")
    led = ledger.RunLedger(str(tmp_path), max_bytes=2000, keep_segments=2)
    for i in range(400):
        led.event("rotation.filler", seconds=float(i))
    led.close()
    segments = sorted(
        p for p in os.listdir(tmp_path) if ".jsonl." in p
    )
    assert len(segments) == 2, segments  # oldest pruned down to keep-N
    # every suffix is numeric and monotonically increasing
    suffixes = [int(p.rsplit(".", 1)[1]) for p in segments]
    assert suffixes == sorted(suffixes)
    rotations = metrics.REGISTRY.counter_value("obs.ledger_rotations") - rot0
    assert rotations > 2  # more rotations happened than segments kept
    # the active file plus every kept segment is valid JSONL
    for name in segments + [os.path.basename(led.path)]:
        for line in open(os.path.join(tmp_path, name)):
            json.loads(line)
    # each segment stayed near the cap (one event of slack)
    for name in segments:
        assert os.path.getsize(os.path.join(tmp_path, name)) < 2000 + 500


def test_ledger_reopen_resumes_rotation_state(tmp_path):
    """Reopening an EXISTING run id (a restarted serve --watch process)
    must resume the byte count from the active file and the segment
    numbering past the highest kept suffix — restarting both at zero
    would overwrite a retained segment on the first rotation."""
    led = ledger.RunLedger(
        str(tmp_path), run_id="stable", max_bytes=1500, keep_segments=4
    )
    for i in range(120):
        led.event("rotation.filler", seconds=float(i))
    led.close()
    before = sorted(p for p in os.listdir(tmp_path) if ".jsonl." in p)
    assert before  # at least one rotation happened
    sizes = {
        p: os.path.getsize(os.path.join(tmp_path, p)) for p in before
    }
    led2 = ledger.RunLedger(
        str(tmp_path), run_id="stable", max_bytes=1500, keep_segments=4
    )
    assert led2._segment == max(int(p.rsplit(".", 1)[1]) for p in before)
    assert led2._bytes > 0  # counted the existing active file
    for i in range(120):
        led2.event("rotation.filler", seconds=float(i))
    led2.close()
    after = sorted(p for p in os.listdir(tmp_path) if ".jsonl." in p)
    # the first process's segments were continued past, never replaced
    for p in before:
        if p in after:  # not pruned by keep-N
            assert os.path.getsize(os.path.join(tmp_path, p)) == sizes[p]
    assert len(after) > len(before) or set(after) != set(before)


def test_ledger_rotation_env_knobs(tmp_path, monkeypatch):
    """KEYSTONE_OBS_MAX_BYTES / KEYSTONE_OBS_KEEP_SEGMENTS configure the
    env-activated ledger (the zero-code route)."""
    monkeypatch.setenv(ledger.ENV_MAX_BYTES, "1500")
    monkeypatch.setenv(ledger.ENV_KEEP_SEGMENTS, "1")
    led = ledger.RunLedger(str(tmp_path))
    assert led.max_bytes == 1500 and led.keep_segments == 1
    for i in range(200):
        led.event("rotation.filler", seconds=float(i))
    led.close()
    segments = [p for p in os.listdir(tmp_path) if ".jsonl." in p]
    assert len(segments) == 1
    # unset = unbounded (the historical default)
    monkeypatch.delenv(ledger.ENV_MAX_BYTES)
    led2 = ledger.RunLedger(str(tmp_path))
    assert led2.max_bytes is None
    led2.close()


# ------------------------- per-metric buckets + windowed histograms


def test_register_buckets_gives_ms_resolution():
    """Registered bounds apply to new histograms of that name and ride
    into the Prometheus rendering; unregistered names keep defaults."""
    metrics.register_buckets("bucketed.latency_seconds", metrics.LATENCY_MS_BUCKETS)
    metrics.observe("bucketed.latency_seconds", 0.003)
    metrics.observe("plain.latency_seconds", 0.003)
    text = metrics.REGISTRY.to_prometheus_text()
    assert 'bucketed_latency_seconds_bucket{le="0.0025"} 0' in text
    assert 'bucketed_latency_seconds_bucket{le="0.005"} 1' in text
    # the default grid has no 0.0025 bound
    assert 'plain_latency_seconds_bucket{le="0.0025"}' not in text
    assert 'plain_latency_seconds_bucket{le="0.005"} 1' in text


def test_register_buckets_preserves_kind_conflict_check():
    metrics.register_buckets("conflicted.seconds", (0.1, 1.0))
    with pytest.raises(metrics.MetricKindError):
        metrics.inc("conflicted.seconds")
    # and the registration (plus its histogram-kind claim) survives reset
    metrics.reset()
    with pytest.raises(metrics.MetricKindError):
        metrics.REGISTRY.set_gauge("conflicted.seconds", 1.0)
    assert metrics.REGISTRY.bucket_bounds("conflicted.seconds") == (0.1, 1.0)


def test_windowed_histogram_expires_old_intervals():
    """The ring covers only the window: samples older than
    window_seconds stop influencing the merged percentiles."""
    t = [0.0]
    wh = metrics.WindowedHistogram(
        "windowed.latency_seconds",
        window_seconds=10.0,
        intervals=5,
        bounds=metrics.LATENCY_MS_BUCKETS,
        clock=lambda: t[0],
    )
    for _ in range(50):
        wh.observe(4.0)  # slow epoch
    t[0] = 1.0
    for _ in range(50):
        wh.observe(0.002)
    m = wh.merged()
    assert m.count == 100
    assert wh.percentile(99) > 1.0  # the slow epoch dominates p99
    t[0] = 12.0  # the slow interval has aged out of the window
    for _ in range(50):
        wh.observe(0.002)
    assert wh.merged().count == 50
    assert wh.percentile(99) < 0.01
    # the cumulative registry series kept everything (feeds /metrics)
    snap = metrics.snapshot()["histograms"]["windowed.latency_seconds"]
    assert snap["count"] == 150


def test_windowed_histogram_percentiles_and_fraction():
    t = [0.0]
    wh = metrics.WindowedHistogram(
        "pct.latency_seconds",
        window_seconds=60.0,
        intervals=6,
        bounds=metrics.LATENCY_MS_BUCKETS,
        clock=lambda: t[0],
    )
    assert wh.percentile(99) is None  # empty window
    for v in (0.001, 0.002, 0.003, 0.004, 0.100):
        wh.observe(v)
    p50 = wh.percentile(50)
    assert 0.001 <= p50 <= 0.01
    assert wh.percentile(99) <= 0.100
    frac = wh.fraction_above(0.010)
    assert 0.1 <= frac <= 0.3  # 1 of 5 samples above 10 ms
    s = wh.summary()
    assert s["count"] == 5 and s["max"] == 0.100


def test_obs_report_covers_ingress_and_fleet_sections(tmp_path):
    """ISSUE 18: the offline report folds the front-end ingress block
    and the worker-shipped fleet series out of a metrics snapshot —
    per-label lines, never aggregated across workers."""
    led = ledger.start_run(str(tmp_path))
    reg = metrics.REGISTRY
    reg.inc("ingress.accepts", 3)
    reg.inc("ingress.bin_conns", 2)
    reg.inc("ingress.frames", 5)
    reg.inc("ingress.batch_rows", 40)
    reg.inc("ingress.frame_errors", 2, kind="magic")
    reg.observe("ingress.parse_seconds", 0.001)
    reg.observe("ingress.admit_seconds", 0.002)
    reg.observe(
        "serve.fleet.apply_seconds", 0.004, worker="w0", host="hA"
    )
    reg.observe(
        "serve.fleet.wire_rtt_seconds", 0.001, worker="w0", host="hA"
    )
    led.metrics_snapshot()
    path = led.path
    ledger.stop_run()

    from obs_report import render, summarize

    summary = summarize(path)
    ing = summary["ingress"]
    assert ing["accepts"] >= 3 and ing["bin_conns"] >= 2
    assert ing["frame_errors"].get("magic", 0) >= 2
    assert ing["parse_seconds"]["count"] >= 1
    fleet = summary["fleet"]
    apply_series = fleet["apply_seconds"]
    assert any("worker=w0" in k and "host=hA" in k for k in apply_series)
    assert any("worker=w0" in k for k in fleet["wire_rtt_seconds"])
    text = render(summary)
    assert "== ingress ==" in text
    assert "== fleet (worker-shipped) ==" in text
    assert "worker=w0" in text
