"""The one span primitive (``obs/ledger.py § span``): always on, in memory,
in any profiler session, in the JSONL ledger only when one is active; the
spans the workflow leaves; the names node programs get; and the benchmark's
readers of both (``benchmark/layers/``).
"""

import collections
import glob
import os
import sys
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import compile_log, harness  # noqa: E402
from keystone_tpu.obs import ledger  # noqa: E402
from keystone_tpu.workflow import Dataset, Pipeline, Transformer  # noqa: E402

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _no_ledger(monkeypatch):
    monkeypatch.delenv(ledger.ENV_DIR, raising=False)
    ledger.attach(None)
    yield
    ledger.stop_run()
    ledger.attach(None)


def _since(mark: int):
    """The ring's records newer than span id ``mark``."""
    return [r for r in ledger.recent_spans() if r.span_id > mark]


def _mark() -> int:
    with ledger.span("test.mark") as sp:
        pass
    return sp.span_id


def _toy_fit(n=96, d=24, k=3, seed=0):
    from keystone_tpu.models import BlockWeightedLeastSquaresEstimator
    from keystone_tpu.ops import LinearRectifier

    rng = np.random.default_rng(seed)
    x = Dataset(rng.normal(size=(n, d)).astype(np.float32))
    y = Dataset(np.where(rng.random((n, k)) < 0.3, 1.0, -1.0).astype(np.float32))
    pipe = Pipeline.of(LinearRectifier(0.0)).and_then(
        BlockWeightedLeastSquaresEstimator(block_size=8, num_iter=2, lam=1e-3,
                                           mixture_weight=0.5), x, y
    )
    return pipe.fit().block_until_ready()


# ------------------------------------------------------------------ the ring
def test_ring_records_parent_root_and_self_time():
    mark = _mark()
    with ledger.span("outer", node="A") as outer:
        with ledger.span("inner", n=1) as inner:
            inner.set(rows=2)
            with ledger.span("leaf"):
                pass
        with ledger.span("inner"):
            pass
    recs = {r.span_id: r for r in _since(mark)}
    assert [r.name for r in recs.values()] == ["leaf", "inner", "inner", "outer"]
    top = recs[outer.span_id]
    assert top.parent_id is None and top.root_id == top.span_id
    assert top.attrs == {"node": "A"}
    first = recs[inner.span_id]
    assert first.parent_id == top.span_id and first.root_id == top.span_id
    assert first.attrs == {"n": 1, "rows": 2}
    leaf = next(r for r in recs.values() if r.name == "leaf")
    assert leaf.parent_id == first.span_id and leaf.root_id == top.span_id
    own = ledger.self_seconds(list(recs.values()))
    inners = [r for r in recs.values() if r.name == "inner"]
    assert own[top.span_id] == pytest.approx(
        (top.dur_ns - sum(r.dur_ns for r in inners)) / 1e9
    )
    assert own[first.span_id] == pytest.approx((first.dur_ns - leaf.dur_ns) / 1e9)
    assert own[leaf.span_id] == pytest.approx(leaf.dur_ns / 1e9)
    assert all(v >= 0 for v in own.values())


def test_ring_nests_across_capture_context_threads():
    mark = _mark()
    with ledger.span("caller") as caller:
        token = ledger.capture_context()

        def work():
            ledger.restore_context(token)
            with ledger.span("worker.child"):
                pass

        t = threading.Thread(target=work)
        t.start()
        t.join(10)
        assert not t.is_alive()
    child = next(r for r in _since(mark) if r.name == "worker.child")
    assert child.parent_id == caller.span_id and child.root_id == caller.span_id
    # a thread that restores nothing starts a tree of its own
    t = threading.Thread(target=lambda: ledger.span("orphan").__enter__().__exit__())
    t.start()
    t.join(10)
    orphan = next(r for r in _since(mark) if r.name == "orphan")
    assert orphan.parent_id is None and orphan.root_id == orphan.span_id


def test_ring_is_bounded():
    assert ledger._RING.maxlen == ledger.RING_SIZE == 32768
    for _ in range(ledger.RING_SIZE + 10):
        with ledger.span("filler"):
            pass
    assert len(ledger.recent_spans()) == ledger.RING_SIZE


@pytest.mark.parametrize("value", [
    np.zeros(3, np.float32), jnp.ones(2), {"k": 1}, [np.zeros(2)], object(),
], ids=["numpy", "device_array", "dict", "list_of_arrays", "object"])
@pytest.mark.parametrize("how", ["at_open", "at_set"])
def test_non_scalar_attr_is_refused(value, how):
    if how == "at_open":
        with pytest.raises(TypeError, match="span attribute 'n'"):
            ledger.span("s", n=value)
        return
    with ledger.span("s") as sp:
        with pytest.raises(TypeError, match="span attribute 'n'"):
            sp.set(n=value)
    assert ledger.recent_spans()[-1].attrs == {}


def test_scalar_attrs_and_request_id_lists_are_kept():
    with ledger.span("s", node="a", n=3, seconds=0.5, degraded=True, error=None,
                     request_ids=["r1", None]):
        pass
    assert ledger.recent_spans()[-1].attrs == {
        "node": "a", "n": 3, "seconds": 0.5, "degraded": True, "error": None,
        "request_ids": ["r1", None],
    }


# ------------------------------------------------------- what a fit leaves
def test_fit_without_ledger_leaves_one_tree_and_no_file(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    sampled = []
    monkeypatch.setattr(ledger, "_sample_memory", lambda: sampled.append(1) or {})
    mark = _mark()
    _toy_fit()
    recs = _since(mark)
    fits = [r for r in recs if r.name == "pipeline.fit"]
    assert len(fits) == 1 and fits[0].parent_id is None
    tree = [r for r in recs if r.root_id == fits[0].span_id]
    names = collections.Counter(r.name for r in tree)
    for name in ("pipeline.optimize", "optimizer.rule", "executor.stage", "solver.fit"):
        assert names[name] >= 1, names
    assert names["pipeline.optimize"] == 2  # the rule batches, the post-fit re-fusion
    by_id = {r.span_id: r for r in tree}
    for r in tree:
        if r.name == "optimizer.rule":
            assert by_id[r.parent_id].name == "pipeline.optimize"
            how_it_went = set(r.attrs) - {"rule", "batch"}
            assert how_it_went == (
                {"to_place", "sampled", "priced", "price_hits", "waited_seconds"}
                if r.attrs["rule"] == "ProfiledMaterialize" else set()
            )
        if r.name == "solver.fit":
            assert by_id[r.parent_id].name == "executor.stage"
            # two sweeps, 24 rows a device on the suite's mesh, blocks of 8:
            # the three 8 × 8 float32 factors are kept across the sweeps
            assert r.attrs == {
                "solver": "bcd.weighted", "n": 96, "blocks": 3, "gram_panels": 1,
                "factor_cache": 3, "factor_cache_bytes": 3 * 8 * 8 * 4,
            }
        if r.name == "executor.stage":
            assert by_id[r.parent_id].name == "pipeline.fit"
            assert {"node", "node_id", "attempts", "retries"} <= set(r.attrs)
    # the fit's data went up before the fit opened: root spans of their own
    uploads = [r for r in recs if r.name == "dataset.upload" and r.parent_id is None]
    assert {r.attrs["bytes"] for r in uploads} == {96 * 24 * 4, 96 * 3 * 4}
    assert os.listdir(tmp_path) == [] and not sampled
    assert ledger.active() is None and not ledger.solver_obs()


def test_apply_leaves_a_pipeline_apply_tree_and_a_readback():
    fitted = _toy_fit()
    mark = _mark()
    held = np.random.default_rng(5).normal(size=(16, 24)).astype(np.float32)
    out = fitted(Dataset(held)).get().numpy()
    assert out.shape == (16, 3)
    recs = _since(mark)
    roots = [r.name for r in recs if r.parent_id is None]
    assert roots == ["dataset.upload", "pipeline.apply", "dataset.readback"]
    apply = next(r for r in recs if r.name == "pipeline.apply")
    under = collections.Counter(r.name for r in recs if r.root_id == apply.span_id)
    assert under["pipeline.optimize"] == 1 and under["executor.stage"] >= 2
    assert next(r for r in recs if r.name == "dataset.readback").attrs == {"bytes": 16 * 3 * 4}


def test_jsonl_ledger_gets_the_same_spans_and_root_memory(tmp_path):
    import json

    led = ledger.start_run(str(tmp_path))
    mark = _mark()
    _toy_fit()
    jax.effects_barrier()
    path = led.path
    ledger.stop_run()
    events = [json.loads(line) for line in open(path)]
    ends = {e["span"]: e for e in events if e["kind"] == "span_end"}
    ring = {r.span_id: r for r in _since(mark)}
    assert set(ring) <= set(ends)
    for span_id, r in ring.items():
        e = ends[span_id]
        assert e["name"] == r.name and e.get("parent") == r.parent_id
        assert e["seconds"] == pytest.approx(r.dur_ns / 1e9)
    fit_end = next(e for e in ends.values() if e["name"] == "pipeline.fit")
    assert "host_max_rss_bytes" in fit_end["attrs"]  # sampled at the ROOT's end only
    stage_ends = [e for e in ends.values() if e["name"] == "executor.stage"]
    assert stage_ends and not any("host_max_rss_bytes" in e["attrs"] for e in stage_ends)
    starts = {e["span"]: e for e in events if e["kind"] == "span_start"}
    optimize = [s for s in starts.values() if s["name"] == "pipeline.optimize"]
    # nodes: the graph's size before at the start, after at the end (the
    # re-fusion after the walk fuses the fitted mapper into its chain)
    sizes = [(s["attrs"]["nodes"], ends[s["span"]]["attrs"]["nodes"]) for s in optimize]
    assert len(sizes) == 2 and sizes[1][0] > sizes[1][1], sizes


def test_tracing_never_synchronises(tmp_path, monkeypatch):
    """With a JSONL ledger active ``fit_arrays`` blocks on the device exactly
    as often as without one."""
    from keystone_tpu.models import BlockWeightedLeastSquaresEstimator

    rng = np.random.default_rng(7)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    y = np.where(rng.random((64, 2)) < 0.5, 1.0, -1.0).astype(np.float32)
    est = BlockWeightedLeastSquaresEstimator(block_size=8, num_iter=2, lam=1e-3,
                                             mixture_weight=0.5)
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda v: calls.append(1) or real(v))

    def blocked() -> int:
        before = len(calls)
        est.fit_arrays(x, y)
        return len(calls) - before

    without = blocked()
    ledger.start_run(str(tmp_path))
    with_ledger = blocked()
    jax.effects_barrier()
    ledger.stop_run()
    assert with_ledger == without
    # the program's one way to wait: it returns what it waited for, as a span
    seen, mark, ones = len(calls), _mark(), jnp.ones(3)
    assert ledger.device_wait(ones) is ones and len(calls) == seen + 1
    assert [r.name for r in _since(mark)] == ["device.wait"]


@pytest.fixture
def waits_by_thread(monkeypatch):
    """``{thread id: waits for the device}``: every ``block_until_ready`` of
    an array, by method or batched."""
    from jax._src import api

    waits = collections.Counter()
    array_type = type(jnp.ones(1))
    real, real_batched = array_type.block_until_ready, api.xc.batched_block_until_ready

    def method(self):
        waits[threading.get_ident()] += 1
        return real(self)

    def batched(arrays):
        waits[threading.get_ident()] += 1
        return real_batched(arrays)

    monkeypatch.setattr(array_type, "block_until_ready", method)
    monkeypatch.setattr(api.xc, "batched_block_until_ready", batched)
    return waits


def test_the_calling_thread_waits_for_nothing_new(waits_by_thread):
    """A put hands its array to the watcher and goes on; a toy fit with a
    ``Cacher`` and a toy scoring call wait on the calling thread as often as
    before the waits had names (4 and 2: counted on the parent commit)."""
    me = threading.get_ident()
    rng = np.random.default_rng(0)
    assert ledger.drain_watches(30)  # earlier tests' puts
    waits_by_thread.clear()
    mark = _mark()
    Dataset(rng.normal(size=(32, 8)).astype(np.float32))
    assert waits_by_thread[me] == 0
    assert ledger.drain_watches(30)
    assert [r.name for r in _since(mark)] == ["dataset.upload", "dataset.transfer"]
    assert sum(waits_by_thread.values()) == 1  # the watcher's

    _cacher_fit(rng)  # mints and prices
    before = waits_by_thread[me]
    fitted = _cacher_fit(rng)
    assert waits_by_thread[me] - before == 4
    held = rng.normal(size=(16, 24)).astype(np.float32)
    fitted(Dataset(held)).get().numpy()
    before = waits_by_thread[me]
    fitted(Dataset(held)).get().numpy()
    assert waits_by_thread[me] - before == 2


# ------------------------------------------- the waits and the transfers
def _cacher_fit(rng, n=96, d=24, k=3):
    """A toy fit whose two branches share a node (the sampling pass has one
    to place) and whose features stand behind a ``Cacher``."""
    from keystone_tpu.models import BlockWeightedLeastSquaresEstimator
    from keystone_tpu.ops import LinearRectifier
    from keystone_tpu.workflow.transformer import Cacher

    x = Dataset(rng.normal(size=(n, d)).astype(np.float32))
    y = Dataset(np.where(rng.random((n, k)) < 0.3, 1.0, -1.0).astype(np.float32))
    features = Pipeline.gather([
        Pipeline.of(LinearRectifier(0.0)) | LinearRectifier(0.5),
        Pipeline.of(LinearRectifier(0.0)) | LinearRectifier(1.0),
    ]) | Cacher()
    return features.and_then(
        BlockWeightedLeastSquaresEstimator(block_size=8, num_iter=2, lam=1e-3,
                                           mixture_weight=0.5), x, y
    ).fit()


def test_a_cachers_sync_is_a_device_wait_under_its_stage():
    """A ``Cacher``'s sync is one ``device.wait`` under its own
    ``executor.stage`` (the pipeline's, and the one the rule placed behind the
    shared node), which the stage's self time leaves out; the sampling pass's
    syncs are ``device.wait`` spans under ``optimizer.rule``, and the rule's
    span carries their seconds."""
    mark = _mark()
    _cacher_fit(np.random.default_rng(2)).block_until_ready()
    recs = _since(mark)
    (fit,) = [r for r in recs if r.name == "pipeline.fit"]
    tree = {r.span_id: r for r in recs if r.root_id == fit.span_id}
    waits = [r for r in tree.values() if r.name == "device.wait"]
    walk = [w for w in waits if tree[w.parent_id].parent_id == fit.span_id]
    cachers = [r for r in tree.values() if r.name == "executor.stage"
               and r.parent_id == fit.span_id and r.attrs["node"] == "Cacher"]
    assert len(cachers) == 2 and [tree[w.parent_id] for w in walk] == cachers
    own = ledger.self_seconds(list(tree.values()))
    for stage, sync in zip(cachers, walk):
        assert own[stage.span_id] == pytest.approx((stage.dur_ns - sync.dur_ns) / 1e9)
    (rule,) = [r for r in tree.values() if r.name == "optimizer.rule"
               and r.attrs["rule"] == "ProfiledMaterialize"]
    assert rule.attrs["sampled"] == 1

    def under_rule(r):
        while r.parent_id is not None:
            r = tree[r.parent_id]
            if r.span_id == rule.span_id:
                return True
        return False

    sampled = [w for w in waits if under_rule(w)]
    assert sampled and len(sampled) + len(walk) == len(waits)
    assert rule.attrs["waited_seconds"] == pytest.approx(sum(w.dur_ns for w in sampled) / 1e9)


# ------------------------------------------------- the stage's ``chunks`` attr
class AddConst(Transformer):
    def __init__(self, c):
        self.c = float(c)

    def params(self):
        return (self.c,)

    def apply_batch(self, xs, mask=None):
        return xs + self.c


def _timit_rehearsal_fit(seed=7):
    """``timit-rf.fit``'s own graph (the adapter's) at the cell's rehearsal
    sizes — 256 frames of 24 floats, three cosine blocks of 32 — fitted
    once; (a function that scores the held-out rows through the fitted
    pipeline's own call, the spans the fit left, n)."""
    _, cell, cfg = harness.find_cell("timit-rf.fit")
    cfg.update(cell["rehearse"]["config"])
    cell.update(cell["rehearse"]["cell"])
    adapter = harness.load_module("adapters", cfg["adapter"])
    fit_loop = harness.load_module("drivers", cell["kind"])
    data = adapter.make_data(cfg, cell, seed)
    x, labels = adapter.fit_inputs(data, cell, 0)
    mark = _mark()
    fitted = adapter.build(cfg, cell, seed, *fit_loop.upload(x, labels, "chunks")).fit()
    fitted.block_until_ready()
    records = _since(mark)
    return lambda: adapter.held_out_answers(fitted, data["held_x"]), records, x.shape[0]


def _applies_by_class(monkeypatch):
    T = sys.modules["keystone_tpu.workflow.transformer"]
    calls = collections.Counter()
    real = T.Transformer._apply_batch_jitted

    def counted(self, xs, mask):
        calls[type(self).__name__] += 1
        return real(self, xs, mask)

    monkeypatch.setattr(T.Transformer, "_apply_batch_jitted", counted)
    return calls


def test_an_input_that_fits_in_one_chunk_is_one_apply_a_node(monkeypatch):
    """The cell's frames are within one chunk's bytes, so each of its nodes
    is ONE program over all of them though n is four canonical chunks: one
    ``_apply_batch_jitted`` a ``CosineRandomFeatures`` node, ``chunks`` 1 on
    every stage that applies a node; and the fit predicts what the same fit
    cut into canonical chunks (``KEYSTONE_APPLY_CHUNK`` forces one) does."""
    T = sys.modules["keystone_tpu.workflow.transformer"]
    monkeypatch.delenv("KEYSTONE_APPLY_CHUNK", raising=False)
    monkeypatch.setattr(T, "_apply_chunk_rows", lambda: 64)
    calls = _applies_by_class(monkeypatch)
    score, records, n = _timit_rehearsal_fit()
    assert n == 256 > 64 and n * 24 * 4 <= T._APPLY_CHUNK_BYTES
    (fit,) = [r for r in records if r.name == "pipeline.fit"]
    stages = [r for r in records if r.name == "executor.stage" and r.root_id == fit.span_id]
    cosine = [r for r in stages if r.attrs["node"] == "CosineRandomFeatures"]
    assert len(cosine) == 3 and calls["CosineRandomFeatures"] == 3
    applied = [r for r in stages if "chunks" in r.attrs]
    assert len(applied) > len(cosine) and all(r.attrs["chunks"] == 1 for r in applied)
    whole = score()
    x = np.random.default_rng(3).normal(size=(205, 6)).astype(np.float32)
    added = np.asarray(AddConst(1.5).apply_dataset(Dataset(x)).array)

    calls.clear()
    monkeypatch.setenv("KEYSTONE_APPLY_CHUNK", "64")
    score, records, _ = _timit_rehearsal_fit()
    cosine = [r for r in records if r.name == "executor.stage"
              and r.attrs["node"] == "CosineRandomFeatures" and r.attrs.get("chunks")]
    assert [r.attrs["chunks"] for r in cosine] == [4, 4, 4]
    assert calls["CosineRandomFeatures"] == 12
    cut = score()
    np.testing.assert_allclose(whole, cut, rtol=1e-4, atol=1e-4 * float(np.std(cut)))
    np.testing.assert_array_equal(
        added, np.asarray(AddConst(1.5).apply_dataset(Dataset(x)).array))


def test_an_input_over_one_chunks_bytes_still_reports_its_chunks(monkeypatch):
    T = sys.modules["keystone_tpu.workflow.transformer"]
    monkeypatch.delenv("KEYSTONE_APPLY_CHUNK", raising=False)
    monkeypatch.setattr(T, "_apply_chunk_rows", lambda: 64)
    monkeypatch.setattr(T, "_APPLY_CHUNK_BYTES", 205 * 6 * 4 - 1)  # the input, less a byte
    calls = _applies_by_class(monkeypatch)
    x = np.random.default_rng(5).normal(size=(205, 6)).astype(np.float32)
    mark = _mark()
    out = Pipeline.of(AddConst(1.5))(Dataset(x, shard=False)).get().numpy()
    (stage,) = [r for r in _since(mark) if r.name == "executor.stage"
                and r.attrs["node"] == "AddConst"]
    assert stage.attrs["chunks"] == -(-205 // 64) == 4 == calls["AddConst"]
    np.testing.assert_array_equal(out, x + np.float32(1.5))
    monkeypatch.setattr(T, "_APPLY_CHUNK_BYTES", 205 * 6 * 4)
    mark = _mark()
    Pipeline.of(AddConst(1.5))(Dataset(x, shard=False)).get().numpy()
    (stage,) = [r for r in _since(mark) if r.name == "executor.stage"
                and r.attrs["node"] == "AddConst"]
    assert stage.attrs["chunks"] == 1 and calls["AddConst"] == 5


def test_a_host_arrays_put_leaves_one_transfer_with_its_real_end():
    mark = _mark()
    with ledger.span("caller") as caller:
        data = Dataset(np.zeros((40, 6, 2), np.uint8))
    assert ledger.drain_watches(30)
    recs = _since(mark)
    (upload,) = [r for r in recs if r.name == "dataset.upload"]
    (transfer,) = [r for r in recs if r.name == "dataset.transfer"]
    assert (upload.parent_id, upload.root_id) == (caller.span_id, caller.span_id)
    assert (transfer.parent_id, transfer.root_id) == (upload.span_id, caller.span_id)
    assert transfer.t0_ns == upload.t0_ns and transfer.dur_ns > 0
    assert transfer.attrs == {"bytes": data.array.nbytes, "dtype": "uint8",
                              "shape": list(data.array.shape)}
    # an array that is on the device already is no put
    mark = _mark()
    Dataset(jnp.ones((8, 2)))
    assert ledger.drain_watches(30) and not _since(mark)


def test_a_deleted_array_ends_its_record_and_raises_nothing():
    gate = threading.Event()

    class Held:  # the watcher is busy with this one while the next is deleted
        def block_until_ready(self):
            gate.wait(30)

    mark = _mark()
    with ledger.span("dataset.upload", bytes=0) as upload:
        ledger.watch("dataset.transfer", Held(), upload, bytes=0)
        gone = jnp.ones(4) + 1
        ledger.watch("dataset.transfer", gone, upload, bytes=16)
    gone.delete()
    gate.set()
    assert ledger.drain_watches(30)
    first, second = [r for r in _since(mark) if r.name == "dataset.transfer"]
    assert first.attrs == {"bytes": 0}
    assert second.attrs == {"bytes": 16, "outcome": "deleted"}
    assert ledger._WATCHER.is_alive()


def test_many_threads_hand_off_to_one_watcher_and_lose_nothing():
    """Threads that put at once (serving replicas do) start one watcher
    between them, and every hand-off closes exactly one record."""
    threads, each = 32, 40
    ledger.drain_watches(30)
    watcher, ledger._WATCHER = ledger._WATCHER, None  # the start is raced too
    if watcher is not None:
        ledger._WATCHED.put(None)
        watcher.join(30)
    ready = jnp.ones(2).block_until_ready()
    mark, go = _mark(), threading.Event()

    def work(k):
        go.wait(30)
        with ledger.span("dataset.upload", bytes=k) as upload:
            for i in range(each):
                ledger.watch("dataset.transfer", ready, upload, bytes=k * each + i)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=work, args=(k,)) for k in range(threads)]
        for t in pool:
            t.start()
        go.set()
        for t in pool:
            t.join(60)
        assert not any(t.is_alive() for t in pool)
        assert ledger.drain_watches(60)
    finally:
        sys.setswitchinterval(interval)
    closed = [r for r in _since(mark) if r.name == "dataset.transfer"]
    assert sorted(r.attrs["bytes"] for r in closed) == list(range(threads * each))
    assert len({r.span_id for r in closed}) == threads * each
    uploads = {r.span_id: r for r in _since(mark) if r.name == "dataset.upload"}
    assert all(uploads[r.parent_id].attrs["bytes"] == r.attrs["bytes"] // each for r in closed)
    watchers = [t for t in threading.enumerate() if t.name == "keystone-obs-watcher"]
    assert watchers == [ledger._WATCHER]


def test_a_process_with_a_pending_transfer_exits_cleanly():
    import subprocess

    code = (
        "import numpy as np\n"
        "from keystone_tpu.workflow import Dataset\n"
        "from keystone_tpu.obs import ledger\n"
        "Dataset(np.zeros((4096, 1024), np.float32))\n"
        "print(sum(r.name == 'dataset.upload' for r in ledger.recent_spans()))\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    done = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == "1"


# ---------------------------------------------------- the profiler's clock
def test_profiler_session_holds_the_programs_spans(tmp_path):
    from jax.profiler import ProfileData

    _toy_fit(seed=1)  # compiled before the session
    _cacher_fit(np.random.default_rng(3))
    assert ledger.drain_watches(30)  # their puts are closed before the session opens
    with jax.profiler.trace(str(tmp_path)):
        _toy_fit(seed=1)
        _cacher_fit(np.random.default_rng(3)).block_until_ready()
        assert ledger.drain_watches(30)
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    host = [
        ev for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host")
        for line in plane.lines for ev in line.events
    ]
    fits = [ev for ev in host if ev.name == "pipeline.fit"]
    stages = [ev for ev in host if ev.name == "executor.stage"]
    assert len(fits) == 2 and len(stages) >= 3
    inside = 0
    for ev in stages:
        inside += any(
            f.start_ns <= ev.start_ns and ev.start_ns + ev.duration_ns <= f.start_ns + f.duration_ns
            for f in fits
        )
    assert inside == len(stages)
    # the waits on the fit's thread, the four puts' transfers on the watcher's
    assert len([ev for ev in host if ev.name == "device.wait"]) >= 2
    assert len([ev for ev in host if ev.name == "dataset.transfer"]) == 4


# ------------------------------------------------------------ program names
def _module_name(jitted, *args) -> str:
    return jitted.lower(*args).as_text().split("module @", 1)[1].split(" ", 1)[0]


def test_shared_program_is_named_for_its_class_not_its_parameters():
    from keystone_tpu.ops import CosineRandomFeatures

    T = sys.modules["keystone_tpu.workflow.transformer"]  # the package exports a function of that name

    a = CosineRandomFeatures.init(12, 32, gamma=0.1, seed=3)
    b = CosineRandomFeatures.init(12, 32, gamma=0.1, seed=4)
    data = Dataset(np.random.default_rng(0).normal(size=(40, 12)).astype(np.float32))
    T._SHARED_APPLY_CACHE.clear()
    log = compile_log.CompileLog().install()
    mark = _mark()
    ya = a(data).numpy()
    after_first = log.snapshot()["backend_compiles"]
    yb = b(data).numpy()
    assert log.snapshot()["backend_compiles"] == after_first  # one program, two seeds
    assert not np.allclose(ya, yb)
    mints = [r for r in _since(mark) if r.name == "transformer.jit_mint"]
    assert [r.attrs for r in mints] == [{"node": "CosineRandomFeatures", "shared": True}]
    (fn,) = [f for k, f in T._SHARED_APPLY_CACHE.items() if k[0][1] is CosineRandomFeatures]
    params = {"w": a.w, "b": a.b}
    assert _module_name(fn, params, data.array, None) == "jit_apply_CosineRandomFeatures"


def test_fused_chain_is_named_for_its_classes():
    from keystone_tpu.ops import LinearRectifier, NormalizeRows, SignedHellingerMapper
    from keystone_tpu.workflow.optimizer import FusedTransformer

    chain = FusedTransformer([LinearRectifier(0.5), SignedHellingerMapper(), NormalizeRows()])
    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 6)).astype(np.float32))
    mark = _mark()
    chain.apply_batch(x)
    chain.apply_batch(x)
    mints = [r.attrs["node"] for r in _since(mark) if r.name == "transformer.jit_mint"]
    assert mints == ["LinearRectifier_SignedHellingerMapper_NormalizeRows"]
    from keystone_tpu.workflow import optimizer as O

    T = sys.modules["keystone_tpu.workflow.transformer"]
    named = T.jit_named(lambda v: v, chain.stages)
    assert _module_name(named, x) == "jit_fused_LinearRectifier_SignedHellingerMapper_NormalizeRows"
    long = T.jit_named(lambda v: v, chain.stages * 12)
    assert long.__name__ == ("fused_" + "_".join(type(s).__name__ for s in chain.stages * 12))[:96]
    assert "jit_" + long.__name__.rstrip("_") == _module_name(long, x)
    assert O._FUSED_SHARED_CACHE or chain._jitted  # the chain's wrapper is cached


def _imagenet_toy_fitter(cfg):
    """A function that builds and fits the north-star graph at toy size and
    returns (fitted pipeline, the spans it left, what it asked of the
    compiler); a process's first call mints for real, unless an earlier
    test's did."""
    from keystone_tpu.loaders.imagenet import ImageNetLoader
    from keystone_tpu.pipelines import ImageNetSiftLcsFV

    train = ImageNetLoader.synthetic(
        cfg.synthetic_n, cfg.num_classes, size=(cfg.image_size, cfg.image_size), seed=1
    )
    log = compile_log.CompileLog().install()

    def build_and_fit():
        mark, before = _mark(), log.snapshot()
        fitted = ImageNetSiftLcsFV.build_scorer(cfg, train.data, train.labels).fit()
        fitted.block_until_ready()
        return fitted, _since(mark), compile_log.delta(log.snapshot(), before)

    return build_and_fit, train, log


def test_second_fit_of_the_imagenet_graph_mints_no_node_program(imagenet_toy_config):
    """Build and fit the north-star graph twice in one process: the
    second fit's nodes are new objects with the first's (class,
    params()), so none of them mints a wrapper — not the five of the
    featurizer inside the fit, not the build's eager label node before
    it — and the sampling rule prices its shared ``PixelScaler`` from
    the memo of the first fit's (``profiling.py § _priced_by_shape``):
    the second fit asks the compiler for nothing (``fit_programs`` 0)."""
    build_and_fit, _, _ = _imagenet_toy_fitter(imagenet_toy_config)
    build_and_fit()
    _, records, asked = build_and_fit()
    mints = [r for r in records if r.name == "transformer.jit_mint"]
    assert [r.attrs for r in mints] == []
    assert (asked["requests"], asked["backend_compiles"]) == (0, 0)
    assert len([r for r in records if r.name == "pipeline.fit"]) == 1


def test_the_sampling_rules_span_says_how_its_pass_went(imagenet_toy_config):
    """``to_place`` (shared nodes with no barrier yet), ``sampled`` (the
    sampled run happened), ``priced`` (programs compiled to price) and
    ``price_hits`` (prices from the memo) on the ``optimizer.rule`` span of
    ``ProfiledMaterialize``: a second fit samples its shared nodes and
    prices them from the memo; a scoring call of the fitted pipeline finds
    its fan-out behind the fit's ``Cacher`` and does neither."""
    build_and_fit, train, log = _imagenet_toy_fitter(imagenet_toy_config)

    def passes(records):
        return [
            {k: r.attrs[k] for k in ("to_place", "sampled", "priced", "price_hits")}
            for r in records
            if r.name == "optimizer.rule" and r.attrs["rule"] == "ProfiledMaterialize"
        ]

    build_and_fit()
    fitted, records, _ = build_and_fit()
    (fit_pass,) = passes(records)  # the whole entry's graph: four shared nodes
    assert fit_pass["to_place"] >= 1 and fit_pass["sampled"] == 1
    assert fit_pass["priced"] == 0 and 1 <= fit_pass["price_hits"] <= fit_pass["to_place"]
    images = np.asarray(train.data.numpy()[:8])
    fitted(Dataset(images)).get().numpy()
    mark, before = _mark(), log.snapshot()
    fitted(Dataset(images)).get().numpy()
    records = _since(mark)
    assert passes(records) == [{"to_place": 0, "sampled": 0, "priced": 0, "price_hits": 0}]
    assert compile_log.delta(log.snapshot(), before)["requests"] == 0
    assert not [r for r in records if r.name == "transformer.jit_mint"]


def test_obs_report_prints_how_the_sampling_rules_passes_went(tmp_path):
    from keystone_tpu.ops import LinearRectifier
    from tools.obs_report import render, summarize

    x = Dataset(np.random.default_rng(0).normal(size=(32, 6)).astype(np.float32))
    shared = Pipeline.gather([
        Pipeline.of(LinearRectifier(0.0)) | LinearRectifier(0.5),
        Pipeline.of(LinearRectifier(0.0)) | LinearRectifier(1.0),
    ])
    run = ledger.start_run(str(tmp_path))
    try:
        shared(x).get()  # one shared node: sampled, priced or taken from the memo
        (Pipeline.of(LinearRectifier(0.0)) | LinearRectifier(0.5))(x).get()  # none
    finally:
        ledger.stop_run()
    (line,) = [ln for ln in render(summarize(run.path)).splitlines()
               if "ProfiledMaterialize" in ln]
    assert "to_place=1  sampled=1" in line
    priced, hits = (int(line.split(f"{k}=")[1].split()[0]) for k in ("priced", "price_hits"))
    assert priced + hits == 1


def test_obs_report_prints_a_stages_chunks_beside_its_seconds(tmp_path, monkeypatch):
    from tools.obs_report import render, summarize

    T = sys.modules["keystone_tpu.workflow.transformer"]
    monkeypatch.delenv("KEYSTONE_APPLY_CHUNK", raising=False)
    monkeypatch.setattr(T, "_apply_chunk_rows", lambda: 8)
    x = np.random.default_rng(0).normal(size=(30, 6)).astype(np.float32)
    run = ledger.start_run(str(tmp_path))
    try:
        Pipeline.of(AddConst(0.5))(Dataset(x, shard=False)).get()  # within a chunk's bytes
        monkeypatch.setattr(T, "_APPLY_CHUNK_BYTES", 64)
        Pipeline.of(AddConst(0.5))(Dataset(x, shard=False)).get()  # over them: four of 8 rows
    finally:
        ledger.stop_run()
    summary = summarize(run.path)
    (stage,) = [st for st in summary["stage_top"] if st["node"] == "AddConst"]
    assert (stage["count"], stage["chunks"]) == (2, 1 + 4)
    lines = render(summary).splitlines()
    head = next(i for i, ln in enumerate(lines) if ln.split()[:3] == ["seconds", "chunks", "runs"])
    row = next(ln for ln in lines[head + 1:] if ln.split()[-1] == "AddConst")
    assert row.split()[1:3] == ["5", "2"]


# ---------------------------------------------------------------- the readers
def _rec(span_id, parent_id, root_id, name, t0_ms, dur_ms, **attrs):
    return ledger.SpanRecord(span_id, parent_id, root_id, name, int(t0_ms * 1e6),
                             int(dur_ms * 1e6), attrs)


def _fit_tree(base: int, t0: float):
    """One hand-made fit of 100 ms: optimize 10 + 4 ms (its rule samples one
    node for 2 ms), two stages of 30 and 20 ms holding a mint of 6 ms and a
    solver dispatch of 5 ms."""
    fit = base
    return [
        _rec(base + 8, base + 2, fit, "executor.stage", t0 + 2, 2, node="A", node_id=1),
        _rec(base + 2, base + 1, fit, "optimizer.rule", t0 + 1, 8, rule="r", batch="b"),
        _rec(base + 1, fit, fit, "pipeline.optimize", t0, 10, nodes=5),
        _rec(base + 4, base + 3, fit, "transformer.jit_mint", t0 + 12, 6, node="A", shared=False),
        _rec(base + 3, fit, fit, "executor.stage", t0 + 11, 30, node="A", node_id=1),
        _rec(base + 6, base + 5, fit, "solver.fit", t0 + 45, 5, solver="bcd", n=8, blocks=2),
        _rec(base + 5, fit, fit, "executor.stage", t0 + 42, 20, node="B", node_id=2),
        _rec(base + 7, fit, fit, "pipeline.optimize", t0 + 70, 4, nodes=3),
        _rec(fit, None, fit, "pipeline.fit", t0, 100),
    ]


def _call_tree(base: int, t0: float):
    """One hand-made scoring call: upload 20 ms, then an apply of 200 ms with
    12 ms of optimizer and a 3 ms put inside it, then the readback."""
    apply = base + 1
    return [
        _rec(base, None, base, "dataset.upload", t0, 20, bytes=1000),
        _rec(base + 2, apply, apply, "pipeline.optimize", t0 + 21, 12, nodes=9),
        _rec(base + 4, base + 3, apply, "dataset.upload", t0 + 40, 3, bytes=10),
        _rec(base + 3, apply, apply, "executor.stage", t0 + 35, 100, node="A", node_id=1),
        _rec(apply, None, apply, "pipeline.apply", t0 + 20, 200),
        _rec(base + 5, None, base + 5, "dataset.readback", t0 + 220, 5, bytes=64),
    ]


def _fit_with_waits_and_puts(base: int, t0: float):
    """``_fit_tree`` with what the host waited for and what the link carried:
    two puts before the fit, 10 and 7 ms ahead of it, whose transfers take 30
    and 10 ms (the second inside the first: a union of 30 ms, not 40), a put
    late in the fit whose transfer closes 4 ms after the fit's root (9 ms); a
    sync of 8 ms under the walk's first stage and one of 1 ms under the
    sampling rule's."""
    fit = base
    tree = _fit_tree(base, t0)
    return [
        _rec(base + 20, None, base + 20, "dataset.upload", t0 - 10, 2, bytes=800),
        _rec(base + 22, None, base + 22, "dataset.upload", t0 - 7, 1, bytes=80),
        _rec(base + 23, base + 22, base + 22, "dataset.transfer", t0 - 7, 10, bytes=80),
        _rec(base + 24, base + 8, fit, "device.wait", t0 + 2.5, 1),
    ] + tree[:3] + [
        _rec(base + 21, base + 20, base + 20, "dataset.transfer", t0 - 10, 30, bytes=800),
        _rec(base + 25, base + 3, fit, "device.wait", t0 + 20, 8),
    ] + tree[3:-1] + [
        _rec(base + 26, fit, fit, "dataset.upload", t0 + 95, 1, bytes=8),
        tree[-1],
        _rec(base + 27, base + 26, fit, "dataset.transfer", t0 + 95, 9, bytes=8),
    ]


def _call_with_waits_and_puts(base: int, t0: float):
    """``_call_tree`` whose images take 60 ms to arrive and whose inner put
    takes 30 ms from 40 ms on (a union of 70 ms), with a ``Cacher``'s sync of
    25 ms under the apply's stage."""
    apply, tree = base + 1, _call_tree(base, t0)
    return tree[:4] + [
        _rec(base + 8, base + 3, apply, "device.wait", t0 + 45, 25),
        _rec(base + 6, base, base, "dataset.transfer", t0, 60, bytes=1000),
        _rec(base + 7, base + 4, apply, "dataset.transfer", t0 + 40, 30, bytes=10),
    ] + tree[4:]


_FIT_RING = (
    _fit_tree(100, 0) + [_rec(150, None, 150, "dataset.upload", 100, 1, bytes=8)]  # set-up
    + _fit_tree(200, 200) + _fit_tree(300, 400)  # the window's two fits
    + _call_tree(400, 600)  # the check's apply, after the window
)
_SCORE_RING = _call_tree(100, 0) + _call_tree(200, 300) + _call_tree(300, 600)
_FIT_RING_NAMED = (
    _fit_with_waits_and_puts(100, 0) + _fit_with_waits_and_puts(200, 200)
    + _fit_with_waits_and_puts(300, 400)
    + _call_tree(400, 600)  # the check's apply: its puts are no fit's, open or closed
)
_SCORE_RING_NAMED = (
    _call_with_waits_and_puts(100, 0) + _call_with_waits_and_puts(200, 300)
    + _call_with_waits_and_puts(300, 600)
)
_TRACE = {
    "devices": 1,
    "module_s": {"jit_fused_PixelScaler_GrayScaler_SIFTExtractor": 0.8,
                 "jit_apply_SIFTExtractor": 0.2, "jit_fused_PixelScaler_LCSExtractor": 0.5,
                 "jit__weighted_bcd_fit": 3.0},
    "module_runs": {"jit_apply_A": 6, "jit_fused_A_B": 4, "jit__weighted_bcd_fit": 2,
                    "jit_concatenate": 30},
}
_FIT_COUNTERS = {"units": 2, "elapsed": 1.0}
_SCORE_COUNTERS = {"units": 2000, "calls": 2, "elapsed": 1.0}

READERS = [
    ("fit_optimize_s", _FIT_RING, _FIT_COUNTERS, 0.014),
    ("fit_jit_mints", _FIT_RING, _FIT_COUNTERS, 1.0),
    ("fit_stage_host_s", _FIT_RING, _FIT_COUNTERS, 0.024 + 0.015),
    ("fit_node_launches", _FIT_RING, _FIT_COUNTERS, 5.0),
    ("sift_device_us_per_image", _SCORE_RING, _SCORE_COUNTERS, 500.0),
    ("lcs_device_us_per_image", _SCORE_RING, _SCORE_COUNTERS, 250.0),
    ("score_optimize_s", _SCORE_RING, _SCORE_COUNTERS, 0.012),
    ("score_upload_host_s", _SCORE_RING, _SCORE_COUNTERS, 0.023),
    ("upload_transfer_s.score", _SCORE_RING_NAMED, _SCORE_COUNTERS, 0.070),
    ("upload_transfer_s.fit", _FIT_RING_NAMED, _FIT_COUNTERS, 0.030 + 0.009),
    ("host_wait_s.score", _SCORE_RING_NAMED, _SCORE_COUNTERS, 0.025),
    ("host_wait_s.fit", _FIT_RING_NAMED, _FIT_COUNTERS, 0.008 + 0.001),
]
_FROM_TRACE = {"fit_node_launches", "sift_device_us_per_image", "lcs_device_us_per_image"}


def _ctx(counters, trace=_TRACE):
    return types.SimpleNamespace(counters=dict(counters), trace=trace)


@pytest.mark.parametrize("metric,ring,counters,want", READERS, ids=[r[0] for r in READERS])
def test_reader_on_a_hand_made_ring_and_reduction(monkeypatch, metric, ring, counters, want):
    monkeypatch.setattr(ledger, "_RING", collections.deque(ring, maxlen=ledger.RING_SIZE))
    assert harness.load_reader(metric).read(_ctx(counters)) == pytest.approx(want)


@pytest.mark.parametrize("metric,ring,counters,want", READERS, ids=[r[0] for r in READERS])
def test_reader_finds_nothing_to_read(monkeypatch, metric, ring, counters, want):
    read = harness.load_reader(metric).read
    if metric in _FROM_TRACE:
        # no device plane (a CPU rehearsal), or a program whose node programs
        # are still called jit__lambda_ and jit_run (the parent commit)
        assert read(_ctx(counters, trace={"devices": 0})) is None
        old = {"devices": 1, "module_s": {"jit__lambda_": 1.0, "jit_run": 2.0},
               "module_runs": {"jit__lambda_": 3, "jit_run": 4}}
        assert read(_ctx(counters, trace=old)) is None
        return
    monkeypatch.setattr(ledger, "_RING", collections.deque(maxlen=ledger.RING_SIZE))
    assert read(_ctx(counters)) is None  # an empty ring
    # a ring that wrapped inside the window: exactly full, and no older root
    # of the name shows that the first root's descendants are all still here
    n = counters.get("calls", counters["units"])
    tail = [r for r in ring if r.span_id >= (300 - 100 * (n - 1))]
    monkeypatch.setattr(ledger, "_RING", collections.deque(tail, maxlen=len(tail)))
    monkeypatch.setattr(ledger, "RING_SIZE", len(tail))
    assert read(_ctx(counters)) is None
    # a program without the ring (the parent commit): nothing, and no raise
    monkeypatch.delattr(ledger, "recent_spans")
    assert read(_ctx(counters)) is None


def test_stage_self_time_leaves_the_named_waits_out(monkeypatch):
    """``fit_stage_host_s`` on the ring with named waits: the first stage's
    30 ms less its mint (6) and its sync (8), the second's 15."""
    monkeypatch.setattr(ledger, "_RING",
                        collections.deque(_FIT_RING_NAMED, maxlen=ledger.RING_SIZE))
    read = harness.load_reader("fit_stage_host_s").read
    assert read(_ctx(_FIT_COUNTERS)) == pytest.approx(0.016 + 0.015)


@pytest.mark.parametrize("metric,ring,counters", [
    ("upload_transfer_s.fit", _FIT_RING_NAMED, _FIT_COUNTERS),
    ("upload_transfer_s.score", _SCORE_RING_NAMED, _SCORE_COUNTERS),
])
def test_transfer_reader_reads_nothing_while_a_transfer_is_open(monkeypatch, metric, ring,
                                                                counters):
    read = harness.load_reader(metric).read
    closed = [r for r in ring if r.name == "dataset.transfer"]
    for missing in (closed[-1:], closed):  # one still under way; a parent commit has none
        left = [r for r in ring if r not in missing]
        monkeypatch.setattr(ledger, "_RING", collections.deque(left, maxlen=ledger.RING_SIZE))
        assert read(_ctx(counters)) is None


@pytest.mark.parametrize("metric,ring,counters", [
    ("host_wait_s.fit", _FIT_RING, _FIT_COUNTERS),
    ("host_wait_s.score", _SCORE_RING, _SCORE_COUNTERS),
])
def test_wait_reader_tells_no_wait_from_no_name_for_it(monkeypatch, metric, ring, counters):
    monkeypatch.setattr(ledger, "_RING", collections.deque(ring, maxlen=ledger.RING_SIZE))
    read = harness.load_reader(metric).read
    assert read(_ctx(counters)) == 0.0  # a fit or call that never stood still
    monkeypatch.delattr(ledger, "waiting")  # the parent commit: its waits are bare
    assert read(_ctx(counters)) is None


def test_new_metrics_are_declared_with_their_readers():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    layers = os.path.join(ROOT, "benchmark", "layers")
    for metric, *_ in READERS:
        # where ``harness.load_reader`` looks: the metric's own file, or for a
        # quantity split by cells (``<quantity>.<cells>``) the quantity's
        files = [metric + ".py", metric.rsplit(".", 1)[0] + ".py"]
        assert metric in declared and any(os.path.isfile(os.path.join(layers, f)) for f in files)
        assert harness.load_reader(metric).read is not None
    assert declared["score_upload_host_s"]["layer"] == "host link"
    assert declared["sift_device_us_per_image"]["unit"] == "us"
    fit_cells = ["imagenet-fv.fit-given-vocab", "timit-rf.fit", "timit-krr.fit", "cifar-rp.fit"]
    for quantity, layer in (("upload_transfer_s", "host link"),
                            ("host_wait_s", "executor / transformer")):
        assert os.path.isfile(os.path.join(layers, quantity + ".py"))
        fit, score = declared[quantity + ".fit"], declared[quantity + ".score"]
        assert (fit["moves"], fit["workloads"]) == ("fit_s", fit_cells)
        assert (score["moves"], score["workloads"]) == (
            "score_images_per_s", ["imagenet-fv.score-bulk"])
        for m in (fit, score):
            assert (m["layer"], m["unit"], m["better"], m["source"]) == (
                layer, "s", "lower", "program_span")
