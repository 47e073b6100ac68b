"""Out-of-core block solvers: FeatureBlockStore + StreamDataset + OC BCD.

The correctness pattern is the reference's own (SURVEY.md §4): the
out-of-core solver must match the in-memory solve on the same data to
tight tolerance — the disk tier changes WHERE blocks live, not the math.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.models import (
    BlockLeastSquaresEstimator,
    BlockWeightedLeastSquaresEstimator,
)
from keystone_tpu.workflow import Dataset, FeatureBlockStore, StreamDataset
from keystone_tpu.workflow import Pipeline, transformer


def _problem(n=96, d=37, k=5, seed=0, skew=False):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    if skew:  # imbalanced classes so the weighted path is non-trivial
        probs = np.array([0.6, 0.2, 0.1, 0.06, 0.04])[:k]
        probs = probs / probs.sum()
        lbl = rng.choice(k, size=n, p=probs)
    else:
        lbl = rng.integers(0, k, size=n)
    y = -np.ones((n, k), np.float32)
    y[np.arange(n), lbl] = 1.0
    return x, y, lbl


# ------------------------------------------------------------------ store


def test_store_roundtrip(tmp_path):
    x = np.arange(60, dtype=np.float32).reshape(10, 6)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=4)
    assert store.num_blocks == 2 and store.n == 10 and store.d == 6
    b0 = store.read_block(0)
    b1 = store.read_block(1)
    np.testing.assert_array_equal(b0, x[:, :4])
    np.testing.assert_array_equal(b1[:, :2], x[:, 4:])
    np.testing.assert_array_equal(b1[:, 2:], 0)  # column padding


def test_store_from_batches_matches_from_array(tmp_path):
    x = np.random.default_rng(1).normal(size=(23, 9)).astype(np.float32)
    s1 = FeatureBlockStore.from_array(str(tmp_path / "a"), x, block_size=4)
    batches = [x[:7], x[7:15], x[15:]]
    s2 = FeatureBlockStore.from_batches(str(tmp_path / "b"), batches, 23, 4)
    for b in range(s1.num_blocks):
        np.testing.assert_array_equal(s1.read_block(b), s2.read_block(b))


def test_store_row_count_mismatch(tmp_path):
    with pytest.raises(ValueError, match="produced"):
        FeatureBlockStore.from_batches(
            str(tmp_path / "c"), [np.zeros((3, 4), np.float32)], 5, 2
        )


def test_store_prefetch_order(tmp_path):
    x = np.random.default_rng(2).normal(size=(8, 12)).astype(np.float32)
    store = FeatureBlockStore.from_array(str(tmp_path / "d"), x, block_size=4)
    order = [0, 1, 2, 0, 1, 2]
    seen = [(b, blk.copy()) for b, blk in store.iter_blocks(order)]
    assert [b for b, _ in seen] == order
    for b, blk in seen:
        np.testing.assert_array_equal(blk, store.read_block(b))


# ------------------------------------------------- OC solver == in-memory


@pytest.mark.parametrize("fit_intercept", [True, False])
def test_oc_unweighted_matches_inmemory(tmp_path, fit_intercept):
    x, y, _ = _problem()
    est = BlockLeastSquaresEstimator(
        block_size=16, num_iter=3, lam=1e-2, fit_intercept=fit_intercept
    )
    ref = est.fit_arrays(x, y)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=16)
    oc = est.fit_store(store, Dataset(y, n=y.shape[0]))
    np.testing.assert_allclose(
        np.asarray(oc.flat_weights), np.asarray(ref.flat_weights), atol=2e-4
    )
    if fit_intercept:
        np.testing.assert_allclose(
            np.asarray(oc.intercept), np.asarray(ref.intercept), atol=2e-4
        )


def test_oc_weighted_matches_inmemory(tmp_path):
    x, y, _ = _problem(skew=True)
    est = BlockWeightedLeastSquaresEstimator(
        block_size=16, num_iter=3, lam=1e-2, mixture_weight=0.5
    )
    ref = est.fit_arrays(x, y)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=16)
    oc = est.fit_store(store, Dataset(y, n=y.shape[0]))
    np.testing.assert_allclose(
        np.asarray(oc.flat_weights), np.asarray(ref.flat_weights), atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(oc.intercept), np.asarray(ref.intercept), atol=2e-4
    )


def test_oc_checkpoint_resume(tmp_path):
    """A fit interrupted between epochs resumes and matches the straight
    run — the coarse fault-tolerance story (SURVEY.md §5)."""
    x, y, _ = _problem(seed=3)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=16)
    ckpt = str(tmp_path / "ckpt")
    labels = Dataset(y, n=y.shape[0])
    # run only 2 of 4 epochs (simulated interruption), then resume to 4
    partial = BlockWeightedLeastSquaresEstimator(block_size=16, num_iter=2, lam=1e-2)
    partial.fit_store(store, labels, checkpoint_dir=ckpt)
    full = BlockWeightedLeastSquaresEstimator(block_size=16, num_iter=4, lam=1e-2)
    resumed = full.fit_store(store, labels, checkpoint_dir=ckpt)
    straight = full.fit_store(store, labels)  # no checkpoint
    np.testing.assert_allclose(
        np.asarray(resumed.flat_weights),
        np.asarray(straight.flat_weights),
        atol=2e-4,
    )


# --------------------------------------------------- StreamDataset in DAG


def test_stream_through_pipeline_dag(tmp_path):
    """A StreamDataset flows through transformers and the block solver
    fits out-of-core — the DEFAULT path, not a side API."""
    x, y, lbl = _problem(n=128, d=40, k=4)
    batches = lambda: iter([x[i : i + 32] for i in range(0, 128, 32)])
    stream = StreamDataset(batches, n=128)
    scale = transformer(lambda v: v * 0.5, name="Half")
    est = BlockLeastSquaresEstimator(block_size=16, num_iter=3, lam=1e-3)
    pipe = Pipeline.of(scale).and_then(est, stream, Dataset(y, n=128))
    fitted = pipe.fit()
    pred = fitted(Dataset(x, n=128)).get().numpy()
    # reference: in-memory fit on the same (scaled) features
    ref = est.fit_arrays(x * 0.5, y)
    ref_pred = np.asarray(ref.apply_batch(jnp.asarray(x * 0.5)))
    np.testing.assert_allclose(pred, ref_pred[:128], atol=5e-4)


def test_stream_gather_two_branches():
    """Gather over stream branches zips and concats per batch."""
    x = np.random.default_rng(5).normal(size=(20, 6)).astype(np.float32)
    stream = StreamDataset(lambda: iter([x[:8], x[8:20]]), n=20)
    a = stream.map_batches(lambda v, m: v * 2.0)
    b = stream.map_batches(lambda v, m: v + 1.0)
    gathered = StreamDataset.zip_concat([a, b])
    out = np.concatenate(list(gathered.batches()), axis=0)
    np.testing.assert_allclose(out, np.concatenate([x * 2, x + 1], axis=-1), rtol=1e-6)


def test_stream_materialize_fallback():
    """Consumers without a streaming path still work via .array."""
    x = np.random.default_rng(6).normal(size=(10, 4)).astype(np.float32)
    stream = StreamDataset(lambda: iter([x[:4], x[4:]]), n=10)
    np.testing.assert_allclose(stream.numpy(), x, rtol=1e-6)


def test_oc_checkpoint_fingerprint_sensitive(tmp_path):
    """A checkpoint from different hyperparameters must not be resumed:
    changing mixture_weight (or labels, λ, ...) restarts the fit."""
    x, y, _ = _problem(seed=7, skew=True)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=16)
    labels = Dataset(y, n=y.shape[0])
    ckpt = str(tmp_path / "ckpt")
    a = BlockWeightedLeastSquaresEstimator(
        block_size=16, num_iter=2, lam=1e-2, mixture_weight=0.5
    )
    a.fit_store(store, labels, checkpoint_dir=ckpt)  # leaves epoch-1 state
    b = BlockWeightedLeastSquaresEstimator(
        block_size=16, num_iter=2, lam=1e-2, mixture_weight=0.9
    )
    stale_aware = b.fit_store(store, labels, checkpoint_dir=ckpt)
    fresh = b.fit_store(store, labels)
    np.testing.assert_allclose(
        np.asarray(stale_aware.flat_weights),
        np.asarray(fresh.flat_weights),
        atol=2e-4,
    )


def test_stream_fit_cleans_spill(tmp_path):
    x, y, _ = _problem(n=64, d=24, k=3)
    stream = StreamDataset(lambda: iter([x[:32], x[32:]]), n=64)
    est = BlockLeastSquaresEstimator(block_size=8, num_iter=2, lam=1e-3)
    est.fit_stream_dataset(stream, Dataset(y, n=64), spill_dir=str(tmp_path / "sp"))
    import os

    leftovers = [
        p for p in os.listdir(tmp_path / "sp") if p.startswith("kst_spill_")
    ]
    assert leftovers == []


def test_stream_rejects_one_shot_iterator():
    gen = (np.zeros((2, 3), np.float32) for _ in range(2))
    with pytest.raises(ValueError, match="re-iterable"):
        StreamDataset(gen, n=4)


def test_stream_host_transformer_rejected():
    from keystone_tpu.workflow.transformer import LambdaTransformer

    stream = StreamDataset(lambda: iter([np.zeros((2, 3), np.float32)]), n=2)
    host_t = LambdaTransformer(lambda s: s, name="HostOp", host=True)
    with pytest.raises(TypeError, match="host transformer"):
        host_t.apply_dataset(stream)


# -------------------------------------------------------- bf16 spill tier


def test_store_bf16_roundtrip(tmp_path):
    import ml_dtypes

    x = np.random.default_rng(5).normal(size=(10, 6)).astype(np.float32)
    store = FeatureBlockStore.from_array(
        str(tmp_path / "b"), x, block_size=4, dtype="bfloat16"
    )
    assert store.dtype == "bfloat16"
    b0 = store.read_block(0)
    assert b0.dtype == ml_dtypes.bfloat16
    # values round-trip at bf16 precision (8-bit mantissa)
    np.testing.assert_allclose(
        b0.astype(np.float32), x[:, :4].astype(ml_dtypes.bfloat16).astype(np.float32)
    )
    # half the disk footprint of an f32 store
    f32 = FeatureBlockStore.from_array(str(tmp_path / "f"), x, block_size=4)
    assert store.nbytes() * 2 == f32.nbytes()


def test_store_meta_backcompat_missing_dtype(tmp_path):
    """Stores written before the dtype option must load as float32."""
    import json
    import os

    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=4)
    meta_path = os.path.join(store.directory, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta.pop("dtype")
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    reloaded = FeatureBlockStore(store.directory)
    assert reloaded.dtype == "float32"
    np.testing.assert_array_equal(reloaded.read_block(0), x)


def test_store_invalid_dtype_raises(tmp_path):
    with pytest.raises(ValueError, match="dtype"):
        FeatureBlockStore.create(str(tmp_path / "s"), 4, 4, 2, dtype="float16")


@pytest.mark.parametrize("weighted", [False, True])
def test_oc_bf16_spill_matches_inmemory(tmp_path, weighted):
    """bf16 spill halves sweep IO; the fitted model must still match the
    in-memory f32 fit to bf16-quantization tolerance (weights are O(1),
    bf16 has ~3 decimal digits -> atol ~1e-2 after 3 BCD epochs)."""
    x, y, _ = _problem(seed=7, skew=weighted)
    cls = (
        BlockWeightedLeastSquaresEstimator if weighted else BlockLeastSquaresEstimator
    )
    est = cls(block_size=16, num_iter=3, lam=1e-2)
    ref = est.fit_arrays(x, y)
    store = FeatureBlockStore.from_array(
        str(tmp_path / "s"), x, block_size=16, dtype="bfloat16"
    )
    oc = est.fit_store(store, Dataset(y, n=y.shape[0]))
    np.testing.assert_allclose(
        np.asarray(oc.flat_weights), np.asarray(ref.flat_weights), atol=2e-2
    )
    np.testing.assert_allclose(
        np.asarray(oc.intercept), np.asarray(ref.intercept), atol=2e-2
    )


def test_oc_spill_dtype_plumbed_through_stream_fit(tmp_path, monkeypatch):
    """StreamDataset -> fit_stream_dataset spills at the estimator's
    spill_dtype."""
    from keystone_tpu.workflow import blockstore as bs_mod

    seen = []
    orig = bs_mod.FeatureBlockStore.from_batches.__func__

    def spy(cls, directory, batches, n, block_size, dtype="float32"):
        seen.append(dtype)
        return orig(cls, directory, batches, n, block_size, dtype=dtype)

    monkeypatch.setattr(
        bs_mod.FeatureBlockStore, "from_batches", classmethod(spy)
    )
    x, y, _ = _problem(seed=9)
    est = BlockLeastSquaresEstimator(
        block_size=16, num_iter=2, lam=1e-2, spill_dtype="bfloat16"
    )
    stream = StreamDataset([x[:32], x[32:64], x[64:]], n=x.shape[0])
    oc = est.fit_stream_dataset(stream, Dataset(y, n=y.shape[0]))
    assert seen == ["bfloat16"]
    ref = est.fit_arrays(x, y)
    np.testing.assert_allclose(
        np.asarray(oc.flat_weights), np.asarray(ref.flat_weights), atol=2e-2
    )


# ------------------------------------------------- prefetch + thread hygiene


def _prefetch_spy(monkeypatch):
    """Record the prefetch depth every iter_blocks call receives."""
    from keystone_tpu.workflow import blockstore as bs_mod

    seen = []
    orig = bs_mod.FeatureBlockStore.iter_blocks

    def spy(self, order, prefetch=2):
        seen.append(prefetch)
        return orig(self, order, prefetch=prefetch)

    monkeypatch.setattr(bs_mod.FeatureBlockStore, "iter_blocks", spy)
    return seen


def test_oc_prefetch_plumbed_explicit(tmp_path, monkeypatch):
    """fit_store(prefetch=) reaches every iter_blocks call of the sweep."""
    seen = _prefetch_spy(monkeypatch)
    x, y, _ = _problem(seed=11)
    est = BlockLeastSquaresEstimator(block_size=16, num_iter=2, lam=1e-2)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=16)
    oc = est.fit_store(store, Dataset(y, n=y.shape[0]), prefetch=3)
    assert seen and all(p == 3 for p in seen), seen
    ref = est.fit_arrays(x, y)
    np.testing.assert_allclose(
        np.asarray(oc.flat_weights), np.asarray(ref.flat_weights), atol=2e-4
    )


def test_oc_prefetch_env_override(tmp_path, monkeypatch):
    """KEYSTONE_OC_PREFETCH governs the depth when the caller passes
    nothing; an explicit argument still wins over the env."""
    from keystone_tpu.models.block_ls import _oc_prefetch

    monkeypatch.setenv("KEYSTONE_OC_PREFETCH", "5")
    assert _oc_prefetch() == 5
    assert _oc_prefetch(3) == 3

    monkeypatch.setenv("KEYSTONE_OC_PREFETCH", "4")
    seen = _prefetch_spy(monkeypatch)
    x, y, _ = _problem(seed=12)
    est = BlockWeightedLeastSquaresEstimator(
        block_size=16, num_iter=1, lam=1e-2, mixture_weight=0.25
    )
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=16)
    est.fit_store(store, Dataset(y, n=y.shape[0]))
    assert seen and all(p == 4 for p in seen), seen


@pytest.mark.parametrize("bad", ["junk", "eight", "0", "-3", "100000", "2.5"])
def test_oc_prefetch_rejects_garbage_env(monkeypatch, bad):
    """Garbage KEYSTONE_OC_PREFETCH values used to be silently coerced
    to the default — the operator believed the tuning was in effect
    while the sweep ran at depth 2 (or, for a huge depth, pinned
    n×block_size host blocks until the OOM killer fired).  Now they
    raise a ValueError naming the variable."""
    from keystone_tpu.models.block_ls import _oc_prefetch

    monkeypatch.setenv("KEYSTONE_OC_PREFETCH", bad)
    with pytest.raises(ValueError, match="KEYSTONE_OC_PREFETCH"):
        _oc_prefetch()
    # an explicit caller value is still authoritative over a bad env
    assert _oc_prefetch(3) == 3


def test_oc_prefetch_defaults_and_bounds(monkeypatch):
    from keystone_tpu.models.block_ls import _OC_PREFETCH_MAX, _oc_prefetch

    monkeypatch.delenv("KEYSTONE_OC_PREFETCH", raising=False)
    assert _oc_prefetch() == 2  # unset → the measured default
    monkeypatch.setenv("KEYSTONE_OC_PREFETCH", "")
    assert _oc_prefetch() == 2  # empty string counts as unset
    monkeypatch.setenv("KEYSTONE_OC_PREFETCH", str(_OC_PREFETCH_MAX))
    assert _oc_prefetch() == _OC_PREFETCH_MAX  # inclusive upper bound
    # the explicit fit argument rides the SAME bound as the env var —
    # fit_store(prefetch=100000) is the identical OOM footgun
    monkeypatch.delenv("KEYSTONE_OC_PREFETCH", raising=False)
    with pytest.raises(ValueError, match="prefetch=100000"):
        _oc_prefetch(100000)
    with pytest.raises(ValueError, match="prefetch=0"):
        _oc_prefetch(0)


def test_oc_row_mismatch_raises_before_sweep(tmp_path):
    """The hoisted row-count validation: a label array whose padded rows
    cannot match the staged store blocks fails up front (once), not from
    inside the per-(epoch, block) hot loop."""
    from keystone_tpu.models.block_ls import _oc_bcd_fit

    x, y, _ = _problem(seed=13)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=16)
    y_padded = np.pad(y, ((0, 4), (0, 0)))  # 4 extra pad rows vs the store
    alpha = (np.arange(y_padded.shape[0]) < y.shape[0]).astype(np.float32)
    with pytest.raises(ValueError, match="store rows pad to"):
        _oc_bcd_fit(
            store,
            jnp.asarray(y_padded),
            jnp.asarray(alpha),
            float(y.shape[0]),
            1e-2,
            1,
            False,
        )


# ------------------------------------------- async device feed + donation


def test_iter_device_blocks_order_and_values(tmp_path):
    """The staged feed yields the same (index, block) sequence as the
    host iterator, cast to f32 on device (bf16 stores included)."""
    import ml_dtypes

    x = np.random.default_rng(21).normal(size=(12, 20)).astype(np.float32)
    for dtype in ("float32", "bfloat16"):
        store = FeatureBlockStore.from_array(
            str(tmp_path / dtype), x, block_size=8, dtype=dtype
        )
        order = [0, 2, 1, 0]
        seen = list(store.iter_device_blocks(order, prefetch=2))
        assert [b for b, _ in seen] == order
        for b, dev in seen:
            assert dev.dtype == jnp.float32
            want = np.asarray(store.read_block(b), np.float32)
            if dtype == "bfloat16":
                want = x[:, b * 8 : (b + 1) * 8].astype(
                    ml_dtypes.bfloat16
                ).astype(np.float32)
                want = np.pad(want, ((0, 0), (0, 8 - want.shape[1])))
            np.testing.assert_allclose(np.asarray(dev), want)


def test_iter_device_blocks_keeps_blocks_in_flight(tmp_path):
    """The overlap pin: when the consumer takes block b, the feed has
    already DISPATCHED the staging of at least one later block — the
    double-buffering that lets transfer b+1 overlap compute b."""
    x = np.random.default_rng(22).normal(size=(8, 40)).astype(np.float32)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=8)
    staged_at_yield = []
    staged = []

    def spy_stage(blk):
        staged.append(len(staged))
        return jnp.asarray(blk)

    gen = store.iter_device_blocks(range(5), prefetch=2, stage=spy_stage)
    for i, (b, dev) in enumerate(gen):
        staged_at_yield.append(len(staged))
    # at the first yield, ≥ 2 blocks were already staged (the in-flight
    # window); every later yield keeps ≥ 1 block ahead until the tail
    assert staged_at_yield[0] >= 2, staged_at_yield
    assert all(
        s > i + 1 for i, s in enumerate(staged_at_yield[:-2])
    ), staged_at_yield


def test_iter_device_blocks_bounds_inflight_window(tmp_path):
    """Backpressure: the feed never runs more than `window` staged
    blocks ahead of the consumer (pinned host buffers stay bounded)."""
    x = np.random.default_rng(23).normal(size=(8, 80)).astype(np.float32)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=8)
    staged = []

    def spy_stage(blk):
        staged.append(1)
        return jnp.asarray(blk)

    consumed = 0
    for b, dev in store.iter_device_blocks(
        range(10), prefetch=2, stage=spy_stage, window=2
    ):
        consumed += 1
        assert len(staged) - consumed <= 2, (len(staged), consumed)


def test_iter_blocks_error_carries_block_index(tmp_path, monkeypatch):
    """A failing read mid-sweep must say WHICH block died — and keep its
    exception type (retry/except dispatch downstream keys on it)."""
    from keystone_tpu.utils.durable import CorruptStateError

    x = np.random.default_rng(24).normal(size=(8, 24)).astype(np.float32)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=8)
    orig = FeatureBlockStore.read_block

    def failing(self, b):
        if b == 2:
            raise CorruptStateError("checksum mismatch")
        return orig(self, b)

    monkeypatch.setattr(FeatureBlockStore, "read_block", failing)
    with pytest.raises(CorruptStateError, match="block 2") as ei:
        list(store.iter_blocks([0, 1, 2]))
    assert "checksum mismatch" in str(ei.value)


def test_iter_blocks_oserror_carries_block_index(tmp_path, monkeypatch):
    """OSError is the primary disk-failure class and renders str() from
    errno/strerror, not args — the block tag must land on strerror (so
    the operator sees it) while args stay (errno, strerror) shaped (so
    cross-process reconstruction is not corrupted)."""
    import errno

    x = np.random.default_rng(24).normal(size=(8, 24)).astype(np.float32)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=8)
    orig = FeatureBlockStore.read_block

    def failing(self, b):
        if b == 1:
            raise FileNotFoundError(
                errno.ENOENT, "No such file or directory", "blk_00001.bin"
            )
        return orig(self, b)

    monkeypatch.setattr(FeatureBlockStore, "read_block", failing)
    with pytest.raises(FileNotFoundError, match="block 1") as ei:
        list(store.iter_blocks([0, 1, 2]))
    e = ei.value
    assert "No such file" in str(e)
    assert e.errno == errno.ENOENT  # reconstruction fields intact
    assert e.args[0] == errno.ENOENT
    assert e.filename == "blk_00001.bin"


def _aliased_params(step, *args) -> set:
    """The parameters that the COMPILED program aliases onto outputs
    (the header's ``input_output_alias`` table)."""
    import re

    header = step.lower(*args).compile().as_text().split("\n", 1)[0]
    table = re.search(r"input_output_alias=\{(.*?)\}, entry_", header)
    assert table, header[:300]
    return {int(i) for i in re.findall(r"\((\d+), \{\}, \w+-alias\)", table[1])}


def test_oc_block_step_donates_carry(tmp_path):
    """The donation pin, as the sweep uses it: the carry a step RETURNS
    is CONSUMED by the next step (is_deleted under live references —
    refcount alone could never do that), so the epoch loop cannot grow
    live device state.  A first ``p`` that arrives laid out otherwise
    than the step lays it out (single-device here, rows over the mesh's
    data axis out of the step) cannot be aliased and is copied once."""
    import jax

    from keystone_tpu.models.block_ls import _oc_block_step

    n, bs, k = 16, 8, 3
    rng = np.random.default_rng(25)
    a = jnp.asarray(rng.normal(size=(n, bs)).astype(np.float32))
    xm_b = jnp.zeros((bs,), jnp.float32)
    yc = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    sa = jnp.ones((n,), jnp.float32)
    row_ok = jnp.ones((n,), jnp.float32)
    p = jnp.zeros((n, k), jnp.float32)
    wb = jnp.zeros((bs, k), jnp.float32)
    lam_n = jnp.float32(0.1)
    # exactly the carry aliases outputs: not the block, not the targets
    assert _aliased_params(
        _oc_block_step, a, xm_b, yc, sa, row_ok, p, wb, lam_n
    ) == {5, 6}
    wb2, p2, tick = _oc_block_step(a, xm_b, yc, sa, row_ok, p, wb, lam_n)
    wb3, p3, _ = _oc_block_step(a, xm_b, yc, sa, row_ok, p2, wb2, lam_n)
    jax.block_until_ready(p3)
    assert p2.is_deleted() and wb2.is_deleted()
    assert not yc.is_deleted() and not a.is_deleted()
    # the tick (the sweep's flow-control handle) is NOT donated: it must
    # stay waitable after later steps consume the real outputs
    assert not tick.is_deleted()
    jax.block_until_ready(tick)

    # the live-buffer pin: repeated steps do not accumulate device arrays
    import gc

    gc.collect()
    baseline = len(jax.live_arrays())
    for _ in range(4):
        wb3, p3, tick = _oc_block_step(a, xm_b, yc, sa, row_ok, p3, wb3, lam_n)
    jax.block_until_ready(p3)
    del tick
    gc.collect()
    assert len(jax.live_arrays()) <= baseline + 1  # no per-epoch growth


def test_bcd_epoch_donates_carry():
    """The same pin for the checkpointed host loop's epoch: the (w, p)
    an epoch returns is consumed by the next one."""
    import gc

    import jax

    from keystone_tpu.models.block_ls import _bcd_epoch, blockify

    rng = np.random.default_rng(26)
    x = rng.normal(size=(16, 12)).astype(np.float32)
    y = jnp.asarray(rng.normal(size=(16, 3)).astype(np.float32))
    xb = blockify(jnp.asarray(x), 8)
    w = jnp.zeros((xb.shape[0], 8, 3), jnp.float32)
    p = jnp.zeros((16, 3), jnp.float32)
    n, lam = jnp.float32(16.0), 1e-3
    assert _aliased_params(_bcd_epoch, xb, y, n, lam, w, p) == {4, 5}
    w2, p2 = _bcd_epoch(xb, y, n, lam, w, p)
    w3, p3 = _bcd_epoch(xb, y, n, lam, w2, p2)
    jax.block_until_ready(w3)
    assert w2.is_deleted() and p2.is_deleted()
    assert not xb.is_deleted() and not y.is_deleted()
    gc.collect()
    baseline = len(jax.live_arrays())
    for _ in range(4):
        w3, p3 = _bcd_epoch(xb, y, n, lam, w3, p3)
    jax.block_until_ready(w3)
    gc.collect()
    assert len(jax.live_arrays()) <= baseline


def test_lbfgs_chunk_donates_carry(tmp_path):
    """The resumable L-BFGS driver's scan carry is donated between
    chunks: all carry leaves are consumed, so the 2·m weight-sized
    history buffers never exist twice across a chunk boundary."""
    import jax

    from keystone_tpu.models.lbfgs import lbfgs_minimize_resumable

    rng = np.random.default_rng(27)
    x = jnp.asarray(rng.normal(size=(32, 6)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(32, 2)).astype(np.float32))

    captured = []

    def save_cb(it, carry):
        captured.append(tuple(carry))

    def vag(data, w):
        xd, yd = data
        r = xd @ w - yd
        return 0.5 * jnp.vdot(r, r), xd.T @ r

    w = lbfgs_minimize_resumable(
        vag,
        (x, y),
        jnp.zeros((6, 2), jnp.float32),
        max_iter=6,
        history=3,
        checkpoint_every=3,
        save_cb=save_cb,
    )
    jax.block_until_ready(w)
    assert len(captured) == 2
    # the first chunk's carry was donated INTO the second chunk
    assert all(leaf.is_deleted() for leaf in captured[0])
    # the final carry is live (its iterate was just returned)
    assert not captured[1][0].is_deleted()


def test_oc_fit_dataflow_in_obs_summary(tmp_path):
    """An out-of-core fit under a run ledger reports the dataflow
    accounts (device-busy + transfer seconds) the bench artifact embeds."""
    from keystone_tpu.obs import ledger, metrics
    from tools.obs_report import summarize

    metrics.REGISTRY.reset()
    x, y, _ = _problem(seed=31)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=16)
    led = ledger.start_run(str(tmp_path / "obs"))
    try:
        est = BlockLeastSquaresEstimator(block_size=16, num_iter=2, lam=1e-2)
        est.fit_store(store, Dataset(y, n=y.shape[0]))
        path = led.path
    finally:
        ledger.stop_run()
    s = summarize(path)
    df = s["dataflow"]
    assert df["device_busy_seconds"] > 0
    assert df["transfer_seconds"] > 0
    assert 0 < df["device_busy_fraction"] or df["device_busy_fraction"] == 0


def test_iter_blocks_close_joins_producer(tmp_path):
    """Abandoning the generator mid-sweep must terminate the prefetch
    thread promptly (releasing its parked in-flight block), not leave a
    parked daemon thread behind."""
    import threading
    import time

    def prefetch_threads():
        return [
            t
            for t in threading.enumerate()
            if t.name == "blockstore-prefetch" and t.is_alive()
        ]

    x = np.random.default_rng(3).normal(size=(16, 24)).astype(np.float32)
    store = FeatureBlockStore.from_array(str(tmp_path / "s"), x, block_size=4)
    assert not prefetch_threads()
    order = list(range(store.num_blocks)) * 50  # long sweep, tiny consumer
    gen = store.iter_blocks(order, prefetch=2)
    b, blk = next(gen)
    assert b == order[0]
    gen.close()  # consumer abandons the sweep
    deadline = time.monotonic() + 15.0
    while prefetch_threads() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not prefetch_threads(), "prefetch thread leaked after close()"
