"""Parallel host text maps (round-3 review weak-5: the host text stage was
single-threaded pure Python).  Threads can't help — the GIL serializes
pure-Python tokenization (libjpeg's thread pool worked because C decode
releases the GIL) — so host_map forks processes.  These tests pin
result parity (pooled == sequential), the fallbacks, and the wired
paths through the NLP featurizers."""

import numpy as np
import pytest

from keystone_tpu.ops.nlp import (
    CommonSparseFeatures,
    HashingTF,
    NGramsFeaturizer,
    TermFrequency,
    Tokenizer,
    log_tf,
    stable_term_hash,
)
from keystone_tpu.utils.hostmap import host_map, host_workers
from keystone_tpu.workflow import Dataset


def _docs(n=64, seed=0):
    rng = np.random.default_rng(seed)
    vocab = [f"w{i}" for i in range(200)]
    return [" ".join(rng.choice(vocab, size=30)) for _ in range(n)]


def test_host_map_pool_matches_sequential():
    tok = Tokenizer()
    docs = _docs(64)
    seq = [tok.apply_one(d) for d in docs]
    par = host_map(tok.apply_one, docs, workers=2, min_items=2)
    assert par == seq  # order AND content


def test_host_map_unpicklable_falls_back():
    captured = []
    fn = lambda x: (captured.append(x), x * 2)[1]  # noqa: E731
    out = host_map(fn, list(range(10)), workers=4, min_items=2)
    assert out == [i * 2 for i in range(10)]
    assert len(captured) == 10  # ran in THIS process (sequential fallback)


def test_host_map_small_input_stays_sequential():
    tok = Tokenizer()
    out = host_map(tok.apply_one, ["a b", "c d"], workers=4, min_items=1024)
    assert out == [["a", "b"], ["c", "d"]]


def test_host_workers_env(monkeypatch):
    monkeypatch.setenv("KEYSTONE_HOST_WORKERS", "3")
    assert host_workers() == 3
    monkeypatch.setenv("KEYSTONE_HOST_WORKERS", "nope")
    assert host_workers() == 1


def test_text_chain_pooled_matches_sequential(monkeypatch, mesh):
    """The wired path: the full tokenize→ngram→tf→featurize chain over
    an eager host Dataset under forced 2-worker pooling reproduces the
    single-worker rows exactly."""
    from keystone_tpu.utils import hostmap

    docs = _docs(48, seed=3)
    chain = (
        Tokenizer()
        .and_then(NGramsFeaturizer((1, 2)))
        .and_then(TermFrequency(log_tf))
    )
    terms = chain(Dataset(docs)).get()
    csf = CommonSparseFeatures(512, sparse_output=True).fit_dataset(terms)
    seq_rows = csf.apply_dataset(terms)

    monkeypatch.setattr(hostmap, "host_workers", lambda: 2)
    monkeypatch.setattr(
        hostmap.host_map, "__defaults__", (None, 2)
    )  # min_items=2 so the 48-doc input engages the pool
    par_terms = chain(Dataset(docs)).get()
    par_rows = csf.apply_dataset(par_terms)
    assert [d for d in par_terms.items] == [d for d in terms.items]
    for a, b in zip(par_rows.items, seq_rows.items):
        np.testing.assert_array_equal(a.toarray(), b.toarray())


def test_hashing_tf_memo_is_transparent():
    """stable_term_hash memoization must be value-invisible (cached ==
    uncached) and HashingTF rows unchanged by cache state."""
    from keystone_tpu.ops import nlp

    t1 = ("alpha", "beta")
    h_cold = stable_term_hash(t1)
    assert stable_term_hash(t1) == h_cold  # warm hit
    nlp._TERM_HASH_MEMO.clear()
    assert stable_term_hash(t1) == h_cold  # recomputed identically
    h = HashingTF(256, sparse_output=True)
    row1 = h.apply_one({t1: 2.0, ("gamma",): 1.0}).toarray()
    nlp._TERM_HASH_MEMO.clear()
    row2 = h.apply_one({t1: 2.0, ("gamma",): 1.0}).toarray()
    np.testing.assert_array_equal(row1, row2)


def _boom(x):
    if x == 3:
        raise ValueError("bad doc 3")
    return x * 2


def test_host_map_fn_error_propagates():
    """A data error raised by fn must propagate unchanged (sequential
    semantics), never disable the pool or silently retry."""
    from keystone_tpu.utils import hostmap

    with pytest.raises(ValueError, match="bad doc 3"):
        host_map(_boom, list(range(8)), workers=2, min_items=2)
    # the pool survives a fn error: the next map still works pooled
    out = host_map(_boom, [0, 1, 2], workers=2, min_items=2)
    assert out == [0, 2, 4]
    assert hostmap._EXECUTOR is not None


def test_host_map_broken_pool_falls_back_sequentially(monkeypatch):
    """BrokenProcessPool IS a RuntimeError subclass — the data-error
    re-raise filter must not swallow the broken-pool fallback (a killed
    worker must complete the map sequentially and tear the pool down
    for rebuild on next use)."""
    from concurrent.futures.process import BrokenProcessPool

    from keystone_tpu.utils import hostmap

    class _DeadFuture:
        def result(self):
            raise BrokenProcessPool(
                "A process in the process pool was terminated abruptly"
            )

    class _DeadPool:
        def submit(self, *a, **k):
            return _DeadFuture()

        def shutdown(self, **k):
            pass

    monkeypatch.setattr(
        hostmap, "_get_executor", lambda w: (_DeadPool(), w)
    )
    out = host_map(_boom, [0, 1, 2], workers=2, min_items=2)
    assert out == [0, 2, 4]  # completed sequentially in THIS process


def test_trivial_host_ops_opt_out_of_pool(monkeypatch):
    """Trimmer/LowerCase (one str method per item) must not ship the
    corpus through IPC — parallel_host=False keeps them sequential."""
    from keystone_tpu.ops.nlp import LowerCase, Trimmer
    from keystone_tpu.utils import hostmap

    assert Trimmer.parallel_host is False
    assert LowerCase.parallel_host is False

    def never(*a, **k):  # pragma: no cover - failing is the assert
        raise AssertionError("trivial op reached the worker pool")

    monkeypatch.setattr(hostmap, "host_map", never)
    out = Trimmer().apply_dataset(Dataset(["  a ", " b"]))
    assert out.items == ["a", "b"]


def test_csr_row_rejects_out_of_bounds_columns():
    """The direct CSR constructor skips scipy's validation, so _csr_row
    reinstates it: a vocab/num_features mismatch raises instead of
    silently zeroing features."""
    from keystone_tpu.ops.nlp import _csr_row

    with pytest.raises(ValueError, match="out of bounds"):
        _csr_row([600], [1.0], 512)
    with pytest.raises(ValueError, match="out of bounds"):
        _csr_row([-1], [1.0], 512)


def test_csr_row_direct_matches_coo_semantics():
    """_csr_row (direct constructor, no COO sort/dedup pass) must build
    the same matrix scipy's COO path would for vocab rows."""
    import scipy.sparse as sp

    from keystone_tpu.ops.nlp import _csr_row

    cols, vals, d = [7, 2, 30], [1.5, 2.0, 0.5], 64
    direct = _csr_row(cols, vals, d)
    coo = sp.csr_matrix(
        (vals, ([0] * len(cols), cols)), shape=(1, d), dtype=np.float32
    )
    np.testing.assert_array_equal(direct.toarray(), coo.toarray())
    assert direct.dtype == np.float32
