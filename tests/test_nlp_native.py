"""Parity: the native fused text chain (ops/nlp_native +
native/keystone_native.cpp ks_text_*) against the pure-Python
per-doc chain it replaces (round-4 review item 6).

The df TIE order is documented as divergent (Python Counter.most_common
inherits process-salted set iteration; native is deterministic by
(-df, first-doc, term)), so df parity is asserted on the full
term→count MAP and featurize parity on rows given one shared vocab."""

import collections

import numpy as np
import pytest

from keystone_tpu.ops import nlp_native
from keystone_tpu.ops.nlp import (
    CommonSparseFeatures,
    LowerCase,
    NGramsFeaturizer,
    TermFrequency,
    Tokenizer,
    Trimmer,
    log_tf,
)
from keystone_tpu.workflow.dataset import StreamDataset

pytestmark = pytest.mark.skipif(
    not nlp_native.available(), reason="native text library unavailable"
)

DOCS = [
    "  Hello, world! hello AGAIN ",
    "the quick brown fox, the quick",
    "it's a test; it's ONLY a test",
    "numbers 123 and 123 and letters",
    "",
    "    ",
    "don't DON'T don't",
    "unicode café stays café split",
] * 3


def _chained_stream(docs, batch=4):
    def src():
        for i in range(0, len(docs), batch):
            yield docs[i : i + batch]

    out = StreamDataset(src, n=len(docs), host=True)
    stages = [
        Trimmer(),
        LowerCase(),
        Tokenizer(),
        NGramsFeaturizer((1, 2)),
        TermFrequency(log_tf),
    ]
    for t in stages:
        out = t.apply_dataset(out)
    return out, stages


def _py_dicts(docs):
    t, lc, tok, ng, tf = (
        Trimmer(), LowerCase(), Tokenizer(), NGramsFeaturizer((1, 2)),
        TermFrequency(log_tf),
    )
    return [tf.apply_one(ng.apply_one(tok.apply_one(lc.apply_one(t.apply_one(d)))))
            for d in docs]


def test_df_counts_match_python():
    out, stages = _chained_stream(DOCS)
    cfg = nlp_native.chain_config(stages)
    assert cfg is not None
    acc = nlp_native.DfAccumulator(cfg)
    for i in range(0, len(DOCS), 4):
        acc.update(DOCS[i : i + 4])
    native = dict(acc.topn(100000))
    acc.close()

    df = collections.Counter()
    for d in _py_dicts(DOCS):
        df.update(set(d.keys()))
    assert native == dict(df)


def test_fit_through_stream_uses_native_and_matches():
    out, _ = _chained_stream(DOCS)
    model = CommonSparseFeatures(64, sparse_output=False).fit_dataset(out)
    # every Python-counted term's df rank set must match on distinct dfs;
    # here just assert the vocab covers the same term SET as Python's
    # top-64 (the corpus has < 64 distinct terms, so no tie pressure)
    df = collections.Counter()
    for d in _py_dicts(DOCS):
        df.update(set(d.keys()))
    assert set(model.vocab) == set(df)


@pytest.mark.parametrize("sparse", [False, True])
def test_featurize_rows_match_python(sparse):
    out, _ = _chained_stream(DOCS)
    dicts = _py_dicts(DOCS)
    model = CommonSparseFeatures(128, sparse_output=sparse).fit_arrays(dicts)
    assert model._apply_native_stream(out) is not None  # gate engaged
    want = np.stack(
        [
            (r.toarray()[0] if sparse else r)
            for r in (model.apply_one(d) for d in dicts)
        ]
    )
    feat = model.apply_dataset(out)
    rows = []
    for b in feat.batches():
        for r in b:
            rows.append(r.toarray()[0] if sparse else np.asarray(r))
    got = np.stack(rows)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_nondefault_pattern_falls_back_to_python():
    def src():
        yield ["a-b c", "d-e f"]

    out = StreamDataset(src, n=2, host=True)
    stages = [Tokenizer(pattern=r"[^a-z-]+"), NGramsFeaturizer((1,)),
              TermFrequency(None)]
    for t in stages:
        out = t.apply_dataset(out)
    assert nlp_native.chain_config(stages) is None  # unsupported pattern
    model = CommonSparseFeatures(16).fit_dataset(out)  # python path, no crash
    assert ("a-b",) in model.vocab


@pytest.mark.parametrize("sparse", [False, True])
def test_hashtf_rows_match_python(sparse):
    """Native blake2b(repr(term)) must reproduce stable_term_hash
    exactly — including apostrophe tokens, whose Python repr switches to
    double quotes — and collision accumulation must match to 1e-6."""
    from keystone_tpu.ops.nlp import HashingTF

    out, _ = _chained_stream(DOCS)
    dicts = _py_dicts(DOCS)
    model = HashingTF(num_features=128, sparse_output=sparse)  # force collisions
    # the native gate must actually ENGAGE for this chain — otherwise the
    # comparison below is vacuously Python-vs-Python
    assert model._apply_native_stream(out) is not None
    want = np.stack(
        [
            (r.toarray()[0] if sparse else r)
            for r in (model.apply_one(d) for d in dicts)
        ]
    )
    feat = model.apply_dataset(out)
    rows = []
    for b in feat.batches():
        for r in b:
            rows.append(r.toarray()[0] if sparse else np.asarray(r))
    got = np.stack(rows)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_in_memory_chain_engages_native_and_matches():
    """Non-stream apps (synthetic/loaded host Datasets) ride the same
    native path via with_items provenance: fit + featurize must match
    the per-item Python chain."""
    from keystone_tpu.ops.nlp import CommonSparseFeatures, HashingTF
    from keystone_tpu.workflow.dataset import Dataset

    ds = Dataset(list(DOCS))
    out = ds
    for t in (Trimmer(), LowerCase(), Tokenizer(), NGramsFeaturizer((1, 2)),
              TermFrequency(log_tf)):
        out = t.apply_dataset(out)
    dicts = _py_dicts(DOCS)

    est = CommonSparseFeatures(64, sparse_output=False)
    assert est._fit_native_items(out) is not None  # gate engaged
    model = est.fit_dataset(out)
    import collections

    df = collections.Counter()
    for d in dicts:
        df.update(set(d.keys()))
    assert set(model.vocab) == set(df)

    model_py = CommonSparseFeatures(128).fit_arrays(dicts)
    assert model_py._apply_native_items(out) is not None
    got = np.asarray(model_py.apply_dataset(out).array)
    want = np.stack([model_py.apply_one(d) for d in dicts])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)

    h = HashingTF(num_features=128)
    assert h._apply_native_items(out) is not None
    got = np.asarray(h.apply_dataset(out).array)
    want = np.stack([h.apply_one(d) for d in dicts])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_native_text_property_random_docs():
    """Property guard: random printable-ASCII docs (incl. apostrophes,
    digits, punctuation, odd whitespace) must produce IDENTICAL df maps
    and featurize rows on the native and Python chains."""
    import collections

    from hypothesis import given, settings
    from hypothesis import strategies as st

    from keystone_tpu.ops.nlp import CommonSparseFeatures, HashingTF

    alphabet = st.sampled_from(
        list("abcXYZ019'!.,;- \t\n") + ["don't", "  ", "café"]
    )
    docs_strategy = st.lists(
        st.lists(alphabet, max_size=30).map("".join), min_size=1, max_size=8
    )

    @settings(max_examples=25, deadline=None)
    @given(docs_strategy)
    def check(docs):
        out, stages = _chained_stream(docs, batch=3)
        cfg = nlp_native.chain_config(stages)
        dicts = _py_dicts(docs)

        acc = nlp_native.DfAccumulator(cfg)
        for i in range(0, len(docs), 3):
            acc.update(docs[i : i + 3])
        native_df = dict(acc.topn(100000))
        acc.close()
        df = collections.Counter()
        for d in dicts:
            df.update(set(d.keys()))
        assert native_df == dict(df)

        model = CommonSparseFeatures(64).fit_arrays(dicts)
        want = np.stack([model.apply_one(d) for d in dicts])
        got = np.concatenate(
            [np.asarray(b) for b in model.apply_dataset(out).batches()], axis=0
        )
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)

        h = HashingTF(num_features=64)
        wanth = np.stack([h.apply_one(d) for d in dicts])
        goth = np.concatenate(
            [np.asarray(b) for b in h.apply_dataset(out).batches()], axis=0
        )
        np.testing.assert_allclose(goth, wanth, rtol=1e-6, atol=1e-7)

    check()
