"""Native host-text fast path (round-4 review item 6; SURVEY §2.10 text
pipelines, §7(f)).

The per-doc Python chain trim→lower→tokenize→ngram→tf→{vocab CSR | df}
measured 1.5–3.4k docs/s streaming on a 1-core host (rounds 1–5, not
re-measured) — the reference's answer to the same problem is native
code behind JNI.  Here the whole fused chain runs in
``native/keystone_native.cpp`` (``ks_text_*``): C++ tokenization and
hashing with the GIL released (ctypes) and a thread pool over docs.
The Python implementations remain both the fallback (no compiler,
non-default tokenizer patterns, custom tf functions) and the parity
reference (tests/test_nlp_native.py).

Integration: host StreamDatasets carry provenance (``_host_chain`` —
the base raw-doc stream plus the host transformers applied so far, set
by Transformer.apply_dataset).  ``CommonSparseFeatures.fit_dataset``
and ``CommonSparseFeaturesModel.apply_dataset`` recognize a supported
chain and hand the RAW doc batches to C++, skipping every intermediate
Python object (token lists, tuple n-grams, term dicts).

Known, documented divergences: (1) Unicode case edge cases — a handful
of non-ASCII characters lowercase INTO ASCII in Python (U+0130 'İ',
U+212A Kelvin); the native tokenizer treats their original bytes as
separators, so such docs tokenize differently (ordinary UTF-8 text is
bit-identical; multilingual corpora needing full Unicode case mapping
should use the Python path).  (2) df top-N TIE order.  Python's
``Counter.most_common`` breaks df ties by first-insertion order, which
inherits per-process-salted ``set`` iteration — it is not stable
across processes even Python-vs-Python.  The native path is
deterministic: (-df, first-doc-index, term).  Terms with distinct dfs
are identical.
"""

from __future__ import annotations

import ctypes
from typing import List, Optional, Sequence, Tuple

import numpy as np

#: joined-key separator bridging C++ term strings <-> Python token tuples
SEP = "\x1f"

_DEFAULT_TOKEN_PATTERN = r"[^a-zA-Z0-9']+"


def _lib():
    from keystone_tpu.native import get_lib

    lib = get_lib()
    if lib is None or not hasattr(lib, "ks_text_featurize") or not hasattr(
        lib, "ks_text_hashtf"
    ):
        # both entry points ship in the same build (ABI v4); a partial
        # binary means a stale .so — fall back to Python entirely
        return None
    return lib


def available() -> bool:
    return _lib() is not None


def _pack_docs(docs: Sequence[str]) -> Tuple[bytes, np.ndarray]:
    enc = [d.encode("utf-8", "surrogatepass") for d in docs]
    offs = np.zeros(len(enc) + 1, np.int64)
    np.cumsum([len(b) for b in enc], out=offs[1:])
    return b"".join(enc), offs


def chain_config(stages) -> Optional[dict]:
    """Parse a host-transformer chain into a native config, or None if
    any stage is outside the supported pattern: [Trimmer?] [LowerCase?]
    Tokenizer(default pattern) NGramsFeaturizer(orders within 1..8)
    TermFrequency(None | log_tf)."""
    from keystone_tpu.ops.nlp import (
        LowerCase,
        NGramsFeaturizer,
        TermFrequency,
        Tokenizer,
        Trimmer,
        log_tf,
    )

    stages = list(stages)
    trim = lower = False
    while stages and isinstance(stages[0], (Trimmer, LowerCase)):
        if isinstance(stages[0], Trimmer):
            trim = True
        else:
            lower = True
        stages.pop(0)
    if len(stages) != 3:
        return None
    tok, ngrams, tf = stages
    if not isinstance(tok, Tokenizer) or tok.pattern != _DEFAULT_TOKEN_PATTERN:
        return None
    if not isinstance(ngrams, NGramsFeaturizer) or not all(
        1 <= n <= 8 for n in ngrams.orders
    ):
        return None
    if len(set(ngrams.orders)) != len(ngrams.orders):
        # duplicate orders (e.g. (1, 1)) collapse in the orders_mask, so
        # the native path would emit each n-gram once where the Python
        # path counts it per duplicate — silently halving tf values.
        # Fall back to the Python path, which honors duplicates.
        return None
    if not isinstance(tf, TermFrequency) or tf.fn not in (None, log_tf):
        return None
    mask = 0
    for n in ngrams.orders:
        mask |= 1 << (n - 1)
    return {
        "orders_mask": mask,
        "log_tf": 1 if tf.fn is log_tf else 0,
        "lower": 1 if lower else 0,
        "trim": 1 if trim else 0,
    }



def _unpack_native_rows(lib, indptr, out_idx, out_val, n, num_features,
                        sparse_output):
    """Copy a ks_text_* CSR result out of native memory and build the
    per-doc payload (scipy CSR rows or a dense (n, F) array) — the one
    place that owns the copy-out/free and row-construction contract."""
    import scipy.sparse as sp

    nnz = int(indptr[-1])
    try:
        idx = np.ctypeslib.as_array(out_idx, shape=(max(nnz, 1),))[:nnz].copy()
        val = np.ctypeslib.as_array(out_val, shape=(max(nnz, 1),))[:nnz].copy()
    finally:
        lib.ks_free(out_idx)
        lib.ks_free(out_val)
    if sparse_output:
        rows: List = []
        for i in range(n):
            lo, hi = int(indptr[i]), int(indptr[i + 1])
            rows.append(
                sp.csr_matrix(
                    (val[lo:hi], idx[lo:hi], np.array([0, hi - lo], np.int32)),
                    shape=(1, num_features),
                    copy=False,
                )
            )
        return rows
    dense = np.zeros((n, num_features), np.float32)
    for i in range(n):
        lo, hi = int(indptr[i]), int(indptr[i + 1])
        dense[i, idx[lo:hi]] = val[lo:hi]
    return dense


def featurize_docs(
    docs: Sequence[str],
    vocab_keys_joined: bytes,
    vocab_offs: np.ndarray,
    vsize: int,
    cfg: dict,
    num_features: int,
    sparse_output: bool,
    threads: int = 0,
):
    """Raw docs -> CSR rows (scipy, one per doc) or a dense (n, F) array
    over a prepared vocabulary (see ``pack_vocab``)."""
    import scipy.sparse as sp

    lib = _lib()
    blob, offs = _pack_docs(docs)
    n = len(docs)
    indptr = np.zeros(n + 1, np.int64)
    out_idx = ctypes.POINTER(ctypes.c_int32)()
    out_val = ctypes.POINTER(ctypes.c_float)()
    rc = lib.ks_text_featurize(
        blob,
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int64(n),
        vocab_keys_joined,
        vocab_offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int64(vsize),
        ctypes.c_uint32(cfg["orders_mask"]),
        cfg["log_tf"],
        cfg["lower"],
        cfg["trim"],
        threads,
        indptr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.byref(out_idx),
        ctypes.byref(out_val),
    )
    if rc != 0:
        raise RuntimeError(f"ks_text_featurize failed: {rc}")
    return _unpack_native_rows(
        lib, indptr, out_idx, out_val, n, num_features, sparse_output
    )


def hashtf_docs(
    docs: Sequence[str],
    cfg: dict,
    num_features: int,
    sparse_output: bool,
    threads: int = 0,
):
    """Raw docs -> HashingTF rows: col = blake2b8(repr(term)) %
    num_features (stable_term_hash's exact contract, reimplemented in
    C++ from RFC 7693 — parity pinned incl. apostrophe tokens, whose
    repr double-quotes); colliding terms' tf values accumulate."""
    import scipy.sparse as sp

    lib = _lib()
    blob, offs = _pack_docs(docs)
    n = len(docs)
    indptr = np.zeros(n + 1, np.int64)
    out_idx = ctypes.POINTER(ctypes.c_int32)()
    out_val = ctypes.POINTER(ctypes.c_float)()
    rc = lib.ks_text_hashtf(
        blob,
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int64(n),
        ctypes.c_uint32(cfg["orders_mask"]),
        cfg["log_tf"],
        cfg["lower"],
        cfg["trim"],
        ctypes.c_int64(num_features),
        threads,
        indptr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.byref(out_idx),
        ctypes.byref(out_val),
    )
    if rc != 0:
        raise RuntimeError(f"ks_text_hashtf failed: {rc}")
    return _unpack_native_rows(
        lib, indptr, out_idx, out_val, n, num_features, sparse_output
    )


def pack_vocab(vocab: dict) -> Tuple[bytes, np.ndarray, int]:
    """Python {token-tuple: col} vocab -> (joined blob, offsets, size),
    ordered by column id so C++ ids equal Python ids."""
    items = sorted(vocab.items(), key=lambda kv: kv[1])
    enc = [SEP.join(t).encode("utf-8", "surrogatepass") for t, _ in items]
    offs = np.zeros(len(enc) + 1, np.int64)
    np.cumsum([len(b) for b in enc], out=offs[1:])
    return b"".join(enc), offs, len(enc)


class DfAccumulator:
    """Streaming df sweep: feed raw doc batches, then ``topn`` returns
    [(token-tuple, df)] by (-df, first-doc, term)."""

    def __init__(self, cfg: dict):
        lib = _lib()
        lib.ks_text_df_new.restype = ctypes.c_void_p
        self._lib = lib
        self._h = ctypes.c_void_p(
            lib.ks_text_df_new(
                ctypes.c_uint32(cfg["orders_mask"]), cfg["lower"], cfg["trim"]
            )
        )

    def update(self, docs: Sequence[str]) -> None:
        blob, offs = _pack_docs(docs)
        rc = self._lib.ks_text_df_update(
            self._h,
            blob,
            offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            ctypes.c_int64(len(docs)),
        )
        if rc != 0:
            raise RuntimeError(f"ks_text_df_update failed: {rc}")

    def topn(self, n: int) -> List[Tuple[tuple, int]]:
        lib = self._lib
        terms = ctypes.POINTER(ctypes.c_char)()
        offs = ctypes.POINTER(ctypes.c_int64)()
        counts = ctypes.POINTER(ctypes.c_int64)()
        out_n = ctypes.c_int64(0)
        rc = lib.ks_text_df_topn(
            self._h,
            ctypes.c_int64(n),
            ctypes.byref(terms),
            ctypes.byref(offs),
            ctypes.byref(counts),
            ctypes.byref(out_n),
        )
        if rc != 0:
            raise RuntimeError(f"ks_text_df_topn failed: {rc}")
        try:
            m = out_n.value
            off = np.ctypeslib.as_array(offs, shape=(m + 1,))
            blob = ctypes.string_at(terms, int(off[m])) if m else b""
            cnt = np.ctypeslib.as_array(counts, shape=(max(m, 1),))
            out = []
            for i in range(m):
                key = blob[int(off[i]) : int(off[i + 1])].decode(
                    "utf-8", "surrogatepass"
                )
                out.append((tuple(key.split(SEP)), int(cnt[i])))
            return out
        finally:
            lib.ks_free(terms)
            lib.ks_free(offs)
            lib.ks_free(counts)

    def close(self) -> None:
        if self._h:
            self._lib.ks_text_df_free(self._h)
            self._h = None

    def __del__(self):  # best-effort; close() is the real contract
        try:
            self.close()
        except Exception:
            pass
