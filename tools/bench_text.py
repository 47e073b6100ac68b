"""Host text stage throughput: Python per-doc chain vs the native fused
path (ops/nlp_native), on the r4 synthetic corpus shape (120 tokens/doc,
5k-word vocab; rounds 1–5, not re-measured).

    python tools/bench_text.py [n_docs] [--python-docs M]

Measures the streaming 2-pass fit (df sweep + featurize sweep) docs/s
for both paths through the SAME StreamDataset DAG chain.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

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

VOCAB = 5000
TOKENS_PER_DOC = 120
BATCH = 2048
NUM_FEATURES = 1 << 15


def corpus(n: int):
    rng = np.random.default_rng(0)
    words = np.array([f"w{i}" for i in range(VOCAB)])
    probs = 1.0 / np.arange(1, VOCAB + 1) ** 1.1
    probs /= probs.sum()
    docs = []
    for _ in range(n):
        docs.append(" ".join(words[rng.choice(VOCAB, TOKENS_PER_DOC, p=probs)]))
    return docs


def run_two_pass(docs, use_native: bool) -> float:
    def src():
        for i in range(0, len(docs), BATCH):
            yield docs[i : i + BATCH]

    ds = StreamDataset(src, n=len(docs), host=True)
    out = ds
    for t in (Trimmer(), LowerCase(), Tokenizer(), NGramsFeaturizer((1, 2)),
              TermFrequency(log_tf)):
        out = t.apply_dataset(out)
    if not use_native:
        out._host_chain = None  # force the Python path
    t0 = time.perf_counter()
    model = CommonSparseFeatures(NUM_FEATURES, sparse_output=True).fit_dataset(out)
    t_df = time.perf_counter() - t0
    feat = model.apply_dataset(out)
    nrows = 0
    t1 = time.perf_counter()
    for b in feat.batches():
        nrows += len(b)
    t_feat = time.perf_counter() - t1
    assert nrows == len(docs)
    total = t_df + t_feat
    print(
        f"  {'native' if use_native else 'python'}: df {len(docs)/t_df:8.0f} docs/s"
        f"   featurize {len(docs)/t_feat:8.0f} docs/s   2-pass {len(docs)/total:8.0f} docs/s"
    )
    return len(docs) / total


def run_hash_pass(docs, use_native: bool) -> float:
    from keystone_tpu.ops.nlp import HashingTF

    def src():
        for i in range(0, len(docs), BATCH):
            yield docs[i : i + BATCH]

    ds = StreamDataset(src, n=len(docs), host=True)
    out = ds
    for t in (Trimmer(), LowerCase(), Tokenizer(), NGramsFeaturizer((1, 2)),
              TermFrequency(log_tf)):
        out = t.apply_dataset(out)
    if not use_native:
        out._host_chain = None
    feat = HashingTF(NUM_FEATURES, sparse_output=True).apply_dataset(out)
    t0 = time.perf_counter()
    nrows = sum(len(b) for b in feat.batches())
    dt = time.perf_counter() - t0
    assert nrows == len(docs)
    print(f"  {'native' if use_native else 'python'} hashtf: "
          f"{len(docs)/dt:8.0f} docs/s")
    return len(docs) / dt


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    n = int(args[0]) if args else 100_000
    pydocs = n
    if "--python-docs" in sys.argv:
        pydocs = int(sys.argv[sys.argv.index("--python-docs") + 1])
    print(f"corpus: {n} docs x {TOKENS_PER_DOC} tokens, vocab {VOCAB}")
    docs = corpus(n)
    native = run_two_pass(docs, use_native=True)
    py = run_two_pass(docs[:pydocs], use_native=False)
    print(f"speedup (2-pass docs/s): {native/py:.2f}x")
    hn = run_hash_pass(docs, use_native=True)
    hp = run_hash_pass(docs[:pydocs], use_native=False)
    print(f"speedup (hashtf docs/s): {hn/hp:.2f}x")


if __name__ == "__main__":
    main()
