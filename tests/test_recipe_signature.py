"""Node identity without reading weights (``utils/hashing.py``): a node whose
arrays were drawn from a seed signs with the recipe of the draw; given,
fitted or reassigned arrays sign with a digest of their bytes.  One contract
holds both: equal signatures ⇒ equal values.  A missed merge is allowed, an
alias never.
"""

import copy
import os
import pickle
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.obs import ledger
from keystone_tpu.ops import CosineRandomFeatures, RandomSignNode
from keystone_tpu.utils import hashing
from keystone_tpu.workflow import Dataset, GraphExecutor, Pipeline
from keystone_tpu.workflow import graph as G
from keystone_tpu.workflow.optimizer import EquivalentNodeMergeRule
from keystone_tpu.workflow.state import _signature_key

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COSINE = dict(num_input_features=6, num_output_features=8, gamma=0.05, seed=3,
              distribution="gaussian")


def _cosine(**changed):
    return CosineRandomFeatures.init(**{**COSINE, **changed})


def _by_recipe(node) -> bool:
    return node.params()[1].startswith("recipe:")


def _nodes_of(graph, cls):
    return [
        n for n, op in graph.operators.items()
        if isinstance(op, G.TransformerOperator) and isinstance(op.transformer, cls)
    ]


def _merged(a, b, dim=6):
    """Gather ``a`` and ``b`` over one dataset, run the CSE rule alone and
    execute: (nodes of their class left, times one executed, the output)."""
    data = Dataset(np.random.default_rng(0).normal(size=(16, dim)).astype(np.float32))
    g = EquivalentNodeMergeRule().apply(Pipeline.gather([Pipeline.of(a), Pipeline.of(b)])(data).graph)
    with ledger.span("test.mark") as mark:
        pass
    out = GraphExecutor(g).execute(g.sinks[0]).dataset.array
    ran = [
        r for r in ledger.recent_spans()
        if r.span_id > mark.span_id and r.name == "executor.stage"
        and r.attrs["node"] == type(a).__name__
    ]
    return len(_nodes_of(g, type(a))), len(ran), np.asarray(out)


# ------------------------------------------------ (a) the recipe is the identity
@pytest.mark.parametrize(
    "make_a, make_b, merges",
    [
        (_cosine, _cosine, True),
        (_cosine, lambda: _cosine(seed=4), False),
        (_cosine, lambda: _cosine(gamma=0.06), False),
        (_cosine, lambda: _cosine(distribution="cauchy"), False),
        (_cosine, lambda: _cosine(num_output_features=9), False),
        (lambda: RandomSignNode.init(6, seed=2), lambda: RandomSignNode.init(6, seed=2), True),
        (lambda: RandomSignNode.init(6, seed=2), lambda: RandomSignNode.init(6, seed=5), False),
    ],
    ids=["cosine-same", "cosine-seed", "cosine-gamma", "cosine-distribution",
         "cosine-shape", "signs-same", "signs-seed"],
)
def test_same_recipe_merges_and_runs_once_and_any_other_does_not(make_a, make_b, merges):
    a, b = make_a(), make_b()
    assert _by_recipe(a) and _by_recipe(b)
    assert (a.signature() == b.signature()) is merges
    nodes, ran, out = _merged(a, b)
    assert (nodes, ran) == ((1, 1) if merges else (2, 2))
    # soundness, the one direction promised: equal signatures ⇒ equal values
    half = out.shape[1] // 2
    if merges:
        for name in type(a).traced_attrs:
            np.testing.assert_array_equal(getattr(a, name), getattr(b, name))
        np.testing.assert_array_equal(out[:, :half], out[:, half:])


def test_a_sign_node_of_another_width_does_not_share_a_signature():
    assert RandomSignNode.init(6, seed=2).signature() != RandomSignNode.init(7, seed=2).signature()


# -------------------------------------- (b) a reassigned array re-signs by content
@pytest.mark.parametrize(
    "make, attr",
    [(_cosine, "w"), (_cosine, "b"), (lambda: RandomSignNode.init(6, seed=2), "signs")],
    ids=["cosine-w", "cosine-b", "signs"],
)
def test_reassigning_an_array_falls_back_to_the_content_digest(make, attr):
    node, twin = make(), make()
    recipe_sig = node.signature()
    setattr(node, attr, getattr(node, attr) * 2.0)
    assert not _by_recipe(node)
    assert node.signature() != recipe_sig == twin.signature()
    arrays = [getattr(node, name) for name in type(node).traced_attrs]
    assert node.params()[1] == hashing.array_fingerprint(*arrays)
    # and a third value signs differently again: the cache follows the objects
    setattr(node, attr, getattr(node, attr) * 2.0)
    arrays = [getattr(node, name) for name in type(node).traced_attrs]
    assert node.params()[1] == hashing.array_fingerprint(*arrays)
    # the same VALUES put back as new objects stay on content: only the
    # arrays ``init`` made carry the recipe
    setattr(node, attr, jnp.array(getattr(twin, attr)))
    assert not _by_recipe(node) and node.signature() != recipe_sig
    assert _merged(node, twin)[:2] == (2, 2)


# ------------------------------------ (c) given arrays sign by content, as before
@pytest.mark.parametrize("kind", ["equal-bytes", "other-bytes", "given-vs-init"])
def test_given_arrays_sign_by_content(kind):
    drawn = _cosine()
    w, b = np.asarray(drawn.w), np.asarray(drawn.b)
    a = CosineRandomFeatures(jnp.asarray(w), jnp.asarray(b))
    assert a.params() == (a.w.shape, hashing.array_fingerprint(w, b))
    if kind == "equal-bytes":
        other, merges = CosineRandomFeatures(jnp.asarray(w.copy()), jnp.asarray(b.copy())), True
    elif kind == "other-bytes":
        other, merges = CosineRandomFeatures(jnp.asarray(w + 1.0), jnp.asarray(b)), False
    else:  # the direction that weakened: same bytes, not the same signature
        other, merges = drawn, False
    assert (a.signature() == other.signature()) is merges
    assert _merged(a, other)[:2] == ((1, 1) if merges else (2, 2))


def test_a_recipe_and_a_digest_can_never_be_equal():
    digest = hashing.array_fingerprint(np.zeros(3, np.float32))
    assert all(c in "0123456789abcdef" for c in digest)
    assert not set(hashing._RECIPE_PREFIX) <= set("0123456789abcdef")


# ----------------------- (d) the recipe survives what a node survives, and a process
_WORKER = """
import sys
from keystone_tpu.loaders.mnist import MnistLoader
from keystone_tpu.ops import CosineRandomFeatures, RandomSignNode
from keystone_tpu.workflow import Pipeline
from keystone_tpu.workflow.state import _signature_key
data = MnistLoader.synthetic(8, seed=3).data  # a NAMED dataset
dim = data.array.shape[1]
for node in (CosineRandomFeatures.init(dim, 8, gamma=0.05, seed=3), RandomSignNode.init(dim, seed=2)):
    g = Pipeline.of(node)(data).graph
    key = _signature_key(g.prefix_signature(g.sink_dependencies[g.sinks[0]], {}))
    print("SIG", type(node).__name__, key, repr(node.signature()), sep="\\t", flush=True)
"""


@pytest.fixture(scope="module")
def two_processes():
    """{class name: (state key, signature)} as two fresh processes print them."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    runs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", _WORKER], capture_output=True, text=True,
                           timeout=300, env=env, cwd=ROOT)
        assert p.returncode == 0, p.stderr[-2000:]
        rows = [ln.split("\t")[1:] for ln in p.stdout.splitlines() if ln.startswith("SIG")]
        runs.append({name: (key, sig) for name, key, sig in rows})
    return runs


@pytest.mark.parametrize("cls", [CosineRandomFeatures, RandomSignNode], ids=lambda c: c.__name__)
def test_signature_and_state_key_are_equal_across_processes(two_processes, cls):
    from keystone_tpu.loaders.mnist import MnistLoader

    first, second = (run[cls.__name__] for run in two_processes)
    assert first == second
    assert first[0] != "None" and "recipe:" in first[1]
    # and this process, a third, agrees with both
    data = MnistLoader.synthetic(8, seed=3).data
    dim = data.array.shape[1]
    node = (CosineRandomFeatures.init(dim, 8, gamma=0.05, seed=3)
            if cls is CosineRandomFeatures else RandomSignNode.init(dim, seed=2))
    g = Pipeline.of(node)(data).graph
    key = _signature_key(g.prefix_signature(g.sink_dependencies[g.sinks[0]], {}))
    assert (key, repr(node.signature())) == first


@pytest.mark.parametrize(
    "through",
    [lambda t: pickle.loads(pickle.dumps(t)), copy.copy, copy.deepcopy],
    ids=["pickle", "copy", "deepcopy"],
)
@pytest.mark.parametrize("make", [_cosine, lambda: RandomSignNode.init(6, seed=2)],
                         ids=["cosine", "signs"])
def test_the_recipe_survives_pickle_and_copy(make, through, monkeypatch):
    node = make()
    sig = node.signature()
    monkeypatch.setattr(hashing, "array_fingerprint", lambda *a: pytest.fail("read the bytes"))
    clone = through(node)
    assert clone is not node and clone.signature() == sig == node.signature()


def test_stripped_template_drops_the_recipe_with_the_arrays():
    from keystone_tpu.workflow.transformer import stripped_template

    node = _cosine()
    tpl = stripped_template(node)
    assert tpl.w is None and tpl.b is None and "_fp" not in vars(tpl)
    assert _by_recipe(node)  # the node itself keeps it


# ------------------- (e) a whole fit reads none of the random features' bytes
def _count_fingerprinted_bytes(monkeypatch):
    read = []
    real = hashing.array_fingerprint

    def counting(*arrays):
        read.append(sum(int(np.asarray(a).nbytes) for a in arrays))
        return real(*arrays)

    monkeypatch.setattr(hashing, "array_fingerprint", counting)
    return read


def _toy_timit(branches):
    from keystone_tpu.pipelines.timit import Config, TimitPipeline

    rng = np.random.default_rng(0)
    x = Dataset(rng.normal(size=(96, 12)).astype(np.float32))
    labels = Dataset(rng.integers(0, 5, size=96).astype(np.int32))
    cfg = Config(num_cosine_features=16 * branches, cosine_block_size=16, solver_block_size=16,
                 num_classes=5, num_epochs=1, seed=11)
    return TimitPipeline.build(cfg, x, labels)


def _toy_mnist(branches):
    from keystone_tpu.pipelines.mnist_random_fft import Config, MnistRandomFFT

    rng = np.random.default_rng(0)
    x = Dataset(rng.integers(0, 255, size=(96, 20)).astype(np.float32))
    labels = Dataset(rng.integers(0, 10, size=96).astype(np.int32))
    return MnistRandomFFT.build(Config(num_ffts=branches, seed=11), x, labels)


@pytest.mark.parametrize("build, branches", [(_toy_timit, 4), (_toy_mnist, 3)],
                         ids=["TimitPipeline", "MnistRandomFFT"])
def test_a_fit_hashes_no_bytes_of_seeded_weights(build, branches, monkeypatch):
    read = _count_fingerprinted_bytes(monkeypatch)
    with ledger.span("test.mark") as mark:
        pass
    build(branches).fit().block_until_ready()
    assert sum(read) == 0
    optimizes = [r for r in ledger.recent_spans()
                 if r.span_id > mark.span_id and r.name == "pipeline.optimize"]
    first = min(optimizes, key=lambda r: r.span_id)  # the fit's own: the whole rule set
    assert first.attrs["sig_bytes_hashed"] == 0
    assert first.attrs["sig_by_recipe"] == branches
    assert all(r.attrs["sig_bytes_hashed"] == 0 for r in optimizes)


def test_the_optimize_span_counts_the_bytes_a_content_signature_reads():
    w, b = np.ones((8, 6), np.float32), np.zeros(8, np.float32)
    given = [CosineRandomFeatures(jnp.asarray(w + i), jnp.asarray(b)) for i in range(2)]
    pipe = Pipeline.gather([Pipeline.of(t) for t in given + [_cosine()]])
    with ledger.span("test.mark") as mark:
        pass
    pipe(Dataset(np.zeros((4, 6), np.float32))).get()
    span = min((r for r in ledger.recent_spans()
                if r.span_id > mark.span_id and r.name == "pipeline.optimize"),
               key=lambda r: r.span_id)
    assert span.attrs["sig_bytes_hashed"] == 2 * (w.nbytes + b.nbytes)  # once a node: then cached
    assert span.attrs["sig_by_recipe"] == 1


def test_tallies_nest_and_count_nothing_outside():
    node = _cosine()
    hashing.array_fingerprint(np.zeros(4, np.float32))  # no tally open: not an error
    with hashing.tally_signatures() as outer:
        node.params()
        with hashing.tally_signatures() as inner:
            hashing.array_fingerprint(np.zeros(4, np.float32))
            node.params()
            node.params()
        hashing.array_fingerprint(np.zeros(2, np.float32))
    assert (inner.bytes_hashed, inner.by_recipe) == (16, 1)
    assert (outer.bytes_hashed, outer.by_recipe) == (24, 1)


def test_obs_report_prints_what_signatures_cost(tmp_path):
    sys.path.insert(0, ROOT)
    from tools.obs_report import render, summarize

    run = ledger.start_run(str(tmp_path))
    try:
        _toy_timit(2).fit().block_until_ready()
    finally:
        ledger.stop_run()
    text = render(summarize(run.path))
    line = next(ln for ln in text.splitlines() if "sig_bytes_hashed" in ln)
    assert "sig_bytes_hashed=0" in line and "sig_by_recipe=2" in line
