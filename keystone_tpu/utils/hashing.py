"""Fingerprints of weight arrays: the identity a weight-carrying node signs with.

Weight-carrying transformers (random features, convolution filters, GMM
vocabularies) need a *stable* identity for CSE and saved-state keys —
``id()`` is only unique within a process and unusable as a persistent
key.

**The contract, in one direction: equal signatures ⇒ equal values.**  The
CSE rule aliases nodes on it and ``workflow/state.py`` loads a saved
dataset in place of a prefix on it, in another process.  The other
direction may weaken: two nodes that hold equal values under different
signatures are a missed merge (a recomputation), never a wrong answer.
An alias is never allowed.

Two answers satisfy it, under ONE cache (:func:`cached_fingerprint`):

- **provenance, where the node knows it**: a node whose arrays were drawn
  from a seed (``CosineRandomFeatures.init``, ``RandomSignNode.init``)
  pins the *recipe* of the draw to those very array objects
  (:func:`pin_recipe`), and signs with it without reading a byte — as a
  ``Dataset`` signs with its name and not its rows;
- **content, otherwise**: a short digest of the array bytes
  (:func:`array_fingerprint`), which copies every array to the host —
  for given, fitted or loaded weights, and for a seeded node from the
  moment one of its arrays is reassigned.

What the two cost inside a region is counted by :func:`tally_signatures`
(the ``pipeline.optimize`` span reports it as ``sig_bytes_hashed`` and
``sig_by_recipe``).
"""

from __future__ import annotations

import contextlib
import hashlib
import threading

import numpy as np

#: what a recipe-made fingerprint starts with.  A content digest is hex,
#: so the two kinds can never be equal.
_RECIPE_PREFIX = "recipe:"

_TLS = threading.local()  # .tallies: this thread's open SignatureTally's


def _open_tallies() -> list:
    tallies = getattr(_TLS, "tallies", None)
    if tallies is None:
        tallies = _TLS.tallies = []
    return tallies


class SignatureTally:
    """What signing cost while it was open: ``bytes_hashed`` read to the
    host by :func:`array_fingerprint`, and ``recipe_nodes``, the ``id()``
    of every object that :func:`cached_fingerprint` answered with a pinned
    recipe (a rule may ask a node more than once; the objects outlive the
    tally's region, so their ids are theirs alone there)."""

    def __init__(self):
        self.bytes_hashed = 0
        self.recipe_nodes: set = set()

    @property
    def by_recipe(self) -> int:
        return len(self.recipe_nodes)


@contextlib.contextmanager
def tally_signatures():
    """Count this thread's signing work inside the ``with`` (regions nest:
    an outer tally includes the inner's)."""
    tally = SignatureTally()
    _open_tallies().append(tally)
    try:
        yield tally
    finally:
        _open_tallies().remove(tally)


def array_fingerprint(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        arr = np.asarray(a)
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
        for tally in _open_tallies():
            tally.bytes_hashed += arr.nbytes
    return h.hexdigest()[:16]


def _stable_repr(p) -> str:
    """A process-stable repr of a ``params()`` value: containers recurse
    per element, and ONLY an element whose default repr carries a
    process-local address collapses to its type name — collapsing the
    whole container would also drop its well-behaved siblings, letting
    two pipelines differing only in those params hash identically (the
    stale-artifact hazard the signature exists to prevent)."""
    if isinstance(p, (tuple, list)):
        inner = ",".join(_stable_repr(x) for x in p)
        return f"{type(p).__name__}({inner})"
    if isinstance(p, dict):
        items = sorted(
            (_stable_repr(k), _stable_repr(v)) for k, v in p.items()
        )
        return "dict(" + ",".join(f"{k}:{v}" for k, v in items) + ")"
    r = repr(p)
    return type(p).__name__ if " at 0x" in r else r


def pipeline_fingerprint(pipeline) -> str:
    """Stable content hash of a fitted pipeline: graph structure (topo
    order of operator/transformer types + CSE params) plus every fitted
    array's shape/dtype/bytes.

    The AOT artifact tier (``FrozenApplier.export_artifacts``) keys
    serialized executables by this — an artifact must never be replayed
    against a pipeline whose weights differ from the one it was lowered
    from, and process-local identities (``id()``, optimizer output,
    pickle bytes of hash-randomized sets) are all unstable across the
    publish/deploy process boundary.  Computed from the PRE-optimizer
    graph (the pickled deploy payload), never the optimized one: rules
    like ProfilingAutoCacheRule place nodes by measured timings, so two
    processes can optimize the same pipeline into different graphs.

    Cached on the instance (``_keystone_fp``), validated by fitted-array
    identity like :func:`cached_fingerprint` — replacing a fitted array
    invalidates the cache instead of reporting the stale digest.  The
    cache attribute survives pickling, so replica clones of a published
    pipeline reuse the publisher's hash without re-reading every weight.
    """
    from keystone_tpu.workflow.executor import block_on_arrays

    g = pipeline.graph
    struct = hashlib.sha256()
    arrays: list = []
    for n in g.topological_nodes():
        op = g.operators[n]
        struct.update(type(op).__name__.encode())
        t = getattr(op, "transformer", None)
        if t is None:
            continue
        struct.update(type(t).__name__.encode())
        try:
            p = t.params()
        except Exception:
            p = None
        struct.update(_stable_repr(p).encode())
        block_on_arrays(t, visit=arrays.append)
    struct_hex = struct.hexdigest()[:16]
    cached = getattr(pipeline, "_keystone_fp", None)
    if (
        cached is not None
        and cached[0] == struct_hex
        and len(cached[1]) == len(arrays)
        and all(a is b for a, b in zip(cached[1], arrays))
    ):
        return cached[2]
    fp = struct_hex + array_fingerprint(*arrays)
    try:
        pipeline._keystone_fp = (struct_hex, tuple(arrays), fp)
    except AttributeError:
        pass
    return fp


def cached_fingerprint(obj, attr: str, *arrays) -> str:
    """Compute once per object, cache on the instance.

    The cache records the array objects it answers for (strong refs —
    they're alive through the owning transformer anyway) and is valid
    only while the same objects are passed, so reassigning a
    transformer's weights (``t.filters = new``) invalidates it instead
    of reporting the stale answer (which would let CSE or saved-state
    rules silently alias nodes with different weights).  Bare ``id()``
    keys would be unsound here: CPython reuses addresses after GC.

    The answer is the content digest of ``arrays``, unless
    :func:`pin_recipe` put the recipe of their draw there first: then it
    is the recipe, for as long as the same objects are passed, and the
    content digest from the first call after one was replaced."""
    cached = getattr(obj, attr, None)
    if (
        cached is not None
        and len(cached[0]) == len(arrays)
        and all(a is b for a, b in zip(cached[0], arrays))
    ):
        if cached[1].startswith(_RECIPE_PREFIX):
            for tally in _open_tallies():
                tally.recipe_nodes.add(id(obj))
        return cached[1]
    fp = array_fingerprint(*arrays)
    setattr(obj, attr, (tuple(arrays), fp))
    return fp


def pin_recipe(obj, attr: str, arrays, **recipe) -> None:
    """Seed ``obj``'s :func:`cached_fingerprint` cache with the recipe of
    the seeded draw that just made ``arrays``, so the node signs without
    a device-to-host copy of them.

    ``recipe`` is everything the VALUES depend on that the caller chose
    (seed, scale, distribution, a word for the formula); added here is
    what they depend on besides: the owner's class, each array's shape
    and dtype, and what makes a draw repeat in another process — the
    PRNG implementation and the flags that change its bits or the key
    made from a seed, the versions of jax and jaxlib, and the platform
    and device kind that computed the draw.  Equal recipes therefore
    mean equal values (the module's contract); a node built from given
    arrays that happen to hold the same bytes signs by content and no
    longer merges with this one — a missed merge, allowed.

    The cache entry rides with the instance through ``pickle`` and
    ``copy`` (both keep ``arrays`` the objects the attributes hold) and
    is dropped with the arrays by ``transformer.stripped_template``."""
    import jax
    import jaxlib

    device = next(iter(arrays[0].devices()))
    made_by = dict(
        recipe,
        owner=type(obj).__name__,
        arrays=tuple((tuple(a.shape), str(a.dtype)) for a in arrays),
        prng=str(jax.config.jax_default_prng_impl),
        threefry_partitionable=bool(jax.config.jax_threefry_partitionable),
        x64=bool(jax.config.jax_enable_x64),
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        platform=device.platform,
        device_kind=device.device_kind,
    )
    text = ";".join(f"{k}={made_by[k]!r}" for k in sorted(made_by))
    setattr(obj, attr, (tuple(arrays), _RECIPE_PREFIX + text))
