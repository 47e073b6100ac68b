"""Transformer — the framework's single extension point.

Reference: workflow/Transformer.scala § Transformer[A,B] — an abstract
unary op with ``apply(a: A): B`` plus ``apply(RDD[A]): RDD[B]`` (default
``rdd.map``), ``andThen`` composition, and ``Transformer.apply(fn)`` for
lambda nodes.

TPU translation: ``apply_one`` is the per-datum op; the batch path
``apply_batch`` defaults to ``vmap(apply_one)`` over a sharded device
array — XLA compiles and shards it, replacing closure-shipped executor
map tasks.  Most concrete ops override ``apply_batch`` directly with
natively-batched code (conv, einsum), which is both simpler and faster
than the reference's per-datum formulation.
"""

from __future__ import annotations

import contextlib
import re
import threading
import weakref
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.workflow.dataset import Dataset, as_dataset

#: per-OBJECT jitted apply_batch wrappers, for the nodes that promise no
#: identity (share_key() is None) or hold an array they do not declare
#: in traced_attrs: weak, so wrapper and arrays die with the node (see
#: _apply_batch_jitted)
_JIT_APPLY_CACHE = weakref.WeakKeyDictionary()

#: process-wide jitted applies, one per kind of node:
#: (share_key(node), input signature, traced_param_sig(node)) -> jitted
#: fn (or None = memoized untraceable for that exact signature).  Every
#: equal node built later -- in the same graph, the next fit, the next
#: build -- calls the same wrapper and hits its trace cache.  Values
#: hold parameter-stripped template copies, never fitted arrays.
#: Bounded FIFO like optimizer._FUSED_SHARED_CACHE: a process sweeping a
#: constructor argument mints a key per value, and an evicted live node
#: just mints again.
_SHARED_APPLY_CACHE: dict = {}
_SHARED_APPLY_MAX = 128
_SHARED_APPLY_LOCK = threading.Lock()


def _class_names(stages) -> str:
    return "_".join(type(s).__name__ for s in stages)


_NO_SPAN = contextlib.nullcontext()


def jit_named(fn, stages):
    """``jax.jit(fn)`` under a program name made of ``stages``' CLASS names:
    ``jit_apply_<Class>`` for one transformer, ``jit_fused_<A>_<B>…`` for a
    chain, so a device trace and ``module_s`` / ``module_runs`` split by
    node.  Never of a label's text: the module name is part of the
    persistent compile cache's key, and a parameter, seed, object id or
    count in it would cut one shared program into one per instance."""
    kind = "apply" if len(stages) == 1 else "fused"
    name = re.sub(r"\W", "_", f"{kind}_{_class_names(stages)}", flags=re.ASCII)[:96]
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def mint_span(minted: bool, stages, shared: bool):
    """A ``transformer.jit_mint`` span for the first call of a wrapper
    that was just minted (the call that traces, lowers and compiles or
    loads it); nothing for a cached wrapper's call."""
    if not minted:
        return _NO_SPAN
    from keystone_tpu.obs import ledger

    return ledger.span("transformer.jit_mint", node=_class_names(stages), shared=shared)


def stripped_template(t: "Transformer") -> "Transformer":
    """Shallow copy of ``t`` safe to pin in a process-lifetime shared
    cache: traced_attrs are nulled (they arrive as traced arguments),
    and derived caches that hold strong refs to fitted arrays — the
    cached_fingerprint attr (``_fp``) and per-instance jit dicts — are
    dropped, or the template would pin the first fit's arrays forever.
    The single source for both shared-apply sites (Transformer and
    FusedTransformer)."""
    import copy

    tpl = copy.copy(t)
    for name in type(t).traced_attrs:
        setattr(tpl, name, None)
    if tpl.fallback is not None:
        # the executor reads the substitute from the node, never from
        # the template; a fitted substitute must not be pinned either
        tpl.fallback = None
    for derived in ("_fp", "_jitted"):
        if derived in getattr(tpl, "__dict__", {}):
            try:
                delattr(tpl, derived)
            except AttributeError:
                pass
    return tpl


def rebound(template: "Transformer", params: dict) -> "Transformer":
    """A copy of ``template`` — a ``stripped_template``, or the node itself
    for a trace nothing keeps (``profiling.py``'s pricing) — with its traced
    attributes bound to ``params`` (tracers, inside the program's trace)."""
    import copy

    obj = copy.copy(template)
    for name, v in params.items():
        setattr(obj, name, v)
    return obj


def traced_param_sig(t: "Transformer") -> tuple:
    """Hashable structure signature of an instance's traced parameters
    (pytree treedef + leaf dtypes per attr).  Part of the shared-cache
    key, so an instance whose parameter VALUES cannot trace poisons only
    its own signature — never the whole class."""
    sig = []
    for name in type(t).traced_attrs:
        v = getattr(t, name)
        if v is None:
            sig.append((name, None))
        else:
            leaves, treedef = jax.tree_util.tree_flatten(v)
            sig.append(
                (
                    name,
                    str(treedef),
                    tuple(str(getattr(x, "dtype", type(x).__name__)) for x in leaves),
                )
            )
    return tuple(sig)


#: instance attributes that are caches or the executor's plumbing, never
#: part of what a node computes (analysis/signatures.py reads this too)
PLUMBING_ATTRS = frozenset({"_fp", "_jitted", "_breaker_token", "fallback", "optional"})

#: what an attribute has to be for its value to be compared as it stands
PLAIN_TYPES = (int, float, str, bool, bytes, type(None))


def _is_plain(v) -> bool:
    return isinstance(v, PLAIN_TYPES) or (
        isinstance(v, tuple) and all(_is_plain(e) for e in v)
    )


def share_key(t: "Transformer"):
    """Identity of one transformer for cross-instance program sharing:
    the one rule for "the same node", used by the single node's apply
    (``Transformer._apply_batch_jitted``) and by every stage of a fused
    chain (``optimizer.FusedTransformer``).

    Classes declaring traced_attrs share by (class, jit_static()) with
    their arrays passed as traced arguments.  The rest share by (class,
    params()): the CSE contract already promises params() fully
    identifies such a transformer.  A shared program makes a hole in
    that promise outlive the object, so the key also holds what the code
    can see of the instance beside the promise — its plain attributes
    (numbers, strings, tuples of them) by name: two nodes whose params()
    are equal and whose ``scale`` differs get a program each, where one
    would silently run its twin's.  None = not shareable (params() is
    None: the node never promised an identity), which keeps a single
    node per object and disables sharing for a whole chain.

    The degradation contract (``optional``, ``fallback``; what
    ``signature()`` adds for CSE) is NOT in the key: the executor acts
    on it around the node, apply_batch never reads it, so a degrading
    node runs the program of its plain twin (``stripped_template`` drops
    the substitute from the shared template)."""
    if type(t).traced_attrs:
        st = t.jit_static()
        return None if st is None else ("T", type(t), st)
    p = t.params()
    if p is None:
        return None
    seen = tuple(
        (name, v)
        for name, v in sorted(getattr(t, "__dict__", {}).items())
        if name not in PLUMBING_ATTRS and _is_plain(v)
    )
    return ("C", type(t), p, seen)


def _holds_array(t: "Transformer") -> bool:
    """Whether any instance attribute's pytree leaves include a device
    or host array.  Held without a traced_attrs declaration, it would be
    pinned by a process-lifetime template, and since PR 26 such a node's
    params() may be a content digest that mints a new key every refit."""
    return any(
        isinstance(leaf, (jax.Array, np.ndarray))
        for leaf in jax.tree_util.tree_leaves(getattr(t, "__dict__", {}))
    )


def program_share_key(t: "Transformer"):
    """``share_key(t)`` for what is kept per PROGRAM of a node — its jitted
    apply, its static price — or None where the code can see the promise
    does not cover the program: a node that holds an array it did not
    declare in traced_attrs.  The array check comes first: such a node's
    params() may have to digest the arrays it holds."""
    if not type(t).traced_attrs and _holds_array(t):
        return None
    return share_key(t)


#: The chunk rule (``_chunk_rows_for``): how many rows of its input a
#: device apply takes at a time.  It reads the input's shape and bytes
#: and nothing else, and it is asked once per apply.
#:
#: 1. An input within ``_APPLY_CHUNK_BYTES`` — what one chunk's input may
#:    be anyway — is ONE chunk: one program over the whole array, no
#:    slice, no pad, no concatenate.  (8192 frames of 440 floats are
#:    14 MB; cut into four 2048-row applies by each of sixteen cosine
#:    nodes they were 144 launches a fit more, with the chip idle while
#:    the host made them: my chip runs, PR 35.  A label vector is one
#:    apply.)
#: 2. A larger input is cut into chunks of ``_APPLY_CHUNK_DEFAULT`` rows,
#:    the ragged tail padded up, so that the compiled programs' shapes
#:    stop scaling with the dataset's size and a heavy item (an image's
#:    SIFT descriptors are 0.4 MB) bounds the program's memory.  On by
#:    default since r5, decided by program COUNT (rounds 1–5, not
#:    re-measured): at a new n = 8192 the chunked fit ran 88 of 88
#:    programs from the persistent compile cache where the unchunked one
#:    paid 9 cold full-shape compiles.  Bit-parity with whole-batch
#:    applies is pinned by tests/test_workflow.py.
#: 3. A LONG dataset of NARROW rows takes larger chunks: the chunk
#:    doubles while more than ``_APPLY_MAX_CHUNKS`` chunks remain and the
#:    chunk's input stays within ``_APPLY_CHUNK_BYTES`` (so the shapes
#:    compiled grow with log2 n, and items already heavy at the canonical
#:    chunk — images, descriptor sets, Fisher vectors — never grow).
#:    196,608 rows of 440 floats are 12 chunks of 16,384 where 96 of
#:    2048, for each of two nodes, were 0.20 s of host time a fit with
#:    the chip idle (my chip runs, PR 25).
#: 4. No chunk where its outputs could not exist.  A chunked apply holds
#:    its chunks' outputs beside their concatenation, twice the output:
#:    an input over ``_APPLY_WHOLE_BYTES``, a quarter of a 16 GB device,
#:    is applied whole (80,000 features of 16,384 rows are 5.2 GB; their
#:    scaled copy in eight chunks and again in one piece would be 10.5 GB
#:    beside them), and so is the input of a node that tiles its rows
#:    itself (``Transformer.owns_tiling``).  The largest input a benchmark
#:    cell chunks is 1.6 GB (4096 images' SIFT descriptors).
#: 5. No chunk on a data mesh of more than one device (a row slice of a
#:    sharded array pays resharding collectives per chunk, and per-shard
#:    shapes are already smaller; ``_apply_chunk_rows``), and a forced
#:    ``KEYSTONE_APPLY_CHUNK`` is taken as it is, whatever the input.
_APPLY_CHUNK_DEFAULT = 2048
_APPLY_MAX_CHUNKS = 16
_APPLY_CHUNK_BYTES = 32 << 20
_APPLY_WHOLE_BYTES = 4 << 30


def _apply_chunk_rows() -> int:
    """Row-chunk size for device applies; 0 disables.

    ``KEYSTONE_APPLY_CHUNK`` is a FORCE flag: it bypasses the
    multi-device guard below (the mesh-sharded tests opt in through it
    deliberately — a row slice of a sharded array pays per-chunk
    resharding collectives, which is a performance hazard, not a
    correctness one).  The default-path guard disables chunking
    whenever the data mesh spans >1 device, where per-shard shapes are
    already smaller.  A backend that cannot be read raises."""
    import os

    env = os.environ.get("KEYSTONE_APPLY_CHUNK", "").strip()
    if env:
        try:
            return max(0, int(env))
        except ValueError:
            import logging

            logging.getLogger(__name__).warning(
                "KEYSTONE_APPLY_CHUNK=%r is not an integer; chunking "
                "stays DISABLED",
                env,
            )
            return 0
    if not _APPLY_CHUNK_DEFAULT:
        return 0
    from keystone_tpu.parallel.mesh import active_mesh

    # the mesh the data lives on decides; with none set, Datasets shard
    # over the all-device default mesh.  (A one-device mesh on a
    # four-chip host keeps chunking: the device COUNT says nothing
    # about where this data is.)
    m = active_mesh()
    spans = m.devices.size if m is not None else len(jax.devices())
    return 0 if spans > 1 else _APPLY_CHUNK_DEFAULT


def _chunk_rows_for(arr) -> int:
    """Row-chunk size for a device apply of ``arr``; 0 = one program over
    the whole array.  The rule is stated above ``_APPLY_CHUNK_DEFAULT``:
    whole where the input is within one chunk's bytes or over what a
    chunked apply can hold, else the canonical chunk, grown for long
    datasets of narrow rows; a forced ``KEYSTONE_APPLY_CHUNK`` is taken
    as it is."""
    import os

    chunk = _apply_chunk_rows()
    if not chunk or os.environ.get("KEYSTONE_APPLY_CHUNK", "").strip():
        return chunk
    if arr.nbytes <= _APPLY_CHUNK_BYTES or arr.nbytes > _APPLY_WHOLE_BYTES:
        return 0
    n = arr.shape[0]
    row_bytes = arr.nbytes // max(1, n)
    while n > _APPLY_MAX_CHUNKS * chunk and 2 * chunk * row_bytes <= _APPLY_CHUNK_BYTES:
        chunk *= 2
    return chunk


def iter_row_chunks(arr, mask, chunk: int):
    """Yield ``(rows, mask_rows, start)`` in fixed-size row chunks, the
    ragged tail PADDED UP to ``chunk`` (mask pad rows are zero — callers
    slice outputs back to the true row count).  The single source of the
    chunk/pad discipline shared by Transformer._apply_dataset_chunked
    and ColumnSampler's offset-keyed chunked sampling — their bit-parity
    guarantees both ride this one implementation."""
    for i in range(0, arr.shape[0], chunk):
        a = arr[i : i + chunk]
        m = mask[i : i + chunk] if mask is not None else None
        short = chunk - a.shape[0]
        if short > 0:
            a = jnp.pad(a, ((0, short),) + ((0, 0),) * (a.ndim - 1))
            if m is not None:
                m = jnp.pad(m, ((0, short),) + ((0, 0),) * (m.ndim - 1))
        yield a, m, i


class Chainable:
    """Mixin providing ``and_then`` / ``__or__`` composition sugar."""

    def and_then(self, nxt, data=None, labels=None):
        from keystone_tpu.workflow.pipeline import Pipeline

        return Pipeline.of(self).and_then(nxt, data=data, labels=labels)

    def __or__(self, nxt):
        return self.and_then(nxt)


class Transformer(Chainable):
    #: True for ops that run on host Python objects (e.g. tokenizers).
    is_host: bool = False
    #: host ops whose per-item work is trivial (a str method) opt OUT of
    #: the host_map worker pool — IPC would dwarf the work
    parallel_host: bool = True
    #: Names of array-valued (or None) instance attributes passed as
    #: TRACED arguments to a class-shared jitted apply_batch, so every
    #: instance of the class shares ONE compiled program per input
    #: signature.  Two measured wins (rounds 1–5, not re-measured): N
    #: instances stop tracing/compiling N duplicate programs, and fitted
    #: device arrays stop being closure constants — jax lowering reads
    #: every closed-over device array back to host and embeds it in the
    #: program, and embedding VALUES keys the persistent compile cache
    #: by the fit's bits, so every refit recompiled from scratch.
    #: Declaring classes must route every OTHER attribute that shapes
    #: the trace through jit_static().  Empty = the node holds no fitted
    #: array: it shares one program per (class, params()) if params() is
    #: not None, and keeps a program per object otherwise or where it
    #: holds an array it did not declare here (see share_key).
    traced_attrs: tuple = ()
    #: True for transformers whose apply_batch manages its OWN jit and
    #: program cache (FusedTransformer).  The generic per-instance jit
    #: wrapper must NOT wrap these: an outer per-instance jit would
    #: inline the inner program and embed its traced stage parameters
    #: as outer-program constants, nullifying cross-instance sharing.
    self_jitted: bool = False
    #: True for a transformer whose program loops over tiles of its rows
    #: itself and sizes them from the shapes it sees (PooledConvolver:
    #: its input row is 3 KB and the activation it must never write
    #: 29 MB).  ``apply_dataset`` offers it no chunk: it is applied whole.
    owns_tiling: bool = False
    #: Graceful degradation (workflow/executor.py): an ``optional``
    #: stage whose retry/deadline budget is exhausted — or whose circuit
    #: breaker is open — is replaced by :class:`Identity` (its input
    #: passes through untouched) instead of failing the run.  A
    #: ``fallback`` transformer (set via :meth:`with_fallback`) is the
    #: substitute applied instead.  Default: neither — failure
    #: propagates, exactly as before.
    optional: bool = False
    fallback: Optional["Transformer"] = None

    def with_fallback(self, substitute: "Transformer") -> "Transformer":
        """A copy of this transformer that degrades to ``substitute``:
        when this stage's failure budget (retries, deadline) is spent or
        its breaker is open, the executor applies ``substitute`` to the
        stage's input and emits a ``degraded`` ledger event instead of
        failing the run.  The substitute must accept the same input
        (e.g. a cheaper featurizer, or a constant-output scorer)."""
        import copy

        c = copy.copy(self)
        c.fallback = substitute
        return c

    @property
    def label(self) -> str:
        return type(self).__name__

    # ---------------------------------------------------------- identity
    def params(self):
        """Hashable parameter tuple for CSE equality; None => never merged."""
        return None

    def signature(self):
        p = self.params()
        if p is None:
            return None
        sig = (type(self).__name__, p)
        if self.optional or self.fallback is not None:
            # degradation declarations are part of node identity: CSE
            # merging an optional/fallback node with a plain twin would
            # silently widen (or drop) the degradation contract
            fb = self.fallback
            fb_sig = None if fb is None else (fb.signature() or id(fb))
            sig = sig + ("degrade", self.optional, fb_sig)
        return sig

    def jit_static(self):
        """Hashable key covering every non-traced attribute that affects
        apply_batch's trace structure; part of the shared-program cache
        key for classes declaring traced_attrs."""
        return ()

    # Optimizer hook: physical-operator choice (workflow/NodeOptimizationRule).
    def choose_physical(self, sample) -> "Transformer":
        """Return the best physical implementation of this logical
        transformer given a data sample (shapes).  Default: self."""
        return self

    # ------------------------------------------------------------- apply
    def apply_one(self, x):
        raise NotImplementedError(type(self).__name__)

    def apply_batch(self, xs, mask=None):
        """Batched apply; default is vmap of apply_one."""
        return jax.vmap(self.apply_one)(xs)

    def apply_dataset(self, ds: Dataset) -> Dataset:
        from keystone_tpu.workflow.dataset import StreamDataset

        if isinstance(ds, StreamDataset):
            if ds.is_host:
                if not self.is_host:
                    raise TypeError(
                        f"{self.label} is a device transformer; this stream "
                        "carries host objects. Featurize to arrays first."
                    )
                # host transformer over a host stream: map items lazily,
                # batch by batch — the raw corpus never materializes.
                # host_map fans large batches over worker processes on
                # multi-core hosts (raise stream_batch_size to engage
                # it); small batches, single-core hosts, and trivial ops
                # (parallel_host=False) map sequentially
                if self.parallel_host:
                    from keystone_tpu.utils.hostmap import host_map

                    out = ds.map_batches(
                        lambda batch, _mask: host_map(self.apply_one, batch)
                    )
                else:
                    out = ds.map_batches(
                        lambda batch, _mask: [self.apply_one(x) for x in batch]
                    )
                # provenance for the native text fast path: the base raw
                # stream plus the host transformers applied since —
                # consumers (ops/nlp_native) can re-run the whole chain
                # in C++ from the raw docs instead of the per-item maps
                base, stages = getattr(ds, "_host_chain", None) or (ds, ())
                out._host_chain = (base, stages + (self,))
                return out
            if self.is_host:
                raise TypeError(
                    f"{self.label} is a host transformer; streams carry device "
                    "batches. Featurize to arrays before streaming."
                )
            return ds.map_batches(self._apply_batch_jitted)
        if ds.is_host or self.is_host:
            if self.is_host and self.parallel_host:
                # pure-Python host op: worker-pool for large inputs
                # (device transformers stay sequential — worker
                # processes must never run device code)
                from keystone_tpu.utils.hostmap import host_map

                out = host_map(self.apply_one, ds.items)
            else:
                out = [self.apply_one(x) for x in ds.items]
            if out and isinstance(out[0], (jnp.ndarray,)) or _stackable(out):
                try:
                    return ds.with_array(jnp.stack([jnp.asarray(o) for o in out]))
                except (TypeError, ValueError):
                    pass
            res = ds.with_items(out)
            # provenance for the native text fast path, mirroring the
            # host-STREAM branch above: in-memory host datasets chain
            # through with_items, so downstream featurizers can re-run
            # the whole chain in C++ from the base items
            base, stages = getattr(ds, "_host_chain", None) or (ds, ())
            res._host_chain = (base, stages + (self,))
            return res
        from keystone_tpu.obs import ledger

        chunk = 0 if self.owns_tiling else _chunk_rows_for(ds.array)
        n = ds.array.shape[0]
        chunked = bool(chunk) and n > chunk
        # the applies this node's stage is made of: 1 = whole
        ledger.annotate("executor.stage", chunks=-(-n // chunk) if chunked else 1)
        if chunked:
            return self._apply_dataset_chunked(ds, chunk)
        result = self._apply_batch_jitted(ds.array, ds.mask)
        if isinstance(result, tuple):  # (values, mask) for ragged producers
            return ds.with_array(result[0], mask=result[1])
        return ds.with_array(result)

    def _apply_dataset_chunked(self, ds: Dataset, chunk: int) -> Dataset:
        """Apply in fixed-size row chunks (the ragged tail padded UP to
        the canonical chunk, then sliced off) so the number of distinct
        compiled programs stops scaling with dataset size: an n=8192 fit
        re-traced and cache-loaded every stage at 8192-row shapes — the
        measured ~60 s of a 79 s fit — where the 2048-row programs were
        already warm from smaller runs.  Semantically free: transformer
        apply IS a per-item map (apply_one is the contract), so chunk
        boundaries cannot change any row.  Disabled on multi-device data
        meshes (``_apply_chunk_rows`` → 0): a row slice of a sharded
        array would trigger resharding collectives per chunk."""
        arr, mask = ds.array, ds.mask
        n0 = arr.shape[0]
        vals, masks = [], []
        for a, m, _start in iter_row_chunks(arr, mask, chunk):
            r = self._apply_batch_jitted(a, m)
            if isinstance(r, tuple):
                vals.append(r[0])
                masks.append(r[1])
            else:
                vals.append(r)
        out = jnp.concatenate(vals, axis=0)[:n0]
        if masks:
            return ds.with_array(
                out, mask=jnp.concatenate(masks, axis=0)[:n0]
            )
        return ds.with_array(out)

    def _apply_batch_jitted(self, xs, mask):
        """Run apply_batch as ONE compiled program.

        Un-fused nodes (raw-graph execution: saved-state walks, single-node
        applies) would otherwise dispatch op-by-op eagerly — slower: one
        compiled program and one dispatch per primitive.  (The older fear
        that an eager FFT disturbs what runs after it does not hold on
        today's runtime: chip_smoke.py's eager_fft probe finds the matmul
        after an eager FFT bit-equal to the one before it — my chip run,
        PR 21.)  Untraceable apply_batch implementations (host-side numpy,
        data-dependent Python) fall back to the eager path.

        A wrapper is minted once per (share_key, input signature) a
        PROCESS, not once per node object: a node whose share_key() is
        not None takes it from _SHARED_APPLY_CACHE, so a second
        ``SIFTExtractor(step=4, bin_sizes=(4,))`` — in the same graph,
        the next fit, the next build — traces, lowers and loads nothing.
        Per object (the weak _JIT_APPLY_CACHE) stay the nodes the code
        can see are not safe to pin: share_key() None (LambdaTransformer,
        Cacher, any node that never promised an identity), and a node
        that holds an array without declaring it in traced_attrs.

        The signature key holds the matmul mode — the RESOLVED policy,
        one of f32/bf16/bf16_apply, so e.g. enabling the bf16 apply path
        (utils/precision.py § bf16_apply) retraces every chunked/
        whole-batch apply instead of reusing a stale executable — and
        confines a trace failure to the one input signature that caused
        it: one odd mask/dtype combination must not pin every later call
        to the eager path."""
        from keystone_tpu.utils import precision

        if type(self).self_jitted:
            return self.apply_batch(xs, mask=mask)
        # Keyed by (mode, dtype, rank, mask-presence) — NOT concrete shapes:
        # jit itself retraces per shape under one wrapper, and traceability
        # failures are dtype/mask/structure-driven, so a shape-keyed memo
        # would re-pay a doomed trace (and re-warn) for every ragged batch.
        sig = (
            precision.matmul_mode(),
            str(getattr(xs, "dtype", "")),
            getattr(xs, "ndim", None),
            None if mask is None else str(getattr(mask, "dtype", "")),
        )
        skey = program_share_key(self)
        if skey is not None:
            return self._apply_batch_shared(xs, mask, skey, sig)
        entry = _JIT_APPLY_CACHE.get(self)
        if entry is None:
            entry = {}
            _JIT_APPLY_CACHE[self] = entry
        sentinel = object()
        fn = entry.get(sig, sentinel)
        if fn is None:  # memoized "untraceable" FOR THIS SIGNATURE
            return self.apply_batch(xs, mask=mask)
        minted = fn is sentinel
        if minted:
            # weak cache, NOT an instance attribute: jitted callables are
            # unpicklable and must not ride along in FittedPipeline.save.
            # The closure holds weakref.ref(self) — closing over self
            # would make the cache VALUE pin its own KEY alive forever.
            self_ref = weakref.ref(self)
            fn = entry[sig] = jit_named(
                lambda a, m: self_ref().apply_batch(a, mask=m), [self]
            )
        try:
            with mint_span(minted, [self], shared=False):
                return fn(xs, mask)
        except (TypeError, jax.errors.JAXTypeError):
            entry[sig] = None  # don't re-pay a failed trace for this sig
            return self._apply_batch_untraceable(xs, mask, sig)

    def _apply_batch_untraceable(self, xs, mask, sig):
        import logging

        logging.getLogger(__name__).warning(
            "%s.apply_batch is untraceable for signature %s; using the "
            "eager path (one dispatch per primitive)",
            self.label,
            sig,
        )
        return self.apply_batch(xs, mask=mask)

    def _apply_batch_shared(self, xs, mask, skey, sig):
        """Process-wide shared jitted apply (see _SHARED_APPLY_CACHE).

        The jitted callable closes over a parameter-STRIPPED template
        copy of the first instance seen per key — never over an
        instance, which would die or be pinned — and rebinds the traced
        attributes to tracer values at trace time, so the compiled
        program is a pure function of parameter shapes, shared by every
        equal instance and every refit.  A node without traced_attrs
        passes an empty dict, which adds nothing to the lowered module:
        its text is that of a plain ``(xs, mask)`` program."""
        cls = type(self)
        params = {}
        for name in cls.traced_attrs:
            v = getattr(self, name)
            if v is not None and any(
                isinstance(leaf, np.ndarray)
                for leaf in jax.tree_util.tree_leaves(v)
            ):
                # host-resident parameters (e.g. an unpickled model, or
                # a pytree like FisherVector.gmm holding numpy arrays)
                # would re-transfer on EVERY call as jit arguments;
                # commit them to device once, on the instance
                v = jax.tree_util.tree_map(
                    lambda a: jnp.asarray(a) if isinstance(a, np.ndarray) else a,
                    v,
                )
                setattr(self, name, v)
            params[name] = v
        key = (skey, sig, traced_param_sig(self))
        sentinel = object()
        fn = _SHARED_APPLY_CACHE.get(key, sentinel)
        if fn is None:  # memoized "untraceable" for this exact signature
            return self.apply_batch(xs, mask=mask)
        minted = fn is sentinel
        if minted:
            template = stripped_template(self)

            def run(p, a, m):
                return rebound(template, p).apply_batch(a, mask=m)

            fn = jit_named(run, [self])
            with _SHARED_APPLY_LOCK:
                while len(_SHARED_APPLY_CACHE) >= _SHARED_APPLY_MAX:
                    _SHARED_APPLY_CACHE.pop(next(iter(_SHARED_APPLY_CACHE)))
                _SHARED_APPLY_CACHE[key] = fn
        try:
            with mint_span(minted, [self], shared=True):
                return fn(params, xs, mask)
        except (TypeError, jax.errors.JAXTypeError):
            _SHARED_APPLY_CACHE[key] = None
            return self._apply_batch_untraceable(xs, mask, sig)

    def __call__(self, x):
        from keystone_tpu.workflow.pipeline import Pipeline, PipelineDataset

        if isinstance(x, (Pipeline, PipelineDataset)):
            return Pipeline.of(self)(x)
        if isinstance(x, Dataset):
            return self.apply_dataset(x)
        return self.apply_one(x)

    def __repr__(self):
        return self.label


class LambdaTransformer(Transformer):
    """``Transformer.apply(fn)`` analogue: wrap a function as a node."""

    def __init__(
        self,
        fn: Callable,
        batch_fn: Optional[Callable] = None,
        name: str = "Lambda",
        host: bool = False,
    ):
        self._fn = fn
        self._batch_fn = batch_fn
        self._name = name
        self.is_host = host

    @property
    def label(self):
        return self._name

    def apply_one(self, x):
        return self._fn(x)

    def apply_batch(self, xs, mask=None):
        if self._batch_fn is not None:
            return self._batch_fn(xs)
        return jax.vmap(self._fn)(xs)


def transformer(fn=None, *, batch=None, name=None, host=False):
    """Decorator/factory for lambda nodes: ``transformer(lambda x: x * 2)``."""

    def make(f):
        return LambdaTransformer(
            f, batch_fn=batch, name=name or getattr(f, "__name__", "Lambda"), host=host
        )

    if fn is not None:
        return make(fn)
    return make


class Identity(Transformer):
    def params(self):
        return ()

    def apply_one(self, x):
        return x

    def apply_batch(self, xs, mask=None):
        return xs


class Cacher(Transformer):
    """Identity that forces materialization — the unit of the caching
    optimizer (nodes/util/Cacher.scala).  On TPU this means "block until
    the stage's arrays are resident in HBM" so downstream stages (and the
    profiler) see a stage boundary rather than one fused program."""

    def params(self):
        return None  # each Cacher is its own node; never CSE-merged away

    def apply_one(self, x):
        return x

    def apply_dataset(self, ds: Dataset) -> Dataset:
        return ds.cache()


def _stackable(out) -> bool:
    import numpy as np

    return (
        len(out) > 0
        and all(hasattr(o, "shape") for o in out)
        and len({np.shape(o) for o in out}) == 1
    )
