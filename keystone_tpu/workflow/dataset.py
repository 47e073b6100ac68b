"""The core data abstraction: a Dataset is a sharded batched array.

The reference's unit of distributed data is ``RDD[T]`` — a partitioned
collection of single datums, batched into per-partition matrices only
inside solvers (utils/MatrixUtils.scala § rowsToMatrix).  On TPU the
efficient form is the opposite: data lives batched from the start as a
device array with its leading axis sharded over the mesh 'data' axis;
"partitions" are the per-device shards XLA sees.

Three payload kinds flow through pipelines:
  - device arrays: (n, ...) jnp arrays, the normal case;
  - ragged arrays: (n, max_k, d) with a boolean (n, max_k) mask — e.g.
    per-image SIFT descriptor sets (pad-and-mask, SURVEY.md §7 hard part d);
  - host lists: arbitrary Python objects (e.g. raw text for NLP nodes),
    which stay on host until a featurizer produces arrays.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.obs import ledger
from keystone_tpu.parallel import mesh as _mesh


def _to_device(arr, shard: bool = True):
    """``arr`` on the mesh (or as one device array).  A HOST array's put is
    a ``dataset.upload`` span: the host's time in it (staging copy and
    enqueue — the transfer itself is asynchronous), and a
    ``dataset.transfer`` record under it that the ledger's watcher closes
    when the array is ready: the transfer's own duration, from the put's
    start, at no wait of this thread's."""
    put = _mesh.shard_batch if shard else jnp.asarray
    if isinstance(arr, jax.Array):
        return put(arr)
    arr = np.asarray(arr)
    with ledger.span("dataset.upload", bytes=arr.nbytes) as upload:
        out = put(arr)
        ledger.watch(
            "dataset.transfer", out, upload, bytes=out.nbytes, dtype=str(out.dtype),
            shape=list(out.shape),
        )
        return out


def _to_host(arr) -> np.ndarray:
    """Host copy of a device array, inside a ``dataset.readback`` span (the
    wait for the array is part of it)."""
    with ledger.span("dataset.readback", bytes=arr.nbytes):
        return np.asarray(arr)


class Dataset:
    """A (possibly padded) batch with true length ``n``."""

    def __init__(
        self,
        data: Any,
        n: Optional[int] = None,
        mask: Optional[jnp.ndarray] = None,
        shard: bool = True,
        name: Optional[str] = None,
    ):
        #: optional stable identity — lets prefix signatures (CSE, saved
        #: state) match across processes; unnamed datasets use object id
        self.name = name
        if isinstance(data, (list, tuple)) and not _all_arrays(data):
            # Host payload (strings, PyTrees, variable-shape objects).
            self._host: Optional[list] = list(data)
            self._array = None
            self.n = len(self._host) if n is None else n
            self.mask = None
        else:
            arr = data
            if isinstance(arr, (list, tuple)):
                arr = np.stack([np.asarray(a) for a in arr], axis=0)
            true_n = arr.shape[0] if n is None else n
            self._host = None
            self._array = _to_device(arr, shard)
            self.n = true_n
            self.mask = mask

    # ------------------------------------------------------------ access
    @property
    def is_host(self) -> bool:
        return self._host is not None

    @property
    def array(self) -> jnp.ndarray:
        """Padded, device-resident array. Rows >= n are padding."""
        if self._array is None:
            raise TypeError("host-payload Dataset has no array; featurize it first")
        return self._array

    @property
    def items(self) -> list:
        if self._host is not None:
            return self._host
        return [np.asarray(self._array[i]) for i in range(self.n)]

    def numpy(self) -> np.ndarray:
        """Unpadded host copy."""
        return _to_host(self.array)[: self.n]

    @property
    def item_shape(self) -> tuple:
        """Per-item shape — StreamDataset overrides via peek_shape so
        pipelines can derive feature dims without materializing."""
        return tuple(self.array.shape[1:])

    def __len__(self) -> int:
        return self.n

    # --------------------------------------------------------- derivation
    def with_array(self, arr, mask=None) -> "Dataset":
        """New Dataset sharing this one's true length (padding preserved)."""
        d = Dataset.__new__(Dataset)
        d._host = None
        d._array = arr
        d.n = self.n
        d.mask = mask if mask is not None else None
        d.name = None
        return d

    def with_items(self, items: Sequence) -> "Dataset":
        d = Dataset.__new__(Dataset)
        d._host = list(items)
        d._array = None
        d.n = self.n
        d.mask = None
        d.name = None
        return d

    def cache(self) -> "Dataset":
        """Force materialization (the Cacher analogue, nodes/util/Cacher.scala).

        JAX arrays are already materialized once computed; this blocks on
        completion so downstream timing/profiling sees real costs.
        """
        # under a trace (the frozen apply lowered as one program) there
        # is nothing to wait for: a Cacher is an identity there
        if self._array is not None and not isinstance(self._array, jax.core.Tracer):
            ledger.device_wait(self._array)
        return self

    def __repr__(self):
        if self.is_host:
            return f"Dataset(host, n={self.n})"
        return f"Dataset(shape={tuple(self.array.shape)}, n={self.n})"


class StreamDataset(Dataset):
    """A lazily-evaluated, re-iterable stream of host batches — the
    out-of-core path through the Pipeline DAG.

    The reference streams data through RDD partition iterators so no
    executor ever holds the full dataset (SURVEY.md §2.9); this is the
    TPU analogue: transformers map over the stream batch-by-batch
    (upload → one compiled apply → stay on device for the next map), and
    the block solvers spill the resulting features to a
    :class:`~keystone_tpu.workflow.blockstore.FeatureBlockStore` and fit
    out-of-core, so the feature matrix never needs to fit in HBM.

    ``source``: a callable returning an iterator of host batches (or a
    re-iterable).  Each batch is a ``(m_i, ...)`` array or an
    ``(array, mask)`` pair for ragged payloads.  ``n`` — total rows.
    ``prefetch`` > 0 moves the source's host work (decode, transforms)
    onto a background thread that stays ``prefetch`` batches ahead of
    the consumer (loaders pass their decode cost through this).

    ``host=True`` marks a stream of HOST-object batches (lists of
    texts, term dicts, CSR rows — the text pipelines' payloads before
    featurization): host transformers map over it item-by-item per
    batch, and nothing touches a device until a featurizer produces
    arrays or CSR.  This is how a raw corpus larger than host RAM
    streams through tokenize→n-gram→vocab→CSR (the CSR output is
    orders of magnitude smaller and is collected normally).

    Estimators without a streaming fit path fall back to
    :attr:`array` / :attr:`items`, which materialize the whole stream
    (with a warning) — correctness is preserved everywhere, the
    out-of-core guarantee only where implemented.
    """

    def __init__(
        self,
        source,
        n: int,
        name: Optional[str] = None,
        prefetch: int = 0,
        host: bool = False,
        retries: int = 0,
        max_bad_batches: int = 0,
        timeout: Optional[float] = None,
    ):
        self.name = name
        self.n = int(n)
        self._host = None
        self._array = None
        self._host_stream = bool(host)
        self.mask = None
        if not callable(source) and iter(source) is source:
            # A one-shot iterator would be shared (and interleaved!) by
            # fan-out consumers — e.g. the two branches of a Gather.
            raise ValueError(
                "StreamDataset source must be re-iterable: pass a callable "
                "returning a fresh iterator (or a list of batches), not a "
                "one-shot generator/iterator"
            )
        if retries > 0 or max_bad_batches > 0 or timeout is not None:
            # flaky-source hardening (loaders/stream.resilient): bounded
            # per-batch retry with backoff, then a drop quota — wrapped
            # UNDER prefetched so retries run on the producer thread.
            # ``timeout`` adds a per-fetch watchdog: a silently-hung
            # source raises (DeadlineExceeded, an OSError) into the
            # same retry/quota machinery instead of stalling the fit
            from keystone_tpu.loaders.stream import resilient

            source = resilient(
                source,
                retries=retries,
                max_bad_batches=max_bad_batches,
                timeout=timeout,
            )
        if prefetch > 0:
            from keystone_tpu.loaders.stream import prefetched

            source = prefetched(source, prefetch=prefetch)

        if host:

            def gen():
                src = source() if callable(source) else iter(source)
                for batch in src:
                    yield list(batch), None

        else:

            def gen():
                src = source() if callable(source) else iter(source)
                for batch in src:
                    arr, mask = (
                        batch if isinstance(batch, tuple) else (batch, None)
                    )
                    yield jnp.asarray(arr), (
                        None if mask is None else jnp.asarray(mask)
                    )

        self._gen = gen

    @property
    def is_host(self) -> bool:
        return self._host_stream

    @classmethod
    def _wrap(
        cls, gen, n: int, name: Optional[str] = None, host: bool = False
    ) -> "StreamDataset":
        d = cls.__new__(cls)
        d.name = name
        d.n = int(n)
        d._host = None
        d._array = None
        d._host_stream = bool(host)
        d.mask = None
        d._gen = gen
        return d

    # --------------------------------------------------------- streaming
    def device_batches(self):
        """Iterate ``(array, mask_or_None)`` device batches."""
        return self._gen()

    def peek_shape(self) -> tuple:
        """Per-item shape ``(...)`` from the first batch (cached) —
        lets callers derive feature dims without materializing the
        stream (costs one batch's host work on first call)."""
        if not hasattr(self, "_peek_shape"):
            for arr, _ in self._gen():
                self._peek_shape = tuple(np.shape(arr)[1:])
                break
            else:
                raise ValueError("empty stream")
        return self._peek_shape

    @property
    def item_shape(self) -> tuple:
        return self.peek_shape()

    def batches(self):
        """Iterate host batches of the mapped values (numpy for device
        streams, lists for host streams)."""
        for arr, _ in self._gen():
            yield arr if self._host_stream else _to_host(arr)

    def map_batches(self, fn, host: Optional[bool] = None) -> "StreamDataset":
        """Lazily compose a per-batch function ``fn(batch, mask)``
        (returning an array/list or an (array, mask) pair) over the
        stream.  ``host`` sets the CHILD stream's payload kind; default:
        same as this stream."""
        parent = self._gen

        def gen():
            for arr, mask in parent():
                out = fn(arr, mask)
                if isinstance(out, tuple):
                    yield out
                else:
                    yield out, None

        return StreamDataset._wrap(
            gen,
            self.n,
            host=self._host_stream if host is None else host,
        )

    @staticmethod
    def zip_concat(streams: Sequence["StreamDataset"]) -> "StreamDataset":
        """Gather analogue for streams: zip batches, concat on the last
        axis.  All streams must share batch structure (in pipelines they
        are branches mapped over ONE source, so they do by construction)."""
        ns = {s.n for s in streams}
        if len(ns) != 1:
            raise ValueError(f"gathered streams disagree on n: {sorted(ns)}")
        gens = [s._gen for s in streams]

        def gen():
            for parts in zip(*(g() for g in gens), strict=True):
                arrs = [a for a, _ in parts]
                yield jnp.concatenate(arrs, axis=-1), None

        return StreamDataset._wrap(gen, streams[0].n)

    # -------------------------------------------------- Dataset protocol
    @property
    def array(self) -> jnp.ndarray:
        """Materialize the stream into one sharded device array (escape
        hatch for consumers without a streaming path; defeats out-of-core)."""
        if self._host_stream:
            raise TypeError(
                "host-payload StreamDataset has no array; featurize it first"
            )
        if self._array is None:
            import logging

            logging.getLogger(__name__).warning(
                "materializing StreamDataset (n=%d) into device memory; "
                "this consumer has no out-of-core path",
                self.n,
            )
            parts = []
            masks = []
            for arr, mask in self._gen():
                parts.append(_to_host(arr))
                if mask is not None:
                    masks.append(_to_host(mask))
            self._array = _to_device(np.concatenate(parts, axis=0))
            if masks:
                self.mask = _to_device(np.concatenate(masks, axis=0))
        return self._array

    @property
    def items(self) -> list:
        if self._host_stream:
            # collecting a host stream is often BY DESIGN small (CSR
            # rows after featurization); log at debug, not warning
            if self._host is None:
                import logging

                logging.getLogger(__name__).debug(
                    "collecting host StreamDataset (n=%d) items", self.n
                )
                out: list = []
                for batch, _ in self._gen():
                    out.extend(batch)
                self._host = out
            return self._host
        self.array
        return [np.asarray(self._array[i]) for i in range(self.n)]

    def cache(self) -> "StreamDataset":
        # A Cacher inserted by the optimizer must NOT collapse the stream
        # into memory — out-of-core is the point.  No-op.
        return self

    def __repr__(self):
        kind = "host, " if self._host_stream else ""
        return f"StreamDataset({kind}n={self.n})"


def _all_arrays(seq) -> bool:
    return len(seq) > 0 and all(
        isinstance(x, (np.ndarray, jnp.ndarray)) and hasattr(x, "shape") for x in seq
    ) and len({np.shape(x) for x in seq}) == 1


def as_dataset(x, shard: bool = True) -> Dataset:
    if isinstance(x, Dataset):
        return x
    return Dataset(x, shard=shard)
