"""Disk-backed feature-block store for out-of-core block solvers.

The reference fits d≈200k-dim Fisher-vector models by caching feature
blocks as RDDs (spilled to executor disk/memory) and re-reading them per
(epoch, block) during block coordinate descent
(nodes/learning/BlockLeastSquares.scala per SURVEY.md §3.2).  On TPU the
analogue is this store: features are written once, blockified on disk as
one ``.npy`` memmap per feature block, and re-streamed per sweep so HBM
only ever holds ONE (n × block_size) block plus the (n × k) residual —
the feature matrix itself can exceed device memory by an arbitrary
factor.

Layout of a store directory::

    meta.json                {"n": ..., "d": ..., "block_size": ..., "nb": ...}
    block_0000.npy           float32 (n, block_size)
    block_0001.npy           ...

The final block is zero-padded on columns to ``block_size`` (the
VectorSplitter convention, nodes/util/VectorSplitter.scala), which keeps
every device transfer and every compiled block-step identical in shape —
one XLA program serves all (epoch, block) steps.

``dtype="bfloat16"`` halves both the disk footprint and the
disk→host→device bytes per sweep — on this chip bf16 is a bandwidth
lever, not a compute lever (utils/precision.py), and the out-of-core
sweep is bandwidth-bound, so this is exactly where it pays.  Blocks are
stored as uint16 bit patterns (npy's parser chokes on the registered
bfloat16 descr) and read back as ml_dtypes.bfloat16; consumers cast to
f32 ON DEVICE so solver math is unchanged.
"""

from __future__ import annotations

import json
import os
import queue
import threading
from typing import Iterable, Iterator, Optional, Sequence, Tuple

import numpy as np

from keystone_tpu.faults import fault_point
from keystone_tpu.obs import metrics

_META = "meta.json"
_DTYPES = ("float32", "bfloat16")


def _verify_blocks_enabled() -> bool:
    """Per-read checksum verification kill switch (KEYSTONE_VERIFY_BLOCKS
    =0).  BLAKE2b streams at memory-ish bandwidth, so verification is
    roughly a second disk pass per sweep — on by default because a
    silently-corrupt feature block poisons every subsequent epoch."""
    return os.environ.get("KEYSTONE_VERIFY_BLOCKS", "1") != "0"


def _bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


class _BlockStreamBase:
    """Shared disk→host→device streaming machinery for block stores.

    Subclasses provide :meth:`read_block`; both the column-blocked
    :class:`FeatureBlockStore` (BCD over feature blocks) and the
    row-blocked :class:`RowBlockStore` (the kernel tier's gram-block
    feed) ride the SAME prefetch thread + staged-transfer window, so
    the PR-7 flow-control guarantees — bounded in-flight host buffers,
    donation-safe yielded blocks, ``blockstore.stage_wait_seconds``
    metering — hold identically for every out-of-core sweep."""

    def read_block(self, b: int) -> np.ndarray:  # pragma: no cover
        raise NotImplementedError(type(self).__name__)

    def iter_blocks(
        self, order: Sequence[int], prefetch: int = 2
    ) -> Iterator[Tuple[int, np.ndarray]]:
        """Yield ``(b, block)`` for each index in ``order``, reading ahead
        on a worker thread so disk IO overlaps the consumer's device work
        (the role the reference delegates to Spark's block manager)."""
        q: "queue.Queue" = queue.Queue(maxsize=max(1, int(prefetch)))
        sentinel = object()
        stop = threading.Event()
        err: list = []

        def put(item) -> bool:
            # bounded put that gives up when the consumer abandoned the
            # generator — otherwise the thread would park forever on a
            # full queue, pinning GB-scale host blocks
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def produce():
            b_cur: Optional[int] = None
            try:
                for b in order:
                    b_cur = b
                    if stop.is_set() or not put((b, self.read_block(b))):
                        return
            except BaseException as e:
                # Tag the failing block index onto the error IN PLACE
                # (type preserved: retry_if / except-clauses downstream
                # dispatch on the exception class, so wrapping would
                # silently defeat them).  Without the tag, a sweep of
                # hundreds of blocks reports "checksum mismatch" with no
                # way to know WHICH block file to inspect.
                if b_cur is not None:
                    tag = f"block {b_cur}: "
                    if (
                        isinstance(e, OSError)
                        and e.errno is not None
                        and isinstance(e.strerror, str)
                    ):
                        # str(OSError) renders from errno/strerror, not
                        # args — and args must stay (errno, strerror)
                        # shaped for cross-process reconstruction, so
                        # the tag goes on the strerror field
                        e.strerror = tag + e.strerror
                    elif e.args and isinstance(e.args[0], str):
                        e.args = (tag + e.args[0],) + e.args[1:]
                    else:
                        # exotic arg shapes (fixed-arity/structured
                        # constructors): args mutation would break
                        # type(e)(*e.args) reconstruction — attach the
                        # index as an attribute only
                        e.block_index = b_cur
                err.append(e)
            finally:
                put(sentinel)

        t = threading.Thread(
            target=produce, daemon=True, name="blockstore-prefetch"
        )
        t.start()
        try:
            while True:
                item = q.get()
                if item is sentinel:
                    if err:
                        raise err[0]
                    return
                yield item
        finally:
            stop.set()
            # Join (bounded): when the consumer abandons the generator
            # mid-sweep (early break, exception, GC close), the producer
            # is parked on a full queue holding a GB-scale block; the
            # stop flag makes its bounded put give up within ~0.1 s, and
            # joining here makes the release PROMPT and deterministic
            # instead of leaving a parked daemon thread (and its pinned
            # block) to whenever the scheduler next runs it.  The
            # timeout covers a producer mid-read on a slow disk — a
            # leaked thread then still exits at the next put attempt.
            t.join(timeout=10.0)
            # drop any blocks still parked in the queue so their host
            # buffers free with the generator, not with the GC
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass

    def iter_device_blocks(
        self,
        order: Sequence[int],
        prefetch: int = 2,
        stage=None,
        window: int = 2,
    ) -> Iterator[Tuple[int, object]]:
        """Double-buffered device feed: yield ``(b, staged_block)`` with
        the host→device transfer of the NEXT block(s) already dispatched
        while the consumer computes on the current one.

        Three overlapped tiers: disk→host read-ahead rides
        :meth:`iter_blocks`'s producer thread (``prefetch`` deep);
        host→device staging is dispatched ``window`` blocks ahead of the
        consumer, so block *b+1*'s transfer overlaps block *b*'s
        compute; and the consumer's own device step is async-dispatched
        as usual.  ``stage(host_block) -> device value`` performs the
        put (default: ``jax.device_put`` + on-device f32 cast for bf16
        stores); a pytree return (tuple/list of arrays) is dispatched as
        ONE batched ``jax.device_put``-style transfer — callers staging
        multiple arrays per block should return them together rather
        than staging serially.

        Flow control WITHOUT host round-trips: before a block is
        yielded, ``jax.block_until_ready`` confirms its transfer landed
        (by then it was dispatched ``window`` iterations earlier, so the
        wait is usually zero).  That bounds in-flight staged host
        buffers to ``window`` blocks and guarantees every yielded block
        is safe for the consumer to DONATE to its compute step (a
        donated buffer cannot be waited on afterwards).  It bounds
        TRANSFERS only: transfers are not ordered behind compute, so a
        consumer whose per-block step is slower than the wire must also
        bound its own dispatch lead with a ready-wait on a recent step
        output (as ``_oc_bcd_fit`` does on the step's tick two behind) —
        otherwise yielded blocks pile up in HBM pinned by the queued
        executions that consume them.
        Time spent blocked in staging is recorded as the
        ``blockstore.stage_wait_seconds`` histogram — the obs ledger's
        ``transfer_seconds`` account.
        """
        import time

        import jax
        import jax.numpy as jnp
        from collections import deque

        if stage is None:

            def stage(blk):
                a = jax.device_put(blk)
                if a.dtype != jnp.float32:
                    a = a.astype(jnp.float32)
                return a

        window = max(1, int(window))
        staged: deque = deque()  # (b, value): transfer dispatched, not yielded

        def land(item):
            b, dev = item
            t0 = time.perf_counter()
            dev = jax.block_until_ready(dev)
            metrics.observe(
                "blockstore.stage_wait_seconds", time.perf_counter() - t0
            )
            return b, dev

        it = self.iter_blocks(order, prefetch=prefetch)
        try:
            for b, blk in it:
                t0 = time.perf_counter()
                dev = stage(blk)
                # the dispatch itself does real host work (layout copy +
                # DMA enqueue) — charge it
                # to the same transfer account as the landing wait
                metrics.observe(
                    "blockstore.stage_wait_seconds",
                    time.perf_counter() - t0,
                )
                staged.append((b, dev))
                if len(staged) > window:
                    yield land(staged.popleft())
            while staged:
                yield land(staged.popleft())
        finally:
            it.close()
            staged.clear()


class FeatureBlockStore(_BlockStreamBase):
    """Blockified (n, d) float32 feature matrix on disk.

    Create with :meth:`create` + :meth:`append_rows` (streaming writes),
    or the :meth:`from_array` / :meth:`from_batches` conveniences.
    """

    def __init__(self, directory: str):
        self.directory = directory
        with open(os.path.join(directory, _META)) as f:
            meta = json.load(f)
        self.n = int(meta["n"])
        self.d = int(meta["d"])
        self.block_size = int(meta["block_size"])
        self.num_blocks = int(meta["nb"])
        # stores written before the dtype option are float32
        self.dtype = str(meta.get("dtype", "float32"))

    @property
    def _disk_dtype(self):
        return np.uint16 if self.dtype == "bfloat16" else np.float32

    # ------------------------------------------------------------ create
    @classmethod
    def create(
        cls,
        directory: str,
        n: int,
        d: int,
        block_size: int,
        dtype: str = "float32",
    ):
        """Allocate an empty store; fill it with :meth:`append_rows`."""
        if dtype not in _DTYPES:
            raise ValueError(f"dtype must be one of {_DTYPES}, got {dtype!r}")
        os.makedirs(directory, exist_ok=True)
        nb = -(-d // block_size)
        meta = {
            "n": int(n),
            "d": int(d),
            "block_size": int(block_size),
            "nb": nb,
            "dtype": dtype,
        }
        with open(os.path.join(directory, _META), "w") as f:
            json.dump(meta, f)
        disk_dtype = np.uint16 if dtype == "bfloat16" else np.float32
        for b in range(nb):
            mm = np.lib.format.open_memmap(
                cls._block_path(directory, b),
                mode="w+",
                dtype=disk_dtype,
                shape=(n, block_size),
            )
            del mm  # flushed zero-initialized file
        store = cls(directory)
        store._cursor = 0
        # incremental payload digests, fed from the IN-MEMORY chunks as
        # they are written: finalize() compares them against what the
        # files actually contain, so corruption introduced by the write
        # path itself (torn write, bit flip between buffer and platter)
        # is caught at seal time — a sidecar hashed from the file alone
        # would faithfully checksum the damage
        import hashlib

        store._hashers = [
            hashlib.blake2b(digest_size=16) for _ in range(nb)
        ]
        return store

    @staticmethod
    def _block_path(directory: str, b: int) -> str:
        return os.path.join(directory, f"block_{b:04d}.npy")

    def append_rows(self, x: np.ndarray) -> None:
        """Write the next ``x.shape[0]`` rows of the (n, d) matrix."""
        x = np.asarray(x, np.float32)
        if x.ndim != 2 or x.shape[1] != self.d:
            raise ValueError(f"expected (m, {self.d}) rows, got {x.shape}")
        start = getattr(self, "_cursor", 0)
        stop = start + x.shape[0]
        if stop > self.n:
            raise ValueError(f"store holds {self.n} rows; write would reach {stop}")
        bs = self.block_size
        for b in range(self.num_blocks):
            mm = np.lib.format.open_memmap(
                self._block_path(self.directory, b), mode="r+"
            )
            chunk = x[:, b * bs : (b + 1) * bs]
            if chunk.shape[1] < bs:  # final ragged block: zero-pad columns
                chunk = np.pad(chunk, ((0, 0), (0, bs - chunk.shape[1])))
            if self.dtype == "bfloat16":
                chunk = chunk.astype(_bf16()).view(np.uint16)
            mm[start:stop] = chunk
            del mm
            hashers = getattr(self, "_hashers", None)
            if hashers is not None:
                hashers[b].update(np.ascontiguousarray(chunk).tobytes())
            fault_point(
                "blockstore.write", path=self._block_path(self.directory, b)
            )
            metrics.inc("blockstore.write_bytes", int(chunk.nbytes))
        metrics.inc("blockstore.writes")
        self._cursor = stop

    def finalize(self) -> None:
        """Seal a fully-written store: verify each block file's payload
        against the digest accumulated from the in-memory chunks during
        :meth:`append_rows` (write-path corruption — a torn or flipped
        write — surfaces HERE as :class:`CorruptStateError`, at spill
        time, instead of training on damaged features), then write a
        BLAKE2b sidecar per block so every later :meth:`read_block`
        verifies content integrity (truncation is caught even without
        sidecars via the size check).  ``from_array`` / ``from_batches``
        call this automatically; streaming ``append_rows`` writers call
        it once the last row lands."""
        import hashlib

        from keystone_tpu.utils import durable

        hashers = getattr(self, "_hashers", None)
        complete = getattr(self, "_cursor", None) == self.n
        for b in range(self.num_blocks):
            path = self._block_path(self.directory, b)
            if hashers is not None and complete:
                try:
                    raw = np.load(path, mmap_mode="r")
                    h = hashlib.blake2b(digest_size=16)
                    # stream row chunks off the memmap: the store exists
                    # because n×d does NOT fit in memory, so seal-time
                    # verification must stay O(chunk), not O(block)
                    row_bytes = max(1, raw.shape[1] * raw.itemsize)
                    step = max(1, (4 << 20) // row_bytes)
                    for s in range(0, raw.shape[0], step):
                        h.update(
                            np.ascontiguousarray(raw[s : s + step]).tobytes()
                        )
                    on_disk = h.hexdigest()
                except Exception as e:
                    raise durable.CorruptStateError(
                        f"unreadable block {path} at seal time: {e}"
                    )
                if on_disk != hashers[b].hexdigest():
                    raise durable.CorruptStateError(
                        f"write verification failed for block {path}: "
                        "on-disk payload does not match the bytes that "
                        "were written (torn or corrupted write)"
                    )
            durable.write_checksum(path)

    @classmethod
    def from_array(cls, directory: str, x, block_size: int, dtype: str = "float32"):
        x = np.asarray(x, np.float32)
        store = cls.create(directory, x.shape[0], x.shape[1], block_size, dtype=dtype)
        store.append_rows(x)
        store.finalize()
        return store

    @classmethod
    def from_batches(
        cls,
        directory: str,
        batches: Iterable[np.ndarray],
        n: int,
        block_size: int,
        dtype: str = "float32",
    ):
        """Build from a stream of (m_i, d) host batches (Σ m_i == n)."""
        store = None
        for batch in batches:
            batch = np.asarray(batch, np.float32)
            if store is None:
                store = cls.create(
                    directory, n, batch.shape[1], block_size, dtype=dtype
                )
            store.append_rows(batch)
        if store is None:
            raise ValueError("empty batch stream")
        if store._cursor != n:
            raise ValueError(
                f"batch stream produced {store._cursor} rows, expected {n}"
            )
        store.finalize()
        return store

    # -------------------------------------------------------------- read
    def read_block(self, b: int) -> np.ndarray:
        """One (n, block_size) block, as an in-memory host array.

        Hardened: transient read errors retry with backoff
        (utils/durable), a truncated file (partial write, torn spill)
        raises :class:`~keystone_tpu.utils.durable.CorruptStateError`
        before any bytes reach a solver, and sealed stores
        (:meth:`finalize`) additionally checksum-verify the content.

        bf16 stores return ml_dtypes.bfloat16 — consumers transfer the
        half-width bytes to device and cast to f32 THERE (halving the
        host→device wire cost, the scarce resource on this backend)."""
        from keystone_tpu.utils import durable

        path = self._block_path(self.directory, b)
        expected_bytes = (
            self.n * self.block_size * np.dtype(self._disk_dtype).itemsize
        )
        attempts = [0]

        def _read():
            attempts[0] += 1
            fault_point("blockstore.read", path=path)
            if os.path.getsize(path) < expected_bytes:
                raise durable.CorruptStateError(
                    f"truncated block {path}: {os.path.getsize(path)} bytes "
                    f"< {expected_bytes} of payload for shape "
                    f"({self.n}, {self.block_size})"
                )
            if _verify_blocks_enabled():
                durable.verify_checksum(path)  # no-op for unsealed stores
            try:
                raw = np.array(np.load(path, mmap_mode="r"))
            except ValueError as e:  # npy header inconsistent with size
                raise durable.CorruptStateError(f"corrupt block {path}: {e}")
            if raw.shape != (self.n, self.block_size):
                raise durable.CorruptStateError(
                    f"block {path} has shape {raw.shape}, expected "
                    f"({self.n}, {self.block_size})"
                )
            return raw

        raw = durable.with_retries(_read, description=f"block read {path}")
        metrics.inc("blockstore.reads")
        metrics.inc("blockstore.read_bytes", int(raw.nbytes))
        if attempts[0] > 1:
            metrics.inc("blockstore.read_retries", attempts[0] - 1)
        if self.dtype == "bfloat16":
            return raw.view(_bf16())
        return raw

    def nbytes(self) -> int:
        itemsize = 2 if self.dtype == "bfloat16" else 4
        return self.n * self.num_blocks * self.block_size * itemsize


_ROW_META = "row_meta.json"


class RowBlockStore(_BlockStreamBase):
    """Row-blocked (n, d) float32 matrix on disk — the kernel tier's
    out-of-core feed.

    Where :class:`FeatureBlockStore` splits the matrix by FEATURE
    columns (the BCD-over-feature-blocks layout), this store splits by
    EXAMPLE rows: block *b* is ``X[b·bs : (b+1)·bs]`` as one ``(bs, d)``
    npy file, zero-padded on rows in the final block so every device
    transfer and every compiled gram-block step shares one shape.  The
    kernel BCD sweep streams these row blocks to build ``K_{·b}``
    column blocks tile by tile via the ‖x−z‖² gemm expansion — the n×n
    kernel matrix never materializes anywhere.

    Streaming row batches append SEQUENTIALLY (each batch lands in a
    few consecutive block files), integrity rides the same machinery as
    the feature store: incremental write-path digests verified at
    :meth:`finalize`, BLAKE2b sidecars per block, retried +
    truncation-checked reads through the ``blockstore.read`` fault
    site.  ``dtype="bfloat16"`` halves disk + wire bytes; consumers
    cast to f32 on device (solver math unchanged).

    Layout::

        row_meta.json            {"n","d","block_size","nb","dtype"}
        rblock_0000.npy          (block_size, d) rows [0, bs)
        rblock_0001.npy          ...
    """

    def __init__(self, directory: str):
        self.directory = directory
        with open(os.path.join(directory, _ROW_META)) as f:
            meta = json.load(f)
        self.n = int(meta["n"])
        self.d = int(meta["d"])
        self.block_size = int(meta["block_size"])
        self.num_blocks = int(meta["nb"])
        self.dtype = str(meta.get("dtype", "float32"))

    @property
    def _disk_dtype(self):
        return np.uint16 if self.dtype == "bfloat16" else np.float32

    # ------------------------------------------------------------ create
    @classmethod
    def create(
        cls,
        directory: str,
        n: int,
        d: int,
        block_size: int,
        dtype: str = "float32",
    ):
        """Allocate an empty store; fill it with :meth:`append_rows`."""
        if dtype not in _DTYPES:
            raise ValueError(f"dtype must be one of {_DTYPES}, got {dtype!r}")
        os.makedirs(directory, exist_ok=True)
        nb = -(-n // block_size)
        meta = {
            "n": int(n),
            "d": int(d),
            "block_size": int(block_size),
            "nb": nb,
            "dtype": dtype,
        }
        with open(os.path.join(directory, _ROW_META), "w") as f:
            json.dump(meta, f)
        disk_dtype = np.uint16 if dtype == "bfloat16" else np.float32
        for b in range(nb):
            mm = np.lib.format.open_memmap(
                cls._block_path(directory, b),
                mode="w+",
                dtype=disk_dtype,
                shape=(block_size, d),
            )
            del mm  # flushed zero-initialized file
        store = cls(directory)
        store._cursor = 0
        # write-path digests fed from the in-memory chunks (see
        # FeatureBlockStore.create): finalize() compares them against
        # the files so a torn/flipped write surfaces at seal time
        import hashlib

        store._hashers = [hashlib.blake2b(digest_size=16) for _ in range(nb)]
        return store

    @staticmethod
    def _block_path(directory: str, b: int) -> str:
        return os.path.join(directory, f"rblock_{b:04d}.npy")

    def append_rows(self, x: np.ndarray) -> None:
        """Write the next ``x.shape[0]`` rows.  Sequential: a batch
        spans only the block files covering its row range."""
        x = np.asarray(x, np.float32)
        if x.ndim != 2 or x.shape[1] != self.d:
            raise ValueError(f"expected (m, {self.d}) rows, got {x.shape}")
        start = getattr(self, "_cursor", 0)
        stop = start + x.shape[0]
        if stop > self.n:
            raise ValueError(f"store holds {self.n} rows; write would reach {stop}")
        bs = self.block_size
        hashers = getattr(self, "_hashers", None)
        for b in range(start // bs, -(-stop // bs)):
            lo, hi = max(start, b * bs), min(stop, (b + 1) * bs)
            chunk = x[lo - start : hi - start]
            if self.dtype == "bfloat16":
                chunk = chunk.astype(_bf16()).view(np.uint16)
            mm = np.lib.format.open_memmap(
                self._block_path(self.directory, b), mode="r+"
            )
            mm[lo - b * bs : hi - b * bs] = chunk
            del mm
            if hashers is not None:
                hashers[b].update(np.ascontiguousarray(chunk).tobytes())
            fault_point(
                "blockstore.write", path=self._block_path(self.directory, b)
            )
            metrics.inc("blockstore.write_bytes", int(chunk.nbytes))
        metrics.inc("blockstore.writes")
        self._cursor = stop

    def finalize(self) -> None:
        """Seal a fully-written store: verify every block's WRITTEN rows
        against the write-path digest (the padding rows of the final
        block were zero-filled at create time and never appended, so
        only rows ``< n`` enter the comparison), then write the BLAKE2b
        sidecar covering the whole file for read-time verification."""
        import hashlib

        from keystone_tpu.utils import durable

        hashers = getattr(self, "_hashers", None)
        complete = getattr(self, "_cursor", None) == self.n
        bs = self.block_size
        for b in range(self.num_blocks):
            path = self._block_path(self.directory, b)
            if hashers is not None and complete:
                rows = min(bs, self.n - b * bs)
                try:
                    raw = np.load(path, mmap_mode="r")
                    h = hashlib.blake2b(digest_size=16)
                    row_bytes = max(1, raw.shape[1] * raw.itemsize)
                    step = max(1, (4 << 20) // row_bytes)
                    for s in range(0, rows, step):
                        h.update(
                            np.ascontiguousarray(
                                raw[s : min(s + step, rows)]
                            ).tobytes()
                        )
                    on_disk = h.hexdigest()
                except Exception as e:
                    raise durable.CorruptStateError(
                        f"unreadable block {path} at seal time: {e}"
                    )
                if on_disk != hashers[b].hexdigest():
                    raise durable.CorruptStateError(
                        f"write verification failed for block {path}: "
                        "on-disk payload does not match the bytes that "
                        "were written (torn or corrupted write)"
                    )
            durable.write_checksum(path)

    @classmethod
    def from_array(cls, directory: str, x, block_size: int, dtype: str = "float32"):
        x = np.asarray(x, np.float32)
        store = cls.create(directory, x.shape[0], x.shape[1], block_size, dtype=dtype)
        store.append_rows(x)
        store.finalize()
        return store

    @classmethod
    def from_batches(
        cls,
        directory: str,
        batches: Iterable[np.ndarray],
        n: int,
        block_size: int,
        dtype: str = "float32",
    ):
        """Build from a stream of (m_i, d) host batches (Σ m_i == n)."""
        store = None
        for batch in batches:
            batch = np.asarray(batch, np.float32)
            if store is None:
                store = cls.create(
                    directory, n, batch.shape[1], block_size, dtype=dtype
                )
            store.append_rows(batch)
        if store is None:
            raise ValueError("empty batch stream")
        if store._cursor != n:
            raise ValueError(
                f"batch stream produced {store._cursor} rows, expected {n}"
            )
        store.finalize()
        return store

    # -------------------------------------------------------------- read
    def read_block(self, b: int) -> np.ndarray:
        """One (block_size, d) row block as an in-memory host array,
        with the same hardening as FeatureBlockStore.read_block: retried
        reads, truncation detection, checksum verification, and the
        ``blockstore.read`` fault site."""
        from keystone_tpu.utils import durable

        path = self._block_path(self.directory, b)
        expected_bytes = (
            self.block_size * self.d * np.dtype(self._disk_dtype).itemsize
        )
        attempts = [0]

        def _read():
            attempts[0] += 1
            fault_point("blockstore.read", path=path)
            if os.path.getsize(path) < expected_bytes:
                raise durable.CorruptStateError(
                    f"truncated block {path}: {os.path.getsize(path)} bytes "
                    f"< {expected_bytes} of payload for shape "
                    f"({self.block_size}, {self.d})"
                )
            if _verify_blocks_enabled():
                durable.verify_checksum(path)  # no-op for unsealed stores
            try:
                raw = np.array(np.load(path, mmap_mode="r"))
            except ValueError as e:  # npy header inconsistent with size
                raise durable.CorruptStateError(f"corrupt block {path}: {e}")
            if raw.shape != (self.block_size, self.d):
                raise durable.CorruptStateError(
                    f"block {path} has shape {raw.shape}, expected "
                    f"({self.block_size}, {self.d})"
                )
            return raw

        raw = durable.with_retries(_read, description=f"block read {path}")
        metrics.inc("blockstore.reads")
        metrics.inc("blockstore.read_bytes", int(raw.nbytes))
        if attempts[0] > 1:
            metrics.inc("blockstore.read_retries", attempts[0] - 1)
        if self.dtype == "bfloat16":
            return raw.view(_bf16())
        return raw

    def nbytes(self) -> int:
        itemsize = 2 if self.dtype == "bfloat16" else 4
        return self.num_blocks * self.block_size * self.d * itemsize
