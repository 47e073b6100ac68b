"""Mesh management.

The framework uses a 2-D logical mesh:

  - ``'data'``  — data parallelism: the batch/example axis.  Replaces the
    reference's RDD partitioning (SURVEY.md §2.9 "Data parallelism").
  - ``'model'`` — feature/model parallelism: the feature axis of wide
    models.  The reference scales model dimension *in time* (block
    coordinate descent over 4096-column feature blocks,
    nodes/learning/BlockLeastSquares.scala); we additionally scale it
    *in space* by sharding the feature axis across devices.

A process-global mesh (set with :func:`set_mesh` / :func:`use_mesh`)
keeps user code free of distribution plumbing, analogous to the
reference's process-global ``PipelineEnv`` holding the SparkContext.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Iterator, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


@dataclasses.dataclass
class MeshContext:
    """Holder for the process-global mesh (cf. workflow/PipelineEnv.scala)."""

    mesh: Optional[Mesh] = None


_CTX = MeshContext()
_LOCK = threading.Lock()


def device_count() -> int:
    return len(jax.devices())


def default_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    model_parallelism: int = 1,
) -> Mesh:
    """Build a ('data', 'model') mesh over the given (default: all) devices.

    ``model_parallelism`` devices are assigned to the 'model' axis; the
    remainder to 'data'.  With a single device both axes have size 1 and
    all collectives are no-ops, which is how single-chip runs work
    unchanged (the reference's "local mode").
    """
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if n % model_parallelism != 0:
        raise ValueError(
            f"device count {n} not divisible by model_parallelism {model_parallelism}"
        )
    arr = np.asarray(devs).reshape(n // model_parallelism, model_parallelism)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def local_mesh() -> Mesh:
    """A trivial 1x1 mesh on the first device (single-datum / debug path)."""
    return default_mesh(jax.devices()[:1])


def set_mesh(mesh: Optional[Mesh]) -> None:
    with _LOCK:
        _CTX.mesh = mesh


def current_mesh() -> Mesh:
    """The active mesh, creating the all-device default on first use."""
    with _LOCK:
        if _CTX.mesh is None:
            _CTX.mesh = default_mesh()
        return _CTX.mesh


def active_mesh() -> Optional[Mesh]:
    """The active mesh if one was set, WITHOUT creating the default —
    for callers that only want to inspect (e.g. which platform the
    computation targets) and must not instantiate device state."""
    with _LOCK:
        return _CTX.mesh


@contextlib.contextmanager
def use_mesh(mesh: Mesh) -> Iterator[Mesh]:
    prev = _CTX.mesh
    set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(prev)


def replicated(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Fully-replicated sharding — the analogue of Spark broadcast."""
    return NamedSharding(mesh or current_mesh(), P())


def data_sharding(
    mesh: Optional[Mesh] = None, ndim: int = 2, feature_axis: Optional[int] = None
) -> NamedSharding:
    """Rows over 'data'; optionally one axis over 'model' (feature sharding)."""
    spec = [None] * ndim
    spec[0] = DATA_AXIS
    if feature_axis is not None:
        spec[feature_axis] = MODEL_AXIS
    return NamedSharding(mesh or current_mesh(), P(*spec))


def pad_to_multiple(n: int, m: int) -> int:
    return int(math.ceil(n / m) * m) if m > 1 else n


def shard_batch(x, mesh: Optional[Mesh] = None, feature_axis: Optional[int] = None):
    """Place a host array on the mesh, batch axis over 'data'.

    If the leading axis is not divisible by the data-axis size the array is
    zero-padded (callers that care track true length separately; the
    framework's Dataset does).  This is the moral equivalent of
    ``sc.parallelize(data, numPartitions)``.
    """
    import jax.numpy as jnp

    mesh = mesh or current_mesh()
    # a HOST array goes straight to its shards: jnp.asarray would first
    # land the whole of it on the first device, then reshard from there
    xp = jnp if isinstance(x, jax.Array) else np
    x = xp.asarray(x)
    dsize = mesh.shape[DATA_AXIS]
    n = x.shape[0]
    padded = pad_to_multiple(n, dsize)
    if padded != n:
        pad_widths = [(0, padded - n)] + [(0, 0)] * (x.ndim - 1)
        x = xp.pad(x, pad_widths)
    return jax.device_put(x, data_sharding(mesh, x.ndim, feature_axis))
