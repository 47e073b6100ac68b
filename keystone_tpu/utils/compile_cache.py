"""Persistent XLA compilation cache.

A cold run's first cost is XLA compilation; JAX's persistent compilation
cache keeps compiled executables across *processes*, so a second run of
the same pipeline skips it (the reference amortizes its equivalent —
JVM/JIT warmup — by keeping the cluster alive between jobs).

There is ONE way to place the cache: the ``JAX_COMPILATION_CACHE_DIR``
environment variable, which JAX reads itself.  Where it is set (or a
caller already configured ``jax_compilation_cache_dir``) this module
leaves the directory alone; where it is not, the cache goes to
:data:`CACHE_DIR`, a fixed directory inside the checkout — the path is
part of the cache key, so a directory that moves never hits.
``KEYSTONE_COMPILE_CACHE=0`` (``off``/``none``/``false``) is the off
switch.  CLI/bench entry points and ``PipelineService`` call
:func:`enable_compilation_cache`; library users may too.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

logger = logging.getLogger(__name__)

_DISABLE_VALUES = ("0", "off", "none", "false")

#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset
#: (git-ignored; relative to the checkout, never ``~`` or a temp name)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache and return its
    directory (None when ``KEYSTONE_COMPILE_CACHE`` switches it off).

    The directory is whatever JAX already holds — from
    ``JAX_COMPILATION_CACHE_DIR`` or an earlier ``jax.config.update`` —
    and only when there is none, :data:`CACHE_DIR`.  Idempotent; safe
    before or after backend initialization (config is read at compile
    time)."""
    if os.environ.get("KEYSTONE_COMPILE_CACHE", "").strip().lower() in _DISABLE_VALUES:
        return None
    import jax

    # persist every program: the fit dispatches dozens of sub-second
    # programs whose compiles add up to most of a cold start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    existing = jax.config.jax_compilation_cache_dir
    if existing:
        return existing
    try:
        os.makedirs(CACHE_DIR, exist_ok=True)
    except OSError as e:  # read-only checkout: run uncached
        logger.warning("compilation cache unavailable (%s); continuing without", e)
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def snapshot_cache_entries() -> Optional[set]:
    """The active cache dir's current file set (None: no active dir) —
    the 'before' side of :func:`collect_new_entries`."""
    import jax

    d = jax.config.jax_compilation_cache_dir
    if not d or not os.path.isdir(d):
        return None
    return set(os.listdir(d))


def collect_new_entries(before: Optional[set]) -> dict:
    """Files the active cache dir gained since ``before`` was
    snapshotted, as ``{filename: bytes}`` — the export path captures
    the persistent-cache entries its backend compiles mint, so a
    freeze-artifact bundle can SHIP them (the artifact ladder's last
    cold rung: a fresh host's first deploy then skips even the backend
    compile of the deserialized module)."""
    if before is None:
        return {}
    import jax

    d = jax.config.jax_compilation_cache_dir
    if not d or not os.path.isdir(d):
        return {}
    out = {}
    for name in sorted(set(os.listdir(d)) - before):
        path = os.path.join(d, name)
        try:
            if os.path.isfile(path):
                with open(path, "rb") as f:
                    out[name] = f.read()
        except OSError:
            continue  # capture is best-effort; the entry just re-compiles
    return out


def seed_compile_cache(bundle: Optional[dict]) -> int:
    """Install an artifact bundle's shipped compile-cache entries into
    the active persistent cache dir (missing files only — an existing
    entry is never clobbered).  Returns how many files were written.
    Best-effort end to end: no active cache, no shipped entries, or an
    unwritable dir all degrade to plain compilation, never fail a
    deploy.  Counted as ``serve.cache_seeded``."""
    manifest = (bundle or {}).get("manifest") or {}
    blobs = (bundle or {}).get("blobs") or {}
    entries = {
        key: ent
        for key, ent in (manifest.get("entries") or {}).items()
        if ent.get("kind") == "compile_cache"
    }
    if not entries:
        return 0
    d = enable_compilation_cache()
    if not d:
        return 0
    seeded = 0
    for key, ent in entries.items():
        data = blobs.get(key)
        name = ent.get("name")
        if data is None or not name or os.sep in str(name):
            continue
        path = os.path.join(d, str(name))
        if os.path.exists(path):
            continue
        try:
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, path)
            seeded += 1
        except OSError as e:
            logger.warning("compile-cache seed of %s failed: %s", name, e)
    if seeded:
        from keystone_tpu.obs import metrics

        metrics.inc("serve.cache_seeded", seeded)
        logger.info(
            "seeded %d persistent-compile-cache entr%s from the artifact "
            "bundle",
            seeded,
            "y" if seeded == 1 else "ies",
        )
    return seeded


def cache_active() -> bool:
    """Is a persistent XLA compilation cache configured right now?
    (The serve prime path labels its timings
    ``serve.prime_seconds{source=cache}`` vs ``compile`` on this.)"""
    import jax

    return bool(jax.config.jax_compilation_cache_dir)
