"""AOT freeze artifacts: the pre-lowered-executable tier and its
fallback ladder (ISSUE 11).

What must hold:

- an exported-then-installed bucket program produces BIT-IDENTICAL
  predictions to the freshly-compiled executor walk;
- any mismatch — jax version skew, backend skew, signature drift, a
  corrupt blob or manifest — silently falls one rung down the ladder
  (artifact → compile cache → fresh compile), counted as
  ``serve.artifact_fallbacks``, and NEVER fails a deploy/swap/heal;
- the supervisor's heal primes replacements from artifacts (no fresh
  compile-tier primes — compile time must not be recovery time);
- with no artifacts installed the path is inert (one empty-dict check;
  solver HLO unchanged with the machinery exercised).
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu import faults
from keystone_tpu.models.linear import LinearMapper
from keystone_tpu.obs import metrics
from keystone_tpu.ops.stats import NormalizeRows
from keystone_tpu.serve import ModelRegistry, RegistryWatcher, serve
from keystone_tpu.workflow import ArtifactMismatch, Dataset, Pipeline
from keystone_tpu.workflow.pipeline import FrozenApplier

pytestmark = pytest.mark.serve

DIM = 8
CLASSES = 3
BUCKETS = (2, 4)


def _pipeline(seed: int = 0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(DIM, CLASSES)).astype(np.float32))
    return (Pipeline.of(NormalizeRows()) | LinearMapper(w)).fit()


def _example():
    return np.zeros((DIM,), np.float32)


def _one_device():
    """Pin serve tests to one explicit device: the fleet placement
    discipline, and exact bucket shapes — the session's 4x2 test mesh
    would otherwise pad/shard deviceless batches past the buckets."""
    import jax

    return [jax.devices()[0]]


def _ds(x):
    """An UNSHARDED dataset at the batch's exact shape (what the fleet
    path feeds the applier); the test mesh would pad a bare array."""
    return Dataset(x, shard=False)


def _counter(name: str) -> float:
    return metrics.REGISTRY.counter_total(name)


def _prime_count(source: str) -> int:
    hists = metrics.snapshot().get("histograms") or {}
    h = hists.get(f"serve.prime_seconds{{source={source}}}") or {}
    return int(h.get("count") or 0)


@pytest.fixture(scope="module")
def exported():
    """One pipeline + its exported bundle, shared across the module
    (exports re-trace the whole graph; one is plenty)."""
    pipe = _pipeline()
    frozen = pipe.freeze()
    bundle = frozen.export_artifacts(example=_example(), buckets=BUCKETS)
    return pipe, frozen, bundle


@pytest.fixture()
def registry(tmp_path, exported):
    pipe, _frozen, bundle = exported
    reg = ModelRegistry(str(tmp_path / "registry"))
    version = reg.publish(pipe, artifacts=bundle)
    return reg, version


# ----------------------------------------------------------- roundtrip


def test_roundtrip_bit_parity_vs_fresh_compile(exported):
    """The installed AOT program and the freshly-compiled walk must
    agree bit-for-bit at every bucket shape."""
    pipe, frozen, bundle = exported
    fresh = pipe.freeze()  # a separate applier: pure walk, no programs
    target = pipe.freeze()
    assert target.install_artifacts(bundle) == len(BUCKETS)
    rng = np.random.default_rng(1)
    for b in BUCKETS:
        x = rng.normal(size=(b, DIM)).astype(np.float32)
        via_artifact = np.asarray(target(_ds(x)).array)
        via_walk = np.asarray(fresh(_ds(x)).array)
        assert via_artifact.tobytes() == via_walk.tobytes()


def test_non_bucket_shape_rides_the_walk(exported):
    """A shape with no installed program silently uses the executor
    walk — artifacts narrow nothing."""
    pipe, _frozen, bundle = exported
    ap = pipe.freeze()
    ap.install_artifacts(bundle)
    x = np.random.default_rng(2).normal(size=(3, DIM)).astype(np.float32)
    out = np.asarray(ap(_ds(x)).array)
    assert out.shape == (3, CLASSES)


def test_registry_artifacts_roundtrip(registry, exported):
    _pipe, _frozen, bundle = exported
    reg, version = registry
    loaded = reg.load_artifacts(version)
    assert loaded is not None
    assert loaded["manifest"]["signature"] == bundle["manifest"]["signature"]
    assert set(loaded["blobs"]) == set(bundle["blobs"])
    for key, blob in bundle["blobs"].items():
        assert bytes(loaded["blobs"][key]) == bytes(blob)


# ------------------------------------------------------ fallback ladder


def test_jax_version_skew_falls_back(exported):
    pipe, _frozen, bundle = exported
    skewed = {
        "manifest": {**bundle["manifest"], "jax_version": "0.0.1"},
        "blobs": bundle["blobs"],
    }
    ap = pipe.freeze()
    f0 = _counter("serve.artifact_fallbacks")
    assert ap.install_artifacts(skewed) == 0
    assert ap.installed_buckets() == 0
    assert _counter("serve.artifact_fallbacks") == f0 + 1
    with pytest.raises(ArtifactMismatch):
        ap.install_artifacts(skewed, strict=True)


def test_backend_skew_falls_back(exported):
    pipe, _frozen, bundle = exported
    skewed = {
        "manifest": {**bundle["manifest"], "platforms": ["tpu"]},
        "blobs": bundle["blobs"],
    }
    ap = pipe.freeze()
    f0 = _counter("serve.artifact_fallbacks")
    assert ap.install_artifacts(skewed) == 0
    assert _counter("serve.artifact_fallbacks") == f0 + 1


def test_signature_drift_falls_back(exported):
    """Another pipeline's artifacts (different weights) must never be
    replayed — a silent stale-model serve is the one unacceptable
    failure mode."""
    _pipe, _frozen, bundle = exported
    other = _pipeline(seed=9).freeze()
    f0 = _counter("serve.artifact_fallbacks")
    assert other.install_artifacts(bundle) == 0
    assert _counter("serve.artifact_fallbacks") == f0 + 1


def test_corrupt_blob_tolerated_on_registry_load(registry):
    """A damaged blob drops only its bucket; the rest of the bundle
    still installs."""
    reg, version = registry
    adir = reg.artifacts_dir(version)
    victim = os.path.join(adir, f"b{BUCKETS[0]:05d}.hlo")
    with open(victim, "r+b") as f:
        f.seek(10)
        f.write(b"\xff" * 16)
    f0 = _counter("serve.artifact_fallbacks")
    loaded = reg.load_artifacts(version)
    assert _counter("serve.artifact_fallbacks") == f0 + 1
    assert loaded is not None
    assert f"b{BUCKETS[0]:05d}" not in loaded["blobs"]
    assert f"b{BUCKETS[1]:05d}" in loaded["blobs"]


def test_corrupt_manifest_drops_the_whole_tier(registry):
    reg, version = registry
    mpath = os.path.join(reg.artifacts_dir(version), "MANIFEST.json")
    with open(mpath, "r+b") as f:
        f.seek(2)
        f.write(b"\x00\x00")
    assert reg.load_artifacts(version) is None


def test_artifact_load_fault_site_degrades(registry):
    """An injected ``serve.artifact_load`` failure degrades the load to
    'no artifact tier' — it never raises out of the registry."""
    reg, version = registry
    with faults.inject("serve.artifact_load:raise"):
        assert reg.load_artifacts(version) is None
    assert reg.load_artifacts(version) is not None  # plan gone, tier back


def test_runtime_program_failure_falls_back_to_walk(exported):
    """A bucket program that fails at CALL time is dropped for good and
    the walk serves — one bad executable must not fail serving."""
    pipe, _frozen, bundle = exported
    ap = pipe.freeze()
    ap.install_artifacts(bundle)
    key = ((BUCKETS[0], DIM), "float32")
    assert key in ap._bucket_programs

    def boom(x):
        raise RuntimeError("poisoned program")

    ap._bucket_programs[key] = boom
    f0 = _counter("serve.artifact_fallbacks")
    x = np.random.default_rng(3).normal(size=(BUCKETS[0], DIM))
    out = np.asarray(ap(_ds(x.astype(np.float32))).array)
    assert out.shape == (BUCKETS[0], CLASSES)
    assert key not in ap._bucket_programs  # dropped, not retried per call
    assert _counter("serve.artifact_fallbacks") == f0 + 1


def test_stream_dataset_never_hits_bucket_programs(exported):
    """A StreamDataset must ride the walk untouched: the fast path
    keying on ``.array`` would materialize an out-of-core stream just
    to compute a dict key.  Programs are poisoned so a fast-path
    attempt is observable (drop + fallback counter)."""
    from keystone_tpu.workflow import StreamDataset

    pipe, _frozen, bundle = exported
    ap = pipe.freeze()
    ap.install_artifacts(bundle)
    n_installed = ap.installed_buckets()

    def boom(x):
        raise RuntimeError("bucket program ran on a stream")

    for k in list(ap._bucket_programs):
        ap._bucket_programs[k] = boom
    xs = np.random.default_rng(12).normal(size=(BUCKETS[0], DIM))
    xs = xs.astype(np.float32)

    def batches():
        yield xs

    f0 = _counter("serve.artifact_fallbacks")
    out = ap(StreamDataset(batches, n=BUCKETS[0]))
    vals = np.concatenate([np.asarray(b) for b in out.batches()])
    assert vals.shape == (BUCKETS[0], CLASSES)
    # the poisoned programs were never consulted: nothing dropped,
    # nothing counted
    assert _counter("serve.artifact_fallbacks") == f0
    assert ap.installed_buckets() == n_installed


def test_stable_repr_collapses_only_the_offending_element():
    """Two pipelines differing only in a scalar param NEXT TO an
    address-bearing object must hash differently — collapsing the whole
    container would alias them (the stale-artifact hazard)."""
    from keystone_tpu.utils.hashing import _stable_repr

    class Opaque:
        pass  # default repr carries a process-local address

    a = _stable_repr((0.5, Opaque()))
    b = _stable_repr((0.7, Opaque()))
    assert a != b
    assert "0x" not in a and "0x" not in b  # still process-stable


def test_degradable_pipeline_warms_the_walk_too(registry):
    """A degradation-declaring pipeline routes deadline-carrying
    flushes to the executor walk even with artifacts installed —
    prime() must warm BOTH tiers, so the first deadline-carrying
    request after a cold start/heal pays no in-band compile."""
    import jax

    from keystone_tpu.models.linear import LinearMapper as LM

    rng = np.random.default_rng(13)
    w = jnp.asarray(rng.normal(size=(DIM, CLASSES)).astype(np.float32))
    head = NormalizeRows()
    head.optional = True  # declares degradation -> _degradable applier
    pipe = (Pipeline.of(head) | LM(w)).fit()
    bundle = pipe.freeze().export_artifacts(
        example=_example(), buckets=BUCKETS
    )
    a0 = _prime_count("artifact")
    svc = serve(
        pipe,
        max_batch=BUCKETS[-1],
        buckets=BUCKETS,
        example=_example(),
        deadline_ms=30000.0,
        name="degr_art",
        supervise=False,
        devices=[jax.devices()[0]],
        artifacts=bundle,
    )
    try:
        assert _prime_count("artifact") == a0 + len(BUCKETS)
        x = rng.normal(size=(DIM,)).astype(np.float32)
        # deadline-carrying request -> walk path (degradable): must be
        # served from warm programs, well inside the budget
        y = np.asarray(svc.submit(x, deadline=30.0).result(timeout=30))
        assert np.all(np.isfinite(y))
    finally:
        svc.close()


def test_deadline_contract_survives_the_artifact_path(exported):
    """A deadline-carrying call on the bucket-program path keeps the
    walk's contract: a generous budget runs the program (bit-identical
    to the no-deadline call), an expired one raises the typed
    ``DeadlineExceeded`` — and the program is NOT dropped (a timeout is
    not a broken executable)."""
    from keystone_tpu.utils import guard

    pipe, _frozen, bundle = exported
    ap = pipe.freeze()
    ap.install_artifacts(bundle)
    key = ((BUCKETS[0], DIM), "float32")
    x = np.random.default_rng(5).normal(size=(BUCKETS[0], DIM))
    x = x.astype(np.float32)
    y_plain = np.asarray(ap(_ds(x)).array)
    y_budget = np.asarray(ap(_ds(x), deadline=30.0).array)
    assert y_plain.tobytes() == y_budget.tobytes()
    with pytest.raises(guard.DeadlineExceeded):
        ap(_ds(x), deadline=guard.Deadline.after(0.0))
    assert key in ap._bucket_programs  # kept: timeouts are not corruption


# ------------------------------------------------------------- serving


def test_serve_primes_from_artifacts_and_matches(registry):
    """A service built with the bundle primes every bucket from the
    artifact tier, and serves predictions bit-identical to a
    freshly-compiled service."""
    reg, version = registry
    fitted, v = reg.load()
    arts = reg.load_artifacts(v)
    a0 = _prime_count("artifact")
    h0 = _counter("serve.artifact_hits")
    svc = serve(
        fitted,
        max_batch=BUCKETS[-1],
        buckets=BUCKETS,
        example=_example(),
        name="art_serve",
        supervise=False,
        devices=_one_device(),
        artifacts=arts,
    )
    try:
        assert _prime_count("artifact") == a0 + len(BUCKETS)
        assert _counter("serve.artifact_hits") == h0 + len(BUCKETS)
        x = np.random.default_rng(4).normal(size=(DIM,)).astype(np.float32)
        y_art = np.asarray(svc.submit(x).result(timeout=30))
        st = svc.status()
        assert st["artifacts"]["configured"] is True
        assert st["artifacts"]["installed_buckets"] == len(BUCKETS)
        assert st["artifacts"]["prime_seconds"]["artifact"]["count"] >= len(
            BUCKETS
        )
    finally:
        svc.close()
    svc2 = serve(
        _pipeline(),
        max_batch=BUCKETS[-1],
        buckets=BUCKETS,
        example=_example(),
        name="cmp_serve",
        supervise=False,
        devices=_one_device(),
    )
    try:
        y_cmp = np.asarray(svc2.submit(x).result(timeout=30))
    finally:
        svc2.close()
    assert y_art.tobytes() == y_cmp.tobytes()


def test_swap_survives_damaged_artifacts(registry, tmp_path):
    """A hot-swap whose new version carries corrupt artifacts commits
    anyway (the staged generation compiles) — degraded, never failed.
    Also pins the staged-prime miss accounting: the service SERVES an
    artifact-bearing generation, but the staged generation got no
    bundle, so its primes must not count as artifact_misses (the
    pool's live-generation flag would mislabel them)."""
    reg, version = registry
    fitted, v = reg.load()
    svc = serve(
        fitted,
        max_batch=BUCKETS[-1],
        buckets=BUCKETS,
        example=_example(),
        name="swap_art",
        supervise=False,
        devices=_one_device(),
        artifacts=reg.load_artifacts(v),
    )
    try:
        new_pipe = _pipeline(seed=5)
        new_bundle = new_pipe.freeze().export_artifacts(
            example=_example(), buckets=BUCKETS
        )
        v2 = reg.publish(new_pipe, artifacts=new_bundle)
        adir = reg.artifacts_dir(v2)
        for name in os.listdir(adir):
            # bucket programs, and the compile-cache entries that an export
            # into a cold persistent cache mints and ships beside them
            if name.endswith((".hlo", ".bin")):
                with open(os.path.join(adir, name), "r+b") as f:
                    f.seek(5)
                    f.write(b"\xff" * 8)
        arts = reg.load_artifacts(v2)  # every blob skipped -> None
        assert arts is None
        m0 = _counter("serve.artifact_misses")
        info = svc.swap(fitted, version=v2, artifacts=arts)
        assert info["version"] == v2
        # bundle-less staged generation: no artifact_misses lies
        assert _counter("serve.artifact_misses") == m0
        x = np.random.default_rng(6).normal(size=(DIM,)).astype(np.float32)
        assert np.all(
            np.isfinite(np.asarray(svc.submit(x).result(timeout=30)))
        )
    finally:
        svc.close()


def test_watcher_swap_ships_artifacts(registry):
    """A watcher-driven rollout installs the new version's artifacts:
    the staged generation's prime rides the artifact tier."""
    reg, version = registry
    fitted, v = reg.load()
    svc = serve(
        fitted,
        max_batch=BUCKETS[-1],
        buckets=BUCKETS,
        example=_example(),
        name="watch_art",
        supervise=False,
        devices=_one_device(),
    )
    watcher = RegistryWatcher(svc, reg, poll_seconds=60.0)
    try:
        new_pipe = _pipeline(seed=7)
        bundle = new_pipe.freeze().export_artifacts(
            example=_example(), buckets=BUCKETS
        )
        v2 = reg.publish(new_pipe, artifacts=bundle)
        a0 = _prime_count("artifact")
        watcher._poll_once()
        assert svc.version == v2
        assert _prime_count("artifact") == a0 + len(BUCKETS)
    finally:
        svc.close()


def test_admin_swap_endpoint_ships_artifacts(registry):
    """POST /swap must load the target version's artifacts like the
    watcher does — an admin swap silently dropping the artifact tier
    would also cost every later supervisor heal (the bundle moves with
    the generation at commit)."""
    import urllib.request

    from keystone_tpu.serve import serve_http

    reg, version = registry
    fitted, v = reg.load()
    svc = serve(
        fitted,
        max_batch=BUCKETS[-1],
        buckets=BUCKETS,
        example=_example(),
        name="httpswap_art",
        supervise=False,
        devices=_one_device(),
    )
    try:
        new_pipe = _pipeline(seed=21)
        bundle = new_pipe.freeze().export_artifacts(
            example=_example(), buckets=BUCKETS
        )
        v2 = reg.publish(new_pipe, artifacts=bundle)
        a0 = _prime_count("artifact")
        with serve_http(svc, port=0, registry=reg) as front:
            req = urllib.request.Request(
                f"http://127.0.0.1:{front.port}/swap",
                data=json.dumps({"version": v2}).encode(),
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                info = json.loads(resp.read().decode())
        assert info["version"] == v2
        assert svc.version == v2
        # the staged generation primed from the new version's bundle
        assert _prime_count("artifact") == a0 + len(BUCKETS)
        assert svc._pool.has_artifacts
    finally:
        svc.close()


def test_supervisor_heal_consumes_artifacts(registry):
    """The heal path's compile-count pin: a replacement replica primes
    every bucket from the artifact tier — zero compile/cache-tier
    primes during recovery (compile time must not be recovery time)."""
    reg, version = registry
    fitted, v = reg.load()
    arts = reg.load_artifacts(v)
    svc = serve(
        fitted,
        max_batch=BUCKETS[-1],
        buckets=BUCKETS,
        example=_example(),
        name="heal_art",
        replicas=2,
        supervise=True,
        supervise_interval_s=0.05,
        artifacts=arts,
    )
    import time

    x = np.random.default_rng(8).normal(size=(DIM,)).astype(np.float32)
    try:
        for _ in range(3):
            svc.submit(x).result(timeout=30)
        a0 = _prime_count("artifact")
        c0 = _prime_count("compile")
        k0 = _prime_count("cache")
        with faults.inject("serve.worker:ctx.replica=0:raise:times=1"):
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                try:
                    svc.submit(x).result(timeout=10)
                except Exception:
                    pass
                if svc.supervisor.restarts_total >= 1:
                    break
                time.sleep(0.01)
        assert svc.supervisor.restarts_total >= 1
        # the replacement primed from artifacts, and ONLY from artifacts
        assert _prime_count("artifact") == a0 + len(BUCKETS)
        assert _prime_count("compile") == c0
        assert _prime_count("cache") == k0
        assert np.all(
            np.isfinite(np.asarray(svc.submit(x).result(timeout=30)))
        )
    finally:
        svc.close()


# ----------------------------------------------------------- inert path


def test_no_artifacts_is_inert(exported):
    """Without a bundle the applier holds zero programs and the call
    path is the pre-artifact walk (one empty-dict check)."""
    pipe, _frozen, _bundle = exported
    ap = pipe.freeze()
    assert ap.installed_buckets() == 0
    x = np.ones((BUCKETS[0], DIM), np.float32)
    assert np.asarray(ap(_ds(x)).array).shape == (BUCKETS[0], CLASSES)
    assert ap.installed_buckets() == 0


def test_solver_hlo_identical_with_artifacts_installed(exported):
    """Exporting/installing artifacts must not perturb traced solver
    programs — the machinery lives entirely outside solver jit."""
    import jax

    from keystone_tpu.models.block_ls import _bcd_epoch_body

    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 16, 8)), jnp.float32
    )
    y = jnp.ones((16, 2), jnp.float32)
    w = jnp.zeros((2, 8, 2), jnp.float32)
    p = jnp.zeros((16, 2), jnp.float32)

    def step(xb, yb, wb, pb):
        return _bcd_epoch_body(xb, yb, jnp.float32(16.0), 1e-3, (wb, pb))

    plain = jax.jit(step).lower(x, y, w, p).as_text()
    pipe, _frozen, bundle = exported
    ap = pipe.freeze()
    ap.install_artifacts(bundle)
    np.asarray(ap(np.ones((BUCKETS[0], DIM), np.float32)).array)
    after = jax.jit(step).lower(x, y, w, p).as_text()
    assert plain == after


def test_pickled_applier_drops_programs(exported):
    """Jitted bucket programs are process-local: a pickled applier
    round-trips WITHOUT them (and without error) — clones re-install
    from the bundle via the pool."""
    import pickle

    pipe, _frozen, bundle = exported
    ap = pipe.freeze()
    ap.install_artifacts(bundle)
    clone = pickle.loads(pickle.dumps(ap))
    assert clone.installed_buckets() == 0
    # and the clone can re-install (its fingerprint survives the trip)
    assert clone.install_artifacts(bundle) == len(BUCKETS)


# ------------------------------------------------------------------ CLI


def test_cli_export_writes_bundle_dir(tmp_path, exported):
    """``keystone export --model ... --out DIR`` writes a loadable
    manifest + checksummed blobs."""
    pipe, _frozen, _bundle = exported
    model = str(tmp_path / "model.pkl")
    pipe.save(model)
    out_dir = str(tmp_path / "bundle")
    from keystone_tpu import cli

    rc = cli.main(
        [
            "export",
            "--model",
            model,
            "--example-shape",
            str(DIM),
            "--buckets",
            ",".join(str(b) for b in BUCKETS),
            "--out",
            out_dir,
        ]
    )
    assert rc == 0
    man = json.loads(open(os.path.join(out_dir, "MANIFEST.json")).read())
    assert man["buckets"] == list(BUCKETS)
    for ent in man["entries"].values():
        blob = os.path.join(out_dir, ent["file"])
        assert os.path.exists(blob)
        assert os.path.exists(blob + ".b2")  # durable sidecar


def test_cli_export_publishes_registry_version(tmp_path, exported):
    pipe, _frozen, _bundle = exported
    model = str(tmp_path / "model.pkl")
    pipe.save(model)
    root = str(tmp_path / "reg")
    from keystone_tpu import cli

    rc = cli.main(
        [
            "export",
            "--model",
            model,
            "--model-dir",
            root,
            "--example-shape",
            str(DIM),
            "--buckets",
            ",".join(str(b) for b in BUCKETS),
        ]
    )
    assert rc == 0
    reg = ModelRegistry(root)
    fitted, version = reg.load()
    arts = reg.load_artifacts(version)
    # one blob per bucket (an export into a cold persistent cache ships the
    # compile-cache entries it minted as `cache*` blobs beside them)
    assert arts is not None
    assert len([k for k in arts["blobs"] if not k.startswith("cache")]) == len(BUCKETS)
    # the published pair actually serves from the artifact tier
    ap = fitted.freeze()
    assert ap.install_artifacts(arts) == len(BUCKETS)


# ------------------------------------------- pre-seeded compile cache tier
def _point_cache_at(path) -> str:
    """Place jax's persistent cache at ``path`` for one test.  jax binds
    ONE cache object to the first directory it initialises, so a change
    of directory needs a reset; the program itself never moves the cache
    (utils/compile_cache.py), so the test does."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from keystone_tpu.utils.compile_cache import enable_compilation_cache

    jax.config.update("jax_compilation_cache_dir", None if path is None else str(path))
    cc.reset_cache()
    if path is not None:
        os.makedirs(str(path), exist_ok=True)
        assert enable_compilation_cache() == str(path)
    return path


def test_export_captures_and_seeds_compile_cache(tmp_path, monkeypatch):
    """With a persistent compile cache active, export_artifacts ships
    the backend-compile cache entries alongside the bucket programs;
    seed_compile_cache installs them byte-identically on a fresh host's
    cache dir — the ladder's last cold rung."""
    import jax

    from keystone_tpu.utils.compile_cache import seed_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        _point_cache_at(tmp_path / "xla-cache")
        # a UNIQUE pipeline (fresh weights → fresh HLO): a program this
        # process already compiled hits jax's in-memory cache and never
        # touches the on-disk cache, so capture finds nothing to ship
        bundle = _pipeline(seed=41).freeze().export_artifacts(
            example=_example(), buckets=BUCKETS
        )
        cache_ents = {
            k: e
            for k, e in bundle["manifest"]["entries"].items()
            if e.get("kind") == "compile_cache"
        }
        assert cache_ents, "active cache during export must capture entries"
        for k, e in cache_ents.items():
            assert e["file"].startswith("cache")
            assert bundle["blobs"][k]
        shipped = {e["name"]: bundle["blobs"][k] for k, e in cache_ents.items()}

        # a "fresh host": empty cache dir — seeding installs the files
        fresh = str(_point_cache_at(tmp_path / "fresh-cache"))
        n = seed_compile_cache(bundle)
        assert n == len(cache_ents)
        for name, data in shipped.items():
            with open(os.path.join(fresh, name), "rb") as f:
                assert f.read() == data
        # idempotent: a second seed never clobbers (and writes nothing)
        assert seed_compile_cache(bundle) == 0
    finally:
        _point_cache_at(prev)


def test_cache_entries_never_register_as_bucket_programs(tmp_path):
    """install_artifacts skips compile-cache entries: only row-keyed
    bucket programs register, and the bundle stays install-compatible
    with pre-seed readers (rows entries unchanged)."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    try:
        _point_cache_at(tmp_path / "xla-cache")
        frozen = _pipeline().freeze()
        bundle = frozen.export_artifacts(example=_example(), buckets=BUCKETS)
    finally:
        _point_cache_at(prev)
    target = _pipeline().freeze()
    # identical pipeline params → identical signature; install succeeds
    n = target.install_artifacts(
        bundle, signature=bundle["manifest"]["signature"]
    )
    assert n == len(BUCKETS)
    assert target.installed_buckets() == len(BUCKETS)


def test_registry_roundtrips_cache_entries(tmp_path):
    """Cache entries ride the registry's durable artifact layout like
    any other blob (checksummed, corrupt-tolerant)."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    try:
        _point_cache_at(tmp_path / "xla-cache")
        pipe = _pipeline(seed=42)  # unique HLO: see the capture test
        bundle = pipe.freeze().export_artifacts(
            example=_example(), buckets=BUCKETS
        )
    finally:
        _point_cache_at(prev)
    n_cache = sum(
        1
        for e in bundle["manifest"]["entries"].values()
        if e.get("kind") == "compile_cache"
    )
    assert n_cache >= 1
    reg = ModelRegistry(str(tmp_path / "registry"))
    v = reg.publish(pipe, artifacts=bundle)
    loaded = reg.load_artifacts(v)
    got_cache = {
        k: e
        for k, e in loaded["manifest"]["entries"].items()
        if e.get("kind") == "compile_cache"
    }
    assert len(got_cache) == n_cache
    for k in got_cache:
        assert loaded["blobs"][k] == bundle["blobs"][k]


def test_export_without_cache_ships_no_cache_entries(monkeypatch):
    """No active persistent cache → the bundle simply has no cache
    rung (and nothing fails)."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        bundle = _pipeline().freeze().export_artifacts(
            example=_example(), buckets=BUCKETS
        )
    finally:
        _point_cache_at(prev)
    assert not any(
        e.get("kind") == "compile_cache"
        for e in bundle["manifest"]["entries"].values()
    )
