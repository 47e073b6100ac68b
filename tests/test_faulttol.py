"""Fault injection + recovery (round-1 review item 6; SURVEY §5 "failure
detection / elastic recovery").

The reference inherited fault tolerance from Spark (lineage recompute,
task retry).  The rebuild's decomposition: executor-level stage retry
(GraphExecutor node_retries) + process-restart recovery from durable
state (solver epoch checkpoints, saved pipeline prefixes;
workflow/recovery.py).  The multi-process test here is the real thing:
one of two Gloo-connected processes is killed MID-FIT, both relaunch,
and the fit must resume from the epoch checkpoint and land on exactly
the model an uninterrupted run produces.
"""

import os
import re
import socket
import subprocess
import sys

import numpy as np
import pytest

WORKER = os.path.join(os.path.dirname(__file__), "faulttol_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(mode, ckpt_dir, n_procs=2):
    coordinator = f"127.0.0.1:{_free_port()}"
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=cwd + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    env.pop("JAX_COORDINATOR_ADDRESS", None)
    return [
        subprocess.Popen(
            [sys.executable, WORKER, coordinator, str(n_procs), str(pid),
             mode, ckpt_dir],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
            cwd=cwd,
        )
        for pid in range(n_procs)
    ]


def _drain(procs, timeout=300):
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs


def test_gloo_process_killed_midfit_recovers_from_checkpoint(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    control_ckpt = str(tmp_path / "control-ckpt")
    os.makedirs(ckpt, exist_ok=True)
    os.makedirs(control_ckpt, exist_ok=True)

    # --- control: uninterrupted 2-process fit; record the model digest
    control = _drain(_launch("control", control_ckpt))
    for rc, out, err in control:
        assert rc == 0, f"control worker failed (rc={rc}):\n{err[-2000:]}"
    control_digest = set(
        re.findall(r"digest=(\w+)", "".join(o for _, o, _ in control))
    )
    assert len(control_digest) == 1  # both processes agree

    # --- crash: process 1 dies (os._exit) before its 4th epoch sweep
    procs = _launch("crash", ckpt)
    rc1 = procs[1].wait(timeout=300)
    assert rc1 == 42, f"expected injected crash rc=42, got {rc1}"
    # the survivor is now blocked in (or erroring out of) a collective
    # whose peer is gone — kill it, as a job scheduler would
    try:
        procs[0].wait(timeout=20)
    except subprocess.TimeoutExpired:
        procs[0].kill()
    procs[0].communicate()
    procs[1].communicate()

    # durable state survived: the last COMPLETED epoch's checkpoint
    assert os.path.exists(os.path.join(ckpt, "bcd_epoch.npz"))
    with np.load(os.path.join(ckpt, "bcd_epoch.npz")) as z:
        assert int(z["epoch"]) >= 1

    # --- resume: relaunch BOTH processes (SPMD jobs restart together);
    # the fit must resume from the checkpoint and match the control model
    resumed = _drain(_launch("resume", ckpt))
    for rc, out, err in resumed:
        assert rc == 0, f"resume worker failed (rc={rc}):\n{err[-2000:]}"
    resumed_out = "".join(o for _, o, _ in resumed)
    resumed_from = [int(e) for e in re.findall(r"RESUMED_FROM (\d+)", resumed_out)]
    assert resumed_from and all(e >= 1 for e in resumed_from), resumed_from
    resumed_digest = set(re.findall(r"digest=(\w+)", resumed_out))
    assert resumed_digest == control_digest, (resumed_digest, control_digest)


def test_gloo_process_killed_mid_sparse_lbfgs_resumes(tmp_path):
    """round-3 review weak-3 + next-4: the sparse L-BFGS fit (the vocab-scale
    text solver) killed mid-fit across 2 Gloo processes resumes from the
    persisted optimizer carry and matches the uninterrupted model."""
    ckpt = str(tmp_path / "ckpt")
    control_ckpt = str(tmp_path / "control-ckpt")
    os.makedirs(ckpt, exist_ok=True)
    os.makedirs(control_ckpt, exist_ok=True)

    control = _drain(_launch("sparse-control", control_ckpt))
    for rc, out, err in control:
        assert rc == 0, f"control worker failed (rc={rc}):\n{err[-2000:]}"
    control_digest = set(
        re.findall(r"digest=(\w+)", "".join(o for _, o, _ in control))
    )
    assert len(control_digest) == 1

    procs = _launch("sparse-crash", ckpt)
    rc1 = procs[1].wait(timeout=300)
    assert rc1 == 42, f"expected injected crash rc=42, got {rc1}"
    try:
        procs[0].wait(timeout=20)
    except subprocess.TimeoutExpired:
        procs[0].kill()
    procs[0].communicate()
    procs[1].communicate()

    # the optimizer carry survived (iterate + s/y history + count)
    assert os.path.exists(os.path.join(ckpt, "lbfgs_sparse.npz"))
    with np.load(os.path.join(ckpt, "lbfgs_sparse.npz")) as z:
        assert int(z["it"]) >= 4
        assert z["s_hist"].ndim == 2  # real history buffers persisted

    resumed = _drain(_launch("sparse-resume", ckpt))
    for rc, out, err in resumed:
        assert rc == 0, f"resume worker failed (rc={rc}):\n{err[-2000:]}"
    resumed_out = "".join(o for _, o, _ in resumed)
    resumed_from = [int(e) for e in re.findall(r"RESUMED_FROM (\d+)", resumed_out)]
    assert resumed_from and all(e >= 4 for e in resumed_from), resumed_from
    resumed_digest = set(re.findall(r"digest=(\w+)", resumed_out))
    assert resumed_digest == control_digest, (resumed_digest, control_digest)


def test_executor_stage_retry_recovers_transient_failure():
    """A stage that fails transiently succeeds under node_retries; with
    retries exhausted the error propagates."""
    import jax.numpy as jnp

    from keystone_tpu.workflow import Dataset, GraphExecutor, Pipeline, Transformer

    class Flaky(Transformer):
        fails = 0
        budget = 0

        def params(self):
            return ()

        def apply_batch(self, xs, mask=None):
            if Flaky.fails < Flaky.budget:
                Flaky.fails += 1
                raise RuntimeError("transient device loss")
            return xs + 1.0

        # keep the failure OUTSIDE jit so it happens per execution
        def apply_dataset(self, ds):
            if Flaky.fails < Flaky.budget:
                Flaky.fails += 1
                raise RuntimeError("transient device loss")
            return ds.with_array(ds.array + 1.0)

    Flaky.fails, Flaky.budget = 0, 2
    lazy = Pipeline.of(Flaky())(Dataset(np.ones((4, 2), np.float32)))
    ex = GraphExecutor(lazy.graph, node_retries=2)
    out = ex.execute(lazy.graph.sinks[0])
    np.testing.assert_allclose(np.asarray(out.dataset.array), 2.0)

    Flaky.fails, Flaky.budget = 0, 3
    lazy = Pipeline.of(Flaky())(Dataset(np.ones((4, 2), np.float32)))
    with pytest.raises(RuntimeError, match="transient"):
        GraphExecutor(lazy.graph, node_retries=2).execute(lazy.graph.sinks[0])

    # and the knob is reachable from the NORMAL pipeline path
    from keystone_tpu.workflow.pipeline import PipelineEnv

    prev = PipelineEnv.node_retries
    PipelineEnv.node_retries = 2
    try:
        Flaky.fails, Flaky.budget = 0, 2
        out = Pipeline.of(Flaky())(Dataset(np.ones((4, 2), np.float32))).get()
        np.testing.assert_allclose(np.asarray(out.array), 2.0)
    finally:
        PipelineEnv.node_retries = prev


def test_stage_retries_env_parsing(monkeypatch):
    """KEYSTONE_STAGE_RETRIES is parsed lazily and tolerantly: malformed
    values warn and resolve to 0 instead of crashing imports; post-import
    changes take effect; PipelineEnv.node_retries overrides."""
    from keystone_tpu.workflow.pipeline import PipelineEnv

    monkeypatch.setattr(PipelineEnv, "node_retries", None)
    monkeypatch.setenv("KEYSTONE_STAGE_RETRIES", "3")
    assert PipelineEnv.stage_retries() == 3
    monkeypatch.setenv("KEYSTONE_STAGE_RETRIES", "two")
    assert PipelineEnv.stage_retries() == 0
    monkeypatch.setenv("KEYSTONE_STAGE_RETRIES", "-4")
    assert PipelineEnv.stage_retries() == 0
    monkeypatch.setattr(PipelineEnv, "node_retries", 5)
    assert PipelineEnv.stage_retries() == 5


def test_gather_and_scatter_host_roundtrip_single_process():
    """gather_to_host / global_from_host: the single-process legs (the
    multi-process legs are exercised by the Gloo fault test)."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.parallel import shard_batch
    from keystone_tpu.parallel.multihost import gather_to_host, global_from_host

    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    sharded = shard_batch(x)
    host = gather_to_host(sharded)
    np.testing.assert_allclose(host, x)
    back = global_from_host(host, sharded.sharding)
    assert isinstance(back, jax.Array)
    np.testing.assert_allclose(np.asarray(back), x)


def test_fit_with_recovery_reuses_saved_featurize_prefix(tmp_path):
    """The composed recovery story: an expensive featurize prefix saved
    via save_pipeline_state is RELOADED (not recomputed) by every fit
    attempt under fit_with_recovery — the Spark lineage-reuse analogue."""
    from test_aux import Expensive, expensive_calls

    from keystone_tpu.models import LinearMapEstimator
    from keystone_tpu.workflow import Dataset, Pipeline, fit_with_recovery
    from keystone_tpu.workflow.state import save_pipeline_state

    state_dir = str(tmp_path / "state")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 6)).astype(np.float32)
    y = rng.normal(size=(32, 2)).astype(np.float32)

    featurizer = Pipeline.of(Expensive("prefix"))
    lazy = featurizer(Dataset(x, name="rec-train"))
    Expensive.calls = 0
    save_pipeline_state(lazy, state_dir)
    assert expensive_calls() >= 1  # materialized once to save

    attempt = {"n": 0}

    def build():
        attempt["n"] += 1
        if attempt["n"] == 1:
            raise RuntimeError("injected pre-fit failure")
        return featurizer.and_then(
            LinearMapEstimator(lam=1e-3),
            Dataset(x, name="rec-train"),
            Dataset(y),
        )

    Expensive.calls = 0
    fitted, attempts = fit_with_recovery(build, state_dir=state_dir, max_restarts=2)
    assert attempts == 1
    # the saved prefix replaced the Expensive node before execution AND
    # before the optimizer's sampling passes: zero re-executions
    assert expensive_calls() == 0, expensive_calls()
    pred = fitted(Dataset(x, name="rec-train")).get().numpy()
    assert np.isfinite(pred).all()


def test_fit_with_recovery_restarts_and_resumes(tmp_path):
    """fit_with_recovery: a build_fn whose first attempt dies mid-fit is
    restarted; the solver's epoch checkpoint makes attempt 2 RESUME (the
    checkpoint's epoch advances, and the final model matches an
    uninterrupted fit)."""
    import jax.numpy as jnp

    import keystone_tpu.models.block_ls as bls
    from keystone_tpu.models import BlockLeastSquaresEstimator
    from keystone_tpu.workflow import Dataset, fit_with_recovery

    rng = np.random.default_rng(0)
    n, d, k = 128, 24, 3
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(n, k)).astype(np.float32)
    ckpt = str(tmp_path / "solver-ckpt")

    class CheckpointedBLS(BlockLeastSquaresEstimator):
        """Estimator that routes fit through fit_checkpointed."""

        def fit_dataset(self, data, labels=None):
            return self.fit_checkpointed(data, labels, checkpoint_dir=ckpt)

    est = CheckpointedBLS(block_size=8, num_iter=5, lam=1e-3, fit_intercept=False)
    reference = BlockLeastSquaresEstimator(
        block_size=8, num_iter=5, lam=1e-3, fit_intercept=False
    ).fit_arrays(x, y)

    # crash injection: die after 2 epoch sweeps, once
    state = {"sweeps": 0, "crashed": False}
    orig = bls._bcd_epoch

    def flaky_epoch(*args):
        if state["sweeps"] == 2 and not state["crashed"]:
            state["crashed"] = True
            raise RuntimeError("injected mid-fit failure")
        state["sweeps"] += 1
        return orig(*args)

    bls._bcd_epoch = flaky_epoch
    try:
        fitted, attempts = fit_with_recovery(
            lambda: est.with_data(Dataset(x), Dataset(y)),
            max_restarts=1,
        )
    finally:
        bls._bcd_epoch = orig
    assert attempts == 1  # one failure, one successful restart
    # resumed, not recomputed: 2 sweeps before the crash + 3 after
    assert state["sweeps"] == 5
    got = fitted(Dataset(x)).get().numpy()
    want = np.asarray(reference.apply_batch(jnp.asarray(x)))[:n]
    np.testing.assert_allclose(got, want, atol=1e-5)
