"""Worker for the fault-injection test (test_faulttol.py).

Two Gloo-connected processes fit a BlockLeastSquares solver with
per-epoch checkpointing.  In "crash" mode, process 1 calls ``os._exit``
before launching its 4th epoch sweep — mid-fit, between collectives —
simulating a host failure.  In "resume" mode the workers relaunch with
the same checkpoint dir, must resume from the last completed epoch
(asserted: the checkpoint exists and its epoch > 0), finish the fit,
and print a digest of the final weights.  The parent test compares the
resumed digest against an uninterrupted control run's digest — recovery
must land on EXACTLY the same model.
"""

import hashlib
import os
import sys


def main() -> None:
    coordinator, num_procs, pid, mode, ckpt_dir = (
        sys.argv[1],
        int(sys.argv[2]),
        int(sys.argv[3]),
        sys.argv[4],  # crash | resume | control
        sys.argv[5],
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from keystone_tpu.parallel import multihost, set_mesh

    multihost.initialize(
        coordinator_address=coordinator, num_processes=num_procs, process_id=pid
    )
    mesh = multihost.hybrid_mesh(model_parallelism=1)
    set_mesh(mesh)

    import numpy as np

    import keystone_tpu.models.block_ls as bls

    if mode.startswith("sparse-"):
        _sparse_lbfgs_leg(mode.split("-", 1)[1], ckpt_dir, pid)
        return

    rng = np.random.default_rng(0)
    n, d, k = 256, 48, 3
    x = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=(d, k)).astype(np.float32)
    y = (x @ w_true + 0.01 * rng.normal(size=(n, k))).astype(np.float32)

    sl = multihost.process_batch_slice(n)
    data = multihost.make_global_dataset(x[sl], global_n=n)
    labels = multihost.make_global_dataset(y[sl], global_n=n)

    crash_after = 3  # completed epoch sweeps before the injected death
    if mode == "crash" and pid == 1:
        orig = bls._bcd_epoch
        calls = {"n": 0}

        def crashing(*args):
            if calls["n"] >= crash_after:
                sys.stderr.write("FAULT: injected crash before epoch %d\n" % calls["n"])
                sys.stderr.flush()
                os._exit(42)
            calls["n"] += 1
            return orig(*args)

        bls._bcd_epoch = crashing

    ckpt_path = os.path.join(ckpt_dir, "bcd_epoch.npz")
    if mode == "resume":
        # recovery must actually RESUME: the crash run left epochs 0..2
        assert os.path.exists(ckpt_path), "no checkpoint survived the crash"
        with np.load(ckpt_path) as z:
            resumed_epoch = int(z["epoch"])
        assert resumed_epoch >= 1, resumed_epoch
        print(f"RESUMED_FROM {resumed_epoch}", flush=True)

    est = bls.BlockLeastSquaresEstimator(
        block_size=16, num_iter=6, lam=1e-3, fit_intercept=False
    )
    model = est.fit_checkpointed(data, labels, checkpoint_dir=ckpt_dir)

    w = np.asarray(model.flat_weights, np.float64)
    digest = hashlib.sha256(np.round(w, 4).tobytes()).hexdigest()[:16]
    err = np.abs(w[:d] - np.linalg.solve(
        x.astype(np.float64).T @ x + 1e-3 * n * np.eye(d),
        x.astype(np.float64).T @ y,
    )).max()
    print(f"FAULTTOL_OK pid={pid} mode={mode} digest={digest} err={err:.2e}", flush=True)


def _sparse_lbfgs_leg(submode: str, ckpt_dir: str, pid: int) -> None:
    """Sparse L-BFGS mid-fit kill/resume at vocab scale (round-3 review
    weak-3: the L-BFGS family previously had NO mid-fit checkpoint —
    the reference's Amazon-scale text fits are hours of work).  Both
    Gloo processes fit the same bucketed 20k-vocab problem through
    SparseLBFGSwithL2.fit_checkpointed; in "crash" submode process 1
    dies after the first carry save, mid-chunk-loop, between
    collectives."""
    import hashlib

    import numpy as np
    import scipy.sparse as sparse

    import keystone_tpu.models.lbfgs as lb
    from keystone_tpu.workflow import Dataset

    rng = np.random.default_rng(0)
    n, d, k, nnz = 128, 20_000, 3, 8
    rows = []
    for _ in range(n):
        idx = rng.choice(d, size=nnz, replace=False)
        rows.append(
            sparse.csr_matrix(
                (rng.normal(size=nnz).astype(np.float32), (np.zeros(nnz), idx)),
                shape=(1, d),
            )
        )
    y = rng.normal(size=(n, k)).astype(np.float32)

    if submode == "crash" and pid == 1:
        orig = lb._lbfgs_checkpoint_callbacks

        def crashing_callbacks(*a, **kw):
            load_cb, save_cb = orig(*a, **kw)

            def save(it, carry):
                save_cb(it, carry)
                if it >= 4:
                    sys.stderr.write(
                        "FAULT: injected crash after carry save at it=%d\n" % it
                    )
                    sys.stderr.flush()
                    os._exit(42)

            return load_cb, save

        lb._lbfgs_checkpoint_callbacks = crashing_callbacks

    ckpt_path = os.path.join(ckpt_dir, "lbfgs_sparse.npz")
    if submode == "resume":
        assert os.path.exists(ckpt_path), "no L-BFGS carry survived the crash"
        with np.load(ckpt_path) as z:
            resumed_it = int(z["it"])
        assert resumed_it >= 4, resumed_it
        print(f"RESUMED_FROM {resumed_it}", flush=True)

    est = lb.SparseLBFGSwithL2(lam=1e-2, num_iterations=12, history=4)
    model = est.fit_checkpointed(
        Dataset(rows),
        Dataset(y, shard=False),
        checkpoint_dir=ckpt_dir,
        checkpoint_every=4,
    )
    w = np.asarray(model.weights, np.float64)
    digest = hashlib.sha256(np.round(w, 4).tobytes()).hexdigest()[:16]
    print(
        f"FAULTTOL_OK pid={pid} mode=sparse-{submode} digest={digest}",
        flush=True,
    )


if __name__ == "__main__":
    main()
