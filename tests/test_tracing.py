"""Tracing/profiling helpers + CLI end-to-end.

Reference analogues: the Logging-trait stage timings and Spark event-log
timeline (SURVEY.md §5); bin/run-pipeline.sh CLI entry.
"""

import glob
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np

from keystone_tpu.ops import LinearRectifier, RandomSignNode
from keystone_tpu.utils import tracing
from keystone_tpu.utils.test_utils import gen_image, gen_image_batch, load_test_image
from keystone_tpu.workflow import Dataset, Pipeline


def _toy_result():
    data = Dataset(np.random.default_rng(0).normal(size=(32, 16)).astype(np.float32))
    pipe = Pipeline.of(RandomSignNode.init(16, seed=0)).and_then(LinearRectifier(0.0))
    return pipe(data)


def test_stage_timings_labels_every_node():
    timings = tracing.stage_timings(_toy_result())
    assert timings, "no stages timed"
    labels = " ".join(timings)
    assert "RandomSignNode" in labels
    assert "LinearRectifier" in labels
    assert all(t >= 0 for t in timings.values())


def test_stage_timings_synchronizes_fit_nodes():
    """A fit node's solve must be charged to the fit node itself, not
    dispatched async and absorbed by the next dataset-producing node."""
    from keystone_tpu.models import LinearMapEstimator
    from keystone_tpu.ops import ClassLabelIndicators

    rng = np.random.default_rng(0)
    x = Dataset(rng.normal(size=(512, 128)).astype(np.float32))
    y = ClassLabelIndicators(4)(
        Dataset(rng.integers(0, 4, size=(512,)).astype(np.int32))
    )
    pipe = Pipeline.of(LinearRectifier(0.0)).and_then(
        LinearMapEstimator(lam=1e-2), x, y
    )
    result = pipe(x)
    timings = tracing.stage_timings(result)
    # NodeChoiceRule may legitimately swap the small problem to the
    # local solve (r3); either physical form must appear in the timings
    fit_keys = [k for k in timings if "LeastSquares" in k or "LinearMap" in k]
    assert fit_keys, f"fit node missing from timings: {list(timings)}"
    assert timings[fit_keys[0]] >= 0


def test_trace_context_writes_profile(tmp_path):
    logdir = str(tmp_path / "trace")
    with tracing.trace(logdir, annotation="toy-pipeline"):
        with tracing.step_annotation(0):
            _toy_result().get()
    produced = glob.glob(os.path.join(logdir, "**", "*"), recursive=True)
    assert any(os.path.isfile(p) for p in produced), "no trace artifacts written"


def test_gen_image_deterministic_and_shaped():
    a = gen_image(8, 10, 3, seed=7)
    b = gen_image(8, 10, 3, seed=7)
    assert a.metadata.shape == (8, 10, 3)
    np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data))
    batch = gen_image_batch(5, 8, 8, 1, seed=3)
    assert batch.shape == (5, 8, 8, 1)


def test_load_test_image_variants():
    for name in ("gradient", "checkerboard", "blobs"):
        img = load_test_image(name, size=16)
        assert img.metadata.shape == (16, 16, 3)
        arr = np.asarray(img.data)
        assert np.isfinite(arr).all()
        assert arr.std() > 0  # known non-trivial content
    # gradient channel 0 ramps along x
    g = np.asarray(load_test_image("gradient", size=16).data)
    assert (np.diff(g[:, 0, 0]) > 0).all()


def test_cli_runs_mnist_end_to_end():
    """python -m keystone_tpu.cli MnistRandomFFT … on a tiny synthetic set
    (the bin/run-pipeline.sh path, minus the shell wrapper)."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "keystone_tpu.cli",
            "MnistRandomFFT",
            "--synthetic-n",
            "256",
            "--num-ffts",
            "2",
        ],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "accuracy" in proc.stdout
