"""Guards the headline benchmark program (bench.py).

bench.py only executes on the real chip at round end; this smoke test
compiles and runs the exact same forward on the CPU mesh so a regression
in any stage (SIFT → PCA → FV → normalize → block-linear) is caught by
the suite, not by the driver.
"""

import sys
import os

import jax.numpy as jnp
import numpy as np
import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def test_bench_forward_compiles_and_is_finite():
    fwd = jax.jit(bench.build_forward())
    imgs = jnp.asarray(
        np.random.default_rng(0).uniform(0, 1, (4, bench.IMAGE_HW, bench.IMAGE_HW, 3)),
        jnp.float32,
    )
    out = fwd(imgs)
    assert out.shape == (4, bench.NUM_CLASSES)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_bench_forward_batch_invariance():
    # per-image results must not depend on batch packing (pure map semantics,
    # the reference's Transformer.apply(RDD) contract)
    fwd = jax.jit(bench.build_forward())
    imgs = jnp.asarray(
        np.random.default_rng(1).uniform(0, 1, (6, bench.IMAGE_HW, bench.IMAGE_HW, 3)),
        jnp.float32,
    )
    full = fwd(imgs)
    half = fwd(imgs[:3])
    np.testing.assert_allclose(np.asarray(full[:3]), np.asarray(half), rtol=2e-4, atol=2e-4)


def test_measure_ips_runs_on_cpu():
    ips = bench.measure_ips(batch=2, run_lengths=(1, 2, 3), reps=1, warmup=1)
    assert ips > 0


def test_bench_multiscale_forward_compiles():
    """The multi-scale leg's forward (vl_phow bins + smoothing) must
    compile and stay finite — it is a first-class bench metric since r4."""
    fwd = jax.jit(
        bench.build_forward(
            bin_sizes=bench.MS_BIN_SIZES, smoothing_magnif=bench.MS_SMOOTHING
        )
    )
    imgs = jnp.asarray(
        np.random.default_rng(2).uniform(
            0, 1, (2, bench.IMAGE_HW, bench.IMAGE_HW, 3)
        ),
        jnp.float32,
    )
    out = fwd(imgs)
    assert out.shape == (2, bench.NUM_CLASSES)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_solver_flops_matches_hand_count():
    """2·MACs accounting for the weighted-BCD solve: Gramian + target
    products over blocks x epochs."""
    n, d, k, bs, e = 64, 96, 4, 32, 2
    nb = 3
    want = e * (2 * n * bs * bs * nb + 6 * n * bs * k * nb)
    assert bench.solver_flops(n, d, k, bs, e) == want
    # ragged tail: d=80 → blocks (32, 32, 16); the last block must be
    # charged its TRUE width, not bs (the docstring's honesty guard)
    n, d = 64, 80
    want = e * sum(2 * n * w * w + 6 * n * w * k for w in (32, 32, 16))
    assert bench.solver_flops(n, d, k, bs, e) == want


def test_kernel_flops_matches_hand_count():
    """2·MACs accounting for the blockwise KRR sweep: kernel column
    gemm + F update + block target + Cholesky, over blocks × epochs."""
    n, d, k, bs, e = 96, 12, 4, 32, 2
    nb = 3
    want = e * nb * (
        2 * n * bs * d + 2 * n * bs * k + 2 * bs * bs * k + bs**3 / 3
    )
    assert bench.kernel_flops(n, d, k, bs, e) == want


def test_measure_kernel_at_scale_runs_on_cpu(monkeypatch):
    """The kernel_at_scale leg (scaled down) on CPU: both sweeps run,
    the A/B r² gate holds, and the OC dataflow accounts are populated
    (the acceptance fields)."""
    monkeypatch.setattr(bench, "KERNEL_N", 160)
    monkeypatch.setattr(bench, "KERNEL_D", 16)
    monkeypatch.setattr(bench, "KERNEL_K", 3)
    monkeypatch.setattr(bench, "KERNEL_BLOCK", 32)
    monkeypatch.setattr(bench, "KERNEL_EPOCHS", 2)
    monkeypatch.setattr(bench, "KERNEL_GAMMA", 0.02)
    out = bench.measure_kernel_at_scale()
    assert out["kernel_tflops"] > 0 and out["oc_kernel_tflops"] > 0
    assert out["oc_vs_incore_r2"] >= 0.999
    assert out["transfer_seconds"] > 0
    assert out["device_busy_fraction"] is not None
    assert out["oc_store_bytes"] > 0 and out["oc_over_resident_x"] > 0


def test_measure_solver_runs_on_cpu(monkeypatch):
    """The solver-phase leg runs (scaled down) on the CPU mesh and
    reports positive TFLOP/s."""
    monkeypatch.setattr(bench, "FIT_N", 64)
    monkeypatch.setattr(bench, "FIT_CLASSES", 4)
    monkeypatch.setattr(bench, "FIT_GMM_K", 4)
    monkeypatch.setattr(bench, "FIT_SOLVER_BLOCK", 64)
    out = bench.measure_solver()
    assert out["solver_tflops"] > 0
    assert out["solver_seconds"] > 0


def test_flops_accounting_tracks_real_descriptor_count():
    """MFU honesty guard: the analytic FLOP count must use the actual
    SIFT grid size (a hand-derived T once overcounted it by ~4%), and
    the FV term must dominate as documented."""
    from keystone_tpu.ops.sift import sift_output_count

    t = sift_output_count(bench.IMAGE_HW, bench.IMAGE_HW, bench.SIFT_STEP, (4,))
    total = bench.flops_per_image()
    fv = 4 * 2 * t * bench.PCA_DIMS * bench.GMM_K
    assert fv < total < 3 * fv


def test_orchestrator_exits_nonzero_on_a_failed_leg_and_stays_off_jax(tmp_path):
    """``python bench.py`` (no flags) only orchestrates: every leg is a
    child that owns the chip in turn, so the parent must never import
    jax, a failed leg must make the run exit non-zero (it used to print
    a headline from one sample and exit 0), and the result names the
    device.  Children are faked; the parent runs for real in a fresh
    interpreter (this one has jax loaded)."""
    import json
    import subprocess

    code = f"""
import json, subprocess, sys
import bench
for name in ("SCALE_LEGS KERNEL_LEGS SERVE_LEGS FLEET_LEGS HEDGE_LEGS ARTIFACT_LEGS "
             "TENANT_LEGS PROC_LEGS INGRESS_LEGS PLAN_LEGS PRECISION_LEGS").split():
    setattr(bench, name, 0)
bench.N_LEGS = 1
bench._BASELINE_CACHE = {str(tmp_path / "cpu.json")!r}
DEVICE = {{"device": {{"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}}}}
RESULTS = {{
    "--leg": {{"leg_ips": 100.0, "flops_per_image": 1e9, "f32_peak": 4.9e13}},
    "--leg-ms": {{"leg_ips": 50.0}},
    "--cpu": {{"cpu_ips": 2.0}},
}}
def fake_run(argv, **kw):
    flag = argv[2]
    if flag not in RESULTS:  # --leg-fit: the leg that dies
        return subprocess.CompletedProcess(argv, 1, "", "boom")
    out = json.dumps(DEVICE) + "\\n" + json.dumps(RESULTS[flag]) + "\\n"
    return subprocess.CompletedProcess(argv, 0, out, "")
bench.subprocess.run = fake_run
sys.argv = ["bench.py"]
rc = bench.main()
assert "jax" not in sys.modules, "the orchestrating parent imported jax"
sys.exit(rc)
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert "--leg-fit" in proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["failed_legs"] == ["--leg-fit"]
    assert out["device"] == {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1
    }
    assert out["value"] == 100.0 and out["multiscale"]["images_per_sec"] == 50.0


def test_measuring_leg_refuses_without_a_tpu():
    """A leg on a machine with no TPU refuses (exit 2, nothing printed)
    unless ``--cpu`` says the CPU is meant."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "bench.py", "--leg-ms"], cwd=root, capture_output=True,
        text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 2 and proc.stdout == ""
    assert "needs a TPU" in proc.stderr


def test_cpu_leg_pins_its_descendants_to_the_cpu():
    """A ``--cpu`` leg starts children of its own (process workers, the
    per-arm A/B subprocesses of serve_bench).  The pin must ride the
    ENVIRONMENT so that they inherit it: a ``jax.config`` setting held
    only the leg's own process, and on a TPU host its children then went
    for the one chip.  The leg's body is faked by a child that reports
    what it inherited; the leg runs in a fresh interpreter that starts
    with no ``JAX_PLATFORMS`` at all."""
    import json
    import subprocess

    code = """
import json, os, subprocess, sys
import bench
def fake_leg(args):
    child = subprocess.run(
        [sys.executable, "-c", "import os; print(os.environ.get('JAX_PLATFORMS'))"],
        capture_output=True, text=True)
    import jax
    print(json.dumps({"child": child.stdout.strip(), "backend": jax.default_backend()}))
bench.run_leg = fake_leg
sys.argv = ["bench.py", "--leg-serve-procs", "--cpu"]
sys.exit(bench.main())
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    first, last = (json.loads(ln) for ln in proc.stdout.strip().splitlines())
    assert first["device"]["platform"] == "cpu"
    assert last == {"child": "cpu", "backend": "cpu"}
