"""CPU rehearsal of chip_smoke.py: toy sizes, kernels in interpret mode.

The chip run itself happens on the TPU (``python chip_smoke.py``); these
tests hold the script's control flow — every phase runs, a failed phase
is fatal, and the success line cannot be produced off a TPU.  Each run is
a child process with ONE (or four) plain CPU devices, not the suite's
8-device mesh.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

ONE_CHIP_PHASES = [
    "sync_probe", "eager_fft", "fit", "score", "score_xla_reference",
    "score_f32_streams", "kernel_proof", "save_load", "serve", "gram_block",
    "krr_fit", "native",
]
FOUR_CHIP_PHASES = [
    "bcd_one_device_data", "bcd_one_device_fit", "bcd_four_devices_data",
    "bcd_four_devices_fit", "bcd_collectives", "bcd_compare",
]


def _run(argv, devices=1, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    proc = subprocess.run(
        [sys.executable, *argv], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=timeout,
    )
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return proc, lines


def _no_success_line(lines):
    return not any(ln.get("ok") is True and "phase" not in ln for ln in lines)


def test_rehearsal_runs_every_phase():
    proc, lines = _run(["chip_smoke.py", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert phases["summary"]["phases_passed"] == ONE_CHIP_PHASES
    assert all(phases[p]["ok"] for p in ONE_CHIP_PHASES)
    assert phases["serve"]["request_batches"] == [1, 5, 8, 11]
    assert phases["serve"]["buckets"] == [8]  # 5 and 11 are padded / split
    assert phases["fit"]["branches"] == 2
    assert lines[-1] == {"rehearsal": "passed", "device": phases["start"]["device"]}
    assert _no_success_line(lines)


def test_rehearsal_four_chips_runs_only_the_sharded_fit():
    proc, lines = _run(["chip_smoke.py", "--rehearse", "--chips", "4"], devices=4)
    assert proc.returncode == 0, proc.stderr[-2000:]
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert phases["summary"]["phases_passed"] == FOUR_CHIP_PHASES
    n, d = chip_smoke.TOY["bcd"]["n"], chip_smoke.TOY["bcd"]["d"]
    shards = phases["bcd_four_devices_data"]["shards"]
    assert sorted(dev for dev, _ in shards) == [0, 1, 2, 3]
    assert all(shape == [n // 4, d] for _, shape in shards)
    colls = phases["bcd_collectives"]["collectives"]
    assert colls and all(op == "all-reduce" for op, _ in colls)
    assert lines[-1]["device"]["count"] == 4 and _no_success_line(lines)


def test_failed_phase_is_fatal():
    code = (
        "import sys, chip_smoke as cs\n"
        "def boom(*a, **k): raise RuntimeError('injected failure')\n"
        "cs.phase_eager_fft = boom\n"
        "sys.exit(cs.main(['--rehearse']))\n"
    )
    proc, lines = _run(["-c", code])
    assert proc.returncode != 0
    assert "injected failure" in proc.stderr
    assert [ln["phase"] for ln in lines if "phase" in ln] == ["start", "sync_probe"]
    assert _no_success_line(lines) and "rehearsal" not in lines[-1]


def test_refuses_to_run_off_a_tpu():
    proc, lines = _run(["chip_smoke.py"])
    assert proc.returncode == 2 and lines == []
    assert "needs a TPU" in proc.stderr


def test_success_line_cannot_be_made_off_a_tpu():
    with pytest.raises(RuntimeError, match="refusing"):
        chip_smoke.success_line({"platform": "cpu", "kind": "cpu", "count": 1})
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert chip_smoke.success_line(tpu) == (
        '{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}'
    )


def test_the_driver_command_takes_no_size_option():
    """``n`` and the epoch count are constants (``FULL``): no option can
    shrink the fit under the success line."""
    for argv in (["--n", "8"], ["--epochs", "1"]):
        with pytest.raises(SystemExit) as e:
            chip_smoke.main(argv)
        assert e.value.code == 2
    assert chip_smoke.FULL["n"] == 2048 and chip_smoke.FULL["epochs"] == 2


@pytest.mark.parametrize(
    "seen,ok",
    [
        # (backend seconds, requests, hits, hit names, miss names)
        ((1.5, 3, 2, ["a", "b"], ["c"]), True),
        ((0.0, 0, 0, [], []), True),  # a phase that compiled nothing
        ((1.5, 0, 0, [], []), False),  # compiled, but the cache events are gone
        ((1.5, 3, 2, [], []), False),  # events counted, the log lines reworded
        ((1.5, 3, 2, ["a", "b"], []), False),  # a miss went unnamed
    ],
)
def test_cache_accounting_checks_itself(seen, ok):
    """Hits and misses are counted from jax's monitoring events and named
    from its compiler's log lines; if either source changes under the
    script, the phase fails instead of reading "nothing compiled"."""
    log = chip_smoke._CompileLog()
    log.cache_on = True
    log.backend_seconds, log.requests, log.hits, log.hit_names, log.miss_names = seen
    if ok:
        log.verify()
    else:
        with pytest.raises(RuntimeError, match="chip_smoke check failed"):
            log.verify()
    log.cache_on = False  # KEYSTONE_COMPILE_CACHE=off: nothing to hold it to
    log.verify()
