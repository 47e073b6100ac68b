"""``correct`` of the cell ``timit-krr.fit`` at a size that a test run can
hold, judged by the cell's OWN limits: its rehearsal is correct and compares
the first block's dual coefficients beside the held-out scores; the control
its traffic file names (the reference with the distance gemm below
``highest``, put in the program's place: ``gram_bf16`` on the CPU, where
``high`` and ``highest`` are the same product) and ``faulty_run.py``'s
``half_batch`` and ``answer_altered`` come out as NOT correct; and the
adapter's graph is the entry's own ``build_scorer``."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "timit-krr.fit"

sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def run(script, *args):
    env = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", script), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return done


def limits():
    return harness.find_cell(CELL)[1]["limits"]


def test_rehearsal_is_correct_and_compares_the_dual_coefficients():
    done = run("run.py", "--workload", CELL, "--seed", "3000000023", "--seconds", "0.3",
               "--trace", "1", "--rehearse")
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert set(line["checks"]) == set(limits()) | {"compiles_in_window"}
    assert "alpha0_relative_error" in line["checks"]
    assert line["window"]["unit"] == "fits"


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_a_broken_timed_path_is_not_correct(fault):
    done = run("tests/faulty_run.py", "--fault", fault, "--workload", CELL)
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, (fault, line["checks"])
    over = {n for n, c in line["checks"].items() if c["value"] > c["limit"]}
    # half of the rows change every coefficient; an altered answer changes none
    assert ("alpha0_relative_error" in over) == (fault == "half_batch"), over


def test_the_control_fails_by_the_cells_own_limit_on_the_coefficients():
    spec = harness.find_cell(CELL)[1]
    assert spec["control"] == "gram_high"
    done = run("tests/chip_limits.py", "--workload", CELL, "--seeds", "11,12", "--seconds", "0.2",
               "--controls", "gram_bf16", "--rehearse")
    lines = [json.loads(ln) for ln in done.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 2
    for line in lines:
        program, control = line["program"], line["control"]["gram_bf16"]
        assert all(program[name] <= limit for name, limit in spec["limits"].items()), program
        assert control["alpha0_relative_error"] > 3 * spec["limits"]["alpha0_relative_error"]


def test_the_adapter_calls_the_entrys_own_build_scorer(monkeypatch):
    _, cell, cfg = harness.find_cell(CELL)
    adapter = harness.load_module("adapters", cfg["adapter"])
    calls = []
    entry = adapter.entry()
    monkeypatch.setattr(entry, "build_scorer",
                        staticmethod(lambda conf, x, labels: calls.append(conf) or "pipeline"))
    assert adapter.build(cfg, cell, 7, "frames", "labels") == "pipeline"
    (conf,) = calls
    assert (conf.gamma, conf.lam, conf.block_size, conf.num_epochs, conf.num_classes) == (
        cfg["gamma"], cfg["lam"], cfg["block_size"], cfg["num_epochs"], cfg["num_classes"])
