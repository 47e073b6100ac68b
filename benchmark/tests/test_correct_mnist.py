"""``correct`` of the cell ``mnist-fft.fit`` at a size that a test run can
hold, judged by the cell's OWN limits: its rehearsal is correct and compares
the fitted featurizer's features and the first block's weights beside the
held-out scores; the control its traffic file names (the reference with its
DFT product at one bf16 pass and the solver a step down, put in the
program's place) and ``faulty_run.py``'s ``half_batch`` and
``answer_altered`` come out as NOT correct; and the adapter's graph is the
entry's own ``build_scorer``."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "mnist-fft.fit"

sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def run(script, *args):
    env = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", script), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return done


def spec():
    return harness.find_cell(CELL)[1]


def over(checks):
    return {n for n, c in checks.items() if c["value"] > c["limit"]}


def test_rehearsal_is_correct_and_compares_the_parts():
    done = run("run.py", "--workload", CELL, "--seed", "3000000023", "--seconds", "0.3",
               "--trace", "1", "--rehearse")
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert set(line["checks"]) == set(spec()["limits"]) | {"compiles_in_window"}
    assert {"features_rmse_over_std", "w0_relative_error"} <= set(line["checks"])
    assert line["window"]["unit"] == "fits"


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_a_broken_timed_path_is_not_correct(fault):
    done = run("tests/faulty_run.py", "--fault", fault, "--workload", CELL)
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, (fault, line["checks"])
    # half of the rows fit other weights (the signs are the seed's either
    # way, so the features do not move); an altered answer moves the scores
    if fault == "half_batch":
        assert "w0_relative_error" in over(line["checks"]), over(line["checks"])
        assert "features_rmse_over_std" not in over(line["checks"])
    else:
        assert over(line["checks"]) & {"score_max_gap_over_std", "score_rmse_over_std"}
        assert not over(line["checks"]) & {"features_rmse_over_std", "w0_relative_error"}


def test_the_control_is_seen_by_the_cells_own_comparison():
    """On the CPU `high` is `highest`, so the control is read a step lower
    there: the DFT product and the solver both at one bf16 pass."""
    cell = spec()
    assert cell["control"] == "gram_high"
    done = run("tests/chip_limits.py", "--workload", CELL, "--seeds", "11,12",
               "--seconds", "0.2", "--controls", "gram_bf16", "--rehearse")
    lines = [json.loads(ln) for ln in done.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 2
    for line in lines:
        program, got = line["program"], line["control"]["gram_bf16"]
        assert all(program[name] <= limit for name, limit in cell["limits"].items()), program
        for name in ("features_rmse_over_std", "w0_solver_relative_error"):
            assert got[name] > 100 * program[name], (name, program, got)


def test_the_adapter_calls_the_entrys_own_build_scorer(monkeypatch):
    _, cell, cfg = harness.find_cell(CELL)
    adapter = harness.load_module("adapters", cfg["adapter"])
    calls = []
    entry = adapter.entry()
    monkeypatch.setattr(entry, "build_scorer",
                        staticmethod(lambda conf, x, labels: calls.append(conf) or "pipeline"))
    assert adapter.build(cfg, cell, 7, "images", "labels") == "pipeline"
    (conf,) = calls
    assert (conf.num_ffts, conf.block_size, conf.num_iter, conf.lam, conf.seed) == (
        200, 2048, 1, cfg["lam"], 7)
