"""``correct`` at a size that a test run can hold, judged by the CELLS' OWN
limits (the traffic files have no others): each cell's rehearsal is correct
and prints a well-formed last line with no device metric in it; each fault
that a cell can have comes out as NOT correct; and the control that a cell's
traffic file names (the reference a step lower in precision, put in the
program's place) is seen by the comparison.  The limits themselves rest on
readings at the cells' own sizes on the chip, taken by ``chip_limits.py``
through the same comparison (PERF.md, "How correct is decided")."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = {w["name"]: w for w in json.load(_f)["workloads"]}

#: the faults that each kind of cell can have
FAULTS = {
    "timit-rf.fit": ["state_unchanged", "half_batch", "answer_altered"],
    "bwls-fv.solve-4chip": ["state_unchanged", "half_batch", "no_exchange", "answer_altered"],
    "imagenet-fv.score-bulk": ["answer_altered"],
    "imagenet-fv.fit-given-vocab": ["state_unchanged", "half_batch", "answer_altered"],
}


def run(script, *args):
    env = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", script), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return done


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_is_correct_and_prints_no_device_metric(cell, trace):
    done = run("run.py", "--workload", cell, "--seed", "3000000019", "--seconds", "0.3",
               "--trace", trace, "--rehearse")
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu" and "busy_s" not in line["device"]
    assert "breakdown" not in line
    # each number compared is printed beside its limit, last on stderr too
    tail = [ln for ln in done.stderr.strip().splitlines() if ln.startswith("check ")]
    assert len(tail) == len(line["checks"])
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name


@pytest.mark.parametrize("cell,fault", [(c, f) for c in sorted(FAULTS) for f in FAULTS[c]])
def test_a_broken_timed_path_is_not_correct(cell, fault):
    done = run("tests/faulty_run.py", "--fault", fault, "--workload", cell)
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, (fault, line["checks"])
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def cell_file(cell):
    sys.path.insert(0, ROOT)
    from benchmark import harness

    return harness.find_cell(cell)[1]


def control_readings(cell, control):
    done = run("tests/chip_limits.py", "--workload", cell, "--seeds", "11,12,13",
               "--seconds", "0.2", "--controls", control, "--rehearse")
    lines = [json.loads(ln) for ln in done.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 3
    return [(line["program"], line["control"][control]) for line in lines]


@pytest.mark.parametrize("cell", sorted(c for c in CELLS if cell_file(c).get("control")))
def test_the_control_is_seen(cell):
    """The control that the cell's limits rest on, put in the program's
    place.  On the CPU the program reads rounding alone (its products are
    true float32 there), so the control has to read a thousand times the
    program's on a compared number; whether it passes the cell's own limit
    is a matter of the cell's own size, read on the chip."""
    spec = cell_file(cell)
    control = {"gram_high": "gram_bf16"}.get(spec["control"], spec["control"])
    for program, reading in control_readings(cell, control):
        assert all(program[name] <= limit for name, limit in spec["limits"].items()), program
        assert any(reading[name] > 1000 * max(program[name], 1e-9) for name in spec["limits"]), (
            program, reading)


def test_the_sharded_solve_fails_a_one_pass_gramian_at_its_own_limit():
    spec = cell_file("bwls-fv.solve-4chip")
    for _, reading in control_readings("bwls-fv.solve-4chip", "gram_bf16"):
        assert reading["w0_relative_error"] > spec["limits"]["w0_relative_error"]


def test_the_timit_adapters_graph_is_the_entrys_without_its_argmax():
    """``adapters/timit.py`` composes ``TimitPipeline.build``'s graph up to
    its raw scores.  Fitted on the same rows, the entry itself predicts the
    argmax of those scores on every held-out row: a later change to the entry
    that the adapter does not follow shows here."""
    code = """
import sys, json, numpy as np
sys.path.insert(0, %r)
from benchmark import harness
_, cell, cfg = harness.open_cell("timit-rf.fit", rehearse=True)
adapter = harness.load_module("adapters", cfg["adapter"])
fit_loop = harness.load_module("drivers", cell["kind"])
data = adapter.make_data(cfg, cell, 7)
x, labels = adapter.fit_inputs(data, cell, 0)
ours = adapter.build(cfg, cell, 7, *fit_loop.upload(x, labels, "ours")).fit()
entry = adapter.build_entry(cfg, cell, 7, *fit_loop.upload(x, labels, "entry")).fit()
scores = adapter.held_out_answers(ours, data["held_x"])
classes = adapter.held_out_answers(entry, data["held_x"])
print(json.dumps({"same": bool(np.array_equal(np.argmax(scores, axis=1), classes)),
                  "rows": int(classes.shape[0]), "classes": int(len(set(classes.tolist())))}))
""" % ROOT
    env = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env,
                          cwd=ROOT, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    assert got["same"] and got["rows"] == 64 and got["classes"] > 1, got
