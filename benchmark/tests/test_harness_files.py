"""The harness is driven by data: a new configuration, cell, kind of window,
adapter, reference and per-layer metric come as NEW files and NEW entries of
BENCHMARK.json, with no edit to a file that is there.  Shown on a copy of the
benchmark in a temp directory; the new files import nothing of the program,
so the copy needs no ``keystone_tpu`` beside it."""

import json
import os
import shutil
import subprocess
import sys
import textwrap
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)

NEW_FILES = {
    "configs/toy-sum.json": json.dumps({
        "name": "toy-sum", "source": "none: a test's stand-in", "adapter": "toy_sum",
        "reference": "toy_sum", "width": 8,
    }),
    "traffic/toy-sum.loop.json": json.dumps({
        "kind": "toy_loop", "why": "a test's stand-in", "rows": 16,
        "limits": {"sum_gap": 1e-6},
    }),
    "adapters/toy_sum.py": textwrap.dedent('''
        import numpy as np
        def make_data(cfg, cell, seed):
            return np.random.default_rng(seed).normal(size=(cell["rows"], cfg["width"]))
        def program(x):
            return x.sum(axis=1)
    '''),
    "reference/toy_sum.py": textwrap.dedent('''
        def row_sums(x):
            return [sum(row) for row in x.tolist()]
    '''),
    "drivers/toy_loop.py": textwrap.dedent('''
        import time
        import numpy as np
        class Driver:
            unit = "sums"
            def __init__(self, cell, cfg, adapter, seed, devices, span):
                self.cell, self.cfg, self.adapter, self.seed = cell, cfg, adapter, seed
            def setup(self, ref=None):
                self.x = self.adapter.make_data(self.cfg, self.cell, self.seed)
                return {"warm_fit_s": 0.001}
            def window(self, seconds):
                t0, units = time.perf_counter(), 0
                while time.perf_counter() - t0 < seconds:
                    self.last = self.adapter.program(self.x)
                    units += 1
                return {"units": units, "elapsed": time.perf_counter() - t0, "failed": 0}
            def metrics(self, counters):
                return {"sums_per_s": counters["units"] / counters["elapsed"]}
            def answers(self):
                return self.last
            def release(self):
                pass
            def reference(self, ref, precision="highest", answers=None):
                return np.asarray(ref.row_sums(self.x))
            @staticmethod
            def compare(answers, want):
                return {"sum_gap": float(np.max(np.abs(answers - want)))}
            def ops(self):
                return {}
    '''),
    "layers/toy_units.py": textwrap.dedent('''
        def read(ctx):
            return float(ctx.counters["units"])
    '''),
    "layers/toy_nothing_to_read.py": textwrap.dedent('''
        def read(ctx):
            return None
    '''),
}


@pytest.fixture()
def copy(tmp_path):
    before = {}
    shutil.copytree(HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for dirpath, _, names in os.walk(tmp_path / "benchmark"):
        for n in names:
            path = os.path.join(dirpath, n)
            before[path] = open(path, "rb").read()
    for rel, text in NEW_FILES.items():
        (tmp_path / "benchmark" / rel).write_text(text)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-sum", "source": "none", "reduced": [], "why": "test",
                             "file": "benchmark/configs/toy-sum.json"})
    bench["workloads"].append({"name": "toy-sum.loop", "config": "toy-sum",
                               "traffic": "toy-sum.loop", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "sums_per_s", "unit": "sums/s", "better": "higher",
                                "bound": 0.05, "source": "host_clock",
                                "workloads": ["toy-sum.loop"]})
    for name in ("toy_units", "toy_nothing_to_read"):
        bench["per_layer"].append({"name": name, "unit": "count", "better": "higher",
                                   "source": "program_counter", "layer": "test",
                                   "moves": "sums_per_s", "workloads": ["toy-sum.loop"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path, before


def run_copy(tmp_path, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"), "--workload", "toy-sum.loop",
         "--seed", "3000000001", "--seconds", "0.2", *extra],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300,
    )


def test_new_cell_config_driver_adapter_reference_and_layer_as_new_files(copy):
    tmp_path, before = copy
    done = run_copy(tmp_path, "--trace", "1", "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0
    assert line["checks"]["sum_gap"]["limit"] == 1e-6
    assert list(line)[-1] == "checks"
    # nothing that was there was edited
    for path, content in before.items():
        assert open(path, "rb").read() == content, path


def test_a_reader_with_nothing_to_read_is_left_out(copy):
    tmp_path, _ = copy
    ours = lambda: [m for m in sys.modules if m == "benchmark" or m.startswith("benchmark.")]
    saved = {m: sys.modules.pop(m) for m in ours()}  # the repo's own, put back below
    sys.path.insert(0, str(tmp_path))
    try:
        import importlib

        harness = importlib.import_module("benchmark.harness")
        assert harness.HERE == str(tmp_path / "benchmark")
        bench, cell, cfg = harness.find_cell("toy-sum.loop")
        assert cell["kind"] == "toy_loop" and cfg["adapter"] == "toy_sum"
        names = [m["name"] for m in harness.metrics_of(bench, "toy-sum.loop", "per_layer")]
        assert "toy_units" in names and "toy_nothing_to_read" in names
        assert "bcd_roofline" not in names  # another cell's metric
        ctx = types.SimpleNamespace(counters={"units": 4},
                                    compiles_setup={"requests": 3, "hits": 3})
        got = harness.read_layers(bench, cell, ctx)
        assert got["toy_units"] == {"value": 4.0, "unit": "count"}
        assert "toy_nothing_to_read" not in got
        with pytest.raises(FileNotFoundError):
            harness.load_module("layers", "no_such_metric")
    finally:
        sys.path.remove(str(tmp_path))
        for m in ours():
            sys.modules.pop(m)
        sys.modules.update(saved)


def test_without_a_chip_it_exits_nonzero_and_prints_no_result(copy):
    tmp_path, _ = copy
    done = run_copy(tmp_path, "--trace", "0")
    assert done.returncode != 0
    assert done.stdout.strip() == ""


def test_beside_only_its_own_files_a_real_cell_exits_nonzero(copy):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no program to measure."""
    tmp_path, _ = copy
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"), "--workload", "timit-rf.fit",
         "--seed", "1", "--seconds", "0.2", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300,
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""


def test_every_entry_of_benchmark_json_finds_its_files():
    sys.path.insert(0, ROOT)
    from benchmark import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        _, cell, cfg = harness.find_cell(w["name"])
        for kind, name in (("drivers", cell["kind"]), ("adapters", cfg["adapter"]),
                           ("reference", cfg["reference"])):
            assert os.path.isfile(os.path.join(HERE, kind, name + ".py"))
        assert cell["limits"]
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]).read)
    # a quantity split by the end-to-end metric it moves has one reader
    assert harness.load_reader("device_idle_pct.fit").__file__ == harness.load_reader(
        "device_idle_pct.score").__file__
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["source"] and peaks["peaks"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
