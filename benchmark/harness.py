"""The harness: driven by data.  A cell is an entry of ``BENCHMARK.json``'s
``workloads``; everything that belongs to one configuration, one traffic
mix, one kind of window or one per-layer metric is a file of its own, found
by name:

    configs/<config>.json     sizes, source, cuts, the adapter and reference
    traffic/<traffic>.json    the cell's parameters, the kind of its window,
                              the limits of its check
    adapters/<adapter>.py     data, the program's entry, its answers, its ops
    drivers/<kind>.py         the window: ``class Driver``
    reference/<reference>.py  the plain reference
    layers/<metric>.py        ``read(ctx)`` -> number, or None where there is
                              nothing to read; a quantity split by the
                              end-to-end metric it moves (``<quantity>.<cells>``)
                              has the one reader ``layers/<quantity>.py``

so a later PR adds cells, configurations, kinds and metrics as new files
and new entries of ``BENCHMARK.json`` and edits nothing that is here.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# ----------------------------------------------------------------- finding
def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str, here: str = HERE):
    """The module ``<here>/<kind>/<name>.py``.  Names may hold ``.`` and
    ``-`` (a metric's name is its file's), so it is loaded by path."""
    path = os.path.join(here, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_').replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(name: str, root: str = ROOT, here: str = HERE) -> tuple:
    """(cell parameters, configuration) of the workload ``name``."""
    bench = load_json(root, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = load_json(here, "traffic", entry["traffic"] + ".json")
    cell.update(name=name, chips=entry["chips"], config=entry["config"])
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cfg = load_json(root, conf["file"])
    return bench, cell, cfg


def metrics_of(bench: dict, cell_name: str, group: str) -> list:
    return [
        m for m in bench[group]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def load_reader(metric: str, here: str = HERE):
    """The reader of a per-layer metric: ``layers/<metric>.py``, or, for a
    quantity that is split because its cells report different end-to-end
    metrics (``device_idle_pct.fit``, ``device_idle_pct.score``), the one
    reader of the quantity, ``layers/device_idle_pct.py``."""
    quantity = metric.rsplit(".", 1)[0]
    if not os.path.isfile(os.path.join(here, "layers", metric + ".py")) and os.path.isfile(
        os.path.join(here, "layers", quantity + ".py")
    ):
        metric = quantity
    return load_module("layers", metric, here)


def read_layers(bench: dict, cell: dict, ctx) -> dict:
    """Each per-layer metric of the cell through its reader; a reader that
    finds nothing to read returns None and its metric is left out."""
    out = {}
    for m in metrics_of(bench, cell["name"], "per_layer"):
        value = load_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def open_cell(name: str, rehearse: bool = False) -> tuple:
    """(BENCHMARK.json, cell, configuration) of the workload ``name``.  A
    rehearsal (the builder's CPU run; call this before JAX starts) pins the
    CPU with as many virtual devices as the cell has chips and takes the toy
    sizes of the files' own ``rehearse`` sections."""
    bench, cell, cfg = find_cell(name)
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = f"--xla_force_host_platform_device_count={cell['chips']}"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
        cfg.update(cell.get("rehearse", {}).get("config", {}))
        cell.update(cell.get("rehearse", {}).get("cell", {}))
    return bench, cell, cfg


def make_driver(cell: dict, cfg: dict, seed: int, span) -> tuple:
    """(driver, reference module, devices) of an opened cell."""
    import jax

    devices = jax.devices()[: cell["chips"]]
    module = load_module("drivers", cell["kind"])
    driver = module.Driver(
        cell, cfg, load_module("adapters", cell.get("adapter", cfg["adapter"])), seed,
        devices, span,
    )
    return driver, load_module("reference", cfg["reference"]), devices


# ------------------------------------------------------------------ device
def device_dict() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def compile_cache_dir(rehearse: bool = False) -> str:
    """Where ``JAX_COMPILATION_CACHE_DIR`` says; unset, one fixed directory
    inside the checkout (the path is part of the cache's key)."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if rehearse:  # CPU programs never reach the cache that the chip reads
        jax.config.update("jax_enable_compilation_cache", False)
        return ""
    if not jax.config.jax_compilation_cache_dir:
        path = os.path.join(ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    return jax.config.jax_compilation_cache_dir


def trace_options():
    """The device trace and the benchmark's own host spans; no Python call
    tracer (it logged 195,000 calls in 2 s of a fit and slows the host)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    return options


# --------------------------------------------------------------------- run
def run(args, t0: float) -> int:
    bench, cell, cfg = open_cell(args.workload, args.rehearse)

    import jax

    from benchmark import compile_log, trace_reduce

    device = device_dict()
    peaks_table = load_json(HERE, "peaks.json")["peaks"]
    if not args.rehearse:
        if device["platform"] != "tpu" or device["kind"] not in peaks_table:
            sys.stderr.write(
                f"benchmark: needs a TPU listed in peaks.json, found {device}; "
                "there is no CPU fallback (--rehearse is the builder's toy run)\n"
            )
            return 2
        if device["count"] < cell["chips"]:
            sys.stderr.write(
                f"benchmark: {cell['name']} needs {cell['chips']} chips, found {device}\n"
            )
            return 2
    compile_cache_dir(args.rehearse)
    log = compile_log.CompileLog().install()

    tracing = bool(args.trace)
    span = (
        (lambda name: jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name))
        if tracing else (lambda name: contextlib.nullcontext())
    )
    driver, ref, devices = make_driver(cell, cfg, args.seed, span)

    # ---- set-up: data, compile or cache load, one warm-up of the cell's shapes
    warm = driver.setup(ref)
    seconds = float(args.seconds)
    if tracing:
        seconds = min(seconds, float(cell.get("trace_seconds", 10.0)))
    after_setup = log.snapshot()
    setup_s = time.perf_counter() - t0

    # ---- the window
    logdir = None
    if tracing:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(logdir, profiler_options=trace_options())
    try:
        with span("window"):  # the traced window is the host's, idle start and end included
            counters = driver.window(seconds)
    finally:
        if tracing:
            jax.profiler.stop_trace()
    in_window = compile_log.delta(log.snapshot(), after_setup)
    peak = memory_peak(devices)

    # ---- what the window produced, then the program's state goes
    answers = driver.answers()
    e2e = driver.metrics(counters)
    e2e["setup_s"] = setup_s
    ops = driver.ops()
    driver.release()

    reduction = None
    if tracing:
        try:
            reduction = trace_reduce.reduce(
                trace_reduce.load_xplane(trace_reduce.find_xplane(logdir))
            )
        finally:
            shutil.rmtree(logdir, ignore_errors=True)

    # ---- the check: the plain reference, once, on the freed device
    limits = cell["limits"]
    readings = driver.compare(answers, driver.reference(ref, "highest", answers))
    checks = {
        name: {"value": readings.get(name, float("inf")), "limit": limit}
        for name, limit in limits.items()
    }
    # nothing may compile inside the window (reported; a cold checkout's
    # first run compiles in set-up, never here)
    checks["compiles_in_window"] = {
        "value": in_window["requests"] - in_window["hits"], "limit": 0
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    # ---- the result
    if tracing:
        ctx = types.SimpleNamespace(  # what a per-layer reader may read
            trace=reduction, counters=counters, compiles_window=in_window,
            compiles_setup=after_setup, ops=ops, e2e=e2e, cell=cell, cfg=cfg,
            chips=cell["chips"],
            # a rehearsal runs the readers for their code only (any peaks do:
            # what they return is thrown away below)
            peaks=peaks_table.get(device["kind"]) or next(iter(peaks_table.values())),
        )
        metrics = read_layers(bench, cell, ctx)
    else:
        metrics = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in metrics_of(bench, cell["name"], "end_to_end")
        }
    device["memory_peak_bytes"] = peak
    result = {
        "correct": bool(correct),
        "attempted": int(counters["units"]),
        "failed": int(counters["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if tracing and reduction and reduction.get("devices"):
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = {
            "device_ops": reduction["device_ops"], "idle_gaps": reduction["idle_gaps"],
        }
    result["window"] = {"seconds": counters["elapsed"], "unit": driver.unit,
                        "setup": {k: v for k, v in warm.items() if k != "warm_fit_s"},
                        "warm_unit_s": warm["warm_fit_s"], **{
        k: v for k, v in counters.items() if k not in ("units", "elapsed", "failed")}}
    if args.rehearse:
        # a rehearsal never prints a device metric: counts and the check only
        result["metrics"] = {}
        result["rehearsal"] = True
    result["checks"] = checks  # each number compared beside its limit, last
    for name, c in checks.items():
        sys.stderr.write(f"check {name}: value {c['value']!r} limit {c['limit']!r}\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(argv, t0=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="builder's CPU rehearsal: toy sizes, no device metric")
    args = p.parse_args(argv)
    return run(args, time.perf_counter() if t0 is None else t0)
