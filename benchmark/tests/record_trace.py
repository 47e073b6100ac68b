#!/usr/bin/env python3
"""Record a short traced window of one cell on the chip and write what the
reduction reads: the flattened events (``trace_reduce.load_xplane``) as JSON,
the names of every plane and line, and the reduction itself.  This is how
``tests/data/recorded_trace.json`` was made (cut by hand to two solves).

    python3 benchmark/tests/record_trace.py --workload <cell> --seconds 2 --out chiprun_out/trace
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import harness, trace_reduce  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--out", required=True)
    p.add_argument("--max-events", type=int, default=4000,
                   help="keep the first N events by start time (what comes back is capped)")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    _, cell, cfg = harness.open_cell(args.workload, args.rehearse)
    import jax
    from jax.profiler import ProfileData

    harness.compile_cache_dir(args.rehearse)
    driver, ref, _ = harness.make_driver(
        cell, cfg, args.seed,
        lambda name: jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name),
    )
    driver.setup(ref)
    logdir = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(logdir, profiler_options=harness.trace_options())
    try:
        counters = driver.window(args.seconds)
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(logdir)
    os.makedirs(args.out, exist_ok=True)
    layout = {}
    for plane in ProfileData.from_file(path).planes:
        layout[plane.name] = {
            line.name: [len(list(line.events)), sorted({e.name for e in line.events})[:40]]
            for line in plane.lines
        }
    samples = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            for e in line.events:
                key = trace_reduce.stable_name(e.name)
                if key not in samples and len(samples) < 400:
                    samples[key] = {"line": line.name, "name": e.name[:300],
                                    "stats": {k: str(v)[:200] for k, v in e.stats}}
    with open(os.path.join(args.out, f"{cell['name']}.samples.json"), "w") as f:
        json.dump(samples, f, indent=1)
    events = trace_reduce.load_xplane(path)
    reduction = trace_reduce.reduce(events)
    kept = sorted(events, key=lambda e: e["start_ns"])[: args.max_events]
    with open(os.path.join(args.out, f"{cell['name']}.layout.json"), "w") as f:
        json.dump(layout, f, indent=1)
    with open(os.path.join(args.out, f"{cell['name']}.events.json"), "w") as f:
        json.dump(kept, f)
    with open(os.path.join(args.out, f"{cell['name']}.reduction.json"), "w") as f:
        json.dump({"counters": counters, "xplane_bytes": os.path.getsize(path),
                   "reduction": reduction}, f, indent=1)
    print(json.dumps({"xplane_bytes": os.path.getsize(path), "events": len(events),
                      "counters": counters,
                      "reduction": {k: v for k, v in reduction.items()
                                    if k not in ("op_s", "module_s")}}))
    shutil.rmtree(logdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
