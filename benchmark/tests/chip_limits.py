#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from (PERF.md,
"How correct is decided"), in ONE process on the chip so that set-up is
paid once:

    python3 benchmark/tests/chip_limits.py --workload <cell> --seeds 1,2,3 \
        [--controls gram_high,gram_bf16] [--faults half_batch,answer_altered] \
        [--seconds 3] [--out chiprun_out/limits.jsonl]

For each seed: the cell's own set-up and a short window at the cell's own
size, the program's answers against the plain reference (the LOWER reading),
for each control precision the reference computed at that precision put
in the program's place, and for each fault of ``faulty_run.py`` the program
with that fault planted, set up and run again on the same seed (the UPPER
readings), all through the harness's own comparison.  One JSON line per seed.  The
program's own lower-precision path is read by running this with
``KEYSTONE_SOLVER_PRECISION=default`` in the environment: the "program"
readings are then that control's.  The benchmark's own runs never run this.
"""

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import harness  # noqa: E402
from benchmark.tests import faulty_run  # noqa: E402


#: the reference with its products a step below what the configurations
#: state, put in the program's place
CONTROLS = {
    # the solver's Gramian and cross term, float32 at `highest`, a step down
    # (`high`, three bf16 passes) and two (one bf16 pass: what the program's
    # own KEYSTONE_SOLVER_PRECISION=default path does); the rest as the
    # program runs it, at the MXU's default
    "gram_high": {"solver": "high", "other": "bfloat16"},
    "gram_bf16": {"solver": "bfloat16", "other": "bfloat16"},
    # the bf16 descriptor streams of the image featurizer (and every other
    # product that the MXU's default runs as one bf16 pass) at fp8, the
    # solver at `high`
    "all_lower": {"solver": "high", "other": "fp8"},
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", default="gram_high,gram_bf16")
    p.add_argument("--faults", default="")
    p.add_argument("--control-seeds", type=int, default=3,
                   help="read the controls and the faults on the first N seeds only")
    p.add_argument("--seconds", type=float, default=3.0)
    p.add_argument("--out", default="")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    _, cell, cfg = harness.open_cell(args.workload, args.rehearse)
    harness.compile_cache_dir(args.rehearse)
    no_span = lambda name: contextlib.nullcontext()  # noqa: E731
    out = open(args.out, "a") if args.out else None

    def program(seed, cell=cell):
        driver, ref, devices = harness.make_driver(cell, cfg, seed, no_span)
        warm = driver.setup(ref)
        counters = driver.window(args.seconds)
        peak = harness.memory_peak(devices)
        answers = driver.answers()
        driver.release()
        return driver, ref, warm, counters, peak, answers

    for number, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        driver, ref, warm, counters, peak, answers = program(seed)
        t1 = time.perf_counter()
        want = driver.reference(ref, "highest", answers)
        t2 = time.perf_counter()
        line = {
            "workload": cell["name"], "seed": seed,
            "solver_precision_env": os.environ.get("KEYSTONE_SOLVER_PRECISION", ""), "device": harness.device_dict(),
            "units": counters["units"], "unit_s": counters["elapsed"] / counters["units"],
            "warm_unit_s": warm["warm_fit_s"], "memory_peak_bytes": peak,
            "program_s": t1 - t0, "reference_s": t2 - t1,
            "program": driver.compare(answers, want), "control": {}, "fault": {},
        }
        also = number < args.control_seeds
        for name in [c for c in args.controls.split(",") if c and also]:
            got = driver.reference(ref, CONTROLS[name], answers)
            line["control"][name] = driver.compare(driver.as_answers(got, answers), want)
        for name in [f for f in args.faults.split(",") if f and also]:
            mend = faulty_run.plant(name)
            try:  # the same seed, so the same rows and the same reference; another
                # name, so that nothing the program keeps of the sound run answers
                broken = program(seed, dict(cell, name=f"{cell['name']}+{name}"))[-1]
                line["fault"][name] = driver.compare(broken, want)
            finally:
                mend()
        del driver, answers, want
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
