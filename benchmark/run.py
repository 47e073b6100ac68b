#!/usr/bin/env python3
"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell of BENCHMARK.json: set-up (data from the seed, compile
or cache load, one warm-up of the cell's own shapes), a measured window of
``--seconds``, the check against the plain reference, and one JSON object as
the last line of standard output.  See ``harness.py``.
"""

import os
import sys
import time

_T0 = time.perf_counter()  # set-up is counted from here
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=_T0))
