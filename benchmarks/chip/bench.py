#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once, from the root of a
checkout (see ``BENCHMARK.json`` and ``chipbench/harness.py``):

    python3 benchmarks/chip/bench.py --workload vgg16_w4_fused.bulk \
        --seed 1 --seconds 10 --trace 0

Needs a TPU: without one, or without the program next to it, it exits
non-zero and prints no result.
"""

import time

T_START = time.perf_counter()      # set-up is timed from here

import sys                         # noqa: E402
from pathlib import Path           # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness      # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
