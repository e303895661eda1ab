#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip:

    python3 benchmarks/chip/control.py --workload vgg16_w4_fused.bulk \
        --seeds 101 102 ... --control-seeds 201 202 203 --seconds 3

For each ``--seeds`` seed, one run of the cell as configured (a short
window at the cell's own load) prints the numbers ``correct`` compares:
the largest of them is the lower reading.  For each ``--control-seeds``
seed, the control runs: the program with its own next lower width
switched on (W2 where the configuration states W4), compared with the
reference at the configuration's width; the smallest of those is the
upper reading.  One JSON line per run, all in one process.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness, spec  # noqa: E402

LOWER = {8: 4, 4: 2}


def main() -> int:
    ap = argparse.ArgumentParser(description="Correctness readings.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()

    cell = spec.cell(args.workload)
    harness.start_chip(cell)
    bits = cell.conf["bits"]
    runs = [(s, bits) for s in args.seeds] + \
        [(s, LOWER[bits]) for s in args.control_seeds]
    for seed, width in runs:
        c = spec.Cell(**{**cell.__dict__,
                         "conf": {**cell.conf, "bits": width}})
        run = harness.measure(c, seed, args.seconds, False,
                              time.perf_counter())
        checks = harness.check(run, bits=bits)
        print(json.dumps({"seed": seed, "program_bits": width,
                          "reference_bits": bits,
                          "notes": run.notes,
                          **{k: v["value"] for k, v in checks.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
