#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip:

    python3 benchmarks/chip/sweep.py --workload vgg16_w4_fused.knee80 \
        --rates 800 1200 1600 2000 --seconds 8 --seed 1 --repeats 3

One process sets the cell up once, then offers each rate ``--repeats``
times for ``--seconds`` (after one second of the same rate to settle),
repeat ``i`` with the arrival order of seed ``--seed + i``, and prints
one JSON line per offer: offered and completed rate, latency from the
due time at p50/p95 over the first and the last third of the run, and
the queue left at the end.  An offer is sustained when the completed
rate keeps up (98%) and the last third's p95 is under twice the first's
(no growing backlog); a rate is sustained when most of its offers are,
and the knee is the highest rate that was sustained together with every
lower rate tried.  Write 0.8 x the knee into the cell's traffic file as
``rate_per_s``.
"""

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness, spec, traffic  # noqa: E402


def _pct(xs, q):
    xs = sorted(xs)
    return xs[max(0, math.ceil(q * len(xs)) - 1)] if xs else float("nan")


def _offer(aeng, images, rate: float, seconds: float, seed: int) -> bool:
    """Offer ``rate`` once; print its line and say whether it held."""
    t0 = time.perf_counter() + 0.05
    t1 = t0 + 1.0 + seconds
    gc.collect()
    gc.disable()            # as the harness does for its traffic
    try:
        recs = traffic.run_open(aeng.submit, images, rate, t0, t1, seed)
    finally:
        gc.enable()
    w0 = t0 + 1.0
    due = [r for r in recs if r.due >= w0]
    third = seconds / 3
    lat = lambda rs: [r.done - r.due for r in rs]   # noqa: E731
    first = lat([r for r in due if r.due < w0 + third])
    last = lat([r for r in due if r.due >= t1 - third])
    done = [r for r in recs if w0 <= r.done <= t1]
    row = {"rate_per_s": rate, "seed": seed,
           "completed_per_s": len(done) / seconds,
           "p50_ms": 1e3 * _pct(lat(due), 0.5),
           "p95_ms": 1e3 * _pct(lat(due), 0.95),
           "p95_first_third_ms": 1e3 * _pct(first, 0.95),
           "p95_last_third_ms": 1e3 * _pct(last, 0.95),
           "failed": sum(r.status != "ok" for r in due)}
    row["sustained"] = (row["completed_per_s"] >= 0.98 * rate
                        and row["p95_last_third_ms"]
                        < 2 * row["p95_first_third_ms"]
                        and row["failed"] == 0)
    print(json.dumps(row), flush=True)
    return row["sustained"]


def main() -> int:
    ap = argparse.ArgumentParser(description="Knee sweep of an open loop.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=1)
    args = ap.parse_args()

    cell = spec.cell(args.workload)
    harness.start_chip(cell)
    _, images, aeng = harness.setup(cell, args.seed)
    knee, broken = None, False
    try:
        for rate in sorted(args.rates):
            held = [_offer(aeng, images, rate, args.seconds, args.seed + i)
                    for i in range(args.repeats)]
            if 2 * sum(held) > len(held) and not broken:
                knee = rate
            else:
                broken = True
    finally:
        aeng.close()
    print(json.dumps({"knee_per_s": knee,
                      "rate_at_0.8": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
