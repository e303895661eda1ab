"""latency_p50_ms: the 50th percentile (nearest rank), over every request
due in the window, of the time from its due time to its answer.  A
request that failed or never came back counts as missing every limit
(its latency runs to when the harness stopped waiting)."""

import math


def read(run):
    lat = run.latencies()
    if not lat:
        return None
    return 1e3 * lat[max(0, math.ceil(0.5 * len(lat)) - 1)]
