"""device_idle: share of the window in which no operation ran on the
device.  The profiler slows a busy host by a quarter or more but not
the device's work, so where the cell serves one bucket, and every
forward does the same work, the device time of a forward comes from the
trace (the union of device-op intervals over the traced part, averaged
over the cell's chips, per forward run inside it) and the number of
forwards from the engine's counter over the untraced part: 1 - busy
seconds per forward x forwards per second.  Where buckets are mixed,
forwards differ in device time and the profiler's host cost changes the
mix (fewer, fuller cohorts), so the traced part's share is read as
measured: 1 - busy seconds / traced seconds."""


def read(run):
    t = run.trace
    if t is None:
        return None
    if len(run.cell.traffic["buckets"]) != 1:
        return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
    forwards = run.counters.get("batches", 0)
    if not t["forwards"] or not forwards:
        return None
    chips = len({f["device"] for f in t["forwards"]})
    per_forward = t["busy_s"] * chips / len(t["forwards"])
    t0, t1 = run.untraced
    return 100.0 * (1.0 - per_forward * forwards / (t1 - t0))
