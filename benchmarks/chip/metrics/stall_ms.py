"""stall_ms: the longest single span of the serving worker, of any
phase, that started in the window's untraced part (the program's phase
spans), leaving out the takes that yielded no cohort: those are the
worker idle, waiting up to ``idle_wait_s`` for arrivals, which a pause
of the load generator would lengthen.  Notes which phase, cohort and
bucket it was."""

from chipbench import phases


def read(run):
    got = [s for s in phases.untraced(run) or ()
           if s.phase != "take" or s.cohort >= 0]
    if not got:
        return None
    s = max(got, key=lambda s: s.t1 - s.t0)
    run.notes.append(
        f"stall_ms: longest worker span {s.phase}, cohort {s.cohort}, "
        f"bucket {s.bucket} ({s.n} rows), {1e3 * (s.t1 - s.t0)!r} ms, "
        f"{s.t0 - run.window[0]!r} s into the window")
    return 1e3 * (s.t1 - s.t0)
