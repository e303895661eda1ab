"""host_ms_per_forward: the serving worker's host time per forward, the
mean over the forwards dispatched in the window's untraced part of
their fill + dispatch + readback + resolve phase spans (the program's
phase ring): its loop less the waits for arrivals (take) and for the
device (wait).  Also notes the share of the untraced part that the
worker's spans cover (summed over workers) and each phase's
milliseconds per forward."""

from chipbench import phases


def read(run):
    got = phases.untraced(run)
    if not got:
        return None
    host = phases.ms_per_forward(got, phases.HOST)
    if host is None:
        return None
    t0, t1 = run.untraced
    covered = sum(s.t1 - s.t0 for s in got) / (t1 - t0)
    seen = sorted({s.phase for s in got})
    run.notes.append(
        f"worker phase spans cover {100.0 * covered!r} % of the untraced "
        f"part ({len(got)} spans); ms per forward: " + ", ".join(
            f"{p} {phases.ms_per_forward(got, (p,))!r}" for p in seen))
    return host
