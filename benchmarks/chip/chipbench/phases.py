"""The serving worker's phase spans, as the program records them: one
``PhaseSpan(phase, t0, t1, cohort, bucket, n)`` per phase per forward in
its registry's phase ring (``repro.obs.MetricsRegistry.phases``,
``perf_counter`` seconds, the harness's clock), recorded whether or not
the registry is enabled.  The engine builds its registry from the
process default, and the harness passes none, so the readers of the
``program_span`` metrics read the default's ring after the window.

A reader takes the spans that started in the window's untraced part,
where no profiler ran, and ended in it too: starting the profiler stalls
the process for tens of milliseconds, which a span still open then
would count.  Where the program keeps no phase ring, or the ring no
longer holds the whole part, it reads nothing."""

# the worker's host work per forward: everything in its loop but waiting
# for arrivals (take) and for the device (wait)
HOST = ("fill", "dispatch", "readback", "resolve")


def spans(t0: float, t1: float):
    """The phase spans that started in [t0, t1), by start, or None."""
    from repro import obs

    read = getattr(obs.default_registry(), "phases", None)
    if read is None:
        return None
    got, complete = read(t0, t1)
    return got if complete else None


def untraced(run):
    """The phase spans inside the window's untraced part, or None."""
    if not run.untraced:
        return None
    t0, t1 = run.untraced
    got = spans(t0, t1)
    return None if got is None else [s for s in got if s.t1 <= t1]


def ms_per_forward(got, phases) -> float:
    """Mean over the forwards dispatched in ``got`` of the milliseconds
    spent in ``phases``, or None where none was dispatched."""
    forwards = {s.cohort for s in got if s.phase == "dispatch"}
    if not forwards:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in got
                     if s.phase in phases) / len(forwards)


def read_ms_per_forward(run, phases):
    got = untraced(run)
    return ms_per_forward(got, phases) if got else None
