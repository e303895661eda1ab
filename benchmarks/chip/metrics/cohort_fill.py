"""cohort_fill: real requests over bucket slots, over the engine's steps
in the window's untraced part (the engine's own slot counters)."""


def read(run):
    slots = run.counters.get("slots", 0)
    if not slots:
        return None
    return 100.0 * (slots - run.counters["padded"]) / slots
