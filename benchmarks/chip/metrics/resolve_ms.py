"""resolve_ms: mean milliseconds of the serving worker's ``resolve`` phase per
forward dispatched in the window's untraced part (the program's phase
spans)."""

from chipbench import phases


def read(run):
    return phases.read_ms_per_forward(run, ("resolve",))
