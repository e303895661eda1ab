"""mfu: the whole step's share of the chips' int8 peak.  Useful operations
of the images answered in the window's untraced part (2 x the
benchmark's own dense MAC count of every layer over T; padded slots are
not useful), over its seconds x chips x peak.  The host clock times it,
with no profiler running."""


def read(run):
    t0, t1 = run.untraced
    done = len(run.completed_in((t0, t1)))
    if not done or t1 <= t0:
        return None
    peak = run.chips * run.peaks.int8_ops
    return 100.0 * done * run.ops_per_image / ((t1 - t0) * peak)
