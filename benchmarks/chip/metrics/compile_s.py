"""compile_s: seconds the program spent building its bucket executables
during set-up (process start to the window), the sum of its ``compile``
phase spans there; with a warm persistent cache, mostly loading them."""

from chipbench import phases


def read(run):
    w0 = run.window[0]
    got = phases.spans(w0 - run.setup_s, w0)
    compiles = [s.t1 - s.t0 for s in got or () if s.phase == "compile"]
    return sum(compiles) if compiles else None
