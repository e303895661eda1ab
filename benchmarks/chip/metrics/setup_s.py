"""setup_s: process start to the first request of the window."""


def read(run):
    return run.setup_s
