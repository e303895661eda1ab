"""images_per_s: answers that came back ok inside the window, over the
window's seconds."""


def read(run):
    w0, w1 = run.window
    return len(run.completed_in()) / (w1 - w0)
