"""queue_wait_ms: mean time from enqueue to admission into a rollout
(the async tier's ``queue_s``) of the requests due in the window's
untraced part that were answered."""


def read(run):
    waits = [r.queue_s for r in run.in_window(run.untraced)
             if r.status == "ok"]
    return 1e3 * sum(waits) / len(waits) if waits else None
