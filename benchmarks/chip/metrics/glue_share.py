"""glue_share: device time of the operations inside the served forwards
that are not Pallas kernels (layout glue, the float stem and readout),
over the device's busy time in the traced window."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * run.trace["glue_s"] / run.trace["busy_s"]
