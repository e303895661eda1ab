"""packed_roofline: the packed layers' kernels against their roofline.
Sum over the forwards executed in the traced window of the least time
the chip needs for the packed layers' work (chipbench.ops.packed_least_s:
per layer the larger of int8 operations over peak and bytes over HBM
bandwidth, over the rows each chip was dispatched), over the device
time of the Pallas kernel events inside those forwards.  Read only where
the cell serves one bucket, so every forward's row count is known."""

from chipbench import ops


def read(run):
    buckets = run.cell.traffic["buckets"]
    if run.trace is None or len(buckets) != 1:
        return None
    fwds = run.trace["forwards"]
    pallas_s = sum(f["pallas_s"] for f in fwds)
    if not fwds or pallas_s <= 0:
        return None
    rows = buckets[0] // (run.chips if run.cell.traffic.get("data_parallel")
                          else 1)
    least = ops.packed_least_s(run.cell.conf, rows, run.peaks)
    run.notes.append(
        f"packed_roofline: {least['compute_bound']} packed layers bound "
        f"by int8 operations, {least['memory_bound']} by HBM bytes; least "
        f"{least['seconds'] * 1e3:.4f} ms per forward of {rows} rows")
    return 100.0 * len(fwds) * least["seconds"] / pallas_s
