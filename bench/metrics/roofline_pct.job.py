"""A job's share of its roofline: the least time the chips could take (the
larger of operations over peak rate and bytes over peak bandwidth, from the
configuration's shapes) over the device busy time per job, averaged over
the cell's devices.  Taken over all device ops of the window (trace)."""


def read(run):
    if (run.kind != "job" or run.trace is None or not run.peaks
            or not run.traced_jobs):
        return None
    busy = run.trace.mean_busy_s() / run.traced_jobs
    if busy <= 0:
        return None
    ops, nbytes = run.work
    least = max(ops / (run.chips * run.peaks["flops_per_s"]),
                nbytes / (run.chips * run.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / busy
