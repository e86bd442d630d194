"""descent_roofline (%): the tree descent kernel's least time at the HBM
bound (the work's bytes, chipbench/work.py, over the chip's HBM bandwidth)
over its time in the trace, summed over the window's executions."""


def read(run):
    from chipbench.peaks import peaks
    if run.trace is None or not run.trace.executions:
        return None
    seconds = run.trace.kernel_seconds(run.kernel_roles).get("descent", 0.0)
    work = run.work()
    if seconds <= 0 or len(work) != len(run.trace.executions):
        return None
    least = sum(d for d, _ in work) / peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / seconds
