"""The batched finish_cost kernel's share of its roofline (%).

Memory-bound: the least time is the bytes the traced calls move (86 per
padded lane, ``bench/device.py``) over the chip's peak HBM bandwidth; the
share is that over the summed device time of the kernel's events in the
trace.  Nothing is read when the trace saw a different number of kernel
calls than the benchmark made while it ran.
"""


def read(run):
    tr, peaks = run.trace, run.peaks
    if not tr or not peaks or not tr.get("kernel_bytes") or not tr["kernel_s"]:
        return None
    least_s = tr["kernel_bytes"] / float(peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / tr["kernel_s"]
