"""Mean host wall time of one batched kernel call (ms).

The benchmark's timer around ``finish_cost_batch``: padding, transfer to
the device, the kernel, and the transfer back until NumPy results are in
hand.
"""


def read(run):
    calls = run.device_calls
    if not calls:
        return None
    return 1e3 * sum(c.t1 - c.t0 for c in calls) / len(calls)
