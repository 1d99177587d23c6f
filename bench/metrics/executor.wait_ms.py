"""Mean host wall time one device call waits for its results (ms).

From the program's ``executor.fetch`` spans: from the kernel's dispatch
until its 9 output arrays are NumPy arrays on the host, which takes in
what of the transfer in, the kernel and the transfer out the dispatch did
not overlap.
"""


def read(run):
    fetches = [sp.dur_s for sp in run.spans if sp.name == "executor.fetch"]
    if not fetches:
        return None
    return 1e3 * sum(fetches) / len(fetches)
