"""Mean host wall time to put one device call in flight (ms).

From the program's spans: for each ``executor.finish`` span, its
``executor.put`` (padding, enqueueing the 7 input arrays' transfer) and
``executor.run`` (dispatching the kernel) children, averaged over the calls
that recorded both.  Nothing in the call waits for the device before its
``executor.fetch``.
"""


def read(run):
    spans = run.spans
    calls = {}
    for sp in spans:
        if (sp.name in ("executor.put", "executor.run") and sp.parent >= 0
                and spans[sp.parent].name == "executor.finish"):
            calls.setdefault(sp.parent, []).append(sp.dur_s)
    done = [sum(durs) for durs in calls.values() if len(durs) == 2]
    if not done:
        return None
    return 1e3 * sum(done) / len(done)
