"""Cycle-breaking splits of the partition repair per GA sample.

The program's counter ``normalize.cycle_splits`` (``core/partition.py``: the
groups ``normalize`` split to make a grouping's quotient acyclic) over the
window's GA samples, each generation's samples being the genomes of its
``ga.score`` span.

Both sides are the whole window's, the generation that the window's close
aborts included: its splits so far in the counter and, once its scoring
opened, its genomes in the denominator.  So the reading is off the
completed generations' ratio by at most one population's worth of samples
(500 in the ``oneshot`` cells).
"""


def read(run):
    splits = run.counters.get("normalize.cycle_splits")
    spans = run.spans
    if splits is None or not spans:
        return None
    samples = sum(sp.attrs["genomes"] for sp in spans
                  if sp.name == "ga.score" and sp.parent >= 0
                  and spans[sp.parent].name == "ga.generation")
    if not samples:
        return None
    return splits / samples
