"""Share of GA generation time spent repairing genomes on the host (%).

From the program's spans: the ``ga.repair`` spans (the in-situ split
repair) less the ``evaluate_batch`` spans inside them, over the
``ga.generation`` spans.
"""

from bench.phase_spans import host_share


def read(run):
    return host_share(run, "ga.repair")
