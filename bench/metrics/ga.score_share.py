"""Share of GA generation time spent costing plans on the host (%).

From the program's spans: the ``ga.score`` spans (``plan_batch`` and the
objective of each plan) less the ``evaluate_batch`` spans inside them, over
the ``ga.generation`` spans.
"""

from bench.phase_spans import host_share


def read(run):
    return host_share(run, "ga.score")
