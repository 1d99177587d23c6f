"""Share of GA generation time spent making offspring (%).

From the program's spans: the ``ga.variation`` spans (crossover and
mutation) over the ``ga.generation`` spans.
"""


def read(run):
    var = [sp.dur_s for sp in run.spans if sp.name == "ga.variation"]
    gens = sum(sp.dur_s for sp in run.spans if sp.name == "ga.generation")
    if not var or gens <= 0:
        return None
    return 100.0 * sum(var) / gens
