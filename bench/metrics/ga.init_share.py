"""Share of GA search time spent building the initial population (%).

From the program's spans: the ``ga.init`` spans over the ``ga.init`` and
``ga.generation`` spans together.
"""


def read(run):
    init = [sp.dur_s for sp in run.spans if sp.name == "ga.init"]
    gens = sum(sp.dur_s for sp in run.spans if sp.name == "ga.generation")
    if not init or sum(init) + gens <= 0:
        return None
    return 100.0 * sum(init) / (sum(init) + gens)
