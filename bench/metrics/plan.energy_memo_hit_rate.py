"""Share of plan energy terms read from their subgraph's memo.

The program's counters ``plan.energy_terms`` (the terms ``PlanCost.energy_pj``
summed) and ``plan.energy_term_misses`` (those it computed, the rest being
read memoized at their hardware point; ``core/cost.py``), both added once
per plan, over the window: 100 x (1 - misses / terms).  A program without
the counters reads nothing.
"""


def read(run):
    terms = run.counters.get("plan.energy_terms")
    misses = run.counters.get("plan.energy_term_misses")
    if terms is None or misses is None or not terms:
        return None
    return 100.0 * (1.0 - misses / terms)
