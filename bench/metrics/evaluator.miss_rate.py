"""Share of evaluator lookups that missed its cost memo (%).

From the program's counters ``evaluator.evaluations`` over
``evaluator.lookups``, summed over the searches that completed.
"""


def read(run):
    lookups = run.counters.get("evaluator.lookups", 0)
    if not lookups:
        return None
    return 100.0 * run.counters.get("evaluator.evaluations", 0) / lookups
